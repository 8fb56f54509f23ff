"""Generation tests: KV-cache decode must equal a full re-forward.

The equivalence oracle: greedy-generate N tokens with the cached decode
loop, then re-run ``transformer.apply`` on each growing prefix and argmax
the last position — identical token streams required (same projections,
same RoPE positions, same masking).  This catches every cache bug class:
stale slots, off-by-one write positions, wrong decode positions, padding
leakage from ragged prompts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu import parallel
from cloud_tpu.models import generation, transformer


def _greedy_reference(params, prompt_tokens, prompt_lens, config, n_new):
    """Oracle: argmax-decode by re-running the full forward each step."""
    b, t_prompt = prompt_tokens.shape
    outs = []
    seqs = [
        list(np.asarray(prompt_tokens[i][: int(prompt_lens[i])]))
        for i in range(b)
    ]
    for _ in range(n_new):
        step_toks = []
        for i in range(b):
            toks = jnp.asarray(seqs[i], jnp.int32)[None, :]
            logits, _ = transformer.apply(params, toks, config, mesh=None)
            nxt = int(jnp.argmax(logits[0, -1]))
            seqs[i].append(nxt)
            step_toks.append(nxt)
        outs.append(step_toks)
    return np.asarray(outs).T  # [B, n_new]


class TestGreedyEquivalence:
    def test_cached_decode_matches_full_forward(self):
        config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
        params = transformer.init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(0)
        b, t_prompt, n_new = 3, 8, 6
        prompt = rng.integers(1, 255, (b, t_prompt)).astype(np.int32)
        # Ragged lengths, including one full-length row.
        lens = np.asarray([3, 8, 5], np.int32)

        got = generation.generate(
            params, jnp.asarray(prompt), jnp.asarray(lens), config,
            max_new_tokens=n_new,
            sample=generation.SampleConfig(temperature=0.0),
        )
        want = _greedy_reference(params, prompt, lens, config, n_new)
        np.testing.assert_array_equal(np.asarray(got["tokens"]), want)

    def test_single_token_generation(self):
        """max_new_tokens=1: the decode scan never runs; the one token
        comes straight from prefill and matches the oracle."""
        config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
        params = transformer.init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(3)
        prompt = rng.integers(1, 255, (2, 6)).astype(np.int32)
        lens = np.asarray([4, 6], np.int32)
        got = generation.generate(
            params, jnp.asarray(prompt), jnp.asarray(lens), config,
            max_new_tokens=1,
            sample=generation.SampleConfig(temperature=0.0),
        )
        want = _greedy_reference(params, prompt, lens, config, 1)
        np.testing.assert_array_equal(np.asarray(got["tokens"]), want)
        np.testing.assert_array_equal(
            np.asarray(got["num_generated"]), [1, 1]
        )

    def test_sequences_stitched_at_true_offsets(self):
        config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
        params = transformer.init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(1)
        b, t_prompt, n_new = 2, 6, 4
        prompt = rng.integers(1, 255, (b, t_prompt)).astype(np.int32)
        lens = np.asarray([2, 6], np.int32)

        got = generation.generate(
            params, jnp.asarray(prompt), jnp.asarray(lens), config,
            max_new_tokens=n_new,
            sample=generation.SampleConfig(temperature=0.0),
        )
        seqs = np.asarray(got["sequences"])
        toks = np.asarray(got["tokens"])
        for i in range(b):
            li = int(lens[i])
            np.testing.assert_array_equal(seqs[i, :li], prompt[i, :li])
            np.testing.assert_array_equal(seqs[i, li:li + n_new], toks[i])


class TestSampling:
    def _setup(self):
        config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
        params = transformer.init(jax.random.PRNGKey(0), config)
        prompt = jnp.asarray([[5, 9, 17, 2]], jnp.int32)
        lens = jnp.asarray([4], jnp.int32)
        return config, params, prompt, lens

    def test_temperature_sampling_deterministic_under_key(self):
        config, params, prompt, lens = self._setup()
        out = [
            generation.generate(
                params, prompt, lens, config, max_new_tokens=5,
                sample=generation.SampleConfig(temperature=0.8, top_k=50),
                rng=jax.random.PRNGKey(7),
            )["tokens"]
            for _ in range(2)
        ]
        np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))

    def test_top_k_restricts_support(self):
        config, params, prompt, lens = self._setup()
        # top_k=1 must equal greedy regardless of temperature.
        topk1 = generation.generate(
            params, prompt, lens, config, max_new_tokens=5,
            sample=generation.SampleConfig(temperature=1.7, top_k=1),
            rng=jax.random.PRNGKey(3),
        )["tokens"]
        greedy = generation.generate(
            params, prompt, lens, config, max_new_tokens=5,
            sample=generation.SampleConfig(temperature=0.0),
        )["tokens"]
        np.testing.assert_array_equal(np.asarray(topk1), np.asarray(greedy))

    def test_top_p_zero_degenerates_to_greedy(self):
        """top_p=0.0 must keep the top token (not filter everything to
        -inf and sample garbage)."""
        config, params, prompt, lens = self._setup()
        top_p0 = generation.generate(
            params, prompt, lens, config, max_new_tokens=5,
            sample=generation.SampleConfig(temperature=1.3, top_p=0.0),
            rng=jax.random.PRNGKey(5),
        )["tokens"]
        greedy = generation.generate(
            params, prompt, lens, config, max_new_tokens=5,
            sample=generation.SampleConfig(temperature=0.0),
        )["tokens"]
        np.testing.assert_array_equal(np.asarray(top_p0), np.asarray(greedy))

    def test_top_p_one_keeps_full_support_and_runs(self):
        config, params, prompt, lens = self._setup()
        out = generation.generate(
            params, prompt, lens, config, max_new_tokens=4,
            sample=generation.SampleConfig(temperature=1.0, top_p=1.0),
            rng=jax.random.PRNGKey(11),
        )
        assert out["tokens"].shape == (1, 4)

    def test_eos_freezes_row(self):
        config, params, prompt, lens = self._setup()
        greedy = generation.generate(
            params, prompt, lens, config, max_new_tokens=6,
            sample=generation.SampleConfig(temperature=0.0),
        )["tokens"]
        # Use the 2nd greedy token as the "eos" so the row stops after 1.
        eos = int(np.asarray(greedy)[0, 1])
        stopped = generation.generate(
            params, prompt, lens, config, max_new_tokens=6,
            sample=generation.SampleConfig(
                temperature=0.0, eos_id=eos, pad_id=0
            ),
        )
        toks = np.asarray(stopped["tokens"])[0]
        np.testing.assert_array_equal(toks[0], np.asarray(greedy)[0, 0])
        assert toks[1] == eos  # the eos itself is emitted...
        assert (toks[2:] == 0).all()  # ...and everything after is pad
        assert int(stopped["num_generated"][0]) == 2  # incl. the eos

    def test_repetition_penalty_mechanism(self):
        """sample_logits: a seen token's positive logit is divided (and a
        negative one multiplied) by the penalty, demoting it below the
        runner-up; unseen tokens are untouched."""
        logits = jnp.asarray([[2.0, 1.0, 0.5], [-0.1, -2.0, -3.0]],
                             jnp.float32)
        seen = jnp.asarray([[True, False, False], [True, False, False]])
        cfg = generation.SampleConfig(
            temperature=0.0, repetition_penalty=100.0
        )
        picked = generation.sample_logits(None, logits, cfg, seen=seen)
        # Row 0: 2.0/100 < 1.0 -> runner-up; row 1: -0.1*100 < -2.0 -> idx 1.
        np.testing.assert_array_equal(np.asarray(picked), [1, 1])
        # Without the seen mask, argmax is unchanged.
        picked = generation.sample_logits(None, logits, cfg)
        np.testing.assert_array_equal(np.asarray(picked), [0, 0])

    def test_repetition_penalty_end_to_end_distinct(self):
        """A huge penalty makes every greedy generated token distinct."""
        config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
        params = transformer.init(jax.random.PRNGKey(3), config)
        prompt = jnp.asarray([[7, 3, 11, 2]], jnp.int32)
        lens = jnp.asarray([4], jnp.int32)
        penalized = np.asarray(generation.generate(
            params, prompt, lens, config, max_new_tokens=8,
            sample=generation.SampleConfig(
                temperature=0.0, repetition_penalty=1e6
            ),
        )["tokens"])[0]
        assert len(set(penalized.tolist())) == 8  # all distinct

    def test_min_new_tokens_delays_eos(self):
        config, params, prompt, lens = self._setup()
        greedy = np.asarray(generation.generate(
            params, prompt, lens, config, max_new_tokens=6,
            sample=generation.SampleConfig(temperature=0.0),
        )["tokens"])
        eos = int(greedy[0, 0])  # make the FIRST greedy token the "eos"
        out = generation.generate(
            params, prompt, lens, config, max_new_tokens=6,
            sample=generation.SampleConfig(
                temperature=0.0, eos_id=eos, pad_id=0, min_new_tokens=3
            ),
        )
        toks = np.asarray(out["tokens"])[0]
        # eos masked out of indices 0-2: they hold real non-eos tokens.
        assert all(int(t) != eos for t in toks[:3])
        assert int(out["num_generated"][0]) >= 3

    def test_rng_required_for_sampling(self):
        config, params, prompt, lens = self._setup()
        with pytest.raises(ValueError, match="rng"):
            generation.generate(
                params, prompt, lens, config, max_new_tokens=2,
                sample=generation.SampleConfig(temperature=1.0),
            )

    def test_composed_filters_with_top_k_one_reduce_to_penalized_greedy(self):
        """top_k + top_p + repetition_penalty COMPOSED: with top_k=1 the
        pipeline must collapse to the penalized argmax regardless of
        temperature — penalty applies before the filters, top_k=1 leaves
        one candidate, and top_p must keep (not filter out) that lone
        survivor.  Catches ordering bugs between the three stages that
        exercising each alone cannot."""
        config, params, prompt, lens = self._setup()
        composed = generation.generate(
            params, prompt, lens, config, max_new_tokens=6,
            sample=generation.SampleConfig(
                temperature=1.7, top_k=1, top_p=0.9,
                repetition_penalty=1e6,
            ),
            rng=jax.random.PRNGKey(2),
        )["tokens"]
        penalized_greedy = generation.generate(
            params, prompt, lens, config, max_new_tokens=6,
            sample=generation.SampleConfig(
                temperature=0.0, repetition_penalty=1e6
            ),
        )["tokens"]
        np.testing.assert_array_equal(
            np.asarray(composed), np.asarray(penalized_greedy)
        )

    def test_composed_sampling_deterministic_and_well_formed(self):
        """The full stack at once (temperature + top_k + top_p +
        repetition_penalty + eos + min_new_tokens): reproducible under a
        fixed key and structurally valid output."""
        config, params, prompt, lens = self._setup()
        sample = generation.SampleConfig(
            temperature=0.8, top_k=50, top_p=0.9,
            repetition_penalty=1.3, eos_id=3, pad_id=0, min_new_tokens=2,
        )
        out = [
            generation.generate(
                params, prompt, lens, config, max_new_tokens=6,
                sample=sample, rng=jax.random.PRNGKey(9),
            )
            for _ in range(2)
        ]
        np.testing.assert_array_equal(
            np.asarray(out[0]["tokens"]), np.asarray(out[1]["tokens"])
        )
        toks = np.asarray(out[0]["tokens"])[0]
        num = int(out[0]["num_generated"][0])
        assert num >= 2  # min_new_tokens honored
        assert (toks[num:] == 0).all()  # pad after the generated span


class TestBeamSearch:
    def _setup(self, seed=0):
        config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
        params = transformer.init(jax.random.PRNGKey(seed), config)
        rng = np.random.default_rng(seed)
        prompt = rng.integers(1, 255, (3, 8)).astype(np.int32)
        lens = np.asarray([3, 8, 5], np.int32)
        return config, params, jnp.asarray(prompt), jnp.asarray(lens)

    def test_single_beam_equals_greedy(self):
        config, params, prompt, lens = self._setup()
        beam = generation.beam_search(
            params, prompt, lens, config, num_beams=1, max_new_tokens=6,
        )
        greedy = generation.generate(
            params, prompt, lens, config, max_new_tokens=6,
            sample=generation.SampleConfig(temperature=0.0),
        )
        np.testing.assert_array_equal(
            np.asarray(beam["tokens"]), np.asarray(greedy["tokens"])
        )

    def test_wider_beams_never_score_worse(self):
        """Beam-4's sum-logprob (no length penalty, no eos — fixed-length
        comparison) must be >= beam-1's for every prompt."""
        config, params, prompt, lens = self._setup(seed=1)
        s1 = generation.beam_search(
            params, prompt, lens, config, num_beams=1, max_new_tokens=5,
        )["scores"]
        s4 = generation.beam_search(
            params, prompt, lens, config, num_beams=4, max_new_tokens=5,
        )["scores"]
        assert (np.asarray(s4) >= np.asarray(s1) - 1e-5).all()

    def test_score_matches_rescoring(self):
        """The winning beam's score equals the sum of its tokens'
        log-probs under a full re-forward (the oracle for cache + beam
        bookkeeping together)."""
        config, params, prompt, lens = self._setup(seed=2)
        out = generation.beam_search(
            params, prompt, lens, config, num_beams=3, max_new_tokens=4,
            length_penalty=0.0,  # raw sum-logprob for the oracle compare
        )
        toks = np.asarray(out["tokens"])
        for i in range(toks.shape[0]):
            li = int(lens[i])
            seq = np.concatenate([np.asarray(prompt)[i, :li], toks[i]])
            logits, _ = transformer.apply(
                params, jnp.asarray(seq[None, :], jnp.int32), config,
                mesh=None,
            )
            lp = jax.nn.log_softmax(logits[0], axis=-1)
            # token j of the generation is predicted at position li-1+j.
            total = sum(
                float(lp[li - 1 + j, toks[i, j]])
                for j in range(toks.shape[1])
            )
            np.testing.assert_allclose(
                float(out["scores"][i]), total, rtol=1e-4, atol=1e-4
            )

    def test_eos_freezes_beam_and_pads(self):
        config, params, prompt, lens = self._setup(seed=3)
        greedy = np.asarray(generation.generate(
            params, prompt[:1], lens[:1], config, max_new_tokens=6,
            sample=generation.SampleConfig(temperature=0.0),
        )["tokens"])
        eos = int(greedy[0, 1])
        # length_penalty=0 (raw sums): the 2-token finished hypothesis
        # provably beats any longer continuation (log-probs only add
        # negative mass), so the eos-terminated beam must be returned.
        # (With a penalty > 0 a longer live beam may legitimately win on
        # average log-prob — that is beam search working as intended.)
        out = generation.beam_search(
            params, prompt[:1], lens[:1], config, num_beams=1,
            max_new_tokens=6, eos_id=eos, pad_id=0, length_penalty=0.0,
        )
        toks = np.asarray(out["tokens"])[0]
        assert toks[1] == eos
        assert (toks[2:] == 0).all()
        assert int(out["num_generated"][0]) == 2


    def test_finished_hypothesis_never_evicted(self):
        """Two-set property: the returned score is >= the penalized score
        of ANY hypothesis that finished during the search (here: the
        eos-at-step-1 one), even when live beams keep decoding."""
        config, params, prompt, lens = self._setup(seed=3)
        prompt, lens = prompt[:1], lens[:1]
        greedy = np.asarray(generation.generate(
            params, prompt, lens, config, max_new_tokens=2,
            sample=generation.SampleConfig(temperature=0.0),
        )["tokens"])
        eos = int(greedy[0, 1])
        # Penalized score of the known 2-token finished hypothesis.
        li = int(lens[0])
        seq = np.concatenate([np.asarray(prompt)[0, :li], greedy[0]])
        logits, _ = transformer.apply(
            params, jnp.asarray(seq[None, :], jnp.int32), config, mesh=None
        )
        lp = jax.nn.log_softmax(logits[0], axis=-1)
        fin_sum = float(lp[li - 1, greedy[0, 0]]) + float(
            lp[li, greedy[0, 1]]
        )
        fin_penalized = fin_sum / 2.0
        out = generation.beam_search(
            params, prompt, lens, config, num_beams=2,
            max_new_tokens=8, eos_id=eos, pad_id=0, length_penalty=1.0,
        )
        assert float(out["scores"][0]) >= fin_penalized - 1e-4


class TestShardedGeneration:
    def test_matches_unsharded_under_dp_tp_mesh(self):
        config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
        params = transformer.init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(2)
        prompt = rng.integers(1, 255, (4, 8)).astype(np.int32)
        lens = np.asarray([3, 8, 5, 6], np.int32)

        plain = generation.generate(
            params, jnp.asarray(prompt), jnp.asarray(lens), config,
            max_new_tokens=5,
            sample=generation.SampleConfig(temperature=0.0),
        )["tokens"]

        mesh = parallel.MeshSpec({"dp": 2, "fsdp": 2, "tp": 2}).build()
        with parallel.use_mesh(mesh):
            sharded = jax.jit(
                lambda p, t, l: generation.generate(
                    p, t, l, config, max_new_tokens=5,
                    sample=generation.SampleConfig(temperature=0.0),
                    mesh=mesh,
                )["tokens"]
            )(params, jnp.asarray(prompt), jnp.asarray(lens))
        np.testing.assert_array_equal(np.asarray(plain), np.asarray(sharded))

    def test_pp_rules_rejected(self):
        config = transformer.TINY
        params = transformer.init(jax.random.PRNGKey(0), config)
        mesh = parallel.MeshSpec({"pp": 2, "dp": 4}).build()
        rules = parallel.DEFAULT_RULES.extended(layers="pp")
        with parallel.use_mesh(mesh):
            with pytest.raises(ValueError, match="pp"):
                generation.generate(
                    params, jnp.zeros((2, 4), jnp.int32),
                    jnp.full((2,), 4, jnp.int32), config,
                    max_new_tokens=2, rules=rules, mesh=mesh,
                )


class TestInferenceGuards:
    """_check_inference_supported rejection paths: every inference entry
    point (generate, beam_search, and the public alias the serving
    engine validates through) must refuse the training-only pp and
    zigzag_sp layouts up front — not fail obscurely inside the scan."""

    def _pp_setup(self):
        config = transformer.TINY
        params = transformer.init(jax.random.PRNGKey(0), config)
        mesh = parallel.MeshSpec({"pp": 2, "dp": 4}).build()
        rules = parallel.DEFAULT_RULES.extended(layers="pp")
        return config, params, mesh, rules

    def _zigzag_setup(self):
        config = transformer.TINY.scaled(zigzag_sp=True)
        params = transformer.init(jax.random.PRNGKey(0), config)
        mesh = parallel.MeshSpec({"sp": 4}).build(jax.devices()[:4])
        return config, params, mesh

    def test_beam_search_rejects_pp(self):
        config, params, mesh, rules = self._pp_setup()
        with parallel.use_mesh(mesh):
            with pytest.raises(ValueError, match="pp"):
                generation.beam_search(
                    params, jnp.zeros((2, 4), jnp.int32),
                    jnp.full((2,), 4, jnp.int32), config,
                    num_beams=2, max_new_tokens=2, rules=rules, mesh=mesh,
                )

    def test_generate_rejects_zigzag(self):
        config, params, mesh = self._zigzag_setup()
        with parallel.use_mesh(mesh):
            with pytest.raises(ValueError, match="zigzag"):
                generation.generate(
                    params, jnp.zeros((2, 8), jnp.int32),
                    jnp.full((2,), 8, jnp.int32), config,
                    max_new_tokens=2, mesh=mesh,
                )

    def test_beam_search_rejects_zigzag(self):
        config, params, mesh = self._zigzag_setup()
        with parallel.use_mesh(mesh):
            with pytest.raises(ValueError, match="zigzag"):
                generation.beam_search(
                    params, jnp.zeros((2, 8), jnp.int32),
                    jnp.full((2,), 8, jnp.int32), config,
                    num_beams=2, max_new_tokens=2, mesh=mesh,
                )

    def test_public_alias_used_by_serving(self):
        """check_inference_supported (the serving engine's startup
        validation) raises the same errors, and passes a sane layout."""
        config, params, mesh = self._zigzag_setup()
        with pytest.raises(ValueError, match="zigzag"):
            generation.check_inference_supported(
                config, parallel.DEFAULT_RULES, mesh, "serving"
            )
        generation.check_inference_supported(
            transformer.TINY, parallel.DEFAULT_RULES, None, "serving"
        )


class TestPromptLenValidation:
    """Out-of-domain prompt_lens (0 or > T_prompt) are clamped instead of
    silently indexing out of range (ADVICE r3: a 0 length made last_idx
    negative and stitched sequences out of range)."""

    def test_zero_and_oversized_lens_clamp(self):
        config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
        params = transformer.init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(0)
        b, t_prompt, n_new = 3, 6, 4
        prompt = rng.integers(1, 255, (b, t_prompt)).astype(np.int32)
        bad = jnp.asarray([0, 99, 3], jnp.int32)
        clamped = jnp.asarray([1, t_prompt, 3], jnp.int32)

        got_bad = generation.generate(
            params, jnp.asarray(prompt), bad, config,
            max_new_tokens=n_new,
            sample=generation.SampleConfig(temperature=0.0),
        )
        got_ok = generation.generate(
            params, jnp.asarray(prompt), clamped, config,
            max_new_tokens=n_new,
            sample=generation.SampleConfig(temperature=0.0),
        )
        np.testing.assert_array_equal(
            np.asarray(got_bad["tokens"]), np.asarray(got_ok["tokens"])
        )

    def test_beam_search_clamps_too(self):
        config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
        params = transformer.init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(1)
        prompt = rng.integers(1, 255, (2, 5)).astype(np.int32)
        bad = jnp.asarray([0, 7], jnp.int32)
        clamped = jnp.asarray([1, 5], jnp.int32)
        got_bad = generation.beam_search(
            params, jnp.asarray(prompt), bad, config,
            max_new_tokens=3, num_beams=2,
        )
        got_ok = generation.beam_search(
            params, jnp.asarray(prompt), clamped, config,
            max_new_tokens=3, num_beams=2,
        )
        np.testing.assert_array_equal(
            np.asarray(got_bad["tokens"]), np.asarray(got_ok["tokens"])
        )


class TestSlotPrograms:
    """The continuous-batching primitives (insert_slot_program /
    decode_chunk_program) at the program level, engine-free: chunked
    slot decode over a shared grid must be token-identical to
    per-request generate(), including slot reuse over stale cache."""

    def _model(self):
        config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
        params = transformer.init(jax.random.PRNGKey(0), config)
        return config, params

    def _drive(self, params, config, sample, cache, state, chunk,
               live):
        """Run chunks until every slot is inactive, appending emissions
        into ``live`` ({slot: token list})."""
        while bool(np.asarray(state["active"]).any()):
            cache, state, toks, valid = chunk(params, cache, state)
            toks, valid = np.asarray(toks), np.asarray(valid)
            for slot, tokens in live.items():
                for i in range(toks.shape[1]):
                    if valid[slot, i]:
                        tokens.append(int(toks[slot, i]))
        return cache, state

    def test_chunked_slot_decode_matches_generate(self):
        import functools

        config, params = self._model()
        sample = generation.SampleConfig(temperature=0.0)
        rng = np.random.default_rng(0)
        lens, budgets, bucket = (3, 6, 4), (5, 3, 1), 8
        prompts = [rng.integers(1, 255, n).astype(np.int32) for n in lens]
        num_slots, max_len = 3, bucket + 6

        cache = generation.init_slot_cache(config, num_slots, max_len)
        state = generation.init_slot_state(config, num_slots, sample=sample)
        insert = jax.jit(functools.partial(
            generation.insert_slot_program, config=config, sample=sample
        ))
        chunk = jax.jit(functools.partial(
            generation.decode_chunk_program, config=config, chunk_size=2,
            sample=sample,
        ))
        live = {}
        for slot, (prompt, budget) in enumerate(zip(prompts, budgets)):
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :len(prompt)] = prompt
            cache, state, tok0 = insert(
                params, cache, state, padded, np.int32(len(prompt)),
                np.int32(slot), np.int32(budget),
            )
            live[slot] = [int(tok0)]
        # budget 1 never activates: finished at insert.
        assert not bool(np.asarray(state["active"])[2])
        self._drive(params, config, sample, cache, state, chunk, live)

        for slot, (prompt, budget) in enumerate(zip(prompts, budgets)):
            want = generation.generate(
                params, jnp.asarray(prompt[None, :]),
                jnp.asarray([len(prompt)], np.int32), config,
                max_new_tokens=budget,
            )
            assert live[slot] == np.asarray(want["tokens"])[0].tolist(), slot

    def test_slot_reuse_over_stale_cache(self):
        """A slot that held a LONG sequence is re-inserted with a SHORT
        prompt: the stale cache beyond the new prompt must never leak
        (attention masks >= pos; decode overwrites before attending)."""
        import functools

        config, params = self._model()
        sample = generation.SampleConfig(temperature=0.0)
        rng = np.random.default_rng(1)
        bucket, num_slots, max_len = 16, 2, 16 + 6

        cache = generation.init_slot_cache(config, num_slots, max_len)
        state = generation.init_slot_state(config, num_slots, sample=sample)
        insert = jax.jit(functools.partial(
            generation.insert_slot_program, config=config, sample=sample
        ))
        chunk = jax.jit(functools.partial(
            generation.decode_chunk_program, config=config, chunk_size=3,
            sample=sample,
        ))

        def serve_in_slot(prompt, budget, slot):
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :len(prompt)] = prompt
            nonlocal cache, state
            cache, state, tok0 = insert(
                params, cache, state, padded, np.int32(len(prompt)),
                np.int32(slot), np.int32(budget),
            )
            live = {slot: [int(tok0)]}
            cache, state = self._drive(
                params, config, sample, cache, state, chunk, live
            )
            return live[slot]

        long_prompt = rng.integers(1, 255, 16).astype(np.int32)
        short_prompt = rng.integers(1, 255, 2).astype(np.int32)
        serve_in_slot(long_prompt, 6, 0)
        got = serve_in_slot(short_prompt, 4, 0)  # same slot, shallow
        want = generation.generate(
            params, jnp.asarray(short_prompt[None, :]),
            jnp.asarray([2], np.int32), config, max_new_tokens=4,
        )
        assert got == np.asarray(want["tokens"])[0].tolist()

    def test_chunk_program_eos_and_min_new_tokens(self):
        """eos deactivates a slot mid-chunk; min_new_tokens masks eos out
        of the early steps — both matching generate()'s behavior."""
        import functools

        config, params = self._model()
        prompt = np.asarray([7, 3, 11, 2], np.int32)
        greedy = np.asarray(generation.generate(
            params, jnp.asarray(prompt[None, :]),
            jnp.asarray([4], np.int32), config, max_new_tokens=6,
        )["tokens"])[0]
        eos = int(greedy[1])
        for min_new in (0, 4):
            sample = generation.SampleConfig(
                temperature=0.0, eos_id=eos, pad_id=0,
                min_new_tokens=min_new,
            )
            cache = generation.init_slot_cache(config, 1, 8 + 6)
            state = generation.init_slot_state(config, 1, sample=sample)
            insert = jax.jit(functools.partial(
                generation.insert_slot_program, config=config,
                sample=sample,
            ))
            chunk = jax.jit(functools.partial(
                generation.decode_chunk_program, config=config,
                chunk_size=3, sample=sample,
            ))
            padded = np.zeros((1, 8), np.int32)
            padded[0, :4] = prompt
            cache, state, tok0 = insert(
                params, cache, state, padded, np.int32(4), np.int32(0),
                np.int32(6),
            )
            live = {0: [int(tok0)]}
            self._drive(params, config, sample, cache, state, chunk, live)
            want = generation.generate(
                params, jnp.asarray(prompt[None, :]),
                jnp.asarray([4], np.int32), config, max_new_tokens=6,
                sample=sample,
            )
            want_row = np.asarray(want["tokens"])[0].tolist()
            n = int(want["num_generated"][0])
            assert live[0] == want_row[:n], (min_new, live[0], want_row)

    def test_chunk_program_repetition_penalty_state(self):
        """The seen-token mask rides the slot state: chunked decode with
        a repetition penalty matches generate() under the same greedy
        config (penalty applies to greedy too)."""
        import functools

        config, params = self._model()
        sample = generation.SampleConfig(
            temperature=0.0, repetition_penalty=1.3
        )
        prompt = np.asarray([5, 9, 17, 2], np.int32)
        cache = generation.init_slot_cache(config, 2, 8 + 5)
        state = generation.init_slot_state(config, 2, sample=sample)
        assert "seen" in state
        insert = jax.jit(functools.partial(
            generation.insert_slot_program, config=config, sample=sample
        ))
        chunk = jax.jit(functools.partial(
            generation.decode_chunk_program, config=config, chunk_size=2,
            sample=sample,
        ))
        padded = np.zeros((1, 8), np.int32)
        padded[0, :4] = prompt
        cache, state, tok0 = insert(
            params, cache, state, padded, np.int32(4), np.int32(1),
            np.int32(5),
        )
        live = {1: [int(tok0)]}
        self._drive(params, config, sample, cache, state, chunk, live)
        want = generation.generate(
            params, jnp.asarray(prompt[None, :]),
            jnp.asarray([4], np.int32), config, max_new_tokens=5,
            sample=sample,
        )
        assert live[1] == np.asarray(want["tokens"])[0].tolist()

    def test_quantized_slot_grid_runs(self):
        """kv_quant grids: insert writes int8 + scales, chunk decode
        consumes them (parity is vs the quantized generate path)."""
        import functools

        config, params = self._model()
        sample = generation.SampleConfig(temperature=0.0)
        prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
        cache = generation.init_slot_cache(
            config, 2, 8 + 4, kv_quant=True
        )
        assert "k_scale" in cache
        state = generation.init_slot_state(config, 2, sample=sample)
        insert = jax.jit(functools.partial(
            generation.insert_slot_program, config=config, sample=sample
        ))
        chunk = jax.jit(functools.partial(
            generation.decode_chunk_program, config=config, chunk_size=2,
            sample=sample,
        ))
        padded = np.zeros((1, 8), np.int32)
        padded[0, :5] = prompt
        cache, state, tok0 = insert(
            params, cache, state, padded, np.int32(5), np.int32(0),
            np.int32(4),
        )
        live = {0: [int(tok0)]}
        self._drive(params, config, sample, cache, state, chunk, live)
        want = generation.generate(
            params, jnp.asarray(prompt[None, :]),
            jnp.asarray([5], np.int32), config, max_new_tokens=4,
            kv_quant=True,
        )
        assert live[0] == np.asarray(want["tokens"])[0].tolist()

    @pytest.mark.parametrize("kv_quant", [False, True],
                             ids=["bf16", "int8kv"])
    def test_chunk_writes_only_its_own_rows(self, kv_quant):
        """The chunk program updates the carried cache IN PLACE, so what
        it must not touch matters: after a chunk every byte outside
        ``[*, active slot, pos .. pos + steps)`` is what it was — other
        rows, inactive slots, every layer, every leaf — and a slot
        whose write position is out of range writes nothing at all."""
        import functools

        config = transformer.TINY.scaled(dtype=jnp.bfloat16, num_layers=2)
        params = transformer.init(jax.random.PRNGKey(0), config)
        sample = generation.SampleConfig(temperature=0.0)
        num_slots, max_len, steps = 4, 16, 3
        zeros = generation.init_slot_cache(config, num_slots, max_len,
                                           kv_quant=kv_quant)
        keys = jax.random.split(jax.random.PRNGKey(1), len(zeros))
        cache = {}
        for key, (name, leaf) in zip(keys, sorted(zeros.items())):
            if leaf.dtype == jnp.int8:
                cache[name] = jax.random.randint(
                    key, leaf.shape, -127, 128, jnp.int32).astype(jnp.int8)
            else:
                cache[name] = jax.random.uniform(
                    key, leaf.shape, jnp.float32, 0.5, 1.5
                ).astype(leaf.dtype)
        # Slot 0 decodes all three steps; slot 1 runs out of budget after
        # two; slot 2 is inactive over a frozen position that holds KV;
        # slot 3 is active with its position past the row's end.
        state = generation.init_slot_state(config, num_slots, sample=sample)
        state.update(
            pos=jnp.asarray([5, 9, 7, max_len], jnp.int32),
            tok=jnp.asarray([11, 12, 13, 14], jnp.int32),
            remaining=jnp.asarray([10, 2, 10, 10], jnp.int32),
            active=jnp.asarray([True, True, False, True]),
        )
        chunk = jax.jit(functools.partial(
            generation.decode_chunk_program, config=config,
            chunk_size=steps, sample=sample,
        ))
        after, new_state, _, valid = chunk(params, cache, state)
        np.testing.assert_array_equal(
            np.asarray(valid).sum(axis=1), [3, 2, 0, 3])
        np.testing.assert_array_equal(
            np.asarray(new_state["pos"]), [8, 11, 7, max_len + 3])

        written = np.zeros((num_slots, max_len), bool)
        written[0, 5:8] = True
        written[1, 9:11] = True
        assert sorted(after) == sorted(cache)
        for name, leaf in cache.items():
            was = np.asarray(leaf).view(np.uint8)
            now = np.asarray(after[name]).view(np.uint8)
            assert now.shape == was.shape, name
            np.testing.assert_array_equal(
                now[:, ~written], was[:, ~written], err_msg=name)
            # ... and every layer did write each of its own positions.
            changed = (now != was).any(axis=(3, 4))  # [L, slots, rows]
            np.testing.assert_array_equal(
                changed, np.broadcast_to(written, changed.shape),
                err_msg=name)


class TestQuantizedKvCache:
    """kv_quant=True: int8 cache with per-(position, head) scales.  The
    post-scale attention algebra must equal explicit dequantization
    exactly, decode must stay close to the full-precision cache, and the
    cache must actually shrink."""

    def _model(self, seed=0):
        cfg = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
        params = transformer.init(jax.random.PRNGKey(seed), cfg)
        rng = np.random.default_rng(seed)
        prompts = jnp.asarray(rng.integers(1, 255, (2, 8)), jnp.int32)
        lens = jnp.asarray([8, 6], jnp.int32)
        return cfg, params, prompts, lens

    def test_post_scale_attention_matches_explicit_dequant(self):
        from cloud_tpu.models.generation import (
            _cache_attention,
            _quantize_kv,
        )

        rng = np.random.default_rng(3)
        b, s, h, d = 2, 16, 2, 8
        q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        cur = jnp.asarray([16, 11], jnp.int32)

        k_q, k_sc = _quantize_kv(k)
        v_q, v_sc = _quantize_kv(v)
        got = _cache_attention(
            q, {"k": k_q, "k_scale": k_sc, "v": v_q, "v_scale": v_sc}, cur
        )
        dequant = {
            "k": k_q.astype(jnp.float32) * k_sc,
            "v": v_q.astype(jnp.float32) * v_sc,
        }
        want = _cache_attention(q, dequant, cur)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
        )

    def test_generate_quantized_cache_mostly_agrees(self):
        cfg, params, prompts, lens = self._model()
        full = generation.generate(
            params, prompts, lens, cfg, max_new_tokens=8, mesh=None
        )
        quant = generation.generate(
            params, prompts, lens, cfg, max_new_tokens=8, mesh=None,
            kv_quant=True,
        )
        assert quant["sequences"].shape == full["sequences"].shape
        agree = float(jnp.mean(
            (quant["tokens"][:, :4] == full["tokens"][:, :4])
            .astype(jnp.float32)
        ))
        assert agree >= 0.5, agree

    def test_beam_search_quantized_cache_runs(self):
        cfg, params, prompts, lens = self._model(seed=1)
        out = generation.beam_search(
            params, prompts, lens, cfg, num_beams=3, max_new_tokens=6,
            kv_quant=True,
        )
        assert out["tokens"].shape == (2, 6)
        assert np.isfinite(np.asarray(out["scores"], np.float32)).all()

    def test_cache_bytes_shrink(self):
        from cloud_tpu.models.generation import _init_cache
        from cloud_tpu.models.quantization import param_bytes
        from cloud_tpu.parallel.sharding import DEFAULT_RULES

        cfg = transformer.TINY
        full = _init_cache(cfg, 2, 64, DEFAULT_RULES, None)
        quant = _init_cache(cfg, 2, 64, DEFAULT_RULES, None, kv_quant=True)
        # int8 + f32/hd scales vs the config dtype cache.
        assert param_bytes(quant) < 0.7 * param_bytes(full)


class TestSpeculativePrograms:
    """Draft-and-verify on the slot grid (ISSUE 12), engine-free: the
    verify program's committed emissions must be token-identical to the
    sequential decode path, whatever the draft proposes — proposals
    steer acceptance (how many tokens one target dispatch commits),
    never content.  The degenerate cases are pinned at this level
    because they are deterministic here: a crafted all-rejected window
    still commits exactly one token per active slot, and a
    shared-weights draft accepts full windows so the dispatch count is
    provably sub-one-per-token."""

    def _model(self):
        config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=1)
        params = transformer.init(jax.random.PRNGKey(0), config)
        return config, params

    def _insert_fns(self, config, sample):
        """Jitted insert + draft-prefill pair (prompt_len/slot/budget
        traced, so one compile each serves every slot and both grid
        builds of a test)."""
        insert_fn = jax.jit(
            lambda p, c, st, tok, ln, slot, m:
            generation.insert_slot_program(
                p, c, st, tok, ln, slot, m, config, sample=sample,
            )
        )
        dprefill_fn = jax.jit(
            lambda p, c, tok, ln, slot:
            generation.draft_prefill_slot_program(
                p, c, tok, ln, slot, config,
            )
        )
        return insert_fn, dprefill_fn

    def _armed_grid(self, config, params, sample, prompts, budgets,
                    draft_params, insert_fns=None, bucket=8, max_len=16):
        """Insert each prompt into its slot (target) and prefill the
        draft cache rows; returns (cache, draft_cache, state, live)."""
        if insert_fns is None:
            insert_fns = self._insert_fns(config, sample)
        insert_fn, dprefill_fn = insert_fns
        n = len(prompts)
        cache = generation.init_slot_cache(config, n, max_len)
        dcache = generation.init_slot_cache(config, n, max_len)
        state = generation.init_slot_state(config, n, sample=sample)
        live = {}
        for slot, (prompt, budget) in enumerate(zip(prompts, budgets)):
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :len(prompt)] = prompt
            cache, state, tok0 = insert_fn(
                params, cache, state, jnp.asarray(padded),
                np.int32(len(prompt)), np.int32(slot), np.int32(budget),
            )
            dcache = dprefill_fn(
                draft_params, dcache, jnp.asarray(padded),
                np.int32(len(prompt)), np.int32(slot),
            )
            live[slot] = [int(tok0)]
        return cache, dcache, state, live

    def _spec_round(self, config, sample, spec_k):
        """Jitted draft+verify pair — ONE compile each serves every
        drive-loop iteration and every draft-params variant (params are
        traced arguments), exactly the engine's compile economy."""
        draft_fn = jax.jit(
            lambda dp, dc, st: generation.draft_chunk_program(
                dp, dc, st, config, spec_k=spec_k,
            )
        )
        verify_fn = jax.jit(
            lambda p, c, st, w: generation.verify_chunk_program(
                p, c, st, w, config, sample=sample,
            )
        )
        return draft_fn, verify_fn

    def _drive_spec(self, params, draft_params, cache, dcache, state,
                    live, spec_k, round_fns):
        """Draft-and-verify rounds until every slot retires; returns
        the per-dispatch (active, emitted) trail."""
        draft_fn, verify_fn = round_fns
        trail = []
        while bool(np.asarray(state["active"]).any()):
            active_n = int(np.asarray(state["active"]).sum())
            dcache, window = draft_fn(draft_params, dcache, state)
            cache, state, toks, valid = verify_fn(
                params, cache, state, window
            )
            toks, valid = np.asarray(toks), np.asarray(valid)
            trail.append((active_n, int(valid.sum())))
            for slot, tokens in live.items():
                for i in range(spec_k):
                    if valid[slot, i]:
                        tokens.append(int(toks[slot, i]))
            assert len(trail) < 40, "speculative loop failed to converge"
        return trail

    @pytest.mark.slow
    def test_shared_and_mismatching_drafts_match_generate(self):
        """The two acceptance extremes through ONE compiled round pair.
        draft == target: every proposal matches, each dispatch commits
        a full window (modulo budget) — strictly fewer verify dispatches
        than tokens emitted.  A fresh-init draft: acceptance collapses,
        but every committed token is still the target's own greedy
        choice — parity is unconditional, with >= 1 emission per active
        slot per dispatch.

        Slow tier (tier-1 wall-clock sits against its 870s budget, the
        PR 8/10 precedent): both extremes stay pinned FAST at engine
        level — test_serving.py TestSpeculative's shared-draft test
        asserts full-window acceptance + dispatches < tokens, its
        mismatching-draft test the parity/floor — and e2e under churn
        by scripts/check_serving.py phase 4 every CI run; the program-
        level degenerate cases below (all-rejected window, budget/eos
        truncation) remain fast."""
        config, params = self._model()
        draft_params = transformer.init(jax.random.PRNGKey(7), config)
        sample = generation.SampleConfig(temperature=0.0)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 255, n).astype(np.int32)
                   for n in (5, 3)]
        budgets = (7, 4)
        round_fns = self._spec_round(config, sample, spec_k=3)
        oracles = [
            list(np.asarray(generation.generate(
                params, jnp.asarray(prompt[None, :]),
                jnp.asarray([len(prompt)], np.int32), config,
                max_new_tokens=budget, sample=sample,
            )["tokens"])[0])
            for prompt, budget in zip(prompts, budgets)
        ]

        insert_fns = self._insert_fns(config, sample)
        cache, dcache, state, live = self._armed_grid(
            config, params, sample, prompts, budgets, params,
            insert_fns=insert_fns)
        trail = self._drive_spec(
            params, params, cache, dcache, state, live, 3, round_fns)
        for slot in range(len(prompts)):
            assert live[slot] == oracles[slot]
        decode_emissions = sum(e for _, e in trail)
        assert len(trail) < decode_emissions
        # Full first window: both slots had >= spec_k budget left, so
        # the shared-weights draft commits 3 tokens per slot at once.
        assert trail[0] == (2, 6)

        cache, dcache, state, live = self._armed_grid(
            config, params, sample, prompts, budgets, draft_params,
            insert_fns=insert_fns)
        trail = self._drive_spec(
            params, draft_params, cache, dcache, state, live, 3,
            round_fns)
        for slot in range(len(prompts)):
            assert live[slot] == oracles[slot]
        for active_n, emitted in trail:
            assert emitted >= active_n

    def test_all_rejected_window_commits_exactly_one_token(self):
        """A window whose every proposal is crafted to mismatch the
        target's greedy choice degenerates to the non-speculative step:
        exactly one committed token per active slot, pos advanced by
        one."""
        config, params = self._model()
        sample = generation.SampleConfig(temperature=0.0)
        rng = np.random.default_rng(2)
        prompts = [rng.integers(1, 255, n).astype(np.int32)
                   for n in (5, 3)]
        cache, dcache, state, live = self._armed_grid(
            config, params, sample, prompts, (4, 4), params)
        spec_k = 3
        _, verify_fn = self._spec_round(config, sample, spec_k)
        # Learn the greedy next tokens from a throwaway verify, then
        # craft proposals one off from each — guaranteed mismatches
        # (same jitted program both times: one compile).
        probe_cache = jax.tree_util.tree_map(jnp.copy, cache)
        _, _, probe_toks, _ = verify_fn(
            params, probe_cache, dict(state),
            jnp.stack([state["tok"]] * spec_k, axis=1),
        )
        g0 = np.asarray(probe_toks)[:, 0]
        wrong = (g0 + 1) % config.vocab_size
        window = np.stack(
            [np.asarray(state["tok"])] + [wrong] * (spec_k - 1), axis=1
        )
        pos_before = np.asarray(state["pos"]).copy()
        cache, state, toks, valid = verify_fn(
            params, cache, state, jnp.asarray(window).astype(jnp.int32),
        )
        valid = np.asarray(valid)
        assert valid[:, 0].all() and not valid[:, 1:].any()
        np.testing.assert_array_equal(
            np.asarray(state["pos"]), pos_before + 1
        )
        np.testing.assert_array_equal(np.asarray(toks)[:, 0], g0)

    def test_verify_truncates_at_budget_and_eos(self):
        """The window may offer spec_k tokens; ``remaining`` and eos cap
        the commit exactly as the sequential path would."""
        config, params = self._model()
        rng = np.random.default_rng(3)
        prompt = rng.integers(1, 255, 5).astype(np.int32)
        plain = generation.generate(
            params, jnp.asarray(prompt[None, :]),
            jnp.asarray([len(prompt)], np.int32), config,
            max_new_tokens=6,
            sample=generation.SampleConfig(temperature=0.0),
        )
        eos = int(np.asarray(plain["tokens"])[0][2])
        sample = generation.SampleConfig(temperature=0.0, eos_id=eos,
                                         pad_id=0)
        # Slot 0: eos arrives at emission index 2, inside the first
        # spec_k=4 window.  Slot 1: budget 2 truncates the same window.
        cache, dcache, state, live = self._armed_grid(
            config, params, sample, [prompt, prompt], (6, 2), params)
        self._drive_spec(
            params, params, cache, dcache, state, live, 4,
            self._spec_round(config, sample, spec_k=4),
        )
        # Oracles derive from the one plain run: greedy-with-eos is the
        # plain stream cut after the first eos (emitted inclusive), and
        # a budget is a prefix — no further generate() compiles needed.
        plain_toks = list(np.asarray(plain["tokens"])[0])
        assert live[0] == plain_toks[:3]  # t0, t1, eos
        assert live[1] == plain_toks[:2]  # budget 2

    def test_verify_rejects_non_greedy(self):
        config, params = self._model()
        state = generation.init_slot_state(config, 1)
        cache = generation.init_slot_cache(config, 1, 8)
        with pytest.raises(ValueError, match="greedy"):
            generation.verify_chunk_program(
                params, cache, state, jnp.zeros((1, 2), jnp.int32),
                config,
                sample=generation.SampleConfig(temperature=0.7),
            )
