"""The latent-attention block with a chip's share of dropless experts
(Kimi-K2's, the DeepSeek-V3 block) against its plain reference, at the
configuration's ``tiny`` sizes on the CPU: seeded random weights, float32
compute so that what differs is the algorithm (latent cache, absorbed
read, sorted and grouped experts, slots) and not the rounding.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.adapters import serve_latent_moe  # noqa: E402
from benchmarks.references import kimi_k2  # noqa: E402
from cloud_tpu.models import generation, moe, transformer  # noqa: E402
from cloud_tpu.serving import DraftConfig, ServeConfig, ServingEngine  # noqa: E402

SEED = 2 ** 31 + 35
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "kimi-k2-ep32-stage.json")) as f:
    _FILE = json.load(f)
#: The tiny sizes: 16 experts of which this share holds [4, 8).
SIZES = {**_FILE, **_FILE["tiny"], "expert_offset": 4}
BUCKETS, NEW = (16, 32), 8
MIX = {"engine": {"prompt_buckets": list(BUCKETS), "max_new_tokens": NEW}}
CONFIG = serve_latent_moe.model_config(SIZES, MIX)
GREEDY = generation.SampleConfig(temperature=0.0)

#: Program against reference in units of the row's logit standard
#: deviation, both in float32: they differ in the order of sums (a latent
#: cache read in the absorbed form against a full expanded pass, rows
#: sorted by expert against every expert on every token) and read under
#: 4e-6 on these prompts.  The limit sits five times over that; a choice
#: of experts that flipped would read 1e-2 and more.
LOGIT_TOLERANCE = 2e-5


@pytest.fixture(scope="module", autouse=True)
def _small_fit():
    """The fit of ``b`` on 8 x 32 tokens: a test needs no better one."""
    saved = kimi_k2.CALIBRATION_SEQUENCES, kimi_k2.CALIBRATION_LENGTH
    kimi_k2.CALIBRATION_SEQUENCES, kimi_k2.CALIBRATION_LENGTH = 8, 32
    yield
    kimi_k2.CALIBRATION_SEQUENCES, kimi_k2.CALIBRATION_LENGTH = saved


@pytest.fixture(scope="module")
def params():
    return kimi_k2.make_params(SEED, SIZES, dtype=jnp.float32)


def reference_logits(params, tokens):
    """The reference's full forward pass over one sequence: [T, V]."""
    key = kimi_k2._sizes_key(SIZES)
    keys = kimi_k2._keys(SEED, SIZES)
    k_embed, k_dense, k_experts = keys[:3]
    biases = params["layers"]["mlp"]["bias"]
    with jax.default_matmul_precision("highest"):
        xs = kimi_k2._embed(k_embed, jnp.asarray(tokens, jnp.int32)[None],
                            key, jnp.float32)
        for k in k_dense:
            xs, _, _ = kimi_k2._apply_layer(k, biases[0], xs, key, "f32",
                                         jnp.float32, True)
        for k, bias in zip(k_experts, biases):
            xs, _, _ = kimi_k2._apply_layer(k, bias, xs, key, "f32",
                                         jnp.float32, False)
        y = kimi_k2._rmsnorm(xs[0], params["ln_f"]["scale"],
                             SIZES["rms_norm_eps"])
        return np.asarray(kimi_k2.matmul(y, params["head"]["kernel"], "f32"))


def gap(logits, reference):
    logits, reference = np.asarray(logits), np.asarray(reference)
    return float(np.max(np.abs(logits - reference)
                        / np.std(reference, axis=-1, keepdims=True)))


def _prompt(length, seed=0):
    return np.random.default_rng(seed).integers(
        1, SIZES["vocab_size"], length).astype(np.int32)


def _served_logits(params, prompt, steps, slot=1, slots=3):
    """Prefill ``prompt`` into ``slot`` of a grid whose other slots stand
    idle, then ``steps`` single-token steps through the slot cache, each
    fed its own greedy token: the logits sampled from and the tokens."""
    bucket = next(b for b in BUCKETS if b >= len(prompt))
    cache = generation.init_slot_cache(CONFIG, slots, BUCKETS[-1] + NEW)
    buf = np.zeros((1, bucket), np.int32)
    buf[0, :len(prompt)] = prompt
    left, logits0 = generation._prefill_forward(
        params, jnp.asarray(buf), jnp.asarray([len(prompt)]), CONFIG,
        generation.DEFAULT_RULES, None)
    cache = generation._write_prefill(cache, left, (0, slot, 0, 0, 0),
                                      CONFIG)
    rows, tokens = [logits0[0]], [int(jnp.argmax(logits0[0]))]
    pos = np.zeros((slots,), np.int32)
    for step in range(steps):
        pos[slot] = len(prompt) + step
        token = np.zeros((slots,), np.int32)
        token[slot] = tokens[-1]
        write = np.full((slots,), BUCKETS[-1] + NEW, np.int32)
        write[slot] = pos[slot]
        cache, logits = generation._decode_step(
            params, cache, jnp.asarray(token), jnp.asarray(pos), CONFIG,
            generation.DEFAULT_RULES, None, write_pos=jnp.asarray(write))
        rows.append(logits[slot])
        tokens.append(int(jnp.argmax(logits[slot])))
    return np.stack(rows), tokens[:-1], cache


# -- (a) the share ties to the model --------------------------------------


def _layer_input(seed=3, tokens=24):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (tokens, SIZES["hidden_size"])), jnp.float32)


def _share(offset, held):
    return {**SIZES, "n_routed_experts": held, "expert_offset": offset}


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 shares of 4: what the four shares' routed
    experts give, with the shared expert counted once, is the uncut
    layer of the reference; and the program's share is the reference's."""
    key = jax.random.PRNGKey(7)
    m = _layer_input()
    bias = 0.02 * jnp.asarray(np.random.default_rng(5).standard_normal(
        SIZES["published"]["n_routed_experts"]), jnp.float32)

    def mlp_of(sizes):
        p = kimi_k2.layer_params(key, sizes, jnp.float32, False)["mlp"]
        return dict(p, bias=bias)

    with jax.default_matmul_precision("highest"):
        whole = mlp_of(_share(0, 16))
        uncut, _, _ = kimi_k2._expert_layer(whole, m, _share(0, 16), "f32")
        shared = kimi_k2._mlp(whole["shared"], m, "f32")
        routed = 0.0
        for offset in (0, 4, 8, 12):
            sizes = _share(offset, 4)
            ours, _, _ = kimi_k2._expert_layer(mlp_of(sizes), m, sizes, "f32")
            cfg = serve_latent_moe.model_config(sizes, MIX).moe
            theirs, counted = moe.dropless_mlp_apply(mlp_of(sizes), m[None],
                                                     cfg)
            np.testing.assert_allclose(theirs[0], ours, rtol=2e-5, atol=2e-6)
            routed = routed + (ours - shared)
            # Every assignment lands on exactly one share.
            assert int(counted[0]) == 24 * SIZES["num_experts_per_tok"]
    np.testing.assert_allclose(routed + shared, uncut, rtol=2e-5, atol=2e-6)
    assert float(jnp.max(jnp.abs(routed))) > 0.1  # the experts did work


# -- (b), (c) the two attention paths against one reference ---------------


def test_absorbed_decode_is_expanded_attention_on_the_same_cache(params):
    """A decode step (absorbed read over the latent rows) against the
    program's own full forward pass (expanded) over the same tokens."""
    prompt = _prompt(11)
    served, tokens, _ = _served_logits(params, prompt, 3)
    full, _ = transformer.apply(
        params, jnp.asarray(np.concatenate([prompt, tokens]))[None], CONFIG)
    assert gap(served, full[0, len(prompt) - 1:]) < LOGIT_TOLERANCE


@pytest.mark.parametrize("length", [5, 16, 23])
def test_prefill_then_decode_agrees_with_the_reference(params, length):
    prompt = _prompt(length, seed=length)
    served, tokens, _ = _served_logits(params, prompt, 6)
    reference = reference_logits(params, np.concatenate([prompt, tokens]))
    assert gap(served, reference[length - 1:]) < LOGIT_TOLERANCE


def test_submit_over_two_buckets_and_a_reused_slot_agrees(params):
    """Through ``ServingEngine.submit``: five requests over two buckets
    and two slots (every slot reused); each served token is the
    reference's best at its position, to the tolerance."""
    prompts = [_prompt(n, seed=n) for n in (7, 20, 13, 30, 4)]
    serve = ServeConfig(prompt_buckets=BUCKETS, max_new_tokens=NEW,
                        num_slots=2, chunk_tokens=4, warmup=False)
    with ServingEngine(params, CONFIG, serve) as engine:
        futures = [engine.submit(p, max_new_tokens=NEW - (i % 3))
                   for i, p in enumerate(prompts)]
        results = [f.result(timeout=300) for f in futures]
        stats = engine.stats()
    width = BUCKETS[-1] + NEW
    tokens = np.zeros((len(prompts), width), np.int32)
    rows = np.zeros((len(prompts), NEW), np.int32)
    chosen, valid = np.zeros_like(rows), np.zeros(rows.shape, bool)
    for i, (p, r) in enumerate(zip(prompts, results)):
        served = list(r.tokens[:r.num_generated])
        assert len(served) == NEW - (i % 3)
        tokens[i, :len(p) + len(served)] = np.concatenate([p, served])
        rows[i, :len(served)] = len(p) - 1 + np.arange(len(served))
        chosen[i, :len(served)], valid[i, :len(served)] = served, True
    scores = kimi_k2.score(SEED, SIZES, tokens, rows, chosen, "f32",
                           jnp.float32)
    gaps = (scores["best"] - scores["chosen"]) / scores["std"]
    assert float(np.max(np.where(valid, gaps, 0.0))) < LOGIT_TOLERANCE
    # The routing came back with the tokens: every real token's
    # assignments, two expert layers, none of an idle slot's.
    made = sum(len(p) + NEW - (i % 3) - 1 for i, p in enumerate(prompts))
    assert stats["expert_assignments"] == (
        made * SIZES["num_experts_per_tok"] * 2)
    assert 0 < stats["expert_assignments_here"] < stats["expert_assignments"]
    assert sum(stats["expert_loads"]) == stats["expert_assignments_here"]
    assert stats["kv_bytes_reserved"] == (
        3 * 2 * width * CONFIG.latent.row_width * 4)


# -- (d) no token is dropped ------------------------------------------------


def test_no_token_is_dropped_under_a_routing_skewed_onto_one_expert(
        monkeypatch):
    """Every token's first choice forced onto ONE held expert, and the
    rows walked in blocks of 8: all 24 tokens reach it."""
    monkeypatch.setattr(moe, "ROW_BLOCK", 8)
    sizes = _share(4, 4)
    cfg = serve_latent_moe.model_config(sizes, MIX).moe
    p = kimi_k2.layer_params(jax.random.PRNGKey(9), sizes, jnp.float32,
                             False)["mlp"]
    p = dict(p, bias=jnp.zeros((16,)).at[6].set(10.0))
    m = _layer_input(seed=4)
    out, counted = jax.jit(
        lambda p, m: moe.dropless_mlp_apply(p, m[None], cfg))(p, m)
    with jax.default_matmul_precision("highest"):
        want, _, _ = kimi_k2._expert_layer(p, m, sizes, "f32")
    np.testing.assert_allclose(out[0], want, rtol=2e-5, atol=2e-6)
    loads = np.asarray(counted[moe.ROUTING_HEAD:])
    assert loads[6 - 4] == 24 and counted[1] == loads.sum() >= 24
    # Padding and idle rows are kept off the experts and out of the counts.
    live = jnp.asarray([[1] * 10 + [0] * 14])
    out, counted = moe.dropless_mlp_apply(p, m[None], cfg, live=live)
    assert int(counted[0]) == 10 * 4 and int(counted[moe.ROUTING_HEAD + 2]) \
        == 10
    np.testing.assert_allclose(out[0, :10], want[:10], rtol=2e-5, atol=2e-6)


# -- (e) the rows' way back to their tokens ---------------------------------


def _stub_experts(params, rows, sizes, layer, weights):
    """An expert that scales: row x (1 + its held expert's number) x
    weight, rounded once to the rows' type; NaN past the rows the groups
    hold, where the grouped product leaves what it finds."""
    ends = jnp.cumsum(sizes)
    at = jnp.arange(rows.shape[0])
    expert = jnp.searchsorted(ends, at, side="right")
    y = rows.astype(jnp.float32) * ((1.0 + expert) * weights)[:, None]
    return jnp.where((at < ends[-1])[:, None], y, jnp.nan).astype(rows.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case,row_block,live_tokens", [
    ("several-choices-in-one-block", 1024, 24),
    ("choices-in-two-blocks", 8, 24),
    ("live-rows", 8, 10),
    ("no-row-landed", 1024, 0),
])
def test_a_blocks_rows_are_summed_onto_their_tokens(
        monkeypatch, case, row_block, live_tokens, dtype):
    """The combine alone (the experts stubbed by a scaling) against a
    plain scatter-add: every choice held here reaches its token once, from
    whichever block it fell in; rows past those that landed add nothing
    though they hold NaN, to token 0 no more than to another; float32 to
    2e-6, bfloat16 rows summed in float32 and rounded once."""
    monkeypatch.setattr(moe, "ROW_BLOCK", row_block)
    monkeypatch.setattr(moe, "_held_experts", _stub_experts)
    sizes = _share(4, 4)
    cfg = serve_latent_moe.model_config(sizes, MIX).moe
    cfg = moe.MoeConfig(**{**vars(cfg), "shared_hidden": 0})
    p = kimi_k2.layer_params(jax.random.PRNGKey(9), sizes, dtype,
                             False)["mlp"]
    m = _layer_input(seed=6).astype(dtype)
    # Token 0 is not live: nothing may reach it, and the rows past the
    # landed (padding's among them) carry its id.
    live = (jnp.arange(24) < live_tokens) & (jnp.arange(24) > 0)
    out, counted = jax.jit(lambda p, m, live: moe.dropless_mlp_apply(
        p, m[None], cfg, live=live[None]))(p, m, live)

    idx, weights = moe.route(p, m, cfg)
    local = np.asarray(idx) - cfg.expert_offset
    here = (local >= 0) & (local < cfg.held) & np.asarray(live)[:, None]
    rows = (m.astype(jnp.float32)[:, None, :]
            * ((1.0 + local) * weights)[..., None]).astype(dtype)
    token = np.broadcast_to(np.arange(24)[:, None], here.shape)
    want = jnp.zeros(m.shape, jnp.float32).at[token[here]].add(
        rows[here].astype(jnp.float32))

    assert int(counted[1]) == here.sum()
    if case == "several-choices-in-one-block":
        assert here.sum(axis=1).max() >= 2 and here.sum() <= row_block
    elif case == "choices-in-two-blocks":
        # Sorted by expert: a token with two experts here whose rows lie
        # further apart than a block.
        order = np.argsort(np.where(here, local, cfg.held).reshape(-1),
                           kind="stable")
        block_of = np.empty(order.size, int)
        block_of[order] = np.arange(order.size) // row_block
        block_of = np.where(here, block_of.reshape(here.shape), -1)
        assert any(len(set(b[b >= 0])) >= 2 for b in block_of)
    elif case == "no-row-landed":
        assert here.sum() == 0
    got = np.asarray(out[0].astype(jnp.float32))
    assert np.isfinite(got).all()
    assert not got[0].any() and not got[live_tokens:].any()
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    else:
        # One rounding of the float32 sum: half a unit in the last of
        # bfloat16's 8 bits, and the sum's own order beside it.
        np.testing.assert_allclose(got, want, rtol=2.0 ** -8, atol=0)
        exact = np.asarray(want.astype(jnp.bfloat16).astype(jnp.float32))
        assert (got == exact).mean() > 0.99


# -- (f) what refuses a latent cache ----------------------------------------


@pytest.mark.parametrize("overrides", [
    dict(prefix_cache_blocks=4, prefix_block_tokens=4),
    dict(prefill_chunk_tokens=8),
    dict(decode_kernel="pallas", prefix_cache_blocks=4,
         prefix_block_tokens=4),
    dict(draft=DraftConfig(params={}, config=transformer.TINY, spec_k=2)),
    dict(kv_quant=True),
    dict(mesh_shape=(2, 1)),
], ids=["prefix-pool", "chunked-prefill", "paged", "draft", "int8",
        "tp-mesh"])
def test_the_engine_refuses_in_words(params, overrides):
    serve = ServeConfig(prompt_buckets=(16,), max_new_tokens=4, num_slots=2,
                        warmup=False, **overrides)
    with pytest.raises(NotImplementedError, match="latent-attention cache"):
        ServingEngine(params, CONFIG, serve, start=False)


def test_the_programs_refuse_in_words(params):
    tokens = jnp.asarray(_prompt(8))[None]
    lens = jnp.asarray([8])
    refused = pytest.raises(NotImplementedError,
                            match="latent-attention cache")
    with refused:
        generation.beam_search(params, tokens, lens, CONFIG, num_beams=2,
                               max_new_tokens=2)
    with refused:
        generation.init_prefix_pool(CONFIG, 4, 4)
    with refused:
        generation.generate(params, tokens, lens, CONFIG, max_new_tokens=2,
                            kv_quant=True)
    cache = generation.init_slot_cache(CONFIG, 2, 24)
    with refused:
        generation.prefill_chunk_program(params, cache, tokens, 0, 8, 0,
                                         CONFIG)
    state = generation.init_slot_state(CONFIG, 2, sample=GREEDY)
    with refused:
        generation.draft_chunk_program(params, cache, state, CONFIG,
                                       spec_k=2)
    # The recurrent state's refusal is the same function's.
    from cloud_tpu.models import ssm

    hybrid = transformer.TINY.scaled(ssm=ssm.SsmConfig(
        num_heads=2, head_dim=8, state_dim=16, num_groups=1, chunk_size=4))
    with pytest.raises(NotImplementedError, match="recurrent state"):
        generation.init_prefix_pool(hybrid, 4, 4)
    assert generation.cache_kind(transformer.TINY) is None


def test_a_share_the_capacity_route_does_not_know_is_refused():
    with pytest.raises(ValueError, match="dropless"):
        moe.MoeConfig(num_experts=8, experts_held=2)
    with pytest.raises(ValueError, match="not among"):
        moe.MoeConfig(num_experts=8, experts_held=4, expert_offset=6,
                      dropless=True)
    with pytest.raises(ValueError, match="leading_dense_layers"):
        transformer.TINY.scaled(leading_dense_layers=1)


# -- (g) the configurations that were there ----------------------------------


def test_existing_parameter_trees_and_counters_are_unchanged():
    plain = transformer.init(jax.random.PRNGKey(0), transformer.TINY)
    assert set(plain) == {"embed", "layers", "ln_f", "head"}
    assert set(plain["layers"]) == {"att", "ln1", "mlp", "ln2"}
    assert set(plain["layers"]["att"]) == {"q", "k", "v", "out"}
    assert set(plain["layers"]["mlp"]) == {"wi", "wg", "wo"}
    routed = transformer.TINY.scaled(moe=moe.MoeConfig(num_experts=4))
    tree = transformer.init(jax.random.PRNGKey(0), routed)
    assert set(tree["layers"]["mlp"]) == {"router", "wi", "wg", "wo"}
    assert tree["layers"]["mlp"]["wi"].shape[:2] == (4, 4)
    axes = transformer.param_logical_axes(routed)
    assert set(axes["layers"]["mlp"]) == {"router", "wi", "wg", "wo"}
    # The latent model's tree: two stacks, the selection bias, a shared
    # expert.
    ours = jax.eval_shape(
        lambda: transformer.init(jax.random.PRNGKey(0), CONFIG))
    assert set(ours) == {"embed", "dense_layers", "layers", "ln_f", "head"}
    assert set(ours["layers"]["mlp"]) == {"router", "bias", "wi", "wg", "wo",
                                          "shared"}
    assert ours["layers"]["mlp"]["wi"].shape[:2] == (2, 4)
    assert ours["layers"]["mlp"]["router"]["kernel"].shape[-1] == 16
    assert jax.tree_util.tree_structure(ours) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda a: 0, transformer.param_logical_axes(CONFIG),
            is_leaf=lambda a: isinstance(a, tuple)))
    # A model without experts: the counters are there, and zero.
    config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=1)
    serve = ServeConfig(prompt_buckets=(8,), max_new_tokens=3, num_slots=1,
                        chunk_tokens=2, warmup=False)
    with ServingEngine(transformer.init(jax.random.PRNGKey(0), config),
                       config, serve) as engine:
        engine.submit(np.asarray([1, 2, 3], np.int32)).result(timeout=300)
        stats = engine.stats()
    for key in ("expert_assignments", "expert_assignments_here",
                "expert_steps", "expert_steps_touched",
                "expert_load_max_over_mean"):
        assert stats[key] == 0
    assert stats["expert_loads"] == ()
    for key in ("chunks", "kv_row_steps_read", "state_row_steps_read",
                "insert_rows_computed", "kv_bytes_reserved"):
        assert key in stats
