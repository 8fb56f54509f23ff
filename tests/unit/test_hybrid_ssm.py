"""The hybrid attention + state-space block (Falcon-H1's: grouped K/V heads,
a Mamba-2 mixer beside attention in every block, muP multipliers) against
its plain reference, at the configuration's ``tiny`` sizes on the CPU:
seeded random weights, float32 compute so that what differs is the
algorithm (cache, chunked scan, slots) and not the rounding, and every
multiplier at a value of its own so that dropping or swapping one fails.
"""

import dataclasses
import json
import math
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

from benchmarks.adapters import serve_hybrid  # noqa: E402
from benchmarks.references import falcon_h1  # noqa: E402
from cloud_tpu.models import generation, layers, ssm, transformer  # noqa: E402
from cloud_tpu.ops import ssm_state  # noqa: E402
from cloud_tpu.serving import DraftConfig, ServeConfig, ServingEngine  # noqa: E402

SEED = 2 ** 31 + 28
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "falcon-h1-34b-stage.json")) as f:
    _FILE = json.load(f)
#: The tiny sizes, and fourteen multipliers no two of which are alike.
SIZES = {
    **_FILE, **_FILE["tiny"],
    "embedding_multiplier": 1.7, "attention_in_multiplier": 0.8,
    "attention_out_multiplier": 0.6, "key_multiplier": 0.7,
    "ssm_in_multiplier": 0.9, "ssm_out_multiplier": 1.1,
    "ssm_multipliers": [0.75, 1.2, 0.85, 1.3, 0.65],
    "mlp_multipliers": [1.15, 0.55], "lm_head_multiplier": 0.45,
}
BUCKET, CHUNK, SLOTS, NEW = 16, 4, 3, 12
MIX = {"engine": {"prompt_buckets": [BUCKET], "max_new_tokens": NEW}}
CONFIG = serve_hybrid.model_config(SIZES, MIX).scaled(dtype=jnp.float32)
GREEDY = generation.SampleConfig(temperature=0.0)
#: The same model with a state that tiles as the state-step kernel needs
#: (``ops.ssm_state``: N a multiple of 128), so that the programs below
#: can run once more with the kernel, interpreted, in the layer loop.
TILED_SIZES = {**SIZES, "mamba_d_state": 128}
TILED = serve_hybrid.model_config(TILED_SIZES, MIX).scaled(dtype=jnp.float32)

#: Program against reference, in units of the row's logit standard
#: deviation.  Both compute in float32; they differ in the order of
#: sums (a cache against a full pass, the chunked scan against the
#: token-by-token recurrence) and read 1.4e-6 to 1.7e-6 on four prompts.
#: The same program with its recurrent state kept in bfloat16 reads
#: 4e-4 to 1e-3 after eight tokens
#: (``test_state_in_bfloat16_fails_the_tolerance_float32_passes``): a
#: state rounded at every token compounds.  The limit sits ten times
#: over the one and twenty under the other.
LOGIT_TOLERANCE = 2e-5


@pytest.fixture(scope="module")
def params():
    return falcon_h1.make_params(SEED, SIZES, dtype=jnp.float32)


@dataclasses.dataclass
class Model:
    """One model under test: the reference's sizes, the program's
    configuration, the weights."""
    sizes: dict
    config: transformer.TransformerConfig
    params: dict


@pytest.fixture(params=["jnp", "kernel"])
def model(request, params, monkeypatch):
    """The tiny model with the state advanced by the ``jnp`` step, and
    its tiled twin with the state-step kernel in the layer loop (the
    interpreter armed, as the chip would arm the kernel)."""
    if request.param == "jnp":
        yield Model(SIZES, CONFIG, params)
        return
    monkeypatch.setenv("CLOUD_TPU_FLASH_FORCE_INTERPRET", "1")
    traced = ssm_state.KERNEL_TRACE_COUNT
    yield Model(TILED_SIZES, TILED, falcon_h1.make_params(
        SEED, TILED_SIZES, dtype=jnp.float32))
    assert ssm_state.KERNEL_TRACE_COUNT > traced


def reference_logits(params, tokens, sizes=SIZES):
    """The reference's full forward pass over one sequence: [T, V]."""
    key = falcon_h1._sizes_key(sizes)
    k_embed, k_layers, _, _ = falcon_h1._keys(SEED, sizes)
    with jax.default_matmul_precision("highest"):
        xs = falcon_h1._embed(k_embed, jnp.asarray(tokens, jnp.int32)[None],
                              key, jnp.float32)
        for k in k_layers:
            xs = falcon_h1._apply_layer(k, xs, key, "f32", jnp.float32)
        y = falcon_h1._rmsnorm(xs[0], params["ln_f"]["scale"],
                               sizes["rms_norm_eps"])
        return np.asarray(falcon_h1.matmul(y, params["head"]["kernel"], "f32")
                          * sizes["lm_head_multiplier"])


def gap(logits, reference):
    """The widest distance of a logit from the reference's, in the
    reference row's standard deviations."""
    logits, reference = np.asarray(logits), np.asarray(reference)
    return float(np.max(np.abs(logits - reference)
                        / np.std(reference, axis=-1, keepdims=True)))


def _prompt(length, seed=0):
    return np.random.default_rng(seed).integers(
        1, SIZES["vocab_size"], length).astype(np.int32)


def _padded(prompt):
    buf = np.zeros((1, BUCKET), np.int32)
    buf[0, :len(prompt)] = prompt
    return jnp.asarray(buf)


def _grid(config=CONFIG):
    return (generation.init_slot_cache(config, SLOTS, BUCKET + NEW),
            generation.init_slot_state(config, SLOTS, sample=GREEDY))


def _insert(params, cache, state, prompt, slot, new, config=CONFIG):
    return generation.insert_slot_program(
        params, cache, state, _padded(prompt), len(prompt), slot, new,
        config, sample=GREEDY)


def _chunk(params, cache, state, config=CONFIG):
    return generation.decode_chunk_program(
        params, cache, state, config, chunk_size=CHUNK, sample=GREEDY)


def _served_logits(params, prompt, slot, steps, cache=None, config=CONFIG):
    """Prefill ``prompt`` at a bucket longer than it into ``slot`` of a
    grid whose other slots stand idle, then ``steps`` single-token steps
    through the slot cache, feeding each step its own greedy token: the
    logits the slot programs sample from ([1 + steps, V]), the tokens,
    and the cache."""
    cache = _grid(config)[0] if cache is None else cache
    left, logits0 = generation._prefill_forward(
        params, _padded(prompt), jnp.array([len(prompt)]), config,
        transformer.DEFAULT_RULES, None)
    cache = generation._write_prefill(cache, left, (0, slot, 0, 0, 0),
                                      config)
    rows, tokens = [logits0[0]], [int(jnp.argmax(logits0[0]))]
    idle = BUCKET + NEW  # out of range: the other slots write nowhere
    for i in range(steps):
        tok = jnp.zeros((SLOTS,), jnp.int32).at[slot].set(tokens[-1])
        pos = jnp.zeros((SLOTS,), jnp.int32).at[slot].set(len(prompt) + i)
        write = jnp.full((SLOTS,), idle, jnp.int32).at[slot].set(
            len(prompt) + i)
        cache, logits = generation._decode_step(
            params, cache, tok, pos, config, transformer.DEFAULT_RULES, None,
            write_pos=write)
        rows.append(logits[slot])
        tokens.append(int(jnp.argmax(logits[slot])))
    return np.stack(rows), tokens, cache


# -- (a) prefill at a bucket, then decode through the slot cache ----------


def test_prefill_then_two_chunks_of_decode_match_the_full_forward_pass(
        model):
    params, config = model.params, model.config
    prompt = _prompt(11)
    served, tokens, _ = _served_logits(params, prompt, 1, 2 * CHUNK,
                                       config=config)
    full = reference_logits(params, np.concatenate([prompt, tokens[:-1]]),
                            model.sizes)
    assert gap(served, full[len(prompt) - 1:]) < LOGIT_TOLERANCE
    # The slot programs emit exactly the tokens those logits put first.
    cache, state = _grid(config)
    cache, state, tok0 = _insert(params, cache, state, prompt, 1, NEW,
                                 config)
    emitted = [int(tok0)]
    for _ in range(2):
        cache, state, toks, valid = _chunk(params, cache, state, config)
        assert bool(valid[1].all()) and not bool(valid[0].any())
        emitted += [int(t) for t in toks[1]]
    assert emitted == tokens


@pytest.mark.parametrize("length", [5, 12, 13, 16])
def test_insert_at_the_prompts_width_leaves_the_whole_buffers_state(
        params, monkeypatch, length):
    """A 16-row buffer in tiles of 4 runs a prompt of up to 12 tokens at
    12 rows (``generation.prefill_widths``): the slot's state, its
    convolution tail, its K/V rows before the prompt's end and the first
    token's logits are what the forward over all 16 rows leaves (the
    mixer masks the padding; a narrower buffer only holds less of it)."""
    monkeypatch.setattr(generation, "PREFILL_TILE_ROWS", 4)
    assert generation.prefill_widths(BUCKET) == (12, 16)
    prompt = _prompt(length, seed=5)
    cache, state = _grid()
    got, _, tok0 = _insert(params, dict(cache), dict(state), prompt, 1, NEW)
    left, logits0 = generation._prefill_forward(
        params, _padded(prompt), jnp.array([length]), CONFIG,
        transformer.DEFAULT_RULES, None)
    want = generation._write_prefill(dict(cache), left, (0, 1, 0, 0, 0),
                                     CONFIG)
    _, got_logits = generation._prefill_into(
        params, dict(cache), _padded(prompt), jnp.array([length]),
        (0, 1, 0, 0, 0), CONFIG, transformer.DEFAULT_RULES, None)
    assert gap(got_logits, logits0) < LOGIT_TOLERANCE
    assert int(tok0) == int(jnp.argmax(logits0[0]))
    for name in generation.STATE_LEAVES:
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]),
                                   rtol=1e-5, atol=1e-6)
        assert np.asarray(got[name][:, 1]).any()
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(got[name][:, 1, :length]),
                                   np.asarray(want[name][:, 1, :length]),
                                   rtol=1e-5, atol=1e-6)


def test_generate_is_pinned_to_apply_and_to_the_reference(params):
    prompt = _prompt(9, seed=3)
    out = generation.generate(params, _padded(prompt),
                              jnp.array([len(prompt)]), CONFIG,
                              max_new_tokens=6)
    tokens = np.concatenate([prompt, np.asarray(out["tokens"][0])])
    logits, _ = transformer.apply(params, jnp.asarray(tokens)[None], CONFIG)
    assert gap(logits[0], reference_logits(params, tokens)) < LOGIT_TOLERANCE
    greedy = np.argmax(np.asarray(logits[0]), -1)[len(prompt) - 1:-1]
    assert greedy.tolist() == np.asarray(out["tokens"][0]).tolist()


MULTIPLIERS = [f.name for f in dataclasses.fields(transformer.Multipliers)
               if f.name != "ssm"] + [f"ssm.{i}" for i in range(5)]


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_dropping_any_one_multiplier_fails(params, name):
    mult = CONFIG.multipliers
    if name.startswith("ssm."):
        segments = list(mult.ssm)
        segments[int(name[4:])] = 1.0
        dropped = dataclasses.replace(mult, ssm=tuple(segments))
    else:
        dropped = dataclasses.replace(
            mult, **{name: None if name == "embedding" else 1.0})
    tokens = _prompt(13, seed=5)
    logits, _ = transformer.apply(params, jnp.asarray(tokens)[None],
                                  CONFIG.scaled(multipliers=dropped))
    assert gap(logits[0], reference_logits(params, tokens)) > 100 * \
        LOGIT_TOLERANCE


# -- (b) the chunked scan against the token-by-token recurrence -----------


def test_chunked_scan_matches_the_recurrence_off_the_chunk_grid(params):
    cfg, mult, eps = CONFIG.ssm, CONFIG.multipliers, CONFIG.norm_eps
    layer = jax.tree_util.tree_map(lambda x: x[0], params["layers"]["ssm"])
    length, lens = 11, np.array([11, 7])  # chunks of 4: neither divides
    u = jax.random.normal(jax.random.PRNGKey(7), (2, length, CONFIG.dim))
    mask = (np.arange(length)[None, :] < lens[:, None]).astype(np.int32)
    out, state, tail = ssm.ssd_prefill(layer, u, jnp.asarray(mask),
                                       jnp.asarray(lens), cfg, mult, eps)
    for row, n in enumerate(lens):
        h = jnp.zeros((1, cfg.num_heads, cfg.head_dim, cfg.state_dim))
        c = jnp.zeros((1, cfg.conv_width - 1, cfg.conv_dim))
        for t in range(n):
            y, h, c = ssm.ssm_step(layer, u[row:row + 1, t], h, c, cfg, mult,
                                   eps)
            np.testing.assert_allclose(out[row, t], y[0], rtol=2e-4,
                                       atol=2e-5)
        # The state and the tail AT the row's last real token: the
        # padding after it has left both alone.
        np.testing.assert_allclose(state[row], h[0], rtol=2e-4, atol=1e-6)
        np.testing.assert_array_equal(tail[row], c[0])


# -- (c) slots a chunk apart; a frozen slot; a reused slot ----------------


def test_frozen_slot_keeps_its_state_and_a_reused_slot_carries_nothing(
        model):
    params, config = model.params, model.config
    first, second, short, late = (_prompt(n, seed=n) for n in (10, 16, 5, 7))
    cache, state = _grid(config)
    cache, state, _ = _insert(params, cache, state, first, 0, NEW, config)
    # The third slot's request ends inside this chunk (2 of 4 steps).
    cache, state, _ = _insert(params, cache, state, short, 2, 3, config)
    cache, state, toks_a, valid = _chunk(params, cache, state, config)
    assert valid[2].tolist() == [True, True, False, False]
    assert not bool(state["active"][2])
    # A chunk later the second slot is inserted, beside the running first.
    cache, state, _ = _insert(params, cache, state, second, 1, NEW, config)
    frozen = {name: np.asarray(cache[name][:, 2]) for name in
              generation.STATE_LEAVES}
    running = {name: np.asarray(cache[name][:, 0]) for name in
               generation.STATE_LEAVES}
    cache, state, toks_b, valid = _chunk(params, cache, state, config)
    assert bool(valid[0].all()) and bool(valid[1].all())
    assert not bool(valid[2].any())
    for name, before in frozen.items():
        # Bit for bit: the retired slot rode a whole chunk untouched...
        np.testing.assert_array_equal(before, np.asarray(cache[name][:, 2]))
        # ...while the live ones moved.
        assert not np.array_equal(running[name],
                                  np.asarray(cache[name][:, 0]))
    # Each live slot decodes as it would alone.
    alone = _served_logits(params, first, 0, 2 * CHUNK, config=config)[1]
    assert [int(t) for t in toks_a[0]] + [int(t) for t in toks_b[0]] == \
        alone[1:]
    # The retired slot is reused: the newcomer's state and tokens are
    # those of a grid that never held anything.
    cache, state, tok0 = _insert(params, cache, state, late, 2, NEW, config)
    fresh_cache, fresh_state = _grid(config)
    fresh_cache, fresh_state, fresh_tok0 = _insert(
        params, fresh_cache, fresh_state, late, 2, NEW, config)
    assert int(tok0) == int(fresh_tok0)
    for name in generation.STATE_LEAVES:
        np.testing.assert_array_equal(np.asarray(cache[name][:, 2]),
                                      np.asarray(fresh_cache[name][:, 2]))
    cache, state, toks, _ = _chunk(params, cache, state, config)
    fresh_cache, fresh_state, fresh_toks, _ = _chunk(
        params, fresh_cache, fresh_state, config)
    assert toks[2].tolist() == fresh_toks[2].tolist()
    for name in generation.STATE_LEAVES:
        np.testing.assert_array_equal(np.asarray(cache[name][:, 2]),
                                      np.asarray(fresh_cache[name][:, 2]))


# -- (c') the state-step kernel against the jnp step ----------------------

#: Widths that keep Falcon-H1's state tile (P = 128, N = 256) and a block
#: of 8 heads, small everywhere else.
WIDE_SIZES = {**SIZES, "mamba_n_heads": 8, "mamba_d_head": 128,
              "mamba_d_ssm": 1024, "mamba_d_state": 256}
WIDE = serve_hybrid.model_config(WIDE_SIZES, MIX).scaled(dtype=jnp.float32)
LIVE_MASKS = {"mixed": [False, True, False, True, True], "all": [True] * 5,
              "none": [False] * 5,
              "first-and-last-frozen": [False, False, True, True, False]}


@pytest.fixture(scope="module")
def wide_params():
    return falcon_h1.make_params(SEED, WIDE_SIZES, dtype=jnp.float32)


@pytest.mark.parametrize("mask", LIVE_MASKS.values(), ids=LIVE_MASKS)
def test_state_kernel_matches_the_jnp_step(wide_params, mask):
    """One mixer step over a stacked leaf, the kernel (interpreted)
    against ``ssm_step``: the mixer's output and the new state of a row
    that advances to float32 rounding; every other byte of the leaf, a
    frozen row's and the other layer's, as it was."""
    cfg, mult, eps = WIDE.ssm, WIDE.multipliers, WIDE.norm_eps
    layer = jax.tree_util.tree_map(lambda x: x[1],
                                   wide_params["layers"]["ssm"])
    live = np.array(mask)
    keys = jax.random.split(jax.random.PRNGKey(31), 3)
    u = jax.random.normal(keys[0], (len(mask), WIDE.dim))
    states = jax.random.normal(
        keys[1], (2, len(mask), cfg.num_heads, cfg.head_dim, cfg.state_dim))
    conv = jax.random.normal(
        keys[2], (len(mask), cfg.conv_width - 1, cfg.conv_dim))
    want_out, want_state, want_tail = ssm.ssm_step(
        layer, u, states[1], conv, cfg, mult, eps)
    traced = ssm_state.KERNEL_TRACE_COUNT
    out, new, tail = ssm.ssm_step_in_place(
        layer, u, states, 1, jnp.asarray(live), conv, cfg, mult, eps)
    assert ssm_state.KERNEL_TRACE_COUNT == traced + 1
    np.testing.assert_allclose(out[live], want_out[live], rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_allclose(new[1][live], want_state[live], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(tail, want_tail)
    np.testing.assert_array_equal(new[1][~live], states[1][~live])
    np.testing.assert_array_equal(new[0], states[0])


@pytest.mark.parametrize("mask", list(LIVE_MASKS.values())[:3],
                         ids=list(LIVE_MASKS)[:3])
def test_decode_step_with_the_kernel_freezes_state_and_tail_bit_for_bit(
        wide_params, monkeypatch, mask):
    """A decode step of five slots through ``_scan_layers``, the state
    kernel in its layer loop against the ``jnp`` step: logits and state
    of a slot that advances agree to float32 rounding, and a slot whose
    write is suppressed keeps its state AND its convolution's tail bit
    for bit, in every layer."""
    slots, rows = len(mask), BUCKET + NEW
    live = np.array(mask)
    cache = generation.init_slot_cache(WIDE, slots, rows)
    keys = jax.random.split(jax.random.PRNGKey(32), len(cache))
    cache = {name: jax.random.normal(key, leaf.shape, leaf.dtype)
             for key, (name, leaf) in zip(keys, sorted(cache.items()))}
    tok = jnp.arange(1, slots + 1, dtype=jnp.int32)
    pos = jnp.full((slots,), 6, jnp.int32)
    write = jnp.where(jnp.asarray(live), pos, rows)  # out of range: frozen

    def step():
        return generation._decode_step(
            wide_params, dict(cache), tok, pos, WIDE,
            transformer.DEFAULT_RULES, None, write_pos=write)

    want_cache, want_logits = step()
    monkeypatch.setenv("CLOUD_TPU_FLASH_FORCE_INTERPRET", "1")
    traced = ssm_state.KERNEL_TRACE_COUNT
    got_cache, logits = step()
    assert ssm_state.KERNEL_TRACE_COUNT == traced + 1  # one call a loop
    np.testing.assert_allclose(logits[live], want_logits[live], rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(got_cache["ssm"], want_cache["ssm"],
                               rtol=1e-6, atol=1e-6)
    for name in generation.STATE_LEAVES:
        np.testing.assert_array_equal(got_cache[name][:, ~live],
                                      cache[name][:, ~live])
        if live.any():
            assert not np.array_equal(got_cache[name][:, live],
                                      cache[name][:, live])
    np.testing.assert_allclose(got_cache["conv"], want_cache["conv"],
                               rtol=1e-4, atol=1e-5)


def test_state_kernel_is_taken_by_what_the_code_can_see(monkeypatch):
    """``takes_kernel``: off a TPU only with the interpreter armed; never
    for a state that is not float32 or does not tile; ``use_pallas=True``
    raises where the kernel cannot run."""
    tiled = jax.ShapeDtypeStruct((2, 3, 8, 16, 128), jnp.float32)
    untiled = jax.ShapeDtypeStruct((2, 3, 4, 16, 16), jnp.float32)
    narrow = jax.ShapeDtypeStruct(tiled.shape, jnp.bfloat16)
    assert not ssm_state.takes_kernel(tiled, 2)
    assert ssm_state.takes_kernel(tiled, 2, use_pallas=True)
    monkeypatch.setenv("CLOUD_TPU_FLASH_FORCE_INTERPRET", "1")
    assert ssm_state.takes_kernel(tiled, 2)
    assert not ssm_state.takes_kernel(tiled, 2, use_pallas=False)
    for leaf in (untiled, narrow):
        assert not ssm_state.takes_kernel(leaf, 2)
        with pytest.raises(ValueError, match="float32"):
            ssm_state.takes_kernel(leaf, 2, use_pallas=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("CLOUD_TPU_FLASH_FORCE_INTERPRET")
    assert ssm_state.takes_kernel(tiled, 2)


# -- (d) a state in bfloat16 is caught ------------------------------------


def test_state_in_bfloat16_fails_the_tolerance_float32_passes(params):
    prompt = _prompt(14, seed=2)
    steps = 2 * CHUNK
    sound, tokens, _ = _served_logits(params, prompt, 0, steps)
    full = reference_logits(params, np.concatenate([prompt, tokens[:-1]]))
    assert gap(sound, full[len(prompt) - 1:]) < LOGIT_TOLERANCE
    # The programs store the state in the leaf's own type: a grid whose
    # ``ssm`` leaf is bfloat16 rounds it at the insert and at every step.
    cache = _grid()[0]
    cache["ssm"] = cache["ssm"].astype(jnp.bfloat16)
    narrow, narrow_tokens, _ = _served_logits(params, prompt, 0, steps,
                                              cache=cache)
    full = reference_logits(
        params, np.concatenate([prompt, narrow_tokens[:-1]]))
    assert gap(narrow[1:], full[len(prompt):]) > 3 * LOGIT_TOLERANCE


# -- (e) what refuses a recurrent state -----------------------------------


def _draft():
    tiny = transformer.TINY.scaled(vocab_size=SIZES["vocab_size"],
                                   num_layers=1)
    return DraftConfig(config=tiny, params=transformer.init(
        jax.random.PRNGKey(0), tiny), spec_k=2)


@pytest.mark.parametrize("serve", [
    dict(prefix_cache_blocks=4),
    dict(prefill_chunk_tokens=4),
    dict(decode_kernel="pallas"),
    dict(decode_kernel="auto"),
    dict(draft=_draft),
    dict(role="prefill", prefix_cache_blocks=4),
    dict(mesh_shape=(2, 1)),
    dict(layout="auto"),
], ids=lambda d: "+".join(d))
def test_engine_features_that_refuse_a_recurrent_state(params, serve):
    serve = {k: v() if callable(v) else v for k, v in serve.items()}
    with pytest.raises(NotImplementedError, match="recurrent state"):
        ServingEngine(params, CONFIG, ServeConfig(
            prompt_buckets=(BUCKET,), max_new_tokens=NEW, num_slots=SLOTS,
            **serve), start=False)


def test_programs_that_refuse_a_recurrent_state(params):
    cache, state = _grid()
    tokens = _padded(_prompt(8))
    refused = [
        lambda: generation.beam_search(
            params, tokens, jnp.array([8]), CONFIG, num_beams=2,
            max_new_tokens=2),
        lambda: generation.prefill_chunk_program(
            params, cache, tokens[:, :4], 0, 4, 0, CONFIG),
        lambda: generation.verify_chunk_program(
            params, cache, state, jnp.zeros((SLOTS, 2), jnp.int32), CONFIG),
        lambda: generation.init_prefix_pool(CONFIG, 4, 4),
    ]
    for call in refused:
        with pytest.raises(NotImplementedError, match="recurrent state"):
            call()


def test_engine_serves_the_hybrid_through_submit_and_counts_its_state(
        params):
    engine = ServingEngine(params, CONFIG, ServeConfig(
        prompt_buckets=(8, BUCKET), max_new_tokens=NEW, num_slots=2,
        chunk_tokens=CHUNK))
    try:
        prompts = [_prompt(n, seed=n) for n in (5, 11, 16, 3, 9)]
        budgets = (NEW, 4, 7, NEW, 2)
        futures = [engine.submit(p, max_new_tokens=m)
                   for p, m in zip(prompts, budgets)]
        for prompt, future, budget in zip(prompts, futures, budgets):
            served = future.result(timeout=300).tokens[:budget]
            alone = generation.generate(
                params, _padded(prompt), jnp.array([len(prompt)]), CONFIG,
                max_new_tokens=budget)["tokens"][0]
            assert list(served) == np.asarray(alone).tolist()
        stats, health = engine.stats(), engine.health()
    finally:
        engine.close()
    layers_, m = CONFIG.num_layers, CONFIG.ssm
    state_bytes = 2 * layers_ * (
        4 * m.num_heads * m.head_dim * m.state_dim
        + 4 * (m.conv_width - 1) * m.conv_dim)
    assert stats["state_bytes_reserved"] == state_bytes
    assert health["state_bytes_reserved"] == state_bytes
    # K/V bytes count the 2 K/V heads, not the 4 query heads.
    assert stats["kv_bytes_reserved"] == (
        2 * layers_ * 2 * (BUCKET + NEW) * CONFIG.kv_heads
        * CONFIG.head_dim * 4)
    assert stats["kv_bytes_reserved"] == engine._kv_bytes_estimate()
    chunks = stats["chunks"]
    assert stats["state_row_steps_reserved"] == chunks * 2 * layers_
    assert 0 < stats["state_row_steps_in_use"] <= \
        stats["state_row_steps_reserved"]
    assert stats["state_row_steps_in_use"] % layers_ == 0


# -- (f) a model without multipliers computes what it computed before -----


def _parent_logits(params, tokens, config):
    """The forward pass as the tree before this PR spelled it: sqrt(dim)
    on the embedding, eps 1e-6, as many K/V heads as query heads, no
    multiplier anywhere."""
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    x = layers.embedding_apply(params["embed"], tokens, dtype=config.dtype)
    x = x * math.sqrt(config.dim)

    def block(x, p):
        y = layers.rmsnorm_apply(p["ln1"], x)

        def proj(w):
            return layers.dense_apply(w, y).reshape(
                b, t, config.num_heads, config.head_dim)

        q = layers.rotary_embedding(proj(p["att"]["q"]), positions,
                                    base=config.rope_base)
        k = layers.rotary_embedding(proj(p["att"]["k"]), positions,
                                    base=config.rope_base)
        attended = layers.sharded_attention(q, k, proj(p["att"]["v"]),
                                            causal=True)
        x = x + layers.dense_apply(p["att"]["out"],
                                   attended.reshape(b, t, -1))
        y = layers.rmsnorm_apply(p["ln2"], x)
        return x + layers.mlp_block_apply(p["mlp"], y), None

    x, _ = jax.lax.scan(block, x, params["layers"])
    x = layers.rmsnorm_apply(params["ln_f"], x).astype(jnp.float32)
    return jnp.einsum("...d,dv->...v", x,
                      params["head"]["kernel"].astype(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_default_multipliers_give_the_parents_logits_bit_for_bit(dtype):
    config = transformer.TINY.scaled(dtype=dtype)
    params = transformer.init(jax.random.PRNGKey(28), config)
    tokens = jnp.asarray(_prompt(24, seed=9).reshape(2, 12))
    logits, _ = transformer.apply(params, tokens, config)
    np.testing.assert_array_equal(
        np.asarray(logits), np.asarray(_parent_logits(params, tokens,
                                                      config)))
    assert config.multipliers == transformer.Multipliers()
    assert config.kv_heads == config.num_heads and config.ssm is None


def test_hybrid_config_survives_an_export_round_trip(params, tmp_path):
    from cloud_tpu.models import export

    export.save_pretrained(str(tmp_path / "m"), params, CONFIG)
    _, loaded = export.load_pretrained(str(tmp_path / "m"))
    # The nested configs come back whole, the five segment multipliers
    # as a tuple again (JSON gives a list; a jit-static config hashes).
    assert loaded == CONFIG and loaded.ssm == CONFIG.ssm
    assert isinstance(loaded.multipliers.ssm, tuple)
    hash(loaded.multipliers)
