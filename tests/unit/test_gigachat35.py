"""GigaChat3.5's block — gated delta-rule layers interleaved 3:1 with latent
attention, over a chip's share of dropless experts — against its plain
reference, at the configuration's ``tiny`` sizes on the CPU: seeded random
weights, float32 compute, so that what differs is the algorithm (the
chunked prefill, the kernel's step, per-kind cache leaves, slots) and not
the rounding.  And the three configurations that were there: their slot
programs trace to the parent's jaxprs.
"""

import hashlib
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

from benchmarks.adapters import (serve_engine, serve_hybrid,  # noqa: E402
                                 serve_hybrid_latent_moe, serve_latent_moe)
from benchmarks.references import gigachat35  # noqa: E402
from cloud_tpu.models import (delta_net, generation, moe,  # noqa: E402
                              transformer)
from cloud_tpu.ops import delta_state  # noqa: E402
from cloud_tpu.serving import ServeConfig, ServingEngine  # noqa: E402

SEED = 2 ** 31 + 39
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "gigachat35-ep16-stage.json")) as f:
    _FILE = json.load(f)
#: The tiny sizes: 16 experts of which this share holds [4, 8).
SIZES = {**_FILE, **_FILE["tiny"], "expert_offset": 4}
BUCKETS, NEW = (16, 32), 8
MIX = {"engine": {"prompt_buckets": list(BUCKETS), "max_new_tokens": NEW}}
CONFIG = serve_hybrid_latent_moe.model_config(SIZES, MIX)
GREEDY = generation.SampleConfig(temperature=0.0)

#: Program against reference in units of the row's logit standard
#: deviation, both in float32: they differ in the order of sums (the
#: chunked form's triangular solve against the per-token recurrence, a
#: latent cache read in the absorbed form, rows sorted by expert) and read
#: about 2e-5 on these prompts.  The limit sits five times over that; a
#: choice of experts that flipped, or a state a token off, reads 1e-2 and
#: more.
LOGIT_TOLERANCE = 1e-4


@pytest.fixture(scope="module", autouse=True)
def _small_fit():
    """The fit of ``b`` on 4 x 16 tokens: a test needs no better one."""
    saved = gigachat35.CALIBRATION_SEQUENCES, gigachat35.CALIBRATION_LENGTH
    gigachat35.CALIBRATION_SEQUENCES, gigachat35.CALIBRATION_LENGTH = 4, 16
    yield
    gigachat35.CALIBRATION_SEQUENCES, gigachat35.CALIBRATION_LENGTH = saved


@pytest.fixture(scope="module")
def params():
    return gigachat35.make_params(SEED, SIZES, dtype=jnp.float32)


def _prompt(length, seed=0):
    return np.random.default_rng(seed).integers(
        1, SIZES["vocab_size"], length).astype(np.int32)


#: The reference reads every sequence in a [6, 40] buffer (the widest
#: prompt's bucket and the answer; whole batches of its delta layers'
#: ``DELTA_BATCH``): one compile of each of its layer programs serves the
#: whole file.
REFERENCE_ROWS, REFERENCE_WIDTH = 2 * gigachat35.DELTA_BATCH, BUCKETS[-1] + NEW


def reference_logits(params, tokens):
    """The reference's full forward pass over one sequence: [T, V]."""
    buf = np.zeros((REFERENCE_ROWS, REFERENCE_WIDTH), np.int32)
    buf[0, :len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        xs, _, _ = gigachat35.forward(SEED, SIZES, buf, "f32", jnp.float32)
        y = gigachat35._rmsnorm(xs[0, :len(tokens)],
                                params["ln_f"]["scale"],
                                SIZES["rms_norm_eps"])
        return np.asarray(gigachat35.matmul(y, params["head"]["kernel"],
                                            "f32"))


def gap(logits, reference):
    logits, reference = np.asarray(logits), np.asarray(reference)
    return float(np.max(np.abs(logits - reference)
                        / np.std(reference, axis=-1, keepdims=True)))


# -- (a) the chunked prefill is the recurrence -------------------------------

#: A mixer at tiny widths with heads of 8 and chunks of 8 tokens.
MIXER = delta_net.DeltaNetConfig(key_heads=2, value_heads=4, key_dim=8,
                                 value_dim=8, chunk_size=8)


@pytest.fixture(scope="module")
def mixer():
    p, _ = delta_net.delta_init(jax.random.PRNGKey(3), 32, MIXER)
    # A decay strong enough to matter over a chunk.
    return dict(p, A_log=p["A_log"] + 1.0)


@pytest.fixture(scope="module")
def recurrence(mixer):
    """The per-token steps (``delta_step``) over a 24-token buffer from a
    zero state: each position's output, state and tail."""
    u = jax.random.normal(jax.random.PRNGKey(4), (3, 24, 32), jnp.float32)

    def step(carry, u_t):
        state, conv = carry
        out, state, conv = delta_net.delta_step(mixer, u_t, state, conv,
                                                MIXER)
        return (state, conv), (out, state, conv)

    zeros = (jnp.zeros((3, 4, 8, 8)), jnp.zeros((3, 3, MIXER.conv_dim)))
    _, (outs, states, tails) = jax.jit(
        lambda u: jax.lax.scan(step, zeros, jnp.moveaxis(u, 1, 0)))(u)
    return u, jnp.moveaxis(outs, 0, 1), states, tails


@pytest.mark.parametrize("lengths", [(7, 8, 19), (24, 1, 16)],
                         ids=["off-and-on-the-chunk-edge", "whole-and-one"])
def test_chunked_prefill_is_the_recurrence(mixer, recurrence, lengths):
    """``delta_prefill`` over a right-padded 24-token buffer (three chunks
    of 8) against the per-token steps: every real position's output, and
    the state and the convolution's tail at each row's real length — the
    padding after it leaves both alone."""
    u, outs, states, tails = recurrence
    lens = jnp.asarray(lengths)
    mask = (jnp.arange(24)[None] < lens[:, None]).astype(jnp.int32)
    out, state, tail = jax.jit(
        lambda u, mask, lens: delta_net.delta_prefill(mixer, u, mask, lens,
                                                      MIXER))(u, mask, lens)
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(out[row, :n], outs[row, :n], rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(state[row], states[n - 1, row],
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(tail[row], tails[n - 1, row], rtol=1e-5,
                                   atol=1e-6)


# -- (b) the kernel's step is the jnp step -----------------------------------


@pytest.fixture(scope="module")
def kernel_inputs():
    """Two layers of a 4-row leaf, 16 value heads of 128 x 128 (two
    blocks a row at a block of 8 heads), and one token's inputs."""
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    b, h, d = 4, 16, 128

    def unit(key, shape):
        x = jax.random.normal(key, shape, jnp.float32)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    states = jax.random.normal(keys[0], (2, b, h, d, d), jnp.float32)
    return (states, unit(keys[1], (b, h, d)) * d ** -0.5,
            unit(keys[2], (b, h, d)),
            jax.random.normal(keys[3], (b, h, d), jnp.float32),
            jax.random.uniform(keys[4], (b, h), jnp.float32, 0.3, 1.0),
            jax.random.uniform(keys[5], (b, h), jnp.float32))


@pytest.mark.parametrize("live", [(1, 0, 1, 1), (0, 0, 0, 0), (0, 1, 0, 0)],
                         ids=["one-frozen", "none-live", "one-live"])
def test_the_kernels_step_is_the_jnp_step(monkeypatch, kernel_inputs, live):
    """``ops.delta_state`` interpreted against the gated delta rule in
    ``jnp`` (``delta_step``'s own arithmetic) on layer 1 of the leaf: a
    live row's state and ``y`` to float32's rounding, a frozen row's state
    bit for bit and its ``y`` zeros, layer 0 untouched."""
    monkeypatch.setattr(delta_state, "BLOCK_BYTES", 8 * 128 * 128 * 4)
    states, q, k, v, alpha, beta = kernel_inputs
    live = jnp.asarray(live, bool)
    traced = delta_state.KERNEL_TRACE_COUNT
    new, y = jax.jit(lambda s, *a: delta_state.state_step(s, 1, live, *a),
                     donate_argnums=0)(jnp.copy(states), q, k, v, alpha,
                                       beta)
    assert delta_state.KERNEL_TRACE_COUNT > traced
    state = states[1]
    read = jnp.sum(state * k[..., :, None], axis=-2)
    fresh = beta[..., None] * (v - alpha[..., None] * read)
    want = alpha[..., None, None] * state + k[..., :, None] * fresh[..., None, :]
    want_y = jnp.sum(want * q[..., :, None], axis=-2)
    np.testing.assert_array_equal(new[0], states[0])
    for row, on in enumerate(np.asarray(live)):
        if on:
            np.testing.assert_allclose(new[1, row], want[row], rtol=1e-5,
                                       atol=1e-5)
            np.testing.assert_allclose(y[row], want_y[row], rtol=1e-4,
                                       atol=1e-4)
        else:
            np.testing.assert_array_equal(new[1, row], states[1, row])
            np.testing.assert_array_equal(y[row], 0.0)


def test_the_kernel_takes_what_it_can_tile():
    leaf = jax.ShapeDtypeStruct((4, 96, 64, 128, 128), jnp.float32)
    assert delta_state._heads_per_block(64, 128 * 128 * 4) == 32
    assert delta_state.takes_kernel(leaf, use_pallas=None) is False  # CPU
    assert delta_state.takes_kernel(leaf, use_pallas=True)
    for bad in (jax.ShapeDtypeStruct((4, 3, 4, 16, 16), jnp.float32),
                jax.ShapeDtypeStruct((4, 3, 64, 128, 128), jnp.bfloat16)):
        assert not delta_state._kernel_eligible(bad)
        with pytest.raises(ValueError, match="delta state step"):
            delta_state.takes_kernel(bad, use_pallas=True)


# -- (c) the engine's logits are the reference's -----------------------------


@jax.jit
def _prefill_into_slot(params, cache, buf, length, slot):
    zero = jnp.int32(0)
    left, logits0 = generation._prefill_forward(
        params, buf, jnp.reshape(length, (1,)), CONFIG,
        generation.DEFAULT_RULES, None)
    return generation._write_prefill(cache, left, (zero, slot, zero, zero,
                                                   zero), CONFIG), logits0


@jax.jit
def _decode_step(params, cache, token, pos, write):
    return generation._decode_step(params, cache, token, pos, CONFIG,
                                   generation.DEFAULT_RULES, None,
                                   write_pos=write)


def _served_logits(params, prompt, steps, slot=1, slots=3):
    """Prefill ``prompt`` into ``slot`` of a grid whose other slots stand
    idle (one bucket of 32 rows for every prompt), then ``steps``
    single-token steps through the slot cache, each fed its own greedy
    token: the logits sampled from, the tokens, the cache."""
    cache = generation.init_slot_cache(CONFIG, slots, BUCKETS[-1] + NEW)
    buf = np.zeros((1, BUCKETS[-1]), np.int32)
    buf[0, :len(prompt)] = prompt
    cache, logits0 = _prefill_into_slot(params, cache, buf,
                                        jnp.int32(len(prompt)),
                                        jnp.int32(slot))
    rows, tokens = [logits0[0]], [int(jnp.argmax(logits0[0]))]
    pos = np.zeros((slots,), np.int32)
    for step in range(steps):
        pos[slot] = len(prompt) + step
        token = np.zeros((slots,), np.int32)
        token[slot] = tokens[-1]
        write = np.full((slots,), BUCKETS[-1] + NEW, np.int32)
        write[slot] = pos[slot]
        cache, logits = _decode_step(params, cache, token, pos, write)
        rows.append(logits[slot])
        tokens.append(int(jnp.argmax(logits[slot])))
    return np.stack(rows), tokens[:-1], cache


@pytest.mark.parametrize("length", [5, 23])
def test_prefill_then_decode_agrees_with_the_reference(params, length):
    """Prefill (the chunked form, the expanded latent read) into one slot
    of an idle grid, then decode (the delta step, the absorbed read)
    through the per-kind leaves: every logit against the reference's full
    forward pass; the idle slots' state leaves stay zeros."""
    prompt = _prompt(length, seed=length)
    served, tokens, cache = _served_logits(params, prompt, 4)
    reference = reference_logits(params, np.concatenate([prompt, tokens]))
    assert gap(served, reference[length - 1:]) < LOGIT_TOLERANCE
    assert set(cache) == {"latent", "delta", "delta_conv"}
    assert cache["delta"].shape[:2] == (4, 3)
    assert cache["latent"].shape[:2] == (1, 3)
    for name in ("delta", "delta_conv"):
        assert not np.any(np.asarray(cache[name])[:, [0, 2]])
        assert np.any(np.asarray(cache[name])[:, 1])


def test_submit_over_two_buckets_and_a_reused_slot_agrees(params):
    """Through ``ServingEngine.submit``: four requests over two buckets
    and two slots (both reused); each served token is the reference's
    best at its position, to the tolerance; the state rows are counted a
    slot a delta layer."""
    prompts = [_prompt(n, seed=n) for n in (7, 20, 13, 30)]
    serve = ServeConfig(prompt_buckets=BUCKETS, max_new_tokens=NEW,
                        num_slots=2, chunk_tokens=4, warmup=False)
    with ServingEngine(params, CONFIG, serve) as engine:
        futures = [engine.submit(p, max_new_tokens=NEW - (i % 3))
                   for i, p in enumerate(prompts)]
        results = [f.result(timeout=300) for f in futures]
        stats = engine.stats()
    width = BUCKETS[-1] + NEW
    tokens = np.zeros((REFERENCE_ROWS, width), np.int32)
    rows = np.zeros((REFERENCE_ROWS, NEW), np.int32)
    chosen, valid = np.zeros_like(rows), np.zeros(rows.shape, bool)
    for i, (p, r) in enumerate(zip(prompts, results)):
        served = list(r.tokens[:r.num_generated])
        assert len(served) == NEW - (i % 3)
        tokens[i, :len(p) + len(served)] = np.concatenate([p, served])
        rows[i, :len(served)] = len(p) - 1 + np.arange(len(served))
        chosen[i, :len(served)], valid[i, :len(served)] = served, True
    scores = gigachat35.score(SEED, SIZES, tokens, rows, chosen, "f32",
                              jnp.float32)
    gaps = (scores["best"] - scores["chosen"]) / scores["std"]
    assert float(np.max(np.where(valid, gaps, 0.0))) < LOGIT_TOLERANCE
    # Every real token's assignments in four expert layers.
    made = sum(len(p) + NEW - (i % 3) - 1 for i, p in enumerate(prompts))
    assert stats["expert_assignments"] == (
        made * SIZES["num_experts_per_tok"] * 4)
    # One state row a slot a delta layer (4 of the 5), read whole off the
    # kernel: every reserved row.
    assert stats["state_row_steps_reserved"] == stats["chunks"] * 2 * 4
    assert stats["state_row_steps_read"] == stats["state_row_steps_reserved"]
    assert 0 < stats["state_row_steps_in_use"] <= \
        stats["state_row_steps_reserved"]
    assert stats["kv_bytes_reserved"] == (
        2 * 1 * width * CONFIG.latent.row_width * 4)


# -- (d) the shares add up to the uncut layer --------------------------------


def _share(offset, held):
    return {**SIZES, "n_routed_experts": held, "expert_offset": offset}


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts over 2 shares of 8: what the shares' routed experts
    give, with the shared expert counted once, is the uncut layer of the
    reference (the clamped SwiGLU in both); and the program's share is the
    reference's."""
    key = jax.random.PRNGKey(7)
    m = jnp.asarray(np.random.default_rng(3).standard_normal(
        (24, SIZES["hidden_size"])), jnp.float32)
    bias = 0.02 * jnp.asarray(np.random.default_rng(5).standard_normal(
        SIZES["published"]["n_routed_experts"]), jnp.float32)

    def mlp_of(sizes):
        p = gigachat35.layer_params(key, sizes, jnp.float32, "delta",
                                    False)["mlp"]
        return dict(p, bias=bias)

    with jax.default_matmul_precision("highest"):
        whole = mlp_of(_share(0, 16))
        uncut, _, _ = gigachat35._expert_layer(whole, m, _share(0, 16),
                                               "f32")
        shared = gigachat35._mlp(whole["shared"], m, SIZES, "f32")
        routed = 0.0
        for offset in (0, 8):
            sizes = _share(offset, 8)
            ours, _, _ = gigachat35._expert_layer(mlp_of(sizes), m, sizes,
                                                  "f32")
            config = serve_hybrid_latent_moe.model_config(sizes, MIX)
            theirs, counted = jax.jit(
                lambda p, m, cfg=config.moe: moe.dropless_mlp_apply(
                    p, m, cfg, limit=config.swiglu_limit))(mlp_of(sizes),
                                                           m[None])
            np.testing.assert_allclose(theirs[0], ours, rtol=2e-5, atol=2e-6)
            routed = routed + (ours - shared)
            assert int(counted[0]) == 24 * SIZES["num_experts_per_tok"]
    np.testing.assert_allclose(routed + shared, uncut, rtol=2e-5, atol=2e-6)
    assert float(jnp.max(jnp.abs(routed))) > 0.1  # the experts did work
    # The clamp at the tiny limit of 1 does work here.
    gate = gigachat35.matmul(m, whole["shared"]["wi"]["kernel"], "f32")
    assert float(jnp.max(gate)) > SIZES["swiglu_limit"]


# -- the block's configuration, tree and refusals ----------------------------


def test_the_published_pattern_runs_as_period_stacks(params):
    """The kept layers 0, 4-7 run as a leading dense delta layer and one
    period (delta, delta, delta, latent); each kind has its own stack, its
    own leaves over its own layers, and the tree is ``init``'s."""
    assert CONFIG.layer_kinds == ("delta", "delta", "delta", "latent")
    assert CONFIG.leading_dense_layers == 1
    assert (CONFIG.layers_of("delta"), CONFIG.layers_of("latent")) == (4, 1)
    assert set(params["dense_layers"]) == {"delta"}
    assert set(params["layers"]) == {"delta", "latent"}
    assert params["layers"]["delta"]["mlp"]["wi"].shape[:2] == (3, 4)
    assert "gate" in params["layers"]["latent"]["att"]
    assert {"ln1_post", "ln2_post"} <= set(params["layers"]["latent"])
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(jax.eval_shape(
            lambda: transformer.init(jax.random.PRNGKey(0), CONFIG)))
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda a: 0, transformer.param_logical_axes(CONFIG),
            is_leaf=lambda a: isinstance(a, tuple)))
    with pytest.raises(ValueError, match="whole periods"):
        CONFIG.scaled(num_layers=4)
    with pytest.raises(ValueError, match="mixer's configuration"):
        CONFIG.scaled(delta=None)
    with pytest.raises(NotImplementedError, match="layer_kinds"):
        transformer.apply(params, jnp.ones((1, 4), jnp.int32), CONFIG)


@pytest.mark.parametrize("overrides", [
    dict(prefix_cache_blocks=4, prefix_block_tokens=4),
    dict(prefill_chunk_tokens=8),
    dict(kv_quant=True),
], ids=["prefix-pool", "chunked-prefill", "int8"])
def test_the_refusal_names_both_kinds(params, overrides):
    serve = ServeConfig(prompt_buckets=(16,), max_new_tokens=4, num_slots=2,
                        warmup=False, **overrides)
    with pytest.raises(NotImplementedError,
                       match=r"latent-attention cache and a delta-rule "
                       r"state \(TransformerConfig.latent and "
                       r"TransformerConfig.delta\)"):
        ServingEngine(params, CONFIG, serve, start=False)


# -- (e) the configurations that were there ----------------------------------

#: sha256 (first 16 hex digits) of ``str(jax.make_jaxpr(...))`` of each
#: configuration's insert and chunk programs at its ``tiny`` sizes (the
#: grid of tests/unit/test_device_scopes.py), as the parent commit
#: traces them: a PR that changes one of these programs on purpose says so
#: and writes the new digest here.
PARENT_JAXPRS = {
    ("kv", "insert"): "3c6d019aeab54da6",
    ("kv", "chunk"): "10976f905f44ea64",
    ("ssm", "insert"): "003c5f88b6cec236",
    ("ssm", "chunk"): "daa1c8c6b4302492",
    ("latent_moe", "insert"): "613bc93e0ff6bf2d",
    ("latent_moe", "chunk"): "d592cc2ab7f42d0a",
}
KINDS = {"kv": ("baichuan-7b-l16", serve_engine),
         "ssm": ("falcon-h1-34b-stage", serve_hybrid),
         "latent_moe": ("kimi-k2-ep32-stage", serve_latent_moe)}


@pytest.mark.parametrize("kind,program", sorted(PARENT_JAXPRS))
def test_the_existing_programs_trace_to_the_parents_jaxprs(kind, program):
    name, adapter = KINDS[kind]
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           f"{name}.json")) as f:
        sizes = json.load(f)
    sizes = {**sizes, **sizes["tiny"]}
    bucket, new, slots = 16, 8, 3
    config = adapter.model_config(
        sizes, {"engine": {"prompt_buckets": [bucket],
                           "max_new_tokens": new}})
    shapes = jax.eval_shape(lambda: (
        transformer.init(jax.random.PRNGKey(0), config),
        generation.init_slot_cache(config, slots, bucket + new),
        generation.init_slot_state(config, slots, sample=GREEDY)))
    if program == "insert":
        jaxpr = jax.make_jaxpr(
            lambda p, c, s, t: generation.insert_slot_program(
                p, c, s, t, 11, 1, new, config, sample=GREEDY))(
            *shapes, jax.ShapeDtypeStruct((1, bucket), jnp.int32))
    else:
        jaxpr = jax.make_jaxpr(
            lambda p, c, s: generation.decode_chunk_program(
                p, c, s, config, chunk_size=2, sample=GREEDY))(*shapes)
    digest = hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]
    assert digest == PARENT_JAXPRS[kind, program]


# -- what the configuration, the cache and the kernel's rule accept ----------


@pytest.mark.parametrize("change,match", [
    (dict(layer_kinds=("delta", "window")), "mixer's configuration"),
    (dict(latent=None), "mixer's configuration"),
    (dict(num_layers=6), "whole periods"),
    (dict(leading_dense_layers=5, num_layers=9), "at most one"),
], ids=["unknown-kind", "no-latent", "half-a-period", "dense-over-a-period"])
def test_a_layer_pattern_the_stack_cannot_run_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        CONFIG.scaled(**change)


@pytest.mark.parametrize("slots,rows", [(1, 16), (3, 40), (96, 6144)])
def test_each_kind_has_its_leaves_over_its_own_layers(slots, rows):
    """The latent rows for the one latent layer, the delta state (float32)
    and its tail for the four delta layers, whatever the grid."""
    cache = jax.eval_shape(
        lambda: generation.init_slot_cache(CONFIG, slots, rows))
    d = CONFIG.delta
    assert cache["latent"].shape == (1, slots, rows,
                                     CONFIG.latent.row_width)
    assert cache["delta"].shape == (4, slots, d.value_heads, d.key_dim,
                                    d.value_dim)
    assert cache["delta"].dtype == jnp.float32
    assert cache["delta_conv"].shape == (4, slots, d.conv_width - 1,
                                         d.conv_dim)


@pytest.mark.parametrize("heads,head_bytes,hb", [
    (64, 128 * 128 * 4, 32), (16, 128 * 128 * 4, 16), (12, 128 * 128 * 4, 12),
    (64, 256 * 128 * 4, 16), (24, 128 * 128 * 4, 24), (20, 256 * 256 * 4, None),
])
def test_a_block_is_whole_sublane_tiles_of_heads(heads, head_bytes, hb):
    assert delta_state._heads_per_block(heads, head_bytes) == hb


@pytest.mark.parametrize("kinds,names", [
    (dict(), None),
    (dict(ssm="x"), "a recurrent state"),
    (dict(latent="x"), "a latent-attention cache"),
    (dict(latent="x", delta="x"),
     "a latent-attention cache and a delta-rule state"),
], ids=["kv-rows", "ssm", "latent", "latent-and-delta"])
def test_the_cache_kinds_a_configuration_brings(kinds, names):
    class Fields:
        ssm = latent = delta = None

    config = Fields()
    for field, value in kinds.items():
        setattr(config, field, value)
    kind = generation.cache_kind(config)
    assert (kind and kind[1]) == names
    if names and " and " in names:
        assert kind[0] == "latent and TransformerConfig.delta"
