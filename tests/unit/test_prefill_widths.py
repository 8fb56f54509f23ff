"""ISSUE 34: a prefill does the prompt's work, not the buffer's.

CPU only, a tiny model, the row tile patched from 512 to 8 so that a
buffer of 32 rows has the two widths a 2048 bucket has (24 and 32).  What
the cache holds for rows before the prompt's end, and the logits the first
token is drawn from, are the whole-buffer forward's at every length on
both sides of every tile edge; a buffer with one width IS the whole-buffer
program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu.models import generation, transformer
from cloud_tpu.parallel import mesh as mesh_lib
from cloud_tpu.parallel.sharding import DEFAULT_RULES

TILE, T, SLOTS, NEW = 8, 32, 2, 4
GREEDY = generation.SampleConfig(temperature=0.0)


@pytest.fixture(autouse=True)
def small_tile(monkeypatch):
    monkeypatch.setattr(generation, "PREFILL_TILE_ROWS", TILE)


@pytest.fixture(scope="module")
def model():
    config = transformer.TINY.scaled(dtype=jnp.float32)
    return config, transformer.init(jax.random.PRNGKey(0), config)


def _tokens(rows, width=T, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, width), 1,
                              transformer.TINY.vocab_size)


def _whole_buffer_insert(params, cache, state, tokens, prompt_len, slot,
                         new, config):
    """The insert program as it was before the widths: the forward pass
    over the whole buffer, written into the slot's row."""
    prompt_len = jnp.clip(jnp.asarray(prompt_len, jnp.int32), 1,
                          tokens.shape[1])
    left, logits0 = generation._prefill_forward(
        params, tokens, jnp.reshape(prompt_len, (1,)), config,
        DEFAULT_RULES, None)
    slot = jnp.asarray(slot, jnp.int32)
    zero = jnp.int32(0)
    cache = generation._write_prefill(
        cache, left, (zero, slot, zero, zero, zero), config)
    state, tok0 = generation._arm_slot(
        state, logits0, prompt_len, slot, new, config, sample=GREEDY,
        rng=None)
    return cache, state, tok0, logits0


def _grid(config, rows=T):
    return (generation.init_slot_cache(config, SLOTS, rows + NEW),
            generation.init_slot_state(config, SLOTS, sample=GREEDY))


@pytest.mark.parametrize("t_prompt, tile, widths", [
    (128, 512, (128,)), (512, 512, (512,)), (768, 512, (768,)),
    (1024, 512, (1024,)), (1280, 512, (1280,)), (2048, 512, (1536, 2048)),
    (4096, 512, (2560, 3072, 3584, 4096)), (32, 8, (24, 32)),
    (16, 8, (16,)),
])
def test_widths_are_the_whole_tiles_above_half_the_buffer(
        monkeypatch, t_prompt, tile, widths):
    monkeypatch.setattr(generation, "PREFILL_TILE_ROWS", tile)
    assert generation.prefill_widths(t_prompt) == widths
    assert generation.prefill_rows_computed(t_prompt, 1) == widths[0]
    assert generation.prefill_rows_computed(t_prompt, t_prompt) == t_prompt
    for width in widths:
        assert generation.prefill_rows_computed(t_prompt, width) == width
        if width < t_prompt:
            assert generation.prefill_rows_computed(
                t_prompt, width + 1) == width + tile


def test_the_tile_is_512_rows_and_no_option(monkeypatch):
    monkeypatch.undo()
    assert generation.PREFILL_TILE_ROWS == 512


def test_a_mesh_that_shards_seq_keeps_the_whole_buffer():
    """One width under an sp mesh (a narrower one fights the sharding);
    a tp mesh, which leaves ``seq`` whole, keeps the widths."""
    devices = np.array(jax.devices()[:2])
    sp = jax.sharding.Mesh(devices.reshape(1, 2),
                           (mesh_lib.AXIS_TP, mesh_lib.AXIS_SP))
    tp = jax.sharding.Mesh(devices.reshape(2, 1),
                           (mesh_lib.AXIS_TP, mesh_lib.AXIS_SP))
    assert generation.prefill_widths(T, DEFAULT_RULES, sp) == (T,)
    assert generation.prefill_rows_computed(T, 3, DEFAULT_RULES, sp) == T
    assert generation.prefill_widths(T, DEFAULT_RULES, tp) == (24, T)


@pytest.mark.parametrize("prompt_len", [
    1, TILE - 1, TILE, TILE + 1, 2 * TILE - 1, 2 * TILE, 2 * TILE + 1,
    3 * TILE - 1, 3 * TILE, 3 * TILE + 1, T - 1, T])
def test_insert_leaves_what_the_whole_buffer_forward_leaves(model,
                                                            prompt_len):
    config, params = model
    tokens = _tokens(1)
    cache, state = _grid(config)
    want_cache, want_state, want_tok, want_logits = _whole_buffer_insert(
        params, dict(cache), dict(state), tokens, prompt_len, 1, NEW, config)
    got_cache, got_state, got_tok = jax.jit(
        lambda c, s, n: generation.insert_slot_program(
            params, c, s, tokens, n, 1, NEW, config, sample=GREEDY)
    )(cache, state, prompt_len)
    assert int(got_tok) == int(want_tok)
    for name in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(got_cache[name][:, 1, :prompt_len]),
            np.asarray(want_cache[name][:, 1, :prompt_len]),
            rtol=1e-5, atol=1e-6)
        # The other slot's row is nobody's business.
        assert not np.asarray(got_cache[name][:, 0]).any()
        # Nothing is written at or past the width that ran.
        width = generation.prefill_rows_computed(T, prompt_len)
        assert not np.asarray(got_cache[name][:, 1, width:]).any()
    for name in got_state:
        np.testing.assert_array_equal(np.asarray(got_state[name]),
                                      np.asarray(want_state[name]))
    _, got_logits = generation._prefill_into(
        params, dict(cache), tokens, jnp.array([prompt_len]),
        (0, 1, 0, 0, 0), config, DEFAULT_RULES, None)
    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(want_logits), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("bucket", [TILE, 2 * TILE, 20])
def test_a_buffer_with_one_width_traces_to_the_whole_buffer_program(
        model, bucket):
    config, params = model
    tokens = _tokens(1, bucket)
    cache, state = _grid(config, bucket)

    def now(cache, state, n):
        return generation.insert_slot_program(
            params, cache, state, tokens, n, 1, NEW, config, sample=GREEDY)

    def before(cache, state, n):
        return _whole_buffer_insert(params, cache, state, tokens, n, 1, NEW,
                                    config)[:3]

    args = (cache, state, jnp.int32(5))
    assert str(jax.make_jaxpr(now)(*args)) == str(
        jax.make_jaxpr(before)(*args))


def test_a_buffer_with_two_widths_holds_one_switch_over_them(model):
    config, params = model
    tokens = _tokens(1)
    cache, state = _grid(config)
    text = str(jax.make_jaxpr(
        lambda c, s, n: generation.insert_slot_program(
            params, c, s, tokens, n, 1, NEW, config, sample=GREEDY)
    )(cache, state, jnp.int32(5)))
    assert text.count(" cond[") == 1
    assert text.count("branches=(") == 1


@pytest.mark.parametrize("lens", [(3, 17, 24), (25, 9, 1), (32, 31, 30)],
                         ids=["at_24", "at_32", "full"])
def test_generate_with_ragged_rows_runs_at_the_longest_rows_width(
        model, monkeypatch, lens):
    config, params = model
    tokens = _tokens(3)
    lens = jnp.asarray(lens, jnp.int32)
    got = generation.generate(params, tokens, lens, config,
                              max_new_tokens=5)
    monkeypatch.setattr(generation, "PREFILL_TILE_ROWS", 512)  # one width
    want = generation.generate(params, tokens, lens, config,
                               max_new_tokens=5)
    for name in ("tokens", "sequences", "num_generated"):
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]))


def test_the_switch_runs_under_a_mesh_that_leaves_seq_whole(model):
    """Under a dp x tp mesh a 32-row buffer keeps both widths, and the
    switch over them gives the tokens of the run without a mesh."""
    from cloud_tpu import parallel

    config, params = model
    tokens = _tokens(4)
    lens = jnp.asarray([3, 20, 9, 17], jnp.int32)
    plain = generation.generate(params, tokens, lens, config,
                                max_new_tokens=4)["tokens"]
    mesh = parallel.MeshSpec({"dp": 2, "tp": 2}).build(jax.devices()[:4])
    assert generation.prefill_widths(T, DEFAULT_RULES, mesh) == (24, T)
    with parallel.use_mesh(mesh):
        sharded = jax.jit(lambda p, t, n: generation.generate(
            p, t, n, config, max_new_tokens=4, mesh=mesh)["tokens"])(
                params, tokens, lens)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(sharded))


def test_beam_search_and_the_draft_prefill_take_the_same_path(
        model, monkeypatch):
    config, params = model
    tokens = _tokens(1)
    cache, _ = _grid(config)
    n = 2 * TILE + 3
    got_beams = generation.beam_search(
        params, tokens, jnp.array([n]), config, max_new_tokens=3,
        num_beams=2)
    got_draft = generation.draft_prefill_slot_program(
        params, dict(cache), tokens, n, 1, config)
    monkeypatch.setattr(generation, "PREFILL_TILE_ROWS", 512)
    want_beams = generation.beam_search(
        params, tokens, jnp.array([n]), config, max_new_tokens=3,
        num_beams=2)
    want_draft = generation.draft_prefill_slot_program(
        params, dict(cache), tokens, n, 1, config)
    np.testing.assert_array_equal(np.asarray(got_beams["tokens"]),
                                  np.asarray(want_beams["tokens"]))
    for name in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(got_draft[name][:, 1, :n]),
            np.asarray(want_draft[name][:, 1, :n]), rtol=1e-5, atol=1e-6)
