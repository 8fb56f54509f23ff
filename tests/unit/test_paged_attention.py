"""Paged decode-attention kernel tests, interpreter mode on CPU.

The interpreter executes the same kernel body Mosaic compiles on TPU —
block-table page selection, the dead-page DMA clamp, the online-softmax
loop, and the fused int8 dequant — against the pure-jnp reference that
is also the production fallback.  Unlike flash_attention's interpret
tests (known-red on jax 0.4.37: ``ShapeDtypeStruct(vma=...)``), this
kernel's interpret path runs clean on the pinned toolchain, so these
are green gates, not ledger entries.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cloud_tpu.models.generation import _cache_attention
from cloud_tpu.ops import paged_attention as pa
from cloud_tpu.ops.paged_attention import (
    paged_chunk_attention,
    paged_decode_attention,
    paged_verify_attention,
)

ENTRY = {
    "decode": paged_decode_attention,
    "chunk": paged_chunk_attention,
    "verify": paged_verify_attention,
}


def _make(b, s, h, hd, bt, nb, *, quant=False, seed=0):
    rng = np.random.default_rng(seed)
    cache = {
        "k": jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32),
        "v": jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32),
    }
    pool = {
        "k": jnp.asarray(rng.normal(size=(nb, bt, h, hd)), jnp.float32),
        "v": jnp.asarray(rng.normal(size=(nb, bt, h, hd)), jnp.float32),
    }
    if quant:
        for leaf in (cache, pool):
            scale_shape = leaf["k"].shape[:2] + (h, 1)
            leaf["k_scale"] = jnp.asarray(
                rng.uniform(0.01, 0.1, size=scale_shape), jnp.float32
            )
            leaf["v_scale"] = jnp.asarray(
                rng.uniform(0.01, 0.1, size=scale_shape), jnp.float32
            )
            leaf["k"] = jnp.asarray(
                rng.integers(-127, 127, size=leaf["k"].shape), jnp.int8
            )
            leaf["v"] = jnp.asarray(
                rng.integers(-127, 127, size=leaf["v"].shape), jnp.int8
            )
    n_pages = -(-s // bt)
    table = rng.integers(-1, nb, size=(b, n_pages)).astype(np.int32)
    if s % bt:
        table[:, -1] = -1  # a partial page is always slot-backed
    return cache, pool, jnp.asarray(table), rng


class TestKernelMatchesReference:
    """Kernel (interpret) vs jnp reference, every serving shape."""

    @pytest.mark.parametrize("kind,tq", [("decode", 1), ("chunk", 4),
                                         ("verify", 3)])
    def test_entry_points(self, kind, tq):
        b, s, h, hd, bt, nb = 3, 40, 4, 64, 8, 6
        cache, pool, table, rng = _make(b, s, h, hd, bt, nb)
        q = jnp.asarray(
            rng.normal(size=(b, tq, h, hd)), jnp.float32
        )
        cur_len = jnp.asarray(
            rng.integers(1, s - tq + 2, size=(b,)), jnp.int32
        )
        ref = pa._reference(q, cache, cur_len, pool, table)
        out = ENTRY[kind](
            q, cache, cur_len, pool_l=pool, block_table=table,
            use_pallas=True, interpret=True,
        )
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("kind,tq", [("decode", 1), ("chunk", 4)])
    def test_int8_dequant_fused(self, kind, tq):
        b, s, h, hd, bt, nb = 2, 24, 4, 32, 8, 5
        cache, pool, table, rng = _make(b, s, h, hd, bt, nb, quant=True)
        q = jnp.asarray(
            rng.normal(size=(b, tq, h, hd)), jnp.float32
        )
        cur_len = jnp.asarray(
            rng.integers(1, s - tq + 2, size=(b,)), jnp.int32
        )
        ref = pa._reference(q, cache, cur_len, pool, table)
        out = ENTRY[kind](
            q, cache, cur_len, pool_l=pool, block_table=table,
            use_pallas=True, interpret=True,
        )
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_partial_last_page(self):
        # S not a multiple of the page size: the padded tail columns
        # must be masked out, not poison the softmax with garbage.
        b, s, h, hd, bt, nb = 2, 30, 2, 32, 8, 4
        cache, pool, table, rng = _make(b, s, h, hd, bt, nb)
        q = jnp.asarray(rng.normal(size=(b, 2, h, hd)), jnp.float32)
        cur_len = jnp.asarray([s - 1, 5], jnp.int32)
        ref = pa._reference(q, cache, cur_len, pool, table)
        out = paged_chunk_attention(
            q, cache, cur_len, pool_l=pool, block_table=table,
            use_pallas=True, interpret=True,
        )
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_no_pool_no_table_matches_cache_attention(self):
        # The pure slot path (no prefix pool riding along) is the
        # in-place replacement for _cache_attention on the decode hot
        # path: same math, no gather.
        b, s, h, hd = 2, 24, 4, 32
        cache, _, _, rng = _make(b, s, h, hd, 8, 4)
        q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.float32)
        cur_len = jnp.asarray([7, s], jnp.int32)
        want = _cache_attention(q, cache, cur_len)
        out = paged_decode_attention(
            q, cache, cur_len, use_pallas=True, interpret=True,
        )
        np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)

    def test_reference_all_slot_table_is_cache_attention(self):
        # A block table of all -1 selects only slot rows: the reference
        # must then be exactly _cache_attention (the fallback really is
        # bit-compatible with the copy-based XLA path).
        b, s, h, hd = 2, 16, 2, 16
        cache, pool, _, rng = _make(b, s, h, hd, 8, 4)
        q = jnp.asarray(rng.normal(size=(b, 3, h, hd)), jnp.float32)
        cur_len = jnp.asarray([4, 9], jnp.int32)
        table = jnp.full((b, 2), -1, jnp.int32)
        ref = pa._reference(q, cache, cur_len, pool, table)
        want = _cache_attention(q, cache, cur_len, chunk_causal=True)
        np.testing.assert_allclose(ref, want, atol=1e-6, rtol=1e-6)

    def test_kernel_trace_counter_advances(self):
        b, s, h, hd, bt, nb = 1, 16, 2, 16, 8, 2
        cache, pool, table, rng = _make(
            b, s, h, hd, bt, nb, seed=3
        )
        q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.float32)
        before = pa.KERNEL_TRACE_COUNT
        paged_decode_attention(
            q, cache, jnp.asarray([s], jnp.int32), pool_l=pool,
            block_table=table, use_pallas=True, interpret=True,
        )
        assert pa.KERNEL_TRACE_COUNT > before


class TestDispatch:
    def test_cpu_auto_falls_back_to_reference(self):
        # use_pallas=None off-TPU without the interpret knob: the jnp
        # reference, never the kernel.
        b, s, h, hd = 1, 16, 2, 16
        cache, pool, table, rng = _make(b, s, h, hd, 8, 2)
        q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.float32)
        before = pa.KERNEL_TRACE_COUNT
        out = paged_decode_attention(
            q, cache, jnp.asarray([s], jnp.int32), pool_l=pool,
            block_table=table,
        )
        assert pa.KERNEL_TRACE_COUNT == before
        ref = pa._reference(
            q, cache, jnp.asarray([s], jnp.int32), pool, table
        )
        np.testing.assert_allclose(out, ref, atol=0, rtol=0)

    def test_would_use_kernel_requires_tpu(self):
        b, s, h, hd = 1, 2048, 2, 16
        cache, _, _, rng = _make(b, s, h, hd, 8, 2)
        q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.float32)
        want = jax.default_backend() == "tpu"
        assert pa.would_use_kernel(q, cache) is want

    def test_kill_switch(self, monkeypatch):
        b, s, h, hd = 1, 2048, 2, 16
        cache, _, _, rng = _make(b, s, h, hd, 8, 2)
        q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.float32)
        monkeypatch.setenv("CLOUD_TPU_PAGED_KERNEL", "0")
        assert pa.would_use_kernel(q, cache) is False

    def test_fit_page(self):
        assert pa._fit_page(24, 8) == 8      # pool block wins
        assert pa._fit_page(300, None) == 128  # capped at the default
        assert pa._fit_page(30, None) == 24    # multiple of 8, <= S
        assert pa._fit_page(4, None) is None   # too short to page

    def test_interpret_knob_routes_kernel(self, monkeypatch):
        monkeypatch.setenv("CLOUD_TPU_FLASH_FORCE_INTERPRET", "1")
        b, s, h, hd = 1, 16, 2, 16
        cache, pool, table, rng = _make(b, s, h, hd, 8, 2, seed=5)
        q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.float32)
        before = pa.KERNEL_TRACE_COUNT
        out = paged_decode_attention(
            q, cache, jnp.asarray([s], jnp.int32), pool_l=pool,
            block_table=table,
        )
        assert pa.KERNEL_TRACE_COUNT > before
        ref = pa._reference(
            q, cache, jnp.asarray([s], jnp.int32), pool, table
        )
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# ISSUE 29: the decode read over the carried cache, in place
# ---------------------------------------------------------------------------


def _stacked_cache(layers, b, s, kv_heads, hd, *, kv, seed):
    rng = np.random.default_rng(seed)
    shape = (layers, b, s, kv_heads, hd)
    if kv == "int8":
        cache = {
            "k": jnp.asarray(rng.integers(-127, 127, size=shape), jnp.int8),
            "v": jnp.asarray(rng.integers(-127, 127, size=shape), jnp.int8),
            "k_scale": jnp.asarray(
                rng.uniform(0.01, 0.1, size=shape[:-1] + (1,)), jnp.float32),
            "v_scale": jnp.asarray(
                rng.uniform(0.01, 0.1, size=shape[:-1] + (1,)), jnp.float32),
        }
    else:
        cache = {"k": jnp.asarray(rng.normal(size=shape), jnp.bfloat16),
                 "v": jnp.asarray(rng.normal(size=shape), jnp.bfloat16)}
    return cache, rng


#: Row lengths over pages of 8 in rows of 40: mid-page, at a page's
#: edge, one token, a whole row — and 0, a slot that does not decode.
LENGTHS = {
    "mid-page": [13, 3, 21, 39],
    "page-edge": [8, 16, 40, 24],
    "dead-first": [0, 0, 11, 40],
    "dead-between": [17, 0, 0, 1],
    "dead-last": [9, 32, 0, 0],
}


@pytest.mark.parametrize("lengths", list(LENGTHS))
@pytest.mark.parametrize("group", [1, 5])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_stacked_leaves_read_in_place(kv, group, lengths):
    """The kernel over the STACKED leaves with a traced layer index
    against ``_cache_attention`` on the sliced layer.  A row of length 0
    comes back finite (zeros) and its neighbours read as if it were not
    there."""
    layers, b, s, kv_heads, hd, bt = 3, 4, 40, 2, 32, 8
    cache, rng = _stacked_cache(layers, b, s, kv_heads, hd, kv=kv, seed=7)
    qdtype = jnp.bfloat16 if kv == "bf16" else jnp.float32
    q = jnp.asarray(rng.normal(size=(b, 1, kv_heads * group, hd)), qdtype)
    lens = jnp.asarray(LENGTHS[lengths], jnp.int32)

    @jax.jit
    def read(q, cache, lens, layer):
        return pa._paged_pallas(q, cache, lens, None, None, bt, layer=layer,
                                interpret=True)

    live = np.asarray(lens) > 0
    for layer in (0, 2):
        got = np.asarray(read(q, cache, lens, jnp.int32(layer)), np.float32)
        cache_l = {name: leaf[layer] for name, leaf in cache.items()}
        want = np.asarray(
            _cache_attention(q, cache_l, jnp.maximum(lens, 1)), np.float32)
        # bf16 outputs: one rounding of values up to a few units.
        tol = 2e-2 if kv == "bf16" else 2e-5
        np.testing.assert_allclose(got[live], want[live], atol=tol, rtol=tol)
        assert np.isfinite(got).all()
        assert not got[~live].any()


def test_one_token_body_keeps_float32_weights():
    """float32 queries over a bf16 cache: scores, softmax and the
    weighted sum stay float32 (the weights go through the MXU as three
    bf16 parts that sum back to them), so the read equals
    ``_cache_attention`` far inside a bf16 rounding."""
    layers, b, s, kv_heads, hd, bt = 2, 3, 44, 2, 32, 16
    cache, rng = _stacked_cache(layers, b, s, kv_heads, hd, kv="bf16", seed=9)
    q = jnp.asarray(rng.normal(size=(b, 1, kv_heads, hd)), jnp.float32)
    lens = jnp.asarray([5, 44, 17], jnp.int32)  # 44: into the partial page
    got = pa._paged_pallas(q, cache, lens, None, None, bt,
                           layer=jnp.int32(1), interpret=True)
    want = _cache_attention(
        q, {name: leaf[1] for name, leaf in cache.items()}, lens)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("lens, rows, first, last", [
    ([0, 130, 0, 0, 300, 0], [1, 1, 1, 1, 4, 4], [0, 0, 1, 1, 0, 2],
     [0, 1, 1, 1, 2, 2]),
    ([0, 0, 0], [0, 0, 0], [0, 0, 0], [0, 0, 0]),
    ([128, 129, 640], [0, 1, 2], [0, 0, 0], [0, 1, 4]),
], ids=["dead-rows-pin", "all-dead", "all-live"])
def test_fetch_plan_pins_dead_rows_to_the_resident_block(lens, rows, first,
                                                         last):
    """Every grid step of a row of length 0 names the block the step
    before left resident, so it is no fetch: the last live page of the
    nearest live row above, else page 0 of the nearest below."""
    got = pa._fetch_plan(jnp.asarray(lens, jnp.int32), 1, 128, 5)
    assert [list(map(int, x)) for x in got] == [rows, first, last]


@pytest.mark.parametrize("kv_heads", [None, 2], ids=["mha", "grouped"])
def test_decode_chunk_program_reads_in_place(kv_heads):
    """The call site: ``decode_chunk_program`` with the kernel forced on
    (interpreted here; on a TPU it is the default) emits the tokens of
    the ``_cache_attention`` program, with an inactive slot in the grid
    and a slot that finishes mid-chunk."""
    from cloud_tpu.models import generation, transformer

    config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2,
                                     num_kv_heads=kv_heads)
    params = transformer.init(jax.random.PRNGKey(0), config)
    slots, rows = 3, 24
    sample = generation.SampleConfig(temperature=0.0)

    def chunks(use_pallas):
        cache = generation.init_slot_cache(config, slots, rows)
        state = generation.init_slot_state(config, slots, sample=sample)
        for slot, (prompt, budget) in enumerate(
                [([3, 1, 4, 1, 5], 9), ([9, 2, 6], 3)]):
            tokens = jnp.asarray([prompt + [0] * (8 - len(prompt))])
            cache, state, _ = generation.insert_slot_program(
                params, cache, state, tokens, len(prompt), slot, budget,
                config, sample=sample)
        out = []
        for _ in range(2):
            cache, state, toks, valid = generation.decode_chunk_program(
                params, cache, state, config, chunk_size=4, sample=sample,
                use_pallas=use_pallas)
            out.append((np.asarray(toks), np.asarray(valid)))
        return out, cache

    before = pa.KERNEL_TRACE_COUNT
    got, got_cache = chunks(True)
    assert pa.KERNEL_TRACE_COUNT > before
    want, want_cache = chunks(None)
    for (toks, valid), (want_toks, want_valid) in zip(got, want):
        np.testing.assert_array_equal(valid, want_valid)
        np.testing.assert_array_equal(toks[valid], want_toks[want_valid])
    assert not got[0][1][2].any()  # the third slot never decodes
    np.testing.assert_allclose(got_cache["k"], want_cache["k"], atol=1e-5)
