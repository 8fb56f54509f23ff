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
