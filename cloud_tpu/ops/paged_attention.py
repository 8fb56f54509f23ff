"""Paged decode-attention as a Pallas TPU kernel, with a jnp reference.

The serving hot path (``models.generation``'s slot-grid programs) reads
KV through ``_cache_attention`` over a padded ``[num_slots, max_len]``
slot grid, and a prefix-cache hit first COPIES pool blocks into the slot
row (``copy_prefix_program``) before a single token decodes.  This
module removes both costs: attention gathers KV **in place** through a
per-slot block table — page ``p`` of a row reads either the slot row
itself (table entry ``-1``) or a prefix-pool block (table entry ``>= 0``,
an index into the ``init_prefix_pool`` layout ``[num_blocks,
block_tokens, H, hd]`` per layer) — and pages past each row's valid
length are skipped outright, so decode stops re-reading padded dead
slots and a prefix hit stops dispatching the copy program.

The kernel is the house flash-attention shape transposed to serving:
the grid walks ``(row, page)`` with the block table and per-row lengths
scalar-prefetched (``pltpu.PrefetchScalarGridSpec`` — the table drives
the page BlockSpec index maps, which is what makes the gather a DMA
schedule rather than a gather op), online-softmax accumulators in VMEM
scratch, and the kv_quant int8 dequant fused in-VMEM (scales fold into
scores/weights exactly like ``_cache_attention``'s post-scale algebra —
no full-width page ever materializes).

Three entry points match the serving dispatch shapes:

- :func:`paged_decode_attention` — the single-token decode step
  (``decode_chunk_program``'s inner attention, ``T_q == 1``);
- :func:`paged_chunk_attention` — the chunk-causal prefill shape
  (``prefill_chunk_program``: query ``t`` sits at cache position
  ``cur_len - 1 + t``);
- :func:`paged_verify_attention` — the speculative verify window
  (``verify_chunk_program``; same mask as the chunk shape).

Dispatch follows the house playbook: ``use_pallas=None`` auto-dispatch
takes the kernel on real TPU at ``S >= CLOUD_TPU_PAGED_MIN_LEN``
(measure with ``scripts/decode_crossover.py`` and keep docs/KERNELS.md's
table honest), ``CLOUD_TPU_FLASH_FORCE_INTERPRET=1``
(``dispatch.force_interpret``) runs the kernel code path through the
Pallas interpreter (the CPU rigs), and everything else — off-TPU,
ineligible shapes, ``CLOUD_TPU_PAGED_KERNEL=0`` — takes
:func:`_reference`, a pure-jnp block-table gather whose math mirrors
``_cache_attention`` term for term (same einsum order, same finite mask,
same post-scale quant algebra), so the fallback is bit-identical to the
copy-based XLA path given identical pool bytes.  An explicit
``use_pallas=True`` on a shape the kernel cannot take raises.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

from cloud_tpu.ops import dispatch as dispatch_lib

NEG_INF = -1e30  # finite: fully-masked rows softmax to zeros, not NaN

#: Auto-dispatch (``use_pallas=None``) takes the kernel only when the slot
#: row length S reaches this.  Default mirrors the flash kernel's measured
#: shape of crossover (short rows fit XLA's fused path cache-friendly;
#: the kernel pays at long context where the dead-page skip and the
#: no-copy hit path dominate) — measure on the real rig with
#: scripts/decode_crossover.py and pin the table in docs/KERNELS.md.
MIN_SEQ_LEN_FOR_KERNEL = int(os.environ.get("CLOUD_TPU_PAGED_MIN_LEN", 1024))

#: Operational kill switch for auto-dispatch.
def _kernel_enabled() -> bool:
    return os.environ.get("CLOUD_TPU_PAGED_KERNEL", "1") != "0"


#: Page size used when no prefix pool rides along (pure slot paging): the
#: lane-width default; fitted down to the row length when shorter.
DEFAULT_PAGE_TOKENS = 128

#: Diagnostic counter: bumped every time the Pallas kernel is actually
#: traced — serving retrace guards and the unit suite assert it advances
#: to prove the kernel path (not the jnp reference) ran.
KERNEL_TRACE_COUNT = 0


# ---------------------------------------------------------------------------
# Reference implementation (ground truth + non-TPU fallback)
# ---------------------------------------------------------------------------


def _gather_paged(slot_leaf, pool_leaf, block_table):
    """Materialize the virtual KV a block table describes: position ``j``
    of row ``b`` reads ``pool_leaf[table[b, j // bt], j % bt]`` when that
    table entry is ``>= 0``, else ``slot_leaf[b, j]``.  Positions beyond
    the table's page coverage always read the slot row.  Pure jnp — the
    reference path's (and only the reference path's) full-width gather.
    """
    b, s = slot_leaf.shape[:2]
    if pool_leaf is None or block_table is None:
        return slot_leaf
    bt = pool_leaf.shape[1]
    n_pages = block_table.shape[1]
    j = jnp.arange(s)
    page = j // bt  # [S]
    in_pages = page < n_pages
    blk = jnp.where(
        in_pages[None, :],
        jnp.take(block_table, jnp.minimum(page, n_pages - 1), axis=1),
        jnp.int32(-1),
    )  # [B, S]
    gathered = pool_leaf[jnp.maximum(blk, 0), (j % bt)[None, :]]  # [B,S,...]
    sel = (blk >= 0).reshape(b, s, *([1] * (slot_leaf.ndim - 2)))
    return jnp.where(sel, gathered, slot_leaf)


def _reference(q, cache_l, cur_len, pool_l, block_table):
    """``_cache_attention``'s exact math over the block-table gather:
    chunk-causal mask (key ``j`` valid for query ``t`` iff ``j <
    cur_len + t`` — with ``T_q == 1`` this IS the plain decode mask),
    f32 softmax, finite mask value, post-scale int8 algebra."""
    k_cache = _gather_paged(
        cache_l["k"], None if pool_l is None else pool_l["k"], block_table
    )
    v_cache = _gather_paged(
        cache_l["v"], None if pool_l is None else pool_l["v"], block_table
    )
    s = k_cache.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])

    def fold(scores_like, kv_scale):
        # [B, S, H, 1] -> [B, H, 1, S] broadcast over the query dim.
        return scores_like * jnp.transpose(kv_scale, (0, 2, 3, 1))

    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32),
        k_cache.astype(jnp.float32),
    ) * scale
    if "k_scale" in cache_l:
        k_sc = _gather_paged(
            cache_l["k_scale"],
            None if pool_l is None else pool_l["k_scale"], block_table,
        )
        scores = fold(scores, k_sc)
    valid = jnp.arange(s)[None, None, :] < (
        cur_len[:, None, None] + jnp.arange(q.shape[1])[None, :, None]
    )
    scores = jnp.where(valid[:, None, :, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    if "v_scale" in cache_l:
        v_sc = _gather_paged(
            cache_l["v_scale"],
            None if pool_l is None else pool_l["v_scale"], block_table,
        )
        weights = fold(weights, v_sc)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", weights, v_cache.astype(jnp.float32)
    )
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _paged_kernel(*refs, bt, tq, h, hd, s_total, scale, quantized,
                  has_pool):
    """One (row, page) grid cell: select the page's KV source (slot row
    vs pool block), dequant in-VMEM, fold the page into the online
    softmax.  Scalar-prefetch refs lead: the block table and per-row
    lengths."""
    refs = list(refs)
    table_ref, len_ref = refs[0], refs[1]
    pos = 2
    q_ref = refs[pos]; pos += 1
    sk_ref, sv_ref = refs[pos], refs[pos + 1]; pos += 2
    sks_ref = svs_ref = None
    if quantized:
        sks_ref, svs_ref = refs[pos], refs[pos + 1]; pos += 2
    pk_ref = pv_ref = pks_ref = pvs_ref = None
    if has_pool:
        pk_ref, pv_ref = refs[pos], refs[pos + 1]; pos += 2
        if quantized:
            pks_ref, pvs_ref = refs[pos], refs[pos + 1]; pos += 2
    o_ref = refs[pos]; pos += 1
    m_scr, l_scr, acc_scr = refs[pos], refs[pos + 1], refs[pos + 2]

    b, p = pl.program_id(0), pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Dead-page skip: keys of page p start at p*bt; the largest index any
    # query can see is cur_len + tq - 2 (key j valid iff j < cur_len + t,
    # t < tq).  Pages past that contribute nothing — no compute (and the
    # index maps pin their DMA to the last live page, so no fetch either).
    limit = len_ref[b] + (tq - 1)
    run = p * bt < limit

    @pl.when(run)
    def _compute():
        def pick(slot_ref, pool_ref):
            page = slot_ref[0].astype(jnp.float32)
            if pool_ref is None:
                return page
            use_pool = table_ref[b, p] >= 0
            return jnp.where(use_pool, pool_ref[0].astype(jnp.float32),
                             page)

        # Zero columns past the true row length: the last page may be a
        # padded partial block whose out-of-bounds lanes hold garbage
        # (NaN under the interpreter) — 0 * garbage would still poison
        # the pv matmul through masked-but-summed lanes.
        col = jax.lax.broadcasted_iota(jnp.int32, (bt, 1, 1), 0)
        in_range = (p * bt + col) < s_total
        k_page = jnp.where(in_range, pick(sk_ref, pk_ref), 0.0)
        v_page = jnp.where(in_range, pick(sv_ref, pv_ref), 0.0)

        q = q_ref[0].astype(jnp.float32)  # [tq, h, hd]
        s = jax.lax.dot_general(
            q.transpose(1, 0, 2), k_page.transpose(1, 0, 2),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # [h, tq, bt]
        if quantized:
            k_sc = pick(sks_ref, pks_ref)  # [bt, h, 1]
            s = s * k_sc.transpose(1, 2, 0)  # [h, 1, bt]

        jglob = p * bt + jax.lax.broadcasted_iota(jnp.int32, (tq, bt), 1)
        tq_idx = jax.lax.broadcasted_iota(jnp.int32, (tq, bt), 0)
        valid = (jglob < len_ref[b] + tq_idx) & (jglob < s_total)
        s = jnp.where(valid[None], s, NEG_INF)

        s2 = s.reshape(h * tq, bt)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s2, axis=-1, keepdims=True))
        pmat = jnp.exp(s2 - m_new)  # [h*tq, bt]
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(pmat, axis=-1, keepdims=True)
        p3 = pmat.reshape(h, tq, bt)
        if quantized:
            v_sc = jnp.where(in_range, pick(svs_ref, pvs_ref), 0.0)
            p3 = p3 * v_sc.transpose(1, 2, 0)
        pv = jax.lax.dot_general(
            p3, v_page.transpose(1, 0, 2), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [h, tq, hd]
        acc_scr[...] = acc_scr[...] * correction + pv.reshape(h * tq, hd)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(p == n_pages - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out = (acc_scr[...] / safe_l).reshape(h, tq, hd)
        o_ref[0] = out.transpose(1, 0, 2).astype(o_ref.dtype)


# Imported lazily-but-module-level like flash_attention: pallas is part
# of jax proper; the TPU sub-module only at kernel-build time.
from jax.experimental import pallas as pl  # noqa: E402


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary")
    )


def _paged_pallas(q, cache_l, cur_len, pool_l, block_table, bt, *,
                  interpret):
    """q [B,Tq,H,hd]; slot leaves [B,S,H,hd]; pool leaves [NB,bt,H,hd];
    block_table [B, ceil(S/bt)] int32 (-1 = slot page); cur_len [B]."""
    global KERNEL_TRACE_COUNT
    KERNEL_TRACE_COUNT += 1
    from jax.experimental.pallas import tpu as pltpu

    b, tq, h, hd = q.shape
    s_total = cache_l["k"].shape[1]
    n_pages = -(-s_total // bt)
    quantized = "k_scale" in cache_l
    has_pool = pool_l is not None
    scale = 1.0 / math.sqrt(hd)

    if block_table is None:
        block_table = jnp.full((b, n_pages), -1, jnp.int32)
    else:
        block_table = block_table.astype(jnp.int32)
        width = block_table.shape[1]
        if width < n_pages:
            block_table = jnp.pad(
                block_table, ((0, 0), (0, n_pages - width)),
                constant_values=-1,
            )
        elif width > n_pages:
            block_table = block_table[:, :n_pages]
    cur_len = cur_len.astype(jnp.int32)

    def last_live(ln, b_):
        # Largest page any query of row b_ can read (>= 0 so the map is
        # always a legal index); dead pages pin here -> their DMA is a
        # repeat fetch the pipeline skips.
        limit = ln[b_] + (tq - 1)
        return jnp.maximum((limit - 1) // bt, 0)

    def q_map(b_, p_, tbl, ln):
        return (b_, 0, 0, 0)

    def slot_map(b_, p_, tbl, ln):
        return (b_, jnp.minimum(p_, last_live(ln, b_)), 0, 0)

    def pool_map(b_, p_, tbl, ln):
        pc = jnp.minimum(p_, last_live(ln, b_))
        return (jnp.maximum(tbl[b_, pc], 0), 0, 0, 0)

    kv_spec = pl.BlockSpec((1, bt, h, hd), slot_map)
    sc_spec = pl.BlockSpec((1, bt, h, 1), slot_map)
    pkv_spec = pl.BlockSpec((1, bt, h, hd), pool_map)
    psc_spec = pl.BlockSpec((1, bt, h, 1), pool_map)

    in_specs = [pl.BlockSpec((1, tq, h, hd), q_map), kv_spec, kv_spec]
    operands = [q, cache_l["k"], cache_l["v"]]
    if quantized:
        in_specs += [sc_spec, sc_spec]
        operands += [cache_l["k_scale"], cache_l["v_scale"]]
    if has_pool:
        in_specs += [pkv_spec, pkv_spec]
        operands += [pool_l["k"], pool_l["v"]]
        if quantized:
            in_specs += [psc_spec, psc_spec]
            operands += [pool_l["k_scale"], pool_l["v_scale"]]

    kernel = functools.partial(
        _paged_kernel, bt=bt, tq=tq, h=h, hd=hd, s_total=s_total,
        scale=scale, quantized=quantized, has_pool=has_pool,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tq, h, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((h * tq, 128), jnp.float32),
            pltpu.VMEM((h * tq, 128), jnp.float32),
            pltpu.VMEM((h * tq, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="paged_decode",
    )(block_table, cur_len, *operands)


# ---------------------------------------------------------------------------
# Dispatch + public entry points
# ---------------------------------------------------------------------------


def _fit_page(s: int, bt: Optional[int]) -> Optional[int]:
    """Resolve the page size: the pool's block_tokens when a pool rides
    along (pages must align to pool blocks), else the largest multiple
    of 8 at or below ``min(DEFAULT_PAGE_TOKENS, S)``."""
    if bt is not None:
        return bt
    fitted = min(DEFAULT_PAGE_TOKENS, s)
    fitted -= fitted % 8
    return fitted if fitted >= 8 else None


def _kernel_eligible(q, cache_l, bt) -> bool:
    return (
        q.ndim == 4
        and cache_l["k"].ndim == 4
        and bt is not None
        and q.shape[-1] <= 256  # head_dim beyond this overflows VMEM
        and q.shape[0] == cache_l["k"].shape[0]
    )


def would_use_kernel(q, cache_l, *, page_tokens: Optional[int] = None
                     ) -> bool:
    """The ``use_pallas=None`` auto-dispatch predicate, exposed so the
    serving engine and tests share one spelling."""
    bt = _fit_page(cache_l["k"].shape[1], page_tokens)
    return (
        jax.default_backend() == "tpu"
        and _kernel_enabled()
        and _kernel_eligible(q, cache_l, bt)
        and cache_l["k"].shape[1] >= MIN_SEQ_LEN_FOR_KERNEL
    )


def _heads_sharded(mesh, head_axes, bt, interpret, q, cache_l, cur_len,
                   pool_l, block_table):
    """The kernel per head shard of ``mesh`` (a full-manual shard_map —
    ops/dispatch.py says why): every KV leaf, slot or pool, value or
    scale, is rank 4 with heads third; table and lengths are whole."""
    from jax.sharding import PartitionSpec as P

    heads = P(None, None,
              dispatch_lib.dividing_axes(mesh, head_axes, q.shape[2]), None)

    def local(q, cache_l, cur_len, pool_l, block_table):
        return _paged_pallas(q, cache_l, cur_len, pool_l, block_table, bt,
                             interpret=interpret)

    # One spec per operand: a leaf spec covers a whole dict of KV leaves
    # (and a ``pool_l`` of None, which has none).
    return jax.shard_map(
        local, mesh=mesh, in_specs=(heads, heads, P(), heads, P()),
        out_specs=heads, check_vma=False,
    )(q, cache_l, cur_len, pool_l, block_table)


def _paged(q, cache_l, cur_len, *, pool_l, block_table, use_pallas,
           interpret, partitioned, mesh=None, head_axes=None):
    bt = _fit_page(
        cache_l["k"].shape[1],
        None if pool_l is None else pool_l["k"].shape[1],
    )
    if not interpret and dispatch_lib.force_interpret():
        interpret = True
    eligible = _kernel_eligible(q, cache_l, bt)
    if use_pallas and not eligible:
        raise ValueError(
            "paged attention (use_pallas=True): the kernel cannot take "
            f"q{tuple(q.shape)} over slot rows "
            f"{tuple(cache_l['k'].shape)} (needs rank-4 q and rows of one "
            "batch, head_dim <= 256, a page of >= 8 tokens)"
        )
    if use_pallas is None:
        use_pallas = would_use_kernel(
            q, cache_l,
            page_tokens=None if pool_l is None else pool_l["k"].shape[1],
        ) or (interpret and eligible and _kernel_enabled())
    if use_pallas and jax.default_backend() != "tpu":
        # CPU-test convenience: off-TPU the kernel can only be interpreted.
        interpret = True
    if not use_pallas:
        return _reference(q, cache_l, cur_len, pool_l, block_table)
    # Inside a manual region, or under no mesh of more than one device,
    # the shapes are one device's already: the direct call.
    kernel_mesh = dispatch_lib.kernel_mesh(mesh) if partitioned else None
    if kernel_mesh is not None:
        return _heads_sharded(kernel_mesh, head_axes, bt, interpret, q,
                              cache_l, cur_len, pool_l, block_table)
    return _paged_pallas(q, cache_l, cur_len, pool_l, block_table, bt,
                         interpret=interpret)


def paged_decode_attention(
    q: jnp.ndarray,
    cache_l,
    cur_len: jnp.ndarray,
    *,
    pool_l=None,
    block_table: Optional[jnp.ndarray] = None,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    partitioned: bool = False,
    mesh=None,
    head_axes=None,
) -> jnp.ndarray:
    """Single-token decode attention ([B, 1, H, hd] queries) over a
    block-table view of slot rows + pool blocks.

    Drop-in for ``_cache_attention(q, cache_l, cur_len)``: key ``j`` of
    row ``b`` is valid iff ``j < cur_len[b]`` (callers pass ``pos + 1``
    exactly as they do to ``_cache_attention``).  ``block_table``
    [B, n_pages] int32 maps page ``p`` (positions ``[p*bt, (p+1)*bt)``)
    to a ``pool_l`` block when ``>= 0``, to the slot row when ``-1``;
    ``block_table=None`` (or ``pool_l=None``) reads slot rows only —
    the cold-insert shape.

    ``partitioned=True`` under ``mesh`` (default: the framework's global
    mesh) of more than one device runs the kernel per head shard:
    ``head_axes`` names the mesh axes the CALLER's heads dimension is
    split over (its rules' ``"heads"`` assignment; None: not split).
    """
    return _paged(q, cache_l, cur_len, pool_l=pool_l,
                  block_table=block_table, use_pallas=use_pallas,
                  interpret=interpret, partitioned=partitioned, mesh=mesh,
                  head_axes=head_axes)


def paged_chunk_attention(
    q: jnp.ndarray,
    cache_l,
    cur_len: jnp.ndarray,
    *,
    pool_l=None,
    block_table: Optional[jnp.ndarray] = None,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    partitioned: bool = False,
    mesh=None,
    head_axes=None,
) -> jnp.ndarray:
    """Chunk-causal paged attention — the ``prefill_chunk_program``
    shape.  Queries are CONSECUTIVE cache positions starting at
    ``cur_len - 1``: key ``j`` is valid for query ``t`` iff
    ``j < cur_len + t`` (``_cache_attention(..., chunk_causal=True)``'s
    exact mask).  With ``T_q == 1`` this degenerates to
    :func:`paged_decode_attention` — one kernel serves both."""
    return _paged(q, cache_l, cur_len, pool_l=pool_l,
                  block_table=block_table, use_pallas=use_pallas,
                  interpret=interpret, partitioned=partitioned, mesh=mesh,
                  head_axes=head_axes)


def paged_verify_attention(
    q: jnp.ndarray,
    cache_l,
    cur_len: jnp.ndarray,
    *,
    pool_l=None,
    block_table: Optional[jnp.ndarray] = None,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    partitioned: bool = False,
    mesh=None,
    head_axes=None,
) -> jnp.ndarray:
    """Speculative verify-window paged attention — the
    ``verify_chunk_program`` shape ([num_slots, spec_k, H, hd] queries,
    per-slot window starts).  Mask-wise identical to
    :func:`paged_chunk_attention` (the window IS a chunk at ``pos``);
    a separate entry point so the serving dispatch sites and the
    crossover bench name the shape they measure."""
    return _paged(q, cache_l, cur_len, pool_l=pool_l,
                  block_table=block_table, use_pallas=use_pallas,
                  interpret=interpret, partitioned=partitioned, mesh=mesh,
                  head_axes=head_axes)
