"""Paged decode-attention as a Pallas TPU kernel, with a jnp reference.

The serving hot path (``models.generation``'s slot-grid programs) keeps
KV in a padded ``[L, num_slots, max_len, Hkv, hd]`` slot grid that rides
the layer loop as its carry.  A plain read of layer ``l`` fetches every
row of every slot, live or not, and a prefix-cache hit first COPIES pool
blocks into the slot row (``copy_prefix_program``).  This module removes
both costs: attention reads KV **in place**, page by page — page ``p``
of a row is either the slot row itself (table entry ``-1``, or no table
at all) or a prefix-pool block (table entry ``>= 0``, an index into the
``init_prefix_pool`` layout ``[num_blocks, block_tokens, Hkv, hd]`` per
layer) — and only the pages that hold a live row's tokens are fetched.

The grid walks ``(row, page)`` with everything that steers a fetch
scalar-prefetched (``pltpu.PrefetchScalarGridSpec``): the layer index
(the kernel takes the STACKED leaves ``[L, B, S, Hkv, hd]`` and its
index maps return ``(layer, row, page, 0, 0)``, so no layer is ever
sliced out before the call), the per-row lengths, the block table, and
a fetch plan (:func:`_fetch_plan`) that pins every grid step past a
row's last live page — and every step of a row of length 0 — to the
block already resident, so such a step costs a grid step and no DMA.
Online-softmax accumulators sit in VMEM scratch.

Two bodies share that plumbing.  One query token a row over a bf16/f32
cache (the decode step) is a matrix-vector product per K/V head: the
page stays as stored, ``[bt * Hkv, hd]``, and one MXU product of all
query rows against it gives every (head, head') score, of which the
mask keeps head == head' (the MXU has the room: a decode step is bound
by the page's DMA); no transpose, no float32 copy of the page.  Longer
query windows (chunk prefill, verify) and int8 caches take the batched
per-head form, with the int8 dequant fused in-VMEM (scales fold into
scores/weights exactly like ``_cache_attention``'s post-scale algebra).
Grouped K/V heads enter either body as ``group`` query rows of their
K/V head, as ``_cache_attention`` lines them up.

Three entry points match the serving dispatch shapes:

- :func:`paged_decode_attention` — the single-token decode step
  (``decode_chunk_program``'s inner attention, ``T_q == 1``);
- :func:`paged_chunk_attention` — the chunk-causal prefill shape
  (``prefill_chunk_program``: query ``t`` sits at cache position
  ``cur_len - 1 + t``);
- :func:`paged_verify_attention` — the speculative verify window
  (``verify_chunk_program``; same mask as the chunk shape).

Dispatch follows the house playbook: ``use_pallas=None`` auto-dispatch
takes the kernel on real TPU — always for ``T_q == 1`` (measured on the
chip at both benchmark grids, docs/KERNELS.md), at ``S >=
CLOUD_TPU_PAGED_MIN_LEN`` for the longer windows (never measured);
``CLOUD_TPU_FLASH_FORCE_INTERPRET=1`` (``dispatch.force_interpret``)
runs the kernel code path through the Pallas interpreter (the CPU
rigs), and everything else — off-TPU, ineligible shapes,
``CLOUD_TPU_PAGED_KERNEL=0`` — takes :func:`_reference`, a pure-jnp
block-table gather whose math mirrors ``_cache_attention`` term for
term (same einsum order, same finite mask, same post-scale quant
algebra), so the fallback is bit-identical to the copy-based XLA path
given identical pool bytes.  An explicit ``use_pallas=True`` on a shape
the kernel cannot take raises.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from cloud_tpu.ops import dispatch as dispatch_lib

NEG_INF = -1e30  # finite: fully-masked rows softmax to zeros, not NaN

#: Auto-dispatch (``use_pallas=None``) takes the kernel for a query
#: window of MORE than one token only when the slot row length S reaches
#: this: a guess from before the chip, which no chip run has replaced
#: (no cell runs those shapes).  The one-token decode read has no such
#: gate: it won at S = 640 and S = 2,080 (docs/KERNELS.md).
MIN_SEQ_LEN_FOR_KERNEL = int(os.environ.get("CLOUD_TPU_PAGED_MIN_LEN", 1024))


#: Operational kill switch for auto-dispatch.
def _kernel_enabled() -> bool:
    return os.environ.get("CLOUD_TPU_PAGED_KERNEL", "1") != "0"


#: Page size used when no prefix pool rides along (pure slot paging);
#: fitted down to the row length when shorter.  Chosen on the chip among
#: 64 / 128 / 256 at the two benchmark grids (docs/KERNELS.md).
DEFAULT_PAGE_TOKENS = 128

#: How many bfloat16 parts carry the one-token body's float32 softmax
#: weights through the MXU against the bf16 values (:func:`_split_bf16`):
#: three are the float32 weights exactly.
WEIGHT_PARTS = 3

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


def _group_queries(q, kv_heads):
    """[B, Tq, H, hd] -> [B, Tq * group, Hkv, hd]: the ``group`` query
    heads that read one K/V head line up as extra query rows of that
    head (row ``t * group + g``), as in ``_cache_attention``."""
    b, t_q, h, hd = q.shape
    group = h // kv_heads
    if group == 1:
        return q
    return q.reshape(b, t_q, kv_heads, group, hd).transpose(
        0, 1, 3, 2, 4).reshape(b, t_q * group, kv_heads, hd)


def _ungroup_outputs(out, t_q):
    """:func:`_group_queries` undone on the attended rows."""
    b, rows, kv_heads, hd = out.shape
    group = rows // t_q
    if group == 1:
        return out
    return out.reshape(b, t_q, group, kv_heads, hd).transpose(
        0, 1, 3, 2, 4).reshape(b, t_q, kv_heads * group, hd)


def _reference(q, cache_l, cur_len, pool_l, block_table):
    """``_cache_attention``'s exact math over the block-table gather:
    chunk-causal mask (key ``j`` valid for query ``t`` iff ``j <
    cur_len + t`` — with ``T_q == 1`` this IS the plain decode mask),
    f32 softmax, finite mask value, post-scale int8 algebra, grouped
    K/V heads as extra query rows."""
    k_cache = _gather_paged(
        cache_l["k"], None if pool_l is None else pool_l["k"], block_table
    )
    v_cache = _gather_paged(
        cache_l["v"], None if pool_l is None else pool_l["v"], block_table
    )
    s = k_cache.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])
    t_q, dtype = q.shape[1], q.dtype
    group = q.shape[2] // k_cache.shape[2]
    q = _group_queries(q, k_cache.shape[2])

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
        cur_len[:, None, None]
        + (jnp.arange(q.shape[1]) // group)[None, :, None]
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
    return _ungroup_outputs(out, t_q).astype(dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _fetch_plan(cur_len, tq, bt, n_pages):
    """What each grid step of row ``b`` fetches, as three [B] int32
    arrays ``(rows, first, last)``: page ``clip(p, first[b], last[b])``
    of cache row ``rows[b]``.

    A live row walks its own pages up to the last one any of its queries
    can see, then stays there.  A row of length 0 (a slot that does not
    decode) never moves: all its steps name the block the step before
    its first one left resident — the last live page of the nearest live
    row above it, or, with none above, the first page of the nearest one
    below (which that row then finds fetched).  The pipeline skips a
    fetch whose block index repeats, so a dead page and a dead row cost
    grid steps and no DMA."""
    b = cur_len.shape[0]
    live = cur_len > 0
    last_own = jnp.where(
        live, jnp.minimum((cur_len + tq - 2) // bt, n_pages - 1), 0)
    idx = jnp.arange(b, dtype=jnp.int32)
    above = jax.lax.cummax(jnp.where(live, idx, -1))
    below = jax.lax.cummin(jnp.where(live, idx, b), reverse=True)
    rows = jnp.where(above >= 0, above, jnp.where(below < b, below, 0))
    pinned = jnp.where(above >= 0, last_own[jnp.maximum(above, 0)], 0)
    return (rows.astype(jnp.int32),
            jnp.where(live, 0, pinned).astype(jnp.int32),
            jnp.where(live, last_own, pinned).astype(jnp.int32))


def _split_bf16(x, parts):
    """float32 ``x`` as a sum of ``parts`` bfloat16 arrays (8 mantissa
    bits each; three carry all 24)."""
    out = []
    for _ in range(parts - 1):
        hi = x.astype(jnp.bfloat16)
        out.append(hi)
        x = x - hi.astype(jnp.float32)
    out.append(x.astype(jnp.bfloat16))
    return out


def _paged_kernel(*refs, bt, rows_per_head, kv_heads, hd, group, s_total,
                  scale, quantized, has_pool, flat):
    """One (row, page) grid cell: select the page's KV source (slot row
    vs pool block), fold the page into the online softmax.  Scalar-
    prefetch refs lead.  ``flat`` is the one-token body (module
    docstring); otherwise the batched per-head body, int8 dequant fused
    in-VMEM."""
    refs = list(refs)
    len_ref, table_ref = refs[1], refs[5]
    pos = 6
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
    g = rows_per_head  # query rows a K/V head: Tq * group
    n_rows = kv_heads * g
    cur = len_ref[b]

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Dead-page skip: keys of page p start at p*bt; the largest index any
    # query can see is cur_len + tq - 2 (key j valid iff j < cur_len + t,
    # t < tq).  Pages past that, and every page of a row of length 0,
    # contribute nothing — no compute (and the fetch plan pins their DMA
    # to the block already resident, so no fetch either).
    limit = jnp.where(cur > 0, cur + (g // group - 1), 0)
    run = p * bt < limit

    def pick(slot_ref, pool_ref):
        if pool_ref is None:
            return slot_ref[...]
        return jax.lax.cond(table_ref[b, p] >= 0, lambda: pool_ref[...],
                            lambda: slot_ref[...])

    def online_softmax(s2, weigh):
        """Fold masked scores ``s2`` [n_rows, keys] into the scratch;
        ``weigh`` turns the page's softmax numerators into its share of
        the weighted values [n_rows, hd]."""
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s2, axis=-1, keepdims=True))
        pmat = jnp.exp(s2 - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(pmat, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * correction + weigh(pmat)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    def _flat_page(tail):
        keys = bt * kv_heads  # one column a (token, K/V head) of the page
        k2 = pick(sk_ref, pk_ref).reshape(keys, hd)
        v2 = pick(sv_ref, pv_ref).reshape(keys, hd)
        q2 = q_ref[0]  # [n_rows, hd]
        if k2.dtype != q2.dtype:
            q2, k2 = q2.astype(jnp.float32), k2.astype(jnp.float32)
        s2 = jax.lax.dot_general(
            q2, k2, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [n_rows, keys]: every query row against every head
        # Column c is (token c // kv_heads, head c % kv_heads) of the
        # page: a row keeps its own head's columns, of tokens below its
        # length (token t < n  <=>  c < n * kv_heads).  Heads and
        # lengths are worked out on one row and one column and only
        # compared at full size: integer division is slow on the VPU.
        col = jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        row_head = jax.lax.broadcasted_iota(jnp.int32, (n_rows, 1), 0) // g
        valid = ((col % kv_heads == row_head)
                 & (col < (cur - p * bt) * kv_heads))
        s2 = jnp.where(valid, s2, NEG_INF)
        if tail:
            # The last page of rows that end mid-page is a padded partial
            # block whose out-of-bounds rows hold garbage (NaN under the
            # interpreter): 0 * garbage would still poison the product
            # below.
            key = jax.lax.broadcasted_iota(jnp.int32, (keys, 1), 0)
            v2 = jnp.where(key < (s_total % bt) * kv_heads, v2,
                           jnp.zeros_like(v2))

        def weigh(pmat):
            # Masked columns are exact zeros, so the product over all
            # keys is each row's sum over its own head's tokens.
            if v2.dtype != jnp.bfloat16:
                return jax.lax.dot_general(
                    pmat, v2.astype(jnp.float32), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            # bf16 values as stored; the float32 weights go through the
            # MXU as bf16 parts that sum back to them, stacked as rows of
            # ONE product so each tile of the values is loaded once.
            parts = jnp.concatenate(_split_bf16(pmat, WEIGHT_PARTS), axis=0)
            out = jax.lax.dot_general(
                parts, v2, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return sum(out[i * n_rows:(i + 1) * n_rows]
                       for i in range(WEIGHT_PARTS))

        online_softmax(s2, weigh)

    def _batched_page():
        # Zero columns past the true row length: the last page may be a
        # padded partial block whose out-of-bounds lanes hold garbage
        # (NaN under the interpreter) — 0 * garbage would still poison
        # the pv matmul through masked-but-summed lanes.
        col = jax.lax.broadcasted_iota(jnp.int32, (bt, 1, 1), 0)
        in_range = (p * bt + col) < s_total
        k_page = jnp.where(
            in_range, pick(sk_ref, pk_ref).astype(jnp.float32), 0.0)
        v_page = jnp.where(
            in_range, pick(sv_ref, pv_ref).astype(jnp.float32), 0.0)

        q = q_ref[0].astype(jnp.float32)  # [kv_heads, g, hd]
        s = jax.lax.dot_general(
            q, k_page.transpose(1, 0, 2),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # [kv_heads, g, bt]
        if quantized:
            k_sc = pick(sks_ref, pks_ref)  # [bt, kv_heads, 1]
            s = s * k_sc.transpose(1, 2, 0)  # [kv_heads, 1, bt]

        jglob = p * bt + jax.lax.broadcasted_iota(jnp.int32, (g, bt), 1)
        t_idx = jax.lax.broadcasted_iota(jnp.int32, (g, bt), 0) // group
        valid = (jglob < cur + t_idx) & (jglob < s_total)
        s = jnp.where(valid[None], s, NEG_INF)

        def weigh(pmat):
            p3 = pmat.reshape(kv_heads, g, bt)
            if quantized:
                v_sc = jnp.where(in_range, pick(svs_ref, pvs_ref), 0.0)
                p3 = p3 * v_sc.transpose(1, 2, 0)
            return jax.lax.dot_general(
                p3, v_page.transpose(1, 0, 2), (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ).reshape(n_rows, hd)

        online_softmax(s.reshape(n_rows, bt), weigh)

    if not flat:
        pl.when(run)(_batched_page)
    elif s_total % bt == 0:
        pl.when(run)(functools.partial(_flat_page, False))
    else:
        # Two copies of the body, so that only the last page pays for
        # the select over its values (chosen inside one body, the page
        # would come back from a conditional: a copy of it every step).
        last = p == n_pages - 1
        pl.when(run & ~last)(functools.partial(_flat_page, False))
        pl.when(run & last)(functools.partial(_flat_page, True))

    @pl.when(p == n_pages - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out = acc_scr[...] / safe_l
        o_ref[0] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def _paged_pallas(q, cache_l, cur_len, pool_l, block_table, bt, *,
                  layer=None, interpret):
    """q [B,Tq,H,hd]; slot leaves [B,S,Hkv,hd], or the stacked
    [L,B,S,Hkv,hd] with ``layer`` the (traced) layer to read; pool leaves
    [NB,bt,Hkv,hd], or stacked likewise; block_table [B, ceil(S/bt)]
    int32 (-1 = slot page) or None; cur_len [B], 0 for a row to skip."""
    global KERNEL_TRACE_COUNT
    KERNEL_TRACE_COUNT += 1
    from jax.experimental.pallas import tpu as pltpu

    def stacked(leaves):
        # A single layer is a stack of one: a reshape, not a copy.
        if leaves["k"].ndim == 5:
            return leaves, layer
        return {name: x[None] for name, x in leaves.items()}, 0

    b, tq, h, hd = q.shape
    cache_l, slot_layer = stacked(cache_l)
    s_total, kv_heads = cache_l["k"].shape[2:4]
    group = h // kv_heads
    g = tq * group
    n_pages = -(-s_total // bt)
    quantized = "k_scale" in cache_l
    has_pool = pool_l is not None
    pool_layer = 0
    if has_pool:
        pool_l, pool_layer = stacked(pool_l)
    scale = 1.0 / math.sqrt(hd)
    # The one-token body merges the page's (token, head) rows into one
    # axis; Mosaic refuses that merge where an odd count of heads meets
    # a head_dim short of a lane row (a planning compile says so).
    flat = (tq == 1 and not quantized
            and (hd % 128 == 0 or kv_heads % 2 == 0))

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
    layers = jnp.stack([jnp.asarray(slot_layer, jnp.int32),
                        jnp.asarray(pool_layer, jnp.int32)])
    rows, first, last = _fetch_plan(cur_len, tq, bt, n_pages)

    # Query rows line up K/V-head-major: row kvh * g + (t * group + j).
    q = _group_queries(q, kv_heads).transpose(0, 2, 1, 3)  # [B,Hkv,g,hd]
    if flat:
        q = q.reshape(b, kv_heads * g, hd)
    q_block = (1,) + q.shape[1:]

    def q_map(b_, p_, *_):
        return (b_,) + (0,) * (len(q_block) - 1)

    def page_of(b_, p_, first_, last_):
        return jnp.minimum(jnp.maximum(p_, first_[b_]), last_[b_])

    def slot_map(b_, p_, lyr, ln, rows_, first_, last_, tbl):
        return (lyr[0], rows_[b_], page_of(b_, p_, first_, last_), 0, 0)

    def pool_map(b_, p_, lyr, ln, rows_, first_, last_, tbl):
        block = tbl[rows_[b_], page_of(b_, p_, first_, last_)]
        return (lyr[1], jnp.maximum(block, 0), 0, 0, 0)

    kv_spec = pl.BlockSpec((None, None, bt, kv_heads, hd), slot_map)
    sc_spec = pl.BlockSpec((None, None, bt, kv_heads, 1), slot_map)
    pkv_spec = pl.BlockSpec((None, None, bt, kv_heads, hd), pool_map)
    psc_spec = pl.BlockSpec((None, None, bt, kv_heads, 1), pool_map)

    in_specs = [pl.BlockSpec(q_block, q_map), kv_spec, kv_spec]
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
        _paged_kernel, bt=bt, rows_per_head=g, kv_heads=kv_heads, hd=hd,
        group=group, s_total=s_total, scale=scale, quantized=quantized,
        has_pool=has_pool, flat=flat,
    )
    n_rows = kv_heads * g
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(b, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(q_block, q_map),
        scratch_shapes=[
            pltpu.VMEM((n_rows, 128), jnp.float32),
            pltpu.VMEM((n_rows, 128), jnp.float32),
            pltpu.VMEM((n_rows, hd), jnp.float32),
        ],
    )
    page_bytes = bt * kv_heads * hd * cache_l["k"].dtype.itemsize
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # K and V pages (and the pool's), double-buffered, and room
            # for the page-sized float32 intermediates of either body.
            vmem_limit_bytes=min(
                100 << 20,
                (16 << 20) + 4 * (1 + has_pool) * page_bytes
                + 8 * bt * kv_heads * max(hd, n_rows) * 4),
        ),
        interpret=interpret,
        name="paged_decode",
    )(layers, cur_len, rows, first, last, block_table, *operands)
    out = out.reshape(b, kv_heads, g, hd).transpose(0, 2, 1, 3)
    return _ungroup_outputs(out, tq)


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
    k = cache_l["k"]
    return (
        q.ndim == 4
        and k.ndim in (4, 5)
        and bt is not None
        and q.shape[-1] <= 256  # head_dim beyond this overflows VMEM
        and q.shape[0] == k.shape[-4]
        and q.shape[2] % k.shape[-2] == 0
    )


def would_use_kernel(q, cache_l, *, page_tokens: Optional[int] = None
                     ) -> bool:
    """The ``use_pallas=None`` auto-dispatch predicate, exposed so the
    model programs, the serving engine and tests share one spelling.
    ``cache_l`` holds one layer's leaves or the stacked ones."""
    s = cache_l["k"].shape[-3]
    return (
        jax.default_backend() == "tpu"
        and _kernel_enabled()
        and _kernel_eligible(q, cache_l, _fit_page(s, page_tokens))
        and (q.shape[1] == 1 or s >= MIN_SEQ_LEN_FOR_KERNEL)
        # An int8 cache's scale leaves [..., Hkv, 1] reach the kernel
        # re-laid-out, one lane row a scale: a copy of 128 times their
        # bytes at every call (a planning compile shows it as temp of
        # four times the cache).  Only ``use_pallas=True`` takes that.
        and "k_scale" not in cache_l
    )


def _sharded(mesh, head_axes, batch_axes, bt, interpret, q, cache_l,
             cur_len, pool_l, block_table, layer):
    """The kernel per shard of ``mesh`` (a full-manual shard_map —
    ops/dispatch.py says why): every KV leaf, slot or pool, value or
    scale, has heads next to last; rows and their lengths, table and
    queries split over the batch axes, a pool's blocks never."""
    from jax.sharding import PartitionSpec as P

    heads = dispatch_lib.dividing_axes(mesh, head_axes,
                                       cache_l["k"].shape[-2])
    batch = dispatch_lib.dividing_axes(mesh, batch_axes, q.shape[0])

    def leaves(tree, rows):
        return None if tree is None else {
            name: P(*([None] * (x.ndim - 4)), rows, None, heads, None)
            for name, x in tree.items()}

    def local(q, cache_l, cur_len, pool_l, block_table, layer):
        return _paged_pallas(q, cache_l, cur_len, pool_l, block_table, bt,
                             layer=layer, interpret=interpret)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(batch, None, heads, None), leaves(cache_l, batch),
                  P(batch), leaves(pool_l, None), P(batch), P()),
        out_specs=P(batch, None, heads, None), check_vma=False,
    )(q, cache_l, cur_len, pool_l, block_table, layer)


def kernel_page(q, cache_l, *, page_tokens: Optional[int] = None,
                use_pallas: Optional[bool] = None,
                interpret: bool = False) -> Optional[int]:
    """The page size the kernel reads ``q``'s rows by, or None where the
    read takes the jnp reference over whole rows: ``use_pallas`` decides
    when given (and raises on a shape the kernel cannot take), else
    :func:`would_use_kernel` or an eligible shape under the interpreter.
    ``page_tokens`` is the prefix pool's block size when one rides
    along.  One spelling for the dispatch below and for whoever counts
    what a decode read fetches (the serving engine)."""
    bt = _fit_page(cache_l["k"].shape[-3], page_tokens)
    eligible = _kernel_eligible(q, cache_l, bt)
    if use_pallas and not eligible:
        raise ValueError(
            "paged attention (use_pallas=True): the kernel cannot take "
            f"q{tuple(q.shape)} over slot rows "
            f"{tuple(cache_l['k'].shape)} (needs rank-4 q and rows of one "
            "batch, query heads a multiple of the K/V heads, head_dim <= "
            "256, a page of >= 8 tokens)"
        )
    if use_pallas is None:
        use_pallas = would_use_kernel(
            q, cache_l, page_tokens=page_tokens
        ) or ((interpret or dispatch_lib.force_interpret())
              and eligible and _kernel_enabled())
    return bt if use_pallas else None


def paged_decode_attention(
    q: jnp.ndarray,
    cache_l,
    cur_len: jnp.ndarray,
    *,
    layer=None,
    pool_l=None,
    block_table: Optional[jnp.ndarray] = None,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    partitioned: bool = False,
    mesh=None,
    head_axes=None,
    batch_axes=None,
) -> jnp.ndarray:
    """Single-token decode attention ([B, 1, H, hd] queries) over a
    block-table view of slot rows + pool blocks.

    Drop-in for ``_cache_attention(q, cache_l, cur_len)``: key ``j`` of
    row ``b`` is valid iff ``j < cur_len[b]`` (callers pass ``pos + 1``
    exactly as they do to ``_cache_attention``); a row of length 0 is
    skipped whole — nothing of it is fetched, its output is zeros.
    ``cache_l`` (and ``pool_l``) hold either one layer's leaves
    [B, S, Hkv, hd] or the STACKED leaves [L, B, S, Hkv, hd] with
    ``layer`` the layer to read, a traced scalar: the stacked form is
    read in place, no layer is sliced out.  ``H`` may be a multiple of
    ``Hkv`` (grouped K/V heads).  ``block_table`` [B, n_pages] int32
    maps page ``p`` (positions ``[p*bt, (p+1)*bt)``) to a ``pool_l``
    block when ``>= 0``, to the slot row when ``-1``;
    ``block_table=None`` (or ``pool_l=None``) reads slot rows only —
    the cold-insert shape.

    ``partitioned=True`` under ``mesh`` (default: the framework's global
    mesh) of more than one device runs the kernel per shard:
    ``head_axes`` / ``batch_axes`` name the mesh axes the CALLER's heads
    and rows are split over (its rules' ``"heads"`` / ``"batch"``
    assignment; None: not split).

    The mask is the chunk-causal one (key ``j`` valid for query ``t``
    iff ``j < cur_len + t``), so longer query windows go through the
    same dispatch: :func:`paged_chunk_attention`,
    :func:`paged_verify_attention`.
    """
    bt = kernel_page(
        q, cache_l,
        page_tokens=None if pool_l is None else pool_l["k"].shape[-3],
        use_pallas=use_pallas, interpret=interpret)
    # CPU-test convenience: off-TPU the kernel can only be interpreted.
    interpret = (interpret or dispatch_lib.force_interpret()
                 or jax.default_backend() != "tpu")
    if bt is None:
        def one_layer(leaves):
            if leaves is None or leaves["k"].ndim == 4:
                return leaves
            return {name: jax.lax.dynamic_index_in_dim(
                x, layer, keepdims=False) for name, x in leaves.items()}

        return _reference(q, one_layer(cache_l), cur_len,
                          one_layer(pool_l), block_table)
    # Inside a manual region, or under no mesh of more than one device,
    # the shapes are one device's already: the direct call.
    kernel_mesh = dispatch_lib.kernel_mesh(mesh) if partitioned else None
    if kernel_mesh is not None:
        return _sharded(kernel_mesh, head_axes, batch_axes, bt, interpret,
                        q, cache_l, cur_len, pool_l, block_table,
                        jnp.asarray(0 if layer is None else layer,
                                    jnp.int32))
    return _paged_pallas(q, cache_l, cur_len, pool_l, block_table, bt,
                         layer=layer, interpret=interpret)


def paged_chunk_attention(q, cache_l, cur_len, **kwargs) -> jnp.ndarray:
    """Chunk-causal paged attention — the ``prefill_chunk_program``
    shape.  Queries are CONSECUTIVE cache positions starting at
    ``cur_len - 1``: key ``j`` is valid for query ``t`` iff
    ``j < cur_len + t`` (``_cache_attention(..., chunk_causal=True)``'s
    exact mask).  With ``T_q == 1`` this degenerates to
    :func:`paged_decode_attention` — one kernel serves both, and the
    keywords are its."""
    return paged_decode_attention(q, cache_l, cur_len, **kwargs)


def paged_verify_attention(q, cache_l, cur_len, **kwargs) -> jnp.ndarray:
    """Speculative verify-window paged attention — the
    ``verify_chunk_program`` shape ([num_slots, spec_k, H, hd] queries,
    per-slot window starts).  Mask-wise identical to
    :func:`paged_chunk_attention` (the window IS a chunk at ``pos``);
    a separate entry point so the serving dispatch sites and the
    crossover bench name the shape they measure."""
    return paged_decode_attention(q, cache_l, cur_len, **kwargs)
