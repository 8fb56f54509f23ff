"""Flash attention as a Pallas TPU kernel, with a jnp reference fallback.

Forward: online-softmax over K/V blocks — a grid over the (query block,
key block) pairs that hold work walks the key blocks of one query block
while VMEM scratch carries the running (max, sum, output) accumulators, so
attention scores never materialize in HBM (memory O(block_q x block_k)
instead of O(T^2)).  It does a causal prompt's work and no more: no grid
step or fetch for a pair above the diagonal, no work past a row's
``lengths``, a mask only on a tile the diagonal crosses, a value head of
its own size ("The forward kernel's schedule", below).  Backward: custom
VJP with the standard recompute scheme — one kernel accumulates dQ over
key blocks, one accumulates dK/dV over query blocks, both reusing the
forward's saved logsumexp so no O(T^2) residuals are stored.

Layout contract matches ``layers.causal_attention``: [B, T, H, D] in,
[B, T, H, Dv] out.  The backward kernels run over [B, H, T, D] (last two
dims tile onto the (8,128) VMEM lanes); the forward kernel holds its
scores transposed, so its queries, values and output have the sequence
in the lanes ([B, H, D, T]).  D and the block sizes should be multiples
of 128 for full MXU tiles — head_dim 64 works, at half-lane occupancy.

Dispatch: real TPU + tile-divisible shapes -> kernels; anything else (CPU
tests, ragged shapes, explicit masks) -> ``_reference`` (pure jnp, XLA).
The causal mask is applied in *global* positions so the kernels compose
with ring attention's per-block fold later.

No reference counterpart (SURVEY.md §5: the reference owns no kernels);
this is TPU-native capability the rebuild adds.
"""

from __future__ import annotations

import functools
import math
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from cloud_tpu.ops import dispatch as dispatch_lib
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30  # finite: fully-masked rows softmax to zeros, not NaN

#: The forward kernel's blocks (``_schedule``): a block of either side is
#: the largest power of two of rows, at most ``MAX_BLOCK_*``, that stays
#: within ``BLOCK_BYTES`` at the lanes its head size pads to; inside a
#: query block the work is decided in tiles of ``TILE_Q_ROWS`` rows.
#: Read on a TPU v5e by ``scripts/flash_crossover.py`` (docs/KERNELS.md).
MAX_BLOCK_Q = 512
MAX_BLOCK_K = 512
BLOCK_BYTES = 512 * 1024
TILE_Q_ROWS = 256
TILES_FUSED = 2

#: The backward kernels keep the blocks they were written with (their
#: schedule is no cell's; ROADMAP S10): the forward's, capped at these.
BWD_BLOCK_Q = 256
BWD_BLOCK_K = 512

#: A block whose rows lie in the lanes is a multiple of this, or whole.
LANES = 128

#: Auto-dispatch (``use_pallas=None``) takes the kernel only at T >= this.
#: Set from a round-3 reading on a TPU v5e (value+grad, steady state; not
#: re-measured on today's chip, ROADMAP S8): XLA's fused attention was
#: faster at T in [256, 512] (the whole O(T^2) score tensor still fits
#: cache-friendly tiles there), the kernel from 1024 up — and it
#: is O(T) in memory where XLA materializes the [B,H,T,T] scores.  Callers
#: that need the kernel below the threshold (masked long-tail, tests) pass
#: ``use_pallas=True`` explicitly.
MIN_SEQ_LEN_FOR_KERNEL = int(os.environ.get("CLOUD_TPU_FLASH_MIN_SEQ", 1024))

#: ...unless the would-be [B, H, Tq, Tk] f32 score tensor is this large
#: (bytes), in which case the kernel is taken regardless of T.  Speed is
#: not the issue below the T threshold — memory is: under ``value_and_grad``
#: XLA saves the softmax scores as residuals PER LAYER (a 12-layer BERT
#: scan at B=32, T=512 allocates 4.5 GiB f32 + 2.25 GiB bf16 of score
#: residuals and OOMs a 16 GiB v5e chip), while the kernel's residual is
#: the O(T) logsumexp.  128 MiB per call keeps a 12-layer stack under
#: ~1.5 GiB of attention residuals.
SCORE_BYTES_FOR_KERNEL = int(
    os.environ.get("CLOUD_TPU_FLASH_SCORE_BYTES", 128 * 1024**2)
)

#: Diagnostic counter: bumped every time a Pallas kernel call is actually
#: traced (fwd or bwd).  The multichip dryrun asserts it advances to prove
#: the kernel path — not the jnp reference — ran inside the pipeline
#: region (VERDICT r2 weak #5's done-criterion).
KERNEL_TRACE_COUNT = 0


# ---------------------------------------------------------------------------
# Reference implementation (ground truth + non-TPU fallback)
# ---------------------------------------------------------------------------


def _reference(q, k, v, *, causal, mask):
    return _reference_with_lse(q, k, v, causal=causal, mask=mask)[0]


def _reference_with_lse(q, k, v, *, causal, mask):
    """Reference path that also returns the log-sum-exp [B, H, T_q] —
    the quantity ring attention needs to merge per-block partials."""
    dim = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    s = s / math.sqrt(dim)
    t_q, t_k = q.shape[1], k.shape[1]
    if causal:
        causal_mask = jnp.tril(jnp.ones((t_q, t_k), bool), k=t_k - t_q)
        s = jnp.where(causal_mask, s, NEG_INF)
    if mask is not None:
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    w = (p / safe_l).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v)
    lse = (m + jnp.log(safe_l))[..., 0]  # [B, H, T_q]
    return out, lse


# ---------------------------------------------------------------------------
# The forward kernel's schedule
# ---------------------------------------------------------------------------
#
# A grid step holds one (query block, key block) pair: ``block_q`` query
# rows stay in VMEM while the key blocks at or under their diagonal pass
# by, ``block_k`` rows a step.  Inside a step the query block is worked
# through in tiles of ``tile_q`` rows, so what is COMPUTED is decided a
# tile at a time (and done ``fuse`` adjacent tiles at a time where all
# have work) while what is FETCHED comes in blocks large enough to keep K
# and V off the HBM ridge.  A causal call's grid is the list of pairs at
# or under the diagonal (scalar-prefetched), so a pair above it costs
# neither a step nor a fetch.  With ``lengths`` a tile whose rows or keys
# lie wholly past the row's length is not computed, and a pair with
# nothing to compute re-names the block that is already in VMEM, so it
# fetches nothing either.


class _Schedule(NamedTuple):
    block_q: int  # query rows a grid step holds
    block_k: int  # key rows a grid step fetches and computes against
    tile_q: int   # query rows of one compute tile inside the block
    fuse: int = 1  # adjacent tiles worked as one product where all have work


def _schedule(t, d, dv, itemsize, block_q=None, block_k=None, *,
              masked=False) -> Optional[_Schedule]:
    """The blocks for a [.., t, d] call with values of ``dv``: the ONE
    rule behind the dispatch, :func:`would_use_kernel` and
    :func:`forward_tiles`.  ``block_q`` / ``block_k`` are upper bounds
    (None: the rule's own, sized so a block of either side stays within
    ``BLOCK_BYTES`` of VMEM at the lanes its head size pads to); each is
    fitted to the largest aligned divisor of ``t`` under it.  The rule's
    own blocks are multiples of 128 or the whole of ``t``: the kernel
    holds the sequence in the LANES of its query, value and output
    blocks.  (A caller's own bound is fitted to a multiple of 8, for the
    interpreter's small shapes; the chip's compiler refuses such a block
    in words, ``_fwd_call``.)  None where no aligned divisor exists, or,
    under a key-side ``mask``, where the backward kernels' mask block
    finds none."""
    own_q, own_k = block_q is None, block_k is None
    if own_q:
        block_q = _rows_within(BLOCK_BYTES, d, itemsize, MAX_BLOCK_Q)
    if own_k:
        block_k = _rows_within(BLOCK_BYTES, max(d, dv), itemsize, MAX_BLOCK_K)
    # The rule's own query block is whole groups of fused tiles where T
    # has such a divisor (every width ``generation.prefill_widths`` makes
    # is a multiple of 512).
    fitted_q = (own_q and _fit_block(t, block_q,
                                     step=TILE_Q_ROWS * TILES_FUSED)
                or _fit_block(t, block_q, lane_aligned=own_q))
    fitted_k = _fit_block(t, block_k, lane_aligned=own_k)
    if fitted_q is None or fitted_k is None or (masked and _fit_block(
            t, min(fitted_k, BWD_BLOCK_K), lane_aligned=True) is None):
        return None
    tile_q = _fit_block(fitted_q, TILE_Q_ROWS, lane_aligned=own_q)
    return _Schedule(fitted_q, fitted_k, tile_q or fitted_q, TILES_FUSED)


def _rows_within(budget, width, itemsize, most):
    """The largest power-of-two count of rows, at most ``most``, whose
    block of ``width`` numbers (padded to whole 128-lane tiles) fits
    ``budget`` bytes."""
    lanes = -(-width // 128) * 128
    rows = most
    while rows > 128 and rows * lanes * itemsize > budget:
        rows //= 2
    return rows


def _tile_live(row0, key0, rows, length, causal):
    """Whether the tile of ``rows`` query rows from ``row0`` has anything
    to compute against the key block that starts at ``key0``: a key at or
    under its last row's diagonal and, with ``length``, a real row and a
    real key.  THE predicate: the kernel asks it of traced scalars, the
    grid and :func:`forward_tiles` of numpy arrays."""
    live = (key0 <= row0 + rows - 1) if causal else True
    if length is not None:
        live = live & (row0 < length) & (key0 < length)
    return live  # True itself where nothing bounds the tile


def _grid_pairs(t, schedule, causal):
    """The (query block, key block) pairs of the grid, in the order it
    walks them: by query block, the keys ascending."""
    qi, ki = np.meshgrid(np.arange(t // schedule.block_q),
                         np.arange(t // schedule.block_k), indexing="ij")
    keep = np.broadcast_to(
        _tile_live(qi * schedule.block_q, ki * schedule.block_k,
                   schedule.block_q, None, causal), qi.shape)
    return qi[keep].astype(np.int32), ki[keep].astype(np.int32)


def _tiles_run(t, schedule, length, causal=True) -> int:
    """The compute tiles (``tile_q`` x ``block_k``) the kernel runs for a
    row of ``length`` real tokens (None: all ``t``)."""
    qi, ki = _grid_pairs(t, schedule, causal)
    tile = np.arange(schedule.block_q // schedule.tile_q) * schedule.tile_q
    row0 = qi[:, None] * schedule.block_q + tile[None, :]
    live = _tile_live(row0, ki[:, None] * schedule.block_k,
                      schedule.tile_q, length, causal)
    return int(np.sum(np.broadcast_to(live, row0.shape)))


def forward_tiles(t: int, length: Optional[int] = None, *, head_dim: int,
                  value_dim: Optional[int] = None, itemsize: int = 2) -> int:
    """How many compute tiles the causal forward kernel runs, a head, for
    a row of ``length`` real tokens in a width of ``t`` (None: the whole
    width's causal triangle), under the schedule the dispatch gives that
    call.  For whoever counts the kernel's work (``ServingEngine.stats()``
    ``flash_pairs_run`` / ``flash_pairs_width``)."""
    schedule = _schedule(t, head_dim, value_dim or head_dim, itemsize)
    return 0 if schedule is None else _tiles_run(t, schedule, length)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, scale, causal, schedule, num_k_blocks, use_mask,
                use_lengths):
    """Scores are held TRANSPOSED, ``[keys, queries]``: the running max
    and sum of a query are then reductions over sublanes (element-wise
    over vector registers, then 8 -> 1) and live one query a LANE
    (``[1, block_q]``), where ``[block_q, 1]`` columns would cost a
    cross-lane reduction and a lane broadcast a row group a step.  So
    the queries and the values arrive with the sequence in the lanes
    (``qT`` [D, block_q], ``vT`` [Dv, block_k]), the output leaves that
    way (``[Dv, block_q]``), and no operand is transposed in here."""
    refs = list(refs)
    qi_ref, ki_ref = refs[:2]
    pos = 2
    len_ref = refs[pos] if use_lengths else None
    pos += 1 if use_lengths else 0
    qt_ref, k_ref, vt_ref = refs[pos:pos + 3]
    pos += 3
    mask_ref = refs[pos] if use_mask else None
    pos += 1 if use_mask else 0
    ot_ref, lse_ref, qt_scr, m_scr, l_scr, acct_scr = refs[pos:]
    block_q, block_k, tile_q, fuse = schedule
    step = pl.program_id(2)
    qi, ki = qi_ref[step], ki_ref[step]
    length = len_ref[pl.program_id(0)] if use_lengths else None
    key0 = ki * block_k

    # The body is written in ``lax`` primitives: every ``jnp`` call inside
    # a kernel is a jitted function of its own to trace, and an insert
    # program holds a dozen instances of this kernel (set-up pays for it).
    f32 = jnp.float32

    def lanes(row, shape):  # [1, n] over the sublanes of [m, n]
        return jax.lax.broadcast_in_dim(row, shape, (0, 1))

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jax.lax.full(m_scr.shape, NEG_INF, f32)
        l_scr[...] = jax.lax.full(l_scr.shape, 0.0, f32)
        acct_scr[...] = jax.lax.full(acct_scr.shape, 0.0, f32)
        # The scale goes into the queries once a query block, not into
        # every block of scores.
        qt = jax.lax.convert_element_type(qt_ref[0, 0], f32)
        qt_scr[...] = jax.lax.convert_element_type(
            jax.lax.mul(qt, jax.lax.full(qt.shape, scale, f32)),
            qt_scr.dtype)

    def tile_step(cols, row0, masked):
        st = jax.lax.dot_general(
            k_ref[0, 0], qt_scr[:, cols], (((1,), (0,)), ((), ())),
            preferred_element_type=f32,
        )  # [block_k, tile_q]: a key a row, a query a lane
        if masked:
            # Only a tile the diagonal crosses pays for the causal mask:
            # query - key >= key0 - row0, in positions inside the tile.
            ahead = jax.lax.sub(
                jax.lax.broadcasted_iota(jnp.int32, st.shape, 1),
                jax.lax.broadcasted_iota(jnp.int32, st.shape, 0))
            seen = jax.lax.ge(ahead, jax.lax.broadcast(key0 - row0, st.shape))
            st = jax.lax.select(seen, st,
                                jax.lax.full(st.shape, NEG_INF, f32))
        if mask_ref is not None:
            # Key-side padding mask, a key a row [block_k, 1] (nonzero =
            # valid token), broadcast over the queries — matches the
            # reference path's mask[:, None, None, :] semantics.
            st = jnp.where(mask_ref[0] != 0, st, NEG_INF)

        m_prev = m_scr[:, cols]  # [1, tile_q]
        m_new = jax.lax.max(m_prev, jax.lax.reduce_max(st, (0,))[None, :])
        p = jax.lax.exp(jax.lax.sub(st, lanes(m_new, st.shape)))
        correction = jax.lax.exp(jax.lax.sub(m_prev, m_new))  # [1, tile_q]
        l_scr[:, cols] = jax.lax.add(
            jax.lax.mul(l_scr[:, cols], correction),
            jax.lax.reduce_sum(p, (0,))[None, :])
        vt = vt_ref[0, 0]  # [Dv, block_k]
        pv = jax.lax.dot_general(
            vt, jax.lax.convert_element_type(p, vt.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=f32,
        )  # [Dv, tile_q]
        acc = acct_scr[:, cols]
        acct_scr[:, cols] = jax.lax.add(
            jax.lax.mul(acc, lanes(correction, acc.shape)), pv)
        m_scr[:, cols] = m_new

    def run(live, cols, row0):
        """The tiles of ``cols`` (from row ``row0``) as one product,
        where ``live``; masked only where the diagonal crosses them."""
        if causal:
            crossed = key0 + block_k - 1 > row0
            pl.when(live & crossed)(
                functools.partial(tile_step, cols, row0, True))
            pl.when(live & jnp.logical_not(crossed))(
                functools.partial(tile_step, cols, row0, False))
        elif live is True:
            tile_step(cols, row0, False)
        else:
            pl.when(live)(functools.partial(tile_step, cols, row0, False))

    # Work is DECIDED a tile at a time and DONE a group of adjacent tiles
    # at a time: one wide product where every tile of the group has work
    # (the common case, and the efficient one), a narrower one over the
    # run of tiles that have where a length ends the work inside the
    # group, or a key block starts inside it.  The tiles with work are
    # always one run: the diagonal takes leading tiles, a length trailing
    # ones.
    tiles = block_q // tile_q
    for first in range(0, tiles, fuse):
        count = min(fuse, tiles - first)
        width = count * tile_q
        start = qi * block_q + first * tile_q
        live = [_tile_live(start + n * tile_q, key0, tile_q, length, causal)
                for n in range(count)]
        # Where groups and key blocks start on multiples of the group's
        # width the diagonal never enters a group from inside.
        aligned = not causal or not (
            block_q % width or (first * tile_q) % width or block_k % width)
        for a in range(count):
            for b in range(a + 1, count + 1):
                if (a and aligned) or (b < count and length is None):
                    continue  # no such run
                # The run's edges say it all: the tiles between are live.
                only = live[a] & live[b - 1]
                if a:
                    only = only & jnp.logical_not(live[a - 1])
                if b < count:
                    only = only & jnp.logical_not(live[b])
                run(only, slice((first + a) * tile_q, (first + b) * tile_q),
                    start + a * tile_q)

    last_ki = (((qi + 1) * block_q - 1) // block_k if causal
               else num_k_blocks - 1)

    @pl.when(ki == last_ki)
    def _finalize():
        l = l_scr[...]  # [1, block_q]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out = acct_scr[...] / safe_l  # [Dv, block_q]
        if use_lengths:
            # Rows past the length hold no token: zeros, whatever the
            # tiles that held them computed (a block wholly past it
            # computed nothing).
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_q), 1)
            out = jnp.where(row < length, out, 0.0)
        ot_ref[0, 0] = out.astype(ot_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(safe_l)


def _check_divisible(t, block_q, block_k):
    if t % block_q or t % block_k:
        # The grid would silently skip the tail rows otherwise.
        raise ValueError(
            f"flash attention kernel needs T divisible by the block sizes; "
            f"got T={t}, block_q={block_q}, block_k={block_k}"
        )
    if block_q % 8 or block_k % 8:
        # Catches e.g. T=100 clamped to block=100: divisible, but Mosaic
        # would fail the (8,128) sublane tile with a cryptic error.
        raise ValueError(
            f"flash attention blocks must be multiples of 8 (sublane tile); "
            f"got block_q={block_q}, block_k={block_k}"
        )


def _carry_vma(*operands):
    """The varying-manual-axes set the kernel outputs must declare when the
    call is traced inside a ``check_vma=True`` shard_map (e.g. the pipeline
    body): outputs vary over every axis any operand varies over.  Outside a
    manual region every vma is empty, so this is a no-op there."""
    vma = frozenset()
    for x in operands:
        if x is None:
            continue
        aval = jax.typeof(x)
        vma = vma | getattr(aval, "vma", frozenset())
    return vma


def _fwd_pallas(q, k, v, mask, lengths, *, causal, schedule, interpret):
    """q,k: [B, H, T, D]; v: [B, H, T, Dv]; mask: [B, T] i32 or None;
    lengths: [B] i32 or None (causal calls only) ->
    (out [B, H, T, Dv], lse [B, H, T, 1]).  The kernel takes the queries
    and the values, and leaves the output, with the sequence in the
    lanes (:func:`_fwd_call`); the transposes here are XLA's, which folds
    each into the one the dispatch already makes of that operand."""
    global KERNEL_TRACE_COUNT
    KERNEL_TRACE_COUNT += 1
    out_t, lse = _fwd_call(
        q.swapaxes(2, 3), k, v.swapaxes(2, 3), mask, lengths, causal=causal,
        schedule=schedule, interpret=interpret)
    return out_t.swapaxes(2, 3), lse.swapaxes(2, 3)


# Jitted (and inlined) for its tracing cache alone: a program calls the
# kernel at one shape from several places (an insert program's two layer
# stacks at each of its widths), and tracing the kernel is what a call
# costs at set-up.
@functools.partial(jax.jit, inline=True,
                   static_argnames=("causal", "schedule", "interpret"))
def _fwd_call(qt, k, vt, mask, lengths, *, causal, schedule, interpret):
    """qt: [B, H, D, T]; k: [B, H, T, D]; vt: [B, H, Dv, T] ->
    (out [B, H, Dv, T], lse [B, H, 1, T])."""
    from jax.experimental.pallas import tpu as pltpu

    b, h, t, d = k.shape
    dv = vt.shape[2]
    block_q, block_k, tile_q, _ = schedule
    _check_divisible(t, block_q, block_k)
    if not interpret and any(
            rows % LANES and rows != whole
            for rows, whole in ((block_q, t), (block_k, t),
                                (tile_q, block_q))):
        raise ValueError(
            "flash attention's forward kernel holds the sequence in the "
            "lanes: blocks must be multiples of 128 or the whole of T; "
            f"got T={t}, block_q={block_q}, block_k={block_k}, "
            f"tile_q={tile_q}")
    use_mask, use_lengths = mask is not None, lengths is not None
    assert causal or not use_lengths, "lengths are a causal call's"
    if use_lengths:
        lengths = jnp.clip(lengths, 0, t)  # the index maps divide by it
    pairs_q, pairs_k = _grid_pairs(t, schedule, causal)

    # Index maps see (b, h, step, *scalar-prefetched refs).  With lengths a
    # pair with nothing to compute names the last block that has, so the
    # pipeline re-uses what is in VMEM and fetches nothing.
    def last_real(refs, b_):
        return jnp.maximum(refs[2][b_] - 1, 0)

    def query_block(b_, step, refs):
        qi = refs[0][step]
        if use_lengths:
            qi = jnp.minimum(qi, last_real(refs, b_) // block_q)
        return qi

    def key_block(b_, step, refs):
        ki = refs[1][step]
        if use_lengths:
            last = last_real(refs, b_)
            ki = jnp.where(refs[0][step] * block_q <= last,
                           jnp.minimum(ki, last // block_k),
                           last // block_k)
        return ki

    in_specs = [
        pl.BlockSpec((1, 1, d, block_q), lambda b_, h_, step, *refs: (
            b_, h_, 0, query_block(b_, step, refs))),
        pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, step, *refs: (
            b_, h_, key_block(b_, step, refs), 0)),
        pl.BlockSpec((1, 1, dv, block_k), lambda b_, h_, step, *refs: (
            b_, h_, 0, key_block(b_, step, refs))),
    ]
    prefetched = [pairs_q, pairs_k] + ([lengths] if use_lengths else [])
    operands = [qt, k, vt]
    if use_mask:
        in_specs.append(pl.BlockSpec(
            (1, block_k, 1),
            lambda b_, h_, step, *refs: (b_, key_block(b_, step, refs), 0)))
        operands.append(mask[:, :, None])  # a key a row, as the scores
    vma = _carry_vma(qt, k, vt, mask, lengths)

    def o_map(b_, h_, step, *refs):
        return b_, h_, 0, refs[0][step]

    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=1.0 / math.sqrt(d), causal=causal,
            schedule=schedule, num_k_blocks=t // block_k,
            use_mask=use_mask, use_lengths=use_lengths,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(b, h, len(pairs_q)),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, dv, block_q), o_map),
                pl.BlockSpec((1, 1, 1, block_q), o_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((d, block_q), qt.dtype),
                pltpu.VMEM((1, block_q), jnp.float32),
                pltpu.VMEM((1, block_q), jnp.float32),
                pltpu.VMEM((dv, block_q), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, dv, t), qt.dtype, vma=vma),
            jax.ShapeDtypeStruct((b, h, 1, t), jnp.float32, vma=vma),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(*prefetched, *operands)


def _mask_rows(mask):
    """[B, T] -> [B, 1, T]: a (1, block_k) block of the rank-2 mask has a
    second-to-last dim of 1, neither a multiple of 8 nor the array's own,
    and Mosaic refuses it; with a unit middle axis the block (1, 1,
    block_k) takes the array's own there."""
    return mask[:, None, :]


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    )


# ---------------------------------------------------------------------------
# Backward kernels (recompute scheme)
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, use_mask,
                   use_glse):
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    pos = 6
    glse_ref = refs[pos] if use_glse else None
    pos += 1 if use_glse else 0
    mask_ref = refs[pos] if use_mask else None
    pos += 1 if use_mask else 0
    dq_ref, dq_scr = refs[pos], refs[pos + 1]
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run = (
        (ki * block_k <= qi * block_q + block_q - 1) if causal else True
    )

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # [block_q, 1]
        delta = delta_ref[0, 0]  # [block_q, 1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if mask_ref is not None:
            s = jnp.where(mask_ref[0] != 0, s, NEG_INF)
        p = jnp.exp(s - lse)  # [block_q, block_k]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        # lse cotangent: d(lse_i)/d(s_ij) = p_ij, so ds += p * g_lse.
        row_term = delta - (glse_ref[0, 0] if glse_ref is not None else 0.0)
        ds = p * (dp - row_term)  # [block_q, block_k] f32
        dq_scr[...] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, block_q, block_k, use_mask,
                    use_glse):
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    pos = 6
    glse_ref = refs[pos] if use_glse else None
    pos += 1 if use_glse else 0
    mask_ref = refs[pos] if use_mask else None
    pos += 1 if use_mask else 0
    dk_ref, dv_ref, dk_scr, dv_scr = refs[pos:pos + 4]
    ki, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # Query blocks entirely above the diagonal see none of this key block.
    run = (
        (qi * block_q + block_q - 1 >= ki * block_k) if causal else True
    )

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # [block_q, 1]
        delta = delta_ref[0, 0]  # [block_q, 1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if mask_ref is not None:
            # This grid walks key blocks in dim 2: the mask block is the
            # one covering this kernel's key rows (index i, not j).
            s = jnp.where(mask_ref[0] != 0, s, NEG_INF)
        p = jnp.exp(s - lse)  # [block_q, block_k]
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_k, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        row_term = delta - (glse_ref[0, 0] if glse_ref is not None else 0.0)
        ds = p * (dp - row_term)
        dk_scr[...] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_k, D]

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, mask, lengths, do, out, lse, *, causal, schedule,
                interpret, g_lse=None):
    """``g_lse`` is the [B, H, T, 1] cotangent of the forward's lse output
    (None for the out-only entry point); it adds ``p * g_lse`` to ds in
    both kernels.  The value head may have a size of its own (``dv``):
    ``dp`` contracts over it, ``dv`` has it.  With ``lengths`` the rows
    past a length are the forward's zeros: their cotangents are dropped
    and their ``p`` made zero (an lse no score reaches) before the causal
    kernels run."""
    global KERNEL_TRACE_COUNT
    KERNEL_TRACE_COUNT += 1
    b, h, t, d = q.shape
    dv = v.shape[-1]
    block_q = _fit_block(t, min(schedule.block_q, BWD_BLOCK_Q))
    block_k = _fit_block(t, min(schedule.block_k, BWD_BLOCK_K),
                         lane_aligned=mask is not None)
    _check_divisible(t, block_q, block_k)
    nq, nk = t // block_q, t // block_k
    scale = 1.0 / math.sqrt(d)
    use_mask = mask is not None
    use_glse = g_lse is not None
    if lengths is not None:
        real = _length_mask(lengths, t)[:, None, :, None]
        do = jnp.where(real, do, jnp.zeros_like(do))
        lse = jnp.where(real, lse, -NEG_INF)
        if use_glse:
            g_lse = jnp.where(real, g_lse, 0.0)
    # delta_i = rowsum(dO_i * O_i): elementwise, XLA fuses it; no kernel.
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=True,
    )  # [B, H, T, 1], matching lse's layout

    def rows_i(block, width):  # blocks of the grid's third axis
        return pl.BlockSpec((1, 1, block, width),
                            lambda b_, h_, i, j: (b_, h_, i, 0))

    def rows_j(block, width):  # blocks of its fourth, accumulating axis
        return pl.BlockSpec((1, 1, block, width),
                            lambda b_, h_, i, j: (b_, h_, j, 0))

    dq_in_specs = [rows_i(block_q, d), rows_j(block_k, d),
                   rows_j(block_k, dv), rows_i(block_q, dv),
                   rows_i(block_q, 1), rows_i(block_q, 1)]
    dq_operands = [q, k, v, do, lse, delta]
    if use_glse:
        dq_in_specs.append(rows_i(block_q, 1))
        dq_operands.append(g_lse)
    if use_mask:
        dq_in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b_, h_, i, j: (b_, 0, j))
        )
        dq_operands.append(_mask_rows(mask))
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, use_mask=use_mask,
            use_glse=use_glse,
        ),
        grid=(b, h, nq, nk),
        in_specs=dq_in_specs,
        out_specs=[rows_i(block_q, d)],
        out_shape=[jax.ShapeDtypeStruct(
            q.shape, q.dtype, vma=_carry_vma(*dq_operands))],
        scratch_shapes=[_vmem((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*dq_operands)[0]

    # dK/dV: grid walks key blocks in the parallel dims, query blocks in the
    # arbitrary (accumulating) dim.
    dkv_in_specs = [rows_j(block_q, d), rows_i(block_k, d),
                    rows_i(block_k, dv), rows_j(block_q, dv),
                    rows_j(block_q, 1), rows_j(block_q, 1)]
    dkv_operands = [q, k, v, do, lse, delta]
    if use_glse:
        dkv_in_specs.append(rows_j(block_q, 1))
        dkv_operands.append(g_lse)
    if use_mask:
        dkv_in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b_, h_, i, j: (b_, 0, i))
        )
        dkv_operands.append(_mask_rows(mask))
    dk, dv_out = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, use_mask=use_mask,
            use_glse=use_glse,
        ),
        grid=(b, h, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[rows_i(block_k, d), rows_i(block_k, dv)],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype,
                                 vma=_carry_vma(*dkv_operands)),
            jax.ShapeDtypeStruct(v.shape, v.dtype,
                                 vma=_carry_vma(*dkv_operands)),
        ],
        scratch_shapes=[
            _vmem((block_k, d), jnp.float32),
            _vmem((block_k, dv), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*dkv_operands)
    return dq, dk, dv_out


# ---------------------------------------------------------------------------
# custom_vjp plumbing + public dispatch
# ---------------------------------------------------------------------------


def _int_cotangents(*operands):
    """The cotangents of the i32 mask and lengths: float0 (integer
    operands carry no gradient), None for one that was not given."""
    return tuple(
        None if x is None else np.zeros(x.shape, jax.dtypes.float0)
        for x in operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash(q, k, v, mask, lengths, causal, schedule, interpret):
    out, _ = _fwd_pallas(
        q, k, v, mask, lengths, causal=causal, schedule=schedule,
        interpret=interpret,
    )
    return out


def _flash_fwd(q, k, v, mask, lengths, causal, schedule, interpret):
    out, lse = _fwd_pallas(
        q, k, v, mask, lengths, causal=causal, schedule=schedule,
        interpret=interpret,
    )
    return out, (q, k, v, mask, lengths, out, lse)


def _flash_bwd(causal, schedule, interpret, residuals, g):
    q, k, v, mask, lengths, out, lse = residuals
    dq, dk, dv = _bwd_pallas(
        q, k, v, mask, lengths, g, out, lse, causal=causal,
        schedule=schedule, interpret=interpret,
    )
    return (dq, dk, dv) + _int_cotangents(mask, lengths)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_lse(q, k, v, mask, lengths, causal, schedule, interpret):
    """Kernel forward returning (out, lse [B,H,T,1]) — the building block
    for ring attention's per-block folds.  The VJP handles BOTH outputs'
    cotangents: g_lse enters ds as ``p * g_lse`` (dlse/ds = softmax)."""
    return _fwd_pallas(
        q, k, v, mask, lengths, causal=causal, schedule=schedule,
        interpret=interpret,
    )


def _flash_lse_fwd(q, k, v, mask, lengths, causal, schedule, interpret):
    out, lse = _fwd_pallas(
        q, k, v, mask, lengths, causal=causal, schedule=schedule,
        interpret=interpret,
    )
    return (out, lse), (q, k, v, mask, lengths, out, lse)


def _flash_lse_bwd(causal, schedule, interpret, residuals, g):
    q, k, v, mask, lengths, out, lse = residuals
    g_out, g_lse = g
    dq, dk, dv = _bwd_pallas(
        q, k, v, mask, lengths, g_out, out, lse, causal=causal,
        schedule=schedule, interpret=interpret, g_lse=g_lse,
    )
    return (dq, dk, dv) + _int_cotangents(mask, lengths)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# ---------------------------------------------------------------------------
# Mesh route
# ---------------------------------------------------------------------------
#
# ``pallas_call`` lowers to a custom call the partitioner cannot split: in
# an auto-sharded context an unwrapped kernel would replicate every
# operand.  ``partitioned=True`` under a mesh of more than one device runs
# the kernels per (batch, heads) shard in a full-manual shard_map
# (ops/dispatch.py says why not ``custom_partitioning``).  INSIDE a
# partial-manual region (the pp pipeline body) there is no route the
# chip's compiler takes yet — a nested shard_map fails sdy verification
# ("manual axis after free axis"), JAX refuses a Mosaic call there, libtpu
# refuses ``custom_partitioning`` on more than one chip — so there the
# compiled kernel is not offered (ROADMAP S8); the interpreter, whose
# kernel is plain HLO the partitioner splits itself, is called directly.


def _flash_sharded(mesh, batch_axes, head_axes, q, k, v, mask_i32, lengths,
                   *, causal, schedule, interpret):
    """The kernels per (batch, heads) shard of ``mesh``; [B, T, H, D] in and
    out.  Sequence and depth are whole in every shard."""
    from jax.sharding import PartitionSpec as P

    batch = dispatch_lib.dividing_axes(mesh, batch_axes, q.shape[0])
    heads = dispatch_lib.dividing_axes(mesh, head_axes, q.shape[2])
    bthd = P(batch, None, heads, None)
    rows = {"mask": (mask_i32, P(batch, None)), "lengths": (lengths, P(batch))}
    given = {name: x for name, x in rows.items() if x[0] is not None}

    def local(q, k, v, *per_row):
        per_row = dict(zip(given, per_row))
        out = _flash(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), per_row.get("mask"),
            per_row.get("lengths"), causal, schedule, interpret,
        )
        return out.transpose(0, 2, 1, 3)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(bthd,) * 3 + tuple(spec for _, spec in given.values()),
        out_specs=bthd, check_vma=False,
    )(q, k, v, *(x for x, _ in given.values()))


def _in_partial_manual_region() -> bool:
    """Inside a shard_map that is manual over SOME mesh axes (the pp
    pipeline body) — not a full-manual one (ring attention, Ulysses, the
    mesh route above), where a kernel call is one device's own."""
    from cloud_tpu.parallel import sharding as sharding_lib

    am = sharding_lib.manual_context_mesh()
    return am is not None and any(
        t != jax.sharding.AxisType.Manual for t in am.axis_types
    )


def _score_bytes(q, k) -> int:
    """Size of the would-be [B, H, Tq, Tk] f32 score tensor."""
    return (
        q.shape[0] * q.shape[2] * q.shape[1] * k.shape[1] * 4
        if q.ndim == 4 else 0
    )


def _kernel_worthwhile(q, k) -> bool:
    """The size half of the auto-dispatch predicate: is this shape big
    enough that the kernel (not XLA's fused path) is the right call?
    Shared by would_use_kernel and the partitioned-fallback warning so
    the two can't drift."""
    return (
        q.shape[1] >= MIN_SEQ_LEN_FOR_KERNEL
        or _score_bytes(q, k) >= SCORE_BYTES_FOR_KERNEL
    )


_partitioned_fallback_warned = False


def _warn_partitioned_fallback(q, k, mask):
    """One-time warning when a ``partitioned=True`` caller (the pipeline
    region / mesh-auto path, which EXPECTS the O(T) kernel) falls back to
    the O(T^2) reference at a size where that hurts — ineligible shapes
    (unalignable T, head_dim > 256, mask shape mismatch) and the compiled
    kernel inside a partial-manual region reach here with no other
    signal."""
    global _partitioned_fallback_warned
    if _partitioned_fallback_warned:
        return
    if not _kernel_worthwhile(q, k):
        return  # below both thresholds XLA's fused path is the right call
    if jax.default_backend() != "tpu" and not dispatch_lib.force_interpret():
        return  # off-TPU the reference is the only option — not a fallback
    _partitioned_fallback_warned = True
    import logging

    logging.getLogger(__name__).warning(
        "partitioned attention dispatch at q shape %s fell back to the "
        "O(T^2) jnp reference (%s). Expect per-layer score residual "
        "memory.",
        tuple(q.shape),
        "the compiled kernel has no route inside a partial-manual region "
        "(the pp pipeline body) yet"
        if _in_partial_manual_region() else
        "shape not kernel-eligible: unalignable T, head_dim > 256, or mask "
        "shape mismatch; pad T to an 8-aligned size to restore the kernel",
    )


def _length_mask(lengths, t, mask=None):
    """[B] lengths -> the [B, t] key-side mask of right-padded rows, and
    with ``mask``, of what both leave."""
    held = jnp.arange(t)[None, :] < lengths[:, None]
    return held if mask is None else held & (mask != 0)


def _dispatch(q, k, v, *, causal, mask, lengths, block_q, block_k,
              use_pallas, interpret, with_lse, partitioned=False, mesh=None,
              batch_axes=None, head_axes=None):
    """Shared fit/dispatch/transpose wrapper for both public entry points
    (kept in ONE place so mask/fit rules can't drift between them)."""
    explicit_opt_out = use_pallas is False
    if not interpret and dispatch_lib.force_interpret():
        interpret = True
    if lengths is not None and not causal:
        # Without the diagonal the lengths are a key-side mask like any.
        mask, lengths = _length_mask(lengths, k.shape[1], mask), None
    schedule = _schedule_for(q, v, mask, block_q, block_k)
    if use_pallas and not (_mask_ok(q, k, mask, lengths)
                           and _shape_eligible(q, k, v)):
        # An explicit request is never answered with the jnp reference.
        # (A T no block fits is _check_divisible's error, further down.)
        raise ValueError(
            "flash_attention(use_pallas=True): the kernel cannot take "
            f"q{tuple(q.shape)} k{tuple(k.shape)} v{tuple(v.shape)} "
            f"mask{None if mask is None else tuple(mask.shape)} (needs "
            "[B,T,H,D] q and k of one shape, v [B,T,H,Dv], head sizes "
            "<= 256, mask [B,T], lengths [B])"
        )
    if use_pallas and not interpret and _in_partial_manual_region():
        raise NotImplementedError(
            "flash_attention(use_pallas=True) inside a partial-manual "
            "region (the pp pipeline body): no route compiles for the "
            "chip there yet (ROADMAP S8)"
        )
    if use_pallas is None:
        use_pallas = takes_kernel(
            q, k, v, mask, lengths=lengths, block_q=block_q,
            block_k=block_k, interpret=interpret)
    if not use_pallas:
        # Warn only when AUTO dispatch fell back — an explicit
        # use_pallas=False caller opted out deliberately.
        if partitioned and not explicit_opt_out:
            _warn_partitioned_fallback(q, k, mask)
        if lengths is not None:
            # The reference knows a padded row by its key-side mask.
            mask = _length_mask(lengths, k.shape[1], mask)
        if with_lse:
            return _reference_with_lse(q, k, v, causal=causal, mask=mask)
        return _reference(q, k, v, causal=causal, mask=mask)
    if schedule is None:
        # No aligned divisor of T (forced kernel path only): the clamp,
        # and _check_divisible's error.
        t = q.shape[1]
        schedule = _Schedule(min(block_q or t, t), min(block_k or t, t),
                             min(block_q or t, t))
    mask_i32 = None if mask is None else mask.astype(jnp.int32)
    lengths = None if lengths is None else lengths.astype(jnp.int32)
    kernel_mesh = dispatch_lib.kernel_mesh(mesh) if partitioned else None
    if kernel_mesh is not None:
        return _flash_sharded(
            kernel_mesh, batch_axes, head_axes, q, k, v, mask_i32, lengths,
            causal=causal, schedule=schedule, interpret=interpret,
        )
    # [B, T, H, D] -> [B, H, T, D] for (T, D)-tiled kernels.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if with_lse:
        out, lse = _flash_lse(
            qt, kt, vt, mask_i32, lengths, causal, schedule, interpret
        )
        return out.transpose(0, 2, 1, 3), lse[..., 0]
    out = _flash(qt, kt, vt, mask_i32, lengths, causal, schedule, interpret)
    return out.transpose(0, 2, 1, 3)


def flash_attention_with_lse(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    mask: Optional[jnp.ndarray] = None,
    lengths: Optional[jnp.ndarray] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
):
    """Like :func:`flash_attention` but also returns lse [B, H, T_q] —
    fully differentiable in both outputs (ring attention merges per-block
    partials through the lse, so its gradient must flow).
    """
    return _dispatch(
        q, k, v, causal=causal, mask=mask, lengths=lengths, block_q=block_q,
        block_k=block_k, use_pallas=use_pallas, interpret=interpret,
        with_lse=True,
    )


def _fit_block(t: int, block: int, *, lane_aligned: bool = False,
               step: Optional[int] = None) -> Optional[int]:
    """Largest multiple-of-8 block <= ``block`` that divides ``t``.

    T=768 with a block of 512 fits at 384 (not a clamp — 512 doesn't
    divide 768); T=100 has no 8-aligned divisor and returns None
    (the (8,128) sublane tile would break).  ``lane_aligned`` is for the
    key block under a mask: it is the LAST dim of the mask's block, so
    it must be a multiple of 128 unless it is the whole of T.  ``step``
    asks for a multiple of that many rows instead."""
    top = min(block, t)
    if lane_aligned and top == t:
        return t if t % 8 == 0 else None
    step = step or (LANES if lane_aligned else 8)
    for candidate in range(top - top % step, step - 1, -step):
        if t % candidate == 0:
            return candidate
    return None


def _schedule_for(q, v, mask, block_q, block_k) -> Optional[_Schedule]:
    """:func:`_schedule` of a [B, T, H, D] call, read off its operands."""
    if q.ndim != 4 or v.ndim != 4:
        return None
    return _schedule(q.shape[1], q.shape[-1], v.shape[-1],
                     jnp.dtype(q.dtype).itemsize, block_q, block_k,
                     masked=mask is not None)


def _mask_ok(q, k, mask, lengths=None) -> bool:
    return (mask is None or (
        mask.ndim == 2
        and mask.shape[0] == q.shape[0]
        and mask.shape[1] == k.shape[1]
    )) and (lengths is None or tuple(lengths.shape) == (q.shape[0],))


def _shape_eligible(q, k, v=None) -> bool:
    """q and k of one shape; the values may have a head size of their
    own.  A head beyond 256 overflows the VMEM blocks."""
    v = k if v is None else v
    return (
        q.ndim == 4
        and q.shape == k.shape
        and v.shape[:-1] == k.shape[:-1]
        and q.shape[-1] <= 256
        and v.shape[-1] <= 256
    )


def _kernel_eligible(q, k, block_q, block_k, v=None) -> bool:
    """Called with blocks already fitted to T: both must have resolved to
    8-aligned divisors of their sequence length."""
    return (
        _shape_eligible(q, k, v)
        and block_q is not None
        and block_k is not None
    )


def _expressible(q, k, v, mask, lengths, block_q, block_k) -> bool:
    """Whether the kernels can take the call at all: its shapes, and
    blocks that fit its T."""
    schedule = _schedule_for(q, v, mask, block_q, block_k)
    blocks = (None, None) if schedule is None else schedule[:2]
    return (_mask_ok(q, k, mask, lengths)
            and _kernel_eligible(q, k, *blocks, v=v))


def would_use_kernel(
    q,
    k,
    mask: Optional[jnp.ndarray] = None,
    *,
    v=None,
    lengths=None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> bool:
    """The ``use_pallas=None`` auto-dispatch predicate on a TPU, exposed
    so callers (tests, capacity planners) never duplicate it and drift.
    ``v`` (default: of ``k``'s shape) may have a head size of its own."""
    return (
        jax.default_backend() == "tpu"
        and _kernel_worthwhile(q, k)
        and _expressible(q, k, k if v is None else v, mask, lengths,
                         block_q, block_k)
        and not _in_partial_manual_region()
    )


def takes_kernel(q, k, v=None, mask=None, *, lengths=None, block_q=None,
                 block_k=None, interpret: Optional[bool] = None) -> bool:
    """What ``use_pallas=None`` does with a call of these shapes:
    :func:`would_use_kernel` on a TPU; under the interpreter (``interpret``,
    default: armed by ``CLOUD_TPU_FLASH_FORCE_INTERPRET``) every shape the
    kernels can express — rectangular q/k, an oversize head or an
    unalignable T still take the reference.  The dispatch asks it, and
    whoever counts the kernel's work."""
    if interpret is None:
        interpret = dispatch_lib.force_interpret()
    return would_use_kernel(
        q, k, mask, v=v, lengths=lengths, block_q=block_q, block_k=block_k,
    ) or (interpret and _expressible(
        q, k, k if v is None else v, mask, lengths, block_q, block_k))


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    mask: Optional[jnp.ndarray] = None,
    lengths: Optional[jnp.ndarray] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    partitioned: bool = False,
    mesh=None,
    batch_axes=None,
    head_axes=None,
) -> jnp.ndarray:
    """Attention over [B, T, H, D] tensors, differentiable.  ``q`` and
    ``k`` have one shape; ``v`` may have a head size of its own, which
    the output takes ([B, T, H, Dv]).

    ``use_pallas=None`` auto-dispatches: kernels on TPU when shapes tile,
    reference jnp otherwise.  ``mask`` is a [B, T_k] valid-token padding
    mask (bool/int; nonzero = attend) applied key-side inside the kernels —
    fully-masked query rows produce uniform garbage (finite NEG_INF
    semantics), which the caller's loss mask must drop, matching the
    reference path.  ``interpret=True`` runs the kernels in the Pallas
    interpreter (CPU tests of kernel logic).

    ``lengths`` [B] says each row is right-padded past that many real
    tokens.  On a causal call it takes the mask's place — a real row's
    keys all lie under its own position, so no key needs masking — and
    the forward kernel neither fetches nor computes what lies wholly past
    the length; a row past it comes back as ZEROS from the kernel and as
    whatever the key-side mask leaves from the reference: the caller reads
    neither.  On a non-causal call it is the key-side mask it spells.

    ``block_q`` / ``block_k`` are upper bounds on the forward kernel's
    blocks; left None the blocks follow the shapes (``_schedule``).

    ``partitioned=True`` places the kernels under a mesh (batch/heads
    shardable, sequence replicated) instead of the caller wrapping a
    shard_map: over ``mesh`` (default: the framework's global mesh) in a
    full-manual shard_map, batch and heads split over ``batch_axes`` /
    ``head_axes`` — the mesh axes the CALLER's sharding rules assign to
    them (None: not split).  Inside a partial-manual region (the pipeline
    body) the compiled kernel is not offered yet: ``use_pallas=True``
    raises, auto-dispatch takes the reference with a warning.
    """
    return _dispatch(
        q, k, v, causal=causal, mask=mask, lengths=lengths, block_q=block_q,
        block_k=block_k, use_pallas=use_pallas, interpret=interpret,
        with_lse=False, partitioned=partitioned, mesh=mesh,
        batch_axes=batch_axes, head_axes=head_axes,
    )
