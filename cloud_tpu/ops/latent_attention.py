"""The decode read over a latent (MLA) slot cache as a Pallas TPU kernel,
with a jnp reference.

A cache row is one vector a token, ``[c | k_pe | 0]`` (``models/mla.py``),
and it is key and value at once: every head's query ``[q_lat | q_pe | 0]``
scores against the whole row, and the values are the row's first
``value_dim`` numbers.  So the kernel fetches the rows ONCE for all heads:
one MXU product of the ``H`` queries against them gives the scores, the
online softmax runs in float32, and the weights go back through the MXU
against the same rows (as ``WEIGHT_PARTS`` bfloat16 parts stacked as rows
of one product).  At 64 heads over a 640-wide row that is about a hundred
operations a byte: MXU work and the DMA side by side, unlike the per-head
K/V read of ``ops.paged_attention``.

The grid is one step a slot, and the stacked leaf stays in HBM whole (the
layer a scalar-prefetch operand, nothing sliced out).  A slot's pages up
to its length, and no others, are walked by a loop inside the kernel, up
to ``STEP_ROWS`` rows a step, each step's rows fetched by the kernel's own
DMA into one of ``BUFFERS`` buffers: the walk runs over the grid's live
slots as one sequence, so the next slot's first rows are in flight before
the current slot's last are scored, and a slot of length 0 costs its grid
step and nothing else.

Dispatch follows the house rule: ``use_pallas=None`` takes the kernel on a
TPU (or under ``CLOUD_TPU_FLASH_FORCE_INTERPRET=1`` through the
interpreter) for rows that make whole pages, else :func:`_reference`; an
explicit ``use_pallas=True`` on a shape the kernel cannot take raises.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from cloud_tpu.ops import dispatch as dispatch_lib
from cloud_tpu.ops.paged_attention import NEG_INF, _split_bf16

#: Rows a page, the unit a slot's rows are fetched by: the largest of these
#: that divides the slot row.  Chosen on the chip against pages of 512 at
#: 64 slots x 4,608 rows (docs/KERNELS.md; scripts/decode_crossover.py k2):
#: the walk runs at the DMA's pace, so a finer page fetches less of a
#: slot's last page past its length for the same time a row.
PAGE_ROWS = (256, 128, 64, 32, 16, 8)

#: Rows one step of the walk scores at most (one DMA, one pass of the
#: body), and the most pages such a step holds (a body each): the larger
#: the step, the less its fixed cost a row (the chain of two dependent MXU
#: products and the softmax between them), up to what fast memory holds.
STEP_ROWS = 1536
STEP_PAGES = 6

#: Buffers the walk fetches into: the two steps after the one being scored
#: are in flight, so a short step (a slot's last) hides the next one's DMA.
BUFFERS = 3

#: bfloat16 parts that carry the float32 softmax weights through the MXU:
#: two hold 16 mantissa bits, and 2 x 64 heads fill the MXU's 128 rows.
WEIGHT_PARTS = 2

#: Bumped whenever the kernel is traced (tests prove which path ran).
KERNEL_TRACE_COUNT = 0


def _reference(q, rows, cur_len, *, value_dim, scale):
    """q [B, H, W] against one layer's rows [B, S, W]; key ``j`` of row
    ``b`` is valid iff ``j < cur_len[b]``; a row of length 0 gives zeros.
    float32 softmax, finite mask."""
    rows32 = rows.astype(jnp.float32)
    scores = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32),
                        rows32) * scale
    valid = jnp.arange(rows.shape[1])[None, :] < cur_len[:, None]
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    weights = jnp.where((cur_len > 0)[:, None, None], weights, 0.0)
    out = jnp.einsum("bhs,bsv->bhv", weights, rows32[..., :value_dim])
    return out.astype(q.dtype)


def _next_live(cur_len):
    """[B + 1] int32: entry ``i`` is the first slot at or after ``i``
    whose length is not 0, or ``B`` where there is none."""
    b = cur_len.shape[0]
    idx = jnp.where(cur_len > 0, jnp.arange(b, dtype=jnp.int32), b)
    return jnp.append(jax.lax.cummin(idx, reverse=True), b).astype(jnp.int32)


def _kernel(layer_ref, len_ref, live_ref, q_ref, rows_hbm, o_ref, buf, sems,
            m_scr, l_scr, acc_scr, done_ref, *, bt, per_step, buffers,
            value_dim, scale):
    """Grid step ``b`` is slot ``b``: its live pages, and no other, walked
    in order ``per_step`` pages at a time (one DMA, one body for each
    number of pages a step can hold) through the ``buffers`` buffers in
    ``buf``.  The walk runs over the grid's live slots as one sequence of
    steps, and the ``buffers - 1`` steps after the one being scored are
    always in flight, whichever slots they belong to: a slot's first pages
    arrive during the slot before.  ``done_ref`` counts the steps walked
    so far over the grid: step ``k`` of the walk lands in buffer
    ``k % buffers``."""
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    unit = bt * per_step
    layer = layer_ref[0]

    def pages_of(slot):
        return jnp.minimum((len_ref[slot] + bt - 1) // bt,
                           rows_hbm.shape[2] // bt)

    def steps_of(slot):
        """Steps of the walk in ``slot``; none in the end mark ``n_slots``."""
        held = pages_of(jnp.minimum(slot, n_slots - 1))
        return jnp.where(slot < n_slots,
                         (held + per_step - 1) // per_step, 0)

    def successor(slot, step):
        """The walk's step after ``(slot, step)``: the slot's next, else
        the first of the next live slot; the end mark stays the end."""
        more = step + 1 < steps_of(slot)
        following = live_ref[jnp.minimum(slot + 1, n_slots)]
        return (jnp.where(more, slot, following),
                jnp.where(more, step + 1, 0))

    def each_size(slot, step, fn):
        """``fn(pages)`` under the one static page count step ``step`` of
        ``slot`` holds."""
        held = pages_of(slot) - step * per_step
        for n in range(1, per_step + 1):
            fits = held >= n if n == per_step else held == n
            pl.when(fits)(functools.partial(fn, n))

    def copy(slot, step, at, n):
        return pltpu.make_async_copy(
            rows_hbm.at[layer, slot, pl.ds(step * unit, n * bt)],
            buf.at[at, pl.ds(0, n * bt)], sems.at[at])

    def start(slot, step, at):
        @pl.when(slot < n_slots)
        def _():
            each_size(slot, step, lambda n: copy(slot, step, at, n).start())

    @pl.when(b == 0)
    def _start():
        done_ref[0] = 0
        slot, step = live_ref[0], jnp.int32(0)
        for k in range(buffers - 1):
            start(slot, step, k)
            slot, step = successor(slot, step)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    cur = len_ref[b]
    steps = steps_of(b)
    walked = done_ref[0]
    q = q_ref[0]                                    # [H, W]

    def score(step, at, n):
        copy(b, step, at, n).wait()
        rows = n * bt
        page = buf[at, :rows]                       # [rows, W] as stored
        qp = q
        if page.dtype != qp.dtype:
            qp, page = qp.astype(jnp.float32), page.astype(jnp.float32)
        s = jax.lax.dot_general(
            qp, page, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, rows]
        col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        s = jnp.where(col < cur - step * unit, s, NEG_INF)
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pmat = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        values = page[:, :value_dim]
        heads = pmat.shape[0]
        if values.dtype == jnp.bfloat16:
            parts = jnp.concatenate(_split_bf16(pmat, WEIGHT_PARTS), axis=0)
            out = jax.lax.dot_general(
                parts, values, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            weighed = sum(out[i * heads:(i + 1) * heads]
                          for i in range(WEIGHT_PARTS))
        else:
            weighed = jax.lax.dot_general(
                pmat, values.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * correction + weighed
        l_scr[...] = jnp.broadcast_to(
            l_prev * correction + jnp.sum(pmat, axis=-1, keepdims=True),
            l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    def walk_step(step, carry):
        ahead = (b, step)
        for _ in range(buffers - 1):
            ahead = successor(*ahead)
        start(*ahead, (walked + step + buffers - 1) % buffers)
        each_size(b, step, functools.partial(score, step,
                                             (walked + step) % buffers))
        return carry

    jax.lax.fori_loop(0, steps, walk_step, 0)
    done_ref[0] = walked + steps
    l = l_scr[:, :1]
    o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                ).astype(o_ref.dtype)


def _step_pages(bt, s_total):
    """Pages a step of the walk holds over slot rows of ``s_total``."""
    return max(1, min(STEP_ROWS // bt, STEP_PAGES, s_total // bt))


def _pallas(q, rows, cur_len, layer, bt, *, value_dim, scale, interpret,
            per_step=None, buffers=BUFFERS):
    """``bt`` rows a page, ``per_step`` pages a step (by default as many as
    ``STEP_ROWS`` and ``STEP_PAGES`` allow, and the slot row holds),
    ``buffers`` buffers."""
    global KERNEL_TRACE_COUNT
    KERNEL_TRACE_COUNT += 1
    if per_step is None:
        per_step = _step_pages(bt, rows.shape[-2])
    return _call(q, rows, cur_len.astype(jnp.int32),
                 jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), bt=bt,
                 value_dim=value_dim, scale=scale, interpret=interpret,
                 per_step=per_step, buffers=buffers)


# Jitted (and inlined) for its tracing cache alone: a chunk program calls
# the kernel once a layer stack and step, and tracing it is what a call
# costs at set-up.
@functools.partial(jax.jit, inline=True,
                   static_argnames=("bt", "value_dim", "scale", "interpret",
                                    "per_step", "buffers"))
def _call(q, rows, cur_len, layer, *, bt, value_dim, scale, interpret,
          per_step, buffers):
    from jax.experimental.pallas import tpu as pltpu

    b, h, w = q.shape

    def q_map(b_, *_):
        return (b_, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, w), q_map),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, h, value_dim), q_map),
        scratch_shapes=[pltpu.VMEM((buffers, bt * per_step, w),
                                   rows.dtype),
                        pltpu.SemaphoreType.DMA((buffers,)),
                        pltpu.VMEM((h, 128), jnp.float32),
                        pltpu.VMEM((h, 128), jnp.float32),
                        pltpu.VMEM((h, value_dim), jnp.float32),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bt=bt, per_step=per_step,
                          buffers=buffers, value_dim=value_dim, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, value_dim), q.dtype),
        # One walk over the grid: a slot's first rows are fetched during
        # the slot before, so the steps run in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_decode",
    )(layer, cur_len, _next_live(cur_len), q, rows)


def kernel_page(q, rows, *, use_pallas: Optional[bool] = None
                ) -> Optional[int]:
    """The page the kernel reads ``rows`` [.., B, S, W] by, or None where
    the read takes the jnp reference over whole rows.  One spelling for
    the dispatch below and for whoever counts what a decode read fetches
    (the serving engine)."""
    s_total, w = rows.shape[-2:]
    bt = next((r for r in PAGE_ROWS if s_total % r == 0), None)
    eligible = (q.ndim == 3 and bt is not None and w % 128 == 0
                and q.shape[-1] == w and q.shape[0] == rows.shape[-3])
    if use_pallas and not eligible:
        raise ValueError(
            "latent_decode_attention(use_pallas=True): the kernel cannot "
            f"take q{tuple(q.shape)} over rows {tuple(rows.shape)} (needs "
            "q [B, H, W] and rows [.., B, S, W] of one batch and width, W "
            "whole lane rows, S a multiple of 8)")
    if use_pallas is None:
        use_pallas = eligible and (jax.default_backend() == "tpu"
                                   or dispatch_lib.force_interpret())
    return bt if use_pallas else None


def latent_decode_attention(q, rows, cur_len, *, value_dim: int,
                            scale: float, layer=None,
                            use_pallas: Optional[bool] = None,
                            interpret: bool = False):
    """One token's attention over latent rows: ``q`` [B, H, W] against
    ``rows`` — one layer's [B, S, W], or the STACKED leaf [L, B, S, W]
    with ``layer`` the (traced) layer to read, in place.  Key ``j`` of row
    ``b`` is valid iff ``j < cur_len[b]``; a row of length 0 is skipped
    whole (nothing fetched, zeros out).  Scores are ``scale * q . row``
    over the whole width, values the row's first ``value_dim`` numbers.
    Returns [B, H, value_dim]."""
    bt = kernel_page(q, rows, use_pallas=use_pallas)
    if bt is None:
        if rows.ndim == 4:
            rows = jax.lax.dynamic_index_in_dim(rows, layer, keepdims=False)
        return _reference(q, rows, cur_len, value_dim=value_dim,
                          scale=scale)
    if rows.ndim == 3:
        rows, layer = rows[None], 0
    interpret = (interpret or dispatch_lib.force_interpret()
                 or jax.default_backend() != "tpu")
    return _pallas(q, rows, cur_len, layer, bt, value_dim=value_dim,
                   scale=scale, interpret=interpret)
