"""The decode read over a latent (MLA) slot cache as a Pallas TPU kernel,
with a jnp reference.

A cache row is one vector a token, ``[c | k_pe | 0]`` (``models/mla.py``),
and it is key and value at once: every head's query ``[q_lat | q_pe | 0]``
scores against the whole row, and the values are the row's first
``value_dim`` numbers.  So the kernel fetches a page of rows ONCE for all
heads: one MXU product of the ``H`` queries against the page gives the
scores, the online softmax runs in float32, and the weights go back
through the MXU against the same page (as ``WEIGHT_PARTS`` bfloat16 parts
stacked as rows of one product).  At 64 heads over a 640-wide row that is
about a hundred operations a byte: MXU work and the page's DMA side by
side, unlike the per-head K/V read of ``ops.paged_attention``, whose fetch
plan (:func:`paged_attention._fetch_plan`: a dead page or a dead slot
costs a grid step and no DMA) and stacked-leaf addressing (the layer a
scalar-prefetch operand, nothing sliced out) it reuses.

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
from cloud_tpu.ops.paged_attention import NEG_INF, _fetch_plan, _split_bf16

#: Rows a page, the largest of these that divides the slot row.  Chosen on
#: the chip among 128 / 256 / 512 at 64 slots x 4,608 rows
#: (docs/KERNELS.md; scripts/decode_crossover.py k2): a grid step costs
#: about 0.35 us, as long as the DMA of a 128-row page, so a larger page
#: halves what the walk over (slot, page) costs and wastes half a page a
#: slot at most.
PAGE_ROWS = (512, 256, 128, 64, 32, 16, 8)

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


def _kernel(layer_ref, len_ref, rows_ref, first_ref, last_ref, q_ref,
            page_ref, o_ref, m_scr, l_scr, acc_scr, *, bt, value_dim,
            scale):
    b, p = pl.program_id(0), pl.program_id(1)
    cur = len_ref[b]

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(p * bt < cur)
    def _page():
        page = page_ref[...]                        # [bt, W] as stored
        q = q_ref[0]                                # [H, W]
        if page.dtype != q.dtype:
            q, page = q.astype(jnp.float32), page.astype(jnp.float32)
        s = jax.lax.dot_general(
            q, page, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, bt]
        col = jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        s = jnp.where(col < cur - p * bt, s, NEG_INF)
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

    @pl.when(p == pl.num_programs(1) - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def _pallas(q, rows, cur_len, layer, bt, *, value_dim, scale, interpret):
    global KERNEL_TRACE_COUNT
    KERNEL_TRACE_COUNT += 1
    from jax.experimental.pallas import tpu as pltpu

    b, h, w = q.shape
    s_total = rows.shape[2]
    n_pages = s_total // bt
    cur_len = cur_len.astype(jnp.int32)
    fetch_rows, first, last = _fetch_plan(cur_len, 1, bt, n_pages)

    def q_map(b_, p_, *_):
        return (b_, 0, 0)

    def page_map(b_, p_, lyr, ln, rows_, first_, last_):
        page = jnp.minimum(jnp.maximum(p_, first_[b_]), last_[b_])
        return (lyr[0], rows_[b_], page, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, n_pages),
        in_specs=[pl.BlockSpec((1, h, w), q_map),
                  pl.BlockSpec((None, None, bt, w), page_map)],
        out_specs=pl.BlockSpec((1, h, value_dim), q_map),
        scratch_shapes=[pltpu.VMEM((h, 128), jnp.float32),
                        pltpu.VMEM((h, 128), jnp.float32),
                        pltpu.VMEM((h, value_dim), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bt=bt, value_dim=value_dim, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="latent_decode",
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), cur_len,
      fetch_rows, first, last, q, rows)


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
