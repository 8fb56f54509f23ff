"""Flash attention as a Pallas TPU kernel, with a jnp reference fallback.

Forward: online-softmax over K/V blocks — the grid's innermost dimension
walks key blocks while VMEM scratch carries the running (max, sum, output)
accumulators, so attention scores never materialize in HBM (memory
O(block_q x block_k) instead of O(T^2)).  Backward: custom VJP with the
standard recompute scheme — one kernel accumulates dQ over key blocks, one
accumulates dK/dV over query blocks, both reusing the forward's saved
logsumexp so no O(T^2) residuals are stored.

Layout contract matches ``layers.causal_attention``: [B, T, H, D] in, same
out.  Kernels run over [B, H, T, D] internally (last two dims tile onto
the (8,128) VMEM lanes; D and the block sizes should be multiples of 128
for full MXU tiles — head_dim 64 works, at half-lane occupancy).

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
from typing import Optional

import jax
import jax.numpy as jnp

from cloud_tpu.ops import dispatch as dispatch_lib
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30  # finite: fully-masked rows softmax to zeros, not NaN

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512

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
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, scale, causal, block_q, block_k, use_mask):
    if use_mask:
        (q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
        mask_ref = None
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Blocks strictly above the causal diagonal contribute nothing: skip
    # the matmuls entirely (the grid still visits them; compute does not).
    run = (
        (ki * block_k <= qi * block_q + block_q - 1) if causal else True
    )

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]  # [block_q, D]
        k = k_ref[0, 0]  # [block_k, D]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k]

        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            )
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if mask_ref is not None:
            # Key-side padding mask [1, block_k] (nonzero = valid token),
            # broadcast over query rows — matches the reference path's
            # mask[:, None, None, :] semantics.
            s = jnp.where(mask_ref[0] != 0, s, NEG_INF)

        m_prev = m_scr[:, :1]  # [block_q, 1] (value replicated over lanes)
        l_prev = l_scr[:, :1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)  # [block_q, block_k] f32
        correction = jnp.exp(m_prev - m_new)  # [block_q, 1]
        l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, D]
        acc_scr[...] = acc_scr[...] * correction + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
        # lse carried as [block_q, 1] (trailing singleton keeps the block
        # tile legal: Mosaic requires the last dim equal to the array's).
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(safe_l)


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


def _fwd_pallas(q, k, v, mask, *, causal, block_q, block_k, interpret):
    """q,k,v: [B, H, T, D]; mask: [B, T] i32 or None ->
    (out [B, H, T, D], lse [B, H, T, 1])."""
    global KERNEL_TRACE_COUNT
    KERNEL_TRACE_COUNT += 1
    b, h, t, d = q.shape
    _check_divisible(t, block_q, block_k)
    nq, nk = t // block_q, t // block_k
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, use_mask=mask is not None,
    )
    qspec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0))
    kspec = pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, qi, ki: (b_, h_, ki, 0))
    in_specs = [qspec, kspec, kspec]
    operands = [q, k, v]
    if mask is not None:
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b_, h_, qi, ki: (b_, 0, ki))
        )
        operands.append(_mask_rows(mask))
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=in_specs,
        out_specs=[
            qspec,
            pl.BlockSpec(
                (1, 1, block_q, 1), lambda b_, h_, qi, ki: (b_, h_, qi, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype,
                                 vma=_carry_vma(q, k, v, mask)),
            jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32,
                                 vma=_carry_vma(q, k, v, mask)),
        ],
        scratch_shapes=[
            _vmem((block_q, 128), jnp.float32),
            _vmem((block_q, 128), jnp.float32),
            _vmem((block_q, d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_fwd",
    )(*operands)
    return out, lse


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


def _bwd_pallas(q, k, v, mask, do, out, lse, *, causal, block_q, block_k,
                interpret, g_lse=None):
    """``g_lse`` is the [B, H, T, 1] cotangent of the forward's lse output
    (None for the out-only entry point); it adds ``p * g_lse`` to ds in
    both kernels."""
    global KERNEL_TRACE_COUNT
    KERNEL_TRACE_COUNT += 1
    b, h, t, d = q.shape
    _check_divisible(t, block_q, block_k)
    nq, nk = t // block_q, t // block_k
    scale = 1.0 / math.sqrt(d)
    use_mask = mask is not None
    use_glse = g_lse is not None
    # delta_i = rowsum(dO_i * O_i): elementwise, XLA fuses it; no kernel.
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=True,
    )  # [B, H, T, 1], matching lse's layout

    qspec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    kspec_i = pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_, j, 0))
    rowspec = pl.BlockSpec(
        (1, 1, block_q, 1), lambda b_, h_, i, j: (b_, h_, i, 0)
    )

    dq_in_specs = [qspec, kspec_i, kspec_i, qspec, rowspec, rowspec]
    dq_operands = [q, k, v, do, lse, delta]
    if use_glse:
        dq_in_specs.append(rowspec)
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
        out_specs=[qspec],
        out_shape=[jax.ShapeDtypeStruct(
            q.shape, q.dtype, vma=_carry_vma(*dq_operands))],
        scratch_shapes=[_vmem((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*dq_operands)[0]

    # dK/dV: grid walks key blocks in the parallel dims, query blocks in the
    # arbitrary (accumulating) dim.
    kspec_o = pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    qspec_j = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, j, 0))
    rowspec_j = pl.BlockSpec(
        (1, 1, block_q, 1), lambda b_, h_, i, j: (b_, h_, j, 0)
    )
    dkv_in_specs = [qspec_j, kspec_o, kspec_o, qspec_j, rowspec_j, rowspec_j]
    dkv_operands = [q, k, v, do, lse, delta]
    if use_glse:
        dkv_in_specs.append(rowspec_j)
        dkv_operands.append(g_lse)
    if use_mask:
        dkv_in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b_, h_, i, j: (b_, 0, i))
        )
        dkv_operands.append(_mask_rows(mask))
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, use_mask=use_mask,
            use_glse=use_glse,
        ),
        grid=(b, h, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[kspec_o, kspec_o],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype,
                                 vma=_carry_vma(*dkv_operands)),
            jax.ShapeDtypeStruct(v.shape, v.dtype,
                                 vma=_carry_vma(*dkv_operands)),
        ],
        scratch_shapes=[
            _vmem((block_k, d), jnp.float32),
            _vmem((block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*dkv_operands)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing + public dispatch
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, mask, causal, block_q, block_k, interpret):
    out, _ = _fwd_pallas(
        q, k, v, mask, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out


def _flash_fwd(q, k, v, mask, causal, block_q, block_k, interpret):
    out, lse = _fwd_pallas(
        q, k, v, mask, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out, (q, k, v, mask, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, residuals, g):
    q, k, v, mask, out, lse = residuals
    dq, dk, dv = _bwd_pallas(
        q, k, v, mask, g, out, lse, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret,
    )
    # The i32 mask's cotangent is float0 (integer operands carry no grad).
    dmask = (
        None if mask is None
        else np.zeros(mask.shape, jax.dtypes.float0)
    )
    return dq, dk, dv, dmask


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_lse(q, k, v, mask, causal, block_q, block_k, interpret):
    """Kernel forward returning (out, lse [B,H,T,1]) — the building block
    for ring attention's per-block folds.  The VJP handles BOTH outputs'
    cotangents: g_lse enters ds as ``p * g_lse`` (dlse/ds = softmax)."""
    return _fwd_pallas(
        q, k, v, mask, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


def _flash_lse_fwd(q, k, v, mask, causal, block_q, block_k, interpret):
    out, lse = _fwd_pallas(
        q, k, v, mask, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return (out, lse), (q, k, v, mask, out, lse)


def _flash_lse_bwd(causal, block_q, block_k, interpret, residuals, g):
    q, k, v, mask, out, lse = residuals
    g_out, g_lse = g
    dq, dk, dv = _bwd_pallas(
        q, k, v, mask, g_out, out, lse, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret, g_lse=g_lse,
    )
    dmask = (
        None if mask is None
        else np.zeros(mask.shape, jax.dtypes.float0)
    )
    return dq, dk, dv, dmask


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


def _flash_sharded(mesh, batch_axes, head_axes, q, k, v, mask_i32, *,
                   causal, block_q, block_k, interpret):
    """The kernels per (batch, heads) shard of ``mesh``; [B, T, H, D] in and
    out.  Sequence and depth are whole in every shard."""
    from jax.sharding import PartitionSpec as P

    batch = dispatch_lib.dividing_axes(mesh, batch_axes, q.shape[0])
    heads = dispatch_lib.dividing_axes(mesh, head_axes, q.shape[2])
    bthd = P(batch, None, heads, None)

    def local(q, k, v, *mask):
        out = _flash(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), mask[0] if mask else None,
            causal, block_q, block_k, interpret,
        )
        return out.transpose(0, 2, 1, 3)

    masks = () if mask_i32 is None else (mask_i32,)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(bthd,) * 3 + (P(batch, None),) * len(masks),
        out_specs=bthd, check_vma=False,
    )(q, k, v, *masks)


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


def _dispatch(q, k, v, *, causal, mask, block_q, block_k, use_pallas,
              interpret, with_lse, partitioned=False, mesh=None,
              batch_axes=None, head_axes=None):
    """Shared fit/dispatch/transpose wrapper for both public entry points
    (kept in ONE place so mask/fit rules can't drift between them)."""
    explicit_opt_out = use_pallas is False
    if not interpret and dispatch_lib.force_interpret():
        interpret = True
    fitted_q = _fit_block(q.shape[1], block_q)
    fitted_k = _fit_block(k.shape[1], block_k, lane_aligned=mask is not None)
    shape_ok = _mask_ok(q, k, mask) and _shape_eligible(q, k)
    if use_pallas and not shape_ok:
        # An explicit request is never answered with the jnp reference.
        # (A T no block fits is _check_divisible's error, further down.)
        raise ValueError(
            "flash_attention(use_pallas=True): the kernel cannot take "
            f"q{tuple(q.shape)} k{tuple(k.shape)} "
            f"mask{None if mask is None else tuple(mask.shape)} (needs "
            "[B,T,H,D] q and k of one shape, head_dim <= 256, mask [B,T])"
        )
    if use_pallas and not interpret and _in_partial_manual_region():
        raise NotImplementedError(
            "flash_attention(use_pallas=True) inside a partial-manual "
            "region (the pp pipeline body): no route compiles for the "
            "chip there yet (ROADMAP S8)"
        )
    if use_pallas is None:
        # Auto: by shape on TPU; under the interpreter wherever the
        # kernels apply — shapes they cannot express (rectangular q/k,
        # oversize head_dim, unalignable T) still take the reference.
        use_pallas = would_use_kernel(
            q, k, mask, block_q=block_q, block_k=block_k
        ) or (interpret and shape_ok
              and fitted_q is not None and fitted_k is not None)
    if not use_pallas:
        # Warn only when AUTO dispatch fell back — an explicit
        # use_pallas=False caller opted out deliberately.
        if partitioned and not explicit_opt_out:
            _warn_partitioned_fallback(q, k, mask)
        if with_lse:
            return _reference_with_lse(q, k, v, causal=causal, mask=mask)
        return _reference(q, k, v, causal=causal, mask=mask)
    # Requested blocks are upper bounds: run with the largest aligned
    # divisor of T at or below them.  No aligned divisor (forced kernel
    # path only) falls through to the clamp and _check_divisible's error.
    block_q = fitted_q if fitted_q is not None else min(block_q, q.shape[1])
    block_k = fitted_k if fitted_k is not None else min(block_k, k.shape[1])
    mask_i32 = None if mask is None else mask.astype(jnp.int32)
    kernel_mesh = dispatch_lib.kernel_mesh(mesh) if partitioned else None
    if kernel_mesh is not None:
        return _flash_sharded(
            kernel_mesh, batch_axes, head_axes, q, k, v, mask_i32,
            causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )
    # [B, T, H, D] -> [B, H, T, D] for (T, D)-tiled kernels.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if with_lse:
        out, lse = _flash_lse(
            qt, kt, vt, mask_i32, causal, block_q, block_k, interpret
        )
        return out.transpose(0, 2, 1, 3), lse[..., 0]
    out = _flash(qt, kt, vt, mask_i32, causal, block_q, block_k, interpret)
    return out.transpose(0, 2, 1, 3)


def flash_attention_with_lse(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    mask: Optional[jnp.ndarray] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
):
    """Like :func:`flash_attention` but also returns lse [B, H, T_q] —
    fully differentiable in both outputs (ring attention merges per-block
    partials through the lse, so its gradient must flow).
    """
    return _dispatch(
        q, k, v, causal=causal, mask=mask, block_q=block_q, block_k=block_k,
        use_pallas=use_pallas, interpret=interpret, with_lse=True,
    )


def _fit_block(t: int, block: int, *, lane_aligned: bool = False
               ) -> Optional[int]:
    """Largest multiple-of-8 block <= ``block`` that divides ``t``.

    T=768 with the default block_k=512 fits at 384 (not a clamp — 512
    doesn't divide 768); T=100 has no 8-aligned divisor and returns None
    (the (8,128) sublane tile would break).  ``lane_aligned`` is for the
    key block under a mask: it is the LAST dim of the mask's block, so
    it must be a multiple of 128 unless it is the whole of T."""
    top = min(block, t)
    if lane_aligned and top == t:
        return t if t % 8 == 0 else None
    step = 128 if lane_aligned else 8
    for candidate in range(top - top % step, step - 1, -step):
        if t % candidate == 0:
            return candidate
    return None


def _mask_ok(q, k, mask) -> bool:
    return mask is None or (
        mask.ndim == 2
        and mask.shape[0] == q.shape[0]
        and mask.shape[1] == k.shape[1]
    )


def _shape_eligible(q, k) -> bool:
    return (
        q.ndim == 4
        and q.shape == k.shape
        and q.shape[-1] <= 256  # head_dim beyond this overflows VMEM blocks
    )


def _kernel_eligible(q, k, block_q, block_k) -> bool:
    """Called with blocks already fitted to T: both must have resolved to
    8-aligned divisors of their sequence length."""
    return (
        _shape_eligible(q, k)
        and block_q is not None
        and block_k is not None
    )


def would_use_kernel(
    q,
    k,
    mask: Optional[jnp.ndarray] = None,
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
) -> bool:
    """The full ``use_pallas=None`` auto-dispatch predicate, exposed so
    callers (tests, capacity planners) never duplicate it and drift."""
    fitted_q = _fit_block(q.shape[1], block_q)
    fitted_k = _fit_block(k.shape[1], block_k, lane_aligned=mask is not None)
    return (
        jax.default_backend() == "tpu"
        and _mask_ok(q, k, mask)
        and _kernel_worthwhile(q, k)
        and _kernel_eligible(q, k, fitted_q, fitted_k)
        and not _in_partial_manual_region()
    )


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    mask: Optional[jnp.ndarray] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    partitioned: bool = False,
    mesh=None,
    batch_axes=None,
    head_axes=None,
) -> jnp.ndarray:
    """Attention over [B, T, H, D] tensors, differentiable.

    ``use_pallas=None`` auto-dispatches: kernels on TPU when shapes tile,
    reference jnp otherwise.  ``mask`` is a [B, T_k] valid-token padding
    mask (bool/int; nonzero = attend) applied key-side inside the kernels —
    fully-masked query rows produce uniform garbage (finite NEG_INF
    semantics), which the caller's loss mask must drop, matching the
    reference path.  ``interpret=True`` runs the kernels in the Pallas
    interpreter (CPU tests of kernel logic).

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
        q, k, v, causal=causal, mask=mask, block_q=block_q, block_k=block_k,
        use_pallas=use_pallas, interpret=interpret, with_lse=False,
        partitioned=partitioned, mesh=mesh, batch_axes=batch_axes,
        head_axes=head_axes,
    )
