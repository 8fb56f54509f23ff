"""Fused GroupNorm Pallas kernels (NHWC, per-sample grid).

Why a kernel: at CIFAR scale the ResNet50 step is VPU/HBM-bound and
GroupNorm is its largest non-conv cost (a round-3 reading on a v5e).
XLA's lowering reads the activation twice (reduce, then normalize); the
kernel computes group statistics and writes the normalized+affine output
in ONE pass over VMEM-resident data — one HBM read + one write per
sample.  The backward pass is a second kernel producing dx plus
per-sample dscale/dbias partials (summed outside — a [B, C] reduction).

Group reductions avoid the TPU-hostile [H, W, G, C/g] reshape (C/g lands
in the lane dimension at width 2-64): the activation stays [HW, C] with
channels in lanes, per-channel sums reduce over sublanes, and a [C, G]
one-hot matmul folds channels into groups (MXU-friendly).

Numerics match models/resnet.py's shifted-moments implementation: sums
are computed around a per-channel pivot (the first spatial row) so the
E[x^2]-E[x]^2 combination stays O(var) even when |mean| >> std, and the
group variance is assembled from per-channel shifted sums exactly
(grouped shifted-data algebra, not an approximation).

Reference parity note: the reference framework has no kernels at all —
this is TPU-native capability (SURVEY.md SS5 "perf baselines are
established by this rebuild").
"""

from __future__ import annotations

import functools

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from cloud_tpu.ops import dispatch as dispatch_lib

#: Diagnostic counter (see flash_attention.KERNEL_TRACE_COUNT): bumped per
#: kernel trace so tests can assert the fused path — not the jnp
#: reference — actually ran.
KERNEL_TRACE_COUNT = 0


def _reference(x, scale, bias, num_groups, eps=1e-5, relu=False,
               residual=None):
    """Ground truth (and non-TPU fallback) — mirrors models/resnet.py."""
    b, h, w, c = x.shape
    g = min(num_groups, c)
    x32 = x.astype(jnp.float32).reshape(b, h, w, g, c // g)
    pivot = jax.lax.stop_gradient(x32[:, :1, :1, :, :1])
    xc = x32 - pivot
    m1c = jnp.mean(xc, axis=(1, 2, 4), keepdims=True)
    m2c = jnp.mean(xc * xc, axis=(1, 2, 4), keepdims=True)
    var = jnp.maximum(m2c - m1c * m1c, 0.0)
    y = (xc - m1c) * jax.lax.rsqrt(var + eps)
    y = y.reshape(b, h, w, c) * scale + bias
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype)


def _onehot(c: int, g: int) -> jnp.ndarray:
    """[C, G] channel->group fold matrix, built from iota (traced ops,
    not a baked array constant)."""
    cg = c // g
    ch_group = jax.lax.broadcasted_iota(jnp.int32, (c, g), 0) // cg
    group = jax.lax.broadcasted_iota(jnp.int32, (c, g), 1)
    return (ch_group == group).astype(jnp.float32)


def _fwd_math(x2, scale_row, bias_row, oh, oht, hw, cg, eps):
    """Shared forward math: [HW, C] -> (pre-activation y2, mean_g, rstd_g)."""
    n = float(hw * cg)
    pivot = x2[0:1, :]  # [1, C] per-channel shift
    xc = x2 - pivot
    s1 = jnp.sum(xc, axis=0, keepdims=True)        # [1, C]
    s2 = jnp.sum(xc * xc, axis=0, keepdims=True)   # [1, C]

    sum_g = (s1 + hw * pivot) @ oh                 # [1, G] true sums
    mean_g = sum_g / n
    mean_c = mean_g @ oht                           # [1, C]
    d = mean_c - pivot                              # [1, C]
    # sum_(hw,c in g) (x - m)^2 = s2 - 2 d s1 + hw d^2, folded per group.
    var_g = (s2 - 2.0 * d * s1 + hw * d * d) @ oh / n
    rstd_g = jax.lax.rsqrt(jnp.maximum(var_g, 0.0) + eps)
    rstd_c = rstd_g @ oht                           # [1, C]
    y2 = (x2 - mean_c) * rstd_c * scale_row + bias_row
    return y2, mean_g, rstd_g


def _fwd_kernel(x_ref, scale_ref, bias_ref, oh_ref, oht_ref, y_ref,
                mean_ref, rstd_ref, *, eps, hw, cg, relu):
    x = x_ref[0].astype(jnp.float32)
    h, w, c = x.shape
    y, mean_g, rstd_g = _fwd_math(
        x.reshape(hw, c), scale_ref[...], bias_ref[...],
        oh_ref[...], oht_ref[...], hw, cg, eps,
    )
    if relu:
        # Fused epilogue: the separate XLA relu would cost one more HBM
        # read+write of the whole activation on a bandwidth-bound model.
        y = jnp.maximum(y, 0.0)
    y_ref[0] = y.reshape(h, w, c).astype(y_ref.dtype)
    mean_ref[0] = mean_g
    rstd_ref[0] = rstd_g


def _fwd_kernel_res(x_ref, scale_ref, bias_ref, res_ref, oh_ref, oht_ref,
                    y_ref, mean_ref, rstd_ref, *, eps, hw, cg, relu):
    """Forward with a fused residual add: y = [relu](gn(x) + residual) —
    the bottleneck tail's add+relu never round-trips HBM separately."""
    x = x_ref[0].astype(jnp.float32)
    h, w, c = x.shape
    y, mean_g, rstd_g = _fwd_math(
        x.reshape(hw, c), scale_ref[...], bias_ref[...],
        oh_ref[...], oht_ref[...], hw, cg, eps,
    )
    y = y + res_ref[0].astype(jnp.float32).reshape(hw, c)
    if relu:
        y = jnp.maximum(y, 0.0)
    y_ref[0] = y.reshape(h, w, c).astype(y_ref.dtype)
    mean_ref[0] = mean_g
    rstd_ref[0] = rstd_g


def _bwd_core(x2, dy2, mean_row, rstd_row, scale_row, oh, oht, n):
    """GN backward for an already-gated cotangent: (dx2, ds, db)."""
    mean_c = mean_row @ oht                         # [1, C]
    rstd_c = rstd_row @ oht                         # [1, C]
    xhat = (x2 - mean_c) * rstd_c
    dxh = dy2 * scale_row

    a_c = (jnp.sum(dxh, axis=0, keepdims=True) @ oh) @ oht         # [1, C]
    b_c = (jnp.sum(dxh * xhat, axis=0, keepdims=True) @ oh) @ oht   # [1, C]
    dx = rstd_c * (dxh - (a_c + xhat * b_c) / n)
    ds = jnp.sum(dy2 * xhat, axis=0, keepdims=True)  # [1, C] per-sample partial
    db = jnp.sum(dy2, axis=0, keepdims=True)         # [1, C]
    return dx, ds, db, xhat


def _bwd_kernel(x_ref, dy_ref, mean_ref, rstd_ref, scale_ref, bias_ref,
                oh_ref, oht_ref, dx_ref, ds_ref, db_ref, *, hw, cg, relu):
    x = x_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    h, w, c = x.shape
    x2 = x.reshape(hw, c)
    dy2 = dy.reshape(hw, c)
    oh = oh_ref[...]
    oht = oht_ref[...]
    n = float(hw * cg)

    if relu:
        # Recompute the pre-activation sign from the saved stats: the
        # relu gate zeroes the cotangent where the fused forward clamped.
        mean_c = mean_ref[0] @ oht
        rstd_c = rstd_ref[0] @ oht
        pre = (x2 - mean_c) * rstd_c * scale_ref[...] + bias_ref[...]
        dy2 = jnp.where(pre > 0.0, dy2, 0.0)
    dx, ds, db, _ = _bwd_core(
        x2, dy2, mean_ref[0], rstd_ref[0], scale_ref[...], oh, oht, n
    )
    dx_ref[0] = dx.reshape(h, w, c).astype(dx_ref.dtype)
    ds_ref[0] = ds
    db_ref[0] = db


def _bwd_kernel_res(x_ref, dy_ref, mean_ref, rstd_ref, scale_ref, bias_ref,
                    res_ref, oh_ref, oht_ref, dx_ref, ds_ref, db_ref,
                    dres_ref, *, hw, cg, relu):
    """Backward of y = [relu](gn(x) + residual): the gate (recomputed
    from stats + the residual) applies to BOTH branches; the residual's
    cotangent is exactly the gated dy."""
    x = x_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    h, w, c = x.shape
    x2 = x.reshape(hw, c)
    dy2 = dy.reshape(hw, c)
    oh = oh_ref[...]
    oht = oht_ref[...]
    n = float(hw * cg)

    if relu:
        mean_c = mean_ref[0] @ oht
        rstd_c = rstd_ref[0] @ oht
        pre = (
            (x2 - mean_c) * rstd_c * scale_ref[...] + bias_ref[...]
            + res_ref[0].astype(jnp.float32).reshape(hw, c)
        )
        dy2 = jnp.where(pre > 0.0, dy2, 0.0)
    dres_ref[0] = dy2.reshape(h, w, c).astype(dres_ref.dtype)
    dx, ds, db, _ = _bwd_core(
        x2, dy2, mean_ref[0], rstd_ref[0], scale_ref[...], oh, oht, n
    )
    dx_ref[0] = dx.reshape(h, w, c).astype(dx_ref.dtype)
    ds_ref[0] = ds
    db_ref[0] = db


def _block_specs(b, h, w, c, g):
    x_spec = pl.BlockSpec((1, h, w, c), lambda i: (i, 0, 0, 0))
    vec_spec = pl.BlockSpec((1, c), lambda i: (0, 0))
    oh_spec = pl.BlockSpec((c, g), lambda i: (0, 0))
    oht_spec = pl.BlockSpec((g, c), lambda i: (0, 0))
    # Per-sample rows (stats [b, 1, g], dscale/dbias partials [b, 1, c])
    # carry a unit middle axis: Mosaic wants a block's last two dims
    # divisible by (8, 128) or equal to the array's, and a (1, g) block of
    # [b, g] is neither, while (1, 1, g) of [b, 1, g] is the array's own.
    stat_spec = pl.BlockSpec((1, 1, g), lambda i: (i, 0, 0))
    partial_spec = pl.BlockSpec((1, 1, c), lambda i: (i, 0, 0))
    return x_spec, vec_spec, oh_spec, oht_spec, stat_spec, partial_spec


def _fwd_pallas(x, scale, bias, num_groups, eps, interpret, relu=False):
    global KERNEL_TRACE_COUNT
    KERNEL_TRACE_COUNT += 1
    b, h, w, c = x.shape
    g = min(num_groups, c)
    hw, cg = h * w, c // g
    oh = _onehot(c, g)
    x_spec, vec_spec, oh_spec, oht_spec, stat_spec, _ = _block_specs(
        b, h, w, c, g)
    y, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, hw=hw, cg=cg, relu=relu),
        grid=(b,),
        in_specs=[x_spec, vec_spec, vec_spec, oh_spec, oht_spec],
        out_specs=[x_spec, stat_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((b, 1, g), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, g), jnp.float32),
        ],
        interpret=interpret,
        name="group_norm_fwd",
    )(x, scale.reshape(1, c), bias.reshape(1, c), oh, oh.T)
    return y, mean, rstd


def _bwd_pallas(x, dy, mean, rstd, scale, bias, num_groups, interpret,
                relu=False):
    global KERNEL_TRACE_COUNT
    KERNEL_TRACE_COUNT += 1
    b, h, w, c = x.shape
    g = min(num_groups, c)
    hw, cg = h * w, c // g
    oh = _onehot(c, g)
    (x_spec, vec_spec, oh_spec, oht_spec, stat_spec,
     partial_spec) = _block_specs(b, h, w, c, g)
    dx, ds, db = pl.pallas_call(
        functools.partial(_bwd_kernel, hw=hw, cg=cg, relu=relu),
        grid=(b,),
        in_specs=[x_spec, x_spec, stat_spec, stat_spec, vec_spec, vec_spec,
                  oh_spec, oht_spec],
        out_specs=[x_spec, partial_spec, partial_spec],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
        ],
        interpret=interpret,
        name="group_norm_bwd",
    )(x, dy, mean, rstd, scale.reshape(1, c), bias.reshape(1, c), oh, oh.T)
    return dx, ds, db


def _fwd_pallas_res(x, scale, bias, residual, num_groups, eps, interpret,
                    relu):
    global KERNEL_TRACE_COUNT
    KERNEL_TRACE_COUNT += 1
    b, h, w, c = x.shape
    g = min(num_groups, c)
    hw, cg = h * w, c // g
    oh = _onehot(c, g)
    x_spec, vec_spec, oh_spec, oht_spec, stat_spec, _ = _block_specs(
        b, h, w, c, g)
    y, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel_res, eps=eps, hw=hw, cg=cg, relu=relu),
        grid=(b,),
        in_specs=[x_spec, vec_spec, vec_spec, x_spec, oh_spec, oht_spec],
        out_specs=[x_spec, stat_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((b, 1, g), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, g), jnp.float32),
        ],
        interpret=interpret,
        name="group_norm_res_fwd",
    )(x, scale.reshape(1, c), bias.reshape(1, c), residual, oh, oh.T)
    return y, mean, rstd


def _bwd_pallas_res(x, dy, mean, rstd, scale, bias, residual, num_groups,
                    interpret, relu):
    global KERNEL_TRACE_COUNT
    KERNEL_TRACE_COUNT += 1
    b, h, w, c = x.shape
    g = min(num_groups, c)
    hw, cg = h * w, c // g
    oh = _onehot(c, g)
    (x_spec, vec_spec, oh_spec, oht_spec, stat_spec,
     partial_spec) = _block_specs(b, h, w, c, g)
    dx, ds, db, dres = pl.pallas_call(
        functools.partial(_bwd_kernel_res, hw=hw, cg=cg, relu=relu),
        grid=(b,),
        in_specs=[x_spec, x_spec, stat_spec, stat_spec, vec_spec, vec_spec,
                  x_spec, oh_spec, oht_spec],
        out_specs=[x_spec, partial_spec, partial_spec, x_spec],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
            jax.ShapeDtypeStruct(residual.shape, residual.dtype),
        ],
        interpret=interpret,
        name="group_norm_res_bwd",
    )(x, dy, mean, rstd, scale.reshape(1, c), bias.reshape(1, c), residual,
      oh, oh.T)
    return dx, ds, db, dres


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gn(x, scale, bias, num_groups, eps, interpret, relu=False):
    y, _, _ = _fwd_pallas(x, scale, bias, num_groups, eps, interpret,
                          relu=relu)
    return y


def _gn_fwd(x, scale, bias, num_groups, eps, interpret, relu=False):
    y, mean, rstd = _fwd_pallas(x, scale, bias, num_groups, eps, interpret,
                                relu=relu)
    return y, (x, mean, rstd, scale, bias)


def _gn_bwd(num_groups, eps, interpret, relu, residuals, dy):
    x, mean, rstd, scale, bias = residuals
    dx, ds, db = _bwd_pallas(
        x, dy, mean, rstd, scale, bias, num_groups, interpret, relu=relu
    )
    return dx, jnp.sum(ds, axis=(0, 1)), jnp.sum(db, axis=(0, 1))


_gn.defvjp(_gn_fwd, _gn_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _gn_res(x, scale, bias, residual, num_groups, eps, interpret, relu):
    y, _, _ = _fwd_pallas_res(x, scale, bias, residual, num_groups, eps,
                              interpret, relu)
    return y


def _gn_res_fwd(x, scale, bias, residual, num_groups, eps, interpret, relu):
    y, mean, rstd = _fwd_pallas_res(x, scale, bias, residual, num_groups,
                                    eps, interpret, relu)
    # Without relu the backward never reads the residual (dres == dy
    # exactly); keep only a zero-size dtype token so the full tensor
    # neither lives in residuals nor streams through the bwd kernel.
    saved_res = residual if relu else residual[:0]
    return y, (x, mean, rstd, scale, bias, saved_res)


def _gn_res_bwd(num_groups, eps, interpret, relu, residuals, dy):
    x, mean, rstd, scale, bias, saved_res = residuals
    if relu:
        dx, ds, db, dres = _bwd_pallas_res(
            x, dy, mean, rstd, scale, bias, saved_res, num_groups,
            interpret, relu,
        )
    else:
        dx, ds, db = _bwd_pallas(
            x, dy, mean, rstd, scale, bias, num_groups, interpret,
            relu=False,
        )
        dres = dy.astype(saved_res.dtype)
    return dx, jnp.sum(ds, axis=(0, 1)), jnp.sum(db, axis=(0, 1)), dres


_gn_res.defvjp(_gn_res_fwd, _gn_res_bwd)


# ---------------------------------------------------------------------------
# Mesh route: under the framework's global mesh an unwrapped pallas_call
# cannot be partitioned, so the kernel runs per batch shard inside a
# full-manual shard_map (ops/dispatch.py says why not custom_partitioning).
# ---------------------------------------------------------------------------


def _gn_batch_sharded(mesh, batch_axes, x, scale, bias, residual,
                      num_groups, eps, interpret, relu):
    from jax.sharding import PartitionSpec as P

    def local(x, scale, bias, *res):
        if res:
            return _gn_res(x, scale, bias, res[0], num_groups, eps,
                           interpret, relu)
        return _gn(x, scale, bias, num_groups, eps, interpret, relu)

    res = () if residual is None else (residual,)
    batch = P(dispatch_lib.dividing_axes(mesh, batch_axes, x.shape[0]))
    # Axes the specs do not name see the operands replicated.  check_vma
    # off: pallas_call results carry no varying-axes annotation.
    return jax.shard_map(
        local, mesh=mesh, in_specs=(batch, P(), P()) + (batch,) * len(res),
        out_specs=batch, check_vma=False,
    )(x, scale, bias, *res)


def kernel_eligible(x, num_groups, has_residual: bool = False) -> bool:
    """Shapes the kernel handles: 4-D NHWC, groups divide channels, the
    [HW, C] view sublane-aligned, and a per-sample block that fits VMEM
    (f32 activation + working copies, conservatively 4 MiB; halved when
    a fused residual doubles the resident blocks)."""
    if x.ndim != 4:
        return False
    b, h, w, c = x.shape
    g = min(num_groups, c)
    if c % g:
        return False
    if (h * w) % 8:
        return False
    budget = (2 if has_residual else 4) * 1024 * 1024
    return h * w * c * 4 <= budget


def group_norm(
    x: jnp.ndarray,
    scale: jnp.ndarray,
    bias: jnp.ndarray,
    *,
    num_groups: int = 32,
    eps: float = 1e-5,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    partitioned: Optional[bool] = None,
    activation: Optional[str] = None,
    residual: Optional[jnp.ndarray] = None,
    mesh=None,
    batch_axes=None,
) -> jnp.ndarray:
    """GroupNorm over NHWC with affine params [C]; differentiable.

    ``use_pallas=None`` auto-dispatches to the fused kernel on TPU when
    :func:`kernel_eligible`; elsewhere (or on odd shapes) the jnp
    reference runs — identical algorithm, so dispatch never changes
    numerics beyond kernel-vs-fusion float ordering.

    ``partitioned`` — under ``mesh`` (default: the framework's global
    mesh) of more than one device the kernel runs per batch shard in a
    shard_map (an unwrapped pallas_call cannot be partitioned there):
    ``batch_axes`` names the mesh axes the CALLER's batch is split over
    (its rules' ``"batch"`` assignment; None: every device normalizes
    the whole batch).  ``False`` forces the direct call.

    ``activation="relu"`` fuses the ReLU epilogue into the kernel (the
    separate XLA relu costs one extra HBM read+write of the whole
    activation per call — material on the bandwidth-bound ResNet path);
    the backward gates the cotangent by the recomputed pre-activation
    sign, so gradients equal relu(group_norm(x)) exactly.

    ``residual`` (same shape as x) fuses a residual add BEFORE the
    activation — ``[relu](group_norm(x) + residual)`` — the ResNet
    bottleneck tail, whose separate add+relu otherwise re-reads both
    tensors from HBM.  Fully differentiable in the residual too.
    """
    if activation not in (None, "relu"):
        raise ValueError(
            f"activation must be None or 'relu', got {activation!r}"
        )
    relu = activation == "relu"
    if residual is not None and residual.shape != x.shape:
        raise ValueError(
            f"residual shape {residual.shape} != x shape {x.shape}"
        )
    if not interpret and dispatch_lib.force_interpret():
        interpret = True
    has_res = residual is not None
    eligible = kernel_eligible(x, num_groups)
    if use_pallas and not eligible:
        raise ValueError(
            f"group_norm(use_pallas=True): the kernel cannot take shape "
            f"{tuple(x.shape)} with num_groups={num_groups} "
            "(see kernel_eligible)"
        )
    if use_pallas is None:
        use_pallas = eligible and (
            interpret or jax.default_backend() == "tpu"
        )
    if not use_pallas:
        return _reference(x, scale, bias, num_groups, eps, relu=relu,
                          residual=residual)
    if has_res and not kernel_eligible(x, num_groups, True):
        # The block + residual pair exceeds the VMEM budget: drop ONLY
        # the fusion (kernel GN + XLA add/relu — the pre-fusion
        # schedule), never the whole kernel.
        y = group_norm(
            x, scale, bias, num_groups=num_groups, eps=eps,
            use_pallas=True, interpret=interpret, partitioned=partitioned,
            mesh=mesh, batch_axes=batch_axes,
        )
        y = y.astype(jnp.float32) + residual.astype(jnp.float32)
        if relu:
            y = jnp.maximum(y, 0.0)
        return y.astype(x.dtype)
    scale32 = scale.astype(jnp.float32)
    bias32 = bias.astype(jnp.float32)
    mesh = None if partitioned is False else dispatch_lib.kernel_mesh(mesh)
    if mesh is not None:
        return _gn_batch_sharded(mesh, batch_axes, x, scale32, bias32,
                                 residual, num_groups, eps, interpret, relu)
    if residual is not None:
        return _gn_res(x, scale32, bias32, residual, num_groups, eps,
                       interpret, relu)
    return _gn(x, scale32, bias32, num_groups, eps, interpret, relu)
