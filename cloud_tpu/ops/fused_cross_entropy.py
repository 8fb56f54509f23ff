"""Fused linear + softmax cross-entropy: the LM-head loss without the
[N, V] materialization.

Why: CloudLM's stock loss path computes ``logits = x @ W`` ([B, T, V]
f32) and then ``log_softmax`` — under ``value_and_grad`` XLA keeps both
as residuals, ~2 * B*T*V*4 bytes.  At B8 x T2048 x V32000 that is
~4 GiB of HBM for ONE layer of the program, and the softmax+gather
epilogue is pure HBM traffic (a round-3 BERT ablation on a v5e read the
vocab term at 1.4 ms/step at only V=30k classification scale).

This op computes per-token ``nll = logsumexp_V(x @ W) - (x @ W)[target]``
by scanning the vocab in chunks with an online (running max / scaled
sum) logsumexp — the same numerics trick as flash attention's softmax —
and a ``custom_vjp`` whose backward RE-computes each chunk's logits
(one extra [N, C] matmul per chunk) instead of keeping any [N, V]
residual.  Peak extra memory is O(N * chunk_size); FLOPs go up ~1.33x
on the head (recompute) in exchange — on an HBM-bound epilogue that is
the right trade for the MXU.

No reference counterpart (the reference owns no kernels or losses —
SURVEY.md §5); the technique is the public "fused/chunked linear
cross-entropy" pattern used by large-vocab LM trainers.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

#: Default vocab chunk: 8k columns x f32 keeps the live chunk tensor at
#: N x 32 KiB — far below the [N, V] it replaces, big enough to feed the
#: MXU efficient [*, D] x [D, C] tiles.
DEFAULT_CHUNK = 8192


def _prep_table(table, layout: str):
    """Normalize to [V, D] (rows = classes)."""
    if layout == "vd":
        return table
    if layout == "dv":
        return table.T
    raise ValueError(f"table layout must be 'vd' or 'dv', got {layout!r}")


def _chunked(table_vd, chunk: int):
    """[V, D] -> (padded [n_chunks, chunk, D], n_chunks, V)."""
    v = table_vd.shape[0]
    n_chunks = -(-v // chunk)
    pad = n_chunks * chunk - v
    if pad:
        table_vd = jnp.pad(table_vd, ((0, pad), (0, 0)))
    return table_vd.reshape(n_chunks, chunk, table_vd.shape[-1]), n_chunks, v


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_nll(x, table, targets, layout, chunk):
    nll, _ = _fused_fwd(x, table, targets, layout, chunk)
    return nll


def _fused_fwd(x, table, targets, layout, chunk):
    x32 = x.astype(jnp.float32)
    chunks, n_chunks, v = _chunked(
        _prep_table(table, layout).astype(jnp.float32), chunk
    )
    n = x32.shape[0]

    def body(carry, inp):
        m, s, tgt = carry
        idx, w_c = inp  # w_c: [C, D]
        logits = x32 @ w_c.T  # [N, C] — the only [N, C] live at a time
        cols = idx * chunk + jnp.arange(chunk)  # global class ids
        logits = jnp.where(cols[None, :] < v, logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1
        )
        # Accumulate the target logit when it falls in this chunk.
        hit = (targets >= idx * chunk) & (targets < (idx + 1) * chunk)
        local = jnp.clip(targets - idx * chunk, 0, chunk - 1)
        picked = jnp.take_along_axis(logits, local[:, None], axis=-1)[:, 0]
        tgt = jnp.where(hit, picked, tgt)
        return (m_new, s, tgt), None

    init = (
        jnp.full((n,), -jnp.inf, jnp.float32),
        jnp.zeros((n,), jnp.float32),
        jnp.zeros((n,), jnp.float32),
    )
    (m, s, tgt), _ = lax.scan(body, init, (jnp.arange(n_chunks), chunks))
    lse = m + jnp.log(s)
    return lse - tgt, (x, table, targets, lse)


def _fused_bwd(layout, chunk, res, g):
    x, table, targets, lse = res
    x32 = x.astype(jnp.float32)
    chunks, n_chunks, v = _chunked(
        _prep_table(table, layout).astype(jnp.float32), chunk
    )
    g32 = g.astype(jnp.float32)

    def body(dx, inp):
        idx, w_c = inp
        logits = x32 @ w_c.T  # recompute — no [N, V] residual exists
        cols = idx * chunk + jnp.arange(chunk)
        p = jnp.where(
            cols[None, :] < v, jnp.exp(logits - lse[:, None]), 0.0
        )
        onehot = (targets[:, None] == cols[None, :]).astype(jnp.float32)
        gp = (p - onehot) * g32[:, None]  # [N, C]
        dx = dx + gp @ w_c  # [N, D]
        dw_c = gp.T @ x32  # [C, D]
        return dx, dw_c

    dx, dws = lax.scan(
        body, jnp.zeros(x32.shape, jnp.float32),
        (jnp.arange(n_chunks), chunks),
    )
    dtable_vd = dws.reshape(n_chunks * chunk, -1)[:v]
    dtable = dtable_vd if layout == "vd" else dtable_vd.T
    return (
        dx.astype(x.dtype),
        dtable.astype(table.dtype),
        None,
    )


_fused_nll.defvjp(_fused_fwd, _fused_bwd)


def fused_linear_cross_entropy(
    x: jnp.ndarray,
    table: jnp.ndarray,
    targets: jnp.ndarray,
    *,
    table_layout: str = "vd",
    chunk_size: int = DEFAULT_CHUNK,
    weights: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Mean cross-entropy of ``softmax(x @ W)`` against ``targets``
    without materializing the [..., V] logits.

    Args:
      x: activations [..., D] (any leading shape; flattened internally).
      table: class matrix — [V, D] (``table_layout="vd"``, the tied
        token-embedding layout: logits = x @ table^T) or [D, V]
        (``"dv"``, a dense head kernel).
      targets: int class ids, shape = x's leading shape.
      chunk_size: vocab columns per scan step (memory/efficiency knob).
      weights: optional per-position weights, broadcastable to targets'
        shape; the result is sum(nll * w) / max(sum(w), 1) — the same
        normalization as the stock loss path.

    Returns the scalar mean loss.  Compute is f32 regardless of input
    dtypes (matching ``lm_logits``' f32 head).
    """
    import math

    lead = targets.shape
    n = math.prod(lead)
    nll = _fused_nll(
        x.reshape(n, x.shape[-1]),
        table,
        targets.reshape(n),
        table_layout,
        int(chunk_size),
    ).reshape(lead)
    if weights is None:
        return jnp.mean(nll)
    w = jnp.broadcast_to(weights.astype(jnp.float32), lead)
    return jnp.sum(nll * w) / jnp.clip(jnp.sum(w), 1.0)
