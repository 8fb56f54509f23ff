"""Latent attention (MLA, the DeepSeek-V3 block's): low-rank query and
key/value projections, and a cache row that is one latent vector a token.

One block, with ``u`` the normed input [.., D]::

    cq          = rmsnorm(u Wdq)                       q_rank
    [q_n | q_r] = cq Wuq            per head            nope_dim | rope_dim
    [c | k_r]   = u Wdkv                                kv_rank | rope_dim
    c           = rmsnorm(c)
    [k_n | v]   = c Wukv            per head            nope_dim | v_dim
    q_r, k_r rotated (RoPE over rope_dim, YaRN frequencies); k_r is ONE
    vector a token, shared by every head
    o = softmax(scale (q_n k_n^T + q_r k_r^T) + causal) v ;  out = concat(o) Wo

What a token leaves in the cache is ``[c | k_r]`` (``kv_rank + rope_dim``
numbers, padded to whole lane rows: :func:`row_width`), whatever the head
count.  Two forms of the same attention read it:

- **expanded** (prefill): ``k_n`` and ``v`` are made from ``c`` for every
  head and plain causal attention runs at head sizes ``nope + rope`` / ``v``
  (:func:`expanded_attention`, through the flash kernel);
- **absorbed** (decode): ``Wukv`` splits a head into ``Wuk`` (kv_rank ->
  nope) and ``Wuv`` (kv_rank -> v); ``q_lat = Wuk^T q_n`` scores straight
  against ``c``, the weighted sum of the rows ``o_lat`` goes through
  ``Wuv``: the row is key and value at once and is fetched once for all
  heads (:func:`absorbed_queries`, ``ops.latent_attention``,
  :func:`absorbed_values`).

The rotation pairs dimensions ``(2i, 2i + 1)`` of the projection's output
and leaves them de-interleaved (first halves, then second halves): q and k
get the same permutation, so every score is the interleaved rotation's.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from cloud_tpu.models import layers

LANES = 128


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    #: YaRN (``rope_scaling`` of the published config); a factor of 1 is
    #: plain RoPE at ``TransformerConfig.rope_base``.
    rope_factor: float = 1.0
    rope_original: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def row_width(self) -> int:
        """A cache row: ``kv_rank + rope_dim`` numbers in whole lane rows
        (512 + 64 -> 640: the 64 after the rotated key are zeros)."""
        return -(-(self.kv_rank + self.rope_dim) // LANES) * LANES


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg: LatentConfig) -> float:
    """``qk_dim^-0.5``, times YaRN's ``mscale(factor, mscale_all_dim)^2``
    (the DeepSeek-V3 attention's rule)."""
    scale = cfg.qk_dim ** -0.5
    if cfg.mscale_all_dim:
        scale *= _yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim) ** 2
    return scale


def inv_freq(cfg: LatentConfig, base: float) -> np.ndarray:
    """The rotation's ``rope_dim / 2`` frequencies: ``base^(-2i/d)``,
    blended with that over ``rope_factor`` by YaRN's linear ramp between
    the correction dimensions of ``beta_fast`` and ``beta_slow``."""
    d = cfg.rope_dim
    plain = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if cfg.rope_factor <= 1:
        return plain.astype(np.float32)

    def correction_dim(rotations):
        return (d * math.log(cfg.rope_original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(cfg.beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (plain / cfg.rope_factor * ramp
            + plain * (1.0 - ramp)).astype(np.float32)


def rotate(x, positions, cfg: LatentConfig, base: float):
    """RoPE on the last axis of ``x`` [B, T, .., rope_dim] at ``positions``
    [B, T]: pairs ``(2i, 2i + 1)`` in, de-interleaved out."""
    angles = (positions.astype(jnp.float32)[..., None]
              * jnp.asarray(inv_freq(cfg, base)))
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3)
                            + angles.shape[-1:])
    factor = (_yarn_mscale(cfg.rope_factor, cfg.mscale)
              / _yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim))
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def attention_axes():
    dense = layers.dense_axes
    return {
        "q_a": dense("embed", None, use_bias=False),
        "q_norm": {"scale": (None,)},
        "q_b": dense(None, "heads", use_bias=False),
        "kv_a": dense("embed", None, use_bias=False),
        "kv_norm": {"scale": (None,)},
        "kv_b": dense(None, "heads", use_bias=False),
        "out": dense("heads", "embed", use_bias=False),
    }


def attention_init(rng, dim: int, num_heads: int, cfg: LatentConfig):
    rngs = jax.random.split(rng, 5)
    params = {"q_norm": layers.rmsnorm_init(cfg.q_rank)[0],
              "kv_norm": layers.rmsnorm_init(cfg.kv_rank)[0]}
    for name, r, i, o in [
        ("q_a", rngs[0], dim, cfg.q_rank),
        ("q_b", rngs[1], cfg.q_rank, num_heads * cfg.qk_dim),
        ("kv_a", rngs[2], dim, cfg.kv_rank + cfg.rope_dim),
        ("kv_b", rngs[3], cfg.kv_rank,
         num_heads * (cfg.nope_dim + cfg.v_dim)),
        ("out", rngs[4], num_heads * cfg.v_dim, dim),
    ]:
        params[name], _ = layers.dense_init(r, i, o, in_axis=None,
                                            out_axis=None, use_bias=False)
    return params, attention_axes()


def project(att, u, positions, config):
    """What one block's attention needs of its normed input ``u``
    [B, T, D]: ``q_nope`` [B, T, H, nope], ``q_pe`` [B, T, H, rope]
    (rotated), and the token's cache row as its two parts, ``c``
    [B, T, kv_rank] (normed) and ``k_pe`` [B, T, rope] (rotated)."""
    cfg, eps = config.latent, config.norm_eps
    b, t, _ = u.shape
    with layers.scope("attn_proj"):
        cq = layers.rmsnorm_apply(att["q_norm"],
                                  layers.dense_apply(att["q_a"], u), eps=eps)
        q = layers.dense_apply(att["q_b"], cq).reshape(
            b, t, config.num_heads, cfg.qk_dim)
        q_nope, q_pe = q[..., :cfg.nope_dim], q[..., cfg.nope_dim:]
        down = layers.dense_apply(att["kv_a"], u)
        c = layers.rmsnorm_apply(att["kv_norm"], down[..., :cfg.kv_rank],
                                 eps=eps)
        k_pe = down[..., cfg.kv_rank:]
        return (q_nope, rotate(q_pe, positions, cfg, config.rope_base),
                c, rotate(k_pe, positions, cfg, config.rope_base))


def cache_rows(c, k_pe, cfg: LatentConfig, dtype):
    """``[c | k_pe | 0]`` [.., row_width] as the cache stores a token."""
    pad = cfg.row_width - cfg.kv_rank - cfg.rope_dim
    parts = [c.astype(dtype), k_pe.astype(dtype)]
    if pad:
        parts.append(jnp.zeros(c.shape[:-1] + (pad,), dtype))
    return jnp.concatenate(parts, axis=-1)


def _up_kernel(att, config):
    """``Wukv`` as [kv_rank, H, nope + v]."""
    cfg = config.latent
    kernel = layers.materialize_matrix(att["kv_b"], "kernel", config.dtype)
    return kernel.reshape(cfg.kv_rank, config.num_heads,
                          cfg.nope_dim + cfg.v_dim)


def expanded_attention(att, q_nope, q_pe, c, k_pe, lengths, config, *,
                       rules, mesh):
    """Causal attention over a prompt in the expanded form: per-head keys
    ``[k_nope | k_pe]`` and values made from ``c``, through the flash
    kernel, which takes the values at their own head size.  The kernel
    scales by the root of the queries' size, so YaRN's factor goes into
    the queries.  ``lengths`` [B] (None: every row whole) says where each
    right-padded row ends.  Returns [B, T, H, v_dim]."""
    from cloud_tpu import ops

    cfg = config.latent
    b, t = c.shape[:2]
    with layers.scope("attn_proj"):
        up = layers.dense_apply(att["kv_b"], c).reshape(
            b, t, config.num_heads, cfg.nope_dim + cfg.v_dim)
        k = jnp.concatenate([
            up[..., :cfg.nope_dim],
            jnp.broadcast_to(k_pe[:, :, None, :],
                             (b, t, config.num_heads, cfg.rope_dim)),
        ], axis=-1)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        q = layers.scaled(q, softmax_scale(cfg) * math.sqrt(cfg.qk_dim))
        v = up[..., cfg.nope_dim:]
    with layers.scope("attn_read"):
        return ops.flash_attention(
            q, k, v, causal=True, lengths=lengths,
            partitioned=mesh is not None, mesh=mesh,
            batch_axes=rules.assignment("batch"),
            head_axes=rules.assignment("heads"),
        )


def absorbed_queries(att, q_nope, q_pe, config):
    """One token's queries against a cache row as it is stored:
    ``[Wuk^T q_nope | q_pe | 0]`` [B, H, row_width]."""
    cfg = config.latent
    with layers.scope("attn_proj"):
        w_uk = _up_kernel(att, config)[..., :cfg.nope_dim]
        q_lat = jnp.einsum("bhn,chn->bhc", q_nope, w_uk)
        return cache_rows(q_lat, q_pe, cfg, q_nope.dtype)


def absorbed_values(att, o_lat, config):
    """The weighted sum of the rows' latent part ``o_lat`` [B, H, kv_rank]
    through ``Wuv``: [B, H, v_dim]."""
    cfg = config.latent
    with layers.scope("attn_out"):
        w_uv = _up_kernel(att, config)[..., cfg.nope_dim:]
        return jnp.einsum("bhc,chv->bhv", o_lat.astype(w_uv.dtype), w_uv)


def attention_out(att, attended, config):
    """The output projection on ``attended`` [B, T, H, v_dim]."""
    b, t = attended.shape[:2]
    with layers.scope("attn_out"):
        return layers.dense_apply(att["out"], attended.reshape(b, t, -1))
