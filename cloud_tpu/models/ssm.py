"""The Mamba-2 mixer: a selective state-space layer with a FIXED-SIZE
recurrent state (no position axis), as Falcon-H1 runs it beside attention
in every block.

With ``u`` the block's normed input ``[..., D]``::

    p          = ((u * ssm_in) W_in) * mup       W_in: D -> d_ssm (z) | conv_dim (xBC) | H (dt)
    xBC        = silu(causal_depthwise_conv(xBC, width W) + conv_bias)
    x | B | C  = split(xBC, [d_ssm, G N, G N])
    dt         = softplus(dt + dt_bias) ;  A = -exp(A_log)
    h_t        = exp(dt_t A) h_{t-1} + dt_t outer(x_t, B_t)      per head: h [P, N]
    y_t        = h_t C_t + D x_t
    y          = group_rmsnorm(y * silu(z))                      G groups, gate before norm
    out        = (y W_out) * ssm_out

Two entry points share the projections: :func:`ssd_prefill` runs a whole
prompt in the chunked (SSD) form — quadratic inside a chunk, a scan
over chunk states between them — and returns the state and the
convolution's tail AT EACH ROW'S LAST REAL TOKEN; :func:`ssm_step`
advances one token from a carried state.  Both are plain ``jnp``;
:func:`ssm_step_in_place` is the step with the state's own update handed
to ``ops.ssm_state``'s kernel, over the stacked leaf of a slot cache.

What the recurrence keeps is float32 (:data:`STATE_DTYPE`): the state is
multiplied by a decay and added to at every token, so a narrower type
compounds its rounding over the whole context
(tests/unit/test_hybrid_ssm.py pins that bf16 fails the tolerance float32
passes).  The convolution's tail holds activations as they were
produced, in the model's compute dtype.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from cloud_tpu.models import layers

#: The recurrent state's type at rest in a cache (the programs compute in
#: float32 and store in the leaf's own type).
STATE_DTYPE = jnp.float32


@dataclasses.dataclass(frozen=True)
class SsmConfig:
    """The mixer's sizes (Falcon-H1's ``mamba_*`` keys; ``mamba_d_ssm``
    is ``num_heads x head_dim`` and ``mamba_expand`` sizes nothing once
    that is given: neither has a field here)."""

    num_heads: int = 32      # mamba_n_heads
    head_dim: int = 128      # mamba_d_head (P)
    state_dim: int = 256     # mamba_d_state (N)
    num_groups: int = 2      # mamba_n_groups: heads sharing one B and C
    conv_width: int = 4      # mamba_d_conv
    chunk_size: int = 128    # mamba_chunk_size

    def __post_init__(self):
        if self.num_heads % self.num_groups:
            raise ValueError(
                f"num_groups={self.num_groups} must divide "
                f"num_heads={self.num_heads}"
            )

    @property
    def d_ssm(self) -> int:
        """The mixer's inner width (``mamba_d_ssm``)."""
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x | B | C."""
        return self.d_ssm + 2 * self.num_groups * self.state_dim

    @property
    def in_dim(self) -> int:
        """The input projection's width: z | xBC | dt."""
        return self.d_ssm + self.conv_dim + self.num_heads


def ssm_axes():
    """Logical axes of :func:`ssm_init`'s tree.  Nothing is sharded over
    ``tp`` yet (heads and groups would be; ROADMAP R4), so every serving
    mesh refuses a configuration with a mixer."""
    return {
        "in": layers.dense_axes("embed", None, use_bias=False),
        "conv": {"kernel": (None, None), "bias": (None,)},
        "dt_bias": (None,), "A_log": (None,), "D": (None,),
        "norm": {"scale": (None,)},
        "out": layers.dense_axes(None, "embed", use_bias=False),
    }


def ssm_init(rng, dim: int, cfg: SsmConfig):
    """Parameters of one mixer: ``A_log`` = log U[1, 16], ``dt_bias`` the
    inverse softplus of a log-uniform step in [1e-3, 1e-1] (Mamba-2's
    own), ``D`` = 1."""
    r_in, r_conv, r_dt, r_a, r_out = jax.random.split(rng, 5)
    proj_in, _ = layers.dense_init(r_in, dim, cfg.in_dim, in_axis="embed",
                                   out_axis=None, use_bias=False)
    proj_out, _ = layers.dense_init(r_out, cfg.d_ssm, dim, in_axis=None,
                                    out_axis="embed", use_bias=False)
    step = jnp.exp(jax.random.uniform(
        r_dt, (cfg.num_heads,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    params = {
        "in": proj_in,
        "conv": {
            "kernel": jax.random.normal(
                r_conv, (cfg.conv_width, cfg.conv_dim), jnp.float32
            ) / np.sqrt(cfg.conv_width),
            "bias": jnp.zeros((cfg.conv_dim,), jnp.float32),
        },
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(
            r_a, (cfg.num_heads,), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((cfg.num_heads,), jnp.float32),
        "norm": {"scale": jnp.ones((cfg.d_ssm,), jnp.float32)},
        "out": proj_out,
    }
    return params, ssm_axes()


def _project(params, u, cfg: SsmConfig, mult):
    """``u`` [..., D] -> gate ``z`` [..., d_ssm], pre-convolution ``xBC``
    [..., conv_dim] (both in u's dtype) and the raw step ``dt``
    [..., H] float32, each segment times its own multiplier."""
    gn = cfg.num_groups * cfg.state_dim
    p = layers.dense_apply(params["in"], layers.scaled(u, mult.ssm_in))
    if any(m != 1.0 for m in mult.ssm):
        widths = (cfg.d_ssm, cfg.d_ssm, gn, gn, cfg.num_heads)
        p = p * jnp.asarray(np.repeat(mult.ssm, widths), p.dtype)
    z, xbc, dt = jnp.split(p, [cfg.d_ssm, cfg.d_ssm + cfg.conv_dim], axis=-1)
    return z, xbc, dt.astype(jnp.float32)


def _after_conv(params, conv_out, dt, cfg: SsmConfig):
    """The convolution's float32 output [..., conv_dim] -> x [..., H, P],
    B and C [..., G, N], the step dt [..., H] and A [H]."""
    gn = cfg.num_groups * cfg.state_dim
    xbc = jax.nn.silu(conv_out + params["conv"]["bias"])
    x, b_mat, c_mat = jnp.split(xbc, [cfg.d_ssm, cfg.d_ssm + gn], axis=-1)
    lead = xbc.shape[:-1]
    x = x.reshape(lead + (cfg.num_heads, cfg.head_dim))
    b_mat = b_mat.reshape(lead + (cfg.num_groups, cfg.state_dim))
    c_mat = c_mat.reshape(lead + (cfg.num_groups, cfg.state_dim))
    dt = jax.nn.softplus(dt + params["dt_bias"])
    return x, b_mat, c_mat, dt, -jnp.exp(params["A_log"])


def _to_heads(grouped, cfg: SsmConfig):
    """B or C [..., G, N] -> [..., H, N]: head h reads group
    ``h // (H / G)``."""
    return jnp.repeat(grouped, cfg.num_heads // cfg.num_groups, axis=-2)


def _gate_norm_out(params, y, z, cfg: SsmConfig, mult, eps, dtype):
    """y [..., d_ssm] float32 gated by silu(z), RMS-normed inside each of
    the G groups, projected out."""
    y = y * jax.nn.silu(z.astype(jnp.float32))
    grouped = y.reshape(y.shape[:-1] + (cfg.num_groups, -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    y = grouped.reshape(y.shape) * params["norm"]["scale"]
    return layers.scaled(
        layers.dense_apply(params["out"], y.astype(dtype)), mult.ssm_out)


def _ssd_scan(x, dt, a, b_mat, c_mat, cfg: SsmConfig):
    """The recurrence over [B, T] in chunks of ``chunk_size``: inside a
    chunk every output is a masked, decayed sum over the chunk's earlier
    tokens (two matrix products); between chunks one state per chunk is
    carried by a scan.  x [B, T, H, P], dt [B, T, H] (0 where a position
    must leave the state alone), a [H], b_mat / c_mat [B, T, H, N], all
    float32.  Returns y [B, T, H, P] and the state after position T - 1
    [B, H, P, N]."""
    bsz, t, h, p = x.shape
    q = cfg.chunk_size
    pad = -t % q
    if pad:
        # dt = 0 on the tail: no decay and nothing added.
        x, dt, b_mat, c_mat = (
            jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            for v in (x, dt, b_mat, c_mat))
    nc = (t + pad) // q
    x, dt, b_mat, c_mat = (v.reshape((bsz, nc, q) + v.shape[2:])
                           for v in (x, dt, b_mat, c_mat))
    # Log-decay from the chunk's start up to and including each token.
    cs = jnp.cumsum(dt * a, axis=2)                         # [B, nc, Q, H]
    # Inside a chunk: token i reads token j <= i, decayed over (j, i].
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]       # [B, nc, i, j, H]
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = jnp.einsum("bcihn,bcjhn->bcijh", c_mat, b_mat)
    y = jnp.einsum("bcijh,bcjhp->bcihp",
                   scores * decay * dt[:, :, None, :, :], x)
    # What each chunk adds to the state, decayed to the chunk's end.
    to_end = jnp.exp(cs[:, :, -1:, :] - cs) * dt            # [B, nc, Q, H]
    added = jnp.einsum("bcjhp,bcjhn->bchpn", x * to_end[..., None], b_mat)
    chunk_decay = jnp.exp(cs[:, :, -1, :])                  # [B, nc, H]

    def carry_state(state, per_chunk):
        add, keep = per_chunk
        return keep[..., None, None] * state + add, state

    final, entering = jax.lax.scan(
        carry_state, jnp.zeros((bsz, h, p, b_mat.shape[-1]), jnp.float32),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    # What the state entering a chunk gives each of its tokens.
    y = y + jnp.einsum("bcihn,cbhpn->bcihp",
                       c_mat * jnp.exp(cs)[..., None], entering)
    return y.reshape(bsz, nc * q, h, p)[:, :t], final


def ssd_prefill(params, u, mask, prompt_lens, cfg: SsmConfig, mult, eps):
    """The mixer over a right-padded prompt buffer.

    ``u`` [B, T, D] is the block's normed input, ``mask`` [B, T] marks
    real tokens and ``prompt_lens`` [B] counts them.  A padded position
    is inert to attention but would poison a recurrence, so ``dt`` is
    zeroed there (no decay, nothing added: the state after the buffer is
    the state after the last real token) and the convolution's tail is
    gathered at ``prompt_len - (W - 1) .. prompt_len - 1`` (zeros before
    the prompt's start).  Returns the mixer's output [B, T, D], the
    state [B, H, P, N] float32 and the tail [B, W - 1, conv_dim] in u's
    dtype."""
    bsz, t, _ = u.shape
    w = cfg.conv_width
    with layers.scope("ssm_proj"):
        z, xbc, dt = _project(params, u, cfg, mult)
        padded = jnp.pad(xbc, [(0, 0), (w - 1, 0), (0, 0)])
        kernel = params["conv"]["kernel"]
        conv_out = sum(kernel[i] * padded[:, i:i + t].astype(jnp.float32)
                       for i in range(w))
        # padded[j] = xbc[j - (W - 1)]: the last W - 1 real inputs.
        tail_idx = prompt_lens[:, None] + jnp.arange(w - 1)[None, :]
        tail = jnp.take_along_axis(padded, tail_idx[:, :, None], axis=1)
        x, b_mat, c_mat, dt, a = _after_conv(params, conv_out, dt, cfg)
        dt = jnp.where(mask[..., None] > 0, dt, 0.0)
    with layers.scope("ssm_state"):
        y, state = _ssd_scan(x, dt, a, _to_heads(b_mat, cfg),
                             _to_heads(c_mat, cfg), cfg)
    with layers.scope("ssm_proj"):
        y = y + params["D"][:, None] * x
        out = _gate_norm_out(params, y.reshape(bsz, t, cfg.d_ssm), z, cfg,
                             mult, eps, u.dtype)
    return out, state, tail


def _step_inputs(params, u, conv, cfg: SsmConfig, mult):
    """What one token brings to the recurrence: the gate ``z`` [B, d_ssm],
    x [B, H, P], the groups' B and C [B, G, N], the step dt [B, H] and
    A [H] (float32), and the convolution's new tail."""
    z, xbc, dt = _project(params, u, cfg, mult)
    window = jnp.concatenate([conv, xbc[:, None].astype(conv.dtype)], axis=1)
    conv_out = jnp.sum(
        params["conv"]["kernel"] * window.astype(jnp.float32), axis=1)
    return (z,) + _after_conv(params, conv_out, dt, cfg) + (window[:, 1:],)


def _step_output(params, y, x, z, cfg: SsmConfig, mult, eps, dtype):
    """``y = h' C`` [B, H, P] -> the mixer's output [B, D]."""
    y = y + params["D"][:, None] * x
    return _gate_norm_out(params, y.reshape(y.shape[0], cfg.d_ssm), z, cfg,
                          mult, eps, dtype)


def ssm_step(params, u, state, conv, cfg: SsmConfig, mult, eps):
    """One token: ``u`` [B, D], ``state`` [B, H, P, N] float32, ``conv``
    [B, W - 1, conv_dim] the last W - 1 convolution inputs.  Returns the
    mixer's output [B, D], the new state (float32) and the new tail.
    The state is read once and written once, elementwise."""
    with layers.scope("ssm_proj"):
        z, x, b_mat, c_mat, dt, a, tail = _step_inputs(params, u, conv, cfg,
                                                       mult)
    with layers.scope("ssm_state"):
        b_mat, c_mat = _to_heads(b_mat, cfg), _to_heads(c_mat, cfg)
        keep = jnp.exp(dt * a)                                    # [B, H]
        state = (keep[..., None, None] * state.astype(jnp.float32)
                 + (dt[..., None] * x)[..., None] * b_mat[:, :, None, :])
        y = jnp.sum(state * c_mat[:, :, None, :], axis=-1)        # [B, H, P]
    with layers.scope("ssm_proj"):
        out = _step_output(params, y, x, z, cfg, mult, eps, u.dtype)
    return out, state, tail


def ssm_step_in_place(params, u, states, layer, live, conv, cfg: SsmConfig,
                      mult, eps):
    """:func:`ssm_step` over the stacked leaf ``states`` [L, B, H, P, N]
    taken whole: layer ``layer`` of it advances in place, through
    ``ops.ssm_state``'s kernel, for the rows where ``live`` [B] is true,
    and no other byte of it moves.  Returns the mixer's output [B, D]
    (for a row that did not advance, of a zero ``h' C``: whoever froze
    the row masks it), the leaf and the new tail.  The projections, the
    convolution and the gate are :func:`ssm_step`'s own."""
    from cloud_tpu.ops import ssm_state

    with layers.scope("ssm_proj"):
        z, x, b_mat, c_mat, dt, a, tail = _step_inputs(params, u, conv, cfg,
                                                       mult)
    with layers.scope("ssm_state"):
        states, y = ssm_state.state_step(
            states, layer, live, jnp.exp(dt * a), dt[..., None] * x, b_mat,
            c_mat)
    with layers.scope("ssm_proj"):
        out = _step_output(params, y, x, z, cfg, mult, eps, u.dtype)
    return out, states, tail
