"""Plain reference of the Falcon-H1 block (``modeling_falcon_h1.py``:
attention heads and Mamba-2 heads side by side in every block, grouped
K/V heads, muP multipliers), and the weights every run is made of.

Imports nothing of the program.  The weights come from the seed alone:
``make_params`` builds them for the system under test, in one jitted call,
in the type they are served in; the reference builds the same numbers
again, one layer at a time and the head in slices of the vocabulary, and
computes in float32 under ``jax.default_matmul_precision("highest")`` —
no kernels, no cache, no slots, one sequence at a time, the recurrence a
plain ``lax.scan`` over tokens (not the chunked form).

One block, with ``x`` the residual stream [T, D] (``eps`` = rms_norm_eps)::

    x0   = embed[tokens] * embedding_multiplier
    u    = rmsnorm(x; ln1)
    q    = (u * attention_in) Wq ; k = ((u * attention_in) Wk) * key_multiplier ; v = (u * attention_in) Wv
    a    = softmax(causal(rope(q) rope(k)^T / sqrt(head_dim))) v     query head h reads K/V head h // group
    att  = (a Wo) * attention_out
    p    = ((u * ssm_in) Win) * mup                Win: D -> z | x | B | C | dt ;  mup = ssm_multipliers by segment
    xBC  = silu(causal_depthwise_conv(x | B | C, width W) + conv_bias)
    dt   = softplus(dt + dt_bias) ;  A = -exp(A_log)
    h_t  = exp(dt_t A) h_{t-1} + dt_t outer(x_t, B_t) ;  y_t = h_t C_t + D x_t      per head, B and C of its group
    ssm  = (group_rmsnorm(y * silu(z); norm) Wout) * ssm_out
    x    = x + att + ssm                             both mixers read the SAME u; one residual add
    m    = rmsnorm(x; ln2)
    x    = x + ((m Wup) * silu((m Wgate) * mlp_multipliers[0])) Wdown * mlp_multipliers[1]
    logits = (rmsnorm(x_L; ln_f) Whead) * lm_head_multiplier

Points the published ``config.json`` does not settle, as read from the
modelling code from memory (each also under ``assumed`` in the
configuration file): the gate is applied BEFORE the group norm
(``mamba_norm_before_gate`` false), whose groups are the ``mamba_n_groups``
contiguous spans of ``d_ssm``; ``key_multiplier`` multiplies the key
projection before RoPE; ``time_step_limit`` is (0, inf), no clamp on dt;
RoPE is rotate-half over the whole head; heads 0..H/G-1 read group 0.
The weights are random: N(0, 1/fan_in) kernels, N(0, 0.02^2) embeddings,
norm scales 1 + 0.1 N(0,1), ``A_log`` = log U[1, 16], ``dt_bias`` the
inverse softplus of a log-uniform step in [1e-3, 1e-1], ``D`` = 1, the
convolution's kernel N(0, 1/W) and its bias 0.1 N(0,1).

``precision`` selects what a matrix product sees: ``"f32"`` (the
reference) or ``"fp8"`` (the control: operands rounded to float8_e4m3 with
one scale per tensor; the recurrence itself stays float32).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references.common import fp8, root_key

#: The embedding table and the head are made, and the head is read, in
#: this many slices of the vocabulary: the head's float32 kernel is
#: 5.35 GB at the published sizes, a slice a seventeenth of it.
VOCAB_SLICES = 17


def _vocab_slices(sizes):
    return VOCAB_SLICES if sizes["vocab_size"] % VOCAB_SLICES == 0 else 1


def _kernel(key, fan_in, fan_out, dtype):
    w = jax.random.normal(key, (fan_in, fan_out), jnp.float32)
    return (w * (1.0 / math.sqrt(fan_in))).astype(dtype)


def _norm_scale(key, dim):
    return 1.0 + 0.1 * jax.random.normal(key, (dim,), jnp.float32)


def _ssm_dims(sizes):
    gn = sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    conv_dim = sizes["mamba_d_ssm"] + 2 * gn
    return gn, conv_dim, sizes["mamba_d_ssm"] + conv_dim + sizes["mamba_n_heads"]


def layer_params(key, sizes, dtype):
    d, inner = sizes["hidden_size"], sizes["intermediate_size"]
    hd = sizes["head_dim"]
    q_dim = sizes["num_attention_heads"] * hd
    kv_dim = sizes["num_key_value_heads"] * hd
    heads, d_ssm, w = (sizes["mamba_n_heads"], sizes["mamba_d_ssm"],
                       sizes["mamba_d_conv"])
    _, conv_dim, in_dim = _ssm_dims(sizes)
    k = jax.random.split(key, 17)
    step = jnp.exp(jax.random.uniform(
        k[13], (heads,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        "att": {
            "q": {"kernel": _kernel(k[0], d, q_dim, dtype)},
            "k": {"kernel": _kernel(k[1], d, kv_dim, dtype)},
            "v": {"kernel": _kernel(k[2], d, kv_dim, dtype)},
            "out": {"kernel": _kernel(k[3], q_dim, d, dtype)},
        },
        "ln1": {"scale": _norm_scale(k[4], d)},
        "mlp": {
            "wi": {"kernel": _kernel(k[5], d, inner, dtype)},   # gate
            "wg": {"kernel": _kernel(k[6], d, inner, dtype)},   # up
            "wo": {"kernel": _kernel(k[7], inner, d, dtype)},   # down
        },
        "ln2": {"scale": _norm_scale(k[8], d)},
        "ssm": {
            "in": {"kernel": _kernel(k[9], d, in_dim, dtype)},
            "conv": {
                "kernel": jax.random.normal(
                    k[10], (w, conv_dim), jnp.float32) / math.sqrt(w),
                "bias": 0.1 * jax.random.normal(
                    k[11], (conv_dim,), jnp.float32),
            },
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(jax.random.uniform(
                k[12], (heads,), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((heads,), jnp.float32),
            "norm": {"scale": _norm_scale(k[14], d_ssm)},
            "out": {"kernel": _kernel(k[15], d_ssm, d, dtype)},
        },
    }


def _sizes_key(sizes):
    """The sizes and multipliers a program is specialised by: whole
    numbers, reals and the two lists of multipliers."""
    def number(v):
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    return tuple(sorted(
        (k, tuple(v) if isinstance(v, (list, tuple)) else v)
        for k, v in sizes.items()
        if number(v) or (isinstance(v, (list, tuple)) and v
                         and all(number(x) for x in v))))


def _keys(seed, sizes):
    """(embedding slices, layers [L], head slices, final norm) keys.
    Made eagerly and handed to the jitted programs as ARGUMENTS: a seed
    baked into a program would make every new seed a new program."""
    k_embed, k_layers, k_head, k_ln = jax.random.split(root_key(seed), 4)
    n = _vocab_slices(sizes)
    return (jax.random.split(k_embed, n),
            jax.random.split(k_layers, sizes["num_hidden_layers"]),
            jax.random.split(k_head, n), k_ln)


def _embedding(keys, sizes, dtype):
    """The table [V, D], a slice of rows at a time, so that one slice's
    float32 normals are all the scratch it needs."""
    v, d = sizes["vocab_size"], sizes["hidden_size"]
    rows = v // keys.shape[0]
    slices = jax.lax.map(
        lambda key: (jax.random.normal(key, (rows, d), jnp.float32)
                     * 0.02).astype(dtype), keys)
    return slices.reshape(v, d)


def _head_slice(key, sizes, dtype, slices):
    """Columns [i V/n, (i + 1) V/n) of the head's kernel [D, V]."""
    return _kernel(key, sizes["hidden_size"],
                   sizes["vocab_size"] // slices, dtype)


def _head_kernel(keys, sizes, dtype):
    """The head [D, V], a slice of columns at a time, written in place."""
    v, d = sizes["vocab_size"], sizes["hidden_size"]
    n = keys.shape[0]

    def write(i, kernel):
        return jax.lax.dynamic_update_slice(
            kernel, _head_slice(keys[i], sizes, dtype, n), (0, i * (v // n)))

    return jax.lax.fori_loop(0, n, write, jnp.zeros((d, v), dtype))


@functools.partial(jax.jit, static_argnames=("sizes_key", "dtype"))
def _build_params(keys, sizes_key, dtype):
    sizes = dict(sizes_key)
    k_embed, k_layers, k_head, k_ln = keys
    return {
        "embed": {"table": _embedding(k_embed, sizes, dtype)},
        # ``lax.map`` makes one layer at a time, so the float32 normals of
        # one layer are all the scratch it needs.
        "layers": jax.lax.map(
            lambda key: layer_params(key, sizes, dtype), k_layers),
        "ln_f": {"scale": _norm_scale(k_ln, sizes["hidden_size"])},
        "head": {"kernel": _head_kernel(k_head, sizes, dtype)},
    }


def make_params(seed, sizes, dtype=jnp.bfloat16):
    """All weights from the seed, layers stacked on a leading axis, on the
    device in one jitted call, in the type they are served in."""
    return _build_params(_keys(seed, sizes), _sizes_key(sizes), dtype)


def params_shape(sizes, dtype=jnp.bfloat16):
    """``make_params``'s shapes and types, with nothing made."""
    return jax.eval_shape(
        lambda: _build_params(_keys(0, sizes), _sizes_key(sizes), dtype))


# -- the forward pass ---------------------------------------------------


def matmul(x, w, precision):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if precision == "f32":
        return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)
    if precision == "fp8":
        return jnp.matmul(fp8(x), fp8(w),
                          precision=jax.lax.Precision.HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """Rotate-half RoPE on [T, H, D] at ``positions`` [T]."""
    half = x.shape[-1] // 2
    freqs = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, u, sizes, precision):
    t = u.shape[0]
    h, kv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    positions = jnp.arange(t)
    u = u * sizes["attention_in_multiplier"]
    q = _rope(matmul(u, p["q"]["kernel"], precision).reshape(t, h, hd),
              positions, sizes["rope_theta"])
    k = matmul(u, p["k"]["kernel"], precision) * sizes["key_multiplier"]
    k = _rope(k.reshape(t, kv, hd), positions, sizes["rope_theta"])
    v = matmul(u, p["v"]["kernel"], precision).reshape(t, kv, hd)
    # Query head i reads K/V head i // (h / kv).
    k, v = (jnp.repeat(a, h // kv, axis=1) for a in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v,
                     precision=jax.lax.Precision.HIGHEST)
    return (matmul(att.reshape(t, h * hd), p["out"]["kernel"], precision)
            * sizes["attention_out_multiplier"])


def _mamba(p, u, sizes, precision):
    """The Mamba-2 mixer on one sequence u [T, D], token by token."""
    t = u.shape[0]
    heads, hd, n, groups, w, d_ssm = (
        sizes["mamba_n_heads"], sizes["mamba_d_head"], sizes["mamba_d_state"],
        sizes["mamba_n_groups"], sizes["mamba_d_conv"], sizes["mamba_d_ssm"])
    gn, conv_dim, _ = _ssm_dims(sizes)
    mup = jnp.asarray(np.repeat(
        sizes["ssm_multipliers"], (d_ssm, d_ssm, gn, gn, heads)), jnp.float32)
    proj = matmul(u * sizes["ssm_in_multiplier"], p["in"]["kernel"],
                  precision) * mup
    z, xbc, dt = jnp.split(proj, [d_ssm, d_ssm + conv_dim], axis=-1)
    # Causal depthwise convolution: the kernel's last tap on the token.
    padded = jnp.pad(xbc, [(w - 1, 0), (0, 0)])
    conv = sum(p["conv"]["kernel"][i] * padded[i:i + t] for i in range(w))
    xbc = jax.nn.silu(conv + p["conv"]["bias"])
    x, b_mat, c_mat = jnp.split(xbc, [d_ssm, d_ssm + gn], axis=-1)
    x = x.reshape(t, heads, hd)
    # Heads 0 .. heads/groups - 1 read group 0, and so on.
    b_mat, c_mat = (jnp.repeat(m.reshape(t, groups, n), heads // groups,
                               axis=1) for m in (b_mat, c_mat))
    dt = jax.nn.softplus(dt + p["dt_bias"])                    # [T, heads]
    a = -jnp.exp(p["A_log"])

    def token(state, at):
        x_t, b_t, c_t, dt_t = at
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((heads, hd, n), jnp.float32),
                        (x, b_mat, c_mat, dt))
    y = (y + p["D"][:, None] * x).reshape(t, d_ssm)
    y = (y * jax.nn.silu(z)).reshape(t, groups, d_ssm // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + sizes["rms_norm_eps"])
    y = y.reshape(t, d_ssm) * p["norm"]["scale"]
    return (matmul(y, p["out"]["kernel"], precision)
            * sizes["ssm_out_multiplier"])


def _layer(p, x, sizes, precision):
    """One block on one sequence x [T, D], causal."""
    eps = sizes["rms_norm_eps"]
    u = _rmsnorm(x, p["ln1"]["scale"], eps)
    x = (x + _attention(p["att"], u, sizes, precision)
         + _mamba(p["ssm"], u, sizes, precision))
    m = _rmsnorm(x, p["ln2"]["scale"], eps)
    gate_mult, down_mult = sizes["mlp_multipliers"]
    gate = jax.nn.silu(
        matmul(m, p["mlp"]["wi"]["kernel"], precision) * gate_mult)
    up = matmul(m, p["mlp"]["wg"]["kernel"], precision)
    return x + matmul(gate * up, p["mlp"]["wo"]["kernel"],
                      precision) * down_mult


@functools.partial(jax.jit, static_argnames=("sizes_key", "precision",
                                             "dtype"), donate_argnums=1)
def _apply_layer(key, xs, sizes_key, precision, dtype):
    sizes = dict(sizes_key)
    p = layer_params(key, sizes, dtype)
    return jax.lax.map(lambda x: _layer(p, x, sizes, precision), xs)


@functools.partial(jax.jit, static_argnames=("sizes_key", "dtype"))
def _embed(keys, tokens, sizes_key, dtype):
    sizes = dict(sizes_key)
    table = _embedding(keys, sizes, dtype)
    return (jnp.take(table, tokens, axis=0).astype(jnp.float32)
            * sizes["embedding_multiplier"])


@functools.partial(jax.jit, static_argnames=("sizes_key", "precision",
                                             "dtype"))
def _head(k_head, k_ln, xs, rows, chosen, sizes_key, precision, dtype):
    """The logits' best, its token, the chosen token's logit and the
    row's standard deviation, a slice of the vocabulary at a time: the
    maximum, the chosen logit and the moments (count, mean, sum of
    squared distances) combine exactly across slices."""
    sizes = dict(sizes_key)
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    n = k_head.shape[0]
    width = v // n
    picked = jnp.take_along_axis(xs, rows[:, :, None], axis=1)
    y = _rmsnorm(picked, _norm_scale(k_ln, d), sizes["rms_norm_eps"])

    def one_slice(carry, at):
        best, argmax, picked_logit, mean, m2 = carry
        i, key = at
        logits = matmul(y, _head_slice(key, sizes, dtype, n),
                        precision) * sizes["lm_head_multiplier"]
        top = jnp.max(logits, -1)
        argmax = jnp.where(top > best, i * width + jnp.argmax(logits, -1),
                           argmax)
        local = chosen - i * width
        here = (local >= 0) & (local < width)
        value = jnp.take_along_axis(
            logits, jnp.clip(local, 0, width - 1)[:, :, None], axis=-1)[..., 0]
        # Chan's update of the mean and the squared distances from it.
        seen = (i * width).astype(jnp.float32)
        slice_mean = jnp.mean(logits, -1)
        delta = slice_mean - mean
        total = seen + width
        m2 = (m2 + jnp.sum((logits - slice_mean[..., None]) ** 2, -1)
              + delta ** 2 * seen * width / total)
        return (jnp.maximum(best, top), argmax,
                jnp.where(here, value, picked_logit),
                mean + delta * width / total, m2), None

    zeros = jnp.zeros(rows.shape, jnp.float32)
    (best, argmax, picked_logit, _, m2), _ = jax.lax.scan(
        one_slice,
        (zeros - jnp.inf, jnp.zeros(rows.shape, jnp.int32), zeros, zeros,
         zeros), (jnp.arange(n), k_head))
    return {"best": best, "argmax": argmax, "chosen": picked_logit,
            "std": jnp.sqrt(m2 / v)}


def score(seed, sizes, tokens, rows, chosen, precision="f32",
          dtype=jnp.bfloat16):
    """The full forward pass over ``tokens`` [N, T] (right-padded; causal
    and recurrent from the left, so the padding is inert), read at
    positions ``rows`` [N, R]: for each the best logit, its token, the
    logit of ``chosen`` [N, R] and the standard deviation of the row's
    logits.  Layer by layer, so that one layer's weights are all it
    holds, and the head by slices of the vocabulary."""
    sizes_key = _sizes_key(sizes)
    k_embed, k_layers, k_head, k_ln = _keys(seed, sizes)
    with jax.default_matmul_precision("highest"):
        xs = _embed(k_embed, jnp.asarray(tokens, jnp.int32), sizes_key,
                    dtype)
        for key in k_layers:
            xs = _apply_layer(key, xs, sizes_key, precision, dtype)
        out = _head(k_head, k_ln, xs, jnp.asarray(rows, jnp.int32),
                    jnp.asarray(chosen, jnp.int32), sizes_key, precision,
                    dtype)
    return {k: np.asarray(v) for k, v in out.items()}
