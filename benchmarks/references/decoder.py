"""Plain reference of a pre-RMSNorm, RoPE, SwiGLU decoder (Baichuan-7B's
equations, `modeling_baichuan.py`), and the weights every run is made of.

Imports nothing of the program.  The weights come from the seed alone:
``make_params`` builds them for the system under test, in one jitted call,
in the type they are served in; the reference builds the same numbers
again, one layer at a time, and computes in float32 under
``jax.default_matmul_precision("highest")`` — no kernels, no cache, no
slots.  Departure from the published model, as the repo's model has it
(listed under ``assumed`` in the configuration): token embeddings are
multiplied by sqrt(hidden_size).

``precision`` selects what a matrix product sees: ``"f32"`` (the
reference) or ``"fp8"`` (the control: operands rounded to float8_e4m3 with
one scale per tensor).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references.common import fp8, root_key

RMS_EPS = 1e-6
ROPE_BASE = 10000.0


def _kernel(key, fan_in, fan_out, dtype):
    w = jax.random.normal(key, (fan_in, fan_out), jnp.float32)
    return (w * (1.0 / math.sqrt(fan_in))).astype(dtype)


def _norm_scale(key, dim):
    return 1.0 + 0.1 * jax.random.normal(key, (dim,), jnp.float32)


def layer_params(key, sizes, dtype):
    d, inner = sizes["hidden_size"], sizes["intermediate_size"]
    hd = sizes["num_attention_heads"] * sizes["head_dim"]
    k = jax.random.split(key, 9)
    return {
        "att": {
            "q": {"kernel": _kernel(k[0], d, hd, dtype)},
            "k": {"kernel": _kernel(k[1], d, hd, dtype)},
            "v": {"kernel": _kernel(k[2], d, hd, dtype)},
            "out": {"kernel": _kernel(k[3], hd, d, dtype)},
        },
        "ln1": {"scale": _norm_scale(k[4], d)},
        "mlp": {
            "wi": {"kernel": _kernel(k[5], d, inner, dtype)},
            "wg": {"kernel": _kernel(k[6], d, inner, dtype)},
            "wo": {"kernel": _kernel(k[7], inner, d, dtype)},
        },
        "ln2": {"scale": _norm_scale(k[8], d)},
    }


def _sizes_key(sizes):
    return tuple(sorted((k, v) for k, v in sizes.items()
                        if isinstance(v, int) and not isinstance(v, bool)))


def _keys(seed, sizes):
    """(embedding, layers [L], head, final norm) keys.  Made eagerly and
    handed to the jitted programs as ARGUMENTS: a seed baked into a program
    would make every new seed a new program, and a compile."""
    k_embed, k_layers, k_head, k_ln = jax.random.split(root_key(seed), 4)
    return (k_embed, jax.random.split(k_layers, sizes["num_hidden_layers"]),
            k_head, k_ln)


def _embedding(key, sizes, dtype):
    table = jax.random.normal(
        key, (sizes["vocab_size"], sizes["hidden_size"]), jnp.float32) * 0.02
    return table.astype(dtype)


@functools.partial(jax.jit, static_argnames=("sizes_key", "dtype"))
def _build_params(keys, sizes_key, dtype):
    sizes = dict(sizes_key)
    k_embed, k_layers, k_head, k_ln = keys
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    return {
        "embed": {"table": _embedding(k_embed, sizes, dtype)},
        # ``lax.map`` makes one layer at a time, so the float32 normals of
        # one layer are all the scratch it needs.
        "layers": jax.lax.map(
            lambda key: layer_params(key, sizes, dtype), k_layers),
        "ln_f": {"scale": _norm_scale(k_ln, d)},
        "head": {"kernel": _kernel(k_head, d, v, dtype)},
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


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS) * scale


def _rope(x, positions):
    """Rotate-half RoPE on [T, H, D] at ``positions`` [T]."""
    half = x.shape[-1] // 2
    freqs = ROPE_BASE ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(p, x, sizes, precision):
    """One block on one sequence x [T, D], causal."""
    t = x.shape[0]
    h, hd = sizes["num_attention_heads"], sizes["head_dim"]
    positions = jnp.arange(t)
    y = _rmsnorm(x, p["ln1"]["scale"])
    q = _rope(matmul(y, p["att"]["q"]["kernel"], precision)
              .reshape(t, h, hd), positions)
    k = _rope(matmul(y, p["att"]["k"]["kernel"], precision)
              .reshape(t, h, hd), positions)
    v = matmul(y, p["att"]["v"]["kernel"], precision).reshape(t, h, hd)
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        precision=jax.lax.Precision.HIGHEST)
    scores = scores / math.sqrt(hd)
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v,
                     precision=jax.lax.Precision.HIGHEST)
    x = x + matmul(att.reshape(t, h * hd), p["att"]["out"]["kernel"],
                   precision)
    y = _rmsnorm(x, p["ln2"]["scale"])
    gate = jax.nn.silu(matmul(y, p["mlp"]["wi"]["kernel"], precision))
    up = matmul(y, p["mlp"]["wg"]["kernel"], precision)
    return x + matmul(gate * up, p["mlp"]["wo"]["kernel"], precision)


@functools.partial(jax.jit, static_argnames=("sizes_key", "precision",
                                             "dtype"), donate_argnums=1)
def _apply_layer(key, xs, sizes_key, precision, dtype):
    sizes = dict(sizes_key)
    p = layer_params(key, sizes, dtype)
    return jax.lax.map(lambda x: _layer(p, x, sizes, precision), xs)


@functools.partial(jax.jit, static_argnames=("sizes_key", "dtype"))
def _embed(key, tokens, sizes_key, dtype):
    sizes = dict(sizes_key)
    table = _embedding(key, sizes, dtype).astype(jnp.float32)
    return jnp.take(table, tokens, axis=0) * math.sqrt(sizes["hidden_size"])


@functools.partial(jax.jit, static_argnames=("sizes_key", "precision",
                                             "dtype"))
def _head(k_head, k_ln, xs, rows, chosen, sizes_key, precision, dtype):
    sizes = dict(sizes_key)
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    picked = jnp.take_along_axis(xs, rows[:, :, None], axis=1)
    y = _rmsnorm(picked, _norm_scale(k_ln, d))
    logits = matmul(y, _kernel(k_head, d, v, dtype), precision)
    return {
        "best": jnp.max(logits, -1),
        "argmax": jnp.argmax(logits, -1),
        "chosen": jnp.take_along_axis(
            logits, chosen[:, :, None], axis=-1)[..., 0],
        "std": jnp.std(logits, -1),
    }


def score(seed, sizes, tokens, rows, chosen, precision="f32",
          dtype=jnp.bfloat16):
    """The full forward pass over ``tokens`` [N, T] (right-padded; causal,
    so the padding is inert), read at positions ``rows`` [N, R]: for each
    the best logit, its token, the logit of ``chosen`` [N, R] and the
    standard deviation of the row's logits.  Layer by layer, so that one
    layer's weights are all it holds."""
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
