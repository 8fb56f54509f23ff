"""Plain reference of ResNet-50 (He et al. 2015, Table 1; bottleneck
blocks, stride on the 3x3) with GroupNorm-32 in place of BatchNorm, its
cross-entropy loss, gradients and SGD-with-momentum steps, and the weights
every run starts from.

Imports nothing of the program.  float32 ``jax.numpy``/``lax`` under
``Precision.HIGHEST``, rows of a batch taken in blocks so that the float32
activations fit (GroupNorm is per sample, so the mean of the blocks'
gradients is the batch's).  ``precision``: ``"f32"`` (the reference),
``"bf16"`` (what the configuration states: bf16 activations and products),
``"fp8"`` (the control: convolution operands rounded to float8_e4m3 with
one scale per tensor).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references.common import fp8, root_key  # noqa: F401

GN_EPS = 1e-5


def _conv_init(key, kh, kw, cin, cout):
    std = math.sqrt(2.0 / (kh * kw * cin))
    return {"kernel": jax.random.normal(
        key, (kh, kw, cin, cout), jnp.float32) * std}


def _gn_init(key, c):
    ks, kb = jax.random.split(key)
    return {"scale": 1.0 + 0.1 * jax.random.normal(ks, (c,), jnp.float32),
            "bias": 0.1 * jax.random.normal(kb, (c,), jnp.float32)}


def blocks(sizes):
    """(name, cin, cmid, stride) of every bottleneck, in order."""
    out, cin = [], sizes["width"]
    for stage, n in enumerate(sizes["stage_sizes"]):
        cmid = sizes["width"] * 2 ** stage
        for block in range(n):
            stride = 2 if (block == 0 and stage > 0) else 1
            out.append((f"stage{stage}_block{block}", cin, cmid, stride))
            cin = cmid * 4
    return out


def init_params(key, sizes):
    """float32 parameters from ``key`` (a pytree of plain dicts)."""
    keys = iter(jax.random.split(key, 4 + 8 * len(blocks(sizes))))
    params = {"stem": _conv_init(next(keys), 7, 7, 3, sizes["width"]),
              "gn_stem": _gn_init(next(keys), sizes["width"])}
    for name, cin, cmid, stride in blocks(sizes):
        cout = cmid * 4
        block = {
            "conv1": _conv_init(next(keys), 1, 1, cin, cmid),
            "gn1": _gn_init(next(keys), cmid),
            "conv2": _conv_init(next(keys), 3, 3, cmid, cmid),
            "gn2": _gn_init(next(keys), cmid),
            "conv3": _conv_init(next(keys), 1, 1, cmid, cout),
            "gn3": _gn_init(next(keys), cout),
        }
        if stride != 1 or cin != cout:
            block["proj"] = _conv_init(next(keys), 1, 1, cin, cout)
            block["gn_proj"] = _gn_init(next(keys), cout)
        params[name] = block
    cin = blocks(sizes)[-1][2] * 4
    params["head"] = {
        "kernel": jax.random.normal(
            next(keys), (cin, sizes["num_classes"]), jnp.float32)
        / math.sqrt(cin),
        "bias": jnp.zeros((sizes["num_classes"],), jnp.float32),
    }
    return params


# -- forward, loss, steps -----------------------------------------------


def _conv(p, x, stride, precision):
    w = p["kernel"]
    kw = dict(window_strides=(stride, stride), padding="SAME",
              dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if precision == "bf16":
        return jax.lax.conv_general_dilated(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), **kw)
    if precision == "fp8":
        x, w = fp8(x.astype(jnp.float32)), fp8(w)
    return jax.lax.conv_general_dilated(
        x, w, precision=jax.lax.Precision.HIGHEST, **kw)


def _group_norm(p, x, groups, relu=False, residual=None):
    dtype = x.dtype
    b, h, w, c = x.shape
    g = min(groups, c)
    x32 = x.astype(jnp.float32).reshape(b, h * w, g, c // g)
    mean = jnp.mean(x32, axis=(1, 3), keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=(1, 3), keepdims=True)
    y = ((x32 - mean) * jax.lax.rsqrt(var + GN_EPS)).reshape(b, h, w, c)
    y = y * p["scale"] + p["bias"]
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(dtype)


def forward(params, images, sizes, precision):
    groups = sizes["num_groups"]
    act = jnp.bfloat16 if precision == "bf16" else jnp.float32
    x = images.astype(act)
    x = _conv(params["stem"], x, 2, precision)
    x = _group_norm(params["gn_stem"], x, groups, relu=True)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for name, _, _, stride in blocks(sizes):
        p, shortcut = params[name], x
        y = _group_norm(p["gn1"], _conv(p["conv1"], x, 1, precision),
                        groups, relu=True)
        y = _group_norm(p["gn2"], _conv(p["conv2"], y, stride, precision),
                        groups, relu=True)
        if "proj" in p:
            shortcut = _group_norm(
                p["gn_proj"], _conv(p["proj"], x, stride, precision), groups)
        x = _group_norm(p["gn3"], _conv(p["conv3"], y, 1, precision), groups,
                        relu=True, residual=shortcut)
    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
    return jnp.matmul(x, params["head"]["kernel"],
                      precision=jax.lax.Precision.HIGHEST) \
        + params["head"]["bias"]


def loss_sum(params, images, labels, sizes, precision):
    logp = jax.nn.log_softmax(forward(params, images, sizes, precision))
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


@functools.partial(jax.jit, static_argnames=("sizes_key", "precision"))
def _grad_block(params, images, labels, sizes_key, precision):
    return jax.value_and_grad(loss_sum)(
        params, images, labels, dict(sizes_key), precision)


@functools.partial(jax.jit, static_argnames=("learning_rate", "momentum"))
def _update(params, trace, grads, learning_rate, momentum):
    trace = jax.tree_util.tree_map(lambda g, t: g + momentum * t, grads,
                                   trace)
    params = jax.tree_util.tree_map(
        lambda p, t: p - learning_rate * t, params, trace)
    return params, trace


@functools.partial(jax.jit, static_argnames=("sizes_key",))
def _init(key, sizes_key):
    return init_params(key, dict(sizes_key))


def _sizes_key(sizes):
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in sizes.items() if isinstance(v, (int, list))))


def first_steps(key, sizes, batches, *, learning_rate, momentum,
                precision="f32", rows=32, drop_half=False):
    """Drive ``len(batches)`` SGD-with-momentum steps from ``key``.

    Returns the loss of each step, every leaf of the first gradient, and
    every leaf's change over the steps (float32 on the host, leaves in
    ``jax.tree_util.tree_leaves`` order).  ``drop_half`` plants the
    fault "half of the batch left out, the mean taken over the rest".
    """
    sizes_key = _sizes_key(sizes)
    params0 = _init(key, sizes_key)
    params = params0
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for batch in batches:
        images, labels = batch["image"], batch["label"]
        n = len(labels) // 2 if drop_half else len(labels)
        total, grads = 0.0, None
        for start in range(0, n, rows):
            stop = min(start + rows, n)
            loss, g = _grad_block(params, images[start:stop],
                                  labels[start:stop], sizes_key, precision)
            total += float(loss)
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        grads = jax.tree_util.tree_map(lambda g: g / n, grads)
        losses.append(total / n)
        if first_grad is None:
            first_grad = _host_leaves(grads)
        params, trace = _update(params, trace, grads, learning_rate,
                                momentum)
    change = _host_leaves(jax.tree_util.tree_map(
        jnp.subtract, params, params0))
    names = ["/".join(str(k.key) for k in path) for path, _ in
             jax.tree_util.tree_flatten_with_path(params0)[0]]
    return {"loss": np.asarray(losses), "grad": first_grad, "change": change,
            "names": names}


def _host_leaves(tree):
    return [np.asarray(x, np.float32)
            for x in jax.tree_util.tree_leaves(tree)]
