"""``layers.dense_heads_apply``: a projection split into heads after the
product.  What it computes is ``dense_apply(...).reshape(...)`` bit for
bit, for every kind of kernel leaf, and so is its gradient (the training
path); what it changes is only how the chip's compiler lays the product
out (tests/unit/test_chip_compile.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu.models import layers, quantization

B, T, D, H, HD = 2, 5, 64, 4, 16


def _params(kind, seed=0):
    rng = np.random.default_rng(seed)
    params = {"kernel": jnp.asarray(rng.normal(size=(D, H * HD)) * 0.2,
                                    jnp.float32)}
    if kind == "bias":
        params["bias"] = jnp.asarray(rng.normal(size=(H * HD,)),
                                     jnp.float32)
    if kind == "int8":
        q, scale = quantization.quantize_array(params["kernel"], axis=-2)
        params = {"kernel_q": q, "kernel_scale": scale}
    return params


def _x(dtype, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(B, T, D)), dtype)


def _old(params, x):
    return layers.dense_apply(params, x).reshape(B, T, H, HD)


def _new(params, x):
    return layers.dense_heads_apply(params, x, H, HD)


KINDS = ["kernel", "bias", "int8"]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", KINDS)
def test_heads_are_the_reshaped_product_bit_for_bit(kind, dtype):
    params, x = _params(kind), _x(dtype)
    got = jax.jit(_new)(params, x)
    want = jax.jit(_old)(params, x)
    assert got.shape == (B, T, H, HD) and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("kind", KINDS)
def test_a_scan_over_stacked_kernels_gives_the_same_heads(kind):
    """The decode programs' shape: one row a slot, the layer's kernel
    sliced from the stack by the scan."""
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves),
        *[_params(kind, seed) for seed in range(3)])
    x = _x(jnp.bfloat16)[:, :1]

    def run(project):
        def body(carry, p):
            y = project(p, carry)
            return carry, y
        return jax.lax.scan(body, x, stacked)[1]

    got = jax.jit(lambda: run(
        lambda p, u: layers.dense_heads_apply(p, u, H, HD)))()
    want = jax.jit(lambda: run(
        lambda p, u: layers.dense_apply(p, u).reshape(B, 1, H, HD)))()
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("kind", ["kernel", "bias"])
def test_gradients_are_the_old_spellings(kind):
    params, x = _params(kind), _x(jnp.float32)
    weights = jnp.asarray(
        np.random.default_rng(2).normal(size=(B, T, H, HD)), jnp.float32)

    def loss(project):
        def f(params, x):
            return jnp.sum(jnp.tanh(project(params, x)) * weights)
        return jax.jit(jax.grad(f, argnums=(0, 1)))(params, x)

    got, want = loss(_new), loss(_old)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
