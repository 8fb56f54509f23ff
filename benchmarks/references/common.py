"""What the plain references share: a key from any seed, and the rounding
of the controls.  Imports nothing of the program."""

import jax
import jax.numpy as jnp


def root_key(seed):
    """A key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed % (2 ** 31)), seed // (2 ** 31))


def fp8(x):
    """``x`` rounded to float8_e4m3 with one scale for the tensor; the
    gradient passes straight through (a cotangent cast to fp8 is 0)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x + jax.lax.stop_gradient(rounded * scale - x)
