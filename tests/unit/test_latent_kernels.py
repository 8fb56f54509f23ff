"""The two kernels the latent-attention expert model brings, against their
``jnp`` routes, through the Pallas interpreter on the CPU: the decode read
over latent rows (``ops.latent_attention``) and the grouped matrix product
of the dropless experts (``ops.grouped_matmul``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu.ops import grouped_matmul as gm
from cloud_tpu.ops import latent_attention as la


def _normal(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(dtype)


#: Five slots' lengths, from the rows a slot holds, the kernel's page and
#: the rows a step of its walk holds: which slots the walk visits (a dead
#: slot, length 0, is skipped whole and reads zeros), where a step ends,
#: and from which slot the steps after the one being scored are fetched.
LENGTHS = {
    "mixed": lambda rows, page, step: [0, 1, 17, rows, 32],
    "all-dead": lambda rows, page, step: [0, 0, 0, 0, 0],
    "live-first": lambda rows, page, step: [rows, 0, 0, 0, 0],
    "live-last": lambda rows, page, step: [0, 0, 0, 0, page + 1],
    "live-between": lambda rows, page, step: [0, 0, page - 1, 0, 0],
    "page-edges": lambda rows, page, step: [1, page - 1, page, page + 1,
                                            rows - 1],
    "step-edges": lambda rows, page, step: [step - 1, step, 0, step + 1,
                                            rows],
    "whole-pages": lambda rows, page, step: [page, rows, 0, 2 * page, rows],
}


@pytest.mark.parametrize("lengths", list(LENGTHS))
@pytest.mark.parametrize("dtype, tolerance", [(jnp.float32, 2e-6),
                                              (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("rows", [64, 48, 112])
def test_latent_decode_reads_a_layer_of_the_stack_in_place(dtype, tolerance,
                                                           rows, lengths):
    """Five slots at each of :data:`LENGTHS` (64 rows: one page of 64; 48:
    three pages of 16, one step; 112: seven pages of 16, steps of six and
    one); layer 1 of a stack of 3, traced."""
    rng = np.random.default_rng(0)
    b, h, w, v = 5, 4, 128, 96
    q = _normal(rng, (b, h, w), dtype)
    stack = _normal(rng, (3, b, rows, w), dtype)
    page = la.kernel_page(q, stack, use_pallas=True)
    assert page == {64: 64, 48: 16, 112: 16}[rows]
    step = page * la._step_pages(page, rows)
    lens = jnp.minimum(jnp.asarray(LENGTHS[lengths](rows, page, step),
                                   jnp.int32), rows)
    want = la._reference(q, stack[1], lens, value_dim=v, scale=0.3)
    traced = la.KERNEL_TRACE_COUNT
    got = jax.jit(lambda q, s, n, l: la.latent_decode_attention(
        q, s, n, value_dim=v, scale=0.3, layer=l, use_pallas=True,
        interpret=True))(q, stack, lens, jnp.int32(1))
    assert la.KERNEL_TRACE_COUNT == traced + 1
    assert got.shape == (b, h, v) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tolerance)
    dead = np.asarray(lens) == 0
    assert not np.asarray(got, np.float32)[dead].any()
    # Stale rows past every slot's length change nothing: the rest of a
    # slot's last page, the pages past it, and a dead slot's rows.
    past = jnp.arange(rows)[None, :, None] >= lens[:, None, None]
    noisy = stack.at[1].set(jnp.where(past, jnp.asarray(1e4, dtype),
                                      stack[1]))
    again = la.latent_decode_attention(
        q, noisy, lens, value_dim=v, scale=0.3, layer=1, use_pallas=True,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(again, np.float32),
                                  np.asarray(got, np.float32))


def test_latent_decode_dispatch():
    q = jnp.zeros((2, 4, 128))
    rows = jnp.zeros((2, 20, 128))  # 20 rows: no page of 8 divides them
    assert la.kernel_page(q, rows) is None
    with pytest.raises(ValueError, match="cannot take"):
        la.kernel_page(q, rows, use_pallas=True)
    assert la.kernel_page(q, jnp.zeros((3, 2, 768, 128)),
                          use_pallas=True) == 256
    # Off the chip, with nothing armed, the read is the jnp route's.
    assert la.kernel_page(q, jnp.zeros((2, 512, 128))) is None


@pytest.mark.parametrize("m, k, n, sizes", [
    (32, 64, 256, [5, 0, 9, 3]),            # an empty group, a short tail
    (512, 128, 384, [100, 0, 200, 50, 0, 3]),   # groups across row tiles
    (2048, 64, 128, [700, 0, 1, 600, 747]),     # the prompt's row tile
    (32, 16, 24, [0, 32, 0]),               # columns short of a lane row
    (64, 32, 128, [0, 0, 0]),               # nothing landed here
])
def test_grouped_matmul_against_ragged_dot(m, k, n, sizes):
    rng = np.random.default_rng(1)
    x = _normal(rng, (m, k), jnp.float32)
    w = _normal(rng, (len(sizes), k, n), jnp.float32)
    groups = jnp.asarray(sizes, jnp.int32)
    traced = gm.KERNEL_TRACE_COUNT
    got = jax.jit(lambda x, w, g: gm.grouped_matmul(
        x, w, g, use_pallas=True, interpret=True))(x, w, groups)
    assert gm.KERNEL_TRACE_COUNT == traced + 1
    total = sum(sizes)
    want = gm._reference(x, w, groups)
    np.testing.assert_allclose(got[:total], want[:total], rtol=1e-5,
                               atol=1e-5)


def test_grouped_matmul_reads_a_layer_of_the_stack_in_place():
    """The stacked matrices [L, E, K, N] with a traced layer: layer 2's
    product, by the kernel and by its jnp route alike."""
    rng = np.random.default_rng(2)
    x = _normal(rng, (64, 32), jnp.float32)
    stack = _normal(rng, (3, 4, 32, 128), jnp.float32)
    groups = jnp.asarray([10, 0, 30, 5], jnp.int32)
    want = gm._reference(x, stack[2], groups)
    for use_pallas in (True, False):
        got = jax.jit(lambda x, w, g, l: gm.grouped_matmul(
            x, w, g, layer=l, use_pallas=use_pallas, interpret=True))(
                x, stack, groups, jnp.int32(2))
        np.testing.assert_allclose(got[:45], want[:45], rtol=1e-5,
                                   atol=1e-5)


def test_grouped_matmul_never_names_a_group_without_rows():
    """The plan's visits: groups 1 and 4 hold no rows and appear in no
    visit, so their matrices are never fetched; the visits past the last
    one that holds rows repeat it."""
    group, tile, offsets, active = gm._plan(
        jnp.asarray([100, 0, 200, 50, 0, 3]), 512, 128)
    active = int(active[0])
    # Rows 0-99 | 100-299 (three row tiles) | 300-349 | 350-352.
    assert active == 6
    visits = list(zip(np.asarray(group)[:active].tolist(),
                      np.asarray(tile)[:active].tolist()))
    assert visits == [(0, 0), (2, 0), (2, 1), (2, 2), (3, 2), (5, 2)]
    assert np.asarray(group)[active:].tolist() == [5] * 3
    assert set(np.asarray(group).tolist()) <= {0, 2, 3, 5}
    assert np.asarray(offsets).tolist() == [0, 100, 100, 300, 350, 350, 353]


def test_grouped_matmul_dispatch():
    x, w = jnp.zeros((12, 16)), jnp.zeros((2, 16, 8))
    with pytest.raises(ValueError, match="cannot take"):
        gm.takes_kernel(x, w, use_pallas=True)      # 12 rows: no tile of 8
    assert not gm.takes_kernel(jnp.zeros((16, 16)), w)  # off the chip
    assert gm.takes_kernel(jnp.zeros((16, 16)), w, use_pallas=True)
    assert gm._tiles(512, 7168, 2048, 2) == (128, 256)
    assert gm._tiles(1024, 2048, 7168, 2) == (256, 1024)
