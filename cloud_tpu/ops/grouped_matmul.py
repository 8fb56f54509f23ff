"""Grouped matrix product as a Pallas TPU kernel, with ``lax.ragged_dot``
as its jnp route: rows sorted by group, each group against its own
matrix — the dropless expert layer's three products (``models/moe.py``).

``x`` [M, K] holds the groups' rows one after another (``group_sizes``
[E], their sum at most M); row ``r`` of group ``g`` is multiplied by
``w[g]`` [K, N].  The grid walks ``(column tile, visit)``: a visit is one
(group, row tile) pair that holds rows, listed in row order on the host
side of the call (:func:`_plan`, scalar-prefetched), so a group without
rows is never visited and ITS MATRIX IS NEVER FETCHED — a decode step of
64 tokens touches about 9 of 12 held experts and reads those alone.  A
row tile that two groups share is visited once for each, and each visit
stores only its own group's rows.  ``K`` is not tiled: a visit is one MXU
product of the row tile against a [K, tn] block, and the block is sized
to about 4 MB so that its DMA, not the grid step, is what a
bandwidth-bound call waits for.

Rows past the groups' sum are NOT defined where no visit reaches their
tile (zeros where one does): the caller selects them away.

``w`` may be the STACKED matrices of every layer, [L, E, K, N], with
``layer`` the (traced) layer to use: the layer rides in as a
scalar-prefetch operand and the index maps address ``(layer, group, ...)``,
so nothing is sliced out before the call.  (A layer's [E, K, N] handed in
as a scan's slice IS copied first, whole, at every call: 1.07 ms for each
of a layer's three 352 MB stacks on a v5e, more than a decode step's whole
read of the experts it touches.)
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from cloud_tpu.ops import dispatch as dispatch_lib

#: Bytes of one [K, tn] block of a group's matrix (tn in whole lane rows).
BLOCK_BYTES = 4 << 20

#: Rows a tile: a row-count up to SMALL_ROWS (a decode step's
#: assignments) runs at 128, more (a prompt's) at 256, which halves the
#: tiles that two groups share and with them the blocks fetched twice.
#: The call is NAMED by the same split, after what sends that many rows
#: (``grouped_matmul_decode`` / ``grouped_matmul_prefill``), so that a
#: device trace tells the two apart whatever their tiles are tuned to.
SMALL_ROWS = 512

KERNEL_TRACE_COUNT = 0


def _reference(x, w, group_sizes, layer=None):
    if w.ndim == 4:
        w = jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
    return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)


def _plan(group_sizes, m: int, tm: int):
    """The visits, in row order: ``(group, row tile)`` of each, the
    groups' row offsets [E + 1] and the count of visits that hold rows.
    Visits past that count repeat the last one (no fetch, no work)."""
    e = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    visit_end = jnp.cumsum(tiles)
    active = visit_end[-1]
    visit = jnp.arange(m // tm + e - 1, dtype=jnp.int32)
    visit = jnp.minimum(visit, jnp.maximum(active - 1, 0))
    group = jnp.minimum(
        jnp.searchsorted(visit_end, visit, side="right"), e - 1
    ).astype(jnp.int32)
    tile = first_tile[group] + visit - (visit_end[group] - tiles[group])
    tile = jnp.clip(tile, 0, m // tm - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return group, tile, offsets, jnp.reshape(active, (1,))


def _kernel(group_ref, tile_ref, offsets_ref, active_ref, layer_ref, x_ref,
            w_ref, o_ref, *, tm):
    v = pl.program_id(1)

    @pl.when(v < active_ref[0])
    def _visit():
        g, t = group_ref[v], tile_ref[v]
        fresh = (v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != t)
        acc = jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        row = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        kept = jnp.where(fresh, jnp.zeros_like(o_ref), o_ref[...])
        o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), kept)


def _tiles(m: int, k: int, n: int, itemsize: int):
    """(tm, tn), or None where the kernel cannot tile the shapes."""
    tm = 128 if m <= SMALL_ROWS else 256
    tm = min(tm, m)
    if m % tm or tm % 8:
        return None
    if n % 128:
        return (tm, n) if k * n * itemsize <= 2 * BLOCK_BYTES else None
    tn = max(128, BLOCK_BYTES // (k * itemsize) // 128 * 128)
    while n % tn:
        tn -= 128
    return tm, tn


def _pallas(x, w, group_sizes, layer, tm, tn, *, interpret):
    """``w`` [L, E, K, N], ``layer`` the layer of it to use."""
    global KERNEL_TRACE_COUNT
    KERNEL_TRACE_COUNT += 1
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    _, e, _, n = w.shape
    group, tile, offsets, active = _plan(group_sizes, m, tm)

    def x_map(n_, v_, group_, tile_, *_):
        return (tile_[v_], 0)

    def w_map(n_, v_, group_, tile_, offsets_, active_, layer_):
        return (layer_[0], group_[v_], 0, n_)

    def o_map(n_, v_, group_, tile_, *_):
        return (tile_[v_], n_)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n // tn, m // tm + e - 1),
        in_specs=[pl.BlockSpec((tm, k), x_map),
                  pl.BlockSpec((None, None, k, tn), w_map)],
        out_specs=pl.BlockSpec((tm, tn), o_map),
    )
    itemsize = x.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # The row tile and the matrix block, double-buffered, the
            # float32 product and the output tile.
            vmem_limit_bytes=min(
                100 << 20, (8 << 20) + 2 * itemsize * k * (tm + tn)
                + 12 * tm * tn)),
        interpret=interpret,
        name="grouped_matmul_" + ("decode" if m <= SMALL_ROWS
                                  else "prefill"),
    )(group, tile, offsets, active,
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), x, w)


def takes_kernel(x, w, use_pallas: Optional[bool] = None) -> bool:
    """The one dispatch rule: a TPU (or the armed interpreter) and shapes
    the kernel tiles; ``use_pallas=True`` raises elsewhere."""
    tiles = (x.ndim == 2 and w.ndim in (3, 4)
             and x.shape[1] == w.shape[-2] and x.dtype == w.dtype
             and _tiles(x.shape[0], x.shape[1], w.shape[-1],
                        x.dtype.itemsize))
    if use_pallas and not tiles:
        raise ValueError(
            "grouped_matmul(use_pallas=True): the kernel cannot take "
            f"x{tuple(x.shape)} {x.dtype} against w{tuple(w.shape)} "
            f"{w.dtype} (needs x [M, K] and w [E, K, N] or [L, E, K, N] "
            "of one type, M a multiple of its row tile)")
    if use_pallas is None:
        use_pallas = bool(tiles) and (jax.default_backend() == "tpu"
                                      or dispatch_lib.force_interpret())
    return bool(use_pallas)


def grouped_matmul(x, w, group_sizes, *, layer=None,
                   use_pallas: Optional[bool] = None,
                   interpret: bool = False):
    """``x`` [M, K] (rows sorted by group) against ``w`` [E, K, N] — or
    the stacked [L, E, K, N] with ``layer`` the (traced) layer to use,
    read in place: [M, N] in ``x``'s type, float32 accumulation.  Rows
    past the groups' sum come back undefined."""
    if not takes_kernel(x, w, use_pallas):
        return _reference(x, w, group_sizes, layer)
    tm, tn = _tiles(x.shape[0], x.shape[1], w.shape[-1], x.dtype.itemsize)
    if w.ndim == 3:
        w, layer = w[None], 0  # a stack of one: a reshape, not a copy
    interpret = (interpret or dispatch_lib.force_interpret()
                 or jax.default_backend() != "tpu")
    return _pallas(x, w, group_sizes, layer, tm, tn, interpret=interpret)
