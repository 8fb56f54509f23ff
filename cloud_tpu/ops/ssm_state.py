"""The recurrent state's one-token step as a Pallas TPU kernel.

A Mamba-2 mixer's decode step advances each row's state by one token::

    h' = keep * h + outer(dt x, B)        h [P, N] per head, keep = exp(dt A)
    y  = h' C

The state is by far the largest thing the step touches (25 MB a slot a
layer at Falcon-H1's widths) and every element of it is read once and
written once, so the step is bound by the state's bytes.  XLA splits it
into a reduce fusion (reads ``h`` for ``y``) and an update fusion (reads
``h`` again, writes ``h'``) and, in the slot grid, rewrites a slot that
does not decode with its own bytes.  This kernel passes over the state
ONCE, and only over the rows that advance.

It takes the carried ``[L, B, H, P, N]`` float32 leaf WHOLE, aliased to
its output, with the layer index scalar-prefetched (the way
``ops.paged_attention`` takes the K/V leaves): no layer is sliced out and
none is written back.  The grid walks ``(row, head block)``; a block is
``Hb`` heads of one row, loaded, advanced, reduced against ``C`` over the
lanes and stored to the same block.  A row that does not advance
(``live`` false) is NEITHER FETCHED NOR WRITTEN: Pallas writes an output
block back whenever the grid leaves it, touched by the body or not, so
such a row is never given a block of its own.  Its grid steps name the
block the step before left resident (:func:`_walk`) and skip the body, so
they cost a grid step and no DMA, and the row keeps its bytes because
nothing touches them.  Its ``y`` rows are zeros.

Everything else of the mixer's step (projections, convolution, gate,
norm) stays ``jnp`` in ``models.ssm``, before and after this call, and
``models.ssm.ssm_step`` stays the reference this kernel is tested
against.  Dispatch follows the house playbook (:func:`takes_kernel`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from cloud_tpu.ops import dispatch as dispatch_lib

#: A block's bytes at most: ``Hb`` heads of one row.  Four such buffers
#: are in flight (two in, two out); chosen on the chip among 1, 2 and
#: 4 MB at Falcon-H1's grid (docs/KERNELS.md).
BLOCK_BYTES = 2 << 20

#: Diagnostic counter: bumped every time the kernel is traced, so a test
#: can tell the kernel ran and not the jnp step.
KERNEL_TRACE_COUNT = 0


def _heads_per_block(heads: int, per_group: int, head_bytes: int
                     ) -> Optional[int]:
    """How many heads of a row make a block: the most that fit
    :data:`BLOCK_BYTES`, divide the row's, cover whole groups or divide
    one (a block's heads find their B and C in one small block), and
    leave the [Hb, P] vectors that ride along in whole sublane tiles;
    None if no count does."""
    for hb in range(min(heads, max(1, BLOCK_BYTES // head_bytes)), 0, -1):
        if (heads % hb == 0 and (hb % 8 == 0 or hb == heads)
                and (hb % per_group == 0 or per_group % hb == 0)):
            return hb
    return None


def _kernel_eligible(state, groups: int) -> bool:
    if state.ndim != 5 or state.dtype != jnp.float32:
        return False
    heads, p, n = state.shape[2:]
    return (p % 8 == 0 and n % 128 == 0 and heads % groups == 0
            and _heads_per_block(heads, heads // groups, p * n * 4)
            is not None)


def takes_kernel(state, groups: int, use_pallas: Optional[bool] = None
                 ) -> bool:
    """Whether the step over the stacked ``state`` leaf goes through the
    kernel: ``use_pallas`` decides when given (and raises on a leaf the
    kernel cannot take), else a TPU — or the interpreter armed by
    ``dispatch.force_interpret`` — and a float32 leaf whose ``(P, N)``
    tile as ``(8k, 128k)``.  One spelling for the model programs and for
    whoever counts what a step fetches (the serving engine)."""
    eligible = _kernel_eligible(state, groups)
    if use_pallas and not eligible:
        raise ValueError(
            "ssm state step (use_pallas=True): the kernel cannot take a "
            f"{state.dtype} state {tuple(state.shape)} in {groups} groups "
            "(needs a float32 [L, B, H, P, N] leaf, P a multiple of 8, N "
            "of 128, and heads a group in whole blocks of 8)"
        )
    if use_pallas is None:
        return eligible and (jax.default_backend() == "tpu"
                             or dispatch_lib.force_interpret())
    return use_pallas


def _walk(live, blocks: int):
    """What each grid step ``(row, block)`` fetches and whether it runs,
    as three flat [B * blocks] int32 arrays ``(rows, blks, run)``.

    A live row walks its own blocks.  A row that does not advance names,
    at every step, the block the step before left resident: the last
    block of the nearest live row above it or, with none above, the first
    block of the nearest one below (which that row then finds fetched).
    The pipeline moves nothing while a block index repeats, in either
    direction.  ``run`` is 1 for a step that advances its block and 0 for
    one that skips; with no live row at all every step names block 0 of
    row 0 and the first is 2: it hands the block through unchanged, since
    the pipeline writes back what the grid leaves."""
    b = live.shape[0]
    idx = jnp.arange(b, dtype=jnp.int32)
    above = jax.lax.cummax(jnp.where(live, idx, -1))
    below = jax.lax.cummin(jnp.where(live, idx, b), reverse=True)
    rows = jnp.where(above >= 0, above, jnp.where(below < b, below, 0))
    pinned = jnp.where(above >= 0, blocks - 1, 0)
    own = jnp.arange(blocks, dtype=jnp.int32)[None, :]
    blks = jnp.where(live[:, None], own, pinned[:, None])
    run = jnp.broadcast_to(live[:, None], (b, blocks)).astype(jnp.int32)
    run = run.at[0, 0].set(jnp.where(jnp.any(live), run[0, 0], 2))
    rows = jnp.broadcast_to(rows[:, None], (b, blocks))
    return (rows.reshape(-1).astype(jnp.int32),
            blks.reshape(-1).astype(jnp.int32), run.reshape(-1))


def _state_step_kernel(lyr_ref, rows_ref, blks_ref, run_ref, keep_ref,
                       dtx_ref, b_ref, c_ref, h_ref, o_ref, y_ref, *,
                       hb, heads):
    """One (row, head block) grid cell.  ``h_ref`` / ``o_ref`` [Hb, P, N]
    are the same block of the aliased leaf; ``dtx_ref`` [Hb, P] holds
    dt x, ``b_ref`` / ``c_ref`` [Gb, 1, N] the B and C of the groups the
    block's heads belong to, ``keep_ref`` (SMEM, [B * H]) the decays;
    ``y_ref`` [Hb, P]."""
    row, blk = pl.program_id(0), pl.program_id(1)
    run = run_ref[row * pl.num_programs(1) + blk]

    @pl.when(run == 1)
    def _advance():
        # P rides the sublanes of a state tile, so dt x has to meet it as
        # a column: one small transpose a block.
        dtx_t = dtx_ref[...].T                                   # [P, Hb]
        cols = []
        for i in range(hb):
            g = i * b_ref.shape[0] // hb
            keep = keep_ref[row * heads + blk * hb + i]
            new = keep * h_ref[i] + dtx_t[:, i:i + 1] * b_ref[g]  # [P, N]
            o_ref[i] = new
            cols.append(jnp.sum(new * c_ref[g], axis=-1, keepdims=True))
        y_ref[...] = jnp.concatenate(cols, axis=1).T              # [Hb, P]

    @pl.when(run != 1)
    def _skip():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(run == 2)
    def _hand_through():
        o_ref[...] = h_ref[...]


def _state_step_pallas(state, layer, live, keep, dtx, b_mat, c_mat, *,
                       interpret):
    global KERNEL_TRACE_COUNT
    KERNEL_TRACE_COUNT += 1
    from jax.experimental.pallas import tpu as pltpu

    _, b, heads, p, n = state.shape
    groups = b_mat.shape[1]
    per_group = heads // groups
    hb = _heads_per_block(heads, per_group, p * n * 4)
    blocks, gb = heads // hb, max(1, hb // per_group)
    rows, blks, run = _walk(live, blocks)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    def at(step_row, step_blk, rows_, blks_):
        step = step_row * blocks + step_blk
        return rows_[step], blks_[step]

    def state_map(r, j, lyr, rows_, blks_, run_, keep_):
        row, blk = at(r, j, rows_, blks_)
        return (lyr[0], row, blk, 0, 0)

    def vector_map(r, j, lyr, rows_, blks_, run_, keep_):
        return at(r, j, rows_, blks_) + (0,)

    def group_map(r, j, lyr, rows_, blks_, run_, keep_):
        row, blk = at(r, j, rows_, blks_)
        return (row, blk * hb // (per_group * gb), 0, 0)

    def y_map(r, j, *_):
        return (r, j, 0)

    state_spec = pl.BlockSpec((None, None, hb, p, n), state_map)
    group_spec = pl.BlockSpec((None, gb, 1, n), group_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, blocks),
        in_specs=[pl.BlockSpec((None, hb, p), vector_map), group_spec,
                  group_spec, state_spec],
        out_specs=[state_spec, pl.BlockSpec((None, hb, p), y_map)],
    )
    new_state, y = pl.pallas_call(
        functools.partial(_state_step_kernel, hb=hb, heads=heads),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, heads, p), jnp.float32)],
        # The leaf is operand 8 (after the five prefetched scalars and
        # dt x, B, C) and output 0: updated in place.
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=(16 << 20) + 8 * hb * p * n * 4,
        ),
        interpret=interpret,
        name="ssm_state_step",
    )(layer, rows, blks, run, keep.reshape(-1), dtx,
      b_mat[:, :, None, :], c_mat[:, :, None, :], state)
    return new_state, y


def state_step(state, layer, live, keep, dtx, b_mat, c_mat):
    """Advance layer ``layer`` of the stacked leaf ``state``
    [L, B, H, P, N] float32 by one token, in place, for the rows where
    ``live`` [B] is true.

    ``keep`` [B, H] is each head's decay ``exp(dt A)``, ``dtx`` [B, H, P]
    its ``dt x``, ``b_mat`` / ``c_mat`` [B, G, N] the groups' B and C, all
    float32.  Returns the leaf, donated to the call and updated where a
    row advanced (every other byte of it untouched), and ``y`` [B, H, P]
    = ``h' C``, zeros for a row that did not advance.  Whether to call
    this at all is :func:`takes_kernel`'s to say; off a TPU the kernel
    can only be interpreted."""
    return _state_step_pallas(
        state, layer, live, keep.astype(jnp.float32),
        dtx.astype(jnp.float32), b_mat.astype(jnp.float32),
        c_mat.astype(jnp.float32),
        interpret=(dispatch_lib.force_interpret()
                   or jax.default_backend() != "tpu"))
