"""Time the recurrent state's one-token step on this chip, alone: XLA's
form of it (layer ``l`` of the carried ``[L, B, H, P, N]`` leaf read by
index, advanced, a frozen row rewritten with its own bytes: a reduce
fusion and an update fusion, two passes over every row) against
``ops.ssm_state``'s kernel over the same leaf in place, at
``falcon-h1-34b-stage.chat-open``'s grid with 32, 20 and 4 of its 32 slots
advancing, by block size.  Prints one JSON line per (live, path) —
milliseconds per decode step (all layers), the GB of state the path must
move and the rate on them.

Before it times anything it CHECKS the kernel on the chip, where the
interpreter cannot: a row that does not advance comes back bit for bit
(the first rows, rows in the middle, the last ones, all of them), a row
that does matches the ``jnp`` step.  It exits 1 if not.

This is the run ``ops.ssm_state.BLOCK_BYTES`` was set from
(docs/KERNELS.md has its numbers).  Run it through the chip tool; it
refuses to run off a TPU.

    python scripts/state_step_crossover.py [block MB ...]
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np

from cloud_tpu.ops import ssm_state

STEPS = 8  # decode steps a timed call: a chunk
#: falcon-h1-34b-stage.chat-open: layers, slots, heads, P, N, groups
GRID = (6, 32, 32, 128, 256, 2)
LIVE = (32, 20, 4)


def masks():
    """Which slots advance: the counts of :data:`LIVE` scattered over the
    grid, plus the patterns the walk has to get right."""
    slots = GRID[1]
    rng = np.random.default_rng(31)
    out = {}
    for n in LIVE:
        mask = np.zeros(slots, bool)
        mask[rng.permutation(slots)[:n]] = True
        out[f"{n}-of-{slots}"] = mask
    edge = np.zeros(slots, bool)
    edge[5:9] = edge[20] = True       # the first rows and the last do not
    out["first-and-last-frozen"] = edge
    out["none"] = np.zeros(slots, bool)
    return out


def operands(key):
    layers, slots, heads, p, n, groups = GRID
    keys = jax.random.split(key, 5)
    return (jax.random.normal(keys[0], (layers, slots, heads, p, n)),
            jax.random.uniform(keys[1], (slots, heads), minval=0.5),
            jax.random.normal(keys[2], (slots, heads, p)) * 0.1,
            jax.random.normal(keys[3], (slots, groups, n)),
            jax.random.normal(keys[4], (slots, groups, n)))


def xla_step(state, layer, live, keep, dtx, b_mat, c_mat):
    """``models.ssm.ssm_step``'s update as ``_scan_layers`` wraps it off
    the kernel path."""
    rep = state.shape[2] // b_mat.shape[1]
    held = jax.lax.dynamic_index_in_dim(state, layer, keepdims=False)
    new = (keep[..., None, None] * held + dtx[..., None]
           * jnp.repeat(b_mat, rep, axis=1)[:, :, None, :])
    y = jnp.sum(new * jnp.repeat(c_mat, rep, axis=1)[:, :, None, :], axis=-1)
    new = jnp.where(live[:, None, None, None], new, held)
    return jax.lax.dynamic_update_index_in_dim(state, new, layer, 0), y


def kernel_step(state, layer, live, keep, dtx, b_mat, c_mat):
    return ssm_state.state_step(state, layer, live, keep, dtx, b_mat, c_mat)


def chunk_of(step):
    """``STEPS`` decode steps of every layer, the leaf carried and
    donated, each step's ``y`` feeding the next one's ``dt x`` so that
    nothing overlaps and nothing is elided."""
    def steps(state, live, keep, dtx, b_mat, c_mat):
        def one(carry, _):
            def layer(carry, l):
                state, dtx = carry
                state, y = step(state, l, live, keep, dtx, b_mat, c_mat)
                return (state, dtx + 1e-3 * y), None
            return jax.lax.scan(layer, carry, jnp.arange(GRID[0]))[0], None
        return jax.lax.scan(one, (state, dtx), None, length=STEPS)[0]
    return jax.jit(steps, donate_argnums=(0,))


def timed(step, state, live, *rest):
    fn = chunk_of(step)
    state, _ = fn(state, live, *rest)
    state.block_until_ready()
    best = float("inf")
    for _ in range(3):
        start = time.perf_counter()
        state, _ = fn(state, live, *rest)
        state.block_until_ready()
        best = min(best, time.perf_counter() - start)
    return best / STEPS * 1e3, state


def check(state, rest):
    """The kernel against the jnp step, one layer of one step, on this
    chip: frozen rows bit for bit, advancing rows to float32 rounding."""
    ok = True

    @jax.jit
    def compare(before, got, y, want, want_y, live):
        rows = live[:, None, None, None]
        others = jnp.arange(before.shape[0]) != 3
        frozen = (jnp.all(jnp.where(rows, True, got[3] == before[3]))
                  & jnp.all(jnp.where(others[:, None, None, None, None],
                                      got == before, True)))
        moved = jnp.all(jnp.where(
            rows, jnp.abs(got[3] - want[3]) <= 1e-6 + 1e-6 * jnp.abs(want[3]),
            True))
        y_err = jnp.max(jnp.where(live[:, None, None],
                                  jnp.abs(y - want_y), 0.0))
        y_zero = jnp.all(jnp.where(live[:, None, None], True, y == 0.0))
        return frozen, moved, y_err, y_zero

    for name, mask in masks().items():
        live = jnp.asarray(mask)
        want, want_y = jax.jit(xla_step)(state, 3, live, *rest)
        got, y = jax.jit(kernel_step, donate_argnums=(0,))(
            jnp.copy(state), 3, live, *rest)
        frozen, moved, y_err, y_zero = (
            x.item() for x in compare(state, got, y, want, want_y, live))
        y_ok = y_err < 1e-3 and y_zero
        print(json.dumps({"check": name, "frozen_bit_equal": frozen,
                          "advanced_match": moved, "y_max_err": y_err,
                          "y_ok": bool(y_ok)}), flush=True)
        ok = ok and frozen and moved and y_ok
    return ok


def main(blocks_mb):
    if jax.default_backend() != "tpu":
        raise SystemExit("state_step_crossover.py times a TPU; none here")
    state, *rest = operands(jax.random.PRNGKey(31))
    if not check(state, rest):
        raise SystemExit(1)
    layers, slots, heads, p, n, _ = GRID
    row_gb = 2 * layers * heads * p * n * 4 / 1e9  # read once, written once
    for name, mask in list(masks().items())[:len(LIVE)]:
        live = jnp.asarray(mask)
        paths = [("xla", None, xla_step)]
        for mb in blocks_mb:
            paths.append(("kernel", mb, kernel_step))
        for path, mb, step in paths:
            if mb is not None:
                ssm_state.BLOCK_BYTES = int(mb * (1 << 20))
            ms, state = timed(step, state, live, *rest)
            must = int(mask.sum()) * row_gb
            print(json.dumps({
                "live": name, "path": path, "block_mb": mb,
                "ms_per_step": round(ms, 4), "gb_live_state": round(must, 4),
                "gb_per_s": round(must / ms * 1e3, 1),
            }), flush=True)


if __name__ == "__main__":
    main([float(a) for a in sys.argv[1:]]
         or [ssm_state.BLOCK_BYTES / (1 << 20)])
