"""Time the flash forward kernel on this chip, alone, at the shapes of the
two cells whose inserts run it: ``kimi-k2-ep32-stage.agent-saturated``
(64 heads, queries and keys of 192, values of 128, widths 1,536-4,096) and
``baichuan-7b-l16.docs-saturated`` (32 heads of 128, widths 1,024-2,048),
causal, bfloat16, one row, at the full width and at a ragged length
inside it.  Three columns a shape:

- ``parent``: the kernel of the commit before this schedule, imported
  from ``_parent/`` (``git archive <parent> | tar -x -C _parent``; the
  column is left out where that directory is missing), called as its
  call sites called it: the prompt's ``mask=``, the values zero-padded to
  the keys' head size;
- ``kernel``: ``ops.flash_attention``'s forward under the schedule its
  dispatch gives the call (``_schedule``), with ``lengths``;
- ``reference`` (at 1,024 rows only, where the auto-dispatch threshold
  sits): ``_reference`` with the key-side mask, XLA's fused attention.

Prints one JSON line a (shape, length, column): milliseconds a call and
the share of the compute roofline of the prompt's REAL causal pairs
(``L (L + 1) / 2`` pairs x heads x (D + Dv) x 2 operations over the
v5e's 197 TFLOP/s), and writes the table to
``chiprun_out/flash_crossover.md``.  Before it times anything it checks
the kernel against the reference on the chip at 1,024 rows (real rows
agree, rows past the length are zeros) and exits 1 if not.

``--sweep`` times the kernel under other schedules too (block_q x
block_k x tile_q x tiles fused): the run ``MAX_BLOCK_Q`` / ``MAX_BLOCK_K`` /
``BLOCK_BYTES`` / ``TILE_Q_ROWS`` / ``TILES_FUSED`` were set from (docs/KERNELS.md has its
numbers).  Run it through the chip tool; it refuses to run off a TPU.

    python scripts/flash_crossover.py [--sweep] [k2|docs ...]
"""

import importlib.util
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np

import cloud_tpu.ops  # noqa: F401  (binds the kernel modules)

fa = sys.modules["cloud_tpu.ops.flash_attention"]

PEAK_FLOPS = 197e12  # TPU v5e, bf16 (Google Cloud, "TPU v5e")
CALLS = 20  # timed calls a reading; the median of REPEATS readings
REPEATS = 5

#: heads, query/key head, value head, widths (``generation.prefill_widths``
#: of the cell's buckets)
SHAPES = {
    "k2": (64, 192, 128, (1536, 2048, 2560, 3072, 3584, 4096)),
    "docs": (32, 128, 128, (1024, 1536, 2048)),
}
#: A ragged length a width: the middle of the 512-row tile the width ends.
RAGGED = 256

#: (block_q, block_k, tile_q, fuse) tried by ``--sweep``.
SWEEP = [(1024, 512, 256, 1), (1024, 512, 256, 2), (1024, 512, 256, 4),
         (1024, 512, 512, 1), (1024, 512, 512, 2), (1024, 512, 128, 4),
         (1024, 1024, 256, 2), (1024, 1024, 256, 4), (512, 512, 256, 2),
         (512, 512, 256, 1), (512, 1024, 256, 2), (2048, 512, 256, 2),
         (256, 512, 256, 1)]


def parent_module():
    path = os.path.join(REPO, "_parent", "cloud_tpu", "ops",
                        "flash_attention.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location("parent_flash", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def operands(t, heads, d, dv, seed=0):
    """[1, H, T, D] as the kernels take them (the dispatch's transposes
    are XLA's, and not timed here)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (1, heads, t, d), jnp.bfloat16),
            jax.random.normal(keys[1], (1, heads, t, d), jnp.bfloat16),
            jax.random.normal(keys[2], (1, heads, t, dv), jnp.bfloat16))


def timed(fn, *args):
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    readings = []
    for _ in range(REPEATS):
        start = time.perf_counter()
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
        readings.append((time.perf_counter() - start) / CALLS)
    return float(np.median(readings)) * 1e3


def kernel_call(schedule):
    """The kernel alone, its operands laid out as it takes them (queries
    and values with the sequence in the lanes: the transposes are XLA's,
    folded into the ones the dispatch makes anyway)."""
    def call(q, k, v, lengths):
        return fa._fwd_call(q, k, v, None, lengths, causal=True,
                            schedule=schedule, interpret=False)[0]
    call.lanes = True
    return call


def dispatched_call(schedule):
    """The kernel from and to [B, H, T, D], its own transposes timed."""
    def call(q, k, v, lengths):
        return fa._fwd_pallas(q, k, v, None, lengths, causal=True,
                              schedule=schedule, interpret=False)[0]
    return call


def parent_call(parent, t, d, dv):
    block_q = parent._fit_block(t, parent.DEFAULT_BLOCK_Q)
    block_k = parent._fit_block(t, parent.DEFAULT_BLOCK_K, lane_aligned=True)

    def call(q, k, v, lengths):
        mask = (jnp.arange(t)[None, :] < lengths[:, None]).astype(jnp.int32)
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, d - dv),))  # mla.py's pad
        return parent._fwd_pallas(q, k, v, mask, causal=True, block_q=block_q,
                                  block_k=block_k, interpret=False)[0]
    return call


def reference_call(q, k, v, lengths):
    t = q.shape[2]
    mask = jnp.arange(t)[None, :] < lengths[:, None]
    return fa._reference(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                         v.transpose(0, 2, 1, 3), causal=True, mask=mask)


def check_on_chip():
    """The kernel against the reference at 1,024 rows, both head shapes,
    whole and ragged: real rows agree, rows past the length are zeros."""
    ok = True
    for name, (heads, d, dv, _) in SHAPES.items():
        q, k, v = operands(1024, heads, d, dv, seed=7)
        schedule = fa._schedule(1024, d, dv, 2)
        for length in (1024, 1024 - RAGGED, 513, 1):
            lengths = jnp.array([length], jnp.int32)
            out, lse = jax.jit(lambda q, k, v, n: fa._fwd_pallas(
                q, k, v, None, n, causal=True, schedule=schedule,
                interpret=False))(q, k, v, lengths)
            ref = jax.jit(reference_call)(q, k, v, lengths)
            out = np.asarray(out.transpose(0, 2, 1, 3), np.float32)
            ref = np.asarray(ref, np.float32)
            gap = float(np.abs(out[:, :length] - ref[:, :length]).max())
            zeros = not out[:, length:].any()
            finite = bool(np.isfinite(np.asarray(lse)).all())
            good = gap < 3e-2 and zeros and finite
            ok = ok and good
            print(json.dumps({"check": name, "length": length,
                              "max_gap": gap, "past_length_zero": zeros,
                              "lse_finite": finite, "ok": good}), flush=True)
    return ok


def main(argv):
    if jax.default_backend() != "tpu":
        print("no TPU: this script times the chip and runs nowhere else",
              file=sys.stderr)
        return 2
    sweep = "--sweep" in argv
    names = [a for a in argv if a in SHAPES] or list(SHAPES)
    if not check_on_chip():
        return 1
    parent = parent_module()
    rows = []
    for name in names:
        heads, d, dv, widths = SHAPES[name]
        for t in widths:
            q, k, v = operands(t, heads, d, dv)
            qt, vt = q.swapaxes(2, 3), v.swapaxes(2, 3)
            schedule = fa._schedule(t, d, dv, 2)
            columns = {"kernel": kernel_call(schedule),
                       "kernel+transposes": dispatched_call(schedule)}
            if parent is not None:
                columns["parent"] = parent_call(parent, t, d, dv)
            if t == 1024:
                columns["reference"] = reference_call
            if sweep:
                for trial in SWEEP:
                    if t % trial[0] == 0 and t % trial[1] == 0:
                        columns["x".join(map(str, trial))] = kernel_call(
                            fa._Schedule(*trial))
            for length in (t, t - RAGGED):
                lengths = jnp.array([length], jnp.int32)
                flops = length * (length + 1) // 2 * heads * (d + dv) * 2
                for column, call in columns.items():
                    lanes = getattr(call, "lanes", False)
                    try:
                        ms = timed(call, qt if lanes else q, k,
                                   vt if lanes else v, lengths)
                    except Exception as e:  # noqa: BLE001 — a schedule Mosaic refuses
                        print(json.dumps({"shape": name, "t": t,
                                          "column": column,
                                          "error": str(e)[:200]}), flush=True)
                        continue
                    row = {
                        "shape": name, "t": t, "length": length,
                        "column": column, "ms": round(ms, 4),
                        "roofline_pct": round(
                            100 * flops / PEAK_FLOPS / (ms / 1e3), 2),
                    }
                    if column == "kernel":
                        row["schedule"] = list(schedule)
                        row["tiles_run_pct"] = round(
                            100 * fa._tiles_run(t, schedule, length)
                            / fa._tiles_run(t, schedule, None), 1)
                    rows.append(row)
                    print(json.dumps(row), flush=True)
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "flash_crossover.md"), "w") as f:
        f.write("| shape | T | length | column | ms a call | % of the "
                "compute roofline |\n|---|---|---|---|---|---|\n")
        for r in rows:
            f.write(f"| {r['shape']} | {r['t']} | {r['length']} | "
                    f"{r['column']} | {r['ms']} | {r['roofline_pct']} |\n")
    with open(os.path.join(out_dir, "flash_crossover.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
