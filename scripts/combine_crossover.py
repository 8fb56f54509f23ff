"""Time one dropless expert layer on this chip, alone, at the widths of
``kimi-k2-ep32-stage.agent-saturated`` (7,168 wide, 12 of 384 experts of
2,048 held, top-8, a shared expert), by the number of tokens it is
called with: a decode step's 64, an insert's widths, and 8,192 and 16,384
(what ``transformer.apply`` may hand it).  Two columns a size:

- ``parent``: ``dropless_mlp_apply`` of the commit before the placement
  product (rows gathered, weighted in float32 and put back with
  ``out.at[tokens].add``), imported from ``_parent/`` (``git archive
  <parent> | tar -x -C _parent``; left out where that is missing);
- ``change``: this tree's.

Prints one JSON line a (size, column): milliseconds a call by the host's
clock, its device time by program scope (``moe_route`` / ``moe_experts`` /
``moe_combine`` / ``mlp``, from a trace of five calls), the assignments
that landed, and how far the column's output lies from the parent's (the
two round the same numbers in another order).  Writes the table to
``chiprun_out/combine_crossover.md``.  docs/KERNELS.md's numbers for the
placement product, the large-n one among them, are this run's.  Run it
through the chip tool; it refuses to run off a TPU.

    python scripts/combine_crossover.py [tokens ...]
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

from benchmarks.adapters import serve_latent_moe
from benchmarks.harness import manifest, xplane, xplane_scopes
from benchmarks.references import kimi_k2
from cloud_tpu.models import layers, moe

CELL = "kimi-k2-ep32-stage.agent-saturated"
TOKENS = (64, 1536, 2560, 4096, 8192, 16384)
SLOTS = 64  # up to this many tokens are a decode step's: [n, 1, D]
CALLS, REPEATS, TRACED_CALLS = 10, 3, 5
SCOPES = ("moe_route", "moe_experts", "moe_combine", "mlp")


def parent_module():
    path = os.path.join(REPO, "_parent", "cloud_tpu", "models", "moe.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location("parent_moe", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module  # its dataclass looks itself up there
    spec.loader.exec_module(module)
    return module


def layer_call(module, cfg, name):
    def call(params, x, live):
        return module.dropless_mlp_apply(params, x, cfg, live=live, layer=0)
    call.__name__ = name  # the trace's module is ``jit_<name>``
    return jax.jit(call)


def wall_ms(fn, *args):
    readings = []
    for _ in range(REPEATS):
        start = time.perf_counter()
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
        readings.append((time.perf_counter() - start) / CALLS)
    return float(np.median(readings)) * 1e3


def device_ms_by_scope(calls):
    """One trace of ``TRACED_CALLS`` executions of every call: a
    program's device time an execution, by scope."""
    logdir = os.path.join(xplane_scopes.TRACE_DIR, "combine_crossover")
    xplane.start_trace(logdir)
    for fn, args in calls.values():
        for _ in range(TRACED_CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
    xplane.stop_trace()
    trace = xplane_scopes.load(xplane.find_xplane(logdir))
    ends = [(op[1], op[1] + op[2]) for d in trace["devices"]
            for op in d["ops"]]
    trace["window"] = [min(e[0] for e in ends), max(e[1] for e in ends)]
    tables = xplane_scopes.scope_tables(trace, layers.SCOPES)
    out = {}
    for name in calls:
        table = xplane_scopes.merged(tables, f"^jit_{name}$")
        runs = max(table["executions"], 1e-9)
        out[name] = {scope: round(table["seconds"].get(scope, 0.0) / runs
                                  * 1e3, 4) for scope in SCOPES}
        out[name]["module"] = round(table["module_seconds"] / runs * 1e3, 4)
    return out


def main(argv):
    tokens = [int(v) for v in argv] or TOKENS
    cell = manifest.Cell(CELL, root=REPO)
    cfg = serve_latent_moe.model_config(cell.config, cell.traffic).moe
    params = kimi_k2.layer_params(jax.random.PRNGKey(38), cell.config,
                                  jnp.bfloat16, False)["mlp"]
    # As the serving programs hand them over: every layer's, stacked.
    params = dict(params, **{name: params[name][None]
                             for name in moe.EXPERT_LEAVES})
    columns = {"parent": parent_module(), "change": moe}
    rows, calls = [], {}
    for n in tokens:
        # Every slot decoding; a prompt a sixteenth short of its width.
        shape, real = ((n, 1), n) if n <= SLOTS else ((1, n), n - n // 16)
        x = jax.random.normal(jax.random.PRNGKey(n),
                              shape + (cell.config["hidden_size"],),
                              jnp.bfloat16)
        live = (jnp.arange(n).reshape(shape) < real).astype(jnp.int32)
        want = None
        for column, module in columns.items():
            if module is None:
                continue
            fn = layer_call(module, cfg, f"{column}_{n}")
            out, routing = jax.block_until_ready(fn(params, x, live))
            out = np.asarray(out.astype(jnp.float32))
            want = out if want is None else want
            row = {"tokens": n, "column": column,
                   "wall_ms": round(wall_ms(fn, params, x, live), 4),
                   "landed": int(routing[1]),
                   "finite": bool(np.isfinite(out).all()),
                   "mean_abs": float(np.abs(out).mean()),
                   "mean_abs_from_parent": float(np.abs(out - want).mean())}
            rows.append(row)
            calls[f"{column}_{n}"] = (fn, (params, x, live))
    scopes = device_ms_by_scope(calls)
    ok = True
    for row in rows:
        row["device_ms"] = scopes[f"{row['column']}_{row['tokens']}"]
        # Two roundings to 8 bits in another order: a few 1e-4 of the
        # output's size in the mean; a misplaced row would read 1e-1.
        ok &= row["finite"] and (row["mean_abs_from_parent"]
                                 < 2e-3 * row["mean_abs"])
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "combine_crossover.md"),
              "w") as f:
        f.write("| tokens | column | landed | wall ms | module | "
                + " | ".join(SCOPES) + " |\n|" + "---|" * (5 + len(SCOPES))
                + "\n")
        for row in rows:
            dev = row["device_ms"]
            f.write(f"| {row['tokens']} | {row['column']} | {row['landed']} "
                    f"| {row['wall_ms']} | {dev['module']} | "
                    + " | ".join(str(dev[s]) for s in SCOPES) + " |\n")
    print(json.dumps({"ok": bool(ok)}))
    return 0 if ok else 1


if __name__ == "__main__":
    if jax.devices()[0].platform != "tpu":
        sys.exit("combine_crossover.py times the chip: run it through the "
                 "chip tool")
    sys.exit(main(sys.argv[1:]))
