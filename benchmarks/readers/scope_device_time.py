"""Device seconds of the programs whose module events match ``module``
that went to the program scopes ``scopes`` (``cloud_tpu.models.layers``'s
list, with ``control`` and ``unscoped``: ``harness.xplane_scopes``), per
unit of ``per`` — a number of ``outcome.work``, or ``count`` for the
programs' own number of executions — times ``scale``.

A reader is handed no ``Run``: it reads the trace where ``run.py`` wrote
it, once a process, and prints one line for each program that took a
hundredth of the window or more,

    scopes <program>: <scope>=<ms an execution> ... | sum .. of module ..

with what its largest ``unscoped`` operations are.  It returns nothing, and
says why, where the program has no scopes (a parent commit) and where over
half of the matched programs' device time is ``unscoped``: the compile
cache's key leaves names out, so an executable compiled before the scopes
existed is served to a tree that has them, without them.
"""

import functools

from benchmarks.harness import xplane, xplane_scopes

#: The smallest share of the traced window for which a program gets a line.
PRINTED_SHARE = 0.01

_REFUSED = set()


def _program_scopes():
    from cloud_tpu.models import layers

    return getattr(layers, "SCOPES", None)


def _describe(program, table, scopes):
    seconds, runs = table["seconds"], table["executions"]
    per_run = 1e3 / runs
    order = [s for s in (*scopes, xplane_scopes.CONTROL,
                         xplane_scopes.UNSCOPED) if s in seconds]
    print(f"scopes {program}: "
          + " ".join(f"{s}={seconds[s] * per_run:.4f}" for s in order)
          + f" | sum {sum(seconds.values()) * per_run:.4f} of module "
          f"{table['module_seconds'] * per_run:.4f} ms an execution, "
          f"{runs:.2f} executions", flush=True)
    largest = sorted(table["unscoped_ops"].items(),
                     key=lambda kv: -kv[1])[:8]
    if largest:
        print(f"scopes {program}: unscoped holds " + ", ".join(
            f"{name}={s * per_run:.4f}" for name, s in largest), flush=True)


@functools.lru_cache(maxsize=None)
def _tables():
    """``xplane_scopes.scope_tables`` of the run's trace, once a process
    (None: nothing to read, and why was printed)."""
    scopes = _program_scopes()
    if scopes is None:
        print("scopes: the program has no scopes (no "
              "cloud_tpu.models.layers.SCOPES)", flush=True)
        return None
    trace = xplane_scopes.traced()
    if trace is None or not trace["devices"]:
        return None
    tables = xplane_scopes.scope_tables(trace, scopes)
    least = PRINTED_SHARE * xplane.window_seconds(trace)
    for program, table in sorted(tables.items()):
        if program and table["executions"] and (
                table["module_seconds"] >= least):
            _describe(program, table, scopes)
    return tables


def read(args, outcome, peaks):
    if outcome.trace is None:
        return None
    tables = _tables()
    if tables is None:
        return None
    table = xplane_scopes.merged(tables, args["module"])
    total = sum(table["seconds"].values())
    per = (table["executions"] if args["per"] == "count"
           else outcome.work.get(args["per"]))
    if not total or not per:
        return None
    unscoped = table["seconds"].get(xplane_scopes.UNSCOPED, 0.0)
    if unscoped > 0.5 * total:
        if args["module"] not in _REFUSED:
            _REFUSED.add(args["module"])
            print(f"scopes {args['module']}: no value: "
                  f"{unscoped / total:.0%} of the device time is unscoped, "
                  "so the executables were compiled before the scopes "
                  "existed and served from the compile cache (its key "
                  "leaves names out); empty JAX_COMPILATION_CACHE_DIR, or "
                  ".jax_cache, and run again", flush=True)
        return None
    seconds = sum(table["seconds"].get(s, 0.0) for s in args["scopes"])
    return args.get("scale", 1.0) * seconds / per
