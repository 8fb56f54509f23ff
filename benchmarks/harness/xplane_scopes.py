"""A device trace's operations WITH the name the framework gave them, and
their time summed by program scope (``cloud_tpu.models.layers.SCOPES``).

``xplane.load`` reads a trace through ``jax.profiler.ProfileData``, which
shows an event's own stats (on a v5e: ``device_offset_ps``,
``device_duration_ps``) and not those of the event's METADATA, where the
chip's profiler keeps what it knows of the HLO instruction: ``tf_op`` (the
instruction's ``op_name``: ``jit(chunk_fn)/while/body/.../mlp/dot_general:``),
``hlo_category``, ``program_id``, ``flops``, ``bytes_accessed``, ``source``.
So this reads the ``.xplane.pb`` as the protobuf it is, with the few fields
of tsl's ``xplane.proto`` it needs declared here, into the intermediate form
of ``xplane.load`` with one more entry an operation:

    {"window": [start_s, end_s],
     "devices": [{"name": ...,
                  "ops": [[name, start_s, dur_s, framework_name], ...],
                  "modules": [[name, start_s, dur_s], ...]}]}

A scope is the last component of the framework name that is in ``SCOPES``;
a fusion carries the name of its root.  Two pseudo-scopes: ``control`` (the
own time of a ``while``, ``conditional`` or ``call``) and ``unscoped`` (no
name of the list: the compiler's own copies, a scan's slicing of its
operands, and what the program left outside every scope).
"""

import functools
import os
import re
import time

from benchmarks.harness import manifest, xplane

CONTROL, UNSCOPED = "control", "unscoped"
#: Seconds under which an end and the next start are the same instant: the
#: chip stamps its operations in ticks of 1.25 ns, and two that abut come
#: out of float arithmetic an ulp apart either way.  (``xplane.top_ops``,
#: on ``ProfileData``'s whole nanoseconds, takes such a neighbour for a
#: child and leaves its time with the loop around both.)
ABUT_S = 1e-10
CONTROL_KINDS = ("while", "conditional", "call")
FRAMEWORK_NAME_STAT = "tf_op"
TRACE_DIR = os.path.join(manifest.ROOT, ".bench_trace")

#: message -> [(field, number, type, repeated)]: what is read of
#: tsl/profiler/protobuf/xplane.proto; every other field is skipped.
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "string", False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "EventMetadataEntry": [("key", 1, "int64", False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int64", False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("name", 2, "string", False),
              ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("str_value", 5, "string", False),
              ("ref_value", 7, "uint64", False)],
    "XEventMetadata": [("name", 2, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, "string", False)],
}


@functools.lru_cache(maxsize=None)
def _xspace_class():
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    field = descriptor_pb2.FieldDescriptorProto
    scalars = {"int64": field.TYPE_INT64, "uint64": field.TYPE_UINT64,
               "string": field.TYPE_STRING}
    package = "bench_xplane"
    file = descriptor_pb2.FileDescriptorProto(
        name=f"{package}.proto", package=package, syntax="proto3")
    for message, fields in _SCHEMA.items():
        entry = file.message_type.add(name=message)
        for name, number, kind, repeated in fields:
            f = entry.field.add(
                name=name, number=number,
                label=field.LABEL_REPEATED if repeated
                else field.LABEL_OPTIONAL)
            if kind in scalars:
                f.type = scalars[kind]
            else:
                f.type, f.type_name = field.TYPE_MESSAGE, f".{package}.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{package}.XSpace"))


def _events(plane, line, named=None):
    """[[name, start_s, dur_s(, framework name)], ...] of one line."""
    names = {e.key: e.value.name for e in plane.event_metadata}
    base = line.timestamp_ns * 1e-9
    out = []
    for ev in line.events:
        row = [names.get(ev.metadata_id, ""), base + ev.offset_ps * 1e-12,
               ev.duration_ps * 1e-12]
        if named is not None:
            row.append(named.get(ev.metadata_id, ""))
        out.append(row)
    return out


def _framework_names(plane):
    """event metadata id -> the framework's name for the instruction."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    out = {}
    for entry in plane.event_metadata:
        for stat in entry.value.stats:
            if stat_names.get(stat.metadata_id) == FRAMEWORK_NAME_STAT:
                out[entry.key] = (stat.str_value
                                  or stat_names.get(stat.ref_value, ""))
    return out


def load(path):
    """The intermediate form, with framework names, of the trace at
    ``path``."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices, window = [], None
    for plane in space.planes:
        lines = {line.name: line for line in plane.lines}
        if re.match(r"/device:TPU:\d+$", plane.name):
            if xplane.OPS_LINE not in lines:
                continue
            modules = lines.get(xplane.MODULES_LINE)
            devices.append({
                "name": plane.name,
                "ops": _events(plane, lines[xplane.OPS_LINE],
                               _framework_names(plane)),
                "modules": ([] if modules is None
                            else _events(plane, modules)),
            })
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, start, dur in _events(plane, line):
                    if name == xplane.WINDOW_ANNOTATION:
                        window = [start, start + dur]
    if window is None:
        ends = [(op[1], op[1] + op[2]) for d in devices for op in d["ops"]]
        window = ([min(e[0] for e in ends), max(e[1] for e in ends)]
                  if ends else [0.0, 0.0])
    return {"window": window, "devices": devices}


@functools.lru_cache(maxsize=None)
def traced():
    """The trace ``run.py`` wrote (``.bench_trace`` under the checkout),
    loaded once a process; None where there is none to read."""
    started = time.perf_counter()
    try:
        trace = load(xplane.find_xplane(TRACE_DIR))
    except FileNotFoundError:
        return None
    print(f"scopes: {sum(len(d['ops']) for d in trace['devices'])} "
          "operations loaded with their framework names in "
          f"{time.perf_counter() - started:.2f}s", flush=True)
    return trace


# -- reductions over the intermediate form ------------------------------


def scope_of(short_name, framework_name, scopes):
    """The scope an operation's time goes to, from its
    ``xplane.short_name`` and the framework's name for it."""
    kind = short_name.split(" ")[1:2]
    if kind and kind[0] in CONTROL_KINDS:
        return CONTROL
    for part in reversed(framework_name.rstrip(":").split("/")):
        if part in scopes:
            return part
    return UNSCOPED


def scope_tables(trace, scopes):
    """The device time of every program of the trace, by scope, inside the
    window and averaged over the devices, in one pass over the operations:

        {program: {"seconds": {scope: s}, "executions": n,
                   "module_seconds": s, "unscoped_ops": {short name: s}}}

    A program is a module event's name up to its ``(``; its executions
    count as ``xplane.executions`` counts them (one cut by the window for
    its share inside).  An operation's time is its OWN (``xplane.top_ops``'s
    reckoning: a loop's time less that of what ran inside it), so a
    program's scopes add up to the time in which it kept the device
    busy."""
    lo, hi = trace["window"]
    n = max(len(trace["devices"]), 1)
    tables = {}
    # A trace names a few hundred instructions a million times over.
    classified = {}  # (name, framework name) -> (scope, short name)

    def table(program):
        return tables.setdefault(program, {
            "seconds": {}, "executions": 0.0, "module_seconds": 0.0,
            "unscoped_ops": {}})

    def add(program, scope, short, seconds):
        into = table(program)
        into["seconds"][scope] = (into["seconds"].get(scope, 0.0)
                                  + seconds / n)
        if scope == UNSCOPED:
            into["unscoped_ops"][short] = (
                into["unscoped_ops"].get(short, 0.0) + seconds / n)

    for d in trace["devices"]:
        for name, start, dur in d["modules"]:
            inside = min(start + dur, hi) - max(start, lo)
            if inside > 0 and dur > 0:
                into = table(name.split("(")[0])
                into["module_seconds"] += inside / n
                into["executions"] += inside / dur / n
        module_of = xplane._module_of(d["modules"])
        clipped = []
        for name, start, dur, framework_name in d["ops"]:
            s, e = max(start, lo), min(start + dur, hi)
            if e > s:
                clipped.append((s, e - s, name, framework_name))
        open_ops = []  # [end, (program, scope, short name)] around the next
        for start, dur, name, framework_name in sorted(
                clipped, key=lambda op: (op[0], -op[1])):
            while open_ops and open_ops[-1][0] <= start + ABUT_S:
                open_ops.pop()
            if open_ops:
                end, around = open_ops[-1]
                add(*around, -min(dur, end - start))
            if (name, framework_name) not in classified:
                short = xplane.short_name(name)
                classified[name, framework_name] = (
                    scope_of(short, framework_name, scopes), short)
            mine = (module_of(start), *classified[name, framework_name])
            add(*mine, dur)
            open_ops.append([start + dur, mine])
    return tables


def merged(tables, pattern):
    """One table of the programs of ``tables`` whose name matches
    ``pattern`` (two programs may share a name, and a pattern two
    names)."""
    rx = re.compile(pattern)
    out = {"seconds": {}, "executions": 0.0, "module_seconds": 0.0,
           "unscoped_ops": {}}
    for program, table in tables.items():
        if not rx.search(program):
            continue
        out["executions"] += table["executions"]
        out["module_seconds"] += table["module_seconds"]
        for key in ("seconds", "unscoped_ops"):
            for name, value in table[key].items():
                out[key][name] = out[key].get(name, 0.0) + value
    return out

