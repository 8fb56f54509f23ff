"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the
per-layer readers need, through a plain intermediate form that a test can
write by hand:

    {"window": [start_s, end_s],
     "devices": [{"name": ..., "ops": [[name, start_s, dur_s], ...],
                  "modules": [[name, start_s, dur_s], ...]}],
     "host": [[name, start_s, dur_s], ...]}

``ops`` are the device's operations (the ``XLA Ops`` line), ``modules``
the compiled programs they belong to (``XLA Modules``), ``host`` the
annotations host threads wrote (the program's spans mirror themselves as
``TraceAnnotation`` while a profile is live).  All on the profiler's one
clock, in seconds.
"""

import bisect
import glob
import os
import re

WINDOW_ANNOTATION = "bench/window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def start_trace(logdir):
    """Start the profiler with the host tracer cut to annotations (the
    default also records every futex and Python call: millions of events,
    and a host slowed tenfold), and tell the program's tracing that a
    profile is live, so that its spans mirror themselves into it."""
    import jax
    from cloud_tpu.monitoring import tracing

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    tracing.xprof_trace_started()


def stop_trace():
    import jax
    from cloud_tpu.monitoring import tracing

    jax.profiler.stop_trace()
    tracing.xprof_trace_stopped()


def short_name(name):
    """``%fusion.12 fusion`` from the full HLO text the chip's trace
    names an operation by (``%fusion.12 = bf16[...] fusion(...)``), with
    ``tpu_custom_call`` kept for a Pallas kernel."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    kind = re.search(r"(?:^|[\s)}])([a-z][a-z\-]*)\(", rest)
    parts = [head, kind.group(1) if kind else ""]
    if "tpu_custom_call" in rest:
        parts.append("tpu_custom_call")
    return " ".join(p for p in parts if p)[:120]


def find_xplane(logdir):
    paths = sorted(glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path, host_prefixes=("bench/", "serve/", "step/", "data/",
                              "compile/", "train/", "pipeline_io/")):
    """The intermediate form of the trace at ``path``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, window = [], [], None
    for plane in data.planes:
        if re.match(r"/device:TPU:\d+$", plane.name):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            devices.append({
                "name": plane.name,
                "ops": _events(lines[OPS_LINE]),
                "modules": _events(lines.get(MODULES_LINE)),
            })
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_ANNOTATION:
                        window = [ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9]
                    elif ev.name.startswith(host_prefixes):
                        host.append([ev.name, ev.start_ns * 1e-9,
                                     ev.duration_ns * 1e-9])
    if window is None:
        starts = [op[1] for d in devices for op in d["ops"]]
        ends = [op[1] + op[2] for d in devices for op in d["ops"]]
        window = [min(starts), max(ends)] if starts else [0.0, 0.0]
    return {"window": window, "devices": devices, "host": host}


def _events(line):
    if line is None:
        return []
    return [[ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9]
            for ev in line.events]


def describe(path, limit=40):
    """Planes, lines and the commonest event names of a trace, for the
    look by hand that comes before writing a pattern against it."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            names = {}
            for ev in line.events:
                agg = names.setdefault(ev.name, [0, 0.0])
                agg[0] += 1
                agg[1] += ev.duration_ns * 1e-9
            count = sum(v[0] for v in names.values())
            out.append(f"  line {line.name!r}: {count} events")
            for name, (n, s) in sorted(names.items(),
                                       key=lambda kv: -kv[1][1])[:limit]:
                out.append(f"    {s:10.6f}s {n:6d}x {name[:160]}")
    return "\n".join(out)


# -- reductions over the intermediate form ------------------------------


def clip(events, window):
    """Events cut to ``window``; those outside dropped."""
    lo, hi = window
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append([name, s, e - s])
    return out


def union_seconds(events):
    """Total length of the union of the events' intervals."""
    total, end = 0.0, float("-inf")
    for _, start, dur in sorted(events, key=lambda ev: ev[1]):
        stop = start + dur
        if stop > end:
            total += stop - max(start, end)
            end = stop
    return total


def busy_seconds(trace):
    """Seconds in which an operation ran, averaged over the devices."""
    if not trace["devices"]:
        return 0.0
    per = [union_seconds(clip(d["ops"], trace["window"]))
           for d in trace["devices"]]
    return sum(per) / len(per)


def window_seconds(trace):
    return trace["window"][1] - trace["window"][0]


def matching(events, pattern):
    rx = re.compile(pattern)
    return [ev for ev in events if rx.search(ev[0])]


def device_seconds(trace, pattern, line="ops"):
    """(seconds, count) of the events matching ``pattern`` inside the
    window, averaged over the devices."""
    n = max(len(trace["devices"]), 1)
    picked = [ev for d in trace["devices"]
              for ev in matching(clip(d[line], trace["window"]), pattern)]
    return sum(ev[2] for ev in picked) / n, len(picked) / n


def executions(trace, pattern, line="modules"):
    """How many times the events matching ``pattern`` ran inside the
    window, averaged over the devices: one cut by an end of the window
    counts for the share of it that lies inside, so that work counted by
    executions matches device time that was clipped the same way."""
    lo, hi = trace["window"]
    total = 0.0
    for d in trace["devices"]:
        for _, start, dur in matching(d[line], pattern):
            inside = min(start + dur, hi) - max(start, lo)
            if inside > 0 and dur > 0:
                total += inside / dur
    return total / max(len(trace["devices"]), 1)


def _module_of(modules):
    """op start -> the name of the compiled program that was running."""
    modules = sorted(modules, key=lambda ev: ev[1])
    starts = [ev[1] for ev in modules]

    def lookup(start):
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < modules[i][1] + modules[i][2]:
            return modules[i][0].split("(")[0]
        return ""

    return lookup


def top_ops(trace, limit=10):
    """[[``program/operation``, seconds], ...] of the operations that
    took most time OF THEIR OWN: a loop's time is less that of the
    operations that ran inside it, so the whole list adds up to the busy
    time and a loop does not hide what it loops over."""
    totals = {}
    for d in trace["devices"]:
        module_of = _module_of(d["modules"])
        open_ops = []  # [end, key] of the operations that enclose the next
        for name, start, dur in sorted(clip(d["ops"], trace["window"]),
                                       key=lambda ev: (ev[1], -ev[2])):
            while open_ops and open_ops[-1][0] <= start:
                open_ops.pop()
            key = f"{module_of(start)}/{short_name(name)}".lstrip("/")
            totals[key] = totals.get(key, 0.0) + dur
            if open_ops:
                totals[open_ops[-1][1]] -= min(dur, open_ops[-1][0] - start)
            open_ops.append([start + dur, key])
    n = max(len(trace["devices"]), 1)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, seconds / n] for name, seconds in ranked]


def idle_gaps(trace):
    """[(start, end), ...] of the first device's idle intervals."""
    if not trace["devices"]:
        return []
    lo, hi = trace["window"]
    gaps, cursor = [], lo
    for _, start, dur in sorted(clip(trace["devices"][0]["ops"],
                                     trace["window"]), key=lambda ev: ev[1]):
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, start + dur)
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


def idle_by_host_span(trace, limit=10):
    """[[name, seconds], ...]: idle time by what the host was doing in it
    — every idle gap goes to the host annotation that covers most of it
    (the innermost on a tie), or to ``(no span)``."""
    host = sorted(trace["host"], key=lambda ev: ev[1])
    totals = {}
    for lo, hi in idle_gaps(trace):
        best, best_cover, best_dur = "(no span)", 0.0, float("inf")
        for name, start, dur in host:
            if start >= hi:
                break
            cover = min(hi, start + dur) - max(lo, start)
            if cover > best_cover + 1e-9 or (
                    abs(cover - best_cover) <= 1e-9 and cover > 0
                    and dur < best_dur):
                best, best_cover, best_dur = name, cover, dur
        totals[best] = totals.get(best, 0.0) + (hi - lo)
    return [[k, v] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:limit]]
