"""What one run hands its adapter, and what the adapter hands back."""

import dataclasses
import gc
import glob
import threading
import time
from typing import Any, Dict, List, Optional


@dataclasses.dataclass
class Run:
    cell: Any            # manifest.Cell
    seed: int
    seconds: float
    trace: bool
    process_start: float  # perf_counter at process start
    trace_dir: Optional[str] = None
    #: Also read the control (the reference in the precision below) and
    #: the planted faults: for setting limits, never in a measured run.
    control: bool = False
    #: False skips the reference (a sweep for a rate reads no ``correct``).
    check: bool = True

    def say(self, message):
        print(f"[{self.cell.name} seed {self.seed} "
              f"+{time.perf_counter() - self.process_start:.1f}s] {message}",
              flush=True)


@dataclasses.dataclass
class Outcome:
    window_start: float                  # perf_counter
    window_s: float
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: List[tuple]                  # (name, value, limit)
    memory_peak_bytes: int
    #: The program's spans: (name, start perf_counter, seconds, args).
    spans: List[tuple] = dataclasses.field(default_factory=list)
    #: Counters over the window (after minus before).
    stats: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: Work done inside the traced window, computed by the adapter with
    #: ``harness.work``: name -> number, or name -> {"flops", "bytes"}.
    work: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: The traced window on the host's clock, and ``xplane``'s reduction.
    traced: Optional[tuple] = None
    trace: Optional[dict] = None
    control_checks: List[tuple] = dataclasses.field(default_factory=list)


class CompileCounter:
    """Counts backend compilations and the persistent cache's hits and
    misses, by JAX's own monitoring events.  One a process
    (``CompileCounter.get()``): JAX keeps a listener for good."""

    _instance = None

    @classmethod
    def get(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def __init__(self):
        import jax.monitoring

        self.times, self.cache = [], {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_event(self, name, seconds, **kwargs):
        if "backend_compile" in name:
            self.times.append(time.perf_counter())

    def _on_count(self, name, **kwargs):
        for key in self.cache:
            if name.endswith(f"compilation_cache/cache_{key}"):
                self.cache[key] += 1

    def between(self, start, end):
        return sum(1 for t in self.times if start <= t <= end)


def program_spans():
    """The program's finished spans, on the ``perf_counter`` clock."""
    from cloud_tpu.monitoring import tracing

    collector = tracing.active()
    if collector is None:
        return []
    return [(ev["name"], ev["ts"] * 1e-6 + collector.epoch,
             ev["dur"] * 1e-6, ev.get("args", {}))
            for ev in collector.events()]


def memory_peak_bytes():
    """The peak on the fullest chip, as JAX's ``memory_stats`` reports it:
    the buffers' peak plus the peak of what the runtime reserved for the
    programs' temporaries (``peak_bytes_reserved``, a region of its own:
    ``peak_bytes_in_use`` alone reads a cell's arguments to the byte and
    none of its temp space).  0 where the backend reports nothing."""
    import jax

    peaks = []
    for device in jax.local_devices():
        stats = device.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))


class HostWatch:
    """Whose stall was it?  A thread that sleeps ``tick`` seconds at a time
    and keeps its longest oversleep (the process, or the machine, stood
    still that long), with the seconds this process's threads waited for a
    CPU (``/proc/self/task/*/schedstat``) and the machine's stolen seconds
    (``/proc/stat``) over the same stretch, and the longest collection of
    Python's own garbage collector.  For an earlier line of a run:
    a tail that a stall of the host made is no finding about the program.
    """

    def __init__(self, tick=0.01):
        self.tick, self.longest = tick, 0.0
        self.longest_gc, self._gc_started = (0.0, None), None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="bench-host-watch")

    def _watch(self):
        last = time.perf_counter()
        while not self._stop.wait(self.tick):
            now = time.perf_counter()
            self.longest = max(self.longest, now - last - self.tick)
            last = now

    def _on_gc(self, phase, info):
        """Python's own collections, which hold every thread: the longest,
        and of which generation."""
        now = time.perf_counter()
        if phase == "start":
            self._gc_started = now
        elif self._gc_started is not None:
            self.longest_gc = max(
                self.longest_gc, (now - self._gc_started, info["generation"]))

    @staticmethod
    def _waited_s():
        total = 0
        for path in glob.glob("/proc/self/task/*/schedstat"):
            try:
                with open(path) as f:
                    total += int(f.read().split()[1])
            except (OSError, IndexError, ValueError):
                pass
        return total * 1e-9

    @staticmethod
    def _stolen_s():
        try:
            with open("/proc/stat") as f:
                return int(f.readline().split()[8]) / 100.0
        except (OSError, IndexError, ValueError):
            return 0.0

    def start(self):
        self._before = (self._waited_s(), self._stolen_s())
        gc.callbacks.append(self._on_gc)
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)
        self.waited_s = self._waited_s() - self._before[0]
        self.stolen_s = self._stolen_s() - self._before[1]

    def __str__(self):
        return (f"host: longest stall {self.longest * 1e3:.0f} ms; longest "
                f"collection of Python's {self.longest_gc[0] * 1e3:.0f} ms "
                f"(generation {self.longest_gc[1]}); threads waited "
                f"{self.waited_s:.2f}s for a CPU; stolen "
                f"{self.stolen_s:.2f}s")
