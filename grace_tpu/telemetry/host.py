"""The host ledger: what the host was doing during set-up and during a step
that stalled, from counters the operating system keeps anyway.

The compile ledger (:mod:`~grace_tpu.telemetry.compiles`) names the part of
set-up that lies inside JAX's trace, lowering and compile events. This
module names the rest, by the same pattern: one ledger a process, fed by
cheap reads, in memory, no switch, nothing on a compiled step's path.

* **Snapshots.** :func:`snapshot` reads, for the process and for the calling
  thread: wall (``time.time()``, the clock of JAX's events, and
  ``time.perf_counter()``), CPU seconds, seconds *runnable but waiting for
  a CPU* (the second field of ``/proc/self/task/*/schedstat`` summed, and
  of ``/proc/thread-self/schedstat``), major page faults and involuntary
  context switches (``getrusage``), and the ``some total`` of
  ``/proc/pressure/{cpu,memory,io}``: the whole machine's pressure, which
  tells a starved host from a starved process. A field the platform lacks
  reads ``None``, never 0. :func:`thread_snapshot` is the cheap part (the
  calling thread alone), which :class:`grace_tpu.utils.profiling.StepTimer`
  takes around a step. A snapshot is a few file reads, taken only at the
  places below: no thread, no sampler.
* **Spans** at the program's own boundaries on the set-up path
  (:func:`spanned`, :func:`span`): the import of ``grace_tpu``,
  ``place_compile_cache``, ``grace_from_params``, the transform's build,
  the step builders and the first call's wrap (``partition_specs``,
  ``shard_map``, ``jax.jit``), the state initialisers. Each is kept one by
  one (a dozen a process): name, start, end, the span that encloses it, the
  snapshot's deltas; each also opens a
  ``jax.profiler.TraceAnnotation("grace/host/<name>")``, so under a profile
  it stands on the profiler's clock beside the device planes. A span's
  **self time** is its length less what its child spans and the compile
  ledger's intervals inside it cover: :meth:`HostLedger.program_s` sums
  them, and that is what the program's own Python costs set-up outside
  JAX's events.
* **Marks.** *Process start*: the process's age when the ledger is made
  (``/proc/self/stat`` start time against ``CLOCK_BOOTTIME``); the ledger
  is made by the first line of ``grace_tpu/__init__.py``, so
  :meth:`HostLedger.pre_program_s` is "process start until ``grace_tpu``
  begins to import": the interpreter and whatever the caller did first
  (``import jax``, reaching the chip: :attr:`HostLedger.backends_ready_at_load`
  says whether JAX's backends were initialised by then, so the span is
  named for what it held; where they were not, reaching the chip lies in
  the gap between two later spans). *Built*: a snapshot at the end of
  every ``backend_compile_duration`` event (tens to hundreds a process);
  the last one is "the last program of set-up was built". CPU seconds,
  run-queue wait and faults at that mark are totals since the process
  started, by their nature.

``LEDGER`` is the process's; :func:`reset` is for tests.
"""

from __future__ import annotations

import bisect
import contextlib
import functools
import os
import resource
import threading
import time
from typing import Iterator, NamedTuple, Optional

__all__ = ["Snapshot", "ThreadSnapshot", "snapshot", "thread_snapshot",
           "process_age_s", "parse_schedstat_wait_s", "parse_stat_start_s",
           "parse_pressure_some_s", "HostLedger", "LEDGER", "span",
           "spanned", "reset"]

SPAN_PREFIX = "grace/host/"
# A tuner builds thousands of configurations in one process; the ledger is
# for the dozen spans of a set-up: past this many a span is counted and
# costs nothing more (no snapshot, no annotation).
MAX_SPANS = 1024
# What this kernel offers is asked once, here: a sandboxed kernel (the chip
# tool's machine) has neither file, and a failed open for each of 230
# threads made a snapshot 6 ms there. Where the kernel has ``schedstat`` a
# process snapshot opens one file a live thread (PERF.md section 6, PR 38,
# has both costs).
_HAS_SCHEDSTAT = os.path.exists("/proc/thread-self/schedstat")
_PRESSURES = tuple(r for r in ("cpu", "memory", "io")
                   if os.path.exists(f"/proc/pressure/{r}"))


# ---------------------------------------------------------------------------
# what the operating system counts
# ---------------------------------------------------------------------------

def _read(path: str) -> Optional[str]:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


def parse_schedstat_wait_s(text: Optional[str]) -> Optional[float]:
    """Seconds a task was runnable and waited for a CPU: the second of a
    ``schedstat`` file's three fields (nanoseconds on a CPU, nanoseconds
    waiting on a run queue, time slices)."""
    fields = (text or "").split()
    if len(fields) < 2 or not fields[1].isdigit():
        return None
    return int(fields[1]) * 1e-9


def parse_stat_start_s(text: Optional[str], ticks_per_s: float
                       ) -> Optional[float]:
    """Seconds after boot at which the process started: field 22 of
    ``/proc/<pid>/stat``. The command's name (field 2, in parentheses) may
    hold spaces and parentheses of its own, so fields are counted from the
    last ``)``."""
    fields = (text or "").rpartition(")")[2].split()
    if len(fields) < 20 or not fields[19].isdigit() or ticks_per_s <= 0:
        return None
    return int(fields[19]) / ticks_per_s


def parse_pressure_some_s(text: Optional[str]) -> Optional[float]:
    """Seconds in which at least one task of the machine stalled on the
    resource: ``total=`` (microseconds) of a pressure file's ``some`` line."""
    for line in (text or "").splitlines():
        if line.startswith("some "):
            total = line.rpartition("total=")[2].strip()
            return int(total) * 1e-6 if total.isdigit() else None
    return None


def process_age_s() -> Optional[float]:
    """Seconds since the kernel started this process."""
    try:
        started = parse_stat_start_s(_read("/proc/self/stat"),
                                     os.sysconf("SC_CLK_TCK"))
        now = time.clock_gettime(time.CLOCK_BOOTTIME)
    except (AttributeError, OSError, ValueError):
        return None
    return None if started is None else max(now - started, 0.0)


def _thread_wait_s() -> Optional[float]:
    if not _HAS_SCHEDSTAT:
        return None
    return parse_schedstat_wait_s(_read("/proc/thread-self/schedstat"))


def _tasks_wait_s() -> Optional[float]:
    """Run-queue wait summed over the process's live threads."""
    if not _HAS_SCHEDSTAT:
        return None
    try:
        tasks = os.listdir("/proc/self/task")
    except OSError:
        return None
    waits = [parse_schedstat_wait_s(_read(f"/proc/self/task/{t}/schedstat"))
             for t in tasks]
    waits = [w for w in waits if w is not None]   # a thread may just have ended
    return sum(waits) if waits else None


class ThreadSnapshot(NamedTuple):
    """The calling thread alone: the cheap part."""
    perf: float
    cpu: float
    runq: Optional[float]
    major_faults: Optional[int]


class Snapshot(NamedTuple):
    """The process (every thread) and the calling thread."""
    time: float                       # time.time(): the clock of JAX's events
    perf: float                       # time.perf_counter()
    cpu: float                        # every thread, user + system
    thread_cpu: float
    runq: Optional[float]             # live threads summed
    thread_runq: Optional[float]
    major_faults: Optional[int]
    involuntary_switches: Optional[int]
    pressure_cpu: Optional[float]     # the whole machine's
    pressure_memory: Optional[float]
    pressure_io: Optional[float]


def _rusage(who) -> Optional[resource.struct_rusage]:
    try:
        return resource.getrusage(who)
    except (OSError, TypeError, ValueError):    # no RUSAGE_THREAD here
        return None


def thread_snapshot() -> ThreadSnapshot:
    usage = _rusage(getattr(resource, "RUSAGE_THREAD", None))
    return ThreadSnapshot(
        perf=time.perf_counter(), cpu=time.thread_time(),
        runq=_thread_wait_s(),
        major_faults=None if usage is None else usage.ru_majflt)


def snapshot() -> Snapshot:
    usage = _rusage(resource.RUSAGE_SELF)
    return Snapshot(
        time=time.time(), perf=time.perf_counter(),
        cpu=time.process_time(), thread_cpu=time.thread_time(),
        runq=_tasks_wait_s(), thread_runq=_thread_wait_s(),
        major_faults=None if usage is None else usage.ru_majflt,
        involuntary_switches=None if usage is None else usage.ru_nivcsw,
        **{f"pressure_{r}": (parse_pressure_some_s(
            _read(f"/proc/pressure/{r}")) if r in _PRESSURES else None)
           for r in ("cpu", "memory", "io")})


def deltas(before: NamedTuple, after: NamedTuple) -> dict:
    """Field by field ``after − before``; ``None`` where either lacks it."""
    return {k: None if a is None or b is None else b - a
            for k, a, b in zip(before._fields, before, after)}


# The module's first statement that runs anything: before ``import jax``
# below, which this import may be the process's first of. It is the moment
# ``grace_tpu`` began to import (``grace_tpu/__init__.py``'s first line
# imports this module, and ``grace_tpu.telemetry`` imports it first).
_AT_LOAD = snapshot()

import jax  # noqa: E402
from jax import monitoring  # noqa: E402

from grace_tpu.telemetry import compiles  # noqa: E402

_BUILT_EVENT = "/jax/core/compile/backend_compile_duration"


try:                            # a private name: absent, not fatal
    from jax._src.xla_bridge import backends_are_initialized
except ImportError:
    backends_are_initialized = None


def _backends_ready() -> Optional[bool]:
    """Whether JAX has initialised its backends (``None``: cannot say)."""
    if backends_are_initialized is None:
        return None
    return bool(backends_are_initialized())


def _covered(lo: float, hi: float, intervals) -> float:
    """Length of the union of ``intervals`` (any order, may overlap) cut to
    ``[lo, hi]``."""
    total, reach = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, reach), min(e, hi)
        if e > s:
            total += e - s
            reach = e
    return total


class HostLedger:
    """Spans and marks of one process. Reads are pure functions of it."""

    def __init__(self, snapshot=snapshot, process_age=process_age_s,
                 at_load: Optional[Snapshot] = None):
        self._snapshot = snapshot
        self._process_age = process_age
        self.reset(at_load)

    def reset(self, at_load: Optional[Snapshot] = None) -> None:
        """``at_load``: a snapshot taken earlier, at the moment the ledger
        counts as made (nothing that initialises a backend may lie between
        the two: importing JAX does not)."""
        self.made: Snapshot = at_load or self._snapshot()
        age = self._process_age()
        now = self.made.time if at_load is None else time.time()
        # When the process started, on time.time()'s clock (None: unknown).
        self.process_began: Optional[float] = (
            None if age is None else now - age)
        # Whether the chip was reached before ``grace_tpu`` began to
        # import: what the time before the program held.
        self.backends_ready_at_load = _backends_ready()
        self.spans: list[dict] = []       # in order of their start
        self.dropped = 0                  # spans past MAX_SPANS
        self.built: Optional[Snapshot] = None
        self.builds = 0
        self._open = threading.local()

    # -- spans --------------------------------------------------------------
    def begin(self, name: str, at: Optional[Snapshot] = None
              ) -> Optional[dict]:
        """Open a span; ``at`` is a snapshot already taken at its start.
        ``None`` past :data:`MAX_SPANS`: counted, not kept, nothing read."""
        if len(self.spans) >= MAX_SPANS:
            self.dropped += 1
            return None
        stack = self._open.__dict__.setdefault("stack", [])
        row = {"name": name, "parent": stack[-1]["index"] if stack else None,
               "index": len(self.spans), "start": None, "end": None,
               "deltas": None}
        self.spans.append(row)
        row["_annotation"] = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
        row["_annotation"].__enter__()
        row["_before"] = at or self._snapshot()
        row["start"] = row["_before"].time
        stack.append(row)
        return row

    def end(self, row: Optional[dict]) -> None:
        if row is None:
            return
        after = self._snapshot()
        row["end"] = after.time
        row["deltas"] = deltas(row.pop("_before"), after)
        row.pop("_annotation").__exit__(None, None, None)
        stack = self._open.__dict__.get("stack", [])
        for i, open_row in enumerate(stack):
            if open_row is row:           # also drops what it left open
                del stack[i:]
                break

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[Optional[dict]]:
        row = self.begin(name)
        try:
            yield row
        finally:
            self.end(row)

    # -- the listener -------------------------------------------------------
    def on_span(self, event, start_s, end_s, **_) -> None:
        if event == _BUILT_EVENT:
            self.built = self._snapshot()
            self.builds += 1

    # -- reads --------------------------------------------------------------
    def closed(self) -> list[dict]:
        return [s for s in self.spans if s["end"] is not None]

    def self_times(self, compile_intervals=()) -> list[tuple[str, float]]:
        """``(name, self seconds)`` of every closed span: its length less
        what its child spans and ``compile_intervals`` (disjoint, by start:
        :func:`compiles.intervals`) cover of it."""
        spans = self.closed()
        children: dict = {}
        for row in spans:
            children.setdefault(row["parent"], []).append(
                (row["start"], row["end"]))
        starts = [s for s, _ in compile_intervals]
        out = []
        for row in spans:
            lo, hi = row["start"], row["end"]
            # the interval that began before the span may reach into it
            first = max(bisect.bisect_right(starts, lo) - 1, 0)
            last = bisect.bisect_left(starts, hi)
            inside = (children.get(row["index"], [])
                      + list(compile_intervals[first:last]))
            out.append((row["name"], max(hi - lo - _covered(lo, hi, inside),
                                         0.0)))
        return out

    def program_s(self, compile_intervals=()) -> Optional[float]:
        """The self times summed: the program's own Python on the set-up
        path, outside JAX's trace, lowering and compile events."""
        times = self.self_times(compile_intervals)
        return sum(t for _, t in times) if times else None

    def pre_program_s(self) -> Optional[float]:
        """Process start until ``grace_tpu`` began to import."""
        if self.process_began is None:
            return None
        return self.made.time - self.process_began

    def summary(self) -> dict:
        """One dictionary of everything, plain data: the recorder's
        ``perf_setup`` record (``process_began`` and the spans' ``start`` and
        ``end`` are on ``time.time()``'s clock; ``jit_wall_s`` and
        ``cache_read_s`` are the compile ledger's, so that set-up's parts
        stand in one place)."""
        times = self.self_times(compiles.intervals())
        return {
            "process_began": self.process_began,
            "pre_program_s": self.pre_program_s(),
            "backends_ready_at_load": self.backends_ready_at_load,
            "jit_wall_s": compiles.wall_s(),
            "cache_read_s": compiles.durations()["cache_read_s"],
            "program_s": sum(t for _, t in times) if times else None,
            "spans": [{"name": s["name"], "parent": s["parent"],
                       "start": s["start"], "end": s["end"], "self_s": t,
                       **{k: v for k, v in s["deltas"].items()
                          if k not in ("time", "perf")}}
                      for s, (_, t) in zip(self.closed(), times)],
            "spans_dropped": self.dropped,
            "builds": self.builds,
            "built": None if self.built is None else self.built._asdict(),
        }


LEDGER = HostLedger(at_load=_AT_LOAD)
monitoring.register_event_time_span_listener(LEDGER.on_span)

span = LEDGER.span
reset = LEDGER.reset


def spanned(name: str):
    """Decorator: the call is one span of the process's ledger."""
    def wrap(fn):
        @functools.wraps(fn)
        def inside(*args, **kwargs):
            with LEDGER.span(name):
                return fn(*args, **kwargs)
        return inside
    return wrap
