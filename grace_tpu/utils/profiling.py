"""Profiling helpers: XLA trace capture and honest step timing.

Replaces the reference's print-driven instrumentation (SURVEY.md §5:
`torch.cuda.synchronize()` + wall-clock prints left in
grace_dl/torch/compressor/qsgd.py:14-15 and examples). On TPU the profiler
of record is ``jax.profiler`` (Perfetto/TensorBoard traces of the XLA
schedule, including ICI collective overlap); ``StepTimer`` gives cheap
steady-state throughput numbers with correct async-dispatch handling.

The runtime recorder built on top of this (step-time percentiles, retrace
detection, memory watermarks, sink emission) lives in
:class:`grace_tpu.profiling.ProfileRecorder`; the offline trace analyzer is
:mod:`grace_tpu.profiling.trace_analysis`.
"""

from __future__ import annotations

import collections
import contextlib
import statistics
import warnings
from typing import Iterator, List, Optional

import jax
import numpy as np

from grace_tpu.telemetry import compiles, host

__all__ = ["trace", "StepTimer"]

# A step is stalled when its wall time is over STALL_RATIO times the running
# median of the steady steps before it and at least STALL_MIN_S over it.
STALL_RATIO = 1.5
STALL_MIN_S = 0.05
# The running median is over this many of the latest steady steps, and is
# not asked before this many are in.
_MEDIAN_WINDOW = 64
_MEDIAN_MIN = 3


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """Capture a device trace viewable in TensorBoard/Perfetto/XProf."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepTimer:
    """Per-step wall-clock stats that respect JAX's async dispatch.

    Usage::

        timer = StepTimer(warmup=2)
        for batch in batches:
            with timer.step():
                state, loss = train_step(state, batch)
                timer.sync_on(loss)     # block on a step OUTPUT, not the world

    ``mean_sec``/``p50_sec`` skip the warmup steps (compile + autotune).

    Without ``sync_on`` the timer measures only the *async dispatch* of the
    step — microseconds of Python enqueueing work, not device execution —
    and the resulting "throughput" is fiction. The first such step warns
    once, and :attr:`measured_async_dispatch` stays True so downstream
    consumers (``grace_tpu.profiling.ProfileRecorder`` stamps it on every
    emitted record) can flag the numbers.

    A step body that raises still records its timing row (wall-clock up to
    the raise) and bumps :attr:`failed_steps` — a crash mid-run used to
    silently swallow the row, hiding exactly the slow step that died.

    **A stalled step gets a cause.** Around each step the timer takes the
    calling thread's snapshot (:func:`grace_tpu.telemetry.host.thread_snapshot`:
    wall, the thread's CPU seconds, its seconds runnable but waiting for a
    CPU, its major page faults) and splits the step's wall time into
    ``cpu``, ``runq`` and ``blocked = wall - cpu - runq``. A steady step
    whose wall is over 1.5 times the running median of the steady steps
    before it, and at least 50 ms over it, is kept in :attr:`stalls` with
    the largest part as its ``cause``: ``runq`` (the thread was runnable and
    the machine gave it no CPU), ``cpu`` (the thread computed: Python, a
    collection), ``blocked`` (asleep inside the runtime: the device, a
    transfer, an allocation, a lock); ``cpu`` or ``blocked`` during a step
    in which the compile ledger saw a new lowering is ``compile``. Under a
    ``jax.profiler`` trace the step stands on the host plane as
    ``grace/step`` (a ``StepTraceAnnotation``) and its blocking wait as
    ``grace/host/fetch``. ``snapshot`` is the function that returns the
    thread's :class:`~grace_tpu.telemetry.host.ThreadSnapshot` (tests hand
    in their own).
    """

    def __init__(self, warmup: int = 2, snapshot=host.thread_snapshot):
        self.warmup = warmup
        self.failed_steps = 0
        # True once any completed step was timed without a sync target:
        # the recorded times are dispatch-only and throughput is unusable.
        self.measured_async_dispatch = False
        # One row a stalled step: step (the timer's count), wall_s, cpu_s,
        # runq_s, blocked_s, major_faults, cause.
        self.stalls: List[dict] = []
        self._times: List[float] = []
        self._recent = collections.deque(maxlen=_MEDIAN_WINDOW)
        self._snapshot = snapshot
        self._sync_target = None
        self._warned_async = False

    def sync_on(self, out) -> None:
        self._sync_target = out

    def _note_async_dispatch(self) -> None:
        self.measured_async_dispatch = True
        if not self._warned_async:
            self._warned_async = True
            warnings.warn(
                "StepTimer.step() completed without sync_on(): the recorded "
                "time covers only async dispatch, not device execution — "
                "call timer.sync_on(<a step output>) inside the step block "
                "(jax dispatches asynchronously; without a blocking fetch "
                "the step 'finishes' in microseconds).",
                RuntimeWarning, stacklevel=3)

    def _record(self, before, lowered: int) -> None:
        """Close the step opened at ``before``: its time, and its cause if
        it stalled."""
        after = self._snapshot()
        n = len(self._times)
        wall = after.perf - before.perf
        self._times.append(wall)
        if n < self.warmup:
            return
        if len(self._recent) >= _MEDIAN_MIN:
            median = statistics.median(self._recent)
            if wall > STALL_RATIO * median and wall - median >= STALL_MIN_S:
                self.stalls.append(_stall_row(
                    n, before, after, compiles.lowerings() - lowered))
        self._recent.append(wall)

    @contextlib.contextmanager
    def step(self) -> Iterator[None]:
        before, lowered = self._snapshot(), compiles.lowerings()
        with jax.profiler.StepTraceAnnotation("grace/step",
                                              step_num=len(self._times)):
            try:
                yield
            except BaseException:
                # Record the partial row (the slow step that died is the one
                # a postmortem needs to see) but never let a failed step's
                # sync target poison the next one.
                self._sync_target = None
                self._record(before, lowered)
                self.failed_steps += 1
                raise
            if self._sync_target is not None:
                with jax.profiler.TraceAnnotation(host.SPAN_PREFIX + "fetch"):
                    jax.block_until_ready(self._sync_target)
                self._sync_target = None
            else:
                self._note_async_dispatch()
        self._record(before, lowered)

    def __len__(self) -> int:
        return len(self._times)

    @property
    def steady(self) -> np.ndarray:
        if not self._times:
            raise RuntimeError("StepTimer has no recorded steps")
        return np.asarray(self._times[self.warmup:] or self._times)

    @property
    def mean_sec(self) -> float:
        return float(self.steady.mean())

    @property
    def p50_sec(self) -> float:
        return float(np.median(self.steady))

    def percentile_sec(self, q: float) -> float:
        """Steady-state percentile, e.g. ``percentile_sec(99)``."""
        return float(np.percentile(self.steady, q))

    def throughput(self, items_per_step: int) -> float:
        return items_per_step / self.mean_sec


def _stall_row(step: int, before, after, new_lowerings: int) -> dict:
    """What a stalled step's thread did with its wall time. ``runq_s`` is
    ``None`` where the platform keeps no ``schedstat``; ``blocked_s`` then
    holds it too."""
    d = host.deltas(before, after)
    wall, cpu, runq = d["perf"], d["cpu"], d["runq"]
    blocked = max(wall - cpu - (runq or 0.0), 0.0)
    parts = {"cpu": cpu, "blocked": blocked}
    if runq is not None:
        parts["runq"] = runq
    cause = max(parts, key=parts.get)
    if new_lowerings > 0 and cause != "runq":
        cause = "compile"
    return {"step": step, "wall_s": wall, "cpu_s": cpu, "runq_s": runq,
            "blocked_s": blocked, "major_faults": d["major_faults"],
            "cause": cause}
