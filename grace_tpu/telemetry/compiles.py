"""The compile ledger: every trace, lowering, compile and persistent-cache
read of this process, by function, from the events JAX fires itself.

``jax.monitoring`` reports, for every program JAX builds, three time spans
with a ``fun_name`` — ``/jax/core/compile/jaxpr_trace_duration`` (Python
tracing to a jaxpr), ``jaxpr_to_mlir_module_duration`` (lowering) and
``backend_compile_duration`` (XLA compile, or the read from the persistent
cache) — and fires ``/jax/compilation_cache/cache_hits`` and
``cache_misses`` as plain events. Importing this module registers one
listener for each of the two kinds and keeps what they hear in memory. It
sees the jit call path and the ahead-of-time
``fn.lower(...).compile()`` path alike, which a poll of a jitted function's
cache size cannot.

There is no switch and nothing runs per step: a compiled step fires no
event. A set-up fires thousands (every ``jnp`` function a step calls is
traced as a program of its own, nested inside the step's trace), so the
ledger keeps no span one by one: running sums per function, which the
recorder and the step's metrics read, and the union of all spans as
disjoint intervals, which ``setup_jit_wall_s`` reads (and the host ledger,
:mod:`~grace_tpu.telemetry.host`, to take JAX's time out of its own spans).

JAX also reports a plain duration when a compile is answered by the
persistent cache: ``/jax/compilation_cache/cache_retrieval_time_sec``, the
read and deserialisation, part of that program's ``compile`` span. A third
listener sums it (``setup_cache_read_s``, and the recorder's
``perf_setup``).

The ledger is per process, as JAX's listeners are: :data:`LEDGER` is the one
the listeners feed, and the module-level functions read it. JAX's events
carry a function's name and nothing else of it, so functions of one name
are summed under that name: ``grace_tpu.train`` gives every step it builds
a name of its own (``step.fun_name``). :func:`reset` is for tests.
"""

from __future__ import annotations

from jax import monitoring

__all__ = ["CompileLedger", "LEDGER", "summary", "wall_s", "intervals",
           "counts", "durations", "lowerings", "reset"]

_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_DURATIONS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
}
_NO_SPANS = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
             "lowerings": 0, "cache_hits": 0}


def _key(fun_name) -> str:
    """JAX names the trace of a function ``f`` and its lowering and compile
    ``jit(f)``: one key for the three."""
    name = str(fun_name)
    return name[4:-1] if name.startswith("jit(") and name.endswith(")") else name


class CompileLedger:
    """What the two listeners keep. Reads are pure functions of it."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self._by_name: dict[str, dict] = {}
        self._intervals: list[tuple[float, float]] = []   # disjoint, by start
        self._counts = dict.fromkeys(_COUNTS.values(), 0)
        self._durations = dict.fromkeys(_DURATIONS.values(), 0.0)
        self._lowerings = 0
        self._hits_attributed = 0

    # -- the listeners ------------------------------------------------------
    def on_span(self, event, start_s, end_s, fun_name="", **_) -> None:
        kind = _KINDS.get(event)
        if kind is None:
            return
        name = _key(fun_name)
        sums = self._by_name.get(name)
        if sums is None:
            sums = self._by_name[name] = dict(_NO_SPANS)
        sums[kind + "_s"] += end_s - start_s
        if kind == "lower":
            # A trace span says nothing of a retrace: JAX's tracing cache
            # answers in one too, a few microseconds long.
            sums["lowerings"] += 1
            self._lowerings += 1
        elif kind == "compile":
            # a hit is counted inside the compile span that it answers
            hits = self._counts["cache_hits"]
            sums["cache_hits"] += hits - self._hits_attributed
            self._hits_attributed = hits
        # Spans arrive as they end: an outer span swallows the nested ones
        # that ended inside it, which are the tail of the list.
        iv = self._intervals
        while iv and iv[-1][1] >= start_s:
            s0, e0 = iv.pop()
            start_s, end_s = min(start_s, s0), max(end_s, e0)
        iv.append((start_s, end_s))

    def on_event(self, event, **_) -> None:
        key = _COUNTS.get(event)
        if key is not None:
            self._counts[key] += 1

    def on_duration(self, event, duration_s, **_) -> None:
        key = _DURATIONS.get(event)
        if key is not None:
            self._durations[key] += duration_s

    # -- reads --------------------------------------------------------------
    def summary(self, fun_name) -> dict:
        """``trace_s``, ``lower_s``, ``compile_s`` of the function's own
        spans; ``lowerings`` (1 for a function that was never traced
        again) and ``cache_hits`` (compiles of it read from the persistent
        cache)."""
        return dict(self._by_name.get(_key(fun_name), _NO_SPANS))

    def wall_s(self) -> float:
        """Wall time spent tracing, lowering, compiling or reading the
        cache: the length of the union of all spans (a sum would count a
        nested trace twice)."""
        return sum(e - s for s, e in self._intervals)

    def intervals(self) -> list[tuple[float, float]]:
        """The union of all spans as disjoint ``(start, end)`` intervals on
        ``time.time()``'s clock, by start."""
        return list(self._intervals)

    def counts(self) -> dict:
        return dict(self._counts)

    def durations(self) -> dict:
        """``cache_read_s``: seconds spent reading and deserialising
        persistent-cache entries."""
        return dict(self._durations)

    def lowerings(self) -> int:
        """Lowerings of every function so far: a step during which this
        rises built a program."""
        return self._lowerings


LEDGER = CompileLedger()
monitoring.register_event_time_span_listener(LEDGER.on_span)
monitoring.register_event_listener(LEDGER.on_event)
monitoring.register_event_duration_secs_listener(LEDGER.on_duration)

summary = LEDGER.summary
wall_s = LEDGER.wall_s
intervals = LEDGER.intervals
counts = LEDGER.counts
durations = LEDGER.durations
lowerings = LEDGER.lowerings
reset = LEDGER.reset
