"""Runtime performance recorder: step times, retraces, memory watermarks.

:class:`ProfileRecorder` is the runtime twin of the offline trace analyzer
(:mod:`grace_tpu.profiling.trace_analysis`) and the dynamic twin of
graft-lint's ``signature_stability`` pass: where the static pass proves the
state signature is a fixed point *of the traced update*, the recorder
watches the program's lowerings and catches whatever escapes static analysis
(a data-dependent shape, a host wrapper rebuilding closures) the moment it
recompiles. It promotes :class:`grace_tpu.utils.profiling.StepTimer` from a
bench-local helper into the long-run observability stack:

* **set-up** — one ``perf_setup`` record at the first flush, the host
  ledger's summary (:mod:`grace_tpu.telemetry.host`):
  ``to_first_step_s`` (process start through that step, its compile
  included) beside its named parts,
  ``pre_program_s`` (process start until ``grace_tpu`` began to import;
  ``backends_ready_at_load`` says whether the chip was reached in it),
  ``jit_wall_s`` (JAX's trace, lowering and compile events, of which
  ``cache_read_s`` reading the persistent cache), ``program_s`` (the
  program's own Python on the set-up path), the ``spans`` one by one with
  their self time and the operating system's counters, and the ``built``
  mark (the process's CPU seconds, run-queue wait, faults and the
  machine's pressure when the last program was built);
* **step-time percentiles** (mean/p50/p90/p99/max over the steady window),
  emitted every flush as ``perf_step_times`` records — stamped with
  ``sync_missing`` when the timer only ever measured async dispatch, so a
  meaningless number carries its own caveat, and with ``stalls``, the
  count of stalled steps so far;
* **stalled steps** — one ``perf_stall`` record a step whose wall time was
  over 1.5 times the running steady median and at least 50 ms over it
  (:class:`~grace_tpu.utils.profiling.StepTimer` decides), with what the
  loop's thread did meanwhile, from the operating system's counters:
  ``wall_s``, ``cpu_s``, ``runq_s`` (runnable, waiting for a CPU),
  ``blocked_s`` (asleep in the runtime), ``major_faults`` and the largest
  part as ``cause`` (``runq`` | ``cpu`` | ``blocked``; ``compile`` when the
  compile ledger saw a new lowering during the step);
* **compile/retrace events** — ``perf_compile`` for the step function's
  first lowering, ``perf_retrace`` for each later one, read from the
  compile ledger (:mod:`grace_tpu.telemetry.compiles`, JAX's own events)
  and so carrying ``trace_s`` / ``lower_s`` / ``compile_s`` and
  ``cache_hit``, for the jit call path and ``fn.lower().compile()`` alike
  (each retrace silently doubles compile memory and stalls the device for
  seconds; a per-step retrace is the weak-type closure-leak bug class);
* **device-memory watermarks** (``perf_memory``: ``bytes_in_use`` /
  ``peak_bytes_in_use`` from the runtime's allocator stats, max across
  local devices; silently absent on backends without stats, e.g. CPU);
* **GraceState footprint accounting** (``perf_state_footprint``): the
  measured mem/comp/telem bytes of the live state, checked against the
  codec's *expected* footprint (the abstract shape of ``transform.init``
  — exact by construction, so any mismatch means the live state was built
  under a different config than the one being reported).

All records flow through the same :class:`grace_tpu.telemetry.Sink` funnel
as the telemetry reader and the guard/consensus monitors, so one JSONL
artifact carries the whole run — ``tools/telemetry_report.py`` renders the
``perf_*`` records in their own section.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from grace_tpu.telemetry import compiles, host
from grace_tpu.utils.profiling import StepTimer

__all__ = ["ProfileRecorder", "device_memory_watermarks",
           "grace_state_footprint", "expected_state_footprint",
           "check_state_footprint"]


def device_memory_watermarks(devices=None) -> Optional[Dict[str, int]]:
    """Max ``bytes_in_use`` / ``peak_bytes_in_use`` across local devices,
    from the runtime allocator's ``memory_stats()``. None when no local
    device reports stats (CPU backends)."""
    devices = list(devices) if devices is not None else jax.local_devices()
    in_use, peak = [], []
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            pass
        if not stats:
            continue
        if "bytes_in_use" in stats:
            in_use.append(int(stats["bytes_in_use"]))
        if "peak_bytes_in_use" in stats:
            peak.append(int(stats["peak_bytes_in_use"]))
    if not in_use and not peak:
        return None
    out: Dict[str, int] = {"n_devices": len(devices)}
    if in_use:
        out["bytes_in_use"] = max(in_use)
    if peak:
        out["peak_bytes_in_use"] = max(peak)
    return out


# ---------------------------------------------------------------------------
# GraceState footprint accounting
# ---------------------------------------------------------------------------

def _leaf_nbytes(leaf) -> int:
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize


def _tree_nbytes(tree) -> int:
    return sum(_leaf_nbytes(l) for l in jax.tree_util.tree_leaves(tree))


def grace_state_footprint(tree) -> Dict[str, int]:
    """Bytes held by every :class:`~grace_tpu.transform.GraceState` in
    ``tree``, grouped by component: ``mem`` (error-feedback residuals),
    ``comp`` (compressor state, e.g. PowerSGD Q), ``telem`` (the on-device
    metric ring), and ``bookkeeping`` (count/rng/fallback/audit scalars).
    Works on live arrays and on ``jax.eval_shape`` structures alike —
    that symmetry is what :func:`check_state_footprint` exploits. On a
    global (train-loop) state the mem/comp/telem leaves carry their sharded
    world axis, so the numbers are whole-mesh bytes, not per-device."""
    from grace_tpu.transform import GraceState

    mem = comp = telem = book = 0
    found = 0

    def visit(node):
        nonlocal mem, comp, telem, book, found
        if isinstance(node, GraceState):
            found += 1
            mem += _tree_nbytes(node.mem)
            comp += _tree_nbytes(node.comp)
            # The graft-watch summary ring is telemetry state: per-rank
            # sharded like the metric ring, world-independent row shape,
            # so it scales with `world` in expected_state_footprint
            # exactly like telem does.
            telem += _tree_nbytes((node.telem, node.watch))
            book += _tree_nbytes((node.count, node.rng_key, node.fallback,
                                  node.audit, node.adapt))
        return node

    jax.tree_util.tree_map(visit, tree,
                           is_leaf=lambda n: isinstance(n, GraceState))
    return {"grace_states": found,
            "mem_bytes": mem, "comp_bytes": comp, "telem_bytes": telem,
            "bookkeeping_bytes": book,
            "total_bytes": mem + comp + telem + book}


def expected_state_footprint(grace_or_tx, params,
                             world: int = 1) -> Dict[str, int]:
    """The codec's expected GraceState footprint for ``params``: the
    abstract shapes of ``transform.init`` (no allocation — safe on a
    device-free box), with the per-rank-sharded components (mem/comp/telem)
    scaled to ``world`` ranks to match the global layout
    ``init_train_state`` builds. ``grace_or_tx`` is a ``Grace`` bundle or
    a ready ``optax.GradientTransformation``."""
    tx = (grace_or_tx.transform(seed=0)
          if hasattr(grace_or_tx, "transform") else grace_or_tx)
    fp = grace_state_footprint(jax.eval_shape(tx.init, params))
    for key in ("mem_bytes", "comp_bytes", "telem_bytes"):
        fp[key] *= world
    fp["total_bytes"] = (fp["mem_bytes"] + fp["comp_bytes"]
                         + fp["telem_bytes"] + fp["bookkeeping_bytes"])
    return fp


def check_state_footprint(state, grace_or_tx, params,
                          world: int = 1) -> Dict[str, Any]:
    """Live GraceState bytes vs the expected model. ``matches`` compares
    the three per-codec components exactly — the model is the abstract
    init shape, so a mismatch means the live state was built under a
    different codec/fusion/telemetry config than the one being reported."""
    live = grace_state_footprint(state)
    model = expected_state_footprint(grace_or_tx, params, world=world)
    matches = all(live[k] == model[k]
                  for k in ("mem_bytes", "comp_bytes", "telem_bytes"))
    return {"live": live, "model": model, "matches": matches}


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

class ProfileRecorder:
    """Record step times, retraces, and memory through a telemetry sink.

    Usage::

        rec = ProfileRecorder(sink, every=25, step_fn=step)
        for i, batch in enumerate(batches):
            with rec.step():
                state, loss = step(state, batch)
                rec.sync_on(loss)
            rec.update(i)
        rec.flush(len(batches) - 1)

    ``step_fn`` (optional) enables retrace detection: the compile ledger is
    asked for the function's ``fun_name``. Without it only timing/memory
    records are emitted. ``grace_tpu.train`` gives each step it builds a
    ``fun_name`` no other program of the process has, so a process that
    builds several steps (a tuner's candidates, a rebuilt step after a
    reshard) records each on its own, and the first lowering of another
    step is no retrace of this one. A raw ``jax.jit`` wrapper is asked for
    by its ``__name__``, which JAX's events cannot tell from another
    function of that name: give a recorded function a name of its own. The
    ledger is per process, so the first record counts every lowering of
    the name so far, an ahead-of-time compile made before the recorder was
    built included. The
    recorder never touches the device between flushes — step timing is
    host wall-clock around the timer's sync fetch, memory stats are an
    allocator query, and the retrace probe reads a host-side dict — so it
    is safe on the hot path (contrast the host callbacks graft-lint's
    pass 4 rejects).
    """

    def __init__(self, sink=None, every: int = 20, warmup: int = 2,
                 step_fn=None, percentiles=(50, 90, 99)):
        if every < 1:
            raise ValueError(f"flush interval must be >= 1; got {every}")
        self.sink = sink
        self.every = every
        self.percentiles = tuple(percentiles)
        self.timer = StepTimer(warmup=warmup)
        self.retraces = 0        # lowerings after the first
        self.flushes = 0
        self._fun_name: Optional[str] = (
            getattr(step_fn, "fun_name", None)
            or getattr(step_fn, "__name__", None))
        self._seen: Optional[dict] = None     # the ledger's sums last reported
        self._stalls_emitted = 0
        self._first_step_done: Optional[float] = None    # time.time()

    # -- timing (delegates to the promoted StepTimer) -----------------------
    def step(self):
        return self.timer.step()

    def sync_on(self, out) -> None:
        self.timer.sync_on(out)

    # -- per-iteration hook -------------------------------------------------
    def update(self, step: int) -> List[dict]:
        """Call once per loop iteration (after the step). Asks the compile
        ledger every iteration — a retrace must be attributed to the step
        that caused it, not to a flush boundary — and emits the windowed
        records on every ``every``-th call."""
        if self._first_step_done is None:
            self._first_step_done = time.time()
        records = self._check_retrace(step)
        if (step + 1) % self.every == 0:
            records.extend(self.flush(step))
        return records

    def _check_retrace(self, step: int) -> List[dict]:
        if self._fun_name is None:
            return []
        now = compiles.summary(self._fun_name)
        seen = self._seen or dict.fromkeys(now, 0)
        new = now["lowerings"] - seen["lowerings"]
        if new <= 0:
            return []
        self._seen = now
        rec = {"event": "perf_retrace" if seen["lowerings"] else "perf_compile",
               "step": step, "cache_size": now["lowerings"],
               "trace_s": now["trace_s"] - seen["trace_s"],
               "lower_s": now["lower_s"] - seen["lower_s"],
               "compile_s": now["compile_s"] - seen["compile_s"],
               "cache_hit": now["cache_hits"] - seen["cache_hits"] == new}
        if seen["lowerings"]:
            self.retraces += new
            rec["retraces"] = self.retraces
        self._emit([rec])
        return [rec]

    def _setup_record(self, step: int) -> dict:
        """The host ledger's summary: what the process did from its start
        through the loop's first step (the first ``update``, or this
        flush), that step's compile included."""
        rec = {"event": "perf_setup", "step": step, **host.LEDGER.summary()}
        began = rec["process_began"]
        done = self._first_step_done or time.time()
        rec["to_first_step_s"] = None if began is None else done - began
        return rec

    def flush(self, step: int) -> List[dict]:
        """Emit the windowed records: a ``perf_stall`` for each step that
        stalled since the last flush, step-time percentiles and (when the
        backend reports allocator stats) the memory watermark; before them,
        at the first flush, one ``perf_setup``. ``step`` is the loop's
        index of the last step timed: a stall's own index is counted back
        from it."""
        records: List[dict] = []
        if not self.flushes:
            records.append(self._setup_record(step))
        if len(self.timer):
            # the loop's index of the timer's step 0
            first = step - (len(self.timer) - 1)
            for row in self.timer.stalls[self._stalls_emitted:]:
                records.append({"event": "perf_stall", **row,
                                "step": first + row["step"]})
            self._stalls_emitted = len(self.timer.stalls)
            arr = self.timer.steady * 1e3
            rec = {"event": "perf_step_times", "step": step,
                   "n_steps": int(arr.size),
                   "mean_ms": float(arr.mean()),
                   "max_ms": float(arr.max()),
                   "stalls": len(self.timer.stalls)}
            for q in self.percentiles:
                rec[f"p{q:g}_ms"] = float(np.percentile(arr, q))
            if self.timer.measured_async_dispatch:
                # dispatch-only timings: the number is not a step time
                rec["sync_missing"] = True
            if self.timer.failed_steps:
                rec["failed_steps"] = self.timer.failed_steps
            records.append(rec)
        mem = device_memory_watermarks()
        if mem is not None:
            records.append({"event": "perf_memory", "step": step, **mem})
        self.flushes += 1
        self._emit(records)
        return records

    def record_state_footprint(self, state, grace_or_tx=None, params=None,
                               world: int = 1, step: int = -1) -> dict:
        """One-shot GraceState footprint record (the footprint is fixed at
        init, so once per run is enough). With ``grace_or_tx`` + ``params``
        the live bytes are checked against the expected model and the
        record carries ``footprint_matches``."""
        rec: Dict[str, Any] = {"event": "perf_state_footprint", "step": step}
        if grace_or_tx is not None and params is not None:
            checked = check_state_footprint(state, grace_or_tx, params,
                                            world=world)
            rec.update(checked["live"])
            rec.update({f"model_{k}": v for k, v in checked["model"].items()
                        if k.endswith("_bytes")})
            rec["footprint_matches"] = checked["matches"]
        else:
            rec.update(grace_state_footprint(state))
        self._emit([rec])
        return rec

    def _emit(self, records: List[dict]) -> None:
        if self.sink is not None:
            for rec in records:
                self.sink.write(rec)

    def close(self) -> None:
        if self.sink is not None:
            self.sink.close()
