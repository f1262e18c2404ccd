"""In-graph telemetry: on-device metric rings, named trace stages, sinks,
cross-rank health aggregation, anomaly detection, and the run timeline.

Six layers (see each module's docstring for the design rationale):

* :mod:`~grace_tpu.telemetry.state` — the on-device
  :class:`TelemetryState` ring buffer that ``grace_transform(telemetry=…)``
  threads through the optimizer state, accumulating per-step scalars
  (gradient/update norms, residual health, compression error, *effective*
  wire bytes across the dense-fallback flip) with zero host syncs.
* :mod:`~grace_tpu.telemetry.aggregate` — graft-watch:
  ``grace_transform(watch=…)`` adds an in-graph *cross-rank* health
  summary every window (one tiny gated ``all_gather``; replicated
  mean/min/max + per-rank skew into :class:`WatchState`), wire cost
  folded into the ring's ``wire_bytes`` as ``watch_bytes``.
* :mod:`~grace_tpu.telemetry.reader` — :class:`TelemetryReader`, the host
  drain: one ``jax.device_get`` per N-step window, watch rings and guard
  counters bundled into the same transfer.
* :mod:`~grace_tpu.telemetry.anomaly` — streaming detectors
  (:class:`WatchMonitor`, armed via ``TelemetryReader(anomaly=…)``):
  robust per-rank skew outliers, EWMA spikes, wire-model drift, step-time
  and retrace anomalies → ``watch_anomaly`` sink records.
* :mod:`~grace_tpu.telemetry.timeline` — :class:`Timeline`, the unified
  step-keyed merge of every sink record kind (``tools/graft_watch.py``).
* :mod:`~grace_tpu.telemetry.sinks` — structured outputs
  (:class:`JSONLSink` with provenance headers and fsync-on-close
  durability, dependency-free :class:`TensorBoardSink`,
  :class:`MultiSink`).

Plus :func:`trace_stage` (:mod:`~grace_tpu.telemetry.scopes`), which names
the compress / exchange / decompress / memory-update stages in XLA op
metadata so ``utils.profiling.trace`` captures attributable Perfetto spans,
and the compile ledger (:mod:`~grace_tpu.telemetry.compiles`): every trace,
lowering, compile and cache read of the process, by function, from JAX's
own events. It is imported here so that it is listening before any entry
point builds a step. The host ledger (:mod:`~grace_tpu.telemetry.host`) is
imported before everything else of the package: the moment it loads is
"``grace_tpu`` began to import" in the set-up it accounts for.

IMPORT CONSTRAINT: modules in this package must not import
``grace_tpu.core`` / ``transform`` / ``resilience`` at module level —
``core.py`` imports :mod:`scopes`, so anything heavier would cycle. The
reader's ``GuardState`` lookup is deliberately lazy.
"""

from grace_tpu.telemetry import host  # first: see above
from grace_tpu.telemetry import compiles
from grace_tpu.telemetry.aggregate import (WATCH_FIELDS, WatchConfig,
                                           WatchState, watch_init,
                                           watch_record)
from grace_tpu.telemetry.anomaly import AnomalyConfig, WatchMonitor
from grace_tpu.telemetry.reader import TelemetryReader
from grace_tpu.telemetry.scopes import trace_stage
from grace_tpu.telemetry.sinks import (JSONLSink, MultiSink, Sink,
                                       TensorBoardSink)
from grace_tpu.telemetry.state import (FIELDS, TelemetryConfig,
                                       TelemetryState, telemetry_init,
                                       telemetry_record)
from grace_tpu.telemetry.timeline import Timeline

__all__ = [
    "FIELDS", "TelemetryConfig", "TelemetryState", "telemetry_init",
    "telemetry_record",
    "WATCH_FIELDS", "WatchConfig", "WatchState", "watch_init",
    "watch_record",
    "AnomalyConfig", "WatchMonitor",
    "Timeline",
    "TelemetryReader",
    "Sink", "JSONLSink", "TensorBoardSink", "MultiSink",
    "trace_stage",
    "compiles", "host",
]
