"""Performance attribution: the read side of the observability stack.

PR 2 made every pipeline stage *writable* into a device trace (canonical
``grace/...`` scopes, :mod:`grace_tpu.telemetry.scopes`); this package
reads the evidence back:

* :mod:`~grace_tpu.profiling.trace_analysis` — parse a ``jax.profiler``
  artifact (``trace.json.gz`` or raw ``xplane.pb``) into a per-stage
  device-time breakdown, a compute-vs-collective split, an **overlap
  fraction** (collective time hidden under compute, from device timelines),
  and step-time percentiles. Pure host-side; runs on a CPU-only box against
  a saved trace.
* :mod:`~grace_tpu.profiling.trace_export` — the write side: spans back
  out as Chrome-trace JSON (``parse_chrome_trace`` round-trips it
  exactly) plus :func:`merge_host_traces` so a multi-host capture ships
  one merged per-hop/per-tier timeline.
* :mod:`~grace_tpu.profiling.recorder` — :class:`ProfileRecorder`, the
  runtime side: step-time percentiles, compile/retrace events (the dynamic
  twin of graft-lint's ``signature_stability`` pass), device-memory
  watermarks, and GraceState footprint accounting checked against the
  codec's expected model — all emitted through the existing telemetry
  sinks.

CLI: ``tools/perf_report.py`` (stage table + overlap % + percentiles +
baseline gating, writes ``PROF_LAST.json``).
"""

from grace_tpu.profiling.recorder import (ProfileRecorder,
                                          check_state_footprint,
                                          device_memory_watermarks,
                                          expected_state_footprint,
                                          grace_state_footprint)
from grace_tpu.profiling.trace_analysis import (Span, TraceAnalysis,
                                                analyze_spans, analyze_trace,
                                                enrich_spans,
                                                find_latest_trace,
                                                hlo_scope_map,
                                                interval_union_us,
                                                load_trace_events,
                                                overlap_us,
                                                parse_chrome_trace,
                                                parse_xplane)
from grace_tpu.profiling.trace_export import (chrome_trace_doc,
                                              merge_host_traces,
                                              write_chrome_trace)

__all__ = [
    "ProfileRecorder", "check_state_footprint",
    "device_memory_watermarks", "expected_state_footprint",
    "grace_state_footprint",
    "Span", "TraceAnalysis", "analyze_spans", "analyze_trace",
    "enrich_spans", "find_latest_trace", "hlo_scope_map",
    "interval_union_us", "load_trace_events", "overlap_us",
    "parse_chrome_trace", "parse_xplane",
    "chrome_trace_doc", "merge_host_traces", "write_chrome_trace",
]
