#!/usr/bin/env python
"""Render a telemetry JSONL run log as a per-stage / per-metric summary.

Input: a file written by ``grace_tpu.telemetry.JSONLSink`` — a provenance
header line followed by per-step metric records
(``TelemetryReader``) and guard-transition events (``GuardMonitor``).

Output (text, stdout): the provenance block, a per-metric stats table
(count / mean / min / max / last over the per-step records), wire-traffic
accounting including dense-fallback windows reconstructed from the
``fallback`` flag flips, a graft-watch section (cross-rank health
summaries and ``watch_anomaly`` findings, from
``grace_tpu.telemetry.aggregate``/``anomaly``), a profiling section
(step-time percentiles, compile/retrace events, memory watermarks, and the
GraceState footprint check, from ``grace_tpu.profiling.ProfileRecorder``'s
``perf_*`` records), and the guard event log — one report covers one run.
``--json`` emits the same content as one machine-readable document, so CI
consumes structure instead of scraping text. Pure stdlib — usable on any
box that holds the artifact, no jax required.

Usage::

    python tools/telemetry_report.py chaos_telemetry.jsonl
    python tools/telemetry_report.py run.jsonl --metrics grad_norm,wire_bytes
    python tools/telemetry_report.py run.jsonl --json
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional

# Metric columns in display order; anything else numeric found in records
# is appended after these.
PREFERRED = ["grad_norm", "update_norm", "residual_norm", "residual_max",
             "compression_error", "wire_bytes", "wire_bytes_ici",
             "wire_bytes_dcn", "dense_bytes", "fallback", "audit_bytes",
             "watch_bytes", "negotiation_bytes", "adapt_rung",
             "adapt_bytes"]


def load(path: str):
    provenance, records, events = None, [], []
    with open(path) as f:
        for lineno, line in enumerate(f, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError as e:
                print(f"[telemetry_report] {path}:{lineno}: bad JSON "
                      f"({e}); skipping", file=sys.stderr)
                continue
            if "provenance" in obj and provenance is None:
                provenance = obj["provenance"]
            elif "event" in obj:
                events.append(obj)
            else:
                records.append(obj)
    return provenance, records, events


def _stats(values: List[float]) -> dict:
    return {"count": len(values),
            "mean": sum(values) / len(values),
            "min": min(values),
            "max": max(values),
            "last": values[-1]}


def _fmt(v: float) -> str:
    if v == int(v) and abs(v) < 1e15:
        return f"{int(v):>12,d}"
    return f"{v:>12.6g}"


def fallback_windows(records: List[dict]) -> List[tuple]:
    """[(first_step, last_step), ...] of contiguous fallback==1 records."""
    windows, start, prev = [], None, None
    for rec in records:
        if rec.get("fallback"):
            if start is None:
                start = rec["step"]
            prev = rec["step"]
        elif start is not None:
            windows.append((start, prev))
            start = None
    if start is not None:
        windows.append((start, prev))
    return windows


def render(provenance, records, events,
           metrics: Optional[List[str]] = None) -> str:
    out = []
    out.append("== provenance ==")
    if provenance:
        for k, v in provenance.items():
            out.append(f"  {k}: {v}")
    else:
        out.append("  (no provenance header — was this file written by "
                   "JSONLSink?)")

    out.append("")
    out.append(f"== per-step metrics ({len(records)} records) ==")
    if records:
        steps = [r["step"] for r in records if "step" in r]
        if steps:
            out.append(f"  steps {min(steps)}..{max(steps)}")
        dropped = sum(r.get("dropped_steps", 0) for r in records)
        if dropped:
            out.append(f"  ring-wraparound dropped rows: {dropped} "
                       "(flush interval exceeded telemetry capacity)")
        numeric = [k for k in records[-1]
                   if isinstance(records[-1][k], (int, float))
                   and not isinstance(records[-1][k], bool)
                   and k != "step"]
        cols = [m for m in (metrics or PREFERRED) if any(m in r
                                                         for r in records)]
        cols += [k for k in sorted(numeric)
                 if k not in cols and metrics is None]
        head = f"  {'metric':<24s}{'count':>8s}" + "".join(
            f"{h:>13s}" for h in ("mean", "min", "max", "last"))
        out.append(head)
        for m in cols:
            vals = [float(r[m]) for r in records if m in r]
            if not vals:
                continue
            s = _stats(vals)
            out.append(f"  {m:<24s}{s['count']:>8d}"
                       + "".join(" " + _fmt(s[k])
                                 for k in ("mean", "min", "max", "last")))

        wire = [float(r["wire_bytes"]) for r in records if "wire_bytes" in r]
        dense = [float(r["dense_bytes"]) for r in records
                 if "dense_bytes" in r]
        if wire and dense:
            out.append("")
            out.append("== wire traffic ==")
            out.append(f"  effective bytes received per rank, total: "
                       f"{int(sum(wire)):,d} (raw dense gradient bytes "
                       f"{int(sum(dense)):,d}; ratio "
                       f"{sum(wire) / max(sum(dense), 1):.4f} — "
                       "communicator-aware, so allgather at scale can "
                       "legitimately exceed 1.0)")
            ici = [float(r["wire_bytes_ici"]) for r in records
                   if "wire_bytes_ici" in r]
            dcn = [float(r["wire_bytes_dcn"]) for r in records
                   if "wire_bytes_dcn" in r]
            wan = [float(r.get("wire_bytes_wan", 0.0)) for r in records
                   if "wire_bytes_ici" in r]
            if ici and dcn:
                tot = sum(ici) + sum(dcn) + sum(wan)
                wan_part = (f", wan {int(sum(wan)):,d} B" if sum(wan)
                            else "")
                out.append(
                    f"  per-link split: ici {int(sum(ici)):,d} B, "
                    f"dcn {int(sum(dcn)):,d} B{wan_part} "
                    f"({100.0 * sum(dcn) / max(tot, 1):.1f}% over DCN, "
                    f"{100.0 * sum(wan) / max(tot, 1):.1f}% over WAN — "
                    "flat communicators bill everything at the worst "
                    "tier they cross; a mixed split means a "
                    "hierarchical schedule)")
            wins = fallback_windows(records)
            if wins:
                spans = ", ".join(f"{a}..{b}" for a, b in wins)
                out.append(f"  dense-fallback windows (recorded steps): "
                           f"{spans}")
            else:
                out.append("  dense-fallback windows: none")
            out.append("  (logical payload bytes — XLA may pad/repack on "
                       "the wire; treat as the algorithmic lower bound, "
                       "see grace_tpu/utils/metrics.py)")
        guard_keys = sorted(k for k in records[-1] if k.startswith("guard_"))
        if guard_keys:
            out.append("")
            out.append("== guard counters (at last flush) ==")
            for k in guard_keys:
                out.append(f"  {k}: {records[-1][k]}")
    else:
        out.append("  (none)")

    perf = [e for e in events if str(e.get("event", "")).startswith("perf_")]
    watch = [e for e in events
             if e.get("event") in ("watch", "watch_anomaly")]
    lint = [e for e in events if e.get("event") == "lint_finding"]
    adapt = [e for e in events
             if str(e.get("event", "")).startswith("adapt")]
    other = [e for e in events
             if e not in perf and e not in watch and e not in lint
             and e not in adapt]
    if adapt or any("adapt_rung" in r and float(r["adapt_rung"]) >= 0
                    for r in records):
        out.append("")
        out.append("== adapt (graft-adapt rung transitions) ==")
        out.extend(_render_adapt(adapt, records))
    if watch:
        out.append("")
        out.append("== watch (graft-watch summaries + anomalies) ==")
        out.extend(_render_watch(watch))
    if perf:
        out.append("")
        out.append("== profiling (ProfileRecorder perf_* records) ==")
        out.extend(_render_perf(perf))
    if lint:
        out.append("")
        out.append(f"== static analysis ({len(lint)} lint_finding "
                   "event(s)) ==")
        out.extend(_render_lint(lint))

    out.append("")
    out.append(f"== guard events ({len(other)}) ==")
    for e in other:
        extras = {k: v for k, v in e.items() if k not in ("event", "step")}
        brief = ", ".join(f"{k}={v}" for k, v in sorted(extras.items())
                          if isinstance(v, (int, float, bool)))
        out.append(f"  step {e.get('step', '?'):>6}: {e['event']}"
                   + (f"  ({brief})" if brief else ""))
    if not other:
        out.append("  (none)")
    return "\n".join(out)


def _render_adapt(adapt: List[dict], records: List[dict]) -> List[str]:
    """graft-adapt controller trail: the rung trajectory from the metric
    rows' ``adapt_rung`` column plus one line per tighten/loosen
    transition event — rendered before the guard log because tightening
    ahead of the guard is the controller's whole claim."""
    out = []
    rungs = [(r["step"], int(r["adapt_rung"])) for r in records
             if "adapt_rung" in r and float(r["adapt_rung"]) >= 0
             and "step" in r]
    if rungs:
        lo = min(v for _, v in rungs)
        hi = max(v for _, v in rungs)
        out.append(f"  rung range over {len(rungs)} recorded steps: "
                   f"{lo}..{hi} (0 = dense escape; last "
                   f"{rungs[-1][1]} at step {rungs[-1][0]})")
        # Effective-rung dwell: how the state-dependent wire bill splits.
        counts: dict = {}
        for _, v in rungs:
            counts[v] = counts.get(v, 0) + 1
        dwell = ", ".join(f"rung {k}: {v}" for k, v in sorted(counts.items()))
        out.append(f"  dwell (steps per effective rung): {dwell}")
    tightens = [e for e in adapt if e.get("event") == "adapt_tighten"]
    loosens = [e for e in adapt if e.get("event") == "adapt_loosen"]
    out.append(f"  transitions: {len(tightens)} tighten(s), "
               f"{len(loosens)} loosen(s)")
    for e in adapt:
        out.append(f"    step {e.get('step', '?'):>6}: {e['event']} "
                   f"rung {e.get('from_rung', '?')} -> {e.get('rung', '?')}")
    if not adapt and not rungs:
        out.append("  (controller armed but no rows recorded)")
    return out


def _render_watch(watch: List[dict]) -> List[str]:
    """Cross-rank health summaries (one line per window) and anomaly
    findings — the early-warning layer, rendered before the guard log it
    is meant to preempt."""
    out = []
    summaries = [e for e in watch if e["event"] == "watch"]
    anomalies = [e for e in watch if e["event"] == "watch_anomaly"]
    if summaries:
        out.append(f"  {len(summaries)} cross-rank summaries "
                   f"(steps {summaries[0].get('step', '?')}"
                   f"..{summaries[-1].get('step', '?')})")
        worst = max(summaries, key=lambda e: e.get("skew_max", 0.0))
        out.append(
            f"  worst compression-error skew: {worst.get('skew_max', 0):.4g}"
            f" (rank {worst.get('skew_rank', '?')} at step "
            f"{worst.get('step', '?')}; relative to the cross-rank mean)")
        last = summaries[-1]
        for metric in ("grad_norm", "compression_error", "residual_norm"):
            mean = last.get(f"{metric}_mean")
            lo, hi = last.get(f"{metric}_min"), last.get(f"{metric}_max")
            if mean is None:
                continue
            out.append(f"  last window {metric}: mean {mean:.6g} "
                       f"(cross-rank min {lo:.6g} / max {hi:.6g})")
    if anomalies:
        out.append(f"  ANOMALIES ({len(anomalies)}):")
        for a in anomalies:
            rank = a.get("rank", -1)
            who = f"rank {rank}" if isinstance(rank, int) and rank >= 0 \
                else "fleet-wide"
            out.append(
                f"    step {a.get('step', '?'):>6}: "
                f"{a.get('kind', '?')}/{a.get('metric', '?')} ({who}) "
                f"score {a.get('score', 0):.3g} "
                f"threshold {a.get('threshold', 0):.3g} "
                f"value {a.get('value', 0):.4g}")
    else:
        out.append("  anomalies: none")
    return out


def _render_lint(lint: List[dict]) -> List[str]:
    """graft-lint ``lint_finding`` events (the chaos_smoke --lint gate and
    ``graft_lint --jsonl``), one line per finding with the same stage
    attribution the passes computed — so a schedulability/numeric/footprint
    finding lands in the unified run timeline next to the guard/consensus
    events of the step range it would have bitten."""
    out = []
    for e in lint:
        loc = str(e.get("config", "?"))
        if e.get("stage"):
            loc += f" [{e['stage']}]"
        out.append(f"  {str(e.get('severity', '?')).upper():7s} "
                   f"{str(e.get('pass', '?')):24s} {loc}")
        msg = str(e.get("message", ""))
        out.append(f"          {msg[:160]}" + ("…" if len(msg) > 160 else ""))
    return out


_STALL_CAUSES = {
    "runq": "runnable, but the machine gave the thread no CPU: the host's",
    "cpu": "the thread computed (Python, a collection): the program's",
    "compile": "a program was built during the step: a retrace",
    "blocked": "asleep in the runtime: the device, a transfer, an "
               "allocation, a lock",
}


def _s(value, digits: int = 2) -> str:
    """Seconds, or ``n/a`` for what the platform does not count."""
    return "n/a" if value is None else f"{value:.{digits}f}"


def _render_setup(e: dict) -> List[str]:
    """The host ledger's summary (``perf_setup``): process start through
    the first step, split into its named parts and what is left."""
    parts = [e.get(k) for k in ("pre_program_s", "jit_wall_s", "program_s")]
    total = e.get("to_first_step_s")
    left = (None if total is None or None in parts else total - sum(parts))
    ready = {True: "the chip was reached in it",
             False: "the chip was not reached yet: that lies between the "
                    "spans below"}.get(e.get("backends_ready_at_load"), "")
    out = [f"  set-up: {_s(total)} s from process start through the first "
           "step",
           f"    before grace_tpu began to import: {_s(parts[0])} s"
           + (f" ({ready})" if ready else ""),
           f"    JAX's trace, lowering and compile events: {_s(parts[1])} s, "
           f"of which reading the persistent cache {_s(e.get('cache_read_s'))}",
           f"    the program's own Python (the spans' self time): "
           f"{_s(parts[2])} s",
           f"    unnamed (the caller's code between the spans, the first "
           f"step's run): {_s(left)} s"]
    began = e.get("process_began")
    for span in e.get("spans", []):
        at = None if began is None else span["start"] - began
        out.append(
            f"    span {span['name']} at {_s(at)} s: "
            f"{_s(span['end'] - span['start'], 3)} s, self "
            f"{_s(span.get('self_s'), 3)}, process cpu {_s(span.get('cpu'), 3)}"
            f", runq {_s(span.get('runq'), 3)}, major faults "
            f"{span.get('major_faults', '?')}")
    if e.get("spans_dropped"):
        out.append(f"    spans not kept: {e['spans_dropped']}")
    built = e.get("built")
    if built:
        pressure = ", ".join(
            f"{r} {_s(built.get('pressure_' + r))}"
            for r in ("cpu", "memory", "io"))
        out.append(
            f"    when the last of {e.get('builds', '?')} programs was built:"
            f" process cpu {_s(built.get('cpu'))} s, runnable but waiting "
            f"{_s(built.get('runq'))} s, major faults "
            f"{built.get('major_faults', '?')}, involuntary switches "
            f"{built.get('involuntary_switches', '?')}; the machine's "
            f"pressure since boot (s): {pressure}")
    return out


def _render_perf(perf: List[dict]) -> List[str]:
    """Set-up's parts, step-time percentiles (last window wins — they are
    cumulative), stalled steps, compile/retrace events, memory watermarks,
    footprint check."""
    out = []
    for e in perf:
        if e["event"] == "perf_setup":
            out.extend(_render_setup(e))
    times = [e for e in perf if e["event"] == "perf_step_times"]
    if times:
        t = times[-1]
        order = ["mean_ms", "p50_ms", "p90_ms", "p99_ms", "max_ms"]
        keys = [k for k in order if k in t] + \
            [k for k in sorted(t) if k.endswith("_ms") and k not in order]
        pcts = ", ".join(f"{k[:-3]} {t[k]:.3f}" for k in keys)
        out.append(f"  step times (n={t.get('n_steps', '?')}): {pcts} ms")
        if t.get("sync_missing"):
            out.append("  WARNING: timed without sync_on() — these are "
                       "async-dispatch times, not step times")
        if t.get("failed_steps"):
            out.append(f"  failed steps recorded: {t['failed_steps']}")
    stalls = [e for e in perf if e["event"] == "perf_stall"]
    if stalls:
        out.append(f"  stalled steps: {len(stalls)} (wall over 1.5x the "
                   "running median and 50 ms over it; the loop's thread, "
                   "from the operating system's counters)")
        for e in stalls:
            runq = e.get("runq_s")
            out.append(
                f"    step {e.get('step', '?')}: wall {e['wall_s']:.3f} s = "
                f"cpu {e['cpu_s']:.3f} + runq "
                + ("n/a" if runq is None else f"{runq:.3f}")
                + f" + blocked {e['blocked_s']:.3f}; major faults "
                f"{e.get('major_faults', '?')}; cause {e['cause']} — "
                + _STALL_CAUSES.get(e["cause"], "?"))
    compiles = [e for e in perf if e["event"] == "perf_compile"]
    retraces = [e for e in perf if e["event"] == "perf_retrace"]
    if compiles or retraces:
        steps = ", ".join(str(e.get("step", "?")) for e in retraces)
        out.append(f"  compiles observed: {len(compiles)}; retraces: "
                   f"{len(retraces)}"
                   + (f" at step(s) {steps} — the step function recompiled "
                      "mid-run (weak-type/shape leak into carried state; "
                      "see graft-lint signature_stability)"
                      if retraces else ""))
    mems = [e for e in perf if e["event"] == "perf_memory"]
    if mems:
        m = mems[-1]
        peak = m.get("peak_bytes_in_use")
        cur = m.get("bytes_in_use")
        bits = []
        if peak is not None:
            bits.append(f"peak {peak:,d} B")
        if cur is not None:
            bits.append(f"in use {cur:,d} B")
        out.append(f"  device memory watermark (max over "
                   f"{m.get('n_devices', '?')} devices): "
                   + ", ".join(bits))
    feet = [e for e in perf if e["event"] == "perf_state_footprint"]
    if feet:
        f = feet[-1]
        out.append(
            f"  GraceState footprint: mem {f.get('mem_bytes', 0):,d} B, "
            f"comp {f.get('comp_bytes', 0):,d} B, "
            f"telem {f.get('telem_bytes', 0):,d} B")
        if "footprint_matches" in f:
            out.append("  footprint vs codec model: "
                       + ("matches" if f["footprint_matches"] else
                          "MISMATCH — live state was built under a "
                          "different config than reported"))
    if not out:
        out.append("  (perf records present but empty)")
    return out


def build_doc(provenance, records, events,
              metrics: Optional[List[str]] = None) -> dict:
    """Machine-readable twin of :func:`render` — the ``--json`` document
    CI consumes instead of scraping the text layout."""
    numeric = sorted({k for r in records for k, v in r.items()
                      if isinstance(v, (int, float))
                      and not isinstance(v, bool) and k != "step"})
    cols = [m for m in (metrics or PREFERRED)
            if any(m in r for r in records)]
    cols += [k for k in numeric if k not in cols and metrics is None]
    stats = {}
    for m in cols:
        vals = [float(r[m]) for r in records if m in r]
        if vals:
            stats[m] = _stats(vals)
    steps = [r["step"] for r in records if "step" in r]
    doc = {
        "provenance": provenance,
        "records": len(records),
        "step_span": [min(steps), max(steps)] if steps else None,
        "dropped_steps": sum(r.get("dropped_steps", 0) for r in records),
        "metrics": stats,
        "fallback_windows": [list(w) for w in fallback_windows(records)],
        "guard_counters": ({k: records[-1][k] for k in sorted(records[-1])
                            if k.startswith("guard_")} if records else {}),
        "watch_summaries": [e for e in events if e.get("event") == "watch"],
        "watch_anomalies": [e for e in events
                            if e.get("event") == "watch_anomaly"],
        "perf_events": [e for e in events
                        if str(e.get("event", "")).startswith("perf_")],
        "lint_findings": [e for e in events
                          if e.get("event") == "lint_finding"],
        "adapt_events": [e for e in events
                         if str(e.get("event", "")).startswith("adapt")],
        "guard_events": [e for e in events
                         if e.get("event") not in ("watch", "watch_anomaly",
                                                   "lint_finding")
                         and not str(e.get("event", "")).startswith("perf_")
                         and not str(e.get("event", "")).startswith("adapt")],
    }
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("path", help="telemetry JSONL file (JSONLSink output)")
    ap.add_argument("--metrics", default=None,
                    help="comma-separated metric subset to summarize")
    ap.add_argument("--json", action="store_true",
                    help="emit one machine-readable JSON document instead "
                         "of the text report")
    args = ap.parse_args(argv)
    provenance, records, events = load(args.path)
    metrics = args.metrics.split(",") if args.metrics else None
    if args.json:
        print(json.dumps(build_doc(provenance, records, events, metrics),
                         indent=1))
    else:
        print(render(provenance, records, events, metrics))
    return 0 if (records or events) else 1


if __name__ == "__main__":
    sys.exit(main())
