#!/usr/bin/env python
"""perf-report CLI: performance attribution from a saved profiler trace.

Renders what ``grace_tpu.profiling.trace_analysis`` extracts from a
``jax.profiler`` artifact (``trace.json.gz`` or raw ``xplane.pb``, or a
profile directory): the per-stage device-time table over the canonical
``grace/...`` vocabulary (summing exactly to total device time), the
compute-vs-collective split, the **overlap fraction** (collective time
hidden under compute — the number the tuner's projection model assumes
is zero), and step-time percentiles from the trace's step markers.

Optionally gates against a stored baseline with a tolerance band (the
graft-lint idiom: measured perf facts become CI-checkable), and writes the
``PROF_LAST.json`` evidence document.

Pure host-side: runs on a CPU-only box with no devices against a saved
trace (pinned by tests/test_profiling.py on the canned fixture
``tests/data/perf_trace.json.gz``).

Exit status: 0 clean, 1 baseline regression, 2 crash — CI-gateable.

Usage::

    python tools/perf_report.py --trace profiles/topk1pct
    python tools/perf_report.py --trace tests/data/perf_trace.json.gz
    python tools/perf_report.py --trace t.json.gz --write-baseline PROF_BASELINE.json
    python tools/perf_report.py --trace t.json.gz --baseline PROF_BASELINE.json
    python tools/perf_report.py --trace t.json.gz --json
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(ROOT, "PROF_LAST.json")

# Tolerance band of the baseline gate. Relative for times (a step-time or
# stage-time growth beyond rtol is a regression), absolute for the overlap
# fraction (already a ratio; a 5-point drop means hidden collective time
# became exposed wall-clock). Improvements never fail.
DEFAULT_RTOL = 0.10
STAGE_ATOL_MS = 0.05          # ignore sub-50µs stage jitter
OVERLAP_ATOL = 0.05


def compare_to_baseline(current: dict, baseline: dict,
                        rtol: float) -> list:
    """Regression findings of ``current`` (an ``TraceAnalysis.as_dict``)
    against a stored baseline of the same shape. Time-like metrics regress
    upward; overlap fraction regresses downward."""
    findings = []

    def worse(name, cur, base, atol=0.0):
        if cur is None or base is None:
            return
        if cur > base * (1.0 + rtol) + atol:
            findings.append(
                f"{name}: {cur:.3f} vs baseline {base:.3f} "
                f"(+{100.0 * (cur / base - 1.0) if base else 0.0:.1f}%, "
                f"tolerance {100.0 * rtol:.0f}%)")

    cur_steps = current.get("step_times") or {}
    base_steps = baseline.get("step_times") or {}
    worse("step p50 ms", cur_steps.get("p50_ms"), base_steps.get("p50_ms"))
    worse("step p99 ms", cur_steps.get("p99_ms"), base_steps.get("p99_ms"))
    worse("total device ms", current.get("total_device_ms"),
          baseline.get("total_device_ms"))
    base_stages = baseline.get("stages_ms") or {}
    for stage, base_ms in sorted(base_stages.items()):
        worse(f"stage {stage} ms",
              (current.get("stages_ms") or {}).get(stage),
              base_ms, atol=STAGE_ATOL_MS)
    cur_ov = current.get("overlap_fraction")
    base_ov = baseline.get("overlap_fraction")
    if cur_ov is not None and base_ov is not None \
            and cur_ov < base_ov - OVERLAP_ATOL:
        findings.append(
            f"overlap fraction: {cur_ov:.3f} vs baseline {base_ov:.3f} "
            f"(collective time that used to hide under compute is now "
            f"exposed; tolerance {OVERLAP_ATOL:.2f} absolute)")
    return findings


def baseline_view(analysis_dict: dict) -> dict:
    """The comparable subset of an analysis, for --write-baseline."""
    return {
        "step_times": analysis_dict.get("step_times"),
        "total_device_ms": analysis_dict.get("total_device_ms"),
        "stages_ms": analysis_dict.get("stages_ms"),
        "overlap_fraction": analysis_dict.get("overlap_fraction"),
        "trace": analysis_dict.get("trace"),
        "captured_at": _now(),
    }


def _now() -> str:
    return datetime.datetime.now(
        datetime.timezone.utc).isoformat(timespec="seconds")


def _atomic_write(path: str, doc: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--trace", required=True,
                    help="profiler artifact (trace.json.gz / xplane.pb) "
                         "or a profile directory (newest capture wins)")
    ap.add_argument("--baseline", default=None,
                    help="stored baseline JSON to gate against "
                         "(--write-baseline output)")
    ap.add_argument("--rtol", type=float, default=DEFAULT_RTOL,
                    help="relative tolerance of the baseline gate "
                         f"(default {DEFAULT_RTOL})")
    ap.add_argument("--write-baseline", default=None,
                    help="write the comparable metric subset to this path "
                         "and exit clean")
    ap.add_argument("--overlap-config", default=None,
                    help="registry config name (tools/graft_lint.py --list) "
                         "to sandwich the trace's measured overlap fraction "
                         "against: measured must stay <= graft-flow's "
                         "static schedulability bound (+slack) for that "
                         "config's traced dataflow; a violation means the "
                         "capture's attribution is lying and exits 1")
    ap.add_argument("--json", action="store_true",
                    help="emit a JSON document instead of text")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="evidence document path ('' disables; default "
                         "PROF_LAST.json at the repo root)")
    args = ap.parse_args(argv)

    # The analyzer is pure host-side (stdlib + numpy over a saved trace),
    # but grace_tpu imports jax at package load — keep an offline report
    # off whatever accelerator the box has.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from grace_tpu.profiling import analyze_trace

    analysis = analyze_trace(args.trace)
    doc = analysis.as_dict()
    if os.sep + os.path.join("tests", "data") + os.sep in \
            os.path.abspath(str(doc.get("trace") or "")):
        doc["note"] = ("canned CPU fixture trace — pipeline evidence, "
                       "not a chip capture")

    regressions = []
    if args.baseline:
        with open(args.baseline) as f:
            baseline = json.load(f)
        regressions = compare_to_baseline(doc, baseline, args.rtol)
        doc["baseline"] = args.baseline
        doc["baseline_rtol"] = args.rtol
        doc["regressions"] = regressions

    if args.overlap_config:
        # The measured<=possible overlap sandwich: graft-flow's
        # schedulability pass computes the byte-weighted static upper bound
        # the config's dataflow permits, and judges THIS capture's measured
        # overlap against it (meta['measured_overlap'] — the same hook the
        # lint tests use). A measured fraction above the bound is not the
        # scheduler over-performing; it is the trace attribution lying.
        from grace_tpu.analysis import AUDIT_CONFIGS, build_grace, \
            overlap_summary, trace_update
        from grace_tpu.analysis.flow import (OVERLAP_SLACK,
                                             pass_overlap_schedulability)
        entry = next((e for e in AUDIT_CONFIGS
                      if e["name"] == args.overlap_config), None)
        if entry is None:
            print(f"unknown config {args.overlap_config!r}; "
                  "tools/graft_lint.py --list shows the registry",
                  file=sys.stderr)
            return 2
        measured = doc.get("overlap_fraction")
        grace = build_grace(entry)
        traced = trace_update(
            grace, name=entry["name"],
            meta={"grace": grace, "measured_overlap": measured})
        bound = overlap_summary(traced)["static_overlap_bound"]
        sandwich = {
            "config": entry["name"],
            "measured_overlap": measured,
            "static_overlap_bound": (round(bound, 6)
                                     if bound is not None else None),
            "slack": OVERLAP_SLACK,
        }
        violations = [f.message for f in pass_overlap_schedulability(traced)
                      if "measured overlap" in f.message]
        sandwich["violations"] = violations
        doc["overlap_sandwich"] = sandwich
        regressions = regressions + violations

    if args.write_baseline:
        _atomic_write(args.write_baseline, baseline_view(doc))
        print(f"[perf_report] baseline -> {args.write_baseline}",
              file=sys.stderr)

    if args.out:
        evidence = {"tool": "perf_report", **doc, "captured_at": _now()}
        try:
            _atomic_write(args.out, evidence)
        except OSError as e:
            print(f"[perf_report] could not save {args.out}: {e}",
                  file=sys.stderr)

    if args.json:
        print(json.dumps(doc, indent=1))
    else:
        print(analysis.render())
        if args.overlap_config:
            s = doc["overlap_sandwich"]
            print()
            print(f"overlap sandwich vs {s['config']}: measured="
                  f"{s['measured_overlap']} <= static bound="
                  f"{s['static_overlap_bound']} (+{s['slack']} slack): "
                  + ("VIOLATED" if s["violations"] else "holds"))
        if args.baseline:
            print()
            if regressions:
                print(f"BASELINE REGRESSIONS ({len(regressions)}) vs "
                      f"{args.baseline}:")
                for r in regressions:
                    print(f"  REGRESSION {r}")
            else:
                print(f"baseline {args.baseline}: within tolerance "
                      f"(rtol {args.rtol})")
    return 1 if regressions else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:                                 # noqa: BLE001
        print(f"[perf_report] crashed: {type(e).__name__}: {e}",
              file=sys.stderr)
        sys.exit(2)
