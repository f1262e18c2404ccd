#!/usr/bin/env python
"""graft-watch CLI: unified run timeline + anomaly report over one artifact.

Input: a JSONL run artifact as written by ``grace_tpu.telemetry.JSONLSink``
— telemetry metric rows, graft-watch summaries, ``watch_anomaly`` records,
guard/consensus transitions, ``perf_*`` profiling records, and
``lint_finding`` events, all in one stream. This tool is the read side:

* default / ``--timeline``: the merged, step-keyed timeline
  (:class:`grace_tpu.telemetry.Timeline`) — the answer to "what happened
  around step N" without hand-joining five record shapes;
* ``--anomalies``: re-run the streaming detectors
  (:class:`grace_tpu.telemetry.WatchMonitor`) over the artifact offline —
  so a run recorded *without* live detection can still be triaged — and
  list both the recorded and the re-derived findings;
* ``--baseline FILE``: regression gate. Compares the run's summary
  (anomaly counts by detector kind, max scores, first-anomaly step,
  guard/consensus activity) against a stored baseline
  (``--write-baseline``): new anomaly kinds, growing counts, rising max
  scores, or resilience events appearing where the baseline had none are
  regressions. The graft-lint/perf_report idiom: watch facts become
  CI-checkable.

Writes the ``WATCH_LAST.json`` evidence document (``--out ''``
disables). Pure host-side, usable on any box that holds the artifact.

Exit status: 0 clean, 1 baseline regression, 2 crash — CI-gateable.

Usage::

    python tools/graft_watch.py chaos_telemetry.jsonl
    python tools/graft_watch.py run.jsonl --anomalies
    python tools/graft_watch.py run.jsonl --json
    python tools/graft_watch.py run.jsonl --write-baseline WATCH_BASELINE.json
    python tools/graft_watch.py run.jsonl --baseline WATCH_BASELINE.json
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(ROOT, "WATCH_LAST.json")

# Headroom of the baseline gate on anomaly max scores: a detector score is
# already a ratio over its own threshold band, so growth beyond 25% over
# the baseline's worst episode is a real escalation, not jitter.
SCORE_RTOL = 0.25


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


def compare_to_baseline(current: dict, baseline: dict,
                        score_rtol: float = SCORE_RTOL) -> list:
    """Regression findings of a timeline summary against a stored one.

    Anomaly regressions: a detector kind fires that the baseline never
    saw, fires more often, or fires harder (max score beyond rtol).
    Resilience regressions: guard/consensus/lint events appear (or grow)
    where the baseline had fewer — watch is the early-warning layer, so
    the downstream layers lighting up IS the regression signal.
    """
    findings = []
    cur_by = current.get("anomalies_by_kind") or {}
    base_by = baseline.get("anomalies_by_kind") or {}
    for kind in sorted(cur_by):
        cur_n, base_n = cur_by[kind], base_by.get(kind, 0)
        if cur_n > base_n:
            findings.append(
                f"anomaly kind '{kind}': {cur_n} event(s) vs baseline "
                f"{base_n}" + (" (new kind)" if base_n == 0 else ""))
    cur_scores = current.get("anomaly_max_score") or {}
    base_scores = baseline.get("anomaly_max_score") or {}
    for kind, cur_s in sorted(cur_scores.items()):
        base_s = base_scores.get(kind)
        if base_s and cur_s > base_s * (1.0 + score_rtol):
            findings.append(
                f"anomaly kind '{kind}': max score {cur_s:.3g} vs "
                f"baseline {base_s:.3g} (+{100 * (cur_s / base_s - 1):.0f}%"
                f", tolerance {100 * score_rtol:.0f}%)")
    cur_counts = current.get("kind_counts") or {}
    base_counts = baseline.get("kind_counts") or {}
    for kind in ("guard", "consensus", "lint"):
        cur_n, base_n = cur_counts.get(kind, 0), base_counts.get(kind, 0)
        if cur_n > base_n:
            findings.append(
                f"{kind} events: {cur_n} vs baseline {base_n} — the "
                "downstream resilience layer fired more than the baseline "
                "run")
    cur_first = current.get("first_anomaly_step")
    base_first = baseline.get("first_anomaly_step")
    if cur_first is not None and base_first is not None \
            and cur_first < base_first:
        findings.append(
            f"first anomaly at step {cur_first} vs baseline {base_first} "
            "— the run degrades earlier than it used to")
    return findings


def baseline_view(summary: dict) -> dict:
    """The comparable subset of a timeline summary, for --write-baseline."""
    return {
        "anomalies": summary.get("anomalies", 0),
        "anomalies_by_kind": summary.get("anomalies_by_kind") or {},
        "anomaly_max_score": summary.get("anomaly_max_score") or {},
        "kind_counts": summary.get("kind_counts") or {},
        "first_anomaly_step": summary.get("first_anomaly_step"),
        "captured_at": _now(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("path", help="telemetry JSONL artifact (JSONLSink "
                                 "output)")
    ap.add_argument("--timeline", action="store_true",
                    help="render the merged step-keyed timeline (default "
                         "when no other view is selected)")
    ap.add_argument("--anomalies", action="store_true",
                    help="re-run the streaming detectors offline and list "
                         "recorded + re-derived anomalies")
    ap.add_argument("--kinds", default=None,
                    help="comma-separated timeline kind filter "
                         "(telemetry,watch,anomaly,guard,consensus,perf,"
                         "lint,other)")
    ap.add_argument("--limit", type=int, default=60,
                    help="max timeline lines (0 = unlimited)")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON document (summary + anomalies) "
                         "instead of text")
    ap.add_argument("--baseline", default=None,
                    help="stored baseline JSON to gate against "
                         "(--write-baseline output)")
    ap.add_argument("--write-baseline", default=None,
                    help="write the comparable summary subset to this "
                         "path")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="evidence document path ('' disables; default "
                         "WATCH_LAST.json at the repo root)")
    args = ap.parse_args(argv)

    from grace_tpu.telemetry.anomaly import WatchMonitor
    from grace_tpu.telemetry.timeline import Timeline

    timeline = Timeline.from_jsonl(args.path)
    summary = timeline.summary()

    recorded = [e.record for e in timeline.anomalies()]
    derived = []
    if args.anomalies:
        # Offline re-derivation: replay every non-anomaly record through a
        # fresh monitor. On a run that armed live detection this re-finds
        # the same episodes; on one that didn't, it's the triage pass.
        monitor = WatchMonitor()
        derived = monitor.observe(
            e.record for e in timeline if e.kind != "anomaly")

    regressions = []
    if args.baseline:
        with open(args.baseline) as f:
            baseline = json.load(f)
        regressions = compare_to_baseline(summary, baseline)

    doc = {
        "tool": "graft_watch",
        "artifact": args.path,
        **summary,
        "recorded_anomalies": recorded,
    }
    if args.anomalies:
        doc["derived_anomalies"] = derived
    if args.baseline:
        doc["baseline"] = args.baseline
        doc["regressions"] = regressions

    if args.write_baseline:
        _atomic_write(args.write_baseline, baseline_view(summary))
        print(f"[graft_watch] baseline -> {args.write_baseline}",
              file=sys.stderr)

    if args.out:
        # Uniform provenance stamp: the watch doc carries the same
        # n_devices/topology/git_rev triple as every other evidence
        # writer, lifted from the artifact's own provenance header.
        from grace_tpu.utils.logging import git_commit
        n_dev = (timeline.provenance or {}).get("n_devices")
        stamped = {**doc, "git_rev": git_commit(), "n_devices": n_dev,
                   "topology": ({"world": n_dev, "tiers": ["ici"],
                                 "slice": None, "region": None}
                                if n_dev else None),
                   "captured_at": _now()}
        try:
            _atomic_write(args.out, stamped)
        except OSError as e:
            print(f"[graft_watch] could not save {args.out}: {e}",
                  file=sys.stderr)

    if args.json:
        print(json.dumps(doc, indent=1))
    else:
        kinds = tuple(args.kinds.split(",")) if args.kinds else None
        if args.timeline or not args.anomalies:
            print(timeline.render(kinds=kinds,
                                  limit=args.limit or None))
            print()
        if args.anomalies:
            print(f"== anomalies (recorded {len(recorded)}, re-derived "
                  f"{len(derived)}) ==")
            # Dedup by identity, not dict equality — a re-derived finding
            # is "the same anomaly" when it names the same episode, even
            # if float formatting differs across the JSON round-trip.
            ident = lambda a: (a.get("step"), a.get("kind"),       # noqa: E731
                               a.get("metric"), a.get("rank"))
            known = {ident(a) for a in recorded}
            seen = recorded + [d for d in derived
                               if ident(d) not in known]
            for a in seen:
                print(f"  step {a.get('step', '?'):>6}: "
                      f"{a.get('kind', '?')}/{a.get('metric', '?')} "
                      f"rank={a.get('rank', -1)} "
                      f"score={a.get('score', 0):.3g} "
                      f"value={a.get('value', 0):.4g}")
            if not seen:
                print("  (none)")
            print()
        counts = summary.get("kind_counts") or {}
        print("== summary ==")
        print("  " + ", ".join(f"{k}: {v}" for k, v in
                               sorted(counts.items())))
        if summary.get("anomalous_ranks"):
            print(f"  anomalous ranks: {summary['anomalous_ranks']} "
                  f"(first anomaly at step "
                  f"{summary.get('first_anomaly_step')})")
        if args.baseline:
            if regressions:
                print(f"\nBASELINE REGRESSIONS ({len(regressions)}) vs "
                      f"{args.baseline}:")
                for r in regressions:
                    print(f"  REGRESSION {r}")
            else:
                print(f"\nbaseline {args.baseline}: within tolerance")
    return 1 if regressions else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:                                 # noqa: BLE001
        print(f"[graft_watch] crashed: {type(e).__name__}: {e}",
              file=sys.stderr)
        sys.exit(2)
