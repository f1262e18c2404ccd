#!/usr/bin/env python
"""graft-lint CLI: static SPMD collective auditor + repo rule engine.

Traces registered codec x communicator x resilience configs to jaxprs on an
AbstractMesh (no devices, CPU-only, CI-safe) and runs the ten audit
passes — the four jaxpr walkers (collective consistency across cond
branches, bit-exactness of cross-replica reductions, wire-byte
reconciliation against Communicator.recv_wire_bytes, retrace/host-sync
sniffing), the three graft-flow dependence-graph passes (overlap
schedulability: static overlap bounds and independent compress→exchange
chain counting; numeric-range safety: fp16 accumulation overflow, vote
integer-exactness, index/pack-width contracts; HBM footprint: GraceState
accounting vs the config's own eval_shape model, replicated-O(W) buffers),
and the three graft-sound stateful-semantics passes (rng lineage:
independent stochastic sites must consume independently derived,
replicated PRNG keys; rollback coverage: every state leaf a guarded step
writes is restored by a rollback select or declared written-through;
replication contract: replicated GraceState fields provably leave the
step replicated, and the field-role constants agree with partition_specs)
— plus the AST-level repo rules (compressor capability declarations,
telemetry FIELDS reducers, pytest marker registration, GraceState
field-role coverage). See grace_tpu/analysis/ and IMPLEMENTING.md "What
graft-lint checks and why".

A full-matrix run lands LINT_LAST.json and attaches it to the evidence
ledger (id ``lint-clean``, claim_class measured) so README lint-clean
claims can carry ``<!-- evidence: -->`` markers through the graft-gate.

Exit status: 0 clean, 1 findings, 2 crash — CI-gateable.

Usage::

    python tools/graft_lint.py                   # repo rules + core configs
    python tools/graft_lint.py --all-configs     # the full compat matrix
    python tools/graft_lint.py --config topk-ring --config qsgd-ring
    python tools/graft_lint.py --all-configs --passes numeric_safety
    python tools/graft_lint.py --all-configs \
        --passes rng_lineage,rollback_coverage,replication_contract
    python tools/graft_lint.py --all-configs --json
    python tools/graft_lint.py --all-configs --jsonl lint_findings.jsonl
    python tools/graft_lint.py --list            # show registry names
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The headline subset audited on a default (argument-free) run: one config
# per communicator family plus the resilience stack — fast enough for a
# pre-commit hook; --all-configs is the CI spelling.
CORE_CONFIGS = ("topk-allgather", "none-allreduce", "qsgd-ring",
                "topk-twoshot", "signsgd-sign_allreduce",
                "topk-allgather-bucketed", "qsgd4-allgather-packed",
                "topk-escape-telemetry", "topk-guard-consensus")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--all-configs", action="store_true",
                    help="audit the full registered compat matrix "
                         "(default: repo rules + a core subset)")
    ap.add_argument("--config", action="append", default=[],
                    help="audit only the named registry config(s)")
    ap.add_argument("--rules-only", action="store_true",
                    help="run only the AST repo rules (no tracing)")
    ap.add_argument("--no-rules", action="store_true",
                    help="skip the AST repo rules")
    ap.add_argument("--passes", default=None,
                    help="comma-separated pass subset (intersected with "
                         "each config's own pass selection; configs with "
                         "an empty intersection are skipped)")
    ap.add_argument("--evidence", default=None,
                    help="where --all-configs writes its LINT_LAST.json "
                         "evidence (default: the repo root copy)")
    ap.add_argument("--world", type=int, default=8,
                    help="abstract mesh size to trace at (default 8)")
    ap.add_argument("--json", action="store_true",
                    help="emit a JSON document instead of text")
    ap.add_argument("--jsonl", default=None,
                    help="also append findings as lint_finding events to "
                         "this JSONL file (telemetry_report.py-compatible)")
    ap.add_argument("--list", action="store_true",
                    help="list registered config names and exit")
    args = ap.parse_args(argv)

    # Tracing never executes anything: stay off the chip unless told.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from grace_tpu.analysis import (AUDIT_CONFIGS, PASS_NAMES, audit_all,
                                    render_text, findings_to_json,
                                    run_repo_rules, write_jsonl, RULE_NAMES)

    if args.list:
        for entry in AUDIT_CONFIGS:
            print(f"{entry['name']:30s} mode={entry['mode']:6s} "
                  f"passes={','.join(entry['passes'])}")
        return 0

    if args.config:
        by_name = {e["name"]: e for e in AUDIT_CONFIGS}
        unknown = [n for n in args.config if n not in by_name]
        if unknown:
            print(f"unknown config(s) {unknown}; --list shows the registry",
                  file=sys.stderr)
            return 2
        configs = [by_name[n] for n in args.config]
    elif args.all_configs:
        configs = list(AUDIT_CONFIGS)
    else:
        configs = [e for e in AUDIT_CONFIGS if e["name"] in CORE_CONFIGS]
    if args.passes:
        selected = tuple(p.strip() for p in args.passes.split(",")
                         if p.strip())
        unknown = [p for p in selected if p not in PASS_NAMES]
        if unknown:
            print(f"unknown pass(es) {unknown}; registered: "
                  f"{', '.join(PASS_NAMES)}", file=sys.stderr)
            return 2
        configs = [dict(e, passes=tuple(p for p in e["passes"]
                                        if p in selected))
                   for e in configs]
        configs = [e for e in configs if e["passes"]]
    if args.rules_only:
        configs = []

    findings = []
    rules_checked = 0
    if not args.no_rules:
        findings.extend(run_repo_rules())
        rules_checked = len(RULE_NAMES)
    progress = None
    if not args.json:
        progress = lambda name: print(f"[graft_lint] tracing {name}",  # noqa: E731
                                      file=sys.stderr, flush=True)
    findings.extend(audit_all(configs, world=args.world, progress=progress))

    if args.all_configs and not args.rules_only:
        # Evidence artifact: the last full-matrix lint verdict. Atomic
        # tmp+replace like the rest of the evidence flow.
        import datetime
        import json as _json
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        # Per-pass finding counts over every pass that could have run —
        # zeros are evidence too (a pass that ran clean is a different
        # statement than a pass that never ran).
        passes_run = sorted({p for e in configs for p in e["passes"]})
        pass_counts = {p: sum(1 for f in findings if f.pass_name == p)
                       for p in passes_run}
        # Static overlap bounds for every bucketed (fusion=<int>) config:
        # the static half of the measured<=possible overlap sandwich, kept
        # in the evidence so a later chip capture (tools/perf_report.py
        # --overlap-config) is judged against the bound the lint run that
        # blessed the schedule actually computed.
        from grace_tpu.analysis import overlap_bound_report
        overlap_bounds = {}
        for e in configs:
            try:
                rep = overlap_bound_report(e, world=args.world)
            except Exception as err:            # noqa: BLE001
                rep = {"error": f"{type(err).__name__}: {err}"}
            if rep is not None:
                overlap_bounds[e["name"]] = rep
        doc = {
            "tool": "graft_lint",
            "errors": sum(1 for f in findings if f.severity == "error"),
            "warnings": sum(1 for f in findings if f.severity != "error"),
            "configs_audited": len(configs),
            "rules_checked": rules_checked,
            "world": args.world,
            "passes_run": passes_run,
            "pass_counts": pass_counts,
            "overlap_bounds": overlap_bounds,
            "findings": [f.as_dict() for f in findings],
            "captured_at": datetime.datetime.now(
                datetime.timezone.utc).isoformat(timespec="seconds"),
        }
        path = args.evidence or os.path.join(root, "LINT_LAST.json")
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                _json.dump(doc, f, indent=1)
                f.write("\n")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError as e:
            print(f"[graft_lint] could not save {path}: {e}",
                  file=sys.stderr)

    if args.jsonl:
        try:
            from grace_tpu.utils.logging import run_provenance
            provenance = run_provenance(data="static", tool="graft_lint",
                                        argv=" ".join(sys.argv[1:]))
        except Exception:
            provenance = None
        write_jsonl(findings, args.jsonl, provenance=provenance)
    if args.json:
        print(findings_to_json(findings, audited=len(configs),
                               rules_checked=rules_checked))
    else:
        print(render_text(findings, audited=len(configs),
                          rules_checked=rules_checked))
    return 1 if any(f.severity == "error" for f in findings) else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:                                 # noqa: BLE001
        print(f"[graft_lint] crashed: {type(e).__name__}: {e}",
              file=sys.stderr)
        sys.exit(2)
