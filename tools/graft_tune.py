#!/usr/bin/env python
"""graft-tune CLI: topology-aware automatic config selection.

Enumerates (codec, communicator, fusion, pallas, precision) candidates
from the audited registry plus generated variants, prunes them statically
(capability gates, numeric safety at the target world, per-link wire
pricing under the target topology, graft-flow overlap/numeric/footprint
passes), measures the shortlist with real timed steps, and stamps a
provenance-carrying winner config into ``TUNE_LAST.json`` — gated by the
measured≤static overlap sandwich. See grace_tpu/tuning/ and IMPLEMENTING.md
"Static prune → measured shortlist → sandwich gate".

Exit status: 0 clean, 1 gate violation (no measurable winner, or the
winner's overlap sandwich is violated), 2 crash/usage — CI-gateable.

Usage::

    python tools/graft_tune.py --static-only              # rank, don't run
    python tools/graft_tune.py --topology 8               # single slice, W=8
    python tools/graft_tune.py --topology 256,8 --static-only
    python tools/graft_tune.py --topology 8 --shortlist 3 --json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The default --static-only survey: the single-slice regime every
# committed measurement ran in, and the xslice projection topology the
# hier communicator was built for.
DEFAULT_TOPOLOGIES = ("8", "256,8")


def _render(doc: dict) -> str:
    out = []
    for label, st in doc["static"].items():
        c = st["counts"]
        out.append(f"== static ranking @ {label} "
                   f"(model={doc['model']}) ==")
        out.append(
            f"funnel: {c['enumerated']} enumerated -> "
            f"{c['capability_rejected']} capability-rejected, "
            f"{c['numeric_rejected']} numeric-rejected, "
            f"{c['degradation_rejected']} degradation-rejected -> "
            f"{c['priced']} priced -> {c['flow_rejected']} flow-rejected "
            f"-> {c['shortlisted']} shortlisted")
        for i, r in enumerate(st["ranking"][:10]):
            mark = "*" if r["verdict"] == "shortlisted" else " "
            out.append(
                f" {mark}{i + 1:2d}. {r['candidate']:36s} "
                f"proj {r['projected_step_ms']:.4f} ms  "
                f"x{r['predicted_speedup_vs_dense']} vs dense  "
                f"(ici {r['ici_bytes']:,} B / dcn {r['dcn_bytes']:,} B)")
        out.append("")
    m = doc.get("measured")
    if m:
        out.append(f"== measured shortlist @ {doc['target']} "
                   f"(world={m['measured_world']}, {m['repeats']}x"
                   f"{m['timed_steps']} steps) ==")
        for r in m["rows"]:
            out.append(
                f"  {r['candidate']:36s} measured "
                f"{r['measured_step_ms']:.3f} ms "
                f"(dense {r['baseline_step_ms']:.3f}) -> projected "
                f"{r['projected_step_ms']:.3f} ms at target")
        for s in m["skipped"]:
            out.append(f"  {s['candidate']:36s} SKIPPED: {s['reason']}")
        out.append("")
    w = doc.get("winner")
    if w:
        s = w["overlap_sandwich"]
        out.append(f"WINNER: {w['candidate']} @ {doc['target']}")
        out.append(f"  grace_from_params({json.dumps(w['grace_params'])})")
        out.append(
            f"  sandwich: measured={s['measured_overlap']} <= "
            f"static bound={s['static_overlap_bound']} (+{s['slack']}): "
            + ("holds" if s["holds"] else "VIOLATED"))
    if doc.get("error"):
        out.append(f"ERROR: {doc['error']}")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--topology", action="append", default=[],
                    help="target mesh as 'W' or 'W,slice_size' (repeatable;"
                         " first one is the decision target; default: "
                         + " + ".join(DEFAULT_TOPOLOGIES) + ")")
    ap.add_argument("--model", default="toy",
                    help="param tree to price and measure against "
                         "('toy' — the audit registry's model; 'resnet50'"
                         " is priced statically only)")
    ap.add_argument("--shortlist", type=int, default=3,
                    help="how many ranked survivors to measure (default 3)")
    ap.add_argument("--static-only", action="store_true",
                    help="enumerate + prune + rank only; no timed steps")
    ap.add_argument("--timed-steps", type=int, default=8,
                    help="steps per timing window (default 8)")
    ap.add_argument("--repeats", type=int, default=2,
                    help="interleaved dense/candidate sample pairs "
                         "(default 2)")
    ap.add_argument("--audit-world", type=int, default=8,
                    help="abstract mesh size for the flow passes "
                         "(default 8)")
    ap.add_argument("--json", action="store_true",
                    help="emit the evidence document instead of text")
    ap.add_argument("--out", default=None,
                    help="evidence path ('' disables; default TUNE_LAST."
                         "json at the repo root)")
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    on_cpu = os.environ["JAX_PLATFORMS"].lower() == "cpu"
    if not args.static_only and on_cpu:
        # The measured shortlist needs a real mesh; mirror the test
        # harness's 8 simulated devices. Must run BEFORE the first
        # jax.devices() call — backend init freezes the device count.
        from grace_tpu.parallel import (relax_cpu_collective_timeouts,
                                        set_cpu_device_count)
        set_cpu_device_count(8)
        relax_cpu_collective_timeouts()

    from grace_tpu.tuning import (TUNE_EVIDENCE_PATH, run_tune,
                                  write_tune_evidence)

    topologies = tuple(args.topology) or DEFAULT_TOPOLOGIES
    doc = run_tune(topologies, model=args.model,
                   shortlist_n=args.shortlist,
                   static_only=args.static_only,
                   audit_world=args.audit_world,
                   timed_steps=args.timed_steps, repeats=args.repeats,
                   argv=" ".join(sys.argv[1:]))

    out = TUNE_EVIDENCE_PATH if args.out is None else args.out
    if out:
        try:
            write_tune_evidence(doc, out)
        except OSError as e:
            print(f"[graft_tune] could not save {out}: {e}",
                  file=sys.stderr)

    if args.json:
        print(json.dumps(doc, indent=1))
    else:
        print(_render(doc))
    return 0 if doc.get("ok") else 1


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:                                 # noqa: BLE001
        print(f"[graft_tune] crashed: {type(e).__name__}: {e}",
              file=sys.stderr)
        sys.exit(2)
