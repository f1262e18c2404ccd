#!/usr/bin/env python
"""graft-wire CLI: static HBM-traffic projection of the fused wire path.

PR 19 moves the ring hop's decode→accumulate(→requantize) into one
VMEM-resident Pallas pass. Until the stage-attribution capture campaign
(ROADMAP item 1) measures the hop on silicon, the honest headline is a
*projection* through the documented byte model
(:func:`grace_tpu.ops.pallas_wire.hop_hbm_bytes`): hop device time on
TPU is HBM-bandwidth-bound — every op in the hop is elementwise or a
tiny constant dot — so bytes moved is the static proxy for device time.

This tool evaluates staged-vs-fused bytes over a grid of bucket sizes ×
pack widths, checks the ≥2× wire-cut target, optionally graft-lints the
shipping fused-pipelined registry config and writes ``WIRE_LAST.json``
with ``claim_class="projected"`` and a ``deferred_capture`` note naming
the measurement that will supersede it.

Exit status: 0 when every grid point meets the target, 1 otherwise.

Usage::

    python tools/graft_wire.py                 # writes WIRE_LAST.json
    python tools/graft_wire.py --json          # print the doc, still write
    python tools/graft_wire.py --no-lint       # skip the config audit
    python tools/graft_wire.py --out ''        # stdout only, no artifact
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_OUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "WIRE_LAST.json")

# The ROADMAP item-2 bar: the fused hop must cut wire-stage HBM traffic
# at least 2x vs the staged spelling at every shipped pack width.
TARGET_RATIO = 2.0

# Representative flat-bucket sizes (elements): a LeNet-scale bucket, a
# bench bucket_mb=4-scale bucket, and a ResNet-50-scale flat buffer.
DEFAULT_NUMELS = (1 << 14, 1 << 20, 25_557_032)

# Shipped pack widths (ops.packing): sign 1-bit, qsgd quantum_num<=1 ->
# 2-bit, <=3 -> 3-bit, <=7 -> 4-bit.
DEFAULT_WIDTHS = (1, 2, 3, 4)

# The shipping fused-pipelined config this projection is claimed for —
# the same registry entry chaos_smoke --lint --pipeline audits.
WIRE_CONFIG = "qsgd2-ring-packed-pipelined"

DEFERRED_CAPTURE = (
    "hop_hbm_bytes is a static byte model, not a device measurement; "
    "supersede this record with a measured stage attribution of "
    "grace/bucket/*/wire on >=2 chips (a benchmark cell's traced run).")


def _now() -> str:
    return datetime.datetime.now(
        datetime.timezone.utc).isoformat(timespec="seconds")


def _atomic_write(path: str, doc: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def projection_grid(numels, widths):
    """Staged/fused byte rows for every (numel, width) grid point."""
    from grace_tpu.ops.pallas_wire import hop_hbm_bytes
    rows = []
    for n in numels:
        for w in widths:
            staged = hop_hbm_bytes(n, w, fused=False)
            fused = hop_hbm_bytes(n, w, fused=True)
            rows.append({"numel": int(n), "pack_width": int(w),
                         "staged_bytes": int(staged),
                         "fused_bytes": int(fused),
                         "ratio": round(staged / fused, 4)})
    return rows


def lint_wire_config(name: str = WIRE_CONFIG):
    """Audit the shipping fused-pipelined registry entry; returns
    (lint_clean, n_findings) or (None, None) when the audit itself is
    unavailable (e.g. no jax on this box)."""
    try:
        from grace_tpu.analysis import audit_config
        from grace_tpu.analysis.configs import AUDIT_CONFIGS
        entry = next(e for e in AUDIT_CONFIGS if e["name"] == name)
        findings = audit_config(entry)
        errors = [f for f in findings if f.severity == "error"]
        return (not errors), len(findings)
    except Exception as e:                                 # noqa: BLE001
        print(f"[graft_wire] lint of {name!r} unavailable: {e}",
              file=sys.stderr)
        return None, None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="evidence doc path ('' disables)")
    ap.add_argument("--json", action="store_true",
                    help="print the doc to stdout")
    ap.add_argument("--no-lint", action="store_true",
                    help="skip the graft-lint audit of the shipping "
                         "fused-pipelined config")
    args = ap.parse_args(argv)

    rows = projection_grid(DEFAULT_NUMELS, DEFAULT_WIDTHS)
    ratios = [r["ratio"] for r in rows]
    min_ratio, max_ratio = min(ratios), max(ratios)
    meets = min_ratio >= TARGET_RATIO

    from grace_tpu.comm import WIRE_PIPELINE_EFFICIENCY
    lint_clean, n_findings = ((None, None) if args.no_lint
                              else lint_wire_config())

    from grace_tpu.utils.logging import git_commit

    doc = {
        "tool": "graft_wire",
        "captured_at": _now(),
        "git_rev": git_commit(),
        "claim_class": "projected",
        "model": "grace_tpu.ops.pallas_wire.hop_hbm_bytes",
        "target_ratio": TARGET_RATIO,
        "min_ratio": min_ratio,
        "max_ratio": max_ratio,
        "meets_target": meets,
        "grid": rows,
        # The overlap half of the wire story: the double-buffered ring
        # hides WIRE_PIPELINE_EFFICIENCY*(P-1)/P of wire time behind the
        # neighbouring segment's compute, statically refereed by flow
        # pass 5 (>= P independent chains per bucket).
        "pipeline_overlap": {
            "efficiency": WIRE_PIPELINE_EFFICIENCY,
            "hidden_fraction": {
                str(p): round(WIRE_PIPELINE_EFFICIENCY * (p - 1) / p, 4)
                for p in (2, 4)},
        },
        "config": WIRE_CONFIG,
        "lint_clean": lint_clean,
        "lint_findings": n_findings,
        "deferred_capture": DEFERRED_CAPTURE,
    }

    if args.out:
        try:
            _atomic_write(args.out, doc)
        except OSError as e:
            print(f"[graft_wire] could not save {args.out}: {e}",
                  file=sys.stderr)
        else:
            print(f"[graft_wire] wire projection -> {args.out}",
                  file=sys.stderr)

    if args.json:
        print(json.dumps(doc, indent=1, sort_keys=True))
    else:
        print(f"[graft_wire] hop HBM bytes staged/fused: "
              f"min {min_ratio:.2f}x, max {max_ratio:.2f}x "
              f"(target >= {TARGET_RATIO:.1f}x) -> "
              f"{'OK' if meets else 'MISS'}")
        if lint_clean is not None:
            print(f"[graft_wire] {WIRE_CONFIG}: lint_clean={lint_clean} "
                  f"({n_findings} finding(s))")
    return 0 if meets else 1


if __name__ == "__main__":
    sys.exit(main())
