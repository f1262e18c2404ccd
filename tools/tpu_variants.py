"""One-off TPU sweep over Top-K pipeline variants to pick the headline fix.

VERDICT round-2 item 2: the measured compressed/dense ratio is 0.34 on the
chip; this sweeps the in-tree knobs (selection algorithm, wire dtype,
fusion) side by side in one session so the winner can be promoted into
bench.py's HEADLINE config. Results append to TPU_VARIANTS.jsonl row by row
(a run cut short keeps its finished rows, as bench.progressive_emit).

Usage (on the chip): python tools/tpu_variants.py [--configs a,b,...]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402

BASE = {"memory": "residual", "communicator": "allgather", "fusion": "flat"}

VARIANTS = {
    "none": {"compressor": "none", "memory": "none",
             "communicator": "allreduce", "fusion": "flat"},
    "approx": dict(BASE, compressor="topk", compress_ratio=0.01,
                   topk_algorithm="approx"),
    "chunk": dict(BASE, compressor="topk", compress_ratio=0.01,
                  topk_algorithm="chunk"),
    "chunk_bf16": dict(BASE, compressor="topk", compress_ratio=0.01,
                       topk_algorithm="chunk", wire_dtype="bfloat16"),
    "approx_bf16": dict(BASE, compressor="topk", compress_ratio=0.01,
                        topk_algorithm="approx", wire_dtype="bfloat16"),
    "exact": dict(BASE, compressor="topk", compress_ratio=0.01,
                  topk_algorithm="exact"),
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--configs", default=None)
    ap.add_argument("--out", default="TPU_VARIANTS.jsonl")
    args = ap.parse_args()
    names = (args.configs.split(",") if args.configs
             else list(VARIANTS))
    configs = [{"name": n, "params": VARIANTS[n]} for n in names]

    rows = []

    def emit(row):
        rows.append(row)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(f"[variants] {row['config']}: {row['imgs_per_sec']} imgs/sec "
              f"(x{row['vs_baseline']})", file=sys.stderr, flush=True)

    bench.bench_configs("tpu", configs, emit)

    # Ledger emission (repo-root artifact only): one record for the whole
    # appended sweep, superseding the previous variants record.
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if rows and os.path.dirname(os.path.abspath(args.out)) == root:
        from grace_tpu.evidence.ledger import record_artifact
        n_dev = rows[0].get("n_devices")
        record_artifact(
            args.out, id="variants-tpu", metric="resnet50_variant_rows",
            value=len(rows), claim_class="measured", tool="tpu_variants",
            platform=rows[0].get("platform"), chip=rows[0].get("chip"),
            n_devices=n_dev,
            topology={"world": n_dev, "tiers": ["ici"], "slice": None,
                      "region": None},
            config=",".join(names), lint_clean=None)


if __name__ == "__main__":
    main()
