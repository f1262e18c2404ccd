"""Per-algorithm benchmark table: every BASELINE.json config, one JSON line each.

Sweeps the algorithm catalog over the same ResNet-50 synthetic protocol as
bench.py (shared measurement core) and reports, per config: training
imgs/sec, ratio vs the uncompressed-allreduce baseline, and bytes-on-wire
per step per rank (grace_tpu.utils.wire_report — a first-class metric the
reference never measured). Covers BASELINE.json configs 2-5: Top-K 1%,
QSGD/TernGrad, PowerSGD rank-4, 1-bit/signSGD; plus a fusion ablation for
the headline pair (flat vs unfused — Horovod's 64MiB-fusion-buffer analog,
SURVEY.md §2.4).

Usage:
    python bench_all.py                 # on the chip; exits non-zero and
                                        # prints no row when there is none
    python bench_all.py --_worker cpu   # explicitly named CPU rehearsal
                                        # (tiny shapes, platform: cpu)

Output: one JSON line per config on stdout, e.g.
  {"config": "qsgd", "imgs_per_sec": ..., "vs_baseline": ...,
   "wire_bytes_per_step": ..., "wire_ratio": ..., "platform": "tpu"}
"""

from __future__ import annotations

import json
import os
import sys

import bench

# Ordered by evidence value: rows persist one by one (progressive_emit), so
# if a run is cut mid-sweep the completed prefix survives — put the rows
# the analysis needs most right after the headline pair.
CONFIGS = [
    # The headline pair (dense baseline first) comes verbatim from bench.py
    # so the two benchmarks can never drift apart.
    *bench.HEADLINE,
    # ---- Round-5 priority block (VERDICT r4 items 1+3): the rows the
    # analysis needs most, placed right after the headline pair because
    # a cut run keeps only the prefix. --
    #
    # THE beat-dense candidates (VERDICT r4 item 1): two-shot keeps recv
    # ~O(k) flat in W (vs allgather's O(W·k) and dense's 2·n), so at the
    # amortizing batch its multi-chip projection is the one config with a
    # shot at speedup_vs_dense > 1 on DCN. Round 4 only measured twoshot
    # at bs=32 (0.53x, fixed-overhead-dominated).
    {"name": "topk1pct_twoshot_bs256", "per_device_bs": 256,
     "params": {"compressor": "topk", "compress_ratio": 0.01,
                "topk_algorithm": "chunk", "memory": "residual",
                "communicator": "twoshot", "fusion": "flat"}},
    # + bf16 residual state: the cheapest HBM lever that doesn't touch
    # model numerics (rounding rides the error-feedback loop).
    {"name": "topk1pct_twoshot_bs256_rbf16", "per_device_bs": 256,
     "params": {"compressor": "topk", "compress_ratio": 0.01,
                "topk_algorithm": "chunk", "memory": "residual",
                "memory_dtype": "bfloat16",
                "communicator": "twoshot", "fusion": "flat"}},
    # Both levers: bf16 params AND bf16 residual on the twoshot wire.
    {"name": "topk1pct_twoshot_bs256_pbf16_rbf16", "per_device_bs": 256,
     "param_dtype": "bfloat16",
     "note": "bf16 grads take the staged chunk Top-K "
             "(the Pallas kernel is f32-only)",
     "params": {"compressor": "topk", "compress_ratio": 0.01,
                "topk_algorithm": "chunk", "memory": "residual",
                "memory_dtype": "bfloat16",
                "communicator": "twoshot", "fusion": "flat"}},
    # Re-capture of the round-4 best measured row (0.9246x, spread 0.05%)
    # plus its bf16 variants — never measured in round 4 (dead rows).
    {"name": "topk1pct_bs256", "per_device_bs": 256,
     "params": {"compressor": "topk", "compress_ratio": 0.01,
                "topk_algorithm": "chunk", "memory": "residual",
                "communicator": "allgather", "fusion": "flat"}},
    # Both amortization levers together: the headline batch AND bf16
    # params — round-4 candidates for the best measured ratio.
    {"name": "topk1pct_bs256_pbf16", "per_device_bs": 256,
     "param_dtype": "bfloat16",
     "note": "bf16 grads take the staged chunk Top-K "
             "(the Pallas kernel is f32-only)",
     "params": {"compressor": "topk", "compress_ratio": 0.01,
                "topk_algorithm": "chunk", "memory": "residual",
                "communicator": "allgather", "fusion": "flat"}},
    # bf16 RESIDUAL with f32 params (ResidualMemory state_dtype): halves
    # the largest state tensor's HBM traffic without touching the model's
    # numerics; the rounding rides the same feedback loop as the
    # compression error.
    {"name": "topk1pct_bs256_rbf16", "per_device_bs": 256,
     "params": {"compressor": "topk", "compress_ratio": 0.01,
                "topk_algorithm": "chunk", "memory": "residual",
                "memory_dtype": "bfloat16",
                "communicator": "allgather", "fusion": "flat"}},
    # Fused dense at the headline batch: with the round-5 headline moving
    # to per-leaf (see bench.HEADLINE), this row keeps the strict
    # fused-vs-fused pairing measurable against topk1pct_bs256 above
    # (dense fused-vs-unfused measured 2285.9 vs 2289.8 — ~0.2%).
    {"name": "none_flat_bs256", "per_device_bs": 256,
     "params": {"compressor": "none", "memory": "none",
                "communicator": "allreduce", "fusion": "flat"}},
    # Ring all-reduce (ISSUE 4): hop-pipelined reduce-scatter/all-gather
    # that keeps the payload compressed on every hop — recv ~2·k·(W-1)/W,
    # flat in W like two-shot, but aggregation is spread around the ring
    # and phase 2 ships the reduced shards still in wire format. The bs=256
    # row pairs with topk1pct_bs256/topk1pct_twoshot_bs256 above for the
    # three-way allgather/twoshot/ring comparison at the amortizing batch.
    {"name": "topk1pct_ring_bs256", "per_device_bs": 256,
     "params": {"compressor": "topk", "compress_ratio": 0.01,
                "topk_algorithm": "chunk", "memory": "residual",
                "communicator": "ring", "fusion": "flat"}},
    # The FSDP exchange (ISSUE 14): one all_to_all + one all_gather,
    # payload-space sums for exact codecs and exactly ONE requant
    # boundary for topk — the schedule whose requant chain stays ≤1 at
    # any W (the flat ring pays W−2), so it is the flat schedule the
    # tuner can still rank at pod scale. Pairs with the ring/twoshot
    # rows above for the four-way comparison at the amortizing batch.
    {"name": "topk1pct_rscatter_bs256", "per_device_bs": 256,
     "params": {"compressor": "topk", "compress_ratio": 0.01,
                "topk_algorithm": "chunk", "memory": "residual",
                "communicator": "rscatter", "fusion": "flat"}},
    # QSGD on the ring exercises the per-hop requantization path proper
    # (decompress → accumulate → requantize each hop; topk re-selects).
    # use_pallas pinned False to match the staged qsgd row below —
    # communicator is the only variable between the pair.
    {"name": "qsgd_ring", "params": {"compressor": "qsgd",
                                     "quantum_num": 64,
                                     "use_pallas": False,
                                     "memory": "none",
                                     "communicator": "ring",
                                     "fusion": "flat"}},
    # Hierarchical ICI×DCN family (ISSUE 7): the two-level schedule whose
    # xslice projection is THE cross-slice headline — flat topk+allgather
    # LOSES to dense at W=256 over DCN (0.896×, see the projection blocks
    # of topk1pct_bs256); the hier rows keep ~2·k·(S−1)/S on ICI and ship
    # only (K−1)·k/S across DCN, so the same measured step time projects
    # >1× dense at W=256, slice_size=8. slice_size=8 matches the one real
    # v5e slice this repo measures on AND the xslice projection topology,
    # so recv_link_bytes prices a genuinely mixed split in every row.
    # (On the single 8-chip mesh the schedule collapses to the flat ring —
    # the measured step time is the ring's; the projection is the story.)
    {"name": "topk1pct_hier_bs256", "per_device_bs": 256,
     "params": {"compressor": "topk", "compress_ratio": 0.01,
                "topk_algorithm": "chunk", "memory": "residual",
                "communicator": "hier", "slice_size": 8,
                "fusion": "flat"}},
    {"name": "qsgd_hier", "params": {"compressor": "qsgd",
                                     "quantum_num": 64,
                                     "use_pallas": False,
                                     "memory": "none",
                                     "communicator": "hier",
                                     "slice_size": 8,
                                     "fusion": "flat"}},
    {"name": "none_hier", "params": {"compressor": "none",
                                     "memory": "none",
                                     "communicator": "hier",
                                     "slice_size": 8,
                                     "fusion": "flat"}},
    # Aggregation-homomorphic row family (ISSUE 13): shared-scale qsgd4
    # whose integer payloads SUM on every hop and at the slice boundary —
    # zero requant regardless of W, one decode at the schedule's end, one
    # scalar pmax negotiation before stage 1. Pairs with qsgd_ring (the
    # per-hop requant path this family retires: W−1 re-encodes, the
    # PR-12 MAX_REQUANT_CHAIN degradation) and with the hier rows (same
    # two-level schedule, boundary requant → boundary integer add). Wire
    # is int16 (fp16-width) — the story is the quality-at-ring-cost, not
    # the bytes: hop-count-independent compression error at ring/hier's
    # O(k), where the tuner's funnel now prices requant-chain 0.
    {"name": "homoqsgd4_ring_bs256", "per_device_bs": 256,
     "params": {"compressor": "homoqsgd", "quantum_num": 7,
                "memory": "residual", "communicator": "ring",
                "fusion": "flat"}},
    {"name": "homoqsgd4_hier_slice8", "per_device_bs": 256,
     "params": {"compressor": "homoqsgd", "quantum_num": 7,
                "memory": "residual", "communicator": "hier",
                "slice_size": 8, "fusion": "flat"}},
    # graft-adapt row (ISSUE 15): the self-tuning homoqsgd ladder (dense
    # escape → 8-bit → 4-bit) over the zero-requant ring, measured at its
    # quiet steady state — the top rung IS homoqsgd4_ring_bs256's codec,
    # so this row's delta against that one is the controller's whole
    # overhead bill (the per-step scalar pmean/pmax signal + the ladder
    # switch + the telemetry ring). The acceptance claim is ~parity:
    # a self-tuning config matching the best static config's steady-state
    # throughput (the convergence-floor half lives in tests/test_adapt).
    {"name": "adapt_homoqsgd4_ring_bs256", "per_device_bs": 256,
     "note": "self-tuning ladder (dense->homoqsgd8->homoqsgd4) at its "
             "steady state; compare against homoqsgd4_ring_bs256 for "
             "the controller overhead",
     "params": {"compressor": "homoqsgd", "quantum_num": 7,
                "memory": "residual", "communicator": "ring",
                "fusion": "flat", "escape": "fp16", "telemetry": 16,
                "adapt": {"window": 25,
                          "ladder": [{"quantum_num": 127}]}}},
    # The overdue graft-tune chip-window row (ISSUE 12 / ROADMAP item 1):
    # everything PRs 7-10 built, on in one config — fused Pallas
    # quantize-and-pack (4-bit nibbles, 2 codes/byte) feeding the bucketed
    # overlap executor over the hop-requant ring, at the amortizing batch.
    # The committed TPU captures predate all of it (the sweep's qsgd rows
    # are staged, unbucketed, quantum_num=64); this row plus the hier rows
    # above are the `--tuned` family, so refreshing the evidence on
    # the chip is one command: `python bench_all.py --tuned`.
    # tpu_only for the same reason as qsgd_pallas: interpret-mode Pallas
    # off-chip is a per-element emulation.
    {"name": "qsgd4_packed_bucketed_pallas_bs256", "per_device_bs": 256,
     "tpu_only": True,
     "note": "graft-tune row family: fused quantize-pack kernel + "
             "bucketed executor + hop-requant ring",
     "params": {"compressor": "qsgd", "quantum_num": 7,
                "use_pallas": True, "memory": "none",
                "communicator": "ring", "fusion": 1024}},
    # Its staged twin keeps the kernel ablation measurable (and gives the
    # CPU smoke a runnable row of the same wire format + executor).
    {"name": "qsgd4_packed_bucketed_bs256", "per_device_bs": 256,
     "params": {"compressor": "qsgd", "quantum_num": 7,
                "use_pallas": False, "memory": "none",
                "communicator": "ring", "fusion": 1024}},
    # Sub-nibble wire widths (ISSUE 19): quantum_num=1 ships 2-bit fields
    # (4 codes/byte — 16x under int8, 2x under the 4-bit nibble) and
    # quantum_num=3 the 3-bit LSB-first bitstream (8 codes / 3 bytes),
    # both through the hop-requant ring. Rows stamp pack_width so the
    # 2/3/4-bit family is distinguishable in the evidence; the quality
    # cost of the coarser lattice is the convergence suite's question,
    # the wire win is this sweep's.
    {"name": "qsgd2_packed_ring_bs256", "per_device_bs": 256,
     "params": {"compressor": "qsgd", "quantum_num": 1,
                "use_pallas": False, "memory": "none",
                "communicator": "ring", "fusion": "flat"}},
    {"name": "qsgd3_packed_ring_bs256", "per_device_bs": 256,
     "params": {"compressor": "qsgd", "quantum_num": 3,
                "use_pallas": False, "memory": "none",
                "communicator": "ring", "fusion": "flat"}},
    # Double-buffered ring twins (ISSUE 19): pipeline=2 splits the flat
    # buffer into two segments whose ring schedules overlap on real links
    # — the delta against the serial siblings above is the measured side
    # of the wire_pipeline story (rows stamp pipelined=2, projections
    # discount the wire leg by wire_overlap_fraction, and flow pass 5
    # referees the >= 2 independent chains statically).
    {"name": "qsgd2_packed_ring_pipelined_bs256", "per_device_bs": 256,
     "params": {"compressor": "qsgd", "quantum_num": 1,
                "use_pallas": False, "memory": "none",
                "communicator": "ring", "fusion": "flat", "pipeline": 2}},
    {"name": "qsgd4_packed_ring_pipelined_bs256", "per_device_bs": 256,
     "params": {"compressor": "qsgd", "quantum_num": 7,
                "use_pallas": False, "memory": "none",
                "communicator": "ring", "fusion": "flat", "pipeline": 2}},
    # qsgd vs qsgd_pallas: THE evidence gate for flipping QSGD's
    # use_pallas default (VERDICT r3 item 5, two rounds dark).
    # use_pallas pinned False: this row is the STAGED side of the
    # qsgd-vs-qsgd_pallas A/B. (The round-5 A/B measured the kernel 42%
    # faster, so 'auto' — the factory default — now resolves kernel-on
    # for TPU; leaving this unpinned would make both rows measure the
    # kernel and erase the ablation.)
    {"name": "qsgd",       "params": {"compressor": "qsgd",
                                      "quantum_num": 64,
                                      "use_pallas": False,
                                      "memory": "none",
                                      "communicator": "allgather",
                                      "fusion": "flat"}},
    # tpu_only: off-TPU this forces the quant kernel into interpret mode
    # over the full 25.5M-param model — observed >45 min for ONE config on
    # the CPU smoke (interpret Pallas is a per-element emulation); the
    # kernel's off-TPU correctness is covered at small sizes by
    # tests/test_pallas_quant.py, and the row only means anything on-chip.
    {"name": "qsgd_pallas", "tpu_only": True,
     "params": {"compressor": "qsgd",
                "quantum_num": 64,
                "use_pallas": True,
                "memory": "none",
                "communicator": "allgather",
                "fusion": "flat"}},
    {"name": "powersgd_r4", "params": {"compressor": "powersgd",
                                       "compress_rank": 4,
                                       "memory": "powersgd",
                                       "communicator": "allreduce",
                                       "fusion": "none"}},
    # Fixed-cost psum majority vote (~4n bf16 on the wire, W-independent):
    # the pod-scale route for sign methods, next to the packed allgather
    # row below (also VERDICT round-2 item 5). Errored mid-compile in
    # round 4 and was never re-run.
    # The vote at the amortizing batch, per-leaf: 0.9775x dense single-chip
    # (round-5 capture) with recv flat in W (bf16 psum = half dense's
    # bytes), so it projects above dense on DCN at every W — the third
    # winning family after PowerSGD and small-mesh per-leaf Top-K.
    {"name": "signsgd_vote_bs256", "per_device_bs": 256,
     "params": {"compressor": "signsgd", "memory": "residual",
                "communicator": "sign_allreduce", "fusion": "none"}},
    {"name": "signsgd_vote", "params": {"compressor": "signsgd",
                                        "memory": "none",
                                        "communicator": "sign_allreduce",
                                        "fusion": "flat"}},
    {"name": "onebit",     "params": {"compressor": "onebit",
                                      "memory": "residual",
                                      "communicator": "allgather",
                                      "fusion": "flat"}},
    {"name": "terngrad",   "params": {"compressor": "terngrad",
                                      "memory": "none",
                                      "communicator": "allgather",
                                      "fusion": "flat"}},
    # ---- end priority block ----
    # Two-shot scatter-reduce-recompress all-reduce at the small batch:
    # isolates the stage-2 recompress overhead (VERDICT round-2 item 5).
    {"name": "topk1pct_twoshot", "params": {"compressor": "topk",
                                            "compress_ratio": 0.01,
                                            "topk_algorithm": "chunk",
                                            "memory": "residual",
                                            "communicator": "twoshot",
                                            "fusion": "flat"}},
    {"name": "signsgd",    "params": {"compressor": "signsgd",
                                      "memory": "none",
                                      "communicator": "allgather",
                                      "fusion": "flat"}},
    {"name": "topk1pct_bf16", "params": {"compressor": "topk",
                                         "compress_ratio": 0.01,
                                         "topk_algorithm": "chunk",
                                         "wire_dtype": "bfloat16",
                                         "memory": "residual",
                                         "communicator": "allgather",
                                         "fusion": "flat"}},
    # Top-K selection variants (the headline uses 'chunk'; exact top-k
    # lowers to a full sort — the most expensive op in the pipeline; see
    # compressors/topk.py):
    {"name": "topk1pct_approx", "params": {"compressor": "topk",
                                           "compress_ratio": 0.01,
                                           "topk_algorithm": "approx",
                                           "memory": "residual",
                                           "communicator": "allgather",
                                           "fusion": "flat"}},
    {"name": "topk1pct_exact", "params": {"compressor": "topk",
                                          "compress_ratio": 0.01,
                                          "topk_algorithm": "exact",
                                          "memory": "residual",
                                          "communicator": "allgather",
                                          "fusion": "flat"}},
    # Batch-size sweep tail (VERDICT round-3 item 4): bs64/bs128 show where
    # the fixed compression cost amortizes; measured in round 4, kept for
    # re-capture freshness. bench_configs re-measures the dense baseline at
    # each row's own shapes so vs_baseline stays like-for-like.
    *[{"name": f"topk1pct_bs{bs}", "per_device_bs": bs,
       "params": {"compressor": "topk", "compress_ratio": 0.01,
                  "topk_algorithm": "chunk", "memory": "residual",
                  "communicator": "allgather", "fusion": "flat"}}
      for bs in (64, 128)],
    # bf16 master params at the amortizing batch. NOTE the fused Pallas
    # Top-K kernel is f32-only (compressors/topk.py fused gate) so bf16
    # grads take the STAGED chunk path — the note rides the emitted row.
    {"name": "topk1pct_bs128_pbf16", "per_device_bs": 128,
     "param_dtype": "bfloat16",
     "note": "bf16 grads take the staged chunk Top-K "
             "(the Pallas kernel is f32-only; staged is the default "
             "everywhere since round 4 anyway)",
     "params": {"compressor": "topk", "compress_ratio": 0.01,
                "topk_algorithm": "chunk", "memory": "residual",
                "communicator": "allgather", "fusion": "flat"}},
    # Ablation: chunk selection WITH the fused Pallas kernels forced on
    # (ops/pallas_topk.py). The round-4 on-chip A/B measured the staged
    # XLA path FASTER end-to-end (1602 vs 1441 imgs/sec at bs=32, same
    # session), so 'auto' now resolves to staged and this row keeps the
    # kernel measurable should a later change flip the verdict back.
    {"name": "topk1pct_pallas", "params": {"compressor": "topk",
                                           "compress_ratio": 0.01,
                                           "topk_algorithm": "chunk",
                                           "use_pallas": True,
                                           "memory": "residual",
                                           "communicator": "allgather",
                                           "fusion": "flat"}},
    # Fusion ablation (headline pair unfused, and Horovod's default 64 MiB
    # bucketing — SURVEY.md §2.4):
    {"name": "none_unfused", "params": {"compressor": "none",
                                        "memory": "none",
                                        "communicator": "allreduce",
                                        "fusion": "none"}},
    {"name": "topk1pct_unfused", "params": {"compressor": "topk",
                                            "compress_ratio": 0.01,
                                            "topk_algorithm": "chunk",
                                            "memory": "residual",
                                            "communicator": "allgather",
                                            "fusion": "none"}},
    {"name": "topk1pct_64mib", "params": {"compressor": "topk",
                                          "compress_ratio": 0.01,
                                          "topk_algorithm": "chunk",
                                          "memory": "residual",
                                          "communicator": "allgather",
                                          "fusion": 64 * 2**20}},
]

# The graft-tune evidence family (ISSUE 12): the dense anchor + headline
# pair plus the rows the committed captures are missing — hier at the
# projection topology and the packed+bucketed+pallas qsgd4 row. One
# command refreshes them all: `python bench_all.py --tuned`.
TUNED_ROW_NAMES = ("none", "topk1pct", "topk1pct_hier_bs256", "qsgd_hier",
                   "none_hier", "qsgd4_packed_bucketed_pallas_bs256",
                   "qsgd4_packed_bucketed_bs256",
                   # the homomorphic family (ISSUE 13): the zero-requant
                   # ring/hier rows the tuner's requant-chain-0 pricing
                   # needs measured evidence for
                   "homoqsgd4_ring_bs256", "homoqsgd4_hier_slice8",
                   # graft-shard (ISSUE 14): the rscatter schedule now
                   # tops the W256/slice8 static ranking — its measured
                   # step time is the next capture's most-wanted row
                   "topk1pct_rscatter_bs256",
                   # graft-adapt (ISSUE 15): the self-tuning ladder at
                   # its steady state next to its static twin — the
                   # controller-overhead ablation the acceptance
                   # criterion ("matches the best static config's
                   # steady-state throughput") needs on-chip
                   "adapt_homoqsgd4_ring_bs256",
                   # graft-wire (ISSUE 19): the 2/3-bit pack widths and
                   # the double-buffered ring twins — the serial vs
                   # pipelined deltas are the measured side of the
                   # wire_pipeline discount
                   "qsgd2_packed_ring_bs256", "qsgd3_packed_ring_bs256",
                   "qsgd2_packed_ring_pipelined_bs256",
                   "qsgd4_packed_ring_pipelined_bs256")


def active_configs():
    """The sweep's config list, honoring the --tuned selection
    (GRACE_BENCH_TUNED, set by the command line). configs[0] must stay the
    dense-recipe anchor in both modes (bench_configs' baseline contract)."""
    if os.environ.get("GRACE_BENCH_TUNED"):
        return [c for c in CONFIGS if c["name"] in TUNED_ROW_NAMES]
    return list(CONFIGS)


# Sweep-specific TPU evidence file (same incremental-persistence contract as
# bench.py's BENCH_TPU_LAST.json): every measured row lands on disk
# immediately, so a run cut mid-sweep keeps the completed prefix.
SWEEP_EVIDENCE_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_ALL_TPU_LAST.json")


def _resume_configs():
    """Attach previously measured rows (persisted in SWEEP_EVIDENCE_PATH)
    as cached_row so bench_configs re-emits them instead of re-measuring —
    a retry on the chip after a cut sweep then only pays for the missing
    configs. Two gates (a stale last-week file must never replay as fresh):

    * GRACE_BENCH_RESUME — explicit operator override, any file accepted;
    * GRACE_BENCH_RESUME_SINCE=<unix epoch>: the file is only reused if
      its captured_at stamp is at/after that moment.

    Rows must match the config's current shapes (bs/hw/dtype), carry a real
    measurement (no error rows), and get "resumed": true stamped on."""
    configs = [dict(c) for c in active_configs()]
    explicit = os.environ.get("GRACE_BENCH_RESUME")
    since = os.environ.get("GRACE_BENCH_RESUME_SINCE")
    if not (explicit or since):
        return configs
    try:
        with open(SWEEP_EVIDENCE_PATH) as f:
            doc = json.load(f)
        if not explicit:
            from datetime import datetime
            captured = datetime.fromisoformat(doc["captured_at"]).timestamp()
            if captured < float(since):
                return configs
        prev = {r["config"]: r for r in doc.get("rows", [])
                if r.get("config") and r.get("imgs_per_sec") is not None}
    except Exception:
        return configs
    for cfg in configs:
        row = prev.get(cfg["name"])
        if not row:
            continue
        # Shape defaults come from bench.py's exported constants — the
        # literals here once duplicated bench_configs' and could drift
        # (ADVICE r4): a collision with old rows could replay a
        # wrong-shape row.
        want = (cfg.get("per_device_bs", bench.TPU_DEFAULT_BS),
                cfg.get("image_hw", bench.TPU_DEFAULT_HW),
                cfg.get("param_dtype", bench.TPU_DEFAULT_PDTYPE))
        got = (row.get("per_device_bs"), row.get("image_hw"),
               row.get("param_dtype"))
        if want != got:
            continue
        # Same name + shapes is not enough: a config whose *params* were
        # edited since the row was measured must re-measure. Rows stamp
        # grace_params (bench_configs); a row without the stamp predates
        # it and is only trusted under the explicit operator override.
        if "grace_params" in row:
            if row["grace_params"] != cfg["params"]:
                continue
        elif not explicit:
            continue
        cfg["cached_row"] = {**row, "resumed": True}
        if explicit:
            # The operator's assertion that this file is trustworthy also
            # covers rows predating the pallas_enabled stamp — the
            # bench-side gate (_cached_row_valid) fails closed on those
            # otherwise.
            cfg["cached_row"]["resume_trusted"] = True
    return configs


def _worker(platform: str) -> None:
    # Resume replays rows measured on the chip, so only a run on the chip
    # may resume; the CPU rehearsal measures its own tiny rows, replays
    # nothing, and writes no TPU evidence file.
    if platform == "tpu":
        configs, evidence_path = _resume_configs(), SWEEP_EVIDENCE_PATH
    else:
        configs, evidence_path = [dict(c) for c in active_configs()], None
    emit = bench.progressive_emit(
        lambda r: print(json.dumps(r), flush=True),
        n_expected=len(configs),
        evidence_path=evidence_path,
        metric="resnet50_all_configs_imgs_per_sec")
    bench.bench_configs(platform, configs, emit)


if __name__ == "__main__":
    if "--tuned" in sys.argv:
        # One-command graft-tune evidence refresh: restrict the sweep to
        # the tuned row family.
        os.environ["GRACE_BENCH_TUNED"] = "1"
        sys.argv = [a for a in sys.argv if a != "--tuned"]
    _worker(bench.worker_platform(sys.argv))
