"""The audited config registry: the enforced codec x communicator matrix.

One entry per *valid* triad the repo supports (the same compatibility
matrix ``Allreduce``/``RingAllreduce``/``TwoShotAllreduce`` enforce at
build time, plus the resilience variants: escape hatch, telemetry,
guard + consensus). ``audit_all`` traces every entry with
:func:`~grace_tpu.analysis.trace.trace_update` (or
:func:`~grace_tpu.analysis.trace.trace_train_step` for ``mode='train'``
entries) and runs the selected passes.

Pass selection per entry:

* ``wire_reconciliation`` runs only on bare-update traces without an
  escape hatch (the escape cond makes "the" wire cost bimodal — telemetry
  prices that flip separately) and without in-compress collectives priced
  analytically at a different granularity;
* train-mode entries (guard/consensus) skip wire reconciliation — the
  audit's fingerprint gathers and the loss pmean are deliberately outside
  the exchange model — but are exactly where ``collective_consistency``
  and ``bit_exactness`` earn their keep.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from grace_tpu.analysis.passes import Finding, PASS_NAMES, run_passes
from grace_tpu.analysis.trace import trace_train_step, trace_update

__all__ = ["AUDIT_CONFIGS", "audit_all", "audit_config", "build_grace",
           "overlap_bound_report"]

_ALL = tuple(PASS_NAMES)
_NO_WIRE = tuple(p for p in PASS_NAMES if p != "wire_reconciliation")


def _cfg(name: str, params: Dict[str, Any], *, passes=_ALL, mode="update",
         guard=None, consensus=None, fsdp=None,
         world=None) -> Dict[str, Any]:
    # world: per-entry audit-mesh override. Most entries trace at the
    # caller's world (8 by default); configs whose payload accumulator
    # legitimately bounds the world — e.g. packed sub-byte homoqsgd,
    # whose payload_sum_max_world is (2^(bits-1)-1)//quantum_num — pin
    # the world their contract actually supports, so the registry stays
    # lint-clean while the out-of-bound worlds remain the rejection
    # demonstrators tests pin explicitly.
    return {"name": name, "params": params, "passes": passes, "mode": mode,
            "guard": guard, "consensus": consensus, "fsdp": fsdp,
            "world": world}


AUDIT_CONFIGS: List[Dict[str, Any]] = [
    # -- linear codecs: the summable-payload Allreduce family ---------------
    _cfg("none-allreduce", {"compressor": "none", "memory": "none",
                            "communicator": "allreduce"}),
    _cfg("fp16-allreduce", {"compressor": "fp16", "memory": "none",
                            "communicator": "allreduce"}),
    _cfg("randomk-allreduce", {"compressor": "randomk",
                               "compress_ratio": 0.5, "memory": "residual",
                               "communicator": "allreduce"}),
    _cfg("powersgd-allreduce", {"compressor": "powersgd",
                                "compress_rank": 2, "memory": "powersgd",
                                "communicator": "allreduce"}),
    # -- the general-purpose allgather family -------------------------------
    _cfg("topk-allgather", {"compressor": "topk", "compress_ratio": 0.3,
                            "memory": "residual",
                            "communicator": "allgather"}),
    _cfg("randomk-allgather", {"compressor": "randomk",
                               "compress_ratio": 0.5, "memory": "residual",
                               "communicator": "allgather"}),
    _cfg("qsgd-allgather", {"compressor": "qsgd", "quantum_num": 64,
                            "use_pallas": False, "memory": "none",
                            "communicator": "allgather"}),
    _cfg("terngrad-allgather", {"compressor": "terngrad", "memory": "none",
                                "communicator": "allgather"}),
    _cfg("signsgd-allgather", {"compressor": "signsgd", "memory": "none",
                               "communicator": "allgather"}),
    _cfg("signum-allgather", {"compressor": "signum", "momentum": 0.9,
                              "memory": "none",
                              "communicator": "allgather"}),
    _cfg("efsignsgd-allgather", {"compressor": "efsignsgd", "lr": 0.1,
                                 "memory": "efsignsgd",
                                 "communicator": "allgather"}),
    _cfg("onebit-allgather", {"compressor": "onebit", "memory": "residual",
                              "communicator": "allgather"}),
    _cfg("natural-allgather", {"compressor": "natural",
                               "memory": "residual",
                               "communicator": "allgather"}),
    _cfg("dgc-allgather", {"compressor": "dgc", "compress_ratio": 0.3,
                           "memory": "dgc", "communicator": "allgather"}),
    _cfg("threshold-allgather", {"compressor": "threshold",
                                 "threshold": 0.01,
                                 "memory": "residual",
                                 "communicator": "allgather"}),
    _cfg("sketch-allgather", {"compressor": "sketch", "quantum_num": 64,
                              "memory": "none",
                              "communicator": "allgather"}),
    _cfg("u8bit-allgather", {"compressor": "u8bit", "memory": "none",
                             "communicator": "allgather"}),
    _cfg("adaq-allgather", {"compressor": "adaq", "compress_ratio": 0.3,
                            "memory": "residual",
                            "communicator": "allgather"}),
    _cfg("inceptionn-allgather", {"compressor": "inceptionn",
                                  "memory": "none",
                                  "communicator": "allgather"}),
    _cfg("topk-broadcast", {"compressor": "topk", "compress_ratio": 0.3,
                            "memory": "residual",
                            "communicator": "broadcast"}),
    # -- vote routing --------------------------------------------------------
    _cfg("signsgd-sign_allreduce", {"compressor": "signsgd",
                                    "memory": "none",
                                    "communicator": "sign_allreduce"}),
    _cfg("signsgd-allreduce-vote", {"compressor": "signsgd",
                                    "memory": "none",
                                    "communicator": "allreduce"}),
    # -- shard-parallel families (flat fusion hands them whole buffers) -----
    _cfg("topk-twoshot", {"compressor": "topk", "compress_ratio": 0.3,
                          "memory": "residual", "communicator": "twoshot",
                          "fusion": "flat"}),
    _cfg("qsgd-twoshot", {"compressor": "qsgd", "quantum_num": 64,
                          "use_pallas": False, "memory": "none",
                          "communicator": "twoshot", "fusion": "flat"}),
    _cfg("topk-ring", {"compressor": "topk", "compress_ratio": 0.3,
                       "memory": "residual", "communicator": "ring",
                       "fusion": "flat"}),
    _cfg("qsgd-ring", {"compressor": "qsgd", "quantum_num": 64,
                       "use_pallas": False, "memory": "none",
                       "communicator": "ring", "fusion": "flat"}),
    _cfg("signsgd-ring", {"compressor": "signsgd", "memory": "none",
                          "communicator": "ring", "fusion": "flat"}),
    _cfg("fp16-ring", {"compressor": "fp16", "memory": "none",
                       "communicator": "ring", "fusion": "flat"}),
    _cfg("randomk-ring", {"compressor": "randomk", "compress_ratio": 0.5,
                          "memory": "residual", "communicator": "ring",
                          "fusion": "flat"}),
    # -- hierarchical ICI×DCN family (ISSUE 7): slice_size=4 puts a slice
    #    boundary inside the 8-way audit mesh (K=2 slices), so the traced
    #    schedule exercises both grouped sub-axis collectives AND the
    #    per-link split reconciliation (wire_reconciliation counts the
    #    intra-slice legs as ICI and the cross-slice gather as DCN against
    #    HierarchicalAllreduce.recv_link_bytes — the mixed split that
    #    makes the xslice projections trustworthy).
    _cfg("topk1pct_hier", {"compressor": "topk", "compress_ratio": 0.01,
                           "topk_algorithm": "chunk", "memory": "residual",
                           "communicator": "hier", "slice_size": 4,
                           "fusion": "flat"}),
    _cfg("qsgd_hier", {"compressor": "qsgd", "quantum_num": 64,
                       "use_pallas": False, "memory": "none",
                       "communicator": "hier", "slice_size": 4,
                       "fusion": "flat"}),
    _cfg("none_hier", {"compressor": "none", "memory": "none",
                       "communicator": "hier", "slice_size": 4,
                       "fusion": "flat"}),
    _cfg("signsgd_hier", {"compressor": "signsgd", "memory": "none",
                          "communicator": "hier", "slice_size": 4,
                          "fusion": "flat"}),
    # -- aggregation-homomorphic family (ISSUE 13): payload-algebra codecs
    #    whose wire payloads SUM on every hop and slice boundary with zero
    #    requant. The homoqsgd traces carry the hoisted shared-scale
    #    negotiation (one pmax before stage 1 — a scalar collective inside
    #    the wire model's atol, audited by wire_reconciliation like every
    #    other traced collective), integer ppermute/gather payloads, and
    #    ONE decode at the schedule's end; numeric_safety additionally
    #    checks the int accumulator against payload_sum_max_world at the
    #    audit world.
    _cfg("homoqsgd-ring", {"compressor": "homoqsgd", "quantum_num": 7,
                           "memory": "residual", "communicator": "ring",
                           "fusion": "flat"}),
    _cfg("homoqsgd-hier", {"compressor": "homoqsgd", "quantum_num": 7,
                           "memory": "residual", "communicator": "hier",
                           "slice_size": 4, "fusion": "flat"}),
    # -- three-tier WAN family (ISSUE 16): slice_size=2 + region_size=4
    #    puts BOTH a slice and a region boundary inside the 8-way audit
    #    mesh (2 regions × 2 slices × 2 ranks), so the traced three-level
    #    schedule exercises intra-slice ppermute hops (ICI), same-region
    #    cross-slice gathers (DCN), and cross-region gathers (WAN) — and
    #    wire_reconciliation reconciles all THREE legs against
    #    HierarchicalAllreduce.recv_link_bytes under the comm's own
    #    (slice_size, region_size).
    _cfg("topk-hier3", {"compressor": "topk", "compress_ratio": 0.25,
                        "topk_algorithm": "chunk", "memory": "residual",
                        "communicator": "hier", "slice_size": 2,
                        "region_size": 4, "fusion": "flat"}),
    # Homomorphic payloads cross the WAN tier exactly-summable (zero
    # requant at BOTH the slice and the region boundary) — the traced
    # schedule is negotiate pmax + int hops + two nested gather-sums +
    # ONE decode.
    _cfg("homoqsgd-hier3", {"compressor": "homoqsgd", "quantum_num": 7,
                            "memory": "residual", "communicator": "hier",
                            "slice_size": 2, "region_size": 4,
                            "fusion": "flat"}),
    # Mergeable count-sketch over the gather family: the sketch algebra's
    # ctx (hash indices/signs) is rng-derived, so the data-free-ctx decode
    # contract holds and the payload (rows × width f32 tables) reconciles
    # against the gather model like any other codec.
    _cfg("countsketch-allgather", {"compressor": "countsketch",
                                   "compress_ratio": 0.25,
                                   "memory": "residual",
                                   "communicator": "allgather"}),
    # -- sharded-model track (ISSUE 14): compressed reduce-scatter on 1-D
    #    and 2-D dp×fsdp meshes. The rscatter schedule is one all_to_all
    #    (the reduce-scatter's data movement) + one all_gather; payload-
    #    space summation for exact/homomorphic codecs, exactly ONE requant
    #    boundary for the rest. The fsdp=2 entries split the 8-way audit
    #    mesh into dp=4 × fsdp=2: the tracer seeds GraceState leaves from
    #    the 2-D partition_specs (P((dp, fsdp))), the replication analysis
    #    runs PER AXIS, and wire_reconciliation counts the dp-axis
    #    collectives at the dp world — proving the whole 7-pass stack
    #    holds on 2-D configs.
    _cfg("topk-rscatter", {"compressor": "topk", "compress_ratio": 0.3,
                           "memory": "residual", "communicator": "rscatter",
                           "fusion": "flat"}),
    _cfg("fp16-rscatter-fsdp", {"compressor": "fp16", "memory": "none",
                                "communicator": "rscatter",
                                "fusion": "flat", "fsdp_axis": "fsdp"},
         fsdp=2),
    _cfg("topk-rscatter-fsdp", {"compressor": "topk",
                                "compress_ratio": 0.3,
                                "memory": "residual",
                                "communicator": "rscatter",
                                "fusion": "flat", "fsdp_axis": "fsdp"},
         fsdp=2),
    _cfg("homoqsgd-rscatter-fsdp", {"compressor": "homoqsgd",
                                    "quantum_num": 7, "memory": "residual",
                                    "communicator": "rscatter",
                                    "fusion": "flat",
                                    "fsdp_axis": "fsdp"}, fsdp=2),
    # ScaleCom-style cyclic Top-K: the rng+step-derived shared index set
    # makes the payload exactly summable, so it rides the psum allreduce
    # at k values/rank with ZERO negotiation bytes (the schedule is
    # rank-deterministic — nothing to broadcast), which this entry pins.
    _cfg("cyclictopk-allreduce", {"compressor": "cyclictopk",
                                  "compress_ratio": 0.3,
                                  "memory": "residual",
                                  "communicator": "allreduce"}),
    # The data-free-ctx unlock (ROADMAP item 4): cyclictopk's ctx is
    # derived from the replicated rng alone, so the hop-pipelined ring
    # rebuilds the scatter map per shard and the exact payload algebra
    # sums losslessly hop by hop.
    _cfg("cyclictopk-ring", {"compressor": "cyclictopk",
                             "compress_ratio": 0.3,
                             "memory": "residual",
                             "communicator": "ring",
                             "fusion": "flat"}),
    # First-class per-leaf codec routing (1-D): the wire model becomes the
    # SUM of per-leaf prices through each leaf's own codec/communicator —
    # wire_reconciliation audits the routed spelling end to end.
    _cfg("routed-topk-fp16", {"compressor": "topk", "compress_ratio": 0.3,
                              "memory": "residual",
                              "communicator": "allgather",
                              "route": [("b", {"compressor": "fp16",
                                               "memory": "none",
                                               "communicator":
                                                   "allreduce"})]}),
    # Routed rscatter over the 2-D mesh: the transformer-track shape —
    # the big leaf rides sparsification through the per-shard
    # reduce-scatter, the small leaf rides dense fp16 psum.
    _cfg("routed-rscatter-fsdp", {"compressor": "topk",
                                  "compress_ratio": 0.3,
                                  "memory": "residual",
                                  "communicator": "rscatter",
                                  "fsdp_axis": "fsdp",
                                  "route": [("b", {"compressor": "fp16",
                                                   "memory": "none",
                                                   "communicator":
                                                       "allreduce"})]},
         fsdp=2),
    # -- degenerate / fusion variants ---------------------------------------
    _cfg("none-identity", {"compressor": "none", "memory": "none",
                           "communicator": "identity"}),
    _cfg("topk-allgather-flat", {"compressor": "topk",
                                 "compress_ratio": 0.3,
                                 "memory": "residual",
                                 "communicator": "allgather",
                                 "fusion": "flat"}),
    _cfg("topk-allgather-grouped", {"compressor": "topk",
                                    "compress_ratio": 0.3,
                                    "memory": "residual",
                                    "communicator": "allgather",
                                    "fusion": "grouped"}),
    # Int-bucket fusion (graft-flow, ISSUE 9): the 1024-byte plan splits
    # the default params into K=2 buckets (w is 1920 B — its own bucket;
    # b rides the second), so the overlap_schedulability pass verifies the
    # traced graph actually exposes 2 independent compress→exchange chains
    # — the schedulability contract the bucketed overlap executor
    # (ISSUE 10) now delivers at runtime.
    _cfg("topk-allgather-bucketed", {"compressor": "topk",
                                     "compress_ratio": 0.3,
                                     "memory": "residual",
                                     "communicator": "allgather",
                                     "fusion": 1024}),
    # -- fused compress-and-pack wire formats (ISSUE 10) --------------------
    # qsgd at quantum_num<=7 ships 4-bit packed nibbles (2 codes/byte):
    # the payload is a sub-byte uint8 array, so numeric_safety's pack-width
    # contract re-verifies ops/packing.pack_4bit on every audit, and
    # wire_reconciliation prices the halved payload against the traced
    # all_gather — the staged path traced here is byte-identical in layout
    # to the fused Pallas kernel (bit-identity pinned in
    # tests/test_pallas_quant.py).
    _cfg("qsgd4-allgather-packed", {"compressor": "qsgd", "quantum_num": 7,
                                    "use_pallas": False, "memory": "none",
                                    "communicator": "allgather"}),
    # Bucketed executor × packed wire × hop-requant ring in one trace: two
    # independent per-bucket ring schedules (14 ppermute hops + 2 gathers),
    # each requantizing 4-bit packed partials — schedulability must count
    # K=2 chains and the wire model must reconcile per-bucket.
    _cfg("qsgd4-ring-packed-bucketed", {"compressor": "qsgd",
                                        "quantum_num": 7,
                                        "use_pallas": False,
                                        "memory": "none",
                                        "communicator": "ring",
                                        "fusion": 1024}),
    # The fused sign-bitpack Pallas kernel traced INSIDE the audited graph
    # (use_pallas=True runs the interpret-mode kernel off-TPU — same
    # pallas_call equation structure as on-chip): proves the kernels are
    # auditable, not a blind spot — the packed payload still reconciles
    # and the pack-width contract still runs.
    _cfg("signsgd-pallas-packed", {"compressor": "signsgd",
                                   "use_pallas": True, "memory": "none",
                                   "communicator": "allgather"}),
    # -- kernel-resident wire path (ISSUE 19) -------------------------------
    # 2-bit packed qsgd (quantum_num=1 → pack_width 2, 4 codes/byte)
    # through the double-buffered ring: pipeline=2 splits the flat buffer
    # into two segments whose full ring schedules trace as independent
    # compress→exchange chains — flow pass 5 counts them via the
    # grace/pipeline/<p> scope tags and requires >= pipeline chains, the
    # static referee for the runtime overlap the wire_pipeline discount
    # prices. Pack-width 2 is re-verified by pass 6's sub-byte audit.
    _cfg("qsgd2-ring-packed-pipelined", {"compressor": "qsgd",
                                         "quantum_num": 1,
                                         "use_pallas": False,
                                         "memory": "none",
                                         "communicator": "ring",
                                         "fusion": "flat", "pipeline": 2}),
    # Packed-wire homomorphic ring with the fused payload accumulate
    # traced INSIDE the audited graph (use_pallas=True → the interpret-
    # mode packed_int_accumulate kernel runs at every hop and the final
    # gather-sum): accum_bits=4 makes the 4-bit two's-complement field
    # BOTH the wire word and the hop accumulator, so
    # payload_sum_max_world tightens to (2^3 - 1)//quantum_num = 7 —
    # this entry audits at world=4 (inside the bound). The 8-way default
    # would fire the static accumulator finding AND the communicators'
    # runtime gate from the same constant, which is exactly the
    # graduated-rejection contract tests/test_wire.py pins at 2 bits.
    _cfg("homoqsgd4-ring-fused", {"compressor": "homoqsgd",
                                  "quantum_num": 1, "accum_bits": 4,
                                  "use_pallas": True, "memory": "residual",
                                  "communicator": "ring",
                                  "fusion": "flat"}, world=4),
    # The fused decode→accumulate boundary kernel inside the two-level
    # schedule: packed 4-bit qsgd through hier's intra-slice hop requants
    # AND the cross-slice boundary, with use_pallas=True swapping the
    # boundary's staged vmap-decompress + aggregate for the fused K-way
    # decode_accumulate pass (wire_fused() live) — the interpret-mode
    # pallas_call equations trace inside the audited graph, proving the
    # kernel-resident boundary is auditable end to end.
    _cfg("hier-fused-boundary", {"compressor": "qsgd", "quantum_num": 7,
                                 "use_pallas": True, "memory": "none",
                                 "communicator": "hier", "slice_size": 4,
                                 "fusion": "flat"}),
    # The fused-boundary schedule's train-mode twin under the full
    # resilience stack: the same packed qsgd + interpret-mode wire
    # kernels, now inside the guarded train step with the consensus audit
    # fingerprinting downstream — the pallas_call equations sit inside
    # the escape cond's compressed branch, and collective_consistency /
    # bit_exactness must bless the kernel-resident path exactly as they
    # bless the staged one.
    _cfg("hier-fused-boundary-guard-consensus",
         {"compressor": "qsgd", "quantum_num": 7, "use_pallas": True,
          "memory": "none", "communicator": "hier", "slice_size": 4,
          "fusion": "flat", "escape": "fp16", "consensus": True},
         passes=_NO_WIRE, mode="train",
         guard={"fallback_after": 3, "fallback_steps": 8}, consensus=True),
    # -- graft-watch variants (ISSUE 8): the watch summary adds a lax.cond
    #    (window-boundary predicate from the replicated step counter) whose
    #    taken branch issues an all_gather the untaken branch lacks — the
    #    exact branch-divergent-collective shape pass 1 condemns when the
    #    predicate is rank-varying, so these entries are the standing proof
    #    it blesses the legal version. The non-escape entries keep
    #    wire_reconciliation: the gather's (W-1)·12 B sit inside the
    #    documented atol, pinning that the watch cost stays "tiny" — a
    #    watch redesign that starts gathering big vectors every window
    #    becomes a lint error, not a silent telemetry tax.
    _cfg("topk-watch", {"compressor": "topk", "compress_ratio": 0.3,
                        "memory": "residual", "communicator": "allgather",
                        "telemetry": True, "watch": 5}),
    _cfg("qsgd-ring-watch", {"compressor": "qsgd", "quantum_num": 64,
                             "use_pallas": False, "memory": "none",
                             "communicator": "ring", "fusion": "flat",
                             "telemetry": True, "watch": 5}),
    _cfg("hier-watch", {"compressor": "topk", "compress_ratio": 0.01,
                        "topk_algorithm": "chunk", "memory": "residual",
                        "communicator": "hier", "slice_size": 4,
                        "fusion": "flat", "telemetry": True, "watch": 5}),
    # -- graft-adapt variants (ISSUE 15): the in-graph adaptive controller
    #    — a lax.switch over the WHOLE degradation ladder (branch 0 the
    #    dense escape psum, branch r the rung-r codec's full schedule)
    #    whose index derives from replicated policy state + the replicated
    #    fallback flag, plus the per-step scalar pmean/pmax signal
    #    reductions. These entries are the standing proof pass 1 blesses
    #    the legal version of EXACTLY the shape it exists to condemn
    #    (branch-divergent collective sequences under a predicate), and
    #    flow pass 6 audits every reachable rung's payload contract —
    #    including each shared-scale rung's payload_sum_max_world bound.
    #    Wire reconciliation is excluded like every escape-carrying entry:
    #    the ladder makes "the" wire cost R-modal by design (telemetry
    #    prices the flip per rung instead).
    _cfg("adapt-homoqsgd-ring",
         {"compressor": "homoqsgd", "quantum_num": 7, "memory": "residual",
          "communicator": "ring", "fusion": "flat", "escape": "fp16",
          "telemetry": True,
          "adapt": {"window": 5, "ladder": [{"quantum_num": 127}]}},
         passes=_NO_WIRE),
    _cfg("adapt-topk-hier",
         {"compressor": "topk", "compress_ratio": 0.01,
          "topk_algorithm": "chunk", "memory": "residual",
          "communicator": "hier", "slice_size": 4, "fusion": "flat",
          "escape": "fp16", "telemetry": True,
          "adapt": {"window": 5, "ladder": [{"compress_ratio": 0.04}]}},
         passes=_NO_WIRE),
    # The controller under the full resilience stack: the guard's psum-OR
    # feeds the fallback flag that forces rung 0, the consensus audit
    # fingerprints (and would repair) the replicated AdaptState, and the
    # ladder switch nests inside the guarded train step — every
    # replicated-predicate argument graft-adapt makes, verified in one
    # trace.
    _cfg("adapt-guard-consensus",
         {"compressor": "topk", "compress_ratio": 0.05,
          "memory": "residual", "communicator": "allgather",
          "escape": "fp16", "telemetry": True, "consensus": True,
          "adapt": {"window": 5, "ladder": [{"compress_ratio": 0.2}]}},
         passes=_NO_WIRE, mode="train",
         guard={"fallback_after": 3, "fallback_steps": 8}, consensus=True),
    # -- a PowerSGD rank ladder under adapt: the rung-invariant layout's
    #    standing proof. Every rung's Q/P state is padded to the ladder
    #    max rank so ONE lax.switch dispatches all rungs over one state
    #    shape — a rank move is a mask flip, never a reshape, which is
    #    what makes the adapt controller's tighten/loosen a pure index
    #    change the auditor can trace.
    _cfg("adapt-powersgd-rankladder",
         {"compressor": "powersgd", "compress_rank": 4,
          "memory": "powersgd", "communicator": "allreduce",
          "escape": "fp16", "telemetry": True,
          "adapt": {"window": 5, "ladder": [{"compress_rank": 1}]}},
         passes=_NO_WIRE),
    # homoqsgd flat under guard and consensus: the shared-scale
    # homomorphic codec inside the guarded train step with the consensus
    # audit fingerprinting its replicated state.
    _cfg("retune-incumbent-homoqsgd",
         {"compressor": "homoqsgd", "quantum_num": 7, "memory": "residual",
          "communicator": "allreduce", "fusion": "flat", "escape": "fp16",
          "telemetry": True, "consensus": True},
         passes=_NO_WIRE, mode="train",
         guard={"fallback_after": 3, "fallback_steps": 8}, consensus=True),
    # -- resilience variants: the conds the auditor exists for --------------
    _cfg("topk-escape-telemetry",
         {"compressor": "topk", "compress_ratio": 0.3, "memory": "residual",
          "communicator": "allgather", "escape": "fp16", "telemetry": True},
         passes=_NO_WIRE),
    _cfg("topk-guard-consensus",
         {"compressor": "topk", "compress_ratio": 0.3, "memory": "residual",
          "communicator": "allgather", "escape": "fp16", "telemetry": True,
          "consensus": True},
         passes=_NO_WIRE, mode="train",
         guard={"fallback_after": 3, "fallback_steps": 8}, consensus=True),
    _cfg("ring-guard-consensus",
         {"compressor": "qsgd", "quantum_num": 64, "use_pallas": False,
          "memory": "none", "communicator": "ring", "fusion": "flat",
          "escape": "fp16", "consensus": True},
         passes=_NO_WIRE, mode="train",
         guard={"fallback_after": 3, "fallback_steps": 8}, consensus=True),
    # The nested-axis schedule under the full resilience stack: the escape
    # cond's branches now differ by grouped sub-axis collectives, and the
    # consensus audit's fingerprint gathers run downstream of a
    # hierarchically-aggregated update — collective_consistency must bless
    # both (replicated predicates) with the two-level exchange in place.
    _cfg("hier-guard-consensus",
         {"compressor": "topk", "compress_ratio": 0.01,
          "topk_algorithm": "chunk", "memory": "residual",
          "communicator": "hier", "slice_size": 4, "fusion": "flat",
          "escape": "fp16", "consensus": True},
         passes=_NO_WIRE, mode="train",
         guard={"fallback_after": 3, "fallback_steps": 8}, consensus=True),
    # The bucketed executor under the full resilience stack (ISSUE 10):
    # the escape cond's compressed branch is now K=2 per-bucket pipelines
    # (its dense branch stays per-leaf — branches differ by whole
    # schedules, legal only because the fallback predicate is replicated),
    # the guard's post-exchange check runs once over ALL buckets' updates
    # and its rollback selects the whole per-bucket state tuple
    # atomically, and the consensus audit fingerprints downstream of the
    # split — collective_consistency and bit_exactness must bless all of
    # it with the bucketed schedule in place.
    _cfg("bucketed-guard-consensus",
         {"compressor": "topk", "compress_ratio": 0.3, "memory": "residual",
          "communicator": "allgather", "fusion": 1024, "escape": "fp16",
          "telemetry": True, "consensus": True},
         passes=_NO_WIRE, mode="train",
         guard={"fallback_after": 3, "fallback_steps": 8}, consensus=True),
    # The homomorphic two-level schedule under the full resilience stack
    # (ISSUE 13): the escape cond's compressed branch is now the hier
    # payload-space integer summation (negotiate pmax + int ppermute hops
    # + int cross-slice gather-sum + ONE decode) while its dense branch
    # stays the fp16 psum — branches differ by whole schedules, legal only
    # because the fallback predicate is replicated; the consensus audit
    # fingerprints downstream of the homomorphic aggregate, so
    # collective_consistency and bit_exactness must bless the zero-requant
    # path end to end.
    _cfg("homoqsgd-hier-guard-consensus",
         {"compressor": "homoqsgd", "quantum_num": 7, "memory": "residual",
          "communicator": "hier", "slice_size": 4, "fusion": "flat",
          "escape": "fp16", "consensus": True},
         passes=_NO_WIRE, mode="train",
         guard={"fallback_after": 3, "fallback_steps": 8}, consensus=True),
    # The three-level WAN schedule under the full resilience stack
    # (ISSUE 16): the escape cond's compressed branch now carries THREE
    # nested levels of grouped sub-axis collectives (intra-slice hops,
    # same-region cross-slice gather, cross-region gather) plus the
    # slice- and region-boundary requants, while its dense branch stays
    # the fp16 psum; the consensus audit fingerprints downstream of the
    # three-level aggregate — collective_consistency and bit_exactness
    # must bless every replicated-predicate argument with both extra
    # boundaries in place.
    _cfg("hier3-guard-consensus",
         {"compressor": "topk", "compress_ratio": 0.25,
          "topk_algorithm": "chunk", "memory": "residual",
          "communicator": "hier", "slice_size": 2, "region_size": 4,
          "fusion": "flat", "escape": "fp16", "consensus": True},
         passes=_NO_WIRE, mode="train",
         guard={"fallback_after": 3, "fallback_steps": 8}, consensus=True),
    # The full observability+resilience stack in one trace: watch's gated
    # gather, the escape cond, the guard's psum-OR and the consensus audit
    # all nested in one train step — every replicated-predicate argument
    # the system makes, verified together.
    _cfg("watch-guard-consensus",
         {"compressor": "topk", "compress_ratio": 0.3, "memory": "residual",
          "communicator": "allgather", "escape": "fp16", "telemetry": True,
          "watch": 5, "consensus": True},
         passes=_NO_WIRE, mode="train",
         guard={"fallback_after": 3, "fallback_steps": 8}, consensus=True),
    # The sharded-model resilience stack in one 2-D trace (ISSUE 14): a
    # ROUTED rscatter exchange (per-leaf codecs, per-shard reduce-scatter
    # over dp) under guard + consensus on the dp×fsdp mesh. The escape
    # cond's branches differ by whole routed schedules, the guard's
    # psum-OR and the consensus audit's fingerprint gathers all run over
    # the dp axis only — collective_consistency must bless every
    # replicated-predicate argument with the 2-D seeding in place
    # (fingerprints match replicas per fsdp shard by construction).
    _cfg("rscatter-fsdp-routed-guard-consensus",
         {"compressor": "topk", "compress_ratio": 0.3, "memory": "residual",
          "communicator": "rscatter", "fsdp_axis": "fsdp",
          "route": [("b", {"compressor": "fp16", "memory": "none",
                           "communicator": "allreduce"})],
          "escape": "fp16", "consensus": True},
         passes=_NO_WIRE, mode="train", fsdp=2,
         guard={"fallback_after": 3, "fallback_steps": 8}, consensus=True),
]

# -- tuner-generated variants (ISSUE 12) -----------------------------------
# graft-tune's candidate generator crosses codec/communicator/fusion knobs
# the hand-written registry left uncovered (bucketed executor OVER the
# two-level hier schedule; packed 4-bit wire through hier's hop AND
# slice-boundary requant points). Registering them here means
# `graft_lint --all-configs` audits everything the tuner can emit — the
# tuner consumes this registry, so a variant it may shortlist is never a
# lint blind spot. Entries live in grace_tpu.tuning.candidates (lazy
# analysis imports there keep this append cycle-free).
from grace_tpu.tuning.candidates import variant_audit_entries  # noqa: E402

AUDIT_CONFIGS.extend(
    _cfg(name, params) for name, params, _why in variant_audit_entries())


def build_grace(entry: Dict[str, Any]):
    """The Grace bundle for one registry entry."""
    from grace_tpu.helper import grace_from_params
    return grace_from_params(dict(entry["params"]))


def overlap_bound_report(entry: Dict[str, Any], *, world: int = 8
                         ) -> Optional[Dict[str, Any]]:
    """Schedulability evidence for one bucketed (``fusion=<int bytes>``)
    update-mode registry entry: the static overlap upper bound, the counted
    independent compress→exchange chains, and the bucketing plan's promised
    K. ``None`` for entries the overlap sandwich doesn't apply to (non-int
    fusion, or train mode — the fwd/bwd graph drowns the bound in model
    compute). Written into ``LINT_LAST.json`` by ``tools/graft_lint.py
    --all-configs`` so the measured side of the sandwich
    (``tools/perf_report.py --overlap-config``) always has the static side
    on record next to the lint verdict it came from."""
    from grace_tpu.analysis import flow

    fusion = entry["params"].get("fusion")
    if entry.get("mode", "update") != "update" \
            or isinstance(fusion, bool) or not isinstance(fusion, int):
        return None
    world = int(entry.get("world") or world)
    grace = entry.get("grace") or build_grace(entry)
    traced = trace_update(grace, world=world, name=entry["name"],
                          meta={"grace": grace})
    s = flow.overlap_summary(traced)
    bound = s["static_overlap_bound"]
    return {"static_overlap_bound": (round(bound, 6)
                                     if bound is not None else None),
            "independent_chains": int(s["independent_chains"]),
            "expected_chains": flow._expected_chains(traced),
            "exchange_collectives": int(s["exchange_collectives"]),
            "world": int(world)}


def audit_config(entry: Dict[str, Any], *, world: int = 8
                 ) -> List[Finding]:
    """Trace one registry entry (or an ad-hoc ``{'name', 'params', ...}``
    dict) and run its passes. Trace failures surface as findings, not
    exceptions — a config that stops tracing at all is itself a finding."""
    name = entry["name"]
    passes = tuple(entry.get("passes") or PASS_NAMES)
    world = int(entry.get("world") or world)
    grace = entry.get("grace") or build_grace(entry)
    meta = {"grace": grace, "params": entry.get("params")}
    try:
        if entry.get("mode", "update") == "train":
            traced = trace_train_step(
                grace, world=world, guard=entry.get("guard"),
                consensus=entry.get("consensus"), name=name, meta=meta,
                fsdp=entry.get("fsdp"))
        else:
            traced = trace_update(grace, world=world, name=name, meta=meta,
                                  fsdp=entry.get("fsdp"))
    except Exception as e:                               # noqa: BLE001
        return [Finding(
            pass_name="trace", config=name, severity="error",
            message=(f"config failed to trace on the abstract mesh: "
                     f"{type(e).__name__}: {e} — if this is a "
                     "ConcretizationTypeError, a traced value is forcing a "
                     "host sync (Python control flow / float() on a "
                     "tracer), the exact retrace hazard pass 4 hunts"))]
    return run_passes(traced, passes)


def audit_all(configs: Optional[Sequence[Dict[str, Any]]] = None, *,
              world: int = 8, progress=None) -> List[Finding]:
    """Audit every registry config; returns the concatenated findings."""
    findings: List[Finding] = []
    for entry in (configs if configs is not None else AUDIT_CONFIGS):
        if progress is not None:
            progress(entry["name"])
        findings.extend(audit_config(entry, world=world))
    return findings
