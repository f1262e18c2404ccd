#!/usr/bin/env python
"""Chaos smoke: LeNet under NaN injection must survive, and the guard must
actually fire.

A CI-able end-to-end probe of the resilience subsystem (ISSUE 1): train
LeNet on synthetic MNIST-shaped data for --steps steps on the 8-device mesh
with a --nan-prob per-(step, leaf) NaN implant on one rank
(``ChaosCommunicator``), under the full guard + dense-fallback stack.

Telemetry (ISSUE 2): the run records the in-graph telemetry ring
(grad/update norms, residual health, compression error, effective wire
bytes across the dense-fallback flip) and drains it through a provenance-
stamped JSONL artifact at --telemetry-out, with guard transitions emitted
into the same stream by ``GuardMonitor(sink=...)``. Render it with
``python tools/telemetry_report.py <artifact>``.

SDC scenario (ISSUE 3): ``--sdc`` runs the *full* chaos matrix — the NaN
injection above PLUS single-rank silent data corruption: ``ChaosParams``
flips one bit of one param element in exactly one device's replica at
``--sdc-steps``, a fault the guard is structurally blind to (finite values,
rank-identical updates). The consensus auditor
(``grace_tpu.resilience.consensus``, armed via ``consensus=``/
``make_train_step(consensus=...)``) must detect and repair it within one
audit window; repairs/escalations are emitted as ``consensus_repair`` /
``consensus_escalation`` events into the same JSONL artifact as the
telemetry rows and guard events (``ConsensusMonitor``), so the audit trail
is a CI artifact.

Exit status (for CI):
  0  final loss is finite AND the guard tripped at least once AND (with
     --sdc) every injected corruption was repaired and replicas end
     bit-identical
  1  final loss is non-finite (the guard failed to contain the faults), the
     guard never tripped (injection is not reaching the pipeline — the
     smoke itself is broken), or --sdc corruption went undetected /
     replicas end diverged

Hierarchical scenario (ISSUE 7): ``--hier`` swaps the communicator for the
two-level ICI×DCN ``HierarchicalAllreduce`` (``--slice-size`` ranks per
slice), so the guard's atomic rollback and the consensus repair are
exercised over the nested grouped-collective exchange — and the telemetry
artifact's ``wire_bytes_ici``/``wire_bytes_dcn`` rows carry the mixed
per-link split.

Homomorphic scenario (ISSUE 13): ``--homo`` swaps the codec for the
shared-scale homomorphic QSGD (``payload_algebra='shared_scale'``), so the
fault matrix rides the zero-requant payload-space integer summation: a
poisoned gradient NaNs the negotiated scale (pmax propagates NaN), every
rank's single decode goes NaN, and the guard's replicated predicate must
trip fleet-wide with rollback atomic around the hoisted negotiation.
Combine with ``--hier`` for the slice-boundary integer-add variant.

Watch scenario (ISSUE 8): ``--watch`` seeds a single-rank
*compression-error drift* — ``ChaosCompressor(drift_scale=...)``
attenuates one rank's payload values every step. The fault is perfectly
finite (the guard is structurally blind: NaN injection is disabled in
this mode and the smoke REQUIRES the guard to stay silent) and lives in
per-rank state (the consensus audit is blind by design) — yet graft-watch
(``grace_tpu.telemetry.aggregate`` + ``anomaly``) must flag the drifting
rank with a ``watch_anomaly`` record in the artifact within one watch
window, attributing the exact rank, before any guard/consensus event
exists. Combine with ``--sdc`` to cross-validate: the consensus repair
zeroes the SDC rank's residuals, which the watch skew detector also sees.

Adapt scenario (ISSUE 15): ``--adapt`` drills the in-graph adaptive
compression controller (``grace_tpu.resilience.adapt``) through its three
claims in one timeline-ordered run. Phase A seeds a single-rank
compression-error drift (``ChaosCompressor(drift_scale=...)`` on every
ladder rung's codec — finite, so the guard MUST stay silent) and requires
the controller to TIGHTEN a rung within one window of the spike, with the
``adapt_tighten`` event landing in the artifact BEFORE any guard event
exists. Phase B removes the drift and requires the controller to LOOSEN
back after ``quiet_windows`` quiet windows (the hysteresis claim). Phase C
injects NaNs so the guard genuinely trips, and requires the controller to
register the trip as escalate-and-hold evidence (``escalations > 0``) —
the ladder-floor-too-loose semantics. Evidence (tighten/loosen counts and
steps, the tighten-before-guard ordering verdict, the rung trace) lands in
``--adapt-out`` (ADAPT_LAST.json);
``adapt_*`` events stream into the telemetry JSONL (timeline kind
``adapt``).

Elastic scenario (ISSUE 11): ``--elastic`` runs the full preemption
lifecycle on the 8-device mesh — drift on one rank (guard-blind, like
``--watch``) until graft-watch flags it, the :class:`ElasticController`
drains (last-known-good ``Checkpointer`` save), the flagged rank is killed
and the run RESUMES at W−1 (``reshard_grace_state``: replicated state
carried bit-exactly, per-rank residuals/rings re-initialized, validated
against flow pass 7's footprint model), then the rank REJOINS at W with
params restored from the stale pre-departure checkpoint and must pass the
consensus-gated rejoin barrier (one forced fingerprint audit; repairs ==
rejoins, replicas bit-identical after). With ``--hier`` the kill takes the
flagged rank's WHOLE slice — a K→K−1 DCN-level resize that keeps
``slice_size``. Evidence (resize events, rejoin fingerprint pricing,
convergence-floor verdict, per-world footprint checks) lands in
``--elastic-out`` (ELASTIC_LAST.json);
``elastic_*`` events additionally stream into the telemetry JSONL.

Region scenario (ISSUE 16): ``--region`` runs the cross-region failure
lifecycle on the 8-device mesh laid out as 2 regions × 2 slices × 2 ranks
(``Topology(slice_size=2, region_size=4)``, three-level hier exchange).
Drift is seeded on one rank PER SLICE of the doomed region (guard-blind);
graft-watch flags them, and once a quorum (``region_quorum=0.5``) of the
region's ranks carries skew episodes the :class:`ElasticController`
recognizes the region-wide episode (:meth:`region_scope`) and handles it
as ONE drain → resize → rejoin transition — never ``region_size``
independent rank losses. The kill takes the WHOLE region: an R→R−1
WAN-level resize that collapses to the two-tier ``Topology(slice_size=2)``
when a single region remains, resumes at W−4, then the region REJOINS at
W with stale pre-departure params implanted on every lost rank and must
pass the consensus-gated rejoin barrier (one region rejoin == one barrier
repair event; replicas bit-identical after). The guard must stay silent
throughout the healthy path, and the convergence floor is judged after
the rejoin. Evidence lands in ``--region-out`` (REGION_LAST.json).

Usage::

    JAX_PLATFORMS=cpu python tools/chaos_smoke.py            # defaults
    python tools/chaos_smoke.py --steps 200 --nan-prob 0.01
    python tools/chaos_smoke.py --sdc                        # + param SDC
    python tools/chaos_smoke.py --sdc --hier --slice-size 4  # hier matrix
    python tools/chaos_smoke.py --hier --homo                # zero-requant
    python tools/chaos_smoke.py --watch --watch-rank 3       # drift watch
    python tools/chaos_smoke.py --elastic                    # kill + rejoin
    python tools/chaos_smoke.py --elastic --hier --slice-size 4  # slice kill
    python tools/chaos_smoke.py --region                     # region kill
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def _write_evidence_doc(doc: dict, out_path: str, *, world: int,
                        slice_size=None, region_size=None,
                        label: str = "evidence") -> None:
    """The one exit for chaos evidence docs: stamp the uniform
    n_devices/topology/git_rev provenance triple, write atomically."""
    import json

    from grace_tpu.utils.logging import git_commit
    tiers = ["ici"]
    if slice_size:
        tiers.append("dcn")
    if region_size:
        tiers.append("wan")
    doc = {**doc, "git_rev": git_commit(), "n_devices": world,
           "topology": {"world": world, "tiers": tiers,
                        "slice": slice_size or None,
                        "region": region_size or None}}
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    os.replace(tmp, out_path)
    print(f"[chaos_smoke] {label}: {out_path}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--nan-prob", type=float, default=0.01,
                    help="per-(step, leaf) NaN implant probability")
    ap.add_argument("--rank", type=int, default=0,
                    help="mesh index the faults land on")
    ap.add_argument("--batch", type=int, default=32,
                    help="global batch (split over 8 devices)")
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--fallback-after", type=int, default=3)
    ap.add_argument("--fallback-steps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry-out", default="chaos_telemetry.jsonl",
                    help="JSONL telemetry artifact path ('' disables)")
    ap.add_argument("--telemetry-every", type=int, default=25,
                    help="steps per telemetry flush (one device_get each)")
    ap.add_argument("--sdc", action="store_true",
                    help="also inject single-rank param SDC (ChaosParams) "
                         "and require the consensus auditor to repair it")
    ap.add_argument("--sdc-rank", type=int, default=5,
                    help="mesh index whose param replica gets the bitflips")
    ap.add_argument("--sdc-steps", default="",
                    help="comma-separated injection steps (default: two "
                         "hits at 1/3 and 2/3 of --steps)")
    ap.add_argument("--audit-every", type=int, default=20,
                    help="consensus audit interval (with --sdc)")
    ap.add_argument("--profile", action="store_true",
                    help="record runtime profiling (step-time percentiles, "
                         "compile/retrace events, memory watermarks, "
                         "GraceState footprint check) into the telemetry "
                         "artifact as perf_* events "
                         "(grace_tpu.profiling.ProfileRecorder)")
    ap.add_argument("--hier", action="store_true",
                    help="run the chaos matrix over the hierarchical "
                         "ICI×DCN communicator (communicator='hier', "
                         "fusion='flat') instead of allgather — "
                         "guard rollback and consensus repair must stay "
                         "atomic across the two-level grouped exchange")
    ap.add_argument("--slice-size", type=int, default=4,
                    help="with --hier: ranks per ICI slice (the 8-device "
                         "mesh then spans 8/slice_size slices)")
    ap.add_argument("--homo", action="store_true",
                    help="run the chaos matrix over the aggregation-"
                         "homomorphic codec (compressor='homoqsgd', "
                         "payload_algebra='shared_scale') instead of "
                         "topk — the NaN implant must propagate through "
                         "the zero-requant payload-space integer "
                         "summation (and, with --hier, the boundary "
                         "integer add) to trip the guard on every rank, "
                         "and rollback must stay atomic around the "
                         "hoisted scale negotiation")
    ap.add_argument("--watch", action="store_true",
                    help="graft-watch scenario: seed a single-rank "
                         "compression-error drift (finite — guard-blind; "
                         "per-rank — consensus-blind) and require a "
                         "watch_anomaly record naming that rank within "
                         "one watch window. Disables NaN injection: the "
                         "guard MUST stay silent, proving watch warns "
                         "where guard/consensus cannot")
    ap.add_argument("--watch-rank", type=int, default=3,
                    help="mesh index whose encoder drifts (with --watch)")
    ap.add_argument("--drift-scale", type=float, default=0.5,
                    help="payload attenuation of the drifting rank "
                         "(with --watch)")
    ap.add_argument("--watch-window", type=int, default=10,
                    help="steps between in-graph cross-rank health "
                         "summaries (with --watch)")
    ap.add_argument("--adapt", action="store_true",
                    help="adaptive-controller scenario (ISSUE 15): "
                         "phase A single-rank drift -> controller "
                         "tightens within one window (guard silent, "
                         "adapt_tighten precedes any guard event); "
                         "phase B quiet -> controller loosens back; "
                         "phase C NaN injection -> guard trips and the "
                         "controller escalates-and-holds")
    ap.add_argument("--adapt-window", type=int, default=8,
                    help="controller decision window in steps "
                         "(with --adapt)")
    ap.add_argument("--adapt-rank", type=int, default=3,
                    help="mesh index whose encoder drifts in phase A "
                         "(with --adapt)")
    ap.add_argument("--adapt-out", default="ADAPT_LAST.json",
                    help="evidence JSON path for --adapt ('' disables)")
    ap.add_argument("--elastic", action="store_true",
                    help="run the full elastic lifecycle: drift → watch "
                         "drain signal → kill the flagged rank (its whole "
                         "slice with --hier) → resume at W-1 → rejoin at W "
                         "behind the consensus fingerprint barrier. "
                         "Disables NaN injection (the faults here are "
                         "drift and staleness; the guard must stay silent)")
    ap.add_argument("--elastic-rank", type=int, default=5,
                    help="mesh index that degrades and dies (with "
                         "--elastic; under --hier its whole slice is lost)")
    ap.add_argument("--elastic-out", default="ELASTIC_LAST.json",
                    help="evidence JSON path for --elastic ('' disables)")
    ap.add_argument("--region", action="store_true",
                    help="run the cross-region failure lifecycle (ISSUE "
                         "16): three-tier mesh (2 regions × 2 slices × 2 "
                         "ranks), drift on one rank per slice of the "
                         "doomed region → watch flags them → the "
                         "controller recognizes the region-wide episode "
                         "(region_scope quorum) and drains ONCE → the "
                         "whole region dies (R→R−1, topology collapses "
                         "to two-tier) → resume at W−4 → the region "
                         "rejoins at W behind the consensus barrier")
    ap.add_argument("--region-size", type=int, default=4,
                    help="ranks per region for --region (slices are half "
                         "a region wide so all three tiers are exercised)")
    ap.add_argument("--region-out", default="REGION_LAST.json",
                    help="evidence JSON path for --region ('' disables)")
    ap.add_argument("--drain-timeout", type=float, default=60.0,
                    help="ElasticController drain watchdog seconds "
                         "(--region; 0 disables the watchdog)")
    ap.add_argument("--floor", type=float, default=2.25,
                    help="convergence floor: the post-rejoin final loss "
                         "must be below this (10-class CE starts ~2.303)")
    ap.add_argument("--ckpt-dir", default="",
                    help="checkpoint directory for the elastic drain "
                         "(default: a fresh temp dir)")
    ap.add_argument("--fsdp", action="store_true",
                    help="sharded-model scenario (ISSUE 14): the chaos "
                         "matrix over a 2-D dp×fsdp mesh (4×2 of the 8 "
                         "devices) — a tensor-parallel MLP with w1 "
                         "sharded over fsdp, the ROUTED rscatter exchange "
                         "(big leaves topk per-shard reduce-scatter, "
                         "bias leaves dense fp16 psum), NaN injection "
                         "(guard rollback must stay atomic across the "
                         "per-shard exchanges) plus single-rank param "
                         "SDC (the consensus audit fingerprints "
                         "replicated fields PER FSDP SHARD over the dp "
                         "axis and must repair within one window, "
                         "residual zeroing scoped to the divergent "
                         "rank). Telemetry rows must carry the two-axis "
                         "wire split (wire_bytes_ici/wire_bytes_dcn)")
    ap.add_argument("--fsdp-size", type=int, default=2,
                    help="fsdp axis width (dp = 8 // fsdp_size)")
    ap.add_argument("--lint", action="store_true",
                    help="first run graft-lint (repo rules + a static "
                         "audit of this smoke's own grace config); "
                         "findings land in the telemetry artifact as "
                         "lint_finding events and fail the smoke")
    ap.add_argument("--pipeline", type=int, default=0,
                    help="ride the double-buffered wire path (ISSUE 19): "
                         "packed 4-bit qsgd over a ring with pipeline=N "
                         "segments (unless --homo/--hier already chose "
                         "the codec/communicator, which then just gain "
                         "pipeline=N). With --lint, the static audit "
                         "traces the FUSED spelling (use_pallas=True → "
                         "interpret-mode wire kernels inside the audited "
                         "graph) and flow pass 5 must count >= N "
                         "independent chains before chaos runs")
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    if os.environ["JAX_PLATFORMS"].lower() == "cpu":
        from grace_tpu.parallel import (relax_cpu_collective_timeouts,
                                        set_cpu_device_count)
        try:
            set_cpu_device_count(8)
        except RuntimeError:
            # Backend already initialized — e.g. main() invoked from the
            # pytest harness, whose conftest set the 8-device mesh up
            # before any test ran. Reuse its devices.
            pass
        relax_cpu_collective_timeouts()

    if args.adapt:
        return _adapt_main(args)
    if args.elastic:
        return _elastic_main(args)
    if args.region:
        return _region_main(args)
    if args.fsdp:
        return _fsdp_main(args)

    import jax.numpy as jnp
    import numpy as np
    import optax

    from grace_tpu import grace_from_params
    from grace_tpu.models import lenet
    from grace_tpu.parallel import data_parallel_mesh
    from grace_tpu.resilience import (ChaosCommunicator, ChaosParams,
                                      ConsensusConfig, audit_report,
                                      guarded_chain)
    from grace_tpu.telemetry import JSONLSink, TelemetryReader
    from grace_tpu.train import init_train_state, make_train_step
    from grace_tpu.utils.logging import (ConsensusMonitor, GuardMonitor,
                                         run_provenance)
    from grace_tpu.utils.metrics import guard_report

    mesh = data_parallel_mesh()
    world = mesh.devices.size
    batch = max(args.batch, world) // world * world

    rng = np.random.default_rng(args.seed)
    images = rng.normal(size=(4 * batch, 28, 28, 1)).astype(np.float32)
    labels = rng.integers(0, 10, size=(4 * batch,)).astype(np.int32)

    def loss_fn(params, b):
        x, y = b
        logits, _ = lenet.apply(params, {}, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    consensus = None
    sdc = None
    if args.sdc:
        consensus = ConsensusConfig(
            audit_every=args.audit_every,
            escalate_window=4 * args.audit_every,
            escalate_steps=args.fallback_steps)
        sdc_steps = (tuple(int(s) for s in args.sdc_steps.split(","))
                     if args.sdc_steps
                     else (args.steps // 3, 2 * args.steps // 3))
        sdc = ChaosParams(rank=args.sdc_rank, at_steps=sdc_steps,
                          seed=args.seed + 2)
    if args.watch:
        # The drift must be the ONLY fault: the scenario's claim is that
        # watch flags a degradation the guard cannot see, so the guard
        # staying silent is part of the assertion.
        if args.nan_prob:
            print("[chaos_smoke] --watch: disabling NaN injection "
                  f"(nan_prob {args.nan_prob} -> 0.0) — the drift "
                  "scenario requires a guard-silent run")
        args.nan_prob = 0.0
    grace_params = {"compressor": "topk", "compress_ratio": 0.3,
                    "memory": "residual",
                    "communicator": "allgather",
                    "escape": "fp16",
                    "consensus": consensus,
                    # ring sized to the flush window so a healthy
                    # run never wraps between flushes
                    "telemetry": max(2 * args.telemetry_every, 16)}
    if args.watch:
        grace_params["watch"] = {
            "window": args.watch_window,
            # summary ring sized so a flush window never wraps it
            "capacity": max(2 * args.telemetry_every // args.watch_window,
                            8)}
    if args.homo and args.watch:
        print("[chaos_smoke] --homo is incompatible with --watch: the "
              "drift injector attenuates float payload lanes and the "
              "homomorphic codec ships integer levels (drift would be a "
              "silent no-op, voiding the scenario's claim)",
              file=sys.stderr)
        return 1
    if args.homo:
        # Homomorphic scenario (ISSUE 13): a NaN poisoned into one rank's
        # gradient rides the negotiate pmax (NaN-max → shared scale NaN)
        # and/or the integer level sums' decode into EVERY rank's update,
        # so the guard's replicated predicate must trip fleet-wide and the
        # rollback must restore GraceState around the zero-requant path.
        grace_params.update(compressor="homoqsgd", quantum_num=7)
        grace_params.pop("compress_ratio", None)
    if args.hier:
        # Guard + consensus over the two-level ICI×DCN exchange: the NaN
        # implant must propagate through the intra-slice ring AND the
        # cross-slice grouped gather to every rank (or the guard's psum-OR
        # desyncs), and the consensus repair must leave replicas
        # bit-identical when the update itself was hierarchically
        # aggregated. slice_size also flips the telemetry rows to the
        # mixed wire_bytes_ici/wire_bytes_dcn split.
        grace_params.update(communicator="hier",
                            slice_size=args.slice_size,
                            fusion="flat")
    if args.pipeline > 1:
        # graft-wire scenario (ISSUE 19): the double-buffered ring. The
        # RUN rides use_pallas='auto' (staged off-TPU, kernel on-chip —
        # bit-identical either way, the pack_widths contract); the --lint
        # audit below flips to use_pallas=True so the fused
        # decode→accumulate kernels trace INSIDE the audited pipelined
        # graph. --homo/--hier keep their own codec/communicator and just
        # gain the segmented schedule.
        if not (args.homo or args.hier):
            grace_params.update(compressor="qsgd", quantum_num=7,
                                use_pallas="auto", communicator="ring",
                                fusion="flat")
            grace_params.pop("compress_ratio", None)
        grace_params["pipeline"] = args.pipeline
    grc = grace_from_params(grace_params)
    grc = dataclasses.replace(grc, communicator=ChaosCommunicator(
        inner=grc.communicator, nan_prob=args.nan_prob, rank=args.rank,
        seed=args.seed + 1))
    if args.watch:
        from grace_tpu.resilience import ChaosCompressor
        grc = dataclasses.replace(grc, compressor=ChaosCompressor(
            inner=grc.compressor, drift_scale=args.drift_scale,
            rank=args.watch_rank, seed=args.seed + 3))
    tx = guarded_chain(grc, optax.sgd(args.lr),
                       fallback_after=args.fallback_after,
                       fallback_steps=args.fallback_steps)

    params, _ = lenet.init(jax.random.key(args.seed))
    state = init_train_state(params, tx, mesh)
    step = make_train_step(loss_fn, tx, mesh, donate=False,
                           consensus=consensus)

    sink = None
    reader = None
    if args.watch and not args.telemetry_out:
        print("[chaos_smoke] --watch requires --telemetry-out: the "
              "acceptance artifact IS the watch_anomaly record",
              file=sys.stderr)
        return 1
    if args.telemetry_out:
        prov = run_provenance(
            data="synthetic",
            tool="chaos_smoke",
            argv=" ".join(sys.argv[1:]),
            nan_prob=args.nan_prob, steps=args.steps,
            fallback_after=args.fallback_after,
            fallback_steps=args.fallback_steps,
            homo=bool(args.homo))
        sink = JSONLSink(args.telemetry_out, provenance=prov)
        reader = TelemetryReader(sink, every=args.telemetry_every,
                                 anomaly=args.watch)
    monitor = GuardMonitor(sink=sink)
    consensus_mon = ConsensusMonitor(sink=sink)
    profiler = None
    if args.profile:
        from grace_tpu.profiling import ProfileRecorder
        # Shares the telemetry sink so perf_* records land in the same
        # JSONL stream as the metric rows and guard/consensus events (one
        # artifact covers one run); close() is NOT delegated — the smoke
        # owns the sink's lifetime.
        profiler = ProfileRecorder(sink=sink, every=args.telemetry_every,
                                   step_fn=step)

    if args.lint:
        # Static gate before any step runs: repo rules + the jaxpr
        # passes over THIS smoke's production config (pre-chaos-wrapper —
        # the injectors are test fixtures, not an audited deployment).
        # Findings become lint_finding events in the same JSONL artifact
        # as the guard/consensus trail; errors fail the smoke fast.
        from grace_tpu.analysis import audit_config, run_repo_rules
        from grace_tpu.analysis.report import emit_to_sink
        lint_params = dict(grace_params)
        if args.pipeline > 1:
            # Audit the FUSED spelling of the pipelined wire: forcing the
            # kernels on (interpret off-TPU) puts the decode→accumulate
            # hops inside the audited graph, and flow pass 5's referee
            # must count >= pipeline independent chains per bucket.
            lint_params["use_pallas"] = True
        lint_findings = run_repo_rules() + audit_config(
            {"name": "chaos_smoke-config",
             "params": lint_params,
             # Everything except wire reconciliation (the escape cond makes
             # the wire cost bimodal, same exclusion as the registry's
             # escape entries) — the graft-flow passes (schedulability,
             # numeric safety, footprint) gate this run's config too.
             # ... plus the graft-sound stateful-semantics passes: the
             # chaos matrix's whole point is exercising guard rollback
             # and consensus repair, so the smoke config must itself
             # prove its rollback write-set and replication contract.
             "passes": ("collective_consistency", "bit_exactness",
                        "signature_stability", "overlap_schedulability",
                        "numeric_safety", "memory_footprint",
                        "rng_lineage", "rollback_coverage",
                        "replication_contract")})
        if sink is not None and lint_findings:
            emit_to_sink(lint_findings, sink)
        errors = [f for f in lint_findings if f.severity == "error"]
        print(f"[chaos_smoke] graft-lint: {len(errors)} error(s), "
              f"{len(lint_findings) - len(errors)} warning(s)")
        if errors:
            for f in errors:
                print(f"[chaos_smoke]   {f.pass_name} {f.config}: "
                      f"{f.message}", file=sys.stderr)
            print("[chaos_smoke] FAIL: graft-lint found static SPMD "
                  "hazards — not running the chaos matrix on a config "
                  "that can deadlock a pod", file=sys.stderr)
            if sink is not None:
                sink.close()
            return 1

    t0 = time.perf_counter()
    loss = float("nan")
    for i in range(args.steps):
        if sdc is not None:
            state = sdc(state, i)
        lo = (i * batch) % len(images)
        b = (jnp.asarray(images[lo:lo + batch]),
             jnp.asarray(labels[lo:lo + batch]))
        if profiler is not None:
            with profiler.step():
                state, loss = step(state, b)
                profiler.sync_on(loss)
            profiler.update(i)
        else:
            state, loss = step(state, b)
        monitor.update(i, guard_report(state))
        if sdc is not None:
            consensus_mon.update(i, audit_report(state))
        if reader is not None:
            reader.update(i, state)
    loss = float(loss)
    dt = time.perf_counter() - t0
    if profiler is not None:
        if args.steps % args.telemetry_every:
            profiler.flush(args.steps - 1)        # drain the tail window
        profiler.record_state_footprint(state, grc, params,
                                        world=world, step=args.steps - 1)
        arr = profiler.timer.steady * 1e3
        print(f"[chaos_smoke] profiling: step p50 "
              f"{np.percentile(arr, 50):.1f} ms, p99 "
              f"{np.percentile(arr, 99):.1f} ms over {arr.size} steps | "
              f"retraces {profiler.retraces}")
    if reader is not None:
        reader.flush(state)      # drain the tail window
        reader.close()
        print(f"[chaos_smoke] telemetry artifact: {args.telemetry_out} "
              f"({reader.flushes} flushes, {reader.dropped} dropped rows)")

    rep = guard_report(state)
    print(f"[chaos_smoke] {args.steps} steps in {dt:.1f}s | final loss "
          f"{loss:.4f} | skipped {rep['notfinite_count']} | "
          f"last_bad_step {rep['last_bad_step']} | "
          f"fallback_active {rep['fallback_active']}")

    if not np.isfinite(loss):
        print("[chaos_smoke] FAIL: final loss is non-finite — the guard did "
              "not contain the injected faults", file=sys.stderr)
        return 1
    if args.watch:
        anomalies = reader.monitor.anomalies if reader.monitor else []
        allowed = {args.watch_rank}
        if sdc is not None:
            # --sdc cross-validation: the consensus repair zeroes the SDC
            # rank's residuals, a legitimate residual-skew the watch sees.
            allowed.add(args.sdc_rank)
        # Attribution is judged on the CODEC-HEALTH metrics the drift
        # corrupts (compression error, residual norm). grad_norm skews are
        # excluded from the misattribution check: this smoke feeds each
        # rank a FIXED batch shard, so per-rank gradient-norm outliers are
        # real data heterogeneity the detector is right to report.
        fault_metrics = ("compression_error", "residual_norm")
        skews = [a for a in anomalies if a.get("kind") == "skew"
                 and a.get("metric") in fault_metrics]
        hits = [a for a in skews if a.get("rank") == args.watch_rank]
        wrong = [a for a in skews if a.get("rank") not in allowed]
        first = min((a["step"] for a in hits), default=None)
        print(f"[chaos_smoke] watch: {len(anomalies)} anomalies | "
              f"rank-{args.watch_rank} codec-skew hits {len(hits)} "
              f"(first at step {first}) | misattributed {len(wrong)}")
        if rep["notfinite_count"] != 0:
            print("[chaos_smoke] FAIL: guard tripped during the drift "
                  "scenario — the fault is supposed to be finite and "
                  "guard-invisible; the smoke itself is broken",
                  file=sys.stderr)
            return 1
        if not hits:
            print("[chaos_smoke] FAIL: seeded single-rank drift on rank "
                  f"{args.watch_rank} produced no skew watch_anomaly for "
                  "that rank", file=sys.stderr)
            return 1
        if wrong:
            print(f"[chaos_smoke] FAIL: skew anomalies misattributed to "
                  f"rank(s) {sorted({a['rank'] for a in wrong})}",
                  file=sys.stderr)
            return 1
        if first > args.watch_window:
            print(f"[chaos_smoke] FAIL: first rank-{args.watch_rank} "
                  f"anomaly at step {first} — later than one watch window "
                  f"({args.watch_window})", file=sys.stderr)
            return 1
    elif rep["notfinite_count"] == 0:
        print("[chaos_smoke] FAIL: guard never tripped — injection is not "
              "reaching the pipeline", file=sys.stderr)
        return 1
    if sdc is not None:
        arep = audit_report(state)
        diverged = max(
            len({np.asarray(s.data).tobytes()
                 for s in leaf.addressable_shards})
            for leaf in jax.tree_util.tree_leaves(state.params))
        print(f"[chaos_smoke] sdc: injected {len(sdc.injections)} | "
              f"audits {arep['audits']} | repairs {arep['repairs']} | "
              f"escalations {arep['escalations']} | "
              f"replica_variants {diverged}")
        if arep["repairs"] < len(sdc.injections):
            print("[chaos_smoke] FAIL: consensus auditor repaired "
                  f"{arep['repairs']} of {len(sdc.injections)} injected "
                  "corruptions", file=sys.stderr)
            return 1
        if diverged > 1:
            print("[chaos_smoke] FAIL: param replicas still diverged after "
                  "the final audit window", file=sys.stderr)
            return 1
    print("[chaos_smoke] OK")
    return 0


def _fsdp_main(args) -> int:
    """The sharded-model chaos scenario: guard rollback + consensus
    repair over a 2-D dp×fsdp mesh with the routed rscatter exchange.

    Exit 0 requires: final loss finite; the guard tripped (NaN injection
    reaches every per-shard exchange); every injected SDC repaired with
    residual zeroing scoped to the divergent rank (consensus fingerprints
    match replicas PER FSDP SHARD — param shards legitimately differ
    across fsdp); and the telemetry artifact's rows carry the two-axis
    wire split (``wire_bytes_ici``/``wire_bytes_dcn``).
    """
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from grace_tpu import grace_from_params
    from grace_tpu.parallel import make_mesh
    from grace_tpu.resilience import (ChaosCommunicator, ChaosParams,
                                      ConsensusConfig, audit_report,
                                      guarded_chain)
    from grace_tpu.telemetry import JSONLSink, TelemetryReader
    from grace_tpu.train import init_train_state, make_train_step
    from grace_tpu.transform import MeshSpec
    from grace_tpu.utils.logging import (ConsensusMonitor, GuardMonitor,
                                         run_provenance)
    from grace_tpu.utils.metrics import guard_report

    fsdp = max(1, args.fsdp_size)
    if 8 % fsdp:
        print(f"[chaos_smoke] --fsdp-size {fsdp} does not divide the "
              "8-device mesh", file=sys.stderr)
        return 1
    dp = 8 // fsdp
    mesh = make_mesh((dp, fsdp), ("data", "fsdp"))
    mesh_spec = MeshSpec("data", "fsdp")

    feat, hid, classes = 32, 16, 8
    rng = np.random.default_rng(args.seed)
    params = {
        "w1": jnp.asarray(rng.normal(scale=0.3, size=(feat, hid)),
                          jnp.float32),
        "b1": jnp.zeros((hid,), jnp.float32),
        "w2": jnp.asarray(rng.normal(scale=0.3, size=(hid, classes)),
                          jnp.float32),
        "b2": jnp.zeros((classes,), jnp.float32),
    }
    # EVERY param is fsdp-sharded — the honest FSDP layout. This is not
    # cosmetic: a param replicated across fsdp would have its gradient
    # aggregated independently per dp group (collectives span dp only),
    # so a single corrupt rank could contaminate ITS group's aggregate
    # and silently diverge the "replicated" copies ACROSS groups where
    # the per-fsdp-shard consensus audit structurally cannot see it.
    # Sharding everything over fsdp keeps each shard's trajectory inside
    # exactly one dp group — the audit's jurisdiction.
    param_specs = {"w1": P("fsdp", None), "b1": P("fsdp"),
                   "w2": P("fsdp", None), "b2": P("fsdp")}
    feat_sh, hid_sh = feat // fsdp, hid // fsdp

    def loss_fn(p, b):
        x, y = b
        f = lax.axis_index("fsdp")
        # FSDP forward: gather the sharded biases, contract each weight
        # shard against this shard's input slice, psum the partials over
        # fsdp. The all_gather's transpose hands each owner exactly its
        # shard's bias gradient — per-shard gradients by construction.
        b1 = lax.all_gather(p["b1"], "fsdp", axis=0, tiled=True)
        b2 = lax.all_gather(p["b2"], "fsdp", axis=0, tiled=True)
        xs = lax.dynamic_slice_in_dim(x, f * feat_sh, feat_sh, 1)
        h = jnp.tanh(lax.psum(xs @ p["w1"], "fsdp") + b1)
        hs = lax.dynamic_slice_in_dim(h, f * hid_sh, hid_sh, 1)
        logits = lax.psum(hs @ p["w2"], "fsdp") + b2
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    consensus = ConsensusConfig(
        audit_every=args.audit_every,
        escalate_window=4 * args.audit_every,
        escalate_steps=args.fallback_steps)
    sdc_steps = (tuple(int(s) for s in args.sdc_steps.split(","))
                 if args.sdc_steps
                 else (args.steps // 3, 2 * args.steps // 3))
    sdc = ChaosParams(rank=args.sdc_rank, at_steps=sdc_steps,
                      seed=args.seed + 2)

    grace_params = {
        "compressor": "topk", "compress_ratio": 0.3, "memory": "residual",
        "communicator": "rscatter", "fsdp_axis": "fsdp",
        # slice boundary inside the dp axis: the flat rscatter's rows
        # honestly price a DCN leg — the artifact's two-axis wire split.
        "slice_size": max(1, dp // 2),
        "route": [("b*", {"compressor": "fp16", "memory": "none",
                          "communicator": "allreduce"})],
        "escape": "fp16", "consensus": consensus,
        "telemetry": max(2 * args.telemetry_every, 16),
    }
    grc = grace_from_params(grace_params)
    grc = _dc.replace(grc, communicator=ChaosCommunicator(
        inner=grc.communicator, nan_prob=args.nan_prob, rank=args.rank,
        seed=args.seed + 1))
    tx = guarded_chain(grc, optax.sgd(args.lr),
                       fallback_after=args.fallback_after,
                       fallback_steps=args.fallback_steps)

    state = init_train_state(params, tx, mesh, axis_name=mesh_spec,
                             param_specs=param_specs)
    step = make_train_step(loss_fn, tx, mesh, axis_name=mesh_spec,
                           param_specs=param_specs, donate=False,
                           consensus=consensus)

    sink = reader = None
    if args.telemetry_out:
        prov = run_provenance(
            data="synthetic", tool="chaos_smoke",
            argv=" ".join(sys.argv[1:]),
            nan_prob=args.nan_prob, steps=args.steps,
            fsdp=fsdp, dp=dp)
        sink = JSONLSink(args.telemetry_out, provenance=prov)
        reader = TelemetryReader(sink, every=args.telemetry_every)
    monitor = GuardMonitor(sink=sink)
    consensus_mon = ConsensusMonitor(sink=sink)

    batch_n = max(args.batch, dp) // dp * dp
    images = rng.normal(size=(4 * batch_n, feat)).astype(np.float32)
    labels = rng.integers(0, classes, size=(4 * batch_n,)).astype(np.int32)

    def repairs_by_group(st) -> list:
        """Per-fsdp-group consensus repair counts. The AuditState is
        replicated WITHIN each dp group (its whole jurisdiction) but a
        repair in group 1 never bumps group 0's counter — reading one
        device (audit_report) under-reports, so sum the per-group view."""
        from grace_tpu.transform import GraceState
        audits = []

        def find(node):
            if isinstance(node, GraceState) and node.audit is not None:
                audits.append(node.audit)
            return node

        jax.tree_util.tree_map(find, st.opt_state,
                               is_leaf=lambda n: isinstance(n, GraceState))
        reps = audits[0].repairs
        per_dev = {s.device: int(np.asarray(s.data).reshape(-1)[0])
                   for s in reps.addressable_shards}
        return [max(per_dev[mesh.devices[d, f]] for d in range(dp))
                for f in range(fsdp)]

    loss = float("nan")
    t0 = time.perf_counter()
    for i in range(args.steps):
        state = sdc(state, i)
        lo = (i * batch_n) % len(images)
        b = (jnp.asarray(images[lo:lo + batch_n]),
             jnp.asarray(labels[lo:lo + batch_n]))
        state, loss = step(state, b)
        monitor.update(i, guard_report(state))
        consensus_mon.update(i, audit_report(state))
        if reader is not None:
            reader.update(i, state)
    loss = float(loss)
    dt = time.perf_counter() - t0
    if reader is not None:
        reader.flush(state)
        reader.close()

    rep = guard_report(state)
    arep = dict(audit_report(state))
    group_repairs = repairs_by_group(state)
    arep["repairs"] = sum(group_repairs)
    # Replicas must be bit-identical PER FSDP SHARD: group every param
    # leaf's device buffers by the global index window they cover (a
    # replicated leaf has one window — all 8 buffers must agree; w1 has
    # one window per fsdp shard — its dp replicas must agree within each).
    variants = 0
    for leaf in jax.tree_util.tree_leaves(state.params):
        groups: dict = {}
        for s in leaf.addressable_shards:
            key = str(s.index)
            groups.setdefault(key, set()).add(
                np.asarray(s.data).tobytes())
        variants = max(variants, max(len(v) for v in groups.values()))
    print(f"[chaos_smoke] fsdp: {args.steps} steps in {dt:.1f}s on "
          f"dp{dp}×fsdp{fsdp} | final loss {loss:.4f} | skipped "
          f"{rep['notfinite_count']} | injected {len(sdc.injections)} | "
          f"repairs {arep['repairs']} (per fsdp group: {group_repairs}) | "
          f"per-shard replica variants {variants}")

    ok = True
    if not np.isfinite(loss):
        print("[chaos_smoke] FAIL: final loss non-finite over the 2-D "
              "mesh", file=sys.stderr)
        ok = False
    if args.nan_prob and rep["notfinite_count"] == 0:
        print("[chaos_smoke] FAIL: guard never tripped — injection is "
              "not reaching the per-shard exchanges", file=sys.stderr)
        ok = False
    if arep["repairs"] < len(sdc.injections):
        print(f"[chaos_smoke] FAIL: consensus repaired {arep['repairs']} "
              f"of {len(sdc.injections)} injected corruptions over the "
              "2-D mesh", file=sys.stderr)
        ok = False
    if variants > 1:
        print("[chaos_smoke] FAIL: replicas still diverged within an "
              "fsdp shard after the final audit window", file=sys.stderr)
        ok = False
    if args.telemetry_out:
        import json as _json
        split_rows = both_axes = 0
        with open(args.telemetry_out) as f:
            for line in f:
                rec = _json.loads(line)
                if "step" not in rec or "wire_bytes" not in rec:
                    continue
                if "wire_bytes_ici" in rec and "wire_bytes_dcn" in rec:
                    split_rows += 1
                    if rec["wire_bytes_dcn"] > 0 and \
                            rec["wire_bytes_ici"] > 0:
                        both_axes += 1
        print(f"[chaos_smoke] fsdp: {split_rows} telemetry rows carry "
              f"the per-link split ({both_axes} with bytes on BOTH "
              "links)")
        if not split_rows:
            print("[chaos_smoke] FAIL: no telemetry row carries the "
                  "two-axis wire split", file=sys.stderr)
            ok = False
    print("[chaos_smoke] OK" if ok else "[chaos_smoke] FAIL",
          flush=True)
    return 0 if ok else 1


def _adapt_main(args) -> int:
    """The --adapt lifecycle: drift → tighten (before any guard event) →
    quiet → loosen → NaN → guard trip + escalate-and-hold. Returns 0 only
    when every acceptance fact holds (see module docstring)."""
    import dataclasses
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from grace_tpu import grace_from_params
    from grace_tpu.parallel import data_parallel_mesh
    from grace_tpu.resilience import (AdaptMonitor, ChaosCommunicator,
                                      ChaosCompressor, adapt_report,
                                      guarded_chain)
    from grace_tpu.telemetry import JSONLSink, TelemetryReader
    from grace_tpu.telemetry.timeline import Timeline
    from grace_tpu.train import init_train_state, make_train_step
    from grace_tpu.utils.logging import GuardMonitor, run_provenance
    from grace_tpu.utils.metrics import guard_report

    mesh = data_parallel_mesh()
    world = mesh.devices.size
    window = args.adapt_window
    # Phase split: A (drift — must tighten), B (quiet — must loosen),
    # C (NaN — guard trips, controller escalates). Each phase spans
    # enough windows for its claim.
    steps_a = max(3 * window, args.steps // 3)
    steps_b = max(4 * window, args.steps // 3)
    steps_c = max(window + args.fallback_after + args.fallback_steps + 2,
                  args.steps - steps_a - steps_b)

    # The degradation ladder: dense escape (rung 0) → gentle 8-bit qsgd
    # (rung 1) → aggressive 4-bit-ish qsgd (rung 2, the steady state).
    # Thresholds sit between the healthy steady-state error (~0.2-0.3 for
    # q=15 on this model) and the drifted rank's error (~drift_scale):
    # quiet runs read below loosen_error, the drifting rank's pmax
    # crosses tighten_peak within its first window.
    drift = 0.9
    grace_params = {
        "compressor": "qsgd", "quantum_num": 15, "use_pallas": False,
        "memory": "none", "communicator": "allgather",
        "escape": "fp16",
        "telemetry": max(2 * args.telemetry_every, 16),
        "adapt": {"window": window,
                  "ladder": [{"quantum_num": 127}],
                  "tighten_error": 0.5, "tighten_peak": 0.6,
                  "loosen_error": 0.35, "quiet_windows": 2,
                  "hold_windows": 2},
    }

    def build(drift_rank=None, nan_prob=0.0):
        """(grace, guarded tx) for one phase. The drift injector must
        wrap EVERY ladder rung's codec (the controller swaps codecs
        mid-run; a drift that only afflicted the top rung would vanish
        the moment the controller tightened — voiding the scenario)."""
        grc = grace_from_params(grace_params)
        if drift_rank is not None:
            def wrap(c):
                return ChaosCompressor(inner=c, drift_scale=drift,
                                       rank=drift_rank,
                                       seed=args.seed + 3)
            grc = dataclasses.replace(
                grc, compressor=wrap(grc.compressor),
                adapt=dataclasses.replace(
                    grc.adapt,
                    ladder=tuple(wrap(c) for c in grc.adapt.ladder)))
        if nan_prob:
            grc = dataclasses.replace(grc, communicator=ChaosCommunicator(
                inner=grc.communicator, nan_prob=nan_prob, rank=args.rank,
                seed=args.seed + 1))
        tx = guarded_chain(grc, optax.sgd(args.lr),
                           fallback_after=args.fallback_after,
                           fallback_steps=args.fallback_steps)
        return grc, tx

    # Small dense MLP (the _fsdp_main scale): three phase recompiles with
    # a 3-branch ladder each — LeNet-sized compiles would triple that
    # cost for no extra coverage.
    feat, hid, classes = 32, 16, 8
    rng = np.random.default_rng(args.seed)
    params = {
        "w1": jnp.asarray(rng.normal(scale=0.3, size=(feat, hid)),
                          jnp.float32),
        "b1": jnp.zeros((hid,), jnp.float32),
        "w2": jnp.asarray(rng.normal(scale=0.3, size=(hid, classes)),
                          jnp.float32),
        "b2": jnp.zeros((classes,), jnp.float32),
    }

    def loss_fn(p, b):
        x, y = b
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        logits = h @ p["w2"] + p["b2"]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    batch = max(args.batch, world) // world * world
    images = rng.normal(size=(4 * batch, feat)).astype(np.float32)
    labels = rng.integers(0, classes, size=(4 * batch,)).astype(np.int32)

    def at(i):
        lo = (i * batch) % (len(images) - batch + 1)
        return (jnp.asarray(images[lo:lo + batch]),
                jnp.asarray(labels[lo:lo + batch]))

    sink = reader = None
    if not args.telemetry_out:
        print("[chaos_smoke] --adapt requires --telemetry-out: the "
              "acceptance artifact IS the adapt_tighten/guard event "
              "ordering", file=sys.stderr)
        return 1
    prov = run_provenance(
        data="synthetic", tool="chaos_smoke",
        argv=" ".join(sys.argv[1:]), steps=args.steps,
        adapt=True, adapt_window=window, adapt_rank=args.adapt_rank)
    sink = JSONLSink(args.telemetry_out, provenance=prov)
    reader = TelemetryReader(sink, every=args.telemetry_every)
    adapt_mon = AdaptMonitor(sink=sink)
    monitor = GuardMonitor(sink=sink)

    total = float("nan")
    t0 = time.perf_counter()

    def run_phase(state, step_fn, lo, hi):
        loss = float("nan")
        for i in range(lo, hi):
            state, loss = step_fn(state, at(i))
            monitor.update(i, guard_report(state))
            adapt_mon.observe(reader.update(i, state))
        return state, float(loss)

    # ---- phase A: one rank's encoder drifts — tighten, guard silent ----
    grc_a, tx_a = build(drift_rank=args.adapt_rank)
    state = init_train_state(params, tx_a, mesh)
    step_a = make_train_step(loss_fn, tx_a, mesh, donate=False)
    state, _ = run_phase(state, step_a, 0, steps_a)
    adapt_mon.observe(reader.flush(state))        # drain the tail window
    guard_a = guard_report(state)
    tightens_a = [e for e in adapt_mon.events
                  if e["event"] == "adapt_tighten"]
    first_tighten = min((e["step"] for e in tightens_a), default=None)
    rep_a = adapt_report(state)
    print(f"[chaos_smoke] adapt phase A (drift rank {args.adapt_rank}): "
          f"{steps_a} steps | rung {rep_a['rung']} | tightens "
          f"{rep_a['tightens']} (first event step {first_tighten}) | "
          f"guard skips {guard_a['notfinite_count']}")
    if guard_a["notfinite_count"] != 0:
        print("[chaos_smoke] FAIL: guard tripped during the drift phase "
              "— the fault is finite and guard-invisible; the smoke "
              "itself is broken", file=sys.stderr)
        return 1
    if not tightens_a:
        print("[chaos_smoke] FAIL: seeded drift produced no adapt_tighten "
              "event — the controller is not reacting to the error "
              "spike", file=sys.stderr)
        return 1
    if first_tighten > 2 * window:
        print(f"[chaos_smoke] FAIL: first tighten at step {first_tighten} "
              f"— later than one window ({window}) plus the decision "
              "latency", file=sys.stderr)
        return 1

    # ---- phase B: drift off — the controller must loosen back ----------
    grc_b, tx_b = build()
    step_b = make_train_step(loss_fn, tx_b, mesh, donate=False)
    state, _ = run_phase(state, step_b, steps_a, steps_a + steps_b)
    adapt_mon.observe(reader.flush(state))
    loosens = [e for e in adapt_mon.events if e["event"] == "adapt_loosen"]
    rep_b = adapt_report(state)
    print(f"[chaos_smoke] adapt phase B (quiet): {steps_b} steps | rung "
          f"{rep_b['rung']} | loosens {rep_b['loosens']}")
    if not loosens:
        print("[chaos_smoke] FAIL: quiet phase produced no adapt_loosen "
              "event — the controller never recovers from degradation",
              file=sys.stderr)
        return 1

    # ---- phase C: NaN injection — guard trips, controller escalates ----
    grc_c, tx_c = build(nan_prob=1.0)
    step_c = make_train_step(loss_fn, tx_c, mesh, donate=False)
    state, total = run_phase(state, step_c, steps_a + steps_b,
                             steps_a + steps_b + steps_c)
    adapt_mon.observe(reader.flush(state))
    reader.close()
    dt = time.perf_counter() - t0

    guard_c = guard_report(state)
    rep_c = adapt_report(state)
    print(f"[chaos_smoke] adapt phase C (NaN): {steps_c} steps | final "
          f"loss {total:.4f} | guard skips {guard_c['notfinite_count']} | "
          f"escalations {rep_c['escalations']} | hold {rep_c['hold']} | "
          f"{dt:.1f}s total")

    # Ordering is judged from the ARTIFACT, not loop bookkeeping: the
    # first adapt event must precede the first guard event in the unified
    # timeline — tighten-before-guard is the scenario's whole claim.
    # (Step-less guard_only flush records are skipped: they carry
    # counters, not an event position.)
    tl = Timeline.from_jsonl(args.telemetry_out)
    first_adapt = next((e for e in tl.kinds("adapt")
                        if e.step is not None), None)
    first_guard = next((e for e in tl.kinds("guard")
                        if e.step is not None), None)
    ordering_ok = (first_adapt is not None and first_guard is not None
                   and first_adapt.step < first_guard.step)
    print(f"[chaos_smoke] adapt ordering: first adapt event step "
          f"{first_adapt.step if first_adapt else None} < first guard "
          f"event step {first_guard.step if first_guard else None} -> "
          f"{'OK' if ordering_ok else 'VIOLATED'}")

    if args.adapt_out:
        doc = {
            "tool": "chaos_smoke",
            "captured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "argv": " ".join(sys.argv[1:]),
            "world": world,
            "window": window,
            "ladder": ["fp16 dense escape (rung 0)",
                       "qsgd quantum_num=127 (rung 1)",
                       "qsgd quantum_num=15 (rung 2, steady state)"],
            "phases": {"drift": [0, steps_a],
                       "quiet": [steps_a, steps_a + steps_b],
                       "nan": [steps_a + steps_b,
                               steps_a + steps_b + steps_c]},
            "tighten": {"count": int(rep_c["tightens"]),
                        "first_step": first_tighten,
                        "within_one_window": bool(
                            first_tighten <= 2 * window)},
            "loosen": {"count": int(rep_c["loosens"]),
                       "first_step": min((e["step"] for e in loosens),
                                         default=None)},
            "escalations": int(rep_c["escalations"]),
            "final_rung": int(rep_c["rung"]),
            "first_adapt_step": (first_adapt.step if first_adapt
                                 else None),
            "first_guard_step": (first_guard.step if first_guard
                                 else None),
            "ordering_ok": bool(ordering_ok),
            "guard_skips": int(guard_c["notfinite_count"]),
            "final_loss": float(total),
        }
        _write_evidence_doc(doc, args.adapt_out, world=world,
                            label="adapt evidence")

    if not np.isfinite(total):
        print("[chaos_smoke] FAIL: final loss non-finite — the "
              "guard+ladder stack did not contain the NaN phase",
              file=sys.stderr)
        return 1
    if guard_c["notfinite_count"] == 0:
        print("[chaos_smoke] FAIL: guard never tripped in the NaN phase "
              "— injection is not reaching the pipeline", file=sys.stderr)
        return 1
    if rep_c["escalations"] == 0:
        print("[chaos_smoke] FAIL: the controller registered no "
              "escalate-and-hold evidence despite the guard's fallback "
              "windows", file=sys.stderr)
        return 1
    if not ordering_ok:
        print("[chaos_smoke] FAIL: the first adapt event does not "
              "precede the first guard event — tighten-before-guard is "
              "the scenario's claim", file=sys.stderr)
        return 1
    print("[chaos_smoke] OK")
    return 0


def _elastic_main(args) -> int:
    """The --elastic lifecycle: drift → drain → kill → W−1 resume → rejoin
    → W, with the consensus barrier gating the rejoin. Returns 0 only when
    every acceptance fact holds (see module docstring)."""
    import dataclasses
    import json
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from grace_tpu import grace_from_params
    from grace_tpu.checkpoint import Checkpointer
    from grace_tpu.core import Topology
    from grace_tpu.models import lenet
    from grace_tpu.parallel import data_parallel_mesh
    from grace_tpu.resilience import (ChaosCompressor, ConsensusConfig,
                                      ElasticController, guarded_chain,
                                      plan_resize, validate_resharded)
    from grace_tpu.telemetry import JSONLSink, TelemetryReader
    from grace_tpu.train import init_train_state, make_train_step
    from grace_tpu.utils.logging import GuardMonitor, run_provenance
    from grace_tpu.utils.metrics import guard_report

    devices = jax.devices()
    world = len(devices)
    doomed = args.elastic_rank
    if args.hier:
        if world % args.slice_size:
            print(f"[chaos_smoke] --elastic --hier: world {world} not a "
                  f"multiple of slice_size {args.slice_size}",
                  file=sys.stderr)
            return 1
        k = doomed // args.slice_size
        lost = tuple(range(k * args.slice_size, (k + 1) * args.slice_size))
        topo = Topology(slice_size=args.slice_size)
    else:
        lost = (doomed,)
        topo = Topology()
    plan = plan_resize(world, lost, topo)
    # Phase split: A (full world, drift until drained), B (survivors),
    # C (post-rejoin, where the convergence floor is judged).
    steps_a = max(args.steps // 3, 2 * args.watch_window)
    steps_b = max(args.steps // 4, 4)
    steps_c = max(args.steps - steps_a - steps_b, 4)

    consensus = ConsensusConfig(audit_every=args.audit_every)

    def build(slice_size, drift_rank=None):
        """(grace, guarded tx) for one phase. Rebuilding the transform is
        the resize's single topology-invalidation point."""
        p = {"compressor": "topk", "compress_ratio": 0.3,
             "memory": "residual", "communicator": "allgather",
             "escape": "fp16", "consensus": consensus,
             "telemetry": max(2 * args.telemetry_every, 16),
             "watch": {"window": args.watch_window,
                       "capacity": max(2 * args.telemetry_every
                                       // args.watch_window, 8)}}
        if args.hier:
            # A whole-slice loss keeps slice_size (the K→K−1 resize);
            # a partial loss would hand back None and the flat schedule —
            # exactly HierarchicalAllreduce.shrunk's contract.
            p.update(communicator="hier", fusion="flat")
            if slice_size:
                p["slice_size"] = slice_size
        grc = grace_from_params(p)
        if drift_rank is not None:
            grc = dataclasses.replace(grc, compressor=ChaosCompressor(
                inner=grc.compressor, drift_scale=args.drift_scale,
                rank=drift_rank, seed=args.seed + 3))
        tx = guarded_chain(grc, optax.sgd(args.lr),
                           fallback_after=args.fallback_after,
                           fallback_steps=args.fallback_steps)
        return grc, tx

    def batches(w):
        b = max(args.batch, w) // w * w
        rng = np.random.default_rng(args.seed)
        images = rng.normal(size=(4 * args.batch, 28, 28, 1)).astype(
            np.float32)
        labels = rng.integers(0, 10, size=(4 * args.batch,)).astype(np.int32)

        def at(i):
            lo = (i * b) % (len(images) - b + 1)
            return (jnp.asarray(images[lo:lo + b]),
                    jnp.asarray(labels[lo:lo + b]))
        return at

    def loss_fn(params, b):
        x, y = b
        logits, _ = lenet.apply(params, {}, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    sink = None
    reader = None
    if args.telemetry_out:
        prov = run_provenance(
            data="synthetic", tool="chaos_smoke",
            argv=" ".join(sys.argv[1:]), steps=args.steps,
            elastic=True, elastic_rank=doomed, hier=args.hier)
        sink = JSONLSink(args.telemetry_out, provenance=prov)
        reader = TelemetryReader(sink, every=args.telemetry_every,
                                 anomaly=True)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="grace_elastic_")
    ckpt = Checkpointer(ckpt_dir, max_to_keep=2)
    controller = ElasticController(consensus=consensus, checkpointer=ckpt,
                                   sink=sink, anomaly_threshold=1)
    monitor = GuardMonitor(sink=sink)

    # ---- phase A: full world, one rank drifting -------------------------
    mesh_a = data_parallel_mesh(devices)
    grc_a, tx_a = build(args.slice_size if args.hier else None,
                        drift_rank=doomed)
    params, _ = lenet.init(jax.random.key(args.seed))
    state = init_train_state(params, tx_a, mesh_a)
    step = make_train_step(loss_fn, tx_a, mesh_a, donate=False,
                           consensus=consensus)
    at = batches(world)
    t0 = time.perf_counter()
    first_loss = None
    drain_rank = None
    drain_step = None
    seen_anomalies = 0
    for i in range(steps_a):
        state, loss = step(state, at(i))
        if first_loss is None:
            first_loss = float(loss)
        monitor.update(i, guard_report(state))
        if reader is not None:
            reader.update(i, state)
            anomalies = reader.monitor.anomalies
            rank = controller.observe(i, anomalies[seen_anomalies:])
            seen_anomalies = len(anomalies)
            if rank is not None and drain_rank is None:
                drain_rank = rank
                drain_step = i
                controller.drain(i, state, rank)
    if reader is not None and drain_rank is None:
        # Tail window: the last flush may hold the first episode.
        reader.flush(state)
        rank = controller.observe(
            steps_a - 1, reader.monitor.anomalies[seen_anomalies:])
        if rank is not None:
            drain_rank, drain_step = rank, steps_a - 1
            controller.drain(steps_a - 1, state, rank)
    if reader is not None and drain_rank is None:
        print("[chaos_smoke] FAIL: seeded drift on rank "
              f"{doomed} produced no watch drain signal in {steps_a} "
              "steps — the early-warning channel is broken", file=sys.stderr)
        return 1
    if reader is None:
        # No telemetry stream to carry the warning — drain unconditionally
        # so the lifecycle below still runs (documented degraded mode).
        controller.drain(steps_a - 1, state, doomed)
        drain_rank, drain_step = doomed, steps_a - 1
    if drain_rank != doomed and not args.hier:
        print(f"[chaos_smoke] FAIL: drain signal named rank {drain_rank}, "
              f"but rank {doomed} is the one drifting", file=sys.stderr)
        return 1
    guard_a = guard_report(state)
    if guard_a["notfinite_count"] != 0:
        print("[chaos_smoke] FAIL: guard tripped during the drift phase — "
              "the elastic faults are supposed to be guard-invisible",
              file=sys.stderr)
        return 1

    # ---- kill + resize to the survivor world ----------------------------
    survivors = [devices[r] for r in plan.survivors]
    mesh_b = data_parallel_mesh(survivors)
    grc_b, tx_b = build(plan.topology.slice_size)
    state_b, resize_down = controller.resize(
        steps_a, state, tx_b, mesh_a, mesh_b, plan,
        grace=grc_b, params=params)
    print(f"[chaos_smoke] resize: W{plan.old_world} -> W{plan.new_world} "
          f"(lost {list(plan.lost_ranks)}, slice_size "
          f"{plan.topology.slice_size}, footprint_matches "
          f"{resize_down['footprint_matches']})")

    # ---- phase B: survivors keep training -------------------------------
    step_b = make_train_step(loss_fn, tx_b, mesh_b, donate=False,
                             consensus=consensus)
    at_b = batches(plan.new_world)
    loss_b = float("nan")
    for i in range(steps_a, steps_a + steps_b):
        state_b, loss_b = step_b(state_b, at_b(i))
        if reader is not None:
            reader.update(i, state_b)
    if not np.isfinite(float(loss_b)):
        print("[chaos_smoke] FAIL: loss went non-finite at the survivor "
              f"world W{plan.new_world}", file=sys.stderr)
        return 1

    # ---- rejoin at full world behind the consensus barrier --------------
    mesh_c = data_parallel_mesh(devices)
    grc_c, tx_c = build(args.slice_size if args.hier else None)
    grow = plan_resize(world, (), topo)   # no losses: W stays, fresh plan
    state_c, _ = controller.resize(
        steps_a + steps_b, state_b, tx_c, mesh_b, mesh_c,
        dataclasses.replace(grow, old_world=plan.new_world),
        grace=grc_c, params=params)
    # The rejoining rank(s) come back with the state they drained with —
    # restore the last-known-good checkpoint and implant it on exactly
    # the replicas that left, which is what a preempted process restoring
    # from disk looks like to the survivors.
    from grace_tpu.resilience import implant_stale_replica
    stale = ckpt.restore_last_good(state_c)
    for r in plan.lost_ranks:
        state_c = implant_stale_replica(state_c, r, stale.params)
    state_c, barrier = controller.rejoin(steps_a + steps_b, state_c, mesh_c)
    print(f"[chaos_smoke] rejoin: barrier_repairs "
          f"{barrier['barrier_repairs']} | replica_variants "
          f"{barrier['replica_variants']} | divergent rank "
          f"{barrier['last_divergent_rank']} | fingerprint "
          f"{barrier['fingerprint_bytes']} B")
    if barrier["barrier_repairs"] != 1:
        print(f"[chaos_smoke] FAIL: rejoin barrier repaired "
              f"{barrier['barrier_repairs']} times for 1 rejoin event — "
              "repairs must equal rejoins", file=sys.stderr)
        return 1
    if barrier["replica_variants"] != 1:
        print("[chaos_smoke] FAIL: replicas not bit-identical after the "
              "rejoin barrier", file=sys.stderr)
        return 1

    # ---- phase C: full world again, judge the floor ---------------------
    step_c = make_train_step(loss_fn, tx_c, mesh_c, donate=False,
                             consensus=consensus)
    at_c = batches(world)
    loss_c = float("nan")
    for i in range(steps_a + steps_b, steps_a + steps_b + steps_c):
        state_c, loss_c = step_c(state_c, at_c(i))
        monitor.update(i, guard_report(state_c))
        if reader is not None:
            reader.update(i, state_c)
    loss_c = float(loss_c)
    dt = time.perf_counter() - t0
    if reader is not None:
        reader.flush(state_c)
        reader.close()
    ckpt.close()

    fp_down = bool(resize_down["footprint_matches"])
    fp_up = validate_resharded(state_c, grc_c, params, world)["matches"]
    floor_met = np.isfinite(loss_c) and loss_c < args.floor
    print(f"[chaos_smoke] elastic: {steps_a}+{steps_b}+{steps_c} steps in "
          f"{dt:.1f}s | W {plan.old_world}->{plan.new_world}->{world} | "
          f"loss {first_loss:.4f} -> {loss_c:.4f} (floor {args.floor}) | "
          f"drain rank {drain_rank} @ step {drain_step}")

    if args.elastic_out:
        doc = {
            "tool": "chaos_smoke",
            "captured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "argv": " ".join(sys.argv[1:]),
            "world_cycle": [plan.old_world, plan.new_world, world],
            "hier": bool(args.hier),
            "slice_size": args.slice_size if args.hier else None,
            "drain": {"rank": drain_rank, "step": drain_step,
                      "episodes": controller.episodes.get(drain_rank, 0)},
            "resize_events": controller.events,
            "rejoin": {"rejoins": 1, **{
                k: int(barrier[k]) for k in
                ("barrier_repairs", "repairs", "audits", "replica_variants",
                 "last_divergent_rank", "fingerprint_bytes",
                 "repair_bytes")}},
            "floor": {"first_loss": first_loss, "final_loss": loss_c,
                      "floor": args.floor, "met": bool(floor_met)},
            "footprint": {str(plan.new_world): fp_down, str(world): fp_up},
        }
        _write_evidence_doc(doc, args.elastic_out, world=world,
                            slice_size=(args.slice_size if args.hier
                                        else None),
                            label="elastic evidence")

    if not np.isfinite(loss_c):
        print("[chaos_smoke] FAIL: final loss non-finite after the rejoin",
              file=sys.stderr)
        return 1
    if not floor_met:
        print(f"[chaos_smoke] FAIL: final loss {loss_c:.4f} misses the "
              f"convergence floor {args.floor}", file=sys.stderr)
        return 1
    if not (fp_down and fp_up):
        print("[chaos_smoke] FAIL: re-sharded state does not match the "
              "static footprint model", file=sys.stderr)
        return 1
    print("[chaos_smoke] OK")
    return 0


def _region_main(args) -> int:
    """The --region lifecycle: drift inside one region → region-wide watch
    signal → ONE drain → whole-region kill (R→R−1, topology collapses to
    two-tier) → W−rz resume → region rejoin at W behind the consensus
    barrier. Returns 0 only when every acceptance fact holds (see module
    docstring)."""
    import dataclasses
    import json
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from grace_tpu import grace_from_params
    from grace_tpu.checkpoint import Checkpointer
    from grace_tpu.core import Topology
    from grace_tpu.resilience import (ChaosCompressor, ConsensusConfig,
                                      ElasticController, guarded_chain,
                                      plan_resize, validate_resharded)
    from grace_tpu.telemetry import JSONLSink, TelemetryReader
    from grace_tpu.train import init_train_state, make_train_step
    from grace_tpu.utils.logging import GuardMonitor, run_provenance
    from grace_tpu.utils.metrics import guard_report
    from grace_tpu.models import lenet
    from grace_tpu.parallel import data_parallel_mesh

    devices = jax.devices()
    world = len(devices)
    rz = args.region_size
    if world % rz or rz < 2:
        print(f"[chaos_smoke] --region: world {world} is not a multiple of "
              f"region_size {rz} (>= 2 required)", file=sys.stderr)
        return 1
    if world // rz < 2:
        print(f"[chaos_smoke] --region: need >= 2 regions; world {world} "
              f"/ region_size {rz} leaves {world // rz}", file=sys.stderr)
        return 1
    # Slices half a region wide: every run exercises intra-slice ICI hops,
    # same-region cross-slice DCN gathers AND cross-region WAN gathers.
    s = max(1, rz // 2)
    topo3 = Topology(slice_size=s, region_size=rz)
    doomed_region = world // rz - 1              # the last region dies
    lost = tuple(range(doomed_region * rz, (doomed_region + 1) * rz))
    # One drifting rank per slice of the doomed region — enough for the
    # 0.5 region quorum, few enough (2 of 8) that the fleet median the
    # watch skew detector references stays healthy.
    drift_ranks = tuple(doomed_region * rz + k * s
                        for k in range(rz // s))
    plan = plan_resize(world, lost, topo3)

    steps_a = max(args.steps // 3, 2 * args.watch_window)
    steps_b = max(args.steps // 4, 4)
    steps_c = max(args.steps - steps_a - steps_b, 4)
    consensus = ConsensusConfig(audit_every=args.audit_every)

    def build(slice_size, region_size, drift=()):
        """(grace, guarded tx) for one phase; rebuilding the transform is
        the resize's single topology-invalidation point. slice/region
        sizes come from the surviving Topology — the whole-region kill
        hands back (slice_size, None) and the rejoin restores both."""
        p = {"compressor": "topk", "compress_ratio": 0.3,
             "memory": "residual", "communicator": "hier",
             "fusion": "flat", "escape": "fp16", "consensus": consensus,
             "telemetry": max(2 * args.telemetry_every, 16),
             "watch": {"window": args.watch_window,
                       "capacity": max(2 * args.telemetry_every
                                       // args.watch_window, 8)}}
        if slice_size:
            p["slice_size"] = slice_size
        if region_size:
            p["region_size"] = region_size
        grc = grace_from_params(p)
        for dr in drift:
            grc = dataclasses.replace(grc, compressor=ChaosCompressor(
                inner=grc.compressor, drift_scale=args.drift_scale,
                rank=dr, seed=args.seed + 3 + dr))
        tx = guarded_chain(grc, optax.sgd(args.lr),
                           fallback_after=args.fallback_after,
                           fallback_steps=args.fallback_steps)
        return grc, tx

    def batches(w):
        b = max(args.batch, w) // w * w
        rng = np.random.default_rng(args.seed)
        images = rng.normal(size=(4 * args.batch, 28, 28, 1)).astype(
            np.float32)
        labels = rng.integers(0, 10,
                              size=(4 * args.batch,)).astype(np.int32)

        def at(i):
            lo = (i * b) % (len(images) - b + 1)
            return (jnp.asarray(images[lo:lo + b]),
                    jnp.asarray(labels[lo:lo + b]))
        return at

    def loss_fn(params, b):
        x, y = b
        logits, _ = lenet.apply(params, {}, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    sink = None
    reader = None
    if args.telemetry_out:
        prov = run_provenance(
            data="synthetic", tool="chaos_smoke",
            argv=" ".join(sys.argv[1:]), steps=args.steps,
            region=True, region_size=rz, slice_size=s)
        sink = JSONLSink(args.telemetry_out, provenance=prov)
        reader = TelemetryReader(sink, every=args.telemetry_every,
                                 anomaly=True)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="grace_region_")
    ckpt = Checkpointer(ckpt_dir, max_to_keep=2)
    controller = ElasticController(
        consensus=consensus, checkpointer=ckpt, sink=sink,
        anomaly_threshold=1, topology=topo3, region_quorum=0.5,
        drain_timeout_s=args.drain_timeout or None, drain_retries=1)
    monitor = GuardMonitor(sink=sink)

    # ---- phase A: full world, one rank per slice of region R-1 drifting --
    mesh_a = data_parallel_mesh(devices)
    grc_a, tx_a = build(s, rz, drift=drift_ranks)
    params, _ = lenet.init(jax.random.key(args.seed))
    state = init_train_state(params, tx_a, mesh_a)
    step = make_train_step(loss_fn, tx_a, mesh_a, donate=False,
                           consensus=consensus)
    at = batches(world)
    t0 = time.perf_counter()
    first_loss = None
    drain_rank = None
    drain_step = None
    drain_scope = ()
    seen_anomalies = 0

    def try_drain(i, state):
        """Widen each flagged rank to its region scope; drain only when
        the episode is region-wide — the ONE-transition contract."""
        nonlocal drain_rank, drain_step, drain_scope
        for r in sorted(controller.episodes):
            scope = controller.region_scope(r)
            if len(scope) > 1:
                controller.drain(i, state, r, scope=scope)
                drain_rank, drain_step, drain_scope = r, i, scope
                return True
        return False

    for i in range(steps_a):
        state, loss = step(state, at(i))
        if first_loss is None:
            first_loss = float(loss)
        monitor.update(i, guard_report(state))
        if reader is not None and drain_rank is None:
            reader.update(i, state)
            anomalies = reader.monitor.anomalies
            # Feed one record at a time: observe() returns early at a
            # threshold crossing and would drop the rest of the batch.
            for a in anomalies[seen_anomalies:]:
                controller.observe(i, [a])
            seen_anomalies = len(anomalies)
            if try_drain(i, state):
                break
    if reader is not None and drain_rank is None:
        reader.flush(state)
        for a in reader.monitor.anomalies[seen_anomalies:]:
            controller.observe(steps_a - 1, [a])
        try_drain(steps_a - 1, state)
    if reader is not None and drain_rank is None:
        print(f"[chaos_smoke] FAIL: seeded drift on ranks "
              f"{list(drift_ranks)} produced no region-wide drain signal "
              f"in {steps_a} steps (episodes: {controller.episodes}) — "
              "the early-warning channel is broken", file=sys.stderr)
        return 1
    if reader is None:
        controller.episodes.update({r: 1 for r in drift_ranks})
        controller.drain(steps_a - 1, state, drift_ranks[0],
                         scope=controller.region_scope(drift_ranks[0]))
        drain_rank, drain_step = drift_ranks[0], steps_a - 1
        drain_scope = controller.region_scope(drift_ranks[0])
    if tuple(sorted(drain_scope)) != lost:
        print(f"[chaos_smoke] FAIL: drain scope {sorted(drain_scope)} is "
              f"not the doomed region {list(lost)}", file=sys.stderr)
        return 1
    drain_events = [e for e in controller.events
                    if e["event"] == "elastic_drain"]
    if len(drain_events) != 1:
        print(f"[chaos_smoke] FAIL: {len(drain_events)} drain transitions "
              "for ONE region-wide episode", file=sys.stderr)
        return 1
    guard_a = guard_report(state)
    if guard_a["notfinite_count"] != 0:
        print("[chaos_smoke] FAIL: guard tripped during the drift phase — "
              "the region faults are supposed to be guard-invisible",
              file=sys.stderr)
        return 1

    # ---- kill the whole region, resize to the survivor world ------------
    if not plan.whole_regions or plan.topology.region_size is not None:
        print(f"[chaos_smoke] FAIL: plan {plan} did not recognize the "
              "whole-region loss / single-region collapse", file=sys.stderr)
        return 1
    survivors = [devices[r] for r in plan.survivors]
    mesh_b = data_parallel_mesh(survivors)
    grc_b, tx_b = build(plan.topology.slice_size,
                        plan.topology.region_size)
    state_b, resize_down = controller.resize(
        drain_step, state, tx_b, mesh_a, mesh_b, plan,
        grace=grc_b, params=params)
    print(f"[chaos_smoke] resize: W{plan.old_world} -> W{plan.new_world} "
          f"(lost region {doomed_region}: ranks {list(plan.lost_ranks)}; "
          f"topology -> slice_size {plan.topology.slice_size}, "
          f"region_size {plan.topology.region_size}; whole_regions "
          f"{plan.whole_regions}; footprint_matches "
          f"{resize_down['footprint_matches']})")

    # ---- phase B: the surviving region keeps training --------------------
    step_b = make_train_step(loss_fn, tx_b, mesh_b, donate=False,
                             consensus=consensus)
    at_b = batches(plan.new_world)
    loss_b = float("nan")
    for i in range(steps_a, steps_a + steps_b):
        state_b, loss_b = step_b(state_b, at_b(i))
        if reader is not None:
            reader.update(i, state_b)
    if not np.isfinite(float(loss_b)):
        print("[chaos_smoke] FAIL: loss went non-finite at the survivor "
              f"world W{plan.new_world}", file=sys.stderr)
        return 1

    # ---- region rejoin at full world behind the consensus barrier --------
    mesh_c = data_parallel_mesh(devices)
    grc_c, tx_c = build(s, rz)
    grow = plan_resize(world, (), topo3)   # no losses: fresh 3-tier plan
    state_c, _ = controller.resize(
        steps_a + steps_b, state_b, tx_c, mesh_b, mesh_c,
        dataclasses.replace(grow, old_world=plan.new_world),
        grace=grc_c, params=params)
    from grace_tpu.resilience import implant_stale_replica
    stale = ckpt.restore_last_good(state_c)
    for r in plan.lost_ranks:
        state_c = implant_stale_replica(state_c, r, stale.params)
    state_c, barrier = controller.rejoin(steps_a + steps_b, state_c,
                                         mesh_c)
    print(f"[chaos_smoke] rejoin: barrier_repairs "
          f"{barrier['barrier_repairs']} | replica_variants "
          f"{barrier['replica_variants']} | fingerprint "
          f"{barrier['fingerprint_bytes']} B")
    # ONE region rejoin == ONE barrier repair event (the forced audit's
    # masked broadcast repairs every stale replica of the region at once
    # — region-granular, exactly like the drain).
    if barrier["barrier_repairs"] != 1:
        print(f"[chaos_smoke] FAIL: rejoin barrier repaired "
              f"{barrier['barrier_repairs']} times for 1 region rejoin — "
              "repairs must equal rejoins", file=sys.stderr)
        return 1
    if barrier["replica_variants"] != 1:
        print("[chaos_smoke] FAIL: replicas not bit-identical after the "
              "rejoin barrier", file=sys.stderr)
        return 1

    # ---- phase C: full three-tier world again, judge the floor -----------
    step_c = make_train_step(loss_fn, tx_c, mesh_c, donate=False,
                             consensus=consensus)
    at_c = batches(world)
    loss_c = float("nan")
    for i in range(steps_a + steps_b, steps_a + steps_b + steps_c):
        state_c, loss_c = step_c(state_c, at_c(i))
        monitor.update(i, guard_report(state_c))
        if reader is not None:
            reader.update(i, state_c)
    loss_c = float(loss_c)
    dt = time.perf_counter() - t0
    if reader is not None:
        reader.flush(state_c)
        reader.close()
    ckpt.close()

    fp_down = bool(resize_down["footprint_matches"])
    fp_up = validate_resharded(state_c, grc_c, params, world)["matches"]
    floor_met = np.isfinite(loss_c) and loss_c < args.floor
    timeouts = sum(e.get("drain_timeouts", 0) for e in drain_events)
    print(f"[chaos_smoke] region: {steps_a}+{steps_b}+{steps_c} steps in "
          f"{dt:.1f}s | W {plan.old_world}->{plan.new_world}->{world} | "
          f"loss {first_loss:.4f} -> {loss_c:.4f} (floor {args.floor}) | "
          f"drain scope {list(drain_scope)} @ step {drain_step}")

    if args.region_out:
        doc = {
            "tool": "chaos_smoke",
            "captured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "argv": " ".join(sys.argv[1:]),
            "world_cycle": [plan.old_world, plan.new_world, world],
            "slice_size": s,
            "region_size": rz,
            "regions": world // rz,
            "drift_ranks": list(drift_ranks),
            "drain": {"rank": drain_rank, "step": drain_step,
                      "scope": list(drain_scope),
                      "region_wide": len(drain_scope) == rz,
                      "transitions": len(drain_events),
                      "drain_timeouts": timeouts,
                      "episodes": dict(sorted(
                          (str(k), v)
                          for k, v in controller.episodes.items()))},
            "resize_events": controller.events,
            "rejoin": {"rejoins": 1, "rejoined_ranks": len(lost), **{
                k: int(barrier[k]) for k in
                ("barrier_repairs", "repairs", "audits",
                 "replica_variants", "last_divergent_rank",
                 "fingerprint_bytes", "repair_bytes")}},
            "floor": {"first_loss": first_loss, "final_loss": loss_c,
                      "floor": args.floor, "met": bool(floor_met)},
            "footprint": {str(plan.new_world): fp_down,
                          str(world): fp_up},
            "guard_silent": guard_a["notfinite_count"] == 0,
        }
        _write_evidence_doc(doc, args.region_out, world=world,
                            slice_size=s, region_size=rz,
                            label="region evidence")

    if not np.isfinite(loss_c):
        print("[chaos_smoke] FAIL: final loss non-finite after the rejoin",
              file=sys.stderr)
        return 1
    if not floor_met:
        print(f"[chaos_smoke] FAIL: final loss {loss_c:.4f} misses the "
              f"convergence floor {args.floor}", file=sys.stderr)
        return 1
    if not (fp_down and fp_up):
        print("[chaos_smoke] FAIL: re-sharded state does not match the "
              "static footprint model", file=sys.stderr)
        return 1
    print("[chaos_smoke] OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
