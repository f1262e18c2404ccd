"""graft-elastic: preemption-tolerant elastic training.

Production training runs on spot/preemptible capacity: ranks — and on
multislice pods, whole ICI slices — come and go mid-run. Every resilience
layer so far (guard, consensus repair, graft-watch early warning) assumes
the world size W is fixed for the life of the run. This module makes W a
*resizable* property, built from the pieces the stack already proved:

* **Early warning → drain** (:class:`ElasticController`): graft-watch's
  ``watch_anomaly`` records flag a degrading rank *before* it dies (PR-8's
  measured lead over guard/consensus). The controller treats repeated skew
  episodes on one rank as the pre-death signal and triggers a
  last-known-good :class:`~grace_tpu.checkpoint.Checkpointer` save while
  every rank is still alive to participate — the drain.

* **World resize → re-shard** (:func:`reshard_grace_state`): GraceState is
  two different kinds of data. The replicated fields (count, rng_key,
  fallback, audit — plus params, downstream optimizer state, and guard
  counters) are world-independent facts that carry forward **bit-exactly**
  (:func:`grace_tpu.transform.carry_replicated`). The per-rank fields
  (mem error-feedback residuals, comp compressor state, telemetry/watch
  rings) are sharded one-row-per-rank and are **re-initialized at the new
  world, never re-partitioned**: a departed rank's residual describes
  compression error *that rank's* shard stream accumulated — no surviving
  rank can inherit it without double-counting feedback, and a rejoining
  rank's residual is stale by exactly the steps it missed. Zero-and-
  re-accumulate is safe by the error-feedback contract — the PR-3 repair
  rationale, applied to the whole fleet (see IMPLEMENTING.md, "Why
  re-shard re-initializes residuals"). Compressor state is re-built by
  ``init_state`` (zeros are NOT a valid PowerSGD Q — same PR-3 argument),
  and the rings are re-allocated with their wraparound counters reset.
  The re-init is validated statically for free against flow pass 7's
  ``footprint_model`` at the new world (:func:`validate_resharded`).

* **Slice- and region-granular shrink**: under the hierarchical
  ICI×DCN[×WAN] communicator, losing a whole slice is a K→K−1 DCN-level
  resize that never touches intra-slice state, and losing a whole region
  is an R→R−1 WAN-level resize that never touches intra-region state —
  :meth:`grace_tpu.core.Topology.shrink` keeps ``slice_size`` for
  whole-slice losses, keeps both tiers for whole-region losses (dropping
  the WAN tier when a single region remains), and collapses to flat for
  partial ones; :meth:`grace_tpu.comm.HierarchicalAllreduce.shrunk`
  rebuilds the communicator to match (the WAN codec is dropped with its
  tier). A region-wide failure domain — one metro's power event taking S·K
  ranks at once — is ONE drain → resize → rejoin transition, not S·K
  independent rank losses: :meth:`ElasticController.region_scope` widens
  the drain to the whole region once a quorum of its ranks carries skew
  episodes.

* **Rejoin barrier** (:func:`rejoin_barrier`): a rank rejoining at W was
  restored from a checkpoint the fleet has since trained past — its
  replicated state is *legitimately* stale, which is exactly the fault
  class the PR-3 consensus auditor repairs. The barrier forces one ungated
  audit (:func:`grace_tpu.resilience.consensus.force_audit`) at admission:
  the rejoiner must fingerprint-match the reference replica or receive the
  bit-exact masked-broadcast repair (residuals zeroed) *before its
  gradients count*. Repairs == rejoins and bit-identical replicas after
  the barrier are the acceptance facts ``chaos_smoke --elastic`` asserts.

The wire cost of the barrier is priced like the scheduled audit's
(fingerprint exchange + any repair broadcast) and stamped into the
``elastic_rejoin`` event record, so resize events carry honest byte
accounting into the same JSONL stream as telemetry/guard/consensus events
(timeline kind ``elastic``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from grace_tpu.core import DEFAULT_AXIS, Topology
from grace_tpu.parallel import (local_world_size, replicated, shard_map)
from grace_tpu.resilience.consensus import (_tree_nbytes, audit_report,
                                            force_audit, normalize_consensus,
                                            replicated_view)
from grace_tpu.transform import (GraceState, add_world_axis,
                                 carry_replicated, partition_specs,
                                 strip_world_axis)

__all__ = ["ResizePlan", "plan_resize", "reshard_grace_state",
           "validate_resharded", "rejoin_barrier", "implant_stale_replica",
           "replica_variants", "ElasticController"]


def _is_grace(x) -> bool:
    return isinstance(x, GraceState)


def _reinit_adapt(carried_tree, fresh_tree):
    """Swap the carried graft-adapt policy state for the fresh init's —
    the one replicated GraceState field a world resize deliberately does
    NOT carry (see :func:`reshard_grace_state`)."""

    def graft(carried, fresh):
        if _is_grace(carried):
            return carried._replace(adapt=fresh.adapt)
        return carried

    return jax.tree_util.tree_map(graft, carried_tree, fresh_tree,
                                  is_leaf=_is_grace)


def _grace_world(tree) -> Optional[int]:
    """Leading world-axis extent of the first per-rank GraceState leaf in
    ``tree`` (global layout), or None when no sized per-rank leaf exists."""
    worlds: List[int] = []

    def visit(node):
        if _is_grace(node):
            for leaf in jax.tree_util.tree_leaves(
                    (node.mem, node.comp, node.telem, node.watch)):
                if hasattr(leaf, "shape") and len(leaf.shape) >= 1:
                    worlds.append(int(leaf.shape[0]))
        return node

    jax.tree_util.tree_map(visit, tree, is_leaf=_is_grace)
    return worlds[0] if worlds else None


# ---------------------------------------------------------------------------
# resize planning
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ResizePlan:
    """One world resize, decided before any state is touched.

    ``survivors`` are old-world rank indices in ascending order — the new
    world's rank k is old rank ``survivors[k]`` (contiguous renumbering,
    the layout :meth:`Topology.shrink` prices; for whole-region losses the
    renumbering is region-granular — every surviving region carries its
    ranks across intact). ``topology`` is the surviving link layout:
    whole-slice losses keep ``slice_size`` (K→K−1), whole-region losses
    keep both tiers (R→R−1; the WAN tier is dropped when one region
    remains), partial losses collapse to flat.
    """

    old_world: int
    new_world: int
    lost_ranks: Tuple[int, ...]
    survivors: Tuple[int, ...]
    topology: Topology
    whole_slices: bool
    whole_regions: bool = False


def plan_resize(world: int, lost_ranks,
                topology: Optional[Topology] = None) -> ResizePlan:
    """Plan the W→W′ resize that removes ``lost_ranks``.

    Pure decision logic — validates the loss against the link layout
    (:meth:`Topology.shrink`) and fixes the survivor renumbering; no
    device state is touched until :func:`reshard_grace_state` executes
    the plan.
    """
    topo = topology if topology is not None else Topology()
    lost = tuple(sorted(set(int(r) for r in lost_ranks)))
    new_topo, new_world = topo.shrink(world, lost)
    lost_set = set(lost)
    survivors = tuple(r for r in range(world) if r not in lost_set)
    whole = (topo.slice_size is not None
             and new_topo.slice_size == topo.slice_size)
    whole_regions = False
    if lost and topo.region_size is not None and world % topo.region_size == 0:
        rz = topo.region_size
        touched = sorted({r // rz for r in lost})
        whole_regions = all(rho * rz + i in lost_set
                            for rho in touched for i in range(rz))
    return ResizePlan(old_world=world, new_world=new_world,
                      lost_ranks=lost, survivors=survivors,
                      topology=new_topo, whole_slices=whole,
                      whole_regions=whole_regions)


# ---------------------------------------------------------------------------
# the re-shard
# ---------------------------------------------------------------------------

def reshard_grace_state(state, optimizer, old_mesh, new_mesh,
                        axis_name: str = DEFAULT_AXIS):
    """Re-shard a global train state from ``old_mesh``'s world to
    ``new_mesh``'s.

    ``state`` is a :class:`~grace_tpu.train.TrainState` /
    :class:`~grace_tpu.train.StatefulTrainState` (or any NamedTuple with
    ``params`` [, ``model_state``] and ``opt_state``) in the global layout
    ``init_train_state`` builds. ``optimizer`` is the optax chain for the
    NEW world — rebuild the grace transform for the post-resize topology
    first (that rebuild is also the wire model's single invalidation
    point; see ``grace_transform(topology=...)``).

    Replicated data — params, model state, non-grace optimizer state,
    guard counters, and the replicated GraceState fields — carries forward
    **bit-exactly** onto the new mesh. Per-rank GraceState data (mem /
    comp / telem / watch) is **re-initialized at the new world** by the
    new transform's own ``init`` (residuals zeroed, compressor state
    freshly built — zeros are not a valid PowerSGD Q —, rings re-allocated
    with step counters reset), never re-partitioned. Validate the result
    against the static footprint model with :func:`validate_resharded`.
    """
    from grace_tpu.train import init_opt_state

    old_world = local_world_size(old_mesh, axis_name)
    new_world = local_world_size(new_mesh, axis_name)
    state_world = _grace_world(state.opt_state)
    if state_world is not None and state_world != old_world:
        raise ValueError(
            f"reshard_grace_state: the state's per-rank GraceState leaves "
            f"carry world axis {state_world} but old_mesh has "
            f"{old_world} ranks on '{axis_name}' — pass the mesh the state "
            "was built on (states built without init_train_state lack the "
            "global world axis entirely).")

    def put(x):
        return jax.device_put(np.asarray(x), replicated(new_mesh))

    params = jax.tree_util.tree_map(put, jax.device_get(state.params))
    fresh_opt = init_opt_state(params, optimizer, new_mesh, axis_name)
    # Only the replicated payload of the old state crosses the resize —
    # strip the per-rank fields BEFORE the host transfer so a large
    # residual set at old W is never fetched just to be discarded.
    old_light = jax.device_get(replicated_view(state.opt_state))
    new_opt = carry_replicated(old_light, fresh_opt, convert=put)
    # graft-adapt policy state is replicated, so carry_replicated grafted
    # the OLD controller across — but its windowed signal statistics and
    # operating rung were learned at the old world's error profile (a
    # W-rank mean/peak is not a W'-rank mean/peak), so the resize
    # re-initializes it from the NEW transform's init: the ladder
    # restarts at its configured start rung, robustness-first, exactly
    # like the re-zeroed residuals.
    new_opt = _reinit_adapt(new_opt, fresh_opt)
    fields: Dict[str, Any] = {"params": params, "opt_state": new_opt}
    if hasattr(state, "model_state"):
        fields["model_state"] = jax.tree_util.tree_map(
            put, jax.device_get(state.model_state))
    return type(state)(**fields)


def validate_resharded(state, grace_or_tx, params, world: int) -> dict:
    """Check a (re-)sharded state against flow pass 7's ``footprint_model``
    at ``world`` — the same static model graft-lint's ``memory_footprint``
    pass audits configs with and the profiling recorder's live check uses,
    so re-init correctness is checked by machinery that exists anyway.

    Raises ``ValueError`` naming the first component (mem/comp/telem)
    whose live bytes disagree with the model — the signature of a state
    initialized at the wrong world or under a different codec/fusion
    config. Returns ``{"live", "model", "matches": True}`` on success.
    """
    from grace_tpu.analysis.flow import footprint_model
    from grace_tpu.profiling import grace_state_footprint

    live = grace_state_footprint(state)
    model = footprint_model(grace_or_tx, params, world=world)
    bad = {k: (live[k], model[k])
           for k in ("mem_bytes", "comp_bytes", "telem_bytes")
           if live[k] != model[k]}
    if bad:
        detail = ", ".join(f"{k}: live {lv} != model {mv}"
                           for k, (lv, mv) in sorted(bad.items()))
        raise ValueError(
            f"re-sharded GraceState does not match the static footprint "
            f"model at world {world} ({detail}) — the state was "
            "re-initialized at a different world or under a different "
            "codec/fusion/telemetry config than the one being validated.")
    return {"live": live, "model": model, "matches": True}


# ---------------------------------------------------------------------------
# the rejoin barrier
# ---------------------------------------------------------------------------

def barrier_wire_bytes(state, consensus, world: int) -> Dict[str, int]:
    """Static wire price of one rejoin barrier at ``world``: the
    fingerprint exchange every rank pays, and the repair broadcast paid
    only when a rejoiner diverges — the same two terms the scheduled
    audit folds into ``audit_bytes``, surfaced here so resize events
    carry honest byte accounting."""
    config = normalize_consensus(consensus)
    fp = int(world) * 2 * config.segments * 4
    if hasattr(state, "opt_state"):
        tree = ((state.params, state.model_state, state.opt_state)
                if hasattr(state, "model_state")
                else (state.params, state.opt_state))
    else:
        tree = state
    return {"fingerprint_bytes": fp,
            "repair_bytes": _tree_nbytes(replicated_view(tree))}


def rejoin_barrier(state, consensus, mesh,
                   axis_name: str = DEFAULT_AXIS, check: bool = True):
    """Admission gate for a world grown back to W: force one consensus
    audit over ``state`` on ``mesh``, repairing any rank whose replicated
    state (typically the rejoiner's, restored from a pre-departure
    checkpoint) diverges from the reference replica. Returns
    ``(state, report)`` where ``report`` is the post-barrier
    :func:`~grace_tpu.resilience.consensus.audit_report` extended with
    ``replica_variants`` (max distinct byte patterns over params replicas
    — 1 == bit-identical) and the barrier's wire pricing.

    ``check=True`` raises if replicas are still not bit-identical after
    the repair — a rejoiner the masked broadcast could not reconcile must
    not be admitted to the next collective.
    """
    from jax.sharding import PartitionSpec as P

    config = normalize_consensus(consensus)
    if config is None:
        raise ValueError("rejoin_barrier requires an armed consensus "
                         "config — the fingerprint audit IS the gate.")
    has_model = hasattr(state, "model_state")

    def rejoin_audit(st):
        opt = strip_world_axis(st.opt_state)
        if has_model:
            params, mstate, opt = force_audit(
                (st.params, st.model_state, opt), config, axis_name)
            return type(st)(params, mstate, add_world_axis(opt))
        params, opt = force_audit((st.params, opt), config, axis_name)
        return type(st)(params, add_world_axis(opt))

    specs = partition_specs(state, axis_name)
    fn = jax.jit(shard_map(rejoin_audit, mesh=mesh, in_specs=(specs,),
                           out_specs=specs, check_vma=False))
    pre_repairs = audit_report(state).get("repairs", 0)
    new_state = fn(state)
    report = dict(audit_report(new_state))
    # The barrier's own repair count — audit_report is cumulative over the
    # run, and a fleet that already self-healed earlier must not make a
    # clean rejoin look repaired (repairs == rejoins is the acceptance
    # identity chaos_smoke asserts on exactly this field).
    report["barrier_repairs"] = report.get("repairs", 0) - pre_repairs
    report["replica_variants"] = replica_variants(new_state.params)
    report.update(barrier_wire_bytes(
        new_state, config, local_world_size(mesh, axis_name)))
    if check and report["replica_variants"] > 1:
        raise RuntimeError(
            "rejoin barrier failed: params replicas still hold "
            f"{report['replica_variants']} distinct byte patterns after "
            "the forced audit — the rejoining rank must not be admitted. "
            f"(report: {report})")
    return new_state, report


def replica_variants(tree) -> int:
    """Max over leaves of the number of distinct per-device byte patterns
    — 1 means every replica is bit-identical (the post-barrier
    invariant). Only counts leaves that expose addressable shards."""
    worst = 1
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if not shards:
            continue
        worst = max(worst, len({np.asarray(s.data).tobytes()
                                for s in shards}))
    return worst


def implant_stale_replica(state, rank: int, stale_params):
    """Overwrite device ``rank``'s replica of every params leaf with the
    values from ``stale_params`` — the rejoin simulation primitive (the
    ChaosParams mechanics, aimed at staleness instead of bitflips): in a
    real elastic run the rejoining process restores yesterday's checkpoint
    and joins the collective; in a single-process simulation this builds
    exactly that divergence, which :func:`rejoin_barrier` must repair."""
    leaves, treedef = jax.tree_util.tree_flatten(state.params)
    stale_leaves = jax.tree_util.tree_leaves(stale_params)
    if len(leaves) != len(stale_leaves):
        raise ValueError(
            f"stale params have {len(stale_leaves)} leaves but the live "
            f"state has {len(leaves)} — restore the stale checkpoint into "
            "the same params structure first.")
    out = []
    for live, stale in zip(leaves, stale_leaves):
        shards = list(live.addressable_shards)
        if rank >= len(shards):
            raise ValueError(
                f"implant_stale_replica(rank={rank}) but the leaf has only "
                f"{len(shards)} addressable shards — params must be "
                "replicated with one shard per device.")
        stale_np = np.asarray(jax.device_get(stale))
        bufs = []
        for si, s in enumerate(shards):
            data = stale_np if si == rank else np.array(s.data)
            bufs.append(jax.device_put(data, s.device))
        out.append(jax.make_array_from_single_device_arrays(
            live.shape, live.sharding, bufs))
    return state._replace(params=jax.tree_util.tree_unflatten(treedef, out))


# ---------------------------------------------------------------------------
# the host-loop controller
# ---------------------------------------------------------------------------

class ElasticController:
    """Host-side orchestrator of the drain → resize → rejoin lifecycle.

    Wires the existing layers together without owning any of them: feed it
    the ``watch_anomaly`` records the
    :class:`~grace_tpu.telemetry.anomaly.WatchMonitor` emits
    (:meth:`observe`), and it elects a drain candidate once one rank
    accumulates ``anomaly_threshold`` skew episodes — the pre-death signal
    a degrading-but-alive rank gives before guard or consensus ever react.
    :meth:`drain` saves the last-known-good checkpoint while the fleet is
    whole; :meth:`resize` executes a :class:`ResizePlan` via
    :func:`reshard_grace_state` + :func:`validate_resharded`; and
    :meth:`rejoin` runs the consensus-gated admission barrier. Every
    transition is appended to :attr:`events` and — when ``sink`` is set —
    emitted as an ``elastic_drain`` / ``elastic_resize`` /
    ``elastic_rejoin`` record into the same JSONL stream as telemetry,
    guard, and consensus events (timeline kind ``elastic``).

    When the controller knows the fleet's link layout (``topology`` with a
    ``region_size``), a region-wide skew episode — a metro-level network
    or power event degrading every rank behind one WAN boundary at once —
    is recognized by :meth:`region_scope` and handled as ONE drain →
    resize → rejoin transition over the whole region, not ``region_size``
    independent rank losses (every rank in the scope is marked drained,
    so later threshold crossings inside the same region are absorbed).

    The drain's checkpoint save runs under a bounded watchdog when
    ``drain_timeout_s`` is set: a stalled checkpoint backend must not
    wedge the drain while the flagged rank keeps degrading, so each stall
    emits an ``elastic_drain_timeout`` record, retries with doubled
    timeout up to ``drain_retries`` extra attempts, and finally proceeds
    with the last known good checkpoint already on disk.
    """

    def __init__(self, *, consensus=None, checkpointer=None, sink=None,
                 anomaly_threshold: int = 2,
                 anomaly_metrics=("compression_error", "residual_norm"),
                 topology: Optional[Topology] = None,
                 region_quorum: float = 0.5,
                 drain_timeout_s: Optional[float] = None,
                 drain_retries: int = 1,
                 axis_name: str = DEFAULT_AXIS):
        self.consensus = normalize_consensus(consensus) \
            if consensus not in (None, False) else None
        self.checkpointer = checkpointer
        self.sink = sink
        self.anomaly_threshold = int(anomaly_threshold)
        # Only codec-health skews count toward the drain signal by default:
        # grad_norm skews are real data heterogeneity on fixed shards (the
        # chaos_smoke --watch misattribution rationale), not a dying rank.
        self.anomaly_metrics = tuple(anomaly_metrics)
        self.topology = topology
        if not (0.0 < float(region_quorum) <= 1.0):
            raise ValueError(f"region_quorum must be in (0, 1]; "
                             f"got {region_quorum}")
        self.region_quorum = float(region_quorum)
        if drain_timeout_s is not None and float(drain_timeout_s) <= 0:
            raise ValueError(f"drain_timeout_s must be positive; "
                             f"got {drain_timeout_s}")
        self.drain_timeout_s = (float(drain_timeout_s)
                                if drain_timeout_s is not None else None)
        if int(drain_retries) < 0:
            raise ValueError(f"drain_retries must be >= 0; "
                             f"got {drain_retries}")
        self.drain_retries = int(drain_retries)
        self.axis_name = axis_name
        self.events: List[dict] = []
        self.episodes: Dict[int, int] = {}
        self.drained_ranks: set = set()

    def _emit(self, event: str, step: int, **payload) -> dict:
        rec = {"event": event, "step": int(step), **payload}
        self.events.append(rec)
        if self.sink is not None:
            self.sink.write(rec)
        return rec

    # -- early warning ------------------------------------------------------
    def observe(self, step: int, anomalies) -> Optional[int]:
        """Feed new ``watch_anomaly`` dicts; returns the rank to drain the
        first time one rank's skew-episode count crosses the threshold
        (None otherwise — call :meth:`drain` with the returned rank)."""
        for a in anomalies or ():
            if a.get("kind") != "skew":
                continue
            if (self.anomaly_metrics
                    and a.get("metric") not in self.anomaly_metrics):
                continue
            rank = a.get("rank")
            if rank is None or int(rank) < 0:
                continue
            rank = int(rank)
            self.episodes[rank] = self.episodes.get(rank, 0) + 1
            if (self.episodes[rank] >= self.anomaly_threshold
                    and rank not in self.drained_ranks):
                self.drained_ranks.add(rank)
                return rank
        return None

    def region_scope(self, rank: int) -> Tuple[int, ...]:
        """The drain scope the flagged rank implies: the whole region's
        rank tuple when the controller knows a region layout and at least
        ``region_quorum`` of the region's ranks carry skew episodes (ONE
        failing domain — drain once, resize R→R−1), else ``(rank,)``."""
        rank = int(rank)
        topo = self.topology
        if topo is None or getattr(topo, "region_size", None) is None:
            return (rank,)
        rz = int(topo.region_size)
        rho = rank // rz
        members = tuple(range(rho * rz, (rho + 1) * rz))
        hot = sum(1 for m in members if self.episodes.get(m, 0) > 0)
        need = max(1, int(np.ceil(self.region_quorum * rz)))
        return members if hot >= need else (rank,)

    # -- lifecycle ----------------------------------------------------------
    def _drain_checkpoint(self, step: int, state) -> Tuple[bool, int]:
        """Save+wait the last-known-good checkpoint under a watchdog.

        Returns ``(checkpointed, timeouts)``. With ``drain_timeout_s``
        unset the save blocks indefinitely (the pre-region behavior).
        With it set, each attempt gets a bounded window; a stall emits an
        ``elastic_drain_timeout`` record and retries with doubled timeout
        (backoff) up to ``drain_retries`` extra attempts before giving up
        and proceeding with the last known good checkpoint on disk. The
        stalled attempt's thread is a daemon — a wedged backend is left
        behind, never joined on the drain path.
        """
        def attempt():
            self.checkpointer.save(step, state, force=True, good=True)
            self.checkpointer.wait()

        if self.drain_timeout_s is None:
            attempt()
            return True, 0

        import threading
        timeout = self.drain_timeout_s
        timeouts = 0
        for trial in range(self.drain_retries + 1):
            done = threading.Event()
            errs: List[BaseException] = []

            def run():
                try:
                    attempt()
                except BaseException as e:   # noqa: BLE001 — re-raised below
                    errs.append(e)
                finally:
                    done.set()

            threading.Thread(target=run, daemon=True).start()
            if done.wait(timeout):
                if errs:
                    raise errs[0]
                return True, timeouts
            timeouts += 1
            last_good = None
            if hasattr(self.checkpointer, "last_good_step"):
                try:
                    last_good = self.checkpointer.last_good_step()
                except Exception:
                    last_good = None
            self._emit("elastic_drain_timeout", step, attempt=trial + 1,
                       timeout_s=float(timeout),
                       retries_left=self.drain_retries - trial,
                       last_good_step=last_good)
            timeout *= 2.0
        return False, timeouts

    def drain(self, step: int, state, rank: int, scope=None) -> dict:
        """Pre-death drain: save the last-known-good checkpoint while the
        flagged scope is still participating, so the resize restores from
        a state every healthy rank agreed on. ``scope`` widens the drain
        beyond the flagged rank (pass :meth:`region_scope`'s result for
        region-wide episodes); every rank in it is marked drained so the
        same failing domain never triggers a second transition."""
        scope = (tuple(int(r) for r in scope)
                 if scope is not None else (int(rank),))
        self.drained_ranks.update(scope)
        checkpointed, timeouts = (self._drain_checkpoint(step, state)
                                  if self.checkpointer is not None
                                  else (False, 0))
        return self._emit("elastic_drain", step, rank=int(rank),
                          scope=list(scope),
                          episodes=self.episodes.get(int(rank), 0),
                          checkpointed=checkpointed,
                          drain_timeouts=timeouts)

    def resize(self, step: int, state, optimizer, old_mesh, new_mesh,
               plan: ResizePlan, grace=None, params=None) -> Tuple[Any,
                                                                   dict]:
        """Execute a resize plan: re-shard onto ``new_mesh`` and (when
        ``grace`` and ``params`` are given) validate the re-init against
        the static footprint model at the new world."""
        new_state = reshard_grace_state(state, optimizer, old_mesh,
                                        new_mesh, self.axis_name)
        footprint_ok = None
        if grace is not None and params is not None:
            footprint_ok = validate_resharded(
                new_state, grace, params, plan.new_world)["matches"]
        event = self._emit(
            "elastic_resize", step,
            old_world=plan.old_world, new_world=plan.new_world,
            lost_ranks=list(plan.lost_ranks),
            slice_size=plan.topology.slice_size,
            region_size=plan.topology.region_size,
            whole_slices=plan.whole_slices,
            whole_regions=plan.whole_regions,
            footprint_matches=footprint_ok)
        return new_state, event

    def rejoin(self, step: int, state, mesh) -> Tuple[Any, dict]:
        """Run the consensus-gated rejoin barrier over the grown world."""
        if self.consensus is None:
            raise ValueError("ElasticController.rejoin needs an armed "
                             "consensus config (the fingerprint audit IS "
                             "the admission gate).")
        new_state, report = rejoin_barrier(state, self.consensus, mesh,
                                           self.axis_name)
        self._emit("elastic_rejoin", step, **{
            k: report[k] for k in ("repairs", "barrier_repairs", "audits",
                                   "last_divergent_rank",
                                   "replica_variants",
                                   "fingerprint_bytes", "repair_bytes")})
        return new_state, report
