"""The four composable jaxpr audit passes.

Every pass takes a :class:`~grace_tpu.analysis.trace.TracedGraph` and
returns a list of :class:`Finding`. Shared machinery:

* **recursive equation walk** — collectives hide inside ``cond`` branches,
  ``while`` bodies, ``pjit``/``custom_*_call`` sub-jaxprs and (post-vmap)
  batched shapes; every pass sees the whole nest;
* **replication analysis** — a forward dataflow pass over the body jaxpr:
  a value is *rank-varying* when it descends from a rank-varying input
  (sharded batch, per-rank residuals — seeded by the tracer from
  ``partition_specs``) or from ``axis_index``, and becomes *replicated*
  again when it passes through a full-axis ``psum``/``all_gather`` (every
  rank computes the identical reduction). ``ppermute``/``all_to_all``
  outputs are rank-varying by construction. This is what lets the
  collective-consistency pass bless the dense-escape cond (its predicate
  is the replicated fallback flag) while condemning a cond whose predicate
  descends from local data;
* **stage attribution** — each equation's ``source_info.name_stack``
  carries the ``grace/...`` scope names from
  :mod:`grace_tpu.telemetry.scopes`, so findings name the pipeline stage
  (``grace/exchange``, ``grace/consensus``, ...) they sit in.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from grace_tpu.analysis.trace import TracedGraph

__all__ = ["Finding", "PASS_NAMES", "run_passes",
           "pass_collective_consistency", "pass_bit_exactness",
           "pass_wire_reconciliation", "pass_signature_stability",
           "collective_signature", "count_recv_bytes",
           "count_recv_link_bytes"]

# Cross-replica primitives, by behavior class. `pbroadcast` is check_rep
# bookkeeping (identity on every rank), not a wire collective.
_REDUCTIONS = frozenset({"psum", "psum2", "pmax", "pmin", "pmean"})
_GATHERS = frozenset({"all_gather", "all_gather_invariant"})
_PERMUTES = frozenset({"ppermute", "pshuffle"})
_ALLTOALL = frozenset({"all_to_all"})
_SCATTER = frozenset({"reduce_scatter"})
COLLECTIVE_PRIMS = _REDUCTIONS | _GATHERS | _PERMUTES | _ALLTOALL | _SCATTER

# `debug_print` is what jax.debug.print lowers to on the installed JAX
# (it was a `debug_callback` before).
_CALLBACK_PRIMS = frozenset({
    "io_callback", "debug_callback", "debug_print", "pure_callback",
    "callback", "outside_call", "host_callback_call"})

# Passes 5-7 (graft-flow, ISSUE 9) live in analysis/flow.py on the
# dependence-graph layer and passes 8-10 (graft-sound, ISSUE 20) in
# analysis/state_passes.py on the stateful-semantics layer; both sets are
# resolved lazily by run_passes — the names are plain strings here so
# config registration and CLI selection never import those modules (which
# import this module) at module-load time.
PASS_NAMES = ("collective_consistency", "bit_exactness",
              "wire_reconciliation", "signature_stability",
              "overlap_schedulability", "numeric_safety",
              "memory_footprint", "rng_lineage", "rollback_coverage",
              "replication_contract")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One lint finding. ``severity`` is ``'error'`` (CI-failing) or
    ``'warning'``; ``stage`` is the ``grace/...`` trace-scope the offending
    equation sits in (empty when unattributable)."""

    pass_name: str
    config: str
    severity: str
    message: str
    stage: str = ""
    details: Tuple[Tuple[str, Any], ...] = ()

    def as_dict(self) -> dict:
        return {"pass": self.pass_name, "config": self.config,
                "severity": self.severity, "message": self.message,
                "stage": self.stage, **dict(self.details)}


def _stage_of(eqn) -> str:
    """The canonical stage the equation was traced under — the shared
    longest-prefix vocabulary match
    (:func:`grace_tpu.telemetry.scopes.match_stage`), applied to the
    equation's ``name_stack``. The profiler trace analyzer
    (:mod:`grace_tpu.profiling`) attributes device spans with literally the
    same function, so static findings and measured time name stages
    identically."""
    from grace_tpu.telemetry.scopes import match_stage

    try:
        stack = str(eqn.source_info.name_stack)
    except Exception:
        return ""
    return match_stage(stack)


def _axes_of(eqn) -> Tuple[str, ...]:
    """The mesh axis names a collective equation operates over."""
    p = eqn.params
    axes = p.get("axes", p.get("axis_name", ()))
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def _sub_jaxprs_of(eqn):
    out = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for item in vs:
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns") and hasattr(inner, "invars"):
                out.append(inner)
    return out


def _is_var(v) -> bool:
    return hasattr(v, "aval") and not hasattr(v, "val")


def _aval_nbytes(aval) -> int:
    return int(np.prod(aval.shape, dtype=np.int64)) * aval.dtype.itemsize


# ---------------------------------------------------------------------------
# replication (rank-variance) dataflow
# ---------------------------------------------------------------------------

def _propagate_variance(jaxpr, axis_name: str,
                        seed: Dict[Any, bool]) -> Dict[Any, bool]:
    """Forward rank-variance over one jaxpr (recursing into sub-jaxprs).

    Conservative in the safe direction: unknown structure propagates
    variance, replication is only granted by full-axis reductions/gathers.
    """
    var: Dict[Any, bool] = {}
    for v in jaxpr.invars:
        var[v] = seed.get(v, True)
    for v in jaxpr.constvars:
        var[v] = False

    def lookup(v) -> bool:
        return var.get(v, False) if _is_var(v) else False

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        any_in = any(lookup(v) for v in eqn.invars)
        if name == "axis_index":
            out = axis_name in _axes_of(eqn) or any_in
        elif name in _REDUCTIONS or name in _GATHERS:
            # Full-axis reduction/gather over our axis: every rank computes
            # the identical result (axis_index_groups would break that).
            full = (axis_name in _axes_of(eqn)
                    and eqn.params.get("axis_index_groups") is None)
            out = False if full else any_in
        elif name in _PERMUTES or name in _ALLTOALL or name in _SCATTER:
            # Rank-varying by construction over the axes they permute; a
            # permute over a DIFFERENT mesh axis (the 2-D dp×fsdp case)
            # moves values within this axis's groups and leaves this
            # axis's variance as the operands had it.
            out = axis_name in _axes_of(eqn) or any_in
        elif name == "pbroadcast":
            out = any_in
        else:
            subs = _sub_jaxprs_of(eqn)
            if subs:
                # Map operand variance into each sub-jaxpr positionally
                # where arities line up (cond drops the predicate operand;
                # other call-like prims pass operands straight through) and
                # OR the sub-results; fall back to any_in otherwise.
                out_flags = []
                for sub in subs:
                    if name == "cond":
                        ops = eqn.invars[1:]
                    else:
                        ops = eqn.invars
                    if len(sub.invars) == len(ops):
                        sub_seed = {sv: lookup(ov)
                                    for sv, ov in zip(sub.invars, ops)}
                        sub_var = _propagate_variance(sub, axis_name,
                                                      sub_seed)
                        out_flags.append(any(
                            sub_var.get(ov, any_in) if _is_var(ov) else False
                            for ov in sub.outvars))
                    else:
                        out_flags.append(any_in)
                out = any(out_flags) or (name == "cond"
                                         and lookup(eqn.invars[0]))
            else:
                out = any_in
        for v in eqn.outvars:
            var[v] = out
    return var


# ---------------------------------------------------------------------------
# pass 1: collective consistency across cond/while branches
# ---------------------------------------------------------------------------

def collective_signature(jaxpr) -> Tuple:
    """Ordered tuple of (prim, axes, operand shapes/dtypes, schedule params)
    for every collective in ``jaxpr``, recursing into nested jaxprs in
    equation order. Two branches with equal signatures issue the same
    collective sequence and can never deadlock against each other."""
    sig = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            operands = tuple(
                (tuple(v.aval.shape), str(v.aval.dtype))
                for v in eqn.invars if _is_var(v))
            extra = tuple(sorted(
                (k, str(v)) for k, v in eqn.params.items()
                if k in ("perm", "all_gather_dimension", "tiled",
                         "axis_index_groups", "split_axis", "concat_axis")))
            sig.append((name, _axes_of(eqn), operands, extra))
        else:
            for sub in _sub_jaxprs_of(eqn):
                sig.extend(collective_signature(sub))
    return tuple(sig)


def _signature_axes(sig, mesh_axes) -> set:
    """The mesh axes a collective signature's entries span."""
    return {a for _name, axes, _ops, _extra in sig
            for a in axes if a in mesh_axes}


def pass_collective_consistency(traced: TracedGraph) -> List[Finding]:
    """Branch-divergent collective sequences under a predicate that is not
    provably replicated: the cross-rank deadlock/desync class. A cond whose
    branches differ (the dense escape hatch, the consensus audit gate) is
    legal exactly when its predicate is replicated **over every mesh axis
    the divergent collectives span** — every rank that must rendezvous
    takes the same branch. On a 2-D dp×fsdp mesh the analysis is
    per-axis: a predicate that varies only over fsdp may legally gate a
    dp-axis collective (the dp peers share an fsdp index, so they agree),
    while a predicate replicated over the *wrong* axis — e.g. psummed
    over fsdp but still dp-varying, gating a dp collective — is condemned.
    """
    findings: List[Finding] = []
    axes = traced.axes

    def walk(jaxpr, var_maps):
        def lookup(axis, v):
            m = var_maps[axis]
            return m.get(v, False) if _is_var(v) else False

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "cond":
                branches = [getattr(b, "jaxpr", b)
                            for b in eqn.params["branches"]]
                sigs = [collective_signature(b) for b in branches]
                if any(s != sigs[0] for s in sigs[1:]):
                    spanned = set()
                    for s in sigs:
                        spanned |= _signature_axes(s, axes)
                    bad = sorted(a for a in spanned
                                 if lookup(a, eqn.invars[0]))
                    if bad:
                        findings.append(Finding(
                            pass_name="collective_consistency",
                            config=traced.name, severity="error",
                            stage=_stage_of(eqn),
                            message=(
                                "lax.cond branches issue different "
                                "collective sequences "
                                f"({[len(s) for s in sigs]} collectives per "
                                "branch) spanning mesh "
                                f"axis(es) {sorted(spanned)} and the "
                                "predicate is derived from data that "
                                f"varies over {bad} — ranks that must "
                                "rendezvous can take different branches "
                                "and deadlock/desync at the first "
                                "mismatched collective"),
                            details=(("world", traced.world),
                                     ("varying_axes", tuple(bad)))))
            elif name == "while":
                cond_j = getattr(eqn.params.get("cond_jaxpr"), "jaxpr",
                                 eqn.params.get("cond_jaxpr"))
                body_j = getattr(eqn.params.get("body_jaxpr"), "jaxpr",
                                 eqn.params.get("body_jaxpr"))
                sig = (collective_signature(body_j)
                       if body_j is not None else ())
                sig += (collective_signature(cond_j)
                        if cond_j is not None else ())
                spanned = _signature_axes(sig, axes) or (
                    set(axes) if sig else set())
                if sig and any(lookup(a, v) for a in spanned
                               for v in eqn.invars):
                    findings.append(Finding(
                        pass_name="collective_consistency",
                        config=traced.name, severity="error",
                        stage=_stage_of(eqn),
                        message=(
                            f"while loop contains {len(sig)} collective(s) "
                            "but its carry includes rank-varying data — "
                            "trip counts can diverge across ranks and "
                            "strand a subset in the collective"),
                        details=(("world", traced.world),)))
            # Recurse with operand variance mapped into the sub-jaxpr.
            for sub in _sub_jaxprs_of(eqn):
                ops = eqn.invars[1:] if name == "cond" else eqn.invars
                sub_maps = {}
                for a in axes:
                    if len(sub.invars) == len(ops):
                        seed = {sv: lookup(a, ov)
                                for sv, ov in zip(sub.invars, ops)}
                    else:
                        seed = {sv: True for sv in sub.invars}
                    sub_maps[a] = _propagate_variance(sub, a, seed)
                walk(sub, sub_maps)

    walk(traced.body, {a: _propagate_variance(traced.body, a,
                                              traced.varying_for(a))
                       for a in axes})
    return findings


# ---------------------------------------------------------------------------
# pass 2: bit-exactness of cross-replica reductions
# ---------------------------------------------------------------------------

def pass_bit_exactness(traced: TracedGraph) -> List[Finding]:
    """Bit-pattern data must never ride a float-space cross-replica
    reduction (the PR-3 bug class: ``-0.0 + 0.0 == +0.0`` flips sign bits,
    NaN payloads are not preserved through float adds).

    Taint: values whose *numeric content encodes a bit pattern* — produced
    by ``bitcast_convert_type`` to an integer dtype (fingerprint words,
    checksum folds, masked-broadcast words), propagated through arithmetic
    and value conversions, and cleared by a bitcast back to float (which
    reconstructs the original values). A float-dtype
    ``psum``/``pmean``/... over tainted data is the finding; integer-space
    reductions (``masked_broadcast``'s uint psum) and gathers (which move
    bits verbatim) are exactly the sanctioned alternatives.
    """
    findings: List[Finding] = []

    def walk(jaxpr, seed_taint: Dict[Any, bool]):
        taint: Dict[Any, bool] = {}
        for v in jaxpr.invars:
            taint[v] = seed_taint.get(v, False)
        for v in jaxpr.constvars:
            taint[v] = False

        def lookup(v):
            return taint.get(v, False) if _is_var(v) else False

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            any_in = any(lookup(v) for v in eqn.invars)
            if name == "bitcast_convert_type":
                new_dtype = np.dtype(eqn.params["new_dtype"])
                out = not np.issubdtype(new_dtype, np.floating)
            elif name in _REDUCTIONS:
                if (any(a in _axes_of(eqn) for a in traced.axes) and any(
                        lookup(v) and np.issubdtype(v.aval.dtype,
                                                    np.floating)
                        for v in eqn.invars if _is_var(v))):
                    findings.append(Finding(
                        pass_name="bit_exactness",
                        config=traced.name, severity="error",
                        stage=_stage_of(eqn),
                        message=(
                            f"float-dtype {name} over bit-pattern data "
                            "(descends from an integer bitcast: "
                            "fingerprint/checksum/masked-broadcast words) "
                            "— float adds alias -0.0/+0.0 and drop NaN "
                            "payloads; reduce in integer bit space "
                            "(comm.masked_broadcast) instead"),
                        details=(("world", traced.world),)))
                out = any_in
            else:
                subs = _sub_jaxprs_of(eqn)
                for sub in subs:
                    ops = eqn.invars[1:] if name == "cond" else eqn.invars
                    if len(sub.invars) == len(ops):
                        walk(sub, {sv: lookup(ov)
                                   for sv, ov in zip(sub.invars, ops)})
                    else:
                        walk(sub, {sv: any_in for sv in sub.invars})
                out = any_in
            for v in eqn.outvars:
                taint[v] = out

    walk(traced.body, {})
    return findings


# ---------------------------------------------------------------------------
# pass 3: wire-byte reconciliation against Communicator.recv_wire_bytes
# ---------------------------------------------------------------------------

def _group_size(eqn, world: int) -> int:
    """Ranks one collective actually spans: the ``axis_index_groups`` group
    size when set (the hierarchical communicator's nested sub-axes —
    cross-slice peers, intra-slice peers), else the whole axis. Groups
    partition the axis into equal-size sets, so the first group's length is
    the per-rank schedule width."""
    groups = eqn.params.get("axis_index_groups")
    if not groups:
        return world
    return len(groups[0])


def _link_tier(eqn, world: int, topology) -> int:
    """Worst link tier this collective's schedule touches under
    ``topology`` — 0 = ICI (intra-slice), 1 = DCN (cross-slice), 2 = WAN
    (cross-region): the critical-path attribution of
    :meth:`~grace_tpu.core.Communicator.recv_link_bytes`, derived from the
    *traced* rank sets instead of the hand-maintained model:

    * a ``ppermute`` crosses a boundary iff any (src, dst) pair sits on
      different sides of it (a flat ring's wrap-around neighbor pair
      always does once the axis spans the boundary — which is why flat
      rings price at the worst tier the axis spans);
    * a grouped collective crosses iff any group mixes sides (the
      hierarchical comm's cross-slice groups cross DCN yet stay inside a
      region; its cross-region groups cross WAN; intra-slice groups
      never cross anything);
    * an ungrouped full-axis collective crosses whatever the axis does.
    """
    if topology is None or not topology.crosses_dcn(world):
        return 0
    spans = [topology.slice_size]
    if topology.region_size is not None and topology.crosses_wan(world):
        spans.append(topology.region_size)

    def crosses(span: int) -> bool:
        if eqn.primitive.name in _PERMUTES:
            perm = eqn.params.get("perm") or ()
            return any(int(a) // span != int(b) // span for a, b in perm)
        groups = eqn.params.get("axis_index_groups")
        if groups:
            return any(len({int(r) // span for r in grp}) > 1
                       for grp in groups)
        return True

    tier = 0
    for i, span in enumerate(spans, start=1):
        if crosses(span):
            tier = i
    return tier


def count_recv_bytes(jaxpr, axis_name: str, world: int) -> int:
    """Logical bytes RECEIVED per rank for the collectives in ``jaxpr`` —
    the scalar view of :func:`count_recv_link_bytes`."""
    return sum(count_recv_link_bytes(jaxpr, axis_name, world, None))


def count_recv_link_bytes(jaxpr, axis_name: str, world: int,
                          topology) -> Tuple[int, int, int]:
    """Per-rank received bytes of the collectives in ``jaxpr``, split into
    ``(ici, dcn, wan)`` by the worst boundary each collective's traced
    schedule crosses under ``topology`` (recursive; cond branches count as
    the branch with the larger total — an upper bound matching how the wire
    model prices the live path). ``topology=None`` attributes everything to
    ICI (the single-slice scalar count).

    Per-collective accounting mirrors the standard schedules the model in
    :meth:`grace_tpu.core.Communicator.recv_wire_bytes` assumes, over the
    ranks the collective actually spans (``axis_index_groups`` narrows a
    collective to its group — the hierarchical communicator's nested
    sub-axes): ring all-reduce moves ``2·n·(G-1)/G``; a gather receives
    every other member's shard ``n·(G-1)``; a ppermute hop receives one
    full operand; all_to_all and reduce_scatter receive ``n·(G-1)/G``.
    """
    tiers = [0, 0, 0]
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS and axis_name in _axes_of(eqn):
            nbytes = sum(_aval_nbytes(v.aval) for v in eqn.invars
                         if _is_var(v))
            g = _group_size(eqn, world)
            if name in _REDUCTIONS:
                got = 2 * nbytes * (g - 1) // max(1, g)
            elif name in _GATHERS:
                got = nbytes * max(0, g - 1)
            elif name in _PERMUTES:
                got = nbytes
            else:                      # all_to_all / reduce_scatter
                got = nbytes * (g - 1) // max(1, g)
            tiers[_link_tier(eqn, world, topology)] += got
        elif name == "cond":
            branches = [count_recv_link_bytes(getattr(b, "jaxpr", b),
                                              axis_name, world, topology)
                        for b in eqn.params["branches"]]
            if branches:
                best = max(branches, key=sum)
                tiers = [a + b for a, b in zip(tiers, best)]
        else:
            for sub in _sub_jaxprs_of(eqn):
                sub_t = count_recv_link_bytes(sub, axis_name, world,
                                              topology)
                tiers = [a + b for a, b in zip(tiers, sub_t)]
    return tiers[0], tiers[1], tiers[2]


def pass_wire_reconciliation(traced: TracedGraph) -> List[Finding]:
    """Count the traced graph's per-rank received collective bytes and
    reconcile them against the ``Communicator.recv_wire_bytes`` model that
    telemetry rows and bench projections trust. Fails when the
    hand-maintained model drifts from the real collective schedule by more
    than the documented tolerance (:data:`grace_tpu.core.WIRE_MODEL_RTOL` /
    ``WIRE_MODEL_ATOL``). Needs ``meta['grace']`` (the config bundle) — a
    no-op on traces without a priceable model."""
    from grace_tpu.core import (WIRE_MODEL_ATOL, WIRE_MODEL_RTOL, LinkBytes,
                                negotiation_bytes_for)
    from grace_tpu.transform import (fusion_payload_nbytes,
                                     fusion_payload_structs)
    from grace_tpu.analysis.trace import default_param_structs

    grace = traced.meta.get("grace")
    if grace is None:
        return []
    named = traced.meta.get("param_structs")
    if named is None:
        named = default_param_structs()
    import jax
    leaves = jax.tree_util.tree_leaves(named)

    counted = count_recv_bytes(traced.body, traced.axis_name, traced.world)
    routed = bool(getattr(grace, "routes", None))
    if routed:
        # Routed configs price as the SUM of per-leaf models through each
        # leaf's own codec and communicator (negotiation collectives
        # included) — the one enumeration helper.routed_recv_link_bytes
        # owns, so telemetry, bench, and this audit can never disagree.
        from grace_tpu.helper import routed_recv_link_bytes

        def model_link_at(topo):
            return routed_recv_link_bytes(grace, named, traced.world,
                                          topology=topo)

        model = model_link_at(None).total
        comp_b = None
        comm_name = "routed per-leaf model"
    else:
        _, comp_b, n_elems = fusion_payload_nbytes(
            grace.compressor, leaves, grace.fusion)
        vote = bool(getattr(grace.compressor, "vote_aggregate", False))
        # Negotiation collectives (shared-scale pmax, cyclic Top-K's index
        # broadcast) are real traced bytes — the model must carry them or
        # an index negotiation larger than the atol reads as drift.
        import numpy as _np
        neg_b = sum(count * negotiation_bytes_for(
            grace.compressor,
            int(_np.prod(s.shape, dtype=_np.int64)), traced.world)
            for s, count in fusion_payload_structs(leaves, grace.fusion))

        def model_link_at(topo):
            lb = grace.communicator.recv_link_bytes(
                comp_b, n_elems, traced.world, topology=topo, vote=vote)
            if not neg_b:
                return lb
            # Negotiations are flat full-axis collectives: their bytes
            # land on the worst tier the axis spans (ICI within one
            # slice, DCN across slices, WAN across regions) — same
            # flat_tier rule the telemetry fold uses.
            from grace_tpu.core import Topology as _T
            t = topo if topo is not None else _T()
            tier = t.flat_tier(traced.world)
            return lb._replace(**{tier: getattr(lb, tier) + neg_b})

        model = grace.communicator.recv_wire_bytes(
            comp_b, n_elems, traced.world, vote=vote) + neg_b
        comm_name = f"{type(grace.communicator).__name__}.recv_wire_bytes"
    tol = max(WIRE_MODEL_RTOL * max(model, counted), WIRE_MODEL_ATOL)
    if abs(counted - model) > tol:
        return [Finding(
            pass_name="wire_reconciliation", config=traced.name,
            severity="error", stage="grace/exchange",
            message=(
                f"{comm_name} "
                f"models {model} B/rank/step but the traced graph moves "
                f"{counted} B (world={traced.world}, payload={comp_b} B) — "
                f"drift {abs(counted - model)} B exceeds the documented "
                f"tolerance (rtol={WIRE_MODEL_RTOL}, "
                f"atol={WIRE_MODEL_ATOL} B); telemetry wire_bytes and "
                "bench projections are lying"),
            details=(("model_bytes", int(model)),
                     ("counted_bytes", int(counted)),
                     ("world", traced.world)))]
    # Scalar model reconciles — now hold the per-link breakdown to it.
    # The split (ici, dcn, wan) must sum to the scalar bit-exactly under
    # any topology: a communicator that overrides recv_link_bytes without
    # keeping the identity (or vice versa) would make bench projections
    # price different bytes than telemetry records. Checked at the
    # single-slice default, a slice boundary that forces the DCN leg, and
    # a region boundary that forces the WAN leg.
    from grace_tpu.core import Topology
    half = max(1, traced.world // 2)
    identity_topos = [None, Topology(slice_size=half)]
    if traced.world >= 4:
        identity_topos.append(Topology(slice_size=max(1, traced.world // 4),
                                       region_size=half))
    for topo in identity_topos:
        link = model_link_at(topo)
        if link.total != model:
            return [Finding(
                pass_name="wire_reconciliation", config=traced.name,
                severity="error", stage="grace/exchange",
                message=(
                    f"{comm_name} "
                    f"splits into ici={link.ici} + dcn={link.dcn} + "
                    f"wan={link.wan} = {link.total} B under topology "
                    f"{topo!r}, but the scalar model says {model} B — the "
                    "per-link breakdown and the scalar model must be one "
                    "implementation (override _recv_total_bytes, not the "
                    "public methods)"),
                details=(("model_bytes", int(model)),
                         ("ici_bytes", int(link.ici)),
                         ("dcn_bytes", int(link.dcn)),
                         ("wan_bytes", int(link.wan)),
                         ("world", traced.world)))]
    # Finally reconcile the split itself against the TRACED schedule: put a
    # slice boundary on the audit mesh (the communicator's own slice_size
    # when it declares one — the hierarchical comm's nested sub-axes must
    # land on it — else world/2) and attribute each traced collective's
    # bytes by whether its rank sets cross that boundary. This is what
    # keeps a "mixed" recv_link_bytes honest: a hierarchical communicator
    # whose intra-slice ring secretly crossed slices, or whose DCN leg
    # moved more than the modeled partials, drifts leg-by-leg even when
    # the scalar total still balances.
    own_slice = getattr(grace.communicator, "slice_size", None)
    own_region = getattr(grace.communicator, "region_size", None)
    audit_topo = Topology(
        slice_size=(int(own_slice) if own_slice
                    else max(1, traced.world // 2)),
        region_size=int(own_region) if own_region else None)
    counted_link = count_recv_link_bytes(
        traced.body, traced.axis_name, traced.world, audit_topo)
    model_link = model_link_at(audit_topo)
    for leg, got, want in (("ici", counted_link[0], model_link.ici),
                           ("dcn", counted_link[1], model_link.dcn),
                           ("wan", counted_link[2], model_link.wan)):
        tol = max(WIRE_MODEL_RTOL * max(got, want), WIRE_MODEL_ATOL)
        if abs(got - want) > tol:
            return [Finding(
                pass_name="wire_reconciliation", config=traced.name,
                severity="error", stage="grace/exchange",
                message=(
                    f"{type(grace.communicator).__name__}.recv_link_bytes "
                    f"models {leg}={want} B under topology {audit_topo!r} "
                    f"but the traced schedule moves {got} B over that link "
                    f"class (counted split ici={counted_link[0]}, "
                    f"dcn={counted_link[1]}, wan={counted_link[2]}) — "
                    f"drift {abs(got - want)} B "
                    f"exceeds the documented tolerance "
                    f"(rtol={WIRE_MODEL_RTOL}, atol={WIRE_MODEL_ATOL} B); "
                    "the per-link projections and telemetry split are "
                    "lying about which link the bytes ride"),
                details=(("leg", leg),
                         ("model_ici", int(model_link.ici)),
                         ("model_dcn", int(model_link.dcn)),
                         ("model_wan", int(model_link.wan)),
                         ("counted_ici", int(counted_link[0])),
                         ("counted_dcn", int(counted_link[1])),
                         ("counted_wan", int(counted_link[2])),
                         ("world", traced.world)))]
    return []


# ---------------------------------------------------------------------------
# pass 4: retrace / host-sync sniffing
# ---------------------------------------------------------------------------

def _aval_sig(aval) -> Tuple:
    return (tuple(aval.shape), str(aval.dtype),
            bool(getattr(aval, "weak_type", False)))


def pass_signature_stability(traced: TracedGraph) -> List[Finding]:
    """Two retrace/host-sync smells that turn a compiled step into a
    per-step recompile or a device round-trip:

    * the abstract state signature must be a **fixed point** of the update
      — a weak-type promotion or Python-scalar closure leak (``count +
      1.0``) changes the next step's input avals, forcing jit to retrace
      every step (and silently duplicating compile memory);
    * host callbacks (``io_callback``/``debug_callback``/``pure_callback``)
      inside the compiled step serialize the device against the host —
      telemetry exists precisely so the hot path never does this.
    """
    findings: List[Finding] = []
    for (path, in_aval), (_, out_aval) in zip(traced.state_in,
                                              traced.state_out):
        if _aval_sig(in_aval) != _aval_sig(out_aval):
            si, so = _aval_sig(in_aval), _aval_sig(out_aval)
            what = ("weak-type promotion"
                    if si[:2] == so[:2] and si[2] != so[2]
                    else "abstract-signature change")
            findings.append(Finding(
                pass_name="signature_stability", config=traced.name,
                severity="error",
                message=(
                    f"state leaf '{path}' is not a signature fixed point: "
                    f"in {si[0]}/{si[1]}"
                    f"{'/weak' if si[2] else ''} -> out {so[0]}/{so[1]}"
                    f"{'/weak' if so[2] else ''} ({what} — likely a Python "
                    "scalar leaking into the carried state; jit retraces "
                    "every step)"),
                details=(("path", path),)))

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in _CALLBACK_PRIMS:
                cb = eqn.params.get("callback", "")
                findings.append(Finding(
                    pass_name="signature_stability", config=traced.name,
                    severity="error", stage=_stage_of(eqn),
                    message=(
                        f"host callback '{name}' inside the compiled step "
                        f"({cb!r}) — serializes every step against the "
                        "host; use the in-graph telemetry ring "
                        "(grace_tpu.telemetry) and drain it at flush "
                        "boundaries instead"),
                    details=()))
            for sub in _sub_jaxprs_of(eqn):
                walk(sub)

    walk(traced.body)
    return findings


_PASS_FNS = {
    "collective_consistency": pass_collective_consistency,
    "bit_exactness": pass_bit_exactness,
    "wire_reconciliation": pass_wire_reconciliation,
    "signature_stability": pass_signature_stability,
}


def _resolve_pass(name: str):
    """Pass function by name; loads the graft-flow and graft-sound modules
    on first use of one of their passes (both import this module, so eager
    registration would be a cycle)."""
    fn = _PASS_FNS.get(name)
    if fn is None:
        from grace_tpu.analysis import flow, state_passes
        _PASS_FNS.update(flow.PASS_FNS)
        _PASS_FNS.update(state_passes.PASS_FNS)
        fn = _PASS_FNS[name]
    return fn


def run_passes(traced: TracedGraph,
               passes: Optional[Tuple[str, ...]] = None) -> List[Finding]:
    """Run the named passes (default: all ten) over one traced graph."""
    out: List[Finding] = []
    for name in (passes if passes is not None else PASS_NAMES):
        out.extend(_resolve_pass(name)(traced))
    return out
