"""graft-tune: the topology-aware autotuner (ROADMAP item 1).

The first subsystem that consumes the repo's seven lint passes and the
shared per-link wire model as *inputs to a decision* rather than as gates:
given a model's param tree and a target mesh topology, it

1. **enumerates** (codec, communicator, fusion, pallas, precision)
   candidates from the static auditor's registry plus topology-aware
   generated variants (:mod:`.candidates`), rejecting illegal combos with
   the same capability gates the communicators enforce;
2. **prunes statically** (:mod:`.prune`): numeric safety at the target
   world, per-link wire pricing under the target
   :class:`~grace_tpu.core.Topology` through the documented
   wire-dominated cost model (:mod:`.cost`), flow pass 5/6/7 over the
   ranked survivors — every rejection recorded with its reason;
3. **measures the shortlist** (:mod:`.measure`): real timed steps on the
   host clock, dense brackets interleaved same-session,
   each candidate's own measured compute step substituted back into the
   cost model for the target-topology ranking;
4. **stamps the winner**: a ``grace_from_params``-loadable config with git
   revision, topology, the prune funnel, and the measured≤static overlap
   sandwich as its honesty gate, written to ``TUNE_LAST.json``.

CLI: ``tools/graft_tune.py``.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Sequence, Union

from grace_tpu.tuning.candidates import (Candidate, candidate_legal,
                                         enumerate_candidates,
                                         variant_audit_entries)
from grace_tpu.tuning.cost import TuneTopology, price_candidate, \
    projection_constants
from grace_tpu.tuning.measure import (build_model_step, measure_shortlist,
                                      model_structs, overlap_sandwich)
from grace_tpu.tuning.prune import numeric_verdict, static_prune

__all__ = ["Candidate", "TuneTopology", "candidate_legal",
           "enumerate_candidates", "measure_shortlist", "model_structs",
           "numeric_verdict", "overlap_sandwich", "price_candidate",
           "projection_constants", "run_tune", "static_prune",
           "variant_audit_entries", "write_tune_evidence",
           "TUNE_EVIDENCE_PATH"]

TUNE_EVIDENCE_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "TUNE_LAST.json")


def run_tune(topologies: Sequence[Union[str, TuneTopology]], *,
             model: str = "toy", shortlist_n: int = 3,
             static_only: bool = False, audit_world: int = 8,
             timed_steps: int = 8, repeats: int = 2, seed: int = 0,
             mesh=None, trace_dir: Optional[str] = None,
             argv: str = "") -> Dict[str, Any]:
    """The whole tuning loop; returns the ``TUNE_LAST.json`` document.

    The FIRST topology is the decision target (its shortlist is measured
    and its winner stamped); the rest get static rankings only — the
    ``--static-only`` registry survey ranks every listed topology. The
    document's ``ok`` field is the CLI's exit-0 condition: static runs are
    ok by construction, measured runs require a winner whose overlap
    sandwich holds.
    """
    specs = [t if isinstance(t, TuneTopology) else TuneTopology.parse(t)
             for t in topologies]
    if not specs:
        raise ValueError("at least one topology is required")
    target = specs[0]
    structs = model_structs(model)
    ici_bw, dcn_bw, wan_bw, projection_model = projection_constants()

    static: Dict[str, Any] = {}
    candidates_by_name: Dict[str, Candidate] = {}
    for spec in specs:
        cands = enumerate_candidates(spec)
        for c in cands:
            candidates_by_name.setdefault(c.name, c)
        static[spec.label] = static_prune(
            cands, spec, structs, audit_world=audit_world,
            shortlist_n=shortlist_n)

    doc: Dict[str, Any] = {
        "tool": "graft_tune",
        "model": model,
        "topologies": [{"world": s.world, "slice_size": s.slice_size,
                        "region_size": s.region_size,
                        "label": s.label} for s in specs],
        "target": target.label,
        "cost_model": {
            "ici_bytes_per_s": ici_bw,
            "dcn_bytes_per_s": dcn_bw,
            "wan_bytes_per_s": wan_bw,
            "rule": "projected_step = base_compute_step + ici_bytes/ICI_BW"
                    " + dcn_bytes/DCN_BW + wan_bytes/WAN_BW (per-link "
                    "recv_link_bytes under the target Topology; see "
                    "grace_tpu/tuning/cost.py)",
            "constants_source": projection_model["constants_source"],
        },
        "static": static,
        "static_only": bool(static_only),
        "ok": True,
    }

    if not static_only:
        target_prune = static[target.label]
        shortlist = [candidates_by_name[n]
                     for n in target_prune["shortlist"]]
        if mesh is None:
            import jax

            from grace_tpu.parallel import data_parallel_mesh
            mesh = data_parallel_mesh(jax.devices())
        measured = measure_shortlist(
            shortlist, target, mesh, model=model,
            timed_steps=timed_steps, repeats=repeats, seed=seed)
        doc["measured"] = measured
        winner_name = measured["winner"]
        if winner_name is None:
            doc["ok"] = False
            doc["error"] = "no shortlisted candidate produced a measurement"
        else:
            if trace_dir is None:
                import tempfile
                trace_dir = tempfile.mkdtemp(prefix="graft_tune_prof_")
            sandwich = overlap_sandwich(
                candidates_by_name[winner_name], mesh, trace_dir,
                model=model, seed=seed)
            funnel_rec = next(
                r for r in target_prune["funnel"]
                if r["candidate"] == winner_name)
            row = next(r for r in measured["rows"]
                       if r["candidate"] == winner_name)
            doc["winner"] = {
                "candidate": winner_name,
                # The loadable config: grace_from_params(winner["grace_params"])
                # rebuilds the winning triad verbatim.
                "grace_params": dict(
                    candidates_by_name[winner_name].params),
                "topology": {"world": target.world,
                             "slice_size": target.slice_size},
                "predicted": funnel_rec.get("predicted"),
                "static_overlap_bound":
                    (funnel_rec.get("flow") or {}).get("overlap_bound"),
                "measured": row,
                "overlap_sandwich": sandwich,
            }
            doc["ok"] = bool(sandwich["holds"])

    # Provenance last: everything above is deterministic for a fixed
    # registry + topology (the determinism contract tests/test_tuning.py
    # pins, modulo these stamps).
    try:
        from grace_tpu.utils.logging import run_provenance
        doc["provenance"] = run_provenance(
            data="synthetic", tool="graft_tune", argv=argv)
    except Exception:                                    # noqa: BLE001
        doc["provenance"] = None
    import datetime
    doc["captured_at"] = datetime.datetime.now(
        datetime.timezone.utc).isoformat(timespec="seconds")
    return doc


def write_tune_evidence(doc: Dict[str, Any],
                        path: str = TUNE_EVIDENCE_PATH) -> None:
    """Atomic tmp+fsync+replace, the repo's evidence-write idiom."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
