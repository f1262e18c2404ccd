"""graft-tune: topology-aware autotuner (ISSUE 12).

The acceptance criteria pinned here: the static funnel is auditable (every
candidate leaves with a stage + reason), seeded-bad candidates die at the
right gate (capability-illegal combos never reach measurement, the W=4096
fp16 hop-sum dies in the numeric stage, the flat hop-requant ring dies at
pod scale in the degradation stage), the full-registry static ranking puts
the hier family on top at the W=256/slice8 projection topology, the tuner
is deterministic (same registry + topology → byte-identical TUNE_LAST.json
modulo timestamps), and a real end-to-end CPU run produces a
provenance-stamped winner that beats the worst shortlisted candidate on
measured step time and passes the measured≤static overlap sandwich.
"""

import importlib.util
import json
import os

import pytest

from grace_tpu.helper import grace_from_params
from grace_tpu.tuning import (Candidate, TuneTopology, candidate_legal,
                              enumerate_candidates, run_tune, static_prune,
                              variant_audit_entries, write_tune_evidence)
from grace_tpu.tuning.measure import model_structs
from grace_tpu.tuning.prune import (MAX_REQUANT_CHAIN, degradation_verdict,
                                    numeric_verdict, requant_chain_length)

pytestmark = pytest.mark.tune

W8 = TuneTopology(world=8)
XSLICE = TuneTopology(world=256, slice_size=8)


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_under_test",
        os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                     f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# topology spec + gates
# ---------------------------------------------------------------------------

def test_topology_parse():
    assert TuneTopology.parse("8") == TuneTopology(8)
    assert TuneTopology.parse("256,8") == TuneTopology(256, 8)
    assert TuneTopology.parse(" 64 , 4 ").label == "W64/slice4"
    for bad in ("", "8,4,2", "0", "8,0"):
        with pytest.raises(ValueError):
            TuneTopology.parse(bad)


@pytest.mark.parametrize("params,why", [
    ({"compressor": "topk", "compress_ratio": 0.3, "memory": "residual",
      "communicator": "allreduce"}, "summable_payload"),
    ({"compressor": "fp16", "memory": "none",
      "communicator": "sign_allreduce"}, "vote_aggregate"),
    ({"compressor": "dgc", "compress_ratio": 0.3, "memory": "dgc",
      "communicator": "ring"}, "payload algebra"),
    ({"compressor": "signum", "momentum": 0.9, "memory": "none",
      "communicator": "twoshot"}, "stateless"),
    ({"compressor": "topk", "compress_ratio": 0.01,
      "topk_algorithm": "chunk", "memory": "residual",
      "communicator": "hier", "slice_size": 3}, "does not divide world"),
])
def test_capability_gate_mirrors_runtime(params, why):
    """Illegal combos the communicators reject at build/step time are
    rejected statically, with the communicator's rationale."""
    legal, reason, _ = candidate_legal(
        Candidate("bad", params, "generated"), W8)
    assert not legal and why in reason


def test_capability_gate_accepts_the_registry():
    """Every enumerated candidate is legal at the world-8 audit mesh —
    the registry IS the enforced compat matrix."""
    for c in enumerate_candidates(W8):
        legal, reason, _ = candidate_legal(c, W8)
        assert legal, (c.name, reason)


def test_numeric_gate_fp16_hop_sum_at_4096():
    """THE seeded numeric-unsafe candidate: W=4096 fp16 payload-space sums
    blow the 65504 cliff — rejected statically, same constant as flow
    pass 6 (safe_sum_terms)."""
    spec = TuneTopology(world=4096)
    reason = numeric_verdict(
        grace_from_params({"compressor": "fp16", "memory": "none",
                           "communicator": "allreduce"}), spec)
    assert reason is not None and "safe_sum_terms" in reason
    # bf16 has no cliff at any real W (same registry shape, safe dtype).
    assert numeric_verdict(
        grace_from_params({"compressor": "bf16", "memory": "none",
                           "communicator": "allreduce"}), spec) is None


def test_numeric_gate_vote_bound():
    g = grace_from_params({"compressor": "signsgd", "memory": "none",
                           "communicator": "sign_allreduce"})
    assert numeric_verdict(g, TuneTopology(256)) is None      # bf16 edge
    reason = numeric_verdict(g, TuneTopology(512))
    assert reason is not None and "vote_exact_max_world" in reason


def test_requant_chain_lengths():
    ring_topk = grace_from_params({
        "compressor": "topk", "compress_ratio": 0.01,
        "topk_algorithm": "chunk", "memory": "residual",
        "communicator": "ring", "fusion": "flat"})
    hier_topk = grace_from_params({
        "compressor": "topk", "compress_ratio": 0.01,
        "topk_algorithm": "chunk", "memory": "residual",
        "communicator": "hier", "slice_size": 8, "fusion": "flat"})
    fp16_ring = grace_from_params({"compressor": "fp16", "memory": "none",
                                   "communicator": "ring",
                                   "fusion": "flat"})
    gather = grace_from_params({"compressor": "topk", "compress_ratio": 0.3,
                                "memory": "residual",
                                "communicator": "allgather"})
    assert requant_chain_length(ring_topk, W8) == 7
    assert requant_chain_length(ring_topk, XSLICE) == 255
    # hier: S-1 intra hops + ONE boundary re-encode regardless of K.
    assert requant_chain_length(hier_topk, XSLICE) == 8
    assert requant_chain_length(hier_topk, W8) == 7    # collapses to ring
    assert requant_chain_length(fp16_ring, XSLICE) == 0   # exact path
    assert requant_chain_length(gather, XSLICE) == 0
    # The gate: flat hop-requant ring dies at pod scale, hier survives.
    assert degradation_verdict(ring_topk, XSLICE) is not None
    assert "ScaleCom" in degradation_verdict(ring_topk, XSLICE)
    assert degradation_verdict(hier_topk, XSLICE) is None
    assert requant_chain_length(hier_topk, XSLICE) <= MAX_REQUANT_CHAIN


# ---------------------------------------------------------------------------
# the prune funnel
# ---------------------------------------------------------------------------

def test_prune_funnel_seeded_bad_candidates():
    """Every seeded-bad candidate dies at its own stage with a recorded
    reason, and none of them reaches the shortlist (i.e. measurement)."""
    structs = model_structs("toy")
    spec = TuneTopology(world=4096)
    cands = [
        Candidate("bad-capability",
                  {"compressor": "topk", "compress_ratio": 0.3,
                   "memory": "residual", "communicator": "allreduce"},
                  "generated"),
        Candidate("bad-numeric",
                  {"compressor": "fp16", "memory": "none",
                   "communicator": "allreduce"}, "generated"),
        Candidate("bad-degradation",
                  {"compressor": "qsgd", "quantum_num": 64,
                   "use_pallas": False, "memory": "none",
                   "communicator": "ring", "fusion": "flat"}, "generated"),
        Candidate("good",
                  {"compressor": "topk", "compress_ratio": 0.01,
                   "topk_algorithm": "chunk", "memory": "residual",
                   "communicator": "hier", "slice_size": 8,
                   "fusion": "flat"}, "generated"),
    ]
    out = static_prune(cands, spec, structs, shortlist_n=2)
    by = {r["candidate"]: r for r in out["funnel"]}
    assert by["bad-capability"]["stage"] == "capability"
    assert by["bad-numeric"]["stage"] == "numeric"
    assert by["bad-degradation"]["stage"] == "degradation"
    for name in ("bad-capability", "bad-numeric", "bad-degradation"):
        assert by[name]["verdict"] == "rejected"
        assert by[name]["reason"]            # auditable, never silent
    assert out["shortlist"] == ["good"]
    assert by["good"]["verdict"] == "shortlisted"
    assert by["good"]["flow"]["overlap_bound"] is not None
    c = out["counts"]
    assert (c["capability_rejected"], c["numeric_rejected"],
            c["degradation_rejected"], c["shortlisted"]) == (1, 1, 1, 1)


@pytest.fixture(scope="module")
def static_doc():
    """One full-registry static survey under both acceptance topologies,
    shared across the ranking assertions (the expensive part is the flow
    audit of each topology's ranked head)."""
    return run_tune(("8", "256,8"), static_only=True, shortlist_n=2,
                    argv="test-static")


def test_static_ranks_full_registry_under_both_topologies(static_doc):
    assert set(static_doc["static"]) == {"W8", "W256/slice8"}
    for label, st in static_doc["static"].items():
        # every enumerated candidate leaves the funnel with a verdict
        assert all(r.get("verdict") for r in st["funnel"]), label
        rejected = [r for r in st["funnel"] if r["verdict"] == "rejected"]
        assert all(r.get("reason") for r in rejected), label
        assert st["counts"]["enumerated"] == len(st["funnel"])
        assert len(st["ranking"]) == st["counts"]["priced"]
    assert static_doc["ok"] is True


def test_static_top_pick_at_xslice_is_sharded_or_hier_family(static_doc):
    """ISSUE 12/14 acceptance: the top static pick at W=256/slice8 is the
    rscatter family — the ISSUE-14 one-shot reduce-scatter moves ~2·k
    over DCN where hier still ships (K−1)·k/S partials, and its requant
    chain is ≤1 at any W so the degradation gate never rejects it — with
    the hier family (the pinned 1.06× xslice projection) right behind,
    still carrying the genuinely mixed split."""
    st = static_doc["static"]["W256/slice8"]
    top = st["ranking"][0]
    rec = next(r for r in st["funnel"] if r["candidate"] == top["candidate"])
    assert rec["params"]["communicator"] == "rscatter"
    assert rec["requant_chain"] <= 1
    assert top["predicted_speedup_vs_dense"] > 1.0
    # hier is the runner-up family, and its mixed split is real: both
    # links carry bytes
    hier = next(r for r in st["ranking"]
                if "hier" in r["candidate"])
    assert hier["ici_bytes"] > 0 and hier["dcn_bytes"] > 0
    # while the flat-communicator candidates degenerate to all-DCN there
    flat = next(r for r in st["funnel"]
                if r["candidate"] == "topk-allgather"
                and r.get("predicted"))
    assert flat["predicted"]["ici_bytes"] == 0
    assert flat["predicted"]["dcn_bytes"] > 0


def test_cost_model_stamped_from_cost_constants(static_doc):
    from grace_tpu.tuning import cost
    cm = static_doc["cost_model"]
    assert cm["ici_bytes_per_s"] == cost.ICI_RING_BYTES_PER_S
    assert cm["dcn_bytes_per_s"] == cost.DCN_BYTES_PER_S
    assert cm["wan_bytes_per_s"] == cost.WAN_BYTES_PER_S
    assert cm["constants_source"] \
        == cost.PROJECTION_MODEL["constants_source"]
    assert "recv_link_bytes" in cm["rule"]


def test_tune_determinism(tmp_path):
    """Same registry + topology → byte-identical TUNE_LAST.json modulo
    the two timestamps (captured_at, provenance.generated_utc)."""
    paths = []
    for i in range(2):
        doc = run_tune(("8",), static_only=True, shortlist_n=1,
                       argv="determinism")
        p = tmp_path / f"tune{i}.json"
        write_tune_evidence(doc, str(p))
        paths.append(p)

    def canon(p):
        d = json.loads(p.read_text())
        d.pop("captured_at")
        d["provenance"].pop("generated_utc")
        return json.dumps(d, sort_keys=True)

    assert canon(paths[0]) == canon(paths[1])


# ---------------------------------------------------------------------------
# end-to-end: measured shortlist + sandwich + evidence
# ---------------------------------------------------------------------------

def test_tune_e2e_cpu_winner_and_sandwich(mesh, tmp_path):
    """The whole loop on the 8-device CPU mesh: enumerate → prune →
    measure (real timed steps, dense brackets interleaved same-session) →
    winner stamped with provenance + topology + the measured≤static
    sandwich — and the winner beats the worst shortlisted candidate on
    measured step time (what makes the measured stage worth its steps)."""
    doc = run_tune(("8",), shortlist_n=2, timed_steps=2, repeats=1,
                   mesh=mesh, trace_dir=str(tmp_path / "prof"),
                   argv="e2e")
    assert doc["ok"] is True
    rows = doc["measured"]["rows"]
    assert len(rows) >= 2
    assert all(r["same_session"] for r in rows)
    w = doc["winner"]
    winner_row = next(r for r in rows if r["candidate"] == w["candidate"])
    worst = max(rows, key=lambda r: r["measured_step_ms"])
    assert winner_row["measured_step_ms"] <= worst["measured_step_ms"]
    # provenance-stamped, topology-stamped, loadable
    assert doc["provenance"]["git_commit"]
    assert w["topology"] == {"world": 8, "slice_size": None}
    rebuilt = grace_from_params(dict(w["grace_params"]))
    assert type(rebuilt.communicator).__name__   # builds verbatim
    # the honesty gate
    s = w["overlap_sandwich"]
    assert s["holds"] and s["violations"] == []
    if s["measured_overlap"] is not None:
        assert s["measured_overlap"] \
            <= s["static_overlap_bound"] + s["slack"]

    # evidence round-trip: TUNE_LAST.json reads back what was stamped
    write_tune_evidence(doc, str(tmp_path / "TUNE_LAST.json"))
    back = json.loads((tmp_path / "TUNE_LAST.json").read_text())
    assert back["winner"]["candidate"] == w["candidate"]
    assert back["winner"]["overlap_sandwich"]["holds"]


def test_graft_tune_cli_static(tmp_path):
    """tools/graft_tune.py --static-only: exit 0, evidence written."""
    tool = _load_tool("graft_tune")
    out = tmp_path / "TUNE_LAST.json"
    rc = tool.main(["--static-only", "--topology", "8",
                    "--shortlist", "1", "--out", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["tool"] == "graft_tune" and doc["static_only"]
    assert doc["static"]["W8"]["counts"]["enumerated"] > 40


# ---------------------------------------------------------------------------
# satellites: lint registry coverage
# ---------------------------------------------------------------------------

def test_variant_configs_registered_for_lint():
    """The tuner-generated variants are first-class lint registry entries
    — what the tuner can emit is never a static-analysis blind spot."""
    from grace_tpu.analysis import AUDIT_CONFIGS
    names = {e["name"] for e in AUDIT_CONFIGS}
    for name, params, _why in variant_audit_entries():
        assert name in names
        entry = next(e for e in AUDIT_CONFIGS if e["name"] == name)
        assert entry["params"] == params
    # and they are part of the enumerated candidate space
    cand_names = {c.name for c in enumerate_candidates(W8)}
    assert {"tune-topk1pct-hier-bucketed",
            "tune-qsgd4-hier-packed"} <= cand_names


def test_variant_config_audits_clean():
    from grace_tpu.analysis import AUDIT_CONFIGS, audit_config
    entry = next(e for e in AUDIT_CONFIGS
                 if e["name"] == "tune-qsgd4-hier-packed")
    findings = audit_config(entry)
    assert [f for f in findings if f.severity == "error"] == []


# ---------------------------------------------------------------------------
# graft-wire tuner integration (ISSUE 19)
# ---------------------------------------------------------------------------

def test_price_candidate_wire_pipeline_discount():
    """The double-buffered ring's declared overlap fraction discounts the
    compressed wire leg — and ONLY that leg: link bytes are
    pipeline-invariant and the dense bracket always rides the flat
    undiscounted psum."""
    from grace_tpu.tuning.cost import price_candidate
    structs = model_structs("toy")
    base = {"compressor": "qsgd", "quantum_num": 7, "use_pallas": False,
            "memory": "none", "communicator": "ring", "fusion": "flat"}
    serial = price_candidate(grace_from_params(base), structs, W8)
    piped = price_candidate(
        grace_from_params({**base, "pipeline": 2}), structs, W8)
    assert serial["wire_pipeline_overlap"] == 0.0
    assert piped["wire_pipeline_overlap"] == 0.25   # 0.5 * (2-1)/2
    # same bytes on the wire — the discount models overlap, not volume
    for k in ("payload_bytes", "ici_bytes", "dcn_bytes", "wire_ms"):
        assert piped[k] == serial[k], k
    assert piped["projected_step_ms"] == pytest.approx(
        0.75 * serial["projected_step_ms"], abs=1e-9)   # record rounds @9dp
    assert piped["dense_projected_step_ms"] == \
        serial["dense_projected_step_ms"]
    # deeper buffering asymptotes at the declared efficiency cap
    p4 = price_candidate(
        grace_from_params({**base, "pipeline": 4}), structs, W8)
    assert p4["wire_pipeline_overlap"] == 0.375     # 0.5 * (4-1)/4


def test_pipelined_variant_candidate_registered_and_audits_clean():
    """The tuner-generated pipelined ring variant is a legal candidate, a
    first-class lint registry entry, and traces clean — flow pass 5's
    pipelined-chain referee is the static backing for the pricing
    discount, so the discounted candidate can never be an audit blind
    spot."""
    from grace_tpu.analysis import AUDIT_CONFIGS, audit_config
    name = "tune-qsgd4-ring-packed-pipelined"
    assert name in {n for n, _, _ in variant_audit_entries()}
    cand = next(c for c in enumerate_candidates(W8) if c.name == name)
    assert cand.params["pipeline"] == 2
    legal, reason, _ = candidate_legal(cand, W8)
    assert legal, reason
    entry = next(e for e in AUDIT_CONFIGS if e["name"] == name)
    findings = audit_config(entry)
    assert [f for f in findings if f.severity == "error"] == []


def test_numeric_gate_shared_scale_2bit():
    """The 2-bit shared-scale accumulator bound: accum_bits=2 at q=1
    holds ONE level sum (payload_sum_max_world=1), so any multi-rank
    topology dies in the numeric stage — the same single constant the
    communicators raise on a live mesh and flow pass 6 flags statically."""
    homo2 = grace_from_params({
        "compressor": "homoqsgd", "quantum_num": 1, "accum_bits": 2,
        "use_pallas": False, "memory": "residual", "communicator": "ring",
        "fusion": "flat"})
    assert homo2.compressor.payload_sum_max_world() == 1
    reason = numeric_verdict(homo2, TuneTopology(world=2))
    assert reason is not None and "payload_sum_max_world=1" in reason
    # the 4-bit sibling survives exactly to its own bound (7) and no
    # further — the registry's world=4 audit override is inside it
    homo4 = grace_from_params({
        "compressor": "homoqsgd", "quantum_num": 1, "accum_bits": 4,
        "use_pallas": False, "memory": "residual", "communicator": "ring",
        "fusion": "flat"})
    assert numeric_verdict(homo4, TuneTopology(world=4)) is None
    r8 = numeric_verdict(homo4, W8)
    assert r8 is not None and "payload_sum_max_world=7" in r8
