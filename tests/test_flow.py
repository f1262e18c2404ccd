"""graft-flow: the dependence-graph layer and its three passes (ISSUE 9).

Same contract as test_analysis.py: the registered matrix must audit CLEAN
with the new passes enabled (covered there via AUDIT_CONFIGS — this file
adds the numbers those audits are built on), and every new alarm must be
proven LIVE on a deliberately seeded bad graph: a serialized bucket chain,
a W=4096 fp16 hop-sum, a hand-rolled bf16 vote past 256 ranks, an
undersized index dtype, a broken bit-packer, a replicated O(W) buffer, and
a state traced under a different config than the one audited.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import optax
import pytest
from jax import lax

from grace_tpu.analysis import (build_depgraph, build_grace, footprint_model,
                                footprint_report, overlap_summary,
                                pass_memory_footprint, pass_numeric_safety,
                                pass_overlap_schedulability, trace_fn,
                                trace_update)
from grace_tpu.analysis import flow
from grace_tpu.analysis.configs import AUDIT_CONFIGS, audit_config
from grace_tpu.comm import vote_exact_max_world
from grace_tpu.telemetry.scopes import STAGE_EXCHANGE, trace_stage

pytestmark = pytest.mark.analysis

X64 = jax.ShapeDtypeStruct((64,), jnp.float32)


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_under_test",
        os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                     f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _exchange(fn):
    """Wrap a traced body in the exchange stage scope, the vocabulary the
    chain counting keys on."""
    def wrapped(*args):
        with trace_stage(STAGE_EXCHANGE):
            return fn(*args)
    return wrapped


def _topk_grace(**extra):
    params = {"compressor": "topk", "compress_ratio": 0.3,
              "memory": "residual", "communicator": "allgather", **extra}
    return build_grace({"name": "x", "params": params})


# ---------------------------------------------------------------------------
# the dependence graph itself
# ---------------------------------------------------------------------------

def test_depgraph_ancestor_closure():
    """c = psum(a); d = c + b: the psum is an ancestor of the add, the
    add is not an ancestor of the psum, and the add's gradient roots
    cover both inputs while the psum's cover only the first."""

    def f(a, b):
        c = lax.psum(a * 2.0, "data")
        return c + b * 3.0

    t = trace_fn(f, [X64, X64], name="dep")
    g = build_depgraph(t)
    colls = [n for n in g.nodes if n.collective]
    assert len(colls) == 1
    psum = colls[0]
    adds = [n for n in g.nodes if n.prim == "add"]
    assert adds, "no add node"
    final = adds[-1]
    assert g.is_ancestor(psum.idx, final.idx)
    assert not g.is_ancestor(final.idx, psum.idx)
    assert g.n_grad_roots == 2
    assert psum.roots == 0b01                 # only arg a
    assert final.roots == 0b11                # both args


def test_depgraph_flattens_cond_branches():
    """Equations inside cond branches join the global graph and the cond's
    outputs carry their dependence."""

    def f(x, flag):
        y = lax.cond(flag, lambda o: lax.psum(o, "data"),
                     lambda o: o * 2.0, x)
        return y + 1.0

    t = trace_fn(f, [X64, jax.ShapeDtypeStruct((), jnp.bool_)], name="cond")
    g = build_depgraph(t)
    colls = [n for n in g.nodes if n.collective]
    assert len(colls) == 1                    # the branch psum is a node
    final_add = [n for n in g.nodes if n.prim == "add"][-1]
    assert g.is_ancestor(colls[0].idx, final_add.idx)


# ---------------------------------------------------------------------------
# pass 5: overlap schedulability
# ---------------------------------------------------------------------------

def test_serialized_bucket_graph_fires():
    """THE seeded-bad graph: bucket 2's exchange consumes bucket 1's
    result, so the two promised chains collapse into one serialized
    sequence — the scheduler can never overlap them."""

    def serialized(a, b):
        s1 = lax.psum(a * 2.0, "data")
        return lax.psum(s1 + b, "data")

    t = trace_fn(_exchange(serialized), [X64, X64], name="serialized",
                 meta={"expected_chains": 2})
    s = overlap_summary(t)
    assert s["exchange_collectives"] == 2
    assert s["independent_chains"] == 1
    findings = pass_overlap_schedulability(t)
    assert len(findings) == 1
    assert findings[0].severity == "error"
    assert "serialization point" in findings[0].message
    assert findings[0].stage == STAGE_EXCHANGE


def test_independent_bucket_graph_clean():
    def parallel(a, b):
        return lax.psum(a * 2.0, "data") + lax.psum(b * 3.0, "data")

    t = trace_fn(_exchange(parallel), [X64, X64], name="parallel",
                 meta={"expected_chains": 2})
    assert overlap_summary(t)["independent_chains"] == 2
    assert pass_overlap_schedulability(t) == []


def test_static_overlap_bound_zero_when_everything_chains():
    """All compute feeds the collective or consumes its result: nothing is
    schedulable under the exchange, bound == 0."""

    def chained(x):
        y = x * 2.0 + 1.0
        s = lax.psum(y, "data")
        return s * 3.0

    t = trace_fn(chained, [X64], name="chained")
    assert overlap_summary(t)["static_overlap_bound"] == 0.0


def test_static_overlap_bound_positive_with_independent_compute():
    """A second, data-independent compute chain big enough to hide the
    collective pushes the bound to 1."""

    def overlappable(x, z):
        s = lax.psum(x, "data")
        busy = jnp.tanh(z * 2.0) + jnp.tanh(z * 3.0)   # independent of s
        return s, busy

    t = trace_fn(overlappable, [X64, X64], name="overlappable")
    s = overlap_summary(t)
    assert s["static_overlap_bound"] == 1.0
    per = s["per_collective"][0]
    assert per["independent_compute_bytes"] > 0


def test_measured_overlap_exceeding_static_bound_fires():
    """graft-prof reporting more overlap than the dataflow permits means
    the attribution is lying — flagged, with both numbers emitted."""

    def chained(x):
        return lax.psum(x * 2.0, "data") * 3.0

    t = trace_fn(chained, [X64], name="lying-profile",
                 meta={"measured_overlap": 0.8})
    findings = pass_overlap_schedulability(t)
    assert len(findings) == 1
    d = dict(findings[0].details)
    assert d["measured_overlap"] == 0.8
    assert d["static_overlap_bound"] == 0.0
    # measured within the bound is fine
    t2 = trace_fn(chained, [X64], name="honest-profile",
                  meta={"measured_overlap": 0.0})
    assert pass_overlap_schedulability(t2) == []


def test_bucketed_registry_config_exposes_two_chains():
    """The registered fusion=1024 config: the bucketing plan splits the
    default params into 2 buckets and the traced graph must expose (at
    least) 2 independent compress→exchange chains — the contract ROADMAP
    item 2's chunked bucket scheduling builds on."""
    entry = next(e for e in AUDIT_CONFIGS
                 if e["name"] == "topk-allgather-bucketed")
    grace = build_grace(entry)
    t = trace_update(grace, name=entry["name"], meta={"grace": grace})
    s = overlap_summary(t)
    assert flow._expected_chains(t) == 2
    # EXACTLY the plan's K (chain heads group by gradient-root set, so the
    # two-tensor top-k payload is one chain per bucket, not two).
    assert s["independent_chains"] == 2
    assert pass_overlap_schedulability(t) == []


def test_pipelined_ring_registry_config_exposes_pipeline_chains():
    """ACCEPTANCE (ISSUE 19): the registered double-buffered packed ring
    (pipeline=2) promises — and the traced graph exposes — 2 independent
    collective chains, one per grace/pipeline/<p> segment; the serial
    twin exposes 1. This chain count is the static referee behind the
    tuner's wire_pipeline discount."""
    entry = next(e for e in AUDIT_CONFIGS
                 if e["name"] == "qsgd2-ring-packed-pipelined")
    grace = build_grace(entry)
    assert grace.communicator.pipeline == 2
    t = trace_update(grace, name=entry["name"], meta={"grace": grace})
    assert flow._expected_chains(t) == 2
    assert overlap_summary(t)["independent_chains"] == 2
    assert pass_overlap_schedulability(t) == []
    # the serial twin of the same codec exposes a single chain
    serial = build_grace({"name": "serial",
                          "params": {**dict(entry["params"]),
                                     "pipeline": 1}})
    t1 = trace_update(serial, name="serial", meta={"grace": serial})
    assert overlap_summary(t1)["independent_chains"] == 1


# ---------------------------------------------------------------------------
# pass 6: numeric-range safety
# ---------------------------------------------------------------------------

def test_fp16_hop_sum_overflows_at_large_world():
    """THE seeded-bad graph: a W=4096 fp16 payload sum saturates the 65504
    cliff (4096 terms x 256 magnitude budget >> finfo(f16).max) with no
    NaN for the guard to see."""

    def f16sum(x):
        return lax.psum(x.astype(jnp.float16), "data")

    t = trace_fn(f16sum, [X64], world=4096, name="f16-hop-4096")
    findings = pass_numeric_safety(t)
    assert len(findings) == 1
    d = dict(findings[0].details)
    assert d["dtype"] == "float16" and d["terms"] == 4096
    assert "overflows to inf" in findings[0].message
    # same graph at world 8: 8 terms, comfortably inside the budget
    assert pass_numeric_safety(
        trace_fn(f16sum, [X64], world=8, name="f16-hop-8")) == []
    # bfloat16 has no overflow cliff: clean at any audited W
    assert pass_numeric_safety(trace_fn(
        lambda x: lax.psum(x.astype(jnp.bfloat16), "data"),
        [X64], world=4096, name="bf16-hop-4096")) == []


def test_safe_sum_terms_derivation():
    assert flow.safe_sum_terms(jnp.float16) == int(65504 / 256)
    assert flow.safe_sum_terms(jnp.bfloat16) > 10 ** 30
    assert flow.safe_sum_terms(jnp.int32) is None


def test_vote_exact_max_world_rederives_256_from_first_principles():
    """The bf16-vote 256 bound is not folklore: p explicit mantissa bits
    represent integers exactly up to 2^(p+1), and a W-rank vote tally
    lives in [-W, W]."""
    assert vote_exact_max_world("bfloat16") \
        == 2 ** (jnp.finfo(jnp.bfloat16).nmant + 1) == 256
    assert vote_exact_max_world("float16") == 2048
    assert vote_exact_max_world("float32") == 2 ** 24
    with pytest.raises(TypeError):
        vote_exact_max_world(jnp.int32)


def test_runtime_vote_guard_reads_the_same_constant():
    """The comm-level runtime check and the static pass read ONE constant:
    tracing the psum-vote communicator past the bound raises with the
    function's name in the message (surfaced as a trace finding by the
    registry machinery)."""
    findings = audit_config(
        {"name": "vote-512",
         "params": {"compressor": "signsgd", "memory": "none",
                    "communicator": "sign_allreduce"}}, world=512)
    assert len(findings) == 1 and findings[0].pass_name == "trace"
    assert "vote_exact_max_world" in findings[0].message


def test_hand_rolled_vote_psum_past_bound_fires_statically():
    """A vote psum that bypasses the communicator's runtime guard (the
    hand-rolled case) is still caught by the static pass via the
    psum_vote trace scope."""

    def vote(x):
        with trace_stage(f"{STAGE_EXCHANGE}/psum_vote"):
            return lax.psum(x.astype(jnp.bfloat16), "data")

    t = trace_fn(vote, [X64], world=512, name="vote-512")
    findings = pass_numeric_safety(t)
    assert len(findings) == 1
    assert dict(findings[0].details)["exact_max_world"] == 256
    assert pass_numeric_safety(
        trace_fn(vote, [X64], world=256, name="vote-256")) == []


def test_undersized_index_dtype_fires():
    """A selection codec shipping int16 indices for a 100k-element fused
    leaf: positions past 32767 wrap on decode."""
    from grace_tpu.core import Compressor

    @dataclasses.dataclass(frozen=True)
    class NarrowTopK(Compressor):
        summable_payload = False
        supports_hop_requant = False

        def compress(self, x, state, rng):
            k = 16
            idx = jnp.argsort(-jnp.abs(x))[:k].astype(jnp.int16)
            return (x[:k], idx), (x.size, x.shape, x.dtype), state

        def decompress(self, payload, ctx):
            values, idx = payload
            n, shape, dtype = ctx
            return jnp.zeros((n,), dtype).at[idx.astype(jnp.int32)].set(
                values).reshape(shape)

    base = _topk_grace()
    grace = dataclasses.replace(base, compressor=NarrowTopK())
    big = {"w": jax.ShapeDtypeStruct((100_000,), jnp.float32)}
    t = trace_update(grace, params=big, name="narrow-idx",
                     meta={"grace": grace, "param_structs": big})
    findings = pass_numeric_safety(t)
    assert len(findings) == 1
    assert "int16 index payload" in findings[0].message
    # the real TopK (int32 indices) on the same leaf is clean
    t2 = trace_update(base, params=big, name="wide-idx",
                      meta={"grace": base, "param_structs": big})
    assert pass_numeric_safety(t2) == []


def test_broken_bit_packer_fires():
    """Injected 3-codes-per-byte 'pack_bits': in-range codes truncate."""

    def bad_pack(bits):
        n = bits.shape[0]
        nbytes = -(-n // 3)                       # wrong lane count
        padded = jnp.zeros((nbytes * 3,), jnp.uint8).at[:n].set(
            bits.astype(jnp.uint8))
        return jnp.sum(padded.reshape(nbytes, 3), axis=1, dtype=jnp.uint8)

    from grace_tpu.ops.packing import unpack_bits

    grace = build_grace({"name": "x",
                         "params": {"compressor": "signsgd",
                                    "memory": "none",
                                    "communicator": "allgather"}})
    t = trace_update(grace, name="bad-pack", meta={"grace": grace})
    findings = flow._packing_findings(
        t, pack_fns=((1, bad_pack, unpack_bits),))
    assert findings and all("ops/packing" in f.message for f in findings)
    # the shipped packers hold their declared widths
    assert flow._packing_findings(t) == []


@pytest.mark.parametrize("width", [2, 3, 4])
def test_bad_packer_fires_at_every_subbyte_width(width):
    """The pass-6 packer audit is live at the NEW widths too: an injected
    packer that truncates the top bit of ``width``-bit codes (declares
    the width, packs width-1) corrupts in-range codes and must fire for
    each of 2/3/4 — the widths QSGD/homoqsgd select via pack_width."""
    from grace_tpu.ops.packing import pack_widths

    good = {w: (p, u) for w, p, u in pack_widths()}
    narrow_pack, _ = good[width - 1]
    _, wide_unpack = good[width]

    def truncating_pack(codes):
        # drop the MSB, pack at width-1: ceil(n*(width-1)/8) bytes — both
        # the byte-count contract and the round-trip break
        return narrow_pack(codes & jnp.uint8((1 << (width - 1)) - 1))

    grace = build_grace({"name": "x",
                         "params": {"compressor": "qsgd", "quantum_num": 7,
                                    "memory": "none",
                                    "communicator": "allgather"}})
    t = trace_update(grace, name=f"bad-{width}bit", meta={"grace": grace})
    findings = flow._packing_findings(
        t, pack_fns=((width, truncating_pack, wide_unpack),))
    assert findings
    assert all("ops/packing" in f.message and f"{width}-bit" in f.message
               for f in findings)


def test_packing_check_only_runs_for_packed_payloads():
    """fp16 ships no sub-byte payload — no packing findings regardless."""
    grace = build_grace({"name": "x", "params": {"compressor": "fp16",
                                                 "memory": "none",
                                                 "communicator":
                                                 "allreduce"}})
    t = trace_update(grace, name="fp16", meta={"grace": grace})

    def exploding_pack(bits):                     # must never be called
        raise AssertionError("packing check ran for an unpacked codec")

    assert flow._packing_findings(
        t, pack_fns=((1, exploding_pack, exploding_pack),)) == []


# ---------------------------------------------------------------------------
# pass 7: HBM footprint
# ---------------------------------------------------------------------------

def test_footprint_model_matches_live_world8_state(mesh):
    """ACCEPTANCE: the pass's model equals grace_state_footprint on the
    live world=8 chaos_smoke-shaped state (topk + residual + escape +
    telemetry, sharded over the 8-device mesh)."""
    from grace_tpu.profiling import grace_state_footprint
    from grace_tpu.train import init_train_state

    grace = build_grace({"name": "smoke",
                         "params": {"compressor": "topk",
                                    "compress_ratio": 0.3,
                                    "memory": "residual",
                                    "communicator": "allgather",
                                    "escape": "fp16", "telemetry": 32}})
    tx = optax.chain(grace.transform(seed=0), optax.sgd(0.1))
    params = {"w": jnp.zeros((32, 16)), "b": jnp.zeros((16,))}
    state = init_train_state(params, tx, mesh)
    live = grace_state_footprint(state.opt_state)
    model = footprint_model(grace, params, world=8)
    for key in ("mem_bytes", "comp_bytes", "telem_bytes", "total_bytes"):
        assert live[key] == model[key], key


def test_footprint_report_groups_match_the_model():
    from grace_tpu.analysis.trace import default_param_structs

    grace = _topk_grace(telemetry=16)
    t = trace_update(grace, name="fp", meta={"grace": grace})
    rep = footprint_report(t)
    model = footprint_model(grace, default_param_structs())
    for key in ("mem_bytes", "comp_bytes", "telem_bytes"):
        assert rep[key] == model[key], key
    assert rep["wire_peak_bytes"] > 0            # the gathered (W, k) stack
    assert rep["wire_total_bytes"] >= rep["wire_peak_bytes"]
    assert rep["n_collectives"] >= 2             # values + indices gathers


def test_state_traced_under_different_config_fires():
    ga = _topk_grace(telemetry=4)
    gb = _topk_grace(telemetry=64)
    t = trace_update(ga, name="drifted", meta={"grace": gb})
    findings = pass_memory_footprint(t)
    assert len(findings) == 1
    assert "different" in findings[0].message
    assert dict(findings[0].details)["component"] == "telem_bytes"
    assert pass_memory_footprint(
        trace_update(ga, name="same", meta={"grace": ga})) == []


def test_replicated_o_w_buffer_fires():
    """THE seeded-bad graph: a replicated (P()) state buffer shaped (W,)
    — O(W) HBM per rank on every rank."""
    base = _topk_grace()
    world = 8

    class OWGrace:
        communicator = base.communicator
        compressor = base.compressor
        fusion = None

        def transform(self, seed=0):
            tx = base.transform(seed)

            def init(params):
                return tx.init(params)._replace(
                    audit=jnp.zeros((world,), jnp.float32))

            def update(updates, state, params=None):
                out, new = tx.update(updates, state, params)
                return out, new._replace(audit=state.audit)

            return optax.GradientTransformation(init, update)

    t = trace_update(OWGrace(), name="o-w-buffer")
    findings = pass_memory_footprint(t)
    assert len(findings) == 1
    assert "O(W)" in findings[0].message or "O(W²)" in findings[0].message
    assert dict(findings[0].details)["path"] == "audit"


def test_replicated_state_scalars_do_not_fire():
    grace = _topk_grace()
    t = trace_update(grace, name="plain")
    assert [p for p, _ in t.state_replicated]    # count/rng/fallback exist
    assert pass_memory_footprint(t) == []


# ---------------------------------------------------------------------------
# CLI + evidence + smoke wiring
# ---------------------------------------------------------------------------

def test_graft_lint_all_configs_end_to_end(tmp_path, capsys):
    """CI gate: the full registry, all ten passes, exit 0 — a pass
    regression fails pytest, not just the smoke. Evidence lands at the
    given path with per-pass counts for every pass that ran."""
    graft_lint = _load_tool("graft_lint")
    evidence = tmp_path / "LINT_LAST.json"
    assert graft_lint.main(["--all-configs",
                            "--evidence", str(evidence)]) == 0
    out = capsys.readouterr().out
    assert "0 error(s)" in out
    doc = json.loads(evidence.read_text())
    assert doc["errors"] == 0
    assert set(doc["passes_run"]) == {
        "collective_consistency", "bit_exactness", "wire_reconciliation",
        "signature_stability", "overlap_schedulability", "numeric_safety",
        "memory_footprint", "rng_lineage", "rollback_coverage",
        "replication_contract"}
    assert all(v == 0 for v in doc["pass_counts"].values())
    assert doc["configs_audited"] == len(AUDIT_CONFIGS)
    # The static half of the overlap sandwich rides the evidence: every
    # bucketed (fusion=<int>) update-mode config records its bound and its
    # chain counts, and the executor delivers exactly the promised K.
    assert "topk-allgather-bucketed" in doc["overlap_bounds"]
    assert "qsgd4-ring-packed-bucketed" in doc["overlap_bounds"]
    for rep in doc["overlap_bounds"].values():
        assert rep["independent_chains"] == rep["expected_chains"]
        assert rep["static_overlap_bound"] is not None


def test_graft_lint_passes_selection(tmp_path, capsys):
    graft_lint = _load_tool("graft_lint")
    assert graft_lint.main(["--config", "fp16-allreduce", "--no-rules",
                            "--passes", "numeric_safety"]) == 0
    assert graft_lint.main(["--passes", "not_a_pass"]) == 2


def test_new_finding_kinds_render_in_telemetry_report(tmp_path):
    """The unified-timeline satellite: schedulability/numeric/footprint
    findings written as lint_finding events render with their stage
    attribution, like guard/consensus events do."""
    from grace_tpu.analysis import write_jsonl

    def serialized(a, b):
        s1 = lax.psum(a * 2.0, "data")
        return lax.psum(s1 + b, "data")

    t = trace_fn(_exchange(serialized), [X64, X64], name="ser",
                 meta={"expected_chains": 2, "measured_overlap": 0.9})
    findings = pass_overlap_schedulability(t)
    t16 = trace_fn(lambda x: lax.psum(x.astype(jnp.float16), "data"),
                   [X64], world=4096, name="f16")
    findings += pass_numeric_safety(t16)
    ga, gb = _topk_grace(telemetry=4), _topk_grace(telemetry=64)
    findings += pass_memory_footprint(
        trace_update(ga, name="drift", meta={"grace": gb}))
    assert {f.pass_name for f in findings} == {
        "overlap_schedulability", "numeric_safety", "memory_footprint"}

    path = tmp_path / "lint.jsonl"
    write_jsonl(findings, str(path), provenance={"tool": "graft_lint"})
    telemetry_report = _load_tool("telemetry_report")
    provenance, records, events = telemetry_report.load(str(path))
    rendered = telemetry_report.render(provenance, records, events)
    assert "lint_finding" in rendered
    for kind in ("overlap_schedulability", "numeric_safety",
                 "memory_footprint"):
        assert kind in rendered
    assert f"[{STAGE_EXCHANGE}]" in rendered      # stage attribution
    doc = telemetry_report.build_doc(provenance, records, events)
    assert len(doc["lint_findings"]) == len(findings)
    assert doc["guard_events"] == []


def test_chaos_smoke_lint_gate_runs_flow_passes(tmp_path, monkeypatch):
    """chaos_smoke --lint audits its own config with the graft-flow AND
    graft-sound passes before any step runs (clean here — the artifact
    stays free of lint_finding events)."""
    import grace_tpu.analysis as analysis
    smoke = _load_tool("chaos_smoke")
    audited = {}
    real_audit = analysis.audit_config

    def spy(entry, *a, **kw):
        audited["passes"] = tuple(entry["passes"])
        return real_audit(entry, *a, **kw)

    # chaos_smoke imports audit_config at gate time, so the module
    # attribute is the seam.
    monkeypatch.setattr(analysis, "audit_config", spy)
    out = tmp_path / "smoke.jsonl"
    rc = smoke.main(["--steps", "8", "--nan-prob", "1.0", "--batch", "16",
                     "--fallback-after", "2", "--fallback-steps", "4",
                     "--lint", "--telemetry-out", str(out),
                     "--telemetry-every", "4"])
    assert rc == 0
    # the smoke's own guarded config must prove its stateful semantics,
    # not just its collective/flow properties
    assert {"rng_lineage", "rollback_coverage",
            "replication_contract"} <= set(audited["passes"])
    # clean gate: no lint_finding events in the artifact
    lines = [json.loads(l) for l in out.read_text().splitlines()]
    assert not [l for l in lines if l.get("event") == "lint_finding"]
