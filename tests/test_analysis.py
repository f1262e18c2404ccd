"""graft-lint: the static auditor and repo rule engine (ISSUE 5).

Device-free by construction — everything traces over an AbstractMesh, so
these tests never touch the 8-device fixture. Two halves:

* the full registered compat matrix must audit CLEAN (the CI gate that
  locks the invariants PRs 1-4 established by hand);
* deliberately seeded bad graphs/sources must make each pass and each repo
  rule FIRE — an auditor is only evidence if its alarms are proven live.
"""

import json

import jax
import jax.numpy as jnp
import optax
import pytest
from jax import lax

from grace_tpu import comm
from grace_tpu.analysis import (AUDIT_CONFIGS, audit_config, build_grace,
                                run_repo_rules, trace_fn, trace_update,
                                write_jsonl)
from grace_tpu.analysis.passes import (count_recv_bytes,
                                       pass_bit_exactness,
                                       pass_collective_consistency,
                                       pass_signature_stability,
                                       pass_wire_reconciliation)
from grace_tpu.analysis.rules import registered_markers, repo_root
from grace_tpu.analysis.trace import default_param_structs
from grace_tpu.transform import fusion_payload_nbytes

pytestmark = pytest.mark.analysis

X64 = jax.ShapeDtypeStruct((64,), jnp.float32)


# ---------------------------------------------------------------------------
# the clean gate: the full compat matrix audits green
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("entry", AUDIT_CONFIGS,
                         ids=[e["name"] for e in AUDIT_CONFIGS])
def test_registered_config_audits_clean(entry):
    findings = audit_config(entry)
    assert findings == [], "\n".join(
        f"{f.pass_name}: {f.message}" for f in findings)


def test_registry_covers_compressor_catalog():
    """Every cataloged codec is audited under at least one communicator."""
    import grace_tpu.compressors as C

    audited = {e["params"]["compressor"] for e in AUDIT_CONFIGS}
    catalog = {"none", "fp16", "topk", "randomk", "threshold", "qsgd",
               "terngrad", "signsgd", "signum", "efsignsgd", "onebit",
               "natural", "dgc", "powersgd", "sketch", "u8bit", "adaq",
               "inceptionn",
               # the aggregation-homomorphic family (ISSUE 13)
               "homoqsgd", "countsketch",
               # the sharded-model track (ISSUE 14): ScaleCom-style
               # cyclic local-selection topk
               "cyclictopk"}
    assert catalog <= audited
    # and the catalog names really are the exported classes
    assert len(C.__all__) == 21


def test_incompatible_config_traces_to_a_finding():
    """A triad the communicators reject (topk+Allreduce: unsummable
    payload) surfaces as a trace finding, never an exception — the lint
    run must survive a broken registry entry and report it."""
    findings = audit_config({"name": "bad-triad",
                             "params": {"compressor": "topk",
                                        "memory": "residual",
                                        "communicator": "allreduce"}})
    assert len(findings) == 1 and findings[0].pass_name == "trace"
    assert "summable" in findings[0].message


# ---------------------------------------------------------------------------
# seeded bad graphs: each pass proven live
# ---------------------------------------------------------------------------

def test_cond_divergent_collective_fires():
    """PASS 1: a psum in one cond branch only, predicate derived from
    rank-varying data — the cross-rank deadlock shape."""

    def bad(x):
        return lax.cond(x.sum() > 0,
                        lambda o: lax.psum(o, "data"),
                        lambda o: o * 2.0, x)

    t = trace_fn(bad, [X64], name="bad-cond")
    findings = pass_collective_consistency(t)
    assert len(findings) == 1
    assert findings[0].severity == "error"
    assert "different collective sequences" in findings[0].message


def test_replicated_predicate_cond_passes():
    """The dense-escape shape: branch-divergent collectives are legal when
    the predicate is replicated (every rank takes the same branch)."""

    def ok(x, flag):
        return lax.cond(flag,
                        lambda o: lax.psum(o, "data"),
                        lambda o: o * 2.0, x)

    t = trace_fn(ok, [X64, jax.ShapeDtypeStruct((), jnp.bool_)],
                 varying=[True, False], name="escape-shape")
    assert pass_collective_consistency(t) == []


def test_replication_regained_through_psum():
    """A predicate derived from rank-varying data THROUGH a full-axis psum
    is replicated again — the guard's OR-reduced bad flag shape."""

    def ok(x):
        any_bad = lax.psum(jnp.any(x > 0).astype(jnp.int32), "data") > 0
        return lax.cond(any_bad,
                        lambda o: lax.psum(o, "data"),
                        lambda o: o * 2.0, x)

    t = trace_fn(ok, [X64], name="guard-shape")
    assert pass_collective_consistency(t) == []


def test_float_checksum_psum_fires():
    """PASS 2: bit-pattern words pushed through a float-space psum — the
    PR-3 ±0.0 aliasing bug class, rebuilt on purpose."""

    def bad(x):
        bits = lax.bitcast_convert_type(x, jnp.uint32)
        return lax.psum(bits.astype(jnp.float32), "data")

    t = trace_fn(bad, [X64], name="bad-checksum")
    findings = pass_bit_exactness(t)
    assert len(findings) == 1
    assert "bit-pattern" in findings[0].message


def test_integer_checksum_psum_clean():
    """The sanctioned masked_broadcast shape: integer-space psum of bit
    words, bitcast back to float afterwards — exactly what PR 3 shipped."""

    def ok(x):
        bits = lax.bitcast_convert_type(x, jnp.uint32)
        summed = lax.psum(jnp.where(lax.axis_index("data") == 0, bits,
                                    jnp.zeros_like(bits)), "data")
        return lax.bitcast_convert_type(summed, jnp.float32)

    t = trace_fn(ok, [X64], name="masked-broadcast-shape")
    assert pass_bit_exactness(t) == []


def test_stale_wire_model_fires():
    """PASS 3: a communicator whose recv_wire_bytes drifted from its real
    collective schedule (here: claims half the bytes) is flagged."""
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class StaleModelAllgather(comm.Allgather):
        def recv_wire_bytes(self, payload_nbytes, n_elems, world,
                            vote=False):
            return payload_nbytes * max(0, world - 1) // 2   # drifted

    base = build_grace({"name": "x",
                        "params": {"compressor": "topk",
                                   "compress_ratio": 0.3,
                                   "memory": "residual",
                                   "communicator": "allgather"}})
    grace = dataclasses.replace(base,
                                communicator=StaleModelAllgather())
    t = trace_update(grace, name="stale-model", meta={"grace": grace})
    findings = pass_wire_reconciliation(t)
    assert len(findings) == 1
    assert "drift" in findings[0].message
    # and the honest model on the same trace reconciles
    t2 = trace_update(base, name="fresh-model", meta={"grace": base})
    assert pass_wire_reconciliation(t2) == []


def test_wire_count_matches_model_exactly_for_allgather():
    """Beyond tolerance: the gather schedule has no rounding, so counted
    == modeled to the byte."""
    grace = build_grace({"name": "x",
                         "params": {"compressor": "topk",
                                    "compress_ratio": 0.3,
                                    "memory": "residual",
                                    "communicator": "allgather"}})
    t = trace_update(grace, name="exact")
    counted = count_recv_bytes(t.body, t.axis_name, t.world)
    _, comp_b, n_elems = fusion_payload_nbytes(
        grace.compressor, list(default_param_structs().values()), None)
    assert counted == grace.communicator.recv_wire_bytes(
        comp_b, n_elems, t.world)


def test_signature_leak_fires():
    """PASS 4: a Python float leaking into the carried step counter turns
    the state signature into a moving target (retrace every step)."""
    base = build_grace({"name": "x",
                        "params": {"compressor": "topk",
                                   "compress_ratio": 0.3,
                                   "memory": "residual",
                                   "communicator": "allgather"}})

    class LeakyGrace:
        communicator = base.communicator

        def transform(self, seed=0):
            tx = base.transform(seed)

            def update(updates, state, params=None):
                out, new_state = tx.update(updates, state, params)
                # the seeded bug: a host scalar promotes count to weak f32
                return out, new_state._replace(count=new_state.count + 1.5)

            return optax.GradientTransformation(tx.init, update)

    t = trace_update(LeakyGrace(), name="leaky")
    findings = pass_signature_stability(t)
    assert any("count" in f.message and "fixed point" in f.message
               for f in findings)


def test_host_callback_fires():
    """PASS 4: jax.debug.print inside the compiled step is a host sync."""

    def bad(x):
        jax.debug.print("sum {}", x.sum())
        return lax.psum(x, "data")

    t = trace_fn(bad, [X64], name="bad-callback")
    findings = pass_signature_stability(t)
    assert len(findings) == 1 and "host callback" in findings[0].message


# ---------------------------------------------------------------------------
# satellite: recv_wire_bytes W=1 / W=2 edge cases
# ---------------------------------------------------------------------------

_COMMUNICATORS = [comm.Allreduce, comm.Allgather, comm.Broadcast,
                  comm.SignAllreduce, comm.TwoShotAllreduce,
                  comm.RingAllreduce, comm.HierarchicalAllreduce,
                  comm.Identity]


@pytest.mark.parametrize("cls", _COMMUNICATORS,
                         ids=[c.__name__ for c in _COMMUNICATORS])
def test_recv_wire_bytes_degenerate_worlds(cls):
    """W=1 (ring degenerates to zero hops) must cost 0 bytes — and never
    divide by zero or go negative; W=2 must be positive for every real
    communicator and bounded by the dense 2-rank exchange."""
    c = cls()
    payload, n = 4096, 1024
    for vote in (False, True):
        assert c.recv_wire_bytes(payload, n, 1, vote=vote) == 0
    two = c.recv_wire_bytes(payload, n, 2)
    assert two >= 0
    if cls is comm.Identity:
        assert two == 0
    else:
        assert 0 < two <= 2 * payload + 4 * n   # ≤ dense-ish upper bound
    # W=0 is nonsensical but must price to 0, not negative: the tuner
    # enumerates degenerate meshes, and a negative byte price would rank
    # the broken config best (the ring-family 2·p·(W-1)/W formulas used
    # to return -2p here before the max(0, W-1) clamp).
    for vote in (False, True):
        assert c.recv_wire_bytes(payload, n, 0, vote=vote) == 0
        lb = c.recv_link_bytes(payload, n, 0, vote=vote)
        assert lb.ici == lb.dcn == 0


def test_hier_slice1_degenerate_worlds():
    """HierarchicalAllreduce(slice_size=1) — every rank its own slice, the
    tuner's most degenerate generated mesh: W<=1 prices to 0 on both links,
    and at W>1 the schedule is pure cross-slice exchange ((W-1)·payload
    partials, no intra-slice hops) — all DCN once a multi-slice topology
    says the axis crosses."""
    from grace_tpu.core import Topology

    c = comm.HierarchicalAllreduce(slice_size=1)
    payload, n = 4096, 1024
    for w in (0, 1):
        for vote in (False, True):
            assert c.recv_wire_bytes(payload, n, w, vote=vote) == 0
            lb = c.recv_link_bytes(payload, n, w,
                                   topology=Topology(slice_size=1),
                                   vote=vote)
            assert lb.ici == lb.dcn == 0
    # W=2, slice_size=1: no intra hops (S-1 == 0), one cross-slice partial.
    assert c.recv_wire_bytes(payload, n, 2) == payload
    lb = c.recv_link_bytes(payload, n, 2, topology=Topology(slice_size=1))
    assert (lb.ici, lb.dcn) == (0, payload)


def test_ring_wire_model_monotone_in_world():
    """2·p·(W-1)/W is increasing and flat-bounded by 2·p — the whole point
    of the ring; a regression here corrupts every bench projection."""
    c = comm.RingAllreduce()
    vals = [c.recv_wire_bytes(8192, 2048, w) for w in (1, 2, 4, 8, 64)]
    assert vals[0] == 0
    assert all(a < b for a, b in zip(vals, vals[1:]))
    assert vals[-1] < 2 * 8192


# ---------------------------------------------------------------------------
# per-link (ici, dcn) wire model — ISSUE 6 prerequisite surgery
# ---------------------------------------------------------------------------

# The PRE-refactor scalar formulas, hardcoded: recv_wire_bytes is now the
# sum of the per-link split, and this pins that the refactor moved ZERO
# bytes — bit-identical for every communicator, world, and vote flag.
_OLD_SCALAR = {
    comm.Allreduce: lambda p, n, w, vote: (
        2 * 2 * n * (w - 1) // max(1, w) if vote
        else 2 * p * (w - 1) // max(1, w)),
    comm.Allgather: lambda p, n, w, vote: p * max(0, w - 1),
    comm.Broadcast: lambda p, n, w, vote: p * max(0, w - 1),
    comm.SignAllreduce: lambda p, n, w, vote:
        2 * 2 * n * (w - 1) // max(1, w),
    comm.TwoShotAllreduce: lambda p, n, w, vote:
        2 * p * (w - 1) // max(1, w),
    comm.RingAllreduce: lambda p, n, w, vote:
        2 * p * (w - 1) // max(1, w),
    # Default-constructed (slice_size=None): one slice, so the two-level
    # schedule — and therefore the model — collapses to the flat ring.
    comm.HierarchicalAllreduce: lambda p, n, w, vote:
        2 * p * (w - 1) // max(1, w),
    comm.Identity: lambda p, n, w, vote: 0,
}


@pytest.mark.parametrize("cls", _COMMUNICATORS,
                         ids=[c.__name__ for c in _COMMUNICATORS])
def test_recv_link_bytes_sums_to_old_scalar_model(cls):
    from grace_tpu.core import Topology

    c = cls()
    payload, n = 8192, 2048
    topologies = (None, Topology(), Topology(slice_size=4),
                  Topology(slice_size=8), Topology(slice_size=1024))
    for w in (1, 2, 4, 8, 64, 256):
        for vote in (False, True):
            old = _OLD_SCALAR[cls](payload, n, w, vote)
            assert c.recv_wire_bytes(payload, n, w, vote=vote) == old
            for topo in topologies:
                lb = c.recv_link_bytes(payload, n, w, topology=topo,
                                       vote=vote)
                assert lb.ici + lb.dcn == old == lb.total, \
                    (cls.__name__, w, vote, topo, lb)


@pytest.mark.parametrize("cls", _COMMUNICATORS,
                         ids=[c.__name__ for c in _COMMUNICATORS])
def test_recv_link_bytes_split_semantics(cls):
    """Flat schedules: all-ICI within one slice, all-DCN once the axis
    crosses a slice boundary (the critical rank's incoming link)."""
    from grace_tpu.core import Topology

    c = cls()
    payload, n, w = 8192, 2048, 64
    inside = c.recv_link_bytes(payload, n, w,
                               topology=Topology(slice_size=64))
    assert inside.dcn == 0
    crossing = c.recv_link_bytes(payload, n, w,
                                 topology=Topology(slice_size=8))
    assert crossing.ici == 0
    assert crossing.dcn == inside.ici         # same bytes, other link
    # default topology is single-slice: everything ICI
    assert c.recv_link_bytes(payload, n, w).dcn == 0


def test_topology_descriptor():
    from grace_tpu.core import SINGLE_SLICE, Topology

    assert not SINGLE_SLICE.crosses_dcn(10 ** 6)
    assert Topology(slice_size=8).crosses_dcn(9)
    assert not Topology(slice_size=8).crosses_dcn(8)
    with pytest.raises(ValueError):
        Topology(slice_size=0)
    # CPU / simulated devices: always one slice
    assert Topology.detect().slice_size is None


# ---------------------------------------------------------------------------
# repo rule engine
# ---------------------------------------------------------------------------

def test_repo_rules_clean():
    findings = run_repo_rules()
    assert findings == [], "\n".join(f"{f.config}: {f.message}"
                                     for f in findings)


def test_rule_fires_on_undeclared_compressor():
    src = ("from grace_tpu.core import Compressor\n"
           "class ShinyNewCompressor(Compressor):\n"
           "    ratio: float = 0.5\n")
    findings = run_repo_rules(
        rules=("compressor-capabilities",),
        sources={"grace_tpu/compressors/shiny.py": src})
    mine = [f for f in findings if "ShinyNewCompressor" in f.message]
    assert len(mine) == 1
    assert "payload_algebra" in mine[0].message


def test_rule_fires_on_bad_fields_reducer():
    src = ('FIELDS = (("grad_norm", "mean"), ("mystery", "median"))\n')
    findings = run_repo_rules(
        rules=("telemetry-fields-reducer",),
        sources={"grace_tpu/telemetry/state.py": src})
    assert len(findings) == 1 and "median" in findings[0].message


def test_rule_fires_on_unregistered_marker():
    src = ("import pytest\n"
           "@pytest.mark.totally_new_marker\n"
           "def test_x():\n    pass\n")
    findings = run_repo_rules(
        rules=("pytest-marker-registration",),
        sources={"tests/test_fake_marker.py": src})
    assert any(f.details and dict(f.details).get("marker")
               == "totally_new_marker" for f in findings)


def test_analysis_marker_is_registered():
    assert "analysis" in registered_markers(repo_root())


# ---------------------------------------------------------------------------
# reporting: JSONL round-trips through tools/telemetry_report.py
# ---------------------------------------------------------------------------

def test_jsonl_findings_render_in_telemetry_report(tmp_path):
    import os
    import sys
    sys.path.insert(0, os.path.join(repo_root(), "tools"))
    import telemetry_report

    findings = audit_config({"name": "bad-triad",
                             "params": {"compressor": "topk",
                                        "memory": "residual",
                                        "communicator": "allreduce"}})
    path = tmp_path / "lint.jsonl"
    write_jsonl(findings, str(path), provenance={"tool": "graft_lint"})
    provenance, records, events = telemetry_report.load(str(path))
    assert provenance == {"tool": "graft_lint"}
    assert records == []
    assert [e["event"] for e in events] == ["lint_finding"]
    rendered = telemetry_report.render(provenance, records, events)
    assert "lint_finding" in rendered


def test_cli_rules_only_exits_zero(capsys):
    import os
    import sys
    sys.path.insert(0, os.path.join(repo_root(), "tools"))
    import graft_lint

    assert graft_lint.main(["--rules-only"]) == 0
    out = capsys.readouterr().out
    assert "0 error(s)" in out


def test_findings_are_json_serializable():
    findings = audit_config({"name": "bad-triad",
                             "params": {"compressor": "topk",
                                        "memory": "residual",
                                        "communicator": "allreduce"}})
    doc = json.dumps([f.as_dict() for f in findings])
    assert "bad-triad" in doc
