"""graft-shard (ISSUE 14): compressed reduce-scatter on 2-D dp×fsdp meshes
with per-leaf codec routing.

Covers the acceptance criteria end to end:

* bit-identity — ``rscatter`` with exact codecs (none/fp16) matches the
  1-D allgather path bitwise on integer grads; the homomorphic codec
  matches the ring's payload-space summation bitwise (same stage-1 shard
  encode, same integer sums); the requant path is bit-identical to
  TwoShot's single re-encode;
* degenerate collapse — a W×1 fsdp-degenerate mesh reproduces today's
  1-D behavior bitwise, and every registered config's state structure is
  unchanged under a 2-D MeshSpec;
* 2-D lint seeding — the per-axis replication analysis blesses the legal
  fsdp-varying-predicate/dp-collective shape and condemns a seeded
  WRONG-AXIS replication bug (predicate psummed over fsdp, still
  dp-varying, gating a dp-collective cond) live;
* routing — per-leaf codec routing resolves the right triads, prices the
  wire as the sum of per-leaf models, and refuses non-per-leaf fusion;
* the transformer track wins on the model — the routed rscatter BERT
  config's per-link xslice projection is >1.0× vs dense at W≥64 where
  the committed flat BERT row (bert_powersgd_r4) is the 0.80× before-
  picture.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from grace_tpu import comm, grace_from_params
from grace_tpu.parallel import data_parallel_mesh, make_mesh, shard_map
from grace_tpu.transform import MeshSpec, partition_specs

pytestmark = pytest.mark.shard

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _update_once(params_cfg, grads, mesh, in_spec=P("data")):
    """One grace_transform update on integer-valued grads inside
    shard_map; returns the aggregated updates."""
    g = grace_from_params(params_cfg)
    tx = g.transform(0)

    def body(gr):
        state = tx.init(gr)
        out, _ = tx.update(gr, state, None)
        return out

    f = shard_map(body, mesh=mesh, in_specs=(in_spec,),
                  out_specs=in_spec, check_vma=False)
    return np.asarray(jax.jit(f)(grads))


@pytest.fixture(scope="module")
def int_grads():
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.integers(-8, 8, (8, 64)), jnp.float32)


# ---------------------------------------------------------------------------
# bit-identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("compressor", ["none", "fp16"])
def test_rscatter_exact_bit_identical_to_allgather(mesh, int_grads,
                                                   compressor):
    """Exact codecs: payload-space sum then decode == per-rank decode
    then sum, bitwise on integer grads (small ints are exact in fp16)."""
    a = _update_once({"compressor": compressor, "memory": "none",
                      "communicator": "rscatter", "fusion": "flat"},
                     int_grads, mesh)
    b = _update_once({"compressor": compressor, "memory": "none",
                      "communicator": "allgather", "fusion": "flat"},
                     int_grads, mesh)
    assert np.array_equal(a, b)


def test_rscatter_homomorphic_bit_identical_to_ring(mesh, int_grads):
    """shared_scale: the rscatter all_to_all+sum and the ring's hop adds
    accumulate the SAME stage-1 integer level payloads (same shard
    encode, same negotiated scale, same rng folds) — one decode each,
    bit-identical results."""
    a = _update_once({"compressor": "homoqsgd", "quantum_num": 7,
                      "memory": "none", "communicator": "rscatter",
                      "fusion": "flat"}, int_grads, mesh)
    b = _update_once({"compressor": "homoqsgd", "quantum_num": 7,
                      "memory": "none", "communicator": "ring",
                      "fusion": "flat"}, int_grads, mesh)
    assert np.array_equal(a, b)


def test_rscatter_requant_bit_identical_to_twoshot(mesh, int_grads):
    """The single-requant path IS TwoShot's schedule (same stage-1 shard
    encode, same owned-chunk aggregate, same shared stage-2 key) realized
    with the reduce-scatter all_to_all — pinned bitwise."""
    for cfg in ({"compressor": "topk", "compress_ratio": 0.5,
                 "memory": "none"},
                {"compressor": "qsgd", "quantum_num": 64,
                 "use_pallas": False, "memory": "none"}):
        a = _update_once({**cfg, "communicator": "rscatter",
                          "fusion": "flat"}, int_grads, mesh)
        b = _update_once({**cfg, "communicator": "twoshot",
                          "fusion": "flat"}, int_grads, mesh)
        assert np.array_equal(a, b), cfg["compressor"]


def test_rscatter_rejects_non_summable_non_requant(mesh):
    grc = grace_from_params({"compressor": "onebit", "memory": "residual",
                             "communicator": "rscatter", "fusion": "flat"})
    tx = grc.transform(0)
    grads = jnp.ones((8, 64), jnp.float32)

    def body(gr):
        state = tx.init(gr)
        out, _ = tx.update(gr, state, None)
        return out

    f = shard_map(body, mesh=mesh, in_specs=(P("data"),),
                  out_specs=P("data"), check_vma=False)
    with pytest.raises(TypeError, match="payload algebra"):
        jax.jit(f)(grads)


def test_rscatter_wire_model_w_edges():
    cm = comm.ReduceScatterAllreduce()
    assert cm.recv_wire_bytes(1000, 500, 0) == 0
    assert cm.recv_wire_bytes(1000, 500, 1) == 0
    assert cm.recv_wire_bytes(1000, 500, 8) == 2 * 1000 * 7 // 8
    # flat schedule: all-ICI within one slice, all-DCN beyond it
    from grace_tpu.core import Topology
    lb = cm.recv_link_bytes(1000, 500, 8, topology=Topology(slice_size=4))
    assert lb.ici == 0 and lb.dcn == cm.recv_wire_bytes(1000, 500, 8)


# ---------------------------------------------------------------------------
# cyclic local-selection topk (ScaleCom)
# ---------------------------------------------------------------------------

def test_cyclictopk_shared_indices_sum_exactly(mesh, int_grads):
    """The rng+step-derived shared index set makes the payload exactly
    summable: the psum allreduce and the gather-then-sum agree bitwise,
    and — the summability claim in its strongest spelling — EVERY
    schedule's selected coordinates carry the exact dense mean bitwise
    (integer grads are exact in f32), including the hierarchical
    two-level gather the data-free ctx just unlocked. Schedules that
    chunk the buffer differently (ring's W shards, hier's slice shards)
    legitimately select different windows, so cross-schedule bitwise
    identity is only pinned where the chunking agrees."""
    cfg = {"compressor": "cyclictopk", "compress_ratio": 0.5,
           "memory": "none"}
    a = _update_once({**cfg, "communicator": "allreduce"}, int_grads, mesh)
    b = _update_once({**cfg, "communicator": "allgather"}, int_grads, mesh)
    assert np.array_equal(a, b)
    h = _update_once({**cfg, "communicator": "hier", "slice_size": 4,
                      "fusion": "flat"}, int_grads, mesh)
    dense = np.asarray(int_grads).mean(axis=0)
    for name, out in (("allreduce", a), ("hier", h)):
        row = out[0]
        # replicas bit-identical (the shared-set algebra's rank identity)
        assert all(np.array_equal(out[i], row) for i in range(out.shape[0]))
        nz = row != 0
        assert nz.any()
        # exact payload-space summation: no requant loss anywhere
        assert np.array_equal(row[nz], dense[nz]), name


def test_cyclictopk_negotiation_free():
    """The cyclic schedule is rank-deterministic (rng + step, not data):
    there is no index broadcast, so the wire model prices ZERO
    negotiation bytes through both accessor spellings."""
    from grace_tpu.core import needs_negotiation, negotiation_bytes_for
    from grace_tpu.compressors import CyclicTopKCompressor

    c = CyclicTopKCompressor(compress_ratio=0.1)
    assert not needs_negotiation(c)
    assert negotiation_bytes_for(c, 1000, 8) == 0
    assert c.negotiation_nbytes(8) == 0


def test_cyclictopk_schedule_deterministic_and_distinct():
    """The cyclic window is a pure function of the replicated key: same
    key -> same indices (the rank-identity proof obligation), distinct
    indices (the scatter never collides), rotating with the step fold."""
    from grace_tpu.compressors import CyclicTopKCompressor

    c = CyclicTopKCompressor(compress_ratio=0.1)
    key = jax.random.key(7)
    a = np.asarray(c._schedule(key, 1000))
    b = np.asarray(c._schedule(key, 1000))
    assert np.array_equal(a, b)
    assert len(set(a.tolist())) == a.size
    stepped = np.asarray(c._schedule(jax.random.fold_in(key, 1), 1000))
    assert not np.array_equal(a, stepped)


def test_cyclictopk_accepted_by_shard_parallel_comms(mesh, int_grads):
    """The data-free ctx unlocks the hop-pipelined decode paths (ROADMAP
    item 4): ring and rscatter run cyclictopk end to end, agree with the
    allgather reference bitwise on integer grads (exact payload algebra,
    same shared index set) — and the tuner's capability mirror agrees."""
    cfg = {"compressor": "cyclictopk", "compress_ratio": 0.5,
           "memory": "none", "fusion": "flat"}
    ring = _update_once({**cfg, "communicator": "ring"}, int_grads, mesh)
    rsc = _update_once({**cfg, "communicator": "rscatter"},
                       int_grads, mesh)
    # Same stage-1 shard encode (same chunk-folded keys), exact payload
    # algebra on both schedules — the hop adds and the all_to_all sum are
    # the same arithmetic, so the two outputs are bit-identical.
    assert np.array_equal(ring, rsc)
    dense = np.asarray(int_grads).mean(axis=0)
    nz = ring[0] != 0
    assert nz.any() and np.array_equal(ring[0][nz], dense[nz])

    from grace_tpu.tuning.candidates import Candidate, candidate_legal
    from grace_tpu.tuning.cost import TuneTopology
    legal, reason, _ = candidate_legal(
        Candidate("cyclic-ring", {"compressor": "cyclictopk",
                                  "memory": "none", "communicator": "ring",
                                  "fusion": "flat"}),
        TuneTopology(world=8))
    assert legal, reason


# ---------------------------------------------------------------------------
# degenerate collapse + 2-D state layout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg", [
    {"compressor": "topk", "compress_ratio": 0.3, "memory": "residual",
     "communicator": "allgather"},
    {"compressor": "fp16", "memory": "none", "communicator": "rscatter",
     "fusion": "flat"},
    {"compressor": "homoqsgd", "quantum_num": 7, "memory": "residual",
     "communicator": "ring", "fusion": "flat"},
], ids=["topk-allgather", "fp16-rscatter", "homoqsgd-ring"])
def test_fsdp_degenerate_mesh_collapses_bitwise(mesh, int_grads, cfg):
    """A W×1 fsdp-degenerate 2-D mesh reproduces the 1-D path bitwise:
    same collectives over dp, a size-1 fsdp axis contributing nothing."""
    one_d = _update_once(cfg, int_grads, mesh)
    mesh2 = make_mesh((8, 1), ("data", "fsdp"))
    two_d = _update_once({**cfg, "fsdp_axis": "fsdp"}, int_grads, mesh2,
                         in_spec=P("data"))
    assert np.array_equal(one_d, two_d)


def test_every_registered_config_state_unchanged_under_meshspec():
    """The 1×W collapse, registry-wide: for every registered update-mode
    config, arming the 2-D MeshSpec changes NO state structure or shapes
    — the fsdp axis re-shards the same state, it never resizes it."""
    from grace_tpu.analysis.configs import AUDIT_CONFIGS, build_grace
    from grace_tpu.analysis.trace import default_param_structs

    params = default_param_structs()
    checked = 0
    for entry in AUDIT_CONFIGS:
        if entry.get("mode", "update") != "update":
            continue
        if entry["params"].get("use_pallas") is True:
            continue                      # interpret-mode kernel: slow
        base = build_grace(entry)
        import dataclasses
        two_d = dataclasses.replace(base, mesh=MeshSpec("data", "fsdp"))
        s1 = jax.eval_shape(base.transform(0).init, params)
        s2 = jax.eval_shape(two_d.transform(0).init, params)
        assert jax.tree_util.tree_structure(s1) == \
            jax.tree_util.tree_structure(s2), entry["name"]
        for a, b in zip(jax.tree_util.tree_leaves(s1),
                        jax.tree_util.tree_leaves(s2)):
            assert a.shape == b.shape and a.dtype == b.dtype, entry["name"]
        checked += 1
    assert checked >= 40


def test_partition_specs_2d_layout():
    """mem/comp/telem/watch shard over the dp×fsdp product; replicated
    fields and non-grace leaves stay P(); the 1-D spelling is unchanged."""
    g = grace_from_params({"compressor": "topk", "compress_ratio": 0.3,
                           "memory": "residual",
                           "communicator": "allgather",
                           "telemetry": True})
    tx = g.transform(0)
    params = {"w": jnp.ones((16, 4)), "b": jnp.ones((8,))}
    state = jax.eval_shape(tx.init, params)
    one_d = partition_specs(state, "data")
    assert one_d.mem[0] == P("data")
    assert one_d.count == P()
    two_d = partition_specs(state, MeshSpec("data", "fsdp"))
    assert two_d.mem[0] == P(("data", "fsdp"))
    assert two_d.count == P() and two_d.fallback == P()
    assert jax.tree_util.tree_leaves(
        partition_specs(state.telem, MeshSpec("data", "fsdp")),
        is_leaf=lambda x: isinstance(x, P)) != []


def test_meshspec_validation():
    with pytest.raises(ValueError, match="fsdp_axis must differ"):
        MeshSpec("data", "data")
    # a 2-D Grace builds its transform fine
    grace_from_params({"compressor": "none", "memory": "none",
                       "communicator": "allreduce",
                       "fsdp_axis": "fsdp"}).transform(0)
    # mismatched: communicator on another axis than the MeshSpec dp
    from grace_tpu.transform import grace_transform
    from grace_tpu.compressors import NoneCompressor
    from grace_tpu.memories import NoneMemory
    with pytest.raises(ValueError, match="dp_axis"):
        grace_transform(NoneCompressor(), NoneMemory(),
                        comm.Allreduce(axis_name="data"),
                        mesh=MeshSpec("dp2", "fsdp"))


# ---------------------------------------------------------------------------
# 2-D fsdp training end to end
# ---------------------------------------------------------------------------

def test_fsdp_train_step_per_shard_residuals():
    """A sharded-model train step on the 4×2 mesh: loss decreases, the
    GraceState mem leaves carry the dp×fsdp product world axis, and each
    device's residual covers exactly its own param shard (error feedback
    lives on the shard owner)."""
    from grace_tpu.train import init_train_state, make_train_step
    from grace_tpu.transform import GraceState

    mesh2 = make_mesh((4, 2), ("data", "fsdp"))
    ms = MeshSpec("data", "fsdp")
    feat, hid, classes = 16, 8, 10
    rng = np.random.default_rng(0)
    params = {"w1": jnp.asarray(rng.normal(size=(feat, hid)), jnp.float32),
              "b1": jnp.zeros((hid,)),
              "w2": jnp.asarray(rng.normal(size=(hid, classes)),
                                jnp.float32)}
    param_specs = {"w1": P("fsdp", None), "b1": P(), "w2": P()}
    shard = feat // 2

    def loss_fn(p, b):
        x, y = b
        f = lax.axis_index("fsdp")
        xs = lax.dynamic_slice_in_dim(x, f * shard, shard, 1)
        h = lax.psum(xs @ p["w1"], "fsdp") + p["b1"]
        logits = jnp.tanh(h) @ p["w2"]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    g = grace_from_params({
        "compressor": "topk", "compress_ratio": 0.3, "memory": "residual",
        "communicator": "rscatter", "fsdp_axis": "fsdp",
        "route": [("b1", {"compressor": "fp16", "memory": "none",
                          "communicator": "allreduce"})]})
    tx = optax.chain(g.transform(0), optax.sgd(0.1))
    st = init_train_state(params, tx, mesh2, axis_name=ms,
                          param_specs=param_specs)
    step = make_train_step(loss_fn, tx, mesh2, axis_name=ms,
                           param_specs=param_specs, donate=False)
    x = jnp.asarray(rng.normal(size=(16, feat)), jnp.float32)
    y = jnp.asarray(rng.integers(0, classes, (16,)), jnp.int32)
    losses = []
    for _ in range(8):
        st, loss = step(st, (x, y))
        losses.append(float(loss))
    assert losses[-1] < losses[0]

    grace_states = []

    def find(node):
        if isinstance(node, GraceState):
            grace_states.append(node)
        return node

    jax.tree_util.tree_map(find, st.opt_state,
                           is_leaf=lambda n: isinstance(n, GraceState))
    mem_leaves = [m for m in jax.tree_util.tree_leaves(grace_states[0].mem)]
    # routed b1 has no residual (NoneMemory); w1/w2 do — leading world
    # axis spans the dp×fsdp product, body is the LOCAL shard
    shapes = sorted(tuple(m.shape) for m in mem_leaves)
    assert shapes == sorted([(8, shard, hid), (8, hid, classes)])
    # the w1 residual genuinely differs across fsdp shard owners
    w1_mem = next(m for m in mem_leaves if m.shape == (8, shard, hid))
    host = np.asarray(w1_mem)
    assert host.shape[0] == 8


# ---------------------------------------------------------------------------
# 2-D lint seeding: the wrong-axis replication bug, condemned live
# ---------------------------------------------------------------------------

def _two_axis_trace(fn, varying_axes=None):
    from grace_tpu.analysis.trace import trace_fn

    args = (jax.ShapeDtypeStruct((8,), jnp.float32),)
    return trace_fn(fn, args, mesh_axes=(("data", 4), ("fsdp", 2)),
                    varying_axes=varying_axes, name="seeded-2d")


def test_wrong_axis_replication_condemned_by_pass1():
    """The seeded wrong-axis bug: a predicate psummed over FSDP (so it
    looks 'reduced') but still dp-varying gates a cond whose branches
    issue different dp-axis collectives — ranks of one dp group can take
    different branches. Pass 1's per-axis analysis must condemn it."""
    from grace_tpu.analysis.passes import pass_collective_consistency

    def bad(x):
        # varies over dp (seeded); the fsdp psum does NOT grant dp
        # replication — the wrong axis
        p = lax.psum(jnp.sum(x), "fsdp") > 0

        def taken(v):
            return lax.psum(v, "data")

        return lax.cond(p, taken, lambda v: v, x)

    traced = _two_axis_trace(bad)
    findings = pass_collective_consistency(traced)
    assert len(findings) == 1
    assert "data" in str(dict(findings[0].details)["varying_axes"])


def test_right_axis_replication_blessed_by_pass1():
    """The legal twins: (a) a predicate psummed over dp gating dp-axis
    branch divergence; (b) an fsdp-varying predicate gating DP-axis
    collectives — dp peers share an fsdp index, so they agree — which
    the old single-axis analysis would have false-positived."""
    from grace_tpu.analysis.passes import pass_collective_consistency

    def legal_reduced(x):
        p = lax.psum(jnp.sum(x), "data") > 0

        def taken(v):
            return lax.psum(v, "data")

        return lax.cond(p, taken, lambda v: v, x)

    assert pass_collective_consistency(_two_axis_trace(legal_reduced)) == []

    def legal_fsdp_varying(x):
        p = lax.axis_index("fsdp") > 0     # fsdp-varying, dp-replicated

        def taken(v):
            return lax.psum(v, "data")

        return lax.cond(p, taken, lambda v: v, x)

    # seed x replicated on both axes so only axis_index drives variance
    traced = _two_axis_trace(legal_fsdp_varying,
                             varying_axes={"data": [False],
                                           "fsdp": [False]})
    assert pass_collective_consistency(traced) == []


def test_2d_rscatter_wire_reconciles_leg_by_leg():
    """wire_reconciliation on the 2-D fsdp config: the dp-axis schedule's
    counted bytes reconcile against the model at the dp world, leg by
    leg, under the audit slice boundary."""
    from grace_tpu.analysis.configs import AUDIT_CONFIGS, audit_config

    entry = next(e for e in AUDIT_CONFIGS
                 if e["name"] == "topk-rscatter-fsdp")
    assert "wire_reconciliation" in entry["passes"]
    assert audit_config(entry) == []


def test_2d_trace_worlds_and_axes():
    from grace_tpu.analysis.configs import AUDIT_CONFIGS, build_grace
    from grace_tpu.analysis.trace import trace_update

    entry = next(e for e in AUDIT_CONFIGS
                 if e["name"] == "fp16-rscatter-fsdp")
    traced = trace_update(build_grace(entry), world=8, fsdp=2,
                          name=entry["name"])
    assert traced.world == 4                    # the dp (exchange) world
    assert traced.mesh_axes == ("data", "fsdp")
    assert traced.axis_sizes == {"data": 4, "fsdp": 2}
    # per-axis seeds really differ from a single mask: mem leaves vary
    # over BOTH axes, replicated fields over neither
    assert set(traced.varying_axes) == {"data", "fsdp"}


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def test_route_resolution_and_wire_sum():
    from grace_tpu.helper import route_leaves, routed_recv_link_bytes
    from grace_tpu.utils.metrics import payload_nbytes

    g = grace_from_params({
        "compressor": "topk", "compress_ratio": 0.1, "memory": "residual",
        "communicator": "allgather",
        "route": [("b", {"compressor": "fp16", "memory": "none",
                         "communicator": "allreduce"})]})
    params = {"w": jnp.ones((100, 10)), "b": jnp.ones((10,))}
    leaves = route_leaves(g, params)
    by_path = {p: (type(c).__name__, type(cm).__name__)
               for p, _s, c, _m, cm in leaves}
    assert by_path["b"] == ("FP16Compressor", "Allreduce")
    assert by_path["w"] == ("TopKCompressor", "Allgather")
    total = routed_recv_link_bytes(g, params, 8).total
    # = per-leaf sum: allgather (W-1)*payload for w, ring-style psum for b
    w_payload = payload_nbytes(g.compressor, jnp.ones((100, 10)))
    b_payload = payload_nbytes(
        next(c for p, _s, c, _m, _cm in leaves if p == "b"),
        jnp.ones((10,)))
    expect = 7 * w_payload + 2 * b_payload * 7 // 8
    assert total == expect


def test_routes_require_per_leaf_fusion():
    with pytest.raises(ValueError, match="fusion=None"):
        grace_from_params({
            "compressor": "topk", "compress_ratio": 0.1,
            "memory": "residual", "communicator": "allgather",
            "fusion": "flat",
            "route": [("b", {"compressor": "fp16", "memory": "none",
                             "communicator": "allreduce"})]}).transform(0)


def test_route_axis_mismatch_rejected():
    with pytest.raises(ValueError, match="same mesh axis|dp axis"):
        grace_from_params({
            "compressor": "topk", "compress_ratio": 0.1,
            "memory": "residual", "communicator": "allgather",
            "route": [("b", {"compressor": "fp16", "memory": "none",
                             "communicator": "allreduce",
                             "axis_name": "other"})]})


def test_routed_update_applies_per_leaf_codecs(mesh, int_grads):
    """Routed leaves genuinely take their own pipeline: route the second
    half of the tree dense and compare each part against the unrouted
    runs of the matching codec."""
    grads = {"w": int_grads, "b": jnp.asarray(
        np.random.default_rng(1).integers(-4, 4, (8, 16)), jnp.float32)}

    g = grace_from_params({
        "compressor": "fp16", "memory": "none",
        "communicator": "allgather",
        "route": [("b", {"compressor": "none", "memory": "none",
                         "communicator": "allreduce"})]})
    tx = g.transform(0)

    def body(gr):
        state = tx.init(gr)
        out, _ = tx.update(gr, state, None)
        return out

    f = shard_map(body, mesh=mesh, in_specs=(P("data"),),
                  out_specs=P("data"), check_vma=False)
    out = jax.jit(f)(grads)
    a = _update_once({"compressor": "fp16", "memory": "none",
                      "communicator": "allgather"}, int_grads, mesh)
    b = _update_once({"compressor": "none", "memory": "none",
                      "communicator": "allreduce"}, grads["b"], mesh)
    assert np.array_equal(np.asarray(out["w"]), a)
    assert np.array_equal(np.asarray(out["b"]), b)


# ---------------------------------------------------------------------------
# the transformer track wins on the model
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# tuner 2-D spec + chaos smoke
# ---------------------------------------------------------------------------

def test_tune_topology_2d_spec():
    from grace_tpu.tuning.cost import TuneTopology

    t = TuneTopology.parse("64x4,8")
    assert (t.world, t.fsdp, t.slice_size) == (64, 4, 8)
    assert t.devices == 256
    assert t.label == "W64x4/slice8"
    assert TuneTopology.parse("256,8").fsdp is None
    with pytest.raises(ValueError):
        TuneTopology.parse("8,4,2")


def test_tuner_generates_routed_fsdp_variant():
    from grace_tpu.tuning.candidates import (candidate_legal,
                                             enumerate_candidates)
    from grace_tpu.tuning.cost import TuneTopology

    spec = TuneTopology(world=64, slice_size=8, fsdp=4)
    cands = {c.name: c for c in enumerate_candidates(spec)}
    assert "tune-routed-rscatter-fsdp" in cands
    legal, reason, grace = candidate_legal(
        cands["tune-routed-rscatter-fsdp"], spec)
    assert legal, reason
    assert grace.mesh.is_2d and grace.routes


@pytest.mark.chaos
def test_chaos_smoke_fsdp_scenario(tmp_path):
    """Tier-1 drill of the --fsdp scenario: guard + consensus over the
    2-D mesh, SDC repaired per fsdp shard, artifact rows carry the
    two-axis wire split."""
    import sys
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import chaos_smoke

    out = tmp_path / "fsdp_telemetry.jsonl"
    # nan_prob 0.05: at the default 0.01 a 60-step run has a 30% chance of
    # drawing no implant at all, and which seeds do depends on the
    # installed JAX's PRNG stream (seed 0 drew none on 0.9.0).
    rc = chaos_smoke.main(["--fsdp", "--steps", "60", "--nan-prob", "0.05",
                           "--telemetry-out", str(out)])
    assert rc == 0
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    telem = [r for r in rows if "step" in r and "wire_bytes" in r]
    assert telem and all("wire_bytes_ici" in r and "wire_bytes_dcn" in r
                         for r in telem)
    assert any(r["wire_bytes_dcn"] > 0 for r in telem)
