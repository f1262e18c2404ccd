"""Hierarchical ICI×DCN communicator (comm.HierarchicalAllreduce, ISSUE 7).

The properties pinned here are the two-level schedule's acceptance
criteria: exact codecs are BIT-identical to the flat ring at any slice
split (integer-valued grads make every partial sum exactly representable,
so no tolerance can hide a wrong shard route or a dropped cross-slice
partial); the requant path's extra loss stays bounded (one slice-boundary
re-encode, not K−1 cross-slice hops); the per-link wire model satisfies the
PR-6 split-sum identity, is monotone-in-slices on the DCN leg, and
collapses to the flat ring formula when there is nothing to split; the
telemetry ring's new ``wire_bytes_ici``/``wire_bytes_dcn`` fields carry the
honest mixed split from a REAL sharded step; ``Topology.detect`` rejects
the device lists it used to mis-size silently.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from grace_tpu import comm, grace_from_params
from grace_tpu import compressors as C
from grace_tpu.core import LinkBytes, Topology
from grace_tpu.memories import NoneMemory, ResidualMemory
from grace_tpu.parallel import shard_map
from grace_tpu.resilience import ConsensusConfig, audit_report, guarded_chain
from grace_tpu.telemetry import TelemetryReader
from grace_tpu.train import init_train_state, make_train_step
from grace_tpu.transform import set_fallback_flag
from grace_tpu.utils.metrics import guard_report

W = 8

pytestmark = pytest.mark.hier

BATCH, DIM, CLASSES = 64, 20, 4

SPLITS = (None, 1, 2, 4, 8)      # slice_size values that divide the 8-mesh


def run_step(mesh, communicator, compressor, memory, per_rank, seed=0):
    """Full pipeline step per rank on ``mesh``; returns (out, mem) of rank 0."""
    w = len(mesh.devices)

    def body(x):
        x = x[0]
        ms = memory.init_state(x)
        cs = compressor.init_state(x)
        out, ms, _ = communicator.step(x, ms, cs, memory, compressor,
                                       jax.random.key(seed))
        ms_leaf = ms if ms is not None else jnp.zeros_like(x)
        return out[None], ms_leaf[None]

    fn = shard_map(body, mesh=mesh, in_specs=P("data"),
                   out_specs=(P("data"), P("data")), check_vma=False)
    assert per_rank.shape[0] == w
    out, ms = fn(per_rank)
    return np.asarray(out[0]), np.asarray(ms[0])


def submesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("data",))


# ---------------------------------------------------------------------------
# exact path: payload-space accumulation intra-slice AND cross-slice
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s", SPLITS, ids=[f"s{s}" for s in SPLITS])
def test_none_equals_dense_mean_with_padding(mesh, rng, s):
    x = rng.normal(size=(W, 41)).astype(np.float32)  # 41: exercises padding
    out, _ = run_step(mesh, comm.HierarchicalAllreduce(slice_size=s),
                      C.NoneCompressor(), NoneMemory(), jnp.asarray(x))
    np.testing.assert_allclose(out, x.mean(0), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("comp", [C.NoneCompressor(), C.FP16Compressor()],
                         ids=["none", "fp16"])
@pytest.mark.parametrize("s", SPLITS, ids=[f"s{s}" for s in SPLITS])
def test_exact_codec_bit_identical_to_flat_ring_at_any_split(mesh, rng,
                                                             comp, s):
    """ISSUE 7 acceptance: bit-identity vs the flat ring for exact codecs
    at ANY slice split. Integer-valued gradients make every partial sum
    exactly representable in f32 AND fp16, so summation order (intra-slice
    ring order + cross-slice gather-sum vs the flat ring's W−1 sequential
    hops) cannot matter — a wrong shard route, a double-counted slice
    partial, or a mis-aligned ctx shows up as an integer-sized error."""
    x = rng.integers(-8, 9, size=(W, 37)).astype(np.float32)
    ref, _ = run_step(mesh, comm.RingAllreduce(), comp, NoneMemory(),
                      jnp.asarray(x))
    out, _ = run_step(mesh, comm.HierarchicalAllreduce(slice_size=s), comp,
                      NoneMemory(), jnp.asarray(x))
    np.testing.assert_array_equal(out, ref)


def test_randomk_shared_indices_exact_on_selected(mesh, rng):
    """randomk rides the exact path end to end: per-shard selection
    (shard-folded keys, like the flat ring) and every selected lane
    carries the exact mean through both levels."""
    x = rng.normal(size=(W, 64)).astype(np.float32)
    out, _ = run_step(mesh, comm.HierarchicalAllreduce(slice_size=4),
                      C.RandomKCompressor(compress_ratio=0.5), NoneMemory(),
                      jnp.asarray(x), seed=3)
    nz = out != 0
    assert nz.sum() == 32           # 4 shards x k=8 of 16 lanes
    # cross-slice gather-sum order differs from the flat ring's hop order,
    # so float associativity allows last-ulp differences — nothing more.
    np.testing.assert_allclose(out[nz], x.mean(0)[nz], rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# requant path: intra-slice hop requant + ONE slice-boundary re-encode
# ---------------------------------------------------------------------------

def test_topk_residual_memory_sees_stage1_error(mesh, rng):
    """Error feedback covers the stage-1 shard encode exactly (intra-hop
    requants and the boundary re-encode are downstream, like the flat
    ring's hop losses): residual + stage-1 reconstruction == compensated."""
    x = rng.normal(size=(W, 64)).astype(np.float32)
    comp = C.TopKCompressor(compress_ratio=0.25)
    out, residual = run_step(mesh, comm.HierarchicalAllreduce(slice_size=4),
                             comp, ResidualMemory(), jnp.asarray(x))
    recon = x[0] - residual
    kept = recon != 0
    np.testing.assert_allclose(recon[kept], x[0][kept], rtol=1e-6)
    assert 0 < kept.sum() <= 64 * 0.25 + 8     # per-shard k of 16 lanes


def test_qsgd_error_comparable_to_flat_ring(mesh, rng):
    """The two-level schedule trades W−2 flat-ring intermediate requants
    for S−2 intra-slice ones plus ONE boundary re-encode — its total
    requant error must stay within a small factor of the flat ring's at
    the same world, never explode."""
    q = 64
    comp = C.QSGDCompressor(quantum_num=q)
    x = rng.normal(size=(W, 64)).astype(np.float32)

    def rel_err(communicator):
        out, _ = run_step(mesh, communicator, comp, NoneMemory(),
                          jnp.asarray(x))
        return np.linalg.norm(out - x.mean(0)) / np.linalg.norm(x.mean(0))

    err_ring = rel_err(comm.RingAllreduce())
    err_hier = rel_err(comm.HierarchicalAllreduce(slice_size=4))
    assert err_hier < 0.25, err_hier
    assert err_hier < 4 * max(err_ring, 1.0 / q), (err_ring, err_hier)


def test_signsgd_cascaded_vote_preserves_unanimity(mesh):
    """Intra-slice hops re-sign the running partial (cascaded vote), the
    boundary encode re-signs the slice tally, and the cross-slice
    aggregate majority-votes over slices. Unanimous coordinates MUST
    survive exactly; the output stays ±1 everywhere."""
    col0 = np.ones((W,), np.float32)
    x = np.stack([col0, -col0, col0, -col0], axis=1)
    for s in (2, 4):
        out, _ = run_step(mesh, comm.HierarchicalAllreduce(slice_size=s),
                          C.SignSGDCompressor(), NoneMemory(),
                          jnp.asarray(x))
        np.testing.assert_array_equal(out, [1.0, -1.0, 1.0, -1.0])
    rng = np.random.default_rng(7)
    xr = rng.normal(size=(W, 53)).astype(np.float32)
    outr, _ = run_step(mesh, comm.HierarchicalAllreduce(slice_size=2),
                       C.SignSGDCompressor(), NoneMemory(), jnp.asarray(xr))
    assert set(np.unique(outr)) <= {-1.0, 1.0}


# ---------------------------------------------------------------------------
# enforced compatibility gates
# ---------------------------------------------------------------------------

def test_rejects_stateful_compressors(mesh, rng):
    x = rng.normal(size=(W, 16)).astype(np.float32)
    with pytest.raises(TypeError, match="stateless"):
        run_step(mesh, comm.HierarchicalAllreduce(slice_size=4),
                 C.SignumCompressor(), NoneMemory(), jnp.asarray(x))


def test_rejects_codecs_without_requant_or_summable(mesh, rng):
    """Same capability gates as Ring — enforced, not documented."""
    x = rng.normal(size=(W, 16)).astype(np.float32)
    for comp in [C.OneBitCompressor(), C.SketchCompressor(bins=16),
                 C.DgcCompressor(compress_ratio=0.5)]:
        with pytest.raises(TypeError, match="supports_hop_requant"):
            run_step(mesh, comm.HierarchicalAllreduce(slice_size=4), comp,
                     NoneMemory(), jnp.asarray(x))


def test_rejects_bare_exchange():
    with pytest.raises(TypeError, match="step"):
        comm.HierarchicalAllreduce().exchange((jnp.zeros(4),), None,
                                              C.NoneCompressor())


def test_non_divisible_world_raises(mesh, rng):
    """world % slice_size != 0 is a trace-time ValueError, not a silent
    mis-shard (8 ranks cannot form whole 3-wide slices)."""
    x = rng.normal(size=(W, 16)).astype(np.float32)
    with pytest.raises(ValueError, match="does not divide"):
        run_step(mesh, comm.HierarchicalAllreduce(slice_size=3),
                 C.NoneCompressor(), NoneMemory(), jnp.asarray(x))
    with pytest.raises(ValueError, match="does not divide"):
        comm.HierarchicalAllreduce(slice_size=3).recv_wire_bytes(1000, 256, 8)


def test_from_params_builds_hier_with_topology():
    g = grace_from_params({"compressor": "topk", "compress_ratio": 0.3,
                           "memory": "residual", "communicator": "hier",
                           "slice_size": 4})
    assert isinstance(g.communicator, comm.HierarchicalAllreduce)
    assert g.communicator.slice_size == 4
    assert g.communicator.shard_parallel
    # slice_size also declares the Topology telemetry prices against
    assert g.topology == Topology(slice_size=4)
    # without it the layout is detected (None = detect at wire-plan time)
    g2 = grace_from_params({"compressor": "none", "communicator": "hier"})
    assert g2.communicator.slice_size is None and g2.topology is None


def test_grouped_fusion_rejected():
    g = grace_from_params({"compressor": "topk", "compress_ratio": 0.3,
                           "memory": "residual", "communicator": "hier",
                           "slice_size": 4, "fusion": "grouped"})
    with pytest.raises(ValueError, match="shard-parallel"):
        g.transform(seed=0)


# ---------------------------------------------------------------------------
# per-link wire model: split-sum identity, monotonicity, collapse
# ---------------------------------------------------------------------------

PAYLOAD, NELEMS = 8192, 2048


def test_recv_link_bytes_split_sum_identity():
    """The PR-6 identity, now over a genuinely MIXED split: ici + dcn ==
    recv_wire_bytes for every world, slice split, topology, and vote flag
    — bench projections and telemetry must price the same bytes."""
    for s in (None, 1, 2, 4, 8, 64):
        c = comm.HierarchicalAllreduce(slice_size=s)
        for w in (1, 2, 8, 64, 256):
            if s is not None and w > s and w % s:
                continue
            for topo in (None, Topology(), Topology(slice_size=s),
                         Topology(slice_size=8), Topology(slice_size=1024)):
                if topo is not None and topo.slice_size == 0:
                    continue
                for vote in (False, True):
                    total = c.recv_wire_bytes(PAYLOAD, NELEMS, w, vote=vote)
                    lb = c.recv_link_bytes(PAYLOAD, NELEMS, w,
                                           topology=topo, vote=vote)
                    assert lb.ici + lb.dcn == total == lb.total, \
                        (s, w, topo, vote, lb, total)


def test_dcn_bytes_monotone_in_num_slices():
    """More slices (smaller S at fixed W) => strictly more DCN bytes: the
    cross-slice leg ships (K−1)·payload/S, which grows as the hierarchy
    fragments — slice_size is a real knob, not a relabeling."""
    w = 256
    dcns = []
    for s in (128, 64, 32, 16, 8, 4, 2, 1):
        c = comm.HierarchicalAllreduce(slice_size=s)
        lb = c.recv_link_bytes(PAYLOAD, NELEMS, w,
                               topology=Topology(slice_size=s))
        assert lb.dcn > 0
        dcns.append(lb.dcn)
    assert all(a < b for a, b in zip(dcns, dcns[1:])), dcns


def test_collapses_to_flat_ring_formula():
    """slice_size=None or world <= slice_size: one slice, no DCN leg, and
    the scalar model IS the flat ring's 2·p·(W−1)/W."""
    ring = comm.RingAllreduce()
    for s, w in ((None, 8), (None, 256), (8, 8), (8, 4), (64, 8), (1024, 256)):
        c = comm.HierarchicalAllreduce(slice_size=s)
        assert c.recv_wire_bytes(PAYLOAD, NELEMS, w) == \
            ring.recv_wire_bytes(PAYLOAD, NELEMS, w), (s, w)
        assert c.recv_link_bytes(PAYLOAD, NELEMS, w).dcn == 0


def test_mixed_split_values_and_misaligned_topology():
    """slice_size=8 at W=256 under the matching physical topology: ICI leg
    is the flat-ring-within-a-slice 2·p·7/8, DCN leg the 31 cross-slice
    partials of p/8. A topology the schedule's slices straddle (physical
    slices of 4 under 8-wide comm slices, or an unsliced comm on a sliced
    mesh) degrades to the flat all-DCN critical path — honestly."""
    c = comm.HierarchicalAllreduce(slice_size=8)
    lb = c.recv_link_bytes(PAYLOAD, NELEMS, 256,
                           topology=Topology(slice_size=8))
    assert lb == LinkBytes(ici=2 * PAYLOAD * 7 // 8, dcn=31 * PAYLOAD // 8)
    # comm slices of 8 nest in physical slices of 16: still mixed
    nested = c.recv_link_bytes(PAYLOAD, NELEMS, 256,
                               topology=Topology(slice_size=16))
    assert nested.ici == lb.ici and nested.dcn == lb.dcn
    # comm slices of 8 straddle physical slices of 4: all DCN
    straddle = c.recv_link_bytes(PAYLOAD, NELEMS, 256,
                                 topology=Topology(slice_size=4))
    assert straddle.ici == 0 and straddle.dcn == lb.total
    # and far below the flat ALLGATHER's all-DCN cost at the same world —
    # the schedule topk actually rides today (255·p over DCN vs 31·p/8).
    gather_dcn = comm.Allgather().recv_link_bytes(
        PAYLOAD, NELEMS, 256, topology=Topology(slice_size=8)).dcn
    assert lb.dcn < 0.02 * gather_dcn


# ---------------------------------------------------------------------------
# Topology.detect hardening (fake device objects)
# ---------------------------------------------------------------------------

class _Dev:
    def __init__(self, slice_index=None):
        if slice_index is not None:
            self.slice_index = slice_index


def test_detect_even_multislice():
    devs = [_Dev(i // 4) for i in range(16)]      # 4 slices of 4
    assert Topology.detect(devs) == Topology(slice_size=4)


def test_detect_single_slice_and_missing_attr():
    assert Topology.detect([_Dev(0) for _ in range(8)]) == Topology()
    assert Topology.detect([_Dev() for _ in range(8)]) == Topology()
    assert Topology.detect([]) == Topology()
    # CPU / simulated devices: always one slice
    assert Topology.detect().slice_size is None


def test_detect_heterogeneous_slice_index_raises():
    devs = [_Dev(0), _Dev(0), _Dev(), _Dev(1)]
    with pytest.raises(ValueError, match="heterogeneous|no slice_index"):
        Topology.detect(devs)


def test_detect_uneven_slices_raise():
    """5+3 devices across two slices: the old len//n_slices floor would
    have silently reported slice_size=4 — a layout no rank actually has."""
    devs = [_Dev(0)] * 5 + [_Dev(1)] * 3
    with pytest.raises(ValueError, match="uneven"):
        Topology.detect(devs)
    # slice_index=None mixed with real indices is heterogeneous, not 0
    with pytest.raises(ValueError):
        Topology.detect([_Dev(None), _Dev(1), _Dev(1)])


# ---------------------------------------------------------------------------
# telemetry: the per-link wire_bytes_ici / wire_bytes_dcn fields
# ---------------------------------------------------------------------------

def _problem(seed=0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(DIM, CLASSES)).astype(np.float32)
    x = rng.normal(size=(BATCH * 8, DIM)).astype(np.float32)
    y = np.argmax(x @ w_true, axis=1)
    return jnp.asarray(x), jnp.asarray(y)


def _loss_fn(params, batch):
    x, y = batch
    logits = x @ params["w"] + params["b"]
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def _init_params(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(
                rng.normal(size=(DIM, CLASSES)).astype(np.float32) * 0.1),
            "b": jnp.zeros((CLASSES,), jnp.float32)}


def _build(mesh, grace_params, lr=0.3, guard=False, consensus=None,
           **guard_kw):
    grc = grace_from_params(dict(grace_params))
    if guard or consensus is not None:
        tx = guarded_chain(grc, optax.sgd(lr), **guard_kw)
    else:
        tx = optax.chain(grc.transform(seed=0), optax.sgd(lr))
    state = init_train_state(_init_params(), tx, mesh)
    step = make_train_step(_loss_fn, tx, mesh, donate=False,
                           consensus=consensus)
    return state, step


@pytest.mark.telemetry
def test_telemetry_link_split_mixed_for_hier_all_ici_for_flat(mesh):
    """ISSUE 7 telemetry honesty: hier rows carry a genuinely mixed split
    that sums to wire_bytes; flat comms fall back to the all-ICI split on
    the (single-slice-detected) CPU mesh."""
    x, y = _problem()
    base = {"compressor": "topk", "compress_ratio": 0.3,
            "memory": "residual", "fusion": "flat", "telemetry": 16}

    def last_row(extra):
        state, step = _build(mesh, dict(base, **extra))
        for _ in range(2):
            state, _ = step(state, (x, y))
        rows = TelemetryReader(sink=None, every=100).flush(state)
        assert rows
        return rows[-1]

    hier = last_row({"communicator": "hier", "slice_size": 4})
    assert hier["wire_bytes_ici"] > 0 and hier["wire_bytes_dcn"] > 0
    assert hier["wire_bytes_ici"] + hier["wire_bytes_dcn"] == \
        hier["wire_bytes"]
    # the model the row must match: this config's own recv_link_bytes
    g = grace_from_params(dict(base, communicator="hier", slice_size=4))
    from grace_tpu.transform import fusion_payload_nbytes
    _, comp_b, n_elems = fusion_payload_nbytes(
        g.compressor, jax.tree_util.tree_leaves(_init_params()), "flat")
    lb = g.communicator.recv_link_bytes(comp_b, n_elems, 8,
                                        topology=Topology(slice_size=4))
    assert (hier["wire_bytes_ici"], hier["wire_bytes_dcn"]) == \
        (lb.ici, lb.dcn)

    flat = last_row({"communicator": "allgather"})
    assert flat["wire_bytes_dcn"] == 0.0
    assert flat["wire_bytes_ici"] == flat["wire_bytes"]


@pytest.mark.telemetry
def test_telemetry_link_split_flips_with_fallback_window(mesh):
    """During a dense-fallback window the split flips with the scalar: the
    escape psum is a FLAT schedule, so under the hier config's 2-slice
    topology its bytes ride DCN entirely — the row must say so."""
    x, y = _problem()
    params = {"compressor": "topk", "compress_ratio": 0.3,
              "memory": "residual", "communicator": "hier", "slice_size": 4,
              "fusion": "flat", "escape": "fp16", "telemetry": 32}
    state, step = _build(mesh, params)
    for _ in range(2):
        state, _ = step(state, (x, y))
    state = set_fallback_flag(state, True)
    for _ in range(2):
        state, _ = step(state, (x, y))
    state = set_fallback_flag(state, False)
    state, _ = step(state, (x, y))
    rows = TelemetryReader(sink=None, every=100).flush(state)
    assert [r["fallback"] for r in rows] == [0, 0, 1, 1, 0]
    for r in rows:
        assert r["wire_bytes_ici"] + r["wire_bytes_dcn"] == r["wire_bytes"]
    compressed = [r for r in rows if not r["fallback"]]
    dense = [r for r in rows if r["fallback"]]
    assert all(r["wire_bytes_ici"] > 0 and r["wire_bytes_dcn"] > 0
               for r in compressed)
    assert all(r["wire_bytes_ici"] == 0 and r["wire_bytes_dcn"] > 0
               for r in dense)


# ---------------------------------------------------------------------------
# static analysis: the auditor learned the nested-axis schedule
# ---------------------------------------------------------------------------

@pytest.mark.analysis
def test_wire_pass_counts_grouped_collectives_by_group_size():
    """count_recv_link_bytes attributes the traced hier schedule's bytes
    by link class under the comm's own slice split — intra legs ICI, the
    cross-slice gather DCN — and both legs reconcile with the model."""
    from grace_tpu.analysis import build_grace
    from grace_tpu.analysis.passes import count_recv_link_bytes
    from grace_tpu.analysis.trace import default_param_structs, trace_update
    from grace_tpu.core import WIRE_MODEL_ATOL, WIRE_MODEL_RTOL
    from grace_tpu.transform import fusion_payload_nbytes

    grace = build_grace({"name": "hier", "params": {
        "compressor": "topk", "compress_ratio": 0.3, "memory": "residual",
        "communicator": "hier", "slice_size": 4, "fusion": "flat"}})
    t = trace_update(grace, name="hier", meta={"grace": grace})
    topo = Topology(slice_size=4)
    ici, dcn, wan = count_recv_link_bytes(t.body, t.axis_name, t.world,
                                          topo)
    assert wan == 0  # 2-tier topology: nothing crosses a region
    _, comp_b, n_elems = fusion_payload_nbytes(
        grace.compressor, list(default_param_structs().values()), "flat")
    lb = grace.communicator.recv_link_bytes(comp_b, n_elems, t.world,
                                            topology=topo)
    assert dcn > 0 and ici > 0
    for got, want in ((ici, lb.ici), (dcn, lb.dcn)):
        assert abs(got - want) <= max(WIRE_MODEL_RTOL * max(got, want),
                                      WIRE_MODEL_ATOL), (ici, dcn, lb)


@pytest.mark.analysis
def test_wire_pass_fires_on_lying_link_split():
    """The forcing function, proven live: a hier comm whose recv_link_bytes
    claims the cross-slice leg rides ICI keeps the scalar total intact —
    only the new leg-by-leg reconciliation against the traced collectives
    catches it."""
    from grace_tpu.analysis import build_grace
    from grace_tpu.analysis.passes import pass_wire_reconciliation
    from grace_tpu.analysis.trace import trace_update

    @dataclasses.dataclass(frozen=True)
    class AllIciHier(comm.HierarchicalAllreduce):
        def recv_link_bytes(self, payload_nbytes, n_elems, world,
                            topology=None, vote=False):
            total = self._recv_total_bytes(payload_nbytes, n_elems, world,
                                           vote=vote)
            return LinkBytes(ici=int(total), dcn=0)      # the lie

    base = build_grace({"name": "x", "params": {
        "compressor": "topk", "compress_ratio": 0.3, "memory": "residual",
        "communicator": "hier", "slice_size": 4, "fusion": "flat"}})
    grace = dataclasses.replace(base,
                                communicator=AllIciHier(slice_size=4))
    t = trace_update(grace, name="lying-split", meta={"grace": grace})
    findings = pass_wire_reconciliation(t)
    assert len(findings) == 1
    assert "link" in findings[0].message
    # the honest comm on the same trace reconciles leg-by-leg
    t2 = trace_update(base, name="honest-split", meta={"grace": base})
    assert pass_wire_reconciliation(t2) == []


@pytest.mark.analysis
def test_hoisted_constants_seed_replicated():
    """The tracer regression the hier configs exposed: jnp constants
    created inside the step are hoisted to extra shard_map invars, and a
    naive positional mask seeded them (and everything after them)
    rank-varying — turning the legal escape-cond shape into a false
    positive. Constants must seed replicated."""
    from grace_tpu.analysis import trace_fn
    from grace_tpu.analysis.passes import pass_collective_consistency
    from jax import lax

    table = jnp.arange(7, dtype=jnp.int32)       # hoisted constant

    def ok(x, flag):
        y = x[:7] * table                        # closes over the constant
        return lax.cond(flag,
                        lambda o: lax.psum(o, "data"),
                        lambda o: o * 2.0, y)

    t = trace_fn(ok, [jax.ShapeDtypeStruct((64,), jnp.float32),
                      jax.ShapeDtypeStruct((), jnp.bool_)],
                 varying=[True, False], name="const-hoist")
    # the constant's body invar must be seeded replicated
    assert sum(1 for v in t.varying.values() if v) == 1
    assert pass_collective_consistency(t) == []


# ---------------------------------------------------------------------------
# resilience composition: guard rollback + consensus audit over two levels
# ---------------------------------------------------------------------------

HIER_EF = {"compressor": "topk", "compress_ratio": 0.3,
           "memory": "residual", "communicator": "hier", "slice_size": 4,
           "fusion": "flat", "escape": "fp16"}


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(p), np.asarray(q))
               for p, q in zip(la, lb))


@pytest.mark.chaos
def test_guard_rolls_back_hier_step_atomically(mesh):
    """A NaN in one rank's shard propagates through the intra-slice ring
    AND the cross-slice exchange to every rank; the guard must skip the
    step atomically — params and every mem leaf bitwise-unchanged."""
    x, y = _problem()
    state, step = _build(mesh, HIER_EF, guard=True)
    for _ in range(3):
        state, loss = step(state, (x, y))
    assert np.isfinite(float(loss))
    before = state

    xbad = np.asarray(x).copy()
    xbad[0, 0] = np.nan                       # rank 0's shard only
    state, _ = step(state, (jnp.asarray(xbad), y))

    rep = guard_report(state)
    assert rep["notfinite_count"] == 1
    assert _leaves_equal(before.params, state.params)
    g0 = before.opt_state.inner[0]
    g1 = state.opt_state.inner[0]
    assert _leaves_equal(g0.mem, g1.mem)
    assert _leaves_equal(g0.count, g1.count)

    state, loss = step(state, (x, y))         # clean data -> resumes
    assert np.isfinite(float(loss))
    assert not _leaves_equal(before.params, state.params)


@pytest.mark.chaos
@pytest.mark.telemetry
def test_chaos_smoke_hier_scenario(tmp_path):
    """tools/chaos_smoke.py --hier: the guard+fallback matrix over the
    two-level exchange must survive end to end, and the artifact's metric
    rows must carry the mixed per-link split (this CPU run declares
    slice_size=4, so 2 slices of 4 and a real DCN leg in every row)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "chaos_smoke_hier_under_test",
        os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                     "chaos_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    out = tmp_path / "hier_chaos.jsonl"
    rc = smoke.main(["--steps", "12", "--nan-prob", "1.0", "--batch", "16",
                     "--fallback-after", "2", "--fallback-steps", "4",
                     "--hier", "--slice-size", "4",
                     "--telemetry-out", str(out), "--telemetry-every", "6"])
    assert rc == 0
    import json
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    metric = [r for r in rows if "wire_bytes_dcn" in r]
    assert metric, "no per-step metric rows in the artifact"
    for r in metric:
        assert r["wire_bytes_ici"] + r["wire_bytes_dcn"] == r["wire_bytes"]
        # nan_prob=1.0 puts every accepted step in a dense-fallback
        # window: the escape psum is flat, so its bytes all ride DCN
        # under the 2-slice layout.
        assert r["wire_bytes_dcn"] > 0


@pytest.mark.consensus
def test_consensus_audit_is_noop_on_healthy_hier_run(mesh):
    """The consensus audit must stay a bit-exact no-op over the two-level
    exchange: same loss trajectory and params as the audit-off run, zero
    repairs — i.e. the hierarchically aggregated updates really are
    rank-identical."""
    x, y = _problem()
    cfg = dict(HIER_EF, consensus=True)
    on = ConsensusConfig(audit_every=2)
    s_on, step_on = _build(mesh, cfg, consensus=on)
    s_off, step_off = _build(mesh, dict(HIER_EF), guard=True)
    for _ in range(6):
        s_on, l_on = step_on(s_on, (x, y))
        s_off, l_off = step_off(s_off, (x, y))
    assert float(l_on) == float(l_off)
    assert _leaves_equal(s_on.params, s_off.params)
    rep = audit_report(s_on)
    assert rep["audits"] == 3 and rep["repairs"] == 0
