"""Hop-pipelined compressed ring all-reduce (comm.RingAllreduce, ISSUE 4).

The properties pinned here are the ring communicator's acceptance criteria:
exact-codec numerics match the allgather path (bit-identical when every
intermediate sum is exactly representable — integer-valued grads — so no
tolerance can hide a wire-format bug); the per-hop requantization error is
bounded and grows ~linearly in hop count (one requant hop vs world−1),
never explodes; communicator-aware wire bytes are < 0.5× allgather's at
W=8 and agree with the shared ``recv_wire_bytes`` model the tuner's
pricing uses; the enforced compatibility gates (stateless +
summable-or-hop-requant) reject everything else with an actionable
TypeError; and the ring composes with the resilience stack — guard
rollback stays atomic and the consensus audit stays a bit-exact no-op on
healthy steps.
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
from grace_tpu.memories import NoneMemory, ResidualMemory
from grace_tpu.parallel import shard_map
from grace_tpu.resilience import ConsensusConfig, audit_report, guarded_chain
from grace_tpu.telemetry import TelemetryReader
from grace_tpu.train import init_train_state, make_train_step
from grace_tpu.utils.metrics import guard_report

W = 8

pytestmark = pytest.mark.ring

BATCH, DIM, CLASSES = 64, 20, 4


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
# exact path: linear codecs accumulate in payload space, no requant loss
# ---------------------------------------------------------------------------

def test_none_equals_dense_mean_with_padding(mesh, rng):
    x = rng.normal(size=(W, 41)).astype(np.float32)  # 41: exercises padding
    out, _ = run_step(mesh, comm.RingAllreduce(), C.NoneCompressor(),
                      NoneMemory(), jnp.asarray(x))
    # ring accumulation order differs from jnp.sum's, so float
    # associativity allows last-ulp differences — but nothing more.
    np.testing.assert_allclose(out, x.mean(0), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("comp", [C.NoneCompressor(), C.FP16Compressor()],
                         ids=["none", "fp16"])
def test_exact_codec_matches_allgather_bit_identical(mesh, rng, comp):
    """Integer-valued gradients make every partial sum exactly
    representable in f32 AND fp16, so summation order cannot matter:
    ring == allgather + aggregate to the BIT. Any wire-format bug (wrong
    shard routing, a dropped hop, double-counted own contribution,
    mis-aligned ctx) shows up as an integer-sized error."""
    x = rng.integers(-8, 9, size=(W, 37)).astype(np.float32)

    def via_allgather(xa):
        def body(t):
            t = t[0]
            payload, ctx, _ = comp.compress(t, None, jax.random.key(0))
            return comm.Allgather().exchange(payload, ctx, comp)[None]
        fn = shard_map(body, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"), check_vma=False)
        return np.asarray(fn(xa)[0])

    ref = via_allgather(jnp.asarray(x))
    out, _ = run_step(mesh, comm.RingAllreduce(), comp, NoneMemory(),
                      jnp.asarray(x))
    np.testing.assert_array_equal(out, ref)


def test_randomk_shared_indices_exact_on_selected(mesh, rng):
    """randomk rides the exact (summable) path; its ring selection is
    per-shard (shard-folded keys) rather than global — same relaxation as
    two-shot — but every selected lane must carry the exact mean."""
    x = rng.normal(size=(W, 64)).astype(np.float32)
    out, _ = run_step(mesh, comm.RingAllreduce(),
                      C.RandomKCompressor(compress_ratio=0.5), NoneMemory(),
                      jnp.asarray(x), seed=3)
    nz = out != 0
    assert nz.sum() == 32           # 8 shards x k=4 of 8 lanes
    np.testing.assert_allclose(out[nz], x.mean(0)[nz], rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# requant path: decompress -> accumulate -> requantize each hop
# ---------------------------------------------------------------------------

def test_topk_residual_memory_sees_stage1_error(mesh, rng):
    """Error feedback covers the stage-1 shard encode exactly (the hop
    requant losses are downstream, like two-shot's stage-2):
    residual + stage-1 reconstruction == the compensated gradient."""
    x = rng.normal(size=(W, 64)).astype(np.float32)
    comp = C.TopKCompressor(compress_ratio=0.25)
    out, residual = run_step(mesh, comm.RingAllreduce(), comp,
                             ResidualMemory(), jnp.asarray(x))
    recon = x[0] - residual
    kept = recon != 0
    np.testing.assert_allclose(recon[kept], x[0][kept], rtol=1e-6)
    assert 0 < kept.sum() <= 64 * 0.25 + 8     # per-shard k of 8 lanes


def test_qsgd_hop_error_bounded_one_vs_seven_hops(mesh, rng):
    """Per-hop requantization error accumulates ~linearly in hop count,
    never explodes. W=2 runs ONE hop with no intermediate requant (hop 0
    accumulates, then the final shard encode); W=8 runs 7 hops with 6
    intermediate requants. Both relative errors must sit well under the
    analytic ladder (each QSGD encode errs <= ||t||/q per element) and the
    7-hop error must stay within a small linear factor of the 1-hop one."""
    q = 64
    comp = C.QSGDCompressor(quantum_num=q)

    def rel_err(w):
        xw = rng.normal(size=(w, 64)).astype(np.float32)
        out, _ = run_step(submesh(w), comm.RingAllreduce(), comp,
                          NoneMemory(), jnp.asarray(xw))
        return np.linalg.norm(out - xw.mean(0)) / np.linalg.norm(xw.mean(0))

    err1, err7 = rel_err(2), rel_err(8)
    assert err7 < 0.25, err7                  # sane in absolute terms
    # linear (not exponential) accumulation: 7 hops of extra encodes stay
    # within ~W x the single-hop error (generous: shard layouts differ too)
    assert err7 < 8 * max(err1, 1.0 / q), (err1, err7)


def test_signsgd_cascaded_vote_preserves_unanimity(mesh):
    """The hop requant re-signs the running partial — a cascaded vote.
    Unanimous coordinates MUST survive exactly; split coordinates may
    differ from the one-shot majority, but the output stays ±1."""
    col0 = np.ones((W,), np.float32)
    x = np.stack([col0, -col0, col0, -col0], axis=1)
    out, _ = run_step(mesh, comm.RingAllreduce(), C.SignSGDCompressor(),
                      NoneMemory(), jnp.asarray(x))
    np.testing.assert_array_equal(out, [1.0, -1.0, 1.0, -1.0])
    rng = np.random.default_rng(7)
    xr = rng.normal(size=(W, 53)).astype(np.float32)
    outr, _ = run_step(mesh, comm.RingAllreduce(), C.SignSGDCompressor(),
                       NoneMemory(), jnp.asarray(xr))
    assert set(np.unique(outr)) <= {-1.0, 1.0}


# ---------------------------------------------------------------------------
# enforced compatibility gates
# ---------------------------------------------------------------------------

def test_rejects_stateful_compressors(mesh, rng):
    x = rng.normal(size=(W, 16)).astype(np.float32)
    with pytest.raises(TypeError, match="stateless"):
        run_step(mesh, comm.RingAllreduce(), C.SignumCompressor(),
                 NoneMemory(), jnp.asarray(x))


def test_rejects_codecs_without_requant_or_summable(mesh, rng):
    """The Allreduce-style compat matrix is enforced, not documented: a
    codec that is neither linear nor hop-requant-capable (its payload
    carries structure a partial sum destroys) is a TypeError."""
    x = rng.normal(size=(W, 16)).astype(np.float32)
    for comp in [C.OneBitCompressor(), C.SketchCompressor(bins=16),
                 C.DgcCompressor(compress_ratio=0.5)]:
        with pytest.raises(TypeError, match="supports_hop_requant"):
            run_step(mesh, comm.RingAllreduce(), comp, NoneMemory(),
                     jnp.asarray(x))


def test_rejects_bare_exchange(mesh):
    with pytest.raises(TypeError, match="step"):
        comm.RingAllreduce().exchange((jnp.zeros(4),), None,
                                      C.NoneCompressor())


def test_catalog_requant_flags():
    """The shipped hop-requant matrix: topk/qsgd/signsgd opt in; codecs
    with non-summable structural payloads stay out."""
    assert C.TopKCompressor(0.1).supports_hop_requant
    assert C.QSGDCompressor().supports_hop_requant
    assert C.SignSGDCompressor().supports_hop_requant
    for comp in [C.OneBitCompressor(), C.SketchCompressor(),
                 C.DgcCompressor(0.1), C.ThresholdCompressor(0.01),
                 C.AdaqCompressor(0.1)]:
        assert not comp.supports_hop_requant, comp


def test_from_params_builds_ring():
    g = grace_from_params({"compressor": "topk", "compress_ratio": 0.3,
                           "memory": "residual", "communicator": "ring"})
    assert isinstance(g.communicator, comm.RingAllreduce)
    assert g.communicator.shard_parallel


# ---------------------------------------------------------------------------
# wire-byte accounting: the shared recv_wire_bytes model + live telemetry
# ---------------------------------------------------------------------------

def test_recv_wire_bytes_model():
    """One model shared by the tuner's pricing and the telemetry ring:
    ring receives ~2·payload·(W−1)/W — flat in W — vs allgather's
    (W−1)·payload; under half allgather's bytes from W=8 up."""
    payload, n = 1000, 4096
    ring = comm.RingAllreduce()
    gather = comm.Allgather()
    for w in (2, 4, 8, 64, 256):
        rb = ring.recv_wire_bytes(payload, n, w)
        gb = gather.recv_wire_bytes(payload, n, w)
        assert rb == 2 * payload * (w - 1) // w
        assert gb == payload * (w - 1)
        if w >= 4:
            assert rb < gb
        if w >= 8:
            assert rb < 0.5 * gb


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
def test_telemetry_wire_bytes_ring_under_half_of_allgather(mesh):
    """ISSUE 4 acceptance: telemetry-reported wire bytes per step on the
    8-device mesh are < 0.5× the Allgather communicator's for the same
    compressor config — measured from real sharded steps, not a formula."""
    x, y = _problem()
    base = {"compressor": "topk", "compress_ratio": 0.3,
            "memory": "residual", "fusion": "flat", "telemetry": 16}

    def wire_of(communicator):
        state, step = _build(mesh, dict(base, communicator=communicator))
        for _ in range(2):
            state, _ = step(state, (x, y))
        rows = TelemetryReader(sink=None, every=100).flush(state)
        assert rows
        return rows[-1]["wire_bytes"], rows[-1]["dense_bytes"]

    ring_b, dense_r = wire_of("ring")
    gather_b, dense_g = wire_of("allgather")
    assert dense_r == dense_g                 # same gradients, same model
    assert ring_b < 0.5 * gather_b, (ring_b, gather_b)
    # and both agree with the shared static model at W=8
    assert gather_b / ring_b == pytest.approx(7 / (2 * 7 / 8), rel=1e-6)


# ---------------------------------------------------------------------------
# resilience composition: guard rollback + consensus audit
# ---------------------------------------------------------------------------

RING_EF = {"compressor": "topk", "compress_ratio": 0.3,
           "memory": "residual", "communicator": "ring", "escape": "fp16"}


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(p), np.asarray(q))
               for p, q in zip(la, lb))


@pytest.mark.chaos
def test_guard_rolls_back_ring_step_atomically(mesh):
    """A NaN in one rank's batch shard propagates around the ring to all
    ranks; the guard must skip the step atomically — params and every
    mem leaf bitwise-unchanged — exactly as on the allgather path."""
    x, y = _problem()
    state, step = _build(mesh, RING_EF, guard=True)
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


@pytest.mark.consensus
def test_consensus_audit_is_noop_on_healthy_ring_run(mesh):
    """The consensus audit must stay a bit-exact no-op over the ring: same
    loss trajectory and params as the audit-off run, zero repairs."""
    x, y = _problem()
    cfg = dict(RING_EF, consensus=True)
    on = ConsensusConfig(audit_every=2)
    s_on, step_on = _build(mesh, cfg, consensus=on)
    s_off, step_off = _build(mesh, dict(RING_EF), guard=True)
    for _ in range(6):
        s_on, l_on = step_on(s_on, (x, y))
        s_off, l_off = step_off(s_off, (x, y))
    assert float(l_on) == float(l_off)
    assert _leaves_equal(s_on.params, s_off.params)
    rep = audit_report(s_on)
    assert rep["audits"] == 3 and rep["repairs"] == 0
