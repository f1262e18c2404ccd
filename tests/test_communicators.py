"""Communicator semantics on a real 8-device (simulated CPU) mesh.

This is the "fake backend" the reference never had (SURVEY.md §4): genuine
all_gather/psum collectives, single process.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from grace_tpu.parallel import shard_map
from grace_tpu import comm
from grace_tpu import compressors as C

W = 8


def run_exchange(mesh, communicator, compressor, per_rank, state=None, seed=0):
    """per_rank: [W, ...] array, one slice per rank; returns one rank's output."""

    def body(x):
        x = x[0]  # shard_map gives [1, ...] per device on the data axis
        st = state if state is not None else compressor.init_state(x)
        payload, ctx, _ = compressor.compress(x, st, jax.random.key(seed))
        return communicator.exchange(payload, ctx, compressor)[None]

    fn = shard_map(body, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"), check_vma=False)
    return np.asarray(fn(per_rank)[0])


def test_allreduce_none_average(mesh, rng):
    x = rng.normal(size=(W, 16)).astype(np.float32)
    out = run_exchange(mesh, comm.Allreduce(), C.NoneCompressor(), jnp.asarray(x))
    np.testing.assert_allclose(out, x.mean(0), rtol=1e-5)


def test_allreduce_none_sum(mesh, rng):
    x = rng.normal(size=(W, 16)).astype(np.float32)
    out = run_exchange(mesh, comm.Allreduce(), C.NoneCompressor(average=False),
                       jnp.asarray(x))
    np.testing.assert_allclose(out, x.sum(0), rtol=1e-5)


def test_allgather_topk(mesh, rng):
    x = rng.normal(size=(W, 50)).astype(np.float32)
    comp = C.TopKCompressor(compress_ratio=0.2)
    out = run_exchange(mesh, comm.Allgather(), comp, jnp.asarray(x))
    # expected: mean over ranks of each rank's top-10-sparsified tensor
    expect = np.zeros((W, 50), np.float32)
    for r in range(W):
        idx = np.argsort(-np.abs(x[r]))[:10]
        expect[r, idx] = x[r, idx]
    np.testing.assert_allclose(out, expect.mean(0), rtol=1e-5)


def test_allgather_signsgd_majority_vote(mesh):
    # 5 ranks positive, 3 negative at coord 0; opposite at coord 1
    col0 = np.array([1, 1, 1, 1, 1, -1, -1, -1], np.float32)
    x = np.stack([col0, -col0], axis=1)
    comp = C.SignSGDCompressor()
    out = run_exchange(mesh, comm.Allgather(), comp, jnp.asarray(x))
    np.testing.assert_array_equal(out, [1.0, -1.0])


def test_allgather_qsgd_per_rank_norms(mesh, rng):
    """Each rank has a different norm; ctx-replication contract must hold."""
    x = (rng.normal(size=(W, 40)) * np.arange(1, W + 1)[:, None]).astype(np.float32)
    comp = C.QSGDCompressor(quantum_num=127)
    out = run_exchange(mesh, comm.Allgather(), comp, jnp.asarray(x))
    # error per rank bounded by its norm/q; mean over ranks
    bound = np.linalg.norm(x, axis=1).sum() / 127 / W + 1e-5
    assert np.max(np.abs(out - x.mean(0))) <= bound


def test_allgather_randomk_shared_indices(mesh, rng):
    x = rng.normal(size=(W, 30)).astype(np.float32)
    comp = C.RandomKCompressor(compress_ratio=0.5)
    out = run_exchange(mesh, comm.Allgather(), comp, jnp.asarray(x), seed=3)
    # all ranks picked the same indices -> result is mean of x at those coords
    nz = out != 0
    assert nz.sum() == 15
    np.testing.assert_allclose(out[nz], x.mean(0)[nz], rtol=1e-5)


def test_broadcast_equals_allgather(mesh, rng):
    x = rng.normal(size=(W, 24)).astype(np.float32)
    comp = C.FP16Compressor()
    a = run_exchange(mesh, comm.Allgather(), comp, jnp.asarray(x))
    b = run_exchange(mesh, comm.Broadcast(), comp, jnp.asarray(x))
    np.testing.assert_array_equal(a, b)


def test_sign_allreduce_matches_allgather_majority(mesh, rng):
    """psum-based majority vote == allgather + SignSGD.aggregate (SURVEY.md
    §7 hard part 4) — same result, fixed-cost collective."""
    x = rng.normal(size=(W, 33)).astype(np.float32)
    comp = C.SignSGDCompressor()
    via_gather = run_exchange(mesh, comm.Allgather(), comp, jnp.asarray(x))
    via_psum = run_exchange(mesh, comm.SignAllreduce(), comp, jnp.asarray(x))
    np.testing.assert_array_equal(via_gather, via_psum)
    assert set(np.unique(via_psum)) <= {-1.0, 1.0}


def test_sign_allreduce_rejects_non_vote_compressors(mesh, rng):
    import pytest
    x = rng.normal(size=(W, 16)).astype(np.float32)
    with pytest.raises(TypeError, match="majority-vote"):
        run_exchange(mesh, comm.SignAllreduce(), C.TopKCompressor(0.5),
                     jnp.asarray(x))
    # average=False is NOT sufficient: EF-SignSGD's aggregate divides by lr,
    # which the re-sign would silently drop.
    with pytest.raises(TypeError, match="majority-vote"):
        run_exchange(mesh, comm.SignAllreduce(), C.EFSignSGDCompressor(),
                     jnp.asarray(x))


def test_allreduce_routes_sign_methods_through_vote(mesh, rng):
    """Regression: 'allreduce' + signsgd once psummed the packed sign BYTES
    and decompressed the byte-sum — garbage votes that made toy training
    climb. The generic Allreduce must route vote_aggregate compressors
    through the psum majority vote (== allgather + aggregate)."""
    x = rng.normal(size=(W, 33)).astype(np.float32)
    comp = C.SignSGDCompressor()
    via_gather = run_exchange(mesh, comm.Allgather(), comp, jnp.asarray(x))
    via_allreduce = run_exchange(mesh, comm.Allreduce(), comp, jnp.asarray(x))
    np.testing.assert_array_equal(via_gather, via_allreduce)


def test_allreduce_rejects_non_summable_payloads(mesh, rng):
    """The reference only documents the Allreduce compatibility matrix
    (IMPLEMENTING.md:43-45) and silently sums Top-K values belonging to
    different per-rank indices; here the combination is a TypeError."""
    import pytest
    x = rng.normal(size=(W, 16)).astype(np.float32)
    for comp in [C.TopKCompressor(0.5), C.QSGDCompressor(),
                 C.OneBitCompressor(), C.EFSignSGDCompressor()]:
        with pytest.raises(TypeError, match="summable_payload"):
            run_exchange(mesh, comm.Allreduce(), comp, jnp.asarray(x))


def test_sign_allreduce_from_params(mesh, rng):
    from grace_tpu import grace_from_params
    g = grace_from_params({"compressor": "signum",
                           "communicator": "sign_allreduce"})
    assert isinstance(g.communicator, comm.SignAllreduce)
    x = rng.normal(size=(W, 16)).astype(np.float32)
    out = run_exchange(mesh, g.communicator, g.compressor, jnp.asarray(x))
    assert set(np.unique(out)) <= {-1.0, 1.0}


def test_powersgd_inside_compress(mesh, rng):
    """PowerSGD's collectives run inside compress; empty payload path."""
    x = rng.normal(size=(W, 12, 6)).astype(np.float32)
    comp = C.PowerSGDCompressor(rank=6, axis_name="data")

    out = run_exchange(mesh, comm.Allreduce(), comp, jnp.asarray(x))
    # rank 6 >= min(n, m) = 6 -> reconstruction should approximate the mean
    np.testing.assert_allclose(out, x.mean(0), atol=1e-3)


def test_powersgd_hwio_matricization(mesh, rng):
    """4-D conv kernels factor on the output-channel (last) dim — the
    (shape[0], -1) rule of the torch reference would give a degenerate
    (3, rest) matrix for HWIO layouts (wire cost > dense; see
    compressors/powersgd.py docstring)."""
    x = rng.normal(size=(W, 3, 3, 4, 8)).astype(np.float32)
    comp = C.PowerSGDCompressor(rank=4, axis_name="data")

    q0 = comp.init_state(jnp.asarray(x[0]))
    # Q factors over the 8-channel output dim, not the 3-tall kernel dim.
    assert q0.shape == (8, 4)

    out = run_exchange(mesh, comm.Allreduce(), comp, jnp.asarray(x))
    assert out.shape == x.shape[1:]
    # rank-4 truncation of a (36, 8) matrix: inexact but must be a real
    # low-rank approximation of the mean, not garbage.
    err = np.linalg.norm(out - x.mean(0)) / np.linalg.norm(x.mean(0))
    assert err < 0.9, err


def test_powersgd_1d_bypass(mesh, rng):
    x = rng.normal(size=(W, 9)).astype(np.float32)
    comp = C.PowerSGDCompressor(rank=2, axis_name="data")
    out = run_exchange(mesh, comm.Allreduce(), comp, jnp.asarray(x))
    np.testing.assert_allclose(out, x.mean(0), rtol=1e-5)


def test_allreduce_int_payload_average_raises(mesh, rng):
    x = rng.normal(size=(W, 16)).astype(np.float32)
    try:
        run_exchange(mesh, comm.Allreduce(), C.QSGDCompressor(quantum_num=64),
                     jnp.asarray(x))
        raised = False
    except TypeError:
        raised = True
    assert raised


def run_step(mesh, communicator, compressor, memory, per_rank, seed=0):
    """Full pipeline step (compensate→compress→update→exchange) per rank;
    returns (output, new_mem_state) for rank 0."""

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
    out, ms = fn(per_rank)
    return np.asarray(out[0]), np.asarray(ms[0])


class TestTwoShotAllreduce:
    """Scatter-reduce-recompress all-reduce (O(k) wire vs allgather's O(Wk))."""

    def test_none_equals_dense_mean(self, mesh, rng):
        from grace_tpu.memories import NoneMemory
        x = rng.normal(size=(W, 41)).astype(np.float32)  # 41: exercises padding
        out, _ = run_step(mesh, comm.TwoShotAllreduce(), C.NoneCompressor(),
                          NoneMemory(), jnp.asarray(x))
        np.testing.assert_allclose(out, x.mean(0), rtol=1e-6)

    def test_signsgd_equals_allgather_vote(self, mesh, rng):
        """Vote is elementwise, so chunking cannot change it, and stage-2
        sign-compression of ±1 is lossless: two-shot == allgather, exactly."""
        from grace_tpu.memories import NoneMemory
        x = rng.normal(size=(W, 53)).astype(np.float32)
        comp = C.SignSGDCompressor()
        via_gather = run_exchange(mesh, comm.Allgather(), comp, jnp.asarray(x))
        via_twoshot, _ = run_step(mesh, comm.TwoShotAllreduce(), comp,
                                  NoneMemory(), jnp.asarray(x))
        np.testing.assert_array_equal(via_gather, via_twoshot)

    def test_topk_residual_memory_sees_stage1_error(self, mesh, rng):
        """ResidualMemory.update must receive the stage-1 reconstruction:
        residual + reconstruction == the compensated gradient."""
        from grace_tpu.memories import ResidualMemory
        x = rng.normal(size=(W, 64)).astype(np.float32)
        comp = C.TopKCompressor(compress_ratio=0.25)
        out, residual = run_step(mesh, comm.TwoShotAllreduce(), comp,
                                 ResidualMemory(), jnp.asarray(x))
        recon = x[0] - residual           # stage-1 decode of rank 0's chunks
        # every reconstructed lane is either 0 (dropped) or the original value
        kept = recon != 0
        np.testing.assert_allclose(recon[kept], x[0][kept], rtol=1e-6)
        assert 0 < kept.sum() <= 64 * 0.25 + 8  # per-chunk k=2 of 8 lanes

    def test_rejects_stateful_compressors(self, mesh, rng):
        import pytest
        from grace_tpu.memories import NoneMemory
        x = rng.normal(size=(W, 16)).astype(np.float32)
        with pytest.raises(TypeError, match="stateless"):
            run_step(mesh, comm.TwoShotAllreduce(), C.SignumCompressor(),
                     NoneMemory(), jnp.asarray(x))

    def test_from_params_builds_twoshot(self, mesh):
        # End-to-end convergence through grace_from_params is covered by the
        # twoshot entries in tests/test_transform.py CONFIGS.
        from grace_tpu import grace_from_params
        g = grace_from_params({"compressor": "topk", "compress_ratio": 0.3,
                               "memory": "residual",
                               "communicator": "twoshot"})
        assert isinstance(g.communicator, comm.TwoShotAllreduce)

    def test_rejects_data_derived_ctx(self, mesh, rng):
        """Stage 3 decodes every rank's gathered chunk with the rank-local
        ctx2, which is only sound for data-free ctx arrays. A codec that
        stashes e.g. its input's norm in ctx (legal under the base Ctx
        contract) must be rejected at trace time, not silently corrupt."""
        import pytest
        from grace_tpu.memories import NoneMemory

        class NormInCtx(C.NoneCompressor):
            def compress(self, x, state, rng):
                norm = jnp.maximum(jnp.linalg.norm(x), 1e-12)
                return (x / norm,), {"norm": norm}, state

            def decompress(self, payload, ctx):
                return payload[0] * ctx["norm"]

        x = rng.normal(size=(W, 32)).astype(np.float32)
        with pytest.raises(TypeError, match="data-free ctx"):
            run_step(mesh, comm.TwoShotAllreduce(), NormInCtx(),
                     NoneMemory(), jnp.asarray(x))

    def test_catalog_stateless_codecs_have_data_free_ctx(self):
        """Every stateless catalog codec must keep data-derived arrays in
        the payload (the TwoShot soundness condition, checked structurally
        by comm.ctx_is_data_free)."""
        codecs = [C.NoneCompressor(), C.FP16Compressor(),
                  C.TopKCompressor(compress_ratio=0.1),
                  C.RandomKCompressor(compress_ratio=0.1),
                  C.ThresholdCompressor(threshold=0.01),
                  C.QSGDCompressor(quantum_num=64), C.TernGradCompressor(),
                  C.SignSGDCompressor(), C.EFSignSGDCompressor(lr=0.1),
                  C.OneBitCompressor(), C.NaturalCompressor(),
                  C.DgcCompressor(compress_ratio=0.1), C.U8bitCompressor(),
                  C.SketchCompressor(bins=64),
                  C.AdaqCompressor(compress_ratio=0.1),
                  C.InceptionNCompressor()]
        for codec in codecs:
            assert comm.ctx_is_data_free(codec, 256, jnp.float32), codec

    def test_stage2_feedback_tightens_tracking(self, mesh, rng):
        """ScaleCom-style owner error feedback: with stage2_feedback the
        cumulative aggregated gradient tracks the allgather (single-loss)
        trajectory at least as closely as without it."""
        from grace_tpu.memories import ResidualMemory

        def accumulate(communicator):
            rng_local = np.random.default_rng(7)
            grads = rng_local.normal(size=(6, W, 96)).astype(np.float32)
            comp = C.TopKCompressor(compress_ratio=0.25)
            memory = ResidualMemory()

            def body(gs):
                gs = gs[:, 0]                       # (steps, n) local grads
                ms = memory.init_state(gs[0])
                total = jnp.zeros_like(gs[0])
                for t in range(gs.shape[0]):
                    out, ms, _ = communicator.step(
                        gs[t], ms, None, memory, comp, jax.random.key(t))
                    total = total + out
                return total[None]

            fn = shard_map(body, mesh=mesh, in_specs=P(None, "data"),
                               out_specs=P("data"), check_vma=False)
            return np.asarray(fn(jnp.asarray(grads))[0]), grads

        got_fb, grads = accumulate(comm.TwoShotAllreduce(stage2_feedback=True))
        got_no, _ = accumulate(comm.TwoShotAllreduce())
        ref, _ = accumulate(comm.Allgather())   # single-compression reference
        err_fb = np.linalg.norm(got_fb - ref)
        err_no = np.linalg.norm(got_no - ref)
        assert err_fb <= err_no + 1e-5, (err_fb, err_no)

    def test_stage2_feedback_rejects_dgc_memory(self, mesh, rng):
        import pytest
        from grace_tpu.memories import DgcMemory
        x = rng.normal(size=(W, 32)).astype(np.float32)
        with pytest.raises(TypeError, match="stage2_feedback"):
            run_step(mesh, comm.TwoShotAllreduce(stage2_feedback=True),
                     C.TopKCompressor(0.25), DgcMemory(), jnp.asarray(x))


def test_allreduce_chunked_psum_matches_whole(mesh, rng, monkeypatch):
    """The oversized-1-D chunked psum (comm._psum, the XLA layout-pathology
    guard) is numerically identical to one whole psum. Thresholds are
    monkeypatched small so the test exercises the chunk seams (including a
    ragged tail) without a 33M-element buffer."""
    monkeypatch.setattr(comm, "_PSUM_CHUNK_THRESHOLD", 1000)
    monkeypatch.setattr(comm, "_PSUM_CHUNK_ELEMS", 768)
    x = rng.standard_normal((W, 2500)).astype(np.float32)  # 2500 % 768 != 0
    out = run_exchange(mesh, comm.Allreduce(), C.NoneCompressor(average=False),
                       jnp.asarray(x))
    np.testing.assert_allclose(out, x.sum(0), rtol=1e-5)
    # 2-D payloads and small 1-D payloads must bypass chunking entirely.
    y = rng.standard_normal((W, 40, 12)).astype(np.float32)
    out2 = run_exchange(mesh, comm.Allreduce(),
                        C.NoneCompressor(average=False), jnp.asarray(y))
    np.testing.assert_allclose(out2, y.sum(0), rtol=1e-5)


# ---------------------------------------------------------------------------
# Allgather of chunk-structured top-k payloads: aggregate, then reshape
# ---------------------------------------------------------------------------

# (shape, ratio, wire dtype): k a multiple of 128 with rows*k == numel; k no
# multiple of 128 with rows*k > numel (padding); a leaf with k = 1; bfloat16
# wire values.
_ROWS_CASES = {
    "k128": ((128, 100), 0.01, "float32"),        # k=128, rows=100
    "k34-padded": ((3, 3, 16, 24), 0.01, "float32"),   # k=34, 102*34 > 3456
    "k1": ((64,), 0.01, "float32"),               # k=1, rows=64
    "bf16-wire": ((40, 50), 0.05, "bfloat16"),    # k=100, rows=20
}


class _SumTopK(C.TopKCompressor):
    average = False         # a class flag of Compressor, not a field


def _chunk_codec(case, average=True):
    shape, ratio, wire = _ROWS_CASES[case]
    cls = C.TopKCompressor if average else _SumTopK
    return shape, cls(compress_ratio=ratio, algorithm="chunk",
                      wire_dtype=wire)


def _rank_inputs(rng, world, shape, k, disjoint):
    """Per-rank gradients. ``disjoint``: rank r is zero outside row r of the
    (rows, k) view, so the ranks keep disjoint positions and every element
    of the sum has one non-zero addend."""
    n = int(np.prod(shape))
    x = rng.standard_normal((world, n)).astype(np.float32)
    if disjoint:
        keep = np.zeros((world, n), bool)
        for r in range(world):
            keep[r, r * k:(r + 1) * k] = True
        x = np.where(keep, x, 0.0).astype(np.float32)
    return x.reshape((world,) + shape)


def _exchange_both_ways(world, comp, per_rank):
    """Rank 0's output of ``Allgather.exchange`` and of the per-rank decode
    it replaces (vmap(decompress) + aggregate + average) on the same
    gathered payloads, plus whether the compressor's hook answered."""
    mesh = Mesh(np.asarray(jax.devices()[:world]), ("data",))
    answered = []

    def body(x):
        x = x[0]
        payload, ctx, _ = comp.compress(x, None, jax.random.key(0))
        out = comm.Allgather().exchange(payload, ctx, comp)
        gathered = tuple(jax.lax.all_gather(t, "data") for t in payload)
        answered.append(comp.fused_aggregate_decompress(
            gathered, ctx, world) is not None)
        ref = comp.aggregate(jax.vmap(
            lambda p: comp.decompress(p, ctx))(gathered))
        if comp.average:
            ref = ref / world
        return out[None], ref[None]

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("data"),
                           out_specs=(P("data"), P("data")), check_vma=False))
    out, ref = fn(jnp.asarray(per_rank))
    return np.asarray(out[0]), np.asarray(ref[0]), answered[0]


@pytest.mark.parametrize("average", [True, False], ids=["mean", "sum"])
@pytest.mark.parametrize("disjoint", [False, True],
                         ids=["overlapping", "disjoint"])
@pytest.mark.parametrize("case", sorted(_ROWS_CASES))
@pytest.mark.parametrize("world", [1, 2, 4])
def test_allgather_chunk_topk_aggregates_rows_like_per_rank_decode(
        rng, world, case, disjoint, average):
    """The staged aggregate-then-reshape decode (ops.sparse.
    chunkwise_dense_sum) against vmap(decompress) + aggregate (+ average)
    on a real ``world``-device mesh: equal to 1e-6 of the leaf's norm, and
    bitwise where the ranks' kept positions are disjoint. At world == 1 the
    hook declines and the exchange IS the per-rank decode."""
    shape, comp = _chunk_codec(case, average=average)
    k = max(1, int(np.prod(shape) * comp.compress_ratio))
    x = _rank_inputs(rng, world, shape, k, disjoint)
    out, ref, answered = _exchange_both_ways(world, comp, x)
    assert answered == (world > 1)
    assert out.shape == shape and out.dtype == ref.dtype
    assert np.linalg.norm(ref) > 0
    if disjoint or world == 1:
        np.testing.assert_array_equal(out, ref)
    else:
        assert np.linalg.norm(out - ref) <= 1e-6 * np.linalg.norm(ref)


@pytest.mark.parametrize("average", [True, False], ids=["mean", "sum"])
@pytest.mark.parametrize("case", sorted(_ROWS_CASES))
def test_allgather_w1_decodes_a_row_slices_leaf_directly(
        rng, monkeypatch, case, average):
    """At one device a leaf on the row-slices route (ops.sparse: its view
    passes the constant, set small here, and k is no multiple of 128) is
    decoded by the hook as the memory update decodes it — one decode for
    XLA to keep, where the vmapped one would chain a row block at a time —
    and equals the per-rank decode bitwise. ``k128`` stays on the view, and
    there the hook declines as before."""
    from grace_tpu.ops import sparse
    monkeypatch.setattr(sparse, "RELAYOUT_LOOP_ELEMENTS", 40)
    shape, comp = _chunk_codec(case, average=average)
    n = int(np.prod(shape))
    k = max(1, int(n * comp.compress_ratio))
    x = _rank_inputs(rng, 1, shape, k, disjoint=False)
    out, ref, answered = _exchange_both_ways(1, comp, x)
    assert answered == sparse.takes_row_slices(-(-n // k), k)
    assert answered == (case != "k128")
    assert out.shape == shape and out.dtype == ref.dtype
    assert np.linalg.norm(ref) > 0
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("world", [1, 2, 4])
def test_chunkwise_dense_sum_is_the_sum_of_chunkwise_dense(rng, world):
    """The function alone, off the mesh: W payloads with padding (rows*k >
    numel) against W single decodes added in rank order — bitwise, both
    being the same left-to-right float32 sum."""
    from grace_tpu.ops.sparse import chunkwise_dense, chunkwise_dense_sum
    shape, k = (7, 11, 13), 17                     # 1001 elements, rows=59
    numel = int(np.prod(shape))
    rows = -(-numel // k)
    values = jnp.asarray(rng.standard_normal((world, k)), jnp.float32)
    win_row = jnp.asarray(rng.integers(0, rows - 1, (world, k)), jnp.int32)
    got = chunkwise_dense_sum(values, win_row, rows, numel, shape)
    want = chunkwise_dense(values[0], win_row[0], rows, numel, shape)
    for w in range(1, world):
        want = want + chunkwise_dense(values[w], win_row[w], rows, numel,
                                      shape)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("why", ["sub-k", "exact", "rows<2"])
def test_aggregate_rows_declines_what_decompress_scatters(rng, why):
    """A payload without the full-column chunk structure keeps the
    communicator's vmapped decode, whose ``decompress`` takes the scatter
    path: a sub-k payload (a two-shot slice), a non-chunk algorithm, a
    leaf with fewer than two rows per chunk."""
    world, n = 4, 1000
    comp = C.TopKCompressor(compress_ratio=0.9 if why == "rows<2" else 0.05,
                            algorithm="exact" if why == "exact" else "chunk")
    k = int(n * comp.compress_ratio)
    width = k // 2 if why == "sub-k" else k
    values = jnp.asarray(rng.standard_normal((world, width)), jnp.float32)
    indices = jnp.asarray(np.stack([rng.permutation(n)[:width]
                                    for _ in range(world)]), jnp.int32)
    ctx = (n, (n,), jnp.float32)
    assert comp.fused_aggregate_decompress((values, indices), ctx,
                                           world) is None
    dense = jax.vmap(lambda p: comp.decompress(p, ctx))((values, indices))
    expect = np.zeros((world, n), np.float32)
    for r in range(world):
        expect[r, np.asarray(indices[r])] = np.asarray(values[r])
    np.testing.assert_array_equal(np.asarray(dense), expect)


@pytest.mark.parametrize("world", [1, 4])
def test_demanded_kernel_still_takes_the_kernel(rng, monkeypatch, world):
    """``use_pallas=True`` on a TPU answers with the Pallas aggregate at
    any world, never with the staged rows path."""
    import grace_tpu.ops.pallas_topk as pallas_topk
    n, k = 4000, 40
    calls = []

    def kernel(values, win, k_, numel, *, average, interpret):
        calls.append((values.shape, average, interpret))
        return jnp.zeros((numel,), jnp.float32)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_topk, "chunk_aggregate_dense", kernel)
    comp = C.TopKCompressor(compress_ratio=0.01, algorithm="chunk",
                            use_pallas=True)
    monkeypatch.setattr(
        C.TopKCompressor, "_aggregate_rows",
        lambda *a, **kw: pytest.fail("staged path taken under use_pallas"))
    values = jnp.asarray(rng.standard_normal((world, k)), jnp.float32)
    indices = jnp.tile(jnp.arange(k, dtype=jnp.int32), (world, 1))
    out = comp.fused_aggregate_decompress((values, indices),
                                          (n, (n,), jnp.float32), world)
    assert out.shape == (n,)
    assert calls == [((world, k), True, False)]
