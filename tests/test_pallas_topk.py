"""Fused Pallas chunk-Top-K local pipeline vs the plain XLA path.

The Communicator.step fast path (core.py) collapses compensate -> compress
-> residual-update into ops/pallas_topk.py's one-pass kernel whenever the
memory declares linear error feedback. These tests pin the contract: the
fused path must be BIT-IDENTICAL to the staged path — payload, exchanged
output, and residual state — across awkward paddings, feedback
coefficients, and the bf16 wire format. Interpreter mode runs the same
kernel code on CPU (use_pallas=True off-TPU).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grace_tpu.parallel import shard_map
from grace_tpu.comm import Identity
from grace_tpu.compressors import TopKCompressor
from grace_tpu.memories import EFSignSGDMemory, ResidualMemory
from grace_tpu.ops.pallas_topk import (chunk_compress_feedback,
                                       compress_block_cols)


def _step(compressor, memory, x, resid, rng):
    comm = Identity(axis_name="data")
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))

    def body(x, resid):
        return comm.step(x, resid, None, memory, compressor, rng)[:2]

    return shard_map(body, mesh=mesh, in_specs=(P(), P()),
                         out_specs=(P(), P()), check_vma=False)(x, resid)


@pytest.mark.parametrize("n,ratio", [(1000, 0.01), (1003, 0.013),
                                     (4096, 0.25), (257, 0.04)])
def test_fused_step_bit_identical(n, ratio):
    key = jax.random.key(0)
    x = jax.random.normal(key, (n,), jnp.float32)
    resid = jax.random.normal(jax.random.key(1), (n,), jnp.float32) * 0.1
    rng = jax.random.key(2)
    mem = ResidualMemory()
    plain = TopKCompressor(compress_ratio=ratio, algorithm="chunk",
                           use_pallas=False)
    fused = TopKCompressor(compress_ratio=ratio, algorithm="chunk",
                           use_pallas=True)
    out_p, mem_p = _step(plain, mem, x, resid, rng)
    out_f, mem_f = _step(fused, mem, x, resid, rng)
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_f))
    np.testing.assert_array_equal(np.asarray(mem_p), np.asarray(mem_f))


def test_fused_respects_feedback_coeffs():
    n = 2048
    x = jax.random.normal(jax.random.key(0), (n,), jnp.float32)
    resid = jax.random.normal(jax.random.key(1), (n,), jnp.float32)
    rng = jax.random.key(2)
    for mem in (ResidualMemory(beta=0.9, gamma=0.5), EFSignSGDMemory(lr=0.3)):
        plain = TopKCompressor(compress_ratio=0.05, algorithm="chunk",
                               use_pallas=False)
        fused = TopKCompressor(compress_ratio=0.05, algorithm="chunk",
                               use_pallas=True)
        out_p, mem_p = _step(plain, mem, x, resid, rng)
        out_f, mem_f = _step(fused, mem, x, resid, rng)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_f),
                                   rtol=0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(mem_p), np.asarray(mem_f),
                                   rtol=0, atol=1e-6)


def test_fused_bf16_wire_rounding_lands_in_residual():
    n = 3000
    x = jax.random.normal(jax.random.key(0), (n,), jnp.float32) * 3.7
    resid = jnp.zeros((n,), jnp.float32)
    rng = jax.random.key(2)
    mem = ResidualMemory()
    plain = TopKCompressor(compress_ratio=0.02, algorithm="chunk",
                           wire_dtype="bfloat16", use_pallas=False)
    fused = TopKCompressor(compress_ratio=0.02, algorithm="chunk",
                           wire_dtype="bfloat16", use_pallas=True)
    out_p, mem_p = _step(plain, mem, x, resid, rng)
    out_f, mem_f = _step(fused, mem, x, resid, rng)
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_f))
    np.testing.assert_array_equal(np.asarray(mem_p), np.asarray(mem_f))
    # the rounding error must be non-trivially present (bf16 has 8 mantissa
    # bits; 3.7-scaled normals round visibly)
    assert float(jnp.abs(mem_f).max()) > 0


def test_kernel_indices_in_range_and_unique():
    for n, ratio in [(1000, 0.01), (999, 0.1), (130, 0.5)]:
        k = max(1, int(n * ratio))
        if n < 2 * k:
            continue
        flat = jax.random.normal(jax.random.key(3), (n,), jnp.float32)
        vals, win, resid = chunk_compress_feedback(
            flat, None, k, interpret=True)
        idx = np.asarray(win) * k + np.arange(k)
        assert idx.max() < n and idx.min() >= 0
        assert len(np.unique(idx)) == k
        # winners zeroed, losers intact
        dense = np.zeros(n, np.float32)
        dense[idx] = np.asarray(vals)
        np.testing.assert_allclose(np.asarray(resid),
                                   np.asarray(flat) - dense, atol=1e-7)


def test_nan_column_keeps_indices_in_range():
    n, k = 1000, 10
    flat = jax.random.normal(jax.random.key(0), (n,), jnp.float32)
    flat = flat.at[437].set(jnp.nan)        # poisons column 437 % 10 = 7
    vals, win, resid = chunk_compress_feedback(flat, None, k, interpret=True)
    idx = np.asarray(win) * k + np.arange(k)
    assert idx.max() < n and idx.min() >= 0
    assert len(np.unique(idx)) == k
    # the NaN lane stays visible in the residual (not silently dropped)
    assert np.isnan(np.asarray(resid)).any()


def test_vmem_overflow_ratio_falls_back():
    # ~10k rows at ratio 1e-4 cannot fit 128-lane f32 blocks in the VMEM
    # budget; the fused hook must decline rather than blow compilation.
    from grace_tpu.ops.pallas_topk import (aggregate_block_cols,
                                           compress_block_cols)
    assert compress_block_cols(10_000) == 0
    # pod-scale worlds inflate the aggregate kernel's input blocks
    assert aggregate_block_cols(4, 65536) == 0
    assert aggregate_block_cols(4, 8) >= 128
    comp = TopKCompressor(compress_ratio=1e-4, algorithm="chunk",
                          use_pallas=True)
    x = jnp.ones((200_000,), jnp.float32)
    st = jnp.zeros((200_000,), jnp.float32)
    assert comp.fused_feedback_compress(x, st, (1.0, 1.0),
                                        jax.random.key(0)) is None


def test_bf16_buffer_falls_back_to_staged_path():
    comp = TopKCompressor(compress_ratio=0.1, algorithm="chunk",
                          use_pallas=True)
    x = jnp.ones((1000,), jnp.bfloat16)
    st = jnp.zeros((1000,), jnp.bfloat16)
    assert comp.fused_feedback_compress(x, st, (1.0, 1.0),
                                        jax.random.key(0)) is None


@pytest.mark.parametrize("world,n,ratio", [(1, 1000, 0.01), (4, 1003, 0.013),
                                           (8, 4096, 0.25),
                                           # > _AGG_UNROLL_MAX: exercises the
                                           # lax.fori_loop accumulation path
                                           (40, 1000, 0.01)])
def test_aggregate_kernel_matches_staged_exchange(world, n, ratio):
    """Exchange-side kernel == vmapped one-hot decompress + sum + average,
    including colliding indices across ranks and the tail row."""
    from grace_tpu.ops.pallas_topk import chunk_aggregate_dense

    from grace_tpu.compressors.topk import static_k
    comp = TopKCompressor(compress_ratio=ratio, algorithm="chunk",
                          use_pallas=False)
    k = static_k(n, ratio)
    if n < 2 * k:
        pytest.skip("degenerate")
    xs = jax.random.normal(jax.random.key(0), (world, n), jnp.float32)
    payloads = [comp.compress(xs[w], None, jax.random.key(1))[0]
                for w in range(world)]
    vals = jnp.stack([p[0] for p in payloads])
    idx = jnp.stack([p[1] for p in payloads])
    ctx = (n, (n,), jnp.float32)

    staged = jnp.mean(jax.vmap(
        lambda v, i: comp.decompress((v, i), ctx))(vals, idx), axis=0)
    fused = chunk_aggregate_dense(vals, (idx // k).astype(jnp.int32), k, n,
                                  average=True, interpret=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(staged),
                               rtol=0, atol=1e-6)

    hook = TopKCompressor(compress_ratio=ratio, algorithm="chunk",
                          use_pallas=True)
    out = hook.fused_aggregate_decompress((vals, idx), ctx, world)
    # world == 1: the kernel, interpreted. Otherwise interpret mode
    # declines multi-device worlds (deadlock guard) and the staged
    # aggregate-then-reshape decode answers in its place.
    assert out is not None
    np.testing.assert_allclose(np.asarray(out), np.asarray(staged),
                               rtol=0, atol=1e-6)


def test_non_chunk_and_tiny_k_fall_back():
    mem_state = jnp.zeros((100,), jnp.float32)
    x = jnp.ones((100,), jnp.float32)
    rng = jax.random.key(0)
    exact = TopKCompressor(compress_ratio=0.1, algorithm="exact",
                           use_pallas=True)
    assert exact.fused_feedback_compress(x, mem_state, (1.0, 1.0), rng) is None
    huge_k = TopKCompressor(compress_ratio=0.9, algorithm="chunk",
                            use_pallas=True)
    assert huge_k.fused_feedback_compress(x, mem_state, (1.0, 1.0), rng) \
        is None
    off = TopKCompressor(compress_ratio=0.1, algorithm="chunk",
                         use_pallas=False)
    assert off.fused_feedback_compress(x, mem_state, (1.0, 1.0), rng) is None


def test_demanded_kernel_raises_on_tpu_instead_of_going_staged(monkeypatch):
    """use_pallas=True is a demand: on a TPU (compiled kernel, interpret
    False) a buffer the kernel cannot take raises with the shape — it does
    not silently time the staged path under the kernel's name. 'auto' may
    still choose, and off-TPU True keeps the quiet interpret-mode
    fallback (pinned above)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rng = jax.random.key(0)
    demanded = TopKCompressor(compress_ratio=0.0001, algorithm="chunk",
                              use_pallas=True)
    x = jnp.ones((100_000,), jnp.float32)    # k=10: 10,000 rows per chunk
    assert compress_block_cols(x.size // max(1, int(x.size * 0.0001))) == 0
    with pytest.raises(ValueError, match="VMEM block budget"):
        demanded.fused_feedback_compress(x, jnp.zeros_like(x), (1.0, 1.0),
                                         rng)
    with pytest.raises(ValueError, match="not float32"):
        demanded.fused_feedback_compress(x.astype(jnp.bfloat16), None,
                                         (1.0, 1.0), rng)
    k = max(1, int(x.size * 0.0001))
    with pytest.raises(ValueError, match=r"is not \(world=8"):
        demanded.fused_aggregate_decompress(
            (jnp.ones((8, k // 2)), jnp.zeros((8, k // 2), jnp.int32)),
            (x.size, x.shape, jnp.float32), 8)
    auto = TopKCompressor(compress_ratio=0.0001, algorithm="chunk")
    assert auto.fused_feedback_compress(x, jnp.zeros_like(x), (1.0, 1.0),
                                        rng) is None


def test_demanded_kernel_refused_under_shard_parallel_communicators(
        monkeypatch):
    """The fused kernel lives on the whole-buffer step path only: ring,
    two-shot, rscatter and hier compress per shard through the staged
    select, so use_pallas=True on a TPU is a clear TypeError there (found
    by PR 21's described-chip sweep: those pairs lowered with 0 kernels).
    Off-TPU, and with 'auto', nothing changes."""
    from grace_tpu.comm import _shard_compress

    chunks = jnp.ones((4, 4096), jnp.float32)
    demanded = TopKCompressor(compress_ratio=0.01, algorithm="chunk",
                              use_pallas=True)
    _shard_compress(demanded, chunks, jax.random.key(0), "RingAllreduce")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(TypeError, match="under RingAllreduce"):
        _shard_compress(demanded, chunks, jax.random.key(0),
                        "RingAllreduce")
    auto = TopKCompressor(compress_ratio=0.01, algorithm="chunk")
    _shard_compress(auto, chunks, jax.random.key(0), "RingAllreduce")
