"""Each plain codec reference (``benchmarks/reference/``, which imports
nothing of ``grace_tpu``) against the library's codec at a small size."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_harness_helpers import REPO  # noqa: E402,F401

from benchmarks.reference import none as plain_none  # noqa: E402
from benchmarks.reference import powersgd as plain_powersgd  # noqa: E402
from benchmarks.reference import topk_chunk as plain_topk  # noqa: E402
from grace_tpu.compressors import PowerSGDCompressor, TopKCompressor  # noqa: E402
from grace_tpu.memories import PowerSGDMemory, ResidualMemory  # noqa: E402
from grace_tpu.parallel import shard_map  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

SHAPES = [(64,), (1000,), (7, 7, 3, 8), (3, 3, 16, 16), (33, 5)]


def test_the_references_import_nothing_of_the_program():
    ref_dir = os.path.join(REPO, "benchmarks", "reference")
    for name in os.listdir(ref_dir):
        if name.endswith(".py"):
            with open(os.path.join(ref_dir, name)) as f:
                text = f.read()
            assert "import grace_tpu" not in text, name
            assert "from grace_tpu" not in text, name


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("ratio", [0.01, 0.05])
def test_chunked_topk_indices_values_and_residual_are_equal(shape, ratio):
    rng = np.random.default_rng(hash((shape, ratio)) % 2 ** 32)
    grad = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    resid = jnp.asarray(0.1 * rng.standard_normal(shape), jnp.float32)
    codec = TopKCompressor(compress_ratio=ratio, algorithm="chunk",
                           use_pallas=False)
    memory = ResidualMemory()
    comp, _ = memory.compensate(grad, resid)
    (values, indices), ctx, _ = codec.compress(comp, None, jax.random.key(0))
    new_resid = memory.update(comp, (values, indices), ctx, codec, resid)

    k = max(1, int(grad.size * ratio))
    want_values, want_indices = plain_topk.select((grad + resid).reshape(-1),
                                                  k)
    np.testing.assert_array_equal(indices, want_indices)
    np.testing.assert_array_equal(values, want_values)
    kept, want_resid = plain_topk.exchange(
        grad[None], resid[None], {"compress_ratio": ratio})
    np.testing.assert_array_equal(codec.decompress((values, indices), ctx),
                                  kept)
    np.testing.assert_array_equal(new_resid, want_resid[0])


def test_chunked_topk_averages_the_replicas_kept_entries():
    rng = np.random.default_rng(1)
    grads = jnp.asarray(rng.standard_normal((4, 200)), jnp.float32)
    state = plain_topk.init_state((200,), None, 4, {})
    kept, resid = plain_topk.exchange(grads, state, {"compress_ratio": 0.05})
    singles = [plain_topk.exchange(g[None], jnp.zeros((1, 200)),
                                   {"compress_ratio": 0.05})[0]
               for g in grads]
    np.testing.assert_allclose(kept, sum(singles) / 4, rtol=1e-6)
    assert resid.shape == (4, 200)
    assert int(jnp.sum(kept != 0)) <= 4 * 10


def _library_powersgd(grad, resid, q0, rank):
    """One step of the library's PowerSGD + its memory on one device."""
    codec = PowerSGDCompressor(rank=rank)
    memory = PowerSGDMemory()
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))

    def one(g, r, q):
        comp, _ = memory.compensate(g, r)
        payload, ctx, new_q = codec.compress(comp, q, jax.random.key(0))
        new_r = memory.update(comp, payload, ctx, codec, r)
        return codec.decompress(payload, ctx), new_r, new_q

    return jax.jit(shard_map(one, mesh=mesh, in_specs=(P(), P(), P()),
                             out_specs=(P(), P(), P())))(grad, resid, q0)


@pytest.mark.parametrize("shape", [(48, 32), (3, 3, 16, 24), (100, 4)])
def test_powersgd_reconstruction_and_error_memory(shape):
    rng = np.random.default_rng(2)
    grad = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    resid = jnp.asarray(0.1 * rng.standard_normal(shape), jnp.float32)
    spec = {"compress_rank": 4}
    state = plain_powersgd.init_state(shape, jax.random.key(7), 1, spec)
    state["residual"] = resid[None]
    got, got_resid, got_q = _library_powersgd(grad, resid, state["q"], 4)
    with jax.default_matmul_precision("highest"):
        want, new = plain_powersgd.exchange(grad[None], state, spec)
    scale = float(jnp.max(jnp.abs(want)))
    # float32 round-off of a QR and two thin products
    tol = 2e-5 * scale
    np.testing.assert_allclose(got, want, atol=tol)
    np.testing.assert_allclose(got_resid, new["residual"][0], atol=tol)
    np.testing.assert_allclose(got_q, new["q"], atol=2e-5 * float(
        jnp.max(jnp.abs(new["q"]))))
    # ... which the same step on gradients held in bfloat16 misses (the
    # CPU has no bfloat16 QR: the values are rounded, the arithmetic not)
    def rounded(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    low, _ = plain_powersgd.exchange(
        rounded(grad[None]),
        {"q": rounded(state["q"]), "residual": rounded(resid[None])}, spec)
    assert float(jnp.max(jnp.abs(low - want))) > 10 * tol


def test_powersgd_leaves_one_dimensional_leaves_dense():
    g = jnp.arange(8.0).reshape(2, 4)
    assert plain_powersgd.init_state((4,), None, 2, {"compress_rank": 4}) is None
    out, state = plain_powersgd.exchange(g, None, {"compress_rank": 4})
    np.testing.assert_array_equal(out, g.mean(0))
    assert state is None


def test_dense_exchange_is_the_mean():
    g = jnp.arange(12.0).reshape(4, 3)
    out, state = plain_none.exchange(g, None, {})
    np.testing.assert_array_equal(out, g.mean(0))
    assert state is None and plain_none.seeded(None) is None
