"""Per-compressor property tests (round-trip, payload shape/dtype, semantics).

The reference backs its algorithms with no tests at all; the semantics
asserted here are transcribed from SURVEY.md §2.3 and the reference sources
cited in each compressor's docstring.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grace_tpu import compressors as C
from grace_tpu.compressors.topk import static_k
from grace_tpu.memories import ResidualMemory
from grace_tpu.ops import sparse

KEY = jax.random.key(42)


def _compress(comp, x, state=None, key=KEY):
    if state is None:
        state = comp.init_state(x)
    return comp.compress(x, state, key)


def _roundtrip(comp, x, key=KEY):
    payload, ctx, _ = _compress(comp, x, key=key)
    return comp.decompress(payload, ctx)


def rand(shape, rng, scale=1.0):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32) * scale)


def test_none_identity(rng):
    x = rand((13, 7), rng)
    out = _roundtrip(C.NoneCompressor(), x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


@pytest.mark.parametrize("dtype", ["bfloat16", "float16"])
def test_fp16_roundtrip(rng, dtype):
    x = rand((64,), rng)
    comp = C.FP16Compressor(dtype=dtype)
    payload, ctx, _ = _compress(comp, x)
    assert payload[0].dtype == jnp.dtype(dtype)
    out = comp.decompress(payload, ctx)
    assert out.dtype == x.dtype
    tol = 0.04 if dtype == "bfloat16" else 0.01
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=tol, atol=tol)


def test_topk_keeps_largest(rng):
    x = rand((10, 10), rng)
    comp = C.TopKCompressor(compress_ratio=0.1)
    payload, ctx, _ = _compress(comp, x)
    values, indices = payload
    assert values.shape == (10,) and indices.shape == (10,)
    out = comp.decompress(payload, ctx)
    assert out.shape == x.shape
    flat = np.asarray(x).ravel()
    expect_idx = np.argsort(-np.abs(flat))[:10]
    got = np.asarray(out).ravel()
    # kept entries match original, everything else is zero
    np.testing.assert_allclose(got[expect_idx], flat[expect_idx], rtol=1e-6)
    mask = np.ones_like(flat, bool)
    mask[expect_idx] = False
    assert np.all(got[mask] == 0)


def test_randomk_shared_seed(rng):
    """Same rng key on every 'rank' -> identical ctx indices (the wire contract)."""
    comp = C.RandomKCompressor(compress_ratio=0.25)
    x1, x2 = rand((40,), rng), rand((40,), rng)
    key = jax.random.key(7)
    _, ctx1, _ = _compress(comp, x1, key=key)
    _, ctx2, _ = _compress(comp, x2, key=key)
    np.testing.assert_array_equal(np.asarray(ctx1[0]), np.asarray(ctx2[0]))
    assert ctx1[0].shape == (10,)
    # indices are distinct (sampling without replacement)
    assert len(np.unique(np.asarray(ctx1[0]))) == 10


def test_threshold_static_capacity(rng):
    x = jnp.asarray([0.5, -0.001, 0.2, 0.0009, -0.9, 0.003])
    comp = C.ThresholdCompressor(threshold=0.1, capacity_ratio=1.0)
    payload, ctx, _ = _compress(comp, x)
    out = np.asarray(comp.decompress(payload, ctx))
    expect = np.where(np.abs(np.asarray(x)) > 0.1, np.asarray(x), 0.0)
    np.testing.assert_allclose(out, expect, rtol=1e-6)


def test_qsgd_bound(rng):
    x = rand((257,), rng)
    comp = C.QSGDCompressor(quantum_num=64)
    payload, ctx, _ = _compress(comp, x)
    levels, norm = payload
    assert levels.dtype == jnp.int8
    out = np.asarray(comp.decompress(payload, ctx))
    # quantization error per element is at most norm/quantum_num
    bound = float(norm) / 64 + 1e-6
    assert np.max(np.abs(out - np.asarray(x))) <= bound


def test_qsgd_int16_for_many_levels(rng):
    comp = C.QSGDCompressor(quantum_num=256)
    payload, _, _ = _compress(comp, rand((32,), rng))
    assert payload[0].dtype == jnp.int16


def test_terngrad_values(rng):
    x = rand((500,), rng)
    comp = C.TernGradCompressor()
    payload, ctx, _ = _compress(comp, x)
    out = np.asarray(comp.decompress(payload, ctx))
    scalar = float(payload[1])
    uniq = np.unique(out)
    assert set(np.round(uniq / scalar).astype(int)) <= {-1, 0, 1}
    # signs agree where nonzero
    nz = out != 0
    assert np.all(np.sign(out[nz]) == np.sign(np.asarray(x)[nz]))


def test_terngrad_unbiased(rng):
    """Stochastic ternarization is unbiased in expectation (clip aside)."""
    x = jnp.asarray(rng.normal(size=2000).astype(np.float32) * 0.1)
    comp = C.TernGradCompressor()

    @jax.jit
    def rt(key):
        payload, ctx, _ = comp.compress(x, None, key)
        return comp.decompress(payload, ctx)

    outs = [np.asarray(rt(jax.random.key(i))) for i in range(200)]
    mean = np.mean(outs, axis=0)
    assert np.abs(mean - np.asarray(x)).mean() < 0.02


def test_signsgd_majority_vote(rng):
    comp = C.SignSGDCompressor()
    assert comp.average is False
    x = rand((33,), rng)
    out = np.asarray(_roundtrip(comp, x))
    np.testing.assert_array_equal(out, np.where(np.asarray(x) >= 0, 1.0, -1.0))
    stacked = jnp.asarray([[1.0, 1, -1], [1, -1, -1], [-1, -1, -1]])
    vote = np.asarray(comp.aggregate(stacked))
    np.testing.assert_array_equal(vote, [1.0, -1.0, -1.0])


def test_signum_momentum(rng):
    comp = C.SignumCompressor(momentum=0.5)
    x = jnp.asarray([1.0, -1.0, 4.0])
    state = comp.init_state(x)
    payload, ctx, state = comp.compress(x, state, KEY)
    # first step: sign of raw gradient
    np.testing.assert_array_equal(np.asarray(comp.decompress(payload, ctx)),
                                  [1.0, -1.0, 1.0])
    y = jnp.asarray([-4.0, -1.0, -1.0])
    payload, ctx, state = comp.compress(y, state, KEY)
    # m = 0.5*y + 0.5*m_prev = [-1.5, -1.0, 1.5]
    np.testing.assert_array_equal(np.asarray(comp.decompress(payload, ctx)),
                                  [-1.0, -1.0, 1.0])
    np.testing.assert_allclose(np.asarray(state["momentum"]), [-1.5, -1.0, 1.5])


def test_efsignsgd_roundtrip(rng):
    x = rand((100,), rng)
    comp = C.EFSignSGDCompressor(lr=0.5)
    payload, ctx, _ = _compress(comp, x)
    out = np.asarray(comp.decompress(payload, ctx))
    mean = float(np.mean(np.abs(np.asarray(x))))
    np.testing.assert_allclose(np.abs(out), mean, rtol=1e-5)
    assert np.all(np.sign(out) == np.where(np.asarray(x) >= 0, 1, -1))
    # aggregate divides by lr
    stacked = jnp.stack([x, x])
    np.testing.assert_allclose(np.asarray(comp.aggregate(stacked)),
                               np.asarray(x + x) / 0.5, rtol=1e-5)


def test_onebit_means(rng):
    x = jnp.asarray([-2.0, -4.0, 1.0, 3.0, 5.0])
    comp = C.OneBitCompressor()
    payload, ctx, _ = _compress(comp, x)
    out = np.asarray(comp.decompress(payload, ctx))
    np.testing.assert_allclose(out, [-3.0, -3.0, 3.0, 3.0, 3.0], rtol=1e-6)


def test_onebit_all_positive(rng):
    x = jnp.asarray([1.0, 2.0, 3.0])
    out = np.asarray(_roundtrip(C.OneBitCompressor(), x))
    np.testing.assert_allclose(out, [2.0, 2.0, 2.0], rtol=1e-6)


def test_natural_power_of_two(rng):
    x = rand((1000,), rng)
    comp = C.NaturalCompressor()
    payload, ctx, _ = _compress(comp, x)
    assert payload[0].dtype == jnp.uint8
    out = np.asarray(comp.decompress(payload, ctx))
    nz = out != 0
    # every decompressed magnitude is a power of two
    log2 = np.log2(np.abs(out[nz]))
    np.testing.assert_allclose(log2, np.round(log2), atol=1e-6)
    # signs preserved, magnitude within a factor of two
    xs = np.asarray(x)[nz]
    assert np.all(np.sign(out[nz]) == np.sign(xs))
    ratio = np.abs(out[nz]) / np.abs(xs)
    assert np.all(ratio <= 2.0 + 1e-6) and np.all(ratio >= 0.5 - 1e-6)


def test_natural_unbiased(rng):
    x = jnp.asarray([0.75] * 512, jnp.float32)
    comp = C.NaturalCompressor()

    @jax.jit
    def rt(key):
        payload, ctx, _ = comp.compress(x, None, key)
        return comp.decompress(payload, ctx)

    outs = [np.asarray(rt(jax.random.key(i))) for i in range(64)]
    mean = np.mean(outs)
    assert abs(mean - 0.75) < 0.02


def test_dgc_selects_about_ratio(rng):
    x = rand((10000,), rng)
    comp = C.DgcCompressor(compress_ratio=0.05)
    payload, ctx, _ = _compress(comp, x)
    values, indices = payload
    nnz = int(np.sum(np.asarray(values) != 0))
    # refinement targets [0.7k, 1.3k]; sampling noise can leave an extra margin
    assert 0.4 * 500 <= nnz <= 1.3 * 500 + 1
    out = np.asarray(comp.decompress(payload, ctx))
    flat = np.asarray(x)
    sent = out != 0
    np.testing.assert_allclose(out[sent], flat[sent], rtol=1e-6)


def test_compressor_hashable():
    """Frozen dataclasses: usable as static jit args / dict keys."""
    assert hash(C.TopKCompressor(0.5)) == hash(C.TopKCompressor(0.5))
    assert C.TopKCompressor(0.5) != C.TopKCompressor(0.25)


class TestTopKAlgorithms:
    """TPU-first selection variants share the exact variant's wire format."""

    def _roundtrip(self, algo, n=10000, ratio=0.01):
        from grace_tpu.compressors import TopKCompressor
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal(n), jnp.float32)
        c = TopKCompressor(compress_ratio=ratio, algorithm=algo)
        # ctx carries static host data (shape/dtype) — jit only the payload.
        vals, idx = jax.jit(
            lambda x: c.compress(x, None, jax.random.key(0))[0])(x)
        _, ctx, _ = c.compress(x, None, jax.random.key(0))
        k = max(1, int(n * ratio))
        assert vals.shape == (k,) and idx.shape == (k,)
        assert jnp.all(idx >= 0) and jnp.all(idx < n)
        np.testing.assert_allclose(np.asarray(vals),
                                   np.asarray(x)[np.asarray(idx)])
        dec = c.decompress((vals, idx), ctx)
        assert dec.shape == x.shape
        return x, vals, idx

    def test_exact_is_true_topk(self):
        x, vals, idx = self._roundtrip("exact")
        thresh = np.sort(np.abs(np.asarray(x)))[-100]
        assert np.all(np.abs(np.asarray(vals)) >= thresh - 1e-6)

    def test_approx_high_recall(self):
        x, vals, idx = self._roundtrip("approx")
        exact = set(np.argsort(np.abs(np.asarray(x)))[-100:].tolist())
        got = set(np.asarray(idx).tolist())
        assert len(exact & got) / 100 >= 0.9

    def test_chunk_selects_chunk_maxima(self):
        # Strided chunks: chunk c = elements {c, c+k, c+2k, ...}.
        x, vals, idx = self._roundtrip("chunk")
        xn = np.abs(np.asarray(x))
        k = 100  # n=10000, ratio=0.01
        for c, i in enumerate(np.asarray(idx)):
            members = xn[c::k]
            assert i % k == c
            assert xn[i] == members.max()

    def test_chunk_indices_unique_and_cover(self):
        _, _, idx = self._roundtrip("chunk", n=10007, ratio=0.013)
        idx = np.asarray(idx)
        assert len(np.unique(idx)) == len(idx)

    @pytest.mark.parametrize("n,ratio", [
        (27, 0.3),          # pad spans whole contiguous chunks (regression)
        (25_557, 0.01),     # ResNet-50-like shape scaled down
        (101, 0.5),
    ])
    def test_chunk_indices_in_range_awkward_shapes(self, n, ratio):
        """Regression: contiguous chunking emitted out-of-range indices when
        the tail padding spanned whole chunks; strided chunking cannot."""
        self._roundtrip("chunk", n=n, ratio=ratio)

    @pytest.mark.parametrize("n,ratio", [
        (10_000, 0.01),
        (27, 0.3),
        (25_557, 0.01),
        (101, 0.5),
    ])
    def test_chunk_onehot_decompress_matches_scatter(self, n, ratio):
        """Chunk mode's scatter-free one-hot decompress must equal the
        general scatter build bit-exactly for every payload."""
        from grace_tpu.compressors import TopKCompressor
        from grace_tpu.ops.sparse import scatter_dense

        c = TopKCompressor(compress_ratio=ratio, algorithm="chunk")
        x = jax.random.normal(jax.random.key(3), (n,))
        (vals, idx), ctx, _ = c.compress(x, None, jax.random.key(0))
        numel, shape, dtype = ctx
        got = c.decompress((vals, idx), ctx)
        want = scatter_dense(vals.astype(dtype), idx, numel, shape)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_chunk_subk_payload_falls_back_to_scatter(self):
        """A sliced payload (TwoShot per-rank slice) loses the full-column
        structure; decompress must route it through the general scatter."""
        from grace_tpu.compressors import TopKCompressor
        from grace_tpu.ops.sparse import scatter_dense

        c = TopKCompressor(compress_ratio=0.01, algorithm="chunk")
        x = jax.random.normal(jax.random.key(5), (10_000,))
        (vals, idx), ctx, _ = c.compress(x, None, jax.random.key(0))
        sub = (vals[:40], idx[:40])                    # 40 < k=100
        got = c.decompress(sub, ctx)
        want = scatter_dense(sub[0], sub[1], *ctx[:2])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_unknown_algorithm_rejected(self):
        from grace_tpu.compressors import TopKCompressor
        with pytest.raises(ValueError, match="algorithm"):
            TopKCompressor(algorithm="banana")

    def test_helper_plumbs_algorithm(self):
        from grace_tpu import grace_from_params
        g = grace_from_params({"compressor": "topk", "compress_ratio": 0.01,
                               "topk_algorithm": "chunk"})
        assert g.compressor.algorithm == "chunk"


ALL_CODECS = ["none", "fp16", "bf16", "topk", "randomk", "threshold", "qsgd",
              "terngrad", "signsgd", "signum", "efsignsgd", "onebit",
              "natural", "dgc", "u8bit", "sketch", "adaq", "inceptionn"]


@pytest.mark.parametrize("name", ALL_CODECS)
def test_payload_shapes_are_value_independent(name, rng):
    """The XLA contract: payload shapes depend only on the input SHAPE, never
    on values (data-dependent sizes cannot compile; SURVEY.md §7 hard part 1).
    Two very different value distributions must produce identical payload
    shapes/dtypes and identical static ctx."""
    from grace_tpu.helper import grace_from_params
    c = grace_from_params({"compressor": name}).compressor
    a = jnp.asarray(rng.normal(size=60).astype(np.float32))
    b = jnp.asarray((rng.normal(size=60) * 1e6).astype(np.float32))
    key = jax.random.key(0)
    pa, ctxa, _ = c.compress(a, c.init_state(a), key)
    pb, ctxb, _ = c.compress(b, c.init_state(b), key)
    assert [(p.shape, p.dtype) for p in pa] == \
           [(p.shape, p.dtype) for p in pb]
    # static (non-array) ctx leaves must not depend on values either —
    # a data-derived static aux value would break jit caching
    def static_leaves(ctx):
        return [l for l in jax.tree_util.tree_leaves(ctx)
                if not isinstance(l, jax.Array)]
    assert static_leaves(ctxa) == static_leaves(ctxb)


@pytest.mark.parametrize("name", ALL_CODECS)
@pytest.mark.parametrize("case", ["zeros", "tiny", "single", "constant"])
def test_degenerate_inputs_stay_finite(name, case, rng):
    """Zero gradients (frozen params, step 0 biases), denormals, single
    elements and constants hit every divide-by-norm/scale path; decompress
    must stay finite with the right shape/dtype."""
    from grace_tpu.helper import grace_from_params
    c = grace_from_params({"compressor": name}).compressor
    x = {"zeros": jnp.zeros(48), "tiny": jnp.full(48, 1e-30),
         "single": jnp.zeros(1), "constant": jnp.full(48, 3.25)}[case]
    p, ctx, _ = c.compress(x, c.init_state(x), jax.random.key(1))
    d = c.decompress(p, ctx)
    assert d.shape == x.shape and d.dtype == x.dtype
    assert bool(jnp.all(jnp.isfinite(d)))


# ---------------------------------------------------------------------------
# chunk top-k on large leaves: static row-block slices against the view
# ---------------------------------------------------------------------------

# rows, k, lanes of padding in the last row, the limit the test sets in
# ``ops.sparse.RELAYOUT_LOOP_ELEMENTS`` to send this small leaf down the
# row-slices route, and the ``(per, count)`` blocks its whole rows are then
# walked in (the last block starts early, to end with the last whole row).
_SLICE_LAYOUTS = {
    "divisible": (20, 30, 0, 100, (3, 7)),        # 20 whole rows, 21 walked
    "pad1": (9, 37, 1, 37, (1, 8)),               # a row a block + the padded
    "pad-k-minus-1": (21, 11, 10, 110, (8, 3)),   # tiles of 8: 20 rows as 24
    "rows2": (2, 37, 4, 40, (1, 1)),              # one whole row, one padded
    "k1": (17, 1, 0, 9, (8, 3)),                  # k = 1: 17 rows as 24
}


def _slice_layout_input(layout, data, rng):
    rows, k, pad, _, _ = _SLICE_LAYOUTS[layout]
    n = rows * k - pad
    if data == "random":
        x = rng.standard_normal(n)
    elif data == "ties":                  # equal |x| across rows, both signs
        x = rng.integers(-2, 3, n).astype(np.float64)
    elif data == "zeros":                 # all tie at 0; -0.0 among them
        x = np.where(rng.random(n) < 0.5, 0.0, -0.0)
    elif data == "nan":                   # a NaN past row 0, and two in one
        x = rng.standard_normal(n)        # column: the first one wins
        x[(rows - 1) * k:(rows - 1) * k + 1] = np.nan
        x[(k + k // 2) % n] = np.nan
        x[((rows - 1) * k + k // 2) % n] = np.nan
    elif data == "inf":                   # +inf and -inf tie in |x|
        x = rng.standard_normal(n)
        x[rng.integers(0, n, max(2, n // 7))] = np.inf
        x[rng.integers(0, n, max(2, n // 7))] = -np.inf
    return jnp.asarray(x.astype(np.float32)), (k + 0.5) / n


def _codec_and_residual(x, ratio, wire):
    """values, indices, the decode and the residual it leaves, as bytes."""
    comp = C.TopKCompressor(compress_ratio=ratio, algorithm="chunk",
                            wire_dtype=wire)
    (values, indices), ctx, _ = comp.compress(x, None, KEY)
    dense = comp.decompress((values, indices), ctx)
    resid = ResidualMemory().update(x, (values, indices), ctx, comp,
                                    jnp.zeros_like(x))
    assert values.dtype == jnp.dtype(wire) and indices.dtype == jnp.int32
    return [np.asarray(a).tobytes() for a in (values, indices, dense, resid)]


@pytest.mark.parametrize("wire", ["float32", "bfloat16"])
@pytest.mark.parametrize("data", ["random", "ties", "zeros", "nan", "inf"])
@pytest.mark.parametrize("layout", sorted(_SLICE_LAYOUTS))
def test_topk_chunk_row_slices_equal_the_view_bitwise(monkeypatch, rng,
                                                      layout, data, wire):
    """The two routes of chunk top-k (the (rows, k) view by reshape; static
    row-block slices of the flat buffer, ops.sparse) on the same leaf:
    ``values``, ``indices``, the decode and the new residual byte for byte.
    The route follows from (rows, k) against one constant, set here so that
    a small leaf takes the slices."""
    rows, k, pad, limit, blocks = _SLICE_LAYOUTS[layout]
    x, ratio = _slice_layout_input(layout, data, rng)
    assert not sparse.takes_row_slices(rows, k)
    view = _codec_and_residual(x, ratio, wire)
    monkeypatch.setattr(sparse, "RELAYOUT_LOOP_ELEMENTS", limit)
    assert sparse.takes_row_slices(rows, k)
    assert sparse.row_blocks(rows - bool(pad), k) == blocks
    sliced = _codec_and_residual(x, ratio, wire)
    for name, a, b in zip(("values", "indices", "decode", "residual"),
                          view, sliced):
        assert a == b, name
    indices = np.frombuffer(sliced[1], np.int32)
    assert indices.max() < x.size           # a padding lane never wins


def _lowered_scopes(n, ratio=0.01, dtype=jnp.float32):
    comp = C.TopKCompressor(compress_ratio=ratio, algorithm="chunk")

    def f(x, r):
        payload, ctx, _ = comp.compress(x, None, KEY)
        return ResidualMemory().update(x, payload, ctx, comp, r)

    x = jax.ShapeDtypeStruct((n,), dtype)
    text = jax.jit(f).lower(x, x).as_text(debug_info=True)
    return ("grace/compress/row_slices" in text,
            "grace/decompress/row_slices" in text)


@pytest.mark.parametrize("n, k, sliced", [
    (100 * 41_527 + 50, 41_527, False),   # view of 4,194,227 elements
    (100 * 41_529 + 50, 41_529, True),    # 4,194,429: just past 2**22
    (100 * 41_600 + 50, 41_600, False),   # k % 128 == 0: the reshape is free
    (2_359_296, 23_592, False),           # ResNet-50's largest leaf
    (25_165_824, 251_658, True),          # an LFM2 expert stack
], ids=["under", "over", "k-lane-multiple", "resnet-largest", "lfm2-expert"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_topk_chunk_route_follows_the_view_size(n, k, sliced, dtype):
    """A leaf whose (rows, k) view holds at most RELAYOUT_LOOP_ELEMENTS
    takes the view, the next one up the slices, in both directions and
    whatever the element width; what the lowered program carries says so
    (the sub-scopes ``grace/compress/row_slices`` and
    ``grace/decompress/row_slices``)."""
    assert static_k(n, 0.01) == k
    assert sparse.takes_row_slices(-(-n // k), k) == sliced
    assert _lowered_scopes(n, dtype=jnp.dtype(dtype)) == (sliced, sliced)
