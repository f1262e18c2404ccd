"""``ops.pallas_attention.causal_gqa`` against the plain spelling it
replaces on the chip (``models.lfm2._scores_block`` over the whole
sequence), in Pallas's interpreter on the CPU: output and the gradients of
``q``, ``k`` and ``v``, over dtype and group size, at a sequence of two
tiles so that a tile above the diagonal (skipped), on it (masked inside)
and below it (whole) all occur; at both pairs of head sizes the kernel
takes, ``64 | 64`` (LFM2's grouped queries) and ``192 | 128`` (latent
attention's queries and keys | values). And who takes the kernel:
``engages`` alone decides, from platform and shape, and ``lfm2.attention``
and ``deepseek_v3.mla`` give the plain path's bits wherever it says no.

Under the block-diffusion mask (PR 42) the kernel is given the clean keys
alone and a noised query's own block is scored beside it and merged in by
log-sum-exp: that path against the plain spelling under the whole mask,
the merge where the kernel's row is empty, the tiles the rectangle visits,
and what a recomputed part keeps of it.

Whether Mosaic accepts the kernel at the benchmark's size is
``tests/test_tpu_compile.py``'s; what it does to the step is the chip's
(PERF.md §6, PR 31).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grace_tpu.models import deepseek_v3
from grace_tpu.models import layers as L
from grace_tpu.models import lfm2
from grace_tpu.models import sdar
from grace_tpu.ops import pallas_attention
from grace_tpu.ops.pallas_attention import (CAUSAL, TILE, BlockDiffusion,
                                            SlidingWindow, causal_gqa,
                                            engages, masked_gqa)

T = 2 * TILE
D = 64
GQA, MLA = (64, 64), (192, 128)       # queries and keys | values
SDAR = (128, 128)
WIDE = (256, 256)                    # models/qwen3_next.py's full layers
HKV = 2


def _inputs(group, dtype, t=T, key=0, dims=GQA):
    ks = jax.random.split(jax.random.key(key), 4)

    def normal(k, heads, d):       # head-major, as the kernel's wrapper takes
        return jax.random.normal(k, (1, heads, t, d), jnp.float32
                                 ).astype(dtype)

    return (normal(ks[0], HKV * group, dims[0]), normal(ks[1], HKV, dims[0]),
            normal(ks[2], HKV, dims[1]), normal(ks[3], HKV * group, dims[1]))


def _tokens_first(fn):
    """A plain spelling over ``(n, T, H, D)`` as a function of head-major
    operands that answers head-major, as the kernel's wrapper does."""
    def swapped(q, k, v, **kw):
        return jnp.swapaxes(
            fn(*(jnp.swapaxes(a, 1, 2) for a in (q, k, v)), **kw), 1, 2)
    return swapped


@_tokens_first
def _plain(q, k, v, mask=CAUSAL):
    n, t, hq, d = q.shape
    hkv = k.shape[2]
    out = lfm2._scores_block(q.reshape(n, t, hkv, hq // hkv, d), k, v, 0,
                             mask)
    return out.reshape(n, t, hq, v.shape[-1])



def _scaled(q):
    """``q / sqrt(D)``, the scale the plain spelling puts on its scores."""
    return (q.astype(jnp.float32) / np.sqrt(q.shape[-1])).astype(q.dtype)


def _weighted(fn):
    """Output, and the gradients of a fixed weighting of it."""
    def loss(q, k, v, w):
        out = fn(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))


def _gap(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# float32: both spellings sum the same products in another order. bfloat16:
# the plain spelling rounds scores and probabilities to 8 bits of mantissa
# (2**-8 a number), the kernel keeps the scores in float32.
TOLERANCE = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dims, group", [(GQA, 1), (GQA, 4), (MLA, 1),
                                         (WIDE, 2)],
                         ids=["mha", "gqa4", "mla192-128", "gqa2-256"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernel_agrees_with_the_plain_spelling(dtype, dims, group):
    """The kernel applies no scale, so ``q`` is handed over scaled: through
    float32, which at 192 (no power of two) is one rounding more than the
    plain spelling's scaled scores in bfloat16, well inside that dtype's
    tolerance."""
    q, k, v, w = _inputs(group, dtype, dims=dims)
    kernel = _weighted(lambda q, k, v: causal_gqa(_scaled(q), k, v,
                                                  interpret=True))
    (_, out), grads = kernel(q, k, v, w)
    (_, want), want_grads = _weighted(_plain)(q, k, v, w)
    assert out.dtype == dtype and out.shape == q.shape[:3] + (dims[1],)
    tol = TOLERANCE[dtype]
    assert _gap(out, want) < tol
    for name, got, ref in zip("qkv", grads, want_grads):
        assert got.dtype == dtype and got.shape == ref.shape
        assert _gap(got, ref) < tol, name


def test_bfloat16_kernel_is_no_further_from_float32_than_the_plain_path():
    """The kernel keeps the scores in float32 where the plain spelling
    rounds them to bfloat16: against the float32 result of the same
    bfloat16 inputs it is the closer of the two, not only within reach."""
    q, k, v, w = _inputs(4, jnp.bfloat16, key=3)
    exact = _plain(*(a.astype(jnp.float32) for a in (q, k, v)))
    fused = causal_gqa(_scaled(q), k, v, interpret=True)
    assert _gap(fused, exact) <= _gap(_plain(q, k, v), exact)


@pytest.mark.parametrize("dims", [GQA, MLA], ids=["gqa", "mla192-128"])
def test_the_kernel_is_causal(dims):
    q, k, v, _ = _inputs(4 if dims is GQA else 1, jnp.float32, dims=dims)
    out = causal_gqa(q, k, v, interpret=True)
    cut = TILE + 37           # inside the second tile's diagonal block
    k2 = k.at[:, :, cut:].set(7.0)
    v2 = v.at[:, :, cut:].set(-3.0)
    out2 = causal_gqa(q, k2, v2, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[:, :, :cut]),
                                  np.asarray(out2[:, :, :cut]))
    assert not np.allclose(np.asarray(out[:, :, cut:]),
                           np.asarray(out2[:, :, cut:]))


# The doubled sequence of block diffusion at the kernel's least size: one
# tile of noised positions and one of clean ones, blocks of 4. Of the four
# tiles the noised diagonal is masked inside to 4 keys a query, noised
# queries over clean keys and clean over clean are block-causal, and clean
# queries over noised keys are skipped.
BLOCK_MASK = BlockDiffusion(TILE, 4)


@pytest.mark.parametrize("dims, group", [(SDAR, 4), (GQA, 1)],
                         ids=["sdar128-gqa4", "mha64"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernel_agrees_with_the_plain_spelling_under_the_block_mask(
        dtype, dims, group):
    """Output and the gradients of ``q``, ``k`` and ``v`` under the
    block-diffusion mask, at Qwen3's head size of 128 (``1 / sqrt(128)`` is
    no power of two: ``q`` is handed over scaled through float32, one
    rounding more than the plain spelling's scaled scores, inside
    bfloat16's tolerance) and at 64; the causal cases above are what they
    were."""
    q, k, v, w = _inputs(group, dtype, dims=dims)
    kernel = _weighted(lambda q, k, v: masked_gqa(
        _scaled(q), k, v, BLOCK_MASK, interpret=True))
    (_, out), grads = kernel(q, k, v, w)
    (_, want), want_grads = _weighted(
        functools.partial(_plain, mask=BLOCK_MASK))(q, k, v, w)
    assert out.dtype == dtype and out.shape == q.shape[:3] + (dims[1],)
    tol = TOLERANCE[dtype]
    assert _gap(out, want) < tol
    for name, got, ref in zip("qkv", grads, want_grads):
        assert got.dtype == dtype and got.shape == ref.shape
        assert _gap(got, ref) < tol, name
    # and it is another function than the causal one
    assert _gap(out, _plain(q, k, v)) > 0.1


def test_the_kernel_reads_what_the_block_mask_allows_and_no_more():
    """Changing the keys a query may not read changes nothing it gives:
    for the noised query at token 5 (block 1) everything but noised 4-7 and
    clean 0-3; for the clean copy every noised key."""
    q, k, v, _ = _inputs(4, jnp.float32, dims=SDAR)
    out = masked_gqa(q, k, v, BLOCK_MASK, interpret=True)
    readable = np.zeros(T, bool)
    readable[4:8] = readable[TILE:TILE + 4] = True
    k2 = jnp.where(readable[None, None, :, None], k, 7.0)
    v2 = jnp.where(readable[None, None, :, None], v, -3.0)
    out2 = masked_gqa(q, k2, v2, BLOCK_MASK, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[:, :, 4:8]),
                                  np.asarray(out2[:, :, 4:8]))
    assert not np.allclose(np.asarray(out[:, :, 8:12]),
                           np.asarray(out2[:, :, 8:12]))
    noised = np.arange(T) < TILE
    out3 = masked_gqa(q, jnp.where(noised[None, None, :, None], 7.0, k),
                      jnp.where(noised[None, None, :, None], -3.0, v),
                      BLOCK_MASK, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[:, :, TILE:]),
                                  np.asarray(out3[:, :, TILE:]))


@pytest.mark.parametrize("mask, dtype, group", [
    (BLOCK_MASK, jnp.float32, 8), (BLOCK_MASK, jnp.bfloat16, 8),
    (BLOCK_MASK, jnp.float32, 1), (BLOCK_MASK, jnp.bfloat16, 1),
    (BlockDiffusion(TILE, pallas_attention.OWN_ROWS), jnp.float32, 2),
], ids=["float32-gqa8", "bfloat16-gqa8", "float32-mha", "bfloat16-mha",
        "block-of-a-tile-float32"])
def test_the_rectangle_and_the_own_block_agree_with_scores_in_blocks(
        mask, dtype, group):
    """The kernel over the clean keys merged with a noised query's own
    block, against ``lfm2._scores_in_blocks`` under the whole mask, 256
    queries at a time: output and the gradients of ``q``, ``k`` and
    ``v``, the noised and the clean halves each within the tolerance (the
    noised keys' gradients come from the own block alone, the clean keys'
    from the kernel alone, the noised queries' from both). At the largest
    block the own-block kernels take, a whole tile of theirs, nothing in a
    tile is masked and the first 128 noised queries read no clean key."""
    q, k, v, w = _inputs(group, dtype, dims=SDAR, key=11)
    kernel = _weighted(lambda q, k, v: masked_gqa(
        _scaled(q), k, v, mask, interpret=True))
    (_, out), grads = kernel(q, k, v, w)
    (_, want), want_grads = _weighted(functools.partial(
        lfm2._plain_scores, q_block=256, mask=mask))(q, k, v, w)
    tol = TOLERANCE[dtype]
    assert out.dtype == dtype and out.shape == want.shape
    for name, got, ref in zip(["out", "dq", "dk", "dv"], (out,) + grads,
                              (want,) + want_grads):
        assert got.dtype == dtype and got.shape == ref.shape
        assert np.isfinite(np.asarray(got, np.float32)).all(), name
        for half in (slice(0, TILE), slice(TILE, T)):
            assert _gap(got[:, :, half], ref[:, :, half]) < tol, (name, half)


def test_a_noised_query_of_block_0_gets_its_own_blocks_answer_exactly():
    """The first block's noised queries read no clean key: the kernel's row
    is empty there (a log-sum-exp at the mask's value, an output that means
    nothing) and the merge gives the own block's softmax, the same bits
    whatever the clean keys and values hold."""
    q, k, v, _ = _inputs(4, jnp.float32, dims=SDAR, key=12)
    out = masked_gqa(q, k, v, BLOCK_MASK, interpret=True)
    clean = (np.arange(T) >= TILE)[None, None, :, None]
    out2 = masked_gqa(q, jnp.where(clean, 50.0, k),
                      jnp.where(clean, -3e30, v), BLOCK_MASK, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[:, :, :4]),
                                  np.asarray(out2[:, :, :4]))
    assert not np.allclose(np.asarray(out[:, :, 4:8]),
                           np.asarray(out2[:, :, 4:8]))
    group = q.shape[1] // k.shape[1]
    k4, v4 = (jnp.repeat(x[:, :, :4], group, axis=1) for x in (k, v))
    own = jnp.einsum(
        "nhqk,nhkd->nhqd",
        jax.nn.softmax(jnp.einsum("nhqd,nhkd->nhqk", q[:, :, :4], k4), -1),
        v4)
    np.testing.assert_allclose(np.asarray(out[:, :, :4]), np.asarray(own),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("clean_read", [False, True],
                         ids=["mask-value", "both-read"])
def test_the_own_block_kernel_merges_the_two_softmaxes(clean_read):
    """``_own_forward`` alone, handed an output and a log-sum-exp as the
    fused kernel leaves them. A row the fused kernel masked whole (the
    mask's value as its log-sum-exp, anything finite as its output) weighs
    nothing: the own block's softmax comes back, whatever the output held.
    A row that read clean keys is merged: the softmax over both sets, from
    the two log-sum-exps. The clean copy's half is not touched."""
    n, hq, hkv, d = 1, 4, 2, 128
    length = 2 * pallas_attention.OWN_ROWS
    ks = jax.random.split(jax.random.key(3), 5)
    q = jax.random.normal(ks[0], (n, hq, 2 * length, d)) * 0.3
    k = jax.random.normal(ks[1], (n, hkv, 2 * length, d))
    v = jax.random.normal(ks[2], (n, hkv, 2 * length, d))
    mask_value = -0.7 * float(np.finfo(np.float32).max)
    lse_clean = (jax.random.normal(ks[3], (n, hq, 2 * length)) * 2
                 if clean_read else jnp.full((n, hq, 2 * length), mask_value))
    own = functools.partial(
        pallas_attention._own_forward, q, k, v, length=length, block=4,
        mask_value=mask_value, interpret=True)
    out_clean = jax.random.normal(ks[4], (n, hq, 2 * length, d))
    out, lse = own(out_clean, lse_clean)
    # the own blocks alone, the plain way
    qb = q[:, :, :length].reshape(n, hkv, hq // hkv, length // 4, 4, d)
    kb, vb = (x[:, :, :length].reshape(n, hkv, length // 4, 4, d)
              for x in (k, v))
    s = jnp.einsum("nhgbqd,nhbkd->nhgbqk", qb, kb)
    lse_own = jax.nn.logsumexp(s, axis=-1).reshape(n, hq, length)
    out_own = jnp.einsum("nhgbqk,nhbkd->nhgbqd", jax.nn.softmax(s, -1),
                         vb).reshape(n, hq, length, d)
    if clean_read:
        want_lse = jnp.logaddexp(lse_clean[:, :, :length], lse_own)
        want = (jnp.exp(lse_clean[:, :, :length] - want_lse)[..., None]
                * out_clean[:, :, :length]
                + jnp.exp(lse_own - want_lse)[..., None] * out_own)
    else:
        want_lse, want = lse_own, out_own
        other, _ = own(out_clean * 1e30, lse_clean)
        np.testing.assert_array_equal(np.asarray(out[:, :, :length]),
                                      np.asarray(other[:, :, :length]))
    np.testing.assert_allclose(out[:, :, :length], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lse[:, :, :length], want_lse, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(out[:, :, length:]),
                                  np.asarray(out_clean[:, :, length:]))
    np.testing.assert_array_equal(np.asarray(lse[:, :, length:]),
                                  np.asarray(lse_clean[:, :, length:]))


@pytest.mark.parametrize("seq_len, block, visited", [
    (4096, 4, 20), (4096, 1024, 16), (1024, 4, 2), (2048, 4, 6)])
def test_tiles_without_an_allowed_pair_are_not_visited(seq_len, block,
                                                       visited):
    """The kernel's own table of tiles (0: skipped) under the
    block-diffusion mask over ``2 * seq_len`` positions: the rectangle of
    all queries over the clean keys, 10 + 10 of the 8 x 4 tiles of 1,024 a
    doubled sequence of 8,192 has (the four tiles of the noised diagonal,
    which it visited as a square until PR 42, are no part of it), where the
    causal mask over as many positions visits 36; forward and fused
    backward alike. At blocks of a whole tile the first tile of noised
    queries reads no clean key and visits nothing. The mask is evaluated
    from what the kernel is handed a query, how many clean keys it reads:
    the kernel carries no mask blocks."""
    total = 2 * seq_len
    mask = BlockDiffusion(seq_len, block)
    kernel = pallas_attention._kernel(total, 2, True, mask)
    reads = np.asarray(mask.clean_keys_read(np.arange(total)))
    for info in (kernel.fwd_mask_info, kernel.dkv_mask_info):
        table = np.asarray(info.block_mask)
        assert table.size == (total // TILE) * (seq_len // TILE)
        assert int((table > 0).sum()) == visited
        assert info.partial_mask_blocks is None
        np.testing.assert_array_equal(np.asarray(info.q_sequence), reads)
    tiles = total // TILE
    causal = pallas_attention._kernel(total, 2, True)
    assert int((np.asarray(causal.fwd_mask_info.block_mask) > 0).sum()) \
        == tiles * (tiles + 1) // 2


def test_the_clean_keys_a_query_reads_are_the_masks():
    """``clean_keys_read`` against ``allowed``, every pair of a doubled
    sequence of 32 positions in blocks of 4: a clean key is allowed iff its
    place in the clean copy is below the count, whoever asks."""
    mask = BlockDiffusion(16, 4)
    ids = np.arange(32)
    allowed = mask.allowed(ids[:, None], ids[None, :])
    reads = mask.clean_keys_read(ids)
    np.testing.assert_array_equal(allowed[:, 16:],
                                  np.arange(16)[None, :] < reads[:, None])
    assert list(reads[:8]) == [0] * 4 + [4] * 4 and reads[16] == 4
    # and what is left of the mask is a noised query's own block
    own = (ids[:16, None] // 4) == (ids[None, :16] // 4)
    np.testing.assert_array_equal(allowed[:16, :16], own)
    assert not allowed[16:, :16].any()


def test_a_mask_over_another_length_is_refused():
    q = jnp.zeros((1, 2, T, 128), jnp.float32)
    with pytest.raises(ValueError, match="positions"):
        masked_gqa(q, q, q, BlockDiffusion(TILE // 2, 4), interpret=True)


@pytest.mark.parametrize("seq_len, dims, dtype, platform, taken", [
    (4096, GQA, jnp.bfloat16, "tpu", True),
    (TILE, GQA, jnp.float32, "tpu", True),
    (4096, MLA, jnp.bfloat16, "tpu", True),
    (TILE, MLA, jnp.float32, "tpu", True),
    (4096, GQA, jnp.bfloat16, "cpu", False),
    (4096, MLA, jnp.bfloat16, None, False),       # this process: the CPU
    (TILE + 128, GQA, jnp.bfloat16, "tpu", False),
    (TILE + 128, MLA, jnp.bfloat16, "tpu", False),
    (16, (8, 8), jnp.float32, "tpu", False),      # lfm2.tiny()
    (16, (12, 8), jnp.float32, "tpu", False),     # deepseek_v3.tiny()
    (4096, (8, 8), jnp.bfloat16, "tpu", False),
    (4096, (192, 192), jnp.bfloat16, "tpu", False),   # values as wide as keys
    (4096, (128, 128), jnp.bfloat16, "tpu", True),    # Qwen3's heads (PR 41)
    (4096, (64, 128), jnp.bfloat16, "tpu", False),
    (4096, GQA, jnp.float16, "tpu", False),
    (8192, SDAR, jnp.bfloat16, "tpu", True),          # a doubled sequence
    (8192, SDAR, jnp.bfloat16, "cpu", False),
    (4096, (128, 64), jnp.bfloat16, "tpu", False),    # no pair of the three
    (32, (8, 8), jnp.float32, "tpu", False),          # sdar.tiny()
    (16384, WIDE, jnp.bfloat16, "tpu", True),         # heads of 256 (PR 49)
    (16384, WIDE, jnp.bfloat16, "cpu", False),
    (4096, (256, 128), jnp.bfloat16, "tpu", False),   # no pair of the four
    (32, (16, 16), jnp.float32, "tpu", False),        # qwen3_next.tiny()
], ids=["lfm2-cell", "one-tile-f32", "kanana-cell", "mla-one-tile-f32", "cpu",
        "here", "part-tile", "mla-part-tile", "tiny", "mla-tiny", "head8",
        "192-192", "128-128", "64-128", "float16", "sdar-cell", "sdar-cpu",
        "128-64", "sdar-tiny", "qwen3-next-cell", "qwen3-next-cpu", "256-128",
        "qwen3-next-tiny"])
def test_who_takes_the_kernel(seq_len, dims, dtype, platform, taken):
    assert engages(seq_len, *dims, dtype, platform) is taken


@pytest.mark.parametrize("seq_len, mask, taken", [
    (8192, BlockDiffusion(4096, 4), True),        # the SDAR cell
    (2 * TILE, BLOCK_MASK, True),
    (TILE, BlockDiffusion(TILE // 2, 4), False),  # whole tiles, but no copy
    (3 * TILE, BlockDiffusion(3 * TILE // 2, 4), False),
    (32, BlockDiffusion(16, 4), False),           # sdar.tiny()
    (2 * TILE, BlockDiffusion(TILE, 128), True),  # a block an own-block tile
    (2 * TILE, BlockDiffusion(TILE, 256), False),     # a block over one
    (2 * TILE, BlockDiffusion(TILE, TILE), False),    # a copy one block
], ids=["sdar-cell", "one-tile-a-copy", "half-a-tile-a-copy",
        "a-tile-and-a-half", "sdar-tiny", "block-128", "block-256",
        "block-1024"])
def test_under_the_block_mask_each_copy_is_whole_tiles(seq_len, mask, taken):
    """The kernel's keys are the clean copy alone, so the one rule asks
    for whole tiles of a copy, not of the doubled sequence, and for blocks
    that lie within a tile of the own blocks' kernels; off the TPU it
    says no before it looks; and a mask over another length is the
    caller's mistake wherever it is asked."""
    assert engages(seq_len, *SDAR, jnp.bfloat16, "tpu", mask=mask) is taken
    assert engages(seq_len, *SDAR, jnp.bfloat16, "cpu", mask=mask) is False
    with pytest.raises(ValueError, match="positions"):
        engages(seq_len + TILE, *SDAR, jnp.bfloat16, "tpu", mask=mask)


def test_half_a_tile_a_copy_takes_the_plain_path(monkeypatch):
    """As on a TPU, 1,024 positions of two copies of 512 are whole tiles of
    the doubled sequence and none of a copy: ``lfm2.attention`` asks with
    the mask and scores them in plain blocks."""
    cfg = sdar.tiny(head_dim=128, attn_q_block=256)
    p = sdar.init(jax.random.key(8), cfg)[0]["layers"][0]["attn"]
    u = jax.random.normal(jax.random.key(9), (1, TILE, cfg.hidden_size))
    mask = BlockDiffusion(TILE // 2, 4)
    positions = np.tile(np.arange(TILE // 2), 2)
    want = lfm2.attention(p, u, cfg, mask, positions)

    def no_kernel(*a, **kw):
        raise AssertionError("the kernel was called")

    monkeypatch.setattr(pallas_attention, "engages",
                        functools.partial(engages, platform="tpu"))
    monkeypatch.setattr(pallas_attention, "masked_gqa", no_kernel)
    got = lfm2.attention(p, u, cfg, mask, positions)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape, dv", [
    ((1, 4, TILE + 128, 64), 64), ((1, 4, T, 8), 8), ((1, 4, T, 192), 192),
    ((1, 4, T, 64), 128)],
    ids=["part-tile", "head8", "192-192", "64-128"])
def test_a_refused_shape_raises_in_the_kernel(shape, dv):
    q = jnp.zeros(shape, jnp.float32)
    k = jnp.zeros((shape[0], 2) + shape[2:], jnp.float32)
    v = jnp.zeros((shape[0], 2, shape[2], dv), jnp.float32)
    with pytest.raises(ValueError, match="whole tiles.*queries and keys, "
                                         "values"):
        causal_gqa(q, k, v, interpret=True)


def test_keys_of_another_size_than_the_queries_are_refused():
    q = jnp.zeros((1, 2, TILE, 192), jnp.float32)
    kv = jnp.zeros((1, 2, TILE, 128), jnp.float32)
    with pytest.raises(ValueError, match=r"\(n, Hkv, T, Dv\)"):
        causal_gqa(q, kv, kv, interpret=True)


def test_operands_that_lead_with_the_positions_are_refused():
    """The wrapper moves no axis: ``(n, T, H, D)`` operands, the layout it
    took until PR 45, are no sequence of whole tiles (two positions of
    ``T`` heads) and are refused, not swapped."""
    q = jnp.zeros((1, TILE, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="whole tiles"):
        causal_gqa(q, q, q, interpret=True)


def _attention_inputs(cfg, t, key=5):
    """An attention operator's weights as ``lfm2.init`` lays them out, and
    normalised input for two sequences."""
    d, hd = cfg.hidden_size, cfg.head_dim
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    ks = jax.random.split(jax.random.key(key), 5)
    p = {"q_proj": L.trunc_normal(ks[0], (d, hq * hd)),
         "k_proj": L.trunc_normal(ks[1], (d, hkv * hd)),
         "v_proj": L.trunc_normal(ks[2], (d, hkv * hd)),
         "o_proj": L.trunc_normal(ks[3], (hq * hd, d)),
         "q_norm": L.rms_init(hd), "k_norm": L.rms_init(hd)}
    return p, jax.random.normal(ks[4], (2, t, d))


@pytest.mark.parametrize("cfg, t", [
    (lfm2.tiny(), 16),                                # head size 8
    (lfm2.tiny(head_dim=64, attn_q_block=24), 72),    # not a whole tile
], ids=["head8", "part-tile"])
def test_a_refused_shape_takes_the_plain_path_bit_for_bit(monkeypatch, cfg, t):
    """As on a TPU (``engages`` answering for one): a shape the kernel
    refuses goes through ``_scores_in_blocks``, and the result is the one
    the model gives where there is no kernel at all."""
    p, u = _attention_inputs(cfg, t)
    want = lfm2.attention(p, u, cfg)
    asked = []

    def as_on_tpu(seq_len, head_dim_qk, head_dim_v, dtype, mask=CAUSAL):
        asked.append((seq_len, head_dim_qk, head_dim_v))
        return engages(seq_len, head_dim_qk, head_dim_v, dtype,
                       platform="tpu", mask=mask)

    def no_kernel(*a, **kw):
        raise AssertionError("the kernel was called")

    monkeypatch.setattr(pallas_attention, "engages", as_on_tpu)
    monkeypatch.setattr(pallas_attention, "causal_gqa", no_kernel)
    got = lfm2.attention(p, u, cfg)
    assert asked == [(t, cfg.head_dim, cfg.head_dim)]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_attention_takes_the_kernel_where_it_engages(monkeypatch):
    """With ``engages`` answering as on a TPU and the kernel interpreted,
    ``lfm2.attention`` of a whole tile at head size 64 goes through the
    kernel and agrees with the plain path, values and parameter
    gradients."""
    cfg = dataclasses.replace(lfm2.tiny(), head_dim=64, attn_q_block=256)
    p, u = _attention_inputs(cfg, TILE)

    def loss(p, u):
        return jnp.sum(lfm2.attention(p, u, cfg) ** 2)

    want, want_grads = jax.jit(jax.value_and_grad(loss))(p, u)
    calls = []

    def interpreted(q, k, v):
        calls.append(q.shape)
        return causal_gqa(q, k, v, interpret=True)

    monkeypatch.setattr(pallas_attention, "engages",
                        functools.partial(engages, platform="tpu"))
    monkeypatch.setattr(pallas_attention, "causal_gqa", interpreted)
    got, grads = jax.jit(jax.value_and_grad(loss))(p, u)
    assert calls == [(2, cfg.num_attention_heads, TILE, 64)]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for got_leaf, want_leaf in zip(jax.tree_util.tree_leaves(grads),
                                   jax.tree_util.tree_leaves(want_grads)):
        assert _gap(got_leaf, want_leaf) < 2e-5


def _mla_case(t, **kw):
    """Latent attention's weights as ``deepseek_v3.init`` lays them out (two
    heads, scaled up so that the softmax is far from uniform), and
    normalised input for one sequence."""
    cfg = deepseek_v3.tiny(num_attention_heads=2, **kw)
    p = deepseek_v3.init(jax.random.key(6), cfg)[0]["layers"][0]["attn"]
    p = jax.tree_util.tree_map(lambda x: x * 6 if x.ndim == 2 else x, p)
    return cfg, p, jax.random.normal(jax.random.key(7), (1, t, 32))


def test_a_refused_latent_shape_takes_the_plain_path_bit_for_bit(monkeypatch):
    """Heads of 12 | 8 as on a TPU: ``mla`` asks with both head sizes, is
    refused, folds no scale and gives the bits it gives without a kernel."""
    cfg, p, u = _mla_case(16)
    want = deepseek_v3.mla(p, u, cfg)
    asked = []

    def as_on_tpu(seq_len, head_dim_qk, head_dim_v, dtype, mask=CAUSAL):
        asked.append((seq_len, head_dim_qk, head_dim_v))
        return engages(seq_len, head_dim_qk, head_dim_v, dtype,
                       platform="tpu", mask=mask)

    def no_kernel(*a, **kw):
        raise AssertionError("the kernel was called")

    monkeypatch.setattr(pallas_attention, "engages", as_on_tpu)
    monkeypatch.setattr(pallas_attention, "causal_gqa", no_kernel)
    got = deepseek_v3.mla(p, u, cfg)
    assert asked == [(16, 12, 8)]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_latent_attention_takes_the_kernel_where_it_engages(monkeypatch):
    """With ``engages`` answering as on a TPU and the kernel interpreted,
    ``deepseek_v3.mla`` of a whole tile at the published head sizes (128 +
    64 | 128) goes through the kernel with the scale folded into ``W_q``
    (the kernel applies none) and agrees with the plain path, values and
    parameter gradients: the shared rotary key's among them."""
    cfg, p, u = _mla_case(TILE, qk_nope_head_dim=128, qk_rope_head_dim=64,
                          v_head_dim=128, attn_q_block=256)

    def loss(p, u):
        return jnp.sum(deepseek_v3.mla(p, u, cfg) ** 2)

    want, want_grads = jax.jit(jax.value_and_grad(loss))(p, u)
    calls = []

    def interpreted(q, k, v):
        calls.append((q.shape, k.shape, v.shape))
        return causal_gqa(q, k, v, interpret=True)

    monkeypatch.setattr(pallas_attention, "engages",
                        functools.partial(engages, platform="tpu"))
    monkeypatch.setattr(pallas_attention, "causal_gqa", interpreted)
    got, grads = jax.jit(jax.value_and_grad(loss))(p, u)
    assert calls == [((1, 2, TILE, 192), (1, 2, TILE, 192),
                      (1, 2, TILE, 128))]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for (path, got_leaf), want_leaf in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree_util.tree_leaves(want_grads)):
        assert _gap(got_leaf, want_leaf) < 2e-5, jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(want_leaf))) > 0


# ---------------------------------------------------------------------------
# what a recomputed part keeps of the kernel (PR 33)
# ---------------------------------------------------------------------------

def _lfm2_part():
    """The attention operator of a decoder layer at head size 64, with its
    weights and two sequences of one tile."""
    cfg = dataclasses.replace(lfm2.tiny(), head_dim=64, attn_q_block=256)
    layer = cfg.layer_types.index("full_attention")
    p = lfm2.init(jax.random.key(8), cfg)[0]["layers"][layer]
    return lfm2._operator_part("full_attention", cfg), p, cfg.hidden_size


def _latent_part():
    """Latent attention of a decoder layer at the published head sizes
    (128 + 64 | 128), two heads."""
    cfg = deepseek_v3.tiny(num_attention_heads=2, qk_nope_head_dim=128,
                           qk_rope_head_dim=64, v_head_dim=128,
                           attn_q_block=256)
    p = deepseek_v3.init(jax.random.key(8), cfg)[0]["layers"][0]
    return deepseek_v3._mla_part(cfg), p, cfg.hidden_size


def _sdar_part():
    """Attention of an SDAR layer at Qwen3's head size of 128 under the
    block-diffusion mask: the noised copy's tile and the clean copy's."""
    cfg = sdar.tiny(head_dim=128, attn_q_block=256)
    p = sdar.init(jax.random.key(8), cfg)[0]["layers"][0]
    return (sdar._attention_part(cfg, BLOCK_MASK, np.tile(np.arange(TILE), 2)),
            p, cfg.hidden_size, T)


PARTS = {"lfm2-attention": _lfm2_part, "latent-attention": _latent_part,
         "sdar-attention": _sdar_part}


@pytest.fixture
def kernel_interpreted(monkeypatch):
    """``engages`` answering as on a TPU, the kernel in Pallas's
    interpreter."""
    monkeypatch.setattr(pallas_attention, "engages",
                        functools.partial(engages, platform="tpu"))
    monkeypatch.setattr(pallas_attention, "causal_gqa",
                        functools.partial(causal_gqa, interpret=True))
    monkeypatch.setattr(pallas_attention, "masked_gqa",
                        functools.partial(masked_gqa, interpret=True))


def _walked(part, p, hidden, length=TILE):
    """Value and gradient of a part walked over two sequences of ``length``
    positions, one after the other, as the step walks it (a function of its
    own each time: ``keep_nothing``)."""
    x = jax.random.normal(jax.random.key(9), (2, length, hidden))

    def loss(p, x):
        return jnp.sum(lfm2._over_sequences(part, p, x, 1) ** 2)

    return jax.value_and_grad(loss, argnums=(0, 1)), p, x


@pytest.mark.parametrize("mask", [CAUSAL, BLOCK_MASK],
                         ids=["causal", "block-diffusion"])
def test_the_forward_rule_names_its_output_and_log_sum_exp(mask):
    """The name is on the kernel whoever calls it: under no
    ``jax.checkpoint`` at all the gradient's jaxpr holds it twice (output,
    log-sum-exp) and nothing else changes. Under the block-diffusion mask
    the two named are the merged output and the joint log-sum-exp, all the
    backward reads of the forward; the fused kernel is there twice as under
    the causal mask (forward, fused backward), and beside each call the own
    blocks' kernel of that direction."""
    q, k, v, _ = _inputs(2, jnp.float32, t=T if mask == BLOCK_MASK else TILE,
                         dims=SDAR)
    text = str(jax.make_jaxpr(jax.grad(
        lambda q: jnp.sum(masked_gqa(q, k, v, mask, interpret=True))))(q))
    assert text.count(f"name[name={pallas_attention.RESIDUAL_NAME}]") == 2
    own = 2 if mask == BLOCK_MASK else 0
    assert text.count("name=splash_mha") == 2    # forward, fused backward
    assert text.count(f"name={pallas_attention.OWN_BLOCK_NAME}") == own
    assert text.count("pallas_call") == 2 + own


@pytest.mark.parametrize("which", sorted(PARTS))
def test_a_recomputed_part_runs_the_forward_kernel_once(
        kernel_interpreted, keep_nothing, which):
    """Through ``_over_sequences`` the gradient of an attention part holds
    the kernel twice, forward and fused backward: the backward pass reads
    the kept output and log-sum-exp (under the block-diffusion mask the
    merged output and the joint log-sum-exp). Under a ``jax.checkpoint``
    that keeps nothing it holds a third call, the forward run again."""
    grad, p, x = _walked(*PARTS[which]())
    kept = str(jax.make_jaxpr(grad)(p, x))
    keep_nothing()
    grad, p, x = _walked(*PARTS[which]())
    recomputed = str(jax.make_jaxpr(grad)(p, x))
    # under the block-diffusion mask the own blocks' kernel of a direction
    # stands beside each call of the fused kernel
    beside = 2 if which == "sdar-attention" else 1
    assert kept.count("pallas_call") == 2 * beside
    assert recomputed.count("pallas_call") == 3 * beside
    assert kept.count("name=splash_mha") == 2
    assert recomputed.count("name=splash_mha") == 3
    name = f"name[name={pallas_attention.RESIDUAL_NAME}]"
    assert kept.count(name) == 2 and name in recomputed


@pytest.mark.parametrize("which", sorted(PARTS))
def test_the_kept_residuals_are_the_recomputed_ones_bit_for_bit(
        kernel_interpreted, keep_nothing, which):
    """Value, parameter gradients and input gradient of an attention part
    are the same bits whether the backward pass reads what the forward
    kernel wrote or runs it again. Under the block-diffusion mask what is
    kept was merged by XLA, which rounds the own block's float32
    arithmetic as it happens to fuse it in either program: the same
    numbers to float32's last bits, not the same bits."""
    grad, p, x = _walked(*PARTS[which]())
    kept = jax.jit(grad)(p, x)
    keep_nothing()
    grad, p, x = _walked(*PARTS[which]())
    recomputed = jax.jit(grad)(p, x)
    for (path, got), want in zip(
            jax.tree_util.tree_flatten_with_path(kept)[0],
            jax.tree_util.tree_leaves(recomputed)):
        if which == "sdar-attention":
            np.testing.assert_allclose(
                got, want, rtol=1e-4, atol=1e-6 * float(jnp.max(jnp.abs(want))),
                err_msg=jax.tree_util.keystr(path))
        else:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                          err_msg=jax.tree_util.keystr(path))
    assert float(jnp.max(jnp.abs(kept[1][1]))) > 0


# ---------------------------------------------------------------------------
# the same kernel under a sliding window (PR 43)
# ---------------------------------------------------------------------------

# a window that ends inside a tile: of the 4 x 4 tiles of 1,024 over 4,096
# positions it leaves the diagonal's four, the three below it and the two
# two below (their nearest pair is 1,025 apart)
WINDOW = SlidingWindow(1500)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernel_agrees_with_scores_in_blocks_under_a_window(dtype):
    """Four tiles of 1,024 positions at heads of 128 | 128, a window of
    1,500: the interpreted kernel against ``lfm2._scores_in_blocks``, 512
    queries at a time over the keys they can read, output and the three
    gradients."""
    q, k, v, w = _inputs(2, dtype, t=4 * TILE, dims=SDAR)
    kernel = _weighted(lambda q, k, v: masked_gqa(_scaled(q), k, v, WINDOW,
                                                  interpret=True))
    (_, out), grads = kernel(q, k, v, w)
    (_, want), want_grads = _weighted(functools.partial(
        lfm2._plain_scores, q_block=512, mask=WINDOW))(q, k, v, w)
    assert out.dtype == dtype and out.shape == q.shape
    tol = TOLERANCE[dtype]
    assert _gap(out, want) < tol
    for name, got, ref in zip("qkv", grads, want_grads):
        assert got.dtype == dtype and got.shape == ref.shape
        assert _gap(got, ref) < tol, name
    # and it is no causal attention: the last tile's queries read a window
    causal = causal_gqa(_scaled(q), k, v, interpret=True)
    assert _gap(out[:, :, 3 * TILE:], causal[:, :, 3 * TILE:]) > 0.1
    np.testing.assert_allclose(np.asarray(out[:, :, :1500], np.float32),
                               np.asarray(causal[:, :, :1500], np.float32),
                               rtol=2e-2, atol=1e-3)


def test_the_kernel_reads_a_window_and_no_more():
    """A key's value changed: the queries within the window after it move,
    the queries past it and before it do not, bit for bit."""
    q, k, v, _ = _inputs(1, jnp.float32, t=4 * TILE, dims=SDAR)
    fn = jax.jit(lambda v: masked_gqa(_scaled(q), k, v, WINDOW,
                                      interpret=True))
    base, moved = fn(v), fn(v.at[:, :, 1000].add(5.0))
    changed = np.flatnonzero(np.asarray(
        jnp.any(base != moved, axis=(0, 1, 3))))
    assert changed.min() == 1000 and changed.max() == 1000 + 1499
    assert len(changed) == 1500


@pytest.mark.parametrize("seq_len, window, visited", [
    (16384, 4096, 70), (4096, 1500, 9), (4096, 1024, 7), (4096, 4096, 10),
    (2048, 1, 2)])
def test_tiles_outside_the_band_are_not_visited(seq_len, window, visited):
    """The kernel's own table of tiles (0: skipped) under a window: at the
    SmallThinker cell's 16,384 positions in tiles of 1,024 a window of 4,096
    leaves 70 of 256 (five a row of queries from the fifth row on), where
    the causal mask leaves 136; forward and fused backward alike (the
    forward's grid is five key tiles wide, not sixteen). The mask is
    evaluated from positions: the kernel carries no mask blocks."""
    kernel = pallas_attention._kernel(seq_len, 2, True,
                                      SlidingWindow(window))
    tiles = seq_len // TILE
    for info in (kernel.fwd_mask_info, kernel.dkv_mask_info):
        table = np.asarray(info.block_mask)
        assert int((table > 0).sum()) == visited
        assert info.partial_mask_blocks is None
    if seq_len == 16384:
        assert np.asarray(kernel.fwd_mask_info.block_mask).shape[-1] == 5
        assert np.asarray(kernel.dkv_mask_info.block_mask).size == tiles ** 2
    causal = pallas_attention._kernel(seq_len, 2, True)
    assert int((np.asarray(causal.fwd_mask_info.block_mask) > 0).sum()) \
        == tiles * (tiles + 1) // 2


@pytest.mark.parametrize("seq_len, platform, taken", [
    (16384, "tpu", True), (16384, "cpu", False), (TILE, "tpu", True),
    (TILE + 512, "tpu", False), (32, "tpu", False)],
    ids=["smallthinker-cell", "cpu", "one-tile", "part-tile", "tiny"])
def test_who_takes_the_kernel_under_a_window(seq_len, platform, taken):
    assert engages(seq_len, *SDAR, jnp.bfloat16, platform,
                   mask=SlidingWindow(4096)) is taken


def _headless_inputs(cfg, t):
    """Attention's weights without norms on the heads, as
    ``smallthinker.init`` lays them out, scaled so that the softmax is far
    from uniform."""
    from grace_tpu.models import smallthinker
    p = smallthinker.init(jax.random.key(4), cfg)[0]["layers"][1]["attn"]
    p = jax.tree_util.tree_map(lambda x: x * 8, p)
    return p, jax.random.normal(jax.random.key(5), (1, t, cfg.hidden_size))


@pytest.mark.parametrize("rotate", [True, False], ids=["rotary", "nope"])
def test_attention_without_head_norms_takes_the_kernel_under_its_mask(
        monkeypatch, rotate):
    """``lfm2.attention`` of a SmallThinker-shaped layer (no norm on the
    heads, heads of 128, so the scale ``1/sqrt(128)`` goes into the query
    projection's float32 weights) under a window, rotated or not: with
    ``engages`` answering as on a TPU and the kernel interpreted it agrees
    with the plain path, values and parameter gradients, and the kernel is
    handed the window."""
    from grace_tpu.models import smallthinker
    cfg = smallthinker.tiny(head_dim=128, num_attention_heads=2,
                            num_key_value_heads=1, attn_q_block=512)
    mask = SlidingWindow(700)
    p, u = _headless_inputs(cfg, 2 * TILE)
    assert "q_norm" not in p

    def loss(p, u):
        return jnp.sum(lfm2.attention(p, u, cfg, mask, rotate=rotate) ** 2)

    want, want_grads = jax.jit(jax.value_and_grad(loss))(p, u)
    calls = []

    def interpreted(q, k, v, mask):
        calls.append((q.shape, mask))
        return masked_gqa(q, k, v, mask, interpret=True)

    monkeypatch.setattr(pallas_attention, "engages",
                        functools.partial(engages, platform="tpu"))
    monkeypatch.setattr(pallas_attention, "masked_gqa", interpreted)
    got, grads = jax.jit(jax.value_and_grad(loss))(p, u)
    assert calls == [((1, 2, 2 * TILE, 128), mask)]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for got_leaf, want_leaf in zip(jax.tree_util.tree_leaves(grads),
                                   jax.tree_util.tree_leaves(want_grads)):
        assert _gap(got_leaf, want_leaf) < 2e-5
    # positions matter to the rotary layer alone
    other = jax.jit(lambda p, u: jnp.sum(lfm2.attention(
        p, u, cfg, mask, rotate=not rotate) ** 2))(p, u)
    assert abs(float(other) - float(want)) > 1e-3 * abs(float(want))


def test_gated_attention_at_heads_of_256_takes_the_kernel(monkeypatch):
    """``lfm2.attention`` of a Qwen3-Next-shaped full layer (heads of 256,
    zero-centred norms on the heads, the first 64 numbers rotated, the
    query projection carrying the output gate): with ``engages`` answering
    as on a TPU and the kernel interpreted it agrees with the plain path,
    values and parameter gradients; the kernel is handed the queries alone
    (256 wide, not the gate's 256 behind them), scaled by 1/16 exactly."""
    from grace_tpu.models import qwen3_next
    cfg = qwen3_next.tiny(head_dim=256, rotary_dim=64, num_attention_heads=2,
                          num_key_value_heads=1, attn_q_block=512)
    p = qwen3_next.init(jax.random.key(4), cfg)[0]["layers"][3]["op"]
    p = jax.tree_util.tree_map(lambda x: x * 8 if x.ndim == 2 else x + 1.0, p)
    assert p["q_proj"].shape == (cfg.hidden_size, 2 * 2 * 256)
    u = jax.random.normal(jax.random.key(5), (1, TILE, cfg.hidden_size))

    def loss(p, u):
        return jnp.sum(lfm2.attention(p, u, cfg, rotary_dim=64,
                                      gated=True) ** 2)

    want, want_grads = jax.jit(jax.value_and_grad(loss))(p, u)
    calls = []

    def interpreted(q, k, v):
        calls.append((q.shape, k.shape))
        return causal_gqa(q, k, v, interpret=True)

    monkeypatch.setattr(pallas_attention, "engages",
                        functools.partial(engages, platform="tpu"))
    monkeypatch.setattr(pallas_attention, "causal_gqa", interpreted)
    got, grads = jax.jit(jax.value_and_grad(loss))(p, u)
    assert calls == [((1, 2, TILE, 256), (1, 1, TILE, 256))]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for got_leaf, want_leaf in zip(jax.tree_util.tree_leaves(grads),
                                   jax.tree_util.tree_leaves(want_grads)):
        assert _gap(got_leaf, want_leaf) < 2e-5
    # the gate is part of the result: without it the layer is another one
    ungated = jax.jit(lambda p, u: jnp.sum(lfm2.attention(
        dict(p, q_proj=p["q_proj"].reshape(-1, 2, 2, 256)[:, :, 0].reshape(
            -1, 512)), u, cfg, rotary_dim=64) ** 2))(p, u)
    assert abs(float(ungated) - float(want)) > 1e-3 * abs(float(want))


# ---------------------------------------------------------------------------
# head-major from product to product (PR 45)
# ---------------------------------------------------------------------------

def _causal_layer():
    cfg = dataclasses.replace(lfm2.tiny(), head_dim=64, attn_q_block=256)
    p, u = _attention_inputs(cfg, TILE)
    return functools.partial(lfm2.attention, cfg=cfg), p, u, (4, 2, TILE, 64)


def _headless_layer(mask, rotate):
    from grace_tpu.models import smallthinker
    cfg = smallthinker.tiny(head_dim=128, num_attention_heads=2,
                            num_key_value_heads=1, attn_q_block=512)
    p, u = _headless_inputs(cfg, 2 * TILE)
    return (functools.partial(lfm2.attention, cfg=cfg, mask=mask,
                              rotate=rotate), p, u, (2, 1, 2 * TILE, 128))


def _block_diffusion_layer():
    cfg = sdar.tiny(head_dim=128, attn_q_block=256)
    p = sdar.init(jax.random.key(8), cfg)[0]["layers"][0]["attn"]
    p = jax.tree_util.tree_map(lambda x: x * 8 if x.ndim == 2 else x, p)
    u = jax.random.normal(jax.random.key(9), (1, T, cfg.hidden_size))
    return (functools.partial(lfm2.attention, cfg=cfg, mask=BLOCK_MASK,
                              positions=np.tile(np.arange(TILE), 2)), p, u,
            (cfg.num_attention_heads, cfg.num_key_value_heads, T, 128))


def _latent_layer():
    cfg, p, u = _mla_case(TILE, qk_nope_head_dim=128, qk_rope_head_dim=64,
                          v_head_dim=128, attn_q_block=256)
    return functools.partial(deepseek_v3.mla, cfg=cfg), p, u, (2, 2, TILE, 192)


LAYERS = {
    "causal": _causal_layer,
    "window": functools.partial(_headless_layer, SlidingWindow(700), True),
    "no-position": functools.partial(_headless_layer, CAUSAL, False),
    "block-diffusion": _block_diffusion_layer,
    "latent": _latent_layer,
}


@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_a_layer_on_the_kernel_route_equals_its_plain_route(monkeypatch,
                                                            layer):
    """``lfm2.attention`` (causal with head norms, windowed and without
    positions as SmallThinker's layers are, under the block-diffusion mask
    by position ids as SDAR's) and ``deepseek_v3.mla``, with ``engages``
    answering as on a TPU and the kernel interpreted, against the same layer
    on the plain route: value, parameter gradients and the input's gradient
    in float32. The kernel's wrapper is handed head-major operands, ``(n, H,
    T, D)``, as the projections wrote them: no axis is moved between a
    product and the kernel."""
    attend, p, u, (hq, hkv, t, d) = LAYERS[layer]()

    def loss(p, u):
        return jnp.sum(attend(p, u) ** 2)

    def grad():     # traced anew on either route
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))

    want, want_grads = grad()(p, u)
    handed = []

    def interpreted(q, k, v, mask=CAUSAL):
        handed.append((q.shape, k.shape[:3], v.shape[:3]))
        return masked_gqa(q, k, v, mask, interpret=True)

    monkeypatch.setattr(pallas_attention, "engages",
                        functools.partial(engages, platform="tpu"))
    monkeypatch.setattr(pallas_attention, "causal_gqa", interpreted)
    monkeypatch.setattr(pallas_attention, "masked_gqa", interpreted)
    got, grads = grad()(p, u)
    n = u.shape[0]
    assert handed == [((n, hq, t, d), (n, hkv, t), (n, hkv, t))]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for (path, got_leaf), want_leaf in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree_util.tree_leaves(want_grads)):
        assert _gap(got_leaf, want_leaf) < TOLERANCE[jnp.float32], \
            jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(want_leaf))) > 0
