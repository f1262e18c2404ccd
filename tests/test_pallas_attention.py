"""``ops.pallas_attention.causal_gqa`` against the plain spelling it
replaces on the chip (``models.lfm2._scores_block`` over the whole
sequence), in Pallas's interpreter on the CPU: output and the gradients of
``q``, ``k`` and ``v``, over dtype and group size, at a sequence of two
tiles so that a tile above the diagonal (skipped), on it (masked inside)
and below it (whole) all occur. And who takes the kernel: ``engages``
alone decides, from platform and shape, and ``lfm2.attention`` gives the
plain path's bits wherever it says no.

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

from grace_tpu.models import layers as L
from grace_tpu.models import lfm2
from grace_tpu.ops import pallas_attention
from grace_tpu.ops.pallas_attention import TILE, causal_gqa, engages

T = 2 * TILE
D = 64
HKV = 2


def _inputs(group, dtype, t=T, key=0):
    ks = jax.random.split(jax.random.key(key), 4)

    def normal(k, heads):
        return jax.random.normal(k, (1, t, heads, D), jnp.float32
                                 ).astype(dtype)

    return (normal(ks[0], HKV * group), normal(ks[1], HKV),
            normal(ks[2], HKV), normal(ks[3], HKV * group))


def _plain(q, k, v):
    n, t, hq, d = q.shape
    hkv = k.shape[2]
    out = lfm2._scores_block(q.reshape(n, t, hkv, hq // hkv, d), k, v, 0)
    return out.reshape(n, t, hq, d)


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


@pytest.mark.parametrize("group", [1, 4], ids=["mha", "gqa4"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernel_agrees_with_the_plain_spelling(dtype, group):
    q, k, v, w = _inputs(group, dtype)
    kernel = _weighted(lambda q, k, v: causal_gqa(q, k, v, interpret=True))
    (_, out), grads = kernel(q, k, v, w)
    (_, want), want_grads = _weighted(_plain)(q, k, v, w)
    assert out.dtype == dtype and out.shape == q.shape
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
    fused = causal_gqa(q, k, v, interpret=True)
    assert _gap(fused, exact) <= _gap(_plain(q, k, v), exact)


def test_the_kernel_is_causal():
    q, k, v, _ = _inputs(4, jnp.float32)
    out = causal_gqa(q, k, v, interpret=True)
    cut = TILE + 37           # inside the second tile's diagonal block
    k2 = k.at[:, cut:].set(7.0)
    v2 = v.at[:, cut:].set(-3.0)
    out2 = causal_gqa(q, k2, v2, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[:, :cut]),
                                  np.asarray(out2[:, :cut]))
    assert not np.allclose(np.asarray(out[:, cut:]), np.asarray(out2[:, cut:]))


@pytest.mark.parametrize("seq_len, head_dim, dtype, platform, taken", [
    (4096, 64, jnp.bfloat16, "tpu", True),
    (TILE, 64, jnp.float32, "tpu", True),
    (4096, 64, jnp.bfloat16, "cpu", False),
    (4096, 64, jnp.bfloat16, None, False),        # this process: the CPU
    (TILE + 128, 64, jnp.bfloat16, "tpu", False),
    (16, 8, jnp.float32, "tpu", False),           # lfm2.tiny()
    (4096, 8, jnp.bfloat16, "tpu", False),
    (4096, 64, jnp.float16, "tpu", False),
], ids=["cell", "one-tile-f32", "cpu", "here", "part-tile", "tiny", "head8",
        "float16"])
def test_who_takes_the_kernel(seq_len, head_dim, dtype, platform, taken):
    assert engages(seq_len, head_dim, dtype, platform) is taken


@pytest.mark.parametrize("shape", [(1, TILE + 128, 4, 64), (1, T, 4, 8)],
                         ids=["part-tile", "head8"])
def test_a_refused_shape_raises_in_the_kernel(shape):
    q = jnp.zeros(shape, jnp.float32)
    kv = jnp.zeros(shape[:2] + (2, shape[3]), jnp.float32)
    with pytest.raises(ValueError, match="whole tiles"):
        causal_gqa(q, kv, kv, interpret=True)


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

    def as_on_tpu(seq_len, head_dim, dtype):
        asked.append((seq_len, head_dim))
        return engages(seq_len, head_dim, dtype, platform="tpu")

    def no_kernel(*a, **kw):
        raise AssertionError("the kernel was called")

    monkeypatch.setattr(pallas_attention, "engages", as_on_tpu)
    monkeypatch.setattr(pallas_attention, "causal_gqa", no_kernel)
    got = lfm2.attention(p, u, cfg)
    assert asked == [(t, cfg.head_dim)]
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
    assert calls == [(2, TILE, cfg.num_attention_heads, 64)]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for got_leaf, want_leaf in zip(jax.tree_util.tree_leaves(grads),
                                   jax.tree_util.tree_leaves(want_grads)):
        assert _gap(got_leaf, want_leaf) < 2e-5
