"""Pure-functional NN layers for grace-tpu's model zoo.

The reference has no model library of its own — its examples lean on
torchvision / keras.applications (examples/torch/pytorch_synthetic_benchmark.py:49,
examples/tensorflow/tensorflow2_synthetic_benchmark.py:63) plus one hand-rolled
CIFAR net (examples/dist/CIFAR10-dawndist/dawn.py:60-97). grace-tpu ships a
small functional layer kit instead: params are plain pytrees (so the GRACE
memory-state pytrees mirror them one leaf per tensor), layers are pure
``apply(params, x)`` functions that jit/shard_map cleanly, and layouts are
TPU-native (NHWC activations, HWIO conv kernels — XLA's preferred MXU tiling).

Stateful normalisation (BatchNorm running stats) is explicit: ``(params,
state) -> (out, new_state)``. No module classes, no tracing magic.
"""

from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Params = dict
ModelState = dict


# ---------------------------------------------------------------------------
# initialisers
# ---------------------------------------------------------------------------

def he_normal(key: jax.Array, shape: Sequence[int], fan_in: int,
              dtype=jnp.float32) -> jax.Array:
    std = math.sqrt(2.0 / fan_in)
    return jax.random.normal(key, tuple(shape), dtype) * std


def glorot_uniform(key: jax.Array, shape: Sequence[int], fan_in: int,
                   fan_out: int, dtype=jnp.float32) -> jax.Array:
    limit = math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, tuple(shape), dtype, -limit, limit)


def trunc_normal(key: jax.Array, shape: Sequence[int], std: float = 0.02,
                 dtype=jnp.float32) -> jax.Array:
    return jax.random.truncated_normal(key, -2.0, 2.0, tuple(shape), dtype) * std


# ---------------------------------------------------------------------------
# conv / dense
# ---------------------------------------------------------------------------

def conv_init(key: jax.Array, kh: int, kw: int, cin: int, cout: int,
              use_bias: bool = False) -> Params:
    """HWIO kernel (TPU/XLA-native conv layout)."""
    p = {"w": he_normal(key, (kh, kw, cin, cout), fan_in=kh * kw * cin)}
    if use_bias:
        p["b"] = jnp.zeros((cout,))
    return p


def conv_apply(p: Params, x: jax.Array, stride: int = 1,
               padding: str = "SAME") -> jax.Array:
    """NHWC conv. Kernel is cast to the activation dtype so a bf16 forward
    pass runs the MXU in bf16 while master params stay fp32."""
    y = lax.conv_general_dilated(
        x, p["w"].astype(x.dtype), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def dense_init(key: jax.Array, din: int, dout: int, use_bias: bool = True,
               init: str = "he") -> Params:
    if init == "he":
        w = he_normal(key, (din, dout), fan_in=din)
    elif init == "glorot":
        w = glorot_uniform(key, (din, dout), din, dout)
    else:
        w = trunc_normal(key, (din, dout))
    p = {"w": w}
    if use_bias:
        p["b"] = jnp.zeros((dout,))
    return p


def dense_apply(p: Params, x: jax.Array) -> jax.Array:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# normalisation
# ---------------------------------------------------------------------------

def bn_init(c: int) -> Tuple[Params, ModelState]:
    params = {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}
    state = {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))}
    return params, state


def bn_apply(p: Params, s: ModelState, x: jax.Array, train: bool,
             momentum: float = 0.9, eps: float = 1e-5
             ) -> Tuple[jax.Array, ModelState]:
    """BatchNorm over all non-channel axes; stats per device (the reference's
    DDP examples likewise never sync BN stats across ranks)."""
    red = tuple(range(x.ndim - 1))
    if train:
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=red)
        var = jnp.var(xf, axis=red)
        new_s = {"mean": momentum * s["mean"] + (1 - momentum) * mean,
                 "var": momentum * s["var"] + (1 - momentum) * var}
    else:
        mean, var = s["mean"], s["var"]
        new_s = s
    inv = lax.rsqrt(var + eps) * p["scale"]
    y = (x.astype(jnp.float32) - mean) * inv + p["bias"]
    return y.astype(x.dtype), new_s


def ln_init(d: int) -> Params:
    return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}


def ln_apply(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


def rms_init(d: int) -> Params:
    return {"scale": jnp.ones((d,))}


def rms_apply(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm over the last axis with a learned weight, computed in
    float32 and returned in the activation dtype."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * p["scale"]).astype(x.dtype)


def _angles(t: int, freq: jax.Array, axis: int,
            positions: jax.Array | None = None) -> jax.Array:
    """Float32 angles ``positions * freq`` (``0 .. t - 1`` without
    positions), shaped to broadcast against an array whose ``axis`` (from
    the end) holds the ``t`` entries and whose last holds ``freq``'s."""
    if positions is None:
        positions = jnp.arange(t, dtype=jnp.float32)
    elif positions.shape != (t,):
        raise ValueError(f"{positions.shape} positions for {t} entries")
    return (positions.astype(jnp.float32).reshape((t,) + (1,) * (-axis - 1))
            * freq)


def rotary(x: jax.Array, theta: float, positions: jax.Array | None = None,
           axis: int = -3, rotary_dim: int | None = None) -> jax.Array:
    """Rotary positions over the whole head or, given ``rotary_dim``, over
    its first ``rotary_dim`` numbers alone (``partial_rotary_factor``: the
    pairs are ``(j, j + rotary_dim / 2)``, the frequencies those of a head
    of ``rotary_dim``, and what lies behind passes through as it is),
    rotate-half: ``x`` is
    ``(..., T, heads, head_dim)`` or, with ``axis=-2``, head-major ``(...,
    heads, T, head_dim)``; along ``axis`` (counted from the end) lie the
    entries of ``positions`` ``(T,)`` or, without them, ``0 .. T - 1``.
    Angles and the rotation are float32, the same numbers in either layout."""
    d = x.shape[-1]
    if rotary_dim is not None and rotary_dim != d:
        if not 0 < rotary_dim < d or rotary_dim % 2:
            raise ValueError(f"{rotary_dim} of a head's {d} numbers are not "
                             "whole pairs of it")
        turned = rotary(x[..., :rotary_dim], theta, positions, axis)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = _angles(x.shape[axis], freq, axis, positions)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def rotary_pairs(x: jax.Array, theta: float, axis: int = -3) -> jax.Array:
    """Rotary positions over neighbouring pairs (``rope_interleave``):
    entries ``2i`` and ``2i + 1`` of the last axis turn by the angle
    :func:`rotary` gives entries ``i`` and ``i + d/2``. Same layouts of
    ``x``: the positions along ``axis``, ``-3`` for ``(..., T, heads, d)``,
    ``-2`` for head-major ``(..., heads, T, d)``. An entry's partner
    (``-x[2i + 1]`` for ``2i``, ``x[2i]`` for ``2i
    + 1``) is reached by a product with a signed permutation matrix, which
    is exact in any dtype (one term a sum) and shuffles no lanes: two
    shifts along the 64-wide last axis and a select read 20 ms a layer more
    at 8 x 4,096 tokens and 32 heads on a TPU v5e (PERF.md section 6,
    PR 32)."""
    d = x.shape[-1]
    freq = theta ** (-(jnp.arange(d) // 2 * 2).astype(jnp.float32) / d)
    ang = _angles(x.shape[axis], freq, axis)
    swap = np.zeros((d, d), np.float32)
    swap[np.arange(1, d, 2), np.arange(0, d, 2)] = -1.0
    swap[np.arange(0, d, 2), np.arange(1, d, 2)] = 1.0
    partner = jnp.matmul(x, jnp.asarray(swap, x.dtype),
                         precision=lax.Precision.HIGHEST)
    return (x.astype(jnp.float32) * jnp.cos(ang)
            + partner.astype(jnp.float32) * jnp.sin(ang)).astype(x.dtype)


# ---------------------------------------------------------------------------
# pooling / misc
# ---------------------------------------------------------------------------

def max_pool(x: jax.Array, window: int = 2, stride: int | None = None
             ) -> jax.Array:
    stride = stride or window
    return lax.reduce_window(
        x, -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else x.dtype.type(0),
        lax.max, (1, window, window, 1), (1, stride, stride, 1), "VALID")


def avg_pool(x: jax.Array, window: int, stride: int | None = None,
             padding: str = "VALID") -> jax.Array:
    stride = stride or window
    dims, strides = (1, window, window, 1), (1, stride, stride, 1)
    summed = lax.reduce_window(x, jnp.zeros((), x.dtype), lax.add,
                               dims, strides, padding)
    # Divide by the per-window count of *real* elements so SAME padding does
    # not bias edge outputs low (count_exclude_pad semantics).
    counts = lax.reduce_window(jnp.ones_like(x), jnp.zeros((), x.dtype),
                               lax.add, dims, strides, padding)
    return summed / counts


def global_avg_pool(x: jax.Array) -> jax.Array:
    return jnp.mean(x, axis=(1, 2))


def embedding_init(key: jax.Array, vocab: int, d: int) -> Params:
    return {"table": trunc_normal(key, (vocab, d))}


def embedding_apply(p: Params, ids: jax.Array, dtype=None) -> jax.Array:
    t = p["table"]
    if dtype is not None:
        t = t.astype(dtype)
    return jnp.take(t, ids, axis=0)


def split_keys(key: jax.Array, n: int):
    return list(jax.random.split(key, n))
