"""Plain reference of the ResNet bottleneck network (He et al.,
arXiv:1512.03385, Table 1) in ``jax.numpy``/``lax``: weights from a seed,
forward pass, softmax cross-entropy. Imports nothing of ``grace_tpu``.

Departures from the paper, the same the program's model makes: the stride
of a down-sampling bottleneck sits on its 3x3 convolution ("v1.5"), and
convolutions carry no bias. The precision is the configuration's:
parameters ``param_dtype``, activations ``activation_dtype``, batch-norm
statistics and the classifier in float32.

The weights are laid out as the nested dict the program's model reads (a
checkpoint layout, not code): ``stem``/``stem_bn``, ``s<stage>b<block>``
with ``conv1..3``/``bn1..3`` (+ ``proj``/``proj_bn`` where the shape
changes), ``fc``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

BN_MOMENTUM = 0.9
BN_EPS = 1e-5


def _conv_w(key, kh, kw, cin, cout, dtype):
    std = math.sqrt(2.0 / (kh * kw * cin))             # He normal, fan-in
    return {"w": (jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
                  * std).astype(dtype)}


def _bn(c, dtype):
    return ({"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)},
            {"mean": jnp.zeros((c,), jnp.float32),
             "var": jnp.ones((c,), jnp.float32)})


def _block_plan(sizes):
    """(name, cin, cmid, stride) of every bottleneck, in order."""
    width, expand = sizes["base_width"], sizes["bottleneck_expansion"]
    cin = width
    for stage, n in enumerate(sizes["stage_blocks"]):
        cmid = width * 2 ** stage
        for b in range(n):
            yield f"s{stage}b{b}", cin, cmid, 2 if (b == 0 and stage) else 1
            cin = cmid * expand


def init(key, sizes, param_dtype=jnp.float32):
    """Seeded weights and batch-norm state: ``(params, state)``."""
    expand = sizes["bottleneck_expansion"]
    width = sizes["base_width"]
    n = [0]

    def k():
        n[0] += 1
        return jax.random.fold_in(key, n[0])

    params, state = {}, {}
    ks = sizes["stem_kernel"]
    params["stem"] = _conv_w(k(), ks, ks, 3, width, param_dtype)
    params["stem_bn"], state["stem_bn"] = _bn(width, param_dtype)
    cout = width
    for name, cin, cmid, stride in _block_plan(sizes):
        cout = cmid * expand
        p, s = {}, {}
        p["conv1"] = _conv_w(k(), 1, 1, cin, cmid, param_dtype)
        p["bn1"], s["bn1"] = _bn(cmid, param_dtype)
        p["conv2"] = _conv_w(k(), 3, 3, cmid, cmid, param_dtype)
        p["bn2"], s["bn2"] = _bn(cmid, param_dtype)
        p["conv3"] = _conv_w(k(), 1, 1, cmid, cout, param_dtype)
        p["bn3"], s["bn3"] = _bn(cout, param_dtype)
        if stride != 1 or cin != cout:
            p["proj"] = _conv_w(k(), 1, 1, cin, cout, param_dtype)
            p["proj_bn"], s["proj_bn"] = _bn(cout, param_dtype)
        params[name], state[name] = p, s
    classes = sizes["num_classes"]
    limit = math.sqrt(6.0 / (cout + classes))          # Glorot uniform
    params["fc"] = {
        "w": jax.random.uniform(k(), (cout, classes), jnp.float32,
                                -limit, limit).astype(param_dtype),
        "b": jnp.zeros((classes,), param_dtype)}
    return params, state


def make_batch(key, n, sizes):
    """``n`` images and labels, every row different."""
    kx, ky = jax.random.split(key)
    hw = sizes["image_size"]
    return (jax.random.normal(kx, (n, hw, hw, 3), jnp.float32),
            jax.random.randint(ky, (n,), 0, sizes["num_classes"], jnp.int32))


def _conv(p, x, stride=1):
    return lax.conv_general_dilated(
        x, p["w"].astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn_train(p, s, x):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=(0, 1, 2))
    var = jnp.var(xf, axis=(0, 1, 2))
    new_s = {"mean": BN_MOMENTUM * s["mean"] + (1 - BN_MOMENTUM) * mean,
             "var": BN_MOMENTUM * s["var"] + (1 - BN_MOMENTUM) * var}
    y = (xf - mean) * (lax.rsqrt(var + BN_EPS) * p["scale"]) + p["bias"]
    return y.astype(x.dtype), new_s


def _bottleneck(p, s, x, stride):
    ns = {}
    y, ns["bn1"] = _bn_train(p["bn1"], s["bn1"], _conv(p["conv1"], x))
    y = jax.nn.relu(y)
    y, ns["bn2"] = _bn_train(p["bn2"], s["bn2"],
                             _conv(p["conv2"], y, stride))
    y = jax.nn.relu(y)
    y, ns["bn3"] = _bn_train(p["bn3"], s["bn3"], _conv(p["conv3"], y))
    shortcut = x
    if "proj" in p:
        shortcut, ns["proj_bn"] = _bn_train(
            p["proj_bn"], s["proj_bn"], _conv(p["proj"], x, stride))
    return jax.nn.relu(y + shortcut), ns


def loss(params, state, batch, sizes, activation_dtype=jnp.bfloat16):
    """Mean softmax cross-entropy of one batch in training mode:
    ``(loss, new_state)``."""
    x, labels = batch
    ns = {}
    y = _conv(params["stem"], x.astype(activation_dtype), 2)
    y, ns["stem_bn"] = _bn_train(params["stem_bn"], state["stem_bn"], y)
    y = jax.nn.relu(y)
    y = lax.reduce_window(y, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    for name, _, _, stride in _block_plan(sizes):
        y, ns[name] = _bottleneck(params[name], state[name], y, stride)
    pooled = jnp.mean(y, axis=(1, 2)).astype(jnp.float32)
    fc = params["fc"]
    logits = pooled @ fc["w"].astype(jnp.float32) + fc["b"].astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return -jnp.mean(picked), ns
