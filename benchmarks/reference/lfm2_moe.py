"""Plain reference of the decoder the ``lfm2-24b-a2b-ep8`` configuration
trains, in ``jax.numpy``: weights from a seed, forward pass, next-token
loss. Imports nothing of ``grace_tpu``; no grouped product, no sort.

The model is LiquidAI's ``lfm2_moe`` (LFM2-24B-A2B ``config.json``; the
equations as its ``transformers`` model computes them). With ``u =
RMSNorm(x)`` (learned weight, ``eps`` ``norm_eps``), no bias anywhere:

* layer: ``h = x + Op(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; after the
  last layer RMSNorm and the output head.
* ``conv``: ``(B, C, X) = split3(W_in u)``; ``z = B * X``; ``c_t = sum_j
  w_j * z_{t-(L-1)+j}`` with zeros before the sequence's start (depth-wise,
  ``L = conv_L_cache``); ``Op = W_out (C * c)``.
* ``full_attention``: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads; RMSNorm over each query and key
  head; rotary positions over the whole head (rotate-half, ``rope_theta``);
  causal ``softmax(q k^T / sqrt(head_dim)) v``; ``W_o``.
* dense feed-forward (the leading layers): ``W_2 (silu(W_1 h) * W_3 h)``.
* expert feed-forward: ``s = sigmoid(W_r h)`` over all the router's
  outputs; the ``num_experts_per_tok`` largest of ``s + b``; their weights
  ``s_i / (sum s_i + 1e-6) * routed_scaling_factor``; every **held** expert
  is applied to every token and its result weighted by the token's gate
  for it, which is zero where the token did not choose it.

**The share.** The configuration is one chip's share of a layer divided
over ``chips_sharing_a_layer`` chips: ``num_experts`` experts held of
``published.num_experts`` the router scores (experts ``share * held`` on),
``vocab_size`` rows of the embedding and the head. A token's result is the
sum over those of its chosen experts that are held, weighted as above
(normalised over all chosen); what the absent experts would add is left
out and the partial result goes on to the next layer. Token ids, logits
and loss are over the rows held.

Departures from the published model: the expert bias ``b`` is held at
zero and never updated (the config gives no update rule); embedding and
head are untied (the config carries no ``tie_word_embeddings``); ``head_dim``
is ``hidden_size / num_attention_heads``. Precision is the configuration's:
parameters ``param_dtype``, activations ``activation_dtype``; RMSNorm,
rotation, softmax, the router's scores and the loss in float32.

Memory: the loss walks the batch one sequence after another and
attention one key/value head after another, each recomputed in the
backward pass, because all of it at once does not fit beside the
reference's own optimizer state (routing is per token and attention per
sequence, so the result is the same).

The weights are laid out as the nested dict ``grace_tpu.models.lfm2`` reads.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

INIT_STD = 0.02


def layout(sizes):
    """What the share holds: the kinds of its layers, which of them have
    experts, the router's width and the first expert held."""
    kinds = [sizes["layer_types"][i] for i in sizes["layers_held"]]
    if len(kinds) != sizes["num_hidden_layers"]:
        raise ValueError("layers_held and num_hidden_layers disagree")
    return {"kinds": kinds,
            "moe": [i >= sizes["num_dense_layers"] for i in range(len(kinds))],
            "router": sizes["published"]["num_experts"],
            "first": sizes["share"] * sizes["num_experts"],
            "head_dim": sizes["hidden_size"] // sizes["num_attention_heads"]}


def init(key, sizes, param_dtype=jnp.float32):
    """Seeded weights and the model's state: truncated normal (std 0.02)
    matrices, unit norm weights, convolution kernels uniform in
    +-1/sqrt(L) (PyTorch's ``Conv1d`` default), expert bias zero."""
    lay = layout(sizes)
    d, hd = sizes["hidden_size"], lay["head_dim"]
    taps = sizes["conv_L_cache"]
    n = [0]

    def k():
        n[0] += 1
        return jax.random.fold_in(key, n[0])

    def mat(*shape):
        return (jax.random.truncated_normal(k(), -2.0, 2.0, shape,
                                            jnp.float32)
                * INIT_STD).astype(param_dtype)

    def norm(width):
        return {"scale": jnp.ones((width,), param_dtype)}

    def layer(kind, moe):
        if kind == "conv":
            bound = 1.0 / math.sqrt(taps)
            op = {"in_proj": mat(d, 3 * d),
                  "kernel": jax.random.uniform(
                      k(), (taps, d), jnp.float32, -bound,
                      bound).astype(param_dtype),
                  "out_proj": mat(d, d)}
        else:
            hq, hkv = (sizes["num_attention_heads"],
                       sizes["num_key_value_heads"])
            op = {"q_proj": mat(d, hq * hd), "k_proj": mat(d, hkv * hd),
                  "v_proj": mat(d, hkv * hd), "o_proj": mat(hq * hd, d),
                  "q_norm": norm(hd), "k_norm": norm(hd)}
        if moe:
            e, f = sizes["num_experts"], sizes["moe_intermediate_size"]
            ffn = {"router": mat(d, lay["router"]), "w1": mat(e, d, f),
                   "w3": mat(e, d, f), "w2": mat(e, f, d)}
        else:
            f = sizes["intermediate_size"]
            ffn = {"w1": mat(d, f), "w3": mat(d, f), "w2": mat(f, d)}
        return {"op_norm": norm(d), "op": op, "ffn_norm": norm(d),
                "ffn": ffn}

    params = {"embed": {"table": mat(sizes["vocab_size"], d)},
              "layers": [layer(kind, moe)
                         for kind, moe in zip(lay["kinds"], lay["moe"])],
              "final_norm": norm(d),
              "head": mat(d, sizes["vocab_size"])}

    def moe_state():
        return {"expert_bias": jnp.zeros((lay["router"],), jnp.float32),
                "drawn": jnp.zeros((lay["router"],), jnp.float32),
                "held": jnp.zeros((), jnp.float32),
                "dropped": jnp.zeros((), jnp.float32)}

    state = {"layers": [moe_state() if moe else {} for moe in lay["moe"]]}
    return params, state


def make_batch(key, n, sizes):
    """``n`` sequences of ``seq_length`` token ids, uniform over the rows
    of the vocabulary held."""
    return jax.random.randint(key, (n, sizes["seq_length"]), 0,
                              sizes["vocab_size"], jnp.int32)


# ---------------------------------------------------------------------------
# one sequence: x is (T, d)
# ---------------------------------------------------------------------------

def _mm(x, w):
    return x @ w.astype(x.dtype)


def _rms(p, x, eps):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def _rotate(x, theta):
    """``x``: ``(T, heads, head_dim)``."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv
    xf = x.astype(jnp.float32)
    a, b = xf[..., :d // 2], xf[..., d // 2:]
    return jnp.concatenate(
        [a * jnp.cos(angle) - b * jnp.sin(angle),
         b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1).astype(x.dtype)


def _conv(p, u, sizes):
    d = u.shape[-1]
    bcx = _mm(u, p["in_proj"])
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = b * x
    taps, t = sizes["conv_L_cache"], u.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, d), z.dtype), z], axis=0)
    w = p["kernel"].astype(z.dtype)
    conv = jnp.zeros_like(z)
    for j in range(taps):
        conv = conv + w[j] * padded[j:j + t]
    return _mm(c * conv, p["out_proj"])


def _attention(p, u, sizes, head_dim):
    t = u.shape[0]
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    eps, theta = sizes["norm_eps"], sizes["rope_parameters"]["rope_theta"]
    q = _mm(u, p["q_proj"]).reshape(t, hq, head_dim)
    k = _mm(u, p["k_proj"]).reshape(t, hkv, head_dim)
    v = _mm(u, p["v_proj"]).reshape(t, hkv, head_dim)
    q = _rotate(_rms(p["q_norm"], q, eps), theta)
    k = _rotate(_rms(p["k_norm"], k, eps), theta)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def group(qkv):
        """The query heads one key/value head serves."""
        qg, kg, vg = qkv                       # (T, G, D), (T, D), (T, D)
        s = jnp.einsum("qgd,kd->gqk", qg, kg).astype(jnp.float32)
        s = jnp.where(causal, s / math.sqrt(head_dim), -jnp.inf)
        a = jax.nn.softmax(s, axis=-1).astype(vg.dtype)
        return jnp.einsum("gqk,kd->qgd", a, vg)

    qg = q.reshape(t, hkv, hq // hkv, head_dim).transpose(1, 0, 2, 3)
    out = lax.map(jax.checkpoint(group),
                  (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return _mm(out.transpose(1, 0, 2, 3).reshape(t, hq * head_dim),
               p["o_proj"])


def _gated(u, w1, w3, w2):
    return _mm(jax.nn.silu(_mm(u, w1)) * _mm(u, w3), w2)


def _experts(p, bias, u, sizes, first):
    """The held experts' part of the expert layer's result."""
    s = jax.nn.sigmoid(_mm(u, p["router"]).astype(jnp.float32))
    biased = s + bias
    chosen = jnp.zeros(s.shape, bool)
    for _ in range(sizes["num_experts_per_tok"]):     # the largest, k times
        best = jnp.argmax(jnp.where(chosen, -jnp.inf, biased), axis=-1)
        chosen = chosen | jax.nn.one_hot(best, s.shape[-1], dtype=bool)
    picked = jnp.where(chosen, s, 0.0)
    gates = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    gates = gates * sizes["routed_scaling_factor"]
    y = jnp.zeros(u.shape, jnp.float32)
    for e in range(sizes["num_experts"]):
        out = _gated(u, p["w1"][e], p["w3"][e], p["w2"][e])
        y = y + gates[:, first + e, None] * out.astype(jnp.float32)
    return y.astype(u.dtype)


def _layer(p, state, x, kind, moe, sizes, lay):
    eps = sizes["norm_eps"]
    u = _rms(p["op_norm"], x, eps)
    x = x + (_conv(p["op"], u, sizes) if kind == "conv"
             else _attention(p["op"], u, sizes, lay["head_dim"]))
    u = _rms(p["ffn_norm"], x, eps)
    f = p["ffn"]
    return x + (_experts(f, state["expert_bias"], u, sizes, lay["first"])
                if moe else _gated(u, f["w1"], f["w3"], f["w2"]))


def loss(params, state, batch, sizes, activation_dtype=jnp.bfloat16):
    """Mean over all tokens of the cross-entropy of position ``t``'s logits
    against token ``t + 1`` (a sequence's last position has no target):
    ``(loss, state)``. The counters in ``state`` are the program's own and
    pass through untouched."""
    lay = layout(sizes)
    n, t = batch.shape

    def sequence(ids):
        x = jnp.take(params["embed"]["table"], ids, axis=0).astype(
            activation_dtype)
        for p, s, kind, moe in zip(params["layers"], state["layers"],
                                   lay["kinds"], lay["moe"]):
            x = jax.checkpoint(
                lambda p, s, x, kind=kind, moe=moe: _layer(
                    p, s, x, kind, moe, sizes, lay))(p, s, x)
        u = _rms(params["final_norm"], x[:-1], sizes["norm_eps"])
        logits = _mm(u, params["head"]).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, ids[1:, None], axis=1))

    total = jnp.sum(lax.map(jax.checkpoint(sequence), batch))
    return total / (n * (t - 1)), state
