"""Plain reference of the decoder the ``smallthinker-21b-a3b-ep8``
configuration trains, in ``jax.numpy`` and float32 at ``highest`` matmul
precision: weights from a seed, forward pass, next-token loss (its gradients
are ``jax.grad``'s). Imports nothing of ``grace_tpu``; no kernel, no grouped
product, no sort.

The model is ``PowerInfer/SmallThinker-21BA3B-Instruct`` as its
``config.json`` states it. With ``x`` the layer's input (the residual
stream), ``RMSNorm`` with a learned weight and ``eps`` ``rms_norm_eps``, no
bias anywhere, no norm on the query and key heads:

1. ``r = x W_r`` (2560 -> the router's 64 outputs), **from the layer's
   input itself, before the input norm and before attention**; the 6
   largest are chosen, and their gates are ``softmax(r)`` over all 64
   renormalised over the 6 chosen (``moe_primary_router_apply_softmax``,
   ``norm_topk_prob``), which is the softmax over the six logits.
2. ``u = RMSNorm_in(x)``; ``q = u W_q`` as ``(T, 28, 128)``, ``k = u W_k``
   and ``v = u W_v`` as ``(T, 4, 128)``; on layer ``l`` with ``rope_layout[l]
   == 1`` ``q`` and ``k`` are rotated (rotate-half, ``rope_theta``) by their
   positions ``0 .. T - 1``, with ``rope_layout[l] == 0`` they are not;
   scores ``q k^T / sqrt(128)``, query head ``i`` reading key/value head ``i
   // 7``; query ``i`` reads key ``j`` iff ``0 <= i - j`` where
   ``sliding_window_layout[l] == 0`` and iff ``0 <= i - j <
   sliding_window_size`` where it is 1; softmax; ``h = x + Attn W_o``.
3. ``m = RMSNorm_post(h)``; every **held** expert is applied to every token,
   ``W_2 (relu(W_1 m) * W_3 m)``, and its result weighted by the token's
   gate for it, which is zero where the token did not choose it; the layer's
   output is ``h + y``.

After the last layer RMSNorm and the untied output head; the loss is the
mean over all tokens of the cross-entropy of position ``t``'s logits against
token ``t + 1`` (a sequence's last position has no target).

**The share.** One chip's share of a layer divided over
``chips_sharing_a_layer`` chips: ``moe_num_primary_experts`` experts held of
``published.moe_num_primary_experts`` the router scores (experts ``share *
held`` on), ``vocab_size`` rows of the embedding and the head; attention,
the router and the norms whole. A token's result is the sum over those of
its 6 experts that are held, the gates normalised over all 6; what the
absent experts would add is left out and the partial result goes on to the
next layer. Token ids, logits and loss are over the rows held. The layers
held are the first of the two layouts (``layers_held``).

Readings of the published description that the config does not settle (the
configuration's ``assumed`` lists them): the router reads the un-normed
input; no bias in any projection and no norm on the heads; the window
counts the query's own position. Departure from the configuration's stated
precision: everything here is float32 (the configuration's activations are
bfloat16), so that the comparison holds the program to the mathematics and
not to another bfloat16 rounding (as ``reference/sdar_moe.py``).

Memory and size: every layer walks the batch one sequence after another,
attention one head after another and ``Q_ROWS`` queries at a time (a
``lax.map`` over the blocks: one computation a layer, whatever the length)
over all the keys or, under a window, over the ``Q_ROWS + window - 1`` keys
such a block can read, the mask made from the positions (each block and
each head recomputed in the backward pass, so 16,384 x 16,384 scores never
exist), the routed part one
expert after another (a ``lax.scan`` over the held experts' stacked
weights, each expert recomputed in the backward pass), the head ``Q_ROWS``
positions at a time; each sequence's layer is recomputed in the backward
pass from its input (routing is per token and attention per sequence, so
the result is the same).

The weights are laid out as the nested dict ``grace_tpu.models.smallthinker``
reads.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

INIT_STD = 0.02
Q_ROWS = 1024             # queries scored together, positions of a head part


def layout(sizes):
    """What the share holds beside the file's own keys: the router's width
    and the first expert held."""
    return {"router": sizes["published"]["moe_num_primary_experts"],
            "first": sizes["share"] * sizes["moe_num_primary_experts"]}


def init(key, sizes, param_dtype=jnp.float32):
    """Seeded weights and the model's state (the counters the program fills,
    all zero). Truncated normal matrices of std 0.02 and unit norm weights,
    but for two things that stand in for the trained checkpoint this job
    continues from (the configuration's ``assumed.initialisation``): the
    embedding's rows have std 1 (``torch.nn.Embedding``'s default) and the
    two projections that write to the residual stream (``o_proj``, ``w2``)
    are scaled by ``1 / sqrt(2 * published layers)`` (GPT-2's and
    Megatron's scaled initialisation). The router reads the stream itself,
    so with them a position's experts follow its own token."""
    lay = layout(sizes)
    depth = sizes["published"].get("num_hidden_layers",
                                   sizes["num_hidden_layers"])
    out_scale = 1.0 / math.sqrt(2 * depth)
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    e, f = sizes["moe_num_primary_experts"], sizes["moe_ffn_hidden_size"]
    n = [0]

    def mat(*shape, std=INIT_STD):
        n[0] += 1
        return (jax.random.truncated_normal(
            jax.random.fold_in(key, n[0]), -2.0, 2.0, shape, jnp.float32)
            * std).astype(param_dtype)

    def norm(width):
        return {"scale": jnp.ones((width,), param_dtype)}

    def layer():
        attn = {"q_proj": mat(d, hq * hd), "k_proj": mat(d, hkv * hd),
                "v_proj": mat(d, hkv * hd),
                "o_proj": mat(hq * hd, d, std=INIT_STD * out_scale)}
        ffn = {"router": mat(d, lay["router"]), "w1": mat(e, d, f),
               "w3": mat(e, d, f),
               "w2": mat(e, f, d, std=INIT_STD * out_scale)}
        return {"attn_norm": norm(d), "attn": attn, "ffn_norm": norm(d),
                "ffn": ffn}

    params = {"embed": {"table": mat(sizes["vocab_size"], d, std=1.0)},
              "layers": [layer() for _ in range(sizes["num_hidden_layers"])],
              "final_norm": norm(d),
              "head": mat(d, sizes["vocab_size"])}

    def zero():
        return jnp.zeros((), jnp.float32)

    state = {"layers": [{"held": zero(), "dropped": zero(),
                         "computed": zero(), "combined": zero()}
                        for _ in range(sizes["num_hidden_layers"])]}
    return params, state


def make_batch(key, n, sizes):
    """``n`` sequences of ``seq_length`` token ids, uniform over the rows of
    the vocabulary held."""
    return jax.random.randint(key, (n, sizes["seq_length"]), 0,
                              sizes["vocab_size"], jnp.int32)


# ---------------------------------------------------------------------------
# one sequence: x is (T, d), float32
# ---------------------------------------------------------------------------

def _mm(x, w):
    return x @ w.astype(x.dtype)


def _rms(p, x, eps):
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * p["scale"].astype(x.dtype)


def _rotate(x, theta):
    """``x``: ``(T, heads, head_dim)``, rotate-half by the positions ``0 ..
    T - 1``."""
    t, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [a * jnp.cos(angle) - b * jnp.sin(angle),
         b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)


def may_read(q_ids, kv_ids, window):
    """Which keys a query reads: those at or before it, and under a
    ``window`` (not ``None``) the ``window`` of them that end at it."""
    ok = kv_ids <= q_ids
    return ok if window is None else ok & (q_ids - kv_ids < window)


def _attention(p, u, sizes, rotated, window):
    t = u.shape[0]
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["head_dim"]
    q = _mm(u, p["q_proj"]).reshape(t, hq, hd)
    k = _mm(u, p["k_proj"]).reshape(t, hkv, hd)
    v = _mm(u, p["v_proj"]).reshape(t, hkv, hd)
    if rotated:
        q, k = _rotate(q, sizes["rope_theta"]), _rotate(k, sizes["rope_theta"])
    # query head i reads key/value head i // (hq // hkv)
    k, v = (jnp.repeat(a, hq // hkv, axis=1) for a in (k, v))
    rows = min(t, Q_ROWS)
    # the keys a block of queries can read: all of them, or under a window
    # the block's own positions and the window before its first
    span = t if window is None else min(t, rows + window - 1)

    def head(qkv):
        qh, kh, vh = qkv                                    # (T, head_dim)

        def some_queries(start):
            first = jnp.clip(start - (span - rows), 0, t - span)
            qb = lax.dynamic_slice_in_dim(qh, start, rows)
            kb = lax.dynamic_slice_in_dim(kh, first, span)
            vb = lax.dynamic_slice_in_dim(vh, first, span)
            ok = may_read(start + jnp.arange(rows)[:, None],
                          first + jnp.arange(span)[None, :], window)
            s = (qb @ kb.T) / math.sqrt(hd)
            return jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1) @ vb

        out = lax.map(jax.checkpoint(some_queries), jnp.arange(0, t, rows))
        return out.reshape(t, hd)

    out = lax.map(jax.checkpoint(head),
                  tuple(a.transpose(1, 0, 2) for a in (q, k, v)))
    return _mm(out.transpose(1, 0, 2).reshape(t, hq * hd), p["o_proj"])


def _gated(m, w):
    """``W_2 (relu(W_1 m) * W_3 m)``."""
    return _mm(jax.nn.relu(_mm(m, w["w1"])) * _mm(m, w["w3"]), w["w2"])


def gates(p, x, sizes):
    """Every token's weight for each of the router's experts, from the
    layer's input ``x``: zero but for the
    ``moe_num_active_primary_experts`` it chose, the chosen ones'
    probabilities over their sum."""
    s = jax.nn.softmax(_mm(x, p["router"]), axis=-1)
    chosen = jnp.zeros(s.shape, bool)
    for _ in range(sizes["moe_num_active_primary_experts"]):
        best = jnp.argmax(jnp.where(chosen, -jnp.inf, s), axis=-1)
        chosen = chosen | jax.nn.one_hot(best, s.shape[-1], dtype=bool)
    picked = jnp.where(chosen, s, 0.0)
    return picked / jnp.sum(picked, axis=-1, keepdims=True)


def _routed(p, m, token_gates, sizes, first):
    """The held experts' part of the expert layer's result: one expert
    after another, each applied to every token."""
    held = token_gates[:, first:first + sizes["moe_num_primary_experts"]].T

    def expert(y, weights_and_gate):
        w, gate = weights_and_gate
        return y + gate[:, None] * _gated(m, w), None

    y, _ = lax.scan(jax.checkpoint(expert), jnp.zeros_like(m),
                    ({k: p[k] for k in ("w1", "w3", "w2")}, held))
    return y


def layer(p, x, sizes, lay, index):
    """Published layer ``index`` on one sequence ``x`` ``(T, d)``."""
    eps = sizes["rms_norm_eps"]
    # from the input as it is: before the norm, before attention
    token_gates = gates(p["ffn"], x, sizes)
    windowed = sizes["sliding_window_layout"][index]
    h = x + _attention(
        p["attn"], _rms(p["attn_norm"], x, eps), sizes,
        rotated=bool(sizes["rope_layout"][index]),
        window=sizes["sliding_window_size"] if windowed else None)
    return h + _routed(p["ffn"], _rms(p["ffn_norm"], h, eps), token_gates,
                       sizes, lay["first"])


def layers_held(sizes):
    """The published layers this share holds, in order."""
    return sizes.get("layers_held", range(sizes["num_hidden_layers"]))


def loss(params, state, ids, sizes):
    """The next-token loss of the module's docstring: ``(loss, state)``;
    the counters in ``state`` are the program's own and pass through
    untouched."""
    lay = layout(sizes)
    n, t = ids.shape
    rows = min(t, Q_ROWS)

    def part_loss(x_targets_w):
        x, targets, w = x_targets_w
        u = _rms(params["final_norm"], x, sizes["rms_norm_eps"])
        logp = jax.nn.log_softmax(_mm(u, params["head"]), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[:, None], axis=1)[:, 0]
        return jnp.sum(w * nll)

    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["table"], ids, axis=0).astype(
            jnp.float32)
        for p, index in zip(params["layers"], layers_held(sizes)):
            x = lax.map(jax.checkpoint(
                lambda xs, p=p, index=index: layer(p, xs, sizes, lay, index)),
                x)
        # position t is scored against token t + 1; a sequence's last
        # position has no target and weighs nothing
        targets = jnp.concatenate([ids[:, 1:], ids[:, :1]], axis=1)
        weights = jnp.broadcast_to(
            (jnp.arange(t) < t - 1).astype(jnp.float32), (n, t))
        parts = tuple(a.reshape(n * t // rows, rows, *a.shape[2:])
                      for a in (x, targets, weights))
        total = jnp.sum(lax.map(jax.checkpoint(part_loss), parts))
    return total / (n * (t - 1)), state
