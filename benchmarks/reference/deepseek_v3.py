"""Plain reference of the decoder the ``kanana-2-30b-a3b-ep16``
configuration trains, in ``jax.numpy`` and float32 at ``highest`` matmul
precision: weights from a seed, forward pass, next-token loss (its
gradients are ``jax.grad``'s). Imports nothing of ``grace_tpu``; no kernel,
no grouped product, no sort.

The model is ``kakaocorp/kanana-2-30b-a3b-instruct-2601`` (``model_type``
``deepseek_v3``, no query latent); the equations as ``transformers``'
``modeling_deepseek_v3`` computes them. With ``u = RMSNorm(x)`` (learned
weight, ``eps`` ``rms_norm_eps``), no bias anywhere:

* layer: ``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; after
  the last layer RMSNorm and the untied output head.
* MLA: ``q = W_q u`` as ``(T, H, qk_nope_head_dim + qk_rope_head_dim)``;
  ``(c, k_pe) = split(W_kva u)``, the latent ``kv_lora_rank`` wide and one
  rotary key for all heads; ``(k_nope, v) = split(W_kvb RMSNorm(c))`` as
  ``(T, H, qk_nope_head_dim + v_head_dim)``. Rotary positions on ``q_pe``
  and ``k_pe`` only, as HF applies them under ``rope_interleave``: the
  entries are de-interleaved (evens first, then odds) and the halves
  rotated. ``k = concat(k_nope, k_pe for every head)``; a full masked
  ``softmax(q k^T / sqrt(192)) v`` one head at a time; ``W_o``.
* dense feed-forward (layers before ``first_k_dense_replace``):
  ``W_2 (silu(W_1 h) * W_3 h)``.
* expert feed-forward: ``s = sigmoid(W_r h)``; the ``num_experts_per_tok``
  largest of ``s + b`` over all the router's outputs (``n_group`` 1: the
  group step selects everything); weights ``s_i / (sum s_i + 1e-20) *
  routed_scaling_factor``; every **held** expert is applied to every token
  and its result weighted by the token's gate for it, which is zero where
  the token did not choose it; plus the shared expert, one gated
  feed-forward of width ``moe_intermediate_size * n_shared_experts``.

**The share.** One chip's share of a layer divided over
``chips_sharing_a_layer`` chips: ``n_routed_experts`` experts held of
``published.n_routed_experts`` the router scores (experts ``share * held``
on), ``vocab_size`` rows of the embedding and the head; attention, the
shared expert, the router and the norms whole. A token's routed result is
the sum over those of its chosen experts that are held, weighted as above
(normalised over all chosen); what the absent experts would add is left
out and the partial result goes on to the next layer. Token ids, logits
and loss are over the rows held.

Departures from the published model: the correction bias ``b`` is held at
zero and never updated (no update rule is published). From the
configuration's stated precision: everything here is float32 (the
configuration's activations are bfloat16), so that the comparison holds
the program to the mathematics and not to another bfloat16 rounding.

Memory and size: every layer walks the batch one sequence after another,
attention one head after another and the routed part one expert after
another (a ``lax.scan`` over the held experts' stacked weights), each
sequence's layer recomputed in the backward pass from its input (routing
is per token and attention per sequence, so the result is the same). Two
products of one input are made as one product with the weights side by
side (``W_1 | W_3``, ``W_q | W_kva``). Both for the program's size, not
the mathematics: written out expert by expert, the float32 ``highest``
products made a reference step of 1.2 GB that took four minutes to compile
and fitted no compile cache; a scan over the expert layers' stacked
weights as well does not fit the chip beside the reference's optimizer
state (PERF.md section 6, PR 32).

The weights are laid out as the nested dict ``grace_tpu.models.
deepseek_v3`` reads.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

INIT_STD = 0.02
ROUTE_EPS = 1e-20


def layout(sizes):
    """What the share holds beside the file's own keys: which layers have
    experts, the router's width and the first expert held."""
    return {"moe": [i >= sizes["first_k_dense_replace"]
                    for i in range(sizes["num_hidden_layers"])],
            "router": sizes["published"]["n_routed_experts"],
            "first": sizes["share"] * sizes["n_routed_experts"]}


def init(key, sizes, param_dtype=jnp.float32):
    """Seeded weights and the model's state: truncated normal (std 0.02)
    matrices, unit norm weights, correction bias zero."""
    lay = layout(sizes)
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    n = [0]

    def mat(*shape):
        n[0] += 1
        return (jax.random.truncated_normal(
            jax.random.fold_in(key, n[0]), -2.0, 2.0, shape, jnp.float32)
            * INIT_STD).astype(param_dtype)

    def norm(width):
        return {"scale": jnp.ones((width,), param_dtype)}

    def gated(width, *stack):
        return {"w1": mat(*stack, d, width), "w3": mat(*stack, d, width),
                "w2": mat(*stack, width, d)}

    def layer(moe):
        attn = {"q_proj": mat(d, h * (nope + rope)),
                "kv_a_proj": mat(d, sizes["kv_lora_rank"] + rope),
                "kv_a_norm": norm(sizes["kv_lora_rank"]),
                "kv_b_proj": mat(sizes["kv_lora_rank"],
                                 h * (nope + sizes["v_head_dim"])),
                "o_proj": mat(h * sizes["v_head_dim"], d)}
        if moe:
            f = sizes["moe_intermediate_size"]
            ffn = {"router": mat(d, lay["router"]),
                   **gated(f, sizes["n_routed_experts"]),
                   "shared": gated(f * sizes["n_shared_experts"])}
        else:
            ffn = gated(sizes["intermediate_size"])
        return {"attn_norm": norm(d), "attn": attn, "ffn_norm": norm(d),
                "ffn": ffn}

    params = {"embed": {"table": mat(sizes["vocab_size"], d)},
              "layers": [layer(moe) for moe in lay["moe"]],
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
# one sequence: x is (T, d), float32
# ---------------------------------------------------------------------------

def _mm(x, w):
    return x @ w.astype(x.dtype)


def _rms(p, x, eps):
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * p["scale"].astype(x.dtype)


def _rotate(x, theta):
    """HF's ``apply_rotary_pos_emb_interleave`` on ``x`` ``(T, heads, d)``:
    de-interleave (entries 0, 2, 4, … then 1, 3, 5, …), then rotate the
    halves against each other."""
    t, heads, d = x.shape
    x = x.reshape(t, heads, d // 2, 2).transpose(0, 1, 3, 2).reshape(
        t, heads, d)
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [a * jnp.cos(angle) - b * jnp.sin(angle),
         b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)


def _mla(p, u, sizes):
    t = u.shape[0]
    h, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rope, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                      sizes["v_head_dim"])
    theta = sizes["rope_theta"]
    # W_q u and W_kva u as one product
    q_kva = _mm(u, jnp.concatenate([p["q_proj"], p["kv_a_proj"]], axis=1))
    q, c, k_pe = jnp.split(
        q_kva, [h * (nope + rope), h * (nope + rope) + rank], axis=1)
    q = q.reshape(t, h, nope + rope)
    kv = _mm(_rms(p["kv_a_norm"], c, sizes["rms_norm_eps"]),
             p["kv_b_proj"]).reshape(t, h, nope + dv)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], theta)],
                        axis=-1)
    k_pe = _rotate(k_pe[:, None, :], theta)                 # one head
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (t, h, rope))], axis=-1)
    v = kv[..., nope:]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def head(qkv):
        qh, kh, vh = qkv                        # (T, 192), (T, 192), (T, 128)
        s = (qh @ kh.T) / math.sqrt(nope + rope)
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ vh

    out = lax.map(jax.checkpoint(head),
                  tuple(a.transpose(1, 0, 2) for a in (q, k, v)))
    return _mm(out.transpose(1, 0, 2).reshape(t, h * dv), p["o_proj"])


def _gated(u, w):
    """``W_2 (silu(W_1 u) * W_3 u)``, ``W_1 u`` and ``W_3 u`` as one
    product."""
    width = w["w1"].shape[-1]
    h = _mm(u, jnp.concatenate([w["w1"], w["w3"]], axis=-1))
    return _mm(jax.nn.silu(h[:, :width]) * h[:, width:], w["w2"])


def _gates(p, bias, u, sizes):
    """Every token's weight for each of the router's experts: zero but for
    the ``num_experts_per_tok`` it chose."""
    s = jax.nn.sigmoid(_mm(u, p["router"]))
    biased = s + bias
    chosen = jnp.zeros(s.shape, bool)
    for _ in range(sizes["num_experts_per_tok"]):     # the largest, k times
        best = jnp.argmax(jnp.where(chosen, -jnp.inf, biased), axis=-1)
        chosen = chosen | jax.nn.one_hot(best, s.shape[-1], dtype=bool)
    picked = jnp.where(chosen, s, 0.0)
    return (picked / (jnp.sum(picked, axis=-1, keepdims=True) + ROUTE_EPS)
            * sizes["routed_scaling_factor"])


def _routed(p, bias, u, sizes, first):
    """The held experts' part of the expert layer's result: one expert
    after another, each applied to every token."""
    gates = _gates(p, bias, u, sizes)
    held = gates[:, first:first + sizes["n_routed_experts"]].T

    def expert(y, weights_and_gate):
        w, gate = weights_and_gate
        return y + gate[:, None] * _gated(u, w), None

    y, _ = lax.scan(expert, jnp.zeros_like(u),
                    ({k: p[k] for k in ("w1", "w3", "w2")}, held))
    return y


def _layer(p, state, x, moe, sizes, lay):
    eps = sizes["rms_norm_eps"]
    x = x + _mla(p["attn"], _rms(p["attn_norm"], x, eps), sizes)
    u = _rms(p["ffn_norm"], x, eps)
    f = p["ffn"]
    if not moe:
        return x + _gated(u, f)
    return (x + _routed(f, state["expert_bias"], u, sizes, lay["first"])
            + _gated(u, f["shared"]))


def loss(params, state, batch, sizes):
    """Mean over all tokens of the cross-entropy of position ``t``'s logits
    against token ``t + 1`` (a sequence's last position has no target):
    ``(loss, state)``. The counters in ``state`` are the program's own and
    pass through untouched."""
    lay = layout(sizes)
    n, t = batch.shape

    def over_sequences(moe, p, s, x):
        """One layer on every sequence of ``x`` ``(n, T, d)``, one after
        another, each recomputed from its input in the backward pass."""
        return lax.map(jax.checkpoint(
            lambda xs: _layer(p, s, xs, moe, sizes, lay)), x)

    def sequence_loss(x_ids):
        x, ids = x_ids
        u = _rms(params["final_norm"], x[:-1], sizes["rms_norm_eps"])
        logp = jax.nn.log_softmax(_mm(u, params["head"]), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, ids[1:, None], axis=1))

    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["table"], batch, axis=0).astype(
            jnp.float32)
        for p, s, moe in zip(params["layers"], state["layers"], lay["moe"]):
            x = over_sequences(moe, p, s, x)
        total = jnp.sum(lax.map(jax.checkpoint(sequence_loss), (x, batch)))
    return total / (n * (t - 1)), state
