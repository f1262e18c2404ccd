"""Plain reference of the decoder the ``sdar-30b-a3b-ep8`` configuration
trains, in ``jax.numpy`` and float32 at ``highest`` matmul precision:
weights from a seed, the step's noise, forward pass, block-diffusion loss
(its gradients are ``jax.grad``'s). Imports nothing of ``grace_tpu``; no
kernel, no grouped product, no sort.

The model is ``JetLM/SDAR-30B-A3B-Chat`` (``model_type`` ``sdar_moe``: a
Qwen3-MoE decoder trained as a block diffusion model). With ``u =
RMSNorm(x)`` (learned weight, ``eps`` ``rms_norm_eps``), no bias anywhere:

* layer: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; after
  the last layer RMSNorm and the untied output head.
* ``Attn``: ``q = W_q u`` as ``(T, 32, 128)``, ``k = W_k u`` and ``v = W_v
  u`` as ``(T, 4, 128)``; ``q`` and ``k`` RMS-normalised over each head's
  128 entries with a learnt weight, then rotated (rotate-half,
  ``rope_theta``) by the token's **position id**; scores ``q k^T /
  sqrt(128)`` under the mask below, softmax, query head ``i`` reading
  key/value head ``i // 8``; ``W_o``.
* ``MoE``: ``p = softmax(W_r u)`` over all the router's 128 outputs; the 8
  largest; gates ``p_e / sum of the 8``; each expert ``W_2 (silu(W_1 u) *
  W_3 u)``; no shared expert. Every **held** expert is applied to every
  token and its result weighted by the token's gate for it, which is zero
  where the token did not choose it.
* **block-diffusion training** of clean tokens ``x_0 .. x_{L-1}``, block
  length ``B``, ``b(i) = i // B``. A sequence and block draws ``t_b ~
  U(eps, 1)``; token ``i`` is masked with probability ``t_b(i)`` (``m_i``);
  the noised copy holds the mask id where ``m_i`` and ``x_i`` elsewhere.
  The input is ``[noised ; clean]``, ``2 L`` positions, position ids ``(0 ..
  L - 1, 0 .. L - 1)``. Query ``a`` may read key ``c`` iff: both noised and
  ``b(a) = b(c)``; or ``a`` noised, ``c`` clean and ``b(c) < b(a)``; or
  both clean and ``b(c) <= b(a)``; never a clean query a noised key. Loss:
  ``1 / (n L) * sum_i m_i / t_b(i) * CE(logits of noised position i,
  x_i)``: the token at its own position, no shift; only the noised copy's
  ``L`` positions go through the final norm and the head.

**The noise** of a step comes from ``fold_in(the sequence's key, step)``,
``step`` counted in the model state; :func:`draw_noise` makes the same
calls of ``jax.random`` as the program's ``models.sdar.draw_noise`` (a
test holds them to the same bits), so both see the same masks at each of
the compared steps.

**The share.** One chip's share of a layer divided over
``chips_sharing_a_layer`` chips: ``num_experts`` experts held of
``published.num_experts`` the router scores (experts ``share * held`` on),
``vocab_size`` rows of the embedding and the head; attention, the router
and the norms whole. A token's result is the sum over those of its 8
experts that are held, the gates normalised over all 8; what the absent
experts would add is left out and the partial result goes on to the next
layer. Token ids, logits and loss are over the rows held; the mask id is
the last of them and tokens are drawn from the rows before it.

Departure from the configuration's stated precision: everything here is
float32 (the configuration's activations are bfloat16), so that the
comparison holds the program to the mathematics and not to another bfloat16
rounding (as ``reference/deepseek_v3.py``).

Memory and size: every layer walks the batch one sequence after another,
attention one head after another (recomputed in the backward pass) and
2,048 queries at a time, the routed
part one expert after another (a ``lax.scan`` over the held experts'
stacked weights, each expert recomputed in the backward pass), each
sequence's layer recomputed in the backward pass from its input (routing is
per token and attention per sequence, so the result is the same).

The weights are laid out as the nested dict ``grace_tpu.models.sdar`` reads.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

INIT_STD = 0.02
QK_NORM_INIT = 2.0        # the query and key heads' norm weights
Q_ROWS = 2048             # queries scored together


def layout(sizes):
    """What the share holds beside the file's own keys: the router's width
    and the first expert held."""
    return {"router": sizes["published"]["num_experts"],
            "first": sizes["share"] * sizes["num_experts"]}


def init(key, sizes, param_dtype=jnp.float32):
    """Seeded weights and the model's state; the step counter and the
    counters the program fills, all zero. Truncated normal matrices of std
    0.02 and unit norm weights, but for four things that together keep a
    position's stream its own (the configuration's
    ``assumed.initialisation`` says why that matters here): the embedding's
    rows have std 1 (``torch.nn.Embedding``'s default), the two projections
    that write to the residual stream (``o_proj``, ``w2``) are scaled by
    ``1 / sqrt(2 * published layers)`` (GPT-2's and Megatron's scaled
    initialisation), the mask token's row, a token new to the checkpoint,
    is the mean of the rows before it (what ``transformers`` gives a row it
    adds to a trained table), and the query and key heads' norm weights are
    ``QK_NORM_INIT``, so that attention reads a few keys and not the
    average of thousands, as a trained checkpoint's does: the masked
    positions, which share one embedding, then carry different vectors."""
    lay = layout(sizes)
    # a configuration that states no published depth holds all its layers
    depth = sizes["published"].get("num_hidden_layers",
                                   sizes["num_hidden_layers"])
    out_scale = 1.0 / math.sqrt(2 * depth)
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    e, f = sizes["num_experts"], sizes["moe_intermediate_size"]
    n = [0]

    def mat(*shape, std=INIT_STD):
        n[0] += 1
        return (jax.random.truncated_normal(
            jax.random.fold_in(key, n[0]), -2.0, 2.0, shape, jnp.float32)
            * std).astype(param_dtype)

    def norm(width, weight=1.0):
        return {"scale": jnp.full((width,), weight, param_dtype)}

    def layer():
        attn = {"q_proj": mat(d, hq * hd), "k_proj": mat(d, hkv * hd),
                "v_proj": mat(d, hkv * hd),
                "o_proj": mat(hq * hd, d, std=INIT_STD * out_scale),
                "q_norm": norm(hd, QK_NORM_INIT),
                "k_norm": norm(hd, QK_NORM_INIT)}
        ffn = {"router": mat(d, lay["router"]), "w1": mat(e, d, f),
               "w3": mat(e, d, f),
               "w2": mat(e, f, d, std=INIT_STD * out_scale)}
        return {"attn_norm": norm(d), "attn": attn, "ffn_norm": norm(d),
                "ffn": ffn}

    table = mat(sizes["vocab_size"], d, std=1.0).astype(jnp.float32)
    table = table.at[-1].set(jnp.mean(table[:-1], axis=0)).astype(param_dtype)
    params = {"embed": {"table": table},
              "layers": [layer() for _ in range(sizes["num_hidden_layers"])],
              "final_norm": norm(d),
              "head": mat(d, sizes["vocab_size"])}

    def zero():
        return jnp.zeros((), jnp.float32)

    state = {"step": zero(), "masked": zero(),
             "layers": [{"held": zero(), "dropped": zero(),
                         "computed": zero(), "combined": zero()}
                        for _ in range(sizes["num_hidden_layers"])]}
    return params, state


def make_batch(key, n, sizes):
    """``n`` sequences of ``seq_length`` clean token ids, uniform over the
    rows of the vocabulary held before the mask id (the last), and a key's
    data a sequence for its noise."""
    k_ids, k_noise = jax.random.split(key)
    return {"ids": jax.random.randint(k_ids, (n, sizes["seq_length"]), 0,
                                      sizes["vocab_size"] - 1, jnp.int32),
            "key": jax.random.key_data(jax.random.split(k_noise, n))}


def draw_noise(key, step, ids, sizes):
    """One sequence's draws at ``step``: the noised copy of ``ids`` ``(L,)``
    and each position's weight in the loss (``1 / t`` of its block where it
    is masked, zero elsewhere)."""
    (length,) = ids.shape
    block = sizes["block_length"]
    k = jax.random.fold_in(jax.random.wrap_key_data(key),
                           jnp.asarray(step, jnp.int32))
    k_level, k_mask = jax.random.split(k)
    levels = jax.random.uniform(k_level, (length // block,), jnp.float32,
                                sizes["noise_eps"], 1.0)
    t = jnp.repeat(levels, block)
    masked = jax.random.uniform(k_mask, (length,), jnp.float32) < t
    mask_id = sizes["vocab_size"] - 1
    return (jnp.where(masked, jnp.asarray(mask_id, ids.dtype), ids),
            jnp.where(masked, 1.0 / t, 0.0))


def allowed(length, block):
    """The ``(2 L, 2 L)`` table of which query may read which key."""
    position = jnp.arange(2 * length)
    noised = position < length
    b = (position % length) // block
    a_noised, c_noised = noised[:, None], noised[None, :]
    b_a, b_c = b[:, None], b[None, :]
    return ((a_noised & c_noised & (b_a == b_c))
            | (a_noised & ~c_noised & (b_c < b_a))
            | (~a_noised & ~c_noised & (b_c <= b_a)))


# ---------------------------------------------------------------------------
# one sequence: x is (T, d), float32, T = 2 L
# ---------------------------------------------------------------------------

def _mm(x, w):
    return x @ w.astype(x.dtype)


def _rms(p, x, eps):
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * p["scale"].astype(x.dtype)


def _rotate(x, positions, theta):
    """``x``: ``(T, heads, head_dim)``, rotate-half by ``positions``
    ``(T,)``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None, None] * inv
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [a * jnp.cos(angle) - b * jnp.sin(angle),
         b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)


def _attention(p, u, positions, may_read, sizes):
    t = u.shape[0]
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    theta = sizes["rope_theta"]
    q = _mm(u, p["q_proj"]).reshape(t, hq, hd)
    k = _mm(u, p["k_proj"]).reshape(t, hkv, hd)
    v = _mm(u, p["v_proj"]).reshape(t, hkv, hd)
    q = _rotate(_rms(p["q_norm"], q, eps), positions, theta)
    k = _rotate(_rms(p["k_norm"], k, eps), positions, theta)
    # query head i reads key/value head i // (hq // hkv)
    k, v = (jnp.repeat(a, hq // hkv, axis=1) for a in (k, v))
    rows = min(t, Q_ROWS)

    def head(qkv):
        qh, kh, vh = qkv                                    # (T, head_dim)

        def some_queries(q_and_mask):
            qb, mb = q_and_mask                             # (rows, ...)
            s = (qb @ kh.T) / math.sqrt(hd)
            return jax.nn.softmax(jnp.where(mb, s, -jnp.inf), axis=-1) @ vh

        out = lax.map(some_queries, (qh.reshape(t // rows, rows, hd),
                                     may_read.reshape(t // rows, rows, t)))
        return out.reshape(t, hd)

    out = lax.map(jax.checkpoint(head),
                  tuple(a.transpose(1, 0, 2) for a in (q, k, v)))
    return _mm(out.transpose(1, 0, 2).reshape(t, hq * hd), p["o_proj"])


def _gated(u, w):
    """``W_2 (silu(W_1 u) * W_3 u)``."""
    return _mm(jax.nn.silu(_mm(u, w["w1"])) * _mm(u, w["w3"]), w["w2"])


def _gates(p, u, sizes):
    """Every token's weight for each of the router's experts: zero but for
    the ``num_experts_per_tok`` it chose, the chosen ones' probabilities
    over their sum."""
    s = jax.nn.softmax(_mm(u, p["router"]), axis=-1)
    chosen = jnp.zeros(s.shape, bool)
    for _ in range(sizes["num_experts_per_tok"]):     # the largest, k times
        best = jnp.argmax(jnp.where(chosen, -jnp.inf, s), axis=-1)
        chosen = chosen | jax.nn.one_hot(best, s.shape[-1], dtype=bool)
    picked = jnp.where(chosen, s, 0.0)
    return picked / jnp.sum(picked, axis=-1, keepdims=True)


def _routed(p, u, sizes, first):
    """The held experts' part of the expert layer's result: one expert
    after another, each applied to every token."""
    gates = _gates(p, u, sizes)
    held = gates[:, first:first + sizes["num_experts"]].T

    def expert(y, weights_and_gate):
        w, gate = weights_and_gate
        return y + gate[:, None] * _gated(u, w), None

    y, _ = lax.scan(jax.checkpoint(expert), jnp.zeros_like(u),
                    ({k: p[k] for k in ("w1", "w3", "w2")}, held))
    return y


def _layer(p, x, positions, may_read, sizes, lay):
    eps = sizes["rms_norm_eps"]
    x = x + _attention(p["attn"], _rms(p["attn_norm"], x, eps), positions,
                       may_read, sizes)
    return x + _routed(p["ffn"], _rms(p["ffn_norm"], x, eps), sizes,
                       lay["first"])


def loss(params, state, batch, sizes):
    """The block-diffusion loss of the module's docstring at the step the
    state counts: ``(loss, state)`` with the step counted on. The other
    counters in ``state`` are the program's own and pass through
    untouched."""
    lay = layout(sizes)
    ids = batch["ids"]
    n, length = ids.shape
    noised, weights = jax.vmap(
        lambda k, x: draw_noise(k, state["step"], x, sizes))(batch["key"],
                                                             ids)
    both = jnp.concatenate([noised, ids], axis=1)
    positions = jnp.concatenate([jnp.arange(length), jnp.arange(length)])
    may_read = allowed(length, sizes["block_length"])

    def sequence_loss(x_ids_w):
        x, targets, w = x_ids_w
        u = _rms(params["final_norm"], x[:length], sizes["rms_norm_eps"])
        logp = jax.nn.log_softmax(_mm(u, params["head"]), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[:, None], axis=1)[:, 0]
        return jnp.sum(w * nll)

    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["table"], both, axis=0).astype(
            jnp.float32)
        for p in params["layers"]:
            x = lax.map(jax.checkpoint(
                lambda xs, p=p: _layer(p, xs, positions, may_read, sizes,
                                       lay)), x)
        total = jnp.sum(lax.map(jax.checkpoint(sequence_loss),
                                (x, ids, weights)))
    return total / (n * length), {**state, "step": state["step"] + 1.0}
