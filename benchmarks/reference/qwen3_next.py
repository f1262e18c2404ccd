"""Plain reference of the decoder the ``qwen3-next-80b-a3b-ep32``
configuration trains, in ``jax.numpy`` and float32 at ``highest`` matmul
precision: weights from a seed, forward pass, next-token loss (its gradients
are ``jax.grad``'s). Imports nothing of ``grace_tpu``; no kernel, no chunked
rule, no grouped product, no sort.

The model is ``Qwen/Qwen3-Next-80B-A3B-Instruct`` as its ``config.json``
states it (``model_type: qwen3_next``) and the family's public
implementation (``transformers``' ``modeling_qwen3_next.py``) computes it.
``norm(x) = x / rms(x) * (1 + w)``, ``w`` starting at zero, ``eps``
``rms_norm_eps``; no bias anywhere. Layer ``i`` is a full-attention layer
where ``(i + 1) % full_attention_interval == 0`` and a gated delta layer
otherwise; each is ``x <- x + op(norm_1(x))``, then ``x <- x +
moe(norm_2(x))``.

*Gated delta layer* (``u = norm_1(x)``; 16 key heads and 32 value heads of
128, key head ``h`` serving value heads ``2h`` and ``2h + 1``):

1. ``[q, k, v, z] = u W_qkvz`` (2048 + 2048 + 4096 + 4096 columns), ``[b,
   a] = u W_ba`` (32 + 32).
2. ``[q, k, v] <- silu(conv4([q, k, v]))``: causal, depthwise over the
   8,192 channels, ``y_t = sum_j c_j x_{t-3+j}``, zeros before the
   sequence's start, no bias.
3. A head: ``q <- q / sqrt(|q|^2 + 1e-6) / sqrt(128)``, ``k <- k /
   sqrt(|k|^2 + 1e-6)``.
4. ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``, one
   number a value head and token.
5. **Token by token**, with ``S_0 = 0`` (128 x 128 a value head): ``S <-
   exp(g_t) S``; ``delta_t = beta_t (v_t - S^T k_t)``; ``S <- S + k_t
   delta_t^T``; ``o_t = S^T q_t``.
6. ``y = w_n * (o / rms(o)) * silu(z)`` a head of 128 (``w_n`` starting at
   one); the operator's output is ``y W_out``.

*Full-attention layer* (16 query heads over 2 key/value heads of 256):
``[q, gate] = u W_q`` a head (256 each), ``k = u W_k``, ``v = u W_v``;
``q`` and ``k`` through a zero-centred RMSNorm over the head; the first 64
numbers of a head rotated by the positions ``0 .. T - 1`` (``rope_theta``,
half-split pairs ``(j, j + 32)``), the other 192 not; causal softmax of ``q
k^T / sqrt(256)``, query head ``i`` reading key/value head ``i // 8``;
``out <- out * sigmoid(gate)``; ``out W_o``.

*Expert layer* (``m = norm_2(x)``): ``s = softmax(m W_r)`` over 512; the 10
largest; gates ``s_e / sum of the chosen``; every **held** expert is applied
to every token, ``W_2 (silu(W_1 m) * W_3 m)``, and its result weighted by
the token's gate for it, zero where the token did not choose it; plus
``sigmoid(m w_g) * shared(m)``, the shared expert the same feed-forward at
its own width.

After the last layer the (zero-centred) final norm and the untied head; the
loss is the mean over all tokens of the cross-entropy of position ``t``'s
logits against token ``t + 1``. The config has no key for a
multi-token-prediction block, and none is built.

**The share.** One chip's share of a layer divided over
``chips_sharing_a_layer`` chips: ``num_experts`` experts held of
``published.num_experts`` the router scores (experts ``share * held`` on),
``vocab_size`` rows of the embedding and the head; both operators, the
router, the shared expert and the norms whole. A token's result is the sum
over those of its 10 experts that are held, the gates normalised over all
10, plus the shared expert's; what the absent experts would add is left out
and the partial result goes on to the next layer. Token ids, logits and
loss are over the rows held.

Memory and size: a layer walks the batch one sequence after another, each
sequence's layer recomputed in the backward pass from its input. Whatever
a position computes from its own row (norms, projections, the convolution
with the three rows before it, gates, the gated norm, the whole expert
layer, the head) is made ``Q_ROWS`` positions at a time (``in_rows``: a
``lax.map`` whose blocks are recomputed in the backward pass). The
recurrence is a ``lax.scan`` over blocks of ``RULE_ROWS`` positions whose
body, a ``lax.scan`` over the block's positions, is recomputed in the
backward pass: 256 states a sequence are kept and not 16,384 (68 GB); a
gated delta layer makes ``HEAD_GROUP`` key heads with their value heads at
a time and a full layer as many query heads, each group recomputed.
Attention goes one head after another and ``Q_ROWS`` queries at a time over
all the keys (``lax.map``s, each block and head recomputed, so 16,384 x
16,384 scores never exist); the routed part one expert after another (a
``lax.scan`` over the held experts' stacked weights). Every walk is a
``lax.scan`` or a ``lax.map``: one computation whatever the length.

The weights are laid out as the nested dict ``grace_tpu.models.qwen3_next``
reads.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

INIT_STD = 0.02
Q_ROWS = 1024             # queries scored together, positions of a head part
RULE_ROWS = 64            # positions of the recurrence recomputed together
HEAD_GROUP = 4            # heads of an operator made together (key heads of
                          # a gated delta layer, query heads of a full one)


def layout(sizes):
    """What the share holds beside the file's own keys: the router's width
    and the first expert held."""
    return {"router": sizes["published"]["num_experts"],
            "first": sizes["share"] * sizes["num_experts"]}


def layers_held(sizes):
    """The published layers this share holds, in order."""
    return sizes.get("layers_held", range(sizes["num_hidden_layers"]))


def is_full(sizes, index):
    return (index + 1) % sizes["full_attention_interval"] == 0


def init(key, sizes, param_dtype=jnp.float32):
    """Seeded weights and the model's state (the counters the program fills,
    all zero). Truncated normal matrices of std 0.02 but for what stands in
    for the trained checkpoint this job continues from (the configuration's
    ``assumed.initialisation``): the embedding's rows have std 1, the
    projections that write to the residual stream (``out_proj``, ``o_proj``,
    every ``w2``) are scaled by ``1 / sqrt(2 * published layers)``, the
    convolution is uniform in +-1/2, ``A_log = log(U(0, 16))`` and
    ``dt_bias = 1`` (the public implementation's), the zero-centred norms'
    weights are 0 and the gated norm's 1."""
    lay = layout(sizes)
    depth = sizes["published"].get("num_hidden_layers",
                                   sizes["num_hidden_layers"])
    out_std = INIT_STD / math.sqrt(2 * depth)
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    taps = sizes["linear_conv_kernel_dim"]
    n = [0]

    def draw(sample, *args):
        n[0] += 1
        return sample(jax.random.fold_in(key, n[0]), *args)

    def mat(*shape, std=INIT_STD):
        return (draw(jax.random.truncated_normal, -2.0, 2.0, shape,
                     jnp.float32) * std).astype(param_dtype)

    def uniform(shape, low, high):
        return draw(jax.random.uniform, shape, jnp.float32, low, high)

    def norm(width, at=0.0):
        return {"scale": jnp.full((width,), at, param_dtype)}

    def gated(width, *stack):
        return {"w1": mat(*stack, d, width), "w3": mat(*stack, d, width),
                "w2": mat(*stack, width, d, std=out_std)}

    def operator(index):
        if is_full(sizes, index):
            return {"q_proj": mat(d, hq * 2 * hd), "k_proj": mat(d, hkv * hd),
                    "v_proj": mat(d, hkv * hd),
                    "o_proj": mat(hq * hd, d, std=out_std),
                    "q_norm": norm(hd), "k_norm": norm(hd)}
        bound = 1.0 / math.sqrt(taps)
        return {"in_proj_qkvz": mat(d, 2 * hk * dk + 2 * hv * dv),
                "in_proj_ba": mat(d, 2 * hv),
                "conv": uniform((taps, 2 * hk * dk + hv * dv), -bound,
                                bound).astype(param_dtype),
                "dt_bias": jnp.ones((hv,), param_dtype),
                "A_log": jnp.log(uniform((hv,), 1e-6, 16.0)).astype(
                    param_dtype),
                "norm": norm(dv, 1.0),
                "out_proj": mat(hv * dv, d, std=out_std)}

    def layer(index):
        ffn = {"router": mat(d, lay["router"]),
               **gated(sizes["moe_intermediate_size"], sizes["num_experts"]),
               "shared": gated(sizes["shared_expert_intermediate_size"]),
               "shared_gate": mat(d, 1)}
        return {"op_norm": norm(d), "op": operator(index),
                "ffn_norm": norm(d), "ffn": ffn}

    params = {"embed": {"table": mat(sizes["vocab_size"], d, std=1.0)},
              "layers": [layer(i) for i in layers_held(sizes)],
              "final_norm": norm(d),
              "head": mat(d, sizes["vocab_size"])}

    def zero(*shape):
        return jnp.zeros(shape, jnp.float32)

    state = {"layers": [{"drawn": zero(lay["router"]), "held": zero(),
                         "dropped": zero(), "computed": zero(),
                         "combined": zero()} for _ in params["layers"]]}
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


def _rms(x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _norm(p, x, eps):
    """Zero-centred: ``x / rms(x) * (1 + w)``."""
    return _rms(x, eps) * (1.0 + p["scale"].astype(x.dtype))


def in_rows(fn, *xs, before=0):
    """``fn`` of what each position's rows hold, ``Q_ROWS`` positions at a
    time, each block recomputed in the backward pass: ``xs`` lead with the
    ``T`` positions, and so does every leaf of the result. With ``before``,
    ``fn`` is also handed the ``before`` rows that stand before its block
    (zeros before the sequence's start) and answers for the block's own
    positions alone."""
    t = xs[0].shape[0]
    rows = min(t, Q_ROWS)
    xs = tuple(jnp.concatenate(
        [jnp.zeros((before,) + x.shape[1:], x.dtype), x]) for x in xs)

    def block(start):
        return fn(*(lax.dynamic_slice_in_dim(x, start, rows + before)
                    for x in xs))

    out = lax.map(jax.checkpoint(block), jnp.arange(0, t, rows))
    return jax.tree_util.tree_map(
        lambda y: y.reshape(t, *y.shape[2:]), out)


def over_groups(of_group, weights, x):
    """The sum over the groups of heads of ``of_group(a group's weights,
    x)``, one group after another, each recomputed in the backward pass:
    ``weights``' leaves lead with the groups."""
    def add_group(y, w):
        return y + jax.checkpoint(of_group)(w, x), None

    y, _ = lax.scan(add_group, jnp.zeros_like(x), weights)
    return y


def _rotate_first(x, theta, width, positions):
    """``x``: ``(rows, heads, head_dim)``; its first ``width`` numbers
    rotated by ``positions`` (pairs ``(j, j + width / 2)``), the rest as
    they are."""
    inv = 1.0 / theta ** (jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angle = positions.astype(jnp.float32)[:, None, None] * inv
    a, b = x[..., :width // 2], x[..., width // 2:width]
    return jnp.concatenate(
        [a * jnp.cos(angle) - b * jnp.sin(angle),
         b * jnp.cos(angle) + a * jnp.sin(angle), x[..., width:]], axis=-1)


def _attention(p, norm, x, sizes):
    """The full-attention operator of the layer's input ``x``,
    ``HEAD_GROUP`` query heads at a time (a group takes its columns of
    ``W_q``, queries and gates, the one key/value head it reads and its
    rows of ``W_o``, and the groups' results add up), each group recomputed
    in the backward pass."""
    t, d = x.shape
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    width = int(hd * sizes["partial_rotary_factor"])
    rows = min(t, Q_ROWS)
    hg = min(hq // hkv, HEAD_GROUP)         # query heads of a group
    parts = hq // hg
    # query head i reads key/value head i // (hq // hkv)
    reads = jnp.arange(parts) * hg // (hq // hkv)
    mine = {
        "q": jnp.moveaxis(p["q_proj"].reshape(d, parts, hg * 2 * hd), 1, 0),
        "k": jnp.moveaxis(p["k_proj"].reshape(d, hkv, hd), 1, 0)[reads],
        "v": jnp.moveaxis(p["v_proj"].reshape(d, hkv, hd), 1, 0)[reads],
        "o": p["o_proj"].reshape(parts, hg * hd, d)}

    def of_group(w, x):
        def heads_of(x, positions):
            u = _norm(norm, x, eps)
            n = u.shape[0]
            q, gate = jnp.split(_mm(u, w["q"]).reshape(n, hg, 2 * hd), 2,
                                axis=-1)
            k, v = _mm(u, w["k"])[:, None], _mm(u, w["v"])
            q = _rotate_first(_norm(p["q_norm"], q, eps), sizes["rope_theta"],
                              width, positions)
            k = _rotate_first(_norm(p["k_norm"], k, eps), sizes["rope_theta"],
                              width, positions)
            return q, gate, k[:, 0], v

        q, gate, k, v = in_rows(heads_of, x, jnp.arange(t))

        def head(qh):                                       # (T, head_dim)
            def some_queries(start):
                qb = lax.dynamic_slice_in_dim(qh, start, rows)
                ok = ((start + jnp.arange(rows)[:, None])
                      >= jnp.arange(t)[None, :])
                s = (qb @ k.T) / math.sqrt(hd)
                return jax.nn.softmax(jnp.where(ok, s, -jnp.inf),
                                      axis=-1) @ v

            out = lax.map(jax.checkpoint(some_queries),
                          jnp.arange(0, t, rows))
            return out.reshape(t, hd)

        out = lax.map(jax.checkpoint(head), q.transpose(1, 0, 2))
        return in_rows(lambda out, gate: _mm(
            (out * jax.nn.sigmoid(gate)).reshape(-1, hg * hd), w["o"]),
            out.transpose(1, 0, 2), gate)

    return over_groups(of_group, mine, x)


def conv_taps(x, kernel):
    """``y_t = sum_j kernel[j] * x_{t - (taps - 1) + j}`` a channel, zeros
    before the start: ``x`` ``(T, C)``, ``kernel`` ``(taps, C)``."""
    taps, t = kernel.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(kernel[j].astype(x.dtype) * xp[j:j + t] for j in range(taps))


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token, from ``S = 0``: ``q``, ``k`` ``(T,
    Hk, d_k)``; ``v`` ``(T, Hk, G, d_v)`` and ``g``, ``beta`` ``(T, Hk,
    G)``, key head ``h`` serving the value heads ``[h, 0 .. G - 1]`` ->
    ``(T, Hk, G, d_v)``."""
    t, hk, dk = q.shape
    groups, dv = v.shape[2:]
    rows = min(t, RULE_ROWS)

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None, None]
        delta = b_t[..., None] * (v_t - jnp.einsum("hgde,hd->hge", s, k_t))
        s = s + k_t[:, None, :, None] * delta[:, :, None, :]
        return s, jnp.einsum("hgde,hd->hge", s, q_t)

    def block(s, xs):
        return lax.scan(token, s, xs)

    _, o = lax.scan(jax.checkpoint(block),
                    jnp.zeros((hk, groups, dk, dv), q.dtype),
                    tuple(a.reshape(t // rows, rows, *a.shape[1:])
                          for a in (q, k, v, g, beta)))
    return o.reshape(t, hk, groups, dv)


def _gated_delta(p, norm, x, sizes):
    """The gated delta operator of the layer's input ``x``: steps 1 to 6 of
    the module's docstring, ``HEAD_GROUP`` key heads with their value heads
    at a time (every step but the two projections is a head's own: a group
    takes its columns of ``W_qkvz``, ``W_ba`` and the convolution and its
    rows of ``W_out``, and the groups' results add up), each group
    recomputed in the backward pass: the float32 operands of all 48 heads
    over 16,384 positions, their gradients and the rule's kept states are 4
    GB. What stands before the rule and behind it is made ``Q_ROWS``
    positions at a time; the convolution of a block reads the three rows
    before it, which a zero input row leaves zero as the sequence's start
    wants them (no bias)."""
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    taps, eps = sizes["linear_conv_kernel_dim"], sizes["rms_norm_eps"]
    key_dim, value_dim, groups = hk * dk, hv * dv, hv // hk
    hg = min(hk, HEAD_GROUP)                # key heads of a group
    parts = hk // hg

    def columns(w, first, width):
        """``w``'s columns ``first .. first + width``, which belong to the
        heads in order, a group of heads at a time: ``(parts, rows, width /
        parts)``."""
        rows = w.shape[0]
        return jnp.moveaxis(w[:, first:first + width].reshape(
            rows, parts, width // parts), 1, 0)

    w_in, conv = p["in_proj_qkvz"], p["conv"]
    mine = {
        "q": columns(w_in, 0, key_dim),
        "k": columns(w_in, key_dim, key_dim),
        "v": columns(w_in, 2 * key_dim, value_dim),
        "z": columns(w_in, 2 * key_dim + value_dim, value_dim),
        "b": columns(p["in_proj_ba"], 0, hv),
        "a": columns(p["in_proj_ba"], hv, hv),
        "conv_q": columns(conv, 0, key_dim),
        "conv_k": columns(conv, key_dim, key_dim),
        "conv_v": columns(conv, 2 * key_dim, value_dim),
        "A_log": p["A_log"].reshape(parts, -1),
        "dt_bias": p["dt_bias"].reshape(parts, -1),
        "out": p["out_proj"].reshape(parts, value_dim // parts, -1)}

    def of_group(w, x):
        def operands(x):
            u = _norm(norm, x, eps)         # rows + 3: the block's and before
            n = u.shape[0] - (taps - 1)

            def conv_silu(name):
                return jax.nn.silu(conv_taps(
                    _mm(u, w[name]), w["conv_" + name])[taps - 1:])

            q = conv_silu("q").reshape(n, hg, dk)
            k = conv_silu("k").reshape(n, hg, dk)
            # key head h serves the value heads h * groups on
            v = conv_silu("v").reshape(n, hg, groups, dv)
            q = (q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
                 / math.sqrt(dk))
            k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
            own = u[taps - 1:]
            beta = jax.nn.sigmoid(_mm(own, w["b"]))
            g = -jnp.exp(w["A_log"].astype(u.dtype)) * jax.nn.softplus(
                _mm(own, w["a"]) + w["dt_bias"].astype(u.dtype))
            z = _mm(own, w["z"]).reshape(n, hg * groups, dv)
            return (q, k, v, g.reshape(n, hg, groups),
                    beta.reshape(n, hg, groups)), z

        def gated_norm(o, z):
            y = (p["norm"]["scale"].astype(o.dtype)
                 * _rms(o.reshape(-1, hg * groups, dv), eps) * jax.nn.silu(z))
            return _mm(y.reshape(y.shape[0], -1), w["out"])

        rule_operands, z = in_rows(operands, x, before=taps - 1)
        return in_rows(gated_norm, delta_rule(*rule_operands), z)

    return over_groups(of_group, mine, x)


def _ffn(m, w):
    """``W_2 (silu(W_1 m) * W_3 m)``."""
    return _mm(jax.nn.silu(_mm(m, w["w1"])) * _mm(m, w["w3"]), w["w2"])


def gates(p, m, sizes):
    """Every token's weight for each of the router's experts: zero but for
    the ``num_experts_per_tok`` it chose, the chosen ones' probabilities
    over their sum."""
    s = jax.nn.softmax(_mm(m, p["router"]), axis=-1)
    chosen = jnp.zeros(s.shape, bool)
    for _ in range(sizes["num_experts_per_tok"]):
        best = jnp.argmax(jnp.where(chosen, -jnp.inf, s), axis=-1)
        chosen = chosen | jax.nn.one_hot(best, s.shape[-1], dtype=bool)
    picked = jnp.where(chosen, s, 0.0)
    return picked / jnp.sum(picked, axis=-1, keepdims=True)


def routed(p, m, token_gates, first, held):
    """The part of the expert layer's result that the ``held`` experts from
    ``first`` on give (``p``'s stacks are theirs): one expert after another,
    each applied to every token."""
    mine = token_gates[:, first:first + held].T

    def expert(y, weights_and_gate):
        w, gate = weights_and_gate
        return y + gate[:, None] * _ffn(m, w), None

    y, _ = lax.scan(expert, jnp.zeros_like(m),
                    ({k: p[k] for k in ("w1", "w3", "w2")}, mine))
    return y


def shared(p, m):
    """``sigmoid(m w_g) * shared(m)``: what every chip of a layer computes
    alike."""
    return jax.nn.sigmoid(_mm(m, p["shared_gate"])) * _ffn(m, p["shared"])


def layer(p, x, sizes, lay, index):
    """Published layer ``index`` on one sequence ``x`` ``(T, d)``."""
    eps = sizes["rms_norm_eps"]
    op = _attention if is_full(sizes, index) else _gated_delta
    h = x + op(p["op"], p["op_norm"], x, sizes)

    def experts(h):
        m = _norm(p["ffn_norm"], h, eps)
        y = routed(p["ffn"], m, gates(p["ffn"], m, sizes), lay["first"],
                   sizes["num_experts"])
        return h + y + shared(p["ffn"], m)

    return in_rows(experts, h)


def loss(params, state, ids, sizes):
    """The next-token loss of the module's docstring: ``(loss, state)``;
    the counters in ``state`` are the program's own and pass through
    untouched."""
    lay = layout(sizes)
    n, t = ids.shape
    rows = min(t, Q_ROWS)

    def part_loss(x_targets_w):
        x, targets, w = x_targets_w
        u = _norm(params["final_norm"], x, sizes["rms_norm_eps"])
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
