"""Qwen3-Next causal decoder (``Qwen/Qwen3-Next-80B-A3B-Instruct`` as
configured, ``model_type: qwen3_next``): gated delta-rule layers, three in
four, beside softmax attention with an output gate at heads of 256; sparse
experts in every layer beside a gated shared expert; next-token loss.

``norm(x) = x / rms(x) * (1 + w)`` with ``w`` starting at zero (every norm
but the gated one). Layer ``i`` is full attention where ``(i + 1) %
full_attention_interval == 0`` and a gated delta layer otherwise; each is
``x <- x + op(norm_1(x))``, then ``x <- x + moe(norm_2(x))``. No bias
anywhere.

**Gated delta layer** (``u = norm_1(x)``; ``linear_num_key_heads`` key
heads and ``linear_num_value_heads`` value heads, each key head serving
``G`` consecutive value heads):

1. ``[q, k, v, z] = u W_qkvz``, ``[b, a] = u W_ba`` (this module's column
   order: all of ``q``, then ``k``, ``v``, ``z``; ``b`` then ``a``).
2. ``[q, k, v] <- silu(conv([q, k, v]))``: causal, depthwise over the
   channels, ``linear_conv_kernel_dim`` taps, zeros before a sequence's
   start, no bias.
3. A head: ``q <- q / |q| / sqrt(d_k)``, ``k <- k / |k|`` (``eps`` 1e-6
   under the root), float32.
4. ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``,
   float32, one number a value head and token.
5. The rule, with ``S_0 = 0`` (``d_k x d_v`` a value head, float32): ``S <-
   exp(g_t) S``; ``delta_t = beta_t (v_t - S^T k_t)``; ``S <- S + k_t
   delta_t^T``; ``o_t = S^T q_t``.
6. ``y = w_n * (o / rms(o)) * silu(z)`` a head (``w_n`` starting at one,
   the gate applied after the norm); the layer's output is ``y W_out``.

**Step 5 in chunks** (:func:`delta_rule`; the WY form of arXiv:2412.06464).
Within a chunk of ``c`` tokens, with ``G_i`` the running sum of ``g`` from
the chunk's start and ``D_ij = exp(G_i - G_j)`` for ``j <= i``: the
``delta``s of a chunk solve ``(I + A) delta = beta v - (beta k exp(G)) S``
with ``A_ij = beta_i (k_i . k_j) D_ij`` for ``j < i``, a unit lower
triangular system. Its inverse ``T`` is made for all chunks at once by
products alone (``A`` is nilpotent: ``(I + A)^-1 = prod_j (I + (-A)^(2^j))``;
:func:`_unit_lower_inverse`, float32, with a backward rule of its own that
keeps ``T`` and nothing of the chain); ``u = T (beta v)`` and ``w = T (beta
k exp(G))`` likewise. What is sequential is one ``lax.scan`` over the
chunks, two products a step: ``delta = u - w S``, ``S <- exp(G_c) S + (k
exp(G_c - G))^T delta``. The outputs are again products over all chunks:
``o = (q exp(G)) S + ((q k^T) * D) delta``, ``S`` the state each chunk
started from. The state is carried in float32; products take operands in
the activation dtype and sum in float32. **Memory of its backward pass**:
the sequence is walked in spans of ``delta_span`` positions, an outer
``lax.scan`` whose body is recomputed in the backward pass, so what is kept
is the state at each span's start, and within the span being differentiated
the state at each chunk's start with the chunk-local products.

**Full-attention layer**: ``lfm2.attention`` told three things: the query
projection is twice as wide and carries a head's output gate behind its
queries (``out * sigmoid(gate)`` before ``o_proj``), the query and key
heads' norms are zero-centred, and the first ``partial_rotary_factor *
head_dim`` numbers of a head are rotated (half-split pairs), the rest not.

**Expert layer**: ``s = softmax(u W_r)`` in float32 over all
``num_experts``, the ``num_experts_per_tok`` largest, gates ``s_e / sum of
the chosen`` (``sdar.route``); the held experts' walk is ``lfm2``'s; beside
it ``sigmoid(u w_g) * shared(u)``, the shared expert a gated-SiLU
feed-forward every chip of a layer computes whole.

Memory as in the other decoders: every part of a layer is recomputed in the
backward pass from its input (the fused attention kernel's output and
log-sum-exp kept), the operator ``seq_block`` sequences at a time, the
expert layer all sequences together; the head walks ``head_positions``
positions at a time and forms its gradient in that walk. The config has no
key for a multi-token-prediction block, and none is built.

Model state: per layer the expert layer's counters of ``lfm2`` without a
bias (``drawn``, ``held``, ``computed``, ``combined``, ``dropped``), float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from grace_tpu.models import layers as L
from grace_tpu.models.lfm2 import (_dot, _over_sequences, attention,
                                   dense_ffn, expert_layer_state, head_loss,
                                   moe_ffn, next_token_targets)
from grace_tpu.models.sdar import route
from grace_tpu.telemetry.scopes import (STAGE_ATTENTION, STAGE_DELTA_RULE,
                                        STAGE_GATED_DELTA,
                                        STAGE_SHARED_EXPERT)

_L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Config:
    """Qwen3-Next-80B-A3B-Instruct as published, all of it held here,
    unless said otherwise. ``vocab_size`` is the number of rows held;
    ``layer_types`` has an entry a layer held.

    The functions imported from ``lfm2`` and ``sdar`` are handed this
    ``Config`` in place of their own and read their fields from it
    (``deepseek_v3.SHARED_FIELDS`` but for the two ``route`` does not use,
    and attention's ``num_attention_heads``, ``num_key_value_heads``,
    ``head_dim``, ``rope_theta``, ``attn_q_block``): the dataclasses keep
    those names with one meaning."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = (("linear_attention",) * 3
                                    + ("full_attention",)) * 12
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512
    num_experts_per_tok: int = 10
    first_expert: int = 0
    experts_held: int = 512
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64            # partial_rotary_factor * head_dim
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    norm_eps: float = 1e-6
    # scaled initialisation counts the published depth, held or not
    published_layers: int = 48
    # how the work is walked, not what is computed
    seq_block: int = 1            # sequences recomputed together
    attn_q_block: int = 1024      # queries scored together (plain path)
    moe_row_block: int = 0        # rows of one tile of the expert walk; 0:
                                  # from the shapes
    head_positions: int = 4096    # positions the head scores together
    delta_chunk: int = 64         # tokens of one chunk of the delta rule
    delta_span: int = 2048        # positions of one span of its backward

    def __post_init__(self):
        if not 0 <= self.first_expert <= (self.num_experts
                                          - self.experts_held):
            raise ValueError(
                f"experts {self.first_expert}..+{self.experts_held} are not "
                f"among the router's {self.num_experts}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide over key/value heads")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("value heads must divide over key heads")
        unknown = set(self.layer_types) - {"linear_attention",
                                           "full_attention"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.delta_span % self.delta_chunk:
            raise ValueError("a span is whole chunks")

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)


def tiny(**kw) -> Config:
    """Test-scale config: one period (three gated delta layers, a full
    layer), 8 experts, 2 a token, 2 key heads serving 4 value heads of 8,
    chunks of 4 tokens in spans of 8."""
    d = dict(vocab_size=128, hidden_size=32,
             layer_types=("linear_attention",) * 3 + ("full_attention",),
             moe_intermediate_size=16, shared_expert_intermediate_size=16,
             num_experts=8, num_experts_per_tok=2, experts_held=8,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             rotary_dim=4, linear_num_key_heads=2, linear_num_value_heads=4,
             linear_key_head_dim=8, linear_value_head_dim=8,
             published_layers=4, attn_q_block=8, moe_row_block=16,
             head_positions=8, delta_chunk=4, delta_span=8)
    d.update(kw)
    return Config(**d)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def init(key: jax.Array, cfg: Config) -> Tuple[L.Params, L.ModelState]:
    """Truncated normal (std 0.02) matrices; zero-centred norm weights 0 and
    the gated norm's 1; the embedding's rows std 1 and the projections that
    write to the residual stream (``out_proj``, ``o_proj``, every ``w2``)
    scaled by ``1 / sqrt(2 * published layers)`` (``models/sdar.py::init``
    says why); the convolution uniform in +-1/sqrt(taps); ``A_log = log(U(0,
    16))`` and ``dt_bias = 1`` as the public implementation starts them."""
    d = cfg.hidden_size
    keys = iter(L.split_keys(key, 2 + 16 * cfg.num_hidden_layers))
    out_std = 0.02 / math.sqrt(2 * cfg.published_layers)

    def mat(*shape, std=0.02):
        return L.trunc_normal(next(keys), shape, std)

    def zero_centred(width):
        return {"scale": jnp.zeros((width,))}

    def gated(width, *stack):
        return {"w1": mat(*stack, d, width), "w3": mat(*stack, d, width),
                "w2": mat(*stack, width, d, std=out_std)}

    def operator(kind):
        if kind == "full_attention":
            hq, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                           cfg.head_dim)
            return {"q_proj": mat(d, hq * 2 * hd), "k_proj": mat(d, hkv * hd),
                    "v_proj": mat(d, hkv * hd),
                    "o_proj": mat(hq * hd, d, std=out_std),
                    "q_norm": zero_centred(hd), "k_norm": zero_centred(hd)}
        hv, taps = cfg.linear_num_value_heads, cfg.linear_conv_kernel_dim
        key_dim = cfg.linear_num_key_heads * cfg.linear_key_head_dim
        value_dim = hv * cfg.linear_value_head_dim
        bound = 1.0 / math.sqrt(taps)
        return {"in_proj_qkvz": mat(d, 2 * key_dim + 2 * value_dim),
                "in_proj_ba": mat(d, 2 * hv),
                "conv": jax.random.uniform(
                    next(keys), (taps, 2 * key_dim + value_dim), jnp.float32,
                    -bound, bound),
                "dt_bias": jnp.ones((hv,)),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (hv,), jnp.float32, 1e-6, 16.0)),
                "norm": L.rms_init(cfg.linear_value_head_dim),
                "out_proj": mat(value_dim, d, std=out_std)}

    def layer(kind):
        ffn = {"router": mat(d, cfg.num_experts),
               **gated(cfg.moe_intermediate_size, cfg.experts_held),
               "shared": gated(cfg.shared_expert_intermediate_size),
               "shared_gate": mat(d, 1)}
        return {"op_norm": zero_centred(d), "op": operator(kind),
                "ffn_norm": zero_centred(d), "ffn": ffn}

    params = {"embed": {"table": mat(cfg.vocab_size, d, std=1.0)},
              "layers": [layer(kind) for kind in cfg.layer_types],
              "final_norm": zero_centred(d),
              "head": mat(d, cfg.vocab_size)}
    return params, init_state(cfg)


def init_state(cfg: Config) -> L.ModelState:
    def expert_layer():
        state = expert_layer_state(cfg.num_experts)
        del state["expert_bias"]        # the router has none
        return state

    return {"layers": [expert_layer() for _ in cfg.layer_types]}


def _one_plus(p):
    """A zero-centred norm's weight as ``layers.rms_apply`` takes one: ``1
    + w``, in float32."""
    return {"scale": 1.0 + p["scale"]}


def _norm(p, x, eps):
    return L.rms_apply(_one_plus(p), x, eps)


# ---------------------------------------------------------------------------
# the gated delta rule, in chunks
# ---------------------------------------------------------------------------

# The inverse's products multiply float32 matrices whose entries the rest of
# the rule takes at the activations' precision: three bfloat16 passes.
_INVERSE_PRECISION = lax.Precision.HIGH


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower triangular ``a`` ``(..., c, c)``,
    float32, by products alone: ``a`` is nilpotent, so the inverse is the
    finite series ``sum_k (-a)^k = prod_j (I + (-a)^(2^j))``. Backwards
    ``da = -T^T dT T^T`` of the result ``T``, which is all that is kept."""
    c = a.shape[-1]
    x = -a
    inv = jnp.eye(c, dtype=a.dtype) + x
    power = 2
    while power < c:
        x = jnp.matmul(x, x, precision=_INVERSE_PRECISION)
        inv = inv + jnp.matmul(inv, x, precision=_INVERSE_PRECISION)
        power *= 2
    return inv


def _unit_lower_inverse_fwd(a):
    inv = _unit_lower_inverse(a)
    return inv, inv


def _unit_lower_inverse_bwd(inv, g):
    t = jnp.swapaxes(inv, -1, -2)
    return (-jnp.matmul(jnp.matmul(t, g, precision=_INVERSE_PRECISION), t,
                        precision=_INVERSE_PRECISION),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _span(state, q, k, v, g, beta, chunk: int):
    """The rule over one span of whole chunks from ``state``: ``(outputs,
    the state behind the span)``. ``q``, ``k``: ``(n, Hk, L, d_k)``; ``v``:
    ``(n, Hk, G, L, d_v)``; ``g``, ``beta``: ``(n, Hk, G, L)`` float32;
    ``state``: ``(n, Hk, G, d_k, d_v)`` float32."""
    f32, dtype = jnp.float32, v.dtype
    n, hk, length, dk = q.shape
    groups, dv = v.shape[2], v.shape[4]
    chunks = length // chunk

    def dot(spec, a, b):
        return jnp.einsum(spec, a, b, preferred_element_type=f32)

    qc = q.reshape(n, hk, chunks, chunk, dk)
    kc = k.reshape(n, hk, chunks, chunk, dk)
    vc = v.reshape(n, hk, groups, chunks, chunk, dv)
    bc = beta.reshape(n, hk, groups, chunks, chunk)
    gc = jnp.cumsum(g.reshape(n, hk, groups, chunks, chunk), axis=-1)
    at = jnp.arange(chunk)
    lower = at[:, None] >= at[None, :]
    # exp(G_i - G_j) where j <= i (never above one), zero elsewhere; the
    # difference is masked before the exponential, which would overflow
    # above the diagonal, in the gradient too
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, gc[..., :, None] - gc[..., None, :], 0.0)), 0.0)
    kk = dot("nhcid,nhcjd->nhcij", kc, kc)
    a = jnp.where(at[:, None] > at[None, :],
                  bc[..., :, None] * kk[:, :, None] * decay, 0.0)
    t = _unit_lower_inverse(a).astype(dtype)
    kf = kc[:, :, None].astype(f32)
    u = dot("nhgcij,nhgcjd->nhgcid", t,
            (vc.astype(f32) * bc[..., None]).astype(dtype))
    w = dot("nhgcij,nhgcjd->nhgcid", t,
            (kf * (bc * jnp.exp(gc))[..., None]).astype(dtype)).astype(dtype)
    g_last = gc[..., -1]
    # what a chunk's keys add to the state behind it
    kd = (kf * jnp.exp(g_last[..., None] - gc)[..., None]).astype(dtype)

    def chunk_step(s, x):
        w_c, u_c, kd_c, keep = x
        delta = u_c - dot("nhgid,nhgde->nhgie", w_c, s.astype(dtype))
        delta = delta.astype(dtype)
        behind = s * keep[..., None, None] + dot("nhgid,nhgie->nhgde", kd_c,
                                                 delta)
        return behind, (s, delta)

    def chunks_first(x):
        return jnp.moveaxis(x, 3, 0)

    state, (starts, delta) = lax.scan(
        chunk_step, state,
        (chunks_first(w), chunks_first(u), chunks_first(kd),
         chunks_first(jnp.exp(g_last))))
    starts, delta = (jnp.moveaxis(x, 0, 3) for x in (starts, delta))
    qk = dot("nhcid,nhcjd->nhcij", qc, kc)
    within = (qk[:, :, None] * decay).astype(dtype)
    qg = (qc[:, :, None].astype(f32) * jnp.exp(gc)[..., None]).astype(dtype)
    out = (dot("nhgcid,nhgcde->nhgcie", qg, starts.astype(dtype))
           + dot("nhgcij,nhgcje->nhgcie", within, delta))
    return out.astype(dtype).reshape(n, hk, groups, length, dv), state


def delta_rule(q, k, v, g, beta, chunk: int, span: int):
    """Step 5 of the module's docstring over whole sequences from ``S_0 =
    0``, in chunks of ``chunk`` tokens and, for the backward pass's memory,
    spans of ``span`` positions each recomputed from the state it started
    from. ``q``, ``k`` ``(n, Hk, T, d_k)`` normalised (``q`` scaled); ``v``
    ``(n, Hk, G, T, d_v)``, value head ``h * G + j`` at ``[h, j]``; ``g``,
    ``beta`` ``(n, Hk, G, T)`` float32. Returns ``o`` in ``v``'s shape and
    dtype."""
    n, hk, t, dk = q.shape
    groups, dv = v.shape[2], v.shape[4]
    span = min(span, t)
    chunk = min(chunk, span)        # a shorter sequence is one chunk
    if t % span or span % chunk:
        raise ValueError(f"{t} positions are not whole spans of {span} in "
                         f"chunks of {chunk}")
    state = jnp.zeros((n, hk, groups, dk, dv), jnp.float32)
    if span == t:
        return _span(state, q, k, v, g, beta, chunk)[0]

    def spans_first(x, axis):
        shape = x.shape[:axis] + (t // span, span) + x.shape[axis + 1:]
        return jnp.moveaxis(x.reshape(shape), axis, 0)

    def visit(s, x):
        out, s = _span(s, *x, chunk)
        return s, out

    _, out = lax.scan(
        jax.checkpoint(visit), state,
        (spans_first(q, 2), spans_first(k, 2), spans_first(v, 3),
         spans_first(g, 3), spans_first(beta, 3)))
    return jnp.moveaxis(out, 0, 3).reshape(v.shape)


def causal_conv(x, kernel):
    """``y_t = sum_j kernel[j] * x_{t - (taps - 1) + j}``, depthwise over
    the channels of ``x`` ``(n, T, C)``, zeros before the sequence's start;
    ``kernel``: ``(taps, C)``."""
    taps, t = kernel.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    kernel = kernel.astype(x.dtype)
    return sum(kernel[j] * xp[:, j:j + t] for j in range(taps))


def _l2_normalised(x, scale=1.0):
    xf = x.astype(jnp.float32)
    return xf * (lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + _L2_EPS)
                 * scale)


def gated_delta(p, u, cfg: Config):
    """The gated delta layer's operator of normalised ``u`` ``(n, T, d)``:
    steps 1 to 6 of the module's docstring."""
    n, t, _ = u.shape
    f32, dtype = jnp.float32, u.dtype
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    groups, key_dim, value_dim = hv // hk, hk * dk, hv * dv
    qkv, z = jnp.split(_dot(u, p["in_proj_qkvz"]), [2 * key_dim + value_dim],
                       axis=-1)
    b, a = jnp.split(_dot(u, p["in_proj_ba"]).astype(f32), 2, axis=-1)
    qkv = jax.nn.silu(causal_conv(qkv, p["conv"]))
    q, k, v = jnp.split(qkv, [key_dim, 2 * key_dim], axis=-1)

    def key_heads(x, scale=1.0):            # (n, T, Hk * d_k) -> (n, Hk, T, .)
        x = _l2_normalised(x.reshape(n, t, hk, dk), scale)
        return jnp.swapaxes(x, 1, 2).astype(dtype)

    def value_heads(x, *width):             # (n, T, Hv * .) -> (n, Hk, G, T, .)
        return jnp.moveaxis(x.reshape(n, t, hk, groups, *width), 1, 3)

    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
        a + p["dt_bias"].astype(f32))
    with jax.named_scope(STAGE_DELTA_RULE):
        o = delta_rule(key_heads(q, dk ** -0.5), key_heads(k),
                       value_heads(v, dv), value_heads(g), value_heads(beta),
                       cfg.delta_chunk, cfg.delta_span)
    o = jnp.moveaxis(o, 3, 1).reshape(n, t, hv, dv)
    y = L.rms_apply(p["norm"], o, cfg.norm_eps).astype(f32)
    y = y * jax.nn.silu(z.reshape(n, t, hv, dv).astype(f32))
    return _dot(y.astype(dtype).reshape(n, t, value_dim), p["out_proj"])


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _operator_part(kind, cfg):
    def delta(p, x):
        with jax.named_scope(STAGE_GATED_DELTA):
            return x + gated_delta(p["op"], _norm(p["op_norm"], x,
                                                  cfg.norm_eps), cfg)

    def full(p, x):
        op = dict(p["op"], q_norm=_one_plus(p["op"]["q_norm"]),
                  k_norm=_one_plus(p["op"]["k_norm"]))
        with jax.named_scope(STAGE_ATTENTION):
            return x + attention(op, _norm(p["op_norm"], x, cfg.norm_eps),
                                 cfg, rotary_dim=cfg.rotary_dim, gated=True)

    return full if kind == "full_attention" else delta


def shared_expert(p, u):
    """``sigmoid(u w_g) * shared(u)``: the gated-SiLU feed-forward every
    token passes, weighed a token by one number (its sigmoid in float32)."""
    gate = jax.nn.sigmoid(_dot(u, p["shared_gate"]).astype(jnp.float32))
    return gate.astype(u.dtype) * dense_ffn(p["shared"], u)


def _moe_part(cfg):
    def part(p, state, x):
        u = _norm(p["ffn_norm"], x, cfg.norm_eps)
        y, state = moe_ffn(p["ffn"], state, u, cfg, route)
        with jax.named_scope(STAGE_SHARED_EXPERT):
            y = y + shared_expert(p["ffn"], u)
        return x + y, state
    return part


def hidden_states(params, model_state, ids, cfg: Config, dtype=jnp.float32):
    """ids ``(n, T)`` -> the last layer's output ``(n, T, d)`` (before the
    final norm) and the new model state."""
    x = L.embedding_apply(params["embed"], ids, dtype=dtype)
    # one traced function a kind of operator, not one a layer
    parts = {kind: _operator_part(kind, cfg) for kind in set(cfg.layer_types)}
    moe = jax.checkpoint(_moe_part(cfg))
    new_state = []
    for kind, p, s in zip(cfg.layer_types, params["layers"],
                          model_state["layers"]):
        x = _over_sequences(parts[kind], p, x, cfg.seq_block)
        # recomputed from x, all sequences together, as kanana's
        x, s = moe(p, s, x)
        new_state.append(s)
    return x, {"layers": new_state}


def next_token_loss(params, model_state, ids, cfg: Config,
                    dtype=jnp.float32):
    """Mean over all tokens of the cross-entropy of position ``t``'s logits
    against token ``t + 1`` (a sequence's last position has no target):
    ``(loss, new_model_state)``. The head walks ``head_positions``
    positions at a time, the last position of a sequence weighted zero."""
    n, t = ids.shape
    x, new_state = hidden_states(params, model_state, ids, cfg, dtype)
    part = min(t, cfg.head_positions)
    if t % part:
        raise ValueError(f"{t} positions are not whole parts of {part}")
    head = {"final_norm": _one_plus(params["final_norm"]),
            "head": params["head"]}
    loss = head_loss(head, x, *next_token_targets(ids), 1.0 / (n * (t - 1)),
                     cfg.seq_block * part, cfg.norm_eps)
    return loss, new_state
