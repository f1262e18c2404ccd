"""DeepSeek-V3-shaped causal decoder (``model_type`` ``deepseek_v3``, as
``kakaocorp/kanana-2-30b-a3b-instruct-2601`` is configured): multi-head
latent attention in every layer, a dense gated feed-forward in the leading
layers, then shared experts beside sparse routed ones, next-token loss.

Equations, as ``transformers``' ``modeling_deepseek_v3`` computes them
without a query latent (``q_lora_rank`` null), with ``u = RMSNorm(x)``
(learned weight; no bias anywhere):

* layer: ``h = x + MLA(RMSNorm_in(x))``, ``y = h + FFN(RMSNorm_post(h))``;
  after the last layer RMSNorm, then the untied output head.
* latent attention (MLA): ``q = W_q u`` is ``(T, H, nope + rope)``, split
  into ``q_nope`` and ``q_pe``. ``(c, k_pe) = split(W_kva u)``: the latent
  ``c``, ``kv_lora_rank`` wide, and ONE rotary key ``k_pe``, ``rope`` wide,
  that all ``H`` heads share. ``(k_nope, v) = split(W_kvb RMSNorm_kv(c))``
  is ``(T, H, nope + v_head_dim)``. Rotary positions turn ``q_pe`` and
  ``k_pe`` only, in neighbouring pairs (``rope_interleave``). ``q =
  concat(q_nope, q_pe)``, ``k = concat(k_nope, k_pe for every head)``;
  ``out = softmax(q k^T / sqrt(nope + rope) + causal) v`` in float32, then
  ``W_o``. The shared key's gradient is the sum over the heads.
* dense feed-forward (the first ``first_k_dense_replace`` layers):
  ``W_2 (silu(W_1 h) * W_3 h)``.
* expert feed-forward: ``s = sigmoid(W_r h)`` in float32; the
  ``num_experts_per_tok`` experts are the top of ``s + b`` over all
  ``num_experts`` (``b`` the correction bias, model state, no gradient; one
  group, so the group step of ``noaux_tc`` selects everything); gates ``s_i
  / (sum s_i + 1e-20) * routed_scaling_factor``; ``y = sum_i g_i E_i(h) +
  S(h)`` with ``E_i`` a gated feed-forward of width
  ``moe_intermediate_size`` and ``S`` one of ``n_shared_experts`` times
  that width (the shared experts as one feed-forward, as HF builds them).

Departures. Where HF de-interleaves the rotary part and rotates halves,
the pairs are turned where they lie: queries and keys get the same
permutation of their rotary entries either way, so every score is the same.
The correction bias is held at zero and never updated (no update rule is
published).

What is shared with ``models/lfm2.py`` is imported from it, not copied: the
router, the expert layer told which experts it holds (``moe_ffn``: routes
over all ``num_experts``, computes its own experts' part over the sorted
assignments in tiles of one expert each, as many as hold a row, sums the
tiles' rows by token with sorts and gathers, and drops none), the dense
feed-forward and its part of a layer,
the walk over sequences, the head with the loss. The shared expert is
computed whole by every chip of a layer and added to the routed part. Two
spellings of the scores, as there: the fused kernel where ``ops.pallas_attention.engages`` says so (the
platform, the sequence length, the head sizes ``(nope + rope, v_head_dim)``
and the dtype decide), ``attn_q_block`` queries at a time everywhere else;
head-major ``(n, H, T, .)`` from the query and ``kv_b`` products to the
output projection's, as there.
The kernel applies no scale and ``1 / sqrt(192)`` is no power of two, so
on its path the scale is folded into ``W_q`` in float32 as the weights are
cast to the activation dtype: ``q`` is rounded once, as the plain
spelling's is, and its float32 scores are scaled where they are made.

Memory as in ``lfm2.py``: every part of a layer is recomputed in the
backward pass from its input (but for the fused kernel's output and
log-sum-exp, which are kept), attention and the dense feed-forward
``seq_block`` sequences at a time, and the head forms its gradient in the
walk that makes the logits; parameters float32, cast inside a block, so a
weight's gradient is summed over the blocks in float32. Model state: per
expert layer the correction bias and the counters ``drawn``, ``held``,
``computed``, ``combined``, ``dropped`` of ``lfm2``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from grace_tpu.models import layers as L
from grace_tpu.models.lfm2 import (_dense_part, _dot, _from_heads, _heads_of,
                                   _over_sequences, _plain_scores, dense_ffn,
                                   expert_layer_state, loss_of_hidden_states,
                                   moe_ffn)
from grace_tpu.ops import pallas_attention
from grace_tpu.telemetry.scopes import (STAGE_ATTENTION, STAGE_MLA_LATENT,
                                        STAGE_SHARED_EXPERT)


# What lfm2's route, moe_ffn, _dense_part and loss_of_hidden_states (which
# hands head_loss ``norm_eps`` and ``seq_block`` sequences' positions) read
# from the Config they are given.
SHARED_FIELDS = ("num_experts", "num_experts_per_tok", "first_expert",
                 "experts_held", "routed_scaling_factor", "route_eps",
                 "moe_row_block", "norm_eps", "seq_block")


@dataclasses.dataclass(frozen=True)
class Config:
    """kanana-2-30b-a3b-instruct-2601 as published, all of it held here,
    unless said otherwise. ``vocab_size`` is the number of rows held.

    The functions imported from ``lfm2`` are handed this ``Config`` in
    place of ``lfm2.Config`` and read :data:`SHARED_FIELDS` from it: both
    dataclasses keep those names with one meaning."""
    vocab_size: int = 128256
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    first_k_dense_replace: int = 1
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    n_shared_experts: int = 2
    num_experts: int = 128        # the router's outputs (n_routed_experts)
    num_experts_per_tok: int = 6
    first_expert: int = 0
    experts_held: int = 128
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    routed_scaling_factor: float = 2.448
    route_eps: float = 1e-20
    # how the work is walked, not what is computed
    seq_block: int = 1            # sequences recomputed together
    attn_q_block: int = 1024      # queries scored together (plain path)
    moe_row_block: int = 0        # rows of one tile of the expert walk; 0:
                                  # from the shapes

    def __post_init__(self):
        if not 0 <= self.first_expert <= (self.num_experts
                                          - self.experts_held):
            raise ValueError(
                f"experts {self.first_expert}..+{self.experts_held} are not "
                f"among the router's {self.num_experts}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotary positions turn pairs of entries")

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def tiny(**kw) -> Config:
    """Test-scale config: one dense layer and four expert layers, 8
    experts, head sizes 12 | 8 (8 without positions, 4 rotary)."""
    d = dict(vocab_size=128, hidden_size=32, num_hidden_layers=5,
             intermediate_size=64, moe_intermediate_size=16,
             n_shared_experts=2, num_experts=8, num_experts_per_tok=2,
             experts_held=8, num_attention_heads=4, kv_lora_rank=16,
             qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
             attn_q_block=8, moe_row_block=16)
    d.update(kw)
    return Config(**d)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def init(key: jax.Array, cfg: Config) -> Tuple[L.Params, L.ModelState]:
    """Truncated normal (std 0.02) matrices, unit norm weights, untied
    embedding and head, correction bias zero."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    keys = iter(L.split_keys(key, 2 + 12 * cfg.num_hidden_layers))

    def mat(*shape):
        return L.trunc_normal(next(keys), shape)

    def gated(width, *stack):
        return {"w1": mat(*stack, d, width), "w3": mat(*stack, d, width),
                "w2": mat(*stack, width, d)}

    def layer(i):
        attn = {"q_proj": mat(d, h * cfg.qk_head_dim),
                "kv_a_proj": mat(d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
                "kv_a_norm": L.rms_init(cfg.kv_lora_rank),
                "kv_b_proj": mat(cfg.kv_lora_rank,
                                 h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                "o_proj": mat(h * cfg.v_head_dim, d)}
        if cfg.is_moe(i):
            ffn = {"router": mat(d, cfg.num_experts),
                   **gated(cfg.moe_intermediate_size, cfg.experts_held),
                   "shared": gated(cfg.moe_intermediate_size
                                   * cfg.n_shared_experts)}
        else:
            ffn = gated(cfg.intermediate_size)
        return {"attn_norm": L.rms_init(d), "attn": attn,
                "ffn_norm": L.rms_init(d), "ffn": ffn}

    params = {"embed": L.embedding_init(next(keys), cfg.vocab_size, d),
              "layers": [layer(i) for i in range(cfg.num_hidden_layers)],
              "final_norm": L.rms_init(d),
              "head": mat(d, cfg.vocab_size)}
    return params, init_state(cfg)


def init_state(cfg: Config) -> L.ModelState:
    return {"layers": [expert_layer_state(cfg.num_experts) if cfg.is_moe(i)
                       else {} for i in range(cfg.num_hidden_layers)]}


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

def mla(p, u, cfg: Config):
    """Multi-head latent attention of normalised ``u`` ``(n, T, d)``,
    head-major from product to product as ``lfm2.attention`` is: the query
    and ``kv_b`` projections write ``(n, H, T, .)``, the one rotary key is
    broadcast over the head axis where it leads, ``v`` is a slice of the
    head-major ``kv``, and ``o_proj`` reads the output where it lies."""
    n, t, _ = u.shape
    h, nope, rope, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    fused = pallas_attention.engages(t, cfg.qk_head_dim, dv, u.dtype)
    w_q = p["q_proj"]
    if fused:       # the kernel applies no scale: see the module's docstring
        w_q = w_q * (1.0 / math.sqrt(cfg.qk_head_dim))
    q = _heads_of(u, w_q, h)
    c, k_pe = jnp.split(_dot(u, p["kv_a_proj"]), [cfg.kv_lora_rank], axis=-1)
    kv = _heads_of(L.rms_apply(p["kv_a_norm"], c, cfg.norm_eps),
                   p["kv_b_proj"], h)
    q = jnp.concatenate(
        [q[..., :nope],
         L.rotary_pairs(q[..., nope:], cfg.rope_theta, axis=-2)], axis=-1)
    k_pe = L.rotary_pairs(k_pe[:, None], cfg.rope_theta, axis=-2)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (n, h, t, rope))], axis=-1)
    v = kv[..., nope:]
    with jax.named_scope(STAGE_ATTENTION):
        if fused:
            out = pallas_attention.causal_gqa(q, k, v)
        else:
            out = _plain_scores(q, k, v, cfg.attn_q_block)
    return _from_heads(out, p["o_proj"])


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _mla_part(cfg):
    def part(p, x):
        with jax.named_scope(STAGE_MLA_LATENT):
            return x + mla(p["attn"],
                           L.rms_apply(p["attn_norm"], x, cfg.norm_eps), cfg)
    return part


def _moe_part(cfg):
    def part(p, state, x):
        u = L.rms_apply(p["ffn_norm"], x, cfg.norm_eps)
        y, state = moe_ffn(p["ffn"], state, u, cfg)
        with jax.named_scope(STAGE_SHARED_EXPERT):
            y = y + dense_ffn(p["ffn"]["shared"], u)
        return x + y, state
    return part


def hidden_states(params, model_state, ids, cfg: Config,
                  dtype=jnp.float32):
    """ids ``(n, T)`` -> the last layer's output ``(n, T, d)`` (before the
    final norm) and the new model state."""
    x = L.embedding_apply(params["embed"], ids, dtype=dtype)
    new_state = []
    for i, (p, s) in enumerate(zip(params["layers"], model_state["layers"])):
        x = _over_sequences(_mla_part(cfg), p, x, cfg.seq_block)
        if cfg.is_moe(i):
            # recomputed from x, all sequences together: the routed experts'
            # own forward is not needed again (their backward recomputes
            # tile by tile), and the shared expert's weight gradients are
            # one product each, not a float32 sum over sequences
            x, s = jax.checkpoint(_moe_part(cfg))(p, s, x)
        else:
            x = _over_sequences(_dense_part(cfg), p, x, cfg.seq_block)
        new_state.append(s)
    return x, {"layers": new_state}


def next_token_loss(params, model_state, ids, cfg: Config,
                    dtype=jnp.float32):
    """Mean over all tokens of the cross-entropy of position ``t``'s logits
    against token ``t + 1``: ``(loss, new_model_state)``."""
    x, new_state = hidden_states(params, model_state, ids, cfg, dtype)
    return loss_of_hidden_states(params, x, ids, cfg), new_state
