"""SDAR block-diffusion decoder (``model_type`` ``sdar_moe``, as
``JetLM/SDAR-30B-A3B-Chat`` is configured): a Qwen3-shaped sparse-expert
decoder trained to fill in masked tokens a block at a time, not to predict
the next one.

Equations, with ``u = RMSNorm(x)`` (learned weight; no bias anywhere):

* layer: ``h = x + Attn(RMSNorm_in(x))``, ``y = h + MoE(RMSNorm_post(h))``;
  after the last layer RMSNorm, then the untied output head.
* ``Attn``: grouped-query attention (``num_attention_heads`` query heads
  over ``num_key_value_heads`` key/value heads of ``head_dim``), RMSNorm
  over each query and key head, rotary positions over the whole head
  (rotate-half) **by the token's position id**, ``softmax(q k^T /
  sqrt(head_dim) + mask) v`` in float32, ``W_o``. ``lfm2.attention``, told
  the mask and the positions.
* ``MoE``: ``p = softmax(W_r u)`` over all ``num_experts`` in float32; the
  ``num_experts_per_tok`` largest; gates ``p_e`` over the sum of the chosen
  (``norm_topk_prob``); ``y = sum_e g_e W_2,e (silu(W_1,e u) * W_3,e u)``;
  no shared expert, no bias on the scores. ``lfm2.moe_ffn`` with this
  module's :func:`route`: told which experts it holds, it routes over all
  of them and computes its own experts' part.
* **block-diffusion training** of clean tokens ``x_0 .. x_{L-1}`` in blocks
  of ``block_length`` (``b(i) = i // block_length``). A sequence and block
  draws ``t_b ~ U(noise_eps, 1)``; token ``i`` is masked with probability
  ``t_b(i)`` (``m_i``), and the noised copy holds ``mask_token_id`` there
  and ``x_i`` elsewhere. The model's input is the noised copy and the clean
  copy side by side, ``2 L`` positions with position ids ``(0 .. L - 1, 0 ..
  L - 1)``, under ``ops.pallas_attention.BlockDiffusion(L, block_length)``:
  a noised query reads the noised keys of its own block and the clean keys
  of the blocks before it, a clean query the clean keys up to its own
  block's end. Only the noised copy's ``L`` positions go through the final
  norm and the head, and the loss is ``1 / (n L) * sum_i m_i / t_b(i) *
  CE(logits_i, x_i)``: the token at its own position, no shift.

The draws of a step come from ``fold_in(key of the sequence, step)``, the
sequence's key a row of the batch and ``step`` a counter in the model state
the step carries: fresh noise every step, the same at the same step from
the same seed. They are made on the device, inside the step, under
``grace/diffusion_noise`` (:func:`draw_noise`).

What is shared with ``models/lfm2.py`` is imported from it, not copied:
attention's projections, norms and two spellings of the scores (the fused
kernel where ``ops.pallas_attention.engages`` says so, ``attn_q_block``
queries at a time everywhere else), the expert layer's walk, the walk over
sequences, the head with the loss (``head_loss``, handed the noise's
weights and the divisor ``n L``). Under this mask the fused path hands the
kernel all ``2 L`` queries over the clean copy's ``L`` keys alone, of which
every query reads a prefix (one comparison a pair), scores a noised query's
own block of ``block_length`` noised keys beside it and merges the two by
log-sum-exp (``ops/pallas_attention.py``); the plain path evaluates the
whole mask pair by pair. Memory as there: every part of a layer is
recomputed in the backward pass from its input but for the merged output
and the joint log-sum-exp, which the kernel's backward reads; the head
makes its logits once.

Model state: ``step`` (the steps taken, the noise's counter), ``masked``
(positions the last step scored: the masked tokens of all sequences) and,
per layer, the expert layer's counters of ``lfm2`` without a bias
(``drawn``, ``held``, ``computed``, ``combined``, ``dropped``); all float32,
so that the step's mean over replicas keeps their type.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from grace_tpu.models import layers as L
from grace_tpu.models.lfm2 import (_chosen_scores, _dot, _head_params,
                                   _over_sequences, attention,
                                   expert_layer_state, head_loss, moe_ffn)
from grace_tpu.ops import pallas_attention
from grace_tpu.telemetry.scopes import (STAGE_ATTENTION,
                                        STAGE_DIFFUSION_NOISE)


@dataclasses.dataclass(frozen=True)
class Config:
    """SDAR-30B-A3B-Chat as published, all of it held here, unless said
    otherwise. ``vocab_size`` is the number of rows held; its last row is
    the mask token and tokens are the rows before it.

    The functions imported from ``lfm2`` are handed this ``Config`` in
    place of ``lfm2.Config`` and read their fields from it (``deepseek_v3.
    SHARED_FIELDS`` and attention's ``num_attention_heads``,
    ``num_key_value_heads``, ``head_dim``, ``rope_theta``,
    ``attn_q_block``): the dataclasses keep those names with one meaning."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    first_expert: int = 0
    experts_held: int = 128
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    # block-diffusion training (not in the published config: the caller's)
    block_length: int = 4
    noise_eps: float = 1e-3       # the least noise level a block draws
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
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide over key/value heads")
        if not 0.0 < self.noise_eps < 1.0:
            raise ValueError("a noise level lies between 0 and 1")

    @property
    def mask_token_id(self) -> int:
        return self.vocab_size - 1


def tiny(**kw) -> Config:
    """Test-scale config: four layers, 8 experts, 2 a token, blocks of 4."""
    d = dict(vocab_size=128, hidden_size=32, num_hidden_layers=4,
             moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
             experts_held=8, num_attention_heads=4, num_key_value_heads=2,
             head_dim=8, attn_q_block=8, moe_row_block=16)
    d.update(kw)
    return Config(**d)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

QK_NORM_INIT = 2.0      # the query and key heads' norm weights at the start


def init(key: jax.Array, cfg: Config) -> Tuple[L.Params, L.ModelState]:
    """Truncated normal (std 0.02) matrices, unit norm weights, untied
    embedding and head; the embedding's rows have std 1, the projections
    that write to the residual stream (``o_proj``, ``w2``) are scaled by
    ``1 / sqrt(2 * layers)``, the mask token's row is the mean of the rows
    before it, and the query and key heads' norm weights are
    ``QK_NORM_INIT``. The four keep a position's stream its own: at std
    0.02 throughout, untrained attention's average over thousands of keys
    outweighs the embedding, every position comes to carry the same
    vector, and the router sends all of them to the same experts; at unit
    head norms the masked positions, which share one embedding, still read
    one average and go to the same experts together."""
    d, hd = cfg.hidden_size, cfg.head_dim
    keys = iter(L.split_keys(key, 2 + 8 * cfg.num_hidden_layers))
    out_std = 0.02 / math.sqrt(2 * cfg.num_hidden_layers)

    def mat(*shape, std=0.02):
        return L.trunc_normal(next(keys), shape, std)

    def layer():
        e, f = cfg.experts_held, cfg.moe_intermediate_size
        attn = {"q_proj": mat(d, cfg.num_attention_heads * hd),
                "k_proj": mat(d, cfg.num_key_value_heads * hd),
                "v_proj": mat(d, cfg.num_key_value_heads * hd),
                "o_proj": mat(cfg.num_attention_heads * hd, d, std=out_std),
                "q_norm": {"scale": jnp.full((hd,), QK_NORM_INIT)},
                "k_norm": {"scale": jnp.full((hd,), QK_NORM_INIT)}}
        ffn = {"router": mat(d, cfg.num_experts), "w1": mat(e, d, f),
               "w3": mat(e, d, f), "w2": mat(e, f, d, std=out_std)}
        return {"attn_norm": L.rms_init(d), "attn": attn,
                "ffn_norm": L.rms_init(d), "ffn": ffn}

    table = mat(cfg.vocab_size, d, std=1.0)
    table = table.at[cfg.mask_token_id].set(
        jnp.mean(table[:cfg.mask_token_id], axis=0))
    params = {"embed": {"table": table},
              "layers": [layer() for _ in range(cfg.num_hidden_layers)],
              "final_norm": L.rms_init(d),
              "head": mat(d, cfg.vocab_size)}
    return params, init_state(cfg)


def init_state(cfg: Config) -> L.ModelState:
    def expert_layer():
        state = expert_layer_state(cfg.num_experts)
        del state["expert_bias"]        # Qwen3's router has none
        return state

    return {"step": jnp.zeros((), jnp.float32),
            "masked": jnp.zeros((), jnp.float32),
            "layers": [expert_layer() for _ in range(cfg.num_hidden_layers)]}


# ---------------------------------------------------------------------------
# the step's noise
# ---------------------------------------------------------------------------

def draw_noise(key: jax.Array, step, ids: jax.Array, block_length: int,
               noise_eps: float, mask_token_id: int):
    """One sequence's draws at ``step``: ``(noised, weights)`` of the clean
    tokens ``ids`` ``(L,)``. ``key``: the sequence's own (raw key data, as
    a batch carries it). Each block draws its noise level ``t ~
    U(noise_eps, 1)``, each token is masked with its block's ``t``;
    ``noised`` holds ``mask_token_id`` at the masked positions, ``weights``
    ``1 / t`` there and zero elsewhere (float32)."""
    (length,) = ids.shape
    k = jax.random.fold_in(jax.random.wrap_key_data(key),
                           jnp.asarray(step, jnp.int32))
    k_level, k_mask = jax.random.split(k)
    t = jnp.repeat(jax.random.uniform(
        k_level, (length // block_length,), jnp.float32, noise_eps, 1.0),
        block_length)
    masked = jax.random.uniform(k_mask, (length,), jnp.float32) < t
    return (jnp.where(masked, jnp.asarray(mask_token_id, ids.dtype), ids),
            jnp.where(masked, 1.0 / t, 0.0))


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def route(p, bias, x, cfg: Config):
    """``(experts, gates)`` of every token of ``x`` ``(N, d)``, as
    ``lfm2.route`` gives them: a softmax over all ``num_experts`` in
    float32, the ``num_experts_per_tok`` largest, their probabilities over
    the sum of the chosen. ``bias`` is ``lfm2``'s signature's: there is
    none here."""
    del bias
    s = jax.nn.softmax(_dot(x, p["router"]).astype(jnp.float32), axis=-1)
    _, experts = lax.top_k(s, cfg.num_experts_per_tok)
    g = _chosen_scores(s, experts, cfg.num_experts)
    return experts, g / jnp.sum(g, axis=-1, keepdims=True)


def _attention_part(cfg, mask, positions):
    def part(p, x):
        with jax.named_scope(STAGE_ATTENTION):
            return x + attention(
                p["attn"], L.rms_apply(p["attn_norm"], x, cfg.norm_eps), cfg,
                mask, positions)
    return part


def _moe_part(cfg):
    def part(p, state, x):
        u = L.rms_apply(p["ffn_norm"], x, cfg.norm_eps)
        y, state = moe_ffn(p["ffn"], state, u, cfg, route)
        return x + y, state
    return part


def hidden_states(params, layer_states, ids, cfg: Config, mask, positions,
                  dtype=jnp.float32):
    """ids ``(n, T)`` -> the last layer's output ``(n, T, d)`` (before the
    final norm) under ``mask`` and ``positions`` ``(T,)``, and the layers'
    new states."""
    x = L.embedding_apply(params["embed"], ids, dtype=dtype)
    new_states = []
    for p, s in zip(params["layers"], layer_states):
        x = _over_sequences(_attention_part(cfg, mask, positions), p, x,
                            cfg.seq_block)
        # recomputed from x, all sequences together (as lfm2's)
        x, s = jax.checkpoint(_moe_part(cfg))(p, s, x)
        new_states.append(s)
    return x, new_states


def block_diffusion_loss(params, model_state, batch, cfg: Config,
                         dtype=jnp.float32):
    """The block-diffusion loss of the module's docstring on ``batch`` =
    ``{"ids": (n, L) clean tokens, "key": (n, 2) uint32, a key's data a
    sequence}`` at the step the model state counts: ``(loss,
    new_model_state)``."""
    ids, keys = batch["ids"], batch["key"]
    n, length = ids.shape
    if length % cfg.block_length:
        raise ValueError(f"{length} tokens are not whole blocks of "
                         f"{cfg.block_length}")
    with jax.named_scope(STAGE_DIFFUSION_NOISE):
        noised, weights = jax.vmap(
            lambda k, x: draw_noise(k, model_state["step"], x,
                                    cfg.block_length, cfg.noise_eps,
                                    cfg.mask_token_id))(keys, ids)
        both = jnp.concatenate([noised, ids], axis=1)
        positions = np.tile(np.arange(length), 2)
        scored = jnp.sum(weights > 0, dtype=jnp.float32)
    mask = pallas_attention.BlockDiffusion(length, cfg.block_length)
    x, layer_states = hidden_states(params, model_state["layers"], both, cfg,
                                    mask, positions, dtype)
    loss = head_loss(_head_params(params), x[:, :length], ids, weights,
                     1.0 / (n * length), cfg.seq_block * length,
                     cfg.norm_eps)
    return loss, {
        "step": model_state["step"] + 1.0, "masked": scored,
        "layers": layer_states}
