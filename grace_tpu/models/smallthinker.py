"""SmallThinker causal decoder (``PowerInfer/SmallThinker-21BA3B-Instruct``
as configured): sparse experts in every layer, windowed rotary layers beside
full-attention layers that are given no positions, a router that reads the
layer's input before attention runs, ReLU-gated experts, next-token loss.

Equations of one layer (``x``: the layer's input, the residual stream; no
bias anywhere, no norm on the query and key heads):

1. ``r = x W_r`` **from the layer's input itself, before the input norm and
   before attention** (the family's pre-attention router: the logits exist
   before attention runs, so that a deployment can fetch the chosen experts
   meanwhile). The ``num_experts_per_tok`` largest are chosen; their gates
   are the softmax over all ``num_experts`` renormalised over the chosen
   (``moe_primary_router_apply_softmax``, ``norm_topk_prob``): ``sdar.
   route``, on another input.
2. ``u = RMSNorm_in(x)``; ``q, k, v = u W_q, u W_k, u W_v``
   (``num_attention_heads`` query heads over ``num_key_value_heads``
   key/value heads of ``head_dim``). On a layer whose ``rope_layout`` is 1
   ``q`` and ``k`` are rotated by their positions (rotate-half,
   ``rope_theta``); on a layer whose entry is 0 they are not (NoPE). Scores
   ``q k^T / sqrt(head_dim)``, softmax in float32; query ``i`` reads key
   ``j`` iff ``0 <= i - j`` on a layer whose ``sliding_window_layout`` is 0
   and iff ``0 <= i - j < sliding_window_size`` where it is 1. ``h = x +
   Attn W_o``. ``lfm2.attention``, told the layer's mask and whether to
   rotate.
3. ``m = RMSNorm_post(h)``; ``y = sum_e g_e W_2,e (relu(W_1,e m) * W_3,e
   m)`` over the chosen experts held here, gates normalised over all the
   chosen; the layer's output is ``h + y``. ``lfm2``'s walk over the sorted
   assignments (``_route_and_sort`` before attention, ``held_experts`` after
   it), told the gate's activation.

After the last layer RMSNorm, then the untied output head; the loss is the
mean over all tokens of the cross-entropy of position ``t``'s logits against
token ``t + 1``.

What is shared with ``models/lfm2.py`` and ``models/sdar.py`` is imported
from them, not copied: attention's projections and two spellings of the
scores (the fused kernel where ``ops.pallas_attention.engages`` says so,
under ``CAUSAL`` or ``SlidingWindow(sliding_window_size)`` as the layer
has it; ``attn_q_block`` queries at a time over the keys they can read
everywhere else), the softmax router, the expert walk, the walk over
sequences, the head with the loss. Memory as there: every part of a layer
is recomputed in the backward pass from its input but for the kernel's
output and log-sum-exp; what the router's part hands the experts' part (the
sorted assignments: a few numbers a token and expert chosen) is kept
between them. The head walks a sequence in parts of ``head_positions``
positions and forms its gradient in that walk (``lfm2.head_loss``), so that
one part's float32 logits are what is alive, once a step (a whole sequence
of 16,384 positions over 18,992 rows would be 1.24 GB, and its gradient as
much).

Model state: per layer the expert layer's counters of ``lfm2`` without a
bias (``drawn``, ``held``, ``computed``, ``combined``, ``dropped``), float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from grace_tpu.models import layers as L
from grace_tpu.models.lfm2 import (_head_params, _over_sequences,
                                   _route_and_sort, attention,
                                   expert_layer_state, head_loss,
                                   held_experts, next_token_targets,
                                   walk_sizes)
from grace_tpu.models.sdar import route
from grace_tpu.ops import pallas_attention
from grace_tpu.telemetry.scopes import (STAGE_ATTENTION,
                                        STAGE_WINDOW_ATTENTION)

_PERIOD = (0, 1, 1, 1)


@dataclasses.dataclass(frozen=True)
class Config:
    """SmallThinker-21BA3B-Instruct as published, all of it held here,
    unless said otherwise. ``vocab_size`` is the number of rows held. The
    two layouts have an entry a layer held: ``sliding_window_layout`` 1
    where the layer reads a window and 0 where it reads the whole prefix,
    ``rope_layout`` 1 where queries and keys are rotated.

    The functions imported from ``lfm2`` are handed this ``Config`` in
    place of ``lfm2.Config`` and read their fields from it (``deepseek_v3.
    SHARED_FIELDS`` and attention's ``num_attention_heads``,
    ``num_key_value_heads``, ``head_dim``, ``rope_theta``,
    ``attn_q_block``): the dataclasses keep those names with one meaning."""
    vocab_size: int = 151936
    hidden_size: int = 2560
    sliding_window_layout: Tuple[int, ...] = _PERIOD * 13
    rope_layout: Tuple[int, ...] = _PERIOD * 13
    sliding_window_size: int = 4096
    moe_intermediate_size: int = 768          # moe_ffn_hidden_size
    num_experts: int = 64                     # moe_num_primary_experts
    num_experts_per_tok: int = 6              # moe_num_active_primary_experts
    first_expert: int = 0
    experts_held: int = 64
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1.5e6
    norm_eps: float = 1e-6
    # scaled initialisation counts the published depth, held or not
    published_layers: int = 52
    # how the work is walked, not what is computed
    seq_block: int = 1            # sequences recomputed together
    attn_q_block: int = 1024      # queries scored together (plain path)
    moe_row_block: int = 0        # rows of one tile of the expert walk; 0:
                                  # from the shapes
    head_positions: int = 4096    # positions the head scores together

    def __post_init__(self):
        if not 0 <= self.first_expert <= (self.num_experts
                                          - self.experts_held):
            raise ValueError(
                f"experts {self.first_expert}..+{self.experts_held} are not "
                f"among the router's {self.num_experts}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide over key/value heads")
        if len(self.rope_layout) != len(self.sliding_window_layout):
            raise ValueError("the two layouts have an entry a layer")
        if set(self.rope_layout + self.sliding_window_layout) - {0, 1}:
            raise ValueError("a layout's entries are 0 or 1")

    @property
    def num_hidden_layers(self) -> int:
        return len(self.sliding_window_layout)

    def mask_of(self, layer: int):
        """The layer's mask, a value of ``ops.pallas_attention``."""
        if self.sliding_window_layout[layer]:
            return pallas_attention.SlidingWindow(self.sliding_window_size)
        return pallas_attention.CAUSAL


def tiny(**kw) -> Config:
    """Test-scale config: one period (a full layer without positions, three
    windowed rotary ones), a window of 8, 8 experts, 2 a token."""
    d = dict(vocab_size=128, hidden_size=32, sliding_window_layout=_PERIOD,
             rope_layout=_PERIOD, sliding_window_size=8,
             moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
             experts_held=8, num_attention_heads=4, num_key_value_heads=2,
             head_dim=8, published_layers=4, attn_q_block=8, moe_row_block=16,
             head_positions=8)
    d.update(kw)
    return Config(**d)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def init(key: jax.Array, cfg: Config) -> Tuple[L.Params, L.ModelState]:
    """Truncated normal (std 0.02) matrices, unit norm weights, untied
    embedding and head; the embedding's rows have std 1 and the projections
    that write to the residual stream (``o_proj``, ``w2``) are scaled by ``1
    / sqrt(2 * published layers)``, which keep a position's stream its own
    token's embedding (``models/sdar.py::init`` says what happens without):
    the router reads that stream, so a position's experts follow its
    token."""
    d, hd = cfg.hidden_size, cfg.head_dim
    keys = iter(L.split_keys(key, 2 + 8 * cfg.num_hidden_layers))
    out_std = 0.02 / math.sqrt(2 * cfg.published_layers)

    def mat(*shape, std=0.02):
        return L.trunc_normal(next(keys), shape, std)

    def layer():
        e, f = cfg.experts_held, cfg.moe_intermediate_size
        attn = {"q_proj": mat(d, cfg.num_attention_heads * hd),
                "k_proj": mat(d, cfg.num_key_value_heads * hd),
                "v_proj": mat(d, cfg.num_key_value_heads * hd),
                "o_proj": mat(cfg.num_attention_heads * hd, d, std=out_std)}
        ffn = {"router": mat(d, cfg.num_experts), "w1": mat(e, d, f),
               "w3": mat(e, d, f), "w2": mat(e, f, d, std=out_std)}
        return {"attn_norm": L.rms_init(d), "attn": attn,
                "ffn_norm": L.rms_init(d), "ffn": ffn}

    params = {"embed": {"table": mat(cfg.vocab_size, d, std=1.0)},
              "layers": [layer() for _ in range(cfg.num_hidden_layers)],
              "final_norm": L.rms_init(d),
              "head": mat(d, cfg.vocab_size)}
    return params, init_state(cfg)


def init_state(cfg: Config) -> L.ModelState:
    def expert_layer():
        state = expert_layer_state(cfg.num_experts)
        del state["expert_bias"]        # the router has none
        return state

    return {"layers": [expert_layer() for _ in range(cfg.num_hidden_layers)]}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _router_part(cfg, sizes):
    """The layer's input ``x`` ``(n, T, d)``, as it is, to the sorted
    assignments ``held_experts`` takes and the layer's counters."""
    def part(p, state, x):
        return _route_and_sort(p["ffn"], state, x.reshape(-1, x.shape[-1]),
                               cfg, sizes, route)
    return part


def _attention_part(cfg, layer: int):
    windowed = bool(cfg.sliding_window_layout[layer])
    stage = STAGE_WINDOW_ATTENTION if windowed else STAGE_ATTENTION

    def part(p, x):
        with jax.named_scope(stage):
            return x + attention(
                p["attn"], L.rms_apply(p["attn_norm"], x, cfg.norm_eps), cfg,
                cfg.mask_of(layer), rotate=bool(cfg.rope_layout[layer]))
    return part


def _experts_part(cfg, sizes):
    def part(p, h, sorted_rows):
        m = L.rms_apply(p["ffn_norm"], h, cfg.norm_eps)
        y = held_experts(sizes, {k: p["ffn"][k] for k in ("w1", "w3", "w2")},
                         m.reshape(-1, m.shape[-1]), *sorted_rows)
        return h + y.reshape(h.shape)
    return part


def hidden_states(params, model_state, ids, cfg: Config, dtype=jnp.float32):
    """ids ``(n, T)`` -> the last layer's output ``(n, T, d)`` (before the
    final norm) and the new model state."""
    x = L.embedding_apply(params["embed"], ids, dtype=dtype)
    sizes = walk_sizes(cfg, ids.size, gate="relu")
    new_state = []
    for i, (p, s) in enumerate(zip(params["layers"], model_state["layers"])):
        # each part recomputed from its input: the router from the layer's
        # input before attention runs, all sequences together as the
        # experts'; what it hands them is kept, not sorted again
        sorted_rows, s = jax.checkpoint(_router_part(cfg, sizes))(p, s, x)
        x = _over_sequences(_attention_part(cfg, i), p, x, cfg.seq_block)
        x = jax.checkpoint(_experts_part(cfg, sizes))(p, x, sorted_rows)
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
    loss = head_loss(_head_params(params), x, *next_token_targets(ids),
                     1.0 / (n * (t - 1)), cfg.seq_block * part, cfg.norm_eps)
    return loss, new_state
