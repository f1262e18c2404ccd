"""LFM2-MoE causal decoder (LiquidAI ``lfm2_moe``): gated short convolutions
with a full-attention layer among every few, a dense gated feed-forward in
the leading layers and sparse experts after them, next-token loss.

Equations (``u = RMSNorm(x)`` with a learned weight; no bias anywhere):

* layer: ``h = x + Op(RMSNorm_op(x))``, ``y = h + FFN(RMSNorm_ffn(h))``;
  after the last layer RMSNorm, then the output head.
* ``conv`` operator: ``(B, C, X) = split3(W_in u)``, ``z = B * X``,
  ``c_t = sum_j w_j * z_{t-(L-1)+j}`` (depth-wise, causal, kernel ``L =
  conv_L_cache``, zeros before the start of each sequence),
  ``Op = W_out (C * c)``.
* ``full_attention`` operator: grouped-query attention, RMSNorm over each
  query and key head, rotary positions over the whole head (rotate-half),
  causal softmax of ``q k^T / sqrt(head_dim)`` in float32. Two spellings
  of the scores, chosen from what the trace can see (``ops.
  pallas_attention.engages``: the platform, the sequence length, the head
  size and the dtype; no field of ``Config`` and no argument): on a TPU, at
  shapes the fused kernel takes, a whole sequence goes through it, tile by
  tile in VMEM with an online softmax and the kernel's own backward pass,
  and no ``(heads, queries, keys)`` tensor reaches HBM; everywhere else
  ``attn_q_block`` queries are scored at a time against the keys up to
  the block's end, each block recomputed in the backward pass. ``q``,
  ``k``, ``v`` and the output are head-major ``(n, H, T, D)`` from the
  projections' products to the output projection's, as the kernel reads and
  writes them: the weights are viewed ``(d, H, D)`` and ``(H, D, d)`` (their
  shapes as parameters do not change), norms and rotation run on that
  layout, and no axis is moved on the kernel's path (PERF.md §6, PR 45).
* dense feed-forward: ``W_2 (silu(W_1 h) * W_3 h)``.
* expert feed-forward: ``s = sigmoid(W_r h)``; the ``num_experts_per_tok``
  experts are the top of ``s + b`` (``b`` the expert bias, model state, not
  trained by the gradient); their weights are ``s_i`` (without ``b``) over
  ``sum s_i + 1e-6`` times ``routed_scaling_factor``; ``y = sum_i g_i
  W_2,i (silu(W_1,i h) * W_3,i h)``.

**The expert layer is told which experts it holds** (``first_expert``,
``experts_held``): it routes over all ``num_experts``, and computes the
part of the result its own experts give, over the assignments sorted by
expert. No assignment is dropped: the walk is bounded by the worst case
(every one of a token's ``k`` assignments held here) and runs over the
tiles in use only, so the work follows the load and not the bound. A tile
is ``moe_row_block`` sorted rows, or a quarter of a balanced router's load
on one expert; each held expert's rows start on a tile's boundary, so a
tile belongs to one expert: it gathers its rows, multiplies them with that
expert's three matrices (plain products, under their stage's name) and
writes the weighted results where they lie in sorted order, and backwards
adds its weight gradients to that expert's float32 slices in place. What
is multiplied beyond the rows held is less than a tile an expert. With all
experts held the layer is the whole one; with a share it is what that chip
computes before an exchange this file does not have.

How rows and gates travel between token order and sorted order: by sorts,
gathers of whole rows and contiguous writes, never one index at a time. On
the chip a scatter-add takes 0.25 us a row of 2,048 numbers, a gather of
such rows 0.024 us a row, and a gather of single numbers 7.6 ns a number,
ten times what a sort of as many keys takes (PERF.md, PRs 37 and 39). So
the gates come sorted out of the one stable sort that orders the slots by
held expert, and their gradient goes back through a sort by the slots; a
tile's results (and backwards its rows' ``dx``) go into a buffer in sorted
order that nothing fills; and a second pass sums that buffer by token: the
held rows sorted back by token, windows of tokens one behind another,
chunks of held rows laid over them as tiles are over experts, a chunk
gathering its rows and summing them by token with one product of ones and
zeros, as many chunks as hold a row (and one a window at least, so that
every token is written). Forward and backward share that pass: it is one
linear map, and its transpose is the walk's gather of a row's token.

Memory: every part of a layer (operator, feed-forward) is recomputed in
the backward pass from its input, the dense parts ``seq_block`` sequences
at a time, so the step holds two activations a layer and one block's
intermediates; and, where attention took the fused kernel, the kernel's
output and log-sum-exp of every sequence, which its backward pass reads in
place of a second run of the forward kernel. The head with the loss
(:func:`head_loss`) is the last thing the forward pass does and the first
the backward pass does, so nothing of it is kept or made again: it forms
its gradient in the walk that makes the logits, ``seq_block`` sequences'
float32 logits alive at a time, once a step. Parameters are cast to the
activation dtype inside a block, so a weight's gradient is summed over the
blocks in float32.

Model state carries, per expert layer, the expert bias and five counters
of the last step (float32, so that the step's mean over replicas keeps
their type): ``drawn`` (assignments each of the ``num_experts`` experts
drew), ``held`` (assignments to held experts that were computed),
``computed`` (rows multiplied for them: the tiles in use, padding
included), ``combined`` (rows the second pass gathered and summed by
token: its chunks in use, padding included, forward only) and ``dropped``
(assignments to held experts that were not computed: always 0). A state
made without one of the counters comes back without it.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from grace_tpu.models import layers as L
from grace_tpu.ops import pallas_attention
from grace_tpu.telemetry.scopes import (STAGE_ATTENTION, STAGE_DENSE_FFN,
                                        STAGE_LM_HEAD, STAGE_MOE_COMBINE,
                                        STAGE_MOE_DISPATCH,
                                        STAGE_MOE_EXPERTS, STAGE_MOE_ROUTER,
                                        STAGE_SHORT_CONV)

_PERIOD = ("full_attention", "conv", "conv", "conv")


@dataclasses.dataclass(frozen=True)
class Config:
    """LFM2-24B-A2B as published, all of it held here, unless said
    otherwise. ``vocab_size`` is the number of rows held."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = ("conv", "conv") + _PERIOD * 9 + (
        "full_attention", "conv")
    num_dense_layers: int = 2
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_experts: int = 64
    num_experts_per_tok: int = 4
    first_expert: int = 0
    experts_held: int = 64
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    conv_L_cache: int = 3
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    routed_scaling_factor: float = 1.0
    route_eps: float = 1e-6       # added to the sum the gates are divided by
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
        unknown = set(self.layer_types) - {"conv", "full_attention"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")

    def is_moe(self, layer: int) -> bool:
        return layer >= self.num_dense_layers


def tiny(**kw) -> Config:
    """Test-scale config: one dense layer and a period, 8 experts."""
    d = dict(vocab_size=128, hidden_size=32, layer_types=("conv",) + _PERIOD,
             num_dense_layers=1, intermediate_size=64,
             moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
             experts_held=8, num_attention_heads=4, num_key_value_heads=2,
             head_dim=8, attn_q_block=8, moe_row_block=16)
    d.update(kw)
    return Config(**d)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def init(key: jax.Array, cfg: Config) -> Tuple[L.Params, L.ModelState]:
    """Truncated normal (std 0.02) matrices, unit norm weights, a
    convolution kernel uniform in +-1/sqrt(L), untied embedding and head,
    expert bias zero."""
    d, hd = cfg.hidden_size, cfg.head_dim
    keys = iter(L.split_keys(key, 2 + 8 * len(cfg.layer_types)))

    def mat(*shape):
        return L.trunc_normal(next(keys), shape)

    def layer(i, kind):
        if kind == "conv":
            bound = 1.0 / math.sqrt(cfg.conv_L_cache)
            op = {"in_proj": mat(d, 3 * d),
                  "kernel": jax.random.uniform(
                      next(keys), (cfg.conv_L_cache, d), jnp.float32,
                      -bound, bound),
                  "out_proj": mat(d, d)}
        else:
            op = {"q_proj": mat(d, cfg.num_attention_heads * hd),
                  "k_proj": mat(d, cfg.num_key_value_heads * hd),
                  "v_proj": mat(d, cfg.num_key_value_heads * hd),
                  "o_proj": mat(cfg.num_attention_heads * hd, d),
                  "q_norm": L.rms_init(hd), "k_norm": L.rms_init(hd)}
        if cfg.is_moe(i):
            e, f = cfg.experts_held, cfg.moe_intermediate_size
            ffn = {"router": mat(d, cfg.num_experts), "w1": mat(e, d, f),
                   "w3": mat(e, d, f), "w2": mat(e, f, d)}
        else:
            f = cfg.intermediate_size
            ffn = {"w1": mat(d, f), "w3": mat(d, f), "w2": mat(f, d)}
        return {"op_norm": L.rms_init(d), "op": op,
                "ffn_norm": L.rms_init(d), "ffn": ffn}

    params = {"embed": L.embedding_init(next(keys), cfg.vocab_size, d),
              "layers": [layer(i, kind)
                         for i, kind in enumerate(cfg.layer_types)],
              "final_norm": L.rms_init(d),
              "head": mat(d, cfg.vocab_size)}
    return params, init_state(cfg)


def expert_layer_state(num_experts: int) -> L.ModelState:
    """An expert layer's model state: the router's bias and the counters
    of the last step, all zero."""
    return {"expert_bias": jnp.zeros((num_experts,), jnp.float32),
            "drawn": jnp.zeros((num_experts,), jnp.float32),
            "held": jnp.zeros((), jnp.float32),
            "computed": jnp.zeros((), jnp.float32),
            "combined": jnp.zeros((), jnp.float32),
            "dropped": jnp.zeros((), jnp.float32)}


def init_state(cfg: Config) -> L.ModelState:
    return {"layers": [expert_layer_state(cfg.num_experts) if cfg.is_moe(i)
                       else {} for i in range(len(cfg.layer_types))]}


# ---------------------------------------------------------------------------
# walking the batch in blocks
# ---------------------------------------------------------------------------

def _over_sequences(fn, p, x, block: int):
    """``fn(p, x_block)`` over blocks of ``block`` sequences, one after
    another, each recomputed from its input in the backward pass but for
    what the fused attention kernel names (``pallas_attention.
    RESIDUAL_NAME``: its output and log-sum-exp, which only the kernel can
    make again; a part without the kernel holds no such name and keeps
    nothing). ``x`` and what ``fn`` returns are trees whose leaves lead with
    the sequences. For the layers' parts: the head, which nothing stands
    behind, walks its parts itself (:func:`head_loss`)."""
    fn = jax.checkpoint(fn, policy=jax.checkpoint_policies.
                        save_only_these_names(pallas_attention.RESIDUAL_NAME))
    n = jax.tree_util.tree_leaves(x)[0].shape[0]
    if block >= n:
        return fn(p, x)
    if n % block:
        raise ValueError(f"{n} sequences do not divide into blocks of {block}")
    out = lax.map(lambda xb: fn(p, xb), jax.tree_util.tree_map(
        lambda a: a.reshape(n // block, block, *a.shape[1:]), x))
    return jax.tree_util.tree_map(
        lambda y: y.reshape(n, *y.shape[2:]), out)


def _dot(x, w):
    return x @ w.astype(x.dtype)


# ---------------------------------------------------------------------------
# operators
# ---------------------------------------------------------------------------

def short_conv(p, u, cfg: Config):
    """The gated short convolution of normalised ``u``: ``(n, T, d)``."""
    b, c, x = jnp.split(_dot(u, p["in_proj"]), 3, axis=-1)
    z = b * x
    taps = cfg.conv_L_cache
    t = z.shape[1]
    zp = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    kernel = p["kernel"].astype(z.dtype)
    conv = sum(kernel[j] * zp[:, j:j + t] for j in range(taps))
    return _dot(c * conv, p["out_proj"])


def _scores_block(q, k, v, start, mask=pallas_attention.CAUSAL, first=0):
    """Attention under ``mask`` of one block of queries, at positions
    ``start`` on, over the keys given, those at positions ``first`` on
    (under the causal mask the sequence's first up to the block's end; under
    a window those the block's first query reads, on). ``q``: ``(n, Q, Hkv,
    G, D)``; ``k``, ``v``: ``(n, K, Hkv, D)``."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("nqhgd,nkhd->nhgqk", q, k).astype(jnp.float32) * scale
    qpos = start + jnp.arange(q.shape[1])
    allowed = mask.allowed(qpos[:, None],
                           jnp.arange(first, first + k.shape[1])[None, :])
    s = jnp.where(allowed, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("nhgqk,nkhd->nqhgd", a, v)


def _scores_in_blocks(q, k, v, q_block: int, mask=pallas_attention.CAUSAL):
    """Attention under ``mask`` (``ops.pallas_attention``'s: the causal one,
    a sliding window, or block diffusion's) ``q_block`` queries at a time
    over the keys the block can read, each block's ``(heads, queries,
    keys)`` scores made, normalised and multiplied into the values by XLA:
    the plain spelling, and what the kernel is tested against. ``q``: ``(n,
    T, Hq, D)``; ``k``: ``(n, T, Hkv, D)``; ``v``: ``(n, T, Hkv, Dv)``, and
    so the result's heads."""
    n, t, hq, hd = q.shape
    hkv = k.shape[2]
    q = q.reshape(n, t, hkv, hq // hkv, hd)
    block = jax.checkpoint(_scores_block, static_argnums=(3, 4, 5))
    out = []
    for s in range(0, t, q_block):
        first, stop = mask.first_key(s), mask.keys_read(s + q_block, t)
        out.append(block(q[:, s:s + q_block], k[:, first:stop],
                         v[:, first:stop], s, mask, first))
    return jnp.concatenate(out, axis=1).reshape(n, t, hq, v.shape[-1])


def _heads_of(u, w, heads: int):
    """``u`` ``(n, T, d)`` through ``w`` ``(d, heads * D)``, head-major:
    ``(n, heads, T, D)``, the product's own output (the weight is viewed
    ``(d, heads, D)``, which moves nothing), so that nothing stands between
    it and the kernel that reads heads first."""
    w = w.astype(u.dtype).reshape(w.shape[0], heads, -1)
    return jnp.einsum("ntd,dhk->nhtk", u, w)


def _from_heads(out, w):
    """Head-major ``out`` ``(n, H, T, Dv)`` through ``w`` ``(H * Dv, d)``,
    read where the kernel left it: ``(n, T, d)``."""
    h, dv = out.shape[1], out.shape[3]
    return jnp.einsum("nhtk,hkd->ntd", out,
                      w.astype(out.dtype).reshape(h, dv, -1))


def _plain_scores(q, k, v, q_block: int, mask=pallas_attention.CAUSAL):
    """:func:`_scores_in_blocks` of head-major ``q``, ``k``, ``v``, head-major:
    the plain route keeps its ``(n, T, H, D)`` spelling, and the axes are
    swapped for it here, off the kernel's path."""
    out = _scores_in_blocks(*(jnp.swapaxes(a, 1, 2) for a in (q, k, v)),
                            q_block, mask)
    return jnp.swapaxes(out, 1, 2)


def attention(p, u, cfg: Config, mask=pallas_attention.CAUSAL,
              positions=None, rotate: bool = True, rotary_dim=None,
              gated: bool = False):
    """Grouped-query self-attention of normalised ``u`` under ``mask``
    (causal unless told otherwise), rotated by ``positions`` (``0 .. T -
    1`` unless given) unless ``rotate`` is false (a layer without
    positions), over the whole head or its first ``rotary_dim`` numbers;
    the query and key heads are normalised where ``p`` holds ``q_norm``
    and ``k_norm``. ``gated``: the query projection is twice as wide and
    carries, a head, its queries and behind them an output gate, and the
    heads' output is multiplied by the gate's sigmoid (float32) before
    ``o_proj`` reads it. Head-major from product to product: the
    three projections write ``(n, H, T, D)``, norms and rotation are applied
    there, and ``o_proj`` reads the output where it lies, so that no axis is
    moved between a product and the kernel. On a TPU, a sequence of whole
    tiles at a head size the fused kernel takes goes through it
    (``ops.pallas_attention``: no score tensor in HBM, its own backward
    pass); everything else through :func:`_scores_in_blocks`."""
    t = u.shape[1]
    hq, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    fused = pallas_attention.engages(t, hd, hd, u.dtype, mask=mask)
    # The kernel applies no scale. 1/sqrt(64) is a power of two, so q times
    # it is exact in q's dtype; 1/sqrt(128) is none, and goes into the
    # float32 weight of the queries' norm or, without a norm, into the
    # float32 weights of the queries' projection as they are cast, so that
    # q is rounded as often as the plain spelling's.
    scale = 1.0 / math.sqrt(hd)
    exact = math.frexp(scale)[0] == 0.5
    q_norm, q_proj = p.get("q_norm"), p["q_proj"]
    if fused and not exact:
        if q_norm is None:
            q_proj = q_proj * scale
        else:
            q_norm = {"scale": q_norm["scale"] * scale}
    q = _heads_of(u, q_proj, hq)
    if gated:
        q, gate = jnp.split(q, 2, axis=-1)
    k = _heads_of(u, p["k_proj"], hkv)
    v = _heads_of(u, p["v_proj"], hkv)

    def placed(heads, norm):
        if norm is not None:
            heads = L.rms_apply(norm, heads, cfg.norm_eps)
        if not rotate:
            return heads
        return L.rotary(heads, cfg.rope_theta, positions, axis=-2,
                        rotary_dim=rotary_dim)

    q, k = placed(q, q_norm), placed(k, p.get("k_norm"))
    if fused:
        if exact:
            q = q * jnp.asarray(scale, q.dtype)
        if mask == pallas_attention.CAUSAL:
            out = pallas_attention.causal_gqa(q, k, v)
        else:
            out = pallas_attention.masked_gqa(q, k, v, mask)
    else:
        out = _plain_scores(q, k, v, cfg.attn_q_block, mask)
    if gated:
        out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
    return _from_heads(out, p["o_proj"])


def dense_ffn(p, u):
    return _dot(jax.nn.silu(_dot(u, p["w1"])) * _dot(u, p["w3"]), p["w2"])


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def _chosen_scores(s, experts, num_experts: int):
    """The scores ``s`` ``(N, E)`` of the ``experts`` ``(N, k)`` chosen, by
    a compare and a sum (a gather of ``N * k`` single numbers runs one at a
    time on the chip)."""
    chosen = experts[..., None] == jnp.arange(num_experts)
    return jnp.sum(jnp.where(chosen, s[:, None, :], 0.0), axis=-1)


def route(p, bias, x, cfg: Config):
    """``(experts, gates)`` of every token of ``x`` ``(N, d)``: ``(N, k)``
    expert ids among all ``num_experts`` and their float32 weights."""
    s = jax.nn.sigmoid(_dot(x, p["router"]).astype(jnp.float32))
    _, experts = lax.top_k(s + bias, cfg.num_experts_per_tok)
    g = _chosen_scores(s, experts, cfg.num_experts)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + cfg.route_eps)
    return experts, g * cfg.routed_scaling_factor


def _count(ids, bins: int):
    """How often each of ``bins`` values occurs in ``ids`` (a compare and a
    sum: a scatter of ones runs one add at a time on the chip)."""
    return jnp.sum(ids[:, None] == jnp.arange(bins)[None, :], axis=0,
                   dtype=jnp.int32)


@jax.custom_vjp
def _sort_by_group(group, gates):
    """``(slot_of_row, row_gates)``: the slots ``0 .. N*k`` in the order of
    their ``group`` (slot order within a group) and their gates in that
    order, out of one stable sort on one key: the gates ride the sort (a
    gather of ``N*k`` single numbers through the permutation runs one
    number at a time on the chip, ten times what the sort takes). Backwards
    the sorted gates' gradient is sorted by ``slot_of_row``, a permutation,
    so that sort is its inverse."""
    slots = jnp.arange(group.shape[0], dtype=jnp.int32)
    _, slot_of_row, row_gates = lax.sort((group, slots, gates), num_keys=1,
                                         is_stable=True)
    return slot_of_row, row_gates


def _sort_by_group_fwd(group, gates):
    slot_of_row, row_gates = _sort_by_group(group, gates)
    return (slot_of_row, row_gates), slot_of_row


def _sort_by_group_bwd(slot_of_row, cotangents):
    _, g = lax.sort((slot_of_row, cotangents[1]), num_keys=1,
                    is_stable=False)
    return None, g


_sort_by_group.defvjp(_sort_by_group_fwd, _sort_by_group_bwd)


def _tile_rows(cfg: Config, tokens: int) -> int:
    """Rows of one tile of the walk over the sorted assignments:
    ``moe_row_block`` as given, or a quarter of the rows a balanced router
    sends one expert (``tokens * num_experts_per_tok / num_experts``), in
    whole multiples of 128. A held expert's rows start on a tile's
    boundary, so each pads half a tile on average: an eighth of its
    balanced load at this default. Smaller tiles pad less and pay more for
    what every tile costs whatever its rows, the float32 sum into its
    expert's slice of each weight gradient (PERF.md, PR 37)."""
    if cfg.moe_row_block:
        return cfg.moe_row_block
    balanced = tokens * cfg.num_experts_per_tok // cfg.num_experts
    return max(128, balanced // 4 // 128 * 128)


def _window_tokens(cfg: Config, tokens: int, tile: int) -> int:
    """Tokens of one window of the pass that sums the sorted rows by token
    (:func:`_sum_by_token`), which walks windows of tokens one behind
    another, a chunk of ``tile`` held rows at a time: the tokens whose held
    rows a balanced router makes half a chunk of (in whole multiples of
    128, as the tile), so one chunk a window until its load doubles. A
    chunk costs 8 us on the chip before its first row and 0.024 us a row,
    and one product of ``window x tile`` ones and zeros with its rows:
    fewer and fuller chunks win as long as that product stays small
    (PERF.md, PR 39)."""
    here = cfg.num_experts_per_tok * cfg.experts_held
    window = max(1, tile * cfg.num_experts // (2 * here))
    if window > 128:
        window = window // 128 * 128
    return min(tokens, window)


def _lay_tiles(counts, tile: int, n_tiles: int, least: int = 0):
    """Tiles of ``tile`` rows over groups of ``counts`` rows that lie one
    behind another, each group's first tile at its first row and at least
    ``least`` tiles a group: per tile (of ``n_tiles``, the bound) its
    group, its first row, how many of its rows are the group's and whether
    it is the group's first; and how many tiles are in use. By a compare
    and a sum, as in :func:`route`."""
    groups = counts.shape[0]
    ends = jnp.cumsum(counts)
    tiles_of = jnp.maximum(-(-counts // tile), least)
    tile_ends = jnp.cumsum(tiles_of)
    t = jnp.arange(n_tiles)
    group = jnp.minimum(
        jnp.sum(t[:, None] >= tile_ends[None, :], axis=1), groups - 1)
    mine = group[:, None] == jnp.arange(groups)[None, :]

    def of_group(v):
        return jnp.sum(jnp.where(mine, v[None, :], 0), axis=1)

    opens = t - of_group(tile_ends - tiles_of)      # the tile's place in it
    first = of_group(ends - counts) + opens * tile
    n_valid = jnp.clip(of_group(ends) - first, 0, tile)
    return (group, first, n_valid, opens == 0), tile_ends[-1]


def _cast(w, dtype):
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), w)


def _scratch(shape, dtype):
    """A buffer of which every element that is read has been written
    before: on the chip it is not filled."""
    return lax.empty(shape, dtype)


def _tile_of(tokens, gates, tiles, t, tile: int):
    """Tile ``t`` of the walk: its expert, the tokens of its ``tile`` sorted
    rows and their gates, and which of the rows are its expert's. The rows
    past those are the next expert's or nobody's: their gate is zero here,
    so their results are zeros, and they add nothing to a gradient."""
    expert, first, n_valid, _ = (a[t] for a in tiles)
    valid = jnp.arange(tile) < n_valid
    tok = lax.dynamic_slice(tokens, (first,), (tile,))
    g = jnp.where(valid, lax.dynamic_slice(gates, (first,), (tile,)), 0)
    return expert, first, valid, tok, g


def _expert(wa, expert):
    """One held expert's matrices of the cast stacks."""
    return {k: lax.dynamic_index_in_dim(a, expert, keepdims=False)
            for k, a in wa.items()}


_GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _gated(a, b, gate="silu"):
    return _GATES[gate](a) * b


def _dot_rows(a, b):
    """``a^T b`` summed over the rows both lead with, in float32."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _sum_by_token(rows, by_token, window: int, chunk: int, n_tokens: int):
    """``y[token] = sum of rows[r] over the token's held rows r``:
    ``(n_tokens, d)`` in ``rows``' dtype, summed in float32. ``rows``: a
    buffer in sorted order; ``by_token``: the held rows in the order of
    their tokens (each one's token's place in its window and its own in
    ``rows``, a chunk of padding behind), the chunks laid over the windows
    (per chunk its window, its first held row, how many of its ``chunk``
    rows are the window's and whether it is the window's first) and the
    chunks in use. A chunk gathers its rows, sums them by token with one
    product (ones where a row is a token's: the same float32 sum of the
    same addends as an add a row), and writes its window's sum so far in
    its place; every window has a chunk, so all of ``y`` is written. This
    is the forward's combine and, of the tiles' ``dx`` rows, the
    backward's too: one linear map, whose transpose is the walk's gather
    of a row's token."""
    token_of, row_of, chunks, in_use = by_token
    n_windows, d = -(-n_tokens // window), rows.shape[1]
    # a product with ones and zeros is a sum, not a rounding
    exact = None if rows.dtype == jnp.bfloat16 else lax.Precision.HIGHEST

    def visit(c, carry):
        y, so_far = carry
        win, first, n_valid, opens = (a[c] for a in chunks)
        valid = jnp.arange(chunk) < n_valid
        tok = lax.dynamic_slice(token_of, (first,), (chunk,))
        at = lax.dynamic_slice(row_of, (first,), (chunk,))
        # what lies past the window's rows may never have been written
        held = jnp.where(valid[:, None], jnp.take(rows, at, axis=0), 0)
        whose = tok[None, :] == jnp.arange(window)[:, None]
        part = lax.dot_general(
            whose.astype(rows.dtype), held, (((1,), (0,)), ((), ())),
            precision=exact, preferred_element_type=jnp.float32)
        so_far = jnp.where(opens, part, so_far + part)
        return lax.dynamic_update_slice(
            y, so_far.astype(y.dtype)[None], (win, 0, 0)), so_far

    y, _ = lax.fori_loop(
        0, in_use, visit, (_scratch((n_windows, window, d), rows.dtype),
                           jnp.zeros((window, d), jnp.float32)))
    return y.reshape(-1, d)[:n_tokens]


def walk_sizes(cfg: Config, tokens: int, gate: str = "silu"):
    """What :func:`held_experts` and :func:`_route_and_sort` take as
    ``sizes``: the rows of a tile and the tokens of a window of the walk
    over ``tokens`` tokens' assignments, and the activation of an expert's
    gate (``silu`` or ``relu``)."""
    tile = _tile_rows(cfg, tokens)
    return tile, _window_tokens(cfg, tokens, tile), gate


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def held_experts(sizes, w, x, tokens, gates, tiles, in_use, by_token):
    """Sum over the sorted rows of the held experts' weighted results, by
    token: ``(N, d)``. ``sizes``: the rows of a tile (and of a chunk of
    :func:`_sum_by_token`), the tokens of its window and the activation of
    an expert's gate (:func:`walk_sizes`); ``tokens``,
    ``gates``: the token and the weight of each sorted row, with a tile of
    padding behind; ``tiles``: per tile its expert, its first sorted row
    and how many of its rows are that expert's; ``in_use``: the tiles that
    hold a row; ``by_token``: what :func:`_sum_by_token` takes.

    No row and no gate moves one index at a time. On the chip a
    scatter-add takes 0.25 us a row of 2,048, a gather of rows 0.024 us a
    row, a gather of single numbers 7.6 ns a number (PERF.md, PRs 37 and
    39). So forward walks the tiles in use: a tile gathers its rows,
    multiplies them with its one expert's matrices and writes the weighted
    results where they lie in sorted order, a contiguous write into a
    buffer nothing fills (the rows past the tile's own are zeros, and the
    next tile's to write); then :func:`_sum_by_token` sums the buffer by
    token with gathers. Backward walks the tiles again, recomputing each,
    adds the tile's weight gradients to that expert's float32 slices in
    place and writes the rows' ``dx`` in sorted order, which the same
    function sums by token. The gates came sorted out of the sort
    (:func:`_sort_by_group`), and their gradient goes back through it."""
    tile, window, gate = sizes
    wa = _cast(w, x.dtype)

    def visit(t, out):
        expert, first, _, tok, g = _tile_of(tokens, gates, tiles, t, tile)
        with jax.named_scope(STAGE_MOE_DISPATCH):
            xt = jnp.take(x, tok, axis=0)
        with jax.named_scope(STAGE_MOE_EXPERTS):
            we = _expert(wa, expert)
            o = (_gated(xt @ we["w1"], xt @ we["w3"], gate) @ we["w2"]
                 * g[:, None].astype(xt.dtype))
        with jax.named_scope(STAGE_MOE_COMBINE):
            return lax.dynamic_update_slice(out, o, (first, 0))

    out = lax.fori_loop(0, in_use, visit,
                        _scratch((tokens.shape[0], x.shape[1]), x.dtype))
    with jax.named_scope(STAGE_MOE_COMBINE):
        return _sum_by_token(out, by_token, window, tile, x.shape[0])


def _held_experts_fwd(sizes, w, x, tokens, gates, tiles, in_use, by_token):
    return (held_experts(sizes, w, x, tokens, gates, tiles, in_use, by_token),
            (w, x, tokens, gates, tiles, in_use, by_token))


def _held_experts_bwd(sizes, res, dy):
    tile, window, gate = sizes
    w, x, tokens, gates, tiles, in_use, by_token = res
    wa = _cast(w, x.dtype)

    def visit(t, carry):
        dw, dx, dgates = carry
        expert, first, valid, tok, g = _tile_of(tokens, gates, tiles, t, tile)
        with jax.named_scope(STAGE_MOE_DISPATCH):
            xt = jnp.take(x, tok, axis=0)
        with jax.named_scope(STAGE_MOE_COMBINE):
            dout = jnp.take(dy, tok, axis=0)
        with jax.named_scope(STAGE_MOE_EXPERTS):
            we = _expert(wa, expert)
            h, pull = jax.vjp(functools.partial(_gated, gate=gate),
                              xt @ we["w1"], xt @ we["w3"])
            dg = jnp.sum(dout.astype(jnp.float32)
                         * (h @ we["w2"]).astype(jnp.float32), axis=-1)
            dout = dout * g[:, None].astype(dout.dtype)
            da, db = pull(dout @ we["w2"].T)
            dxt = da @ we["w1"].T + db @ we["w3"].T
            dwe = {"w1": _dot_rows(xt, da), "w3": _dot_rows(xt, db),
                   "w2": _dot_rows(h, dout)}
            dw = {k: dw[k].at[expert].add(dwe[k]) for k in dw}
        with jax.named_scope(STAGE_MOE_DISPATCH):
            # the rows past the tile's own are a later tile's to write:
            # zeros here in dx (their gate is zero), kept as they are in
            # the gates' gradient
            dx = lax.dynamic_update_slice(dx, dxt, (first, 0))
            dg = jnp.where(valid, dg.astype(dgates.dtype),
                           lax.dynamic_slice(dgates, (first,), (tile,)))
        return dw, dx, lax.dynamic_update_slice(dgates, dg, (first,))

    dw, dx, dgates = lax.fori_loop(
        0, in_use, visit,
        (jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, jnp.float32), w),
         _scratch((tokens.shape[0], x.shape[1]), x.dtype),
         jnp.zeros(gates.shape, gates.dtype)))
    with jax.named_scope(STAGE_MOE_DISPATCH):
        dx = _sum_by_token(dx, by_token, window, tile, x.shape[0])
    return (jax.tree_util.tree_map(lambda a, b: a.astype(b.dtype), dw, w),
            dx, None, dgates, None, None, None)


held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def _route_and_sort(p, state, u, cfg: Config, sizes, router):
    """Route the tokens ``u`` ``(N, d)`` (by ``router``, :func:`route` or
    another with its signature, which is handed the state's ``expert_bias``
    where it has one), sort their assignments by held
    expert and lay tiles over the sorted rows, each held expert's first
    tile at its first row; sort the held rows back by token and lay chunks
    over the windows of tokens: what :func:`held_experts` takes, and the
    layer's counters. A chunk is a tile's rows; tiles and chunks are
    bounded by the worst case, every assignment held here and every
    expert's (window's) last tile (chunk) all but empty."""
    tile, window, _ = sizes
    k, held = cfg.num_experts_per_tok, cfg.experts_held
    n_tokens, n_slots = u.shape[0], u.shape[0] * k
    n_windows = -(-n_tokens // window)
    with jax.named_scope(STAGE_MOE_ROUTER):
        experts, gates = router(p, state.get("expert_bias"), u, cfg)
        drawn = _count(experts.reshape(-1), cfg.num_experts)
    with jax.named_scope(STAGE_MOE_DISPATCH):
        local = experts.reshape(-1) - cfg.first_expert
        here = (local >= 0) & (local < held)
        group = jnp.where(here, local, held)          # the rest sort last
        slot_of_row, row_gates = _sort_by_group(group, gates.reshape(-1))
        counts = _count(group, held + 1)[:held]
        tiles, in_use = _lay_tiles(counts, tile, -(-n_slots // tile) + held)
        # a tile of padding, so that the last tile's window lies inside
        tokens = jnp.pad(slot_of_row // k, (0, tile))
        row_gates = jnp.pad(row_gates, (0, tile))
        # the held rows by token: their slots sorted, each with its row
        rows = jnp.arange(n_slots, dtype=jnp.int32)
        slot, row_of = lax.sort(
            (jnp.where(rows < jnp.sum(counts), slot_of_row, n_slots), rows),
            num_keys=1, is_stable=False)
        per_window = jnp.sum(jnp.pad(
            here, (0, n_windows * window * k - n_slots)).reshape(
                n_windows, -1), axis=1, dtype=jnp.int32)
        chunks, chunks_in_use = _lay_tiles(
            per_window, tile, -(-n_slots // tile) + n_windows, least=1)
        by_token = (jnp.pad(slot // k % window, (0, tile)),
                    jnp.pad(row_of, (0, tile)), chunks, chunks_in_use)
    held_rows = jnp.sum(tiles[2]).astype(jnp.float32)
    counters = {"expert_bias": state.get("expert_bias"),
                "drawn": drawn.astype(jnp.float32), "held": held_rows,
                "computed": (in_use * tile).astype(jnp.float32),
                "combined": (chunks_in_use * tile).astype(jnp.float32),
                "dropped": jnp.sum(here).astype(jnp.float32) - held_rows}
    # a state made without a counter (the benchmark's own makes the four it
    # knows) goes on without it
    return ((tokens, row_gates, tiles, in_use, by_token),
            {name: counters[name] for name in state})


def moe_ffn(p, state, u, cfg: Config, router=None):
    """The held experts' part of the expert layer's result for normalised
    ``u`` ``(n, T, d)``, and the layer's new state (the counters).
    ``router``: another decoder's, with :func:`route`'s signature, in place
    of this module's."""
    x = u.reshape(-1, u.shape[-1])
    sizes = walk_sizes(cfg, x.shape[0])
    sorted_rows, counters = _route_and_sort(p, state, x, cfg, sizes,
                                            router or route)
    y = held_experts(sizes, {k: p[k] for k in ("w1", "w3", "w2")}, x,
                     *sorted_rows)
    return y.reshape(u.shape), counters


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _operator_part(kind, cfg):
    stage = STAGE_SHORT_CONV if kind == "conv" else STAGE_ATTENTION
    op = short_conv if kind == "conv" else attention

    def part(p, x):
        with jax.named_scope(stage):
            return x + op(p["op"], L.rms_apply(p["op_norm"], x, cfg.norm_eps),
                          cfg)
    return part


def _dense_part(cfg):
    def part(p, x):
        with jax.named_scope(STAGE_DENSE_FFN):
            return x + dense_ffn(
                p["ffn"], L.rms_apply(p["ffn_norm"], x, cfg.norm_eps))
    return part


def _moe_part(cfg):
    def part(p, state, x):
        u = L.rms_apply(p["ffn_norm"], x, cfg.norm_eps)
        y, state = moe_ffn(p["ffn"], state, u, cfg)
        return x + y, state
    return part


def hidden_states(params, model_state, ids, cfg: Config,
                  dtype=jnp.float32):
    """ids ``(n, T)`` -> the last layer's output ``(n, T, d)`` (before the
    final norm) and the new model state."""
    x = L.embedding_apply(params["embed"], ids, dtype=dtype)
    new_state = []
    for i, (kind, p, s) in enumerate(zip(cfg.layer_types, params["layers"],
                                         model_state["layers"])):
        x = _over_sequences(_operator_part(kind, cfg), p, x, cfg.seq_block)
        if cfg.is_moe(i):
            # recomputed from x; the experts' own forward is not needed
            # again (their backward recomputes tile by tile) and falls away
            x, s = jax.checkpoint(_moe_part(cfg))(p, s, x)
        else:
            x = _over_sequences(_dense_part(cfg), p, x, cfg.seq_block)
        new_state.append(s)
    return x, {"layers": new_state}


def _head_part(p, x, targets, weights, scale, eps):
    """Final norm, head and the weighted cross-entropy of one part of
    positions, summed and times ``scale``."""
    u = L.rms_apply(p["final_norm"], x, eps)
    logits = _dot(u, p["head"]).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * weights) * scale


def _head_parts(x, targets, weights, positions: int):
    """The positions of all sequences, one behind another, in parts of
    ``positions``: what the head's walk scans."""
    total = targets.size
    positions = min(positions, total)
    if total % positions:
        raise ValueError(f"{total} positions are not whole parts of "
                         f"{positions}")
    return (x.reshape(-1, positions, x.shape[-1]),
            targets.reshape(-1, positions), weights.reshape(-1, positions))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def head_loss(p, x, targets, weights, scale, positions, eps):
    """``scale`` times the sum over all positions of ``weights`` times the
    cross-entropy of the head's logits against ``targets``: final norm
    (``eps``), head and float32 log-softmax of ``x`` ``(n, T, d)`` under
    ``p`` = ``{"final_norm", "head"}``, ``positions`` positions at a time,
    so that one part's float32 logits are what is alive. ``targets`` and
    ``weights`` ``(n, T)``: a token and a float32 weight a position (a
    position without a target weighs zero); they take no gradient.

    The head is the last thing the forward pass does and the first the
    backward pass does, so differentiated it forms its gradient in the walk
    that makes the logits (:func:`_head_loss_fwd`): each part's logits exist
    once a step, and a part costs three products (logits, ``dx``, ``d
    head``) where a part recomputed in the backward pass costs four. Called
    without differentiation it makes one product a part and the loss."""
    with jax.named_scope(STAGE_LM_HEAD):
        def visit(loss, part):
            return loss + _head_part(p, *part, scale, eps), None

        loss, _ = lax.scan(visit, jnp.zeros((), jnp.float32),
                           _head_parts(x, targets, weights, positions))
        return loss


def _head_loss_fwd(p, x, targets, weights, scale, positions, eps):
    """The loss, and as residuals its gradient by ``p`` and ``x``: one walk
    over the parts, each under ``jax.value_and_grad``, so the rounding is
    where autodiff puts it (``scale`` enters the float32 ``dlogits`` before
    their cast to the activations' dtype). The carry holds the loss and the
    float32 sums of the parts' ``d final_norm`` and ``d head``; the parts'
    ``dx`` are the walk's outputs, in ``x``'s shape and dtype."""
    with jax.named_scope(STAGE_LM_HEAD):
        part_grad = jax.value_and_grad(_head_part, argnums=(0, 1))

        def visit(carry, part):
            loss, dp = carry
            part_loss, (dp_part, dx) = part_grad(p, *part, scale, eps)
            dp = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), dp, dp_part)
            return (loss + part_loss, dp), dx

        (loss, dp), dx = lax.scan(
            visit, (jnp.zeros((), jnp.float32), jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, jnp.float32), p)),
            _head_parts(x, targets, weights, positions))
        dp = jax.tree_util.tree_map(lambda a, b: a.astype(b.dtype), dp, p)
        return loss, (dp, dx.reshape(x.shape))


def _head_loss_bwd(scale, positions, eps, grads, g):
    with jax.named_scope(STAGE_LM_HEAD):
        dp, dx = jax.tree_util.tree_map(lambda a: a * g.astype(a.dtype),
                                        grads)
    return dp, dx, None, None


head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


def _head_params(params):
    return {"final_norm": params["final_norm"], "head": params["head"]}


def next_token_loss(params, model_state, ids, cfg: Config,
                    dtype=jnp.float32):
    """Mean over all tokens of the cross-entropy of position ``t``'s logits
    against token ``t + 1`` (a sequence's last position has no target):
    ``(loss, new_model_state)``."""
    x, new_state = hidden_states(params, model_state, ids, cfg, dtype)
    return loss_of_hidden_states(params, x, ids, cfg), new_state


def next_token_targets(ids):
    """``(targets, weights)`` of the next-token loss over whole sequences:
    position ``t``'s target is token ``t + 1``; a sequence's last position
    has none (it is handed the first token) and weighs zero."""
    n, t = ids.shape
    weights = jnp.broadcast_to(
        (jnp.arange(t) < t - 1).astype(jnp.float32), (n, t))
    return jnp.roll(ids, -1, axis=1), weights


def loss_of_hidden_states(params, x, ids, cfg):
    """The next-token loss of the last layer's output ``x`` ``(n, T, d)``:
    final norm, head and cross-entropy, ``seq_block`` sequences at a time,
    over all ``T`` positions of each (whole tiles; the last weighs zero)."""
    n, t = ids.shape
    return head_loss(_head_params(params), x, *next_token_targets(ids),
                     1.0 / (n * (t - 1)), cfg.seq_block * t, cfg.norm_eps)
