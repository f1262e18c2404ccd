"""``grace_tpu.models.deepseek_v3`` against the plain reference
(``benchmarks/reference/deepseek_v3.py``) at a small size on the CPU, and
the properties the model promises: latent attention's shared rotary key,
its rotation of the rotary slice only, its scale and causality; the shares
of an expert layer, with the shared expert counted once, add up to the
whole layer; no assignment is dropped however skewed the router; every
part of the step stands under its stage.
"""

import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.models import deepseek_v3 as builder  # noqa: E402
from benchmarks.reference import deepseek_v3 as plain  # noqa: E402
from benchmarks.trace_reduce import STAGE, stage_of  # noqa: E402
from grace_tpu.models import deepseek_v3 as dsv3  # noqa: E402
from grace_tpu.models import layers as L  # noqa: E402
from grace_tpu.models import lfm2  # noqa: E402
from grace_tpu.telemetry import scopes  # noqa: E402

# A share of a small model in the configuration file's own keys: 2 routed
# experts held (experts 2 and 3) of the 8 the router scores, 2 a token, 2
# shared; heads of 12 | 8 (8 without positions + 4 rotary | values).
SIZES = {
    "hidden_size": 32, "intermediate_size": 64, "moe_intermediate_size": 16,
    "n_shared_experts": 2, "n_routed_experts": 2, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448, "vocab_size": 128,
    "published": {"n_routed_experts": 8}, "share": 1, "seq_length": 16,
    "per_chip_batch": 4, "activation_dtype": "float32",
    "param_dtype": "float32"}
# several blocks of each kind at this size
WALK = {"attn_q_block": 8, "moe_row_block": 32, "seq_block": 2}
GROUPS = ["embed", "final_norm", "head"] + [f"layers/{i}" for i in range(3)]


def _program_loss(sizes, **walk):
    cfg = dataclasses.replace(builder.model_config(sizes), **{**WALK, **walk})
    dtype = jnp.dtype(sizes["activation_dtype"])
    return lambda params, mstate, batch: dsv3.next_token_loss(
        params, mstate, batch, cfg, dtype=dtype)


def _run(loss_fn, sizes=SIZES, key=1):
    with jax.default_matmul_precision("highest"):
        params, state = builder.init(jax.random.key(key), sizes)
        ids = builder.make_batch(jax.random.key(key + 1),
                                 sizes["per_chip_batch"], sizes)
        (loss, new_state), grads = jax.jit(jax.value_and_grad(
            loss_fn, has_aux=True))(params, state, ids)
    return float(loss), grads, new_state


@pytest.fixture(scope="module")
def float32_pair():
    return (_run(_program_loss(SIZES)), _run(builder.reference_loss(SIZES)))


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def _group(tree, name):
    for part in name.split("/"):
        tree = tree[int(part)] if part.isdigit() else tree[part]
    return tree


# In float32 at 'highest' program and reference differ in the order of sums
# (grouped product against one product per expert, blocks of queries
# against whole rows of scores, the scale on the scores against the scale
# folded nowhere on this path) and in the rotary entries' order, which no
# score sees: a few units of 2**-24 a sum. The bfloat16 run below is a
# thousand times over.
LOSS_TOL = 2e-6
GRAD_TOL = 2e-5


def test_loss_agrees_with_the_plain_reference(float32_pair):
    (got, _, _), (want, _, _) = float32_pair
    assert abs(got - want) <= LOSS_TOL * abs(want)
    assert 4.0 < want < 6.0                      # ln 128 = 4.85 at the start


@pytest.mark.parametrize("group", GROUPS)
def test_every_leafs_gradient_agrees_with_the_plain_reference(
        float32_pair, group):
    (_, got, _), (_, want, _) = float32_pair
    gaps = jax.tree_util.tree_map(_rel, _group(got, group),
                                  _group(want, group))
    flat = jax.tree_util.tree_flatten_with_path(gaps)[0]
    assert flat and all(g <= GRAD_TOL for _, g in flat), flat
    assert all(float(jnp.max(jnp.abs(w))) > 0 for w in
               jax.tree_util.tree_leaves(_group(want, group)))


def test_a_bfloat16_run_is_outside_the_tolerances(float32_pair):
    _, (want_loss, want, _) = float32_pair
    low = dict(SIZES, activation_dtype="bfloat16")
    loss, grads, _ = _run(_program_loss(low))
    gaps = jax.tree_util.tree_leaves(jax.tree_util.tree_map(_rel, grads, want))
    assert (abs(loss - want_loss) > LOSS_TOL * want_loss
            or max(gaps) > GRAD_TOL)
    assert max(gaps) > 50 * GRAD_TOL


@pytest.mark.parametrize("walk", [{"seq_block": 4}, {"attn_q_block": 4},
                                  {"moe_row_block": 0}])
def test_walking_the_work_in_other_blocks_changes_nothing(walk, float32_pair):
    (want_loss, want, _), _ = float32_pair
    loss, grads, _ = _run(_program_loss(SIZES, **walk))
    assert abs(loss - want_loss) <= LOSS_TOL * want_loss
    gaps = jax.tree_util.tree_leaves(jax.tree_util.tree_map(_rel, grads, want))
    assert max(gaps) <= GRAD_TOL


def test_the_program_reads_the_tree_the_benchmark_makes():
    cfg = builder.model_config(SIZES)
    own, own_state = jax.eval_shape(lambda k: dsv3.init(k, cfg),
                                    jax.random.key(0))
    made, made_state = jax.eval_shape(lambda k: builder.init(k, SIZES),
                                      jax.random.key(0))
    assert (jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), own)
            == jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), made))
    # the program's own state counts the rows multiplied (PR 37) and the
    # rows its second pass summed (PR 39) too
    for own_layer, made_layer in zip(own_state["layers"],
                                     made_state["layers"]):
        assert set(own_layer) - set(made_layer) == (
            {"computed", "combined"} if made_layer else set())
        assert {k: own_layer[k] for k in made_layer} == made_layer
    assert len(jax.tree_util.tree_leaves(own)) == 3 + 10 + 2 * 14
    assert cfg.route_eps == 1e-20 and lfm2.Config().route_eps == 1e-6


def _counts(cfg):
    shapes = jax.eval_shape(lambda k: dsv3.init(k, cfg)[0], jax.random.key(0))
    return shapes, [math.prod(s.shape)
                    for s in jax.tree_util.tree_leaves(shapes)]


def test_the_published_configuration_counts_its_parameters():
    """The share the benchmark's configuration states: 424,960,512
    parameters in 69 leaves; and the uncut model through the same Config:
    the catalog's 36 M a layer beside 128 experts of 4.7 M, 525 M of
    embedding and head."""
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "kanana-2-30b-a3b-ep16.json")) as f:
        sizes = json.load(f)
    cfg = builder.model_config(sizes)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.n_shared_experts) == (2048, 6144, 768, 2)
    assert (cfg.num_attention_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.qk_head_dim, cfg.v_head_dim) == (
                32, 512, 128, 64, 192, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.experts_held,
            cfg.first_expert, cfg.first_k_dense_replace,
            cfg.num_hidden_layers) == (128, 6, 8, 0, 1, 5)
    assert (cfg.norm_eps, cfg.rope_theta, cfg.routed_scaling_factor,
            cfg.route_eps) == (1e-6, 1e6, 2.448, 1e-20)
    # the walk's tile is chosen from the shapes, no key of the file: a
    # quarter of a balanced expert's 1,536 rows, in whole multiples of 128
    # (PR 37; until then one block of twice the balanced load, 24,576 rows)
    assert cfg.moe_row_block == 0 and "expert_row_block" not in sizes
    assert lfm2._tile_rows(cfg, 32768) == 32768 * 6 // 128 // 4 == 384
    shapes, counts = _counts(cfg)
    assert len(counts) == 69 and sum(counts) == sizes["parameters_held"]
    assert sum(counts) == 424_960_512
    mla = sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(
        shapes["layers"][0]["attn"]))
    assert mla == 12_582_912 + 1_179_648 + 512 + 4_194_304 + 8_388_608
    layer0 = sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(
        shapes["layers"][0]))
    layer1 = sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(
        shapes["layers"][1]))
    assert (layer0, layer1) == (64_098_816, 73_798_144)
    assert counts.count(8 * 2048 * 768) >= 12          # the expert stacks
    # the whole published model through the same Config
    whole = dsv3.Config()
    assert (whole.num_hidden_layers, whole.vocab_size, whole.experts_held
            ) == (48, 128256, 128)
    shapes, counts = _counts(dataclasses.replace(whole, num_hidden_layers=2))
    expert_layer = shapes["layers"][1]
    per_expert = 3 * 2048 * 768
    routed = 128 * per_expert
    outside = sum(math.prod(s.shape) for s in
                  jax.tree_util.tree_leaves(expert_layer)) - routed
    assert per_expert == 4_718_592                     # 4.7 M an expert
    assert outside == 36_049_408                       # 36 M a layer
    assert 2 * 128256 * 2048 == 525_336_576            # embedding and head
    assert (math.prod(shapes["embed"]["table"].shape)
            + math.prod(shapes["head"].shape)) == 525_336_576


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

def _mla_weights(cfg, key=2):
    p = dsv3.init(jax.random.key(key), cfg)[0]["layers"][0]["attn"]
    # large enough that the softmax is far from uniform
    return jax.tree_util.tree_map(
        lambda x: x * 12 if x.ndim == 2 else x + 0.1 * jnp.arange(x.size), p)


def test_mla_agrees_with_the_plain_reference():
    cfg = dsv3.tiny(attn_q_block=4)
    p = _mla_weights(cfg)
    u = jax.random.normal(jax.random.key(3), (2, 16, 32))
    with jax.default_matmul_precision("highest"):
        got = dsv3.mla(p, u, cfg)
        want = jax.vmap(lambda x: plain._mla(p, x, SIZES))(u)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert got.shape == (2, 16, 32)


def test_mla_is_causal_and_reads_no_other_sequence():
    cfg = dsv3.tiny()
    p = _mla_weights(cfg)
    u = jax.random.normal(jax.random.key(4), (2, 16, 32))
    base = dsv3.mla(p, u, cfg)
    later = dsv3.mla(p, u.at[:, 9:].add(1.0), cfg)
    np.testing.assert_array_equal(base[:, :9], later[:, :9])
    assert float(jnp.max(jnp.abs(base[:, 9:] - later[:, 9:]))) > 0
    other = dsv3.mla(p, u.at[1].add(1.0), cfg)
    np.testing.assert_array_equal(base[0], other[0])


def test_the_scores_are_scaled_by_the_whole_query_heads_size():
    """``1 / sqrt(nope + rope)``: 12 here, 192 as published; not the 8 of
    the part without positions nor the values' 8."""
    cfg = dsv3.tiny()
    p = _mla_weights(cfg)
    u = jax.random.normal(jax.random.key(5), (1, 16, 32))
    got = dsv3.mla(p, u, cfg)
    # the same operator with q pre-multiplied so that a scale of
    # 1/sqrt(8) would give what 1/sqrt(12) gives here
    wrong = dict(p, q_proj=p["q_proj"] * math.sqrt(12 / 8))
    assert float(jnp.max(jnp.abs(dsv3.mla(wrong, u, cfg) - got))) > 1e-3
    want = jax.vmap(lambda x: plain._mla(p, x, SIZES))(u)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_one_rotary_key_serves_every_head():
    """``W_kva``'s last ``rope`` columns make ONE key part: changing them
    moves every head's scores, and their gradient sums over the heads."""
    cfg = dsv3.tiny()
    p = _mla_weights(cfg)
    u = jax.random.normal(jax.random.key(6), (1, 16, 32))

    def per_head(p):
        # W_o as a selector: each head's output on its own
        out = dsv3.mla(dict(p, o_proj=jnp.eye(32)), u, cfg)
        return out.reshape(1, 16, 4, 8)

    base = per_head(p)
    kva = p["kv_a_proj"].at[:, cfg.kv_lora_rank:].multiply(-1.0)
    moved = per_head(dict(p, kv_a_proj=kva))
    per = jnp.max(jnp.abs(moved - base), axis=(0, 1, 3))
    assert per.shape == (4,) and float(jnp.min(per)) > 1e-4
    # the gradient of the shared part is the sum of what each head sends
    heads = [jax.grad(lambda p, h=h: jnp.sum(per_head(p)[:, :, h]))(p)
             ["kv_a_proj"] for h in range(4)]
    whole = jax.grad(lambda p: jnp.sum(per_head(p)))(p)["kv_a_proj"]
    np.testing.assert_allclose(sum(heads), whole, rtol=1e-4, atol=1e-6)
    assert float(jnp.max(jnp.abs(whole[:, cfg.kv_lora_rank:]))) > 0


def test_rotary_positions_turn_neighbouring_pairs():
    x = jax.random.normal(jax.random.key(7), (6, 3, 8))
    got = L.rotary_pairs(x, 1e6)
    # HF's spelling: de-interleave, rotate halves; the same numbers in
    # another order
    hf = plain._rotate(x, 1e6)
    np.testing.assert_allclose(got[..., 0::2], hf[..., :4], rtol=1e-6,
                               atol=1e-7)
    np.testing.assert_allclose(got[..., 1::2], hf[..., 4:], rtol=1e-6,
                               atol=1e-7)
    np.testing.assert_allclose(got[0], x[0], rtol=1e-6)      # position 0
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # entry 2i is turned with entry 2i + 1 and with no other
    bumped = L.rotary_pairs(x.at[..., 2].add(1.0), 1e6)
    changed = jnp.max(jnp.abs(bumped - got), axis=(0, 1)) > 0
    np.testing.assert_array_equal(
        changed, np.array([0, 0, 1, 1, 0, 0, 0, 0], bool))
    # a rotated query times a rotated key depends on their distance alone
    r = L.rotary_pairs(jnp.broadcast_to(x[:1], x.shape), 1e6)
    np.testing.assert_allclose(jnp.sum(r[1] * r[3]), jnp.sum(r[2] * r[4]),
                               rtol=1e-5)
    assert L.rotary_pairs(x.astype(jnp.bfloat16), 1e6).dtype == jnp.bfloat16


def test_positions_reach_the_rotary_slice_only():
    """With the rotary columns of ``W_q`` zeroed the scores know no
    position: permuting the earlier tokens of a sequence leaves the last
    token's output unchanged. With them in, the order matters."""
    cfg = dsv3.tiny()
    p = _mla_weights(cfg)
    q = p["q_proj"].reshape(32, 4, 12).at[:, :, 8:].set(0.0).reshape(32, 48)
    p = dict(p, q_proj=q)
    u = jax.random.normal(jax.random.key(8), (1, 16, 32))
    perm = jnp.concatenate([jnp.arange(15)[::-1], jnp.array([15])])
    base = dsv3.mla(p, u, cfg)[:, -1]
    shuffled = dsv3.mla(p, u[:, perm], cfg)[:, -1]
    np.testing.assert_allclose(base, shuffled, rtol=1e-4, atol=1e-6)
    # with the rotary part in, the order matters
    p = _mla_weights(cfg)
    assert float(jnp.max(jnp.abs(
        dsv3.mla(p, u, cfg)[:, -1] - dsv3.mla(p, u[:, perm], cfg)[:, -1]))
    ) > 1e-4


# ---------------------------------------------------------------------------
# the expert layer with its shared expert
# ---------------------------------------------------------------------------

def _expert_layer(key):
    """One expert layer's weights for all 8 routed experts and the shared
    one, a normalised input, and the sizes of the uncut layer."""
    sizes = dict(SIZES, n_routed_experts=8, share=0)
    d, f = 32, 16
    ks = jax.random.split(jax.random.key(key), 9)
    whole = {"router": jax.random.normal(ks[0], (d, 8)) * 0.3,
             "w1": jax.random.normal(ks[1], (8, d, f)) * 0.2,
             "w3": jax.random.normal(ks[2], (8, d, f)) * 0.2,
             "w2": jax.random.normal(ks[3], (8, f, d)) * 0.2,
             "shared": {"w1": jax.random.normal(ks[4], (d, 2 * f)) * 0.2,
                        "w3": jax.random.normal(ks[5], (d, 2 * f)) * 0.2,
                        "w2": jax.random.normal(ks[6], (2 * f, d)) * 0.2}}
    u = jax.random.normal(ks[7], (3, 16, d))
    bias = jax.random.normal(ks[8], (8,)) * 0.1
    return sizes, whole, u, bias


def _share_of(whole, first, held):
    return {"router": whole["router"], "shared": whole["shared"],
            **{k: whole[k][first:first + held] for k in ("w1", "w3", "w2")}}


def _state(bias):
    return {"expert_bias": bias, "drawn": jnp.zeros((8,)),
            "held": jnp.zeros(()), "dropped": jnp.zeros(())}


def _normalised(u):
    return u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True) + 1e-6)


@pytest.mark.parametrize("shares", [1, 4])
def test_the_shares_add_up_with_the_shared_expert_counted_once(shares):
    """8 routed experts over ``shares`` chips: every chip's layer gives its
    own experts' part plus the shared expert whole; the routed parts of
    all shares plus the shared expert counted once are the uncut
    reference's layer."""
    sizes, whole, u, bias = _expert_layer(3)
    u = _normalised(u)            # so that the layer's norm changes nothing
    held = 8 // shares
    norm = {"scale": jnp.ones((32,))}
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda x: plain._routed(whole, bias, x, sizes, 0)
                        + plain._gated(x, whole["shared"]))(u)
        shared = dsv3.dense_ffn(whole["shared"], u)
        total, computed = jnp.zeros_like(u), 0.0
        for share in range(shares):
            cfg = dsv3.tiny(first_expert=share * held, experts_held=held,
                            moe_row_block=24, norm_eps=0.0)
            p = _share_of(whole, share * held, held)
            routed, counters = lfm2.moe_ffn(p, _state(bias), u, cfg)
            # the chip's layer: its routed part and the shared expert whole
            y, _ = dsv3._moe_part(cfg)({"ffn_norm": norm, "ffn": p},
                                       _state(bias), u)
            np.testing.assert_allclose(y, u + routed + shared, rtol=1e-5,
                                       atol=5e-6)      # sums in another order
            total = total + routed
            computed += float(counters["held"])
            assert float(counters["dropped"]) == 0.0
    np.testing.assert_allclose(total + shared, want, rtol=2e-5, atol=2e-6)
    assert computed == u.shape[0] * u.shape[1] * 2     # every assignment once
    assert float(jnp.max(jnp.abs(shared))) > 1e-3
    assert float(jnp.max(jnp.abs(total))) > 1e-3


def test_the_gates_are_scaled_and_normalised_over_all_chosen():
    """Two a token here, over all 8: gates ``s_i / (sum + 1e-20) * 2.448``,
    whether or not the chosen experts are held."""
    _, whole, u, bias = _expert_layer(4)
    cfg = dsv3.tiny(first_expert=2, experts_held=2)
    experts, gates = lfm2.route(whole, bias, u.reshape(-1, 32), cfg)
    np.testing.assert_allclose(jnp.sum(gates, axis=-1), 2.448, rtol=1e-5)
    s = jax.nn.sigmoid(u.reshape(-1, 32) @ whole["router"])
    top = jnp.argsort(-(s + bias), axis=-1)[:, :2]
    np.testing.assert_array_equal(jnp.sort(experts, -1), jnp.sort(top, -1))


@pytest.mark.parametrize("target,row_block", [(0, 96), (1, 8)])
def test_no_assignment_is_dropped_under_a_skewed_router(target, row_block):
    """6 a token of 8, a bias that sends every token to the held expert
    ``target`` first: all 48 tokens' rows land in one group, past any
    balanced capacity, and every one is computed."""
    sizes, whole, u, _ = _expert_layer(5)
    sizes = dict(sizes, num_experts_per_tok=6)
    bias = jnp.zeros((8,)).at[2 + target].set(10.0)
    cfg = dsv3.tiny(first_expert=2, experts_held=2, num_experts_per_tok=6,
                    moe_row_block=row_block)
    part = dict(sizes, n_routed_experts=2, share=1)
    with jax.default_matmul_precision("highest"):
        got, counters = jax.jit(lambda p, s, x: lfm2.moe_ffn(p, s, x, cfg))(
            _share_of(whole, 2, 2), _state(bias), u)
        want = jax.vmap(lambda x: plain._routed(
            _share_of(whole, 2, 2), bias, x, part, 2))(u)
    assert float(counters["drawn"][2 + target]) == 48.0
    assert float(counters["drawn"].sum()) == 48 * 6
    assert float(counters["held"]) >= 48.0
    assert float(counters["dropped"]) == 0.0
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


# the bias on the scores of the two held experts, 2 and 3 of 8
ROUTERS = {"balanced": (0.0, 0.0), "skewed": (10.0, 0.0),
           "idle": (-10.0, 0.0), "worst_case": (10.0, 10.0)}


@pytest.mark.parametrize("tile", [0, 8, 16, 40])
@pytest.mark.parametrize("router", sorted(ROUTERS))
def test_six_a_token_in_expert_aligned_tiles(router, tile):
    """This decoder's routing (6 a token, gates scaled) through the tiles
    of ``lfm2.moe_ffn`` (PR 37) and its sum by token in windows and chunks
    (PR 39): the routed part and its gradients against the plain
    reference, nothing dropped, less than a tile an expert multiplied
    beyond the rows held and less than a chunk a window summed beyond
    them, whatever the router does (``worst_case``: both experts' 48 rows
    end on a boundary of the tiles of 8 and 16)."""
    sizes, whole, u, _ = _expert_layer(6)
    sizes = dict(sizes, num_experts_per_tok=6)
    b = jnp.zeros((8,)).at[2:4].set(jnp.asarray(ROUTERS[router]))
    cfg = dsv3.tiny(first_expert=2, experts_held=2, num_experts_per_tok=6,
                    moe_row_block=tile)
    part = dict(sizes, n_routed_experts=2, share=1)
    share = _share_of(whole, 2, 2)
    state = dict(lfm2.expert_layer_state(8), expert_bias=b)

    def program(p, x):
        y, counters = lfm2.moe_ffn(p, state, x, cfg)
        return jnp.sum(jnp.sin(y)), (y, counters)

    def reference(p, x):
        y = jax.vmap(lambda row: plain._routed(p, b, row, part, 2))(x)
        return jnp.sum(jnp.sin(y)), y

    routed = {k: share[k] for k in ("router", "w1", "w3", "w2")}
    with jax.default_matmul_precision("highest"):
        (_, (got, counters)), grads = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1), has_aux=True))(routed, u)
        (_, want), want_grads = jax.value_and_grad(
            reference, argnums=(0, 1), has_aux=True)(routed, u)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-6)
    rows = tile or 128
    held = float(counters["drawn"][2] + counters["drawn"][3])
    assert float(counters["dropped"]) == 0.0
    assert float(counters["held"]) == held
    assert 0 <= float(counters["computed"]) - held < 2 * rows
    assert float(counters["computed"]) % rows == 0
    experts, _ = lfm2.route(share, b, u.reshape(-1, 32), cfg)
    window, chunk = lfm2._window_tokens(cfg, 48, rows), rows
    mine = np.asarray((experts >= 2) & (experts < 4)).sum(1)
    assert float(counters["combined"]) == sum(
        max(1, -(-int(mine[i:i + window].sum()) // chunk)) * chunk
        for i in range(0, 48, window))
    assert {"balanced": 48 < held < 96, "skewed": held > 48,
            "idle": float(counters["drawn"][2]) == 0 and held > 0,
            "worst_case": held == 96}[router]


def test_both_decoders_configs_keep_the_fields_the_shared_parts_read():
    """``lfm2``'s router, expert layer, dense part and loss are handed
    either decoder's ``Config``: the names they read are fields of both,
    of one type."""
    ours = {f.name: f.type for f in dataclasses.fields(dsv3.Config)}
    theirs = {f.name: f.type for f in dataclasses.fields(lfm2.Config)}
    for name in dsv3.SHARED_FIELDS:
        assert ours[name] == theirs[name], name


def test_a_share_outside_the_routers_experts_is_refused():
    with pytest.raises(ValueError, match="not among"):
        dsv3.tiny(first_expert=7, experts_held=2)
    with pytest.raises(ValueError, match="pairs"):
        dsv3.tiny(qk_rope_head_dim=3)


# ---------------------------------------------------------------------------
# what a recomputed part keeps (PR 33): nothing, where there is no kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("which", ["plain_latent_attention", "dense_layer"])
def test_a_part_without_the_kernel_keeps_nothing(keep_nothing, which):
    """On the CPU latent attention scores in its plain blocks (``engages``
    says no), so neither it nor the dense layer names anything for
    ``_over_sequences`` to keep: the gradient's jaxpr (but for the line
    that prints the policy's address) and its lowered text are those of a
    ``jax.checkpoint`` that keeps nothing."""
    cfg = dsv3.tiny()
    p = dsv3.init(jax.random.key(3), cfg)[0]["layers"][0]
    x = jax.random.normal(jax.random.key(4), (4, 16, cfg.hidden_size))
    part = (dsv3._mla_part(cfg) if which == "plain_latent_attention"
            else lfm2._dense_part(cfg))

    def texts():
        # a function of its own each time (`keep_nothing`)
        grad = jax.value_and_grad(
            lambda p, x: jnp.sum(lfm2._over_sequences(part, p, x, 1) ** 2),
            argnums=(0, 1))
        jaxpr = [line for line in str(jax.make_jaxpr(grad)(p, x)).splitlines()
                 if "policy=" not in line]
        return jaxpr, jax.jit(grad).lower(p, x).as_text()

    jaxpr, lowered = texts()
    assert not any("pallas_call" in line or "name[" in line
                   for line in jaxpr)
    assert any("remat" in line for line in jaxpr)
    keep_nothing()
    plain_jaxpr, plain_lowered = texts()
    assert jaxpr == plain_jaxpr
    assert lowered == plain_lowered


def test_the_whole_model_is_the_same_bits_under_a_plain_checkpoint(
        keep_nothing, float32_pair):
    """All parts together: loss and every gradient are the bits a
    ``jax.checkpoint`` that keeps nothing gives."""
    (want_loss, want, _), _ = float32_pair
    keep_nothing()
    loss, grads, _ = _run(_program_loss(SIZES))
    assert loss == want_loss
    for got, ref in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# on the normal path: the compressed training step, its counters, its scopes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trained():
    """Four steps of ``make_stateful_train_step`` under the top-k
    transform and AdamW, on the CPU's devices."""
    import optax
    from grace_tpu import data_parallel_mesh, grace_from_params
    from grace_tpu.train import (init_stateful_train_state,
                                 make_stateful_train_step)

    sizes = dict(SIZES, activation_dtype="bfloat16")
    mesh = data_parallel_mesh()
    world = mesh.devices.size
    grace = grace_from_params({
        "compressor": "topk", "compress_ratio": 0.05,
        "topk_algorithm": "chunk", "memory": "residual",
        "communicator": "allgather", "fusion": "none"})
    tx = optax.chain(grace.transform(seed=0), optax.adamw(1e-2))
    params, mstate = builder.init(jax.random.key(3), sizes)
    ids = builder.make_batch(jax.random.key(4), 2 * world, sizes)
    state = init_stateful_train_state(params, mstate, tx, mesh)
    step = make_stateful_train_step(builder.program_loss(sizes), tx, mesh,
                                    donate=False)
    losses = []
    for _ in range(4):
        state, loss = step(state, ids)
        losses.append(float(loss))
    text = next(iter(step.jit_cache.values())).lower(state, ids).as_text(
        debug_info=True)
    return {"losses": losses, "state": state, "text": text,
            "tokens": 2 * sizes["seq_length"]}


def test_the_compressed_step_trains_the_model(trained):
    losses = trained["losses"]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.01


def test_the_model_state_carries_bias_and_counters_through_the_step(trained):
    layers = trained["state"].model_state["layers"]
    assert layers[0] == {} and len(layers) == 3
    for layer in layers[1:]:
        assert set(layer) == {"expert_bias", "drawn", "held", "dropped"}
        assert float(layer["drawn"].sum()) == pytest.approx(
            trained["tokens"] * 2)
        assert 0 <= float(layer["held"]) <= trained["tokens"] * 2
        assert float(layer["dropped"]) == 0.0
        assert float(jnp.max(jnp.abs(layer["expert_bias"]))) == 0.0


def test_every_part_of_the_step_is_under_its_stage(trained):
    text = trained["text"]
    mine = (scopes.STAGE_MLA_LATENT, scopes.STAGE_ATTENTION,
            scopes.STAGE_SHARED_EXPERT, scopes.STAGE_DENSE_FFN,
            scopes.STAGE_MOE_ROUTER, scopes.STAGE_MOE_DISPATCH,
            scopes.STAGE_MOE_EXPERTS, scopes.STAGE_MOE_COMBINE,
            scopes.STAGE_LM_HEAD)
    for stage in mine:
        assert stage in text, stage
        assert STAGE.fullmatch(stage), stage             # the reducer reads it
        assert stage in scopes.ALL_STAGES and stage in scopes.MODEL_STAGES
    assert scopes.STAGE_SHORT_CONV not in text
    # the scores nest inside the latent's stage and the rightmost names them
    name = ("jit(device_step)/grace/forward_backward/checkpoint/"
            "grace/mla_latent/grace/attention/checkpoint/dot_general")
    assert stage_of(name) == "grace/attention"
    assert scopes.match_stage(name) == scopes.STAGE_ATTENTION
    assert "grace/mla_latent/grace/attention" in text
    name = ("jit(device_step)/grace/forward_backward/transpose(jvp("
            "grace/mla_latent))/dot_general")
    assert stage_of(name) == "grace/mla_latent"
    assert scopes.match_stage(
        "grace/forward_backward/jvp(grace/shared_expert)/dot") \
        == scopes.STAGE_SHARED_EXPERT
    assert len(set(scopes.ALL_STAGES)) == len(scopes.ALL_STAGES) == 30
