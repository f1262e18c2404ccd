"""``grace_tpu.models.lfm2`` against the plain reference
(``benchmarks/reference/lfm2_moe.py``) at a small size on the CPU, and the
properties the model promises: the shares of an expert layer add up to the
whole layer, no assignment is dropped however skewed the router, the short
convolution reads neither across a sequence's start nor from the future,
and walking the work in blocks changes nothing.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.models import lfm2_moe as builder  # noqa: E402
from benchmarks.reference import lfm2_moe as plain  # noqa: E402
from benchmarks.trace_reduce import STAGE, stage_of  # noqa: E402
from grace_tpu.models import layers as L  # noqa: E402
from grace_tpu.models import lfm2  # noqa: E402
from grace_tpu.ops import pallas_attention  # noqa: E402
from grace_tpu.telemetry import scopes  # noqa: E402

# A share of a small model in the configuration file's own keys: 2 experts
# held (experts 2 and 3) of the 8 the router scores, 2 a token.
SIZES = {
    "conv_L_cache": 3, "hidden_size": 32, "intermediate_size": 64,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv"],
    "moe_intermediate_size": 16, "norm_eps": 1e-5, "num_attention_heads": 4,
    "num_dense_layers": 1, "num_experts": 2, "num_experts_per_tok": 2,
    "num_hidden_layers": 5, "num_key_value_heads": 2,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "vocab_size": 128,
    "published": {"num_experts": 8}, "share": 1,
    "layers_held": [0, 2, 3, 4, 5], "seq_length": 16, "per_chip_batch": 4,
    "activation_dtype": "float32", "param_dtype": "float32"}
# How the program walks the work here (``lfm2.Config``'s block sizes; the
# benchmark's builder leaves them at their defaults): several blocks of each
# kind at this size.
WALK = {"attn_q_block": 8, "moe_row_block": 32, "seq_block": 2}
GROUPS = ["embed", "final_norm", "head"] + [f"layers/{i}" for i in range(5)]


def _program_loss(sizes, **walk):
    """The builder's ``program_loss`` with other block sizes."""
    cfg = dataclasses.replace(builder.model_config(sizes), **{**WALK, **walk})
    dtype = jnp.dtype(sizes["activation_dtype"])
    return lambda params, mstate, batch: lfm2.next_token_loss(
        params, mstate, batch, cfg, dtype=dtype)


def _both(sizes, key=1):
    """Loss and gradients of the program and of the reference on the same
    seeded weights and batch, at float32 'highest'."""
    with jax.default_matmul_precision("highest"):
        params, state = builder.init(jax.random.key(key), sizes)
        ids = builder.make_batch(jax.random.key(key + 1),
                                 sizes["per_chip_batch"], sizes)
        out = []
        for loss_fn in (_program_loss(sizes),
                        builder.reference_loss(sizes)):
            (loss, new_state), grads = jax.jit(jax.value_and_grad(
                loss_fn, has_aux=True))(params, state, ids)
            out.append((float(loss), grads, new_state))
    return out


@pytest.fixture(scope="module")
def float32_pair():
    return _both(SIZES)


def _rel(a, b):
    """Largest difference of a leaf over the reference leaf's largest
    entry."""
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def _group(tree, name):
    for part in name.split("/"):
        tree = tree[int(part)] if part.isdigit() else tree[part]
    return tree


# Tolerances. In float32 at 'highest' the two differ only in the order of
# sums (grouped product against one product per expert, a sum over four
# chosen rows against a sum over eight masked ones, blocks of queries
# against whole rows of scores): a few units of 2**-24 per sum, through
# five layers. 2e-5 relative is ~300 such units; the bfloat16 run below
# is a thousand times over it.
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
    """The tolerances are tight enough that the program computed in a
    lower precision than the test states fails one of them."""
    (_, _, _), (want_loss, want, _) = float32_pair
    with jax.default_matmul_precision("highest"):
        params, state = builder.init(jax.random.key(1), SIZES)
        ids = builder.make_batch(jax.random.key(2), 4, SIZES)
        low = dict(SIZES, activation_dtype="bfloat16")
        (loss, _), grads = jax.jit(jax.value_and_grad(
            _program_loss(low), has_aux=True))(params, state, ids)
    gaps = jax.tree_util.tree_leaves(jax.tree_util.tree_map(_rel, grads, want))
    assert (abs(float(loss) - want_loss) > LOSS_TOL * want_loss
            or max(gaps) > GRAD_TOL)
    assert max(gaps) > 50 * GRAD_TOL


def test_the_program_reads_the_tree_the_benchmark_makes():
    cfg = builder.model_config(SIZES)
    own, own_state = jax.eval_shape(lambda k: lfm2.init(k, cfg),
                                    jax.random.key(0))
    made, made_state = jax.eval_shape(lambda k: builder.init(k, SIZES),
                                      jax.random.key(0))
    assert (jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), own)
            == jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), made))
    # the program's own state counts the rows multiplied (PR 37) and the
    # rows its second pass summed (PR 39) too; the benchmark's makes the
    # four counters it knows, and the layer goes on with those
    for own_layer, made_layer in zip(own_state["layers"],
                                     made_state["layers"]):
        assert set(own_layer) - set(made_layer) == (
            {"computed", "combined"} if made_layer else set())
        assert {k: own_layer[k] for k in made_layer} == made_layer
    assert len(jax.tree_util.tree_leaves(own)) == 50


def test_the_published_configuration_counts_its_parameters():
    """The share the benchmark's configuration states: 486,062,208
    parameters in 50 leaves, 62 % of them in expert stacks."""
    import json
    import math
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "lfm2-24b-a2b-ep8.json")) as f:
        sizes = json.load(f)
    cfg = builder.model_config(sizes)
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.moe_intermediate_size) == (2048, 11776, 1536)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim) == (32, 8, 64)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.experts_held,
            cfg.first_expert, cfg.conv_L_cache) == (64, 4, 8, 0, 3)
    assert cfg.layer_types == ("conv", "full_attention", "conv", "conv",
                               "conv")
    shapes = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda k: lfm2.init(k, cfg)[0], jax.random.key(0)))
    counts = [math.prod(s.shape) for s in shapes]
    assert len(counts) == 50 and sum(counts) == sizes["parameters_held"]
    assert sum(counts) == 486_062_208
    assert max(counts) == 8 * 2048 * 1536
    assert sum(c for c, s in zip(counts, shapes) if len(s.shape) == 3) \
        == 301_989_888
    # the whole published model through the same Config: 40 layers
    whole = lfm2.Config()
    assert len(whole.layer_types) == 40
    assert whole.layer_types.count("full_attention") == 10
    assert whole.layer_types[:6] == ("conv", "conv", "full_attention",
                                     "conv", "conv", "conv")


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def _expert_layer(key):
    """One expert layer's weights for all 8 experts, a normalised input,
    and the sizes of the uncut layer."""
    sizes = dict(SIZES, num_experts=8, share=0)
    d, f = 32, 16
    ks = jax.random.split(jax.random.key(key), 6)
    whole = {"router": jax.random.normal(ks[0], (d, 8)) * 0.3,
             "w1": jax.random.normal(ks[1], (8, d, f)) * 0.2,
             "w3": jax.random.normal(ks[2], (8, d, f)) * 0.2,
             "w2": jax.random.normal(ks[3], (8, f, d)) * 0.2}
    u = jax.random.normal(ks[4], (3, 16, d))
    bias = jax.random.normal(ks[5], (8,)) * 0.1
    return sizes, whole, u, bias


def _share_of(whole, first, held):
    return {"router": whole["router"],
            **{k: whole[k][first:first + held] for k in ("w1", "w3", "w2")}}


def _state(bias):
    return {"expert_bias": bias, "drawn": jnp.zeros((8,)),
            "held": jnp.zeros(()), "dropped": jnp.zeros(())}


@pytest.mark.parametrize("shares", [1, 2, 4, 8])
def test_the_shares_partial_results_add_up_to_the_whole_layer(shares):
    """Every chip of ``shares`` computes its own experts' part; the parts
    add up to what the uncut plain reference gives for the whole layer
    (nothing is computed alike by all, so nothing is counted twice)."""
    sizes, whole, u, bias = _expert_layer(3)
    held = 8 // shares
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda x: plain._experts(
            whole, bias, x, sizes, first=0))(u)
        total, computed = jnp.zeros_like(u), 0.0
        for share in range(shares):
            cfg = lfm2.tiny(first_expert=share * held, experts_held=held,
                            moe_row_block=24)
            y, counters = lfm2.moe_ffn(
                _share_of(whole, share * held, held), _state(bias), u, cfg)
            total = total + y
            computed += float(counters["held"])
            assert float(counters["dropped"]) == 0.0
            np.testing.assert_array_equal(counters["expert_bias"], bias)
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-6)
    assert computed == u.shape[0] * u.shape[1] * 2     # every assignment once
    assert float(jnp.max(jnp.abs(want))) > 1e-3


def test_a_share_leaves_out_what_the_absent_experts_would_add():
    sizes, whole, u, bias = _expert_layer(4)
    cfg = lfm2.tiny(first_expert=2, experts_held=2)
    part = dict(sizes, num_experts=2, share=1)
    with jax.default_matmul_precision("highest"):
        got, counters = lfm2.moe_ffn(_share_of(whole, 2, 2), _state(bias), u,
                                     cfg)
        want = jax.vmap(lambda x: plain._experts(
            _share_of(whole, 2, 2), bias, x, part,
            first=plain.layout(part)["first"]))(u)
        full = jax.vmap(lambda x: plain._experts(
            whole, bias, x, dict(sizes), first=0))(u)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(got - full))) > 1e-3
    # the counters: what the router drew over all 8, what was held here
    experts, _ = lfm2.route(_share_of(whole, 2, 2), bias,
                            u.reshape(-1, 32), cfg)
    np.testing.assert_array_equal(
        counters["drawn"], np.bincount(np.asarray(experts).ravel(),
                                       minlength=8))
    assert float(counters["drawn"].sum()) == 3 * 16 * 2
    assert float(counters["held"]) == float(
        ((experts >= 2) & (experts < 4)).sum())


@pytest.mark.parametrize("target,row_block", [(0, 96), (1, 32), (1, 8)])
def test_no_assignment_is_dropped_under_a_skewed_router(target, row_block):
    """A bias that sends every token to the held expert ``target`` first:
    all 48 tokens' rows land in one group, past any balanced capacity, and
    every one of them is computed."""
    sizes, whole, u, _ = _expert_layer(5)
    bias = jnp.zeros((8,)).at[2 + target].set(10.0)
    cfg = lfm2.tiny(first_expert=2, experts_held=2, moe_row_block=row_block)
    part = dict(sizes, num_experts=2, share=1)
    with jax.default_matmul_precision("highest"):
        got, counters = jax.jit(lambda p, s, x: lfm2.moe_ffn(p, s, x, cfg))(
            _share_of(whole, 2, 2), _state(bias), u)
        want = jax.vmap(lambda x: plain._experts(
            _share_of(whole, 2, 2), bias, x, part, first=2))(u)
    assert float(counters["drawn"][2 + target]) == 48.0
    assert float(counters["held"]) >= 48.0
    assert float(counters["dropped"]) == 0.0
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_every_token_to_held_experts_fills_the_worst_case_bound():
    """Both of a token's experts held here: the grouped product's rows are
    all in use, which is the bound, and none is dropped."""
    sizes, whole, u, _ = _expert_layer(6)
    bias = jnp.zeros((8,)).at[jnp.array([4, 5])].set(10.0)
    cfg = lfm2.tiny(first_expert=4, experts_held=2, moe_row_block=32)
    _, counters = lfm2.moe_ffn(_share_of(whole, 4, 2), _state(bias), u, cfg)
    assert float(counters["held"]) == 48 * 2 and float(
        counters["dropped"]) == 0.0


def test_expert_gradients_agree_and_the_bias_takes_none():
    sizes, whole, u, bias = _expert_layer(7)
    cfg = lfm2.tiny(first_expert=2, experts_held=2, moe_row_block=24)
    part = dict(sizes, num_experts=2, share=1)
    share = _share_of(whole, 2, 2)

    def program(p, b, x):
        return jnp.sum(jnp.sin(lfm2.moe_ffn(p, _state(b), x, cfg)[0]))

    def reference(p, b, x):
        return jnp.sum(jnp.sin(jax.vmap(lambda row: plain._experts(
            p, b, row, part, first=2))(x)))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(program, argnums=(0, 1, 2))(share, bias, u)
        want = jax.grad(reference, argnums=(0, 1, 2))(share, bias, u)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-6)
    assert float(jnp.max(jnp.abs(got[1]))) == 0.0     # not trained by it
    assert float(jnp.max(jnp.abs(got[0]["router"]))) > 0


# Routers the walk has to take (PR 37): the bias added to the scores of the
# four held experts 2..5 of 8, two a token over 48 tokens, so that a
# balanced router sends 48 rows here and the walk's old block was 96.
ROUTERS = {
    "balanced": (0.0, 0.0, 0.0, 0.0),
    "skewed_to_one": (0.0, 10.0, 0.0, 0.0),
    "one_draws_nothing": (0.0, 0.0, -10.0, 0.0),
    "over_the_old_block": (10.0, 0.3, 0.3, 0.3),
    "worst_case": (10.0, 10.0, 0.0, 0.0),
    # two experts draw every token and two none: the load ends on a tile's
    # boundary (48 rows each) and every window's on a chunk's
    "ends_on_a_boundary": (10.0, 10.0, -10.0, -10.0),
}
TILES = [0, 8, 16, 40, 128]


def _combined_by_hand(experts, cfg, first, held):
    """Rows the second pass gathers, from the routing: every window of
    tokens in whole chunks of its held rows, and at least one."""
    n = experts.shape[0]
    tile = lfm2._tile_rows(cfg, n)
    window, chunk = lfm2._window_tokens(cfg, n, tile), tile
    here = np.asarray((experts >= first) & (experts < first + held)).sum(1)
    per_window = [int(here[i:i + window].sum()) for i in range(0, n, window)]
    return sum(max(1, -(-c // chunk)) * chunk for c in per_window)


def _routed_layer(router):
    sizes, whole, u, bias = _expert_layer(8)
    bias = (bias * 0.1).at[2:6].add(jnp.asarray(ROUTERS[router]))
    return dict(sizes, num_experts=4, share=1), _share_of(whole, 2, 4), u, bias


@pytest.mark.parametrize("tile", TILES)
@pytest.mark.parametrize("router", sorted(ROUTERS))
def test_tiles_compute_every_held_row_and_little_more(router, tile):
    """Result, every leaf's gradient and the counters of the layer walked
    in expert-aligned tiles of ``tile`` rows (0: chosen from the shapes),
    against the plain reference, whatever the router does: nothing is
    dropped, ``held`` is what the routing says, and what is multiplied
    beyond it is less than a tile an expert."""
    part, share, u, bias = _routed_layer(router)
    cfg = lfm2.tiny(first_expert=2, experts_held=4, moe_row_block=tile)
    state = dict(lfm2.expert_layer_state(8), expert_bias=bias)

    def program(p, b, x):
        y, counters = lfm2.moe_ffn(p, dict(state, expert_bias=b), x, cfg)
        return jnp.sum(jnp.sin(y)), (y, counters)

    def reference(p, b, x):
        y = jax.vmap(lambda row: plain._experts(p, b, row, part, first=2))(x)
        return jnp.sum(jnp.sin(y)), y

    with jax.default_matmul_precision("highest"):
        (_, (got, counters)), grads = jax.jit(jax.value_and_grad(
            program, argnums=(0, 2), has_aux=True))(share, bias, u)
        (_, want), want_grads = jax.value_and_grad(
            reference, argnums=(0, 2), has_aux=True)(share, bias, u)
        experts, _ = lfm2.route(share, bias, u.reshape(-1, 32), cfg)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-6)
    drawn = np.bincount(np.asarray(experts).ravel(), minlength=8)
    held = int(drawn[2:6].sum())
    rows = lfm2._tile_rows(cfg, 48)
    assert rows == (tile or 128)
    assert float(counters["dropped"]) == 0.0
    assert float(counters["held"]) == held
    np.testing.assert_array_equal(counters["drawn"], drawn)
    # every held expert's rows in whole tiles of its own
    assert float(counters["computed"]) == sum(
        -(-int(n) // rows) * rows for n in drawn[2:6])
    assert 0 <= float(counters["computed"]) - held < 4 * rows
    # the second pass: every window of tokens in whole chunks of its rows
    assert float(counters["combined"]) == _combined_by_hand(experts, cfg, 2, 4)
    window, chunk = lfm2._window_tokens(cfg, 48, rows), rows
    assert 0 <= float(counters["combined"]) - held <= -(-48 // window) * chunk
    # the routers are what their names say
    mine = np.asarray((experts >= 2) & (experts < 6)).sum(1)
    assert {"balanced": 24 < held < 72 and 0 in mine and 2 in mine,
            "skewed_to_one": drawn[3] == 48,
            "one_draws_nothing": drawn[4] == 0 and held > 0,
            "over_the_old_block": drawn[2] == 48 and 48 < held < 96,
            "worst_case": held == 96 and set(mine) == {2},
            "ends_on_a_boundary": (drawn[2], drawn[3], drawn[4], drawn[5])
            == (48, 48, 0, 0)}[router]


def test_a_state_without_the_new_counter_goes_on_without_it():
    """``computed`` and ``combined`` are counters of the program's own
    state; a state made without them (the benchmark's, from before PR 37)
    comes back with the keys it had, the others unchanged."""
    _, share, u, bias = _routed_layer("balanced")
    cfg = lfm2.tiny(first_expert=2, experts_held=4, moe_row_block=16)
    own = dict(lfm2.expert_layer_state(8), expert_bias=bias)
    assert set(own) == {"expert_bias", "drawn", "held", "computed",
                        "combined", "dropped"}
    assert set(lfm2.init_state(lfm2.tiny())["layers"][1]) == set(own)
    y, counters = lfm2.moe_ffn(share, own, u, cfg)
    y_old, old = lfm2.moe_ffn(share, _state(bias), u, cfg)
    assert set(counters) == set(own) and set(old) == set(_state(bias))
    np.testing.assert_array_equal(y, y_old)
    for name in old:
        np.testing.assert_array_equal(old[name], counters[name])


def test_the_tile_is_chosen_from_the_shapes():
    """A quarter of the rows a balanced router sends one expert, in whole
    multiples of 128, and ``moe_row_block`` as given where it is set: 512
    rows for the published LFM2 share on 32,768 tokens (2,048 an
    expert)."""
    cfg = lfm2.Config(first_expert=0, experts_held=8)
    assert cfg.moe_row_block == 0
    assert lfm2._tile_rows(cfg, 32768) == 32768 * 4 // 64 // 4 == 512
    assert lfm2._tile_rows(cfg, 4096) == 128         # never under 128
    assert lfm2._tile_rows(cfg, 3 * 32768) == 1536
    assert lfm2._tile_rows(dataclasses.replace(cfg, moe_row_block=24),
                           32768) == 24


def test_the_window_is_chosen_from_the_shapes():
    """The second pass's chunk is a tile's rows and its window the tokens
    whose held rows a balanced router makes half a chunk of, in whole
    multiples of 128: 512 tokens in both benchmark cells (chunks of 512 and
    of 384 rows)."""
    cfg = lfm2.Config(first_expert=0, experts_held=8)
    assert lfm2._window_tokens(cfg, 32768, 512) == 512
    kanana = dataclasses.replace(cfg, num_experts=128, num_experts_per_tok=6)
    assert lfm2._tile_rows(kanana, 32768) == 384
    assert lfm2._window_tokens(kanana, 32768, 384) == 512
    assert lfm2._window_tokens(kanana, 4096, 128) == 128        # 170 whole
    assert lfm2._window_tokens(lfm2.tiny(experts_held=4), 48, 8) == 4
    assert lfm2._window_tokens(lfm2.tiny(experts_held=4), 48, 128) == 48
    assert lfm2._window_tokens(lfm2.tiny(experts_held=4), 48, 1) == 1


# Both decoders' routing through the one expert layer: LFM2's two of eight,
# and kanana's six of eight with its scaled gates, six experts held so that
# a token's six slots can all be held here.
DECODERS = {
    "lfm2": dict(first_expert=2, experts_held=4),
    "kanana": dict(first_expert=2, experts_held=6, num_experts_per_tok=6,
                   routed_scaling_factor=2.448, route_eps=1e-20),
}


def _plain_held(w, x, experts, gates, first):
    """The held experts' part, token by token: every held expert's
    feed-forward of every token, weighted by the token's gate for it (zero
    where it was not chosen), summed over the experts in float32. The
    roundings are the program's: products in ``x``'s dtype, the gate cast
    to it."""
    total = jnp.zeros(x.shape, jnp.float32)
    for e in range(w["w1"].shape[0]):
        we = {k: v[e].astype(x.dtype) for k, v in w.items()}
        out = (jax.nn.silu(x @ we["w1"]) * (x @ we["w3"])) @ we["w2"]
        g = jnp.sum(jnp.where(experts == first + e, gates, 0.0), axis=-1)
        total = total + (out * g[:, None].astype(x.dtype)).astype(jnp.float32)
    return total.astype(x.dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("router", sorted(ROUTERS))
@pytest.mark.parametrize("decoder", sorted(DECODERS))
def test_rows_and_gates_travel_by_sorts_and_gathers(monkeypatch, decoder,
                                                    router, dtype):
    """The result and the gradients of the weights, the rows **and the
    gates** (back through the sort that carried them) against the plain
    per-token spelling at the same routing, in float32 and in bfloat16,
    for tokens that hold all their slots here, some and none; and the
    buffers nothing fills hold no number the result reads: filled with
    NaN, the layer gives the same bits."""
    sizes, whole, u, bias = _expert_layer(9)
    kw = DECODERS[decoder]
    first, held = kw["first_expert"], kw["experts_held"]
    cfg = lfm2.tiny(moe_row_block=8, **kw)
    bias = (bias * 0.1).at[2:6].add(jnp.asarray(ROUTERS[router]))
    share = _share_of(whole, first, held)
    w = {k: share[k] for k in ("w1", "w3", "w2")}
    x = u.reshape(-1, 32).astype(dtype)
    with jax.default_matmul_precision("highest"):
        experts, gates0 = lfm2.route(share, bias, x, cfg)
    state = dict(lfm2.expert_layer_state(8), expert_bias=bias)
    weight = jnp.cos(jnp.arange(48 * 32, dtype=jnp.float32)).reshape(48, 32)

    def program(w, x, gates):
        monkeypatch.setattr(lfm2, "route", lambda *a: (experts, gates))
        y, counters = lfm2.moe_ffn(dict(w, router=share["router"]), state,
                                   x[None], cfg)
        return jnp.sum(y[0].astype(jnp.float32) * weight), (y[0], counters)

    def reference(w, x, gates):
        y = _plain_held(w, x, experts, gates, first)
        return jnp.sum(y.astype(jnp.float32) * weight), y

    with jax.default_matmul_precision("highest"):
        (_, (got, counters)), grads = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1, 2), has_aux=True))(w, x, gates0)
        (_, want), want_grads = jax.jit(jax.value_and_grad(
            reference, argnums=(0, 1, 2), has_aux=True))(w, x, gates0)
        monkeypatch.setattr(lfm2, "_scratch",
                            lambda shape, dt: jnp.full(shape, jnp.nan, dt))
        (_, (unfilled, _)), unfilled_grads = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1, 2), has_aux=True))(w, x, gates0)
    # float32: another order of sums; bfloat16: a product of a tile's rows
    # and the same product of all rows differ in an addend's last bit
    rtol, atol = {"float32": (2e-4, 2e-6), "bfloat16": (2e-2, 1e-2)}[dtype]
    f32 = lambda a: np.asarray(a.astype(jnp.float32))   # noqa: E731
    np.testing.assert_allclose(f32(got), f32(want), rtol=rtol, atol=atol)
    for g, r in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        assert g.dtype == r.dtype and g.shape == r.shape
        np.testing.assert_allclose(f32(g), f32(r), rtol=rtol,
                                   atol=atol * float(jnp.max(jnp.abs(r)) + 1))
    # the gates' gradient: zero where the slot's expert is not held, and
    # something where it is
    mine = np.asarray((experts >= first) & (experts < first + held))
    dgates = np.asarray(grads[2])
    assert dgates.shape == mine.shape and not dgates[~mine].any()
    assert mine.any() and np.abs(dgates[mine]).min() > 0
    np.testing.assert_array_equal(f32(got), f32(unfilled))
    for g, r in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(unfilled_grads)):
        np.testing.assert_array_equal(f32(g), f32(r))
    k = cfg.num_experts_per_tok
    assert float(counters["held"]) == mine.sum()
    assert float(counters["combined"]) == _combined_by_hand(
        experts, cfg, first, held)
    if router == "worst_case" and decoder == "lfm2":
        assert set(mine.sum(1)) == {k}          # every slot of every token
    if router == "balanced":
        assert mine.sum(1).min() < mine.sum(1).max()


def test_a_share_outside_the_routers_experts_is_refused():
    with pytest.raises(ValueError, match="not among"):
        lfm2.tiny(first_expert=7, experts_held=2)
    with pytest.raises(ValueError, match="unknown layer types"):
        lfm2.tiny(layer_types=("conv", "mamba"))


# ---------------------------------------------------------------------------
# the short convolution, attention, the walk
# ---------------------------------------------------------------------------

def _conv_weights(key, d=8):
    ks = jax.random.split(jax.random.key(key), 4)
    return {"in_proj": jax.random.normal(ks[0], (d, 3 * d)),
            "kernel": jax.random.normal(ks[1], (3, d)),
            "out_proj": jax.random.normal(ks[2], (d, d))}, ks[3]


def test_short_convolution_reads_neither_the_future_nor_another_sequence():
    cfg = lfm2.tiny(hidden_size=8)
    p, key = _conv_weights(11)
    u = jax.random.normal(key, (2, 10, 8))
    base = lfm2.short_conv(p, u, cfg)
    later = lfm2.short_conv(p, u.at[:, 6:].add(1.0), cfg)
    np.testing.assert_array_equal(base[:, :6], later[:, :6])
    assert float(jnp.max(jnp.abs(base[:, 6:] - later[:, 6:]))) > 0
    # the other sequence of the batch changes nothing here
    other = lfm2.short_conv(p, u.at[1].add(1.0), cfg)
    np.testing.assert_array_equal(base[0], other[0])
    # position t reads t-2..t and no further back
    back = lfm2.short_conv(p, u.at[:, 2].add(1.0), cfg)
    np.testing.assert_array_equal(base[:, 5:], back[:, 5:])
    assert float(jnp.max(jnp.abs(base[:, 4] - back[:, 4]))) > 0


def test_short_convolution_starts_each_sequence_on_zeros():
    """Two sequences laid end to end as one differ from the two apart at
    the second one's first two positions, and only there: nothing of a
    sequence reaches the next one of the batch."""
    cfg = lfm2.tiny(hidden_size=8)
    p, key = _conv_weights(12)
    u = jax.random.normal(key, (2, 6, 8))
    apart = lfm2.short_conv(p, u, cfg)
    joined = lfm2.short_conv(p, u.reshape(1, 12, 8), cfg)[0]
    np.testing.assert_allclose(apart[0], joined[:6], rtol=1e-6)
    np.testing.assert_allclose(apart[1, 2:], joined[8:], rtol=1e-6)
    assert float(jnp.max(jnp.abs(apart[1, :2] - joined[6:8]))) > 1e-3
    want = jax.vmap(lambda x: plain._conv(p, x, {"conv_L_cache": 3}))(u)
    np.testing.assert_allclose(apart, want, rtol=1e-5, atol=1e-6)


def test_the_model_is_causal():
    cfg = lfm2.tiny()
    params, state = lfm2.init(jax.random.key(0), cfg)
    ids = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    base, _ = lfm2.hidden_states(params, state, ids, cfg)
    later, _ = lfm2.hidden_states(
        params, state, ids.at[:, 9:].set((ids[:, 9:] + 1) % cfg.vocab_size),
        cfg)
    np.testing.assert_array_equal(base[:, :9], later[:, :9])
    assert float(jnp.max(jnp.abs(base[:, 9:] - later[:, 9:]))) > 0


def test_attention_agrees_with_the_plain_reference():
    cfg = lfm2.tiny(attn_q_block=4)
    p = lfm2.init(jax.random.key(2), cfg)[0]["layers"][1]["op"]
    p = jax.tree_util.tree_map(
        lambda x: x * 8 if x.ndim == 2 else x + 0.1 * jnp.arange(x.size), p)
    u = jax.random.normal(jax.random.key(3), (2, 16, 32))
    sizes = dict(SIZES)
    with jax.default_matmul_precision("highest"):
        got = lfm2.attention(p, u, cfg)
        want = jax.vmap(lambda x: plain._attention(p, x, sizes, 8))(u)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_rotary_positions_rotate_half_the_whole_head():
    x = jax.random.normal(jax.random.key(4), (5, 2, 8))
    got = L.rotary(x, 1e6)
    np.testing.assert_allclose(got, plain._rotate(x, 1e6), rtol=1e-6)
    np.testing.assert_allclose(got[0], x[0], rtol=1e-6)      # position 0
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # the dot of a rotated query and key depends on their distance alone
    q = jnp.broadcast_to(x[:1], x.shape)
    r = L.rotary(q, 1e6)
    np.testing.assert_allclose(jnp.sum(r[1] * r[3]), jnp.sum(r[2] * r[4]),
                               rtol=1e-5)


def test_rms_norm():
    x = jax.random.normal(jax.random.key(5), (3, 16)) * 4
    p = {"scale": jnp.linspace(0.5, 2.0, 16)}
    got = L.rms_apply(p, x, 1e-5)
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * p["scale"]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert L.rms_apply(p, x.astype(jnp.bfloat16)).dtype == jnp.bfloat16
    assert set(L.rms_init(16)) == {"scale"}


@pytest.mark.parametrize("walk", [
    {"seq_block": 1}, {"seq_block": 4}, {"attn_q_block": 16},
    {"attn_q_block": 4}, {"moe_row_block": 8}, {"moe_row_block": 128},
    {"moe_row_block": 0}, {"moe_row_block": 24}, {"moe_row_block": 56}])
def test_walking_the_work_in_other_blocks_changes_nothing(walk, float32_pair):
    (want_loss, want, _), _ = float32_pair
    with jax.default_matmul_precision("highest"):
        params, state = builder.init(jax.random.key(1), SIZES)
        ids = builder.make_batch(jax.random.key(2), 4, SIZES)
        (loss, _), grads = jax.jit(jax.value_and_grad(
            _program_loss(SIZES, **walk), has_aux=True))(params, state, ids)
    assert abs(float(loss) - want_loss) <= LOSS_TOL * want_loss
    gaps = jax.tree_util.tree_leaves(jax.tree_util.tree_map(_rel, grads, want))
    assert max(gaps) <= GRAD_TOL


def test_blocks_that_do_not_divide_the_batch_are_refused():
    cfg = lfm2.tiny(seq_block=3)
    params, state = lfm2.init(jax.random.key(0), cfg)
    ids = jnp.zeros((4, 8), jnp.int32)
    with pytest.raises(ValueError, match="do not divide"):
        lfm2.next_token_loss(params, state, ids, cfg)


# ---------------------------------------------------------------------------
# what a recomputed part keeps (PR 33): nothing, where there is no kernel
# ---------------------------------------------------------------------------

def _gradient_texts(loss, *args):
    """The gradient's jaxpr and lowered text of ``loss``, through a function
    of its own each time (``keep_nothing``). The jaxpr prints the policy's
    address on a line of its own: left out."""
    grad = jax.value_and_grad(lambda *a: loss(*a), argnums=(0, 1))
    jaxpr = "\n".join(line for line in str(jax.make_jaxpr(grad)(*args))
                      .splitlines() if "policy=" not in line)
    return jaxpr, jax.jit(grad).lower(*args).as_text()


def _part_case(which):
    """A part of a tiny decoder without the fused kernel, as the step walks
    it (four sequences, one at a time): ``(loss, weights, input)``."""
    cfg = lfm2.tiny()
    params, _ = lfm2.init(jax.random.key(3), cfg)
    x = jax.random.normal(jax.random.key(4), (4, 16, cfg.hidden_size))
    kind = {"short_conv": "conv", "plain_attention": "full_attention"}.get(
        which)
    part, layer = ((lfm2._dense_part(cfg), 0) if kind is None else
                   (lfm2._operator_part(kind, cfg),
                    cfg.layer_types.index(kind)))
    return (lambda p, x: jnp.sum(lfm2._over_sequences(part, p, x, 1) ** 2),
            params["layers"][layer], x)


@pytest.mark.parametrize("which", ["short_conv", "dense_ffn",
                                   "plain_attention"])
def test_a_part_without_the_kernel_keeps_nothing(keep_nothing, which):
    """``_over_sequences`` keeps what the fused attention kernel names. A
    part that does not hold the kernel (here, on the CPU, attention too:
    ``engages`` says no and the scores go through their plain blocks)
    names nothing, so its gradient is, equation for equation and in the
    lowered text, what a ``jax.checkpoint`` that keeps nothing gives."""
    loss, *args = _part_case(which)
    jaxpr, lowered = _gradient_texts(loss, *args)
    assert pallas_attention.RESIDUAL_NAME not in jaxpr
    assert "pallas_call" not in jaxpr
    keep_nothing()
    plain_jaxpr, plain_lowered = _gradient_texts(loss, *args)
    assert jaxpr == plain_jaxpr
    assert lowered == plain_lowered
    assert "remat" in jaxpr       # the part is recomputed, as before


def test_the_whole_model_is_the_same_bits_under_a_plain_checkpoint(
        keep_nothing, float32_pair):
    """All parts together (the lowered text of the whole model names its
    shared functions by the order JAX met them, so here the numbers are
    compared): loss and every gradient are the bits a ``jax.checkpoint``
    that keeps nothing gives."""
    (want_loss, want, _), _ = float32_pair
    keep_nothing()
    with jax.default_matmul_precision("highest"):
        params, state = builder.init(jax.random.key(1), SIZES)
        ids = builder.make_batch(jax.random.key(2), 4, SIZES)
        (loss, _), grads = jax.jit(jax.value_and_grad(
            _program_loss(SIZES), has_aux=True))(params, state, ids)
    assert float(loss) == want_loss
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
    return {"losses": losses, "state": state, "world": world, "text": text,
            "grace": grace, "tokens": 2 * sizes["seq_length"]}


def test_the_compressed_step_trains_the_model(trained):
    losses = trained["losses"]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.01


def test_the_model_state_carries_bias_and_counters_through_the_step(trained):
    layers = trained["state"].model_state["layers"]
    assert layers[0] == {} and len(layers) == 5
    for layer in layers[1:]:
        assert set(layer) == {"expert_bias", "drawn", "held", "dropped"}
        # the step's mean over replicas: each replica drew 2 for each of
        # its tokens over the router's 8 experts
        assert float(layer["drawn"].sum()) == pytest.approx(
            trained["tokens"] * 2)
        assert 0 <= float(layer["held"]) <= trained["tokens"] * 2
        assert float(layer["dropped"]) == 0.0
        assert float(jnp.max(jnp.abs(layer["expert_bias"]))) == 0.0


def test_wire_report_reads_the_expert_stacks(trained):
    from grace_tpu.utils.metrics import wire_report
    report = wire_report(trained["grace"].compressor, trained["state"].params)
    stacks = [leaf for leaf in report.leaves if leaf.path.endswith("['w1']")
              and "layers'][1]" in leaf.path]
    assert len(report.leaves) == 50 and len(stacks) == 1
    assert stacks[0].dense_bytes == 2 * 32 * 16 * 4
    k = max(1, int(2 * 32 * 16 * 0.05))
    assert stacks[0].wire_bytes == k * 8                 # a value, an index
    assert 0 < report.wire_bytes < report.dense_bytes


def test_every_part_of_the_step_is_under_its_stage(trained):
    text = trained["text"]
    # the other decoders' six are not in this step
    others = (scopes.STAGE_MLA_LATENT, scopes.STAGE_SHARED_EXPERT,
              scopes.STAGE_DIFFUSION_NOISE, scopes.STAGE_WINDOW_ATTENTION,
              scopes.STAGE_GATED_DELTA, scopes.STAGE_DELTA_RULE)
    assert all(stage not in text for stage in others)
    for stage in set(scopes.MODEL_STAGES) - set(others):
        assert stage in text, stage
        assert STAGE.fullmatch(stage), stage             # the reducer reads it
        assert stage in scopes.ALL_STAGES
    # the rightmost scope names the part: forward, recomputation, backward
    name = ("jit(device_step)/grace/forward_backward/transpose(jvp("
            "grace/moe_experts))/checkpoint/ragged_dot")
    assert stage_of(name) == "grace/moe_experts"
    assert scopes.match_stage(name) == scopes.STAGE_MOE_EXPERTS
    assert scopes.match_stage(
        "grace/forward_backward/jvp(grace/short_conv)/dot") \
        == scopes.STAGE_SHORT_CONV
    assert len(set(scopes.ALL_STAGES)) == len(scopes.ALL_STAGES) == 30
