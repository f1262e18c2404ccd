"""``grace_tpu.models.smallthinker`` against the plain reference
(``benchmarks/reference/smallthinker_moe.py``) at a small size on the CPU,
and what the architecture promises: a layer's mask and whether it rotates
are its entries of the two layouts (a full layer without positions beside
windowed rotary ones, a window shorter than the sequence), the router reads
the layer's input and nothing later, the experts' gate is a ReLU through
the hand-written backward, the eight shares of an expert layer add up to
the whole layer, and the compressed step carries its counters under its
stages.
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

from benchmarks.models import smallthinker_moe as builder  # noqa: E402
from benchmarks.reference import smallthinker_moe as plain  # noqa: E402
from benchmarks.trace_reduce import STAGE, stage_of  # noqa: E402
from grace_tpu.models import layers as L  # noqa: E402
from grace_tpu.models import lfm2, sdar, smallthinker  # noqa: E402
from grace_tpu.ops.pallas_attention import CAUSAL, SlidingWindow  # noqa: E402
from grace_tpu.telemetry import scopes  # noqa: E402

# A share of a small model in the configuration file's own keys: 4 experts
# held (experts 4-7) of the 8 the router scores, 2 a token; 4 | 2 heads of
# 8; one period of the layouts (a full layer without positions, three
# windowed rotary ones), a window of 8 over 32 tokens.
SIZES = {
    "hidden_size": 32, "moe_ffn_hidden_size": 16,
    "moe_num_primary_experts": 4, "moe_num_active_primary_experts": 2,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "rms_norm_eps": 1e-6,
    "rope_theta": 1500000, "rope_layout": [0, 1, 1, 1],
    "sliding_window_layout": [0, 1, 1, 1], "sliding_window_size": 8,
    "vocab_size": 128, "published": {"moe_num_primary_experts": 8},
    "share": 1, "seq_length": 32, "per_chip_batch": 4,
    "activation_dtype": "float32", "param_dtype": "float32"}
# several blocks of each kind at this size: four blocks of queries (the
# later ones start past the window's first key), two parts of the head
WALK = {"attn_q_block": 8, "moe_row_block": 16, "seq_block": 2,
        "head_positions": 16}
GROUPS = ["embed", "final_norm", "head"] + [f"layers/{i}" for i in range(4)]


def _program_loss(sizes, **walk):
    cfg = dataclasses.replace(builder.model_config(sizes), **{**WALK, **walk})
    dtype = jnp.dtype(sizes["activation_dtype"])
    return lambda params, mstate, batch: smallthinker.next_token_loss(
        params, mstate, batch, cfg, dtype=dtype)


def _run(loss_fn, sizes=SIZES, key=1):
    with jax.default_matmul_precision("highest"):
        params, state = builder.init(jax.random.key(key), sizes)
        batch = builder.make_batch(jax.random.key(key + 1),
                                   sizes["per_chip_batch"], sizes)
        (loss, new_state), grads = jax.jit(jax.value_and_grad(
            loss_fn, has_aux=True))(params, state, batch)
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


# ---------------------------------------------------------------------------
# against the plain reference
# ---------------------------------------------------------------------------

# float32 with every product at ``highest``: program and reference compute
# the same mathematics in another order (tiles of sorted rows against one
# expert after another, blocks of queries against heads one by one, the
# gates by a compare and a sum against a masked table): a few units of
# 2**-24 a sum. The bfloat16 run below is a thousand times over.
LOSS_TOL = 2e-6
GRAD_TOL = 2e-5


def test_loss_agrees_with_the_plain_reference(float32_pair):
    (got, _, _), (want, _, _) = float32_pair
    assert abs(got - want) <= LOSS_TOL * abs(want)
    assert 4.0 < want < 6.0                    # ln 128 = 4.85 a token


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


def test_bfloat16_activations_stay_within_their_rounding(float32_pair):
    """With bfloat16 activations (what the configuration states) the
    program is held to the float32 reference by the activations' rounding,
    a few parts in a thousand of the loss and a few in a hundred of a
    gradient leaf: a thousand times outside the float32 tolerances, so
    those would catch a program that computes in the lower precision. A
    router is the exception: its scores are made in bfloat16, so a token
    near a tie takes another expert than in the reference, and at 128
    tokens one such token is a fifth of an expert stack's largest entry
    (the expert stacks and the norm before them read 0.04-0.21 here, every
    other leaf under 0.01)."""
    _, (want_loss, want, _) = float32_pair
    low = dict(SIZES, activation_dtype="bfloat16")
    loss, grads, _ = _run(_program_loss(low))
    gaps = jax.tree_util.tree_map(_rel, grads, want)
    routers = [layer["ffn"].pop("router") for layer in gaps["layers"]]
    gaps = jax.tree_util.tree_leaves(gaps)
    assert abs(loss - want_loss) <= 5e-3 * want_loss
    assert max(gaps) <= 0.3 and float(np.median(gaps)) <= 0.03
    assert max(routers) <= 0.8
    assert max(gaps) > 50 * GRAD_TOL


@pytest.mark.parametrize("walk", [{"seq_block": 4}, {"attn_q_block": 32},
                                  {"moe_row_block": 0},
                                  {"head_positions": 32}])
def test_walking_the_work_in_other_blocks_changes_nothing(walk, float32_pair):
    (want_loss, want, _), _ = float32_pair
    loss, grads, _ = _run(_program_loss(SIZES, **walk))
    assert abs(loss - want_loss) <= LOSS_TOL * want_loss
    gaps = jax.tree_util.tree_leaves(jax.tree_util.tree_map(_rel, grads, want))
    assert max(gaps) <= GRAD_TOL


def test_the_references_blocks_change_nothing(monkeypatch, float32_pair):
    """The reference scores ``Q_ROWS`` queries at a time over the keys such
    a block can read (all of them in a full layer, ``Q_ROWS + window - 1``
    under a window, clamped to the sequence) and walks the head in parts of
    as many positions: four blocks of 8 over these 32 positions, where the
    fixture's run had one, give the same loss and gradients."""
    _, (want_loss, want, _) = float32_pair
    monkeypatch.setattr(plain, "Q_ROWS", 8)
    loss, grads, _ = _run(builder.reference_loss(SIZES))
    assert abs(loss - want_loss) <= LOSS_TOL * want_loss
    gaps = jax.tree_util.tree_leaves(jax.tree_util.tree_map(_rel, grads, want))
    assert max(gaps) <= GRAD_TOL


def test_a_head_part_that_does_not_divide_the_sequence_is_refused():
    with pytest.raises(ValueError, match="whole parts"):
        _run(_program_loss(SIZES, head_positions=12))


def test_the_program_reads_the_tree_the_benchmark_makes():
    cfg = builder.model_config(SIZES)
    bench, bench_state = jax.eval_shape(
        lambda k: builder.init(k, SIZES), jax.random.key(0))
    own, own_state = jax.eval_shape(lambda k: smallthinker.init(k, cfg),
                                    jax.random.key(0))
    assert (jax.tree_util.tree_structure(bench)
            == jax.tree_util.tree_structure(own))
    assert jax.tree_util.tree_map(lambda a: a.shape, bench) \
        == jax.tree_util.tree_map(lambda a: a.shape, own)
    # no norm on the heads, no bias anywhere, no shared expert
    assert set(own["layers"][0]["attn"]) == {"q_proj", "k_proj", "v_proj",
                                             "o_proj"}
    assert set(own["layers"][0]["ffn"]) == {"router", "w1", "w3", "w2"}
    assert set(own_state) == set(bench_state) == {"layers"}
    assert set(bench_state["layers"][0]) == {"held", "dropped", "computed",
                                             "combined"}
    assert set(own_state["layers"][0]) == {"drawn", "held", "dropped",
                                           "computed", "combined"}


def test_the_initialisation_keeps_the_stream_its_tokens():
    """Both makers of weights: embedding rows of std 1, the two projections
    that write to the residual stream at 0.02 / sqrt(2 * published layers),
    every norm's weight 1, every other matrix at 0.02."""
    sizes = dict(SIZES, hidden_size=256, vocab_size=512,
                 published=dict(SIZES["published"], num_hidden_layers=52))
    cfg = builder.model_config(sizes)
    assert cfg.published_layers == 52
    made = {"benchmark": builder.init(jax.random.key(0), sizes)[0],
            "program": smallthinker.init(jax.random.key(0), cfg)[0]}
    out = 0.02 / np.sqrt(2 * 52)
    for name, params in made.items():
        table = np.asarray(params["embed"]["table"])
        assert 0.8 < table.std() < 0.95, name           # truncated at 2 sd
        layer = params["layers"][1]
        for leaf in (layer["attn"]["o_proj"], layer["ffn"]["w2"]):
            assert 0.8 * out < float(jnp.std(leaf)) < 0.95 * out, name
        for leaf in (layer["attn"]["q_proj"], layer["ffn"]["w1"],
                     layer["ffn"]["router"], params["head"]):
            assert 0.016 < float(jnp.std(leaf)) < 0.019, name
        for norm in (layer["attn_norm"], layer["ffn_norm"],
                     params["final_norm"]):
            np.testing.assert_array_equal(norm["scale"], 1.0, name)
    # a configuration that states no published depth holds all its layers
    own = builder.init(jax.random.key(0), SIZES)[0]["layers"][0]
    assert float(jnp.std(own["attn"]["o_proj"])) == pytest.approx(
        0.88 * 0.02 / np.sqrt(8), rel=0.1)


def test_the_published_configuration_counts_its_parameters():
    import json
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "smallthinker-21b-a3b-ep8.json")) as f:
        sizes = json.load(f)
    cfg = builder.model_config(sizes)
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert,
            cfg.num_experts_per_tok) == (64, 8, 0, 6)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim, cfg.hidden_size) == (28, 4, 128, 2560)
    assert (cfg.sliding_window_size, cfg.rope_theta) == (4096, 1.5e6)
    assert [cfg.mask_of(i) for i in range(4)] == [CAUSAL] + 3 * [
        SlidingWindow(4096)]
    shapes = jax.eval_shape(lambda k: smallthinker.init(k, cfg)[0],
                            jax.random.key(0))
    counts = [int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)]
    layer = 2 * 9_175_040 + 2 * 1_310_720 + 163_840 + 47_185_920 + 5_120
    assert layer == 68_326_400
    assert sum(counts) == 4 * layer + 2 * 48_619_520 + 2_560 \
        == sizes["parameters_held"] == 370_547_200
    assert len(counts) == 4 * 10 + 3


def test_the_published_model_is_the_configs_defaults():
    cfg = smallthinker.Config()
    assert cfg.num_hidden_layers == cfg.published_layers == 52
    assert cfg.sliding_window_layout == cfg.rope_layout == (0, 1, 1, 1) * 13
    assert sum(cfg.sliding_window_layout) == 39                 # 1 : 3


# ---------------------------------------------------------------------------
# the window's mask
# ---------------------------------------------------------------------------

def test_the_windows_mask_is_the_brute_force_loop():
    """Query ``i`` reads key ``j`` iff ``0 <= i - j < window``: the query's
    own position counts, so a window of 3 reads 3 keys; by a loop, for
    numpy positions (the tiles to visit) and jax ones (inside a tile)."""
    mask = SlidingWindow(3)
    want = np.zeros((8, 8), bool)
    for i in range(8):
        for j in range(8):
            want[i, j] = 0 <= i - j < 3
    assert list(np.flatnonzero(want[5])) == [3, 4, 5]
    assert list(np.flatnonzero(want[1])) == [0, 1]
    assert want.sum() == 21
    ids = np.arange(8)
    np.testing.assert_array_equal(mask.allowed(ids[:, None], ids[None, :]),
                                  want)
    np.testing.assert_array_equal(
        np.asarray(mask.allowed(jnp.arange(8)[:, None],
                                jnp.arange(8)[None, :])), want)
    # a window as long as the sequence is the causal mask
    np.testing.assert_array_equal(
        SlidingWindow(8).allowed(ids[:, None], ids[None, :]),
        CAUSAL.allowed(ids[:, None], ids[None, :]))
    with pytest.raises(ValueError, match="reads nothing"):
        SlidingWindow(0)


def test_the_plain_path_reads_a_windows_keys_alone():
    """The keys a block of queries can read: under the causal mask the
    prefix up to the block's end; under a window those from the first key
    the block's first query reads. Blocks of 8 queries over 32 positions
    under a window of 8 are handed 8, 15, 15 and 15 keys, and the result
    is the full table's."""
    mask = SlidingWindow(8)
    assert [mask.first_key(s) for s in (0, 8, 16, 24)] == [0, 1, 9, 17]
    assert [mask.keys_read(s + 8, 32) for s in (0, 8, 16, 24)] \
        == [8, 16, 24, 32]
    assert [CAUSAL.first_key(s) for s in (0, 8, 16, 24)] == [0, 0, 0, 0]
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, 32, 4, 8))
    k = jax.random.normal(ks[1], (2, 32, 2, 8))
    v = jax.random.normal(ks[2], (2, 32, 2, 8))
    with jax.default_matmul_precision("highest"):
        got = lfm2._scores_in_blocks(q, k, v, 8, mask)
        ids = jnp.arange(32)
        table = mask.allowed(ids[:, None], ids[None, :])
        s = jnp.einsum("nqhgd,nkhd->nhgqk", q.reshape(2, 32, 2, 2, 8),
                       k) / np.sqrt(8)
        a = jax.nn.softmax(jnp.where(table, s, -jnp.inf), axis=-1)
        want = jnp.einsum("nhgqk,nkhd->nqhgd", a, v).reshape(2, 32, 4, 8)
        causal = lfm2._scores_in_blocks(q, k, v, 8)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the first window's queries read what the causal mask lets them
    np.testing.assert_allclose(got[:, :8], causal[:, :8], rtol=1e-6)
    assert float(jnp.max(jnp.abs(got[:, 8:] - causal[:, 8:]))) > 1e-2
    # and the reference's own spelling of the mask is the same table
    np.testing.assert_array_equal(
        np.asarray(plain.may_read(ids[:, None], ids[None, :], 8)),
        np.asarray(table))
    np.testing.assert_array_equal(
        np.asarray(plain.may_read(ids[:, None], ids[None, :], None)),
        np.asarray(CAUSAL.allowed(ids[:, None], ids[None, :])))


# ---------------------------------------------------------------------------
# a layer's kind is its entries of the two layouts
# ---------------------------------------------------------------------------

def _attention_inputs(key=0):
    cfg = smallthinker.tiny()
    params, _ = smallthinker.init(jax.random.key(key), cfg)
    x = jax.random.normal(jax.random.key(key + 1), (2, 32, 32))
    return cfg, params, x


def test_a_layer_without_positions_does_not_rotate():
    """Layer 0 (``rope_layout`` 0) is blind to where its keys stand but for
    the mask: its last query's result does not change when the keys before
    it are permuted; layer 1 (rotary) does."""
    cfg, params, x = _attention_inputs()
    order = np.concatenate([np.random.RandomState(0).permutation(31), [31]])

    def last(layer, cfg):
        part = smallthinker._attention_part(cfg, layer)
        p = params["layers"][layer]
        return part(p, x)[:, -1] - x[:, -1], \
            part(p, x[:, order])[:, -1] - x[:, -1]

    with jax.default_matmul_precision("highest"):
        a, b = last(0, cfg)
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)
        # the same layer made rotary, still reading the whole prefix
        rotary = dataclasses.replace(cfg, rope_layout=(1, 1, 1, 1))
        a, b = last(0, rotary)
        assert _rel(a, b) > 1e-2


def test_a_windowed_layer_reads_its_window_and_no_further():
    """Layer 1's query at position 20 reads keys 13-20 (a window of 8):
    changing position 12's input leaves its result as it was, changing
    position 13's does not; layer 0 reads both."""
    cfg, params, x = _attention_inputs(3)

    def at_20(layer, x):
        # what attention adds to the stream there
        return smallthinker._attention_part(cfg, layer)(
            params["layers"][layer], x)[:, 20] - x[:, 20]

    bumped12 = x.at[:, 12].add(1.0)
    bumped13 = x.at[:, 13].add(1.0)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_array_equal(at_20(1, x), at_20(1, bumped12))
        assert _rel(at_20(1, bumped13), at_20(1, x)) > 1e-4
        assert _rel(at_20(0, bumped12), at_20(0, x)) > 1e-4


def test_the_two_layouts_must_agree_in_length():
    with pytest.raises(ValueError, match="an entry a layer"):
        smallthinker.tiny(rope_layout=(0, 1, 1))
    with pytest.raises(ValueError, match="0 or 1"):
        smallthinker.tiny(rope_layout=(0, 1, 2, 1))
    with pytest.raises(ValueError, match="not among the router's"):
        smallthinker.tiny(first_expert=6, experts_held=4)


# ---------------------------------------------------------------------------
# the router reads the layer's input
# ---------------------------------------------------------------------------

def test_the_router_reads_the_layers_input_before_norm_and_attention():
    """One layer by hand from the module's three equations, the routing
    worked out from ``x`` itself: ``hidden_states`` gives that. Routed from
    the normed input, or from the stream after attention, the experts
    chosen differ (so this fails if the router is moved), and the counters
    come from the same routing."""
    cfg = smallthinker.tiny(sliding_window_layout=(1,), rope_layout=(1,))
    params, state = smallthinker.init(jax.random.key(5), cfg)
    # norm weights away from 1, so that the normed input is another vector,
    # and writes to the stream large enough to tell the routings apart
    p = params["layers"][0]
    p["attn_norm"]["scale"] = 1.0 + jax.random.normal(jax.random.key(6),
                                                      (32,))
    p["attn"]["o_proj"] = p["attn"]["o_proj"] * 50.0
    p["ffn"]["w2"] = p["ffn"]["w2"] * 50.0
    ids = jax.random.randint(jax.random.key(7), (2, 32), 0, 128)

    def by_hand(routed_from):
        x = L.embedding_apply(params["embed"], ids, dtype=jnp.float32)
        u = L.rms_apply(p["attn_norm"], x, cfg.norm_eps)
        h = x + lfm2.attention(p["attn"], u, cfg, SlidingWindow(8))
        m = L.rms_apply(p["ffn_norm"], h, cfg.norm_eps)
        source = {"input": x, "normed": u, "after": h}[routed_from]
        experts, gates = sdar.route(p["ffn"], None, source.reshape(-1, 32),
                                    cfg)
        y = jnp.zeros((64, 32))
        flat = m.reshape(-1, 32)
        for slot in range(2):
            for e in range(8):
                out = (jax.nn.relu(flat @ p["ffn"]["w1"][e])
                       * (flat @ p["ffn"]["w3"][e])) @ p["ffn"]["w2"][e]
                y = y + jnp.where((experts[:, slot] == e)[:, None],
                                  gates[:, slot, None] * out, 0.0)
        return h + y.reshape(h.shape), experts

    with jax.default_matmul_precision("highest"):
        got, new_state = smallthinker.hidden_states(params, state, ids, cfg)
        want, experts = by_hand("input")
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
        for moved in ("normed", "after"):
            other, chosen = by_hand(moved)
            assert (np.sort(np.asarray(chosen), axis=1)
                    != np.sort(np.asarray(experts), axis=1)).any()
            assert _rel(other, want) > 1e-3
    drawn = np.bincount(np.asarray(experts).reshape(-1), minlength=8)
    np.testing.assert_array_equal(new_state["layers"][0]["drawn"], drawn)
    assert float(new_state["layers"][0]["held"]) == 2 * 64


# ---------------------------------------------------------------------------
# ReLU through the walk's hand-written backward; the shares
# ---------------------------------------------------------------------------

def _expert_layer(key):
    """One expert layer's weights for all 8 experts, the layer's input (what
    the router reads) and the normalised stream after attention (what the
    experts read), and the sizes of the uncut layer."""
    sizes = dict(SIZES, moe_num_primary_experts=8, share=0)
    d, f = 32, 16
    ks = jax.random.split(jax.random.key(key), 6)
    whole = {"router": jax.random.normal(ks[0], (d, 8)) * 0.3,
             "w1": jax.random.normal(ks[1], (8, d, f)) * 0.2,
             "w3": jax.random.normal(ks[2], (8, d, f)) * 0.2,
             "w2": jax.random.normal(ks[3], (8, f, d)) * 0.2}
    x = jax.random.normal(ks[4], (3, 32, d))
    m = jax.random.normal(ks[5], (3, 32, d))
    return sizes, whole, x, m


def _counters():
    return {"drawn": jnp.zeros((8,)), "held": jnp.zeros(()),
            "computed": jnp.zeros(()), "combined": jnp.zeros(()),
            "dropped": jnp.zeros(())}


def _walk(p, x, m, cfg, gate):
    """The program's expert layer: routed from ``x``, applied to ``m``."""
    sizes = lfm2.walk_sizes(cfg, x.shape[0] * x.shape[1], gate)
    rows, counters = lfm2._route_and_sort(
        p, _counters(), x.reshape(-1, 32), cfg, sizes, sdar.route)
    y = lfm2.held_experts(sizes, {k: p[k] for k in ("w1", "w3", "w2")},
                          m.reshape(-1, 32), *rows)
    return y.reshape(m.shape), counters


def _plain_layer(p, x, m, gate):
    """The plain spelling: every expert on every token, weighted by the
    token's gate for it."""
    cfg = smallthinker.tiny()
    experts, gates = sdar.route(p, None, x.reshape(-1, 32), cfg)
    flat = m.reshape(-1, 32)
    y = jnp.zeros_like(flat)
    for e in range(8):
        g = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=1)
        y = y + g[:, None] * ((gate(flat @ p["w1"][e]) * (flat @ p["w3"][e]))
                              @ p["w2"][e])
    return y.reshape(m.shape)


@pytest.mark.parametrize("gate", ["relu", "silu"])
def test_the_gates_activation_goes_through_the_walks_backward(gate):
    """``held_experts``' hand-written backward differentiates the gate it
    is told (``jax.vjp`` of the same function): its gradients for weights,
    the experts' input and the router's input are ``jax.grad``'s of the
    plain spelling, under ReLU as under SiLU; and the two differ."""
    _, whole, x, m = _expert_layer(11)
    cfg = smallthinker.tiny(moe_row_block=8)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[gate]

    def walked(p, x, m):
        return jnp.sum(jnp.sin(_walk(p, x, m, cfg, gate)[0]))

    def spelled(p, x, m):
        return jnp.sum(jnp.sin(_plain_layer(p, x, m, act)))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(walked, argnums=(0, 1, 2))(whole, x, m)
        want = jax.grad(spelled, argnums=(0, 1, 2))(whole, x, m)
    gaps = jax.tree_util.tree_map(_rel, got, want)
    assert max(jax.tree_util.tree_leaves(gaps)) <= GRAD_TOL, gaps
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree_util.tree_leaves(want))
    with jax.default_matmul_precision("highest"):
        relu = _walk(whole, x, m, cfg, "relu")[0]
        silu = _walk(whole, x, m, cfg, "silu")[0]
    assert _rel(relu, silu) > 1e-2
    with pytest.raises(KeyError):
        _walk(whole, x, m, cfg, "gelu")


@pytest.mark.parametrize("shares", [1, 2, 8])
def test_the_shares_of_a_layer_add_up_to_the_whole(shares):
    """8 experts over ``shares`` chips (eight as the configuration's
    deployment has them, one expert a chip here): every chip routes over
    all 8 from the layer's input, with gates normalised over both of a
    token's experts, and gives its own experts' part; the parts of all
    shares are the uncut reference's layer, and every assignment is
    computed once."""
    sizes, whole, x, m = _expert_layer(3)
    held = 8 // shares
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda x, m: plain._routed(
            whole, m, plain.gates(whole, x, sizes), sizes, 0))(x, m)
        total, computed, drawn = jnp.zeros_like(m), 0.0, None
        for share in range(shares):
            cfg = smallthinker.tiny(first_expert=share * held,
                                    experts_held=held, moe_row_block=8)
            p = {"router": whole["router"],
                 **{k: whole[k][share * held:(share + 1) * held]
                    for k in ("w1", "w3", "w2")}}
            part, counters = _walk(p, x, m, cfg, "relu")
            here = dict(sizes, moe_num_primary_experts=held)
            np.testing.assert_allclose(
                part, jax.vmap(lambda x, m: plain._routed(
                    p, m, plain.gates(p, x, here), here, share * held))(x, m),
                rtol=2e-5, atol=2e-6)
            total = total + part
            computed += float(counters["held"])
            assert float(counters["dropped"]) == 0.0
            assert "expert_bias" not in counters
            drawn = counters["drawn"]
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)
    assert computed == x.shape[0] * x.shape[1] * 2     # every assignment once
    assert float(drawn.sum()) == computed               # each chip routes all
    assert float(jnp.max(jnp.abs(total))) > 1e-3


def test_the_configs_keep_the_fields_the_shared_parts_read():
    from grace_tpu.models import deepseek_v3
    names = {f.name for f in dataclasses.fields(smallthinker.Config)}
    attention = {"num_attention_heads", "num_key_value_heads", "head_dim",
                 "rope_theta", "attn_q_block", "norm_eps"}
    walk = set(deepseek_v3.SHARED_FIELDS) - {"routed_scaling_factor",
                                            "route_eps"}
    assert attention | walk <= names


# ---------------------------------------------------------------------------
# the compressed step
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
    batch = builder.make_batch(jax.random.key(4), 2 * world, sizes)
    state = init_stateful_train_state(params, mstate, tx, mesh)
    step = make_stateful_train_step(builder.program_loss(sizes), tx, mesh,
                                    donate=False)
    losses = []
    for _ in range(4):
        state, loss = step(state, batch)
        losses.append(float(loss))
    text = next(iter(step.jit_cache.values())).lower(state, batch).as_text(
        debug_info=True)
    return {"losses": losses, "state": state, "text": text,
            "positions": 2 * sizes["seq_length"], "world": world}


def test_the_compressed_step_trains_the_model(trained):
    assert all(np.isfinite(trained["losses"]))
    assert trained["losses"][-1] < trained["losses"][0]


def test_the_model_state_counts_the_held_rows(trained):
    state = trained["state"].model_state
    assert len(state["layers"]) == 4
    for layer in state["layers"]:
        assert set(layer) == {"held", "dropped", "computed", "combined"}
        assert 0 <= float(layer["held"]) <= trained["positions"] * 2
        assert float(layer["held"]) <= float(layer["computed"])
        assert float(layer["dropped"]) == 0.0


def test_every_part_of_the_step_is_under_its_stage(trained):
    text = trained["text"]
    mine = (scopes.STAGE_WINDOW_ATTENTION, scopes.STAGE_ATTENTION,
            scopes.STAGE_MOE_ROUTER, scopes.STAGE_MOE_DISPATCH,
            scopes.STAGE_MOE_EXPERTS, scopes.STAGE_MOE_COMBINE,
            scopes.STAGE_LM_HEAD)
    for stage in mine:
        assert stage in text, stage
        assert STAGE.fullmatch(stage), stage             # the reducer reads it
        assert stage in scopes.ALL_STAGES and stage in scopes.MODEL_STAGES
    for other in (scopes.STAGE_SHORT_CONV, scopes.STAGE_MLA_LATENT,
                  scopes.STAGE_SHARED_EXPERT, scopes.STAGE_DENSE_FFN,
                  scopes.STAGE_DIFFUSION_NOISE):
        assert other not in text
    # the window's stage is no prefix of the full layer's, nor the other
    # way: the rightmost scope names the part, for reducer and report alike
    name = ("jit(device_step)/grace/forward_backward/jvp("
            "grace/window_attention)/dot_general")
    assert stage_of(name) == "grace/window_attention"
    assert scopes.match_stage(name) == scopes.STAGE_WINDOW_ATTENTION
    assert scopes.match_stage(
        "grace/forward_backward/jvp(grace/attention)/dot_general") \
        == scopes.STAGE_ATTENTION
