"""``grace_tpu.models.sdar`` against the plain reference
(``benchmarks/reference/sdar_moe.py``) at a small size on the CPU, and the
properties block-diffusion training promises: the mask as a truth table
written out by hand, position ids ``(0 .. L - 1, 0 .. L - 1)`` through the
rotation, the softmax router against a hand-worked case, the eight shares
of an expert layer adding up to the whole layer, the same noise bits in
program and reference step after step, and the compressed step carrying
its counters under its stages.
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

from benchmarks.models import sdar_moe as builder  # noqa: E402
from benchmarks.reference import sdar_moe as plain  # noqa: E402
from benchmarks.trace_reduce import STAGE, stage_of  # noqa: E402
from grace_tpu.models import layers as L  # noqa: E402
from grace_tpu.models import lfm2, sdar  # noqa: E402
from grace_tpu.ops import pallas_attention  # noqa: E402
from grace_tpu.ops.pallas_attention import BlockDiffusion, CAUSAL  # noqa: E402
from grace_tpu.telemetry import scopes  # noqa: E402

# A share of a small model in the configuration file's own keys: 4 experts
# held (experts 4-7) of the 8 the router scores, 2 a token; 4 | 2 heads of
# 8; 16 clean tokens in blocks of 4, entering as 32 positions.
SIZES = {
    "hidden_size": 32, "moe_intermediate_size": 16, "num_experts": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000, "vocab_size": 128,
    "published": {"num_experts": 8}, "share": 1, "seq_length": 16,
    "per_chip_batch": 4, "block_length": 4, "noise_eps": 1e-3,
    "activation_dtype": "float32", "param_dtype": "float32"}
# several blocks of each kind at this size
WALK = {"attn_q_block": 8, "moe_row_block": 16, "seq_block": 2}
GROUPS = ["embed", "final_norm", "head"] + [f"layers/{i}" for i in range(3)]


def _program_loss(sizes, **walk):
    cfg = dataclasses.replace(builder.model_config(sizes), **{**WALK, **walk})
    dtype = jnp.dtype(sizes["activation_dtype"])
    return lambda params, mstate, batch: sdar.block_diffusion_loss(
        params, mstate, batch, cfg, dtype=dtype)


def _run(loss_fn, sizes=SIZES, key=1, step=0.0):
    with jax.default_matmul_precision("highest"):
        params, state = builder.init(jax.random.key(key), sizes)
        state = dict(state, step=jnp.asarray(step, jnp.float32))
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
# 2**-24 a sum; the loss's weights reach 1 / noise_eps = 1000, which scales
# both sides alike. The bfloat16 run below is a thousand times over.
LOSS_TOL = 2e-6
GRAD_TOL = 2e-5


def test_loss_agrees_with_the_plain_reference(float32_pair):
    (got, _, got_state), (want, _, want_state) = float32_pair
    assert abs(got - want) <= LOSS_TOL * abs(want)
    assert want > 1.0              # ln 128 = 4.85 a masked token, weighted
    assert float(got_state["step"]) == float(want_state["step"]) == 1.0


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
    program is held to the float32 reference by the activations' rounding:
    8 bits of mantissa a number, summed over three layers, is a few parts
    in a thousand of the loss and a few in a hundred of a gradient leaf
    (the median leaf one in a hundred) — and a thousand times outside the
    float32 tolerances, so those would catch a program that computes in the
    lower precision. A router is the exception: its scores are made in
    bfloat16, so a token near a tie takes another expert than in the
    reference, and at 64 positions one such token is a third of its
    gradient (the benchmark's cell reads 0.7-6 % on its worst router)."""
    _, (want_loss, want, _) = float32_pair
    low = dict(SIZES, activation_dtype="bfloat16")
    loss, grads, _ = _run(_program_loss(low))
    gaps = jax.tree_util.tree_map(_rel, grads, want)
    routers = [layer["ffn"].pop("router") for layer in gaps["layers"]]
    gaps = jax.tree_util.tree_leaves(gaps)
    assert abs(loss - want_loss) <= 5e-3 * want_loss
    assert max(gaps) <= 0.1 and float(np.median(gaps)) <= 0.02
    assert max(routers) <= 0.6
    assert max(gaps) > 50 * GRAD_TOL


@pytest.mark.parametrize("walk", [{"seq_block": 4}, {"attn_q_block": 32},
                                  {"moe_row_block": 0}])
def test_walking_the_work_in_other_blocks_changes_nothing(walk, float32_pair):
    (want_loss, want, _), _ = float32_pair
    loss, grads, _ = _run(_program_loss(SIZES, **walk))
    assert abs(loss - want_loss) <= LOSS_TOL * want_loss
    gaps = jax.tree_util.tree_leaves(jax.tree_util.tree_map(_rel, grads, want))
    assert max(gaps) <= GRAD_TOL


def test_the_program_reads_the_tree_the_benchmark_makes():
    cfg = builder.model_config(SIZES)
    bench, bench_state = jax.eval_shape(
        lambda k: builder.init(k, SIZES), jax.random.key(0))
    own, own_state = jax.eval_shape(lambda k: sdar.init(k, cfg),
                                    jax.random.key(0))
    assert (jax.tree_util.tree_structure(bench)
            == jax.tree_util.tree_structure(own))
    assert jax.tree_util.tree_map(lambda a: a.shape, bench) \
        == jax.tree_util.tree_map(lambda a: a.shape, own)
    # the benchmark's state makes the counters it reads; the program's own
    # counts what each expert drew as well, and neither has a bias
    assert set(own_state) == set(bench_state) == {"step", "masked", "layers"}
    assert set(bench_state["layers"][0]) == {"held", "dropped", "computed",
                                             "combined"}
    assert set(own_state["layers"][0]) == {"drawn", "held", "dropped",
                                           "computed", "combined"}


def test_the_initialisation_keeps_the_stream_its_tokens():
    """Both makers of weights, the benchmark's and the program's own:
    embedding rows of std 1, the mask token's row the mean of the rows
    before it, the two projections that write to the residual stream at
    0.02 / sqrt(2 * layers) (the benchmark's: the published 48, 4 held),
    the query and key heads' norm weights the same constant above 1 in
    both, every other norm's 1, every other matrix at 0.02."""
    import json
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "sdar-30b-a3b-ep8.json")) as f:
        published = json.load(f)["published"]
    sizes = dict(SIZES, hidden_size=256, vocab_size=512,
                 published=dict(SIZES["published"],
                                num_hidden_layers=published[
                                    "num_hidden_layers"]))
    cfg = builder.model_config(sizes)
    made = {"benchmark": (builder.init(jax.random.key(0), sizes)[0], 48),
            "program": (sdar.init(jax.random.key(0), cfg)[0], 3)}
    for name, (params, depth) in made.items():
        table = np.asarray(params["embed"]["table"])
        assert 0.8 < table[:-1].std() < 0.95, name      # truncated at 2 sd
        np.testing.assert_allclose(table[-1], table[:-1].mean(axis=0),
                                   rtol=1e-5, atol=1e-7)
        layer = params["layers"][1]
        out = 0.02 / np.sqrt(2 * depth)
        for leaf in (layer["attn"]["o_proj"], layer["ffn"]["w2"]):
            assert 0.8 * out < float(jnp.std(leaf)) < 0.95 * out, name
        for leaf in (layer["attn"]["q_proj"], layer["ffn"]["w1"],
                     layer["ffn"]["router"], params["head"]):
            assert 0.016 < float(jnp.std(leaf)) < 0.019, name
        for norm in ("q_norm", "k_norm"):
            np.testing.assert_array_equal(
                layer["attn"][norm]["scale"], sdar.QK_NORM_INIT, name)
        for norm in (layer["attn_norm"], layer["ffn_norm"],
                     params["final_norm"]):
            np.testing.assert_array_equal(norm["scale"], 1.0, name)
    assert sdar.QK_NORM_INIT == plain.QK_NORM_INIT > 1.0
    # a configuration that states no published depth holds all its layers
    own = builder.init(jax.random.key(0), SIZES)[0]["layers"][0]
    assert float(jnp.std(own["attn"]["o_proj"])) == pytest.approx(
        0.88 * 0.02 / np.sqrt(6), rel=0.1)


def test_the_published_configuration_counts_its_parameters():
    import json
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "sdar-30b-a3b-ep8.json")) as f:
        sizes = json.load(f)
    cfg = builder.model_config(sizes)
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert) == (128, 16, 0)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim) == (32, 4, 128)
    assert (cfg.block_length, cfg.noise_eps, cfg.mask_token_id) == (
        4, 1e-3, 18991)
    shapes = jax.eval_shape(lambda k: sdar.init(k, cfg)[0], jax.random.key(0))
    counts = [int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)]
    layer = 2 * 8_388_608 + 2 * 1_048_576 + 262_144 + 75_497_472 + 4_352
    assert layer == 94_638_336
    assert sum(counts) == 4 * layer + 2 * 38_895_616 + 2_048 \
        == sizes["parameters_held"] == 456_346_624
    assert len(counts) == 4 * 12 + 3


# ---------------------------------------------------------------------------
# the mask
# ---------------------------------------------------------------------------

def test_the_mask_is_the_four_rules_written_out():
    """``L = 16``, ``B = 4``: positions 0-15 are the noised copy, 16-31 the
    clean one; block ``b`` of either copy is its tokens ``4 b .. 4 b + 3``.
    Written out row by row: a noised query of block ``b`` reads the 4 noised
    keys of block ``b`` and the ``4 b`` clean keys of the blocks before; a
    clean query of block ``b`` reads the ``4 (b + 1)`` clean keys up to its
    block's end and no noised key."""
    want = np.zeros((32, 32), bool)
    for block in range(4):
        for query in range(4 * block, 4 * block + 4):
            want[query, 4 * block:4 * block + 4] = True          # rule 1
            want[query, 16:16 + 4 * block] = True                 # rule 2
            want[16 + query, 16:16 + 4 * block + 4] = True        # rule 3
    # rule 4: nothing from a clean query to a noised key
    assert not want[16:, :16].any()
    # a few cells by hand: token 5 (block 1) noised reads noised 4-7 and
    # clean 0-3 (positions 16-19); clean token 5 reads clean 0-7
    assert list(np.flatnonzero(want[5])) == [4, 5, 6, 7, 16, 17, 18, 19]
    assert list(np.flatnonzero(want[21])) == list(range(16, 24))
    assert list(np.flatnonzero(want[0])) == [0, 1, 2, 3]
    assert want.sum() == 16 * 4 + 16 ** 2                 # L B + L^2 pairs
    mask = BlockDiffusion(16, 4)
    ids = np.arange(32)
    np.testing.assert_array_equal(mask.allowed(ids[:, None], ids[None, :]),
                                  want)
    np.testing.assert_array_equal(
        np.asarray(mask.allowed(jnp.arange(32)[:, None],
                                jnp.arange(32)[None, :])), want)
    np.testing.assert_array_equal(np.asarray(plain.allowed(16, 4)), want)
    assert want.diagonal().all()            # no query without a key
    # blocks that are no power of two take a division where 4 takes a
    # shift: the same table as the reference's, which is the rules' text
    for length, block in ((12, 6), (12, 3), (15, 5), (8, 8), (8, 1)):
        ids = np.arange(2 * length)
        np.testing.assert_array_equal(
            BlockDiffusion(length, block).allowed(ids[:, None], ids[None, :]),
            np.asarray(plain.allowed(length, block)))


def test_a_mask_is_a_value():
    assert BlockDiffusion(16, 4) == BlockDiffusion(16, 4) != BlockDiffusion(16, 8)
    assert hash(BlockDiffusion(16, 4)) == hash(BlockDiffusion(16, 4))
    assert CAUSAL == pallas_attention.Causal() != BlockDiffusion(16, 4)
    ids = np.arange(6)
    np.testing.assert_array_equal(CAUSAL.allowed(ids[:, None], ids[None, :]),
                                  np.tril(np.ones((6, 6), bool)))
    assert CAUSAL.keys_read(8, 32) == 8
    assert BlockDiffusion(16, 4).keys_read(8, 32) == 32
    with pytest.raises(ValueError, match="whole blocks"):
        BlockDiffusion(18, 4)


def test_the_plain_path_takes_the_mask():
    """``_scores_in_blocks`` under the block-diffusion mask against a full
    masked softmax; under the causal mask it is what it was."""
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, 32, 4, 8))
    k = jax.random.normal(ks[1], (2, 32, 2, 8))
    v = jax.random.normal(ks[2], (2, 32, 2, 8))

    def full(allowed):
        kk, vv = (jnp.repeat(a, 2, axis=2) for a in (k, v))
        s = jnp.einsum("nqhd,nkhd->nhqk", q, kk) / np.sqrt(8)
        a = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        return jnp.einsum("nhqk,nkhd->nqhd", a, vv)

    with jax.default_matmul_precision("highest"):
        got = lfm2._scores_in_blocks(q, k, v, 8, BlockDiffusion(16, 4))
        np.testing.assert_allclose(got, full(plain.allowed(16, 4)),
                                   rtol=1e-5, atol=1e-6)
        causal = lfm2._scores_in_blocks(q, k, v, 8)
        np.testing.assert_allclose(
            causal, full(jnp.tril(jnp.ones((32, 32), bool))), rtol=1e-5,
            atol=1e-6)
        np.testing.assert_array_equal(
            np.asarray(causal),
            np.asarray(lfm2._scores_in_blocks(q, k, v, 8, CAUSAL)))


# ---------------------------------------------------------------------------
# positions
# ---------------------------------------------------------------------------

def test_position_ids_reach_the_rotation():
    """Position ids ``(0 .. L - 1, 0 .. L - 1)``: the clean copy's token
    ``i`` is turned by the angle of position ``i``, not ``L + i``; without
    ids the rotation is what it was."""
    x = jax.random.normal(jax.random.key(0), (2, 8, 3, 4))
    ids = np.tile(np.arange(4), 2)
    got = L.rotary(x, 100.0, ids)
    first, second = L.rotary(x[:, :4], 100.0), L.rotary(x[:, 4:], 100.0)
    np.testing.assert_array_equal(np.asarray(got[:, :4]), np.asarray(first))
    np.testing.assert_array_equal(np.asarray(got[:, 4:]), np.asarray(second))
    np.testing.assert_array_equal(
        np.asarray(L.rotary(x, 100.0)),
        np.asarray(L.rotary(x, 100.0, jnp.arange(8))))
    assert not np.allclose(np.asarray(got[:, 4:]),
                           np.asarray(L.rotary(x, 100.0)[:, 4:]))
    # by hand: position 1, theta 100, head size 4 -> angles 1 and 0.1
    one = L.rotary(jnp.asarray([1.0, 2.0, 3.0, 4.0]).reshape(1, 1, 1, 4),
                   100.0, np.asarray([1]))
    want = [1 * np.cos(1.0) - 3 * np.sin(1.0), 2 * np.cos(0.1) - 4 * np.sin(0.1),
            3 * np.cos(1.0) + 1 * np.sin(1.0), 4 * np.cos(0.1) + 2 * np.sin(0.1)]
    np.testing.assert_allclose(np.asarray(one).reshape(-1), want, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(plain._rotate(jnp.asarray([1.0, 2.0, 3.0, 4.0]).reshape(
            1, 1, 4), jnp.asarray([1]), 100.0)).reshape(-1), want, rtol=1e-6)
    with pytest.raises(ValueError, match="positions"):
        L.rotary(x, 100.0, np.arange(7))


def test_attention_sees_the_clean_copy_at_its_own_positions():
    """A noised query reads clean keys of earlier blocks, which lie ``L``
    positions behind it on the axis and at most ``L - 1`` before it by
    their ids: with the ids the program's attention is the reference's,
    without them the rows that read across the copies are others (rotary
    positions are relative, so rows that read their own copy alone do not
    change)."""
    cfg = sdar.tiny()
    p = sdar.init(jax.random.key(0), cfg)[0]["layers"][0]["attn"]
    p = jax.tree_util.tree_map(lambda a: a * 20.0, p)      # scores that matter
    u = jax.random.normal(jax.random.key(1), (1, 16, 32))
    mask, ids = BlockDiffusion(8, 4), np.tile(np.arange(8), 2)
    sizes = dict(SIZES, rms_norm_eps=cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        got = lfm2.attention(p, u, cfg, mask, ids)
        want = plain._attention(p, u[0], jnp.asarray(ids),
                                plain.allowed(8, 4), sizes)
        by_axis = lfm2.attention(p, u, cfg, mask)
    # weights twenty times their size sharpen the softmax: the two
    # spellings' sums in another order show at 1e-4 of a row
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    # noised block 0 (rows 0-3) and the clean copy (rows 8-15) read their
    # own copy only; noised block 1 (rows 4-7) reads clean block 0
    same = np.r_[0:4, 8:16]
    np.testing.assert_allclose(np.asarray(by_axis[0, same]),
                               np.asarray(got[0, same]), rtol=2e-4, atol=2e-5)
    assert not np.allclose(np.asarray(by_axis[0, 4:8]),
                           np.asarray(got[0, 4:8]), rtol=1e-2, atol=1e-3)


# ---------------------------------------------------------------------------
# the router and the share
# ---------------------------------------------------------------------------

def test_the_router_is_a_softmax_renormalised_over_the_chosen():
    """Four experts, two a token, by hand. Logits (0, ln 2, ln 3, ln 4):
    probabilities (1, 2, 3, 4) / 10; the top two are experts 3 and 2 with
    gates 4/7 and 3/7. Logits (5, 5 + ln 3, 0, 0): probabilities (1, 3, e^-5,
    e^-5) / (4 + 2 e^-5); experts 1 and 0 with gates 3/4 and 1/4."""
    cfg = sdar.tiny(num_experts=4, experts_held=4, hidden_size=4)
    logits = jnp.asarray([[0.0, np.log(2.0), np.log(3.0), np.log(4.0)],
                          [5.0, 5.0 + np.log(3.0), 0.0, 0.0]])
    p = {"router": jnp.eye(4)}
    experts, gates = sdar.route(p, None, logits, cfg)
    np.testing.assert_array_equal(np.asarray(experts), [[3, 2], [1, 0]])
    np.testing.assert_allclose(np.asarray(gates), [[4 / 7, 3 / 7],
                                                   [3 / 4, 1 / 4]], rtol=1e-6)
    table = plain._gates(p, logits, {"num_experts_per_tok": 2})
    np.testing.assert_allclose(
        np.asarray(table), [[0, 0, 3 / 7, 4 / 7], [1 / 4, 3 / 4, 0, 0]],
        rtol=1e-6, atol=1e-7)


def _expert_layer(key):
    """One expert layer's weights for all 8 experts, a normalised input,
    and the sizes of the uncut layer."""
    sizes = dict(SIZES, num_experts=8, share=0)
    d, f = 32, 16
    ks = jax.random.split(jax.random.key(key), 5)
    whole = {"router": jax.random.normal(ks[0], (d, 8)) * 0.3,
             "w1": jax.random.normal(ks[1], (8, d, f)) * 0.2,
             "w3": jax.random.normal(ks[2], (8, d, f)) * 0.2,
             "w2": jax.random.normal(ks[3], (8, f, d)) * 0.2}
    u = jax.random.normal(ks[4], (3, 32, d))
    return sizes, whole, u * jax.lax.rsqrt(
        jnp.mean(u * u, -1, keepdims=True) + 1e-6)


def _counters():
    return {"drawn": jnp.zeros((8,)), "held": jnp.zeros(()),
            "computed": jnp.zeros(()), "combined": jnp.zeros(()),
            "dropped": jnp.zeros(())}


@pytest.mark.parametrize("shares", [1, 2, 8])
def test_the_shares_of_a_layer_add_up_to_the_whole(shares):
    """8 experts over ``shares`` chips (eight as the configuration's
    deployment has them, one expert a chip here): every chip routes over
    all 8 with gates normalised over both of a token's experts and gives
    its own experts' part; the parts of all shares are the uncut
    reference's layer, and every assignment is computed once."""
    sizes, whole, u = _expert_layer(3)
    held = 8 // shares
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda x: plain._routed(whole, x, sizes, 0))(u)
        total, computed, drawn = jnp.zeros_like(u), 0.0, None
        for share in range(shares):
            cfg = sdar.tiny(first_expert=share * held, experts_held=held,
                            moe_row_block=8)
            p = {"router": whole["router"],
                 **{k: whole[k][share * held:(share + 1) * held]
                    for k in ("w1", "w3", "w2")}}
            part, counters = lfm2.moe_ffn(p, _counters(), u, cfg, sdar.route)
            np.testing.assert_allclose(
                part, jax.vmap(lambda x: plain._routed(
                    p, x, dict(sizes, num_experts=held), share * held))(u),
                rtol=2e-5, atol=2e-6)
            total = total + part
            computed += float(counters["held"])
            assert float(counters["dropped"]) == 0.0
            assert "expert_bias" not in counters
            drawn = counters["drawn"]
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)
    assert computed == u.shape[0] * u.shape[1] * 2     # every assignment once
    assert float(drawn.sum()) == computed               # each chip routes all
    assert float(jnp.max(jnp.abs(total))) > 1e-3


def test_a_share_outside_the_routers_experts_is_refused():
    with pytest.raises(ValueError, match="not among the router's"):
        sdar.tiny(first_expert=6, experts_held=4)
    with pytest.raises(ValueError, match="noise level"):
        sdar.tiny(noise_eps=0.0)


def test_the_configs_keep_the_fields_the_shared_parts_read():
    from grace_tpu.models import deepseek_v3
    names = {f.name for f in dataclasses.fields(sdar.Config)}
    attention = {"num_attention_heads", "num_key_value_heads", "head_dim",
                 "rope_theta", "attn_q_block", "norm_eps"}
    walk = set(deepseek_v3.SHARED_FIELDS) - {"routed_scaling_factor",
                                            "route_eps"}
    assert attention | walk <= names
    assert attention <= {f.name for f in dataclasses.fields(lfm2.Config)}


# ---------------------------------------------------------------------------
# the noise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("step", [0, 1, 2])
def test_program_and_reference_draw_the_same_bits(step):
    """The three compared steps: the program's draw and the reference's are
    two spellings of the same calls of ``jax.random``; noised copies and
    weights are the same bits, and differ from step to step."""
    cfg = builder.model_config(SIZES)
    batch = builder.make_batch(jax.random.key(5), 4, SIZES)
    for key, ids in zip(batch["key"], batch["ids"]):
        got = sdar.draw_noise(key, jnp.float32(step), ids, cfg.block_length,
                              cfg.noise_eps, cfg.mask_token_id)
        want = plain.draw_noise(key, jnp.float32(step), ids, SIZES)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        other = plain.draw_noise(key, jnp.float32(step + 1), ids, SIZES)
        assert not np.array_equal(np.asarray(want[0]), np.asarray(other[0]))


def test_the_draw_is_block_diffusions():
    """One level a block, ``U(eps, 1)``; a token masked with its block's
    level carries ``1 / t`` and the mask id, the others their own id and no
    weight; clean tokens never are the mask id; over many blocks the share
    masked is the mean level, one half."""
    sizes = dict(SIZES, seq_length=4096)
    batch = builder.make_batch(jax.random.key(9), 2, sizes)
    assert int(batch["ids"].max()) <= sizes["vocab_size"] - 2
    assert batch["key"].shape == (2, 2) and batch["key"].dtype == jnp.uint32
    noised, w = plain.draw_noise(batch["key"][0], 0.0, batch["ids"][0], sizes)
    noised, w, ids = (np.asarray(a) for a in (noised, w, batch["ids"][0]))
    masked = w > 0
    assert (noised[masked] == sizes["vocab_size"] - 1).all()
    assert (noised[~masked] == ids[~masked]).all()
    assert w[masked].min() >= 1.0 and w.max() <= 1000.0 * (1 + 1e-6)
    for block in w.reshape(-1, 4):                  # one level a block
        assert len(set(block[block > 0])) <= 1
    assert 0.45 < masked.mean() < 0.55
    # the weights make the loss an unbiased count: E[m / t] = 1 a token
    assert 0.7 < w.mean() < 1.4
    other, _ = plain.draw_noise(batch["key"][1], 0.0, batch["ids"][0], sizes)
    assert not np.array_equal(np.asarray(other), noised)     # a key a sequence


def test_the_step_counter_moves_the_noise(float32_pair):
    (loss0, _, _), _ = float32_pair
    loss1, _, state = _run(_program_loss(SIZES), step=1.0)
    assert float(state["step"]) == 2.0 and loss1 != loss0
    want1, _, _ = _run(builder.reference_loss(SIZES), step=1.0)
    assert abs(loss1 - want1) <= LOSS_TOL * abs(want1)


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
    losses, masked = [], []
    for _ in range(4):
        state, loss = step(state, batch)
        losses.append(float(loss))
        masked.append(float(state.model_state["masked"]))
    text = next(iter(step.jit_cache.values())).lower(state, batch).as_text(
        debug_info=True)
    return {"losses": losses, "masked": masked, "state": state, "text": text,
            "positions": 2 * 2 * sizes["seq_length"], "world": world}


def test_the_compressed_step_runs_the_model(trained):
    assert all(np.isfinite(trained["losses"]))
    assert len(set(trained["losses"])) == 4


def test_the_model_state_counts_steps_masked_tokens_and_rows(trained):
    state = trained["state"].model_state
    assert float(state["step"]) == 4.0
    # a chip's two sequences of 16 tokens: the mean over the replicas of
    # what each masked, another number every step
    assert all(0 < m < 2 * 16 for m in trained["masked"])
    assert len(set(trained["masked"])) > 1
    assert len(state["layers"]) == 3
    for layer in state["layers"]:
        assert set(layer) == {"held", "dropped", "computed", "combined"}
        assert 0 <= float(layer["held"]) <= trained["positions"] * 2
        assert float(layer["held"]) <= float(layer["computed"])
        assert float(layer["dropped"]) == 0.0


def test_every_part_of_the_step_is_under_its_stage(trained):
    text = trained["text"]
    mine = (scopes.STAGE_DIFFUSION_NOISE, scopes.STAGE_ATTENTION,
            scopes.STAGE_MOE_ROUTER, scopes.STAGE_MOE_DISPATCH,
            scopes.STAGE_MOE_EXPERTS, scopes.STAGE_MOE_COMBINE,
            scopes.STAGE_LM_HEAD)
    for stage in mine:
        assert stage in text, stage
        assert STAGE.fullmatch(stage), stage             # the reducer reads it
        assert stage in scopes.ALL_STAGES and stage in scopes.MODEL_STAGES
    for other in (scopes.STAGE_SHORT_CONV, scopes.STAGE_MLA_LATENT,
                  scopes.STAGE_SHARED_EXPERT, scopes.STAGE_DENSE_FFN):
        assert other not in text
    name = ("jit(device_step)/grace/forward_backward/jvp("
            "grace/diffusion_noise)/threefry2x32")
    assert stage_of(name) == "grace/diffusion_noise"
    assert scopes.match_stage(name) == scopes.STAGE_DIFFUSION_NOISE
