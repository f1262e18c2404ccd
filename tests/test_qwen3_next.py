"""``grace_tpu.models.qwen3_next`` against the plain reference
(``benchmarks/reference/qwen3_next.py``) at a small size on the CPU, and
what the architecture promises: the chunked delta rule is the token-by-token
recurrence (several chunks and spans, gates near both ends of ``exp(g)``),
the unit-lower-triangular inverse is the inverse, the first quarter of a
head is rotated and the rest untouched, the output gate multiplies the
heads' output, the norms are zero-centred, the thirty-two shares of an
expert layer with the gated shared expert counted once add up to the whole
layer, and the compressed step carries its counters under its stages.
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

from benchmarks.models import qwen3_next as builder  # noqa: E402
from benchmarks.reference import qwen3_next as plain  # noqa: E402
from benchmarks.trace_reduce import STAGE, stage_of  # noqa: E402
from grace_tpu.models import layers as L  # noqa: E402
from grace_tpu.models import lfm2, qwen3_next, sdar  # noqa: E402
from grace_tpu.telemetry import scopes  # noqa: E402

# A share of a small model in the configuration file's own keys: 4 experts
# held (experts 4-7) of the 8 the router scores, 2 a token; one period
# (three gated delta layers of 2 key | 4 value heads of 8, a full layer of
# 4 | 2 heads of 16 whose first 4 numbers are rotated); 32 tokens.
SIZES = {
    "hidden_size": 32, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "num_experts": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 4,
    "full_attention_interval": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 8,
    "linear_value_head_dim": 8, "linear_conv_kernel_dim": 4,
    "rms_norm_eps": 1e-6, "vocab_size": 128, "published": {"num_experts": 8},
    "share": 1, "seq_length": 32, "per_chip_batch": 4,
    "activation_dtype": "float32", "param_dtype": "float32"}
# several blocks of each kind at this size: four blocks of queries, two
# parts of the head, eight chunks of the rule in four spans
WALK = {"attn_q_block": 8, "moe_row_block": 16, "seq_block": 2,
        "head_positions": 16, "delta_chunk": 4, "delta_span": 8}
GROUPS = ["embed", "final_norm", "head"] + [f"layers/{i}" for i in range(4)]


def _program_loss(sizes, **walk):
    cfg = dataclasses.replace(builder.model_config(sizes), **{**WALK, **walk})
    dtype = jnp.dtype(sizes["activation_dtype"])
    return lambda params, mstate, batch: qwen3_next.next_token_loss(
        params, mstate, batch, cfg, dtype=dtype)


def _moved(params, key):
    """The seeded weights with the leaves that start at a constant (norm
    weights 0 or 1, ``dt_bias`` 1) moved off it, so that a norm that were
    not zero-centred, or a forgotten weight, would show."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
        for x, k in zip(leaves, keys)])


def _run(loss_fn, sizes=SIZES, key=1):
    with jax.default_matmul_precision("highest"):
        params, state = builder.init(jax.random.key(key), sizes)
        params = _moved(params, jax.random.key(key + 7))
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
# the same mathematics in another order (the rule chunk by chunk through a
# triangular inverse against token by token; tiles of sorted rows against
# one expert after another; blocks of queries against heads one by one): a
# few units of 2**-24 a sum, and more through the rule: the running sums of
# ``g`` reach the hundreds here, their differences lose that many units
# before the exponential, and the inverse's chain of products carries them
# (5e-5 to 7e-5 on ``A_log`` and ``dt_bias`` by the chunk length). The
# bfloat16 run below is some hundred times over.
LOSS_TOL = 2e-6
GRAD_TOL = 2e-4


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
    tokens one such token is a fifth of an expert stack's largest entry."""
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
                                  {"head_positions": 32},
                                  {"delta_chunk": 8, "delta_span": 32},
                                  {"delta_chunk": 16, "delta_span": 16}])
def test_walking_the_work_in_other_blocks_changes_nothing(walk, float32_pair):
    (want_loss, want, _), _ = float32_pair
    loss, grads, _ = _run(_program_loss(SIZES, **walk))
    assert abs(loss - want_loss) <= LOSS_TOL * want_loss
    gaps = jax.tree_util.tree_leaves(jax.tree_util.tree_map(_rel, grads, want))
    assert max(gaps) <= GRAD_TOL


def test_the_references_blocks_change_nothing(monkeypatch, float32_pair):
    """The reference recomputes the recurrence ``RULE_ROWS`` positions at a
    time, makes what a position computes from its own row and scores its
    queries ``Q_ROWS`` positions at a time (a convolution's block reading
    the three rows before it) and an operator's heads ``HEAD_GROUP`` at a
    time: four blocks of 8 over these 32 positions and one head a group,
    where the fixture's run had one block and two heads a group, give the
    same loss and gradients."""
    _, (want_loss, want, _) = float32_pair
    monkeypatch.setattr(plain, "Q_ROWS", 8)
    monkeypatch.setattr(plain, "RULE_ROWS", 8)
    monkeypatch.setattr(plain, "HEAD_GROUP", 1)
    loss, grads, _ = _run(builder.reference_loss(SIZES))
    assert abs(loss - want_loss) <= LOSS_TOL * want_loss
    gaps = jax.tree_util.tree_leaves(jax.tree_util.tree_map(_rel, grads, want))
    assert max(gaps) <= GRAD_TOL


def test_spans_and_chunks_that_do_not_divide_are_refused():
    with pytest.raises(ValueError, match="whole chunks"):
        qwen3_next.tiny(delta_chunk=4, delta_span=6)
    q = jnp.zeros((1, 1, 12, 4))
    v = jnp.zeros((1, 1, 1, 12, 4))
    g = jnp.zeros((1, 1, 1, 12))
    with pytest.raises(ValueError, match="whole spans"):
        qwen3_next.delta_rule(q, q, v, g, g, 4, 8)
    # a sequence shorter than a chunk is one chunk
    assert qwen3_next.delta_rule(q, q, v, g, g, 64, 2048).shape == v.shape
    cfg = dataclasses.replace(builder.model_config(SIZES), head_positions=24)
    params, state = builder.init(jax.random.key(0), SIZES)
    with pytest.raises(ValueError, match="whole parts"):
        qwen3_next.next_token_loss(params, state,
                                   jnp.zeros((2, 32), jnp.int32), cfg)


def test_the_program_reads_the_tree_the_benchmark_makes():
    """The benchmark's weights come from the reference's ``init``; the
    program's own ``init`` makes the same tree: names, shapes, dtypes, and
    the constants (norm weights 0, the gated norm's and ``dt_bias`` 1)."""
    cfg = builder.model_config(SIZES)
    mine, mine_state = qwen3_next.init(jax.random.key(0), cfg)
    theirs, their_state = builder.init(jax.random.key(0), SIZES)
    shape = lambda t: jax.tree_util.tree_map(      # noqa: E731
        lambda a: (a.shape, a.dtype), t)
    assert shape(mine) == shape(theirs)
    assert set(mine_state["layers"][0]) == set(their_state["layers"][0]) == {
        "drawn", "held", "dropped", "computed", "combined"}
    for tree in (mine, theirs):
        for layer, kind in zip(tree["layers"], cfg.layer_types):
            assert not np.any(layer["op_norm"]["scale"])
            assert not np.any(layer["ffn_norm"]["scale"])
            if kind == "linear_attention":
                assert np.all(np.asarray(layer["op"]["norm"]["scale"]) == 1)
                assert np.all(np.asarray(layer["op"]["dt_bias"]) == 1)
                a = np.exp(np.asarray(layer["op"]["A_log"]))
                assert np.all((a > 0) & (a <= 16))
                assert np.abs(np.asarray(layer["op"]["conv"])).max() <= 0.5
            else:
                assert not np.any(layer["op"]["q_norm"]["scale"])
        assert not np.any(tree["final_norm"]["scale"])
    assert cfg.layer_types == ("linear_attention",) * 3 + ("full_attention",)


def test_the_published_configuration_counts_its_parameters():
    """The cell's share, leaf by leaf, from abstract shapes: a gated delta
    layer's operator 33,718,464, a full layer's 27,263,488, an expert
    layer's held part 54,528,000, 424,340,544 in 70 leaves."""
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "qwen3-next-80b-a3b-ep32.json")) as f:
        import json
        sizes = json.load(f)
    shapes = jax.eval_shape(lambda k: builder.init(k, sizes)[0],
                            jax.random.key(0))

    def count(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))

    layers = shapes["layers"]
    assert [count(l["op"]) for l in layers] == 3 * [33_718_464] + [27_263_488]
    assert {count(l["ffn"]) for l in layers} == {54_528_000}
    assert count(layers[0]["ffn"]["shared"]) == 3_145_728
    assert layers[0]["ffn"]["shared_gate"].shape == (2048, 1)
    assert layers[0]["op"]["in_proj_qkvz"].shape == (2048, 12288)
    assert layers[0]["op"]["conv"].shape == (4, 8192)
    assert layers[3]["op"]["q_proj"].shape == (2048, 8192)
    assert count(shapes) == 424_340_544 == sizes["parameters_held"]
    assert len(jax.tree_util.tree_leaves(shapes)) == 70


def test_the_published_model_is_the_configs_defaults():
    cfg = qwen3_next.Config()
    assert cfg.num_hidden_layers == 48
    assert cfg.layer_types.count("full_attention") == 12
    assert all(kind == "full_attention"
               for kind in cfg.layer_types[3::4])
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.head_dim,
            cfg.rotary_dim, cfg.linear_num_value_heads) == (512, 10, 256, 64,
                                                            32)
    with pytest.raises(ValueError, match="layer types"):
        qwen3_next.Config(layer_types=("conv",))
    with pytest.raises(ValueError, match="value heads"):
        qwen3_next.Config(linear_num_key_heads=5)


def test_the_configs_keep_the_fields_the_shared_parts_read():
    from grace_tpu.models import deepseek_v3
    names = {f.name for f in dataclasses.fields(qwen3_next.Config)}
    attention = {"num_attention_heads", "num_key_value_heads", "head_dim",
                 "rope_theta", "attn_q_block", "norm_eps"}
    walk = set(deepseek_v3.SHARED_FIELDS) - {"routed_scaling_factor",
                                            "route_eps"}
    assert attention | walk <= names


# ---------------------------------------------------------------------------
# the gated delta rule
# ---------------------------------------------------------------------------

def _rule_inputs(key, n=2, hk=2, groups=2, t=24, dk=8, dv=8, gates="mixed"):
    """Normalised ``q`` and ``k``, values, and gates: ``mixed`` draws ``g``
    from -55 (``exp(g)`` is 1e-24: the state is wiped) to -3e-4 (all but
    kept), ``kept`` from -3e-4 to -6e-6, ``wiped`` from -20 to -3,000
    (``exp(g)`` underflows), and ``beta`` from 0.003 to 0.997."""
    ks = jax.random.split(jax.random.key(key), 5)
    q = jax.random.normal(ks[0], (n, hk, t, dk))
    k = jax.random.normal(ks[1], (n, hk, t, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (n, hk, groups, t, dv))
    low, high = {"mixed": (-8.0, 4.0), "kept": (-12.0, -8.0),
                 "wiped": (3.0, 8.0)}[gates]
    g = -jnp.exp(jax.random.uniform(ks[3], (n, hk, groups, t), minval=low,
                                    maxval=high))
    beta = jax.nn.sigmoid(3 * jax.random.normal(ks[4], (n, hk, groups, t)))
    return q, k, v, g, beta


def _token_by_token(q, k, v, g, beta):
    """The reference's recurrence over the program's layout."""
    def one(q, k, v, g, beta):          # q (Hk, T, d), v (Hk, G, T, d)
        o = plain.delta_rule(
            jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
            jnp.moveaxis(v, 2, 0), jnp.moveaxis(g, 2, 0),
            jnp.moveaxis(beta, 2, 0))
        return jnp.moveaxis(o, 0, 2)

    return jax.vmap(one)(q, k, v, g, beta)


# float32 at ``highest``: the chunked form sums a chunk's tokens in another
# order and takes a decay as the exponential of a difference of running
# sums of ``g``: where those reach the hundreds (``mixed`` draws ``g`` down
# to -55, 24 tokens a chunk) the difference has lost 2**-24 of them, some
# 1e-5 of the decay, and the inverse's products carry it on; outputs are
# O(1)
RULE_TOL = 1e-4


@pytest.mark.parametrize("gates", ["mixed", "kept", "wiped"])
@pytest.mark.parametrize("chunk,span", [(4, 8), (8, 24), (2, 4), (24, 24)])
def test_the_chunked_rule_is_the_token_by_token_rule(chunk, span, gates):
    """Values and all five gradients, over sequences of several chunks and
    spans (24 tokens in chunks of 4 and spans of 8, and others), with gates
    near both ends of ``exp(g)``: a state all but kept through a chunk, one
    wiped at every token (``exp(-3000)`` is zero: nothing above the
    diagonal of a chunk may overflow or turn a gradient into ``nan``), and
    both mixed."""
    x = _rule_inputs(5, gates=gates)
    weigh = jax.random.normal(jax.random.key(6), x[2].shape)
    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.value_and_grad(
            lambda *a: jnp.sum(weigh * qwen3_next.delta_rule(*a, chunk, span)),
            argnums=(0, 1, 2, 3, 4))(*x)
        want, want_grads = jax.value_and_grad(
            lambda *a: jnp.sum(weigh * _token_by_token(*a)),
            argnums=(0, 1, 2, 3, 4))(*x)
        out = qwen3_next.delta_rule(*x, chunk, span)
        np.testing.assert_allclose(out, _token_by_token(*x), rtol=RULE_TOL,
                                   atol=RULE_TOL)
    assert np.isfinite(got) and abs(got - want) <= RULE_TOL * abs(want) + 1e-5
    for a, b in zip(got_grads, want_grads):
        # a gradient may be zero throughout (``dg`` where every state is
        # wiped): held to the inputs' scale, which is one, as well
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, b, rtol=5 * RULE_TOL,
                                   atol=5 * RULE_TOL * max(
                                       1.0, float(jnp.max(jnp.abs(b)))))


def test_the_rule_by_hand_at_one_head_and_two_tokens():
    """``S_1 = k_1 (beta_1 v_1)^T``; ``S_2 = e^{g_2} S_1 + k_2 (beta_2 (v_2
    - e^{g_2} S_1^T k_2))^T``; ``o_t = S_t^T q_t``."""
    q, k, v, g, beta = _rule_inputs(9, n=1, hk=1, groups=1, t=2, dk=4, dv=4)
    with jax.default_matmul_precision("highest"):
        out = qwen3_next.delta_rule(q, k, v, g, beta, 2, 2)[0, 0, 0]
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in
                        (q[0, 0], k[0, 0], v[0, 0, 0], g[0, 0, 0],
                         beta[0, 0, 0]))
    s1 = np.outer(k[0], beta[0] * v[0])
    s2 = np.exp(g[1]) * s1
    s2 = s2 + np.outer(k[1], beta[1] * (v[1] - s2.T @ k[1]))
    np.testing.assert_allclose(out[0], s1.T @ q[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out[1], s2.T @ q[1], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("size", [2, 4, 6, 64])
def test_the_unit_lower_inverse_is_the_inverse(size):
    a = jnp.tril(jax.random.normal(jax.random.key(size), (3, size, size)),
                 -1) * 0.3
    with jax.default_matmul_precision("highest"):
        inv = qwen3_next._unit_lower_inverse(a)
        want = jnp.linalg.inv(jnp.eye(size) + a)
        weigh = jax.random.normal(jax.random.key(1), a.shape)
        got_grad = jax.grad(lambda a: jnp.sum(
            weigh * qwen3_next._unit_lower_inverse(a)))(a)
        want_grad = jax.grad(lambda a: jnp.sum(
            weigh * jnp.linalg.inv(jnp.eye(size) + a)))(a)
    np.testing.assert_allclose(inv, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got_grad, want_grad, rtol=1e-3, atol=1e-4)


def test_the_convolution_is_causal_and_starts_from_zeros():
    x = jax.random.normal(jax.random.key(0), (2, 9, 3))
    kernel = jax.random.normal(jax.random.key(1), (4, 3))
    y = qwen3_next.causal_conv(x, kernel)
    for t in range(9):
        want = sum(kernel[j] * x[:, t - 3 + j] for j in range(4)
                   if t - 3 + j >= 0)
        np.testing.assert_allclose(y[:, t], want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        y[0], plain.conv_taps(x[0], kernel), rtol=1e-6, atol=1e-6)
    # a later token moves no earlier output
    later = qwen3_next.causal_conv(x.at[:, 5].add(1.0), kernel)
    assert np.array_equal(np.asarray(later[:, :5]), np.asarray(y[:, :5]))
    assert not np.allclose(later[:, 5], y[:, 5])


def test_a_gated_delta_layer_reads_no_later_token():
    cfg = qwen3_next.tiny()
    params, _ = qwen3_next.init(jax.random.key(0), cfg)
    p = params["layers"][0]["op"]
    u = jax.random.normal(jax.random.key(1), (1, 16, cfg.hidden_size))
    y = qwen3_next.gated_delta(p, u, cfg)
    later = qwen3_next.gated_delta(p, u.at[:, 11].add(1.0), cfg)
    np.testing.assert_allclose(later[:, :11], y[:, :11], rtol=1e-5, atol=1e-7)
    assert float(jnp.max(jnp.abs(later[:, 11:] - y[:, 11:]))) > 1e-5


# ---------------------------------------------------------------------------
# the full layer: a quarter of a head rotated, an output gate
# ---------------------------------------------------------------------------

def test_the_first_64_of_256_are_rotated_and_the_rest_bit_equal():
    x = jax.random.normal(jax.random.key(0), (2, 3, 16, 256), jnp.bfloat16)
    y = L.rotary(x, 1e7, axis=-2, rotary_dim=64)
    assert y.shape == x.shape and y.dtype == x.dtype
    assert np.array_equal(np.asarray(y[..., 64:], np.float32),
                          np.asarray(x[..., 64:], np.float32))
    # the pairs are (j, j + 32) at a head of 64's frequencies
    want = L.rotary(x[..., :64], 1e7, axis=-2)
    assert np.array_equal(np.asarray(y[..., :64], np.float32),
                          np.asarray(want, np.float32))
    xf = np.asarray(x[0, 0], np.float64)
    t, j = 5, 3
    angle = t * 1e7 ** (-2 * j / 64)
    np.testing.assert_allclose(
        float(y[0, 0, t, j]),
        xf[t, j] * np.cos(angle) - xf[t, j + 32] * np.sin(angle), atol=2e-2)
    np.testing.assert_allclose(
        float(y[0, 0, t, j + 32]),
        xf[t, j + 32] * np.cos(angle) + xf[t, j] * np.sin(angle), atol=2e-2)
    # position 0 turns nothing; the whole head as before where not told
    assert np.array_equal(np.asarray(y[:, :, 0], np.float32),
                          np.asarray(x[:, :, 0], np.float32))
    assert np.array_equal(
        np.asarray(L.rotary(x, 1e7, axis=-2, rotary_dim=256), np.float32),
        np.asarray(L.rotary(x, 1e7, axis=-2), np.float32))
    with pytest.raises(ValueError, match="whole pairs"):
        L.rotary(x, 1e7, axis=-2, rotary_dim=63)
    # against the reference's spelling, token-major
    theirs = plain._rotate_first(
        jnp.swapaxes(x[0], 0, 1).astype(jnp.float32), 1e7, 64,
        jnp.arange(16))
    np.testing.assert_allclose(
        jnp.swapaxes(theirs, 0, 1), L.rotary(x[0].astype(jnp.float32), 1e7,
                                             axis=-2, rotary_dim=64),
        rtol=1e-5, atol=1e-5)


def test_the_output_gate_multiplies_the_heads_output():
    """With the gate's columns of ``q_proj`` zero the gated layer is half
    the ungated one over the queries' columns alone (``sigmoid(0)``); a
    large gate passes the ungated output whole."""
    cfg = qwen3_next.tiny()
    params, _ = qwen3_next.init(jax.random.key(0), cfg)
    p = dict(params["layers"][3]["op"])
    hq, hd = cfg.num_attention_heads, cfg.head_dim
    u = jax.random.normal(jax.random.key(1), (2, 16, cfg.hidden_size))
    w = p["q_proj"].reshape(-1, hq, 2, hd)
    plain_p = dict(p, q_proj=w[:, :, 0].reshape(-1, hq * hd))
    with jax.default_matmul_precision("highest"):
        ungated = lfm2.attention(plain_p, u, cfg, rotary_dim=cfg.rotary_dim)
        shut = dict(p, q_proj=w.at[:, :, 1].set(0.0).reshape(p["q_proj"].shape))
        half = lfm2.attention(shut, u, cfg, rotary_dim=cfg.rotary_dim,
                              gated=True)
    np.testing.assert_allclose(half, 0.5 * ungated, rtol=1e-5, atol=1e-7)


def test_the_norms_are_zero_centred():
    x = jax.random.normal(jax.random.key(0), (3, 8))
    w = jax.random.normal(jax.random.key(1), (8,)) * 0.1
    got = qwen3_next._norm({"scale": w}, x, 1e-6)
    rms = jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(got, x / rms * (1 + w), rtol=1e-5)
    np.testing.assert_allclose(plain._norm({"scale": w}, x, 1e-6), got,
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# the expert layer and its shares
# ---------------------------------------------------------------------------

def _expert_layer(key, experts=32):
    """An uncut expert layer of ``experts`` experts, 4 a token, with its
    shared expert, and a batch of normalised inputs."""
    sizes = dict(SIZES, num_experts=experts, num_experts_per_tok=4,
                 published={"num_experts": experts}, share=0)
    params, _ = builder.init(jax.random.key(key), sizes)
    ffn = params["layers"][0]["ffn"]
    m = jax.random.normal(jax.random.key(key + 1), (2, 64, 32))
    return sizes, ffn, m


@pytest.mark.parametrize("shares", [1, 4, 32])
def test_the_shares_of_a_layer_add_up_to_the_whole(shares):
    """32 experts over ``shares`` chips (thirty-two as the configuration's
    deployment has them, one expert a chip here): every chip routes over
    all 32 with gates normalised over all of a token's four experts and
    gives its own experts' part; **the routed parts of all shares plus the
    gated shared expert, which every chip computes alike, counted once**
    are the uncut reference's expert layer, and every assignment is
    computed once."""
    sizes, whole, m = _expert_layer(3)
    held = 32 // shares
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda m: plain.routed(
            whole, m, plain.gates(whole, m, sizes), 0, 32)
            + plain.shared(whole, m))(m)
        total, computed = jnp.zeros_like(m), 0.0
        for share in range(shares):
            cfg = qwen3_next.tiny(num_experts=32, num_experts_per_tok=4,
                                  first_expert=share * held,
                                  experts_held=held, moe_row_block=8)
            p = {"router": whole["router"],
                 **{k: whole[k][share * held:(share + 1) * held]
                    for k in ("w1", "w3", "w2")}}
            state = qwen3_next.init_state(cfg)["layers"][0]
            part, counters = lfm2.moe_ffn(p, state, m, cfg, sdar.route)
            np.testing.assert_allclose(
                part, jax.vmap(lambda m: plain.routed(
                    p, m, plain.gates(p, m, sizes), share * held, held))(m),
                rtol=2e-5, atol=2e-6)
            total = total + part
            computed += float(counters["held"])
            assert float(counters["dropped"]) == 0.0
            assert float(counters["drawn"].sum()) == m.shape[0] * 64 * 4
        once = qwen3_next.shared_expert(whole, m)
        np.testing.assert_allclose(
            once, jax.vmap(lambda m: plain.shared(whole, m))(m), rtol=2e-5,
            atol=2e-6)
    np.testing.assert_allclose(total + once, want, rtol=2e-5, atol=2e-6)
    assert computed == m.shape[0] * 64 * 4         # every assignment once
    assert float(jnp.max(jnp.abs(total))) > 1e-4
    assert float(jnp.max(jnp.abs(once))) > 1e-4


def test_the_shared_experts_gate_is_one_sigmoid_a_token():
    sizes, whole, m = _expert_layer(5)
    with jax.default_matmul_precision("highest"):
        gated = qwen3_next.shared_expert(whole, m)
        ungated = lfm2.dense_ffn(whole["shared"], m)
        gate = jax.nn.sigmoid(m @ whole["shared_gate"])
    assert gate.shape == (2, 64, 1)
    np.testing.assert_allclose(gated, gate * ungated, rtol=1e-5, atol=1e-8)


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
        assert set(layer) == {"drawn", "held", "dropped", "computed",
                              "combined"}
        assert layer["drawn"].shape[-1] == 8
        assert 0 <= float(layer["held"]) <= trained["positions"] * 2
        assert float(layer["held"]) <= float(layer["computed"])
        assert float(layer["dropped"]) == 0.0


def test_every_part_of_the_step_is_under_its_stage(trained):
    text = trained["text"]
    mine = (scopes.STAGE_GATED_DELTA, scopes.STAGE_DELTA_RULE,
            scopes.STAGE_ATTENTION, scopes.STAGE_SHARED_EXPERT,
            scopes.STAGE_MOE_ROUTER, scopes.STAGE_MOE_DISPATCH,
            scopes.STAGE_MOE_EXPERTS, scopes.STAGE_MOE_COMBINE,
            scopes.STAGE_LM_HEAD)
    for stage in mine:
        assert stage in text, stage
        assert STAGE.fullmatch(stage), stage             # the reducer reads it
        assert stage in scopes.ALL_STAGES and stage in scopes.MODEL_STAGES
    for other in (scopes.STAGE_SHORT_CONV, scopes.STAGE_MLA_LATENT,
                  scopes.STAGE_WINDOW_ATTENTION, scopes.STAGE_DENSE_FFN,
                  scopes.STAGE_DIFFUSION_NOISE):
        assert other not in text
    # the rule nests inside the operator, and the rightmost scope names the
    # part, for reducer and report alike
    name = ("jit(device_step)/grace/forward_backward/jvp(grace/gated_delta)/"
            "grace/delta_rule/while/body/dot_general")
    assert stage_of(name) == "grace/delta_rule"
    assert scopes.match_stage(name) == scopes.STAGE_DELTA_RULE
    outer = "grace/forward_backward/jvp(grace/gated_delta)/conv"
    assert stage_of(outer) == "grace/gated_delta"
    assert scopes.match_stage(outer) == scopes.STAGE_GATED_DELTA
    assert len(scopes.MODEL_STAGES) == 14
