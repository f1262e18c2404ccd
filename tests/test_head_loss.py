"""The decoders' head (``models/lfm2.head_loss``, PR 44): final norm, head
and weighted cross-entropy, whose gradient is formed in the walk that makes
the logits. Held against the plain formula differentiated by ``jax.grad``
with no walk, and counted in the traced program of each decoder's loss:
three products a part under ``grace/lm_head``, none of them recomputed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grace_tpu.models import deepseek_v3, layers as L, lfm2, sdar, smallthinker
from grace_tpu.telemetry import scopes

N, T = 4, 16
TOL = 1e-6


def _case(weights="ones", dtype=jnp.float32):
    """A tiny head, the last layer's output of four sequences of 16, their
    targets and a weight a position."""
    cfg = lfm2.tiny()
    params, _ = lfm2.init(jax.random.key(3), cfg)
    p = {"final_norm": {"scale": 1.0 + 0.1 * jax.random.normal(
        jax.random.key(6), (cfg.hidden_size,))}, "head": params["head"]}
    x = jax.random.normal(jax.random.key(4), (N, T, cfg.hidden_size), dtype)
    targets = jax.random.randint(jax.random.key(5), (N, T), 0, cfg.vocab_size)
    if weights == "ones":
        w = jnp.ones((N, T), jnp.float32)
    elif weights == "zero_last":
        w = jnp.broadcast_to((jnp.arange(T) < T - 1).astype(jnp.float32),
                             (N, T))
    else:   # block diffusion's: a weight where a token is masked, half are
        w = jnp.where(jax.random.bernoulli(jax.random.key(7), 0.5, (N, T)),
                      jax.random.uniform(jax.random.key(8), (N, T),
                                         minval=1.0, maxval=8.0), 0.0)
    return cfg, p, x, targets, w


def _plain(p, x, targets, w, scale, eps):
    """The formula with no walk: all logits at once."""
    u = L.rms_apply(p["final_norm"], x, eps)
    logp = jax.nn.log_softmax((u @ p["head"].astype(u.dtype)).astype(
        jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * w) * scale


def _gap(got, want):
    return float(jnp.max(jnp.abs(got - want)))


@pytest.mark.parametrize("cotangent", [1.0, -2.5])
@pytest.mark.parametrize("weights", ["ones", "zero_last", "half_zero"])
@pytest.mark.parametrize("sequences", [1, 2])
def test_the_walk_gives_the_plain_formulas_loss_and_gradients(
        sequences, weights, cotangent):
    cfg, p, x, targets, w = _case(weights)
    scale = 1.0 / (N * T)

    def walked(p, x):
        return cotangent * lfm2.head_loss(p, x, targets, w, scale,
                                          sequences * T, cfg.norm_eps)

    def plain(p, x):
        return cotangent * _plain(p, x, targets, w, scale, cfg.norm_eps)

    with jax.default_matmul_precision("highest"):
        loss, (dp, dx) = jax.jit(jax.value_and_grad(walked, (0, 1)))(p, x)
        want, (want_dp, want_dx) = jax.jit(
            jax.value_and_grad(plain, (0, 1)))(p, x)
    assert abs(float(loss) - float(want)) <= TOL
    assert _gap(dx, want_dx) <= TOL
    assert _gap(dp["head"], want_dp["head"]) <= TOL
    assert _gap(dp["final_norm"]["scale"],
                want_dp["final_norm"]["scale"]) <= TOL
    assert float(jnp.max(jnp.abs(want_dp["head"]))) > 100 * TOL
    # where a position weighs nothing its input takes no gradient at all
    assert not np.any(np.asarray(dx)[np.asarray(w) == 0])


@pytest.mark.parametrize("sequences", [1, 2, 4])
def test_called_without_differentiation_it_gives_the_same_loss(sequences):
    cfg, p, x, targets, w = _case("half_zero")
    args = (p, x, targets, w, 1.0 / (N * T), sequences * T, cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        alone = jax.jit(lfm2.head_loss, static_argnums=(4, 5, 6))(*args)
        differentiated, _ = jax.jit(jax.value_and_grad(
            lambda p: lfm2.head_loss(p, *args[1:])))(p)
    assert float(alone) == float(differentiated)


def test_in_bfloat16_the_gradients_are_those_of_the_parts_summed():
    """Activations in bfloat16, weights in float32, as the cells run: ``dx``
    comes in the activations' dtype, ``d head`` and the norm's in float32,
    and they are what ``jax.grad`` gives for the sum of the parts' losses,
    to the rounding of bfloat16 (the CPU fuses the two programs apart, so
    not to the bit)."""
    cfg, p, x, targets, w = _case("zero_last", jnp.bfloat16)
    scale = 1.0 / (N * (T - 1))

    def parts(p, x):
        return sum(lfm2._head_part(p, x[i:i + 2], targets[i:i + 2],
                                   w[i:i + 2], scale, cfg.norm_eps)
                   for i in (0, 2))

    loss, (dp, dx) = jax.jit(jax.value_and_grad(
        lambda p, x: lfm2.head_loss(p, x, targets, w, scale, 2 * T,
                                    cfg.norm_eps), (0, 1)))(p, x)
    want, (want_dp, want_dx) = jax.jit(jax.value_and_grad(parts, (0, 1)))(p, x)
    assert abs(float(loss) - float(want)) <= TOL * float(want)
    assert dx.dtype == jnp.bfloat16 and dx.shape == x.shape
    assert dp["head"].dtype == dp["final_norm"]["scale"].dtype == jnp.float32
    for got, ref in zip(jax.tree_util.tree_leaves((dp, dx)),
                        jax.tree_util.tree_leaves((want_dp, want_dx))):
        got, ref = (np.asarray(a, np.float32) for a in (got, ref))
        assert np.max(np.abs(got - ref)) <= 2.0 ** -7 * np.max(np.abs(ref))


def test_parts_that_do_not_divide_the_positions_are_refused():
    cfg, p, x, targets, w = _case()
    with pytest.raises(ValueError, match="whole parts"):
        lfm2.head_loss(p, x, targets, w, 1.0, 24, cfg.norm_eps)
    with pytest.raises(ValueError, match="whole parts"):
        jax.grad(lfm2.head_loss)(p, x, targets, w, 1.0, 24, cfg.norm_eps)


# ---------------------------------------------------------------------------
# LFM2 and kanana over whole sequences: all T positions, the last weighs zero
# ---------------------------------------------------------------------------

def _sliced(p, x, ids, eps):
    """The next-token loss as it was spelt until PR 44: the last position
    cut off, ``T - 1`` positions a sequence."""
    n, t = ids.shape
    return _plain(p, x[:, :-1], ids[:, 1:], jnp.ones((n, t - 1)),
                  1.0 / (n * (t - 1)), eps)


@pytest.mark.parametrize("seq_block", [1, 2])
def test_the_head_over_whole_sequences_is_the_sliced_formula(seq_block):
    cfg = lfm2.tiny(seq_block=seq_block)
    params, _ = lfm2.init(jax.random.key(3), cfg)
    x = jax.random.normal(jax.random.key(4), (N, T, cfg.hidden_size))
    ids = jax.random.randint(jax.random.key(5), (N, T), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        loss, (dp, dx) = jax.jit(jax.value_and_grad(
            lambda p, x: lfm2.loss_of_hidden_states(p, x, ids, cfg),
            (0, 1)))(params, x)
        want, (want_dp, want_dx) = jax.jit(jax.value_and_grad(
            lambda p, x: _sliced(p, x, ids, cfg.norm_eps), (0, 1)))(params, x)
    assert abs(float(loss) - float(want)) <= TOL
    assert _gap(dx, want_dx) <= TOL
    assert not np.any(np.asarray(dx[:, -1]))         # exactly zero
    assert np.any(np.asarray(dx[:, -2]))
    for name in ("final_norm", "head"):
        for got, ref in zip(jax.tree_util.tree_leaves(dp[name]),
                            jax.tree_util.tree_leaves(want_dp[name])):
            assert _gap(got, ref) <= TOL
    # the head takes the gradient of nothing else
    assert not any(np.any(np.asarray(g)) for g in jax.tree_util.tree_leaves(
        {k: v for k, v in dp.items() if k not in ("final_norm", "head")}))


def test_kananas_loss_over_whole_sequences_is_the_sliced_formula():
    cfg = deepseek_v3.tiny(seq_block=2)
    params, state = deepseek_v3.init(jax.random.key(1), cfg)
    ids = jax.random.randint(jax.random.key(2), (N, T), 0, cfg.vocab_size)

    def sliced(params):
        x, _ = deepseek_v3.hidden_states(params, state, ids, cfg)
        return _sliced(params, x, ids, cfg.norm_eps)

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: deepseek_v3.next_token_loss(p, state, ids, cfg)[0]))(
                params)
        want, want_grads = jax.jit(jax.value_and_grad(sliced))(params)
    assert abs(float(loss) - float(want)) <= TOL
    gaps = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(_gap, grads, want_grads))
    assert max(gaps) <= TOL


# ---------------------------------------------------------------------------
# the counter that says the mechanism engages: the products in the program
# ---------------------------------------------------------------------------

def _head_products(jaxpr, stack=(), recomputed=False, found=None):
    """Every ``dot_general`` under ``grace/lm_head`` in a traced program:
    ``(name, inside a jax.checkpoint)`` each, the name from the outermost
    scope down (an inner program's names start at its equation)."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        here = stack + (str(eqn.source_info.name_stack),)
        if (eqn.primitive.name == "dot_general"
                and scopes.STAGE_LM_HEAD in "/".join(here)):
            found.append(("/".join(here), recomputed))
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _head_products(inner, here + (eqn.primitive.name,), recomputed
                           or eqn.primitive.name.startswith(
                               ("remat", "checkpoint")), found)
    return found


def _decoder_loss(name):
    """A tiny decoder's loss of its parameters, as the step calls it, with
    two parts to the head's walk."""
    ids = jax.random.randint(jax.random.key(2), (N, T), 0, 128)
    if name == "sdar":
        cfg = sdar.tiny(seq_block=2)
        params, state = sdar.init(jax.random.key(1), cfg)
        batch = {"ids": ids, "key": jax.random.key_data(
            jax.random.split(jax.random.key(9), N))}
        return (lambda p: sdar.block_diffusion_loss(p, state, batch, cfg)[0],
                params)
    model, cfg = {"lfm2": (lfm2, lfm2.tiny(seq_block=2)),
                  "kanana": (deepseek_v3, deepseek_v3.tiny(seq_block=2)),
                  "smallthinker": (smallthinker, smallthinker.tiny(
                      seq_block=4))}[name]
    params, state = model.init(jax.random.key(1), cfg)
    return lambda p: model.next_token_loss(p, state, ids, cfg)[0], params


@pytest.mark.parametrize("decoder", ["lfm2", "kanana", "sdar",
                                     "smallthinker"])
def test_the_gradient_holds_three_head_products_and_recomputes_none(decoder):
    """Logits, ``dx`` and ``d head`` in one loop over the parts: the logits
    are made once a step. A head recomputed in the backward pass held four
    products, one of them under a ``jax.checkpoint``."""
    loss, params = _decoder_loss(decoder)
    products = _head_products(
        jax.make_jaxpr(jax.value_and_grad(loss))(params).jaxpr)
    assert len(products) == 3, products
    assert not any(recomputed for _, recomputed in products)
    assert all("/scan" in name for name, _ in products)   # one walk


@pytest.mark.parametrize("decoder", ["lfm2", "kanana", "sdar",
                                     "smallthinker"])
def test_the_loss_alone_holds_one_head_product(decoder):
    loss, params = _decoder_loss(decoder)
    products = _head_products(jax.make_jaxpr(loss)(params).jaxpr)
    assert len(products) == 1, products


def test_the_counter_sees_a_recomputed_head():
    """The same count on the head as it was until PR 44 (the part under
    ``jax.checkpoint``, walked by ``lax.map``): four products, the fourth
    under the checkpoint. So the tests above would fail on it."""
    cfg, p, x, targets, w = _case()

    def part(p, xtw):
        with jax.named_scope(scopes.STAGE_LM_HEAD):
            return jnp.broadcast_to(
                lfm2._head_part(p, *xtw, 1.0, cfg.norm_eps), (2,))

    products = _head_products(jax.make_jaxpr(jax.grad(
        lambda p: jnp.sum(lfm2._over_sequences(part, p, (x, targets, w), 2)))
    )(p).jaxpr)
    assert len(products) == 4
    assert sum(recomputed for _, recomputed in products) >= 1
