"""Plain reference of the data-parallel training step every cell runs:
``jax.value_and_grad`` of the configuration's plain loss on each replica's
share of the batch, the cell's exchange in its plain form, the cell's
optimizer. No ``grace_tpu``, no ``shard_map``: the replicas are a leading
axis walked one after another on one device, so each keeps its own batch
statistics and its own error-feedback state, as on the mesh.

``follow`` takes the first steps from seeded weights and returns what the
benchmark compares with the program: each step's loss, the norm of every
leaf of the first gradient as the optimizer gets it, and the norm of every
leaf of the parameters' change after the last step.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import optax
from jax import lax


def codec(name):
    return importlib.import_module(f"benchmarks.reference.{name}")


def optimizer(spec, dtype=None):
    if spec["name"] == "sgd":
        return optax.sgd(spec["lr"])
    if spec["name"] == "adamw":
        return optax.adamw(spec["lr"], mu_dtype=dtype)
    raise ValueError(f"no plain optimizer {spec['name']!r}")


def init_codec_state(params, key, world, spec):
    """The seeded start state of the exchange, one entry per leaf in
    ``tree_leaves`` order."""
    mod = codec(spec["reference"])
    leaves = jax.tree_util.tree_leaves(params)
    return [mod.init_state(tuple(p.shape), jax.random.fold_in(key, i), world,
                           spec) for i, p in enumerate(leaves)]


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def make_step(loss_fn, tx, spec, grad_dtype=None):
    """One jitted reference step over ``(params, mstate, opt_state,
    codec_state, batch_w)`` with ``batch_w`` leaves shaped ``(W, n, ...)``."""
    mod = codec(spec["reference"])

    def step(params, mstate, opt_state, codec_state, batch_w):
        def replica(b):
            (loss, ms), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mstate, b)
            return loss, ms, g

        losses, ms, grads = lax.map(replica, batch_w)
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        out, new_codec = [], []
        for g, s in zip(leaves, codec_state):
            g_hat, s = mod.exchange(g.astype(jnp.float32), s, spec)
            out.append(g_hat if grad_dtype is None
                       else g_hat.astype(grad_dtype))
            new_codec.append(s)
        g_hat = jax.tree_util.tree_unflatten(treedef, out)
        updates, opt_state = tx.update(g_hat, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        ms = jax.tree_util.tree_map(lambda x: jnp.mean(x, axis=0), ms)
        return (new_params, ms, opt_state, new_codec, jnp.mean(losses),
                leaf_norms(g_hat))

    return jax.jit(step, donate_argnums=(0, 1, 2, 3))


def follow(loss_fn, params, mstate, codec_state, batch, *, world, steps,
           optimizer_spec, codec_spec, lower_precision=False):
    """Take ``steps`` reference steps. ``lower_precision`` is the control:
    parameters, gradients and optimizer state held in bfloat16 where the
    configuration states float32."""
    dtype = jnp.bfloat16 if lower_precision else None
    if lower_precision:
        params = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16), params)
    tx = optimizer(optimizer_spec, dtype)
    step = make_step(loss_fn, tx, codec_spec, dtype)
    batch_w = jax.tree_util.tree_map(
        lambda x: x.reshape(world, x.shape[0] // world, *x.shape[1:]), batch)
    start = jax.tree_util.tree_map(jnp.copy, params)
    opt_state = tx.init(params)
    losses, grad1 = [], None
    for i in range(steps):
        params, mstate, opt_state, codec_state, loss, gnorms = step(
            params, mstate, opt_state, codec_state, batch_w)
        losses.append(float(loss))
        if i == 0:
            grad1 = gnorms
    delta = leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        params, start))
    return {"losses": losses,
            "grad1_norms": [float(x) for x in grad1],
            "delta_norms": [float(x) for x in delta]}
