"""Plain reference of PowerSGD (Vogels et al., arXiv:1905.13727, Alg. 1)
with error feedback and a warm-started ``Q``.

A leaf of two or more dimensions is read as the matrix ``M`` of shape
``(prod(leading dims), last dim)``; with ``r = min(rank, rows, cols)``:
``Q <- orth(Q)``, ``P <- orth(mean_w(M_w Q))``, ``Q <- mean_w(M_w^T P)``,
and every replica applies ``P Q^T`` and keeps ``M_w - P Q^T`` as its
residual. One-dimensional leaves are averaged uncompressed. The start
``Q`` is part of the run's seeded state: the benchmark draws it and gives
the same one to the program. Imports nothing of ``grace_tpu``.
"""

import math

import jax
import jax.numpy as jnp


def _factors(shape, rank):
    cols = shape[-1]
    rows = math.prod(shape[:-1])
    return rows, cols, min(rank, rows, cols)


def init_state(shape, key, world, spec):
    if len(shape) <= 1:
        return None
    rows, cols, r = _factors(shape, spec["compress_rank"])
    return {"q": jax.random.normal(key, (cols, r), jnp.float32),
            "residual": jnp.zeros((world, *shape), jnp.float32)}


def exchange(grads, state, spec):
    if state is None:
        return jnp.mean(grads, axis=0), None
    shape = grads.shape[1:]
    rows, cols, _ = _factors(shape, spec["compress_rank"])
    m = (grads + state["residual"]).reshape(-1, rows, cols)
    q, _ = jnp.linalg.qr(state["q"])
    p, _ = jnp.linalg.qr(jnp.mean(m @ q, axis=0))
    q = jnp.mean(jnp.swapaxes(m, 1, 2) @ p, axis=0)
    approx = p @ q.T
    return (approx.reshape(shape),
            {"q": q, "residual": (m - approx).reshape(grads.shape)})


def seeded(state):
    """The start ``Q`` is drawn from the seed; the program is handed it."""
    return None if state is None else state["q"]
