"""Plain reference of chunked top-k with residual error feedback.

Semantics (Aji & Heafield 2017 sparsification with the strided-chunk
selection the library documents): with ``k = max(1, floor(ratio * n))``,
view the flattened leaf, zero-padded, as ``(ceil(n / k), k)`` row-major;
in each of the ``k`` columns keep the entry of largest magnitude (the
first on a tie) and drop the rest. Every replica adds its residual to its
gradient before selecting, keeps ``compensated - kept`` as the new
residual, and applies the mean over replicas of the kept entries.
Imports nothing of ``grace_tpu``.
"""

import jax
import jax.numpy as jnp


def init_state(shape, key, world, spec):
    return jnp.zeros((world, *shape), jnp.float32)


def select(flat, k):
    """``(values, indices)`` of the kept entries of one flat buffer."""
    n = flat.shape[0]
    if n < 2 * k:
        raise ValueError(f"chunked top-k needs n >= 2k, got n={n} k={k}")
    rows = -(-n // k)
    body = jnp.pad(flat, (0, rows * k - n)).reshape(rows, k)
    win = jnp.argmax(jnp.abs(body), axis=0)
    values = jnp.take_along_axis(body, win[None, :], axis=0)[0]
    return values, (win * k + jnp.arange(k)).astype(jnp.int32)


def densify(values, indices, n):
    return jnp.zeros((n,), values.dtype).at[indices].set(values)


def _one(grad, residual, ratio):
    n = grad.size
    k = max(1, int(n * ratio))
    compensated = (grad + residual).reshape(-1)
    values, indices = select(compensated, k)
    kept = densify(values, indices, n)
    return kept.reshape(grad.shape), (compensated - kept).reshape(grad.shape)


def exchange(grads, state, spec):
    kept, residual = jax.vmap(
        lambda g, r: _one(g, r, spec["compress_ratio"]))(grads, state)
    return jnp.mean(kept, axis=0), residual


def seeded(state):
    """Nothing of the start state is drawn from the seed (the residual
    starts at zero, in the program as here)."""
    return None
