"""Shared sparse-codec primitive: scatter (values, indices) into a dense tensor.

The reference repeats this scatter in every sparsifying compressor
(e.g. grace_dl/dist/compressor/topk.py:14-18 `desparsify`); here it is the
one shared implementation used by topk/randomk/threshold/dgc/adaq.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def scatter_dense(values: jax.Array, indices: jax.Array, numel: int,
                  shape: tuple) -> jax.Array:
    """Place ``values`` at flat ``indices`` of a zero tensor of ``shape``.

    Fixed-capacity payloads rely on invalid lanes carrying value 0, which a
    scatter-set writes harmlessly (every index is in range; duplicates do
    not occur by construction — top_k/permutation indices are unique).
    """
    flat = jnp.zeros((numel,), values.dtype).at[indices].set(values)
    return flat.reshape(shape)


def chunkwise_dense(values: jax.Array, win_row: jax.Array, rows: int,
                    numel: int, shape: tuple) -> jax.Array:
    """Scatter-free dense build for chunk-structured sparsity.

    For payloads where exactly one element per column of the (rows, k)
    row-major view of the flat tensor is kept (TopKCompressor
    ``algorithm='chunk'``), the dense tensor is a one-hot row-select per
    column — a single fused elementwise comparison instead of a scatter.
    TPU scatter serializes (measured: it dominates the Top-K pipeline on a
    25.5M-element fused gradient); this build is pure VPU work at the same
    O(n) cost as one elementwise pass.

    ``values``/``win_row`` have length k; element c lands at flat index
    ``win_row[c] * k + c``. Padding columns introduced at compress time
    carry value 0, so rows*k > numel overhang truncates harmlessly.

    This is ONE rank's decode: ``TopKCompressor.decompress`` (the memory
    update, the ring/two-shot hops, the W = 1 exchange). The final
    ``reshape(-1)`` flattens a tiled (rows, k) layout whose k is rarely a
    multiple of 128, a physical relayout; alone it fuses into its consumer,
    but vmapped over W gathered payloads it becomes a (W, rows, k) stack
    that XLA:TPU relayouts in a loop over row windows per large leaf. The
    all-gather exchange of W > 1 payloads therefore decodes through
    :func:`chunkwise_dense_sum`, which sums first and relayouts once.
    """
    mask = jnp.arange(rows, dtype=win_row.dtype)[:, None] == win_row[None, :]
    dense = jnp.where(mask, values[None, :], jnp.zeros((), values.dtype))
    return dense.reshape(-1)[:numel].reshape(shape)


def chunkwise_dense_sum(values: jax.Array, win_row: jax.Array, rows: int,
                        numel: int, shape: tuple) -> jax.Array:
    """Sum of W ranks' chunk-structured payloads, built dense ONCE.

    ``values``/``win_row`` are the gathered ``(W, k)`` stacks. The ranks are
    added in the (rows, k) view — W compares and adds per element in one
    fused elementwise pass, rank 0 first like ``jnp.sum(stacked, axis=0)``
    — and only the sum is flattened, truncated and reshaped: the same
    float sum over the same W addends as ``vmap(chunkwise_dense)`` + sum,
    without the (W, rows, k) tensor and its W relayouts (see
    :func:`chunkwise_dense`).
    """
    row = jnp.arange(rows, dtype=win_row.dtype)[:, None]
    zero = jnp.zeros((), values.dtype)
    dense = jnp.where(row == win_row[0][None, :], values[0][None, :], zero)
    for w in range(1, values.shape[0]):
        dense = dense + jnp.where(row == win_row[w][None, :],
                                  values[w][None, :], zero)
    return dense.reshape(-1)[:numel].reshape(shape)
