"""Shared sparse-codec primitive: scatter (values, indices) into a dense tensor.

The reference repeats this scatter in every sparsifying compressor
(e.g. grace_dl/dist/compressor/topk.py:14-18 `desparsify`); here it is the
one shared implementation used by topk/randomk/threshold/dgc/adaq.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from grace_tpu.telemetry.scopes import (STAGE_COMPRESS, STAGE_DECOMPRESS,
                                        trace_stage)

# The largest (rows, k) view, in elements, whose reshape from or to the flat
# buffer XLA:TPU still runs as one operation. Past it, and unless k is a
# multiple of the 128 lanes (then the reshape moves nothing), the compiler
# runs the relayout as a ``while`` loop of its own (``wide.body``: 4-row
# windows by dynamic-slice and dynamic-update-slice into the tiled view,
# under no ``op_name``) at about a fifth of the HBM bandwidth: 52 ms of the
# 727 ms LFM2 step (PERF.md, PR 29). Read from compiles for a described
# v5e:2x2 (libtpu 0.0.34) of one leaf's compensate -> compress -> decompress
# -> update on the view route, the count of `` while(``:
#   rows   k        rows*k      loops     rows  k        rows*k     loops
#   101    41,527   4,194,227   0         11    381,299  4,194,289  0
#   101    41,529   4,194,429   2         11    381,301  4,194,311  2
#   1001   4,189    4,193,189   0         101   23,592   2,382,792  0 (ResNet)
#   1001   4,191    4,195,191   2         101   41,600   4,201,600  0 (k%128=0)
# float32 and bfloat16 alike: the count is of elements, not bytes.
RELAYOUT_LOOP_ELEMENTS = 1 << 22
# The rows one step of the route's own walk takes (:func:`row_blocks`), in
# elements: half the limit. The whole LFM2 step on the chip (PERF.md, PR 29;
# parent 727.28 ms, 51 MiB in JAX's compile cache, 302.6 MB serialized):
#   blocks of 2**22   654.73 ms   57 MiB   326.4 MB
#   blocks of 2**21   651.64 ms   51 MiB   299.6 MB   (a block's buffers
#   blocks of 2**20   657.77 ms            295.6 MB    stay in VMEM)
# The reshape inside a step is unrolled code, about 75 KB a row.
ROW_BLOCK_ELEMENTS = 1 << 21


def takes_row_slices(rows: int, k: int) -> bool:
    """Whether the (rows, k) view is reached through row-block slices of
    the flat buffer (:func:`chunk_first_max`, :func:`chunkwise_dense`) and
    not through a reshape: exactly where XLA:TPU would loop the reshape.
    Static facts only, so each leaf of a program takes its own route."""
    return rows * k > RELAYOUT_LOOP_ELEMENTS and k % 128 != 0


def row_blocks(rows: int, k: int) -> tuple:
    """``(per, count)``: the whole rows of the view as ``count`` blocks of
    ``per`` rows, at most :data:`ROW_BLOCK_ELEMENTS` a block (so its
    reshape stays one operation), evened out over the blocks and in whole
    sublane tiles of 8 where a block holds that many. The last block starts
    early enough to end with the last whole row, so every block has one
    shape: 13 blocks of 8 rows for the 100 whole rows of a 25 M-element
    leaf at 1 %, one row a block once a single row passes the limit."""
    most = max(1, min(ROW_BLOCK_ELEMENTS, RELAYOUT_LOOP_ELEMENTS) // k)
    if most >= 8:
        most -= most % 8
    count = -(-rows // min(most, rows))
    per = -(-rows // count)
    if most >= 8:
        per = min(-(-per // 8) * 8, most, rows)
    return per, count


@functools.partial(jax.jit, static_argnames=("k", "per", "count"))
def row_blocks_first_max(flat: jax.Array, k: int, per: int, count: int):
    n = flat.shape[0]
    whole = n // k                     # rows without a padding lane

    def merge(held, top, row, value):
        # argmax's rule in row order: later rows win only with a strictly
        # larger |x|, or with the column's first NaN. Rows met twice (the
        # last block overlaps the one before) change nothing.
        take = (top > held[0]) | (jnp.isnan(top) & ~jnp.isnan(held[0]))
        return tuple(jnp.where(take, new, old)
                     for new, old in zip((top, row, value), held))

    def block(i, held):
        first = jnp.minimum(i * per, whole - per)
        body = lax.reshape(
            lax.dynamic_slice(flat, (first * k,), (per * k,)), (per, k))
        mag = jnp.abs(body)
        row = jnp.argmax(mag, axis=0).astype(jnp.int32)
        hot = jnp.arange(per, dtype=jnp.int32)[:, None] == row[None, :]
        return merge(held, jnp.max(mag, axis=0), row + first,
                     jnp.sum(jnp.where(hot, body, 0), axis=0))

    # nothing is held at first: -1 loses to every |x|, NaN included
    held = (jnp.full((k,), -1, flat.dtype), jnp.zeros((k,), jnp.int32),
            jnp.zeros((k,), flat.dtype))
    held = lax.fori_loop(0, count, block, held)
    if n > whole * k:                  # the last row, padded, stays 1-D
        last = lax.pad(lax.slice(flat, (whole * k,), (n,)),
                       jnp.zeros((), flat.dtype),
                       [(0, (whole + 1) * k - n, 0)])
        held = merge(held, jnp.abs(last), jnp.int32(whole), last)
    _, win_row, values = held
    # The view route sums the kept entry with rows - 1 >= 1 zeros, so a
    # kept -0.0 reads +0.0 there; here it may have had no zero to meet.
    return jnp.where(values == 0, jnp.zeros((), flat.dtype), values), win_row


def chunk_first_max(flat: jax.Array, k: int):
    """``(values, win_row)`` of the zero-padded (rows, k) view of ``flat``
    — per column the first row of largest ``|x|`` and its value — without
    the view. The whole rows are walked in the equal blocks of
    :func:`row_blocks`, each a slice of the FLAT buffer reshaped on its
    own, by one short loop (13 steps for a 25 M-element leaf): the slice's
    offset is the only dynamic thing, so one reshape's code serves every
    block, and the blocks' maxima are compared in row order. The last row,
    where it is padded, is compared as it lies, in one dimension. Bit for
    bit what ``argmax(|view|, axis=0)`` and the one-hot masked sum give
    (TopKCompressor._chunk_compress): the first row wins a tie, a padding
    lane (0, in the last row only) never beats a real row, a column's first
    NaN wins as ``jnp.argmax`` has it, and a kept -0.0 reads +0.0 on both
    routes. Traced once per distinct ``(n, k, dtype)``."""
    per, count = row_blocks(flat.shape[0] // k, k)
    with trace_stage(f"{STAGE_COMPRESS}/row_slices"):
        return row_blocks_first_max(flat, k=k, per=per, count=count)


@functools.partial(jax.jit, static_argnames=("numel", "per", "count"))
def row_blocks_dense(values: jax.Array, win_row: jax.Array, numel: int,
                     per: int, count: int) -> jax.Array:
    k = values.shape[0]
    whole = numel // k
    zero = jnp.zeros((), values.dtype)

    def block(i, out):
        first = jnp.minimum(i * per, whole - per)
        row = first + jnp.arange(per, dtype=win_row.dtype)[:, None]
        dense = jnp.where(row == win_row[None, :], values[None, :], zero)
        return lax.dynamic_update_slice(
            out, lax.reshape(dense, (per * k,)), (first * k,))

    out = lax.fori_loop(0, count, block, jnp.zeros((numel,), values.dtype))
    if numel > whole * k:              # what the last row has of real lanes
        last = jnp.where(win_row == whole, values, zero)
        out = lax.dynamic_update_slice(
            out, lax.slice(last, (0,), (numel - whole * k,)), (whole * k,))
    return out


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
    multiple of 128, a physical relayout. Up to
    :data:`RELAYOUT_LOOP_ELEMENTS` it is one operation that fuses into its
    consumer; past it XLA:TPU runs it as a loop over 4-row windows, so such
    a leaf (:func:`takes_row_slices`) is built the other way round: the
    one-hot rows of each block of :func:`row_blocks` flattened on their own
    and written at their offset ``first * k`` of the flat tensor by one
    short loop, the padded row's real lanes after them — the same elements,
    each step one reshape of a few million. Vmapped over W
    gathered payloads the view route's flatten becomes a (W, rows, k) stack
    that loops already at a million elements; the all-gather exchange of
    W > 1 payloads therefore decodes through :func:`chunkwise_dense_sum`,
    which sums first and relayouts once.
    """
    if takes_row_slices(rows, values.shape[0]):
        with trace_stage(f"{STAGE_DECOMPRESS}/row_slices"):
            per, count = row_blocks(numel // values.shape[0],
                                    values.shape[0])
            return row_blocks_dense(values, win_row, numel=numel, per=per,
                                    count=count).reshape(shape)
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
