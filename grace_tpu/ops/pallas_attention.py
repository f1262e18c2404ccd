"""Pallas TPU kernel: masked grouped-query attention with an online softmax.

``softmax(q k^T + mask) v`` of whole sequences without the ``(heads,
queries, keys)`` tensor ever reaching HBM: a tile of scores is made in VMEM
in float32, masked, exponentiated against a running maximum, multiplied
into the values and dropped. What reaches HBM is the output and one float32
log-sum-exp a query and head; the backward pass is the kernel's own
(``custom_vjp``): it recomputes each tile from ``q``, ``k``, ``v``, the
output and the log-sum-exp. Tiles in which the mask allows no pair are
skipped, in the grid and in the copies from HBM.

**Masks** (a value handed to :func:`masked_gqa`; each is evaluated from the
positions inside a tile, so no mask tensor reaches HBM either):
:data:`CAUSAL`, a query reads the keys at or before it (tiles wholly above
the diagonal are skipped), and :class:`BlockDiffusion` ``(seq_len,
block)``, block-diffusion training's mask over a doubled sequence, the
noised copy's ``seq_len`` positions first and the clean copy's behind them
(``models/sdar.py``): block-diagonal among the noised positions, strictly
block-causal from a noised query to the clean keys, block-causal among the
clean positions, and nothing from a clean query to a noised key. Of the 8 x
8 tiles of 1,024 a doubled sequence of 8,192 has, it visits 24 (4 on the
noised diagonal, 10 and 10 in the two lower triangles).

The kernel is JAX's ``splash_attention`` (``jax.experimental.pallas.ops.
tpu``), wrapped: its multi-head form with fewer key/value heads than query
heads, a head size for queries and keys and one for values, a mask it
evaluates from positions inside the tile (no mask tensor), the fused
backward kernel (``dk``, ``dv`` and ``dq`` from one recomputation of a
tile). Scores, running maximum, running sum and output accumulator are
float32; the forward multiplies float32 probabilities into the values, the
backward casts the probabilities and the score gradients to the
gradient's dtype for its products.

Three pairs of head sizes are taken (:data:`HEAD_DIMS`, queries and keys |
values): ``(64, 64)``, grouped-query attention as ``models/lfm2.py`` has
it, ``(192, 128)``, latent attention as ``models/deepseek_v3.py`` has it
(a 128-wide part without positions beside a 64-wide rotary part), and
``(128, 128)``, grouped-query attention as ``models/sdar.py`` has it. The
tile sizes were chosen by chip runs on a TPU v5e at ``(1, 4096, 32 | 8,
64)`` bfloat16, where JAX's other kernel, ``flash_attention``, read 1.6
times this one's time (PERF.md §6, PR 31), and read again at ``(1, 4096,
32, 192 | 128)`` (PERF.md §6, PR 32). Mosaic takes the 192 lanes as they
are (a tile and a half of 128): no zero padding to 256.

**The scale.** The kernel has none, and neither has this wrapper: what it
is given as ``q`` is what it multiplies into the keys, so ``softmax(q k^T +
mask) v`` is what comes back. Each caller scales ``q`` where that rounds
nothing its plain spelling does not round: ``models/lfm2.py`` multiplies
``q`` by ``1 / sqrt(64)`` in ``q``'s dtype (a power of two: exact) and, at
a head size whose root is none (128, ``models/sdar.py``), folds the scale
into the float32 weight of the queries' norm; ``models/deepseek_v3.py``
folds ``1 / sqrt(192)`` into the query projection's weights in float32 as
it casts them, so ``q`` is rounded once.

**What a recomputed part keeps.** The kernel's backward pass needs two
things that only its forward can make: the output and the log-sum-exp. The
forward rule names both :data:`RESIDUAL_NAME` (``jax.ad_checkpoint.
checkpoint_name``). Inside a ``jax.checkpoint`` whose policy keeps that
name (``save_only_these_names``: ``models/lfm2.py::_over_sequences``) the
backward pass reads the forward's output and log-sum-exp and the forward
kernel is not run again: two calls a part (forward, backward) where there
were three (forward, recomputation, backward) until PR 33. Under a policy
that does not know the name, or under none, nothing changes.

``engages`` is the ONE rule for who takes the kernel: a TPU, a sequence of
whole tiles, a pair of head sizes and a dtype the kernel takes. Callers
ask it and keep their plain spelling for everything else.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

# Tiles of the forward kernel and of the fused backward kernel alike (no
# pair of tile sets read better than one for both): queries, keys copied
# from HBM, keys multiplied at once.
BLOCK_Q = 1024
BLOCK_KV = 1024
BLOCK_KV_COMPUTE = 512
# A sequence is whole tiles of both kinds.
TILE = math.lcm(BLOCK_Q, BLOCK_KV)
# (queries and keys, values)
HEAD_DIMS = ((64, 64), (192, 128), (128, 128))
DTYPES = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
# What the forward rule calls its output and its log-sum-exp: a
# `jax.checkpoint` policy that saves this name spares the backward pass the
# forward kernel (the module's docstring).
RESIDUAL_NAME = "attention_residuals"


@dataclasses.dataclass(frozen=True)
class Causal:
    """A query reads the keys at or before its own position."""

    def allowed(self, q_ids, kv_ids):
        """Which pairs the mask allows, from positions that broadcast
        against each other (``numpy`` arrays where the tiles to visit are
        worked out, ``jax`` ones inside a tile and on the plain path)."""
        return q_ids >= kv_ids

    def keys_read(self, stop: int, total: int) -> int:
        """The queries before ``stop`` read no key at or after this."""
        return min(stop, total)


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """Block-diffusion training's mask over ``2 * seq_len`` positions, the
    noised copy of a sequence first and the clean copy behind it, in blocks
    of ``block`` tokens (``b(i) = i // block`` in either copy). Query ``a``
    reads key ``c`` iff both are noised and ``b(a) == b(c)``; or ``a`` is
    noised, ``c`` clean and ``b(c) < b(a)``; or both are clean and ``b(c)
    <= b(a)``. A clean query reads no noised key. Every query reads its own
    position, so no row is empty."""
    seq_len: int
    block: int

    def __post_init__(self):
        if self.seq_len <= 0 or self.block <= 0 or self.seq_len % self.block:
            raise ValueError(f"{self.seq_len} positions are not whole "
                             f"blocks of {self.block}")

    def _block_of(self, ids):
        """A position's block in its own copy. A subtraction and, where the
        block length is a power of two, a shift: the kernel evaluates this
        for every pair of a tile the mask cuts, on a vector unit that has
        no integer division."""
        clean = ids >= self.seq_len
        own = ids - clean.astype(ids.dtype) * self.seq_len
        shift = self.block.bit_length() - 1
        block = own >> shift if 1 << shift == self.block else own // self.block
        return clean, block

    def allowed(self, q_ids, kv_ids):
        (q_clean, q_block), (kv_clean, kv_block) = (self._block_of(q_ids),
                                                    self._block_of(kv_ids))
        same = q_block == kv_block
        # a clean key: of an earlier block, or of the query's own block if
        # the query is clean too; a noised key: of a noised query's block
        return ((kv_clean & ((kv_block < q_block) | (q_clean & same)))
                | (~q_clean & ~kv_clean & same))

    def keys_read(self, stop: int, total: int) -> int:
        return total


CAUSAL = Causal()


def _takes(seq_len: int, head_dim_qk: int, head_dim_v: int, dtype) -> bool:
    return (seq_len > 0 and seq_len % TILE == 0
            and (head_dim_qk, head_dim_v) in HEAD_DIMS
            and jnp.dtype(dtype) in DTYPES)


def engages(seq_len: int, head_dim_qk: int, head_dim_v: int, dtype,
            platform: str | None = None) -> bool:
    """Whether :func:`masked_gqa` is the path for such a sequence on
    ``platform`` (default: the process's backend; a compile for a described
    chip from a CPU process names it)."""
    platform = jax.default_backend() if platform is None else platform
    return platform == "tpu" and _takes(seq_len, head_dim_qk, head_dim_v,
                                        dtype)


def _computed_mask(splash, mask, seq_len: int):
    """``mask`` as the kernel takes one it evaluates itself: a mask object
    that answers for a slice of the square (the tiles to visit) and hands
    the kernel the function for the positions inside a tile."""
    if mask == CAUSAL:
        return splash.CausalMask((seq_len, seq_len))
    if isinstance(mask, BlockDiffusion) and 2 * mask.seq_len != seq_len:
        raise ValueError(f"{mask} is over {2 * mask.seq_len} positions, "
                         f"not {seq_len}")
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask)

    class Computed(splash_attention_mask._ComputableMask):
        def __init__(self):
            super().__init__((seq_len, seq_len), mask.allowed)

        def __eq__(self, other):
            return isinstance(other, Computed)     # one class a mask value

        def __hash__(self):
            return hash((Computed.__qualname__, mask, seq_len))

    return Computed()


@functools.lru_cache(maxsize=8)
def _kernel(seq_len: int, q_heads: int, interpret: bool, mask=CAUSAL):
    # here, so that asking `engages` costs no one Pallas's import (1 s)
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    blocks = splash.BlockSizes(
        block_q=BLOCK_Q, block_kv=BLOCK_KV,
        block_kv_compute=BLOCK_KV_COMPUTE, block_q_dkv=BLOCK_Q,
        block_kv_dkv=BLOCK_KV, block_kv_dkv_compute=BLOCK_KV_COMPUTE,
        use_fused_bwd_kernel=True)
    heads = splash.MultiHeadMask(
        [_computed_mask(splash, mask, seq_len)] * q_heads)
    # the mask's block tables are numpy's work, made into device constants
    # here and not inside whatever trace asked first
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(heads, block_sizes=blocks, head_shards=1,
                                      q_seq_shards=1, interpret=interpret,
                                      residual_checkpoint_name=RESIDUAL_NAME)


def masked_gqa(q: jax.Array, k: jax.Array, v: jax.Array, mask=CAUSAL, *,
               interpret: bool = False) -> jax.Array:
    """Attention under ``mask`` (:data:`CAUSAL` or a :class:`BlockDiffusion`)
    of ``q`` ``(n, T, Hq, D)`` over ``k`` ``(n, T, Hkv, D)`` and ``v`` ``(n,
    T, Hkv, Dv)``, query head ``h`` reading key/value head ``h // (Hq //
    Hkv)``: ``(n, T, Hq, Dv)`` in ``q``'s dtype. ``T`` is a multiple of
    :data:`TILE` and ``(D, Dv)`` one of :data:`HEAD_DIMS` (see
    :func:`engages`). No scale is applied: the caller's ``q`` carries it
    (the module's docstring). ``interpret`` runs the kernel in Pallas's
    interpreter, for tests without the chip."""
    n, t, hq, d = q.shape
    dv = v.shape[3]
    if (k.shape[:3] != v.shape[:3] or k.shape[:2] != (n, t)
            or k.shape[3] != d):
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape} are not "
                         "(n, T, Hq, D), (n, T, Hkv, D), (n, T, Hkv, Dv)")
    if hq % k.shape[2]:
        raise ValueError("query heads must divide over key/value heads")
    if not _takes(t, d, dv, q.dtype):
        raise ValueError(
            f"the kernel takes sequences of whole tiles of {TILE}, head "
            f"sizes (queries and keys, values) {HEAD_DIMS}, bfloat16 or "
            f"float32; got T={t}, D={d}, Dv={dv}, {q.dtype}")
    kernel = _kernel(t, hq, interpret, mask)
    heads_first = functools.partial(jnp.swapaxes, axis1=1, axis2=2)
    out = jax.vmap(kernel)(heads_first(q), heads_first(k), heads_first(v))
    return heads_first(out)


def causal_gqa(q: jax.Array, k: jax.Array, v: jax.Array, *,
               interpret: bool = False) -> jax.Array:
    """:func:`masked_gqa` under the causal mask."""
    return masked_gqa(q, k, v, CAUSAL, interpret=interpret)
