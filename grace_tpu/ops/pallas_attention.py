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
the diagonal are skipped); :class:`SlidingWindow` ``(window)``, a query
reads the ``window`` keys that end at its own position (the tiles above the
diagonal and those wholly before the band are skipped: at 16,384 positions
in tiles of 1,024 a window of 4,096 leaves 70 of 256 tiles, where the causal
mask leaves 136; ``models/smallthinker.py``'s windowed layers); and
:class:`BlockDiffusion` ``(seq_len, block)``, block-diffusion training's
mask over a doubled sequence, the noised copy's ``seq_len`` positions first
and the clean copy's behind them (``models/sdar.py``): block-diagonal among
the noised positions, strictly block-causal from a noised query to the clean
keys, block-causal among the clean positions, and nothing from a clean query
to a noised key.

**What the kernel sees under a block-diffusion mask** (PR 42) is not the
``2L x 2L`` square but a rectangle: all ``2L`` queries over the ``L`` clean
keys and values alone. Of those every query reads a prefix (a noised query
the clean blocks before its own, a clean one those up to its block's end:
``BlockDiffusion.clean_keys_read``), so the mask inside a tile is one
comparison a pair, as the causal one is: the kernel is handed, as each
query's position, how many clean keys it reads (the mask object's
``q_sequence``, which also decides the tiles to visit). The installed
kernel evaluates a computed mask in every tile it visits, not only in those
the mask cuts, so what a pair's mask costs is paid everywhere. Of the 8 x 4
tiles of 1,024 a doubled sequence of 8,192 has, it visits 20 (10 and 10;
the square had 4 more on the noised diagonal, each 99.6 % masked). What is
left of the mask, a noised query's own block (``block`` noised keys), is
scored beside the fused kernel by two small kernels of this module
(``block_diffusion_own_block_fwd`` / ``_bwd``: not the fused kernel's
names, by which the benchmark finds the fused kernel's calls): a tile of
:data:`OWN_ROWS` noised positions against the noised keys of the same
positions, the query heads of one key/value head a step, scores in float32
in VMEM, masked to the blocks; nothing of size ``T x T`` exists, and
nothing of the own block reaches HBM but the result. (Written first as
XLA's: five batched products of ``block x block`` a block and head come out
as dilated convolutions over operands padded to whole tiles, a 2 MB tensor
of scores on 33 MB, with float32 copies of 67 MB a sequence and layer
between them; XLA's own count for the part rose from 10.3 to 13.3 GB a
sequence and layer, more than the tiles saved.) The forward one merges the
two softmaxes by log-sum-exp in float32 (``lse = logaddexp(lse_kernel,
lse_own)``, ``out = exp(lse_kernel - lse) * out_kernel + exp(lse_own - lse) *
out_own``), casts once and writes over the fused kernel's output in place.
A noised query of block 0 reads no clean key: the fused kernel's row is
empty, its log-sum-exp the mask's value, and the merge gives the own
block's answer exactly. **Backward**, as ring attention does a chunk: the
fused backward kernel, given the clean keys with the *merged* output and
the *joint* log-sum-exp, makes the joint softmax's probabilities again tile
by tile and returns ``dk``, ``dv`` of the clean keys and their part of
``dq``; the own blocks' backward kernel adds its part of ``dq`` in place
and gives the noised keys' ``dk``, ``dv``, out of the same two and ``di =
sum(out * do)``. No gradient flows through a log-sum-exp. This is a
``jax.custom_vjp`` of this module's around the fused kernel's forward and
backward entry points (``splash_attention_kernel._splash_attention_forward``
with ``save_residuals=True`` and ``_splash_attention_bwd``, private names of
the pinned JAX 0.9.0, as the mask's base class ``_ComputableMask`` is): one
``splash_mha_fwd_residuals`` and one ``splash_mha_dkv_no_residuals`` a
layer, the names the causal path's calls carry. The causal path is JAX's
own ``custom_vjp`` untouched; the two paths are chosen by the mask's type.

The kernel is JAX's ``splash_attention`` (``jax.experimental.pallas.ops.
tpu``), wrapped: its multi-head form with fewer key/value heads than query
heads, a head size for queries and keys and one for values, a mask it
evaluates from positions inside the tile (no mask tensor), the fused
backward kernel (``dk``, ``dv`` and ``dq`` from one recomputation of a
tile). Scores, running maximum, running sum and output accumulator are
float32; the forward multiplies float32 probabilities into the values, the
backward casts the probabilities and the score gradients to the
gradient's dtype for its products.

**Layout.** Every kernel here reads and writes heads first, and so does
:func:`masked_gqa`: ``q`` ``(n, Hq, T, D)``, ``k`` ``(n, Hkv, T, D)``, ``v``
``(n, Hkv, T, Dv)`` -> ``(n, Hq, T, Dv)``. The wrapper moves no axis (until
PR 45 it took ``(n, T, H, D)`` and swapped three operands in and the result
out, each a copy of a tiled array in HBM, forward, recomputed and backward).
The callers' projections write head-major themselves (``einsum("ntd,dhk->
nhtk")`` over the weight viewed ``(d, H, D)``) and their output projection
reads the result where the kernel left it (``einsum("nhtk,hkd->ntd")``), so
what stands between a product and the kernel is norms and rotations alone.

Four pairs of head sizes are taken (:data:`HEAD_DIMS`, queries and keys |
values): ``(64, 64)``, grouped-query attention as ``models/lfm2.py`` has
it, ``(192, 128)``, latent attention as ``models/deepseek_v3.py`` has it
(a 128-wide part without positions beside a 64-wide rotary part),
``(128, 128)``, grouped-query attention as ``models/sdar.py`` and
``models/smallthinker.py`` have it, and ``(256, 256)``, as
``models/qwen3_next.py``'s full layers have it (two tiles of 128 lanes a
head; the same tiles of positions). The
tile sizes were chosen by chip runs on a TPU v5e at ``(1, 4096, 32 | 8,
64)`` bfloat16, where JAX's other kernel, ``flash_attention``, read 1.6
times this one's time (PERF.md §6, PR 31), and read again at ``(1, 4096,
32, 192 | 128)`` (PERF.md §6, PR 32). Mosaic takes the 192 lanes as they
are (a tile and a half of 128): no zero padding to 256.

**The scale.** The kernel has none, and neither has this wrapper: what it
is given as ``q`` is what it multiplies into the keys, so ``softmax(q k^T +
mask) v`` is what comes back. Each caller scales ``q`` where that rounds
nothing its plain spelling does not round: ``models/lfm2.py`` multiplies
``q`` by ``1 / sqrt(64)`` (and, for ``models/qwen3_next.py``'s heads, ``1
/ sqrt(256)``) in ``q``'s dtype (a power of two: exact) and, at
a head size whose root is none (128), folds the scale into the float32
weight of the queries' norm (``models/sdar.py``) or, where the heads have
no norm (``models/smallthinker.py``), into the query projection's weights
in float32 as it casts them; ``models/deepseek_v3.py`` folds ``1 /
sqrt(192)`` into the query projection's weights the same way, so ``q`` is
rounded once.

**What a recomputed part keeps.** The kernel's backward pass needs two
things that only its forward can make: the output and the log-sum-exp. The
forward rule names both :data:`RESIDUAL_NAME` (``jax.ad_checkpoint.
checkpoint_name``; under a block-diffusion mask the two named are the
merged output and the joint log-sum-exp, all that path's backward reads of
its forward). Inside a ``jax.checkpoint`` whose policy keeps that
name (``save_only_these_names``: ``models/lfm2.py::_over_sequences``) the
backward pass reads the forward's output and log-sum-exp and the forward
kernel is not run again: two calls a part (forward, backward) where there
were three (forward, recomputation, backward) until PR 33. Under a policy
that does not know the name, or under none, nothing changes.

``engages`` is the ONE rule for who takes the kernel: a TPU, a sequence of
whole tiles (under a block-diffusion mask each copy is, since the kernel's
keys are one copy, and a block lies within a tile of the own blocks'
kernels), a pair of head sizes and a dtype the kernel takes.
Callers ask it and keep their plain spelling for everything else.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tiles of the forward kernel and of the fused backward kernel alike (no
# pair of tile sets read better than one for both): queries, keys copied
# from HBM, keys multiplied at once.
BLOCK_Q = 1024
BLOCK_KV = 1024
BLOCK_KV_COMPUTE = 512
# A sequence is whole tiles of both kinds.
TILE = math.lcm(BLOCK_Q, BLOCK_KV)
# (queries and keys, values)
HEAD_DIMS = ((64, 64), (192, 128), (128, 128), (256, 256))
DTYPES = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
# What the forward rule calls its output and its log-sum-exp: a
# `jax.checkpoint` policy that saves this name spares the backward pass the
# forward kernel (the module's docstring).
RESIDUAL_NAME = "attention_residuals"
# Noised positions a tile of the own-block kernels (a block lies within one),
# and those kernels' name: not the fused kernel's, by which the benchmark
# finds the fused kernel's calls.
OWN_ROWS = 128
OWN_BLOCK_NAME = "block_diffusion_own_block"


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

    def first_key(self, start: int) -> int:
        """The queries from ``start`` on read no key before this."""
        return 0

    def whole_tiles(self, seq_len: int) -> bool:
        """Whether what the kernel is given of ``seq_len`` positions under
        this mask is whole tiles: here, the positions themselves."""
        return seq_len % TILE == 0


@dataclasses.dataclass(frozen=True)
class SlidingWindow(Causal):
    """A query reads the ``window`` keys that end at its own position, itself
    included: query ``i`` reads key ``j`` iff ``0 <= i - j < window``. A
    window as long as the sequence is the causal mask."""
    window: int

    def __post_init__(self):
        if self.window <= 0:
            raise ValueError(f"a window of {self.window} keys reads nothing")

    def allowed(self, q_ids, kv_ids):
        return (q_ids >= kv_ids) & (q_ids - kv_ids < self.window)

    def first_key(self, start: int) -> int:
        return max(0, start - self.window + 1)


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """Block-diffusion training's mask over ``2 * seq_len`` positions, the
    noised copy of a sequence first and the clean copy behind it, in blocks
    of ``block`` tokens (``b(i) = i // block`` in either copy). Query ``a``
    reads key ``c`` iff both are noised and ``b(a) == b(c)``; or ``a`` is
    noised, ``c`` clean and ``b(c) < b(a)``; or both are clean and ``b(c)
    <= b(a)``. A clean query reads no noised key. Every query reads its own
    position, so no row is empty.

    :meth:`allowed` is the whole mask, pair by pair: the plain path's, and
    what the kernel path is tested against. The kernel never evaluates it.
    It is given the clean keys alone, of which every query reads a prefix
    (:meth:`clean_keys_read`: one comparison a pair), and a noised query's
    own block is scored beside it (the module's docstring), for which
    ``block`` is a power of two up to :data:`OWN_ROWS`."""
    seq_len: int
    block: int

    def __post_init__(self):
        if self.seq_len <= 0 or self.block <= 0 or self.seq_len % self.block:
            raise ValueError(f"{self.seq_len} positions are not whole "
                             f"blocks of {self.block}")

    def _block_of(self, ids):
        """A position's block in its own copy. A subtraction and, where the
        block length is a power of two, a shift."""
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

    def first_key(self, start: int) -> int:
        return 0

    def clean_keys_read(self, q_ids):
        """How many clean keys, from the clean copy's first on, the queries
        at ``q_ids`` read: a noised query those of the blocks before its
        own (none in block 0), a clean one those up to its block's end.
        Clean key ``c`` (counted within its copy) is allowed iff ``c`` is
        below this."""
        clean, block = self._block_of(q_ids)
        return (block + clean.astype(block.dtype)) * self.block

    def whole_tiles(self, seq_len: int) -> bool:
        """The kernel's queries are all the positions, its keys the clean
        copy's ``self.seq_len``; the own blocks' kernels take tiles of
        :data:`OWN_ROWS` noised positions, whole blocks each."""
        if seq_len != 2 * self.seq_len:
            raise ValueError(f"{self} is over {2 * self.seq_len} positions, "
                             f"not {seq_len}")
        return self.seq_len % TILE == 0 and OWN_ROWS % self.block == 0


CAUSAL = Causal()


def _takes(seq_len: int, head_dim_qk: int, head_dim_v: int, dtype,
           mask=CAUSAL) -> bool:
    return (seq_len > 0 and mask.whole_tiles(seq_len)
            and (head_dim_qk, head_dim_v) in HEAD_DIMS
            and jnp.dtype(dtype) in DTYPES)


def engages(seq_len: int, head_dim_qk: int, head_dim_v: int, dtype,
            platform: str | None = None, mask=CAUSAL) -> bool:
    """Whether :func:`masked_gqa` is the path for such a sequence under
    ``mask`` on ``platform`` (default: the process's backend; a compile for
    a described chip from a CPU process names it)."""
    platform = jax.default_backend() if platform is None else platform
    return platform == "tpu" and _takes(seq_len, head_dim_qk, head_dim_v,
                                        dtype, mask)


def _below(read, kv_ids):
    """The rectangle's mask inside a tile: the kernel hands it, as the
    queries' positions, how many clean keys each query reads."""
    return kv_ids < read


def _computed_mask(splash, mask, seq_len: int):
    """``mask`` as the kernel takes one it evaluates itself: a mask object
    that answers for a slice of it (the tiles to visit) and hands the kernel
    the function for the positions inside a tile. The causal mask and a
    sliding window are squares over the sequence; a block-diffusion mask the
    rectangle of all positions over the clean copy's keys."""
    if mask == CAUSAL:
        return splash.CausalMask((seq_len, seq_len))
    if isinstance(mask, SlidingWindow):
        # (keys before the query's own, keys after it)
        return splash.LocalMask((seq_len, seq_len), (mask.window - 1, 0), 0)
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask)

    class CleanPrefix(splash_attention_mask._ComputableMask):
        def __init__(self):
            super().__init__((seq_len, mask.seq_len), _below)
            # what the kernel is handed a query for its position, and what
            # a slice of the mask is worked out from
            self.q_sequence = mask.clean_keys_read(
                np.arange(seq_len, dtype=np.int32))

        def __eq__(self, other):
            return isinstance(other, CleanPrefix)   # one class a mask value

        def __hash__(self):
            return hash((CleanPrefix.__qualname__, mask))

    return CleanPrefix()


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


# ---------------------------------------------------------------------------
# block diffusion: the kernel over the clean keys, a query's own block beside
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _own_pairs(rows: int, block: int):
    """Which (key, query) pairs of a tile of ``rows`` noised positions lie
    in one block (``block`` a power of two that divides ``rows``), and the
    tile's diagonal."""
    shift = block.bit_length() - 1
    r = lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    c = lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    return (r >> shift) == (c >> shift), r == c


def _own_fwd_kernel(q_ref, k_ref, v_ref, out_clean_ref, lse_clean_ref,
                    out_ref, lse_ref, *, block: int, mask_value: float):
    """A tile of noised positions, the query heads of one key/value head:
    the own blocks' softmax merged with the fused kernel's over the clean
    keys (its output and log-sum-exp, read here) into the joint one, written
    over the fused kernel's output. Keys on sublanes, queries on lanes, as
    the fused backward has them: what there is one of a query (maxima, sums,
    the clean log-sum-exp) is a row, and no sum runs along lanes. The clean
    part is scaled a query by a product with a diagonal matrix, at float32's
    precision."""
    f32 = jnp.float32
    own, diagonal = _own_pairs(k_ref.shape[0], block)
    k, v = k_ref[...], v_ref[...].astype(f32)
    for head in range(q_ref.shape[0]):
        s = jnp.where(own, lax.dot_general(k, q_ref[head], _NT,
                                           preferred_element_type=f32),
                      mask_value)
        # A query of block 0 reads no clean key: the fused kernel masked
        # its row whole and wrote the mask's value as its log-sum-exp (and a
        # mean of values as its output), which weighs exactly nothing here.
        lse_clean = lse_clean_ref[head, :1, :]
        top = jnp.maximum(s.max(axis=0, keepdims=True), lse_clean)
        e = jnp.exp(s - top)
        clean = jnp.exp(lse_clean - top)
        total = e.sum(axis=0, keepdims=True) + clean
        out = lax.dot_general(e / total, v, _TN, preferred_element_type=f32)
        out += jnp.dot(jnp.where(diagonal, clean / total, 0.0),
                       out_clean_ref[head].astype(f32),
                       preferred_element_type=f32,
                       precision=lax.Precision.HIGHEST)
        out_ref[head] = out.astype(out_ref.dtype)
        lse_ref[head] = jnp.broadcast_to(top + jnp.log(total),
                                         lse_ref.shape[1:])


def _own_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_clean_ref,
                    dq_ref, dk_ref, dv_ref, *, block: int, mask_value: float):
    """The own blocks' part of the joint softmax's gradients for a tile of
    noised positions, the query heads of one key/value head: keys on
    sublanes, queries on lanes, probabilities and score gradients cast to
    the gradient's dtype for their products, as the fused backward has
    them. ``dq`` is added to the clean keys' part in place; ``dk``, ``dv``
    are summed over the heads."""
    f32 = jnp.float32
    own, _ = _own_pairs(k_ref.shape[0], block)
    k, v = k_ref[...], v_ref[...]
    dk, dv = jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32)
    for head in range(q_ref.shape[0]):
        q, do = q_ref[head], do_ref[head]
        s = lax.dot_general(k, q, _NT, preferred_element_type=f32)
        p = jnp.exp(jnp.where(own, s, mask_value) - lse_ref[head, :1, :])
        dv += jnp.dot(p.astype(do.dtype), do, preferred_element_type=f32)
        dp = lax.dot_general(v, do, _NT, preferred_element_type=f32)
        ds = ((dp - di_ref[head, :1, :]) * p).astype(q.dtype)
        dk += jnp.dot(ds, q, preferred_element_type=f32)
        dq = lax.dot_general(ds, k, _TN, preferred_element_type=f32)
        dq_ref[head] = (dq_clean_ref[head].astype(f32) + dq).astype(
            dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _own_call(kernel, name, q, k, length, interpret, **kw):
    """``pallas_call`` of an own-block kernel over ``(sequence, key/value
    head, tile of noised positions)`` and the three kinds of block it reads
    and writes: the group's query heads' rows, the key/value head's rows,
    the group's per-query numbers (a row each, on eight sublanes)."""
    n, hq = q.shape[:2]
    hkv, rows = k.shape[1], OWN_ROWS
    group = hq // hkv
    tile = lambda b, h, r: (b, h, r, 0)                     # noqa: E731
    q_rows = lambda d: pl.BlockSpec((None, group, rows, d), tile)  # noqa: E731
    kv_rows = lambda d: pl.BlockSpec((None, None, rows, d), tile)  # noqa: E731
    numbers = pl.BlockSpec((None, group, 8, rows),
                           lambda b, h, r: (b, h, 0, r))
    call = functools.partial(
        pl.pallas_call, kernel, grid=(n, hkv, length // rows), name=name,
        interpret=interpret, compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")), **kw)
    return call, q_rows, kv_rows, numbers


def _as_rows(x):
    """Per-query numbers ``(n, H, T)`` as the kernels read a row of them:
    on eight sublanes (Mosaic's tile of float32), as the fused backward
    takes its own."""
    n, h, t = x.shape
    return jnp.broadcast_to(x[:, :, None, :], (n, h, 8, t))


def _own_forward(q, k, v, out, lse, *, length, block, mask_value, interpret):
    """``(out, lse)`` of the fused kernel over the clean keys, ``(n, Hq, 2L,
    Dv)`` and ``(n, Hq, 2L)``, with the noised half merged with the own
    blocks' softmax: ``out`` in place, the noised half of ``lse`` anew."""
    d, dv = q.shape[3], v.shape[3]
    call, q_rows, kv_rows, numbers = _own_call(
        functools.partial(_own_fwd_kernel, block=block, mask_value=mask_value),
        OWN_BLOCK_NAME + "_fwd", q, k, length, interpret,
        input_output_aliases={3: 0})
    out, lse_noised = call(
        in_specs=[q_rows(d), kv_rows(d), kv_rows(dv), q_rows(dv), numbers],
        out_specs=[q_rows(dv), numbers],
        out_shape=[jax.ShapeDtypeStruct(out.shape, out.dtype),
                   jax.ShapeDtypeStruct(lse.shape[:2] + (8, length),
                                        jnp.float32)],
    )(q, k, v, out, _as_rows(lse))
    return out, lax.dynamic_update_slice_in_dim(lse, lse_noised[:, :, 0], 0,
                                                axis=2)


def _own_backward(q, k, v, out, lse, do, dq, *, length, block, mask_value,
                  interpret):
    """The own blocks' part of the gradients: ``dq`` (the clean keys' part,
    ``(n, Hq, 2L, D)``) with it added in place, and the noised keys' ``dk``,
    ``dv`` ``(n, Hkv, L, D | Dv)``."""
    d, dv = q.shape[3], v.shape[3]
    di = jnp.sum(out[:, :, :length].astype(jnp.float32)
                 * do[:, :, :length].astype(jnp.float32), axis=-1)
    call, q_rows, kv_rows, numbers = _own_call(
        functools.partial(_own_bwd_kernel, block=block, mask_value=mask_value),
        OWN_BLOCK_NAME + "_bwd", q, k, length, interpret,
        input_output_aliases={6: 0})
    return call(
        in_specs=[q_rows(d), kv_rows(d), kv_rows(dv), q_rows(dv), numbers,
                  numbers, q_rows(d)],
        out_specs=[q_rows(d), kv_rows(d), kv_rows(dv)],
        out_shape=[jax.ShapeDtypeStruct(dq.shape, dq.dtype),
                   jax.ShapeDtypeStruct(k.shape[:2] + (length, d), k.dtype),
                   jax.ShapeDtypeStruct(v.shape[:2] + (length, dv), v.dtype)],
    )(q, k, v, do, _as_rows(lse), _as_rows(di), dq)


@functools.lru_cache(maxsize=8)
def _block_diffusion_kernel(seq_len: int, q_heads: int, interpret: bool,
                            mask: BlockDiffusion):
    """Attention under ``mask``, heads first: ``q`` ``(n, Hq, 2L, D)``,
    ``k``, ``v`` ``(n, Hkv, 2L, D | Dv)`` -> ``(n, Hq, 2L, Dv)``. The fused
    kernel over the clean keys, a noised query's own block beside it, the
    two merged by log-sum-exp; backward, the fused kernel's own over the
    clean keys from the merged output and the joint log-sum-exp, and the own
    block's part from the same two (the module's docstring)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)

    # the rectangle's tables and settings; never called: it would give the
    # clean keys' part alone
    clean = _kernel(seq_len, q_heads, interpret, mask)
    settings = {name: clean.kwargs[name] for name in (
        "mask_value", "is_mqa", "block_sizes", "mask_function", "interpret")}
    length = mask.seq_len
    own = dict(length=length, block=mask.block, interpret=interpret,
               mask_value=settings["mask_value"])

    def forward(q, k, v):
        # The fused kernel is handed a sequence (``vmap``), the own blocks'
        # kernel the batch: two shapes of one ``q``. Without the barrier XLA
        # carries the reshape up through the rotation and rotates ``q`` twice,
        # once a shape (0.3 ms a sequence and layer at 8,192 positions and 32
        # heads on a TPU v5e: PERF.md section 6, PR 45).
        q, k, v = lax.optimization_barrier((q, k, v))
        out, (lse,) = jax.vmap(lambda q, k, v: sk._splash_attention_forward(
            clean.fwd_mask_info, q, k, v, segment_ids=None, sinks=None,
            save_residuals=True, residual_checkpoint_name=None, **settings))(
                q, k[:, :, length:], v[:, :, length:])
        out, lse = _own_forward(q, k, v, out, lse, **own)
        return (checkpoint_name(out, RESIDUAL_NAME),
                checkpoint_name(lse, RESIDUAL_NAME))

    @jax.custom_vjp
    def attend(q, k, v):
        return forward(q, k, v)[0]

    def attend_fwd(q, k, v):
        out, lse = forward(q, k, v)
        return out, (q, k, v, out, lse)

    def attend_bwd(res, do):
        # as in `forward`: the recomputed `q` goes to two kernels
        q, k, v, out, lse = lax.optimization_barrier(res)

        def clean_keys(q, k, v, out, lse, do):
            # The joint softmax's gradients over the clean keys: the fused
            # kernel makes each tile's probabilities again from the joint
            # log-sum-exp, and `di = sum(out * do)` from the merged output.
            return sk._splash_attention_bwd(
                save_residuals=False, residual_checkpoint_name=None,
                attn_logits_soft_cap=None, **settings,
                res=(q, k, v, None, None, out, lse, None,
                     clean.dkv_mask_info), do=do)[3:6]

        dq, dk, dv = jax.vmap(clean_keys)(q, k[:, :, length:],
                                          v[:, :, length:], out, lse, do)
        dq, dk_own, dv_own = _own_backward(q, k, v, out, lse, do, dq, **own)
        return (dq, jnp.concatenate([dk_own, dk], axis=2),
                jnp.concatenate([dv_own, dv], axis=2))

    attend.defvjp(attend_fwd, attend_bwd)
    return attend


def masked_gqa(q: jax.Array, k: jax.Array, v: jax.Array, mask=CAUSAL, *,
               interpret: bool = False) -> jax.Array:
    """Attention under ``mask`` (:data:`CAUSAL`, a :class:`SlidingWindow` or a
    :class:`BlockDiffusion`), head-major as the kernel reads and writes: ``q``
    ``(n, Hq, T, D)`` over ``k`` ``(n, Hkv, T, D)`` and ``v`` ``(n, Hkv, T,
    Dv)``, query head ``h`` reading key/value head ``h // (Hq // Hkv)``: ``(n,
    Hq, T, Dv)`` in ``q``'s dtype. No axis is moved on the way in or out: a
    caller makes its projections head-major (``einsum("ntd,dhk->nhtk")``)
    and reads the result where it lies. ``T`` is a
    multiple of :data:`TILE` (under a :class:`BlockDiffusion`, each copy is)
    and ``(D, Dv)`` one of :data:`HEAD_DIMS` (see :func:`engages`). No scale is
    applied: the caller's ``q`` carries it (the module's docstring).
    ``interpret`` runs the kernel in Pallas's interpreter, for tests without
    the chip."""
    n, hq, t, d = q.shape
    dv = v.shape[3]
    if (k.shape[:3] != v.shape[:3] or (k.shape[0], k.shape[2]) != (n, t)
            or k.shape[3] != d):
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape} are not "
                         "(n, Hq, T, D), (n, Hkv, T, D), (n, Hkv, T, Dv)")
    if hq % k.shape[1]:
        raise ValueError("query heads must divide over key/value heads")
    if not _takes(t, d, dv, q.dtype, mask):
        raise ValueError(
            f"the kernel takes sequences of whole tiles of {TILE}, head "
            f"sizes (queries and keys, values) {HEAD_DIMS}, bfloat16 or "
            f"float32; got T={t}, D={d}, Dv={dv}, {q.dtype}, {mask}")
    # one key population or two: separate paths, chosen by the mask's type
    if isinstance(mask, BlockDiffusion):
        return _block_diffusion_kernel(t, hq, interpret, mask)(q, k, v)
    return jax.vmap(_kernel(t, hq, interpret, mask))(q, k, v)


def causal_gqa(q: jax.Array, k: jax.Array, v: jax.Array, *,
               interpret: bool = False) -> jax.Array:
    """:func:`masked_gqa` under the causal mask."""
    return masked_gqa(q, k, v, CAUSAL, interpret=interpret)
