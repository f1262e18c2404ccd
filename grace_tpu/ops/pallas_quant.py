"""Pallas TPU kernels: fused stochastic quantization and compress-and-pack.

The QSGD family (reference grace_dl/dist/compressor/qsgd.py:19-23) needs a
uniform random draw per element for stochastic rounding. Expressed in plain
jnp, XLA materializes the threefry random tensor and streams it through HBM
alongside the gradient; this kernel keeps the whole quantize step — scale,
floor, random draw, round, sign fold — in VMEM with the TPU's in-core PRNG
(`pltpu.prng_random_bits`), one HBM read + one (8× smaller) HBM write.

Layout: the flat tensor is processed as (rows, 256) f32 blocks (sublane
multiple of 8, lane 128×2), grid over row-tiles. Padding lanes quantize
garbage that callers slice off.

Used by ``QSGDCompressor(use_pallas=True)``; runs in interpreter mode on
CPU so the test suite exercises the same code path everywhere.

**Fused compress-and-pack** (the EQuARX regime — quantize/pack fused into
the kernel that produces the wire payload, arXiv:2506.17615):
:func:`quantize_pack_stochastic` and :func:`sign_pack` emit the packed
sub-byte wire words *directly* — the payload leaves VMEM wire-ready
(ceil(n·bits/8) uint8 bytes) instead of staging full-width codes through
HBM for a separate jnp packing pass. The byte layout is pinned to the
reference packers' :func:`grace_tpu.ops.packing.pack_widths` contracts
(LSB-first within a byte, low nibble first), verified bit-exactly by
tests/test_pallas_quant.py, and re-audited by the static analyzer's
numeric-safety pass whenever a codec ships a packed payload. Packing is
expressed as a small matmul against a constant 0/1·2^k matrix — groups of
``8/bits`` consecutive lanes reduce onto one output byte lane on the MXU
(all values ≤ 255, exact in f32 accumulation), which keeps the lane-
dimension reduction a single dot instead of a Mosaic-hostile strided
gather.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret_mode(interpret: bool):
    """The ``interpret=`` argument of ``pallas_call``: the TPU interpreter's
    parameter object, or False for a Mosaic compile."""
    return pltpu.InterpretParams() if interpret else False


LANES = 256          # last-dim tile (2 × 128 lanes)
ROWS_PER_BLOCK = 64  # sublane tile multiple


def _hash_bits(seed, shape):
    """Counter-based uint32 hash (xorshift-multiply) over element indices.

    Used when the hardware PRNG is unavailable (CPU interpreter mode, where
    `pltpu.prng_random_bits` silently returns zeros) — same numerics as the
    TPU path, just a different bit source, so the full quantization logic is
    testable off-TPU.
    """
    rows = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    h = (rows * jnp.uint32(shape[1]) + cols) * jnp.uint32(2654435761)
    h = h + seed.astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x45D9F3B)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x45D9F3B)
    return h ^ (h >> 16)


def _signed_levels(x, scale, block_seed, hw_prng: bool):
    """The QSGD stochastic-rounding core, shared VERBATIM by the plain
    quantize kernel and the fused quantize-and-pack kernel — bit-identity
    between 'quantize then pack' and 'fused compress-and-pack' holds
    because both run literally this expression over the same block/seed
    layout."""
    level_float = jnp.abs(x) * scale
    previous = jnp.floor(level_float)
    if hw_prng:
        pltpu.prng_seed(block_seed)
        bits = pltpu.prng_random_bits(x.shape).astype(jnp.uint32)
    else:
        bits = _hash_bits(block_seed, x.shape)
    # Top 24 bits -> uniform [0, 1) with full f32 mantissa coverage.
    # Mosaic has no uint32->f32 cast (observed on-chip: NotImplementedError
    # "Unsupported cast: uint32 -> float32"); bits>>8 < 2^24 fits int32
    # exactly, so the int32 hop is lossless.
    u = ((bits >> 8).astype(jnp.int32).astype(jnp.float32)
         * (1.0 / (1 << 24)))
    level = previous + (u < level_float - previous).astype(jnp.float32)
    return level * jnp.sign(x)


def _make_quantize_kernel(hw_prng: bool):
    def kernel(seed_ref, scale_ref, x_ref, out_ref):
        block_seed = seed_ref[0, 0] + pl.program_id(0)
        signed = _signed_levels(x_ref[:], scale_ref[0, 0], block_seed, hw_prng)
        out_ref[:] = signed.astype(out_ref.dtype)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("quantum_num", "out_dtype", "interpret"))
def quantize_stochastic(flat: jax.Array, norm: jax.Array, seed: jax.Array,
                        quantum_num: int, out_dtype=jnp.int8,
                        interpret: bool = False) -> jax.Array:
    """Stochastically quantize ``flat`` (1-D f32) to signed integer levels.

    level ~ floor(q/||x|| * |x|) + Bernoulli(frac), sign folded in — the
    QSGD encoding. ``norm`` is the (precomputed) L2 norm; ``seed`` an int32
    scalar. Returns int levels, same length as ``flat``.
    """
    n = flat.size
    block = ROWS_PER_BLOCK * LANES
    n_pad = -n % block
    padded = jnp.pad(flat.astype(jnp.float32), (0, n_pad))
    rows = padded.size // LANES
    x2d = padded.reshape(rows, LANES)
    scale = jnp.where(norm > 0, quantum_num / norm, 0.0).astype(jnp.float32)

    out = pl.pallas_call(
        _make_quantize_kernel(hw_prng=not interpret),
        grid=(rows // ROWS_PER_BLOCK,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((ROWS_PER_BLOCK, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((ROWS_PER_BLOCK, LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), out_dtype),
        interpret=_interpret_mode(interpret),
    )(seed.reshape(1, 1).astype(jnp.int32), scale.reshape(1, 1), x2d)
    return out.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# fused compress-and-pack
# ---------------------------------------------------------------------------

# Sign-pack block: 1024 input lanes reduce 8:1 onto 128 output byte lanes
# (a full lane tile for the uint8 output); 32 sublanes hit the uint8
# (32, 128) minimum output tile exactly.
SIGN_ROWS = 32
SIGN_LANES = 1024


@functools.lru_cache(maxsize=8)
def _pack_matrix_np(width: int, in_lanes: int):
    import numpy as np

    per_byte = 8 // width
    w = np.zeros((in_lanes, in_lanes // per_byte), np.float32)
    for lane in range(in_lanes):
        w[lane, lane // per_byte] = float(1 << (width * (lane % per_byte)))
    return w


@functools.lru_cache(maxsize=4)
def _pack_matrix3_np(in_lanes: int):
    """3-bit bit-plane pack matrix: row ``b·L + l`` (bit ``b`` of code
    ``l``) routes to output byte ``(3l+b)//8`` with weight ``2^((3l+b)%8)``
    — :func:`grace_tpu.ops.packing.pack_3bit`'s LSB-first bitstream. 3
    does not divide 8, so codes straddle byte boundaries and the per-code
    shift trick of :func:`_pack_matrix_np` cannot apply; decomposing each
    code into its three bit planes first makes the pack three dots (one
    per plane) against row-slices of this one constant — every output
    byte still sums 8 disjoint weighted bits, ≤ 255, exact in f32."""
    import numpy as np

    w = np.zeros((3 * in_lanes, 3 * in_lanes // 8), np.float32)
    for b in range(3):
        for lane in range(in_lanes):
            gb = 3 * lane + b
            w[b * in_lanes + lane, gb // 8] = float(1 << (gb % 8))
    return w


def _pack_matrix(width: int, in_lanes: int) -> jax.Array:
    """The constant pack matrix: ``W[l, l // (8//width)] = 2^(width·(l mod
    8//width))``, zero elsewhere. ``codes @ W`` sums each group of
    ``8/width`` consecutive lanes' codes shifted into their byte position —
    exactly :mod:`grace_tpu.ops.packing`'s LSB-first layout, as one MXU dot
    (every product ≤ 240 and every byte sum ≤ 255: exact in f32). The
    numpy constant is cached; the device constant is minted per trace (a
    cached jnp array would leak a tracer across jits)."""
    return jnp.asarray(_pack_matrix_np(width, in_lanes))


def _pack_lanes(codes, packw_ref):
    """Pack f32 integer codes (rows, L) -> (rows, L·width/8) uint8 via the
    pack-matrix dot. int32 hop on the way out: Mosaic's f32->uint8 path is
    the same cast class the PRNG bits needed in reverse."""
    packed = jax.lax.dot_general(codes, packw_ref[:],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    return packed.astype(jnp.int32).astype(jnp.uint8)


def _pack_lanes3(codes, packw_ref):
    """Pack f32 integer codes (rows, L) -> (rows, 3L/8) uint8 in
    :func:`grace_tpu.ops.packing.pack_3bit`'s bitstream layout: three
    bit-plane dots against row-slices of the :func:`_pack_matrix3_np`
    constant, summed (disjoint output bits, so the sum is the OR)."""
    lanes = codes.shape[-1]
    w = packw_ref[:]
    acc = None
    for b in range(3):
        plane = jnp.mod(jnp.floor(codes * (1.0 / (1 << b))), 2.0)
        part = jax.lax.dot_general(plane, w[b * lanes:(b + 1) * lanes],
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
    return acc.astype(jnp.int32).astype(jnp.uint8)


def _make_quantize_pack_kernel(hw_prng: bool, width: int):
    def kernel(seed_ref, scale_ref, q_ref, packw_ref, x_ref, out_ref):
        block_seed = seed_ref[0, 0] + pl.program_id(0)
        signed = _signed_levels(x_ref[:], scale_ref[0, 0], block_seed, hw_prng)
        # Two's-complement field: clamp to ±quantum_num (stochastic
        # overshoot past +q would not fit the field's 2^(width-1)-1
        # ceiling), then fold negatives into the upper half of the code
        # range. First element lands in the lowest bits — the
        # packing.pack_{2,3,4}bit layouts.
        q = q_ref[0, 0].astype(jnp.float32)
        signed = jnp.clip(signed, -q, q)
        codes = signed + float(1 << width) * (signed < 0).astype(jnp.float32)
        if width == 3:
            out_ref[:] = _pack_lanes3(codes, packw_ref)
        else:
            out_ref[:] = _pack_lanes(codes, packw_ref)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("quantum_num", "width", "interpret"))
def quantize_pack_stochastic(flat: jax.Array, norm: jax.Array,
                             seed: jax.Array, quantum_num: int,
                             width: int = 4,
                             interpret: bool = False) -> jax.Array:
    """Fused QSGD compress-and-pack: stochastically quantize ``flat`` (1-D
    f32) to signed levels in ``[-quantum_num, quantum_num]`` and emit the
    packed ``width``-bit two's-complement wire words in one kernel — the
    payload leaves VMEM wire-ready (``ceil(n·width/8)`` uint8 bytes).

    ``width`` ∈ {2, 3, 4}; requires ``quantum_num <= 2^(width-1) - 1``
    (the two's-complement field's magnitude ceiling: 1 / 3 / 7).
    Bit-identity contract (pinned in tests/test_pallas_quant.py): equals
    :func:`quantize_stochastic` at the same seed followed by clamp →
    two's-complement fold → :func:`grace_tpu.ops.packing.pack_2bit` /
    ``pack_3bit`` / ``pack_4bit`` — same block layout, same PRNG stream,
    same rounding expression, so fusing the pack changes WHERE the bytes
    are produced, never WHAT they are. (3·LANES is a multiple of 8, so
    every block row's 3-bit bitstream starts byte-aligned and the
    per-block pack concatenates into the global bitstream exactly.)
    """
    if width not in (2, 3, 4):
        raise ValueError(f"width must be 2, 3 or 4; got {width}")
    if quantum_num > (1 << (width - 1)) - 1:
        raise ValueError(
            f"quantize_pack_stochastic packs {width}-bit two's-complement "
            f"levels (magnitude <= {(1 << (width - 1)) - 1}); "
            f"quantum_num={quantum_num} cannot fit — use a wider pack or "
            "quantize_stochastic (int8/int16 wire) instead.")
    n = flat.size
    block = ROWS_PER_BLOCK * LANES
    n_pad = -n % block
    # Zero padding quantizes to level 0 -> code 0, matching the reference
    # packers' zero-padded final byte, so a shared trailing byte is still
    # identical.
    padded = jnp.pad(flat.astype(jnp.float32), (0, n_pad))
    rows = padded.size // LANES
    x2d = padded.reshape(rows, LANES)
    scale = jnp.where(norm > 0, quantum_num / norm, 0.0).astype(jnp.float32)
    out_lanes = LANES * width // 8
    packw = (jnp.asarray(_pack_matrix3_np(LANES)) if width == 3
             else _pack_matrix(width, LANES))

    out = pl.pallas_call(
        _make_quantize_pack_kernel(hw_prng=not interpret, width=width),
        grid=(rows // ROWS_PER_BLOCK,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(packw.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ROWS_PER_BLOCK, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((ROWS_PER_BLOCK, out_lanes), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, out_lanes), jnp.uint8),
        interpret=_interpret_mode(interpret),
    )(seed.reshape(1, 1).astype(jnp.int32), scale.reshape(1, 1),
      jnp.asarray(quantum_num, jnp.int32).reshape(1, 1), packw, x2d)
    return out.reshape(-1)[: -(-n * width // 8)]


def _sign_pack_kernel(packw_ref, x_ref, out_ref):
    bits = (x_ref[:] >= 0).astype(jnp.float32)
    out_ref[:] = _pack_lanes(bits, packw_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sign_pack(flat: jax.Array, interpret: bool = False) -> jax.Array:
    """Fused signSGD compress-and-pack: the sign mask of ``flat`` (1-D, any
    float dtype) packed 8 signs/byte in one kernel — bit-identical to
    ``packing.pack_bits(flat >= 0)`` (pinned in tests), deterministic, so
    kernel and staged paths agree everywhere, not just in distribution.
    """
    n = flat.size
    block = SIGN_ROWS * SIGN_LANES
    n_pad = -n % block
    # Pad with -1.0: a negative pad lane contributes a 0 bit, exactly like
    # pack_bits' zero padding, so a shared final byte is still identical.
    # (float32 cast preserves sign for every input dtype incl. -0.0, whose
    # >= 0 is True on both paths.)
    padded = jnp.pad(flat.astype(jnp.float32), (0, n_pad),
                     constant_values=-1.0)
    rows = padded.size // SIGN_LANES
    x2d = padded.reshape(rows, SIGN_LANES)
    out = pl.pallas_call(
        _sign_pack_kernel,
        grid=(rows // SIGN_ROWS,),
        in_specs=[
            pl.BlockSpec((SIGN_LANES, SIGN_LANES // 8), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((SIGN_ROWS, SIGN_LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((SIGN_ROWS, SIGN_LANES // 8),
                               lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, SIGN_LANES // 8), jnp.uint8),
        interpret=_interpret_mode(interpret),
    )(_pack_matrix(1, SIGN_LANES), x2d)
    return out.reshape(-1)[: -(-n // 8)]
