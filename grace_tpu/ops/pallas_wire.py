"""Pallas TPU kernels for the kernel-resident wire path (ROADMAP item 2).

PR 10's kernels fused the ENCODE side (compress-and-pack); every ring hop,
hier slice boundary, and rscatter owned-chunk sum still decoded /
accumulated as staged unpack → cast → add HLO — per-hop traffic that
materializes full-width intermediates in HBM, exactly what EQuARX
(PAPERS.md) eliminates by fusing quantized aggregation inside XLA and
what THC's payload-space aggregation shows pays off most at narrow pack
widths. This module is the decode-side twin of
:mod:`grace_tpu.ops.pallas_quant`:

* :func:`decode_accumulate` — K packed payloads (ring hop: K=2, recv +
  own; hier slice boundary: K = gathered slice count) are unpacked,
  sign-extended, scaled and accumulated into ONE f32 partial inside one
  kernel: 2 (or K) packed HBM reads + 1 full-width HBM write, no staged
  intermediates. Handles the qsgd two's-complement widths {2, 3, 4} and
  the 1-bit sign mask (``sign=True``; ``vote=True`` additionally applies
  the majority-vote re-sign at the end — the hier boundary's aggregate).
* :func:`packed_int_accumulate` — the exact payload-space accumulate for
  ``shared_scale`` packed payloads (homoqsgd at ``accum_bits`` ∈
  {2, 3, 4}): unpack → integer add → repack in one kernel, bytes in /
  bytes out. Exactness is the communicators' ``payload_sum_max_world``
  gate: every partial sum of W levels in ``[-q, q]`` fits the field iff
  ``W·q <= 2^(bits-1) - 1`` — the same ONE constant flow pass 6 and the
  tuner's numeric gate check statically.

Bit-identity contract (the acceptance bar, pinned in tests/test_wire.py):
each kernel equals its staged path — sequential
``decompress(payload_k)`` adds in stack order (the exact expression the
communicators run), same f32 operations in the same order — so fusing
changes WHERE the arithmetic runs, never WHAT it computes. The scale
passed in is the PRE-DIVIDED ``norm / quantum_num`` computed by the
caller with the staged path's own expression, so even the scalar
division contributes identical bits.

Unpacking without gathers: the pack-matrix trick from ``pallas_quant``
run in reverse. Every code lane's byte is a single known source lane, so
a constant matrix with ONE nonzero per column — ``M[byte(l), l] =
2^(-shift(l))`` — turns "route each byte to its code lanes, pre-shifted"
into one MXU dot (``bytes @ M``), and the code is then
``mod(floor(·), 2^width)`` elementwise. All values are integers ≤ 255
times exact powers of two: exact in f32. The 3-bit width straddles byte
boundaries, so it decodes per BIT (``M3[byte(g), g] = 2^(-(g%8))``,
``bit = mod(floor(·), 2)``) and reassembles codes with a second
constant dot (``bits @ C``, ``C[3l+b, l] = 2^b``) — the decode twin of
the bit-plane pack in ``pallas_quant._pack_matrix3_np``.

The selection rule for every caller is :func:`grace_tpu.ops.pallas_mode`
with kernel family ``"wire"`` (``GRACE_DISABLE_PALLAS`` /
``GRACE_DISABLE_PALLAS_WIRE`` honored, ``use_pallas='auto'`` = kernel on
real TPU, staged elsewhere, interpret mode off-TPU when forced).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from grace_tpu.ops.pallas_quant import (LANES, ROWS_PER_BLOCK,
                                        _interpret_mode, _pack_matrix3_np,
                                        _pack_matrix_np)

__all__ = ["decode_accumulate", "packed_int_accumulate", "hop_hbm_bytes",
           "WIRE_WIDTHS"]

# The pack widths this module's kernels decode: the sign mask plus the
# qsgd/homoqsgd two's-complement fields (grace_tpu.ops.packing declares
# the reference layouts).
WIRE_WIDTHS = (1, 2, 3, 4)


@functools.lru_cache(maxsize=8)
def _decode_matrix_np(width: int, code_lanes: int):
    """Unpack matrix for widths dividing 8: ``M[l // per_byte, l] =
    2^(-width·(l % per_byte))`` — one nonzero per column, so ``bytes @ M``
    lands every code lane's source byte pre-shifted; ``mod(floor(·),
    2^width)`` masks it to the code."""
    import numpy as np

    per_byte = 8 // width
    m = np.zeros((code_lanes // per_byte, code_lanes), np.float32)
    for lane in range(code_lanes):
        m[lane // per_byte, lane] = 2.0 ** (-(width * (lane % per_byte)))
    return m


@functools.lru_cache(maxsize=4)
def _decode_matrix3_np(code_lanes: int):
    """The 3-bit decode pair: ``M3`` routes byte ``g//8`` to bit lane
    ``g`` pre-shifted by ``2^(-(g%8))`` (bit = ``mod(floor(·), 2)``), and
    ``C[3l+b, l] = 2^b`` reassembles the three planes into codes."""
    import numpy as np

    m = np.zeros((3 * code_lanes // 8, 3 * code_lanes), np.float32)
    for g in range(3 * code_lanes):
        m[g // 8, g] = 2.0 ** (-(g % 8))
    c = np.zeros((3 * code_lanes, code_lanes), np.float32)
    for lane in range(code_lanes):
        for b in range(3):
            c[3 * lane + b, lane] = float(1 << b)
    return m, c


def _unpack_block(bytes_f32, dec_ref, c_ref, width: int):
    """(rows, bytes) f32 -> (rows, LANES) f32 codes in [0, 2^width)."""
    e = jax.lax.dot_general(bytes_f32, dec_ref[:], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if width == 3:
        bits = jnp.mod(jnp.floor(e), 2.0)
        return jax.lax.dot_general(bits, c_ref[:], (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    return jnp.mod(jnp.floor(e), float(1 << width))


def _make_decode_accum_kernel(width: int, k_payloads: int, sign: bool,
                              vote: bool):
    mask = float(1 << width)
    half = float(1 << (width - 1))

    def kernel(scale_ref, dec_ref, c_ref, x_ref, out_ref):
        acc = None
        for k in range(k_payloads):
            # uint8 -> f32 via the int32 hop (Mosaic has no direct
            # uint->float cast — same constraint as the PRNG bits in
            # pallas_quant._signed_levels).
            b = x_ref[k].astype(jnp.int32).astype(jnp.float32)
            code = _unpack_block(b, dec_ref, c_ref, width)
            if sign:
                val = code * 2.0 - 1.0
            else:
                level = code - mask * (code >= half).astype(jnp.float32)
                val = scale_ref[0, k] * level
            acc = val if acc is None else acc + val
        if vote:
            acc = (acc >= 0).astype(jnp.float32) * 2.0 - 1.0
        out_ref[:] = acc

    return kernel


def _block_layout(width: int, numel: int):
    """(padded_rows, byte_lanes, padded_nbytes): the (rows, LANES) code
    grid padded to whole ROWS_PER_BLOCK tiles, and its byte image.
    ``LANES·width`` is a multiple of 8 for every wire width, so each code
    row's bitstream starts byte-aligned and the per-row byte blocks
    concatenate into the packers' global byte stream exactly."""
    block = ROWS_PER_BLOCK * LANES
    padded_codes = numel + (-numel % block)
    rows = padded_codes // LANES
    byte_lanes = LANES * width // 8
    return rows, byte_lanes, rows * byte_lanes


def _stack_bytes(stacked: jax.Array, width: int, numel: int):
    rows, byte_lanes, padded_nbytes = _block_layout(width, numel)
    k = stacked.shape[0]
    padded = jnp.zeros((k, padded_nbytes), jnp.uint8
                       ).at[:, :stacked.shape[1]].set(stacked)
    return padded.reshape(k, rows, byte_lanes), rows, byte_lanes


def _decode_constants(width: int):
    if width == 3:
        m, c = _decode_matrix3_np(LANES)
        return jnp.asarray(m), jnp.asarray(c)
    m = _decode_matrix_np(width, LANES)
    # The 3-bit reassembly dot is dead for the other widths; a (1, 1)
    # placeholder keeps ONE kernel signature across widths.
    import numpy as np

    return jnp.asarray(m), jnp.zeros((1, 1), np.float32)


@functools.partial(jax.jit, static_argnames=("numel", "width", "sign",
                                             "vote", "interpret"))
def decode_accumulate(stacked: jax.Array, scales: jax.Array, numel: int,
                      width: int, sign: bool = False, vote: bool = False,
                      interpret: bool = False) -> jax.Array:
    """Fused decode→accumulate: K packed payloads -> one f32 partial.

    ``stacked`` is (K, nbytes) uint8 — the K payloads' packed bytes in
    accumulation order (ring hop: (recv, own)); ``scales`` (K,) f32 is
    each payload's PRE-DIVIDED decode scale (``norm_k / quantum_num``,
    computed by the caller with the staged path's own expression;
    ignored when ``sign=True``). Returns the length-``numel`` f32
    partial, bit-identical to sequential staged
    ``decompress(payload_0) + decompress(payload_1) + …``.

    ``sign=True`` decodes 1-bit masks to ±1 and sums (the signsgd ring
    hop's partial); ``vote=True`` additionally re-signs the sum
    (``(Σ >= 0)·2 − 1`` — the majority-vote aggregate the hier slice
    boundary applies, ties resolving +1 exactly like
    ``SignSGDCompressor.aggregate``).
    """
    if width not in WIRE_WIDTHS:
        raise ValueError(f"width must be one of {WIRE_WIDTHS}; got {width}")
    if sign and width != 1:
        raise ValueError("sign decode is the 1-bit mask path")
    if vote and not sign:
        raise ValueError("vote re-sign only applies to the sign path")
    k = stacked.shape[0]
    x3d, rows, byte_lanes = _stack_bytes(stacked, width, numel)
    dec, c3 = _decode_constants(width)
    out = pl.pallas_call(
        _make_decode_accum_kernel(width, k, sign, vote),
        grid=(rows // ROWS_PER_BLOCK,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(dec.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(c3.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, ROWS_PER_BLOCK, byte_lanes),
                         lambda i: (0, i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((ROWS_PER_BLOCK, LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=_interpret_mode(interpret),
    )(scales.reshape(1, -1).astype(jnp.float32), dec, c3, x3d)
    return out.reshape(-1)[:numel]


def _make_packed_accum_kernel(width: int, k_payloads: int):
    mask = float(1 << width)
    half = float(1 << (width - 1))

    def kernel(dec_ref, c_ref, packw_ref, x_ref, out_ref):
        acc = None
        for k in range(k_payloads):
            b = x_ref[k].astype(jnp.int32).astype(jnp.float32)
            code = _unpack_block(b, dec_ref, c_ref, width)
            level = code - mask * (code >= half).astype(jnp.float32)
            acc = level if acc is None else acc + level
        # Fold the (gate-bounded, field-exact) integer sum back into the
        # two's-complement code range and repack with the encode side's
        # pack matrices.
        codes = acc + mask * (acc < 0).astype(jnp.float32)
        if width == 3:
            from grace_tpu.ops.pallas_quant import _pack_lanes3
            out_ref[:] = _pack_lanes3(codes, packw_ref)
        else:
            from grace_tpu.ops.pallas_quant import _pack_lanes
            out_ref[:] = _pack_lanes(codes, packw_ref)

    return kernel


@functools.partial(jax.jit, static_argnames=("numel", "width", "interpret"))
def packed_int_accumulate(stacked: jax.Array, numel: int, width: int,
                          interpret: bool = False) -> jax.Array:
    """Exact payload-space accumulate for packed ``shared_scale`` levels:
    K packed payloads in, ONE packed payload of the integer level sums
    out — unpack → add → repack never leaves VMEM. Exact iff the summed
    levels fit the ``width``-bit two's-complement field, which is
    precisely the ``payload_sum_max_world`` bound the communicators'
    runtime gate and flow pass 6 enforce from the same constant."""
    if width not in (2, 3, 4):
        raise ValueError(f"width must be 2, 3 or 4; got {width}")
    k = stacked.shape[0]
    nbytes = stacked.shape[1]
    x3d, rows, byte_lanes = _stack_bytes(stacked, width, numel)
    dec, c3 = _decode_constants(width)
    packw = (jnp.asarray(_pack_matrix3_np(LANES)) if width == 3
             else jnp.asarray(_pack_matrix_np(width, LANES)))
    out = pl.pallas_call(
        _make_packed_accum_kernel(width, k),
        grid=(rows // ROWS_PER_BLOCK,),
        in_specs=[
            pl.BlockSpec(dec.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(c3.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(packw.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, ROWS_PER_BLOCK, byte_lanes),
                         lambda i: (0, i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((ROWS_PER_BLOCK, byte_lanes),
                               lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, byte_lanes), jnp.uint8),
        interpret=_interpret_mode(interpret),
    )(dec, c3, packw, x3d)
    return out.reshape(-1)[:nbytes]


def hop_hbm_bytes(numel: int, width: int, fused: bool) -> int:
    """The documented HBM-traffic model of ONE ring hop's
    decode→accumulate→requant at pack width ``width`` (f32 element width
    4 B) — the projection behind the wire-path ≥2× device-time target
    (ROADMAP item 2), pinned by tests/test_wire.py and stamped into
    WIRE_LAST.json. Hop device time on TPU is HBM-bandwidth-bound (every
    op is elementwise or a tiny constant dot), so bytes moved is the
    honest static proxy until the item-1 capture campaign measures stage
    attribution on silicon.

    Staged path (what the pre-PR-19 hop traced to): each of the 2
    payloads materializes unpacked codes (1 B/elem, write+read),
    sign-extended int levels (1 B, write+read), and the decoded f32
    tensor (4 B, write+read) — plus the packed reads, the f32 partial
    write+read, and the requant encode's staged quantize (f32
    read/write) and pack (code write+read, packed write).

    Fused path: the decode_accumulate kernel reads 2 packed payloads and
    writes ONE f32 partial; the fused compress-and-pack encode kernel
    (PR 10) reads the partial and writes the packed requant payload.
    """
    packed = -(-numel * width // 8)
    f32 = 4 * numel
    if fused:
        return (2 * packed + f32) + (f32 + packed)
    staged_decode = 2 * (packed + 2 * numel + 2 * numel + 2 * f32)
    partial = 2 * f32                       # accumulate write + read
    staged_requant = 2 * f32 + 2 * numel + packed
    return staged_decode + partial + staged_requant
