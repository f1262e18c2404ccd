"""QSGD stochastic quantization (Alistarh et al. 2017).

Reference: grace_dl/dist/compressor/qsgd.py:6-38 — quantize |x| to
``quantum_num`` levels scaled by the L2 norm, stochastic rounding, sign
folded into the signed level. Payload dtype: int8 when quantum_num < 128;
for larger level counts the reference casts to torch.half (qsgd.py:27),
which silently loses integer precision above 2048 — here we use int16
instead (exact, same wire width). The torch copy's leftover debug prints
(torch/compressor/qsgd.py:14-15,33-34) are, of course, not replicated.

Sub-byte wire format (grace-tpu extension, no reference analog): for
``quantum_num <= 7`` the signed levels fit a two's-complement sub-byte
field, so the payload ships packed — the field width follows the level
range (:attr:`QSGDCompressor.pack_width`): 2-bit at ``quantum_num <= 1``
(4 codes/byte), 3-bit at ``<= 3`` (an LSB-first bitstream, 8 codes per
3 bytes), 4-bit at ``<= 7`` (2 codes/byte) — via the
:mod:`grace_tpu.ops.packing` reference packers (staged path) or the
fused Pallas quantize-and-pack kernel
(:func:`grace_tpu.ops.pallas_quant.quantize_pack_stochastic`), which
emits the packed bytes directly from VMEM with no full-width intermediate
in HBM. Both paths produce the identical byte layout (the pack_widths
contract, bit-identity pinned in tests/test_pallas_quant.py). The decode
side of the wire path is fused too: :meth:`decode_accumulate` runs the
ring-hop / boundary decode→accumulate as ONE Pallas kernel
(:mod:`grace_tpu.ops.pallas_wire`) when the shared selection rule
(:func:`grace_tpu.ops.pallas_mode`, family ``"wire"``) enables it,
bit-identical to the staged sequential decompress-and-add.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from grace_tpu.core import Compressor, Ctx, Payload, State
from grace_tpu.ops.packing import (pack_2bit, pack_3bit, pack_4bit,
                                   unpack_2bit, unpack_3bit, unpack_4bit)

# Staged reference packers per two's-complement field width.
_PACKERS = {2: (pack_2bit, unpack_2bit), 3: (pack_3bit, unpack_3bit),
            4: (pack_4bit, unpack_4bit)}


@dataclasses.dataclass(frozen=True)
class QSGDCompressor(Compressor):
    # Ring hop requant (comm.RingAllreduce): re-quantizing a partial sum is
    # exactly QSGD applied to a fresh tensor — unbiased, with per-element
    # error <= ||partial||/quantum_num per hop (the EQuARX-style quantized
    # multi-hop accumulation regime). Errors add over the W-2 intermediate
    # hops; raise quantum_num on large rings if the tail matters.
    supports_hop_requant = True
    # Quantized levels decode against each rank's own norm — no payload
    # algebra (the shared-scale variant is HomoQSGDCompressor, whose one
    # negotiated scale is exactly what makes the levels summable).
    payload_algebra = None

    quantum_num: int = 64
    # Fused Pallas TPU kernel for the quantize step (in-core PRNG, one HBM
    # pass — see grace_tpu/ops/pallas_quant.py). 'auto' (the default, also
    # what grace_from_params passes): kernel on real TPU, staged XLA path
    # elsewhere — the on-chip A/B of 2026-08-01, before the driver's
    # ledger, measured the kernel 42% faster end-to-end; not re-measured
    # on v5e in a cell (PERF.md §7, first open cell). Note the OPPOSITE
    # resolution from Top-K. True forces the kernel even off-TPU
    # (interpret mode: slow, test-only); False forces staged.
    use_pallas: bool | str = "auto"

    def __post_init__(self):
        # Identity membership, not ==: 1 == True would pass equality
        # validation yet be treated differently by the `is True` checks
        # below — accept exactly the three documented spellings.
        if not (self.use_pallas == "auto" or self.use_pallas is True
                or self.use_pallas is False):
            raise ValueError(f"use_pallas must be True, False or 'auto'; "
                             f"got {self.use_pallas!r}")

    def _pallas_mode(self):
        # The ONE shared selection rule (grace_tpu.ops.pallas_mode): under
        # 'auto' the kernel runs on real TPU and the staged path elsewhere
        # — the on-chip A/B of 2026-08-01, before the driver's ledger,
        # measured the fused quant kernel at 2111 img/s vs 1483 staged;
        # not re-measured on v5e in a cell (PERF.md §7, first open cell).
        # Unlike Top-K, QSGD's per-element stochastic rounding gained 42%
        # from the single-pass kernel with in-core PRNG.
        from grace_tpu.ops import pallas_mode
        return pallas_mode(self.use_pallas, kernel="quant")

    def _wire_mode(self):
        # Decode-side kernels are their own family ("wire"): a Mosaic
        # failure in one side must not force the other onto its staged
        # path (the PR-10 lesson that split _QUANT from _TOPK).
        from grace_tpu.ops import pallas_mode
        return pallas_mode(self.use_pallas, kernel="wire")

    @property
    def packed_wire(self) -> bool:
        """True iff the payload ships sub-byte packed codes: the packed
        wire format engages when the level range (±quantum_num after the
        overshoot clamp) fits a two's-complement nibble or narrower."""
        return self.quantum_num <= 7

    @property
    def pack_width(self) -> int:
        """Two's-complement field width of the packed wire format: the
        narrowest of {2, 3, 4} whose magnitude ceiling ``2^(w-1) - 1``
        holds ``quantum_num`` (1 → 2-bit, 3 → 3-bit, 7 → 4-bit). Only
        meaningful when :attr:`packed_wire`; declared in
        ``ops.packing.pack_widths()`` so flow pass 6's sub-byte audit
        covers every width this property can select."""
        if self.quantum_num <= 1:
            return 2
        if self.quantum_num <= 3:
            return 3
        return 4

    def compress(self, x: jax.Array, state: State, rng: jax.Array
                 ) -> tuple[Payload, Ctx, State]:
        shape = x.shape
        flat = x.reshape(-1)
        norm = jnp.linalg.norm(flat)
        dtype = jnp.int8 if self.quantum_num < 128 else jnp.int16
        enabled, interpret = self._pallas_mode()
        if enabled:
            seed = jax.random.randint(rng, (), 0, 2**31 - 1, jnp.int32)
            if self.packed_wire:
                from grace_tpu.ops.pallas_quant import \
                    quantize_pack_stochastic
                packed = quantize_pack_stochastic(
                    flat, norm, seed, self.quantum_num,
                    width=self.pack_width, interpret=interpret)
                return (packed, norm), (shape, x.dtype), state
            from grace_tpu.ops.pallas_quant import quantize_stochastic
            signed = quantize_stochastic(flat, norm, seed, self.quantum_num,
                                         out_dtype=dtype,
                                         interpret=interpret)
            return (signed, norm), (shape, x.dtype), state
        abs_g = jnp.abs(flat)
        level_float = jnp.where(norm > 0, self.quantum_num / norm * abs_g, 0.0)
        previous_level = jnp.floor(level_float)
        prob = jax.random.uniform(rng, flat.shape)
        is_next = (prob < (level_float - previous_level)).astype(flat.dtype)
        new_level = previous_level + is_next
        signed = new_level * jnp.sign(flat)
        if self.packed_wire:
            # Same clamp + two's-complement fold as the fused kernel, then
            # the reference packer — staged and kernel paths share ONE
            # byte layout (they differ only in the PRNG stream).
            w = self.pack_width
            q = float(self.quantum_num)
            clamped = jnp.clip(signed.astype(jnp.float32), -q, q)
            codes = jnp.where(clamped < 0, clamped + float(1 << w),
                              clamped).astype(jnp.uint8)
            return (_PACKERS[w][0](codes), norm), (shape, x.dtype), state
        return (signed.astype(dtype), norm), (shape, x.dtype), state

    def decompress(self, payload: Payload, ctx: Ctx) -> jax.Array:
        levels, norm = payload
        shape, dtype = ctx
        if self.packed_wire:
            import numpy as np
            w = self.pack_width
            numel = int(np.prod(shape, dtype=np.int64)) if shape else 1
            codes = _PACKERS[w][1](levels, numel).astype(jnp.int8)
            levels = jnp.where(codes >= (1 << (w - 1)), codes - (1 << w),
                               codes)
        out = norm / self.quantum_num * levels.astype(dtype)
        return out.reshape(shape)

    def wire_fused(self) -> bool:
        """Live wire-kernel gate (core.Compressor.wire_fused): True only
        when the shared selection rule enables the "wire" family AND the
        payload ships packed — exactly the condition under which
        :meth:`decode_accumulate` takes its fused branch."""
        return self._wire_mode()[0] and self.packed_wire

    def decode_accumulate(self, payloads, ctxs):
        """The fused hop decode: K packed payloads -> one f32 partial in
        ONE Pallas kernel (grace_tpu.ops.pallas_wire.decode_accumulate),
        bit-identical to the staged sequential ``decompress +
        decompress`` the base hook runs (same unpack layout, same
        sign-extension, same per-payload ``norm/quantum_num`` scalar
        division, same accumulation order) — so 'auto' gating can only
        ever change WHERE the hop runs. Falls back to the staged spelling
        whenever the wire-kernel family is disabled, the payload is not
        packed, or the decode dtype is not f32."""
        enabled, interpret = self._wire_mode()
        shape, dtype = ctxs[0]
        if (not enabled or not self.packed_wire
                or jnp.dtype(dtype) != jnp.float32
                or any(c[:2] != (shape, dtype) for c in ctxs)):
            return super().decode_accumulate(payloads, ctxs)
        import numpy as np

        from grace_tpu.ops.pallas_wire import decode_accumulate as _fused
        numel = int(np.prod(shape, dtype=np.int64)) if shape else 1
        stacked = jnp.stack([p[0] for p in payloads])
        # The staged decompress computes ``norm / quantum_num * level``:
        # the identical scalar division here feeds the kernel, so even
        # the scale bits match the staged path.
        scales = jnp.stack([p[1] / self.quantum_num for p in payloads])
        out = _fused(stacked, scales, numel, self.pack_width,
                     interpret=interpret)
        return out.reshape(shape)
