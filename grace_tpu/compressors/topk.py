"""Top-K magnitude sparsification — exact, hardware-approximate, and chunked.

Reference: grace_dl/dist/compressor/topk.py:6-36 — keep the k = ⌈ratio·n⌉
largest-magnitude entries, ship (values, indices), scatter into zeros to
decompress. All three variants here share that wire format (fixed k, so the
all-gather path needs no size exchange; XLA static shapes).

``algorithm`` picks the selection strategy — this is where TPU-first design
diverges from the CUDA reference, because exact global top-k lowers to a
full sort, the single most expensive op in the whole pipeline (measured
~70 ms for a 25.5M-element fused ResNet-50 gradient on one chip, ~700×
the cost of an elementwise pass):

* ``'exact'`` — `lax.top_k`. Bit-exact reference parity.
* ``'approx'`` — `lax.approx_max_k`, TPU's hardware-accelerated PartialReduce
  top-k (Chern et al. 2022, arXiv:2206.14286) with a configurable
  ``recall_target``. Misses are caught by error-feedback memory the same way
  DGC's sampled threshold misses are.
* ``'chunk'`` — split the flat tensor into k equal chunks and keep the
  single largest-|x| entry of each (a pure VPU argmax reduction — no sort
  anywhere). Selection is top-1-per-chunk rather than global top-k, the
  same relaxation DGC makes with sampled thresholds
  (grace_dl/dist/compressor/dgc.py:17-24); residual feedback compensates.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from grace_tpu.core import Compressor, Ctx, Payload, State
from grace_tpu.ops.sparse import (chunk_first_max, chunkwise_dense,
                                  chunkwise_dense_sum, scatter_dense,
                                  takes_row_slices)
from grace_tpu.telemetry.scopes import STAGE_DECOMPRESS, trace_stage


def static_k(numel: int, ratio: float) -> int:
    return max(1, int(numel * ratio))


@dataclasses.dataclass(frozen=True)
class TopKCompressor(Compressor):
    # Ring hop requant (comm.RingAllreduce): re-selecting top-k over a
    # partial sum of sparsified shards is the standard multi-hop relaxation
    # (DynamiQ-style re-sparsification) — the survivors of earlier hops
    # compete with the new contribution, and dropped mass is bounded by the
    # per-hop selection error. Sound for any selection algorithm here.
    supports_hop_requant = True
    # Per-rank index sets: summing payloads adds values belonging to
    # different coordinates (the reference's silent topk+Allreduce bug) —
    # no payload algebra, requant is the only hop-pipelined route.
    payload_algebra = None

    compress_ratio: float = 0.3
    algorithm: str = "exact"      # 'exact' | 'approx' | 'chunk'
    recall_target: float = 0.95   # for 'approx'
    wire_dtype: str = "float32"   # 'float32' | 'bfloat16' wire values
    # Fused Pallas TPU kernel for the chunk-mode LOCAL pipeline (compensate
    # + select + value extract + residual update in one HBM pass — see
    # grace_tpu/ops/pallas_topk.py), used via the Communicator.step fast
    # path with linear-error-feedback memories. 'auto' resolves to the
    # staged XLA path everywhere: the on-chip A/B of 2026-07-31, before
    # the driver's ledger, measured staged at 1602 vs fused-kernel 1441
    # imgs/sec on ResNet-50; not re-measured on v5e in a cell (PERF.md §7,
    # first open cell). So the kernel is an explicit opt-in
    # (True; forces interpret mode off-TPU for tests) until a measurement
    # says otherwise.
    use_pallas: bool | str = "auto"

    def __post_init__(self):
        if self.algorithm not in ("exact", "approx", "chunk"):
            raise ValueError(f"unknown topk algorithm {self.algorithm!r}")
        if self.wire_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"unknown wire_dtype {self.wire_dtype!r}")
        # Identity membership, not ==: 1 == True would pass equality
        # validation yet fail the `is True` opt-in check in _pallas_mode,
        # silently running staged — accept exactly the three spellings.
        if not (self.use_pallas == "auto" or self.use_pallas is True
                or self.use_pallas is False):
            raise ValueError(f"use_pallas must be True, False or 'auto'; "
                             f"got {self.use_pallas!r}")

    def _pallas_mode(self):
        from grace_tpu.ops import pallas_disabled
        if pallas_disabled(explicit=self.use_pallas is True, kernel="topk"):
            return False, False
        if self.use_pallas is True:
            return True, jax.default_backend() != "tpu"
        return False, False            # 'auto' == staged (measured faster)

    def _staged(self, interpret: bool, why: str):
        """A fused path cannot take this buffer: go staged (return None) —
        unless the config DEMANDED the kernel (``use_pallas=True``) and
        this is a TPU, where the compiled kernel is what was asked for;
        then say which shape refused instead of silently timing the
        staged path under the kernel's name. Off-TPU ``True`` means
        interpret mode, a test vehicle, and keeps the quiet fallback."""
        if self.use_pallas is True and not interpret:
            raise ValueError(
                f"TopKCompressor(use_pallas=True): the fused chunk kernel "
                f"cannot run here — {why}. Use use_pallas='auto' to let the "
                "staged path take such buffers.")
        return None

    def refuse_per_shard_compress(self, comm_name: str) -> None:
        """Hook of ``comm._shard_compress``. The shard-parallel
        communicators (two-shot, ring, rscatter, hier) encode each shard
        with plain :meth:`compress`; the fused chunk kernels exist only on
        the whole-buffer step path (:meth:`fused_feedback_compress`,
        :meth:`fused_aggregate_decompress`). A kernel DEMANDED on a TPU
        cannot be honoured there, and is refused rather than quietly
        replaced by the staged select."""
        enabled, interpret = self._pallas_mode()
        if self.use_pallas is True and enabled and not interpret:
            raise TypeError(
                f"TopKCompressor(use_pallas=True) under {comm_name}: the "
                "fused chunk Top-K kernel runs only on the whole-buffer "
                "step path (allgather/broadcast/identity); shard-parallel "
                "communicators compress per shard through the staged "
                "select. Use use_pallas='auto' (staged) with this "
                "communicator, or Allgather with the kernel.")

    def _fused_chunk_gate(self, numel: int, dtype, world):
        """Shared guard for both fused fast paths. Returns (k, interpret)
        or None when the staged path must run: non-chunk algorithm, Pallas
        disabled, non-f32 data (the kernels compute/ship f32 — the staged
        path works in x.dtype, so wire size and numerics would change),
        degenerate k, or interpret mode on a multi-device mesh
        (interpreter Pallas deadlocks inside a multi-device shard_map
        program on CPU — observed: one 8-device step hangs >7 min where
        the 1-device step takes milliseconds; the compiled TPU kernel has
        no such restriction, and on a TPU ``interpret`` is False so the
        guard cannot fire there). The shape/dtype refusals raise instead
        when the kernel was demanded on a TPU (:meth:`_staged`).
        ``world`` is a zero-arg thunk so the check works outside shard_map
        too."""
        if self.algorithm != "chunk":
            return None
        enabled, interpret = self._pallas_mode()
        if not enabled:
            return None
        if dtype != jnp.float32:
            return self._staged(interpret, f"data dtype {dtype} is not "
                                "float32")
        if interpret and world() > 1:
            return None
        k = static_k(numel, self.compress_ratio)
        if numel < 2 * k:
            return self._staged(interpret, f"{numel} elements at k={k} "
                                "leave fewer than two rows per chunk")
        return k, interpret

    def fused_feedback_compress(self, x: jax.Array, state, coeffs,
                                rng: jax.Array, world=lambda: 1):
        """Communicator.step fused fast path (one-HBM-pass local pipeline).

        ``coeffs = (beta, gamma)`` is the paired memory's declared linear
        feedback ``compensate = beta*state + gamma*x``; returns
        ``(payload, ctx, new_residual_state)`` bit-identical to
        compensate -> compress -> update, or None when this config cannot
        take the fast path (see ``_fused_chunk_gate``, plus a VMEM block
        budget check for the row count).
        """
        gate = self._fused_chunk_gate(x.size, x.dtype, world)
        if gate is None:
            return None
        k, interpret = gate
        if state is not None and state.dtype != jnp.float32:
            return self._staged(interpret, f"residual dtype {state.dtype} "
                                "is not float32")
        shape, numel = x.shape, x.size
        from grace_tpu.ops.pallas_topk import (chunk_compress_feedback,
                                               compress_block_cols)
        if compress_block_cols(numel // k) <= 0:
            # tiny ratio => too many rows for one VMEM block
            return self._staged(
                interpret, f"shape {shape}: {numel // k} rows per chunk "
                f"(k={k}) do not fit the VMEM block budget")
        beta, gamma = coeffs
        resid = None if state is None else state.reshape(-1)
        values, win_row, new_resid = chunk_compress_feedback(
            x.reshape(-1), resid, k, beta=float(beta), gamma=float(gamma),
            wire_bf16=self.wire_dtype == "bfloat16", interpret=interpret)
        indices = win_row * k + jnp.arange(k, dtype=jnp.int32)
        new_state = None if state is None else new_resid.reshape(state.shape)
        return ((values, indices), (numel, shape, x.dtype), new_state)

    def _select(self, flat: jax.Array, k: int) -> jax.Array:
        if self.algorithm == "approx" and flat.size > 4 * k:
            _, indices = lax.approx_max_k(jnp.abs(flat), k,
                                          recall_target=self.recall_target)
            return indices
        _, indices = lax.top_k(jnp.abs(flat), k)
        return indices

    def _chunk_compress(self, flat: jax.Array, k: int
                        ) -> tuple[jax.Array, jax.Array]:
        """Gather-free chunk-mode selection: (values, indices).

        STRIDED chunks: viewing the 0-padded flat buffer as (rows, k)
        row-major, chunk c is column c = {c, c+k, c+2k, ...}. Padding lives
        only in the last row (pad = rows*k - n < k), so every column keeps
        >= rows-1 >= 1 real elements — contiguous chunking can strand whole
        all-padding chunks when pad >= chunk. A 0-padding lane can at worst
        tie a real |x| = 0, and argmax's first-max rule resolves the tie to
        the earlier, REAL row (row 0 is never padding), so every wire index
        stays < n — no separate -1-padded buffer needed for the argmax.

        Values come from a one-hot masked sum over the (rows, k) view, NOT
        ``flat[indices]``: a k-element gather from the fused buffer
        serializes on TPU (measured ~5-6 ms of the ~10 ms compressed-step
        overhead at n=25.5M, on-chip 2026-08-01) while the masked reduction
        is one more elementwise pass (~0.3 ms). Exactly one mask row is hot
        per column, so the sum reproduces the gathered value bit-exactly —
        argmax and the mask agree on ties (both take the first max).

        The view itself is a physical relayout on the TPU (k is rarely a
        multiple of the 128 lanes), and past ``ops.sparse.
        RELAYOUT_LOOP_ELEMENTS`` XLA:TPU runs the pad + ``reshape(rows, k)``
        as a ``while`` loop over 4-row windows. Such a leaf never asks for
        the view: ``ops.sparse.chunk_first_max`` reads the same columns
        from row-block slices of the flat buffer, each small enough for
        one reshape, and returns the same ``values`` and winning rows, bit
        for bit. The route follows from the leaf's static ``(rows, k)``
        alone.
        """
        n = flat.size
        rows = -(-n // k)                      # ceil(n / k) >= 2
        if takes_row_slices(rows, k):
            values, win_row = chunk_first_max(flat, k)
        else:
            body = jnp.zeros((rows * k,), flat.dtype).at[:n].set(flat)
            body = body.reshape(rows, k)
            win_row = jnp.argmax(jnp.abs(body), axis=0).astype(jnp.int32)
            mask = (jnp.arange(rows, dtype=jnp.int32)[:, None]
                    == win_row[None, :])
            values = jnp.sum(jnp.where(mask, body, 0), axis=0)
        indices = win_row * k + jnp.arange(k, dtype=jnp.int32)
        return values, indices

    def compress(self, x: jax.Array, state: State, rng: jax.Array
                 ) -> tuple[Payload, Ctx, State]:
        shape, numel = x.shape, x.size
        flat = x.reshape(-1)
        k = static_k(numel, self.compress_ratio)
        if self.algorithm == "chunk" and numel >= 2 * k:
            values, indices = self._chunk_compress(flat, k)
        else:
            indices = self._select(flat, k).astype(jnp.int32)
            values = flat[indices]
        if self.wire_dtype == "bfloat16":
            # 25% fewer wire bytes (6 vs 8 per kept element, with int32
            # indices); the rounding error lands in the residual memory and
            # is re-injected next step — same argument as 'approx' recall.
            values = values.astype(jnp.bfloat16)
        return (values, indices), (numel, shape, x.dtype), state

    def fused_aggregate_decompress(self, gathered: Payload, ctx: Ctx,
                                   world: int):
        """Allgather exchange path: (world, k) payload stacks -> aggregated
        (and world-averaged, per ``self.average``) dense tensor, without
        the (world, rows, k) stack of ``vmap(decompress)``.

        With the Pallas kernel enabled: one n-sized HBM pass
        (ops/pallas_topk.py chunk_aggregate_dense). Otherwise, for a
        chunk-structured payload of world > 1 ranks: the staged
        :meth:`_aggregate_rows`. None = the communicator's vmapped decode
        (non-chunk algorithms, sub-k payloads, world == 1).
        """
        numel, shape, dtype = ctx
        gate = self._fused_chunk_gate(numel, dtype, lambda: world)
        if gate is None:
            return self._aggregate_rows(gathered, ctx, world)
        k, interpret = gate
        values, indices = gathered
        if values.shape != (world, k):
            # sub-k payloads lose chunk structure
            return self._staged(
                interpret, f"gathered payload {values.shape} is not "
                f"(world={world}, k={k})")
        from grace_tpu.ops.pallas_topk import (aggregate_block_cols,
                                               chunk_aggregate_dense)
        if aggregate_block_cols(numel // k, world) <= 0:
            # pod-scale W inflates the input blocks
            return self._staged(
                interpret, f"shape {shape}: {numel // k} rows x "
                f"world={world} do not fit the VMEM block budget")
        win = (indices // k).astype(jnp.int32)
        out = chunk_aggregate_dense(values.astype(jnp.float32), win, k,
                                    numel, average=self.average,
                                    interpret=interpret)
        return out.reshape(shape).astype(dtype)

    def _aggregate_rows(self, gathered: Payload, ctx: Ctx, world: int):
        """Staged aggregate-then-reshape decode of a gathered chunk payload
        (ops.sparse.chunkwise_dense_sum): the ranks' sum in the (rows, k)
        view, ONE flatten, then the average — the same sum over the same
        ``world`` addends as ``vmap(decompress)`` + ``aggregate``. Static
        conditions only: the same chunk structure :meth:`decompress` checks
        per rank, and more than one rank. At world == 1 the single decode
        fuses into its consumer as it is, and None leaves that program
        unchanged; only a leaf on the row-slices route
        (``ops.sparse.takes_row_slices``) is answered, with its plain
        :meth:`decompress`."""
        values, indices = gathered
        numel, shape, dtype = ctx
        k = static_k(numel, self.compress_ratio)
        if (self.algorithm != "chunk" or numel < 2 * k
                or values.shape != (world, k)):
            return None
        if world == 1:
            # One payload: nothing to sum. A leaf on the row-slices route
            # is decoded as the memory update decodes it, unbatched, so
            # that XLA keeps one of the two decodes.
            if not takes_row_slices(-(-numel // k), k):
                return None
            return self.decompress((values[0], indices[0]), ctx)
        with trace_stage(f"{STAGE_DECOMPRESS}/aggregate_rows"):
            out = chunkwise_dense_sum(values.astype(dtype),
                                      (indices // k).astype(jnp.int32),
                                      -(-numel // k), numel, shape)
            return out / world if self.average else out

    def decompress(self, payload: Payload, ctx: Ctx) -> jax.Array:
        values, indices = payload
        numel, shape, dtype = ctx
        k = static_k(numel, self.compress_ratio)
        # Chunk-mode payloads have exactly one kept element per column of
        # the (rows, k) view, so the dense tensor is a one-hot row select —
        # no scatter (which serializes on TPU and dominated the headline
        # bench). Shape check is static: a sub-k payload (e.g. a TwoShot
        # per-rank slice) loses the full-column structure and takes the
        # general scatter path instead.
        if (self.algorithm == "chunk" and numel >= 2 * k
                and values.shape[0] == k):
            rows = -(-numel // k)
            win_row = (indices // k).astype(jnp.int32)
            return chunkwise_dense(values.astype(dtype), win_row, rows,
                                   numel, shape)
        return scatter_dense(values.astype(dtype), indices, numel, shape)
