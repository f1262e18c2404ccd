"""Communicators: XLA collectives over a named mesh axis.

TPU-native replacements for the reference's three communicators
(grace_dl/dist/communicator/{allreduce,allgather,broadcast}.py), which issue
eager c10d/Horovod NCCL calls per tensor. Here each communicator is a pure
function of the payload built from `jax.lax` collectives, traced inside
`shard_map`/`pjit` over a device mesh so XLA schedules them on ICI and
overlaps them with compute — no handle tables, no background thread
(cf. patch_files/horovod/torch/mpi_ops.py:68-75,423-439).

Compatibility matrix (reference IMPLEMENTING.md:43-45): ``Allreduce`` only
suits compressors whose payload is dense, same-shaped and summable (none,
fp16, randomk, powersgd); ``Allgather`` is general-purpose; ``Broadcast``
exists for parity and is realised with the same all-gather collective — a
loop of per-root broadcasts (grace_dl/dist/communicator/broadcast.py:18-33)
would serialise W collectives for an identical result.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from grace_tpu.core import (Communicator, Compressor, Ctx, LinkBytes,
                            Payload, SINGLE_SLICE, Topology, axis_size)
from grace_tpu.telemetry.scopes import (STAGE_DECOMPRESS, STAGE_EXCHANGE,
                                        STAGE_PIPELINE, STAGE_RING_HOP,
                                        trace_stage)

__all__ = ["Allreduce", "Allgather", "Broadcast", "Identity",
           "SignAllreduce", "TwoShotAllreduce", "RingAllreduce",
           "ReduceScatterAllreduce", "HierarchicalAllreduce",
           "vote_exact_max_world", "masked_broadcast",
           "masked_broadcast_tree"]


def vote_exact_max_world(vote_dtype) -> int:
    """Largest world size whose ±1 majority-vote sums stay integer-exact
    in ``vote_dtype`` — the declared numeric contract of the psum-vote
    routing, derived from first principles rather than hardcoded: a float
    with p explicit mantissa bits represents every integer up to
    ``2^(p+1)`` exactly (p stored bits plus the implicit leading one), and
    a W-rank vote tally lives in ``[-W, W]``, so the sum is exact iff
    ``W <= 2^(p+1)``. bfloat16 (p=7) gives the famous 256; float16 (p=10)
    gives 2048; float32 (p=23) gives 16,777,216.

    ONE constant, two enforcement points: the runtime check in
    ``_psum_majority_vote`` raises past the bound on a live mesh, and the
    static auditor's ``numeric_safety`` pass
    (:mod:`grace_tpu.analysis.flow`) re-verifies every traced vote psum
    against the same function — the bound can never drift between the
    docstring, the runtime guard, and the lint gate.
    """
    dt = jnp.dtype(vote_dtype)
    if not jnp.issubdtype(dt, jnp.floating):
        raise TypeError(f"vote_dtype must be a float dtype; got {dt.name}")
    return int(2 ** (jnp.finfo(dt).nmant + 1))


# XLA-TPU layout pathology guard (observed on BERT-base, 2026-08-01): a
# materialized 1-D f32[108793346] that feeds an all-reduce and is then
# consumed by a ~200-way slice/reshape fan-out gets assigned layout
# f32[54396673,2]{1,0:T(8,128)} — the minor-dim pad 2->128 inflates 435 MB
# to 27.8 GB and OOMs 16 GB HBM at compile time. Psumming such buffers in
# fixed-size chunks keeps every materialized piece small enough that XLA
# picks a sane layout (verified: same program compiles at 2.2 GB temp).
# ResNet-50's 25.5 M-element fused gradient does NOT trigger it (measured
# 4.7 MB temp), so chunking only engages above _PSUM_CHUNK_ELEMS to leave
# proven-clean programs byte-identical.
_PSUM_CHUNK_ELEMS = 8_388_608          # 32 MiB of f32 per collective chunk
_PSUM_CHUNK_THRESHOLD = 33_554_432     # chunk only oversized 1-D payloads

# Fraction of a pipelined segment's wire time the tuner may credit as
# hidden behind the neighbouring segment's compute (stage-1 encode /
# hop decode-accumulate-requant). Deliberately conservative: a 2-segment
# double buffer can at best hide min(compute, wire) of every inner
# boundary, and the hop kernels are far cheaper than the ppermute they
# overlap, so crediting half of the steady-state (P-1)/P overlap keeps
# the projection honest until a measured trace replaces it. ONE constant:
# ``wire_overlap_fraction`` here, the tuner's ``wire_pipeline`` discount
# (tuning/cost.py), and the bench projections all read it.
WIRE_PIPELINE_EFFICIENCY = 0.5


def _pipeline_segments(n: int, pipeline: int) -> list[tuple[int, int]]:
    """Static ``[lo, hi)`` bounds of the ``pipeline`` contiguous segments a
    flat ``n``-element buffer is split into by the double-buffered ring
    schedule. Equal ``ceil(n/P)`` segments (the last may be shorter);
    clamped so no segment is empty — tiny buffers simply pipeline less."""
    p = max(1, min(int(pipeline), n if n else 1))
    per = -(-n // p)
    return [(lo, min(lo + per, n)) for lo in range(0, max(n, 1), per)]


@dataclasses.dataclass(frozen=True)
class _PipelinedView:
    """Decompress-only adapter over P per-segment :class:`_ChunkedView`
    ctxs: each segment's stacked shard payloads decode and reassemble
    independently, then concatenate back into the full leaf — so every
    Memory's ``update`` sees one reconstruction of the whole buffer and
    the error-feedback contract is unchanged by pipelining."""

    inner: Compressor

    def decompress(self, payload: Payload, ctx) -> jax.Array:
        seg_ctxs, n, shape, dtype = ctx
        view = _ChunkedView(self.inner)
        parts = [view.decompress(p, c).reshape(-1)
                 for p, c in zip(payload, seg_ctxs)]
        flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        return flat[:n].reshape(shape).astype(dtype)


def _psum(t: jax.Array, axis_name: str) -> jax.Array:
    """``lax.psum`` with oversized 1-D operands split into chunked psums
    (numerically identical: psum is elementwise)."""
    if t.ndim != 1 or t.shape[0] <= _PSUM_CHUNK_THRESHOLD:
        return lax.psum(t, axis_name)
    n = t.shape[0]
    return jnp.concatenate([
        lax.psum(t[o:min(o + _PSUM_CHUNK_ELEMS, n)], axis_name)
        for o in range(0, n, _PSUM_CHUNK_ELEMS)])


def _psum_majority_vote(payload: Payload, ctx: Ctx, compressor: Compressor,
                        axis_name: str, vote_dtype: str) -> jax.Array:
    """Decompress this rank's ±1 signs, psum, re-sign: exact majority vote
    at fixed (world-size-independent) collective cost — SURVEY.md §7 hard
    part 4. Shared by SignAllreduce and the Allreduce vote routing."""
    w = axis_size(axis_name)           # static at trace time
    bound = vote_exact_max_world(vote_dtype)
    if w > bound:
        raise ValueError(
            f"vote_dtype={vote_dtype!r} is integer-exact only up to world "
            f"size {bound} (comm.vote_exact_max_world: 2^(mantissa+1)); "
            f"this axis has {w} — use vote_dtype='float32'.")
    with trace_stage(STAGE_DECOMPRESS):
        dec = compressor.decompress(payload, ctx)
    with trace_stage(f"{STAGE_EXCHANGE}/psum_vote"):
        summed = _psum(dec.astype(vote_dtype), axis_name)
    out = (summed >= 0).astype(vote_dtype) * 2 - 1
    return out.astype(dec.dtype)


def _algebra(compressor) -> str | None:
    """The codec's declared payload algebra (core.PAYLOAD_ALGEBRAS)."""
    return getattr(compressor, "payload_algebra", None)


def _check_payload_sum_world(compressor: Compressor, world: int,
                             schedule: str) -> None:
    """Runtime twin of the static shared-scale overflow gate: the payload-
    space sum of ``world`` ranks must stay exact in the payload dtype —
    the bound is the codec's OWN ``payload_sum_max_world`` constant (e.g.
    ``iinfo(accum_dtype).max // quantum_num`` for homomorphic QSGD), the
    same function flow pass 6 and the tuner's numeric gate evaluate, so
    the three enforcement points can never disagree (the
    ``vote_exact_max_world`` pattern)."""
    bound = compressor.payload_sum_max_world()
    if bound is not None and world > bound:
        raise ValueError(
            f"{schedule} sums {type(compressor).__name__} payloads across "
            f"{world} ranks but the payload dtype carries exact sums only "
            f"up to world {bound} (payload_sum_max_world: accumulator "
            "iinfo.max // max level) — widen accum_dtype or lower "
            "quantum_num; the numeric_safety pass rejects this statically "
            "from the same constant.")


_MB_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}


def masked_broadcast(x: jax.Array, root, axis_name: str) -> jax.Array:
    """Bit-exact broadcast of rank ``root``'s value over ``axis_name``.

    Realised as an ``lax.axis_index``-masked psum in *integer bit space*:
    the value is reinterpreted as unsigned words, every rank except ``root``
    contributes zeros, and the integer sum reconstructs root's words exactly.
    A float-space masked psum would NOT be bit-exact (``-0.0 + 0.0 == +0.0``
    flips the sign bit, and NaN payloads are not preserved through float
    adds), which matters because the consensus repair path
    (:mod:`grace_tpu.resilience.consensus`) must leave replicas
    *bit-identical* — fingerprints are bit-pattern checksums.

    ``root`` may be a static int or a traced (replicated) scalar. Must be
    called where ``axis_name`` is bound (inside ``shard_map``/``pjit``).

    This integer-bit-space idiom is now *enforced repo-wide*: the static
    auditor's bit-exactness pass (:mod:`grace_tpu.analysis`,
    ``tools/graft_lint.py``) taint-tracks bitcast products through every
    registered config's jaxpr and fails CI on any float-space
    cross-replica reduction over them — re-introducing the PR-3 bug class
    is a lint error, not a code-review catch.
    """
    x = jnp.asarray(x)
    i = lax.axis_index(axis_name)
    is_root = (i == root)
    if x.dtype == jnp.bool_:
        v = x.astype(jnp.uint8)
        out = lax.psum(jnp.where(is_root, v, jnp.zeros_like(v)), axis_name)
        return out != 0
    if jnp.issubdtype(x.dtype, jnp.integer):
        masked = jnp.where(is_root, x, jnp.zeros_like(x))
        return lax.psum(masked, axis_name)
    uint = _MB_UINT[x.dtype.itemsize]
    bits = lax.bitcast_convert_type(x, uint)
    summed = lax.psum(jnp.where(is_root, bits, jnp.zeros_like(bits)),
                      axis_name)
    return lax.bitcast_convert_type(summed, x.dtype)


def masked_broadcast_tree(tree, root, axis_name: str):
    """:func:`masked_broadcast` over every array leaf of a pytree."""
    return jax.tree_util.tree_map(
        lambda l: masked_broadcast(l, root, axis_name), tree)


@dataclasses.dataclass(frozen=True)
class Allreduce(Communicator):
    """Sum payloads across ranks, then decompress once.

    Mirrors grace_dl/dist/communicator/allreduce.py:6-13: all-reduce each
    payload tensor, divide by world size if ``compressor.average``, then
    decompress the summed payload. Valid only for linear codecs — and unlike
    the reference, which merely documents that (IMPLEMENTING.md:43-45) and
    psums e.g. Top-K values belonging to different indices without complaint,
    this enforces ``compressor.summable_payload``. Majority-vote compressors
    (``vote_aggregate=True``: signsgd, signum) are legal here too and are
    routed through the fixed-cost psum vote (:class:`SignAllreduce`
    semantics) — psumming their packed sign *bytes* would be garbage.
    """

    vote_dtype: str = "bfloat16"

    def _recv_total_bytes(self, payload_nbytes: int, n_elems: int,
                          world: int, vote: bool = False) -> int:
        # max(0, W-1): the tuner enumerates degenerate meshes (W=0/1 single
        # rank, no exchange) and a negative byte price would rank them best.
        if vote:
            # psum of dense ±1 votes in bf16 (2 bytes), ring: 2·(W-1)/W·n·2
            return 2 * 2 * n_elems * max(0, world - 1) // max(1, world)
        return 2 * payload_nbytes * max(0, world - 1) // max(1, world)

    def exchange(self, payload: Payload, ctx: Ctx, compressor: Compressor
                 ) -> jax.Array:
        if getattr(compressor, "vote_aggregate", False):
            return _psum_majority_vote(payload, ctx, compressor,
                                       self.axis_name, self.vote_dtype)
        if not getattr(compressor, "summable_payload", False):
            raise TypeError(
                f"Allreduce requires a payload that sums meaningfully across "
                f"ranks; {type(compressor).__name__} does not declare "
                "summable_payload=True (its per-rank payloads decode "
                "differently, e.g. per-rank indices or norms). Use "
                "Allgather/Broadcast instead — reference compatibility "
                "matrix, IMPLEMENTING.md:43-45.")
        homo = _algebra(compressor) in ("shared_scale", "sketch")
        if homo:
            _check_payload_sum_world(compressor, axis_size(self.axis_name),
                                     "Allreduce")
        with trace_stage(f"{STAGE_EXCHANGE}/psum"):
            summed = tuple(_psum(t, self.axis_name) for t in payload)
        if homo:
            # Homomorphic decode: integer level sums / merged sketch
            # tables decode ONCE, and the mean divides the decoded dense
            # tensor (an int payload cannot carry the /W; a sketch's
            # median estimate commutes with positive scaling either way).
            with trace_stage(STAGE_DECOMPRESS):
                out = compressor.decompress(summed, ctx)
            if compressor.average:
                out = out / self.world_size()
            return out
        if compressor.average and payload:
            if not all(jnp.issubdtype(t.dtype, jnp.inexact) for t in summed):
                raise TypeError(
                    "Allreduce with average=True requires float payloads; "
                    f"got {[t.dtype for t in summed]}. Use Allgather for "
                    "integer-coded compressors (see IMPLEMENTING.md:43-45 "
                    "compatibility matrix in the reference).")
            w = self.world_size()
            summed = tuple(t / w for t in summed)
        with trace_stage(STAGE_DECOMPRESS):
            return compressor.decompress(summed, ctx)


@dataclasses.dataclass(frozen=True)
class Allgather(Communicator):
    """Gather every rank's payload, decompress per rank, aggregate.

    Mirrors grace_dl/dist/communicator/allgather.py:7-45. The reference's
    variable-size path (gather sizes → pad → split, lines 16-38) is
    unnecessary: payloads are statically shaped under XLA, with invalid lanes
    zero-valued (see compressors with static-capacity payloads).

    Which decode runs: a compressor that can aggregate its gathered
    payloads without W dense copies says so through
    ``fused_aggregate_decompress(gathered, ctx, world)`` — TopK chunk mode
    sums the ranks in the (rows, k) view and flattens once (staged, W > 1),
    or runs its Pallas kernel when that is enabled. Everything else (and
    any payload the hook declines with None: W = 1, sub-k slices, other
    algorithms) is decoded per rank under ``vmap`` over the gathered world
    axis, then ``aggregate``d and averaged — the reference's Python loop
    (SURVEY.md §3.1 hot spot) as one traced computation. That stacks W
    dense tensors; XLA fuses the stack away for small leaves, but for a
    chunk payload of a million-element leaf it relayouts all W copies in a
    loop (PERF.md §6, PR 27), which is why the hook exists.
    """

    def exchange(self, payload: Payload, ctx: Ctx, compressor: Compressor
                 ) -> jax.Array:
        if not payload:
            # e.g. PowerSGD: communication already happened inside compress.
            with trace_stage(STAGE_DECOMPRESS):
                return compressor.decompress(payload, ctx)
        with trace_stage(f"{STAGE_EXCHANGE}/all_gather"):
            gathered = tuple(
                lax.all_gather(t, self.axis_name, axis=0, tiled=False)
                for t in payload)
        with trace_stage(STAGE_DECOMPRESS):
            fused = getattr(compressor, "fused_aggregate_decompress", None)
            if fused is not None:
                out = fused(gathered, ctx, axis_size(self.axis_name))
                if out is not None:      # handles aggregate + average itself
                    return out
            stacked = jax.vmap(
                lambda p: compressor.decompress(p, ctx))(gathered)
            out = compressor.aggregate(stacked)
            if compressor.average:
                out = out / self.world_size()
            return out


@dataclasses.dataclass(frozen=True)
class Broadcast(Allgather):
    """Parity alias for the reference's broadcast communicator.

    The reference loops over root ranks broadcasting each payload and
    decompressing it (grace_dl/dist/communicator/broadcast.py:18-33) — W
    sequential collectives computing exactly what one all-gather computes.
    On TPU we keep the all-gather realisation; semantics (per-rank decompress
    → aggregate → optional average) are identical.
    """


@dataclasses.dataclass(frozen=True)
class SignAllreduce(Communicator):
    """Majority vote via psum instead of allgather (SURVEY.md §7 hard part 4).

    Decompress this rank's payload to ±1, ``psum`` over the axis, re-sign —
    mathematically identical to Allgather + the sign compressors' majority-
    vote ``aggregate`` (sum of ±1 then sign), but the collective is a fixed-
    cost all-reduce instead of a world-size-proportional gather. Wire math
    per rank: allgather of packed signs receives (W-1)·n/8 bytes; an XLA
    ring all-reduce of ±1 in bf16 moves ~2·(2n) bytes regardless of W — so
    allgather wins on small meshes (W ≲ 32) and SignAllreduce wins on pod
    slices beyond that. Same decision the reference could not express: its
    allgather was the only variable-size-safe collective (IMPLEMENTING.md:
    43-45); here both sides are static-shaped, so the choice is free.

    Only valid for compressors whose decompressed tensors are exactly the
    vote inputs and whose aggregate is the majority vote (signsgd, signum).
    ``vote_dtype='bfloat16'`` is integer-exact for vote sums up to |W|=256;
    pick ``'float32'`` on larger meshes.
    """

    vote_dtype: str = "bfloat16"

    def _recv_total_bytes(self, payload_nbytes: int, n_elems: int,
                          world: int, vote: bool = False) -> int:
        return 2 * 2 * n_elems * max(0, world - 1) // max(1, world)

    def exchange(self, payload: Payload, ctx: Ctx, compressor: Compressor
                 ) -> jax.Array:
        if not getattr(compressor, "vote_aggregate", False):
            raise TypeError(
                "SignAllreduce implements majority-vote aggregation; "
                f"{type(compressor).__name__} does not declare "
                "vote_aggregate=True (its aggregate carries scaling the "
                "re-sign would drop) — use Allreduce/Allgather instead.")
        return _psum_majority_vote(payload, ctx, compressor,
                                   self.axis_name, self.vote_dtype)


def _split_ctx(ctx):
    """Partition a ctx pytree into (treedef, [leaf|None static], [arrays])."""
    leaves, treedef = jax.tree_util.tree_flatten(ctx)
    is_arr = [isinstance(l, (jax.Array, jnp.ndarray)) for l in leaves]
    static = [None if a else l for a, l in zip(is_arr, leaves)]
    arrays = [l for a, l in zip(is_arr, leaves) if a]
    return treedef, static, arrays


def _join_ctx(treedef, static, arrays):
    arrays = iter(arrays)
    leaves = [next(arrays) if s is None else s for s in static]
    return jax.tree_util.tree_unflatten(treedef, leaves)


@functools.lru_cache(maxsize=256)
def ctx_is_data_free(compressor: Compressor, n: int, dtype) -> bool:
    """True iff no ctx array leaf of ``compressor.compress`` depends on the
    *data* (rng-derived and constant leaves are fine). Cached per
    (compressor, n, dtype) — compressors are frozen dataclasses, so the
    answer is a pure config property and the extra compress trace is paid
    once, not per leaf per jit trace.

    TwoShotAllreduce decodes every rank's gathered stage-2 chunk with the
    rank-local ctx2 from compressing this rank's own (rank-divergent)
    aggregate. That is only sound when ctx array leaves are functions of
    shape and the shared rng alone — a codec that stashes e.g. its input's
    norm in ctx would silently corrupt every other rank's chunk. Checked
    structurally: trace ``compress`` to a jaxpr and taint-walk from the data
    input; conservative for opaque sub-calls (pjit/scan/cond propagate taint
    through all outputs), so a false *positive* is possible but a silent
    false negative is not.
    """
    def ctx_arrays(x, key):
        _, ctx, _ = compressor.compress(x, None, key)
        _, _, arrays = _split_ctx(ctx)
        return tuple(arrays)

    from jax.extend.core import Var

    closed = jax.make_jaxpr(ctx_arrays)(
        jax.ShapeDtypeStruct((n,), dtype),
        jax.eval_shape(lambda: jax.random.key(0)))
    jaxpr = closed.jaxpr
    tainted = {jaxpr.invars[0]}
    for eqn in jaxpr.eqns:
        if any(isinstance(v, Var) and v in tainted for v in eqn.invars):
            tainted.update(eqn.outvars)
    return not any(isinstance(v, Var) and v in tainted
                   for v in jaxpr.outvars)


@dataclasses.dataclass(frozen=True)
class _ChunkedView:
    """Decompress-only adapter: (w, …) stacked chunk payloads → full leaf.

    Lets every Memory's ``update`` (which only ever calls
    ``compressor.decompress``) compute the stage-1 residual/keep-mask of the
    two-shot pipeline without knowing about chunking. With stage-2 feedback,
    the owner's re-compression error is subtracted at the owned chunk so a
    residual-style memory (``compensated − decompress``) accumulates it."""

    inner: Compressor

    def decompress(self, payload: Payload, ctx) -> jax.Array:
        treedef, static, arr_stack, n, shape, dtype, stage2 = ctx

        def dec(p, arrs):
            return self.inner.decompress(p, _join_ctx(treedef, static, arrs))

        chunks = jax.vmap(dec)(payload, arr_stack)      # (w, m)
        flat = chunks.reshape(-1)
        if stage2 is not None:
            e2, start = stage2                          # own-chunk error (m,)
            flat = lax.dynamic_update_slice(
                flat, lax.dynamic_slice(flat, (start,), e2.shape)
                - e2.astype(flat.dtype), (start,))
        return flat[:n].reshape(shape).astype(dtype)


def _shard_compress(compressor: Compressor, chunks: jax.Array,
                    rng: jax.Array, comm_name: str, shared=None):
    """Stage-1 shard encode shared by the shard-parallel communicators
    (TwoShotAllreduce, RingAllreduce): probe one shard to pin the
    (shard-uniform) static ctx structure, then vmap ``compress`` over the
    ``(w, m)`` shard stack under shard-folded shared keys. Validates the
    shared soundness conditions — a wire payload must exist to shard, and
    ctx arrays must be data-free so every rank's locally derived ctx for
    shard ``c`` equals the one the sender compressed with (the condition
    that lets ranks decode each other's shard payloads without shipping
    ctx). ``shared`` is the hoisted shared-scale negotiation result
    (``payload_algebra == 'shared_scale'``): when present, every shard
    encodes against it and the data-free-ctx gate is replaced by the
    stronger collective-replication argument — the scale came out of a
    full-axis pmax, so the ctx it seeds is rank-identical by construction
    even though it is data-derived. Returns ``(payloads, ctx_arrays,
    treedef, static)`` with payloads and ctx arrays stacked along the
    shard axis."""
    w = chunks.shape[0]
    refuse = getattr(compressor, "refuse_per_shard_compress", None)
    if refuse is not None:
        refuse(comm_name)

    def enc(chunk, key):
        if shared is None:
            return compressor.compress(chunk, None, key)
        return compressor.compress(chunk, None, key, shared=shared)

    probe_payload, probe_ctx, _ = enc(chunks[0], jax.random.fold_in(rng, 0))
    if not probe_payload:
        raise TypeError(
            f"{comm_name} needs a wire payload to scatter; "
            f"{type(compressor).__name__} communicates inside compress "
            "— use Allreduce instead.")
    if shared is None and not ctx_is_data_free(compressor, chunks.shape[1],
                                               chunks.dtype):
        raise TypeError(
            f"{comm_name} requires a data-free ctx; "
            f"{type(compressor).__name__}.compress puts data-derived "
            "arrays in ctx, and ranks decode each other's shard payloads "
            "with locally derived ctx (identical across ranks only when "
            "ctx arrays are functions of shape and the shared rng alone) "
            "— other ranks' shards would decode against the wrong values. "
            "Keep data-derived arrays in the payload (they travel on the "
            "wire) or use Allgather/Allreduce.")
    treedef, static, _ = _split_ctx(probe_ctx)

    def comp_one(chunk, c):
        payload, ctx, _ = enc(chunk, jax.random.fold_in(rng, c))
        _, _, arrays = _split_ctx(ctx)
        return tuple(payload), tuple(arrays)

    payloads, ctx_arrays = jax.vmap(comp_one)(chunks, jnp.arange(w))
    return payloads, ctx_arrays, treedef, static


def _gathered_aggregate(base: Compressor, codec: Compressor, stacked,
                        ctx, k: int) -> jax.Array:
    """Aggregate ``k`` gathered wire payloads (leading axis ``k`` on every
    leaf) that share one data-free ``ctx`` — the requant boundaries'
    decode-and-reduce, shared by ReduceScatterAllreduce's owned chunk and
    HierarchicalAllreduce's slice/region boundaries. When ``codec``
    overrides :meth:`Compressor.decode_accumulate` (the wire-path codecs:
    qsgd/signsgd) the decode and the accumulate run as ONE fused pass —
    the payloads never materialise densely — and the singleton
    ``aggregate`` re-signs vote tallies exactly like the ring's final
    hop; otherwise the staged vmap-decompress + aggregate spelling runs
    unchanged. ``base`` supplies the aggregation semantics (sum or
    majority vote) even when a distinct WAN ``codec`` did the encode.

    The fused spelling engages only when the codec's wire kernels are
    LIVE (``codec.wire_fused()``): the K-way fused pass accumulates
    sequentially while the staged ``aggregate`` reduces with ``jnp.sum``,
    and float adds are not associative — with the kernel disabled the
    committed staged spelling must keep running bit-for-bit."""
    if (codec.wire_fused()
            and type(codec).decode_accumulate
            is not Compressor.decode_accumulate):
        parts = tuple(tuple(t[j] for t in stacked) for j in range(k))
        partial = codec.decode_accumulate(parts, (ctx,) * k)
        return base.aggregate(partial[None])
    decoded = jax.vmap(lambda p: codec.decompress(p, ctx))(stacked)
    return base.aggregate(decoded)


@dataclasses.dataclass(frozen=True)
class TwoShotAllreduce(Communicator):
    """Scatter–reduce–(re)compress all-reduce: O(k) wire per rank.

    The reference's only general communicator, allgather, costs every rank
    (W−1)·k received payload bytes — linear in world size
    (grace_dl/dist/communicator/allgather.py:7-45). The standard fix in the
    compression literature (ScaleCom's scatter-reduce, arXiv:2104.11125;
    DynamiQ's multi-hop compressed all-reduce, arXiv:2602.08923; EQuARX's
    quantized XLA all-reduce, arXiv:2506.17615) is a two-shot scheme, which
    XLA collectives express directly inside shard_map:

    1. split the compensated gradient into W equal chunks; compress each
       with a chunk-folded shared rng;
    2. ``all_to_all`` the stacked chunk payloads — rank i receives every
       rank's payload for chunk i (wire ≈ k);
    3. decompress + ``aggregate`` (sum / majority vote) the owned chunk,
       divide by W if ``compressor.average``;
    4. re-compress the aggregated chunk (shared stage-2 rng) and
       ``all_gather`` it (wire ≈ k); every rank decodes all W chunk
       aggregates and concatenates.

    Total ≈ 2k per rank vs allgather's (W−1)k: break-even at W=3, ~4× at
    W=8, ~100× on a 256-chip pod. Cost: the aggregate is compressed once
    more (stage-2 loss, not covered by error feedback — ScaleCom §III
    discusses why this is benign for mean-like aggregates), and selection
    codecs select per chunk rather than globally (same trade as
    ``topk_algorithm='chunk'``).

    Works with any *stateless* codec (stateful ones — signum momentum,
    powersgd Q — hold full-tensor state that has no per-chunk meaning and
    are rejected; powersgd's in-compress psum makes two-shot moot anyway).
    All memories compose: ``update`` sees a stage-1 reconstruction via
    :class:`_ChunkedView`.

    ``stage2_feedback=True`` (ScaleCom's chunk-owner error feedback,
    arXiv:2104.11125 §III) additionally folds each owner's stage-2
    re-compression error into its residual at the owned chunk, so a
    residual-style memory corrects it on later steps — each chunk has a
    fixed owner, so the whole stage-2 error is covered exactly once across
    ranks. Requires a memory whose update is ``compensated − decompress``
    (Residual/EFSignSGD/PowerSGD-style); DgcMemory interprets nonzero
    decompressed lanes as "transmitted" and would wrongly clear its
    accumulators over the whole owned chunk, so it is rejected.
    """

    stage2_feedback: bool = False
    shard_parallel = True

    def _recv_total_bytes(self, payload_nbytes: int, n_elems: int,
                          world: int, vote: bool = False) -> int:
        # stage-1 all_to_all + stage-2 all_gather, each ~payload_b·(W-1)/W
        return 2 * payload_nbytes * max(0, world - 1) // max(1, world)

    def step(self, x: jax.Array, mem_state, comp_state,
             memory, compressor: Compressor, rng: jax.Array):
        if comp_state is not None:
            raise TypeError(
                f"TwoShotAllreduce requires a stateless compressor; "
                f"{type(compressor).__name__} carries cross-step state "
                "(init_state != None) that has no per-chunk meaning — use "
                "Allgather/Allreduce instead.")
        shape, dtype = x.shape, x.dtype
        compensated, mem_state = memory.compensate(x, mem_state)
        flat = compensated.reshape(-1)
        n = flat.size
        w, _, pad = self.shard_spec(n)              # static at trace time
        chunks = jnp.pad(flat, (0, pad)).reshape(w, -1)

        # Stage 1: per-chunk compress under a chunk-folded shared key
        # (shared shard plumbing; the data-free-ctx gate is what makes
        # stage 3's decode of every rank's gathered chunk with the
        # rank-local ctx2 — built from this rank's own divergent
        # aggregate — sound).
        with trace_stage(f"{STAGE_EXCHANGE}/twoshot_stage1_compress"):
            payloads, ctx_arrays, treedef, static = _shard_compress(
                compressor, chunks, rng, "TwoShotAllreduce")

        if self.stage2_feedback:
            from grace_tpu.memories import DgcMemory
            if isinstance(memory, DgcMemory):
                raise TypeError(
                    "TwoShotAllreduce(stage2_feedback=True) is incompatible "
                    "with DgcMemory: its keep-mask reads decompress()==0 and "
                    "the injected stage-2 error would clear the accumulators "
                    "across the whole owned chunk. Use ResidualMemory or "
                    "disable stage2_feedback.")

        # Stage 2: swap chunk axis for world axis; aggregate the owned chunk.
        i = lax.axis_index(self.axis_name)
        with trace_stage(f"{STAGE_EXCHANGE}/twoshot_all_to_all"):
            mine = tuple(lax.all_to_all(p, self.axis_name, 0, 0)
                         for p in payloads)
        my_ctx = _join_ctx(treedef, static,
                           [jnp.take(a, i, axis=0) for a in ctx_arrays])
        stacked = jax.vmap(lambda p: compressor.decompress(p, my_ctx))(mine)
        agg = compressor.aggregate(stacked)
        if compressor.average:
            agg = agg / w

        # Stage 3: re-compress the aggregate (shared stage-2 key: ctx must
        # be chunk-index-independent so every rank can decode every chunk),
        # all-gather, decode, reassemble.
        agg = agg.astype(chunks.dtype)
        payload2, ctx2, _ = compressor.compress(
            agg, None, jax.random.fold_in(rng, w))

        stage2 = None
        if self.stage2_feedback:
            e2 = agg - compressor.decompress(payload2, ctx2)
            # A mean-aggregate dilutes a single owner's correction by 1/W;
            # pre-scale so the error is repaid exactly once across ranks.
            if compressor.average:
                e2 = e2 * w
            stage2 = (e2, i * chunks.shape[1])
        view_ctx = (treedef, static, ctx_arrays, n, shape, dtype, stage2)
        mem_state = memory.update(compensated, payloads, view_ctx,
                                  _ChunkedView(compressor), mem_state)

        with trace_stage(f"{STAGE_EXCHANGE}/twoshot_all_gather"):
            gathered = tuple(
                lax.all_gather(p, self.axis_name, axis=0, tiled=False)
                for p in payload2)
        with trace_stage(STAGE_DECOMPRESS):
            out = jax.vmap(
                lambda p: compressor.decompress(p, ctx2))(gathered)
        out = out.reshape(-1)[:n].reshape(shape).astype(dtype)
        return out, mem_state, comp_state

    def exchange(self, payload: Payload, ctx: Ctx, compressor: Compressor
                 ) -> jax.Array:
        raise TypeError("TwoShotAllreduce re-chunks the gradient before "
                        "compression; it only supports the full step() "
                        "pipeline, not a bare exchange().")


@dataclasses.dataclass(frozen=True)
class RingAllreduce(Communicator):
    """Hop-pipelined compressed ring all-reduce: O(k) wire per rank.

    The classic ring decomposition (reduce-scatter around the ring, then
    all-gather the reduced shards) with the payload kept **compressed on
    every hop** — the regime EQuARX (quantized allreduce decomposed inside
    XLA, arXiv:2506.17615) and DynamiQ (compressed multi-hop all-reduce,
    arXiv:2602.08923) target. Expressed with ``lax.ppermute`` over the mesh
    axis so XLA schedules the W−1 neighbor exchanges on ICI:

    1. split the compensated gradient into W equal shards
       (``Communicator.shard_spec``); compress each with a shard-folded
       shared key (the stage-1 encode shared with ``TwoShotAllreduce`` —
       error-feedback memories see exactly this reconstruction);
    2. **reduce-scatter**, W−1 hops: at hop s rank i sends the running
       partial of shard (i−1−s) mod W to rank i+1 and receives shard
       (i−2−s) mod W from rank i−1; each hop decompresses the received
       payload, accumulates its own stage-1 contribution for that shard,
       and — on the requant path — re-compresses the partial for the next
       hop. After the last hop rank i holds the full reduction of shard i;
    3. **all-gather** the W reduced shards, still in wire format; every
       rank decodes all W and reassembles.

    Wire per rank ≈ 2·(W−1)/W·k received (like two-shot) vs allgather's
    (W−1)·k, and the aggregation work is spread around the ring instead of
    replicated on every rank (allgather) or concentrated on the shard owner
    (two-shot). Three accumulation paths, gated on the compressor's
    declared ``payload_algebra`` — the compatibility matrix is *enforced*,
    not documented:

    * **exact path** (``payload_algebra='exact'``: none, fp16/bf16,
      randomk) — the codec is linear, so hops add wire words directly
      (payload-space accumulation). No requant round-trip, no per-hop loss
      beyond the accumulation dtype; phase 2 gathers the summed payloads
      themselves.
    * **homomorphic path** (``payload_algebra='shared_scale'`` — homoqsgd,
      or ``'sketch'`` — countsketch): same zero-requant hop adds, but the
      scale negotiation is hoisted before stage 1 (one pmax; ctx becomes
      rank-identical by collective replication rather than data-freeness),
      the integer/sketch sums are bounded by the codec's
      ``payload_sum_max_world`` (runtime gate here, static twin in flow
      pass 6), and the mean divides AFTER the single final decode. ONE
      decode for the whole schedule, zero requant regardless of W — the
      THC regime that kills the tuner's ``MAX_REQUANT_CHAIN`` degradation.
    * **requant path** (``supports_hop_requant=True``: topk, qsgd, signsgd)
      — decompress → accumulate → requantize at each hop with a shared hop
      key (data-free ctx lets the receiver derive the sender's ctx
      locally). Each intermediate requant adds one codec error that is NOT
      covered by error feedback (the memory covers only the stage-1 encode,
      like two-shot's stage-2 loss) — W−2 intermediate hops + the final
      shard encode, so the requant error grows ~linearly in W. For
      vote codecs (signsgd) the hop requant re-signs the running partial —
      a *cascaded* vote whose result can differ from the one-shot majority
      on split coordinates (unanimous coordinates are preserved exactly).

    Works with any *stateless* codec (same gate as two-shot; powersgd
    communicates inside compress and is rejected at the wire-payload
    check). ``average`` divides the owned shard by W before the gather.
    Per-hop spans are named under ``STAGE_RING_HOP`` in device traces.
    The hop loop is unrolled at trace time (W−1 ppermutes of statically
    shaped payloads) — compile cost grows with W, the trade XLA's static
    ring collectives make themselves.

    **Double-buffered wire pipeline** (``pipeline=P > 1``): the flat
    buffer splits into P contiguous segments and each segment runs the
    WHOLE schedule above under its own ``grace/pipeline/<p>`` scope and
    rng fold — P independent collective chains, so XLA can overlap
    segment p's ppermute hops with segment p±1's encode/decode compute
    (the classic double buffer at P=2). Pure schedule restructuring:
    per-segment error feedback reassembles to the full buffer
    (:class:`_PipelinedView`), the static overlap auditor (flow pass 5)
    counts the chains, and the tuner credits
    ``wire_overlap_fraction`` = ``WIRE_PIPELINE_EFFICIENCY·(P−1)/P`` of
    the wire bill. ``pipeline=1`` is the committed single-chain schedule
    bit-for-bit. Segmentation DOES change the stochastic encodes (each
    segment folds its own keys), so a pipelined config is a different —
    equally valid — draw of the same estimator, not a bit-twin of its
    serial sibling.
    """

    pipeline: int = 1
    shard_parallel = True

    def __post_init__(self):
        if self.pipeline < 1:
            raise ValueError(
                f"RingAllreduce pipeline must be >= 1; got {self.pipeline} "
                "— it is the number of double-buffered buffer segments, "
                "each running the full hop schedule.")

    def wire_overlap_fraction(self) -> float:
        p = self.pipeline
        if p <= 1:
            return 0.0
        return WIRE_PIPELINE_EFFICIENCY * (p - 1) / p

    def _recv_total_bytes(self, payload_nbytes: int, n_elems: int,
                          world: int, vote: bool = False) -> int:
        # (W-1) reduce-scatter hop payloads + (W-1) gathered shard
        # payloads, each ~payload/W: ≈ 2·payload·(W-1)/W, flat in W.
        # Pipeline-invariant: P segments each move the same formula over
        # 1/P of the buffer; per-segment shard padding adds at most
        # P·(W-1) extra elements — inside the wire-reconciliation
        # tolerance, so the scalar model stays the serial one.
        return 2 * payload_nbytes * max(0, world - 1) // max(1, world)

    def step(self, x: jax.Array, mem_state, comp_state,
             memory, compressor: Compressor, rng: jax.Array):
        if comp_state is not None:
            raise TypeError(
                f"RingAllreduce requires a stateless compressor; "
                f"{type(compressor).__name__} carries cross-step state "
                "(init_state != None) that has no per-shard meaning — use "
                "Allgather/Allreduce instead.")
        algebra = _algebra(compressor)
        homo = algebra in ("shared_scale", "sketch")
        exact = bool(getattr(compressor, "summable_payload", False))
        requant = bool(getattr(compressor, "supports_hop_requant", False))
        if not (exact or requant):
            raise TypeError(
                f"RingAllreduce keeps the payload compressed on every hop, "
                "which needs a payload algebra (exact: none/fp16/randomk; "
                "shared_scale: homoqsgd; sketch: countsketch — all give "
                "exact payload-space accumulation) or an opt-in to per-hop "
                "requantization (supports_hop_requant=True: "
                "topk/qsgd/signsgd); "
                f"{type(compressor).__name__} declares neither — its "
                "payload carries structure a partial sum destroys. Use "
                "Allgather (general-purpose) or TwoShotAllreduce instead.")
        shape, dtype = x.shape, x.dtype
        compensated, mem_state = memory.compensate(x, mem_state)
        flat = compensated.reshape(-1)
        n = flat.size
        if homo:
            _check_payload_sum_world(compressor, axis_size(self.axis_name),
                                     "RingAllreduce")

        # Shared-scale negotiation, hoisted before stage 1 over the WHOLE
        # buffer (one per-bucket scale, not per shard or per pipeline
        # segment): every shard then encodes against the identical
        # replicated scale, so hop sums are exact and error feedback
        # covers this single encode.
        shared = None
        if algebra == "shared_scale":
            with trace_stage(f"{STAGE_EXCHANGE}/negotiate_scale"):
                shared = compressor.negotiate(flat, self.axis_name,
                                              rng=rng)

        segs = _pipeline_segments(n, self.pipeline)
        if len(segs) == 1:
            out, payloads, ctx_arrays, treedef, static = \
                self._segment_schedule(flat, compressor, rng, shared,
                                       homo, exact)
            # Error feedback covers the stage-1 encode exactly (the hop
            # requant losses are downstream of it, like two-shot's
            # stage-2 loss).
            view_ctx = (treedef, static, ctx_arrays, n, shape, dtype, None)
            mem_state = memory.update(compensated, payloads, view_ctx,
                                      _ChunkedView(compressor), mem_state)
        else:
            # Double-buffered schedule: every contiguous segment runs the
            # WHOLE ring under its own pipeline scope and rng fold — P
            # independent collective chains XLA can interleave, so
            # segment p's ppermutes hide behind segment p±1's
            # encode/decode compute. Error feedback still covers the
            # full-buffer stage-1 encode: the per-segment reconstructions
            # concatenate through _PipelinedView.
            outs, seg_pay, seg_ctx = [], [], []
            for p, (lo, hi) in enumerate(segs):
                with trace_stage(f"{STAGE_PIPELINE}/{p}"):
                    o, pay, arrs, treedef, static = \
                        self._segment_schedule(
                            flat[lo:hi], compressor,
                            jax.random.fold_in(rng, p), shared, homo,
                            exact)
                outs.append(o)
                seg_pay.append(pay)
                seg_ctx.append((treedef, static, arrs, hi - lo,
                                (hi - lo,), flat.dtype, None))
            out = jnp.concatenate(outs)
            view_ctx = (tuple(seg_ctx), n, shape, dtype)
            mem_state = memory.update(compensated, tuple(seg_pay),
                                      view_ctx, _PipelinedView(compressor),
                                      mem_state)
        out = out[:n].reshape(shape).astype(dtype)
        return out, mem_state, comp_state

    def _segment_schedule(self, flat, compressor: Compressor,
                          rng: jax.Array, shared, homo: bool, exact: bool):
        """One full ring schedule over one contiguous flat segment — the
        stage-1 shard encode, the W−1 hops, the gather and the decode,
        shared verbatim by the single-segment run (``pipeline=1``: the
        committed path bit-for-bit) and the pipelined segments. Returns
        ``(decoded flat segment, stage-1 payloads, ctx arrays, treedef,
        static)`` so the caller wires error feedback."""
        n = flat.shape[0]
        w, _, pad = self.shard_spec(n)              # static at trace time
        chunks = jnp.pad(flat, (0, pad)).reshape(w, -1)

        with trace_stage(f"{STAGE_EXCHANGE}/ring_stage1_compress"):
            payloads, ctx_arrays, treedef, static = _shard_compress(
                compressor, chunks, rng, "RingAllreduce", shared=shared)

        i = lax.axis_index(self.axis_name)
        perm = [(j, (j + 1) % w) for j in range(w)]

        def take_payload(stack, c):
            return tuple(jnp.take(t, c, axis=0) for t in stack)

        def shard_ctx(c):
            return _join_ctx(treedef, static,
                             [jnp.take(a, c, axis=0) for a in ctx_arrays])

        if exact:
            # Payload-space accumulation: decode-the-sum == sum-the-decodes
            # (the Allreduce linearity condition), so the wire format IS
            # the accumulator and phase 2 needs no re-encode. The same
            # hops serve all three algebras — homomorphic (shared_scale /
            # sketch) payloads add exactly as integers/merged tables, with
            # ZERO requant at any hop regardless of W.
            send = take_payload(payloads, (i - 1) % w)
            for s in range(w - 1):
                with trace_stage(f"{STAGE_RING_HOP}/{s}"):
                    recv = tuple(lax.ppermute(t, self.axis_name, perm)
                                 for t in send)
                    own = take_payload(payloads, (i - 2 - s) % w)
                    # payload_add is the codec's payload-space add —
                    # elementwise for plain wire words (the committed
                    # spelling bit-for-bit), a packed-field add (fused
                    # Pallas accumulate) for sub-byte homomorphic
                    # payloads that a byte-wise ``+`` would corrupt.
                    send = compressor.payload_add(recv, own)
            owned = send                 # wire-format reduction of shard i
            if compressor.average and not homo:
                if not all(jnp.issubdtype(t.dtype, jnp.inexact)
                           for t in owned):
                    raise TypeError(
                        "RingAllreduce with average=True requires float "
                        f"payloads; got {[t.dtype for t in owned]} — "
                        "integer-coded payloads cannot carry the mean "
                        "(reference compatibility matrix, "
                        "IMPLEMENTING.md:43-45; shared_scale/sketch "
                        "algebras divide after the final decode instead).")
                owned = tuple(t / w for t in owned)
            with trace_stage(f"{STAGE_EXCHANGE}/ring_all_gather"):
                gathered = tuple(
                    lax.all_gather(t, self.axis_name, axis=0, tiled=False)
                    for t in owned)
            with trace_stage(STAGE_DECOMPRESS):
                # gathered[j] is rank j's owned shard == shard j, so the
                # stacked stage-1 ctx arrays align by construction.
                def dec(p, arrs):
                    return compressor.decompress(
                        p, _join_ctx(treedef, static, list(arrs)))

                out = jax.vmap(dec)(gathered, ctx_arrays)
            if homo and compressor.average:
                # The ONE decode already happened; an int-level/sketch
                # payload cannot carry /W, so the mean lands on the dense
                # result — bit-equal placement to the escape psum's /W.
                out = out / w
        else:
            hop_ctx = None
            send = take_payload(payloads, (i - 1) % w)
            partial = None
            for s in range(w - 1):
                with trace_stage(f"{STAGE_RING_HOP}/{s}"):
                    recv = tuple(lax.ppermute(t, self.axis_name, perm)
                                 for t in send)
                    rc = (i - 2 - s) % w
                    # Hop 0 arrives in stage-1 format (per-shard keys);
                    # later hops in the previous hop's requant format. The
                    # receiver's own compress at the same shared key
                    # produced identical (data-free) ctx arrays, so the
                    # local hop_ctx decodes the neighbor's payload.
                    rctx = shard_ctx(rc) if s == 0 else hop_ctx
                    # decode_accumulate defaults to the committed
                    # sequential decompress-and-add spelling; wire-path
                    # codecs (qsgd/signsgd) override it with ONE fused
                    # Pallas decode→accumulate pass, bit-identical by the
                    # tests' contract.
                    partial = compressor.decode_accumulate(
                        (recv, take_payload(payloads, rc)),
                        (rctx, shard_ctx(rc)))
                    if s < w - 2:
                        pay, hop_ctx, _ = compressor.compress(
                            partial, None,
                            jax.random.fold_in(rng, w + 1 + s))
                        send = tuple(pay)
            if partial is None:                     # w == 1: nothing moved
                partial = compressor.decompress(take_payload(payloads, 0),
                                                shard_ctx(0))
            # Singleton stack: sum codecs pass through, vote codecs re-sign
            # the final tally — the one place the aggregate differs.
            owned = compressor.aggregate(partial[None])
            if compressor.average:
                owned = owned / w
            # Phase 2: one final shard encode under a shared key, gather
            # still in wire format, decode all W shards locally.
            payload2, ctx2, _ = compressor.compress(
                owned.astype(chunks.dtype), None, jax.random.fold_in(rng, w))
            with trace_stage(f"{STAGE_EXCHANGE}/ring_all_gather"):
                gathered = tuple(
                    lax.all_gather(t, self.axis_name, axis=0, tiled=False)
                    for t in payload2)
            with trace_stage(STAGE_DECOMPRESS):
                out = jax.vmap(
                    lambda p: compressor.decompress(p, ctx2))(gathered)
        return out.reshape(-1)[:n], payloads, ctx_arrays, treedef, static

    def exchange(self, payload: Payload, ctx: Ctx, compressor: Compressor
                 ) -> jax.Array:
        raise TypeError("RingAllreduce re-shards the gradient before "
                        "compression; it only supports the full step() "
                        "pipeline, not a bare exchange().")


@dataclasses.dataclass(frozen=True)
class ReduceScatterAllreduce(Communicator):
    """One-shot compressed reduce-scatter + all-gather: the FSDP exchange.

    The sharded-model track's collective (``communicator: "rscatter"``):
    on a dp×fsdp mesh each device's gradient is already its fsdp shard's,
    and the reduce to compress is the **per-shard reduce-scatter over the
    dp axis**. This schedule expresses it as ONE ``all_to_all`` (the
    reduce-scatter's data movement) plus one ``all_gather``, instead of
    the ring's W−1 pipelined hops:

    1. split the compensated (per-shard) gradient into W equal chunks
       (``Communicator.shard_spec``); stage-1 encode shared with
       Ring/TwoShot via ``_shard_compress`` — error feedback covers it
       exactly, so residuals stay on the shard owner;
    2. ``all_to_all`` the stacked chunk payloads: rank i receives every
       dp peer's payload for chunk i (wire ≈ payload·(W−1)/W);
    3. reduce the owned chunk — this is where the PR-13 payload algebra
       pays off, with accumulation paths gated exactly like Ring's:

       * **exact / homomorphic path** (``summable_payload``: none, fp16,
         randomk; ``shared_scale``: homoqsgd — negotiation hoisted before
         stage 1, sum bounded by ``payload_sum_max_world``; ``sketch``:
         countsketch) — the W received payloads are summed **in payload
         space** and the summed wire words themselves are gathered in
         step 4. ZERO re-encode anywhere: unlike the ring (which also
         sums in payload space but pays W−1 hop latencies) and unlike
         TwoShot (which re-compresses the aggregate even for linear
         codecs), this path is bit-identical to the one-shot
         decode-of-the-sum at one collective's latency;
       * **single-requant path** (``supports_hop_requant=True``: topk,
         qsgd, signsgd) — decompress all W chunk payloads, ``aggregate``
         (sum, or a true one-shot majority vote for sign codecs — not
         the ring's cascaded vote), re-encode ONCE under a shared key.
         Exactly one requant boundary regardless of W — the flat ring
         pays W−2 intermediate requants, which is the ScaleCom
         degradation cliff the tuner's ``MAX_REQUANT_CHAIN`` gate
         rejects at pod scale; this schedule's requant chain is 1 at
         any W.

    4. ``all_gather`` the reduced shards, still in wire format; decode
       all W locally and reassemble.

    Wire per rank ≈ 2·payload·(W−1)/W received — same bytes as
    Ring/TwoShot, priced through the shared per-link model (a flat
    schedule: all-ICI within one slice, honestly all-DCN beyond it; pair
    with ``HierarchicalAllreduce`` when the dp axis crosses slices).
    Same enforced gates as Ring: stateless codec, wire payload, data-free
    ctx (or a hoisted negotiation), and ``summable_payload`` or
    ``supports_hop_requant``.
    """

    shard_parallel = True

    def _recv_total_bytes(self, payload_nbytes: int, n_elems: int,
                          world: int, vote: bool = False) -> int:
        # all_to_all receives (W-1)/W of the stacked stage-1 payloads +
        # all_gather receives (W-1) reduced shards of ~payload/W each.
        return 2 * payload_nbytes * max(0, world - 1) // max(1, world)

    def step(self, x: jax.Array, mem_state, comp_state,
             memory, compressor: Compressor, rng: jax.Array):
        if comp_state is not None:
            raise TypeError(
                f"ReduceScatterAllreduce requires a stateless compressor; "
                f"{type(compressor).__name__} carries cross-step state "
                "(init_state != None) that has no per-shard meaning — use "
                "Allgather/Allreduce instead.")
        algebra = _algebra(compressor)
        homo = algebra in ("shared_scale", "sketch")
        exact = bool(getattr(compressor, "summable_payload", False))
        requant = bool(getattr(compressor, "supports_hop_requant", False))
        if not (exact or requant):
            raise TypeError(
                f"ReduceScatterAllreduce sums or re-aggregates chunk "
                "payloads after the all_to_all, which needs a payload "
                "algebra (exact: none/fp16/randomk; shared_scale: "
                "homoqsgd; sketch: countsketch — exact payload-space "
                "summation at the owned chunk) or an opt-in to "
                "re-encoding the aggregate once "
                "(supports_hop_requant=True: topk/qsgd/signsgd); "
                f"{type(compressor).__name__} declares neither — its "
                "payload carries structure a partial sum destroys. Use "
                "Allgather (general-purpose) instead.")
        shape, dtype = x.shape, x.dtype
        compensated, mem_state = memory.compensate(x, mem_state)
        flat = compensated.reshape(-1)
        n = flat.size
        w, _, pad = self.shard_spec(n)              # static at trace time
        if homo:
            _check_payload_sum_world(compressor, w,
                                     "ReduceScatterAllreduce")
        chunks = jnp.pad(flat, (0, pad)).reshape(w, -1)

        # Shared-scale negotiation hoisted over the WHOLE buffer before
        # stage 1 (one pmax; every shard encodes against the identical
        # replicated scale), exactly as Ring/Hier do.
        shared = None
        if algebra == "shared_scale":
            with trace_stage(f"{STAGE_EXCHANGE}/negotiate_scale"):
                shared = compressor.negotiate(flat, self.axis_name,
                                              rng=rng)

        with trace_stage(f"{STAGE_EXCHANGE}/rscatter_stage1_compress"):
            payloads, ctx_arrays, treedef, static = _shard_compress(
                compressor, chunks, rng, "ReduceScatterAllreduce",
                shared=shared)

        # Error feedback covers the stage-1 shard encode exactly; the
        # single requant boundary (requant path only) is downstream of it
        # — the same contract as Ring/TwoShot.
        view_ctx = (treedef, static, ctx_arrays, n, shape, dtype, None)
        mem_state = memory.update(compensated, payloads, view_ctx,
                                  _ChunkedView(compressor), mem_state)

        i = lax.axis_index(self.axis_name)

        def shard_ctx(c):
            return _join_ctx(treedef, static,
                             [jnp.take(a, c, axis=0) for a in ctx_arrays])

        # The reduce-scatter's data movement: swap chunk axis for world
        # axis — rank i now holds every dp peer's payload for chunk i.
        with trace_stage(f"{STAGE_EXCHANGE}/rscatter_all_to_all"):
            mine = tuple(lax.all_to_all(p, self.axis_name, 0, 0)
                         for p in payloads)

        if exact:
            # Payload-space reduction of the owned chunk: the wire format
            # IS the accumulator, and phase 2 gathers the summed wire
            # words themselves — zero requant at any W. payload_sum is
            # the codec's stacked payload-space reduction: the committed
            # dtype-pinned jnp.sum for plain wire words (integer level
            # sums stay in the declared accumulator width), the fused
            # packed-field accumulate for sub-byte homomorphic payloads.
            owned = compressor.payload_sum(mine)
            if compressor.average and not homo:
                if not all(jnp.issubdtype(t.dtype, jnp.inexact)
                           for t in owned):
                    raise TypeError(
                        "ReduceScatterAllreduce with average=True requires "
                        f"float payloads; got {[t.dtype for t in owned]} — "
                        "integer-coded payloads cannot carry the mean "
                        "(shared_scale/sketch algebras divide after the "
                        "final decode instead).")
                owned = tuple(t / w for t in owned)
            with trace_stage(f"{STAGE_EXCHANGE}/rscatter_all_gather"):
                gathered = tuple(
                    lax.all_gather(t, self.axis_name, axis=0, tiled=False)
                    for t in owned)
            with trace_stage(STAGE_DECOMPRESS):
                # gathered[j] is rank j's owned shard == shard j, so the
                # stacked stage-1 ctx arrays align by construction.
                def dec(p, arrs):
                    return compressor.decompress(
                        p, _join_ctx(treedef, static, list(arrs)))

                out = jax.vmap(dec)(gathered, ctx_arrays)
            if homo and compressor.average:
                # The ONE decode already happened; int/sketch payloads
                # cannot carry /W, so the mean divides the dense result.
                out = out / w
        else:
            # Single-requant path: decode all W contributions for the
            # owned chunk with the locally derived (data-free) ctx,
            # aggregate — a true ONE-SHOT sum/majority vote, not the
            # ring's cascaded one — and re-encode exactly once under a
            # shared key every rank can decode. _gathered_aggregate fuses
            # the decode+reduce into one kernel pass for wire-path codecs.
            my_ctx = shard_ctx(i)
            agg = _gathered_aggregate(compressor, compressor, mine,
                                      my_ctx, w)
            if compressor.average:
                agg = agg / w
            payload2, ctx2, _ = compressor.compress(
                agg.astype(chunks.dtype), None, jax.random.fold_in(rng, w))
            with trace_stage(f"{STAGE_EXCHANGE}/rscatter_all_gather"):
                gathered = tuple(
                    lax.all_gather(t, self.axis_name, axis=0, tiled=False)
                    for t in payload2)
            with trace_stage(STAGE_DECOMPRESS):
                out = jax.vmap(
                    lambda p: compressor.decompress(p, ctx2))(gathered)
        out = out.reshape(-1)[:n].reshape(shape).astype(dtype)
        return out, mem_state, comp_state

    def exchange(self, payload: Payload, ctx: Ctx, compressor: Compressor
                 ) -> jax.Array:
        raise TypeError("ReduceScatterAllreduce re-shards the gradient "
                        "before compression; it only supports the full "
                        "step() pipeline, not a bare exchange().")


@dataclasses.dataclass(frozen=True)
class HierarchicalAllreduce(Communicator):
    """Multi-level ICI×DCN[×WAN] compressed all-reduce: the cross-slice
    (and, with ``region_size``, cross-region) schedule.

    Every flat communicator above treats the mesh axis as one ring/gather —
    which goes all-DCN the moment the axis crosses an ICI slice (see
    ``Communicator.recv_link_bytes``), and is why topk+allgather *loses* to
    dense at W=256 over DCN in the bench projections. This is the
    DynamiQ-style fix (compressed multi-hop allreduce, arXiv:2602.08923;
    THC's aggregation-friendly encodings): exploit the bandwidth hierarchy
    with a two-level schedule that keeps the bulk of the traffic on the fast
    intra-slice links and ships only the S-times-smaller per-slice partials
    across DCN. With ``slice_size=S`` on a world of ``W = K·S`` ranks
    (ranks ``[k·S, (k+1)·S)`` form slice ``k`` — the
    :class:`~grace_tpu.core.Topology` layout):

    1. **intra-slice ring reduce-scatter** (S−1 ``ppermute`` hops over ICI):
       split the compensated gradient into S shards
       (stage-1 encode shared with Ring/TwoShot via ``_shard_compress``;
       error feedback covers it exactly), then run the PR-4 hop machinery
       over the *slice sub-axis* — the permutation rotates ranks within
       their slice only, so no hop touches DCN. After the last hop, local
       rank ℓ of every slice holds its slice's partial of shard ℓ.
    2. **cross-slice exchange** (one grouped ``all_gather`` over DCN):
       the K ranks sharing local index ℓ — one per slice — exchange their
       shard-ℓ partials. Linear codecs (``summable_payload``) ship the
       wire-format partial and sum in payload space (zero extra loss);
       requant codecs (``supports_hop_requant``) re-encode the partial
       ONCE at the slice boundary, gather, decompress all K and
       ``aggregate`` (sum / majority vote). Either way the DCN leg moves
       ≈(K−1)·k/S bytes per rank — ~S²/K× less than the flat allgather's
       (W−1)·k once the whole flat schedule is priced at DCN (the flat
       *ring* moves 2·k over DCN: less than this leg beyond K=2S slices,
       but it pays every hop's latency through the boundary link, which
       the critical-path byte model deliberately understates).
    3. **intra-slice all-gather** (grouped over ICI): every slice gathers
       its S reduced shards, still in wire format, and decodes locally.

    Wire per rank: ``2·k·(S−1)/S`` over ICI + ``(K−1)·k/S`` over DCN — the
    first genuinely *mixed* ``recv_link_bytes`` split in the repo; bench
    xslice projections, telemetry's per-link fields, and graft-lint's
    wire-reconciliation pass all price it through the override below.
    ``slice_size=None`` (or ``world <= slice_size``) collapses the schedule
    and the model to the flat ring bit-for-bit: one slice, no DCN leg.

    **Three-level (region) schedule**: ``region_size=Rz`` ranks (a whole
    number of slices, ``Kr = Rz/S`` per region) adds the WAN tier. The
    cross-slice exchange splits in two: the boundary partial is first
    summed/aggregated *within the region* over DCN (the ``Kr``-member
    groups), then the region partial crosses regions over WAN (the
    ``R``-member groups, ``R = W/Rz``). Exact/homomorphic payloads cross
    WAN exactly-summable (the zero-requant property one level up —
    ``wan_compressor`` is rejected for them); requant codecs re-encode the
    region partial ONCE at the region boundary, optionally through a more
    *aggressive per-level codec* (``wan_compressor``, itself a
    ``supports_hop_requant`` codec with a data-free ctx) so the
    ~100×-slower WAN leg ships ``(R−1)·k_wan/S`` bytes at whatever ratio
    the WAN budget demands. ``region_size=None`` (or ``world <=
    region_size``, or a single region after an elastic shrink) collapses
    the schedule and the model to the two-level one bit-for-bit.

    Same enforced gates as Ring: stateless codec, wire payload, data-free
    ctx, and ``summable_payload`` or ``supports_hop_requant``. Requant loss:
    S−2 intermediate intra-slice hops + 1 slice-boundary encode
    [+ 1 region-boundary encode when R > 1] + 1 final shard encode — each
    boundary encode is paid once regardless of Kr/R (a cross-slice or
    cross-region *ring* would pay a requant per hop), which is the point of
    aggregating the gathered partials locally instead of hopping them.
    ``world % S != 0`` / ``world % Rz != 0`` are trace-time ValueErrors (an
    uneven split would silently mis-shard).
    """

    slice_size: Optional[int] = None
    region_size: Optional[int] = None
    wan_compressor: Optional[Compressor] = None
    pipeline: int = 1
    shard_parallel = True

    def __post_init__(self):
        if self.pipeline < 1:
            raise ValueError(
                "HierarchicalAllreduce pipeline must be >= 1; got "
                f"{self.pipeline} — it is the number of double-buffered "
                "buffer segments, each running the full multi-level "
                "schedule (the RingAllreduce.pipeline semantics applied "
                "to the intra-slice ring and both boundary exchanges).")
        if self.slice_size is not None and self.slice_size < 1:
            raise ValueError(f"slice_size must be >= 1 or None; "
                             f"got {self.slice_size}")
        if self.region_size is not None:
            if self.slice_size is None:
                raise ValueError(
                    "HierarchicalAllreduce(region_size=...) requires "
                    "slice_size — the region tier groups whole ICI slices, "
                    "so a three-level schedule without a slice level is "
                    f"contradictory (got region_size={self.region_size}, "
                    "slice_size=None).")
            if (self.region_size < self.slice_size
                    or self.region_size % self.slice_size):
                raise ValueError(
                    f"region_size {self.region_size} must be a whole "
                    f"multiple of slice_size {self.slice_size} — regions "
                    "are made of whole slices (the Topology contract).")
        if self.wan_compressor is not None and self.region_size is None:
            raise ValueError(
                "HierarchicalAllreduce(wan_compressor=...) without "
                "region_size — there is no WAN level to re-encode for; "
                "set region_size or drop the WAN codec.")

    def shrunk(self, topology: Topology) -> "HierarchicalAllreduce":
        """The communicator for a post-resize world described by
        ``topology`` (typically :meth:`grace_tpu.core.Topology.shrink`'s
        result): same axis, the surviving tier widths. A whole-region loss
        keeps both tiers (R→R−1 never touches intra-region schedule); a
        whole-slice loss keeps ``slice_size`` (K→K−1); a partial-slice
        loss hands back the flat ring — matching the topology collapse.
        The WAN codec rides along only while a region tier survives (a
        two-level or flat schedule has no WAN leg to encode for)."""
        wan = self.wan_compressor if topology.region_size is not None \
            else None
        return dataclasses.replace(self, slice_size=topology.slice_size,
                                   region_size=topology.region_size,
                                   wan_compressor=wan)

    def wire_overlap_fraction(self) -> float:
        p = self.pipeline
        if p <= 1:
            return 0.0
        return WIRE_PIPELINE_EFFICIENCY * (p - 1) / p

    def _split(self, world: int) -> tuple[int, int]:
        """(intra-slice size S, slice count K) for this world. Static."""
        s = self.slice_size
        if s is None or world <= s:
            return max(1, world), 1
        if world % s:
            raise ValueError(
                f"HierarchicalAllreduce(slice_size={s}) does not divide "
                f"world size {world} — the two-level schedule needs whole "
                "slices (ranks [k*S, (k+1)*S) per slice); run on a "
                "world that is a multiple of slice_size or adjust "
                "slice_size to the physical slice width.")
        return s, world // s

    def _split3(self, world: int) -> tuple[int, int, int]:
        """(S intra-slice, Kr slices per region, R regions). Static.
        ``R == 1`` is the two-level schedule (and ``Kr`` its K); a world
        inside one region never pays a WAN leg."""
        s, k = self._split(world)
        rz = self.region_size
        if rz is None or k == 1 or world <= rz:
            return s, k, 1
        if world % rz:
            raise ValueError(
                f"HierarchicalAllreduce(region_size={rz}) does not divide "
                f"world size {world} — the three-level schedule needs "
                "whole regions (ranks [r*Rz, (r+1)*Rz) per region); run "
                "on a world that is a multiple of region_size or adjust "
                "region_size to the physical region width.")
        return s, rz // s, world // rz

    def _wan_leg_nbytes(self, payload_nbytes: int, n_elems: int,
                        s: int, r: int) -> int:
        """Per-rank WAN-leg bytes: (R−1) region partials of one shard.
        With a ``wan_compressor`` the shard crosses at the WAN codec's own
        payload width (sized on the padded float32 shard — the dtype every
        registered config's compensated gradient carries), else at the
        base payload's per-shard share."""
        if r <= 1:
            return 0
        per = payload_nbytes // max(1, s)
        if self.wan_compressor is not None:
            from grace_tpu.utils.metrics import payload_nbytes as _pnb
            n = int(n_elems)
            shard = (n + (-n) % max(1, s)) // max(1, s)
            per = int(_pnb(self.wan_compressor,
                           jax.ShapeDtypeStruct((shard,), jnp.float32)))
        return (r - 1) * per

    def _recv_total_bytes(self, payload_nbytes: int, n_elems: int,
                          world: int, vote: bool = False) -> int:
        s, kr, r = self._split3(world)
        # (S-1) intra hops + (S-1) gathered shards of ~payload/S each over
        # ICI; (Kr-1) cross-slice partials of ~payload/S over DCN; (R-1)
        # cross-region partials over WAN (at the WAN codec's width when one
        # is armed). R == 1 reduces to the committed two-level formula
        # bit-for-bit (Kr is then the full slice count K).
        intra = 2 * payload_nbytes * (s - 1) // max(1, s)
        dcn = (kr - 1) * payload_nbytes // max(1, s)
        return intra + dcn + self._wan_leg_nbytes(payload_nbytes, n_elems,
                                                  s, r)

    def recv_link_bytes(self, payload_nbytes: int, n_elems: int, world: int,
                        topology=None, vote: bool = False) -> LinkBytes:
        """The genuinely mixed (ici, dcn, wan) split: intra-slice legs ride
        ICI, the cross-slice gather rides DCN, the cross-region gather
        rides WAN — *when the schedule's groupings nest inside the physical
        ones*. A mismatched layout degrades tier by tier to the flat
        communicators' worst-boundary critical path, honestly: comm slices
        straddling physical slices price everything at the worst tier the
        axis spans; comm regions straddling physical regions (or a
        two-level schedule on a three-tier fleet) price the whole
        cross-slice traffic at WAN, because some group member's incoming
        link is a region boundary."""
        total = int(self._recv_total_bytes(payload_nbytes, n_elems, world,
                                           vote=vote))
        topo = topology if topology is not None else SINGLE_SLICE
        if not topo.crosses_dcn(world):
            return LinkBytes(ici=total, dcn=0)
        s, kr, r = self._split3(world)
        k = kr * r
        aligned = (k > 1 and topo.slice_size is not None
                   and s <= topo.slice_size and topo.slice_size % s == 0)
        if not aligned:
            # k == 1: the comm thinks the axis is one slice but it
            # physically is not — its "intra-slice" ring crosses the worst
            # boundary the axis spans, exactly the flat-ring indictment.
            if topo.crosses_wan(world):
                return LinkBytes(ici=0, dcn=0, wan=total)
            return LinkBytes(ici=0, dcn=total)
        intra = 2 * payload_nbytes * (s - 1) // max(1, s)
        cross = total - intra
        if not topo.crosses_wan(world):
            # No physical WAN boundary inside this axis: both cross legs
            # (if the schedule even has two) ride DCN.
            return LinkBytes(ici=intra, dcn=cross)
        region_aligned = (r > 1 and topo.region_size is not None
                          and self.region_size <= topo.region_size
                          and topo.region_size % self.region_size == 0)
        if not region_aligned:
            # A two-level schedule on a three-tier fleet (or comm regions
            # straddling physical regions): every cross-slice group spans
            # a region boundary, so the whole cross bill lands on WAN.
            return LinkBytes(ici=intra, dcn=0, wan=cross)
        dcn_leg = (kr - 1) * payload_nbytes // max(1, s)
        return LinkBytes(ici=intra, dcn=dcn_leg, wan=cross - dcn_leg)

    def step(self, x: jax.Array, mem_state, comp_state,
             memory, compressor: Compressor, rng: jax.Array):
        if comp_state is not None:
            raise TypeError(
                f"HierarchicalAllreduce requires a stateless compressor; "
                f"{type(compressor).__name__} carries cross-step state "
                "(init_state != None) that has no per-shard meaning — use "
                "Allgather/Allreduce instead.")
        algebra = _algebra(compressor)
        homo = algebra in ("shared_scale", "sketch")
        exact = bool(getattr(compressor, "summable_payload", False))
        requant = bool(getattr(compressor, "supports_hop_requant", False))
        if not (exact or requant):
            raise TypeError(
                f"HierarchicalAllreduce keeps the payload compressed on "
                "every hop and re-aggregates the per-slice partials, which "
                "needs a payload algebra (exact: none/fp16/randomk; "
                "shared_scale: homoqsgd; sketch: countsketch — exact "
                "payload-space accumulation through BOTH levels) or an "
                "opt-in to per-hop requantization "
                "(supports_hop_requant=True: topk/qsgd/signsgd); "
                f"{type(compressor).__name__} declares neither — its "
                "payload carries structure a partial sum destroys. Use "
                "Allgather (general-purpose) or TwoShotAllreduce instead.")
        w = axis_size(self.axis_name)            # static at trace time
        s, kr, r = self._split3(w)
        k = kr * r
        if self.wan_compressor is not None:
            if exact:
                raise TypeError(
                    f"HierarchicalAllreduce(wan_compressor="
                    f"{type(self.wan_compressor).__name__}) with "
                    f"{type(compressor).__name__}: exact/homomorphic "
                    "payloads cross WAN exactly-summable — that zero-"
                    "requant property is the whole reason to use them, and "
                    "a WAN re-encode would break the payload-space sum "
                    "while adding loss. Drop wan_compressor, or pair it "
                    "with a supports_hop_requant base codec.")
            if not getattr(self.wan_compressor, "supports_hop_requant",
                           False):
                raise TypeError(
                    "HierarchicalAllreduce wan_compressor re-encodes the "
                    "region partial at the region boundary — a hop requant "
                    "one level up — so it must declare "
                    "supports_hop_requant (topk/qsgd/signsgd); "
                    f"{type(self.wan_compressor).__name__} does not.")
        # The full multi-level sum spans W = R·Kr·S ranks (S-term
        # intra-slice partials, Kr of them summed at the slice boundary, R
        # region partials summed across WAN), so the shared-scale
        # accumulator bound is on W — not S — exactly as the static gate
        # prices it.
        if homo:
            _check_payload_sum_world(compressor, w, "HierarchicalAllreduce")
        shape, dtype = x.shape, x.dtype
        compensated, mem_state = memory.compensate(x, mem_state)
        flat = compensated.reshape(-1)
        n = flat.size

        # Shared-scale negotiation hoisted before stage 1: ONE full-axis
        # pmax (not per slice or per pipeline segment — a per-slice scale
        # would break the cross-slice payload sum), so the boundary
        # exchange stays a pure integer add with zero requant regardless
        # of K.
        shared = None
        if algebra == "shared_scale":
            with trace_stage(f"{STAGE_EXCHANGE}/negotiate_scale"):
                shared = compressor.negotiate(flat, self.axis_name,
                                              rng=rng)

        segs = _pipeline_segments(n, self.pipeline)
        if len(segs) == 1:
            out, payloads, ctx_arrays, treedef, static = \
                self._segment_schedule(flat, compressor, rng, shared,
                                       homo, exact, w, s, kr, r)
            # Error feedback covers the stage-1 shard encode exactly; the
            # intra-slice hop requants and the boundary re-encodes are
            # downstream of it (same contract as Ring/TwoShot).
            view_ctx = (treedef, static, ctx_arrays, n, shape, dtype, None)
            mem_state = memory.update(compensated, payloads, view_ctx,
                                      _ChunkedView(compressor), mem_state)
        else:
            # Double-buffered schedule (RingAllreduce.pipeline semantics):
            # each contiguous segment runs the WHOLE multi-level schedule
            # under its own pipeline scope and rng fold, so the
            # intra-slice ppermutes and both boundary gathers of segment p
            # can hide behind segment p±1's encode/decode compute.
            outs, seg_pay, seg_ctx = [], [], []
            for p, (lo, hi) in enumerate(segs):
                with trace_stage(f"{STAGE_PIPELINE}/{p}"):
                    o, pay, arrs, treedef, static = \
                        self._segment_schedule(
                            flat[lo:hi], compressor,
                            jax.random.fold_in(rng, p), shared, homo,
                            exact, w, s, kr, r)
                outs.append(o)
                seg_pay.append(pay)
                seg_ctx.append((treedef, static, arrs, hi - lo,
                                (hi - lo,), flat.dtype, None))
            out = jnp.concatenate(outs)
            view_ctx = (tuple(seg_ctx), n, shape, dtype)
            mem_state = memory.update(compensated, tuple(seg_pay),
                                      view_ctx, _PipelinedView(compressor),
                                      mem_state)
        out = out[:n].reshape(shape).astype(dtype)
        return out, mem_state, comp_state

    def _segment_schedule(self, flat, compressor: Compressor,
                          rng: jax.Array, shared, homo: bool, exact: bool,
                          w: int, s: int, kr: int, r: int):
        """One full multi-level schedule over one contiguous flat segment
        — stage-1 encode, S−1 intra-slice hops, the slice/region boundary
        exchanges, the gather and the decode — shared verbatim by the
        single-segment run (``pipeline=1``: the committed path
        bit-for-bit) and the pipelined segments."""
        k = kr * r
        n = flat.shape[0]
        pad = (-n) % s
        chunks = jnp.pad(flat, (0, pad)).reshape(s, -1)

        with trace_stage(f"{STAGE_EXCHANGE}/hier_stage1_compress"):
            payloads, ctx_arrays, treedef, static = _shard_compress(
                compressor, chunks, rng, "HierarchicalAllreduce",
                shared=shared)

        i = lax.axis_index(self.axis_name)
        local = i % s                            # position within the slice
        # Rotate within each slice only: rank j talks to its ICI neighbor,
        # never across a slice boundary. slice_size=None/one slice makes
        # this the flat ring permutation bit-for-bit.
        perm_intra = [(j, (j // s) * s + ((j % s) + 1) % s)
                      for j in range(w)]
        # Rank groups of the grouped collectives: cross-slice peers share
        # a local index; intra-slice peers share a slice. With a region
        # tier (R > 1) the cross-slice exchange splits level-by-level:
        # dcn_groups are the Kr slices of ONE region sharing a local index
        # (all-DCN), wan_groups one rank per region sharing (slice-in-
        # region, local) — by then every rank of a dcn group holds the
        # identical region partial, so any one member per region
        # represents it and the grouping stays a partition of the axis.
        cross_groups = [[kk * s + ll for kk in range(k)] for ll in range(s)]
        intra_groups = [[kk * s + ll for ll in range(s)] for kk in range(k)]
        if r > 1:
            rz = kr * s
            dcn_groups = [[rho * rz + kk * s + ll for kk in range(kr)]
                          for rho in range(r) for ll in range(s)]
            wan_groups = [[rho * rz + kk * s + ll for rho in range(r)]
                          for kk in range(kr) for ll in range(s)]
        else:
            dcn_groups, wan_groups = cross_groups, None

        def take_payload(stack, c):
            return tuple(jnp.take(t, c, axis=0) for t in stack)

        def shard_ctx(c):
            return _join_ctx(treedef, static,
                             [jnp.take(a, c, axis=0) for a in ctx_arrays])

        def gather_groups(payload, groups, stage):
            with trace_stage(stage):
                return tuple(
                    lax.all_gather(t, self.axis_name, axis=0, tiled=False,
                                   axis_index_groups=groups)
                    for t in payload)

        if exact:
            # Phase 1: payload-space ring reduce-scatter over the slice
            # sub-axis — identical hop logic to RingAllreduce with W -> S.
            # Serves all three algebras: homomorphic payloads (integer
            # levels under the hoisted shared scale, mergeable sketch
            # tables) hop-add with zero requant.
            send = take_payload(payloads, (local - 1) % s)
            for hop in range(s - 1):
                with trace_stage(f"{STAGE_RING_HOP}/{hop}"):
                    recv = tuple(lax.ppermute(t, self.axis_name, perm_intra)
                                 for t in send)
                    own = take_payload(payloads, (local - 2 - hop) % s)
                    # Codec payload-space add: elementwise for plain wire
                    # words (the committed spelling bit-for-bit), a fused
                    # packed-field accumulate for sub-byte homomorphic
                    # payloads (see RingAllreduce).
                    send = compressor.payload_add(recv, own)
            partial = send       # wire-format slice partial of shard `local`
            # Phase 2: the payload algebra makes the cross-slice exchange
            # an exact payload-space sum of the K slice partials — no
            # boundary requant (the requant path's ONE remaining re-encode
            # point, now zero), no extra loss, and only ~payload/S rides
            # DCN.
            if k > 1:
                stacked = gather_groups(
                    partial, dcn_groups,
                    f"{STAGE_EXCHANGE}/hier_cross_slice")
                # payload_sum pins the accumulation to the wire dtype:
                # numpy promotion would silently widen integer level sums
                # to int32 here, but the accumulator width is the codec's
                # declared contract (payload_sum_max_world bounds W so
                # THIS dtype is enough); packed homomorphic payloads
                # reduce in field space through the fused accumulate.
                owned = compressor.payload_sum(stacked)
                if r > 1:
                    # Level 3: the region partials cross WAN still in
                    # payload space — the exact/homomorphic algebra makes
                    # the (R-1)-partial WAN exchange a zero-requant sum,
                    # one tier up from the slice-boundary argument.
                    stacked_w = gather_groups(
                        owned, wan_groups,
                        f"{STAGE_EXCHANGE}/hier_cross_region")
                    owned = compressor.payload_sum(stacked_w)
            else:
                owned = partial
            if compressor.average and not homo:
                if not all(jnp.issubdtype(t.dtype, jnp.inexact)
                           for t in owned):
                    raise TypeError(
                        "HierarchicalAllreduce with average=True requires "
                        f"float payloads; got {[t.dtype for t in owned]} — "
                        "integer-coded payloads cannot carry the mean "
                        "(reference compatibility matrix, "
                        "IMPLEMENTING.md:43-45; shared_scale/sketch "
                        "algebras divide after the final decode instead).")
                owned = tuple(t / w for t in owned)
            # Phase 3: gather the S reduced shards within the slice, still
            # in wire format; gathered[j] is local rank j's shard == shard
            # j, so the stacked stage-1 ctx arrays align by construction.
            gathered = gather_groups(owned, intra_groups,
                                     f"{STAGE_EXCHANGE}/hier_all_gather")
            with trace_stage(STAGE_DECOMPRESS):
                def dec(p, arrs):
                    return compressor.decompress(
                        p, _join_ctx(treedef, static, list(arrs)))

                out = jax.vmap(dec)(gathered, ctx_arrays)
            if homo and compressor.average:
                # One decode for the whole two-level schedule; the mean
                # divides the dense result (int/sketch payloads cannot
                # carry /W).
                out = out / w
        else:
            # Phase 1: decompress -> accumulate -> requantize per intra
            # hop (shared hop keys; the receiver derives the sender's
            # data-free ctx locally — the Ring soundness argument).
            hop_ctx = None
            send = take_payload(payloads, (local - 1) % s)
            partial = None
            for hop in range(s - 1):
                with trace_stage(f"{STAGE_RING_HOP}/{hop}"):
                    recv = tuple(lax.ppermute(t, self.axis_name, perm_intra)
                                 for t in send)
                    rc = (local - 2 - hop) % s
                    rctx = shard_ctx(rc) if hop == 0 else hop_ctx
                    partial = (compressor.decompress(recv, rctx)
                               + compressor.decompress(
                                   take_payload(payloads, rc),
                                   shard_ctx(rc)))
                    if hop < s - 2:
                        pay, hop_ctx, _ = compressor.compress(
                            partial, None,
                            jax.random.fold_in(rng, s + 1 + hop))
                        send = tuple(pay)
            if partial is None:                  # s == 1: one-rank slices
                partial = compressor.decompress(take_payload(payloads, 0),
                                                shard_ctx(0))
            if k > 1:
                # The ONE slice-boundary requant: re-encode the slice
                # partial under a shared key, gather the Kr encoded
                # partials across the region's slices over DCN, decode and
                # aggregate locally (sum, or the majority vote for sign
                # codecs — every rank of a cross-slice group computes the
                # identical result).
                payload_b, ctx_b, _ = compressor.compress(
                    partial, None, jax.random.fold_in(rng, 2 * s))
                stacked = gather_groups(
                    tuple(payload_b), dcn_groups,
                    f"{STAGE_EXCHANGE}/hier_cross_slice")
                # Fused decode+aggregate of the Kr gathered slice partials
                # for wire-path codecs; the staged vmap-decompress +
                # aggregate spelling otherwise (see _gathered_aggregate).
                agg = _gathered_aggregate(compressor, compressor, stacked,
                                          ctx_b, kr)
                if r > 1:
                    # The ONE region-boundary requant, one level up: every
                    # rank of a dcn group now holds the identical region
                    # partial, so re-encode it — through the aggressive
                    # WAN codec when one is armed, else the base codec —
                    # under a shared key, gather the R encoded region
                    # partials across regions over WAN, decode and
                    # aggregate with the BASE codec's semantics (sum, or
                    # the cascaded majority vote). Paid once regardless of
                    # R; a cross-region ring would pay R-1 requants.
                    wan_codec = self.wan_compressor or compressor
                    if (self.wan_compressor is not None
                            and not ctx_is_data_free(
                                self.wan_compressor, agg.size, agg.dtype)):
                        raise TypeError(
                            "HierarchicalAllreduce wan_compressor needs a "
                            "data-free ctx — ranks decode each other's "
                            "region partials with locally derived ctx; "
                            f"{type(self.wan_compressor).__name__}."
                            "compress puts data-derived arrays in ctx.")
                    payload_w, ctx_w, _ = wan_codec.compress(
                        agg.astype(chunks.dtype), None,
                        jax.random.fold_in(rng, 2 * s + 2))
                    stacked_w = gather_groups(
                        tuple(payload_w), wan_groups,
                        f"{STAGE_EXCHANGE}/hier_cross_region")
                    # Base codec supplies the aggregation semantics even
                    # when the aggressive WAN codec did the encode.
                    agg = _gathered_aggregate(compressor, wan_codec,
                                              stacked_w, ctx_w, r)
            else:
                # Singleton stack: sum codecs pass through, vote codecs
                # re-sign the final tally — same as the flat ring.
                agg = compressor.aggregate(partial[None])
            if compressor.average:
                agg = agg / w
            # Final shard encode under a shared key; gather within the
            # slice still in wire format; decode all S shards locally.
            payload2, ctx2, _ = compressor.compress(
                agg.astype(chunks.dtype), None,
                jax.random.fold_in(rng, 2 * s + 1))
            gathered = gather_groups(tuple(payload2), intra_groups,
                                     f"{STAGE_EXCHANGE}/hier_all_gather")
            with trace_stage(STAGE_DECOMPRESS):
                out = jax.vmap(
                    lambda p: compressor.decompress(p, ctx2))(gathered)
        return out.reshape(-1)[:n], payloads, ctx_arrays, treedef, static

    def exchange(self, payload: Payload, ctx: Ctx, compressor: Compressor
                 ) -> jax.Array:
        raise TypeError("HierarchicalAllreduce re-shards the gradient "
                        "before compression; it only supports the full "
                        "step() pipeline, not a bare exchange().")


@dataclasses.dataclass(frozen=True)
class Identity(Communicator):
    """No-op communicator: decompress this rank's own payload.

    No reference analog; used for single-device debugging and as the
    injectable no-comm fake the reference never wrote (SURVEY.md §4).
    """

    def _recv_total_bytes(self, payload_nbytes: int, n_elems: int,
                          world: int, vote: bool = False) -> int:
        return 0

    def exchange(self, payload: Payload, ctx: Ctx, compressor: Compressor
                 ) -> jax.Array:
        return compressor.decompress(payload, ctx)
