"""Pallas TPU kernel: fused error-feedback + chunk-mode Top-K compress.

The chunk Top-K local pipeline (compensate -> select -> extract wire values
-> residual update; reference semantics grace_dl/dist/communicator pipeline,
grace_dl/dist/__init__.py:47-52) is pure elementwise/reduction work over the
fused gradient buffer, but expressed in jnp it streams the n-element buffer
through HBM ~6 times (compensated, padded body, |body| argmax, masked value
sum, one-hot dense, residual subtract — XLA fuses some neighbors but the
compressed-step overhead on a 25.5M buffer was still ~10 ms vs a ~3-pass
roofline on chip, 2026-07-31, before the driver's ledger). This kernel does the
whole thing in ONE pass: read grad + residual tiles into VMEM, write the
new residual tile plus the k-sized wire values/rows.

Layout: the flat buffer is viewed as (rows, k) row-major — strided chunk c
is column c, exactly the TopKCompressor 'chunk' wire format. To avoid
materializing a zero-padded copy of the whole buffer (which would re-add
two full HBM passes), the buffer is split into a row-major reshape of
the ``n // k`` full rows plus one k-sized zero-padded tail row; the kernel
reduces over both. That reshape copies nothing in row-major terms, but on
the TPU it is free only where k is a multiple of the 128 lanes: otherwise
``flat -> (rows, k)`` is a physical relayout of a tiled layout, which
XLA:TPU runs as one operation up to 2**22 elements and as a ``while`` loop
over 4-row windows past that (``ops/sparse.py`` RELAYOUT_LOOP_ELEMENTS; the
staged route avoids it by walking row-block slices of the flat buffer,
this one does not). beta/gamma feedback coefficients are static jit args
folded into the kernel, so the only HBM traffic is: read grad + residual,
write residual + the two k-sized wire planes, plus one n-sized reassembly
write of the residual halves.

Selection rule (must match TopKCompressor._chunk_compress exactly): the
winner of column c is the FIRST row attaining the column max of |comp| —
main rows in order, then the tail row. Tail padding lanes (columns >= n
mod k) hold 0 and can only tie, and ties resolve to an earlier real row,
so wire indices stay < n. If a column max is NaN no equality fires and the
guard picks row 0 — defined, in-range behavior under poisoned gradients
(the NaN stays in the residual either way, so it remains visible).

Used by ``TopKCompressor.fused_feedback_compress`` via the
``Communicator.step`` fused fast path; runs in interpreter mode on CPU so
the test suite exercises the same code path everywhere (single-device
meshes only — see the interpret guard in TopKCompressor).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from grace_tpu.ops.pallas_quant import _interpret_mode

# Per-block VMEM budget across ALL of a kernel's f32 block buffers (Mosaic
# pads each buffer's sublane count to 8 and double-buffers; the 4 MiB
# budget leaves that headroom within ~16 MiB VMEM); lane blocks must be
# multiples of 128. If the budget cannot fit even bc=128 (tiny compress
# ratios => many rows; huge worlds), the *_block_cols gate returns 0 and
# callers fall back to the unfused XLA path instead of blowing VMEM.
_VMEM_BUDGET = 4 * 2**20
_MAX_BC = 2048


def _block_cols(*buffer_rows: int) -> int:
    units = sum(-(-r // 8) * 8 for r in buffer_rows)
    bc = _VMEM_BUDGET // (4 * units)
    return min(_MAX_BC, (bc // 128) * 128)        # 0 => does not fit


def compress_block_cols(main_rows: int) -> int:
    """bc for chunk_compress_feedback: grad/resid main+tail inputs, resid
    main+tail outputs, two k-wide wire planes."""
    return _block_cols(main_rows, main_rows, main_rows, 1, 1, 1, 1, 1)


def aggregate_block_cols(main_rows: int, world: int) -> int:
    """bc for chunk_aggregate_dense: (world, bc) vals+win inputs, main+tail
    outputs — world-aware, a pod-scale W inflates the input blocks."""
    return _block_cols(world, world, main_rows, 1)


def _make_kernel(main_rows: int, has_resid: bool, beta: float, gamma: float,
                 wire_bf16: bool):
    def kernel(*refs):
        refs = list(refs)
        g_ref, t_ref = refs[0], refs[1]
        if has_resid:
            r_ref, rt_ref = refs[2], refs[3]
        vals_ref, row_ref, resid_ref, resid_t_ref = refs[-4:]

        comp = g_ref[:] * gamma                      # (mr, bc)
        tcomp = t_ref[:] * gamma                     # (1, bc)
        if has_resid:
            comp = comp + r_ref[:] * beta
            tcomp = tcomp + rt_ref[:] * beta
        a = jnp.abs(comp)
        at = jnp.abs(tcomp)
        m = jnp.maximum(jnp.max(a, axis=0, keepdims=True), at)   # (1, bc)
        row_iota = jax.lax.broadcasted_iota(jnp.int32, comp.shape, 0)
        # First-max among main rows; sentinel main_rows if none matches.
        win_main = jnp.min(jnp.where(a == m, row_iota, main_rows), axis=0,
                           keepdims=True)            # (1, bc)
        tail_hit = at == m
        # Column winner: first main-row max, else the tail row, else (NaN
        # column: no equality fires anywhere) row 0 — always a real lane.
        win = jnp.where(win_main < main_rows, win_main,
                        jnp.where(tail_hit, main_rows, 0))
        hot = row_iota == win
        hot_tail = win == main_rows
        vals = (jnp.sum(jnp.where(hot, comp, 0.0), axis=0, keepdims=True)
                + jnp.where(hot_tail, tcomp, 0.0))
        if wire_bf16:
            vals = vals.astype(jnp.bfloat16)
            # Residual absorbs the bf16 wire rounding, same as the unfused
            # path where update decompresses the bf16 payload.
            dense = vals.astype(comp.dtype)
        else:
            dense = vals
        resid_ref[:] = comp - jnp.where(hot, dense, 0.0)
        resid_t_ref[:] = tcomp - jnp.where(hot_tail, dense, 0.0)
        vals_ref[:] = vals
        row_ref[:] = win

    return kernel


@functools.partial(jax.jit, static_argnames=("k", "beta", "gamma",
                                             "wire_bf16", "interpret"))
def chunk_compress_feedback(flat: jax.Array, residual, k: int,
                            beta: float = 1.0, gamma: float = 1.0,
                            wire_bf16: bool = False, interpret: bool = False):
    """Fused ``comp = gamma*flat + beta*residual`` -> chunk-Top-K select ->
    ``(values, win_row, new_residual)``.

    ``residual`` may be None (no-feedback variant: the returned residual is
    the keep-complement of the scaled gradient; callers that don't need it
    just drop it). Requires f32 inputs and ``flat.size >= 2*k``; callers
    must check :func:`block_cols` first. Semantics are bit-identical to
    TopKCompressor._chunk_compress followed by ResidualMemory.update.
    """
    n = flat.size
    main_rows = n // k                      # >= 2 by the caller's n >= 2k
    rem = n - main_rows * k
    bc = compress_block_cols(main_rows)
    if bc <= 0:
        raise ValueError(
            f"chunk_compress_feedback: {main_rows} rows do not fit the VMEM "
            "block budget — gate on compress_block_cols() > 0")

    def two_d(buf):
        main = buf[:main_rows * k].reshape(main_rows, k)   # free reshape
        tail = jnp.zeros((1, k), buf.dtype)
        if rem:
            tail = tail.at[0, :rem].set(buf[main_rows * k:])
        return main, tail

    operands = list(two_d(flat))
    if residual is not None:
        operands += list(two_d(residual))

    main_spec = pl.BlockSpec((main_rows, bc), lambda j: (0, j),
                             memory_space=pltpu.VMEM)
    tail_spec = pl.BlockSpec((1, bc), lambda j: (0, j),
                             memory_space=pltpu.VMEM)
    wire_dtype = jnp.bfloat16 if wire_bf16 else jnp.float32
    vals, win, resid_main, resid_tail = pl.pallas_call(
        _make_kernel(main_rows, residual is not None, beta, gamma, wire_bf16),
        grid=(pl.cdiv(k, bc),),
        in_specs=[main_spec, tail_spec] * (2 if residual is not None else 1),
        out_specs=[tail_spec, tail_spec, main_spec, tail_spec],
        out_shape=[
            jax.ShapeDtypeStruct((1, k), wire_dtype),
            jax.ShapeDtypeStruct((1, k), jnp.int32),
            jax.ShapeDtypeStruct((main_rows, k), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
        ],
        interpret=_interpret_mode(interpret),
    )(*operands)
    new_resid = resid_main.reshape(-1)
    if rem:
        new_resid = jnp.concatenate([new_resid, resid_tail[0, :rem]])
    return vals.reshape(k), win.reshape(k), new_resid


# ---------------------------------------------------------------------------
# Exchange-side kernel: W gathered chunk payloads -> aggregated dense tensor
# ---------------------------------------------------------------------------

# Beyond this world size the per-rank accumulation runs as a lax.fori_loop
# instead of a static unroll: worlds in the hundreds can pass the VMEM gate
# (e.g. world=256 with ~100 rows still yields bc=384) but a 256-way unroll
# makes a very long Mosaic program with a correspondingly long compile.
_AGG_UNROLL_MAX = 32


def _make_agg_kernel(main_rows: int, world: int, average: bool):
    def kernel(vals_ref, win_ref, out_ref, tail_ref):
        v = vals_ref[:].astype(jnp.float32)          # (world, bc)
        w = win_ref[:]                               # (world, bc)
        row_iota = jax.lax.broadcasted_iota(
            jnp.int32, (main_rows, v.shape[1]), 0)
        acc0 = jnp.zeros((main_rows, v.shape[1]), jnp.float32)
        tail0 = jnp.zeros((1, v.shape[1]), jnp.float32)

        def add_rank(vi, wi, carry):
            acc, tail = carry
            acc = acc + jnp.where(row_iota == wi, vi, 0.0)
            tail = tail + jnp.where(wi == main_rows, vi, 0.0)
            return acc, tail

        if world <= _AGG_UNROLL_MAX:                 # static unroll, VPU adds
            acc, tail = acc0, tail0
            for i in range(world):
                acc, tail = add_rank(v[i][None, :], w[i][None, :],
                                     (acc, tail))
        else:
            def body(i, carry):
                vi = jax.lax.dynamic_slice_in_dim(v, i, 1, axis=0)
                wi = jax.lax.dynamic_slice_in_dim(w, i, 1, axis=0)
                return add_rank(vi, wi, carry)

            acc, tail = jax.lax.fori_loop(0, world, body, (acc0, tail0))
        if average:
            acc = acc / world
            tail = tail / world
        out_ref[:] = acc
        tail_ref[:] = tail

    return kernel


@functools.partial(jax.jit, static_argnames=("k", "n", "average",
                                             "interpret"))
def chunk_aggregate_dense(vals: jax.Array, win: jax.Array, k: int, n: int,
                          average: bool = True, interpret: bool = False
                          ) -> jax.Array:
    """Aggregate ``world`` gathered chunk payloads into one dense tensor.

    ``vals``/``win`` are (world, k) stacks of wire values and winning-row
    ids (flat index = win*k + column). The staged XLA path materializes
    ``world`` one-hot dense buffers and sums them (~world+1 HBM passes over
    n); this kernel reads the (world, k) wire planes once and writes the
    summed (optionally world-averaged) dense tensor in a single n-sized
    pass — the exchange-side twin of :func:`chunk_compress_feedback`.
    A payload row may carry win == n//k (the tail row); out-of-range rows
    beyond that cannot occur by the compress-side invariant.
    """
    main_rows = n // k
    rem = n - main_rows * k
    world = vals.shape[0]
    bc = aggregate_block_cols(main_rows, world)
    if bc <= 0:
        raise ValueError(
            f"chunk_aggregate_dense: {main_rows} rows x world={world} do "
            "not fit the VMEM block budget — gate on "
            "aggregate_block_cols() > 0")

    wspec = pl.BlockSpec((world, bc), lambda j: (0, j),
                         memory_space=pltpu.VMEM)
    out_main, out_tail = pl.pallas_call(
        _make_agg_kernel(main_rows, world, average),
        grid=(pl.cdiv(k, bc),),
        in_specs=[wspec, wspec],
        out_specs=[pl.BlockSpec((main_rows, bc), lambda j: (0, j),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, bc), lambda j: (0, j),
                                memory_space=pltpu.VMEM)],
        out_shape=[jax.ShapeDtypeStruct((main_rows, k), jnp.float32),
                   jax.ShapeDtypeStruct((1, k), jnp.float32)],
        interpret=_interpret_mode(interpret),
    )(vals, win)
    out = out_main.reshape(-1)
    if rem:
        out = jnp.concatenate([out, out_tail[0, :rem]])
    return out
