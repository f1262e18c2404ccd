"""PowerSGD low-rank compression (Vogels et al. 2019).

Reference: grace_dl/dist/compressor/powersgd.py:21-65 — the one algorithm
whose communication happens *inside* compress: P = MQ → allreduce(P)/W →
orthogonalize → Q = MᵀP → allreduce(Q)/W; compress returns ``([], ctx)`` so
the communicator has nothing to send, and decompress reconstructs PQᵀ. This
is natural in JAX: compress already runs inside `shard_map`, so the
allreduces are plain ``lax.psum`` over the mesh axis.

State contract (SURVEY.md §7 hard part 2): the reference couples compressor
and memory through a shared mutable ``q_memory`` dict (helper passes
``compressor.q_memory`` into the memory, which overwrites it with fresh
Gaussian Q every step — torch/dist reference never actually warm-starts).
Here Q is explicit per-leaf compressor state: ``warm_start=True`` (default)
reuses last step's Q as the power-iteration start, which is the published
algorithm and converges better; ``warm_start=False`` redraws Gaussian Q each
step, reproducing the reference's effective behavior. No shared-dict
coupling either way.

1-D tensors bypass compression (reference powersgd.py:31-32): payload is the
raw tensor, summed/averaged densely by the communicator.

Matricization: the reference views tensors as ``(shape[0], -1)``
(powersgd.py:34) — correct for torch's OIHW conv kernels, where dim 0 is the
output-channel dim. JAX convs are HWIO (output channels LAST), so the same
rule would factor a (3,3,cin,cout) kernel as a degenerate (3, 3·cin·cout)
matrix whose Q factor is nearly dense-sized (measured 2.5x the dense bytes
over ResNet-50). Here tensors matricize as ``(-1, shape[-1])`` — the
output-channel dim is one factor side, exactly the reference's semantics in
the native JAX layout; 2-D weights are unchanged.

Orthogonalization uses ``jnp.linalg.qr`` — a fused XLA op on the MXU —
instead of the reference's column-by-column @torch.jit.script Gram-Schmidt
(powersgd.py:7-18), which would serialize r matvecs.

Rung-invariant state layout (graft-adapt): an adapt ladder across
PowerSGD *ranks* must thread one comp-state structure through every
``lax.switch`` branch, but a rank-r rung natively stores a ``(m, r)`` Q —
structurally different per rung. ``state_rank`` decouples the stored
layout from the active rank: the per-leaf state is padded to
``(m, min(n, m, state_rank))`` and each rung operates on its leading
``rank`` columns, writing its refined Q back into that slice and carrying
the inactive tail columns UNCHANGED. That makes the padding a warm-start
carrier, not dead weight — when the controller moves UP a rung, the new
columns resume from whatever power-iteration state they last held (the
PowerSGD paper's warm-start result, extended across rung moves). With
``state_rank=None`` (or ``== rank``) the slice and re-pad are no-ops and
the codec is bit-identical to the unpadded layout. Wire pricing is
untouched: only the ACTIVE ``(n + m) * rank`` factors ever travel.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from grace_tpu.core import DEFAULT_AXIS, Compressor, Ctx, Payload, State


@dataclasses.dataclass(frozen=True)
class PowerSGDCompressor(Compressor):
    rank: int = 1
    warm_start: bool = True
    axis_name: str = DEFAULT_AXIS
    # Stored-Q column count for rung-invariant adapt ladders: pad the
    # per-leaf state to the ladder's max rank so every rung threads the
    # same structure through lax.switch. None = store exactly `rank`
    # columns (the classic layout). Must be >= rank when set.
    state_rank: Optional[int] = None
    # 1-D leaves ride the communicator dense; >=2-D leaves were already
    # psum-reduced inside compress, so the outer allreduce sees a replicated
    # payload that sums/averages consistently — exact composition.
    payload_algebra = "exact"
    # Communicates inside compress and carries cross-step Q state — the
    # shard-parallel communicators reject it before capability gating.
    supports_hop_requant = False

    def _factor_shapes(self, shape):
        m = shape[-1]              # output-channel dim (HWIO/(*, features))
        n = int(np.prod(shape[:-1], dtype=np.int64))
        r = min(n, m, self.rank)
        return n, m, r

    def _state_cols(self, n: int, m: int) -> int:
        """Stored-Q column count: the padded layout when ``state_rank``
        is set, else exactly the active rank."""
        if self.state_rank is not None:
            if self.state_rank < self.rank:
                raise ValueError(
                    f"PowerSGD state_rank={self.state_rank} < rank="
                    f"{self.rank}: the stored Q must hold at least the "
                    "active columns")
            return min(n, m, self.state_rank)
        return min(n, m, self.rank)

    def init_state(self, x: jax.Array) -> State:
        if x.ndim <= 1:
            return None
        n, m, _ = self._factor_shapes(x.shape)
        rs = self._state_cols(n, m)
        # Deterministic initial Q; identical on all ranks by construction.
        # The bit-exactness claim for the padded layout holds at rs == r
        # (state_rank None or == rank) — a wider draw is a different
        # random matrix, which is fine: padding exists to serve ladders,
        # whose quiet-run contract is judged per layout, not across them.
        return jax.random.normal(jax.random.key(x.size), (m, rs), x.dtype)

    def wire_nbytes(self, shape, dtype) -> int:
        """Analytic: compress's psums of P (n,r) and Q (m,r) ARE the wire
        traffic; the payload tuple is empty and compress cannot be
        shape-traced without a bound mesh axis."""
        itemsize = jnp.dtype(dtype).itemsize
        if len(shape) <= 1:
            # 1-D bypass rides dense
            return int(np.prod(shape, dtype=np.int64)) * itemsize
        n, m, r = self._factor_shapes(shape)
        return (n + m) * r * itemsize

    def compress(self, x: jax.Array, state: State, rng: jax.Array
                 ) -> tuple[Payload, Ctx, State]:
        if x.ndim <= 1:
            return (x,), None, state
        shape = x.shape
        n, m, r = self._factor_shapes(shape)
        matrix = x.reshape(n, m)   # n = prod(leading dims), m = shape[-1]
        q_full = state             # (m, rs) with rs >= r; rs == r unpadded
        if self.warm_start:
            q = q_full[:, :r]      # active columns only drive this rung
        else:
            # rng is replicated across ranks, so the redrawn Q agrees too.
            q = jax.random.normal(rng, (m, r), x.dtype)
        q, _ = jnp.linalg.qr(q)
        w = lax.psum(1, self.axis_name)
        p = matrix @ q
        p = lax.psum(p, self.axis_name) / w
        p, _ = jnp.linalg.qr(p)
        q = matrix.T @ p
        q = lax.psum(q, self.axis_name) / w
        # Re-pad: refined active columns in front, inactive tail carried
        # untouched — the warm-start store for any HIGHER rung this ladder
        # may move to. At rs == r the tail is empty and this is q itself.
        return (), (p, q, shape), jnp.concatenate(
            [q, q_full[:, r:]], axis=1)

    def decompress(self, payload: Payload, ctx: Ctx) -> jax.Array:
        if ctx is None:
            (x,) = payload
            return x
        p, q, shape = ctx
        return (p @ q.T).reshape(shape)
