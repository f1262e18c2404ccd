"""Framework-agnostic gradient bridge: numpy in, aggregated numpy out.

The reference's torch backend launches one async NCCL op per parameter from
inside backward hooks (grace_dl/torch/__init__.py:50-58). On TPU the whole
pipeline — compensate → compress → exchange over the mesh → decompress →
aggregate — is ONE jitted XLA program over a single fused gradient buffer
(frontend gradients are bucketed host-side anyway, so fusion is free). The
bridge owns the compression state (GraceState, world axis sharded over the
mesh, see grace_tpu/transform.py) and keeps it on device between calls.

Process model — identical to Horovod's (one process per accelerator,
SURVEY.md §2.5): under `jax.distributed`, each process contributes its local
gradient as its shard of a global ``(world, n)`` array. If a process owns
several mesh devices, its gradient is replicated across them; for
``average=True`` compressors the duplicated rows drop out of the mean, and
majority votes are unchanged (uniform duplication), so semantics match the
one-process-per-chip layout. Sum-semantics compressors with ``average=False``
would be scaled by the duplication factor — the bridge warns in that case.

The async split of the reference (`send_step` during backward /
`receive_step` at `optimizer.step`, grace_dl/torch/__init__.py:37-58) maps
to JAX dispatch: :meth:`exchange` returns immediately with a live device
array (the XLA computation runs asynchronously); :func:`numpy` / blocking
reads realise it — that is the `synchronize` point.
"""

from __future__ import annotations

import warnings
from collections import Counter
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from grace_tpu.helper import Grace
from grace_tpu.parallel import data_parallel_mesh, shard_map
from grace_tpu.transform import (add_world_axis, partition_specs,
                                 strip_world_axis)

__all__ = ["GraceBridge"]


class GraceBridge:
    """Jitted grace pipeline for one flat gradient buffer of fixed size.

    Usage (per process)::

        bridge = GraceBridge(grace_from_params({...}), n=total_grad_elems)
        agg = bridge.exchange(flat_local_grads)   # async device value
        out = np.asarray(agg)                     # blocks; aggregated grads
    """

    def __init__(self, grace: Grace, n: int, mesh: Optional[Mesh] = None,
                 seed: int = 0, dtype=jnp.float32):
        self.grace = grace
        self.n = int(n)
        self.dtype = jnp.dtype(dtype)
        self.mesh = mesh if mesh is not None else data_parallel_mesh()
        self.axis = grace.communicator.axis_name
        if self.axis not in self.mesh.shape:
            raise ValueError(f"mesh has no axis {self.axis!r}; "
                             f"axes: {tuple(self.mesh.shape)}")
        self.world = self.mesh.shape[self.axis]
        rows_per_proc = Counter(d.process_index
                                for d in self.mesh.devices.flat)
        self._local_rows = max(1, rows_per_proc.get(jax.process_index(), 0))
        if max(rows_per_proc.values()) > 1 and not grace.compressor.average:
            uniform = len(set(rows_per_proc.values())) == 1
            if getattr(grace.compressor, "vote_aggregate", False):
                # A *uniform* duplication factor leaves a majority vote
                # unchanged (every process casts k identical ballots, the
                # re-signed sum is scale-free). Unequal factors weight the
                # vote by local device count — warn on EVERY process, the
                # biased aggregate reaches all of them.
                if not uniform:
                    warnings.warn(
                        "GraceBridge: processes feed unequal numbers of mesh "
                        f"devices ({sorted(rows_per_proc.values())}); each "
                        "process's identical sign votes are duplicated by "
                        "its local device count, biasing the majority vote "
                        "toward larger processes. Use one process per device "
                        "for an unweighted vote.")
            else:
                warnings.warn(
                    "GraceBridge: some process feeds multiple mesh devices "
                    "and the compressor has average=False (sum semantics): "
                    "duplicated rows scale the aggregate (per-process "
                    f"duplication factors {sorted(rows_per_proc.values())}). "
                    "Use one process per device for exact sum semantics.")

        tx = grace.transform(seed=seed)
        template = jnp.zeros((self.n,), self.dtype)

        # Global-layout state: grace mem/comp leaves sharded over the axis.
        abstract = jax.eval_shape(tx.init, [template])
        specs = partition_specs(abstract, self.axis)
        init_fn = shard_map(
            lambda t: add_world_axis(tx.init([t[0]])),
            mesh=self.mesh, in_specs=(P(self.axis),), out_specs=specs,
            check_vma=False)
        self._state = jax.jit(init_fn)(
            jnp.zeros((self.world, self.n), self.dtype))

        def bridge_step(state, local):
            # local: this device's (1, n) row of the (world, n) gradient
            out, new_state = tx.update([local[0]], strip_world_axis(state))
            return add_world_axis(new_state), out[0]

        sharded = shard_map(
            bridge_step, mesh=self.mesh,
            in_specs=(specs, P(self.axis)),
            out_specs=(specs, P()),
            check_vma=False)
        self._fn = jax.jit(sharded, donate_argnums=(0,))

        def bridge_step_row(state, row):
            # row: the full (n,) gradient, replicated — the single-process
            # case where every "rank" carries this process's gradient. Avoids
            # materializing world× duplicated rows over the host link.
            out, new_state = tx.update([row], strip_world_axis(state))
            return add_world_axis(new_state), out[0]

        sharded_row = shard_map(
            bridge_step_row, mesh=self.mesh,
            in_specs=(specs, P()),
            out_specs=(specs, P()),
            check_vma=False)
        self._fn_row = jax.jit(sharded_row, donate_argnums=(0,))
        self._grad_sharding = NamedSharding(self.mesh, P(self.axis))
        self._row_sharding = NamedSharding(self.mesh, P())

    # -- wire-in ------------------------------------------------------------
    def exchange_global(self, global_grads) -> jax.Array:
        """Exchange a fully formed (world, n) gradient array (tests/power
        users: lets a single process feed distinct per-rank gradients)."""
        global_grads = jnp.asarray(global_grads, self.dtype)
        if global_grads.shape != (self.world, self.n):
            raise ValueError(f"expected ({self.world}, {self.n}), "
                             f"got {global_grads.shape}")
        self._state, out = self._fn(self._state, global_grads)
        return out

    def exchange(self, local_flat_grads: np.ndarray) -> jax.Array:
        """Start the compressed exchange for this process's gradients.

        Returns the aggregated flat gradient as a live (async) device array;
        convert with ``np.asarray`` to block — the reference's
        `receive_step`/`synchronize` point.
        """
        local = np.asarray(local_flat_grads, self.dtype)
        if local.shape != (self.n,):
            raise ValueError(f"expected flat gradients of shape ({self.n},), "
                             f"got {local.shape}")
        if jax.process_count() == 1:
            # Transfer the n-element row once; every mesh device reads the
            # same replicated row (no world× host-side duplication).
            row = jax.device_put(local, self._row_sharding)
            self._state, out = self._fn_row(self._state, row)
            return out
        rows = np.broadcast_to(local, (self._local_rows, self.n))
        global_grads = jax.make_array_from_process_local_data(
            self._grad_sharding, rows, (self.world, self.n))
        self._state, out = self._fn(self._state, global_grads)
        return out

    # -- state management ---------------------------------------------------
    @property
    def state(self):
        """Compression state (GraceState pytree, world-axis layout) — expose
        for checkpointing; the reference never persisted this (SURVEY.md §5).

        Serialize (or ``jax.device_get``) before the next :meth:`exchange`:
        the jitted step donates the previous state buffers, so a live
        reference held across an exchange is deleted."""
        return self._state

    @state.setter
    def state(self, value):
        # Fail at assignment, not at the first exchange deep inside XLA:
        # a restored checkpoint must match this bridge's state template
        # (same n, same compressor config) structurally and shape-wise.
        expect = jax.tree_util.tree_map(
            lambda x: (jnp.shape(x), jnp.result_type(x)), self._state)
        got = jax.tree_util.tree_map(
            lambda x: (jnp.shape(x), jnp.result_type(x)), value)
        if expect != got:
            raise ValueError(
                "restored grace state does not match this bridge's layout "
                f"(n={self.n}, world={self.world}); expected "
                f"{expect}, got {got}")
        self._state = value
