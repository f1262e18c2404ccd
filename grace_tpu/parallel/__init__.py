"""Mesh construction and multi-host initialization helpers.

TPU-native replacement for the reference's cluster bring-up: ``hvd.init()``
(MPI topology, examples/torch/pytorch_mnist.py:50) and
``dist.init_process_group('nccl', 'tcp://…')``
(examples/dist/CIFAR10-dawndist/core.py:225-226). On TPU, process discovery
and ICI/DCN topology come from `jax.distributed.initialize` + the device
mesh; collectives ride ICI within a slice and DCN across slices with no
NCCL/MPI anywhere.

The default mesh is 1-D over axis ``'data'`` — GRACE's scope is exactly
synchronous data parallelism (SURVEY.md §2.5) — but axes are named so model/
sequence axes can be added later without API change.
"""

from __future__ import annotations

import os
import sys
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from grace_tpu.core import DEFAULT_AXIS

__all__ = ["DEFAULT_AXIS", "data_parallel_mesh", "make_mesh",
           "initialize_distributed", "replicated", "batch_sharded",
           "local_world_size", "broadcast_tree", "metric_average",
           "relax_cpu_collective_timeouts", "shard_map",
           "set_cpu_device_count"]


def set_cpu_device_count(n: int) -> None:
    """Request ``n`` virtual XLA:CPU devices. Must run before the CPU
    backend initializes (before the first ``jax.devices()``/array
    creation) — importing jax earlier is fine."""
    jax.config.update("jax_num_cpu_devices", n)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """:func:`jax.shard_map` with the replication check off by default —
    the one spelling every shard_map in grace-tpu goes through."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def relax_cpu_collective_timeouts(warn_s: int = 300,
                                  terminate_s: int = 1200) -> None:
    """Raise XLA:CPU's in-process collective rendezvous timeouts.

    The simulated N-device CPU mesh runs each "device" as a host thread; on
    a host with few cores a heavy step can keep
    half the device threads from reaching an all-reduce rendezvous within
    XLA's default 20s warn / 40s terminate window, which kills the process
    mid-collective (seen: LeNet/MNIST on the 8-device mesh). XLA reads
    these flags from $XLA_FLAGS at backend initialization, so call this
    before the first `jax.devices()` — importing jax earlier is fine.
    No-op for flags the caller already set explicitly.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    extra = []
    if "--xla_cpu_collective_call_warn_stuck_timeout_seconds" not in flags:
        extra.append("--xla_cpu_collective_call_warn_stuck_timeout_seconds"
                     f"={warn_s}")
    if "--xla_cpu_collective_call_terminate_timeout_seconds" not in flags:
        extra.append("--xla_cpu_collective_call_terminate_timeout_seconds"
                     f"={terminate_s}")
    if extra:
        os.environ["XLA_FLAGS"] = " ".join([flags, *extra]).strip()


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Multi-host bring-up (replaces hvd.init / init_process_group).

    On Cloud TPU all arguments are auto-detected from the metadata server;
    pass them explicitly for other clusters. Must run before any JAX
    computation (do NOT touch jax.devices()/process_count() first — that
    initializes the local backend and forecloses cluster bring-up).

    With no arguments and no detectable cluster environment this is a no-op
    (single-process run). With explicit arguments, failures propagate: a
    mis-configured multi-host job must die loudly rather than silently train
    as independent single-host replicas.
    """
    if coordinator_address is None and num_processes is None and process_id is None:
        # Markers that say "this process believes it is part of a cluster".
        # If any is set, an auto-init failure means a MIS-configured cluster
        # (e.g. SLURM_JOB_ID without the rank/size vars) — dying loudly
        # beats silently training as independent single-process replicas.
        # Only a genuinely marker-free environment downgrades to a no-op.
        markers = [v for v in ("SLURM_JOB_ID", "SLURM_PROCID",
                               "OMPI_COMM_WORLD_RANK", "OMPI_COMM_WORLD_SIZE",
                               "PMI_RANK", "PMI_SIZE",
                               "JAX_COORDINATOR_ADDRESS",
                               "MEGASCALE_COORDINATOR_ADDRESS")
                   if os.environ.get(v) is not None]
        try:
            jax.distributed.initialize()
        except Exception as e:
            if markers:
                raise RuntimeError(
                    f"cluster environment markers {markers} are set but "
                    f"jax.distributed.initialize() failed — refusing to "
                    f"fall back to a single-process run") from e
            print(f"[grace-tpu] no cluster environment auto-detected "
                  f"({type(e).__name__}: {e}); single-process run",
                  file=sys.stderr)
            return
    else:
        jax.distributed.initialize(coordinator_address, num_processes, process_id)


def data_parallel_mesh(devices: Optional[Sequence[jax.Device]] = None,
                       axis_name: str = DEFAULT_AXIS) -> Mesh:
    """1-D mesh over all (global) devices — the GRACE data-parallel world."""
    devices = list(devices) if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (axis_name,))


def make_mesh(shape: Sequence[int], axis_names: Sequence[str],
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """N-D mesh for layouts beyond pure DP (e.g. ('data', 'model'))."""
    devices = list(devices) if devices is not None else jax.devices()
    arr = np.asarray(devices).reshape(tuple(shape))
    return Mesh(arr, tuple(axis_names))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh, axis_name: str = DEFAULT_AXIS) -> NamedSharding:
    """Shard the leading (batch) dim over the data axis."""
    return NamedSharding(mesh, P(axis_name))


def local_world_size(mesh: Mesh, axis_name: str = DEFAULT_AXIS) -> int:
    return mesh.shape[axis_name]


def broadcast_tree(tree, root_process: int = 0):
    """Broadcast a host pytree from one process to all (multi-host init sync).

    The pure-JAX analog of the reference's init-time parameter broadcast
    (examples/torch/pytorch_mnist.py:116 ``hvd.broadcast_parameters``, and
    the BroadcastGlobalVariablesCallback of
    examples/tensorflow/tensorflow2_keras_mnist.py:73). Initializing params
    from the same seed on every process already makes replicas identical by
    construction; use this when init is *not* deterministic across hosts
    (e.g. restored from a host-local file) to make the sync explicit.

    Single-process: identity. Multi-process: every leaf is replaced by
    ``root_process``'s value on all hosts.
    """
    if jax.process_count() == 1:
        return tree
    from jax.experimental import multihost_utils
    return multihost_utils.broadcast_one_to_all(
        tree, is_source=jax.process_index() == root_process)


def metric_average(metrics):
    """Average a host-side metrics pytree across processes.

    The reference's ``metric_average`` idiom
    (examples/torch/pytorch_mnist.py:163-166: allreduce a scalar, return the
    mean). For metrics computed inside a jitted eval step prefer
    :func:`grace_tpu.train.make_eval_step`, which pmeans on-device; this
    helper is for host-side values (e.g. per-process validation accuracy
    over a host-sharded eval set).
    """
    if jax.process_count() == 1:
        return jax.tree_util.tree_map(lambda x: np.asarray(x), metrics)
    from jax.experimental import multihost_utils
    gathered = multihost_utils.process_allgather(metrics)
    return jax.tree_util.tree_map(
        lambda g: np.mean(np.asarray(g), axis=0), gathered)
