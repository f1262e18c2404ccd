"""grace-tpu: TPU-native gradient compression for data-parallel training.

A ground-up JAX/XLA re-design of the GRACE framework (sands-lab/grace): the
Communicator / Compressor / Memory decomposition of compressed data-parallel
training, the full algorithm catalog, and drop-in optax integration — with
collectives over named TPU mesh axes instead of NCCL/MPI, pure jitted codecs
instead of stateful per-tensor Python, and explicit state pytrees instead of
name-keyed dicts. See SURVEY.md at the repo root for the full mapping to the
reference.
"""

# First, before anything else of the package runs: the host ledger's clock
# for "grace_tpu began to import" is the moment this module loads.
from grace_tpu.telemetry import host as _host

_importing = _host.LEDGER.begin("import", at=_host.LEDGER.made)

from grace_tpu.core import Communicator, Compressor, Memory
from grace_tpu.comm import (Allgather, Allreduce, Broadcast,
                            HierarchicalAllreduce, Identity, RingAllreduce,
                            SignAllreduce, TwoShotAllreduce,
                            masked_broadcast)
from grace_tpu.helper import Grace, grace_from_params
from grace_tpu.resilience import (ChaosCommunicator, ChaosCompressor,
                                  ChaosParams, ConsensusConfig, GuardState,
                                  audit_report, consensus_step,
                                  guard_transform, guarded_chain)
from grace_tpu.telemetry import (JSONLSink, MultiSink, TelemetryConfig,
                                 TelemetryReader, TelemetryState,
                                 TensorBoardSink, trace_stage)
from grace_tpu.transform import GraceState, grace_transform
from grace_tpu.train import (TrainState, init_train_state, make_eval_step,
                             make_train_step)
from grace_tpu.parallel import data_parallel_mesh, make_mesh

__version__ = "0.1.0"

__all__ = [
    "Communicator", "Compressor", "Memory",
    "Allreduce", "Allgather", "Broadcast", "Identity", "SignAllreduce",
    "TwoShotAllreduce", "RingAllreduce", "HierarchicalAllreduce",
    "Grace", "grace_from_params", "grace_transform", "GraceState",
    "GuardState", "guard_transform", "guarded_chain",
    "ChaosCompressor", "ChaosCommunicator", "ChaosParams",
    "ConsensusConfig", "consensus_step", "audit_report", "masked_broadcast",
    "TelemetryConfig", "TelemetryState", "TelemetryReader",
    "JSONLSink", "TensorBoardSink", "MultiSink", "trace_stage",
    "TrainState", "init_train_state", "make_train_step", "make_eval_step",
    "data_parallel_mesh", "make_mesh",
    "__version__",
]

_host.LEDGER.end(_importing)
del _importing
