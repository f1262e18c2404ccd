"""Shared example plumbing: CLI flags, synthetic datasets, idx/CIFAR readers.

The reference's examples each re-declare argparse flags and dataset loading
(SURVEY.md §2.8); this module factors the common part. Data policy: synthetic
datasets by default (runs anywhere, zero downloads), with loaders for the
standard on-disk formats (MNIST idx, CIFAR-10 binary batches) when a
--data-dir is supplied.
"""

from __future__ import annotations

import argparse
import gzip
import os
import struct
import sys

# Examples run as scripts (`python examples/foo.py`), where sys.path[0] is
# examples/ — put the repo root first so `import grace_tpu` resolves without
# an install step. Examples import this module before grace_tpu.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

# The two ways an example runs: on the CPU (JAX_PLATFORMS=cpu, as many
# virtual devices as XLA_FLAGS=--xla_force_host_platform_device_count=N
# asks for — JAX reads both itself) or on whatever accelerator JAX finds.
# Either way the compile cache goes where grace_tpu.utils.compile_cache
# says, before any device touch.
from grace_tpu.parallel import relax_cpu_collective_timeouts
from grace_tpu.utils.compile_cache import place_compile_cache

relax_cpu_collective_timeouts()  # N device threads on a few-core host
place_compile_cache(os.environ.get("JAX_PLATFORMS", "").lower() or "tpu")

import numpy as np

GRACE_FLAG_DOC = """GRACE compression flags (reference params-dict schema,
grace_dl/dist/helper.py): --compressor/--memory/--communicator select the
triad; per-algorithm hyperparameters have the reference defaults."""


def add_grace_args(parser: argparse.ArgumentParser) -> None:
    g = parser.add_argument_group("grace", GRACE_FLAG_DOC)
    g.add_argument("--compressor", default="none",
                   help="none|fp16|topk|randomk|threshold|qsgd|homoqsgd|"
                        "countsketch|terngrad|signsgd|signum|efsignsgd|"
                        "onebit|natural|dgc|powersgd|u8bit|sketch|adaq|"
                        "inceptionn")
    g.add_argument("--memory", default="none",
                   help="none|residual|efsignsgd|dgc|powersgd")
    g.add_argument("--communicator", default="allgather",
                   help="allreduce|allgather|broadcast|sign_allreduce|"
                        "twoshot|ring|hier|identity")
    g.add_argument("--slice-size", type=int, default=None,
                   help="with --communicator hier: ranks per ICI slice "
                        "(the two-level schedule needs whole slices)")
    g.add_argument("--compress-ratio", type=float, default=0.01)
    g.add_argument("--quantum-num", type=int, default=64)
    g.add_argument("--threshold", type=float, default=0.01)
    g.add_argument("--momentum", type=float, default=0.9)
    g.add_argument("--compress-rank", type=int, default=4,
                   help="PowerSGD rank")
    g.add_argument("--fusion", default="flat",
                   help="flat|grouped|none|<bytes> — gradient fusion buffer")
    g.add_argument("--topk-algorithm", default="exact",
                   help="exact|approx|chunk — top-k selection strategy")
    g.add_argument("--recall-target", type=float, default=0.95,
                   help="recall for --topk-algorithm approx")
    g.add_argument("--use-pallas", default="auto",
                   choices=["auto", "on", "off"],
                   help="fused Pallas kernels (qsgd quantize, chunk top-k "
                        "local pipeline): auto = each compressor's default "
                        "(staged since round 4's on-chip A/B); on = force")
    g.add_argument("--memory-dtype", default=None,
                   help="storage dtype for the residual memory state "
                        "(e.g. bfloat16 halves its HBM traffic; round-4 "
                        "grace-tpu extension, ResidualMemory.state_dtype)")
    g.add_argument("--seed", type=int, default=42)


def grace_params_from_args(args) -> dict:
    fusion = args.fusion
    if fusion in ("none", "None", ""):
        fusion = None
    elif fusion not in ("flat", "grouped"):
        fusion = int(fusion)
    params = {
        "compressor": args.compressor,
        "memory": args.memory,
        "communicator": args.communicator,
        "compress_ratio": args.compress_ratio,
        "quantum_num": args.quantum_num,
        "threshold": args.threshold,
        "momentum": args.momentum,
        "compress_rank": args.compress_rank,
        "fusion": fusion,
        "topk_algorithm": args.topk_algorithm,
        "recall_target": args.recall_target,
    }
    if getattr(args, "slice_size", None):
        params["slice_size"] = args.slice_size
    # Only force use_pallas when the operator explicitly asked: the flag's
    # resting default must leave each compressor's own default in charge —
    # 'auto' resolves per the measured on-chip A/Bs (TopK: staged; QSGD:
    # kernel on TPU since the round-5 measurement, see TRAINING.md).
    if args.use_pallas != "auto":
        params["use_pallas"] = args.use_pallas == "on"
    if getattr(args, "memory_dtype", None):
        if args.memory != "residual":
            # Fail fast like the library does for a bad dtype string: the
            # knob only exists on ResidualMemory, and a silently ignored
            # flag would leave the operator believing the state is narrow.
            raise SystemExit(
                f"--memory-dtype applies only to --memory residual "
                f"(got --memory {args.memory})")
        params["memory_dtype"] = args.memory_dtype
    return params


def grace_provenance(args) -> dict:
    """The grace-config fields every curve evidence file must carry —
    one place, so a new curve-affecting knob (round-4 case:
    --memory-dtype) cannot be added without its provenance stamp."""
    prov = {"compressor": args.compressor, "memory": args.memory,
            "communicator": args.communicator,
            # fusion changes selection semantics (flat = global-k,
            # none = per-tensor-k, the round-5 headline mode) — a curve
            # without it is ambiguous evidence.
            "fusion": args.fusion}
    if getattr(args, "memory_dtype", None):
        prov["memory_dtype"] = args.memory_dtype
    if args.compressor == "topk":
        prov["topk_algorithm"] = args.topk_algorithm
    return prov


# ---------------------------------------------------------------------------
# Datasets
# ---------------------------------------------------------------------------

def _synthetic_classification(n, seed, shape, noise, proto_seed):
    """Class-conditional data: 10 fixed prototype images + per-sample noise.
    The prototypes come from ``proto_seed`` so train/test splits built with
    different ``seed`` values share the same underlying task."""
    protos = np.random.default_rng(proto_seed).standard_normal(
        (10, *shape)).astype(np.float32)
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 10, n).astype(np.int32)
    x = protos[y] + noise * rng.standard_normal((n, *shape)).astype(np.float32)
    return x, y


def synthetic_mnist(n: int, seed: int = 0, proto_seed: int = 1234):
    """Synthetic digits, separable enough that LeNet exceeds 95% quickly."""
    return _synthetic_classification(n, seed, (28, 28, 1), 0.3, proto_seed)


def synthetic_cifar10(n: int, seed: int = 0, proto_seed: int = 1234):
    return _synthetic_classification(n, seed, (32, 32, 3), 0.5, proto_seed)


def load_mnist_idx(data_dir: str, train: bool = True):
    """Read the standard MNIST idx(.gz) files from ``data_dir``."""
    prefix = "train" if train else "t10k"

    def _open(name):
        for cand in (os.path.join(data_dir, name),
                     os.path.join(data_dir, name + ".gz")):
            if os.path.exists(cand):
                return gzip.open(cand, "rb") if cand.endswith(".gz") \
                    else open(cand, "rb")
        raise FileNotFoundError(f"{name}[.gz] not found under {data_dir}")

    with _open(f"{prefix}-images-idx3-ubyte") as f:
        magic, n, rows, cols = struct.unpack(">IIII", f.read(16))
        assert magic == 2051, f"bad idx magic {magic}"
        x = np.frombuffer(f.read(), np.uint8).reshape(n, rows, cols, 1)
    with _open(f"{prefix}-labels-idx1-ubyte") as f:
        magic, n = struct.unpack(">II", f.read(8))
        assert magic == 2049, f"bad idx magic {magic}"
        y = np.frombuffer(f.read(), np.uint8).astype(np.int32)
    x = (x.astype(np.float32) / 255.0 - 0.1307) / 0.3081
    return x, y


BUNDLED_MNIST_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "data", "MNIST", "raw")


def load_mnist_auto(data_dir: str, split_seed: int = 0):
    """(x_train, y_train, x_test, y_test), normalized, from whatever MNIST
    files ``data_dir`` holds: the full train/t10k pair when present, else a
    deterministic 8,000/2,000 split of the t10k set alone (the bundled
    fixture case — see grace_tpu.data.mnist_split_dataset)."""
    has_full = any(
        os.path.exists(os.path.join(data_dir, "train-images-idx3-ubyte" + s))
        for s in ("", ".gz"))
    if has_full:
        return (*load_mnist_idx(data_dir, train=True),
                *load_mnist_idx(data_dir, train=False))
    from grace_tpu.data import mnist_split_dataset
    tr = mnist_split_dataset(data_dir, train=True, split_seed=split_seed)
    te = mnist_split_dataset(data_dir, train=False, split_seed=split_seed)
    # Eval uses the train stats (the torchvision convention).
    return (tr.normalize(tr.images), tr.labels,
            tr.normalize(te.images), te.labels)


def load_cifar10_binary(data_dir: str, train: bool = True):
    """Read CIFAR-10 binary batches (data_batch_*.bin / test_batch.bin)."""
    names = [f"data_batch_{i}.bin" for i in range(1, 6)] if train \
        else ["test_batch.bin"]
    xs, ys = [], []
    for name in names:
        path = os.path.join(data_dir, name)
        raw = np.fromfile(path, np.uint8).reshape(-1, 3073)
        ys.append(raw[:, 0].astype(np.int32))
        xs.append(raw[:, 1:].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1))
    x = np.concatenate(xs).astype(np.float32) / 255.0
    y = np.concatenate(ys)
    mean = np.array([0.4914, 0.4822, 0.4465], np.float32)
    std = np.array([0.2471, 0.2435, 0.2616], np.float32)
    return (x - mean) / std, y


def batches(x, y, batch_size: int, *, shuffle: bool, seed: int,
            drop_last: bool = True):
    """Shuffled minibatch iterator over host arrays."""
    n = x.shape[0]
    idx = np.arange(n)
    if shuffle:
        np.random.default_rng(seed).shuffle(idx)
    stop = n - (n % batch_size) if drop_last else n
    for i in range(0, stop, batch_size):
        sel = idx[i:i + batch_size]
        yield x[sel], y[sel]


def compute_dtype():
    """bf16 on TPU (MXU-native), f32 elsewhere (bf16 is emulated-slow on CPU)."""
    import jax
    import jax.numpy as jnp
    return jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
