"""Synthetic throughput benchmark: ResNet / BERT on random data.

TPU-native port of the reference's examples/torch/pytorch_synthetic_benchmark.py
(and the TF2 twin): fixed random batch, timed iterations, img/sec mean
±1.96σ. Covers BASELINE.json configs 2/3/5 via the grace flags, e.g.:

    python examples/synthetic_benchmark.py --model resnet50 \\
        --compressor topk --compress-ratio 0.01 --memory residual
    python examples/synthetic_benchmark.py --model resnet50 \\
        --compressor qsgd --quantum-num 128
    python examples/synthetic_benchmark.py --model resnet50 \\
        --compressor signsgd --memory residual
    python examples/synthetic_benchmark.py --model bert \\
        --compressor powersgd --memory powersgd --communicator allreduce
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import common  # noqa: E402 — sys.path bootstrap so grace_tpu imports resolve
from grace_tpu import grace_from_params
from grace_tpu.models import resnet, transformer, vgg
from grace_tpu.parallel import (batch_sharded, data_parallel_mesh,
                                initialize_distributed)
from grace_tpu.train import (init_stateful_train_state,
                             make_stateful_train_step)
from grace_tpu.utils import rank_zero_print, wire_report



def build(args, mesh):
    if args.model.startswith("resnet") or args.model.startswith("vgg"):
        prefix = "resnet" if args.model.startswith("resnet") else "vgg"
        net = resnet if prefix == "resnet" else vgg
        spec = args.model[len(prefix):]
        kwargs = {}
        if prefix == "vgg":
            # torchvision naming: vgg16 is plain, vgg16_bn has BatchNorm
            kwargs["batch_norm"] = spec.endswith("_bn")
            spec = spec.removesuffix("_bn")
        if not spec.isdigit() or int(spec) not in net.SUPPORTED_DEPTHS:
            raise SystemExit(f"unknown --model {args.model}")
        params, mstate = net.init(jax.random.key(args.seed), depth=int(spec),
                                  num_classes=args.num_classes, **kwargs)

        def loss_fn(params, mstate, batch):
            x, y = batch
            logits, new_mstate = net.apply(
                params, mstate, x.astype(common.compute_dtype()), train=True)
            loss = optax.softmax_cross_entropy_with_integer_labels(logits, y)
            return loss.mean(), new_mstate

        rng = np.random.default_rng(args.seed)
        n = args.batch_size * mesh.devices.size
        data = (jnp.asarray(rng.standard_normal(
                    (n, args.image_size, args.image_size, 3)), jnp.float32),
                jnp.asarray(rng.integers(0, args.num_classes, (n,)),
                            jnp.int32))
    elif args.model == "benchnet":
        # The exact architecture of torch_synthetic_benchmark.py's BenchNet
        # (conv 3→32 s2, conv 32→64 s2, global mean pool, fc 64→512→512→C,
        # biased convs like torch.nn.Conv2d) so `--model benchnet` here vs
        # the torch script is a same-model frontend-overhead comparison
        # (TRAINING.md "Interop overhead").
        from grace_tpu.models import layers as L
        keys = L.split_keys(jax.random.key(args.seed), 5)
        params = {"conv1": L.conv_init(keys[0], 3, 3, 3, 32, use_bias=True),
                  "conv2": L.conv_init(keys[1], 3, 3, 32, 64, use_bias=True),
                  "fc1": L.dense_init(keys[2], 64, 512),
                  "fc2": L.dense_init(keys[3], 512, 512),
                  "fc3": L.dense_init(keys[4], 512, args.num_classes)}
        mstate = {}

        def loss_fn(params, mstate, batch):
            x, y = batch
            x = x.astype(common.compute_dtype())
            x = jax.nn.relu(L.conv_apply(params["conv1"], x, stride=2))
            x = jax.nn.relu(L.conv_apply(params["conv2"], x, stride=2))
            x = x.mean(axis=(1, 2))
            x = jax.nn.relu(L.dense_apply(params["fc1"], x))
            x = jax.nn.relu(L.dense_apply(params["fc2"], x))
            logits = L.dense_apply(params["fc3"], x).astype(jnp.float32)
            loss = optax.softmax_cross_entropy_with_integer_labels(logits, y)
            return loss.mean(), mstate

        rng = np.random.default_rng(args.seed)
        n = args.batch_size * mesh.devices.size
        data = (jnp.asarray(rng.standard_normal(
                    (n, args.image_size, args.image_size, 3)), jnp.float32),
                jnp.asarray(rng.integers(0, args.num_classes, (n,)),
                            jnp.int32))
    elif args.model == "bert":
        cfg = transformer.base(num_classes=args.num_classes)
        params, mstate = transformer.init(jax.random.key(args.seed), cfg)

        def loss_fn(params, mstate, batch):
            ids, y = batch
            logits, new_mstate = transformer.apply(
                params, mstate, ids, cfg=cfg, dtype=common.compute_dtype())
            loss = optax.softmax_cross_entropy_with_integer_labels(logits, y)
            return loss.mean(), new_mstate

        rng = np.random.default_rng(args.seed)
        n = args.batch_size * mesh.devices.size
        data = (jnp.asarray(rng.integers(0, cfg.vocab_size,
                                         (n, args.seq_len)), jnp.int32),
                jnp.asarray(rng.integers(0, args.num_classes, (n,)),
                            jnp.int32))
    else:
        raise SystemExit(f"unknown --model {args.model}")
    return params, mstate, loss_fn, data


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    common.add_grace_args(parser)
    parser.add_argument("--model", default="resnet50",
                        help="resnet50|resnet101|resnet152|vgg{11,13,16,19}"
                             "[_bn]|bert|benchnet (the torch interop "
                             "benchmark's model, for frontend comparisons)")
    parser.add_argument("--batch-size", type=int, default=32,
                        help="per-device batch (reference default 32)")
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--num-iters", type=int, default=10,
                        help="timed iterations (reference protocol: 10)")
    parser.add_argument("--num-batches-per-iter", type=int, default=10)
    parser.add_argument("--num-warmup-batches", type=int, default=10)
    parser.add_argument("--lr", type=float, default=0.01)
    args = parser.parse_args()

    initialize_distributed()
    mesh = data_parallel_mesh()
    params, mstate, loss_fn, data = build(args, mesh)

    grace = grace_from_params(common.grace_params_from_args(args))
    optimizer = optax.chain(grace.transform(seed=args.seed),
                            optax.sgd(args.lr))
    step = make_stateful_train_step(loss_fn, optimizer, mesh)
    ts = init_stateful_train_state(params, mstate, optimizer, mesh)
    batch = jax.device_put(data, batch_sharded(mesh))

    rank_zero_print(f"Model: {args.model}, global batch "
                    f"{batch[1].shape[0]} over {mesh.devices.size} devices")
    rank_zero_print("wire cost:", wire_report(grace.compressor, params))

    for _ in range(args.num_warmup_batches):
        ts, loss = step(ts, batch)
    jax.block_until_ready(ts)

    items = batch[1].shape[0] * args.num_batches_per_iter
    unit = "seq" if args.model == "bert" else "img"
    per_iter = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            ts, loss = step(ts, batch)
        jax.block_until_ready((ts, loss))
        per_iter.append(items / (time.perf_counter() - t0))
        rank_zero_print(f"Iter #{i}: {per_iter[-1]:.1f} {unit}/sec")

    mean = float(np.mean(per_iter))
    rank_zero_print(f"{unit}/sec: {mean:.1f} "
                    f"+-{1.96 * float(np.std(per_iter)):.1f}")
    rank_zero_print(f"{unit}/sec/device: {mean / mesh.devices.size:.1f}")


if __name__ == "__main__":
    main()
