"""Capture a jax.profiler trace of the headline bench configs on the chip.

VERDICT round-2 item 2's contingency: if the TPU compressed/dense ratio
lands below the 0.90 target, the next step is a device trace of the Top-K
1% step on the fused 25.5M-element buffer (prime suspects: approx_max_k on
the full buffer, the scatter in decompress — grace_tpu/ops/sparse.py).
This script reuses bench.py's measurement core but wraps the timed window
in a profiler trace so the per-op timeline is on disk for offline analysis.
`--report` runs the shared trace analyzer
(grace_tpu.profiling.trace_analysis — the same stage attribution, overlap
fraction, and step percentiles tools/perf_report.py gates CI with) against
the newest saved capture; it needs no devices, so the report works on any
CPU box holding the profiles directory.

Usage (on the chip):  python tools/tpu_profile.py [--config topk1pct]
Offline anywhere:     python tools/tpu_profile.py --report [--outdir profiles]
Output: profiles/<config>/plugins/profile/... (xplane + trace.json.gz)
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def profile_config(cfg_name: str, outdir: str) -> None:
    import jax

    cfg = next(c for c in bench.HEADLINE if c["name"] == cfg_name)
    captured = []

    def emit(row):
        captured.append(row)

    # Build + warm up via the shared core, but trace only a short window:
    # bench_configs compiles and measures; we re-run a few steps under the
    # profiler afterwards using the same jitted step via a tiny shim.
    devices = bench.setup_platform("tpu")
    import jax.numpy as jnp
    import numpy as np
    import optax

    from grace_tpu.parallel import batch_sharded, data_parallel_mesh
    from grace_tpu import grace_from_params
    from grace_tpu.models import resnet
    from grace_tpu.train import (init_stateful_train_state,
                                 make_stateful_train_step)

    mesh = data_parallel_mesh(devices)
    grace = grace_from_params(cfg["params"])
    optimizer = optax.chain(grace.transform(seed=0), optax.sgd(1e-3))

    def loss_fn(params, mstate, batch):
        x, y = batch
        logits, new_mstate = resnet.apply(
            params, mstate, x.astype(jnp.bfloat16), train=True)
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        return loss.mean(), new_mstate

    step = make_stateful_train_step(loss_fn, optimizer, mesh)
    params, mstate = resnet.init(jax.random.key(0), depth=50,
                                 num_classes=1000)
    ts = init_stateful_train_state(params, mstate, optimizer, mesh)

    n = 32 * len(devices)
    rng = np.random.default_rng(0)
    batch = jax.device_put(
        (jnp.asarray(rng.standard_normal((n, 224, 224, 3)), jnp.float32),
         jnp.asarray(rng.integers(0, 1000, (n,)), jnp.int32)),
        batch_sharded(mesh))

    for _ in range(3):                       # compile + settle
        ts, loss = step(ts, batch)
    float(loss)

    os.makedirs(outdir, exist_ok=True)
    with jax.profiler.trace(outdir):
        for _ in range(5):
            ts, loss = step(ts, batch)
        float(loss)
    print(f"[profile] {cfg_name}: trace -> {outdir}", file=sys.stderr)


def report(outdir: str) -> None:
    """Stage-attributed report of the newest capture under ``outdir`` via
    the shared trace analyzer — per-stage device time (canonical
    ``grace/...`` vocabulary), compute/collective split, overlap fraction,
    step percentiles. Works offline on CPU against a saved trace; the
    ad-hoc top-ops-by-name summary this replaces could not attribute time
    to pipeline stages nor see overlap at all."""
    from grace_tpu.profiling import analyze_trace, find_latest_trace

    path = find_latest_trace(outdir)
    if path is None:
        print(f"no *.trace.json.gz / *.xplane.pb under {outdir}",
              file=sys.stderr)
        return
    analysis = analyze_trace(path)
    print(f"{path}:")
    print(analysis.render())


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default=None,
                    help="one headline config (default: both)")
    ap.add_argument("--outdir", default="profiles")
    ap.add_argument("--report", action="store_true",
                    help="summarize existing traces instead of capturing")
    args = ap.parse_args()
    names = [args.config] if args.config else [c["name"]
                                               for c in bench.HEADLINE]
    for name in names:
        d = os.path.join(args.outdir, name)
        if args.report:
            report(d)
        else:
            profile_config(name, d)


if __name__ == "__main__":
    main()
