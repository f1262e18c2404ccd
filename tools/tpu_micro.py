"""Microbenchmark the Top-K pipeline pieces on the chip.

The headline gap (chunk Top-K 0.55x dense, BENCH_TPU_LAST.json 2026-07-31)
is ~10 ms/step of overhead on a 25.5M-element fused gradient. This times
each stage of the GRACE pipeline in isolation so the fix targets the
measured hot spot instead of a guess.

Method: repetition runs ON DEVICE via lax.fori_loop with a data-dependent
carry, one dispatch per measurement — a Python-loop-of-dispatches floors
every op at the host's per-dispatch overhead and reads pure noise. The carry feeds each iteration's input so XLA cannot hoist the
body out of the loop; the reported per-iter time includes one carry add
(~0.1 ms), negligible against the ops under test.

Usage (on the chip): python tools/tpu_micro.py
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N = 25_557_032          # ResNet-50 fused gradient element count
K = N // 100
ITERS = 20
ALLOW_CPU = False       # --allow-cpu: script self-test off-chip (tiny N)


OUT_PATH = None         # --out: mirror every result line to this file


def _report(line: str) -> None:
    print(line, flush=True)
    if OUT_PATH:
        # Lines accumulate in a .tmp sibling; __main__ os.replace()s it
        # over OUT_PATH only after a COMPLETE run, so a crashed/timed-out
        # run can neither clobber the previous complete breakdown nor
        # leave a fresh-stamped partial that reads as authoritative.
        with open(OUT_PATH + ".tmp", "a") as f:
            f.write(line + "\n")


def timed(name, make_body, *args, carry0=None):
    """make_body(carry, *args) -> new carry (same shape/dtype as carry)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def run(c0, *a):
        def body(i, c):
            # i-dependent perturbation pins the body inside the loop
            # (float leaves only: int leaves like a step counter must keep
            # their dtype or the fori_loop carry type check fails).
            def pin(x):
                if jnp.issubdtype(jnp.result_type(x), jnp.floating):
                    return x + i * 1e-12
                return x
            return jax.tree.map(pin, make_body(c, *a))
        return lax.fori_loop(0, ITERS, body, c0)

    c0 = jnp.zeros((N,), jnp.float32) if carry0 is None else carry0
    out = run(c0, *args)
    first = jax.tree.leaves(out)[0]
    float(first.reshape(-1)[0])
    t0 = time.perf_counter()
    out = run(c0, *args)
    float(jax.tree.leaves(out)[0].reshape(-1)[0])
    dt = (time.perf_counter() - t0) / ITERS
    _report(f"{name:34s} {dt*1e3:8.3f} ms/iter")


def main() -> None:
    import jax

    from grace_tpu.utils.compile_cache import place_compile_cache

    if ALLOW_CPU:
        # The script's self-test: never on the chip.
        jax.config.update("jax_platforms", "cpu")
    place_compile_cache("cpu" if ALLOW_CPU else "tpu")

    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    if not ALLOW_CPU:
        assert jax.devices()[0].platform == "tpu"
    flat = jax.random.normal(jax.random.key(0), (N,), jnp.float32)
    resid = jax.random.normal(jax.random.key(1), (N,), jnp.float32)
    k = K
    rows = -(-N // k)
    idx0 = jnp.arange(k, dtype=jnp.int32) * rows  # spread, in-range indices
    vals0 = jnp.ones((k,), jnp.float32)
    wr0 = jnp.zeros((k,), jnp.int32)

    _report(f"n={N} k={k} rows={rows} iters={ITERS}")

    timed("carry add only (baseline)", lambda c: c)
    timed("elementwise add", lambda c: c + resid)
    timed("abs+pad+argmax (chunk select)", lambda c: c.at[0].add(
        jnp.argmax(jnp.full((rows * k,), -1.0, c.dtype).at[:N]
                   .set(jnp.abs(c)).reshape(rows, k), axis=0)
        .astype(c.dtype).sum() * 1e-20))
    timed("approx_max_k", lambda c: c.at[0].add(
        lax.approx_max_k(jnp.abs(c), k, recall_target=0.95)[0].sum() * 1e-20))
    timed("gather flat[idx] (k)", lambda c: c.at[0].add(
        c[idx0].sum() * 1e-20))
    timed("scatter k into n", lambda c:
          jnp.zeros((N,), c.dtype).at[idx0].set(c[:k] * 0 + vals0) + c * 1e-20)
    timed("one-hot k into n", lambda c:
          jnp.where(jnp.arange(rows, dtype=jnp.int32)[:, None]
                    == (wr0 + c[0].astype(jnp.int32) * 0)[None, :],
                    vals0[None, :], 0.0).reshape(-1)[:N] + c * 1e-20)

    def full_pipeline(c):
        comp = c + resid
        body = jnp.full((rows * k,), -1.0, comp.dtype)
        body = body.at[:N].set(jnp.abs(comp)).reshape(rows, k)
        win_row = jnp.argmax(body, axis=0).astype(jnp.int32)
        idx = win_row * k + jnp.arange(k, dtype=jnp.int32)
        vals = comp[idx]
        mask = jnp.arange(rows, dtype=jnp.int32)[:, None] == win_row[None, :]
        dense = jnp.where(mask, vals[None, :], 0.0).reshape(-1)[:N]
        return comp - dense          # new residual: the carried state

    timed("full chunk pipeline", full_pipeline)

    def gatherfree_pipeline(c):
        comp = c + resid
        sbody = jnp.zeros((rows * k,), comp.dtype).at[:N].set(comp)
        sbody = sbody.reshape(rows, k)
        win_row = jnp.argmax(jnp.abs(sbody).at[-1].add(-1e-9), axis=0)
        mask = (jnp.arange(rows, dtype=jnp.int32)[:, None]
                == win_row.astype(jnp.int32)[None, :])
        dense = jnp.where(mask, sbody, 0.0)
        vals = jnp.sum(dense, axis=0)             # wire values, gather-free
        return comp - (dense.reshape(-1)[:N] + vals[0] * 1e-20)

    timed("gather-free chunk pipeline", gatherfree_pipeline)

    # ---- round-4 additions: the pieces the headline ACTUALLY runs -------
    # (fusion='flat' + chunk Top-K with the fused Pallas kernels; the rows
    # above are the staged building blocks, these are the deployed paths.)
    from grace_tpu.ops.pallas_topk import (chunk_aggregate_dense,
                                           chunk_compress_feedback)

    def pallas_fused(c):
        vals, win, new_resid = chunk_compress_feedback(
            flat, c, k, interpret=ALLOW_CPU)
        return new_resid + vals[0] * 1e-20

    timed("pallas fused compress+residual", pallas_fused)

    world = 8
    gvals = jax.random.normal(jax.random.key(5), (world, k), jnp.float32)
    gwin = jax.random.randint(jax.random.key(6), (world, k), 0, rows,
                              dtype=jnp.int32)

    def pallas_agg(c):
        # c[0]-dependence keeps the aggregate inside the loop.
        dense = chunk_aggregate_dense(gvals + c[0] * 1e-20, gwin, k, N,
                                      average=True, interpret=ALLOW_CPU)
        return c * 1e-20 + dense

    timed(f"pallas aggregate W={world}", pallas_agg)

    # Leaf plumbing around the fused buffer: ResNet-50's real leaf shapes —
    # unless N was overridden (script self-test off-chip), in which case
    # synthesize a same-cardinality split of N so every stage scales down.
    if N == 25_557_032:
        from grace_tpu.models import resnet
        pshapes = jax.eval_shape(
            lambda key: resnet.init(key, depth=50, num_classes=1000)[0],
            jax.random.key(0))
        shapes = [s.shape for s in jax.tree.leaves(pshapes)]
    else:
        n_leaves = 160
        per = max(1, N // n_leaves)
        shapes = [(per,)] * (n_leaves - 1) + [(N - per * (n_leaves - 1),)]
    total = sum(int(np.prod(s, dtype=np.int64)) if s else 1 for s in shapes)
    _report(f"resnet50 leaves={len(shapes)} total={total}")
    leaves = [jax.random.normal(jax.random.key(10 + j), s, jnp.float32)
              for j, s in enumerate(shapes)]

    def concat_leaves(c):
        scaled = [leaves[0] * (1.0 + c[0] * 1e-20)] + leaves[1:]
        flat_all = jnp.concatenate([jnp.ravel(l) for l in scaled])
        return c * 1e-20 + jnp.zeros((N,), jnp.float32
                                     ).at[:flat_all.size].set(flat_all[:N])

    timed(f"concat {len(shapes)} leaves", concat_leaves)

    def concat_split(lvs):
        flat_all = jnp.concatenate([jnp.ravel(l) for l in lvs])
        out, off = [], 0
        for s in shapes:
            size = int(np.prod(s, dtype=np.int64)) if s else 1
            out.append(flat_all[off:off + size].reshape(s))
            off += size
        return out

    timed("concat+split round trip", concat_split, carry0=leaves)

    # End-to-end transform.update — everything the compressed step does on
    # top of forward/backward/SGD: compensate, chunk-select (Pallas),
    # extract, residual, allgather (1 device), aggregate-decompress,
    # plus the concat/split plumbing. Init runs inside the timed fn but is
    # amortized over ITERS and is just zeros. Carry feeds each step's
    # output gradients back in, so the loop is honest.
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map
    from grace_tpu import grace_from_params
    from grace_tpu.parallel import data_parallel_mesh

    mesh = data_parallel_mesh()

    for fusion, label in (("flat", "transform update (fusion=flat)"),
                          (None, "transform update (per-leaf)")):
        grc = grace_from_params({"compressor": "topk",
                                 "compress_ratio": 0.01,
                                 "topk_algorithm": "chunk",
                                 "memory": "residual",
                                 "communicator": "allgather",
                                 "fusion": fusion})
        tx = grc.transform(seed=0)

        def inner(lvs, _tx=tx):
            st = _tx.init(lvs)

            def body(i, carry):
                st, lv = carry
                out, st2 = _tx.update(lv, st)
                out = [o + i * 1e-12 for o in out]
                return (st2, out)

            _, out = lax.fori_loop(0, ITERS, body, (st, lvs))
            return out

        fn = jax.jit(shard_map(inner, mesh=mesh,
                               in_specs=(P(),), out_specs=P(),
                               check_rep=False))
        t_out = fn(leaves)
        float(jax.tree.leaves(t_out)[0].reshape(-1)[0])
        t0 = time.perf_counter()
        t_out = fn(leaves)
        float(jax.tree.leaves(t_out)[0].reshape(-1)[0])
        dt = (time.perf_counter() - t0) / ITERS
        _report(f"{label:34s} {dt*1e3:8.3f} ms/iter")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=N)
    ap.add_argument("--iters", type=int, default=ITERS)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="self-test the script off-chip (pair with small"
                         " --n; timings are meaningless)")
    ap.add_argument("--out", default=None,
                    help="also append every result line to this file "
                         "(the watcher points it at TPU_MICRO.txt)")
    a = ap.parse_args()
    N, K, ITERS, ALLOW_CPU = a.n, max(1, a.n // 100), a.iters, a.allow_cpu
    OUT_PATH = a.out
    if OUT_PATH:
        with open(OUT_PATH + ".tmp", "w") as f:
            f.write(f"=== tpu_micro run "
                    f"{time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime())}\n")
    main()
    if OUT_PATH:
        os.replace(OUT_PATH + ".tmp", OUT_PATH)
