"""The quickest proof that the compressed training step runs on the chip.

    python chip_smoke.py             # one TPU chip: train, kernels, transformer
    python chip_smoke.py --chips 4   # four chips: the exchange across chips
                                     # and its dense comparison, nothing else

One process, one ``import jax``, no child. Every phase prints one JSON
object on its own stdout line; the LAST line is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The run stops with exit code 1 and ``{"ok": false, ...}`` the moment the
first device is not a TPU, a ``GRACE_DISABLE_PALLAS*`` variable is set, a
phase raises, or a check fails. Nothing falls back: no CPU mesh, no
interpret-mode kernel, no staged path where a kernel was demanded.

Phases (all through the public entry points: ``grace_from_params`` →
``grc.transform`` → ``optax.chain`` → ``init_stateful_train_state`` →
``make_stateful_train_step``):

* ``train`` — ResNet-50, 1000 classes, 224², bf16 activations, f32
  parameters, a fixed synthetic batch from the seed, plain SGD; dense and
  three compressed configs. Checks: losses finite and falling, compressed
  last loss inside ``LOSS_BAND`` of dense, ``tpu_custom_call`` in the
  compiled step exactly where a kernel is expected.
* ``kernels`` — every entry point of ``ops.pallas_topk``, ``pallas_quant``
  and ``pallas_wire`` compiled by Mosaic at ResNet-50's flat gradient size
  and compared on the device with its staged XLA counterpart.
* ``transformer`` — BERT-base encoder, PowerSGD rank 4 (QR, low-rank
  factors, stateful codec: the path no ResNet config touches).
* ``--chips 4``: ResNet-50 over ``data_parallel_mesh()`` of four devices,
  dense against top-k/allgather, packed QSGD/ring with the wire kernels
  live, and a hierarchical schedule. Checks replicas byte-identical,
  error-feedback state spread one shard per device, the collectives and
  kernels each communicator should leave in the compiled text.

Compile seconds and step milliseconds are printed under ``info`` for the
reader's orientation only: they are not benchmark results.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

# ResNet-50's flat gradient size: the buffer every fused kernel sees under
# fusion='flat' on the headline model.
RESNET50_NUMEL = 25_557_032

# Loss band, in units of dense's own drop (first loss − last loss on the
# fixed batch): a compressed config's last loss must lie within
# [dense_last − LOW·drop, dense_last + HIGH·drop]. HIGH = 0.9 demands at
# least a tenth of dense's progress — a codec that ships nothing, or the
# wrong sign, makes none; Top-K 1 % applies only the largest hundredth of
# the coordinates per step and error feedback repays the rest over later
# steps than a ten-step smoke has. LOW = 0.5 catches the opposite fault, a
# decode that over-scales the update (e.g. a sum that was meant to be a
# mean looks like a larger learning rate and can overshoot dense).
LOSS_BAND_LOW = 0.5
LOSS_BAND_HIGH = 0.9

# Plain SGD on the fixed batch, one rate for every config and both phases.
# (On the chip at this rate dense drops 7.61 -> 6.40 in ten steps and even
# 4-bit QSGD over the whole 25.5M-element flat buffer keeps 0.64 of that
# at W=1 — my chip run, PR 21. A CPU rehearsal at eight images per device
# had it diverge; batch-norm over eight images is not this problem.)
SGD_LR = 0.01


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The shapes of one smoke run. The defaults are the real ones; only
    tests/test_chip_smoke.py builds another (a CPU rehearsal)."""
    image_hw: int = 224
    num_classes: int = 1000
    # Per-chip batch: the old headline's, falling only when it does not fit.
    batches: tuple = (256, 128, 32)
    steps: int = 8            # after warm-up
    warmup: int = 2
    kernel_n: int = RESNET50_NUMEL
    bert_layers: int = 12
    bert_hidden: int = 768
    bert_heads: int = 12
    bert_ff: int = 3072
    bert_vocab: int = 30522
    bert_seq: int = 384
    bert_batch: int = 8
    bert_steps: int = 3


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_tpu() -> None:
    """The platform check. tests/test_chip_smoke.py replaces it to rehearse
    the phases on the CPU mesh; nothing else may."""
    platform = jax.devices()[0].platform
    require(platform == "tpu",
            f"first device is {platform!r}, not 'tpu': the smoke measures "
            "nothing off the chip")


def refuse_disabled_kernels() -> None:
    hatch = sorted(k for k, v in os.environ.items()
                   if k.startswith("GRACE_DISABLE_PALLAS") and v.strip())
    require(not hatch, f"{hatch} set: the smoke does not run with the "
            "Pallas kernels switched off")


def device_line() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# train phases
# ---------------------------------------------------------------------------

def _topk_headline():
    """(dense, top-k 1 %) parameter dicts, both per leaf: the pair the
    benchmark's ResNet-50 cells run (`benchmarks/workloads/`)."""
    dense = {"compressor": "none", "memory": "none",
             "communicator": "allreduce", "fusion": "none"}
    topk = {"compressor": "topk", "compress_ratio": 0.01,
            "topk_algorithm": "chunk", "memory": "residual",
            "communicator": "allgather", "fusion": "none"}
    return dense, topk


def one_chip_configs() -> list[dict]:
    dense, topk = _topk_headline()
    return [
        {"name": "dense", "params": dense, "kernel": False},
        {"name": "topk1pct_perleaf", "params": topk, "kernel": False},
        {"name": "topk1pct_flat_pallas", "kernel": True,
         "params": {**topk, "fusion": "flat", "use_pallas": True}},
        # use_pallas is left at its default 'auto': on a TPU that must
        # resolve to the quantize kernel, one call per leaf.
        {"name": "qsgd_auto_perleaf", "kernel": True,
         "params": {"compressor": "qsgd", "quantum_num": 64,
                    "memory": "none", "communicator": "allgather",
                    "fusion": "none"}},
    ]


def four_chip_configs() -> list[dict]:
    dense, topk = _topk_headline()
    return [
        {"name": "dense", "params": dense, "kernel": False,
         "collectives": ("all-reduce",)},
        {"name": "topk1pct_allgather", "params": topk, "kernel": False,
         "collectives": ("all-gather",)},
        # The path PRs 10 and 19 were written for: 4-bit packed QSGD, the
        # encode kernel on every shard and the fused decode→accumulate
        # kernel on every ring hop.
        {"name": "qsgd4_packed_ring_flat", "kernel": True,
         "params": {"compressor": "qsgd", "quantum_num": 7,
                    "use_pallas": True, "memory": "none",
                    "communicator": "ring", "fusion": "flat"},
         # W−1 reduce-scatter hops and W−1 all-gather hops, all ppermutes
         "collectives": ("collective-permute",)},
        {"name": "qsgd4_packed_hier2_flat", "kernel": True,
         "params": {"compressor": "qsgd", "quantum_num": 7,
                    "use_pallas": True, "memory": "none",
                    "communicator": "hier", "slice_size": 2,
                    "fusion": "flat"},
         # intra-slice ring hops; XLA is free to spell the tiny
         # cross-slice gathers as all-reduces, so only the hops are pinned
         "collectives": ("collective-permute",)},
    ]


def _count_ops(text: str, op: str) -> int:
    return text.count(f" {op}(") + text.count(f" {op}-start(")


def synthetic_batch(mesh, make, seed: int):
    """A fixed batch made on the devices, already sharded over the mesh."""
    from grace_tpu.parallel import batch_sharded
    return jax.jit(lambda: make(jax.random.key(seed)),
                   out_shardings=batch_sharded(mesh))()


def train_config(mesh, cfg: dict, init_model, loss_fn, batch,
                 optimizer, n_steps: int, warmup: int, seed: int):
    """Build one config through the public entry points, take
    ``warmup + n_steps`` steps on the fixed batch; returns
    ``(state, losses, compiled_text, info)``."""
    from grace_tpu import grace_from_params
    from grace_tpu.train import (init_stateful_train_state,
                                 make_stateful_train_step)

    grace = grace_from_params(cfg["params"])
    tx = optax.chain(grace.transform(seed=seed), optimizer)
    params, mstate = init_model(jax.random.key(seed))
    step = make_stateful_train_step(loss_fn, tx, mesh)
    ts = init_stateful_train_state(params, mstate, tx, mesh)
    del params, mstate

    # Trace the step the entry point built (this fills step.jit_cache and
    # runs nothing), compile it once ahead of time, and take every step
    # with that executable: the text that is checked is the program that
    # ran, and nothing depends on a second compile hitting a cache.
    jax.eval_shape(step, ts, batch)
    fn = next(iter(step.jit_cache.values()))
    t0 = time.perf_counter()
    compiled = fn.lower(ts, batch).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    temp_bytes = compiled.memory_analysis().temp_size_in_bytes

    losses, step_ms = [], []
    for i in range(warmup + n_steps):
        t = time.perf_counter()
        ts, loss = compiled(ts, batch)
        jax.block_until_ready((ts, loss))
        if i >= warmup:
            step_ms.append((time.perf_counter() - t) * 1e3)
        losses.append(float(loss))
    info = {"compile_s": round(compile_s, 1),
            # memory_stats' peak leaves the program's temporaries out on
            # this runtime; the compiler's own count of them is beside it
            "compiled_temp_bytes": temp_bytes,
            "median_step_ms": round(statistics.median(step_ms), 2),
            "note": "host clock, information only, not a benchmark result"}
    return ts, losses, text, info


def check_finite(name: str, losses: list[float]) -> None:
    require(all(math.isfinite(x) for x in losses),
            f"{name}: non-finite loss in {losses}")


def check_falling(name: str, losses: list[float]) -> None:
    require(losses[-1] < losses[0],
            f"{name}: loss on the fixed batch did not fall: "
            f"{losses[0]} -> {losses[-1]}")


def check_band(name: str, losses: list[float], dense: list[float]) -> dict:
    """Dense fell, so a last loss inside the band (HIGH < 1) fell too."""
    drop = dense[0] - dense[-1]
    lo = dense[-1] - LOSS_BAND_LOW * drop
    hi = dense[-1] + LOSS_BAND_HIGH * drop
    require(lo <= losses[-1] <= hi,
            f"{name}: last loss {losses[-1]:.4f} outside the band "
            f"[{lo:.4f}, {hi:.4f}] of dense ({dense[0]:.4f} -> "
            f"{dense[-1]:.4f})")
    return {"band": [round(lo, 4), round(hi, 4)],
            "share_of_dense_drop": round((losses[0] - losses[-1]) / drop, 3)}


def check_kernel_text(name: str, text: str, expect: bool, on_tpu: bool) -> int:
    n = text.count("tpu_custom_call")
    # Off the chip (the test's CPU rehearsal) kernels run interpreted and
    # leave no custom call; the expectation is the chip's.
    if on_tpu:
        require((n > 0) == expect,
                f"{name}: {n} tpu_custom_call(s) in the compiled step, "
                f"expected {'some' if expect else 'none'}")
    return n


def _is_oom(err: Exception) -> bool:
    return (isinstance(err, jax.errors.JaxRuntimeError)
            and "RESOURCE_EXHAUSTED" in str(err))


def resnet_phase(sizes: Sizes, seed: int, mesh,
                 configs: list[dict], phase: str,
                 after_config=None) -> None:
    """ResNet-50 at full width on ``mesh``; the first config is dense and
    every later one is held to its band. Falls to the next smaller
    per-chip batch only on the device's own out-of-memory error."""
    from grace_tpu.models import resnet

    on_tpu = jax.devices()[0].platform == "tpu"
    world = mesh.devices.size

    def init_model(key):
        return resnet.init(key, depth=50, num_classes=sizes.num_classes)

    def loss_fn(params, mstate, batch):
        x, y = batch
        logits, new_mstate = resnet.apply(
            params, mstate, x.astype(jnp.bfloat16), train=True)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), y)
        return loss.mean(), new_mstate

    def run_at(per_chip: int) -> None:
        n = per_chip * world
        hw = sizes.image_hw

        def make(key):
            kx, ky = jax.random.split(key)
            return (jax.random.normal(kx, (n, hw, hw, 3), jnp.float32),
                    jax.random.randint(ky, (n,), 0, sizes.num_classes,
                                       jnp.int32))

        batch = synthetic_batch(mesh, make, seed)
        dense_losses = None
        for cfg in configs:
            ts, losses, text, info = train_config(
                mesh, cfg, init_model, loss_fn, batch,
                optax.sgd(SGD_LR), sizes.steps, sizes.warmup, seed)
            line = {"phase": phase, "config": cfg["name"],
                    "model": "resnet50", "image_hw": hw,
                    "per_chip_batch": per_chip, "world": world, "lr": SGD_LR,
                    "losses": [round(x, 4) for x in losses]}
            check_finite(cfg["name"], losses)
            if dense_losses is None:
                check_falling(cfg["name"], losses)
                dense_losses = losses
            else:
                line.update(check_band(cfg["name"], losses, dense_losses))
            line["tpu_custom_calls"] = check_kernel_text(
                cfg["name"], text, cfg["kernel"], on_tpu)
            if after_config is not None:
                line.update(after_config(cfg, ts, text))
            line["peak_bytes_in_use"] = peak_bytes()
            line["info"] = info
            line["ok"] = True
            emit(line)
            del ts, text

    for i, per_chip in enumerate(sizes.batches):
        try:
            run_at(per_chip)
            return
        except Exception as e:  # noqa: BLE001 — re-raised unless OOM
            if not _is_oom(e) or i == len(sizes.batches) - 1:
                raise
            emit({"phase": phase, "per_chip_batch": per_chip,
                  "fits": False, "falling_to": sizes.batches[i + 1]})


def transformer_phase(sizes: Sizes, seed: int, mesh) -> None:
    """BERT-base encoder with PowerSGD rank 4 + its memory + allreduce."""
    from grace_tpu.models import layers as L
    from grace_tpu.models import transformer

    seq = sizes.bert_seq
    cfg = transformer.Config(
        vocab_size=sizes.bert_vocab, d_model=sizes.bert_hidden,
        num_heads=sizes.bert_heads, num_layers=sizes.bert_layers,
        d_ff=sizes.bert_ff, max_len=seq, num_classes=2)
    n = sizes.bert_batch * mesh.devices.size

    def make(key):
        ki, ka, kb = jax.random.split(key, 3)
        ids = jax.random.randint(ki, (n, seq), 0, cfg.vocab_size, jnp.int32)
        spans = jnp.stack(
            [jax.random.randint(ka, (n,), 0, seq // 2, jnp.int32),
             jax.random.randint(kb, (n,), seq // 2, seq, jnp.int32)], 1)
        return ids, spans

    def loss_fn(params, mstate, batch):
        ids, spans = batch
        x = transformer.encode(params, ids, cfg, dtype=jnp.bfloat16)
        logits = L.dense_apply(params["cls"], x.astype(jnp.float32))
        loss = (optax.softmax_cross_entropy_with_integer_labels(
                    logits[..., 0], spans[:, 0])
                + optax.softmax_cross_entropy_with_integer_labels(
                    logits[..., 1], spans[:, 1]))
        return loss.mean(), mstate

    run = {"name": "bert_base_powersgd_r4", "kernel": False,
           "params": {"compressor": "powersgd", "compress_rank": 4,
                      "memory": "powersgd", "communicator": "allreduce"}}
    batch = synthetic_batch(mesh, make, seed)
    _, losses, text, info = train_config(
        mesh, run, lambda key: transformer.init(key, cfg),
        loss_fn, batch, optax.adamw(1e-4), sizes.bert_steps - 1, 1, seed)
    check_finite(run["name"], losses)
    check_falling(run["name"], losses)
    emit({"phase": "transformer", "config": run["name"],
          "layers": cfg.num_layers, "hidden": cfg.d_model, "seq": seq,
          "per_chip_batch": sizes.bert_batch,
          "losses": [round(x, 4) for x in losses],
          "tpu_custom_calls": text.count("tpu_custom_call"),
          "peak_bytes_in_use": peak_bytes(), "info": info, "ok": True})


# ---------------------------------------------------------------------------
# four chips: what the exchange must leave behind
# ---------------------------------------------------------------------------

def check_world(mesh, world: int) -> None:
    require(len(jax.devices()) == world,
            f"{len(jax.devices())} devices, need {world}")
    ids = {d.id for d in mesh.devices.flat}
    require(mesh.devices.size == world and len(ids) == world,
            f"mesh spans {len(ids)} distinct devices, need {world}")


def replica_checks(world: int):
    """The per-config checks of the multi-chip phase, as the
    ``after_config`` hook of :func:`resnet_phase`."""
    from grace_tpu.transform import GraceState

    def check(cfg, ts, text) -> dict:
        # 1. every parameter leaf byte-identical on all replicas
        leaves = jax.tree_util.tree_leaves(ts.params)
        for i, leaf in enumerate(leaves):
            shards = leaf.addressable_shards
            require(len(shards) == world,
                    f"{cfg['name']}: param leaf {i} has {len(shards)} "
                    f"shards, need {world}")
            ref = np.asarray(shards[0].data).tobytes()
            for s in shards[1:]:
                require(np.asarray(s.data).tobytes() == ref,
                        f"{cfg['name']}: param leaf {i} differs between "
                        f"device {shards[0].device.id} and {s.device.id}")
        # 2. error-feedback state: leading world axis, one shard per device
        graces = [n for n in jax.tree_util.tree_leaves(
            ts.opt_state, is_leaf=lambda x: isinstance(x, GraceState))
            if isinstance(n, GraceState)]
        require(len(graces) == 1, f"{cfg['name']}: {len(graces)} GraceState")
        mem_leaves = jax.tree_util.tree_leaves(graces[0].mem)
        for i, leaf in enumerate(mem_leaves):
            require(leaf.shape[0] == world,
                    f"{cfg['name']}: mem leaf {i} leading dim "
                    f"{leaf.shape[0]}, need {world}")
            owners = {s.device.id for s in leaf.addressable_shards}
            require(len(leaf.addressable_shards) == world
                    and len(owners) == world
                    and all(s.data.shape[0] == 1
                            for s in leaf.addressable_shards),
                    f"{cfg['name']}: mem leaf {i} is not one shard per "
                    f"device (devices {sorted(owners)})")
        # 3. the collectives this communicator should have left
        counts = {op: _count_ops(text, op)
                  for op in ("all-reduce", "all-gather",
                             "collective-permute", "all-to-all")}
        if world > 1:
            for op in cfg["collectives"]:
                require(counts[op] > 0,
                        f"{cfg['name']}: no {op} in the compiled step "
                        f"({counts})")
        return {"replicas_identical": True, "param_leaves": len(leaves),
                "mem_leaves_sharded": len(mem_leaves),
                "collectives": counts}

    return check


# ---------------------------------------------------------------------------
# kernels phase
# ---------------------------------------------------------------------------

def kernels_phase(sizes: Sizes, seed: int) -> None:
    """Every Pallas entry point at ``sizes.kernel_n`` against its staged
    XLA counterpart, compared on the device.

    Deterministic kernels (top-k select/aggregate, sign pack, every
    decode/accumulate) are held to the contract of tests/test_pallas_topk,
    test_pallas_quant and test_wire: bit-identical (the averaged aggregate
    to 1e-6, as its test). The stochastic quantizers draw from the chip's
    own PRNG, a different stream from the interpreter's hash, so they are
    held to what any correct stochastic rounding satisfies: every level
    inside the floor/ceil envelope of |x|·q/‖x‖ with the sign of x, the
    sum of level − |x|·q/‖x‖ over the buffer within six sigma of zero
    (unbiasedness; a stuck bit source lands hundreds of sigma out), and
    the packed kernel's bytes unpacking to levels
    inside ±q that obey the same envelope, repack to the same bytes, and
    equal the plain kernel's clamped levels at the same seed.
    """
    from grace_tpu.compressors import TopKCompressor
    from grace_tpu.ops import packing
    from grace_tpu.ops.pallas_quant import (quantize_pack_stochastic,
                                            quantize_stochastic, sign_pack)
    from grace_tpu.ops.pallas_topk import (chunk_aggregate_dense,
                                           chunk_compress_feedback)
    from grace_tpu.ops.pallas_wire import (decode_accumulate,
                                           packed_int_accumulate)

    on_tpu = jax.devices()[0].platform == "tpu"
    interpret = not on_tpu        # the chip never interprets
    n = sizes.kernel_n
    key = jax.random.key(seed)

    def same(a, b) -> bool:
        return bool(jnp.array_equal(a, b))

    def compiled_has_kernel(fn, *args, **kw) -> None:
        if on_tpu:
            text = fn.lower(*args, **kw).compile().as_text()
            require("tpu_custom_call" in text,
                    f"{fn.__name__}: no tpu_custom_call in compiled text")

    def done(kernel: str, variant: str, compared: str, t0: float) -> None:
        emit({"phase": "kernels", "kernel": kernel, "variant": variant,
              "n": n, "compared": compared, "interpret": interpret,
              "info": {"seconds_incl_compile":
                       round(time.perf_counter() - t0, 2)},
              "ok": True})

    # Heavy-tailed on purpose: 64 entries carry the norm, so |x|·q/‖x‖
    # spans several levels there (about 0.3·q at the largest) and stays far
    # below one elsewhere — a flat Gaussian of this length never leaves
    # level 0/1.
    flat = jax.random.normal(jax.random.fold_in(key, 0), (n,), jnp.float32)
    flat = flat * jnp.where(jnp.arange(n) < 64, 1.0, 1e-4)
    resid = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (n,),
                                    jnp.float32)

    # -- pallas_topk ------------------------------------------------------
    ratio = 0.01
    k = max(1, int(n * ratio))
    staged = TopKCompressor(compress_ratio=ratio, algorithm="chunk",
                            use_pallas=False)
    t0 = time.perf_counter()
    compiled_has_kernel(chunk_compress_feedback, flat, resid, k,
                        interpret=interpret)
    vals, win, new_resid = chunk_compress_feedback(flat, resid, k,
                                                   interpret=interpret)
    comp = flat + resid
    (rvals, ridx), ctx, _ = staged.compress(comp, None, key)
    idx = win * k + jnp.arange(k, dtype=jnp.int32)
    require(same(idx, ridx), "chunk_compress_feedback: index mismatch")
    require(same(vals, rvals), "chunk_compress_feedback: value mismatch")
    require(same(new_resid, comp - staged.decompress((rvals, ridx), ctx)),
            "chunk_compress_feedback: residual mismatch")
    done("chunk_compress_feedback", "k=1%", "bit-identical to staged "
         "compensate->compress->update", t0)

    for world in (1, 4):
        t0 = time.perf_counter()
        xs = jax.random.normal(jax.random.fold_in(key, 2), (world, n),
                               jnp.float32)
        gvals, gidx = jax.vmap(
            lambda x: staged.compress(x, None, key)[0])(xs)
        del xs
        gwin = (gidx // k).astype(jnp.int32)
        compiled_has_kernel(chunk_aggregate_dense, gvals, gwin, k, n,
                            average=True, interpret=interpret)
        fused = chunk_aggregate_dense(gvals, gwin, k, n, average=True,
                                      interpret=interpret)
        want = jnp.mean(jax.vmap(
            lambda v, i: staged.decompress((v, i), ctx))(gvals, gidx), axis=0)
        require(bool(jnp.allclose(fused, want, atol=1e-6, rtol=1e-6)),
                f"chunk_aggregate_dense W={world}: mismatch")
        done("chunk_aggregate_dense", f"W={world}", "allclose(atol=1e-6) "
             "to staged vmap-decompress mean", t0)
        del gvals, gidx, gwin, fused, want

    # -- pallas_quant -----------------------------------------------------
    norm = jnp.linalg.norm(flat)
    seed32 = jnp.int32(seed + 7)

    def envelope(levels, q: int, what: str) -> None:
        """What any correct stochastic rounding of ``flat`` to ``q`` levels
        satisfies, whatever its bit source (``levels`` float, signed)."""
        lf = jnp.abs(flat) * (q / norm)
        mag = jnp.abs(levels)
        # One part in 1e6 of slack: the kernel's q/norm is divided in
        # another program than this one and may differ in the last bit.
        require(bool(jnp.all((mag >= jnp.floor(lf * (1 - 1e-6)))
                             & (mag <= jnp.ceil(lf * (1 + 1e-6))))),
                f"{what}: level outside the floor/ceil envelope")
        require(not bool(jnp.any(levels * flat < 0)),
                f"{what}: sign mismatch")
        # Unbiasedness: level − lf is −frac or 1 − frac with P(up) = frac,
        # so its sum over the buffer is N(0, Σ frac·(1 − frac)). A bit
        # source stuck at 0 or 1 rounds everything one way and lands
        # hundreds of sigma out; 6 sigma passes a fair one.
        frac = lf - jnp.floor(lf)
        sigma = math.sqrt(float(jnp.sum(frac * (1.0 - frac))))
        z = float(jnp.sum(mag - lf)) / max(sigma, 1e-30)
        require(abs(z) < 6.0,
                f"{what}: rounding is biased ({z:.1f} sigma)")

    t0 = time.perf_counter()
    compiled_has_kernel(quantize_stochastic, flat, norm, seed32, 64,
                        interpret=interpret)
    levels = quantize_stochastic(flat, norm, seed32, 64, interpret=interpret)
    envelope(levels.astype(jnp.float32), 64, "quantize_stochastic")
    done("quantize_stochastic", "q=64 int8", "floor/ceil envelope, "
         "sign, unbiased sum (6 sigma)", t0)
    del levels

    unpackers = {2: packing.unpack_2bit, 3: packing.unpack_3bit,
                 4: packing.unpack_4bit}
    packers = {2: packing.pack_2bit, 3: packing.pack_3bit,
               4: packing.pack_4bit}
    q_for = {2: 1, 3: 3, 4: 7}
    packed_payloads = {}
    for width in (2, 3, 4):
        q = q_for[width]
        t0 = time.perf_counter()
        compiled_has_kernel(quantize_pack_stochastic, flat, norm, seed32, q,
                            width=width, interpret=interpret)
        packed = quantize_pack_stochastic(flat, norm, seed32, q, width=width,
                                          interpret=interpret)
        require(packed.shape == (-(-n * width // 8),)
                and packed.dtype == jnp.uint8,
                f"quantize_pack w={width}: shape/dtype {packed.shape} "
                f"{packed.dtype}")
        codes = unpackers[width](packed, n).astype(jnp.int32)
        lv = jnp.where(codes >= (1 << (width - 1)), codes - (1 << width),
                       codes)
        require(bool(jnp.all(jnp.abs(lv) <= q)),
                f"quantize_pack w={width}: level beyond ±{q}")
        envelope(lv.astype(jnp.float32), q, f"pack w={width}")
        # pack∘unpack identity on the kernel's own levels: the reference
        # packer reproduces the kernel's bytes from the decoded codes.
        require(same(packers[width](codes.astype(jnp.uint8)), packed),
                f"quantize_pack w={width}: reference packer disagrees with "
                "the kernel's byte layout")
        # Same seed, same block layout: the fused pack shares the plain
        # kernel's PRNG stream, so its levels are the plain levels clamped.
        plain = quantize_stochastic(flat, norm, seed32, q,
                                    interpret=interpret).astype(jnp.int32)
        require(same(jnp.clip(plain, -q, q), lv),
                f"quantize_pack w={width}: != clamp(quantize_stochastic) at "
                "the same seed")
        done("quantize_pack_stochastic", f"width={width} q={q}",
             "envelope, unbiased sum, pack∘unpack identity, == clamped "
             "plain kernel at equal seed", t0)
        packed_payloads[width] = packed
        del codes, lv, plain

    t0 = time.perf_counter()
    compiled_has_kernel(sign_pack, flat, interpret=interpret)
    signs = sign_pack(flat, interpret=interpret)
    require(same(signs, packing.pack_bits(flat >= 0)),
            "sign_pack != pack_bits(x >= 0)")
    done("sign_pack", "1-bit", "bit-identical to pack_bits(x >= 0)", t0)

    # -- pallas_wire ------------------------------------------------------
    # Ring-hop shape: K=2 payloads (received, own). The second payload is
    # the kernel's own bytes for -flat's sibling buffer.
    other = resid * 10.0
    onorm = jnp.linalg.norm(other)
    for width in (2, 3, 4):
        q = q_for[width]
        t0 = time.perf_counter()
        p1 = quantize_pack_stochastic(other, onorm, seed32 + 1, q,
                                      width=width, interpret=interpret)
        stacked = jnp.stack([packed_payloads[width], p1])
        scales = jnp.stack([norm / q, onorm / q])
        compiled_has_kernel(decode_accumulate, stacked, scales, n, width,
                            interpret=interpret)
        got = decode_accumulate(stacked, scales, n, width,
                                interpret=interpret)

        def staged_decode(p, scale):
            codes = unpackers[width](p, n).astype(jnp.int8)
            lv = jnp.where(codes >= (1 << (width - 1)),
                           codes - (1 << width), codes)
            return scale * lv.astype(jnp.float32)

        want = staged_decode(stacked[0], scales[0]) \
            + staged_decode(stacked[1], scales[1])
        require(same(got, want), f"decode_accumulate w={width}: not "
                "bit-identical to staged decompress+decompress")
        done("decode_accumulate", f"width={width} K=2",
             "bit-identical to staged sequential decompress adds", t0)

        t0 = time.perf_counter()
        # homoqsgd's exact payload-space sum needs K·q inside the field:
        # re-encode both operands at a level bound of q//2 (>= 1 only for
        # width 3 and 4; width 2 sums one payload with an all-zero one).
        qh = max(1, q // 2) if width > 2 else 1
        a = quantize_pack_stochastic(flat, norm, seed32, qh, width=width,
                                     interpret=interpret)
        b = (jnp.zeros_like(a) if width == 2 else
             quantize_pack_stochastic(other, onorm, seed32 + 1, qh,
                                      width=width, interpret=interpret))
        pair = jnp.stack([a, b])
        compiled_has_kernel(packed_int_accumulate, pair, n, width,
                            interpret=interpret)
        summed = packed_int_accumulate(pair, n, width, interpret=interpret)

        def levels_of(p):
            c = unpackers[width](p, n).astype(jnp.int32)
            return jnp.where(c >= (1 << (width - 1)), c - (1 << width), c)

        tot = levels_of(a) + levels_of(b)
        ref = packers[width](jnp.where(tot < 0, tot + (1 << width),
                                       tot).astype(jnp.uint8))
        require(same(summed, ref), f"packed_int_accumulate w={width}: not "
                "bit-identical to unpack->add->repack")
        done("packed_int_accumulate", f"width={width} K=2",
             "bit-identical to staged unpack->int add->repack", t0)
        del p1, stacked, got, want, a, b, pair, summed, tot, ref

    other_signs = sign_pack(other, interpret=interpret)
    third = sign_pack(flat - other, interpret=interpret)
    for vote, stack in ((False, (signs, other_signs)),
                        (True, (signs, other_signs, third))):
        t0 = time.perf_counter()
        stacked = jnp.stack(stack)
        ones = jnp.ones((len(stack),), jnp.float32)
        compiled_has_kernel(decode_accumulate, stacked, ones, n, 1,
                            sign=True, vote=vote, interpret=interpret)
        got = decode_accumulate(stacked, ones, n, 1, sign=True, vote=vote,
                                interpret=interpret)
        want = sum(packing.unpack_bits(p, n).astype(jnp.float32) * 2.0 - 1.0
                   for p in stack)
        if vote:
            want = (want >= 0).astype(jnp.float32) * 2.0 - 1.0
        require(same(got, want),
                f"decode_accumulate sign vote={vote}: mismatch")
        done("decode_accumulate", f"sign K={len(stack)}"
             + (" vote" if vote else ""),
             "bit-identical to staged unpack->±1 sum"
             + ("->re-sign" if vote else ""), t0)


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def run(chips: int, seed: int, sizes: Sizes) -> dict:
    """All phases for ``chips``; returns the device dict of the last line.
    Raises on the first failure."""
    refuse_disabled_kernels()

    from grace_tpu.parallel import data_parallel_mesh
    from grace_tpu.utils.compile_cache import place_compile_cache

    require_tpu()
    place_compile_cache(jax.devices()[0].platform)
    device = device_line()
    mesh = data_parallel_mesh()
    check_world(mesh, chips)
    emit({"phase": "start", "device": device, "seed": seed,
          "jax": jax.__version__,
          # mesh order = jax.devices() order; the coords say which ring
          # neighbours are one link apart
          "mesh": [{"id": d.id, "coords": getattr(d, "coords", None)}
                   for d in mesh.devices.flat]})

    if chips == 1:
        resnet_phase(sizes, seed, mesh, one_chip_configs(),
                     "train")
        kernels_phase(sizes, seed)
        transformer_phase(sizes, seed, mesh)
    else:
        resnet_phase(sizes, seed, mesh, four_chip_configs(),
                     "train_multichip",
                     after_config=replica_checks(chips))
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the multi-chip exchange phase and its dense "
                         "comparison only")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthetic batch and the weights")
    args = ap.parse_args(argv)
    try:
        device = run(args.chips, args.seed, Sizes())
    except Exception as e:  # noqa: BLE001 — reported, then exit 1
        import traceback
        traceback.print_exc()
        emit({"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]})
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
