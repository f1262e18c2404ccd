"""On-chip BERT-base bench rows: dense, PowerSGD r4, and the graft-shard
transformer track (rscatter + per-leaf codec routing).

BASELINE.json config 4 pairs "BERT-base SQuAD" with PowerSGD rank-4 over
allreduce (reference grace_dl/dist/compressor/powersgd.py); the committed
capture has that row LOSING at 0.80× dense single-chip (the before-picture
ROADMAP item 2 names). The graft-shard rows are the after-picture: Top-K 1%
through the compressed per-shard reduce-scatter (``communicator:
"rscatter"``), and the ROUTED config — embeddings and the big matrices
ride sparsification, LayerNorm/bias leaves ride dense fp16 psum — whose
per-link xslice projection is the test-pinned >1× vs dense at W≥64
(tests/test_shard.py). All rows measure dense interleaved in ONE session —
the same same-session discipline as bench.bench_configs — reporting
tokens/sec, spread, per-leaf wire bytes (helper.route_leaves for routed
rows), and per-link projections through the ONE shared wire model
(helper.routed_recv_link_bytes — collapses to the plain model for
unrouted rows). Rows persist row-by-row to BENCH_BERT_TPU_LAST.json
(bench.progressive_emit), so a run cut short keeps the dense row.

    python tools/tpu_bert_bench.py                   # on the chip; exits
                                                     # non-zero without one
    python tools/tpu_bert_bench.py --platform cpu    # explicitly named CPU
                                                     # rehearsal, tiny model
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402

EVIDENCE_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_BERT_TPU_LAST.json")

# Transformer routing (ISSUE 14): LayerNorm scales/offsets and biases hate
# sparsification and are a rounding error of the wire bill — they ride
# dense fp16 psum; everything else (embeddings, qkv/proj/ff matrices — the
# >99% of BERT's 108.8M params where wire bytes concentrate) rides chunked
# Top-K 1% through the per-shard reduce-scatter.
BERT_ROUTE = [("*ln*", {"compressor": "fp16", "memory": "none",
                        "communicator": "allreduce"}),
              ("*bias*", {"compressor": "fp16", "memory": "none",
                          "communicator": "allreduce"}),
              ("*/b", {"compressor": "fp16", "memory": "none",
                       "communicator": "allreduce"})]

CONFIGS = [
    # fusion "none", twice over: (a) like-for-like with the powersgd config
    # below (also per-leaf); (b) fusion "flat" on the 108.8M-element BERT
    # gradient trips an XLA-TPU layout pathology — the materialized flat
    # f32[108793346] consumed by the 200-way split gets laid out as
    # f32[54396673,2]{1,0:T(8,128)}, whose minor-dim pad 2->128 inflates
    # 435 MB to 27.8 GB and OOMs 16 GB HBM at compile. Allreduce chunks
    # oversized dense psums to sidestep this (comm/__init__.py), but the
    # per-leaf program is the cleaner baseline here regardless.
    {"name": "bert_dense", "params": {"compressor": "none", "memory": "none",
                                      "communicator": "allreduce",
                                      "fusion": "none"}},
    {"name": "bert_powersgd_r4", "params": {"compressor": "powersgd",
                                            "compress_rank": 4,
                                            "memory": "powersgd",
                                            "communicator": "allreduce",
                                            "fusion": "none"}},
    # graft-shard (ISSUE 14): the per-shard reduce-scatter — one
    # all_to_all + one all_gather per leaf, requant chain 1 at any W.
    {"name": "bert_topk1pct_rscatter",
     "params": {"compressor": "topk", "compress_ratio": 0.01,
                "topk_algorithm": "chunk", "memory": "residual",
                "communicator": "rscatter", "fusion": "none"}},
    # ...and the routed config: the transformer-track headline shape.
    {"name": "bert_routed_rscatter",
     "params": {"compressor": "topk", "compress_ratio": 0.01,
                "topk_algorithm": "chunk", "memory": "residual",
                "communicator": "rscatter", "fusion": "none",
                "route": BERT_ROUTE}},
]


def routed_wire_report(grace, params):
    """(wire_bytes, dense_bytes) summed per leaf through each leaf's own
    routed codec — collapses to wire_report's totals for unrouted rows."""
    import numpy as np

    from grace_tpu.helper import route_leaves
    from grace_tpu.utils.metrics import payload_nbytes

    wire = dense = 0
    for _p, s, comp, _m, _cm in route_leaves(grace, params):
        ne = int(np.prod(s.shape, dtype=np.int64))
        dense += ne * s.dtype.itemsize
        wire += payload_nbytes(comp, s)
    return wire, dense


def project_routed(step_s: float, dense_step_s: float, grace, params,
                   n_elems: int) -> list:
    """Per-link multi-chip projection for a (possibly routed) config
    through ``helper.routed_recv_link_bytes`` — the routed spelling of
    ``bench.project_multichip``, same worlds, same bandwidth constants,
    same NO-OVERLAP convention, dense priced through the identical shared
    model."""
    from grace_tpu.comm import Allreduce
    from grace_tpu.core import Topology
    from grace_tpu.helper import routed_recv_link_bytes

    dense_comm = Allreduce()
    dense_b = sum(x.size * x.dtype.itemsize
                  for x in __import__("jax").tree_util.tree_leaves(params))
    xtopo = Topology(slice_size=bench.XSLICE_CHIPS)
    out = []
    for w in bench.PROJECTION_WORLDS:
        cfg_recv = routed_recv_link_bytes(grace, params, w).total
        dense_recv = dense_comm.recv_wire_bytes(dense_b, n_elems, w)
        row = {"world": w, "recv_bytes_per_rank": cfg_recv}
        for net, bw in (("ici", bench.ICI_RING_BYTES_PER_S),
                        ("dcn", bench.DCN_BYTES_PER_S)):
            t_cfg = step_s + cfg_recv / bw
            t_dense = dense_step_s + dense_recv / bw
            row[f"step_ms_{net}"] = round(t_cfg * 1e3, 3)
            row[f"speedup_vs_dense_{net}"] = round(t_dense / t_cfg, 3)
        cfg_link = routed_recv_link_bytes(grace, params, w, topology=xtopo)
        dense_link = dense_comm.recv_link_bytes(dense_b, n_elems, w,
                                                topology=xtopo)

        def t_split(base_s, link):
            return (base_s + link.ici / bench.ICI_RING_BYTES_PER_S
                    + link.dcn / bench.DCN_BYTES_PER_S)

        t_cfg = t_split(step_s, cfg_link)
        row["xslice"] = {
            "slice_size": bench.XSLICE_CHIPS,
            "ici_bytes": cfg_link.ici,
            "dcn_bytes": cfg_link.dcn,
            "step_ms": round(t_cfg * 1e3, 3),
            "speedup_vs_dense": round(
                t_split(dense_step_s, dense_link) / t_cfg, 3),
        }
        out.append(row)
    return out


def run(platform: str, emit) -> None:
    devices = bench.setup_platform(platform)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from grace_tpu import grace_from_params
    from grace_tpu.models import layers as L
    from grace_tpu.models import transformer
    from grace_tpu.parallel import batch_sharded, data_parallel_mesh
    from grace_tpu.train import (init_stateful_train_state,
                                 make_stateful_train_step)

    on_tpu = devices[0].platform == "tpu"
    mesh = data_parallel_mesh(devices)
    # BERT-base at the standard SQuAD fine-tuning length on the chip; a tiny
    # encoder on the CPU mesh so the smoke finishes on a one-core host.
    seq = 384 if on_tpu else 64
    per_device_bs = 8 if on_tpu else 2
    cfg = (transformer.base(num_classes=2, max_len=seq) if on_tpu
           else transformer.tiny(num_classes=2, max_len=seq))
    repeats = 3 if on_tpu else 1
    # Window >= ~1.3 s against host-clock jitter: BERT-base steps are ~10x
    # a ResNet bs=32 step, so fewer batches suffice.
    n_batches = 40 if on_tpu else 2

    n = per_device_bs * len(devices)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (n, seq)), jnp.int32)
    spans = jnp.asarray(
        np.stack([rng.integers(0, seq // 2, n),
                  rng.integers(seq // 2, seq, n)], 1), jnp.int32)
    batch = jax.device_put((ids, spans), batch_sharded(mesh))

    def build(grace_params):
        grace = grace_from_params(grace_params)
        optimizer = optax.chain(grace.transform(seed=0), optax.adamw(5e-5))

        def loss_fn(params, mstate, b):
            idb, spanb = b
            x = transformer.encode(params, idb, cfg, dtype=jnp.bfloat16)
            logits = L.dense_apply(params["cls"], x.astype(jnp.float32))
            loss = (optax.softmax_cross_entropy_with_integer_labels(
                        logits[..., 0], spanb[:, 0])
                    + optax.softmax_cross_entropy_with_integer_labels(
                        logits[..., 1], spanb[:, 1]))
            return loss.mean(), mstate

        step = make_stateful_train_step(loss_fn, optimizer, mesh)
        params, mstate = transformer.init(jax.random.key(0), cfg)
        ts = init_stateful_train_state(params, mstate, optimizer, mesh)
        return step, ts, grace, params

    chip = getattr(devices[0], "device_kind", devices[0].platform)
    print(f"[bert-bench] mesh: {len(devices)}x {devices[0].platform} "
          f"({chip}), seq={seq}, bs={per_device_bs}/device",
          file=sys.stderr, flush=True)

    built = [build(c["params"]) for c in CONFIGS]
    samples = [[] for _ in CONFIGS]
    for r in range(repeats):
        warm = 4 if r == 0 else 2
        for j, (step, ts, _g, _p) in enumerate(built):
            s, ts = bench.throughput(step, ts, batch, n_batches,
                                     warmup=warm)
            built[j] = (step, ts, built[j][2], built[j][3])
            samples[j].append(s)

    med = statistics.median
    base_samples = samples[0]
    n_elems = sum(x.size for x in jax.tree_util.tree_leaves(built[0][3]))
    for c, (step, ts, grace, params), ss in zip(CONFIGS, built, samples):
        seqs = med(ss)
        wire_b, dense_b = routed_wire_report(grace, params)
        spread = (100.0 * (max(ss) - min(ss)) / seqs if seqs else 0.0)
        from grace_tpu.helper import routed_recv_link_bytes
        emit({
            "config": c["name"],
            "tokens_per_sec": round(seqs * seq, 1),
            "seqs_per_sec": round(seqs, 2),
            "samples_seqs_per_sec": [round(s, 2) for s in ss],
            "spread_pct": round(spread, 2),
            "vs_baseline": round(seqs / med(base_samples), 4),
            "same_session": True,
            "seq_len": seq,
            "per_device_bs": per_device_bs,
            "model": "bert-base" if on_tpu else "bert-tiny(smoke)",
            "n_params": n_elems,
            "routed": bool(c["params"].get("route")),
            "wire_bytes_per_step": wire_b,
            "wire_ratio": round(wire_b / max(1, dense_b), 6),
            "wire_recv_bytes_per_step": routed_recv_link_bytes(
                grace, params, len(devices)).total,
            "projection": project_routed(
                n / seqs, n / med(base_samples), grace, params, n_elems),
            "platform": devices[0].platform,
            "n_devices": len(devices),
            "chip": chip,
        })


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--platform", default="tpu", choices=["tpu", "cpu"])
    args = ap.parse_args()
    emit = bench.progressive_emit(
        lambda r: print(json.dumps(r), flush=True),
        n_expected=len(CONFIGS),
        evidence_path=EVIDENCE_PATH,
        metric="bert_powersgd_r4_tokens_per_sec",
        headline_config="bert_powersgd_r4",
        value_key="tokens_per_sec")
    run(args.platform, emit)


if __name__ == "__main__":
    main()
