"""Unit tests for bench.py's TPU evidence persistence.

A chip run can be cut at any row, so whatever landed on disk is often all
there is. These tests pin the protection logic: row-by-row persistence, atomicity of the
write, and the no-regression rule that keeps a fresh 1-row partial from
clobbering an earlier complete record; plus the sweep-resume gates
(bench_all) and the cached-row passthrough — the passthrough test calls
bench_configs, which does initialize the (CPU) jax backend.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def _row(config, imgs, platform="tpu"):
    return {"config": config, "imgs_per_sec": imgs, "vs_baseline": 1.0,
            "platform": platform, "n_devices": 1, "chip": "TPU test",
            "peak_flops": 1.0, "mfu": 0.5}


def test_progressive_emit_persists_each_tpu_row(tmp_path):
    path = str(tmp_path / "ev.json")
    seen = []
    emit = bench.progressive_emit(seen.append, n_expected=2,
                                  evidence_path=path, metric="m")
    emit(_row("none", 100.0))
    rec = json.load(open(path))
    assert rec["partial"] is True and rec["rows_measured"] == 1
    emit(_row("topk1pct", 50.0))
    rec = json.load(open(path))
    assert rec["partial"] is False and rec["rows_measured"] == 2
    assert rec["value"] == 50.0          # headline = the topk1pct row
    assert len(seen) == 2


def test_progressive_emit_ignores_non_tpu_rows(tmp_path):
    path = str(tmp_path / "ev.json")
    emit = bench.progressive_emit(lambda r: None, n_expected=2,
                                  evidence_path=path, metric="m")
    emit(_row("none", 1.0, platform="cpu"))
    assert not os.path.exists(path)


def test_partial_never_clobbers_complete(tmp_path):
    path = str(tmp_path / "ev.json")
    emit = bench.progressive_emit(lambda r: None, n_expected=2,
                                  evidence_path=path, metric="m")
    emit(_row("none", 100.0))
    emit(_row("topk1pct", 50.0))        # complete record on disk
    complete = json.load(open(path))

    # A fresh attempt dies after one row: its 1-row partial must land in
    # the .partial sibling, leaving the complete record untouched.
    emit2 = bench.progressive_emit(lambda r: None, n_expected=2,
                                   evidence_path=path, metric="m")
    emit2(_row("none", 90.0))
    assert json.load(open(path)) == complete
    demoted = json.load(open(path + ".partial"))
    assert demoted["partial"] is True and demoted["rows_measured"] == 1


def test_longer_partial_replaces_shorter(tmp_path):
    path = str(tmp_path / "ev.json")
    emit = bench.progressive_emit(lambda r: None, n_expected=3,
                                  evidence_path=path, metric="m")
    emit(_row("none", 100.0))            # 1-row partial on disk
    emit2 = bench.progressive_emit(lambda r: None, n_expected=3,
                                   evidence_path=path, metric="m")
    emit2(_row("none", 90.0))            # same length: not a regression
    emit2(_row("topk1pct", 40.0))        # longer prefix: must replace
    rec = json.load(open(path))
    assert rec["rows_measured"] == 2
    assert rec["rows"][0]["imgs_per_sec"] == 90.0


def test_regresses_handles_round2_format():
    # Round-2 records lack rows/partial fields; a non-null value means a
    # real measured headline that a fresh 1-row partial must not erase.
    old = {"metric": "m", "value": 985.68, "vs_baseline": None}
    new = {"partial": True, "rows_measured": 1}
    assert bench._regresses(new, old) is True
    complete = {"partial": False, "rows_measured": 2}
    assert bench._regresses(complete, old) is False


def test_headline_metric_prefers_topk_row(tmp_path):
    path = str(tmp_path / "ev.json")
    emit = bench.progressive_emit(lambda r: None, n_expected=2,
                                  evidence_path=path, metric="m")
    emit(_row("topk1pct", 42.0))         # compressed row can land first
    rec = json.load(open(path))
    assert rec["value"] == 42.0 and rec["mfu"] == 0.5


# ---------------------------------------------------------------------------
# Sweep resume (bench_all._resume_configs + bench_configs cached_row)
# ---------------------------------------------------------------------------

import datetime  # noqa: E402

import bench_all  # noqa: E402


def _evidence_file(tmp_path, captured_at=None, rows=()):
    doc = {"metric": "resnet50_all_configs_imgs_per_sec",
           "captured_at": captured_at
           or datetime.datetime.now(datetime.timezone.utc).isoformat(),
           "rows": list(rows)}
    path = tmp_path / "sweep.json"
    path.write_text(json.dumps(doc))
    return str(path)


def _sweep_row(config, bs=32, hw=224, pdtype="float32", **extra):
    row = {"config": config, "imgs_per_sec": 100.0, "vs_baseline": 0.9,
           "per_device_bs": bs, "image_hw": hw, "param_dtype": pdtype,
           "platform": "tpu", **extra}
    for c in bench_all.CONFIGS:       # stamp the real params, like bench.py
        if c["name"] == config:
            row.setdefault("grace_params", c["params"])
    return row


def _patch_evidence(monkeypatch, path):
    monkeypatch.setattr(bench_all, "SWEEP_EVIDENCE_PATH", path)


def test_resume_no_gate_no_cache(tmp_path, monkeypatch):
    _patch_evidence(monkeypatch, _evidence_file(
        tmp_path, rows=[_sweep_row("topk1pct_bs64", bs=64)]))
    monkeypatch.delenv("GRACE_BENCH_RESUME", raising=False)
    monkeypatch.delenv("GRACE_BENCH_RESUME_SINCE", raising=False)
    assert not any("cached_row" in c for c in bench_all._resume_configs())


def test_resume_explicit_matches_shapes_and_skips_errors(tmp_path,
                                                         monkeypatch):
    _patch_evidence(monkeypatch, _evidence_file(tmp_path, rows=[
        _sweep_row("topk1pct_bs64", bs=64),
        _sweep_row("topk1pct", bs=32),       # headline is bs=256 now
        {"config": "signsgd_vote", "error": "boom", "per_device_bs": 32,
         "image_hw": 224, "param_dtype": "float32"},
    ]))
    monkeypatch.setenv("GRACE_BENCH_RESUME", "1")
    monkeypatch.delenv("GRACE_BENCH_RESUME_SINCE", raising=False)
    cfgs = bench_all._resume_configs()
    cached = {c["name"]: c["cached_row"] for c in cfgs if "cached_row" in c}
    assert set(cached) == {"topk1pct_bs64"}
    assert cached["topk1pct_bs64"]["resumed"] is True


def test_resume_rejects_edited_params(tmp_path, monkeypatch):
    # Same name + shapes but different grace_params (config edited since
    # the row was measured) -> re-measure; a row with no stamp at all is
    # trusted only under the explicit operator override.
    edited = _sweep_row("topk1pct_bs64", bs=64)
    edited["grace_params"] = {**edited["grace_params"],
                              "compress_ratio": 0.05}
    unstamped = _sweep_row("topk1pct_bs128", bs=128)
    del unstamped["grace_params"]
    _patch_evidence(monkeypatch, _evidence_file(
        tmp_path, rows=[edited, unstamped]))
    monkeypatch.delenv("GRACE_BENCH_RESUME", raising=False)
    monkeypatch.setenv("GRACE_BENCH_RESUME_SINCE", "0")
    assert not any("cached_row" in c for c in bench_all._resume_configs())
    monkeypatch.setenv("GRACE_BENCH_RESUME", "1")
    cached = {c["name"] for c in bench_all._resume_configs()
              if "cached_row" in c}
    assert cached == {"topk1pct_bs128"}   # unstamped ok ONLY when explicit


def test_resume_since_rejects_stale_accepts_fresh(tmp_path, monkeypatch):
    path = _evidence_file(tmp_path, rows=[_sweep_row("topk1pct_bs64",
                                                     bs=64)])
    _patch_evidence(monkeypatch, path)
    monkeypatch.delenv("GRACE_BENCH_RESUME", raising=False)
    # Watcher started an hour from now -> the file predates it: stale.
    import time
    monkeypatch.setenv("GRACE_BENCH_RESUME_SINCE", str(time.time() + 3600))
    assert not any("cached_row" in c for c in bench_all._resume_configs())
    monkeypatch.setenv("GRACE_BENCH_RESUME_SINCE", "0")
    assert any("cached_row" in c for c in bench_all._resume_configs())


def test_cached_row_passthrough_no_measurement():
    # bench_configs must emit cached rows verbatim without building a model
    # (a real build would compile ResNet-50 — the sub-second runtime of
    # this test is itself the proof the passthrough short-circuits).
    rows = []
    cfg = {"name": "x", "params": {"compressor": "none"},
           "cached_row": {"config": "x", "imgs_per_sec": 1.0,
                          "resumed": True}}
    # platform="cpu" under the test env (conftest pins the 8-dev CPU mesh).
    bench.bench_configs("cpu", [cfg], rows.append)
    assert rows == [{"config": "x", "imgs_per_sec": 1.0, "resumed": True}]


def test_cached_row_invalid_on_pallas_resolution_change():
    # A row stamped pallas_enabled=True replays only if the config still
    # resolves the kernel on today ('auto' resolves staged everywhere
    # since round 4, so a kernel-measured row must re-measure).
    params = {"compressor": "topk", "compress_ratio": 0.01,
              "topk_algorithm": "chunk", "memory": "residual",
              "communicator": "allgather", "fusion": "flat"}
    cfg = {"name": "topk1pct", "params": params,
           "cached_row": {"config": "topk1pct", "imgs_per_sec": 1.0,
                          "pallas_enabled": True, "resumed": True}}
    assert bench._cached_row_valid(cfg) is False
    cfg["cached_row"]["pallas_enabled"] = False
    assert bench._cached_row_valid(cfg) is True
    # Pre-stamp row on a kernel-capable config: fails CLOSED (the round-4
    # bs-sweep rows were measured under the old kernel-on default and
    # nothing in them says so) unless the operator override vouches.
    del cfg["cached_row"]["pallas_enabled"]
    assert bench._cached_row_valid(cfg) is False
    cfg["cached_row"]["resume_trusted"] = True
    assert bench._cached_row_valid(cfg) is True
    # Non-kernel-capable config (e.g. compressor none): nothing to compare.
    cfg2 = {"name": "none", "params": {"compressor": "none",
                                       "memory": "none",
                                       "communicator": "allreduce"},
            "cached_row": {"config": "none", "imgs_per_sec": 1.0}}
    assert bench._cached_row_valid(cfg2) is True


def test_stamped_row_fails_closed_when_capability_gone(monkeypatch):
    # A row stamped pallas_enabled=True for a config that no longer
    # resolves any kernel capability (now=None) must re-measure.
    class NoKernel:
        compressor = object()      # no _pallas_mode attribute

    cfg = {"name": "topk1pct", "params": {"compressor": "topk",
                                          "compress_ratio": 0.01},
           "cached_row": {"config": "topk1pct", "imgs_per_sec": 1.0,
                          "pallas_enabled": True, "resume_trusted": True}}
    monkeypatch.setattr("grace_tpu.grace_from_params",
                        lambda params: NoKernel())
    assert bench._cached_row_valid(cfg) is False


def test_sweep_summary_trims_rows(tmp_path):
    # Fallback runs carry a trimmed sweep view; bulky fields (projection,
    # samples, grace_params) must not ride along, error rows must.
    big = {"metric": "m", "captured_at": "2026-07-31T19:04:30+00:00",
           "partial": True,
           "rows": [{"config": "topk1pct_bs256", "imgs_per_sec": 2114.1,
                     "vs_baseline": 0.9246, "same_session": True,
                     "per_device_bs": 256, "projection": [{"world": 8}],
                     "samples": [1, 2, 3], "grace_params": {"x": 1}},
                    {"config": "boom", "error": "died"}]}
    p = tmp_path / "BENCH_ALL_TPU_LAST.json"
    p.write_text(json.dumps(big))
    s = bench.load_tpu_sweep_summary(str(p))
    assert s["partial"] is True
    assert s["rows"][0]["vs_baseline"] == 0.9246
    assert "projection" not in s["rows"][0]
    assert "samples" not in s["rows"][0]
    assert "grace_params" not in s["rows"][0]
    assert s["rows"][1] == {"config": "boom", "error": "died"}


# ---------------------------------------------------------------------------
# Multi-chip projection model (VERDICT r4 item 5: "unit-test the arithmetic")
# ---------------------------------------------------------------------------

def _mk_grace(comm, vote=False):
    class _Comp:
        vote_aggregate = vote

    class _G:
        communicator = comm
        compressor = _Comp()

    return _G()


def test_recv_bytes_model_arithmetic():
    from grace_tpu.comm import (Allgather, Allreduce, Identity,
                                SignAllreduce, TwoShotAllreduce)
    payload, n, w = 1_000_000, 500_000, 8
    # Ring allreduce: 2·(W-1)/W·payload received per rank.
    assert bench.recv_bytes_model(Allreduce(), False, payload, n, w) == \
        2 * payload * (w - 1) // w
    # Allgather: every other rank's payload, O(W·k).
    assert bench.recv_bytes_model(Allgather(), False, payload, n, w) == \
        payload * (w - 1)
    # Two-shot: all_to_all + all_gather of the O(k) reduced payload.
    assert bench.recv_bytes_model(TwoShotAllreduce(), False, payload, n,
                                  w) == 2 * payload * (w - 1) // w
    # Sign vote: dense bf16 votes (2 bytes/elem) on a ring — payload-blind.
    assert bench.recv_bytes_model(SignAllreduce(), False, payload, n, w) == \
        2 * 2 * n * (w - 1) // w
    assert bench.recv_bytes_model(Identity(), False, payload, n, w) == 0


def test_recv_bytes_twoshot_flat_allgather_linear_in_world():
    # The round-5 beat-dense argument hangs on this property: twoshot's
    # per-rank recv saturates (~2·payload) while allgather's grows
    # linearly with world size.
    from grace_tpu.comm import Allgather, TwoShotAllreduce
    payload, n = 1_000_000, 500_000
    two = [bench.recv_bytes_model(TwoShotAllreduce(), False, payload, n, w)
           for w in (8, 64, 256)]
    gat = [bench.recv_bytes_model(Allgather(), False, payload, n, w)
           for w in (8, 64, 256)]
    assert max(two) < 2 * payload                     # saturates below 2k
    assert gat[2] == (256 - 1) * payload              # linear growth
    assert gat[2] / gat[0] > 30


def test_project_multichip_arithmetic_and_assumptions():
    from grace_tpu.comm import Allgather
    step_s, dense_step_s = 0.1, 0.09
    wire_b, dense_b, n = 1_000_000, 100_000_000, 25_000_000
    rows = bench.project_multichip(step_s, dense_step_s,
                                   _mk_grace(Allgather()), wire_b, dense_b,
                                   n)
    assert [r["world"] for r in rows] == list(bench.PROJECTION_WORLDS)
    for r in rows:
        w = r["world"]
        cfg_recv = wire_b * (w - 1)
        dense_recv = 2 * dense_b * (w - 1) // w
        assert r["recv_bytes_per_rank"] == cfg_recv
        for net, bw in (("ici", bench.ICI_RING_BYTES_PER_S),
                        ("dcn", bench.DCN_BYTES_PER_S)):
            t_cfg = step_s + cfg_recv / bw
            t_dense = dense_step_s + dense_recv / bw
            assert abs(r[f"step_ms_{net}"] - t_cfg * 1e3) < 1e-2
            assert abs(r[f"speedup_vs_dense_{net}"] - t_dense / t_cfg) < 1e-3
    # The stamped model metadata matches the constants actually used.
    assert bench.PROJECTION_MODEL["ici_bytes_per_s"] == \
        bench.ICI_RING_BYTES_PER_S
    assert bench.PROJECTION_MODEL["dcn_bytes_per_s"] == bench.DCN_BYTES_PER_S
    assert "no-overlap" in bench.PROJECTION_MODEL["assumption"].lower() or \
        "NO-OVERLAP" in bench.PROJECTION_MODEL["assumption"]


# ---------------------------------------------------------------------------
# no fallback (PR 21): the device is named or the run fails
# ---------------------------------------------------------------------------

class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("kind,peak", [
    ("TPU v5 lite", 197e12), ("TPU v5e", 197e12), ("TPU v5p", 459e12),
    ("TPU v4", 275e12), ("TPU v6 lite", 918e12)])
def test_device_peak_flops_known_kinds(kind, peak):
    assert bench.device_peak_flops(_Dev("tpu", kind)) == peak


def test_device_peak_flops_unknown_tpu_raises_and_cpu_is_none():
    # The bare "v5" row gave any unknown v5 kind the v5p peak.
    with pytest.raises(ValueError, match="no published peak"):
        bench.device_peak_flops(_Dev("tpu", "TPU v5"))
    with pytest.raises(ValueError, match="no published peak"):
        bench.device_peak_flops(_Dev("tpu", "TPU v9x"))
    assert bench.device_peak_flops(_Dev("cpu", "cpu")) is None


@pytest.mark.parametrize("argv,want", [
    (["bench.py"], "tpu"), (["bench.py", "--_worker", "cpu"], "cpu"),
    (["bench.py", "--_worker", "tpu"], "tpu")])
def test_worker_platform_defaults_to_the_chip(argv, want):
    assert bench.worker_platform(argv) == want


def test_worker_platform_rejects_other_names():
    with pytest.raises(SystemExit):
        bench.worker_platform(["bench.py", "--_worker", "gpu"])


def test_setup_platform_tpu_fails_without_a_tpu(monkeypatch):
    # Under the test harness the first device is a CPU: the chip path must
    # exit, not substitute the CPU mesh. It places the persistent compile
    # cache before it looks at the devices; kept out of here, or every later
    # compile of this worker process is written to ``.jax_cache`` and counted
    # as a cache miss by the compile ledger, whichever test it belongs to.
    from grace_tpu.utils import compile_cache
    monkeypatch.setattr(compile_cache, "place_compile_cache",
                        lambda platform: None)
    with pytest.raises(SystemExit, match="not a TPU"):
        bench.setup_platform("tpu")


def test_throughput_times_with_block_until_ready(monkeypatch):
    """No fetch round trip is measured and none is subtracted: the window
    is n_batches steps between two block_until_ready calls."""
    import jax
    import jax.numpy as jnp

    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append("sync") or real(x))

    def step(ts, batch):
        calls.append("step")
        return ts + 1, jnp.float32(0.0)

    batch = (jnp.zeros((4, 2)), jnp.zeros((4,), jnp.int32))
    rate, ts = bench.throughput(step, jnp.int32(0), batch, 3, warmup=2)
    assert calls == ["step"] * 2 + ["sync"] + ["step"] * 3 + ["sync"]
    assert int(ts) == 5 and rate > 0
