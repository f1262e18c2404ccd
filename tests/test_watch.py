"""graft-watch: in-graph cross-rank health aggregation, streaming anomaly
detection, and the unified run timeline (ISSUE 8).

The properties pinned here are the acceptance criteria of the watch
subsystem: cross-rank summaries computed in-graph for one tiny collective
per window (wire cost folded honestly into the telemetry ring, single
flush transfer preserved), a seeded single-rank compression-error drift
flagged with the correct rank within one window while the guard provably
stays silent, zero false positives on a healthy run, window-ordered
drain across guard-fallback and consensus-audit windows, and the
graft_watch CLI's baseline regression gate (exit 1 + WATCH_LAST.json).
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from grace_tpu import grace_from_params
from grace_tpu.resilience import ChaosCompressor, ConsensusConfig, \
    guarded_chain
from grace_tpu.telemetry import (AnomalyConfig, JSONLSink, TelemetryReader,
                                 Timeline, WatchConfig, WatchMonitor)
from grace_tpu.telemetry.aggregate import (WATCH_FIELDS, normalize_watch,
                                           watch_gather_bytes)
from grace_tpu.telemetry.anomaly import Ewma
from grace_tpu.telemetry.timeline import classify
from grace_tpu.train import init_train_state, make_train_step

BATCH, DIM, CLASSES = 64, 20, 4

TOPK_WATCH = {"compressor": "topk", "compress_ratio": 0.3,
              "memory": "residual", "communicator": "allgather",
              "telemetry": 64, "watch": 5}


def _problem(seed=0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(DIM, CLASSES)).astype(np.float32)
    x = rng.normal(size=(BATCH * 8, DIM)).astype(np.float32)
    y = np.argmax(x @ w_true, axis=1)
    return jnp.asarray(x), jnp.asarray(y)


def _loss_fn(params, batch):
    x, y = batch
    logits = x @ params["w"] + params["b"]
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def _init_params(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(
                rng.normal(size=(DIM, CLASSES)).astype(np.float32) * 0.1),
            "b": jnp.zeros((CLASSES,), jnp.float32)}


def _build(mesh, grace_params, lr=0.3, guard=False, drift_rank=None,
           drift_scale=0.6, consensus=None, **guard_kw):
    grc = grace_from_params(dict(grace_params))
    if drift_rank is not None:
        grc = dataclasses.replace(grc, compressor=ChaosCompressor(
            inner=grc.compressor, drift_scale=drift_scale, rank=drift_rank))
    if guard:
        tx = guarded_chain(grc, optax.sgd(lr), **guard_kw)
    else:
        tx = optax.chain(grc.transform(seed=0), optax.sgd(lr))
    state = init_train_state(_init_params(), tx, mesh)
    step = make_train_step(_loss_fn, tx, mesh, donate=False,
                           consensus=consensus)
    return state, step


class _ListSink:
    def __init__(self):
        self.records = []

    def write(self, record):
        self.records.append(dict(record))

    def close(self):
        pass


def _load_tool(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"{name}_under_test",
        os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                     f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# in-graph aggregation
# ---------------------------------------------------------------------------

@pytest.mark.watch
@pytest.mark.telemetry
def test_watch_rows_on_window_boundaries_with_consistent_stats(mesh):
    """Summaries land exactly on window boundaries; replicated stats obey
    min <= mean <= max; the per-rank skew vectors re-assembled from the
    world axis have length W and sum to ~0 (deviations from the mean)."""
    x, y = _problem()
    state, step = _build(mesh, TOPK_WATCH)
    reader = TelemetryReader(sink=None, every=100)
    for _ in range(12):
        state, _ = step(state, (x, y))
    records = reader.flush(state)
    watch = [r for r in records if r.get("event") == "watch"]
    assert [r["step"] for r in watch] == [0, 5, 10]
    for rec in watch:
        for metric in ("grad_norm", "compression_error", "residual_norm"):
            assert (rec[f"{metric}_min"] <= rec[f"{metric}_mean"]
                    <= rec[f"{metric}_max"])
            skew = rec[f"{metric}_skew"]
            assert len(skew) == 8
            assert abs(sum(skew)) < 1e-3 * max(rec[f"{metric}_mean"], 1.0)
        assert 0 <= rec["skew_rank"] < 8
        assert rec["skew_max"] >= 0
        assert rec["watch_bytes"] == watch_gather_bytes(8) == 7 * 3 * 4


@pytest.mark.watch
@pytest.mark.telemetry
def test_watch_bytes_fold_into_wire_accounting(mesh):
    """Window-boundary rows carry the gather's bytes in wire_bytes AND the
    per-link split (ici on a single slice), other rows don't — and the
    ici + dcn == wire_bytes identity survives the fold."""
    x, y = _problem()
    state, step = _build(mesh, TOPK_WATCH)
    reader = TelemetryReader(sink=None, every=100)
    for _ in range(7):
        state, _ = step(state, (x, y))
    rows = [r for r in reader.flush(state) if "wire_bytes" in r]
    assert len(rows) == 7
    base = rows[1]["wire_bytes"]        # step 1: no watch gather
    gb = watch_gather_bytes(8)
    for rec in rows:
        boundary = rec["step"] % 5 == 0
        assert rec["watch_bytes"] == (gb if boundary else 0.0)
        assert rec["wire_bytes"] == base + (gb if boundary else 0.0)
        assert rec["wire_bytes_ici"] + rec["wire_bytes_dcn"] \
            == rec["wire_bytes"]


@pytest.mark.watch
@pytest.mark.telemetry
def test_flush_is_still_one_transfer_with_watch_armed(mesh, monkeypatch):
    """Watch rings ride the SAME device_get as the metric rings and guard
    counters — arming watch must not add transfers."""
    x, y = _problem()
    state, step = _build(mesh, dict(TOPK_WATCH, escape="fp16"), guard=True)
    reader = TelemetryReader(sink=None, every=10, anomaly=True)

    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    for i in range(20):
        state, _ = step(state, (x, y))
        reader.update(i, state)
    assert len(calls) == 2
    assert reader.flushes == 2


@pytest.mark.watch
@pytest.mark.chaos
def test_watch_row_rolls_back_with_skipped_step(mesh):
    """A poisoned step on a window boundary rolls the watch ring back with
    the rest of the state: no NaN summary ever reaches a flush and the
    boundary row is written by the retried (accepted) step instead."""
    x, y = _problem()
    state, step = _build(mesh, dict(TOPK_WATCH, escape="fp16"), guard=True)
    xbad = np.asarray(x).copy()
    xbad[0, 0] = np.nan
    # Wall step 5 is poisoned; accepted counts stay contiguous so the
    # count-5 boundary row comes from the NEXT (healthy) batch.
    batches = [x] * 5 + [jnp.asarray(xbad)] + [x] * 3
    reader = TelemetryReader(sink=None, every=100)
    for xb in batches:
        state, _ = step(state, (jnp.asarray(xb), y))
    records = reader.flush(state)
    watch = [r for r in records if r.get("event") == "watch"]
    assert [r["step"] for r in watch] == [0, 5]
    for rec in watch:
        for name, agg in WATCH_FIELDS:
            vals = rec[name] if agg == "gather" else [rec[name]]
            assert all(np.isfinite(v) for v in vals), (rec["step"], name)
    metric_steps = [r["step"] for r in records if "wire_bytes" in r]
    assert metric_steps == list(range(8))      # 9 wall steps, 1 skipped


@pytest.mark.watch
def test_watch_requires_telemetry():
    grc = grace_from_params({"compressor": "topk", "compress_ratio": 0.3,
                             "memory": "residual",
                             "communicator": "allgather", "watch": 5})
    with pytest.raises(ValueError, match="requires telemetry"):
        grc.transform(seed=0)


@pytest.mark.watch
def test_normalize_watch_spellings():
    assert normalize_watch(None) is None and normalize_watch(False) is None
    assert normalize_watch(True) == WatchConfig()
    assert normalize_watch(7) == WatchConfig(window=7)
    assert normalize_watch({"window": 3, "capacity": 4}) \
        == WatchConfig(window=3, capacity=4)
    with pytest.raises(TypeError):
        normalize_watch("yes")
    with pytest.raises(ValueError):
        WatchConfig(window=0)


@pytest.mark.watch
@pytest.mark.profiling
def test_state_footprint_counts_watch_ring(mesh):
    """The live watch ring bytes are part of the telem component and the
    expected model (eval_shape of init x world) matches them — the ring's
    row shape is world-independent by design, so the footprint check
    keeps working on any mesh."""
    from grace_tpu.profiling import check_state_footprint

    grc = grace_from_params(dict(TOPK_WATCH))
    tx = optax.chain(grc.transform(seed=0), optax.sgd(0.1))
    params = _init_params()
    state = init_train_state(params, tx, mesh)
    with_watch = check_state_footprint(state, grc, params, world=8)
    assert with_watch["matches"]
    no_watch = grace_from_params(
        {k: v for k, v in TOPK_WATCH.items() if k != "watch"})
    expected_delta = 8 * (16 * len(WATCH_FIELDS) * 4 + 16 * 4)
    assert with_watch["model"]["telem_bytes"] \
        - check_state_footprint(
            state, no_watch, params, world=8)["model"]["telem_bytes"] \
        == expected_delta


# ---------------------------------------------------------------------------
# anomaly detection
# ---------------------------------------------------------------------------

@pytest.mark.watch
@pytest.mark.chaos
def test_seeded_drift_flagged_with_correct_rank_within_one_window(mesh):
    """The acceptance scenario, in-process: a single-rank payload drift —
    finite (guard-blind), per-rank (consensus-blind) — produces a skew
    watch_anomaly naming exactly that rank at the first window boundary,
    while a drift-free twin of the run produces zero anomalies."""
    x, y = _problem()
    sink = _ListSink()
    state, step = _build(mesh, TOPK_WATCH, drift_rank=5)
    reader = TelemetryReader(sink, every=10, anomaly=True)
    for i in range(20):
        state, _ = step(state, (x, y))
        reader.update(i, state)
    anomalies = [r for r in sink.records
                 if r.get("event") == "watch_anomaly"]
    # Attribution judged on the codec-health metrics the drift corrupts;
    # grad_norm skew can legitimately flag batch-shard heterogeneity.
    skews = [a for a in anomalies if a["kind"] == "skew"
             and a["metric"] in ("compression_error", "residual_norm")]
    assert skews, "seeded drift produced no skew anomaly"
    assert {a["rank"] for a in skews} == {5}
    assert min(a["step"] for a in skews) == 0      # first window boundary
    assert any(a["metric"] == "compression_error" for a in skews)

    healthy_sink = _ListSink()
    state, step = _build(mesh, TOPK_WATCH)
    reader = TelemetryReader(healthy_sink, every=10, anomaly=True)
    for i in range(20):
        state, _ = step(state, (x, y))
        reader.update(i, state)
    assert not [r for r in healthy_sink.records
                if r.get("event") == "watch_anomaly"]


@pytest.mark.watch
def test_skew_detector_hysteresis_one_record_per_episode():
    """A persistently drifting rank is flagged once on the rising edge,
    not once per window — and re-arms after the skew subsides."""
    monitor = WatchMonitor()

    def watch_rec(step, outlier):
        skew = [0.01, -0.02, 0.3 if outlier else 0.01, -0.01, 0.01,
                -0.02, 0.02, 0.0]
        return {"event": "watch", "step": step,
                "compression_error_mean": 0.5,
                "compression_error_skew": skew,
                "grad_norm_mean": 1.0, "grad_norm_skew": [0.0] * 8,
                "residual_norm_mean": 1.0, "residual_norm_skew": [0.0] * 8}

    out = monitor.observe([watch_rec(0, True), watch_rec(5, True),
                           watch_rec(10, True)])
    assert len([a for a in out if a["metric"] == "compression_error"]) == 1
    out = monitor.observe([watch_rec(15, False), watch_rec(20, True)])
    hits = [a for a in out if a["metric"] == "compression_error"]
    assert len(hits) == 1 and hits[0]["step"] == 20    # new episode


@pytest.mark.watch
def test_ewma_spike_and_step_time_and_retrace_detectors():
    monitor = WatchMonitor(config=AnomalyConfig(warmup=3))
    base = [{"event": "perf_step_times", "step": s, "p50_ms": 10.0 + 0.01 * s}
            for s in range(5)]
    assert monitor.observe(base) == []
    spike = monitor.observe([{"event": "perf_step_times", "step": 6,
                              "p50_ms": 40.0}])
    assert [a["kind"] for a in spike] == ["step_time"]
    retr = monitor.observe([{"event": "perf_retrace", "step": 7,
                             "cache_size": 2, "retraces": 1}])
    assert [a["kind"] for a in retr] == ["retrace"]

    e = Ewma(alpha=0.25, warmup=2)
    assert e.update(1.0) is None and e.update(1.0) is None
    assert e.update(1.0) < 1.0
    assert e.update(100.0) > 4.0


@pytest.mark.watch
def test_wire_model_drift_detector():
    """The exchange bytes (wire - audit - watch) changing mid-run beyond
    rtol is an anomaly; audit/watch surcharges on their own are not."""
    monitor = WatchMonitor()
    rows = [{"step": 0, "wire_bytes": 1000.0, "audit_bytes": 0.0,
             "watch_bytes": 84.0, "fallback": 0.0},
            {"step": 1, "wire_bytes": 916.0, "audit_bytes": 0.0,
             "watch_bytes": 0.0, "fallback": 0.0},
            {"step": 2, "wire_bytes": 1016.0, "audit_bytes": 100.0,
             "watch_bytes": 0.0, "fallback": 0.0}]
    assert monitor.observe(rows) == []
    drift = monitor.observe([{"step": 3, "wire_bytes": 2000.0,
                              "audit_bytes": 0.0, "watch_bytes": 0.0,
                              "fallback": 0.0}])
    assert [a["kind"] for a in drift] == ["wire_drift"]
    assert drift[0]["expected"] == 916.0


# ---------------------------------------------------------------------------
# drain ordering across guard-fallback + consensus-audit windows
# ---------------------------------------------------------------------------

@pytest.mark.watch
@pytest.mark.telemetry
@pytest.mark.consensus
def test_multiwindow_drain_ordering_under_guard_and_consensus(mesh):
    """Records from window N always precede window N+1 in the sink, and
    steps are strictly increasing, even when a guard-fallback window and
    consensus audit steps land inside the same flush — the step-keying the
    timeline relies on."""
    x, y = _problem()
    params = dict(TOPK_WATCH, escape="fp16", consensus=True)
    state, step = _build(mesh, params, guard=True, fallback_after=2,
                         fallback_steps=4,
                         consensus=ConsensusConfig(audit_every=5))
    sink = _ListSink()
    reader = TelemetryReader(sink, every=12)
    xbad = jnp.asarray(np.where(np.arange(x.size).reshape(x.shape) == 0,
                                np.nan, np.asarray(x)).astype(np.float32))
    flush_of = {}
    for i in range(24):
        xb = xbad if i in (6, 7) else x       # 2 consecutive bad -> fallback
        state, _ = step(state, (xb, y))
        for rec in reader.update(i, state):
            if "wire_bytes" in rec:
                flush_of[rec["step"]] = reader.flushes
    metric = [r for r in sink.records if "wire_bytes" in r]
    steps = [r["step"] for r in metric]
    assert steps == sorted(steps) == list(range(22))   # 24 wall, 2 skipped
    assert any(r["fallback"] for r in metric)          # fallback inside
    assert any(r["audit_bytes"] > 0 for r in metric)   # audits inside
    assert reader.flushes == 2
    # Window partition: every step of flush 1 precedes every step of 2.
    assert max(s for s, f in flush_of.items() if f == 1) \
        < min(s for s, f in flush_of.items() if f == 2)
    # Watch rows stay window-ordered alongside the metric rows.
    watch_steps = [r["step"] for r in sink.records
                   if r.get("event") == "watch"]
    assert watch_steps == sorted(watch_steps)


# ---------------------------------------------------------------------------
# timeline
# ---------------------------------------------------------------------------

@pytest.mark.watch
def test_timeline_classify_merge_and_queries():
    records = [
        {"provenance": {"tool": "test"}},
        {"step": 0, "grad_norm": 1.0, "wire_bytes": 100.0},
        {"event": "watch", "step": 0, "skew_max": 0.1, "skew_rank": 2,
         "compression_error_mean": 0.4},
        {"event": "watch_anomaly", "step": 0, "kind": "skew",
         "metric": "compression_error", "rank": 2, "score": 9.0},
        {"step": 1, "grad_norm": 0.9, "wire_bytes": 100.0},
        {"event": "guard_skip", "step": 2, "notfinite_count": 1},
        {"event": "consensus_repair", "step": 3, "repairs": 1},
        {"event": "perf_step_times", "step": 3, "p50_ms": 1.0},
        {"event": "lint_finding", "step": 3, "severity": "error"},
        {"event": "guard_only", "guard_step": 4},
    ]
    assert classify(records[1]) == "telemetry"
    assert classify(records[3]) == "anomaly"
    assert classify(records[-1]) == "guard"
    tl = Timeline.from_records(records)
    assert tl.provenance == {"tool": "test"}
    assert len(tl) == 9
    # Within a step, emission order is preserved (causal order).
    kinds_at_0 = [e.kind for e in tl.at_step(0)]
    assert kinds_at_0 == ["telemetry", "watch", "anomaly"]
    assert [e.kind for e in tl.between(2, 3)] == \
        ["guard", "consensus", "perf", "lint"]
    assert tl.first("anomaly").step == 0
    assert tl.steps() == [0, 1, 2, 3]
    s = tl.summary()
    assert s["anomalies"] == 1 and s["anomalous_ranks"] == [2]
    assert s["first_anomaly_step"] == 0 and s["first_guard_step"] == 2
    assert s["anomalies_by_kind"] == {"skew": 1}
    text = tl.render()
    assert "ANOMALY skew/compression_error rank=2" in text
    with pytest.raises(ValueError):
        tl.kinds("nonsense")


@pytest.mark.watch
def test_timeline_from_jsonl_skips_torn_tail(tmp_path):
    path = tmp_path / "run.jsonl"
    path.write_text(json.dumps({"step": 0, "grad_norm": 1.0}) + "\n"
                    + '{"step": 1, "grad_no')          # killed mid-line
    tl = Timeline.from_jsonl(str(path))
    assert len(tl) == 1 and tl.events[0].step == 0


# ---------------------------------------------------------------------------
# graft_watch CLI
# ---------------------------------------------------------------------------

def _write_artifact(path, drift: bool):
    sink = JSONLSink(path, provenance={"tool": "test", "data": "synthetic"})
    monitor = WatchMonitor(sink=sink)
    for s in range(20):
        sink.write({"step": s, "grad_norm": 1.0, "wire_bytes": 100.0,
                    "audit_bytes": 0.0, "watch_bytes": 0.0,
                    "fallback": 0.0, "compression_error": 0.4})
        if s % 5 == 0:
            outlier = 0.3 if (drift and s >= 10) else 0.01
            rec = {"event": "watch", "step": s,
                   "grad_norm_mean": 1.0, "grad_norm_skew": [0.0] * 8,
                   "residual_norm_mean": 1.0,
                   "residual_norm_skew": [0.0] * 8,
                   "compression_error_mean": 0.4,
                   "compression_error_skew":
                       [0.01, -0.01, 0.0, outlier, 0.01, -0.02, 0.0, 0.0]}
            sink.write(rec)
            monitor.observe([rec])
    sink.close()


@pytest.mark.watch
def test_graft_watch_cli_views_and_evidence(tmp_path, capsys):
    watch_tool = _load_tool("graft_watch")
    art = tmp_path / "run.jsonl"
    _write_artifact(str(art), drift=True)
    out = tmp_path / "WATCH_LAST.json"
    rc = watch_tool.main([str(art), "--timeline", "--anomalies",
                          "--out", str(out)])
    assert rc == 0
    text = capsys.readouterr().out
    assert "== timeline" in text and "== anomalies" in text
    assert "rank=3" in text
    assert "anomalous ranks: [3]" in text
    doc = json.loads(out.read_text())
    assert doc["tool"] == "graft_watch"
    assert doc["anomalous_ranks"] == [3]
    assert doc["recorded_anomalies"] and doc["derived_anomalies"]

    rc = watch_tool.main([str(art), "--json", "--out", ""])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["anomalies_by_kind"] == {"skew": 1}


@pytest.mark.watch
def test_graft_watch_baseline_gates_seeded_regression(tmp_path, capsys):
    """The regression gate: a clean baseline vs a drift run exits 1 and
    writes the evidence document; clean-vs-clean exits 0."""
    watch_tool = _load_tool("graft_watch")
    clean = tmp_path / "clean.jsonl"
    drift = tmp_path / "drift.jsonl"
    _write_artifact(str(clean), drift=False)
    _write_artifact(str(drift), drift=True)
    base = tmp_path / "WATCH_BASELINE.json"
    out = tmp_path / "WATCH_LAST.json"

    assert watch_tool.main([str(clean), "--write-baseline", str(base),
                            "--out", ""]) == 0
    assert watch_tool.main([str(clean), "--baseline", str(base),
                            "--out", ""]) == 0
    capsys.readouterr()
    rc = watch_tool.main([str(drift), "--baseline", str(base),
                          "--out", str(out)])
    assert rc == 1
    text = capsys.readouterr().out
    assert "BASELINE REGRESSIONS" in text
    assert "new kind" in text
    doc = json.loads(out.read_text())
    assert doc["regressions"]
    assert doc["baseline"] == str(base)


# ---------------------------------------------------------------------------
# telemetry_report watch section + --json (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.watch
@pytest.mark.telemetry
def test_telemetry_report_watch_section_and_json(tmp_path, capsys):
    report = _load_tool("telemetry_report")
    path = tmp_path / "r.jsonl"
    sink = JSONLSink(path, provenance={"data": "synthetic"})
    for s in range(6):
        sink.write({"step": s, "grad_norm": 1.0, "wire_bytes": 184.0
                    if s % 5 == 0 else 100.0, "dense_bytes": 336.0,
                    "fallback": 0.0, "watch_bytes": 84.0
                    if s % 5 == 0 else 0.0})
    sink.write({"event": "watch", "step": 5, "grad_norm_mean": 1.0,
                "grad_norm_min": 0.9, "grad_norm_max": 1.1,
                "compression_error_mean": 0.4,
                "compression_error_min": 0.3, "compression_error_max": 0.6,
                "residual_norm_mean": 1.0, "residual_norm_min": 0.9,
                "residual_norm_max": 1.1, "skew_max": 0.42, "skew_rank": 6})
    sink.write({"event": "watch_anomaly", "step": 5, "kind": "skew",
                "metric": "compression_error", "rank": 6, "score": 9.5,
                "threshold": 6.0, "value": 0.2})
    sink.write({"event": "guard_skip", "step": 5, "notfinite_count": 1})
    sink.close()

    assert report.main([str(path)]) == 0
    text = capsys.readouterr().out
    assert "== watch" in text
    assert "worst compression-error skew: 0.42 (rank 6" in text
    assert "skew/compression_error (rank 6)" in text
    # watch events never leak into the guard section
    assert "watch_anomaly" not in text.split("== guard events")[1]

    assert report.main([str(path), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["records"] == 6
    assert doc["metrics"]["watch_bytes"]["max"] == 84.0
    assert len(doc["watch_summaries"]) == 1
    assert doc["watch_anomalies"][0]["rank"] == 6
    assert [e["event"] for e in doc["guard_events"]] == ["guard_skip"]


# ---------------------------------------------------------------------------
# JSONLSink hardening (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.watch
@pytest.mark.telemetry
def test_jsonl_sink_retries_transient_oserror(tmp_path):
    path = tmp_path / "r.jsonl"
    sink = JSONLSink(path)
    sink.write({"step": 0})
    real_file = sink._file
    fails = {"n": 0}

    class Flaky:
        def write(self, s):
            if fails["n"] == 0:
                fails["n"] += 1
                raise OSError("transient NFS blip")
            return real_file.write(s)

        def __getattr__(self, name):
            return getattr(real_file, name)

    sink._file = Flaky()
    sink.write({"step": 1})
    sink._file = real_file
    sink.close()
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert {"step": 0} in lines and {"step": 1} in lines
    assert fails["n"] == 1


@pytest.mark.watch
@pytest.mark.telemetry
def test_jsonl_sink_fsyncs_on_close(tmp_path, monkeypatch):
    import grace_tpu.telemetry.sinks as sinks_mod

    synced = []
    real_fsync = os.fsync
    monkeypatch.setattr(sinks_mod.os, "fsync",
                        lambda fd: synced.append(fd) or real_fsync(fd))
    sink = JSONLSink(tmp_path / "s.jsonl")
    sink.write({"step": 0})
    sink.close()
    assert synced, "close() must fsync so a preempted run never loses " \
                   "flushed-but-unsynced records"
    sink.close()                                   # idempotent


# ---------------------------------------------------------------------------
# chaos_smoke --watch (CI wiring)
# ---------------------------------------------------------------------------

@pytest.mark.watch
@pytest.mark.chaos
def test_chaos_smoke_watch_names_drifting_rank_before_any_guard_event(
        tmp_path):
    """The acceptance artifact: a sharded (world=8) run with a seeded
    single-rank compression-error drift must contain a watch_anomaly
    naming that rank, emitted before any guard event exists (here: the
    guard stays entirely silent — the point of the scenario)."""
    smoke = _load_tool("chaos_smoke")
    out = tmp_path / "watch_telemetry.jsonl"
    # seed 2: with two samples per rank a healthy rank's norms can
    # themselves be cross-sectional outliers. Under the installed JAX's
    # PRNG stream seed 0 draws a residual-norm one on rank 0 at step 15
    # and seeds 1, 5, 6 a gradient-norm one (data heterogeneity, which
    # the tool excludes and this test does not); seeds 2-4 draw none.
    rc = smoke.main(["--watch", "--watch-rank", "5", "--steps", "30",
                     "--seed", "2",
                     "--batch", "16", "--watch-window", "5",
                     "--telemetry-out", str(out),
                     "--telemetry-every", "10"])
    assert rc == 0

    lines = [json.loads(l) for l in out.read_text().splitlines()]
    anomalies = [l for l in lines if l.get("event") == "watch_anomaly"]
    assert anomalies, "no watch_anomaly in the artifact"
    skews = [a for a in anomalies if a["kind"] == "skew"]
    assert {a["rank"] for a in skews} == {5}
    assert min(a["step"] for a in skews) <= 5      # within one window
    guard_events = [l for l in lines
                    if str(l.get("event", "")).startswith("guard")
                    and l.get("event") != "guard_only"]
    assert not guard_events, "guard fired on a finite drift"
    # The timeline tells the same story end-to-end.
    tl = Timeline.from_jsonl(str(out))
    s = tl.summary()
    assert s["anomalous_ranks"] == [5] and s["first_anomaly_step"] <= 5
