"""A CPU rehearsal of ``benchmarks/run.py`` on test-size cells: the run
ends in a last line that keeps the builder's contract; without the
explicitly named rehearsal flag it fails here, where there is no TPU, and
prints no result. Nothing such a run prints is a device metric."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_harness_helpers import rehearse, tiny_catalog  # noqa: E402

NUMBER = (int, float)


def check_last_line(line, catalog, workload, traced):
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert isinstance(line["correct"], bool)
    assert isinstance(line["attempted"], int) and line["attempted"] > 0
    assert line["failed"] == 0
    section = "per_layer" if traced else "end_to_end"
    declared = {m["name"]: m for m in catalog.metrics_of(section, workload)}
    assert set(line["metrics"]) <= set(declared)
    for name, m in line["metrics"].items():
        assert isinstance(m["value"], NUMBER) and m["value"] == m["value"]
        assert m["unit"] == declared[name]["unit"]
    device = line["device"]
    assert set(device) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert device["memory_peak_bytes"] > 0
    if traced:
        assert device["busy_s"] > 0 and device["window_s"] >= device["busy_s"]
        for key in ("device_ops", "idle_gaps"):
            rows = line["breakdown"][key]
            assert len(rows) <= 10
            assert all(isinstance(n, str) and isinstance(s, NUMBER)
                       for n, s in rows)
    else:
        assert set(line["metrics"]) == set(declared)     # all end-to-end
        assert "breakdown" not in line


def test_untraced_run_reports_every_end_to_end_metric(capsys):
    cat = tiny_catalog()
    rc, lines = rehearse(capsys, cat, "--workload", "tiny-bert-powersgd-w1",
                         "--seed", "2147483777", "--seconds", "0.5",
                         "--trace", "0")
    assert rc == 0
    check_last_line(lines[-1], cat, "tiny-bert-powersgd-w1", traced=False)
    assert lines[-1]["correct"] is True
    assert lines[-1]["device"]["platform"] == "cpu"      # named, not hidden
    compared = next(l for l in lines if l.get("phase") == "correct")
    assert all({"name", "value", "limit", "ok"} <= set(r)
               for r in compared["compared"])
    window = next(l for l in lines if l.get("phase") == "window")
    assert window["steps"] == lines[-1]["attempted"]


def test_traced_run_on_four_devices_reports_the_cells_layer_metrics(capsys):
    cat = tiny_catalog()
    rc, lines = rehearse(capsys, cat, "--workload", "tiny-resnet-topk-w4",
                         "--seed", "7", "--seconds", "0.5", "--trace", "1")
    assert rc == 0
    last = lines[-1]
    check_last_line(last, cat, "tiny-resnet-topk-w4", traced=True)
    assert last["correct"] is True
    assert set(last["metrics"]) == {
        "dispatch_ms", "step_device_ms", "grace_ms", "collective_exposed_ms",
        "wire_bytes", "device_idle_share", "hbm_program_gib"}
    assert last["attempted"] == cat.cell("tiny-resnet-topk-w4")["trace_steps"]
    compared = next(l for l in lines if l.get("phase") == "correct")
    names = [r["name"] for r in compared["compared"]]
    assert "replica_leaves_differing" in names and "collectives_missing" in names


def test_dense_cell_leaves_out_what_it_has_nothing_to_read_for(capsys):
    cat = tiny_catalog()
    rc, lines = rehearse(capsys, cat, "--workload", "tiny-resnet-dense-w1",
                         "--seed", "8", "--seconds", "0.5", "--trace", "1")
    assert rc == 0 and lines[-1]["correct"] is True
    assert set(lines[-1]["metrics"]) == {
        "dispatch_ms", "step_device_ms", "device_idle_share",
        "hbm_program_gib"}


def test_without_a_tpu_the_run_fails_and_prints_no_result(capsys):
    rc, lines = rehearse(capsys, tiny_catalog(), "--workload",
                         "tiny-bert-powersgd-w1", "--seed", "1", "--seconds",
                         "0.5", "--trace", "0", rehearse_cpu=False)
    assert rc != 0 and lines == []


def test_more_chips_asked_for_than_there_are_fails(capsys, monkeypatch):
    import jax
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a: one)
    rc, lines = rehearse(capsys, tiny_catalog(), "--workload",
                         "tiny-resnet-topk-w4", "--seed", "1", "--seconds",
                         "0.5", "--trace", "0")
    assert rc != 0 and lines == []


def test_unknown_workload_fails(capsys):
    rc, lines = rehearse(capsys, tiny_catalog(), "--workload", "no-such-cell",
                         "--seed", "1", "--seconds", "0.5", "--trace", "0")
    assert rc != 0 and lines == []
