"""The six set-up metrics that read the program's compile ledger
(``grace_tpu.telemetry.compiles``): a traced CPU rehearsal of a tiny cell
prints all of them, and against a program that has no ledger (the parent
of the PR that added them) each reader finds nothing and does not raise.
Nothing such a run prints is a device metric."""

import contextlib
import io
import json
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_harness_helpers import DATA, REPO, harness, run, tiny_catalog  # noqa: E402

DURATIONS = ("step_trace_s", "step_lower_s", "step_compile_s",
             "setup_jit_wall_s")
COUNTS = ("compile_cache_misses", "step_lowerings")
CELL = "tiny-resnet-topk-w1"


@pytest.fixture(scope="module")
def traced_lines(tmp_path_factory):
    """One traced rehearsal of a tiny cell under a ``BENCHMARK.json`` that
    is the test-size one plus the six entries of the real one."""
    from grace_tpu.telemetry import compiles

    with open(os.path.join(DATA, "BENCHMARK.tiny.json")) as f:
        spec = json.load(f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    spec["per_layer"] += [real[name] for name in DURATIONS + COUNTS]
    path = tmp_path_factory.mktemp("ledger") / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    compiles.reset()           # the test process has compiled other steps
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", CELL, "--seed", "2147483999",
                       "--seconds", "0.3", "--trace", "1", "--rehearse-cpu"],
                      tiny_catalog(benchmark_json=str(path)))
    assert rc == 0
    return [json.loads(l) for l in out.getvalue().splitlines() if l.strip()]


def test_the_six_entries_move_setup_and_are_read_in_every_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entries = [m for m in spec["per_layer"] if m["name"] in DURATIONS + COUNTS]
    assert [m["name"] for m in entries] == [
        "step_trace_s", "step_lower_s", "step_compile_s", "setup_jit_wall_s",
        "compile_cache_misses", "step_lowerings"]
    assert spec["per_layer"][-6:] == entries            # appended, in order
    for m in entries:
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert "workloads" not in m
        assert m["unit"] == ("s" if m["name"] in DURATIONS else "count")


@pytest.mark.parametrize("name", DURATIONS)
def test_a_traced_run_prints_the_duration(traced_lines, name):
    m = traced_lines[-1]["metrics"][name]
    assert m["unit"] == "s" and math.isfinite(m["value"]) and m["value"] > 0


def test_the_step_was_lowered_once_and_the_rehearsal_keeps_no_cache(
        traced_lines):
    metrics = traced_lines[-1]["metrics"]
    assert traced_lines[-1]["correct"] is True
    assert metrics["step_lowerings"] == {"value": 1.0, "unit": "count"}
    # a CPU rehearsal places no persistent cache: nothing to miss
    assert metrics["compile_cache_misses"] == {"value": 0.0, "unit": "count"}


def test_inside_and_outside_time_one_interval(traced_lines):
    """``compile_s`` of the ``setup`` line is the harness's clock around
    ``fn.lower().compile()``; the ledger's lowering and compile spans of
    the step lie inside it (how much of it they fill is the chip's to say:
    ``PERF.md`` §6)."""
    metrics = traced_lines[-1]["metrics"]
    outside = next(l for l in traced_lines
                   if l.get("phase") == "setup")["compile_s"]
    inside = metrics["step_lower_s"]["value"] + metrics["step_compile_s"]["value"]
    assert 0 < inside <= outside
    # every program of set-up is in the union; the step's own are part of it
    step = inside + metrics["step_trace_s"]["value"]
    assert metrics["setup_jit_wall_s"]["value"] >= step


@pytest.mark.parametrize("name", DURATIONS + COUNTS)
def test_reader_finds_nothing_in_a_program_without_the_ledger(
        monkeypatch, name):
    import grace_tpu.telemetry

    monkeypatch.delattr(grace_tpu.telemetry, "compiles")
    monkeypatch.setitem(sys.modules, "grace_tpu.telemetry.compiles", None)
    read = harness.Catalog().reader(name)
    # the parent's step has no ``fun_name`` either: nothing is asked of it
    assert read({"program": object()}) is None
