"""The four set-up metrics that read the program's host ledger
(``grace_tpu.telemetry.host``) and the compile ledger's cache durations: on
a ledger filled by hand each reader gives the number it is documented to;
a traced CPU rehearsal of a tiny cell prints all four with their declared
units; against a program that has no host ledger (the parent of the PR
that added them) each reader finds nothing and does not raise. Nothing such
a run prints is a device metric.

The issue named a fifth, ``setup_runq_wait_s`` (the ledger's run-queue wait
at the *built* mark): the chip's machine keeps no ``schedstat``, the reader
read nothing there, and an entry that reads nothing has no place (PERF.md
section 6, PR 38). The ledger still holds the field for a machine that has
it."""

import contextlib
import io
import json
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_harness_helpers import DATA, REPO, harness, run, tiny_catalog  # noqa: E402

NEW = ("setup_pre_program_s", "setup_program_s", "setup_cache_read_s",
       "setup_host_cpu_s")
SOURCES = {"setup_pre_program_s": "program_span",
           "setup_program_s": "program_span",
           "setup_cache_read_s": "host_clock",
           "setup_host_cpu_s": "host_clock"}
CELL = "tiny-resnet-topk-w1"
COMPILE = "/jax/core/compile/backend_compile_duration"


def real_entries():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)["per_layer"]


@pytest.mark.parametrize("name", NEW)
def test_the_entry_is_the_entry_layers_moves_setup_and_lists_no_cell(name):
    (m,) = [m for m in real_entries() if m["name"] == name]
    assert m == {"name": name, "unit": "s", "better": "lower",
                 "source": SOURCES[name], "layer": "entry",
                 "moves": "setup_s"}


def test_the_entries_are_appended_after_what_was_there():
    names = [m["name"] for m in real_entries()]
    first = names.index(NEW[0])
    assert tuple(names[first:]) == NEW
    assert names[first - 1] == "shared_expert_ms"      # PR 32's last
    # nothing lists a run-queue wait: the chip's machine has none to read
    assert "setup_runq_wait_s" not in names
    assert not os.path.exists(os.path.join(
        REPO, "benchmarks", "layer_metrics", "setup_runq_wait_s.py"))


# ---------------------------------------------------------------------------
# a ledger filled by hand
# ---------------------------------------------------------------------------

@pytest.fixture
def hand_filled(monkeypatch):
    """The process's two ledgers replaced by hand-made ones: a process that
    was 6.5 s old when ``grace_tpu`` began to import, two spans (one inside
    the other, a compile interval inside the inner one), two programs
    built, two reads of the cache."""
    from grace_tpu.telemetry import compiles, host

    now = [1000.0]

    def snapshot():
        t = now[0]
        return host.Snapshot(time=t, perf=t, cpu=3 * (t - 993.5),
                             thread_cpu=t - 993.5, runq=(t - 993.5) / 10,
                             thread_runq=0.0, major_faults=0,
                             involuntary_switches=0, pressure_cpu=None,
                             pressure_memory=None, pressure_io=None)

    led = host.HostLedger(snapshot=snapshot, process_age=lambda: 6.5)
    with led.span("outer"):
        now[0] = 1001.0
        with led.span("inner"):
            now[0] = 1004.0
        now[0] = 1005.0
    comp = compiles.CompileLedger()
    comp.on_span(COMPILE, 1002.0, 1003.5, fun_name="f")
    now[0] = 1003.5
    led.on_span(COMPILE, 1002.0, 1003.5, fun_name="f")
    comp.on_span(COMPILE, 1010.0, 1013.5, fun_name="g")
    now[0] = 1013.5
    led.on_span(COMPILE, 1010.0, 1013.5, fun_name="g")
    comp.on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.75)
    comp.on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    monkeypatch.setattr(host, "LEDGER", led)
    monkeypatch.setattr(compiles, "intervals", comp.intervals)
    monkeypatch.setattr(compiles, "durations", comp.durations)
    return led


@pytest.mark.parametrize("name,want", [
    ("setup_pre_program_s", 6.5),
    # outer 5 s less inner's 3 = 2; inner 3 less the compile's 1.5 = 1.5
    ("setup_program_s", 3.5),
    ("setup_cache_read_s", 1.25),
    # at the last built mark, 20 s after the process started: totals
    ("setup_host_cpu_s", 60.0),
])
def test_reader_on_a_ledger_filled_by_hand(hand_filled, name, want):
    read = harness.Catalog().reader(name)
    assert read({"program": object()}) == pytest.approx(want)


def test_before_anything_was_built_there_is_nothing_to_read(hand_filled):
    hand_filled.built = None
    assert harness.Catalog().reader("setup_host_cpu_s")({}) is None


def test_a_platform_without_schedstat_still_has_its_cpu_seconds(hand_filled):
    """The chip's machine: no run-queue wait to read, the rest stands."""
    hand_filled.built = hand_filled.built._replace(runq=None)
    assert harness.Catalog().reader("setup_host_cpu_s")({}) == 60.0
    assert hand_filled.summary()["built"]["runq"] is None


def test_an_unknown_process_start_has_no_pre_program_time(hand_filled):
    hand_filled.process_began = None
    assert harness.Catalog().reader("setup_pre_program_s")({}) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_the_ledgers(
        monkeypatch, name):
    """The parent's ``grace_tpu.telemetry`` has no ``host`` module, and its
    compile ledger no ``durations``."""
    import grace_tpu.telemetry
    from grace_tpu.telemetry import compiles

    monkeypatch.delattr(grace_tpu.telemetry, "host")
    monkeypatch.setitem(sys.modules, "grace_tpu.telemetry.host", None)
    monkeypatch.delattr(compiles, "durations")
    assert harness.Catalog().reader(name)({"program": object()}) is None


# ---------------------------------------------------------------------------
# through run.py
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_lines(tmp_path_factory):
    """One traced rehearsal of a tiny cell under a ``BENCHMARK.json`` that
    is the test-size one plus the four entries of the real one (and
    ``setup_jit_wall_s``, to stand beside them)."""
    from grace_tpu.telemetry import compiles, host

    with open(os.path.join(DATA, "BENCHMARK.tiny.json")) as f:
        spec = json.load(f)
    real = {m["name"]: m for m in real_entries()}
    spec["per_layer"] += [real[n] for n in ("setup_jit_wall_s",) + NEW]
    path = tmp_path_factory.mktemp("host") / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    compiles.reset()           # the test process has built other steps,
    host.reset()               # under spans of their own
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", CELL, "--seed", "2147483888",
                       "--seconds", "0.3", "--trace", "1", "--rehearse-cpu"],
                      tiny_catalog(benchmark_json=str(path)))
    assert rc == 0
    return [json.loads(l) for l in out.getvalue().splitlines() if l.strip()]


@pytest.mark.parametrize("name", NEW)
def test_a_traced_run_prints_the_metric_in_seconds(traced_lines, name):
    m = traced_lines[-1]["metrics"][name]
    assert m["unit"] == "s" and math.isfinite(m["value"]) and m["value"] >= 0


def test_the_parts_are_parts_of_the_set_up(traced_lines):
    """What each number can be at most, on any machine: the time before the
    program and the program's own Python lie inside ``setup_s``; a CPU
    rehearsal places no cache, so nothing was read from one; the process
    computed (XLA compiles on many threads: no upper bound by the wall)."""
    last = traced_lines[-1]
    assert last["correct"] is True
    metrics = {k: v["value"] for k, v in last["metrics"].items()}
    setup_s = next(l for l in traced_lines
                   if l.get("phase") == "setup")["setup_s"]
    assert 0 < metrics["setup_program_s"] < setup_s
    assert (metrics["setup_program_s"] + metrics["setup_jit_wall_s"]
            < setup_s + 1.0)      # the readers run some steps after set-up
    assert metrics["setup_cache_read_s"] == 0.0
    assert metrics["setup_host_cpu_s"] > 0
