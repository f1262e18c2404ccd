"""The host ledger (``grace_tpu.telemetry.host``): what the operating
system counts, parsed from fixture strings; spans, self time and the marks
on hand-made events; a stalled step's cause from an injected snapshot and
from two real ones (a busy loop, a sleep); the recorder's ``perf_stall``
records and the report's lines; the spans on a profile's host plane.

Nothing here is a time of a device: every number is the host's clock or a
counter of the kernel's, on the CPU.
"""

from __future__ import annotations

import ast
import glob
import importlib.util
import json
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grace_tpu.profiling import ProfileRecorder
from grace_tpu.telemetry import compiles, host
from grace_tpu.utils import profiling
from grace_tpu.utils.profiling import StepTimer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


# ---------------------------------------------------------------------------
# parsers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("text,want", [
    ("464727 61189 2\n", 61189e-9),
    ("98765432100 2500000000 4120", 2.5),
    ("0 0 0", 0.0),
    ("464727", None),                 # a kernel without the second field
    ("12 waiting 3", None),
    ("", None),
    (None, None),                     # the file is not there
])
def test_schedstat_wait_is_the_second_field_in_seconds(text, want):
    got = host.parse_schedstat_wait_s(text)
    assert got == want if want is None else got == pytest.approx(want)


STAT = ("30722 ({comm}) R 30714 30722 30714 0 -1 4194304 103 0 0 0 0 0 0 0 "
        "20 0 1 0 6701652 2998272 413 18446744073709551615 1 1 0 0 0 0 0")


@pytest.mark.parametrize("text,ticks,want", [
    (STAT.format(comm="python3"), 100, 67016.52),
    (STAT.format(comm="a b) (c"), 100, 67016.52),   # a name with ") ("
    (STAT.format(comm="python3"), 1000, 6701.652),
    ("30722 (python3) R 1 2 3", 100, None),          # cut short
    (STAT.format(comm="python3"), 0, None),
    ("", 100, None),
    (None, 100, None),
])
def test_stat_start_time_is_field_22_counted_from_the_last_parenthesis(
        text, ticks, want):
    got = host.parse_stat_start_s(text, ticks)
    assert got == want if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("text,want", [
    ("some avg10=0.98 avg60=1.39 avg300=1.22 total=1866226060\n"
     "full avg10=0.00 avg60=0.00 avg300=0.00 total=0\n", 1866.22606),
    ("full avg10=0.00 avg60=0.00 avg300=0.00 total=7\n"
     "some avg10=0.00 avg60=0.00 avg300=0.00 total=250000\n", 0.25),
    ("full avg10=0.00 avg60=0.00 avg300=0.00 total=7\n", None),
    ("some avg10=0.98 total=soon\n", None),
    ("", None),
    (None, None),
])
def test_pressure_is_the_some_lines_total_in_seconds(text, want):
    got = host.parse_pressure_some_s(text)
    assert got == want if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("asked_once", [True, False])
def test_a_missing_file_reads_none_not_zero(monkeypatch, tmp_path,
                                            asked_once):
    """A kernel without the files (the chip tool's machine): asked once when
    the module loads, and not again; or a file that goes away later."""
    assert host._read(str(tmp_path / "absent")) is None
    opened = []
    monkeypatch.setattr(host, "_read", lambda path: opened.append(path))
    monkeypatch.setattr(os, "listdir", lambda path: ["1", "2"])
    if asked_once:
        monkeypatch.setattr(host, "_HAS_SCHEDSTAT", False)
        monkeypatch.setattr(host, "_PRESSURES", ())
    snap, thread = host.snapshot(), host.thread_snapshot()
    assert (opened == []) if asked_once else len(opened) >= 3
    assert snap.runq is None and snap.thread_runq is None
    assert snap.pressure_cpu is None and snap.pressure_io is None
    assert thread.runq is None
    assert host.process_age_s() is None
    # what needs no file is still read
    assert snap.cpu > 0 and thread.cpu > 0 and snap.major_faults is not None


def test_a_real_snapshot_rises_and_the_thread_is_part_of_the_process():
    a = host.snapshot()
    x = 0
    for i in range(200_000):
        x += i * i
    b = host.snapshot()
    d = host.deltas(a, b)
    assert d["perf"] > 0 and d["time"] > 0
    assert d["cpu"] > 0 and d["thread_cpu"] > 0
    if b.runq is not None:                  # this kernel keeps schedstat
        assert d["runq"] >= 0 and b.runq >= b.thread_runq >= 0
    age = host.process_age_s()
    if age is not None:
        assert 0 < age < 24 * 3600


def test_deltas_are_none_where_either_side_lacks_the_field():
    a = host.ThreadSnapshot(perf=1.0, cpu=0.5, runq=None, major_faults=3)
    b = host.ThreadSnapshot(perf=3.0, cpu=0.75, runq=0.25, major_faults=5)
    assert host.deltas(a, b) == {"perf": 2.0, "cpu": 0.25, "runq": None,
                                 "major_faults": 2}


# ---------------------------------------------------------------------------
# the ledger on hand-made events and spans
# ---------------------------------------------------------------------------

class Clock:
    """A snapshot function whose every field follows one hand-set time."""

    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        t = self.t
        return host.Snapshot(time=t, perf=t - 90.0, cpu=2 * t,
                             thread_cpu=t / 2, runq=t / 4, thread_runq=t / 8,
                             major_faults=int(t), involuntary_switches=None,
                             pressure_cpu=None, pressure_memory=0.0,
                             pressure_io=t)


def hand_ledger(clock, age=7.5):
    return host.HostLedger(snapshot=clock, process_age=lambda: age)


def test_process_start_is_the_ledgers_birth_less_the_process_age():
    led = hand_ledger(Clock(100.0), age=7.5)
    assert led.process_began == 92.5 and led.pre_program_s() == 7.5
    assert hand_ledger(Clock(), age=None).pre_program_s() is None


def test_a_ledger_made_from_an_earlier_snapshot_counts_from_it():
    """``grace_tpu`` begins to import when ``host.py`` loads; ``import jax``
    may come between that and the ledger: the age is read against now."""
    clock = Clock(100.0)
    early = clock()
    led = host.HostLedger(snapshot=clock, process_age=lambda: 2.0,
                          at_load=early)
    assert led.made is early
    assert led.process_began == pytest.approx(time.time() - 2.0, abs=1.0)


@pytest.mark.parametrize("answer,want", [
    (lambda: True, True),       # jax.devices() came before the import
    (lambda: False, False),     # the program was imported first
    (None, None),               # a JAX without the private name
])
def test_the_ledger_says_whether_the_chip_was_reached_before_the_program(
        monkeypatch, answer, want):
    """The time before the program is named for what it held: the flag is
    read once, when the ledger is made, and no span asks again."""
    asked = []

    def asking():
        asked.append(1)
        return answer()

    monkeypatch.setattr(host, "backends_are_initialized",
                        None if answer is None else asking)
    led = hand_ledger(Clock())
    assert led.backends_ready_at_load is want
    assert led.summary()["backends_ready_at_load"] is want
    n = len(asked)
    with led.span("a"):
        with led.span("b"):
            pass
    assert len(asked) == n
    assert not hasattr(led, "reach_s")


def test_spans_nest_and_keep_their_deltas():
    clock = Clock(100.0)
    led = hand_ledger(clock)
    with led.span("outer") as outer:
        clock.t = 101.0
        with led.span("inner") as inner:
            clock.t = 103.0
        clock.t = 104.0
    with led.span("next"):
        clock.t = 106.0
    assert [(s["name"], s["parent"], s["start"], s["end"])
            for s in led.spans] == [("outer", None, 100.0, 104.0),
                                    ("inner", 0, 101.0, 103.0),
                                    ("next", None, 104.0, 106.0)]
    assert inner["deltas"]["cpu"] == 4.0 and inner["deltas"]["runq"] == 0.5
    assert outer["deltas"]["major_faults"] == 4
    assert outer["deltas"]["pressure_cpu"] is None      # the platform's lack
    assert outer["deltas"]["pressure_memory"] == 0.0    # a counted nothing
    assert "_before" not in outer and "_annotation" not in outer


@pytest.mark.parametrize("compile_intervals,want", [
    # name -> self seconds. outer [100, 110] holds inner [102, 105].
    ([], {"outer": 7.0, "inner": 3.0}),
    # a compile inside the child is the child's to lose, not the parent's
    ([(103.0, 104.0)], {"outer": 7.0, "inner": 2.0}),
    # one inside the parent only
    ([(106.0, 108.5)], {"outer": 4.5, "inner": 3.0}),
    # one across the child's end counts once in the parent
    ([(104.0, 107.0)], {"outer": 5.0, "inner": 2.0}),
    # one that began before the span and one that ends after it are cut
    ([(90.0, 101.0), (109.5, 120.0)], {"outer": 5.5, "inner": 3.0}),
    # one that swallows everything
    ([(99.0, 111.0)], {"outer": 0.0, "inner": 0.0}),
    # intervals wholly outside change nothing
    ([(10.0, 20.0), (95.0, 99.0), (111.0, 112.0)],
     {"outer": 7.0, "inner": 3.0}),
])
def test_self_time_is_less_child_spans_and_compile_intervals(
        compile_intervals, want):
    clock = Clock(100.0)
    led = hand_ledger(clock)
    with led.span("outer"):
        clock.t = 102.0
        with led.span("inner"):
            clock.t = 105.0
        clock.t = 110.0
    assert dict(led.self_times(compile_intervals)) == pytest.approx(want)
    assert led.program_s(compile_intervals) == pytest.approx(
        sum(want.values()))


def test_an_empty_ledger_has_no_program_time_and_an_open_span_is_not_counted():
    clock = Clock()
    led = hand_ledger(clock)
    assert led.program_s() is None
    row = led.begin("open")
    assert led.closed() == [] and led.program_s() is None
    clock.t += 1.0
    led.end(row)
    assert led.program_s() == 1.0


def test_the_built_mark_is_the_last_compile_event():
    clock = Clock(100.0)
    led = hand_ledger(clock)
    assert led.built is None
    led.on_span(TRACE, 100.0, 100.5, fun_name="f")
    led.on_span(LOWER, 100.5, 100.6, fun_name="f")
    assert led.built is None and led.builds == 0      # nothing built yet
    clock.t = 103.0
    led.on_span(COMPILE, 100.6, 103.0, fun_name="f")
    first = led.built
    clock.t = 109.0
    led.on_span(TRACE, 108.0, 108.5, fun_name="g")
    led.on_span(COMPILE, 108.5, 109.0, fun_name="g")
    assert first.time == 103.0 and led.built.time == 109.0
    assert led.builds == 2
    # totals since the process started, by their nature
    assert led.built.cpu == 218.0 and led.built.runq == 27.25


def test_spans_past_the_cap_are_counted_not_kept(monkeypatch):
    monkeypatch.setattr(host, "MAX_SPANS", 3)
    led = hand_ledger(Clock())
    for i in range(5):
        with led.span(f"s{i}"):
            pass
    assert [s["name"] for s in led.spans] == ["s0", "s1", "s2"]
    assert led.dropped == 2


def test_a_span_past_the_cap_reads_nothing(monkeypatch):
    """A tuner's thousandth configuration pays for no snapshot and opens no
    annotation: its span is counted and that is all."""
    monkeypatch.setattr(host, "MAX_SPANS", 1)
    taken, opened = [], []

    def counting():
        taken.append(1)
        return Clock()()

    led = host.HostLedger(snapshot=counting, process_age=lambda: 1.0)
    with led.span("kept"):
        pass
    n = len(taken)
    monkeypatch.setattr(host.jax.profiler, "TraceAnnotation",
                        lambda name: opened.append(name))
    with led.span("outer") as outer:
        with led.span("inner") as inner:
            pass
    assert outer is None and inner is None
    assert len(taken) == n and opened == [] and led.dropped == 2
    assert led.summary()["spans_dropped"] == 2
    assert host.spanned("f")(lambda x: x + 1)(1) == 2   # and calls go through


def test_a_span_that_raises_is_closed_and_leaves_the_stack():
    led = hand_ledger(Clock())
    with pytest.raises(ValueError):
        with led.span("fails"):
            raise ValueError("boom")
    with led.span("after"):
        pass
    assert [(s["name"], s["parent"], s["end"] is not None)
            for s in led.spans] == [("fails", None, True),
                                    ("after", None, True)]


def test_the_summary_is_plain_data():
    clock = Clock(100.0)
    led = hand_ledger(clock)
    with led.span("a"):
        clock.t = 101.0
    led.on_span(COMPILE, 100.0, 101.0, fun_name="f")
    doc = json.loads(json.dumps(led.summary()))
    assert doc["pre_program_s"] == 7.5 and doc["builds"] == 1
    assert doc["process_began"] == 92.5
    # the compile ledger's two beside them: set-up's parts in one place
    assert doc["jit_wall_s"] == compiles.wall_s()
    assert doc["cache_read_s"] == compiles.durations()["cache_read_s"]
    assert "reach_s" not in doc
    assert doc["spans"][0]["name"] == "a" and doc["spans"][0]["cpu"] == 2.0
    assert doc["built"]["time"] == 101.0


# ---------------------------------------------------------------------------
# the process's own ledger: the boundaries on the set-up path
# ---------------------------------------------------------------------------

@pytest.fixture
def mesh():
    from grace_tpu.parallel import data_parallel_mesh
    return data_parallel_mesh()


@pytest.fixture
def process_ledger():
    """The process's own ledger, emptied: this test process may have built
    a thousand configurations before, and the ledger stops at its cap."""
    host.reset()
    return host.LEDGER


IMPORTING = """
import json, os, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.devices()                      # as benchmarks/run.py: the chip first
before = time.time()
import grace_tpu
after = time.time()
from grace_tpu.telemetry import host
led = host.LEDGER
print(json.dumps({"before": before, "after": after, "made": led.made.time,
                  "began": led.process_began, "pre": led.pre_program_s(),
                  "ready": led.backends_ready_at_load,
                  "spans": [[s["name"], s["parent"], s["start"], s["end"]]
                            for s in led.spans],
                  "program_s": led.program_s()}))
"""


def test_the_import_of_the_package_is_the_ledgers_first_span():
    """In a process of its own, as an entry script does it: the ledger is
    made by the first line of ``grace_tpu/__init__.py`` and the import is
    its first span, from that line to the last."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    done = subprocess.run([sys.executable, "-c", IMPORTING], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    doc = json.loads(done.stdout.strip().splitlines()[-1])
    assert doc["spans"] and doc["spans"][0][:2] == ["import", None]
    _, _, start, end = doc["spans"][0]
    assert start == doc["made"]
    assert doc["before"] <= start <= end <= doc["after"]
    assert doc["ready"] is True          # jax.devices() came first
    # process start -> the import began: the interpreter, jax, the backend
    assert doc["began"] < doc["before"]
    assert doc["pre"] == pytest.approx(doc["made"] - doc["began"])
    assert 0 < doc["pre"] < 300
    assert 0 < doc["program_s"] <= doc["after"] - doc["before"]


def test_set_up_through_the_public_entry_points_leaves_its_spans(
        mesh, process_ledger):
    import optax
    from grace_tpu import grace_from_params
    from grace_tpu.train import (init_stateful_train_state,
                                 make_stateful_train_step)
    from grace_tpu.utils.compile_cache import place_compile_cache

    before = len(host.LEDGER.spans)
    place_compile_cache("cpu")
    grc = grace_from_params({"compressor": "topk", "compress_ratio": 0.1,
                             "memory": "residual",
                             "communicator": "allgather"})
    tx = optax.chain(grc.transform(seed=0), optax.sgd(0.1))

    def loss_fn(p, mstate, batch):
        return jnp.mean((batch @ p["w"]) ** 2), mstate

    step = make_stateful_train_step(loss_fn, tx, mesh, donate=False)
    state = init_stateful_train_state({"w": jnp.ones((4, 2))}, {}, tx, mesh)
    n = mesh.devices.size
    state, _ = step(state, jnp.ones((2 * n, 4)))
    state, _ = step(state, jnp.ones((2 * n, 4)))      # no second wrap
    new = host.LEDGER.spans[before:]
    assert [s["name"] for s in new] == [
        "place_compile_cache", "grace_from_params", "transform",
        "make_stateful_train_step", "init_stateful_train_state",
        "init_opt_state", "wrap_step"]
    by_name = {s["name"]: s for s in new}
    assert (by_name["init_opt_state"]["parent"]
            == by_name["init_stateful_train_state"]["index"])
    assert all(s["end"] >= s["start"] for s in new)
    # the initialiser compiled a program: JAX's time is not the span's own
    times = dict(host.LEDGER.self_times(compiles.intervals())[before:])
    init = by_name["init_opt_state"]
    assert times["init_opt_state"] < init["end"] - init["start"]
    assert host.LEDGER.built is not None and host.LEDGER.builds > 0


def test_the_plain_train_steps_builders_are_spans_too(mesh, process_ledger):
    import optax
    from grace_tpu.train import init_train_state, make_train_step

    before = len(host.LEDGER.spans)
    tx = optax.sgd(1e-2)
    make_train_step(lambda p, b: jnp.mean((b @ p["w"]) ** 2), tx, mesh)
    init_train_state({"w": jnp.ones((4, 2))}, tx, mesh)
    assert [s["name"] for s in host.LEDGER.spans[before:]] == [
        "make_train_step", "init_train_state", "init_opt_state"]


def test_a_spanned_function_keeps_its_name_signature_and_result(
        process_ledger):
    import inspect
    from grace_tpu.transform import grace_transform
    from grace_tpu.train import make_train_step

    assert grace_transform.__name__ == "grace_transform"
    assert "compressor" in inspect.signature(grace_transform).parameters
    assert "loss_fn" in inspect.signature(make_train_step).parameters

    @host.spanned("answer")
    def answer(x, *, y=1):
        return x + y

    before = len(host.LEDGER.spans)
    assert answer(2, y=3) == 5
    assert host.LEDGER.spans[before]["name"] == "answer"


def test_the_host_ledger_starts_no_thread_and_reads_no_environment():
    with open(host.__file__) as f:
        source = f.read()
    tree = ast.parse(source)
    called = {ast.unparse(n.func) for n in ast.walk(tree)
              if isinstance(n, ast.Call)}
    assert not {c for c in called if c.endswith("Thread") or "Timer" in c}
    assert "environ" not in source and "getenv" not in source


# ---------------------------------------------------------------------------
# the compile ledger's new reads
# ---------------------------------------------------------------------------

def test_the_cache_reads_are_summed_and_other_durations_ignored():
    led = compiles.CompileLedger()
    assert led.durations() == {"cache_read_s": 0.0}
    led.on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    led.on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    # what JAX reckons a hit saved has no reader and is not kept
    led.on_duration("/jax/compilation_cache/compile_time_saved_sec", 3.0)
    led.on_duration("/jax/some/other_duration", 9.0)
    assert led.durations() == {"cache_read_s": 0.75}
    led.reset()
    assert led.durations() == {"cache_read_s": 0.0}


def test_intervals_are_a_copy_and_lowerings_count_every_function():
    led = compiles.CompileLedger()
    led.on_span(TRACE, 1.0, 2.0, fun_name="f")
    led.on_span(LOWER, 2.0, 2.5, fun_name="f")
    led.on_span(LOWER, 4.0, 4.5, fun_name="jit(g)")
    assert led.intervals() == [(1.0, 2.5), (4.0, 4.5)]
    led.intervals().clear()
    assert led.wall_s() == 2.0
    assert led.lowerings() == 2


def test_jax_fires_the_cache_durations_at_the_ledger(tmp_path):
    """A hit of the persistent cache reaches ``durations()`` through the
    listener registered at import."""
    from jax import monitoring

    before = compiles.durations()
    monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.125)
    assert (compiles.durations()["cache_read_s"]
            == pytest.approx(before["cache_read_s"] + 0.125))


# ---------------------------------------------------------------------------
# a stalled step's cause
# ---------------------------------------------------------------------------

class Script:
    """A thread-snapshot function that plays steps of given parts: two
    calls a step, the second later by the step's ``(wall, cpu, runq,
    faults)``."""

    def __init__(self, steps, runq=True):
        self.now = host.ThreadSnapshot(perf=0.0, cpu=0.0,
                                       runq=0.0 if runq else None,
                                       major_faults=0)
        self.steps = iter(steps)
        self.inside = False

    def __call__(self):
        if self.inside:
            wall, cpu, runq, faults = next(self.steps)
            n = self.now
            self.now = host.ThreadSnapshot(
                perf=n.perf + wall, cpu=n.cpu + cpu,
                runq=None if n.runq is None else n.runq + runq,
                major_faults=n.major_faults + faults)
        self.inside = not self.inside
        return self.now


STEADY = (0.100, 0.002, 0.0, 0)


def run_script(steps, warmup=0, runq=True, lowering_at=()):
    timer = StepTimer(warmup=warmup, snapshot=Script(steps, runq=runq))
    for i in range(len(steps)):
        with timer.step():
            if i in lowering_at:
                compiles.LEDGER.on_span(LOWER, 0.0, 0.001, fun_name="retraced")
            timer.sync_on(jnp.zeros(()))
    return timer


@pytest.mark.parametrize("parts,lowering,cause", [
    # (wall, cpu, runq, faults) of the sixth step, after five steady ones
    ((3.0, 0.01, 2.9, 0), False, "runq"),      # the machine gave it no CPU
    ((3.0, 2.8, 0.1, 0), False, "cpu"),        # the thread computed
    ((3.0, 0.01, 0.02, 7), False, "blocked"),  # asleep in the runtime
    ((3.0, 2.8, 0.1, 0), True, "compile"),     # computed: a retrace
    ((3.0, 0.3, 0.1, 0), True, "compile"),     # waited for XLA's threads
    ((3.0, 0.01, 2.9, 0), True, "runq"),       # starved, retrace or not
])
def test_a_stalled_step_is_given_its_largest_part_as_cause(
        parts, lowering, cause):
    timer = run_script([STEADY] * 5 + [parts] + [STEADY] * 2,
                       lowering_at=(5,) if lowering else ())
    assert len(timer) == 8 and len(timer.stalls) == 1
    row = timer.stalls[0]
    wall, cpu, runq, faults = parts
    assert row["step"] == 5 and row["cause"] == cause
    assert row["wall_s"] == pytest.approx(wall)
    assert row["cpu_s"] == pytest.approx(cpu)
    assert row["runq_s"] == pytest.approx(runq)
    assert row["blocked_s"] == pytest.approx(wall - cpu - runq)
    assert row["major_faults"] == faults


@pytest.mark.parametrize("parts", [
    (0.149, 0.1, 0.0, 0),     # 1.49 times the median: under the ratio
    (0.145, 0.0, 0.04, 0),    # over nothing
    (0.100, 0.09, 0.0, 0),
])
def test_a_slow_step_is_not_a_stalled_one(parts):
    assert run_script([STEADY] * 5 + [parts] + [STEADY]).stalls == []


def test_a_step_fifty_per_cent_over_a_short_median_is_under_the_floor():
    """1.5 times the median and *at least 50 ms over it*: steps of 10 ms
    that take 40 ms once are jitter, not a stall."""
    short = (0.010, 0.001, 0.0, 0)
    assert run_script([short] * 5 + [(0.040, 0.03, 0.0, 0)]).stalls == []
    assert len(run_script([short] * 5 + [(0.061, 0.05, 0.0, 0)]).stalls) == 1


def test_warmup_steps_and_the_first_steady_ones_are_never_stalls():
    compile_step = (20.0, 5.0, 0.1, 100)
    timer = run_script([compile_step, compile_step] + [STEADY] * 6, warmup=2)
    assert timer.stalls == []
    # without a median yet (fewer than three steady steps) nothing is asked
    assert run_script([STEADY, STEADY, (9.0, 0.0, 0.0, 0)]).stalls == []


def test_the_median_runs_with_the_steps():
    """A run whose steps grow slowly (a load that drifts) has no stall; a
    step against the *latest* median is the one that counts."""
    grow = [(0.100 * 1.005 ** i, 0.002, 0.0, 0) for i in range(120)]
    assert run_script(grow).stalls == []
    timer = run_script(grow + [(0.100 * 1.005 ** 120 * 1.7, 0.0, 0.0, 0)])
    assert [s["step"] for s in timer.stalls] == [120]


def test_without_schedstat_the_wait_is_none_and_counts_as_blocked():
    timer = run_script([STEADY] * 5 + [(3.0, 0.01, 0.0, 0)], runq=False)
    row = timer.stalls[0]
    assert row["runq_s"] is None and row["cause"] == "blocked"
    assert row["blocked_s"] == pytest.approx(2.99)


def test_a_step_that_raises_is_still_timed_and_judged():
    timer = StepTimer(warmup=0, snapshot=Script(
        [STEADY] * 4 + [(2.0, 1.9, 0.0, 0)]))
    for _ in range(4):
        with timer.step():
            timer.sync_on(jnp.zeros(()))
    with pytest.raises(RuntimeError):
        with timer.step():
            raise RuntimeError("died in a slow step")
    assert timer.failed_steps == 1 and len(timer) == 5
    assert timer.stalls[0]["cause"] == "cpu" and timer.stalls[0]["step"] == 4


def real_steps(timer, n, body=lambda: None):
    for _ in range(n):
        with timer.step():
            body()
            timer.sync_on(jnp.zeros(()))


def the_last_steps_stall(timer):
    """The row of the step that was made slow. A loaded machine (the other
    test workers) may stall one of the 10 ms steps before it as well: that
    is the machine's and not what these tests are about."""
    rows = [r for r in timer.stalls if r["step"] == 6]
    assert len(rows) == 1, timer.stalls
    return rows[0]


def test_a_real_busy_loop_is_a_cpu_stall():
    timer = StepTimer(warmup=0)
    real_steps(timer, 6, lambda: time.sleep(0.01))

    def busy():
        end = time.thread_time() + 0.25       # a quarter second of this
        while time.thread_time() < end:       # thread's own CPU time
            pass

    real_steps(timer, 1, busy)
    row = the_last_steps_stall(timer)
    assert row["cpu_s"] >= 0.24 and row["wall_s"] >= row["cpu_s"]
    assert row["blocked_s"] < row["cpu_s"]
    # where the machine kept the thread waiting for a CPU longer than it
    # let it compute (six test workers on eight cores do), the cause is
    # rightly the machine's
    starved = (row["runq_s"] or 0.0) > row["cpu_s"]
    assert row["cause"] == ("runq" if starved else "cpu")


def test_a_real_sleep_is_a_blocked_stall():
    timer = StepTimer(warmup=0)
    real_steps(timer, 6, lambda: time.sleep(0.01))
    real_steps(timer, 1, lambda: time.sleep(0.3))
    row = the_last_steps_stall(timer)
    assert row["blocked_s"] >= 0.2 and row["cpu_s"] < 0.1
    starved = (row["runq_s"] or 0.0) > row["blocked_s"]
    assert row["cause"] == ("runq" if starved else "blocked")


def test_confidence95_is_gone_and_the_rest_of_the_timer_stands():
    timer = run_script([STEADY] * 4)
    assert not hasattr(timer, "confidence95")
    assert timer.mean_sec == pytest.approx(0.1)
    assert timer.p50_sec == pytest.approx(0.1)
    assert timer.throughput(32) == pytest.approx(320.0)
    assert (profiling.STALL_RATIO, profiling.STALL_MIN_S) == (1.5, 0.05)


# ---------------------------------------------------------------------------
# the recorder's records and the report's lines
# ---------------------------------------------------------------------------

class ListSink:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(dict(rec))

    def close(self):
        pass


def test_the_recorder_emits_one_perf_stall_a_stalled_step_and_counts_them():
    sink = ListSink()
    rec = ProfileRecorder(sink, every=4, warmup=0)
    steps = [STEADY] * 5 + [(3.0, 0.01, 2.9, 2)] + [STEADY] * 4 + \
        [(1.0, 0.9, 0.0, 0)] + [STEADY]
    rec.timer = StepTimer(warmup=0, snapshot=Script(steps))
    # a resumed run: the loop's first step is 1,000
    for i in range(len(steps)):
        with rec.step():
            rec.sync_on(jnp.zeros(()))
        rec.update(1000 + i)
    stalls = [r for r in sink.records if r["event"] == "perf_stall"]
    assert [(r["step"], r["cause"]) for r in stalls] == [(1005, "runq"),
                                                         (1010, "cpu")]
    assert set(stalls[0]) == {"event", "step", "wall_s", "cpu_s", "runq_s",
                              "blocked_s", "major_faults", "cause"}
    assert stalls[0]["major_faults"] == 2
    times = [r for r in sink.records if r["event"] == "perf_step_times"]
    assert [t["stalls"] for t in times] == [0, 1, 2]
    # each stall once, before the window's percentiles
    order = [r["event"] for r in sink.records
             if r["event"] in ("perf_stall", "perf_step_times")]
    assert order == ["perf_step_times", "perf_stall", "perf_step_times",
                     "perf_stall", "perf_step_times"]
    assert rec.flush(1011)[0]["event"] == "perf_step_times"    # none twice


def test_the_recorder_emits_the_host_ledgers_summary_once(process_ledger):
    """``perf_setup``: why this job took so long to its first step, at the
    first flush and never again."""
    with host.span("make_train_step"):
        time.sleep(0.01)
    sink = ListSink()
    rec = ProfileRecorder(sink, every=2, warmup=0)
    for i in range(4):
        with rec.step():
            rec.sync_on(jnp.zeros(()))
        if i == 0:
            done = time.time()
        rec.update(i)
        time.sleep(0.02)             # the loop goes on: not set-up's time
    setups = [r for r in sink.records if r["event"] == "perf_setup"]
    assert len(setups) == 1
    assert sink.records[0]["event"] == "perf_setup"      # first of the flush
    doc = json.loads(json.dumps(setups[0]))              # plain data
    assert doc["step"] == 1
    want = host.LEDGER.summary()
    for key in ("pre_program_s", "backends_ready_at_load", "program_s",
                "spans_dropped", "builds", "process_began"):
        assert doc[key] == want[key], key
    assert [s["name"] for s in doc["spans"]] == ["make_train_step"]
    assert doc["spans"][0]["self_s"] >= 0.009
    # process start through the loop's first step (its first ``update``),
    # on time.time()'s clock
    assert doc["to_first_step_s"] == pytest.approx(
        done - doc["process_began"], abs=0.015)
    assert doc["to_first_step_s"] > doc["pre_program_s"] > 0
    assert rec.flush(4)[0]["event"] == "perf_step_times"


def test_a_loop_that_only_flushes_gets_its_set_up_record_there():
    sink = ListSink()
    rec = ProfileRecorder(sink)
    assert [r["event"] for r in rec.flush(0)][0] == "perf_setup"
    assert "perf_setup" not in [r["event"] for r in rec.flush(1)]
    assert [r["event"] for r in sink.records].count("perf_setup") == 1
    began = sink.records[0]["process_began"]
    assert sink.records[0]["to_first_step_s"] == pytest.approx(
        time.time() - began, abs=1.0)


def _tools_import(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_report_lists_stalls_under_profiling(tmp_path, capsys):
    telemetry_report = _tools_import("telemetry_report")
    rows = [
        {"provenance": {"data": "synthetic"}},
        {"step": 0, "grad_norm": 1.0, "wire_bytes": 10, "dense_bytes": 40},
        {"event": "perf_stall", "step": 4317, "wall_s": 3.012,
         "cpu_s": 0.011, "runq_s": 2.9, "blocked_s": 0.101,
         "major_faults": 0, "cause": "runq"},
        {"event": "perf_stall", "step": 5000, "wall_s": 1.5, "cpu_s": 0.2,
         "runq_s": None, "blocked_s": 1.3, "major_faults": 12,
         "cause": "blocked"},
        {"event": "perf_step_times", "step": 5999, "n_steps": 6000,
         "mean_ms": 2.0, "p50_ms": 1.9, "max_ms": 3012.0, "stalls": 2},
    ]
    path = tmp_path / "run.jsonl"
    path.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    assert telemetry_report.main([str(path)]) == 0
    text = capsys.readouterr().out.split("== profiling")[1]
    assert "stalled steps: 2" in text
    assert "step 4317: wall 3.012 s = cpu 0.011 + runq 2.900 + blocked 0.101" \
        in text
    assert "cause runq" in text and "gave the thread no CPU" in text
    assert "runq n/a" in text and "cause blocked" in text
    assert "major faults 12" in text


def test_the_report_splits_set_up_into_its_named_parts(tmp_path, capsys):
    telemetry_report = _tools_import("telemetry_report")
    span = {"parent": None, "thread_cpu": 0.1, "thread_runq": None,
            "involuntary_switches": 0, "pressure_cpu": None,
            "pressure_memory": None, "pressure_io": None}
    rows = [
        {"provenance": {"data": "synthetic"}},
        {"step": 0, "grad_norm": 1.0, "wire_bytes": 10, "dense_bytes": 40},
        {"event": "perf_setup", "step": 19, "to_first_step_s": 45.0,
         "process_began": 1000.0, "pre_program_s": 12.35,
         "backends_ready_at_load": True, "jit_wall_s": 16.2,
         "cache_read_s": 4.11, "program_s": 0.22,
         "spans": [dict(span, name="import", start=1012.35, end=1013.2,
                        self_s=0.83, cpu=0.8, runq=0.012, major_faults=3),
                   dict(span, name="init_opt_state", start=1020.0,
                        end=1020.45, self_s=0.03, cpu=0.5, runq=None,
                        major_faults=None)],
         "spans_dropped": 7, "builds": 37,
         "built": {"time": 1040.0, "perf": 40.0, "cpu": 24.23,
                   "thread_cpu": 20.0, "runq": 1.5, "thread_runq": 0.5,
                   "major_faults": 4, "involuntary_switches": 900,
                   "pressure_cpu": 12.5, "pressure_memory": None,
                   "pressure_io": 0.25}},
        {"event": "perf_step_times", "step": 19, "n_steps": 18,
         "mean_ms": 2.0, "p50_ms": 1.9, "max_ms": 3.0, "stalls": 0},
    ]
    path = tmp_path / "run.jsonl"
    path.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    assert telemetry_report.main([str(path)]) == 0
    text = capsys.readouterr().out.split("== profiling")[1]
    assert "set-up: 45.00 s from process start through the first step" in text
    assert "before grace_tpu began to import: 12.35 s (the chip was " \
        "reached in it)" in text
    assert "compile events: 16.20 s, of which reading the persistent " \
        "cache 4.11" in text
    assert "(the spans' self time): 0.22 s" in text
    assert "unnamed (the caller's code between the spans, the first " \
        "step's run): 16.23 s" in text
    assert "span import at 12.35 s: 0.850 s, self 0.830, process cpu " \
        "0.800, runq 0.012, major faults 3" in text
    assert "span init_opt_state at 20.00 s: 0.450 s, self 0.030, process " \
        "cpu 0.500, runq n/a, major faults None" in text
    assert "spans not kept: 7" in text
    assert "when the last of 37 programs was built: process cpu 24.23 s, " \
        "runnable but waiting 1.50 s, major faults 4, involuntary " \
        "switches 900" in text
    assert "cpu 12.50, memory n/a, io 0.25" in text
    assert text.index("set-up:") < text.index("step times")


def test_the_report_stands_a_set_up_record_of_a_bare_platform(tmp_path,
                                                              capsys):
    """Where the platform counts nothing (no ``/proc``): ``n/a``, no raise."""
    telemetry_report = _tools_import("telemetry_report")
    rows = [
        {"provenance": {"data": "synthetic"}},
        {"step": 0, "grad_norm": 1.0, "wire_bytes": 10, "dense_bytes": 40},
        {"event": "perf_setup", "step": 0, "to_first_step_s": None,
         "process_began": None, "pre_program_s": None,
         "backends_ready_at_load": None, "jit_wall_s": 0.0,
         "cache_read_s": 0.0, "program_s": None, "spans": [],
         "spans_dropped": 0, "builds": 0, "built": None},
    ]
    path = tmp_path / "run.jsonl"
    path.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    assert telemetry_report.main([str(path)]) == 0
    text = capsys.readouterr().out.split("== profiling")[1]
    assert "set-up: n/a s from process start" in text
    assert "began to import: n/a s\n" in text
    assert "step's run): n/a s" in text and "programs was built" \
        not in text


# ---------------------------------------------------------------------------
# under a profile the spans stand on the profiler's clock
# ---------------------------------------------------------------------------

def test_a_profile_of_an_operators_loop_carries_the_host_spans(tmp_path):
    """``StepTimer`` around a ResNet step under ``jax.profiler``: the host
    plane has ``grace/step``, ``grace/host/fetch`` and the set-up spans
    that fell inside the trace."""
    import optax
    from grace_tpu import grace_from_params
    from grace_tpu.models import resnet
    from grace_tpu.train import (init_stateful_train_state,
                                 make_stateful_train_step)

    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    images = jnp.ones((2, 32, 32, 3))
    labels = jnp.zeros((2,), jnp.int32)

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0       # the spans are the profiler's own
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        # ResNet-50 with one bottleneck a stage: ``apply`` reads the depth
        # from the blocks the parameters have
        params, mstate = resnet.init(jax.random.key(0), num_classes=4)
        later = re.compile(r"s\d+b[1-9]\d*")
        params = {k: v for k, v in params.items() if not later.fullmatch(k)}
        mstate = {k: v for k, v in mstate.items() if not later.fullmatch(k)}
        grc = grace_from_params({"compressor": "topk", "compress_ratio": 0.1,
                                 "memory": "residual",
                                 "communicator": "allgather"})
        tx = optax.chain(grc.transform(seed=0), optax.sgd(0.1))

        def loss_fn(params, mstate, batch):
            x, y = batch
            logits, new = resnet.apply(params, mstate, x, train=True)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean(), new

        step = make_stateful_train_step(loss_fn, tx, mesh, donate=False)
        state = init_stateful_train_state(params, mstate, tx, mesh)
        timer = StepTimer(warmup=1)
        for _ in range(3):
            with timer.step():
                state, loss = step(state, (images, labels))
                timer.sync_on(loss)
    finally:
        jax.profiler.stop_trace()

    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    names = {event.name for plane in data.planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for event in line.events}
    assert {"grace/step", "grace/host/fetch"} <= names
    assert {"grace/host/grace_from_params", "grace/host/transform",
            "grace/host/make_stateful_train_step",
            "grace/host/init_stateful_train_state",
            "grace/host/init_opt_state", "grace/host/wrap_step"} <= names
    assert "grace/host/import" not in names        # it ended before the trace
    assert len(timer) == 3
