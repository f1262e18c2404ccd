"""Performance attribution (grace_tpu.profiling) — ISSUE 6.

Covers the read side of the observability stack:

* trace analyzer exactness on the checked-in canned trace
  (tests/data/perf_trace.json.gz — hand-built spans with known durations,
  so attribution is asserted to the microsecond);
* overlap-fraction math on disjoint / fully-hidden / partially-hidden
  collective-vs-compute span pairs;
* the xplane protobuf path (round-trip through the module's own schema
  table);
* StepTimer fixes: warn-once on never-synced dispatch timing, timing row
  retained on BaseException;
* ProfileRecorder: a seeded weak-type closure leak detected as a runtime
  retrace, percentile/sync-missing records, GraceState footprint checked
  against live arrays (per-device and world-sharded layouts);
* the compile ledger (grace_tpu.telemetry.compiles) the recorder reads:
  JAX's own trace/lower/compile spans and cache events, by function;
* tools/perf_report.py CLI: clean exit on the fixture, exit 1 on a seeded
  baseline regression, PROF_LAST.json evidence;
* the Chrome-trace export (profiling.trace_export): round trip, determinism,
  host merge.

Everything runs on CPU with no devices (the mesh fixture is the simulated
8-device CPU mesh).
"""

from __future__ import annotations

import gzip
import json
import os
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grace_tpu.profiling import (ProfileRecorder, Span, analyze_spans,
                                 analyze_trace, check_state_footprint,
                                 expected_state_footprint,
                                 grace_state_footprint, interval_union_us,
                                 overlap_us, parse_xplane)
from grace_tpu.profiling.trace_analysis import _XPLANE_SCHEMA, UNATTRIBUTED
from grace_tpu.utils.profiling import StepTimer

pytestmark = pytest.mark.profiling

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FIXTURE = os.path.join(DATA, "perf_trace.json.gz")
TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def _tools_import(name):
    if TOOLS not in sys.path:
        sys.path.insert(0, TOOLS)
    import importlib
    return importlib.import_module(name)


# ---------------------------------------------------------------------------
# canned-trace attribution (exact numbers: see the fixture's span layout —
# per device, per step: fwd/bwd 400µs, compress 150µs (50µs nested child),
# decompress 100µs, optimizer 100µs on the compute lane; a 200µs all-gather
# on the async lane overlapping compress by 50µs; a 900µs step marker.
# 2 devices × 4 steps.)
# ---------------------------------------------------------------------------

def test_fixture_exact_stage_attribution():
    a = analyze_trace(FIXTURE)
    assert a.devices == ["/device:TPU:0", "/device:TPU:1"]
    assert a.device_lanes_detected
    stages_ms = {k: round(v * 1e-3, 6) for k, v in a.stage_us.items()}
    assert stages_ms == {"grace/forward_backward": 3.2,
                         "grace/exchange": 1.6,
                         "grace/compress": 1.2,
                         "grace/decompress": 0.8,
                         "grace/optimizer": 0.8}
    # the acceptance invariant: per-stage device time sums to total exactly
    assert abs(sum(a.stage_us.values()) - a.total_us) < 1e-9
    assert round(a.total_us * 1e-3, 6) == 7.6


def test_fixture_overlap_and_split():
    a = analyze_trace(FIXTURE)
    assert round(a.collective_us * 1e-3, 6) == 1.6
    assert round(a.compute_us * 1e-3, 6) == 6.0
    # 50µs of each 200µs all-gather hides under the compress tail
    assert a.overlap_fraction == pytest.approx(0.25, abs=1e-9)


def test_fixture_step_percentiles():
    a = analyze_trace(FIXTURE)
    sp = a.step_percentiles_ms()
    assert sp["n"] == 8                       # 2 devices × 4 steps
    assert sp["p50_ms"] == pytest.approx(0.9)
    assert sp["max_ms"] == pytest.approx(0.9)


def test_analysis_as_dict_render_consistent():
    a = analyze_trace(FIXTURE)
    d = a.as_dict()
    assert d["overlap_fraction"] == pytest.approx(0.25)
    assert sum(d["stages_ms"].values()) == pytest.approx(
        d["total_device_ms"])
    text = a.render()
    assert "grace/forward_backward" in text and "overlap" in text


# ---------------------------------------------------------------------------
# overlap-fraction math on constructed span pairs
# ---------------------------------------------------------------------------

def _dev_spans(comp, coll):
    """Compute spans on lane 'a', collective spans on lane 'b', one TPU."""
    spans = [Span(name="fusion.1", ts=s, dur=e - s,
                  device="/device:TPU:0", lane="a") for s, e in comp]
    spans += [Span(name="all-reduce.1", ts=s, dur=e - s,
                   device="/device:TPU:0", lane="b") for s, e in coll]
    return spans


def test_overlap_disjoint_is_zero():
    a = analyze_spans(_dev_spans([(0, 100)], [(100, 200)]))
    assert a.overlap_fraction == 0.0


def test_overlap_fully_hidden_is_one():
    a = analyze_spans(_dev_spans([(0, 200)], [(50, 150)]))
    assert a.overlap_fraction == 1.0


def test_overlap_partial_is_exact():
    a = analyze_spans(_dev_spans([(0, 100)], [(50, 150)]))
    assert a.overlap_fraction == pytest.approx(0.5)


def test_overlap_none_without_collectives():
    a = analyze_spans(_dev_spans([(0, 100)], []))
    assert a.overlap_fraction is None
    assert "n/a" in a.render()


def test_overlap_not_double_counted_across_fragments():
    # two collective fragments, one long compute region: intersection is
    # measured on interval unions, not per-span products
    a = analyze_spans(_dev_spans([(0, 300)], [(0, 100), (50, 150)]))
    assert a.collective_us == 150.0           # union, not 200
    assert a.overlap_fraction == 1.0


def test_interval_primitives():
    assert interval_union_us([(0, 10), (5, 20), (30, 40)]) == \
        [(0, 20), (30, 40)]
    assert overlap_us([(0, 20), (30, 40)], [(10, 35)]) == 15.0


def test_self_time_nesting_no_double_count():
    spans = [
        Span("grace/compress/outer.1", ts=0, dur=100,
             device="/device:TPU:0", lane="a"),
        Span("grace/decompress/inner.2", ts=10, dur=30,
             device="/device:TPU:0", lane="a"),
    ]
    a = analyze_spans(spans)
    assert a.stage_us["grace/compress"] == pytest.approx(70.0)
    assert a.stage_us["grace/decompress"] == pytest.approx(30.0)
    assert a.total_us == pytest.approx(100.0)


def test_unattributed_bucket_keeps_sum_exact():
    spans = _dev_spans([(0, 100)], []) + [
        Span("grace/compress/x.1", ts=200, dur=50,
             device="/device:TPU:0", lane="a")]
    a = analyze_spans(spans)
    assert a.stage_us[UNATTRIBUTED] == pytest.approx(100.0)
    assert abs(sum(a.stage_us.values()) - a.total_us) < 1e-9


# ---------------------------------------------------------------------------
# xplane path: round-trip through the module's own schema table
# ---------------------------------------------------------------------------

def _vint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _f_varint(field: int, val: int) -> bytes:
    return _vint(field << 3) + _vint(val)


def _f_len(field: int, payload: bytes) -> bytes:
    return _vint((field << 3) | 2) + _vint(len(payload)) + payload


def _xspace_bytes() -> bytes:
    S = _XPLANE_SCHEMA

    def ev_meta(mid, name):
        md = _f_varint(S["XEventMetadata"]["id"], mid) + \
            _f_len(S["XEventMetadata"]["name"], name.encode())
        return _f_varint(S["map_entry"]["key"], mid) + \
            _f_len(S["map_entry"]["value"], md)

    def event(mid, off_ps, dur_ps):
        return (_f_varint(S["XEvent"]["metadata_id"], mid)
                + _f_varint(S["XEvent"]["offset_ps"], off_ps)
                + _f_varint(S["XEvent"]["duration_ps"], dur_ps))

    def line(name, ts_ns, events):
        buf = _f_len(S["XLine"]["name"], name.encode()) + \
            _f_varint(S["XLine"]["timestamp_ns"], ts_ns)
        for e in events:
            buf += _f_len(S["XLine"]["events"], e)
        return buf

    ops = line("XLA Ops", 5000, [
        event(1, 0, 100_000_000),             # grace/compress, 100µs
        event(2, 100_000_000, 50_000_000),    # all-reduce, 50µs
    ])
    steps = line("Steps", 5000, [event(3, 0, 150_000_000)])
    plane = (_f_len(S["XPlane"]["name"], b"/device:TPU:0")
             + _f_len(S["XPlane"]["lines"], ops)
             + _f_len(S["XPlane"]["lines"], steps)
             + _f_len(S["XPlane"]["event_metadata"],
                      ev_meta(1, "grace/compress/pack.1"))
             + _f_len(S["XPlane"]["event_metadata"],
                      ev_meta(2, "all-reduce.2"))
             + _f_len(S["XPlane"]["event_metadata"], ev_meta(3, "step 0")))
    return _f_len(S["XSpace"]["planes"], plane)


def test_xplane_roundtrip(tmp_path):
    data = _xspace_bytes()
    spans = parse_xplane(data)
    assert {s.name for s in spans} == {"grace/compress/pack.1",
                                       "all-reduce.2", "step 0"}
    comp = next(s for s in spans if "compress" in s.name)
    assert comp.ts == pytest.approx(5.0)      # 5000 ns base → µs
    assert comp.dur == pytest.approx(100.0)
    a = analyze_spans(spans)
    assert a.stage_us["grace/compress"] == pytest.approx(100.0)
    assert a.stage_us[UNATTRIBUTED] == pytest.approx(50.0)
    assert a.collective_us == pytest.approx(50.0)
    assert a.step_times_us == [pytest.approx(150.0)]
    # and the file-extension dispatch picks the proto reader
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(data)
    a2 = analyze_trace(str(path))
    assert a2.total_us == pytest.approx(a.total_us)


# ---------------------------------------------------------------------------
# HLO-metadata scope enrichment (the XLA:CPU capture layout: execution
# events carry bare instruction names; scopes live in the embedded HLO
# proto's per-instruction metadata.op_name)
# ---------------------------------------------------------------------------

def test_hlo_scope_map_harvests_nearest_named_ancestor():
    from grace_tpu.profiling import hlo_scope_map

    # a message with field-1 name "all-gather.11" whose nested submessage
    # carries an op_name string containing the grace scope — the shape of
    # HloInstructionProto{name=1, metadata{op_name}}
    op_name = b"jit(step)/grace/optimizer/grace/exchange/all_gather"
    meta = _f_len(2, op_name)
    instr = _f_len(1, b"all-gather.11") + _f_len(7, meta)
    blob = _f_len(3, instr)                   # wrapped once more (module)
    m = hlo_scope_map(blob)
    # the harvested value may carry framing bytes of the enclosing
    # message — attribution is substring-based, so only the stage matters
    from grace_tpu.telemetry.scopes import match_stage
    assert list(m) == ["all-gather.11"]
    assert match_stage(m["all-gather.11"]) == "grace/exchange"


def test_enrich_spans_overrides_stage_free_scope():
    """Chrome CPU exports stuff the bare op name into args.name — an
    existing stage-free scope must not block enrichment (the verify-drive
    bug), while spans already attributable stay untouched."""
    from grace_tpu.profiling import enrich_spans

    spans = [
        Span(name="all-gather.11", scope="all-gather.11",   # args.name echo
             ts=0, dur=10, device="/host:CPU", lane="t"),
        Span(name="grace/compress/x.1", scope="", ts=10, dur=10,
             device="/host:CPU", lane="t"),
        Span(name="copy.9", scope="", ts=20, dur=10,
             device="/host:CPU", lane="t"),
    ]
    m = {"all-gather.11": "jit(s)/grace/exchange/all_gather",
         "grace/compress/x.1": "jit(s)/grace/decompress/WRONG"}
    out = enrich_spans(spans, m)
    assert out[0].stage() == "grace/exchange"
    assert out[1].stage() == "grace/compress"   # already attributable: kept
    assert out[2].stage() == ""                 # no mapping: untouched


def test_match_stage_prefers_innermost_scope():
    """jax name stacks nest (optimizer wraps the transform wraps the
    exchange): the innermost (rightmost) stage is the one doing the work."""
    from grace_tpu.telemetry.scopes import match_stage

    nested = "jit(s)/grace/optimizer/grace/exchange/grace/decompress/fuse.1"
    assert match_stage(nested) == "grace/decompress"
    assert match_stage("grace/exchange/psum_vote") == "grace/exchange"
    assert match_stage("grace/optimizer/grace/exchange") == "grace/exchange"
    assert match_stage("unrelated/fusion.3") == ""


# ---------------------------------------------------------------------------
# StepTimer satellite fixes
# ---------------------------------------------------------------------------

def test_steptimer_warns_once_on_missing_sync():
    t = StepTimer(warmup=0)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for _ in range(3):
            with t.step():
                pass
    msgs = [w for w in caught if "sync_on" in str(w.message)]
    assert len(msgs) == 1                     # once, not per step
    assert t.measured_async_dispatch
    assert len(t) == 3


def test_steptimer_synced_steps_do_not_warn():
    t = StepTimer(warmup=0)
    x = jnp.ones((4,))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with t.step():
            t.sync_on(x * 2)
    assert not [w for w in caught if "sync_on" in str(w.message)]
    assert not t.measured_async_dispatch


def test_steptimer_keeps_timing_row_on_exception():
    t = StepTimer(warmup=0)
    with pytest.raises(KeyboardInterrupt):
        with t.step():
            raise KeyboardInterrupt       # BaseException, not Exception
    assert len(t) == 1                    # the row is NOT swallowed
    assert t.failed_steps == 1
    # and the poisoned sync target was cleared for the next step
    with t.step():
        t.sync_on(jnp.ones(()))
    assert len(t) == 2 and t.failed_steps == 1


def test_steptimer_percentiles():
    t = StepTimer(warmup=1)
    t._times = [99.0, 1.0, 2.0, 3.0, 4.0]     # warmup row skipped
    assert t.p50_sec == pytest.approx(2.5)
    assert t.percentile_sec(100) == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# ProfileRecorder
# ---------------------------------------------------------------------------

class ListSink:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(dict(rec))

    def close(self):
        pass


def test_recorder_detects_weak_type_retrace():
    """The seeded signature_stability bug class, caught at RUNTIME: an
    int32 carry plus a Python float promotes to weak f32, so the second
    call retraces — the recorder must attribute it to that step."""

    @jax.jit
    def leaky(c):
        return c + 1.5

    sink = ListSink()
    rec = ProfileRecorder(sink, every=100, warmup=0, step_fn=leaky)
    c = jnp.zeros((), jnp.int32)
    for i in range(4):
        with rec.step():
            c = leaky(c)
            rec.sync_on(c)
        rec.update(i)
    assert rec.retraces == 1
    events = [(r["event"], r.get("step")) for r in sink.records]
    assert ("perf_compile", 0) in events
    assert ("perf_retrace", 1) in events      # attributed to the 2nd step


def test_recorder_stable_step_no_retrace():
    @jax.jit
    def stable(c):
        return c + jnp.float32(1)

    rec = ProfileRecorder(ListSink(), every=100, warmup=0, step_fn=stable)
    c = jnp.zeros((), jnp.float32)
    for i in range(4):
        with rec.step():
            c = stable(c)
            rec.sync_on(c)
        rec.update(i)
    assert rec.retraces == 0


@pytest.mark.filterwarnings(
    "ignore:StepTimer.step\\(\\) completed without sync_on:RuntimeWarning")
def test_recorder_flush_records_percentiles_and_sync_flag():
    sink = ListSink()
    rec = ProfileRecorder(sink, every=2, warmup=0)
    for i in range(4):
        with rec.step():
            pass                              # no sync_on: dispatch-only
        rec.update(i)
    times = [r for r in sink.records if r["event"] == "perf_step_times"]
    assert len(times) == 2                    # every=2 over 4 steps
    last = times[-1]
    assert last["n_steps"] == 4
    assert {"mean_ms", "p50_ms", "p90_ms", "p99_ms", "max_ms"} <= set(last)
    assert last["sync_missing"] is True       # the caveat travels with it


# ---------------------------------------------------------------------------
# the compile ledger (grace_tpu.telemetry.compiles) and the recorder on it
# ---------------------------------------------------------------------------

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


@pytest.fixture
def ledger():
    """The process's ledger, emptied: this test process has compiled many
    functions before, some of them under the names used here."""
    from grace_tpu.telemetry import compiles
    compiles.reset()
    return compiles


def everything(ledger):
    """All the ledger holds. A span of any length leaves a mark: it adds
    an interval, or widens one, or adds to a name's sums."""
    led = ledger.LEDGER
    return ({k: dict(v) for k, v in led._by_name.items()},
            list(led._intervals), led.counts())


def _tiny_step(mesh, scale=1.0):
    import optax
    from grace_tpu.train import init_train_state, make_train_step

    def loss_fn(p, b):
        return jnp.mean((b @ p["w"]) ** 2) * scale

    tx = optax.sgd(1e-2)
    step = make_train_step(loss_fn, tx, mesh, donate=False)
    return step, init_train_state({"w": jnp.ones((4, 2))}, tx, mesh)


def test_lazy_wrapper_carries_the_fun_name_the_ledger_hears(mesh, ledger):
    """``grace_tpu.train``'s step says under which name JAX reports its
    program, so that nobody guesses: the ledger, asked for that name,
    holds the one trace, lowering and compile of the step."""
    step, state = _tiny_step(mesh)
    assert step.fun_name.startswith("device_step")
    assert ledger.summary(step.fun_name)["lowerings"] == 0
    state, loss = step(state, jnp.ones((8, 4)))
    jax.block_until_ready(loss)
    got = ledger.summary(step.fun_name)
    assert got["lowerings"] == 1
    assert got["trace_s"] > 0 and got["lower_s"] > 0 and got["compile_s"] > 0
    assert len(step.jit_cache) == 1


def test_first_step_of_a_process_keeps_the_plain_name(mesh, monkeypatch):
    """The first step a process builds is ``device_step``, as it always
    was: its HLO module's name, and the compile cache's key, stand. Later
    ones are numbered."""
    import itertools
    from grace_tpu import train

    monkeypatch.setattr(train, "_steps_built", itertools.count(1))
    names = [_tiny_step(mesh)[0].fun_name for _ in range(3)]
    assert names == ["device_step", "device_step_2", "device_step_3"]


def test_another_steps_first_lowering_is_no_retrace_of_this_one(mesh, ledger):
    """A process that builds several steps (a tuner's candidates, chaos
    smoke's step_a / step_b) records each on its own."""
    step_a, state_a = _tiny_step(mesh)
    step_b, state_b = _tiny_step(mesh, scale=2.0)
    assert step_a.fun_name != step_b.fun_name
    sink_a, sink_b = ListSink(), ListSink()
    rec_a = ProfileRecorder(sink_a, every=100, warmup=0, step_fn=step_a)
    rec_b = ProfileRecorder(sink_b, every=100, warmup=0, step_fn=step_b)
    batch = jnp.ones((8, 4))
    state_a, _ = step_a(state_a, batch)
    rec_a.update(0)
    rec_b.update(0)
    state_b, loss = step_b(state_b, batch)          # b's first lowering
    jax.block_until_ready(loss)
    rec_a.update(1)
    rec_b.update(1)
    assert [(r["event"], r["step"], r["cache_size"])
            for r in sink_a.records] == [("perf_compile", 0, 1)]
    assert [(r["event"], r["step"], r["cache_size"])
            for r in sink_b.records] == [("perf_compile", 1, 1)]
    assert rec_a.retraces == rec_b.retraces == 0
    assert ledger.summary(step_a.fun_name)["lowerings"] == 1
    assert ledger.summary(step_b.fun_name)["lowerings"] == 1


def test_ledger_imports_only_jax_monitoring_and_the_standard_library():
    import ast
    import grace_tpu  # noqa: F401  (importing the package starts the ledger)
    from grace_tpu.telemetry import compiles

    assert sys.modules["grace_tpu.telemetry.compiles"] is compiles
    with open(compiles.__file__) as f:
        tree = ast.parse(f.read())
    imports = [n for n in ast.walk(tree)
               if isinstance(n, (ast.Import, ast.ImportFrom))]
    names = sorted(a.name for n in imports if isinstance(n, ast.Import)
                   for a in n.names)
    froms = sorted((n.module, a.name) for n in imports
                   if isinstance(n, ast.ImportFrom) for a in n.names)
    assert names == []
    assert froms == [("__future__", "annotations"), ("jax", "monitoring")]


def test_ledger_keys_trace_lower_compile_of_one_function_together(ledger):
    @jax.jit
    def ledger_probe_a(x):
        return jnp.tanh(x) * 2

    jax.block_until_ready(ledger_probe_a(jnp.ones((3,))))
    got = ledger.summary("ledger_probe_a")
    assert got["lowerings"] == 1 and got["cache_hits"] == 0
    assert got["trace_s"] > 0 and got["lower_s"] > 0 and got["compile_s"] > 0
    assert ledger.summary("jit(ledger_probe_a)") == got      # JAX's other name
    assert ledger.summary("never_compiled")["lowerings"] == 0


def test_ledger_sees_the_ahead_of_time_path(ledger):
    """``fn.lower(x).compile()`` never grows the jitted function's own
    cache, which is what the old probe polled."""

    @jax.jit
    def ledger_probe_aot(x):
        return x * 3 + 1

    compiled = ledger_probe_aot.lower(jnp.ones((5,))).compile()
    got = ledger.summary("ledger_probe_aot")
    assert got["lowerings"] == 1 and got["compile_s"] > 0
    for _ in range(50):
        out = compiled(jnp.ones((5,)))
    jax.block_until_ready(out)
    assert ledger.summary("ledger_probe_aot") == got


def test_fifty_steady_calls_of_a_compiled_step_add_no_event(ledger):
    @jax.jit
    def ledger_probe_steady(c):
        return c + jnp.float32(1)

    c = ledger_probe_steady(jnp.zeros((), jnp.float32))
    before, wall = everything(ledger), ledger.wall_s()
    assert before[0]["ledger_probe_steady"]["lowerings"] == 1
    for _ in range(50):
        c = ledger_probe_steady(c)
    jax.block_until_ready(c)
    assert everything(ledger) == before and ledger.wall_s() == wall


def test_a_cached_trace_adds_no_lowering():
    """JAX's tracing cache answers in a trace span of its own, of no or
    next to no length: it is no retrace."""
    from grace_tpu.telemetry.compiles import CompileLedger

    led = CompileLedger()
    led.on_span(TRACE, 10.0, 12.0, fun_name="f")
    led.on_span(LOWER, 12.0, 13.0, fun_name="jit(f)")
    led.on_span(COMPILE, 13.0, 17.0, fun_name="jit(f)")
    led.on_span(TRACE, 20.0, 20.0, fun_name="f")           # the cache
    got = led.summary("f")
    assert got == {"trace_s": 2.0, "lower_s": 1.0, "compile_s": 4.0,
                   "lowerings": 1, "cache_hits": 0}
    led.on_span("/jax/some/other/span", 0.0, 99.0, fun_name="f")
    assert led.summary("f") == got and led.wall_s() == 7.0


def test_five_thousand_nested_spans_do_not_evict_a_functions_sums():
    """A step's trace holds thousands of nested traces of ``jnp``
    functions, and the ledger holds what a set-up of a dozen spans would:
    one entry a name and one interval a stretch of work."""
    from grace_tpu.telemetry.compiles import CompileLedger

    led = CompileLedger()
    for i in range(5000):                    # inside the step's trace
        led.on_span(TRACE, 1.0 + i * 1e-4, 1.0 + i * 1e-4 + 5e-5,
                    fun_name=f"jnp_fn_{i % 40}")
    led.on_span(TRACE, 0.5, 2.0, fun_name="device_step")
    for i in range(5000):                    # other programs, before the lowering
        led.on_span(TRACE, 3.0 + i * 1e-4, 3.0 + i * 1e-4 + 5e-5,
                    fun_name=f"jnp_fn_{i % 40}")
    led.on_span(LOWER, 4.0, 4.75, fun_name="jit(device_step)")
    assert led.summary("device_step") == {
        "trace_s": 1.5, "lower_s": 0.75, "compile_s": 0.0,
        "lowerings": 1, "cache_hits": 0}
    # the step's trace swallowed its 5000 children; the later 5000 are
    # disjoint slivers of 50 µs and the lowering is apart from them
    assert led.wall_s() == pytest.approx(1.5 + 5000 * 5e-5 + 0.75)
    assert len(led._by_name) == 41 and len(led._intervals) == 5002
    assert led._intervals[0] == (0.5, 2.0)


def test_wall_of_nested_intervals_is_the_outer_one():
    from grace_tpu.telemetry.compiles import CompileLedger

    led = CompileLedger()
    led.on_span(TRACE, 1.0, 2.0, fun_name="inner_a")
    led.on_span(TRACE, 2.5, 3.0, fun_name="inner_b")
    led.on_span(TRACE, 0.0, 4.0, fun_name="outer")
    assert led.wall_s() == 4.0                           # not 5.5
    led.on_span(COMPILE, 6.0, 7.0, fun_name="jit(outer)")
    led.on_span(LOWER, 6.5, 8.0, fun_name="jit(other)")  # overlaps: one more second
    assert led.wall_s() == 6.0
    assert led._intervals == [(0.0, 4.0), (6.0, 8.0)]


def test_cache_events_are_counted_and_hits_belong_to_their_compile():
    from grace_tpu.telemetry.compiles import CompileLedger

    led = CompileLedger()
    assert led.counts() == {"cache_hits": 0, "cache_misses": 0}
    led.on_event("/jax/compilation_cache/compile_requests_use_cache")
    led.on_event("/jax/compilation_cache/cache_misses")
    led.on_span(COMPILE, 0.0, 9.0, fun_name="jit(cold)")
    led.on_event("/jax/compilation_cache/compile_requests_use_cache")
    led.on_event("/jax/compilation_cache/cache_hits")
    led.on_span(COMPILE, 10.0, 10.5, fun_name="jit(warm)")
    led.on_event("/jax/compilation_cache/tasks_using_cache")     # not kept
    assert led.counts() == {"cache_hits": 1, "cache_misses": 1}
    assert led.summary("cold")["cache_hits"] == 0
    assert led.summary("warm")["cache_hits"] == 1
    led.reset()
    assert led.counts()["cache_hits"] == 0 and led.wall_s() == 0.0
    assert led.summary("warm")["compile_s"] == 0.0 and led._by_name == {}


def test_persistent_cache_counts_a_miss_then_a_hit(tmp_path, ledger):
    """With a compilation cache directory the first compile of a fresh
    program is written (a miss) and the next compile of the same program,
    from another function object, is read back (a hit)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def fresh():
        def ledger_probe_cached(x):
            return jnp.cos(x) * 7 + jnp.float32(0.125)
        return jax.jit(ledger_probe_cached)

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    x = jnp.ones((11,))          # made before the cache is on
    try:
        for k, v in zip(keys, (str(tmp_path), 0, -1)):
            jax.config.update(k, v)
        cc.reset_cache()
        fresh().lower(x).compile()
        first = ledger.counts()
        assert first["cache_misses"] == 1 and first["cache_hits"] == 0
        assert ledger.summary("ledger_probe_cached")["cache_hits"] == 0
        fresh().lower(x).compile()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
    after = ledger.counts()
    assert after == {"cache_hits": 1, "cache_misses": 1}
    got = ledger.summary("ledger_probe_cached")
    assert got["lowerings"] == 2 and got["cache_hits"] == 1


def test_recorder_retrace_record_carries_durations(ledger):
    @jax.jit
    def ledger_probe_leaky(c):
        return c + 1.5

    sink = ListSink()
    rec = ProfileRecorder(sink, every=100, warmup=0,
                          step_fn=ledger_probe_leaky)
    c = jnp.zeros((), jnp.int32)
    for i in range(4):
        c = ledger_probe_leaky(c)
        rec.update(i)
    assert ledger.summary("ledger_probe_leaky")["lowerings"] == 2
    first, again = sink.records
    assert (first["event"], first["step"], first["cache_size"]) == (
        "perf_compile", 0, 1)
    assert (again["event"], again["step"], again["cache_size"],
            again["retraces"]) == ("perf_retrace", 1, 2, 1)
    for r in (first, again):
        assert r["trace_s"] > 0 and r["lower_s"] > 0 and r["compile_s"] > 0
        assert r["cache_hit"] is False
    total = ledger.summary("ledger_probe_leaky")
    assert first["lower_s"] + again["lower_s"] == pytest.approx(total["lower_s"])


def test_recorder_sees_an_ahead_of_time_compile(ledger):
    """The path the benchmark, ``chip_smoke.py`` and ``bench.py`` take."""

    @jax.jit
    def ledger_probe_aot_step(c):
        return c * jnp.float32(2)

    compiled = ledger_probe_aot_step.lower(jnp.ones(())).compile()
    sink = ListSink()
    rec = ProfileRecorder(sink, every=100, warmup=0,
                          step_fn=ledger_probe_aot_step)
    c = jnp.ones(())
    for i in range(3):
        c = compiled(c)
        rec.update(i)
    assert [(r["event"], r["step"], r["cache_size"]) for r in sink.records] == [
        ("perf_compile", 0, 1)]
    assert sink.records[0]["compile_s"] > 0 and rec.retraces == 0
    # a callable with no name to ask for gives no compile records
    assert ProfileRecorder(sink, step_fn=object()).update(0) == []


# ---------------------------------------------------------------------------
# GraceState footprint accounting
# ---------------------------------------------------------------------------

def _grace(telemetry=16):
    from grace_tpu import grace_from_params
    return grace_from_params({"compressor": "topk", "compress_ratio": 0.25,
                              "memory": "residual",
                              "communicator": "allgather",
                              "telemetry": telemetry})


def test_footprint_matches_live_arrays():
    g = _grace()
    params = {"w": jnp.zeros((64,)), "b": jnp.zeros((8,))}
    state = g.transform(seed=0).init(params)
    out = check_state_footprint(state, g, params, world=1)
    assert out["matches"]
    # residual memory is one dense copy of the gradients
    assert out["live"]["mem_bytes"] == (64 + 8) * 4
    assert out["live"]["telem_bytes"] > 0


def test_footprint_mismatch_flags_config_drift():
    g = _grace(telemetry=16)
    params = {"w": jnp.zeros((64,)), "b": jnp.zeros((8,))}
    state = g.transform(seed=0).init(params)
    other = _grace(telemetry=False)           # model built w/o telemetry
    out = check_state_footprint(state, other, params, world=1)
    assert not out["matches"]
    assert out["model"]["telem_bytes"] == 0 < out["live"]["telem_bytes"]


def test_footprint_world_scaling_on_sharded_state(mesh):
    import optax
    from grace_tpu.train import init_train_state

    g = _grace(telemetry=8)
    tx = optax.chain(g.transform(seed=0), optax.sgd(0.1))
    params = {"w": jnp.zeros((32, 16)), "b": jnp.zeros((16,))}
    state = init_train_state(params, tx, mesh)
    out = check_state_footprint(state.opt_state, g, params, world=8)
    assert out["matches"]
    assert out["live"]["mem_bytes"] == 8 * (32 * 16 + 16) * 4


def test_footprint_model_is_abstract():
    """expected_state_footprint must not allocate (it is eval_shape-only,
    so it stays honest on a device-free box and never OOMs pricing a big
    codec)."""
    g = _grace()
    params = {"w": jax.ShapeDtypeStruct((1 << 20,), jnp.float32)}
    fp = expected_state_footprint(g, params, world=256)
    assert fp["mem_bytes"] == 256 * (1 << 20) * 4


def test_recorder_emits_footprint_record():
    g = _grace()
    params = {"w": jnp.zeros((16,))}
    state = g.transform(seed=0).init(params)
    sink = ListSink()
    rec = ProfileRecorder(sink)
    out = rec.record_state_footprint(state, g, params, world=1, step=7)
    assert out["footprint_matches"]
    assert sink.records[-1]["event"] == "perf_state_footprint"
    assert sink.records[-1]["model_mem_bytes"] == out["mem_bytes"]


def test_grace_state_footprint_counts_components():
    g = _grace()
    state = g.transform(seed=0).init({"w": jnp.zeros((10,))})
    fp = grace_state_footprint(state)
    assert fp["grace_states"] == 1
    assert fp["total_bytes"] == (fp["mem_bytes"] + fp["comp_bytes"]
                                 + fp["telem_bytes"]
                                 + fp["bookkeeping_bytes"])


# ---------------------------------------------------------------------------
# perf_report CLI (offline, no devices) + evidence flow
# ---------------------------------------------------------------------------

def test_perf_report_clean_run_and_evidence(tmp_path, capsys):
    perf_report = _tools_import("perf_report")
    out = tmp_path / "PROF_LAST.json"
    rc = perf_report.main(["--trace", FIXTURE, "--out", str(out)])
    assert rc == 0
    text = capsys.readouterr().out
    assert "grace/forward_backward" in text and "overlap" in text
    doc = json.loads(out.read_text())
    assert doc["tool"] == "perf_report"
    assert sum(doc["stages_ms"].values()) == pytest.approx(
        doc["total_device_ms"])
    assert doc["overlap_fraction"] == pytest.approx(0.25)
    assert "canned CPU fixture" in doc["note"]


def test_perf_report_baseline_gate_exit_codes(tmp_path):
    perf_report = _tools_import("perf_report")
    base = tmp_path / "base.json"
    rc = perf_report.main(["--trace", FIXTURE, "--out", "",
                           "--write-baseline", str(base)])
    assert rc == 0
    # gating against its own baseline is clean…
    rc = perf_report.main(["--trace", FIXTURE, "--out", "",
                           "--baseline", str(base)])
    assert rc == 0
    # …and a seeded regression (baseline claims 2× faster) exits 1
    doc = json.loads(base.read_text())
    doc["step_times"]["p50_ms"] /= 2
    doc["total_device_ms"] /= 2
    regressed = tmp_path / "regressed.json"
    regressed.write_text(json.dumps(doc))
    rc = perf_report.main(["--trace", FIXTURE, "--out", "",
                           "--baseline", str(regressed)])
    assert rc == 1


def test_perf_report_overlap_regression_fires(tmp_path):
    perf_report = _tools_import("perf_report")
    current = {"step_times": None, "total_device_ms": 1.0,
               "stages_ms": {}, "overlap_fraction": 0.10}
    baseline = {"step_times": None, "total_device_ms": 1.0,
                "stages_ms": {}, "overlap_fraction": 0.50}
    findings = perf_report.compare_to_baseline(current, baseline, 0.10)
    assert any("overlap" in f for f in findings)
    # improvements never regress
    assert perf_report.compare_to_baseline(baseline, current, 0.10) == []


def test_telemetry_report_renders_perf_records(tmp_path, capsys):
    telemetry_report = _tools_import("telemetry_report")
    path = tmp_path / "run.jsonl"
    rows = [
        {"provenance": {"data": "synthetic"}},
        {"step": 0, "grad_norm": 1.0, "wire_bytes": 10, "dense_bytes": 40},
        {"event": "perf_compile", "step": 0, "cache_size": 1},
        {"event": "perf_retrace", "step": 3, "cache_size": 2,
         "retraces": 1},
        {"event": "perf_step_times", "step": 9, "n_steps": 10,
         "mean_ms": 2.0, "p50_ms": 1.9, "p90_ms": 2.5, "p99_ms": 3.0,
         "max_ms": 3.1, "sync_missing": True},
        {"event": "perf_memory", "step": 9, "n_devices": 8,
         "bytes_in_use": 1000, "peak_bytes_in_use": 2000},
        {"event": "perf_state_footprint", "step": 9, "mem_bytes": 288,
         "comp_bytes": 0, "telem_bytes": 640, "footprint_matches": True},
        {"event": "guard_skip", "step": 4, "notfinite_count": 1},
    ]
    path.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    assert telemetry_report.main([str(path)]) == 0
    text = capsys.readouterr().out
    assert "== profiling" in text
    assert "p50 1.900" in text
    assert "retraces: 1" in text
    assert "async-dispatch" in text
    assert "peak 2,000 B" in text
    assert "matches" in text
    # guard events keep their own section, without the perf records
    assert "guard_skip" in text.split("== guard events")[1]
    assert "perf_step_times" not in text.split("== guard events")[1]


# ---------------------------------------------------------------------------
# Chrome-trace export round-trip


def _spans():
    from grace_tpu.profiling.trace_analysis import Span
    return [
        Span(name="allreduce-hop0", ts=0.0, dur=10.0,
             device="/device:TPU:0", lane="XLA Ops", scope="ici"),
        Span(name="allreduce-hop1", ts=10.0, dur=12.0,
             device="/device:TPU:0", lane="XLA Ops", scope="dcn"),
        Span(name="step", ts=0.0, dur=25.0,
             device="/device:TPU:0", lane="Steps", scope=""),
        Span(name="allreduce-hop0", ts=1.0, dur=9.0,
             device="/device:TPU:1", lane="XLA Ops", scope="ici"),
    ]


@pytest.mark.parametrize("suffix", [".json", ".json.gz"])
def test_chrome_trace_round_trip(tmp_path, suffix):
    from grace_tpu.profiling.trace_analysis import load_trace_events
    from grace_tpu.profiling.trace_export import write_chrome_trace
    spans = _spans()
    path = str(tmp_path / f"trace{suffix}")
    write_chrome_trace(spans, path)
    assert set(load_trace_events(path)) == set(spans)


def test_chrome_trace_doc_is_deterministic():
    from grace_tpu.profiling.trace_export import chrome_trace_doc
    spans = _spans()
    assert (json.dumps(chrome_trace_doc(spans))
            == json.dumps(chrome_trace_doc(list(reversed(spans)))))


def test_merge_host_traces_prefixes_and_aligns():
    from grace_tpu.profiling.trace_analysis import parse_chrome_trace
    from grace_tpu.profiling.trace_export import (chrome_trace_doc,
                                                  merge_host_traces)
    spans = _spans()
    # host1's clock starts 1e6 µs later; align rebases both to t=0.
    shifted = [type(s)(name=s.name, ts=s.ts + 1e6, dur=s.dur,
                       device=s.device, lane=s.lane, scope=s.scope)
               for s in spans]
    merged = merge_host_traces({"host0": spans, "host1": shifted})
    assert len(merged) == 2 * len(spans)
    devices = {s.device for s in merged}
    assert "host0//device:TPU:0" in devices
    assert "host1//device:TPU:1" in devices
    by_host = {h: [s for s in merged if s.device.startswith(h + "/")]
               for h in ("host0", "host1")}
    assert min(s.ts for s in by_host["host0"]) == 0.0
    assert min(s.ts for s in by_host["host1"]) == 0.0
    # the merged timeline still round-trips through the parser
    assert set(parse_chrome_trace(chrome_trace_doc(merged))) == set(merged)
