"""The trace reducer: its interval arithmetic on hand-made operations, and
the whole reduction on a small trace recorded on the TPU v5e
(``data/tiny-resnet-topk-w1.xplane.pb.gz``: three steps of the test-size
ResNet with top-k, my chip run, PR 23; ``.scopes.json`` is the
``scopes_of`` map of that run's compiled step)."""

import gzip
import json
import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_harness_helpers import DATA  # noqa: E402

from benchmarks import trace_reduce as tr  # noqa: E402
from benchmarks.trace_reduce import Op, Trace  # noqa: E402


def test_union_and_total():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert tr.total([[0, 3], [5, 8]]) == 6


@pytest.mark.parametrize("a,b,want", [
    ([[0, 10]], [], [[0, 10]]),
    ([[0, 10]], [[2, 4], [6, 12]], [[0, 2], [4, 6]]),
    ([[0, 3], [5, 9]], [[1, 6]], [[0, 1], [6, 9]]),
    ([[0, 3]], [[0, 3]], []),
    ([[2, 4]], [[0, 1], [5, 6]], [[2, 4]]),
])
def test_subtract(a, b, want):
    assert tr.subtract(a, b) == want


def test_self_time_takes_out_nested_operations():
    ops = [Op("while.1", 0, 100), Op("fusion.1", 10, 30),
           Op("fusion.2", 50, 20), Op("copy.3", 100, 5)]
    selfs = {o.name: s for o, s in tr.self_times(ops)}
    assert selfs == {"while.1": 50, "fusion.1": 30, "fusion.2": 20,
                     "copy.3": 5}


def test_stage_is_the_rightmost_grace_scope():
    assert tr.stage_of("jit(step)/grace/bucket/0/grace/compress/mul") == \
        "grace/compress"
    assert tr.stage_of("jit(step)/reduce_sum") is None
    assert tr.stage_of(None) is None


def test_scopes_of_reads_op_names_from_compiled_text():
    text = '''
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%c, metadata={op_name="jit(s)/grace/compress/abs" stack_frame_id=1}
  ROOT %add.2 = f32[] add(%a, %b), metadata={op_name="jit(s)/grace/optimizer/add"}
  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
'''
    assert tr.scopes_of(text) == {
        "fusion.7": "jit(s)/grace/compress/abs",
        "add.2": "jit(s)/grace/optimizer/add"}


def two_device_trace():
    """Two devices, two steps. Device 0: compute 0-40, an all-gather 40-60
    of which 50-60 runs beside compute on... nothing (one line is serial),
    so all 20 are exposed; idle 60-70; compute 70-100. Device 1 the same,
    later by 5."""
    us = 1000           # the times below are microseconds

    def ops(t0):
        return [Op("fusion.1", (t0 + 0) * us, 40 * us),
                Op("all-gather-start.3", (t0 + 40) * us, 2 * us),
                Op("all-gather-done.3", (t0 + 42) * us, 18 * us),
                Op("fusion.2", (t0 + 70) * us, 30 * us)]
    host = [Op("bench/dispatch", 0, 10 * us), Op("bench/fetch", 10 * us, 95 * us)]
    return Trace({0: ops(0), 1: ops(5)}, host)


def test_reduce_on_hand_made_intervals():
    scopes = {"fusion.1": "jit(s)/grace/forward_backward/conv",
              "fusion.2": "jit(s)/grace/decompress/select",
              "all-gather-start.3": "jit(s)/grace/exchange/all_gather",
              "all-gather-done.3": "jit(s)/grace/exchange/all_gather"}
    r = tr.reduce(two_device_trace(), steps=2, scopes=scopes)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(90e-6)
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["step_device_s"] == pytest.approx(45e-6)
    assert r["collective_exposed_s_per_step"] == pytest.approx(10e-6)
    assert r["stage_s_per_step"] == pytest.approx({
        "grace/forward_backward": 20e-6, "grace/decompress": 15e-6,
        "grace/exchange": 10e-6})
    assert r["grace_s_per_step"] == pytest.approx(15e-6)
    # the gap 60-70 (65-75 on device 1) falls inside the fetch span
    assert r["idle_gaps"] == [["bench/fetch", pytest.approx(10e-6)]]
    assert r["device_ops"][0] == ["fusion.1@grace/forward_backward",
                                  pytest.approx(20e-6)]


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(RuntimeError):
        tr.reduce(Trace({}, []), 1, {})
    with pytest.raises(RuntimeError):
        tr.reduce(Trace({0: []}, []), 1, {})


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(os.path.join(DATA, "tiny-resnet-topk-w1.xplane.pb.gz")) as f, \
            open(path, "wb") as out:
        shutil.copyfileobj(f, out)
    with open(os.path.join(DATA, "tiny-resnet-topk-w1.scopes.json")) as f:
        scopes = json.load(f)
    return tr.load(str(path)), scopes


def test_recorded_trace_loads_device_operations_and_host_spans(recorded):
    trace, scopes = recorded
    assert list(trace.devices) == [0]
    ops = trace.devices[0]
    assert len(ops) == 3375 and len(ops) % 3 == 0      # 1,125 a step
    # a TPU trace names an operation by its whole instruction; the loader
    # keeps the instruction's name, which the compiled text knows
    assert all(" " not in o.name and not o.name.startswith("%") for o in ops)
    assert sum(o.name in scopes for o in ops) > 0.5 * len(ops)
    assert [h.name for h in trace.host].count("bench/dispatch") == 3
    assert [h.name for h in trace.host].count("bench/fetch") == 3


def test_recorded_trace_reduces_to_the_numbers_read_by_hand(recorded):
    trace, scopes = recorded
    r = tr.reduce(trace, 3, scopes)
    assert r["busy_s"] == pytest.approx(0.000419581, rel=1e-6)
    assert r["window_s"] == pytest.approx(0.003171645, rel=1e-6)
    assert r["step_device_s"] == pytest.approx(0.000139860333, rel=1e-6)
    assert r["collective_exposed_s_per_step"] == 0.0
    stages = r["stage_s_per_step"]
    # the line is serial, so the stages' self times add up to the busy time
    assert sum(stages.values()) * 3 == pytest.approx(r["busy_s"], rel=1e-6)
    assert stages["grace/compress"] == pytest.approx(3.6991333e-05, rel=1e-5)
    assert r["grace_s_per_step"] == pytest.approx(6.5178e-05, rel=1e-5)
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) <= 10
    # at this size the host cannot keep the chip busy: the longest gaps sit
    # under the loss fetch and the dispatch
    assert {g[0] for g in r["idle_gaps"]} == {"bench/fetch", "bench/dispatch"}
