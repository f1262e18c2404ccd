"""The timing loop's stamps and the arithmetic on them."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_harness_helpers import harness  # noqa: E402


class FakeDevice:
    """A clock that moves only when a result is waited for: a step takes
    ``step_s`` on the device, dispatch is free."""

    def __init__(self, step_s, stall_every=0, stall_s=0.0):
        self.now, self.step_s = 0.0, step_s
        self.stall_every, self.stall_s = stall_every, stall_s
        self.dispatched = self.waited = 0
        self.max_in_flight = 0

    def clock(self):
        return self.now

    def call(self):
        self.dispatched += 1
        self.max_in_flight = max(self.max_in_flight,
                                 self.dispatched - self.waited)
        device = self

        class Loss:
            def __float__(self):
                device.waited += 1
                device.now += device.step_s
                if device.stall_every and device.waited % device.stall_every == 0:
                    device.now += device.stall_s
                return 1.0

        return Loss()


def test_window_runs_ahead_by_one_and_sees_every_step():
    dev = FakeDevice(0.1)
    stamps, losses = harness.timed_window(dev.call, 1.0, dev.clock)
    assert dev.dispatched == dev.waited == len(losses) == len(stamps) - 1
    assert dev.max_in_flight == 2          # step i+1 queued while i is awaited
    assert stamps[0] == 0.0
    # dispatching stops once the window is up; what is in flight is drained
    assert stamps[-1] >= 1.0 and stamps[-2] < 1.0 + 0.1 + 1e-9
    assert all(b - a == pytest.approx(0.1) for a, b in zip(stamps, stamps[1:]))


def test_rate_is_all_samples_over_all_time():
    stamps = [0.0, 0.1, 0.2, 0.5, 0.6]      # one slow step of 0.3 s
    m = harness.window_metrics(stamps, samples_per_step=256, span_steps=1)
    assert m["steps"] == 4
    assert m["samples_per_s"] == pytest.approx(256 * 4 / 0.6)
    assert m["step_ms_median"] == pytest.approx(100.0)


@pytest.mark.parametrize("values,q,want", [
    ([1.0], 95, 1.0),
    ([1.0, 2.0], 50, 1.5),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 95, 4.8),
    (list(range(101)), 95, 95.0),
    ([5.0, 1.0, 3.0], 0, 1.0),
    ([5.0, 1.0, 3.0], 100, 5.0),
])
def test_percentile_interpolates_between_closest_ranks(values, q, want):
    import numpy as np
    assert harness.percentile(values, q) == pytest.approx(want)
    assert harness.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


def test_step_time_is_read_over_spans_of_steps():
    # 100 ms steps, every 20th stalls by 60 ms: a span of three steps
    # carries a third of the stall, and the 95th percentile still sees it
    dev = FakeDevice(0.1, stall_every=20, stall_s=0.06)
    stamps, _ = harness.timed_window(dev.call, 30.0, dev.clock)
    m = harness.window_metrics(stamps, 1, span_steps=3)
    assert m["step_ms_median"] == pytest.approx(100.0)
    assert m["step_ms.p95"] == pytest.approx(120.0)
    assert m["step_ms_single_p95"] > 100.0
    spans = len(stamps) - 1 - 3 + 1
    assert spans > 250                       # every run of three steps counts


def test_too_short_a_window_is_an_error():
    with pytest.raises(harness.BenchError):
        harness.window_metrics([0.0, 0.1], 1, span_steps=3)
