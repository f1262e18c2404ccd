"""``correct``: passes for the sound program, fails for the control (the
step in a lower precision than the configuration states) and for a timed
path broken underneath. CPU, test-size cells; the limits of ``data/`` were
read the same way as the real cells' (PERF.md): above the sound runs,
below the control."""

import os
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_harness_helpers import harness, rehearse, tiny_catalog  # noqa: E402

CELLS = ["tiny-resnet-topk-w1", "tiny-resnet-dense-w1",
         "tiny-bert-powersgd-w1"]


def numbers(name, seed):
    cat = tiny_catalog()
    cell = cat.cell(name)
    config = cat.config(cell["config"])
    builder = cat.builder(config)
    mesh = Mesh(np.asarray(jax.devices()[:cell["chips"]]), ("data",))
    program = harness.Program(cell, config, builder, mesh, seed)
    got = harness.first_steps(program)
    keys, world = program.keys, program.world
    del program
    want = harness.reference_numbers(keys, cell, config, builder, world)
    low = harness.reference_numbers(keys, cell, config, builder, world,
                                    lower_precision=True)
    return cell, got, want, low


@pytest.mark.parametrize("name", CELLS)
def test_sound_program_passes_and_lower_precision_control_fails(name):
    cell, got, want, low = numbers(name, seed=2147483659)
    sound = harness.compare(got, want, cell["limits"])
    assert all(r["ok"] for r in sound), sound
    control = harness.compare(low, want, cell["limits"])
    assert not all(r["ok"] for r in control), control
    # the margin the limits were set with: threefold on both sides of one
    worst = max((r["value"] / r["limit"] for r in control))
    assert worst > 3, control
    assert all(r["value"] < r["limit"] / 3 for r in sound), sound


def test_leaf_gap_is_measured_against_the_median_leaf_at_least():
    # a leaf whose gradient is all but zero may be off by its whole norm
    got, want = [1.0, 2.0e-9, 3.0], [1.0, 1.0e-9, 3.3]
    gaps = harness.leaf_gaps(got, want)
    assert gaps == pytest.approx([0.0, 1.0e-9, 0.3 / 3.3])


def test_compare_lists_every_number_beside_its_limit():
    nums = {"losses": [1.0, 0.9, 0.8], "grad1_norms": [1.0, 2.0],
            "delta_norms": [0.1, 0.2]}
    off = {"losses": [1.0, 0.9, float("nan")], "grad1_norms": [1.0, 2.5],
           "delta_norms": [0.1, 0.2]}
    limits = {"loss_gap": [0.01, 0.01, 0.01], "grad1_norm_gap": 0.2,
              "grad1_norm_gap_median": 0.1, "delta_norm_gap": 0.1,
              "delta_norm_gap_median": 0.1}
    rows = harness.compare(off, nums, limits)
    assert [r["name"] for r in rows] == [
        "loss_gap.step1", "loss_gap.step2", "loss_gap.step3",
        "grad1_norm_gap", "grad1_norm_gap_median",
        "delta_norm_gap", "delta_norm_gap_median"]
    # the worst leaf is off by a quarter, the median leaf by an eighth
    assert [r["value"] for r in rows[3:5]] == pytest.approx([0.25, 0.125])
    assert [r["ok"] for r in rows] == [True, True, False, False, False,
                                       True, True]
    assert all(r["ok"] for r in harness.compare(nums, nums, limits))


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch"])
def test_a_run_with_the_timed_path_broken_is_not_correct(
        fault, monkeypatch, capsys):
    """Drives a whole run (no look for a chip) with the step broken
    underneath."""
    sound_call = harness.Program.call

    def state_unchanged(self):
        keep = jax.tree_util.tree_map(lambda x: x.copy(), self.state)
        loss = sound_call(self)
        self.state = keep
        return loss

    def half_the_batch(self):
        if not getattr(self, "_halved", False):
            self._halved = True
            self.batch = jax.tree_util.tree_map(
                lambda x: x.at[x.shape[0] // 2:].set(x[:x.shape[0] // 2]),
                self.batch)
        return sound_call(self)

    monkeypatch.setattr(harness.Program, "call", locals()[fault])
    rc, lines = rehearse(capsys, tiny_catalog(), "--workload",
                         "tiny-bert-powersgd-w1", "--seed", "11",
                         "--seconds", "2", "--trace", "0")
    assert rc == 0
    assert lines[-1]["correct"] is False
    compared = next(l for l in lines if l.get("phase") == "correct")
    assert any(not r["ok"] for r in compared["compared"])
