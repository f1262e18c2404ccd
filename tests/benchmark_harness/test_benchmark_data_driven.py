"""The harness is driven by data: a cell, a configuration and a per-layer
metric are each added as new files plus one ``BENCHMARK.json`` entry, with
no file that is there edited. Shown with throw-away files in a temporary
directory."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_harness_helpers import DATA, harness, rehearse, tiny_catalog  # noqa: E402

NEW_METRIC = '''
"""Throw-away per-layer metric: device-busy microseconds per traced step."""


def read(ctx):
    return ctx["reduced"]["step_device_s"] * 1e6
'''

NOTHING_TO_READ = '''
def read(ctx):
    return None
'''


@pytest.fixture
def extended(tmp_path):
    """A root with one new configuration, one new cell and two new
    metrics, and a ``BENCHMARK.json`` that names them."""
    for kind in ("configs", "workloads", "layer_metrics"):
        (tmp_path / kind).mkdir()
    with open(os.path.join(DATA, "configs", "tiny-bert.json")) as f:
        config = json.load(f)
    config.update(name="tinier-bert", num_hidden_layers=1, per_chip_batch=2)
    (tmp_path / "configs" / "tinier-bert.json").write_text(json.dumps(config))
    with open(os.path.join(DATA, "workloads",
                           "tiny-bert-powersgd-w1.json")) as f:
        cell = json.load(f)
    cell.update(config="tinier-bert", trace_steps=2)
    (tmp_path / "workloads" / "tinier-bert-powersgd-w1.json").write_text(
        json.dumps(cell))
    (tmp_path / "layer_metrics" / "step_device_us.py").write_text(NEW_METRIC)
    (tmp_path / "layer_metrics" / "never_there.py").write_text(NOTHING_TO_READ)

    with open(os.path.join(DATA, "BENCHMARK.tiny.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tinier-bert", "source": "test",
                            "file": "configs/tinier-bert.json",
                            "reduced": [], "why": "added as a file"})
    spec["workloads"].append({"name": "tinier-bert-powersgd-w1",
                              "config": "tinier-bert",
                              "traffic": "powersgd-w1", "chips": 1,
                              "why": "added as a file"})
    for name, unit in (("step_device_us", "us"), ("never_there", "ms")):
        spec["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "device_trace", "layer": "train step",
            "moves": "samples_per_s",
            "workloads": ["tinier-bert-powersgd-w1"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return tiny_catalog(str(tmp_path), benchmark_json=str(path))


def test_files_are_found_by_name_under_any_root(extended):
    cell = extended.cell("tinier-bert-powersgd-w1")
    assert cell["config"] == "tinier-bert" and cell["why"] == "added as a file"
    config = extended.config(cell["config"])
    assert config["num_hidden_layers"] == 1
    assert extended.builder(config).__name__.endswith("bert_base")
    assert extended.reader("step_device_us")(
        {"reduced": {"step_device_s": 2e-6}}) == pytest.approx(2.0)
    names = [m["name"] for m in extended.metrics_of(
        "per_layer", "tinier-bert-powersgd-w1")]
    assert "step_device_us" in names and "collective_exposed_ms" not in names
    # the cells that were there do not report the new metric
    assert "step_device_us" not in [m["name"] for m in extended.metrics_of(
        "per_layer", "tiny-bert-powersgd-w1")]


def test_a_cell_added_as_files_runs_and_reports_the_new_metric(
        extended, capsys):
    rc, lines = rehearse(capsys, extended, "--workload",
                         "tinier-bert-powersgd-w1", "--seed", "3",
                         "--seconds", "0.3", "--trace", "1")
    assert rc == 0
    last = lines[-1]
    assert last["correct"] is True and last["attempted"] == 2
    assert last["metrics"]["step_device_us"]["unit"] == "us"
    assert last["metrics"]["step_device_us"]["value"] == pytest.approx(
        last["metrics"]["step_device_ms"]["value"] * 1e3)
    # a reader that finds nothing to read is left out of the line
    assert "never_there" not in last["metrics"]


def test_what_is_missing_is_an_error_and_not_a_default(extended):
    with pytest.raises(harness.BenchError):
        extended.cell("no-such-cell")
    with pytest.raises(harness.BenchError):
        extended.config("no-such-config")
    with pytest.raises(harness.BenchError):
        extended.reader("no_such_metric")
    with pytest.raises(harness.BenchError):
        extended.peaks("TPU v9 imaginary")
    assert extended.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
