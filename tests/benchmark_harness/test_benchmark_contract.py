"""``BENCHMARK.json`` against the form the builder's contract gives it, and
the benchmark's files against what the JSON says of them."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_harness_helpers import REPO, harness  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|projection)_size"
                    r"|_dim$|_rank$|head_size|expansion|experts_per_tok")


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(SPEC["command"]) <= 32 and all(map(line, SPEC["command"]))
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    assert any(w.startswith(tuple(SPEC["paths"])) for w in SPEC["command"])


def test_a_full_check_fits_its_time_with_all_24_cells():
    runs = 2 + 14 * 24
    assert (runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200) <= 43200


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_configuration_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert line(config["source"]) and line(config["why"])
    assert config["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
    assert PATH.match(config["file"])
    assert len(config["reduced"]) <= 16
    assert not any(WIDTHS.search(k) for k in config["reduced"])
    with open(os.path.join(REPO, config["file"])) as f:
        body = json.load(f)
    assert body["name"] == config["name"] and body["source"] == config["source"]
    assert body["reduced"] == config["reduced"]
    assert any(w["config"] == config["name"] for w in SPEC["workloads"])
    # its builder and plain reference are files beside it
    builder = harness.Catalog().builder(body)
    for fn in ("init", "make_batch", "program_loss", "reference_loss"):
        assert callable(getattr(builder, fn))


def test_no_two_configurations_share_a_file_or_a_name():
    files = [c["file"] for c in SPEC["configs"]]
    names = [c["name"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files) and len(set(names)) == len(names)
    assert 1 <= len(names) <= 24


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_workload_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}
    assert cell["chips"] in (1, 4) and line(cell["why"])
    merged = harness.Catalog().cell(cell["name"])
    assert merged["chips"] == cell["chips"]
    assert {"grace", "codec", "optimizer", "span_steps", "trace_steps",
            "collectives", "limits"} <= set(merged)
    limits = dict(merged["limits"])
    steps = limits.pop("loss_gap")
    assert set(limits) == {"grad1_norm_gap", "grad1_norm_gap_median",
                           "delta_norm_gap", "delta_norm_gap_median"}
    assert len(steps) == harness.CHECK_STEPS
    # a step that returns its state unchanged puts every leaf's gap at
    # exactly 1: the median leaf's limit has to lie under that
    assert all(0 < v < 1 for v in [*steps, limits["grad1_norm_gap_median"],
                                   limits["delta_norm_gap_median"]])
    assert all(0 < limits[k] <= 2 for k in ("grad1_norm_gap",
                                            "delta_norm_gap"))
    # a cell on several chips names the collectives its exchange leaves
    assert bool(merged["collectives"]) == (cell["chips"] > 1)


def test_cells_are_distinct_and_few_take_four_chips():
    cells = SPEC["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({c["name"] for c in cells}) == len(cells)
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    four = sum(c["chips"] == 4 for c in cells)
    assert four <= max(1, len(cells) // 4)


@pytest.mark.parametrize("metric", SPEC["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound",
                                           "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES and line(metric["layer"])
    assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}
    cells = {c["name"] for c in SPEC["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    assert callable(harness.Catalog().reader(metric["name"]))


def test_metric_names_are_unique_and_setup_is_there():
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in names
    assert 1 <= len(SPEC["end_to_end"]) <= 16
    assert 1 <= len(SPEC["per_layer"]) <= 128


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric(cell):
    cat = harness.Catalog()
    e2e = [m["name"] for m in cat.metrics_of("end_to_end", cell["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cat.metrics_of("per_layer", cell["name"])


def test_layers_are_the_ones_perf_md_lists():
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in SPEC["per_layer"]}:
        assert f"| {layer} |" in perf, layer


def test_files_under_paths_are_named_from_a_names_characters():
    for root in SPEC["paths"]:
        for base, dirs, files in os.walk(os.path.join(REPO, root)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(base, name), REPO)
                assert PATH.match(rel), rel
