"""The ``lfm2-24b-a2b-ep8`` configuration and its cell, as the harness sees
them: a CPU rehearsal of ``benchmarks/run.py`` on a test-size share of the
model (new files under ``data/`` and a ``BENCHMARK.json`` written into a
temporary root; ``data/BENCHMARK.tiny.json`` is not edited), the new
per-layer readers on a program that has nothing for them, and the real
configuration's file against the catalog row it was cut from."""

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_harness_helpers import (DATA, REPO, harness, rehearse,  # noqa: E402
                                   tiny_catalog)

from benchmarks import calibrate  # noqa: E402

CELL = "tiny-lfm2-topk-w1"
NEW_METRICS = ("moe_ms", "short_conv_ms", "grace_ps_per_param",
               "moe_held_assignments", "moe_dropped_assignments")

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)


@pytest.fixture(scope="module")
def catalog(tmp_path_factory):
    """The test-size catalog with the tiny share of the model added as the
    real one was: a configuration, a cell, the five metrics."""
    root = tmp_path_factory.mktemp("lfm2")
    with open(os.path.join(DATA, "BENCHMARK.tiny.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-lfm2", "source": "test",
                            "file": "configs/tiny-lfm2.json", "reduced": [],
                            "why": "test size"})
    spec["workloads"].append({"name": CELL, "config": "tiny-lfm2",
                              "traffic": "topk-w1", "chips": 1,
                              "why": "test size"})
    for metric in SPEC["per_layer"]:
        if metric["name"] in NEW_METRICS:
            spec["per_layer"].append(dict(metric, workloads=[CELL]))
        elif metric["name"] in ("grace_ms", "wire_bytes"):
            next(m for m in spec["per_layer"] if m["name"] == metric["name"]
                 )["workloads"].append(CELL)
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return tiny_catalog(benchmark_json=str(path))


@pytest.fixture(scope="module")
def traced(catalog):
    """One traced rehearsal of the cell; its printed lines."""
    import contextlib
    import io
    from benchmarks import run
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", CELL, "--seed", "2147483777",
                       "--seconds", "0.5", "--trace", "1", "--rehearse-cpu"],
                      catalog)
    lines = [json.loads(l) for l in out.getvalue().splitlines() if l.strip()]
    return rc, lines


def test_the_real_entries_name_the_cell_and_no_other():
    """By name, never by position: a later PR appends after these."""
    metrics = {m["name"]: m for m in SPEC["per_layer"]}
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == ["lfm2-24b-a2b-topk1pct-w1"]
        assert metrics[name]["moves"] == "samples_per_s"
    cell = next(w for w in SPEC["workloads"]
                if w["name"] == "lfm2-24b-a2b-topk1pct-w1")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-24b-a2b-ep8", "topk1pct-w1", 1)
    config = next(c for c in SPEC["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    # the lists of the metrics that were there are as they were
    assert all("lfm2-24b-a2b-topk1pct-w1" not in m.get("workloads", [])
               for m in SPEC["per_layer"] if m["name"] not in NEW_METRICS)


def test_the_set_up_metrics_are_as_they_were():
    """What ``test_benchmark_compile_ledger`` checks of its six entries
    apart from their being the last of ``per_layer``, which they no longer
    are (this PR's five are appended after them, as the builder's contract
    has it, and that test fails on the line until a ``benchmark`` PR
    compares by name): there, in order, one after another."""
    names = [m["name"] for m in SPEC["per_layer"]]
    six = ["step_trace_s", "step_lower_s", "step_compile_s",
           "setup_jit_wall_s", "compile_cache_misses", "step_lowerings"]
    first = names.index(six[0])
    assert names[first:first + 6] == six
    for m in SPEC["per_layer"][first:first + 6]:
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert "workloads" not in m
        assert m["unit"] == ("s" if m["name"].endswith("_s") else "count")


def test_the_rehearsed_cell_is_correct_against_the_plain_reference(traced):
    rc, lines = traced
    assert rc == 0
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == 3
    compared = next(l for l in lines if l.get("phase") == "correct")
    assert len(compared["compared"]) == 7
    assert all(r["ok"] for r in compared["compared"])
    # the numbers compared are of a model that learns on its one batch
    first = next(l for l in lines if l.get("phase") == "setup")["first_losses"]
    assert first[2] < first[0]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_the_traced_run_reports_the_new_metric(traced, metric):
    last = traced[1][-1]
    declared = {m["name"]: m for m in SPEC["per_layer"]}
    assert last["metrics"][metric]["unit"] == declared[metric]["unit"]
    value = last["metrics"][metric]["value"]
    if metric == "moe_dropped_assignments":
        assert value == 0.0
    elif metric == "moe_held_assignments":
        # 4 expert layers, 4 x 16 tokens, 2 a token, 2 experts held of 8
        assert 0 < value < 4 * 64 * 2 and value == int(value)
    else:
        assert value > 0


def test_the_traced_run_splits_the_models_parts_by_stage(traced):
    metrics = traced[1][-1]["metrics"]
    stages = {name for name, _ in traced[1][-1]["breakdown"]["stages"]}
    assert {"grace/short_conv", "grace/moe_experts"} <= stages
    assert metrics["moe_ms"]["value"] < metrics["step_device_ms"]["value"]
    assert metrics["short_conv_ms"]["value"] < metrics["step_device_ms"]["value"]
    # picoseconds a parameter: the transform's time over the leaves' sizes
    import jax
    import math
    sizes = tiny_catalog().config("tiny-lfm2")
    builder = tiny_catalog().builder(sizes)
    held = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda k: builder.init(k, sizes)[0],
                       jax.random.key(0))))
    assert metrics["grace_ps_per_param"]["value"] == pytest.approx(
        metrics["grace_ms"]["value"] * 1e9 / held)


def test_the_control_fails_the_rehearsed_cells_limits(catalog, capsys):
    """The plain reference put in the program's place in bfloat16 is
    outside at least one limit of the cell; sound runs are inside all."""
    rc = calibrate.main(["--workload", CELL, "--seeds", "2",
                         "--control-seeds", "2", "--first-seed", "7"],
                        catalog, rehearse=True)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.strip()]
    assert rc == 0
    limits = catalog.cell(CELL)["limits"]
    flat = {f"loss_gap.step{i + 1}": v
            for i, v in enumerate(limits["loss_gap"])}
    flat.update({k: v for k, v in limits.items() if k != "loss_gap"})
    for line in lines[:-1]:
        assert all(line["sound"][k] <= flat[k] for k in flat), line["sound"]
        assert any(line["control"][k] > flat[k] for k in flat)
        assert any(line["half_batch"][k] > flat[k] for k in flat)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_program_without_the_span_or_counter_has_nothing_to_read(metric):
    """What the parent commit gives the readers: no such stage in the
    trace, a model state without counters. They return nothing and do not
    raise, and the line leaves the metric out."""
    read = harness.Catalog().reader(metric)
    state = types.SimpleNamespace(params={"w": None},
                                  model_state={"bn": {"mean": 0.0}})
    ctx = {"reduced": {"stage_s_per_step": {"grace/forward_backward": 0.1},
                       "grace_s_per_step": 0.0},
           "program": types.SimpleNamespace(state=state)}
    assert read(ctx) is None
    ctx["program"].state.model_state = {}
    assert read(ctx) is None


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog row's ``config`` is in the file under
    the same key, but the four listed in ``reduced``; nested groups whole."""
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(rows):
        pytest.skip("the catalog of architectures is not here")
    with open(rows) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "lfm2-24b-a2b-ep8.json")) as f:
        body = json.load(f)
    assert body["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if body.get(k) != v}
    assert changed == set(body["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"}
    assert body["published"] == {k: row["config"][k] for k in body["reduced"]}
    # the floors of a cut: a whole period after the dense layer, 8 experts,
    # an eighth of the vocabulary
    held = [body["layer_types"][i] for i in body["layers_held"]]
    assert held == ["conv", "full_attention", "conv", "conv", "conv"]
    assert body["num_hidden_layers"] == len(held) == 5
    assert body["num_experts"] * body["chips_sharing_a_layer"] == 64
    assert body["vocab_size"] * body["chips_sharing_a_layer"] == 65536
    assert body["param_dtype"] == "float32"
