"""The ``kanana-2-30b-a3b-ep16`` configuration and its cell, as the harness
sees them: a CPU rehearsal of ``benchmarks/run.py`` on a test-size share of
the model (new files under ``data/`` and a ``BENCHMARK.json`` written into
a temporary root; ``data/BENCHMARK.tiny.json`` is not edited), the two new
per-layer readers on a program that has nothing for them, the functions
that count the attention kernel's operations and bytes, and the real
configuration's file against the catalog row it was cut from. Entries are
found by name, never by position: a later PR appends after these."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_harness_helpers import (DATA, REPO, harness,  # noqa: E402
                                   tiny_catalog)

from benchmarks import calibrate  # noqa: E402

CELL = "tiny-kanana-topk-w1"
REAL_CELL = "kanana-2-30b-a3b-topk1pct-w1"
NEW_METRICS = ("mla_ms", "shared_expert_ms")

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)


@pytest.fixture(scope="module")
def catalog(tmp_path_factory):
    """The test-size catalog with the tiny share of the model added as the
    real one was: a configuration, a cell, the two metrics."""
    root = tmp_path_factory.mktemp("kanana")
    with open(os.path.join(DATA, "BENCHMARK.tiny.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-kanana", "source": "test",
                            "file": "configs/tiny-kanana.json", "reduced": [],
                            "why": "test size"})
    spec["workloads"].append({"name": CELL, "config": "tiny-kanana",
                              "traffic": "topk-w1", "chips": 1,
                              "why": "test size"})
    for metric in SPEC["per_layer"]:
        if metric["name"] in NEW_METRICS:
            spec["per_layer"].append(dict(metric, workloads=[CELL]))
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


def test_the_real_entries_are_the_issues():
    metrics = {m["name"]: m for m in SPEC["per_layer"]}
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [REAL_CELL]
        assert metrics[name]["moves"] == "samples_per_s"
        assert metrics[name]["layer"] == "model"
        assert metrics[name]["source"] == "device_trace"
    cell = next(w for w in SPEC["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kanana-2-30b-a3b-ep16", "topk1pct-w1", 1)
    config = next(c for c in SPEC["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["file"] == "benchmarks/configs/kanana-2-30b-a3b-ep16.json"
    # no accepted metric's list gained the cell
    assert all(REAL_CELL not in m.get("workloads", [])
               for m in SPEC["per_layer"] if m["name"] not in NEW_METRICS)
    own = harness.Catalog().cell(REAL_CELL)
    lfm2 = harness.Catalog().cell("lfm2-24b-a2b-topk1pct-w1")
    for key in ("grace", "codec", "optimizer", "span_steps", "trace_steps",
                "collectives"):
        assert own[key] == lfm2[key], key           # the LFM2 cell's traffic


def test_the_rehearsed_cell_is_correct_against_the_plain_reference(traced):
    rc, lines = traced
    assert rc == 0
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == 3
    compared = next(l for l in lines if l.get("phase") == "correct")
    assert len(compared["compared"]) == 7
    assert all(r["ok"] for r in compared["compared"])
    first = next(l for l in lines if l.get("phase") == "setup")["first_losses"]
    assert first[2] < first[0]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_the_traced_run_reports_the_new_metric(traced, metric):
    last = traced[1][-1]
    declared = {m["name"]: m for m in SPEC["per_layer"]}
    assert last["metrics"][metric]["unit"] == declared[metric]["unit"]
    assert last["metrics"][metric]["value"] > 0


def test_the_traced_run_splits_the_models_parts_by_stage(traced):
    """``breakdown.stages`` holds the ten largest stages only, so the small
    ones are read through their metrics."""
    metrics = traced[1][-1]["metrics"]
    stages = dict(traced[1][-1]["breakdown"]["stages"])
    assert "grace/mla_latent" in stages
    # the scores' stage counts in: mla_ms is the latent's own time and more
    assert (stages["grace/mla_latent"] * 1e3 < metrics["mla_ms"]["value"]
            < metrics["step_device_ms"]["value"])
    assert 0 < metrics["shared_expert_ms"]["value"] < metrics["mla_ms"]["value"]


def test_the_control_fails_the_rehearsed_cells_limits(catalog, capsys):
    """The plain reference put in the program's place in bfloat16 is
    outside at least one limit of the cell; sound runs are inside all."""
    rc = calibrate.main(["--workload", CELL, "--seeds", "1",
                         "--control-seeds", "1", "--first-seed", "7"],
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
def test_a_program_without_the_span_has_nothing_to_read(metric):
    """What the parent commit gives the readers: no such stage in the
    trace. They return nothing and do not raise, and the line leaves the
    metric out."""
    read = harness.Catalog().reader(metric)
    ctx = {"reduced": {"stage_s_per_step": {"grace/forward_backward": 0.1,
                                            "grace/attention": 0.03},
                       "grace_s_per_step": 0.0}}
    assert read(ctx) is None


def test_the_kernels_operations_and_bytes_at_the_cells_shape():
    """The counts PERF.md's roofline share of the kernel is read against:
    at 8 sequences, 5 layers, 4,096 tokens, 32 heads of 192 | 128, five
    products 192 wide and four 128 wide over half the square."""
    mla = harness.Catalog()._module("layer_metrics", "mla_ms")
    shape = dict(sequences=8, layers=5, seq_len=4096, heads=32, d_qk=192,
                 d_v=128)
    one_192 = 4096 ** 2 * 192 * 32              # 2 * T*T/2 * 192 * heads
    one_128 = 4096 ** 2 * 128 * 32
    assert one_192 == pytest.approx(103.1e9, rel=1e-3)
    assert mla.attention_flops(**shape) == 40 * (5 * one_192 + 4 * one_128)
    assert mla.attention_flops(**shape) == pytest.approx(31.6e12, rel=2e-3)
    # the tiles a kernel of 1,024 x 1,024 visits: 10 of 16
    assert mla.visited_share(4096, 1024, 1024) == 10 / 16
    assert mla.visited_share(4096, 512, 512) == 36 / 64
    assert mla.visited_share(4096, 4096, 4096) == 1.0
    assert mla.attention_flops(**shape, share=10 / 16) == pytest.approx(
        1.25 * mla.attention_flops(**shape))
    with pytest.raises(ValueError, match="whole tiles"):
        mla.visited_share(4096, 1000, 1024)
    # the LFM2 cell's kernel, as PERF.md has it since PR 31: 2.476 TFLOP
    assert mla.attention_flops(8, 1, 4096, 32, 64, 64) == pytest.approx(
        2.476e12, rel=1e-3)
    # bytes: q and k 192 wide, v, output and its gradient 128 wide, bfloat16
    qk, v, lse = 4096 * 32 * 192 * 2, 4096 * 32 * 128 * 2, 4096 * 32 * 4
    forward = 2 * qk + 2 * v + lse
    backward = forward + v + 2 * qk + v
    assert mla.attention_bytes(**shape) == 40 * (2 * forward + backward)
    # compute-bound on a v5e: 33 ms of HBM traffic under 160 ms of products
    assert (mla.attention_bytes(**shape) / 819e9
            < 0.25 * mla.attention_flops(**shape) / 197e12)


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog row's ``config`` is in the file under
    the same key, but the three listed in ``reduced``."""
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(rows):
        pytest.skip("the catalog of architectures is not here")
    with open(rows) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "kanana-2-30b-a3b-ep16.json")) as f:
        body = json.load(f)
    assert body["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items()
               if k not in body or body[k] != v}
    assert changed == set(body["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert body["published"] == {k: row["config"][k] for k in body["reduced"]}
    # the floors of a cut: the dense layer and four expert layers, 8
    # experts, an eighth of the vocabulary
    assert body["layers_held"] == [0, 1, 2, 3, 4]
    assert body["num_hidden_layers"] - body["first_k_dense_replace"] == 4
    assert body["n_routed_experts"] * body["chips_sharing_a_layer"] == 128
    assert body["n_routed_experts"] == 8 and body["vocab_size"] * 8 == 128256
    assert body["param_dtype"] == "float32"
    assert body["parameters_held"] == 424_960_512
    for key in ("seq_length", "per_chip_batch", "optimizer", "initialisation",
                "e_score_correction_bias", "expert_rows"):
        assert key in body["assumed"], key
