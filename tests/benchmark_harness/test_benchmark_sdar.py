"""The ``sdar-30b-a3b-ep8`` configuration and its cell, as the harness sees
them: a CPU rehearsal of ``benchmarks/run.py`` on a test-size share of the
model (new files under ``data/`` and a ``BENCHMARK.json`` written into a
temporary root; ``data/BENCHMARK.tiny.json`` is not edited), the six new
per-layer readers on what they read and on programs that have nothing for
them, the functions that count the kernel's pairs, tiles, operations and
bytes under the block-diffusion mask, and the real configuration's file
against the catalog row it was cut from. Entries are found by name, never
by position: a later PR appends after these."""

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_harness_helpers import (DATA, REPO, harness,  # noqa: E402
                                   tiny_catalog)

from benchmarks import calibrate  # noqa: E402

CELL = "tiny-sdar-blockdiff-w1"
REAL_CELL = "sdar-30b-a3b-blockdiff-topk1pct-w1"
# metric -> (layer, source, unit)
NEW_METRICS = {
    "diffusion_noise_ms": ("model", "device_trace", "ms"),
    "block_attention_ms": ("model", "device_trace", "ms"),
    "block_attention_kernel_ms": ("kernels", "device_trace", "ms"),
    "block_attention_kernel_roofline": ("kernels", "device_trace", "%"),
    "diffusion_masked_tokens": ("model", "program_counter", "count"),
    "expert_load_ratio": ("model", "program_counter", "ratio")}
# what a CPU rehearsal has something to read for: the plain path runs no
# kernel, so the kernel's two have nothing there
REHEARSED = ("diffusion_noise_ms", "block_attention_ms",
             "diffusion_masked_tokens", "expert_load_ratio")

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)


def _real_sizes():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "sdar-30b-a3b-ep8.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def catalog(tmp_path_factory):
    """The test-size catalog with the tiny share of the model added as the
    real one was: a configuration, a cell, the six metrics."""
    root = tmp_path_factory.mktemp("sdar")
    with open(os.path.join(DATA, "BENCHMARK.tiny.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-sdar", "source": "test",
                            "file": "configs/tiny-sdar.json", "reduced": [],
                            "why": "test size"})
    spec["workloads"].append({"name": CELL, "config": "tiny-sdar",
                              "traffic": "blockdiff-w1", "chips": 1,
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
    for name, (layer, source, unit) in NEW_METRICS.items():
        assert metrics[name]["workloads"] == [REAL_CELL]
        assert metrics[name]["moves"] == "samples_per_s"
        assert (metrics[name]["layer"], metrics[name]["source"],
                metrics[name]["unit"]) == (layer, source, unit)
    cell = next(w for w in SPEC["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar-30b-a3b-ep8", "blockdiff-b4-topk1pct-w1", 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in SPEC["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["file"] == "benchmarks/configs/sdar-30b-a3b-ep8.json"
    assert len(config["why"]) <= 200
    # no accepted metric's list gained the cell
    assert all(REAL_CELL not in m.get("workloads", [])
               for m in SPEC["per_layer"] if m["name"] not in NEW_METRICS)
    own = harness.Catalog().cell(REAL_CELL)
    lfm2 = harness.Catalog().cell("lfm2-24b-a2b-topk1pct-w1")
    for key in ("grace", "codec", "span_steps", "trace_steps", "collectives"):
        assert own[key] == lfm2[key], key           # the decoder cells' codec
    # continued training of a trained checkpoint: a tenth of their rate
    assert own["optimizer"] == {"name": "adamw", "lr": 1e-05}
    assert lfm2["optimizer"]["lr"] == 10 * own["optimizer"]["lr"]


def test_the_rehearsed_cell_is_correct_against_the_plain_reference(traced):
    rc, lines = traced
    assert rc == 0
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == 3
    compared = next(l for l in lines if l.get("phase") == "correct")
    assert len(compared["compared"]) == 7
    assert all(r["ok"] for r in compared["compared"])
    # fresh noise every step: three losses, none twice, and the
    # reference's at each step beside them
    first = next(l for l in lines if l.get("phase") == "setup")["first_losses"]
    assert len(set(first)) == 3
    for got, want in zip(first, compared["reference_losses"]):
        assert abs(got - want) < 1e-3 * want


@pytest.mark.parametrize("metric", REHEARSED)
def test_the_traced_run_reports_the_new_metric(traced, metric):
    last = traced[1][-1]
    assert last["metrics"][metric]["unit"] == NEW_METRICS[metric][2]
    assert last["metrics"][metric]["value"] > 0


def test_the_traced_run_reads_the_programs_counters(traced, catalog):
    """Three steps of set-up and three traced: the last step's ``masked``
    counter is about half the chip's 4 x 16 tokens, and the load ratio is
    the two layers' held rows over 2 x 128 positions x 2 a token x 4 / 8."""
    metrics = traced[1][-1]["metrics"]
    assert 0 < metrics["diffusion_masked_tokens"]["value"] < 64
    ratio = metrics["expert_load_ratio"]["value"]
    assert 0.3 < ratio < 3.0
    reader = catalog._module("layer_metrics", "expert_load_ratio")
    assert reader.balanced_load(catalog.config("tiny-sdar")) == 256
    assert ratio * 256 == pytest.approx(round(ratio * 256))    # whole rows
    assert reader.balanced_load(_real_sizes()) == 131_072
    stages = dict(traced[1][-1]["breakdown"]["stages"])
    assert stages["grace/attention"] * 1e3 == pytest.approx(
        metrics["block_attention_ms"]["value"])
    assert (metrics["diffusion_noise_ms"]["value"]
            < metrics["block_attention_ms"]["value"]
            < metrics["step_device_ms"]["value"])
    # the plain path ran no kernel: nothing to read, and nothing reported
    assert "block_attention_kernel_ms" not in metrics
    assert "block_attention_kernel_roofline" not in metrics


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


# ---------------------------------------------------------------------------
# the readers on hand-made contexts
# ---------------------------------------------------------------------------

def _program(sizes, model_state, device_kind="TPU v5 lite"):
    device = types.SimpleNamespace(device_kind=device_kind)
    return types.SimpleNamespace(
        config=sizes, state=types.SimpleNamespace(model_state=model_state),
        mesh=types.SimpleNamespace(devices=types.SimpleNamespace(
            flat=[device])))


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_a_program_without_the_span_has_nothing_to_read(metric):
    """What an accepted decoder cell's program gives the readers: no
    diffusion stage in the trace, a model state without ``step`` or
    ``masked``, a configuration without a block length, its kernel's calls
    among the device operations. They return nothing and do not raise."""
    read = harness.Catalog().reader(metric)
    kanana = harness.Catalog().config("kanana-2-30b-a3b-ep16")
    state = {"layers": [{}, {"held": 1.0, "dropped": 0.0}]}
    ctx = {"reduced": {"stage_s_per_step": {"grace/forward_backward": 0.1,
                                            "grace/attention": 0.03},
                       "device_ops": [
                           ["splash_mha_dkv_no_residuals.59@unattributed",
                            0.03], ["fusion.1@grace/attention", 0.01]],
                       "grace_s_per_step": 0.0},
           "program": _program(kanana, state)}
    assert read(ctx) is None
    bare = {"reduced": {"stage_s_per_step": {}, "device_ops": []},
            "program": types.SimpleNamespace(state=None)}
    assert read(bare) is None


def test_the_kernels_readers_on_a_hand_made_trace():
    """All eight calls among the ten largest: 4 forward calls of 15 ms and
    4 backward of 37 ms a step. One layer's calls are 4 sequences x 32
    heads x 16,793,600 allowed pairs x 512 (forward) or 1,280 (backward)
    operations = 1.1006 and 2.7515 TFLOP: 5.587 and 13.967 ms at 197
    TFLOP/s, so the share is 4 x (5.587 + 13.967) / 208 = 37.6 %. With one
    backward call fallen out of the ten, both sides lose it."""
    sizes = _real_sizes()
    ops = ([[f"splash_mha_dkv_no_residuals.{i}@unattributed", 0.037]
            for i in range(4)]
           + [[f"splash_mha_fwd_residuals.{i}@unattributed", 0.015]
              for i in range(4)]
           + [["fusion.7@grace/lm_head", 0.009],
              ["fusion.9@grace/moe_experts", 0.008]])
    ctx = {"reduced": {"stage_s_per_step": {"grace/diffusion_noise": 1e-4},
                       "device_ops": ops},
           "program": _program(sizes, {"step": 9.0})}
    kernel_ms = harness.Catalog().reader("block_attention_kernel_ms")
    roofline = harness.Catalog().reader("block_attention_kernel_roofline")
    assert kernel_ms(ctx) == pytest.approx(4 * (37 + 15))
    fwd = 4 * 32 * 16_793_600 * 512 / 197e12
    bwd = 4 * 32 * 16_793_600 * 1280 / 197e12
    assert fwd * 1e3 == pytest.approx(5.587, rel=1e-3)
    assert roofline(ctx) == pytest.approx(100 * 4 * (fwd + bwd) / 0.208)
    assert roofline(ctx) == pytest.approx(37.6, abs=0.05)
    fewer = dict(ctx, reduced=dict(ctx["reduced"], device_ops=ops[1:]))
    assert kernel_ms(fewer) == pytest.approx(3 * 37 + 4 * 15)
    assert roofline(fewer) == pytest.approx(
        100 * (4 * fwd + 3 * bwd) / 0.171)
    assert 1 < roofline(fewer) < 100
    # an unknown chip has no peak to read against: nothing, not a guess
    cpu = dict(ctx, program=_program(sizes, {"step": 9.0}, "cpu"))
    assert roofline(cpu) is None and kernel_ms(cpu) == pytest.approx(208)


def test_the_kernels_pairs_tiles_operations_and_bytes():
    """Three shapes by hand. ``L = 16, B = 4`` (the truth table of
    ``tests/test_sdar.py``): 16 x 4 + 16^2 = 320 pairs; in tiles of 8, two a
    copy: 2 on the noised diagonal, 3 + 3 in the triangles, 8 of 16. ``L =
    4,096, B = 4`` in tiles of 1,024 (the cell): 16,384 + 16,777,216 pairs,
    24 of 64 tiles. ``L = 4,096, B = 1,024`` (a block a tile): the noised
    queries' clean keys are strictly earlier blocks, so the diagonal tiles
    of that quadrant go: 4 + 6 + 10 = 20."""
    counts = harness.Catalog()._module("layer_metrics",
                                       "block_attention_kernel_roofline")
    assert counts.allowed_pairs(16, 4) == 320
    assert counts.visited_tiles(16, 4, 8, 8) == (8, 16)
    assert counts.allowed_pairs(4096, 4) == 16_384 + 16_777_216 == 16_793_600
    assert counts.visited_tiles(4096, 4, 1024, 1024) == (24, 64)
    assert counts.allowed_pairs(4096, 1024) == 4096 * 1024 + 4096 ** 2
    assert counts.visited_tiles(4096, 1024, 1024, 1024) == (20, 64)
    with pytest.raises(ValueError, match="whole"):
        counts.visited_tiles(4096, 4, 1000, 1024)
    with pytest.raises(ValueError, match="whole blocks"):
        counts.allowed_pairs(18, 4)
    # operations: a forward is two products a pair (128 + 128 wide), the
    # fused backward five (3 x 128 + 2 x 128)
    assert counts.kernel_flops(1, 1, 128, 128, 1, 0) == 512
    assert counts.kernel_flops(1, 1, 128, 128, 0, 1) == 1280
    assert counts.kernel_flops(1, 1, 192, 128, 1, 1) == 2 * (320 + 832)
    step = 4 * 4 * counts.kernel_flops(16_793_600, 32, 128, 128)
    assert step == pytest.approx(15.41e12, rel=1e-3)       # the pairs allowed
    visited = 4 * 4 * counts.kernel_flops(24 * 1024 ** 2, 32, 128, 128)
    assert visited == pytest.approx(23.09e12, rel=1e-3)    # the tiles visited
    # bytes of one sequence of 8,192 positions, 32 | 4 heads of 128, bf16:
    # q and the output 67.1 MB each, k and v 8.4 MB, log-sum-exp 1.05 MB
    q = 8192 * 32 * 128 * 2
    kv = 8192 * 4 * 128 * 2
    lse = 8192 * 32 * 4
    assert counts.kernel_bytes(8192, 32, 4, 128, 128, 1, 0) \
        == 2 * q + 2 * kv + lse
    assert counts.kernel_bytes(8192, 32, 4, 128, 128, 0, 1) \
        == (2 * q + 2 * kv + lse) + q + (q + 2 * kv)
    # compute-bound on a v5e: 9 ms of HBM traffic a step under 78 of products
    moved = 4 * 4 * counts.kernel_bytes(8192, 32, 4, 128, 128)
    assert moved / 819e9 < 0.15 * step / 197e12


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog row's ``config`` is in the file under
    the same key, but the three listed in ``reduced``."""
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(rows):
        pytest.skip("the catalog of architectures is not here")
    with open(rows) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    body = _real_sizes()
    assert body["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items()
               if k not in body or body[k] != v}
    assert changed == set(body["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert body["published"] == {k: row["config"][k] for k in body["reduced"]}
    # the floors of a cut: four expert layers (no leading dense one), 8 or
    # more experts, an eighth of the vocabulary
    assert body["layers_held"] == [0, 1, 2, 3] and body["mlp_only_layers"] == []
    assert body["num_hidden_layers"] == 4
    assert body["num_experts"] * body["chips_sharing_a_layer"] == 128
    assert body["num_experts"] == 16 and body["vocab_size"] * 8 == 151936
    assert body["param_dtype"] == "float32"
    assert body["parameters_held"] == 456_346_624
    # what the catalog says the config does not give is assumed by name
    assert set(row["not_given"]) == {"block length", "noise schedule"}
    for key in ("block_length", "noise_schedule", "mask_token", "seq_length",
                "per_chip_batch", "optimizer", "initialisation",
                "router_precision", "attention_scale"):
        assert key in body["assumed"], key
    assert (body["block_length"], body["noise_eps"]) == (4, 0.001)
    # a held expert's load: 32,768 positions x 8 / 128
    assert (body["per_chip_batch"] * 2 * body["seq_length"]
            * body["num_experts_per_tok"] // 128) == 2048
