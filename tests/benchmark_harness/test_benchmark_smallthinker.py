"""The ``smallthinker-21b-a3b-ep8`` configuration and its cell, as the
harness sees them: a CPU rehearsal of ``benchmarks/run.py`` on a test-size
share of the model (new files under ``data/`` and a ``BENCHMARK.json``
written into a temporary root; ``data/BENCHMARK.tiny.json`` is not edited),
the seven new per-layer readers on what they read and on programs that have
nothing for them, the functions that count the kernel's pairs and tiles
under a window, and the real configuration's file against the catalog row
it was cut from. Entries are found by name, never by position: a later PR
appends after these."""

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_harness_helpers import (DATA, REPO, harness,  # noqa: E402
                                   tiny_catalog)

from benchmarks import calibrate  # noqa: E402

CELL = "tiny-smallthinker-swa-w1"
REAL_CELL = "smallthinker-21b-a3b-swa16k-topk1pct-w1"
REAL_CONFIG = "smallthinker-21b-a3b-ep8"
# metric -> (layer, source, unit, better)
NEW_METRICS = {
    "window_attention_ms": ("model", "device_trace", "ms", "lower"),
    "full_attention_ms": ("model", "device_trace", "ms", "lower"),
    "window_attention_kernel_ms": ("kernels", "device_trace", "ms", "lower"),
    "full_attention_kernel_ms": ("kernels", "device_trace", "ms", "lower"),
    "window_attention_kernel_roofline": ("kernels", "device_trace", "%",
                                         "higher"),
    "full_attention_kernel_roofline": ("kernels", "device_trace", "%",
                                       "higher"),
    "pre_router_load_ratio": ("model", "program_counter", "ratio", "lower")}
# what a CPU rehearsal has something to read for: the plain path runs no
# kernel, so the kernels' four have nothing there
REHEARSED = ("window_attention_ms", "full_attention_ms",
             "pre_router_load_ratio")

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)


def _real_sizes():
    return harness.Catalog().config(REAL_CONFIG)


def _counts():
    return harness.Catalog()._module("layer_metrics",
                                     "window_attention_kernel_roofline")


@pytest.fixture(scope="module")
def catalog(tmp_path_factory):
    """The test-size catalog with the tiny share of the model added as the
    real one was: a configuration, a cell, the seven metrics."""
    root = tmp_path_factory.mktemp("smallthinker")
    with open(os.path.join(DATA, "BENCHMARK.tiny.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-smallthinker", "source": "test",
                            "file": "configs/tiny-smallthinker.json",
                            "reduced": [], "why": "test size"})
    spec["workloads"].append({"name": CELL, "config": "tiny-smallthinker",
                              "traffic": "swa-w1", "chips": 1,
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
    for name, (layer, source, unit, better) in NEW_METRICS.items():
        assert metrics[name]["workloads"] == [REAL_CELL]
        assert metrics[name]["moves"] == "samples_per_s"
        assert (metrics[name]["layer"], metrics[name]["source"],
                metrics[name]["unit"], metrics[name]["better"]) == (
                    layer, source, unit, better)
    # the seven stand together, after everything that was there
    names = [m["name"] for m in SPEC["per_layer"]]
    first = min(names.index(n) for n in NEW_METRICS)
    assert set(names[first:first + 7]) == set(NEW_METRICS)
    assert "expert_load_ratio" in names[:first]
    cell = next(w for w in SPEC["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL_CONFIG, "swa16k-topk1pct-w1", 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in SPEC["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers",
                                 "moe_num_primary_experts", "vocab_size"]
    assert config["file"] == f"benchmarks/configs/{REAL_CONFIG}.json"
    assert len(config["why"]) <= 200
    # no accepted metric's list gained the cell
    assert all(REAL_CELL not in m.get("workloads", [])
               for m in SPEC["per_layer"] if m["name"] not in NEW_METRICS)
    own = harness.Catalog().cell(REAL_CELL)
    sdar = harness.Catalog().cell("sdar-30b-a3b-blockdiff-topk1pct-w1")
    for key in ("grace", "codec", "span_steps", "trace_steps", "collectives",
                "optimizer"):
        assert own[key] == sdar[key], key           # the decoder cells' codec
    assert own["optimizer"] == {"name": "adamw", "lr": 1e-05}


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
    for got, want in zip(first, compared["reference_losses"]):
        assert abs(got - want) < 1e-3 * want


@pytest.mark.parametrize("metric", REHEARSED)
def test_the_traced_run_reports_the_new_metric(traced, metric):
    last = traced[1][-1]
    assert last["metrics"][metric]["unit"] == NEW_METRICS[metric][2]
    assert last["metrics"][metric]["value"] > 0


def test_the_traced_run_reads_the_programs_stages_and_counters(traced,
                                                               catalog):
    """The two kinds of layer stand under two stages, and the load ratio is
    the four layers' held rows over 4 x 128 positions x 2 a token x 4 / 8."""
    metrics = traced[1][-1]["metrics"]
    ratio = metrics["pre_router_load_ratio"]["value"]
    assert 0.3 < ratio < 3.0
    reader = catalog._module("layer_metrics", "pre_router_load_ratio")
    assert reader.balanced_load(catalog.config("tiny-smallthinker")) == 512
    assert ratio * 512 == pytest.approx(round(ratio * 512))    # whole rows
    # 4 layers x 32,768 positions x 6 x 8 / 64
    assert reader.balanced_load(_real_sizes()) == 98_304
    stages = dict(traced[1][-1]["breakdown"]["stages"])
    assert stages["grace/window_attention"] * 1e3 == pytest.approx(
        metrics["window_attention_ms"]["value"])
    assert stages["grace/attention"] * 1e3 == pytest.approx(
        metrics["full_attention_ms"]["value"])
    assert (metrics["window_attention_ms"]["value"]
            + metrics["full_attention_ms"]["value"]
            < metrics["step_device_ms"]["value"])
    # the plain path ran no kernel: nothing to read, and nothing reported
    for name in NEW_METRICS:
        if "kernel" in name:
            assert name not in metrics
    # and no block-diffusion metric reads this program
    assert "expert_load_ratio" not in metrics


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

def _call(name, stage):
    """A Pallas call as the compiled text prints it: the ``op_name`` lines
    below the instruction's name."""
    return (f"  %{name} = (f32[1024,128]{{1,0}}, bf16[28,16384,128]{{2,1,0}}) "
            f"custom-call(%copy-done.26, %iota.2), "
            f'custom_call_target="tpu_custom_call", operand_layout_'
            f"constraints={{s8[1,1]{{1,0}}}}, output_to_operand_aliasing={{\n"
            f"  }}\n}}, metadata={{op_name=\"jit(device_step)/grace/forward_"
            f"backward/jvp({stage})/vmap(jit(_splash_attention))/splash/"
            f'pallas_call" stack_frame_id=62}}, backend_config={{}}\n')


def _text():
    """A step's text: the full layer's two calls, the three windowed
    layers' six, and a fusion between that carries another stage."""
    lines = ["HloModule jit_device_step\n",
             _call("splash_mha_fwd_residuals.1", "grace/attention"),
             '  %fusion.7 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={'
             'op_name="jit(device_step)/grace/lm_head/dot"}\n',
             _call("splash_mha_dkv_no_residuals.1", "grace/attention")]
    for i in (2, 3, 4):
        lines.append(_call(f"splash_mha_fwd_residuals.{i}",
                           "grace/window_attention"))
        lines.append(_call(f"splash_mha_dkv_no_residuals.{i}",
                           "grace/window_attention"))
    return "".join(lines)


def _program(sizes, model_state, text=None, device_kind="TPU v5 lite"):
    device = types.SimpleNamespace(device_kind=device_kind)
    return types.SimpleNamespace(
        config=sizes, text=text,
        state=types.SimpleNamespace(model_state=model_state),
        mesh=types.SimpleNamespace(devices=types.SimpleNamespace(
            flat=[device])))


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_a_program_without_the_stage_has_nothing_to_read(metric):
    """What an accepted decoder cell's program gives the readers: no window
    stage in the trace, its kernel's calls among the device operations and
    under ``grace/attention`` in its text, ``held`` counters in its state.
    They return nothing and do not raise."""
    read = harness.Catalog().reader(metric)
    sdar = harness.Catalog().config("sdar-30b-a3b-ep8")
    state = {"layers": [{"held": 1.0, "dropped": 0.0}]}
    ctx = {"reduced": {"stage_s_per_step": {"grace/forward_backward": 0.1,
                                            "grace/attention": 0.03},
                       "device_ops": [
                           ["splash_mha_dkv_no_residuals.1@unattributed",
                            0.03], ["fusion.1@grace/attention", 0.01]],
                       "grace_s_per_step": 0.0},
           "program": _program(sdar, state, _text())}
    assert read(ctx) is None
    bare = {"reduced": {"stage_s_per_step": {}, "device_ops": []},
            "program": types.SimpleNamespace(state=None)}
    assert read(bare) is None


def test_the_kernels_readers_on_a_hand_made_trace():
    """All eight calls among the ten largest: the full layer's forward 40 ms
    and backward 100 ms a step, each windowed layer's 18 and 45 ms. A full
    layer's calls are 2 sequences x 28 heads x 134,225,920 allowed pairs x
    512 (forward) or 1,280 (backward) operations: 19.54 and 48.84 ms at 197
    TFLOP/s, so its share is 68.38 / 140 = 48.8 %; a windowed layer's are
    over 58,722,304 pairs: 8.547 and 21.37 ms, 3 x 29.91 / 189 = 47.5 %.
    With a windowed forward call fallen out of the ten, both sides of the
    window's share lose it and the full layer's does not move."""
    sizes = _real_sizes()
    ops = ([["splash_mha_dkv_no_residuals.1@unattributed", 0.100],
            ["splash_mha_fwd_residuals.1@unattributed", 0.040]]
           + [[f"splash_mha_dkv_no_residuals.{i}@unattributed", 0.045]
              for i in (2, 3, 4)]
           + [["fusion.7@grace/lm_head", 0.019]]
           + [[f"splash_mha_fwd_residuals.{i}@unattributed", 0.018]
              for i in (2, 3, 4)])
    stages = {"grace/window_attention": 0.06, "grace/attention": 0.02}
    ctx = {"reduced": {"stage_s_per_step": stages, "device_ops": ops},
           "program": _program(sizes, {}, _text())}
    read = harness.Catalog().reader
    assert read("full_attention_kernel_ms")(ctx) == pytest.approx(140)
    assert read("window_attention_kernel_ms")(ctx) == pytest.approx(189)
    assert read("window_attention_ms")(ctx) == pytest.approx(60)
    assert read("full_attention_ms")(ctx) == pytest.approx(20)
    full = 2 * 28 * 134_225_920 * (512 + 1280) / 197e12
    fwd = 2 * 28 * 58_722_304 * 512 / 197e12
    bwd = 2 * 28 * 58_722_304 * 1280 / 197e12
    assert full * 1e3 == pytest.approx(68.38, rel=1e-3)
    assert (fwd * 1e3, bwd * 1e3) == pytest.approx((8.547, 21.37), rel=1e-3)
    assert read("full_attention_kernel_roofline")(ctx) == pytest.approx(
        100 * full / 0.140)
    assert read("full_attention_kernel_roofline")(ctx) == pytest.approx(
        48.8, abs=0.05)
    assert read("window_attention_kernel_roofline")(ctx) == pytest.approx(
        100 * 3 * (fwd + bwd) / 0.189)
    assert read("window_attention_kernel_roofline")(ctx) == pytest.approx(
        47.5, abs=0.05)
    fewer = dict(ctx, reduced=dict(ctx["reduced"], device_ops=ops[:-1]))
    assert read("window_attention_kernel_ms")(fewer) == pytest.approx(171)
    assert read("window_attention_kernel_roofline")(fewer) == pytest.approx(
        100 * (2 * fwd + 3 * bwd) / 0.171)
    assert read("full_attention_kernel_ms")(fewer) == pytest.approx(140)
    # a call whose instruction the text does not hold belongs to no stage
    lost = dict(ctx, program=_program(sizes, {}, "HloModule empty\n"))
    assert read("window_attention_kernel_ms")(lost) is None
    assert read("full_attention_kernel_roofline")(lost) is None
    # an unknown chip has no peak to read against: nothing, not a guess
    cpu = dict(ctx, program=_program(sizes, {}, _text(), "cpu"))
    assert read("window_attention_kernel_roofline")(cpu) is None
    assert read("window_attention_kernel_ms")(cpu) == pytest.approx(189)


def test_a_calls_stage_is_read_instruction_by_instruction():
    """The ``op_name`` of a Pallas call stands lines below its name; the
    stage is the rightmost ``grace/<stage>`` of the first ``op_name`` after
    the instruction's own name, and a name that only begins like another's
    (``.1`` and ``.12``) is not taken for it."""
    counts = _counts()
    text = _text() + _call("splash_mha_fwd_residuals.12", "grace/mla_latent")
    assert counts.stage_of_call(text, "splash_mha_fwd_residuals.1") \
        == "grace/attention"
    assert counts.stage_of_call(text, "splash_mha_dkv_no_residuals.3") \
        == "grace/window_attention"
    assert counts.stage_of_call(text, "splash_mha_fwd_residuals.12") \
        == "grace/mla_latent"
    assert counts.stage_of_call(text, "splash_mha_fwd_residuals.9") is None
    assert counts.stage_of_call(None, "splash_mha_fwd_residuals.1") is None


def test_the_kernels_pairs_and_tiles_under_a_window():
    """By hand. 8 positions, a window of 3: rows read 1, 2, 3, 3, 3, 3, 3, 3
    keys = 21; without a window 36. In tiles of 4 the window visits 3 of 4
    (the tile above the diagonal goes); in tiles of 2 the diagonal's 4 and
    the 3 below it, 7 of 16 (the nearest pair of a tile two below is 3
    apart); a window of 4 reaches that pair, 9; a window of 1 is the
    diagonal, 4. The cell: 16,384 positions, a window of 4,096, tiles of 1,024:
    58,722,304 of the causal mask's 134,225,920 pairs (44 %), 70 tiles of
    256 where the causal mask visits 136."""
    counts = _counts()
    assert counts.allowed_pairs(8, 3) == 21
    assert counts.allowed_pairs(8) == 36 == counts.allowed_pairs(8, 8)
    assert counts.allowed_pairs(8, 100) == 36
    assert counts.visited_tiles(8, 3, 4, 4) == (3, 4)
    assert counts.visited_tiles(8, 3, 2, 2) == (7, 16)
    assert counts.visited_tiles(8, 4, 2, 2) == (9, 16)
    assert counts.visited_tiles(8, 1, 2, 2) == (4, 16)
    assert counts.visited_tiles(8, None, 2, 2) == (10, 16)
    brute = sum(1 for i in range(8) for j in range(8) if 0 <= i - j < 3)
    assert brute == 21
    assert counts.allowed_pairs(16384, 4096) == 58_722_304
    assert counts.allowed_pairs(16384) == 134_225_920
    assert counts.visited_tiles(16384, 4096, 1024, 1024) == (70, 256)
    assert counts.visited_tiles(16384, None, 1024, 1024) == (136, 256)
    with pytest.raises(ValueError, match="whole"):
        counts.visited_tiles(16384, 4096, 1000, 1024)
    with pytest.raises(ValueError, match="position"):
        counts.allowed_pairs(8, 0)
    # a step's kernel operations over the allowed pairs: one full layer and
    # three windowed ones, 2 sequences, 28 heads of 128 | 128
    step = 2 * (counts.kernel_flops(134_225_920, 28, 128, 128)
                + 3 * counts.kernel_flops(58_722_304, 28, 128, 128))
    assert step == pytest.approx(31.15e12, rel=1e-3)
    # compute-bound on a v5e: a layer's bytes take a twentieth of its products
    moved = 2 * counts.kernel_bytes(16384, 28, 4, 128, 128)
    assert moved / 819e9 < 0.1 * 2 * counts.kernel_flops(
        58_722_304, 28, 128, 128) / 197e12


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog row's ``config`` is in the file under
    the same key, but the three listed in ``reduced``; the two layouts
    stand whole."""
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(rows):
        pytest.skip("the catalog of architectures is not here")
    with open(rows) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    body = _real_sizes()
    assert body["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items()
               if k not in body or body[k] != v}
    assert changed == set(body["reduced"]) == {
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"}
    assert body["published"] == {k: row["config"][k] for k in body["reduced"]}
    assert body["published"] == {"num_hidden_layers": 52,
                                 "moe_num_primary_experts": 64,
                                 "vocab_size": 151936}
    # the floors of a cut: one whole period of four expert layers, 8
    # experts, an eighth of the vocabulary
    assert body["layers_held"] == [0, 1, 2, 3]
    assert [body["sliding_window_layout"][i] for i in body["layers_held"]] \
        == [body["rope_layout"][i] for i in body["layers_held"]] \
        == [0, 1, 1, 1]
    assert len(body["rope_layout"]) == len(body["sliding_window_layout"]) == 52
    assert body["num_hidden_layers"] == 4
    assert (body["moe_num_primary_experts"]
            * body["chips_sharing_a_layer"]) == 64
    assert body["moe_num_primary_experts"] == 8
    assert body["vocab_size"] * 8 == 151936
    assert body["param_dtype"] == "float32"
    assert body["parameters_held"] == 370_547_200
    assert body["seq_length"] == body["max_position_embeddings"] == 16384
    for key in ("router_input", "bias_and_head_norms", "window",
                "layers_held", "chips_sharing_a_layer", "seq_length",
                "per_chip_batch", "optimizer", "initialisation",
                "router_precision", "attention_scale"):
        assert key in body["assumed"], key
    # a held expert's load: 32,768 positions x 6 / 64
    assert (body["per_chip_batch"] * body["seq_length"]
            * body["moe_num_active_primary_experts"] // 64) == 3072
    # the builder's Config holds the share, and its parameters add up
    import jax
    builder = harness.Catalog().builder(body)
    cfg = builder.model_config(body)
    assert (cfg.num_hidden_layers, cfg.num_experts, cfg.experts_held,
            cfg.first_expert, cfg.published_layers) == (4, 64, 8, 0, 52)
    assert cfg.sliding_window_layout == cfg.rope_layout == (0, 1, 1, 1)
    shapes = jax.eval_shape(lambda k: builder.init(k, body)[0],
                            jax.random.key(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(x.size for x in leaves) == body["parameters_held"]
