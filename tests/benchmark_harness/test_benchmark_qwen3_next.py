"""The ``qwen3-next-80b-a3b-ep32`` configuration and its cell, as the harness
sees them: a CPU rehearsal of ``benchmarks/run.py`` on a test-size share of
the model (new files under ``data/`` and a ``BENCHMARK.json`` written into a
temporary root; ``data/BENCHMARK.tiny.json`` is not edited), the six new
per-layer readers on what they read and on programs that have nothing for
them, the functions that count the delta rule's operations and bytes
against a hand count, and the real configuration's file against the catalog
row it was cut from. Entries are found by name, never by position: a later
PR appends after these."""

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_harness_helpers import (DATA, REPO, harness,  # noqa: E402
                                   tiny_catalog)

from benchmarks import calibrate  # noqa: E402

CELL = "tiny-qwen3-next-gdn-w1"
REAL_CELL = "qwen3-next-80b-a3b-gdn16k-topk1pct-w1"
REAL_CONFIG = "qwen3-next-80b-a3b-ep32"
# metric -> (layer, source, unit, better)
NEW_METRICS = {
    "gated_delta_ms": ("model", "device_trace", "ms", "lower"),
    "delta_rule_ms": ("kernels", "device_trace", "ms", "lower"),
    "delta_rule_roofline": ("kernels", "device_trace", "%", "higher"),
    "gated_attention_kernel_ms": ("kernels", "device_trace", "ms", "lower"),
    "gated_attention_kernel_roofline": ("kernels", "device_trace", "%",
                                        "higher"),
    "top10_load_ratio": ("model", "program_counter", "ratio", "lower")}
# what a CPU rehearsal has something to read for: the plain path runs no
# kernel and the CPU has no peak to read a roofline against
REHEARSED = ("gated_delta_ms", "delta_rule_ms", "top10_load_ratio")

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)


def _real_sizes():
    return harness.Catalog().config(REAL_CONFIG)


def _counts():
    return harness.Catalog()._module("layer_metrics", "delta_rule_roofline")


@pytest.fixture(scope="module")
def catalog(tmp_path_factory):
    """The test-size catalog with the tiny share of the model added as the
    real one was: a configuration, a cell, the six metrics."""
    root = tmp_path_factory.mktemp("qwen3_next")
    with open(os.path.join(DATA, "BENCHMARK.tiny.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-qwen3-next", "source": "test",
                            "file": "configs/tiny-qwen3-next.json",
                            "reduced": [], "why": "test size"})
    spec["workloads"].append({"name": CELL, "config": "tiny-qwen3-next",
                              "traffic": "gdn-w1", "chips": 1,
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
    # the six stand together, after everything that was there
    names = [m["name"] for m in SPEC["per_layer"]]
    first = min(names.index(n) for n in NEW_METRICS)
    assert set(names[first:first + 6]) == set(NEW_METRICS)
    assert "pre_router_load_ratio" in names[:first]
    cells = [w["name"] for w in SPEC["workloads"]]
    assert cells.index(REAL_CELL) > cells.index(
        "smallthinker-21b-a3b-swa16k-topk1pct-w1")
    cell = next(w for w in SPEC["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL_CONFIG, "gdn16k-topk1pct-w1", 1)
    assert len(cell["why"]) <= 200
    # the why says both loads: the experts' quarter, the operators' eightfold
    assert "640" in cell["why"] and "8x" in cell["why"]
    config = next(c for c in SPEC["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["file"] == f"benchmarks/configs/{REAL_CONFIG}.json"
    assert len(config["why"]) <= 200
    # this cell and no other uses the configuration, and no accepted
    # metric's list gained the cell
    assert [w["name"] for w in SPEC["workloads"]
            if w["config"] == REAL_CONFIG] == [REAL_CELL]
    assert all(REAL_CELL not in m.get("workloads", [])
               for m in SPEC["per_layer"] if m["name"] not in NEW_METRICS)
    # one cell in nine asks for four chips
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) == 1
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
    """The operator and the rule stand under two stages, one nested in the
    other, and the load ratio is the busiest held expert's rows over 2 x 128
    positions x 2 a token / 8."""
    metrics = traced[1][-1]["metrics"]
    ratio = metrics["top10_load_ratio"]["value"]
    assert 1.0 <= ratio < 3.0           # the busiest is no less than the mean
    reader = catalog._module("layer_metrics", "top10_load_ratio")
    assert reader.balanced_rows(catalog.config("tiny-qwen3-next")) == 64
    assert ratio * 64 == pytest.approx(round(ratio * 64))      # whole rows
    # 32,768 positions x 10 / 512
    assert reader.balanced_rows(_real_sizes()) == 640
    stages = dict(traced[1][-1]["breakdown"]["stages"])
    assert stages["grace/delta_rule"] * 1e3 == pytest.approx(
        metrics["delta_rule_ms"]["value"])
    assert (stages["grace/delta_rule"] + stages["grace/gated_delta"]) * 1e3 \
        == pytest.approx(metrics["gated_delta_ms"]["value"])
    assert metrics["delta_rule_ms"]["value"] \
        < metrics["gated_delta_ms"]["value"] \
        < metrics["step_device_ms"]["value"]
    # the plain path ran no kernel and the CPU is in no table of peaks:
    # nothing to read, and nothing reported
    for name in ("gated_attention_kernel_ms",
                 "gated_attention_kernel_roofline", "delta_rule_roofline"):
        assert name not in metrics
    # and no other decoder's metric reads this program
    for name in ("expert_load_ratio", "pre_router_load_ratio",
                 "full_attention_kernel_ms", "window_attention_ms"):
        assert name not in metrics


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
    return (f"  %{name} = (f32[1024,128]{{1,0}}, bf16[16,16384,256]{{2,1,0}}) "
            f"custom-call(%copy-done.26, %iota.2), "
            f'custom_call_target="tpu_custom_call", operand_layout_'
            f"constraints={{s8[1,1]{{1,0}}}}, output_to_operand_aliasing={{\n"
            f"  }}\n}}, metadata={{op_name=\"jit(device_step)/grace/forward_"
            f"backward/jvp({stage})/vmap(jit(_splash_attention))/splash/"
            f'pallas_call" stack_frame_id=62}}, backend_config={{}}\n')


def _text():
    """A step's text: the full layer's two calls and a fusion between that
    carries another stage."""
    return "".join([
        "HloModule jit_device_step\n",
        _call("splash_mha_fwd_residuals.1", "grace/attention"),
        '  %fusion.7 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={'
        'op_name="jit(device_step)/grace/lm_head/dot"}\n',
        _call("splash_mha_dkv_no_residuals.1", "grace/attention")])


def _program(sizes, model_state, text=None, device_kind="TPU v5 lite"):
    device = types.SimpleNamespace(device_kind=device_kind)
    return types.SimpleNamespace(
        config=sizes, text=text,
        state=types.SimpleNamespace(model_state=model_state),
        mesh=types.SimpleNamespace(devices=types.SimpleNamespace(
            flat=[device])))


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_a_program_without_the_stage_has_nothing_to_read(metric):
    """What an accepted decoder cell's program gives the readers (and the
    parent's, on which the traced runs of every cell are made with these
    files): no gated delta stage in the trace, its kernel's calls among the
    device operations and under ``grace/attention`` in its text, ``held``
    counters in its state and no key of a gated delta layer in its
    configuration. They return nothing and do not raise."""
    read = harness.Catalog().reader(metric)
    sdar = harness.Catalog().config("sdar-30b-a3b-ep8")
    state = {"layers": [{"held": 1.0, "dropped": 0.0,
                         "drawn": [3.0] * 128}]}
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


def test_the_readers_on_a_hand_made_trace():
    """The rule 300 ms a step and the operator around it 120; the full
    layer's forward 30 ms and backward 75 ms. The rule's least time in the
    cell is 7.958 ms (below), so its share is 2.653 %. The kernel's calls
    are 2 sequences x 16 heads x 134,225,920 allowed pairs x 1,024
    (forward) or 2,560 (backward) operations: 22.33 and 55.82 ms at 197
    TFLOP/s, 74.4 % of 105 ms."""
    sizes = _real_sizes()
    ops = [["while.7@grace/delta_rule", 0.2],
           ["splash_mha_dkv_no_residuals.1@unattributed", 0.075],
           ["splash_mha_fwd_residuals.1@unattributed", 0.030],
           ["fusion.7@grace/lm_head", 0.019]]
    stages = {"grace/delta_rule": 0.3, "grace/gated_delta": 0.12,
              "grace/attention": 0.02}
    state = {"layers": [{"drawn": [640.0] * 15 + [700.0] + [9000.0] * 496},
                        {"drawn": [672.0] * 16 + [640.0] * 496}]}
    ctx = {"reduced": {"stage_s_per_step": stages, "device_ops": ops},
           "program": _program(sizes, state, _text())}
    read = harness.Catalog().reader
    assert read("delta_rule_ms")(ctx) == pytest.approx(300)
    assert read("gated_delta_ms")(ctx) == pytest.approx(420)
    assert read("delta_rule_roofline")(ctx) == pytest.approx(
        100 * 7.9584e-3 / 0.3, rel=1e-3)
    assert read("delta_rule_roofline")(ctx) == pytest.approx(2.653, abs=2e-3)
    assert read("gated_attention_kernel_ms")(ctx) == pytest.approx(105)
    fwd = 2 * 16 * 134_225_920 * 1024 / 197e12
    bwd = 2 * 16 * 134_225_920 * 2560 / 197e12
    assert (fwd * 1e3, bwd * 1e3) == pytest.approx((22.33, 55.82), rel=1e-3)
    assert read("gated_attention_kernel_roofline")(ctx) == pytest.approx(
        100 * (fwd + bwd) / 0.105)
    assert read("gated_attention_kernel_roofline")(ctx) == pytest.approx(
        74.4, abs=0.05)
    # the busiest of the sixteen held here (experts 0-15), not of the 512
    assert read("top10_load_ratio")(ctx) == pytest.approx(700 / 640)
    # a call fallen out of the ten lowers both sides of its share
    fewer = dict(ctx, reduced=dict(ctx["reduced"], device_ops=ops[:2]))
    assert read("gated_attention_kernel_ms")(fewer) == pytest.approx(75)
    assert read("gated_attention_kernel_roofline")(fewer) == pytest.approx(
        100 * bwd / 0.075)
    # a call whose instruction the text does not hold belongs to no stage
    lost = dict(ctx, program=_program(sizes, state, "HloModule empty\n"))
    assert read("gated_attention_kernel_ms")(lost) is None
    assert read("gated_attention_kernel_roofline")(lost) is None
    # an unknown chip has no peak to read against: nothing, not a guess
    cpu = dict(ctx, program=_program(sizes, state, _text(), "cpu"))
    assert read("delta_rule_roofline")(cpu) is None
    assert read("gated_attention_kernel_roofline")(cpu) is None
    assert read("delta_rule_ms")(cpu) == pytest.approx(300)
    # a trace without the rule's stage: nothing
    none = dict(ctx, reduced=dict(ctx["reduced"], stage_s_per_step={
        "grace/attention": 0.02}))
    for name in ("delta_rule_ms", "gated_delta_ms", "delta_rule_roofline",
                 "gated_attention_kernel_ms"):
        assert read(name)(none) is None


def test_the_rules_operations_and_bytes_against_a_hand_count():
    """At a tiny shape, by hand: 3 tokens, 2 value heads over 1 key head,
    ``d_k`` 4 and ``d_v`` 5. Forward a token and value head: ``S^T k`` 4 x 5
    multiply-adds, the rank-one update 4 x 5, ``S^T q`` 4 x 5: 3 x 2 x 20 =
    120 operations; 3 tokens x 2 heads = 720; backward twice that. Bytes a
    token forward at 2 B: q 8, k 8, v 20, g 8, beta 8 read and o 20
    written: 72; backward those 52 and do 20 read and five gradients 52
    written: 124.

    In the cell: a token and layer forward 6 x 128 x 128 x 32 = 3,145,728
    operations and 24,832 bytes (the issue's 3.1 MFLOP and 25 KB: 16 ns
    against 30 ns, memory-bound), 41,472 bytes backward; three layers of
    32,768 tokens a step: 2.441 + 4.077 GB, 7.958 ms at 819 GB/s, against
    4.71 ms of operations."""
    counts = _counts()
    assert counts.rule_flops(3, 2, 4, 5, 1, 0) == 720
    assert counts.rule_flops(3, 2, 4, 5, 0, 1) == 1440
    assert counts.rule_flops(3, 2, 4, 5) == 2160
    assert counts.rule_bytes(1, 1, 2, 4, 5, 1, 0) == 72
    assert counts.rule_bytes(1, 1, 2, 4, 5, 0, 1) == 124
    assert counts.rule_bytes(3, 1, 2, 4, 5) == 3 * 196
    assert counts.rule_bytes(1, 1, 2, 4, 5, 1, 0, itemsize=4) == 128
    assert counts.rule_flops(1, 32, 128, 128, 1, 0) == 3_145_728
    assert counts.rule_bytes(1, 16, 32, 128, 128, 1, 0) == 24_832
    assert counts.rule_bytes(1, 16, 32, 128, 128, 0, 1) == 41_472
    sizes = _real_sizes()
    assert counts.delta_layers(sizes) == 3
    assert counts.delta_layers(dict(sizes, layers_held=[3, 4, 5, 6, 7])) == 3
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    tokens = 3 * 32_768
    assert counts.rule_bytes(tokens, 16, 32, 128, 128, 1, 0) == 2_441_084_928
    assert counts.rule_bytes(tokens, 16, 32, 128, 128, 0, 1) == 4_076_863_488
    least = counts.least_seconds(sizes, peaks)
    assert least == pytest.approx((2_441_084_928 + 4_076_863_488) / 819e9)
    assert least * 1e3 == pytest.approx(7.9584, rel=1e-3)
    assert counts.rule_flops(tokens, 32, 128, 128) / 197e12 * 1e3 \
        == pytest.approx(4.709, rel=1e-3)
    # a chip with a slow enough MXU would be bound by the operations
    slow = dict(peaks, bf16_flops_per_s=1e12)
    assert counts.least_seconds(sizes, slow) == pytest.approx(
        counts.rule_flops(tokens, 32, 128, 128) / 1e12)


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog row's ``config`` is in the file under
    the same key, but the three listed in ``reduced``."""
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(rows):
        pytest.skip("the catalog of architectures is not here")
    with open(rows) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    body = _real_sizes()
    assert body["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items()
               if k not in body or body[k] != v}
    assert changed == set(body["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert body["published"] == {k: row["config"][k] for k in body["reduced"]}
    assert body["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                 "vocab_size": 151936}
    # the floors of a cut: one whole period of four expert layers, 8
    # experts or more, an eighth of the vocabulary
    assert body["layers_held"] == [0, 1, 2, 3]
    assert body["num_hidden_layers"] == 4 == body["full_attention_interval"]
    assert body["num_experts"] * body["chips_sharing_a_layer"] == 512
    assert body["num_experts"] == 16 and body["chips_sharing_a_layer"] == 32
    assert body["vocab_size"] * 8 == 151936
    assert body["param_dtype"] == "float32"
    assert body["parameters_held"] == 424_340_544
    assert "thirty-two chips" in body["deployment"]
    assert "multi-token-prediction" in body["deployment"]
    for key in ("layer_equations", "column_order", "layers_held",
                "chips_sharing_a_layer", "vocab_size", "seq_length",
                "per_chip_batch", "optimizer", "initialisation",
                "router_precision", "delta_rule_precision",
                "attention_scale", "multi_token_prediction"):
        assert key in body["assumed"], key
    # a held expert's load: 32,768 positions x 10 / 512
    assert (body["per_chip_batch"] * body["seq_length"]
            * body["num_experts_per_tok"] // 512) == 640
    # the builder's Config holds the share, and its parameters add up
    import jax
    builder = harness.Catalog().builder(body)
    cfg = builder.model_config(body)
    assert (cfg.num_hidden_layers, cfg.num_experts, cfg.experts_held,
            cfg.first_expert, cfg.published_layers) == (4, 512, 16, 0, 48)
    assert cfg.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert (cfg.head_dim, cfg.rotary_dim, cfg.num_attention_heads,
            cfg.num_key_value_heads) == (256, 64, 16, 2)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim) == (16, 32, 128, 128, 4)
    shapes = jax.eval_shape(lambda k: builder.init(k, body)[0],
                            jax.random.key(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(x.size for x in leaves) == body["parameters_held"]
