"""CPU rehearsal of chip_smoke.py (``on-chip-measurement`` guide, section
2.1–2.2): the smoke's own phases at a tiny size on the virtual CPU devices,
so a wrong path, argument or sharding rule is found here and not on the
chip's clock. The platform check is told it is a rehearsal by the test
(monkeypatch) — the script has no option for it — and with the check live
the script must fail on this machine and print no result.
"""

import json
import os
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

# ResNet-50 keeps its full width and depth (the repo's only depths are 50+);
# the input, the batch and the step count shrink. BERT shrinks to two layers.
TINY = chip_smoke.Sizes(
    image_hw=32, batches=(8,), steps=1, warmup=1, kernel_n=70_000,
    bert_layers=2, bert_hidden=64, bert_heads=4, bert_ff=128,
    bert_vocab=1000, bert_seq=32, bert_batch=2)


def phase_lines(capsys) -> list[dict]:
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    return [json.loads(l) for l in lines]      # every line must parse


def submesh(n: int) -> Mesh:
    return Mesh(np.asarray(jax.devices()[:n]), ("data",))


@pytest.fixture
def wide_band(monkeypatch):
    """The loss band is about a real batch on the chip; three steps on
    eight 32x32 images through batch-norm say nothing about it."""
    monkeypatch.setattr(chip_smoke, "LOSS_BAND_HIGH", 1e3)
    monkeypatch.setattr(chip_smoke, "LOSS_BAND_LOW", 1e3)


def test_one_chip_phases_rehearse_on_cpu(capsys, wide_band):
    mesh = submesh(1)
    chip_smoke.resnet_phase(TINY, 0, mesh,
                            chip_smoke.one_chip_configs(), "train")
    chip_smoke.kernels_phase(TINY, 0)
    chip_smoke.transformer_phase(TINY, 0, mesh)
    lines = phase_lines(capsys)
    assert all(l["ok"] is True for l in lines)
    train = [l for l in lines if l["phase"] == "train"]
    assert [l["config"] for l in train] == [
        "dense", "topk1pct_perleaf", "topk1pct_flat_pallas",
        "qsgd_auto_perleaf"]
    assert all(len(l["losses"]) == TINY.warmup + TINY.steps for l in train)
    kernels = {(l["kernel"], l["variant"]) for l in lines
               if l["phase"] == "kernels"}
    # every entry point of the three Pallas modules, every width
    assert {k for k, _ in kernels} == {
        "chunk_compress_feedback", "chunk_aggregate_dense",
        "quantize_stochastic", "quantize_pack_stochastic", "sign_pack",
        "decode_accumulate", "packed_int_accumulate"}
    assert len(kernels) == 16
    assert [l["config"] for l in lines if l["phase"] == "transformer"] == [
        "bert_base_powersgd_r4"]


def test_four_device_phase_finds_replicas_identical(capsys, wide_band):
    configs = chip_smoke.four_chip_configs()
    for cfg in configs:
        # Interpreted kernels inside a multi-device CPU program take many
        # minutes; the exchange schedule and its checks are what is
        # rehearsed here, the kernels are test_tpu_compile.py's.
        if cfg["params"].get("use_pallas") is True:
            cfg["params"]["use_pallas"] = False
    mesh = submesh(4)
    chip_smoke.resnet_phase(
        TINY, 0, mesh, configs, "train_multichip",
        after_config=chip_smoke.replica_checks(4))
    lines = phase_lines(capsys)
    assert [l["config"] for l in lines] == [c["name"] for c in configs]
    for line in lines:
        assert line["ok"] and line["replicas_identical"] and \
            line["world"] == 4
    by_name = {l["config"]: l for l in lines}
    assert by_name["dense"]["collectives"]["all-reduce"] > 0
    assert by_name["topk1pct_allgather"]["collectives"]["all-gather"] > 0
    assert by_name["topk1pct_allgather"]["mem_leaves_sharded"] == \
        by_name["topk1pct_allgather"]["param_leaves"]
    assert by_name["qsgd4_packed_ring_flat"]["collectives"][
        "collective-permute"] > 0


def test_mesh_check_refuses_too_few_devices():
    with pytest.raises(chip_smoke.SmokeFailure, match="distinct devices"):
        chip_smoke.check_world(submesh(2), len(jax.devices()))


def test_live_platform_check_fails_off_the_chip(capsys):
    assert chip_smoke.main([]) == 1
    last = phase_lines(capsys)[-1]
    assert last["ok"] is False and "not 'tpu'" in last["error"]
    assert "device" not in last


def test_refuses_to_start_with_kernels_disabled(capsys, monkeypatch):
    monkeypatch.setenv("GRACE_DISABLE_PALLAS_WIRE", "1")
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda: None)
    assert chip_smoke.main(["--chips", "4"]) == 1
    last = phase_lines(capsys)[-1]
    assert last["ok"] is False and "GRACE_DISABLE_PALLAS_WIRE" in last["error"]
