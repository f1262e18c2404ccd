"""Device self time per step of the operations under ``grace/attention`` in
a program that also has windowed layers (the ``grace/window_attention``
stage): the projections and copies around the scores of the layers that
read the whole prefix and are given no positions, without the fused
kernel's own calls (``full_attention_kernel_ms``). A program without the
window stage has nothing to read."""

from benchmarks.layer_metrics.window_attention_kernel_roofline import (
    FULL_STAGE, has_window_stage)


def read(ctx):
    if not has_window_stage(ctx):
        return None
    return ctx["reduced"]["stage_s_per_step"].get(FULL_STAGE, 0.0) * 1e3
