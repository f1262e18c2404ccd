"""Device self time per step of the operations under
``grace/window_attention``: the windowed layers' projections, rotations and
head-major copies around the scores and their output projection, forward,
recomputation and backward alike. **Without the fused kernel's own
calls**, which the reducer files under ``unattributed`` (PERF.md section 7)
and ``window_attention_kernel_ms`` reads by name. A program without the
stage has nothing to read."""

from benchmarks.layer_metrics.window_attention_kernel_roofline import (
    WINDOW_STAGE, has_window_stage)


def read(ctx):
    if not has_window_stage(ctx):
        return None
    return ctx["reduced"]["stage_s_per_step"][WINDOW_STAGE] * 1e3
