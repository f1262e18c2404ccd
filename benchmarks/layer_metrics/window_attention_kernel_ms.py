"""Device time per step of the fused attention kernel's own calls in the
layers that read a window (``splash_mha_fwd_residuals.<n>`` and
``splash_mha_dkv_no_residuals.<n>`` whose ``op_name`` stands under
``grace/window_attention``), summed over those found among the step's ten
largest operations, where the reducer leaves them ``unattributed`` (PERF.md
section 7). A program without the window stage, or a trace without such a
call (the plain path), has nothing to read."""

from benchmarks.layer_metrics import window_attention_kernel_roofline as counts


def read(ctx):
    return counts.kernel_ms(ctx, counts.WINDOW_STAGE)
