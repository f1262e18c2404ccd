"""Device time per step of the fused attention kernel's own calls under the
block-diffusion mask (``splash_mha_fwd_residuals.<n>`` and
``splash_mha_dkv_no_residuals.<n>``, one instruction a layer and kind),
summed over those found among the step's ten largest operations, where the
reducer leaves them ``unattributed`` (PERF.md section 7). A step that does
not train by block diffusion, or whose trace holds no such call (the plain
path), has nothing to read."""

from benchmarks.layer_metrics import block_attention_kernel_roofline as counts


def read(ctx):
    calls = counts.kernel_calls(ctx)
    if not calls:
        return None
    return sum(seconds for _, _, seconds in calls) * 1e3
