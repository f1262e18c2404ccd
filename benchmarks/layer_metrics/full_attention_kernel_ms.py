"""Device time per step of the fused attention kernel's own calls in the
layers that read the whole causal prefix (``op_name`` under
``grace/attention``), of a program that also has windowed layers: as
``window_attention_kernel_ms``. A program without the window stage has
nothing to read."""

from benchmarks.layer_metrics import window_attention_kernel_roofline as counts


def read(ctx):
    return counts.kernel_ms(ctx, counts.FULL_STAGE)
