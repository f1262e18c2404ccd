"""The fused attention kernel's share of its roofline in the layers that
read the whole causal prefix, of a program that also has windowed layers:
as ``window_attention_kernel_roofline``, for the calls whose ``op_name``
stands under ``grace/attention``, over the pairs the causal mask allows. A
program without the window stage has nothing to read."""

from benchmarks.layer_metrics import window_attention_kernel_roofline as counts


def read(ctx):
    return counts.roofline(ctx, counts.FULL_STAGE)
