"""Device time per step of the fused attention kernel's own calls in the
output-gated full-attention layers (``op_name`` under ``grace/attention``)
of a program that also has gated delta layers: as
``gated_attention_kernel_roofline`` finds them. A program without the
``grace/gated_delta`` stage, or whose trace holds no such call, has nothing
to read."""

from benchmarks.layer_metrics import gated_attention_kernel_roofline as counts


def read(ctx):
    return counts.kernel_ms(ctx)
