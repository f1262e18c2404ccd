"""How far the router that reads the layer's input has drifted towards the
experts held here: the assignments to held experts the last traced step
computed (the expert layers' ``held`` counters, summed) over what a
balanced router would send them, ``layers * positions * experts a token *
experts held / experts routed over``. 1 at a balanced router; a step's
time follows it. Read in a program with the window stage
(``expert_load_ratio`` reads block-diffusion programs only); any other
program has nothing to read."""

from benchmarks.layer_metrics import moe_held_assignments
from benchmarks.layer_metrics.block_attention_kernel_roofline import sizes_of
from benchmarks.layer_metrics.window_attention_kernel_roofline import (
    has_window_stage)


def balanced_load(sizes):
    """Assignments a step would send the held experts of all layers if the
    router spread them evenly."""
    positions = sizes["per_chip_batch"] * sizes["seq_length"]
    return (sizes["num_hidden_layers"] * positions
            * sizes["moe_num_active_primary_experts"]
            * sizes["moe_num_primary_experts"]
            / sizes["published"]["moe_num_primary_experts"])


def read(ctx):
    held = moe_held_assignments.counter(ctx, "held")
    if held is None or not has_window_stage(ctx):
        return None
    return held / balanced_load(sizes_of(ctx))
