"""How far the router has drifted towards the experts held here: the
assignments to held experts the last traced step computed (the expert
layers' ``held`` counters, summed) over what a balanced router would send
them, ``layers * positions * experts a token * experts held / experts
routed over``. 1 at a balanced router; a step's time follows it. Read in a
step that trains by block diffusion (its configuration states a block
length, and its sequences enter twice); any other program has nothing to
read."""

from benchmarks.layer_metrics import moe_held_assignments


def balanced_load(sizes):
    """Assignments a step would send the held experts of all layers if the
    router spread them evenly."""
    positions = sizes["per_chip_batch"] * 2 * sizes["seq_length"]
    return (sizes["num_hidden_layers"] * positions
            * sizes["num_experts_per_tok"] * sizes["num_experts"]
            / sizes["published"]["num_experts"])


def read(ctx):
    sizes = getattr(ctx["program"], "config", None) or {}
    held = moe_held_assignments.counter(ctx, "held")
    if held is None or "block_length" not in sizes:
        return None
    return held / balanced_load(sizes)
