"""The busiest held expert's rows of the last traced step over the rows a
balanced router would send one expert, ``positions * experts a token /
experts routed over`` (640 in the cell): the largest entry, over the layers
and over the experts held here, of the ``drawn`` counters the program's
model state carries. 1 at a balanced router; the expert walk's longest run
of tiles follows it. Read in a program whose configuration states gated
delta layers (``pre_router_load_ratio`` and ``expert_load_ratio`` read
other programs); any other program, or a state without the counter, has
nothing to read."""

from benchmarks.layer_metrics.block_attention_kernel_roofline import sizes_of


def balanced_rows(sizes):
    """Rows a step would send each expert of a layer if the router spread
    them evenly."""
    return (sizes["per_chip_batch"] * sizes["seq_length"]
            * sizes["num_experts_per_tok"]
            / sizes["published"]["num_experts"])


def busiest_rows(ctx, sizes):
    state = getattr(ctx["program"].state, "model_state", None)
    layers = state.get("layers", []) if isinstance(state, dict) else []
    first = sizes.get("share", 0) * sizes["num_experts"]
    rows = [float(max(layer["drawn"][first:first + sizes["num_experts"]]))
            for layer in layers
            if isinstance(layer, dict) and "drawn" in layer]
    return max(rows) if rows else None


def read(ctx):
    sizes = sizes_of(ctx)
    if "linear_num_value_heads" not in sizes:
        return None
    rows = busiest_rows(ctx, sizes)
    return None if rows is None else rows / balanced_rows(sizes)
