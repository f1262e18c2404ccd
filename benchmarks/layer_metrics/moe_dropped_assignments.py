"""Assignments of a token to an expert held here that the last traced step
did not compute, summed over the expert layers: the ``dropped`` counters
the program's model state carries. The expert layer bounds its grouped
products by the worst case, so anything but 0 is a fault."""

from benchmarks.layer_metrics.moe_held_assignments import counter


def read(ctx):
    return counter(ctx, "dropped")
