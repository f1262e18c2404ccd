"""Picoseconds of the transform's device self time a step (``grace_ms``'s
four stages) for each parameter the chip holds: what compression costs
where the model's own work is per token and the library's per parameter.
Like ``grace_ms`` it misses the transform's relayout loops, which carry no
``op_name`` (41.6 read against 149 with them, PERF.md section 5). A
program whose step has no such stage has nothing to read."""

import math

from benchmarks.trace_reduce import TRANSFORM_STAGES


def read(ctx):
    import jax

    if not any(s in ctx["reduced"]["stage_s_per_step"]
               for s in TRANSFORM_STAGES):
        return None
    held = sum(math.prod(p.shape) for p in
               jax.tree_util.tree_leaves(ctx["program"].state.params))
    return ctx["reduced"]["grace_s_per_step"] * 1e12 / held
