"""Device self time per step of the operations under the transform's own
stages (``grace/compensate``, ``compress``, ``decompress``,
``memory_update``). A cell whose program has no such operation (the dense
exchange) has nothing to read."""


from benchmarks.trace_reduce import TRANSFORM_STAGES


def read(ctx):
    if not any(s in ctx["reduced"]["stage_s_per_step"]
               for s in TRANSFORM_STAGES):
        return None
    return ctx["reduced"]["grace_s_per_step"] * 1e3
