"""Device self time per step of the operations under
``grace/shared_expert`` (the gated feed-forward every token passes beside
its routed experts, computed whole by every chip of a layer): forward,
recomputation and backward alike. A program without the stage has nothing
to read."""


def read(ctx):
    seconds = ctx["reduced"]["stage_s_per_step"].get("grace/shared_expert")
    return None if seconds is None else seconds * 1e3
