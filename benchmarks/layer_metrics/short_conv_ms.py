"""Device self time per step of the operations under ``grace/short_conv``
(the gated short convolution with its two projections): forward,
recomputation and backward alike. A program without the stage has nothing
to read."""


def read(ctx):
    seconds = ctx["reduced"]["stage_s_per_step"].get("grace/short_conv")
    return None if seconds is None else seconds * 1e3
