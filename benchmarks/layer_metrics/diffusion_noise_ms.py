"""Device self time per step of the operations under
``grace/diffusion_noise``: block-diffusion training's draws of a step (each
block's noise level, each token's mask), the noised copy and the loss's
weights, made on the device inside the step. A program without the stage
has nothing to read."""

STAGE = "grace/diffusion_noise"


def read(ctx):
    stages = ctx["reduced"]["stage_s_per_step"]
    if STAGE not in stages:
        return None
    return stages[STAGE] * 1e3
