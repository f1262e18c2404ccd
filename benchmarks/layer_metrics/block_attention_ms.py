"""Device self time per step of the operations under ``grace/attention`` in
a step that trains by block diffusion (it has the ``grace/diffusion_noise``
stage): the projections, norms and rotations by position id around the
scores, head-major copies, the output projection, forward, recomputation
and backward alike. **Without the fused kernel's own calls**, which the
reducer files under ``unattributed`` (PERF.md section 7) and
``block_attention_kernel_ms`` reads by name. A program without the
diffusion stage has nothing to read."""

from benchmarks.layer_metrics import diffusion_noise_ms


def read(ctx):
    stages = ctx["reduced"]["stage_s_per_step"]
    if diffusion_noise_ms.STAGE not in stages:
        return None
    return stages.get("grace/attention", 0.0) * 1e3
