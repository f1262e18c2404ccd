"""Device self time per step of the operations under the expert layer's
four stages (``grace/moe_router``, ``moe_dispatch``, ``moe_experts``,
``moe_combine``): forward, recomputation and backward alike. **Without
the grouped products themselves**: XLA runs ``lax.ragged_dot`` as kernels
it names ``ragged-dot-none``, which keep no ``op_name``, so the reducer
files them under ``unattributed`` (75.95 ms a step beside this metric's
86.93 on the chip, PERF.md section 5); a change to the grouped products
alone does not move this metric until the reducer attributes an operation
by the computation it sits in. A program without such a stage has nothing
to read."""

MOE_STAGES = ("grace/moe_router", "grace/moe_dispatch", "grace/moe_experts",
              "grace/moe_combine")


def read(ctx):
    stages = ctx["reduced"]["stage_s_per_step"]
    if not any(s in stages for s in MOE_STAGES):
        return None
    return sum(stages.get(s, 0.0) for s in MOE_STAGES) * 1e3
