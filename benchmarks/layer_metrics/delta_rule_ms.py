"""Device self time per step of the operations under ``grace/delta_rule``:
the gated delta rule alone (the products within a chunk, the triangular
inverse, the scan over chunks that carries the state), forward, recomputed
and backward alike. A program without the stage has nothing to read."""

from benchmarks.layer_metrics.delta_rule_roofline import RULE_STAGE, stage_ms


def read(ctx):
    return stage_ms(ctx, RULE_STAGE)
