"""Device self time per step of a program's gated delta layers' operators:
the operations under ``grace/gated_delta`` (projections, the causal
convolution, the heads' norms and gates, the gated norm, the output
product) and those under ``grace/delta_rule`` nested inside (the rule
itself), summed; forward, recomputed and backward alike. A program without
either stage has nothing to read."""

from benchmarks.layer_metrics.delta_rule_roofline import (OPERATOR_STAGE,
                                                          RULE_STAGE,
                                                          stage_ms)


def read(ctx):
    return stage_ms(ctx, OPERATOR_STAGE, RULE_STAGE)
