"""The gated delta rule's share of its roofline, in per cent: the least
time the chip could take for the rule of a step (step 5 of a gated delta
layer alone: the recurrence ``S <- exp(g) S + k (beta (v - S^T k))^T``, ``o
= S^T q``, forward and backward) over the device time of the operations
under ``grace/delta_rule``.

The least time is, forward and backward each, the larger of the rule's
operations over the bf16 peak and its bytes over the HBM's rate
(``peaks.json``). Both are counted **from the mathematics and from nothing
that implements it**, so that plain XLA today and a kernel later are read
against the same work:

* operations, the recurrence's own: ``S^T k``, the rank-one update and
  ``S^T q`` are ``2 * d_k * d_v`` each a token and value head, ``6 * d_k *
  d_v`` forward, and twice that backward. No chunk length changes it: what
  a chunked form multiplies besides (the products within a chunk, the
  triangular inverse) is that form's own cost;
* bytes: ``q``, ``k`` (a key head), ``v``, ``g``, ``beta`` (a value head)
  read and ``o`` written once forward; those five and ``do`` read and the
  five gradients written once backward; activations at ``itemsize`` bytes,
  ``g`` and ``beta`` float32. The state never has to leave the chip.

A forward run again for recomputation is not counted again: it lowers the
share. The reader goes by stage, so it holds whatever implements the rule,
and since the stage's time holds all the rule's work and the counts hold
its least, the share cannot pass 100. A program without the stage, or a
device that is not in ``peaks.json`` (a rehearsal on the CPU), has nothing
to read.
"""

from benchmarks.layer_metrics.block_attention_kernel_roofline import (
    peaks_of, sizes_of)

RULE_STAGE = "grace/delta_rule"
OPERATOR_STAGE = "grace/gated_delta"


def rule_flops(tokens, value_heads, d_k, d_v, forwards=1, backwards=1):
    """Floating-point operations of the recurrence over ``tokens`` tokens
    of one layer: ``6 * d_k * d_v`` a token and value head forward, twice
    that backward."""
    return tokens * value_heads * 6 * d_k * d_v * (forwards + 2 * backwards)


def rule_bytes(tokens, key_heads, value_heads, d_k, d_v, forwards=1,
               backwards=1, itemsize=2):
    """Bytes the rule reads and writes in HBM over ``tokens`` tokens of one
    layer: forward ``q``, ``k``, ``v``, ``g``, ``beta`` in and ``o`` out;
    backward those five and ``do`` in and the five gradients out."""
    qk = 2 * key_heads * d_k * itemsize
    v = value_heads * d_v * itemsize
    gates = 2 * value_heads * 4
    operands = qk + v + gates
    return tokens * (forwards * (operands + v)
                     + backwards * (operands + v + operands))


def delta_layers(sizes):
    """How many of the layers held are gated delta layers."""
    held = sizes.get("layers_held", range(sizes["num_hidden_layers"]))
    return sum((i + 1) % sizes["full_attention_interval"] != 0 for i in held)


def least_seconds(sizes, peaks):
    """The least time the chip could take for one step's rule: forward and
    backward each bound by the slower of its operations and its bytes."""
    tokens = (sizes["per_chip_batch"] * sizes["seq_length"]
              * delta_layers(sizes))
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    return sum(
        max(rule_flops(tokens, hv, dk, dv, fwd, bwd)
            / peaks["bf16_flops_per_s"],
            rule_bytes(tokens, hk, hv, dk, dv, fwd, bwd)
            / peaks["hbm_bytes_per_s"])
        for fwd, bwd in ((1, 0), (0, 1)))


def stage_ms(ctx, *stages):
    """Device self time a step under ``stages``, summed, in ms; nothing
    where the trace holds none of them."""
    table = ctx["reduced"].get("stage_s_per_step", {})
    found = [table[s] for s in stages if s in table]
    return sum(found) * 1e3 if found else None


def read(ctx):
    took = stage_ms(ctx, RULE_STAGE)
    sizes = sizes_of(ctx)
    if not took or "linear_num_value_heads" not in sizes:
        return None
    peaks = peaks_of(ctx["program"])
    if peaks is None:
        return None
    return 100.0 * least_seconds(sizes, peaks) * 1e3 / took
