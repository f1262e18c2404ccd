"""The fused attention kernel's share of its roofline in the output-gated
full-attention layers of a program that also has gated delta layers, in
per cent: the least time the chip could take for the calls found (the
larger of their operations over the bf16 peak and their bytes over the
HBM's rate, ``peaks.json``) over the time they took.

Read as ``full_attention_kernel_roofline`` is: the kernel's calls
(``splash_mha_fwd_residuals.<n>``, ``splash_mha_dkv_no_residuals.<n>``)
by name from the ten largest operations of the step, each one's stage from
its own ``op_name`` in the program's text (``grace/attention``), operations
and bytes **per call found and by its kind** from
``block_attention_kernel_roofline``'s counts at the configuration's heads
(256 | 256) over the pairs the causal mask allows. The gate is no part of
the kernel: it multiplies the kernel's output. A program without the
``grace/gated_delta`` stage, or a trace without such calls (the plain path,
or a head size the kernel does not take), has nothing to read.
"""

from benchmarks.layer_metrics.block_attention_kernel_roofline import (
    KINDS, kernel_bytes, kernel_flops, peaks_of, sizes_of)
from benchmarks.layer_metrics.delta_rule_roofline import OPERATOR_STAGE
from benchmarks.layer_metrics.window_attention_kernel_roofline import (
    FULL_STAGE, allowed_pairs, stage_of_call)


def kernel_calls(ctx):
    """``[(name, kind, seconds a step)]`` of the kernel's calls under
    ``grace/attention`` among the ten largest operations of a step whose
    program has gated delta layers; of any other step, none."""
    found = []
    if OPERATOR_STAGE not in ctx["reduced"].get("stage_s_per_step", {}):
        return found
    text = getattr(ctx["program"], "text", None)
    for key, seconds in ctx["reduced"].get("device_ops", []):
        name = key.split("@")[0]
        kind = next((k for k in KINDS if name.startswith(k)), None)
        if kind is not None and stage_of_call(text, name) == FULL_STAGE:
            found.append((name, kind, seconds))
    return found


def kernel_ms(ctx):
    calls = kernel_calls(ctx)
    if not calls:
        return None
    return sum(seconds for _, _, seconds in calls) * 1e3


def read(ctx):
    calls = kernel_calls(ctx)
    peaks = peaks_of(ctx["program"]) if calls else None
    if peaks is None:
        return None
    sizes = sizes_of(ctx)
    length, hq = sizes["seq_length"], sizes["num_attention_heads"]
    d = sizes["head_dim"]
    n = sizes["per_chip_batch"]    # a call is one layer's: every sequence
    pairs = allowed_pairs(length)
    least = seconds = 0.0
    for _, kind, took in calls:
        fwd, bwd = KINDS[kind]
        flops = n * kernel_flops(pairs, hq, d, d, fwd, bwd)
        moved = n * kernel_bytes(length, hq, sizes["num_key_value_heads"],
                                 d, d, fwd, bwd)
        least += max(flops / peaks["bf16_flops_per_s"],
                     moved / peaks["hbm_bytes_per_s"])
        seconds += took
    return 100.0 * least / seconds if seconds > 0 else None
