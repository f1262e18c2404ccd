"""The fused attention kernel's share of its roofline under the sliding
window's mask, in per cent: the least time the chip could take for the
windowed layers' calls found (the larger of their operations over the bf16
peak and their bytes over the HBM's rate, ``peaks.json``) over the time
they took.

The kernel's calls (``splash_mha_fwd_residuals.<n>``,
``splash_mha_dkv_no_residuals.<n>``: one instruction a layer and kind, run
once a sequence) carry no stage in the reducer's table (a Pallas call's
metadata stands lines below its name: PERF.md section 7), so they are read
by name from ``ctx["reduced"]["device_ops"]``, the ten largest operations
of the step, and **told apart by the stage in the instruction's own
``op_name``**, read instruction by instruction from the program's text:
``grace/window_attention`` for a layer that reads a window,
``grace/attention`` for one that reads the whole prefix. Operations and
bytes are counted **per call found and by its kind**, so a call that falls
out of the ten lowers both sides, and over **the pairs the mask allows**,
not the tiles the kernel visits: what the mathematics needs. A program
without the window stage, or a trace without such calls (the plain path),
has nothing to read.

Beside the reader, the counts themselves: the pairs a causal mask allows
with and without a window, the tiles a kernel visits under it; a call's
operations and bytes are ``block_attention_kernel_roofline``'s.
"""

import re

from benchmarks.layer_metrics.block_attention_kernel_roofline import (
    KINDS, kernel_bytes, kernel_flops, peaks_of, sizes_of)
from benchmarks.trace_reduce import stage_of

WINDOW_STAGE = "grace/window_attention"
FULL_STAGE = "grace/attention"


def allowed_pairs(seq_len, window=None):
    """Pairs (query, key) a causal mask allows over ``seq_len`` positions:
    query ``i`` reads the keys ``j`` with ``0 <= i - j``, and under a
    ``window`` only those with ``i - j < window``."""
    if seq_len <= 0 or (window is not None and window <= 0):
        raise ValueError("a sequence and a window hold a position at least")
    w = seq_len if window is None else min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def visited_tiles(seq_len, window, block_q, block_kv):
    """``(visited, all)`` tiles of ``block_q x block_kv`` over the
    sequence's square: those that hold an allowed pair."""
    if seq_len % block_q or seq_len % block_kv:
        raise ValueError("a sequence is whole tiles")
    visited = 0
    for q0 in range(0, seq_len, block_q):
        for k0 in range(0, seq_len, block_kv):
            # the tile's nearest pair: its last query, its first key or,
            # where the tile lies on the diagonal, a key at the query
            below = k0 <= q0 + block_q - 1
            near = max(q0 - (k0 + block_kv - 1), 0)
            visited += below and (window is None or near < window)
    return visited, (seq_len // block_q) * (seq_len // block_kv)


def has_window_stage(ctx):
    return WINDOW_STAGE in ctx["reduced"].get("stage_s_per_step", {})


def stage_of_call(text, name):
    """The stage of the compiled text's instruction ``name``: the rightmost
    ``grace/<stage>`` of the first ``op_name`` after the instruction's
    name, which for a Pallas call stands lines below it."""
    m = re.search(r"^\s*(?:ROOT\s+)?%?" + re.escape(name)
                  + r"\s*=.*?op_name=\"([^\"]*)\"", text or "", re.M | re.S)
    return stage_of(m.group(1)) if m else None


def kernel_calls(ctx, stage):
    """``[(name, kind, seconds a step)]`` of the kernel's calls under
    ``stage`` among the ten largest operations of a step whose program has
    the window stage; of any other step, none."""
    found = []
    if not has_window_stage(ctx):
        return found
    text = getattr(ctx["program"], "text", None)
    for key, seconds in ctx["reduced"].get("device_ops", []):
        name = key.split("@")[0]
        kind = next((k for k in KINDS if name.startswith(k)), None)
        if kind is not None and stage_of_call(text, name) == stage:
            found.append((name, kind, seconds))
    return found


def kernel_ms(ctx, stage):
    calls = kernel_calls(ctx, stage)
    if not calls:
        return None
    return sum(seconds for _, _, seconds in calls) * 1e3


def roofline(ctx, stage):
    """The share for the calls under ``stage``; a windowed layer's pairs
    under the configuration's window, a full layer's without."""
    calls = kernel_calls(ctx, stage)
    peaks = peaks_of(ctx["program"]) if calls else None
    if peaks is None:
        return None
    sizes = sizes_of(ctx)
    length, hq = sizes["seq_length"], sizes["num_attention_heads"]
    d = sizes["head_dim"]
    n = sizes["per_chip_batch"]    # a call is one layer's: every sequence
    pairs = allowed_pairs(
        length, sizes["sliding_window_size"] if stage == WINDOW_STAGE
        else None)
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


def read(ctx):
    return roofline(ctx, WINDOW_STAGE)
