"""The fused attention kernel's share of its roofline under the
block-diffusion mask, in per cent: the least time the chip could take for
the calls found (the larger of their operations over the bf16 peak and
their bytes over the HBM's rate, ``peaks.json``) over the time they took.

The kernel's calls (``splash_mha_fwd_residuals.<n>``,
``splash_mha_dkv_no_residuals.<n>``: one instruction a layer and kind, run
once a sequence) carry no stage in the reducer's table (PERF.md section 7),
so they are read by name from ``ctx["reduced"]["device_ops"]``, the ten
largest operations of the step. Operations and bytes are counted **per call
found and by its kind**, so a call that falls out of the ten lowers both
sides, and over **the pairs the mask allows**, not the tiles the kernel
visits: what the mathematics needs. A trace without such calls (the plain
path, a program without the kernel) has nothing to read.

Beside the reader, the counts themselves: the pairs a block-diffusion mask
allows, the tiles a kernel visits under it, a call's operations and bytes.
"""

import json
import os

KERNEL_PREFIX = "splash_mha_"
# a call's kind by its name: (forward passes, backward passes) it makes
KINDS = {"splash_mha_fwd": (1, 0), "splash_mha_dkv": (0, 1)}


def allowed_pairs(seq_len, block):
    """Pairs (query, key) the block-diffusion mask allows over the ``2 *
    seq_len`` positions of a doubled sequence: a noised query reads its own
    block's noised keys (``seq_len * block`` pairs) and the clean keys of
    the blocks before it, a clean query the clean keys up to its block's
    end; the two triangles together are a full square of blocks."""
    if seq_len % block:
        raise ValueError("a sequence is whole blocks")
    return seq_len * block + seq_len ** 2


def visited_tiles(seq_len, block, block_q, block_kv):
    """``(visited, all)`` tiles of ``block_q x block_kv`` over the doubled
    sequence's square: those that hold an allowed pair."""
    total = 2 * seq_len
    if total % block_q or total % block_kv or seq_len % block:
        raise ValueError("a sequence is whole tiles and whole blocks")

    def blocks(first, size):        # the blocks (of one copy) a tile spans
        first %= seq_len
        return first // block, (first + size - 1) // block

    visited = 0
    for q0 in range(0, total, block_q):
        for k0 in range(0, total, block_kv):
            q_clean, k_clean = q0 >= seq_len, k0 >= seq_len
            (q_lo, q_hi), (k_lo, k_hi) = blocks(q0, block_q), blocks(k0,
                                                                    block_kv)
            if not q_clean and not k_clean:
                visited += q_lo <= k_hi and k_lo <= q_hi
            elif not q_clean and k_clean:
                visited += k_lo < q_hi
            elif q_clean and k_clean:
                visited += k_lo <= q_hi
    return visited, (total // block_q) * (total // block_kv)


def kernel_flops(pairs, heads, d_qk, d_v, forwards=1, backwards=1):
    """Floating-point operations of the kernel over ``pairs`` (query, key)
    pairs a head of one sequence: a forward makes scores (``d_qk``) and
    multiplies them into the values (``d_v``); the fused backward makes the
    scores again, then ``dp`` and ``dv`` (``d_v``), ``dq`` and ``dk``
    (``d_qk``)."""
    per_pair = 2 * (forwards * (d_qk + d_v)
                    + backwards * (3 * d_qk + 2 * d_v))
    return heads * pairs * per_pair


def kernel_bytes(positions, q_heads, kv_heads, d_qk, d_v, forwards=1,
                 backwards=1, itemsize=2):
    """Bytes the calls read and write in HBM for one sequence of
    ``positions``: a forward reads ``q``, ``k``, ``v`` and writes the output
    and a float32 log-sum-exp a query and head; the backward reads those
    five and the output's gradient and writes the three gradients."""
    q = positions * q_heads * d_qk * itemsize
    k = positions * kv_heads * d_qk * itemsize
    v = positions * kv_heads * d_v * itemsize
    out = positions * q_heads * d_v * itemsize
    lse = positions * q_heads * 4
    forward = q + k + v + out + lse
    backward = forward + out + q + k + v
    return forwards * forward + backwards * backward


def sizes_of(ctx):
    """The program's configuration, or nothing to index."""
    return getattr(ctx["program"], "config", None) or {}


def kernel_calls(ctx):
    """``[(name, kind, seconds a step)]`` of the kernel's calls among the
    ten largest operations of a step that trains by block diffusion (its
    configuration states a block length); of any other step, none."""
    found = []
    if "block_length" not in sizes_of(ctx):
        return found
    for key, seconds in ctx["reduced"].get("device_ops", []):
        name = key.split("@")[0]
        kind = next((k for k in KINDS if name.startswith(k)), None)
        if kind is not None:
            found.append((name, kind, seconds))
    return found


def peaks_of(program):
    """The chip's peaks, or nothing where the device is not in the table (a
    rehearsal on the CPU)."""
    kind = program.mesh.devices.flat[0].device_kind
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "peaks.json")) as f:
        return json.load(f).get(kind)


def read(ctx):
    calls = kernel_calls(ctx)
    peaks = peaks_of(ctx["program"]) if calls else None
    if peaks is None:
        return None
    sizes = sizes_of(ctx)
    length, hq = sizes["seq_length"], sizes["num_attention_heads"]
    d = sizes["head_dim"]
    n = sizes["per_chip_batch"]    # a call is one layer's: every sequence
    pairs = allowed_pairs(length, sizes["block_length"])
    least = seconds = 0.0
    for _, kind, took in calls:
        fwd, bwd = KINDS[kind]
        flops = n * kernel_flops(pairs, hq, d, d, fwd, bwd)
        moved = n * kernel_bytes(2 * length, hq,
                                 sizes["num_key_value_heads"], d, d, fwd, bwd)
        least += max(flops / peaks["bf16_flops_per_s"],
                     moved / peaks["hbm_bytes_per_s"])
        seconds += took
    return 100.0 * least / seconds if seconds > 0 else None
