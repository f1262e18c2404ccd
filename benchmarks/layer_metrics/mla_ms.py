"""Device self time per step of the operations under ``grace/mla_latent``
(latent attention's products around the scores: query projection, the
projection down to the latent and the shared rotary key, the latent's
norm, the projection up, the rotation, the shared key's broadcast and its
gradient's sum, the output projection) and ``grace/attention`` (what is
traced around the scores): forward, recomputation and backward alike.
**Without the fused kernel's own calls**: a Pallas call's ``op_name``
stands two lines below its instruction's name in the compiled text, where
the reducer reads one line, so it files them under ``unattributed``
(PERF.md section 7); they are the three largest ``breakdown.device_ops``
of the cell, and a change to the kernel alone does not move this metric.
A program without the ``grace/mla_latent`` stage has nothing to read.

Beside the reader, what the kernel's roofline share is computed from by
hand (PERF.md section 5): the operations and bytes of causal attention at
a shape, as the step runs it (forward, the forward's recomputation, one
fused backward)."""

MLA_STAGES = ("grace/mla_latent", "grace/attention")


def read(ctx):
    stages = ctx["reduced"]["stage_s_per_step"]
    if "grace/mla_latent" not in stages:
        return None
    return sum(stages.get(s, 0.0) for s in MLA_STAGES) * 1e3


def visited_share(seq_len, block_q, block_kv):
    """Share of the ``seq_len`` square a causal kernel with such tiles
    visits: every tile that holds a query at or after one of its keys."""
    if seq_len % block_q or seq_len % block_kv:
        raise ValueError("a sequence is whole tiles")
    tiles = sum(-(-(i + 1) * block_q // block_kv)
                for i in range(seq_len // block_q))
    return tiles * block_q * block_kv / seq_len ** 2


def attention_flops(sequences, layers, seq_len, heads, d_qk, d_v,
                    forwards=2, backwards=1, share=0.5):
    """Floating-point operations a step of causal attention over ``share``
    of the square: 0.5 is what the mathematics needs (the recomputed
    forward counted as the step runs it), :func:`visited_share` what a
    tiled kernel does."""
    # a tile's products, by the width they contract or produce: the forward
    # makes scores (d_qk) and multiplies them into the values (d_v); the
    # fused backward makes the scores again, then dp and dv (d_v), dq and dk
    # (d_qk)
    per_pair = 2 * (forwards * (d_qk + d_v)
                    + backwards * (3 * d_qk + 2 * d_v))
    return sequences * layers * heads * share * seq_len ** 2 * per_pair


def attention_bytes(sequences, layers, seq_len, heads, d_qk, d_v,
                    forwards=2, backwards=1, itemsize=2):
    """Bytes the calls read and write in HBM: a forward reads ``q``, ``k``,
    ``v`` and writes the output and a float32 log-sum-exp a query; the
    backward reads those five and the output's gradient and writes three
    gradients. Unpadded (the chip lays 192 lanes out as 256)."""
    qk, v = seq_len * heads * d_qk * itemsize, seq_len * heads * d_v * itemsize
    lse = seq_len * heads * 4
    forward = 2 * qk + 2 * v + lse
    backward = forward + v + 2 * qk + v
    return sequences * layers * (forwards * forward + backwards * backward)
