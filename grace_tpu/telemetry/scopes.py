"""Named trace stages: attributable timings instead of anonymous XLA ops.

``utils.profiling.trace`` captures a Perfetto/TensorBoard device trace, but
without scope names the GRACE pipeline shows up as a soup of fusions and
``all-gather.N`` ops. :func:`trace_stage` wraps a pipeline stage in both:

* ``jax.named_scope`` — prepends the stage name to the XLA op name metadata,
  so *device-side* ops (the compress kernels, the collectives, the residual
  update) group under readable ``grace/…`` spans in the profiler; and
* ``jax.profiler.TraceAnnotation`` — emits a host-side TraceMe for the same
  span, so trace-time (and any eager host work) is attributable too.

Both are free at execution time: named_scope only rewrites op metadata
during tracing, and TraceAnnotation is a no-op unless a profiler session is
active. IMPORTANT for library code: the wrapped region must not capture
tracers across the context boundary in surprising ways — this is a plain
``contextmanager`` around pure tracing, not a transformation.
"""

from __future__ import annotations

import contextlib
from typing import Iterator

import jax

__all__ = ["trace_stage", "match_stage", "ALL_STAGES",
           "STAGE_COMPENSATE", "STAGE_COMPRESS",
           "STAGE_EXCHANGE", "STAGE_DECOMPRESS", "STAGE_MEMORY_UPDATE",
           "STAGE_FWD_BWD", "STAGE_OPTIMIZER", "STAGE_APPLY",
           "STAGE_TELEMETRY", "STAGE_DENSE_ESCAPE", "STAGE_CONSENSUS",
           "STAGE_RING_HOP", "STAGE_WATCH", "STAGE_BUCKET", "STAGE_ADAPT",
           "STAGE_PIPELINE", "STAGE_ATTENTION", "STAGE_SHORT_CONV",
           "STAGE_DENSE_FFN", "STAGE_MOE_ROUTER", "STAGE_MOE_DISPATCH",
           "STAGE_MOE_EXPERTS", "STAGE_MOE_COMBINE", "STAGE_LM_HEAD",
           "STAGE_MLA_LATENT", "STAGE_SHARED_EXPERT", "STAGE_DIFFUSION_NOISE",
           "STAGE_WINDOW_ATTENTION", "STAGE_GATED_DELTA", "STAGE_DELTA_RULE",
           "MODEL_STAGES"]

# Canonical stage names — one vocabulary for the profiler, the report tool,
# and the docs. Keep in sync with README "Observability".
STAGE_COMPENSATE = "grace/compensate"
STAGE_COMPRESS = "grace/compress"
STAGE_EXCHANGE = "grace/exchange"
STAGE_DECOMPRESS = "grace/decompress"
STAGE_MEMORY_UPDATE = "grace/memory_update"
STAGE_FWD_BWD = "grace/forward_backward"
STAGE_OPTIMIZER = "grace/optimizer"
STAGE_APPLY = "grace/apply_updates"
STAGE_TELEMETRY = "grace/telemetry"
STAGE_DENSE_ESCAPE = "grace/dense_escape"
STAGE_CONSENSUS = "grace/consensus"
# RingAllreduce reduce-scatter hops: each of the W-1 neighbor exchanges
# (ppermute + decompress + accumulate + requantize) renders as its own
# "grace/ring_hop/<s>" span, so per-hop cost is attributable in a trace.
STAGE_RING_HOP = "grace/ring_hop"
# graft-watch cross-rank health aggregation (telemetry/aggregate.py): the
# window-boundary all_gather of per-rank health vectors plus the summary
# math — one attributable span so its (tiny) cost never hides inside the
# telemetry scope it runs next to.
STAGE_WATCH = "grace/watch"
# Bucketed overlap executor (transform.py, fusion=<int bytes>): each
# bucket's full compensate→compress→exchange→decompress→memory-update
# chain renders as its own "grace/bucket/<b>" span, so a device trace
# shows bucket i's exchange overlapping bucket i+1's compression — the
# per-chain attribution the measured-vs-static overlap sandwich reads.
# The inner pipeline scopes nest inside it; match_stage's rightmost rule
# still attributes their ops to compress/exchange/… as before.
STAGE_BUCKET = "grace/bucket"
# graft-adapt in-graph controller (resilience/adapt.py): the per-step
# scalar signal reductions (pmean/pmax of the local compression error)
# plus the window-boundary rung decision — one attributable span, so the
# controller's (tiny) cost never hides inside the telemetry scope, and
# static findings against the ladder dispatch name this stage.
STAGE_ADAPT = "grace/adapt"
# Double-buffered wire pipeline (RingAllreduce/HierarchicalAllreduce with
# pipeline=P > 1): each of the P contiguous buffer segments runs the whole
# hop schedule under its own "grace/pipeline/<p>" span, so a device trace
# shows segment p's ppermute hops overlapping segment p+1's stage-1 encode
# — the per-segment attribution the static overlap pass (analysis/flow.py
# pass 5) reads to count independent collective chains. Inner hop scopes
# nest inside it; match_stage's rightmost rule still attributes their ops
# to ring_hop/exchange as before.
STAGE_PIPELINE = "grace/pipeline"
# The parts of a model's forward and backward pass (models/lfm2.py,
# models/deepseek_v3.py): they
# nest inside STAGE_FWD_BWD, and the rightmost rule attributes a part's
# forward, recomputed and backward operations to it, so what is left
# under "grace/forward_backward" is what no part names (embedding,
# residual adds). Lower case and underscores only: the benchmark's
# reducer reads ``grace/[a-z_]+``. The expert layer's products are plain
# ones and stand under "grace/moe_experts" (PERF.md, PR 37; until then they
# were ``lax.ragged_dot``s, which XLA runs as a kernel of its own naming,
# ``ragged-dot-none``, that keeps no scope).
STAGE_ATTENTION = "grace/attention"
STAGE_SHORT_CONV = "grace/short_conv"
STAGE_DENSE_FFN = "grace/dense_ffn"
STAGE_MOE_ROUTER = "grace/moe_router"
STAGE_MOE_DISPATCH = "grace/moe_dispatch"      # sort, gather
STAGE_MOE_EXPERTS = "grace/moe_experts"        # a tile's products, gates
STAGE_MOE_COMBINE = "grace/moe_combine"
STAGE_LM_HEAD = "grace/lm_head"                # final norm, head, loss
# Latent attention's products around the scores (models/deepseek_v3.py):
# the query projection, the projection down to the latent and the shared
# rotary key, the latent's norm, the projection up to every head's keys
# and values, the rotation, the shared key's broadcast and its gradient's
# sum over the heads, the output projection. The scores themselves stand
# under STAGE_ATTENTION, nested inside.
STAGE_MLA_LATENT = "grace/mla_latent"
# The expert every token passes and every chip of a layer computes whole.
STAGE_SHARED_EXPERT = "grace/shared_expert"
# Block-diffusion training's draws of a step (models/sdar.py): each block's
# noise level, each token's mask, the noised copy and the loss's weights.
STAGE_DIFFUSION_NOISE = "grace/diffusion_noise"
# Attention of a layer that reads a causal window of keys
# (models/smallthinker.py): its projections, rotation and copies and, through
# the kernel's ``op_name``, the fused kernel's calls under the window's mask.
# A layer of the same model that reads the whole prefix stands under
# STAGE_ATTENTION, so a trace tells the two kinds apart.
STAGE_WINDOW_ATTENTION = "grace/window_attention"
# A gated delta-rule layer (models/qwen3_next.py), in two stages. The
# operator around the rule: its projections, the causal depthwise
# convolution with its SiLU, the heads' l2 norms, the gates' ``beta`` and
# ``g``, the norm gated by ``silu(z)`` and the output product. And, nested
# inside, the rule itself alone, forward, recomputed and backward: the
# recurrence ``S <- exp(g) S + k (beta (v - S^T k))^T``, ``o = S^T q`` in its
# chunked form (the products within a chunk, the unit-lower-triangular
# inverse, the scan over chunks that carries the state), so that a trace
# says what the scan costs apart from the products around it.
STAGE_GATED_DELTA = "grace/gated_delta"
STAGE_DELTA_RULE = "grace/delta_rule"
MODEL_STAGES = (STAGE_ATTENTION, STAGE_SHORT_CONV, STAGE_DENSE_FFN,
                STAGE_MOE_ROUTER, STAGE_MOE_DISPATCH, STAGE_MOE_EXPERTS,
                STAGE_MOE_COMBINE, STAGE_LM_HEAD, STAGE_MLA_LATENT,
                STAGE_SHARED_EXPERT, STAGE_DIFFUSION_NOISE,
                STAGE_WINDOW_ATTENTION, STAGE_GATED_DELTA, STAGE_DELTA_RULE)

# The canonical stage vocabulary, longest-prefix-matchable: the profiler,
# tools/telemetry_report.py, and the static auditor's finding attribution
# (grace_tpu.analysis — findings name the stage whose scope the offending
# jaxpr equation was traced under) all share it. Keep sorted by length so
# "grace/exchange/psum_vote" attributes to STAGE_EXCHANGE, not a shorter
# accidental prefix.
ALL_STAGES = tuple(sorted(
    (STAGE_COMPENSATE, STAGE_COMPRESS, STAGE_EXCHANGE, STAGE_DECOMPRESS,
     STAGE_MEMORY_UPDATE, STAGE_FWD_BWD, STAGE_OPTIMIZER, STAGE_APPLY,
     STAGE_TELEMETRY, STAGE_DENSE_ESCAPE, STAGE_CONSENSUS, STAGE_RING_HOP,
     STAGE_WATCH, STAGE_BUCKET, STAGE_ADAPT, STAGE_PIPELINE, *MODEL_STAGES),
    key=len, reverse=True))


def match_stage(path: str) -> str:
    """The canonical stage a scope path / op name belongs to.

    Scope paths nest (``grace/optimizer/grace/exchange/grace/decompress``
    is a real jax name stack: the optimizer scope wraps the transform,
    which wraps the exchange, which wraps the decode), so the *rightmost*
    matching stage from :data:`ALL_STAGES` wins — the innermost scope is
    the one doing the work. Ties at the same position take the longest
    stage (``grace/exchange/psum_vote`` attributes to ``grace/exchange``,
    never a shorter accidental prefix). Falls back to the raw two-segment
    ``grace/<x>`` prefix for ad-hoc sub-scopes, and ``""`` for paths
    outside the grace vocabulary. ONE implementation shared by the static
    auditor's finding attribution (:mod:`grace_tpu.analysis`) and the
    profiler trace analyzer (:mod:`grace_tpu.profiling`) — both read the
    scope names :func:`trace_stage` wrote, so they must parse them
    identically.
    """
    best, best_pos = "", -1
    for stage in ALL_STAGES:            # longest-first: ties keep the longer
        pos = path.rfind(stage)
        if pos > best_pos:
            best, best_pos = stage, pos
    if best:
        return best
    segs = [seg for seg in path.split("/") if seg]
    if "grace" not in segs:
        return ""
    i = segs.index("grace")
    return "/".join(segs[i:i + 2])


@contextlib.contextmanager
def trace_stage(name: str) -> Iterator[None]:
    """Name a pipeline stage in both the XLA op metadata and host TraceMe."""
    anno = getattr(jax.profiler, "TraceAnnotation", None)
    with contextlib.ExitStack() as stack:
        stack.enter_context(jax.named_scope(name))
        if anno is not None:   # absent on exotic/old jax builds — degrade
            stack.enter_context(anno(name))
        yield
