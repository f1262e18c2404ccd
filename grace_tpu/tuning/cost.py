"""The tuner's documented cost model: wire-dominated step-time projection.

One pricing rule, stated once and stamped into every ``TUNE_LAST.json``:

    projected_step = base_compute_step + ici_bytes / ICI_BW
                     + dcn_bytes / DCN_BW + wan_bytes / WAN_BW

where ``(ici_bytes, dcn_bytes, wan_bytes)`` is
:meth:`Communicator.recv_link_bytes` under the *target*
:class:`~grace_tpu.core.Topology` — the same shared per-link wire model
the telemetry ring and the static auditor's wire-reconciliation pass
already agree on — and the bandwidth constants
are this module's own (ICI ~90 GB/s, DCN ~25 GB/s, WAN ~0.25 GB/s — the
documented cross-region model assumption): a PROJECTION MODEL, not a
measurement. What the chip measures is `PERF_LEDGER.jsonl`'s.

Why the legs are priced separately: a flat communicator's critical-path
rank receives every pipelined chunk over the worst boundary link the
moment the axis crosses it, so its whole bill lands on the ~3.6×-slower
DCN — or the ~100×-below-DCN WAN once the axis spans regions; the
hierarchical communicator's mixed split keeps the 2·k·(S−1)/S intra-slice
legs on ICI, ships (K/R−1)·k/S across DCN, and only (R−1) aggressively
re-coded shards across WAN. Collapsing the legs into one bandwidth erases
exactly the distinction the topology-aware selection exists to exploit
(ScaleCom's W-dependent topk degradation, EQuARX's per-topology tuning —
PAPERS.md).

Model limits (recorded in the evidence, enforced by the measured stage):

* **wire-dominated**: the static stage prices every candidate at the SAME
  base compute step — codec compute cost (topk selection, qsgd quantize,
  pallas fusion) is deliberately NOT modeled, because the repo's own
  chip history shows it is unpredictable from first principles (the
  staged qsgd path measured 42% slower than the kernel; chunk vs exact
  top-k is a 2× swing). That is what the measured shortlist is for.
* **no overlap** — with ONE declared exception: a double-buffered
  communicator (``pipeline=P`` on Ring/Hier, ISSUE 19) advertises its own
  ``wire_overlap_fraction()`` = ``WIRE_PIPELINE_EFFICIENCY · (P−1)/P``,
  and the wire leg is discounted by exactly that factor
  (``step = base + wire · (1 − overlap)``). The discount is honest
  because it is *statically refereed*: flow pass 5 requires the traced
  graph of a pipelined config to expose ≥ P independent
  compress→exchange chains before the config lints clean, so a
  communicator claiming the discount without the schedule to back it is
  a lint error, not an optimistic projection. Everything else keeps the
  NO-OVERLAP upper bound; the pass-5 static overlap bound still rides
  along per candidate as the honesty reference for the measured
  sandwich.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

# Projection model, not a measurement: per-chip bandwidths a projected
# step time is priced at.
ICI_RING_BYTES_PER_S = 9.0e10
DCN_BYTES_PER_S = 2.5e10
WAN_BYTES_PER_S = 2.5e8

PROJECTION_MODEL = {
    "constants_source": (
        "TPU v5e: 4 ICI links/chip in a 2D torus, ~45 GB/s per direction "
        "per link (cloud.google.com/tpu/docs/system-architecture-tpu-vm; "
        "jax-ml.github.io/scaling-book/ 'TPU networking'); a 1-D ring "
        "collective rides 2 links -> ~90 GB/s per chip. DCN ~25 GB/s/host "
        "(scaling-book cross-slice figure). WAN ~0.25 GB/s/host of "
        "sustained cross-region collective bandwidth — a MODEL ASSUMPTION "
        "(~100x below DCN), not a measurement."),
}


def projection_constants():
    """(ici_bytes_per_s, dcn_bytes_per_s, wan_bytes_per_s,
    projection_model_doc) — the ONE set of bandwidth assumptions."""
    return (ICI_RING_BYTES_PER_S, DCN_BYTES_PER_S, WAN_BYTES_PER_S,
            PROJECTION_MODEL)


@dataclasses.dataclass(frozen=True)
class TuneTopology:
    """The tuner's target mesh: dp world size + ICI slice width + optional
    region width and fsdp width (the 2-D sharded-model mesh).

    ``slice_size=None`` is a single ICI slice of any width (the regime
    every committed single-chip measurement ran in); ``W=256, slice8`` is
    the xslice projection topology; a third spec part adds the WAN tier
    (``1024,8,256`` = 4 regions of 256 ranks, 32 slices of 8 each).
    Parsed from the CLI's ``W`` / ``W,slice_size[,region_size]`` /
    ``dp×fsdp[,slice_size[,region_size]]`` spelling (``64x4,8`` = dp=64 ×
    fsdp=4, slices of 8). ``world`` is the EXCHANGE (dp) axis size — the
    span every wire/numeric model prices, because the compressed
    collective is the per-shard reduce over dp; ``fsdp`` multiplies the
    device count without widening any priced collective.
    """

    world: int
    slice_size: Optional[int] = None
    fsdp: Optional[int] = None
    region_size: Optional[int] = None

    def __post_init__(self):
        if self.world < 1:
            raise ValueError(f"world must be >= 1; got {self.world}")
        if self.slice_size is not None and self.slice_size < 1:
            raise ValueError(
                f"slice_size must be >= 1 or None; got {self.slice_size}")
        if self.fsdp is not None and self.fsdp < 1:
            raise ValueError(f"fsdp must be >= 1 or None; got {self.fsdp}")
        if self.region_size is not None and self.slice_size is None:
            raise ValueError(
                "region_size requires slice_size — the WAN tier nests "
                "outside the slice tier")
        if self.region_size is not None:
            # mirror core.Topology's tier-nesting contract at parse time,
            # so an impossible spec dies on the CLI, not mid-funnel
            if (self.region_size < 1
                    or self.region_size % self.slice_size != 0):
                raise ValueError(
                    f"region_size {self.region_size} must be a whole "
                    f"multiple of slice_size {self.slice_size} — regions "
                    "are made of whole slices")

    @classmethod
    def parse(cls, text: str) -> "TuneTopology":
        parts = [p.strip() for p in str(text).split(",") if p.strip()]
        if not parts or len(parts) > 3:
            raise ValueError(
                f"topology spec {text!r} is not 'W', "
                "'W,slice_size[,region_size]', or "
                "'DPxFSDP[,slice_size[,region_size]]'")
        head = parts[0].lower().replace("×", "x")
        if "x" in head:
            dp_s, fsdp_s = head.split("x", 1)
            world, fsdp = int(dp_s), int(fsdp_s)
        else:
            world, fsdp = int(head), None
        slice_size = int(parts[1]) if len(parts) >= 2 else None
        region_size = int(parts[2]) if len(parts) == 3 else None
        return cls(world=world, slice_size=slice_size, fsdp=fsdp,
                   region_size=region_size)

    def core_topology(self):
        from grace_tpu.core import Topology
        return Topology(slice_size=self.slice_size,
                        region_size=self.region_size)

    @property
    def devices(self) -> int:
        """Total device count: dp × fsdp."""
        return self.world * (self.fsdp or 1)

    @property
    def label(self) -> str:
        w = (f"W{self.world}" if self.fsdp is None
             else f"W{self.world}x{self.fsdp}")
        if self.slice_size is None:
            return w
        if self.region_size is None:
            return f"{w}/slice{self.slice_size}"
        return f"{w}/slice{self.slice_size}/region{self.region_size}"


def dense_bytes(model_structs) -> int:
    """Dense gradient bytes of a param pytree (structs or arrays)."""
    import jax
    import numpy as np

    return int(sum(
        int(np.prod(l.shape, dtype=np.int64)) * np.dtype(l.dtype).itemsize
        for l in jax.tree_util.tree_leaves(model_structs)))


def n_elements(model_structs) -> int:
    import jax
    import numpy as np

    return int(sum(int(np.prod(l.shape, dtype=np.int64))
                   for l in jax.tree_util.tree_leaves(model_structs)))


def price_candidate(grace, model_structs, spec: TuneTopology, *,
                    base_step_s: float = 0.0,
                    dense_step_s: Optional[float] = None) -> Dict[str, Any]:
    """One candidate's static price under the target topology.

    ``base_step_s`` is the compute-side step time assumed for EVERY
    candidate (0.0 = pure wire ranking; the measured stage replaces it
    with each candidate's own timed step); ``dense_step_s`` defaults to
    the same value so the speedup ratio stays like-for-like. Dense rides
    a ring allreduce priced through the identical shared model.
    """
    from grace_tpu.comm import Allreduce
    from grace_tpu.utils import wire_report

    ici_bw, dcn_bw, wan_bw, _ = projection_constants()
    dense_step_s = base_step_s if dense_step_s is None else dense_step_s
    rep = wire_report(grace.compressor, model_structs)
    n = n_elements(model_structs)
    dense_b = dense_bytes(model_structs)
    vote = bool(getattr(grace.compressor, "vote_aggregate", False))
    topo = spec.core_topology()
    link = grace.communicator.recv_link_bytes(
        rep.wire_bytes, n, spec.world, topology=topo, vote=vote)
    # Shared-scale negotiation collectives, priced honestly into the wire
    # bill (Compressor.negotiation_nbytes × one negotiate per compress
    # call of the fusion plan; 0 for every other codec). The pmax is a
    # flat full-axis collective, so — like the watch gather — it rides ICI
    # within one slice and DCN the moment the axis crosses slices.
    import jax

    from grace_tpu.transform import fusion_payload_structs

    n_calls = sum(count for _, count in fusion_payload_structs(
        jax.tree_util.tree_leaves(model_structs), grace.fusion))
    neg_b = n_calls * int(grace.compressor.negotiation_nbytes(spec.world))
    if neg_b:
        # Flat full-axis collective: priced on the worst tier the axis
        # spans — the same flat_tier rule the telemetry fold uses.
        tier = topo.flat_tier(spec.world)
        link = link._replace(**{tier: getattr(link, tier) + neg_b})
    dense_link = Allreduce(
        axis_name=grace.communicator.axis_name).recv_link_bytes(
            dense_b, n, spec.world, topology=topo)

    def wire_s(lb):
        return lb.ici / ici_bw + lb.dcn / dcn_bw + lb.wan / wan_bw

    # wire_pipeline discount: the communicator's OWN declared overlap
    # fraction (0.0 everywhere except the double-buffered ring/hier
    # schedules, whose claim flow pass 5 referees statically — see the
    # module docstring's model-limits note). Dense always rides the flat
    # undiscounted psum bracket.
    overlap = float(getattr(grace.communicator, "wire_overlap_fraction",
                            lambda: 0.0)())
    step_s = base_step_s + wire_s(link) * (1.0 - overlap)
    d_step_s = dense_step_s + wire_s(dense_link)
    adapt = getattr(grace, "adapt", None)
    extra: Dict[str, Any] = {}
    if adapt is not None:
        # graft-adapt candidates are priced at their STEADY STATE — the
        # top rung IS the base compressor (normalize_adapt's contract),
        # so the headline projected_step_ms above is exactly the static
        # top-rung config's: a quiet adaptive run matches the
        # hand-picked winner's projected throughput by construction.
        # The full rung schedule rides along so the funnel record shows
        # what each degradation level costs — the transparency the
        # "price adaptive candidates by their rung schedule" contract
        # asks for.
        extra = {
            "steady_state_rung": len(adapt.ladder),
            "rung_prices": adapt_rung_prices(grace, model_structs, spec,
                                             base_step_s=base_step_s),
        }
    return {
        **extra,
        "payload_bytes": int(rep.wire_bytes),
        "wire_ratio": round(rep.wire_bytes / max(1, dense_b), 6),
        "negotiation_bytes": int(neg_b),
        "ici_bytes": int(link.ici),
        "dcn_bytes": int(link.dcn),
        "wan_bytes": int(link.wan),
        "wire_ms": round(wire_s(link) * 1e3, 9),
        "wire_pipeline_overlap": round(overlap, 6),
        "dense_ici_bytes": int(dense_link.ici),
        "dense_dcn_bytes": int(dense_link.dcn),
        "dense_wan_bytes": int(dense_link.wan),
        "dense_wire_ms": round(wire_s(dense_link) * 1e3, 9),
        "projected_step_ms": round(step_s * 1e3, 9),
        "dense_projected_step_ms": round(d_step_s * 1e3, 9),
        "predicted_speedup_vs_dense": round(d_step_s / step_s, 4)
        if step_s > 0 else None,
    }


def adapt_rung_prices(grace, model_structs, spec: TuneTopology, *,
                      base_step_s: float = 0.0):
    """Static per-rung prices of a graft-adapt candidate's whole
    degradation ladder: rung 0 is the dense escape psum (the same
    Allreduce pricing the dense bracket uses, at the escape codec's
    payload width), rung r >= 1 the ladder codec through the candidate's
    own communicator — each through the identical shared per-link model,
    so the controller's state-dependent wire bill is an enumerated fact
    in the funnel record, not a surprise at run time."""
    from grace_tpu.comm import Allreduce
    from grace_tpu.utils import wire_report

    ici_bw, dcn_bw, wan_bw, _ = projection_constants()
    n = n_elements(model_structs)
    topo = spec.core_topology()

    def wire_s(lb):
        return lb.ici / ici_bw + lb.dcn / dcn_bw + lb.wan / wan_bw

    out = []
    esc = getattr(grace, "escape", None)
    esc_b = (wire_report(esc, model_structs).wire_bytes
             if esc is not None else dense_bytes(model_structs))
    link0 = Allreduce(
        axis_name=grace.communicator.axis_name).recv_link_bytes(
            esc_b, n, spec.world, topology=topo)
    out.append({"rung": 0,
                "codec": (type(esc).__name__ if esc is not None
                          else "dense"),
                "payload_bytes": int(esc_b),
                "ici_bytes": int(link0.ici), "dcn_bytes": int(link0.dcn),
                "wan_bytes": int(link0.wan),
                "projected_step_ms": round(
                    (base_step_s + wire_s(link0)) * 1e3, 9)})
    for ri, comp in enumerate(grace.adapt.ladder, start=1):
        rep = wire_report(comp, model_structs)
        vote = bool(getattr(comp, "vote_aggregate", False))
        link = grace.communicator.recv_link_bytes(
            rep.wire_bytes, n, spec.world, topology=topo, vote=vote)
        neg = int(comp.negotiation_nbytes(spec.world))
        out.append({"rung": ri, "codec": type(comp).__name__,
                    "payload_bytes": int(rep.wire_bytes),
                    "negotiation_bytes": neg,
                    "ici_bytes": int(link.ici),
                    "dcn_bytes": int(link.dcn),
                    "wan_bytes": int(link.wan),
                    "projected_step_ms": round(
                        (base_step_s + wire_s(link)) * 1e3, 9)})
    return out
