"""String-keyed factory: the `grace_from_params` compatibility surface.

Reference: grace_dl/dist/helper.py:1-86 (and the torch/tf twins). The params
dict schema is preserved so reference users can port configs verbatim:
``compressor`` / ``memory`` / ``communicator`` selectors plus per-algorithm
hyperparameters (``compress_ratio``, ``quantum_num``, ``threshold``,
``momentum``, ``gradient_clipping``, ``compress_rank``, ``lr``). Differences:

* ``world_size`` is accepted and ignored — world size is a property of the
  device mesh, not configuration.
* ``axis_name`` selects the mesh axis (default ``'data'``).
* The reference's latent Broadcast bug (helper.py:84 omits the required
  ``rank`` ctor arg → TypeError) has no analog: broadcast needs no rank here.
* Returns a :class:`Grace` bundle with ``.transform(seed)`` (optax) instead
  of a stateful Communicator object.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import optax

from grace_tpu import comm
from grace_tpu import compressors as C
from grace_tpu import memories as M
from grace_tpu.core import (DEFAULT_AXIS, Communicator, Compressor,
                            LinkBytes, Memory, Topology,
                            negotiation_bytes_for)
from grace_tpu.telemetry import host
from grace_tpu.transform import MeshSpec, grace_transform, leaf_path_str, \
    normalize_routes, route_for


@dataclasses.dataclass(frozen=True)
class Grace:
    """Bundle of the configured triad; the `grc` object of reference examples."""

    compressor: Compressor
    memory: Memory
    communicator: Communicator
    fusion: Any = None   # None | 'flat' | 'grouped' | bucket bytes
                         # (see grace_transform)
    escape: Any = None   # None | dense Compressor: the resilience escape
                         # hatch (see grace_transform / resilience.guard)
    telemetry: Any = None  # None | True | capacity | dict | TelemetryConfig:
                           # in-graph telemetry ring (grace_tpu.telemetry)
    consensus: Any = None  # None | True | audit_every | dict |
                           # ConsensusConfig: cross-rank consistency audit
                           # (grace_tpu.resilience.consensus). Arms the
                           # AuditState here; pass the same value to
                           # make_train_step(consensus=...) for the hook.
    topology: Any = None   # None | core.Topology: the mesh link layout the
                           # telemetry ring prices its per-link wire split
                           # with (wire_bytes_ici/wire_bytes_dcn). None =
                           # Topology.detect() at wire-plan time; set from
                           # params["slice_size"] by grace_from_params.
    watch: Any = None      # None | True | window | dict | WatchConfig:
                           # graft-watch in-graph cross-rank health
                           # aggregation (grace_tpu.telemetry.aggregate);
                           # requires telemetry.
    mesh: Any = None       # None | axis str | transform.MeshSpec: the mesh
                           # layout (dp axis + optional fsdp axis for the
                           # sharded-model track). Set from
                           # params["fsdp_axis"] by grace_from_params.
    routes: Tuple = ()     # normalized ((pattern, compressor, memory,
                           # communicator), ...): first-class per-leaf
                           # codec routing — embeddings ride aggressive
                           # sparsification while LayerNorm/bias leaves
                           # ride dense/fp16. Set from params["route"].
    adapt: Any = None      # None | resilience.adapt.AdaptConfig with the
                           # BUILT rung compressors (base codec as the
                           # top rung): the graft-adapt in-graph
                           # degradation ladder. Set from
                           # params["adapt"]; requires escape+telemetry.
                           # Stored normalized so the static auditor and
                           # the tuner enumerate the same rungs the
                           # transform dispatches over.

    def transform(self, seed: int = 0) -> optax.GradientTransformation:
        return grace_transform(self.compressor, self.memory,
                               self.communicator, seed=seed,
                               fusion=self.fusion, escape=self.escape,
                               telemetry=self.telemetry,
                               consensus=self.consensus,
                               topology=self.topology,
                               watch=self.watch,
                               mesh=self.mesh,
                               routes=self.routes or None,
                               adapt=self.adapt)


def _pad_powersgd_states(base: Compressor, rungs: Tuple[Compressor, ...]
                         ) -> Tuple[Compressor, Tuple[Compressor, ...]]:
    """Rung-invariant PowerSGD layout for an adapt ladder: every PowerSGD
    codec among the rungs AND the base (the base is the ladder's top rung,
    and the transform allocates comp state from it) gets ``state_rank``
    pinned to the ladder's max rank, so all rungs thread one padded
    ``(m, max_rank)`` Q structure through the adapt ``lax.switch``.
    No-op for ladders without PowerSGD, and for single-entry "ladders"
    (base only, no rungs) where padding buys nothing."""
    ps = [c for c in (*rungs, base)
          if isinstance(c, C.PowerSGDCompressor)]
    if not ps or not rungs:
        return base, tuple(rungs)
    pad = max(c.state_rank or c.rank for c in ps)

    def fix(c):
        if isinstance(c, C.PowerSGDCompressor) and c.state_rank != pad:
            return dataclasses.replace(c, state_rank=pad)
        return c

    return fix(base), tuple(fix(c) for c in rungs)


def _build_compressor(params: Dict[str, Any], axis: str) -> Compressor:
    name = params.get("compressor", "none")
    ratio = params.get("compress_ratio", 0.3)
    if name == "none":
        return C.NoneCompressor()
    if name in ("fp16", "bf16", "bfloat16"):
        return C.FP16Compressor(dtype="float16" if name == "fp16" else "bfloat16")
    if name == "cyclictopk":
        # ScaleCom-style cyclic Top-K: one shared k-index set per step,
        # derived from the replicated rng + step (rank-deterministic,
        # data-free ctx), so the payload is exactly summable
        # (payload_algebra='exact') — the large-W fix for per-rank topk's
        # degradation cliff, with zero negotiation bytes.
        return C.CyclicTopKCompressor(compress_ratio=ratio)
    if name == "topk":
        return C.TopKCompressor(
            compress_ratio=ratio,
            algorithm=params.get("topk_algorithm", "exact"),
            recall_target=params.get("recall_target", 0.95),
            wire_dtype=params.get("wire_dtype", "float32"),
            use_pallas=params.get("use_pallas", "auto"))
    if name == "randomk":
        return C.RandomKCompressor(compress_ratio=ratio)
    if name == "threshold":
        return C.ThresholdCompressor(
            threshold=params.get("threshold", 0.01),
            capacity_ratio=params.get("capacity_ratio", 0.25))
    if name == "qsgd":
        return C.QSGDCompressor(quantum_num=params.get("quantum_num", 64),
                                use_pallas=params.get("use_pallas", "auto"))
    if name == "homoqsgd":
        # Shared-scale homomorphic QSGD (payload_algebra='shared_scale'):
        # quantum_num defaults to the 4-bit qsgd4 family; accum_dtype sizes
        # the integer payload for exact W-rank sums.
        return C.HomoQSGDCompressor(
            quantum_num=params.get("quantum_num", 7),
            accum_dtype=params.get("accum_dtype", "int16"),
            accum_bits=params.get("accum_bits"),
            use_pallas=params.get("use_pallas", "auto"))
    if name == "countsketch":
        return C.CountSketchCompressor(
            compress_ratio=params.get("compress_ratio", 0.25),
            rows=params.get("sketch_rows", 3))
    if name == "terngrad":
        return C.TernGradCompressor()
    if name == "signsgd":
        return C.SignSGDCompressor(use_pallas=params.get("use_pallas",
                                                         "auto"))
    if name == "signum":
        return C.SignumCompressor(momentum=params.get("momentum", 0.9),
                                  use_pallas=params.get("use_pallas",
                                                        "auto"))
    if name == "efsignsgd":
        return C.EFSignSGDCompressor(lr=params.get("lr", 0.1))
    if name == "onebit":
        return C.OneBitCompressor()
    if name == "natural":
        return C.NaturalCompressor()
    if name == "dgc":
        return C.DgcCompressor(compress_ratio=params.get("compress_ratio", 0.01))
    if name == "powersgd":
        return C.PowerSGDCompressor(rank=params.get("compress_rank", 1),
                                    axis_name=axis)
    if name == "u8bit":
        return C.U8bitCompressor()
    if name == "sketch":
        return C.SketchCompressor(bins=params.get("quantum_num", 256))
    if name == "adaq":
        return C.AdaqCompressor(compress_ratio=params.get("compress_ratio", 0.01))
    if name == "inceptionn":
        return C.InceptionNCompressor()
    raise ValueError(f"unknown compressor {name!r}")


def _build_memory(params: Dict[str, Any], axis: str) -> Memory:
    name = params.get("memory", "none")
    if name == "none":
        return M.NoneMemory()
    if name == "residual":
        return M.ResidualMemory(beta=params.get("beta", 1.0),
                                gamma=params.get("gamma", 1.0),
                                state_dtype=params.get("memory_dtype"))
    if name == "efsignsgd":
        return M.EFSignSGDMemory(lr=params.get("lr", 0.1))
    if name == "dgc":
        return M.DgcMemory(momentum=params.get("momentum", 0.9),
                           gradient_clipping=params.get("gradient_clipping",
                                                        False),
                           axis_name=axis)
    if name == "powersgd":
        return M.PowerSGDMemory()
    raise ValueError(f"unknown memory {name!r}")


def _build_communicator(params: Dict[str, Any], axis: str) -> Communicator:
    name = params.get("communicator", "allgather")
    if name == "allreduce":
        return comm.Allreduce(
            axis_name=axis,
            vote_dtype=params.get("vote_dtype", "bfloat16"))
    if name == "allgather":
        return comm.Allgather(axis_name=axis)
    if name == "broadcast":
        return comm.Broadcast(axis_name=axis)
    if name in ("twoshot", "twoshot_allreduce"):
        return comm.TwoShotAllreduce(
            axis_name=axis,
            stage2_feedback=bool(params.get("stage2_feedback", False)))
    if name in ("ring", "ring_allreduce"):
        # pipeline: double-buffered wire schedule — P > 1 splits the flat
        # buffer into P segments whose ring schedules trace as independent
        # chains (flow pass 5's pipelined-ring referee), letting hop k of
        # segment p overlap hop k+1 of segment p-1 on real links.
        return comm.RingAllreduce(axis_name=axis,
                                  pipeline=int(params.get("pipeline", 1)))
    if name in ("rscatter", "reduce_scatter", "rscatter_allreduce"):
        # Compressed reduce-scatter + all-gather over the dp axis: the
        # sharded-model (FSDP) exchange — one all_to_all instead of the
        # ring's W−1 hops; payload-space sums for exact/homomorphic
        # codecs, exactly ONE requant boundary for the rest.
        return comm.ReduceScatterAllreduce(axis_name=axis)
    if name in ("hier", "hierarchical", "hier_allreduce"):
        # slice_size: ranks [k*S, (k+1)*S) form one ICI slice; the
        # two-level ICI×DCN schedule (intra-slice ring reduce-scatter,
        # cross-slice partial exchange, intra-slice all-gather). None
        # collapses to the flat ring (one slice). region_size adds the
        # third (WAN) level; wan_compressor is a nested params dict
        # naming the aggressive cross-region codec.
        wan_params = params.get("wan_compressor")
        wan = (_build_compressor(dict(wan_params), axis)
               if isinstance(wan_params, dict) else None)
        return comm.HierarchicalAllreduce(
            axis_name=axis, slice_size=params.get("slice_size"),
            region_size=params.get("region_size"),
            wan_compressor=wan,
            pipeline=int(params.get("pipeline", 1)))
    if name in ("sign_allreduce", "signallreduce"):
        return comm.SignAllreduce(
            axis_name=axis,
            vote_dtype=params.get("vote_dtype", "bfloat16"))
    if name in ("identity", "none"):
        return comm.Identity(axis_name=axis)
    raise ValueError(f"unknown communicator {name!r}")


@host.spanned("grace_from_params")
def grace_from_params(params: Dict[str, Any]) -> Grace:
    """Configure the triad from the reference's params-dict schema.

    ``fusion`` (None | 'flat' | 'grouped' | int bytes) is a grace-tpu
    extension with no reference analog in the params dict — Horovod's fusion
    buffer was a buried env knob (HOROVOD_FUSION_THRESHOLD); here it is
    first-class.

    ``fsdp_axis`` (grace-tpu extension): name of the mesh axis params and
    optimizer state shard over — declares the 2-D dp×fsdp sharded-model
    layout (:class:`grace_tpu.transform.MeshSpec`); the communicator's
    exchange stays the per-shard reduce over ``axis_name``.

    ``adapt`` (grace-tpu extension): the graft-adapt in-graph adaptive
    compression controller (:mod:`grace_tpu.resilience.adapt`). ``True``
    / int ``window`` / dict of :class:`AdaptConfig` kwargs where
    ``ladder`` is a list of *override dicts* — each merged over this
    config's own params (minus adapt/route) and built into a rung codec,
    safest first; this config's own compressor is always the top
    (steady-state) rung and the dense escape is rung 0. Requires
    ``escape`` and ``telemetry``. Example — a homoqsgd bit-width ladder
    (dense → 8-bit → 4-bit)::

        {"compressor": "homoqsgd", "quantum_num": 7,
         "memory": "residual", "communicator": "ring", "fusion": "flat",
         "escape": "fp16", "telemetry": True,
         "adapt": {"window": 20, "ladder": [{"quantum_num": 127}]}}

    ``route`` (grace-tpu extension): ``[(pattern, overrides), ...]`` —
    first-class per-leaf codec routing. Each ``overrides`` dict is merged
    over this config's own params (minus the route itself) and built into
    a full sub-triad; ``pattern`` is an fnmatch glob matched against the
    gradient leaf's ``"/"``-joined tree path. First match wins; unmatched
    leaves ride the base triad. Example — transformer routing::

        {"compressor": "topk", "compress_ratio": 0.01,
         "topk_algorithm": "chunk", "memory": "residual",
         "communicator": "rscatter",
         "route": [("*ln*", {"compressor": "fp16",
                             "communicator": "allreduce",
                             "memory": "none"}),
                   ("*bias*", {"compressor": "fp16",
                               "communicator": "allreduce",
                               "memory": "none"})]}
    """
    axis = params.get("axis_name", DEFAULT_AXIS)
    fusion = params.get("fusion")
    if fusion in ("none", "None", ""):   # CLI-style spelling of "no fusion"
        fusion = None
    escape = params.get("escape")
    if isinstance(escape, str):
        if escape in ("none", "dense"):
            escape = C.NoneCompressor()
        elif escape in ("fp16", "bf16", "bfloat16"):
            escape = C.FP16Compressor(
                dtype="float16" if escape == "fp16" else "bfloat16")
        else:
            raise ValueError(f"unknown escape compressor {escape!r} — use "
                             "'none'/'dense', 'fp16', or 'bf16'")
    # slice_size/region_size also declare the mesh link layout: the
    # telemetry ring's per-link wire split (wire_bytes_ici/dcn/wan)
    # prices against the Topology they imply. Without them the layout is
    # auto-detected (Topology.detect) — single slice on CPU/simulated
    # meshes.
    slice_size = params.get("slice_size")
    region_size = params.get("region_size")
    fsdp_axis = params.get("fsdp_axis")
    mesh = (MeshSpec(dp_axis=axis, fsdp_axis=str(fsdp_axis))
            if fsdp_axis else None)
    routes: Tuple = ()
    if params.get("route"):
        sub_entries = []
        for entry in params["route"]:
            pattern, overrides = entry
            merged = {k: v for k, v in params.items() if k != "route"}
            # Route overrides REPLACE the base codec selection wholesale:
            # inheriting e.g. the base compress_ratio under an fp16
            # override is fine, but a leftover base "compressor" key must
            # not survive an override that names its own.
            merged.update(dict(overrides))
            sub_entries.append((str(pattern), grace_from_params(merged)))
        routes = normalize_routes(
            sub_entries, _build_communicator(params, axis))
    compressor = _build_compressor(params, axis)
    adapt_cfg = None
    if params.get("adapt"):
        from grace_tpu.resilience.adapt import AdaptConfig, normalize_adapt

        spec = params["adapt"]
        if isinstance(spec, AdaptConfig):
            compressor, ladder = _pad_powersgd_states(
                compressor, tuple(spec.ladder))
            if ladder != tuple(spec.ladder):
                spec = dataclasses.replace(spec, ladder=ladder)
            adapt_cfg = normalize_adapt(spec, compressor)
        else:
            if spec is True:
                kwargs: Dict[str, Any] = {}
            elif isinstance(spec, int):
                kwargs = {"window": spec}
            elif isinstance(spec, dict):
                kwargs = dict(spec)
            else:
                raise TypeError(
                    f"adapt must be True/int/dict/AdaptConfig; got "
                    f"{type(spec).__name__}")
            # Ladder entries are override dicts merged over this config's
            # own params (the route idiom): each builds one rung codec,
            # safest first; the base codec becomes the top rung.
            rungs = []
            for overrides in kwargs.pop("ladder", ()):
                merged = {k: v for k, v in params.items()
                          if k not in ("adapt", "route")}
                merged.update(dict(overrides))
                rungs.append(_build_compressor(merged, axis))
            # Rung-invariant PowerSGD layout: every PowerSGD codec in
            # this ladder (base included — it IS the top rung, and the
            # transform's comp state is allocated from the Grace
            # compressor) stores Q at the ladder's max rank so the adapt
            # lax.switch threads ONE state structure across rungs.
            compressor, rungs = _pad_powersgd_states(
                compressor, tuple(rungs))
            adapt_cfg = normalize_adapt(
                AdaptConfig(ladder=rungs, **kwargs), compressor)
    return Grace(compressor=compressor,
                 memory=_build_memory(params, axis),
                 communicator=_build_communicator(params, axis),
                 fusion=fusion,
                 escape=escape,
                 mesh=mesh,
                 routes=routes,
                 topology=(Topology(
                     slice_size=int(slice_size) if slice_size else None,
                     region_size=int(region_size) if region_size else None)
                           if (slice_size or region_size) else None),
                 # True | ring capacity | {"capacity": ..,
                 # "compression_error": ..} — see grace_transform(telemetry=)
                 telemetry=params.get("telemetry"),
                 # True | audit_every | {"audit_every": .., "escalate_*": ..}
                 # — see grace_transform(consensus=) / resilience.consensus
                 consensus=params.get("consensus"),
                 # True | window | {"window": .., "capacity": ..} — see
                 # grace_transform(watch=) / telemetry.aggregate
                 watch=params.get("watch"),
                 adapt=adapt_cfg)


def route_leaves(grace: Grace, tree):
    """Per-leaf route resolution for a gradient/param pytree:
    ``[(path, struct, compressor, memory, communicator), ...]`` in
    flatten order — the one enumeration the routed wire models (telemetry,
    bench projections, the static auditor's reconciliation) share."""
    import jax
    import jax.numpy as jnp

    base = (grace.compressor, grace.memory, grace.communicator)
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        p = leaf_path_str(path)
        comp, mem, cm = route_for(grace.routes or (), p, base)
        out.append((p, jax.ShapeDtypeStruct(tuple(jnp.shape(leaf)),
                                            jnp.result_type(leaf)),
                    comp, mem, cm))
    return out


def routed_recv_link_bytes(grace: Grace, tree, world: int,
                           topology=None) -> LinkBytes:
    """Per-rank received bytes of one routed step, split by link class:
    the SUM of per-leaf prices through each leaf's own codec and
    communicator (negotiation collectives included) — the routed spelling
    of ``Communicator.recv_link_bytes`` that bench projections and the
    auditor's wire pass reconcile against. Works for unrouted bundles too
    (every leaf resolves to the base triad), so callers need no special
    case."""
    from grace_tpu.utils.metrics import payload_nbytes
    import numpy as np

    ici = dcn = wan = 0
    for _p, s, comp, _mem, cm in route_leaves(grace, tree):
        ne = int(np.prod(s.shape, dtype=np.int64))
        vote = bool(getattr(comp, "vote_aggregate", False))
        lb = cm.recv_link_bytes(payload_nbytes(comp, s), ne, world,
                                topology=topology, vote=vote)
        neg = negotiation_bytes_for(comp, ne, world)
        topo = topology if topology is not None else Topology()
        if neg:
            # The negotiation pmax is a flat full-axis collective: its
            # bytes land on the worst tier the axis spans (flat_tier).
            tier = topo.flat_tier(world)
            lb = lb._replace(**{tier: getattr(lb, tier) + neg})
        ici += lb.ici
        dcn += lb.dcn
        wan += lb.wan
    return LinkBytes(ici=ici, dcn=dcn, wan=wan)
