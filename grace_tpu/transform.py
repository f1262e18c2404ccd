"""Optax integration: compressed gradient exchange as a GradientTransformation.

This replaces the reference's entire Horovod patch surface
(patch_files/horovod/torch/__init__.py:46-201 `_DistributedOptimizer`,
patch_files/horovod/tensorflow/__init__.py:190-205 grads fn, …): instead of
monkey-patching a framework optimizer with per-parameter backward hooks, the
whole 6-stage GRACE pipeline is an `optax.GradientTransformation` that slots
into any optax chain:

    tx = optax.chain(grace_transform(compressor, memory, communicator),
                     optax.sgd(0.1))

``update`` must run where the communicator's mesh axis is bound — i.e.
inside `shard_map`/`pjit` (see grace_tpu.train.make_train_step). Every
parameter's compensate→compress→update→exchange is traced into ONE XLA
program — the reference's per-parameter Python loop over world_size × n_params
decompressions (SURVEY.md §3.1 hot loop) disappears into the compiler.

State layout: ``GraceState(count, rng_key, mem, comp, fallback, telem,
audit, watch)``
where ``mem``/``comp`` are tuples aligned with the flattened gradient leaves,
``fallback`` is the replicated resilience health flag (see
``grace_transform(escape=...)``), ``telem`` is the optional on-device
telemetry ring (``grace_transform(telemetry=...)``; None when telemetry is
off, so the default state is unchanged), ``audit`` is the optional
replicated consensus-audit bookkeeping (``grace_transform(consensus=...)``;
see :mod:`grace_tpu.resilience.consensus`), and ``watch`` is the optional
per-rank graft-watch summary ring (``grace_transform(watch=...)``; see
:mod:`grace_tpu.telemetry.aggregate`). The rng key is
replicated across ranks, so per-(step, leaf) keys derived via ``fold_in`` are
rank-identical — the explicit contract RandomK/PowerSGD rely on (the
reference relied on global-seed side effects, grace_dl/dist/compressor/
randomk.py:26-29).

**Memory/compressor state is per-rank data** — each worker accumulates its
own residual, exactly as the reference's per-process dicts do
(grace_dl/dist/memory/residual.py:6-20). In the global (outside-shard_map)
view, every ``mem``/``comp`` leaf therefore carries a leading world axis
sharded over the mesh: global shape ``(world, *leaf_shape)``, one row per
rank. ``add_world_axis``/``strip_world_axis`` convert between that layout
and the per-device view used inside the transform, and
``partition_specs`` produces the matching `PartitionSpec` pytree
(``P(axis)`` for mem/comp leaves, ``P()`` for everything else). This makes
residual state an honest sharded array — checkpoints capture every rank's
error feedback, not whichever replica the host happened to read.
"""

from __future__ import annotations

import dataclasses
import fnmatch
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from grace_tpu.core import (Communicator, Compressor, DEFAULT_AXIS,
                            LinkBytes, Memory, State, Topology, axis_size,
                            negotiation_bytes_for)
from grace_tpu.telemetry import host
from grace_tpu.telemetry.aggregate import (normalize_watch,
                                           watch_gather_bytes, watch_init,
                                           watch_record)
from grace_tpu.telemetry.scopes import (STAGE_BUCKET, STAGE_TELEMETRY,
                                        STAGE_WATCH, trace_stage)
from grace_tpu.telemetry.state import (TelemetryConfig, telemetry_init,
                                       telemetry_record)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """The transform's view of the device mesh: a data-parallel axis plus
    an optional FSDP (sharded-model) axis.

    Pure data parallelism — the only layout the repo spoke until the
    sharded-model track — is the 1-axis degenerate case
    (``fsdp_axis=None``), and every ``axis_name: str`` call site keeps
    working via :meth:`normalize`. With ``fsdp_axis`` set, the training
    step runs inside ``shard_map`` over a 2-D ``dp×fsdp`` mesh:

    * **params and optimizer state are sharded over** ``fsdp_axis`` (the
      caller's ``param_specs`` say how — typically embeddings/weights
      split a dimension, LayerNorm/bias stay replicated), so each device
      holds and updates only its *shard* of the model;
    * **the gradient each device hands the grace transform is the
      per-shard gradient**, and the compressed collective — the
      communicator, whose ``axis_name`` must equal ``dp_axis`` — is the
      per-shard reduce over the dp axis. ``lax`` collectives over
      ``dp_axis`` inside a 2-D mesh operate within each fsdp shard's dp
      group automatically, which is exactly the semantics FSDP needs;
    * **GraceState mem/comp/telem/watch leaves shard over dp per fsdp
      shard**: the global layout's leading world axis spans the dp×fsdp
      *product* (``partition_specs`` emits ``P((dp, fsdp))``), so each
      device's error-feedback residual covers exactly its own shard's
      gradient — residuals live on the shard owner, never re-indexed
      across shards (see IMPLEMENTING.md, "Why error feedback lives on
      the shard owner");
    * replicated GraceState fields (count/rng_key/fallback/audit) stay
      ``P()`` — bit-identical across BOTH axes, which is what lets the
      consensus audit fingerprint-match replicas *per fsdp shard* (its
      collectives run over ``dp_axis`` only).
    """

    dp_axis: str = DEFAULT_AXIS
    fsdp_axis: Optional[str] = None

    def __post_init__(self):
        if self.fsdp_axis is not None and self.fsdp_axis == self.dp_axis:
            raise ValueError(
                f"fsdp_axis must differ from dp_axis; both are "
                f"{self.dp_axis!r}")

    @property
    def axes(self) -> Tuple[str, ...]:
        """The mesh axis names, dp first."""
        if self.fsdp_axis is None:
            return (self.dp_axis,)
        return (self.dp_axis, self.fsdp_axis)

    @property
    def is_2d(self) -> bool:
        return self.fsdp_axis is not None

    def varying_spec(self):
        """PartitionSpec of a per-rank GraceState leaf's leading world
        axis: ``P(dp)`` on a 1-D mesh (bit-compatible with every
        pre-MeshSpec checkpoint/spec), ``P((dp, fsdp))`` on a 2-D mesh —
        one leading axis over the device *product*, one row per
        (dp, fsdp) rank."""
        from jax.sharding import PartitionSpec as P

        if self.fsdp_axis is None:
            return P(self.dp_axis)
        return P((self.dp_axis, self.fsdp_axis))

    @classmethod
    def normalize(cls, spec) -> "MeshSpec":
        """Accept the ergonomic spellings: an axis-name string (pure dp —
        every existing call site), a MeshSpec, or None (the default
        axis)."""
        if spec is None:
            return cls()
        if isinstance(spec, cls):
            return spec
        if isinstance(spec, str):
            return cls(dp_axis=spec)
        raise TypeError(f"mesh must be an axis-name str or MeshSpec; got "
                        f"{type(spec).__name__}")


class AuditState(NamedTuple):
    """Replicated bookkeeping of the cross-rank consistency auditor.

    Threaded through ``GraceState.audit`` when ``grace_transform`` is built
    with ``consensus=...``; read and advanced in-graph by
    :func:`grace_tpu.resilience.consensus.consensus_step`. Every field is
    an int32 scalar, replicated across ranks (derived from all-gathered
    fingerprints, so all ranks compute identical values) — and is itself
    part of the audited/repaired replicated state.
    """

    audits: jax.Array                 # audits performed
    repairs: jax.Array                # repair events (any-rank divergence)
    escalations: jax.Array            # repeat-offender dense-fallback trips
    last_divergent_rank: jax.Array    # mesh index of last divergent rank, -1
    last_repair_step: jax.Array       # GraceState.count at last repair, -1


def audit_init() -> AuditState:
    zero = jnp.zeros((), jnp.int32)
    return AuditState(audits=zero, repairs=zero, escalations=zero,
                      last_divergent_rank=zero - 1, last_repair_step=zero - 1)


class GraceState(NamedTuple):
    count: jax.Array          # step counter (replicated)
    rng_key: jax.Array        # replicated base key, stored as raw key data
    mem: Tuple[State, ...]    # per-leaf memory state, leaf order of tree_flatten
    comp: Tuple[State, ...]   # per-leaf compressor state
    # Health flag (replicated): True routes the next update's exchange
    # through the dense escape hatch (see grace_transform(escape=...)).
    # Written by resilience.guard_transform via set_fallback_flag; plain
    # grace_transform never sets it, so the default False is a no-op.
    fallback: jax.Array = False
    # On-device telemetry ring (per-rank data, like mem/comp): a
    # grace_tpu.telemetry.TelemetryState when grace_transform was built with
    # telemetry=..., else None (an empty pytree node — invisible to
    # checkpointing, sharding, and the guard).
    telem: Any = None
    # Consensus-audit bookkeeping (replicated, like count/fallback): an
    # AuditState when grace_transform was built with consensus=..., else
    # None (an empty pytree node). grace_transform only *threads* it; the
    # audit itself runs at the train-step level (make_train_step(consensus=))
    # where params and the whole optimizer state are in scope — see
    # grace_tpu.resilience.consensus.
    audit: Any = None
    # graft-watch cross-rank health-summary ring (per-rank data, like
    # telem — the skew columns genuinely differ per rank): a
    # grace_tpu.telemetry.aggregate.WatchState when grace_transform was
    # built with watch=..., else None (an empty pytree node).
    watch: Any = None
    # graft-adapt in-graph controller state (replicated, like count/
    # fallback/audit — every field derives from the replicated step
    # counter, the replicated fallback flag, and full-axis pmean/pmax
    # outputs, so all ranks agree bitwise and the lax.switch rung
    # dispatch can never desync): a resilience.adapt.AdaptState when
    # grace_transform was built with adapt=..., else None.
    adapt: Any = None


# The GraceState field split every layout-aware consumer agrees on:
# VARYING fields hold genuinely per-rank data (leading world axis sharded
# over the mesh in the global view — partition_specs gives them P(axis));
# REPLICATED fields are bit-identical across ranks (P()) and are exactly
# what an elastic world-resize carries forward unchanged while the varying
# fields are re-initialized at the new world (see carry_replicated and
# grace_tpu.resilience.elastic — which deliberately RE-INITIALIZES the
# replicated `adapt` policy state at the new world: its windowed signal
# statistics and operating rung were learned at the old world's error
# profile).
GRACE_VARYING_FIELDS = ("mem", "comp", "telem", "watch")
GRACE_REPLICATED_FIELDS = ("count", "rng_key", "fallback", "audit",
                           "adapt")

# The OBSERVATIONAL subset of the varying fields: rings that record
# pipeline values verbatim (a poisoned gradient's norm, a cross-rank skew
# column) and therefore must never flip a guarded step bad on their own —
# the guard's check_state scan strips exactly these
# (resilience.guard._strip_telemetry ties its type-based strip to this
# list), while they still ROLL BACK with the rest of the inner state on a
# bad step. graft-sound's rollback-coverage pass reads this constant
# instead of re-deriving the contract from comments.
GRACE_OBSERVATIONAL_FIELDS = ("telem", "watch")


def _is_grace(x) -> bool:
    return isinstance(x, GraceState)


def _map_grace_varying(fn, tree):
    """Apply ``fn`` to the device-varying leaves (mem/comp/telem/watch) of
    every GraceState embedded in ``tree``; leave all other leaves
    untouched."""

    def per_node(node):
        if _is_grace(node):
            return node._replace(**{
                name: jax.tree_util.tree_map(fn, getattr(node, name))
                for name in GRACE_VARYING_FIELDS})
        return node

    return jax.tree_util.tree_map(per_node, tree, is_leaf=_is_grace)


def add_world_axis(tree):
    """Per-device → global layout: prepend a (local size 1) world axis to
    every mem/comp leaf. Call on values produced inside shard_map."""
    return _map_grace_varying(lambda x: x[None], tree)


def strip_world_axis(tree):
    """Global → per-device layout: drop this rank's world axis (local shards
    have leading dim 1 inside shard_map)."""

    def strip(x):
        if jnp.ndim(x) < 1 or x.shape[0] != 1:
            raise ValueError(
                "grace mem/comp state leaf has no leading world axis "
                f"(local shape {jnp.shape(x)}). Build training states with "
                "init_train_state/init_stateful_train_state(params, optimizer"
                ", mesh) — states built as optimizer.init(params) lack the "
                "sharded world axis and would be silently mis-sharded.")
        return x[0]

    return _map_grace_varying(strip, tree)


def partition_specs(tree, axis_name):
    """PartitionSpec pytree for a state pytree containing GraceState nodes.

    ``axis_name`` is an axis-name string (pure data parallelism — the
    historical signature) or a :class:`MeshSpec`. Per-rank GraceState
    leaves (mem/comp/telem/watch) shard their leading world axis over the
    mesh: ``P(dp)`` on a 1-D mesh, ``P((dp, fsdp))`` on a 2-D dp×fsdp
    mesh — per fsdp shard, the dp replicas' residuals/rings tile the same
    leading axis, so the global array holds one row per device and the
    shard owner keeps its own error feedback. Everything else (replicated
    GraceState fields and non-grace leaves) is ``P()``; params and
    param-shaped optimizer state on a sharded-model mesh carry their OWN
    fsdp specs, supplied by the caller (``make_train_step(param_specs=)``)
    — this function owns the GraceState contract, not the model's."""
    from jax.sharding import PartitionSpec as P

    mesh = MeshSpec.normalize(axis_name)
    vspec = mesh.varying_spec()

    def per_node(node):
        if _is_grace(node):
            return GraceState(
                count=jax.tree_util.tree_map(lambda _: P(), node.count),
                rng_key=jax.tree_util.tree_map(lambda _: P(), node.rng_key),
                mem=jax.tree_util.tree_map(lambda _: vspec, node.mem),
                comp=jax.tree_util.tree_map(lambda _: vspec, node.comp),
                fallback=jax.tree_util.tree_map(lambda _: P(),
                                                node.fallback),
                telem=jax.tree_util.tree_map(lambda _: vspec, node.telem),
                audit=jax.tree_util.tree_map(lambda _: P(), node.audit),
                watch=jax.tree_util.tree_map(lambda _: vspec, node.watch),
                adapt=jax.tree_util.tree_map(lambda _: P(), node.adapt))
        return jax.tree_util.tree_map(lambda _: P(), node)

    return jax.tree_util.tree_map(per_node, tree, is_leaf=_is_grace)


def set_fallback_flag(tree, active) -> Any:
    """Write ``active`` into the ``fallback`` flag of every GraceState in
    ``tree``. Used by :func:`grace_tpu.resilience.guard_transform` to route
    the next step's exchange through the dense escape hatch; a no-op on
    trees without GraceState nodes."""
    active = jnp.asarray(active, jnp.bool_)

    def per_node(node):
        if _is_grace(node):
            return node._replace(fallback=active)
        return node

    return jax.tree_util.tree_map(per_node, tree, is_leaf=_is_grace)


def fallback_flags(tree) -> list:
    """The ``fallback`` flags of every GraceState in ``tree`` (leaf order)."""
    flags = []

    def per_node(node):
        if _is_grace(node):
            flags.append(node.fallback)
        return node

    jax.tree_util.tree_map(per_node, tree, is_leaf=_is_grace)
    return flags


def carry_replicated(old_tree, fresh_tree, convert=None):
    """Graft the replicated payload of ``old_tree`` onto ``fresh_tree``.

    The transform-level re-shard hook of elastic training
    (:mod:`grace_tpu.resilience.elastic`): ``fresh_tree`` is a
    freshly-initialized state pytree (same structure, per-rank leaves
    sized for the NEW world), ``old_tree`` the pre-resize state. Every
    GraceState keeps the fresh :data:`GRACE_VARYING_FIELDS`
    (mem/comp/telem/watch — re-initialized, never re-partitioned; see
    IMPLEMENTING.md, "Why re-shard re-initializes residuals") and takes
    the old :data:`GRACE_REPLICATED_FIELDS` (count/rng_key/fallback/audit)
    bit-exactly; every non-GraceState leaf (params-adjacent optimizer
    state, guard counters) is carried from ``old_tree`` — those are
    replicated by the ``partition_specs`` contract. ``convert`` (e.g. a
    ``device_put`` onto the new mesh) is applied to each carried leaf.
    ``old_tree`` may hold ``None`` in the varying fields (a stripped
    :func:`~grace_tpu.resilience.consensus.replicated_view`) — only its
    replicated payload is read."""
    conv = convert if convert is not None else (lambda x: x)

    def graft(old, fresh):
        if _is_grace(old):
            if not _is_grace(fresh):
                raise ValueError(
                    "carry_replicated: old tree has a GraceState where the "
                    f"fresh tree has {type(fresh).__name__} — the two "
                    "states were built from different optimizer chains.")
            return fresh._replace(**{
                name: jax.tree_util.tree_map(conv, getattr(old, name))
                for name in GRACE_REPLICATED_FIELDS})
        return conv(old)

    return jax.tree_util.tree_map(graft, old_tree, fresh_tree,
                                  is_leaf=_is_grace)


def leaf_path_str(path) -> str:
    """The ``"/"``-joined spelling of a ``tree_flatten_with_path`` key path
    — the string codec routes match against (and the same spelling the
    static auditor's state paths use)."""
    parts = []
    for e in path:
        for attr in ("name", "key", "idx"):
            if hasattr(e, attr):
                parts.append(str(getattr(e, attr)))
                break
        else:
            parts.append(str(e))
    return "/".join(parts)


def normalize_routes(routes, base_communicator) -> Tuple:
    """Normalize a per-leaf codec routing table to
    ``((pattern, compressor, memory, communicator), ...)``.

    Each entry is ``(pattern, triad)`` where ``pattern`` is an
    ``fnmatch`` glob matched against the leaf's ``"/"``-joined tree path
    (``"*emb*"``, ``"layers/*/ln*/*"``) and ``triad`` is either a
    3-tuple ``(compressor, memory, communicator)`` or any object with
    those attributes (a :class:`grace_tpu.helper.Grace` bundle). First
    match wins; unmatched leaves ride the transform's base triad. Every
    route's communicator must exchange over the SAME mesh axis as the
    base one — per-leaf pipelines issue separate collectives, but they
    all rendezvous on one dp axis."""
    out = []
    for entry in routes:
        if len(entry) == 4:          # already-normalized 4-tuple
            pat, comp, mem, cm = entry
        else:
            pat, triad = entry
            if isinstance(triad, (tuple, list)):
                if len(triad) != 3:
                    raise ValueError(
                        f"route {pat!r}: triad must be (compressor, "
                        f"memory, communicator); got {len(triad)} "
                        "elements")
                comp, mem, cm = triad
            else:
                comp, mem, cm = (triad.compressor, triad.memory,
                                 triad.communicator)
        if cm.axis_name != base_communicator.axis_name:
            raise ValueError(
                f"route {pat!r}: communicator axis {cm.axis_name!r} "
                f"differs from the base communicator's "
                f"{base_communicator.axis_name!r} — all routed exchanges "
                "must rendezvous on one dp axis")
        out.append((str(pat), comp, mem, cm))
    return tuple(out)


def route_for(routes, path_str: str, default):
    """The ``(compressor, memory, communicator)`` triad for one leaf path:
    the first route whose pattern matches, else ``default``."""
    for pat, comp, mem, cm in routes:
        if fnmatch.fnmatchcase(path_str, pat):
            return comp, mem, cm
    return default


def _bucketize(shapes_dtypes, bucket_bytes: Optional[int]):
    """Group leaf indices into fusion buckets of at most ``bucket_bytes``
    (whole leaves only; an oversized leaf gets its own bucket). ``None``
    means one bucket for everything. Deterministic in leaf order, so init
    and update always agree — and bucket count/ordering is a pinned
    contract (tests/test_fusion.py): the static auditor's schedulability
    pass derives the promised number of independent compress→exchange
    chains from this exact plan. Concatenating the buckets always yields
    ``range(n)``; an empty leaf list yields NO buckets (not one empty
    bucket — an empty bucket would make the fused update concatenate
    nothing). Returns (buckets, common_dtype)."""
    n = len(shapes_dtypes)
    cdtype = jnp.result_type(*(d for _, d in shapes_dtypes)) \
        if shapes_dtypes else jnp.float32
    if bucket_bytes is None:
        return ([list(range(n))] if n else []), cdtype
    itemsize = jnp.dtype(cdtype).itemsize
    buckets, cur, cur_bytes = [], [], 0
    for i, (shape, _) in enumerate(shapes_dtypes):
        nbytes = int(np.prod(shape, dtype=np.int64)) * itemsize
        if cur and cur_bytes + nbytes > bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
    if cur:
        buckets.append(cur)
    return buckets, cdtype


def _group_views(leaves):
    """Grouped-fusion plan: leaf-index lists keyed by (shape, dtype), in
    first-appearance order. Deterministic in leaf order so init and update
    always agree on group numbering."""
    groups: dict = {}
    for i, leaf in enumerate(leaves):
        key = (jnp.shape(leaf), str(jnp.result_type(leaf)))
        groups.setdefault(key, []).append(i)
    return list(groups.values())


def fusion_payload_structs(leaves, fusion) -> list:
    """``[(struct, multiplicity), ...]`` — the exact tensor structures the
    active fusion mode hands the codec, one entry per distinct compress
    call shape. Per-leaf: every leaf, ×1. ``'grouped'``: one representative
    per shape group, ×group size (vmap batches identical compressions).
    ``'flat'``/int buckets: one flat common-dtype buffer per bucket, ×1 —
    for int buckets this is also the executor's chain plan: one entry ==
    one independent compensate→compress→exchange pipeline. Shared by the
    wire models here, the static auditor's payload-contract checks
    (:mod:`grace_tpu.analysis.flow`), and the per-bucket telemetry pricing,
    so they can never enumerate different structures."""
    structs = [jax.ShapeDtypeStruct(tuple(jnp.shape(l)), jnp.result_type(l))
               for l in leaves]
    if fusion == "grouped":
        return [(structs[idxs[0]], len(idxs))
                for idxs in _group_views(structs)]
    if fusion is None:
        return [(s, 1) for s in structs]
    bucket_bytes = None if fusion == "flat" else int(fusion)
    buckets, cdtype = _bucketize(
        [(s.shape, s.dtype) for s in structs], bucket_bytes)
    return [(jax.ShapeDtypeStruct(
        (sum(int(np.prod(structs[i].shape, dtype=np.int64))
             for i in idxs),), jnp.dtype(cdtype)), 1)
        for idxs in buckets]


def fusion_payload_nbytes(compressor: Compressor, leaves, fusion
                          ) -> Tuple[int, int, int]:
    """``(dense_bytes, payload_bytes, n_elems)`` for these gradient leaves
    under a fusion setting (None | 'flat' | 'grouped' | int bucket bytes).

    ``dense_bytes`` is the raw dense gradient size (the codec-blind
    reference), ``payload_bytes`` one rank's whole-gradient wire payload
    priced over the exact structures the fusion mode compresses
    (:func:`fusion_payload_structs`), ``n_elems`` the dense element count.
    Module-level so the telemetry wire plan inside :func:`grace_transform`
    and the static auditor's wire-byte reconciliation pass
    (:mod:`grace_tpu.analysis`) price payloads with literally the same code
    — drift between the priced model and the traced graph is then a lint
    finding, never a silent disagreement.
    """
    from grace_tpu.utils.metrics import payload_nbytes

    structs = [jax.ShapeDtypeStruct(tuple(jnp.shape(l)), jnp.result_type(l))
               for l in leaves]
    n_elems = sum(int(np.prod(s.shape, dtype=np.int64)) for s in structs)
    dense = sum(int(np.prod(s.shape, dtype=np.int64)) * s.dtype.itemsize
                for s in structs)
    comp_b = sum(payload_nbytes(compressor, s) * count
                 for s, count in fusion_payload_structs(structs, fusion))
    return dense, comp_b, n_elems


def _normalize_telemetry(telemetry) -> Optional[TelemetryConfig]:
    """Accept the ergonomic spellings of the telemetry knob: None/False
    (off), True (defaults), int (ring capacity), dict (config kwargs), or a
    TelemetryConfig."""
    if telemetry is None or telemetry is False:
        return None
    if telemetry is True:
        return TelemetryConfig()
    if isinstance(telemetry, TelemetryConfig):
        return telemetry
    if isinstance(telemetry, int):
        return TelemetryConfig(capacity=telemetry)
    if isinstance(telemetry, dict):
        return TelemetryConfig(**telemetry)
    raise TypeError(f"telemetry must be None/bool/int/dict/TelemetryConfig; "
                    f"got {type(telemetry).__name__}")


@host.spanned("transform")
def grace_transform(compressor: Compressor, memory: Memory,
                    communicator: Communicator, seed: int = 0,
                    fusion: Optional[int | str] = None,
                    escape: Optional[Compressor] = None,
                    telemetry=None,
                    consensus=None,
                    topology: Optional[Topology] = None,
                    watch=None,
                    mesh=None,
                    routes: Optional[Sequence] = None,
                    adapt=None
                    ) -> optax.GradientTransformation:
    """Build the compressed-exchange transformation.

    The returned transform maps *local* (per-device) gradients to globally
    aggregated ones, exactly like ``Communicator.step`` in the reference
    (grace_dl/dist/__init__.py:47-52) but over whole pytrees.

    ``fusion`` is the TPU-native analog of Horovod's C++ fusion buffer
    (SURVEY.md §2.4: the reference inherits tensor fusion from Horovod's
    background coordinator; the dist backend has none and pays one NCCL call
    per tensor, SURVEY.md §3.3). Options:

    * ``None`` — per-leaf pipeline: one compress+collective per parameter,
      matching the reference's per-tensor semantics exactly (Top-K ratio
      applied per tensor, etc.).
    * ``'flat'`` — concatenate every gradient into ONE flat buffer: one
      compress + one collective for the whole model. Fewer, larger
      collectives ride ICI far better; selection-based compressors then pick
      k over the whole model (cross-tensor Top-K — slightly different but
      generally *stronger* selection than per-tensor).
    * ``'grouped'`` — stack same-(shape, dtype) leaves and ``jax.vmap`` the
      whole per-leaf pipeline over each stack: G same-shaped tensors cost
      one *batched* compress (e.g. PowerSGD's G small QRs/matmuls become
      batched MXU ops) and one batched collective instead of G small ones,
      while per-tensor semantics are preserved EXACTLY (vmap is just
      batching — unlike ``'flat'``, which changes selection semantics;
      grouped-vs-per-leaf bit-equality is pinned in tests/test_fusion.py).
      Measured single-chip (BERT-base + PowerSGD r4, TPU v5e 2026-08-01):
      **0.90× of per-leaf** — under XLA there is no per-op dispatch cost
      to amortize (everything is one compiled program either way), so the
      stack/unstack HBM copies are pure overhead on one chip. The case
      for 'grouped' is multi-chip: one batched psum replaces G per-leaf
      collectives, cutting per-collective latency on real meshes — weigh
      it against the measured single-chip cost on your topology. Per-leaf
      RNG derivation differs from ``None`` mode (keys split per group,
      not folded per leaf index), so stochastic codecs draw different —
      equally valid — randomness.
    * ``int`` — greedy whole-leaf buckets of at most this many bytes
      (Horovod's default fusion threshold is 64 MiB), executed as the
      **bucketed overlap executor**: K data-independent pipelines, each
      running its bucket's full compensate→compress→exchange→decompress→
      memory-update chain under its own rng and its own
      ``grace/bucket/<b>`` trace scope. Bucket b's collective depends only
      on bucket b's gradient leaves, so XLA's latency-hiding scheduler can
      overlap bucket i's exchange with bucket i+1's compression and the
      tail of the backward pass (DDP-style bucket scheduling) — the
      contract graft-flow's ``overlap_schedulability`` pass enforces (K
      independent compress→exchange chains in the traced graph) and
      graft-prof's measured overlap fraction is sandwiched against.
      Resilience and accounting stay step-atomic across the split: the
      guard checks once after ALL buckets land and rolls back the whole
      step (per-bucket rollback would desync error feedback between
      buckets), the consensus audit fingerprints the post-apply state as
      one unit, and the telemetry row sums the per-bucket wire prices
      (each bucket's collective priced separately through
      ``recv_link_bytes``) into one step row.

    Leaves are cast to their common result dtype inside a fused buffer and
    cast back on return.

    ``escape`` (resilience escape hatch, no reference analog): a dense-safe
    compressor (``NoneCompressor``/``FP16Compressor``) that, whenever the
    state's ``fallback`` flag is set, replaces the whole compressed pipeline
    for one step with ``escape``-encode → psum → decode over the same mesh
    axis (classic dense all-reduce semantics) via `lax.cond` — mem/comp
    state is left untouched, so compression resumes exactly where it left
    off when the flag clears. The flag is driven by
    :func:`grace_tpu.resilience.guard_transform`; without a guard it stays
    False and the cond always takes the compressed branch.

    ``telemetry`` (None | True | int capacity | dict | ``TelemetryConfig``):
    arm the in-graph telemetry ring (:mod:`grace_tpu.telemetry`). Every
    update then records per-step scalars — gradient/update norms,
    residual-memory norm and max (error-feedback health), the relative
    compression error ``‖g − decompress(compress(g))‖/‖g‖``, and the
    *effective* wire bytes — COMMUNICATOR-AWARE bytes received per rank per
    step (``Communicator.recv_wire_bytes``: allgather pays (W−1)·payload,
    ring/two-shot ≈2·payload·(W−1)/W), which flip to the ``escape`` codec's
    dense psum cost while the fallback flag is set — into a bounded
    on-device ring buffer
    (``GraceState.telem``) with zero host syncs; drain it with
    :class:`grace_tpu.telemetry.TelemetryReader`. The compression-error
    metric re-runs compress→decompress on the step's gradients (XLA CSEs
    the duplicate when no error-feedback memory rewrites the input); set
    ``TelemetryConfig(compression_error=False)`` to make telemetry
    near-free.

    ``topology`` (None | :class:`grace_tpu.core.Topology`): the mesh link
    layout the telemetry ring prices its per-link wire split with — every
    row's ``wire_bytes_ici``/``wire_bytes_dcn`` come from
    ``Communicator.recv_link_bytes`` under this topology (flat
    communicators therefore report the all-ICI split within one slice and
    all-DCN beyond it; the hierarchical communicator reports a genuinely
    mixed split). ``None`` auto-detects the live layout ONCE, at build
    time (``Topology.detect()`` — a single slice on CPU/simulated meshes,
    which is the documented all-ICI fallback for flat comms); every wire
    consumer inside the transform then shares that single resolved object,
    so an elastic world resize invalidates the topology by rebuilding the
    transform and nowhere else.

    ``consensus`` (None | True | int ``audit_every`` | dict |
    ``ConsensusConfig``): arm the cross-rank consistency auditor
    (:mod:`grace_tpu.resilience.consensus`) by threading an
    :class:`AuditState` through ``GraceState.audit``. The transform only
    carries the state — the audit hook itself runs at the train-step level
    (``make_train_step(consensus=...)``), where params and the full
    optimizer state are in scope for fingerprinting and repair. Any truthy
    value arms the state; the schedule/repair knobs are read from the
    config handed to the train step.

    ``mesh`` (None | axis-name str | :class:`MeshSpec`): the mesh layout
    the transform runs under. ``None``/str is pure data parallelism over
    the communicator's axis (today's behavior, unchanged byte-for-byte).
    A 2-D :class:`MeshSpec` declares the sharded-model track: the
    communicator's ``axis_name`` must equal ``mesh.dp_axis`` (the
    exchange is the per-shard reduce over dp; a collective over the dp
    axis inside a 2-D shard_map operates within each fsdp shard's dp
    group automatically), and ``partition_specs`` built from the same
    MeshSpec shards the per-rank GraceState leaves over the dp×fsdp
    product — residuals live on the shard owner.

    ``routes`` (None | ``[(pattern, triad), ...]``): first-class per-leaf
    codec routing (see :func:`normalize_routes`). Wire bytes in a
    transformer concentrate in embeddings/tied layers while
    LayerNorm/bias leaves hate sparsification — routing gives each leaf
    family its own (compressor, memory, communicator) triad, matched by
    fnmatch glob against the leaf's tree path, with unmatched leaves on
    the base triad. Requires ``fusion=None``: routing IS per-leaf
    semantics (a flat/bucketed concat would fuse leaves with different
    codecs into one payload). The telemetry wire plan, the per-link
    split, and the static auditor's wire reconciliation all price routed
    configs as the SUM of per-leaf prices through each leaf's own codec
    and communicator.

    ``watch`` (None | True | int ``window`` | dict | ``WatchConfig``): arm
    graft-watch (:mod:`grace_tpu.telemetry.aggregate`) — every
    ``window``-th step all_gathers each rank's local health vector
    (grad norm, compression error, residual norm) and writes a replicated
    cross-rank mean/min/max summary plus the per-rank **skew** (deviation
    from the replicated mean) into a bounded on-device ring
    (``GraceState.watch``), gated by a ``lax.cond`` on the replicated step
    counter exactly like the consensus audit. Costs one tiny collective
    per window (``(W-1)·12`` B received per rank), folded honestly into
    the telemetry row's ``wire_bytes``/``wire_bytes_ici``/
    ``wire_bytes_dcn`` and surfaced as ``watch_bytes``. Requires
    ``telemetry=...`` — the health scalars are the telemetry row's, and
    without a ring there is nowhere to account the gather's wire cost.

    ``adapt`` (None | True | int ``window`` | dict |
    :class:`grace_tpu.resilience.adapt.AdaptConfig`): arm the in-graph
    adaptive compression controller (graft-adapt). The declared
    **degradation ladder** replaces the single static codec: rung 0 is
    the dense escape (requires ``escape=...`` — rung 0 IS the escape
    path), rungs 1..R-1 the config's ladder codecs (safest first), and
    the transform's base ``compressor`` is always the top rung — the
    steady state a quiet run converges to. Every update executes exactly
    one rung via ``lax.switch`` on the replicated rung index (the
    guard's fallback flag forces rung 0, so the M-step dense window is
    the same branch), and every ``window`` steps the controller moves
    the rung from the replicated windowed compression-error signal (one
    scalar pmean + pmax per step — see
    :mod:`grace_tpu.resilience.adapt` for the tighten/loosen/
    escalate-and-hold semantics). Requires ``telemetry=...`` with
    ``compression_error=True`` (the signal IS the telemetry row's
    relative compression error, computed against the active rung's
    codec) and ``routes=None`` (the ladder swaps the base codec
    wholesale; per-leaf route sub-triads are outside the rung plan).
    Telemetry prices each row at the ACTIVE rung via a per-rung wire
    plan — the dense-fallback byte flip generalized to R rungs — and
    surfaces the rung as ``adapt_rung`` plus the signal reductions' cost
    as ``adapt_bytes``. Policy state (``GraceState.adapt``) is
    replicated: fingerprinted by the consensus audit, repaired by the
    masked broadcast, rolled back bitwise by the guard, re-initialized
    by an elastic world resize.
    """
    telemetry = _normalize_telemetry(telemetry)
    watch = normalize_watch(watch)
    if adapt is not None and adapt is not False:
        # Lazy import: resilience.__init__ imports guard, which imports
        # this module — a module-level import here would cycle.
        from grace_tpu.resilience.adapt import normalize_adapt
        adapt = normalize_adapt(adapt, compressor)
    else:
        adapt = None
    mesh = MeshSpec.normalize(mesh if mesh is not None
                              else communicator.axis_name)
    if mesh.dp_axis != communicator.axis_name:
        raise ValueError(
            f"mesh.dp_axis {mesh.dp_axis!r} differs from the "
            f"communicator's axis_name {communicator.axis_name!r} — the "
            "compressed exchange IS the per-shard reduce over the dp "
            "axis, so the two must name the same mesh axis.")
    routes = (normalize_routes(routes, communicator) if routes else ())
    if routes and fusion is not None:
        raise ValueError(
            "routes=... requires fusion=None: per-leaf codec routing is "
            "per-leaf semantics — 'flat'/'grouped'/bucketed fusion "
            "concatenates or stacks leaves, which would fuse leaves "
            "with different codecs into one payload. Route instead of "
            "fusing (each leaf family already gets its own collective).")
    if watch is not None and telemetry is None:
        raise ValueError(
            "watch=... requires telemetry=...: graft-watch summarizes the "
            "telemetry row's health scalars cross-rank and folds its "
            "gather cost into the ring's wire_bytes — arm "
            "grace_transform(telemetry=True) (or a capacity/config) "
            "alongside watch.")
    if adapt is not None:
        if escape is None:
            raise ValueError(
                "adapt=... requires escape=...: the degradation ladder's "
                "rung 0 IS the dense escape path (the same codec+psum the "
                "guard's fallback window routes through) — arm "
                "grace_transform(escape=FP16Compressor()/NoneCompressor()) "
                "alongside adapt.")
        if telemetry is None or not telemetry.compression_error:
            raise ValueError(
                "adapt=... requires telemetry=... with "
                "compression_error=True: the controller's windowed signal "
                "IS the telemetry row's relative compression error "
                "(computed against the active rung's codec) — arm "
                "grace_transform(telemetry=True) alongside adapt.")
        if routes:
            raise ValueError(
                "adapt=... requires routes=None: the ladder swaps the "
                "base codec wholesale each rung; per-leaf route "
                "sub-triads are outside the rung plan (route OR adapt, "
                "not both).")
    consensus_armed = consensus is not None and consensus is not False
    if escape is not None and not (getattr(escape, "summable_payload", False)
                                   and escape.average):
        raise ValueError(
            "escape must be a dense, summable, averaging compressor "
            "(NoneCompressor/FP16Compressor) — the escape hatch psums its "
            f"payload; got {type(escape).__name__}.")
    if isinstance(fusion, str) and fusion not in ("flat", "grouped"):
        raise ValueError(f"fusion must be None, 'flat', 'grouped', or int "
                         f"bytes; got {fusion!r}")
    grouped = fusion == "grouped"
    if grouped and getattr(communicator, "shard_parallel", False):
        raise ValueError(
            "fusion='grouped' vmaps the per-leaf pipeline over leaf stacks "
            "and is validated for the exchange-based communicator families "
            "(Allreduce/Allgather/Broadcast/SignAllreduce/Identity); "
            f"{type(communicator).__name__} re-chunks the gradient into "
            "per-rank shards inside step() (shard-parallel family: "
            "TwoShotAllreduce/RingAllreduce/HierarchicalAllreduce), and "
            "vmapping its all_to_all/ppermute schedule is not a traced "
            "path — use "
            "fusion=None, 'flat', or integer byte buckets, which hand the "
            "communicator whole buffers to shard.")
    bucket_bytes = None if fusion == "flat" else fusion
    fused = fusion is not None and not grouped
    # Resolve the link topology ONCE, at build time. Both consumers below
    # (the wire-plan pricing and the watch-gather link fold) close over this
    # single object, so they can never disagree — and an elastic world
    # resize has exactly one invalidation point: rebuild the transform
    # (which a resize must do anyway to re-size the per-rank state).
    # Detection is only needed when telemetry prices a per-link split.
    resolved_topology = topology
    if resolved_topology is None and telemetry is not None:
        resolved_topology = Topology.detect()

    def _bucket_views(leaves):
        """Static bucketing plan for these leaves: (buckets, common dtype)."""
        return _bucketize([(jnp.shape(l), jnp.result_type(l))
                           for l in leaves], bucket_bytes)

    _base_triad = (compressor, memory, communicator)

    def _leaf_triads(tree):
        """Per-leaf (compressor, memory, communicator) plan for a pytree:
        (paths, triads), first matching route wins, base triad otherwise.
        Deterministic in leaf order so init and update always agree."""
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        paths = [leaf_path_str(p) for p, _leaf in flat]
        return paths, [route_for(routes, p, _base_triad) for p in paths]

    def init(params) -> GraceState:
        leaves = jax.tree_util.tree_leaves(params)
        if routes:
            _, triads = _leaf_triads(params)
            mem = tuple(m.init_state(p)
                        for p, (_c, m, _cm) in zip(leaves, triads))
            comp = tuple(c.init_state(p)
                         for p, (c, _m, _cm) in zip(leaves, triads))
            return GraceState(
                count=jnp.zeros((), jnp.int32),
                rng_key=jax.random.key_data(jax.random.key(seed)),
                mem=mem, comp=comp,
                fallback=jnp.zeros((), jnp.bool_),
                telem=(telemetry_init(telemetry)
                       if telemetry is not None else None),
                audit=audit_init() if consensus_armed else None,
                watch=(watch_init(watch) if watch is not None else None),
                adapt=None)
        if grouped:
            stacks = [jnp.stack([leaves[i] for i in idxs])
                      for idxs in _group_views(leaves)]
            mem = tuple(jax.vmap(memory.init_state)(s) for s in stacks)
            comp = tuple(jax.vmap(compressor.init_state)(s) for s in stacks)
        elif fused:
            buckets, cdtype = _bucket_views(leaves)
            flats = [jnp.concatenate([jnp.ravel(leaves[i]).astype(cdtype)
                                      for i in idxs]) for idxs in buckets]
            mem = tuple(memory.init_state(f) for f in flats)
            comp = tuple(compressor.init_state(f) for f in flats)
        else:
            mem = tuple(memory.init_state(p) for p in leaves)
            comp = tuple(compressor.init_state(p) for p in leaves)
        # Raw key data (uint32) instead of a typed key array so the whole
        # state is plain-array checkpointable with any writer.
        adapt_state = None
        if adapt is not None:
            from grace_tpu.resilience.adapt import adapt_init
            adapt_state = adapt_init(adapt)
        return GraceState(count=jnp.zeros((), jnp.int32),
                          rng_key=jax.random.key_data(jax.random.key(seed)),
                          mem=mem, comp=comp,
                          fallback=jnp.zeros((), jnp.bool_),
                          telem=(telemetry_init(telemetry)
                                 if telemetry is not None else None),
                          audit=audit_init() if consensus_armed else None,
                          watch=(watch_init(watch)
                                 if watch is not None else None),
                          adapt=adapt_state)

    def _run_compressed(operand, codec: Optional[Compressor] = None):
        # ``codec`` overrides the base compressor for one call — the
        # graft-adapt ladder dispatch runs this same executor once per
        # rung branch with the rung's codec; everything else (memory,
        # communicator, fusion plan, rng derivation) is rung-invariant,
        # which is what keeps the lax.switch branches structurally
        # interchangeable.
        compressor_ = codec if codec is not None else compressor
        leaves, mem, comp, step_key = operand
        new_mem, new_comp = [], []
        if grouped:
            groups = _group_views(leaves)
            if len(mem) != len(groups):
                raise ValueError(
                    f"grace state has {len(mem)} groups but the "
                    f"leaves form {len(groups)} — the state was built under "
                    "a different fusion setting. Re-init the optimizer "
                    "state (or restore a checkpoint written with the same "
                    "fusion config).")
            outs = [None] * len(leaves)
            for gi, idxs in enumerate(groups):
                # Group COUNT can coincide between fusion settings (e.g. a
                # per-leaf state whose leaves all have distinct shapes);
                # the stacked leading dim cannot — validate it here so a
                # stale state raises the re-init message instead of an
                # opaque vmap batch-dimension error.
                for leaf in jax.tree_util.tree_leaves((mem[gi], comp[gi])):
                    if hasattr(leaf, "shape") and (
                            jnp.ndim(leaf) < 1
                            or leaf.shape[0] != len(idxs)):
                        raise ValueError(
                            f"grace state group {gi} has a leaf of shape "
                            f"{jnp.shape(leaf)} but the group stacks "
                            f"{len(idxs)} same-shaped leaves (expected "
                            f"leading dim {len(idxs)}) — the state was "
                            "built under a different fusion setting. "
                            "Re-init the optimizer state (or restore a "
                            "checkpoint written with the same fusion "
                            "config).")
                stacked = jnp.stack([leaves[i] for i in idxs])
                keys = jax.random.split(
                    jax.random.fold_in(step_key, gi), len(idxs))

                def one(g, ms, cs, key):
                    return communicator.step(g, ms, cs, memory, compressor_,
                                             key)

                out, ms, cs = jax.vmap(one)(stacked, mem[gi],
                                            comp[gi], keys)
                for j, i in enumerate(idxs):
                    outs[i] = out[j]
                new_mem.append(ms)
                new_comp.append(cs)
        elif fused:
            # Bucketed overlap executor: K data-independent pipelines, one
            # per fusion bucket. Each bucket's FULL chain — concatenate its
            # own leaves, compensate against its own residual buffer,
            # compress, exchange, decompress, update its own memory — runs
            # under a per-bucket rng (fold_in(step_key, b)) and touches no
            # other bucket's values, so bucket b's collective depends only
            # on bucket b's gradient leaves. That dataflow independence is
            # the whole point: XLA's latency-hiding scheduler may then run
            # bucket i's exchange under bucket i+1's compression and under
            # whatever tail of the backward pass produces later buckets'
            # gradients (DDP-style bucket scheduling). The contract is
            # ENFORCED, not hoped for: graft-flow's overlap_schedulability
            # pass counts the independent compress→exchange chains in the
            # traced graph and fails lint when fewer than len(buckets)
            # survive — any accidental cross-bucket dependency introduced
            # here is a CI error, not a silent serialization. Per-bucket
            # "grace/bucket/<b>" scopes make each chain attributable in a
            # device trace (the measured side of the overlap sandwich);
            # 'flat' is the K=1 degenerate case of the same executor.
            buckets, cdtype = _bucket_views(leaves)
            if len(mem) != len(buckets):
                raise ValueError(
                    f"grace state has {len(mem)} buffers but the "
                    f"fusion plan has {len(buckets)} buckets — the state was "
                    "built under a different fusion setting. Re-init the "
                    "optimizer state (or restore a checkpoint written with "
                    "the same fusion config).")
            outs = [None] * len(leaves)
            for b, idxs in enumerate(buckets):
                with trace_stage(f"{STAGE_BUCKET}/{b}"):
                    rng = jax.random.fold_in(step_key, b)
                    flat = jnp.concatenate([jnp.ravel(leaves[i]).astype(
                        cdtype) for i in idxs])
                    out, ms, cs = communicator.step(
                        flat, mem[b], comp[b], memory, compressor_, rng)
                    off = 0
                    for i in idxs:
                        shape = jnp.shape(leaves[i])
                        size = int(np.prod(shape, dtype=np.int64)) \
                            if shape else 1
                        piece = out[off:off + size]
                        outs[i] = piece.reshape(shape).astype(
                            jnp.result_type(leaves[i]))
                        off += size
                new_mem.append(ms)
                new_comp.append(cs)
        else:
            outs = []
            triads = _route_plan[0] if routes else None
            for i, (g, ms, cs) in enumerate(zip(leaves, mem, comp,
                                                strict=True)):
                comp_i, mem_i, cm_i = (triads[i] if triads is not None
                                       else (compressor_, memory,
                                             communicator))
                rng = jax.random.fold_in(step_key, i)
                out, ms, cs = cm_i.step(g, ms, cs, mem_i, comp_i, rng)
                outs.append(out)
                new_mem.append(ms)
                new_comp.append(cs)
        return tuple(outs), tuple(new_mem), tuple(new_comp)

    def _run_dense(operand):
        """Escape hatch: dense ``escape``-coded psum all-reduce of the raw
        gradients; mem/comp pass through untouched so error feedback resumes
        exactly where it paused when compression re-arms."""
        from grace_tpu.comm import Allreduce
        from grace_tpu.telemetry.scopes import STAGE_DENSE_ESCAPE

        leaves, mem, comp, step_key = operand
        allreduce = Allreduce(axis_name=communicator.axis_name)
        outs = []
        with trace_stage(STAGE_DENSE_ESCAPE):
            for i, g in enumerate(leaves):
                rng = jax.random.fold_in(step_key, i)
                payload, ctx, _ = escape.compress(g, escape.init_state(g),
                                                  rng)
                out = allreduce.exchange(payload, ctx, escape)
                outs.append(out.astype(jnp.result_type(g)))
        return tuple(outs), mem, comp

    # -- telemetry ----------------------------------------------------------

    _wire_plan_cache: dict = {}
    # Trace-time cell: the per-leaf route plan of the update being traced
    # (triads aligned with the flattened leaves). Set by update() before
    # the escape cond so both branches (and the telemetry pricing) read
    # one consistent plan; pure Python state, never traced.
    _route_plan: list = [None]

    def _routed_wire_plan(leaves, world):
        """Routed twin of ``_wire_plan``: dense/link/escape/negotiation
        prices summed per leaf through each leaf's OWN codec and
        communicator — the sum-of-per-leaf-prices contract the static
        auditor's wire reconciliation holds routed configs to."""
        from grace_tpu.comm import Allreduce
        from grace_tpu.utils.metrics import payload_nbytes

        triads = _route_plan[0]
        topo = resolved_topology
        structs = [jax.ShapeDtypeStruct(tuple(jnp.shape(l)),
                                        jnp.result_type(l)) for l in leaves]
        dense = n_elems = ici = dcn = wan = neg_b = 0
        for s, (comp_i, _mem_i, cm_i) in zip(structs, triads):
            ne = int(np.prod(s.shape, dtype=np.int64))
            dense += ne * s.dtype.itemsize
            n_elems += ne
            vote_i = bool(getattr(comp_i, "vote_aggregate", False))
            lb = cm_i.recv_link_bytes(payload_nbytes(comp_i, s), ne, world,
                                      topology=topo, vote=vote_i)
            ici += lb.ici
            dcn += lb.dcn
            wan += lb.wan
            neg_b += negotiation_bytes_for(comp_i, ne, world)
        link = LinkBytes(ici=ici, dcn=dcn, wan=wan)
        if escape is not None:
            esc_b = sum(payload_nbytes(escape, s) for s in structs)
            esc_link = Allreduce(
                axis_name=communicator.axis_name).recv_link_bytes(
                    esc_b, n_elems, world, topology=topo)
        else:
            esc_link = None
        return dense, link, esc_link, neg_b

    def _bound_axis_size(axis_name) -> int:
        """Static world size when the mesh axis is bound (inside
        shard_map/pjit, the normal train-step case); 1 when it is not
        (single-process use, e.g. the Identity communicator outside a
        mesh)."""
        try:
            return int(axis_size(axis_name))
        except NameError:       # unbound axis name
            return 1

    def _wire_plan(leaves, world, codec: Optional[Compressor] = None):
        """(dense, link, escape_link, negotiation) logical bytes for these
        leaves under the active fusion mode at world size ``world``.
        ``negotiation`` is the shared-scale negotiation collectives' cost
        (``Compressor.negotiation_nbytes`` × one ``negotiate`` pmax per
        compress call of the fusion plan; 0 for every other codec) —
        surfaced as the ``negotiation_bytes`` telemetry field and folded
        into the effective wire accounting like ``watch_bytes``, since the
        pmax is a real flat full-axis collective. ``dense`` is the
        raw dense gradient bytes (the codec- and communicator-blind
        reference); ``link``/``escape_link`` are COMMUNICATOR-AWARE
        per-link :class:`~grace_tpu.core.LinkBytes` splits of the bytes
        received per rank per step (``Communicator.recv_link_bytes`` under
        the transform's topology; ``link.total`` is the scalar
        ``recv_wire_bytes`` model) — payload bytes alone cannot rank e.g.
        ring/two-shot's O(k) against allgather's O(W·k) received, and the
        scalar alone cannot show that a flat schedule's bytes all ride DCN
        beyond one slice. Static Python ints, cached per (leaf signature,
        world) — eval_shape tracing inside ``payload_nbytes`` is a
        trace-time cost paid once per shape set, never at run time. Same
        logical-vs-padded-bytes caveat as
        :func:`grace_tpu.utils.metrics.wire_report`."""
        from grace_tpu.utils.metrics import payload_nbytes

        compressor_ = codec if codec is not None else compressor
        if routes:
            # Per-leaf routed pricing; uncached (the plan depends on leaf
            # paths, not just shapes — and this is trace-time-only cost).
            return _routed_wire_plan(leaves, world)
        sig = tuple((tuple(jnp.shape(l)), str(jnp.result_type(l)))
                    for l in leaves)
        plan = _wire_plan_cache.get((sig, world, compressor_))
        if plan is not None:
            return plan
        structs = [jax.ShapeDtypeStruct(shape, jnp.dtype(d))
                   for shape, d in sig]
        dense, comp_b, n_elems = fusion_payload_nbytes(
            compressor_, structs, fusion)
        vote = bool(getattr(compressor_, "vote_aggregate", False))
        topo = resolved_topology
        if isinstance(fusion, int) and not isinstance(fusion, bool):
            # The bucketed executor issues one collective CHAIN per bucket,
            # so the honest model is the sum of per-bucket prices, not one
            # whole-payload call: for linear schedules (gather/psum) the
            # two are identical, but ring/two-shot floor-round per
            # collective — K separate exchanges really do move the
            # per-bucket-rounded bytes. Pinned against the per-bucket sum
            # in tests/test_bucketed.py; still inside WIRE_MODEL_RTOL of
            # the whole-payload recv_wire_bytes the auditor reconciles.
            from grace_tpu.utils.metrics import payload_nbytes
            ici = dcn = wan = 0
            for s, count in fusion_payload_structs(structs, fusion):
                b_elems = int(np.prod(s.shape, dtype=np.int64))
                lb = communicator.recv_link_bytes(
                    payload_nbytes(compressor_, s), b_elems, world,
                    topology=topo, vote=vote)
                ici += count * lb.ici
                dcn += count * lb.dcn
                wan += count * lb.wan
            link = LinkBytes(ici=ici, dcn=dcn, wan=wan)
        else:
            link = communicator.recv_link_bytes(comp_b, n_elems, world,
                                                topology=topo, vote=vote)
        if escape is not None:
            from grace_tpu.comm import Allreduce
            esc_b = sum(payload_nbytes(escape, s) for s in structs)
            # The escape hatch is a dense psum all-reduce of the escape
            # payload — price it with the Allreduce ring model (a flat
            # schedule: its split is all-ICI or all-DCN under ``topo``).
            esc_link = Allreduce(
                axis_name=communicator.axis_name).recv_link_bytes(
                    esc_b, n_elems, world, topology=topo)
        else:
            esc_link = None
        # One negotiation collective per compress call the fusion plan
        # issues (per bucket/leaf/group) — zero for codecs without one,
        # leaf-size-aware for index negotiations (cyclic Top-K).
        neg_b = sum(count * negotiation_bytes_for(
            compressor_, int(np.prod(s.shape, dtype=np.int64)), world)
            for s, count in fusion_payload_structs(structs, fusion))
        plan = _wire_plan_cache[(sig, world, compressor_)] = (
            dense, link, esc_link, neg_b)
        return plan

    def _sqsum(ls) -> jax.Array:
        tot = jnp.zeros((), jnp.float32)
        for l in ls:
            if hasattr(l, "dtype") and jnp.issubdtype(l.dtype, jnp.inexact):
                tot = tot + jnp.sum(jnp.square(l.astype(jnp.float32)))
        return tot

    def _codec_error_sq(leaves, comp, step_key,
                        codec: Optional[Compressor] = None) -> jax.Array:
        """Σ‖x − decompress(compress(x))‖² over the exact structures (and
        rng derivation) the active fusion mode compresses — so with no
        error-feedback memory the duplicate compress CSEs against the
        pipeline's own. ``codec`` overrides the base compressor (the
        graft-adapt ladder measures the ACTIVE rung's error)."""
        compressor_ = codec if codec is not None else compressor
        diff = jnp.zeros((), jnp.float32)
        if grouped:
            for gi, idxs in enumerate(_group_views(leaves)):
                stacked = jnp.stack([leaves[i] for i in idxs])
                keys = jax.random.split(
                    jax.random.fold_in(step_key, gi), len(idxs))

                def roundtrip(g, cs, key):
                    payload, ctx, _ = compressor_.compress(g, cs, key)
                    return compressor_.decompress(payload, ctx)

                dec = jax.vmap(roundtrip)(stacked, comp[gi], keys)
                diff = diff + _sqsum([stacked - dec])
        elif fused:
            buckets, cdtype = _bucket_views(leaves)
            for b, idxs in enumerate(buckets):
                flat = jnp.concatenate([jnp.ravel(leaves[i]).astype(cdtype)
                                        for i in idxs])
                payload, ctx, _ = compressor_.compress(
                    flat, comp[b], jax.random.fold_in(step_key, b))
                diff = diff + _sqsum([flat
                                      - compressor_.decompress(payload,
                                                               ctx)])
        else:
            triads = _route_plan[0] if routes else None
            for i, g in enumerate(leaves):
                comp_i = (triads[i][0] if triads is not None
                          else compressor_)
                payload, ctx, _ = comp_i.compress(
                    g, comp[i], jax.random.fold_in(step_key, i))
                diff = diff + _sqsum([g - comp_i.decompress(payload, ctx)])
        return diff

    def _telemetry_next(state: GraceState, leaves, outs, new_mem, step_key,
                        err_value=None, eff_idx=None):
        """One telemetry row, written at slot count % capacity, plus the
        maybe-updated graft-watch summary ring. The row itself is pure
        in-graph math over values the step already computed (plus the
        optional codec round-trip) — no collectives, no host syncs; the
        watch summary (when armed) adds exactly one tiny all_gather on
        window-boundary steps, whose wire cost is folded into this row.

        With graft-adapt armed, ``eff_idx`` is the replicated EFFECTIVE
        rung this step's exchange ran at and ``err_value`` the active
        rung's relative compression error (already 0 on the dense rung):
        the row's effective wire bytes then come from a per-rung wire
        plan indexed by ``eff_idx`` — the dense-fallback byte flip
        generalized to R rungs, ici/dcn split included — and the rung
        plus the signal reductions' cost are surfaced as
        ``adapt_rung``/``adapt_bytes``."""
        if state.telem is None:
            raise ValueError(
                "grace_transform was built with telemetry=... but the state "
                "has no telemetry ring — it was initialized by a transform "
                "without telemetry (or restored from such a checkpoint). "
                "Re-init the optimizer state with the telemetry-enabled "
                "transform.")
        dense_b, link, esc_link, neg_b = _wire_plan(
            leaves, _bound_axis_size(communicator.axis_name))
        comp_b, esc_b = link.total, (
            esc_link.total if esc_link is not None else None)
        grad_norm = jnp.sqrt(_sqsum(leaves))
        update_norm = jnp.sqrt(_sqsum(outs))
        mem_leaves = [l for l in jax.tree_util.tree_leaves(new_mem)
                      if hasattr(l, "dtype")
                      and jnp.issubdtype(l.dtype, jnp.inexact)]
        residual_norm = jnp.sqrt(_sqsum(mem_leaves))
        residual_max = (jnp.max(jnp.stack(
            [jnp.max(jnp.abs(l.astype(jnp.float32))) for l in mem_leaves]))
            if mem_leaves else jnp.zeros((), jnp.float32))
        if telemetry.compression_error:
            if err_value is not None:
                # graft-adapt: the active rung's error, computed once in
                # update() (shared with the controller's signal) — 0 on
                # the dense rung by construction, which subsumes the
                # fallback-window zeroing below.
                err = jnp.asarray(err_value, jnp.float32)
            else:
                err = jnp.sqrt(_codec_error_sq(leaves, state.comp,
                                               step_key)) \
                    / jnp.maximum(grad_norm,
                                  jnp.asarray(1e-20, jnp.float32))
                if escape is not None:
                    # During a dense window the codec is bypassed: the
                    # *effective* error of what actually shipped is ~0.
                    err = jnp.where(jnp.asarray(state.fallback, jnp.bool_),
                                    jnp.zeros((), jnp.float32), err)
        else:
            err = jnp.zeros((), jnp.float32)
        if eff_idx is not None:
            # Per-rung effective wire plan (graft-adapt): static prices
            # for every reachable rung — rung 0 is the escape psum, rung
            # r >= 1 the ladder codec's plan through the same
            # communicator — selected by the replicated effective rung.
            # The guard's fallback flag forces eff_idx to 0 upstream, so
            # the dense-fallback flip is the same mechanism.
            from grace_tpu.resilience.adapt import adapt_signal_bytes
            world = _bound_axis_size(communicator.axis_name)
            rung_plans = [_wire_plan(leaves, world, codec=c)
                          for c in adapt.ladder]
            rung_tot = jnp.asarray(
                [float(esc_link.total)]
                + [float(p[1].total) for p in rung_plans], jnp.float32)
            rung_ici = jnp.asarray(
                [float(esc_link.ici)]
                + [float(p[1].ici) for p in rung_plans], jnp.float32)
            rung_dcn = jnp.asarray(
                [float(esc_link.dcn)]
                + [float(p[1].dcn) for p in rung_plans], jnp.float32)
            rung_wan = jnp.asarray(
                [float(esc_link.wan)]
                + [float(p[1].wan) for p in rung_plans], jnp.float32)
            rung_neg = jnp.asarray(
                [0.0] + [float(p[3]) for p in rung_plans], jnp.float32)
            eff = rung_tot[eff_idx]
            eff_ici = rung_ici[eff_idx]
            eff_dcn = rung_dcn[eff_idx]
            eff_wan = rung_wan[eff_idx]
            ngb = rung_neg[eff_idx]
            # The signal reductions run every step — two scalar
            # full-axis collectives, folded like watch_bytes (flat
            # schedule: ICI within one slice, DCN beyond, WAN beyond one
            # region — Topology.flat_tier).
            ab = jnp.asarray(float(adapt_signal_bytes(world)), jnp.float32)
            tier = resolved_topology.flat_tier(world)
            eff = eff + ngb + ab
            if tier == "wan":
                eff_wan = eff_wan + ngb + ab
            elif tier == "dcn":
                eff_dcn = eff_dcn + ngb + ab
            else:
                eff_ici = eff_ici + ngb + ab
        elif escape is None:
            eff = jnp.asarray(float(comp_b), jnp.float32)
            eff_ici = jnp.asarray(float(link.ici), jnp.float32)
            eff_dcn = jnp.asarray(float(link.dcn), jnp.float32)
            eff_wan = jnp.asarray(float(link.wan), jnp.float32)
        else:
            fb = jnp.asarray(state.fallback, jnp.bool_)
            eff = jnp.where(fb, jnp.asarray(float(esc_b), jnp.float32),
                            jnp.asarray(float(comp_b), jnp.float32))
            # The per-link split flips with the scalar: a dense-fallback
            # window's bytes ride the escape psum's flat schedule.
            eff_ici = jnp.where(
                fb, jnp.asarray(float(esc_link.ici), jnp.float32),
                jnp.asarray(float(link.ici), jnp.float32))
            eff_dcn = jnp.where(
                fb, jnp.asarray(float(esc_link.dcn), jnp.float32),
                jnp.asarray(float(link.dcn), jnp.float32))
            eff_wan = jnp.where(
                fb, jnp.asarray(float(esc_link.wan), jnp.float32),
                jnp.asarray(float(link.wan), jnp.float32))
        if eff_idx is None:
            # Shared-scale negotiation cost, folded like watch_bytes —
            # into the scalar AND the per-link split (the pmax is a flat
            # full-axis collective), zeroed during dense-fallback windows
            # (the dense branch never negotiates). The adapt path above
            # already selected a per-rung negotiation price instead.
            ab = jnp.zeros((), jnp.float32)
            ngb = jnp.asarray(float(neg_b), jnp.float32)
            if escape is not None:
                ngb = jnp.where(jnp.asarray(state.fallback, jnp.bool_),
                                jnp.zeros((), jnp.float32), ngb)
            if neg_b:
                world = _bound_axis_size(communicator.axis_name)
                tier = resolved_topology.flat_tier(world)
                eff = eff + ngb
                if tier == "wan":
                    eff_wan = eff_wan + ngb
                elif tier == "dcn":
                    eff_dcn = eff_dcn + ngb
                else:
                    eff_ici = eff_ici + ngb
        new_watch = state.watch
        wb = jnp.zeros((), jnp.float32)
        if watch is not None:
            if state.watch is None:
                raise ValueError(
                    "grace_transform was built with watch=... but the "
                    "state has no watch ring — it was initialized by a "
                    "transform without watch (or restored from such a "
                    "checkpoint). Re-init the optimizer state with the "
                    "watch-enabled transform.")
            with trace_stage(STAGE_WATCH):
                world = _bound_axis_size(communicator.axis_name)
                due = jnp.equal(jnp.mod(state.count, watch.window), 0)
                new_watch = watch_record(
                    state.watch, state.count,
                    {"grad_norm": grad_norm, "compression_error": err,
                     "residual_norm": residual_norm},
                    communicator.axis_name, due)
                # Fold the gather's received bytes into the effective wire
                # accounting — the same honesty contract as audit_bytes,
                # but split by link too: the health gather is a flat
                # full-axis collective, so it rides ICI within one slice,
                # DCN beyond it, and WAN beyond one region — exactly like
                # the escape psum (Topology.flat_tier).
                tier = resolved_topology.flat_tier(world)
                wb = jnp.where(due, jnp.asarray(
                    float(watch_gather_bytes(world)), jnp.float32), 0.0)
                eff = eff + wb
                if tier == "wan":
                    eff_wan = eff_wan + wb
                elif tier == "dcn":
                    eff_dcn = eff_dcn + wb
                else:
                    eff_ici = eff_ici + wb
        return new_watch, telemetry_record(state.telem, state.count, {
            "grad_norm": grad_norm,
            "update_norm": update_norm,
            "residual_norm": residual_norm,
            "residual_max": residual_max,
            "compression_error": err,
            "wire_bytes": eff,
            "dense_bytes": jnp.asarray(float(dense_b), jnp.float32),
            "fallback": jnp.asarray(state.fallback, jnp.float32),
            # Filled in after the fact by consensus_step on audit steps —
            # the audit runs post-apply, after this row is written.
            "audit_bytes": jnp.zeros((), jnp.float32),
            # Per-link split of the exchange's wire_bytes under the
            # transform's Topology; ici + dcn + wan == wire_bytes on every
            # non-audit step (the consensus hook folds its flat-collective
            # audit cost into the scalar only; the watch gather is folded
            # into scalar AND split, so the identity survives it).
            "wire_bytes_ici": eff_ici,
            "wire_bytes_dcn": eff_dcn,
            "wire_bytes_wan": eff_wan,
            "watch_bytes": wb,
            "negotiation_bytes": ngb,
            # graft-adapt: the effective rung this row's bytes were
            # priced at (-1 = controller not armed) and the signal
            # reductions' cost (folded into wire_bytes AND the split,
            # like watch_bytes).
            "adapt_rung": (eff_idx.astype(jnp.float32)
                           if eff_idx is not None
                           else jnp.asarray(-1.0, jnp.float32)),
            "adapt_bytes": ab,
        })

    def update(updates, state: GraceState, params=None):
        del params
        leaves, treedef = jax.tree_util.tree_flatten(updates)
        if routes:
            _route_plan[0] = _leaf_triads(updates)[1]
        base_key = jax.random.wrap_key_data(state.rng_key)
        step_key = jax.random.fold_in(base_key, state.count)
        operand = (tuple(leaves), state.mem, state.comp, step_key)
        eff_idx = local_err = None
        adapt_state = state.adapt
        if adapt is not None:
            # graft-adapt ladder dispatch: one lax.switch over every
            # reachable rung — branch 0 is the dense escape (the guard's
            # fallback flag forces it, so the M-step dense window is this
            # same branch), branch r the ladder's rung-r codec through
            # the unchanged memory/communicator/fusion plan. The index is
            # replicated by construction (the commanded rung is policy
            # state derived from full-axis reductions; the fallback flag
            # is the guard's replicated verdict), which is the exact
            # predicate contract lint pass 1 verifies — every rank takes
            # the same branch and the rung's collectives rendezvous.
            if state.adapt is None:
                raise ValueError(
                    "grace_transform was built with adapt=... but the "
                    "state has no AdaptState — it was initialized by a "
                    "transform without adapt (or restored from such a "
                    "checkpoint). Re-init the optimizer state with the "
                    "adapt-enabled transform.")
            from grace_tpu.resilience.adapt import (adapt_advance,
                                                    adapt_signal)
            from grace_tpu.telemetry.scopes import STAGE_ADAPT
            top = len(adapt.ladder)
            fb = jnp.asarray(state.fallback, jnp.bool_)
            eff_idx = jnp.where(
                fb, jnp.zeros((), jnp.int32),
                jnp.clip(jnp.asarray(state.adapt.rung, jnp.int32), 0,
                         top)).astype(jnp.int32)
            branches = [_run_dense] + [
                (lambda op, c=c: _run_compressed(op, codec=c))
                for c in adapt.ladder]
            try:
                outs, new_mem, new_comp = lax.switch(eff_idx, branches,
                                                     operand)
            except TypeError as e:
                raise ValueError(
                    "adapt ladder rungs must thread identical mem/comp "
                    "state structures (the lax.switch branches return one "
                    "state type) — a rung whose compressor state changes "
                    "shape per rung cannot ride one ladder. PowerSGD rank "
                    "ladders need a uniform padded state: set state_rank "
                    "to the ladder's max rank on every rung "
                    "(grace_from_params does this automatically): "
                    f"{e}") from None
            # The controller's signal + advance: the ACTIVE rung's local
            # relative compression error (0 on the dense rung — nothing
            # lossy shipped), reduced to a replicated (mean, worst-rank)
            # pair with one scalar pmean + pmax, accumulated into the
            # replicated window statistics, and decided at the window
            # boundary (the consensus/watch lax.cond idiom).
            grad_norm = jnp.sqrt(_sqsum(leaves))
            err_ops = (tuple(leaves), state.comp, step_key)
            err_branches = [lambda op: jnp.zeros((), jnp.float32)] + [
                (lambda op, c=c: jnp.sqrt(_codec_error_sq(
                    op[0], op[1], op[2], codec=c))
                 / jnp.maximum(grad_norm, jnp.asarray(1e-20, jnp.float32)))
                for c in adapt.ladder]
            with trace_stage(STAGE_ADAPT):
                local_err = lax.switch(eff_idx, err_branches, err_ops)
                err_mean, err_peak = adapt_signal(local_err,
                                                  communicator.axis_name)
                adapt_state = adapt_advance(state.adapt, adapt,
                                            state.count, state.fallback,
                                            err_mean, err_peak)
        elif escape is None:
            outs, new_mem, new_comp = _run_compressed(operand)
        else:
            # Both branches carry collectives; the predicate is replicated
            # (the guard derives it from rank-identical post-exchange
            # updates, OR-reduced over the axis), so every rank takes the
            # same branch and the collectives rendezvous.
            outs, new_mem, new_comp = lax.cond(
                jnp.asarray(state.fallback, jnp.bool_),
                _run_dense, _run_compressed, operand)
        telem, watch_state = state.telem, state.watch
        if telemetry is not None:
            with trace_stage(STAGE_TELEMETRY):
                watch_state, telem = _telemetry_next(state, leaves, outs,
                                                     new_mem, step_key,
                                                     err_value=local_err,
                                                     eff_idx=eff_idx)
        new_state = GraceState(count=state.count + 1, rng_key=state.rng_key,
                               mem=new_mem, comp=new_comp,
                               fallback=state.fallback, telem=telem,
                               audit=state.audit, watch=watch_state,
                               adapt=adapt_state)
        return jax.tree_util.tree_unflatten(treedef, outs), new_state

    # The one resolved topology object both pricing paths close over —
    # exposed so tests can pin the single-invalidation-point contract
    # (None when telemetry is off: nothing prices a per-link split).
    update.grace_topology = resolved_topology
    # The mesh layout and route table the transform was built under —
    # read by the static auditor's tracer (2-D replication seeding) and
    # the routed wire reconciliation.
    update.grace_mesh = mesh
    update.grace_routes = routes
    return optax.GradientTransformation(init, update)
