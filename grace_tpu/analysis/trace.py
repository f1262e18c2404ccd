"""Device-free SPMD tracing: any grace config to a jaxpr on a CPU in CI.

The insight making static auditing possible: ``jax.shard_map`` accepts an
``AbstractMesh`` — a mesh of *names and sizes* with no devices behind it —
and ``jax.make_jaxpr`` happily traces through it. So the full compressed
pipeline (compress, collectives, error feedback, escape cond, consensus
audit) lowers to an inspectable jaxpr at world size W on a machine with one
CPU core and zero TPUs. Collectives appear as first-class equations
(``psum``/``all_gather``/``ppermute``/``all_to_all``), conds carry their
branch jaxprs, and ``jax.named_scope`` stage names from
:mod:`grace_tpu.telemetry.scopes` ride along in each equation's
``source_info.name_stack`` — which is how findings name the offending
pipeline stage.

Rank-variance seeding: inside ``shard_map`` every value is per-device, but
only *some* carry rank-varying data (gradients from the sharded batch,
GraceState mem/comp residuals, telemetry rings); the rest are replicated by
contract (step count, rng key, fallback flag, params). The tracer derives
the seed mask from :func:`grace_tpu.transform.partition_specs` — the same
source of truth the real train step shards state with — so the passes'
replication analysis starts from the layout the system actually promises.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from grace_tpu.core import DEFAULT_AXIS
from grace_tpu.parallel import shard_map
from grace_tpu.transform import MeshSpec, partition_specs

__all__ = ["TracedGraph", "abstract_mesh", "default_param_structs",
           "trace_fn", "trace_update", "trace_train_step"]

# Default parameter tree for config audits. Flat size 512 = 8 * 64: evenly
# shardable over the 8-way audit mesh with shard sizes divisible by 8, so
# bit-packing codecs (signsgd's 8-signs-per-byte) cost the same whether
# packed per shard or whole — keeping the wire-byte reconciliation pass
# free of pure test-shape rounding noise (real gradients are megabytes;
# ceil-rounding on 17-element shards is not a model drift worth flagging).
_DEFAULT_PARAMS = (("w", (60, 8)), ("b", (32,)))


def abstract_mesh(world: int, axis_name: str = DEFAULT_AXIS):
    """A 1-D ``AbstractMesh`` of ``world`` ranks."""
    return abstract_mesh_nd(((axis_name, world),))


def abstract_mesh_nd(axes: Sequence[Tuple[str, int]]):
    """N-D ``AbstractMesh`` from ``((name, size), ...)`` pairs — the 2-D
    dp×fsdp audit meshes trace through this."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(int(s) for _, s in axes),
                        tuple(str(n) for n, _ in axes))


def default_param_structs() -> Dict[str, jax.ShapeDtypeStruct]:
    return {name: jax.ShapeDtypeStruct(shape, jnp.float32)
            for name, shape in _DEFAULT_PARAMS}


@dataclasses.dataclass
class TracedGraph:
    """One audited program: the shard_map body jaxpr plus audit context.

    ``varying`` maps each body input var to whether it carries rank-varying
    data (the replication-analysis seed). ``state_in``/``state_out`` are
    aligned (path, aval) lists for the optimizer-state portion of the
    signature — the fixed-point check of ``signature_stability``.
    ``grad_in`` lists the body invars carrying gradient (or batch) leaves —
    the dependence-graph layer's bucket roots (:mod:`.flow`); and
    ``state_replicated`` the (path, aval) state leaves whose partition spec
    is ``P()`` — the buffers the memory-footprint pass checks for
    world-scaling shapes. ``meta`` carries whatever the config registry
    wants findings to report (compressor/communicator names, the Grace
    bundle for the wire model).
    """

    name: str
    closed: Any                      # ClosedJaxpr of the whole traced fn
    body: Any                        # the shard_map body Jaxpr
    world: int                       # size of the EXCHANGE (dp) axis
    axis_name: str                   # the exchange (dp) axis name
    varying: Dict[Any, bool]         # dp-axis rank-variance seeds
    state_in: List[Tuple[str, Any]] = dataclasses.field(default_factory=list)
    state_out: List[Tuple[str, Any]] = dataclasses.field(default_factory=list)
    grad_in: List[Any] = dataclasses.field(default_factory=list)
    state_replicated: List[Tuple[str, Any]] = dataclasses.field(
        default_factory=list)
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # 2-D mesh support (dp×fsdp): every mesh axis name in order (empty =
    # 1-D, (axis_name,)), per-axis sizes, and PER-AXIS rank-variance seed
    # maps — a value can be dp-replicated yet fsdp-varying (a param
    # shard), which is exactly what the per-axis replication dataflow of
    # pass 1 distinguishes. Seeded from the same partition_specs contract
    # as ``varying``.
    mesh_axes: Tuple[str, ...] = ()
    axis_sizes: Dict[str, int] = dataclasses.field(default_factory=dict)
    varying_axes: Dict[str, Dict[Any, bool]] = dataclasses.field(
        default_factory=dict)
    # Aligned (path, body var) lists for the state portion of the traced
    # signature — the *var* twins of ``state_in``/``state_out``, recorded so
    # the stateful-semantics passes (graft-sound, :mod:`.state_passes`) can
    # seed per-leaf dataflow from the actual jaxpr vars: rng-lineage roots
    # (the ``rng_key`` leaf), rollback write-sets (every state leaf's
    # input→output pair), and the step-exit replication check. Unlike
    # ``state_in``, these ARE populated for train-step traces (the guard's
    # rollback selects only exist there). ``grace_prefixes`` are the
    # "/"-joined path prefixes of every GraceState node in the traced state
    # tree ("" when the state IS a GraceState), so passes can classify a
    # leaf path into its GraceState field without guessing.
    state_in_vars: List[Tuple[str, Any]] = dataclasses.field(
        default_factory=list)
    state_out_vars: List[Tuple[str, Any]] = dataclasses.field(
        default_factory=list)
    grace_prefixes: Tuple[str, ...] = ()

    @property
    def axes(self) -> Tuple[str, ...]:
        return self.mesh_axes if self.mesh_axes else (self.axis_name,)

    def varying_for(self, axis: str) -> Dict[Any, bool]:
        """Per-axis rank-variance seeds: the recorded per-axis map when
        the tracer produced one, the dp map for the dp axis, else the dp
        map as the conservative stand-in (over-seeding variance can only
        produce false positives, never silent passes)."""
        if axis in self.varying_axes:
            return self.varying_axes[axis]
        return self.varying


def _is_jaxpr_var(v) -> bool:
    return hasattr(v, "aval") and not hasattr(v, "val")


# Sentinels for _seed_positions entries that carry no outer arg index.
_CONST = "const"        # literal / hoisted constant: replicated on every rank
_UNKNOWN = "unknown"    # computed between the inputs and the shard_map


def _seed_positions(closed, n_outer: int):
    """Map each shard_map *body* invar to the outer arg leaf it carries.

    Returns ``(body, positions)`` for the first shard_map equation found
    (depth-first through ``pjit``/``cond``/… wrappers — ``make_train_step``
    jits, so the shard_map usually sits one ``pjit`` down). ``positions``
    has one entry per body invar: the index of the flattened outer
    argument leaf it forwards, :data:`_CONST` for a **hoisted constant** —
    jnp constants created inside the traced step (codec chunk-index
    tables, empty padding arrays, iota ramps) that shard_map lifts into
    extra body invars *ahead of* the real arguments — or :data:`_UNKNOWN`
    for a value computed on the way in. Constants are replicated by
    construction (same bytes on every rank), so seeding them rank-varying
    — which is what a naive positional zip does the moment one appears —
    poisons the whole replication analysis: the escape/audit cond
    predicates read as rank-varying and every legal branch divergence
    becomes a false positive (first seen on the hierarchical
    communicator's chunked Top-K stage-1 encode, whose empty chunk-index
    constants shifted the mask).

    ``positions`` is ``None`` when the shard_map/body arities disagree;
    the whole result is ``None`` when no shard_map equation exists.
    """

    def classify(v, env, jaxpr):
        if not _is_jaxpr_var(v):
            return _CONST
        if v in env:
            return env[v]
        if v in set(getattr(jaxpr, "constvars", ())):
            return _CONST
        return _UNKNOWN

    def walk(jaxpr, env):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "shard_map":
                body = eqn.params["jaxpr"]
                body = getattr(body, "jaxpr", body)
                if len(eqn.invars) != len(body.invars):
                    return body, None
                return body, [classify(v, env, jaxpr) for v in eqn.invars]
            for sub in _sub_jaxprs(eqn):
                ops = (eqn.invars[1:] if eqn.primitive.name == "cond"
                       else eqn.invars)
                if len(sub.invars) == len(ops):
                    sub_env = {sv: classify(ov, env, jaxpr)
                               for sv, ov in zip(sub.invars, ops)}
                else:
                    sub_env = {sv: _UNKNOWN for sv in sub.invars}
                found = walk(sub, sub_env)
                if found is not None:
                    return found
        return None

    env0 = {v: (i if i < n_outer else _UNKNOWN)
            for i, v in enumerate(closed.jaxpr.invars)}
    return walk(closed.jaxpr, env0)


def _seeds_from_positions(positions, mask: List[bool],
                          n_invars: int) -> List[bool]:
    """Rank-variance seed per body invar from a :func:`_seed_positions`
    result: outer leaves take their mask entry, hoisted constants are
    replicated, anything unresolvable is conservatively varying."""
    if positions is None:
        return [True] * n_invars
    return [False if p is _CONST
            else (mask[p] if isinstance(p, int) else True)
            for p in positions]


def _sub_jaxprs(eqn):
    """Every jaxpr nested in an equation's params (cond branches, pjit
    bodies, scan/while jaxprs, custom_*_call), normalized to raw Jaxprs."""
    out = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for item in vs:
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns") and hasattr(inner, "invars"):
                out.append(inner)
    return out


def _spec_mentions(spec, axis_name: str) -> bool:
    if spec is None:
        return False
    for entry in spec:
        if entry is None:
            continue
        names = entry if isinstance(entry, (tuple, list)) else (entry,)
        if axis_name in names:
            return True
    return False


def _flat_paths(tree) -> List[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [_path_str(path) for path, _leaf in flat]


def _path_str(path) -> str:
    parts = []
    for e in path:
        for attr in ("name", "key", "idx"):
            if hasattr(e, attr):
                parts.append(str(getattr(e, attr)))
                break
        else:
            parts.append(str(e))
    return "/".join(parts)


def _grace_prefixes(state_struct) -> Tuple[str, ...]:
    """Path prefixes of every GraceState node embedded in ``state_struct``
    ("" when the state itself is one) — recorded on the TracedGraph so the
    graft-sound passes can map a state leaf path to its GraceState field
    by structure, not by guessing at segment names."""
    from grace_tpu.transform import GraceState

    is_grace = lambda n: isinstance(n, GraceState)          # noqa: E731
    flat, _ = jax.tree_util.tree_flatten_with_path(
        state_struct, is_leaf=is_grace)
    return tuple(_path_str(path) for path, node in flat if is_grace(node))


def _varying_mask_from_specs(state_struct, axis_name: str) -> List[bool]:
    """Per-leaf rank-variance of a state pytree, derived from the same
    ``partition_specs`` the real train step shards it with: leaves whose
    spec mentions the mesh axis (GraceState mem/comp/telem) vary per rank;
    everything else is replicated by the system's own sharding contract."""
    return _varying_masks(state_struct,
                          MeshSpec(dp_axis=axis_name))[axis_name]


def _varying_masks(state_struct, mesh_spec: MeshSpec
                   ) -> Dict[str, List[bool]]:
    """Per-axis per-leaf rank-variance of a state pytree under a (possibly
    2-D) :class:`MeshSpec` — the 2-D replication seeding: a GraceState
    mem leaf (spec ``P((dp, fsdp))``) varies over BOTH axes, a replicated
    field over neither, and the seeding stays derived from the same
    ``partition_specs`` the real train step shards state with."""
    specs = partition_specs(state_struct, mesh_spec)
    flat_specs = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    flat_state = jax.tree_util.tree_leaves(state_struct)
    if len(flat_specs) != len(flat_state):      # structure drifted — be safe
        return {a: [True] * len(flat_state) for a in mesh_spec.axes}
    return {a: [_spec_mentions(s, a) for s in flat_specs]
            for a in mesh_spec.axes}


def _mesh_of(grace, world: int, fsdp: Optional[int]):
    """Resolve the audit mesh for a config: ``(mesh_spec, axes, dp)``
    where ``axes`` is the ``((name, size), ...)`` AbstractMesh layout and
    ``dp`` the exchange-axis size. A 2-D config (``grace.mesh`` carries
    an fsdp axis, or ``fsdp`` passed explicitly) splits the ``world``
    devices into ``dp = world // fsdp`` exchange groups."""
    mesh_spec = getattr(grace, "mesh", None)
    mesh_spec = MeshSpec.normalize(
        mesh_spec if mesh_spec is not None
        else grace.communicator.axis_name)
    if mesh_spec.is_2d:
        f = int(fsdp) if fsdp else 2
        if world % f:
            raise ValueError(f"fsdp={f} does not divide the audit world "
                             f"{world}")
        dp = world // f
        return mesh_spec, ((mesh_spec.dp_axis, dp),
                           (mesh_spec.fsdp_axis, f)), dp
    return mesh_spec, ((mesh_spec.dp_axis, world),), world


def trace_fn(fn, args: Sequence[Any], *, world: int = 8,
             axis_name: str = DEFAULT_AXIS,
             varying: Optional[Sequence[bool]] = None,
             name: str = "fn", meta: Optional[dict] = None,
             mesh_axes: Optional[Sequence[Tuple[str, int]]] = None,
             varying_axes: Optional[Dict[str, Sequence[bool]]] = None
             ) -> TracedGraph:
    """Trace an arbitrary function inside an AbstractMesh shard_map.

    ``args`` are ShapeDtypeStructs (or arrays) handed to the body
    per-device; ``varying`` flags each *flattened leaf* of ``args`` as
    rank-varying (default: all varying — conservative). This is the
    low-level entry the seeded-bad-graph tests use; config audits go
    through :func:`trace_update` / :func:`trace_train_step`.

    ``mesh_axes`` (``((name, size), ...)``) traces over an N-D mesh
    instead of the 1-D ``(axis_name, world)``; the first axis is the
    exchange axis (``TracedGraph.axis_name``/``world``).
    ``varying_axes`` optionally gives a per-axis mask (defaults to
    ``varying`` for every axis) — how the seeded 2-D replication tests
    express "dp-replicated but fsdp-varying".
    """
    layout = (tuple((str(n), int(s)) for n, s in mesh_axes)
              if mesh_axes is not None else ((axis_name, world),))
    axis_name = layout[0][0]
    world = layout[0][1]
    am = abstract_mesh_nd(layout)
    n_args = len(args)
    sm = shard_map(lambda *a: fn(*a), mesh=am,
                   in_specs=tuple(P() for _ in range(n_args)),
                   out_specs=P(), check_vma=False)
    closed = jax.make_jaxpr(sm)(*args)
    found = _seed_positions(closed, len(jax.tree_util.tree_leaves(
        tuple(args))))
    if found is None:
        raise ValueError("no shard_map equation found in the traced jaxpr")
    body, positions = found
    flat = jax.tree_util.tree_leaves(tuple(args))
    mask = list(varying) if varying is not None else [True] * len(flat)
    if len(mask) != len(flat):
        raise ValueError(f"varying mask has {len(mask)} entries for "
                         f"{len(flat)} flattened arg leaves")
    axis_masks = {a: mask for a, _ in layout}
    if varying_axes:
        for a, m in varying_axes.items():
            m = list(m)
            if len(m) != len(flat):
                raise ValueError(
                    f"varying_axes[{a!r}] has {len(m)} entries for "
                    f"{len(flat)} flattened arg leaves")
            axis_masks[a] = m
    axis_seeds = {a: dict(zip(body.invars, _seeds_from_positions(
        positions, m, len(body.invars))))
        for a, m in axis_masks.items()}
    # Every outer-argument-carrying invar is a dependence root for the
    # low-level entry (the seeded-bad-graph tests treat each arg as one
    # "gradient bucket"); hoisted constants and computed values are not.
    grad_in = ([v for v, p in zip(body.invars, positions)
                if isinstance(p, int)]
               if positions is not None else list(body.invars))
    return TracedGraph(name=name, closed=closed, body=body, world=world,
                       axis_name=axis_name, varying=axis_seeds[axis_name],
                       grad_in=grad_in, meta=dict(meta or {}),
                       mesh_axes=tuple(a for a, _ in layout),
                       axis_sizes={a: s for a, s in layout},
                       varying_axes=axis_seeds)


def trace_update(grace, *, world: int = 8, params=None,
                 name: str = "update", meta: Optional[dict] = None,
                 fsdp: Optional[int] = None) -> TracedGraph:
    """Trace one ``grace_transform`` update (the whole 6-stage pipeline,
    escape cond and telemetry included) at world size ``world``.

    The traced body is exactly what runs inside the real train step's
    shard_map: per-device state in, per-device gradients in, aggregated
    updates and next state out. No devices are touched — state comes from
    ``jax.eval_shape`` over ``init``.

    2-D configs (``grace.mesh`` carries an fsdp axis, or ``fsdp`` given)
    trace over a dp×fsdp AbstractMesh of the same ``world`` devices
    (``dp = world // fsdp``): the gradients seed rank-varying over BOTH
    axes (each device holds its own shard's local gradient), GraceState
    leaves seed from the 2-D ``partition_specs``, and ``TracedGraph.world``
    becomes the dp size — the span every wire/numeric model prices.
    """
    axis_name = grace.communicator.axis_name
    mesh_spec, mesh_axes, dp = _mesh_of(grace, world, fsdp)
    tx = grace.transform(seed=0)
    params = params if params is not None else default_param_structs()
    state_struct = jax.eval_shape(tx.init, params)
    grads_struct = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), params)

    def body(state, grads):
        updates, new_state = tx.update(grads, state, None)
        return updates, new_state

    am = abstract_mesh_nd(mesh_axes)
    sm = shard_map(body, mesh=am, in_specs=(P(), P()),
                   out_specs=(P(), P()), check_vma=False)
    closed = jax.make_jaxpr(sm)(state_struct, grads_struct)
    state_flat = jax.tree_util.tree_leaves(state_struct)
    grads_flat = jax.tree_util.tree_leaves(grads_struct)
    found = _seed_positions(closed, len(state_flat) + len(grads_flat))
    if found is None:
        raise ValueError("no shard_map equation found in the traced update")
    inner, positions = found

    masks = _varying_masks(state_struct, mesh_spec)
    axis_seeds = {}
    for a in mesh_spec.axes:
        mask_a = masks[a] + [True] * len(grads_flat)
        axis_seeds[a] = dict(zip(inner.invars, _seeds_from_positions(
            positions, mask_a, len(inner.invars))))
    state_in = []
    state_in_vars = []
    grad_in = []
    if positions is not None:
        # Body invar carrying outer arg leaf i (hoisted constants shift
        # the real arguments, so positional zip is not enough).
        arg_to_body = {i: p for p, i in enumerate(positions)
                       if isinstance(i, int)}
        paths = _flat_paths(state_struct)
        state_in_vars = [(p, inner.invars[arg_to_body[i]])
                         for i, p in enumerate(paths)
                         if i in arg_to_body]
        if len(state_in_vars) != len(paths):     # a state leaf went missing
            state_in_vars = []
        state_in = [(p, v.aval) for p, v in state_in_vars]
        grad_in = [inner.invars[b] for i, b in sorted(arg_to_body.items())
                   if i >= len(state_flat)]
    # Replicated-by-contract state leaves (spec P() — replicated over
    # EVERY mesh axis): the buffers the memory-footprint pass checks for
    # world-scaling shapes.
    state_replicated = [
        (p, a) for i, (p, a) in enumerate(state_in)
        if not any(masks[ax][i] for ax in mesh_spec.axes)]

    # Body outputs are (updates..., new_state...): the state signature the
    # next step re-traces against is the trailing slice.
    n_state = len(state_flat)
    state_out = []
    state_out_vars = []
    if state_in and len(inner.outvars) >= n_state:
        out_tail = inner.outvars[len(inner.outvars) - n_state:]
        state_out_vars = [(p, v) for (p, _), v in zip(state_in, out_tail)]
        state_out = [(p, v.aval)
                     for (p, _), v in zip(state_in, out_tail)]
    return TracedGraph(name=name, closed=closed, body=inner, world=dp,
                       axis_name=axis_name,
                       varying=axis_seeds[mesh_spec.dp_axis],
                       state_in=state_in, state_out=state_out,
                       grad_in=grad_in, state_replicated=state_replicated,
                       meta=dict(meta or {}),
                       mesh_axes=tuple(mesh_spec.axes),
                       axis_sizes={n: s for n, s in mesh_axes},
                       varying_axes=axis_seeds,
                       state_in_vars=state_in_vars,
                       state_out_vars=state_out_vars,
                       grace_prefixes=_grace_prefixes(state_struct))


def trace_train_step(grace, *, world: int = 8, guard: Optional[dict] = None,
                     consensus=None, name: str = "train_step",
                     meta: Optional[dict] = None,
                     fsdp: Optional[int] = None) -> TracedGraph:
    """Trace a full ``make_train_step`` program (fwd/bwd, optimizer chain,
    optional guard and consensus audit) over an AbstractMesh.

    This is the graph the collective-consistency and bit-exactness passes
    care most about: the guard's skip/rollback selects, the dense-escape
    cond, and the consensus ``lax.cond`` audit gate with its fingerprint
    all_gather and masked-psum repair broadcasts all appear here exactly as
    they would on a pod.
    """
    from grace_tpu.train import TrainState, make_train_step
    from grace_tpu.transform import add_world_axis

    axis_name = grace.communicator.axis_name
    mesh_spec, mesh_axes, dp = _mesh_of(grace, world, fsdp)
    params = default_param_structs()
    dim, classes = _DEFAULT_PARAMS[0][1][0], _DEFAULT_PARAMS[0][1][1]

    def loss_fn(p, batch):
        x, y = batch
        logits = x @ p["w"] + p["b"][:classes]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    tx = optax.chain(grace.transform(seed=0), optax.sgd(0.1))
    if guard is not None:
        from grace_tpu.resilience import guard_transform
        guard_axes = (tuple(mesh_spec.axes) if mesh_spec.is_2d
                      else axis_name)
        tx = guard_transform(tx, axis_name=guard_axes, **guard)

    am = abstract_mesh_nd(mesh_axes)
    abstract = jax.eval_shape(tx.init, params)
    specs = partition_specs(abstract, mesh_spec)
    init_fn = shard_map(lambda p: add_world_axis(tx.init(p)), mesh=am,
                        in_specs=(P(),), out_specs=specs, check_vma=False)
    opt_struct = jax.eval_shape(init_fn, params)
    state_struct = TrainState(params=params, opt_state=opt_struct)
    batch = (jax.ShapeDtypeStruct((dp * 4, dim), jnp.float32),
             jax.ShapeDtypeStruct((dp * 4,), jnp.int32))

    step = make_train_step(loss_fn, tx, mesh=am, axis_name=mesh_spec,
                           donate=False, consensus=consensus)
    closed = jax.make_jaxpr(step)(state_struct, batch)
    state_flat = jax.tree_util.tree_leaves(state_struct)
    batch_flat = jax.tree_util.tree_leaves(batch)
    found = _seed_positions(closed, len(state_flat) + len(batch_flat))
    if found is None:
        raise ValueError("no shard_map equation found in the traced step")
    inner, positions = found

    masks = _varying_masks(state_struct, mesh_spec)
    axis_seeds = {}
    for a in mesh_spec.axes:
        mask_a = masks[a] + [True] * len(batch_flat)
        axis_seeds[a] = dict(zip(inner.invars, _seeds_from_positions(
            positions, mask_a, len(inner.invars))))
    grad_in = []
    state_in_vars = []
    state_out_vars = []
    if positions is not None:
        arg_to_body = {i: p for p, i in enumerate(positions)
                       if isinstance(i, int)}
        grad_in = [inner.invars[b] for i, b in sorted(arg_to_body.items())
                   if i >= len(state_flat)]
        # The step returns (TrainState, loss): the flattened outputs lead
        # with the state leaves in the same path order the inputs carry.
        paths = _flat_paths(state_struct)
        state_in_vars = [(p, inner.invars[arg_to_body[i]])
                         for i, p in enumerate(paths) if i in arg_to_body]
        if len(state_in_vars) != len(paths):
            state_in_vars = []
        elif len(inner.outvars) >= len(paths):
            state_out_vars = list(zip(paths, inner.outvars[:len(paths)]))
    meta = dict(meta or {})
    meta.setdefault("guard", guard)
    meta.setdefault("consensus", consensus)
    return TracedGraph(name=name, closed=closed, body=inner, world=dp,
                       axis_name=axis_name,
                       varying=axis_seeds[mesh_spec.dp_axis],
                       grad_in=grad_in, meta=meta,
                       mesh_axes=tuple(mesh_spec.axes),
                       axis_sizes={n: s for n, s in mesh_axes},
                       varying_axes=axis_seeds,
                       state_in_vars=state_in_vars,
                       state_out_vars=state_out_vars,
                       grace_prefixes=_grace_prefixes(state_struct))
