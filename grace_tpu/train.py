"""Training-step builders: the shard_map harness around the compressed pipeline.

Replaces the reference's L5 integration layer (SURVEY.md §1): where GRACE
patches Horovod's DistributedOptimizer to fire per-parameter hooks during
backward (patch_files/horovod/torch/__init__.py:107-161), grace-tpu builds
one jitted SPMD train step: per-device gradients are computed inside
`shard_map` over the ``'data'`` mesh axis and the optax chain (containing
`grace_transform`) performs the compressed collective exchange. XLA overlaps
the compression collectives with remaining backward compute — the async
send/receive split of the torch backend (grace_dl/torch/__init__.py:37-58)
falls out of the compiler for free.

State layout: params / model state / non-grace optimizer state are
replicated; GraceState mem/comp leaves (per-rank residuals/momenta, see
grace_tpu/transform.py) carry a leading world axis sharded over the mesh.
Always build states with :func:`init_train_state` /
:func:`init_stateful_train_state` (passing the mesh) so the layout matches
what the step functions expect.

Resilience wiring: pass a guarded chain
(``grace_tpu.resilience.guarded_chain(grace, optax.sgd(...), ...)``) as the
``optimizer`` — nothing else changes. The guard's skip/rollback/fallback
logic traces into the same jitted shard_map step (its ``GuardState`` rides
inside ``opt_state``; ``partition_specs`` recurses through it to the
GraceState leaves), and the loop reads health via
``grace_tpu.utils.metrics.guard_report(state)`` / reacts via
``grace_tpu.checkpoint.divergence_rollback``.
"""

from __future__ import annotations

import itertools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from grace_tpu.core import DEFAULT_AXIS
from grace_tpu.parallel import replicated, shard_map
from grace_tpu.telemetry import host
from grace_tpu.telemetry.scopes import (STAGE_APPLY, STAGE_CONSENSUS,
                                        STAGE_FWD_BWD, STAGE_OPTIMIZER,
                                        trace_stage)
from grace_tpu.transform import (MeshSpec, add_world_axis, partition_specs,
                                 strip_world_axis)

__all__ = ["TrainState", "StatefulTrainState", "make_train_step",
           "make_stateful_train_step", "make_eval_step",
           "init_train_state", "init_stateful_train_state",
           "init_opt_state", "warmup_schedule"]


class TrainState(NamedTuple):
    params: Any
    opt_state: Any


class StatefulTrainState(NamedTuple):
    params: Any
    model_state: Any   # e.g. BatchNorm running stats
    opt_state: Any


def _apply_param_specs(specs, state, param_specs):
    """Substitute the caller's fsdp param sharding into the derived spec
    pytree: the ``params`` field of a (Stateful)TrainState gets
    ``param_specs`` (a spec pytree matching params, or one PartitionSpec
    for every leaf); everything else keeps the ``partition_specs``
    contract."""
    if param_specs is None:
        return specs
    if isinstance(param_specs, P):
        param_specs = jax.tree_util.tree_map(lambda _: param_specs,
                                             state.params)
    return specs._replace(params=param_specs)


_steps_built = itertools.count(1)


def _lazy_sharded_step(device_step, mesh: Mesh, axis_name, donate: bool,
                       param_specs=None):
    """jit(shard_map(device_step)) with state specs derived from the first
    state actually passed in — the spec pytree depends on where GraceState
    nodes sit inside the (optimizer-dependent) state structure.
    ``axis_name`` may be a :class:`~grace_tpu.transform.MeshSpec`; the
    batch shards over its dp axis and ``param_specs`` (sharded-model
    track) overrides the params portion of the state specs."""
    mesh_spec = MeshSpec.normalize(axis_name)
    cache = {}
    # JAX's compile events tell programs apart by name alone, so each step
    # built in a process gets its own: the first keeps ``device_step`` (and
    # with it the HLO module's name and the compile cache's key), the n-th
    # is ``device_step_<n>``.
    n = next(_steps_built)
    if n > 1:
        device_step.__name__ = f"{device_step.__name__}_{n}"

    def step(state, batch):
        key = jax.tree_util.tree_structure(state)
        fn = cache.get(key)
        if fn is None:
            with host.span("wrap_step"):
                specs = _apply_param_specs(
                    partition_specs(state, mesh_spec), state, param_specs)
                sharded = shard_map(
                    device_step, mesh=mesh,
                    in_specs=(specs, P(mesh_spec.dp_axis)),
                    out_specs=(specs, P()),
                    check_vma=False)
                fn = jax.jit(sharded, donate_argnums=(0,) if donate else ())
            cache[key] = fn
        return fn(state, batch)

    # Callers (benchmarks/harness.Program, chip_smoke.py) reach the jitted
    # functions here to compile ahead of time (fn.lower(...).compile())
    # without wrapping the step again.
    step.jit_cache = cache
    # What grace_tpu.telemetry.compiles.summary is asked for, for this step
    # and no other of the process.
    step.fun_name = device_step.__name__
    return step


@host.spanned("make_train_step")
def make_train_step(loss_fn: Callable[[Any, Any], jax.Array],
                    optimizer: optax.GradientTransformation,
                    mesh: Mesh,
                    axis_name=DEFAULT_AXIS,
                    donate: bool = True,
                    remat: bool = False,
                    consensus=None,
                    param_specs=None):
    """Build ``step(state, batch) -> (state, loss)``.

    ``loss_fn(params, batch)`` must return the mean loss over its *local*
    batch shard; gradients are therefore local means, and the communicator's
    ``average`` semantics reproduce the reference's global mean
    (grace_dl/dist/__init__.py:51-52 `/ world_size`).

    ``batch`` is a pytree whose leaves are sharded on their leading dim over
    ``axis_name`` (the DistributedSampler analog, SURVEY.md §2.5).

    ``remat=True`` wraps the loss in ``jax.checkpoint``: activations are
    recomputed during backward instead of held in HBM — the standard
    FLOPs-for-memory trade when activation footprint (not the gradient
    exchange this library compresses) is the limiting factor.

    ``consensus`` (None | True | int ``audit_every`` | dict |
    ``ConsensusConfig``): run the cross-rank consistency audit + self-heal
    (:mod:`grace_tpu.resilience.consensus`) after ``apply_updates``, inside
    the same jitted shard_map step. Requires the grace transform to have
    been built with ``consensus=...`` so ``GraceState`` carries the
    ``AuditState`` (clear in-graph error otherwise).

    ``axis_name`` may be a :class:`~grace_tpu.transform.MeshSpec` for the
    sharded-model (dp×fsdp) track; pass ``param_specs`` (a PartitionSpec
    pytree matching params, or one spec for every leaf) to shard params —
    and the param-shaped slots the consensus audit repairs — over the
    fsdp axis. ``loss_fn`` then sees its *local* param shards and owns
    any cross-shard collectives (tensor-parallel style, over
    ``mesh_spec.fsdp_axis``); the consensus audit and the loss pmean stay
    on the dp axis, so fingerprints match replicas per fsdp shard.
    """
    if remat:
        loss_fn = jax.checkpoint(loss_fn)
    consensus = _normalize_consensus(consensus)
    mesh_spec = MeshSpec.normalize(axis_name)
    dp = mesh_spec.dp_axis

    def device_step(state: TrainState, batch):
        opt_state = strip_world_axis(state.opt_state)
        # Stage scopes name the phases in an XLA device trace (see
        # grace_tpu.telemetry.scopes); the grace transform inside
        # optimizer.update adds its own compress/exchange/decompress spans.
        with trace_stage(STAGE_FWD_BWD):
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        with trace_stage(STAGE_OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state,
                                                  state.params)
        with trace_stage(STAGE_APPLY):
            params = optax.apply_updates(state.params, updates)
        if consensus is not None:
            with trace_stage(STAGE_CONSENSUS):
                params, opt_state = _consensus_step(
                    (params, opt_state), consensus, dp)
        loss = lax.pmean(loss, dp)
        return TrainState(params, add_world_axis(opt_state)), loss

    return _lazy_sharded_step(device_step, mesh, mesh_spec, donate,
                              param_specs=param_specs)


def _normalize_consensus(consensus):
    """Lazy import: resilience.consensus imports transform (as this module
    does), so the dependency must stay function-local to avoid a cycle."""
    if consensus is None or consensus is False:
        return None
    from grace_tpu.resilience.consensus import normalize_consensus
    return normalize_consensus(consensus)


def _consensus_step(tree, config, axis_name):
    from grace_tpu.resilience.consensus import consensus_step
    return consensus_step(tree, config, axis_name)


@host.spanned("make_stateful_train_step")
def make_stateful_train_step(loss_fn: Callable[[Any, Any, Any],
                                               Tuple[jax.Array, Any]],
                             optimizer: optax.GradientTransformation,
                             mesh: Mesh,
                             axis_name=DEFAULT_AXIS,
                             donate: bool = True,
                             sync_model_state: bool = True,
                             remat: bool = False,
                             consensus=None,
                             param_specs=None):
    """Like :func:`make_train_step` for models with non-param state (BN stats).

    ``loss_fn(params, model_state, batch) -> (loss, new_model_state)``.
    ``sync_model_state`` pmeans the new model state across ranks so running
    statistics stay replicated (the reference's DDP examples leave BN stats
    rank-local and implicitly use rank 0's at save time; replication is the
    deterministic version of the same thing, and the stats are tiny).
    ``remat``/``consensus`` as in :func:`make_train_step` — the audit
    fingerprints model state too (it is replicated), so BN-stat divergence
    is detected and repaired alongside params.
    """
    if remat:
        loss_fn = jax.checkpoint(loss_fn)
    consensus = _normalize_consensus(consensus)
    mesh_spec = MeshSpec.normalize(axis_name)
    dp = mesh_spec.dp_axis

    def device_step(state: StatefulTrainState, batch):
        opt_state = strip_world_axis(state.opt_state)
        with trace_stage(STAGE_FWD_BWD):
            (loss, mstate), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(
                state.params, state.model_state, batch)
        if sync_model_state:
            mstate = jax.tree_util.tree_map(
                lambda m: lax.pmean(m, dp), mstate)
        with trace_stage(STAGE_OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state,
                                                  state.params)
        with trace_stage(STAGE_APPLY):
            params = optax.apply_updates(state.params, updates)
        if consensus is not None:
            with trace_stage(STAGE_CONSENSUS):
                params, mstate, opt_state = _consensus_step(
                    (params, mstate, opt_state), consensus, dp)
        loss = lax.pmean(loss, dp)
        return (StatefulTrainState(params, mstate, add_world_axis(opt_state)),
                loss)

    return _lazy_sharded_step(device_step, mesh, mesh_spec, donate,
                              param_specs=param_specs)


@host.spanned("init_opt_state")
def init_opt_state(params: Any, optimizer: optax.GradientTransformation,
                   mesh: Mesh, axis_name=DEFAULT_AXIS,
                   param_specs=None) -> Any:
    """Optimizer state in the global layout: grace mem/comp leaves get their
    leading world axis, sharded over the mesh (``P(dp)``, or
    ``P((dp, fsdp))`` on a 2-D :class:`~grace_tpu.transform.MeshSpec`);
    the rest is replicated. With ``param_specs`` (sharded-model track),
    ``optimizer.init`` runs on each device's LOCAL param shard — the
    grace residuals it allocates are therefore per-shard by construction,
    which is the "error feedback lives on the shard owner" layout.

    Public because it is also the elastic re-shard's fresh-init hook
    (:func:`grace_tpu.resilience.elastic.reshard_grace_state`): a world
    resize re-initializes the per-rank GraceState payload by running
    exactly this init on the NEW mesh, then grafts the old replicated
    fields back via :func:`grace_tpu.transform.carry_replicated`."""
    mesh_spec = MeshSpec.normalize(axis_name)
    if param_specs is None:
        in_spec: Any = P()
        local_params = params
    else:
        if isinstance(param_specs, P):
            param_specs = jax.tree_util.tree_map(lambda _: param_specs,
                                                 params)
        in_spec = param_specs

        def shard_of(leaf, spec):
            shape = list(jnp.shape(leaf))
            for d, entry in enumerate(spec):
                if entry is None:
                    continue
                names = entry if isinstance(entry, tuple) else (entry,)
                for n in names:
                    shape[d] //= mesh.shape[n]
            return jax.ShapeDtypeStruct(tuple(shape),
                                        jnp.result_type(leaf))

        local_params = jax.tree_util.tree_map(shard_of, params, param_specs)
    abstract = jax.eval_shape(optimizer.init, local_params)
    specs = partition_specs(abstract, mesh_spec)
    init_fn = shard_map(
        lambda p: add_world_axis(optimizer.init(p)),
        mesh=mesh, in_specs=(in_spec,), out_specs=specs, check_vma=False)
    return jax.jit(init_fn)(params)


# Back-compat private alias (pre-elastic callers).
_init_opt_state = init_opt_state


@host.spanned("init_train_state")
def init_train_state(params: Any, optimizer: optax.GradientTransformation,
                     mesh: Mesh, axis_name=DEFAULT_AXIS,
                     param_specs=None) -> TrainState:
    if param_specs is None:
        placed = jax.device_put(params, replicated(mesh))
    else:
        from jax.sharding import NamedSharding
        if isinstance(param_specs, P):
            param_specs = jax.tree_util.tree_map(lambda _: param_specs,
                                                 params)
        placed = jax.device_put(
            params, jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), param_specs,
                is_leaf=lambda x: isinstance(x, P)))
    return TrainState(
        params=placed,
        opt_state=_init_opt_state(params, optimizer, mesh, axis_name,
                                  param_specs=param_specs))


@host.spanned("init_stateful_train_state")
def init_stateful_train_state(params: Any, model_state: Any,
                              optimizer: optax.GradientTransformation,
                              mesh: Mesh, axis_name: str = DEFAULT_AXIS
                              ) -> StatefulTrainState:
    return StatefulTrainState(
        params=jax.device_put(params, replicated(mesh)),
        model_state=jax.device_put(model_state, replicated(mesh)),
        opt_state=_init_opt_state(params, optimizer, mesh, axis_name))


def warmup_schedule(base_lr: float, world_size: int, warmup_steps: int,
                    after: Optional[Callable[[Any], Any]] = None):
    """Linear-scaling LR warmup: ramp ``base_lr`` → ``base_lr * world_size``.

    The pure-JAX analog of the reference's LearningRateWarmupCallback
    (examples/tensorflow/tensorflow2_keras_mnist.py:83-88, Goyal et al.
    gradual warmup): large data-parallel batches want the linearly-scaled
    rate ``base_lr * world_size``, reached gradually over ``warmup_steps``
    to avoid early divergence. Returns an optax schedule; ``after(t)``
    optionally supplies the post-warmup schedule as a function of steps
    *since warmup end* (default: hold the scaled rate).

    The boundary step belongs to the post-warmup schedule: ``count ==
    warmup_steps`` returns ``after(0)``, not the warm ramp (pinned by
    tests/test_resilience.py::test_warmup_boundary_handoff). And
    ``warmup_steps=0`` means no warmup at all: ``after(count)`` from step
    0, or the scaled rate if ``after`` is None.
    """
    scaled = base_lr * world_size

    def schedule(count):
        if warmup_steps <= 0:
            return (jnp.asarray(scaled, jnp.float32) if after is None
                    else after(count))
        frac = jnp.minimum(count / jnp.maximum(warmup_steps, 1), 1.0)
        warm = base_lr + (scaled - base_lr) * frac
        if after is None:
            return warm
        return jnp.where(count < warmup_steps, warm,
                         after(count - warmup_steps))

    return schedule


def make_eval_step(metric_fn: Callable[[Any, Any], Any], mesh: Mesh,
                   axis_name: str = DEFAULT_AXIS):
    """Build ``eval_step(params, batch) -> mesh-averaged metrics``.

    The cross-rank metric averaging idiom of the reference
    (examples/torch/pytorch_mnist.py:163-166 metric_average via allreduce).
    """

    def device_eval(params, batch):
        metrics = metric_fn(params, batch)
        return jax.tree_util.tree_map(
            lambda m: lax.pmean(m, axis_name), metrics)

    sharded = shard_map(
        device_eval, mesh=mesh,
        in_specs=(P(), P(axis_name)),
        out_specs=P(),
        check_vma=False)
    return jax.jit(sharded)
