"""In-graph training resilience: step guard, graceful degradation, chaos.

Three pieces, designed to compose with the existing triad without touching
it (SURVEY.md has no counterpart — the reference assumes a fault-free run):

* :func:`guard_transform` — optax wrapper around the *whole* chain that
  detects non-finite / exploding post-exchange updates in-graph and skips
  the step atomically (params, optimizer state, and every GraceState
  mem/comp leaf roll back together). See ``resilience/guard.py`` for why
  ``optax.apply_if_finite`` cannot do this for error-feedback state.
* the dense escape hatch — ``grace_transform(escape=...)`` +
  ``fallback_after``/``fallback_steps`` on the guard: after K consecutive
  bad steps the exchange degrades to a dense (none/fp16 + psum) all-reduce
  for M cooldown steps, then compression re-arms.
* :mod:`~grace_tpu.resilience.chaos` — deterministic fault injectors
  (NaN/Inf implants, payload bit-flips, single-rank faults, stale
  residuals) as Compressor/Communicator wrappers, plus
  :class:`ChaosParams`, a host-side single-rank SDC injector for
  params/opt-state at rest.
* :mod:`~grace_tpu.resilience.consensus` — the cross-rank consistency
  auditor + in-graph self-healing (fingerprint → compare → masked-psum
  repair → escalate), for the silent single-rank divergence the guard's
  post-exchange checks are structurally blind to.
* :mod:`~grace_tpu.resilience.elastic` — preemption-tolerant elastic
  training: graft-watch-driven drain, world-resize GraceState re-sharding
  (replicated fields carried bit-exactly, per-rank residuals/rings
  re-initialized at the new W), slice-granular hierarchical shrink, and
  the consensus-gated rejoin barrier.
* :mod:`~grace_tpu.resilience.adapt` — the graft-adapt in-graph adaptive
  compression controller: a replicated degradation ladder between the
  static codec and the dense escape, tightening within one window of an
  error spike (before the guard would trip) and loosening with
  hysteresis when gradients go quiet.
"""

from __future__ import annotations

from typing import Optional

import optax

from grace_tpu.resilience.adapt import (AdaptConfig, AdaptMonitor,
                                        AdaptState, adapt_report,
                                        normalize_adapt)
from grace_tpu.resilience.chaos import (ChaosCommunicator, ChaosCompressor,
                                        ChaosParams)
from grace_tpu.resilience.consensus import (ConsensusConfig, audit_report,
                                            consensus_step, fingerprint_tree,
                                            force_audit, normalize_consensus)
from grace_tpu.resilience.elastic import (ElasticController, ResizePlan,
                                          implant_stale_replica, plan_resize,
                                          rejoin_barrier, replica_variants,
                                          reshard_grace_state,
                                          validate_resharded)
from grace_tpu.resilience.guard import (GUARD_ROLLBACK_EXCLUDED,
                                        GUARD_SCAN_EXCLUDED_TYPES,
                                        GuardState, guard_transform)

__all__ = ["GUARD_ROLLBACK_EXCLUDED", "GUARD_SCAN_EXCLUDED_TYPES",
           "GuardState", "guard_transform", "guarded_chain",
           "ChaosCompressor", "ChaosCommunicator", "ChaosParams",
           "ConsensusConfig", "consensus_step", "fingerprint_tree",
           "force_audit", "audit_report", "normalize_consensus",
           "ElasticController", "ResizePlan", "plan_resize",
           "reshard_grace_state", "validate_resharded", "rejoin_barrier",
           "implant_stale_replica", "replica_variants",
           "AdaptConfig", "AdaptState", "AdaptMonitor", "adapt_report",
           "normalize_adapt"]


def guarded_chain(grace, *txs: optax.GradientTransformation,
                  seed: int = 0,
                  max_norm: Optional[float] = None,
                  check_state: bool = True,
                  fallback_after: Optional[int] = None,
                  fallback_steps: Optional[int] = None
                  ) -> optax.GradientTransformation:
    """``guard_transform(optax.chain(grace.transform(seed), *txs))`` with the
    guard's cross-rank flag reduction wired to the grace mesh axis.

    ``grace`` is a :class:`~grace_tpu.helper.Grace` bundle; configure its
    ``escape`` field (e.g. ``escape='fp16'`` in ``grace_from_params``) to
    arm the dense fallback window that ``fallback_after``/``fallback_steps``
    control.
    """
    inner = optax.chain(grace.transform(seed=seed), *txs)
    # On a 2-D dp×fsdp mesh the bad-step OR must span the WHOLE mesh (a
    # tuple of axis names — lax.psum reduces over both): per-rank state
    # scans can disagree across fsdp shards too, and the fallback window
    # must open fleet-wide or the per-shard exchanges desync.
    mesh = getattr(grace, "mesh", None)
    axes = (tuple(mesh.axes) if getattr(mesh, "is_2d", False)
            else grace.communicator.axis_name)
    return guard_transform(inner,
                           max_norm=max_norm,
                           check_state=check_state,
                           fallback_after=fallback_after,
                           fallback_steps=fallback_steps,
                           axis_name=axes)
