"""graft-retune: fault-tolerant online re-tuning — config promotion as a
two-phase transaction with automatic rollback.

The adaptive controller (graft-adapt, PR 15) moves along a FIXED ladder
in-graph; the tuner (graft-tune, PR 14) picks a config offline. Neither
answers the production question this module exists for: the workload
drifted — gradients stopped looking like what the stamped config was
tuned on — and the fleet should move to a *different* config without a
restart and without betting the run on an unproven winner. Restarts are
exactly what the resilience stack spent five PRs avoiding; an unproven
winner is exactly what the tuner's funnel exists to prevent. So the
promotion is a **transaction**, built from pieces the stack already
proved, with the elastic drain watchdog's bounded-timeout discipline on
every leg:

* **Drift watch** (:meth:`RetuneController.observe`): windowed
  compression-error means against a baseline learned from the run's own
  healthy windows. Only SUSTAINED drift (``drift_windows`` consecutive
  hot windows) arms a re-tune — one bad window is noise the error
  feedback already absorbs.

* **Decide** (:meth:`RetuneController.propose`): the tuner's static
  funnel + bounded measured shortlist re-run against the live mesh
  (:func:`grace_tpu.tuning.online.online_funnel`); a hung candidate
  measurement lands in the funnel as ``verdict='measure_timeout'``
  instead of stalling the controller.

* **PREPARE** (:meth:`RetuneController.prepare`) — everything that can
  reject the candidate happens BEFORE any live state changes:

  1. lint-audit the candidate config ad-hoc
     (:func:`grace_tpu.analysis.configs.audit_config`) — a config the
     static auditor rejects is never staged;
  2. build the new transform and a fresh state under it, then migrate
     the live :class:`~grace_tpu.transform.GraceState` across configs
     (:func:`~grace_tpu.transform.migrate_grace_state`): replicated
     fields carry bit-exactly, residuals carry where gradient-shaped,
     PowerSGD factors warm-start by column overlap (the rung-invariant
     padded layout makes same-family moves a pure carry), everything
     else takes the PR-3 fresh init;
  3. validate the migrated state against flow pass 7's static footprint
     model at the live world
     (:func:`~grace_tpu.resilience.elastic.validate_resharded`);
  4. checkpoint the last-known-good incumbent state while the fleet is
     whole (``good=True`` — the demotion target), under the bounded
     watchdog.

* **COMMIT** (:meth:`RetuneController.commit`): consensus-gated cutover
  at a drain boundary — one forced fingerprint audit over the migrated
  state (:func:`~grace_tpu.resilience.elastic.rejoin_barrier`) so every
  rank enters the new config bit-identical, priced and recorded like a
  rejoin. The OLD config is retained as the demotion target; the new one
  enters **probation**.

* **Probation** (:meth:`RetuneController.watch` /
  :meth:`RetuneController.demote`): for ``probation_steps`` after the
  cutover, any guard trip or consensus escalation demotes automatically
  — restore the last-known-good checkpoint under the OLD config,
  bit-exact (the PREPARE-time digest is re-checked on restore). A quiet
  probation clears the transaction and the new config becomes the
  incumbent.

Every leg — measure, checkpoint, commit, restore — runs under
:meth:`RetuneController._watchdog`: bounded timeout, retries with
DOUBLED timeout (backoff), a ``retune_timeout`` record per stall, and a
proceed-with-last-known-good exit (abort the promotion / keep the
incumbent / fall back to a fresh old-config init) instead of a hang.
This is PR 16's drain watchdog generalized from one leg to the whole
transaction: the controller can be slow, wrong, or unlucky — it cannot
wedge the run.

Event vocabulary (timeline kind ``retune``): ``retune_drift``,
``retune_measure``, ``retune_prepare``, ``retune_abort``,
``retune_promote``, ``retune_probation_clear``, ``retune_demote``,
``retune_timeout``.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from grace_tpu.core import DEFAULT_AXIS
from grace_tpu.resilience.consensus import normalize_consensus

__all__ = ["StagedPromotion", "RetuneController"]


def state_digest(state) -> str:
    """Order-stable byte digest of every leaf in ``state`` — the
    bit-exactness witness for transactional rollback: recorded at
    PREPARE over the incumbent state, re-computed over the restored
    state at demotion, equal iff the rollback lost nothing."""
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(state):
        arr = np.asarray(jax.device_get(leaf))
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


@dataclasses.dataclass
class StagedPromotion:
    """Everything PREPARE staged, nothing of which is live yet. COMMIT
    consumes it; an abort just drops it (the incumbent state was never
    touched — migration built a NEW tree)."""

    step: int
    old_params: Dict[str, Any]
    new_params: Dict[str, Any]
    grace: Any
    tx: Any
    state: Any                       # migrated TrainState, not yet live
    migration: Dict[str, Any]
    footprint_matches: Optional[bool]
    lint_errors: int
    checkpointed: bool
    lkg_digest: Optional[str]


class RetuneController:
    """Host-side orchestrator of the drift → decide → PREPARE → COMMIT →
    probation → (clear | demote) transaction.

    ``build`` is the run's own chain factory,
    ``build(grace_params) -> (grace, tx)`` — the controller rebuilds
    BOTH sides of every cutover through it, so old and new optimizer
    chains share one pytree structure (the migration map's contract) and
    the guard/consensus wrapping the run trains with is exactly what a
    promoted config trains with. ``params`` is the incumbent's
    grace-params dict (the first demotion target).

    ``consensus`` arms the COMMIT barrier (required for a consensus-
    gated cutover; ``None`` degrades to an unaudited swap for
    single-host tests). ``checkpointer`` is a
    :class:`~grace_tpu.checkpoint.Checkpointer`; without one PREPARE
    cannot record a demotion target and demotion falls back to a fresh
    old-config init (degraded, recorded as ``restored=False``).

    ``leg_timeout_s``/``leg_retries`` bound every transition leg;
    ``None`` runs legs inline (tests that want determinism without
    threads).
    """

    def __init__(self, *, build: Callable[[Dict[str, Any]], Tuple[Any, Any]],
                 params: Dict[str, Any],
                 consensus=None, checkpointer=None, sink=None,
                 window: int = 8, drift_factor: float = 2.0,
                 drift_error: Optional[float] = None,
                 drift_windows: int = 2,
                 probation_steps: int = 24,
                 demote_on: Tuple[str, ...] = ("guard_skip",
                                               "guard_fallback_engaged",
                                               "consensus_escalation"),
                 leg_timeout_s: Optional[float] = None,
                 leg_retries: int = 1,
                 audit_world: int = 8,
                 axis_name: str = DEFAULT_AXIS):
        self.build = build
        self.params = dict(params)
        self.consensus = (normalize_consensus(consensus)
                          if consensus not in (None, False) else None)
        self.checkpointer = checkpointer
        self.sink = sink
        if int(window) < 1:
            raise ValueError(f"window must be >= 1; got {window}")
        self.window = int(window)
        if float(drift_factor) <= 1.0:
            raise ValueError(f"drift_factor must be > 1 (a factor <= 1 "
                             f"re-tunes on healthy noise); got {drift_factor}")
        self.drift_factor = float(drift_factor)
        self.drift_error = (float(drift_error)
                            if drift_error is not None else None)
        self.drift_windows = max(1, int(drift_windows))
        self.probation_steps = int(probation_steps)
        self.demote_on = tuple(demote_on)
        if leg_timeout_s is not None and float(leg_timeout_s) <= 0:
            raise ValueError(f"leg_timeout_s must be positive; "
                             f"got {leg_timeout_s}")
        self.leg_timeout_s = (float(leg_timeout_s)
                              if leg_timeout_s is not None else None)
        if int(leg_retries) < 0:
            raise ValueError(f"leg_retries must be >= 0; got {leg_retries}")
        self.leg_retries = int(leg_retries)
        self.audit_world = int(audit_world)
        self.axis_name = axis_name

        self.phase = "idle"          # idle | prepared | probation
        self.events: List[dict] = []
        self._staged: Optional[StagedPromotion] = None
        self._probation_until: Optional[int] = None
        self._demotion_params: Optional[Dict[str, Any]] = None
        self._lkg_digest: Optional[str] = None
        self._win: List[float] = []
        self._baseline: Optional[float] = None
        self._hot = 0

    # -- plumbing -----------------------------------------------------------
    def _emit(self, event: str, step: int, **payload) -> dict:
        rec = {"event": event, "step": int(step), **payload}
        self.events.append(rec)
        if self.sink is not None:
            self.sink.write(rec)
        return rec

    def _watchdog(self, leg: str, step: int, fn):
        """Run one transition leg bounded: ``(ok, result, timeouts)``.

        The elastic drain watchdog's exact discipline
        (:meth:`~grace_tpu.resilience.elastic.ElasticController._drain_checkpoint`)
        applied to an arbitrary leg: daemon worker, ``done.wait``,
        doubled timeout per retry, one ``retune_timeout`` record per
        stall, and the hung thread abandoned — callers translate
        ``ok=False`` into their leg's proceed-with-last-known-good exit.
        Exceptions from ``fn`` propagate unchanged and are never retried.
        """
        if self.leg_timeout_s is None:
            return True, fn(), 0
        import threading

        timeout = self.leg_timeout_s
        timeouts = 0
        for trial in range(self.leg_retries + 1):
            done = threading.Event()
            out: List[Any] = []
            errs: List[BaseException] = []

            def run():
                try:
                    out.append(fn())
                except BaseException as e:   # noqa: BLE001 — re-raised below
                    errs.append(e)
                finally:
                    done.set()

            threading.Thread(target=run, daemon=True,
                             name=f"grace-retune-{leg}-{trial}").start()
            if done.wait(timeout):
                if errs:
                    raise errs[0]
                return True, out[0], timeouts
            timeouts += 1
            self._emit("retune_timeout", step, leg=leg, attempt=trial + 1,
                       timeout_s=float(timeout),
                       retries_left=self.leg_retries - trial)
            timeout *= 2.0
        return False, None, timeouts

    def _reset_drift(self) -> None:
        self._win.clear()
        self._baseline = None
        self._hot = 0

    # -- drift watch --------------------------------------------------------
    def observe(self, step: int,
                compression_error: Optional[float]) -> bool:
        """Feed one step's compression error (host float from the
        telemetry reader); returns True the first time drift is
        SUSTAINED — ``drift_windows`` consecutive window means above
        ``drift_factor``× the learned baseline (or above the absolute
        ``drift_error`` override). The first full window IS the
        baseline: the controller calibrates on the run's own healthy
        traffic, not on a magic constant."""
        if self.phase != "idle" or compression_error is None:
            return False
        self._win.append(float(compression_error))
        if len(self._win) < self.window:
            return False
        mean = sum(self._win) / len(self._win)
        self._win.clear()
        if self._baseline is None:
            self._baseline = mean
            return False
        drifting = mean > self._baseline * self.drift_factor
        if self.drift_error is not None:
            drifting = drifting or mean > self.drift_error
        if not drifting:
            self._hot = 0
            return False
        self._hot += 1
        if self._hot < self.drift_windows:
            return False
        self._hot = 0
        self._emit("retune_drift", step, window_mean=mean,
                   baseline=self._baseline,
                   drift_factor=self.drift_factor,
                   drift_windows=self.drift_windows)
        return True

    # -- decide -------------------------------------------------------------
    def propose(self, step: int, mesh, topology, **funnel_kwargs
                ) -> Optional[Dict[str, Any]]:
        """Re-run the tuner's funnel against the live mesh (bounded) and
        return the :func:`~grace_tpu.tuning.online.online_funnel` doc,
        or None when the whole decision leg timed out / produced no
        winner — both mean "stay on the incumbent"."""
        from grace_tpu.tuning.online import online_funnel

        ok, doc, timeouts = self._watchdog(
            "measure", step,
            lambda: online_funnel(topology, mesh, **funnel_kwargs))
        if not ok:
            self._emit("retune_abort", step, leg="measure",
                       reason="measure leg exceeded its bounded wait — "
                              "keeping the incumbent config",
                       timeouts=timeouts)
            return None
        measured = doc["measured"]
        self._emit("retune_measure", step, winner=doc["winner"],
                   measured=len(measured["rows"]),
                   skipped=len(measured["skipped"]),
                   measure_timeouts=sum(
                       1 for s in measured["skipped"]
                       if s.get("verdict") == "measure_timeout"),
                   timeouts=timeouts)
        if doc["winner"] is None:
            return None
        return doc

    # -- PREPARE ------------------------------------------------------------
    def prepare(self, step: int, state, mesh,
                candidate_params: Dict[str, Any]
                ) -> Optional[StagedPromotion]:
        """Stage a promotion without touching live state; returns the
        staged transaction, or None when any PREPARE gate rejected the
        candidate (the run continues on the incumbent untouched)."""
        if self.phase == "probation":
            raise RuntimeError("prepare() during probation — clear or "
                               "demote the in-flight promotion first.")
        from grace_tpu.analysis.configs import audit_config
        from grace_tpu.train import init_train_state
        from grace_tpu.transform import migrate_grace_state

        candidate_params = dict(candidate_params)
        world = len(mesh.devices.flatten())

        # Gate 1: the static auditor. A config the seven lint passes
        # reject offline is never staged online. Escape/adapt-carrying
        # candidates skip wire_reconciliation exactly like their registry
        # entries do: a dense fallback or a ladder makes "the" wire cost
        # multi-modal by design (telemetry prices the flip per rung).
        from grace_tpu.analysis.passes import PASS_NAMES
        passes = tuple(PASS_NAMES)
        if candidate_params.get("escape") or candidate_params.get("adapt"):
            passes = tuple(p for p in PASS_NAMES
                           if p != "wire_reconciliation")
        findings = audit_config({"name": "retune-candidate",
                                 "params": dict(candidate_params),
                                 "passes": passes},
                                world=self.audit_world)
        errors = [f for f in findings if f.severity == "error"]
        if errors:
            self._emit("retune_abort", step, leg="lint",
                       reason=errors[0].message[:200],
                       lint_errors=len(errors))
            return None

        # Gate 2: build + migrate. The fresh init is a NEW tree — the
        # incumbent state is read, never written, so an abort below
        # costs nothing.
        grace, tx = self.build(candidate_params)
        fresh = init_train_state(state.params, tx, mesh, self.axis_name)
        try:
            migrated_opt, mig = migrate_grace_state(state.opt_state,
                                                    fresh.opt_state)
        except ValueError as e:
            self._emit("retune_abort", step, leg="migrate",
                       reason=str(e)[:200])
            return None
        staged_state = state._replace(opt_state=migrated_opt)

        # Gate 3: the migrated state must match the static footprint
        # model at the live world under the NEW config — the elastic
        # re-shard's validation, reused across configs.
        from grace_tpu.resilience.elastic import validate_resharded
        try:
            footprint = validate_resharded(staged_state, grace,
                                           state.params, world)["matches"]
        except ValueError as e:
            self._emit("retune_abort", step, leg="footprint",
                       reason=str(e)[:200])
            return None

        # Leg 4 (bounded): checkpoint the incumbent while the fleet is
        # whole — the demotion target. A stalled backend does not block
        # the promotion (an older good checkpoint may exist on disk),
        # it only degrades the rollback guarantee, and the event says so.
        checkpointed, ck_timeouts = False, 0
        lkg_digest = None
        if self.checkpointer is not None:
            lkg_digest = state_digest(state)

            def save():
                self.checkpointer.save(step, state, force=True, good=True)
                self.checkpointer.wait()

            checkpointed, _, ck_timeouts = self._watchdog(
                "prepare_checkpoint", step, save)

        staged = StagedPromotion(
            step=step, old_params=dict(self.params),
            new_params=candidate_params, grace=grace, tx=tx,
            state=staged_state, migration=mig,
            footprint_matches=footprint, lint_errors=0,
            checkpointed=checkpointed, lkg_digest=lkg_digest)
        self._staged = staged
        self.phase = "prepared"
        self._emit("retune_prepare", step,
                   candidate=candidate_params.get("compressor"),
                   lint_errors=0, footprint_matches=footprint,
                   checkpointed=checkpointed,
                   checkpoint_timeouts=ck_timeouts,
                   mem_carried=mig["mem"]["carried"],
                   mem_overlap=mig["mem"]["overlap"],
                   mem_fresh=mig["mem"]["fresh"],
                   comp_carried=mig["comp"]["carried"],
                   comp_overlap=mig["comp"]["overlap"],
                   comp_fresh=mig["comp"]["fresh"])
        return staged

    # -- COMMIT -------------------------------------------------------------
    def commit(self, step: int, mesh):
        """Consensus-gated cutover of the staged promotion at a drain
        boundary. Returns ``(state, (grace, tx), event)`` with the
        migrated state now live and probation armed — or None when the
        commit leg timed out (staged promotion dropped, incumbent keeps
        running: the abort path IS the last-known-good path, because
        PREPARE never touched live state)."""
        if self.phase != "prepared" or self._staged is None:
            raise RuntimeError("commit() without a staged promotion — "
                               "call prepare() first.")
        staged = self._staged

        def cutover():
            if self.consensus is None:
                return staged.state, None
            from grace_tpu.resilience.elastic import rejoin_barrier
            return rejoin_barrier(staged.state, self.consensus, mesh,
                                  self.axis_name)

        ok, result, timeouts = self._watchdog("commit", step, cutover)
        if not ok:
            self._staged = None
            self.phase = "idle"
            self._emit("retune_abort", step, leg="commit",
                       reason="commit barrier exceeded its bounded wait "
                              "— promotion dropped, incumbent config "
                              "keeps running",
                       timeouts=timeouts)
            return None
        state, report = result
        self._demotion_params = staged.old_params
        self._lkg_digest = staged.lkg_digest
        self.params = dict(staged.new_params)
        self._probation_until = step + self.probation_steps
        self.phase = "probation"
        self._reset_drift()
        barrier = {}
        if report is not None:
            barrier = {k: report[k] for k in
                       ("repairs", "barrier_repairs", "audits",
                        "replica_variants", "fingerprint_bytes",
                        "repair_bytes") if k in report}
        event = self._emit("retune_promote", step,
                           old=staged.old_params.get("compressor"),
                           new=staged.new_params.get("compressor"),
                           probation_until=self._probation_until,
                           commit_timeouts=timeouts, **barrier)
        self._staged = None
        return state, (staged.grace, staged.tx), event

    # -- probation ----------------------------------------------------------
    def watch(self, step: int, records) -> Optional[str]:
        """Feed the run's sink records during probation; returns the
        triggering event name the moment any guard trip / consensus
        escalation demands demotion (call :meth:`demote`), else None.
        A probation window that expires quiet clears the transaction —
        the promoted config becomes the incumbent for good."""
        if self.phase != "probation":
            return None
        for rec in records or ():
            ev = str(rec.get("event", ""))
            if any(ev == t or ev.startswith(t + "_") for t in self.demote_on):
                return ev
        if (self._probation_until is not None
                and step >= self._probation_until):
            self.phase = "idle"
            self._probation_until = None
            self._emit("retune_probation_clear", step,
                       config=self.params.get("compressor"))
        return None

    def demote(self, step: int, state, mesh, *, trigger: str):
        """Automatic rollback: restore the last-known-good checkpoint
        under the OLD config, bit-exact (digest-checked against the
        PREPARE-time witness). A stalled or absent restore falls back to
        a fresh old-config init carrying the CURRENT params — degraded
        (residuals restart, probation steps kept) but alive, and the
        event records ``restored=False``. Returns
        ``(state, (grace, tx), event)``."""
        if self.phase != "probation" or self._demotion_params is None:
            raise RuntimeError("demote() without a probationary promotion.")
        old_params = self._demotion_params
        grace, tx = self.build(old_params)
        from grace_tpu.train import init_train_state

        restored_state = None
        restored, timeouts, bit_exact = False, 0, None
        if self.checkpointer is not None:
            def restore():
                target = init_train_state(state.params, tx, mesh,
                                          self.axis_name)
                return self.checkpointer.restore_last_good(target)

            ok, out, timeouts = self._watchdog("demote_restore", step,
                                               restore)
            if ok:
                restored_state, restored = out, True
                if self._lkg_digest is not None:
                    bit_exact = state_digest(restored_state) == \
                        self._lkg_digest
        if restored_state is None:
            restored_state = init_train_state(state.params, tx, mesh,
                                              self.axis_name)
        self.params = dict(old_params)
        self._demotion_params = None
        self._lkg_digest = None
        self._probation_until = None
        self.phase = "idle"
        self._reset_drift()
        event = self._emit("retune_demote", step, trigger=trigger,
                           restored=restored, bit_exact=bit_exact,
                           restore_timeouts=timeouts,
                           config=old_params.get("compressor"))
        return restored_state, (grace, tx), event
