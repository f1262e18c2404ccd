"""Measured shortlist: real timed steps + the measured≤static sandwich.

The static stage deliberately prices every candidate at the same compute
step (cost.py's wire-dominated model); this stage supplies what it cannot:
each shortlisted candidate's OWN compute cost, from real timed steps of a
real train step on the live mesh. A sample is a window of dependent
steps between two ``jax.block_until_ready`` calls on the host clock, and
every candidate sample is bracketed by a dense baseline sample measured
moments before it, never by a number from another session.

The honesty gate is the measured≤static **overlap sandwich** from
``perf_report --overlap-config``: the winner's step is profiled, the
capture's measured overlap fraction is judged against graft-flow's static
schedulability bound for the SAME config's traced dataflow (+slack). A
violation means the capture's attribution is lying, and the tuner refuses
to stamp the winner (exit 1), because a winner chosen from lying
measurements is exactly the vibes-selection this subsystem exists to kill.

Models: ``"toy"`` is the audit registry's own default param tree (512
params — the model every static number in the funnel was priced on), with
the same linear-softmax loss ``trace_train_step`` audits, which is how
tier-1 drives the whole loop on a CPU mesh in seconds; ``"resnet50"`` is
priced statically only (its step is the benchmark's to measure).
"""

from __future__ import annotations

import statistics
import time
from typing import Any, Dict, List

from grace_tpu.tuning.candidates import Candidate
from grace_tpu.tuning.cost import (TuneTopology, dense_bytes, n_elements,
                                   price_candidate)

__all__ = ["build_model_step", "measure_shortlist", "overlap_sandwich"]


DENSE_ANCHOR = Candidate(
    name="dense", source="generated",
    params={"compressor": "none", "memory": "none",
            "communicator": "allreduce", "fusion": "none"})


def model_structs(model: str = "toy"):
    """Param-tree structs for pricing; must match what
    :func:`build_model_step` trains."""
    import jax

    if model == "toy":
        from grace_tpu.analysis.trace import default_param_structs
        return default_param_structs()
    if model == "resnet50":
        from grace_tpu.models import resnet

        def init():
            params, _ = resnet.init(jax.random.key(0), depth=50,
                                    num_classes=1000)
            return params

        return jax.eval_shape(init)
    raise ValueError(f"unknown model {model!r} — 'toy' or 'resnet50'")


def build_model_step(grace, mesh, model: str = "toy", *, seed: int = 0,
                     per_device_bs: int = 8):
    """(step, state, batch) for one candidate's real train step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from grace_tpu.train import init_train_state, make_train_step

    rng = np.random.default_rng(seed)
    n_dev = len(mesh.devices.flatten())
    if model == "toy":
        from grace_tpu.analysis.trace import default_param_structs
        structs = default_param_structs()
        params = {k: jnp.asarray(rng.normal(size=s.shape).astype(np.float32))
                  for k, s in structs.items()}
        dim, classes = params["w"].shape

        def loss_fn(p, batch):
            x, y = batch
            logits = x @ p["w"] + p["b"][:classes]
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        x = jnp.asarray(rng.normal(
            size=(n_dev * per_device_bs, dim)).astype(np.float32))
        y = jnp.asarray(rng.integers(0, classes,
                                     size=(n_dev * per_device_bs,)))
        batch = (x, y)
    elif model == "resnet50":
        # ResNet-50 needs the stateful step (batch-norm state): the
        # benchmark's cells measure it (benchmarks/run.py). The tuner's
        # in-process measurement keeps the stateless toy step.
        raise NotImplementedError(
            "resnet50 is measured by the benchmark's cells "
            "(benchmarks/run.py); the in-process shortlist uses "
            "model='toy'")
    else:
        raise ValueError(f"unknown model {model!r}")
    tx = optax.chain(grace.transform(seed=seed), optax.sgd(0.1))
    state = init_train_state(params, tx, mesh)
    step = make_train_step(loss_fn, tx, mesh, donate=False)
    return step, state, batch


def _timed_step_s(step, state, batch, *, timed_steps: int,
                  warmup: int) -> tuple:
    """One sample on the host clock: ``timed_steps`` dependent steps
    between two ``jax.block_until_ready`` calls, so the window opens on a
    drained device and closes when the last step's outputs exist —
    returns (step_seconds, new_state)."""
    import jax

    for _ in range(warmup):
        state, loss = step(state, batch)
    jax.block_until_ready((state, loss))
    t0 = time.perf_counter()
    for _ in range(timed_steps):
        state, loss = step(state, batch)
    jax.block_until_ready((state, loss))
    return (time.perf_counter() - t0) / timed_steps, state


def measure_shortlist(shortlisted: List[Candidate], spec: TuneTopology,
                      mesh, *, model: str = "toy", timed_steps: int = 8,
                      repeats: int = 2, seed: int = 0
                      ) -> Dict[str, Any]:
    """Time every shortlisted candidate against an interleaved dense
    baseline; rank by the target-topology projection with each candidate's
    OWN measured compute step substituted into the cost model.

    Returns {"rows", "winner", "skipped"}; ``winner`` is the candidate
    name minimizing ``projected_step_ms`` at the target topology (measured
    compute + per-link wire), the EQuARX-style decision: compute measured
    where we are, wire priced where we're going.
    """
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    structs = model_structs(model)
    dense_b = dense_bytes(structs)
    n_elems = n_elements(structs)

    class _Live:
        def __init__(self, cand):
            self.grace = cand.build()
            self.step, self.state, self.batch = build_model_step(
                self.grace, mesh, model, seed=seed)
            self.warmed = False

        def sample(self):
            warm = 1 if self.warmed else 3
            s, self.state = _timed_step_s(
                self.step, self.state, self.batch,
                timed_steps=timed_steps, warmup=warm)
            self.warmed = True
            return s

    base = _Live(DENSE_ANCHOR)
    rows: List[Dict[str, Any]] = []
    skipped: List[Dict[str, str]] = []
    for cand in shortlisted:
        if cand.tpu_only and not on_tpu:
            skipped.append({"candidate": cand.name,
                            "reason": "tpu_only: interpret-mode Pallas "
                                      "off-chip is a per-element emulation"})
            continue
        def _measure(cand=cand):
            live = _Live(cand)
            samples, bsamples = [], []
            for _ in range(repeats):
                bsamples.append(base.sample())
                samples.append(live.sample())
            return live, samples, bsamples

        try:
            live, samples, bsamples = _measure()
        except Exception as e:                           # noqa: BLE001
            skipped.append({"candidate": cand.name,
                            "verdict": "error",
                            "reason": f"{type(e).__name__}: {str(e)[:200]}"})
            continue
        med = statistics.median(samples)
        base_med = statistics.median(bsamples)
        price = price_candidate(live.grace, structs, spec,
                                base_step_s=med, dense_step_s=base_med)
        rows.append({
            "candidate": cand.name,
            "params": dict(cand.params),
            "measured_step_ms": round(med * 1e3, 4),
            "samples_ms": [round(s * 1e3, 4) for s in samples],
            "baseline_step_ms": round(base_med * 1e3, 4),
            "baseline_samples_ms": [round(s * 1e3, 4) for s in bsamples],
            "measured_speedup_vs_dense": round(base_med / med, 4),
            "same_session": True,
            "projected_step_ms": price["projected_step_ms"],
            "projected_speedup_vs_dense":
                price["predicted_speedup_vs_dense"],
            "ici_bytes": price["ici_bytes"],
            "dcn_bytes": price["dcn_bytes"],
        })
    winner = min(rows, key=lambda r: (r["projected_step_ms"],
                                      r["candidate"]))["candidate"] \
        if rows else None
    return {"rows": rows, "winner": winner, "skipped": skipped,
            "model": model, "timed_steps": timed_steps, "repeats": repeats,
            "measured_world": len(mesh.devices.flatten())}


def overlap_sandwich(candidate: Candidate, mesh, trace_dir: str, *,
                     model: str = "toy", steps: int = 3,
                     seed: int = 0) -> Dict[str, Any]:
    """Profile the winner's real step and close the honesty loop: the
    capture's measured overlap fraction must sit under graft-flow's static
    schedulability bound for the same config's traced dataflow (+slack) —
    ``perf_report --overlap-config``'s gate, run in-process on a capture
    the tuner just made, so a winner is never stamped off a lying trace."""
    import jax

    from grace_tpu.analysis.flow import (OVERLAP_SLACK, overlap_summary,
                                         pass_overlap_schedulability)
    from grace_tpu.analysis.trace import trace_update
    from grace_tpu.profiling import analyze_trace

    grace = candidate.build()
    step, state, batch = build_model_step(grace, mesh, model, seed=seed)
    state, loss = step(state, batch)        # compile outside the capture
    with jax.profiler.trace(str(trace_dir)):
        for _ in range(steps):
            state, loss = step(state, batch)
        jax.block_until_ready(loss)
    doc = analyze_trace(str(trace_dir)).as_dict()
    measured = doc.get("overlap_fraction")
    traced = trace_update(grace, name=candidate.name,
                          meta={"grace": grace,
                                "measured_overlap": measured})
    bound = overlap_summary(traced)["static_overlap_bound"]
    violations = [f.message for f in pass_overlap_schedulability(traced)
                  if "measured overlap" in f.message]
    return {
        "config": candidate.name,
        "measured_overlap": measured,
        "static_overlap_bound": (round(bound, 6)
                                 if bound is not None else None),
        "slack": OVERLAP_SLACK,
        "violations": violations,
        "holds": not violations,
    }
