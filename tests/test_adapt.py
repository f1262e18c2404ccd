"""graft-adapt (ISSUE 15): the in-graph adaptive compression controller.

The properties pinned here are the acceptance criteria:

* the controller is pure replicated state math: tighten within one window
  of a mean/peak spike or guard evidence, loosen only after
  ``quiet_windows`` quiet windows with no hold (hysteresis — it cannot
  flap at window rate), escalate-and-hold on a guard trip;
* a quiet adaptive run IS the static top-rung run, bitwise — the ladder's
  steady state matches the hand-picked config exactly (the throughput
  half of "matches the best static config", with the tuner's
  price-equality pin alongside);
* telemetry prices every row at the ACTIVE rung (per-rung wire plan —
  the dense-fallback flip generalized), the ``ici+dcn == wire_bytes``
  identity survives, and the guard's fallback flag forces rung 0;
* the policy state is replicated GraceState bookkeeping: ``P()`` specs,
  inside the consensus fingerprint, rolled back bitwise by the guard,
  re-initialized by an elastic world resize;
* the static stack covers the ladder: the three registered adapt configs
  audit clean over every pass, flow pass 6 fires on an unsafe
  shared-scale RUNG (not just the base codec), and the tuner's funnel
  gates every rung's legality and numeric bounds;
* ``chaos_smoke --adapt`` proves tighten-before-guard ordering from the
  artifact, and the convergence floors hold — the routed-transformer
  track (the PR-14 leftover) and the adaptive-vs-static pair.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from grace_tpu import compressors as C
from grace_tpu import grace_from_params
from grace_tpu.resilience import guarded_chain
from grace_tpu.resilience.adapt import (AdaptConfig, AdaptMonitor,
                                        AdaptState, adapt_advance,
                                        adapt_init, adapt_report,
                                        adapt_signal_bytes, normalize_adapt)
from grace_tpu.resilience.consensus import _grace_nodes
from grace_tpu.telemetry import TelemetryReader
from grace_tpu.train import init_train_state, make_train_step
from grace_tpu.transform import (GRACE_REPLICATED_FIELDS, grace_transform,
                                 partition_specs)

W = 8

pytestmark = pytest.mark.adapt


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _advance_window(state, cfg, err_mean, err_peak, fallback=False,
                    start_count=0):
    """Run ``cfg.window`` controller steps with a constant signal; returns
    the post-boundary state."""
    for i in range(cfg.window):
        state = adapt_advance(state, cfg, jnp.asarray(start_count + i,
                                                      jnp.int32),
                              jnp.asarray(fallback, jnp.bool_),
                              _f32(err_mean), _f32(err_peak))
    return state


def _cfg(**kw):
    base = dict(ladder=(C.QSGDCompressor(quantum_num=127,
                                         use_pallas=False),
                        C.QSGDCompressor(quantum_num=15,
                                         use_pallas=False)),
                window=4, tighten_error=0.5, tighten_peak=0.75,
                loosen_error=0.25, quiet_windows=2, hold_windows=3)
    base.update(kw)
    return AdaptConfig(**base)


# ---------------------------------------------------------------------------
# config normalization + validation
# ---------------------------------------------------------------------------

def test_normalize_adapt_spellings():
    base = C.QSGDCompressor(quantum_num=15, use_pallas=False)
    for spec in (True, 7, {"window": 7}):
        cfg = normalize_adapt(spec, base)
        assert cfg.ladder[-1] == base          # base appended as top rung
        assert cfg.n_rungs == 2                # dense + base
    cfg = normalize_adapt(7, base)
    assert cfg.window == 7
    # Idempotent when the ladder already ends with the base codec.
    again = normalize_adapt(cfg, base)
    assert again.ladder == cfg.ladder
    # A declared ladder keeps its order, base on top.
    gentle = C.QSGDCompressor(quantum_num=127, use_pallas=False)
    cfg = normalize_adapt({"ladder": [gentle]}, base)
    assert cfg.ladder == (gentle, base) and cfg.top_rung == 2
    assert normalize_adapt(None, base) is None
    assert normalize_adapt(False, base) is None
    with pytest.raises(TypeError):
        normalize_adapt("yes", base)


def test_adapt_config_validation():
    with pytest.raises(ValueError, match="window"):
        _cfg(window=0)
    with pytest.raises(ValueError, match="hysteresis"):
        _cfg(tighten_error=0.3, loosen_error=0.3)
    with pytest.raises(ValueError, match="tighten_peak"):
        _cfg(tighten_peak=0.1)
    with pytest.raises(ValueError, match="quiet_windows"):
        _cfg(quiet_windows=0)
    with pytest.raises(ValueError, match="hold_windows"):
        _cfg(hold_windows=-1)
    with pytest.raises(ValueError, match="start_rung"):
        normalize_adapt(_cfg(start_rung=9),
                        C.QSGDCompressor(quantum_num=15, use_pallas=False))


def test_adapt_build_requirements():
    """The transform's own gates: escape is rung 0, telemetry's error is
    the signal, and routes are outside the rung plan."""
    comp = C.QSGDCompressor(quantum_num=15, use_pallas=False)
    from grace_tpu.comm import Allgather
    from grace_tpu.memories import NoneMemory
    kw = dict(compressor=comp, memory=NoneMemory(),
              communicator=Allgather())
    with pytest.raises(ValueError, match="escape"):
        grace_transform(**kw, adapt=True, telemetry=True)
    with pytest.raises(ValueError, match="compression_error"):
        grace_transform(**kw, adapt=True, escape=C.FP16Compressor(),
                        telemetry={"compression_error": False})
    with pytest.raises(ValueError, match="telemetry"):
        grace_transform(**kw, adapt=True, escape=C.FP16Compressor())
    with pytest.raises(ValueError, match="routes"):
        grace_transform(**kw, adapt=True, escape=C.FP16Compressor(),
                        telemetry=True,
                        routes=[("x", (comp, NoneMemory(), Allgather()))])


# ---------------------------------------------------------------------------
# controller semantics (pure replicated state math)
# ---------------------------------------------------------------------------

def test_tighten_on_mean_spike_within_one_window():
    cfg = _cfg()
    a = adapt_init(cfg)
    assert int(a.rung) == cfg.top_rung == 2
    a = _advance_window(a, cfg, err_mean=0.9, err_peak=0.9)
    assert int(a.rung) == 1 and int(a.tightens) == 1
    assert int(a.escalations) == 0
    # Window accumulators reset at the boundary.
    assert float(a.err_sum) == 0.0 and float(a.err_peak) == 0.0


def test_tighten_on_peak_spike_alone():
    """The worst-rank channel: a single drifting rank raises the pmax but
    barely moves the mean — the controller must still tighten."""
    cfg = _cfg()
    a = _advance_window(adapt_init(cfg), cfg, err_mean=0.1, err_peak=0.9)
    assert int(a.rung) == 1 and int(a.tightens) == 1


def test_hysteresis_band_holds_rung():
    """A signal between loosen_error and tighten_error moves nothing, in
    either direction, for any number of windows."""
    cfg = _cfg()
    a = adapt_init(cfg)
    for w in range(4):
        a = _advance_window(a, cfg, err_mean=0.4, err_peak=0.4,
                            start_count=w * cfg.window)
    assert int(a.rung) == cfg.top_rung
    assert int(a.tightens) == 0 and int(a.loosens) == 0
    assert int(a.quiet) == 0                  # the band is not "quiet"


def test_loosen_needs_consecutive_quiet_windows():
    cfg = _cfg()
    a = adapt_init(cfg)._replace(rung=jnp.asarray(0, jnp.int32))
    a = _advance_window(a, cfg, 0.0, 0.0)
    assert int(a.rung) == 0 and int(a.quiet) == 1   # one quiet: no move
    a = _advance_window(a, cfg, 0.0, 0.0, start_count=cfg.window)
    assert int(a.rung) == 1 and int(a.loosens) == 1  # second quiet: loosen
    assert int(a.quiet) == 0                  # counter restarts per rung
    # An interleaved spike resets the quiet streak.
    a = _advance_window(a, cfg, 0.9, 0.9, start_count=2 * cfg.window)
    assert int(a.rung) == 0
    a = _advance_window(a, cfg, 0.0, 0.0, start_count=3 * cfg.window)
    assert int(a.rung) == 0 and int(a.quiet) == 1


def test_guard_evidence_escalates_and_holds():
    """A step under the guard's fallback flag tightens at the boundary
    AND freezes loosening for hold_windows — the ladder floor was too
    loose."""
    cfg = _cfg()
    a = adapt_init(cfg)
    a = _advance_window(a, cfg, 0.0, 0.0, fallback=True)
    assert int(a.rung) == 1 and int(a.escalations) == 1
    assert int(a.hold) == cfg.hold_windows
    # Quiet windows now pass but the hold blocks loosening until it
    # decays (one per boundary).
    for w in range(cfg.hold_windows):
        a = _advance_window(a, cfg, 0.0, 0.0,
                            start_count=(w + 1) * cfg.window)
        assert int(a.rung) == 1, f"loosened during hold (window {w})"
    a = _advance_window(a, cfg, 0.0, 0.0,
                        start_count=(cfg.hold_windows + 1) * cfg.window)
    assert int(a.rung) == 2 and int(a.loosens) == 1


def test_rung_floor_is_dense():
    cfg = _cfg()
    a = adapt_init(cfg)
    for w in range(5):
        a = _advance_window(a, cfg, 0.9, 0.9, start_count=w * cfg.window)
    assert int(a.rung) == 0                   # clamped at the dense floor


def test_nonfinite_signal_reads_as_spike_not_poison():
    cfg = _cfg()
    a = _advance_window(adapt_init(cfg), cfg, err_mean=float("nan"),
                        err_peak=float("inf"))
    assert int(a.rung) == 1                   # tightened
    assert np.isfinite(float(a.err_sum))      # accumulators stay finite


# ---------------------------------------------------------------------------
# state contract: replicated, fingerprinted, repaired, resharded
# ---------------------------------------------------------------------------

def test_adapt_is_replicated_grace_state():
    assert "adapt" in GRACE_REPLICATED_FIELDS
    grc = _adaptive_grace()
    tx = grc.transform(seed=0)
    state = jax.eval_shape(tx.init, {"w": jnp.zeros((20, 4), jnp.float32)})
    specs = partition_specs(state, "data")
    for leaf in jax.tree_util.tree_leaves(
            specs.adapt, is_leaf=lambda x: isinstance(x, P)):
        assert leaf == P()
    # The consensus fingerprint covers it: two states differing only in
    # the commanded rung fingerprint differently.
    from grace_tpu.resilience.consensus import (fingerprint_tree,
                                                replicated_view)
    live = tx.init({"w": jnp.zeros((20, 4), jnp.float32)})
    assert live.adapt is not None
    moved = live._replace(adapt=live.adapt._replace(
        rung=live.adapt.rung - 1))
    fp_a = np.asarray(fingerprint_tree(replicated_view(live)))
    fp_b = np.asarray(fingerprint_tree(replicated_view(moved)))
    assert not np.array_equal(fp_a, fp_b)


def _adaptive_grace(**adapt_overrides):
    spec = {"window": 4, "ladder": [{"quantum_num": 127}],
            "tighten_error": 0.5, "tighten_peak": 0.75,
            "loosen_error": 0.25, "quiet_windows": 2, "hold_windows": 2}
    spec.update(adapt_overrides)
    return grace_from_params({
        "compressor": "qsgd", "quantum_num": 15, "use_pallas": False,
        "memory": "none", "communicator": "allgather",
        "escape": "fp16", "telemetry": 16, "adapt": spec})


def _ls_problem(seed=0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(20, 4)).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(64, 20)).astype(np.float32))
    y = jnp.asarray(np.argmax(np.asarray(x) @ w_true, axis=1)
                    .astype(np.int32))

    def loss_fn(p, b):
        xb, yb = b
        return optax.softmax_cross_entropy_with_integer_labels(
            xb @ p["w"], yb).mean()

    return loss_fn, (x, y)


def test_quiet_adaptive_run_is_bitwise_static_top_rung(mesh):
    """The steady state IS the static config: with thresholds no healthy
    signal crosses, the ladder never leaves the top rung and the adaptive
    run's params equal the static (escape+telemetry, no adapt) run's
    bit-for-bit — same codec, same rng derivation, same exchange."""
    loss_fn, batch = _ls_problem()
    static = {"compressor": "qsgd", "quantum_num": 15, "use_pallas": False,
              "memory": "none", "communicator": "allgather",
              "escape": "fp16", "telemetry": 16}

    def run(params_dict):
        grc = grace_from_params(params_dict)
        tx = optax.chain(grc.transform(seed=0), optax.sgd(0.05))
        state = init_train_state({"w": jnp.zeros((20, 4), jnp.float32)},
                                 tx, mesh)
        step = make_train_step(loss_fn, tx, mesh, donate=False)
        for _ in range(6):
            state, _ = step(state, batch)
        return np.asarray(state.params["w"])

    w_static = run(static)
    w_adapt = run({**static, "adapt": {
        "window": 4, "ladder": [{"quantum_num": 127}],
        "tighten_error": 50.0, "tighten_peak": 75.0,
        "loosen_error": 25.0}})
    np.testing.assert_array_equal(w_static, w_adapt)


def test_live_spike_tightens_and_telemetry_prices_per_rung(mesh):
    """End-to-end over the mesh: an aggressive-topk ladder on random
    gradients (rel error ~1) tightens at the first boundary; every
    telemetry row's wire bytes equal the ACTIVE rung's static plan plus
    the controller's signal cost, and ici+dcn == wire_bytes survives."""
    loss_fn, batch = _ls_problem()
    grc = grace_from_params({
        "compressor": "topk", "compress_ratio": 0.01, "memory": "residual",
        "communicator": "allgather", "escape": "fp16", "telemetry": 16,
        "adapt": {"window": 3, "ladder": [{"compress_ratio": 0.25}],
                  "tighten_error": 0.5, "tighten_peak": 0.75,
                  "loosen_error": 0.25}})
    tx = optax.chain(grc.transform(seed=0), optax.sgd(0.05))
    state = init_train_state({"w": jnp.zeros((20, 4), jnp.float32)},
                             tx, mesh)
    step = make_train_step(loss_fn, tx, mesh, donate=False)
    for _ in range(8):
        state, _ = step(state, batch)
    rep = adapt_report(state)
    assert rep["tightens"] >= 1 and rep["rung"] < 2
    rows = TelemetryReader(None, every=1).flush(state)
    rows = [r for r in rows if "adapt_rung" in r]
    assert rows

    # Static per-rung expectation: payload bytes through each rung's own
    # schedule (the escape psum at rung 0, the allgather above it) + the
    # signal reductions' cost.
    from grace_tpu.comm import Allreduce
    from grace_tpu.utils.metrics import payload_nbytes
    struct = jax.ShapeDtypeStruct((20, 4), jnp.float32)
    plans = {0: Allreduce().recv_wire_bytes(
        payload_nbytes(C.FP16Compressor(), struct), 80, W)}
    for ri, comp in enumerate(grc.adapt.ladder, start=1):
        pb = payload_nbytes(comp, struct)
        plans[ri] = grc.communicator.recv_wire_bytes(pb, 80, W)
    sig = adapt_signal_bytes(W)
    for r in rows:
        rung = int(r["adapt_rung"])
        assert rung in (0, 1, 2)
        assert r["adapt_bytes"] == float(sig)
        assert r["wire_bytes"] == float(plans[rung] + sig)
        assert r["wire_bytes_ici"] + r["wire_bytes_dcn"] == r["wire_bytes"]
    # The recorded rung trajectory actually moved (the tighten is
    # observable from the ring, which is what AdaptMonitor diffs).
    assert len({int(r["adapt_rung"]) for r in rows}) > 1


def test_fallback_flag_forces_dense_rung_and_escape_pricing(mesh):
    """The guard's fallback flag routes the ladder to rung 0: the row
    records adapt_rung 0 and the escape psum's wire bill."""
    from grace_tpu.transform import set_fallback_flag

    loss_fn, batch = _ls_problem()
    grc = _adaptive_grace()
    tx = optax.chain(grc.transform(seed=0), optax.sgd(0.05))
    state = init_train_state({"w": jnp.zeros((20, 4), jnp.float32)},
                             tx, mesh)
    step = make_train_step(loss_fn, tx, mesh, donate=False)
    state, _ = step(state, batch)
    state = state._replace(opt_state=set_fallback_flag(state.opt_state,
                                                       True))
    state, _ = step(state, batch)
    rows = TelemetryReader(None, every=1).flush(state)
    fb_rows = [r for r in rows if r.get("fallback")]
    assert fb_rows, "the fallback step left no row"
    from grace_tpu.comm import Allreduce
    from grace_tpu.utils.metrics import payload_nbytes
    struct = jax.ShapeDtypeStruct((20, 4), jnp.float32)
    esc_b = payload_nbytes(C.FP16Compressor(), struct)
    esc_wire = Allreduce().recv_wire_bytes(esc_b, 80, W)
    for r in fb_rows:
        assert int(r["adapt_rung"]) == 0
        assert r["wire_bytes"] == float(esc_wire + adapt_signal_bytes(W))
        assert r["wire_bytes_ici"] + r["wire_bytes_dcn"] == r["wire_bytes"]


def test_guard_rollback_keeps_adapt_state_bitwise(mesh):
    """A guard-skipped step rolls the policy state back with everything
    else: under total NaN injection (no fallback arming) the controller
    never advances."""
    from grace_tpu.resilience import ChaosCommunicator

    loss_fn, batch = _ls_problem()
    grc = _adaptive_grace()
    grc = dataclasses.replace(grc, communicator=ChaosCommunicator(
        inner=grc.communicator, nan_prob=1.0, rank=0, seed=1))
    tx = guarded_chain(grc, optax.sgd(0.05))
    state = init_train_state({"w": jnp.zeros((20, 4), jnp.float32)},
                             tx, mesh)
    step = make_train_step(loss_fn, tx, mesh, donate=False)
    for _ in range(6):
        state, _ = step(state, batch)
    rep = adapt_report(state)
    init_rep = {"rung": 2, "tightens": 0, "loosens": 0, "escalations": 0,
                "hold": 0, "quiet": 0, "last_change_step": -1}
    assert rep == init_rep
    from grace_tpu.utils.metrics import guard_report
    assert guard_report(state)["notfinite_count"] == 6


def test_elastic_reshard_reinitializes_adapt(mesh):
    """A world resize carries count/rng bit-exactly but RE-INITIALIZES
    the policy state — the windowed statistics and operating rung were
    learned at the old world's signal profile."""
    from grace_tpu.parallel import data_parallel_mesh
    from grace_tpu.resilience import reshard_grace_state

    loss_fn, batch = _ls_problem()
    grc = _adaptive_grace()
    # Thresholds the healthy signal crosses, so the rung MOVES before
    # the resize — proving re-init, not carry.
    grc2 = dataclasses.replace(grc, adapt=dataclasses.replace(
        grc.adapt, tighten_error=1e-6, loosen_error=1e-7,
        tighten_peak=1e-6))
    tx = optax.chain(grc2.transform(seed=0), optax.sgd(0.05))
    params = {"w": jnp.zeros((20, 4), jnp.float32)}
    state = init_train_state(params, tx, mesh)
    step = make_train_step(loss_fn, tx, mesh, donate=False)
    for _ in range(5):
        state, _ = step(state, batch)
    rep = adapt_report(state)
    assert rep["tightens"] >= 1 and rep["rung"] < 2

    new_mesh = data_parallel_mesh(jax.devices()[:4])
    tx_new = optax.chain(grc2.transform(seed=0), optax.sgd(0.05))
    resharded = reshard_grace_state(state, tx_new, mesh, new_mesh)
    rep2 = adapt_report(resharded)
    assert rep2 == {"rung": 2, "tightens": 0, "loosens": 0,
                    "escalations": 0, "hold": 0, "quiet": 0,
                    "last_change_step": -1}
    # ...while the replicated clock carried bit-exactly.
    graces = _grace_nodes(resharded.opt_state)
    assert int(np.asarray(graces[0].count).reshape(-1)[0]) == 5


def test_mismatched_rung_state_structure_raises():
    """A ladder whose rung threads a different compressor-state structure
    (PowerSGD's Q vs topk's None) is rejected with the named error, not
    an opaque lax.switch TypeError."""
    from grace_tpu.analysis.trace import trace_update

    grc = grace_from_params({
        "compressor": "topk", "compress_ratio": 0.1, "memory": "residual",
        "communicator": "allgather", "escape": "fp16", "telemetry": True})
    base = grc.compressor
    bad = AdaptConfig(ladder=(C.PowerSGDCompressor(rank=2), base),
                      window=4)
    grc = dataclasses.replace(grc, adapt=bad)
    with pytest.raises(ValueError, match="identical mem/comp state"):
        trace_update(grc, world=W, name="bad-ladder")


def _mlp_params(rng):
    return {
        "w1": jnp.asarray(rng.normal(scale=0.3, size=(32, 16)),
                          jnp.float32),
        "b1": jnp.zeros((16,), jnp.float32),
        "w2": jnp.asarray(rng.normal(scale=0.3, size=(16, 8)), jnp.float32),
        "b2": jnp.zeros((8,), jnp.float32),
    }


def _mlp_loss(p, b):
    x, y = b
    h = jnp.tanh(x @ p["w1"] + p["b1"])
    logits = h @ p["w2"] + p["b2"]
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def _mlp_batch(rng, n=16):
    return (jnp.asarray(rng.normal(size=(n, 32)).astype(np.float32)),
            jnp.asarray(rng.integers(0, 8, size=(n,)).astype(np.int32)))


def test_powersgd_ladder_states_padded_to_max_rank(mesh, rng):
    """The rung-invariant comp-state layout: a PowerSGD ladder pads every
    per-direction leaf to the LADDER's max rank so one ``lax.switch``
    dispatches all rungs over one state shape."""
    grc = grace_from_params({"compressor": "powersgd", "compress_rank": 2,
                             "memory": "powersgd",
                             "communicator": "allreduce",
                             "escape": "fp16", "telemetry": 16,
                             "adapt": {"window": 5,
                                       "ladder": [{"compress_rank": 4}]}})
    tx = optax.chain(grc.transform(seed=0), optax.sgd(0.05))
    state = init_train_state(_mlp_params(rng), tx, mesh)
    ranks = {leaf.shape[-1]
             for g in _grace_nodes(state.opt_state)
             for leaf in jax.tree_util.tree_leaves(g.comp)
             if hasattr(leaf, "ndim") and leaf.ndim >= 2}
    assert ranks == {4}, (
        f"comp-state last-axis ranks {ranks}: every rung must share the "
        "ladder max (4) so rank moves are mask flips, not reshapes")
    step = make_train_step(_mlp_loss, tx, mesh, donate=False)
    for _ in range(3):
        state, loss = step(state, _mlp_batch(rng))
    assert np.isfinite(float(loss))


# ---------------------------------------------------------------------------
# static analysis: registry clean, rungs audited
# ---------------------------------------------------------------------------

@pytest.mark.analysis
def test_adapt_registry_configs_audit_clean():
    from grace_tpu.analysis.configs import AUDIT_CONFIGS, audit_config

    names = {"adapt-homoqsgd-ring", "adapt-topk-hier",
             "adapt-guard-consensus"}
    entries = [e for e in AUDIT_CONFIGS if e["name"] in names]
    assert len(entries) == 3
    for e in entries:
        findings = audit_config(e)
        assert findings == [], (e["name"], [f.message for f in findings])


@pytest.mark.analysis
def test_shared_scale_rung_bound_fires_statically():
    """Flow pass 6 audits EVERY reachable rung: a ladder whose gentle
    8-bit rung cannot cover the world fires even though the base (top)
    rung is safe — and the same config at a small world is clean."""
    from grace_tpu.analysis import flow
    from grace_tpu.analysis.trace import TracedGraph

    grc = grace_from_params({
        "compressor": "homoqsgd", "quantum_num": 7, "accum_dtype": "int32",
        "memory": "residual", "communicator": "ring", "fusion": "flat",
        "escape": "fp16", "telemetry": True,
        "adapt": {"window": 5, "ladder": [
            {"quantum_num": 127, "accum_dtype": "int16"}]}})
    rung1 = grc.adapt.ladder[0]
    bound = rung1.payload_sum_max_world()
    base_bound = grc.compressor.payload_sum_max_world()
    assert bound < 512 <= base_bound    # only the RUNG is unsafe at 512

    def fake_trace(world):
        return TracedGraph(name="adapt-rung-bound", closed=None,
                           body=None, world=world, axis_name="data",
                           varying={}, meta={"grace": grc})

    findings = flow._shared_scale_findings(fake_trace(512))
    assert len(findings) == 1
    assert "HomoQSGDCompressor" in findings[0].message
    assert dict(findings[0].details)["payload_sum_max_world"] == bound
    assert flow._shared_scale_findings(fake_trace(8)) == []


# ---------------------------------------------------------------------------
# tuner: rung-schedule pricing + per-rung gates
# ---------------------------------------------------------------------------

@pytest.mark.tune
def test_adaptive_candidate_priced_at_steady_state_matches_static():
    """The acceptance criterion's throughput half, statically: the
    adaptive candidate's projected step time equals the static top-rung
    config's (the controller is free at steady state in the wire model),
    and the funnel record carries the full rung schedule."""
    from grace_tpu.tuning.cost import TuneTopology, price_candidate

    structs = {"w": jax.ShapeDtypeStruct((4096, 64), jnp.float32)}
    spec = TuneTopology(world=256, slice_size=8)
    static = grace_from_params({
        "compressor": "homoqsgd", "quantum_num": 7, "memory": "residual",
        "communicator": "ring", "fusion": "flat"})
    adaptive = grace_from_params({
        "compressor": "homoqsgd", "quantum_num": 7, "memory": "residual",
        "communicator": "ring", "fusion": "flat", "escape": "fp16",
        "telemetry": 16,
        "adapt": {"window": 25, "ladder": [{"quantum_num": 127}]}})
    p_static = price_candidate(static, structs, spec)
    p_adapt = price_candidate(adaptive, structs, spec)
    assert (p_adapt["projected_step_ms"]
            == p_static["projected_step_ms"])
    assert p_adapt["steady_state_rung"] == 2
    rungs = p_adapt["rung_prices"]
    assert [r["rung"] for r in rungs] == [0, 1, 2]
    assert rungs[0]["codec"] == "FP16Compressor"      # the dense escape
    # Degrading never gets cheaper (this homoqsgd ladder TIES across all
    # rungs — int16 accumulator width is quantum-independent, the whole
    # reason THC-style bit-width switching is free here: the rungs trade
    # quality, not bytes) and the top rung's payload is the static
    # config's exactly.
    assert (rungs[2]["projected_step_ms"] <= rungs[1]["projected_step_ms"]
            <= rungs[0]["projected_step_ms"])
    assert rungs[2]["payload_bytes"] == p_static["payload_bytes"]


@pytest.mark.tune
def test_funnel_gates_every_rung():
    from grace_tpu.tuning.candidates import Candidate, candidate_legal
    from grace_tpu.tuning.cost import TuneTopology
    from grace_tpu.tuning.prune import numeric_verdict

    # An int16-accum 8-bit rung dies at W=512 even though the base rung
    # is int32-safe — the numeric gate names the rung.
    grc = grace_from_params({
        "compressor": "homoqsgd", "quantum_num": 7, "accum_dtype": "int32",
        "memory": "residual", "communicator": "ring", "fusion": "flat",
        "escape": "fp16", "telemetry": True,
        "adapt": {"window": 5, "ladder": [
            {"quantum_num": 127, "accum_dtype": "int16"}]}})
    assert numeric_verdict(grc, TuneTopology(world=8)) is None
    verdict = numeric_verdict(grc, TuneTopology(world=512))
    assert verdict and "adapt rung" in verdict
    # A rung codec the communicator rejects at build/step time dies at
    # the capability gate with the rung named.
    cand = Candidate("bad-adapt-rung", {
        "compressor": "qsgd", "quantum_num": 15, "use_pallas": False,
        "memory": "none", "communicator": "ring", "fusion": "flat",
        "escape": "fp16", "telemetry": True,
        "adapt": {"window": 5, "ladder": [{"compressor": "onebit"}]}})
    legal, reason, _ = candidate_legal(cand, TuneTopology(world=8))
    assert not legal and "adapt rung" in reason


@pytest.mark.tune
def test_generated_adaptive_variant_is_legal_and_priced():
    from grace_tpu.tuning.candidates import (candidate_legal,
                                             generated_variants)
    from grace_tpu.tuning.cost import TuneTopology, price_candidate

    spec = TuneTopology(world=8)
    cands = [c for c in generated_variants(spec)
             if c.name == "tune-adapt-homoqsgd4-ring"]
    assert len(cands) == 1
    legal, reason, grace = candidate_legal(cands[0], spec)
    assert legal, reason
    price = price_candidate(grace, {"w": jax.ShapeDtypeStruct(
        (512,), jnp.float32)}, spec)
    assert "rung_prices" in price and len(price["rung_prices"]) == 3


# ---------------------------------------------------------------------------
# host side: monitor, timeline, report
# ---------------------------------------------------------------------------

def test_adapt_monitor_emits_transitions_and_skips_fallback():
    mon = AdaptMonitor()
    rows = [
        {"step": 0, "adapt_rung": 2.0, "fallback": 0.0},
        {"step": 1, "adapt_rung": 2.0, "fallback": 0.0},
        {"step": 2, "adapt_rung": 1.0, "fallback": 0.0},   # tighten
        {"step": 3, "adapt_rung": 0.0, "fallback": 1.0},   # guard window:
        {"step": 4, "adapt_rung": 1.0, "fallback": 0.0},   # not a policy
        {"step": 5, "adapt_rung": 2.0, "fallback": 0.0},   # move; loosen
        {"event": "watch", "step": 5},                     # ignored
        {"step": 6, "adapt_rung": -1.0},                   # unarmed row
    ]
    events = mon.observe(rows)
    assert [(e["event"], e["step"]) for e in events] == [
        ("adapt_tighten", 2), ("adapt_loosen", 5)]
    from grace_tpu.telemetry.timeline import Timeline, classify
    assert classify({"event": "adapt_tighten"}) == "adapt"
    tl = Timeline.from_records(rows[:6] + events)
    assert tl.first("adapt").record["event"] == "adapt_tighten"
    assert tl.summary()["first_adapt_step"] == 2


def test_telemetry_report_renders_adapt_section():
    import importlib.util as ilu
    spec = ilu.spec_from_file_location(
        "telemetry_report_adapt_under_test",
        os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                     "telemetry_report.py"))
    report = ilu.module_from_spec(spec)
    spec.loader.exec_module(report)
    records = [{"step": i, "adapt_rung": float(2 - (i >= 3)),
                "adapt_bytes": 14.0, "wire_bytes": 100.0,
                "dense_bytes": 336.0} for i in range(6)]
    events = [{"event": "adapt_tighten", "step": 3, "rung": 1,
               "from_rung": 2}]
    text = report.render(None, records, events)
    assert "== adapt (graft-adapt rung transitions) ==" in text
    assert "1 tighten(s), 0 loosen(s)" in text
    assert "dwell" in text
    doc = report.build_doc(None, records, events)
    assert doc["adapt_events"] == events
    assert events[0] not in doc["guard_events"]


# ---------------------------------------------------------------------------
# chaos smoke e2e + evidence
# ---------------------------------------------------------------------------

def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chaos_smoke_adapt_under_test",
        os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                     "chaos_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    return smoke


@pytest.mark.chaos
def test_chaos_smoke_adapt_tighten_before_guard(tmp_path):
    """The --adapt scenario end to end: drift → tighten within one window
    with the guard silent, quiet → loosen, NaN → guard trip + escalation,
    with the tighten-before-guard ordering proven from the artifact's
    unified timeline and the ADAPT evidence doc written."""
    smoke = _load_smoke()
    out = tmp_path / "adapt_chaos.jsonl"
    ev = tmp_path / "ADAPT_LAST.json"
    rc = smoke.main(["--adapt", "--steps", "72", "--batch", "16",
                     "--adapt-window", "6", "--telemetry-every", "6",
                     "--telemetry-out", str(out), "--adapt-out", str(ev)])
    assert rc == 0
    doc = json.loads(ev.read_text())
    assert doc["ordering_ok"] is True
    assert doc["tighten"]["within_one_window"] is True
    assert doc["tighten"]["count"] >= 1 and doc["loosen"]["count"] >= 1
    assert doc["escalations"] >= 1
    assert doc["first_adapt_step"] < doc["first_guard_step"]

    from grace_tpu.telemetry.timeline import Timeline
    tl = Timeline.from_jsonl(str(out))
    kinds = tl.summary()["kind_counts"]
    assert kinds.get("adapt", 0) >= 2 and kinds.get("guard", 0) >= 1
    first_adapt = next(e for e in tl.kinds("adapt") if e.step is not None)
    first_guard = next(e for e in tl.kinds("guard") if e.step is not None)
    assert first_adapt.step < first_guard.step


# ---------------------------------------------------------------------------
# convergence floors: the routed transformer track + adaptive vs static
# ---------------------------------------------------------------------------

def test_routed_transformer_track_convergence_floor(mesh):
    """The PR-14 leftover: the bert_routed_rscatter-shaped track (big
    leaves ride topk through the per-shard reduce-scatter, ln/bias leaves
    ride dense fp16 psum) pinned against the dense reference's floor on a
    CPU-smoke-sized problem."""
    rng = np.random.default_rng(11)
    w_true = rng.normal(size=(24, 6)).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(64, 24)).astype(np.float32))
    y = jnp.asarray(np.argmax(np.asarray(x) @ w_true, axis=1)
                    .astype(np.int32))

    def loss_fn(p, b):
        xb, yb = b
        h = jnp.tanh(xb @ p["emb"] * p["ln_scale"] + p["bias"])
        return optax.softmax_cross_entropy_with_integer_labels(
            h @ p["head"], yb).mean()

    params = {"emb": jnp.asarray(rng.normal(scale=0.3, size=(24, 16)),
                                 jnp.float32),
              "ln_scale": jnp.ones((16,), jnp.float32),
              "bias": jnp.zeros((16,), jnp.float32),
              "head": jnp.asarray(rng.normal(scale=0.3, size=(16, 6)),
                                  jnp.float32)}

    def final_loss(p_dict):
        grc = grace_from_params(p_dict)
        tx = optax.chain(grc.transform(seed=0), optax.sgd(0.3))
        state = init_train_state(jax.tree_util.tree_map(jnp.copy, params),
                                 tx, mesh)
        step = make_train_step(loss_fn, tx, mesh, donate=False)
        loss = None
        for _ in range(60):
            state, loss = step(state, (x, y))
        return float(loss)

    dense = final_loss({"compressor": "fp16", "memory": "none",
                        "communicator": "allreduce"})
    routed = final_loss({
        "compressor": "topk", "compress_ratio": 0.25,
        "memory": "residual", "communicator": "rscatter",
        "route": [("*ln*", {"compressor": "fp16", "memory": "none",
                            "communicator": "allreduce"}),
                  ("*bias*", {"compressor": "fp16", "memory": "none",
                              "communicator": "allreduce"})]})
    assert dense < 1.0, dense              # the reference itself converged
    assert routed < dense + 0.1, (routed, dense)


def test_adaptive_matches_static_convergence_floor(mesh):
    """The acceptance criterion's accuracy half: the self-tuning config
    reaches the hand-picked static config's final loss on a real
    trajectory (here bitwise-equal would also hold — the quiet ladder
    never leaves the top rung — but the floor comparison is the stated
    contract and survives a change of thresholds)."""
    loss_fn, batch = _ls_problem(seed=3)

    def final_loss(extra):
        grc = grace_from_params({
            "compressor": "homoqsgd", "quantum_num": 7,
            "memory": "residual", "communicator": "ring",
            "fusion": "flat", **extra})
        tx = optax.chain(grc.transform(seed=0), optax.sgd(0.3))
        state = init_train_state({"w": jnp.zeros((20, 4), jnp.float32)},
                                 tx, mesh)
        step = make_train_step(loss_fn, tx, mesh, donate=False)
        loss = None
        for _ in range(60):
            state, loss = step(state, batch)
        return float(loss), state

    static, _ = final_loss({})
    adaptive, state = final_loss({
        "escape": "fp16", "telemetry": 16,
        "adapt": {"window": 10, "ladder": [{"quantum_num": 127}],
                  "tighten_error": 5.0, "tighten_peak": 7.5,
                  "loosen_error": 2.5}})
    assert static < 0.8, static
    assert adaptive < static + 0.05, (adaptive, static)
    assert adapt_report(state)["rung"] == 2   # held the steady state
