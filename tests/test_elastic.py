"""graft-elastic tests: world-resize re-sharding, slice-granular shrink,
the consensus-gated rejoin barrier, the drain controller, and the
chaos_smoke --elastic lifecycle (ISSUE 11).

The re-shard contract under test, per GraceState field family:

* ``mem`` error-feedback residuals — re-ZEROED at the new world (the PR-3
  zeroing rationale, fleet-wide);
* ``comp`` compressor state — re-INITIALIZED by ``init_state`` (zeros are
  not a valid PowerSGD Q);
* ``telem``/``watch`` rings — re-ALLOCATED at the new world with their
  step/wraparound counters reset;
* replicated bookkeeping (count, rng_key, fallback, audit) and everything
  outside GraceState (params, optimizer momenta, guard counters) —
  carried forward BIT-EXACTLY.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from grace_tpu import grace_from_params
from grace_tpu.core import Topology
from grace_tpu.parallel import data_parallel_mesh
from grace_tpu.resilience import (ConsensusConfig, ElasticController,
                                  audit_report, guarded_chain,
                                  implant_stale_replica, plan_resize,
                                  rejoin_barrier, replica_variants,
                                  reshard_grace_state, validate_resharded)
from grace_tpu.train import init_train_state, make_train_step

pytestmark = pytest.mark.elastic


# ---------------------------------------------------------------------------
# fixture: a consensus+guard+telemetry+watch run at W=8
# ---------------------------------------------------------------------------

PARAMS = {"w": jnp.ones((16, 4)), "b": jnp.zeros((4,))}
GRACE = {"compressor": "topk", "compress_ratio": 0.25, "memory": "residual",
         "communicator": "allgather", "escape": "fp16",
         "consensus": ConsensusConfig(audit_every=50),
         "telemetry": 8, "watch": {"window": 2, "capacity": 4}}


def _loss_fn(params, batch):
    x, y = batch
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


def _build(mesh, grace_params=GRACE, params=PARAMS):
    grc = grace_from_params(dict(grace_params))
    tx = guarded_chain(grc, optax.sgd(1e-2),
                       fallback_after=3, fallback_steps=4)
    state = init_train_state(params, tx, mesh)
    step = make_train_step(_loss_fn, tx, mesh, donate=False,
                           consensus=grace_params.get("consensus"))
    return grc, tx, state, step


def _batch(n=32, seed=0, poison=False):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 16)).astype(np.float32)
    if poison:
        x[0, 0] = np.nan
    return (jnp.asarray(x),
            jnp.asarray(rng.standard_normal((n, 4)), jnp.float32))


@pytest.fixture(scope="module")
def trained8(mesh):
    """(grace, tx, state, step) after 4 healthy steps + 1 guard-skipped
    poisoned step at W=8 — nonzero residuals, nonzero telemetry/watch
    rings, nonzero guard counters, armed audit state."""
    grc, tx, state, step = _build(mesh)
    batch = _batch()
    for _ in range(4):
        state, loss = step(state, batch)
    state, _ = step(state, _batch(poison=True))   # guard skips this one
    assert np.isfinite(float(loss))
    return grc, tx, state, step


def _grace_node(state):
    return state.opt_state.inner[0]


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# Topology.shrink / plan_resize / hier shrunk
# ---------------------------------------------------------------------------

class TestResizePlanning:
    def test_whole_slice_loss_keeps_slice_size(self):
        topo, w = Topology(slice_size=4).shrink(8, range(4, 8))
        assert topo.slice_size == 4 and w == 4

    def test_partial_slice_loss_collapses_to_flat(self):
        topo, w = Topology(slice_size=4).shrink(8, [5])
        assert topo.slice_size is None and w == 7

    def test_flat_topology_stays_flat(self):
        topo, w = Topology().shrink(8, [3])
        assert topo.slice_size is None and w == 7

    def test_empty_loss_is_identity(self):
        topo = Topology(slice_size=4)
        assert topo.shrink(8, []) == (topo, 8)

    def test_out_of_range_and_total_loss_raise(self):
        with pytest.raises(ValueError, match="outside the world"):
            Topology().shrink(8, [8])
        with pytest.raises(ValueError, match="no survivors"):
            Topology().shrink(2, [0, 1])

    def test_plan_resize_survivor_renumbering(self):
        plan = plan_resize(8, [5], Topology(slice_size=4))
        assert plan.survivors == (0, 1, 2, 3, 4, 6, 7)
        assert plan.new_world == 7
        assert not plan.whole_slices
        plan = plan_resize(8, range(4, 8), Topology(slice_size=4))
        assert plan.survivors == (0, 1, 2, 3)
        assert plan.whole_slices and plan.topology.slice_size == 4

    def test_hier_communicator_shrunk(self):
        from grace_tpu.comm import HierarchicalAllreduce

        comm = HierarchicalAllreduce(axis_name="data", slice_size=4)
        kept = comm.shrunk(Topology(slice_size=4))
        assert isinstance(kept, HierarchicalAllreduce)
        assert kept.slice_size == 4 and kept.axis_name == "data"
        flat = comm.shrunk(Topology())
        assert flat.slice_size is None


# ---------------------------------------------------------------------------
# reshard_grace_state: every field family (ISSUE 11 satellite)
# ---------------------------------------------------------------------------

class TestReshard:
    @pytest.fixture(scope="class")
    def resharded(self, trained8, mesh):
        grc, tx, state, _ = trained8
        mesh6 = data_parallel_mesh(jax.devices()[:6])
        new_state = reshard_grace_state(state, tx, mesh, mesh6)
        return state, new_state, mesh6

    def test_mem_residuals_rezeroed_at_new_world(self, resharded):
        old_state, new_state, _ = resharded
        old_g, new_g = _grace_node(old_state), _grace_node(new_state)
        # the old run genuinely accumulated residuals — the zeroing is real
        assert any(float(jnp.abs(m).sum()) > 0 for m in old_g.mem)
        for m in new_g.mem:
            assert m.shape[0] == 6
            assert float(jnp.abs(m).sum()) == 0.0

    def test_telemetry_and_watch_rings_reallocated_reset(self, resharded):
        old_state, new_state, _ = resharded
        old_g, new_g = _grace_node(old_state), _grace_node(new_state)
        # old rings hold rows (steps recorded); new rings are pristine
        assert int(jnp.max(old_g.telem.steps)) >= 0
        assert int(jnp.max(old_g.watch.steps)) >= 0
        for ring in (new_g.telem, new_g.watch):
            assert ring.steps.shape[0] == 6          # world axis
            assert int(jnp.max(ring.steps)) == -1    # wraparound reset
            assert float(jnp.abs(ring.rings).sum()) == 0.0
        # capacity (per-rank row count) preserved from the config
        assert new_g.telem.steps.shape[1] == old_g.telem.steps.shape[1]
        assert new_g.watch.steps.shape[1] == old_g.watch.steps.shape[1]

    def test_replicated_bookkeeping_carried_bit_exactly(self, resharded):
        old_state, new_state, _ = resharded
        old_g, new_g = _grace_node(old_state), _grace_node(new_state)
        for name in ("count", "rng_key", "fallback"):
            assert _leaves_equal(getattr(old_g, name), getattr(new_g, name))
        assert _leaves_equal(old_g.audit, new_g.audit)     # audit counters

    def test_guard_counters_and_params_carried_bit_exactly(self, resharded):
        old_state, new_state, _ = resharded
        old_guard, new_guard = old_state.opt_state, new_state.opt_state
        assert int(old_guard.notfinite_count) == 1   # the poisoned step
        for name in ("notfinite_count", "last_bad_step", "consecutive",
                     "fallback_remaining", "step"):
            assert _leaves_equal(getattr(old_guard, name),
                                 getattr(new_guard, name))
        assert _leaves_equal(old_state.params, new_state.params)
        # downstream (sgd) optimizer state rides along too
        assert _leaves_equal(old_guard.inner[1], new_guard.inner[1])

    def test_resharded_state_trains(self, resharded, trained8):
        _, new_state, mesh6 = resharded
        grc, tx, _, _ = trained8
        step6 = make_train_step(_loss_fn, tx, mesh6, donate=False,
                                consensus=GRACE["consensus"])
        batch = _batch(n=30, seed=3)
        state = new_state
        for _ in range(2):
            state, loss = step6(state, batch)
        assert np.isfinite(float(loss))
        assert int(_grace_node(state).count) == \
            int(_grace_node(new_state).count) + 2

    def test_powersgd_comp_state_reinitialized_not_zeroed(self, mesh):
        grc, tx, state, step = _build(
            mesh, {"compressor": "powersgd", "compress_rank": 2,
                   "memory": "powersgd", "communicator": "allreduce"})
        state, _ = step(state, _batch())
        mesh6 = data_parallel_mesh(jax.devices()[:6])
        new_state = reshard_grace_state(state, tx, mesh, mesh6)
        comp = [c for c in _grace_node(new_state).comp if c is not None]
        assert comp, "powersgd run produced no comp state"
        for q in comp:
            assert q.shape[0] == 6
            # zeros are not a valid Q — re-init must produce a live iterate
            assert float(jnp.abs(q).sum()) > 0

    def test_reshard_rejects_wrong_old_mesh(self, trained8):
        grc, tx, state, _ = trained8
        mesh6 = data_parallel_mesh(jax.devices()[:6])
        with pytest.raises(ValueError, match="world axis 8"):
            reshard_grace_state(state, tx, mesh6, mesh6)

    def test_validate_against_footprint_model(self, resharded, trained8):
        grc, tx, _, _ = trained8
        _, new_state, _ = resharded
        report = validate_resharded(new_state, grc, PARAMS, 6)
        assert report["matches"]
        assert report["model"] == pytest.approx(report["live"])
        with pytest.raises(ValueError, match="footprint model at world 8"):
            validate_resharded(new_state, grc, PARAMS, 8)


# ---------------------------------------------------------------------------
# rejoin barrier
# ---------------------------------------------------------------------------

@pytest.mark.consensus
class TestRejoinBarrier:
    def test_repairs_stale_replica_and_zeroes_its_residuals(self, mesh):
        grc, tx, state, step = _build(mesh)
        batch = _batch()
        state, _ = step(state, batch)
        stale_params = jax.device_get(state.params)   # "yesterday's" params
        for _ in range(3):
            state, _ = step(state, batch)             # fleet trains on
        g = _grace_node(state)
        assert all(float(jnp.abs(m[5]).sum()) > 0 for m in g.mem)
        state = implant_stale_replica(state, 5, stale_params)
        assert replica_variants(state.params) == 2

        state, report = rejoin_barrier(state, GRACE["consensus"], mesh)
        assert report["barrier_repairs"] == 1
        assert report["replica_variants"] == 1
        assert report["last_divergent_rank"] == 5
        assert report["fingerprint_bytes"] == 8 * 2 * 8 * 4
        assert report["repair_bytes"] > 0
        g = _grace_node(state)
        for m in g.mem:
            # the rejoiner's residuals zeroed (PR-3 rationale); the
            # fleet's error feedback survives the admission untouched
            assert float(jnp.abs(m[5]).sum()) == 0.0
            assert float(jnp.abs(m[0]).sum()) > 0

    def test_noop_on_already_consistent_rejoin(self, mesh):
        grc, tx, state, step = _build(mesh)
        state, _ = step(state, _batch())
        before = jax.device_get(state)
        state, report = rejoin_barrier(state, GRACE["consensus"], mesh)
        assert report["barrier_repairs"] == 0
        assert report["replica_variants"] == 1
        assert _leaves_equal(before.params, state.params)
        assert _leaves_equal(before.opt_state.inner[0].mem,
                             state.opt_state.inner[0].mem)

    def test_requires_armed_consensus(self, mesh):
        grc, tx, state, _ = _build(mesh)
        with pytest.raises(ValueError, match="armed consensus"):
            rejoin_barrier(state, None, mesh)


# ---------------------------------------------------------------------------
# controller
# ---------------------------------------------------------------------------

class TestElasticController:
    def test_drain_signal_thresholds_codec_skew_episodes(self):
        ctl = ElasticController(anomaly_threshold=2)
        skew = {"kind": "skew", "metric": "compression_error", "rank": 3}
        assert ctl.observe(1, [skew]) is None            # 1 episode: hold
        assert ctl.observe(2, [skew]) == 3               # threshold crossed
        assert ctl.observe(3, [skew, skew]) is None      # drains only once

    def test_grad_norm_skews_do_not_drain(self):
        ctl = ElasticController(anomaly_threshold=1)
        noise = {"kind": "skew", "metric": "grad_norm", "rank": 2}
        ewma = {"kind": "ewma", "metric": "compression_error_mean",
                "rank": -1}
        assert ctl.observe(1, [noise, ewma]) is None
        assert ctl.observe(
            2, [{"kind": "skew", "metric": "residual_norm",
                 "rank": 6}]) == 6

    def test_drain_saves_last_known_good(self, tmp_path):
        from grace_tpu.checkpoint import Checkpointer

        with Checkpointer(tmp_path / "ck", max_to_keep=None) as ckpt:
            ctl = ElasticController(checkpointer=ckpt, anomaly_threshold=1)
            rec = ctl.drain(7, {"x": jnp.arange(4.0)}, rank=5)
            assert rec["event"] == "elastic_drain" and rec["rank"] == 5
            assert ckpt.last_good_step() == 7
        assert ctl.events and ctl.events[0]["checkpointed"]

    def test_events_stream_into_sink_as_elastic_kind(self, tmp_path):
        from grace_tpu.telemetry import JSONLSink
        from grace_tpu.telemetry.timeline import Timeline, classify

        path = tmp_path / "e.jsonl"
        sink = JSONLSink(path)
        ctl = ElasticController(sink=sink, anomaly_threshold=1)
        ctl._emit("elastic_resize", 10, old_world=8, new_world=7)
        sink.close()
        records = [json.loads(l) for l in path.read_text().splitlines()]
        assert classify(records[-1]) == "elastic"
        t = Timeline.from_records(records)
        assert t.summary()["kind_counts"]["elastic"] == 1


# ---------------------------------------------------------------------------
# transform: single build-time topology resolution (ISSUE 11 satellite)
# ---------------------------------------------------------------------------

class TestTopologyResolution:
    def test_detect_called_once_at_build_and_shared(self, monkeypatch):
        from grace_tpu import core
        from grace_tpu.transform import grace_transform

        calls = []
        orig = core.Topology.detect.__func__

        def counting(cls, devices=None):
            calls.append(1)
            return orig(cls, devices)

        monkeypatch.setattr(core.Topology, "detect", classmethod(counting))
        grc = grace_from_params({"compressor": "topk",
                                 "compress_ratio": 0.25,
                                 "memory": "residual",
                                 "communicator": "allgather",
                                 "telemetry": 4,
                                 "watch": {"window": 2, "capacity": 4}})
        tx = grc.transform(seed=0)
        assert len(calls) == 1, "Topology.detect must resolve at build time"
        assert isinstance(tx.update.grace_topology, core.Topology)

    def test_update_never_re_detects(self, mesh, monkeypatch):
        from grace_tpu import core

        grc, tx, state, step = _build(mesh)

        def boom(cls, devices=None):   # pragma: no cover - must not run
            raise AssertionError("Topology.detect called after build")

        monkeypatch.setattr(core.Topology, "detect", classmethod(boom))
        batch = _batch()
        for _ in range(2):   # crosses a watch window: both paths execute
            state, loss = step(state, batch)
        assert np.isfinite(float(loss))

    def test_explicit_topology_skips_detection(self, monkeypatch):
        from grace_tpu import core

        def boom(cls, devices=None):   # pragma: no cover - must not run
            raise AssertionError("explicit topology must not detect")

        monkeypatch.setattr(core.Topology, "detect", classmethod(boom))
        grc = grace_from_params({"compressor": "none",
                                 "communicator": "hier", "slice_size": 4,
                                 "telemetry": 4})
        tx = grc.transform(seed=0)
        assert tx.update.grace_topology.slice_size == 4

    def test_no_telemetry_resolves_nothing(self, monkeypatch):
        from grace_tpu import core

        def boom(cls, devices=None):   # pragma: no cover - must not run
            raise AssertionError("no telemetry: nothing prices a split")

        monkeypatch.setattr(core.Topology, "detect", classmethod(boom))
        grc = grace_from_params({"compressor": "none",
                                 "communicator": "allgather"})
        assert grc.transform(seed=0).update.grace_topology is None


# ---------------------------------------------------------------------------
# the full lifecycle smoke (tier-1, world=8) + evidence pickup
# ---------------------------------------------------------------------------

def _load_tool(name):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        f"{name}_under_test",
        os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                     f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.chaos
class TestElasticSmoke:
    def test_chaos_smoke_elastic_cycle(self, tmp_path):
        """kill → W−1 resume → rejoin → W with bit-identical replicas,
        repairs == rejoins, the convergence floor met, and the re-sharded
        state matching flow pass 7's footprint model at both worlds."""
        smoke = _load_tool("chaos_smoke")
        out = tmp_path / "elastic.jsonl"
        doc_path = tmp_path / "ELASTIC_LAST.json"
        rc = smoke.main(["--elastic", "--steps", "36", "--batch", "16",
                         "--watch-window", "5", "--telemetry-every", "10",
                         "--audit-every", "10", "--floor", "2.4",
                         "--telemetry-out", str(out),
                         "--elastic-out", str(doc_path),
                         "--ckpt-dir", str(tmp_path / "ck")])
        assert rc == 0
        doc = json.loads(doc_path.read_text())
        assert doc["world_cycle"] == [8, 7, 8]
        assert doc["drain"]["rank"] == 5
        assert doc["rejoin"]["barrier_repairs"] == doc["rejoin"]["rejoins"]
        assert doc["rejoin"]["replica_variants"] == 1
        assert doc["rejoin"]["fingerprint_bytes"] > 0
        assert doc["floor"]["met"]
        assert doc["footprint"] == {"7": True, "8": True}
        events = [e["event"] for e in doc["resize_events"]]
        assert events == ["elastic_drain", "elastic_resize",
                          "elastic_resize", "elastic_rejoin"]
        # the same lifecycle streams into the telemetry artifact
        from grace_tpu.telemetry.timeline import Timeline

        t = Timeline.from_jsonl(str(out))
        assert t.summary()["kind_counts"]["elastic"] == 4
        assert [e.record["event"] for e in t.kinds("elastic")] == events

    @pytest.mark.slow
    @pytest.mark.hier
    def test_chaos_smoke_elastic_hier_slice_kill(self, tmp_path):
        """--hier: losing the flagged rank's whole slice is a K→K−1
        resize that keeps slice_size through the cycle."""
        smoke = _load_tool("chaos_smoke")
        doc_path = tmp_path / "ELASTIC_LAST.json"
        rc = smoke.main(["--elastic", "--hier", "--slice-size", "4",
                         "--steps", "36", "--batch", "16",
                         "--watch-window", "5", "--telemetry-every", "10",
                         "--audit-every", "10", "--floor", "2.4",
                         "--telemetry-out", str(tmp_path / "h.jsonl"),
                         "--elastic-out", str(doc_path),
                         "--ckpt-dir", str(tmp_path / "ck")])
        assert rc == 0
        doc = json.loads(doc_path.read_text())
        assert doc["world_cycle"] == [8, 4, 8]
        assert doc["slice_size"] == 4
        resize = next(e for e in doc["resize_events"]
                      if e["event"] == "elastic_resize")
        assert resize["lost_ranks"] == [4, 5, 6, 7]
        assert resize["whole_slices"] and resize["slice_size"] == 4
        assert doc["rejoin"]["replica_variants"] == 1
        assert doc["footprint"] == {"4": True, "8": True}
