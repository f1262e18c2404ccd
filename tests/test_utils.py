"""Tests for grace_tpu.utils: loggers, timers, wire metrics."""

import io
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grace_tpu import compressors as C
from grace_tpu.utils import (StepTimer, TableLogger, Timer, TSVLogger,
                             payload_nbytes, wire_report)


class TestTimer:
    def test_segments_and_total(self):
        t = Timer()
        time.sleep(0.01)
        d1 = t()
        time.sleep(0.01)
        d2 = t(include_in_total=False)
        assert d1 >= 0.01 and d2 >= 0.01
        assert t.total_time == pytest.approx(d1)

    def test_sync_hook_called(self):
        calls = []
        t = Timer(sync=lambda: calls.append(1))
        t()
        assert len(calls) == 2  # once at init, once per reading


class TestTableLogger:
    def test_header_latched_and_aligned(self):
        buf = io.StringIO()
        log = TableLogger(width=8, stream=buf)
        log.append({"epoch": 1, "loss": 0.5})
        log.append({"epoch": 2, "loss": 0.25, "extra": "ignored"})
        lines = buf.getvalue().strip().split("\n")
        assert len(lines) == 4   # header, row, new-column notice, row
        assert "epoch" in lines[0] and "loss" in lines[0]
        assert lines[2] == "# new columns (ignored): extra"
        assert "ignored" not in lines[3]  # keys latched from first row
        assert "0.2500" in lines[3]

    def test_missing_key_renders_blank_and_new_key_warns_once(self):
        # The telemetry case: fields appear only after the first flush
        # window and early rows lack them — neither may KeyError.
        buf = io.StringIO()
        log = TableLogger(width=8, stream=buf)
        log.append({"epoch": 1, "loss": 0.5})
        log.append({"epoch": 2})                           # lost a key
        log.append({"epoch": 3, "loss": 0.1, "gnorm": 1.0})  # gained one
        log.append({"epoch": 4, "loss": 0.2, "gnorm": 2.0})  # no re-warn
        lines = buf.getvalue().split("\n")
        notices = [l for l in lines if l.startswith("#")]
        assert notices == ["# new columns (ignored): gnorm"]
        row2 = lines[2]
        assert row2.startswith(f"{2:>8}") and row2.rstrip() == f"{2:>8}"
        assert all("gnorm" not in l for l in lines if not l.startswith("#"))


class TestTSVLogger:
    def test_dawnbench_format(self, tmp_path):
        log = TSVLogger()
        log.append({"epoch": 1, "total time": 3600.0, "test acc": 0.9408})
        s = str(log)
        lines = s.split("\n")
        assert lines[0] == "epoch\thours\ttop1Accuracy"
        assert lines[1] == "1\t1.00000000\t94.08"
        p = tmp_path / "logs.tsv"
        log.write(str(p))
        assert p.read_text().startswith("epoch\thours")


class TestStepTimer:
    def test_warmup_excluded(self):
        st = StepTimer(warmup=1)
        # host-only step bodies: the (intentional) no-sync_on warning is the
        # expected condition here, asserted explicitly
        with pytest.warns(RuntimeWarning, match="sync_on"):
            for i in range(3):
                with st.step():
                    time.sleep(0.02 if i == 0 else 0.005)
        assert len(st.steady) == 2
        assert st.mean_sec < 0.02
        assert st.throughput(10) > 0
        assert st.measured_async_dispatch

    def test_sync_on_blocks_device_value(self):
        st = StepTimer(warmup=0)
        x = jnp.arange(1024.0)
        with st.step():
            y = (x * 2).sum()
            st.sync_on(y)
        assert st.mean_sec >= 0


class TestWireMetrics:
    def test_none_compressor_is_identity_cost(self):
        x = jnp.zeros((128,), jnp.float32)
        assert payload_nbytes(C.NoneCompressor(), x) == 128 * 4

    def test_topk_payload_scales_with_ratio(self):
        x = jnp.zeros((1000,), jnp.float32)
        b = payload_nbytes(C.TopKCompressor(compress_ratio=0.01), x)
        # 10 values (f32) + 10 indices (i32) = 80 bytes
        assert b == 80

    def test_signsgd_saves_bandwidth(self):
        x = jnp.zeros((1024,), jnp.float32)
        b = payload_nbytes(C.SignSGDCompressor(), x)
        assert b < 1024 * 4

    def test_shipped_defaults_beat_dense_bytes(self):
        # VERDICT round-1 item 9: every compressor's default config must cost
        # less on the wire than shipping the dense gradient (None excepted —
        # it IS the dense baseline).
        # 2-D input: PowerSGD's low-rank factorization degenerates on
        # vectors (P+Q of a 1xN matrix costs as much as N values).
        x = jnp.zeros((64, 64), jnp.float32)
        dense = 64 * 64 * 4
        for comp in [C.FP16Compressor(), C.TopKCompressor(),
                     C.RandomKCompressor(), C.ThresholdCompressor(),
                     C.QSGDCompressor(), C.TernGradCompressor(),
                     C.SignSGDCompressor(), C.SignumCompressor(),
                     C.EFSignSGDCompressor(), C.OneBitCompressor(),
                     C.NaturalCompressor(), C.DgcCompressor(),
                     C.AdaqCompressor(),
                     C.U8bitCompressor(), C.SketchCompressor(),
                     C.InceptionNCompressor()]:
            # (PowerSGD excluded: it psums inside compress, so its cost is
            # only measurable inside shard_map — covered in test_fusion.)
            assert payload_nbytes(comp, x) < dense, type(comp).__name__

    def test_topk_bf16_wire_saves_quarter(self):
        x = jnp.zeros((1000,), jnp.float32)
        f32 = payload_nbytes(C.TopKCompressor(compress_ratio=0.1), x)
        bf16 = payload_nbytes(C.TopKCompressor(compress_ratio=0.1,
                                               wire_dtype="bfloat16"), x)
        assert f32 == 100 * 8 and bf16 == 100 * 6
        # round-trip decodes back to the original dtype, values ~exact for
        # bf16-representable inputs
        comp = C.TopKCompressor(compress_ratio=0.5, wire_dtype="bfloat16")
        g = jnp.asarray([1.5, -2.0, 0.25, 0.0])
        payload, ctx, _ = comp.compress(g, None, jax.random.key(0))
        out = comp.decompress(payload, ctx)
        assert out.dtype == g.dtype
        np.testing.assert_allclose(np.asarray(out), [1.5, -2.0, 0, 0])

    def test_threshold_calibrated_tracks_density(self):
        # 2% of entries exceed tau -> capacity tuned to ~3% (1.5x safety),
        # two orders tighter than the 25% correctness default.
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.standard_normal(10_000) * 0.001)
        g = g.at[:200].set(1.0)   # 2% large entries
        comp = C.ThresholdCompressor(threshold=0.01)
        tuned = comp.calibrated(g)
        assert np.isclose(tuned.capacity_ratio, 0.03, atol=0.005)
        assert payload_nbytes(tuned, g) < payload_nbytes(comp, g) / 5
        # round-trip stays exact: capacity still covers every selected entry
        payload, ctx, _ = tuned.compress(g, None, jax.random.key(0))
        out = tuned.decompress(payload, ctx)
        np.testing.assert_allclose(np.asarray(out)[:200], 1.0)

    def test_wire_report_over_tree(self):
        tree = {"w": jnp.zeros((100, 10)), "b": jnp.zeros((10,))}
        rep = wire_report(C.TopKCompressor(compress_ratio=0.1), tree)
        assert rep.dense_bytes == (1000 + 10) * 4
        assert len(rep.leaves) == 2
        assert 0 < rep.ratio < 1
        assert "ratio" in rep.summary()
        assert "CompressionReport" in str(rep)

    def test_randomk_values_only(self):
        # RandomK sends values only (indices derived from shared seed,
        # reference grace_dl/dist/compressor/randomk.py:26-29).
        x = jnp.zeros((1000,), jnp.float32)
        b = payload_nbytes(C.RandomKCompressor(compress_ratio=0.01), x)
        assert b == 10 * 4


def test_debug_nan_residuals_counts_nan_and_inf():
    """The census reports NaN AND Inf per leaf (~jnp.isfinite), in one
    device-to-host transfer; clean states stay an empty dict."""
    from grace_tpu.utils import debug_nan_residuals

    clean = {"a": jnp.zeros((4,)), "n": jnp.arange(3)}   # int leaf ignored
    assert debug_nan_residuals(clean) == {}

    poisoned = {
        "a": jnp.asarray([1.0, jnp.nan, jnp.inf, -jnp.inf]),
        "b": {"c": jnp.asarray([jnp.nan, jnp.nan])},
        "ok": jnp.ones((2,)),
    }
    rep = debug_nan_residuals(poisoned)
    assert set(rep) == {"['a']", "['b']['c']"}
    assert rep["['a']"] == {"nan": 1, "inf": 2}
    assert rep["['b']['c']"] == {"nan": 2, "inf": 0}


def test_run_provenance_includes_git_commit():
    from grace_tpu.utils import git_commit, run_provenance

    prov = run_provenance("synthetic", argv="--steps 5")
    assert prov["data"] == "synthetic"
    assert prov["argv"] == "--steps 5"
    # This repo IS a git checkout, so the best-effort lookup must succeed
    # here and match the helper.
    rev = git_commit()
    assert rev and prov["git_commit"] == rev
    assert 4 <= len(rev) <= 16 and all(c in "0123456789abcdef" for c in rev)


def test_wire_report_powersgd_analytic():
    """PowerSGD's compress psums inside shard_map, so wire_report must use
    its analytic wire_nbytes instead of shape-tracing compress (regression:
    the digits example once crashed with 'unbound axis name: data')."""
    import jax.numpy as jnp

    from grace_tpu.compressors import PowerSGDCompressor
    from grace_tpu.utils import wire_report

    params = {"w": jnp.zeros((20, 8)), "b": jnp.zeros((8,))}
    rep = wire_report(PowerSGDCompressor(rank=4), params)
    # w: (20+8)*4 floats; b rides dense: 8 floats
    assert rep.wire_bytes == ((20 + 8) * 4 + 8) * 4
    assert rep.dense_bytes == (20 * 8 + 8) * 4


# ---------------------------------------------------------------------------
# compile cache placed from outside (PR 21)
# ---------------------------------------------------------------------------

class TestCompileCachePlacement:
    def _updates(self, monkeypatch):
        import jax
        seen = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: seen.append((k, v)))
        return seen

    def test_env_set_means_code_sets_nothing(self, monkeypatch):
        from grace_tpu.utils.compile_cache import place_compile_cache
        seen = self._updates(monkeypatch)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert place_compile_cache("tpu") == "/somewhere/else"
        assert place_compile_cache("cpu") == "/somewhere/else"
        assert seen == []

    def test_unset_means_fixed_path_in_the_checkout(self, monkeypatch):
        import os

        from grace_tpu.utils import compile_cache
        seen = self._updates(monkeypatch)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(root, ".jax_cache")
        assert compile_cache.place_compile_cache("tpu") == want
        assert [v for _, v in seen] == [want]      # one config key set
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_cpu_rehearsal_gets_no_cache(self, monkeypatch):
        from grace_tpu.utils.compile_cache import place_compile_cache
        seen = self._updates(monkeypatch)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert place_compile_cache("cpu") is None and seen == []
