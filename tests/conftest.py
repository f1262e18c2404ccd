"""Test harness: 8 simulated CPU devices.

The reference has no test suite at all (SURVEY.md §4) — multi-rank behavior
was only exercised on real NCCL clusters. JAX lets us run real collective
semantics single-process: 8 host devices via XLA_FLAGS, a Mesh over them,
and `shard_map` executes genuine all_gather/psum. The platform is set
before the first `import jax`, hence this conftest-level setup.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from grace_tpu.parallel import (data_parallel_mesh,  # noqa: E402
                                relax_cpu_collective_timeouts,
                                set_cpu_device_count)

# Before the CPU backend initializes (nothing above touches jax.devices()).
set_cpu_device_count(8)

# 8 device threads on a possibly 1-core host: don't let XLA's 40s collective
# rendezvous terminate-timeout kill a slow-but-healthy test step.
relax_cpu_collective_timeouts()


@pytest.fixture(scope="session")
def mesh():
    devices = jax.devices()
    assert len(devices) == 8, f"expected 8 simulated devices, got {len(devices)}"
    return data_parallel_mesh(devices)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def keep_nothing(monkeypatch):
    """``keep_nothing()``: from then on ``models.lfm2._over_sequences``
    recomputes its parts under a ``jax.checkpoint`` that keeps nothing, as
    it did until PR 33 (its policy keeps what the fused attention kernel
    names). For tests that hold the two against each other; trace a
    function of its own on each side, since JAX answers a second trace of
    the same one from its cache."""
    def switch():
        monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                            lambda *names: None)
    return switch
