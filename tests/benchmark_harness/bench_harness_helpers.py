"""Shared by the benchmark's tests: the test-size catalog under ``data/``
and a way to drive ``run.py`` as a CPU rehearsal and read its lines."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import harness, run  # noqa: E402


def tiny_catalog(*more_roots, benchmark_json=None):
    """The benchmark's own code with the test-size cells of ``data/``."""
    return harness.Catalog(
        benchmark_json or os.path.join(DATA, "BENCHMARK.tiny.json"),
        roots=[harness.HERE, DATA, *more_roots])


def rehearse(capsys, catalog, *argv, rehearse_cpu=True):
    """``run.main`` on the CPU; returns ``(exit code, printed lines)`` with
    every line parsed as JSON."""
    args = list(argv) + (["--rehearse-cpu"] if rehearse_cpu else [])
    rc = run.main(args, catalog)
    out = capsys.readouterr().out
    return rc, [json.loads(l) for l in out.splitlines() if l.strip()]
