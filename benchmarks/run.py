"""One cell of the benchmark, once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one ``import jax``. Set-up (all of it inside ``setup_s``):
compile cache at its fixed place, weights and the one fixed batch made on
the device from the seed, the step built through the public entry points
and compiled ahead of time, its first three steps taken through the
window's own call. Then the window: ``--trace 0`` measures ``--seconds``
seconds of free-running steps by the host clock; ``--trace 1`` traces a
few steady steps with ``jax.profiler`` and reduces the trace. After the
window the program's state is freed and the plain reference follows the
same first three steps; every number compared is printed beside its limit.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``). Without a TPU, or with fewer chips than the cell asks for,
the run exits non-zero and prints no result. ``--rehearse-cpu`` names a
rehearsal on the CPU (tests only): its numbers say nothing about speed.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import harness, trace_reduce  # noqa: E402


def say(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def devices_for(cell: dict, rehearse: bool):
    import jax

    devices = jax.devices()
    if not rehearse and devices[0].platform != "tpu":
        raise harness.BenchError(
            f"first device is {devices[0].platform!r}, not 'tpu': the "
            "benchmark measures nothing off the chip")
    if len(devices) < cell["chips"]:
        raise harness.BenchError(
            f"{len(devices)} device(s), the cell asks for {cell['chips']}")
    return devices[:cell["chips"]]


def place_cache(platform: str) -> None:
    import jax
    from grace_tpu.utils.compile_cache import place_compile_cache

    if place_compile_cache(platform):
        # every program of set-up, however small, is found again
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def traced_window(program, steps: int, trace_dir: str):
    """``steps`` steady steps under the profiler, with the benchmark's own
    host spans around the dispatch call and the loss fetch. Returns
    ``(dispatch_ms, losses)``."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    dispatch, losses, pending = [], [], None
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        for _ in range(steps):
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/dispatch"):
                nxt = program.call()
            dispatch.append((time.perf_counter() - t) * 1e3)
            if pending is not None:
                with jax.profiler.TraceAnnotation("bench/fetch"):
                    losses.append(float(pending))
            pending = nxt
        with jax.profiler.TraceAnnotation("bench/fetch"):
            losses.append(float(pending))
    finally:
        jax.profiler.stop_trace()
    return dispatch, losses


def memory_peak_bytes(program) -> tuple[int, dict]:
    """The peak on the fullest chip. The runtime's own counter leaves the
    program's temporaries out on this installation (PERF.md, PR 21), so the
    compiler's count of the step stands beside it and the larger is given."""
    stats = [d.memory_stats() or {} for d in program.mesh.devices.flat]
    runtime = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    compiled = program.hbm_program_bytes()
    return max(runtime, compiled), {"runtime_peak_bytes_in_use": runtime,
                                    "compiled_step_bytes": compiled}


def run(args, catalog=None) -> dict:
    """The whole run; returns the object of the last line."""
    catalog = catalog or harness.Catalog()
    cell = catalog.cell(args.workload)
    config = catalog.config(cell["config"])
    builder = catalog.builder(config)

    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = devices_for(cell, args.rehearse_cpu)
    place_cache(devices[0].platform)
    if not args.rehearse_cpu:
        catalog.peaks(devices[0].device_kind)     # an unknown chip is an error
    mesh = Mesh(np.asarray(devices), ("data",))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices())}

    program = harness.Program(cell, config, builder, mesh, args.seed)
    got = harness.first_steps(program)
    # Tracing the step leaves a large heap of Python objects behind; they
    # are collected once here and frozen, so that no full collection of
    # them (0.06 s at ResNet-50's size, my chip run, PR 23) lands on a
    # step's stamp inside the window.
    t_gc = time.perf_counter()
    gc.collect()
    gc.freeze()
    gc_s = time.perf_counter() - t_gc
    setup_s = time.perf_counter() - T_START
    say({"phase": "setup", "workload": args.workload, "seed": args.seed,
         "device": device, "compile_s": program.compile_s,
         "full_gc_s": gc_s, "setup_s": setup_s, "first_losses": got["losses"]})

    metrics, breakdown = {}, None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            dispatch_ms, losses = traced_window(
                program, cell["trace_steps"], trace_dir)
            xplane = trace_reduce.find_xplane(trace_dir)
            trace = trace_reduce.load(xplane, rehearsal=args.rehearse_cpu)
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(xplane, os.path.join(
                    args.keep_trace, args.workload + ".xplane.pb"))
                with open(os.path.join(args.keep_trace,
                                       args.workload + ".hlo.txt"), "w") as f:
                    f.write(program.text)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        reduced = trace_reduce.reduce(trace, len(losses),
                                      trace_reduce.scopes_of(program.text))
        ctx = {"reduced": reduced, "program": program,
               "dispatch_ms": dispatch_ms}
        for m in catalog.metrics_of("per_layer", args.workload):
            value = catalog.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"],
                     "stages": [[k, v] for k, v in sorted(
                         reduced["stage_s_per_step"].items(),
                         key=lambda kv: -kv[1])[:10]]}
    else:
        stamps, losses = harness.timed_window(program.call, args.seconds)
        w = harness.window_metrics(stamps, program.global_batch,
                                   cell["span_steps"])
        say({"phase": "window", **w})
        values = {"samples_per_s": w["samples_per_s"],
                  "step_ms.p95": w["step_ms.p95"], "setup_s": setup_s}
        for m in catalog.metrics_of("end_to_end", args.workload):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    gc.unfreeze()
    failed = sum(not math.isfinite(x) for x in losses)

    device["memory_peak_bytes"], memory = memory_peak_bytes(program)
    say({"phase": "memory", **memory})
    rows = harness.replica_rows(program) if program.world > 1 else []
    keys, world = program.keys, program.world
    program.state = program.batch = program.compiled = None   # freed
    t0 = time.perf_counter()
    want = harness.reference_numbers(keys, cell, config, builder, world)
    rows = harness.compare(got, want, cell["limits"]) + rows
    say({"phase": "correct", "reference_s": time.perf_counter() - t0,
         "reference_losses": want["losses"], "compared": rows})

    out = {"correct": all(r["ok"] for r in rows) and failed == 0,
           "attempted": len(losses), "failed": failed, "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    return out


def main(argv=None, catalog=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tests only: run off the chip; no number of such a "
                         "run is a device metric")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="with --trace 1: also copy the raw trace file and the "
                         "compiled step's text there, to read by hand")
    args = ap.parse_args(argv)
    try:
        out = run(args, catalog)
    except harness.BenchError as e:
        print(f"benchmark cannot run: {e}", file=sys.stderr)
        return 1
    say(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
