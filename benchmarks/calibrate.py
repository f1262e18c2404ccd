"""Read, on the chip and at a cell's own size, the two numbers every limit
of ``correct`` is set from: the largest gap sound runs of the program show
against the plain reference over many seeds, and the smallest gap the
control shows. The control is the reference put in the program's place in
the nearest precision below the configuration's (parameters, gradients and
optimizer state in bfloat16 where it states float32).

    python benchmarks/calibrate.py --workload <name> --seeds 12 --control-seeds 3

One process for all seeds (set-up is most of a run). Prints one JSON line
per seed and a summary; sets nothing: the limits are written into the
cell's file by hand, from these readings (PERF.md says which).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import harness, run  # noqa: E402

INF = float("inf")
NO_LIMITS = {"loss_gap": [INF] * harness.CHECK_STEPS,
             "grad1_norm_gap": INF, "grad1_norm_gap_median": INF,
             "delta_norm_gap": INF, "delta_norm_gap_median": INF}


def gaps(got, want) -> dict:
    return {r["name"]: r["value"]
            for r in harness.compare(got, want, NO_LIMITS)}


def half_the_batch(builder):
    """The builder with the second half of every batch replaced by the
    first: the fault the first step's loss is there to catch."""
    import types

    import jax

    def make_batch(key, n, config):
        return jax.tree_util.tree_map(
            lambda x: x.at[n // 2:].set(x[:n // 2]),
            builder.make_batch(key, n, config))

    fields = {k: getattr(builder, k) for k in
              ("init", "program_loss", "reference_loss")}
    return types.SimpleNamespace(make_batch=make_batch, **fields)


def main(argv=None, catalog=None, rehearse=False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--leaves", type=int, default=0,
                    help="also print this many worst leaves of each norm")
    args = ap.parse_args(argv)
    catalog = catalog or harness.Catalog()
    cell = catalog.cell(args.workload)
    config = catalog.config(cell["config"])
    builder = catalog.builder(config)

    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = run.devices_for(cell, rehearse)
    run.place_cache(devices[0].platform)
    mesh = Mesh(np.asarray(devices), ("data",))
    sound, control = [], []
    for i in range(args.seeds):
        # large and odd, as the driver's seeds are
        seed = args.first_seed + i * 178_956_971
        program = harness.Program(cell, config, builder, mesh, seed)
        got = harness.first_steps(program)
        keys, world = program.keys, program.world
        del program
        want = harness.reference_numbers(keys, cell, config, builder, world)
        line = {"seed": seed, "sound": gaps(got, want),
                "losses": got["losses"], "ref_losses": want["losses"]}
        if args.leaves:
            paths = [jax.tree_util.keystr(p) + str(tuple(x.shape)) for p, x in
                     jax.tree_util.tree_flatten_with_path(jax.eval_shape(
                         lambda k: builder.init(k, config)[0],
                         jax.random.key(0)))[0]]
            for what in ("grad1_norms", "delta_norms"):
                g = harness.leaf_gaps(got[what], want[what])
                worst = sorted(range(len(g)), key=lambda j: -g[j])
                line[what + "_worst"] = [
                    [paths[j], got[what][j], want[what][j], g[j]]
                    for j in worst[:args.leaves]]
                line[what + "_median_gap"] = sorted(g)[len(g) // 2]
        sound.append(line["sound"])
        if i < args.control_seeds:
            low = harness.reference_numbers(keys, cell, config, builder,
                                            world, lower_precision=True)
            line["control"] = gaps(low, want)
            control.append(line["control"])
            line["half_batch"] = gaps(harness.reference_numbers(
                keys, cell, config, half_the_batch(builder), world), want)
        print(json.dumps(line), flush=True)
    print(json.dumps({
        "workload": args.workload,
        "device": jax.devices()[0].device_kind,
        "sound_largest": {k: max(s[k] for s in sound) for k in sound[0]},
        "control_smallest": {k: min(c[k] for c in control)
                             for k in sound[0]} if control else None}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
