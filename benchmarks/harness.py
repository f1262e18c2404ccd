"""The benchmark's own machinery: finding a cell's files by name, building
the program's step through the public entry points, the timing loop, and
the comparison that decides ``correct``.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file found by its name under a root (``benchmarks/`` and, in
tests, a further directory): ``configs/<config>.json``,
``workloads/<cell>.json``, ``models/<builder>.py``,
``reference/<codec>.py``, ``layer_metrics/<metric>.py``.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import statistics
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# The first steps the reference follows (the builder's contract: three).
CHECK_STEPS = 3


class BenchError(RuntimeError):
    """The benchmark cannot run as asked; no result is printed."""


# ---------------------------------------------------------------------------
# files by name
# ---------------------------------------------------------------------------

class Catalog:
    """``BENCHMARK.json`` plus the roots its names are looked up under."""

    def __init__(self, benchmark_json=None, roots=None):
        self.roots = list(roots or [HERE])
        with open(benchmark_json or os.path.join(REPO, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def path(self, kind: str, filename: str) -> str:
        for root in self.roots:
            p = os.path.join(root, kind, filename)
            if os.path.exists(p):
                return p
        raise BenchError(f"no {kind}/{filename} under {self.roots}")

    def _json(self, kind, name):
        with open(self.path(kind, name + ".json")) as f:
            return json.load(f)

    def _module(self, kind, name):
        path = self.path(kind, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"_bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def cell(self, name: str) -> dict:
        """The ``workloads`` entry of ``BENCHMARK.json`` merged over the
        cell's own file."""
        entry = next((w for w in self.spec["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json")
        own = self._json("workloads", name)
        if own["config"] != entry["config"] or own["chips"] != entry["chips"]:
            raise BenchError(f"{name}: configuration or chips differ between "
                             "BENCHMARK.json and the cell's file")
        return {**own, **entry}

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def builder(self, config: dict):
        return self._module("models", config["builder"])

    def reader(self, metric: str):
        return self._module("layer_metrics", metric).read

    def metrics_of(self, section: str, cell_name: str) -> list[dict]:
        """The metrics of ``end_to_end`` or ``per_layer`` this cell reports:
        those without a ``workloads`` key, or that list the cell."""
        return [m for m in self.spec[section]
                if cell_name in m.get("workloads", [cell_name])]

    def peaks(self, device_kind: str) -> dict:
        with open(self.path("", "peaks.json")) as f:
            table = json.load(f)
        if device_kind not in table:
            raise BenchError(f"device kind {device_kind!r} is not in "
                             "peaks.json: add it with its source")
        return table[device_kind]


# ---------------------------------------------------------------------------
# the program under test, built as chip_smoke.train_config builds it
# ---------------------------------------------------------------------------

class Program:
    """The compiled step with its state: the one object set-up drives
    through its first steps and hands to the window."""

    def __init__(self, cell, config, builder, mesh, seed):
        import jax
        import optax
        from benchmarks.reference import train as plain
        from grace_tpu import grace_from_params
        from grace_tpu.parallel import batch_sharded, replicated
        from grace_tpu.train import (init_stateful_train_state,
                                     make_stateful_train_step)

        self.cell, self.config, self.mesh = cell, config, mesh
        self.world = mesh.devices.size
        self.global_batch = config["per_chip_batch"] * self.world
        key = jax.random.key(seed)
        self.keys = {"weights": jax.random.fold_in(key, 1),
                     "batch": jax.random.fold_in(key, 2),
                     "codec": jax.random.fold_in(key, 3)}
        self.grace = grace_from_params(dict(cell["grace"]))
        tx = optax.chain(self.grace.transform(seed=0),
                         plain.optimizer(cell["optimizer"]))
        # Weights and the one fixed batch: each made on the devices in one
        # jitted call from the seed.
        params, mstate = jax.jit(
            lambda k: builder.init(k, config),
            out_shardings=replicated(mesh))(self.keys["weights"])
        self.batch = jax.jit(
            lambda k: builder.make_batch(k, self.global_batch, config),
            out_shardings=batch_sharded(mesh))(self.keys["batch"])
        self.step = make_stateful_train_step(
            builder.program_loss(config), tx, mesh)
        state = init_stateful_train_state(params, mstate, tx, mesh)
        self.state = self._seed_codec_state(state)
        del params, mstate, state

        jax.eval_shape(self.step, self.state, self.batch)
        fn = next(iter(self.step.jit_cache.values()))
        t0 = time.perf_counter()
        self.compiled = fn.lower(self.state, self.batch).compile()
        self.compile_s = time.perf_counter() - t0
        self.text = self.compiled.as_text()
        self.memory = self.compiled.memory_analysis()

    def _seed_codec_state(self, state):
        """Hand the program the part of the exchange's start state that is
        drawn from the seed (PowerSGD's first ``Q``), so that the reference
        starts from the same one and takes none the program made."""
        import jax
        import jax.numpy as jnp
        from benchmarks.reference import train as plain
        from grace_tpu.transform import GraceState

        seeded = [plain.codec(self.cell["codec"]["reference"]).seeded(s)
                  for s in plain.init_codec_state(
                      state.params, self.keys["codec"], self.world,
                      self.cell["codec"])]
        if all(s is None for s in seeded):
            return state

        def replace(node):
            if not isinstance(node, GraceState):
                return node
            comp = tuple(
                c if s is None else jax.device_put(
                    jnp.broadcast_to(s.astype(c.dtype), c.shape), c.sharding)
                for c, s in zip(node.comp, seeded))
            return node._replace(comp=comp)

        opt_state = jax.tree_util.tree_map(
            replace, state.opt_state,
            is_leaf=lambda n: isinstance(n, GraceState))
        return state._replace(opt_state=opt_state)

    def call(self):
        """One step of the timed path: dispatches and returns the loss
        array without waiting for it."""
        self.state, loss = self.compiled(self.state, self.batch)
        return loss

    def hbm_program_bytes(self) -> int:
        """Bytes per chip the compiled step holds: arguments + outputs −
        aliased + temporaries, as the compiler counts them."""
        m = self.memory
        return (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)


def first_gradient_norms(optimizer, start, after_one, opt_state):
    """Per-leaf norms of the first gradient as the optimizer got it, worked
    out from the state after one step."""
    import jax
    import optax
    from benchmarks.reference.train import leaf_norms

    if optimizer["name"] == "sgd":
        lr = optimizer["lr"]
        return leaf_norms(jax.tree_util.tree_map(
            lambda a, b: (a - b) / lr, start, after_one))
    if optimizer["name"] == "adamw":
        adam = [n for n in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda n: isinstance(n, optax.ScaleByAdamState))
            if isinstance(n, optax.ScaleByAdamState)]
        if len(adam) != 1:
            raise BenchError(f"{len(adam)} Adam states in the optimizer state")
        return leaf_norms(jax.tree_util.tree_map(
            lambda m: m / (1.0 - 0.9), adam[0].mu))      # optax's b1
    raise BenchError(f"no first-gradient rule for {optimizer['name']!r}")


def first_steps(program: Program) -> dict:
    """Drive the program through its first ``CHECK_STEPS`` steps by the
    window's own call and return what ``correct`` compares."""
    import jax
    import jax.numpy as jnp
    from benchmarks.reference.train import leaf_norms

    copy = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))
    sub = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(lambda x, y: x - y, a, b)))
    start = copy(program.state.params)
    losses, grad1 = [], None
    for i in range(CHECK_STEPS):
        losses.append(float(program.call()))
        if i == 0:
            grad1 = jax.jit(lambda *state: first_gradient_norms(
                program.cell["optimizer"], *state))(
                start, program.state.params, program.state.opt_state)
    delta = sub(program.state.params, start)
    return {"losses": losses,
            "grad1_norms": [float(x) for x in grad1],
            "delta_norms": [float(x) for x in delta]}


def reference_numbers(program_keys, cell, config, builder, world,
                      lower_precision=False) -> dict:
    """The plain reference over the same first steps, from the same seed,
    on device 0. Makes its own weights and batch: nothing the program made
    is read."""
    import jax
    import jax.numpy as jnp
    from benchmarks.reference import train as plain

    dtype = jnp.dtype(config["param_dtype"])
    params, mstate = jax.jit(
        lambda k: builder.init(k, config, dtype))(program_keys["weights"])
    batch = jax.jit(lambda k: builder.make_batch(
        k, config["per_chip_batch"] * world, config))(program_keys["batch"])
    codec_state = plain.init_codec_state(params, program_keys["codec"], world,
                                         cell["codec"])
    return plain.follow(
        builder.reference_loss(config), params, mstate, codec_state, batch,
        world=world, steps=CHECK_STEPS, optimizer_spec=cell["optimizer"],
        codec_spec=cell["codec"], lower_precision=lower_precision)


# ---------------------------------------------------------------------------
# correct: each number compared, beside its limit
# ---------------------------------------------------------------------------

def leaf_gaps(got: list[float], want: list[float]) -> list[float]:
    """Per leaf, the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    floor = statistics.median(want)
    return [abs(g - w) / max(w, floor) for g, w in zip(got, want)]


def compare(got: dict, want: dict, limits: dict) -> list[dict]:
    """Rows ``{"name", "value", "limit", "ok"}``, one for each number
    compared: every step's loss (a limit for each step: the first is the
    loss at the seeded weights, the later ones carry the steps' drift), and
    for the first gradient's norms and the norms of the parameters' change
    both the worst leaf's gap and the median leaf's. The worst leaf catches
    a leaf that is wrong alone; the median is steady from seed to seed
    where single leaves are not (PERF.md, section 2)."""
    rows = [(f"loss_gap.step{i + 1}", abs(g - w), limit)
            for i, (g, w, limit) in enumerate(
                zip(got["losses"], want["losses"], limits["loss_gap"]))]
    for what, key in (("grad1_norm_gap", "grad1_norms"),
                      ("delta_norm_gap", "delta_norms")):
        gaps = leaf_gaps(got[key], want[key])
        rows.append((what, max(gaps), limits[what]))
        rows.append((what + "_median", statistics.median(gaps),
                     limits[what + "_median"]))
    return [{"name": n, "value": v, "limit": l,
             "ok": bool(math.isfinite(v) and v <= l)} for n, v, l in rows]


def replica_rows(program: Program) -> list[dict]:
    """Several chips: every parameter leaf byte-identical on all devices,
    and the collectives the cell's communicator should leave are in the
    compiled text. Exact comparisons: limit 0."""
    import jax
    import numpy as np

    differing = 0
    for leaf in jax.tree_util.tree_leaves(program.state.params):
        shards = leaf.addressable_shards
        ref = np.asarray(shards[0].data).tobytes()
        differing += any(np.asarray(s.data).tobytes() != ref
                         for s in shards[1:]) or len(shards) != program.world
    missing = sum(
        program.text.count(f" {op}(") + program.text.count(f" {op}-start(")
        == 0 for op in program.cell["collectives"])
    return [{"name": "replica_leaves_differing", "value": differing,
             "limit": 0, "ok": differing == 0},
            {"name": "collectives_missing", "value": missing, "limit": 0,
             "ok": missing == 0}]


# ---------------------------------------------------------------------------
# the timing loop
# ---------------------------------------------------------------------------

def timed_window(call, seconds: float, clock=time.perf_counter):
    """Free-running loop with a run-ahead of one: dispatch step i+1, then
    wait for the loss of step i and stamp the clock. Stops dispatching when
    ``seconds`` are up, drains. Returns ``(stamps, losses)``; the first
    stamp is the window's start (no step ends there)."""
    start = clock()
    stamps, losses = [start], []
    pending = call()
    while True:
        nxt = call() if clock() - start < seconds else None
        losses.append(float(pending))
        stamps.append(clock())
        if nxt is None:
            return stamps, losses
        pending = nxt


def percentile(values: list[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def window_metrics(stamps: list[float], samples_per_step: int,
                   span_steps: int) -> dict:
    """The window's end-to-end numbers from its stamps. A rate is all the
    samples over all the time. A step's time is read over ``span_steps``
    consecutive steps (every such run of steps, sliding by one), so that
    each reading of the host's clock spans a quarter of a second or more."""
    steps = len(stamps) - 1
    if steps < span_steps:
        raise BenchError(f"{steps} steps in the window, need {span_steps}")
    total = stamps[-1] - stamps[0]
    spans = [(stamps[i + span_steps] - stamps[i]) / span_steps * 1e3
             for i in range(steps - span_steps + 1)]
    single = [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]
    return {"steps": steps, "window_s": total,
            "samples_per_s": samples_per_step * steps / total,
            "step_ms.p95": percentile(spans, 95),
            "step_ms_median": statistics.median(single),
            "step_ms_single_p95": percentile(single, 95),
            "step_ms_max": max(single)}
