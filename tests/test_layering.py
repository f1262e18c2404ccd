"""The architecture as a table the tests hold.

(i) Which unit of ``grace_tpu/`` may import which, lower layers first. Every
file's AST is walked, function-level imports included, so a lazy import
counts like one at the top. An edge outside ``ALLOWED`` fails unless it is a
named debt in ``KNOWN_UPWARD`` — and a debt that has been paid but is still
listed fails too, so the table stays the drawing of the system as it is.
No unit imports a script from the checkout root, under any name.

(ii) What a cell's step loads: each of the benchmark's cells is built and
stepped once in a process of its own, which must end without the static
auditor, the tuner, the second trace reducer or the framework bridges in
``sys.modules``: nothing a cell measures can depend on them.

(iii) What the documents name: every path under ``grace_tpu/``, ``tools/``,
``tests/`` or ``benchmarks/`` and every dotted ``grace_tpu.<unit>`` name in
the README and its sister documents must exist, so that a deletion's sweep
of the documents can be checked.

(iv) What the root's records claim: each ``*_LAST.json`` at the checkout
root must still have the tool that writes it.
"""

import ast
import functools
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "grace_tpu")
WORKLOADS = os.path.join(REPO, "benchmarks", "workloads")

# Local modules that are not part of the package: the scripts and the
# directories of the checkout root, whatever they are called. No unit may
# import one.
ROOT_MODULES = {
    name[:-len(".py")] if name.endswith(".py") else name
    for name in os.listdir(REPO)
    if not name.startswith(".") and name != "grace_tpu"
    and (name.endswith(".py") or os.path.isdir(os.path.join(REPO, name)))}

# unit -> the units it may import. Lower layers first: a unit's row names
# only units above it in this list (its own files are always allowed).
ALLOWED = {
    # named scopes, the compile and host ledgers, the telemetry ring and
    # its sinks
    "telemetry": set(),
    # Compressor / Memory / Communicator, Topology
    "core": {"telemetry"},
    "ops": {"telemetry"},
    "parallel": {"core"},
    "memories": {"core"},
    # the host ledger (telemetry/host.py) is the lowest thing there is:
    # StepTimer takes its thread snapshot, place_compile_cache is one of
    # its spans
    "utils": {"core", "telemetry"},
    "checkpoint": set(),
    "compressors": {"core", "ops", "telemetry"},
    # the fused attention kernel is an op the two decoders call (lfm2,
    # and deepseek_v3, which also imports what it shares from lfm2: the
    # same unit)
    "models": {"telemetry", "ops"},
    "data": {"parallel"},
    "comm": {"core", "memories", "telemetry", "utils"},
    # the optax transform: compensate, compress, exchange, decompress
    "transform": {"comm", "core", "telemetry", "utils"},
    # params dict -> the configured triad
    "helper": {"comm", "compressors", "core", "memories", "telemetry",
               "transform", "utils"},
    # the jitted shard_map step
    "train": {"core", "parallel", "telemetry", "transform"},
    # guard, consensus, adapt; and the host-side controller that drives
    # train steps (elastic)
    "resilience": {"comm", "core", "parallel", "telemetry", "transform",
                   "train"},
    "profiling": {"telemetry", "transform", "utils"},
    # the static auditor: traces a configured step, reads everything below
    "analysis": {"comm", "core", "helper", "ops", "parallel", "profiling",
                 "resilience", "telemetry", "train", "transform"},
    "tuning": {"analysis", "comm", "core", "helper", "models", "parallel",
               "profiling", "train", "transform", "utils"},
    "interop": {"helper", "parallel", "transform"},
    # the package's own __init__: the public names
    "grace_tpu": {"comm", "core", "helper", "parallel", "resilience",
                  "telemetry", "train", "transform"},
}

# Edges that point up today, each with the debt that owns it (ROADMAP.md
# queue 3). Remove a row in the PR that removes the import.
KNOWN_UPWARD = {
    ("transform", "resilience"): "debt (e): transform.py -> resilience.adapt",
    ("train", "resilience"): "debt (e): train.py -> resilience.consensus",
    ("utils", "resilience"): "debt (e): utils/metrics.py -> resilience.guard",
    ("telemetry", "resilience"):
        "debt (e): telemetry/reader.py -> resilience.guard",
    ("telemetry", "checkpoint"):
        "debt (e): telemetry/sinks.py -> checkpoint._retry_io",
    ("helper", "resilience"):
        "debt (e): helper.py -> resilience.adapt (the ladder's rungs)",
    ("resilience", "analysis"):
        "debt (e)/(f): elastic.py -> analysis",
    ("resilience", "profiling"): "debt (e)/(f): elastic.py -> profiling",
    ("analysis", "tuning"):
        "D7: analysis/configs.py -> tuning's generated variants",
}


def unit_files(unit):
    if unit == "grace_tpu":
        return [os.path.join(PACKAGE, "__init__.py")]
    single = os.path.join(PACKAGE, unit + ".py")
    if os.path.isfile(single):
        return [single]
    return sorted(os.path.join(d, f)
                  for d, _, fs in os.walk(os.path.join(PACKAGE, unit))
                  for f in fs if f.endswith(".py"))


def package_of(path):
    """Dotted package a file's relative imports resolve against."""
    rel = os.path.relpath(path, REPO)[:-len(".py")].split(os.sep)
    return rel[:-1]


def imported_modules(path):
    """Every module a file imports, absolute, wherever the statement is."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    package = package_of(path)
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = (package[:len(package) - node.level + 1]
                    if node.level else [])
            module = ".".join(base + ([node.module] if node.module else []))
            found.add(module)
            # `from grace_tpu import comm`, `from . import passes`
            found.update(f"{module}.{a.name}" for a in node.names)
    return found


def edges_of(unit):
    """(units of the package, root modules) that ``unit`` imports."""
    units, roots = set(), set()
    for path in unit_files(unit):
        for module in imported_modules(path):
            parts = module.split(".")
            if parts[0] == "grace_tpu" and len(parts) > 1:
                if parts[1] in ALLOWED and parts[1] != unit:
                    units.add(parts[1])
            elif parts[0] in ROOT_MODULES:
                roots.add(parts[0])
    return units, roots


def test_the_table_covers_the_package():
    on_disk = {"grace_tpu"}
    for name in os.listdir(PACKAGE):
        if name.endswith(".py") and name != "__init__.py":
            on_disk.add(name[:-len(".py")])
        elif os.path.isfile(os.path.join(PACKAGE, name, "__init__.py")):
            on_disk.add(name)
    assert on_disk == set(ALLOWED)
    # lower layers first: a row names only rows written before it
    seen = set()
    for unit, allowed in ALLOWED.items():
        assert allowed <= seen, (unit, allowed - seen)
        seen.add(unit)
    for (unit, target), debt in KNOWN_UPWARD.items():
        assert unit in ALLOWED and target in ALLOWED and debt
        assert target not in ALLOWED[unit]


@pytest.mark.parametrize("unit", list(ALLOWED))
def test_unit_imports_stay_inside_its_layer(unit):
    units, roots = edges_of(unit)
    assert not roots, (
        f"grace_tpu/{unit} imports from the checkout root: {sorted(roots)}")
    known = {t for (u, t) in KNOWN_UPWARD if u == unit}
    new = units - ALLOWED[unit] - known
    assert not new, (
        f"grace_tpu/{unit} now imports {sorted(new)}: move the code, or "
        "draw the edge in ALLOWED if the layering is meant to change")
    paid = known - units
    assert not paid, (
        f"grace_tpu/{unit} no longer imports {sorted(paid)}: take the "
        "row out of KNOWN_UPWARD")


def test_the_host_ledger_imports_nothing_of_the_package_above_telemetry():
    """``telemetry/host.py`` is imported by the first line of
    ``grace_tpu/__init__.py``: whatever it imported would load before the
    import's span began. Not even the debts ``KNOWN_UPWARD`` allows
    ``telemetry`` are its to use."""
    modules = imported_modules(os.path.join(PACKAGE, "telemetry", "host.py"))
    ours = {m for m in modules if m.split(".")[0] == "grace_tpu"}
    assert ours <= {"grace_tpu.telemetry", "grace_tpu.telemetry.compiles"}


# ---------------------------------------------------------------------------
# (ii) a cell's step loads only the hot path
# ---------------------------------------------------------------------------

CELL_STEP = """
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
cell = json.load(open(sys.argv[1]))
import jax, jax.numpy as jnp, optax
from grace_tpu.parallel import set_cpu_device_count
set_cpu_device_count(cell["chips"])
import grace_tpu
from grace_tpu import grace_from_params
from grace_tpu.train import (init_stateful_train_state,
                             make_stateful_train_step)

mesh = grace_tpu.data_parallel_mesh()
assert mesh.devices.size == cell["chips"]
tx = optax.chain(grace_from_params(dict(cell["grace"])).transform(seed=0),
                 optax.sgd(0.1))
params = {"w": jnp.ones((64, 32)), "b": jnp.zeros((32,)),
          "scale": jnp.ones((64,))}

def loss_fn(p, mstate, batch):
    x, y = batch
    out = (x * p["scale"]) @ p["w"] + p["b"]
    return jnp.mean((out - y) ** 2), mstate

state = init_stateful_train_state(params, {}, tx, mesh)
step = make_stateful_train_step(loss_fn, tx, mesh)
n = 8 * cell["chips"]
state, loss = step(state, (jnp.ones((n, 64)), jnp.zeros((n, 32))))
assert bool(jnp.isfinite(loss))
print(json.dumps(sorted(sys.modules)))
"""

OFF_THE_HOT_PATH = ("grace_tpu.analysis", "grace_tpu.tuning",
                    "grace_tpu.profiling", "grace_tpu.interop")


@pytest.mark.parametrize("cell", sorted(
    f[:-len(".json")] for f in os.listdir(WORKLOADS)))
def test_a_cells_step_loads_only_the_hot_path(cell):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = REPO
    done = subprocess.run(
        [sys.executable, "-c", CELL_STEP,
         os.path.join(WORKLOADS, cell + ".json")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert done.returncode == 0, done.stderr[-2000:]
    loaded = json.loads(done.stdout.strip().splitlines()[-1])
    assert "grace_tpu.transform" in loaded and "grace_tpu.train" in loaded
    extra = [m for m in loaded
             if m.startswith(OFF_THE_HOT_PATH)
             or m.split(".")[0] in ROOT_MODULES]
    assert not extra, extra


# ---------------------------------------------------------------------------
# (iii) the documents name only what exists
# ---------------------------------------------------------------------------

DOCUMENTS = ("README.md", "IMPLEMENTING.md", "TRAINING.md",
             "OBSERVABILITY.md", "INSTALLING.md", "examples/README.md")

# `examples/` is left out: the reference repository has one of its own and
# the documents cite it.
DOC_PATH = re.compile(
    r"(?<![\w/.-])(?:grace_tpu|tools|tests|benchmarks)/[\w./-]*")
DOC_DOTTED = re.compile(r"(?<![\w.])grace_tpu(?:\.[A-Za-z_]\w*)+")


def bound_names(init_py):
    """The names a package's ``__init__.py`` binds at its top level."""
    with open(init_py) as f:
        tree = ast.parse(f.read(), init_py)
    names = set()
    for node in tree.body:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            names.update((a.asname or a.name).split(".")[0]
                         for a in node.names)
        elif isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, ast.Assign):
            names.update(t.id for t in node.targets
                         if isinstance(t, ast.Name))
    return names


def dotted_name_resolves(dotted):
    """Walk ``grace_tpu.a.b.c`` down the packages. It ends at a module
    (what follows is an attribute, and is not resolved) or at a name a
    package's ``__init__.py`` binds."""
    where = REPO
    for part in dotted.split("."):
        if os.path.isdir(os.path.join(where, part)):
            where = os.path.join(where, part)
        elif os.path.isfile(os.path.join(where, part + ".py")):
            return True
        else:
            return part in bound_names(os.path.join(where, "__init__.py"))
    return True


@pytest.mark.parametrize("document", DOCUMENTS)
def test_a_document_names_only_what_exists(document):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    # a sentence's full stop is not the path's
    paths = {p.rstrip(".") for p in DOC_PATH.findall(text)}
    gone = sorted(p for p in paths
                  if not os.path.exists(os.path.join(REPO, p)))
    gone += sorted(n for n in set(DOC_DOTTED.findall(text))
                   if not dotted_name_resolves(n))
    assert not gone, f"{document} names what is not there: {gone}"


# ---------------------------------------------------------------------------
# (iv) a record at the root has the tool that writes it
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def written_names():
    """Every string a file under ``tools/`` or ``grace_tpu/`` holds outside
    its docstrings: an output's name is one of them, a mention in prose is
    not."""
    found = set()
    for top in ("tools", "grace_tpu"):
        for d, _, fs in os.walk(os.path.join(REPO, top)):
            for f in fs:
                if not f.endswith(".py"):
                    continue
                with open(os.path.join(d, f)) as src:
                    tree = ast.parse(src.read(), f)
                docstrings = {
                    id(node.body[0].value) for node in ast.walk(tree)
                    if isinstance(node, (ast.Module, ast.FunctionDef,
                                         ast.ClassDef))
                    and node.body and isinstance(node.body[0], ast.Expr)}
                found.update(
                    node.value for node in ast.walk(tree)
                    if isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and id(node) not in docstrings)
    return frozenset(found)


@pytest.mark.parametrize("record", sorted(
    f for f in os.listdir(REPO) if f.endswith("_LAST.json")))
def test_a_root_record_has_the_tool_that_writes_it(record):
    assert record in written_names(), (
        f"{record}: no file under tools/ or grace_tpu/ names it as an "
        "output. A record whose producer has gone is a claim nobody can "
        "make again: delete it with the producer")
