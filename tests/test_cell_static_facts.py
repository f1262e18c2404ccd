"""The static decisions the nine cells' compiled steps depend on, at the
cells' own shapes.

Every route the transform takes for a leaf follows from static facts: the
cell's ``grace`` parameters, the leaf's shape, the world size. ``PERF.md``
states them in prose ("no ResNet-50 leaf takes the row-slices route at W=1",
"27 of LFM2's 50 leaves do", "32 of kanana's 69", "22 of SDAR's 51", "22 of Qwen3-Next's 70"); here they are assertions, on parameter trees
taken with ``jax.eval_shape`` from the benchmark's own builders at the sizes
in ``benchmarks/configs/*.json`` (read, never edited; no weight is made).
The expected values are written down, not computed by the code under test:
a change that moves one is a change to a compiled step and says so here
before a chip run does.
"""

import os
import sys

import jax
import jax.numpy as jnp
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import harness  # noqa: E402
from benchmarks.reference import train as plain  # noqa: E402
from grace_tpu import grace_from_params  # noqa: E402
from grace_tpu.compressors.topk import static_k  # noqa: E402
from grace_tpu.ops.sparse import takes_row_slices  # noqa: E402
from grace_tpu.transform import GraceState  # noqa: E402
from grace_tpu.utils.metrics import payload_nbytes, wire_report  # noqa: E402

# cell -> (configuration, compressor, memory, communicator): the classes
# `grace_from_params(cell["grace"])` resolves to.
CELLS = {
    "resnet50-topk1pct-w1": ("resnet50-imagenet", "TopKCompressor",
                             "ResidualMemory", "Allgather"),
    "resnet50-topk1pct-w4": ("resnet50-imagenet", "TopKCompressor",
                             "ResidualMemory", "Allgather"),
    "resnet50-dense-w1": ("resnet50-imagenet", "NoneCompressor",
                          "NoneMemory", "Allreduce"),
    "bert-base-powersgd4-w1": ("bert-base-squad", "PowerSGDCompressor",
                               "PowerSGDMemory", "Allreduce"),
    "lfm2-24b-a2b-topk1pct-w1": ("lfm2-24b-a2b-ep8", "TopKCompressor",
                                 "ResidualMemory", "Allgather"),
    "kanana-2-30b-a3b-topk1pct-w1": ("kanana-2-30b-a3b-ep16",
                                     "TopKCompressor", "ResidualMemory",
                                     "Allgather"),
    "sdar-30b-a3b-blockdiff-topk1pct-w1": ("sdar-30b-a3b-ep8",
                                           "TopKCompressor",
                                           "ResidualMemory", "Allgather"),
    "smallthinker-21b-a3b-swa16k-topk1pct-w1": ("smallthinker-21b-a3b-ep8",
                                                "TopKCompressor",
                                                "ResidualMemory",
                                                "Allgather"),
    "qwen3-next-80b-a3b-gdn16k-topk1pct-w1": ("qwen3-next-80b-a3b-ep32",
                                              "TopKCompressor",
                                              "ResidualMemory", "Allgather"),
}

# configuration -> (leaves, parameters, leaves on the row-slices route under
# top-k 1 %, bytes a chip sends a step under its cells' codec).
CONFIGS = {
    "resnet50-imagenet": (161, 25_557_032, 0, 2_044_104),
    "lfm2-24b-a2b-ep8": (50, 486_062_208, 27, 38_884_848),
    "bert-base-squad": (150, 108_793_346, None, 3_369_912),
    "kanana-2-30b-a3b-ep16": (69, 424_960_512, 32, 33_996_704),
    "sdar-30b-a3b-ep8": (51, 456_346_624, 22, 36_507_584),
    "smallthinker-21b-a3b-ep8": (43, 370_547_200, 22, 29_643_640),
    "qwen3-next-80b-a3b-ep32": (70, 424_340_544, 22, 33_947_040),
}

# Top-k 1 % chunk, per distinct leaf shape:
# (shape, leaves of it, elements, k, rows of the (rows, k) view, whether the
# view is reached through row-block slices of the flat buffer).
TOPK_LEAVES = {
    "resnet50-imagenet": [
        ((64,), 14, 64, 1, 64, False),
        ((128,), 16, 128, 1, 128, False),
        ((256,), 32, 256, 2, 128, False),
        ((512,), 22, 512, 5, 103, False),
        ((1000,), 1, 1000, 10, 100, False),
        ((1024,), 14, 1024, 10, 103, False),
        ((2048,), 8, 2048, 20, 103, False),
        ((2048, 1000), 1, 2048000, 20480, 100, False),
        ((1, 1, 64, 64), 1, 4096, 40, 103, False),
        ((1, 1, 64, 256), 4, 16384, 163, 101, False),
        ((1, 1, 128, 512), 4, 65536, 655, 101, False),
        ((1, 1, 256, 64), 2, 16384, 163, 101, False),
        ((1, 1, 256, 128), 1, 32768, 327, 101, False),
        ((1, 1, 256, 512), 1, 131072, 1310, 101, False),
        ((1, 1, 256, 1024), 6, 262144, 2621, 101, False),
        ((1, 1, 512, 128), 3, 65536, 655, 101, False),
        ((1, 1, 512, 256), 1, 131072, 1310, 101, False),
        ((1, 1, 512, 1024), 1, 524288, 5242, 101, False),
        ((1, 1, 512, 2048), 3, 1048576, 10485, 101, False),
        ((1, 1, 1024, 256), 5, 262144, 2621, 101, False),
        ((1, 1, 1024, 512), 1, 524288, 5242, 101, False),
        ((1, 1, 1024, 2048), 1, 2097152, 20971, 101, False),
        ((1, 1, 2048, 512), 2, 1048576, 10485, 101, False),
        ((3, 3, 64, 64), 3, 36864, 368, 101, False),
        ((3, 3, 128, 128), 4, 147456, 1474, 101, False),
        ((3, 3, 256, 256), 6, 589824, 5898, 101, False),
        # the largest view of this configuration: 101 * 23,592 = 2,382,792
        # elements, under ops.sparse.RELAYOUT_LOOP_ELEMENTS
        ((3, 3, 512, 512), 3, 2359296, 23592, 101, False),
        ((7, 7, 3, 64), 1, 9408, 94, 101, False),
    ],
    "lfm2-24b-a2b-ep8": [
        ((64,), 2, 64, 1, 64, False),
        ((2048,), 11, 2048, 20, 103, False),
        ((3, 2048), 4, 6144, 61, 101, False),
        ((2048, 64), 4, 131072, 1310, 101, False),
        ((2048, 512), 2, 1048576, 10485, 101, False),
        # exactly 2**22 elements, and 101 * 41,943 = 4,236,243 in the view
        ((2048, 2048), 6, 4194304, 41943, 101, True),
        ((2048, 6144), 4, 12582912, 125829, 101, True),
        ((2048, 8192), 1, 16777216, 167772, 101, True),
        ((2048, 11776), 2, 24117248, 241172, 101, True),
        ((8192, 2048), 1, 16777216, 167772, 101, True),
        ((11776, 2048), 1, 24117248, 241172, 101, True),
        ((8, 1536, 2048), 4, 25165824, 251658, 101, True),
        ((8, 2048, 1536), 8, 25165824, 251658, 101, True),
    ],
    "kanana-2-30b-a3b-ep16": [
        ((512,), 5, 512, 5, 103, False),            # the latent's norm
        ((2048,), 11, 2048, 20, 103, False),
        # W_kvb: exactly 2**22 elements, as LFM2's (2048, 2048)
        ((512, 8192), 5, 4194304, 41943, 101, True),
        # the shared expert's three, 3,177,157 elements in the view: under
        # the constant
        ((1536, 2048), 4, 3145728, 31457, 101, False),
        ((2048, 1536), 8, 3145728, 31457, 101, False),
        ((2048, 128), 4, 262144, 2621, 101, False),         # the router
        ((2048, 576), 5, 1179648, 11796, 101, False),       # W_kva
        ((2048, 6144), 7, 12582912, 125829, 101, True),     # W_q; dense w1, w3
        ((6144, 2048), 1, 12582912, 125829, 101, True),
        ((4096, 2048), 5, 8388608, 83886, 101, True),       # W_o
        ((2048, 16032), 1, 32833536, 328335, 101, True),
        ((16032, 2048), 1, 32833536, 328335, 101, True),
        ((8, 768, 2048), 4, 12582912, 125829, 101, True),
        ((8, 2048, 768), 8, 12582912, 125829, 101, True),
    ],
    "sdar-30b-a3b-ep8": [
        ((128,), 8, 128, 1, 128, False),            # the heads' q and k norms
        ((2048,), 9, 2048, 20, 103, False),
        ((2048, 128), 4, 262144, 2621, 101, False),         # the router
        ((2048, 512), 8, 1048576, 10485, 101, False),       # W_k, W_v
        ((2048, 4096), 4, 8388608, 83886, 101, True),       # W_q
        ((4096, 2048), 4, 8388608, 83886, 101, True),       # W_o
        ((2048, 18992), 1, 38895616, 388956, 101, True),
        ((18992, 2048), 1, 38895616, 388956, 101, True),
        # sixteen experts a stack: LFM2's stack of eight at twice the width
        ((16, 768, 2048), 4, 25165824, 251658, 101, True),
        ((16, 2048, 768), 8, 25165824, 251658, 101, True),
    ],
    "smallthinker-21b-a3b-ep8": [
        ((2560,), 9, 2560, 25, 103, False),         # no norm on the heads
        ((2560, 64), 4, 163840, 1638, 101, False),          # the router
        ((2560, 512), 8, 1310720, 13107, 101, False),       # W_k, W_v
        ((2560, 3584), 4, 9175040, 91750, 101, True),       # W_q: 28 heads
        ((3584, 2560), 4, 9175040, 91750, 101, True),       # W_o
        ((2560, 18992), 1, 48619520, 486195, 101, True),
        ((18992, 2560), 1, 48619520, 486195, 101, True),
        ((8, 768, 2560), 4, 15728640, 157286, 101, True),
        ((8, 2560, 768), 8, 15728640, 157286, 101, True),
    ],
    "qwen3-next-80b-a3b-ep32": [
        # a value head's A_log and dt_bias: one entry of 32 kept a step
        ((32,), 6, 32, 1, 32, False),
        ((128,), 3, 128, 1, 128, False),            # the gated norm
        ((256,), 2, 256, 2, 128, False),            # the full layer's q, k norms
        ((2048,), 9, 2048, 20, 103, False),
        ((4, 8192), 3, 32768, 327, 101, False),             # the convolution
        ((2048, 1), 4, 2048, 20, 103, False),       # the shared expert's gate
        ((2048, 64), 3, 131072, 1310, 101, False),          # W_ba
        # the router, W_k, W_v and the shared expert's w1, w3
        ((2048, 512), 14, 1048576, 10485, 101, False),
        ((512, 2048), 4, 1048576, 10485, 101, False),       # its w2
        ((2048, 8192), 1, 16777216, 167772, 101, True),     # W_q with its gate
        ((2048, 12288), 3, 25165824, 251658, 101, True),    # W_qkvz
        ((4096, 2048), 4, 8388608, 83886, 101, True),       # W_out, W_o
        ((2048, 18992), 1, 38895616, 388956, 101, True),
        ((18992, 2048), 1, 38895616, 388956, 101, True),
        # sixteen experts a stack of width 512: 2**24 elements, W_q's
        ((16, 512, 2048), 4, 16777216, 167772, 101, True),
        ((16, 2048, 512), 8, 16777216, 167772, 101, True),
    ],
}

# PowerSGD rank 4 on BERT-base, per distinct leaf shape: (shape, leaves of
# it, (n, m, r) of the factors P (n, r) and Q (m, r), or None for a 1-D
# leaf, which passes dense).
# configuration -> a cell that compresses it (the codec of its other
# compressing cells is the same)
CODEC_CELL = {"resnet50-imagenet": "resnet50-topk1pct-w1",
              "lfm2-24b-a2b-ep8": "lfm2-24b-a2b-topk1pct-w1",
              "kanana-2-30b-a3b-ep16": "kanana-2-30b-a3b-topk1pct-w1",
              "sdar-30b-a3b-ep8": "sdar-30b-a3b-blockdiff-topk1pct-w1",
              "smallthinker-21b-a3b-ep8":
                  "smallthinker-21b-a3b-swa16k-topk1pct-w1",
              "qwen3-next-80b-a3b-ep32":
                  "qwen3-next-80b-a3b-gdn16k-topk1pct-w1",
              "bert-base-squad": "bert-base-powersgd4-w1"}

POWERSGD_LEAVES = [
    ((2,), 1, None),
    ((768,), 74, None),
    ((2304,), 12, None),
    ((3072,), 12, None),
    ((384, 768), 1, (384, 768, 4)),
    ((768, 2), 1, (768, 2, 2)),          # rank capped by the two outputs
    ((768, 768), 12, (768, 768, 4)),
    ((768, 2304), 12, (768, 2304, 4)),
    ((768, 3072), 12, (768, 3072, 4)),
    ((3072, 768), 12, (3072, 768, 4)),
    ((30522, 768), 1, (30522, 768, 4)),
]


@pytest.fixture(scope="module")
def catalog():
    return harness.Catalog()


@pytest.fixture(scope="module")
def tree(catalog):
    """``tree(configuration)``: its abstract parameter tree."""
    made = {}

    def of(name):
        if name not in made:
            config = catalog.config(name)
            builder = catalog.builder(config)
            made[name], _ = jax.eval_shape(
                lambda k: builder.init(k, config), jax.random.key(0))
        return made[name]

    return of


@pytest.fixture(scope="module")
def report(catalog, tree):
    """``report(configuration)``: ``wire_report`` of its tree under its
    cells' codec, once a configuration."""
    made = {}

    def of(name):
        if name not in made:
            made[name] = wire_report(
                grace_of(catalog, CODEC_CELL[name]).compressor, tree(name))
        return made[name]

    return of


def leaves_of(params, shape=None):
    leaves = jax.tree_util.tree_leaves(params)
    return [l for l in leaves if shape is None or l.shape == shape]


def grace_of(catalog, cell):
    return grace_from_params(dict(catalog.cell(cell)["grace"]))


def _id(case):
    return "x".join(str(d) for d in case[0])


def test_the_tables_name_the_benchmarks_cells_and_configurations():
    assert sorted(CELLS) == sorted(
        f[:-len(".json")] for f in os.listdir(
            os.path.join(REPO, "benchmarks", "workloads")))
    assert sorted(CONFIGS) == sorted(
        f[:-len(".json")] for f in os.listdir(
            os.path.join(REPO, "benchmarks", "configs")))


# ---------------------------------------------------------------------------
# (i) the branch of grace_transform the driver measures
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_takes_the_plain_branch_of_the_transform(catalog, tree, cell):
    config, compressor, memory, communicator = CELLS[cell]
    spec = catalog.cell(cell)
    assert spec["config"] == config
    grace = grace_from_params(dict(spec["grace"]))
    assert (type(grace.compressor).__name__, type(grace.memory).__name__,
            type(grace.communicator).__name__) == (compressor, memory,
                                                   communicator)
    # per leaf, and nothing armed: no escape, ring, audit, watch, routes
    # or ladder
    assert grace.fusion is None and grace.routes == ()
    assert (grace.escape, grace.telemetry, grace.consensus, grace.watch,
            grace.adapt, grace.mesh) == (None,) * 6

    params = tree(config)
    tx = optax.chain(grace.transform(seed=0),
                     plain.optimizer(spec["optimizer"]))
    found = [s for s in jax.tree_util.tree_leaves(
        jax.eval_shape(tx.init, params),
        is_leaf=lambda n: isinstance(n, GraceState))
        if isinstance(s, GraceState)]
    assert len(found) == 1
    state, = found
    assert (state.telem, state.audit, state.watch, state.adapt) \
        == (None,) * 4
    leaves = leaves_of(params)
    assert len(state.mem) == len(state.comp) == len(leaves)
    if compressor == "TopKCompressor":
        # a float32 residual of the leaf's shape, no codec state
        assert [(m.shape, m.dtype) for m in state.mem] \
            == [(l.shape, jnp.float32) for l in leaves]
        assert state.comp == (None,) * len(leaves)
    elif compressor == "PowerSGDCompressor":
        assert [m if m is None else m.shape for m in state.mem] \
            == [None if l.ndim <= 1 else l.shape for l in leaves]
    else:
        assert state.mem == state.comp == (None,) * len(leaves)


# ---------------------------------------------------------------------------
# (ii) top-k 1 % chunk: k, rows, route and payload of every leaf shape
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "config,case",
    [pytest.param(c, case, id=f"{c}-{_id(case)}")
     for c in sorted(TOPK_LEAVES) for case in TOPK_LEAVES[c]])
def test_topk_leaf_static_facts(catalog, tree, report, config, case):
    shape, count, n, k, rows, row_slices = case
    params = tree(config)
    mine = leaves_of(params, shape)
    assert len(mine) == count and mine[0].size == n
    codec = grace_of(catalog, CODEC_CELL[config]).compressor
    assert codec.algorithm == "chunk" and codec.compress_ratio == 0.01

    assert static_k(n, codec.compress_ratio) == k
    assert -(-n // k) == rows and n >= 2 * k
    assert takes_row_slices(rows, k) is row_slices

    # the payload: k float32 values and k int32 indices, which is this
    # leaf's share of what `wire_bytes` reports
    leaf = mine[0]
    values, indices = jax.eval_shape(
        lambda x: codec.compress(x, None, jax.random.key(0))[0], leaf)
    assert (values.shape, values.dtype) == ((k,), jnp.float32)
    assert (indices.shape, indices.dtype) == ((k,), jnp.int32)
    assert payload_nbytes(codec, leaf) == 8 * k
    shares = [r for r, l in zip(report(config).leaves, leaves_of(params))
              if l.shape == shape]
    assert [(r.wire_bytes, r.dense_bytes) for r in shares] \
        == [(8 * k, 4 * n)] * count

    # the all-gather's decode. One payload (W=1): left to the
    # communicator's own decode, which fuses into its consumer, unless the
    # leaf walks row blocks. Four payloads: every leaf is summed in the
    # (rows, k) view (PR 27), since every leaf has n >= 2k.
    def decode(world):
        return jax.eval_shape(
            lambda v, i: codec.fused_aggregate_decompress(
                (v, i), (n, shape, jnp.float32), world),
            jax.ShapeDtypeStruct((world, k), jnp.float32),
            jax.ShapeDtypeStruct((world, k), jnp.int32))

    one = decode(1)
    assert (one is not None) is row_slices
    four = decode(4)
    assert (four.shape, four.dtype) == (shape, jnp.float32)


# ---------------------------------------------------------------------------
# (iii) PowerSGD rank 4 on BERT-base: the factors of every leaf shape
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", POWERSGD_LEAVES, ids=_id)
def test_powersgd_leaf_static_facts(catalog, tree, case):
    shape, count, factors = case
    params = tree("bert-base-squad")
    mine = leaves_of(params, shape)
    assert len(mine) == count
    grace = grace_of(catalog, "bert-base-powersgd4-w1")
    codec, leaf = grace.compressor, mine[0]
    assert codec.rank == 4 and codec.warm_start
    q = jax.eval_shape(codec.init_state, leaf)
    residual = jax.eval_shape(grace.memory.init_state, leaf)
    if factors is None:
        # 1-D: no factor, no residual, the whole leaf on the wire
        assert q is None and residual is None
        payload = jax.eval_shape(
            lambda x: codec.compress(x, None, jax.random.key(0))[0], leaf)
        assert [p.shape for p in payload] == [shape]
        assert codec.wire_nbytes(shape, leaf.dtype) == 4 * leaf.size
    else:
        n, m, r = factors
        assert codec._factor_shapes(shape) == factors
        assert n * m == leaf.size
        assert (q.shape, q.dtype) == ((m, r), jnp.float32)
        assert (residual.shape, residual.dtype) == (shape, jnp.float32)
        # P (n, r) and Q (m, r) are summed over the ranks inside compress
        assert codec.wire_nbytes(shape, leaf.dtype) == 4 * r * (n + m)


# ---------------------------------------------------------------------------
# summed per configuration: what PERF.md states in prose
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_configuration_totals(tree, report, config):
    n_leaves, n_params, n_row_slices, wire = CONFIGS[config]
    leaves = leaves_of(tree(config))
    assert len(leaves) == n_leaves
    assert sum(l.size for l in leaves) == n_params
    assert {l.dtype for l in leaves} == {jnp.dtype("float32")}
    shapes = {l.shape for l in leaves}
    if config in TOPK_LEAVES:
        table = TOPK_LEAVES[config]
        assert {c[0] for c in table} == shapes      # no shape left out
        assert sum(c[1] for c in table) == n_leaves
        assert sum(c[1] * c[2] for c in table) == n_params
        assert sum(c[1] for c in table if c[5]) == n_row_slices
        assert sum(c[1] * 8 * c[3] for c in table) == wire
    else:
        assert {c[0] for c in POWERSGD_LEAVES} == shapes
        assert sum(c[1] for c in POWERSGD_LEAVES) == n_leaves
    assert report(config).wire_bytes == wire
