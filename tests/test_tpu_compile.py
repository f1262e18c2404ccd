"""Mosaic compile tests: the kernels of the main path, and the exchange
paths that call them, compiled for a TPU v5e that is described and not
attached (``on-chip-measurement`` guide, section 2.3).

Interpret mode accepts programs Mosaic refuses — a squeezed SMEM block, an
unaligned slice, too much VMEM — so every CPU test of the kernels can pass
while the chip's compiler rejects them. These compiles run the chip's own
compiler (libtpu is installed; no chip is needed) at ResNet-50's flat
gradient size, and pin the refusal PR 21 found: the per-shard compress of
the ring family batched the quantize kernels' SMEM scalars into a shape
Mosaic rejects, so QSGD × {ring, twoshot, hier, rscatter} could not be
lowered for more than one chip.

This is the only file that describes the chip. The topology is described
inside a module-scoped fixture (never at import, in a ``skipif`` or in
``parametrize`` arguments): the process that describes it holds libtpu's
lock until it exits, so every xdist worker must collect the same tests and
only the worker that runs this file may load the library. A compile that
passes here is not a chip run and is never reported as one.
"""

import dataclasses
import functools
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import grace_tpu.ops
from grace_tpu import grace_from_params
from grace_tpu.compressors.topk import static_k
from grace_tpu.memories import ResidualMemory
from grace_tpu.models import (deepseek_v3, lfm2, qwen3_next, resnet, sdar,
                              smallthinker)
from grace_tpu.ops import pallas_attention, sparse
from grace_tpu.ops.pallas_quant import (quantize_pack_stochastic,
                                        quantize_stochastic, sign_pack)
from grace_tpu.ops.pallas_topk import (chunk_aggregate_dense,
                                       chunk_compress_feedback)
from grace_tpu.ops.pallas_wire import (decode_accumulate,
                                       packed_int_accumulate)
from grace_tpu.parallel import shard_map

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:        # benchmarks/: the LFM2 configuration's file
    sys.path.insert(0, REPO)

from benchmarks import harness  # noqa: E402
from benchmarks.models import deepseek_v3 as kanana  # noqa: E402
from benchmarks.models import lfm2_moe  # noqa: E402
from benchmarks.models import qwen3_next as qwen3_next_moe  # noqa: E402
from benchmarks.models import sdar_moe  # noqa: E402
from benchmarks.models import smallthinker_moe  # noqa: E402
from benchmarks.reference import train as plain_train  # noqa: E402
from benchmarks.trace_reduce import stage_of  # noqa: E402

N = 25_557_032            # ResNet-50's flat gradient
K = N // 100              # top-k 1 %
# The two 3-bit kernels that WRITE packed bytes take XLA:TPU 15 s each at N
# (the (rows, 96) uint8 output's flatten, not Mosaic); they compile at 64
# kernel blocks instead. chip_smoke.py runs them at N on the chip itself.
N_3BIT_OUT = 64 * 16384
SHARD = 1 << 20           # the per-shard compress regression's buffer
# The four-chip exchanges take a buffer of one kernel block per shard: the
# staged unpack_4bit of the final decode (a (n/2, 2) -> (n,) uint8
# relayout) takes XLA:TPU 25 s to compile at 256 Ki elements and 1 s here.
EXCHANGE = 4 * 16384
Q_FOR_WIDTH = {2: 1, 3: 3, 4: 7}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernels_on(monkeypatch):
    """Steer the ONE selection rule as a TPU would resolve it: the code
    under test asks ``jax.default_backend()``, which still says cpu here."""
    def as_on_tpu(use_pallas, kernel="quant"):
        if grace_tpu.ops.pallas_disabled(explicit=use_pallas is True,
                                         kernel=kernel):
            return False, False
        return use_pallas is True or use_pallas == "auto", False
    monkeypatch.setattr(grace_tpu.ops, "pallas_mode", as_on_tpu)


def compile_text(fn, *avals) -> str:
    return jax.jit(fn).lower(*avals).compile().as_text()


def packed_bytes(width: int, n: int = N) -> int:
    return -(-n * width // 8)


# ---------------------------------------------------------------------------
# every Pallas entry point at n = 25,557,032
# ---------------------------------------------------------------------------

def _kernel_cases():
    f32, i32, u8 = jnp.float32, jnp.int32, jnp.uint8
    cases = {
        "chunk_compress_feedback": (
            lambda x, r: chunk_compress_feedback(x, r, K),
            [((N,), f32), ((N,), f32)]),
        "quantize_stochastic": (
            lambda x, nrm, s: quantize_stochastic(x, nrm, s, 64),
            [((N,), f32), ((), f32), ((), i32)]),
        "sign_pack": (lambda x: sign_pack(x), [((N,), f32)]),
        "decode_accumulate-sign": (
            lambda p, s: decode_accumulate(p, s, N, 1, sign=True),
            [((2, packed_bytes(1)), u8), ((2,), f32)]),
        "decode_accumulate-vote": (
            lambda p, s: decode_accumulate(p, s, N, 1, sign=True, vote=True),
            [((3, packed_bytes(1)), u8), ((3,), f32)]),
    }
    for world in (1, 4):
        cases[f"chunk_aggregate_dense-W{world}"] = (
            lambda v, w: chunk_aggregate_dense(v, w, K, N),
            [((world, K), f32), ((world, K), i32)])
    for width, q in Q_FOR_WIDTH.items():
        n = N_3BIT_OUT if width == 3 else N
        cases[f"quantize_pack_stochastic-w{width}"] = (
            lambda x, nrm, s, q=q, width=width: quantize_pack_stochastic(
                x, nrm, s, q, width=width),
            [((n,), f32), ((), f32), ((), i32)])
        cases[f"decode_accumulate-w{width}"] = (
            lambda p, s, width=width: decode_accumulate(p, s, N, width),
            [((2, packed_bytes(width)), u8), ((2,), f32)])
        cases[f"packed_int_accumulate-w{width}"] = (
            lambda p, width=width, n=n: packed_int_accumulate(p, n, width),
            [((2, packed_bytes(width, n)), u8)])
    return cases


KERNEL_CASES = _kernel_cases()


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_kernel_compiles_under_mosaic(one_chip, name):
    fn, shapes = KERNEL_CASES[name]
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    assert "tpu_custom_call" in compile_text(fn, *avals), name


# ---------------------------------------------------------------------------
# the refusal found in PR 21: batched SMEM scalars in the per-shard compress
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quantum_num", [64, 7], ids=["qsgd8", "qsgd4packed"])
def test_per_shard_compress_lowers_with_kernels(one_chip, kernels_on,
                                                quantum_num):
    """The per-shard compress of a ring hop (comm._shard_compress: vmap of
    ``compress`` over the W shard rows) over W=4 shards of a 1 Mi-element
    buffer, for one described chip. On the parent commit Mosaic refuses the
    vmapped kernel: its (1,)-shaped SMEM scalars become (4, 1) operands
    with a squeezed block."""
    from grace_tpu.comm import _shard_compress
    from grace_tpu.compressors import QSGDCompressor

    codec = QSGDCompressor(quantum_num=quantum_num)     # use_pallas='auto'

    def per_shard(chunks):
        payloads, ctx_arrays, _, _ = _shard_compress(
            codec, chunks, jax.random.key(0), "ring")
        return payloads, ctx_arrays

    chunks = jax.ShapeDtypeStruct((4, SHARD // 4), jnp.float32,
                                  sharding=one_chip)
    assert "tpu_custom_call" in compile_text(per_shard, chunks)


@pytest.mark.parametrize("communicator", [
    {"communicator": "ring"},
    {"communicator": "twoshot"},
    {"communicator": "hier", "slice_size": 2},
    {"communicator": "rscatter"},
], ids=lambda p: p["communicator"])
@pytest.mark.parametrize("quantum_num", [64, 7], ids=["qsgd8", "qsgd4packed"])
def test_qsgd_auto_exchange_lowers_for_four_chips(topo, kernels_on,
                                                  communicator, quantum_num):
    """QSGD with its DEFAULT ``use_pallas='auto'`` through every
    shard-compressing communicator, one program over the four described
    chips: the kernels are in the compiled text and the collectives
    partition."""
    grace = grace_from_params({"compressor": "qsgd",
                               "quantum_num": quantum_num,
                               "memory": "none", **communicator})
    comm, codec, memory = grace.communicator, grace.compressor, grace.memory
    mesh = Mesh(np.asarray(topo.devices), ("data",))

    def body(x):
        x = x[0]
        out, _, _ = comm.step(x, memory.init_state(x), codec.init_state(x),
                              memory, codec, jax.random.key(0))
        return out[None]

    step = shard_map(body, mesh=mesh, in_specs=P("data"),
                     out_specs=P("data"))
    x = jax.ShapeDtypeStruct((4, EXCHANGE), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    text = compile_text(step, x)
    assert "tpu_custom_call" in text
    assert any(op in text for op in ("collective-permute", "all-to-all",
                                     "all-gather", "all-reduce"))


# ---------------------------------------------------------------------------
# the relayout loops PR 27 took out of the four-chip all-gather decode
# ---------------------------------------------------------------------------

def test_topk_chunk_allgather_decode_has_no_relayout_loop(topo):
    """The all-gather exchange of ONE (3, 3, 512, 512) leaf at top-k 1 %
    chunk over the four described chips. Decoded per rank under vmap, the
    (4, 101, 23592) stack's flatten (k is no multiple of 128: a physical
    relayout of a tiled layout) compiled to ``while`` loops over row
    windows, float32 and int32 twin alike: 36 a step in ResNet-50, 11 % of
    the four-chip step. Summed in the (rows, k) view first, no loop is
    left, and the wire is what it was: one collective per payload tensor
    (XLA:TPU runs an all-gather this small as an all-reduce of a buffer
    each chip wrote its own piece of; in the whole step it combines them
    into real all-gathers)."""
    grace = grace_from_params({"compressor": "topk", "compress_ratio": 0.01,
                               "topk_algorithm": "chunk", "memory": "none",
                               "communicator": "allgather"})
    comm, codec = grace.communicator, grace.compressor
    mesh = Mesh(np.asarray(topo.devices), ("data",))

    def body(x):
        payload, ctx, _ = codec.compress(x[0], None, jax.random.key(0))
        return comm.exchange(payload, ctx, codec)[None]

    step = shard_map(body, mesh=mesh, in_specs=P("data"),
                     out_specs=P("data"))
    x = jax.ShapeDtypeStruct((4, 3, 3, 512, 512), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    text = compile_text(step, x)
    assert " while(" not in text
    assert "%wide.body" not in text
    collectives = sum(text.count(f" {op}{start}(")
                      for op in ("all-gather", "all-reduce")
                      for start in ("", "-start"))
    assert collectives == 2                 # values, indices


def scope_engagements(lowered_text: str, scope_op: str) -> int:
    """Operations of a lowered module (``as_text(debug_info=True)``) whose
    name stack ends in ``scope_op``."""
    names = re.findall(r'^(#loc\d+) = loc\("[^"]*%s"' % re.escape(scope_op),
                       lowered_text, re.M)
    return sum(lowered_text.count(f"loc({n})") for n in names)


@pytest.mark.parametrize("params, world, engaged", [
    ({"compressor": "topk", "compress_ratio": 0.01,
      "topk_algorithm": "chunk", "memory": "residual",
      "communicator": "allgather"}, 4, 4),
    ({"compressor": "topk", "compress_ratio": 0.01,
      "topk_algorithm": "chunk", "memory": "residual",
      "communicator": "allgather"}, 1, 0),
    ({"compressor": "topk", "compress_ratio": 0.01,
      "memory": "residual", "communicator": "allgather"}, 4, 0),
    ({"compressor": "powersgd", "compress_rank": 2, "memory": "powersgd",
      "communicator": "allgather"}, 4, 0),
    ({"compressor": "none", "memory": "none",
      "communicator": "allreduce"}, 4, 0),
], ids=["topk-chunk-w4", "topk-chunk-w1", "topk-exact-w4", "powersgd-w4",
        "dense-w4"])
def test_aggregate_rows_engagement_count_in_lowered_step(params, world,
                                                        engaged):
    """How often the staged aggregate-then-reshape decode engages is a
    static count: the leaves whose exchange carries the sub-scope
    ``grace/decompress/aggregate_rows`` in the lowered train step (one
    ``iota``, the row index, per engagement). Every leaf with numel >= 2k
    of a chunk top-k all-gather over more than one device; none at one
    device, for another algorithm, for PowerSGD (empty payload) or dense.
    A small step on the CPU mesh: nothing here needs the described chip."""
    import optax
    from grace_tpu.train import init_train_state, make_train_step

    mesh = Mesh(np.asarray(jax.devices()[:world]), ("data",))
    model = {"conv": jnp.ones((3, 3, 8, 16)), "dense": jnp.ones((64, 32)),
             "bias": jnp.ones((32,)), "pair": jnp.ones((2,)),
             "scalar": jnp.ones((1,))}      # numel 1 < 2k: scatter path

    def loss_fn(p, batch):
        return sum(jnp.sum(leaf) for leaf in p.values()) * jnp.mean(batch)

    tx = optax.chain(grace_from_params(params).transform(seed=0),
                     optax.sgd(0.1))
    state = init_train_state(model, tx, mesh)
    batch = jnp.ones((world * 2, 3))
    step = make_train_step(loss_fn, tx, mesh, donate=False)
    jax.eval_shape(step, state, batch)
    fn = next(iter(step.jit_cache.values()))
    text = fn.lower(state, batch).as_text(debug_info=True)
    assert scope_engagements(
        text, "grace/decompress/aggregate_rows/iota") == engaged


# ---------------------------------------------------------------------------
# the relayout loops PR 29 took out of the one-chip step's large leaves
# ---------------------------------------------------------------------------

def _leaf_pipeline(g, r):
    """One leaf's compensate -> compress -> update -> decompress, top-k 1 %
    chunk with a float32 residual: what the transform runs per leaf."""
    codec = grace_from_params({
        "compressor": "topk", "compress_ratio": 0.01,
        "topk_algorithm": "chunk", "memory": "none",
        "communicator": "allgather"}).compressor
    memory = ResidualMemory()
    c, r = memory.compensate(g, r)
    payload, ctx, _ = codec.compress(c, None, jax.random.key(0))
    r = memory.update(c, payload, ctx, codec, r)
    return codec.decompress(payload, ctx), r


@pytest.mark.parametrize("shape, view_forced, sliced", [
    ((8, 2048, 1536), False, True),         # an LFM2 expert stack
    ((8, 2048, 1536), True, False),         # ... as the parent ran it
    ((3, 3, 512, 512), False, False),       # ResNet-50's largest leaf
    ((100 * 41_527 + 50,), False, False),   # view of 4,194,227 elements
    ((100 * 41_529 + 50,), True, False),    # 4,194,429, by the view
    ((100 * 41_529 + 50,), False, True),    # ... and as it runs now
    ((4_500_000,), False, True),            # rows * k == n: no padding lane
], ids=["lfm2-expert", "lfm2-expert-by-view", "resnet-largest",
        "under-the-constant", "over-by-view", "over", "no-padding"])
def test_topk_chunk_leaf_has_no_relayout_loop(one_chip, monkeypatch, shape,
                                              view_forced, sliced):
    """Past ``ops.sparse.RELAYOUT_LOOP_ELEMENTS`` XLA:TPU runs the flat <->
    (rows, k) reshape of a chunk top-k leaf as two ``while`` loops of its
    own making (``wide.body``: 4-row windows by dynamic-slice and
    dynamic-update-slice into the tiled view, 25 steps a direction for 101
    rows; 54 such loops a step in the LFM2 cell, 52 of its 727 ms, under no
    ``op_name``). On the row-slices route the same leaf compiles without
    them: what loops there is the route's own walk over equal row blocks
    (two ``while``, one a direction, a few steps each, under the route's
    scope), and the reshape inside a step is one operation. The ``by-view``
    cases hold the constant to the compiler that is installed: if they stop
    looping, the constant can go up."""
    if view_forced:
        monkeypatch.setattr(sparse, "RELAYOUT_LOOP_ELEMENTS", 1 << 62)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    # a function of its own each time: jit would answer a second trace of
    # the same function and shapes from its cache, whatever the constant
    text = compile_text(lambda g, r: _leaf_pipeline(g, r), x, x)
    loops = [line for line in text.splitlines() if " while(" in line]
    if view_forced:
        assert len(loops) == 2 and "%wide.body" in text
        assert not any("row_slices" in line for line in loops)
    else:
        assert "%wide.body" not in text
        assert len(loops) == (2 if sliced else 0)
        assert all("/row_slices/" in line for line in loops)
    assert ("grace/compress/row_slices" in text) == sliced
    assert ("grace/decompress/row_slices" in text) == sliced


# ---------------------------------------------------------------------------
# the fused attention of the LFM2 step (PR 31)
# ---------------------------------------------------------------------------

# a (heads, 1,024 queries, keys) float32 tensor: a block of scores in HBM
SCORE_BLOCK = re.compile(r"f32\[(?:1,)?(?:32|8,4),1024,(?:1024|2048|3072|4096)\]")
# the kernel's calls in a part whose forward output and log-sum-exp are
# kept for the backward pass, and in one recomputed whole
KEPT = ["splash_mha_dkv_no_residuals", "splash_mha_fwd_residuals"]
RECOMPUTED = KEPT + ["splash_mha_fwd_residuals"]
# under the block-diffusion mask, beside each of the fused kernel's calls the
# own blocks' kernel of that direction (PR 42): other names, so that the
# benchmark's readers, which find the fused kernel's calls by their names'
# beginning, count the fused kernel alone
OWN_BLOCK = ["block_diffusion_own_block_bwd", "block_diffusion_own_block_fwd"]


def _part_text(part, layer_shapes, cfg, one_chip, sequences=1,
               positions=4096):
    """One part of a decoder layer on ``sequences`` sequences (4,096 x
    hidden unless told, bfloat16), as the step runs it: one sequence after another,
    recomputed from its input but for what the fused kernel names, forward
    and gradient, compiled for the described chip."""
    def loss(p, x):
        y = lfm2._over_sequences(part, p, x, cfg.seq_block)
        return jnp.sum(y.astype(jnp.float32))

    p = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        layer_shapes)
    x = jax.ShapeDtypeStruct((sequences, positions, cfg.hidden_size),
                             jnp.bfloat16, sharding=one_chip)
    return compile_text(jax.value_and_grad(loss, argnums=(0, 1)), p, x)


def _kernel_calls(text):
    """The Pallas calls of a compiled text by kernel name, sorted, each
    with its ``op_name`` (read instruction by instruction: JAX prints a
    kernel's metadata with line breaks, so the ``op_name`` stands lines
    below the call's name)."""
    op_names = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text,
        re.S)
    assert len(op_names) == text.count('custom_call_target="tpu_custom_call"')
    return sorted(name.split("/")[-2] for name in op_names), op_names


def _as_on_the_chip(monkeypatch):
    """``engages`` answering for a TPU (its ``platform`` argument: this
    process's backend is the CPU)."""
    monkeypatch.setattr(
        pallas_attention, "engages",
        functools.partial(pallas_attention.engages, platform="tpu"))


def _attention_part_text(one_chip, sequences=1):
    """The attention operator of the benchmark's LFM2 configuration (32/8
    heads of 64)."""
    cfg = _lfm2_config()
    layer = cfg.layer_types.index("full_attention")
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim) == (32, 8, 64)
    return _part_text(lfm2._operator_part("full_attention", cfg),
                      _lfm2_shapes()["layers"][layer], cfg, one_chip,
                      sequences)


def test_lfm2_attention_compiles_to_the_fused_kernel(one_chip, monkeypatch):
    """With ``engages`` answering as on the chip, the part holds the kernel
    twice — forward and the fused backward — each under ``grace/attention``,
    and no block of float32 scores is left in the text. Two and not three
    (PR 33): ``_over_sequences`` keeps the output and the log-sum-exp the
    forward kernel names, which is all of the forward that the kernel's
    backward reads, so recomputing the part does not run the forward kernel
    again."""
    _as_on_the_chip(monkeypatch)
    text = _attention_part_text(one_chip)
    kernels, op_names = _kernel_calls(text)
    assert all("grace/attention" in name for name in op_names), op_names
    assert kernels == KEPT
    assert not SCORE_BLOCK.search(text)


def test_lfm2_attention_still_compiles_without_the_kernel(one_chip):
    """The fallback: where ``engages`` says no (here: a CPU process), the
    same part compiles for the chip from the plain spelling, score blocks
    and all."""
    text = _attention_part_text(one_chip)
    assert "tpu_custom_call" not in text
    assert SCORE_BLOCK.search(text)


# ---------------------------------------------------------------------------
# the same kernel at latent attention's head sizes, 192 | 128 (PR 32)
# ---------------------------------------------------------------------------

def _kanana_config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "kanana-2-30b-a3b-ep16.json")) as f:
        return kanana.model_config(json.load(f))


def _mla_part_text(one_chip, sequences=1):
    """Latent attention of the benchmark's kanana configuration (32 heads
    of 192 | 128)."""
    cfg = _kanana_config()
    shapes = jax.eval_shape(lambda k: deepseek_v3.init(k, cfg)[0],
                            jax.random.key(0))
    assert (cfg.num_attention_heads, cfg.qk_head_dim, cfg.v_head_dim,
            cfg.kv_lora_rank) == (32, 192, 128, 512)
    return _part_text(deepseek_v3._mla_part(cfg), shapes["layers"][1], cfg,
                      one_chip, sequences)


def test_latent_attention_compiles_to_the_fused_kernel(one_chip, monkeypatch):
    """Mosaic takes the kernel at 192 lanes of queries and keys and 128 of
    values, unpadded: the part holds it twice (forward and the fused
    backward: since PR 33 the backward reads the forward's kept output and
    log-sum-exp, where it ran the forward kernel a second time), each under
    ``grace/mla_latent/grace/attention``, with operands of the published
    head sizes, and no block of float32 scores is left in the text."""
    _as_on_the_chip(monkeypatch)
    text = _mla_part_text(one_chip)
    kernels, op_names = _kernel_calls(text)
    assert all("grace/mla_latent" in name and "grace/attention" in name
               and name.rfind("grace/attention")
               > name.rfind("grace/mla_latent") for name in op_names), op_names
    assert kernels == KEPT
    assert "bf16[32,4096,192]" in text and "bf16[32,4096,128]" in text
    assert "bf16[32,4096,256]" not in text           # no padding to 256
    assert not SCORE_BLOCK.search(text)


def test_latent_attention_still_compiles_without_the_kernel(one_chip):
    text = _mla_part_text(one_chip)
    assert "tpu_custom_call" not in text
    assert SCORE_BLOCK.search(text)


# ---------------------------------------------------------------------------
# the same kernel under the block-diffusion mask: a rectangle over the clean
# keys, a noised query's own block beside it (PR 42)
# ---------------------------------------------------------------------------

def _sdar_part_text(one_chip, sequences=1):
    """Attention of the benchmark's SDAR configuration (32 query heads over
    4 key/value heads of 128) over a doubled sequence of 8,192 positions
    in blocks of 4."""
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "sdar-30b-a3b-ep8.json")) as f:
        sizes = json.load(f)
    cfg = sdar_moe.model_config(sizes)
    length = sizes["seq_length"]
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            length, cfg.block_length) == (32, 4, 128, 4096, 4)
    shapes = jax.eval_shape(lambda k: sdar.init(k, cfg)[0], jax.random.key(0))
    part = sdar._attention_part(
        cfg, pallas_attention.BlockDiffusion(length, cfg.block_length),
        np.tile(np.arange(length), 2))
    return _part_text(part, shapes["layers"][0], cfg, one_chip, sequences,
                      positions=2 * length)


def test_block_diffusion_attention_compiles_to_one_kernel_a_kind(
        one_chip, monkeypatch):
    """Mosaic takes the rectangle, all 8,192 queries over the clean copy's
    4,096 keys and values, and the part holds the fused kernel once a kind:
    ``splash_mha_fwd_residuals`` and the fused backward
    ``splash_mha_dkv_no_residuals`` from the merged output and the joint
    log-sum-exp (the benchmark's readers find the calls by those names, one
    a layer and kind). Beside each, the own blocks' kernel of that direction
    under a name of its own, which Mosaic takes at tiles of 128 noised
    positions and eight query heads a step; all four under
    ``grace/attention``. No block of float32 scores and nothing of 8,192 x
    8,192 is in the text, and the fused backward's partial ``dq`` is a key
    tile's each: four, where the square had eight."""
    _as_on_the_chip(monkeypatch)
    text = _sdar_part_text(one_chip)
    kernels, op_names = _kernel_calls(text)
    assert kernels == OWN_BLOCK + KEPT
    assert all("grace/attention" in name for name in op_names), op_names
    assert "bf16[32,8192,128]" in text and "bf16[4,4096,128]" in text
    assert "bf16[4,32,8192,128]" in text and "bf16[8,32,8192,128]" not in text
    assert not re.search(r"f32\[(?:1,)?(?:32|4,8),1024,(?:4096|8192)\]", text)
    assert not re.search(r"\[(?:\d+,)*8192,8192\]", text)


def test_block_diffusion_attention_still_compiles_without_the_kernel(
        one_chip):
    """Where ``engages`` says no (here: a CPU process), the part compiles
    for the chip from the plain spelling under the whole mask."""
    text = _sdar_part_text(one_chip)
    assert "tpu_custom_call" not in text
    assert re.search(r"f32\[(?:1,)?(?:32|4,8),1024,8192\]", text)


def test_the_block_diffusion_part_keeps_the_merged_pair(one_chip,
                                                        monkeypatch):
    """Walked over two sequences the part keeps the merged output and the
    joint log-sum-exp of both, a row a sequence, and runs the kernel twice a
    sequence as the causal decoders' parts do."""
    _as_on_the_chip(monkeypatch)
    text = _sdar_part_text(one_chip, sequences=2)
    assert _kernel_calls(text)[0] == OWN_BLOCK + KEPT
    loops = [line for line in text.splitlines() if " while(" in line]
    assert sum("bf16[2,1,32,8192,128]" in line
               and "f32[2,1,32,8192]" in line for line in loops) == 2


# ---------------------------------------------------------------------------
# what the recomputed part keeps of the kernel, in both decoders (PR 33)
# ---------------------------------------------------------------------------

# the part's compiled text over so many sequences, and the value heads' size
DECODER_PARTS = {"lfm2": (_attention_part_text, 64),
                 "kanana": (_mla_part_text, 128)}


@pytest.mark.parametrize("decoder", sorted(DECODER_PARTS))
def test_the_kept_pair_is_two_stacked_buffers(one_chip, monkeypatch, decoder):
    """Walked over two sequences, the part keeps the kernel's output and
    log-sum-exp of both: one bfloat16 buffer of the heads-major output at
    the published sizes and one float32 number a query and head, a row a
    sequence, written by the forward loop and read by the backward loop;
    the kernel still runs twice a sequence, and one sequence alone (above)
    has no stack."""
    part_text, dv = DECODER_PARTS[decoder]
    _as_on_the_chip(monkeypatch)
    text = part_text(one_chip, sequences=2)
    assert _kernel_calls(text)[0] == KEPT
    loops = [line for line in text.splitlines() if " while(" in line]
    assert sum(f"bf16[2,1,32,4096,{dv}]" in line
               and "f32[2,1,32,4096]" in line for line in loops) == 2


@pytest.mark.parametrize("decoder", sorted(DECODER_PARTS))
def test_the_name_alone_changes_nothing(one_chip, monkeypatch, keep_nothing,
                                        decoder):
    """Under a ``jax.checkpoint`` that keeps nothing (``_over_sequences``
    until PR 33) the kernel, its residuals named as they now are, is in the
    part three times — forward, the recomputation, the fused backward — and
    no output is stacked: it is the policy that keeps the pair."""
    part_text, dv = DECODER_PARTS[decoder]
    _as_on_the_chip(monkeypatch)
    keep_nothing()
    text = part_text(one_chip, sequences=2)
    assert _kernel_calls(text)[0] == RECOMPUTED
    assert f"bf16[2,1,32,4096,{dv}]" not in text
    assert "f32[2,1,32,4096]" not in text


# ---------------------------------------------------------------------------
# attention is head-major from product to product (PR 45): the relayouts that
# stand under the attention stage of a compiled part
# ---------------------------------------------------------------------------

def _smallthinker_part_text(one_chip, layer):
    """Attention of layer ``layer`` of the benchmark's SmallThinker
    configuration (28 query heads over 4 key/value heads of 128, 16,384
    positions; layer 0 reads the whole prefix without positions, layer 1 a
    window of 4,096, rotated)."""
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "smallthinker-21b-a3b-ep8.json")) as f:
        sizes = json.load(f)
    cfg = smallthinker_moe.model_config(sizes)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            sizes["seq_length"]) == (28, 4, 128, 16384)
    assert (cfg.rope_layout[layer], cfg.sliding_window_layout[layer]) \
        == (layer, layer)
    shapes = jax.eval_shape(lambda k: smallthinker.init(k, cfg)[0],
                            jax.random.key(0))
    return _part_text(smallthinker._attention_part(cfg, layer),
                      shapes["layers"][layer], cfg, one_chip,
                      positions=sizes["seq_length"])


def _layout_changes(text):
    """``dtype[dims]`` of every layout-changing instruction a compiled part
    runs under an attention stage: a ``copy``, a ``transpose``, or a
    ``fusion`` whose root (through bitcasts) is one. Instructions inside
    fusions are the fusion's, not counted again."""
    bodies, name = {}, None
    for line in text.splitlines():
        header = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if header:
            name = header.group(1)
            bodies[name] = []
        elif line == "}":
            name = None
        elif name is not None:
            found = re.match(r"\s+(ROOT )?%([\w.\-]+) = (\w+\[[\d,]*\])\S* "
                             r"([\w\-]+)\(%?([\w.\-]*)", line)
            if found:
                bodies[name].append(found.groups() + (line,))

    def root(body):
        by_name = {i[1]: i for i in bodies[body]}
        at = next(i for i in bodies[body] if i[0])
        while at[3] == "bitcast" and at[4] in by_name:
            at = by_name[at[4]]
        return at[3]

    calls = {i[1]: re.search(r"calls=%([\w.\-]+)", i[5]).group(1)
             for body in bodies.values() for i in body if i[3] == "fusion"}
    found = []
    for body, instructions in bodies.items():
        if body in calls.values():
            continue
        for _, name, shape, opcode, _, line in instructions:
            if opcode == "fusion":
                opcode = root(calls[name])
            op_name = re.search(r'op_name="([^"]*)"', line)
            if opcode in ("copy", "transpose") and op_name and stage_of(
                    op_name.group(1)) in ATTENTION_STAGES:
                found.append(shape)
    return sorted(found)


ATTENTION_STAGES = ("grace/attention", "grace/window_attention",
                    "grace/mla_latent")


def _weight_gradient_operands(positions, q, kv, dv=None):
    """The relayouts a head-major part keeps: ``dq``, ``dk``, ``dv`` and the
    kept output, bfloat16, made ``T``-minor for the four weight-gradient
    products (``q``, ``kv``: heads and head size of the query and the key
    and value projections' outputs; ``dv``: the output's head size)."""
    (hq, dq), (hkv, dkv) = q, kv
    return sorted([f"bf16[{hq},{dq},1,{positions}]",
                   f"bf16[{hq},{dv or dq},{positions}]"]
                  + [f"bf16[{hkv},{dkv},1,{positions}]"] * (2 if hkv != hq
                                                            else 1))


# decoder: (the part's compiled text, positions of a sequence, the elements
# of a sequence's values: what counts as activation-sized, how many
# layout-changing instructions stand under the attention stage, which of
# them are activation-sized)
HEAD_MAJOR_PARTS = {
    "lfm2": (_attention_part_text, 4096, 4096 * 8 * 64, 4,
             _weight_gradient_operands(4096, (32, 64), (8, 64))),
    # one product makes keys and values, 128 + 128 wide, of all 32 heads
    "kanana": (_mla_part_text, 4096, 4096 * 32 * 128, 12,
               _weight_gradient_operands(4096, (32, 192), (32, 256), 128)),
    "sdar": (_sdar_part_text, 8192, 8192 * 4 * 128, 10,
             _weight_gradient_operands(8192, (32, 128), (4, 128))),
    "smallthinker-full": (
        functools.partial(_smallthinker_part_text, layer=0), 16384,
        16384 * 4 * 128, 8,
        _weight_gradient_operands(16384, (28, 128), (4, 128))),
    "smallthinker-window": (
        functools.partial(_smallthinker_part_text, layer=1), 16384,
        16384 * 4 * 128, 8,
        _weight_gradient_operands(16384, (28, 128), (4, 128))),
}


@pytest.mark.parametrize("decoder", sorted(HEAD_MAJOR_PARTS))
def test_the_relayouts_under_the_attention_stage(one_chip, monkeypatch,
                                                 decoder):
    """One attention part of each decoder at its cell's shape, forward,
    recomputation and backward, compiled for the described chip with
    ``engages`` answering as on it: the layout-changing instructions under
    the attention stage (a count made here, not a time), and which of them
    are activation-sized (the positions among their dimensions, and at
    least a sequence's values in elements).

    Attention is head-major from product to product (PR 45): no
    activation-sized relayout is left in the forward pass or the
    recomputation, none is float32, and the backward pass holds the four
    (three under latent attention, whose keys and values are one product)
    that XLA makes for the weight-gradient products, whose contraction over
    the positions stands between the heads and the head size of a
    head-major operand: ``dq``, ``dk``, ``dv`` and the kept output,
    ``T``-minor, bfloat16. The rest are weight-sized casts and the shared
    rotary key's.

    The parent's parts (PR 44, ``(n, T, H, D)`` between the projections and
    a wrapper that swapped axes; counted by this function on its compiled
    texts): LFM2 9 in all, 9 of them activation-sized (four float32, the
    head norms' backward; forward, recomputation and backward); kanana 13
    and 4; SDAR 15 and 9 (four float32); SmallThinker 11 and 5 in either
    kind of layer."""
    part_text, positions, values, count, activations = \
        HEAD_MAJOR_PARTS[decoder]
    _as_on_the_chip(monkeypatch)
    found = _layout_changes(part_text(one_chip))

    def activation_sized(shape):
        dims = [int(d) for d in re.findall(r"\d+", shape.split("[")[1])]
        return positions in dims and np.prod(dims) >= values

    assert [s for s in found if activation_sized(s)] == activations
    assert len(found) == count, found


# ---------------------------------------------------------------------------
# the expert part of both decoders: expert-aligned tiles (PR 37)
# ---------------------------------------------------------------------------

def _instructions(text, opcode):
    """``(result shape, op_name)`` of every ``opcode`` instruction of a
    compiled text, fused ones included."""
    return re.findall(
        r"= (\w+\[[\d,]*\])[^\n]*? %s\([^\n]*?op_name=\"([^\"]*)\""
        % re.escape(opcode), text)


@pytest.mark.parametrize("decoder", ["kanana", "lfm2"])
def test_the_expert_part_walks_tiles_of_one_expert(one_chip, decoder):
    """The expert layer of each benchmark configuration as the step runs it
    (recomputed from its input), forward and gradient, on one sequence of
    4,096 tokens at the tile the cell's 32,768 tokens choose. The walk's
    two loops run to a count the program computes (the tiles in use), the
    products are plain ones under ``grace/moe_experts`` (``lax.ragged_dot``,
    which XLA runs as its own kernel without the scope's name, is gone),
    and a tile's weight gradient is added into its one expert's float32
    slice: nowhere in the part is a whole ``(experts held, d, f)`` stack
    added. Since PR 39 nothing in the part addresses memory one index at a
    time at a size that grows with the tokens: no ``scatter``, no gather
    but of whole rows (a tile's, a chunk's), the gates out of the sort
    that orders the rows; a tile's results and its rows' ``dx`` are written
    where they lie in sorted order, into buffers nothing fills, and two
    more loops (the chunks in use) sum them by token."""
    make_cfg, mod = {"lfm2": (_lfm2_config, lfm2),
                     "kanana": (_kanana_config, deepseek_v3)}[decoder]
    cfg = make_cfg()
    tile = lfm2._tile_rows(cfg, 8 * 4096)
    assert tile == {"lfm2": 512, "kanana": 384}[decoder]
    cfg = dataclasses.replace(cfg, moe_row_block=tile)
    params, state = jax.eval_shape(lambda k: mod.init(k, cfg),
                                   jax.random.key(0))
    part = jax.checkpoint(mod._moe_part(cfg))

    def loss(p, x, s):
        y, s = part(p, s, x)
        return jnp.sum(y.astype(jnp.float32)), s

    def avals(tree):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    text = compile_text(
        jax.value_and_grad(loss, argnums=(0, 1), has_aux=True),
        avals(params["layers"][-1]),
        jax.ShapeDtypeStruct((1, 4096, cfg.hidden_size), jnp.bfloat16,
                             sharding=one_chip),
        avals(state["layers"][-1]))
    held, d, f = cfg.experts_held, cfg.hidden_size, cfg.moe_intermediate_size
    assert (held, d, f) == {"lfm2": (8, 2048, 1536),
                            "kanana": (8, 2048, 768)}[decoder]
    assert "ragged" not in text and "tpu_custom_call" not in text
    # the walk and the sum by token, forward and backward: four loops, none
    # with a trip count known in advance
    loops = [line for line in text.splitlines() if " while(" in line]
    assert len(loops) == 4 and not any("known_trip_count" in line
                                       for line in loops)
    # no row and no gate moves one index at a time
    n, k = 4096, cfg.num_experts_per_tok
    window = lfm2._window_tokens(cfg, 8 * n, tile)
    assert window == 512                  # in both cells; a chunk is a tile
    assert " scatter(" not in text
    gathers = _instructions(text, "gather")
    assert [shape for shape, _ in gathers] == [f"bf16[{tile},{d}]"] * 5
    # the gates ride the sort: one of three operands forward, the same
    # recomputed, and their gradient's way back
    sorts = [line.split(" sort(")[0] for line in text.splitlines()
             if " sort(" in line]
    carried = sorted(len(re.findall(r"\w+\[%d\]" % (n * k), x))
                     for x in sorts if f"f32[{n * k}]" in x)
    assert carried == [2, 3, 3]
    assert not re.search(r"= \w+\[%d\]\S* gather\(" % (n * k), text)
    # every product of the part under its stage: the experts' in the loops,
    # the router's and (kanana) the shared expert's outside them, and the
    # sum by token's one a chunk (ones and zeros times the chunk's rows)
    products = _instructions(text, "convolution")
    stages = [stage_of(name) for _, name in products]
    assert set(stages) == {"grace/moe_experts", "grace/moe_router",
                           "grace/moe_combine", "grace/moe_dispatch"} | (
        {"grace/shared_expert"} if decoder == "kanana" else set())
    assert sorted((shape, stage) for (shape, _), stage in zip(products, stages)
                  if stage in ("grace/moe_combine", "grace/moe_dispatch")) == [
        (f"f32[{window},{d}]", "grace/moe_combine"),
        (f"f32[{window},{d}]", "grace/moe_dispatch")]
    in_tiles = [shape for (shape, name), stage in zip(products, stages)
                if stage == "grace/moe_experts"]
    assert len(in_tiles) >= 11 and all("/while/body/" in name for _, name in
                                       products if "moe_experts" in name)
    # a tile's weight gradients: float32 products of one expert's shape ...
    slices = [f"f32[{d},{f}]", f"f32[{f},{d}]"]
    assert sorted(x for x in in_tiles if x.startswith("f32")) == sorted(
        [slices[0]] * 2 + [slices[1]])
    # ... each added to that expert's slice of the stack, in place
    adds = [shape for shape, _ in _instructions(text, "add")]
    assert adds.count(f"f32[1,{d},{f}]") == 2 and adds.count(
        f"f32[1,{f},{d}]") == 1
    assert not any(x in (f"f32[{held},{d},{f}]", f"f32[{held},{f},{d}]")
                   for x in adds)
    updates = [shape for shape, _ in
               _instructions(text, "dynamic-update-slice")]
    assert updates.count(f"f32[{held},{d},{f}]") == 2 and updates.count(
        f"f32[{held},{f},{d}]") == 1
    # the sorted-order buffers (every slot and a tile of padding) and the
    # windows' sums: written in place, contiguously, and never filled
    assert updates.count(f"bf16[{n * k + tile},{d}]") == 2
    assert updates.count(f"bf16[{n // window},{window},{d}]") == 2
    made = re.findall(r"= (\w+\[[\d,]*\])[^\n]*? custom-call\(\)[^\n]*"
                      r"custom_call_target=\"AllocateBuffer\"", text)
    assert sorted(made) == sorted([f"bf16[{n * k + tile},{d}]"] * 2
                                  + [f"bf16[{n // window},{window},{d}]"] * 2)
    assert not any(shape in (f"f32[{n},{d}]", f"bf16[{n * k + tile},{d}]")
                   and (stage_of(name) or "").startswith("grace/moe")
                   for shape, name in _instructions(text, "broadcast"))


def _lfm2_config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "lfm2-24b-a2b-ep8.json")) as f:
        return lfm2_moe.model_config(json.load(f))


def _lfm2_shapes():
    cfg = _lfm2_config()
    return jax.eval_shape(lambda k: lfm2.init(k, cfg)[0], jax.random.key(0))


def _resnet50_shapes():
    return jax.eval_shape(lambda k: resnet.init(k, depth=50)[0],
                          jax.random.key(0))


_TOPK_CHUNK = {"compressor": "topk", "compress_ratio": 0.01,
               "topk_algorithm": "chunk", "memory": "residual",
               "communicator": "allgather"}


@pytest.mark.parametrize("model, params, leaves, engaged", [
    ("lfm2", _TOPK_CHUNK, 50, 27),
    ("resnet50", _TOPK_CHUNK, 161, 0),
    ("lfm2", dict(_TOPK_CHUNK, topk_algorithm="exact"), 50, 0),
    ("lfm2", dict(_TOPK_CHUNK, topk_algorithm="approx"), 50, 0),
    ("lfm2", {"compressor": "powersgd", "compress_rank": 4,
              "memory": "powersgd", "communicator": "allgather"}, 50, 0),
    ("lfm2", {"compressor": "none", "memory": "none",
              "communicator": "allreduce"}, 50, 0),
], ids=["lfm2-chunk", "resnet50-chunk", "lfm2-exact", "lfm2-approx",
        "lfm2-powersgd", "lfm2-dense"])
def test_row_slices_engagement_count_in_lowered_step(model, params, leaves,
                                                     engaged):
    """How many leaves take the row-slices route is a static count: the
    calls under ``grace/compress/row_slices`` in the lowered one-device
    train step, one a leaf, and two a leaf under
    ``grace/decompress/row_slices`` (the memory update's decode and the
    exchange's, which XLA merges). The leaves of the benchmark's LFM2
    configuration whose view passes the constant (27 of 50: all of
    4,194,304 elements or more), none of ResNet-50's 161, none for another
    algorithm or codec. Lowered from shapes with a loss that touches every
    leaf: nothing here runs or needs the described chip."""
    import optax
    from grace_tpu.train import init_train_state, make_train_step

    shapes = {"lfm2": _lfm2_shapes, "resnet50": _resnet50_shapes}[model]()
    counts = [int(np.prod(s.shape)) for s in
              jax.tree_util.tree_leaves(shapes)]
    assert len(counts) == leaves
    if params is _TOPK_CHUNK:
        by_size = sum(sparse.takes_row_slices(-(-n // static_k(n, 0.01)),
                                              static_k(n, 0.01))
                      for n in counts)
        assert by_size == engaged
        assert by_size == sum(n >= 4_194_304 for n in counts)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))

    def loss_fn(p, batch):
        return sum(jnp.sum(leaf) for leaf in
                   jax.tree_util.tree_leaves(p)) * jnp.mean(batch)

    tx = optax.chain(grace_from_params(params).transform(seed=0),
                     optax.sgd(0.1))
    state = jax.eval_shape(lambda p: init_train_state(p, tx, mesh), shapes)
    batch = jax.ShapeDtypeStruct((2, 3), jnp.float32)
    step = make_train_step(loss_fn, tx, mesh, donate=False)
    jax.eval_shape(step, state, batch)
    fn = next(iter(step.jit_cache.values()))
    text = fn.lower(state, batch).as_text(debug_info=True)
    assert scope_engagements(
        text, "grace/compress/row_slices/jit(row_blocks_first_max)"
    ) == engaged
    assert scope_engagements(
        text, "grace/decompress/row_slices/jit(row_blocks_dense)"
    ) == 2 * engaged


# ---------------------------------------------------------------------------
# whole steps of the benchmark's decoder cells, as `harness.Program` builds
# them, compiled for the described chip (PR 41)
# ---------------------------------------------------------------------------

def _whole_step(cell_name, topo, kernels_on, monkeypatch):
    """The cell's step built as ``benchmarks/harness.Program`` builds it
    (the cell's transform and optimizer, ``make_stateful_train_step``), on
    abstract state placed on the described chip, compiled: ``(text, bytes
    the step holds as ``Program.hbm_program_bytes`` counts them)``."""
    import optax
    from grace_tpu.train import (init_stateful_train_state,
                                 make_stateful_train_step)
    from grace_tpu.transform import partition_specs

    catalog = harness.Catalog()
    cell = catalog.cell(cell_name)
    config = catalog.config(cell["config"])
    builder = catalog.builder(config)
    _as_on_the_chip(monkeypatch)
    key = jax.random.key(0)
    here = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    tx = optax.chain(grace_from_params(dict(cell["grace"])).transform(seed=0),
                     plain_train.optimizer(cell["optimizer"]))
    params, mstate = jax.eval_shape(lambda k: builder.init(k, config), key)
    state = jax.eval_shape(
        lambda p, m: init_stateful_train_state(p, m, tx, here), params, mstate)
    state = jax.tree_util.tree_map(
        lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
        state, partition_specs(state, "data"))
    batch = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, P("data"))),
        jax.eval_shape(lambda k: builder.make_batch(
            k, config["per_chip_batch"], config), key))
    step = make_stateful_train_step(builder.program_loss(config), tx, mesh)
    jax.eval_shape(step, state, batch)
    compiled = next(iter(step.jit_cache.values())).lower(state,
                                                         batch).compile()
    m = compiled.memory_analysis()
    return compiled.as_text(), (
        m.argument_size_in_bytes + m.output_size_in_bytes
        - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_the_sdar_step_compiles_for_the_described_chip(topo, kernels_on,
                                                       monkeypatch):
    """The whole step of ``sdar-30b-a3b-blockdiff-topk1pct-w1`` from the
    CPU: Mosaic takes the kernel at heads of 128 | 128 under the
    block-diffusion mask (all 8,192 queries over the clean copy's 4,096
    keys, PR 42), twice a layer (forward and the fused backward, the kept
    merged output read in place of a second forward), the own blocks'
    kernel of that direction beside each call,
    each under ``grace/attention``; no block of float32 scores is left; the
    noise is drawn inside the step under its stage; and the step leaves room
    on the chip for the harness's copy of the start parameters (1.83 GB)
    under the runtime's 16.91 GB."""
    text, held = _whole_step("sdar-30b-a3b-blockdiff-topk1pct-w1", topo,
                             kernels_on, monkeypatch)
    kernels, op_names = _kernel_calls(text)
    assert kernels == sorted(4 * (OWN_BLOCK + KEPT))
    assert all("grace/attention" in name for name in op_names), op_names
    # the kernel's queries are all 8,192 positions, its keys and values
    # the clean copy's 4,096 (PR 42)
    assert "bf16[32,8192,128]" in text and "bf16[4,4096,128]" in text
    assert not re.search(r"f32\[(?:1,)?(?:32|4,8),1024,8192\]", text)
    assert "grace/diffusion_noise" in text
    assert held + 456_346_624 * 4 < 16.91e9
    assert held > 0.75 * 16e9              # three quarters of the chip


def test_the_smallthinker_step_compiles_for_the_described_chip(
        topo, kernels_on, monkeypatch):
    """The whole step of ``smallthinker-21b-a3b-swa16k-topk1pct-w1`` from
    the CPU: Mosaic takes the kernel at heads of 128 | 128 over sequences of
    16,384 positions in all four layers, forward and the fused backward
    (eight calls), the full layer's under ``grace/attention`` and the three
    windowed layers' under ``grace/window_attention`` (a computed mask: no
    mask tensor); no block of float32 scores and nothing of 16,384 x 16,384
    is in the text; the router stands under its stage; and the step leaves
    room on the chip for the harness's copy of the start parameters (1.48
    GB) under the runtime's 16.91 GB, and holds over a quarter of the
    chip."""
    text, held = _whole_step("smallthinker-21b-a3b-swa16k-topk1pct-w1", topo,
                             kernels_on, monkeypatch)
    kernels, op_names = _kernel_calls(text)
    assert kernels == 4 * KEPT[:1] + 4 * KEPT[1:]
    windowed = [n for n in op_names if "grace/window_attention" in n]
    full = [n for n in op_names
            if "grace/attention" in n and "grace/window_attention" not in n]
    assert (len(windowed), len(full)) == (6, 2), op_names
    assert "bf16[28,16384,128]" in text and "bf16[4,16384,128]" in text
    assert not re.search(r"\[(?:\d+,)*16384,16384\]", text)
    assert not re.search(r"f32\[(?:1,)?(?:28|4,7),1024,\d{4,5}\]", text)
    assert "grace/moe_router" in text
    assert "grace/diffusion_noise" not in text
    print("smallthinker step holds", held)
    assert held + 370_547_200 * 4 < 16.91e9
    assert held > 0.25 * 16e9


# ---------------------------------------------------------------------------
# the two operators of the Qwen3-Next cell (PR 49), a part each
# ---------------------------------------------------------------------------

def _qwen3_next_part_text(one_chip, layer):
    """The operator of layer ``layer`` of the benchmark's Qwen3-Next
    configuration on one sequence of 16,384 positions: layer 0 a gated
    delta layer (16 key and 32 value heads of 128), layer 3 full attention
    (16 query heads over 2 key/value heads of 256, an output gate)."""
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "qwen3-next-80b-a3b-ep32.json")) as f:
        sizes = json.load(f)
    cfg = qwen3_next_moe.model_config(sizes)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.rotary_dim, sizes["seq_length"]) == (16, 2, 256, 64, 16384)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.delta_chunk) == (16, 32, 128, 64)
    assert cfg.layer_types[layer] == ("full_attention" if layer == 3
                                      else "linear_attention")
    shapes = jax.eval_shape(lambda k: qwen3_next.init(k, cfg)[0],
                            jax.random.key(0))
    return _part_text(qwen3_next._operator_part(cfg.layer_types[layer], cfg),
                      shapes["layers"][layer], cfg, one_chip,
                      positions=sizes["seq_length"])


def test_gated_attention_compiles_to_the_fused_kernel_at_heads_of_256(
        one_chip, monkeypatch):
    """Mosaic takes the kernel at heads of 256 | 256 in the accepted tiles
    (1,024 queries, 1,024 keys copied, 512 multiplied at once), forward and
    the fused backward, each under ``grace/attention``; the query projection
    is 8,192 wide (queries and gate), the kernel reads 16 heads of 256 and
    the gate multiplies what it wrote; no block of float32 scores is left."""
    _as_on_the_chip(monkeypatch)
    text = _qwen3_next_part_text(one_chip, 3)
    kernels, op_names = _kernel_calls(text)
    assert kernels == KEPT
    assert all("grace/attention" in name for name in op_names), op_names
    assert "bf16[16,16384,256]" in text and "bf16[2,16384,256]" in text
    assert "f32[2048,8192]" in text                     # d W_q, gate and all
    assert not re.search(r"f32\[(?:1,)?(?:16|2,8),1024,\d{4,5}\]", text)
    assert not re.search(r"\[(?:\d+,)*16384,16384\]", text)


def test_gated_attention_still_compiles_without_the_kernel(one_chip):
    """The fallback (here: a CPU process asks ``engages``): blocks of 1,024
    queries scored by XLA, no Pallas call."""
    text = _qwen3_next_part_text(one_chip, 3)
    assert 'custom_call_target="tpu_custom_call"' not in text
    assert re.search(r"f32\[(?:1,)?2,8,1024,\d{4,5}\]", text)


def test_the_gated_delta_part_compiles_for_the_described_chip(one_chip):
    """One gated delta layer's operator on a sequence of 16,384 positions,
    forward, recomputation and backward: no Pallas call (plain XLA today),
    the rule's operations under ``grace/delta_rule`` and the operator's
    under ``grace/gated_delta``, the state carried in float32 (16 key heads
    x 2 value heads each x 128 x 128), chunks of 64 (a chunk's float32
    triangular system is 64 x 64), loops and not unrolled chunks (a span of
    2,048 positions is 32 chunks, a sequence 8 spans), and nothing of
    16,384 x 16,384 or of a state a token."""
    text = _qwen3_next_part_text(one_chip, 0)
    assert 'custom_call_target="tpu_custom_call"' not in text
    stages = {stage_of(name) for name in re.findall(r'op_name="([^"]*)"',
                                                    text)}
    assert {"grace/gated_delta", "grace/delta_rule"} <= stages
    assert "grace/attention" not in stages
    assert re.search(r"f32\[1,16,2,128,128\]", text)          # the state
    assert re.search(r"f32\[1,16,2,32,64,64\]", text)         # a span's T
    assert not re.search(r"\[(?:\d+,)*16384,16384\]", text)
    assert not re.search(r"\[(?:\d+,)*16384,(?:\d+,)*128,128\]", text)
    # the chunk scans and the span scans, forward, recomputed and backward:
    # loops, a handful, whatever the length
    loops = len(re.findall(r"\bwhile\(", text))
    assert 4 <= loops <= 16, loops


# Marked slow (outside tier-1, as the two causal cells' below): the whole
# step takes 90 s to compile alone, beside the SDAR and SmallThinker steps
# this file's worker already carries inside tier-1's time limit; the two
# parts above are the cell's tier-1 compiles.
@pytest.mark.slow
def test_the_qwen3_next_step_compiles_for_the_described_chip(
        topo, kernels_on, monkeypatch):
    """The whole step of ``qwen3-next-80b-a3b-gdn16k-topk1pct-w1`` from the
    CPU: the fused kernel twice (the one full layer's forward and fused
    backward at heads of 256 | 256 over 16,384 positions, under
    ``grace/attention``), the three gated delta layers in plain XLA under
    their two stages, the shared expert and the router under theirs; no
    block of float32 scores, nothing of 16,384 x 16,384, no state a token;
    and the step leaves room on the chip for the harness's copy of the
    start parameters (1.70 GB) under the runtime's 16.91 GB, and holds over
    three quarters of the chip (13,916,858,880 B when this was written)."""
    text, held = _whole_step("qwen3-next-80b-a3b-gdn16k-topk1pct-w1", topo,
                             kernels_on, monkeypatch)
    kernels, op_names = _kernel_calls(text)
    assert kernels == KEPT
    assert all("grace/attention" in name for name in op_names), op_names
    assert "bf16[16,16384,256]" in text and "bf16[2,16384,256]" in text
    for stage in ("grace/gated_delta", "grace/delta_rule",
                  "grace/shared_expert", "grace/moe_router"):
        assert stage in text, stage
    assert "grace/window_attention" not in text
    assert not re.search(r"\[(?:\d+,)*16384,16384\]", text)
    assert not re.search(r"\[(?:\d+,)*16384,(?:\d+,)*128,128\]", text)
    assert not re.search(r"f32\[(?:1,)?(?:16|2,8),1024,\d{4,5}\]", text)
    print("qwen3-next step holds", held)
    assert held + 424_340_544 * 4 < 16.91e9
    assert held > 0.75 * 16e9


# (kernel calls, bytes the compiled step holds) of the two causal decoder
# cells, compiled here for the described chip by the same helper. The bytes
# are PR 45's: head-major attention took 1,354,752 bytes from the LFM2 step
# and added 163,537,408 to kanana's, whose live bytes at the peak rose by
# five 8 MB weight prefetches and the heap's packing by the rest (PERF.md
# section 6, PR 45). PR 44's were 12,865,564,672 and 14,268,435,968 (the
# head that forms its gradient in the walk that makes the logits), PR 39's
# to PR 43's 12,865,857,024 and 14,399,759,872.
CAUSAL_STEPS = {
    "lfm2-24b-a2b-topk1pct-w1": (1, 12_864_209_920),
    "kanana-2-30b-a3b-topk1pct-w1": (5, 14_431_973_376)}


# Marked slow (outside tier-1): the two whole steps take 135 s and 90 s to
# compile alone and, with the SDAR step, 444 s beside three busy workers,
# which this one file's worker cannot spend inside tier-1's time limit.
@pytest.mark.slow
@pytest.mark.parametrize("cell", sorted(CAUSAL_STEPS))
def test_the_causal_cells_steps_are_what_they_were(topo, kernels_on,
                                                   monkeypatch, cell):
    layers, held = CAUSAL_STEPS[cell]
    text, got = _whole_step(cell, topo, kernels_on, monkeypatch)
    kernels, op_names = _kernel_calls(text)
    assert kernels == layers * KEPT[:1] + layers * KEPT[1:]
    assert all("grace/attention" in name for name in op_names)
    assert got == held
    assert "grace/diffusion_noise" not in text
