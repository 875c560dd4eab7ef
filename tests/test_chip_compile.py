"""The served path's kernels, compiled by the chip's own compiler.

Every case lowers and compiles for a DESCRIBED ``v5e:2x2`` (the four-chip
host; no chip attached, nothing runs) in this test's own process — what
interpret mode cannot show: tile alignment, VMEM budgets, kernels that
cannot be partitioned. Shapes are the smoke's (``chip_smoke.py``):
Qwen3-1.7B on one chip and Qwen3-8B's TP=4 shard, 8 slots, block 16,
chunk 64, a 2,048-block pool.

The topology is described inside a module-scoped fixture, never at import,
in a ``skipif`` or in ``parametrize`` arguments: only one process may load
the TPU library, xdist workers all import this file, and only the worker
that RUNS it may load it. Keep every such test in this one file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from triton_distributed_tpu.runtime.compat import shard_map

BLOCK, SLOTS, CHUNK, MAX_LEN = 16, 8, 64, 4096
MAX_BLOCKS = MAX_LEN // BLOCK
N_BLOCKS = SLOTS * MAX_BLOCKS
DH = 128
# (Hq, Hkv) per device: Qwen3-1.7B whole, Qwen3-8B's TP=4 shard,
# granite-4.0-h-micro's PACKED rows (two key heads of 64 to a row of 128:
# 4 key rows, 8 query heads each) and Nemotron-3-Nano's two key heads under
# sixteen query heads each. The decode shape of each takes the folded tile
# arithmetic (16, 8, 32 and 32 query rows in one operand).
HEADS = {"qwen3-1.7b": (16, 8), "qwen3-8b-tp4": (8, 2),
         "granite-packed": (32, 4), "nemotron-2kv": (32, 2)}
# Qwen3-8B: d_model, fused qkv width, d_ff.
D8, QKV8, FF8 = 4096, (32 + 2 * 8) * DH, 12_288


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp4(topo):
    return Mesh(np.array(topo.devices).reshape(4), ("tp",))


@pytest.fixture(autouse=True)
def _compile_cache_off():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prior)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


@pytest.mark.parametrize("L", [1, CHUNK], ids=["decode", "chunk"])
@pytest.mark.parametrize("model", sorted(HEADS))
def test_paged_attention_compiles(one_chip, model, L):
    from triton_distributed_tpu.kernels.paged_attention import (
        paged_attention,
    )

    hq, hkv = HEADS[model]

    def fn(q, pool, tables, kv_lens, q_lens):
        return paged_attention(q, pool, tables, kv_lens, q_lens=q_lens,
                               interpret=False)

    # a block's K plane and V plane side by side: ONE copy a block (at
    # qwen3-8b-tp4's two key heads a chip, 16 KB where a plane is 8)
    pool = _sds((N_BLOCKS, 2, BLOCK, hkv, DH), jnp.bfloat16, one_chip)
    compiled = jax.jit(fn).lower(
        _sds((SLOTS, L, hq, DH), jnp.bfloat16, one_chip), pool,
        _sds((SLOTS, MAX_BLOCKS), jnp.int32, one_chip),
        _sds((SLOTS,), jnp.int32, one_chip),
        _sds((SLOTS,), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    # the name a device trace knows the K+V build by; the latent build's
    # is its own (a reader's pattern must be able to tell them apart)
    assert "tpu_custom_call" in text and "paged_attention" in text
    assert "latent_paged_attention" not in text


# K-EXAONE-236B-A23B's cell (perfbench/configs/k-exaone-236b-a23b-ep8.json):
# 32 slots, 64 query heads on 8 key heads, contexts up to 24,576 (a block
# table 1,536 wide: 196 KB of scalar prefetch where the other cells' is
# 32 KB), one full layer over a 28,672-block pool, four window layers (128)
# over a ring of 36 blocks a slot (the window and a step's 7 rows of 64).
EXA_SLOTS, EXA_BLOCKS, EXA_TABLE, EXA_RING, EXA_HEADS = \
    32, 28_672, 24_576 // BLOCK, 36, (64, 8)
# SmallThinker-21BA3B's cell (perfbench/configs/smallthinker-21ba3b-l8.json):
# 28 query heads on 4 key heads (a group of 7, no power of two: the decode
# shape folds 28 rows, a chunk tile is 64 x 7), two full layers over a
# 20,480-block pool at the model's whole context of 16,384 (a table 1,024
# wide), six window layers (4,096) over a ring of 284 blocks a slot.
LONG = {
    "k-exaone": dict(heads=EXA_HEADS, window=128, window_layers=4,
                     ring=EXA_RING, blocks=EXA_BLOCKS, table=EXA_TABLE),
    "smallthinker": dict(heads=(28, 4), window=4096, window_layers=6,
                         ring=284, blocks=20_480, table=16_384 // BLOCK),
}


@pytest.mark.parametrize("L", [1, CHUNK], ids=["decode", "chunk"])
@pytest.mark.parametrize("build", ["window", "full-wide-table"])
@pytest.mark.parametrize("model", sorted(LONG))
def test_paged_attention_compiles_at_long_contexts(one_chip, model, build, L):
    """The two builds a model with window layers runs, at the published
    heads (the decode shape folds 64 or 28 query rows into one operand): the
    window build over ring storage, known by its own name, and the K+V
    build with the wide table in SMEM."""
    from triton_distributed_tpu.kernels.paged_attention import (
        paged_attention,
    )

    geo = LONG[model]
    hq, hkv = geo["heads"]
    rows = EXA_SLOTS if L == 1 else 7
    window = geo["window"] if build == "window" else None

    def fn(q, pool, tables, kv_lens, q_lens, layer):
        return paged_attention(q, pool, tables, kv_lens, q_lens=q_lens,
                               interpret=False, layer=layer, window=window)

    if window:
        pool = _sds((geo["window_layers"], EXA_SLOTS, 2, geo["ring"], BLOCK,
                     hkv, DH), jnp.bfloat16, one_chip)
        tables = _sds((rows, 1), jnp.int32, one_chip)
    else:
        pool = _sds((1, geo["blocks"], 2, BLOCK, hkv, DH), jnp.bfloat16,
                    one_chip)
        tables = _sds((rows, geo["table"]), jnp.int32, one_chip)
    text = jax.jit(fn).lower(
        _sds((rows, L, hq, DH), jnp.bfloat16, one_chip), pool, tables,
        _sds((rows,), jnp.int32, one_chip),
        _sds((rows,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text
    assert ("window_paged_attention" in text) == bool(window)
    assert "paged_attention" in text


def _tp4_compile(tp4, fn, in_specs, out_specs, *shapes):
    sm = shard_map(fn, mesh=tp4, in_specs=in_specs, out_specs=out_specs,
                   check_vma=False)
    args = [_sds(s, jnp.bfloat16, NamedSharding(tp4, spec))
            for s, spec in zip(shapes, in_specs)]
    return jax.jit(sm).lower(*args).compile().as_text()


# Rows per device: a decode step at 8 slots over TP=4 (2 — below the
# sublane tile, padded inside the kernel wrappers), a dense chunk
# (8 slots x chunk 64 / 4) and the served mixed step's two blocks (8 slots
# + a prefill block of 7 rows x chunk 64, / 4 = 114: not a sublane multiple).
ROWS = dict(argvalues=[SLOTS // 4, SLOTS * CHUNK // 4,
                       (SLOTS + 7 * CHUNK) // 4],
            ids=["decode-rows", "chunk-rows", "mixed-rows"])


@pytest.mark.parametrize("rows", **ROWS)
@pytest.mark.parametrize("proj", ["qkv", "gate_up"])
def test_ag_gemm_compiles_tp4(tp4, proj, rows):
    from triton_distributed_tpu.kernels.allgather_gemm import (
        AGGEMMConfig,
        ag_gemm_device,
    )

    n = {"qkv": QKV8, "gate_up": 2 * FF8}[proj]
    text = _tp4_compile(
        tp4,
        lambda a, b: ag_gemm_device(a, b, axis="tp",
                                    config=AGGEMMConfig(block_n=256),
                                    interpret=False),
        (P("tp", None), P(None, "tp")), P(None, "tp"),
        (4 * rows, D8), (D8, n))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", **ROWS)
@pytest.mark.parametrize("proj", ["o", "down"])
def test_gemm_rs_compiles_tp4(tp4, proj, rows):
    from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
        GEMMRSConfig,
        gemm_rs_device,
    )

    k = {"o": 32 * DH, "down": FF8}[proj]
    text = _tp4_compile(
        tp4,
        lambda a, b: gemm_rs_device(a, b, axis="tp",
                                    config=GEMMRSConfig(block_n=256),
                                    interpret=False),
        (P(None, "tp"), P("tp", None)), P("tp", None),
        (4 * rows, k), (k, D8))
    assert "tpu_custom_call" in text


# Qwen3-8B's four projections a chip, as the layer scan's kernels see them:
# the 36-layer stack whole and a traced layer index.
LAYERS8 = 36
STACKED8 = {"qkv": (D8, QKV8), "gate_up": (D8, 2 * FF8),
            "o": (32 * DH, D8), "down": (FF8, D8)}


def _staged_weights(text, shapes):
    """The fusions of a compiled program that slice a layer's matrix of one
    of ``shapes`` out of its stack AHEAD of the kernel that multiplies it
    (``%dynamic-slice_bitcast_fusion.N = bf16[K,N]{...S(1)}``: a serial
    pass over the layer's weights into on-chip memory; PERF.md section 6,
    PR 47)."""
    want = "|".join(f"{k},{n}" for k, n in shapes)
    return re.findall(
        rf"^\s*(%\S*dynamic[-_]slice\S* = bf16\[(?:{want})\]\S*)", text,
        flags=re.M)


@pytest.mark.parametrize("block_n,fetch", [(128, "resident"),
                                           (256, "resident"),
                                           (128, "pipeline")])
@pytest.mark.parametrize("rows", **ROWS)
@pytest.mark.parametrize("proj", sorted(STACKED8))
def test_stacked_overlap_gemms_compile_tp4(tp4, monkeypatch, proj, rows,
                                           block_n, fetch):
    """``ag_gemm_device`` (QKV, gate-up: with its tail) and ``gemm_rs_device``
    (output, down) over the 36-layer stack at a layer index a ``lax.scan``
    traces: Mosaic takes the stack whole, and nothing slices a layer's
    matrix out of it first. Every one of these shapes keeps its weight
    tiles RESIDENT (the down projection's 25.2 MB a chip the largest);
    ``pipeline`` is the BlockSpec fetch a weight too large to hold takes."""
    from triton_distributed_tpu.kernels import common
    from triton_distributed_tpu.kernels.allgather_gemm import (
        AGGEMMConfig,
        ag_gemm_device,
    )
    from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
        GEMMRSConfig,
        gemm_rs_device,
    )

    if fetch == "pipeline":
        monkeypatch.setattr(common, "RESIDENT_WEIGHT_VMEM_CAP", 0)
    k, n = STACKED8[proj]
    if proj in ("qkv", "gate_up"):
        def kernel(a, b, li):
            return ag_gemm_device(a, b, axis="tp", layer=li, interpret=False,
                                  config=AGGEMMConfig(block_n=block_n))
        in_specs, out_spec = (P("tp", None), P(None, None, "tp")), \
            P(None, "tp")
    else:
        def kernel(a, b, li):
            return gemm_rs_device(a, b, axis="tp", layer=li, interpret=False,
                                  config=GEMMRSConfig(block_n=block_n))
        in_specs, out_spec = (P(None, "tp"), P(None, "tp", None)), \
            P("tp", None)

    def every_layer(a, b):
        first = kernel(a, b, jnp.int32(0))
        return jax.lax.scan(lambda acc, li: (acc + kernel(a, b, li), None),
                            first, jnp.arange(1, LAYERS8, dtype=jnp.int32))[0]

    text = _tp4_compile(tp4, every_layer, in_specs, out_spec,
                        (4 * rows, k), (LAYERS8, k, n))
    local = (k, n // 4) if proj in ("qkv", "gate_up") else (k // 4, n)
    # the kernel's weight operand is this chip's share of the whole stack
    assert "bf16[%d,%d,%d]{2,1,0}}" % (LAYERS8, *local) in text
    assert not _staged_weights(text, [local])


# The four-chip cell's GEMM-RS calls (``TP4_*`` below: 32 slots, ``block_n``
# 128), rows a device: a decode step's 32 rows (8, padded to the sublane
# tile), the mixed step's 480 (120, padded to 128) and 512; then a prefill
# of 2,048 rows, where the down projection's A, own block and arrived
# partials are past the VMEM a kernel may ask for (42 MB of 36; the output
# projection's, a third as deep, are 31).
CELL_ROWS = dict(
    argvalues=[(8, "one pass"), (120, "one pass"), (128, "one pass"),
               (512, "one pass if it fits")],
    ids=["decode-32", "mixed-480", "mixed-512", "prefill-2048"])


@pytest.mark.parametrize("weight", ["matrix", "stacked"])
@pytest.mark.parametrize("rows,walk", **CELL_ROWS)
@pytest.mark.parametrize("proj", ["o", "down"])
def test_gemm_rs_walk_compiles_at_the_cells_rows_tp4(tp4, proj, rows, walk,
                                                     weight):
    """``gemm_rs_device`` chooses its walk from the shapes it is given: at
    the cell's decode and mixed rows both projections take the ONE pass
    over the column tiles (A whole, the own block in float32, the weight's
    tiles through a ring: the down projection's mixed call 14 of the 36
    MB), the down projection at a prefill of 2,048 rows keeps the grid
    ``(destination, column tile)``; the comm ledger's ``method`` says
    which, and Mosaic takes either."""
    from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
        GEMMRSConfig,
        gemm_rs_device,
    )
    from triton_distributed_tpu.obs import comm_ledger

    k = {"o": 32 * DH, "down": FF8}[proj]
    cfg = GEMMRSConfig(block_n=128)
    if weight == "matrix":
        def fn(a, b):
            return gemm_rs_device(a, b, axis="tp", config=cfg,
                                  interpret=False)
        b_shape, b_spec = (k, D8), P("tp", None)
    else:
        def fn(a, b):
            def kernel(li):
                return gemm_rs_device(a, b, axis="tp", layer=li, config=cfg,
                                      interpret=False)
            return jax.lax.scan(lambda acc, li: (acc + kernel(li), None),
                                kernel(jnp.int32(0)),
                                jnp.arange(1, LAYERS8, dtype=jnp.int32))[0]
        b_shape, b_spec = (LAYERS8, k, D8), P(None, "tp", None)
    with comm_ledger.gathering() as records:
        text = _tp4_compile(tp4, fn, (P(None, "tp"), b_spec), P("tp", None),
                            (4 * rows, k), b_shape)
    fits = walk == "one pass" or proj == "o"
    assert {r.method for r in records} == {
        "device_one_pass" if fits else "device"}
    assert "tpu_custom_call" in text and "gemm_rs" in text


def test_oneshot_allreduce_compiles_tp4(tp4):
    from triton_distributed_tpu.kernels.allreduce import oneshot_all_reduce

    # mode="ar" decode: every device holds the full (8, d_model) partial.
    text = _tp4_compile(
        tp4,
        lambda x: oneshot_all_reduce(x[0], axis="tp", interpret=False)[None],
        (P("tp", None, None),), P("tp", None, None), (4, SLOTS, D8))
    assert "tpu_custom_call" in text


# The four-chip cell (perfbench/configs/qwen3-8b-tp4.json): Qwen3-8B whole
# over TP=4, 32 slots, a 3,328-block pool, the AG-GEMM tile its VMEM allows.
TP4_SLOTS, TP4_BLOCKS, TP4_BLOCK_N, TP4_PREFILL_ROWS = 32, 3328, 128, 7


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_the_four_chip_cells_step_compiles_with_its_kernels_named(tp4, kind):
    """The served step of ``qwen3-8b-tp4.reasoning`` as ``BatchEngine``
    builds it around ``forward_paged``, all 36 layers at the published
    widths: it compiles for the four chips, the pool's arenas are aliased
    in to out, a chip's arguments are its 5.96 GB of weights (the layers'
    quarter, the table and the head whole) and 1.96 GB of pool, and the
    compiled text knows the fused kernels by name and stages no layer's
    weights ahead of them."""
    import dataclasses

    from triton_distributed_tpu.models.config import ModelConfig
    from triton_distributed_tpu.models.engine import Engine
    from triton_distributed_tpu.models.qwen import Qwen3
    from triton_distributed_tpu.serving.kv_pool import (
        paged_state_shapes,
        paged_state_specs,
    )

    cfg = dataclasses.replace(ModelConfig.from_name("qwen3-8b"),
                              max_length=MAX_LEN)

    def placed(tree, specs):
        return jax.tree.map(
            lambda a, spec: _sds(a.shape, a.dtype, NamedSharding(tp4, spec)),
            tree, specs)

    def everywhere(shape, dtype):
        return _sds(shape, dtype, NamedSharding(tp4, P()))

    model = Qwen3(cfg, block_n=TP4_BLOCK_N)
    params = placed(jax.eval_shape(lambda k: model.init(k, tp4),
                                   jax.random.PRNGKey(0)),
                    model.param_specs())
    specs = paged_state_specs(cfg)
    state = placed(paged_state_shapes(cfg, n_blocks=TP4_BLOCKS,
                                      block_size=BLOCK, n_slots=TP4_SLOTS),
                   specs)
    engine = Engine(cfg, mesh=tp4, params=params, mode="dist",
                    block_n=TP4_BLOCK_N, interpret=False)
    step = jax.jit(engine._make_sm("dist", paged=kind, paged_attn="fused",
                                   state_specs=specs), donate_argnums=(2,))
    slots = (everywhere((TP4_SLOTS,), jnp.int32),
             everywhere((TP4_SLOTS, MAX_BLOCKS), jnp.int32),
             everywhere((TP4_SLOTS,), bool))
    if kind == "decode":
        args = (everywhere((TP4_SLOTS, 1), jnp.int32), state, *slots)
    else:
        ids = (everywhere((TP4_SLOTS,), jnp.int32),
               everywhere((TP4_PREFILL_ROWS, CHUNK), jnp.int32),
               everywhere((TP4_PREFILL_ROWS, 3), jnp.int32))
        args = (ids, state, *slots, everywhere((TP4_SLOTS,), jnp.int32))
    compiled = step.lower(params, *args).compile()
    text = compiled.as_text()
    for name in ("ag_gemm", "ag_gemm_tail", "gemm_rs", "paged_attention"):
        assert f"%{name}." in text or f"%{name} =" in text, name
    # the four projections' stacks reach those kernels whole: no layer's
    # matrix is sliced out (and staged) ahead of them
    assert not _staged_weights(
        text, [(k, n // 4) if proj in ("qkv", "gate_up") else (k // 4, n)
               for proj, (k, n) in STACKED8.items()])
    assert "all-gather" in text
    # a chip's pool is ONE arena, its two key heads' K plane and V plane of
    # a block side by side (16 KB a copy): the block walk and the append
    # take that operand and no arena of single planes is left
    assert f"bf16[36,{TP4_BLOCKS},2,{BLOCK},2,{DH}]" in text
    assert f"bf16[36,{TP4_BLOCKS},{BLOCK},2,{DH}]" not in text
    mem = compiled.memory_analysis()
    assert 1.95e9 < mem.alias_size_in_bytes < 1.97e9
    assert 7.9e9 < mem.argument_size_in_bytes < 7.95e9
    assert mem.temp_size_in_bytes < 100e6, mem.temp_size_in_bytes


# JoyAI-LLM-Flash's cell (perfbench/configs/joyai-llm-flash-ep16.json): 32
# slots, a 3,328-block pool of 40 stacked layers, latent rows of 576 padded
# to 640, 32 query heads on the one shared key head.
LAT_SLOTS, LAT_BLOCKS, LAT_LAYERS, LAT_ROW, LAT_V, LAT_HEADS = \
    32, 3328, 40, 640, 512, 32


@pytest.mark.parametrize("L", [1, CHUNK], ids=["decode", "chunk"])
def test_latent_paged_attention_compiles(one_chip, L):
    from triton_distributed_tpu.kernels.paged_attention import (
        paged_attention,
    )

    def fn(q, arena, tables, kv_lens, q_lens, layer):
        return paged_attention(q, arena, tables, kv_lens, q_lens=q_lens,
                               interpret=False, layer=layer, v_dim=LAT_V,
                               scale=192 ** -0.5)

    compiled = jax.jit(fn).lower(
        _sds((LAT_SLOTS, L, LAT_HEADS, LAT_ROW), jnp.bfloat16, one_chip),
        _sds((LAT_LAYERS, LAT_BLOCKS, BLOCK, LAT_ROW), jnp.bfloat16, one_chip),
        _sds((LAT_SLOTS, MAX_BLOCKS), jnp.int32, one_chip),
        _sds((LAT_SLOTS,), jnp.int32, one_chip),
        _sds((LAT_SLOTS,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "latent_paged_attention" in text


@pytest.mark.parametrize("tiles,rows", [(32, 16), (144, 128)],
                         ids=["decode-tiles", "chunk-tiles"])
@pytest.mark.parametrize("d,f", [(2048, 1536), (768, 2048), (2688, 1920),
                                 (1920, 2688)],
                         ids=["gate_up", "down", "relu2-up", "relu2-down"])
def test_grouped_product_over_expert_tiles_compiles(one_chip, tiles, rows, d,
                                                    f):
    """The routed experts' product at the cells' sizes: tiles of rows sorted
    by expert against 16 held experts of 39 stacked layers (JoyAI's gated
    pair; Nemotron-3-Nano's ungated pair at its stored width of 1,920, whose
    f-tile is 384: 512 divides neither 1,920 nor 2,688, and without a tile
    the wrapper would fall back to an einsum over gathered weights).
    ``interpret=False`` hands Mosaic the kernel from a CPU process
    (``platform.plain_off_tpu``)."""
    from triton_distributed_tpu.kernels import moe_utils

    def fn(x, w, live, group_of, layer):
        return moe_utils.grouped_gemm_skip(
            x, w, live, layer_idx=layer, interpret=False, group_of=group_of,
            name="moe_grouped_gemm")

    compiled = jax.jit(fn).lower(
        _sds((tiles, rows, d), jnp.bfloat16, one_chip),
        _sds((39, 16, d, f), jnp.bfloat16, one_chip),
        _sds((tiles,), jnp.int32, one_chip),
        _sds((tiles,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "moe_grouped_gemm" in text


# granite-4.0-h-micro's cell (perfbench/configs/granite-4.0-h-micro.json):
# 32 slots, 36 layers of per-slot state (64 heads x 64 x 128 float32), a
# 3,328-block pool of 4 stacked attention layers whose 8 key heads of 64
# lie two to a row of 128, a prefill block of 7 rows of 64.
HYB_SLOTS, HYB_BLOCKS, HYB_PREFILL_ROWS = 32, 3328, 7


@pytest.mark.parametrize("layers,groups", [(36, 1), (23, 8)],
                         ids=["granite-1-group", "nemotron-8-groups"])
def test_ssm_state_update_compiles_in_place(one_chip, layers, groups):
    """At 8 groups of 8 heads a block of 32 heads spans four groups; both
    geometries run a grid of 32 slots x 2 head tiles."""
    from triton_distributed_tpu.kernels.ssm_update import ssm_state_update

    f32 = jnp.float32
    arena = _sds((layers, HYB_SLOTS, 64, 64, 128), f32, one_chip)
    compiled = jax.jit(
        lambda ar, ly, a, u, b, c: ssm_state_update(ar, ly, a, u, b, c,
                                                    interpret=False),
        donate_argnums=0).lower(
        arena, _sds((), jnp.int32, one_chip),
        _sds((HYB_SLOTS, 64), f32, one_chip),
        _sds((HYB_SLOTS, 64, 64), f32, one_chip),
        _sds((HYB_SLOTS, groups, 128), f32, one_chip),
        _sds((HYB_SLOTS, groups, 128), f32, one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ssm_state_update" in text
    mem = compiled.memory_analysis()
    # the arena is the result: no second one, and nothing beside it
    assert mem.alias_size_in_bytes == layers * HYB_SLOTS * 64 * 64 * 128 * 4
    assert mem.temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_hybrid_step_compiles_with_its_state_in_place(topo, kind):
    """The whole served step of the published configuration (all 40 layers,
    every width), as ``BatchEngine`` builds it around ``forward_paged``:
    it compiles, every arena of the pool's state (rows and per-slot) is
    aliased in to out, and the step's temporaries hold no copy of one (the
    smallest arena is 30 MB, the recurrence's 2.4 GB)."""
    from triton_distributed_tpu.models.config import GraniteHybridConfig
    from triton_distributed_tpu.models.engine import Engine
    from triton_distributed_tpu.models.granite_hybrid import GraniteHybrid
    from triton_distributed_tpu.serving.kv_pool import (
        paged_state_shapes,
        paged_state_specs,
    )

    cfg = GraniteHybridConfig()
    mesh = Mesh(np.array(topo.devices[:1]), ("tp",))
    here = NamedSharding(mesh, P())

    def placed(tree):
        return jax.tree.map(
            lambda a: _sds(a.shape, a.dtype, here), tree)

    params = placed(jax.eval_shape(
        lambda k: GraniteHybrid(cfg).init(k, mesh), jax.random.PRNGKey(0)))
    state = placed(paged_state_shapes(
        cfg, n_blocks=HYB_BLOCKS, block_size=BLOCK, n_slots=HYB_SLOTS))
    state_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in jax.tree.leaves(state))
    assert 2.8e9 < state_bytes < 2.95e9
    engine = Engine(cfg, mesh=mesh, params=params, mode="dist",
                    interpret=False)
    step = jax.jit(
        engine._make_sm("dist", paged=kind, paged_attn="fused",
                        state_specs=paged_state_specs(cfg)),
        donate_argnums=(2,))
    slots = (_sds((HYB_SLOTS,), jnp.int32, here),
             _sds((HYB_SLOTS, MAX_BLOCKS), jnp.int32, here),
             _sds((HYB_SLOTS,), bool, here))
    if kind == "decode":
        args = (_sds((HYB_SLOTS, 1), jnp.int32, here), state, *slots)
    else:
        ids = (_sds((HYB_SLOTS,), jnp.int32, here),
               _sds((HYB_PREFILL_ROWS, CHUNK), jnp.int32, here),
               _sds((HYB_PREFILL_ROWS, 3), jnp.int32, here))
        args = (ids, state, *slots, _sds((HYB_SLOTS,), jnp.int32, here))
    compiled = step.lower(params, *args).compile()
    text = compiled.as_text()
    # nine state updates and one block walk a period of ten layers
    assert text.count("tpu_custom_call") >= 10 and "ssm_state_update" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == state_bytes
    assert mem.temp_size_in_bytes < 100e6, mem.temp_size_in_bytes


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_nemotron_step_compiles_with_its_state_in_place(topo, kind):
    """The whole served step of nemotron-3-nano-30b-a3b-ep8 (all 52 layers,
    every width, 16 of 128 experts held), as ``BatchEngine`` builds it
    around ``forward_paged``: it compiles, every arena of the pool's state
    (rows 6 layers deep, per-slot 23 deep) is aliased in to out, and the
    step's temporaries hold no copy of an arena or of a weight stack (the
    smallest stack of matrices is the attention layers' 0.28 GB, the
    experts' two are 3.8 GB each)."""
    from triton_distributed_tpu.models.config import NemotronHConfig
    from triton_distributed_tpu.models.engine import Engine
    from triton_distributed_tpu.models.nemotron_h import NemotronH
    from triton_distributed_tpu.serving.kv_pool import (
        paged_state_shapes,
        paged_state_specs,
    )

    cfg = NemotronHConfig(experts_held=16)
    mesh = Mesh(np.array(topo.devices[:1]), ("tp",))
    here = NamedSharding(mesh, P())

    def placed(tree):
        return jax.tree.map(
            lambda a: _sds(a.shape, a.dtype, here), tree)

    params = placed(jax.eval_shape(
        lambda k: NemotronH(cfg).init(k, mesh), jax.random.PRNGKey(0)))
    state = placed(paged_state_shapes(
        cfg, n_blocks=HYB_BLOCKS, block_size=BLOCK, n_slots=HYB_SLOTS))
    state_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in jax.tree.leaves(state))
    assert 1.85e9 < state_bytes < 1.95e9
    engine = Engine(cfg, mesh=mesh, params=params, mode="dist",
                    interpret=False)
    step = jax.jit(
        engine._make_sm("dist", paged=kind, paged_attn="fused",
                        state_specs=paged_state_specs(cfg)),
        donate_argnums=(2,))
    slots = (_sds((HYB_SLOTS,), jnp.int32, here),
             _sds((HYB_SLOTS, MAX_BLOCKS), jnp.int32, here),
             _sds((HYB_SLOTS,), bool, here))
    if kind == "decode":
        args = (_sds((HYB_SLOTS, 1), jnp.int32, here), state, *slots)
    else:
        ids = (_sds((HYB_SLOTS,), jnp.int32, here),
               _sds((HYB_PREFILL_ROWS, CHUNK), jnp.int32, here),
               _sds((HYB_PREFILL_ROWS, 3), jnp.int32, here))
        args = (ids, state, *slots, _sds((HYB_SLOTS,), jnp.int32, here))
    compiled = step.lower(params, *args).compile()
    text = compiled.as_text()
    # 14 layer bodies (MEMEM*E, ME, M*E, ME): 6 state updates, two grouped
    # products in each of 6 expert layers, 2 block walks
    assert text.count("tpu_custom_call") >= 20
    assert "ssm_state_update" in text and "moe_grouped_gemm" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == state_bytes
    assert mem.temp_size_in_bytes < 200e6, mem.temp_size_in_bytes


# (configuration file, its family, the pool's state in GB, the weights in GB)
WINDOWED = {
    "k-exaone": ("k-exaone-236b-a23b-ep8", "exaone_moe", (2.15, 2.2), 7.43),
    "smallthinker": ("smallthinker-21ba3b-l8", "smallthinker", (3.1, 3.15),
                     7.93),
}


@pytest.mark.parametrize("kind", ["decode", "prefill"])
@pytest.mark.parametrize("model", sorted(WINDOWED))
def test_exaone_step_compiles_with_its_state_in_place(topo, model, kind):
    """The whole served step of the two configurations the EXAONE walk
    serves, as ``BatchEngine`` builds it around ``forward_paged``:
    k-exaone-236b-a23b-ep8 (layers 0-4, every width, 16 of 128 experts held,
    an eighth of the vocabulary; a 1,536-wide block table) and
    smallthinker-21ba3b-l8 (layers 0-7 at the published widths: a ring of
    284 blocks a slot behind a window of 4,096, 28 query heads on 4 key
    heads, all 64 experts held, the whole vocabulary and context; the
    weights it reports beside the 7.93 GB reckoned). Each compiles, every
    arena of the pool's state (the full layers' rows, the window layers'
    rings) is aliased in to out, and the step's temporaries hold no copy of
    an arena or of a weight stack (the smallest stack of matrices is
    0.3 GB; a chunk's expert buffers and activations stay under 0.2 GB)."""
    import importlib
    import json

    from triton_distributed_tpu.models.engine import Engine
    from triton_distributed_tpu.models.exaone_moe import ExaoneMoe
    from triton_distributed_tpu.serving.kv_pool import (
        paged_state_shapes,
        paged_state_specs,
    )

    name, family, state_gb, weights_gb = WINDOWED[model]
    family = importlib.import_module(f"perfbench.families.{family}")
    geo = LONG[model]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, f"perfbench/configs/{name}.json")) as f:
        file = json.load(f)
    cfg = family.program_config(file, family.sizes(file))
    fleet = file["serve"]["fleet"]
    assert (fleet["n_slots"], fleet["n_blocks"], fleet["block_size"]) == \
        (EXA_SLOTS, geo["blocks"], BLOCK)
    assert cfg.max_length // BLOCK == geo["table"]
    mesh = Mesh(np.array(topo.devices[:1]), ("tp",))
    here = NamedSharding(mesh, P())

    def placed(tree):
        return jax.tree.map(
            lambda a: _sds(a.shape, a.dtype, here), tree)

    params = placed(jax.eval_shape(
        lambda k: ExaoneMoe(cfg).init(k, mesh), jax.random.PRNGKey(0)))
    state = placed(paged_state_shapes(
        cfg, n_blocks=geo["blocks"], block_size=BLOCK, n_slots=EXA_SLOTS,
        max_take=HYB_PREFILL_ROWS * CHUNK))
    assert state.wkv.shape == (geo["window_layers"], EXA_SLOTS, 2,
                               geo["ring"], BLOCK, geo["heads"][1], DH)

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree))

    state_bytes = nbytes(state)
    assert state_gb[0] * 1e9 < state_bytes < state_gb[1] * 1e9
    assert nbytes(params) == pytest.approx(weights_gb * 1e9, rel=2e-3)
    engine = Engine(cfg, mesh=mesh, params=params, mode="dist",
                    interpret=False)
    step = jax.jit(
        engine._make_sm("dist", paged=kind, paged_attn="fused",
                        state_specs=paged_state_specs(cfg)),
        donate_argnums=(2,))
    slots = (_sds((EXA_SLOTS,), jnp.int32, here),
             _sds((EXA_SLOTS, geo["table"]), jnp.int32, here),
             _sds((EXA_SLOTS,), bool, here))
    if kind == "decode":
        args = (_sds((EXA_SLOTS, 1), jnp.int32, here), state, *slots)
    else:
        ids = (_sds((EXA_SLOTS,), jnp.int32, here),
               _sds((HYB_PREFILL_ROWS, CHUNK), jnp.int32, here),
               _sds((HYB_PREFILL_ROWS, 3), jnp.int32, here))
        args = (ids, state, *slots, _sds((EXA_SLOTS,), jnp.int32, here))
    compiled = step.lower(params, *args).compile()
    text = compiled.as_text()
    # the layer bodies: a walk a layer, two grouped products in each expert
    # layer (each walk twice in the mixed step: the decode block and the
    # prefill block)
    assert text.count("tpu_custom_call") >= 10
    assert "window_paged_attention" in text and "moe_grouped_gemm" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == state_bytes
    assert mem.temp_size_in_bytes < 200e6, mem.temp_size_in_bytes
    # weights, the pool and the step's own buffers fit the chip's 16 GB
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 15.75e9


def test_short_conv_update_compiles_in_place(one_chip):
    """LFM2-24B-A2B's cell: 30 layers of windows (two inputs of 2,048 a
    slot, bfloat16), 32 slots in two grid steps of 16 rows."""
    from triton_distributed_tpu.kernels.short_conv_update import (
        short_conv_update,
    )

    d, layers = 2048, 30
    bf16 = jnp.bfloat16
    compiled = jax.jit(
        lambda ar, ly, bcx, w, live, fresh: short_conv_update(
            ar, ly, bcx, w, live, fresh, interpret=False),
        donate_argnums=0).lower(
        _sds((layers, HYB_SLOTS, 2 * d), bf16, one_chip),
        _sds((), jnp.int32, one_chip),
        _sds((HYB_SLOTS, 3 * d), bf16, one_chip),
        _sds((3, d), bf16, one_chip),
        _sds((HYB_SLOTS,), bool, one_chip),
        _sds((HYB_SLOTS,), bool, one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "short_conv_update" in text
    mem = compiled.memory_analysis()
    # the arena is the result: no second one, and nothing beside it
    assert mem.alias_size_in_bytes == layers * HYB_SLOTS * 2 * d * 2
    assert mem.temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_lfm2_step_compiles_with_its_windows_in_place(topo, kind):
    """The whole served step of lfm2-24b-a2b-ep8 (all 40 layers, every
    width, 8 of 64 experts held, the whole tied vocabulary), as
    ``BatchEngine`` builds it around ``forward_paged``: it compiles with the
    update's kernel and the grouped product in it, every arena of the pool's
    state (ten layers of packed rows, thirty of windows and NO recurrence's)
    is aliased in to out, and the step's temporaries hold no copy of an
    arena or of a weight stack (the smallest stack of matrices is the
    attention layers' 0.2 GB)."""
    import json

    from perfbench.families import lfm2_moe as family
    from triton_distributed_tpu.models.engine import Engine
    from triton_distributed_tpu.models.exaone_moe import ExaoneMoe
    from triton_distributed_tpu.serving.kv_pool import (
        paged_state_shapes,
        paged_state_specs,
    )

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "perfbench/configs/lfm2-24b-a2b-ep8.json")) as f:
        file = json.load(f)
    cfg = family.program_config(file, family.sizes(file))
    fleet = file["serve"]["fleet"]
    assert (fleet["n_slots"], fleet["n_blocks"], fleet["block_size"],
            fleet["prefill_chunk"]) == (HYB_SLOTS, HYB_BLOCKS, BLOCK, CHUNK)
    mesh = Mesh(np.array(topo.devices[:1]), ("tp",))
    here = NamedSharding(mesh, P())

    def placed(tree):
        return jax.tree.map(
            lambda a: _sds(a.shape, a.dtype, here), tree)

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree))

    params = placed(jax.eval_shape(
        lambda k: ExaoneMoe(cfg).init(k, mesh), jax.random.PRNGKey(0)))
    state = placed(paged_state_shapes(
        cfg, n_blocks=HYB_BLOCKS, block_size=BLOCK, n_slots=HYB_SLOTS))
    assert state.ssm is None and state.conv.shape == (30, HYB_SLOTS, 4096)
    state_bytes = nbytes(state)
    assert 1.09e9 < state_bytes < 1.11e9
    assert nbytes(params) == pytest.approx(7.52e9, rel=2e-3)
    engine = Engine(cfg, mesh=mesh, params=params, mode="dist",
                    interpret=False)
    step = jax.jit(
        engine._make_sm("dist", paged=kind, paged_attn="fused",
                        state_specs=paged_state_specs(cfg)),
        donate_argnums=(2,))
    slots = (_sds((HYB_SLOTS,), jnp.int32, here),
             _sds((HYB_SLOTS, MAX_BLOCKS), jnp.int32, here),
             _sds((HYB_SLOTS,), bool, here))
    if kind == "decode":
        args = (_sds((HYB_SLOTS, 1), jnp.int32, here), state, *slots)
    else:
        ids = (_sds((HYB_SLOTS,), jnp.int32, here),
               _sds((HYB_PREFILL_ROWS, CHUNK), jnp.int32, here),
               _sds((HYB_PREFILL_ROWS, 3), jnp.int32, here))
        args = (ids, state, *slots, _sds((HYB_SLOTS,), jnp.int32, here))
    compiled = step.lower(params, *args).compile()
    text = compiled.as_text()
    # seven layer bodies: five window updates, two block walks, two grouped
    # products in each of five expert layers
    assert text.count("tpu_custom_call") >= 17
    assert "short_conv_update" in text and "moe_grouped_gemm" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == state_bytes
    assert mem.temp_size_in_bytes < 200e6, mem.temp_size_in_bytes
    # weights, the pool and the step's own buffers fit the chip's 16 GB
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 15.75e9


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_evabyte_step_compiles_with_ring_and_summaries_in_place(topo, kind):
    """The whole served step of evabyte-6.5b-l8 (layers 0-7 at the published
    widths: 32 key heads of 128, a SwiGLU of 11,008, the vocabulary of 320
    under eight heads; 24 slots, a ring of 156 blocks a slot a layer behind
    the window of 2,048 and a take of 448, 1,280 blocks of summary rows a
    layer behind a 128-wide block table), as ``BatchEngine`` builds it
    around ``forward_paged``. It compiles with the two EVA builds of the
    block walk named, every arena of the pool's state (the rings AND the
    rows, in EVERY layer) is aliased in to out, the step's temporaries hold
    no copy of a ring, of an arena or of a layer's slice of a weight stack
    (a layer of one ring is 0.49 GB, the smallest matrix 33 MB; the decode /
    mixed step hold 2 / 19 MB), and the arguments are the
    13.8 GB the configuration's file states."""
    import json

    from perfbench.families import evabyte as family
    from triton_distributed_tpu.models.engine import Engine
    from triton_distributed_tpu.models.evabyte import EvaByte
    from triton_distributed_tpu.serving.kv_pool import (
        blocks_needed,
        paged_state_shapes,
        paged_state_specs,
    )

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "perfbench/configs/evabyte-6.5b-l8.json")) as f:
        file = json.load(f)
    cfg = family.program_config(file, family.sizes(file))
    fleet = file["serve"]["fleet"]
    slots, rows = fleet["n_slots"], HYB_PREFILL_ROWS
    assert (slots, fleet["block_size"], fleet["prefill_chunk"]) == \
        (24, BLOCK, CHUNK)
    table = blocks_needed(cfg.max_length, BLOCK, cfg.kv_row_tokens)
    assert table == 128
    mesh = Mesh(np.array(topo.devices[:1]), ("tp",))
    here = NamedSharding(mesh, P())

    def placed(tree):
        return jax.tree.map(
            lambda a: _sds(a.shape, a.dtype, here), tree)

    params = placed(jax.eval_shape(
        lambda k: EvaByte(cfg).init(k, mesh), jax.random.PRNGKey(0)))
    state = placed(paged_state_shapes(
        cfg, n_blocks=fleet["n_blocks"], block_size=BLOCK, n_slots=slots,
        max_take=rows * CHUNK))
    assert state.wkv.shape == (8, slots, 2, 156, BLOCK, 32, DH)
    assert state.kv.shape == (8, fleet["n_blocks"], 2, BLOCK, 32, DH)

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree))

    state_bytes = nbytes(state)
    assert nbytes(params) == pytest.approx(3.262e9, rel=1e-3)
    assert 13.7e9 < state_bytes + nbytes(params) < 15.2e9
    engine = Engine(cfg, mesh=mesh, params=params, mode="dist",
                    interpret=False)
    step = jax.jit(
        engine._make_sm("dist", paged=kind, paged_attn="fused",
                        state_specs=paged_state_specs(cfg)),
        donate_argnums=(2,))
    ops = (_sds((slots,), jnp.int32, here),
           _sds((slots, table), jnp.int32, here),
           _sds((slots,), bool, here))
    if kind == "decode":
        args = (_sds((slots, 1), jnp.int32, here), state, *ops)
    else:
        ids = (_sds((slots,), jnp.int32, here),
               _sds((rows, CHUNK), jnp.int32, here),
               _sds((rows, 3), jnp.int32, here))
        args = (ids, state, *ops, _sds((slots,), jnp.int32, here))
    compiled = step.lower(params, *args).compile()
    text = compiled.as_text()
    # ONE layer body: two walks (each twice in the mixed step: the decode
    # block and the prefill block)
    assert text.count("tpu_custom_call") == (2 if kind == "decode" else 4)
    assert "eva_attn_window" in text and "eva_attn_summary" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == state_bytes
    assert mem.temp_size_in_bytes < 30e6, mem.temp_size_in_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 15.75e9
