"""The distributed kernels' names and a compiled step's count of what its
collectives move.

1. Every ``pallas_call`` of ``kernels/`` carries a ``name=`` (an unnamed
   kernel is an anonymous ``custom-call`` in a device trace), and each
   named kernel's name is in the lowered text of a jitted call on a mesh of
   four host devices (the TPU interpreter lowers a call under the named
   scope ``<name>/pallas_call``).
2. ``BatchEngine.stats_snapshot()["collectives"]``: zero and empty on a
   mesh of one, the comm ledger's traced bytes on a mesh of four, for the
   decode and for the mixed program; the steps are TRACED here, never run
   (a ``mode="dist"`` step under the interpreter is 20 s, its trace one).
3. A name changes no equation of a one-chip program but the call's own
   ``name`` parameter.
"""

import ast
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu.kernels.allgather_gemm import AGGEMMConfig
from triton_distributed_tpu.kernels.gemm_reduce_scatter import GEMMRSConfig
from triton_distributed_tpu.models import Engine, ModelConfig
from triton_distributed_tpu.obs import comm_ledger
from triton_distributed_tpu.runtime import perf_model as pm
from triton_distributed_tpu.runtime.compat import shard_map
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving import BatchEngine

KERNELS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "triton_distributed_tpu", "kernels")
KERNEL_FILES = sorted(f for f in os.listdir(KERNELS) if f.endswith(".py"))


@pytest.mark.parametrize("file", KERNEL_FILES)
def test_every_pallas_call_is_named(file):
    with open(os.path.join(KERNELS, file)) as f:
        tree = ast.parse(f.read())
    calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call)
             and isinstance(n.func, ast.Attribute)
             and n.func.attr in ("pallas_call", "make_pallas_call")]
    unnamed = [n.lineno for n in calls
               if "name" not in {k.arg for k in n.keywords}]
    assert not unnamed, f"kernels/{file}: pallas_call without name= at " \
                        f"lines {unnamed}"


@pytest.fixture(scope="module")
def tp4():
    return make_mesh({"tp": 4}, devices=jax.devices()[:4], set_default=False)


def _f32(*shape):
    return jnp.ones(shape, jnp.float32)


def _over_tp(fn, in_specs, out_specs):
    return lambda mesh: shard_map(fn, mesh=mesh, in_specs=in_specs,
                                  out_specs=out_specs, check_vma=False)


def _ag_gemm(mesh):
    from triton_distributed_tpu.kernels.allgather_gemm import ag_gemm_device
    return _over_tp(
        lambda a, b: ag_gemm_device(a, b, axis="tp",
                                    config=AGGEMMConfig(block_n=8)),
        (P("tp", None), P(None, "tp")), P(None, "tp"))(mesh), \
        (_f32(8, 16), _f32(16, 32))


def _gemm_rs(mesh):
    from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
        gemm_rs_device)
    return _over_tp(
        lambda a, b: gemm_rs_device(a, b, axis="tp",
                                    config=GEMMRSConfig(block_n=8)),
        (P(None, "tp"), P("tp", None)), P("tp", None))(mesh), \
        (_f32(8, 64), _f32(64, 16))


def _collective(module, name):
    """A per-device collective of ``kernels/<module>.py`` over each
    device's ``(rows, 128)`` slab."""
    def build(mesh):
        import importlib
        fn = getattr(importlib.import_module(
            "triton_distributed_tpu.kernels." + module), name)
        return _over_tp(lambda x: fn(x[0], axis="tp")[None],
                        (P("tp", None, None),), P("tp", None, None))(mesh), \
            (_f32(4, 8, 128),)
    return build


def _ll_allgather(mesh):
    from triton_distributed_tpu.kernels.ll_allgather import (
        ll_all_gather_device)

    def f(x, staging, epoch):
        out, staging = ll_all_gather_device(x[0], staging[0], epoch,
                                            axis="tp")
        return out[None], staging[None]
    return _over_tp(f, (P("tp", None, None), P("tp", None, None, None, None),
                        P()),
                    (P("tp", None, None), P("tp", None, None, None, None))
                    )(mesh), \
        (_f32(4, 8, 128), _f32(4, 2, 3, 8, 128), jnp.zeros((), jnp.int32))


def _local(module, name, *args, **kw):
    """A kernel of ``kernels/<module>.py`` that runs on one device."""
    def build(mesh):
        import importlib
        fn = getattr(importlib.import_module(
            "triton_distributed_tpu.kernels." + module), name)
        return (lambda *a: fn(*a, interpret=True, **kw)), args
    build.one_device = True
    return build


NAMED = {
    "ag_gemm": _ag_gemm,
    "gemm_rs": _gemm_rs,
    "allreduce_one_shot": _collective("allreduce", "oneshot_all_reduce"),
    "allreduce_two_shot": _collective("allreduce", "twoshot_all_reduce"),
    "allgather_ring": _collective("allgather", "ring_all_gather"),
    "allgather_push": _collective("allgather", "a2a_all_gather"),
    "reduce_scatter_one_shot": _collective("reduce_scatter",
                                           "oneshot_reduce_scatter"),
    "reduce_scatter_ring": _collective("reduce_scatter",
                                       "ring_reduce_scatter"),
    "ll_allgather": _ll_allgather,
    "ag_gemm_tail": _local(
        "allgather_gemm", "matmul_tail_into", _f32(64, 128), _f32(64, 128),
        _f32(128, 384), col_start=128, block_n=128),
    "ag_gemm_loopback": _local(
        "allgather_gemm", "ag_gemm_loopback", _f32(64, 32), _f32(32, 128),
        segments=8, config=AGGEMMConfig(block_n=128)),
    "ag_gemm_segmented_bare": _local(
        "allgather_gemm", "ag_gemm_segmented_bare", _f32(64, 32),
        _f32(32, 128), segments=8, config=AGGEMMConfig(block_n=128)),
    "matmul_single_chip": _local(
        "allgather_gemm", "ag_gemm_single_chip", _f32(128, 128),
        _f32(128, 128)),
    "fused_matmul_step": _local(
        "allgather_gemm", "fused_matmul_step", _f32(16, 128), _f32(16, 256),
        _f32(256, 128), 0.75, block_m=8, block_n=128, block_k=128),
    "gemm_rs_loopback": _local(
        "gemm_reduce_scatter", "gemm_rs_loopback", _f32(64, 32),
        _f32(32, 128), segments=8, config=GEMMRSConfig(block_n=128)),
    "allreduce_one_shot_loopback": _local(
        "allreduce", "oneshot_ar_loopback", _f32(8, 128), world=4),
}


@pytest.mark.parametrize("name", sorted(NAMED))
def test_a_named_kernel_is_known_by_its_name_in_the_lowered_text(tp4, name):
    """The call's ``name`` parameter in the traced program, and, for the
    kernels that run over the mesh (the TPU interpreter lowers them under
    the call's named scope, as Mosaic names the custom call), in the
    lowered text. The single-chip kernels go through the plain interpreter
    here, which keeps no scope: their name is checked where it is set."""
    fn, args = NAMED[name](tp4)
    assert re.search(rf"\bname={name}\s", str(jax.make_jaxpr(fn)(*args)))
    if not getattr(NAMED[name], "one_device", False):
        text = jax.jit(fn).lower(*args).as_text(debug_info=True)
        assert f'"{name}/pallas_call"' in text


# -- the count of what a step's collectives move --------------------------------

SLOTS, BLOCK, CHUNK = 8, 4, 8


def _trace_both_steps(tp: int):
    """A ``mode="dist"`` BatchEngine of ``tiny`` on ``tp`` devices whose two
    compiled steps were traced (not run) under the enabled ledger:
    ``(engine, {"decode": ledger entries, "prefill": ...})``."""
    mesh = make_mesh({"tp": tp}, devices=jax.devices()[:tp],
                     set_default=False)
    eng = Engine(ModelConfig.from_name("tiny"), mesh=mesh, mode="dist",
                 block_n=8)
    be = BatchEngine(eng, n_slots=SLOTS, block_size=BLOCK,
                     prefill_chunk=CHUNK)
    rows = be.prefill_rows
    offsets, tables, mask = be._operands([])
    tok = jnp.zeros((SLOTS,), jnp.int32)
    tail = (jnp.zeros((SLOTS,), jnp.float32), None,
            (be._prev, jnp.zeros((SLOTS,), bool)))
    head = (eng.params, be.pool.state, offsets, tables, mask)
    calls = {
        "decode": (be._decode_step, (head[0], tok, *head[1:], *tail)),
        "prefill": (be._mixed_step, (
            head[0], (tok, jnp.zeros((rows, CHUNK), jnp.int32),
                      jnp.full((rows, 3), -1, jnp.int32)), *head[1:],
            jnp.zeros((SLOTS,), jnp.int32), *tail)),
    }
    entries = {}
    for kind, (step, args) in calls.items():
        with comm_ledger.ledger(reset_first=True) as led:
            step.trace(*args)
            entries[kind] = [e for e in led.entries if e.world > 1]
        comm_ledger.reset()
    return be, entries


def test_a_pool_over_four_devices_shards_its_heads_and_the_steps_take_it():
    """The paired arena under ``tp`` = 4: (layers, blocks, 2 planes, lines,
    Hkv, dh) sharded on the head dim alone (one position right of where two
    arenas kept it), a device's shard both planes of its own heads; the
    paged steps take the pool under those specs and hand it back under them
    (the traced steps' state goes in and comes out at the whole arena's
    shape and sharding), and the block walk inside the shard_map reads a
    device's arena: ``Hkv / 4`` heads, ONE operand."""
    mesh = make_mesh({"tp": 4}, devices=jax.devices()[:4], set_default=False)
    eng = Engine(ModelConfig.from_name("tiny"), mesh=mesh, mode="dist",
                 block_n=8)
    be = BatchEngine(eng, n_slots=SLOTS, block_size=BLOCK,
                     prefill_chunk=CHUNK)
    cfg, kv = eng.config, be.pool.state.kv
    assert tuple(be.pool.specs.kv) == (None, None, None, None, "tp", None)
    assert tuple(kv.sharding.spec) == tuple(be.pool.specs.kv)
    local = (cfg.n_layers, be.pool.n_blocks, 2, BLOCK, cfg.n_kv_heads // 4,
             cfg.head_dim)
    assert {s.data.shape for s in kv.addressable_shards} == {local}
    offsets, tables, mask = be._operands([])
    traced = be._decode_step.trace(
        eng.params, jnp.zeros((SLOTS,), jnp.int32), be.pool.state, offsets,
        tables, mask, jnp.zeros((SLOTS,), jnp.float32), None,
        (be._prev, jnp.zeros((SLOTS,), bool)))
    out_state = jax.tree.leaves(traced.out_info)[-1]
    assert out_state.shape == kv.shape and out_state.dtype == kv.dtype

    def pallas_calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from pallas_calls(sub)

    walks = [e for e in pallas_calls(traced.jaxpr.jaxpr)
             if "paged_attention" in str(e.params.get("name")
                                         or e.params.get("name_and_src_info"))]
    assert walks
    for e in walks:
        arenas = [v.aval.shape for v in e.invars if len(v.aval.shape) == 6]
        assert arenas == [local]


def test_a_mesh_of_one_counts_no_collective():
    be, entries = _trace_both_steps(1)
    assert be.trace_counts == {"decode": 1, "prefill": 1}
    assert entries == {"decode": [], "prefill": []}
    assert be.stats_snapshot()["collectives"] == {
        kind: {"collective_calls": 0, "ici_bytes": 0, "by_collective": {}}
        for kind in ("decode", "prefill")}


def test_a_mesh_of_four_counts_what_the_ledger_counts():
    be, entries = _trace_both_steps(4)
    cfg = be.engine.config
    snap = be.stats_snapshot()["collectives"]
    item = jnp.dtype(cfg.dtype).itemsize
    for kind, tokens in (("decode", SLOTS),
                         ("prefill", SLOTS + be.prefill_rows * CHUNK)):
        got = snap[kind]
        by = {}
        for e in entries[kind]:
            c = by.setdefault(e.collective, [0, 0])
            c[0] += e.traced_calls
            c[1] += int(e.bytes_total)
        assert {k: [v["collective_calls"], v["ici_bytes"]]
                for k, v in got["by_collective"].items()} == by
        assert got["collective_calls"] == sum(c[0] for c in by.values())
        assert got["ici_bytes"] == sum(c[1] for c in by.values())
        # two fused pairs a layer, every layer of the scan, and the head's
        # gather once; perf_model's bytes of a device's rows of the batch
        assert {k: v["collective_calls"]
                for k, v in got["by_collective"].items()} == {
            "ag_gemm": 2 * cfg.n_layers, "gemm_rs": 2 * cfg.n_layers,
            "all_gather": 1}
        shard = tokens // 4 * cfg.d_model * item
        assert got["by_collective"]["ag_gemm"]["ici_bytes"] == \
            2 * cfg.n_layers * pm.wire_bytes_all_gather(shard, 4)
        assert got["by_collective"]["gemm_rs"]["ici_bytes"] == \
            2 * cfg.n_layers * pm.wire_bytes_reduce_scatter(4 * shard, 4)
        assert got["by_collective"]["all_gather"]["ici_bytes"] == \
            pm.wire_bytes_all_gather(shard, 4)
    assert snap["prefill"]["ici_bytes"] > snap["decode"]["ici_bytes"] > 0
    # a snapshot is the caller's own
    snap["decode"]["by_collective"].clear()
    assert be.collectives["decode"]["by_collective"]


def test_gathering_needs_no_ledger_and_a_loop_counts_its_trips():
    assert not comm_ledger.enabled() and not comm_ledger.recording()
    with comm_ledger.gathering() as got:
        assert comm_ledger.recording()
        comm_ledger.record_traced("x", axis="tp", world=4, nbytes=10)
        with comm_ledger.repeated(3), comm_ledger.repeated(2):
            comm_ledger.record_traced("y", axis="tp", world=4, nbytes=10,
                                      method="m")
    comm_ledger.record_traced("x", axis="tp", world=4, nbytes=10)
    assert [(r.collective, r.method, r.calls, r.nbytes) for r in got] == [
        ("x", "", 1, 10.0), ("y", "m", 6, 60.0)]
    assert comm_ledger.snapshot() == {}
    with comm_ledger.ledger(reset_first=True) as led:
        with comm_ledger.repeated(5):
            comm_ledger.record_traced("y", axis="tp", world=4, nbytes=10)
        (e,) = led.entries
        assert (e.traced_calls, e.calls, e.bytes_total) == (5, 0, 50.0)
    comm_ledger.reset()


# -- a name is the whole of the change on one chip --------------------------------

def _unnamed(monkeypatch):
    """``pl.pallas_call`` with every kernel's ``name=`` dropped."""
    real = pl.pallas_call

    def call(*args, name=None, **kw):
        return real(*args, **kw)
    monkeypatch.setattr(pl, "pallas_call", call)


def _one_chip_programs():
    """The jaxprs of what a one-chip ``mode="dist"`` deployment traces of
    the files this PR named: the degenerate AG-GEMM / GEMM-RS (a single-chip
    product) and the two served steps of ``tiny``."""
    from triton_distributed_tpu.kernels.allgather_gemm import (
        ag_gemm_single_chip)
    from triton_distributed_tpu.layers import nn

    nn._FUSED_TRACES.clear()        # the paged kernel's traces are cached
    a = jnp.ones((128, 128), jnp.float32)
    out = [str(jax.make_jaxpr(
        lambda a, b: ag_gemm_single_chip(a, b, interpret=True))(a, a))]
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    eng = Engine(ModelConfig.from_name("tiny"), mesh=mesh, mode="dist",
                 block_n=8)
    be = BatchEngine(eng, n_slots=SLOTS, block_size=BLOCK,
                     prefill_chunk=CHUNK)
    offsets, tables, mask = be._operands([])
    for kind, ids, extra in (
            ("decode", jnp.zeros((SLOTS, 1), jnp.int32), ()),
            ("prefill", jnp.zeros((SLOTS, CHUNK), jnp.int32),
             (jnp.ones((SLOTS,), jnp.int32),))):
        sm = eng._make_sm("dist", paged=kind, state_specs=be.pool.specs)
        out.append(str(jax.make_jaxpr(sm)(
            eng.params, ids, be.pool.state, offsets, tables, mask, *extra)))
    return out


def test_a_name_changes_nothing_of_a_one_chip_program_but_the_name(
        monkeypatch):
    named = _one_chip_programs()
    assert "name=matmul_single_chip" in named[0]
    _unnamed(monkeypatch)
    bare = _one_chip_programs()
    strip = re.compile(r"name=\S+")
    for with_name, without in zip(named, bare):
        assert "pallas_call" in with_name and with_name != without
        assert strip.sub("name=", with_name) == strip.sub("name=", without)
