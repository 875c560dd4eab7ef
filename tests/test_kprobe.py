"""Device-side kernel telemetry tests (kernels/probes.py + obs/kprobe.py).

Three layers, matching how the pipeline is meant to run:

1. **Decoder goldens** — hand-built probe buffers with known field values
   decode to exact StepRecords, exact stall percentages (under a pinned
   Hardware profile), and exact Chrome rows; malformed buffers raise.
2. **Analyzer-tracer pipeline** — the ``{base}+probe`` registry variants
   run under the abstract interpreter (``analysis.events``), which is
   deterministic on CPU: every rank's probe buffer decodes, stall shares
   sum to 100, device traces export and merge with the host-span export,
   and measured DMA bytes cross-check against the perf model / ledger.
3. **Bit-identity** — probe-on output equals probe-off output bit-for-bit.
   Paged attention (no barrier semaphores) runs unconditionally on the
   generic CPU interpreter; the distributed kernels need the Pallas TPU
   interpreter (``pltpu.InterpretParams``) or real hardware, matching the
   pre-existing guard situation for every distributed kernel test.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu.analysis import checks, events, registry
from triton_distributed_tpu.kernels import probes
from triton_distributed_tpu.obs import kprobe, roofline, trace
from triton_distributed_tpu.runtime import perf_model as pm
from triton_distributed_tpu.runtime.compat import shard_map

from conftest import pair_planes

WORLDS = (2, 4, 8)
PROBE_VARIANTS = tuple(f"{base}+probe" for base in probes.PROBE_BASES)

# The distributed kernels block on barrier semaphores, which the generic
# (non-TPU) Pallas interpreter does not implement — the same constraint
# every distributed kernel test in this suite lives under.
needs_tpu_interpret = pytest.mark.skipif(
    getattr(pltpu, "InterpretParams", None) is None
    and jax.default_backend() != "tpu",
    reason="distributed kernels need the Pallas TPU interpreter or a TPU",
)

# Pinned profile so golden numbers do not move with the host's detected
# hardware: 1 GB/s link, 1 us hop, 2^20 kflop/s -> round phase seconds.
_HW = pm.Hardware(name="test", peak_bf16_flops=float(1 << 30),
                  hbm_bw=8e9, ici_link_bw=1e9, ici_links=2,
                  ici_hop_lat=1e-6, dcn_bw=1e9, dcn_lat=1e-5)


def _synthetic_buf(*, rank=0, world=2):
    """A well-formed probe buffer: step i waited on 1000*(i+1) bytes, spun
    i times, and computed 2*(i+1) kflops."""
    n_steps = 2
    buf = np.zeros((1 + n_steps, probes.N_FIELDS), np.int32)
    buf[0, probes.H_MAGIC] = probes.MAGIC
    buf[0, probes.H_VERSION] = probes.VERSION
    buf[0, probes.H_STEPS] = n_steps
    buf[0, probes.H_RANK] = rank
    buf[0, probes.H_WORLD] = world
    for i in range(n_steps):
        buf[1 + i] = [i + 1,              # ordinal
                      3,                  # dma_issue
                      2,                  # dma_wait
                      i,                  # sem_spin
                      500,                # local_bytes
                      700 * (i + 1),      # remote_bytes
                      1000 * (i + 1),     # wait_bytes
                      2 * (i + 1)]        # kflops
    return buf


# ---------------------------------------------------------------------------
# 1. Decoder goldens
# ---------------------------------------------------------------------------


def test_decode_golden():
    tr = kprobe.decode(_synthetic_buf(rank=1, world=4))
    assert (tr.rank, tr.world, tr.n_steps) == (1, 4, 2)
    s0, s1 = tr.steps
    assert (s0.ordinal, s0.dma_issue, s0.dma_wait, s0.sem_spin) == (1, 3, 2, 0)
    assert (s0.wait_bytes, s1.wait_bytes) == (1000, 2000)
    assert tr.totals() == {"dma_issue": 6, "dma_wait": 4, "sem_spin": 1,
                           "local_bytes": 1000, "remote_bytes": 2100,
                           "wait_bytes": 3000, "kflops": 6}
    # Modeled phase seconds under the pinned profile are exact.
    assert s0.phase_seconds(_HW) == {
        "dma_wait": 1000 / 1e9, "sem_spin": 0.0,
        "compute": 2 * 1024 / float(1 << 30)}
    assert tr.modeled_seconds(_HW) == pytest.approx(
        3000 / 1e9 + 1e-6 + 6 * 1024 / float(1 << 30))


def test_decode_rejects_malformed():
    with pytest.raises(ValueError, match="shape"):
        kprobe.decode(np.zeros((3, probes.N_FIELDS + 1), np.int32))
    with pytest.raises(ValueError, match="magic"):
        kprobe.decode(np.zeros((2, probes.N_FIELDS), np.int32))
    bad_ver = _synthetic_buf()
    bad_ver[0, probes.H_VERSION] = probes.VERSION + 1
    with pytest.raises(ValueError, match="version"):
        kprobe.decode(bad_ver)
    short = _synthetic_buf()[:2]   # header says 2 steps, 1 row present
    with pytest.raises(ValueError, match="rows"):
        kprobe.decode(short)


def test_decode_all_sorts_by_rank():
    bufs = np.stack([_synthetic_buf(rank=r, world=3) for r in (2, 0, 1)])
    traces = kprobe.decode_all(bufs)
    assert [t.rank for t in traces] == [0, 1, 2]
    assert all(t.world == 3 for t in traces)


def test_stall_summary_golden():
    bufs = np.stack([_synthetic_buf(rank=r, world=2) for r in range(2)])
    s = kprobe.stall_summary(bufs, hw=_HW)
    assert (s["world"], s["ranks"], s["n_steps"]) == (2, 2, 2)
    dma_s, spin_s = 3000 / 1e9, 1e-6
    comp_s = 6 * 1024 / float(1 << 30)
    total = dma_s + spin_s + comp_s
    assert s["pct_dma_wait"] == pytest.approx(100 * dma_s / total)
    assert s["pct_sem_spin"] == pytest.approx(100 * spin_s / total)
    assert s["pct_compute"] == pytest.approx(100 * comp_s / total)
    assert (s["pct_dma_wait"] + s["pct_sem_spin"]
            + s["pct_compute"]) == pytest.approx(100.0)
    # Identical ranks -> no straggler spread; per-rank breakdown present.
    assert s["straggler_spread"] == 0.0
    assert [r["rank"] for r in s["per_rank"]] == [0, 1]


def test_chrome_device_events_golden(tmp_path):
    tr = kprobe.decode(_synthetic_buf(rank=1, world=2))
    ev = kprobe.chrome_device_events(tr, wall_start_us=10.0,
                                     wall_dur_us=100.0, hw=_HW)
    meta = [e for e in ev if e["ph"] == "M"]
    rows = [e for e in ev if e["ph"] == "X"]
    assert {e["name"] for e in meta} == {"process_name", "thread_name"}
    assert [e for e in meta if e["name"] == "process_name"][0]["args"] == {
        "name": "rank 1"}
    # pid = rank, tid = grid step, one X row per non-empty phase.
    assert all(e["pid"] == 1 for e in rows)
    assert {e["tid"] for e in rows} == {0, 1}
    assert {e["name"] for e in rows} <= set(kprobe.PHASES)
    # Step 0 has sem_spin == 0 -> 2 phases; step 1 has all 3.
    assert len([e for e in rows if e["tid"] == 0]) == 2
    assert len([e for e in rows if e["tid"] == 1]) == 3
    # Rows tile the wall bracket contiguously, in ordinal order.
    assert rows[0]["ts"] == 10.0
    assert sum(e["dur"] for e in rows) == pytest.approx(100.0)
    for a, b in zip(rows, rows[1:]):
        assert b["ts"] == pytest.approx(a["ts"] + a["dur"])


def test_crosscheck_bytes_explicit():
    bufs = np.stack([_synthetic_buf(rank=r, world=2) for r in range(2)])
    ok = kprobe.crosscheck_bytes(bufs, expected=4200.0)
    assert ok["ok"] and ok["rel_err"] == 0.0 and ok["source"] == "explicit"
    bad = kprobe.crosscheck_bytes(bufs, expected=42.0)
    assert not bad["ok"] and bad["rel_err"] > 1


def test_split_hbm_bound():
    stalled = {"pct_dma_wait": 30.0, "pct_sem_spin": 5.0}
    busy = {"pct_dma_wait": 5.0, "pct_sem_spin": 1.0}
    assert roofline.split_hbm_bound("hbm", stalled) == "hbm-stalled"
    assert roofline.split_hbm_bound("hbm", busy) == "hbm-bound"
    # Refines only: other classes / missing summaries pass through.
    assert roofline.split_hbm_bound("ici", stalled) == "ici"
    assert roofline.split_hbm_bound("compute", stalled) == "compute"
    assert roofline.split_hbm_bound("hbm", None) == "hbm"


def test_null_probe_is_noop():
    # The probe-off path threads probes.NULL through every helper; it must
    # be free of side effects and accept every probe call shape.
    n = probes.NULL
    assert n.enter(0, 0, 1) is None
    assert n.dma_issue(None) is None and n.dma_wait(None) is None
    assert n.sem_spin(3) is None and n.compute(1 << 20) is None


# ---------------------------------------------------------------------------
# 2. Analyzer-tracer pipeline (deterministic on CPU)
# ---------------------------------------------------------------------------


def _traced_bufs(name: str, world: int) -> np.ndarray:
    spec = registry.get(name).build(world)
    tr = events.trace_kernel(spec, world)
    return np.stack([tr.store[("probe_buf", r)] for r in range(world)])


def test_probe_variants_registered():
    names = {e.name for e in registry.all_kernels()}
    missing = set(PROBE_VARIANTS) - names
    assert not missing, missing


@pytest.mark.parametrize("world", WORLDS)
@pytest.mark.parametrize("name", PROBE_VARIANTS)
def test_probe_variant_traces_clean_and_decodes(name, world):
    vs = checks.check_kernel(name, world)
    assert not vs, [str(v) for v in vs]
    bufs = _traced_bufs(name, world)
    traces = kprobe.decode_all(bufs)
    assert [t.rank for t in traces] == list(range(world))
    assert all(t.world == world for t in traces)
    # Every grid step executed: ordinals are a permutation of 1..n_steps.
    for t in traces:
        assert sorted(s.ordinal for s in t.steps) == list(
            range(1, t.n_steps + 1))
    s = kprobe.stall_summary(bufs, hw=_HW)
    assert (s["pct_dma_wait"] + s["pct_sem_spin"]
            + s["pct_compute"]) == pytest.approx(100.0)


def test_ag_gemm_merged_device_host_trace(tmp_path):
    """The ISSUE acceptance path: traced ag_gemm probe buffers export as
    per-rank per-grid-step Chrome rows that merge under the existing host
    trace glob, and the stall summary's shares sum to ~100."""
    world = 4
    bufs = _traced_bufs("ag_gemm+probe", world)
    # Host side: one span, exported to the same directory.
    tracer = trace.Tracer()
    tracer.enable()
    with tracer.span("ag_gemm_launch"):
        pass
    tracer.export_chrome_trace(str(tmp_path))
    paths = kprobe.export_device_traces(bufs, str(tmp_path),
                                        wall_dur_us=500.0, hw=_HW,
                                        label="ag_gemm")
    assert [os.path.basename(p) for p in paths] == [
        f"trace.p{r}.dev.json" for r in range(world)]
    merged = trace.merge_chrome_traces(str(tmp_path))
    ev = json.loads(open(merged).read())["traceEvents"]
    dev = [e for e in ev if e.get("cat") == "device"]
    assert {e["pid"] for e in dev} == set(range(world))
    n_steps = kprobe.decode(bufs[0]).n_steps
    for r in range(world):
        # Every grid step of every rank has at least one device row.
        assert {e["tid"] for e in dev if e["pid"] == r} == set(
            range(n_steps))
    # Host spans survive the merge alongside the device rows.
    assert any(e.get("name") == "ag_gemm_launch" for e in ev)
    # And the row-label metadata covers all ranks.
    pnames = {e["args"]["name"] for e in ev
              if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert {f"rank {r}" for r in range(world)} <= pnames


def test_gemm_rs_stall_summary_shares():
    world = 8
    bufs = _traced_bufs("gemm_rs+probe", world)
    s = kprobe.stall_summary(bufs, hw=_HW)
    assert s["world"] == world and s["ranks"] == world
    assert (s["pct_dma_wait"] + s["pct_sem_spin"]
            + s["pct_compute"]) == pytest.approx(100.0)
    # An overlapped comm kernel records all three phase kinds.
    assert s["pct_dma_wait"] > 0 and s["pct_compute"] > 0
    assert s["pct_sem_spin"] > 0


def test_crosscheck_ag_ring_vs_perf_model():
    """Measured remote-DMA bytes from the traced ring allgather equal the
    perf model's wire-byte analytics exactly (the tracer moves exactly the
    bytes the kernel asks for)."""
    world = 8
    bufs = _traced_bufs("ag.ring+probe", world)
    spec = registry.get("ag.ring+probe").build(world)
    shard = next(b for b in spec.args if b.name == "x")
    shard_nbytes = int(np.prod(shard.shape)) * np.dtype(shard.dtype).itemsize
    # wire_bytes_* are per-device; the probes sum over every rank.
    expected = world * pm.wire_bytes_all_gather(shard_nbytes, world)
    res = kprobe.crosscheck_bytes(bufs, expected=expected)
    assert res["ok"] and res["rel_err"] == 0.0, res


def test_crosscheck_via_comm_ledger():
    from triton_distributed_tpu.obs import comm_ledger

    world = 4
    bufs = _traced_bufs("ag.ring+probe", world)
    shard = next(b for b in registry.get("ag.ring+probe").build(world).args
                 if b.name == "x")
    shard_nbytes = int(np.prod(shard.shape)) * np.dtype(shard.dtype).itemsize
    ledger = comm_ledger.get_ledger()
    was = ledger.enabled
    ledger.enabled = True
    try:
        # The ledger entry carries the launch's total (all-rank) wire bytes.
        comm_ledger.record(
            "all_gather", axis="tp", world=world,
            nbytes=float(world * pm.wire_bytes_all_gather(shard_nbytes,
                                                          world)),
            method="ring_1d")
        res = kprobe.crosscheck_bytes(bufs, collective="all_gather")
        assert res["source"] == "ledger" and res["ok"], res
    finally:
        ledger.enabled = was
        comm_ledger.reset()


# ---------------------------------------------------------------------------
# 3. Bit-identity: probe-on output == probe-off output
# ---------------------------------------------------------------------------


def test_paged_attention_bit_identity(rng):
    """No barrier semaphores -> runs on the generic CPU interpreter, so the
    full compile-and-run identity check is unconditional."""
    from triton_distributed_tpu.kernels.paged_attention import (
        paged_decode_attention,
    )

    B, Hq, Hkv, dh, bs, max_blocks = 2, 4, 2, 128, 8, 4
    n_blocks = B * max_blocks
    q = jnp.asarray(rng.standard_normal((B, Hq, dh)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_blocks, bs, Hkv, dh)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_blocks, bs, Hkv, dh)),
                     jnp.float32)
    tables = jnp.arange(n_blocks, dtype=jnp.int32).reshape(B, max_blocks)
    kv_lens = jnp.asarray([max_blocks * bs, bs + 3], jnp.int32)

    pool = pair_planes(kp, vp)
    off = paged_decode_attention(q, pool, tables, kv_lens, tile_blocks=2,
                                 interpret=True)
    on, pbuf = paged_decode_attention(q, pool, tables, kv_lens,
                                      tile_blocks=2, interpret=True,
                                      probes=True)
    assert np.array_equal(np.asarray(off), np.asarray(on))
    tr = kprobe.decode(pbuf)
    assert (tr.rank, tr.world, tr.n_steps) == (0, 1, B * 2)
    tot = tr.totals()
    assert tot["dma_issue"] > 0 and tot["kflops"] > 0
    assert tot["remote_bytes"] == 0 and tot["sem_spin"] == 0
    s = kprobe.stall_summary(pbuf[None], hw=_HW)
    assert (s["pct_dma_wait"] + s["pct_sem_spin"]
            + s["pct_compute"]) == pytest.approx(100.0)


def test_paged_prefill_probe_bit_identity(rng):
    """probes=True on an L>1 chunked-prefill step: output bit-identical,
    one probe step per (slot, q_tile, kv_tile) grid cell, and stall
    attribution decodes — prefill is no longer a blind spot."""
    from triton_distributed_tpu.kernels.paged_attention import (
        paged_attention,
    )

    B, L, Hq, Hkv, dh, bs, max_blocks = 2, 8, 4, 2, 128, 8, 4
    n_blocks = B * max_blocks
    q = jnp.asarray(rng.standard_normal((B, L, Hq, dh)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_blocks, bs, Hkv, dh)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_blocks, bs, Hkv, dh)),
                     jnp.float32)
    tables = jnp.arange(n_blocks, dtype=jnp.int32).reshape(B, max_blocks)
    kv_lens = jnp.asarray([max_blocks * bs, bs + 3], jnp.int32)
    q_lens = jnp.asarray([L, 3], jnp.int32)        # ragged mixed step

    pool = pair_planes(kp, vp)
    off = paged_attention(q, pool, tables, kv_lens, q_lens=q_lens,
                          tile_blocks=2, q_tile=4, interpret=True)
    on, pbuf = paged_attention(q, pool, tables, kv_lens, q_lens=q_lens,
                               tile_blocks=2, q_tile=4, interpret=True,
                               probes=True)
    assert np.array_equal(np.asarray(off), np.asarray(on))
    tr = kprobe.decode(pbuf)
    n_q_tiles = 2                                   # ceil(8 / 4)
    assert (tr.rank, tr.world, tr.n_steps) == (0, 1, B * n_q_tiles * 2)
    tot = tr.totals()
    assert tot["dma_issue"] > 0 and tot["kflops"] > 0
    assert tot["remote_bytes"] == 0 and tot["sem_spin"] == 0
    s = kprobe.stall_summary(pbuf[None], hw=_HW)
    assert (s["pct_dma_wait"] + s["pct_sem_spin"]
            + s["pct_compute"]) == pytest.approx(100.0)


@needs_tpu_interpret
@pytest.mark.parametrize("kind", ["ag.ring", "ag.a2a", "ar.oneshot",
                                  "rs.oneshot", "rs.ring"])
def test_collective_bit_identity(mesh8, rng, kind):
    from triton_distributed_tpu.kernels.allgather import (
        a2a_all_gather, ring_all_gather)
    from triton_distributed_tpu.kernels.allreduce import oneshot_all_reduce
    from triton_distributed_tpu.kernels.reduce_scatter import (
        oneshot_reduce_scatter, ring_reduce_scatter)

    world = 8
    fns = {"ag.ring": ring_all_gather, "ag.a2a": a2a_all_gather,
           "ar.oneshot": oneshot_all_reduce,
           "rs.oneshot": oneshot_reduce_scatter,
           "rs.ring": ring_reduce_scatter}
    rows = world * 2 if kind.startswith("rs.") else 2
    x = jnp.asarray(rng.standard_normal((world, rows, 128)), jnp.float32)
    f = fns[kind]

    def run(probes):
        def dev(v):
            out = f(v[0], axis="tp", probes=probes)
            res = out[0] if probes else out
            return res[None]
        return shard_map(dev, mesh=mesh8, in_specs=P("tp"),
                         out_specs=P("tp"), check_vma=False)(x)

    assert np.array_equal(np.asarray(run(False)), np.asarray(run(True)))


@needs_tpu_interpret
def test_gemm_rs_bit_identity(mesh8, rng):
    from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
        GEMMRSConfig, gemm_rs_device)

    world = 8
    M, K, N = 2 * world, 8 * world, 128
    a = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)

    def run(probes):
        def dev(av, bv):
            out = gemm_rs_device(av, bv, axis="tp",
                                 config=GEMMRSConfig(block_n=128),
                                 probes=probes)
            return out[0] if probes else out
        return shard_map(dev, mesh=mesh8, in_specs=(P(None, "tp"), P("tp")),
                         out_specs=P("tp"), check_vma=False)(a, b)

    assert np.array_equal(np.asarray(run(False)), np.asarray(run(True)))


@needs_tpu_interpret
def test_ag_gemm_bit_identity(mesh8, rng):
    from triton_distributed_tpu.kernels.allgather_gemm import (
        AGGEMMConfig, ag_gemm_device)

    world = 8
    M, K, N = 8 * world, 32, 128 * world
    a = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)

    def run(probes):
        def dev(av, bv):
            out = ag_gemm_device(av, bv, axis="tp",
                                 config=AGGEMMConfig(block_n=128),
                                 probes=probes)
            return out[0] if probes else out
        return shard_map(dev, mesh=mesh8, in_specs=(P("tp"), P(None, "tp")),
                         out_specs=P(None, "tp"), check_vma=False)(a, b)

    assert np.array_equal(np.asarray(run(False)), np.asarray(run(True)))


@needs_tpu_interpret
def test_ep_a2a_bit_identity(mesh8, rng):
    from triton_distributed_tpu.kernels.ep_all_to_all import (
        AllToAllContext, fast_all_to_all)

    world, cap, hidden = 8, 8, 16
    ctx = AllToAllContext(capacity=cap, hidden=hidden, axis="tp",
                          chunk_rows=8)
    toks = jnp.asarray(
        rng.standard_normal((world, world, cap, hidden)), jnp.float32)
    counts = jnp.full((world, world), cap, jnp.int32)

    def run(probes):
        def dev(t, c):
            res = fast_all_to_all(t[0], c[0], ctx=ctx, probes=probes)
            out, rcounts = res[0], res[1]
            return out[None], rcounts[None]
        return shard_map(dev, mesh=mesh8, in_specs=(P("tp"), P("tp")),
                         out_specs=(P("tp"), P("tp")),
                         check_vma=False)(toks, counts)

    out_off, cnt_off = run(False)
    out_on, cnt_on = run(True)
    assert np.array_equal(np.asarray(out_off), np.asarray(out_on))
    assert np.array_equal(np.asarray(cnt_off), np.asarray(cnt_on))


@needs_tpu_interpret
def test_moe_ag_group_gemm_bit_identity(mesh8, rng):
    from triton_distributed_tpu.kernels.moe_overlap import (
        MoEOverlapConfig, ag_group_gemm_device)

    world, m, d, E, cap, f = 8, 8, 64, 2, 8, 128
    x = jnp.asarray(rng.standard_normal((world, m, d)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, E, (world, m, 1)), jnp.int32)
    w = jnp.asarray(rng.standard_normal((E, d, world * f)), jnp.float32)

    def run(probes):
        def dev(xv, iv, wv):
            res = ag_group_gemm_device(
                xv[0], iv[0], wv, n_experts=E, capacity=cap, axis="tp",
                config=MoEOverlapConfig(), probes=probes)
            return res[0][None]
        return shard_map(dev, mesh=mesh8,
                         in_specs=(P("tp"), P("tp"), P(None, None, "tp")),
                         out_specs=P("tp"), check_vma=False)(x, ids, w)

    assert np.array_equal(np.asarray(run(False)), np.asarray(run(True)))
