"""The reduction from a profile to busy and idle time, time by name and the
idle gaps by what the host was doing, on a hand-made profile; and the loader
on a trace recorded here (a CPU trace: it holds no TPU plane, which the
reduction refuses)."""

import pytest

from perfbench import core, xplane
from perfbench.layer_metrics import (decode_step_roofline,
                                     paged_attn_device_share)

MS = 1e6   # ns
# names as the chip's trace has them (PR 24's first trace, shortened)
PAGED = ('%closed_call.35 = f32[32,8,128,128]{3,2,1,0:T(8,128)} custom-call('
         's32[32,256]{1,0:T(8,128)S(1)} %b, s32[32]{0} %c, bf16[3328,16,8,128]'
         '{3,2,1,0} %d), custom_call_target="tpu_custom_call", frontend_'
         'attributes={kernel_metadata={}}')
GEMM = ('%closed_call.37 = bf16[2048,12288]{1,0:T(8,128)(2,1)} custom-call('
        'bf16[2048,2048]{1,0} %a, bf16[2048,12288]{1,0} %b), '
        'custom_call_target="tpu_custom_call"')


def device_plane(i, shift=0.0):
    ops = [("%while.2 = (s32[]) while(...)", 0 * MS + shift, 6 * MS),
           ("fusion.1", 0 * MS + shift, 3.5 * MS),      # nested in the while
           (PAGED, 4 * MS + shift, 2 * MS),    # nested too
           ("fusion.1", 10 * MS + shift, 3 * MS),
           (GEMM, 13 * MS + shift, 3 * MS),
           ("fusion.2", 20 * MS + shift, 4 * MS)]
    modules = [("jit_decode_step(123)", 0 * MS + shift, 6 * MS),
               ("jit_decode_step(123)", 10 * MS + shift, 6 * MS),
               ("jit_mixed_step(456)", 20 * MS + shift, 4 * MS)]
    return {"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": modules},
        {"name": "Steps", "events": [("0", 0.0, 24 * MS)]}]}


PROFILE = {"planes": [
    device_plane(0), device_plane(1),
    {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ("bench.step", 0 * MS, 7 * MS), ("bench.submit", 7 * MS, 2.5 * MS),
        ("bench.step", 9.8 * MS, 6.5 * MS),
        ("bench.wait_due", 16.5 * MS, 3 * MS),
        ("something_else", 0.0, 24 * MS)]}]}]}


def test_busy_idle_names_and_gap_blame():
    r = xplane.reduce_profile(PROFILE)
    assert r["window_s"] == pytest.approx(24e-3)
    # busy: [0,6] + [10,16] + [20,24] = 16 ms on each chip
    assert r["busy_s"] == pytest.approx(16e-3)
    assert r["busy_s_per_chip"] == pytest.approx([16e-3, 16e-3])
    assert r["ops_s"]["fusion.1"] == pytest.approx(6.5e-3)
    # the while's own time is what its body leaves uncovered: 6 - 3.5 - 2
    assert r["ops_s"]["%while.2 = (s32[]) while(...)"] == pytest.approx(.5e-3)
    assert sum(r["ops_s"].values()) == pytest.approx(r["busy_s"])
    assert r["modules_s"]["jit_decode_step(123)"] == pytest.approx(12e-3)
    gaps = dict(map(tuple, r["breakdown"]["idle_gaps"]))
    # gap 6..10: bench.submit covers 2.5 of 4 ms; gap 16..20: wait_due 3 of 4
    assert gaps == {"bench.submit": pytest.approx(4e-3),
                    "bench.wait_due": pytest.approx(4e-3)}
    assert r["breakdown"]["device_ops"][0] == ["fusion.1",
                                               pytest.approx(6.5e-3)]
    assert xplane.short_name(
        "%copy.63 = bf16[28,3328,16]{2,1,0:T(8,128)} copy(bf16[28] %p)") == \
        "copy.63 bf16[28,3328,16] copy"
    assert len(r["breakdown"]["device_ops"]) <= 10


def test_a_gap_nothing_covers_is_put_down_to_the_loop():
    assert xplane.blame((0.0, 10.0), [("bench.step", 0.0, 2.0)]) == \
        "between_steps"
    assert xplane.blame((0.0, 10.0), [("bench.step", 0.0, 3.0),
                                      ("bench.step", 4.0, 8.0)]) == \
        "bench.step"


def records(trace, n_chips):
    import json
    import os

    from perfbench import families

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", "qwen3-1.7b.json")) as f:
        cfg = json.load(f)
    family = families.load_family(cfg)
    return core.Records(
        t_open=0.0, t_close=1.0, t_end=1.0,
        setup_s=1.0, tracked=[], kv_live=[], counters={},
        steps=[(0.10, 0.11, "decode", 32, 32, 32 * 1000),
               (0.12, 0.13, "decode", 32, 32, 32 * 1000),
               (0.90, 0.91, "decode", 32, 32, 32 * 4000)],   # outside the span
        queue_wait_s=[], sizes=family.sizes(cfg), family=family, n_slots=32,
        n_chips=n_chips,
        device_kind="TPU v5 lite", trace=trace)


def test_trace_readers():
    trace = xplane.reduce_profile(PROFILE)
    trace["host_window"] = (0.05, 0.5)
    rec = records(trace, 2)
    assert paged_attn_device_share.read(rec) == pytest.approx(100 * 2 / 16)
    # (3.44 GB of weights and head + 32,000 tokens x 112 KiB) / 2 chips
    # at 819 GB/s, over 6 ms a run of the decode program
    floor_ms = (3_440_902_144 + 32_000 * 114_688) / 2 / 819e9 * 1e3
    assert decode_step_roofline.read(rec) == pytest.approx(
        100 * floor_ms / 6.0, rel=1e-6)
    assert decode_step_roofline.read(records(None, 1)) is None


def test_loader_reads_a_recorded_trace_and_refuses_one_with_no_tpu(tmp_path):
    import jax
    import jax.numpy as jnp

    tracer = xplane.SpanTracer(str(tmp_path / "t"), start_s=0.0, span_s=10.0)
    tracer.tick(0.0)
    with tracer.annotate("bench.step"):
        jax.block_until_ready(jax.jit(lambda x: x @ x)(jnp.ones((64, 64))))
    tracer.stop()
    assert tracer.window is not None and tracer.state == "done"
    profile = xplane.load(tracer.path)
    names = {ev[0] for p in profile["planes"] for ln in p["lines"]
             for ev in ln["events"]}
    assert "bench.step" in names
    assert xplane.host_spans(profile)[0][0] == "bench.step"
    with pytest.raises(ValueError, match="no /device:TPU plane"):
        xplane.reduce_profile(profile)
