"""chip_smoke.py's own checks, and the start-up rules it leans on.

The smoke's serving function runs here at ``tiny`` on the CPU (the geometry
comes from this test, not from an option of the script); the rest are unit
tests of the repairs the chip run needs: one compile-cache rule, errors
that must raise instead of being absorbed, and the pool's allocation.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402

from triton_distributed_tpu.models import Engine, ModelConfig  # noqa: E402
from triton_distributed_tpu.runtime import autotuner, perf_model  # noqa: E402
from triton_distributed_tpu.runtime import platform as _platform  # noqa: E402
from triton_distributed_tpu.runtime.mesh import make_mesh  # noqa: E402
from triton_distributed_tpu.serving import HEALTHY, Fleet  # noqa: E402
from triton_distributed_tpu.serving import batch_engine as _be  # noqa: E402
from triton_distributed_tpu.serving import kv_pool as _kv  # noqa: E402
from triton_distributed_tpu.tools import aot  # noqa: E402

TINY = dict(model="tiny", interpret=None, block_n=8, seed=0, n_slots=2,
            block_size=4, prefill_chunk=8, n_requests=4,
            prompt_range=(6, 16), new_tokens=3, ref_len=10, prefix_len=8)


# -- the smoke itself ---------------------------------------------------------


@pytest.fixture
def restore_compile_cache_config():
    """The smoke turns the persistent compile cache on for its process;
    this worker runs other test files afterwards, so put it back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    prior = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in prior.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_smoke_passes_at_tiny_and_prints_the_ok_line(
        capsys, restore_compile_cache_config):
    rc = chip_smoke.smoke(chip_smoke.run_one_chip, jax.devices()[:1], TINY)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    records = [json.loads(line) for line in lines]   # every line is JSON
    last = records[-1]
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    phases = [r.get("phase") for r in records[:-1]]
    assert phases == ["device", "autotune", "build", "first_call", "serve",
                      "numeric", "greedy_agreement", "memory"]
    serve = records[phases.index("serve")]
    assert serve["trace_counts"] == {"decode": 1, "prefill": 1}
    assert serve["prefix_hits"] > 0
    assert serve["tokens_generated"] == serve["requests"] * TINY["new_tokens"]
    assert records[phases.index("numeric")]["prefill_rel"] < 1e-4


def test_hybrid_phase_passes_at_tiny(capsys, restore_compile_cache_config):
    """The smoke's hybrid phase at tiny float32 sizes: requests served, the
    step's counts adding up, and the chunk scan against the one-token
    update on the same tokens."""
    import dataclasses

    from triton_distributed_tpu.models.config import GraniteHybridConfig

    # (the gather path: the fused block walk under the interpreter is
    # tests/test_granite_hybrid.py's)
    geo = dict(chip_smoke.HYBRID, interpret=None, paged_attn="gather",
               n_slots=6, block_size=4,
               prefill_chunk=8, n_requests=3, prompt_range=(10, 20),
               new_tokens=3, walk_len=10,
               overrides=dataclasses.asdict(GraniteHybridConfig.tiny()))
    rc = chip_smoke.smoke(chip_smoke.run_hybrid, jax.devices()[:1], geo)
    records = [json.loads(line)
               for line in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and records[-1]["ok"] is True
    phases = [r.get("phase") for r in records[:-1]]
    assert phases == ["hybrid_build", "hybrid_serve", "numeric", "numeric",
                      "hybrid_memory"]
    assert records[1]["trace_counts"] == {"decode": 1, "prefill": 1}
    assert records[1]["ssm_states_reset"] == 3
    assert records[1]["prefill_rows_extra"] > 0     # a slot's rows chained
    # chunks against tokens, then rows dealt two a step against one a step
    assert records[2]["prefill_rel"] < 1e-4 and records[2]["decode_rel"] < 1e-4
    assert records[2]["decode_routed_apart"] == []  # no router, none apart
    assert "2 a step vs one a step" in records[3]["compared"]
    assert records[3]["prefill_rel"] == 0 == records[3]["decode_rel"]


def test_nemotron_h_phase_passes_at_tiny(capsys, restore_compile_cache_config):
    """The same phase over the Nemotron-H block at tiny float32 sizes: all
    three kinds of layer served, no routed pair dropped."""
    import dataclasses

    from triton_distributed_tpu.models.config import NemotronHConfig

    geo = dict(chip_smoke.NEMOTRON_H, interpret=None, paged_attn="gather",
               n_slots=6, block_size=4,
               prefill_chunk=8, n_requests=3, prompt_range=(10, 20),
               new_tokens=3, walk_len=10,
               overrides=dataclasses.asdict(NemotronHConfig.tiny()))
    rc = chip_smoke.smoke(chip_smoke.run_hybrid, jax.devices()[:1], geo)
    records = [json.loads(line)
               for line in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and records[-1]["ok"] is True
    assert records[0]["state_layers"] == 4 and records[0]["cache_layers"] == 1
    assert records[1]["trace_counts"] == {"decode": 1, "prefill": 1}
    assert records[1]["moe_pairs_held"] > 0 == records[1]["moe_dropped_pairs"]
    assert records[1]["prefill_rows_extra"] > 0     # a slot's rows chained
    assert records[2]["prefill_rel"] < 1e-4 and records[2]["decode_rel"] < 1e-4
    # both programs' routers were read, and chose alike at float32
    assert records[2]["prefill_routed_apart"] == [] \
        == records[2]["decode_routed_apart"]
    assert records[3]["prefill_rel"] == 0 == records[3]["decode_rel"]


def test_exaone_moe_phase_passes_at_tiny(capsys, restore_compile_cache_config):
    """The same phase over the EXAONE-MoE block at tiny float32 sizes:
    window layers (6) beside a full one, the prompts pass the window and the
    walk wraps the ring (6 - 1 + 2 rows of 8 = 6 blocks of 4: 24 lines),
    the deal engages, no routed pair dropped."""
    import dataclasses

    from triton_distributed_tpu.models.config import ExaoneMoeConfig

    geo = dict(chip_smoke.EXAONE_MOE, interpret=None, paged_attn="gather",
               n_slots=2, block_size=4,
               prefill_chunk=8, n_requests=3, prompt_range=(30, 40),
               new_tokens=3, walk_len=30,
               overrides=dataclasses.asdict(ExaoneMoeConfig.tiny()))
    rc = chip_smoke.smoke(chip_smoke.run_hybrid, jax.devices()[:1], geo)
    records = [json.loads(line)
               for line in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and records[-1]["ok"] is True
    assert records[0]["window_layers"] == 3 and records[0]["cache_layers"] == 1
    assert records[0]["window"]["ring_blocks"] == 6
    assert records[1]["trace_counts"] == {"decode": 1, "prefill": 1}
    assert records[1]["moe_pairs_held"] > 0 == records[1]["moe_dropped_pairs"]
    assert records[1]["prefill_rows_extra"] > 0
    assert records[2]["prefill_rel"] < 1e-4 and records[2]["decode_rel"] < 1e-4
    assert records[2]["decode_routed_apart"] == []
    # no state handed from row to row: no second comparison
    assert [r.get("phase") for r in records].count("numeric") == 1


def test_smallthinker_phase_passes_at_tiny(capsys,
                                           restore_compile_cache_config):
    """The same phase over the SmallThinker block (the EXAONE walk with the
    router before attention, softmax over the chosen logits, ReGLU, no
    shared expert, no QK norm) at tiny float32 sizes, three query heads to a
    key head: every pair held, both programs' routers read and alike."""
    import dataclasses

    from triton_distributed_tpu.models.config import ExaoneMoeConfig

    tiny = ExaoneMoeConfig.tiny(
        layer_types=("full_attention", "sliding_attention"),
        sliding_windows=(0, 6), mlp_layer_types=("sparse", "sparse"),
        n_heads=6, n_shared_experts=0, qk_norm=False, scoring="softmax_topk",
        expert_activation="reglu", router_input="layer_input")
    geo = dict(chip_smoke.SMALLTHINKER, interpret=None, paged_attn="gather",
               n_slots=2, block_size=4, prefill_chunk=8, n_requests=3,
               prompt_range=(30, 40), new_tokens=3, walk_len=30,
               overrides=dataclasses.asdict(tiny))
    assert chip_smoke.SMALLTHINKER["config"] == "ExaoneMoeConfig.smallthinker"
    rc = chip_smoke.smoke(chip_smoke.run_hybrid, jax.devices()[:1], geo)
    records = [json.loads(line)
               for line in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and records[-1]["ok"] is True
    assert records[0]["window_layers"] == 1 and records[0]["cache_layers"] == 1
    assert records[1]["trace_counts"] == {"decode": 1, "prefill": 1}
    assert records[1]["moe_pairs_held"] > 0 == records[1]["moe_dropped_pairs"]
    assert records[1]["prefill_rows_extra"] > 0
    assert records[2]["prefill_rel"] < 1e-4 and records[2]["decode_rel"] < 1e-4
    assert records[2]["prefill_routed_apart"] == [] \
        == records[2]["decode_routed_apart"]
    assert [r.get("phase") for r in records].count("numeric") == 1


def test_lfm2_moe_phase_passes_at_tiny(capsys, restore_compile_cache_config):
    """The same phase over the LFM2-MoE block (the EXAONE walk with a conv
    operator) at tiny float32 sizes: six conv layers whose whole state is a
    window a slot, the one-token update against the chunk shape, a slot's
    rows chained through the window and, dealt two a step against one a
    step, the same to the bit."""
    import dataclasses

    from triton_distributed_tpu.models.config import Lfm2MoeConfig

    geo = dict(chip_smoke.LFM2_MOE, interpret=None, paged_attn="gather",
               n_slots=6, block_size=4, prefill_chunk=8, n_requests=3,
               prompt_range=(10, 20), new_tokens=3, walk_len=10,
               overrides=dataclasses.asdict(Lfm2MoeConfig.tiny()))
    assert chip_smoke.LFM2_MOE["overrides"]["layer_types"].count("conv") == 5
    rc = chip_smoke.smoke(chip_smoke.run_hybrid, jax.devices()[:1], geo)
    records = [json.loads(line)
               for line in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and records[-1]["ok"] is True
    assert records[0]["state_layers"] == 6 and records[0]["cache_layers"] == 2
    assert records[0]["slot_state_bytes"] == 6 * 6 * 2 * 64 * 4
    assert records[1]["trace_counts"] == {"decode": 1, "prefill": 1}
    assert records[1]["conv_states_reset"] == 3
    assert "ssm_states_reset" not in records[1]
    assert records[1]["moe_pairs_held"] > 0 == records[1]["moe_dropped_pairs"]
    assert records[1]["prefill_rows_extra"] > 0     # a slot's rows chained
    assert records[2]["prefill_rel"] < 1e-4 and records[2]["decode_rel"] < 1e-4
    assert records[2]["prefill_routed_apart"] == [] \
        == records[2]["decode_routed_apart"]
    assert "2 a step vs one a step" in records[3]["compared"]
    assert records[3]["prefill_rel"] == 0 == records[3]["decode_rel"]


def _routing(margin, experts=(4, 9, 2), base=0.5):
    """One layer's record for three prompts: prompt 0's router ``margin``
    apart at its top-2 boundary and choosing ``experts[:2]``, the others'
    boundaries wide."""
    scores = np.tile(np.float32([0.9, 0.6, 0.3]), (3, 1))
    scores[0] = [0.9, base, base - margin]
    chosen = np.tile(np.int32([1, 5, 7]), (3, 1))
    chosen[0] = experts
    return {0: (scores, chosen)}


@pytest.mark.parametrize("case,off,got,ref,tie,ok", [
    ("equal logits, no record", 0, None, None, None, True),
    ("one prompt apart, no record", 1, None, None, None, False),
    ("apart, routed alike", 1, _routing(1e-5), _routing(1e-5), None, False),
    ("apart, routed apart at a tie", 1,
     _routing(1e-5), _routing(2e-5, (4, 2, 9)), True, True),
    ("apart, one side's margin wide", 1,
     _routing(1e-5), _routing(0.05, (4, 2, 9)), False, False),
    ("apart, margins small but other scores", 1,
     _routing(1e-5), _routing(2e-5, (4, 2, 9), base=0.4), False, False),
    ("two apart, one tie", 2,
     _routing(1e-5), _routing(2e-5, (4, 2, 9)), True, False),
])
def test_only_a_prompt_routed_apart_at_a_tie_is_left_out(
        capsys, case, off, got, ref, tie, ok):
    """``compare_logits`` holds every prompt to the tolerance; with both
    sides' routers on record, a prompt is left out only where its token was
    routed to other experts, both margins are under ``ROUTER_TIE`` and the
    two routers saw the same scores."""
    logits = np.linspace(-1.0, 1.0, 3 * 8, dtype=np.float32).reshape(3, 8)
    moved = logits.copy()
    moved[:off] += 0.5
    sides = [(moved, moved) + ((r, r),) * (r is not None)
             for moved, r in ((moved, got), (logits, ref))]
    if ok:
        chip_smoke.compare_logits(case, *sides, 0.1)
    else:
        with pytest.raises(chip_smoke.SmokeFailure, match="prefill logits"):
            chip_smoke.compare_logits(case, *sides, 0.1)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["prefill_rel"] == pytest.approx(0.5 if off else 0.0)
    assert len(line["prefill_rel_by_prompt"]) == 3
    if got is not None:
        assert [(d["prompt"], d["layer"], d["tie"])
                for d in line["decode_routed_apart"]] == (
            [] if tie is None else [(0, 0, tie)])


def test_the_routers_record_is_what_route_chose():
    """``routing_recorded``: the experts it hands the host are the ones
    ``HeldExpertsMoE.route`` chose, in the step's own order, with the next
    best beside them; closed, the layer is its own again."""
    import jax.numpy as jnp

    from triton_distributed_tpu.layers.moe_mlp import HeldExpertsMoE

    moe = HeldExpertsMoE(d_model=16, d_ff=16, n_experts=8, topk=2, n_held=4,
                         dtype=jnp.float32, activation="relu2")
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    params = {"router": jax.random.normal(k[0], (16, 8)),
              "bias": 0.1 * jax.random.normal(k[1], (8,)),
              "w_up": jax.random.normal(k[2], (4, 4, 16, 16)),  # by layer
              "w_down": jax.random.normal(k[3], (4, 4, 16, 16))}
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 16))
    inner = HeldExpertsMoE.routed
    with chip_smoke.routing_recorded() as at:
        y, _ = jax.jit(lambda p, x: moe.routed(p, x, layer_idx=3))(params, x)
        (scores, experts), = at([0, 5, 15]).values()
        assert list(at([0])) == [3]
    assert HeldExpertsMoE.routed is inner
    _, ids = moe.route(params["router"], params["bias"], x)
    np.testing.assert_array_equal(experts[:, :2], np.asarray(ids)[[0, 5, 15]])
    assert experts.shape == (3, 3) and (np.diff(scores, axis=1) <= 0).all()
    np.testing.assert_allclose(y, moe.routed(params, x, layer_idx=3)[0],
                               rtol=1e-6, atol=1e-6)


def test_forced_step_exception_fails_the_smoke(
        monkeypatch, capsys, restore_compile_cache_config):
    """A step that raises at run time is absorbed by the replica error
    boundary (a quarantined replica, failed requests, no exception) — the
    smoke must turn that into a non-zero exit and no ``ok`` line."""
    def boom(self, live):
        raise RuntimeError("forced decode failure")

    monkeypatch.setattr(_be.BatchEngine, "_run_decode", boom)
    rc = chip_smoke.smoke(chip_smoke.run_one_chip, jax.devices()[:1], TINY)
    out, err = capsys.readouterr()
    assert rc == 1
    assert '"ok"' not in out
    assert "forced decode failure" in err   # first recorded exception shown


def test_main_refuses_a_platform_that_is_not_a_tpu(capsys):
    rc = chip_smoke.main([])
    out, err = capsys.readouterr()
    assert rc != 0 and out == "" and "TPU" in err
    assert chip_smoke.main(["--chips", "4"]) != 0


# -- one compile-cache rule ---------------------------------------------------


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls without applying them."""
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    return calls


def test_cache_dir_from_environment_sets_none_in_code(monkeypatch,
                                                      config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    assert aot.enable_xla_compilation_cache() == "/some/where"
    assert "jax_compilation_cache_dir" not in config_updates


def test_cache_dir_default_is_fixed_and_inside_the_checkout(monkeypatch,
                                                            config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = aot.enable_xla_compilation_cache()
    assert config_updates["jax_compilation_cache_dir"] == first
    assert aot.enable_xla_compilation_cache() == first   # equal across calls
    assert first == os.path.join(_REPO, ".cache", "jax")
    assert not first.startswith(os.path.expanduser("~") + os.sep) \
        or _REPO.startswith(os.path.expanduser("~"))


def test_tuning_and_executable_caches_default_beside_it(monkeypatch):
    monkeypatch.delenv("TDT_AUTOTUNE_CACHE", raising=False)
    monkeypatch.delenv("TDT_AOT_CACHE", raising=False)
    root = os.path.join(_REPO, ".cache")
    assert autotuner._cache_path() == os.path.join(root, "autotune.json")
    assert aot.AOTExecutableCache().cache_dir == os.path.join(root, "aot")
    monkeypatch.setenv("TDT_AUTOTUNE_CACHE", "/x/a.json")
    monkeypatch.setenv("TDT_AOT_CACHE", "/x/aot")
    assert autotuner._cache_path() == "/x/a.json"
    assert aot.AOTExecutableCache().cache_dir == "/x/aot"


# -- errors that raise instead of being absorbed ------------------------------


class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.fixture
def fresh_hw():
    perf_model.detect_hardware.cache_clear()
    yield
    perf_model.detect_hardware.cache_clear()


def test_detect_hardware_raises_on_an_unknown_tpu_kind(monkeypatch, fresh_hw):
    monkeypatch.setattr(perf_model.jax, "devices",
                        lambda *a: [_FakeDevice("tpu", "TPU v9x")])
    with pytest.raises(ValueError, match="TPU v9x"):
        perf_model.detect_hardware()
    with pytest.raises(ValueError, match="TPU v9x"):
        perf_model.peak_bf16_tflops()
    assert perf_model.peak_bf16_tflops(default=1000.0) == 1000.0


def test_detect_hardware_known_tpu_cpu_and_dead_backend(monkeypatch,
                                                        fresh_hw):
    monkeypatch.setattr(perf_model.jax, "devices",
                        lambda *a: [_FakeDevice("tpu", "TPU v5 lite")])
    assert perf_model.detect_hardware().name == "v5e"
    perf_model.detect_hardware.cache_clear()
    monkeypatch.setattr(perf_model.jax, "devices",
                        lambda *a: [_FakeDevice("cpu", "cpu")])
    assert perf_model.detect_hardware().name == "v5e"   # modelling choice

    def dead(*a):
        raise RuntimeError("backend failed to start")

    perf_model.detect_hardware.cache_clear()
    monkeypatch.setattr(perf_model.jax, "devices", dead)
    with pytest.raises(RuntimeError, match="failed to start"):
        perf_model.detect_hardware()


def test_autotuner_raises_when_every_candidate_fails_to_build(
        caplog, monkeypatch, tmp_path):
    monkeypatch.setenv("TDT_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    autotuner.clear_cache()
    autotuner._logged_build_failures.clear()

    def never(cfg):
        raise ValueError(f"Mosaic refuses {cfg}")

    tuner = autotuner.ContextualAutotuner(
        "smoke_all_bad", [(8, 1), (4, 1)],
        multi_timer=lambda thunks: [1.0 for _ in thunks])
    with pytest.raises(RuntimeError, match=r"all 2 candidate.*\(8, 1\)"):
        tuner.tune(never, "ctx")

    # One failing candidate just loses; its exception text is logged once.
    def one_bad(cfg):
        if cfg == (8, 1):
            raise ValueError("Mosaic refuses (8, 1)")
        return lambda n: jnp.zeros(())

    tuner = autotuner.ContextualAutotuner(
        "smoke_one_bad", [(8, 1), (4, 1)],
        multi_timer=lambda thunks: [float("inf") if t is None else 1.0
                                    for t in thunks])
    caplog.clear()
    with caplog.at_level("WARNING"):
        assert tuner.tune(one_bad, "ctx") == (4, 1)
        autotuner.clear_cache()
        assert tuner.tune(one_bad, "ctx") == (4, 1)
    assert sum("Mosaic refuses (8, 1)" in r.getMessage()
               for r in caplog.records) == 1
    autotuner.clear_cache()


@pytest.fixture(scope="module")
def tiny_engine():
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    return Engine(ModelConfig.from_name("tiny"), mesh=mesh, mode="xla",
                  block_n=8)


def _fleet(engine):
    return Fleet.build(engine, n_replicas=1, n_slots=2, n_blocks=16,
                       block_size=4, prefill_chunk=8)


@pytest.mark.parametrize("message, raised", [
    ("Mosaic failed to compile TPU kernel", _be.StepBuildError),
    ("RESOURCE_EXHAUSTED: out of memory allocating 4.0G", RuntimeError),
])
def test_build_and_resource_errors_pass_the_replica_boundary(
        tiny_engine, monkeypatch, message, raised):
    fleet = _fleet(tiny_engine)
    eng = fleet.replicas[0].engine

    def refuse(*a, **k):
        raise RuntimeError(message)

    if raised is RuntimeError:
        # Not a first call: only the resource text carries it through.
        eng._steps_built.update({"engine.prefill", "engine.decode"})
    monkeypatch.setattr(eng, "_mixed_step", refuse)
    fleet.submit([1, 2, 3, 4, 5], 2)
    with pytest.raises(raised, match=message.split(":")[0]):
        fleet.run(max_steps=20)
    assert fleet.replicas[0].state == HEALTHY
    assert not fleet.metrics.as_dict().get("replica_step_failures")


def test_a_runtime_step_error_is_still_a_replica_fault(tiny_engine,
                                                       monkeypatch):
    fleet = _fleet(tiny_engine)
    eng = fleet.replicas[0].engine
    fleet.submit([1, 2, 3, 4, 5], 4)
    assert fleet.step() and fleet.step()      # both steps built and run
    assert eng._steps_built == {"engine.prefill", "engine.decode"}

    def flaky(*a, **k):
        raise RuntimeError("transient device error")

    monkeypatch.setattr(eng, "_decode_step", flaky)
    fleet.step()                              # absorbed, not raised
    assert fleet.metrics.as_dict()["replica_step_failures"] == 1
    assert fleet.replicas[0].state != HEALTHY


def test_quantized_pool_is_refused_on_a_tpu_backend(monkeypatch):
    cfg = ModelConfig.from_name("tiny")
    monkeypatch.setattr(_kv, "on_tpu", lambda: True)
    for kv_dtype in ("int8", "fp8"):
        with pytest.raises(NotImplementedError,
                           match="does not compile for the chip"):
            _kv.KVPool(cfg, n_blocks=8, block_size=4, kv_dtype=kv_dtype)
    _kv.KVPool(cfg, n_blocks=8, block_size=4)      # model dtype: fine
    monkeypatch.setattr(_kv, "on_tpu", lambda: False)
    assert _kv.KVPool(cfg, n_blocks=8, block_size=4,
                      kv_dtype="int8").kv_quant     # interpreter: allowed


# -- the pool is born sharded -------------------------------------------------


def test_kv_pool_arrays_are_allocated_in_their_sharded_layout(mesh8,
                                                              monkeypatch):
    from jax._src.core import trace_state_clean
    from jax.sharding import NamedSharding

    from triton_distributed_tpu.models.kv_cache import KVCache

    real_zeros = jnp.zeros

    def zeros_only_under_a_trace(*a, **k):
        assert not trace_state_clean(), "whole arena built eagerly"
        return real_zeros(*a, **k)

    def no_device_put(*a, **k):
        raise AssertionError("arena re-laid out with device_put")

    monkeypatch.setattr(_kv.jnp, "zeros", zeros_only_under_a_trace)
    monkeypatch.setattr(_kv.jax, "device_put", no_device_put)
    _kv._zeros_fn.cache_clear()
    cfg = ModelConfig.from_name("tiny")          # 8 kv heads over tp=8
    pool = _kv.KVPool(cfg, n_blocks=6, block_size=4, mesh=mesh8,
                      kv_dtype="int8")
    st = pool.state
    # the head dim of the contiguous cache's spec, one position further
    # right: a block's two planes sit between the block and its lines
    want = NamedSharding(mesh8, _kv.PartitionSpec(
        None, None, None, *KVCache.spec("tp")[0][2:]))
    assert st.kv.sharding.is_equivalent_to(want, st.kv.ndim)
    assert {s.data.shape for s in st.kv.addressable_shards} == {
        (cfg.n_layers, 6, 2, 4, 1, cfg.head_dim)}
    assert not np.asarray(st.kv).any()
    want_s = NamedSharding(mesh8, _kv.PartitionSpec(
        None, None, None, *KVCache.scale_spec("tp")[2:]))
    assert st.kv_scale.dtype == jnp.float32
    assert st.kv_scale.sharding.is_equivalent_to(want_s, st.kv_scale.ndim)
    _kv._zeros_fn.cache_clear()


def test_cache_dir_helper_is_fixed_and_in_checkout():
    assert _platform.cache_dir("jax") == os.path.join(_REPO, ".cache", "jax")
    assert _platform.cache_dir() == os.path.join(_REPO, ".cache")


def test_evabyte_phase_passes_at_tiny(capsys, restore_compile_cache_config):
    """The same phase over the EvaByte block (a class of its own: EVA
    attention in every layer) at tiny float32 sizes: a window of 32 in
    chunks of 4, the walk past two window boundaries, so that the chunk
    shape and the decode shape each pool the summaries the other reads; the
    counts of rows appended (the ring's, a token a layer) and of summaries
    written add up."""
    import dataclasses

    from triton_distributed_tpu.models.config import EvaByteConfig

    geo = dict(chip_smoke.EVABYTE, interpret=None, paged_attn="gather",
               n_slots=6, block_size=4, prefill_chunk=8, n_requests=3,
               prompt_range=(80, 100), new_tokens=3, walk_len=75,
               overrides=dataclasses.asdict(EvaByteConfig.tiny()))
    assert chip_smoke.EVABYTE["overrides"] == {"n_layers": 2}
    assert chip_smoke.EVABYTE["walk_len"] > EvaByteConfig().window
    rc = chip_smoke.smoke(chip_smoke.run_hybrid, jax.devices()[:1], geo)
    records = [json.loads(line)
               for line in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and records[-1]["ok"] is True
    assert records[0]["state_layers"] == 0
    assert records[0]["cache_layers"] == records[0]["window_layers"] == 3
    assert records[0]["window"]["window"] == 32
    assert records[1]["trace_counts"] == {"decode": 1, "prefill": 1}
    assert records[1]["eva_summaries_written"] > 0
    assert records[1]["eva_windows_opened"] >= 3 * 2   # 80+ tokens: 32, 64
    assert records[1]["prefill_rows_extra"] > 0
    assert records[2]["prefill_rel"] < 1e-4 and records[2]["decode_rel"] < 1e-4
