"""The whole run at a tiny size on the CPU, through the tests' entry
(``run_cell(allow_cpu=True)``; the command itself refuses a CPU), from files
that only this test adds: a configuration, two mixes, a cell file and a
per-layer metric reader, none of which any file of the harness names."""

import json
import sys
import textwrap

import pytest

from perfbench import core

TINY = {
    "source": "test", "reduced": [], "chips": 1,
    "vocab_size": 4096, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 8,
    "intermediate_size": 128, "rope_theta": 10000, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "max_position_embeddings": 96,
    "torch_dtype": "float32", "qk_norm": True,
    "serve": {"mesh": {"tp": 1},
              "engine": {"mode": "dist", "block_n": 8, "interpret": None},
              "fleet": {"n_replicas": 1, "n_slots": 4, "block_size": 4,
                        "prefill_chunk": 8, "n_blocks": 128,
                        "paged_attn": "fused"}},
}
LENGTHS = {"prompt": {"median": 12, "sigma": 0.5, "lo": 4, "hi": 30},
           "output": {"median": 24, "sigma": 0.3, "lo": 16, "hi": 40}}
BENCH = {
    "command": ["python3", "perfbench/run.py"], "paths": ["perfbench"],
    "run_seconds": 2,
    "configs": [{"name": "tiny", "source": "test", "reduced": [], "why": "t",
                 "file": "perfbench/configs/tiny.json"}],
    "workloads": [
        {"name": "tiny.open", "config": "tiny", "traffic": "open",
         "chips": 1, "why": "t"},
        {"name": "tiny.closed", "config": "tiny", "traffic": "closed",
         "chips": 1, "why": "t"}],
    "end_to_end": [
        {"name": "ttft_mean_ms", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock", "workloads": ["tiny.open"]},
        {"name": "itl_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock"},
        {"name": "out_tokens_per_s", "unit": "tokens/s", "better": "higher",
         "bound": 0.1, "source": "host_clock"},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
         "source": "host_clock"}],
    "per_layer": [
        {"name": "decode_step_ms.closed", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "compiled step",
         "moves": "itl_p95_ms"},
        {"name": "steps_counted", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "compiled step",
         "moves": "out_tokens_per_s"},
        {"name": "paged_attn_device_share", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "kernels",
         "moves": "itl_p95_ms"}],
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout-shaped directory that holds only new files."""
    root = tmp_path_factory.mktemp("bench_root")
    for sub in ("configs", "traffic", "cells", "layer_metrics"):
        (root / "perfbench" / sub).mkdir(parents=True)
    (root / "BENCHMARK.json").write_text(json.dumps(BENCH))
    (root / "perfbench/configs/tiny.json").write_text(json.dumps(TINY))
    (root / "perfbench/traffic/open.json").write_text(json.dumps(
        {"kind": "open_poisson", "drain_limit_s": 30, **LENGTHS}))
    (root / "perfbench/traffic/closed.json").write_text(json.dumps(
        {"kind": "closed_loop", "clients": "n_slots", "rounds": 4,
         "drain_limit_s": 30, "prompt": LENGTHS["prompt"],
         "output": {"median": 8, "sigma": 0.3, "lo": 4, "hi": 12}}))
    # float32 has no limits of its own in the harness: the cells state them
    limits = {"gap_max": 1e-3, "gap_mean": 1e-5}
    (root / "perfbench/cells/tiny.open.json").write_text(json.dumps(
        {"traffic": {"rate_rps": 4.0, "standing": {
            "token_s": 0.02, "prefill_tokens_per_s": 200}},
         "limits": limits}))
    (root / "perfbench/cells/tiny.closed.json").write_text(json.dumps(
        {"limits": limits}))
    (root / "perfbench/layer_metrics/steps_counted.py").write_text(
        textwrap.dedent("""
        def read(rec):
            return rec.counters["decode_steps"] + rec.counters["prefill_steps"]
        """))
    from perfbench import layer_metrics

    layer_metrics.__path__.append(str(root / "perfbench/layer_metrics"))
    yield str(root)
    layer_metrics.__path__.pop()
    sys.modules.pop("perfbench.layer_metrics.steps_counted", None)


@pytest.fixture
def restore_compile_cache_config():
    """A run turns the persistent compile cache on for its process."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    prior = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in prior.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_the_command_refuses_a_cpu(capsys):
    rc = core.main(["--workload", "qwen3-1.7b.chat", "--seed", "1",
                    "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2 and "needs 1 TPU chip" in out.err
    assert '"correct"' not in out.out


def test_a_cell_its_rate_and_its_metrics_come_from_files(root):
    spec = core.load_cell("tiny.open", root)
    assert spec["traffic"]["rate_rps"] == 4.0          # the cell file's
    assert spec["traffic"]["kind"] == "open_poisson"   # the mix's
    assert [m["name"] for m in spec["end_to_end"]] == [
        "ttft_mean_ms", "itl_p95_ms", "out_tokens_per_s", "setup_s"]
    closed = core.load_cell("tiny.closed", root)
    assert "ttft_mean_ms" not in [m["name"] for m in closed["end_to_end"]]
    with pytest.raises(core.BenchFailure):
        core.load_cell("tiny.none", root)


def test_open_loop_run_reports_the_contract_keys_and_fails_the_control(
        root, capsys, restore_compile_cache_config):
    """One run: the result object, the numbers compared beside their limits
    on an earlier line, and the float8 control (the reference in the
    program's place, one precision step down) coming out NOT correct while
    the program's own tokens pass."""
    res = core.run_cell("tiny.open", 2 ** 31 + 11, 2.0, 0, root=root,
                        allow_cpu=True, control=True)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert set(res) == {"correct", "attempted", "failed", "metrics", "device",
                        "check"}
    assert res["correct"] is True and res["failed"] == 0
    standing = next(x for x in lines if x["phase"] == "standing")
    window = next(x for x in lines if x["phase"] == "window")
    # the window opened on requests caught part-way, prefilled in set-up
    assert 1 <= standing["requests"] == window["standing"]
    assert res["attempted"] == 8 + standing["requests"]   # 4 req/s x 2 s
    assert set(res["metrics"]) == {"ttft_mean_ms", "itl_p95_ms",
                                   "out_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    verdict = next(x for x in lines if x["phase"] == "correct")
    assert verdict["compared"]["gap_max"] <= verdict["limits"]["gap_max"]
    assert verdict["tokens"] >= 48
    assert verdict["control_correct"] is False
    assert verdict["control"]["gap_max"] > 3 * verdict["limits"]["gap_max"]
    mem = next(x for x in lines if x["phase"] == "memory")
    assert mem["compilations_in_window"] == 0
    assert mem["trace_counts"] == [{"decode": 1, "prefill": 1}]


def test_closed_loop_run_with_a_token_altered_where_it_is_produced(
        root, capsys, restore_compile_cache_config):
    """The rest of a run with the timed path broken underneath: both
    compiled steps' tokens are shifted by one before the engine records them.
    Everything still runs, finishes and counts; ``correct`` is false."""
    def tamper(served):
        be, vocab = served.be, served.engine.config.vocab_size

        def shifted(step):
            def call(*args, **kw):
                out = step(*args, **kw)
                return ((out[0] + 1) % vocab,) + out[1:]
            return call

        be._decode_step = shifted(be._decode_step)
        be._mixed_step = shifted(be._mixed_step)

    res = core.run_cell("tiny.closed", 5, 3.0, 0, root=root, allow_cpu=True,
                        tamper=tamper)
    assert res["correct"] is False
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "ttft_mean_ms" not in res["metrics"]
    assert res["metrics"]["out_tokens_per_s"]["value"] > 0


def test_per_layer_readers_are_found_by_name_and_may_find_nothing(root):
    """A traced run's metrics are the cell's per-layer metrics: a reader
    added as a file is used, and one that finds no trace is left out."""
    import importlib

    spec = core.load_cell("tiny.closed", root)
    rec = core.Records(
        t_open=0.0, t_close=1.0,
        t_end=1.0, setup_s=1.0, tracked=[], kv_live=[],
        steps=[(0.1, 0.2, "decode", 2, 2, 30)],
        counters={"decode_steps": 1.0, "prefill_steps": 2.0},
        queue_wait_s=[], sizes=None, n_slots=4, n_chips=1,
        device_kind="cpu")
    got = {}
    for m in spec["per_layer"]:
        mod = importlib.import_module(core.reader_module("layer_metrics",
                                                         m["name"]))
        got[m["name"]] = mod.read(rec)
    # "<name>.<suffix>" is read by the reader <name>
    assert got == {"decode_step_ms.closed": pytest.approx(100.0),
                   "steps_counted": 3.0, "paged_attn_device_share": None}
