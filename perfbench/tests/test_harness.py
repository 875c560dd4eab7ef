"""The whole run at a tiny size on the CPU, through the tests' entry
(``run_cell(allow_cpu=True)``; the command itself refuses a CPU), from files
that only this test adds: configurations, two mixes, cell files, per-layer
metric readers and a model family, none of which any file of the harness
names."""

import json
import os
import sys
import textwrap

import pytest

from perfbench import core

TINY = {
    "family": "qwen3", "source": "test", "reduced": [], "chips": 1,
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
# A second architecture, of the family below: no q/k norm, untied head, and a
# first layer whose feed-forward is narrower than the others'.
STEP = {
    "family": "narrowfirst", "source": "test", "reduced": [], "chips": 1,
    "vocab": 4096, "width": 64, "layers": 3, "heads": 8, "kv_heads": 4,
    "head_width": 8, "ff": 128, "ff_first": 64, "theta": 10000, "eps": 1e-6,
    "positions": 96, "dtype": "float32",
    # the gather path (the fused kernel's oracle): a millisecond a step here,
    # where the interpreted kernel takes a third of a second a layer
    "serve": {**TINY["serve"],
              "fleet": {**TINY["serve"]["fleet"], "paged_attn": "gather"}},
}
NARROWFIRST = '''
"""A family that only a test adds: a decoder that is not the harness's own.
No per-head norm on q and k, an untied head, and layer 0's feed-forward is
narrower than the other layers', so a layer's weights depend on its index.
The program serves it through its dense model class with the q/k norm off
and layer 0's gate, up and down padded with zeros to the common width
(silu(0) * 0 adds exactly nothing); the reference computes the narrow layer
as it is."""

import dataclasses
import functools

import jax
import jax.numpy as jnp

from perfbench.peaks import itemsize
from perfbench.reference import attention, linear, rms_norm, rope
from perfbench.weights import keys, norm_weight, randw


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab_size: int
    d_model: int
    n_layers: int
    max_length: int
    dtype: str
    heads: int
    kv_heads: int
    dh: int
    ff: int
    ff_first: int
    theta: float
    eps: float


def sizes(cfg):
    return Sizes(vocab_size=cfg["vocab"], d_model=cfg["width"],
                 n_layers=cfg["layers"], max_length=cfg["positions"],
                 dtype=cfg["dtype"], heads=cfg["heads"],
                 kv_heads=cfg["kv_heads"], dh=cfg["head_width"],
                 ff=cfg["ff"], ff_first=cfg["ff_first"],
                 theta=float(cfg["theta"]), eps=float(cfg["eps"]))


def ff_of(m, layer_index):
    return m.ff_first if layer_index == 0 else m.ff


def plain_layer(m, ff, key):
    dt, d = jnp.dtype(m.dtype), m.d_model
    ks = jax.random.split(key, 9)
    return {"wq": randw(ks[0], (d, m.heads * m.dh), d, dt),
            "wk": randw(ks[1], (d, m.kv_heads * m.dh), d, dt),
            "wv": randw(ks[2], (d, m.kv_heads * m.dh), d, dt),
            "wo": randw(ks[3], (m.heads * m.dh, d), m.heads * m.dh, dt),
            "wg": randw(ks[4], (d, ff), d, dt),
            "wu": randw(ks[5], (d, ff), d, dt),
            "wd": randw(ks[6], (ff, d), ff, dt),
            "norm1": norm_weight(ks[7], (d,)),
            "norm2": norm_weight(ks[8], (d,))}


_layer_weights = jax.jit(plain_layer, static_argnums=(0, 1))


def layer_weights(m, key, layer_index):
    return _layer_weights(m, ff_of(m, layer_index), key)


def plain_globals(m, key):
    dt = jnp.dtype(m.dtype)
    ks = jax.random.split(key, 3)
    return {"embed": randw(ks[0], (m.vocab_size, m.d_model), m.d_model, dt),
            "final_norm": norm_weight(ks[1], (m.d_model,)),
            "lm_head": randw(ks[2], (m.d_model, m.vocab_size), m.d_model, dt)}


global_weights = jax.jit(plain_globals, static_argnums=0)


def head_weights(m, g):
    return {"final_norm": g["final_norm"], "head": g["lm_head"], "eps": m.eps}


def program(cfg, m, seed, mesh, engine_kwargs):
    from jax.sharding import NamedSharding

    from triton_distributed_tpu.models.config import ModelConfig
    from triton_distributed_tpu.models.qwen import Qwen3

    mcfg = ModelConfig(
        model_name=cfg["source"], vocab_size=m.vocab_size, d_model=m.d_model,
        n_layers=m.n_layers, n_heads=m.heads, n_kv_heads=m.kv_heads,
        head_dim=m.dh, d_ff=m.ff, rope_theta=m.theta, rms_eps=m.eps,
        tie_embeddings=False, qk_norm=False, max_length=m.max_length,
        dtype=jnp.dtype(m.dtype))
    model = Qwen3(mcfg, block_n=engine_kwargs.get("block_n", 256))
    world = mesh.shape[model.axis]
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             model.param_specs())
    pad = m.ff - m.ff_first

    @functools.partial(jax.jit, out_shardings=shardings)
    def make(gkey, lkeys):
        first = plain_layer(m, m.ff_first, lkeys[0])
        first["wg"] = jnp.pad(first["wg"], ((0, 0), (0, pad)))
        first["wu"] = jnp.pad(first["wu"], ((0, 0), (0, pad)))
        first["wd"] = jnp.pad(first["wd"], ((0, pad), (0, 0)))
        rest = jax.vmap(functools.partial(plain_layer, m, m.ff))(lkeys[1:])
        lw = jax.tree.map(lambda a, b: jnp.concatenate([a[None], b]),
                          first, rest)
        g = plain_globals(m, gkey)
        return {
            "embed": g["embed"], "final_norm": g["final_norm"],
            "lm_head": g["lm_head"],
            "layers": {
                "input_norm": lw["norm1"], "post_norm": lw["norm2"],
                "attn": {"w_qkv": jax.vmap(lambda q, k, v: model.attn.pack_qkv(
                    q, k, v, world))(lw["wq"], lw["wk"], lw["wv"]),
                         "w_o": lw["wo"]},
                "mlp": {"w_gate_up": jax.vmap(
                    lambda a, b: model.mlp.interleave_gate_up(a, b, world))(
                        lw["wg"], lw["wu"]),
                        "w_down": lw["wd"]}}}

    return mcfg, make(*keys(seed, m.n_layers))


@functools.partial(jax.jit, static_argnames=("m", "ff", "precision"))
def _layer_forward(h, lw, *, m, ff, precision):
    assert lw["wg"].shape == (m.d_model, ff), "the layer's index decides"
    S = h.shape[0]
    pos = jnp.arange(S)
    x = rms_norm(h, lw["norm1"], m.eps)
    q = linear(x, lw["wq"], precision).reshape(S, m.heads, m.dh)
    k = linear(x, lw["wk"], precision).reshape(S, m.kv_heads, m.dh)
    v = linear(x, lw["wv"], precision).reshape(S, m.kv_heads, m.dh)
    a = attention(rope(q, pos, m.theta), rope(k, pos, m.theta), v,
                  m.dh ** -0.5)
    h = h + linear(a, lw["wo"], precision)
    x = rms_norm(h, lw["norm2"], m.eps)
    act = jax.nn.silu(linear(x, lw["wg"], precision)) \\
        * linear(x, lw["wu"], precision)
    return h + linear(act, lw["wd"], precision)


def layer_forward(h, lw, m, layer_index, precision):
    return _layer_forward(h, lw, m=m, ff=ff_of(m, layer_index),
                          precision=precision)


def decode_step_min_bytes(m, context_lens):
    attn = m.d_model * (m.heads + 2 * m.kv_heads) * m.dh \\
        + m.heads * m.dh * m.d_model
    mlp = 3 * m.d_model * sum(ff_of(m, i) for i in range(m.n_layers))
    weights = m.n_layers * attn + mlp + m.d_model * m.vocab_size
    kv = 2 * m.n_layers * m.kv_heads * m.dh
    return (weights + kv * float(sum(context_lens))) * itemsize(m.dtype)
'''
LENGTHS = {"prompt": {"median": 12, "sigma": 0.5, "lo": 4, "hi": 30},
           "output": {"median": 24, "sigma": 0.3, "lo": 16, "hi": 40}}
BENCH = {
    "command": ["python3", "perfbench/run.py"], "paths": ["perfbench"],
    "run_seconds": 2,
    "configs": [{"name": "tiny", "source": "test", "reduced": [], "why": "t",
                 "file": "perfbench/configs/tiny.json"},
                {"name": "step", "source": "test", "reduced": [], "why": "t",
                 "file": "perfbench/configs/step.json"}],
    "workloads": [
        {"name": "tiny.open", "config": "tiny", "traffic": "open",
         "chips": 1, "why": "t"},
        {"name": "tiny.closed", "config": "tiny", "traffic": "closed",
         "chips": 1, "why": "t"},
        {"name": "step.open", "config": "step", "traffic": "open",
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
         "moves": "itl_p95_ms"},
        {"name": "floor_bytes", "unit": "bytes", "better": "lower",
         "source": "program_counter", "layer": "kernels",
         "moves": "out_tokens_per_s", "workloads": ["step.open"]}],
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout-shaped directory that holds only new files."""
    root = tmp_path_factory.mktemp("bench_root")
    for sub in ("configs", "traffic", "cells", "layer_metrics", "families"):
        (root / "perfbench" / sub).mkdir(parents=True)
    (root / "BENCHMARK.json").write_text(json.dumps(BENCH))
    (root / "perfbench/configs/tiny.json").write_text(json.dumps(TINY))
    (root / "perfbench/configs/step.json").write_text(json.dumps(STEP))
    (root / "perfbench/families/narrowfirst.py").write_text(NARROWFIRST)
    (root / "perfbench/traffic/open.json").write_text(json.dumps(
        {"kind": "open_poisson", "drain_limit_s": 30, **LENGTHS}))
    (root / "perfbench/traffic/closed.json").write_text(json.dumps(
        {"kind": "closed_loop", "clients": "n_slots", "rounds": 4,
         "drain_limit_s": 30, "prompt": LENGTHS["prompt"],
         "output": {"median": 8, "sigma": 0.3, "lo": 4, "hi": 12}}))
    # float32 has no limits of its own in the harness: the cells state them
    limits = {"gap_max": 1e-3, "gap_mean": 1e-5}
    rate = {"rate_rps": 4.0, "standing": {
        "token_s": 0.02, "prefill_tokens_per_s": 200}}
    (root / "perfbench/cells/tiny.open.json").write_text(json.dumps(
        {"traffic": rate, "limits": limits}))
    (root / "perfbench/cells/tiny.closed.json").write_text(json.dumps(
        {"limits": limits}))
    (root / "perfbench/cells/step.open.json").write_text(json.dumps(
        {"traffic": rate, "limits": limits, "sample": {"requests": 2}}))
    (root / "perfbench/layer_metrics/steps_counted.py").write_text(
        textwrap.dedent("""
        def read(rec):
            return rec.counters["decode_steps"] + rec.counters["prefill_steps"]
        """))
    (root / "perfbench/layer_metrics/floor_bytes.py").write_text(
        textwrap.dedent("""
        def read(rec):
            return rec.family.decode_step_min_bytes(
                rec.sizes, [s[5] for s in rec.steps if s[2] == "decode"])
        """))
    from perfbench import families, layer_metrics

    layer_metrics.__path__.append(str(root / "perfbench/layer_metrics"))
    families.__path__.append(str(root / "perfbench/families"))
    yield str(root)
    layer_metrics.__path__.pop()
    families.__path__.pop()
    for name in ("layer_metrics.steps_counted", "layer_metrics.floor_bytes",
                 "families.narrowfirst"):
        sys.modules.pop("perfbench." + name, None)


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
    # what the reference reads: the defaults, a cell file's "sample" over them
    assert spec["sample"] == closed["sample"] == core.SAMPLE
    assert core.load_cell("step.open", root)["sample"] == {
        **core.SAMPLE, "requests": 2}
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
    printed = capsys.readouterr()
    lines = [json.loads(x) for x in printed.out.splitlines()]
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device",
                         "check"]             # the numbers compared come last
    assert [x.split()[2::2] for x in printed.err.splitlines()[-2:]] == [
        ["gap_max", "limit"], ["gap_mean", "limit"]]
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


def test_a_family_no_harness_file_names_runs_from_new_files_alone(
        root, capsys, restore_compile_cache_config):
    """A configuration of another architecture is files only: its family
    (found by the ``family`` of its file), its configuration and its cell
    file. The whole run comes out correct against the family's own plain
    layers (layer 0 narrower than the rest), its float8 control does not,
    and the reference reads as many requests as the cell's file says."""
    import os

    from perfbench import families

    here = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "families")
    assert "narrowfirst" in families.known()
    assert not os.path.exists(os.path.join(here, "narrowfirst.py"))
    res = core.run_cell("step.open", 2 ** 31 + 5, 2.0, 0, root=root,
                        allow_cpu=True, control=True)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    build = next(x for x in lines if x["phase"] == "build")
    assert (build["n_layers"], build["d_model"], build["vocab"]) == (3, 64, 4096)
    assert res["correct"] is True and res["failed"] == 0
    verdict = res["check"]
    assert verdict["requests"] == 2 and verdict["tokens"] >= 32
    assert verdict["compared"]["gap_max"] <= verdict["limits"]["gap_max"]
    assert verdict["control_correct"] is False
    assert verdict["control"]["gap_max"] > 3 * verdict["limits"]["gap_max"]
    assert res["metrics"]["out_tokens_per_s"]["value"] > 0


def test_a_reader_reaches_the_familys_counts_through_the_records(root):
    import importlib

    from perfbench import families

    spec = core.load_cell("step.open", root)
    family = families.load_family(spec["config"])
    sizes = family.sizes(spec["config"])
    rec = core.Records(
        t_open=0.0, t_close=1.0, t_end=1.0, setup_s=1.0, tracked=[],
        kv_live=[], steps=[(0.1, 0.2, "decode", 2, 2, 30)], counters={},
        queue_wait_s=[], sizes=sizes, family=family, n_slots=4, n_chips=1,
        device_kind="cpu")
    assert "floor_bytes" in [m["name"] for m in spec["per_layer"]]
    mod = importlib.import_module(core.reader_module("layer_metrics",
                                                     "floor_bytes"))
    # 3 layers of attention (64 x (8 + 2 x 4) x 8 + 8 x 8 x 64), feed-forward
    # widths 64, 128, 128, the head, and 2 x 3 x 4 x 8 a token of context
    params = 3 * (64 * 16 * 8 + 64 * 64) + 3 * 64 * (64 + 128 + 128) \
        + 64 * 4096
    assert mod.read(rec) == (params + 192 * 30) * 4


def test_a_run_of_a_configuration_without_a_family_fails_with_the_list(root):
    import time

    spec = core.load_cell("tiny.closed", root)
    spec["config"] = {**spec["config"], "family": None}
    with pytest.raises(core.BenchFailure, match="narrowfirst.*qwen3"):
        core.set_up(spec, 1, t_start=time.monotonic(), allow_cpu=True)


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
        queue_wait_s=[], sizes=None, family=None, n_slots=4, n_chips=1,
        device_kind="cpu")
    got = {}
    for m in spec["per_layer"]:
        mod = importlib.import_module(core.reader_module("layer_metrics",
                                                         m["name"]))
        got[m["name"]] = mod.read(rec)
    # "<name>.<suffix>" is read by the reader <name>
    assert got == {"decode_step_ms.closed": pytest.approx(100.0),
                   "steps_counted": 3.0, "paged_attn_device_share": None}


# A hand-made list of finished requests: (prompt tokens, served tokens,
# standing). Index 2 is the longest; six of the twelve arrived in the window.
FINISHED = [(300, 2000, True), (200, 1500, True), (400, 3100, True),
            (150, 1100, False), (500, 1300, False), (250, 2400, True),
            (180, 1024, False), (350, 2900, True), (220, 1700, False),
            (128, 1250, False), (410, 3000, True), (333, 1111, False)]


def finished_list(only_standing=False):
    import types

    return [core.Tracked(types.SimpleNamespace(prompt=[0] * p),
                         types.SimpleNamespace(output=[0] * o, status="ok"),
                         0.0, 0.0, standing=standing)
            for p, o, standing in FINISHED if standing or not only_standing]


# What ``pick_sample`` returned for these seeds BEFORE it knew of arrivals
# (read from the parent's tree): three requests inside 9,000 tokens, and
# inside 6,000.
PICKED_BEFORE = {3: ([2, 0, 8], [2, 0]), 51: ([2, 7, 3], [2, 0]),
                 77: ([2, 6, 4], [2, 6, 3])}


@pytest.mark.parametrize("seed", sorted(PICKED_BEFORE))
def test_a_cell_that_states_no_arrived_reads_the_requests_it_read_before(seed):
    fin = finished_list()
    roomy, tight = PICKED_BEFORE[seed]
    assert core.pick_sample(fin, seed, 3, 9000) == roomy
    assert core.pick_sample(fin, seed, 3, 6000) == tight
    assert core.pick_sample(fin, seed, **core.SAMPLE) == roomy
    assert "arrived" not in core.SAMPLE


def test_a_sample_with_a_place_for_an_arrival_holds_one_after_the_longest():
    fin = finished_list()
    for seed in range(40):
        picked = core.pick_sample(fin, seed, 3, 9000, arrived=1)
        assert picked[0] == 2 and len(picked) == 3        # the longest first
        assert fin[picked[1]].standing is False           # then an arrival
        # the arrival is the one the seed's shuffle puts first, and the last
        # place goes on in that shuffle's order, as every place did before
        before = core.pick_sample(fin, seed, 3, 9000)
        first = next(i for i in core.pick_sample(fin, seed, len(fin), 10 ** 6)
                     [1:] if not fin[i].standing)
        assert picked[1] == first
        if not fin[before[1]].standing:
            assert picked == before
    # inside a budget that no second request fits after the arrival
    assert core.pick_sample(fin, 51, 3, 6000, arrived=1) == [2, 3, 6]
    assert core.pick_sample(fin, 3, 3, 6000, arrived=1) == [2, 8]
    # no arrival finished: the order it had before
    old = finished_list(only_standing=True)
    for seed in (3, 51, 77):
        assert core.pick_sample(old, seed, 3, 9000, arrived=1) \
            == core.pick_sample(old, seed, 3, 9000)
    assert core.pick_sample([], 3, 3, 9000, arrived=1) == []


def test_every_sample_the_rule_can_draw_is_walked_and_no_other():
    """``limits.every_sample`` is what a limit is held against: whatever
    ``pick_sample`` returns for a seed is among them, and over many seeds
    every one of a small set is met."""
    from perfbench import limits

    fin = finished_list()
    sizes = [p + o for p, o, _ in FINISHED]
    standing = [s for _, _, s in FINISHED]
    for requests, budget, arrived in ((3, 9000, 0), (3, 9000, 1),
                                      (3, 6000, 1), (2, 9000, 1),
                                      (4, 12000, 2), (1, 9000, 1)):
        allowed = {tuple(x) for x in limits.every_sample(
            sizes, standing, requests, budget, arrived)}
        met = {tuple(core.pick_sample(fin, seed, requests, budget, arrived))
               for seed in range(400)}
        assert met <= allowed
        if len(allowed) <= 10:
            assert met == allowed
    assert len(set(map(tuple, limits.every_sample(
        sizes, standing, 3, 9000, 1)))) == 6 * 10
    rows = [{"prompt_tokens": p, "tokens": o, "standing": s,
             "gap_max": 0.3 if i == 8 else 0.01,
             "gap_mean": 0.002 if i == 8 else 0.0001}
            for i, (p, o, s) in enumerate(FINISHED)]
    got = limits.worst_draws(rows, {"gap_max": 0.1, "gap_mean": 0.001},
                             {"requests": 3, "token_budget": 9000})
    # of the 100 ordered pairs that fit the budget after the longest, request
    # 8 comes first in 10 and second in 10; the other two dilute its mean
    assert (got["samples"], got["over_a_limit"]) == (100, 20)
    assert got["gap_max"] == 0.3 and 0.0004 < got["gap_mean"] < 0.001


def test_limits_all_finished_prints_a_row_a_request_and_fails_the_control(
        root, capsys, restore_compile_cache_config):
    """``limits.py --all-finished`` at a tiny size: the reference reads
    EVERY finished request, one row each with the population it belongs to
    and its own numbers, the pooled numbers stay what a run compares, and
    the float8 control still comes out not correct."""
    from perfbench import limits

    rc = limits.main(["--workload", "step.open", "--seeds", str(2 ** 31 + 7),
                      "--seconds", "2", "--control-seeds", "1",
                      "--all-finished"], root=root, allow_cpu=True)
    assert rc == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    window = next(x for x in lines if x["phase"] == "window")
    verdict = next(x for x in lines if x["phase"] == "correct")
    rows = [x for x in lines if x["phase"] == "request"]
    seed = next(x for x in lines if x["phase"] == "seed")
    assert len(rows) == window["finished"] == verdict["requests"] > 2
    assert all(isinstance(r["standing"], bool) for r in rows)
    assert sum(r["standing"] for r in rows) == window["standing"]
    assert sum(r["tokens"] for r in rows) == verdict["tokens"] == seed["tokens"]
    for k in ("gap_max", "gap_mean"):
        assert max(r[k] for r in rows) <= verdict["limits"][k]
        assert verdict["control"][k] > 3 * verdict["limits"][k]
        # the control's own reading of each request stands beside the sound
        # one (a request of 16 tokens may well pass alone: the pooled fails)
        assert max(r["control"][k] for r in rows) >= verdict["control"][k]
    assert verdict["compared"]["gap_max"] == max(r["gap_max"] for r in rows)
    assert limits.pooled(rows)["gap_mean"] == pytest.approx(
        verdict["compared"]["gap_mean"], rel=1e-9)
    assert seed["correct"] is True and verdict["control_correct"] is False
    pops = seed["populations"]
    assert pops["standing"]["requests"] == window["standing"]
    assert pops["standing"]["requests"] + pops["arrived"]["requests"] \
        == len(rows)
    summary = lines[-1]
    assert summary["phase"] == "limits"
    assert summary["gap_max"]["control_min"] > 3 * verdict["limits"]["gap_max"]
    alone = summary["single_request"]
    assert alone["standing"]["requests"] == window["standing"]
    assert max(p["gap_max"] for p in alone.values() if "gap_max" in p) \
        == verdict["compared"]["gap_max"]
    # the rows, saved, are what ``--draws`` walks without a chip
    path = os.path.join(root, "rows.jsonl")
    with open(path, "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)
    assert limits.main(["--workload", "step.open", "--draws", path],
                       root=root) == 0
    drawn = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [d["sample"].get("arrived") for d in drawn] == [0, None]
    assert all(d["over_a_limit"] == 0 and d["samples"] >= 1 for d in drawn)


def test_the_four_chip_cells_kept_readings_pass_its_limits_and_failed_the_old():
    """The rows its limits were set from (``readings/``, every finished
    request of each seed read on four chips): under the limits and the rule
    that stood before, some sample the rule could draw was not correct; under
    the cell's own, NO sample it can draw for any shuffle reads over either
    limit, each limit stands at twice the largest single request or more,
    and the float8 control fails each limit on every sample the two seeds
    it was read on can draw (one request alone, of 16 served tokens, reads
    next to nothing for it)."""
    from perfbench import limits

    cell = "qwen3-8b-tp4.reasoning"
    spec = core.load_cell(cell)
    assert spec["sample"]["arrived"] == 1
    by_seed = limits.read_rows(os.path.join(
        core.ROOT, "perfbench", "readings", cell + ".jsonl"))
    rows = [r for seed_rows in by_seed.values() for r in seed_rows]
    assert len(by_seed) >= 3
    assert sum(not r["standing"] for r in rows) >= 60
    old = {"gap_max": 0.1, "gap_mean": 0.001}
    before = [limits.worst_draws(r, old, {**spec["sample"], "arrived": 0})
              for r in by_seed.values()]
    assert any(d["over_a_limit"] for d in before)
    for seed_rows in by_seed.values():
        now = limits.worst_draws(seed_rows, spec["limits"], spec["sample"])
        assert now["samples"] > 0 and now["over_a_limit"] == 0
    for k, limit in spec["limits"].items():
        assert 2 * max(r[k] for r in rows) <= limit, k
    judged = [limits.worst_draws(r, spec["limits"], spec["sample"])
              for r in by_seed.values() if "control" in r[0]]
    assert len(judged) >= 2
    for d in judged:
        assert d["control_passes"] == 0
        assert all(d["control_least"][k] > limit
                   for k, limit in spec["limits"].items())
    # the issue's first guess would not have held either
    assert limits.worst_draws(by_seed[5100200001],
                              {"gap_max": 0.45, "gap_mean": 0.004},
                              spec["sample"])["over_a_limit"] > 0
