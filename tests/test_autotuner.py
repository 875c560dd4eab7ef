"""Contextual autotuner tests — analog of the reference's autotuner usage
(docs/autotuner.md): thunk-level tuning, cross-process vote, persistent
cache, decorator form."""

import functools
import json

import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.runtime import autotuner


@pytest.fixture(autouse=True)
def isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("TDT_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    autotuner.clear_cache()
    autotuner._pruned_counts.clear()
    yield
    autotuner.clear_cache()
    autotuner._pruned_counts.clear()


def test_tuner_picks_fastest_and_caches(monkeypatch):
    fake_ms = {1: 5.0, 2: 1.0, 3: 9.0}
    calls = []

    def fake_perf(thunk, **kw):
        return fake_ms[thunk()]

    monkeypatch.setattr(autotuner, "perf_thunk", fake_perf)
    tuner = autotuner.ContextualAutotuner("t", [1, 2, 3])

    def make_thunk(cfg):
        calls.append(cfg)
        return lambda: cfg

    assert tuner.tune(make_thunk, "ctx") == 2
    assert calls == [1, 2, 3]
    # Second call: memory cache, no re-timing.
    assert tuner.tune(make_thunk, "ctx") == 2
    assert calls == [1, 2, 3]
    # Different context re-tunes.
    assert tuner.tune(make_thunk, "ctx2") == 2
    assert calls == [1, 2, 3, 1, 2, 3]


def test_custom_timer_and_slope():
    """A custom per-candidate timer overrides perf_thunk, and slope_timer
    recovers per-iteration cost from a loop(n) callable with constant
    dispatch overhead added (the overhead must cancel in the slope)."""
    import time as _time

    tuner = autotuner.ContextualAutotuner(
        "t", ["a", "b"], timer=lambda loop: loop(1))
    assert tuner.tune(lambda c: (lambda n: 1.0 if c == "b" else 2.0),
                      "k1") == "b"

    def loop(n):  # 0.2ms/iter + 5ms constant "dispatch"
        _time.sleep(0.005 + n * 0.0002)
        return jnp.zeros(())

    ms = autotuner.slope_timer(loop, rounds=3)
    assert 0.1 < ms < 0.4, ms


def test_disk_cache_survives_memory_clear(monkeypatch, tmp_path):
    monkeypatch.setattr(autotuner, "perf_thunk",
                        lambda thunk, **kw: float(thunk()))
    tuner = autotuner.ContextualAutotuner("d", [7.0, 3.0, 5.0])
    assert tuner.tune(lambda c: (lambda: c), "k") == 3.0
    with open(tmp_path / "tune.json") as f:
        # Key embeds a digest of the candidate list (stored value is an
        # index; editing the candidates must invalidate stale indices).
        assert json.load(f) == {tuner._key("k"): 1}

    autotuner.clear_cache()  # memory only; disk remains
    timed = []

    def spy(thunk, **kw):
        timed.append(1)
        return float(thunk())

    monkeypatch.setattr(autotuner, "perf_thunk", spy)
    tuner2 = autotuner.ContextualAutotuner("d", [7.0, 3.0, 5.0])
    assert tuner2.tune(lambda c: (lambda: c), "k") == 3.0
    assert timed == []  # loaded from disk, nothing re-timed


def test_infeasible_configs_lose(monkeypatch):
    def fake_perf(thunk, **kw):
        return float(thunk())

    monkeypatch.setattr(autotuner, "perf_thunk", fake_perf)
    tuner = autotuner.ContextualAutotuner("i", ["bad", 4.0])

    def make_thunk(cfg):
        if cfg == "bad":
            raise ValueError("does not compile")
        return lambda: cfg

    assert tuner.tune(make_thunk, "k") == 4.0

    # Every candidate failing to BUILD is not a transient: there is nothing
    # to choose from, so the tuner raises the first candidate's exception
    # (config 0 would fail the same way later, with the cause gone).
    tuner_all_bad = autotuner.ContextualAutotuner("i2", ["bad", "bad2"])

    def all_bad(cfg):
        raise ValueError(f"does not compile: {cfg}")

    with pytest.raises(RuntimeError, match="all 2 candidate.*bad") as ei:
        tuner_all_bad.tune(all_bad, "k")
    assert isinstance(ei.value.__cause__, ValueError)
    assert tuner_all_bad.peek("k") is None  # nothing cached

    # Candidates that BUILD but yield no valid timing (slope jitter) are
    # the transient: config 0 with a warning, verdict NOT cached, so a
    # later call re-tunes (and in multi-process runs every process still
    # joins the vote).
    tuner_jitter = autotuner.ContextualAutotuner(
        "i3", ["a", "b"], timer=lambda thunk: float("inf"))
    with pytest.warns(UserWarning, match="no candidate"):
        assert tuner_jitter.tune(lambda cfg: (lambda: cfg), "k") == "a"
    assert tuner_jitter.peek("k") is None  # verdict not cached
    with pytest.warns(UserWarning, match="no candidate"):
        tuner_jitter.tune(lambda cfg: (lambda: cfg), "k")  # re-asked


def test_decorator_form(monkeypatch):
    monkeypatch.setattr(autotuner, "perf_thunk",
                        lambda thunk, **kw: float(np.asarray(thunk())[0]))

    @autotuner.contextual_autotune([2.0, 1.0, 3.0], name="deco")
    def op(config, x):
        return x * 0 + config

    x = jnp.ones((4,))
    out = op(x)
    np.testing.assert_allclose(np.asarray(out), 1.0)
    # Cached winner reused for same-shape args.
    assert op.tuner._key("(4,):float32") in autotuner._memory_cache


def test_vote_single_process():
    assert autotuner._vote_across_processes([3.0, 1.0, 2.0]) == (1, True)
    # All-inf vote: index is meaningless but the invalid flag is collective.
    assert autotuner._vote_across_processes(
        [float("inf"), float("inf")]) == (0, False)


def test_pruner_rejected_config_is_never_compiled(monkeypatch):
    """ISSUE 8 acceptance: tune() must never compile (never call make_thunk
    for) a config the resource pruner rejects — the analyzer runs BEFORE
    any build, and pruned counts land in the module accounting."""
    monkeypatch.setattr(autotuner, "perf_thunk",
                        lambda thunk, **kw: float(thunk()))

    def pruner(cfg):
        return ["vmem-budget finding"] if cfg >= 8.0 else []

    compiled = []

    def make_thunk(cfg):
        compiled.append(cfg)
        return lambda: cfg

    tuner = autotuner.ContextualAutotuner("pr", [8.0, 2.0, 16.0, 4.0],
                                          pruner=pruner)
    assert tuner.tune(make_thunk, "k") == 2.0
    assert compiled == [2.0, 4.0]  # 8.0 and 16.0 pruned pre-compile
    assert autotuner.pruned_counts()["pr"] == 2
    assert autotuner.pruned_configs_total() >= 2
    m = autotuner.metrics().as_dict()
    assert m["autotune_pruned_configs{tuner=pr}"] >= 2.0

    # Multi-timer path: pruned entries arrive as None thunks (never built).
    seen = []

    def fake_multi(thunks):
        seen.append([t is None for t in thunks])
        return [float("inf") if t is None else t() for t in thunks]

    compiled.clear()
    tuner2 = autotuner.ContextualAutotuner("pr2", [8.0, 2.0],
                                           multi_timer=fake_multi,
                                           pruner=pruner)
    assert tuner2.tune(make_thunk, "k") == 2.0
    assert compiled == [2.0] and seen == [[True, False]]


def test_pruner_rejecting_everything_is_distrusted(monkeypatch):
    """An analyzer that rejects every candidate is wrong, not the configs:
    the tuner warns, ignores it, and times everything."""
    monkeypatch.setattr(autotuner, "perf_thunk",
                        lambda thunk, **kw: float(thunk()))
    compiled = []

    def make_thunk(cfg):
        compiled.append(cfg)
        return lambda: cfg

    tuner = autotuner.ContextualAutotuner(
        "prall", [3.0, 1.0], pruner=lambda cfg: ["always rejected"])
    with pytest.warns(UserWarning, match="rejected all"):
        assert tuner.tune(make_thunk, "k") == 1.0
    assert compiled == [3.0, 1.0]
    assert autotuner.pruned_counts().get("prall", 0) == 0

    # A pruner that RAISES never prunes (analyzer bugs degrade to timing).
    def broken(cfg):
        raise RuntimeError("analyzer bug")

    compiled.clear()
    tuner2 = autotuner.ContextualAutotuner("prbug", [3.0, 1.0],
                                           pruner=broken)
    assert tuner2.tune(make_thunk, "k") == 1.0
    assert compiled == [3.0, 1.0]


def test_cache_key_separates_hardware_kinds_and_jax_version(monkeypatch):
    """Satellite: the disk-cache key embeds the device kind and jax
    version, so a winner tuned on one chip generation can never be served
    to another (the disk cache file outlives both)."""
    import jax

    tuner = autotuner.ContextualAutotuner("hw", [1, 2])
    monkeypatch.setattr(autotuner, "_device_kind", lambda: "TPU v5e")
    k5 = tuner._key("ctx")
    monkeypatch.setattr(autotuner, "_device_kind", lambda: "TPU v6e")
    k6 = tuner._key("ctx")
    assert k5 != k6
    assert "TPU v5e" in k5 and "TPU v6e" in k6
    assert f"jax{jax.__version__}" in k5

    # A winner cached under one kind is invisible under the other.
    monkeypatch.setattr(autotuner, "perf_thunk",
                        lambda thunk, **kw: float(thunk()))
    monkeypatch.setattr(autotuner, "_device_kind", lambda: "TPU v5e")
    assert tuner.tune(lambda c: (lambda: float(c)), "ctx") == 1
    assert tuner.peek("ctx") == 1
    monkeypatch.setattr(autotuner, "_device_kind", lambda: "TPU v6e")
    assert tuner.peek("ctx") is None


def test_tuned_matmul_blocks_small_cpu(monkeypatch):
    """End-to-end on tiny shapes (CPU): returns a feasible blocking and the
    ag_gemm path computes correctly with it.

    The stock tune is ten candidates (all one blocking once capped at 256)
    by fourteen rounds of 128 interpreted matmuls: 259 s for nothing this
    test asserts. Two distinct candidates and three rounds run the same
    path; the timer's ranking has its own tests above."""
    from triton_distributed_tpu.kernels.allgather_gemm import (
        ag_gemm_single_chip_autotuned,
    )

    monkeypatch.setattr(autotuner, "MATMUL_BLOCK_CANDIDATES",
                        ((256, 256, 256), (128, 256, 256)))
    monkeypatch.setattr(
        autotuner, "interleaved_slope_timer",
        functools.partial(autotuner.interleaved_slope_timer, rounds=3))
    m = k = n = 256
    bm, bn, bk = autotuner.tuned_matmul_blocks(m, k, n, "float32")
    assert m % bm == 0 and n % bn == 0 and k % bk == 0

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    out = ag_gemm_single_chip_autotuned(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a) @ np.asarray(b),
                               atol=1e-3, rtol=1e-3)
