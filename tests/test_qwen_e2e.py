"""End-to-end Qwen3 + Engine tests — analog of the reference's
test_e2e_inference.py: token generation through the distributed kernel path
must match the XLA-collective golden, across prefill/decode mode mixes.
Tiny config per the conftest interpreter ceiling.

One dist-mode forward of `tiny` is about 46,000 interpreter callbacks on
eight virtual devices (66 s on an idle 8-core host) and about 20 s on four,
whatever the batch; an `ar` or `xla` forward on eight is 5 s or less. So
the `dist` comparisons run at TP=4 and everything else at TP=8, and every
engine and every served result is built once and shared."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.models import Engine, KVCache, ModelConfig, Qwen3
from triton_distributed_tpu.runtime import assert_allclose
from triton_distributed_tpu.runtime.mesh import make_mesh

# GEN=3 is one prefill and two decode steps: enough to show that decode
# continues prefill, that the second decode reads what the first wrote, and
# that the scanned loop's body runs a second time on its own carry.
B, L0, GEN = 8, 4, 3


class _Shared:
    """Params, prompt, one Engine per (mode, prefill_mode) and what each
    has served on one mesh, made on first use and kept for the module: no
    test pays for a forward that another has already run."""

    def __init__(self, mesh):
        self.mesh = mesh
        self.config = ModelConfig.from_name("tiny")
        self.params = Qwen3(self.config, block_n=8).init(
            jax.random.PRNGKey(0), mesh)
        self.ids = jax.random.randint(jax.random.PRNGKey(1), (B, L0), 0,
                                      self.config.vocab_size, jnp.int32)

    # No default for prefill_mode: functools.cache keys on the arguments as
    # they are passed, and ("dist",) is not ("dist", None).
    @functools.cache
    def engine(self, mode, prefill_mode):
        return Engine(self.config, mesh=self.mesh, mode=mode,
                      prefill_mode=prefill_mode, params=self.params,
                      block_n=8)

    @functools.cache
    def prefill_logits(self, mode):
        e = self.engine(mode, None)
        return e.prefill(self.ids, e.new_cache(B))[0]

    @functools.cache
    def served(self, mode, prefill_mode):
        return np.asarray(
            self.engine(mode, prefill_mode).serve(self.ids, GEN))


@pytest.fixture(scope="module")
def tp4():
    return _Shared(make_mesh({"tp": 4}, devices=jax.devices()[:4],
                             set_default=False))


@pytest.fixture(scope="module")
def tp8(mesh8):
    return _Shared(mesh8)


def test_prefill_logits_dist_matches_xla(tp4):
    lx = tp4.prefill_logits("xla")
    assert lx.shape == (B, tp4.config.vocab_size)
    assert_allclose(tp4.prefill_logits("dist"), lx, atol=2e-3, rtol=2e-3)


def test_prefill_logits_ar_matches_xla(tp8):
    assert_allclose(tp8.prefill_logits("ar"), tp8.prefill_logits("xla"),
                    atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("mode,prefill_mode", [
    ("dist", None),          # dist everywhere
    ("ar", None),            # AR everywhere
    ("dist", "xla"),         # reference engine style: golden prefill,
])                           # distributed decode (engine.py:121)
def test_generation_matches_xla_golden(request, mode, prefill_mode):
    shared = request.getfixturevalue("tp4" if mode == "dist" else "tp8")
    golden = shared.served("xla", None)
    assert golden.shape == (B, GEN)
    np.testing.assert_array_equal(shared.served(mode, prefill_mode), golden)


def test_serve_scanned_matches_serve(tp4, tp8):
    """The one-executable scanned decode loop (prefill + lax.scan) must
    generate token-for-token what the per-step loop generates, on both the
    xla golden and the distributed kernel path. GEN=3 runs the scan body
    twice: the second iteration reads the KV carry and the collectives'
    semaphore state that the first one left."""
    for mode, shared in (("xla", tp8), ("dist", tp4)):
        np.testing.assert_array_equal(
            np.asarray(
                shared.engine(mode, None).serve_scanned(shared.ids, GEN)),
            shared.served(mode, None), err_msg=mode)


def test_kv_cache_offset_advances(tp8):
    e = tp8.engine("xla", None)
    kv = e.new_cache(B)
    assert int(kv.offset) == 0
    _, kv = e.prefill(tp8.ids, kv)
    assert int(kv.offset) == L0
    _, kv = e.decode_step(jnp.zeros((B,), jnp.int32), kv)
    assert int(kv.offset) == L0 + 1


def test_cache_sharded_over_kv_heads(tp8):
    config = tp8.config
    kv = KVCache.create(config, B, mesh=tp8.mesh)
    # kv-head dim sharded tp-ways
    assert kv.k.sharding.shard_shape(kv.k.shape)[3] == config.n_kv_heads // 8


def test_engine_aot_cache_roundtrip(mesh8, tmp_path, monkeypatch):
    """aot_cache=True: tokens identical to the uncached engine, and a second
    engine process-start loads the serialized step executable from disk
    (source == "cache") instead of re-compiling (reference AOT library
    cold-start role, tools/compile_aot.py:470)."""
    import os

    monkeypatch.setenv("TDT_AOT_CACHE", str(tmp_path))
    cfg = ModelConfig.from_name("tiny")
    prompts = np.arange(24, dtype=np.int32).reshape(8, 3) % cfg.vocab_size

    base = Engine(cfg, mesh=mesh8, mode="xla", block_n=8)
    golden = np.asarray(base.serve(prompts, gen_len=3))

    cached = Engine(cfg, mesh=mesh8, mode="xla", block_n=8, aot_cache=True)
    got = np.asarray(cached.serve(prompts, gen_len=3))
    np.testing.assert_array_equal(got, golden)
    assert os.listdir(tmp_path), "no serialized executables written"

    from triton_distributed_tpu.tools.aot import AOTExecutableCache

    again = Engine(cfg, mesh=mesh8, mode="xla", block_n=8, aot_cache=True)
    step = again._step_fn("xla")
    kv = again.new_cache(prompts.shape[0])
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (again.params, jnp.asarray(prompts), kv))
    _, source = AOTExecutableCache().load_or_compile(
        f"engine_step_{cfg.model_name}_xla", step, *abstract, mesh=mesh8)
    assert source == "cache"
