"""End-to-end Qwen3 + Engine tests — analog of the reference's
test_e2e_inference.py: token generation through the distributed kernel path
must match the XLA-collective golden, across prefill/decode mode mixes.
Tiny config per the conftest interpreter ceiling.

One dist-mode forward of `tiny` is about 46,000 interpreter callbacks on
eight virtual devices (66 s on an idle 8-core host) and about 20 s on four,
whatever the batch; an `ar` or `xla` forward on eight is 5 s or less. So
the `dist` comparisons run at TP=4 (the scanned loop's at TP=2: six `dist`
forwards for its two sides, a quarter of the cost each), everything else at
TP=8, and every engine and every served result is built once and shared."""

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
        self._prefilled = {}

    # No defaults: functools.cache keys on the arguments as they are passed,
    # and ("dist",) is not ("dist", None).
    @functools.cache
    def engine(self, mode, prefill_mode):
        return Engine(self.config, mesh=self.mesh, mode=mode,
                      prefill_mode=prefill_mode, params=self.params,
                      block_n=8)

    def prefill_logits(self, mode):
        """The logits of the prefill that ``served(mode, None, GEN)`` ran: the
        forward ``Engine.serve`` starts with, not one more of it."""
        self.served(mode, None, GEN)
        return self._prefilled[mode]

    @functools.cache
    def served(self, mode, prefill_mode, gen):
        e = self.engine(mode, prefill_mode)
        prefill = e.prefill

        def recording(ids, kv):
            logits, kv = prefill(ids, kv)
            if prefill_mode is None:
                self._prefilled[mode] = logits
            return logits, kv
        e.prefill = recording
        try:
            return np.asarray(e.serve(self.ids, gen))
        finally:
            e.prefill = prefill


@pytest.fixture(scope="module")
def tp4():
    return _Shared(make_mesh({"tp": 4}, devices=jax.devices()[:4],
                             set_default=False))


@pytest.fixture(scope="module")
def tp2():
    return _Shared(make_mesh({"tp": 2}, devices=jax.devices()[:2],
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


@pytest.mark.parametrize("mode,prefill_mode,gen", [
    ("dist", None, GEN),     # dist everywhere
    ("ar", None, GEN),       # AR everywhere
    ("dist", "xla", 2),      # reference engine style: golden prefill,
], ids=["dist-None", "ar-None", "dist-xla"])   # distributed decode
def test_generation_matches_xla_golden(request, mode, prefill_mode, gen):
    """``[dist-None]`` is the LONG FORM of per-step generation through the
    distributed kernels (a prefill and two decodes at TP=4: what
    ``tutorials/10-e2e-inference-engine.py`` shows once more at TP=2 only to
    print its lines), and its prefill is the one
    ``test_prefill_logits_dist_matches_xla`` reads. What only ``[dist-xla]``
    shows is that a cache the golden prefill wrote is read by a distributed
    decode step: one such step (``gen`` 2), the second decode reading the
    first being ``[dist-None]``'s."""
    shared = request.getfixturevalue("tp4" if mode == "dist" else "tp8")
    golden = shared.served("xla", None, GEN)
    assert golden.shape == (B, GEN)
    np.testing.assert_array_equal(shared.served(mode, prefill_mode, gen),
                                  golden[:, :gen])


def test_serve_scanned_matches_serve(tp2, tp8):
    """The one-executable scanned decode loop (prefill + lax.scan) must
    generate token-for-token what the per-step loop generates, on both the
    xla golden and the distributed kernel path. GEN=3 runs the scan body
    twice: the second iteration reads the KV carry and the collectives'
    semaphore state that the first one left. What only this test shows is
    that second iteration inside ONE executable, which any mesh of two or
    more shows: TP=2, against the per-step loop on the same mesh (under six
    busy workers the TP=4 executable alone took 135-170 s of the 300 a test
    may take). The kernels at TP=4 over consecutive steps, each reading the
    semaphores the step before left, are
    ``test_generation_matches_xla_golden[dist-None]``'s; this is the long
    form of the tutorial's scanned line, which runs the body once."""
    for mode, shared in (("xla", tp8), ("dist", tp2)):
        np.testing.assert_array_equal(
            np.asarray(
                shared.engine(mode, None).serve_scanned(shared.ids, GEN)),
            shared.served(mode, None, GEN), err_msg=mode)


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
