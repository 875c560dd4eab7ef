"""The dense model's weight stacks stay out of the layer scan's ``xs`` under
tensor parallelism (``Qwen3._scan_layers``): AG-GEMM, its tail and GEMM-RS
take the stack whole and index ``[layer]`` themselves.

1. At TP=4 under the interpreter the three steps (``forward_paged`` with
   decode ids and with the mixed triple, ``forward_device``) in
   ``mode="dist"`` give, to the bit, what the same layers give called one by
   one on ``tree[li]`` slices (no scan, no stack: the kernels' 2-D form), and
   what ``mode="xla"`` gives within ``tests/test_qwen_e2e.py``'s tolerance.
2. The programs, traced and never run: on four devices no ``xs`` operand of
   the layer scan is one of the four projections' stacks (they are among its
   constants), and on ONE device the decode and the mixed step are, to the
   character, the programs of a model that leaves every leaf in ``xs`` (the
   parent's: the one-chip cells' guarantee, as far as a CPU can check it).

A file of its own so that ``--dist loadfile`` hands it to another worker
than ``tests/test_qwen_e2e.py`` (six ``dist`` forwards on four interpreted
devices, 20 s each); it shares no fixture with that file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.layers import nn
from triton_distributed_tpu.models import Engine, ModelConfig, Qwen3
from triton_distributed_tpu.runtime import assert_allclose
from triton_distributed_tpu.runtime.compat import axis_size
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving import KVPool

SLOTS, BLOCK, CHUNK, ROWS = 8, 4, 4, 4
STACKS = {"attn": ("w_qkv", "w_o"), "mlp": ("w_gate_up", "w_down")}


class _EveryLeafInXs(Qwen3):
    """The layer scan as it was before the stacks left it: every leaf of
    ``params["layers"]`` rides ``xs`` and the kernels get the scan's slice."""

    def _scan_layers(self, params, mode):
        return dict(params["layers"]), None


class _OneByOne(Qwen3):
    """The reference of the bitwise comparison: no scan and no stack, each
    layer called by itself on its own slice of the tree."""

    def _layers(self, params):
        return [jax.tree.map(lambda x: x[li], params["layers"])
                for li in range(self.config.n_layers)]

    def forward_paged(self, params, ids, state, offsets, block_tables,
                      slot_mask, seq_lens=None, *, mode, interpret=None,
                      paged_attn="fused", spec_verify=False):
        flat, blocks, last = nn.paged_token_blocks(
            ids, offsets, block_tables, slot_mask, seq_lens,
            multiple=axis_size(self.axis))
        h, rows = self._embed(params, flat, mode)
        for li, lp in enumerate(self._layers(params)):
            h, state, _ = self._layer(
                lp, h, state, None, li, mode=mode, interpret=interpret,
                blocks=blocks, paged_attn=paged_attn, layer=li)
        return self._head(params, h, rows, last=last)[0], {}, state

    def forward_device(self, params, ids, k_cache, v_cache, offset, *,
                       mode, interpret=None, return_moe_stats=False):
        h, rows = self._embed(params, ids, mode)
        caches = []
        for li, lp in enumerate(self._layers(params)):
            h, cache, _ = self._layer(
                lp, h, (k_cache[li], v_cache[li]), offset, li, mode=mode,
                interpret=interpret)
            caches.append(cache)
        ks, vs = (jnp.stack(c) for c in zip(*caches))
        return self._head(params, h, rows)[0], ks, vs


def _engine(mesh, mode="dist", model=Qwen3, params=None):
    config = ModelConfig.from_name("tiny")
    eng = Engine(config, mesh=mesh, mode=mode, block_n=8, params=params)
    eng.model = model(config, block_n=8)
    return eng


def _operands(eng, kind, rng):
    """Hand-made operands of one step of ``kind`` on ``eng``'s mesh, as
    ``(build, args)``: ``jax.jit(build)(*args)[0]`` is the step's logits.
    ``decode``: eight slots, a token each, behind contexts of 1-8 tokens
    of an untouched pool; ``mixed``: the same beside four prompt rows of
    four tokens dealt to slots 0-3; ``device``: the contiguous cache, a
    prefill of (8, 4)."""
    config = eng.config
    tok = jnp.asarray(rng.integers(0, config.vocab_size, (SLOTS,)), jnp.int32)
    if kind == "device":
        kv = eng.new_cache(SLOTS)
        ids = jnp.asarray(rng.integers(0, config.vocab_size, (SLOTS, 4)),
                          jnp.int32)
        return eng._make_sm(eng.decode_mode), \
            (eng.params, ids, kv.k, kv.v, kv.offset)
    pool = KVPool(config, n_blocks=2 * SLOTS * 4, block_size=BLOCK,
                  mesh=eng.mesh, n_slots=SLOTS)
    for s in range(SLOTS):
        assert pool.ensure(s, 16)
    tables = jnp.asarray(pool.padded_tables(list(range(SLOTS))))
    offsets = jnp.arange(1, SLOTS + 1, dtype=jnp.int32)
    mask = jnp.ones((SLOTS,), bool)
    kw = dict(paged_attn="gather", state_specs=pool.specs)
    if kind == "decode":
        return eng._make_sm(eng.decode_mode, paged="decode", **kw), \
            (eng.params, tok[:, None], pool.state, offsets, tables, mask)
    chunk = jnp.asarray(rng.integers(0, config.vocab_size, (ROWS, CHUNK)),
                        jnp.int32)
    dealt = jnp.asarray([[s, s + 1, CHUNK] for s in range(ROWS)], jnp.int32)
    lens = jnp.asarray([CHUNK] * ROWS + [1] * (SLOTS - ROWS), jnp.int32)
    return eng._make_sm(eng.decode_mode, paged="prefill", **kw), \
        (eng.params, (tok, chunk, dealt), pool.state, offsets, tables, mask,
         lens)


@pytest.fixture(scope="module")
def tp4():
    return make_mesh({"tp": 4}, devices=jax.devices()[:4], set_default=False)


@pytest.mark.parametrize("kind", ["decode", "mixed", "device"])
def test_the_stacked_step_is_the_layers_one_by_one(tp4, kind):
    eng = _engine(tp4)
    logits = {}
    for name, e in (("stacked", eng),
                    ("one_by_one", _engine(tp4, model=_OneByOne,
                                           params=eng.params)),
                    ("xla", _engine(tp4, mode="xla", params=eng.params))):
        build, args = _operands(e, kind, np.random.default_rng(7))
        out = jax.jit(build)(*args)
        logits[name] = np.asarray(out[0])
        if name == "stacked":
            written = jax.tree.map(np.asarray, out[-1])
        elif name == "one_by_one":   # what the step appended, too
            jax.tree.map(np.testing.assert_array_equal, written,
                         jax.tree.map(np.asarray, out[-1]))
    assert logits["stacked"].shape == (SLOTS, eng.config.vocab_size)
    np.testing.assert_array_equal(logits["stacked"], logits["one_by_one"])
    assert_allclose(logits["stacked"], logits["xla"], atol=2e-3, rtol=2e-3)


def _layer_scan(jaxpr, n_layers):
    """The layer scan's equation of a traced step: ``(consts, xs)`` shapes."""
    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "scan" and \
                    eqn.params["length"] == n_layers:
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)
    (eqn,) = walk(jaxpr)
    n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
    shapes = [v.aval.shape for v in eqn.invars]
    return shapes[:n_consts], shapes[n_consts + n_carry:]


@pytest.mark.parametrize("kind", ["decode", "mixed", "device"])
def test_no_projection_stack_rides_the_scan_on_four_devices(tp4, kind):
    eng = _engine(tp4)
    stacks = {jax.tree.map(
        lambda x: x.sharding.shard_shape(x.shape),
        eng.params["layers"][block][name])
        for block, names in STACKS.items() for name in names}
    assert len(stacks) == 4
    build, args = _operands(eng, kind, np.random.default_rng(7))
    consts, xs = _layer_scan(jax.make_jaxpr(build)(*args).jaxpr,
                             eng.config.n_layers)
    assert not stacks & set(xs), "a projection's stack rides the scan as xs"
    assert stacks <= set(consts)
    # the norms and everything small still do
    assert (eng.config.n_layers, eng.config.d_model) in xs
    # and a model that leaves them in puts all four there
    build, args = _operands(_engine(tp4, model=_EveryLeafInXs,
                                    params=eng.params),
                            kind, np.random.default_rng(7))
    _, xs = _layer_scan(jax.make_jaxpr(build)(*args).jaxpr,
                        eng.config.n_layers)
    assert stacks <= set(xs)


@pytest.mark.parametrize("kind", ["decode", "mixed", "device"])
def test_on_one_device_the_step_is_the_one_with_every_leaf_in_xs(kind):
    mesh1 = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    eng = _engine(mesh1)
    texts = []
    for e in (eng, _engine(mesh1, model=_EveryLeafInXs, params=eng.params)):
        build, args = _operands(e, kind, np.random.default_rng(7))
        texts.append(str(jax.make_jaxpr(build)(*args)))
    assert "scan" in texts[0]
    assert texts[0] == texts[1]
