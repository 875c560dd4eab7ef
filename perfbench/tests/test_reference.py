"""The plain reference against the program's own forward pass at a tiny
size in float32, on weights the benchmark made: the two share no code, so
agreement here means that both compute the same block."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import reference, weights
from perfbench.families import qwen3

SIZES = qwen3.Sizes(
    vocab_size=512, d_model=64, n_layers=3, n_heads=8, n_kv_heads=2,
    head_dim=16, d_ff=96, rope_theta=1e4, rms_eps=1e-6, tie_embeddings=False,
    qk_norm=True, max_length=64, dtype="float32")


@pytest.mark.parametrize("tied", [False, True])
def test_reference_agrees_with_the_engines_forward(tied):
    import dataclasses

    from triton_distributed_tpu.models.engine import Engine
    from triton_distributed_tpu.runtime.mesh import make_mesh

    m = dataclasses.replace(SIZES, tie_embeddings=tied)
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2], set_default=False)
    mcfg, params = qwen3.program({"source": "t"}, m, 77, mesh, {"block_n": 8})
    engine = Engine(mcfg, mesh=mesh, params=params, mode="xla", block_n=8)
    tokens = np.random.default_rng(3).integers(0, m.vocab_size, 21).tolist()
    # Engine: prefill the first 20 tokens in a batch of two (xla mode shards
    # the batch over the mesh); its logits are those of position 19.
    ids = jnp.asarray([tokens[:20], tokens[:20]], jnp.int32)
    logits, _ = engine.prefill(ids, engine.new_cache(2))
    logits = np.asarray(logits, np.float32)[0]
    w = weights.Weights(qwen3, m, 77)
    ref = reference.forward_positions(w, [(tokens, 20)])[0]      # position 19
    assert ref["best_token"][0] == int(logits.argmax())
    assert ref["best"][0] == pytest.approx(float(logits.max()), abs=2e-4)
    assert ref["picked"][0] == pytest.approx(float(logits[tokens[20]]),
                                             abs=2e-4)
    assert ref["std"][0] == pytest.approx(float(logits.std()), rel=1e-3)


def test_lower_precisions_move_the_logits():
    w = weights.Weights(qwen3, SIZES, 5)
    tokens = np.random.default_rng(1).integers(0, SIZES.vocab_size, 40).tolist()
    ref = reference.forward_positions(w, [(tokens, 8)])[0]
    assert ref["best"].shape == (32,)
    for precision in ("int8", "fp8"):
        low = reference.forward_positions(w, [(tokens, 8)],
                                          precision=precision)[0]
        rel = np.abs(ref["best"] - low["best"]) / ref["std"]
        assert 1e-4 < rel.max() < 1.0      # moved, and still the same model


def test_weights_by_layer_equal_the_stack_the_program_gets():
    from triton_distributed_tpu.models.qwen import Qwen3
    from triton_distributed_tpu.runtime.mesh import make_mesh

    m = SIZES
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2], set_default=False)
    mcfg, params = qwen3.program({"source": "t"}, m, 2 ** 31 + 3, mesh,
                                 {"block_n": 8})
    model = Qwen3(mcfg, block_n=8)
    w = weights.Weights(qwen3, m, 2 ** 31 + 3)
    for i in (0, m.n_layers - 1):
        lw = w.layer(i)
        wq, wk, wv = model.attn.unpack_qkv(
            params["layers"]["attn"]["w_qkv"][i], 2)
        np.testing.assert_array_equal(np.asarray(wq), np.asarray(lw["wq"]))
        np.testing.assert_array_equal(np.asarray(wv), np.asarray(lw["wv"]))
        np.testing.assert_array_equal(
            np.asarray(params["layers"]["mlp"]["w_down"][i]),
            np.asarray(lw["wd"]))
        assert float(jnp.std(lw["post_norm"])) > 0.05      # not all ones
    np.testing.assert_array_equal(np.asarray(params["lm_head"]),
                                  np.asarray(w.globals_()["lm_head"]))
