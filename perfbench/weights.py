"""Seeded weights, made by the benchmark and not by the program.

One generator, two readers. The program gets the whole stack in its own
parameter layout (``program_params``: one jitted call, on the device, in the
served dtype, born sharded). The plain reference asks for one layer at a
time (``Weights.layer``) and gets the same numbers again from the same keys,
so no second copy of the model ever sits on the device.

Norm weights are 1 + 0.1 * N(0, 1), not ones: a forward pass that left a
norm's weight out would otherwise agree with the reference.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelSizes:
    """What the reference needs of a configuration (HF key in brackets)."""

    vocab_size: int
    d_model: int            # hidden_size
    n_layers: int           # num_hidden_layers
    n_heads: int            # num_attention_heads
    n_kv_heads: int         # num_key_value_heads
    head_dim: int
    d_ff: int               # intermediate_size
    rope_theta: float
    rms_eps: float          # rms_norm_eps
    tie_embeddings: bool    # tie_word_embeddings
    qk_norm: bool
    max_length: int         # max_position_embeddings, as run
    dtype: str              # torch_dtype

    @classmethod
    def from_hf(cls, hf: dict, *, qk_norm: bool = True) -> "ModelSizes":
        return cls(
            vocab_size=int(hf["vocab_size"]), d_model=int(hf["hidden_size"]),
            n_layers=int(hf["num_hidden_layers"]),
            n_heads=int(hf["num_attention_heads"]),
            n_kv_heads=int(hf["num_key_value_heads"]),
            head_dim=int(hf["head_dim"]), d_ff=int(hf["intermediate_size"]),
            rope_theta=float(hf["rope_theta"]),
            rms_eps=float(hf["rms_norm_eps"]),
            tie_embeddings=bool(hf["tie_word_embeddings"]), qk_norm=qk_norm,
            max_length=int(hf["max_position_embeddings"]),
            dtype=str(hf["torch_dtype"]))


def _randw(key, shape, fan_in, dtype):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(fan_in ** -0.5,
                                                              dtype)


def _norm(key, shape):
    return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)


def plain_layer(m: ModelSizes, key):
    """One decoder layer's weights as the architecture names them, each
    ``(in, out)``, in the served dtype; norms in float32."""
    dt = jnp.dtype(m.dtype)
    d, dh = m.d_model, m.head_dim
    ks = jax.random.split(key, 11)
    lw = {
        "wq": _randw(ks[0], (d, m.n_heads * dh), d, dt),
        "wk": _randw(ks[1], (d, m.n_kv_heads * dh), d, dt),
        "wv": _randw(ks[2], (d, m.n_kv_heads * dh), d, dt),
        "wo": _randw(ks[3], (m.n_heads * dh, d), m.n_heads * dh, dt),
        "wg": _randw(ks[4], (d, m.d_ff), d, dt),
        "wu": _randw(ks[5], (d, m.d_ff), d, dt),
        "wd": _randw(ks[6], (m.d_ff, d), m.d_ff, dt),
        "input_norm": _norm(ks[7], (d,)),
        "post_norm": _norm(ks[8], (d,)),
    }
    if m.qk_norm:
        lw["q_norm"] = _norm(ks[9], (dh,))
        lw["k_norm"] = _norm(ks[10], (dh,))
    return lw


def plain_globals(m: ModelSizes, key):
    dt = jnp.dtype(m.dtype)
    ks = jax.random.split(key, 3)
    g = {"embed": _randw(ks[0], (m.vocab_size, m.d_model), m.d_model, dt),
         "final_norm": _norm(ks[1], (m.d_model,))}
    if not m.tie_embeddings:
        g["lm_head"] = _randw(ks[2], (m.d_model, m.vocab_size), m.d_model, dt)
    return g


def _keys(seed: int, n_layers: int):
    root = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    return (jax.random.fold_in(root, 1_000_003),
            jax.vmap(lambda i: jax.random.fold_in(root, i))(
                jnp.arange(n_layers)))


class Weights:
    """The reference's view: one layer, or the embedding and head, on call."""

    def __init__(self, sizes: ModelSizes, seed: int, device=None):
        self._layer = jax.jit(functools.partial(plain_layer, sizes))
        self._globals = jax.jit(functools.partial(plain_globals, sizes))
        # A jitted call runs where its operands live: the keys pin the
        # reference to one device.
        self._g = None
        self._gkey, self._lkeys = jax.device_put(
            _keys(seed, sizes.n_layers), device or jax.devices()[0])

    def layer(self, i: int):
        return self._layer(self._lkeys[i])

    def globals_(self):
        if self._g is None:
            self._g = self._globals(self._gkey)
        return self._g


def param_maker(sizes: ModelSizes, model, mesh):
    """The jitted function ``(global key, layer keys) -> params`` in the
    program's parameter layout (stacked layers, fused and packed
    projections), born with the program's own shardings. ``model`` is the
    program's model object: its ``param_specs`` and its two packers (the
    ones its checkpoint loader uses) are all that is asked of it."""
    from jax.sharding import NamedSharding

    world = mesh.shape[model.axis]
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             model.param_specs())

    @functools.partial(jax.jit, out_shardings=shardings)
    def make(gkey, lkeys):
        lw = jax.vmap(functools.partial(plain_layer, sizes))(lkeys)
        g = plain_globals(sizes, gkey)
        attn = {
            "w_qkv": jax.vmap(lambda q, k, v: model.attn.pack_qkv(
                q, k, v, world))(lw["wq"], lw["wk"], lw["wv"]),
            "w_o": lw["wo"],
        }
        if sizes.qk_norm:
            attn["q_norm"], attn["k_norm"] = lw["q_norm"], lw["k_norm"]
        params = {
            "embed": g["embed"], "final_norm": g["final_norm"],
            "layers": {
                "input_norm": lw["input_norm"], "post_norm": lw["post_norm"],
                "attn": attn,
                "mlp": {"w_gate_up": jax.vmap(
                    lambda a, b: model.mlp.interleave_gate_up(a, b, world))(
                        lw["wg"], lw["wu"]),
                        "w_down": lw["wd"]},
            },
        }
        if not sizes.tie_embeddings:
            params["lm_head"] = g["lm_head"]
        return params

    return make


def program_params(sizes: ModelSizes, seed: int, model, mesh):
    """The whole stack for the program, in one jitted call from the seed."""
    return param_maker(sizes, model, mesh)(*_keys(seed, sizes.n_layers))
