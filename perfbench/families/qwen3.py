"""The Qwen3 family: dense decoders with grouped-query attention, served by
the program's ``models.qwen.Qwen3``. The only file of the harness that
names this model, imports it or reads a dense-GQA field.

The block, as the model's public description has it (Qwen3 technical
report; HF ``modeling_qwen3``): pre-norm residual blocks; RMSNorm in
float32; grouped-query attention with a per-head RMSNorm on q and k before
rotate-half RoPE; causal softmax attention scaled by ``head_dim ** -0.5``;
SwiGLU feed-forward ``down(silu(gate(x)) * up(x))``; a final RMSNorm and a
head that is the transposed embedding when ``tie_word_embeddings``. Every
layer is of one kind, so the layer's index is not read.

The counts of operations and bytes follow the program's
``runtime/perf_model.py`` (``matmul_params``, ``step_hbm_bytes``) and are
kept here so that no later PR can move the yardstick; the original is listed
in PERF.md for a later PR to delete or to import from here.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from perfbench.peaks import itemsize
from perfbench.reference import attention, linear, rms_norm, rope
from perfbench.weights import keys, norm_weight, randw


@dataclasses.dataclass(frozen=True)
class Sizes:
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


def sizes(cfg: dict) -> Sizes:
    return Sizes(
        vocab_size=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layers=int(cfg["num_hidden_layers"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]), d_ff=int(cfg["intermediate_size"]),
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        qk_norm=bool(cfg.get("qk_norm", True)),
        max_length=int(cfg["max_position_embeddings"]),
        dtype=str(cfg["torch_dtype"]))


# -- the seeded plain weights --------------------------------------------------

def plain_layer(m: Sizes, key):
    """One decoder layer's weights as the architecture names them, each
    ``(in, out)``, in the served dtype; norms in float32."""
    dt = jnp.dtype(m.dtype)
    d, dh = m.d_model, m.head_dim
    ks = jax.random.split(key, 11)
    lw = {
        "wq": randw(ks[0], (d, m.n_heads * dh), d, dt),
        "wk": randw(ks[1], (d, m.n_kv_heads * dh), d, dt),
        "wv": randw(ks[2], (d, m.n_kv_heads * dh), d, dt),
        "wo": randw(ks[3], (m.n_heads * dh, d), m.n_heads * dh, dt),
        "wg": randw(ks[4], (d, m.d_ff), d, dt),
        "wu": randw(ks[5], (d, m.d_ff), d, dt),
        "wd": randw(ks[6], (m.d_ff, d), m.d_ff, dt),
        "input_norm": norm_weight(ks[7], (d,)),
        "post_norm": norm_weight(ks[8], (d,)),
    }
    if m.qk_norm:
        lw["q_norm"] = norm_weight(ks[9], (dh,))
        lw["k_norm"] = norm_weight(ks[10], (dh,))
    return lw


def plain_globals(m: Sizes, key):
    dt = jnp.dtype(m.dtype)
    ks = jax.random.split(key, 3)
    g = {"embed": randw(ks[0], (m.vocab_size, m.d_model), m.d_model, dt),
         "final_norm": norm_weight(ks[1], (m.d_model,))}
    if not m.tie_embeddings:
        g["lm_head"] = randw(ks[2], (m.d_model, m.vocab_size), m.d_model, dt)
    return g


_layer_weights = jax.jit(plain_layer, static_argnums=0)
global_weights = jax.jit(plain_globals, static_argnums=0)


def layer_weights(m: Sizes, key, layer_index: int):
    return _layer_weights(m, key)


def head_weights(m: Sizes, g) -> dict:
    return {"final_norm": g["final_norm"], "eps": m.rms_eps,
            "head": g["embed"].T if m.tie_embeddings else g["lm_head"]}


# -- the program's own configuration and parameters ----------------------------

def param_maker(m: Sizes, model, mesh):
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
        lw = jax.vmap(functools.partial(plain_layer, m))(lkeys)
        g = plain_globals(m, gkey)
        attn = {
            "w_qkv": jax.vmap(lambda q, k, v: model.attn.pack_qkv(
                q, k, v, world))(lw["wq"], lw["wk"], lw["wv"]),
            "w_o": lw["wo"],
        }
        if m.qk_norm:
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
        if not m.tie_embeddings:
            params["lm_head"] = g["lm_head"]
        return params

    return make


def program(cfg: dict, m: Sizes, seed: int, mesh, engine_kwargs: dict):
    """The program's configuration object and the whole stack of seeded
    parameters for it, in one jitted call from the seed."""
    from triton_distributed_tpu.models.config import ModelConfig
    from triton_distributed_tpu.models.qwen import Qwen3

    mcfg = ModelConfig(
        model_name=cfg["source"], vocab_size=m.vocab_size, d_model=m.d_model,
        n_layers=m.n_layers, n_heads=m.n_heads, n_kv_heads=m.n_kv_heads,
        head_dim=m.head_dim, d_ff=m.d_ff, rope_theta=m.rope_theta,
        rms_eps=m.rms_eps, tie_embeddings=m.tie_embeddings,
        qk_norm=m.qk_norm, max_length=m.max_length, dtype=jnp.dtype(m.dtype))
    model = Qwen3(mcfg, block_n=engine_kwargs.get("block_n", 256))
    return mcfg, param_maker(m, model, mesh)(*keys(seed, m.n_layers))


# -- the plain forward pass of one layer ---------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _layer_forward(h, lw, *, m, precision):
    S = h.shape[0]
    n_heads, n_kv, dh, eps, theta = (m.n_heads, m.n_kv_heads, m.head_dim,
                                     m.rms_eps, m.rope_theta)
    pos = jnp.arange(S)
    x = rms_norm(h, lw["input_norm"], eps)
    q = linear(x, lw["wq"], precision).reshape(S, n_heads, dh)
    k = linear(x, lw["wk"], precision).reshape(S, n_kv, dh)
    v = linear(x, lw["wv"], precision).reshape(S, n_kv, dh)
    if "q_norm" in lw:
        q = rms_norm(q, lw["q_norm"], eps)
        k = rms_norm(k, lw["k_norm"], eps)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    a = attention(q, k, v, dh ** -0.5)
    h = h + linear(a, lw["wo"], precision)
    x = rms_norm(h, lw["post_norm"], eps)
    gate = linear(x, lw["wg"], precision)
    up = linear(x, lw["wu"], precision)
    return h + linear(jax.nn.silu(gate) * up, lw["wd"], precision)


def layer_forward(h, lw, m: Sizes, layer_index: int, precision: str):
    """One decoder layer over one whole sequence. h: (S, d) float32."""
    return _layer_forward(h, lw, m=m, precision=precision)


# -- operations and bytes -------------------------------------------------------

def layer_matmul_params(m: Sizes) -> int:
    """Weights of the linear layers of the whole stack (no embedding, no
    head, no norms): what every token is multiplied by."""
    attn = m.d_model * (m.n_heads + 2 * m.n_kv_heads) * m.head_dim \
        + m.n_heads * m.head_dim * m.d_model
    mlp = 3 * m.d_model * m.d_ff
    return m.n_layers * (attn + mlp)


def head_params(m: Sizes) -> int:
    return m.d_model * m.vocab_size


def kv_bytes_per_token(m: Sizes) -> int:
    return 2 * m.n_layers * m.n_kv_heads * m.head_dim * itemsize(m.dtype)


def decode_step_min_bytes(m: Sizes, context_lens) -> float:
    """The least bytes one decode step has to move through HBM: every
    linear layer's weights and the head once, and the keys and values of
    every row's context once. Activations, the embedding rows and the
    pool's writes are left out, so this is a lower bound."""
    weights = (layer_matmul_params(m) + head_params(m)) * itemsize(m.dtype)
    return weights + kv_bytes_per_token(m) * float(sum(context_lens))
