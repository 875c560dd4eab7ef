"""The DeepSeek-V3 family: latent attention (MLA), leading dense layers,
then expert layers with sigmoid routing, a selection bias and a shared
expert, served by the program's ``models.deepseek_v3.DeepseekV3``. The
family is the block; a configuration is one model's numbers
(``configs/joyai-llm-flash-ep16.json`` holds JoyAI-LLM-Flash's).

The block, as published (DeepSeek-V3 technical report; HF
``modeling_deepseek_v3``; configuration keys in brackets). Pre-norm residual
blocks, RMSNorm in float32, an untied head after a final RMSNorm.

Attention, H heads [num_attention_heads]:
  ``c_q = RMSNorm(x W_qa)`` [q_lora_rank]; ``q = c_q W_qb`` -> H x (nope +
  rope) [qk_nope_head_dim, qk_rope_head_dim].
  ``[c_kv ; k_r] = x W_kva`` [kv_lora_rank + rope]; ``c_kv = RMSNorm(c_kv)``.
  ``k_r`` (one for all heads) and ``q_rope`` take RoPE on INTERLEAVED pairs
  [rope_interleave]: dims (2i, 2i+1) rotate by ``pos * theta ** (-2i/rope)``.
  ``[k_nope ; v] = c_kv W_kvb`` -> H x (nope + v) [v_head_dim].
  Scores ``(q_nope . k_nope + q_rope . k_r) * (nope + rope) ** -0.5``, causal
  softmax, ``o = (P v)`` flattened, times ``W_o``. Computed here EXPANDED,
  as written; the program serves the absorbed form.
Layers below [first_k_dense_replace]: a dense SwiGLU [intermediate_size].
The others: ``s = sigmoid(x W_r)`` in float32 over ALL routed experts; the
  [num_experts_per_tok] largest of ``s + b`` are chosen (``noaux_tc`` with
  one group: no group limit); weights ``s_i / sum of the chosen s``
  [norm_topk_prob] times [routed_scaling_factor]; ``y = shared(x) + sum of
  w_i expert_i(x)``, each a SwiGLU [moe_intermediate_size].

ONE CHIP'S SHARE. A configuration states the routed experts held here
(``n_routed_experts_held`` of ``n_routed_experts_published``, ids from
``n_routed_experts_lo``): the sum runs over the chosen experts among those
held, the weights are still normalised over all chosen, and what the absent
experts would add is left out, here and in the program alike. Expert e's
weights come from a key folded with e, so every share of one seed holds the
same model. What such a configuration's file has to state beside the
public keys (``perfbench/README.md`` has no row for it: a ``model_config``
PR may not edit that file): the key that counts the routed experts gives
the count HELD (and is listed in ``reduced``), with the three keys above,
and ``deployment`` says over how many chips each layer is divided and what
is replicated. A layer's kind follows from its index (``layer_weights(sizes,
key, layer_index)``, ``layer_forward(..., layer_index, ...)``).

Departures: the multi-token-prediction module is not modelled (the main
model's logits do not depend on it); the router's product is taken in
float32 in every ``precision`` (the block states it so; the control lowers
the linear layers around it).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from perfbench.peaks import itemsize
from perfbench.reference import attention, linear, rms_norm
from perfbench.weights import keys, norm_weight, randw

BIAS_STD = 0.01         # the selection bias is seeded: N(0, BIAS_STD ** 2)


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab_size: int
    d_model: int            # hidden_size
    n_layers: int           # num_hidden_layers
    dense_layers: int       # first_k_dense_replace
    heads: int              # num_attention_heads
    q_rank: int             # q_lora_rank
    kv_rank: int            # kv_lora_rank
    nope: int               # qk_nope_head_dim
    rope: int               # qk_rope_head_dim
    v_width: int            # v_head_dim
    dense_width: int        # intermediate_size
    expert_width: int       # moe_intermediate_size
    router_width: int       # n_routed_experts_published
    held: int               # n_routed_experts_held
    lo: int                 # n_routed_experts_lo
    topk: int               # num_experts_per_tok
    shared: int             # n_shared_experts
    scaling: float          # routed_scaling_factor
    norm_topk: bool         # norm_topk_prob
    theta: float
    eps: float              # rms_norm_eps
    max_length: int         # max_position_embeddings, as run
    dtype: str              # torch_dtype

    @property
    def cache_width(self) -> int:
        return self.kv_rank + self.rope


def sizes(cfg: dict) -> Sizes:
    if (cfg["scoring_func"], cfg["topk_method"], cfg["n_group"],
            cfg["topk_group"]) != ("sigmoid", "noaux_tc", 1, 1):
        raise ValueError("this family routes by sigmoid scores with a bias "
                         "and one group; the configuration states another")
    if not cfg["rope_interleave"] or cfg["rope_scaling"] is not None:
        raise ValueError("this family rotates interleaved pairs, unscaled")
    if cfg["n_routed_experts"] != cfg["n_routed_experts_held"]:
        raise ValueError("n_routed_experts is the count held here")
    return Sizes(
        vocab_size=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layers=int(cfg["num_hidden_layers"]),
        dense_layers=int(cfg["first_k_dense_replace"]),
        heads=int(cfg["num_attention_heads"]),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]),
        rope=int(cfg["qk_rope_head_dim"]),
        v_width=int(cfg["v_head_dim"]),
        dense_width=int(cfg["intermediate_size"]),
        expert_width=int(cfg["moe_intermediate_size"]),
        router_width=int(cfg["n_routed_experts_published"]),
        held=int(cfg["n_routed_experts_held"]),
        lo=int(cfg["n_routed_experts_lo"]),
        topk=int(cfg["num_experts_per_tok"]),
        shared=int(cfg["n_shared_experts"]),
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        max_length=int(cfg["max_position_embeddings"]),
        dtype=str(cfg["torch_dtype"]))


# -- the seeded plain weights --------------------------------------------------

def is_dense(m: Sizes, layer_index: int) -> bool:
    return layer_index < m.dense_layers


def plain_layer(m: Sizes, key, dense: bool):
    """One decoder layer's weights as the architecture names them, each
    ``(in, out)``, in the served dtype; norms, the router and its bias in
    float32. Gate and up halves are made as one matrix (``*_gu``, gate
    first). Expert e's matrices come from the key folded with e."""
    dt = jnp.dtype(m.dtype)
    d, H = m.d_model, m.heads
    ks = jax.random.split(key, 16)
    lw = {
        "wqa": randw(ks[0], (d, m.q_rank), d, dt),
        "q_a_norm": norm_weight(ks[1], (m.q_rank,)),
        "wqb": randw(ks[2], (m.q_rank, H * (m.nope + m.rope)), m.q_rank, dt),
        "wkva": randw(ks[3], (d, m.cache_width), d, dt),
        "kv_a_norm": norm_weight(ks[4], (m.kv_rank,)),
        "wkvb": randw(ks[5], (m.kv_rank, H * (m.nope + m.v_width)),
                      m.kv_rank, dt),
        "wo": randw(ks[6], (H * m.v_width, d), H * m.v_width, dt),
        "input_norm": norm_weight(ks[7], (d,)),
        "post_norm": norm_weight(ks[8], (d,)),
    }
    if dense:
        ff = m.dense_width
        lw["w_gu"] = randw(ks[9], (d, 2 * ff), d, dt)
        lw["w_d"] = randw(ks[10], (ff, d), ff, dt)
        return lw
    ffe, ffs = m.expert_width, m.shared * m.expert_width
    ids = m.lo + jnp.arange(m.held)
    lw["router"] = randw(ks[9], (d, m.router_width), d, dt).astype(
        jnp.float32)
    lw["bias"] = BIAS_STD * jax.random.normal(ks[10], (m.router_width,),
                                              jnp.float32)
    lw["e_gu"] = jax.vmap(lambda e: randw(
        jax.random.fold_in(ks[11], e), (d, 2 * ffe), d, dt))(ids)
    lw["e_d"] = jax.vmap(lambda e: randw(
        jax.random.fold_in(ks[12], e), (ffe, d), ffe, dt))(ids)
    lw["s_gu"] = randw(ks[13], (d, 2 * ffs), d, dt)
    lw["s_d"] = randw(ks[14], (ffs, d), ffs, dt)
    return lw


def plain_globals(m: Sizes, key):
    dt = jnp.dtype(m.dtype)
    ks = jax.random.split(key, 3)
    return {"embed": randw(ks[0], (m.vocab_size, m.d_model), m.d_model, dt),
            "final_norm": norm_weight(ks[1], (m.d_model,)),
            "lm_head": randw(ks[2], (m.d_model, m.vocab_size), m.d_model,
                             dt)}


_layer_weights = jax.jit(plain_layer, static_argnums=(0, 2))
global_weights = jax.jit(plain_globals, static_argnums=0)


def layer_weights(m: Sizes, key, layer_index: int):
    return _layer_weights(m, key, is_dense(m, layer_index))


def head_weights(m: Sizes, g) -> dict:
    return {"final_norm": g["final_norm"], "eps": m.eps,
            "head": g["lm_head"]}


# -- the program's own configuration and parameters ----------------------------

def program_layers(m: Sizes, lw, dense: bool):
    """Layer-stacked plain weights -> the program's layer-stacked layout
    (``models/deepseek_v3.py``): ``W_kvb`` split per head as the absorbed
    form multiplies it."""
    n = lw["wkvb"].shape[0]
    kvb = lw["wkvb"].reshape(n, m.kv_rank, m.heads, m.nope + m.v_width)
    out = {
        "input_norm": lw["input_norm"], "post_norm": lw["post_norm"],
        "attn": {
            "w_qa": lw["wqa"], "q_a_norm": lw["q_a_norm"],
            "w_qb": lw["wqb"], "w_kva": lw["wkva"],
            "kv_a_norm": lw["kv_a_norm"],
            "w_kvb_k": kvb[..., :m.nope].transpose(0, 2, 3, 1),
            "w_kvb_v": kvb[..., m.nope:].transpose(0, 2, 1, 3),
            "w_o": lw["wo"]},
    }
    if dense:
        out["mlp"] = {"w_gate_up": lw["w_gu"], "w_down": lw["w_d"]}
    else:
        out["moe"] = {
            "router": lw["router"], "bias": lw["bias"],
            "w_gate_up": lw["e_gu"], "w_down": lw["e_d"],
            "shared": {"w_gate_up": lw["s_gu"], "w_down": lw["s_d"]}}
    return out


def program_config(cfg: dict, m: Sizes):
    from triton_distributed_tpu.models.config import DeepseekV3Config

    return DeepseekV3Config(
        model_name=cfg["source"], vocab_size=m.vocab_size, d_model=m.d_model,
        n_layers=m.n_layers, n_dense_layers=m.dense_layers, n_heads=m.heads,
        q_lora_rank=m.q_rank, kv_lora_rank=m.kv_rank,
        qk_nope_head_dim=m.nope, qk_rope_head_dim=m.rope,
        v_head_dim=m.v_width, d_ff=m.dense_width, moe_d_ff=m.expert_width,
        n_experts=m.router_width, n_experts_per_tok=m.topk,
        n_shared_experts=m.shared, routed_scaling_factor=m.scaling,
        norm_topk_prob=m.norm_topk, experts_held=m.held, experts_lo=m.lo,
        rope_theta=m.theta, rms_eps=m.eps, max_length=m.max_length,
        dtype=jnp.dtype(m.dtype))


def program(cfg: dict, m: Sizes, seed: int, mesh, engine_kwargs: dict):
    """The program's configuration object and the whole stack of seeded
    parameters for it, in one jitted call from the seed."""
    from jax.sharding import NamedSharding

    from triton_distributed_tpu.models.deepseek_v3 import DeepseekV3

    mcfg = program_config(cfg, m)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             DeepseekV3(mcfg).param_specs())

    @functools.partial(jax.jit, out_shardings=shardings)
    def make(gkey, lkeys):
        nd = m.dense_layers
        stack = {}
        for name, ks, dense in (("dense", lkeys[:nd], True),
                                ("layers", lkeys[nd:], False)):
            lw = jax.vmap(lambda k: plain_layer(m, k, dense))(ks)
            stack[name] = program_layers(m, lw, dense)
        return {**plain_globals(m, gkey), **stack}

    return mcfg, make(*keys(seed, m.n_layers))


# -- the plain forward pass of one layer ---------------------------------------

def rope_interleaved(x, positions, theta):
    """x: (S, H, r); dims (2i, 2i+1) rotate by ``pos * theta ** (-2i/r)``."""
    r = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq        # (S, r/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def swiglu(x, w_gu, w_d, precision):
    h = linear(x, w_gu, precision)
    ff = h.shape[-1] // 2
    return linear(jax.nn.silu(h[:, :ff]) * h[:, ff:], w_d, precision)


def routing(m: Sizes, x, router, bias):
    """Scores in float32 over all experts -> (weights (S, k), ids (S, k))."""
    s = jax.nn.sigmoid(jnp.dot(x, router.astype(jnp.float32)))
    _, ids = jax.lax.top_k(s + bias, m.topk)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if m.norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * m.scaling, ids


def routed_part(m: Sizes, x, lw, precision):
    """What the held experts give: every held expert over every token, in
    the plainest way, weighted by its routing weight where it was chosen
    (one expert at a time, so that one expert's body is all that is
    compiled)."""
    w, ids = routing(m, x, lw["router"], lw["bias"])

    def add(y, expert):
        j, w_gu, w_d = expert
        w_j = jnp.sum(jnp.where(ids == m.lo + j, w, 0.0), axis=-1)   # (S,)
        return y + w_j[:, None] * swiglu(x, w_gu, w_d, precision), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(x),
                        (jnp.arange(m.held), lw["e_gu"], lw["e_d"]))
    return y


@functools.partial(jax.jit, static_argnames=("m", "precision", "dense"))
def _layer_forward(h, lw, *, m, precision, dense):
    S, H = h.shape[0], m.heads
    pos = jnp.arange(S)
    x = rms_norm(h, lw["input_norm"], m.eps)
    cq = rms_norm(linear(x, lw["wqa"], precision), lw["q_a_norm"], m.eps)
    q = linear(cq, lw["wqb"], precision).reshape(S, H, m.nope + m.rope)
    ckv = linear(x, lw["wkva"], precision)
    c = rms_norm(ckv[:, :m.kv_rank], lw["kv_a_norm"], m.eps)
    k_r = rope_interleaved(ckv[:, None, m.kv_rank:], pos, m.theta)
    q_r = rope_interleaved(q[..., m.nope:], pos, m.theta)
    kv = linear(c, lw["wkvb"], precision).reshape(S, H, m.nope + m.v_width)
    k = jnp.concatenate([kv[..., :m.nope],
                         jnp.broadcast_to(k_r, (S, H, m.rope))], axis=-1)
    q = jnp.concatenate([q[..., :m.nope], q_r], axis=-1)
    a = attention(q, k, kv[..., m.nope:], (m.nope + m.rope) ** -0.5)
    h = h + linear(a, lw["wo"], precision)
    x = rms_norm(h, lw["post_norm"], m.eps)
    if dense:
        return h + swiglu(x, lw["w_gu"], lw["w_d"], precision)
    return (h + swiglu(x, lw["s_gu"], lw["s_d"], precision)
            + routed_part(m, x, lw, precision))


def layer_forward(h, lw, m: Sizes, layer_index: int, precision: str):
    """One decoder layer over one whole sequence. h: (S, d) float32."""
    return _layer_forward(h, lw, m=m, precision=precision,
                          dense=is_dense(m, layer_index))


# -- operations and bytes -------------------------------------------------------

def attn_params(m: Sizes) -> int:
    H = m.heads
    return (m.d_model * m.q_rank + m.q_rank * H * (m.nope + m.rope)
            + m.d_model * m.cache_width
            + m.kv_rank * H * (m.nope + m.v_width) + H * m.v_width * m.d_model)


def expert_params(m: Sizes) -> int:
    return 3 * m.d_model * m.expert_width


def weight_bytes_held(m: Sizes) -> float:
    """Every linear weight this chip holds, once: all held experts of every
    expert layer (in the deployment each serves the rows of all the chips
    that share the layer, so all are read), the shared expert, the router
    (float32), the dense layers and the head."""
    b = itemsize(m.dtype)
    moe_layers = m.n_layers - m.dense_layers
    return (b * (m.n_layers * attn_params(m)
                 + m.dense_layers * 3 * m.d_model * m.dense_width
                 + moe_layers * (m.held + m.shared) * expert_params(m)
                 + m.d_model * m.vocab_size)
            + 4 * moe_layers * m.d_model * m.router_width)


def latent_attn_min_bytes(m: Sizes, context_lens) -> float:
    """The least bytes the latent attention of one step reads: every row of
    every context once a layer, at its unpadded width (the pool stores the
    row padded to a lane multiple; the padding is not counted)."""
    return (m.n_layers * m.cache_width * itemsize(m.dtype)
            * float(sum(context_lens)))


def latent_attn_flops(m: Sizes, context_lens) -> float:
    """Absorbed form, one entry of ``context_lens`` a query token: scores
    over the row, values over its latent part, every head, every layer."""
    return (2.0 * m.n_layers * m.heads * (m.cache_width + m.kv_rank)
            * float(sum(context_lens)))


def moe_ffn_min_bytes(m: Sizes, experts_touched: float) -> float:
    """Routed experts only: the three matrices of every expert that got a
    row, summed over the expert layers."""
    return itemsize(m.dtype) * expert_params(m) * float(experts_touched)


def moe_ffn_flops(m: Sizes, pairs: float) -> float:
    return 2.0 * expert_params(m) * float(pairs)


def moe_expected(m: Sizes, rows: float) -> tuple[float, float]:
    """(pairs held, experts touched) a step of ``rows`` live tokens gives
    over all expert layers IF every routed expert is as likely as another
    (seeded weights and a small bias make it nearly so): each row picks a
    given expert with probability topk / router width."""
    p = m.topk / m.router_width
    layers = m.n_layers - m.dense_layers
    return (layers * rows * p * m.held,
            layers * m.held * (1.0 - (1.0 - p) ** rows))


def decode_step_min_bytes(m: Sizes, context_lens) -> float:
    """The least bytes one decode step has to move through HBM: every
    linear weight held once, the head once, and the latent row of every
    token of context once a layer. Activations, the embedding rows and the
    pool's writes are left out, so this is a lower bound."""
    return weight_bytes_held(m) + latent_attn_min_bytes(m, context_lens)
