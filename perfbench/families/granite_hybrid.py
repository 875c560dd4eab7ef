"""The Granite-4.0-H family: a decoder most of whose layers are Mamba-2
(a recurrence whose state is a fixed size a sequence) beside a few layers
of grouped-query attention, a SwiGLU after every mixer, served by the
program's ``models.granite_hybrid.GraniteHybrid``. The family is the block;
a configuration is one model's numbers
(``configs/granite-4.0-h-micro.json`` holds granite-4.0-h-micro's).

The block, as published (HF ``GraniteMoeHybrid`` with ``num_local_experts``
0, so its "shared" MLP is the only one; Mamba-2: Dao and Gu, "Transformers
are SSMs"; configuration keys in brackets). RMSNorm in float32
[rms_norm_eps], weights multiply. With ``r`` = [residual_multiplier]::

    h_0 = E[ids] * [embedding_multiplier]
    h <- h + r * Mixer_i(RMSNorm(h));   h <- h + r * MLP(RMSNorm(h))
    logits = RMSNorm(h) E^T / [logits_scaling]      [tie_word_embeddings]

MLP, every layer: ``[g ; u] = x W_in`` (d -> 2 x [shared_intermediate_size]),
``y = (silu(g) * u) W_out``; no bias.
Attention, where [layer_types] says so: [num_attention_heads] query heads
over [num_key_value_heads] key heads of width d / heads, no bias, NO rotary
embedding [position_embedding_type "nope"], no q/k norm, scores
``q . k * [attention_multiplier]``, causal softmax, ``o W_o``.
Mamba-2, the others: H = [mamba_n_heads] heads of P = [mamba_d_head], G =
[mamba_n_groups] groups, state N = [mamba_d_state], d_inner = H x P =
[mamba_expand] x d::

    [z ; xBC ; dt] = x W_in            (d_inner ; d_inner + 2 G N ; H)
    xBC <- silu(conv(xBC))             causal, depthwise, over the last
                                       [mamba_d_conv] positions, with bias
    [x_s ; B ; C] = xBC                x_s: H x P; B, C: G x N
    D_t = softplus(dt_t + dt_bias);    a_t = exp(D_t A),  A = -exp(A_log)
    S_t = a_t S_{t-1} + D_t x_s,t (x) B_t     (P x N a head, S_0 = 0)
    y_t = S_t C_t + D x_s,t
    out = RMSNorm(y * silu(z)) W_out   (one group's columns at a time; the
                                       gate BEFORE the norm)

computed here as written: ONE sequential scan over the positions of the
sequence, float32, no chunking, no kernel, no state kept anywhere.
[mamba_chunk_size] is the training kernel's blocking and changes no result.

Departures, each because ``reference.forward_positions`` (which a
``model_config`` PR may not edit) embeds and applies the head itself:
the embedding multiplier is applied on entry to layer 0, in float32 (the
same number), and ``1 / logits_scaling`` is folded into the head's matrix
(a power of two: exact). Seeded, as no ``1 / fan_in`` rule covers them
(the configuration's ``assumed`` says so): ``A_log = log U(1, 16)``,
``dt_bias`` the inverse softplus of a step log-uniform in [1e-3, 1e-1]
(Mamba-2's own initialisation), ``D`` = 1, the convolution's bias
0.1 N(0, 1), and the embedding table at ``1 / embedding_multiplier`` of the
other matrices' scale (``plain_globals`` says why).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from perfbench.peaks import itemsize
from perfbench.reference import attention, linear, rms_norm
from perfbench.weights import keys, norm_weight, randw

STATE_ITEMSIZE = 4      # the recurrence's state is float32 (``assumed``)


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab_size: int
    d_model: int            # hidden_size
    layer_types: tuple      # "mamba" | "attention", one a layer
    heads: int              # num_attention_heads
    kv_heads: int           # num_key_value_heads
    mlp_width: int          # shared_intermediate_size
    ssm_heads: int          # mamba_n_heads
    ssm_head_width: int     # mamba_d_head
    ssm_state: int          # mamba_d_state
    ssm_conv: int           # mamba_d_conv
    ssm_groups: int         # mamba_n_groups
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    eps: float              # rms_norm_eps
    max_length: int         # max_position_embeddings, as run
    dtype: str              # torch_dtype

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def attn_head_width(self) -> int:
        return self.d_model // self.heads

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_width

    @property
    def conv_width(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state


def sizes(cfg: dict) -> Sizes:
    said = (cfg["position_embedding_type"], cfg["num_local_experts"],
            cfg["tie_word_embeddings"], cfg["mamba_conv_bias"],
            cfg["mamba_proj_bias"], cfg["attention_bias"],
            cfg["normalization_function"], cfg["hidden_act"])
    if said != ("nope", 0, True, True, False, False, "rmsnorm", "silu"):
        raise ValueError(f"this family is the block in its docstring; the "
                         f"configuration states another: {said}")
    m = Sizes(
        vocab_size=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        layer_types=tuple(cfg["layer_types"]),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        mlp_width=int(cfg["shared_intermediate_size"]),
        ssm_heads=int(cfg["mamba_n_heads"]),
        ssm_head_width=int(cfg["mamba_d_head"]),
        ssm_state=int(cfg["mamba_d_state"]),
        ssm_conv=int(cfg["mamba_d_conv"]),
        ssm_groups=int(cfg["mamba_n_groups"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        eps=float(cfg["rms_norm_eps"]),
        max_length=int(cfg["max_position_embeddings"]),
        dtype=str(cfg["torch_dtype"]))
    if (len(m.layer_types) != int(cfg["num_hidden_layers"])
            or m.d_inner != int(cfg["mamba_expand"]) * m.d_model):
        raise ValueError("layer_types / mamba_expand disagree with the "
                         "depth and widths stated beside them")
    return m


# -- the seeded plain weights --------------------------------------------------

def is_ssm(m: Sizes, layer_index: int) -> bool:
    return m.layer_types[layer_index] == "mamba"


def plain_layer(m: Sizes, key, ssm: bool):
    """One decoder layer's weights as the architecture names them, each
    matrix ``(in, out)`` in the served dtype; norms and the recurrence's own
    parameters in float32. ``w_gu`` is gate and up as one matrix."""
    dt = jnp.dtype(m.dtype)
    d, ff = m.d_model, m.mlp_width
    ks = jax.random.split(key, 14)
    lw = {"input_norm": norm_weight(ks[0], (d,)),
          "post_norm": norm_weight(ks[1], (d,)),
          "w_gu": randw(ks[2], (d, 2 * ff), d, dt),
          "w_d": randw(ks[3], (ff, d), ff, dt)}
    if not ssm:
        dh = m.attn_head_width
        lw.update(wq=randw(ks[4], (d, m.heads * dh), d, dt),
                  wk=randw(ks[5], (d, m.kv_heads * dh), d, dt),
                  wv=randw(ks[6], (d, m.kv_heads * dh), d, dt),
                  wo=randw(ks[7], (m.heads * dh, d), m.heads * dh, dt))
        return lw
    di, C, H, K = m.d_inner, m.conv_width, m.ssm_heads, m.ssm_conv
    step = jnp.exp(jax.random.uniform(ks[8], (H,), jnp.float32,
                                      math.log(1e-3), math.log(1e-1)))
    lw.update(
        w_in=randw(ks[4], (d, di + C + H), d, dt),
        conv_w=randw(ks[5], (K, C), K, dt),
        conv_b=(0.1 * jax.random.normal(ks[6], (C,), jnp.float32)),
        a_log=jnp.log(jax.random.uniform(ks[7], (H,), jnp.float32,
                                         1.0, 16.0)),
        dt_bias=step + jnp.log(-jnp.expm1(-step)),
        d_skip=jnp.ones((H,), jnp.float32),
        gate_norm=norm_weight(ks[9], (di,)),
        w_out=randw(ks[10], (di, d), di, dt))
    return lw


def plain_globals(m: Sizes, key):
    """The table is drawn at ``1 / embedding_multiplier`` of the scale the
    other matrices have: the multiplier restores a stream of the norm the
    other families' tables give. At the full scale the stream would BE the
    token's own row (norm 12 against sub-layers of 0.22 x their output),
    the tied head would put the input token first at every position, and
    no fault of a mixer could show in what is served."""
    dt = jnp.dtype(m.dtype)
    ks = jax.random.split(key, 2)
    return {"embed": randw(ks[0], (m.vocab_size, m.d_model), m.d_model, dt)
            / jnp.asarray(m.embedding_multiplier, dt),
            "final_norm": norm_weight(ks[1], (m.d_model,))}


_layer_weights = jax.jit(plain_layer, static_argnums=(0, 2))
global_weights = jax.jit(plain_globals, static_argnums=0)


def layer_weights(m: Sizes, key, layer_index: int):
    return _layer_weights(m, key, is_ssm(m, layer_index))


def head_weights(m: Sizes, g) -> dict:
    # 1 / logits_scaling folded into the tied head (module docstring)
    return {"final_norm": g["final_norm"], "eps": m.eps,
            "head": g["embed"].T / jnp.asarray(m.logits_scaling,
                                               g["embed"].dtype)}


# -- the program's own configuration and parameters ----------------------------

def program_config(cfg: dict, m: Sizes):
    from triton_distributed_tpu.models.config import GraniteHybridConfig

    return GraniteHybridConfig(
        model_name=cfg["source"], vocab_size=m.vocab_size, d_model=m.d_model,
        layer_types=m.layer_types, n_heads=m.heads, n_kv_heads=m.kv_heads,
        d_ff=m.mlp_width, mamba_n_heads=m.ssm_heads,
        mamba_d_head=m.ssm_head_width, mamba_d_state=m.ssm_state,
        mamba_d_conv=m.ssm_conv, mamba_n_groups=m.ssm_groups,
        embedding_multiplier=m.embedding_multiplier,
        residual_multiplier=m.residual_multiplier,
        attention_multiplier=m.attention_multiplier,
        logits_scaling=m.logits_scaling, rms_eps=m.eps,
        max_length=m.max_length, dtype=jnp.dtype(m.dtype))


def program_layers(lw, ssm: bool):
    """Layer-stacked plain weights of one kind -> the program's layout."""
    out = {"input_norm": lw["input_norm"], "post_norm": lw["post_norm"],
           "mlp": {"w_gate_up": lw["w_gu"], "w_down": lw["w_d"]}}
    if ssm:
        out["mixer"] = {
            "w_in": lw["w_in"], "conv_w": lw["conv_w"],
            "conv_b": lw["conv_b"], "dt_bias": lw["dt_bias"],
            "a_log": lw["a_log"], "d_skip": lw["d_skip"],
            "norm": lw["gate_norm"], "w_out": lw["w_out"]}
    else:
        out["attn"] = {
            "w_qkv": jnp.concatenate([lw["wq"], lw["wk"], lw["wv"]], axis=-1),
            "w_o": lw["wo"]}
    return out


def program(cfg: dict, m: Sizes, seed: int, mesh, engine_kwargs: dict):
    """The program's configuration object and the whole stack of seeded
    parameters for it, in one jitted call from the seed: the layers of each
    kind stacked over (periods, layers of the kind a period), as the
    program's scan over periods reads them."""
    from jax.sharding import NamedSharding

    from triton_distributed_tpu.models.granite_hybrid import GraniteHybrid

    mcfg = program_config(cfg, m)
    model = GraniteHybrid(mcfg)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             model.param_specs())
    n_periods = m.n_layers // len(model.pattern)

    @functools.partial(jax.jit, out_shardings=shardings)
    def make(gkey, lkeys):
        periods = {}
        for kind in set(model.pattern):
            ssm = kind == "mamba"
            of_kind = jnp.asarray([i for i in range(m.n_layers)
                                   if is_ssm(m, i) == ssm])
            lw = jax.vmap(lambda k: plain_layer(m, k, ssm))(lkeys[of_kind])
            periods[kind] = jax.tree.map(
                lambda a: a.reshape(n_periods, -1, *a.shape[1:]),
                program_layers(lw, ssm))
        return {**plain_globals(m, gkey), "periods": periods}

    return mcfg, make(*keys(seed, m.n_layers))


# -- the plain forward pass of one layer ---------------------------------------

def recurrence(x, step, a, b, c, d_skip):
    """The state-space recurrence, one position after another, from a zero
    state. x (S, H, P); step (S, H) the ``D_t``; a (H,) the negative ``A``;
    b, c (S, H, N) (a group's row repeated to its heads); d_skip (H,).
    Returns y (S, H, P)."""

    def one(state, t):
        x_t, step_t, b_t, c_t = t
        decay = jnp.exp(step_t * a)                               # (H,)
        state = (decay[:, None, None] * state
                 + (step_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        y = jnp.sum(state * c_t[:, None, :], axis=-1)
        return state, y + d_skip[:, None] * x_t

    zero = jnp.zeros((*x.shape[1:], b.shape[-1]), jnp.float32)
    return jax.lax.scan(one, zero, (x, step, b, c))[1]


def ssm_mixer(m: Sizes, x, lw, precision):
    S = x.shape[0]
    di, C = m.d_inner, m.conv_width
    H, P, N, G, K = (m.ssm_heads, m.ssm_head_width, m.ssm_state,
                     m.ssm_groups, m.ssm_conv)
    proj = linear(x, lw["w_in"], precision)
    z, xbc, dt = proj[:, :di], proj[:, di:di + C], proj[:, di + C:]
    padded = jnp.concatenate([jnp.zeros((K - 1, C), jnp.float32), xbc])
    xbc = jax.nn.silu(lw["conv_b"] + sum(
        lw["conv_w"][k] * padded[k:k + S] for k in range(K)))
    xs = xbc[:, :di].reshape(S, H, P)
    b = jnp.repeat(xbc[:, di:di + G * N].reshape(S, G, N), H // G, axis=1)
    c = jnp.repeat(xbc[:, di + G * N:].reshape(S, G, N), H // G, axis=1)
    y = recurrence(xs, jax.nn.softplus(dt + lw["dt_bias"]),
                   -jnp.exp(lw["a_log"]), b, c, lw["d_skip"])
    y = (y.reshape(S, di) * jax.nn.silu(z)).reshape(S, G, di // G)
    y = rms_norm(y, 1.0, m.eps).reshape(S, di) * lw["gate_norm"]
    return linear(y, lw["w_out"], precision)


def attn_mixer(m: Sizes, x, lw, precision):
    S, dh = x.shape[0], m.attn_head_width
    q = linear(x, lw["wq"], precision).reshape(S, m.heads, dh)
    k = linear(x, lw["wk"], precision).reshape(S, m.kv_heads, dh)
    v = linear(x, lw["wv"], precision).reshape(S, m.kv_heads, dh)
    return linear(attention(q, k, v, m.attention_multiplier), lw["wo"],
                  precision)


@functools.partial(jax.jit, static_argnames=("m", "precision", "ssm",
                                             "first"))
def _layer_forward(h, lw, *, m, precision, ssm, first):
    if first:
        h = h * m.embedding_multiplier      # module docstring, departures
    r = m.residual_multiplier
    x = rms_norm(h, lw["input_norm"], m.eps)
    h = h + r * (ssm_mixer if ssm else attn_mixer)(m, x, lw, precision)
    x = rms_norm(h, lw["post_norm"], m.eps)
    gu = linear(x, lw["w_gu"], precision)
    ff = m.mlp_width
    return h + r * linear(jax.nn.silu(gu[:, :ff]) * gu[:, ff:], lw["w_d"],
                          precision)


def layer_forward(h, lw, m: Sizes, layer_index: int, precision: str):
    """One decoder layer over one whole sequence. h: (S, d) float32."""
    return _layer_forward(h, lw, m=m, precision=precision,
                          ssm=is_ssm(m, layer_index), first=layer_index == 0)


# -- operations and bytes -------------------------------------------------------

def n_ssm_layers(m: Sizes) -> int:
    return m.layer_types.count("mamba")


def layer_params(m: Sizes, ssm: bool) -> int:
    """Every parameter of one layer: mixer, SwiGLU and its two norms."""
    d = m.d_model
    mlp = 3 * d * m.mlp_width + 2 * d
    if not ssm:
        return mlp + 2 * (m.heads + m.kv_heads) * m.attn_head_width * d
    return (mlp + d * (m.d_inner + m.conv_width + m.ssm_heads)
            + (m.ssm_conv + 1) * m.conv_width + 3 * m.ssm_heads
            + m.d_inner + m.d_inner * d)


def weight_params(m: Sizes) -> int:
    """The whole model, the tied table once."""
    n = n_ssm_layers(m)
    return (n * layer_params(m, True) + (m.n_layers - n)
            * layer_params(m, False) + m.vocab_size * m.d_model + m.d_model)


def state_bytes_per_slot(m: Sizes) -> int:
    """What one sequence keeps in the layers that keep no rows: the
    recurrence's state in float32 and the convolution's window (the last
    ``d_conv - 1`` inputs, in the served dtype), every such layer."""
    return n_ssm_layers(m) * (
        STATE_ITEMSIZE * m.d_inner * m.ssm_state
        + itemsize(m.dtype) * (m.ssm_conv - 1) * m.conv_width)


def kv_bytes_per_token(m: Sizes) -> int:
    """Keys and values of one token over the layers that keep rows."""
    return (2 * (m.n_layers - n_ssm_layers(m)) * m.kv_heads
            * m.attn_head_width * itemsize(m.dtype))


def ssm_update_min_bytes(m: Sizes, n_rows: float) -> float:
    """The least bytes the one-token state update moves: each row's state
    read and written once in every layer that has one."""
    return (2.0 * STATE_ITEMSIZE * n_ssm_layers(m) * m.d_inner * m.ssm_state
            * float(n_rows))


def ssm_update_flops(m: Sizes, n_rows: float) -> float:
    """Five operations an element of state a token: the decay's product,
    the outer product and its sum, the read-out's product and its sum."""
    return 5.0 * n_ssm_layers(m) * m.d_inner * m.ssm_state * float(n_rows)


def decode_step_min_bytes(m: Sizes, context_lens) -> float:
    """The least bytes one decode step has to move through HBM: every
    weight once (the tied table once: the head reads it whole), each
    decoding row's state read AND written once in every layer that keeps
    one, each row's keys and values once in every layer that keeps rows.
    Activations, the embedding rows and the pool's appends are left out, so
    this is a lower bound."""
    return (itemsize(m.dtype) * weight_params(m)
            + 2.0 * state_bytes_per_slot(m) * len(context_lens)
            + kv_bytes_per_token(m) * float(sum(context_lens)))
