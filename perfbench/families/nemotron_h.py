"""The Nemotron-H family: a decoder whose every layer is ONE mixer under one
norm, the mixer a Mamba-2 recurrence, a layer of routed experts or
grouped-query attention, in an order a pattern string gives and that need
not repeat; served by the program's ``models.nemotron_h.NemotronH``. The
family is the block; a configuration is one model's numbers
(``configs/nemotron-3-nano-30b-a3b-ep8.json`` holds NVIDIA-Nemotron-3-Nano-
30B-A3B's).

The block, as published (HF ``nemotron_h``: ``NemotronHBlock``; Mamba-2: Dao
and Gu, "Transformers are SSMs"; configuration keys in brackets). RMSNorm in
float32 [layer_norm_epsilon], weights multiply; the head is untied
[tie_word_embeddings false]. d = [hidden_size]::

    h_0 = E[ids]
    h <- h + Mixer_i(RMSNorm_i(h))     i = 0 .. [num_hidden_layers] - 1,
                                       ONE mixer a layer, its kind letter i
                                       of [hybrid_override_pattern]
    logits = RMSNorm_f(h) W_head

``M``, Mamba-2: H = [mamba_num_heads] heads of P = [mamba_head_dim] (d_inner
= H x P, whatever [expand] x d would be), G = [n_groups] groups, state N =
[ssm_state_size]::

    [z ; xBC ; dt] = x W_in            (d_inner ; d_inner + 2 G N ; H), no bias
    xBC <- silu(conv(xBC))             causal, depthwise, over the last
                                       [conv_kernel] positions, with bias
    [x_s ; B ; C] = xBC                x_s: H x P; B, C: G x N, a group's
                                       row read by its H / G heads
    D_t = softplus(dt_t + dt_bias);    a_t = exp(D_t A),  A = -exp(A_log)
    S_t = a_t S_{t-1} + D_t x_s,t (x) B_t     (P x N a head, S_0 = 0)
    y_t = S_t C_t + D x_s,t
    out = RMSNorm(y * silu(z)) W_out   (one GROUP's d_inner / G columns at a
                                       time; the gate BEFORE the norm)

``E``, routed experts: ``s = sigmoid(x W_r)`` in float32 over ALL
[n_routed_experts_published] experts; the [num_experts_per_tok] largest of
``s + b`` are chosen ([n_group] 1, [topk_group] 1: no group limit); weights
``s_i / sum of the chosen s`` [norm_topk_prob] times
[routed_scaling_factor]; ``expert_e(x) = W_down,e relu(W_up,e x)^2`` at
width [moe_intermediate_size], TWO matrices and no gate [mlp_hidden_act
relu2]; ``y = sum of w_e expert_e(x) + shared(x)``, the shared expert the
same form at [moe_shared_expert_intermediate_size], unweighted.
``*``, attention: [num_attention_heads] query heads over
[num_key_value_heads] key heads of [head_dim], no bias, NO position
embedding (the published modelling code applies none; [rope_theta] and
[partial_rotary_factor] are inert), scores ``q . k / sqrt(head_dim)``,
causal softmax, ``o W_o``.

computed here as written: ONE sequential scan over the positions of the
sequence for the recurrence, every held expert over every token for the
experts, float32, no chunking, no kernel, no state kept anywhere.
[chunk_size] is the training kernel's blocking and changes no result.

ONE CHIP'S SHARE, as ``families/deepseek_v3.py`` states it: the
configuration names the routed experts held here (``n_routed_experts_held``
of ``n_routed_experts_published``, ids from ``n_routed_experts_lo``); the
sum runs over the chosen experts among those held, the weights are still
normalised over all chosen, and what the absent experts would add is left
out, here and in the program alike. Expert e's matrices come from a key
folded with e, so every share of one seed holds the same model.

Departures: the router's product is taken in float32 in every ``precision``
(the block states it so; the control lowers the linear layers around it).
Seeded, as no ``1 / fan_in`` rule covers them (the configuration's
``assumed`` says so): ``A_log = log U(1, 16)``, ``dt_bias`` the inverse
softplus of a step log-uniform in [[time_step_min], [time_step_max]] and at
least [time_step_floor] (Mamba-2's own initialisation), ``D`` = 1, the
convolution's bias 0.1 N(0, 1), the selection bias ``b`` N(0, 0.01^2), and
and the scale of the stream (``branch_out`` says why each): the matrix that
ends a residual branch at ``1 / sqrt(n_layers)`` [rescale_prenorm_residual]
beside a table of N(0, 1) entries, or bfloat16's rounding grows with depth
past what a comparison can see through; the relu² MLPs' down matrices and
the recurrence's out-projection with zero column sums, or every token
routes alike.
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
BIAS_STD = 0.01         # the selection bias is seeded: N(0, BIAS_STD ** 2)
KINDS = {"M": "ssm", "E": "experts", "*": "attention"}


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab_size: int
    d_model: int            # hidden_size
    pattern: str            # hybrid_override_pattern
    heads: int              # num_attention_heads
    kv_heads: int           # num_key_value_heads
    head_width: int         # head_dim
    ssm_heads: int          # mamba_num_heads
    ssm_head_width: int     # mamba_head_dim
    ssm_state: int          # ssm_state_size
    ssm_conv: int           # conv_kernel
    ssm_groups: int         # n_groups
    expert_width: int       # moe_intermediate_size
    shared_width: int       # moe_shared_expert_intermediate_size
    router_width: int       # n_routed_experts_published
    held: int               # n_routed_experts_held
    lo: int                 # n_routed_experts_lo
    topk: int               # num_experts_per_tok
    scaling: float          # routed_scaling_factor
    norm_topk: bool         # norm_topk_prob
    step_range: tuple       # time_step_min, time_step_max, time_step_floor
    eps: float              # layer_norm_epsilon
    max_length: int         # max_position_embeddings, as run
    dtype: str              # torch_dtype

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_width

    @property
    def conv_width(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    def count(self, kind: str) -> int:
        """Layers of a kind (``"ssm"``, ``"experts"``, ``"attention"``)."""
        return sum(KINDS[ch] == kind for ch in self.pattern)


def sizes(cfg: dict) -> Sizes:
    said = (cfg["mlp_hidden_act"], cfg["mamba_hidden_act"], cfg["n_group"],
            cfg["topk_group"], cfg["n_shared_experts"],
            cfg["tie_word_embeddings"], cfg["use_conv_bias"],
            cfg["mamba_proj_bias"], cfg["attention_bias"], cfg["mlp_bias"],
            cfg["use_bias"])
    if said != ("relu2", "silu", 1, 1, 1, False, True, False, False, False,
                False):
        raise ValueError(f"this family is the block in its docstring; the "
                         f"configuration states another: {said}")
    if cfg["n_routed_experts"] != cfg["n_routed_experts_held"]:
        raise ValueError("n_routed_experts is the count held here")
    m = Sizes(
        vocab_size=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        pattern=str(cfg["hybrid_override_pattern"]),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        head_width=int(cfg["head_dim"]),
        ssm_heads=int(cfg["mamba_num_heads"]),
        ssm_head_width=int(cfg["mamba_head_dim"]),
        ssm_state=int(cfg["ssm_state_size"]),
        ssm_conv=int(cfg["conv_kernel"]), ssm_groups=int(cfg["n_groups"]),
        expert_width=int(cfg["moe_intermediate_size"]),
        shared_width=int(cfg["moe_shared_expert_intermediate_size"]),
        router_width=int(cfg["n_routed_experts_published"]),
        held=int(cfg["n_routed_experts_held"]),
        lo=int(cfg["n_routed_experts_lo"]),
        topk=int(cfg["num_experts_per_tok"]),
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        step_range=(float(cfg["time_step_min"]), float(cfg["time_step_max"]),
                    float(cfg["time_step_floor"])),
        eps=float(cfg["layer_norm_epsilon"]),
        max_length=int(cfg["max_position_embeddings"]),
        dtype=str(cfg["torch_dtype"]))
    if (m.n_layers != int(cfg["num_hidden_layers"])
            or set(m.pattern) - set(KINDS)):
        raise ValueError("hybrid_override_pattern disagrees with the depth "
                         "stated beside it, or names a kind of layer this "
                         "family has not")
    return m


# -- the seeded plain weights --------------------------------------------------

def kind_of(m: Sizes, layer_index: int) -> str:
    return KINDS[m.pattern[layer_index]]


def branch_out(m: Sizes, key, shape, fan_in: int, *, zero_sums: bool):
    """The matrix ``(in, out)`` that ends a residual branch (the recurrence's
    out-projection, attention's ``W_o``, the down matrix of a relu² MLP):
    N(0, 1 / fan_in) at ``1 / sqrt(n_layers)`` of its scale, the rule the
    configuration states for such a projection [rescale_prenorm_residual];
    with ``zero_sums``, the mean of each column taken out first.

    Why the scale. With every branch at full scale the stream after l
    layers is l outputs of which each new one is a 1/sqrt(l) part, and
    rounding grows with depth faster than the stream: served in bfloat16
    against this file's float32 reference, read on the chip at the
    published widths, ``gap_mean`` 0.0024 after 7 layers, 0.011 after 14,
    0.040 after 28 and 0.10 after all 52 (``top1_share`` 0.64; a mere
    re-tiling of one kernel moved the 7-layer reading from 0.0024 to
    0.0041): a comparison that could see no fault under that noise. With
    the branches at 1/sqrt(52) beside a token row of N(0, 1) entries
    (``plain_globals``) the 52 branches TOGETHER add as much to the stream
    as the row, one layer moves it by a seventh, and a trained model's
    stability under bfloat16 is what the comparison starts from.

    Why the sums. Such a branch's last product reads an input of POSITIVE
    mean (``relu(z)^2`` of a unit normal has mean 0.5; the recurrence's
    gated ``y * silu(z)`` has one too), so a plain draw adds ONE fixed
    vector (the mean times the column sums) to every token's stream in
    every such layer, which no trained model does. Read with this file's
    reference at full scale, 32 sequences' last tokens: the cosine between
    two tokens' streams is 0.65 after 8 layers, every token picks the same
    few experts (45 of 128 are picked at all, 5 of the 16 held), and on the
    chip the held experts' products moved a third of the bytes that a
    balanced router, as a trained bias makes it, gives them. With zero
    column sums in the relu² MLPs alone the cosine still reaches 0.28 by
    layer 51 and 32 tokens touch 10.3 of the 16 held; in the recurrence's
    out-projection too, 0.009 and 12.35 (a uniform choice: 12.56; the chip
    counted 12.70 a layer a step). Attention's ``W_o`` reads values of
    zero mean and keeps its sums."""
    w = randw(key, shape, fan_in, jnp.float32)
    if zero_sums:
        w = w - jnp.mean(w, axis=0, keepdims=True)
    return (w * m.n_layers ** -0.5).astype(jnp.dtype(m.dtype))


def plain_layer(m: Sizes, key, kind: str):
    """One layer's weights as the architecture names them, each matrix
    ``(in, out)`` in the served dtype; the norm, the router, its bias and
    the recurrence's own parameters in float32. Expert e's matrices come
    from the key folded with e."""
    dt = jnp.dtype(m.dtype)
    d = m.d_model
    ks = jax.random.split(key, 12)
    lw = {"norm": norm_weight(ks[0], (d,))}
    if kind == "attention":
        q, kv = m.heads * m.head_width, m.kv_heads * m.head_width
        lw.update(wq=randw(ks[1], (d, q), d, dt),
                  wk=randw(ks[2], (d, kv), d, dt),
                  wv=randw(ks[3], (d, kv), d, dt),
                  wo=branch_out(m, ks[4], (q, d), q, zero_sums=False))
    elif kind == "experts":
        ffe, ffs = m.expert_width, m.shared_width
        ids = m.lo + jnp.arange(m.held)
        lw.update(
            router=randw(ks[1], (d, m.router_width), d, dt).astype(
                jnp.float32),
            bias=BIAS_STD * jax.random.normal(ks[2], (m.router_width,),
                                              jnp.float32),
            e_up=jax.vmap(lambda e: randw(
                jax.random.fold_in(ks[3], e), (d, ffe), d, dt))(ids),
            e_d=jax.vmap(lambda e: branch_out(
                m, jax.random.fold_in(ks[4], e), (ffe, d), ffe,
                zero_sums=True))(ids),
            s_up=randw(ks[5], (d, ffs), d, dt),
            s_d=branch_out(m, ks[6], (ffs, d), ffs, zero_sums=True))
    else:
        di, C, H, K = m.d_inner, m.conv_width, m.ssm_heads, m.ssm_conv
        lo, hi, floor = m.step_range
        step = jnp.maximum(floor, jnp.exp(jax.random.uniform(
            ks[5], (H,), jnp.float32, math.log(lo), math.log(hi))))
        lw.update(
            w_in=randw(ks[1], (d, di + C + H), d, dt),
            conv_w=randw(ks[2], (K, C), K, dt),
            conv_b=0.1 * jax.random.normal(ks[3], (C,), jnp.float32),
            a_log=jnp.log(jax.random.uniform(ks[4], (H,), jnp.float32,
                                             1.0, 16.0)),
            dt_bias=step + jnp.log(-jnp.expm1(-step)),
            d_skip=jnp.ones((H,), jnp.float32),
            gate_norm=norm_weight(ks[6], (di,)),
            w_out=branch_out(m, ks[7], (di, d), di, zero_sums=True))
    return lw


def plain_globals(m: Sizes, key):
    """The table's entries are N(0, 1), a row of norm sqrt(d): what the
    branches at ``1 / sqrt(n_layers)`` add up to (``branch_out``). The head
    is untied, so the token's own row in the stream puts no token first."""
    dt = jnp.dtype(m.dtype)
    ks = jax.random.split(key, 3)
    return {"embed": randw(ks[0], (m.vocab_size, m.d_model), 1, dt),
            "final_norm": norm_weight(ks[1], (m.d_model,)),
            "lm_head": randw(ks[2], (m.d_model, m.vocab_size), m.d_model,
                             dt)}


_layer_weights = jax.jit(plain_layer, static_argnums=(0, 2))
global_weights = jax.jit(plain_globals, static_argnums=0)


def layer_weights(m: Sizes, key, layer_index: int):
    return _layer_weights(m, key, kind_of(m, layer_index))


def head_weights(m: Sizes, g) -> dict:
    return {"final_norm": g["final_norm"], "eps": m.eps,
            "head": g["lm_head"]}


# -- the program's own configuration and parameters ----------------------------

def program_config(cfg: dict, m: Sizes):
    from triton_distributed_tpu.models.config import NemotronHConfig

    return NemotronHConfig(
        model_name=cfg["source"], vocab_size=m.vocab_size, d_model=m.d_model,
        pattern=m.pattern, n_heads=m.heads, n_kv_heads=m.kv_heads,
        head_dim=m.head_width, mamba_n_heads=m.ssm_heads,
        mamba_d_head=m.ssm_head_width, mamba_d_state=m.ssm_state,
        mamba_d_conv=m.ssm_conv, mamba_n_groups=m.ssm_groups,
        moe_d_ff=m.expert_width, shared_d_ff=m.shared_width,
        n_experts=m.router_width, n_experts_per_tok=m.topk,
        routed_scaling_factor=m.scaling, norm_topk_prob=m.norm_topk,
        experts_held=m.held, experts_lo=m.lo, rms_eps=m.eps,
        max_length=m.max_length, dtype=jnp.dtype(m.dtype))


def program_layer(lw, kind: str, stored_width: int):
    """ONE layer's plain weights -> the program's layout of the kind. The
    experts' matrices are zero-padded to the width the program stores them
    at (a lane multiple, so that its grouped product tiles): a zero column
    of ``w_up`` against a zero row of ``w_down`` adds nothing."""
    if kind == "ssm":
        return {"norm": lw["norm"], "mixer": {
            "w_in": lw["w_in"], "conv_w": lw["conv_w"],
            "conv_b": lw["conv_b"], "dt_bias": lw["dt_bias"],
            "a_log": lw["a_log"], "d_skip": lw["d_skip"],
            "norm": lw["gate_norm"], "w_out": lw["w_out"]}}
    if kind == "attention":
        return {"norm": lw["norm"], "attn": {
            "w_qkv": jnp.concatenate([lw["wq"], lw["wk"], lw["wv"]], axis=-1),
            "w_o": lw["wo"]}}
    pad = stored_width - lw["e_up"].shape[-1]
    return {"norm": lw["norm"], "moe": {
        "router": lw["router"], "bias": lw["bias"],
        "w_up": jnp.pad(lw["e_up"], ((0, 0), (0, 0), (0, pad))),
        "w_down": jnp.pad(lw["e_d"], ((0, 0), (0, pad), (0, 0))),
        "shared": {"w_up": lw["s_up"], "w_down": lw["s_d"]}}}


# a kind's name in the program's parameter tree, the largest first
PROGRAM_KINDS = {"experts": "moe", "ssm": "mamba", "attention": "attention"}


def program(cfg: dict, m: Sizes, seed: int, mesh, engine_kwargs: dict):
    """The program's configuration object and the whole stack of seeded
    parameters for it, the layers of each kind stacked in their order in
    the pattern, as the program's walk reads them. One jitted call a kind,
    one layer after another inside it (``lax.map``: a layer's float32
    draws and its padding are temporaries of ONE layer, 0.3 GB, not of the
    stack's 8.5 GB)."""
    from jax.sharding import NamedSharding

    from triton_distributed_tpu.models.nemotron_h import NemotronH

    mcfg = program_config(cfg, m)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             NemotronH(mcfg).param_specs())
    gkey, lkeys = keys(seed, m.n_layers)
    layers = {}
    for kind, name in PROGRAM_KINDS.items():
        of_kind = [i for i in range(m.n_layers) if kind_of(m, i) == kind]
        if not of_kind:
            continue

        @functools.partial(jax.jit,
                           out_shardings=shardings["layers"][name])
        def make(ks, kind=kind):
            return jax.lax.map(lambda k: program_layer(
                plain_layer(m, k, kind), kind, mcfg.moe_d_ff_stored), ks)

        layers[name] = make(lkeys[jnp.asarray(of_kind)])
    shardings.pop("layers")
    return mcfg, {**jax.jit(functools.partial(plain_globals, m),
                            out_shardings=shardings)(gkey),
                  "layers": layers}


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
    S, dh = x.shape[0], m.head_width
    q = linear(x, lw["wq"], precision).reshape(S, m.heads, dh)
    k = linear(x, lw["wk"], precision).reshape(S, m.kv_heads, dh)
    v = linear(x, lw["wv"], precision).reshape(S, m.kv_heads, dh)
    return linear(attention(q, k, v, dh ** -0.5), lw["wo"], precision)


def relu2_mlp(x, w_up, w_d, precision):
    return linear(jnp.square(jax.nn.relu(linear(x, w_up, precision))), w_d,
                  precision)


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
        j, w_up, w_d = expert
        w_j = jnp.sum(jnp.where(ids == m.lo + j, w, 0.0), axis=-1)   # (S,)
        return y + w_j[:, None] * relu2_mlp(x, w_up, w_d, precision), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(x),
                        (jnp.arange(m.held), lw["e_up"], lw["e_d"]))
    return y


def experts_mixer(m: Sizes, x, lw, precision):
    return (relu2_mlp(x, lw["s_up"], lw["s_d"], precision)
            + routed_part(m, x, lw, precision))


MIXERS = {"ssm": ssm_mixer, "experts": experts_mixer, "attention": attn_mixer}


@functools.partial(jax.jit, static_argnames=("m", "precision", "kind"))
def _layer_forward(h, lw, *, m, precision, kind):
    return h + MIXERS[kind](m, rms_norm(h, lw["norm"], m.eps), lw, precision)


def layer_forward(h, lw, m: Sizes, layer_index: int, precision: str):
    """One decoder layer over one whole sequence. h: (S, d) float32."""
    return _layer_forward(h, lw, m=m, precision=precision,
                          kind=kind_of(m, layer_index))


# -- operations and bytes -------------------------------------------------------

def expert_params(m: Sizes) -> int:
    """TWO matrices an expert: up and down, no gate."""
    return 2 * m.d_model * m.expert_width


def layer_params(m: Sizes, kind: str) -> int:
    """Every parameter one layer of a kind holds HERE, its norm with it
    (the router counts one parameter an output, whatever its dtype)."""
    d = m.d_model
    if kind == "attention":
        return d + 2 * (m.heads + m.kv_heads) * m.head_width * d
    if kind == "experts":
        return (d + m.held * expert_params(m) + 2 * d * m.shared_width
                + (d + 1) * m.router_width)
    return (d + d * (m.d_inner + m.conv_width + m.ssm_heads)
            + (m.ssm_conv + 1) * m.conv_width + 3 * m.ssm_heads
            + m.d_inner + m.d_inner * d)


def weight_params(m: Sizes) -> int:
    """What this chip holds: every layer, the table, the head, the final
    norm."""
    return (sum(m.count(k) * layer_params(m, k) for k in MIXERS)
            + 2 * m.vocab_size * m.d_model + m.d_model)


def fixed_weight_bytes(m: Sizes) -> float:
    """Every weight a decode step reads whatever its rows are, once: all the
    layers hold but the routed experts (Mamba-2, attention, the shared
    expert, the router in float32) and the head; NOT the embedding table, of
    which a step reads one row a token."""
    b = itemsize(m.dtype)
    router = m.count("experts") * m.d_model * m.router_width
    routed = m.count("experts") * m.held * expert_params(m)
    return (b * (weight_params(m) - m.vocab_size * m.d_model - routed
                 - router) + 4 * router)


def state_bytes_per_slot(m: Sizes) -> int:
    """What one sequence keeps in the layers that keep no rows: the
    recurrence's state in float32 and the convolution's window (the last
    ``conv_kernel - 1`` inputs, in the served dtype), every such layer."""
    return m.count("ssm") * (
        STATE_ITEMSIZE * m.d_inner * m.ssm_state
        + itemsize(m.dtype) * (m.ssm_conv - 1) * m.conv_width)


def kv_bytes_per_token(m: Sizes) -> int:
    """Keys and values of one token over the layers that keep rows."""
    return (2 * m.count("attention") * m.kv_heads * m.head_width
            * itemsize(m.dtype))


def ssm_update_min_bytes(m: Sizes, n_rows: float) -> float:
    """The least bytes the one-token state update moves: each row's state
    read and written once in every layer that has one."""
    return (2.0 * STATE_ITEMSIZE * m.count("ssm") * m.d_inner * m.ssm_state
            * float(n_rows))


def ssm_update_flops(m: Sizes, n_rows: float) -> float:
    """Five operations an element of state a token: the decay's product,
    the outer product and its sum, the read-out's product and its sum."""
    return 5.0 * m.count("ssm") * m.d_inner * m.ssm_state * float(n_rows)


def moe_ffn_min_bytes(m: Sizes, experts_touched: float) -> float:
    """Routed experts only: the TWO matrices of every expert that got a
    row, at their published width (the program stores them padded to a lane
    multiple; the padding is not counted), summed over the expert layers."""
    return itemsize(m.dtype) * expert_params(m) * float(experts_touched)


def moe_ffn_flops(m: Sizes, pairs: float) -> float:
    return 2.0 * expert_params(m) * float(pairs)


def moe_expected(m: Sizes, rows: float) -> tuple[float, float]:
    """(pairs held, experts touched) a step of ``rows`` live tokens gives
    over all expert layers IF every routed expert is as likely as another
    (seeded weights and a small bias make it nearly so): each row picks a
    given expert with probability topk / router width."""
    p = m.topk / m.router_width
    layers = m.count("experts")
    return (layers * rows * p * m.held,
            layers * m.held * (1.0 - (1.0 - p) ** rows))


def decode_step_min_bytes(m: Sizes, context_lens) -> float:
    """The least bytes one decode step has to move through HBM: every
    weight outside the routed experts once (``fixed_weight_bytes``), the two
    matrices of every held expert that the step's rows touch (an
    EXPECTATION, ``moe_expected``: 12.6 of 16 a layer for 32 rows; the
    program's grouped product fetches no untouched expert), each decoding
    row's state read AND written once in every layer that keeps one, each
    row's keys and values once in every layer that keeps rows. Activations,
    the embedding rows and the pool's appends are left out.

    An entry of ``context_lens`` is one row's context. A reader that hands
    over the SUM of a step's contexts as one entry
    (``layer_metrics/decode_step_roofline.py``) is counted the fewest rows
    that could hold it, none longer than the configuration runs
    (``max_length``): fewer rows than decoded, so fewer states and fewer
    experts than were moved, and a share that reads low, never high."""
    rows = sum(max(1, -(-int(n) // m.max_length)) for n in context_lens)
    return (fixed_weight_bytes(m)
            + moe_ffn_min_bytes(m, moe_expected(m, rows)[1])
            + 2.0 * state_bytes_per_slot(m) * rows
            + kv_bytes_per_token(m) * float(sum(context_lens)))
