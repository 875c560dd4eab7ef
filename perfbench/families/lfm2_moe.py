"""The LFM2-MoE family: a decoder whose layer is an OPERATOR and an FFN, the
operator a gated short convolution (30 of 40 layers in LFM2-24B-A2B) or
rope'd grouped-query attention with a per-head norm (10), the FFN a dense
SwiGLU (the first two layers) or sigmoid-routed SwiGLU experts chosen under a
bias, with no shared expert; served by the program's
``models.exaone_moe.ExaoneMoe`` walk with its third kind of operator
(``models.config.Lfm2MoeConfig``). The family is the block; a configuration
is one model's numbers (``configs/lfm2-24b-a2b-ep8.json`` holds
LFM2-24B-A2B's).

The block (HF ``modeling_lfm2_moe``; configuration keys in brackets). RMSNorm
in float32 [norm_eps], weights multiply; the head is the embedding table
[tie_word_embeddings]. d = [hidden_size]; layer l's operator is
[layer_types][l], its FFN dense where l < [num_dense_layers]::

    h = x + Op_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))
    logits = RMSNorm(y_last) E^T

    conv:   [B ; C ; X] = u W_in           (d ; d ; d), no bias [conv_bias]
            z_t = B_t * X_t
            c_t = w_0 z_{t-2} + w_1 z_{t-1} + w_2 z_t    depthwise, causal,
                    [conv_L_cache] = 3 taps a channel, no bias, NO activation
            Op  = (C_t * c_t) W_out        all a sequence keeps: z_{t-2},
                                           z_{t-1}
    full_attention:
            q,k,v = W_q u, W_k u, W_v u    [num_attention_heads] /
                    [num_key_value_heads] / the same, heads of d / heads;
                    no bias
            q,k <- RMSNorm over the head dim, one weight a projection
            q,k <- rope(position, [rope_parameters.rope_theta]), rotate-half,
                    every dim of the head
            Op  = W_o softmax(q k^T / sqrt(head_dim) + causal mask) v
    dense:  FFN = W_2 (silu(W_1 n) * W_3 n)        width [intermediate_size]
    sparse: s = sigmoid(W_r n) in float32 over ALL [num_experts_published]
            experts; the [num_experts_per_tok] largest of s + b are chosen
            [use_expert_bias]; their weights are the UNBIASED s over their
            sum + 1e-6 [norm_topk_prob] times [routed_scaling_factor];
            FFN = sum of w_e SwiGLU_e(n), width [moe_intermediate_size]

Computed here as written, float32, no kernel, no cache, no batching: the
convolution as a SUM OF THREE SHIFTED PRODUCTS over the whole sequence, every
held expert over every token, attention over blocks of queries
(``reference.attention``).

ONE CHIP'S SHARE, as ``families/deepseek_v3.py`` states it: the configuration
names the routed experts held here (``num_experts_held`` of
``num_experts_published``, ids from ``num_experts_lo``; ``num_experts`` is
the count held and is listed in ``reduced``); the sum runs over the chosen
experts among those held, the weights are still normalised over all chosen,
and what the absent experts would add is left out, here and in the program
alike. Expert e's matrices come from a key folded with e, so every share of
one seed holds the same model.

Departures. The router's product is taken in float32 in every ``precision``
(the control lowers the linear layers around it). The normaliser's epsilon is
HF's 1e-6 here and 1e-20 in the program's ``HeldExpertsMoE`` (a sum of four
sigmoids is about 2: a relative 5e-7, which the comparison holds). ``z`` is
kept in float32 here; the program rounds it to the served dtype where it
enters the window.

Seeded (the configuration's ``assumed`` repeats each). Matrices N(0, 1 /
fan_in) in the served dtype, the taps N(0, 1 / 3), norm weights 1 + 0.1 N(0,
1), and the table N(0, 1 / d) too: a row of norm 1, because the head is the
table, and a stream that still was its token's own row at the last layer
would put the INPUT token first at every position
(``families/granite_hybrid.py`` read that). Four draws are not 1 / fan_in:

- THE MATRIX THAT ENDS A RESIDUAL BRANCH IS DRAWN AT 1 / sqrt(2 n_layers),
  LAYER 0'S OPERATOR AT FULL SCALE (``branch_out`` says why, with the
  readings): one layer then moves the stream by a ninth, and bfloat16's
  rounding is what a trained model's is.
- THE ROUTER'S ROWS ARE DEALT EVENLY TO THE EIGHT CHIPS
  (``families/exaone_moe.seeded_router``: chip 0's eight rows N(0, 1 / d),
  each other chip's an orthogonal remix of them with the same sum and Gram
  matrix), so that this chip's share of a run's routed pairs is a trained
  router's balance and not one draw a seed.
- THE SELECTION BIAS IS N(0, ``BIAS_STD``^2), 0.01, EACH CHIP'S EIGHT
  VALUES SUMMING TO ZERO (``seeded_bias``): enough to change the choice at
  near ties, as a trained bias does, and not this chip's share of the pairs
  (drawn one by one, read on the chip: ``itl_p95_ms`` spreads by 0.68% over
  six seeds where half its bound is 0.5%).
- THE QK NORMS' WEIGHTS ARE SEEDED ABOUT ``QK_NORM_MEAN`` = 2 (scores of
  spread 4 over the ~1,800 keys of a context): at unit spread a softmax over
  that many keys is an average of hundreds of value rows, which a key one
  position off, or a rope angle, does not move (``families/exaone_moe.py``,
  ``families/smallthinker.py`` read both).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from perfbench.families.exaone_moe import (  # noqa: F401 (the readers' counts)
    attn_params,
    expert_params,
    moe_expected,
    moe_ffn_flops,
    moe_ffn_min_bytes,
    seeded_router,
    swiglu,
)
from perfbench.peaks import itemsize
from perfbench.reference import attention, linear, rms_norm, rope
from perfbench.weights import keys, norm_weight, randw

BIAS_STD = 0.01         # the selection bias is seeded: N(0, BIAS_STD ** 2)
QK_NORM_MEAN = 2.0      # the QK norms' weights are seeded about this
NORM_EPS = 1e-6         # HF's, under the sum of the chosen scores


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab_size: int
    d_model: int            # hidden_size
    n_layers: int           # num_hidden_layers
    conv: tuple             # layer_types == "conv", a layer
    dense_layers: int       # num_dense_layers
    taps: int               # conv_L_cache
    heads: int              # num_attention_heads
    kv_heads: int           # num_key_value_heads
    head_dim: int
    dense_width: int        # intermediate_size
    expert_width: int       # moe_intermediate_size
    router_width: int       # num_experts_published
    held: int               # num_experts_held
    lo: int                 # num_experts_lo
    topk: int               # num_experts_per_tok
    scaling: float          # routed_scaling_factor
    norm_topk: bool         # norm_topk_prob
    theta: float            # rope_parameters.rope_theta
    eps: float              # norm_eps
    max_length: int         # max_position_embeddings, as run
    dtype: str              # torch_dtype

    @property
    def n_conv(self) -> int:
        return sum(self.conv)

    @property
    def n_full(self) -> int:
        return self.n_layers - self.n_conv

    @property
    def n_sparse(self) -> int:
        return self.n_layers - self.dense_layers

    @property
    def row_bytes(self) -> int:
        """One token's K and V rows of one attention layer."""
        return 2 * self.kv_heads * self.head_dim * itemsize(self.dtype)


def sizes(cfg: dict) -> Sizes:
    n = int(cfg["num_hidden_layers"])
    kinds = cfg["layer_types"]
    if len(kinds) != n or set(kinds) - {"conv", "full_attention"}:
        raise ValueError("layer_types names each of num_hidden_layers "
                         "layers 'conv' or 'full_attention'")
    if cfg["conv_bias"] or not cfg["use_expert_bias"] \
            or not cfg["tie_word_embeddings"]:
        raise ValueError("this family takes no convolution bias, a "
                         "selection bias and a tied head; the configuration "
                         "states another")
    if cfg["rope_parameters"].get("rope_type", "default") != "default":
        raise ValueError("this family takes plain rope")
    if cfg["num_experts"] != cfg["num_experts_held"]:
        raise ValueError("num_experts is the count held here")
    return Sizes(
        vocab_size=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layers=n, conv=tuple(k == "conv" for k in kinds),
        dense_layers=int(cfg["num_dense_layers"]),
        taps=int(cfg["conv_L_cache"]),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        dense_width=int(cfg["intermediate_size"]),
        expert_width=int(cfg["moe_intermediate_size"]),
        router_width=int(cfg["num_experts_published"]),
        held=int(cfg["num_experts_held"]), lo=int(cfg["num_experts_lo"]),
        topk=int(cfg["num_experts_per_tok"]),
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        theta=float(cfg["rope_parameters"]["rope_theta"]),
        eps=float(cfg["norm_eps"]),
        max_length=int(cfg["max_position_embeddings"]),
        dtype=str(cfg["torch_dtype"]))


# -- the seeded plain weights --------------------------------------------------

def plain_norms(m: Sizes, key):
    ks = jax.random.split(jax.random.fold_in(key, 0), 2)
    return {"input_norm": norm_weight(ks[0], (m.d_model,)),
            "post_norm": norm_weight(ks[1], (m.d_model,))}


def branch_out(m: Sizes, key, shape, fan_in: int, anchor=False):
    """The matrix ``(in, out)`` that ends a residual branch (an operator's
    out-projection, an FFN's down matrix): N(0, 1 / fan_in) at ``1 / sqrt(2
    n_layers)`` of its scale, but for the ``anchor``, LAYER 0'S OPERATOR, at
    full scale.

    Why. With every branch at full scale under a table of small rows the
    stream after l branches is l outputs of which each new one is a
    1 / sqrt(l) part, and bfloat16's rounding is amplified layer after layer
    (30 of the 40 operators are a product of THREE projections of the
    stream): read on the chip at the published widths, the program against
    this file's float32 reference, ``gap_mean`` 0.18-0.21 and ``top1_share``
    0.42-0.48 with the QK norms' weights about 2 and still 0.061-0.074 /
    0.64-0.68 about 1.41 and 0.063 / 0.68 about 1; and THIS reference with
    every linear layer's operands and result rounded to bfloat16, in the
    program's place (no kernel, no cache, no batching), reads the same
    against itself in float32: 0.210 / 0.40, 0.090 / 0.62, 0.091 / 0.61
    (the CPU, 1,536 positions): the arithmetic's own rounding, under which a
    comparison sees no fault. ``families/nemotron_h.py`` met the same at 52
    layers and drew its branches at 1 / sqrt(52) beside a table of N(0, 1)
    entries, so that all branches TOGETHER add what a token's row carries
    and one layer moves the stream by a seventh. The head here is the table
    [tie_word_embeddings]: a row as large as the stream would put the INPUT
    token first at every position, so the row stays small (norm 1) and what
    stands in its place is layer 0's operator at full scale (norm 45, a
    function of the last three tokens), the other 79 branches together
    adding as much: the same witness then reads 0.0013 / 0.945 at QK norms
    about 2 and 0.0005 / 0.97 about 1.41."""
    scale = jnp.where(anchor, 1.0, (2 * m.n_layers) ** -0.5)
    return (randw(key, shape, fan_in, jnp.float32) * scale).astype(
        jnp.dtype(m.dtype))


def plain_operator(m: Sizes, key, conv: bool, anchor=False):
    """The operator's weights as the architecture names them, each matrix
    ``(in, out)`` in the served dtype, norms in float32. ``anchor`` (bool,
    traced or not): this is layer 0's operator (``branch_out``)."""
    dt, d = jnp.dtype(m.dtype), m.d_model
    ks = jax.random.split(jax.random.fold_in(key, 1), 6)
    if conv:
        return {"w_in": randw(ks[0], (d, 3 * d), d, dt),
                "conv_w": randw(ks[1], (m.taps, d), m.taps, dt),
                "w_out": branch_out(m, ks[2], (d, d), d, anchor)}
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    return {"wq": randw(ks[0], (d, q), d, dt),
            "wk": randw(ks[1], (d, kv), d, dt),
            "wv": randw(ks[2], (d, kv), d, dt),
            "wo": branch_out(m, ks[3], (q, d), q, anchor),
            "q_norm": norm_weight(ks[4], (m.head_dim,)) + (QK_NORM_MEAN - 1),
            "k_norm": norm_weight(ks[5], (m.head_dim,)) + (QK_NORM_MEAN - 1)}


def seeded_bias(m: Sizes, key):
    """The selection bias ``(router width,)``, float32: N(0, ``BIAS_STD``^2)
    and, where the experts are dealt to several chips in groups of ``held``
    (``seeded_router``'s condition), EACH CHIP'S ``held`` VALUES SUMMING TO
    ZERO: a bias moves an expert's chance of being chosen in proportion to
    it, so a chip's share of the routed pairs moves with the sum of its
    experts' biases, which a trained bias (it IS the balance's own
    correction) holds level and eight values drawn by themselves do not."""
    bias = BIAS_STD * jax.random.normal(key, (m.router_width,), jnp.float32)
    chips = m.router_width // m.held
    if chips == 1 or m.held < 8 or m.router_width % m.held:
        return bias
    groups = bias.reshape(chips, m.held)
    return (groups - jnp.mean(groups, axis=1, keepdims=True)).reshape(-1)


def plain_ffn(m: Sizes, key, sparse: bool):
    """Gate and up halves are made as one matrix (``*_gu``, gate first).
    Expert e's matrices come from the key folded with e; the router and its
    bias are float32."""
    dt, d = jnp.dtype(m.dtype), m.d_model
    ks = jax.random.split(jax.random.fold_in(key, 2), 4)
    if not sparse:
        ff = m.dense_width
        return {"w_gu": randw(ks[0], (d, 2 * ff), d, dt),
                "w_d": branch_out(m, ks[1], (ff, d), ff)}
    ffe, ids = m.expert_width, m.lo + jnp.arange(m.held)
    return {"router": seeded_router(m, ks[0]),
            "bias": seeded_bias(m, ks[1]),
            "e_gu": jax.vmap(lambda e: randw(
                jax.random.fold_in(ks[2], e), (d, 2 * ffe), d, dt))(ids),
            "e_d": jax.vmap(lambda e: branch_out(
                m, jax.random.fold_in(ks[3], e), (ffe, d), ffe))(ids)}


def plain_layer(m: Sizes, key, conv: bool, sparse: bool, anchor: bool):
    return {**plain_norms(m, key), **plain_operator(m, key, conv, anchor),
            **plain_ffn(m, key, sparse)}


def plain_globals(m: Sizes, key):
    ks = jax.random.split(key, 2)
    return {"embed": randw(ks[0], (m.vocab_size, m.d_model), m.d_model,
                           jnp.dtype(m.dtype)),
            "final_norm": norm_weight(ks[1], (m.d_model,))}


_layer_weights = jax.jit(plain_layer, static_argnums=(0, 2, 3, 4))
global_weights = jax.jit(plain_globals, static_argnums=0)


def is_sparse(m: Sizes, layer_index: int) -> bool:
    return layer_index >= m.dense_layers


def layer_weights(m: Sizes, key, layer_index: int):
    return _layer_weights(m, key, m.conv[layer_index],
                          is_sparse(m, layer_index), layer_index == 0)


def head_weights(m: Sizes, g) -> dict:
    return {"final_norm": g["final_norm"], "eps": m.eps,
            "head": g["embed"].T}


# -- the program's own configuration and parameters ----------------------------

def program_config(cfg: dict, m: Sizes):
    from triton_distributed_tpu.models.config import Lfm2MoeConfig

    return Lfm2MoeConfig(
        model_name=cfg["source"], vocab_size=m.vocab_size, d_model=m.d_model,
        layer_types=tuple("conv" if c else "full_attention" for c in m.conv),
        n_dense_layers=m.dense_layers, conv_kernel=m.taps,
        conv_bias=bool(cfg["conv_bias"]), n_heads=m.heads,
        n_kv_heads=m.kv_heads, d_ff=m.dense_width, moe_d_ff=m.expert_width,
        n_experts=m.router_width, n_experts_per_tok=m.topk,
        expert_bias=bool(cfg["use_expert_bias"]), norm_topk_prob=m.norm_topk,
        routed_scaling_factor=m.scaling, experts_held=m.held,
        experts_lo=m.lo, rope_theta=m.theta, rms_eps=m.eps,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        max_length=m.max_length, dtype=jnp.dtype(m.dtype))


def program(cfg: dict, m: Sizes, seed: int, mesh, engine_kwargs: dict):
    """The program's configuration object and the whole stack of seeded
    parameters for it (``models/exaone_moe.py``: the norms over all layers,
    every other stack over the layers of its kind), in one jitted call from
    the seed."""
    from jax.sharding import NamedSharding

    from triton_distributed_tpu.models.exaone_moe import ExaoneMoe

    mcfg = program_config(cfg, m)
    if mcfg.head_dim != m.head_dim:
        raise ValueError("head_dim is hidden_size / num_attention_heads")
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             ExaoneMoe(mcfg).param_specs())

    def at(want):
        return jnp.asarray([i for i in range(m.n_layers) if want(i)],
                           jnp.int32)

    @functools.partial(jax.jit, out_shardings=shardings)
    def make(gkey, lkeys):
        def stack(part, rows, *kind):
            return jax.vmap(lambda k: part(m, k, *kind))(lkeys[rows])

        def operators(rows, conv):
            return jax.vmap(lambda k, first: plain_operator(
                m, k, conv, first))(lkeys[rows], rows == 0)

        conv = operators(at(lambda i: m.conv[i]), True)
        attn = operators(at(lambda i: not m.conv[i]), False)
        dense = stack(plain_ffn, at(lambda i: not is_sparse(m, i)), False)
        moe = stack(plain_ffn, at(lambda i: is_sparse(m, i)), True)
        return {**plain_globals(m, gkey),
                "attn": {**stack(plain_norms, jnp.arange(m.n_layers)),
                         "attn": {"w_qkv": jnp.concatenate(
                                      [attn["wq"], attn["wk"], attn["wv"]],
                                      axis=-1),
                                  "w_o": attn["wo"],
                                  "q_norm": attn["q_norm"],
                                  "k_norm": attn["k_norm"]}},
                "conv": conv,
                "dense": {"w_gate_up": dense["w_gu"], "w_down": dense["w_d"]},
                "moe": {"router": moe["router"], "bias": moe["bias"],
                        "w_gate_up": moe["e_gu"], "w_down": moe["e_d"]}}

    return mcfg, make(*keys(seed, m.n_layers))


# -- the plain forward pass of one layer ---------------------------------------

def short_conv(m: Sizes, x, lw, precision):
    """The gated short convolution over one whole sequence, x (S, d): the
    taps as a sum of shifted products, tap k reading ``z`` from ``taps - 1
    - k`` positions back (zeros before the sequence)."""
    S, d = x.shape
    bcx = linear(x, lw["w_in"], precision)
    b, c, xs = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = b * xs
    conv = sum(lw["conv_w"][k] * jnp.pad(z, ((m.taps - 1 - k, 0), (0, 0)))[:S]
               for k in range(m.taps))
    return linear(c * conv, lw["w_out"], precision)


def full_attention(m: Sizes, x, lw, precision):
    S = x.shape[0]
    q = linear(x, lw["wq"], precision).reshape(S, m.heads, m.head_dim)
    k = linear(x, lw["wk"], precision).reshape(S, m.kv_heads, m.head_dim)
    v = linear(x, lw["wv"], precision).reshape(S, m.kv_heads, m.head_dim)
    pos = jnp.arange(S)
    q = rope(rms_norm(q, lw["q_norm"], m.eps), pos, m.theta)
    k = rope(rms_norm(k, lw["k_norm"], m.eps), pos, m.theta)
    return linear(attention(q, k, v, m.head_dim ** -0.5), lw["wo"],
                  precision)


def routing(m: Sizes, x, router, bias):
    """Scores in float32 over all experts; chosen by ``s + bias``, weighted
    by ``s`` -> (weights (S, k), ids (S, k))."""
    s = jax.nn.sigmoid(jnp.dot(x, router.astype(jnp.float32)))
    _, ids = jax.lax.top_k(s + bias, m.topk)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if m.norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + NORM_EPS)
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


@functools.partial(jax.jit, static_argnames=("m", "precision", "conv",
                                             "sparse"))
def _layer_forward(h, lw, *, m, precision, conv, sparse):
    x = rms_norm(h, lw["input_norm"], m.eps)
    h = h + (short_conv if conv else full_attention)(m, x, lw, precision)
    x = rms_norm(h, lw["post_norm"], m.eps)
    if not sparse:
        return h + swiglu(x, lw["w_gu"], lw["w_d"], precision)
    return h + routed_part(m, x, lw, precision)


def layer_forward(h, lw, m: Sizes, layer_index: int, precision: str):
    """One decoder layer over one whole sequence. h: (S, d) float32."""
    return _layer_forward(h, lw, m=m, precision=precision,
                          conv=m.conv[layer_index],
                          sparse=is_sparse(m, layer_index))


# -- operations and bytes -------------------------------------------------------

def conv_params(m: Sizes) -> int:
    return 4 * m.d_model * m.d_model + m.taps * m.d_model


def params_held(m: Sizes) -> int:
    """Every parameter this chip holds: the tied table once and the layers
    with the held experts (the norms' few thousand left out)."""
    return (m.d_model * m.vocab_size + m.n_conv * conv_params(m)
            + m.n_full * attn_params(m)
            + m.dense_layers * 3 * m.d_model * m.dense_width
            + m.n_sparse * (m.held * expert_params(m)
                            + m.d_model * m.router_width))


def weight_bytes_read(m: Sizes, experts_touched: float) -> float:
    """The linear weights a decode step reads: the operators, the dense
    layers, the routers (float32) and the table as the head once, and the
    three matrices of every routed expert that got a row."""
    b = itemsize(m.dtype)
    return (b * (m.n_conv * conv_params(m) + m.n_full * attn_params(m)
                 + m.dense_layers * 3 * m.d_model * m.dense_width
                 + m.d_model * m.vocab_size
                 + expert_params(m) * float(experts_touched))
            + 4 * m.n_sparse * m.d_model * m.router_width)


def short_conv_min_bytes(m: Sizes, row_layers: float) -> float:
    """The least bytes the one-token update moves THROUGH HBM, a row of a
    conv layer: the ``taps - 1`` held inputs read and the one new input
    written (3 x d values at 3 taps). ``B``, ``C``, ``X`` and the result are
    activations of the step, which the compiler may hand from the
    projection to the kernel and on without a trip through HBM (read on the
    chip, PR 46: the kernel takes 0.83 us a call where the 8 x d values a
    row of its four operands and two results would take 1.28 us at the
    chip's bandwidth), and a window kept as a ring would write one input
    and not ``taps - 1``: counting either would count too high."""
    return itemsize(m.dtype) * m.taps * m.d_model * float(row_layers)


def short_conv_flops(m: Sizes, row_layers: float) -> float:
    """A channel of a row of a layer: the product ``B * X``, ``taps``
    products and ``taps - 1`` sums, the gate's product."""
    return (2.0 * m.taps + 1) * m.d_model * float(row_layers)


def decode_step_min_bytes(m: Sizes, context_lens) -> float:
    """The least bytes one decode step has to move through HBM, one entry
    of ``context_lens`` a decoding row: the weights it reads (the routed
    experts that ``moe_expected`` has so many rows touch), each row's window
    read and its new input written once a conv layer
    (``short_conv_min_bytes``), and each row's whole context once an
    attention layer. Handed ONE summed context for several rows it counts
    one row's experts and one row's windows: fewer bytes, never more.
    Activations, the embedding rows and the pool's appends are left out: a
    lower bound."""
    rows = len(context_lens)
    return (weight_bytes_read(m, moe_expected(m, rows)[1])
            + short_conv_min_bytes(m, m.n_conv * rows)
            + m.n_full * m.row_bytes * float(sum(context_lens)))
