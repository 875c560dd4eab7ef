"""The EXAONE-MoE family: grouped-query attention in every layer, of two
kinds (a WINDOW of the last keys with rope, or every key with no position
embedding), and after it a dense SwiGLU or sigmoid-routed experts beside a
shared expert; served by the program's ``models.exaone_moe.ExaoneMoe``. The
family is the block; a configuration is one model's numbers
(``configs/k-exaone-236b-a23b-ep8.json`` holds K-EXAONE-236B-A23B's).

The block (HF ``exaone_moe``; configuration keys in brackets). RMSNorm in
float32 [rms_norm_eps], weights multiply; the head is untied
[tie_word_embeddings false]. d = [hidden_size]; layer l's attention kind is
[layer_types][l] with the window [sliding_windows][l], its FFN kind
[mlp_layer_types][l] (the leading [first_k_dense_replace] are dense)::

    h   = x + Attn_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))        # (a)
    q,k,v = W_q n, W_k n, W_v n     [num_attention_heads] /
            [num_key_value_heads] / the same, heads of [head_dim]
    q,k <- RMSNorm over the head dim, one weight a projection        # (b)
    sliding_attention: q,k <- rope(position, [rope_parameters.rope_theta]),
            rotate-half; key j is seen by query i  iff  0 <= i - j < window
    full_attention:    no position embedding;                        # (c)
            key j is seen by query i  iff  j <= i
    Attn = W_o softmax(q k^T / sqrt(head_dim) + mask) v
    dense:  FFN = W_d (silu(W_g n) * W_u n)      width [intermediate_size]
    sparse: s = sigmoid(W_r n) in float32 over ALL [num_experts_published]
            experts; the [num_experts_per_tok] largest s are chosen   # (d)
            ([n_group] 1, [topk_group] 1: no group limit); weights
            s_e / sum of the chosen s [norm_topk_prob] times
            [routed_scaling_factor];
            FFN = SwiGLU_shared(n) + sum of w_e SwiGLU_e(n), each of width
            [moe_intermediate_size] ([num_shared_experts] shared, as one
            SwiGLU of their summed width)
    logits = RMSNorm(y_last) W_head

ASSUMED, where no key of the public config settles the line (the
configuration's ``assumed`` repeats each): (a) pre-norm with the one
[rms_norm_eps] (the config names one epsilon and no post-norm key; the
placement moves no byte and no operation); (b) the family's QK norm; (c)
rope on the window layers only, the full layers NoPE (the family's hybrid
attention); (d) no selection bias (the config names no ``topk_method`` and
no correction bias). The multi-token-prediction layer
[num_nextn_predict_layers, mtp_layer_types] is neither served nor modelled:
the main model's logits do not depend on it.

Computed here as written, float32, no kernel, no cache, no batching: every
held expert over every token; attention BLOCKED OVER QUERIES (a sampled
request is 10-20 thousand positions, and 64 heads x 20k x 20k float32
scores do not fit a chip): a block of queries against every key under the
mask (full), or against the ``window - 1`` keys before the block and the
block's own (window). The blocking changes no result.

ONE CHIP'S SHARE, as ``families/deepseek_v3.py`` states it: the
configuration names the routed experts held here (``num_experts_held`` of
``num_experts_published``, ids from ``num_experts_lo``; ``num_experts`` is
the count held and is listed in ``reduced``); the sum runs over the chosen
experts among those held, the weights are still normalised over all chosen,
and what the absent experts would add is left out, here and in the program
alike. Expert e's matrices come from a key folded with e, so every share of
one seed holds the same model. A sliced vocabulary is a smaller vocabulary.

Departures: the router's product is taken in float32 in every ``precision``
(the block states it so; the control lowers the linear layers around it).
Seeded where no ``1 / fan_in`` rule says how (the configuration's ``assumed``
repeats it): THE EMBEDDING TABLE HAS N(0, 1) ENTRIES (``plain_globals``), every
other matrix N(0, 1 / fan_in). With a table at ``1 / sqrt(d)`` a token's row
(norm 1) is drowned by the first window layer's output (norm 7: an average
over the last 128 value rows, which moves by a 128th a token), so
consecutive tokens of a sequence route alike for hundreds of steps and a
run's load on the 16 held experts is ONE draw a seed and not an average over
its steps: read on the chip, 12.64 held experts touched a layer a step where
even routing gives 13.97, and over six seeds ``out_tokens_per_s`` spread by
3.3% and ``itl_p95_ms`` by 2.6% on one schedule (the experts' weights are
half a step's bytes and there are only four expert layers to average over).
A trained model's token rows carry the stream through its first layers; at
N(0, 1) entries the router sees a token's own row (read on the chip: 13.96
touched, spreads 1.4% and 0.9%). THE QK NORMS' WEIGHTS ARE SEEDED ABOUT 2
(``QK_NORM_MEAN``; the other norms about 1): with scores of unit spread a
window layer's softmax over 128 keys is nearly flat, the layer adds the same
slow average to every token, the stream is a function of the newest token
alone, and greedy decoding of such a model falls into a few short cycles
that every sequence shares: the tokens in play are a few hundred, and the
share of a run's routed pairs that lands on the 16 held experts is one draw
a seed (read: 0.152 where even routing gives 0.125, and ``itl_p95_ms`` still
spreads by 0.57% over six seeds at 64 rows a step). Scores of spread 4 pick a
few keys, as a trained head does, the choice moves with every query, and no
cycle holds (read at 32 rows: the held share 0.127, 13.87-13.98 touched).
About 3 the bfloat16 program and this float32 reference part ways at near
ties of the picked key (``gap_mean`` 0.10, ``top1_share`` 0.58: not correct,
read on five seeds); about 2 they agree (``gap_mean`` 0.009-0.012). That it
is bfloat16's rounding and not the program's window build has a second
witness: THIS reference with the operands of every linear layer and
attention's q, k, v rounded to bfloat16, in the program's place (no kernel,
no cache, no batching), reads the same against itself in float32: about 3
``gap_mean`` 0.089-0.092 and ``top1_share`` 0.58-0.61, about 2 0.009-0.011
and 0.87-0.88 (two seeds, 1,536 positions, the published widths). So the
cell's limits hold at THIS seeding: the arithmetic's own rounding at a
seeding one notch sharper already reads past them. THE ROUTER'S ROWS ARE
DEALT EVENLY TO THE CHIPS (``seeded_router``): chip 0's ``held`` rows are
drawn N(0, 1 / d) and chip c's are an orthogonal remix of them that keeps
the constant vector, so that for every token each chip's scores have the
same sum and the same sum of squares and each chip is dealt the same share
of the routed pairs to second order in the stream's common part; a trained
router's balance loss sees to that, and 128 rows drawn one by one do not:
this chip's share of a run's pairs is then one draw a seed (read on the
chip: 0.121-0.127 for 0.125), half a step's bytes follow it, and the driver
read ``out_tokens_per_s`` spreading by 1.12% and 0.94% over its two sets of
six seeds. Every row keeps its law, the routing is still the 8 largest of
128 sigmoid scores with no bias, and a tiny test's handful is drawn as
before.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.peaks import itemsize
from perfbench.reference import linear, rms_norm, rope
from perfbench.weights import keys, norm_weight, randw

QUERY_BLOCK = 256       # attention is computed over blocks of query rows
QK_NORM_MEAN = 2.0      # the QK norms' weights are seeded about this


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab_size: int
    d_model: int            # hidden_size
    n_layers: int           # num_hidden_layers
    windows: tuple          # sliding_windows, a layer; 0: a full layer
    sparse: tuple           # mlp_layer_types == "sparse", a layer
    heads: int              # num_attention_heads
    kv_heads: int           # num_key_value_heads
    head_dim: int
    dense_width: int        # intermediate_size
    expert_width: int       # moe_intermediate_size
    router_width: int       # num_experts_published
    held: int               # num_experts_held
    lo: int                 # num_experts_lo
    topk: int               # num_experts_per_tok
    shared: int             # num_shared_experts
    scaling: float          # routed_scaling_factor
    norm_topk: bool         # norm_topk_prob
    theta: float            # rope_parameters.rope_theta
    eps: float              # rms_norm_eps
    max_length: int         # max_position_embeddings, as run
    dtype: str              # torch_dtype

    @property
    def n_window(self) -> int:
        return sum(1 for w in self.windows if w)

    @property
    def n_full(self) -> int:
        return self.n_layers - self.n_window

    @property
    def n_sparse(self) -> int:
        return sum(self.sparse)

    @property
    def window(self) -> int:
        return max(self.windows)

    @property
    def row_bytes(self) -> int:
        """One token's K and V rows of one layer."""
        return 2 * self.kv_heads * self.head_dim * itemsize(self.dtype)


def sizes(cfg: dict) -> Sizes:
    n = int(cfg["num_hidden_layers"])
    kinds, windows, mlps = (cfg[k][:n] for k in (
        "layer_types", "sliding_windows", "mlp_layer_types"))
    if len(kinds) != n or len(windows) != n or len(mlps) != n:
        raise ValueError("layer_types, sliding_windows and mlp_layer_types "
                         "name fewer layers than num_hidden_layers")
    if any((k == "sliding_attention") != bool(w)
           for k, w in zip(kinds, windows)) or set(kinds) - {
               "sliding_attention", "full_attention"}:
        raise ValueError("a sliding_attention layer states its window, a "
                         "full_attention layer 0")
    if [m == "dense" for m in mlps] != [
            i < cfg["first_k_dense_replace"] for i in range(n)]:
        raise ValueError("mlp_layer_types and first_k_dense_replace differ")
    if (cfg["scoring_func"], cfg["n_group"], cfg["topk_group"]) != (
            "sigmoid", 1, 1) or cfg.get("topk_method") is not None:
        raise ValueError("this family routes by sigmoid scores with one "
                         "group and no selection bias")
    if cfg["rope_parameters"].get("rope_type", "default") != "default" \
            or cfg["tie_word_embeddings"] or cfg["hidden_act"] != "silu":
        raise ValueError("this family takes plain rope, an untied head and "
                         "silu; the configuration states another")
    if cfg["num_experts"] != cfg["num_experts_held"]:
        raise ValueError("num_experts is the count held here")
    return Sizes(
        vocab_size=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layers=n, windows=tuple(int(w) for w in windows),
        sparse=tuple(m == "sparse" for m in mlps),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        dense_width=int(cfg["intermediate_size"]),
        expert_width=int(cfg["moe_intermediate_size"]),
        router_width=int(cfg["num_experts_published"]),
        held=int(cfg["num_experts_held"]), lo=int(cfg["num_experts_lo"]),
        topk=int(cfg["num_experts_per_tok"]),
        shared=int(cfg["num_shared_experts"]),
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        theta=float(cfg["rope_parameters"]["rope_theta"]),
        eps=float(cfg["rms_norm_eps"]),
        max_length=int(cfg["max_position_embeddings"]),
        dtype=str(cfg["torch_dtype"]))


# -- the seeded plain weights --------------------------------------------------

@functools.lru_cache(maxsize=None)
def chip_remixes(held: int, chips: int):
    """``(chips, held, held)`` constants: orthogonal matrices that keep the
    constant vector, the first the identity. Each is ``C diag(s) C^T`` with C
    the orthonormal cosine basis of ``R^held`` (its first vector constant)
    and ``s`` a pattern of signs, +1 on the constant vector and -1 on half of
    the others, the patterns of two chips differing in a third of their
    places or more (a fixed generator chooses them: they are the same for
    every seed and layer)."""
    k = np.arange(held)
    basis = np.cos(np.pi * (k[:, None] + 0.5) * k[None, :] / held)
    basis /= np.linalg.norm(basis, axis=0)
    rng, signs = np.random.default_rng(held), [np.ones(held)]
    while len(signs) < chips:
        s = np.ones(held)
        s[1 + rng.permutation(held - 1)[:held // 2]] = -1.0
        if min(np.sum(s != t) for t in signs) >= (held - 1) // 3:
            signs.append(s)
    return np.stack([(basis * s) @ basis.T for s in signs]).astype(np.float32)


def seeded_router(m: Sizes, key):
    """The router's matrix ``(d, router width)``, float32. Where the experts
    are dealt to several chips in groups of ``held`` (eight or more each),
    chip 0's rows are drawn N(0, 1 / d) and chip c's ARE AN ORTHOGONAL REMIX
    OF THEM (``chip_remixes``): every chip's rows then have the same sum and
    the same Gram matrix, so whatever mean and covariance the stream that
    reaches the router has, every chip is dealt the same share of the
    routed pairs to first order, as the balance loss of a trained router
    sees to; rows drawn one by one are balanced only over an isotropic
    stream, which seeded weights under greedy decoding do not give. Every
    row keeps its N(0, 1 / d) law and every share of one seed the same
    matrix. Otherwise (one chip holds every expert, or a tiny test's
    handful): every row drawn by itself."""
    d, chips = m.d_model, m.router_width // m.held
    if chips == 1 or m.held < 8 or m.router_width % m.held:
        return randw(key, (d, m.router_width), d,
                     jnp.dtype(m.dtype)).astype(jnp.float32)
    base = jax.random.normal(key, (m.held, d), jnp.float32) * d ** -0.5
    rows = jnp.einsum("cij,jd->cid", chip_remixes(m.held, chips), base,
                      precision=jax.lax.Precision.HIGHEST)
    return rows.reshape(m.router_width, d).T


def plain_layer(m: Sizes, key, sparse: bool):
    """One decoder layer's weights as the architecture names them, each
    ``(in, out)``, in the served dtype; norms and the router in float32.
    Gate and up halves are made as one matrix (``*_gu``, gate first).
    Expert e's matrices come from the key folded with e. A window layer and
    a full one have the same matrices."""
    dt = jnp.dtype(m.dtype)
    d, q, kv = m.d_model, m.heads * m.head_dim, m.kv_heads * m.head_dim
    ks = jax.random.split(key, 16)
    lw = {
        "wq": randw(ks[0], (d, q), d, dt), "wk": randw(ks[1], (d, kv), d, dt),
        "wv": randw(ks[2], (d, kv), d, dt), "wo": randw(ks[3], (q, d), q, dt),
        "q_norm": norm_weight(ks[4], (m.head_dim,)) + (QK_NORM_MEAN - 1.0),
        "k_norm": norm_weight(ks[5], (m.head_dim,)) + (QK_NORM_MEAN - 1.0),
        "input_norm": norm_weight(ks[6], (d,)),
        "post_norm": norm_weight(ks[7], (d,)),
    }
    if not sparse:
        ff = m.dense_width
        lw["w_gu"] = randw(ks[8], (d, 2 * ff), d, dt)
        lw["w_d"] = randw(ks[9], (ff, d), ff, dt)
        return lw
    ffe, ffs = m.expert_width, m.shared * m.expert_width
    ids = m.lo + jnp.arange(m.held)
    lw["router"] = seeded_router(m, ks[8])
    lw["e_gu"] = jax.vmap(lambda e: randw(
        jax.random.fold_in(ks[9], e), (d, 2 * ffe), d, dt))(ids)
    lw["e_d"] = jax.vmap(lambda e: randw(
        jax.random.fold_in(ks[10], e), (ffe, d), ffe, dt))(ids)
    lw["s_gu"] = randw(ks[11], (d, 2 * ffs), d, dt)
    lw["s_d"] = randw(ks[12], (ffs, d), ffs, dt)
    return lw


def plain_globals(m: Sizes, key):
    dt = jnp.dtype(m.dtype)
    ks = jax.random.split(key, 3)
    return {"embed": randw(ks[0], (m.vocab_size, m.d_model), 1, dt),
            "final_norm": norm_weight(ks[1], (m.d_model,)),
            "lm_head": randw(ks[2], (m.d_model, m.vocab_size), m.d_model,
                             dt)}


_layer_weights = jax.jit(plain_layer, static_argnums=(0, 2))
global_weights = jax.jit(plain_globals, static_argnums=0)


def layer_weights(m: Sizes, key, layer_index: int):
    return _layer_weights(m, key, m.sparse[layer_index])


def head_weights(m: Sizes, g) -> dict:
    return {"final_norm": g["final_norm"], "eps": m.eps,
            "head": g["lm_head"]}


# -- the program's own configuration and parameters ----------------------------

def program_config(cfg: dict, m: Sizes):
    from triton_distributed_tpu.models.config import ExaoneMoeConfig

    return ExaoneMoeConfig(
        model_name=cfg["source"], vocab_size=m.vocab_size, d_model=m.d_model,
        layer_types=tuple("sliding_attention" if w else "full_attention"
                          for w in m.windows),
        sliding_windows=m.windows,
        mlp_layer_types=tuple("sparse" if s else "dense" for s in m.sparse),
        n_heads=m.heads, n_kv_heads=m.kv_heads, head_dim=m.head_dim,
        d_ff=m.dense_width, moe_d_ff=m.expert_width,
        n_experts=m.router_width, n_experts_per_tok=m.topk,
        n_shared_experts=m.shared, routed_scaling_factor=m.scaling,
        norm_topk_prob=m.norm_topk, experts_held=m.held, experts_lo=m.lo,
        rope_theta=m.theta, rms_eps=m.eps, max_length=m.max_length,
        dtype=jnp.dtype(m.dtype))


def program_attn(lw):
    """Layer-stacked plain weights -> the program's stack over all layers
    (``models/exaone_moe.py``): q, k and v side by side in one matrix."""
    return {"input_norm": lw["input_norm"], "post_norm": lw["post_norm"],
            "attn": {"w_qkv": jnp.concatenate(
                         [lw["wq"], lw["wk"], lw["wv"]], axis=-1),
                     "w_o": lw["wo"], "q_norm": lw["q_norm"],
                     "k_norm": lw["k_norm"]}}


def program(cfg: dict, m: Sizes, seed: int, mesh, engine_kwargs: dict):
    """The program's configuration object and the whole stack of seeded
    parameters for it, in one jitted call from the seed."""
    from jax.sharding import NamedSharding

    from triton_distributed_tpu.models.exaone_moe import ExaoneMoe

    mcfg = program_config(cfg, m)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             ExaoneMoe(mcfg).param_specs())
    dense_at = [i for i, s in enumerate(m.sparse) if not s]
    sparse_at = [i for i, s in enumerate(m.sparse) if s]

    @functools.partial(jax.jit, out_shardings=shardings)
    def make(gkey, lkeys):
        def stack(at, sparse):
            return jax.vmap(lambda k: plain_layer(m, k, sparse))(
                lkeys[jnp.asarray(at, jnp.int32)])

        dense, sparse = stack(dense_at, False), stack(sparse_at, True)
        order = jnp.argsort(jnp.asarray(dense_at + sparse_at))
        attn = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b])[order],
            program_attn(dense), program_attn(sparse))
        return {**plain_globals(m, gkey), "attn": attn,
                "dense": {"w_gate_up": dense["w_gu"],
                          "w_down": dense["w_d"]},
                "moe": {"router": sparse["router"],
                        "w_gate_up": sparse["e_gu"],
                        "w_down": sparse["e_d"],
                        "shared": {"w_gate_up": sparse["s_gu"],
                                   "w_down": sparse["s_d"]}}}

    return mcfg, make(*keys(seed, m.n_layers))


# -- the plain forward pass of one layer ---------------------------------------

def attention(q, k, v, scale, window: int):
    """Softmax attention with grouped heads, a block of ``QUERY_BLOCK``
    queries at a time. q: (S, Hq, dh); k, v: (S, Hkv, dh) -> (S, Hq * dh).
    ``window`` 0: query i sees the keys j <= i, and a block is taken against
    every key. ``window`` w: it sees 0 <= i - j < w, and a block is taken
    against the w - 1 keys before it and its own."""
    S, Hq, dh = q.shape
    Hkv = k.shape[1]
    nb = -(-S // QUERY_BLOCK)
    pad = nb * QUERY_BLOCK - S
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        nb, QUERY_BLOCK, Hkv, Hq // Hkv, dh)
    back = window - 1 if window else 0
    if window:
        k = jnp.pad(k, ((back, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((back, pad), (0, 0), (0, 0)))

    def block(args):
        qi, r0 = args
        rows = r0 + jnp.arange(QUERY_BLOCK)
        if window:
            n = QUERY_BLOCK + back
            kb = jax.lax.dynamic_slice_in_dim(k, r0, n)
            vb = jax.lax.dynamic_slice_in_dim(v, r0, n)
            cols = r0 - back + jnp.arange(n)
            seen = ((cols[None, :] <= rows[:, None]) & (cols[None, :] >= 0)
                    & (cols[None, :] > rows[:, None] - window))
        else:
            kb, vb, cols = k, v, jnp.arange(S)
            seen = cols[None, :] <= rows[:, None]
        s = jnp.einsum("qhgd,khd->hgqk", qi, kb) * scale
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, vb)

    out = jax.lax.map(block, (qb, jnp.arange(nb) * QUERY_BLOCK))
    return out.reshape(nb * QUERY_BLOCK, Hq * dh)[:S]


def swiglu(x, w_gu, w_d, precision):
    h = linear(x, w_gu, precision)
    ff = h.shape[-1] // 2
    return linear(jax.nn.silu(h[:, :ff]) * h[:, ff:], w_d, precision)


def routing(m: Sizes, x, router):
    """Scores in float32 over all experts -> (weights (S, k), ids (S, k))."""
    s = jax.nn.sigmoid(jnp.dot(x, router.astype(jnp.float32)))
    w, ids = jax.lax.top_k(s, m.topk)
    if m.norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * m.scaling, ids


def routed_part(m: Sizes, x, lw, precision):
    """What the held experts give: every held expert over every token, in
    the plainest way, weighted by its routing weight where it was chosen
    (one expert at a time, so that one expert's body is all that is
    compiled)."""
    w, ids = routing(m, x, lw["router"])

    def add(y, expert):
        j, w_gu, w_d = expert
        w_j = jnp.sum(jnp.where(ids == m.lo + j, w, 0.0), axis=-1)   # (S,)
        return y + w_j[:, None] * swiglu(x, w_gu, w_d, precision), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(x),
                        (jnp.arange(m.held), lw["e_gu"], lw["e_d"]))
    return y


@functools.partial(jax.jit, static_argnames=("m", "precision", "window",
                                             "sparse"))
def _layer_forward(h, lw, *, m, precision, window, sparse):
    S = h.shape[0]
    x = rms_norm(h, lw["input_norm"], m.eps)
    q = linear(x, lw["wq"], precision).reshape(S, m.heads, m.head_dim)
    k = linear(x, lw["wk"], precision).reshape(S, m.kv_heads, m.head_dim)
    v = linear(x, lw["wv"], precision).reshape(S, m.kv_heads, m.head_dim)
    q = rms_norm(q, lw["q_norm"], m.eps)
    k = rms_norm(k, lw["k_norm"], m.eps)
    if window:
        pos = jnp.arange(S)
        q, k = rope(q, pos, m.theta), rope(k, pos, m.theta)
    a = attention(q, k, v, m.head_dim ** -0.5, window)
    h = h + linear(a, lw["wo"], precision)
    x = rms_norm(h, lw["post_norm"], m.eps)
    if not sparse:
        return h + swiglu(x, lw["w_gu"], lw["w_d"], precision)
    return (h + swiglu(x, lw["s_gu"], lw["s_d"], precision)
            + routed_part(m, x, lw, precision))


def layer_forward(h, lw, m: Sizes, layer_index: int, precision: str):
    """One decoder layer over one whole sequence. h: (S, d) float32."""
    return _layer_forward(h, lw, m=m, precision=precision,
                          window=m.windows[layer_index],
                          sparse=m.sparse[layer_index])


# -- operations and bytes -------------------------------------------------------

def attn_params(m: Sizes) -> int:
    return 2 * m.d_model * (m.heads + m.kv_heads) * m.head_dim


def expert_params(m: Sizes) -> int:
    return 3 * m.d_model * m.expert_width


def params_held(m: Sizes) -> int:
    """Every parameter this chip holds: the embedding table, the head, and
    the layers with the held experts (the norms' few thousand left out)."""
    return (2 * m.d_model * m.vocab_size + m.n_layers * attn_params(m)
            + (m.n_layers - m.n_sparse) * 3 * m.d_model * m.dense_width
            + m.n_sparse * ((m.held + m.shared) * expert_params(m)
                            + m.d_model * m.router_width))


def weight_bytes_read(m: Sizes, experts_touched: float) -> float:
    """The linear weights a decode step reads: attention, the dense layers,
    the shared experts, the router (float32) and the head once, and the
    three matrices of every routed expert that got a row."""
    b = itemsize(m.dtype)
    return (b * (m.n_layers * attn_params(m)
                 + (m.n_layers - m.n_sparse) * 3 * m.d_model * m.dense_width
                 + m.n_sparse * m.shared * expert_params(m)
                 + m.d_model * m.vocab_size
                 + expert_params(m) * float(experts_touched))
            + 4 * m.n_sparse * m.d_model * m.router_width)


def full_attn_min_bytes(m: Sizes, context_lens) -> float:
    """Every row of every context once a full layer."""
    return m.n_full * m.row_bytes * float(sum(context_lens))


def window_attn_min_bytes(m: Sizes, rows: float) -> float:
    """The least bytes the window layers' attention of one step reads:
    ``window`` rows a decoding row a window layer (a context shorter than
    the window has fewer: no cell's has)."""
    return m.n_window * m.row_bytes * m.window * float(rows)


def window_attn_flops(m: Sizes, rows: float) -> float:
    """Scores and values over the window, every query head, every window
    layer, a decoding row."""
    return 4.0 * m.n_window * m.heads * m.head_dim * m.window * float(rows)


def moe_ffn_min_bytes(m: Sizes, experts_touched: float) -> float:
    """Routed experts only: the three matrices of every expert that got a
    row, summed over the sparse layers."""
    return itemsize(m.dtype) * expert_params(m) * float(experts_touched)


def moe_ffn_flops(m: Sizes, pairs: float) -> float:
    return 2.0 * expert_params(m) * float(pairs)


def moe_expected(m: Sizes, rows: float) -> tuple[float, float]:
    """(pairs held, experts touched) a step of ``rows`` live tokens gives
    over all sparse layers IF every routed expert is as likely as another
    (seeded weights make it nearly so): each row picks a given expert with
    probability topk / router width."""
    p = m.topk / m.router_width
    return (m.n_sparse * rows * p * m.held,
            m.n_sparse * m.held * (1.0 - (1.0 - p) ** rows))


def decode_step_min_bytes(m: Sizes, context_lens) -> float:
    """The least bytes one decode step has to move through HBM, one entry
    of ``context_lens`` a decoding row: the weights it reads (the routed
    experts that ``moe_expected`` has so many rows touch), each row's whole
    context once a full layer, and ``min(context, window)`` rows of it a
    window layer. Handed ONE summed context for several rows it counts one
    row's experts and one window: fewer bytes, never more. Activations, the
    embedding rows and the pool's writes are left out: a lower bound."""
    window = m.n_window * m.row_bytes * float(
        sum(min(c, m.window) for c in context_lens))
    return (weight_bytes_read(m, moe_expected(m, len(context_lens))[1])
            + full_attn_min_bytes(m, context_lens) + window)
