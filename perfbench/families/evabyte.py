"""The EvaByte family: a byte-level decoder whose every layer is EVA attention
and a dense SwiGLU; served by the program's ``models.evabyte.EvaByte``. The
family is the block; a configuration is one model's numbers
(``configs/evabyte-6.5b-l8.json`` holds EvaByte 6.5B's).

EVA is Zheng, Yuan, Wang and Kong, "Efficient Attention via Control
Variates" (ICLR 2023), in the form whose exact set is the query's own window
and which keeps one control variate a chunk, with the deterministic
parameters EvaByte released (``adaptive_mu_k``, ``adaptive_phi``: one vector
of [head_dim] a head). The block (HF ``evabyte``; configuration keys in
brackets): no bias [attention_bias false], as many key heads as query heads,
rope over the whole head [rope_theta], RMSNorm [rms_norm_eps] whose weight is
``1 + g`` [norm_add_unit_offset], the residual adds in float32
[fp32_skip_add], float32 logits [fp32_logits], an untied head
[tie_word_embeddings false] of [num_pred_heads] heads of [vocab_size] side
by side. For one head, ``s = 1 / sqrt(head_dim)``, ``w`` = [window_size],
``cs`` = [chunk_size], position ``i``::

    q_i, k_i = rope(x^_i W_q, i), rope(x^_i W_k, i);  v_i = x^_i W_v
                                                x^ = RMSNorm(x) (1 + g)
    chunk c = positions cs c .. cs c + cs - 1:
        k~_c = sum_j softmax_j(s k_j . mu)  k_j        (the chunk's key)  (a, b)
        v~_c = sum_j softmax_j(s k_j . phi) v_j        (the chunk's value)
    W(i) = i // w;  E_i = { j : w W(i) <= j <= i };  C_i = { c : c < (w/cs) W(i) }
    o_i = ( sum_{E_i} e^{s q_i.k_j} v_j + sum_{C_i} e^{s q_i.k~_c} v~_c )
        / ( sum_{E_i} e^{s q_i.k_j}     + sum_{C_i} e^{s q_i.k~_c} )
    y = x + concat(o) W_o;   out = y + W_down( silu(W_gate y^) * (W_up y^) )
    logits = RMSNorm(h_last)(1 + g) W_head, read as (num_pred_heads, V)

So a query reads its own window key by key (1 to ``w`` of them: the window
is ALIGNED, it does not slide, and at a boundary the exact set falls back to
one key) and every earlier window through ``w / cs`` summaries; a chunk of
the query's own window is never read as a summary, whole or not.

ASSUMED: three points are a reading of the release and not of a key in the
public config, each made in ONE place here (and repeated in the
configuration's ``assumed``), so that each is a one-line correction once the
released modelling code is at hand:

(a) rope comes BEFORE the pooling and the summary key takes no further
    rotation (``chunk_summaries`` is handed the rope'd keys);
(b) the pooling scores are ``s k . mu`` and ``s k . phi``, with no
    ``-|k|^2 / 2`` term (``pooling_scores``);
(c) ``mu`` and ``phi`` are drawn N(0, ``POOL_SPREAD``^2) an entry
    (``plain_layer``): at the release's [init_std] they are near zero, every
    summary is its chunk's mean, and ``correct`` could not tell a summary
    pooled under the wrong vector, or under none, from a sound one.

Heads 1-7 of the head are held and compared on the CPU
(``all_heads_logits``), not served: the harness reads head 0
(``head_weights``).

Computed here as written, float32, no kernel, no cache, no batching, over
one whole sequence, one WINDOW of queries at a time and ``QUERY_BLOCK`` of
them at a step (a block of queries against its window's keys under the
causal mask and against every summary under the mask ``c < (w/cs) W``; the
blocking changes no result), so that 28,672 positions fit.

Departures: every matrix is drawn N(0, 1 / fan_in), norm offsets ``g`` 0.1
N(0, 1) (``weights.norm_weight`` less one), but for three draws, as the
sibling families state theirs:

- THE EMBEDDING TABLE HAS N(0, 1) ENTRIES, as the sibling families' has (at
  ``1 / sqrt(d)`` a row's stream is a slow average of its context).
- W_q AND W_k ARE DRAWN N(0, ``SCORE_SPREAD`` / d), scores of spread
  ``SCORE_SPREAD`` = 2: this block has no QK norm whose weight could do it,
  and with unit scores a softmax over 2,048 keys is a mean of some 750 rows,
  so that a fault which moves WHICH keys a query prefers moves nothing
  (``families/smallthinker.py`` has the readings for that block).
- ``mu`` and ``phi``: (c) above.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from perfbench.peaks import itemsize
from perfbench.reference import linear, rms_norm, rope
from perfbench.weights import keys, norm_weight, randw

SCORE_SPREAD = 2.0      # the standard deviation of an attention score
POOL_SPREAD = 2.0       # and of a pooling score within a chunk
QUERY_BLOCK = 512       # queries a step of the blocked attention


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab_size: int
    d_model: int            # hidden_size
    n_layers: int           # num_hidden_layers
    heads: int              # num_attention_heads == num_key_value_heads
    head_dim: int           # hidden_size / num_attention_heads
    d_ff: int               # intermediate_size
    window: int             # window_size
    chunk: int              # chunk_size
    pred_heads: int         # num_pred_heads
    theta: float            # rope_theta
    eps: float              # rms_norm_eps
    max_length: int         # max_position_embeddings
    dtype: str              # torch_dtype

    @property
    def row_bytes(self) -> int:
        """One K row and one V row of one layer: a token's in the ring, a
        chunk's summary in the arenas."""
        return 2 * self.heads * self.head_dim * itemsize(self.dtype)

    @property
    def per_window(self) -> int:
        """Summaries a whole window is read through."""
        return self.window // self.chunk


def sizes(cfg: dict) -> Sizes:
    if cfg["attention_class"] != "eva" or cfg["attention_bias"] \
            or cfg["rope_scaling"] is not None or cfg["tie_word_embeddings"] \
            or not cfg["norm_add_unit_offset"] or cfg["hidden_act"] != "silu" \
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError(
            "this family takes EVA attention with a key head a query head, "
            "no bias, plain rope, a unit-offset RMSNorm, SwiGLU and an "
            "untied head; the configuration states another")
    heads, d = int(cfg["num_attention_heads"]), int(cfg["hidden_size"])
    if d % heads or int(cfg["window_size"]) % int(cfg["chunk_size"]):
        raise ValueError("heads do not divide the width, or chunks the "
                         "window")
    return Sizes(
        vocab_size=int(cfg["vocab_size"]), d_model=d,
        n_layers=int(cfg["num_hidden_layers"]), heads=heads,
        head_dim=d // heads, d_ff=int(cfg["intermediate_size"]),
        window=int(cfg["window_size"]), chunk=int(cfg["chunk_size"]),
        pred_heads=int(cfg["num_pred_heads"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        max_length=int(cfg["max_position_embeddings"]),
        dtype=str(cfg["torch_dtype"]))


# -- the seeded plain weights --------------------------------------------------

def plain_layer(m: Sizes, key):
    """One decoder layer's weights as the architecture names them, each
    ``(in, out)``, in the served dtype; the norms' offsets ``g`` and the two
    pooling vectors a head in float32. Gate and up halves are one matrix
    (``w_gu``, gate first)."""
    dt = jnp.dtype(m.dtype)
    d, hd = m.d_model, m.heads * m.head_dim
    ks = jax.random.split(key, 10)
    sharp = d / SCORE_SPREAD
    pool = (m.heads, m.head_dim)
    return {
        "wq": randw(ks[0], (d, hd), sharp, dt),
        "wk": randw(ks[1], (d, hd), sharp, dt),
        "wv": randw(ks[2], (d, hd), d, dt), "wo": randw(ks[3], (hd, d), hd, dt),
        # ASSUMED (c): the pooling vectors' draw
        "mu": POOL_SPREAD * jax.random.normal(ks[4], pool, jnp.float32),
        "phi": POOL_SPREAD * jax.random.normal(ks[5], pool, jnp.float32),
        "input_norm": norm_weight(ks[6], (d,)) - 1.0,
        "post_norm": norm_weight(ks[7], (d,)) - 1.0,
        "w_gu": randw(ks[8], (d, 2 * m.d_ff), d, dt),
        "w_d": randw(ks[9], (m.d_ff, d), m.d_ff, dt),
    }


def plain_globals(m: Sizes, key):
    dt = jnp.dtype(m.dtype)
    ks = jax.random.split(key, 3)
    return {"embed": randw(ks[0], (m.vocab_size, m.d_model), 1, dt),
            "final_norm": norm_weight(ks[1], (m.d_model,)) - 1.0,
            "lm_head": randw(ks[2], (m.d_model, m.pred_heads * m.vocab_size),
                             m.d_model, dt)}


_layer_weights = jax.jit(plain_layer, static_argnums=0)
global_weights = jax.jit(plain_globals, static_argnums=0)


def layer_weights(m: Sizes, key, layer_index: int):
    return _layer_weights(m, key)


def head_weights(m: Sizes, g) -> dict:
    """Head 0 (the next byte), what the served step samples from and the
    harness compares; the norm's weight is ``1 + g``."""
    return {"final_norm": 1.0 + g["final_norm"], "eps": m.eps,
            "head": g["lm_head"][:, :m.vocab_size]}


def all_heads_logits(m: Sizes, g, h):
    """Every prediction head's logits of hidden rows ``h`` (n, d) float32:
    ``(n, pred_heads, vocab)``, head 0 the next byte (the CPU tests)."""
    x = rms_norm(h, 1.0 + g["final_norm"].astype(jnp.float32), m.eps)
    return linear(x, g["lm_head"], "float32").reshape(
        h.shape[0], m.pred_heads, m.vocab_size)


# -- the program's own configuration and parameters ----------------------------

def program_config(cfg: dict, m: Sizes):
    from triton_distributed_tpu.models.config import EvaByteConfig

    return EvaByteConfig(
        model_name=cfg["source"], vocab_size=m.vocab_size, d_model=m.d_model,
        n_layers=m.n_layers, n_heads=m.heads, n_kv_heads=m.heads,
        head_dim=m.head_dim, d_ff=m.d_ff, window=m.window,
        chunk_size=m.chunk, n_pred_heads=m.pred_heads, rope_theta=m.theta,
        rms_eps=m.eps, max_length=m.max_length, dtype=jnp.dtype(m.dtype))


def program(cfg: dict, m: Sizes, seed: int, mesh, engine_kwargs: dict):
    """The program's configuration object and the whole stack of seeded
    parameters for it (``models/evabyte.py``: every leaf a stack over the
    layers), in one jitted call from the seed."""
    from jax.sharding import NamedSharding

    from triton_distributed_tpu.models.evabyte import EvaByte

    mcfg = program_config(cfg, m)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             EvaByte(mcfg).param_specs())

    @functools.partial(jax.jit, out_shardings=shardings)
    def make(gkey, lkeys):
        def layer(key):
            lw = plain_layer(m, key)
            return {"input_norm": lw["input_norm"],
                    "post_norm": lw["post_norm"],
                    "attn": {"w_qkv": jnp.concatenate(
                                 [lw["wq"], lw["wk"], lw["wv"]], axis=-1),
                             "w_o": lw["wo"], "mu": lw["mu"],
                             "phi": lw["phi"]},
                    "mlp": {"w_gate_up": lw["w_gu"], "w_down": lw["w_d"]}}

        return {**plain_globals(m, gkey), "layers": jax.vmap(layer)(lkeys)}

    return mcfg, make(*keys(seed, m.n_layers))


# -- the plain forward pass of one layer ---------------------------------------

def pooling_scores(k, by, scale):
    """ASSUMED (b): the score of key j of a chunk under the pooling vector
    ``by`` is ``s k_j . by``, with no ``-|k_j|^2 / 2`` term. k: (C, cs, H,
    dh); by: (H, dh) -> (C, cs, H)."""
    return jnp.einsum("cjhd,hd->cjh", k, by) * scale


def chunk_summaries(m: Sizes, k, v, mu, phi, scale):
    """ASSUMED (a): ``k`` arrives rope'd and a summary key takes no further
    rotation. k, v: (S, H, dh), S whole chunks -> (S / cs, H, dh) each."""
    kc = k.reshape(-1, m.chunk, *k.shape[1:])
    vc = v.reshape(-1, m.chunk, *v.shape[1:])
    wk = jax.nn.softmax(pooling_scores(kc, mu, scale), axis=1)
    wv = jax.nn.softmax(pooling_scores(kc, phi, scale), axis=1)
    return (jnp.einsum("cjh,cjhd->chd", wk, kc),
            jnp.einsum("cjh,cjhd->chd", wv, vc))


def attention(m: Sizes, q, k, v, mu, phi):
    """EVA attention over one whole sequence. q, k, v: (S, H, dh), rope'd
    -> (S, H * dh). One window of queries at a time, ``QUERY_BLOCK`` of them
    a step: against the window's keys under the causal mask (the window's
    first position is the lower bound by construction) and against EVERY
    chunk's summary under the mask ``c < per_window * W``."""
    S, H, dh = q.shape
    w, scale = m.window, dh ** -0.5
    qb = min(QUERY_BLOCK, w)
    if w % qb:
        raise ValueError(f"blocks of {qb} queries do not tile a window of "
                         f"{w}")
    nw = -(-S // w)
    pad = ((0, nw * w - S), (0, 0), (0, 0))
    q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
    ks, vs = chunk_summaries(m, k, v, mu, phi, scale)
    chunks = jnp.arange(ks.shape[0])

    def block(r0):
        first = (r0 // w) * w               # the window's first position
        qi = jax.lax.dynamic_slice_in_dim(q, r0, qb)
        kw = jax.lax.dynamic_slice_in_dim(k, first, w)
        vw = jax.lax.dynamic_slice_in_dim(v, first, w)
        rows = r0 + jnp.arange(qb)
        exact = (first + jnp.arange(w))[None, :] <= rows[:, None]
        seen = jnp.broadcast_to(
            chunks[None, :] < m.per_window * (r0 // w), (qb, chunks.size))
        s = jnp.concatenate([jnp.einsum("qhd,khd->hqk", qi, kw),
                             jnp.einsum("qhd,chd->hqc", qi, ks)],
                            axis=-1) * scale
        p = jax.nn.softmax(jnp.where(
            jnp.concatenate([exact, seen], axis=-1)[None], s, -jnp.inf),
            axis=-1)
        return (jnp.einsum("hqk,khd->qhd", p[..., :w], vw)
                + jnp.einsum("hqc,chd->qhd", p[..., w:], vs))

    out = jax.lax.map(block, jnp.arange(nw * w // qb) * qb)
    return out.reshape(nw * w, H * dh)[:S]


def swiglu(x, w_gu, w_d, precision):
    h = linear(x, w_gu, precision)
    ff = h.shape[-1] // 2
    return linear(jax.nn.silu(h[:, :ff]) * h[:, ff:], w_d, precision)


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _layer_forward(x, lw, *, m, precision):
    S = x.shape[0]
    n = rms_norm(x, 1.0 + lw["input_norm"], m.eps)
    q, k, v = (linear(n, lw[name], precision).reshape(S, m.heads, m.head_dim)
               for name in ("wq", "wk", "wv"))
    pos = jnp.arange(S)
    q, k = rope(q, pos, m.theta), rope(k, pos, m.theta)
    a = attention(m, q, k, v, lw["mu"], lw["phi"])
    h = x + linear(a, lw["wo"], precision)
    u = rms_norm(h, 1.0 + lw["post_norm"], m.eps)
    return h + swiglu(u, lw["w_gu"], lw["w_d"], precision)


def layer_forward(h, lw, m: Sizes, layer_index: int, precision: str):
    """One decoder layer over one whole sequence. h: (S, d) float32."""
    return _layer_forward(h, lw, m=m, precision=precision)


# -- operations and bytes -------------------------------------------------------

def layer_params(m: Sizes) -> int:
    """The linear weights of one layer (norms and pooling vectors left
    out: 2 d + 2 H dh)."""
    return 4 * m.d_model * m.heads * m.head_dim + 3 * m.d_model * m.d_ff


def params_held(m: Sizes) -> int:
    """Every parameter this chip holds: the layers, the embedding table and
    every prediction head."""
    return (m.n_layers * (layer_params(m) + 2 * m.d_model
                          + 2 * m.heads * m.head_dim)
            + m.d_model * m.vocab_size * (1 + m.pred_heads))


def weight_bytes_read(m: Sizes) -> float:
    """The linear weights a decode step reads: every layer's and head 0."""
    return itemsize(m.dtype) * (m.n_layers * layer_params(m)
                                + m.d_model * m.vocab_size)


def rows_needed(m: Sizes, context: int) -> tuple[int, int]:
    """(exact rows, summary rows) a decoding row at cache length
    ``context`` has to read in one layer."""
    return (context % m.window + 1, m.per_window * (context // m.window))


def eva_attn_min_bytes(m: Sizes, rows: float) -> float:
    """The least bytes EVA attention reads for ``rows`` rows, exact and
    summary alike (a K row and a V row each), already summed over the
    decoding rows and the layers (the program's ``eva_exact_rows`` +
    ``eva_summary_rows``)."""
    return m.row_bytes * float(rows)


def eva_attn_flops(m: Sizes, rows: float) -> float:
    """A score and a weighted value a head a row read."""
    return 4.0 * m.heads * m.head_dim * float(rows)


def decode_step_min_bytes(m: Sizes, context_lens) -> float:
    """The least bytes one decode step has to move through HBM: the weights
    it reads and, of the cache, ``context / chunk`` rows a layer for every
    entry of ``context_lens``. A row at context ``n`` reads ``n % window +
    1`` exact rows and ``per_window (n // window)`` summaries, never fewer
    than ``n / chunk`` in all, so this holds for one row's context and,
    being linear, for ONE summed context of several rows alike (the step
    roofline's reader hands that): a lower bound that never reads high.
    Activations, the embedding rows and the pool's writes are left out."""
    return (weight_bytes_read(m) + m.n_layers * m.row_bytes
            * sum(float(c) for c in context_lens) / m.chunk)
