"""The SmallThinker family: grouped-query attention in every layer, of two
kinds (a WINDOW of the last keys with rope, or every key with no position
embedding), and after it sparse ReGLU experts routed FROM THE LAYER'S INPUT
(the router stands before attention), with no shared expert and no dense
layer; served by the program's ``models.exaone_moe.ExaoneMoe`` with the four
things that block reads from its configuration stated otherwise
(``ExaoneMoeConfig``: ``qk_norm``, ``n_shared_experts``, ``scoring`` /
``expert_activation``, ``router_input``). The family is the block; a
configuration is one model's numbers (``configs/smallthinker-21ba3b-l8.json``
holds SmallThinker-21BA3B-Instruct's).

The block (HF ``smallthinker``; configuration keys in brackets). RMSNorm in
float32 [rms_norm_eps], weights multiply; the head is untied
[tie_word_embeddings false]. d = [hidden_size]. For layer l with input
stream x::

    r      = x W_r                    # [moe_num_primary_experts] logits from
                                      # the layer's INPUT: no norm, BEFORE
                                      # attention                         (a)
    I      = the [moe_num_active_primary_experts] largest of r
    w      = softmax(r[I])            # over the chosen alone: sums to 1  (b)
    h      = x + Attn_l(RMSNorm(x))
    u      = RMSNorm(h)
    y      = h + sum_{e in I} w_e W_down,e (relu(u W_gate,e) * (u W_up,e))
                                      # width [moe_ffn_hidden_size]       (c)
    logits = RMSNorm(y_last) W_head

    q,k,v = W_q n, W_k n, W_v n       [num_attention_heads] /
            [num_key_value_heads] / the same, heads of [head_dim]; no bias,
            no norm on queries or keys                                   (d)
    [sliding_window_layout][l] == 1: key j is seen by query i iff
            0 <= i - j < [sliding_window_size];   == 0: iff j <= i
    [rope_layout][l] == 1: q,k <- rope(position, [rope_theta]), rotate-half
            over the whole head ([rope_scaling] null);   == 0: no position
            embedding
    Attn = W_o softmax(q k^T / sqrt(head_dim) + mask) v

ASSUMED, where the catalog's copy of the public config does not carry the
key (its ``described_as`` says "router placed before attention", "sparse
ReGLU", "NoPE global", "0 shared"; the configuration's ``assumed`` repeats
each): (a) the early router is on and reads the un-normed layer input; (b)
[moe_primary_router_apply_softmax] true is the softmax over the chosen
logits, which already meets [norm_topk_prob]; (c) no secondary experts are
active (the config names primary experts only) and there is no shared
expert; (d) no attention bias, rotary over all of the head's dims.

Computed here as written, float32, no kernel, no cache, no batching: every
held expert over every token; attention blocked over queries (the sibling
family's ``attention``, ``families/exaone_moe.py``, as are the counts the two
share: a block of queries against every key under the mask,
or against the ``window - 1`` keys before the block and the block's own;
the blocking changes no result).

EVERY EXPERT IS HELD in the configuration the benchmark runs (whole layers
a chip); ``held`` / ``lo`` stay in the sizes so that a test can cut the
layer into shares and add them up (expert e's matrices come from a key
folded with e, so every share of one seed holds the same model).

Departures: the router's product is taken in float32 in every ``precision``
(the control lowers the linear layers around it). Every matrix is drawn
N(0, 1 / fan_in), the router's rows one by one (this chip holds every
expert: there is no share to balance), but for TWO draws, each read apart
on the chip beside the plain one (PR 41: PERF.md section 6; the
configuration's ``assumed`` repeats both):

- THE EMBEDDING TABLE HAS N(0, 1) ENTRIES, as the sibling families' has. At
  ``1 / sqrt(d)`` a row's stream is a slow average of its own context and
  not its newest token; it routes alike step after step and the 32 rows
  touch about 51 of the 64 experts a layer where an even routing touches
  61: the decode step is 7% faster than the deployment's (14.18 ms for
  15.30) and ``moe_ffn_roofline``, which counts an even routing, reads 92
  for 86.6.
- W_q AND W_k ARE DRAWN N(0, ``SCORE_SPREAD`` / d), scores of spread
  ``SCORE_SPREAD`` = 2 where 1 / fan_in gives 1: this block has no QK norm
  whose weight could do it. With unit scores a softmax over 4,096 keys is
  a mean of 1,500 rows; a fault that moves WHICH keys a query prefers then
  moves nothing. A query's rope position one off reads, in float32 at the
  configuration's widths on three seeds, 1.4-2 times bfloat16's own
  rounding at unit scores (``correct`` passes it) and 10-13 times at spread
  2 (it fails); at 3 and 4 the rounding itself grows five and fifteen times
  (``top1_share`` 0.78, 0.60 for 0.93) and shows nothing more. 2 is the
  mildest draw read that shows it.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from perfbench.families.exaone_moe import (  # noqa: F401 (the readers' counts)
    attention,
    attn_params,
    expert_params,
    full_attn_min_bytes,
    moe_ffn_flops,
    moe_ffn_min_bytes,
    window_attn_flops,
)
from perfbench.peaks import itemsize
from perfbench.reference import linear, rms_norm, rope
from perfbench.weights import keys, norm_weight, randw

SCORE_SPREAD = 2.0      # the standard deviation of an attention score


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab_size: int
    d_model: int            # hidden_size
    n_layers: int           # num_hidden_layers
    windows: tuple          # sliding_window_size where the layout says 1,
                            # a layer; 0: a full layer
    ropes: tuple            # rope_layout == 1, a layer
    heads: int              # num_attention_heads
    kv_heads: int           # num_key_value_heads
    head_dim: int
    expert_width: int       # moe_ffn_hidden_size
    router_width: int       # moe_num_primary_experts
    held: int               # of them held here (the configuration: all)
    lo: int                 # the first held
    topk: int               # moe_num_active_primary_experts
    theta: float            # rope_theta
    eps: float              # rms_norm_eps
    max_length: int         # max_position_embeddings
    dtype: str              # torch_dtype

    @property
    def n_window(self) -> int:
        return sum(1 for w in self.windows if w)

    @property
    def n_full(self) -> int:
        return self.n_layers - self.n_window

    @property
    def window(self) -> int:
        return max(self.windows)

    @property
    def row_bytes(self) -> int:
        """One token's K and V rows of one layer."""
        return 2 * self.kv_heads * self.head_dim * itemsize(self.dtype)


def sizes(cfg: dict) -> Sizes:
    n = int(cfg["num_hidden_layers"])
    windowed, roped = (cfg[k][:n] for k in ("sliding_window_layout",
                                            "rope_layout"))
    if len(windowed) != n or len(roped) != n:
        raise ValueError("sliding_window_layout and rope_layout name fewer "
                         "layers than num_hidden_layers")
    if cfg["rope_scaling"] is not None or cfg["tie_word_embeddings"] \
            or not cfg["moe_primary_router_apply_softmax"]:
        raise ValueError("this family takes plain rope, an untied head and "
                         "a softmax over the chosen logits; the "
                         "configuration states another")
    return Sizes(
        vocab_size=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layers=n,
        windows=tuple(int(cfg["sliding_window_size"]) if w else 0
                      for w in windowed),
        ropes=tuple(bool(r) for r in roped),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        expert_width=int(cfg["moe_ffn_hidden_size"]),
        router_width=int(cfg["moe_num_primary_experts"]),
        held=int(cfg["moe_num_primary_experts"]), lo=0,
        topk=int(cfg["moe_num_active_primary_experts"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        max_length=int(cfg["max_position_embeddings"]),
        dtype=str(cfg["torch_dtype"]))


# -- the seeded plain weights --------------------------------------------------

def plain_layer(m: Sizes, key):
    """One decoder layer's weights as the architecture names them, each
    ``(in, out)``, in the served dtype; norms and the router in float32.
    Gate and up halves are made as one matrix (``e_gu``, gate first).
    Expert e's matrices come from the key folded with e. A window layer and
    a full one have the same matrices."""
    dt = jnp.dtype(m.dtype)
    d, q, kv = m.d_model, m.heads * m.head_dim, m.kv_heads * m.head_dim
    ffe = m.expert_width
    ks = jax.random.split(key, 16)
    ids = m.lo + jnp.arange(m.held)
    sharp = d / SCORE_SPREAD
    return {
        "wq": randw(ks[0], (d, q), sharp, dt),
        "wk": randw(ks[1], (d, kv), sharp, dt),
        "wv": randw(ks[2], (d, kv), d, dt), "wo": randw(ks[3], (q, d), q, dt),
        "input_norm": norm_weight(ks[6], (d,)),
        "post_norm": norm_weight(ks[7], (d,)),
        "router": randw(ks[8], (d, m.router_width), d,
                        dt).astype(jnp.float32),
        "e_gu": jax.vmap(lambda e: randw(
            jax.random.fold_in(ks[9], e), (d, 2 * ffe), d, dt))(ids),
        "e_d": jax.vmap(lambda e: randw(
            jax.random.fold_in(ks[10], e), (ffe, d), ffe, dt))(ids),
    }


def plain_globals(m: Sizes, key):
    dt = jnp.dtype(m.dtype)
    ks = jax.random.split(key, 3)
    return {"embed": randw(ks[0], (m.vocab_size, m.d_model), 1, dt),
            "final_norm": norm_weight(ks[1], (m.d_model,)),
            "lm_head": randw(ks[2], (m.d_model, m.vocab_size), m.d_model,
                             dt)}


_layer_weights = jax.jit(plain_layer, static_argnums=0)
global_weights = jax.jit(plain_globals, static_argnums=0)


def layer_weights(m: Sizes, key, layer_index: int):
    return _layer_weights(m, key)


def head_weights(m: Sizes, g) -> dict:
    return {"final_norm": g["final_norm"], "eps": m.eps,
            "head": g["lm_head"]}


# -- the program's own configuration and parameters ----------------------------

def program_config(cfg: dict, m: Sizes):
    from triton_distributed_tpu.models.config import ExaoneMoeConfig

    if any(bool(w) != r for w, r in zip(m.windows, m.ropes)):
        raise ValueError("the program's block takes rope on the window "
                         "layers and none on the full ones")
    return ExaoneMoeConfig(
        model_name=cfg["source"], vocab_size=m.vocab_size, d_model=m.d_model,
        layer_types=tuple("sliding_attention" if w else "full_attention"
                          for w in m.windows),
        sliding_windows=m.windows, mlp_layer_types=("sparse",) * m.n_layers,
        n_heads=m.heads, n_kv_heads=m.kv_heads, head_dim=m.head_dim,
        d_ff=0, moe_d_ff=m.expert_width, n_experts=m.router_width,
        n_experts_per_tok=m.topk, n_shared_experts=0,
        routed_scaling_factor=1.0, experts_held=m.held, experts_lo=m.lo,
        rope_theta=m.theta, rms_eps=m.eps, max_length=m.max_length,
        dtype=jnp.dtype(m.dtype), qk_norm=False, scoring="softmax_topk",
        expert_activation="reglu", router_input="layer_input")


def program(cfg: dict, m: Sizes, seed: int, mesh, engine_kwargs: dict):
    """The program's configuration object and the whole stack of seeded
    parameters for it, in one jitted call from the seed."""
    from jax.sharding import NamedSharding

    from triton_distributed_tpu.models.exaone_moe import ExaoneMoe

    mcfg = program_config(cfg, m)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             ExaoneMoe(mcfg).param_specs())

    @functools.partial(jax.jit, out_shardings=shardings)
    def make(gkey, lkeys):
        lw = jax.vmap(lambda k: plain_layer(m, k))(lkeys)
        return {**plain_globals(m, gkey),
                "attn": {"input_norm": lw["input_norm"],
                         "post_norm": lw["post_norm"],
                         "attn": {"w_qkv": jnp.concatenate(
                                      [lw["wq"], lw["wk"], lw["wv"]],
                                      axis=-1),
                                  "w_o": lw["wo"]}},
                "moe": {"router": lw["router"], "w_gate_up": lw["e_gu"],
                        "w_down": lw["e_d"]}}

    return mcfg, make(*keys(seed, m.n_layers))


# -- the plain forward pass of one layer ---------------------------------------

def reglu(x, w_gu, w_d, precision):
    h = linear(x, w_gu, precision)
    ff = h.shape[-1] // 2
    return linear(jax.nn.relu(h[:, :ff]) * h[:, ff:], w_d, precision)


def routing(m: Sizes, x, router):
    """The layer's input x (S, d), as it is -> (weights (S, k), ids (S, k)):
    the k largest logits in float32, softmax over the chosen."""
    w, ids = jax.lax.top_k(jnp.dot(x, router.astype(jnp.float32)), m.topk)
    return jax.nn.softmax(w, axis=-1), ids


def routed_part(m: Sizes, x, u, lw, precision):
    """What the held experts give for the experts' input ``u``, routed from
    ``x``: every held expert over every token, in the plainest way,
    weighted by its routing weight where it was chosen (one expert at a
    time, so that one expert's body is all that is compiled)."""
    w, ids = routing(m, x, lw["router"])

    def add(y, expert):
        j, w_gu, w_d = expert
        w_j = jnp.sum(jnp.where(ids == m.lo + j, w, 0.0), axis=-1)   # (S,)
        return y + w_j[:, None] * reglu(u, w_gu, w_d, precision), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(u),
                        (jnp.arange(m.held), lw["e_gu"], lw["e_d"]))
    return y


@functools.partial(jax.jit, static_argnames=("m", "precision", "window",
                                             "roped"))
def _layer_forward(x, lw, *, m, precision, window, roped):
    S = x.shape[0]
    n = rms_norm(x, lw["input_norm"], m.eps)
    q = linear(n, lw["wq"], precision).reshape(S, m.heads, m.head_dim)
    k = linear(n, lw["wk"], precision).reshape(S, m.kv_heads, m.head_dim)
    v = linear(n, lw["wv"], precision).reshape(S, m.kv_heads, m.head_dim)
    if roped:
        pos = jnp.arange(S)
        q, k = rope(q, pos, m.theta), rope(k, pos, m.theta)
    a = attention(q, k, v, m.head_dim ** -0.5, window)
    h = x + linear(a, lw["wo"], precision)
    u = rms_norm(h, lw["post_norm"], m.eps)
    return h + routed_part(m, x, u, lw, precision)


def layer_forward(h, lw, m: Sizes, layer_index: int, precision: str):
    """One decoder layer over one whole sequence. h: (S, d) float32."""
    return _layer_forward(h, lw, m=m, precision=precision,
                          window=m.windows[layer_index],
                          roped=m.ropes[layer_index])


# -- operations and bytes -------------------------------------------------------
# (``attn_params``, ``expert_params``, ``full_attn_min_bytes``,
# ``window_attn_flops``, ``moe_ffn_min_bytes`` and ``moe_ffn_flops`` are the
# sibling family's, imported above: the same counts of the same shapes. The
# flops over the window are exact where ``window_attn_min_bytes`` is.)

def params_held(m: Sizes) -> int:
    """Every parameter this chip holds: the embedding table, the head, and
    the layers with the held experts (the norms' few thousand left out)."""
    return (2 * m.d_model * m.vocab_size + m.n_layers * (
        attn_params(m) + m.held * expert_params(m)
        + m.d_model * m.router_width))


def weight_bytes_read(m: Sizes, experts_touched: float) -> float:
    """The linear weights a decode step reads: attention, the router
    (float32) and the head once, and the three matrices of every routed
    expert that got a row."""
    return (itemsize(m.dtype) * (m.n_layers * attn_params(m)
                                 + m.d_model * m.vocab_size
                                 + expert_params(m) * float(experts_touched))
            + 4 * m.n_layers * m.d_model * m.router_width)


def window_attn_min_bytes(m: Sizes, rows: float) -> float:
    """The least bytes the window layers' attention of one step reads:
    ``window`` rows a decoding row a window layer. EXACT in the cell the
    benchmark runs and never over: every prompt of its mix is at least a
    window long (4,096), so a row that decodes has the whole window behind
    it; the reader hands rows, not contexts, and under a mix with shorter
    contexts this count would be too high."""
    return m.n_window * m.row_bytes * m.window * float(rows)


def moe_expected(m: Sizes, rows: float) -> tuple[float, float]:
    """(pairs held, experts touched) a step of ``rows`` live tokens gives
    over all layers IF every routed expert is as likely as another (seeded
    weights make it nearly so): each row picks a given expert with
    probability topk / router width."""
    p = m.topk / m.router_width
    return (m.n_layers * rows * p * m.held,
            m.n_layers * m.held * (1.0 - (1.0 - p) ** rows))


def rows_at_least(m: Sizes, context: float) -> int:
    """How many rows a context stands for at the least: one, or, where it
    is longer than any one request may be, a sum over so many rows."""
    return max(1, -(-int(context) // m.max_length))


def decode_step_min_bytes(m: Sizes, context_lens) -> float:
    """The least bytes one decode step has to move through HBM, one entry
    of ``context_lens`` a decoding row: the weights it reads (the routed
    experts that ``moe_expected`` has so many rows touch), each row's whole
    context once a full layer, and ``min(context, window)`` rows of it a
    window layer. Handed ONE summed context for several rows (the step
    roofline's reader does) it counts the FEWEST rows that sum can be of
    (no request is longer than ``max_length``), all but one of them of that
    length: those rows' experts and windows, fewer bytes than the step's
    and never more. Activations, the embedding rows and the pool's writes
    are left out: a lower bound."""
    rows, window = 0, 0.0
    for c in context_lens:
        k = rows_at_least(m, c)
        rows += k
        window += (k - 1) * m.window + min(c - (k - 1) * m.max_length,
                                           m.window)
    return (weight_bytes_read(m, moe_expected(m, rows)[1])
            + full_attn_min_bytes(m, context_lens)
            + m.n_window * m.row_bytes * window)
