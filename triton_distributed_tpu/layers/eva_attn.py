"""EVA attention over the paged pool: the fourth operator a served layer may
be, beside attention over every key, attention over a sliding window and the
gated short convolution.

EVA is Zheng, Yuan, Wang and Kong, "Efficient Attention via Control Variates"
(ICLR 2023), in the form EvaByte serves: the EXACT set of a query is its own
ALIGNED window, and every earlier window is read through one control variate
a chunk, a pooled key and a pooled value under two learned vectors a head.
For one head, ``s = 1 / sqrt(head_dim)``, position ``i``, window ``w``,
chunk ``c`` the positions ``[chunk * c, chunk * (c + 1))``::

    q_i, k_i = rope(x_i W_q, i), rope(x_i W_k, i);   v_i = x_i W_v
    once chunk c is whole:
        k~_c = sum_j softmax_j(s k_j . mu)  k_j
        v~_c = sum_j softmax_j(s k_j . phi) v_j
    E_i = { j : (i // w) w <= j <= i };   C_i = { c : c < (w / chunk)(i // w) }
    o_i = ( sum_{E_i} e^{s q_i.k_j} v_j + sum_{C_i} e^{s q_i.k~_c} v~_c )
        / ( sum_{E_i} e^{s q_i.k_j}     + sum_{C_i} e^{s q_i.k~_c} )

So the window does NOT slide (at a boundary the exact set falls back to one
key), and a chunk of the query's own window is never read as a summary,
whole or not. Rope comes before the pooling and a summary key takes no
further rotation.

The layer keeps TWO kinds of cache in the pool's state
(``serving.kv_pool``), both at ``layer`` of arenas as deep as the model:

- the window's rows in the RING a slot (``state.wkv``), as a
  sliding-window layer does (``layers.tp_attn``): ``window - 1`` rows and a
  step's largest take;
- one K row and one V row A CHUNK in the block arena (the two planes of
  ``state.kv``), through the slot's block table: row ``c`` of a sequence is
  chunk ``c``'s summary, so a block of ``block_size`` rows stands for
  ``block_size * chunk`` positions (``config.kv_row_tokens``).

A step, in this order (several rows of the mixed step's prefill block may be
one slot's consecutive chunks and may straddle a window boundary between
them): EVERY row's new K and V are appended to the ring; then THE PRODUCER
(``nn.eva_summary_update``) pools every chunk the step closed out of the
ring's lines (a ring block IS a chunk: ``block_size == chunk``) and writes
its two rows; then every row is read (``nn.eva_attn_with_cache``): the
ring from the row's window's first position to its own frontier and the
summaries up to the window before, under one running maximum and sum. A
chunk a step leaves ragged waits in the ring; a preempted request's ring and
summaries are rebuilt from position 0 by its recompute, as its rows are.

One device, every head (``world == 1``): the ring and the summaries are not
sharded (ROADMAP Queue 2 A).
"""

from __future__ import annotations

import dataclasses
import functools

import jax.numpy as jnp

from triton_distributed_tpu.layers import nn
from triton_distributed_tpu.layers.tp_attn import TPAttn, ring_slots


@dataclasses.dataclass(frozen=True)
class EvaAttn:
    """Parameters of one layer: ``w_qkv`` (d, 3 H dh) as ``[q | k | v]``,
    ``w_o`` (H dh, d), ``mu`` and ``phi`` (H, dh) float32: the two pooling
    vectors a head (EvaByte's ``adaptive_mu_k`` / ``adaptive_phi``)."""

    d_model: int
    n_heads: int
    head_dim: int
    window: int
    chunk: int
    dtype: jnp.dtype = jnp.bfloat16
    rope_theta: float = 1e5

    @functools.cached_property
    def _core(self) -> TPAttn:
        # the split of the projection and the rope are attention's own
        return TPAttn(d_model=self.d_model, n_heads=self.n_heads,
                      n_kv_heads=self.n_heads, head_dim=self.head_dim,
                      dtype=self.dtype, rope_theta=self.rope_theta,
                      qk_norm=False)

    def param_shapes(self) -> dict:
        """``(shape, fan_in)`` leaves; ``fan_in`` 0 marks a pooling
        vector (float32, the configuration's family draws it)."""
        d, hd = self.d_model, self.n_heads * self.head_dim
        return {"w_qkv": ((d, 3 * hd), d), "w_o": ((hd, d), hd),
                "mu": ((self.n_heads, self.head_dim), 0),
                "phi": ((self.n_heads, self.head_dim), 0)}

    def fwd(self, params, x, state, *, blocks, layer,
            paged_attn: str = "fused", interpret=None):
        """x the flat token batch (T, d) -> ``((T, d), state)``: local
        products and no collective. ``blocks`` as ``TPAttn._attend`` takes
        them; ``layer`` () int32 indexes the ring and the row arenas."""
        if state.wkv is None or state.latent:
            raise ValueError(
                "the pool's state lacks the ring or the row arenas an EVA "
                "layer keeps: build the pool from this model's "
                "configuration (KVPool(config, ..., n_slots=...))")
        scale = self.head_dim ** -0.5
        qkv = jnp.dot(x, params["w_qkv"])
        slots = ring_slots(blocks, state.wkv, self.window)
        queries = []
        for blk, at in zip(blocks, slots):
            part = qkv[blk.start:blk.stop].reshape(-1, blk.L, qkv.shape[-1])
            q, k, v = self._core._qkv_rope(params, part, blk.offsets, 1)
            queries.append(q)
            wm = blk.valid().reshape(-1, blk.L)
            state = dataclasses.replace(state, wkv=nn.window_cache_update(
                state.wkv, jnp.stack([k, v], axis=2), at, blk.offsets, wm,
                layer))
        # every append of the step lies in the ring: the chunks it closed
        for blk, at in zip(blocks, slots):
            state = dataclasses.replace(state, kv=nn.eva_summary_update(
                state.kv, state.wkv, params["mu"],
                params["phi"], at, blk.tables, blk.offsets,
                jnp.sum(blk.valid().reshape(-1, blk.L), axis=1), layer,
                chunk=self.chunk, scale=scale, max_len=blk.L))
        outs = [nn.eva_attn_with_cache(
            q, state.wkv, state.kv, at, blk.tables,
            blk.offsets, window=self.window, chunk=self.chunk, layer=layer,
            scale=scale, slot_mask=blk.mask, seq_lens=blk.seq_lens,
            interpret=interpret, paged_attn=paged_attn).reshape(
                blk.stop - blk.start, -1)
            for q, blk, at in zip(queries, blocks, slots)]
        tail = qkv.shape[0] - blocks[-1].stop
        if tail:
            outs.append(jnp.zeros((tail, outs[0].shape[-1]), outs[0].dtype))
        return jnp.dot(jnp.concatenate(outs), params["w_o"]), state


def step_counts(blocks, *, window: int, chunk: int):
    """What one layer's step is, counted from the blocks alone (int32
    scalars): ``(chunks closed, rows that crossed a window boundary, exact
    rows and summary rows the DECODING rows had to read, rows appended)``.
    A decoding row at position ``p`` has to read ``p % window + 1`` keys of
    its window and ``(window / chunk) (p // window)`` summaries: the
    roofline's numerator, what the step needed and not what a walk
    visited."""
    closed = opened = exact = seen = appended = jnp.int32(0)
    for blk in blocks:
        n = jnp.sum(blk.valid().reshape(-1, blk.L), axis=1).astype(jnp.int32)
        o = blk.offsets.astype(jnp.int32)
        live = n > 0
        closed += jnp.sum((o + n) // chunk - o // chunk)
        # boundaries among the new positions (position 0 opens no window)
        opened += jnp.sum(jnp.where(
            live, (o + n - 1) // window - (jnp.maximum(o, 1) - 1) // window,
            0))
        appended += jnp.sum(n)
        if blk.L == 1:
            exact += jnp.sum(jnp.where(live, o % window + 1, 0))
            seen += jnp.sum(jnp.where(
                live, (window // chunk) * (o // window), 0))
    return closed, opened, exact, seen, appended
