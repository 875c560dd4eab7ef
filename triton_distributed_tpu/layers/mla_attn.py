"""Multi-head latent attention (MLA), the DeepSeek-V2/V3 attention block,
over a block-paged LATENT cache.

The block (HF ``DeepseekV3Attention``), H heads:

    c_q = RMSNorm(x W_qa);   q = c_q W_qb          -> H x (nope + rope)
    [c_kv ; k_r] = x W_kva;  c_kv = RMSNorm(c_kv)
    q_rope, k_r take RoPE on interleaved pairs; k_r is shared by all heads
    [k_nope ; v] = c_kv W_kvb                      -> H x (nope + v)
    scores = (q_nope . k_nope + q_rope . k_r) * (nope + rope) ** -0.5
    o = softmax(scores) v, flattened, times W_o

What is served is the ABSORBED form, the same mathematics with ``W_kvb``
moved onto the query and the output:

    q_lat = q_nope W_kvb[k]^T        (per head, nope -> kv_lora_rank)
    scores = q_lat . c_kv + q_rope . k_r
    o_lat = softmax(scores) c_kv;    o = o_lat W_kvb[v]

so the cache holds ONE row a token, ``[c_kv ; k_r]`` after the norm and the
rotation (``kv_lora_rank + rope`` wide, zero-padded to a lane multiple), and
attention is multi-query attention with one key head whose keys are the
row and whose values are its first ``kv_lora_rank`` columns
(``nn.latent_attn_with_cache``). The expanded form, cheaper for long
chunks, is not built.

Parameters (per layer; ``w_kvb`` is stored split and per head, as the
absorbed form multiplies it): ``w_qa`` (d, q_lora), ``q_a_norm``,
``w_qb`` (q_lora, H*(nope+rope)), ``w_kva`` (d, kv_lora+rope),
``kv_a_norm``, ``w_kvb_k`` (H, nope, kv_lora), ``w_kvb_v`` (H, kv_lora, v),
``w_o`` (H*v, d). Everything is replicated: this layer does not run under
tensor parallelism yet.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from triton_distributed_tpu.layers import nn


@dataclasses.dataclass(frozen=True)
class MLAttn:
    d_model: int
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    nope: int                  # qk_nope_head_dim
    rope: int                  # qk_rope_head_dim
    v_dim: int                 # v_head_dim
    cache_row: int             # the pool's (padded) row width
    rope_theta: float = 1e4
    rms_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16

    def param_shapes(self) -> dict:
        H, d = self.n_heads, self.d_model
        return {
            "w_qa": (d, self.q_lora_rank),
            "q_a_norm": (self.q_lora_rank,),
            "w_qb": (self.q_lora_rank, H * (self.nope + self.rope)),
            "w_kva": (d, self.kv_lora_rank + self.rope),
            "kv_a_norm": (self.kv_lora_rank,),
            "w_kvb_k": (H, self.nope, self.kv_lora_rank),
            "w_kvb_v": (H, self.kv_lora_rank, self.v_dim),
            "w_o": (H * self.v_dim, d),
        }

    def fwd(self, params, x, state, *, blocks, paged_attn: str = "fused",
            layer=None, interpret=None):
        """x (T, d), a paged step's flat token batch -> (attention output
        (T, d), updated state). ``blocks`` (``nn.TokenBlock``s) says which
        runs of it are which sequences' new tokens: the projections run
        over the flat batch; the rotation, the append and attention a block
        at a time, each with its own offsets, tables, mask and lengths —
        every block's rows are appended before any block is attended, as
        in ``TPAttn._attend`` — and positions no block owns give zeros.
        ``state`` is the pool's state (``serving.kv_pool.PagedKVState``),
        taken and returned whole; its one arena ``k`` is one layer of the
        latent pool or, with ``layer``, the stacked arena: the new rows
        are appended where it lies and attention reads them back through
        the block table."""
        T = x.shape[0]
        H, r, rope = self.n_heads, self.kv_lora_rank, self.rope
        pad = self.cache_row - r - rope
        f32 = jnp.float32

        def dot(a, w):
            return jnp.dot(a, w, preferred_element_type=f32).astype(x.dtype)

        cq = nn.rms_norm(dot(x, params["w_qa"]), params["q_a_norm"],
                         self.rms_eps)
        q = dot(cq, params["w_qb"]).reshape(T, H, self.nope + rope)
        ckv = dot(x, params["w_kva"])
        c_kv = nn.rms_norm(ckv[..., :r], params["kv_a_norm"], self.rms_eps)
        q_lat = jnp.einsum("thn,hnc->thc", q[..., :self.nope],
                           params["w_kvb_k"],
                           preferred_element_type=f32).astype(x.dtype)

        pool, queries = state.kv, []
        for blk in blocks:
            span, rows = slice(blk.start, blk.stop), blk.offsets.shape[0]
            positions = blk.offsets[:, None] + jnp.arange(blk.L)
            cos, sin = nn.rope_angles(positions, rope, self.rope_theta)
            q_rope = nn.apply_rope_interleaved(
                q[span, :, self.nope:].reshape(rows, blk.L, H, rope),
                cos, sin)
            k_r = nn.apply_rope_interleaved(
                ckv[span, r:].reshape(rows, blk.L, 1, rope), cos, sin)
            queries.append(jnp.concatenate(
                [q_lat[span].reshape(rows, blk.L, H, r), q_rope,
                 jnp.zeros((rows, blk.L, H, pad), x.dtype)], axis=-1))
            row = jnp.concatenate(
                [c_kv[span].reshape(rows, blk.L, r), k_r[:, :, 0],
                 jnp.zeros((rows, blk.L, pad), x.dtype)], axis=-1)
            pool = nn.paged_cache_update(
                pool, row, blk.tables, blk.offsets,
                blk.valid().reshape(rows, blk.L), layer=layer)
        outs = [nn.latent_attn_with_cache(
            q_full, pool, blk.tables, blk.offsets, v_dim=r,
            scale=(self.nope + rope) ** -0.5, slot_mask=blk.mask,
            seq_lens=blk.seq_lens, interpret=interpret,
            paged_attn=paged_attn, layer=layer).reshape(-1, H, r)
            for q_full, blk in zip(queries, blocks)]
        if T > blocks[-1].stop:
            outs.append(jnp.zeros((T - blocks[-1].stop, H, r), x.dtype))
        o = jnp.einsum("thc,hcv->thv", jnp.concatenate(outs),
                       params["w_kvb_v"],
                       preferred_element_type=f32).astype(x.dtype)
        return (dot(o.reshape(T, H * self.v_dim), params["w_o"]),
                dataclasses.replace(state, kv=pool))
