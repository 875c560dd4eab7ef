"""Tensor-parallel attention layer.

TPU-native analog of the reference's ``layers/nvidia/tp_attn.py`` (``TP_Attn``
:78): QKV projection column-parallel (sharded over the head dim), output
projection row-parallel, with three forward modes mirroring the reference's
``torch_fwd`` (:170) / ``dist_triton_fwd`` (:203) / ``dist_triton_AR_fwd``
(:240):

  ``xla_fwd``  — golden path: all_gather x -> local QKV -> attention ->
                 psum_scatter (XLA collectives); correctness reference.
  ``dist_fwd`` — AG-GEMM(x, w_qkv) -> qk-norm/RoPE/cache -> attention ->
                 GEMM-RS(out, w_o): comm overlapped into both projections;
                 input and output are batch-sharded.
  ``ar_fwd``   — replicated x: local GEMMs -> attention -> one-shot
                 allreduce — the small-batch latency mode.

All ``*_fwd`` are per-device functions composable inside ``shard_map``
(the Qwen3 model stacks them under one jit). The KV cache holds this
device's kv-head shard for the FULL batch in every mode, so caches are
layout-compatible across modes (prefill in one, decode in another —
reference engine.py:121 prefills in torch mode then decodes dist).
"""

from __future__ import annotations

import dataclasses

import jax
from triton_distributed_tpu.runtime.compat import axis_size as _axis_size
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_distributed_tpu.kernels.allgather_gemm import (
    AGGEMMConfig,
    ag_gemm_device,
)
from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
    GEMMRSConfig,
    gemm_rs_device,
)
from triton_distributed_tpu.kernels.allreduce import oneshot_all_reduce
from triton_distributed_tpu.layers import nn
from triton_distributed_tpu.runtime.mesh import get_default_mesh


@dataclasses.dataclass(frozen=True)
class TPAttn:
    """GQA attention with TP-sharded weights.

    Weight sharding (reference ``_init_parameters``, tp_attn.py:97):
      w_qkv: (d_model, n_heads*dh + 2*n_kv_heads*dh) fused so each device's
             column shard is [q_local | k_local | v_local] (``pack_qkv``).
      w_o:   (n_heads*dh, d_model) sharded on the input (head) dim — heads
             are contiguous per rank, so plain P(axis, None) works.
      q_norm/k_norm: (dh,) replicated (Qwen3 per-head RMSNorm).
    """

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    axis: str = "tp"
    dtype: jnp.dtype = jnp.bfloat16
    rope_theta: float = 1e6
    rope_scaling: tuple | None = None   # llama3 NTK scaling (nn.rope_angles)
    qk_norm: bool = True
    rms_eps: float = 1e-6
    block_n: int = 256

    def sizes(self, world: int):
        """(q_size, kv_size) per device."""
        if self.n_heads % world or self.n_kv_heads % world:
            raise ValueError(
                f"heads ({self.n_heads}, {self.n_kv_heads}) not divisible by "
                f"world {world}")
        return (self.n_heads // world * self.head_dim,
                self.n_kv_heads // world * self.head_dim)

    # -- weight packing -----------------------------------------------------

    def pack_qkv(self, wq, wk, wv, world: int):
        """Fuse (d, Hq*dh), (d, Hkv*dh), (d, Hkv*dh) into the layout whose
        P(None, axis) shard is [q_local | k_local | v_local] per device."""
        d = self.d_model
        qs, kvs = self.sizes(world)
        q = wq.reshape(d, world, qs)
        k = wk.reshape(d, world, kvs)
        v = wv.reshape(d, world, kvs)
        return jnp.concatenate([q, k, v], axis=2).reshape(
            d, world * (qs + 2 * kvs))

    def unpack_qkv(self, w_qkv, world: int):
        """Inverse of ``pack_qkv`` -> (wq, wk, wv)."""
        d = self.d_model
        qs, kvs = self.sizes(world)
        w = w_qkv.reshape(d, world, qs + 2 * kvs)
        return (w[:, :, :qs].reshape(d, world * qs),
                w[:, :, qs:qs + kvs].reshape(d, world * kvs),
                w[:, :, qs + kvs:].reshape(d, world * kvs))

    def init(self, key, mesh: Mesh | None = None):
        """Sharded random params (models load real weights instead)."""
        mesh = mesh or get_default_mesh()
        world = mesh.shape[self.axis]
        kq, kk, kv, ko = jax.random.split(key, 4)
        d, dh = self.d_model, self.head_dim
        scale = d ** -0.5
        wq = (jax.random.normal(kq, (d, self.n_heads * dh)) * scale).astype(self.dtype)
        wk = (jax.random.normal(kk, (d, self.n_kv_heads * dh)) * scale).astype(self.dtype)
        wv = (jax.random.normal(kv, (d, self.n_kv_heads * dh)) * scale).astype(self.dtype)
        wo = (jax.random.normal(ko, (self.n_heads * dh, d)) * scale).astype(self.dtype)
        params = {
            "w_qkv": jax.device_put(self.pack_qkv(wq, wk, wv, world),
                                    NamedSharding(mesh, P(None, self.axis))),
            "w_o": jax.device_put(wo, NamedSharding(mesh, P(self.axis, None))),
        }
        if self.qk_norm:
            params["q_norm"] = jnp.ones((dh,), jnp.float32)
            params["k_norm"] = jnp.ones((dh,), jnp.float32)
        return params

    def param_specs(self):
        specs = {"w_qkv": P(None, self.axis), "w_o": P(self.axis, None)}
        if self.qk_norm:
            specs["q_norm"] = P()
            specs["k_norm"] = P()
        return specs

    # -- shared core --------------------------------------------------------

    def _qkv_to_attn(self, params, qkv, cache, offset, world,
                     use_flash_decode: bool = True, seq_lens=None,
                     interpret=None, block_tables=None, slot_mask=None,
                     paged_attn: str = "fused", layer=None):
        """qkv (B, L, q_size+2*kv_size) local-head projection -> attention
        output (B, L, q_size) plus the updated ``cache``. The qk-norm ->
        RoPE -> cache-append -> GQA-attend pipeline shared by every mode
        (reference tp_attn.py:217-233). Decode steps (L == 1) stream the KV
        cache through the split-KV Pallas kernel unless
        ``use_flash_decode=False`` (the xla golden mode stays dense jnp so
        mode-equality tests compare kernel against reference math).

        Two cache layouts, one pipeline:
        - contiguous (``block_tables=None``): ``cache`` is the pair
          ``(k_cache, v_cache)``, each (B, S, Hkv, dh); ``offset`` ()
          scalar (the Engine path) or (B,) per-row.
        - PAGED (serving): ``cache`` is the pool's state
          (``serving.kv_pool.PagedKVState``), taken and returned whole;
          this layer is where its arenas are read. ``k``/``v`` are one
          layer of the block pool (n_blocks, block_size, Hkv, dh) or, with
          ``layer`` () int32, the whole stacked arena (n_layers, n_blocks,
          block_size, Hkv, dh) — what the model's layer scan carries,
          appended to and read at ``[layer, block]`` where it lies;
          ``block_tables`` (B, max_blocks) maps each slot's sequence onto
          pool blocks, ``offset`` is the (B,) per-slot depth vector, and
          ``slot_mask`` (B,) drops dead slots' cache writes. New K/V
          scatter into the pool; attention reads back through
          ``nn.paged_attn_with_cache``, which routes EVERY step shape —
          decode, chunked prefill, ragged mixed — to the fused Pallas
          block-walk kernel (``paged_attn="fused"``, the default — one
          pool pass, no materialized view; NOTE it wins over
          ``use_flash_decode=False``, so the xla golden mode exercises the
          same fused kernel). ``paged_attn="gather"`` is the explicit
          paged_gather_kv escape hatch / test oracle — either way
          arriving/finishing sequences are pure DATA changes and the step
          never retraces.

        Quantized paged KV (the state has ``k_scale``/``v_scale`` arenas,
        the K/V arenas' shape minus dh, f32): the pool arenas hold
        int8/fp8 rows, new K/V are quantized per (row, kv head) at append
        time (``nn.paged_cache_update(scale_pool=...)``), and the
        attention read dequantizes — inside the fused kernel's VMEM
        staging, or on the gathered view in gather mode.
        """
        B, L, _ = qkv.shape
        qs, kvs = self.sizes(world)
        dh = self.head_dim
        q = qkv[..., :qs].reshape(B, L, -1, dh)
        k = qkv[..., qs:qs + kvs].reshape(B, L, -1, dh)
        v = qkv[..., qs + kvs:].reshape(B, L, -1, dh)
        if self.qk_norm:
            q = nn.rms_norm(q, params["q_norm"], self.rms_eps)
            k = nn.rms_norm(k, params["k_norm"], self.rms_eps)
        offset = jnp.asarray(offset, jnp.int32)
        # (1|B, L): per-row positions when offset is the per-slot vector.
        positions = offset.reshape(-1, 1) + jnp.arange(L)
        cos, sin = nn.rope_angles(positions, dh, self.rope_theta,
                                  self.rope_scaling)
        q = nn.apply_rope(q, cos, sin)
        k = nn.apply_rope(k, cos, sin)
        if block_tables is None:
            k_cache, v_cache = cache
            k_cache = nn.cache_update(k_cache, k, offset)
            v_cache = nn.cache_update(v_cache, v, offset)
            out = nn.attn_with_cache(q, k_cache, v_cache, offset,
                                     scale=dh ** -0.5,
                                     use_flash_decode=use_flash_decode,
                                     seq_lens=seq_lens, interpret=interpret)
            return out.reshape(B, L, qs), (k_cache, v_cache)

        wm = slot_mask                              # (B,) or None
        if seq_lens is not None:
            tok_valid = jnp.arange(L)[None] < seq_lens[:, None]
            wm = tok_valid if wm is None else (wm[:, None] & tok_valid)
        state = cache
        if state.k_scale is not None:
            k_pool, ks = nn.paged_cache_update(
                state.k, k, block_tables, offset, wm,
                scale_pool=state.k_scale, layer=layer)
            v_pool, vs = nn.paged_cache_update(
                state.v, v, block_tables, offset, wm,
                scale_pool=state.v_scale, layer=layer)
            scales = (ks, vs)
        else:
            k_pool = nn.paged_cache_update(state.k, k, block_tables,
                                           offset, wm, layer=layer)
            v_pool = nn.paged_cache_update(state.v, v, block_tables,
                                           offset, wm, layer=layer)
            ks = vs = scales = None
        out = nn.paged_attn_with_cache(
            q, k_pool, v_pool, block_tables, offset, scale=dh ** -0.5,
            slot_mask=slot_mask, use_flash_decode=use_flash_decode,
            seq_lens=seq_lens, interpret=interpret, paged_attn=paged_attn,
            kv_scales=scales, layer=layer)
        return out.reshape(B, L, qs), dataclasses.replace(
            state, k=k_pool, v=v_pool, k_scale=ks, v_scale=vs)

    # -- per-device forwards (inside shard_map) -----------------------------
    # ``cache`` in, ``cache`` out, whatever its layout: the pair
    # ``(k_cache, v_cache)`` of a contiguous cache or, with
    # ``block_tables``, the paged pool's state (``_qkv_to_attn``).

    def dist_fwd(self, params, x_local, cache, offset, *,
                 seq_lens=None, interpret=None, block_tables=None,
                 slot_mask=None, paged_attn: str = "fused", layer=None):
        """x_local: (B_local, L, d) batch-shard -> same layout out.
        AG-GEMM -> attention -> GEMM-RS (reference dist_triton_fwd :203).
        ``seq_lens``: (B,) varlen prefill lengths (nn.attn_with_cache).
        ``block_tables``/``slot_mask``/``paged_attn``: paged-KV serving
        path (``_qkv_to_attn``) — tables/mask cover the FULL batch,
        replicated. ``layer``: the state's arenas are the stacked ones,
        read and appended at this layer (``_qkv_to_attn``)."""
        world = _axis_size(self.axis)
        Bl, L, d = x_local.shape
        qkv = ag_gemm_device(
            x_local.reshape(Bl * L, d), params["w_qkv"], axis=self.axis,
            config=AGGEMMConfig(block_n=self.block_n), interpret=interpret)
        qkv = qkv.reshape(world * Bl, L, -1)
        out, cache = self._qkv_to_attn(
            params, qkv, cache, offset, world, seq_lens=seq_lens,
            interpret=interpret, block_tables=block_tables,
            slot_mask=slot_mask, paged_attn=paged_attn, layer=layer)
        out = gemm_rs_device(
            out.reshape(world * Bl * L, -1), params["w_o"], axis=self.axis,
            config=GEMMRSConfig(block_n=min(self.block_n, self.d_model)),
            interpret=interpret)
        return out.reshape(Bl, L, d), cache

    def ar_fwd(self, params, x_full, cache, offset, *,
               interpret=None, seq_lens=None, block_tables=None,
               slot_mask=None, paged_attn: str = "fused", layer=None):
        """x_full: (B, L, d) replicated -> replicated out.
        Local GEMMs -> one-shot allreduce (reference dist_triton_AR_fwd)."""
        world = _axis_size(self.axis)
        B, L, d = x_full.shape
        qkv = x_full @ params["w_qkv"]
        out, cache = self._qkv_to_attn(
            params, qkv, cache, offset, world, interpret=interpret,
            seq_lens=seq_lens, block_tables=block_tables,
            slot_mask=slot_mask, paged_attn=paged_attn, layer=layer)
        partial = out.reshape(B * L, -1) @ params["w_o"]
        out = oneshot_all_reduce(partial, axis=self.axis, interpret=interpret)
        return out.reshape(B, L, d), cache

    def xla_fwd(self, params, x_local, cache, offset, *,
                seq_lens=None, block_tables=None, slot_mask=None,
                paged_attn: str = "fused", layer=None):
        """Golden/baseline path: same math via jnp + XLA collectives.
        Batch-sharded in/out like ``dist_fwd``. ``paged_attn`` still
        routes paged decode through the fused kernel (interpret mode on
        CPU), so golden-vs-dist equality covers the block walk too; pass
        "gather" to pin the dense reference composition."""
        world = _axis_size(self.axis)
        Bl, L, d = x_local.shape
        x_full = jax.lax.all_gather(x_local, self.axis, axis=0, tiled=True)
        qkv = x_full.reshape(world * Bl * L, d) @ params["w_qkv"]
        qkv = qkv.reshape(world * Bl, L, -1)
        out, cache = self._qkv_to_attn(
            params, qkv, cache, offset, world,
            use_flash_decode=False, seq_lens=seq_lens,
            block_tables=block_tables, slot_mask=slot_mask,
            paged_attn=paged_attn, layer=layer)
        partial = out.reshape(world * Bl * L, -1) @ params["w_o"]
        out = jax.lax.psum_scatter(partial, self.axis, scatter_dimension=0,
                                   tiled=True)
        return out.reshape(Bl, L, d), cache
