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


def ring_slots(blocks, ring, window: int) -> list:
    """The slot of each row of each block of a step over ring storage, after
    the check that the ring holds the step: all of a step's appends come
    before any read, and the reader masks by position, so a line overwritten
    under the first row would be read as a valid key. A block that names its
    rows' slots may give ONE slot every row; one that names none gives slot
    b row b."""
    take = max(blk.L * (1 if blk.slots is None else blk.offsets.shape[0])
               for blk in blocks)
    lines = ring.shape[3] * ring.shape[4]
    if window - 1 + take > lines:
        raise ValueError(
            f"a slot's ring holds {lines} lines, and a step that gives "
            f"one slot {take} tokens behind a window of {window} "
            f"needs {window - 1 + take}: build the pool with "
            f"max_take >= {take} (KVPool(config, ..., max_take=))")
    return [jnp.arange(blk.offsets.shape[0], dtype=jnp.int32)
            if blk.slots is None else blk.slots for blk in blocks]


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
    rope: bool = True           # False: no position embedding ("nope")
    scale: float | None = None  # of the scores; None = head_dim ** -0.5
    # Key heads that share one row of the PAGED pool (``_attend``): heads
    # narrower than the lane count lie side by side in a lane-wide row.
    kv_pack: int = 1
    # A WINDOW layer (paged path only): a query sees the last ``window``
    # keys up to itself, and the layer's rows live in the pool's ring
    # storage (``state.wkv``), read and appended at ``layer``
    # of the WINDOW layers. None: every key, the block arenas.
    window: int | None = None

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

    def _qkv_rope(self, params, qkv, offset, world):
        """qkv (B, L, q_size+2*kv_size) local-head projection of B
        sequences' L new tokens -> ``(q, k, v)``, each (B, L, heads, dh):
        the split, the per-head qk-norm and RoPE at positions ``offset +
        [0, L)``, ``offset`` () or (B,) per row."""
        B, L, _ = qkv.shape
        qs, kvs = self.sizes(world)
        dh = self.head_dim
        q = qkv[..., :qs].reshape(B, L, -1, dh)
        k = qkv[..., qs:qs + kvs].reshape(B, L, -1, dh)
        v = qkv[..., qs + kvs:].reshape(B, L, -1, dh)
        if self.qk_norm:
            q = nn.rms_norm(q, params["q_norm"], self.rms_eps)
            k = nn.rms_norm(k, params["k_norm"], self.rms_eps)
        if not self.rope:
            return q, k, v
        # (1|B, L): per-row positions when offset is the per-slot vector.
        positions = jnp.asarray(offset, jnp.int32).reshape(-1, 1) \
            + jnp.arange(L)
        cos, sin = nn.rope_angles(positions, dh, self.rope_theta,
                                  self.rope_scaling)
        return nn.apply_rope(q, cos, sin), nn.apply_rope(k, cos, sin), v

    def _attend(self, params, qkv, cache, offset, world, *,
                use_flash_decode: bool = True, seq_lens=None,
                interpret=None, blocks=None, paged_attn: str = "fused",
                layer=None):
        """Local-head projection ``qkv`` -> attention output of the same
        leading shape, (..., q_size), plus the updated ``cache``: the
        qk-norm -> RoPE -> cache-append -> GQA-attend pipeline shared by
        every mode (reference tp_attn.py:217-233). Decode steps (L == 1)
        of a contiguous cache stream it through the split-KV Pallas kernel
        unless ``use_flash_decode=False`` (the xla golden mode stays dense
        jnp so mode-equality tests compare kernel against reference math).

        Two cache layouts, one pipeline:
        - contiguous (``blocks`` None): qkv (B, L, width); ``cache`` is the
          pair ``(k_cache, v_cache)``, each (B, S, Hkv, dh); ``offset`` ()
          scalar (the Engine path) or (B,) per-row; ``seq_lens`` (B,)
          varlen prefill lengths.
        - PAGED (serving): qkv is the step's FLAT token batch (T, width)
          and ``blocks`` (``nn.TokenBlock``s, from
          ``nn.paged_token_blocks``) says which runs of it are which
          sequences' new tokens, each block with its own shape (rows, L),
          offsets, block-table rows, live mask and lengths — the mixed
          step's decode block at the decode step's shape, its prefill
          block at chunk shape; positions no block owns give zeros.
          ``cache`` is the pool's state
          (``serving.kv_pool.PagedKVState``), taken and returned whole;
          this layer is where its arenas are read. ``kv`` is one
          layer of the block pool (n_blocks, 2, block_size, Hkv, dh), a
          block's K plane and V plane side by side, or, with
          ``layer`` () int32, the whole stacked arena (n_layers, n_blocks,
          2, block_size, Hkv, dh) — what the model's layer scan carries,
          appended to and read at ``[layer, block]`` where it lies. EVERY
          block's new K/V are scattered into the pool first (a token's K
          row and V row in ONE scatter), one scatter
          after another on the carried arena, and only then is any block
          attended (sequences own their blocks, so a block reads nothing
          another wrote this step): an append that had to leave the pool
          as an earlier read saw it would cost a copy of the arena.
          Attention reads back through ``nn.paged_attn_with_cache``,
          which routes EVERY block shape to the fused Pallas block-walk
          kernel (``paged_attn="fused"``, the default — one pool pass, no
          materialized view; NOTE it wins over ``use_flash_decode=False``,
          so the xla golden mode exercises the same fused kernel).
          ``paged_attn="gather"`` is the explicit paged_gather_kv escape
          hatch / test oracle — either way arriving/finishing sequences
          are pure DATA changes and the step never retraces.

        Quantized paged KV (the state has a ``kv_scale`` arena,
        the K+V arena's shape minus dh, f32): the pool arena holds
        int8/fp8 rows, new K/V are quantized per (row, kv head) at append
        time (``nn.paged_cache_update(scale_pool=...)``), and the
        attention read dequantizes — inside the fused kernel's VMEM
        staging, or on the gathered view in gather mode.
        """
        scale = self.head_dim ** -0.5 if self.scale is None else self.scale
        if blocks is None:
            q, k, v = self._qkv_rope(params, qkv, offset, world)
            k_cache, v_cache = cache
            k_cache = nn.cache_update(k_cache, k, offset)
            v_cache = nn.cache_update(v_cache, v, offset)
            out = nn.attn_with_cache(q, k_cache, v_cache, offset,
                                     scale=scale,
                                     use_flash_decode=use_flash_decode,
                                     seq_lens=seq_lens, interpret=interpret)
            return out.reshape(*qkv.shape[:2], -1), (k_cache, v_cache)

        if self.window is not None:
            return self._attend_window(params, qkv, cache, world, blocks,
                                       scale, paged_attn, layer, interpret)
        state, queries = cache, []
        for blk in blocks:
            part = qkv[blk.start:blk.stop].reshape(-1, blk.L, qkv.shape[-1])
            q, k, v = self._qkv_rope(params, part, blk.offsets, world)
            if self.kv_pack > 1:
                # ``kv_pack`` key heads to a row of the pool, side by side
                # (a free reshape of the rows); each query in its own key
                # head's columns.
                pk, g = self.kv_pack, self.n_heads // self.n_kv_heads
                q = nn.pack_query_heads(q, pk, g)
                k = k.reshape(*k.shape[:2], -1, pk * self.head_dim)
                v = v.reshape(*v.shape[:2], -1, pk * self.head_dim)
            queries.append(q)
            wm = blk.valid().reshape(-1, blk.L)
            # a token's K row and V row: one scatter into both planes
            pool = nn.paged_cache_update(
                state.kv, jnp.stack([k, v], axis=2), blk.tables,
                blk.offsets, wm, scale_pool=state.kv_scale, layer=layer)
            pool, scales = (pool, None) if state.kv_scale is None else pool
            state = dataclasses.replace(state, kv=pool, kv_scale=scales)

        def own_heads(o):
            # each query head's own columns of the packed value rows
            return o if self.kv_pack == 1 else nn.unpack_output_heads(
                o, self.kv_pack, self.n_heads // self.n_kv_heads)

        outs = [own_heads(nn.paged_attn_with_cache(
            q, state.kv, blk.tables, blk.offsets, scale=scale,
            slot_mask=blk.mask, use_flash_decode=use_flash_decode,
            seq_lens=blk.seq_lens, interpret=interpret,
            paged_attn=paged_attn, kv_scales=state.kv_scale,
            layer=layer)).reshape(blk.stop - blk.start, -1)
            for q, blk in zip(queries, blocks)]
        tail = qkv.shape[0] - blocks[-1].stop
        if tail:
            outs.append(jnp.zeros((tail, outs[0].shape[-1]), outs[0].dtype))
        return jnp.concatenate(outs), state

    def _attend_window(self, params, qkv, state, world, blocks, scale,
                       paged_attn, layer, interpret):
        """``_attend``'s paged path for a window layer: the same order
        (every block's rows appended, then every block attended), over the
        pool's ring storage. A row is found by its SLOT (``blk.slots``; row
        b of the decode block is slot b), not by a table of blocks: several
        rows of the prefill block may be one slot's consecutive chunks, and
        the ring holds the window and a step's largest take, so the later
        rows' appends overwrite nothing the first row reads."""
        if state.wkv is None:
            raise ValueError(
                "the pool's state has no window storage: build the pool "
                "from this model's configuration (KVPool(config, ..., "
                "n_slots=...))")
        if self.kv_pack > 1 or world != 1:
            raise NotImplementedError(
                "a window layer is built for one device and unpacked rows")
        slots = ring_slots(blocks, state.wkv, self.window)
        queries = []
        for blk, at in zip(blocks, slots):
            part = qkv[blk.start:blk.stop].reshape(-1, blk.L, qkv.shape[-1])
            q, k, v = self._qkv_rope(params, part, blk.offsets, world)
            queries.append(q)
            wm = blk.valid().reshape(-1, blk.L)
            state = dataclasses.replace(state, wkv=nn.window_cache_update(
                state.wkv, jnp.stack([k, v], axis=2), at, blk.offsets, wm,
                layer))
        outs = [nn.window_attn_with_cache(
            q, state.wkv, at, blk.offsets, window=self.window,
            layer=layer, scale=scale, slot_mask=blk.mask,
            seq_lens=blk.seq_lens, interpret=interpret,
            paged_attn=paged_attn).reshape(blk.stop - blk.start, -1)
            for q, blk, at in zip(queries, blocks, slots)]
        tail = qkv.shape[0] - blocks[-1].stop
        if tail:
            outs.append(jnp.zeros((tail, outs[0].shape[-1]), outs[0].dtype))
        return jnp.concatenate(outs), state

    # -- per-device forwards (inside shard_map) -----------------------------
    # ``cache`` in, ``cache`` out, whatever its layout: the pair
    # ``(k_cache, v_cache)`` of a contiguous cache, x (rows, L, d), or, with
    # ``blocks``, the paged pool's state, x the flat token batch (T, d)
    # (``_attend``).

    def dist_fwd(self, params, x_local, cache, offset=None, *,
                 seq_lens=None, interpret=None, blocks=None,
                 paged_attn: str = "fused", layer=None, layer_idx=None):
        """x_local: this device's rows of the batch — (B_local, L, d), or
        (T_local, d) of a paged step's flat batch — -> same layout out.
        AG-GEMM -> attention -> GEMM-RS (reference dist_triton_fwd :203).
        ``seq_lens``: (B,) varlen prefill lengths (nn.attn_with_cache).
        ``blocks``/``paged_attn``: paged-KV serving path (``_attend``) — the
        blocks cover the FULL batch, replicated. ``layer``: the state's
        arenas are the stacked ones, read and appended at this layer
        (``_attend``). ``layer_idx`` () int32: ``w_qkv`` and ``w_o`` are the
        model's layer STACKS (L, ...), handed to the kernels whole, which
        index the layer themselves (``ag_gemm_device``); the norm weights
        stay this layer's own."""
        world = _axis_size(self.axis)
        lead, d = x_local.shape[:-1], x_local.shape[-1]
        qkv = ag_gemm_device(
            x_local.reshape(-1, d), params["w_qkv"], axis=self.axis,
            config=AGGEMMConfig(block_n=self.block_n), interpret=interpret,
            layer=layer_idx)
        if blocks is None:
            qkv = qkv.reshape(world * lead[0], *lead[1:], -1)
        out, cache = self._attend(
            params, qkv, cache, offset, world, seq_lens=seq_lens,
            interpret=interpret, blocks=blocks, paged_attn=paged_attn,
            layer=layer)
        out = gemm_rs_device(
            out.reshape(-1, out.shape[-1]), params["w_o"], axis=self.axis,
            config=GEMMRSConfig(block_n=min(self.block_n, self.d_model)),
            interpret=interpret, layer=layer_idx)
        return out.reshape(*lead, d), cache

    def local_fwd(self, params, x, state, *, blocks, paged_attn: str = "fused",
                  layer=None, interpret=None):
        """One device holding every head, over the paged pool: x the flat
        token batch (T, d) -> (T, d), local GEMMs and no collective (what
        ``ar_fwd`` is without its all-reduce)."""
        qkv = jnp.dot(x, params["w_qkv"])
        out, state = self._attend(params, qkv, state, None, 1, blocks=blocks,
                                  paged_attn=paged_attn, layer=layer,
                                  interpret=interpret)
        return jnp.dot(out, params["w_o"]), state

    def ar_fwd(self, params, x_full, cache, offset=None, *,
               interpret=None, seq_lens=None, blocks=None,
               paged_attn: str = "fused", layer=None):
        """x_full: (B, L, d) or flat (T, d), replicated -> replicated out.
        Local GEMMs -> one-shot allreduce (reference dist_triton_AR_fwd)."""
        world = _axis_size(self.axis)
        qkv = x_full @ params["w_qkv"]
        out, cache = self._attend(
            params, qkv, cache, offset, world, interpret=interpret,
            seq_lens=seq_lens, blocks=blocks, paged_attn=paged_attn,
            layer=layer)
        partial = out.reshape(-1, out.shape[-1]) @ params["w_o"]
        out = oneshot_all_reduce(partial, axis=self.axis, interpret=interpret)
        return out.reshape(x_full.shape), cache

    def xla_fwd(self, params, x_local, cache, offset=None, *,
                seq_lens=None, blocks=None, paged_attn: str = "fused",
                layer=None):
        """Golden/baseline path: same math via jnp + XLA collectives.
        Batch-sharded in/out like ``dist_fwd``. ``paged_attn`` still
        routes paged decode through the fused kernel (interpret mode on
        CPU), so golden-vs-dist equality covers the block walk too; pass
        "gather" to pin the dense reference composition."""
        world = _axis_size(self.axis)
        x_full = jax.lax.all_gather(x_local, self.axis, axis=0, tiled=True)
        d = x_full.shape[-1]
        qkv = x_full.reshape(-1, d) @ params["w_qkv"]
        if blocks is None:
            qkv = qkv.reshape(*x_full.shape[:-1], -1)
        out, cache = self._attend(
            params, qkv, cache, offset, world,
            use_flash_decode=False, seq_lens=seq_lens, blocks=blocks,
            paged_attn=paged_attn, layer=layer)
        partial = out.reshape(-1, out.shape[-1]) @ params["w_o"]
        out = jax.lax.psum_scatter(partial, self.axis, scatter_dimension=0,
                                   tiled=True)
        return out.reshape(x_local.shape), cache
