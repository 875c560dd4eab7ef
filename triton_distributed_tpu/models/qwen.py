"""Qwen3 decoder stack, TP-sharded.

TPU-native analog of the reference's ``models/qwen.py`` (``Qwen3`` :115,
``Qwen3Layer`` :54): per-layer TP_Attn + TP_MLP with pre/post RMSNorm
residual blocks, embedding + final norm + lm_head, three forward modes
(reference ``set_fwd`` :85 'torch'/'triton_dist'/'triton_dist_AR' map to
``xla``/``dist``/``ar`` here).

TPU-first design differences:
- Layer parameters are STACKED (leading n_layers dim) and the decoder walks
  them with ``lax.scan`` — one traced layer body instead of n_layers copies,
  so compile time is O(1) in depth and XLA pipelines the whole stack.
- The forward is a pure per-device function composed inside one
  ``shard_map`` + ``jit`` (built by the Engine); the KV cache is an explicit
  pytree input/output.
- Weights load from a local HF checkpoint directory (``load_hf``) or
  init randomly; sharding happens at placement time via NamedSharding.

Forward layouts by mode (matching the reference's contracts):
  dist/xla — hidden states batch-sharded over TP inside the stack
             (reference dist_triton_fwd: "Input x is batch-sharded").
  ar       — hidden states replicated (reference torch/AR fwd).
Token ids come in replicated; logits go out replicated in every mode.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
from triton_distributed_tpu.runtime.compat import axis_size as _axis_size
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_distributed_tpu.layers import nn
from triton_distributed_tpu.layers.tp_attn import TPAttn
from triton_distributed_tpu.layers.tp_mlp import TPMLP
from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.obs import comm_ledger as _ledger
from triton_distributed_tpu.runtime import perf_model as _pm
from triton_distributed_tpu.runtime.mesh import get_default_mesh


@dataclasses.dataclass(frozen=True)
class Qwen3:
    config: ModelConfig
    axis: str = "tp"
    block_n: int = 256

    @functools.cached_property
    def attn(self) -> TPAttn:
        c = self.config
        return TPAttn(d_model=c.d_model, n_heads=c.n_heads,
                      n_kv_heads=c.n_kv_heads, head_dim=c.head_dim,
                      axis=self.axis, dtype=c.dtype, rope_theta=c.rope_theta,
                      rope_scaling=c.rope_scaling, qk_norm=c.qk_norm,
                      rms_eps=c.rms_eps, block_n=self.block_n)

    @functools.cached_property
    def mlp(self):
        """The FFN block: dense TP (TPMLP) or sparse MoE (MoEMLP) — both
        expose the same ``{dist,xla}_fwd(params, (n, d)) -> (n, d)``
        per-device contract, so the decoder body is family-agnostic (the
        reference's EP-MoE inference path, test_ep_moe_inference.py)."""
        c = self.config
        if c.n_experts:
            from triton_distributed_tpu.layers.moe_mlp import MoEMLP

            return MoEMLP(d_model=c.d_model, d_ff=c.moe_d_ff,
                          n_experts=c.n_experts, topk=c.n_experts_per_tok,
                          norm_topk_prob=c.norm_topk_prob, axis=self.axis,
                          dtype=c.dtype,
                          capacity_factor=c.moe_capacity_factor)
        return TPMLP(d_model=c.d_model, d_ff=c.d_ff, axis=self.axis,
                     dtype=c.dtype, block_n=self.block_n)

    #: Device-side counts a paged step returns as ``aux["stats"]`` (none
    #: here).
    step_stats = ()

    def step_flops(self, rows) -> float:
        """The analytic cost of a step over ``rows`` of (new tokens, cache
        length) (``obs/efficiency``'s ledger)."""
        from triton_distributed_tpu.runtime import perf_model

        return perf_model.step_flops(self.config, rows)

    def step_hbm_bytes(self, rows, **kw) -> float:
        from triton_distributed_tpu.runtime import perf_model

        return perf_model.step_hbm_bytes(self.config, rows, **kw)

    # -- parameters ---------------------------------------------------------

    def param_specs(self):
        a, c = self.axis, self.config
        attn = {"w_qkv": P(None, None, a), "w_o": P(None, a, None)}
        if c.qk_norm:
            attn["q_norm"] = P()
            attn["k_norm"] = P()
        specs = {
            "embed": P(),
            "final_norm": P(),
            "layers": {
                "input_norm": P(),
                "post_norm": P(),
                "attn": attn,
                "mlp": jax.tree.map(lambda sp: P(None, *sp),
                                    self.mlp.param_specs()),
            },
        }
        if not c.tie_embeddings:
            specs["lm_head"] = P()
        return specs

    def _place(self, params, mesh: Mesh):
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, self.param_specs())

    def init(self, key, mesh: Mesh | None = None):
        """Random sharded params (tests / dryruns; real runs use load_hf).

        Each layer-stacked leaf is generated with ONE vectorized random
        call under a jit with sharded ``out_shardings``: the old per-layer
        eager loop + ``jnp.stack`` held every per-layer weight AND the
        stacked copy live at once (2x the 8 GB of qwen3-4b — the
        standalone-bench OOM), while here XLA's buffer assignment frees
        each fp32 transient as soon as its bf16 leaf is cast."""
        mesh = mesh or get_default_mesh()
        world = mesh.shape[self.axis]
        c = self.config
        d, dh, L = c.d_model, c.head_dim, c.n_layers

        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 self.param_specs())

        @functools.partial(jax.jit, out_shardings=shardings)
        def make(key):
            ks = iter(jax.random.split(key, 9))

            def norm(*shape):
                return jnp.ones(shape, jnp.float32)

            def randw(k, shape, fan_in):
                # Sampled directly in the weight dtype: an fp32 intermediate
                # doubles the transient next to the bf16 leaf (the
                # depth-scaled 30b-a3b bench config's w_gate_up leaf alone
                # would carry a ~10 GB fp32 transient on the 16 GB chip).
                return (jax.random.normal(k, shape, c.dtype)
                        * jnp.asarray(fan_in ** -0.5, c.dtype))

            wq = randw(next(ks), (L, d, c.n_heads * dh), d)
            wk = randw(next(ks), (L, d, c.n_kv_heads * dh), d)
            wv = randw(next(ks), (L, d, c.n_kv_heads * dh), d)

            def mlp_leaves():
                if c.n_experts:
                    E, ffe = c.n_experts, c.moe_d_ff
                    return {
                        "router": (jax.random.normal(next(ks), (L, d, E))
                                   * d ** -0.5).astype(jnp.float32),
                        "w_gate_up": randw(next(ks), (L, E, d, 2 * ffe), d),
                        "w_down": randw(next(ks), (L, E, ffe, d), ffe),
                    }
                wg = randw(next(ks), (L, d, c.d_ff), d)
                wu = randw(next(ks), (L, d, c.d_ff), d)
                return {
                    "w_gate_up": jax.vmap(
                        lambda g, u: self.mlp.interleave_gate_up(
                            g, u, world))(wg, wu),
                    "w_down": randw(next(ks), (L, c.d_ff, d), c.d_ff),
                }
            attn = {
                "w_qkv": jax.vmap(
                    lambda q, k_, v: self.attn.pack_qkv(q, k_, v, world)
                )(wq, wk, wv),
                "w_o": randw(next(ks), (L, c.n_heads * dh, d),
                             c.n_heads * dh),
            }
            if c.qk_norm:
                attn["q_norm"] = norm(L, dh)
                attn["k_norm"] = norm(L, dh)
            params = {
                "embed": randw(next(ks), (c.vocab_size, d), d),
                "final_norm": norm(d),
                "layers": {
                    "input_norm": norm(L, d),
                    "post_norm": norm(L, d),
                    "attn": attn,
                    "mlp": mlp_leaves(),
                },
            }
            if not c.tie_embeddings:
                params["lm_head"] = randw(next(ks), (d, c.vocab_size), d)
            return params

        return make(key)

    def load_hf(self, path: str, mesh: Mesh | None = None):
        """Load weights from a local HuggingFace Qwen3 checkpoint directory
        (reference ``init_parameters``, qwen.py:147 + per-layer shard_local,
        tp_attn.py:97). Reads *.safetensors; no network access. Uses the
        native mmap reader (csrc/ via runtime/io_native.py — zero-copy
        page-cache views) when available, the ``safetensors`` package
        otherwise; identical results (tests/test_native_io.py)."""
        import glob
        import os

        from triton_distributed_tpu.runtime import io_native

        mesh = mesh or get_default_mesh()
        world = mesh.shape[self.axis]
        c = self.config
        files = sorted(glob.glob(os.path.join(path, "*.safetensors")))
        if not files:
            raise FileNotFoundError(f"no *.safetensors under {path!r}")
        if io_native.available():
            raw = io_native.read_checkpoint(files)
        else:
            from safetensors import safe_open

            raw = {}
            for f in files:
                with safe_open(f, framework="np") as sf:
                    for name in sf.keys():
                        raw[name] = sf.get_tensor(name)

        def t(name):  # HF stores (out, in); we use (in, out)
            return jnp.asarray(raw[name]).T.astype(c.dtype)

        def vec(name):
            return jnp.asarray(raw[name]).astype(jnp.float32)

        moe = bool(c.n_experts)
        mlp_init = ({"router": [], "w_gate_up": [], "w_down": []} if moe
                    else {"w_gate_up": [], "w_down": []})
        layers = {"input_norm": [], "post_norm": [],
                  "attn": {"w_qkv": [], "w_o": [], "q_norm": [], "k_norm": []},
                  "mlp": mlp_init}
        for i in range(c.n_layers):
            p = f"model.layers.{i}."
            layers["input_norm"].append(vec(p + "input_layernorm.weight"))
            layers["post_norm"].append(vec(p + "post_attention_layernorm.weight"))
            layers["attn"]["w_qkv"].append(self.attn.pack_qkv(
                t(p + "self_attn.q_proj.weight"),
                t(p + "self_attn.k_proj.weight"),
                t(p + "self_attn.v_proj.weight"), world))
            layers["attn"]["w_o"].append(t(p + "self_attn.o_proj.weight"))
            if c.qk_norm:
                layers["attn"]["q_norm"].append(vec(p + "self_attn.q_norm.weight"))
                layers["attn"]["k_norm"].append(vec(p + "self_attn.k_norm.weight"))
            if moe:
                # HF Qwen3-MoE: mlp.gate = router (E, d) stored (out, in);
                # per-expert gate/up/down under mlp.experts.{e}.
                layers["mlp"]["router"].append(
                    jnp.asarray(raw[p + "mlp.gate.weight"]).T.astype(
                        jnp.float32))
                gu, dn = self.mlp.stack_experts(
                    [t(p + f"mlp.experts.{e}.gate_proj.weight")
                     for e in range(c.n_experts)],
                    [t(p + f"mlp.experts.{e}.up_proj.weight")
                     for e in range(c.n_experts)],
                    [t(p + f"mlp.experts.{e}.down_proj.weight")
                     for e in range(c.n_experts)])
                layers["mlp"]["w_gate_up"].append(gu)
                layers["mlp"]["w_down"].append(dn)
            else:
                layers["mlp"]["w_gate_up"].append(self.mlp.interleave_gate_up(
                    t(p + "mlp.gate_proj.weight"),
                    t(p + "mlp.up_proj.weight"), world))
                layers["mlp"]["w_down"].append(t(p + "mlp.down_proj.weight"))
        if not c.qk_norm:
            layers["attn"].pop("q_norm")
            layers["attn"].pop("k_norm")
        params = {
            "embed": jnp.asarray(raw["model.embed_tokens.weight"]).astype(c.dtype),
            "final_norm": vec("model.norm.weight"),
            "layers": jax.tree.map(lambda x: jnp.stack(x), layers,
                                   is_leaf=lambda x: isinstance(x, list)),
        }
        if not c.tie_embeddings:
            params["lm_head"] = t("lm_head.weight")
        return self._place(params, mesh)

    # -- per-device forward (inside shard_map) ------------------------------
    # Two entries over one decoder: ``forward_device`` over ``Engine``'s own
    # contiguous cache (arrays in, arrays out, the layers of the cache as
    # the scan's ``xs``/``ys``) and ``forward_paged`` over the served pool
    # (its state in and out whole, as the scan's carry). They share the
    # embedding, the layer body and the head below.

    def _embed(self, params, ids, mode: str):
        """ids replicated — (B, L), or a paged step's flat (T,) — ->
        ``(h, rows)``: this device's rows of the hidden state (axis 0 cut
        into ``world`` equal runs in dist/xla mode, all of it in ar mode)
        and ``rows = (me, bl)``, this device's index and how many rows each
        device holds, or None in ar."""
        c = self.config
        if mode == "ar":
            if c.n_experts:
                raise ValueError(
                    "mode='ar' is a dense-TP latency path (GEMM + fused "
                    "AllReduce); an MoE FFN's comm IS the expert dispatch — "
                    "use mode='dist' (a2a kernels) or 'xla'")
            return jnp.take(params["embed"], ids, axis=0), None
        if mode not in ("dist", "xla"):
            raise ValueError(f"unknown mode {mode!r}")
        world = _axis_size(self.axis)
        B = ids.shape[0]
        if B % world:
            raise ValueError(f"batch {B} not divisible by world {world} "
                             f"(required in {mode} mode)")
        bl = B // world
        me = jax.lax.axis_index(self.axis)
        my_ids = jax.lax.dynamic_slice_in_dim(ids, me * bl, bl, axis=0)
        return jnp.take(params["embed"], my_ids, axis=0), (me, bl)

    def _scan_layers(self, params, mode: str):
        """``(scan_layers, heavy)``: what rides the layer scan as ``xs``,
        and the weight STACKS that stay out of it (``{"mlp": {...}, "attn":
        {...}}``, closed over whole, full (L, ...); None when every leaf
        rides) — the body passes the layer index down instead and the
        Pallas kernel that multiplies a stack block-indexes the layer
        itself. A scan-sliced weight is an operand XLA fuses into its own
        dot or einsum but NOT into a custom call, which gets the layer's
        matrix made first:

        - MoE dist mode: the experts' (L, E, ...) stacks (1.2 GB a layer
          MATERIALIZED at 30b-a3b; the stacked form also keeps the
          empty-expert weight-fetch skip live e2e).
        - dense dist mode on an axis of more than one device, where the
          four projections are AG-GEMM, its tail and GEMM-RS: ``w_qkv``,
          ``w_o``, ``w_gate_up``, ``w_down`` (each layer's 96.5 MB a chip
          of Qwen3-8B over four was STAGED into on-chip memory by a serial
          pass before its kernel started: 130 us a layer, a fifth of the
          step; PERF.md section 6, PR 47).

        The norm weights and everything small stay in ``xs``. With one
        device on the axis the products are XLA's own dots, which fuse
        the slice: nothing is taken out and the program is unchanged."""
        scan_layers = dict(params["layers"])
        if mode != "dist":
            return scan_layers, None
        if self.config.n_experts:
            take = {"mlp": ("w_gate_up", "w_down")}
        elif _axis_size(self.axis) > 1:
            take = {"mlp": ("w_gate_up", "w_down"), "attn": ("w_qkv", "w_o")}
        else:
            return scan_layers, None
        heavy = {}
        for block, names in take.items():
            rest = dict(scan_layers[block])
            heavy[block] = {name: rest.pop(name) for name in names}
            scan_layers[block] = rest
        return scan_layers, heavy

    def _layer(self, lp, h, cache, offset, li, *, mode: str, interpret,
               heavy=None, return_moe_stats: bool = False, **paged):
        """One decoder layer: ``(h, cache, stats)``. ``cache`` is this
        layer's ``(k, v)`` of the contiguous cache, h (rows, L, d), or,
        with ``paged`` (blocks, paged_attn, layer), the pool's state, h the
        flat token batch (T, d) — the attention layer reads the state and
        hands it back. ``heavy``: the weight stacks ``_scan_layers`` kept
        out of ``lp``, which the blocks they belong to read at ``li``."""
        c = self.config
        attn, mlp = self.attn, self.mlp
        heavy = heavy or {}

        def stacked(block):
            # (the block's parameters, the index of its stacks' layer)
            if block not in heavy:
                return lp[block], {}
            return dict(lp[block], **heavy[block]), {"layer_idx": li}

        resid = h
        hn = nn.rms_norm(h, lp["input_norm"], c.rms_eps)
        if mode == "dist":
            attn_params, kw = stacked("attn")
            a, cache = attn.dist_fwd(attn_params, hn, cache, offset,
                                     interpret=interpret, **kw, **paged)
        elif mode == "xla":
            a, cache = attn.xla_fwd(lp["attn"], hn, cache, offset, **paged)
        else:
            a, cache = attn.ar_fwd(lp["attn"], hn, cache, offset,
                                   interpret=interpret, **paged)
        h = resid + a
        resid = h
        hn = nn.rms_norm(h, lp["post_norm"], c.rms_eps)
        flat = hn.reshape(-1, c.d_model)
        stats = None
        if mode == "dist":
            mlp_params, kw = stacked("mlp")
            if return_moe_stats:
                m, stats = mlp.dist_fwd(mlp_params, flat, return_stats=True,
                                        interpret=interpret, **kw)
            else:
                m = mlp.dist_fwd(mlp_params, flat, interpret=interpret, **kw)
        elif mode == "xla":
            m = mlp.xla_fwd(lp["mlp"], flat)
        else:
            m = mlp.ar_fwd(lp["mlp"], flat, interpret=interpret)
        return resid + m.reshape(hn.shape), cache, stats

    def _gather_rows(self, x):
        """Every device's rows of ``x`` in rank order (XLA's own
        all-gather), entered in the comm ledger like the kernels'."""
        world = _axis_size(self.axis)
        if world > 1:
            _ledger.record_traced(
                "all_gather", axis=self.axis, world=world, method="xla",
                nbytes=_pm.wire_bytes_all_gather(
                    x.size * x.dtype.itemsize, world))
        return jax.lax.all_gather(x, self.axis, axis=0, tiled=True)

    def _head(self, params, h, rows, *, last=None, greedy_of=None):
        """Final norm and LM head: ``(logits (B, vocab) fp32 replicated,
        greedy)``. h (rows, L, d): row b's logits come from its last
        position. A paged step's flat h (T, d) with ``last`` (B,): from
        flat position ``last[b]``. ``greedy_of`` (a ``nn.TokenBlock``, the
        speculative verify step's) asks for the argmax next-token
        prediction at EVERY position of that block, (rows, L) int32; else
        None."""
        c = self.config
        h = nn.rms_norm(h, params["final_norm"], c.rms_eps)
        lm_head = (params["embed"].T if c.tie_embeddings
                   else params["lm_head"])
        greedy = None
        if greedy_of is not None:
            # Argmax prediction at EVERY position (draft-verify needs the
            # model's continuation after each consumed draft token). The
            # all-position matmul reduces to int32 on device; the
            # last-position logits below still go through the exact same
            # gather-then-dot path as the non-verify step.
            all_logits = jnp.dot(h, lm_head,
                                 preferred_element_type=jnp.float32)
            greedy = jnp.argmax(all_logits, axis=-1).astype(jnp.int32)
            if rows is not None:
                greedy = self._gather_rows(greedy)
            greedy = greedy[greedy_of.start:greedy_of.stop].reshape(
                -1, greedy_of.L)
        if last is None:
            last = h[:, -1]                                    # (*, d)
            if rows is not None:
                last = self._gather_rows(last)
        else:
            # The positions wanted lie anywhere in the flat batch: gather
            # it whole (T rows, once a step), then take.
            if rows is not None:
                h = self._gather_rows(h)
            last = jnp.take(h, last, axis=0)
        # bf16 operands, fp32 accumulation — no materialized fp32 weight copy
        logits = jnp.dot(last, lm_head, preferred_element_type=jnp.float32)
        return logits, greedy

    def forward_device(self, params, ids, k_cache, v_cache, offset, *,
                       mode: str = "dist", interpret=None,
                       return_moe_stats: bool = False):
        """One forward step on this device over the CONTIGUOUS cache
        (``Engine.prefill`` / ``decode_step`` / ``serve_scanned``).

        ids: (B, L) int32, replicated. k/v_cache: this device's shard
        (n_layers, B, S, local_kv_heads, dh). offset: () int32.
        Returns (logits (B, vocab) fp32 replicated, new_k, new_v).

        ``return_moe_stats=True`` (MoE + mode='dist' only) appends a 4th
        output: ``{"n_dropped_dispatch", "n_dropped_expert"}`` int32 totals
        summed over layers and psum'd over the EP axis — the capacity-audit
        observable (ADVICE r4: the default ``capacity_factor`` can drop
        (token, k) pairs under skewed routing, and HF semantics have no drop
        concept; serving stacks must audit these at their real traffic via
        ``Engine.moe_drop_stats`` and raise ``moe_capacity_factor`` or set
        explicit capacities if nonzero).
        """
        c = self.config
        if return_moe_stats and (not c.n_experts or mode != "dist"):
            raise ValueError("return_moe_stats requires an MoE config in "
                             "mode='dist' (drops only exist on the EP "
                             "dispatch path)")
        h, rows = self._embed(params, ids, mode)
        scan_layers, heavy = self._scan_layers(params, mode)

        def body(h, xs):
            lp, kc, vc, li = xs
            h, (kc, vc), stats = self._layer(
                lp, h, (kc, vc), offset, li, mode=mode, interpret=interpret,
                heavy=heavy, return_moe_stats=return_moe_stats)
            return h, (kc, vc) + ((stats,) if return_moe_stats else ())

        with _ledger.repeated(c.n_layers):
            h, ys = jax.lax.scan(
                body, h, (scan_layers, k_cache, v_cache,
                          jnp.arange(c.n_layers, dtype=jnp.int32)))
        moe_stats = (jax.tree.map(
            lambda x: jax.lax.psum(jnp.sum(x), self.axis), ys[2]),
        ) if return_moe_stats else ()
        logits, _ = self._head(params, h, rows)
        return (logits, ys[0], ys[1]) + moe_stats

    def forward_paged(self, params, ids, state, offsets, block_tables,
                      slot_mask, seq_lens=None, *, mode: str = "dist",
                      interpret=None, paged_attn: str = "fused",
                      spec_verify: bool = False):
        """One served step on this device over the block-paged pool:
        ``(logits (B, vocab) fp32 replicated, aux, state)``.

        ``state`` is the pool's device state
        (``serving.kv_pool.PagedKVState``, this device's shard of every
        arena), passed through whole and returned with the structure it
        came with; only the attention layer reads its fields. It rides the
        layer scan as CARRY beside h and the body passes the layer index
        down — the append scatters its rows into ``[li, block, line]`` of
        an arena where it lies and the fused kernel DMAs ``[li, block]``
        out of it. As ``xs``/``ys`` each layer of the pool was sliced out
        to feed the Pallas call and stacked back: five passes over both
        arenas a step and a second pool of temporaries (PERF.md, PR 26).

        The operands are all FULL-batch, replicated, and pure data (fixed
        shapes, so slot churn never retraces). ``ids`` says what the step's
        token batch is made of (``nn.paged_token_blocks``): an array
        (B, L) int32 is B rows of L positions (the decode step's (B, 1));
        a triple ``(tok (B,), chunk (P, L), dealt (P, 3))`` is the MIXED
        step's two blocks, one token a slot beside P rows of L prompt
        tokens that the host has dealt to the slots that take more (row k
        is ``dealt[k] = (slot, cache length before it, live tokens)``;
        several rows may be consecutive chunks of one slot), ``T = B + P *
        L`` positions in place of ``B * L``. The embedding, norms, linear
        layers and the residual stream run over the flat (T, d) batch —
        cut into ``world`` runs of rows in dist/xla mode — so each weight
        is read once a step; rope, the append and attention run a block at
        a time (``TPAttn._attend``). ``offsets`` (B,) per-slot depths;
        ``block_tables`` (B, max_blocks) int32 and ``slot_mask`` (B,) bool;
        ``seq_lens`` (B,) valid new-token counts per slot of a varlen
        step, over all its rows (slot b's logits then come from its last
        valid position), None for the decode step. ``paged_attn`` "fused"
        (default) routes every block through the fused block-walk kernel;
        "gather" pins the materialized-view escape hatch / test oracle
        (nn.paged_attn_with_cache).

        ``aux`` is a dict whose keys are fixed per build: ``"greedy"``
        int32 under ``spec_verify`` (speculative decoding's batched
        verify; requires ``seq_lens``) — the argmax next-token prediction
        at EVERY position of every row of the LAST block ((P, L) of the
        two-block form, (B, L) of the array form). Host-side longest-prefix
        acceptance compares draft token j+1 against ``greedy[row, j]``;
        position ``m`` doubles as the bonus token. The last-position
        ``logits`` path is untouched (same gather-then-dot arithmetic), so
        sampling stays bit-identical to the non-verify step. A model with
        ``step_stats`` adds ``"stats"``; this one has none.
        """
        c = self.config
        if spec_verify and seq_lens is None:
            raise ValueError("spec_verify requires seq_lens (the batched "
                             "verify step is a varlen mixed step)")
        world = 1 if mode == "ar" else _axis_size(self.axis)
        flat, blocks, last = nn.paged_token_blocks(
            ids, offsets, block_tables, slot_mask, seq_lens, multiple=world)
        h, rows = self._embed(params, flat, mode)
        scan_layers, heavy = self._scan_layers(params, mode)

        def body(carry, xs):
            h, state = carry
            lp, li = xs
            h, state, _ = self._layer(
                lp, h, state, None, li, mode=mode, interpret=interpret,
                heavy=heavy, blocks=blocks, paged_attn=paged_attn,
                layer=li)
            return (h, state), None

        with _ledger.repeated(c.n_layers):
            (h, state), _ = jax.lax.scan(
                body, (h, state),
                (scan_layers, jnp.arange(c.n_layers, dtype=jnp.int32)))
        logits, greedy = self._head(
            params, h, rows, last=last,
            greedy_of=blocks[-1] if spec_verify else None)
        return logits, ({"greedy": greedy} if spec_verify else {}), state
