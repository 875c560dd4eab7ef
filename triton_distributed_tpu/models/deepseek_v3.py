"""DeepSeek-V3 decoder stack: latent attention over a latent paged cache,
leading dense layers, then expert layers with sigmoid routing and a shared
expert. The second model class behind ``Engine`` (``models.engine`` picks it
when it is given a ``DeepseekV3Config``), with the contract ``BatchEngine``
and ``Engine._make_sm`` use: ``axis``, ``param_specs``, ``init``,
``step_stats`` and ``forward_paged`` (the pool's state in and out whole).

Layers of two kinds: the ``n_dense_layers`` leading layers (a dense SwiGLU)
run one by one, then ONE ``lax.scan`` walks the expert layers. The latent
pool's state (one stacked arena ``(n_layers, n_blocks, block_size, row)``)
rides both as carry and every layer appends to and reads ``[layer, block]``
of it where it lies (as PR 26 left the K/V arenas of ``models.qwen``). The
routed experts' weights stay out of the scan's ``xs``: the grouped-product
kernel indexes ``[layer, expert]`` of the stacked arrays itself.

What is not built, and refused by name: tensor parallelism (the latent
attention's heads are not sharded, and the experts' exchange over ICI does
not run under ``BatchEngine``: mesh ``{"tp": 1}`` only, this chip standing
for one of a wide expert-parallel deployment's, see
``DeepseekV3Config.experts_held``), speculative verify and a quantized
pool. Not built and not there to call: the contiguous ``Engine.serve`` cache
(the class has no ``forward_device``) and the multi-token-prediction module
(the main model's logits do not depend on it).

Parameters (all replicated)::

    embed (V, d), final_norm (d,), lm_head (d, V)
    dense:  stacked over the leading layers
        input_norm, post_norm, attn {...}, mlp {w_gate_up (d, 2 ff), w_down}
    layers: stacked over the expert layers
        input_norm, post_norm, attn {...},
        moe {router (d, E) f32, bias (E,) f32,
             w_gate_up (held, d, 2 ffe), w_down (held, ffe, d),
             shared {w_gate_up (d, 2 ffs), w_down (ffs, d)}}

with ``attn`` as ``layers.mla_attn.MLAttn.param_shapes`` and every
``w_gate_up`` the gate and up halves concatenated.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_distributed_tpu.layers import nn
from triton_distributed_tpu.layers.mla_attn import MLAttn
from triton_distributed_tpu.layers.moe_mlp import (
    MOE_STATS,
    HeldExpertsMoE,
    swiglu,
)
from triton_distributed_tpu.models.config import DeepseekV3Config
from triton_distributed_tpu.runtime.compat import axis_size as _axis_size
from triton_distributed_tpu.runtime.mesh import get_default_mesh


@dataclasses.dataclass(frozen=True)
class DeepseekV3:
    config: DeepseekV3Config
    axis: str = "tp"

    #: Device-side counts a paged step returns as ``aux["stats"]`` (int32,
    #: this order); ``BatchEngine`` adds them to its counters of the same
    #: names.
    step_stats = MOE_STATS + ("latent_rows_appended",)

    @functools.cached_property
    def attn(self) -> MLAttn:
        c = self.config
        return MLAttn(d_model=c.d_model, n_heads=c.n_heads,
                      q_lora_rank=c.q_lora_rank, kv_lora_rank=c.kv_lora_rank,
                      nope=c.qk_nope_head_dim, rope=c.qk_rope_head_dim,
                      v_dim=c.v_head_dim, cache_row=c.cache_row,
                      rope_theta=c.rope_theta, rms_eps=c.rms_eps,
                      dtype=c.dtype)

    @functools.cached_property
    def moe(self) -> HeldExpertsMoE:
        c = self.config
        return HeldExpertsMoE(
            d_model=c.d_model, d_ff=c.moe_d_ff, n_experts=c.n_experts,
            topk=c.n_experts_per_tok, n_held=c.n_held, lo=c.experts_lo,
            routed_scaling=c.routed_scaling_factor,
            norm_topk_prob=c.norm_topk_prob, dtype=c.dtype)

    # -- parameters ---------------------------------------------------------

    def param_shapes(self):
        """The parameter tree as ``(shape, fan_in)`` leaves; ``fan_in`` None
        marks a norm weight, 0 the router's selection bias."""
        c = self.config
        d, nd = c.d_model, c.n_dense_layers
        nm = c.n_layers - nd
        ffs = c.n_shared_experts * c.moe_d_ff

        def stacked(n, tree):
            return jax.tree.map(lambda leaf: ((n, *leaf[0]), leaf[1]), tree,
                                is_leaf=lambda x: isinstance(x, tuple))

        attn = {k: (s, None if k.endswith("_norm") else s[-2])
                for k, s in self.attn.param_shapes().items()}
        common = {"input_norm": ((d,), None), "post_norm": ((d,), None),
                  "attn": attn}
        dense = dict(common, mlp={"w_gate_up": ((d, 2 * c.d_ff), d),
                                  "w_down": ((c.d_ff, d), c.d_ff)})
        moe = dict(common, moe={
            "router": ((d, c.n_experts), d), "bias": ((c.n_experts,), 0),
            "w_gate_up": ((c.n_held, d, 2 * c.moe_d_ff), d),
            "w_down": ((c.n_held, c.moe_d_ff, d), c.moe_d_ff),
            "shared": {"w_gate_up": ((d, 2 * ffs), d),
                       "w_down": ((ffs, d), ffs)}})
        return {"embed": ((c.vocab_size, d), d), "final_norm": ((d,), None),
                "lm_head": ((d, c.vocab_size), d),
                "dense": stacked(nd, dense), "layers": stacked(nm, moe)}

    def param_specs(self):
        return jax.tree.map(lambda leaf: P(), self.param_shapes(),
                            is_leaf=lambda x: isinstance(x, tuple))

    def init(self, key, mesh: Mesh | None = None):
        """Random replicated params (tests): weights N(0, 1/fan_in) in the
        model dtype, router and bias float32 (bias N(0, 0.01^2)), norms 1."""
        mesh = mesh or get_default_mesh()
        c = self.config
        shapes = self.param_shapes()
        with_paths, treedef = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda x: isinstance(x, tuple))
        paths = [jax.tree_util.keystr(p) for p, _ in with_paths]
        leaves = [leaf for _, leaf in with_paths]
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 self.param_specs())

        @functools.partial(jax.jit, out_shardings=shardings)
        def make(key):
            out = []
            for k, path, (shape, fan_in) in zip(
                    jax.random.split(key, len(leaves)), paths, leaves):
                if fan_in is None:
                    out.append(jnp.ones(shape, jnp.float32))
                elif fan_in == 0:
                    out.append(0.01 * jax.random.normal(k, shape,
                                                        jnp.float32))
                else:
                    dt = jnp.float32 if "router" in path else c.dtype
                    out.append(jax.random.normal(k, shape, dt)
                               * jnp.asarray(fan_in ** -0.5, dt))
            return jax.tree.unflatten(treedef, out)

        return make(key)

    # -- the analytic cost of a step (obs/efficiency's ledger) --------------

    def _weights(self) -> tuple[int, int]:
        """(weights a token is multiplied by, weights held), linear layers
        and head; a token meets ``topk * held / n_experts`` held experts on
        average."""
        c = self.config
        d, H = c.d_model, c.n_heads
        attn = (d * c.q_lora_rank
                + c.q_lora_rank * H * (c.qk_nope_head_dim
                                       + c.qk_rope_head_dim)
                + d * c.cache_width
                + H * c.kv_lora_rank * (c.qk_nope_head_dim + c.v_head_dim)
                + H * c.v_head_dim * d)
        expert = 3 * d * c.moe_d_ff
        nm = c.n_layers - c.n_dense_layers
        fixed = (c.n_layers * attn + c.n_dense_layers * 3 * d * c.d_ff
                 + nm * (c.n_shared_experts * expert + d * c.n_experts)
                 + d * c.vocab_size)
        met = c.n_experts_per_tok * c.n_held / c.n_experts
        return (fixed + nm * met * expert, fixed + nm * c.n_held * expert)

    def step_flops(self, rows) -> float:
        """rows: (new tokens, cache length) per live slot."""
        c = self.config
        tokens = sum(n for n, _ in rows)
        attn = 2.0 * c.n_layers * c.n_heads * (c.cache_row + c.kv_lora_rank)
        return (2.0 * self._weights()[0] * tokens
                + attn * sum(n * kv for n, kv in rows))

    def step_hbm_bytes(self, rows, *, itemsize: int, **_) -> float:
        c = self.config
        return itemsize * (self._weights()[1] + c.n_layers * c.cache_row
                           * sum(kv for _, kv in rows))

    # -- per-device forward (inside shard_map) ------------------------------

    def forward_paged(self, params, ids, state, offsets, block_tables,
                      slot_mask, seq_lens=None, *, mode: str = "dist",
                      interpret=None, paged_attn: str = "fused",
                      spec_verify: bool = False):
        """One served step on this device, as ``Qwen3.forward_paged``:
        ``(logits (B, vocab) f32, aux, state)``, ``ids`` an array (B, L) or
        the mixed step's triple ``(tok (B,), chunk (P, L), dealt (P, 3))``
        (``nn.paged_token_blocks``): the projections, the shared expert and
        the routed experts see the flat token batch (``HeldExpertsMoE``
        sizes its buffer from it), latent attention one block at a time.
        ``state`` is the pool's state, whose one arena is the latent
        (n_layers, n_blocks, block_size, row); ``aux["stats"]`` the int32
        counts ``step_stats`` over the live tokens, summed over the layers.
        ``mode`` is accepted and not read: on one device ``dist``, ``xla``
        and ``ar`` are one path."""
        c = self.config
        if _axis_size(self.axis) != 1:
            raise NotImplementedError(
                f"{c.model_name}: mesh axis {self.axis!r} has "
                f"{_axis_size(self.axis)} devices. Missing for more than "
                f"one: latent attention under tensor parallelism (its heads "
                f"and W_kvb are not sharded) and the routed experts' "
                f"exchange over ICI (layers/ep_a2a_layer.py does not run "
                f"under BatchEngine). One device is one chip's share of "
                f"the deployment (DeepseekV3Config.experts_held); no code "
                f"stands in for the other chips.")
        if not state.latent or state.kv_scale is not None:
            raise NotImplementedError(
                "the latent attention reads a latent pool: one arena of rows "
                "in the model dtype, no planes and no quantized build")
        if spec_verify:
            raise NotImplementedError(
                "speculative verify is not built for the latent/"
                "held-experts block")
        flat, blocks, last = nn.paged_token_blocks(
            ids, offsets, block_tables, slot_mask, seq_lens)
        # The residual stream is carried in float32 (the sub-layers read it
        # in the model dtype, the router as it is): in bfloat16 its rounding
        # at every add is the largest error of a step, and it moves
        # near-tied router scores across the top-k boundary.
        h = jnp.take(params["embed"], flat, axis=0).astype(jnp.float32)
        valid = jnp.concatenate([b.valid() for b in blocks])
        akw = dict(blocks=blocks, paged_attn=paged_attn, interpret=interpret)

        def block(h, state, lp, layer, ffn):
            hn = nn.rms_norm(h, lp["input_norm"], c.rms_eps)
            a, state = self.attn.fwd(lp["attn"], hn.astype(c.dtype), state,
                                     layer=layer, **akw)
            h = h + a
            hn = nn.rms_norm(h, lp["post_norm"], c.rms_eps)
            m, stats = ffn(hn)
            return h + m, state, stats

        for i in range(c.n_dense_layers):
            lp = jax.tree.map(lambda a: a[i], params["dense"])
            h, state, _ = block(
                h, state, lp, jnp.int32(i),
                lambda x: (swiglu(x.astype(c.dtype), lp["mlp"]["w_gate_up"],
                                  lp["mlp"]["w_down"]), None))

        scan_layers = dict(params["layers"])
        light = dict(scan_layers["moe"])
        heavy = {"w_gate_up": light.pop("w_gate_up"),
                 "w_down": light.pop("w_down")}
        scan_layers["moe"] = light

        def body(carry, xs):
            h, state, stats = carry
            lp, i = xs
            h, state, st = block(
                h, state, lp, c.n_dense_layers + i,
                lambda x: self.moe.fwd(dict(lp["moe"], **heavy), x, valid,
                                       layer_idx=i, interpret=interpret))
            return (h, state, stats + st), None

        n_moe = c.n_layers - c.n_dense_layers
        (h, state, moe_stats), _ = jax.lax.scan(
            body, (h, state, jnp.zeros((len(MOE_STATS),), jnp.int32)),
            (scan_layers, jnp.arange(n_moe, dtype=jnp.int32)))

        h = nn.rms_norm(h, params["final_norm"], c.rms_eps).astype(c.dtype)
        logits = jnp.dot(jnp.take(h, last, axis=0), params["lm_head"],
                         preferred_element_type=jnp.float32)
        stats = jnp.concatenate([
            moe_stats,
            (jnp.sum(valid) * c.n_layers).astype(jnp.int32)[None]])
        return logits, {"stats": stats}, state
