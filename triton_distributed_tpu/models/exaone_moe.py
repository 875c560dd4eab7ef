"""EXAONE-MoE decoder stack: attention in every layer, of TWO kinds that keep
their rows differently, and after it a dense SwiGLU or sigmoid-routed experts
with a shared expert. The fifth model class behind ``Engine``
(``models.engine`` picks it when it is given an ``ExaoneMoeConfig`` or an
``Lfm2MoeConfig``: "A THIRD OPERATOR" below), with the contract
``BatchEngine`` and ``Engine._make_sm`` use: ``axis``, ``param_specs``,
``init``, ``step_stats`` and ``forward_paged`` (the pool's state in and out
whole).

The block (HF ``exaone_moe``), pre-norm::

    h = x + Attn_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))
    logits = RMSNorm(y_last) W_head

``Attn_l`` is ``layers.tp_attn.TPAttn`` with a per-head RMSNorm on queries
and keys, in one of two builds: a WINDOW layer (``window=w``, rope) whose
query at position ``p`` sees the keys ``p - w < j <= p`` and whose rows live
in the pool's ring storage, ``w`` and a step's take a slot
(``serving.kv_pool``); a FULL layer (no position embedding) that sees every
key and keeps rows in the block arenas for the whole context. ``FFN_l`` is
``layers.moe_mlp.swiglu`` (a dense layer) or
``layers.moe_mlp.HeldExpertsMoE`` (gated, no selection bias: this device one
chip's share of an expert-parallel deployment,
``ExaoneMoeConfig.experts_held``).

The layer walk is read from the configuration's two tuples, nothing assumes
a period. Weights are stacked BY KIND and a layer reads index ``(layers of
its kind before it)`` of its kind's stack where it lies; attention's weights
and the two norms are one stack over all layers (both builds have the same
matrices). ``models.nemotron_h.pattern_segments`` cuts the walk into runs of
a repeated unit (the published 48 layers: the dense layer, then ``window,
window, full, window`` x 11 and three more: 8 layer bodies traced, not 48);
a run of more than one is a ``lax.scan`` whose body is the unit written out.

FOUR THINGS ARE READ FROM THE CONFIGURATION and are not the block's own
(SmallThinker-21BA3B states all four otherwise: ``ExaoneMoeConfig
.smallthinker()``): the per-head norm (``qk_norm``; without it ``q_norm`` /
``k_norm`` are not parameters), the shared expert (``n_shared_experts`` 0:
no ``shared`` parameters, nothing added), the experts' score and expert
forms (``scoring``, ``expert_activation``: the ``topk`` largest raw logits
with a softmax over the chosen, gated ReGLU) and WHERE THE ROUTER READS
(``router_input``). ``"layer_input"`` is a router that stands before
attention::

    r = x W_r                     # the layer's INPUT, no norm
    h = x + Attn_l(RMSNorm(x));   y = h + Experts(RMSNorm(h); routed by r)

``HeldExpertsMoE.routed(..., route_from=x)`` routes from the stream the
layer came in with and feeds the experts the post-attention norm. In
program order the router's product and the sort stand where the experts
are called, after attention; they depend on nothing attention computes, so
the compiler is free to move them (nothing is claimed from where it puts
them). A walk with no dense layer has no ``dense`` stack.

A THIRD OPERATOR (LFM2-MoE, ``models.config.Lfm2MoeConfig``): a layer's
operator may be ``"conv"``, ``layers.short_conv.ShortConv``, the gated short
convolution, whose whole state is a window of ``conv_kernel - 1`` inputs a
slot in the pool's ``conv`` arena (no ``ssm`` arena beside it), read and
written at (the conv layers before it). Such a walk's attention layers are
its other layers only, so attention's weights are a stack over THOSE (read
at ``window + full`` layers before; with attention in every layer that is
the layer's own index) and the two norms a stack over all layers. Four more
things are then read from the configuration: rope on the full layers
(``rope_full``), key heads packed two to a row of the pool (``kv_pack``), the
embedding table as the head (``tie_embeddings``: no ``lm_head``) and a
selection bias a sparse layer (``expert_bias``: ``moe.bias`` is a parameter;
without it the sigmoid form is handed zeros).

What is not built, and refused by name: more than one device (the ring
storage and the convolution's windows are not sharded, and the experts'
exchange over ICI does not run under ``BatchEngine``), speculative verify
(a rejected draft's rows would have overwritten ring lines that the window
still needs, and would have entered a convolution's window) and a quantized
pool. Not there to call: the contiguous ``Engine.serve`` cache, and the
multi-token-prediction layer (the main model's logits do not depend on it).

Parameters (all replicated)::

    embed (V, d), final_norm (d,)[, lm_head (d, V)]
    attn:  input_norm, post_norm stacked over ALL layers;
           attn {w_qkv, w_o[, q_norm, k_norm]} over the attention layers
    conv:  stacked over the conv layers    {w_in (d, 3 d), conv_w (K, d),
           w_out (d, d)}  (absent where the walk has none)
    dense: stacked over the dense layers   {w_gate_up (d, 2 ff), w_down}
           (absent where the walk has none)
    moe:   stacked over the sparse layers
        router (d, E) f32[, bias (E,) f32], w_gate_up (held, d, 2 ffe),
        w_down (held, ffe, d)
        [, shared {w_gate_up (d, 2 ffs), w_down (ffs, d)}]
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_distributed_tpu.layers import nn
from triton_distributed_tpu.layers.moe_mlp import (
    MOE_STATS,
    HeldExpertsMoE,
    swiglu,
)
from triton_distributed_tpu.layers.short_conv import ShortConv, fresh_rows
from triton_distributed_tpu.layers.tp_attn import TPAttn
from triton_distributed_tpu.models.config import ExaoneMoeConfig, Lfm2MoeConfig
from triton_distributed_tpu.models.nemotron_h import pattern_segments
from triton_distributed_tpu.runtime.compat import axis_size as _axis_size
from triton_distributed_tpu.runtime.mesh import get_default_mesh

#: What a walk counts: layers, and layers of each kind.
COUNTED = ("layer", "window", "full", "conv", "dense", "moe")
#: What a walk with conv layers counts on the device beside the others.
CONV_STATS = ("conv_rows_advanced", "conv_states_reset")


@dataclasses.dataclass(frozen=True)
class ExaoneMoe:
    config: ExaoneMoeConfig | Lfm2MoeConfig
    axis: str = "tp"

    @functools.cached_property
    def layer_counts(self) -> dict:
        """Layers by kind (``BatchEngine.stats_snapshot()["layers"]``):
        the FFN's two and the operator's three, each layer in one of each."""
        kinds = self.config.layer_kinds
        return {k: n for k in ("dense", "moe", "window", "full", "conv")
                if (n := sum(k in pair for pair in kinds))}

    @functools.cached_property
    def n_attn_layers(self) -> int:
        return self.config.n_layers - self.layer_counts.get("conv", 0)

    @functools.cached_property
    def step_stats(self) -> tuple:
        """Device-side counts a paged step returns as ``aux["stats"]``
        (int32, this order); ``BatchEngine`` adds them to its counters of
        the same names: the sparse layers' four (``layers.moe_mlp
        .MOE_STATS``), where the walk has conv layers the windows advanced
        (a live token a conv layer) and those started from zero, and the
        rows appended over the attention layers."""
        conv = CONV_STATS if "conv" in self.layer_counts else ()
        return MOE_STATS + conv + ("kv_rows_appended",)

    @functools.cached_property
    def moe_forms(self) -> dict:
        """The expert layers by name (``stats_snapshot()["moe"]``): the
        score form, the expert form, whether a shared expert is added, and
        where the router reads."""
        return {**self.moe.forms,
                "shared": self.config.n_shared_experts > 0,
                "router_input": self.config.router_input}

    @functools.cached_property
    def segments(self) -> tuple:
        return pattern_segments(self.config.layer_kinds)

    def _attn(self, window: int | None) -> TPAttn:
        c = self.config
        return TPAttn(d_model=c.d_model, n_heads=c.n_heads,
                      n_kv_heads=c.n_kv_heads, head_dim=c.head_dim,
                      axis=self.axis, dtype=c.dtype, rope_theta=c.rope_theta,
                      qk_norm=c.qk_norm, rms_eps=c.rms_eps,
                      rope=window is not None or c.rope_full,
                      kv_pack=c.kv_pack, window=window)

    @functools.cached_property
    def attn(self) -> dict:
        """The two builds of attention, by kind."""
        return {"window": self._attn(self.config.window or None),
                "full": self._attn(None)}

    @functools.cached_property
    def conv(self) -> ShortConv:
        c = self.config
        return ShortConv(d_model=c.d_model, taps=c.conv_kernel)

    @functools.cached_property
    def moe(self) -> HeldExpertsMoE:
        c = self.config
        return HeldExpertsMoE(
            d_model=c.d_model, d_ff=c.moe_d_ff, n_experts=c.n_experts,
            topk=c.n_experts_per_tok, n_held=c.n_held, lo=c.experts_lo,
            routed_scaling=c.routed_scaling_factor,
            norm_topk_prob=c.norm_topk_prob, dtype=c.dtype,
            activation=c.expert_activation, scoring=c.scoring)

    # -- parameters ---------------------------------------------------------

    def param_shapes(self):
        """The parameter tree as ``(shape, fan_in)`` leaves; ``fan_in`` None
        marks a norm weight, 0 the selection bias."""
        c, n = self.config, self.layer_counts
        d, dh = c.d_model, c.head_dim
        ffs = c.n_shared_experts * c.moe_d_ff

        def stacked(count, tree):
            return jax.tree.map(
                lambda leaf: ((count, *leaf[0]), leaf[1]), tree,
                is_leaf=lambda x: isinstance(x, tuple))

        norms = {"input_norm": ((d,), None), "post_norm": ((d,), None)}
        attn = {"w_qkv": ((d, (c.n_heads + 2 * c.n_kv_heads) * dh), d),
                "w_o": ((c.n_heads * dh, d), c.n_heads * dh)}
        if c.qk_norm:
            attn.update(q_norm=((dh,), None), k_norm=((dh,), None))
        dense = {"w_gate_up": ((d, 2 * c.d_ff), d),
                 "w_down": ((c.d_ff, d), c.d_ff)}
        moe = {"router": ((d, c.n_experts), d),
               **({"bias": ((c.n_experts,), 0)} if c.expert_bias else {}),
               "w_gate_up": ((c.n_held, d, 2 * c.moe_d_ff), d),
               "w_down": ((c.n_held, c.moe_d_ff, d), c.moe_d_ff)}
        if ffs:
            moe["shared"] = {"w_gate_up": ((d, 2 * ffs), d),
                             "w_down": ((ffs, d), ffs)}
        tree = {"embed": ((c.vocab_size, d), d), "final_norm": ((d,), None),
                "attn": {**stacked(c.n_layers, norms),
                         "attn": stacked(self.n_attn_layers, attn)},
                "moe": stacked(n.get("moe", 0), moe)}
        if not c.tie_embeddings:
            tree["lm_head"] = ((d, c.vocab_size), d)
        if "dense" in n:
            tree["dense"] = stacked(n["dense"], dense)
        if "conv" in n:
            tree["conv"] = stacked(n["conv"], self.conv.param_shapes())
        return tree

    def param_specs(self):
        return jax.tree.map(lambda leaf: P(), self.param_shapes(),
                            is_leaf=lambda x: isinstance(x, tuple))

    def init(self, key, mesh: Mesh | None = None):
        """Random replicated params (tests): matrices N(0, 1/fan_in) in the
        model dtype (the router float32), norms 1, a selection bias 0."""
        mesh = mesh or get_default_mesh()
        c = self.config
        with_paths, treedef = jax.tree_util.tree_flatten_with_path(
            self.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 self.param_specs())

        @functools.partial(jax.jit, out_shardings=shardings)
        def make(key):
            out = []
            for k, (path, (shape, fan_in)) in zip(
                    jax.random.split(key, len(with_paths)), with_paths):
                if not fan_in:      # a norm's weight 1, the selection bias 0
                    out.append(jnp.full(shape, float(fan_in is None),
                                        jnp.float32))
                    continue
                dt = jnp.float32 if path[-1].key == "router" else c.dtype
                out.append(jax.random.normal(k, shape, dt)
                           * jnp.asarray(fan_in ** -0.5, dt))
            return jax.tree.unflatten(treedef, out)

        return make(key)

    # -- the analytic cost of a step (obs/efficiency's ledger) --------------

    def _weights(self) -> tuple[int, int]:
        """(weights a token is multiplied by, weights held), linear layers
        and head; a token meets ``topk * held / n_experts`` held experts on
        average."""
        c, n = self.config, self.layer_counts
        d = c.d_model
        attn = 2 * (c.n_heads + c.n_kv_heads) * c.head_dim * d
        expert = 3 * d * c.moe_d_ff
        fixed = (self.n_attn_layers * attn + n.get("conv", 0) * 4 * d * d
                 + n.get("dense", 0) * 3 * d * c.d_ff
                 + n.get("moe", 0) * (c.n_shared_experts * expert
                                      + d * c.n_experts)
                 + d * c.vocab_size)
        met = c.n_experts_per_tok * c.n_held / c.n_experts
        return (fixed + n.get("moe", 0) * met * expert,
                fixed + n.get("moe", 0) * c.n_held * expert)

    def _rows_read(self, rows) -> float:
        """Cache rows a step reads: a row's context once a full layer, the
        window and its take (or the context) once a window layer."""
        c = self.config
        return sum(c.n_cache_layers * kv + c.n_window_layers
                   * min(kv, c.window - 1 + n) for n, kv in rows)

    def step_flops(self, rows) -> float:
        """rows: (new tokens, cache length) per live slot."""
        c = self.config
        tokens = sum(n for n, _ in rows)
        attn = 4.0 * c.n_heads * c.head_dim
        return (2.0 * self._weights()[0] * tokens
                + attn * sum(n * (c.n_cache_layers * kv + c.n_window_layers
                                  * min(kv, c.window)) for n, kv in rows))

    def step_hbm_bytes(self, rows, *, itemsize: int, **_) -> float:
        c = self.config
        return itemsize * (self._weights()[1] + 2 * c.n_kv_heads * c.head_dim
                           * self._rows_read(rows))

    # -- per-device forward (inside shard_map) ------------------------------

    def forward_paged(self, params, ids, state, offsets, block_tables,
                      slot_mask, seq_lens=None, *, mode: str = "dist",
                      interpret=None, paged_attn: str = "fused",
                      spec_verify: bool = False):
        """One served step on this device, as ``Qwen3.forward_paged``:
        ``(logits (B, vocab) f32, aux, state)``, ``ids`` an array (B, L) or
        the mixed step's triple ``(tok (B,), chunk (P, L), dealt (P, 3))``
        (``nn.paged_token_blocks``). The projections, the FFNs and the
        residual stream see the flat token batch, attention one block at a
        time: a full layer over the block arenas by the block tables, a
        window layer over the ring storage by slot. ``aux["stats"]`` the
        int32 counts ``step_stats``. ``mode`` is accepted and not read: on
        one device ``dist``, ``xla`` and ``ar`` are one path."""
        c = self.config
        if _axis_size(self.axis) != 1:
            raise NotImplementedError(
                f"{c.model_name}: mesh axis {self.axis!r} has "
                f"{_axis_size(self.axis)} devices. Missing for more than "
                f"one: window layers under tensor parallelism (the pool's "
                f"ring storage and the window build's slot table are not "
                f"sharded), conv layers under it (nor are the "
                f"convolution's windows) and the routed experts' exchange "
                f"over ICI "
                f"(layers/ep_a2a_layer.py does not run under BatchEngine). "
                f"One device is one chip's share of the deployment "
                f"(ExaoneMoeConfig.experts_held); no code stands in for "
                f"the other chips.")
        if c.n_window_layers and state.wkv is None:
            raise ValueError(
                "the pool's state has no window storage: build the pool "
                "from this model's configuration (KVPool(config, ..., "
                "n_slots=...))")
        if "conv" in self.layer_counts and state.conv is None:
            raise ValueError(
                "the pool's state has no per-slot conv arena: build the "
                "pool from this model's configuration (KVPool(config, ..., "
                "n_slots=...))")
        if state.kv_scale is not None:
            raise NotImplementedError(
                "the EXAONE-MoE block has no quantized build of its pool")
        if spec_verify:
            raise NotImplementedError(
                "speculative verify is not built for a model with window "
                "or conv layers: a rejected draft's rows have overwritten "
                "ring lines (the verify row is not sized into the ring) and "
                "entered the convolution's window, which keeps no copy to "
                "roll back to")
        flat, blocks, last = nn.paged_token_blocks(
            ids, offsets, block_tables, slot_mask, seq_lens)
        # The residual stream is carried in float32 (the sub-layers read it
        # in the model dtype, the router as it is): in bfloat16 its rounding
        # at every add moves near-tied router scores across the top-k
        # boundary (``models.deepseek_v3``).
        h = jnp.take(params["embed"], flat, axis=0).astype(jnp.float32)
        valid = jnp.concatenate([b.valid() for b in blocks])

        # The routed experts' stacks stay whole: the grouped product indexes
        # ``[layer, expert]`` of them itself. Every other leaf is read at
        # ``[layer of its kind]`` of its stack where it lies (a slice of a
        # stack handed to a scan as ``xs`` is copied out first).
        light = {k: params[k] for k in ("dense", "conv") if k in params}
        light["attn"] = params["attn"]["attn"]
        light["norms"] = {k: params["attn"][k]
                          for k in ("input_norm", "post_norm")}
        light["moe"] = dict(params["moe"])
        heavy = {k: light["moe"].pop(k) for k in ("w_gate_up", "w_down")}
        if c.scoring == "sigmoid" and not c.expert_bias:
            heavy["bias"] = jnp.zeros((c.n_experts,), jnp.float32)
        early_router = c.router_input == "layer_input"

        def at(tree, idx):
            return jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0, False),
                tree)

        def layer(kinds, idx, h, state, stats):
            """One layer; ``idx[name]`` () int32, traced or not: the
            layer's index, and its index among the layers of its kinds."""
            op_kind, ffn_kind = kinds
            idx = {k: jnp.asarray(v, jnp.int32) for k, v in idx.items()}
            lp = at(light["norms"], idx["layer"])
            x_in = h
            hn = nn.rms_norm(h, lp["input_norm"], c.rms_eps).astype(c.dtype)
            if op_kind == "conv":
                a, state = self.conv.fwd(
                    at(light["conv"], idx["conv"]), hn, state, blocks=blocks,
                    layer=idx["conv"], interpret=interpret)
            else:
                a, state = self.attn[op_kind].local_fwd(
                    at(light["attn"], idx["window"] + idx["full"]), hn,
                    state, blocks=blocks, paged_attn=paged_attn,
                    layer=idx[op_kind], interpret=interpret)
            h = h + a
            hn = nn.rms_norm(h, lp["post_norm"], c.rms_eps)
            if ffn_kind == "dense":
                mp = at(light["dense"], idx["dense"])
                m = swiglu(hn.astype(c.dtype), mp["w_gate_up"], mp["w_down"])
            else:
                mp = dict(at(light["moe"], idx["moe"]), **heavy)
                m, st = self.moe.fwd(mp, hn, valid,
                                     x_in if early_router else None,
                                     layer_idx=idx["moe"],
                                     interpret=interpret)
                stats = stats + st
            return h + m, state, stats

        def unit_walk(unit, first, carry):
            """One unit of a run, its layers written out; ``first[name]``
            what the walk had counted of each name before the unit."""
            seen = dict.fromkeys(COUNTED, 0)
            for kinds in unit:
                carry = layer(kinds, {k: first[k] + seen[k] for k in COUNTED},
                              *carry)
                for k in ("layer", *kinds):
                    seen[k] += 1
            return carry

        def counted(unit) -> dict:
            return {k: len(unit) if k == "layer"
                    else sum(k in kinds for kinds in unit) for k in COUNTED}

        carry = (h, state, jnp.zeros((len(MOE_STATS),), jnp.int32))
        done = dict.fromkeys(COUNTED, 0)
        for unit, count in self.segments:
            per = counted(unit)
            if count == 1:
                carry = unit_walk(unit, done, carry)
            else:
                carry, _ = jax.lax.scan(
                    lambda carry, i, unit=unit, per=per, base=dict(done): (
                        unit_walk(unit, {k: base[k] + i * per[k]
                                         for k in COUNTED}, carry), None),
                    carry, jnp.arange(count, dtype=jnp.int32))
            done = {k: done[k] + count * per[k] for k in COUNTED}
        h, state, moe_stats = carry

        h = nn.rms_norm(h, params["final_norm"], c.rms_eps).astype(c.dtype)
        head = params["embed"].T if c.tie_embeddings else params["lm_head"]
        logits = jnp.dot(jnp.take(h, last, axis=0), head,
                         preferred_element_type=jnp.float32)

        live = jnp.sum(valid)
        counts = [live * self.n_attn_layers]
        if "conv" in self.layer_counts:
            counts = [live * self.layer_counts["conv"],
                      fresh_rows(blocks)] + counts
        stats = jnp.concatenate([moe_stats,
                                 jnp.stack(counts).astype(jnp.int32)])
        return logits, {"stats": stats}, state
