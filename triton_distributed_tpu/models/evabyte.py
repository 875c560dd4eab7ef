"""EvaByte decoder stack: EVA attention and a dense SwiGLU in EVERY layer,
over bytes. The sixth model class behind ``Engine`` (``models.engine`` picks
it when it is given an ``EvaByteConfig``), with the contract ``BatchEngine``
and ``Engine._make_sm`` use: ``axis``, ``param_specs``, ``init``,
``step_stats`` and ``forward_paged`` (the pool's state in and out whole).

The block (HF ``evabyte``), pre-norm, ``x^ = RMSNorm(x) (1 + g)``
[norm_add_unit_offset], the adds in float32 [fp32_skip_add]::

    y   = x + Eva_l(x^) W_o;        out = y + W_down(silu(W_gate y^) * W_up y^)
    logits = RMSNorm(h_last)(1 + g) W_head      float32, read as (heads, V)

``Eva_l`` is ``layers.eva_attn.EvaAttn``: the query's own aligned window
read key by key out of the pool's ring a slot, every earlier window through
one pooled key and value a chunk out of the block arenas, whose rows stand
for ``chunk_size`` tokens each. The head is ``n_pred_heads`` heads of
``vocab_size`` side by side; the step's logits are HEAD 0's (the next byte),
and ``aux["pred_logits"]`` carries all of them (``(B, n_pred_heads * V)``;
a step that does not read them does not compute them): heads 1.. are the
bytes after the next, held and checked, not served (self-drafting from them
is ROADMAP R16).

WHY A CLASS OF ITS OWN and not a fourth operator of ``models.exaone_moe``:
every layer is the same, so the walk is ONE ``lax.scan`` over stacks as deep
as the model and needs no segments (one body traced, 8 layers here, 32
whole); there are no experts, and that class's parameters, counters and
snapshot are built around a routed layer (``moe``, ``MOE_STATS``,
``moe_forms``); the norm carries a unit offset and the head several heads.
A layer's matrices are read at ``[layer]`` of their stacks inside the scan:
at these widths (405 MB a layer, 180 MB the gate-and-up matrix alone) the
products stream them where they lie, 739 GB/s on the chip against 713 for a
buffer a layer with the walk written out (PERF.md section 6, PR 49).
What is shared is shared by call: the ring's append
and the split and rope of ``layers.tp_attn``, ``layers.moe_mlp.swiglu``,
``nn.paged_token_blocks``, the block walk of ``kernels.paged_attention``.

What is not built, and refused by name: more than one device (the ring and
the summaries are not sharded), speculative verify (a rejected draft's rows
would have overwritten ring lines and entered a summary) and a quantized
pool. Not there to call: the contiguous ``Engine.serve`` cache.

Parameters (all replicated)::

    embed (V, d), final_norm (d,), lm_head (d, n_pred_heads * V)
    layers, stacked over all layers:
        input_norm, post_norm (d,)          the offsets g, not 1 + g
        attn {w_qkv (d, 3 H dh), w_o (H dh, d), mu, phi (H, dh) f32}
        mlp  {w_gate_up (d, 2 ff), w_down (ff, d)}
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_distributed_tpu.layers import nn
from triton_distributed_tpu.layers.eva_attn import EvaAttn, step_counts
from triton_distributed_tpu.layers.moe_mlp import swiglu
from triton_distributed_tpu.models.config import EvaByteConfig
from triton_distributed_tpu.runtime.compat import axis_size as _axis_size
from triton_distributed_tpu.runtime.mesh import get_default_mesh

#: Device-side counts a paged step returns as ``aux["stats"]`` (int32, this
#: order; ``BatchEngine`` adds them to its counters of the same names and
#: gives them to the step's span): chunks closed x layers, rows that crossed
#: a window boundary, the exact rows and the summary rows the DECODING rows
#: had to read (summed over rows and layers), rows appended over the layers.
EVA_STATS = ("eva_summaries_written", "eva_windows_opened", "eva_exact_rows",
             "eva_summary_rows", "kv_rows_appended")


@dataclasses.dataclass(frozen=True)
class EvaByte:
    config: EvaByteConfig
    axis: str = "tp"

    step_stats = EVA_STATS

    @functools.cached_property
    def layer_counts(self) -> dict:
        """Layers by kind (``BatchEngine.stats_snapshot()["layers"]``): the
        operator's name and the FFN's, each layer in one of each."""
        return {"eva": self.config.n_layers, "dense": self.config.n_layers}

    @functools.cached_property
    def attn(self) -> EvaAttn:
        c = self.config
        return EvaAttn(d_model=c.d_model, n_heads=c.n_heads,
                       head_dim=c.head_dim, window=c.window,
                       chunk=c.chunk_size, dtype=c.dtype,
                       rope_theta=c.rope_theta)

    # -- parameters ---------------------------------------------------------

    def param_shapes(self):
        """The parameter tree as ``(shape, fan_in)`` leaves; ``fan_in`` None
        marks a norm's offset, 0 a pooling vector."""
        c = self.config
        d, n = c.d_model, c.n_layers
        layer = {"input_norm": ((d,), None), "post_norm": ((d,), None),
                 "attn": self.attn.param_shapes(),
                 "mlp": {"w_gate_up": ((d, 2 * c.d_ff), d),
                         "w_down": ((c.d_ff, d), c.d_ff)}}
        return {"embed": ((c.vocab_size, d), d), "final_norm": ((d,), None),
                "lm_head": ((d, c.n_pred_heads * c.vocab_size), d),
                "layers": jax.tree.map(
                    lambda leaf: ((n, *leaf[0]), leaf[1]), layer,
                    is_leaf=lambda x: isinstance(x, tuple))}

    def param_specs(self):
        return jax.tree.map(lambda leaf: P(), self.param_shapes(),
                            is_leaf=lambda x: isinstance(x, tuple))

    def init(self, key, mesh: Mesh | None = None):
        """Random replicated params (tests): matrices N(0, 1/fan_in) in the
        model dtype, a norm's offset 0, the pooling vectors N(0, 1) in
        float32 (at a released checkpoint's near-zero draw a summary is a
        mean, which a test could not tell from a fault)."""
        mesh = mesh or get_default_mesh()
        c = self.config
        leaves, treedef = jax.tree.flatten(
            self.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 self.param_specs())

        @functools.partial(jax.jit, out_shardings=shardings)
        def make(key):
            out = []
            for k, (shape, fan_in) in zip(
                    jax.random.split(key, len(leaves)), leaves):
                if fan_in is None:
                    out.append(jnp.zeros(shape, jnp.float32))
                elif fan_in == 0:
                    out.append(jax.random.normal(k, shape, jnp.float32))
                else:
                    out.append(jax.random.normal(k, shape, c.dtype)
                               * jnp.asarray(fan_in ** -0.5, c.dtype))
            return jax.tree.unflatten(treedef, out)

        return make(key)

    # -- the analytic cost of a step (obs/efficiency's ledger) --------------

    def _weights(self) -> int:
        c = self.config
        return (c.n_layers * (4 * c.d_model * c.n_heads * c.head_dim
                              + 3 * c.d_model * c.d_ff)
                + c.d_model * c.vocab_size * c.n_pred_heads)

    def _row_reads(self, n: int, kv: int) -> int:
        """Cache rows a slot's step reads in one layer: its own window up
        to its ``n`` new tokens and one summary a chunk of the windows
        before cache length ``kv``."""
        c = self.config
        return (min(kv, kv % c.window + n)
                + (c.window // c.chunk_size) * (kv // c.window))

    def step_flops(self, rows) -> float:
        """rows: (new tokens, cache length) per live slot."""
        c = self.config
        return (2.0 * self._weights() * sum(n for n, _ in rows)
                + 4.0 * c.n_heads * c.head_dim * c.n_layers
                * sum(n * self._row_reads(n, kv) for n, kv in rows))

    def step_hbm_bytes(self, rows, *, itemsize: int, **_) -> float:
        c = self.config
        return itemsize * (self._weights() + 2 * c.n_heads * c.head_dim
                           * c.n_layers
                           * sum(self._row_reads(n, kv) for n, kv in rows))

    # -- per-device forward (inside shard_map) ------------------------------

    def forward_paged(self, params, ids, state, offsets, block_tables,
                      slot_mask, seq_lens=None, *, mode: str = "dist",
                      interpret=None, paged_attn: str = "fused",
                      spec_verify: bool = False):
        """One served step on this device, as ``Qwen3.forward_paged``:
        ``(logits (B, vocab) f32, aux, state)``, ``ids`` an array (B, L) or
        the mixed step's triple (``nn.paged_token_blocks``). The logits are
        prediction head 0's; ``aux["pred_logits"]`` every head's, ``(B,
        n_pred_heads * vocab)``, and ``aux["stats"]`` the int32 counts
        ``step_stats``. ``mode`` is accepted and not read: on one device
        ``dist``, ``xla`` and ``ar`` are one path."""
        c = self.config
        if _axis_size(self.axis) != 1:
            raise NotImplementedError(
                f"{c.model_name}: mesh axis {self.axis!r} has "
                f"{_axis_size(self.axis)} devices. Missing for more than "
                f"one: the ring and the chunk summaries of an EVA layer "
                f"under tensor parallelism (the pool's ring storage, the "
                f"window build's slot table and the producer are not "
                f"sharded over the key heads). One device is one pipeline "
                f"stage's share of the deployment; no code stands in for "
                f"the other stages.")
        if state.wkv is None:
            raise ValueError(
                "the pool's state has no window storage: build the pool "
                "from this model's configuration (KVPool(config, ..., "
                "n_slots=...))")
        if state.kv_scale is not None:
            raise NotImplementedError(
                "the EvaByte block has no quantized build of its pool")
        if spec_verify:
            raise NotImplementedError(
                "speculative verify is not built for a model with EVA "
                "layers: a rejected draft's rows have overwritten ring "
                "lines (the verify row is not sized into the ring) and may "
                "have closed a chunk, whose summary keeps no copy to roll "
                "back to")
        flat, blocks, last = nn.paged_token_blocks(
            ids, offsets, block_tables, slot_mask, seq_lens)
        # The residual stream is float32 (``fp32_skip_add``); the
        # sub-layers read it in the model dtype.
        h = jnp.take(params["embed"], flat, axis=0).astype(jnp.float32)

        def norm(x, g):
            return nn.rms_norm(x, 1.0 + g.astype(jnp.float32), c.rms_eps)

        def layer(carry, i):
            # every leaf read at ``[i]`` of its stack, where it lies
            h, state = carry
            lp = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                params["layers"])
            a, state = self.attn.fwd(
                lp["attn"], norm(h, lp["input_norm"]).astype(c.dtype), state,
                blocks=blocks, layer=i, paged_attn=paged_attn,
                interpret=interpret)
            h = h + a
            h = h + swiglu(norm(h, lp["post_norm"]).astype(c.dtype),
                           lp["mlp"]["w_gate_up"], lp["mlp"]["w_down"])
            return (h, state), None

        (h, state), _ = jax.lax.scan(
            layer, (h, state), jnp.arange(c.n_layers, dtype=jnp.int32))

        hn = jnp.take(norm(h, params["final_norm"]).astype(c.dtype), last,
                      axis=0)
        logits = jnp.dot(hn, params["lm_head"][:, :c.vocab_size],
                         preferred_element_type=jnp.float32)
        every = jnp.dot(hn, params["lm_head"],
                        preferred_element_type=jnp.float32)
        counts = step_counts(blocks, window=c.window, chunk=c.chunk_size)
        stats = jnp.stack(counts).astype(jnp.int32) * jnp.asarray(
            [c.n_layers, 1, c.n_layers, c.n_layers, c.n_layers], jnp.int32)
        return logits, {"stats": stats, "pred_logits": every}, state
