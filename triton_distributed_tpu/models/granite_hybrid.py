"""Granite-4.0-H decoder stack: Mamba-2 layers that keep a fixed-size state
a SLOT beside a few attention layers that keep rows in the paged pool, a
SwiGLU after every mixer. The third model class behind ``Engine``
(``models.engine`` picks it when it is given a ``GraniteHybridConfig``),
with the contract ``BatchEngine`` and ``Engine._make_sm`` use: ``axis``,
``param_specs``, ``init``, ``step_stats`` and ``forward_paged`` (the pool's
state in and out whole).

The block (HF ``GraniteMoeHybrid`` with no routed experts), ``r`` the
residual multiplier::

    h_0 = E[ids] * embedding_multiplier
    h <- h + r * Mixer_i(RMSNorm(h));  h <- h + r * SwiGLU(RMSNorm(h))
    logits = RMSNorm(h) E^T / logits_scaling          (the head is tied)

``Mixer_i`` is ``layers.mamba2.Mamba2`` or, where ``layer_types[i]`` says
``"attention"``, grouped-query attention with no position embedding and
scores scaled by ``attention_multiplier`` (``layers.tp_attn.TPAttn`` with
``rope=False``; its narrow key heads packed ``kv_pack`` to a lane-wide row
of the pool).

Two kinds of state ride the step as ONE pytree
(``serving.kv_pool.PagedKVState``): row arenas as deep as the model has
attention layers, and the per-slot arenas ``ssm`` and ``conv`` as deep as
it has Mamba-2 layers. The layers are walked by ONE ``lax.scan`` over the
PERIODS of ``layer_types`` (the shortest prefix that repeats: 5 Mamba-2,
attention, 4 Mamba-2 for the published model), the period's layers written
out in the scan's body, with the state as carry; layer ``j`` of its kind in
period ``i`` reads and writes ``[i * (layers of the kind a period) + j]``
of its arenas where they lie.

What is not built, and refused by name: more than one device (the Mamba-2
heads and the per-slot arenas are not sharded), speculative verify (a
rejected draft would have to roll the state back) and a quantized pool.
Not there to call: the contiguous ``Engine.serve`` cache.

Parameters (all replicated)::

    embed (V, d), final_norm (d,)
    periods: every leaf stacked over (periods, layers of the kind a period)
        mamba     {input_norm, post_norm, mixer {Mamba2.param_shapes},
                   mlp {w_gate_up (d, 2 ff), w_down (ff, d)}}
        attention {input_norm, post_norm, attn {w_qkv, w_o}, mlp {...}}
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_distributed_tpu.layers import nn
from triton_distributed_tpu.layers.mamba2 import Mamba2, draw_own
from triton_distributed_tpu.layers.moe_mlp import swiglu
from triton_distributed_tpu.layers.short_conv import fresh_rows
from triton_distributed_tpu.layers.tp_attn import TPAttn
from triton_distributed_tpu.models.config import GraniteHybridConfig
from triton_distributed_tpu.runtime.compat import axis_size as _axis_size
from triton_distributed_tpu.runtime.mesh import get_default_mesh

KINDS = ("mamba", "attention")


def shortest_period(layer_types) -> int:
    """Length of the shortest prefix of ``layer_types`` that, repeated, is
    the whole of it."""
    n = len(layer_types)
    return next(p for p in range(1, n + 1) if n % p == 0
                and layer_types == layer_types[:p] * (n // p))


@dataclasses.dataclass(frozen=True)
class GraniteHybrid:
    config: GraniteHybridConfig
    axis: str = "tp"

    #: Device-side counts a paged step returns as ``aux["stats"]`` (int32,
    #: this order); ``BatchEngine`` adds them to its counters of the same
    #: names: live positions whose state advanced, summed over the Mamba-2
    #: layers; slots whose state started from zero; rows appended to the
    #: attention layers' arenas.
    step_stats = ("ssm_rows_advanced", "ssm_states_reset", "kv_rows_appended")

    @functools.cached_property
    def pattern(self) -> tuple:
        """One period's layer kinds."""
        lt = tuple(self.config.layer_types)
        return lt[:shortest_period(lt)]

    @functools.cached_property
    def layer_counts(self) -> dict:
        """Layers by kind (``BatchEngine.stats_snapshot()["layers"]``)."""
        lt = self.config.layer_types
        return {k: lt.count(k) for k in KINDS if k in lt}

    @functools.cached_property
    def mamba(self) -> Mamba2:
        c = self.config
        return Mamba2(d_model=c.d_model, n_heads=c.mamba_n_heads,
                      d_head=c.mamba_d_head, d_state=c.mamba_d_state,
                      d_conv=c.mamba_d_conv, n_groups=c.mamba_n_groups,
                      rms_eps=c.rms_eps, dtype=c.dtype)

    @functools.cached_property
    def attn(self) -> TPAttn:
        c = self.config
        return TPAttn(d_model=c.d_model, n_heads=c.n_heads,
                      n_kv_heads=c.n_kv_heads, head_dim=c.head_dim,
                      axis=self.axis, dtype=c.dtype, qk_norm=False,
                      rms_eps=c.rms_eps, rope=False,
                      scale=c.attention_multiplier, kv_pack=c.kv_pack)

    # -- parameters ---------------------------------------------------------

    def param_shapes(self):
        """The parameter tree as ``(shape, fan_in)`` leaves; ``fan_in`` None
        marks what is not a matrix (``init`` draws those by name)."""
        c = self.config
        d, dh = c.d_model, c.head_dim
        n_periods = c.n_layers // len(self.pattern)
        common = {"input_norm": ((d,), None), "post_norm": ((d,), None),
                  "mlp": {"w_gate_up": ((d, 2 * c.d_ff), d),
                          "w_down": ((c.d_ff, d), c.d_ff)}}
        kinds = {
            "mamba": dict(common, mixer=self.mamba.param_shapes()),
            "attention": dict(common, attn={
                "w_qkv": ((d, (c.n_heads + 2 * c.n_kv_heads) * dh), d),
                "w_o": ((c.n_heads * dh, d), c.n_heads * dh)}),
        }
        periods = {
            kind: jax.tree.map(
                lambda leaf, n=self.pattern.count(kind):
                    ((n_periods, n, *leaf[0]), leaf[1]),
                tree, is_leaf=lambda x: isinstance(x, tuple))
            for kind, tree in kinds.items() if kind in self.pattern}
        return {"embed": ((c.vocab_size, d), d), "final_norm": ((d,), None),
                "periods": periods}

    def param_specs(self):
        return jax.tree.map(lambda leaf: P(), self.param_shapes(),
                            is_leaf=lambda x: isinstance(x, tuple))

    def init(self, key, mesh: Mesh | None = None):
        """Random replicated params (tests): matrices N(0, 1/fan_in) in the
        model dtype (the table at ``1 / embedding_multiplier`` of that, so
        that the stream starts at norm 1), norms 1, and the recurrence's
        own as Mamba-2
        initialises them: ``a_log = log U(1, 16)``, ``dt_bias`` the inverse
        softplus of a step drawn log-uniformly from [1e-3, 1e-1], ``d_skip``
        1, the convolution's bias 0."""
        mesh = mesh or get_default_mesh()
        c = self.config
        with_paths, treedef = jax.tree_util.tree_flatten_with_path(
            self.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 self.param_specs())

        def draw(k, name, shape, fan_in):
            if fan_in is not None:
                scale = fan_in ** -0.5
                if name == "embed":
                    scale /= c.embedding_multiplier
                return (jax.random.normal(k, shape, c.dtype)
                        * jnp.asarray(scale, c.dtype))
            return draw_own(name, k, shape)         # norms: 1

        @functools.partial(jax.jit, out_shardings=shardings)
        def make(key):
            ks = jax.random.split(key, len(with_paths))
            return jax.tree.unflatten(treedef, [
                draw(k, path[-1].key, *leaf)
                for k, (path, leaf) in zip(ks, with_paths)])

        return make(key)

    # -- the analytic cost of a step (obs/efficiency's ledger) --------------

    def _layer_weights(self) -> int:
        """Weights of the linear layers a token is multiplied by."""
        c, m = self.config, self.mamba
        mixers = (c.n_state_layers * (m.param_shapes()["w_in"][0][1]
                                      + m.d_inner) * c.d_model
                  + c.n_cache_layers * 2 * (c.n_heads + c.n_kv_heads)
                  * c.head_dim * c.d_model)
        return mixers + c.n_layers * 3 * c.d_model * c.d_ff \
            + c.d_model * c.vocab_size

    def _state_elems(self) -> int:
        c = self.config
        return c.n_state_layers * c.mamba_n_heads * c.mamba_d_head \
            * c.mamba_d_state

    def step_flops(self, rows) -> float:
        """rows: (new tokens, cache length) per live slot."""
        c = self.config
        tokens = sum(n for n, _ in rows)
        attn = 4.0 * c.n_cache_layers * c.n_heads * c.head_dim
        return (2.0 * self._layer_weights() * tokens
                + 5.0 * self._state_elems() * tokens
                + attn * sum(n * kv for n, kv in rows))

    def step_hbm_bytes(self, rows, *, itemsize: int, **_) -> float:
        """Every weight once, each live slot's state read and written once,
        each row's cache rows once an attention layer."""
        c = self.config
        kv = 2 * c.n_cache_layers * c.n_kv_heads * c.head_dim
        return (itemsize * (self._layer_weights()
                            + kv * sum(kv_len for _, kv_len in rows))
                + 2 * 4 * self._state_elems() * len(rows))

    # -- per-device forward (inside shard_map) ------------------------------

    def forward_paged(self, params, ids, state, offsets, block_tables,
                      slot_mask, seq_lens=None, *, mode: str = "dist",
                      interpret=None, paged_attn: str = "fused",
                      spec_verify: bool = False):
        """One served step on this device, as ``Qwen3.forward_paged``:
        ``(logits (B, vocab) f32, aux, state)``, ``ids`` an array (B, L) or
        the mixed step's triple ``(tok (B,), chunk (P, L), dealt (P, 3))``
        (``nn.paged_token_blocks``). The projections, the SwiGLU and the
        residual stream see the flat token batch; the mixers one block at
        a time, each slot's state advanced in the ONE block it is live in,
        over its live positions only and, where the host dealt the slot
        several rows of the prefill block, from row to row down the run
        (``layers.mamba2``: the rows are chained, the last writes).
        ``aux["stats"]`` the int32 counts ``step_stats``. ``mode`` is
        accepted and not read: on one device ``dist``, ``xla`` and ``ar``
        are one path."""
        c = self.config
        if _axis_size(self.axis) != 1:
            raise NotImplementedError(
                f"{c.model_name}: mesh axis {self.axis!r} has "
                f"{_axis_size(self.axis)} devices. Missing for more than "
                f"one: a per-slot state under tensor parallelism (the "
                f"Mamba-2 heads, their projections and the pool's per-slot "
                f"arenas are not sharded). The model is served whole on "
                f"one device.")
        if state.ssm is None or state.conv is None:
            raise ValueError(
                "the pool's state has no per-slot arenas: build the pool "
                "from this model's configuration (KVPool(config, ...,"
                " n_slots=...))")
        if state.kv_scale is not None:
            raise NotImplementedError(
                "the hybrid block has no quantized build of its pool")
        if spec_verify:
            raise NotImplementedError(
                "speculative verify is not built for a model with per-slot "
                "state: a rejected draft would have to roll the state back")
        flat, blocks, last = nn.paged_token_blocks(
            ids, offsets, block_tables, slot_mask, seq_lens)
        # The residual stream is carried in float32 and read by the
        # sub-layers in the model dtype (as ``models.deepseek_v3``).
        h = jnp.take(params["embed"], flat, axis=0).astype(jnp.float32) \
            * c.embedding_multiplier
        r = c.residual_multiplier
        per = {k: self.pattern.count(k) for k in KINDS}

        def period(carry, i):
            # The weights stay out of the scan's ``xs``: a period's slice of
            # a stack (9 layers of it) would be COPIED out before a layer
            # of it is read (1.4 GB of temporaries in the compiled step);
            # each layer reads ``[i * per + j]`` of the stack where it lies.
            h, state = carry
            seen = dict.fromkeys(KINDS, 0)
            for kind in self.pattern:
                j = seen[kind]
                seen[kind] += 1
                layer = i * per[kind] + j
                lp = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a.reshape(-1, *a.shape[2:]), layer, 0, False),
                    params["periods"][kind])
                hn = nn.rms_norm(h, lp["input_norm"], c.rms_eps)
                hn = hn.astype(c.dtype)
                if kind == "mamba":
                    mix, state = self.mamba.fwd(
                        lp["mixer"], hn, state, blocks=blocks, layer=layer,
                        interpret=interpret)
                else:
                    mix, state = self.attn.local_fwd(
                        lp["attn"], hn, state, blocks=blocks,
                        paged_attn=paged_attn, layer=layer,
                        interpret=interpret)
                h = h + r * mix
                hn = nn.rms_norm(h, lp["post_norm"], c.rms_eps)
                h = h + r * swiglu(hn.astype(c.dtype),
                                   lp["mlp"]["w_gate_up"],
                                   lp["mlp"]["w_down"])
            return (h, state), None

        n_periods = c.n_layers // len(self.pattern)
        (h, state), _ = jax.lax.scan(
            period, (h, state), jnp.arange(n_periods, dtype=jnp.int32))

        h = nn.rms_norm(h, params["final_norm"], c.rms_eps).astype(c.dtype)
        logits = jnp.dot(jnp.take(h, last, axis=0), params["embed"].T,
                         preferred_element_type=jnp.float32) \
            / c.logits_scaling
        live = sum(jnp.sum(b.valid()) for b in blocks)
        stats = jnp.stack([live * c.n_state_layers, fresh_rows(blocks),
                           live * c.n_cache_layers]).astype(jnp.int32)
        return logits, {"stats": stats}, state
