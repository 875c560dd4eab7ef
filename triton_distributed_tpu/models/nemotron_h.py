"""Nemotron-H decoder stack: every layer is ONE mixer under one norm, and
the mixer is one of three kinds, in an order that a pattern string gives and
that need not repeat: a Mamba-2 layer that keeps a fixed-size state a SLOT,
routed experts (ungated relu², a shared expert beside them) that keep
nothing, or grouped-query attention without position embedding that keeps
rows in the paged pool. The fourth model class behind ``Engine``
(``models.engine`` picks it when it is given a ``NemotronHConfig``), with
the contract ``BatchEngine`` and ``Engine._make_sm`` use: ``axis``,
``param_specs``, ``init``, ``step_stats`` and ``forward_paged`` (the pool's
state in and out whole).

The block (HF ``nemotron_h``)::

    h_0 = E[ids];   h <- h + Mixer_i(RMSNorm_i(h));   logits = RMSNorm(h) W_head

``Mixer_i`` is ``layers.mamba2.Mamba2`` (``M``), ``layers.moe_mlp
.HeldExpertsMoE`` with ``activation="relu2"`` (``E``: this device one chip's share
of an expert-parallel deployment, ``NemotronHConfig.experts_held``) or
``layers.tp_attn.TPAttn`` with ``rope=False`` (``*``).

The layer walk is read from the pattern. Weights are stacked BY KIND and a
layer reads index ``(layers of its kind before it)`` of its kind's stack
where it lies; the pool's state rides the walk as carry, its row arenas as
deep as the model has attention layers and its per-slot arenas as deep as it
has Mamba-2 layers. ``pattern_segments`` cuts the pattern into runs ``(unit,
count)`` of a repeated unit so that the units together are as short as can
be (``MEMEM*E`` x 5, ``ME`` x 3, ``M*E``, ``ME`` x 4 for the published 52
layers: 14 layer bodies traced and compiled, not 52); a run of more than one
is a ``lax.scan`` whose body is the unit written out, a run of one is
written out where it stands.

What is not built, and refused by name: more than one device (neither the
Mamba-2 heads, nor the per-slot arenas, nor two key heads are sharded, and
the experts' exchange over ICI does not run under ``BatchEngine``),
speculative verify (a rejected draft would have to roll the state back) and
a quantized pool. Not there to call: the contiguous ``Engine.serve`` cache.

Parameters (all replicated)::

    embed (V, d), final_norm (d,), lm_head (d, V)
    layers: every leaf stacked over the layers of its kind
        mamba     {norm, mixer {Mamba2.param_shapes}}
        moe       {norm, moe {router (d, E) f32, bias (E,) f32,
                   w_up (held, d, ff), w_down (held, ff, d),
                   shared {w_up (d, ffs), w_down (ffs, d)}}}
        attention {norm, attn {w_qkv, w_o}}

with ``ff`` the experts' width as stored (``moe_d_ff_stored``: zero-padded
to a lane multiple).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_distributed_tpu.layers import nn
from triton_distributed_tpu.layers.mamba2 import Mamba2, draw_own
from triton_distributed_tpu.layers.moe_mlp import MOE_STATS, HeldExpertsMoE
from triton_distributed_tpu.layers.short_conv import fresh_rows
from triton_distributed_tpu.layers.tp_attn import TPAttn
from triton_distributed_tpu.models.config import NemotronHConfig
from triton_distributed_tpu.models.granite_hybrid import shortest_period
from triton_distributed_tpu.runtime.compat import axis_size as _axis_size
from triton_distributed_tpu.runtime.mesh import get_default_mesh

KINDS = ("mamba", "moe", "attention")


def pattern_segments(kinds) -> tuple:
    """``kinds`` (one a layer) as runs ``(unit, count)``: the sequence is
    each unit repeated ``count`` times, one run after another, cut so that
    the units' lengths add up to the least (what a walk that scans each run
    has to trace), and among such cuts into the fewest runs."""
    kinds = tuple(kinds)
    n = len(kinds)
    best = [(0, 0, ())] + [None] * n        # (traced layers, runs, segments)
    for end in range(1, n + 1):
        for start in range(end):
            part = kinds[start:end]
            p = shortest_period(part)
            cost, runs, segs = best[start]
            cand = (cost + p, runs + 1, segs + ((part[:p], len(part) // p),))
            if best[end] is None or cand[:2] < best[end][:2]:
                best[end] = cand
    return best[n][2]


@dataclasses.dataclass(frozen=True)
class NemotronH:
    config: NemotronHConfig
    axis: str = "tp"

    #: Device-side counts a paged step returns as ``aux["stats"]`` (int32,
    #: this order); ``BatchEngine`` adds them to its counters of the same
    #: names: the expert layers' four (``layers.moe_mlp.MOE_STATS``, summed
    #: over the expert layers), then live positions whose state advanced,
    #: summed over the Mamba-2 layers; slots whose state started from zero;
    #: rows appended to the attention layers' arenas.
    step_stats = MOE_STATS + ("ssm_rows_advanced", "ssm_states_reset",
                              "kv_rows_appended")

    @functools.cached_property
    def layer_counts(self) -> dict:
        """Layers by kind (``BatchEngine.stats_snapshot()["layers"]``)."""
        kinds = self.config.layer_kinds
        return {k: kinds.count(k) for k in KINDS if k in kinds}

    @functools.cached_property
    def segments(self) -> tuple:
        return pattern_segments(self.config.layer_kinds)

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
                      rms_eps=c.rms_eps, rope=False)

    @functools.cached_property
    def moe(self) -> HeldExpertsMoE:
        c = self.config
        return HeldExpertsMoE(
            d_model=c.d_model, d_ff=c.moe_d_ff_stored, n_experts=c.n_experts,
            topk=c.n_experts_per_tok, n_held=c.n_held, lo=c.experts_lo,
            routed_scaling=c.routed_scaling_factor,
            norm_topk_prob=c.norm_topk_prob, dtype=c.dtype,
            activation="relu2")

    # -- parameters ---------------------------------------------------------

    def param_shapes(self):
        """The parameter tree as ``(shape, fan_in)`` leaves; ``fan_in`` None
        marks what is not a matrix (``init`` draws those by name)."""
        c = self.config
        d, dh, ff = c.d_model, c.head_dim, c.moe_d_ff_stored
        kinds = {
            "mamba": {"mixer": self.mamba.param_shapes()},
            "moe": {"moe": {
                "router": ((d, c.n_experts), d),
                "bias": ((c.n_experts,), None),
                "w_up": ((c.n_held, d, ff), d),
                "w_down": ((c.n_held, ff, d), c.moe_d_ff),
                "shared": {"w_up": ((d, c.shared_d_ff), d),
                           "w_down": ((c.shared_d_ff, d), c.shared_d_ff)}}},
            "attention": {"attn": {
                "w_qkv": ((d, (c.n_heads + 2 * c.n_kv_heads) * dh), d),
                "w_o": ((c.n_heads * dh, d), c.n_heads * dh)}},
        }
        layers = {
            kind: jax.tree.map(
                lambda leaf, n=n: ((n, *leaf[0]), leaf[1]),
                dict(kinds[kind], norm=((d,), None)),
                is_leaf=lambda x: isinstance(x, tuple))
            for kind, n in self.layer_counts.items()}
        return {"embed": ((c.vocab_size, d), d), "final_norm": ((d,), None),
                "lm_head": ((d, c.vocab_size), d), "layers": layers}

    def param_specs(self):
        return jax.tree.map(lambda leaf: P(), self.param_shapes(),
                            is_leaf=lambda x: isinstance(x, tuple))

    def init(self, key, mesh: Mesh | None = None):
        """Random replicated params (tests): matrices N(0, 1/fan_in) in the
        model dtype (the router float32; the experts' padding columns and
        rows zero), norms 1, the selection bias N(0, 0.01^2), and the
        recurrence's own as ``layers.mamba2.draw_own`` draws them."""
        mesh = mesh or get_default_mesh()
        c = self.config
        with_paths, treedef = jax.tree_util.tree_flatten_with_path(
            self.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 self.param_specs())
        kept = jnp.arange(c.moe_d_ff_stored) < c.moe_d_ff

        def draw(k, path, shape, fan_in):
            name = path[-1].key
            if fan_in is not None:
                dt = jnp.float32 if name == "router" else c.dtype
                w = jax.random.normal(k, shape, dt) \
                    * jnp.asarray(fan_in ** -0.5, dt)
                if len(shape) == 4:         # a routed expert's matrices
                    w = jnp.where(kept if name == "w_up" else kept[:, None],
                                  w, 0)
                return w
            if name == "bias":
                return 0.01 * jax.random.normal(k, shape, jnp.float32)
            return draw_own(name, k, shape)         # norms: 1

        @functools.partial(jax.jit, out_shardings=shardings)
        def make(key):
            ks = jax.random.split(key, len(with_paths))
            return jax.tree.unflatten(treedef, [
                draw(k, path, *leaf)
                for k, (path, leaf) in zip(ks, with_paths)])

        return make(key)

    # -- the analytic cost of a step (obs/efficiency's ledger) --------------

    def _weights(self) -> tuple[int, int]:
        """(weights a token is multiplied by, weights held), linear layers
        and head, the experts at their published width; a token meets
        ``topk * held / n_experts`` held experts on average."""
        c, m, n = self.config, self.mamba, self.layer_counts
        d = c.d_model
        mixer = (m.param_shapes()["w_in"][0][1] + m.d_inner) * d
        attn = 2 * (c.n_heads + c.n_kv_heads) * c.head_dim * d
        expert = 2 * d * c.moe_d_ff
        fixed = (n.get("mamba", 0) * mixer + n.get("attention", 0) * attn
                 + n.get("moe", 0) * (2 * d * c.shared_d_ff
                                      + d * c.n_experts)
                 + d * c.vocab_size)
        met = c.n_experts_per_tok * c.n_held / c.n_experts
        return (fixed + n.get("moe", 0) * met * expert,
                fixed + n.get("moe", 0) * c.n_held * expert)

    def _state_elems(self) -> int:
        c = self.config
        return c.n_state_layers * c.mamba_n_heads * c.mamba_d_head \
            * c.mamba_d_state

    def step_flops(self, rows) -> float:
        """rows: (new tokens, cache length) per live slot."""
        c = self.config
        tokens = sum(n for n, _ in rows)
        attn = 4.0 * c.n_cache_layers * c.n_heads * c.head_dim
        return (2.0 * self._weights()[0] * tokens
                + 5.0 * self._state_elems() * tokens
                + attn * sum(n * kv for n, kv in rows))

    def step_hbm_bytes(self, rows, *, itemsize: int, **_) -> float:
        """Every weight held once, each live slot's state read and written
        once, each row's cache rows once an attention layer."""
        c = self.config
        kv = 2 * c.n_cache_layers * c.n_kv_heads * c.head_dim
        return (itemsize * (self._weights()[1]
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
        (``nn.paged_token_blocks``). The projections, the experts and the
        residual stream see the flat token batch; Mamba-2 and attention one
        block at a time. ``aux["stats"]`` the int32 counts ``step_stats``.
        ``mode`` is accepted and not read: on one device ``dist``, ``xla``
        and ``ar`` are one path."""
        c = self.config
        if _axis_size(self.axis) != 1:
            raise NotImplementedError(
                f"{c.model_name}: mesh axis {self.axis!r} has "
                f"{_axis_size(self.axis)} devices. Missing for more than "
                f"one: a per-slot state under tensor parallelism (the "
                f"Mamba-2 heads, their projections and the pool's per-slot "
                f"arenas are not sharded), attention's {c.n_kv_heads} key "
                f"heads over more devices than there are heads, and the "
                f"routed experts' exchange over ICI "
                f"(layers/ep_a2a_layer.py does not run under BatchEngine). "
                f"One device is one chip's share of the deployment "
                f"(NemotronHConfig.experts_held); no code stands in for "
                f"the other chips.")
        if state.ssm is None or state.conv is None:
            raise ValueError(
                "the pool's state has no per-slot arenas: build the pool "
                "from this model's configuration (KVPool(config, ...,"
                " n_slots=...))")
        if state.kv_scale is not None:
            raise NotImplementedError(
                "the Nemotron-H block has no quantized build of its pool")
        if spec_verify:
            raise NotImplementedError(
                "speculative verify is not built for a model with per-slot "
                "state: a rejected draft would have to roll the state back")
        flat, blocks, last = nn.paged_token_blocks(
            ids, offsets, block_tables, slot_mask, seq_lens)
        # The residual stream is carried in float32 (the mixers read it in
        # the model dtype, the router as it is): in bfloat16 its rounding at
        # every add moves near-tied router scores across the top-k boundary
        # (``models.deepseek_v3``).
        h = jnp.take(params["embed"], flat, axis=0).astype(jnp.float32)
        valid = jnp.concatenate([b.valid() for b in blocks])

        # The routed experts' stacks stay whole: the grouped product indexes
        # ``[layer, expert]`` of them itself. Every other leaf is read at
        # ``[layer of its kind]`` of its stack where it lies (a slice of a
        # stack handed to a scan as ``xs`` is copied out first).
        light = jax.tree.map(lambda a: a, params["layers"])  # new dicts
        heavy = {k: light["moe"]["moe"].pop(k)
                 for k in ("w_up", "w_down") if "moe" in light}

        def layer(kind, idx, h, state, stats):
            """Layer ``idx`` of its kind: () int32, traced or not."""
            idx = jnp.asarray(idx, jnp.int32)
            lp = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0, False),
                light[kind])
            hn = nn.rms_norm(h, lp["norm"], c.rms_eps)
            if kind == "mamba":
                mix, state = self.mamba.fwd(
                    lp["mixer"], hn.astype(c.dtype), state, blocks=blocks,
                    layer=idx, interpret=interpret)
            elif kind == "attention":
                mix, state = self.attn.local_fwd(
                    lp["attn"], hn.astype(c.dtype), state, blocks=blocks,
                    paged_attn=paged_attn, layer=idx, interpret=interpret)
            else:
                mix, st = self.moe.fwd(dict(lp["moe"], **heavy), hn, valid,
                                       layer_idx=idx, interpret=interpret)
                stats = stats + st
            return h + mix, state, stats

        def unit_walk(unit, first, carry):
            """One unit of a run, its layers written out; ``first[kind]``
            the index of the unit's first layer of the kind."""
            seen = dict.fromkeys(KINDS, 0)
            for kind in unit:
                carry = layer(kind, first[kind] + seen[kind], *carry)
                seen[kind] += 1
            return carry

        carry = (h, state, jnp.zeros((len(MOE_STATS),), jnp.int32))
        done = dict.fromkeys(KINDS, 0)      # layers of each kind walked
        for unit, count in self.segments:
            per = {k: unit.count(k) for k in KINDS}
            if count == 1:
                carry = unit_walk(unit, done, carry)
            else:
                carry, _ = jax.lax.scan(
                    lambda carry, i, unit=unit, per=per, base=dict(done): (
                        unit_walk(unit, {k: base[k] + i * per[k]
                                         for k in KINDS}, carry), None),
                    carry, jnp.arange(count, dtype=jnp.int32))
            done = {k: done[k] + count * per[k] for k in KINDS}
        h, state, moe_stats = carry

        h = nn.rms_norm(h, params["final_norm"], c.rms_eps).astype(c.dtype)
        logits = jnp.dot(jnp.take(h, last, axis=0), params["lm_head"],
                         preferred_element_type=jnp.float32)
        live = jnp.sum(valid)
        stats = jnp.concatenate([moe_stats, jnp.stack(
            [live * c.n_state_layers, fresh_rows(blocks),
             live * c.n_cache_layers]).astype(jnp.int32)])
        return logits, {"stats": stats}, state
