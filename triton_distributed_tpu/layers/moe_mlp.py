"""Mixture-of-Experts MLP layer (Qwen3-MoE / DeepSeek-style sparse FFN).

The model-facing MoE block the reference exercises end-to-end in
``test/nvidia/test_ep_moe_inference.py`` (routing -> ``fast_all_to_all``
dispatch -> grouped expert GEMMs -> combine) built from this repo's EP
pieces: ``layers/ep_a2a_layer.EPAll2AllLayer`` (single-kernel a2a exchange)
and ``kernels/moe_utils`` (capacity routing, grouped GEMM, topk combine).

Router math follows HF ``Qwen3MoeSparseMoeBlock``: softmax over ALL expert
logits in fp32, top-k, optional re-normalization of the selected
probabilities (``norm_topk_prob``), weighted sum of gated-SwiGLU expert
outputs.

Sharding (inference EP-on-the-TP-axis, the reference's EP group):
  router   (d, E)          replicated
  w_gate_up (E, d, 2*ff_e) sharded on E over ``axis`` -> (E_local, d, 2ff)
  w_down    (E, ff_e, d)   sharded on E over ``axis``
  tokens   batch(M)-sharded like TPMLP.dist_fwd; the a2a moves each
  (token, k) pair to its expert's owner and back.

Static capacities (XLA-friendly): dispatch/expert grids are fixed-size;
(token, k) pairs beyond capacity are DROPPED with the loss surfaced in the
returned stats (the reference instead grows symmetric buffers — SURVEY
§2.4 ep_a2a_layer.py:116-130). Defaults size capacities at
``capacity_factor`` x the uniform-routing expectation; pass explicit
capacities for drop-free runs (tests do).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
from triton_distributed_tpu.runtime.compat import axis_size as _axis_size
from triton_distributed_tpu.runtime.compat import shard_map
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_distributed_tpu.kernels import moe_utils
from triton_distributed_tpu.layers.ep_a2a_layer import EPAll2AllLayer
from triton_distributed_tpu.runtime.mesh import get_default_mesh


def _round8(x: int) -> int:
    return max(8, (int(x) + 7) // 8 * 8)


def _round16(x: int) -> int:
    """Expert-grid row granularity: 16-row minimum so the grouped GEMM's
    bf16 operands never drop below Mosaic's packed-tile sublane count (an
    8-row decode grid measured 2x slower through relayouts)."""
    return max(16, (int(x) + 15) // 16 * 16)


@dataclasses.dataclass(frozen=True)
class MoEMLP:
    """Sparse gated-SwiGLU FFN with top-k routing."""

    d_model: int
    d_ff: int                  # PER-EXPERT intermediate size
    n_experts: int
    topk: int
    norm_topk_prob: bool = True
    axis: str = "tp"
    dtype: jnp.dtype = jnp.bfloat16
    capacity_factor: float = 2.0
    # Explicit capacity overrides (tokens per (src, dst) rank pair / per
    # local expert); None = capacity_factor x uniform expectation.
    capacity: int | None = None
    expert_capacity: int | None = None

    # -- parameters ---------------------------------------------------------

    def init(self, key, mesh: Mesh | None = None):
        mesh = mesh or get_default_mesh()
        kr, kg, ku, kd = jax.random.split(key, 4)
        d, ff, E = self.d_model, self.d_ff, self.n_experts
        scale = d ** -0.5
        params = {
            "router": (jax.random.normal(kr, (d, E)) * scale
                       ).astype(jnp.float32),
            "w_gate_up": jnp.concatenate(
                [(jax.random.normal(kg, (E, d, ff)) * scale).astype(self.dtype),
                 (jax.random.normal(ku, (E, d, ff)) * scale).astype(self.dtype)],
                axis=-1),
            "w_down": (jax.random.normal(kd, (E, ff, d))
                       * ff ** -0.5).astype(self.dtype),
        }
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, self.param_specs())

    def param_specs(self):
        return {"router": P(),
                "w_gate_up": P(self.axis, None, None),
                "w_down": P(self.axis, None, None)}

    @staticmethod
    def stack_experts(gates, ups, downs):
        """Pack per-expert (d, ff)/(ff, d) matrices (HF checkpoint layout)
        into the stacked (E, d, 2ff)/(E, ff, d) leaves."""
        return (jnp.concatenate([jnp.stack(gates), jnp.stack(ups)], axis=-1),
                jnp.stack(downs))

    # -- routing ------------------------------------------------------------

    def route(self, router, x):
        """HF Qwen3MoeSparseMoeBlock routing: fp32 softmax over all expert
        logits -> top-k -> optional renormalization of the selected
        probabilities. x: (n, d) -> (topk_weights (n, k) f32, ids (n, k))."""
        logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        w, ids = jax.lax.top_k(probs, self.topk)
        if self.norm_topk_prob:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return w, ids.astype(jnp.int32)

    def _expert_ffn(self, grouped, w_gate_up, w_down, counts=None,
                    layer_idx=None, interpret=None):
        """Gated SwiGLU over a (E_local, cap, d) capacity grid (empty slots
        are zero and stay zero through the gate). With ``counts`` (the
        dispatch's per-expert arrival counts) the GEMMs run the count-aware
        Pallas kernel that skips empty experts' weight fetches
        (``moe_utils.grouped_gemm_skip`` — decisive at decode batches where
        most experts are empty); without counts (the XLA golden path's
        worst-case grid) the plain batched einsum. ``layer_idx`` selects
        the layer of layer-STACKED ``(L, E, ...)`` weights inside the
        kernel's index maps — the scan-safe form (see dist_fwd)."""
        if counts is None:
            if layer_idx is not None:
                w_gate_up = w_gate_up[layer_idx]
                w_down = w_down[layer_idx]
            h = moe_utils.grouped_gemm(grouped, w_gate_up)
        else:
            h = moe_utils.grouped_gemm_skip(grouped, w_gate_up, counts,
                                            layer_idx=layer_idx,
                                            interpret=interpret)
        ff = h.shape[-1] // 2
        act = (jax.nn.silu(h[..., :ff].astype(jnp.float32))
               * h[..., ff:].astype(jnp.float32)).astype(h.dtype)
        if counts is None:
            return moe_utils.grouped_gemm(act, w_down)
        return moe_utils.grouped_gemm_skip(act, w_down, counts,
                                           layer_idx=layer_idx,
                                           interpret=interpret)

    def _ep_layer(self, n_local_tokens: int, world: int) -> EPAll2AllLayer:
        pairs = n_local_tokens * self.topk
        cap = self.capacity or min(
            _round8(pairs * self.capacity_factor / world), _round8(pairs))
        ecap = self.expert_capacity or min(
            _round16(world * pairs * self.capacity_factor / self.n_experts),
            _round16(world * cap))
        return EPAll2AllLayer(
            n_experts=self.n_experts, topk=self.topk, hidden=self.d_model,
            capacity=cap, expert_capacity=ecap, axis=self.axis)

    # -- per-device forwards (inside shard_map) -----------------------------

    def dist_fwd(self, params, x_local, *, return_stats: bool = False,
                 skip_gemm: bool = True, layer_idx=None, interpret=None):
        """x_local: (n_local, d) M-shard -> (n_local, d) M-shard. Routing is
        local (replicated router); the (token, k) pairs ride the
        single-kernel a2a to their experts' owners and back.

        Under a ``lax.scan`` over layers (the model body) pass the FULL
        layer-stacked ``w_gate_up``/``w_down`` ``(L, E, ...)`` plus
        ``layer_idx``: a scan-SLICED (E, ...) weight operand must
        MATERIALIZE to feed a Pallas custom call — a 1.2 GB copy per layer
        at 30b-a3b that XLA fuses away for an einsum (measured 2x slower
        e2e) — while the stacked form block-indexes the layer inside the
        kernel and keeps the empty-expert fetch skip. ``skip_gemm=False``
        forces the einsum expert GEMM (golden/debug).

        ``return_stats=True`` additionally returns the dispatch drop
        counters (``n_dropped_dispatch`` / ``n_dropped_expert`` int32
        scalars) — THE observable for capacity sizing: the default
        ``capacity_factor`` trades buffer memory for a chance of drops
        under skewed routing, and serving stacks should audit these
        counters at their traffic (then raise the factor or set explicit
        capacities). The plain return keeps the dense-FFN contract for the
        model body."""
        world = _axis_size(self.axis)
        w, ids = self.route(params["router"], x_local)
        ep = self._ep_layer(x_local.shape[0], world)
        grouped, expert_counts, state = ep.dispatch(x_local, ids, w,
                                                    interpret=interpret)
        out = self._expert_ffn(grouped, params["w_gate_up"],
                               params["w_down"],
                               counts=expert_counts if skip_gemm else None,
                               layer_idx=layer_idx, interpret=interpret)
        y = ep.combine(out, state, interpret=interpret).astype(x_local.dtype)
        if return_stats:
            return y, state["stats"]
        return y

    def xla_fwd(self, params, x_local):
        """Golden/baseline path: same math via jnp + XLA collectives —
        every device computes the FULL expert set over the gathered batch
        at worst-case capacity (zero drops), then keeps its M-shard."""
        world = _axis_size(self.axis)
        x_full = jax.lax.all_gather(x_local, self.axis, axis=0, tiled=True)
        n = x_full.shape[0]
        w, ids = self.route(params["router"], x_full)
        # Worst-case capacity: all n*topk pairs on one expert -> no drops.
        grid, slot, kept, _ = moe_utils.route_to_experts(
            x_full, ids, n_experts=self.n_experts,
            capacity=_round8(n * self.topk))
        w_gate_up = jax.lax.all_gather(params["w_gate_up"], self.axis,
                                       axis=0, tiled=True)
        w_down = jax.lax.all_gather(params["w_down"], self.axis, axis=0,
                                    tiled=True)
        out_grid = self._expert_ffn(grid, w_gate_up, w_down)
        out = moe_utils.combine_from_experts(out_grid, ids, w, slot, kept)
        me = jax.lax.axis_index(self.axis)
        m = n // world
        return jax.lax.dynamic_slice_in_dim(
            out, me * m, m, axis=0).astype(x_local.dtype)

    # -- host-level ---------------------------------------------------------

    def fwd(self, params, x, *, mesh: Mesh | None = None, mode: str = "dist",
            interpret=None):
        """x: global (M, d_model) sharded on M. Returns same layout."""
        mesh = mesh or get_default_mesh()
        return _build_fwd(self, mesh, mode, interpret)(params, x)


@functools.lru_cache(maxsize=None)
def _build_fwd(layer: MoEMLP, mesh: Mesh, mode: str, interpret):
    axis = layer.axis

    def f(params, xl):
        if mode == "dist":
            return layer.dist_fwd(params, xl, interpret=interpret)
        if mode == "xla":
            return layer.xla_fwd(params, xl)
        raise ValueError(f"unknown mode {mode!r}")

    return jax.jit(
        shard_map(
            f, mesh=mesh,
            in_specs=(layer.param_specs(), P(axis, None)),
            out_specs=P(axis, None),
            check_vma=False,
        )
    )


def swiglu(x, w_gate_up, w_down):
    """Dense gated SwiGLU, halves concatenated: ``w_gate_up`` (d, 2*ff)."""
    h = jnp.dot(x, w_gate_up, preferred_element_type=jnp.float32)
    ff = h.shape[-1] // 2
    act = (jax.nn.silu(h[..., :ff]) * h[..., ff:]).astype(x.dtype)
    return jnp.dot(act, w_down,
                   preferred_element_type=jnp.float32).astype(x.dtype)


#: What ``HeldExpertsMoE.fwd`` counts on the device, in this order.
MOE_STATS = ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
             "moe_dropped_pairs")


@dataclasses.dataclass(frozen=True)
class HeldExpertsMoE:
    """A routed expert layer (DeepSeek-V3's, Nemotron-H's, EXAONE-MoE's,
    SmallThinker's) as ONE chip of an expert-parallel deployment sees it:
    the router keeps its published width, this device
    holds the routed experts ``[lo, lo + n_held)`` and computes the part of
    the result they give; what the absent experts would add is left out
    (their chips would add it), and no code stands in for them or for the
    exchange. ``n_held == n_experts`` is the whole layer. A shared expert
    (every chip computes it alike) is added whole where the parameters hold
    one (``shared``).

    Two score forms, a property of the layer (``scoring``):

    - ``"sigmoid"`` (HF ``DeepseekV3TopkRouter``, ``noaux_tc`` with one
      group): ``s = sigmoid(x @ router)`` in float32 over ALL experts; the
      ``topk`` largest of ``s + bias`` are chosen; their weights are the
      UNBIASED scores, normalised over all chosen (``norm_topk_prob``),
      times ``routed_scaling``;
    - ``"softmax_topk"`` (SmallThinker): the ``topk`` largest raw logits
      ``x @ router`` are chosen and their weights are the softmax over the
      chosen alone (they sum to 1), times ``routed_scaling``; no bias.

    The router's input need not be the experts' input: ``routed(params, x,
    valid, route_from)`` routes from ``route_from`` (a model whose router
    stands before attention hands it the layer's input) and feeds the
    experts ``x``; None routes from ``x``.

    No pair is dropped at any routing: the held pairs are sorted by expert
    into a buffer that has room for every pair the tokens could route here
    (``moe_utils.rows_by_expert``), and the two grouped products walk its
    tiles, so the work follows the pairs routed.

    Three expert forms, a property of the layer (``activation``), the
    shared expert alike:

    - ``"swiglu"`` (DeepSeek-V3): ``w_down (silu(x w_gate) * (x w_up))``,
      gate and up halves one matrix ``w_gate_up`` (d, 2 * d_ff);
    - ``"reglu"`` (SmallThinker): ``w_down (relu(x w_gate) * (x w_up))``,
      the same two matrices;
    - ``"relu2"`` (Nemotron-H), ungated: ``w_down relu(x w_up)²``, TWO
      matrices an expert, ``w_up`` (d, d_ff). A zero column of ``w_up``
      against a zero row of ``w_down`` adds nothing, so a model may store
      ``d_ff`` padded to what the grouped product tiles by.

    Parameters: ``router`` (d, n_experts) f32, ``bias`` (n_experts,) f32
    (the sigmoid form only), ``w_gate_up`` (n_held, d, 2*d_ff) or ``w_up``
    (n_held, d, d_ff) / ``w_down`` (n_held, d_ff, d) — or layer-stacked
    with ``layer_idx``, as ``grouped_gemm_skip`` wants them under a scan —
    and, where the layer has a shared expert, ``shared`` {the same two
    names, (d, 2*ff_s) or (d, ff_s), ``w_down`` (ff_s, d)}.
    """

    d_model: int
    d_ff: int                  # per routed expert
    n_experts: int             # the router's width
    topk: int
    n_held: int
    lo: int = 0
    routed_scaling: float = 1.0
    norm_topk_prob: bool = True
    dtype: jnp.dtype = jnp.bfloat16
    activation: str = "swiglu"
    scoring: str = "sigmoid"

    def __post_init__(self):
        if self.activation not in ("swiglu", "reglu", "relu2") \
                or self.scoring not in ("sigmoid", "softmax_topk"):
            raise ValueError(
                f"unknown expert form {self.activation!r} or score form "
                f"{self.scoring!r}")

    @property
    def w_in(self) -> str:
        """The name of an expert's first matrix."""
        return "w_up" if self.activation == "relu2" else "w_gate_up"

    @property
    def forms(self) -> dict:
        """What the layer is made of, by name
        (``BatchEngine.stats_snapshot()["moe"]``)."""
        return {"scoring": self.scoring, "activation": self.activation}

    def act(self, h):
        """What stands between an expert's two products, in float32."""
        if self.activation == "relu2":
            return jnp.square(jax.nn.relu(h.astype(jnp.float32)))
        ff = h.shape[-1] // 2
        gate = jax.nn.silu if self.activation == "swiglu" else jax.nn.relu
        return (gate(h[..., :ff].astype(jnp.float32))
                * h[..., ff:].astype(jnp.float32))

    def scores(self, router, bias, x):
        """x (n, d) -> ``(what the choice is made by, what the weights are
        made from)``, each (n, n_experts) float32: the biased and the plain
        sigmoid scores, or the raw logits twice. Float32 in earnest:
        ``HIGHEST`` keeps the chip from taking the float32 product in one
        bfloat16 pass, which moves near-tied scores across the top-k
        boundary."""
        s = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
        if self.scoring == "softmax_topk":
            return s, s
        s = jax.nn.sigmoid(s)
        return s + bias.astype(jnp.float32), s

    def route(self, router, bias, x):
        """x (n, d) -> (weights (n, k) f32, ids (n, k) int32); ``bias`` is
        read by the sigmoid form alone."""
        by, s = self.scores(router, bias, x)
        _, ids = jax.lax.top_k(by, self.topk)
        w = jnp.take_along_axis(s, ids, axis=-1)
        if self.scoring == "softmax_topk":
            w = jax.nn.softmax(w, axis=-1)
        elif self.norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return w * self.routed_scaling, ids.astype(jnp.int32)

    def routed(self, params, x, valid=None, route_from=None, *,
               layer_idx=None, interpret=None):
        """The held experts' part of the result for x (n, d), and the
        counts ``MOE_STATS`` over the valid tokens (``valid`` (n,) bool:
        padding rows route nowhere and cost nothing). The router reads
        ``route_from`` (n, d), or ``x`` where there is none. Either may
        come in float32 (a float32 residual stream): the router reads its
        input as it is, the experts read ``x`` in the layer's dtype."""
        n = x.shape[0]
        w, ids = self.route(params["router"], params.get("bias"),
                            x if route_from is None else route_from)
        x = x.astype(self.dtype)
        live = jnp.ones((n, 1), bool) if valid is None else valid[:, None]
        local = ids - self.lo
        held = (local >= 0) & (local < self.n_held) & live
        tile = 16 if n * self.topk <= 1024 else 128
        row_of_pair, pair_of_row, tile_expert, n_tiles, counts = \
            moe_utils.rows_by_expert(local, held, n_experts=self.n_held,
                                     tile=tile)
        R = pair_of_row.shape[0]
        # An empty row's pair index is n * topk: out of bounds, filled 0.
        rows = x.at[pair_of_row // self.topk].get(
            mode="fill", fill_value=0).reshape(R // tile, tile, -1)
        tile_live = (jnp.arange(R // tile) < n_tiles).astype(jnp.int32)
        kw = dict(layer_idx=layer_idx, interpret=interpret,
                  group_of=tile_expert, name="moe_grouped_gemm")
        h = moe_utils.grouped_gemm_skip(rows, params[self.w_in], tile_live,
                                        **kw)
        out = moe_utils.grouped_gemm_skip(
            self.act(h).astype(h.dtype), params["w_down"], tile_live,
            **kw).reshape(R, -1)
        pair_out = out.at[row_of_pair].get(                     # (n, k, d)
            mode="fill", fill_value=0).astype(jnp.float32)
        y = jnp.sum(pair_out * jnp.where(held, w, 0.0)[..., None], axis=1)
        n_held = jnp.sum(held)
        stats = jnp.stack([
            jnp.sum(live) * self.topk, n_held, jnp.sum(counts > 0),
            n_held - jnp.sum(pair_of_row < n * self.topk)]).astype(jnp.int32)
        return y.astype(x.dtype), stats

    def fwd(self, params, x, valid=None, route_from=None, *, layer_idx=None,
            interpret=None):
        """x (n, d) -> (shared(x) + the held experts' part, stats); the
        held experts' part alone where ``params`` holds no shared expert."""
        y, stats = self.routed(params, x, valid, route_from,
                               layer_idx=layer_idx, interpret=interpret)
        if "shared" not in params:
            return y, stats
        sh, x = params["shared"], x.astype(self.dtype)
        h = jnp.dot(x, sh[self.w_in], preferred_element_type=jnp.float32)
        return y + jnp.dot(
            self.act(h).astype(x.dtype), sh["w_down"],
            preferred_element_type=jnp.float32).astype(x.dtype), stats
