"""Tensor-parallel MLP layer.

TPU-native analog of the reference's ``layers/nvidia/tp_mlp.py`` (``TP_MLP``
:51): gate/up projections column-sharded, down projection row-sharded, with
three forward modes mirroring the reference's
``torch_fwd`` / ``dist_triton_fwd`` (:143) / ``dist_triton_AR_fwd`` (:177):

  ``xla_fwd``    — golden path: plain jnp matmuls + psum (XLA inserts its own
                   collectives); correctness reference and perf baseline.
  ``dist_fwd``   — AG-GEMM(x, w_gate_up) -> GLU activation -> GEMM-RS(h,
                   w_down): comm overlapped into both matmuls; input and
                   output are M-sharded (sequence-parallel boundary layout).
  ``ar_fwd``     — local GEMMs -> one-shot allreduce: the small-M latency
                   mode (reference e2e_dense.md:33 "GEMM+fused AllReduce").

Functional JAX style: the layer object holds static config; parameters are an
explicit pytree; all ``*_fwd`` methods are per-device functions composable
inside ``shard_map`` (models stack them under one jit). Host-level ``fwd``
wraps shard_map for standalone use.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
from triton_distributed_tpu.runtime.compat import axis_size as _axis_size
from triton_distributed_tpu.runtime.compat import shard_map
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
from triton_distributed_tpu.runtime.mesh import get_default_mesh


@dataclasses.dataclass(frozen=True)
class TPMLP:
    """Gated MLP (SwiGLU family) with TP-sharded weights.

    Weight sharding (reference ``shard_local``, tp_mlp.py:37):
      w_gate_up: (d_model, 2 * d_ff) sharded on the output (ffn) dim —
                 per-device (d_model, 2 * ff_local), gate/up interleaved as
                 [gate | up] within the local shard.
      w_down:    (d_ff, d_model) sharded on the input (ffn) dim —
                 per-device (ff_local, d_model).
    """

    d_model: int
    d_ff: int
    axis: str = "tp"
    dtype: jnp.dtype = jnp.bfloat16
    block_n: int = 256

    def interleave_gate_up(self, w_gate, w_up, world: int):
        """Pack separate (d, d_ff) gate/up matrices into the fused
        (d, 2*d_ff) layout whose P(None, axis) shard on each device is
        [gate_local | up_local] — the layout ``_glu`` splits. (The reference
        fuses gate/up the same way so one AG-GEMM serves both,
        tp_mlp.py:37 ``shard_local``.)"""
        ff_local = self.d_ff // world
        g = w_gate.reshape(self.d_model, world, ff_local)
        u = w_up.reshape(self.d_model, world, ff_local)
        return jnp.concatenate([g, u], axis=2).reshape(self.d_model, 2 * self.d_ff)

    def deinterleave_gate_up(self, w_gate_up, world: int):
        """Inverse of ``interleave_gate_up`` -> (w_gate, w_up)."""
        ff_local = self.d_ff // world
        w = w_gate_up.reshape(self.d_model, world, 2, ff_local)
        return (w[:, :, 0].reshape(self.d_model, self.d_ff),
                w[:, :, 1].reshape(self.d_model, self.d_ff))

    def param_specs(self):
        """Per-layer sharding specs (the shared FFN-block contract with
        MoEMLP — models stack these with a leading layer dim)."""
        return {"w_gate_up": P(None, self.axis),
                "w_down": P(self.axis, None)}

    def init(self, key, mesh: Mesh | None = None):
        """Sharded random params (models load real weights instead)."""
        mesh = mesh or get_default_mesh()
        world = mesh.shape[self.axis]
        k1, k2, k3 = jax.random.split(key, 3)
        scale = self.d_model ** -0.5
        w_gate = (jax.random.normal(k1, (self.d_model, self.d_ff)) * scale
                  ).astype(self.dtype)
        w_up = (jax.random.normal(k2, (self.d_model, self.d_ff)) * scale
                ).astype(self.dtype)
        w_down = (jax.random.normal(k3, (self.d_ff, self.d_model)) * scale
                  ).astype(self.dtype)
        return {
            "w_gate_up": jax.device_put(
                self.interleave_gate_up(w_gate, w_up, world),
                NamedSharding(mesh, P(None, self.axis))),
            "w_down": jax.device_put(
                w_down, NamedSharding(mesh, P(self.axis, None))),
        }

    # -- per-device forwards (inside shard_map) -----------------------------

    def _glu(self, h):
        ff_local = h.shape[-1] // 2
        gate, up = h[:, :ff_local], h[:, ff_local:]
        return (jax.nn.silu(gate.astype(jnp.float32)) *
                up.astype(jnp.float32)).astype(h.dtype)

    def dist_fwd(self, params, x_local, *, layer_idx=None, interpret=None):
        """x_local: (m, d_model) M-shard -> (m, d_model) M-shard.
        AG-GEMM -> GLU -> GEMM-RS (reference dist_triton_fwd, tp_mlp.py:143).
        ``layer_idx`` () int32: both weights are the model's layer STACKS
        (L, ...), handed to the kernels whole, which index the layer
        themselves (``ag_gemm_device``)."""
        h = ag_gemm_device(
            x_local, params["w_gate_up"], axis=self.axis,
            config=AGGEMMConfig(block_n=self.block_n), interpret=interpret,
            layer=layer_idx)
        h = self._glu(h)
        return gemm_rs_device(
            h, params["w_down"], axis=self.axis,
            config=GEMMRSConfig(block_n=min(self.block_n, self.d_model)),
            interpret=interpret, layer=layer_idx)

    def ar_fwd(self, params, x_full, *, interpret=None):
        """x_full: (M, d_model) replicated -> (M, d_model) replicated.
        Local GEMMs -> one-shot allreduce (reference dist_triton_AR_fwd)."""
        h = self._glu(x_full @ params["w_gate_up"])
        partial = h @ params["w_down"]
        return oneshot_all_reduce(partial, axis=self.axis, interpret=interpret)

    def xla_fwd(self, params, x_local):
        """Golden/baseline path: same math via jnp + psum."""
        x_full = jax.lax.all_gather(x_local, self.axis, axis=0, tiled=True)
        h = self._glu(x_full @ params["w_gate_up"])
        partial = h @ params["w_down"]
        return jax.lax.psum_scatter(partial, self.axis, scatter_dimension=0,
                                    tiled=True)

    # -- host-level ---------------------------------------------------------

    def fwd(self, params, x, *, mesh: Mesh | None = None,
            mode: Literal["dist", "xla", "ar"] = "dist", interpret=None):
        """x: global (M, d_model) sharded on M. Returns same layout."""
        mesh = mesh or get_default_mesh()
        return _build_fwd(self, mesh, mode, interpret)(params, x)


@functools.lru_cache(maxsize=None)
def _build_fwd(layer: TPMLP, mesh: Mesh, mode: str, interpret):
    axis = layer.axis

    def f(params, xl):
        if mode == "dist":
            return layer.dist_fwd(params, xl, interpret=interpret)
        if mode == "xla":
            return layer.xla_fwd(params, xl)
        if mode == "ar":
            # Replicated-activation mode: gather x, allreduce the output,
            # hand back this device's M-shard so the layout matches.
            x_full = jax.lax.all_gather(xl, axis, axis=0, tiled=True)
            out = layer.ar_fwd(params, x_full, interpret=interpret)
            world = _axis_size(axis)
            m = out.shape[0] // world
            me = jax.lax.axis_index(axis)
            return jax.lax.dynamic_slice_in_dim(out, me * m, m, axis=0)
        raise ValueError(f"unknown mode {mode!r}")

    param_specs = {"w_gate_up": P(None, axis), "w_down": P(axis, None)}
    return jax.jit(
        shard_map(
            f, mesh=mesh,
            in_specs=(param_specs, P(axis, None)),
            out_specs=P(axis, None),
            check_vma=False,
        )
    )
