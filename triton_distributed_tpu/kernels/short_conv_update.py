"""One-token update of a gated short convolution, in place on its windows.

The decode-shape step of ``layers.short_conv.ShortConv``. Every live
sequence keeps the last ``K - 1`` inputs of a causal depthwise convolution of
``K`` taps; one new token gives

    z = B * X                             rounded to the arena's dtype
    y = C * (w_0 win_0 + ... + w_{K-2} win_{K-2} + w_{K-1} z)
    win <- [win_1, ..., win_{K-2}, z]

with ``[B ; C ; X]`` the step's in-projection, a row a slot. The windows of
all layers and slots live in ONE arena ``(state layers, n_slots, (K - 1) *
d)``, oldest input first (``serving.kv_pool.PagedKVState.conv``), the paged
step's donated operand and the layer walk's carry; this kernel is handed the
whole arena with the layer's index, aliased in to out, and reads and writes
block ``[layer, row tile]`` of it where it lies: a slot's window moves once
each way a layer and a step, and no copy of the arena exists. A FRESH row
(its sequence starts with this token) reads a zero window whatever the arena
holds; a DEAD row's window is written back as it was read, and its ``y`` is
nothing anyone reads.

As plain ``jax.numpy`` the same is a slice of the arena, a concatenate, five
elementwise fusions and an update a layer; 30 layers a step, that is the
latency this kernel is for. A grid step is a tile of rows at every channel:
the taps of a channel need nothing of another, so the block is as wide as the
layer and the ``K - 1`` held inputs of a row are static lane slices of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.runtime.platform import (
    plain_off_tpu,
    resolve_interpret,
)

NAME = "short_conv_update"
# Rows a grid step: one sublane tile of a bfloat16 block. 32 slots are two
# steps, each 16 x (3 + 2 (K - 1) + 1) x d values.
ROW_TILE = 16


def _kernel(layer_ref, flags_ref, bcx_ref, w_ref, win_ref, o_win_ref, y_ref,
            *, d: int, taps: int):
    del layer_ref                       # read by the index maps
    f32 = jnp.float32
    shape = (bcx_ref.shape[0], d)
    live = jnp.broadcast_to(flags_ref[:, 0:1], shape) > 0
    fresh = jnp.broadcast_to(flags_ref[:, 1:2], shape) > 0
    b, c, x = (bcx_ref[:, i * d:(i + 1) * d].astype(f32) for i in range(3))
    z = (b * x).astype(win_ref.dtype).astype(f32)
    held = [win_ref[:, k * d:(k + 1) * d].astype(f32)
            for k in range(taps - 1)]
    seq = [jnp.where(fresh, 0.0, h) for h in held] + [z]
    w = w_ref[...].astype(f32)
    y_ref[...] = (c * sum(w[k:k + 1] * seq[k] for k in range(taps))
                  ).astype(y_ref.dtype)
    for k in range(taps - 1):
        o_win_ref[:, k * d:(k + 1) * d] = jnp.where(
            live, seq[k + 1], held[k]).astype(o_win_ref.dtype)


def short_conv_update(arena, layer, bcx, conv_w, live, fresh, *,
                      interpret=None):
    """``arena`` (state layers, n_slots, (K - 1) * d); ``layer`` () int32;
    ``bcx`` (n_slots, 3 * d) the step's ``[B ; C ; X]``; ``conv_w`` (K, d);
    ``live`` and ``fresh`` (n_slots,) bool. Returns ``(arena, y)``: the arena
    with ``[layer]`` advanced by one token a live slot (the same buffer under
    jit: the operand is aliased to the result) and ``y`` (n_slots, d) in
    ``bcx``'s dtype, the gated convolution before its out-projection.

    ``interpret=None`` where there is no TPU returns
    ``short_conv_update_reference`` (``platform.plain_off_tpu``: AUTO off
    the TPU takes the plain form); ``True`` is the interpreted kernel,
    ``False`` Mosaic's."""
    if plain_off_tpu(interpret):
        return short_conv_update_reference(arena, layer, bcx, conv_w, live,
                                           fresh)
    n_slots, d = bcx.shape[0], bcx.shape[1] // 3
    taps = conv_w.shape[0]
    rt = ROW_TILE if n_slots % ROW_TILE == 0 else n_slots

    def rows(width):
        return pl.BlockSpec((rt, width), lambda r, ly: (r, 0))

    window = pl.BlockSpec((None, rt, (taps - 1) * d),
                          lambda r, ly: (ly[0], r, 0))
    flags = jnp.stack([live, fresh], axis=1).astype(jnp.int32)
    itemsize = jnp.dtype(arena.dtype).itemsize
    return pl.pallas_call(
        functools.partial(_kernel, d=d, taps=taps),
        out_shape=(jax.ShapeDtypeStruct(arena.shape, arena.dtype),
                   jax.ShapeDtypeStruct((n_slots, d), bcx.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_slots // rt,),
            in_specs=[rows(2), rows(3 * d),
                      pl.BlockSpec((taps, d), lambda r, ly: (0, 0)), window],
            out_specs=[window, rows(d)]),
        # operand 4 (after the prefetched layer index) is the arena
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        cost_estimate=pl.CostEstimate(
            flops=2 * (taps + 1) * n_slots * d, transcendentals=0,
            bytes_accessed=(2 * (taps - 1) + 4) * n_slots * d * itemsize),
        interpret=resolve_interpret(interpret),
        name=NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), flags, bcx, conv_w, arena)


def short_conv_update_reference(arena, layer, bcx, conv_w, live, fresh):
    """The same in plain ``jax.numpy`` (tests, and every run off the TPU
    that does not ask for the kernel; no aliasing promised)."""
    f32 = jnp.float32
    n_slots, d = bcx.shape[0], bcx.shape[1] // 3
    taps = conv_w.shape[0]
    b, c, x = (bcx[:, i * d:(i + 1) * d].astype(f32) for i in range(3))
    z = (b * x).astype(arena.dtype).astype(f32)
    held = jax.lax.dynamic_index_in_dim(arena, layer, 0, False).astype(f32)
    seq = jnp.concatenate(
        [jnp.where(fresh[:, None], 0.0, held).reshape(n_slots, taps - 1, d),
         z[:, None]], axis=1)
    w = conv_w.astype(f32)
    y = c * sum(w[k] * seq[:, k] for k in range(taps))
    window = jnp.where(live[:, None], seq[:, 1:].reshape(n_slots, -1), held)
    return (jax.lax.dynamic_update_index_in_dim(
        arena, window.astype(arena.dtype), layer, 0), y.astype(bcx.dtype))
