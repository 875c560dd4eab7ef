"""One-token update of a selective state-space recurrence, in place.

The decode-shape step of a Mamba-2 layer. Every live sequence keeps, for
each of the layer's ``H`` heads, a float32 state ``S`` of ``(P, N)``
(head width by state width); one new token moves it

    S' = a * S + u (x) B          a = exp(dt * A)  a scalar a head,
    y  = S' . C                   u = dt * x       (P,) a head,

with ``B`` and ``C`` (N,) shared by the heads of a group. A grid step's
block of heads does not stop at a group's edge: where a group has fewer
heads than the tile (8 groups of 8 under a tile of 32) the block SPANS
groups and is handed the ``B`` and ``C`` rows of each, so that a model of
many groups moves its state in blocks as large as a model of one; where a
group has more, the block is a whole part of one group. The states of all
layers and slots live in ONE arena ``(state layers, n_slots, H, P, N)``
(``serving.kv_pool.PagedKVState.ssm``), the paged step's donated operand
and the layer walk's carry; this kernel is handed the whole arena with the
layer's index, aliased in to out, and reads and writes block
``[layer, slot, head tile]`` of it where it lies: each slot's state moves
once each way a layer and a step, and no copy of the arena exists. A dead
slot is given ``a = 1, u = 0`` by the caller: its block is written back as
it was read.

Layout. A state block is ``(heads, P, N)`` with ``N`` on the lanes, so the
outer product and the decay want ``u`` and ``a`` as COLUMNS over ``P``
(sublanes), broadcast along the lanes, and ``y`` comes out of the lane
reduction as a column too. The small operands are therefore handed over
transposed, ``(slots, head tiles, P, heads a tile)``: column ``j`` of a
tile is head ``j``'s ``(P, 1)`` (a static lane slice), and ``y`` is
written the same way and transposed back outside. ``a`` is repeated along
``P`` for the same reason. All of them together are 1/128 of the state's
bytes.
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

NAME = "ssm_state_update"
# Heads a grid step: 32 x 64 x 128 x 4 B = 1 MiB a block. Read on a v5e at the
# cell's geometry (36 layers x 32 slots; PERF.md, PR 32): 8 heads 435 GB/s of
# state moved, 16 497, 32 527, 64 532. Whatever the number of groups.
HEAD_TILE = 32


def _kernel(layer_ref, a_ref, u_ref, b_ref, c_ref, s_ref, o_ref, y_ref, *,
            heads: int, groups: int):
    del layer_ref                       # read by the index maps
    # each group's (1, N) rows, broadcast over P
    rows = [(b_ref[g:g + 1], c_ref[g:g + 1]) for g in range(groups)]
    for j in range(heads):
        b, c = rows[j * groups // heads]
        s = (a_ref[:, j:j + 1] * s_ref[j] + u_ref[:, j:j + 1] * b)
        o_ref[j] = s
        y_ref[:, j:j + 1] = jnp.sum(s * c, axis=1, keepdims=True)


def ssm_state_update(arena, layer, a, u, b, c, *, head_tile: int | None = None,
                     interpret=None):
    """``arena`` (state layers, n_slots, H, P, N) float32; ``layer`` ()
    int32; ``a`` (n_slots, H) decay, ``u`` (n_slots, H, P) ``dt * x``,
    ``b`` and ``c`` (n_slots, G, N), heads ``[g * H / G, (g + 1) * H / G)``
    reading group ``g``; all float32. Returns ``(arena, y)``: the arena with
    ``[layer]`` advanced by one token a slot (the same buffer under jit:
    the operand is aliased to the result) and ``y`` (n_slots, H, P).

    ``interpret=None`` where there is no TPU returns
    ``ssm_state_update_reference`` (``platform.plain_off_tpu``: AUTO off the
    TPU takes the plain form); ``True`` is the interpreted kernel, ``False``
    Mosaic's."""
    if plain_off_tpu(interpret):
        return ssm_state_update_reference(arena, layer, a, u, b, c)
    n_slots, H, P, N = arena.shape[1:]
    G = b.shape[1]
    ht, hpg = min(head_tile or HEAD_TILE, H), H // G
    # A block is whole groups or a whole part of one: it carries the B and
    # C rows of the ``gpt`` groups its heads read.
    gpt = max(1, ht // hpg)
    if H % ht or (ht % hpg and hpg % ht):
        raise ValueError(f"a head tile of {ht} does not divide {H} heads in "
                         f"{G} group(s) of {hpg}")
    n_ht = H // ht

    def cols(x):                        # (n_slots, H, P) -> (.., n_ht, P, ht)
        return x.reshape(n_slots, n_ht, ht, P).transpose(0, 1, 3, 2)

    a_cols = cols(jnp.broadcast_to(a[:, :, None], (n_slots, H, P)))
    small = pl.BlockSpec((None, None, P, ht),
                         lambda s, h, ly: (s, h, 0, 0))
    row = pl.BlockSpec((None, None, gpt, N),
                       lambda s, h, ly: (s, h * ht // hpg // gpt, 0, 0))
    state = pl.BlockSpec((None, None, ht, P, N),
                         lambda s, h, ly: (ly[0], s, h, 0, 0))
    arena, y = pl.pallas_call(
        functools.partial(_kernel, heads=ht, groups=gpt),
        out_shape=(jax.ShapeDtypeStruct(arena.shape, arena.dtype),
                   jax.ShapeDtypeStruct((n_slots, n_ht, P, ht), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_slots, n_ht),
            in_specs=[small, small, row, row, state],
            out_specs=[state, small]),
        # operand 5 (after the prefetched layer index) is the arena
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=5 * n_slots * H * P * N, transcendentals=0,
            bytes_accessed=2 * 4 * n_slots * H * P * N),
        interpret=resolve_interpret(interpret),
        name=NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), a_cols, cols(u),
      b.reshape(n_slots, G // gpt, gpt, N),
      c.reshape(n_slots, G // gpt, gpt, N), arena)
    return arena, y.transpose(0, 1, 3, 2).reshape(n_slots, H, P)


def ssm_state_update_reference(arena, layer, a, u, b, c):
    """The same in plain ``jax.numpy`` (tests, and every run off the TPU
    that does not ask for the kernel; no aliasing promised)."""
    H, G = arena.shape[2], b.shape[1]
    bh = jnp.repeat(b, H // G, axis=1)                  # (n_slots, H, N)
    ch = jnp.repeat(c, H // G, axis=1)
    s = (a[:, :, None, None] * arena[layer]
         + u[..., None] * bh[:, :, None, :])
    return arena.at[layer].set(s), jnp.sum(s * ch[:, :, None, :], axis=-1)
