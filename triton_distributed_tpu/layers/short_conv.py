"""A causal depthwise convolution over a WINDOW a slot, and the layer whose
whole state that window is: the gated short convolution.

What a sequence keeps of a causal convolution of ``K`` taps is its last
``K - 1`` inputs. They live in the paged pool's state
(``serving.kv_pool.PagedKVState.conv``: one arena over (state layers, slots,
``(K - 1) * channels``), oldest input first), which a layer is handed whole
with its own index and hands back. ``slot_rows`` and ``conv_window`` are the
ONE piece of code that reads, chains and writes such windows; two layers call
it: ``layers.mamba2.Mamba2`` (its convolution feeds a recurrence and takes a
bias and a SiLU, which stay with it) and ``ShortConv`` below (no bias, no
activation, nothing else kept).

The rules, by the block of the paged step's token batch (``nn.TokenBlock``).
A slot is advanced over its LIVE positions only: a dead row, and a chunk's
positions past the row's length, do not enter the window. A row whose cache
length before the step is 0 is FRESH and starts from a zero window, whatever
the arena holds: the request before it in the slot leaves nothing a new one
could read, and nothing has to be cleared from the host. Several rows of a
gathered block (the mixed step's prefill block, whose rows the host deals:
``nn.paged_token_blocks``) may belong to ONE slot, consecutive chunks of its
prompt in consecutive rows, every row but the last full. Such rows are
CHAINED (``chained_rows``): row k starts from the window row k-1 leaves, its
last ``K - 1`` inputs; only the FIRST row of a run reads the arena (or starts
from zero) and only the LAST writes it.

The gated short convolution (LFM2; HF ``Lfm2ShortConv``), for the stream's
token ``u_t``::

    [B ; C ; X] = u W_in                    (d ; d ; d), no bias
    z_t = B_t * X_t
    c_t = sum_k w_k z_{t-K+1+k}             depthwise, causal, K taps a
                                            channel, no bias, NO activation
    out = (C_t * c_t) W_out

``z`` is rounded to the model dtype before the taps read it, so that a value
read back from the arena and one still in the step are the same number. One
token a slot (``L == 1``, row b slot b: the decode block) runs in
``kernels.short_conv_update``, in place on the arena; a chunk runs
``conv_window``, plain ``jax.numpy``. HF gives the two projections and the
convolution a bias under ``conv_bias``; LFM2's published configurations state
false and none is built here (``models.config.Lfm2MoeConfig`` refuses true).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from triton_distributed_tpu.kernels.short_conv_update import short_conv_update


def chained_rows(slots, offsets, n_live, L: int):
    """(R,) bool: row k of a gathered block goes on where row k-1 ends,
    read from the block's own operands: the same slot, both rows live, row
    k-1 full, and row k's cache length the one row k-1 leaves. A dead row
    (no live position) is never chained and nothing is chained to it."""
    def before(a):
        return jnp.roll(a, 1, axis=0)

    return ((jnp.arange(slots.shape[0]) > 0) & (slots == before(slots))
            & (n_live > 0) & (before(n_live) == L)
            & (offsets == before(offsets) + L))


def fresh_rows(blocks):
    """() int: the rows of a step's blocks that start from zero (a cache
    length of 0 before the step and a live position): what a model counts
    as its ``*_states_reset``. Of a slot's run of chained rows only the
    first has that length."""
    return sum(jnp.sum((b.offsets == 0)
                       & jnp.any(b.valid().reshape(-1, b.L), axis=1))
               for b in blocks)


class SlotRows(NamedTuple):
    """How the rows of one block stand to the per-slot arenas."""

    live: jax.Array             # (R, L) bool: the live positions
    n_live: jax.Array           # (R,) live positions of each row, its first
    fresh: jax.Array            # (R,) bool: starts from zero
    whole: bool                 # row b IS slot b (the decode block)
    slots: jax.Array            # (R,) the slot each row belongs to
    chained: jax.Array | None   # (R,) bool (``chained_rows``); None: whole
    writes: jax.Array           # (R,) bool: the row writes its slot

    def put(self, arena):
        """(R,) where each row writes ``arena`` (state layers, n_slots,
        ...): its slot, or ``n_slots`` (out of range, dropped) for a row
        that writes nothing."""
        return jnp.where(self.writes, self.slots, arena.shape[1])


def slot_rows(blk, taps: int) -> SlotRows:
    """Read from the block's own operands. ``taps`` = K: a row of a
    gathered block has to hold the ``K - 1`` inputs it hands on."""
    R, L = blk.offsets.shape[0], blk.L
    live = blk.valid().reshape(R, L)
    n_live = jnp.sum(live, axis=1)
    fresh = (blk.offsets == 0) & (n_live > 0)
    # Where row b is slot b (the decode block) a layer's windows are one
    # slice of the arena: read and written as a slice, the dead rows' put
    # back as they were. As a gather and a scatter of 32 rows the same
    # cost 0.9 ms a decode step of 36 layers on the chip.
    whole = blk.slots is None
    slots = jnp.arange(R) if whole else blk.slots
    # a row with nothing live writes nothing (a dead row of a gathered
    # block names no slot of its own): out of range, dropped
    writes = n_live > 0
    chained = None
    if not whole:
        if L < taps - 1:
            raise ValueError(
                f"prefill_chunk = {L} is below the convolution's taps - 1 = "
                f"{taps - 1}: a row of the prefill block has to hold the "
                f"whole window it hands to the slot's next row")
        # Of a slot's run of rows only the last writes the arenas (a
        # scatter with a repeated index has no defined winner): not a
        # row that is followed (row 0 is never chained, so the roll
        # brings the last row a False).
        chained = chained_rows(slots, blk.offsets, n_live, L)
        writes &= ~jnp.roll(chained, -1)
    return SlotRows(live, n_live, fresh, whole, slots, chained, writes)


def conv_window(arena, layer, rows: SlotRows, inputs, conv_w):
    """The taps over ``[window ; inputs]`` and the arena with the windows
    the rows leave. arena (state layers, n_slots, (K - 1) * C); ``layer`` ()
    int32; inputs (R, L, C) in the arena's dtype; conv_w (K, C). Returns
    ``(sum_k w_k seq[t + k] (R, L, C) float32, arena)``: no bias and no
    activation (the caller's), and each writing row's window its last
    ``K - 1`` LIVE inputs."""
    R, L, C = inputs.shape
    K = conv_w.shape[0]
    held = (jax.lax.dynamic_index_in_dim(arena, layer, 0, False)
            if rows.whole else arena[layer, rows.slots])
    window = jnp.where(rows.fresh[:, None, None], 0,
                       held.reshape(R, K - 1, C))
    if rows.chained is not None:
        # a chained row's window is the full row before's last K-1
        # inputs, in the arena's dtype as through the arena
        window = jnp.where(
            rows.chained[:, None, None],
            jnp.roll(inputs[:, L - (K - 1):], 1, axis=0).astype(held.dtype),
            window)
    seq = jnp.concatenate([window.astype(jnp.float32),
                           inputs.astype(jnp.float32)], axis=1)
    w = conv_w.astype(jnp.float32)
    out = sum(w[k] * seq[:, k:k + L] for k in range(K))
    take = rows.n_live[:, None] + jnp.arange(K - 1)[None]          # (R, K-1)
    window = jnp.take_along_axis(seq, take[..., None], axis=1)
    window = window.reshape(R, -1).astype(held.dtype)
    if rows.whole:
        arena = jax.lax.dynamic_update_index_in_dim(
            arena, jnp.where((rows.n_live > 0)[:, None], window, held),
            layer, 0)
    else:
        arena = arena.at[layer, rows.put(arena)].set(window, mode="drop")
    return out, arena


@dataclasses.dataclass(frozen=True)
class ShortConv:
    d_model: int
    taps: int = 3           # K

    def param_shapes(self) -> dict:
        """name -> (shape, fan_in)."""
        d = self.d_model
        return {"w_in": ((d, 3 * d), d), "conv_w": ((self.taps, d), self.taps),
                "w_out": ((d, d), d)}

    def fwd(self, params, x, state, *, blocks, layer, interpret=None):
        """x: the paged step's flat token batch (T, d_model) in the model
        dtype -> ``(out (T, d_model), state)``. ``layer`` () int32: this
        layer's index among the layers that keep a window (the arena's
        leading axis)."""
        d = self.d_model
        bcx = jnp.dot(x, params["w_in"])
        ys = []
        for blk in blocks:
            part = bcx[blk.start:blk.stop]
            if blk.L == 1 and blk.slots is None:
                live = blk.valid()
                conv, y = short_conv_update(
                    state.conv, layer, part, params["conv_w"], live,
                    live & (blk.offsets == 0), interpret=interpret)
            else:
                b, c, xs = (part[:, i * d:(i + 1) * d].reshape(-1, blk.L, d)
                            for i in range(3))
                z = (b.astype(jnp.float32)
                     * xs.astype(jnp.float32)).astype(state.conv.dtype)
                taps, conv = conv_window(
                    state.conv, layer, slot_rows(blk, self.taps), z,
                    params["conv_w"])
                y = (c.astype(jnp.float32) * taps).astype(x.dtype)
                y = y.reshape(-1, d)
            state = dataclasses.replace(state, conv=conv)
            ys.append(y)
        tail = x.shape[0] - blocks[-1].stop
        if tail:
            ys.append(jnp.zeros((tail, d), x.dtype))
        return jnp.dot(jnp.concatenate(ys), params["w_out"]), state
