"""The Mamba-2 mixer over per-slot state (one device).

A layer keeps, for each SLOT of the serving batch, a state of fixed size:
the recurrence's ``S`` (heads, head width, state width) in float32 and the
convolution's window, the last ``d_conv - 1`` inputs. Both live in the
paged pool's state (``serving.kv_pool.PagedKVState.ssm`` / ``.conv``: one
arena each over (state layers, slots)), which this layer is handed whole
with its own index and hands back, as the attention layers do with the row
arenas.

The equations (Mamba-2; HF ``GraniteMoeHybridMambaLayer``), for one token
``x`` of the stream::

    [z ; xBC ; dt] = x W_in                     (d_inner ; conv_dim ; H)
    xBC <- silu(conv(xBC))     causal depthwise, the last d_conv positions
    [x_s ; B ; C] = xBC        x_s: H heads of P; B, C: G groups of N
    D_t = softplus(dt + dt_bias)                a_t = exp(D_t * A),  A = -exp(A_log)
    S_t = a_t S_{t-1} + D_t x_s (x) B           y = S_t C + D x_s    (a head)
    out = RMSNorm(y * silu(z)) W_out            (gate BEFORE the norm)

Two step shapes, by the block of the paged step's token batch
(``nn.TokenBlock``): one token a slot (``L == 1``; the decode block) runs
the recurrence in ``kernels.ssm_update.ssm_state_update``, in place on the
arena; a chunk of ``L`` positions a row runs ``chunk_scan``, plain
``jax.numpy``, with the row's state gathered out of the arena and scattered
back. Either way a slot is advanced over its LIVE positions only: a dead
row, and a chunk's positions past the row's length, have ``D_t = 0`` (so
``a_t = 1`` and nothing is added to ``S``) and do not enter the window. A
row whose cache length before the step is 0 starts from a zero state and a
zero window, whatever the arena holds: the request before it in the slot
leaves nothing a new one could read, and nothing has to be cleared from the
host.

Several rows of a gathered block (the mixed step's prefill block, whose
rows the host deals: ``nn.paged_token_blocks``) may belong to ONE slot,
consecutive chunks of its prompt in consecutive rows, every row but the
last full. Such rows are CHAINED (``short_conv.chained_rows``, read from the
block's own slots, offsets and live lengths): row k starts from the state row k-1
ENDS on (Mamba-2's own recurrence from chunk to chunk, ``chunk_scan``'s
pass over the rows, float32 throughout) and from the window row k-1 leaves,
its last ``d_conv - 1`` inputs; only the FIRST row of a run reads the arena
(or starts from zero) and only the LAST writes it. A block whose live rows
are all different slots has no chained row and is one gather, one scan of
independent rows and one scatter. The window's part of all this (read,
``fresh``, chained, only a run's last row writes) is ``layers.short_conv``'s
``slot_rows`` and ``conv_window``, which the gated short convolution calls
too; the bias and the SiLU stay here.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from triton_distributed_tpu.kernels.ssm_update import ssm_state_update
from triton_distributed_tpu.layers.short_conv import conv_window, slot_rows

HIGHEST = jax.lax.Precision.HIGHEST


def chunk_scan(x, dt, a_log_step, b, c, s0, chained=None):
    """The recurrence over a chunk, state in and out (all float32).

    x (R, L, H, P); dt (R, L, H) the step ``D_t`` (0 at a dead position);
    a_log_step (R, L, H) = ``D_t * A`` (the log of the decay, <= 0);
    b, c (R, L, G, N); s0 (R, H, P, N). Returns ``(y (R, L, H, P), s_L)``.
    With ``la_t`` the running sum of the log decays,

        y_t = exp(la_t) S_0 C_t + sum_{s<=t} exp(la_t - la_s) (C_t . B_s) D_s x_s
        S_L = exp(la_L) S_0 + sum_s exp(la_L - la_s) D_s x_s (x) B_s

    the second line's products as matrix products over the chunk (the
    structured-state-space duality). Every product runs at ``HIGHEST``: the
    state is carried for thousands of steps.

    ``chained`` (R,) bool or None: row k's ``S_0`` is row k-1's ``S_L`` and
    not ``s0[k]``. What needs no incoming state (the sums over ``s``, the
    chunk's whole decay ``exp(la_L)``) is computed for all rows at once;
    the states then pass from row to row, ``S_L = exp(la_L) S_0 + local``,
    elementwise on (H, P, N)."""
    R, L, H, P = x.shape
    G = b.shape[2]
    la = jnp.cumsum(a_log_step, axis=1)                        # (R, L, H)
    u = dt[..., None] * x                                      # D_s x_s
    hb = jnp.repeat(b, H // G, axis=2)                         # (R, L, H, N)
    hc = jnp.repeat(c, H // G, axis=2)
    # within the chunk
    cb = jnp.einsum("rthn,rshn->rhts", hc, hb, precision=HIGHEST)
    diff = la.transpose(0, 2, 1)[..., :, None] \
        - la.transpose(0, 2, 1)[..., None, :]                  # (R, H, t, s)
    causal = jnp.tril(jnp.ones((L, L), bool))
    w = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0) * cb
    y = jnp.einsum("rhts,rshp->rthp", w, u, precision=HIGHEST)
    to_end = jnp.exp(la[:, -1:, :] - la)                       # (R, L, H)
    decay = jnp.exp(la[:, -1])[..., None, None]                # (R, H, 1, 1)
    local = jnp.einsum("rshp,rshn->rhpn", to_end[..., None] * u, hb,
                       precision=HIGHEST)
    if chained is not None:
        # the state each row starts with: the row before's last, down a run
        def row(s_before, xs):
            go_on, s0_k, decay_k, local_k = xs
            s_in = jnp.where(go_on, s_before, s0_k)
            return decay_k * s_in + local_k, s_in

        _, s0 = jax.lax.scan(row, jnp.zeros_like(s0[0]),
                             (chained, s0, decay, local))
    # from the state the chunk started with
    y += jnp.exp(la)[..., None] * jnp.einsum(
        "rthn,rhpn->rthp", hc, s0, precision=HIGHEST)
    return y, decay * s0 + local


def draw_own(name: str, key, shape):
    """A random draw of one of the layer's parameters that is not a matrix
    (a model's ``init``), as Mamba-2 initialises them: ``a_log = log U(1,
    16)``, ``dt_bias`` the inverse softplus of a step drawn log-uniformly
    from [1e-3, 1e-1], the convolution's bias 0, ``d_skip`` and the norm 1."""
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        step = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return step + jnp.log(-jnp.expm1(-step))
    if name == "conv_b":
        return jnp.zeros(shape, jnp.float32)
    return jnp.ones(shape, jnp.float32)


@dataclasses.dataclass(frozen=True)
class Mamba2:
    d_model: int
    n_heads: int            # H
    d_head: int             # P
    d_state: int            # N
    d_conv: int = 4
    n_groups: int = 1       # G
    rms_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    def param_shapes(self) -> dict:
        """name -> (shape, fan_in); fan_in None marks what is not a matrix
        (``draw_own`` says how each of those is drawn)."""
        d, di, C, H = self.d_model, self.d_inner, self.conv_dim, self.n_heads
        return {"w_in": ((d, 2 * di + 2 * self.n_groups * self.d_state + H),
                         d),
                "conv_w": ((self.d_conv, C), self.d_conv),
                "conv_b": ((C,), None), "dt_bias": ((H,), None),
                "a_log": ((H,), None), "d_skip": ((H,), None),
                "norm": ((di,), None), "w_out": ((di, d), di)}

    # -- the pieces ---------------------------------------------------------

    def _block(self, params, zxbcdt, state, blk, layer, interpret):
        """One block of the token batch: ``(y (rows * L, d_inner) float32,
        state)``, y before the gate and the norm."""
        R, L = blk.offsets.shape[0], blk.L
        H, P, N, G = self.n_heads, self.d_head, self.d_state, self.n_groups
        di, C, K = self.d_inner, self.conv_dim, self.d_conv
        part = zxbcdt[blk.start:blk.stop].reshape(R, L, -1)
        xbc, dt = part[..., di:di + C], part[..., di + C:]
        rows = slot_rows(blk, K)
        taps, conv_arena = conv_window(state.conv, layer, rows, xbc,
                                       params["conv_w"])
        conv = jax.nn.silu(params["conv_b"].astype(jnp.float32) + taps)

        x = conv[..., :di].reshape(R, L, H, P)
        b = conv[..., di:di + G * N].reshape(R, L, G, N)
        c = conv[..., di + G * N:].reshape(R, L, G, N)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + params["dt_bias"].astype(jnp.float32))
        dt = jnp.where(rows.live[..., None], dt, 0.0)                 # (R, L, H)
        a_log_step = dt * -jnp.exp(params["a_log"].astype(jnp.float32))
        if L == 1 and rows.whole:
            # one token a slot: in place on the arena. A fresh row's old
            # state is multiplied by 0 and not by its decay; a dead row's
            # by 1 (its step is 0).
            decay = jnp.where(rows.fresh[:, None], 0.0,
                              jnp.exp(a_log_step[:, 0]))
            ssm, y = ssm_state_update(
                state.ssm, layer, decay, dt[:, 0, :, None] * x[:, 0],
                b[:, 0], c[:, 0], interpret=interpret)
            y = y[:, None]
        else:
            s0 = jnp.where(rows.fresh[:, None, None, None], 0.0,
                           state.ssm[layer, rows.slots])
            y, s = chunk_scan(x, dt, a_log_step, b, c, s0, rows.chained)
            ssm = state.ssm.at[layer, rows.put(state.ssm)].set(s, mode="drop")
        y = y + params["d_skip"].astype(jnp.float32)[:, None] * x
        state = dataclasses.replace(state, ssm=ssm, conv=conv_arena)
        return y.reshape(R * L, di), state

    # -- the layer ----------------------------------------------------------

    def fwd(self, params, x, state, *, blocks, layer, interpret=None):
        """x: the paged step's flat token batch (T, d_model) in the model
        dtype -> ``(out (T, d_model), state)``. ``layer`` () int32: this
        layer's index among the layers that keep a state (the arenas'
        leading axis)."""
        di = self.d_inner
        zxbcdt = jnp.dot(x, params["w_in"])
        ys = []
        for blk in blocks:
            y, state = self._block(params, zxbcdt, state, blk, layer,
                                   interpret)
            ys.append(y)
        tail = x.shape[0] - blocks[-1].stop
        if tail:
            ys.append(jnp.zeros((tail, di), jnp.float32))
        y = jnp.concatenate(ys) * jax.nn.silu(
            zxbcdt[:, :di].astype(jnp.float32))
        # the gated norm: each group's columns normalised on their own
        grouped = y.reshape(y.shape[0], self.n_groups, -1)
        y = (grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, axis=-1, keepdims=True)
            + self.rms_eps)).reshape(y.shape) * params["norm"]
        return jnp.dot(y.astype(x.dtype), params["w_out"]), state
