"""The plain reference: a decoder in straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``.

No cache, no kernels, no batching; it imports nothing of the program under
test and takes nothing the program has made. Weights come from
``perfbench.weights`` (the benchmark's own seeded generator), one layer at a
time, so one float32 layer is all that sits on the device beside the
activations.

This file holds what every model family shares: the primitives (RMSNorm in
float32, rotate-half RoPE, a linear layer in the precision asked for, causal
softmax attention with grouped heads) and the driver (embedding -> layers
-> blocked head, ``forward_positions``). The equations of a layer, and
which weights it has, are its family's (``perfbench/families/<family>.py``:
``layer_forward``, ``layer_weights``, ``head_weights``).

``precision`` selects the arithmetic of the linear layers:

- ``"float32"``: the reference proper.
- ``"fp8"``: the control. Every linear layer's weight and input are rounded
  to float8 (e4m3, one scale a tensor that maps its largest value to 448),
  and the product is taken over those rounded values. It stands for the
  precision step below bfloat16 that would tempt a later PR; ``correct`` has
  to come out false for it.
- ``"int8"``: weights rounded to int8 with one scale per output channel,
  inputs with one scale per token. Kept because its readings are in
  PERF.md: with those scales it lies too close to bfloat16 on the 8 B
  model to serve as the control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024      # attention is computed over blocks of query rows
HEAD_BLOCK = 512        # and the head over blocks of positions
SEQ_PAD = 512           # sequences are padded to a multiple of this


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, positions, theta):
    """Rotate-half rotary embedding. x: (S, H, dh); positions: (S,)."""
    dh = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq        # (S, dh/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _fake_int8(x, axis):
    """Symmetric int8 rounding along ``axis`` (one scale per slice)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _fake_fp8(x):
    """Rounding to float8 e4m3 with one scale for the whole tensor."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def linear(x, w, precision):
    """x: (S, in) float32; w: (in, out) in the served dtype."""
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _fake_fp8(x), _fake_fp8(w)
    elif precision == "int8":
        x = _fake_int8(x, axis=-1)          # per token
        w = _fake_int8(w, axis=0)           # per output channel
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.dot(x, w)


def attention(q, k, v, scale):
    """Causal softmax attention, Hq // Hkv query heads to a key head.
    q: (S, Hq, dh); k: (S, Hkv, dh); v: (S, Hkv, dv) -> (S, Hq*dv)."""
    S, Hq, dh = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    kq = jnp.repeat(k, g, axis=1)                                   # (S, Hq, dh)
    vq = jnp.repeat(v, g, axis=1)
    cols = jnp.arange(S)
    outs = []
    for r0 in range(0, S, QUERY_BLOCK):
        qb = q[r0:r0 + QUERY_BLOCK]
        rows = r0 + jnp.arange(qb.shape[0])
        s = jnp.einsum("qhd,khd->hqk", qb, kq) * scale
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, vq))
    return jnp.concatenate(outs, axis=0).reshape(S, Hq * v.shape[-1])


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def head_block(h, final_norm, head, served, *, eps, precision):
    """Logits of one block of positions, reduced on the device to what the
    comparison reads: the best logit, its token, the logit of the token named in ``served``
    and the spread (standard deviation) of the row."""
    x = rms_norm(h, final_norm, eps)
    logits = linear(x, head, precision)                              # (n, V)
    best = jnp.max(logits, axis=-1)
    return {
        "best": best,
        "best_token": jnp.argmax(logits, axis=-1).astype(jnp.int32),
        "picked": jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0],
        "std": jnp.std(logits, axis=-1),
    }


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def forward_positions(weights, sequences, *, precision="float32",
                      gather=None):
    """Run each of ``sequences`` (``(tokens, first)``: the prompt followed
    by the served tokens, and where the served tokens begin) through the
    whole stack once and read the head at positions ``first - 1`` to
    ``len(tokens) - 2``: the positions whose next token was served.

    ``weights`` (``perfbench.weights.Weights``) carries the family, the
    configuration's sizes and the seed, and makes the weights one layer at a
    time, each layer once for all the sequences. ``gather`` names, for each
    sequence, the token whose logit is read at each of those positions
    (default: the served token). Returns one dict of numpy arrays a
    sequence, one entry a served token.

    A sequence is padded at its end to a multiple of ``SEQ_PAD`` (causal
    attention: padding never reaches an earlier position) and the head
    reads whole blocks, so that few shapes are ever compiled.
    """
    import numpy as np

    family, sizes = weights.family, weights.sizes
    with jax.default_matmul_precision("highest"):
        g = weights.globals_()
        hs = []
        for tokens, _ in sequences:
            ids = np.zeros((-(-len(tokens) // SEQ_PAD) * SEQ_PAD,), np.int32)
            ids[:len(tokens)] = tokens
            hs.append(jnp.take(g["embed"], jnp.asarray(ids),
                               axis=0).astype(jnp.float32))
        for i in range(sizes.n_layers):
            lw = f32(weights.layer(i))
            hs = [family.layer_forward(h, lw, sizes, i, precision)
                  for h in hs]
        hw = family.head_weights(sizes, g)
        head, eps = hw["head"], hw["eps"]
        fn = hw["final_norm"].astype(jnp.float32)
        out = []
        for j, ((tokens, first), h) in enumerate(zip(sequences, hs)):
            n, n_out = len(tokens), len(tokens) - first
            want = tokens[first:] if gather is None else gather[j]
            rows = np.full((-(-n_out // HEAD_BLOCK) * HEAD_BLOCK,), first - 1,
                           np.int32)
            rows[:n_out] = np.arange(first - 1, n - 1)
            toks = np.zeros(rows.shape, np.int32)
            toks[:n_out] = np.asarray(want, np.int32)
            picked = jnp.take(h, jnp.asarray(rows), axis=0)
            parts = [head_block(picked[r0:r0 + HEAD_BLOCK], fn, head,
                                jnp.asarray(toks[r0:r0 + HEAD_BLOCK]),
                                eps=eps, precision=precision)
                     for r0 in range(0, rows.shape[0], HEAD_BLOCK)]
            out.append({k: np.concatenate([np.asarray(p[k]) for p in parts])
                        [:n_out] for k in parts[0]})
    return out
