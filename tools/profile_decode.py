"""Decode-step component breakdown on the real chip: slope-time each
component of the qwen3-1.7b B=8 decode step separately (same methodology
as bench.py), then compare the sum against the measured e2e step.

``--probes``: instead of slope-timing, run the probed paged-attention
build (kernels/probes.py), decode the device telemetry record with
obs.kprobe, print the stall attribution, and write the per-step Chrome
trace rows to ``--trace-dir`` (default /tmp/tdtpu_probe_trace).
``--prefill N`` probes an N-token chunked-prefill step (causal
(B, n_q_tiles) grid, kv tiles walked in the kernel) instead of the L=1
decode step. Runs on any backend (interpret mode off-TPU).

``--kernel``: the fused paged-attention kernel ALONE at a cell's geometry
(defaults: ``qwen3-1.7b.reasoning`` — 28 layers, a 3,328-block pool of
16-row blocks, 8 KV heads x 128, 32 slots, contexts drawn 300-3,300 with
77% of the pool live, shuffled block tables), decode shape (L=1) and chunk
shape (one slot prefilling ``--chunk`` tokens beside 31 decoding rows, the
mixed step), one line a (shape, ``--tiles`` entry): the arithmetic the
shape's tiles take (``folded`` / ``per_head``), ms a step of all layers, us
a live kv tile, GB/s of live pool bytes. ``--layers 4 --hkv 4 --g 8`` is
the granite cell's geometry (packed key rows). ``--latent W,V`` times
the latent build (one arena of W-wide rows, values the first V columns;
``--hkv 1``). ``--window W`` times the window build over ring storage:
contexts drawn 300 .. ``--max-len`` less a chunk (there is no pool to fill),
so ``--window 4096 --max-len 16384 --layers 6 --hkv 4 --g 7`` is the
SmallThinker cell's geometry (a ring of 284 blocks a slot, contexts past the
window and round the ring); its lines also say how many copies a layer's
walks start: one a WHOLE tile whose blocks lie side by side in the ring,
one a live block of any other tile (ragged at an end, or wrapping the
ring). Every line says what one copy carries (``copy_bytes``: a block's K
plane and V plane are one run of the pool's one arena, ``serving.kv_pool``)
and how many a whole tile starts. ``--hkv 2 --g 4 --layers 36`` is the
four-chip cell's walk, a chip's two key heads (16 KB a copy). No cell runs
it; it is ROADMAP S5's yardstick."""
import functools, time
import os, sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax, jax.numpy as jnp


def _probes_mode():
    import numpy as np
    from triton_distributed_tpu.kernels.paged_attention import (
        paged_attention)
    from triton_distributed_tpu.obs import kprobe
    from triton_distributed_tpu.runtime.utils import dist_print

    B, Hq, Hkv, dh, bs, max_blocks, tile = 8, 16, 8, 128, 16, 8, 4
    L = int(sys.argv[sys.argv.index("--prefill") + 1]) \
        if "--prefill" in sys.argv else 1
    n_blocks = B * max_blocks
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, L, Hq, dh)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(n_blocks, 2, bs, Hkv, dh)),
                       jnp.float32)
    tables = jnp.asarray(rng.permutation(n_blocks).reshape(B, max_blocks),
                         jnp.int32)
    kv_lens = jnp.asarray(
        rng.integers(L, max_blocks * bs + 1, size=B), jnp.int32)

    t0 = time.perf_counter()
    out, pbuf = paged_attention(q, pool, tables, kv_lens,
                                tile_blocks=tile, probes=True)
    jax.block_until_ready(out)
    wall_us = (time.perf_counter() - t0) * 1e6

    s = kprobe.stall_summary(np.asarray(pbuf)[None])
    dist_print(f"paged_attn probe (L={L}): {s['n_steps']} grid steps, "
               f"B={B} tiles/slot={max_blocks // tile}")
    dist_print(f"stall attribution: dma_wait {s['pct_dma_wait']:.1f}%  "
               f"sem_spin {s['pct_sem_spin']:.1f}%  "
               f"compute {s['pct_compute']:.1f}%")
    tr = kprobe.decode(pbuf)
    tot = tr.totals()
    dist_print(f"bytes: local {tot['local_bytes']} wait {tot['wait_bytes']} "
               f"remote {tot['remote_bytes']}; kflops {tot['kflops']}")
    tdir = sys.argv[sys.argv.index("--trace-dir") + 1] \
        if "--trace-dir" in sys.argv else "/tmp/tdtpu_probe_trace"
    paths = kprobe.export_device_traces(pbuf[None], tdir,
                                        wall_dur_us=wall_us,
                                        label="paged_decode")
    dist_print(f"device trace rows -> {paths[0]}")


def _window_copies(kv_len, window, bs, tile, ring):
    """What a decoding row's window walk fetches, by the kernel's rule: (whole
    tiles whose blocks lie side by side in the ring: ONE copy, both planes;
    live blocks of every other tile, ragged at an end or wrapping the ring: a
    copy each)."""
    lo, span = max(kv_len - window, 0), tile * bs
    whole = blocks = 0
    for t in range(lo // span, -(-kv_len // span)):
        live = sum((t * tile + i + 1) * bs > lo and (t * tile + i) * bs < kv_len
                   for i in range(tile))
        if (live == tile and (t + 1) * span <= kv_len
                and (t * tile) % ring + tile <= ring):
            whole += 1
        else:
            blocks += live
    return whole, blocks


def _kernel_mode():
    import argparse
    import numpy as np
    from triton_distributed_tpu.kernels.paged_attention import (
        paged_attention)
    from triton_distributed_tpu.runtime.utils import dist_print

    ap = argparse.ArgumentParser()
    ap.add_argument("--kernel", action="store_true")
    ap.add_argument("--layers", type=int, default=28)
    ap.add_argument("--n-blocks", type=int, default=3328)
    ap.add_argument("--block", type=int, default=16)
    ap.add_argument("--hkv", type=int, default=8)
    ap.add_argument("--g", type=int, default=2)
    ap.add_argument("--dh", type=int, default=128)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--max-len", type=int, default=4096)
    ap.add_argument("--live", type=float, default=0.77)
    ap.add_argument("--tiles", default="",
                    help="comma list of tile_blocks; empty = the default")
    ap.add_argument("--latent", default="",
                    help="W,V: one latent arena of W-wide rows")
    ap.add_argument("--window", type=int, default=0,
                    help="the WINDOW build: ring storage a slot, a walk "
                         "over the last WINDOW keys (--layers window "
                         "layers; contexts 300 .. --max-len less a chunk; "
                         "--n-blocks and --live are not read)")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()

    rng = np.random.default_rng(a.seed)
    B, bs, nb = a.slots, a.block, a.n_blocks
    max_blocks = a.max_len // bs
    # Contexts 300-3,300, stretched until the live share of the pool is met
    # (a slot's blocks cover its context and the chunk it may append).
    u = rng.uniform(size=B)

    def drawn(stretch):
        lens = np.minimum((300 + stretch * 3000 * u).astype(np.int64),
                          a.max_len - a.chunk)
        return lens, -(-(lens + a.chunk) // bs)

    lo, hi = 0.0, 1.0
    for _ in range(40):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if drawn(mid)[1].sum() < a.live * nb else (lo, mid)
    lens, n_live_blocks = drawn(lo)
    if a.window:        # a ring a slot: no pool bounds the contexts
        lens = (300 + (a.max_len - a.chunk - 300) * u).astype(np.int64)
    perm = rng.permutation(nb)                       # shuffled tables
    tables = np.zeros((B, max_blocks), np.int32)
    at = 0
    for b in range(B):
        tables[b, :n_live_blocks[b]] = perm[at:at + n_live_blocks[b]]
        at += n_live_blocks[b]
    assert at <= nb, "the contexts drawn do not fit the pool"
    key = jax.random.PRNGKey(a.seed)
    if a.latent:
        W, V = (int(x) for x in a.latent.split(","))
        row, Hq, dh, kw = (W,), a.g, W, dict(v_dim=V)
    else:
        row, Hq, dh, kw = (a.hkv, a.dh), a.hkv * a.g, a.dh, {}
    # the pool's one arena (serving.kv_pool): a block's K plane and V plane
    # side by side; a latent block's rows are both and have no planes
    pool = (a.layers, nb, bs) if a.latent else (a.layers, nb, 2, bs)
    if a.window:
        # a ring a slot that holds the window and a step's 7 rows of a chunk
        # (serving.kv_pool.window_ring_blocks), the planes outside its
        # lines; the table names slots
        ring = -(-(a.window - 1 + 7 * a.chunk) // bs)
        pool, nb = (a.layers, B, 2, ring, bs), B * ring
        tables = np.arange(B, dtype=np.int32)[:, None]
        kw = dict(window=a.window)
    arena = jax.random.normal(key, (*pool, *row), jnp.bfloat16)
    block_bytes = arena.nbytes // (a.layers * nb)
    dist_print(
        f"geometry: {a.layers} layers, pool {nb} x {bs} rows, row {row}, "
        f"{B} slots, contexts {lens.min()}-{lens.max()} "
        + (f"(a ring of {ring} blocks a slot, a window of {a.window})"
           if a.window else
           f"({n_live_blocks.sum()} blocks live, "
           f"{n_live_blocks.sum() / nb:.1%} of the pool)")
        + f", {block_bytes} B a block, "
        f"device {jax.devices()[0].device_kind}")

    for shape, L in (("decode", 1), ("chunk", a.chunk)):
        q_lens = np.ones((B,), np.int32)
        if L > 1:
            q_lens[0] = L        # one slot prefills a chunk, the rest decode
        kv_lens = (lens + q_lens).astype(np.int32)
        q = jax.random.normal(jax.random.fold_in(key, 7),
                              (B, L, Hq, dh), jnp.bfloat16)
        for tile in [int(t) for t in a.tiles.split(",") if t] or [None]:
            resolved = {}       # what the call chose: tile and arithmetic

            @jax.jit
            def step(q, arena):
                def layer(q, li):
                    out = paged_attention(
                        q, arena, jnp.asarray(tables),
                        jnp.asarray(kv_lens), q_lens=jnp.asarray(q_lens),
                        layer=li, tile_blocks=tile, resolved=resolved,
                        **kw)
                    # the next layer's queries hang on this layer's output
                    return q + (out[..., :1] * 1e-9).astype(q.dtype), None
                return jax.lax.scan(layer, q,
                                    jnp.arange(a.layers, dtype=jnp.int32))[0]

            step(q, arena).block_until_ready()
            step(q, arena).block_until_ready()
            t0 = time.perf_counter()
            out = q
            for _ in range(a.iters):
                out = step(out, arena)
            out.block_until_ready()
            ms = (time.perf_counter() - t0) * 1e3 / a.iters
            # Under jit the kernel takes the heuristic default (an eager
            # call on a TPU would tune: twenty minutes, PERF.md section 6).
            t_used = resolved["tile_blocks"]
            live_blocks = -(-kv_lens.astype(np.int64) // bs)
            if a.window:        # the blocks that hold a visible key
                first = np.maximum(kv_lens - q_lens - a.window + 1, 0) // bs
                live_blocks = live_blocks - first
            n_tiles = int((-(-live_blocks // t_used)).sum())
            live_bytes = int(live_blocks.sum()) * block_bytes * a.layers
            copies = ""
            if a.window:
                # A slot's walk is one query tile's unless it prefills (the
                # chunk's query tiles each walk their own window: the line
                # counts the decoding rows, 31 of 32).
                whole = other = 0
                for b in np.flatnonzero(q_lens == 1):
                    w, o = _window_copies(int(kv_lens[b]), a.window, bs,
                                          t_used, ring)
                    whole, other = whole + w, other + o
                copies = (f", {whole + other} copies a layer for the "
                          f"decoding rows ({whole} whole tiles, {other} "
                          f"blocks; a copy a block: "
                          f"{whole * t_used + other})")
            dist_print(
                f"{shape:6s} tile_blocks={t_used:3d} "
                f"{resolved['arithmetic']:8s}: {ms:8.3f} ms a step "
                f"({ms / a.layers * 1e3:7.1f} us a layer), "
                f"{n_tiles} live tiles a layer, "
                f"{ms * 1e3 / a.layers / n_tiles:6.2f} us a tile, "
                f"{live_bytes / ms / 1e6:6.1f} GB/s of live bytes, "
                f"{resolved['copy_bytes']} B a copy, "
                f"{resolved['copies_per_tile']} copies a whole tile"
                + copies)


if "--probes" in sys.argv:
    _probes_mode()
    sys.exit(0)
if "--kernel" in sys.argv:
    _kernel_mode()
    sys.exit(0)

SHORT, LONG = 96, 288

def _timed(loop, args, iters):
    t0 = time.perf_counter()
    out = loop(*args, iters)
    float(jax.tree.leaves(out)[0].ravel()[0])
    return (time.perf_counter() - t0) * 1e3

def slope(loop, args, n=5):
    _timed(loop, args, SHORT); _timed(loop, args, LONG)
    best = []
    for _ in range(n):
        s = _timed(loop, args, SHORT); l = _timed(loop, args, LONG)
        best.append((l - s) / (LONG - SHORT))
    best.sort()
    return best[max(0, (len(best)-1)//4)]

from triton_distributed_tpu.models import ModelConfig
from triton_distributed_tpu.kernels.sp_attention import flash_decode_local
from triton_distributed_tpu.runtime.utils import dist_print

c = ModelConfig.from_name("qwen3-1.7b", max_length=512)
B, S, L = 8, 512, 28
d, Hq, Hkv, dh, dff, V = (c.d_model, c.n_heads, c.n_kv_heads, c.head_dim,
                          c.d_ff, c.vocab_size)
dist_print(f"config: d={d} Hq={Hq} Hkv={Hkv} dh={dh} dff={dff} V={V} "
           f"layers={c.n_layers}")
key = jax.random.PRNGKey(0)

# stacked per-layer weights (as the scan sees them)
wqkv = jax.random.normal(key, (L, d, (Hq + 2*Hkv)*dh), jnp.bfloat16)
wo = jax.random.normal(key, (L, Hq*dh, d), jnp.bfloat16)
wgu = jax.random.normal(key, (L, d, 2*dff), jnp.bfloat16)
wdn = jax.random.normal(key, (L, dff, d), jnp.bfloat16)
kc = jax.random.normal(key, (L, B, S, Hkv, dh), jnp.bfloat16)
vc = jax.random.normal(key, (L, B, S, Hkv, dh), jnp.bfloat16)
lm = jax.random.normal(key, (d, V), jnp.bfloat16)
x = jax.random.normal(key, (B, d), jnp.bfloat16)

def dep(acc):
    return (jax.tree.leaves(acc)[0].ravel()[0] * 1e-24).astype(jnp.float32)

def scan_arm(f, carry_shape=(8, 2048)):
    # scan over L layers of component f, inside fori_loop
    def make(ws):
        @functools.partial(jax.jit, static_argnames=("n",))
        def loop(x, ws, n):
            def body(_, acc):
                xx = (x + dep(acc).astype(x.dtype))
                def lay(h, w):
                    return f(h, w), None
                out, _ = jax.lax.scan(lay, xx, ws)
                return acc + out.astype(jnp.float32)
            return jax.lax.fori_loop(0, n, body, jnp.zeros(carry_shape, jnp.float32))
        return loop
    return make

# 1. qkv+out projections per layer
def attn_proj(h, w):
    wq, wo_ = w
    q = jnp.dot(h, wq, preferred_element_type=jnp.float32).astype(h.dtype)
    return jnp.dot(q[:, :Hq*dh], wo_, preferred_element_type=jnp.float32).astype(h.dtype)
t_proj = slope(scan_arm(attn_proj)(None), (x, (wqkv, wo)))

# 2. flash decode attention per layer (bd path)
def attn_fd(h, w):
    kcl, vcl = w
    q = jnp.broadcast_to(h[:, None, :dh], (B, Hq, dh)).astype(jnp.bfloat16)
    out, _ = flash_decode_local(q, kcl, vcl, kv_len=S, kv_layout="bshd")
    return (h + out.reshape(B, -1)[:, :d].astype(h.dtype) * 1e-6).astype(h.dtype)
t_attn = slope(scan_arm(attn_fd)(None), (x, (kc, vc)))

# 3. MLP per layer
def mlp(h, w):
    g, dn = w
    hh = jnp.dot(h, g, preferred_element_type=jnp.float32)
    act = (jax.nn.silu(hh[:, :dff]) * hh[:, dff:]).astype(h.dtype)
    return jnp.dot(act, dn, preferred_element_type=jnp.float32).astype(h.dtype)
t_mlp = slope(scan_arm(mlp)(None), (x, (wgu, wdn)))

# 4. lm_head (once per step)
@functools.partial(jax.jit, static_argnames=("n",))
def loop_lm(x, lm, n):
    def body(_, acc):
        xx = x + dep(acc).astype(x.dtype)
        return acc + jnp.dot(xx, lm, preferred_element_type=jnp.float32)
    return jax.lax.fori_loop(0, n, body, jnp.zeros((B, V), jnp.float32))
t_lm = slope(loop_lm, (x, lm))

# 5. cache update (dynamic_update_slice per layer, donated)
def cache_upd(h, w):
    kcl = w
    new = h[:, None, None, :dh] * jnp.ones((B, 1, Hkv, dh), h.dtype)
    kcl = jax.lax.dynamic_update_slice(kcl, new.astype(kcl.dtype), (0, 200, 0, 0))
    return (h + kcl[:, 200, 0, :d // 16].repeat(16, -1) * 1e-6).astype(h.dtype)
t_cache = slope(scan_arm(cache_upd)(None), (x, kc))

hbm = 819e9
wb = lambda a: a.nbytes
floors = {
  "attn_proj": (wqkv.nbytes + wo.nbytes) / hbm * 1e3,
  "flash_attn": (kc.nbytes + vc.nbytes) / hbm * 1e3,
  "mlp": (wgu.nbytes + wdn.nbytes) / hbm * 1e3,
  "lm_head": lm.nbytes / hbm * 1e3,
}
dist_print(f"attn_proj: {t_proj:.3f} ms (floor {floors['attn_proj']:.3f})")
dist_print(f"flash_attn: {t_attn:.3f} ms (floor {floors['flash_attn']:.3f})")
dist_print(f"mlp: {t_mlp:.3f} ms (floor {floors['mlp']:.3f})")
dist_print(f"lm_head: {t_lm:.3f} ms (floor {floors['lm_head']:.3f})")
dist_print(f"cache_upd: {t_cache:.3f} ms")
dist_print(f"sum: {t_proj + t_attn + t_mlp + t_lm + t_cache:.3f} ms  "
           "(e2e measured ~7.4-8.0)")
