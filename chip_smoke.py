#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path starts on the chip.

One process, no children. With no arguments (one TPU chip) it serves
Qwen3-1.7B — preset widths, full depth, random weights from a fixed key —
through the entry points a user calls: ``make_mesh`` → ``Engine`` →
``Fleet.build`` → ``BatchEngine`` → the fused paged-attention kernel
compiled by Mosaic (``interpret=False``: a kernel that cannot compile
fails the run; nothing continues on the CPU or the interpreter). It then
FAILS unless every request finished with the tokens it asked for, the
fleet recorded no replica failure, nothing retraced, the pool's
invariants hold, the prefix cache hit, and the paged step's logits agree
with ``Engine``'s contiguous-cache forward on the same chip. The same call
then serves the HYBRID block (``models/granite_hybrid.py``:
granite-4.0-h-micro's widths, one period of its ten layers: nine Mamba-2
layers that keep a state a slot and one attention layer over packed rows)
through the same entry points, and fails unless the one-token state update
(the ``ssm_state_update`` kernel, compiled by Mosaic) and the chunk scan
give the same logits for the same tokens; and the NEMOTRON-H block
(``models/nemotron_h.py``: NVIDIA-Nemotron-3-Nano-30B-A3B's widths, the
first seven of its 52 layers, 16 of 128 experts held: Mamba-2 in eight
groups, ungated relu² experts through the grouped product, attention over
two key heads) the same way, no routed pair dropped; and the EXAONE-MoE
block (``models/exaone_moe.py``: K-EXAONE-236B-A23B's widths, two layers,
one over a WINDOW of 128 keys whose rows live in a ring a slot and one over
every key, 16 of 128 experts held, an eighth of the vocabulary): the window
build of the block walk at its chunk shape against its decode shape, over a
walk long enough to pass the window and to wrap the ring; and the
SMALLTHINKER block (the same class with four things read otherwise from its
configuration: SmallThinker-21BA3B's widths, one full layer and one over a
window of 4,096, 28 query heads on 4 key heads, all 64 ReGLU experts routed
from the layer's input) the same way; and the LFM2-MoE block (the same
class with a third kind of operator: LFM2-24B-A2B's widths, its two dense
conv layers and one period ``full, conv, conv, conv``, 8 of 64 experts held:
the gated short convolution's one-token update, the ``short_conv_update``
kernel compiled by Mosaic, against its chunk shape, a slot's rows chained
through the window); and the EVABYTE block (``models/evabyte.py``:
EvaByte 6.5B's widths, two of its 32 layers, the whole byte vocabulary
under all eight prediction heads: EVA attention, whose every layer keeps a
ring of its aligned window's rows a slot AND one pooled row a chunk of 16
in the block arenas): the two EVA builds of the block walk and the
producer of the summaries at their chunk shape against their decode shape,
over a walk that passes the window boundary at 2,048, so that the decode
step reads 128 summaries that the other program pooled. In all six the
chunk shape takes every walked prompt several rows of the prefill block a
step, every prompt is held to the tolerance, and where the model routes both
programs' routers are on record: a prompt is left out only where its token
was routed to other experts at a shown tie (``ROUTER_TIE``).

``--chips 4`` (a four-chip host) runs only the tensor-parallel phase:
Qwen3-8B over ``make_mesh({"tp": 4})`` through ``BatchEngine``, once in
``mode="dist"`` (AG-GEMM / GEMM-RS over ICI) and once in ``mode="xla"`` on
the same weights, compared numerically; its last line also carries
``collectives``, each mode's ``stats_snapshot()["collectives"]``.

Every line of standard output is one JSON object. The last one is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
on any failure the exit code is non-zero and that line is not printed.
Seconds printed here are set-up and wall records of this run, not
performance results.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import faulthandler
import functools
import json
import sys
import time

# Smoke geometry. Widths, depth, vocabulary and max_length are the presets'
# own; nothing here shrinks the model (block_n is Engine's default).
# tests/test_chip_smoke.py drives the same functions with a tiny dict of its
# own on the CPU.
ONE_CHIP = dict(
    model="qwen3-1.7b", interpret=False, block_n=256, seed=0,
    n_slots=8, block_size=16, prefill_chunk=64,
    n_requests=12, prompt_range=(200, 1500), new_tokens=64,
    ref_len=320,        # two requests share this length (Engine batch)
    prefix_len=512,     # second wave shares this much of a finished prompt
)
FOUR_CHIPS = dict(
    model="qwen3-8b", interpret=False, block_n=256, seed=0,
    n_slots=8, block_size=16, prefill_chunk=64,
    n_requests=10, prompt_range=(200, 1500), new_tokens=64, ref_len=320,
)

# The hybrid phase: the published widths and vocabulary, ONE period of the
# published layer pattern (2.3 GB of weights beside 8 slots of state).
HYBRID = dict(
    config="GraniteHybridConfig",
    overrides=dict(layer_types=("mamba",) * 5 + ("attention",)
                   + ("mamba",) * 4),
    interpret=False, paged_attn="fused", seed=0, n_slots=8, block_size=16,
    prefill_chunk=64, n_requests=6, prompt_range=(100, 400), new_tokens=16,
    walk_len=80,        # tokens fed one at a time through the kernel
)

# The same phase over the Nemotron-H block: the published widths and
# vocabulary, the pattern's first seven layers (all three kinds), one chip's
# share of the experts (2.8 GB of weights).
NEMOTRON_H = dict(HYBRID, config="NemotronHConfig",
                  overrides=dict(pattern="MEMEM*E", experts_held=16))

# And over the EXAONE-MoE block: the published widths, one window layer and
# one full layer (both with experts), one chip's share of the experts and of
# the vocabulary (3.5 GB of weights). The walk passes the window (128) and
# wraps the ring (576 lines at a prefill block of 7 rows of 64).
EXAONE_MOE = dict(HYBRID, config="ExaoneMoeConfig",
                  overrides=dict(
                      layer_types=("sliding_attention", "full_attention"),
                      sliding_windows=(128, 0),
                      mlp_layer_types=("sparse", "sparse"),
                      experts_held=16, vocab_size=19_200),
                  prompt_range=(650, 900), walk_len=640)

# And over the SmallThinker block, which the same class serves with four
# things read otherwise from its configuration: the published widths and
# vocabulary, one full layer (no position embedding) and one window layer
# (4,096, rope), 28 query heads on 4 key heads with no QK norm, all 64 ReGLU
# experts routed from the layer's input (3.2 GB of weights). The walk passes
# the window and wraps the ring (4,544 lines at a prefill block of 7 rows of
# 64); its rows are attention's alone, so no second comparison is asked.
# Its routers read the un-normed stream: the table is scaled to unit entries,
# as a trained one has (at ``init``'s 1 / sqrt(d) the first layers' logits
# lie within 0.05 of each other and every choice is a ``ROUTER_TIE``).
SMALLTHINKER = dict(HYBRID, config="ExaoneMoeConfig.smallthinker",
                    unit_embedding=True,
                    overrides=dict(
                        layer_types=("full_attention", "sliding_attention"),
                        sliding_windows=(0, 4096),
                        mlp_layer_types=("sparse", "sparse")),
                    prompt_range=(4700, 5000), walk_len=4640)

# And over the LFM2-MoE block, the same class with a third kind of operator:
# the published widths and vocabulary, the two dense conv layers and one
# period (attention with rope and QK norm over two key heads to a row, then
# three conv layers), one chip's share of the experts (1.5 GB of weights).
# The pool's per-slot state is the ``conv`` arena alone.
LFM2_MOE = dict(HYBRID, config="Lfm2MoeConfig",
                overrides=dict(
                    layer_types=("conv", "conv", "full_attention", "conv",
                                 "conv", "conv"),
                    experts_held=8))

# And over the EvaByte block (a class of its own): the published widths, two
# layers, the byte vocabulary under eight heads (0.83 GB of weights). The
# walk passes the first window boundary (2,048): past it a query reads its
# own window from position 2,048 on and the first window through 128
# summaries, pooled chunk by chunk by the decode-shaped step in one program
# and four to a row by the chunk shape in the other.
EVABYTE = dict(HYBRID, config="EvaByteConfig", overrides=dict(n_layers=2),
               prompt_range=(2300, 2600), walk_len=2200)

# Largest |difference| of two logit rows over the largest |reference logit|.
# bf16 keeps 8 mantissa bits (2^-8 per rounded op); over 28-36 layers of
# ~8 rounded ops that accumulates to a few percent of the logit scale
# between two correct programs that tile or order the same sums
# differently, while a wrong block, mask or rope position moves logits by
# their own scale (ratio near 1). float32 (the CPU test) gets 1e-4.
TOL_BF16 = 0.1
TOL_F32 = 1e-4
# One program over the same tokens, its prefill rows dealt two ways: the
# hand-over from row to row is bit for bit what the arenas' round trip gives
# (0.0 in every arena and logit on the chip, PR 40).
DEAL_TOL = 1e-4
# A router's margin is its topk-th largest biased score less the next one
# (sigmoid scores). Two correct programs whose hidden states differ by their
# bfloat16 rounding choose differently where it is small, and a token so
# routed is compared with another function of its input: the one case in
# which a prompt is left out of a comparison, by the run's own record
# (``routing_difference``). On the chip (PR 40, the Nemotron-H block, chunk
# shapes against one-token shapes, 66 compared tokens x layers under each of
# the parent's and this PR's programs): rounding alone moves a score by up
# to 1.34e-3 (median 4.2e-4), and the three tokens it routed apart had
# margins of 7e-5 to 6.8e-4 and sides 2.7e-4 to 9.9e-4 apart; the next
# token after one of them, its state off by one expert and not by rounding,
# read 5.7e-3 apart; the median margin is 6.8e-3.
ROUTER_TIE = 2.5e-3
RUN_LIMIT_S = 1150


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class _CacheEvents:
    """Counts JAX's persistent-compilation-cache hits and misses."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"cache_hits": self.hits, "cache_misses": self.misses}

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_listener(self._on_event)


def device_info(devices) -> dict:
    import jax

    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices())}


def make_prompts(rng, vocab: int, geo: dict) -> list[list[int]]:
    """``n_requests`` prompts from ``rng``: the first two of ``ref_len``
    tokens (the pair compared with ``Engine``), the third at the top of the
    range (so a finished prompt always covers the shared prefix), the rest
    uniform over ``prompt_range``."""
    lo, hi = geo["prompt_range"]
    lens = [geo["ref_len"], geo["ref_len"], hi]
    lens += [int(x) for x in rng.integers(lo, hi + 1,
                                          geo["n_requests"] - len(lens))]
    return [[int(t) for t in rng.integers(0, vocab, n)] for n in lens]


def under_trace(fn):
    """``fn()`` evaluated while a jax trace is active — the state in which
    the served steps consult the tuner (it then never times)."""
    import jax

    box = []
    jax.eval_shape(lambda: box.append(fn()) or 0)
    return box[0]


def peak_bytes(devices) -> list:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


# -- numeric references ------------------------------------------------------


@contextlib.contextmanager
def routing_recorded():
    """While open, a step traced also tells the host what the router of
    every expert layer saw: ``HeldExpertsMoE.routed`` is wrapped so that the
    ``topk + 1`` largest biased scores of every row of the flat token batch,
    and their experts, leave through ``jax.debug.callback`` beside the
    layer's index; nothing the step computes changes. Yields ``at(rows) ->
    {layer: (scores, experts)}``, both (len(rows), topk + 1), of the LAST
    step run."""
    import jax
    import numpy as np

    from triton_distributed_tpu.layers.moe_mlp import HeldExpertsMoE

    seen: dict = {}
    inner = HeldExpertsMoE.routed

    def routed(self, params, x, valid=None, route_from=None, *,
               layer_idx=None, **kw):
        by, _ = self.scores(                        # as ``route`` has them
            params["router"], params.get("bias"),
            x if route_from is None else route_from)
        jax.debug.callback(
            lambda layer, *top: seen.__setitem__(
                int(layer), tuple(np.asarray(a) for a in top)),
            -1 if layer_idx is None else layer_idx,
            *jax.lax.top_k(by, self.topk + 1))
        return inner(self, params, x, valid, route_from,
                     layer_idx=layer_idx, **kw)

    def at(rows):
        jax.effects_barrier()
        return {layer: tuple(a[np.asarray(rows)] for a in top)
                for layer, top in sorted(seen.items())}

    HeldExpertsMoE.routed = routed
    try:
        yield at
    finally:
        HeldExpertsMoE.routed = inner


def paged_logits(be, prompts, next_tok, rows=None, routing=None):
    """Logits of the PAGED programs on ``be``'s own pool for equal-length
    ``prompts``: chunked prefill through ``Engine._make_sm(paged="prefill")``
    (the mixed step's forward, which returns logits where the serving step
    returns sampled tokens; its token batch the served one, an idle decode
    block beside the prompts' rows of the prefill block, every prompt
    ``rows`` consecutive rows a step, by default its share of the block: a
    model with per-slot state chains them), then one
    decode-shaped step
    (``paged="decode"``) feeding ``next_tok``; the pool's state goes in and
    comes back whole, donated, as in the serving steps. Returns float32
    ``(prefill_last_position_logits, decode_logits)``, one row a prompt;
    with ``routing`` (``routing_recorded``'s ``at``, open around the call)
    a third entry, what the routers saw at those two tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eng, pool = be.engine, be.pool
    n, chunk, n_p, plen = be.n_slots, be.prefill_chunk, len(prompts), \
        len(prompts[0])
    kw = dict(paged_attn=be.paged_attn, state_specs=pool.specs)
    pre = jax.jit(eng._make_sm(eng.prefill_mode, paged="prefill", **kw),
                  donate_argnums=(2,))
    dec = jax.jit(eng._make_sm(eng.decode_mode, paged="decode", **kw),
                  donate_argnums=(2,))
    sids = [f"smoke-ref-{i}" for i in range(n_p)]
    for sid in sids:
        check(pool.ensure(sid, plen + 1), "pool could not fund the "
              "reference sequences on an idle engine")
    try:
        tables = jnp.asarray(pool.padded_tables(sids + [None] * (n - n_p)))
        live = np.arange(n) < n_p
        mask = jnp.asarray(live)
        toks = np.asarray(prompts, np.int32)
        # the block's rows as the host deals them: every prompt its share
        # of consecutive rows, each but the last full, named by (slot, cache
        # length before the row, live tokens); a dead row names no slot
        rows = rows or be.prefill_rows // n_p
        check(1 <= rows <= be.prefill_rows // n_p, f"{n_p} reference "
              f"prompts of {rows} rows each do not fit a prefill block of "
              f"{be.prefill_rows} rows")
        for off in range(0, plen, rows * chunk):
            take = min(rows * chunk, plen - off)
            tok = np.zeros((n,), np.int32)
            block = np.zeros((be.prefill_rows, chunk), np.int32)
            dealt = np.tile(np.int32([-1, 0, 0]), (be.prefill_rows, 1))
            ends = list(range(n_p))   # a prompt's last token in the flat
            if take == 1:             # batch: one rides the decode block
                tok[:n_p] = toks[:, off]
            else:
                k = 0
                for i in range(n_p):
                    for at in range(off, off + take, chunk):
                        n_k = min(chunk, off + take - at)
                        dealt[k] = i, at, n_k
                        block[k, :n_k] = toks[i, at:at + n_k]
                        ends[i] = n + chunk * k + n_k - 1
                        k += 1
            pre_logits, _, pool.state = pre(
                eng.params,
                (jnp.asarray(tok), jnp.asarray(block), jnp.asarray(dealt)),
                pool.state,
                jnp.asarray(np.where(live, off, 0).astype(np.int32)),
                tables, mask,
                jnp.asarray(np.where(live, take, 0).astype(np.int32)))
        seen = [routing(ends)] if routing else []
        ids = np.zeros((n, 1), np.int32)
        ids[:n_p, 0] = next_tok
        dec_logits, _, pool.state = dec(
            eng.params, jnp.asarray(ids), pool.state,
            jnp.asarray(np.where(live, plen, 0).astype(np.int32)),
            tables, mask)
        seen += [routing(range(n_p))] if routing else []
    finally:
        for sid in sids:
            pool.release(sid)
    return (np.asarray(pre_logits, np.float32)[:n_p],
            np.asarray(dec_logits, np.float32)[:n_p]) + (
                (seen,) if routing else ())


def contiguous_logits(engine, prompts, next_tok):
    """The same two logit rows from ``Engine``'s contiguous-cache forward
    (``prefill`` of the whole prompt, then ``decode_step``)."""
    import jax.numpy as jnp
    import numpy as np

    ids = jnp.asarray(prompts, jnp.int32)
    pre_logits, kv = engine.prefill(ids, engine.new_cache(len(prompts)))
    dec_logits, _ = engine.decode_step(jnp.asarray(next_tok, jnp.int32), kv)
    return (np.asarray(pre_logits, np.float32),
            np.asarray(dec_logits, np.float32))


def routing_difference(a: dict, b: dict, i: int) -> dict | None:
    """The first expert layer, in depth order, whose router chose other
    experts for token ``i`` on side ``a`` than on side ``b`` (two records of
    ``routing_recorded``), or None where every layer chose alike: the
    ``topk + 1`` best experts of each side, each side's margin, the largest
    distance between the sides' sorted scores, and ``tie``: all three under
    ``ROUTER_TIE``, so the routers saw the same scores and the boundary lay
    within what the sides differ by."""
    import numpy as np

    for layer in a:
        (sa, ea), (sb, eb) = a[layer], b[layer]
        if set(ea[i, :-1]) != set(eb[i, :-1]):
            margin = [float(s[i, -2] - s[i, -1]) for s in (sa, sb)]
            shift = float(np.abs(sa[i] - sb[i]).max())
            return {"layer": layer, "experts": [ea[i].tolist(),
                                                eb[i].tolist()],
                    "margin": margin, "shift": shift,
                    "tie": max(*margin, shift) < ROUTER_TIE}
    return None


def compare_logits(what: str, got, ref, tol: float) -> None:
    """``got`` and ``ref``: (prefill logits, decode logits), one row a
    prompt, every row held to ``tol``. Where BOTH bring their routers'
    record as a third entry, a prompt whose compared token was routed
    differently at a shown tie (``routing_difference``) is left out of that
    token's comparison, and the line says so."""
    import numpy as np

    out = {"phase": "numeric", "compared": what, "tolerance": tol}
    judged = {}
    for at, name in enumerate(("prefill", "decode")):
        g, r = got[at], ref[at]
        check(g.shape == r.shape, f"{what}: {name} logits shape {g.shape} "
              f"!= reference {r.shape}")
        check(bool(np.isfinite(g).all() and np.isfinite(r).all()),
              f"{what}: non-finite {name} logits")
        out[f"{name}_max_abs_diff"] = float(np.abs(g - r).max())
        out[f"{name}_max_abs_ref"] = float(np.abs(r).max())
        by_prompt = np.abs(g - r).max(-1) / max(out[f"{name}_max_abs_ref"],
                                                1e-30)
        out[f"{name}_rel"] = float(by_prompt.max())
        out[f"{name}_rel_by_prompt"] = by_prompt.tolist()
        out[f"{name}_argmax_equal"] = int(
            (g.argmax(-1) == r.argmax(-1)).sum())
        held = np.ones(len(g), bool)
        if len(got) > 2 and len(ref) > 2:
            out[f"{name}_routed_apart"] = apart = [
                dict(d, prompt=i) for i in range(len(g))
                if (d := routing_difference(got[2][at], ref[2][at], i))]
            held[[d["prompt"] for d in apart if d["tie"]]] = False
        judged[name] = float(by_prompt[held].max(initial=0.0))
    emit(**out)
    for name, rel in judged.items():
        check(rel <= tol,
              f"{what}: {name} logits differ by {rel:.4g} of the "
              f"reference scale (tolerance {tol})")


def logit_tolerance(config) -> float:
    import jax.numpy as jnp

    return TOL_BF16 if jnp.dtype(config.dtype).itemsize < 4 else TOL_F32


def token_agreement(a: list[int], b: list[int]) -> int:
    """Length of the common greedy prefix (printed, never gated: with bf16
    and random weights greedy tokens flip on near-ties)."""
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


# -- one chip: Fleet -> BatchEngine -> fused paged attention -----------------


def drain_fleet(fleet, caches: _CacheEvents, max_steps: int = 50_000) -> dict:
    """Step ``fleet`` until idle. Returns step/wall counts and, for the
    first call of each compiled step, its wall seconds (trace, compile and
    dispatch) with the compile-cache counters at that point."""
    eng = fleet.replicas[0].engine
    first: dict = {}
    steps = idle = 0
    t_all = time.perf_counter()
    while steps < max_steps:
        # The call that TRACES a step is its first (the engine counts a
        # step a call later, when it reads the step's tokens).
        before = dict(eng.trace_counts)
        c0 = caches.snapshot()
        t0 = time.perf_counter()
        busy = fleet.step()
        dt = time.perf_counter() - t0
        for kind, key in (("mixed", "prefill"), ("decode", "decode")):
            if kind not in first and eng.trace_counts[key] > before[key]:
                c1 = caches.snapshot()
                first[kind] = {
                    "seconds": round(dt, 3),
                    "cache_hits": c1["cache_hits"] - c0["cache_hits"],
                    "cache_misses": c1["cache_misses"] - c0["cache_misses"]}
        steps += 1
        if busy:
            idle = 0
        elif not fleet.pending and all(rep.empty or rep.state == "DEAD"
                                       for rep in fleet.replicas):
            break
        else:
            idle += 1
            check(idle <= 1000, "fleet made no progress for 1000 idle steps")
    check(steps < max_steps, f"fleet still busy after {max_steps} steps")
    return {"steps": steps, "wall_s": time.perf_counter() - t_all,
            "first_call": first}


def check_fleet(fleet, rids: list, n_new: int, *,
                prefix_hit: bool = True) -> None:
    """The checks the replica error boundary cannot swallow: every request
    in ``rids`` finished with exactly ``n_new`` tokens on a healthy fleet
    (and, with ``prefix_hit``, the prefix cache was hit)."""
    rep = fleet.replicas[0]
    eng = rep.engine
    fm = fleet.metrics.as_dict()
    bad = [r for r in fleet.replicas if r.state != "HEALTHY"]
    if bad or fm.get("replica_step_failures", 0.0):
        errors = [r.last_error for r in fleet.replicas if r.last_error]
        raise SmokeFailure(
            f"replica left HEALTHY or a step failed: states "
            f"{[r.state for r in fleet.replicas]}, step failures "
            f"{fm.get('replica_step_failures', 0.0)}, first recorded "
            f"exception: {errors[0] if errors else None}; state log "
            f"{fleet.state_log[:3]}")
    failed = fleet.failed
    check(not failed and not fm.get("requests_failed", 0.0)
          and not eng.metrics.counters.get("requests_failed", 0.0),
          f"requests failed: "
          f"{ {k: getattr(r, 'error', None) for k, r in failed.items()} }")
    fin = fleet.finished
    for rid in rids:
        check(rid in fin, f"request {rid} did not finish")
        check(len(fin[rid].output) == n_new,
              f"request {rid} produced {len(fin[rid].output)} tokens, "
              f"asked for {n_new}")
    check(eng.trace_counts == {"decode": 1, "prefill": 1},
          f"trace_counts {eng.trace_counts} != {{1, 1}} (a step retraced)")
    fleet.check_invariants()      # every replica pool's invariants too
    check(not prefix_hit
          or eng.metrics.counters.get("prefix_hits", 0.0) > 0,
          "the shared-prefix wave produced no prefix-cache hit")


def run_one_chip(devices, geo: dict, caches: _CacheEvents) -> None:
    import jax
    import numpy as np

    from triton_distributed_tpu.kernels.paged_attention import (
        tuned_paged_tile,
    )
    from triton_distributed_tpu.models.config import ModelConfig
    from triton_distributed_tpu.models.engine import Engine
    from triton_distributed_tpu.runtime.mesh import make_mesh
    from triton_distributed_tpu.serving.fleet import Fleet
    from triton_distributed_tpu.tools.aot import enable_xla_compilation_cache

    cache_path = enable_xla_compilation_cache()
    emit(phase="device", compile_cache_dir=cache_path, **device_info(devices))

    cfg = ModelConfig.from_name(geo["model"])
    mesh = make_mesh({"tp": 1}, devices=devices[:1], set_default=False)
    t0 = time.perf_counter()
    engine = Engine(cfg, mesh=mesh, mode="dist",
                    key=jax.random.PRNGKey(geo["seed"]),
                    block_n=geo["block_n"], interpret=geo["interpret"])
    jax.block_until_ready(engine.params)
    t_params = time.perf_counter() - t0

    # The served steps reach ``paged_attention`` only under the jit trace,
    # where the tuner never times: it bakes a cached winner or the
    # heuristic default. So the decode shape (L=1, six kv-tile candidates)
    # is tuned here, eagerly, before the first trace — what the tuner's own
    # trace-fallback warning asks of a caller. The chunk shape (L>1) is NOT:
    # a cold eager tune of its 42 (kv-tile, q-tile) candidates took about
    # twenty minutes on the chip and chose the heuristic default (PR 22,
    # ROADMAP S5), so it is only asked, under a trace as the step asks it.
    g = cfg.n_heads // cfg.n_kv_heads
    max_blocks = -(-cfg.max_length // geo["block_size"])
    tile_args = (geo["block_size"], cfg.n_kv_heads, cfg.head_dim, max_blocks,
                 str(np.dtype(cfg.dtype)))
    t0 = time.perf_counter()
    decode_cfg = tuned_paged_tile(*tile_args, L=1, g=g)
    t_tune = time.perf_counter() - t0
    mixed_cfg = under_trace(lambda: tuned_paged_tile(
        *tile_args, L=geo["prefill_chunk"], g=g))
    emit(phase="autotune", seconds=round(t_tune, 3),
         decode_tile_qtile=list(decode_cfg), decode_tuned_eagerly=True,
         mixed_tile_qtile=list(mixed_cfg), mixed_tuned_eagerly=False)

    t0 = time.perf_counter()
    fleet = Fleet.build(engine, n_replicas=1, n_slots=geo["n_slots"],
                        block_size=geo["block_size"],
                        prefill_chunk=geo["prefill_chunk"],
                        paged_attn="fused")
    be = fleet.replicas[0].engine
    jax.block_until_ready(be.pool.state)
    t_pool = time.perf_counter() - t0
    emit(phase="build", model=geo["model"], n_layers=cfg.n_layers,
         d_model=cfg.d_model, vocab=cfg.vocab_size,
         max_length=cfg.max_length, params_s=round(t_params, 3),
         pool_s=round(t_pool, 3), pool_blocks=be.pool.n_blocks,
         kv_dtype=be.pool.kv_dtype.name, paged_attn=be.paged_attn)

    rng = np.random.default_rng(geo["seed"])
    prompts = make_prompts(rng, cfg.vocab_size, geo)
    rids = [fleet.submit(p, geo["new_tokens"]) for p in prompts]
    wave1 = drain_fleet(fleet, caches)
    # Second wave: two requests sharing a prefix with a request that has
    # FINISHED (the radix cache inserts at completion).
    donor = prompts[2]
    check(len(donor) >= geo["prefix_len"], "donor prompt shorter than the "
          "shared prefix")
    lo, hi = geo["prompt_range"]
    for _ in range(2):
        tail = rng.integers(0, cfg.vocab_size, int(rng.integers(lo, hi) // 4))
        rids.append(fleet.submit(
            donor[:geo["prefix_len"]] + [int(t) for t in tail],
            geo["new_tokens"]))
    wave2 = drain_fleet(fleet, caches)
    emit(phase="first_call", **wave1["first_call"], **caches.snapshot())
    tokens = sum(len(r.output) for r in fleet.finished.values())
    emit(phase="serve", requests=len(rids), steps=wave1["steps"]
         + wave2["steps"], tokens_generated=tokens,
         wall_s=round(wave1["wall_s"] + wave2["wall_s"], 3),
         prefix_hits=be.metrics.counters.get("prefix_hits", 0.0),
         prefix_cached_tokens=be.metrics.counters.get(
             "prefix_cached_tokens", 0.0),
         preemptions=be.metrics.counters.get("preemptions", 0.0),
         trace_counts=be.trace_counts,
         replica_states=[r.state for r in fleet.replicas])
    check_fleet(fleet, rids, geo["new_tokens"])

    # Numbers, not argmax: paged step against the contiguous-cache forward.
    ref_prompts = prompts[:2]
    next_tok = [p[0] for p in ref_prompts]
    compare_logits("paged mixed+decode step vs Engine contiguous cache",
                   paged_logits(be, ref_prompts, next_tok),
                   contiguous_logits(engine, ref_prompts, next_tok),
                   logit_tolerance(cfg))
    be.pool.check_invariants()
    served = [list(fleet.finished[r].output) for r in rids[:2]]
    alone = np.asarray(engine.serve(np.asarray(ref_prompts, np.int32),
                                    geo["new_tokens"])).tolist()
    emit(phase="greedy_agreement", gated=False, of=geo["new_tokens"],
         common_prefix=[token_agreement(s, a)
                        for s, a in zip(served, alone)])
    emit(phase="memory", peak_bytes_in_use=peak_bytes(devices[:1]),
         **caches.snapshot())


# -- one chip: the hybrid block (per-slot state beside paged rows) ------------


def decode_walk_logits(be, prompts, next_tok, routing=None):
    """What ``paged_logits`` gives, with every token of the prompts fed ONE
    AT A TIME through the decode-shaped step: the state advances through
    the one-token update's kernel alone, never through the chunk scan."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eng, pool = be.engine, be.pool
    n, n_p, plen = be.n_slots, len(prompts), len(prompts[0])
    dec = jax.jit(eng._make_sm(eng.decode_mode, paged="decode",
                               paged_attn=be.paged_attn,
                               state_specs=pool.specs), donate_argnums=(2,))
    sids = [f"smoke-walk-{i}" for i in range(n_p)]
    for sid in sids:
        check(pool.ensure(sid, plen + 1), "pool could not fund the walked "
              "sequences on an idle engine")
    try:
        tables = jnp.asarray(pool.padded_tables(sids + [None] * (n - n_p)))
        live = np.arange(n) < n_p
        toks = np.concatenate([np.asarray(prompts, np.int32),
                               np.asarray(next_tok, np.int32)[:, None]], 1)
        # the last two steps' logits are all that is read: a walk of
        # thousands of steps must not keep a row of logits a step
        rows, seen = collections.deque(maxlen=2), []
        for pos in range(plen + 1):
            ids = np.zeros((n, 1), np.int32)
            ids[:n_p, 0] = toks[:, pos]
            logits, _, pool.state = dec(
                eng.params, jnp.asarray(ids), pool.state,
                jnp.asarray(np.where(live, pos, 0).astype(np.int32)),
                tables, jnp.asarray(live))
            rows.append(logits)
            if routing and pos >= plen - 1:
                seen.append(routing(range(n_p)))
    finally:
        for sid in sids:
            pool.release(sid)
    return (np.asarray(rows[-2], np.float32)[:n_p],
            np.asarray(rows[-1], np.float32)[:n_p]) + (
                (seen,) if routing else ())


def run_hybrid(devices, geo: dict, caches: _CacheEvents) -> None:
    import jax
    import numpy as np

    from triton_distributed_tpu.models import config as configs
    from triton_distributed_tpu.models.engine import Engine
    from triton_distributed_tpu.runtime.mesh import make_mesh
    from triton_distributed_tpu.serving.fleet import Fleet

    # a configuration class, or a preset of one (``Class.preset``)
    cfg = functools.reduce(getattr, geo["config"].split("."),
                           configs)(**geo["overrides"])
    mesh = make_mesh({"tp": 1}, devices=devices[:1], set_default=False)
    engine = Engine(cfg, mesh=mesh, mode="dist",
                    key=jax.random.PRNGKey(geo["seed"]),
                    interpret=geo["interpret"])
    if geo.get("unit_embedding"):
        engine.params = dict(engine.params, embed=engine.params["embed"]
                             * cfg.d_model ** 0.5)
    fleet = Fleet.build(engine, n_replicas=1, n_slots=geo["n_slots"],
                        block_size=geo["block_size"],
                        prefill_chunk=geo["prefill_chunk"],
                        paged_attn=geo["paged_attn"])
    be = fleet.replicas[0].engine
    jax.block_until_ready(be.pool.state)
    state_layers = getattr(cfg, "n_state_layers", 0)
    window_layers = getattr(cfg, "n_window_layers", 0)
    emit(phase="hybrid_build", model=cfg.model_name, n_layers=cfg.n_layers,
         state_layers=state_layers, cache_layers=cfg.n_cache_layers,
         window_layers=window_layers, d_model=cfg.d_model,
         vocab=cfg.vocab_size, kv_rows=list(be.pool.state.kv.shape),
         slot_state_bytes=be.pool.slot_state_bytes,
         window=be.pool.geometry().get("window"))
    check(be.prefix_cache is None, "a model with per-slot state or window "
          "layers was given a prefix cache")

    rng = np.random.default_rng(geo["seed"])
    lo, hi = geo["prompt_range"]
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size,
                                             int(rng.integers(lo, hi)))]
               for _ in range(geo["n_requests"])]
    rids = [fleet.submit(p, geo["new_tokens"]) for p in prompts]
    wave = drain_fleet(fleet, caches)
    check_fleet(fleet, rids, geo["new_tokens"], prefix_hit=False)
    c = be.metrics.counters
    tokens = sum(len(p) for p in prompts) \
        + geo["n_requests"] * (geo["new_tokens"] - 1)
    # a recurrence's state (or none), or a convolution's window alone
    kept = "conv" if set(be.pool.slot_state) == {"conv"} else "ssm"
    emit(phase="hybrid_serve", requests=len(rids), steps=wave["steps"],
         wall_s=round(wave["wall_s"], 3), first_call=wave["first_call"],
         **{f"{kept}_{k}": c.get(f"{kept}_{k}", 0.0)
            for k in ("rows_advanced", "states_reset")},
         kv_rows_appended=c.get("kv_rows_appended", 0.0),
         **{k: c[k] for k in ("eva_summaries_written", "eva_windows_opened")
            if k in c},
         prefill_rows_extra=c.get("prefill_rows_extra", 0.0),
         moe_pairs_held=c.get("moe_pairs_held"),
         moe_dropped_pairs=c.get("moe_dropped_pairs"),
         trace_counts=be.trace_counts)
    # a row a token in every layer that keeps rows, by the context or by
    # the window (where a row of the arenas stands for several tokens, an
    # EVA layer's summaries, the ring's rows are the ones a token appends)
    summarised = getattr(cfg, "kv_row_tokens", 1) != 1
    want = {"kv_rows_appended": tokens * (
        window_layers + (0 if summarised else cfg.n_cache_layers))}
    if summarised:
        want["eva_summaries_written"] = cfg.n_cache_layers * sum(
            (len(p) + geo["new_tokens"] - 1) // cfg.kv_row_tokens
            for p in prompts)
    if state_layers:
        want.update({f"{kept}_rows_advanced": tokens * state_layers,
                     f"{kept}_states_reset": geo["n_requests"]})
    check(all(c.get(k) == n for k, n in want.items()),
          f"the step's counts do not add up to {tokens} tokens of "
          f"{geo['n_requests']} requests: {want}")
    check(not c.get("moe_dropped_pairs"), "a routed pair was dropped")
    check(c.get("prefill_rows_extra", 0) > 0, "no prompt took a second "
          "row of the prefill block: the deal did not engage")

    # Numbers: the same tokens through the chunk shapes (chunked prefill,
    # every prompt several rows of the block a step, then one decode step)
    # and through the one-token shapes alone. Two programs: where the model
    # routes, the routers' record of both says whether a token that differs
    # was routed apart at a tie. And, where a slot keeps a state that its
    # rows hand on, the same tokens with one row a step, through the arenas:
    # one program, so nothing is routed apart and a state handed on wrongly
    # has nowhere to hide.
    walked = [p[:geo["walk_len"]] for p in prompts[:min(3, be.n_slots)]]
    next_tok = [p[0] for p in walked]
    with routing_recorded() as routing:
        dealt = paged_logits(be, walked, next_tok, routing=routing)
        walk = decode_walk_logits(be, walked, next_tok, routing=routing)
        one_row = be.pool.slot_state and paged_logits(be, walked, next_tok,
                                                      rows=1)
    compare_logits(f"{geo['config']}: chunked prefill + decode step vs "
                   f"the same tokens one at a time",
                   dealt, walk, logit_tolerance(cfg))
    if one_row:
        compare_logits(f"{geo['config']}: a prompt's rows dealt "
                       f"{be.prefill_rows // len(walked)} a step vs one a "
                       f"step", dealt[:2], one_row, DEAL_TOL)
    be.pool.check_invariants()
    emit(phase="hybrid_memory", peak_bytes_in_use=peak_bytes(devices[:1]))


def run_served_blocks(devices, geo: dict, caches: _CacheEvents) -> None:
    """The one-chip smoke: the dense model, then (its buffers dropped) the
    hybrid block, then the Nemotron-H block, then the EXAONE-MoE block, then
    the SmallThinker block, then the LFM2-MoE block, then the EvaByte
    block."""
    import gc

    run_one_chip(devices, geo, caches)
    for block in (HYBRID, NEMOTRON_H, EXAONE_MOE, SMALLTHINKER, LFM2_MOE,
                  EVABYTE):
        gc.collect()
        run_hybrid(devices, block, caches)


# -- four chips: TP=4 dist against xla ---------------------------------------


def run_four_chips(devices, geo: dict, caches: _CacheEvents) -> dict:
    import jax
    import numpy as np

    from triton_distributed_tpu.models.config import ModelConfig
    from triton_distributed_tpu.models.engine import Engine
    from triton_distributed_tpu.runtime.mesh import make_mesh
    from triton_distributed_tpu.serving.batch_engine import BatchEngine
    from triton_distributed_tpu.tools.aot import enable_xla_compilation_cache

    cache_path = enable_xla_compilation_cache()
    emit(phase="device", compile_cache_dir=cache_path, **device_info(devices))
    cfg = ModelConfig.from_name(geo["model"])
    mesh = make_mesh({"tp": len(devices)}, devices=devices, set_default=False)
    t0 = time.perf_counter()
    engines = {"dist": Engine(cfg, mesh=mesh, mode="dist",
                              key=jax.random.PRNGKey(geo["seed"]),
                              block_n=geo["block_n"],
                              interpret=geo["interpret"])}
    jax.block_until_ready(engines["dist"].params)
    engines["xla"] = Engine(cfg, mesh=mesh, mode="xla",
                            params=engines["dist"].params,
                            block_n=geo["block_n"],
                            interpret=geo["interpret"])
    emit(phase="build", model=geo["model"], n_layers=cfg.n_layers,
         d_model=cfg.d_model, vocab=cfg.vocab_size, tp=len(devices),
         params_s=round(time.perf_counter() - t0, 3),
         peak_bytes_after_params=peak_bytes(devices))

    rng = np.random.default_rng(geo["seed"])
    prompts = make_prompts(rng, cfg.vocab_size, geo)
    ref_prompts = prompts[:2]
    next_tok = [p[0] for p in ref_prompts]
    outputs, logits, collectives = {}, {}, {}
    for mode, engine in engines.items():
        t0 = time.perf_counter()
        # nan_guard: a non-finite logit row quarantines its request, which
        # the failed-requests check below then reports.
        be = BatchEngine(engine, n_slots=geo["n_slots"],
                         block_size=geo["block_size"],
                         prefill_chunk=geo["prefill_chunk"],
                         paged_attn="fused", nan_guard=True)
        rids = [be.submit(p, geo["new_tokens"]) for p in prompts]
        done = be.run(max_steps=50_000)   # a step exception propagates
        check(not be.failed, f"{mode}: requests failed: "
              f"{ {k: r.error for k, r in be.failed.items()} }")
        for rid in rids:
            check(len(done.get(rid, ())) == geo["new_tokens"],
                  f"{mode}: request {rid} produced "
                  f"{len(done.get(rid, ()))} tokens")
        check(be.trace_counts == {"decode": 1, "prefill": 1},
              f"{mode}: trace_counts {be.trace_counts} != {{1, 1}}")
        be.pool.check_invariants()
        outputs[mode] = [done[r] for r in rids]
        logits[mode] = paged_logits(be, ref_prompts, next_tok)
        collectives[mode] = be.stats_snapshot()["collectives"]
        emit(phase="serve", mode=mode, requests=len(rids),
             steps=int(be.metrics.counters.get("prefill_steps", 0.0)
                       + be.metrics.counters.get("decode_steps", 0.0)),
             tokens_generated=sum(len(o) for o in outputs[mode]),
             wall_s=round(time.perf_counter() - t0, 3),
             trace_counts=be.trace_counts, **caches.snapshot())
        del be   # free this mode's pool before the next is built
    compare_logits("TP=4 paged mixed+decode step, mode=dist vs mode=xla",
                   logits["dist"], logits["xla"], logit_tolerance(cfg))
    emit(phase="greedy_agreement", gated=False, of=geo["new_tokens"],
         common_prefix=[token_agreement(a, b) for a, b in
                        zip(outputs["dist"], outputs["xla"])])
    emit(phase="memory", peak_bytes_in_use=peak_bytes(devices),
         **caches.snapshot())
    # A record, not a gate: what the program itself counts an execution
    # of each compiled step moving over the mesh, by mode.
    return {"collectives": collectives}


def smoke(run, devices, geo: dict) -> int:
    """Run one phase. Prints the ``ok`` line (with what the phase returned
    for the record, if anything) and returns 0 only if every check of it
    held; any other exception propagates (non-zero exit, no ``ok``
    line)."""
    caches = _CacheEvents()
    try:
        record = run(devices, geo, caches) or {}
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        caches.close()
    print(json.dumps({"ok": True, "device": device_info(devices), **record}),
          flush=True)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the TP=4 Qwen3-8B dist-vs-xla "
                             "phase (needs a four-chip host)")
    args = parser.parse_args(argv)
    # The contract is 1,200 s: past it, dump every thread's stack and exit
    # non-zero rather than hang on a collective or a compile.
    faulthandler.dump_traceback_later(RUN_LIMIT_S, exit=True,
                                      file=sys.__stderr__)
    try:
        import jax

        devices = jax.devices()
        if devices[0].platform != "tpu" or len(devices) < args.chips:
            print(f"chip_smoke: needs {args.chips} TPU device(s); JAX found "
                  f"{len(devices)} x {devices[0].platform!r}. No CPU or "
                  f"interpreter continuation.", file=sys.stderr)
            return 2
        if args.chips == 4:
            return smoke(run_four_chips, devices[:4], FOUR_CHIPS)
        return smoke(run_served_blocks, devices[:1], ONE_CHIP)
    finally:
        faulthandler.cancel_dump_traceback_later()


if __name__ == "__main__":
    sys.exit(main())
