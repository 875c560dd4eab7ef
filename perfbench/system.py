"""The one module that touches the program under test.

Everything the benchmark takes from ``triton_distributed_tpu`` goes through
``Served``: the entry points a user calls (``make_mesh`` -> ``Engine`` ->
``Fleet.build`` -> ``BatchEngine``), its counters and histograms as they
are, and today's private per-request handle (``fleet._submitted``; a public
one is asked of the ``tracing`` issue in PERF.md). Nothing here names a
model or a cell: the program's configuration object and its parameters come
from the configuration's family (``perfbench/families/``), keyword arguments
of ``Engine`` and ``Fleet.build`` pass through unread.
"""

from __future__ import annotations

import time

COUNTERS = ("decode_steps", "prefill_steps", "decode_rows", "prefill_tokens",
            "preemptions", "prefix_cached_tokens", "prefix_uncached_tokens",
            "tokens_generated", "requests_failed")


def enable_compile_cache() -> str:
    """The repo's one rule (``tools/aot.enable_xla_compilation_cache``):
    ``JAX_COMPILATION_CACHE_DIR`` where it is set, else ``.cache/jax``
    inside the checkout."""
    from triton_distributed_tpu.tools.aot import enable_xla_compilation_cache

    return enable_xla_compilation_cache()


class Served:
    """One configuration, built and ready to take requests."""

    def __init__(self, cfg: dict, family, sizes, seed: int, devices, *,
                 engine_overrides: dict | None = None, phases=None):
        import jax

        from triton_distributed_tpu.models.engine import Engine
        from triton_distributed_tpu.runtime.mesh import make_mesh
        from triton_distributed_tpu.serving.fleet import Fleet

        serve = cfg["serve"]
        n_dev = 1
        for v in serve["mesh"].values():
            n_dev *= v
        mesh = make_mesh(dict(serve["mesh"]), devices=list(devices)[:n_dev],
                         set_default=False)
        ekw = {**serve["engine"], **(engine_overrides or {})}
        t0 = time.monotonic()
        mcfg, params = family.program(cfg, sizes, seed, mesh, ekw)
        jax.block_until_ready(params)
        t1 = time.monotonic()
        self.engine = Engine(mcfg, mesh=mesh, params=params, **ekw)
        self.fleet = Fleet.build(self.engine, **serve["fleet"])
        self.be = self.fleet.replicas[0].engine
        jax.block_until_ready(self.be.pool.state)
        t2 = time.monotonic()
        if phases is not None:
            phases["params_s"] = t1 - t0
            phases["build_s"] = t2 - t1
        self.n_slots = sum(r.engine.n_slots for r in self.fleet.replicas)
        self.n_blocks = self.be.pool.n_blocks
        self.block_size = serve["fleet"].get("block_size", 16)
        self._qw_seen = 0

    # -- requests -----------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int):
        """Returns the program's own request object: ``output`` grows as
        tokens are emitted, ``status`` ends as "ok" or "failed"."""
        rid = self.fleet.submit(prompt, max_new_tokens)
        return self.fleet._submitted[rid]

    def step(self) -> bool:
        return self.fleet.step()

    @staticmethod
    def done(req) -> bool:
        return req.finish_t is not None or req.status == "failed"

    @staticmethod
    def ok(req) -> bool:
        return (req.status != "failed" and req.finish_t is not None
                and len(req.output) == req.max_new_tokens)

    # -- what the program counts ---------------------------------------------

    def counters(self) -> dict:
        out = dict.fromkeys(COUNTERS, 0.0)
        for rep in self.fleet.replicas:
            c = rep.engine.metrics.counters
            for k in COUNTERS:
                out[k] += c.get(k, 0.0)
        return out

    def queue_wait_new(self) -> list:
        """``queue_wait_s`` samples observed since the last call."""
        h = self.be.metrics.histograms.get("queue_wait_s")
        if h is None:
            return []
        n_new = h.count - self._qw_seen
        self._qw_seen = h.count
        samples = list(h.samples)
        return samples[-n_new:] if n_new > 0 else []

    def kv_live_share(self) -> float:
        """Blocks held by running sequences over blocks reserved (blocks
        that only the prefix cache still holds can be reclaimed at once)."""
        pool = self.be.pool
        return (pool.n_used - pool.n_reclaimable) / pool.n_blocks

    def health(self) -> dict:
        fm = self.fleet.metrics.as_dict()
        return {
            "trace_counts": [dict(r.engine.trace_counts)
                             for r in self.fleet.replicas],
            "replica_states": [r.state for r in self.fleet.replicas],
            "replica_step_failures": fm.get("replica_step_failures", 0.0),
            "requests_failed": len(self.fleet.failed),
        }

    @staticmethod
    def sound(health: dict) -> bool:
        return (all(tc == {"decode": 1, "prefill": 1}
                    for tc in health["trace_counts"])
                and all(s == "HEALTHY" for s in health["replica_states"])
                and not health["replica_step_failures"])

    def close(self) -> None:
        """Drop every device buffer of the program (weights, pool, steps)."""
        import gc

        import jax

        self.engine.params = None
        for rep in self.fleet.replicas:
            rep.engine.pool.state = None
        self.fleet = self.be = self.engine = None
        gc.collect()
        jax.clear_caches()
        gc.collect()
