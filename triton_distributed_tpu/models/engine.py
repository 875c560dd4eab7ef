"""Inference engine.

TPU-native analog of the reference's ``models/engine.py`` (``Engine`` :37):
prefill + token-by-token decode over a preallocated KV cache, with the
decode step as ONE compiled program. Where the reference captures a CUDA
Graph for the decode step (:75) and replays it, here the step is a single
``jit`` of (shard_map'd model forward + cache append) with fixed shapes and
donated cache buffers — XLA's executable replay plays the CUDA-Graph role,
and buffer donation keeps the KV cache update in place.

The reference prefills in torch mode and decodes in triton_dist mode
(engine.py:121); cache layouts here are mode-compatible the same way, so
``Engine(prefill_mode=..., decode_mode=...)`` supports any combination of
``xla`` / ``dist`` / ``ar``.
"""

from __future__ import annotations

import functools

import jax
from triton_distributed_tpu.runtime.compat import shard_map
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from triton_distributed_tpu.models.config import (
    DeepseekV3Config,
    EvaByteConfig,
    ExaoneMoeConfig,
    GraniteHybridConfig,
    Lfm2MoeConfig,
    ModelConfig,
    NemotronHConfig,
)
from triton_distributed_tpu.models.kv_cache import KVCache
from triton_distributed_tpu.models.qwen import Qwen3
from triton_distributed_tpu.models.sampling import sample_token
from triton_distributed_tpu.obs import trace as _trace
from triton_distributed_tpu.runtime.mesh import get_default_mesh


def model_for(config, *, block_n: int = 256):
    """The model class that runs ``config``'s decoder block, picked by the
    class of the configuration object."""
    if isinstance(config, DeepseekV3Config):
        from triton_distributed_tpu.models.deepseek_v3 import DeepseekV3

        return DeepseekV3(config)
    if isinstance(config, GraniteHybridConfig):
        from triton_distributed_tpu.models.granite_hybrid import GraniteHybrid

        return GraniteHybrid(config)
    if isinstance(config, NemotronHConfig):
        from triton_distributed_tpu.models.nemotron_h import NemotronH

        return NemotronH(config)
    if isinstance(config, (ExaoneMoeConfig, Lfm2MoeConfig)):
        from triton_distributed_tpu.models.exaone_moe import ExaoneMoe

        return ExaoneMoe(config)
    if isinstance(config, EvaByteConfig):
        from triton_distributed_tpu.models.evabyte import EvaByte

        return EvaByte(config)
    return Qwen3(config, block_n=block_n)


class Engine:
    def __init__(self, config: ModelConfig | DeepseekV3Config
                 | GraniteHybridConfig | NemotronHConfig
                 | ExaoneMoeConfig | Lfm2MoeConfig | EvaByteConfig, *,
                 mesh: Mesh | None = None,
                 mode: str = "dist", prefill_mode: str | None = None,
                 temperature: float = 0.0, top_p: float = 1.0,
                 params=None, key=None, hf_path: str | None = None,
                 block_n: int = 256, max_length: int | None = None,
                 aot_cache: bool = False, interpret=None):
        """``aot_cache=True`` routes step compilation through the serialized
        AOT executable cache (``tools.aot.AOTExecutableCache``): later
        process starts deserialize the step executable instead of
        re-tracing + re-compiling — the reference's AOT kernel library
        cutting engine cold-start (tools/compile_aot.py:470)."""
        self.config = config
        self.mesh = mesh or get_default_mesh()
        self.model = model_for(config, block_n=block_n)
        self.temperature = temperature
        self.top_p = top_p
        self.max_length = max_length or config.max_length
        self.decode_mode = mode
        self.prefill_mode = prefill_mode or mode
        self.interpret = interpret
        if params is not None:
            self.params = params
        elif hf_path is not None:
            self.params = self.model.load_hf(hf_path, self.mesh)
        else:
            self.params = self.model.init(
                jax.random.PRNGKey(0) if key is None else key, self.mesh)
        self._steps: dict[str, object] = {}
        self._aot = None
        if aot_cache:
            from triton_distributed_tpu.tools.aot import AOTExecutableCache

            self._aot = AOTExecutableCache()
        self._aot_steps: dict[tuple, object] = {}

    # -- compiled step ------------------------------------------------------

    def _make_sm(self, mode: str, *, moe_stats: bool = False,
                 paged: str | None = None, paged_attn: str = "fused",
                 spec_verify: bool = False, state_specs=None):
        """The per-mode shard_map of the model forward — the ONE definition
        of the step sharding, shared by the per-step jit (``_step_fn``),
        the scanned loop (``_serve_scanned_fn``), and the drop-stats audit
        (``moe_stats=True`` appends the replicated counters output).

        ``paged='decode'|'prefill'`` builds the continuous-batching serving
        step (``serving/batch_engine.py``) over ``model.forward_paged``:
        ``(params, ids, state, offsets, block_tables, slot_mask[,
        seq_lens]) -> (logits, aux, state)``. ``state`` is the pool's
        device state, one pytree in and out; ``state_specs``
        (``KVPool.specs``) is its PartitionSpecs, a pytree of the same
        structure, and all this function knows of the pool's format. The
        other operands are replicated data, so slot churn never changes a
        shape; the two variants differ only in whether ``seq_lens`` is an
        operand. ``ids`` is an array (B, L) or, for the mixed step's two
        blocks, the triple ``(tok (B,), chunk (P, L), dealt (P, 3))``
        (``nn.paged_token_blocks``). ``paged_attn`` selects the paged KV read path for every
        step shape (fused block-walk kernel vs the gather escape hatch —
        see ``nn.paged_attn_with_cache``); it is baked into the trace, so
        a BatchEngine picks it once at construction.

        ``aux`` is a dict of replicated outputs whose keys are fixed per
        build: ``"greedy"`` int32 under ``spec_verify=True``
        (``paged='prefill'`` only) — the argmax continuation at every
        position of the last block's rows, which a speculative BatchEngine
        bakes into its one mixed-step trace — and ``"stats"`` for a model with
        ``step_stats``."""
        model = self.model
        if spec_verify and paged != "prefill":
            raise ValueError("spec_verify requires the paged='prefill' "
                             "(varlen mixed step) variant")
        if paged is None:
            kspec, vspec = KVCache.spec(model.axis)[:2]
            fwd = functools.partial(model.forward_device, mode=mode,
                                    interpret=self.interpret,
                                    return_moe_stats=moe_stats)
            in_specs = (model.param_specs(), P(), kspec, vspec, P())
            out_specs = (P(), kspec, vspec) + ((P(),) if moe_stats else ())
        elif paged in ("decode", "prefill"):
            if state_specs is None:
                raise ValueError("a paged step needs state_specs, the "
                                 "PartitionSpecs of the pool's state "
                                 "(KVPool.specs)")
            fwd = functools.partial(model.forward_paged, mode=mode,
                                    interpret=self.interpret,
                                    paged_attn=paged_attn,
                                    spec_verify=spec_verify)
            # offsets, block_tables, slot_mask[, seq_lens]
            data = (P(),) * (3 if paged == "decode" else 4)
            in_specs = (model.param_specs(), P(), state_specs, *data)
            # logits, aux (whatever its keys: all replicated), state
            out_specs = (P(), P(), state_specs)
        else:
            raise ValueError(f"unknown paged variant {paged!r}")
        return shard_map(
            fwd,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )

    def _step_fn(self, mode: str):
        """jit(shard_map(forward)) for one mode; the decode instance of this
        (L=1 shapes) is the CUDA-Graph-replay analog."""
        if mode in self._steps:
            return self._steps[mode]
        sm = self._make_sm(mode)

        @functools.partial(jax.jit, donate_argnums=(2,))
        def step(params, ids, kv: KVCache):
            logits, k, v = sm(params, ids, kv.k, kv.v, kv.offset)
            return logits, KVCache(k=k, v=v,
                                   offset=kv.offset + ids.shape[1])

        self._steps[mode] = step
        return step

    def _run_step(self, mode: str, ids, kv: KVCache):
        step = self._step_fn(mode)
        if self._aot is None:
            return step(self.params, ids, kv)
        key = (mode, ids.shape, kv.k.shape)
        if key not in self._aot_steps:
            abstract = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                (self.params, ids, kv))
            self._aot_steps[key], _ = self._aot.load_or_compile(
                f"engine_step_{self.config.model_name}_{mode}", step, *abstract,
                mesh=self.mesh)
        return self._aot_steps[key](self.params, ids, kv)

    # -- public API ---------------------------------------------------------

    def moe_drop_stats(self, input_ids):
        """Capacity audit for MoE configs (ADVICE r4): run one dist-mode
        forward over ``input_ids`` (a representative traffic batch) and
        return ``{"n_dropped_dispatch": int, "n_dropped_expert": int}`` —
        (token, expert) pairs silently dropped by the static EP capacities,
        summed over layers and ranks. HF semantics have no drop concept, so
        a production deployment should see ZEROS here; if not, raise
        ``config.moe_capacity_factor`` (or set explicit capacities on
        ``MoEMLP``) until it does. The counters ride the same scan as the
        real forward, so skew that only appears at depth is counted."""
        if not self.config.n_experts:
            raise ValueError("moe_drop_stats is only meaningful for MoE "
                             "configs (n_experts > 0)")
        # Cached like _step_fn: a serving stack audits over MANY batches,
        # and a fresh jit per call would re-trace + re-compile the whole
        # forward every time.
        if "moe_stats" not in self._steps:
            self._steps["moe_stats"] = jax.jit(
                self._make_sm("dist", moe_stats=True))
        input_ids = jnp.asarray(input_ids, jnp.int32)
        kv = self.new_cache(input_ids.shape[0])
        _, _, _, stats = self._steps["moe_stats"](self.params, input_ids,
                                                  kv.k, kv.v, kv.offset)
        return {k: int(v) for k, v in stats.items()}

    def new_cache(self, batch_size: int) -> KVCache:
        return KVCache.create(self.config, batch_size, mesh=self.mesh,
                              axis=self.model.axis,
                              max_length=self.max_length)

    def prefill(self, input_ids, kv: KVCache):
        """input_ids: (B, L) -> (logits (B, V), kv)."""
        with _trace.span("prefill", mode=self.prefill_mode,
                         tokens=int(input_ids.shape[0] * input_ids.shape[1])):
            return self._run_step(self.prefill_mode, input_ids, kv)

    def decode_step(self, token, kv: KVCache):
        """token: (B,) -> (logits (B, V), kv)."""
        with _trace.span("decode_step", mode=self.decode_mode):
            return self._run_step(self.decode_mode, token[:, None], kv)

    def serve(self, input_ids, gen_len: int, key=None):
        """Generate ``gen_len`` tokens after the prompt.

        input_ids: (B, L0) int32 -> (B, gen_len) int32 (reference
        ``Engine.serve``, engine.py:113: prefill -> sample -> decode loop).
        """
        input_ids = jnp.asarray(input_ids, jnp.int32)
        B, L0 = input_ids.shape
        if gen_len <= 0:
            return jnp.zeros((B, 0), jnp.int32)
        if L0 + gen_len > self.max_length:
            raise ValueError(
                f"prompt ({L0}) + gen_len ({gen_len}) exceeds the KV cache "
                f"max_length ({self.max_length}); dynamic_update_slice would "
                f"silently clamp and corrupt the cache")
        if key is None and self.temperature > 0.0:
            key = jax.random.PRNGKey(0)  # stochastic sampling needs a key
        kv = self.new_cache(B)

        with _trace.span("serve", batch=B, prompt_len=L0, gen_len=gen_len):
            logits, kv = self.prefill(input_ids, kv)
            key, sub = (None, None) if key is None else jax.random.split(key)
            tok = sample_token(logits, sub, temperature=self.temperature,
                               top_p=self.top_p)
            out = [tok]
            for _ in range(gen_len - 1):
                logits, kv = self.decode_step(tok, kv)
                key, sub = ((None, None) if key is None
                            else jax.random.split(key))
                tok = sample_token(logits, sub, temperature=self.temperature,
                                   top_p=self.top_p)
                out.append(tok)
            return jnp.stack(out, axis=1)

    # -- scanned generation (whole decode loop in ONE executable) -----------

    def _serve_scanned_fn(self, gen_len: int, L0: int):
        """jit of prefill + ``lax.scan`` over the decode steps: one dispatch
        generates ``gen_len`` tokens. The step-level jit (``_step_fn``) is
        the CUDA-Graph-replay analog per token; this is the replay LOOP
        captured too — where the host's per-token dispatch would otherwise
        bound a short decode step."""
        cache_key = ("scan", self.decode_mode, self.prefill_mode, gen_len, L0)
        if cache_key in self._steps:
            return self._steps[cache_key]
        sm_prefill = self._make_sm(self.prefill_mode)
        sm_decode = self._make_sm(self.decode_mode)
        temperature, top_p = self.temperature, self.top_p

        @functools.partial(jax.jit, donate_argnums=(2,))
        def run(params, input_ids, kv: KVCache, key):
            logits, k, v = sm_prefill(params, input_ids, kv.k, kv.v,
                                      kv.offset)
            kv = KVCache(k=k, v=v, offset=kv.offset + input_ids.shape[1])
            key, sub = jax.random.split(key)
            tok = sample_token(logits, sub, temperature=temperature,
                               top_p=top_p)

            def body(carry, _):
                tok, kv, key = carry
                logits, k, v = sm_decode(params, tok[:, None], kv.k, kv.v,
                                         kv.offset)
                kv = KVCache(k=k, v=v, offset=kv.offset + 1)
                key, sub = jax.random.split(key)
                tok = sample_token(logits, sub, temperature=temperature,
                                   top_p=top_p)
                return (tok, kv, key), tok

            (_, _, _), toks = jax.lax.scan(
                body, (tok, kv, key), None, length=gen_len - 1)
            return jnp.concatenate([tok[:, None], toks.T.astype(jnp.int32)],
                                   axis=1)

        self._steps[cache_key] = run
        return run

    def serve_scanned(self, input_ids, gen_len: int, key=None):
        """``serve`` with the whole prefill + decode loop in one compiled
        program (tokens match ``serve`` under greedy sampling;
        tests/test_qwen_e2e.py). Recompiles per (gen_len, prompt length)."""
        input_ids = jnp.asarray(input_ids, jnp.int32)
        B, L0 = input_ids.shape
        if gen_len <= 0:
            return jnp.zeros((B, 0), jnp.int32)
        if L0 + gen_len > self.max_length:
            raise ValueError(
                f"prompt ({L0}) + gen_len ({gen_len}) exceeds max_length "
                f"({self.max_length})")
        run = self._serve_scanned_fn(gen_len, L0)
        with _trace.span("serve_scanned", batch=B, prompt_len=L0,
                         gen_len=gen_len):
            return run(self.params, input_ids, self.new_cache(B),
                       jax.random.PRNGKey(0) if key is None else key)
