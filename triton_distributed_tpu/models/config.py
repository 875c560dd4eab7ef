"""Model configuration.

TPU-native analog of the reference's ``models/config.py`` (``ModelConfig``
:31). The reference resolves architecture hyper-parameters from HuggingFace
at load time; this framework runs with zero network egress, so the known
architectures are recorded here as presets (the numbers are the public HF
``config.json`` values) and ``from_name`` resolves them.

One configuration class per decoder block, and ``Engine`` picks the model
class from the class of the object it is given:

- ``ModelConfig``: dense grouped-query decoders (Qwen3, Llama-3) and the
  Qwen3-MoE block, run by ``models.qwen.Qwen3``. Loading real weights goes
  through ``Qwen3.load_hf`` with a local checkpoint path.
- ``DeepseekV3Config``: the DeepSeek-V3 block (latent attention over a
  latent cache, sigmoid-routed experts with a shared expert, leading dense
  layers), run by ``models.deepseek_v3.DeepseekV3``.
- ``GraniteHybridConfig``: the Granite-4.0-H block (Mamba-2 layers that keep
  a fixed-size state a sequence beside a few attention layers that keep
  rows, a SwiGLU after each), run by ``models.granite_hybrid.GraniteHybrid``.
- ``NemotronHConfig``: the Nemotron-H block (every layer ONE mixer: Mamba-2,
  routed relu² experts with a shared expert, or attention, in the order a
  pattern string gives), run by ``models.nemotron_h.NemotronH``.
- ``ExaoneMoeConfig``: the EXAONE-MoE block (attention in every layer, some
  layers over a WINDOW of the last keys with rope and the others over every
  key without; a dense SwiGLU or sigmoid-routed experts with a shared expert
  after it), run by ``models.exaone_moe.ExaoneMoe``.
- ``Lfm2MoeConfig``: the LFM2-MoE block (a gated short convolution that
  keeps a window of two inputs a sequence, or rope'd grouped-query attention
  with a per-head norm; a dense SwiGLU or sigmoid-routed experts chosen
  under a bias after it), run by the same ``ExaoneMoe`` walk with a third
  kind of operator.
- ``EvaByteConfig``: the EvaByte block (EVA attention in every layer: the
  query's own ALIGNED window read key by key, every earlier window through
  one learned summary a chunk; a dense SwiGLU; a byte vocabulary under
  several prediction heads), run by ``models.evabyte.EvaByte``.

Each states what ``serving.kv_pool.KVPool`` builds the pool's state from:
``kv_row_shapes`` (what one token's row of each row arena looks like),
``n_cache_layers`` (how many layers keep such rows for the WHOLE context),
``slot_state_shapes`` (the arenas that hold a fixed-size state for each
SLOT, none for a model whose every layer keeps rows) and, where some layers
keep rows for a window only, ``n_window_layers`` and ``window`` (a ring of
rows for each slot, ``serving.kv_pool``). A model whose rows in the block
arenas stand for SEVERAL tokens each says how many (``kv_row_tokens``; every
other model states nothing and a row is a token).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    model_name: str = "Qwen/Qwen3-32B"
    vocab_size: int = 151_936
    d_model: int = 5120
    n_layers: int = 64
    n_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128
    d_ff: int = 25_600
    rope_theta: float = 1e6
    # Llama-3.1/3.2 "llama3" RoPE scaling: (factor, low_freq_factor,
    # high_freq_factor, original_max_position); None = plain RoPE.
    rope_scaling: tuple | None = None
    rms_eps: float = 1e-6
    tie_embeddings: bool = False
    qk_norm: bool = True
    max_length: int = 4096
    dtype: jnp.dtype = jnp.bfloat16
    # Mixture-of-Experts (Qwen3-MoE family): n_experts == 0 means dense.
    n_experts: int = 0
    n_experts_per_tok: int = 8
    moe_d_ff: int | None = None       # per-expert intermediate size
    norm_topk_prob: bool = True
    # EP buffer headroom over the uniform-routing expectation; raise for
    # drop-free serving of skewed routings (layers/moe_mlp.py capacities).
    moe_capacity_factor: float = 2.0

    @property
    def kv_row_shapes(self):
        """One token's row in the K plane and in the V plane of the paged
        pool's arena: per-head keys and values."""
        row = (self.n_kv_heads, self.head_dim)
        return row, row

    @property
    def n_cache_layers(self) -> int:
        """Every layer keeps rows in the paged pool."""
        return self.n_layers

    #: No layer keeps a state a slot.
    slot_state_shapes = None

    @classmethod
    def from_name(cls, name: str, **overrides) -> "ModelConfig":
        key = name.lower().removeprefix("qwen/").removeprefix("meta-llama/")
        if key not in _PRESETS:
            raise ValueError(
                f"unknown model {name!r}; known: {sorted(_PRESETS)}")
        return cls(model_name=name, **{**_PRESETS[key], **overrides})


LANE = 128      # a cache row is padded to a multiple of the chip's lane count


def packed_key_heads(n_kv_heads: int, head_dim: int) -> int:
    """Key heads that share one row of the pool: heads narrower than the
    chip's lane count are packed side by side into a lane-wide row (8 heads
    of 64 are 4 rows of 128), so that the pool holds no padding and the
    block walk moves whole lane tiles."""
    return max(p for p in range(1, max(1, LANE // head_dim) + 1)
               if n_kv_heads % p == 0)


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    """The DeepSeek-V3 decoder block (HF ``modeling_deepseek_v3``; HF key in
    brackets). Defaults are JoyAI-LLM-Flash's public ``config.json``.

    ``experts_held`` / ``experts_lo``: this device is one chip's share of a
    wide expert-parallel deployment and holds the routed experts
    ``[experts_lo, experts_lo + experts_held)`` of every expert layer. The
    router keeps its published width ``n_experts`` and ``n_experts_per_tok``;
    the layer computes the part of the result its own experts give (weights
    normalised over all chosen experts) and leaves out what the absent ones
    would add. ``None`` holds all of them.
    """

    model_name: str = "jdopensource/JoyAI-LLM-Flash"
    vocab_size: int = 129_280
    d_model: int = 2048                # hidden_size
    n_layers: int = 40                 # num_hidden_layers
    n_dense_layers: int = 1            # first_k_dense_replace
    n_heads: int = 32                  # num_attention_heads
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 7168                   # intermediate_size (dense layers)
    moe_d_ff: int = 768                # moe_intermediate_size
    n_experts: int = 256               # n_routed_experts: the router's width
    n_experts_per_tok: int = 8         # num_experts_per_tok
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    experts_held: int | None = None
    experts_lo: int = 0
    rope_theta: float = 32e6           # on interleaved pairs, no scaling
    rms_eps: float = 1e-6
    max_length: int = 4096
    dtype: jnp.dtype = jnp.bfloat16

    def __post_init__(self):
        held = self.n_held
        if not (0 <= self.experts_lo and held >= 1
                and self.experts_lo + held <= self.n_experts):
            raise ValueError(
                f"experts held [{self.experts_lo}, {self.experts_lo + held}) "
                f"do not lie inside the router's {self.n_experts}")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError("n_dense_layers must lie in [0, n_layers]")

    @property
    def n_held(self) -> int:
        return self.n_experts if self.experts_held is None \
            else self.experts_held

    @property
    def cache_width(self) -> int:
        """The latent cache row: normalised latent, then the rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row(self) -> int:
        """The row as the pool stores it: zero-padded to a lane multiple
        (576 -> 640), so that every DMA and matmul of the latent kernel is
        lane-aligned."""
        return -(-self.cache_width // LANE) * LANE

    @property
    def kv_row_shapes(self):
        """ONE latent arena (no V row, so no planes): keys are the whole
        row, values its first ``kv_lora_rank`` columns."""
        return (self.cache_row,), None

    @property
    def n_cache_layers(self) -> int:
        return self.n_layers

    slot_state_shapes = None

    @classmethod
    def tiny(cls, **overrides) -> "DeepseekV3Config":
        """Tiny float32 sizes for tests (not a real checkpoint)."""
        return cls(**{**dict(
            model_name="tiny-deepseek-v3", vocab_size=128, d_model=64,
            n_layers=3, n_heads=4, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            d_ff=96, moe_d_ff=32, n_experts=16, n_experts_per_tok=4,
            rope_theta=1e4, max_length=64, dtype=jnp.float32), **overrides})


_GRANITE_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The Granite-4.0-H decoder (HF ``GraniteMoeHybrid`` with no routed
    experts; HF key in brackets). Defaults are granite-4.0-h-micro's public
    ``config.json``. ``layer_types`` names each layer's mixer: ``"mamba"``
    (a Mamba-2 layer, whose state is a fixed size a sequence) or
    ``"attention"`` (grouped-query attention without rotary embedding,
    whose keys and values are rows of the paged pool); every layer has the
    same SwiGLU after its mixer."""

    model_name: str = "ibm-granite/granite-4.0-h-micro"
    vocab_size: int = 100_352
    d_model: int = 2048                # hidden_size
    layer_types: tuple = _GRANITE_PERIOD * 4
    n_heads: int = 32                  # num_attention_heads
    n_kv_heads: int = 8                # num_key_value_heads
    d_ff: int = 8192                   # shared_intermediate_size
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_eps: float = 1e-5
    max_length: int = 4096
    dtype: jnp.dtype = jnp.bfloat16

    def __post_init__(self):
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types names {sorted(bad)}; a layer is "
                             f"'mamba' or 'attention'")
        if self.d_model % self.n_heads or self.n_heads % self.n_kv_heads \
                or self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("heads do not divide the widths given")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        """[mamba_expand x hidden_size] = heads x head width."""
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """What the convolution runs over: x, B and C."""
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def kv_pack(self) -> int:
        return packed_key_heads(self.n_kv_heads, self.head_dim)

    @property
    def kv_row_shapes(self):
        row = (self.n_kv_heads // self.kv_pack, self.kv_pack * self.head_dim)
        return row, row

    @property
    def n_cache_layers(self) -> int:
        return self.layer_types.count("attention")

    @property
    def n_state_layers(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def slot_state_shapes(self):
        """What a Mamba-2 layer keeps for one sequence, ``{arena: (shape,
        dtype)}``: the recurrence's state in float32 (it accumulates over
        every token of the sequence) and the convolution's window, the last
        ``mamba_d_conv - 1`` inputs, oldest first, side by side."""
        return {
            "ssm": ((self.mamba_n_heads, self.mamba_d_head,
                     self.mamba_d_state), jnp.float32),
            "conv": (((self.mamba_d_conv - 1) * self.conv_dim,), self.dtype),
        }

    @classmethod
    def tiny(cls, **overrides) -> "GraniteHybridConfig":
        """Tiny float32 sizes for tests (not a real checkpoint): two
        periods of (mamba, attention, mamba)."""
        return cls(**{**dict(
            model_name="tiny-granite-hybrid", vocab_size=128, d_model=64,
            layer_types=("mamba", "attention", "mamba") * 2, n_heads=4,
            n_kv_heads=2, d_ff=96, mamba_n_heads=4, mamba_d_head=8,
            mamba_d_state=16, max_length=64, dtype=jnp.float32),
            **overrides})


#: A layer's kind by its letter in ``hybrid_override_pattern``.
NEMOTRON_H_KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The Nemotron-H decoder (HF ``nemotron_h``; HF key in brackets).
    Defaults are NVIDIA-Nemotron-3-Nano-30B-A3B's public ``config.json``.
    A layer is ONE mixer under one norm, ``h <- h + Mixer_i(RMSNorm_i(h))``,
    and ``pattern`` [hybrid_override_pattern] names each layer's: ``M`` a
    Mamba-2 layer (its state a fixed size a sequence), ``E`` routed experts
    with a shared expert (ungated, relu²), ``*`` grouped-query attention with
    no position embedding (its keys and values rows of the paged pool).

    ``experts_held`` / ``experts_lo`` as ``DeepseekV3Config`` has them: this
    device is one chip's share of an expert-parallel deployment and holds
    the routed experts ``[experts_lo, experts_lo + experts_held)`` of every
    expert layer (a configuration's file states them as
    ``n_routed_experts_held`` / ``n_routed_experts_lo`` beside
    ``n_routed_experts_published``, the router's width ``n_experts``)."""

    model_name: str = "nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
    vocab_size: int = 131_072
    d_model: int = 2688                # hidden_size
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    n_heads: int = 32                  # num_attention_heads
    n_kv_heads: int = 2                # num_key_value_heads
    head_dim: int = 128
    mamba_n_heads: int = 64            # mamba_num_heads
    mamba_d_head: int = 64             # mamba_head_dim
    mamba_d_state: int = 128           # ssm_state_size
    mamba_d_conv: int = 4              # conv_kernel
    mamba_n_groups: int = 8            # n_groups
    moe_d_ff: int = 1856               # moe_intermediate_size
    shared_d_ff: int = 3712            # moe_shared_expert_intermediate_size
    n_experts: int = 128               # n_routed_experts: the router's width
    n_experts_per_tok: int = 6         # num_experts_per_tok
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    experts_held: int | None = None
    experts_lo: int = 0
    rms_eps: float = 1e-5              # layer_norm_epsilon
    max_length: int = 4096
    dtype: jnp.dtype = jnp.bfloat16

    def __post_init__(self):
        bad = set(self.pattern) - set(NEMOTRON_H_KINDS)
        if bad or not self.pattern:
            raise ValueError(f"pattern names {sorted(bad)}; a layer is one "
                             f"of {sorted(NEMOTRON_H_KINDS)}")
        if self.n_heads % self.n_kv_heads \
                or self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("heads do not divide the widths given")
        if not (0 <= self.experts_lo and self.n_held >= 1
                and self.experts_lo + self.n_held <= self.n_experts):
            raise ValueError(
                f"experts held [{self.experts_lo}, "
                f"{self.experts_lo + self.n_held}) do not lie inside the "
                f"router's {self.n_experts}")

    @property
    def layer_kinds(self) -> tuple:
        """``"mamba"`` | ``"moe"`` | ``"attention"``, one a layer."""
        return tuple(NEMOTRON_H_KINDS[ch] for ch in self.pattern)

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def n_held(self) -> int:
        return self.n_experts if self.experts_held is None \
            else self.experts_held

    @property
    def moe_d_ff_stored(self) -> int:
        """A routed expert's width as its matrices are STORED: zero-padded
        to a lane multiple (1,856 -> 1,920), so that the grouped product
        over expert tiles walks whole lane tiles (1,856 has no divisor that
        is one). relu² of a zero column is zero and meets a zero row of
        ``w_down``: the padding changes no result."""
        return -(-self.moe_d_ff // LANE) * LANE

    @property
    def kv_row_shapes(self):
        row = (self.n_kv_heads, self.head_dim)
        return row, row

    @property
    def n_cache_layers(self) -> int:
        return self.pattern.count("*")

    @property
    def n_state_layers(self) -> int:
        return self.pattern.count("M")

    @property
    def conv_dim(self) -> int:
        """What the convolution runs over: x, B and C."""
        return (self.mamba_n_heads * self.mamba_d_head
                + 2 * self.mamba_n_groups * self.mamba_d_state)

    @property
    def slot_state_shapes(self):
        """What a Mamba-2 layer keeps for one sequence, as
        ``GraniteHybridConfig.slot_state_shapes``."""
        return {
            "ssm": ((self.mamba_n_heads, self.mamba_d_head,
                     self.mamba_d_state), jnp.float32),
            "conv": (((self.mamba_d_conv - 1) * self.conv_dim,), self.dtype),
        }

    @classmethod
    def tiny(cls, **overrides) -> "NemotronHConfig":
        """Tiny float32 sizes for tests (not a real checkpoint): all three
        kinds in a pattern no period divides, two groups, 8 experts top-2."""
        return cls(**{**dict(
            model_name="tiny-nemotron-h", vocab_size=128, d_model=64,
            pattern="MEM*EMEME", n_heads=4, n_kv_heads=2, head_dim=16,
            mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16,
            mamba_n_groups=2, moe_d_ff=24, shared_d_ff=48, n_experts=8,
            n_experts_per_tok=2, max_length=64, dtype=jnp.float32),
            **overrides})


_EXAONE_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    """The EXAONE-MoE decoder (HF ``exaone_moe``; HF key in brackets).
    Defaults are K-EXAONE-236B-A23B's public ``config.json``. Every layer is
    grouped-query attention with a per-head RMSNorm on queries and keys and
    then an FFN. ``layer_types`` names each layer's attention:
    ``"sliding_attention"`` sees the last ``sliding_windows[i]`` keys up to
    itself and takes rope; ``"full_attention"`` (window 0) sees every key
    and takes no position embedding. ``mlp_layer_types`` names each layer's
    FFN: ``"dense"`` (a SwiGLU of ``d_ff``) or ``"sparse"`` (sigmoid-routed
    SwiGLU experts of ``moe_d_ff`` beside ``n_shared_experts`` shared ones).
    Nothing here assumes a period: the walk is read from the two tuples.

    Four things a model of this walk may state otherwise (SmallThinker
    does, all four): ``qk_norm`` False (no per-head norm; its two weights
    are not parameters then), ``n_shared_experts`` 0 (no shared expert),
    the experts' forms ``scoring`` / ``expert_activation``
    (``layers.moe_mlp.HeldExpertsMoE``: ``"softmax_topk"``, ``"reglu"``) and
    ``router_input`` ``"layer_input"``: the router stands BEFORE attention
    and reads the layer's input stream as it is, un-normed, while the
    experts still read the post-attention norm. A walk with no dense layer
    has no ``dense`` parameters and reads no ``d_ff``.

    ``experts_held`` / ``experts_lo`` as ``DeepseekV3Config`` has them: this
    device is one chip's share of an expert-parallel deployment and holds
    the routed experts ``[experts_lo, experts_lo + experts_held)`` of every
    sparse layer; the router keeps its published width.

    The pool it describes: ``kv_row_shapes`` rows of ``n_kv_heads x
    head_dim`` for K and for V; ``n_cache_layers`` layers (the full ones)
    keep them for the whole context, ``n_window_layers`` keep them for a
    window of ``window`` tokens in a ring a slot."""

    model_name: str = "LGAI-EXAONE/K-EXAONE-236B-A23B"
    vocab_size: int = 153_600
    d_model: int = 6144                # hidden_size
    layer_types: tuple = _EXAONE_PERIOD * 12
    sliding_windows: tuple = (128, 128, 128, 0) * 12
    mlp_layer_types: tuple = ("dense",) + ("sparse",) * 47
    n_heads: int = 64                  # num_attention_heads
    n_kv_heads: int = 8                # num_key_value_heads
    head_dim: int = 128
    d_ff: int = 18_432                 # intermediate_size (dense layers)
    moe_d_ff: int = 2048               # moe_intermediate_size
    n_experts: int = 128               # num_experts: the router's width
    n_experts_per_tok: int = 8         # num_experts_per_tok
    n_shared_experts: int = 1          # num_shared_experts
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    experts_held: int | None = None
    experts_lo: int = 0
    rope_theta: float = 1e6            # rope_parameters.rope_theta, default
    rms_eps: float = 1e-5              # rms_norm_eps
    max_length: int = 4096
    dtype: jnp.dtype = jnp.bfloat16
    qk_norm: bool = True
    scoring: str = "sigmoid"           # | "softmax_topk"
    expert_activation: str = "swiglu"  # | "reglu"
    router_input: str = "post_attn_norm"   # | "layer_input"

    def __post_init__(self):
        if self.router_input not in ("post_attn_norm", "layer_input") \
                or self.expert_activation not in ("swiglu", "reglu") \
                or self.scoring not in ("sigmoid", "softmax_topk"):
            raise ValueError(
                f"unknown router_input {self.router_input!r}, "
                f"expert_activation {self.expert_activation!r} or scoring "
                f"{self.scoring!r}")
        n = len(self.layer_types)
        if not n or len(self.sliding_windows) != n \
                or len(self.mlp_layer_types) != n:
            raise ValueError("layer_types, sliding_windows and "
                             "mlp_layer_types name every layer, each once")
        bad = (set(self.layer_types)
               - {"sliding_attention", "full_attention"}) \
            | (set(self.mlp_layer_types) - {"dense", "sparse"})
        if bad:
            raise ValueError(f"unknown layer kinds {sorted(bad)}")
        windows = {w for t, w in zip(self.layer_types, self.sliding_windows)
                   if t == "sliding_attention"}
        if len(windows) > 1 or 0 in windows or any(
                w for t, w in zip(self.layer_types, self.sliding_windows)
                if t == "full_attention"):
            raise ValueError(
                "window layers share ONE window of at least one key (the "
                "pool keeps one ring geometry) and full layers state 0")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("heads do not divide the widths given")
        if not (0 <= self.experts_lo and self.n_held >= 1
                and self.experts_lo + self.n_held <= self.n_experts):
            raise ValueError(
                f"experts held [{self.experts_lo}, "
                f"{self.experts_lo + self.n_held}) do not lie inside the "
                f"router's {self.n_experts}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_held(self) -> int:
        return self.n_experts if self.experts_held is None \
            else self.experts_held

    @property
    def layer_kinds(self) -> tuple:
        """``(attention, ffn)`` a layer: ``"window"`` | ``"full"`` and
        ``"dense"`` | ``"moe"``."""
        return tuple(
            ("window" if t == "sliding_attention" else "full",
             "dense" if m == "dense" else "moe")
            for t, m in zip(self.layer_types, self.mlp_layer_types))

    @property
    def kv_row_shapes(self):
        row = (self.n_kv_heads, self.head_dim)
        return row, row

    @property
    def n_cache_layers(self) -> int:
        """Layers whose rows are kept for the whole context."""
        return self.layer_types.count("full_attention")

    @property
    def n_window_layers(self) -> int:
        """Layers whose rows are kept for a window only."""
        return self.layer_types.count("sliding_attention")

    @property
    def window(self) -> int:
        return max(self.sliding_windows)

    # What the walk reads of a configuration and this block states one way
    # (``Lfm2MoeConfig`` states each otherwise): no per-slot state, a key
    # head a row of the pool, no position embedding on the full layers, an
    # untied head, no selection bias.
    slot_state_shapes = None
    kv_pack = 1
    rope_full = False
    tie_embeddings = False
    expert_bias = False

    @classmethod
    def smallthinker(cls, **overrides) -> "ExaoneMoeConfig":
        """SmallThinker-21BA3B-Instruct's public ``config.json``
        (PowerInfer/SmallThinker-21BA3B-Instruct): 52 layers ``full, window,
        window, window`` x 13, window 4,096 with rope on the window layers
        and none on the full ones, 28 query / 4 key heads and no QK norm,
        every layer 64 ReGLU experts of 768 with no shared expert, the 6
        largest logits of a router that reads the layer's input, softmax
        over the chosen."""
        period = ("full_attention",) + ("sliding_attention",) * 3
        return cls(**{**dict(
            model_name="PowerInfer/SmallThinker-21BA3B-Instruct",
            vocab_size=151_936, d_model=2560, layer_types=period * 13,
            sliding_windows=(0, 4096, 4096, 4096) * 13,
            mlp_layer_types=("sparse",) * 52, n_heads=28, n_kv_heads=4,
            head_dim=128, d_ff=0, moe_d_ff=768, n_experts=64,
            n_experts_per_tok=6, n_shared_experts=0,
            routed_scaling_factor=1.0, rope_theta=1.5e6, rms_eps=1e-6,
            max_length=16_384, qk_norm=False, scoring="softmax_topk",
            expert_activation="reglu", router_input="layer_input"),
            **overrides})

    @classmethod
    def tiny(cls, **overrides) -> "ExaoneMoeConfig":
        """Tiny float32 sizes for tests (not a real checkpoint): a dense
        layer then experts, window (6) and full layers in no period."""
        return cls(**{**dict(
            model_name="tiny-exaone-moe", vocab_size=128, d_model=64,
            layer_types=("sliding_attention", "sliding_attention",
                         "full_attention", "sliding_attention"),
            sliding_windows=(6, 6, 0, 6),
            mlp_layer_types=("dense", "sparse", "sparse", "sparse"),
            n_heads=4, n_kv_heads=2, head_dim=16, d_ff=96, moe_d_ff=32,
            n_experts=8, n_experts_per_tok=2, rope_theta=1e4, max_length=64,
            dtype=jnp.float32), **overrides})


_LFM2_PERIOD = ("full_attention",) + ("conv",) * 3


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """The LFM2-MoE decoder (HF ``lfm2_moe``; HF key in brackets). Defaults
    are LFM2-24B-A2B's public ``config.json``. A layer is an operator and an
    FFN, each under its own RMSNorm. ``layer_types`` names each layer's
    operator: ``"conv"``, the gated short convolution
    (``layers.short_conv.ShortConv``: ``conv_kernel`` [conv_L_cache] taps a
    channel, whose last ``conv_kernel - 1`` inputs are ALL a sequence keeps
    of the layer), or ``"full_attention"``, grouped-query attention over
    every key with a per-head RMSNorm on queries and keys and rope. The
    first ``n_dense_layers`` [num_dense_layers] carry a SwiGLU of ``d_ff``,
    the others ``n_experts`` sigmoid-routed SwiGLU experts of ``moe_d_ff``,
    the ``n_experts_per_tok`` largest of score + bias [use_expert_bias]
    weighted by the unbiased scores over their sum; no shared expert. The
    head is the embedding table [tie_word_embeddings, the family's
    convention].

    ``experts_held`` / ``experts_lo`` as ``DeepseekV3Config`` has them: this
    device is one chip's share of an expert-parallel deployment and holds
    the routed experts ``[experts_lo, experts_lo + experts_held)`` of every
    sparse layer; the router keeps its published width.

    The pool it describes: ``kv_row_shapes`` rows of two packed key heads
    (``kv_pack``) for K and for V in the ``n_cache_layers`` attention
    layers, and ONE per-slot arena, ``conv``, as deep as the conv layers
    (``n_state_layers``): ``slot_state_shapes`` names no ``ssm``."""

    model_name: str = "LiquidAI/LFM2-24B-A2B"
    vocab_size: int = 65_536
    d_model: int = 2048                # hidden_size
    layer_types: tuple = ("conv", "conv") + _LFM2_PERIOD * 9 \
        + ("full_attention", "conv")
    n_dense_layers: int = 2            # num_dense_layers
    conv_kernel: int = 3               # conv_L_cache
    conv_bias: bool = False
    n_heads: int = 32                  # num_attention_heads
    n_kv_heads: int = 8                # num_key_value_heads
    d_ff: int = 11_776                 # intermediate_size (dense layers)
    moe_d_ff: int = 1536               # moe_intermediate_size
    n_experts: int = 64                # num_experts: the router's width
    n_experts_per_tok: int = 4         # num_experts_per_tok
    expert_bias: bool = True           # use_expert_bias
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    experts_held: int | None = None
    experts_lo: int = 0
    rope_theta: float = 1e6            # rope_parameters.rope_theta
    rms_eps: float = 1e-5              # norm_eps
    tie_embeddings: bool = True
    max_length: int = 4096
    dtype: jnp.dtype = jnp.bfloat16

    # The block's own, where ``ExaoneMoeConfig`` has a field to read.
    qk_norm = True
    rope_full = True
    n_shared_experts = 0
    scoring = "sigmoid"
    expert_activation = "swiglu"
    router_input = "post_attn_norm"
    n_window_layers = 0
    window = 0

    def __post_init__(self):
        bad = set(self.layer_types) - {"conv", "full_attention"}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types names {sorted(bad)}; a layer is "
                             f"'conv' or 'full_attention'")
        if self.conv_bias:
            raise NotImplementedError(
                "conv_bias: the biases of the short convolution and of its "
                "two projections are not built (LFM2's published "
                "configurations state false)")
        if self.conv_kernel < 2 or self.d_model % self.n_heads \
                or self.n_heads % self.n_kv_heads \
                or not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError("heads, taps or dense layers do not fit the "
                             "widths and the layers given")
        if not (0 <= self.experts_lo and self.n_held >= 1
                and self.experts_lo + self.n_held <= self.n_experts):
            raise ValueError(
                f"experts held [{self.experts_lo}, "
                f"{self.experts_lo + self.n_held}) do not lie inside the "
                f"router's {self.n_experts}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def n_held(self) -> int:
        return self.n_experts if self.experts_held is None \
            else self.experts_held

    @property
    def layer_kinds(self) -> tuple:
        """``(operator, ffn)`` a layer: ``"conv"`` | ``"full"`` and
        ``"dense"`` | ``"moe"``."""
        return tuple(
            ("conv" if t == "conv" else "full",
             "dense" if i < self.n_dense_layers else "moe")
            for i, t in enumerate(self.layer_types))

    @property
    def kv_pack(self) -> int:
        return packed_key_heads(self.n_kv_heads, self.head_dim)

    @property
    def kv_row_shapes(self):
        row = (self.n_kv_heads // self.kv_pack, self.kv_pack * self.head_dim)
        return row, row

    @property
    def n_cache_layers(self) -> int:
        return self.layer_types.count("full_attention")

    @property
    def n_state_layers(self) -> int:
        return self.layer_types.count("conv")

    @property
    def slot_state_shapes(self):
        """What a conv layer keeps for one sequence: the last
        ``conv_kernel - 1`` values of ``B * X``, oldest first, side by
        side. Nothing that grows, no recurrence."""
        return {"conv": (((self.conv_kernel - 1) * self.d_model,),
                         self.dtype)}

    @classmethod
    def tiny(cls, **overrides) -> "Lfm2MoeConfig":
        """Tiny float32 sizes for tests (not a real checkpoint): two dense
        conv layers, one period and a tail of full, conv; two key heads of
        16 packed into one row of the pool."""
        return cls(**{**dict(
            model_name="tiny-lfm2-moe", vocab_size=128, d_model=64,
            layer_types=("conv", "conv", "full_attention", "conv", "conv",
                         "conv", "full_attention", "conv"),
            n_heads=4, n_kv_heads=2, d_ff=96, moe_d_ff=32, n_experts=8,
            n_experts_per_tok=2, rope_theta=1e4, max_length=64,
            dtype=jnp.float32), **overrides})


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    """The EvaByte decoder (HF ``evabyte``; HF key in brackets). Defaults
    are EvaByte/EvaByte's public ``config.json`` (6.5B). Every layer is the
    same: EVA attention [attention_class eva] with as many key heads as
    query heads, no bias, rope over the whole head; a SwiGLU of ``d_ff``;
    RMSNorm whose weight is ``1 + g`` [norm_add_unit_offset]; the residual
    adds in float32 [fp32_skip_add]. A query at position ``p`` reads the
    keys of its own ALIGNED window, ``(p // window) * window <= j <= p``
    [window_size], one by one, and every EARLIER window through one summary
    a chunk of ``chunk_size`` positions [chunk_size]: a key and a value
    pooled over the chunk under two learned vectors a head
    (``layers/eva_attn.py``). The head is ``n_pred_heads`` [num_pred_heads]
    heads of ``vocab_size`` side by side, head 0 the next byte; the served
    step samples from head 0.

    The pool it describes is the first whose every layer keeps TWO kinds of
    cache: a ring of the window's rows a slot (``n_window_layers ==
    n_layers``) AND rows in the block arenas (``n_cache_layers ==
    n_layers``), ONE A CHUNK: ``kv_row_tokens`` is ``chunk_size``, and a
    sequence of ``n`` tokens owns ``ceil(ceil(n / chunk_size) /
    block_size)`` blocks (``serving.kv_pool.blocks_needed``)."""

    model_name: str = "EvaByte/EvaByte"
    vocab_size: int = 320
    d_model: int = 4096                # hidden_size
    n_layers: int = 32                 # num_hidden_layers
    n_heads: int = 32                  # num_attention_heads
    n_kv_heads: int = 32               # num_key_value_heads
    head_dim: int = 128
    d_ff: int = 11_008                 # intermediate_size
    window: int = 2048                 # window_size
    chunk_size: int = 16
    n_pred_heads: int = 8              # num_pred_heads
    rope_theta: float = 1e5
    rms_eps: float = 1e-5              # rms_norm_eps
    max_length: int = 32_768           # max_position_embeddings
    dtype: jnp.dtype = jnp.bfloat16

    slot_state_shapes = None

    def __post_init__(self):
        if self.n_kv_heads != self.n_heads:
            raise ValueError(
                "EVA attention pools a summary a head: as many key heads as "
                "query heads")
        if self.chunk_size < 1 or self.window % self.chunk_size \
                or self.window < self.chunk_size or self.n_pred_heads < 1:
            raise ValueError(
                f"a window of {self.window} positions is not whole chunks "
                f"of {self.chunk_size}")

    @property
    def kv_row_shapes(self):
        row = (self.n_kv_heads, self.head_dim)
        return row, row

    @property
    def n_cache_layers(self) -> int:
        """Every layer keeps summary rows for the whole context ..."""
        return self.n_layers

    @property
    def n_window_layers(self) -> int:
        """... and its window's rows in a ring."""
        return self.n_layers

    @property
    def kv_row_tokens(self) -> int:
        """Tokens one row of the block arenas stands for."""
        return self.chunk_size

    @classmethod
    def tiny(cls, **overrides) -> "EvaByteConfig":
        """Tiny float32 sizes for tests (not a real checkpoint): a window
        of 32 positions in chunks of 4."""
        return cls(**{**dict(
            model_name="tiny-evabyte", vocab_size=40, d_model=64,
            n_layers=3, n_heads=4, n_kv_heads=4, head_dim=16, d_ff=96,
            window=32, chunk_size=4, n_pred_heads=8, rope_theta=1e4,
            max_length=160, dtype=jnp.float32), **overrides})


# Public Qwen3 architecture hyper-parameters (HF config.json values).
_PRESETS: dict[str, dict] = {
    "qwen3-0.6b": dict(d_model=1024, n_layers=28, n_heads=16, n_kv_heads=8,
                       head_dim=128, d_ff=3072, tie_embeddings=True),
    "qwen3-1.7b": dict(d_model=2048, n_layers=28, n_heads=16, n_kv_heads=8,
                       head_dim=128, d_ff=6144, tie_embeddings=True),
    "qwen3-4b": dict(d_model=2560, n_layers=36, n_heads=32, n_kv_heads=8,
                     head_dim=128, d_ff=9728, tie_embeddings=True),
    "qwen3-8b": dict(d_model=4096, n_layers=36, n_heads=32, n_kv_heads=8,
                     head_dim=128, d_ff=12_288),
    "qwen3-14b": dict(d_model=5120, n_layers=40, n_heads=40, n_kv_heads=8,
                      head_dim=128, d_ff=17_408),
    "qwen3-32b": dict(d_model=5120, n_layers=64, n_heads=64, n_kv_heads=8,
                      head_dim=128, d_ff=25_600),
    # Llama-3 family (same decoder skeleton: GQA + SwiGLU + RMSNorm; no
    # per-head qk-norm, plain or "llama3"-scaled RoPE). Public HF
    # config.json values.
    "meta-llama-3-8b": dict(vocab_size=128_256, d_model=4096, n_layers=32,
                            n_heads=32, n_kv_heads=8, head_dim=128,
                            d_ff=14_336, rope_theta=5e5, qk_norm=False,
                            max_length=8192),
    "meta-llama-3-70b": dict(vocab_size=128_256, d_model=8192, n_layers=80,
                             n_heads=64, n_kv_heads=8, head_dim=128,
                             d_ff=28_672, rope_theta=5e5, qk_norm=False,
                             max_length=8192),
    "llama-3.1-8b": dict(vocab_size=128_256, d_model=4096, n_layers=32,
                         n_heads=32, n_kv_heads=8, head_dim=128,
                         d_ff=14_336, rope_theta=5e5, qk_norm=False,
                         rope_scaling=(8.0, 1.0, 4.0, 8192),
                         max_length=16_384),
    "llama-3.2-1b": dict(vocab_size=128_256, d_model=2048, n_layers=16,
                         n_heads=32, n_kv_heads=8, head_dim=64, d_ff=8192,
                         rope_theta=5e5, qk_norm=False,
                         rope_scaling=(32.0, 1.0, 4.0, 8192),
                         tie_embeddings=True, max_length=16_384),
    # Qwen3-MoE family (HF config.json values: num_experts 128, top_k 8,
    # norm_topk_prob, per-expert moe_intermediate_size).
    "qwen3-30b-a3b": dict(d_model=2048, n_layers=48, n_heads=32,
                          n_kv_heads=4, head_dim=128, d_ff=6144,
                          n_experts=128, n_experts_per_tok=8,
                          moe_d_ff=768),
    "qwen3-235b-a22b": dict(d_model=4096, n_layers=94, n_heads=64,
                            n_kv_heads=4, head_dim=128, d_ff=12_288,
                            n_experts=128, n_experts_per_tok=8,
                            moe_d_ff=1536),
    # Depth-scaled 30b-a3b for the single-chip e2e bench (VERDICT r4
    # missing #4): TRUE per-layer shapes (d, experts, topk, moe_d_ff all as
    # the real checkpoint) with 6 layers so the ~1.2 GB/layer of expert
    # weights fits the 16 GB chip next to the KV cache — per-token cost is
    # per-layer-exact, only depth is scaled.
    "qwen3-30b-a3b-d6": dict(d_model=2048, n_layers=6, n_heads=32,
                             n_kv_heads=4, head_dim=128, d_ff=6144,
                             n_experts=128, n_experts_per_tok=8,
                             moe_d_ff=768),
    # Tiny config for tests / virtual-mesh dryruns (not a real checkpoint).
    "tiny": dict(vocab_size=128, d_model=64, n_layers=2, n_heads=8,
                 n_kv_heads=8, head_dim=8, d_ff=128, rope_theta=1e4,
                 max_length=32, dtype=jnp.float32),
    "tiny-moe": dict(vocab_size=128, d_model=64, n_layers=2, n_heads=8,
                     n_kv_heads=8, head_dim=8, d_ff=128, rope_theta=1e4,
                     max_length=32, dtype=jnp.float32, n_experts=8,
                     n_experts_per_tok=2, moe_d_ff=32),
}
