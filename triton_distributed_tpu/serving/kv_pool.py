"""Block-paged KV pool (PagedAttention-style memory management).

The serving-side replacement for the per-request contiguous ``KVCache``:
one fixed device allocation of ``n_blocks`` KV blocks per layer, ONE arena
whose block keeps its K rows and its V rows side by side (plane 0 the keys,
plane 1 the values)

    kv: (n_layers, n_blocks, 2, block_size, n_kv_heads, head_dim)

so the two planes of a (layer, block) are one run of bytes in HBM and the
block walk fetches them with ONE copy (``kernels/paged_attention.py``: a
copy costs about 37 ns whatever it carries, and K's and V's rows of a block
are always wanted together); or, for a model whose cache is one latent row a
token (``config.kv_row_shapes`` names no V row: the row is both), an arena
with no planes

    kv: (n_layers, n_blocks, block_size, row)

``n_layers`` here is the model's ``n_cache_layers``: the layers that keep
rows. A model some of whose layers keep a state of FIXED size a sequence
instead (``config.slot_state_shapes``: a recurrence's state, a
convolution's window) gets a second kind of arena beside these, indexed by
SLOT of the serving batch and not by block,

    ssm, conv, ...: (state layers, n_slots, *shape)

as deep as it has such layers. The allocator below knows nothing of them: a
slot's state belongs to whatever sequence sits in the slot, and the layer
that reads it starts a sequence at cache length 0 from zero
(``layers/mamba2.py``).

A model some of whose attention layers see only a WINDOW of the last ``w``
keys (``config.n_window_layers`` of them, ``config.window``) keeps those
layers' rows in a third kind of arena, by the window and not by the context:
a RING a slot,

    wkv: (window layers, n_slots, 2, ring_blocks, block_size, n_kv_heads,
          head_dim)

(the two planes OUTSIDE the ring's lines: a run of consecutive blocks is one
run of lines a plane, and one copy of two chunks takes both). Token ``p`` of
the sequence in slot ``s`` lies in ring block ``(p // block_size) %
ring_blocks`` of ``s``, line ``p % block_size``, over whatever
an older lap (or the slot's last request) left there. So the window layers
need no allocator, no table beyond arithmetic, and nothing to free; their
bytes do not move with ``max_seq_len`` or ``n_blocks``; consecutive blocks of
a slot lie side by side in HBM, so the window build of the block walk
fetches a whole tile of them that does not wrap the ring in ONE copy
where a paged pool's scattered blocks are a copy each
(``kernels/paged_attention.py``); and what a line held
before is never seen, because a reader masks by POSITION (line ``r`` holds
the newest position congruent to ``r`` that the sequence has written). All
appends of a step come before any read and one slot may take several rows
of the mixed step's prefill block, so the ring holds the window AND a step's
largest take (``window_ring_blocks``): the later rows' appends then land on
lines that the first row's window has left behind. ``n_layers`` of the block
arenas is the model's ``n_cache_layers`` whatever its window layers are: the
FULL layers only where a layer is of one kind or the other (EXAONE-MoE,
SmallThinker), EVERY layer where a layer keeps both, its window in the ring
and rows for the whole context in the block arenas (an EVA layer,
``layers/eva_attn.py``: ``n_window_layers == n_cache_layers == n_layers``).
What a pool with a ring cannot give: a prefix cache (a cached block would
have to carry the window layers' last ``w`` rows at its boundary:
``prefix_cacheable``).

ROWS THAT STAND FOR SEVERAL TOKENS. A model may state how many tokens one
row of the block arenas stands for (``config.kv_row_tokens``; every model
that states nothing: 1). An EVA layer's row is the summary of a CHUNK of 16
positions, so a sequence of ``n`` tokens has ``ceil(n / 16)`` rows and owns
``ceil(ceil(n / 16) / block_size)`` blocks: ``blocks_needed`` takes the
tokens a row stands for, and the table width, ``ensure``, ``truncate``,
admission and the live share sampled for ``kv_used_share_peak`` follow from
it. Such a model's ring block is one chunk (``block_size ==
kv_row_tokens``): the producer of the summaries reads a chunk as one block.

Plus a HOST-side free-list allocator mapping sequences onto blocks. A
sequence of ``n`` tokens owns ``ceil(n / block_size)`` blocks, listed in
order in its block table; internal fragmentation is bounded by one block
per sequence (the vLLM argument) instead of one ``max_length`` row per
request, so a fixed HBM budget serves many more concurrent sequences.

Device arrays are a functional pytree (``PagedKVState``) updated in place
under jit via buffer donation, exactly like ``KVCache``; the pool is
sharded over the TP axis on the kv-head dim (``KVCache.spec``, one position
further right: the planes sit between the block and its lines).

``PagedKVState`` is the ONE description of the pool's format: which arenas
exist and (``paged_state_specs``) how each is sharded. The compiled steps,
``Engine._make_sm`` and the model classes pass it whole — in as the one
donated operand, through the layer scan as carry, out as one result — and
only the attention layers (``layers/tp_attn.py``, ``layers/mla_attn.py``,
``layers/eva_attn.py``), which need the arrays, read its fields. A format
that needs another arena adds a field here and reads it in its own layer.

The allocator is deliberately plain Python: allocation decisions are
host-side control flow between compiled steps (the reference engine makes
its CUDA-graph-replay decisions on host the same way), and the device step
consumes only the resulting (block_tables, offsets, slot_mask) DATA — so
alloc/free churn never retraces anything.

Prefix caching (serving/prefix_cache.py) adds a third block state beside
free and owned: CACHE-RESIDENT. A cached block holds the KV of one
content-addressed token chunk and carries a reference count — the number
of sequence tables currently containing it. ``ensure`` ADOPTS cached
blocks at admission (incref, no allocation) instead of re-prefilling
them, ``release`` decrements instead of freeing (the block stays resident
for the next match), and a block whose prefix only partially matches is
adopted by COPY-ON-WRITE — one device-side block copy into a private
block the sequence may then overwrite. Unreferenced-but-resident blocks
are the LRU eviction pool: when the free list runs short, ``ensure``
reclaims through the attached cache before giving up. The partition
free ∪ private-owned ∪ cached is exact and ``check_invariants`` proves it
(including refcount == table-occurrence agreement) after every mutation.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from triton_distributed_tpu.models.kv_cache import KVCache
from triton_distributed_tpu.resilience import faults as _faults
from triton_distributed_tpu.runtime.platform import on_tpu


#: Wire dtypes the pool can quantize KV storage into. ``"fp8"`` is the
#: serving-facing alias for ``float8_e4m3fn`` (the forward-pass fp8
#: format; e5m2's extra exponent bit buys range KV values never use).
KV_WIRE_DTYPES = {
    "int8": jnp.int8,
    "fp8": jnp.float8_e4m3fn,
    "float8_e4m3fn": jnp.float8_e4m3fn,
}

#: How a K+V pool lays a block out: its K plane and its V plane side by
#: side in ONE arena (``PagedKVState.kv``). Named in ``KVPool.geometry`` and
#: ``kv_fingerprint``, so a checkpoint manifest or a cached block written
#: under the two-arena layout before it is refused, not adopted.
KV_LAYOUT = "paired"

#: Version tag of the per-row symmetric absmax scheme (layers/nn.py
#: ``quantize_kv_rows``). Bump on ANY change to the quantization math —
#: the fingerprint is what stops a cached block quantized under an old
#: scheme from being adopted into a new-scheme pool.
KV_QUANT_SCHEME = "rowmax:v1"


def resolve_kv_dtype(config, kv_dtype):
    """Map a ``kv_dtype`` knob value to a concrete wire dtype.

    ``None`` (and the config dtype itself, by name or dtype object) means
    unquantized storage in ``config.dtype``; ``"int8"``/``"fp8"`` select a
    quantized wire format. Returns ``(jnp.dtype, quantized: bool)``.
    """
    if kv_dtype is None:
        return jnp.dtype(config.dtype), False
    if isinstance(kv_dtype, str) and kv_dtype in KV_WIRE_DTYPES:
        return jnp.dtype(KV_WIRE_DTYPES[kv_dtype]), True
    dt = jnp.dtype(kv_dtype)
    if dt == jnp.dtype(config.dtype):
        return dt, False
    if dt in (jnp.dtype(jnp.int8), jnp.dtype(jnp.float8_e4m3fn)):
        return dt, True
    raise ValueError(
        f"unsupported kv_dtype {kv_dtype!r}: expected None, "
        f"{sorted(KV_WIRE_DTYPES)}, or the model dtype "
        f"{jnp.dtype(config.dtype).name!r}")


@functools.lru_cache(maxsize=32)
def _zeros_fn(shape, dtype, sharding):
    return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sharding)


def _zeros(shape, dtype, sharding):
    """A zero array allocated directly under ``sharding`` (None = default
    device): every device fills only its own shard. The tiny program is
    memoized per (shape, dtype, sharding) — K and V share one."""
    return _zeros_fn(tuple(shape), jnp.dtype(dtype), sharding)()


def blocks_needed(n_tokens: int, block_size: int,
                  row_tokens: int = 1) -> int:
    """THE block-rounding rule: ``ceil(rows / block_size)`` for the
    ``ceil(n_tokens / row_tokens)`` rows the tokens have (one a token for
    every model that states nothing: ``ceil(n_tokens / block_size)``). One
    definition shared by allocation (``KVPool.blocks_for``) and admission
    accounting (``Scheduler.admit``) so the two can never disagree on how
    many blocks a sequence costs."""
    return math.ceil(math.ceil(n_tokens / row_tokens) / block_size)


def row_tokens(config) -> int:
    """Tokens one row of the block arenas stands for, as the model's
    configuration states it; 1 for a model that states nothing."""
    return int(getattr(config, "kv_row_tokens", 1) or 1)


#: Planes of a block of a K+V arena: 0 the keys, 1 the values.
KV_PLANES = 2


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVState:
    """Device half of the pool: the block arrays (functional pytree).

    Quantized pools (``kv_dtype="int8"|"fp8"``) carry one extra array:
    per-row f32 dequantization scales, shaped like the K+V arena minus
    head_dim. A field that is ``None`` is an arena the format does not
    have (an empty pytree subtree): the tree's structure IS the format.
    A ROW arena (``ROW_ARENAS``) keeps (layer, block) as its two leading
    axes, a PER-SLOT arena (the fields after them, named as the model's
    ``slot_state_shapes`` names them) keeps (state layer, slot).
    """

    # (n_layers, n_blocks, 2, block_size, n_kv_heads, head_dim): a block's
    # K plane and V plane side by side; a latent pool's one row is both
    # and its arena has no planes, (n_layers, n_blocks, block_size, row)
    kv: jax.Array
    # (n_layers, n_blocks, 2, block_size, n_kv_heads) float32
    kv_scale: jax.Array | None = None
    # (state layers, n_slots, heads, head width, state width) float32: the
    # state of a recurrence, one a slot and layer
    ssm: jax.Array | None = None
    # (state layers, n_slots, (d_conv - 1) * conv width): the last inputs
    # of a causal convolution, oldest first
    conv: jax.Array | None = None
    # (window layers, n_slots, 2, ring_blocks, block_size, n_kv_heads,
    # head_dim): the ring storage of the layers that see a window of keys,
    # the planes outside a slot's lines
    wkv: jax.Array | None = None

    @property
    def latent(self) -> bool:
        return self.kv.ndim == 4

    @property
    def n_blocks(self) -> int:
        return self.kv.shape[1]

    @property
    def block_size(self) -> int:
        return self.kv.shape[2 if self.latent else 3]


ROW_ARENAS = ("kv", "kv_scale")


def window_kind(config) -> tuple[int, int]:
    """(layers that keep a window of rows, the window) as the model's
    configuration states them; (0, 0) for a model that states none."""
    n = int(getattr(config, "n_window_layers", 0) or 0)
    return (n, int(config.window)) if n else (0, 0)


def window_ring_blocks(window: int, block_size: int, max_take: int) -> int:
    """Blocks of one slot's ring: the positions live in a step are the
    ``window - 1`` behind the step's first query and its ``max_take`` new
    tokens, and no two of them may share a line."""
    return blocks_needed(window - 1 + max(1, max_take), block_size)


def paged_state_specs(config, axis: str = "tp", *,
                      quant: bool = False) -> PagedKVState:
    """The pool's ``PartitionSpec``s as a ``PagedKVState`` of the structure
    the pool's state has: the K+V arena sharded over ``axis`` on the
    kv-head dim (``KVCache.spec`` with the planes' axis before the lines),
    a quantized pool's scale arena the same minus head_dim; a latent pool
    (``config.kv_row_shapes`` names no V row) is one replicated arena,
    its row shared by every head. The per-slot arenas a model states
    (``config.slot_state_shapes``) are replicated: no model shards them
    yet. ``KVPool`` allocates under these and the paged step's shard_map
    takes them as the state's in/out specs."""
    latent = config.kv_row_shapes[1] is None

    def paired(spec):           # (layer, block, PLANE, line, ...)
        return PartitionSpec(*spec[:2], None, *spec[2:])

    kv = PartitionSpec() if latent else paired(KVCache.spec(axis)[0])
    scale = paired(KVCache.scale_spec(axis)) if quant else None
    # (the ring storage of window layers is replicated too: no model runs
    # them on more than one device yet)
    ring = PartitionSpec() if window_kind(config)[0] else None
    return PagedKVState(kv=kv, kv_scale=scale, wkv=ring,
                        **dict.fromkeys(config.slot_state_shapes or (),
                                        PartitionSpec()))


def paged_state_shapes(config, *, n_blocks: int, block_size: int,
                       n_slots: int | None = None, kv_dtype=None,
                       max_take: int | None = None) -> PagedKVState:
    """The pool's state as ``jax.ShapeDtypeStruct``s, a ``PagedKVState`` of
    the structure ``paged_state_specs`` gives: what ``KVPool`` allocates,
    and what a compile rehearsal hands the step in place of arrays. Row
    arena ``(n_cache_layers, n_blocks, 2, block_size, *row)`` in the wire
    dtype (no planes where the model names no V row; the scale arena the
    same minus the row's width, float32), per-slot
    arenas ``(n_state_layers, n_slots, *shape)`` as the model states them,
    window storage ``(n_window_layers, n_slots, 2, ring_blocks, block_size,
    *row)`` with ``ring_blocks`` from the window and ``max_take``, the most
    tokens one slot appends in a step: a model with window layers has no
    default for it (a ring built for a smaller take than a step's is
    overwritten under the step's first row, and the mask by position reads
    the newer rows as valid keys). A layer may pair a ring with rows in
    the block arenas only as a model whose rows stand for several tokens
    (``kv_row_tokens``: an EVA layer's chunk summaries) does: the ring's
    block is then one such row's tokens, and another ``block_size`` is
    refused."""
    dtype, quant = resolve_kv_dtype(config, kv_dtype)
    k_row, v_row = config.kv_row_shapes
    n_window, window = window_kind(config)
    ring = None
    if row_tokens(config) != 1 and (not n_window
                                    or block_size != row_tokens(config)):
        raise ValueError(
            f"a row of the block arenas stands for {row_tokens(config)} "
            f"tokens: the rows are summaries of chunks that wait in a ring, "
            f"so the model needs window layers and a ring block of one "
            f"chunk (block_size == {row_tokens(config)}, got {block_size})")
    if n_window:
        if n_slots is None or max_take is None or quant or v_row is None:
            raise ValueError(
                "window layers keep a ring of K and V rows for each slot in "
                "the model dtype: the pool needs n_slots and max_take (the "
                "most tokens one slot appends in a step), and has no "
                "quantized or latent build of them (a model pairs a ring "
                "with rows in the SAME layer by stating kv_row_tokens: an "
                "EVA layer; window layers beside full ones state nothing)")
        ring = jax.ShapeDtypeStruct(
            (n_window, n_slots, KV_PLANES,
             window_ring_blocks(window, block_size, max_take), block_size,
             *k_row), dtype)
    planes = () if v_row is None else (KV_PLANES,)
    rows = jax.ShapeDtypeStruct(
        (config.n_cache_layers, n_blocks, *planes, block_size, *k_row),
        dtype)
    scale = (jax.ShapeDtypeStruct(rows.shape[:-1], jnp.float32)
             if quant else None)
    slot_state = config.slot_state_shapes or {}
    if slot_state and n_slots is None:
        raise ValueError(
            f"{sorted(slot_state)}: the model keeps a state for each slot; "
            f"the pool needs n_slots to build its arenas")
    return PagedKVState(
        kv=rows, kv_scale=scale, wkv=ring,
        **{name: jax.ShapeDtypeStruct(
            (config.n_state_layers, n_slots, *shape), jnp.dtype(dt))
           for name, (shape, dt) in slot_state.items()})


class KVPool:
    """Fixed block pool + free-list allocator + per-sequence block tables.

    ``n_blocks`` blocks of ``block_size`` tokens each; ``max_seq_len``
    bounds any one sequence (sets the fixed block-table width the compiled
    step sees). ``mesh``/``axis`` shard the kv-head dim like ``KVCache``.
    ``n_slots``: the serving batch's width, which a model with per-slot
    state (``config.slot_state_shapes``) or window layers needs its arenas
    built for. ``max_take``: the most tokens one slot appends in a step
    (the mixed step's prefill block, whole), which sizes a window layer's
    ring: required of a model with window layers, not read of any other.
    """

    def __init__(self, config, *, n_blocks: int, block_size: int = 16,
                 max_seq_len: int | None = None, mesh=None, axis: str = "tp",
                 kv_dtype=None, n_slots: int | None = None,
                 max_take: int | None = None):
        if n_blocks <= 0 or block_size <= 0:
            raise ValueError(f"bad pool geometry ({n_blocks=}, {block_size=})")
        self.block_size = block_size
        self.n_blocks = n_blocks
        self.max_seq_len = max_seq_len or config.max_length
        #: Tokens one row of the block arenas stands for (module text).
        self.row_tokens = row_tokens(config)
        self.max_blocks_per_seq = blocks_needed(self.max_seq_len, block_size,
                                                self.row_tokens)
        self.kv_dtype, self.kv_quant = resolve_kv_dtype(config, kv_dtype)
        if self.kv_quant and on_tpu():
            raise NotImplementedError(
                f"kv_dtype={self.kv_dtype.name!r} does not compile for the "
                f"chip: Mosaic refuses the fused kernel's per-block scale "
                f"DMA (kernels/paged_attention.py, scale arena "
                f"(n_blocks, block_size, n_kv_heads) f32) with 'Slice "
                f"shape along dimension 2 must be aligned to tiling (128), "
                f"but is {config.n_kv_heads}'. Quantized KV has only ever "
                f"run under the Pallas interpreter; serve with the model "
                f"dtype on a TPU (ROADMAP S5).")
        # What one token's row looks like is the model's to say: per-head
        # K and V rows, or one latent row that is both.
        k_row, v_row = config.kv_row_shapes
        self.latent = v_row is None
        self._row_width = k_row[-1]
        if self.latent and self.kv_quant:
            raise NotImplementedError(
                "a latent pool has no quantized build (its one row is both "
                "key and value; the per-head row scales do not apply)")
        # ... and what each SLOT holds beside them, if anything.
        self.slot_state = dict(config.slot_state_shapes or {})
        if self.slot_state and self.kv_quant:
            raise NotImplementedError(
                "a pool with per-slot state has no quantized build")
        # ... and which layers keep only a window of rows, in a ring a slot.
        self.window_layers, self.window = window_kind(config)
        self.max_take = max_take
        #: PartitionSpecs of ``state``, leaf for leaf.
        self.specs = paged_state_specs(config, axis, quant=self.kv_quant)
        # Each arena is born in its sharded layout: ``jnp.zeros`` +
        # ``device_put`` would build the WHOLE pool on the default device
        # first — on four chips, all of it on chip 0 beside its weight
        # shard (``Qwen3.init`` allocates the same way).
        def arena(spec, a):
            if mesh is None:
                return _zeros(a.shape, a.dtype, None)
            from triton_distributed_tpu.runtime.mesh import sharding_for

            return _zeros(a.shape, a.dtype, sharding_for(spec, mesh))

        # (an arena the format does not have is None in both trees: an
        # empty subtree, which the map passes over)
        self.state = jax.tree.map(arena, self.specs, paged_state_shapes(
            config, n_blocks=n_blocks, block_size=block_size,
            n_slots=n_slots, kv_dtype=kv_dtype, max_take=max_take))
        # LIFO free list, low block ids first out — recently freed blocks
        # are reused immediately (warm in whatever cache level they touched).
        self._free: list[int] = list(range(n_blocks - 1, -1, -1))
        self._tables: dict[object, list[int]] = {}
        # Prefix-cache residency: block id -> refcount (number of sequence
        # tables currently containing the block). Keys are the cache-owned
        # blocks; refcount 0 = unreferenced-but-resident (LRU-evictable).
        self._cached: dict[int, int] = {}
        # Cached-block provenance: block id -> kv_fingerprint() at promote
        # time. Within one pool's lifetime every entry matches the pool's
        # own fingerprint (the pool never changes mode), but checkpoint
        # restore / cross-pool bookkeeping bugs would not — ``ensure``
        # refuses to adopt a block whose recorded fingerprint disagrees.
        self._cached_fp: dict[int, str] = {}
        self._cache = None        # attached RadixPrefixCache (LRU reclaim)
        self._cow_jit = None      # compiled-once block copy (lazy)

    # -- allocator ----------------------------------------------------------

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return self.n_blocks - len(self._free)

    @property
    def n_cached(self) -> int:
        """Blocks resident in the prefix cache (referenced or not)."""
        return len(self._cached)

    @property
    def n_reclaimable(self) -> int:
        """Cache-resident blocks with refcount 0 — what an LRU pass could
        return to the free list right now. ``n_free + n_reclaimable`` is
        the admission-visible headroom."""
        return sum(1 for r in self._cached.values() if r == 0)

    @property
    def headroom_frac(self) -> float:
        """Admission-visible headroom as a fraction of the pool:
        (free + reclaimable) / total."""
        return (self.n_free + self.n_reclaimable) / self.n_blocks

    def reclaim_to(self, target_free_frac: float) -> int:
        """Evict unreferenced cached blocks through the attached prefix
        cache until ``n_free/n_blocks`` reaches ``target_free_frac`` (or
        the reclaimable supply runs out). The adaptive controller's
        eviction-aggressiveness actuator: pure host-side free-list motion,
        never touches a referenced block. Returns blocks freed."""
        if self._cache is None:
            return 0
        target_free = min(self.n_blocks,
                          int(float(target_free_frac) * self.n_blocks
                              + 0.5))
        need = target_free - self.n_free
        if need <= 0:
            return 0
        return self._cache.evict(min(need, self.n_reclaimable))

    def blocks_for(self, n_tokens: int) -> int:
        return blocks_needed(n_tokens, self.block_size, self.row_tokens)

    def geometry(self) -> dict:
        """JSON-safe pool geometry for checkpoint manifests
        (resilience/checkpoint.py): restore validates the rebuilt fleet's
        pools against this — the KV BYTES are never serialized (restored
        requests recompute them via prefill), but mismatched geometry
        would change admission/preemption decisions and break the
        bit-identical-resume contract."""
        geo = {"n_blocks": self.n_blocks, "block_size": self.block_size,
               "max_seq_len": self.max_seq_len,
               "max_blocks_per_seq": self.max_blocks_per_seq,
               "kv_dtype": self.kv_dtype.name}
        if not self.latent:
            # K's and V's rows of a block are one run of bytes: a manifest
            # written over two arenas names no layout and is refused
            geo["layout"] = KV_LAYOUT
        if self.row_tokens != 1:
            geo["row_tokens"] = self.row_tokens
        if self.slot_state:
            geo["slot_state"] = {
                name: list(getattr(self.state, name).shape)
                for name in sorted(self.slot_state)}
        if self.window_layers:
            geo["window"] = {
                "layers": self.window_layers, "window": self.window,
                "max_take": self.max_take,
                "ring_blocks": self.state.wkv.shape[3],
                "bytes": self.window_bytes}
        return geo

    @property
    def slot_state_bytes(self) -> int:
        """Bytes of the per-slot arenas (0 for a pool of rows only)."""
        return sum(getattr(self.state, name).nbytes
                   for name in self.slot_state)

    @property
    def window_bytes(self) -> int:
        """Bytes of the window layers' ring storage (0 without any): fixed
        by the window, the step's take and the slots, whatever
        ``max_seq_len`` and ``n_blocks`` are."""
        return self.state.wkv.nbytes if self.window_layers else 0

    @property
    def prefix_cacheable(self) -> bool:
        """Whether a cached block is all that a later request needs of a
        prefix. Not where a layer keeps a state a slot (the block holds
        rows, not the state at its boundary), nor where a layer keeps a
        window of rows a slot (the block would have to carry that layer's
        last ``window`` rows at its boundary)."""
        return not self.slot_state and not self.window_layers

    def kv_fingerprint(self) -> str:
        """Wire-format identity of this pool's KV bytes:
        ``dtype:scheme:layout`` (e.g. ``"int8:rowmax:v1:paired"``,
        ``"bfloat16:none:paired"``). Adoption of a cached block is only
        legal between identical fingerprints — the block's stored bytes are
        meaningless under any other (dtype, quantization scheme, layout)
        triple: a block id recorded over two arenas names no bytes of this
        one."""
        scheme = KV_QUANT_SCHEME if self.kv_quant else "none"
        scheme += (f":latent{self._row_width}" if self.latent
                   else f":{KV_LAYOUT}")
        if self.slot_state:
            scheme += ":slot[" + "+".join(sorted(self.slot_state)) + "]"
        if self.window_layers:
            scheme += f":window{self.window}x{self.window_layers}"
        if self.row_tokens != 1:
            scheme += f":row{self.row_tokens}"
        return f"{self.kv_dtype.name}:{scheme}"

    def owned(self, seq_id) -> int:
        """Blocks currently owned by ``seq_id`` (0 if unknown)."""
        return len(self._tables.get(seq_id, ()))

    def ensure(self, seq_id, n_tokens: int, *, adopt=(),
               cow_src: int | None = None) -> bool:
        """Grow ``seq_id``'s table until it covers ``n_tokens`` tokens.
        Returns False (allocating NOTHING, adopting NOTHING) if the free
        list — after an LRU reclaim through the attached prefix cache —
        can't cover the growth; all-or-nothing keeps admission/preemption
        decisions clean.

        ``adopt`` (admission-time only, the sequence must be NEW) is a
        list of cache-resident block ids that become the table's prefix by
        REFERENCE: each is increfed, none is allocated, and the sequence
        must never write into them (the engine starts prefill past the
        adopted tokens). ``cow_src`` names one more cache-resident block
        whose prefix only partially matches: it is adopted by COPY-ON-
        WRITE — a fresh private block is drawn from the free list, the
        source block's K/V rows are copied on device, and the sequence may
        then overwrite the divergent tail of the COPY.

        Fault site ``pool.ensure``: an installed ``FaultPlan`` may raise
        ``TransientFault`` here (before any mutation, so the allocator
        state — including every cache refcount — is untouched; callers
        retry or degrade).
        """
        if _faults._PLAN is not None:
            _faults.fire("pool.ensure")
        if n_tokens > self.max_seq_len:
            raise ValueError(f"sequence length {n_tokens} exceeds pool "
                             f"max_seq_len {self.max_seq_len}")
        table = self._tables.get(seq_id)
        adopt = list(adopt)
        adopting = bool(adopt) or cow_src is not None
        if adopting and table is not None:
            raise ValueError(
                f"cache adoption for {seq_id!r} is admission-time only: "
                f"the sequence already owns a table")
        here = self.kv_fingerprint()
        for b in adopt + ([cow_src] if cow_src is not None else []):
            if b not in self._cached:
                raise KeyError(f"adopting block {b} that is not "
                               f"cache-resident")
            fp = self._cached_fp.get(b, here)
            if fp != here:
                raise ValueError(
                    f"adopting block {b} quantized as {fp!r} into a "
                    f"{here!r} pool: mixed-dtype adoption would hand the "
                    f"sequence bytes from an incompatible wire format")
        n_cow = 1 if cow_src is not None else 0
        have = (len(table) if table is not None
                else len(adopt) + n_cow)
        need = self.blocks_for(n_tokens) - have   # fresh private blocks
        if adopting and need < 0:
            raise ValueError("adopted prefix longer than the sequence")
        draw = need + n_cow                       # drawn from the free list
        if draw <= 0 and not adopting:
            return True
        if draw > len(self._free) and self._cache is not None:
            # LRU reclaim: evict unreferenced cached blocks — but never
            # the ones this very call is about to adopt.
            pinned = frozenset(adopt)
            if cow_src is not None:
                pinned |= {cow_src}
            self._cache.evict(draw - len(self._free), exclude=pinned)
        if draw > len(self._free):
            # All-or-nothing, including the table entry itself: a rejected
            # brand-new sequence must not leave an empty table behind (an
            # empty table is indistinguishable from a released-then-
            # resurrected ghost; check_invariants flags both). Refcounts
            # are equally untouched — adoption never half-happens.
            return False
        new_blocks: list[int] = []
        if cow_src is not None:
            dst = self._free.pop()
            self._copy_block_device(cow_src, dst)
            new_blocks.append(dst)
        new_blocks.extend(self._free.pop() for _ in range(need))
        if table is None:
            for b in adopt:
                self._cached[b] += 1
            table = self._tables[seq_id] = list(adopt)
        table.extend(new_blocks)
        return True

    def release(self, seq_id) -> None:
        """Return ``seq_id``'s PRIVATE blocks to the free list and decref
        its cache-resident (adopted or promoted) ones — those stay
        resident for the next prefix match; an LRU pass frees them later.

        Unknown (never-ensured or already-released) ``seq_id`` raises —
        the silent no-op it used to be masked double-release bugs, and a
        later ``ensure()`` of the same id would resurrect a stale table
        over freshly-allocated blocks with unrelated KV contents. The
        raise-before-mutate ordering also makes the quarantine path safe:
        a double release can never double-decrement a shared refcount."""
        table = self._tables.pop(seq_id, None)
        if table is None:
            raise KeyError(
                f"release of unknown seq_id {seq_id!r}: never allocated or "
                f"already released (double release?)")
        for b in reversed(table):
            r = self._cached.get(b)
            if r is None:
                self._free.append(b)
            else:
                assert r > 0, f"cached block {b} refcount underflow"
                self._cached[b] = r - 1

    def truncate(self, seq_id, n_tokens: int) -> int:
        """Speculative-decoding rollback primitive: shrink ``seq_id``'s
        table to exactly ``blocks_for(n_tokens)`` blocks, returning the
        now-empty tail blocks to the free list (PRIVATE blocks) or
        decrefing them (cache-resident adopted/promoted blocks — they stay
        resident for the next prefix match, exactly like ``release``).

        The rejected-suffix KV rows inside the LAST kept block are left in
        place: the slot's kv frontier (``offsets``/``seq_lens`` step
        operands) already excludes them from attention, and the next
        accepted token overwrites them — device memory is never touched.

        ``n_tokens`` must be >= 1 (a live sequence always covers its
        pending token; shrinking to zero is ``release``'s job — an empty
        table is an invariant violation) and must not exceed the current
        table's capacity (truncate never grows; that's ``ensure``).
        Returns the number of blocks returned to the free list (decrefed
        cached blocks are kept resident and not counted). Pure host-side
        free-list motion — fault sites don't fire here, so rollback can
        never half-happen."""
        table = self._tables.get(seq_id)
        if table is None:
            raise KeyError(
                f"truncate of unknown seq_id {seq_id!r}: never allocated "
                f"or already released")
        if n_tokens < 1:
            raise ValueError(
                f"truncate to {n_tokens} tokens would leave an empty "
                f"table; use release() to retire the sequence")
        keep = self.blocks_for(n_tokens)
        if keep > len(table):
            raise ValueError(
                f"truncate cannot grow: {seq_id!r} owns {len(table)} "
                f"blocks, {n_tokens} tokens need {keep}")
        freed = 0
        while len(table) > keep:
            b = table.pop()
            r = self._cached.get(b)
            if r is None:
                self._free.append(b)
                freed += 1
            else:
                assert r > 0, f"cached block {b} refcount underflow"
                self._cached[b] = r - 1
        return freed

    # -- prefix-cache residency (serving/prefix_cache.py drives these) ------

    def attach_cache(self, cache) -> None:
        """Register the prefix cache as this pool's LRU reclaim provider
        (``ensure`` calls ``cache.evict`` when the free list runs short).
        One cache per pool; pass None to detach."""
        if cache is not None and self._cache is not None:
            raise RuntimeError("pool already has an attached prefix cache")
        self._cache = cache

    def is_cached(self, block: int) -> bool:
        return block in self._cached

    def refs(self, block: int) -> int:
        """Refcount of a cache-resident block (KeyError if not cached)."""
        return self._cached[block]

    def promote_to_cached(self, seq_id, block: int) -> None:
        """Transfer one of ``seq_id``'s PRIVATE blocks into cache
        residency (called by ``RadixPrefixCache.insert`` when a finished
        sequence contributes a new chunk). The block stays in the table —
        its refcount starts at 1 and drops to 0 at the table's release."""
        table = self._tables.get(seq_id)
        if table is None or block not in table:
            raise KeyError(f"promote of block {block} not owned by "
                           f"{seq_id!r}")
        if block in self._cached:
            raise ValueError(f"block {block} is already cache-resident")
        self._cached[block] = 1
        self._cached_fp[block] = self.kv_fingerprint()

    def uncache(self, block: int) -> None:
        """Cache eviction endpoint: drop residency and free the block.
        Only legal for UNREFERENCED cached blocks — evicting under a live
        reader would hand its KV to the next allocator customer."""
        r = self._cached.get(block)
        if r is None:
            raise KeyError(f"uncache of non-resident block {block}")
        if r:
            raise ValueError(f"uncache of block {block} with {r} live "
                             f"references")
        del self._cached[block]
        self._cached_fp.pop(block, None)
        self._free.append(block)

    def _copy_block_device(self, src: int, dst: int) -> None:
        """Copy-on-write kernel: duplicate block ``src``'s rows (both
        planes, every layer) into ``dst`` on device — and, in a quantized
        pool, the block's scale rows with them (a wire-dtype row without its scale
        is garbage; scales MOVE with their blocks). Compiled ONCE per pool
        — src/dst are traced scalars, so CoW churn never retraces — with
        all pool arrays donated (the copy is in-place for HBM accounting,
        like the steps)."""
        if self._cow_jit is None:
            @functools.partial(jax.jit, donate_argnums=(0,))
            def cow(state, s, d):
                # the ROW arenas only: a per-slot arena's second axis is
                # the slot, and a block's copy leaves it alone
                return dataclasses.replace(state, **{
                    f: a.at[:, d].set(a[:, s]) for f in ROW_ARENAS
                    if (a := getattr(state, f)) is not None})

            self._cow_jit = cow
        self.state = self._cow_jit(
            self.state, jnp.asarray(src, jnp.int32),
            jnp.asarray(dst, jnp.int32))

    def fragmentation(self) -> dict:
        """Free-list fragmentation stats for the perf flight recorder:
        ``free_blocks`` (allocatable headroom), ``largest_free_run``
        (longest run of CONSECUTIVE free block ids — the best a streaming
        reader can hope to touch sequentially), and ``frag_frac``
        (1 - largest_run/free, 0.0 = one contiguous extent, -> 1.0 = free
        space shredded across the pool). Allocation itself never needs
        contiguity (any free block serves), so this is an observability
        stat, not an allocator constraint: block-size sweeps in the run DB
        (``BatchEngine.perfdb_sample``) use it to tell whether a latency
        shift came from pool shredding or from the kernel."""
        free = sorted(self._free)
        longest = run = 0
        prev = None
        for b in free:
            run = run + 1 if prev is not None and b == prev + 1 else 1
            longest = max(longest, run)
            prev = b
        frag = 0.0 if not free else 1.0 - longest / len(free)
        return {"free_blocks": len(free), "largest_free_run": longest,
                "frag_frac": round(frag, 4),
                "cached_blocks": len(self._cached)}

    def table(self, seq_id) -> list[int]:
        return list(self._tables.get(seq_id, ()))

    def padded_tables(self, seq_ids) -> np.ndarray:
        """(len(seq_ids), max_blocks_per_seq) int32 — slot-ordered block
        tables, zero-padded (None entries = empty slots), the fixed-shape
        operand the compiled step consumes.

        An UNKNOWN non-None seq_id raises ``KeyError`` (mirroring the
        ``release`` hardening): the all-zero row it used to emit silently
        is indistinguishable from a real table pointing at block 0, so a
        bookkeeping bug upstream would read another sequence's KV instead
        of crashing."""
        out = np.zeros((len(seq_ids), self.max_blocks_per_seq), np.int32)
        for row, sid in enumerate(seq_ids):
            if sid is None:
                continue
            t = self._tables.get(sid)
            if t is None:
                raise KeyError(
                    f"padded_tables for unknown seq_id {sid!r}: never "
                    f"allocated or already released")
            out[row, :len(t)] = t
        return out

    def check_invariants(self) -> None:
        """Allocator soundness: free ∪ private-owned ∪ cached partition
        the pool EXACTLY — private blocks sit in exactly one table, each
        cached block's refcount equals its table-occurrence count, nothing
        is simultaneously free and resident — and no sequence holds an
        EMPTY table (an empty table is a stale ghost — released or never
        funded — that a later ``ensure()`` would silently resurrect)."""
        owned = [b for t in self._tables.values() for b in t]
        occ = collections.Counter(owned)
        private = [b for b in owned if b not in self._cached]
        assert len(set(private)) == len(private), "private block owned twice"
        assert len(set(self._free)) == len(self._free), "free list duplicate"
        free_set = set(self._free)
        assert not (set(owned) & free_set), "block both free and owned"
        assert not (set(self._cached) & free_set), "block both free and cached"
        for b, r in self._cached.items():
            assert occ.get(b, 0) == r, (
                f"cached block {b}: refcount {r} != {occ.get(b, 0)} table "
                f"occurrences")
        assert (len(private) + len(self._cached) + len(self._free)
                == self.n_blocks), "blocks leaked"
        assert all(0 <= b < self.n_blocks
                   for b in owned + self._free + list(self._cached))
        empty = [sid for sid, t in self._tables.items() if not t]
        assert not empty, f"empty (stale) tables for seq_ids {empty!r}"
        # No table is wider than the step's operand: ``max_seq_len`` tokens
        # at ``row_tokens`` tokens a row.
        assert self.max_blocks_per_seq == blocks_needed(
            self.max_seq_len, self.block_size, self.row_tokens) and all(
            len(t) <= self.max_blocks_per_seq
            for t in self._tables.values()), "a table past the step's width"
        # Quantized-mode soundness: every cache-resident block carries a
        # recorded wire fingerprint (and ONLY residents do), and the scale
        # arenas exist iff the pool is quantized, shaped like the K/V
        # arenas minus head_dim — scales partition with their blocks.
        assert set(self._cached_fp) == set(self._cached), (
            "cached-block fingerprints out of sync with residency")
        st = self.state
        if self.kv_quant:
            assert st.kv_scale is not None, (
                "quantized pool missing its scale arena")
            assert st.kv_scale.shape == st.kv.shape[:-1], (
                f"scale arena shape {st.kv_scale.shape} != KV arena rows "
                f"{st.kv.shape[:-1]}")
            assert st.kv_scale.dtype == jnp.float32
        else:
            assert st.kv_scale is None, (
                "unquantized pool carrying a scale arena")
        assert st.kv.dtype == self.kv_dtype, (
            f"pool arena dtype {st.kv.dtype} != declared {self.kv_dtype}")
        # One latent arena of rows, or one arena of K and V planes.
        if self.latent:
            assert st.kv.ndim == 4, "a latent pool holds one 4-D arena"
        else:
            assert st.kv.ndim == 6 and st.kv.shape[2] == KV_PLANES, (
                f"a K+V arena keeps two planes a block: {st.kv.shape}")
        # The window storage exists iff the model has window layers: one
        # ring a (window layer, slot), two planes of rows as the arena's.
        if not self.window_layers:
            assert st.wkv is None, "pool carrying an unasked arena wkv"
        else:
            ring = window_ring_blocks(self.window, self.block_size,
                                      self.max_take)
            assert st.wkv is not None and st.wkv.dtype == self.kv_dtype, (
                "pool missing its window storage wkv")
            assert st.wkv.shape[0] == self.window_layers \
                and st.wkv.shape[2:4] == (KV_PLANES, ring) \
                and st.wkv.shape[4:] == st.kv.shape[3:], (
                    f"window storage wkv: {st.wkv.shape}")
        # The per-slot arenas are the ones the model states, one entry a
        # (state layer, slot) each.
        for f in dataclasses.fields(st):
            if f.name in ROW_ARENAS or f.name == "wkv":
                continue
            a = getattr(st, f.name)
            if f.name not in self.slot_state:
                assert a is None, f"pool carrying an unasked arena {f.name}"
                continue
            shp, dtype = self.slot_state[f.name]
            assert a is not None, f"pool missing its per-slot arena {f.name}"
            assert a.shape[2:] == tuple(shp) and a.dtype == jnp.dtype(dtype), (
                f"per-slot arena {f.name}: {a.shape} {a.dtype}")
        assert len({getattr(st, n).shape[:2] for n in self.slot_state}) <= 1, (
            "per-slot arenas differ in (state layers, slots)")
