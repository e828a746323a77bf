"""KV cache memory pool for the serving engine: fixed-size token blocks.

The pool is the vLLM answer (PagedAttention, Kwon et al., SOSP '23)
shaped for XLA's static-shape world: fixed-size token BLOCKS in a global
pool,

    k, v: [L, NUM_BLOCKS + 1, BLOCK, H·Dh]       (physical block 0 = trash)

(a position's K, or V, of every head is ONE contiguous row: the shape on
which the row write ``pool.at[layer, block, offset]``, the attention
kernels' block ``(layer, table[...], :, head group)`` and the array's
resting layout agree, so a serving program carries the pool through its
layer loop in one buffer and one layout, written in place and never
copied — tests/test_chip_compile.py holds the compiler to that) plus
per-slot block tables (host-side lists of physical block ids).  The
decode step reads each slot's logical view through its block table —
the tables are plain i32 *values*, structurally stable, so block churn
never recompiles the fused decode program — and occupancy is bounded by
tokens (rounded up to blocks), not by requests.  Physical block 0 is a
reserved trash row: inactive decode rows and padded prefill tails scatter
their garbage writes there, so a freed-and-reused block can never be
corrupted by a stale slot's static-shape write.

On top of the pool, the radix ``PrefixCache`` keeps *full* prompt blocks
resident after retirement with reference-counted sharing (RadixAttention,
Zheng et al. 2024): requests whose prompt shares a cached full-block
prefix reuse those blocks and prefill only the unshared suffix.  Writes
only ever target exclusively-owned blocks (a request's suffix and
generated tokens land in privately allocated blocks by construction), so
the copy-on-write discipline never actually needs a copy.

THE STATIC-SHAPE INVARIANT: nothing in the device programs depends on how
many requests are live.  Admission/retirement only change the host-side
``lengths``/table arrays fed in as (traced) *values*; slot, block and
refcount bookkeeping are pure host work (SlotAllocator / BlockAllocator
below).

NOT EVERY CACHED LAYER KEEPS K AND V.  A latent-attention layer
(``models/decoder.py`` ``"mla"``) keeps ONE row a position, ``c~ || k_r``
padded to whole 128-lane columns, shared by all its heads, and no values at
all (they are the row's first lanes): its pool is ONE array

    k: [L_mla, NUM_BLOCKS + 1, BLOCK, latent_lanes],   v: None

on which blocks, tables, the trash block, admission, retirement and
quarantine work unchanged.  :func:`kv_geometry` and :func:`kv_arrays` say
which shape a description has; the byte budget, the pool's shape and the
kernels' eligibility all read them.

A SECOND KIND OF CACHE lives beside the blocks for descriptions whose
layers keep a state rather than keys and values (``RecurrentState`` below:
KDA's linear-attention state or a Mamba-2 mixer's, and a short
convolution's tail): one row a decode slot a layer, indexed by the slot
itself, because a state does not grow with the sequence.  The pool then has
layers only for the softmax attention layers, ``[L_attn, NUM_BLOCKS + 1,
BLOCK, KV_HEADS·Dh]`` (or the latent layers, as above); a hybrid layer,
attention beside a Mamba-2 mixer, has both.  Unlike a block, a
state row IS scrubbed: every position reads it, so it is zeroed when its
slot is admitted and when a quarantined slot is released.

Block hygiene: a freed block is NOT scrubbed.  That is safe by
construction: a block is only re-used after prefill or decode writes
every position a new request attends to before it first becomes visible
(the mask admits k_pos <= current position only), stale tables are never
handed to the device, and inactive rows are pointed at block 0.
"""

from __future__ import annotations

import heapq
import math
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp

from trustworthy_dl_tpu.models import decoder


def kv_geometry(cfg: Any) -> Tuple[int, int, int]:
    """``(layers that keep rows in the pool, heads a row, head width)`` of
    a model description: every layer and every head of a ``GPT2Config``, the
    softmax attention layers and their shared K/V heads of a
    ``models.decoder.DecoderConfig``, or its latent layers with ONE head of
    ``latent_lanes`` (the shared row)."""
    if isinstance(cfg, decoder.DecoderConfig):
        if cfg.n_mla_layers:
            return cfg.n_mla_layers, 1, cfg.latent_lanes
        return cfg.n_attn_layers, cfg.kv_heads, cfg.head_dim
    return cfg.n_layer, cfg.n_head, cfg.n_embd // cfg.n_head


def kv_arrays(cfg: Any) -> int:
    """Arrays of that geometry the pool keeps: K and V, or the latent rows
    alone."""
    latent = isinstance(cfg, decoder.DecoderConfig) and cfg.n_mla_layers
    return 1 if latent else 2


def latent_value_lanes(cfg: Any) -> Optional[int]:
    """The lanes of a latent row that are also its values (the paged
    kernels' ``v_lanes``); None for a description that keeps K and V."""
    return cfg.kv_lora_rank if kv_arrays(cfg) == 1 else None


def kv_bytes_per_token(cfg: Any, kv_dtype: Optional[Any] = None) -> int:
    """Bytes ONE cached token position costs under ``kv_dtype`` WITHOUT
    allocating — the HBM-budget primitive (a block costs ``block_size``
    of these, a full sequence ``max_seq``).
    int8 counts 1 byte/element plus the 4-byte per-(head, position)
    scale, K and V each.  A latent layer costs its ONE row, padding
    included, and no V term."""
    kv_dtype = cfg.dtype if kv_dtype is None else kv_dtype
    layers, kv_heads, dh = kv_geometry(cfg)
    heads = layers * kv_heads
    if kv_dtype == jnp.int8:
        return kv_arrays(cfg) * heads * (dh + 4)
    itemsize = jnp.zeros((), kv_dtype).dtype.itemsize
    return kv_arrays(cfg) * heads * dh * itemsize


def paged_pool_blocks(cfg: Any, hbm_bytes: int, block_size: int,
                      kv_dtype: Optional[Any] = None) -> int:
    """Largest USABLE block count whose paged pool (including the +1
    trash block the layout always carries) fits in ``hbm_bytes``."""
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    per_block = block_size * kv_bytes_per_token(cfg, kv_dtype)
    return max(int(hbm_bytes // per_block) - 1, 0)


def validate_paged_geometry(max_seq: int, block_size: int,
                            num_blocks: Optional[int],
                            prefill_chunk: Optional[int]) -> None:
    """Loud construction-time validation of the paged-pool knobs —
    shared by ``core.config.ServeConfig`` and the paged scheduler so a
    bad geometry fails where the operator typed it."""
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    if max_seq % block_size != 0:
        raise ValueError(
            f"max_seq={max_seq} must be a multiple of block_size="
            f"{block_size} (the paged pool addresses whole blocks)"
        )
    if num_blocks is not None and num_blocks < max_seq // block_size:
        raise ValueError(
            f"num_blocks={num_blocks} cannot hold even one full "
            f"sequence (max_seq={max_seq} needs "
            f"{max_seq // block_size} blocks of {block_size})"
        )
    if prefill_chunk is not None:
        if (prefill_chunk % block_size != 0
                or not block_size <= prefill_chunk <= max_seq):
            raise ValueError(
                f"prefill_chunk={prefill_chunk} must be a multiple of "
                f"block_size={block_size} in [{block_size}, {max_seq}]"
            )


def resolve_prefill_chunk(max_seq: int, block_size: int,
                          prefill_chunk: Optional[int]) -> int:
    """``None`` -> the auto chunk: 64 positions (rounded down to a block
    multiple), clamped to ``max_seq``.  Explicit values were already
    validated by :func:`validate_paged_geometry`."""
    if prefill_chunk is not None:
        return prefill_chunk
    return max(block_size, (min(64, max_seq) // block_size) * block_size)


class SlotAllocator:
    """Host-side slot lifecycle: free list + quarantine set.

    Quarantine is the serving mirror of the training trust gate: a slot
    whose request was flagged anomalous leaves the pool (capacity shrinks,
    visible in the occupancy metric) until an operator releases it —
    matching the training-side COMPROMISED → probation → readmission
    ladder, where re-entry is also an explicit decision, not automatic."""

    def __init__(self, max_slots: int):
        if max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        self.max_slots = max_slots
        # LIFO free list: the most recently freed slot is re-used first,
        # keeping the working set of cache rows small (cache-friendly).
        self._free: List[int] = list(range(max_slots - 1, -1, -1))
        self._quarantined: Set[int] = set()

    def alloc(self) -> Optional[int]:
        """Claim a free slot, or None when the pool is exhausted."""
        return self._free.pop() if self._free else None

    def free(self, slot: int) -> None:
        if slot in self._quarantined:
            return  # quarantined slots never re-enter the pool via free()
        if slot in self._free or not 0 <= slot < self.max_slots:
            raise ValueError(f"double free / bad slot {slot}")
        self._free.append(slot)

    def quarantine(self, slot: int) -> None:
        """Remove a slot from service (flagged-anomalous request)."""
        self._quarantined.add(slot)
        if slot in self._free:
            self._free.remove(slot)

    def release(self, slot: int) -> None:
        """Operator action: return a quarantined slot to the pool."""
        if slot in self._quarantined:
            self._quarantined.discard(slot)
            self._free.append(slot)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def quarantined(self) -> Set[int]:
        return set(self._quarantined)

    @property
    def capacity(self) -> int:
        """Slots currently in service (total minus quarantined)."""
        return self.max_slots - len(self._quarantined)


# ---------------------------------------------------------------------------
# Paged pool (the default serve data path since the paged-KV PR)
# ---------------------------------------------------------------------------

#: Physical block index reserved as the write sink for garbage: inactive
#: decode rows and padded prefill tails scatter here, never into a block
#: another request could own.  The allocator never hands it out.
TRASH_BLOCK = 0


class PagedKV(NamedTuple):
    """Block-pooled KV arrays; block tables and refcounts live host-side.

    Layout ``[L, NUM_BLOCKS + 1, BLOCK, H·Dh]`` — the +1 is the reserved
    trash block (index 0); a position's heads lie side by side in one
    row, so the row write, the kernels' block and the resting layout are
    the same row-major array (see the module docstring).  int8 tier:
    ``k``/``v`` store int8 and the per-(head, position) f32 scales ride
    in ``k_scale``/``v_scale`` ``[L, NUM_BLOCKS + 1, BLOCK, H]`` (the
    values' shape with one number a head) — the pool pages values and
    scales identically, so the equal-HBM ~1.9x
    capacity win of the int8 tier compounds with paging.  A description
    with latent layers keeps its rows in ``k`` ``[L, NUM_BLOCKS + 1, BLOCK,
    latent_lanes]`` and has ``v`` None (no leaf: nothing is allocated,
    carried or donated for it)."""

    k: jax.Array  # [L, NUM_BLOCKS + 1, BLOCK, H·Dh]
    v: Optional[jax.Array]  # None: latent rows, which keep no V half
    k_scale: Optional[jax.Array] = None  # [L, NUM_BLOCKS + 1, BLOCK, H]
    v_scale: Optional[jax.Array] = None

    @property
    def num_blocks(self) -> int:
        """USABLE blocks (the trash block is excluded)."""
        return self.k.shape[1] - 1

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def pool_bytes(self) -> int:
        """Total HBM the pool holds (values + scales, INCLUDING the trash
        block) — the honest number ``tddl_serve_kv_bytes`` reports."""
        total = self.k.nbytes + (0 if self.v is None else self.v.nbytes)
        if self.k_scale is not None:
            total += self.k_scale.nbytes + self.v_scale.nbytes
        return total

    @property
    def bytes_per_block(self) -> int:
        return self.pool_bytes // (self.num_blocks + 1)


def init_paged_pool(cfg: Any, num_blocks: int, block_size: int,
                    kv_dtype: Optional[Any] = None) -> PagedKV:
    """Allocate ``num_blocks`` usable blocks (+1 trash).  ``kv_dtype``
    None follows the model compute dtype, ``jnp.int8`` allocates the
    quantized pool (int8 values + f32 per-(head, position) scales,
    zeros — an untouched block dequantises to exact zeros)."""
    if num_blocks < 1:
        raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
    if block_size > cfg.n_positions:
        raise ValueError(
            f"block_size={block_size} exceeds the model's position table "
            f"(n_positions={cfg.n_positions})"
        )
    kv_dtype = cfg.dtype if kv_dtype is None else kv_dtype
    layers, kv_heads, dh = kv_geometry(cfg)
    shape = (layers, num_blocks + 1, block_size, kv_heads * dh)
    if kv_arrays(cfg) == 1:
        return PagedKV(k=jnp.zeros(shape, kv_dtype), v=None)
    if kv_dtype == jnp.int8:
        # Two buffers, not one array twice: the serving programs donate
        # all four pool arrays, and one buffer cannot be donated twice.
        scale_shape = shape[:3] + (kv_heads,)
        return PagedKV(k=jnp.zeros(shape, jnp.int8),
                       v=jnp.zeros(shape, jnp.int8),
                       k_scale=jnp.zeros(scale_shape, jnp.float32),
                       v_scale=jnp.zeros(scale_shape, jnp.float32))
    return PagedKV(k=jnp.zeros(shape, kv_dtype),
                   v=jnp.zeros(shape, kv_dtype))


# ---------------------------------------------------------------------------
# Recurrent state: the second kind of cache, rows by SLOT beside blocks by
# table
# ---------------------------------------------------------------------------


class RecurrentState(NamedTuple):
    """What the layers that keep a STATE cache, one row a decode slot a
    layer, beside the block pool: the state does not grow with the
    sequence, so a slot owns its row for as long as it owns the slot and no
    table maps it.  Two kinds, each with its own arrays (None where the
    description has no layer of the kind, so no buffer is carried or
    donated): KDA's (``models/kda.py``) and the Mamba-2 mixer's of a hybrid
    layer (``models/mamba2.py``), which keeps K and V in the pool besides.
    The serving programs take it donated and write it in place like the
    pool; a slot's rows are zeroed when its slot is admitted and when a
    quarantined slot is released (:func:`zero_state_rows`).

    Beside it ride the expert layers' counters, accumulated on the device
    by the same programs and pulled only when a summary is asked for (None
    where no layer has experts)."""

    s: Optional[jax.Array]            # KDA: f32 [L_kda, SLOTS, H, dk, dv]
    conv: Optional[jax.Array]         # KDA: f32 [L_kda, SLOTS, K-1, 3·H·dk]
    #: i32 [L_expert, held]: (token, expert) pairs.
    expert_pairs: Optional[jax.Array]
    #: i32 []: tokens fed through an EXPERT layer, a layer each (a leading
    #: dense layer feeds none).  It grows by ``expert layers x tokens`` a
    #: call and WRAPS (after some 500k chunk calls of 1,024 tokens over 4
    #: layers): it is only ever read as the difference of two summaries,
    #: which the engine takes modulo 2**32.
    expert_tokens: Optional[jax.Array]
    #: Mamba-2: f32 [L_hybrid, SLOTS, H, P, N], a head's ``h [P, N]``.
    ssm: Optional[jax.Array] = None
    #: Mamba-2: f32 [L_hybrid, SLOTS, K-1, H·P + 2·G·N], the convolution's
    #: last input rows (``x``, ``B``, ``C`` before it).
    ssm_conv: Optional[jax.Array] = None

    @property
    def bytes_by_kind(self) -> Dict[str, int]:
        """HBM the state rows of each kind hold, state and convolution tail
        (the counters are a few hundred bytes)."""
        size = lambda *arrays: sum(a.nbytes for a in arrays if a is not None)
        return {decoder.KDA: size(self.s, self.conv),
                decoder.HYBRID: size(self.ssm, self.ssm_conv)}

    @property
    def pool_bytes(self) -> int:
        """HBM the state rows hold, every kind."""
        return sum(self.bytes_by_kind.values())


def _state_shapes(cfg: Any, slots: int) -> Dict[str, Tuple[int, ...]]:
    """The state arrays of a description, by field, for ``slots`` slots:
    those of the kinds it has layers of."""
    tail = cfg.conv_size - 1
    shapes = {}
    if cfg.n_kda_layers:
        h, d = cfg.kda_heads, cfg.kda_head_dim
        shapes["s"] = (cfg.n_kda_layers, slots, h, d, d)
        shapes["conv"] = (cfg.n_kda_layers, slots, tail, cfg.conv_channels)
    if cfg.n_hybrid_layers:
        layers = cfg.n_hybrid_layers
        shapes["ssm"] = (layers, slots, cfg.mamba_heads, cfg.mamba_head_dim,
                         cfg.mamba_state)
        shapes["ssm_conv"] = (layers, slots, tail, cfg.mamba_conv_channels)
    return shapes


def state_bytes_per_slot(cfg: Any) -> int:
    """Bytes ONE slot's recurrent state costs WITHOUT allocating (0 for a
    description with no such layer), every kind: the HBM budget's other
    term."""
    if not isinstance(cfg, decoder.DecoderConfig):
        return 0
    return sum(4 * math.prod(shape)
               for shape in _state_shapes(cfg, 1).values())


def init_state_pool(cfg: Any, max_slots: int) -> Optional[RecurrentState]:
    """Zeroed state rows for ``max_slots`` slots, or None for a description
    whose every layer keeps keys and values."""
    if not isinstance(cfg, decoder.DecoderConfig):
        return None
    rows = {name: jnp.zeros(shape, jnp.float32)
            for name, shape in _state_shapes(cfg, max_slots).items()}
    experts = cfg.n_expert_layers
    return RecurrentState(
        s=rows.get("s"), conv=rows.get("conv"),
        expert_pairs=jnp.zeros((experts, cfg.n_experts_held), jnp.int32)
        if experts else None,
        expert_tokens=jnp.zeros((), jnp.int32) if experts else None,
        ssm=rows.get("ssm"), ssm_conv=rows.get("ssm_conv"))


def zero_state_rows(state: RecurrentState, slot: jax.Array
                    ) -> RecurrentState:
    """``state`` with slot ``slot``'s rows of every layer and kind
    zeroed."""
    zero = lambda a: None if a is None else a.at[:, slot].set(0.0)
    return state._replace(s=zero(state.s), conv=zero(state.conv),
                          ssm=zero(state.ssm),
                          ssm_conv=zero(state.ssm_conv))


class BlockAllocator:
    """Host-side block lifecycle: free list + reference counts +
    quarantine set.

    Refcounts carry the prefix-sharing discipline: a block referenced by
    N requests (and/or the prefix cache) frees only when the LAST holder
    releases it.  ``release(quarantine=True)`` is the trust hook — a
    block whose last holder was a flagged request leaves the pool
    instead of returning to the free list, while blocks still shared
    with clean holders merely decref (quarantining a slot releases only
    its unshared blocks)."""

    def __init__(self, num_blocks: int, journal_capacity: int = 65536):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        self.num_blocks = num_blocks
        # LIFO free list over physical ids [1, num_blocks]; id 0 is the
        # reserved trash block and is never handed out.
        self._free: List[int] = list(range(num_blocks, 0, -1))
        self._ref: Dict[int, int] = {}
        self._quarantined: Set[int] = set()
        # Lifecycle evidence for obs.attribution.verify_attribution, two
        # granularities: ``journal`` is a bounded ring of (op, block,
        # seq[, outcome]) tuples for event-level debugging; ``lifetime``
        # is EXACT cumulative per-block op counts — keyed by block id so
        # it is bounded by the pool size, never by run length (the ring
        # alone would false-positive "never allocated" once a pinned
        # block's alloc entry rotated out).
        import collections as _collections

        self.journal: Any = _collections.deque(maxlen=journal_capacity)
        self._journal_seq = 0
        self.lifetime: Dict[int, Dict[str, int]] = {}

    def _journal_add(self, op: str, block: int, *extra: Any) -> None:
        self._journal_seq += 1
        self.journal.append((op, block, self._journal_seq, *extra))
        counts = self.lifetime.setdefault(
            block, {"alloc": 0, "incref": 0, "release": 0,
                    "unquarantine": 0})
        counts[op] += 1

    def alloc(self, n: int) -> Optional[List[int]]:
        """Claim ``n`` blocks at refcount 1, or None when the pool cannot
        satisfy the request (backpressure, not an error)."""
        if n < 0:
            raise ValueError(f"cannot alloc {n} blocks")
        if len(self._free) < n:
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
            self._journal_add("alloc", b)
        return out

    def incref(self, block: int) -> None:
        if block not in self._ref:
            raise ValueError(f"incref of unallocated block {block}")
        self._ref[block] += 1
        self._journal_add("incref", block)

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    def release(self, block: int, quarantine: bool = False) -> str:
        """Drop one reference.  Returns what happened: ``"shared"``
        (other holders remain), ``"freed"``, or ``"quarantined"`` (hit
        refcount 0 under a trust flag — the block leaves the pool until
        :meth:`unquarantine`)."""
        if self._ref.get(block, 0) <= 0:
            raise ValueError(f"double free / bad block {block}")
        self._ref[block] -= 1
        if self._ref[block] > 0:
            self._journal_add("release", block, "shared")
            return "shared"
        del self._ref[block]
        if quarantine:
            self._quarantined.add(block)
            self._journal_add("release", block, "quarantined")
            return "quarantined"
        self._free.append(block)
        self._journal_add("release", block, "freed")
        return "freed"

    def unquarantine(self, block: int) -> None:
        """Operator action: return a quarantined block to the free pool."""
        if block in self._quarantined:
            self._quarantined.discard(block)
            self._free.append(block)
            self._journal_add("unquarantine", block)

    # -- speculative claims (speculative decoding's COW discipline) -------

    def claim_speculative(self, blocks: Sequence[int]) -> None:
        """Pin the blocks a speculative draft window is about to write:
        one extra reference each (journaled as ordinary increfs, so
        ``verify_attribution``'s per-block ref/release balance covers
        speculative traffic like any other sharing).  While claimed, no
        host-side actor (prefix-cache LRU eviction, admission-pressure
        eviction) can see the block as single-holder-free — un-verified
        draft KV is visibly referenced for exactly the tick it exists."""
        for b in blocks:
            self.incref(b)

    def release_speculative(self, blocks: Sequence[int]) -> None:
        """Drop the speculative claims after the verify pass: THE
        rollback.  Rejected draft tokens cost exactly this refcount
        decrement — no device copy, no scrub; the rejected positions'
        K/V are causally invisible (beyond the accepted length) and are
        overwritten by the next tick's writes before they could ever be
        attended.  Accepted tokens cost the same decrement (the claim
        commits into the slot's own table reference, which already
        holds the block)."""
        for b in blocks:
            self.release(b)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        """Blocks currently referenced (requests and/or prefix cache)."""
        return len(self._ref)

    @property
    def quarantined(self) -> Set[int]:
        return set(self._quarantined)


def blocks_for_span(table: Sequence[int], block_size: int,
                    start: int, end: int) -> List[int]:
    """Distinct physical blocks backing logical positions ``[start,
    end)`` of a slot's block table — the speculative draft window's
    claim set.  Positions past the table's allocation are nobody's
    storage (their static-shape writes land in the trash block) and
    contribute nothing; the trash block itself is never claimable."""
    out: List[int] = []
    for lb in range(start // block_size, -(-end // block_size)):
        if lb < len(table) and table[lb] != TRASH_BLOCK \
                and table[lb] not in out:
            out.append(table[lb])
    return out


class PrefixCache:
    """Host-side radix cache over FULL prompt blocks (RadixAttention-lite).

    Nodes form a block-granular radix tree — each keyed by (parent, its
    one-block token segment) and holding one physical block id on which
    the cache itself keeps a reference — so a retired request's prompt
    blocks stay resident and a later request with the same prefix reuses
    them without prefill.
    Lookups incref every matched block on behalf of the caller (atomic
    with the match, so a concurrent eviction can never free a block the
    caller is about to table).  Eviction is LRU over LEAF nodes whose
    block has no other holder — an interior node is pinned by its cached
    extensions, a shared block by its live requests."""

    def __init__(self, block_size: int, blocks: BlockAllocator):
        self.block_size = block_size
        self._blocks = blocks
        # True radix layout: a node is keyed by (parent node id, the ONE
        # block_size-token segment extending it), so memory and hashing
        # stay LINEAR in cached tokens — keying by cumulative prefix
        # tuples would make a p-token prompt cost O(p^2/block) ints.
        # Record: [physical block id, last-used tick, node id,
        # cached-extension count].  Node id 0 is the implicit root.
        self._nodes: Dict[Tuple[int, Tuple[int, ...]], List[Any]] = {}
        self._by_id: Dict[int, Tuple[int, Tuple[int, ...]]] = {}
        # block id -> request id that PUBLISHED it (attribution: a
        # prefix-cache hit records whose prefill it is trusting).
        self._publisher: Dict[int, int] = {}
        self._next_id = 1
        self._clock = 0

    def _bump(self) -> int:
        self._clock += 1
        return self._clock

    def _segment(self, tokens: Sequence[int], i: int) -> Tuple[int, ...]:
        return tuple(tokens[i * self.block_size:(i + 1) * self.block_size])

    def __len__(self) -> int:
        return len(self._nodes)

    def lookup(self, tokens: Sequence[int], max_blocks: int) -> List[int]:
        """Longest cached full-block prefix of ``tokens`` (at most
        ``max_blocks`` blocks), each matched block increffed for the
        caller.  Callers cap ``max_blocks`` at ``(len(prompt)-1) //
        block_size`` so at least one prompt token always prefills (the
        first sampled token needs fresh logits)."""
        out: List[int] = []
        parent = 0
        for i in range(max_blocks):
            node = self._nodes.get((parent, self._segment(tokens, i)))
            if node is None:
                break
            node[1] = self._bump()
            out.append(node[0])
            parent = node[2]
        for b in out:
            self._blocks.incref(b)
        return out

    def insert(self, tokens: Sequence[int], block_ids: Sequence[int],
               publisher: Optional[int] = None) -> List[int]:
        """Register ``tokens``' full blocks (backed by ``block_ids``, the
        owning request's table) — the cache increfs each newly cached
        block.  A prefix already cached (possibly under a different
        physical block holding identical content) is refreshed, not
        duplicated.  ``publisher`` (the owning request id) is remembered
        per newly cached block for attribution.  Returns the NEWLY
        cached block ids (the caller's publication record — what a later
        quarantine must purge)."""
        n = min(len(tokens) // self.block_size, len(block_ids))
        added: List[int] = []
        parent = 0
        for i in range(n):
            key = (parent, self._segment(tokens, i))
            node = self._nodes.get(key)
            if node is not None:
                node[1] = self._bump()
                parent = node[2]
                continue
            nid = self._next_id
            self._next_id += 1
            self._nodes[key] = [block_ids[i], self._bump(), nid, 0]
            self._by_id[nid] = key
            self._blocks.incref(block_ids[i])
            if publisher is not None:
                self._publisher[block_ids[i]] = publisher
            if parent:
                self._nodes[self._by_id[parent]][3] += 1
            added.append(block_ids[i])
            parent = nid
        return added

    def publishers(self, block_ids: Sequence[int]) -> Dict[int, int]:
        """Publisher request id per cached block (blocks with no
        recorded publisher are omitted)."""
        return {b: self._publisher[b] for b in block_ids
                if b in self._publisher}

    def _remove(self, key: Tuple[int, Tuple[int, ...]]) -> List[int]:
        """Drop one node; returns [block id, node id]."""
        block, _, nid, _ = self._nodes.pop(key)
        self._publisher.pop(block, None)
        del self._by_id[nid]
        if key[0] and key[0] in self._by_id:
            self._nodes[self._by_id[key[0]]][3] -= 1
        return [block, nid]

    def evict(self, n_blocks: int) -> int:
        """Free up to ``n_blocks`` cached blocks, LRU leaves first,
        skipping any block a live request still references.  Returns how
        many were actually freed.  One heap pass — parents exposed by a
        child's eviction are pushed as they become leaves, so evicting k
        blocks from n nodes is O(n + k log n), not O(n*k) (this runs on
        the admission path whenever the pool is tight)."""
        heap = [(node[1], key) for key, node in self._nodes.items()
                if node[3] == 0]
        heapq.heapify(heap)
        freed = 0
        while heap and freed < n_blocks:
            _, key = heapq.heappop(heap)
            node = self._nodes.get(key)
            if node is None or node[3] != 0:
                continue                    # removed or re-grew a child
            if self._blocks.refcount(node[0]) != 1:
                continue                    # a live request pins it
            block, _ = self._remove(key)
            if key[0] and key[0] in self._by_id:
                parent_key = self._by_id[key[0]]
                parent = self._nodes[parent_key]
                if parent[3] == 0:
                    heapq.heappush(heap, (parent[1], parent_key))
            self._blocks.release(block)
            freed += 1
        return freed

    def purge(self, block_ids: Set[int]) -> int:
        """Drop every node backed by one of ``block_ids`` AND the
        subtrees hanging off them (unreachable once their parent is
        gone), releasing the cache's reference on each removed node's
        block.  The quarantine hook: a flagged request's PUBLISHED
        prompt blocks must leave the cache — without this their cache
        ref keeps them 'shared' at quarantine-retire and a later
        same-prefix request would decode straight off suspect KV.
        Returns the number of nodes removed."""
        doomed = [key for key, node in self._nodes.items()
                  if node[0] in block_ids]
        removed = 0
        while doomed:
            key = doomed.pop()
            if key not in self._nodes:
                continue
            block, nid = self._remove(key)
            doomed.extend(k for k in self._nodes if k[0] == nid)
            self._blocks.release(block)
            removed += 1
        return removed
