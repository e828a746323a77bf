"""Pallas TPU serving-kernel tier: ragged paged attention (decode +
chunked prefill), the fused speculative-verify tail, the
in-grid adapter gather, and the fused trust epilogue.

Decode attention over the paged KV pool (serve/kv_slots.PagedKV) has been
reading the cache through jnp gathers: ``models/generate._paged_gather``
materialises each row's FULL logical view [R, H, NBPS·BLOCK, Dh] in HBM
every layer of every tick, pays the gather bandwidth for positions past
the row's true length, and dequantises the int8 tier by algebra over that
view.  This kernel makes the stream explicit — the single biggest
tokens/sec lever ROADMAP item 2 names:

* **one program per block-table row** (grid ``(R, H/g, NT)``): the
  block table and per-row lengths ride as scalar-prefetch operands, and
  the step WALKS its row's blocks itself, in a loop whose trip count
  follows the row's length: it resolves ``logical block j -> physical
  block table[r, j]`` as it aims each copy (``pltpu.make_async_copy`` out
  of the pool in HBM into a VMEM tile) — the gather IS the copy, no [R, H,
  S, Dh] view is ever materialised;
* **the pool is read where it lies**: the kernels take the STACKED pool
  ``[L, NB, BLOCK, H·Dh]`` (every layer; a position's K, or V, of every
  head one contiguous row) whole, in HBM (``memory_space=pl.ANY``), and
  the layer as a fourth scalar-prefetch operand: a copy's source is
  ``pool[layer, table[r, j], :, the head group's lanes]``.  On this shape
  the in-place row write ``pool.at[layer, block, offset]``, the kernel's
  copies and the array's resting layout agree, so a serving program
  carries the pool through its layer loop in one buffer and one layout
  and nothing copies it (tests/test_chip_compile.py holds the compiler to
  that);
* **a pool the step cannot copy is walked by the grid**: a copy out of HBM
  takes whole (8, 128) tiles, so a block off the 8 sublanes or a pool whose
  rows are off the 128 lanes (:func:`_copies_its_blocks`: 25 heads of 64,
  a narrow tensor-parallel shard, a latent row left unpadded) cannot be
  walked inside the step.  The SAME body then takes its blocks from the
  grid's own pipeline, which pads the tile: the walk is the grid's fourth
  dimension ``NBPS``, one block a step, the K/V index map resolves
  ``(layer, table[r, min(j, jmax)], 0, head group)`` before the copy is
  issued, and a step past ``jmax`` copies and computes nothing but is a
  step all the same;
* **a step holds a wave of blocks' heads**: a physical block's rows keep
  the heads side by side in the lanes, so a step copies its blocks for a
  GROUP of ``g`` heads (a window of the lanes: whole 128-lane columns,
  or every head — :func:`_head_groups`), a WAVE of ``W`` blocks at a time
  into one tile ``[W·BLOCK, g·Dh]`` (the next wave's copies in flight
  while this one is multiplied), cuts the heads out of the lanes ONCE a
  wave (:func:`_heads_from_lanes`) and runs their products as one batch
  over the wave's ``W·BLOCK`` positions: a product of 256 or 512
  positions fills the MXU's width where one of a block's 16 fills an
  eighth, and a pass of the loop costs what a grid step costs whatever it
  holds.  ``g``, the query tile and ``W`` come from ONE rule over shapes
  and the pool's dtype (:func:`_step_shape`: the widest tile, then the
  widest head group, then the most blocks, whose tiles fit
  :data:`VMEM_BLOCK_BUDGET`, the wave held to :data:`WAVE_POSITIONS` — all
  20 heads, the chunk's 64 queries and 16 blocks of 16 at GPT-2 large,
  one head and one block at blocks of 4,096 x 128); :func:`grid_steps`
  gives the grid and the static bound of a walk, :func:`walked_blocks`
  the blocks a row's walk copies (PERF.md section 6 has the readings);
* **grouped heads**: where ``rep`` query heads share each K/V head (the
  pool's lanes hold the K/V heads; a query tensor with ``rep`` times as
  many), a step holds a group of K/V heads with the ``rep`` query heads
  of each as ``rep`` times the tile's rows of the SAME two products, so
  a block is copied once for all the queries that read it
  (:func:`_attend` lays the rows out; ``rep = 1`` is the kernel as it
  was, byte for byte);
* **int8 streaming**: int8 KV tiles DMA HBM→VMEM at half the bf16 bytes
  (a quarter of f32), upcast in-register, and the per-(head, position)
  scales PagedKV already pages multiply the scores/probabilities exactly
  where the algebraic jnp path applies them; they ride the same waves, a
  row's scales gathered by its table once a call and laid wave by wave
  (:func:`_wave_planes`), one copy a plane a wave;
* **online softmax** (flash-attention style (m, l, acc) accumulators,
  f32 regardless of input dtype);
* **ragged early exit**: a row with ``start + T`` valid positions walks
  ``ceil((start+T)/BLOCK)`` blocks rounded up to whole waves and not one
  wave more — the loop's trip count is ``jmax // W + 1``, a traced value.
  The blocks of the last wave past the row's last useful block are that
  block copied again (never another row's), and the mask in absolute
  positions hides them with everything else past the row's length; a row
  that holds nothing (a mid-prefill slot's all-trash row in the decode
  call) walks one wave.

**Chunked-prefill program** (:func:`paged_prefill_attention`): the
multi-query-row extension, on the same kernel.  The T chunk rows of a
slot go in ONE query tile where that fits (the rule above) and else in
tiles (the grid's ``NT``), attending over the SAME scalar-prefetch block
tables with the ragged causal mask in absolute positions.  The per-(row,
tile) last-useful-block bound rides as the third scalar-prefetch
operand, so an early query tile's walk ends with the KV blocks its
causal window can see — the flash-attention causal skip applied ACROSS
query tiles of a paged table.  This replaces ``paged_chunk``'s gathered-view
attention (the whole-prompt [R, H, S, Dh] view per chunk per layer).

**Fused speculative-verify tail** (:func:`fused_verify_tail`): the spec
verify window needs logits at EVERY draft position plus the per-position
trust stats.  The jnp tail (``models/generate._all_logits`` then
``logit_trust_stats``) projects [R·(k+1), V] logits to HBM and re-reads
them for the reductions.  The fused program streams ``wte_head`` in
vocab tiles through ONE grid: each step runs the tile's head matmul,
writes the logits tile (sampling's ``jax.random.categorical`` needs the
full row — gumbel noise cannot be reproduced in-kernel without forking
the sampled stream) and folds the SAME online entropy/top-2 algebra as
the trust epilogue over the tile before it leaves VMEM — one vocab
pass, no separate stats read, margin still bit-exact.

**In-grid adapter gather** (:func:`adapter_delta`): the per-tenant
low-rank delta (serve/adapters.py) was a ``jnp.take`` of each row's
pool page ``a_l[apages]`` OUTSIDE the kernel grid.  Here the per-slot
``adapter_page_row`` joins the scalar-prefetch operands: the A/B delta
tiles stream HBM→VMEM alongside the KV blocks (index map resolves
``row -> pages[row]`` before the DMA), int8 pages upcast in-register
with their per-(page, site) scales applied in exactly the
``fused_dequant_matmul.lowrank_delta`` order — the host-of-grid take is
gone.

**Trust epilogue** (:func:`logit_trust_stats`): the serve-side output
monitor reduces every decode step's logits to softmax entropy + top-1
margin (serve/scheduler._logit_signals).  Left to jnp that is a
log_softmax pass, an exp/sum pass and a hierarchical top-k over the
vocab; the epilogue kernel streams the [B, V] logits ONCE, keeping
online (max, Σe^{x−m}, Σx·e^{x−m}) and an exact top-2 merge — entropy
``logZ − Σxp`` and margin ``top1 − top2`` in a single HBM read, so
serve-side trust monitoring rides the decode step at the cost of reading
logits once (which sampling pays anyway).

Dispatch: behind the shared ops-package gate (``pallas_enabled
("TDDL_PAGED_ATTN")`` — default ON on TPU, opt-in off-TPU where it runs
in interpret mode) with the jnp path as the always-available fallback
and reference semantics.  The serving engine resolves ONE path PER
PROGRAM at construction (:func:`resolve_attn_impl` for the decode
program — "pallas" | "interpret" | "jnp" — and
:func:`resolve_attn_impls` for the whole tier: ineligible satellite
programs downgrade LOUDLY to jnp instead of raising, so a geometry that
can decode but not verify still serves) and threads each through its
compiled programs as STATIC values, so A/B arms and tests retrace
cleanly instead of aliasing each other in the process-global jit cache,
and the compile-once pin is untouched: tables/lengths/adapter pages
stay traced VALUES, block and adapter churn never recompile.

Numerics: the online softmax is mathematically identical to the jnp
path's full softmax but accumulates in a different order, so kernel
logits agree to f32-rounding epsilon rather than bit-for-bit (the same
contract as flash-vs-XLA attention; near-tie greedy flips are possible
in principle).  The margin half of the epilogue IS bit-exact (max/merge
only); entropy agrees to epsilon.  tests/test_paged_attention.py pins
both, plus bit-identical served streams vs ``generate()``.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from trustworthy_dl_tpu.ops import pallas_enabled, pallas_interpret

logger = logging.getLogger(__name__)

NEG_INF = -1e30          # finite stand-in: exp(NEG_INF - m) flushes to 0
#: f32 sublane: the query tile's second-to-minor dim (T) pads up to this.
QROWS = 8
#: Vocab tile of the trust epilogue (lanes; V pads up to a multiple).
TRUST_TILE = 512
#: Scoped VMEM one Mosaic kernel may use on the chips this targets (v5e:
#: 16 MiB).  Compiling these kernels for a described v5e, VMEM is the
#: ONLY thing the compiler refuses: block sizes off the dtype's sublane,
#: head sizes off the 128 lanes, ``n_embd`` off 128 and adapter ranks
#: below 8 all lower (Mosaic pads the tile), while a kernel whose
#: double-buffered operand blocks reach this limit is refused with
#: RESOURCE_EXHAUSTED — first at 4 x 4 MiB of K/V tiles for the
#: attention programs and at 2 x 8 MiB of head tile for the verify tail
#: (tests/test_chip_compile.py keeps both sides of that boundary).
VMEM_LIMIT_BYTES = 16 << 20
#: What the eligibility predicate lets the pipelined blocks take: half
#: the limit, the rest being the kernel's own scratch and temporaries
#: (the f32 upcast of a K/V tile, the score tile).
VMEM_BLOCK_BUDGET = VMEM_LIMIT_BYTES // 2
#: The most cached positions one wave of a row's walk holds (a wave is that
#: many positions' worth of physical blocks, :func:`_step_shape`): the one
#: geometry the VMEM rule leaves a longer wave, the latent shape's blocks of
#: 256, read slower at 2,048 positions than at this (PERF.md section 6).
WAVE_POSITIONS = 1024

#: Engine-facing path names.  "auto" resolves through the shared gate;
#: the resolved value is one of the other three.
ATTN_IMPLS = ("auto", "pallas", "interpret", "jnp")

#: The serving-kernel tier's programs: ragged paged-decode attention,
#: the chunked-prefill program, the fused speculative-verify
#: tail, and the in-grid adapter low-rank gather.
PAGED_PROGRAMS = ("decode", "prefill", "verify", "adapter")


def _tile_bytes(rows: int, cols: int, dtype) -> int:
    """VMEM bytes of one [rows, cols] block of ``dtype`` as Mosaic lays
    it out: rows pad to the dtype's sublane (32 bytes' worth: 8 f32,
    16 bf16, 32 int8), cols to the 128 lanes."""
    itemsize = jnp.dtype(dtype).itemsize
    sublane = max(QROWS, 32 // itemsize)
    return (-(-rows // sublane) * sublane) * (-(-cols // 128) * 128) \
        * itemsize


def _pipelined_block_bytes(program: str, *, head_dim: int,
                           block_size: int, kv_dtype,
                           n_embd: Optional[int] = None,
                           adapter_rank: Optional[int] = None,
                           rows: int = QROWS, group: int = 1,
                           q_tile: int = QROWS,
                           v_lanes: Optional[int] = None,
                           wave: int = 1) -> int:
    """VMEM bytes one grid step of ``program`` pins — the quantity the
    compiler's refusal is about: its operand and output blocks, double
    buffered.  Activations count as f32 (the widest the engine feeds);
    ``rows`` is the query rows of one call of the verify tail or the
    adapter gather.

    A step of the attention programs holds ``group`` heads and ``q_tile``
    query rows and walks its row's blocks ``wave`` physical blocks at a
    time: q and out ``[group, q_tile, head_dim]`` (pipelined by the grid),
    the wave's K and V tiles ``[wave, block_size, group * head_dim]`` in
    the pool's dtype, two of each (the next wave is copied while this one
    is multiplied; where the grid walks, its pipeline holds the same two
    of one block), on the int8 tier likewise the wave's two scale planes
    ``[heads, wave * block_size]`` of every head (:func:`_wave_planes`), and
    the f32 scratch ``[group, q_tile, .]`` of the online softmax, which
    grows with the same two numbers and is therefore counted here (once: it
    is not pipelined).

    The LATENT shape (``v_lanes`` given: one shared row of ``head_dim``
    lanes a position whose first ``v_lanes`` are also the values) pins q
    ``[q_tile, head_dim]`` and out ``[q_tile, v_lanes]`` in the pool's dtype
    (they feed the MXU as they are) and ONE tile a wave, there being no
    V."""
    f32 = jnp.float32
    if v_lanes is not None:
        blocks = (_tile_bytes(q_tile, head_dim, kv_dtype)
                  + _tile_bytes(q_tile, v_lanes, kv_dtype)
                  + wave * _tile_bytes(block_size, head_dim, kv_dtype))
        scratch = (_tile_bytes(q_tile, v_lanes, f32)
                   + 2 * _tile_bytes(q_tile, 128, f32))
        return 2 * blocks + scratch
    if program in ("decode", "prefill"):
        blocks = 2 * group * _tile_bytes(q_tile, head_dim, f32)  # q, out
        blocks += 2 * wave * group * _tile_bytes(block_size, head_dim,
                                                 kv_dtype)
        if jnp.dtype(kv_dtype) == jnp.int8:
            # Without n_embd the head count is unknown: the group's.
            heads = n_embd // head_dim if n_embd else group
            blocks += 2 * _tile_bytes(heads, wave * block_size, f32)
        scratch = group * (_tile_bytes(q_tile, head_dim, f32)
                           + 2 * _tile_bytes(q_tile, 128, f32))
        return 2 * blocks + scratch
    if program == "verify":
        blocks = (_tile_bytes(rows, n_embd, f32)
                  + _tile_bytes(TRUST_TILE, n_embd, f32)
                  + _tile_bytes(rows, TRUST_TILE, f32))
    else:
        blocks = (2 * _tile_bytes(rows, n_embd, f32)            # x, out
                  + _tile_bytes(n_embd, adapter_rank, f32)
                  + _tile_bytes(adapter_rank, n_embd, f32))
    return 2 * blocks


def _head_groups(heads: Optional[int], head_dim: int) -> list:
    """The head groups one grid step may hold, widest first.  The pool
    keeps a position's heads side by side in ONE row ``[H·Dh]``, so a
    group is a window of the lanes and Mosaic wants it whole 128-lane
    columns, or the whole row: a divisor ``g`` of ``heads`` with ``g·Dh``
    a multiple of 128, or every head (at 64-wide heads never 1 or 5).
    ``heads`` unknown (None): the narrowest window any head count
    allows."""
    if not heads:
        return [128 // math.gcd(128, head_dim)]
    return [g for g in range(heads, 0, -1)
            if heads % g == 0 and (g == heads or (g * head_dim) % 128 == 0)]


def _copies_its_blocks(block_size: int, lanes: int) -> bool:
    """Whether a step can copy a pool's blocks ``[block_size, lanes]`` out
    of HBM itself, and so walk its row inside the step: Mosaic slices an
    operand that lies in HBM in whole (8, 128) tiles only, whatever the
    dtype (tests/test_chip_compile.py has the refusals).  Any other pool is
    walked by the grid, whose own pipeline pads the tile."""
    return block_size % QROWS == 0 and lanes % 128 == 0


def _step_shape(program: str, *, heads: int, head_dim: int,
                block_size: int, kv_dtype, t: int, rep: int = 1,
                v_lanes: Optional[int] = None) -> Tuple[int, int, int]:
    """THE rule for what one grid step of an attention program holds:
    ``(head group, query tile, wave)``, from shapes and the pool's dtype
    alone.  ``heads`` are the pool's (K/V) heads; where ``rep`` query heads
    share each of them, a step holds the ``rep`` query heads of every K/V
    head of its group as ``rep`` times the tile's rows of the same product.

    The query tile first: the ``t`` query rows of a call, padded to the
    sublane, in ONE tile if the narrowest head group's blocks then fit
    :data:`VMEM_BLOCK_BUDGET` (:func:`_pipelined_block_bytes`), else the
    widest ``QROWS * 2**i`` under it that does (a wider tile streams the
    row's K and V fewer times); the decode program never tiles.  Then the
    widest group of :func:`_head_groups` that fits beside that tile: the
    pool keeps a physical block's heads side by side in its rows, so a
    group is one copy.  A geometry nothing fits gets the narrowest step,
    which :func:`supports_paged_attention` refuses.

    Then the WAVE, the physical blocks a pass of the step's walk copies
    and multiplies at once: the largest power of two whose two tiles of K
    and of V fit the budget beside that tile and group and whose score
    tile and f32 cuts fit the other half of VMEM, held to
    :data:`WAVE_POSITIONS` positions (a caller that knows the table holds
    it to the row's blocks besides); one block where the grid walks
    (:func:`_copies_its_blocks`) and, in the latent shape, where the block
    is not whole sublanes of the pool's dtype: its products take the wave's
    rows as they lie, and the blocks are then not one tile's rows (the
    other shapes lay the blocks end to end in float32, whose 8 sublanes
    every block a step can copy is whole).

    In the latent shape (``v_lanes``; the decode program's) the ``rep``
    query heads of the ONE shared row are the tile's rows, so the positions
    pad only as far as ``rep`` times them is whole sublanes: a decode
    call's one position of 32 heads is a tile of 32 rows, not of 256."""
    unit = QROWS // math.gcd(QROWS, rep) if v_lanes is not None else QROWS
    t8 = -(-t // unit) * unit
    tiles = [t8]
    if program == "prefill":
        tiles += [QROWS << i for i in reversed(range(t8.bit_length()))
                  if QROWS << i < t8]
    groups = _head_groups(heads, head_dim)

    def fits(group: int, q_tile: int, wave: int = 1) -> bool:
        return _pipelined_block_bytes(
            program, head_dim=head_dim, block_size=block_size,
            kv_dtype=kv_dtype, n_embd=heads * head_dim, group=group,
            q_tile=rep * q_tile, v_lanes=v_lanes,
            wave=wave) <= VMEM_BLOCK_BUDGET

    q_tile = next((qt for qt in tiles if fits(groups[-1], qt)), tiles[-1])
    group = next((g for g in groups if fits(g, q_tile)), groups[-1])

    def wave_fits(wave: int) -> bool:
        # Beside the pinned tiles, what the kernel makes of a wave: its
        # score tile and, per head, the f32 cut of its K and of its V.
        span = wave * block_size
        made = group * _tile_bytes(rep * q_tile, span, jnp.float32)
        if v_lanes is None:
            made += 2 * group * _tile_bytes(span, head_dim, jnp.float32)
        return (fits(group, q_tile, wave)
                and made <= VMEM_LIMIT_BYTES - VMEM_BLOCK_BUDGET)

    wave = 1
    packed = 1 if v_lanes is None else 32 // jnp.dtype(kv_dtype).itemsize
    if (block_size % max(QROWS, packed) == 0
            and _copies_its_blocks(block_size, heads * head_dim)):
        while (2 * wave * block_size <= WAVE_POSITIONS
               and wave_fits(2 * wave)):
            wave *= 2
    return group, q_tile, wave


def grid_steps(program: str, rows: int, heads: int, nbps: int, t: int,
               head_dim: int, block_size: int,
               kv_dtype, kv_heads: Optional[int] = None,
               v_lanes: Optional[int] = None
               ) -> Tuple[int, int, int, int]:
    """The steps of one attention call, ``(rows, head groups, query tiles,
    logical blocks)``: ``program`` "decode" or "prefill" over ``rows``
    block-table rows of ``nbps`` blocks, ``t`` query rows each, ``heads``
    query heads over ``kv_heads`` K/V heads (as many where not given; the
    groups are groups of K/V heads; ``v_lanes`` the latent shape, whose
    ``kv_heads`` is 1).  The first three are the call's ``grid=`` where the
    step walks its row itself (:func:`_attn_pallas_call` takes them from the
    same rule) and the fourth is the STATIC BOUND of that walk, the blocks a
    full row holds: the walk is a loop inside the step whose trip count
    follows the row's length (:func:`walked_blocks` counts what it copies),
    so a block past it costs nothing.  Where the grid walks
    (:func:`_copies_its_blocks`) all four are the grid."""
    kv_heads = kv_heads or heads
    group, q_tile, _ = _step_shape(
        program, heads=kv_heads, head_dim=head_dim, block_size=block_size,
        kv_dtype=kv_dtype, t=t, rep=heads // kv_heads, v_lanes=v_lanes)
    return rows, kv_heads // group, -(-t // q_tile), nbps


def _tile_bounds(start, t: int, q_tile: int, block_size: int, nbps: int):
    """``jmax[..., ti]``, the last logical block the walk of query tile
    ``ti`` needs, for rows whose first query stands at ``start`` (an
    integer array): that of the tile's last REAL query, at ``start +
    min((ti+1)·q_tile, t) − 1`` (pad rows compute a finite, masked
    attention nobody reads), clipped into the table: a padded prefill chunk
    can extend past the slot's allocation — those query rows are discarded
    by the caller, and the mask keeps them finite."""
    tiles = np.arange(-(-t // q_tile), dtype=np.int32)
    last = np.minimum((tiles + 1) * q_tile, t) - 1
    return jnp.clip((start[..., None] + last) // block_size, 0, nbps - 1)


def walked_blocks(program: str, work, heads: int, nbps: int, t: int,
                  head_dim: int, block_size: int, kv_dtype,
                  kv_heads: Optional[int] = None,
                  v_lanes: Optional[int] = None) -> int:
    """The blocks the walk of ONE block-table row copies in a call whose
    shapes are :func:`grid_steps`'s: waves times the wave's blocks, summed
    over the row's query tiles, from the rule and the bounds the kernel
    uses.  ``work`` is the row's cached length, the call's ``t`` new
    positions included (``program`` "decode"; 0 a row that holds nothing,
    whose walk is one wave all the same), or ``(pos, rows)``, the chunk's
    first position and its real rows (``"prefill"``: the program pads the
    chunk to ``t`` rows and walks for all of them).  In whole blocks: each
    head group copies its window of the lanes of every one, the groups
    together the block.  Over the blocks that hold the row's live positions
    this is what the schedule costs the memory, 1 at best."""
    kv_heads = kv_heads or heads
    _, q_tile, wave = _step_shape(
        program, heads=kv_heads, head_dim=head_dim, block_size=block_size,
        kv_dtype=kv_dtype, t=t, rep=heads // kv_heads, v_lanes=v_lanes)
    wave = min(wave, nbps)
    start = work[0] if program == "prefill" else max(int(work) - t, 0)
    jmax = np.asarray(_tile_bounds(np.asarray(start, np.int32), t, q_tile,
                                   block_size, nbps))
    return int(np.sum(jmax // wave + 1)) * wave


def supports_paged_attention(*, head_dim: int, block_size: int,
                             kv_dtype, interpret: bool,
                             program: str = "decode",
                             n_embd: Optional[int] = None,
                             adapter_rank: Optional[int] = None,
                             rows: int = QROWS,
                             v_lanes: Optional[int] = None) -> bool:
    """THE kernel-eligibility predicate (the ``supports_flash`` pattern),
    PER PROGRAM: every dispatch site must consult it so the fallback
    condition can never drift from a kernel's real constraints.  True
    means the program lowers for the chip; tests/test_chip_compile.py
    holds it to that against the TPU compiler.

    What the compiler refuses is VMEM (see :data:`VMEM_LIMIT_BYTES`), so
    compiled eligibility is one rule for all four programs: what a grid
    step pins (:func:`_pipelined_block_bytes`) fits
    :data:`VMEM_BLOCK_BUDGET`.  For the attention programs the step
    asked about is the narrowest :func:`_step_shape` can fall to, the
    narrowest head group the pool's rows allow (:func:`_head_groups`: one
    head only where a head is whole 128-lane columns), one sublane of
    queries and a wave of one block: that bounds ``block_size x
    head_dim`` in the POOL's storage dtype (and, on the int8 tier, the
    scale planes of ``n_embd // head_dim`` heads); what fits beyond it
    only widens the step and lengthens the wave, and a pool the step
    cannot copy itself is walked by the grid (:func:`_copies_its_blocks`),
    at the same bytes.  For the verify tail
    it bounds ``n_embd`` (the [TRUST_TILE, n_embd] head tile); for the
    adapter gather ``rows x n_embd`` (``rows`` = the most query rows one
    call carries, the prefill chunk).  ``verify`` and ``adapter`` need
    ``n_embd``; ``adapter`` a positive ``adapter_rank``.  ``v_lanes``
    asks about the latent shape (``head_dim`` then the shared row's lanes).

    Interpret mode (CPU tests) has no such limit — only sanity bounds —
    so the equality pins run at the small geometries the test pools
    use."""
    if program not in PAGED_PROGRAMS:
        raise ValueError(
            f"program must be one of {PAGED_PROGRAMS}, got {program!r}")
    if head_dim < 1 or block_size < 1:
        return False
    if program == "adapter" and (adapter_rank is None or adapter_rank < 1):
        return False
    if interpret:
        return True
    if program in ("verify", "adapter") and not n_embd:
        return False
    group = 1
    if program in ("decode", "prefill"):
        group = _head_groups(n_embd // head_dim if n_embd else None,
                             head_dim)[-1]
    return _pipelined_block_bytes(
        program, head_dim=head_dim, block_size=block_size,
        kv_dtype=kv_dtype, n_embd=n_embd, adapter_rank=adapter_rank,
        rows=rows, group=group, v_lanes=v_lanes) <= VMEM_BLOCK_BUDGET


def resolve_attn_impl(requested: str, *, head_dim: int, block_size: int,
                      kv_dtype, n_embd: Optional[int] = None,
                      v_lanes: Optional[int] = None) -> str:
    """Resolve the engine's ``attn_impl`` knob ONCE, at construction —
    never inside a traced program — to the path its compiled programs
    will bake in: ``"pallas"`` (compiled Mosaic, TPU), ``"interpret"``
    (the same kernel through the Pallas interpreter, off-TPU tests) or
    ``"jnp"`` (the gather fallback, the default everywhere the gate is
    off).

    ``"auto"`` consults the shared ``pallas_enabled("TDDL_PAGED_ATTN")``
    gate and downgrades to "jnp" with a loud warning when the geometry
    overflows VMEM (a silent fallback must at least log; the serve snapshot
    gauge + the sentinel's decode-tick fraction page the rest).  An
    explicit ``"pallas"`` that cannot dispatch COMPILED Mosaic raises —
    the operator asked for the kernel by name, and that includes a
    non-TPU backend (the interpreter is not the kernel; ask for
    ``"interpret"`` explicitly to run it)."""
    if requested not in ATTN_IMPLS:
        raise ValueError(
            f"attn_impl must be one of {ATTN_IMPLS}, got {requested!r}"
        )
    if requested == "jnp":
        return "jnp"
    if requested == "pallas" and pallas_interpret():
        raise ValueError(
            "attn_impl='pallas' needs the TPU backend to dispatch "
            "compiled Mosaic (this process is on "
            "a non-TPU backend); use attn_impl='interpret' to run the "
            "kernel through the Pallas interpreter, or 'auto'/'jnp'"
        )
    if requested == "auto" and not pallas_enabled("TDDL_PAGED_ATTN"):
        return "jnp"
    mode = "interpret" if (requested == "interpret"
                           or pallas_interpret()) else "pallas"
    if supports_paged_attention(head_dim=head_dim, block_size=block_size,
                                kv_dtype=kv_dtype, n_embd=n_embd,
                                interpret=(mode == "interpret"),
                                v_lanes=v_lanes):
        return mode
    detail = (
        f"head_dim={head_dim}, block_size={block_size}, "
        f"kv_dtype={kv_dtype}: the double-buffered K/V blocks must fit "
        f"{VMEM_BLOCK_BUDGET >> 20} MiB of VMEM"
    )
    if requested in ("pallas", "interpret"):
        raise ValueError(
            f"attn_impl={requested!r} cannot dispatch the paged-attention "
            f"kernel ({detail})"
        )
    logger.warning(
        "paged-attention kernel unsupported for this pool geometry (%s); "
        "falling back to the jnp gather path — expect the decode-tick "
        "fraction to page in the perf sentinel", detail,
    )
    return "jnp"


def resolve_attn_impls(requested: str, *, head_dim: int, block_size: int,
                       kv_dtype, n_embd: int,
                       adapter_rank: Optional[int] = None,
                       rows: int = QROWS,
                       satellites: Tuple[str, ...] = ("prefill", "verify",
                                                      "adapter"),
                       v_lanes: Optional[int] = None) -> dict:
    """Resolve the WHOLE serving-kernel tier at construction: one impl
    per program in :data:`PAGED_PROGRAMS` (of the satellite programs,
    those in ``satellites``: an engine that can never dispatch one, a
    description the verify tail is refused for, names the others and is
    spared a warning about it).

    The decode program keeps :func:`resolve_attn_impl`'s loud contract
    (explicit asks that cannot dispatch raise).  The satellite programs
    — prefill, verify, adapter — inherit the decode resolution where
    their geometry is eligible and DOWNGRADE LOUDLY to ``"jnp"`` where
    it is not, even under an explicit ask: a pool that can decode but
    whose ``n_embd`` overflows the verify tail's head tile must still
    serve, and the per-program gauge + the sentinel fractions page the
    downgrade rather than an exception unwinding the engine.  An
    unconfigured adapter tier (``adapter_rank`` falsy) resolves its
    program to ``"jnp"`` silently — there is nothing to fuse.  ``rows``
    is the most query rows one call of a program carries (the prefill
    chunk, or the verify window over every slot)."""
    decode = resolve_attn_impl(requested, head_dim=head_dim,
                               block_size=block_size, kv_dtype=kv_dtype,
                               n_embd=n_embd, v_lanes=v_lanes)
    impls = {p: "jnp" for p in PAGED_PROGRAMS}
    impls["decode"] = decode
    if decode == "jnp":
        return impls
    interp = decode == "interpret"
    for program in satellites:
        if program == "adapter" and not adapter_rank:
            continue
        if supports_paged_attention(
                head_dim=head_dim, block_size=block_size,
                kv_dtype=kv_dtype, interpret=interp, program=program,
                n_embd=n_embd, adapter_rank=adapter_rank, rows=rows,
                v_lanes=v_lanes):
            impls[program] = decode
        else:
            logger.warning(
                "paged %s program's blocks overflow VMEM for this "
                "geometry (n_embd=%s, adapter_rank=%s, rows=%s); that "
                "program falls back to jnp — expect its sentinel "
                "fraction to page", program, n_embd, adapter_rank, rows,
            )
    return impls


def _dot(a: jax.Array, b: jax.Array, trans_b: bool = False) -> jax.Array:
    """f32-accumulating matmul for the MXU: ``a @ b`` (``a @ b.T`` with
    ``trans_b``) over the last two dims, any leading dim a batch."""
    batch = tuple(range(a.ndim - 2))
    cb = b.ndim - 1 if trans_b else b.ndim - 2
    return jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (cb,)), (batch, batch)),
        preferred_element_type=jnp.float32,
    )


def _times_head_scales(x: jax.Array, planes, at, head0) -> jax.Array:
    """``x`` [g, rows, span] times the int8 tier's per-(head, position)
    scales of heads ``head0 ..``: ``planes[at]`` is ``[H., span.]``, every
    head's scales of the wave's positions (heads on the sublanes, positions
    on the lanes as the scores have them; both may be padded).  Each head's
    sublane is read alone (a dynamic window of several sublanes lowers only
    where it starts on a multiple of 8) and the heads' products
    restacked."""
    span = x.shape[-1]
    return jnp.stack([
        x[i] * planes[(*at, pl.ds(head0 + i, 1), slice(None))][:, :span]
        for i in range(x.shape[0])])


def _wave_planes(scales: jax.Array, layer: jax.Array, table: jax.Array,
                 wave: int, whole_tiles: bool) -> jax.Array:
    """The int8 tier's scales ``[L, NB, BLOCK, H]`` at ``layer`` as the walk
    reads them: each row's, gathered by its ``table`` i32[R, NBPS] and laid
    wave by wave, heads on the sublanes and a wave's positions on the lanes:
    ``[R, NW, H, wave * BLOCK]``, so that a wave's scales are ONE copy a
    plane.  The planes are a sixty-fourth of the pool's elements and rest
    block-index-minor (their two minor dimensions are far under a tile), so
    the kernel cannot window them where they lie (PERF.md section 7 has what
    else was tried); the gather is what the jnp path does for the same
    scales.  The layer's plane is turned heads-before-positions FIRST and
    gathered after: on the chip a third of the time of gathering the blocks
    and turning each wave (PERF.md section 6).  ``whole_tiles`` pads heads
    and positions to whole (8, 128) tiles, which a copy out of HBM takes
    (:func:`_copies_its_blocks`)."""
    r, nbps = table.shape
    bsz, h = scales.shape[2:]
    nw = -(-nbps // wave)
    g = scales[layer].transpose(0, 2, 1)[table]     # [R, NBPS, H, BLOCK]
    g = jnp.pad(g, ((0, 0), (0, nw * wave - nbps), (0, 0), (0, 0)))
    g = g.reshape(r, nw, wave, h, bsz).transpose(0, 1, 3, 2, 4)
    g = g.reshape(r, nw, h, wave * bsz)
    if whole_tiles:
        g = jnp.pad(g, ((0, 0), (0, 0), (0, -h % QROWS),
                        (0, -(wave * bsz) % 128)))
    return g


# ---------------------------------------------------------------------------
# Ragged paged attention: one kernel, the decode and the chunked-prefill
# programs
# ---------------------------------------------------------------------------


def _heads_from_lanes(x: jax.Array, group: int) -> jax.Array:
    """A wave's K (or V) blocks ``[W, bsz, group * Dh]`` (a position's heads
    side by side in the lanes, as the pool keeps them) -> f32 ``[group,
    W·bsz, Dh]``, the heads a batch dimension for the two products.  The
    blocks are laid end to end AFTER the upcast: a block is whole float32
    sublanes where it may be half a packed tile of the pool's dtype (16
    rows of int8).  Then static lane windows, restacked: Mosaic refuses the
    reshape that splits the lane dimension ("unsupported shape cast", 16 x
    1280 -> 16 x 20 x 64) and this jax has no ``einshape`` (PERF.md section
    6 has what the windows cost on the chip): once a wave."""
    x = x.astype(jnp.float32).reshape(-1, x.shape[-1])
    dh = x.shape[-1] // group
    if group == 1:
        return x[None]
    return jnp.stack([x[:, i * dh:(i + 1) * dh] for i in range(group)])


def _paged_attn_kernel(table_ref, start_ref, jmax_ref, layer_ref, q_ref,
                       k_in, *rest, scale: float, bsz: int, qt: int,
                       group: int, wave: int, in_step: bool,
                       quantized: bool, rep: int = 1,
                       v_lanes: Optional[int] = None):
    """The walk of a row's blocks for ``group`` heads against ``qt`` query
    rows, the online softmax a WAVE of ``wave`` physical blocks (``wave *
    bsz`` positions) a pass.  The heads are a batch dimension of both
    products (per head the algebra is what a step of one head was; on the
    chip the batched spelling beat a static unroll of two-dimensional dots
    by 1.4 to 1.7 times, PERF.md section 6).  Where ``rep`` query heads
    share a K/V head, the tile's ``qt`` positions of each of them lie one
    after the other in the step's ``rep * qt`` rows (:func:`_attend` lays
    them so).

    Scalar-prefetch refs: ``table_ref`` i32[R, NBPS] (physical ids: the
    walk reads them to aim its copies, so the gather IS the copy),
    ``start_ref`` i32[R] (first query's absolute position), ``jmax_ref``
    i32[R, NT] (the last useful logical block of each (row, query tile),
    the ragged bound: tile ``ti``'s causal window ends at its own last
    query, so an early tile of a long chunk walks a fraction of the blocks
    the chunk touches) and ``layer_ref`` i32[1].  After ``q_ref`` come the
    pools (K, then V) and, on the int8 tier, the two scale planes
    (:func:`_wave_planes`); then the output block and the softmax's scratch
    ``(acc, m, l)``.

    THE WALK IN THE STEP (``in_step``; a grid step is a (row, head group,
    query tile)): the pools are the STACKED pools whole, in HBM where they
    lie, and the scratch goes on with the wave's tiles ``[2, wave, bsz,
    group * Dh]`` (one for K, one for V, on the int8 tier one ``[2, H.,
    wave * bsz.]`` for each plane) and the DMA semaphores ``[2, tiles]``.
    The walk is ``jmax // wave + 1`` waves, a traced count.  Wave ``w``
    copies logical blocks ``w * wave ..`` of the row, block ``j`` from
    ``pool[layer, table[r, min(j, jmax)], :, the group's lanes]``, into
    tile ``w % 2``; wave ``w + 1`` is started before wave ``w`` is waited
    for, so its copies fly while ``w`` is multiplied.  The blocks of the
    last wave that lie past ``jmax`` are the ``jmax`` block again: every
    position of theirs is past the tile's last query and the mask in
    absolute positions hides it, as it hides what a live block holds past
    the row's length; a row that holds nothing walks one wave.  The
    accumulators are reset before the walk and the output written after it.

    THE WALK IN THE GRID (a pool the step cannot copy,
    :func:`_copies_its_blocks`; ``wave`` 1): the grid's fourth dimension is
    the logical block ``j`` and its pipeline hands the step block ``min(j,
    jmax)`` of each operand.  The accumulators are reset at ``j == 0``, a
    step up to ``jmax`` is one pass of the same softmax, the output is
    written at ``jmax``, and a step past it touches nothing (its index
    repeats, so nothing is copied either).

    The LATENT shape (``v_lanes`` given; one shared row a position, so
    ``group`` is 1 and the ``rep`` query heads are the rows): there is no V
    pool, the values being the first ``v_lanes`` lanes of the SAME tile the
    scores were taken against, so ONE copy feeds both products; and both
    products take their operands in the pool's dtype (the MXU's own in
    bfloat16, accumulated in float32) instead of upcasting them: a wave is
    ``[rep * qt, lanes] x [wave * bsz, lanes]^T`` then ``[rep * qt, wave *
    bsz] x [wave * bsz, v_lanes]``, three times the operations of a
    per-head K/V pair, and float32 operands would cost several passes
    each."""
    latent = v_lanes is not None
    n_pools = 1 if latent else 2
    n_in = n_pools + (2 if quantized else 0)
    ins = (k_in,) + rest[:n_in - 1]             # the pools, then the planes
    o_ref, acc_ref, m_ref, l_ref = rest[n_in - 1:n_in + 3]
    r = pl.program_id(0)
    hg = pl.program_id(1)
    ti = pl.program_id(2)
    jmax = jmax_ref[r, ti]
    rows = rep * qt
    span = wave * bsz
    qrow = jax.lax.broadcasted_iota(jnp.int32, (rows, span), 0)
    if rep > 1:
        qrow = qrow % qt
    qpos = start_ref[r] + ti * qt + qrow
    # q [g, rows, Dh] (latent: [1, rows, lanes], in the pool's dtype)
    q = q_ref[0] if latent else q_ref[0].astype(jnp.float32)

    def reset():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def attend(w, k, v, planes, at):
        """Fold wave ``w`` into the accumulators: its K and V blocks
        ``[wave, bsz, g * Dh]`` as the pool keeps them, and the int8 tier's
        two ``planes``, the wave's at the leading indices ``at``."""
        # Causal + ragged mask in absolute positions: query start+ti·qt+t
        # sees cache slots [0, its own position]; everything past the
        # row's true length (garbage in the final block, trash-block
        # padding, the last wave's blocks past jmax) is masked.  One mask
        # for the group.
        kpos = w * span + jax.lax.broadcasted_iota(jnp.int32, (rows, span), 1)
        visible = (kpos <= qpos)[None]
        # q x k [g, span, Dh] (latent: [1, span, lanes])
        k = (k.reshape(1, span, -1) if latent
             else _heads_from_lanes(k, group))
        s = _dot(q, k, trans_b=True) * scale             # [g, rows, span]
        if quantized:
            # Per-(head, position) K scale: constant along the contracted
            # Dh axis, so it multiplies the int8 score AFTER the dot —
            # the same algebra models/generate._block_with_cache applies
            # to the gathered view.
            s = _times_head_scales(s, planes[0], at, hg * group)
        s = jnp.where(visible, s, NEG_INF)
        m_prev = m_ref[:, :, :1]                         # [g, rows, 1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)                           # masked -> 0
        corr = jnp.exp(m_prev - m_cur)
        l_ref[:] = jnp.broadcast_to(
            l_ref[:, :, :1] * corr + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape,
        )
        if quantized:
            # V scale folds into the probabilities before the PV
            # contraction — again the gathered-view algebra, in-register.
            p = _times_head_scales(p, planes[1], at, hg * group)
        v = k[:, :, :v_lanes] if latent else _heads_from_lanes(v, group)
        acc_ref[:] = acc_ref[:] * corr + _dot(p.astype(v.dtype), v)
        m_ref[:] = jnp.broadcast_to(m_cur, m_ref.shape)

    def write_out():
        l = jnp.maximum(l_ref[:, :, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)

    if not in_step:
        j = pl.program_id(3)
        pl.when(j == 0)(reset)

        @pl.when(j <= jmax)
        def _pass():
            attend(j, ins[0][0], None if latent else ins[1][0],
                   ins[n_pools:], (0, 0))

        pl.when(j == jmax)(write_out)
        return

    tiles, sem = rest[n_in + 3:-1], rest[-1]
    layer = layer_ref[0]
    width = tiles[0].shape[-1]
    # The group's window of the pool's lanes: all of them where one group
    # holds every head (no dynamic offset then), else whole 128-lane
    # columns (:func:`_head_groups`).
    lanes = (slice(None) if width == k_in.shape[-1]
             else pl.ds(pl.multiple_of(hg * width, 128), width))

    def copies(w, slot):
        out = []
        for i in range(wave):
            block = table_ref[r, jnp.minimum(w * wave + i, jmax)]
            out += [pltpu.make_async_copy(ins[n].at[layer, block, :, lanes],
                                          tiles[n].at[slot, i],
                                          sem.at[slot, n])
                    for n in range(n_pools)]
        return out + [pltpu.make_async_copy(ins[n].at[r, w],
                                            tiles[n].at[slot],
                                            sem.at[slot, n])
                      for n in range(n_pools, n_in)]

    reset()
    waves = jmax // wave + 1
    for dma in copies(0, 0):
        dma.start()

    def one_wave(w, carry):
        slot = w % 2

        @pl.when(w + 1 < waves)
        def _next():
            for dma in copies(w + 1, 1 - slot):
                dma.start()

        for dma in copies(w, slot):
            dma.wait()
        attend(w, tiles[0][slot], None if latent else tiles[1][slot],
               tiles[n_pools:], (slot,))
        return carry

    jax.lax.fori_loop(0, waves, one_wave, 0)
    write_out()


def _attn_pallas_call(program: str, q: jax.Array, pool_k: jax.Array,
                      pool_v: Optional[jax.Array],
                      k_scale: Optional[jax.Array],
                      v_scale: Optional[jax.Array], table: jax.Array,
                      start: jax.Array, jmax: jax.Array, layer: jax.Array,
                      interpret: bool, rep: int = 1,
                      v_lanes: Optional[int] = None,
                      scale: Optional[float] = None) -> jax.Array:
    """q [R, H, NT·rep·QT, Dh] x the STACKED pool [L, NB, BLOCK, H·Dh] at
    ``layer`` i32[1] -> out like q; ``H`` the pool's heads, each read by
    ``rep`` query heads whose rows lie tile by tile in q's third dimension.
    ``jmax`` i32[R, NT] is the per-(row, query-tile) last useful logical
    block.  Head group, query tile and wave are :func:`_step_shape`'s (the
    wave held to the table's blocks), and the grid :func:`grid_steps`'s
    first three: a step a (row, head group, query tile), which walks the
    row's blocks itself.  The pool is handed over whole and left where it
    lies (``memory_space=pl.ANY``): the kernel copies the blocks it walks
    out of it, a wave at a time, by the table, the layer and its group's
    lanes, so no layer is sliced out of the pool and none relaid out for
    the call.  A pool the step cannot copy (:func:`_copies_its_blocks`) is
    walked by the grid's fourth dimension instead, a block a step, the
    layer and the table in the K/V index map.  The int8 tier's scales come
    as :func:`_wave_planes` lays them, [R, NW, H., wave·BLOCK.], and ride
    the same waves.  In the latent shape (``v_lanes``; ``pool_v`` None,
    ``H`` 1) the pool's rows are ``[lanes]`` wide, the output ``[v_lanes]``
    wide, and the call carries its own name on the device trace."""
    r, h, t_pad, dh = q.shape
    t_pad //= rep
    nbps = table.shape[1]
    bsz = pool_k.shape[2]
    latent = v_lanes is not None
    group, qt, wave = _step_shape(
        program, heads=h, head_dim=dh, block_size=bsz,
        kv_dtype=pool_k.dtype, t=t_pad, rep=rep, v_lanes=v_lanes)
    wave = min(wave, nbps)
    in_step = _copies_its_blocks(bsz, h * dh)
    grid = (r, h // group, t_pad // qt) + (() if in_step else (nbps,))
    if jmax.shape != (r, t_pad // qt) or t_pad % qt:
        raise ValueError(
            f"{program}: q rows {t_pad} and jmax {jmax.shape} are not the "
            f"padding of grid {grid}")
    if pool_k.ndim != 4 or pool_k.shape[3] != h * dh:
        raise ValueError(
            f"{program}: pool {pool_k.shape} is not [L, NB, BLOCK, "
            f"{h} x {dh}]")
    quantized = k_scale is not None
    if quantized and k_scale.shape[:2] != (r, -(-nbps // wave)):
        raise ValueError(
            f"{program}: scale planes {k_scale.shape} are not the rows' "
            f"waves of {wave} blocks")
    kernel = functools.partial(
        _paged_attn_kernel, bsz=bsz, qt=qt, group=group, wave=wave,
        in_step=in_step,
        scale=1.0 / math.sqrt(dh) if scale is None else scale,
        quantized=quantized, rep=rep, v_lanes=v_lanes,
    )
    out_dh = v_lanes if latent else dh

    def q_idx(ri, gi, ti, *_):
        return (ri, gi, ti, 0)

    # Where the grid walks: logical block j of (row r, tile ti) maps to
    # physical block table[r, min(j, jmax[r, ti])] of the call's layer —
    # beyond the tile's last useful block the index repeats and Pallas
    # issues no further copy.  A head group is a window of the lanes.
    def kv_idx(ri, gi, ti, ji, tbl, st, jm, ly):
        return (ly[0], tbl[ri, jnp.minimum(ji, jm[ri, ti])], 0, gi)

    def plane_idx(ri, gi, ti, ji, tbl, st, jm, ly):
        return (ri, jnp.minimum(ji, jm[ri, ti]), 0, 0)

    pools = [pool_k] + ([] if latent else [pool_v])
    planes = [k_scale, v_scale] if quantized else []
    if in_step:
        where_it_lies = pl.BlockSpec(memory_space=pl.ANY)
        in_specs = [where_it_lies] * len(pools + planes)
        tiles = [pltpu.VMEM((2, wave, bsz, group * dh), pool_k.dtype)
                 for _ in pools]
        tiles += [pltpu.VMEM((2,) + a.shape[2:], a.dtype) for a in planes]
        walk_scratch = tiles + [pltpu.SemaphoreType.DMA((2, len(tiles)))]
    else:
        in_specs = [pl.BlockSpec((1, 1, bsz, group * dh), kv_idx)
                    for _ in pools]
        in_specs += [pl.BlockSpec((1, 1) + a.shape[2:], plane_idx)
                     for a in planes]
        walk_scratch = []
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[pl.BlockSpec((1, group, rep * qt, dh), q_idx)] + in_specs,
        out_specs=pl.BlockSpec((1, group, rep * qt, out_dh), q_idx),
        scratch_shapes=[
            pltpu.VMEM((group, rep * qt, out_dh), jnp.float32),
            pltpu.VMEM((group, rep * qt, 128), jnp.float32),
            pltpu.VMEM((group, rep * qt, 128), jnp.float32),
            *walk_scratch,
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape[:3] + (out_dh,), q.dtype),
        interpret=interpret,
        name="latent_decode" if latent else None,
    )(table, start, jmax, layer, q, *pools, *planes)


@functools.partial(jax.jit, static_argnames=("interpret", "rep"))
def _paged_attn_call(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                     k_scale: Optional[jax.Array],
                     v_scale: Optional[jax.Array],
                     table: jax.Array, start: jax.Array, jmax: jax.Array,
                     layer: jax.Array,
                     interpret: bool = False, rep: int = 1) -> jax.Array:
    """The decode program's call (its name is what the device trace shows
    the kernel as): every query row of a slot in one tile."""
    return _attn_pallas_call("decode", q, pool_k, pool_v, k_scale, v_scale,
                             table, start, jmax, layer, interpret, rep)


@functools.partial(jax.jit, static_argnames=("interpret", "rep"))
def _paged_prefill_call(q: jax.Array, pool_k: jax.Array,
                        pool_v: jax.Array,
                        k_scale: Optional[jax.Array],
                        v_scale: Optional[jax.Array],
                        table: jax.Array, start: jax.Array,
                        jmax: jax.Array, layer: jax.Array,
                        interpret: bool = False, rep: int = 1) -> jax.Array:
    """The chunked-prefill program's call (likewise named on the trace):
    the chunk's rows in as few query tiles as fit."""
    return _attn_pallas_call("prefill", q, pool_k, pool_v, k_scale, v_scale,
                             table, start, jmax, layer, interpret, rep)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "rep", "v_lanes", "scale"))
def _latent_decode_call(q: jax.Array, pool: jax.Array, table: jax.Array,
                        start: jax.Array, jmax: jax.Array, layer: jax.Array,
                        interpret: bool, rep: int, v_lanes: int,
                        scale: Optional[float]) -> jax.Array:
    """The decode program's call in the latent shape, under a name of its
    own on the device trace (the benchmark's latent readers find it by).
    The chunk program's latent attention is another kernel, the expanded
    form (``ops/latent_attention.py``)."""
    return _attn_pallas_call("decode", q, pool, None, None, None, table,
                             start, jmax, layer, interpret, rep, v_lanes,
                             scale)


_ATTN_CALLS = {"decode": _paged_attn_call, "prefill": _paged_prefill_call}


def _attend(program: str, q: jax.Array, pool_k: jax.Array,
            pool_v: Optional[jax.Array], table: jax.Array, start: jax.Array,
            layer: jax.Array, k_scale: Optional[jax.Array],
            v_scale: Optional[jax.Array],
            interpret: Optional[bool], v_lanes: Optional[int] = None,
            scale: Optional[float] = None) -> jax.Array:
    """Pad ``q`` to ``program``'s query tiles, bound each tile's walk and
    call the kernel."""
    if (pool_v is None) != (v_lanes is not None):
        raise ValueError("a pool with no V half is the latent shape and "
                         "states v_lanes; any other pool has a V half")
    if v_lanes is not None and program != "decode":
        raise ValueError("the latent shape is the decode program's; a chunk "
                         "runs ops.latent_attention")
    r, h, t, dh = q.shape
    bsz = pool_k.shape[2]
    nbps = table.shape[1]
    kv_heads = pool_k.shape[3] // dh
    if h % kv_heads:
        raise ValueError(f"{h} query heads do not divide over the pool's "
                         f"{kv_heads} K/V heads")
    rep = h // kv_heads
    if interpret is None:
        interpret = pallas_interpret()
    if jnp.ndim(start) == 0:
        start = jnp.broadcast_to(start, (r,))
    start = start.astype(jnp.int32)
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    _, qt, wave = _step_shape(program, heads=kv_heads, head_dim=dh,
                              block_size=bsz, kv_dtype=pool_k.dtype, t=t,
                              rep=rep, v_lanes=v_lanes)
    nt = -(-t // qt)
    jmax = _tile_bounds(start, t, qt, bsz, nbps).astype(jnp.int32)
    if nt * qt != t:
        # Mosaic sublane: the query tile's row dim pads to 8.
        q = jnp.pad(q, ((0, 0), (0, 0), (0, nt * qt - t), (0, 0)))
    if k_scale is not None:
        k_scale, v_scale = (
            _wave_planes(planes, layer[0], table, min(wave, nbps),
                         _copies_its_blocks(bsz, pool_k.shape[3]))
            for planes in (k_scale, v_scale))
    if rep == 1 and v_lanes is None:
        out = _ATTN_CALLS[program](q, pool_k, pool_v, k_scale, v_scale,
                                   table, start, jmax, layer,
                                   interpret=interpret)
        return out[:, :, :t]
    # Grouped heads: the rep query heads of a K/V head become rows of ITS
    # products, tile by tile: [R, KV, rep, NT, QT, Dh] -> [R, KV, NT, rep,
    # QT, Dh] -> [R, KV, NT·rep·QT, Dh], and back.
    def tiled(a, inner, outer):
        a = a.reshape((r, kv_heads) + inner + (qt, a.shape[-1]))
        return jnp.swapaxes(a, 2, 3).reshape((r, kv_heads) + outer)

    rows = tiled(q, (rep, nt), (nt * rep * qt, dh))
    if v_lanes is not None:
        out = _latent_decode_call(
            rows, pool_k, table, start, jmax, layer, interpret=interpret,
            rep=rep, v_lanes=v_lanes, scale=scale)
    else:
        out = _ATTN_CALLS[program](
            rows, pool_k, pool_v, k_scale, v_scale, table, start, jmax,
            layer, interpret=interpret, rep=rep)
    width = out.shape[-1]
    return tiled(out, (nt, rep), (rep * nt * qt, width)).reshape(
        r, h, nt * qt, width)[:, :, :t]


def paged_attention(q: jax.Array, pool_k: jax.Array,
                    pool_v: Optional[jax.Array],
                    table: jax.Array, start: jax.Array, *,
                    layer: jax.Array = 0,
                    k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None,
                    interpret: Optional[bool] = None,
                    v_lanes: Optional[int] = None,
                    scale: Optional[float] = None) -> jax.Array:
    """Ragged paged-decode attention over layer ``layer`` of the STACKED
    block pool.

    ``q`` [R, H, T, Dh] queries at absolute positions ``start[r] + t``
    (``start`` i32[R] or scalar); ``pool_k``/``pool_v`` [L, NB, BLOCK,
    KV·Dh] (a position's heads side by side in one row; ``KV`` = ``H``, or
    a divisor of it: query head ``h`` then reads K/V head ``h // (H /
    KV)``) with optional int8 tier scales [L, NB, BLOCK, H] (at ``KV`` =
    ``H`` only); ``layer`` an i32 scalar (a traced
    value: the layer loop's index); ``table`` i32 [R, NBPS] physical
    block ids (traced values — block churn never recompiles).  The row's
    K/V for positions [0, start+T) — INCLUDING the freshly written
    window — must already be in the pool: the kernel-path block
    (models/generate._paged_block) writes the new rows first, then
    attends, where the jnp path writes into its gathered view.  Returns
    [R, H, T, Dh] in q's dtype with f32 accumulation throughout.

    THE LATENT SHAPE (latent attention in its absorbed form, which the
    decode program runs, ``models/decoder.py``): ``pool_v`` None and
    ``v_lanes`` given.
    ``pool_k`` [L, NB, BLOCK, lanes] keeps ONE shared row a position;
    ``q`` [R, H, T, lanes] are the H heads' absorbed queries against it,
    the scores are ``scale * q . row`` over all the lanes (``scale`` given
    by the caller: the width the queries were made at is not the row's)
    and the values are the row's first ``v_lanes`` lanes, so the result is
    [R, H, T, v_lanes]: one copy of a block feeds both products.

    Semantics contract (pinned by tests/test_paged_attention.py against
    :func:`paged_attention_reference` and the jnp serve path): causal
    mask ``kpos <= start+t`` in absolute positions, int8 scales applied
    post-dot (K) / pre-contraction (V), nothing read past the wave that
    holds a row's last position (and within it no block but the row's
    own), and no layer but ``layer`` read at all."""
    return _attend("decode", q, pool_k, pool_v, table, start, layer,
                   k_scale, v_scale, interpret, v_lanes, scale)


def paged_attention_reference(q: jax.Array, pool_k: jax.Array,
                              pool_v: Optional[jax.Array],
                              table: jax.Array,
                              start: jax.Array, *, layer: jax.Array = 0,
                              k_scale: Optional[jax.Array] = None,
                              v_scale: Optional[jax.Array] = None,
                              v_lanes: Optional[int] = None,
                              scale: Optional[float] = None
                              ) -> jax.Array:
    """The jnp gather semantics the kernel is pinned against — the same
    math models/generate routes through ``_paged_gather`` +
    ``_block_with_cache``, spelled standalone (f32 softmax, full-width
    mask) so the kernel test does not depend on the transformer block.
    The latent shape (``pool_v`` None, ``v_lanes``, ``scale``) as
    :func:`paged_attention` states it: every head reads the ONE shared
    row, the values its first ``v_lanes`` lanes."""
    r, h, t, dh = q.shape
    if jnp.ndim(start) == 0:
        start = jnp.broadcast_to(start, (r,))
    if pool_v is None:
        pool_v = pool_k[..., :v_lanes]

    kv_heads = pool_k.shape[-1] // dh

    def gather(pool):                       # -> [R, H, NBPS*BLOCK, X]
        g = pool[layer][table]              # [R, NBPS, BLOCK, KV*X]
        g = g.reshape(r, -1, kv_heads, g.shape[-1] // kv_heads) \
            .transpose(0, 2, 1, 3)
        # Grouped heads: query head i reads K/V head i // (h / kv_heads).
        return g if kv_heads == h else jnp.repeat(g, h // kv_heads, axis=1)

    view_k = gather(pool_k).astype(jnp.float32)
    view_v = gather(pool_v).astype(jnp.float32)
    s = jnp.einsum("rhtd,rhkd->rhtk", q.astype(jnp.float32), view_k)
    s = s * (1.0 / math.sqrt(q.shape[-1]) if scale is None else scale)
    if k_scale is not None:
        s = s * gather(k_scale)[:, :, None, :, 0]
    kpos = jnp.arange(view_k.shape[2])[None, None, None, :]
    qpos = (start[:, None] + jnp.arange(t)[None, :])[:, None, :, None]
    s = jnp.where(kpos <= qpos, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        p = p * gather(v_scale)[:, :, None, :, 0]
    return jnp.einsum("rhtk,rhkd->rhtd", p, view_v).astype(q.dtype)


def paged_prefill_attention(q: jax.Array, pool_k: jax.Array,
                            pool_v: jax.Array, table: jax.Array,
                            start: jax.Array, *, layer: jax.Array = 0,
                            k_scale: Optional[jax.Array] = None,
                            v_scale: Optional[jax.Array] = None,
                            interpret: Optional[bool] = None) -> jax.Array:
    """Chunked-prefill attention over layer ``layer`` of the stacked
    block pool.

    The multi-query-row twin of :func:`paged_attention` for T ≫ 1, on
    the same kernel: the chunk's T query rows go in ONE tile where that
    fits VMEM (:func:`_step_shape`; 64 rows of 20 heads do) and else in
    tiles, each with its OWN ragged causal bound (the last logical block
    its final query can see), so KV streaming is proportional to the
    causal area — the flash-attention causal skip over a paged block
    table.  Same semantics contract as :func:`paged_attention`
    (absolute-position mask, int8 scales post-dot / pre-contraction, a
    tile's walk ended at its bound); the jnp pin is the same
    :func:`paged_attention_reference`."""
    return _attend("prefill", q, pool_k, pool_v, table, start, layer,
                   k_scale, v_scale, interpret)


# ---------------------------------------------------------------------------
# Trust epilogue: entropy + top-1 margin in one pass over the vocab
# ---------------------------------------------------------------------------


def _trust_init(m_ref, s_ref, w_ref, t1_ref, t2_ref):
    """Reset the five online-reduction accumulators (grid step 0)."""
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    s_ref[:] = jnp.zeros_like(s_ref)
    w_ref[:] = jnp.zeros_like(w_ref)
    t1_ref[:] = jnp.full_like(t1_ref, NEG_INF)
    t2_ref[:] = jnp.full_like(t2_ref, NEG_INF)


def _trust_update(x, m_ref, s_ref, w_ref, t1_ref, t2_ref):
    """Fold one [B, TV] logit tile into the online reductions: logsumexp
    pieces (m, Σe^{x−m}, Σx·e^{x−m}) for the entropy and an exact top-2
    merge for the margin.  ONE spelling shared by the standalone trust
    epilogue and the fused verify tail, so the fused stats can never
    drift from the pinned epilogue algebra."""
    b, tv = x.shape
    tile_m = jnp.max(x, axis=-1, keepdims=True)          # [B, 1]
    m_prev = m_ref[:, :1]
    m_cur = jnp.maximum(m_prev, tile_m)
    corr = jnp.exp(m_prev - m_cur)
    e = jnp.exp(x - m_cur)
    s_ref[:] = jnp.broadcast_to(
        s_ref[:, :1] * corr + jnp.sum(e, axis=-1, keepdims=True),
        s_ref.shape,
    )
    w_ref[:] = jnp.broadcast_to(
        w_ref[:, :1] * corr + jnp.sum(x * e, axis=-1, keepdims=True),
        w_ref.shape,
    )
    m_ref[:] = jnp.broadcast_to(m_cur, m_ref.shape)
    # Exact top-2 within the tile: mask ONE argmax occurrence (duplicated
    # maxima must surface as top2 == top1), then merge with the running
    # pair — max/min only, so the margin is bit-exact vs lax.top_k.
    amax = jnp.argmax(x, axis=-1)[:, None]               # [B, 1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, tv), 1)
    tile_t2 = jnp.max(jnp.where(cols == amax, NEG_INF, x), axis=-1,
                      keepdims=True)
    t1_prev = t1_ref[:, :1]
    t2_prev = t2_ref[:, :1]
    t1_ref[:] = jnp.broadcast_to(jnp.maximum(t1_prev, tile_m),
                                 t1_ref.shape)
    t2_ref[:] = jnp.broadcast_to(
        jnp.maximum(jnp.minimum(t1_prev, tile_m),
                    jnp.maximum(t2_prev, tile_t2)),
        t2_ref.shape,
    )


def _trust_finalize(ent_ref, mar_ref, m_ref, s_ref, w_ref, t1_ref,
                    t2_ref):
    """Write entropy/margin from the accumulators (last grid step)."""
    s = jnp.maximum(s_ref[:, :1], 1e-30)
    logz = m_ref[:, :1] + jnp.log(s)
    # entropy = -Σ p·logp = logZ - Σ p·x with p = e^{x-m}/s.
    ent_ref[:] = logz - w_ref[:, :1] / s                 # [B, 1]
    mar_ref[:] = t1_ref[:, :1] - t2_ref[:, :1]


def _trust_stats_kernel(x_ref, ent_ref, mar_ref, m_ref, s_ref, w_ref,
                        t1_ref, t2_ref, *, nv: int):
    """One [B, TRUST_TILE] logit tile of the standalone epilogue."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        _trust_init(m_ref, s_ref, w_ref, t1_ref, t2_ref)

    _trust_update(x_ref[:], m_ref, s_ref, w_ref, t1_ref, t2_ref)

    @pl.when(j == nv - 1)
    def _finalize():
        _trust_finalize(ent_ref, mar_ref, m_ref, s_ref, w_ref, t1_ref,
                        t2_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _trust_stats_call(logits: jax.Array,
                      interpret: bool = False
                      ) -> Tuple[jax.Array, jax.Array]:
    b, v = logits.shape
    nv = v // TRUST_TILE
    ent, mar = pl.pallas_call(
        functools.partial(_trust_stats_kernel, nv=nv),
        grid=(nv,),
        in_specs=[pl.BlockSpec((b, TRUST_TILE), lambda j: (0, j))],
        out_specs=[
            # [B, 1] columns — the same Mosaic lane-dim rule as flash
            # attention's lse output.
            pl.BlockSpec((b, 1), lambda j: (0, 0)),
            pl.BlockSpec((b, 1), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((b, 128), jnp.float32)
                        for _ in range(5)],
        interpret=interpret,
    )(logits)
    return ent[:, 0], mar[:, 0]


def logit_trust_stats(logits: jax.Array,
                      interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """(softmax entropy [B], top-1 logit margin [B]) of ``logits``
    [B, V] in ONE streaming pass — the output monitor's per-token
    reductions, fused so serve-side trust monitoring costs one extra
    read of nothing (the logits tile is already in VMEM).

    Margin is bit-exact vs the jnp reductions; entropy agrees to f32
    epsilon (online vs two-pass logsumexp)."""
    b, v = logits.shape
    if interpret is None:
        interpret = pallas_interpret()
    logits = logits.astype(jnp.float32)
    pad_v = (-v) % TRUST_TILE
    if pad_v:
        # NEG_INF (finite) padding: e^{pad-m} flushes to exactly 0 and
        # x·0 stays 0 (a true -inf would NaN the Σx·e term), and a pad
        # column can never win either top-2 slot.
        logits = jnp.pad(logits, ((0, 0), (0, pad_v)),
                         constant_values=NEG_INF)
    pad_b = (-b) % QROWS
    if pad_b:
        logits = jnp.pad(logits, ((0, pad_b), (0, 0)))
    ent, mar = _trust_stats_call(logits, interpret=interpret)
    return ent[:b], mar[:b]


def logit_trust_stats_reference(logits: jax.Array
                                ) -> Tuple[jax.Array, jax.Array]:
    """The jnp reference reductions (identical math to
    serve/scheduler._logit_signals' fallback path), for the equality
    pins."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    p = jnp.exp(logp)
    entropy = -jnp.sum(p * logp, axis=-1)
    top2 = jax.lax.top_k(logits, 2)[0]
    return entropy, top2[:, 0] - top2[:, 1]


# ---------------------------------------------------------------------------
# Fused speculative-verify tail: logits projection + trust stats in ONE
# streaming vocab pass
# ---------------------------------------------------------------------------


def _verify_tail_kernel(x_ref, w_ref, logits_ref, ent_ref, mar_ref,
                        m_ref, s_ref, wacc_ref, t1_ref, t2_ref, *,
                        nv: int, v: int, round_dtype):
    """One [TRUST_TILE, D] head tile: matmul the resident activations
    against it, WRITE the logits tile (sampling still needs the full
    row), and fold the tile into the shared trust reductions before it
    leaves VMEM."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        _trust_init(m_ref, s_ref, wacc_ref, t1_ref, t2_ref)

    acc = _dot(x_ref[:], w_ref[:], trans_b=True)         # [B, TV] f32
    if round_dtype is not None:
        # The jnp tail's matmul runs in the compute dtype and upcasts
        # AFTER — round the f32 accumulator the same way so the fused
        # logits match the materialised ones.
        acc = acc.astype(round_dtype).astype(jnp.float32)
    logits_ref[:] = acc
    # Vocab-padding columns (zero rows of the padded head) produce logit
    # 0, not NEG_INF — mask them out of the reductions exactly as the
    # standalone epilogue's NEG_INF padding does; the written tile's pad
    # columns are sliced away by the wrapper.
    b, tv = acc.shape
    cols = j * tv + jax.lax.broadcasted_iota(jnp.int32, (b, tv), 1)
    x = jnp.where(cols < v, acc, NEG_INF)
    _trust_update(x, m_ref, s_ref, wacc_ref, t1_ref, t2_ref)

    @pl.when(j == nv - 1)
    def _finalize():
        _trust_finalize(ent_ref, mar_ref, m_ref, s_ref, wacc_ref,
                        t1_ref, t2_ref)


@functools.partial(jax.jit, static_argnames=("v", "interpret", "round_to"))
def _verify_tail_call(normed: jax.Array, head: jax.Array, v: int,
                      interpret: bool = False,
                      round_to: Optional[str] = None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, d = normed.shape
    v_pad = head.shape[0]
    nv = v_pad // TRUST_TILE
    round_dtype = jnp.dtype(round_to) if round_to is not None else None
    logits, ent, mar = pl.pallas_call(
        functools.partial(_verify_tail_kernel, nv=nv, v=v,
                          round_dtype=round_dtype),
        grid=(nv,),
        in_specs=[
            pl.BlockSpec((b, d), lambda j: (0, 0)),
            pl.BlockSpec((TRUST_TILE, d), lambda j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b, TRUST_TILE), lambda j: (0, j)),
            pl.BlockSpec((b, 1), lambda j: (0, 0)),
            pl.BlockSpec((b, 1), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, v_pad), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((b, 128), jnp.float32)
                        for _ in range(5)],
        interpret=interpret,
    )(normed, head)
    return logits, ent[:, 0], mar[:, 0]


def fused_verify_tail(normed: jax.Array, head: jax.Array, *,
                      interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The speculative-verify tail in ONE streaming vocab pass:
    ``normed`` [B, D] (post-ln_f activations, already in the compute
    dtype) x ``head`` [V, D] (the tied unembedding) -> (logits [B, V]
    f32, entropy [B], margin [B]).

    Replaces the two-pass jnp tail — ``_all_logits`` materialising
    [B, V] to HBM, then :func:`logit_trust_stats` re-reading it — with
    one grid over vocab tiles: each head tile is matmul'd, written once
    (the verify sampler's ``jax.random.categorical`` consumes full
    rows; its gumbel draws cannot be reproduced in-kernel without
    forking the sampled stream, so the logits write stays — the pass
    sampling pays anyway) and reduced while still in VMEM.  The trust
    algebra is literally the epilogue kernel's (`_trust_update`), so
    margin stays bit-exact vs ``lax.top_k`` over the SAME logits and
    entropy agrees to f32 epsilon."""
    b, d = normed.shape
    v = head.shape[0]
    if interpret is None:
        interpret = pallas_interpret()
    # Rounding contract: a bf16 jnp tail rounds the matmul to bf16
    # before the f32 upcast — mirror it so fused == materialised.
    round_to = (None if normed.dtype == jnp.float32
                else jnp.dtype(normed.dtype).name)
    pad_v = (-v) % TRUST_TILE
    if pad_v:
        head = jnp.pad(head, ((0, pad_v), (0, 0)))
    pad_b = (-b) % QROWS
    if pad_b:
        normed = jnp.pad(normed, ((0, pad_b), (0, 0)))
    logits, ent, mar = _verify_tail_call(normed, head, v,
                                         interpret=interpret,
                                         round_to=round_to)
    return logits[:b, :v], ent[:b], mar[:b]


# ---------------------------------------------------------------------------
# In-grid adapter gather: the per-slot low-rank delta with the page
# table as a scalar-prefetch operand
# ---------------------------------------------------------------------------


def _adapter_delta_kernel(pages_ref, sa_ref, sb_ref, x_ref, a_ref, b_ref,
                          o_ref):
    """One row's low-rank delta: the BlockSpec index maps resolved
    ``row -> pages[row]`` before the A/B DMAs were issued, so the pool
    pages stream HBM→VMEM exactly like KV blocks — no gathered [R, D,
    r] copy exists.  Scale order matches ``lowrank_delta`` exactly
    (h·sa between the contractions): scalar folding would change the
    f32 rounding the adapter parity pins rely on."""
    ri = pl.program_id(0)
    x = x_ref[0].astype(jnp.float32)                     # [T, D]
    a = a_ref[0].astype(jnp.float32)                     # [D, r]
    h = _dot(x, a) * sa_ref[ri]                          # [T, r] f32
    b = b_ref[0].astype(jnp.float32)                     # [r, D]
    o_ref[0] = _dot(h, b) * sb_ref[ri]                   # [T, D] f32


@functools.partial(jax.jit, static_argnames=("interpret",))
def _adapter_delta_call(x: jax.Array, a_pool: jax.Array,
                        b_pool: jax.Array, pages: jax.Array,
                        sa: jax.Array, sb: jax.Array,
                        interpret: bool = False) -> jax.Array:
    r, t_pad, d = x.shape
    rank = a_pool.shape[-1]

    def a_idx(ri, pg, sa_, sb_):
        return (pg[ri], 0, 0)

    def x_idx(ri, pg, sa_, sb_):
        return (ri, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(r,),
        in_specs=[
            pl.BlockSpec((1, t_pad, d), x_idx),
            pl.BlockSpec((1, d, rank), a_idx),
            pl.BlockSpec((1, rank, d), a_idx),
        ],
        out_specs=pl.BlockSpec((1, t_pad, d), x_idx),
        scratch_shapes=[],
    )
    return pl.pallas_call(
        _adapter_delta_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, t_pad, d), jnp.float32),
        interpret=interpret,
    )(pages, sa, sb, x, a_pool, b_pool)


def adapter_delta(x: jax.Array, a_pool: jax.Array, b_pool: jax.Array,
                  pages: jax.Array, *,
                  a_scale: Optional[jax.Array] = None,
                  b_scale: Optional[jax.Array] = None,
                  interpret: Optional[bool] = None) -> jax.Array:
    """In-grid paged low-rank delta for ONE adapter site:
    ``x`` [R, T, D] x pool pages ``a_pool`` [P+1, D, r] / ``b_pool``
    [P+1, r, D] selected by ``pages`` i32[R] (the per-slot
    ``adapter_page_row`` — a traced value, so adapter churn never
    recompiles) -> f32 [R, T, D].

    The kernel-grid twin of ``fused_dequant_matmul.lowrank_delta`` over
    ``a_pool[pages]`` — same contraction, same f32 accumulation, same
    scale order — minus the take: the page table joins the
    scalar-prefetch operands and each row's A/B tiles stream HBM→VMEM
    alongside its KV blocks.  ``a_scale``/``b_scale`` are the int8
    tier's per-page scales [P+1] for this site (None on the f32 tier —
    the kernel multiplies by exactly 1.0, a bitwise identity)."""
    r, t, d = x.shape
    if interpret is None:
        interpret = pallas_interpret()
    pages = pages.astype(jnp.int32)
    npg = a_pool.shape[0]
    ones = jnp.ones((npg,), jnp.float32)
    sa = ones if a_scale is None else a_scale.astype(jnp.float32)
    sb = ones if b_scale is None else b_scale.astype(jnp.float32)
    # The [R] per-row scale lookup happens outside — R scalars, not the
    # [R, D, r] page take this kernel exists to eliminate — and rides
    # scalar prefetch so the kernel reads its row's scale from SMEM.
    sa_row = sa[pages]
    sb_row = sb[pages]
    t_pad = -(-t // QROWS) * QROWS
    if t_pad != t:
        x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))
    out = _adapter_delta_call(x, a_pool, b_pool, pages, sa_row, sb_row,
                              interpret=interpret)
    return out[:, :t]


__all__ = [
    "ATTN_IMPLS",
    "PAGED_PROGRAMS",
    "adapter_delta",
    "fused_verify_tail",
    "grid_steps",
    "logit_trust_stats",
    "logit_trust_stats_reference",
    "paged_attention",
    "paged_attention_reference",
    "paged_prefill_attention",
    "resolve_attn_impl",
    "resolve_attn_impls",
    "supports_paged_attention",
    "walked_blocks",
]
