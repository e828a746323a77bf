"""Paged multi-tenant adapter pool — the SECOND paged HBM resource.

The serving engine already pages one HBM resource: KV blocks, claimed
per slot through a traced block table so admission churn never
recompiles (serve/kv_slots.py).  This module applies the same
discipline to MODEL WEIGHTS: per-tenant rank-r low-rank deltas
("adapters") on the attention output projection and the MLP, stored in
one pool array per side::

    a        [L, P+1, 2, D, r]   down-projections (sites: 0 = attn out,
    b        [L, P+1, 2, r, D]   1 = MLP), page 0 reserved as the ZERO
                                 page — the adapter-off identity delta
    a_scale  [L, P+1, 2]         int8 tier only: per-(layer, page,
    b_scale  [L, P+1, 2]         site) symmetric dequant scales

and keyed at trace time by a per-slot **adapter-page table** (i32
[max_slots], the ``paged_decode`` block-table pattern): each decode
tick gathers every slot's pages inside the layer scan and adds

    delta_attn = (attn_out @ A[:, 0]) @ B[:, 0]
    delta_mlp  = (ln_2_out @ A[:, 1]) @ B[:, 1]

so a batch can mix N distinct tenants' adapters in ONE compiled
program.  Adapter residency changes are ``.at[:, page].set`` buffer
updates (same shapes, same donation story: none — the pool persists
across ticks), so adapter churn, eviction and tenant-mix changes NEVER
recompile; the CompileWatcher guard on the decode loop enforces it.

Host side, :class:`AdapterPool` composes ``kv_slots.BlockAllocator``
(refcounts + quarantine + attribution journal, reused verbatim) with
the prefix cache's residency discipline: the pool itself holds ONE
reference on every resident page, each in-flight request holds one
more, and LRU eviction only ever considers pages at refcount 1 (cold —
no live request).  A fleet-wide adapter quarantine impounds the page
through the same ``release(quarantine=True)`` trust hook KV blocks
use, deferring until the last in-flight request drains.

Adapter weights are materialised DETERMINISTICALLY from the adapter id
(:func:`materialize_adapter`): every replica of a fleet uploads
bit-identical deltas for the same tenant, so fail-over and verdict
voting stay exact.

**Locality contract (tddl-lint ``adapter-locality``)**: the adapter
page-table row and any adapter PartitionSpec are spelled ONLY here —
:func:`adapter_page_row` / :func:`adapter_partition_specs` — and
imported by the scheduler/engine, never re-derived.
"""

from __future__ import annotations

import hashlib
from typing import Any, Dict, List, Optional, Set, Tuple

import numpy as np

from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.serve.kv_slots import BlockAllocator

#: Reserved all-zeros pool page: slots without an adapter point here
#: and receive an exactly-zero delta.  Mirrors ``kv_slots.TRASH_BLOCK``.
ZERO_PAGE = 0

#: The two delta injection sites, in pool-axis order.
SITE_ATTN_OUT = 0
SITE_MLP = 1

#: Default init scale for materialised adapter weights — small enough
#: that a benign adapter perturbs rather than destroys the base model's
#: streams, large enough that two tenants' outputs measurably differ.
DEFAULT_INIT_SCALE = 0.02


def adapter_bytes_per_page(cfg: gpt2.GPT2Config, rank: int,
                           adapter_dtype: str = "model") -> int:
    """HBM bytes ONE pool page costs (both sides, both sites, all
    layers) — the unit the engine's headroom-gated sizing works in."""
    if rank < 1:
        raise ValueError(f"rank must be >= 1, got {rank}")
    elems = cfg.n_layer * 2 * cfg.n_embd * rank * 2      # a + b
    if adapter_dtype == "int8":
        return elems + cfg.n_layer * 2 * 2 * 4           # int8 + f32 scales
    import jax.numpy as jnp

    return elems * jnp.dtype(cfg.dtype).itemsize


def adapter_pool_bytes(cfg: gpt2.GPT2Config, pages: int, rank: int,
                       adapter_dtype: str = "model") -> int:
    """Total pool bytes for ``pages`` usable pages (+1 zero page)."""
    return (pages + 1) * adapter_bytes_per_page(cfg, rank, adapter_dtype)


def adapter_page_row(page_by_slot: Dict[int, int],
                     max_slots: int) -> np.ndarray:
    """THE one spelling of the per-slot adapter-page table row: i32
    [max_slots], ``ZERO_PAGE`` everywhere a slot carries no adapter.
    The scheduler feeds this (as a traced array) into every paged
    decode/prefill dispatch — values change per tick, the shape never
    does, so the compile-once pin holds."""
    row = np.full((max_slots,), ZERO_PAGE, np.int32)
    for slot, page in page_by_slot.items():
        row[slot] = page
    return row


def adapter_partition_specs() -> Tuple[Any, Any]:
    """PartitionSpecs for the (a, b) pool arrays: replicated — every
    chip serves every tenant, exactly like the KV pool.  Resolved only
    here (lint: adapter-locality) through the sharding registry; the
    engine applies them when a mesh is active."""
    from trustworthy_dl_tpu.core import sharding as shreg

    return shreg.replicated_spec(), shreg.replicated_spec()


def _adapter_seed(name: str) -> int:
    """Stable 64-bit seed from the adapter id — identical across
    processes, python versions and fleet replicas (``hash()`` is
    salted per process; this must not be)."""
    return int.from_bytes(
        hashlib.sha256(name.encode("utf-8")).digest()[:8], "little")


def materialize_adapter(name: str, cfg: gpt2.GPT2Config, rank: int,
                        init_scale: float = DEFAULT_INIT_SCALE
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Deterministic per-tenant weights: (a [L, 2, D, r], b [L, 2, r, D])
    f32, drawn from a generator seeded by the adapter id alone.  In a
    real deployment these load from a registry; here the registry is a
    seeded RNG so drills, tests and every fleet replica agree
    bit-for-bit on what tenant X's model delta IS."""
    rng = np.random.default_rng(_adapter_seed(name))
    d = cfg.n_embd
    a = rng.standard_normal((cfg.n_layer, 2, d, rank),
                            dtype=np.float32) * init_scale
    b = rng.standard_normal((cfg.n_layer, 2, rank, d),
                            dtype=np.float32) * init_scale
    return a, b


def quantize_adapter(a: np.ndarray, b: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray,
                                np.ndarray, np.ndarray]:
    """Symmetric int8 per-(layer, site) quantization of one adapter's
    (a, b): returns (a_q, a_scale [L, 2], b_q, b_scale).  The scales
    multiply back inside the low-rank matmul's f32 accumulator
    (``ops.fused_dequant_matmul.lowrank_delta`` — dequant in register,
    never a materialised f32 pool copy)."""
    out = []
    for w in (a, b):
        amax = np.max(np.abs(w), axis=(2, 3))            # [L, 2]
        scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
        q = np.clip(np.rint(w / scale[:, :, None, None]),
                    -127, 127).astype(np.int8)
        out.extend([q, scale])
    return tuple(out)


class AdapterPool:
    """Device pool arrays + the host-side page lifecycle.

    ``pages`` usable pages (ids [1, pages]; page 0 = the zero page).
    Every RESIDENT page carries one reference held by the pool itself
    (the residency ref); each admitted request holds one more.  LRU
    eviction considers only refcount-1 (cold) pages, so an adapter with
    in-flight traffic can never be evicted under it.  ``quarantine``
    impounds a page through ``BlockAllocator.release(quarantine=True)``
    — immediately when cold, else deferred to the last request release.
    """

    def __init__(self, cfg: gpt2.GPT2Config, rank: int, pages: int,
                 adapter_dtype: str = "model",
                 init_scale: float = DEFAULT_INIT_SCALE,
                 pages_gauge: Any = None, evictions_counter: Any = None,
                 trace: Any = None):
        import jax.numpy as jnp

        if pages < 1:
            raise ValueError(f"pages must be >= 1, got {pages}")
        if rank < 1:
            raise ValueError(f"rank must be >= 1, got {rank}")
        self.cfg = cfg
        self.rank = int(rank)
        self.pages = int(pages)
        self.adapter_dtype = adapter_dtype
        self.init_scale = float(init_scale)
        d = cfg.n_embd
        shape_a = (cfg.n_layer, pages + 1, 2, d, rank)
        shape_b = (cfg.n_layer, pages + 1, 2, rank, d)
        if adapter_dtype == "int8":
            self.a = jnp.zeros(shape_a, jnp.int8)
            self.b = jnp.zeros(shape_b, jnp.int8)
            # Scale 1.0 everywhere (incl. the zero page): dequantising
            # an untouched page is exactly 0.0 * 1.0 = 0.0.
            self.a_scale = jnp.ones((cfg.n_layer, pages + 1, 2),
                                    jnp.float32)
            self.b_scale = jnp.ones((cfg.n_layer, pages + 1, 2),
                                    jnp.float32)
        elif adapter_dtype == "model":
            self.a = jnp.zeros(shape_a, cfg.dtype)
            self.b = jnp.zeros(shape_b, cfg.dtype)
            self.a_scale = None
            self.b_scale = None
        else:
            raise ValueError(
                f"adapter_dtype must be 'model' or 'int8', got "
                f"{adapter_dtype!r}")
        # The SAME allocator class KV blocks use — refcounts, LIFO free
        # list over [1, pages], quarantine set, attribution journal.
        self.alloc = BlockAllocator(pages)
        self._page_of: Dict[str, int] = {}
        self._adapter_of: Dict[int, str] = {}
        self._lru: Dict[str, int] = {}
        self._clock = 0
        self._quarantined: Set[str] = set()
        self._impounded: Dict[str, int] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.uploads = 0
        self._pages_gauge = pages_gauge
        self._evictions_counter = evictions_counter
        self.trace = trace

    # -- device upload -----------------------------------------------------

    def _upload(self, name: str, page: int) -> None:
        """Materialise ``name``'s weights into pool page ``page`` — a
        pure buffer update (``.at[:, page].set``): shapes are static,
        so residency churn can never be a recompile."""
        import jax.numpy as jnp

        a_np, b_np = materialize_adapter(name, self.cfg, self.rank,
                                         self.init_scale)
        if self.adapter_dtype == "int8":
            a_q, a_s, b_q, b_s = quantize_adapter(a_np, b_np)
            self.a = self.a.at[:, page].set(jnp.asarray(a_q))
            self.b = self.b.at[:, page].set(jnp.asarray(b_q))
            self.a_scale = self.a_scale.at[:, page].set(jnp.asarray(a_s))
            self.b_scale = self.b_scale.at[:, page].set(jnp.asarray(b_s))
        else:
            self.a = self.a.at[:, page].set(
                jnp.asarray(a_np, self.a.dtype))
            self.b = self.b.at[:, page].set(
                jnp.asarray(b_np, self.b.dtype))
        self.uploads += 1

    # -- lifecycle ---------------------------------------------------------

    def _touch(self, name: str) -> None:
        self._clock += 1
        self._lru[name] = self._clock

    def _evict_cold(self) -> Optional[str]:
        """Evict the least-recently-used COLD resident (residency ref
        only — no in-flight request) and return its name, or None when
        every resident page is live (backpressure, not an error)."""
        for name in sorted(self._lru, key=self._lru.get):
            page = self._page_of[name]
            if self.alloc.refcount(page) == 1:
                self._page_of.pop(name)
                self._adapter_of.pop(page)
                self._lru.pop(name)
                self.alloc.release(page)           # residency ref -> freed
                self.evictions += 1
                if self._evictions_counter is not None:
                    self._evictions_counter.inc(tenant=name)
                return name
        return None

    def acquire(self, name: str) -> Optional[int]:
        """Claim one request reference on ``name``'s page, resolving
        residency on miss (alloc, else LRU-evict a cold tenant, else
        None = backpressure — the KV-block admission semantics).
        Quarantined adapters never resolve."""
        if name in self._quarantined:
            return None
        page = self._page_of.get(name)
        if page is not None:
            self.hits += 1
            self.alloc.incref(page)
            self._touch(name)
            self._set_gauge()
            return page
        self.misses += 1
        got = self.alloc.alloc(1)
        evicted: Optional[str] = None
        if got is None:
            evicted = self._evict_cold()
            if evicted is None:
                return None
            got = self.alloc.alloc(1)
            assert got is not None, "free page vanished after eviction"
        page = got[0]
        self._upload(name, page)
        self._page_of[name] = page
        self._adapter_of[page] = name
        self._touch(name)
        if self.trace is not None:
            from trustworthy_dl_tpu.obs.events import EventType

            self.trace.emit(EventType.ADAPTER_SWAP, adapter=name,
                            page=page, evicted=evicted)
        self.alloc.incref(page)                    # the request's ref
        self._set_gauge()
        return page

    def release(self, name: str) -> None:
        """Drop one request reference.  A quarantined adapter whose last
        request just drained has its residency ref released too — the
        page leaves the pool impounded (the KV trust hook, deferred)."""
        page = self._page_of.get(name)
        if page is None:
            # Already evicted-on-quarantine; nothing to balance — the
            # impound path released both refs.
            return
        self.alloc.release(page)
        if name in self._quarantined and self.alloc.refcount(page) == 1:
            self._impound(name, page)
        self._set_gauge()

    def _impound(self, name: str, page: int) -> None:
        self._page_of.pop(name)
        self._adapter_of.pop(page)
        self._lru.pop(name, None)
        self.alloc.release(page, quarantine=True)
        self._impounded[name] = page

    def quarantine(self, name: str) -> None:
        """Fleet-wide trust verdict against the ADAPTER: refuse every
        future resolve and impound its page — immediately when no
        request is in flight, else when the last one drains."""
        self._quarantined.add(name)
        page = self._page_of.get(name)
        if page is not None and self.alloc.refcount(page) == 1:
            self._impound(name, page)
        self._set_gauge()

    def unquarantine(self, name: str) -> None:
        """Operator action: lift the verdict.  The page (if impounded)
        returns to the free list; the adapter re-uploads on next use."""
        self._quarantined.discard(name)
        page = self._impounded.pop(name, None)
        if page is not None:
            self.alloc.unquarantine(page)
        self._set_gauge()

    # -- introspection -----------------------------------------------------

    @property
    def resident(self) -> Dict[str, int]:
        return dict(self._page_of)

    @property
    def quarantined(self) -> Set[str]:
        return set(self._quarantined)

    @property
    def pages_in_use(self) -> int:
        """Resident pages (incl. impounded) — what the
        ``tddl_serve_adapter_pages_in_use`` gauge exports."""
        return self.alloc.in_use + len(self._impounded)

    def is_quarantined(self, name: str) -> bool:
        return name in self._quarantined

    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def _set_gauge(self) -> None:
        if self._pages_gauge is not None:
            self._pages_gauge.set(float(self.pages_in_use))

    def device_args(self) -> Tuple[Any, Any, Optional[Any], Optional[Any]]:
        """The traced pool-array arguments every paged serve dispatch
        threads: (a, b, a_scale, b_scale) — scales None on the model-
        dtype tier (structural pytree absence, the KVCache pattern)."""
        return self.a, self.b, self.a_scale, self.b_scale

    def metrics(self) -> Dict[str, Any]:
        return {
            "pages": self.pages,
            "pages_in_use": self.pages_in_use,
            "resident": len(self._page_of),
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": self.hit_rate(),
            "evictions": self.evictions,
            "uploads": self.uploads,
            "quarantined": sorted(self._quarantined),
        }
