"""Live KV block-table migration: move an in-flight request between
replicas as a BLOCK COPY, not a recompute.

Every capacity-loss path in the fleet (trust drain, scale-in, heartbeat
fail-over, preemption, disaggregated prefill→decode hand-off) used to
end the same way: cancel on the source and replay the whole prompt —
and every already-accepted token — on a fresh replica.  This module
turns that into a two-phase hand-off of the request's PHYSICAL state:

1. **export** — the source engine snapshots the decode-phase request
   (block table, int8 scales ride in the same pool, emitted stream,
   trust signals, the WHOLE sampling key stream, timing).  Read-only;
   the source keeps serving.  Mid-prefill requests refuse (their state
   is a half-written table — replay is the honest path for those).
2. **claim** — the destination reserves a slot + fresh blocks + the
   adapter page through its NORMAL allocator paths (prefix-evict
   retry, adapter acquire, full unwind on any shortage).  A refusal
   here returns ``None`` and the source is left byte-identical —
   admission control is never bypassed by arriving as a migration.
3. **copy** — one jitted gather/scatter per pool leaf moves the
   KV blocks (and their scales — the int8 tier's values and scales
   page identically) from the source pool into the claimed blocks.
   Id vectors are padded to the fixed blocks-per-sequence width with
   ``TRASH_BLOCK`` so the program compiles ONCE per pool geometry; the
   reserved trash block absorbs the pad reads/writes by construction.
4. **commit** — the destination registers the continuation under a
   fresh local id (rng position travels because the key-stream index
   IS ``len(emitted)``), the caller's ``on_commit`` hook runs (the
   fleet re-points its attempt table here), and only THEN does the
   source release — ``cancel(status="migrated")``, which impounds the
   source blocks instead of freeing them when the source is being
   quarantined (``quarantine_src=True``): a suspect replica's bytes
   never silently re-enter its pool even as it loses the request.

Streams are bit-identical to an unmigrated ``generate()`` because
nothing numeric is recomputed: the destination decodes from the copied
blocks with the same keys at the same positions, and both replicas run
the same compile-once programs.

The capability gate (:func:`can_migrate`) is deliberately structural —
paged scheduler on both ends, identical pool geometry/dtype, the
export/adopt surface present — so heterogeneous fleets (and the
unit-test fake engines) fall back to the pre-existing
cancel-and-recompute path instead of failing.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from trustworthy_dl_tpu.serve.kv_slots import TRASH_BLOCK, PagedKV

# Module-level program cache (the scheduler's ``_PROGRAMS`` idiom): one
# jitted copy program shared by every engine pair in the process, keyed
# by jax's own (shape, dtype) cache — fixed-width id vectors mean two
# compiles per pool geometry (values leaf + scales leaf), ever.
_PROGRAMS: Dict[str, Any] = {}


def _programs() -> Dict[str, Any]:
    if not _PROGRAMS:
        def _copy_blocks(dst_pool: jax.Array, src_pool: jax.Array,
                         dst_ids: jax.Array, src_ids: jax.Array
                         ) -> jax.Array:
            # Gather the source rows along the block axis (axis 1 of
            # every pool leaf: values [L, NB, BLOCK, H·Dh] and scales
            # [L, NB, BLOCK, H] alike — the copy is blind to what follows
            # it) and scatter them into the destination pool.  Pad entries map trash →
            # trash; duplicate trash writes are harmless (the reserved
            # block's content is garbage by contract).  No donation:
            # the source pool stays live under the source scheduler.
            return dst_pool.at[:, dst_ids].set(src_pool[:, src_ids])

        _PROGRAMS["copy"] = jax.jit(_copy_blocks)
    return _PROGRAMS


def can_migrate(src_engine: Any, dst_engine: Any) -> bool:
    """True when a live block-copy between the two engines is possible.

    Structural, not declared: both ends expose the export/adopt surface,
    both schedulers are paged, and the pools share geometry and dtype
    (a copy between mismatched pools would be a silent corruption, and
    between int8 and f32 tiers a silent dequant).  Anything that fails
    the gate — fakes, heterogeneous fleets — keeps the old
    cancel-and-recompute behaviour.
    """
    if src_engine is dst_engine:
        return False
    if not (hasattr(src_engine, "export_request")
            and hasattr(dst_engine, "adopt_request")):
        return False
    ss = getattr(src_engine, "scheduler", None)
    ds = getattr(dst_engine, "scheduler", None)
    if getattr(ss, "export_migration", None) is None:
        return False
    if getattr(ds, "claim_migration", None) is None:
        return False
    skv = getattr(ss, "kv", None)
    dkv = getattr(ds, "kv", None)
    if not (isinstance(skv, PagedKV) and isinstance(dkv, PagedKV)):
        return False
    if skv.k.shape != dkv.k.shape or skv.k.dtype != dkv.k.dtype:
        return False
    if skv.quantized != dkv.quantized:
        return False
    if getattr(ss, "nbps", None) != getattr(ds, "nbps", None):
        return False
    # A description with recurrent state beside the blocks has no state
    # snapshot yet (its ``export_migration`` raises): replay is the path.
    if getattr(ss, "recurrent", False) or getattr(ds, "recurrent", False):
        return False
    return True


def _copy_pools(src_sched: Any, dst_sched: Any,
                src_ids: list, dst_ids: list) -> None:
    """Move the named blocks (values AND scales) src pool → dst pool."""
    width = int(dst_sched.nbps)
    s = np.full(width, TRASH_BLOCK, np.int32)
    d = np.full(width, TRASH_BLOCK, np.int32)
    s[:len(src_ids)] = src_ids
    d[:len(dst_ids)] = dst_ids
    si, di = jnp.asarray(s), jnp.asarray(d)
    copy = _programs()["copy"]
    skv, dkv = src_sched.kv, dst_sched.kv
    new_k = copy(dkv.k, skv.k, di, si)
    new_v = copy(dkv.v, skv.v, di, si)
    new_ks = new_vs = None
    if dkv.k_scale is not None:
        new_ks = copy(dkv.k_scale, skv.k_scale, di, si)
        new_vs = copy(dkv.v_scale, skv.v_scale, di, si)
    dst_sched.kv = PagedKV(k=new_k, v=new_v, k_scale=new_ks,
                           v_scale=new_vs)


def migrate_request(src_engine: Any, dst_engine: Any, local_id: int, *,
                    quarantine_src: bool = False,
                    on_token: Optional[Callable[[int, int], None]] = None,
                    src_journal: Optional[str] = None,
                    on_commit: Optional[Callable[[int], None]] = None,
                    on_refuse: Optional[Callable[[str], None]] = None,
                    ) -> Optional[Dict[str, Any]]:
    """Two-phase live migration of one in-flight request.

    Returns ``{"local_id": <new id on the destination>, "blocks":
    <KV blocks copied>}`` on success, or ``None``
    with the source byte-untouched when the request is not migratable
    (unknown id, still prefilling, no tokens yet) or the destination
    refuses the claim (slot/block/adapter shortage).  On success the
    source side is released via ``cancel(status="migrated", quarantine=
    quarantine_src)`` — AFTER the destination committed and after the
    caller's ``on_commit(new_local)`` ran, so a fleet can re-point its
    routing before the source attempt closes and no token is ever
    streamed by zero or two replicas.

    ``src_journal`` (the fleet's ``replica:gen`` allocator-journal key)
    is threaded into the destination's attribution record as
    ``migrated_from`` so ``verify_attribution`` can reconcile the
    source-side block provenance without flagging the release.

    ``on_refuse`` is invoked with the refusal class
    (``"src_not_migratable"`` / ``"claim_refused"``) just before each
    ``None`` return — the fleet's forensic incident records capture
    per-destination refusals through it.
    """
    snap = src_engine.export_request(local_id)
    if snap is None:
        if on_refuse is not None:
            on_refuse("src_not_migratable")
        return None
    task = snap["task"]
    src_ids = list(snap["block_ids"])
    claim = dst_engine.scheduler.claim_migration(len(src_ids),
                                                task.adapter)
    if claim is None:
        if on_refuse is not None:
            on_refuse("claim_refused")
        return None
    _copy_pools(src_engine.scheduler, dst_engine.scheduler,
                src_ids, claim["block_ids"])
    migrated_from = {"block_ids": src_ids,
                     "replica": snap.get("replica")}
    if src_journal is not None:
        migrated_from["journal"] = src_journal
    new_local = dst_engine.adopt_request(snap, claim, on_token=on_token,
                                         migrated_from=migrated_from)
    if on_commit is not None:
        on_commit(new_local)
    src_engine.cancel(local_id, status="migrated",
                      quarantine=quarantine_src)
    return {"local_id": new_local, "blocks": len(src_ids)}
