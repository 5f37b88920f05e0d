"""SpillStore: compressed host-memory tier for cold sequences.

The serving analog of the paper's core move — ship COMPRESSED lines
across the slow link and expand only at the consumer — mapped onto
HBM->host KV tiering (the CXL story in PAPERS.md).  Evicting a cold
sequence does NOT decompress its KV: the store re-encodes the slot's
logical pages under the SPILL tier's own packing (off / pair / quad, an
independent `AutoTuner` axis — quad usually wins on the link because raw
groups cross with no strip), keeping

  * one packed slot per fitting group, plus its base row — the fit
    decision sees only the COMPLETE live pages (dead lanes and the
    partially-filled last page ride as base replicas, the partial page
    crossing raw in `tail`: its zero rows would otherwise poison the
    whole group),
  * the raw lanes of unfitting groups (no in-band metadata),
  * the slot's hot-tier bookkeeping: §VI counter, LLP predictor row, the
    uncounted-fitness mask, the gate its layout was settled under and
    the hot packing geometry it was evicted from.

Restore is the inverse: decode the payload back to logical pages
(`compression.pagepack` codecs are exact whenever the fit bit was set),
write them into a free slot with the saved gate state, mark the slot
dirty, and repack under the payload's recorded gate.  Because the hot
cache's incremental layout is pinned bit-identical to a from-scratch
rebuild (tests/test_kv_cache.py), the resurrected physical state — and
therefore every subsequent `attend` — is bit-identical to the
never-spilled execution.  A sequence waking into a half-migrated cache
simply joins the derived pending set: its layout was settled under
`gate` at evict, and if the pool's target moved while it was cold the
budgeted quanta converge it like any other slot (if the hot cache
switched PACKING while it was cold, the geometry-indexed bookkeeping is
reset and the slot lays directly under the current target).

Async pipeline (DESIGN.md §12): with `async_spill=True` the evict is
split in three — `_capture` snapshots the settled slot on the main
thread (the slot frees immediately), `_encode` re-encodes the payload on
a single background worker (pure numpy — no JAX contention with the
decode stream), and `_commit` books the store insert at *collection*,
back on the main thread.  Wakes overlap the other way: `prefetch`
enqueues the payload decode behind any in-flight encodes (one FIFO
worker makes the chaining deadlock-free), so `restore` finds the pages
already expanded.  Every evict and every restore still books exactly ONE
ledger `spill` event (`bandwidth.adapters.kv_spill_event`) with
compressed-byte duals — booked at completion, on the main thread, in
submission order, so the ledger stream is deterministic and
exactly-once-per-crossing no matter how the worker interleaves.
"""

from __future__ import annotations

from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from ..bandwidth import Ledger
from ..bandwidth.adapters import kv_spill_event
from ..compression import pagepack
from .slots import SlotKVCache

SPILL_LANES = {"off": 1, "pair": 2, "quad": 4}


@dataclass
class SpilledSeq:
    """One evicted sequence's payload, still compressed."""

    seq_id: int
    tokens: int
    packing: str                 # spill-tier packing the payload uses
    fit: np.ndarray              # (Gs,) bool — which spill groups packed
    slots: np.ndarray            # (Gs, page, Hkv, D2) packed slot / lane 0
    bases: np.ndarray            # (n_fit, Hkv, D2) base rows of fit groups
    overflow: list               # per raw group: (live-1, page, Hkv, D2)
                                 # raw lanes, dead tail lanes trimmed
    tail: "np.ndarray | None"    # the partially-filled last page, raw —
                                 # only when its group packed without it
    counter: int                 # hot-tier §VI counter at evict
    predictor: np.ndarray        # (Gh,) hot-tier LLP predictor row
    uncounted: np.ndarray        # (Gh,) hot-tier uncounted-fitness mask
    raw_bytes: int               # decompressed-page cost of this evict
    stored_bytes: int            # payload bytes that actually moved
    gate: bool = True            # gate the hot layout was settled under
    hot_packing: str = "pair"    # hot-tier geometry at evict (predictor/
                                 # uncounted are indexed in it)

    @property
    def n_groups(self) -> int:
        return int(self.fit.size)


def _payload_bytes(*arrays) -> int:
    return int(sum(a.nbytes for a in arrays))


class SpillStore:
    """Host-memory spill tier keyed by sequence id.

    `capacity_pages` bounds the tier (None = unbounded); `packing` is the
    spill-tier layout — independent of the hot cache's, chosen by
    `AutoTuner.choose_kv_packing(tier="spill")` under the spill-link byte
    model.  `async_spill=True` moves the payload re-encode off the decode
    path (see the module docstring); the observable store state is
    identical either way — `__contains__`, `__len__` and the capacity
    check all count in-flight evictions, and every read that needs a
    payload collects it first."""

    def __init__(self, *, packing: str = "quad",
                 capacity_pages: int | None = None,
                 ledger: Ledger | None = None,
                 async_spill: bool = False):
        assert packing in SPILL_LANES, packing
        self.packing = packing
        self.lanes = SPILL_LANES[packing]
        self.capacity_pages = capacity_pages
        self.ledger = ledger if ledger is not None else Ledger("spill")
        self.async_spill = async_spill
        self._store: dict[int, SpilledSeq] = {}
        self._inflight: dict[int, Future] = {}   # seq_id -> encode future
        self._inflight_pages: dict[int, int] = {}
        self._prefetched: dict[int, Future] = {}  # seq_id -> decode future
        self._pool: ThreadPoolExecutor | None = None
        self.spills = 0
        self.restores = 0
        self.raw_bytes = 0        # cumulative decompressed-page duals
        self.stored_bytes = 0     # cumulative payload bytes moved out

    def _worker(self) -> ThreadPoolExecutor:
        # ONE worker, FIFO: jobs complete in submission order, so a
        # prefetch enqueued after its sequence's encode can chain on the
        # future without deadlock, and collection order == evict order
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="kv-spill")
        return self._pool

    def __contains__(self, seq_id) -> bool:
        return seq_id in self._store or seq_id in self._inflight

    def __len__(self) -> int:
        return len(self._store) + len(self._inflight)

    # ------------------------------------------------------------- evict
    def evict(self, cache: SlotKVCache, slot: int, seq_id: int) -> None:
        """Move one slot out of the hot cache, still compressed; the slot
        is reset for reuse before this returns.  Sync mode encodes and
        books inline; async mode snapshots the settled slot, frees it,
        and ships the re-encode to the background worker — the ledger
        `spill` event (exactly one per crossing) is booked when the
        payload is collected."""
        assert seq_id not in self, f"seq {seq_id} already spilled"
        cap = self._capture(cache, slot, seq_id)
        if not self.async_spill:
            self._commit(self._encode(cap))
            return
        self._inflight_pages[seq_id] = cap["n_pages"]
        self._inflight[seq_id] = self._worker().submit(self._encode, cap)

    def spill_in(self, cache: SlotKVCache, seq_id: int, k, v) -> None:
        """Encode a prompt STRAIGHT into the spill tier — no hot lane.

        The spill-direct half of `ServeLoop.admit`: an admit-beyond-pool
        whose sequence would itself be the coldest encodes its prompt
        under the spill packing right away (one evict-direction crossing,
        exactly one ledger `spill` event) instead of thrashing a hotter
        resident out and straight back.  The payload records the hot
        bookkeeping a fresh hot-lane prefill would start from — counter
        at the policy init, every group's fitness uncounted, the default
        target gate — so a later `restore` + repack resurrects the slot's
        physical state, attend output and §VI counter exactly as if the
        sequence had been prefilled into a hot lane (only the LLP
        predictor row starts unseeded; it re-seeds at the next
        observation).  k/v: (T, n_kv, d), the prompt."""
        assert seq_id not in self, f"seq {seq_id} already spilled"
        kk = np.asarray(jnp.asarray(k, jnp.bfloat16).view(jnp.int16))
        vv = np.asarray(jnp.asarray(v, jnp.bfloat16).view(jnp.int16))
        assert kk.ndim == 3, "spill_in takes one sequence (T, n_kv, d)"
        kv = np.concatenate([kk, vv], axis=-1)
        tokens = kv.shape[0]
        assert tokens > 0, "spill_in needs a non-empty prompt"
        page = cache.page
        n_pages = -(-tokens // page)
        gs = -(-n_pages // self.lanes)
        if (self.capacity_pages is not None
                and self._pages_stored() + n_pages > self.capacity_pages):
            raise RuntimeError(
                f"spill store full ({self._pages_stored()}+{n_pages} pages "
                f"> capacity {self.capacity_pages})")
        pages = np.zeros((gs * self.lanes, page, cache.n_kv, cache.d2),
                         np.int16)
        pages.reshape(-1, cache.n_kv, cache.d2)[:tokens] = kv
        gh = -(-n_pages // cache.group_lanes)
        cap = {
            "seq_id": seq_id, "tokens": tokens, "n_pages": n_pages,
            "gs": gs, "pages": pages,
            "counter": cache._counter_init,
            "predictor": np.zeros(gh, bool),
            "uncounted": np.ones(gh, bool),
            "gate": cache.default_slot_gate(),
            "hot_packing": cache.packing,
            "raw_bytes": n_pages * cache.slot_bytes,
        }
        if not self.async_spill:
            self._commit(self._encode(cap))
            return
        self._inflight_pages[seq_id] = n_pages
        self._inflight[seq_id] = self._worker().submit(self._encode, cap)

    def _capture(self, cache: SlotKVCache, slot: int, seq_id: int) -> dict:
        """Main-thread half of an evict: settle the slot's layout (drain
        its pending migration and appends under the frozen target),
        snapshot everything the encode needs as host arrays, and reset the
        slot."""
        cache.drain_migration(slot)
        tokens = int(cache.tokens_b[slot])
        assert tokens > 0, "evicting an empty slot"
        page = cache.page
        n_pages = -(-tokens // page)
        gs = -(-n_pages // self.lanes)
        if (self.capacity_pages is not None
                and self._pages_stored() + n_pages > self.capacity_pages):
            raise RuntimeError(
                f"spill store full ({self._pages_stored()}+{n_pages} pages "
                f"> capacity {self.capacity_pages})")
        avail = min(gs * self.lanes, cache.max_pages)
        pages = np.zeros((gs * self.lanes, page, cache.n_kv, cache.d2),
                         np.int16)
        pages[:avail] = np.asarray(cache.pages_view()[slot, :avail])
        gh = cache.slot_groups(slot)
        cap = {
            "seq_id": seq_id, "tokens": tokens, "n_pages": n_pages,
            "gs": gs, "pages": pages,
            "counter": int(np.asarray(cache.state["counter"][slot])),
            "predictor": np.asarray(
                cache.state["predictor"][slot, :gh]).copy(),
            "uncounted": cache._uncounted_b[slot, :gh].copy(),
            "gate": bool(cache._gate_b[slot]),
            "hot_packing": cache.packing,
            "raw_bytes": n_pages * cache.slot_bytes,
        }
        cache.reset_slot(slot)
        return cap

    def _encode(self, cap: dict) -> SpilledSeq:
        """Pure re-encode of a captured slot under the spill packing —
        numpy only, safe on the background worker."""
        tokens, page = cap["tokens"], cap["pages"].shape[1]
        gs, pages = cap["gs"], cap["pages"]
        hkv, d2 = pages.shape[-2:]
        fit = np.zeros(gs, bool)
        slots = np.empty((gs, page, hkv, d2), np.int16)
        bases, overflow, tail = [], [], None
        n_full, rem = divmod(tokens, page)
        if self.packing == "off":
            slots[:] = pages                      # lanes == 1: page == group
        else:
            pack = (pagepack.pack_pair if self.packing == "pair"
                    else pagepack.pack_quad)
            for g in range(gs):
                orig = pages[g * self.lanes:(g + 1) * self.lanes]
                full = min(max(n_full - g * self.lanes, 0), self.lanes)
                partial = bool(rem) and full < self.lanes \
                    and g * self.lanes + full == n_full
                live = full + partial
                # the fit decision sees only the COMPLETE live pages:
                # dead lanes and the partially-filled last page ride as
                # base-page replicas (delta 0) — their zero rows against
                # a non-zero base would force the whole group raw.  The
                # partial page crosses raw in `tail`; restore re-zeroes
                # the dead lanes.  Fewer than 2 complete pages never
                # packs: slot+base would cost more than trimmed raw.
                grp = orig.copy()
                grp[full:] = grp[0]
                ok, packed, base = (pack(*grp) if full >= 2
                                    else (False, None, None))
                if bool(ok):
                    fit[g] = True
                    slots[g] = packed
                    bases.append(base)
                    if partial:
                        tail = orig[full].copy()
                else:
                    # raw group: lane 0 in the slot row, LIVE extra lanes
                    # in overflow — dead lanes never cross the link
                    slots[g] = orig[0]
                    overflow.append(orig[1:live].copy())
        bases = (np.stack(bases) if bases
                 else np.empty((0, hkv, d2), np.int16))
        return SpilledSeq(
            seq_id=cap["seq_id"], tokens=tokens, packing=self.packing,
            fit=fit, slots=slots, bases=bases, overflow=overflow, tail=tail,
            counter=cap["counter"], predictor=cap["predictor"],
            uncounted=cap["uncounted"], raw_bytes=cap["raw_bytes"],
            stored_bytes=_payload_bytes(
                slots, bases, fit, *overflow,
                *(() if tail is None else (tail,))),
            gate=cap["gate"], hot_packing=cap["hot_packing"],
        )

    def _commit(self, payload: SpilledSeq) -> None:
        """Book one completed evict: store insert, byte totals, and the
        single ledger `spill` event.  Always runs on the main thread."""
        self._store[payload.seq_id] = payload
        self.spills += 1
        self.raw_bytes += payload.raw_bytes
        self.stored_bytes += payload.stored_bytes
        kv_spill_event(self.ledger, raw=payload.raw_bytes,
                       compressed=payload.stored_bytes, direction="evict")

    def _collect(self, seq_id) -> None:
        """Join one in-flight evict and commit it (main thread).  Commit
        BEFORE dropping the in-flight entry: a worker-side `_payload`
        lookup then always finds the sequence in one map or the other."""
        fut = self._inflight.get(seq_id)
        if fut is not None:
            self._commit(fut.result())
            del self._inflight[seq_id]
            self._inflight_pages.pop(seq_id, None)

    def flush(self) -> int:
        """Join every in-flight evict, committing in submission order —
        the sync point before anything reads the ledger's spill rows.
        Returns the number collected."""
        pending = list(self._inflight)
        for sid in pending:
            self._collect(sid)
        return len(pending)

    # ----------------------------------------------------------- prefetch
    def _payload(self, seq_id) -> SpilledSeq:
        # single FIFO worker: an encode submitted before this job ran has
        # already finished, so .result() cannot block the worker on itself.
        # `_collect` commits to the store before dropping the in-flight
        # entry, so one of these lookups always lands.
        p = self._store.get(seq_id)
        if p is not None:
            return p
        fut = self._inflight.get(seq_id)
        if fut is not None:
            return fut.result()
        return self._store[seq_id]

    def _decode_pages(self, p: SpilledSeq, page: int) -> np.ndarray:
        """Payload -> logical pages (n_groups*lanes, page, Hkv, D2) — the
        pure half of a restore, runnable on the worker."""
        hkv, d2 = p.slots.shape[-2:]
        # decode under the packing the payload was EVICTED with, not the
        # store's current setting — per-tier retuning may change the
        # latter while sequences are cold
        lanes = SPILL_LANES[p.packing]
        pages = np.empty((p.n_groups * lanes, page, hkv, d2), np.int16)
        fi = ri = 0
        if p.packing == "off":
            pages[:] = p.slots
        else:
            unpack = (pagepack.unpack_pair if p.packing == "pair"
                      else pagepack.unpack_quad)
            for g in range(p.n_groups):
                dst = pages[g * lanes:(g + 1) * lanes]
                if p.fit[g]:
                    dst[:] = np.stack(unpack(p.slots[g], p.bases[fi]))
                    fi += 1
                else:
                    ov = p.overflow[ri]
                    dst[0] = p.slots[g]
                    dst[1:1 + len(ov)] = ov
                    ri += 1
        if p.tail is not None:             # partial page shipped raw beside
            pages[p.tokens // page] = p.tail        # its packed group
        pages[-(-p.tokens // page):] = 0   # dead lanes back to zeros (the
        # packed path decoded them as base replicas, the raw path trimmed)
        return pages

    def prefetch(self, seq_id, page: int) -> bool:
        """Start decoding a spilled payload on the background worker so a
        later `restore` finds the pages already expanded.  Chained behind
        any in-flight encode of the same sequence by FIFO order.  Returns
        False for unknown / already-prefetched sequences."""
        if seq_id not in self or seq_id in self._prefetched:
            return False
        if not self.async_spill:
            return False
        fut = self._worker().submit(
            lambda: self._decode_pages(self._payload(seq_id), page))
        self._prefetched[seq_id] = fut
        return True

    # ------------------------------------------------------------ restore
    def restore(self, cache: SlotKVCache, slot: int, seq_id: int) -> None:
        """Wake one sequence into a free slot: decode the payload back to
        logical pages (or consume the prefetched expansion), reinstall the
        gate state, and repack under the payload's recorded gate — the hot
        layout resurrects bit-identical to the never-spilled state, and
        joins the migration pending set if the pool's target gate moved
        while it was cold.  Books one ledger `spill` event."""
        self._collect(seq_id)              # join an in-flight encode first
        assert int(cache.tokens_b[slot]) == 0, "restore needs a free slot"
        page = cache.page
        # resolve the prefetch BEFORE popping the payload: the queued
        # decode job reads the store entry
        pre = self._prefetched.pop(seq_id, None)
        pages = pre.result() if pre is not None else None
        p = self._store.pop(seq_id)
        if pages is None:
            pages = self._decode_pages(p, page)
        hkv, d2 = p.slots.shape[-2:]
        n_rows = min(pages.shape[0], cache.max_pages) * page
        flat = pages.reshape(-1, hkv, d2)[:n_rows]
        st = cache.state
        st["pages"] = st["pages"].at[slot, :n_rows].set(jnp.asarray(flat))
        st["counter"] = st["counter"].at[slot].set(p.counter)
        cache.tokens_b[slot] = p.tokens
        cache.tokens = int(cache.tokens_b.max())
        gh = cache.slot_groups(slot)
        gate_vec = cache._gate_b.copy()
        if p.hot_packing == cache.packing:
            # same geometry: the payload's hot bookkeeping slots back in,
            # and the layout resurrects under the gate it was settled with
            # (a target that moved while cold leaves it derived-pending)
            assert gh == len(p.predictor), (gh, len(p.predictor))
            st["predictor"] = st["predictor"].at[slot, :gh].set(
                jnp.asarray(p.predictor))
            cache._uncounted_b[slot, :gh] = p.uncounted
            gate_vec[slot] = p.gate
        else:
            # the hot cache switched packing while this sequence was cold:
            # predictor/uncounted are indexed in the OLD geometry — reset
            # (history is not re-counted) and lay directly under the
            # current target gate
            cache._uncounted_b[slot, :gh] = False
        cache._dirty_b[slot, :gh] = True
        self.restores += 1
        kv_spill_event(self.ledger, raw=p.raw_bytes,
                       compressed=p.stored_bytes, direction="restore")
        cache.repack(gate=gate_vec)   # materialize the resurrected layout

    def drop(self, seq_id) -> None:
        """Discard a spilled sequence (retired while cold).  An in-flight
        evict is collected first — the crossing already happened, its
        bytes moved and its ledger event must still book exactly once."""
        self._collect(seq_id)
        pre = self._prefetched.pop(seq_id, None)
        if pre is not None:
            pre.result()   # let a queued decode finish reading the entry
        self._store.pop(seq_id)

    # ------------------------------------------------------------ queries
    def _pages_stored(self) -> int:
        return (sum(p.n_groups * SPILL_LANES[p.packing]
                    for p in self._store.values())
                + sum(self._inflight_pages.values()))

    def saving(self) -> float:
        """1 - stored/raw over every spill so far (the link-bytes win)."""
        return 1.0 - self.stored_bytes / max(self.raw_bytes, 1)

    def summary(self) -> dict:
        self.flush()
        return {"packing": self.packing, "held": len(self._store),
                "spills": self.spills, "restores": self.restores,
                "raw_bytes": self.raw_bytes,
                "stored_bytes": self.stored_bytes,
                "saving": round(self.saving(), 4)}


__all__ = ["SpillStore", "SpilledSeq", "SPILL_LANES"]
