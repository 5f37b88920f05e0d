"""SlotKVCache: the batched CRAM-KV cache with per-slot sequence lifetimes.

`kv.CRAMKVCache` assumes one uniform token count across its batch axis —
right for offline benches, wrong for serving, where sequences join and
retire mid-flight.  This subclass turns each batch lane into an
independently-progressing *slot*:

  * per-slot token counts (`tokens_b`) drive a per-slot `valid_per_page`
    mask, so the one batched attend/accounting dispatch stays fused while
    every lane sees only its own live pages;
  * appends come in three shapes — uniform (`append`, prefill of every
    slot together), per-slot (`append_slot`, admission prefill), and
    vectorized per-step (`append_active`: one fused scatter appends one
    token to an arbitrary subset of slots — no per-slot dispatch in the
    decode loop);
  * the dirty/uncounted masks become per-slot (B, n_groups): `repack`
    re-lays the UNION of dirty columns in one window dispatch (packing is
    a deterministic function of (pages, gate, markers), so re-laying a
    clean slot's column is idempotent), while §VI fitness is counted
    per slot — a group feeds slot b's counter only once b's own tokens
    complete it, exactly once, as in the base cache;
  * `reset_slot` returns a lane to pristine state for reuse by the next
    admitted sequence (continuous batching never grows the batch axis),
    and `slot_reference_state` is the per-slot rebuild oracle — the base
    `reference_rebuild` judges a uniform prefix, a slot's parity is
    judged on ITS OWN active prefix.

Gate semantics (DESIGN.md §12): the gate each repack lays under is the
frozen per-slot TARGET `_gate_b`, refreshed from the §VI counter only at
observation boundaries (`refresh_gate` — called by the plain `repack`
and by the serve loop's report points) or forced via
`set_gate_override`.  `_applied_b` records the gate every group's
layout was actually laid under; `serving.migrate` derives the pending
set from the two and converges the live layout with bounded per-step
quanta instead of stop-the-world re-dirtying.

`megastep` is the fused serve step: append scatter, window repack
(appends + migration quantum columns), §VI counter update, repack/read
byte booking and the LLP predictor observation all run in ONE donated
jitted dispatch, traced once per pow2-bucketed shape — the decode loop
makes zero host syncs per step (`jaxpr_audit` pins the entry).

The spill tier (`serving.spill.SpillStore`) moves slots out of and back
into this cache; bit-exact resurrection rides on the pinned
incremental==rebuild invariant (tests/test_kv_cache.py): restore writes
the logical pages + gate state and marks the slot dirty, and the next
repack reproduces the never-spilled physical layout bit-for-bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..bandwidth import Ledger
from ..bandwidth.adapters import kv_read_device, kv_repack_device
from ..compression.framing import DEFAULT_MARKER_KEY
from ..compression.gate import COUNTER_INIT, COUNTER_MAX, ENABLE_THRESHOLD
from ..compression.predictor import observe_layout
from ..kernels import ops as kops
from ..kernels.prefill_pack import prefill_pack
from ..kernels.ref import MARKER_LANES
from ..kv.cache import CRAMKVCache, _scatter_window, kernel_cache_slice
from . import migrate as _migrate


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_slot(pages, kv, slot, start):
    """pages (B, Tmax, Hkv, D2) <- kv (1, T, Hkv, D2) at (slot, start)."""
    return jax.lax.dynamic_update_slice(pages, kv, (slot, start, 0, 0))


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_active(pages, kv, starts, active):
    """Per-slot scatter at per-slot offsets: pages (B, Tmax, Hkv, D2) <-
    kv (B, T, Hkv, D2) row b at token starts[b], where active[b]."""
    def one(p, s, t0, a):
        return jnp.where(a, jax.lax.dynamic_update_slice(p, s, (t0, 0, 0)), p)
    return jax.vmap(one)(pages, kv, starts, active)


@functools.partial(
    jax.jit, donate_argnums=(0,),
    static_argnames=("lanes", "slot_bytes", "strip_bytes", "use_pack",
                     "dyn", "interpret"))
def _megastep(state, mk_lanes, k, v, slot_idx, starts, active, idx,
              enabled, countable, valid, *, lanes, slot_bytes, strip_bytes,
              use_pack, dyn, interpret):
    """One fused serve decode step over the whole cache pytree (donated).

    Collapses the per-step dispatch sequence — append scatter, window
    gather + pack/raw re-lay (the window covers the step's dirty appends
    AND the migration quantum's pending columns), physical scatter, §VI
    counter update, repack + read byte booking, LLP hit/miss tally and
    predictor observation — into one jitted call.  Bit-identical to the
    unfused append_active -> repack -> account path on the same window.

    k/v: (S, T, Hkv, D) rows aligned with slot_idx; idx: (W,) union dirty
    group columns, pow2-padded by REPEATING a real column (idempotent re-
    lay; the pad's `countable` entries are False so §VI never recounts);
    valid: (B, lanes*N) live-token counts at the attend bucket.
    """
    st = dict(state)
    kv = jnp.concatenate([jnp.asarray(k, jnp.bfloat16).view(jnp.int16),
                          jnp.asarray(v, jnp.bfloat16).view(jnp.int16)],
                         axis=-1)
    b = st["pages"].shape[0]
    t = kv.shape[1]
    full = jnp.zeros((b, t) + kv.shape[2:], kv.dtype).at[slot_idx].set(kv)

    def one(p, s, t0, a):
        return jnp.where(a, jax.lax.dynamic_update_slice(p, s, (t0, 0, 0)), p)
    st["pages"] = jax.vmap(one)(st["pages"], full, starts, active)

    hkv, d2 = st["pages"].shape[-2:]
    page = st["slots"].shape[2]
    n_groups = st["packed_mask"].shape[1]
    groups = st["pages"].reshape(b, n_groups, lanes, page, hkv, d2)
    win = groups[:, idx]
    slots_w, over_w, strips_w, lay, fit = kops.layout_window(
        win, mk_lanes[idx], enabled, use_pack=use_pack,
        interpret=interpret)
    st["slots"] = st["slots"].at[:, idx].set(slots_w)
    st["slots_overflow"] = st["slots_overflow"].at[:, idx].set(over_w)
    st["strips"] = st["strips"].at[:, idx].set(strips_w)
    st["packed_mask"] = st["packed_mask"].at[:, idx].set(lay)
    traffic, lay_n = kv_repack_device(st["traffic"], lay, lanes=lanes,
                                      slot_bytes=slot_bytes,
                                      strip_bytes=strip_bytes)
    st["packed_n"] = st["packed_n"] + lay_n
    st["raw_n"] = st["raw_n"] + (lay.size - lay_n)
    if dyn:
        fit_n = (fit & countable).sum(1)
        unfit_n = ((~fit) & countable).sum(1)
        st["counter"] = jnp.clip(
            st["counter"] + (fit_n - unfit_n).astype(jnp.int32),
            0, COUNTER_MAX)
    n = valid.shape[1] // lanes
    kc = kernel_cache_slice(st, n)
    raw_seq, cram_seq = kops.hbm_bytes_moved_device(
        kc, valid, predictor=st["predictor"][:, :n], lanes=lanes)
    pm = st["packed_mask"][:, :n]
    pred = st["predictor"][:, :n]
    live = valid.reshape(b, n, lanes).sum(-1) > 0
    mis = pred != pm
    st["pred_hits"] = st["pred_hits"] + ((~mis) & live).sum(1).astype(
        jnp.int32)
    st["pred_misses"] = st["pred_misses"] + (mis & live).sum(1).astype(
        jnp.int32)
    st["traffic"] = kv_read_device(traffic, raw_seq, cram_seq)
    st["predictor"] = observe_layout(st["packed_mask"])
    return st, raw_seq, cram_seq


@functools.partial(
    jax.jit, donate_argnums=(0,),
    static_argnames=("lanes", "slot_bytes", "strip_bytes", "use_pack",
                     "dyn", "interpret"))
def _prefill(state, mk_lanes, k, v, slot, start, idx, enabled, countable,
             *, lanes, slot_bytes, strip_bytes, use_pack, dyn, interpret):
    """Fused chunked-prefill ingest for one slot (donated).

    Installs a whole prompt — scatter at the slot's position, bulk pack of
    every touched page group (`kernels.prefill_pack`: ONE vmapped
    pallas_call for the per-page codec try + marker framing + slot
    placement), physical scatter, repack byte booking, §VI counter update
    from the pack results and LLP predictor initialization — in ONE jitted
    dispatch.  No read-side accounting: nothing was attended yet, so
    unlike `_megastep` there is no read event, no hit/miss tally, and no
    full-predictor observation (only the prefilled slot's row is seeded).

    k/v: (T, Hkv, D) the prompt rows (T pow2-padded by the caller with
    zeros, which land on never-written all-zero page rows); idx: (W,)
    union dirty group columns, pow2-padded by repeating a real column
    (idempotent re-lay, pad `countable` False).  Bit-identical to
    append_slot -> repack(gate) on the same window.
    """
    st = dict(state)
    kv = jnp.concatenate([jnp.asarray(k, jnp.bfloat16).view(jnp.int16),
                          jnp.asarray(v, jnp.bfloat16).view(jnp.int16)],
                         axis=-1)[None]                 # (1, T, Hkv, D2)
    st["pages"] = jax.lax.dynamic_update_slice(
        st["pages"], kv, (slot, start, 0, 0))
    page = st["slots"].shape[2]
    slots_w, over_w, strips_w, lay, fit = prefill_pack(
        st["pages"], idx, mk_lanes, enabled, lanes=lanes, page=page,
        use_pack=use_pack, interpret=interpret)
    st["slots"] = st["slots"].at[:, idx].set(slots_w)
    st["slots_overflow"] = st["slots_overflow"].at[:, idx].set(over_w)
    st["strips"] = st["strips"].at[:, idx].set(strips_w)
    st["packed_mask"] = st["packed_mask"].at[:, idx].set(lay)
    traffic, lay_n = kv_repack_device(st["traffic"], lay, lanes=lanes,
                                      slot_bytes=slot_bytes,
                                      strip_bytes=strip_bytes)
    st["traffic"] = traffic
    st["packed_n"] = st["packed_n"] + lay_n
    st["raw_n"] = st["raw_n"] + (lay.size - lay_n)
    if dyn:
        fit_n = (fit & countable).sum(1)
        unfit_n = ((~fit) & countable).sum(1)
        st["counter"] = jnp.clip(
            st["counter"] + (fit_n - unfit_n).astype(jnp.int32),
            0, COUNTER_MAX)
    # the prompt's own pack results seed the slot's LLP prediction; the
    # other slots' rows are untouched (no observation happened for them)
    st["predictor"] = st["predictor"].at[slot].set(st["packed_mask"][slot])
    return st


class SlotKVCache(CRAMKVCache):
    """CRAMKVCache whose batch lanes are independent sequence slots."""

    def __init__(self, max_pages: int, page: int, n_kv: int, head_dim: int,
                 *, batch: int = 1, policy: str = "dynamic",
                 packing: str = "pair", key: int = DEFAULT_MARKER_KEY,
                 counter_init: int = COUNTER_INIT,
                 interpret: bool | None = None,
                 ledger: Ledger | None = None):
        # a serve cache may live-migrate between pair and quad layouts:
        # round capacity to the 4-page lcm so both geometries tile it
        max_pages = -(-max_pages // 4) * 4
        super().__init__(max_pages, page, n_kv, head_dim, batch=batch,
                         policy=policy, packing=packing, key=key,
                         counter_init=counter_init, interpret=interpret,
                         ledger=ledger)
        self._counter_init = int(counter_init)
        # per-slot sequence positions; base `self.tokens` is kept at the
        # max so the shared pow2 attend bucket covers every live slot
        self.tokens_b = np.zeros(batch, np.int64)
        # per-slot dirty / §VI-uncounted group masks (the base cache's
        # shared 1-D masks assume uniform appends and are superseded here)
        self._dirty_b = np.zeros((batch, self.n_groups), bool)
        self._uncounted_b = np.zeros((batch, self.n_groups), bool)
        # migration state (serving.migrate): frozen per-slot target gate,
        # per-(slot, group) applied gate — pending is DERIVED, not stored
        self._gate_override: bool | None = None
        self._gate_b = self._policy_gate()
        self._applied_b = np.broadcast_to(
            self._gate_b[:, None], (batch, self.n_groups)).copy()

    # ------------------------------------------------------- slot geometry
    def slot_pages(self, slot: int) -> int:
        return int(-(-self.tokens_b[slot] // self.page))

    def slot_groups(self, slot: int) -> int:
        """Active page groups of one slot (its own prefix, not the max)."""
        return -(-self.slot_pages(slot) // self.group_lanes)

    def valid_per_page(self) -> np.ndarray:
        v = np.clip(self.tokens_b[:, None]
                    - np.arange(self.max_pages)[None, :] * self.page,
                    0, self.page)
        return v.astype(np.int32)

    # ------------------------------------------------------------ the gate
    def _policy_gate(self) -> np.ndarray:
        """(B,) bool target gate under the current policy / override.
        The only place the §VI counter crosses to the host."""
        if self._gate_override is not None:
            return np.full(self.batch, self._gate_override, bool)
        if self.policy == "off":
            return np.zeros(self.batch, bool)
        if self.policy == "static":
            return np.ones(self.batch, bool)
        return np.asarray(self.state["counter"]) >= ENABLE_THRESHOLD

    def refresh_gate(self) -> np.ndarray:
        """Re-sample the per-slot target gate (one observation boundary).
        Between refreshes the target is FROZEN: the fused decode step
        never reads the counter back — §VI flips take effect at window
        granularity and converge via budgeted migration quanta."""
        self._gate_b = self._policy_gate()
        return self._gate_b

    def set_gate_override(self, value: bool | None) -> np.ndarray:
        """Force the target gate on/off for every slot (None restores the
        policy-derived gate).  The live layout converges to the new
        target incrementally — see `serving.migrate`."""
        self._gate_override = value
        return self.refresh_gate()

    # ----------------------------------------------------------- migration
    def migration_pending(self) -> np.ndarray:
        """(B, n_groups) bool: groups still laid under a non-target gate."""
        return _migrate.pending_mask(self)

    def migrated_upto(self, slot: int) -> int:
        """Leading groups of `slot` already at the target layout."""
        return _migrate.migrated_upto(self, slot)

    def migration_quantum(self, budget: int = 1) -> int:
        """Claim <= budget pending columns for this step's repack window."""
        return _migrate.quantum(self, budget)

    def drain_migration(self, slot: int | None = None) -> int:
        """Settle all pending migration now (evict capture, oracles)."""
        return _migrate.drain(self, slot)

    def migration_status(self) -> dict:
        return _migrate.status(self)

    def switch_packing(self, packing: str) -> None:
        """Live structural migration to a new packing layout — see
        `serving.migrate.switch_packing`."""
        _migrate.switch_packing(self, packing)

    # ------------------------------------------------------------- appends
    def append(self, k, v):
        """Uniform append to EVERY slot (offline prefill convenience);
        requires all slots at the same position."""
        assert (self.tokens_b == self.tokens_b[0]).all(), (
            "uniform append on heterogeneous slots; use append_slot/"
            "append_active")
        t0 = int(self.tokens_b[0])
        super().append(k, v)            # scatters at t0, updates self.tokens
        span = self.group_lanes * self.page
        lo, hi = t0 // span, (self.tokens - 1) // span
        self._dirty_b[:, lo:hi + 1] = True
        self._uncounted_b[:, lo:hi + 1] = True
        self.tokens_b[:] = self.tokens

    def append_slot(self, slot: int, k, v):
        """k/v (T, n_kv, d): append T tokens to one slot (admission
        prefill) at its own position."""
        k = jnp.asarray(k, jnp.bfloat16).view(jnp.int16)
        v = jnp.asarray(v, jnp.bfloat16).view(jnp.int16)
        assert k.ndim == 3, "append_slot takes one sequence (T, n_kv, d)"
        kv = jnp.concatenate([k, v], axis=-1)[None]     # (1, T, Hkv, D2)
        t = kv.shape[1]
        start = int(self.tokens_b[slot])
        assert start + t <= self.max_pages * self.page, "slot full"
        self.state["pages"] = _scatter_slot(self.state["pages"], kv,
                                            slot, start)
        self._mark_dirty(slot, start, t)
        self.tokens_b[slot] += t
        self.tokens = int(self.tokens_b.max())

    def _check_slot_ids(self, slot_ids, t: int) -> None:
        assert ((slot_ids >= 0) & (slot_ids < self.batch)).all(), \
            f"slot ids out of range: {slot_ids}"      # -1 would wrap the
        # scatter to the LAST lane and corrupt whichever sequence owns it
        assert np.unique(slot_ids).size == slot_ids.size, \
            f"duplicate slot ids: {slot_ids}"
        assert (self.tokens_b[slot_ids] + t
                <= self.max_pages * self.page).all(), "slot full"

    def append_active(self, slot_ids, k, v):
        """One decode step for a subset of slots: k/v (S, T, n_kv, d) rows
        aligned with `slot_ids`, each landing at its slot's own position —
        ONE fused scatter, no per-slot dispatch."""
        slot_ids = np.asarray(slot_ids, np.int64)
        k = jnp.asarray(k, jnp.bfloat16).view(jnp.int16)
        v = jnp.asarray(v, jnp.bfloat16).view(jnp.int16)
        kv = jnp.concatenate([k, v], axis=-1)           # (S, T, Hkv, D2)
        s, t = kv.shape[:2]
        assert s == slot_ids.size
        self._check_slot_ids(slot_ids, t)
        full = jnp.zeros((self.batch, t) + kv.shape[2:], kv.dtype)
        full = full.at[jnp.asarray(slot_ids)].set(kv)
        active = np.zeros(self.batch, bool)
        active[slot_ids] = True
        self.state["pages"] = _scatter_active(
            self.state["pages"], full,
            jnp.asarray(self.tokens_b, jnp.int32), jnp.asarray(active))
        for sl in slot_ids:
            self._mark_dirty(int(sl), int(self.tokens_b[sl]), t)
        self.tokens_b[slot_ids] += t
        self.tokens = int(self.tokens_b.max())

    def _mark_dirty(self, slot: int, start: int, t: int):
        span = self.group_lanes * self.page
        lo, hi = start // span, (start + t - 1) // span
        self._dirty_b[slot, lo:hi + 1] = True
        self._uncounted_b[slot, lo:hi + 1] = True

    # ------------------------------------------------------------- packing
    def repack(self, gate: np.ndarray | None = None):
        """Incrementally re-pack the union of per-slot dirty groups.

        The window dispatch re-lays every slot's version of each union
        column (idempotent for clean slots — packing is deterministic in
        (pages, gate, markers)); §VI fitness is counted per slot, only on
        groups that slot's OWN tokens complete, each exactly once.

        `gate` overrides the layout gate per slot for THIS window (spill
        restore re-laying a payload under its recorded gate); the default
        refreshes the policy gate — an observation boundary.  Groups laid
        under a gate that later moves are NOT stop-the-world re-dirtied:
        they become pending in `migration_pending()` and converge via
        bounded quanta."""
        idx = np.nonzero(self._dirty_b.any(0))[0]
        if idx.size == 0:
            return
        w = int(idx.size)
        enabled = (self.refresh_gate() if gate is None
                   else np.asarray(gate, bool))
        idx_j = jnp.asarray(idx, jnp.int32)
        groups = self.pages_view().reshape(
            self.batch, self.n_groups, self.group_lanes, self.page,
            self.n_kv, self.d2)
        win = groups[:, idx_j]
        slots_w, over_w, strips_w, lay, fit = self._pack_window(
            win, idx_j, enabled)
        st = self.state
        (st["slots"], st["slots_overflow"], st["strips"],
         st["packed_mask"]) = _scatter_window(
            st["slots"], st["slots_overflow"], st["strips"],
            st["packed_mask"], idx_j, slots_w, over_w, strips_w, lay)
        self._book_repack(w, enabled, lay)
        # per-slot completeness: group idx[j] is complete FOR SLOT b once
        # b's own tokens cover it
        span = self.group_lanes * self.page
        complete = (idx[None, :] + 1) * span <= self.tokens_b[:, None]
        if self.policy in ("dynamic", "auto"):
            countable = jnp.asarray(complete & self._uncounted_b[:, idx])
            fit_n = (fit & countable).sum(1)
            unfit_n = ((~fit) & countable).sum(1)
            st["counter"] = jnp.clip(
                st["counter"] + (fit_n - unfit_n).astype(jnp.int32),
                0, COUNTER_MAX)
        u = self._uncounted_b[:, idx]
        u[complete] = False
        self._uncounted_b[:, idx] = u
        self._dirty_b[:] = False
        self._applied_b[:, idx] = enabled[:, None]
        self._last_enabled = enabled.copy()

    # ----------------------------------------------------- fused megastep
    def megastep(self, slot_ids, k, v, *, budget: int = 0) -> dict:
        """One fused serve decode step: append k/v (S, T, n_kv, d) rows to
        `slot_ids`, re-lay the dirty window (+ up to `budget` migration
        columns), and book the step's read/repack traffic — ONE donated
        jitted dispatch (`_megastep`), traced once per pow2-bucketed
        (window, attend) shape.  Device-resident k/v stay on device.

        Bit-identical to append_active -> migration_quantum -> repack ->
        account_step, minus their per-call dispatches and host syncs (the
        layout gate is the frozen `_gate_b`; the §VI counter still
        updates on device every step and is re-sampled at the next
        `refresh_gate`)."""
        with TraceAnnotation("serve.megastep"):
            slot_ids = np.asarray(slot_ids, np.int64)
            assert slot_ids.size > 0, "megastep needs at least one active slot"
            k = jnp.asarray(k)
            v = jnp.asarray(v)
            s, t = k.shape[:2]
            assert s == slot_ids.size
            self._check_slot_ids(slot_ids, t)
            starts = self.tokens_b.copy()
            active = np.zeros(self.batch, bool)
            active[slot_ids] = True
            for sl in slot_ids:
                self._mark_dirty(int(sl), int(self.tokens_b[sl]), t)
            self.tokens_b[slot_ids] += t
            self.tokens = int(self.tokens_b.max())
            if budget:
                self.migration_quantum(budget)
            idx = np.nonzero(self._dirty_b.any(0))[0]
            w = int(idx.size)
            wb = min(1 << (w - 1).bit_length(), self.n_groups)
            idx_pad = np.full(wb, idx[0], np.int32)
            idx_pad[:w] = idx
            enabled = self._gate_b
            span = self.group_lanes * self.page
            complete = (idx[None, :] + 1) * span <= self.tokens_b[:, None]
            countable = np.zeros((self.batch, wb), bool)
            countable[:, :w] = complete & self._uncounted_b[:, idx]
            n = self._active_bucket()
            valid = self.valid_per_page()[:, : self.group_lanes * n]
            with TraceAnnotation("serve.megastep.dispatch"):
                self.state, raw_seq, cram_seq = _megastep(
                    self.state, self._marker_lanes, k, v,
                    jnp.asarray(slot_ids, jnp.int32),
                    jnp.asarray(starts, jnp.int32), jnp.asarray(active),
                    jnp.asarray(idx_pad), jnp.asarray(enabled),
                    jnp.asarray(countable), jnp.asarray(valid),
                    lanes=self.group_lanes, slot_bytes=self.slot_bytes,
                    strip_bytes=self.strip_bytes,
                    use_pack=self.policy != "off",
                    dyn=self.policy in ("dynamic", "auto"),
                    interpret=self.interpret)
            hs = self._host_stats    # same tallies as _book_repack, at the
            if self.policy == "off":  # padded window actually dispatched
                hs.pack_skipped_dynamic += self.batch * wb
            else:
                hs.pack_attempts += self.batch * wb
                hs.pack_skipped_dynamic += int((~enabled).sum()) * wb
            hs.pack_calls += 1
            hs.pack_pairs_processed += self.batch * wb
            u = self._uncounted_b[:, idx]
            u[complete] = False
            self._uncounted_b[:, idx] = u
            self._dirty_b[:] = False
            self._applied_b[:, idx] = enabled[:, None]
            self._last_enabled = enabled.copy()
            return {"raw_per_seq": raw_seq, "cram_per_seq": cram_seq}

    # ------------------------------------------------------ fused prefill
    def prefill_slot(self, slot: int, k, v, *, budget: int = 0) -> dict:
        """Install a whole prompt into one slot as ONE fused dispatch.

        k/v (T, n_kv, d): the prompt, landing at the slot's own position.
        Where the replay path pays T per-token `megastep` dispatches (T
        pack launches for what is one bulk write), this scatters the whole
        prompt and bulk-packs every touched page group in a single donated
        jitted call (`_prefill`): per-page codec try, in-band marker
        framing and packed-slot placement ride in ONE vmapped pallas_call
        (`kernels.prefill_pack`), the §VI counter takes the prompt's
        fitness in one clip, the LLP predictor row is seeded from the pack
        results, and the repack bytes are booked on the device accumulators
        — zero host ledger records.  A partial tail page stays raw (its
        group is zero-padded and fails the fit check), and the resulting
        cache state + attend output are bit-identical to the token-by-token
        append oracle (append_slot -> repack under the frozen `_gate_b`).

        Like `megastep`, an optional migration `budget` folds pending
        gate-flip columns into the same window, so mid-migration admits
        never add a dispatch."""
        k = jnp.asarray(k)
        v = jnp.asarray(v)
        assert k.ndim == 3, "prefill_slot takes one sequence (T, n_kv, d)"
        t = int(k.shape[0])
        assert t > 0, "prefill_slot needs a non-empty prompt"
        start = int(self.tokens_b[slot])
        cap = self.max_pages * self.page
        assert start + t <= cap, "slot full"
        self._mark_dirty(slot, start, t)
        self.tokens_b[slot] += t
        self.tokens = int(self.tokens_b.max())
        if budget:
            self.migration_quantum(budget)
        idx = np.nonzero(self._dirty_b.any(0))[0]
        w = int(idx.size)
        wb = min(1 << (w - 1).bit_length(), self.n_groups)
        idx_pad = np.full(wb, idx[0], np.int32)
        idx_pad[:w] = idx
        enabled = self._gate_b
        span = self.group_lanes * self.page
        complete = (idx[None, :] + 1) * span <= self.tokens_b[:, None]
        countable = np.zeros((self.batch, wb), bool)
        countable[:, :w] = complete & self._uncounted_b[:, idx]
        # pow2 token bucket bounds retraces across prompt lengths; the
        # zero pad rows land on never-written (all-zero) page rows
        t_pad = min(1 << (t - 1).bit_length(), cap - start)
        if t_pad > t:
            k = jnp.concatenate(
                [k, jnp.zeros((t_pad - t,) + k.shape[1:], k.dtype)])
            v = jnp.concatenate(
                [v, jnp.zeros((t_pad - t,) + v.shape[1:], v.dtype)])
        self.state = _prefill(
            self.state, self._marker_lanes, k, v, jnp.int32(slot),
            jnp.int32(start), jnp.asarray(idx_pad), jnp.asarray(enabled),
            jnp.asarray(countable),
            lanes=self.group_lanes, slot_bytes=self.slot_bytes,
            strip_bytes=self.strip_bytes, use_pack=self.policy != "off",
            dyn=self.policy in ("dynamic", "auto"),
            interpret=self.interpret)
        hs = self._host_stats        # same tallies as _book_repack, at the
        if self.policy == "off":     # padded window actually dispatched
            hs.pack_skipped_dynamic += self.batch * wb
        else:
            hs.pack_attempts += self.batch * wb
            hs.pack_skipped_dynamic += int((~enabled).sum()) * wb
        hs.pack_calls += 1
        hs.pack_pairs_processed += self.batch * wb
        u = self._uncounted_b[:, idx]
        u[complete] = False
        self._uncounted_b[:, idx] = u
        self._dirty_b[:] = False
        self._applied_b[:, idx] = enabled[:, None]
        self._last_enabled = enabled.copy()
        return {"tokens": t, "groups": w}

    # ------------------------------------------------------ slot lifecycle
    def reset_slot(self, slot: int):
        """Return a lane to pristine state for reuse (retire/evict)."""
        st = self.state
        for key in ("pages", "slots", "slots_overflow", "strips"):
            st[key] = st[key].at[slot].set(0)
        st["packed_mask"] = st["packed_mask"].at[slot].set(False)
        st["predictor"] = st["predictor"].at[slot].set(False)
        st["counter"] = st["counter"].at[slot].set(self._counter_init)
        self.tokens_b[slot] = 0
        self._dirty_b[slot] = False
        self._uncounted_b[slot] = False
        self._applied_b[slot] = self._gate_b[slot]
        self._last_enabled[slot] = bool(self._gate_b[slot])
        self.tokens = int(self.tokens_b.max())

    def slot_enabled_from_counter(self, counter: int) -> bool:
        """The gate a slot with this counter runs under (policy-resolved)."""
        if self.policy == "off":
            return False
        if self.policy == "static":
            return True
        return counter >= ENABLE_THRESHOLD

    def default_slot_gate(self) -> bool:
        """Target gate a freshly admitted slot would lay under — the
        override if one is forced, else the policy gate at the counter
        init.  Spill-direct admits record THIS as their payload gate so a
        later wake repacks like a fresh hot-lane prefill."""
        if self._gate_override is not None:
            return bool(self._gate_override)
        return self.slot_enabled_from_counter(self._counter_init)

    def slot_reference_state(self, slot: int) -> dict:
        """Per-slot from-scratch rebuild over the slot's OWN active prefix,
        under the PER-GROUP applied gate — the bit-exactness oracle for
        slot-level operations (spill round-trips, slot reuse) INCLUDING
        mid-migration states: groups already re-laid under the new target
        are judged packed, the rest raw (or vice versa), exactly as the
        in-band-marker kernel reads them."""
        g = self.slot_groups(slot)
        assert g > 0, "empty slot has no reference state"
        lanes = self.group_lanes
        pages = self.pages_view()[slot, : g * lanes]
        applied = self._applied_b[slot, :g]
        grouped = pages.reshape(g, lanes, self.page, self.n_kv, self.d2)
        over = (grouped[:, 1] if self.packing == "pair"
                else grouped[:, 1:])
        raw = {
            "slots": grouped[:, 0],
            "slots_overflow": over,
            "strips": jnp.zeros(
                (g, self.n_kv, self.d2 + MARKER_LANES), jnp.int16),
            "packed_mask": jnp.zeros((g,), bool),
        }
        if not applied.any():          # never launches the pack kernel
            c = raw
        else:
            build = (kops.build_cram_cache if self.packing == "pair"
                     else kops.build_cram_cache_quad)
            packed = dict(build(pages, key=self.key,
                                interpret=self.interpret))
            if applied.all():
                c = packed
            else:
                sel = jnp.asarray(applied)
                c = {k: jnp.where(
                        sel.reshape((g,) + (1,) * (raw[k].ndim - 1)),
                        packed[k], raw[k])
                     for k in raw}
        c = dict(c)
        c["markers"] = self.state["markers"][:g]
        return c

    def slot_physical_state(self, slot: int) -> dict:
        """The slot's physical rows over its own active prefix (compare
        against `slot_reference_state`)."""
        g = self.slot_groups(slot)
        st = self.state
        return {"slots": st["slots"][slot, :g],
                "slots_overflow": st["slots_overflow"][slot, :g],
                "strips": st["strips"][slot, :g],
                "packed_mask": st["packed_mask"][slot, :g],
                "markers": st["markers"][:g]}


__all__ = ["SlotKVCache"]
