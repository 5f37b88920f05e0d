"""CRAM-KV: batched paged serving cache with marker-packed page groups.

The serving-side embodiment of the paper (DESIGN.md §3): logical KV pages
pack groupwise into physical slots when delta-compressible, interpretation
is by in-band marker (kernels/cram_attention), a last-compressibility
predictor (the LLP analog, indexed by page group — compression.predictor's
`observe_layout` rule) decides whether the overflow slots need to be
fetched at all, and a per-sequence Dynamic-CRAM counter
(compression.gate, §VI) turns packing off when the data never compresses —
while *still sampling pack fitness on repacked groups*, so it can
re-enable when compressible traffic returns.

Two registry-provided packing layouts (compression.layouts):
  * packing="pair" — KV_PAIR: 2 pages per group, int8-delta codec (2:1);
  * packing="quad" — KV_QUAD: 4 pages per group, int4-delta codec (4:1),
    quad-domain markers (a slot's pair marker can never alias its quad
    marker).

Cache state is a JAX pytree with a batch axis (B sequences x page groups):
`append` is a vectorized token scatter (no per-token host loop), and
`repack` is incremental — a dirty-group mask tracks the page groups touched
since the last pack, so a decode step re-packs O(new groups) instead of
rebuilding every group (the old per-step full build made decode O(T^2) in
sequence length).  The incremental state is bit-identical to a from-scratch
`kernels/ops.build_cram_cache[_quad]` rebuild under the gate applied at the
last repack (`reference_rebuild` is the oracle; tests/test_kv_cache.py pins
it).

Bandwidth accounting (per decode step):
  raw        : one slot DMA per live page
  CRAM       : one slot DMA per packed GROUP (2 or 4 pages), plus the
               strip; unpacked groups cost one slot + strip per live page;
               mispredicted groups cost a second slot access (the paper's
               LLP-miss re-probe)

The accounting is DEVICE-RESIDENT: the decode kernel emits the (raw,
cram) bytes for the layout it walked as a second output
(kernels/cram_attention), and every per-step tally — byte totals,
repack write traffic, predictor hit/miss counts — lands in int32
accumulators carried in the cache pytree (`traffic` is a
bandwidth.device_totals array; `pred_hits`/`pred_misses`/`packed_n`/
`raw_n` are counters).  Nothing crosses to the host per step; a window
fold (`sync_ledger`, called by `saving()` and the serve-loop report
boundaries) absorbs the accumulator into the host `Ledger` with O(1)
`Ledger.record` calls, and the `stats` property reads the counters back
on demand.  So an N-step decode run costs O(1) host syncs, not O(N).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..bandwidth import Ledger
from ..bandwidth.adapters import (kv_read_device, kv_repack_device,
                                  kv_window_fold)
from ..bandwidth.ledger import device_totals
from ..compression.framing import DEFAULT_MARKER_KEY, DOMAIN_PAIR, DOMAIN_QUAD
from ..compression.gate import COUNTER_INIT, COUNTER_MAX, ENABLE_THRESHOLD
from ..compression.predictor import observe_layout
from ..kernels import ops as kops
from ..kernels import resolve_interpret
from ..kernels.ref import MARKER_LANES, marker_to_lanes, slot_markers


@dataclass
class KVStats:
    """Pack/predictor event counters.  Byte accounting is NOT here: every
    byte a decode step or repack moves lands in the cache's `ledger`
    (repro.bandwidth), under consumer "kv".

    Snapshot semantics: `CRAMKVCache.stats` builds one of these on read.
    The layout/predictor tallies (packed/raw groups, predictor hits and
    misses) accumulate in device counters inside the cache pytree and are
    synced back only here; the dispatch-shape counters (pack_attempts,
    pack_calls, …) are plain host ints — they count python-level repack
    dispatches, not device work."""

    packed_pairs: int = 0
    raw_pairs: int = 0
    predictor_hits: int = 0
    predictor_misses: int = 0
    pack_attempts: int = 0
    pack_skipped_dynamic: int = 0
    pack_calls: int = 0
    pack_pairs_processed: int = 0  # sequences x groups run through repack


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_tokens(pages, kv, start):
    """pages (B, Tmax, Hkv, D2) <- kv (B, T, Hkv, D2) at token `start`."""
    return jax.lax.dynamic_update_slice(pages, kv, (0, start, 0, 0))


@functools.partial(jax.jit, static_argnames=("lanes", "slot_bytes",
                                             "strip_bytes"))
def _book_repack_device(traffic, packed_n, raw_n, lay, *, lanes,
                        slot_bytes, strip_bytes):
    """Device-side repack booking.  The byte model lives in
    `adapters.kv_repack_device` (consumers never add byte counts — the
    ledger contract, enforced by analysis rule R5); this wrapper only
    carries the cache's packed/raw layout counters."""
    groups = lay.size
    traffic, lay_n = kv_repack_device(traffic, lay, lanes=lanes,
                                      slot_bytes=slot_bytes,
                                      strip_bytes=strip_bytes)
    return traffic, packed_n + lay_n, raw_n + (groups - lay_n)


@functools.partial(jax.jit, static_argnames=("lanes", "n"))
def _absorb_step_device(traffic, hits, misses, predictor, packed_mask,
                        valid, raw_seq, cram_seq, *, lanes, n):
    """Device-side decode-step booking: fold the kernel's per-sequence
    (raw, cram) bytes into the traffic accumulator as ONE read event,
    tally LLP hits/misses on live groups, and emit the next predictor
    state (last-layout observation, copied so it survives the donated
    repack scatter)."""
    pm = packed_mask[:, :n]
    pred = predictor[:, :n]
    live = valid.reshape(pm.shape[0], n, lanes).sum(-1) > 0
    mis = pred != pm
    hits = hits + ((~mis) & live).sum(1).astype(jnp.int32)
    misses = misses + (mis & live).sum(1).astype(jnp.int32)
    traffic = kv_read_device(traffic, raw_seq, cram_seq)
    return traffic, hits, misses, observe_layout(packed_mask)


def kernel_cache_slice(state: dict, n: int) -> dict:
    """The decode-kernel view of a cache state pytree, restricted to the
    first `n` page groups — the shape every fused consumer (attend,
    byte accounting, `SlotKVCache._megastep`) feeds the kernels.  Pure
    slicing: safe inside jit and on host state alike."""
    return {"slots": state["slots"][:, :n],
            "slots_overflow": state["slots_overflow"][:, :n],
            "strips": state["strips"][:, :n],
            "packed_mask": state["packed_mask"][:, :n],
            "markers": state["markers"][:n]}


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _scatter_window(slots, over, strips, mask, idx, slots_w, over_w,
                    strips_w, lay):
    """One fused, donated update of the physical state at group `idx` —
    the per-step write stays O(new groups), no five-way full-buffer copy."""
    return (slots.at[:, idx].set(slots_w),
            over.at[:, idx].set(over_w),
            strips.at[:, idx].set(strips_w),
            mask.at[:, idx].set(lay))


class CRAMKVCache:
    """Batched paged KV cache: B sequences, uniform token counts."""

    def __init__(self, max_pages: int, page: int, n_kv: int, head_dim: int,
                 *, batch: int = 1, policy: str = "dynamic",
                 packing: str = "pair", key: int = DEFAULT_MARKER_KEY,
                 counter_init: int = COUNTER_INIT,
                 interpret: bool | None = None,
                 ledger: Ledger | None = None):
        # "auto": the AutoTuner picked the packing (see `CRAMKVCache.auto`);
        # at runtime it is the §VI dynamic gate over that layout.
        assert policy in ("dynamic", "static", "off", "auto")
        assert packing in ("pair", "quad")
        self.packing = packing
        self.group_lanes = 2 if packing == "pair" else 4
        # capacity rounds UP to a whole number of page groups: callers ask
        # for the pages they need, the layout owns its own granularity
        max_pages = -(-max_pages // self.group_lanes) * self.group_lanes
        self.page, self.n_kv, self.d = page, n_kv, head_dim
        self.d2 = 2 * head_dim
        self.max_pages = max_pages
        self.n_groups = max_pages // self.group_lanes
        self.batch = batch
        self.policy = policy
        self.key = key
        self.interpret = resolve_interpret(interpret)
        self.tokens = 0
        domain = DOMAIN_PAIR if packing == "pair" else DOMAIN_QUAD
        markers = slot_markers(self.n_groups, key, domain=domain)
        self._marker_lanes = jnp.asarray(marker_to_lanes(markers))
        b, n, p = batch, self.n_groups, page
        over_shape = ((b, n, p, n_kv, self.d2) if packing == "pair"
                      else (b, n, self.group_lanes - 1, p, n_kv, self.d2))
        self.state = {
            "pages": jnp.zeros((b, max_pages * p, n_kv, self.d2), jnp.int16),
            "slots": jnp.zeros((b, n, p, n_kv, self.d2), jnp.int16),
            "slots_overflow": jnp.zeros(over_shape, jnp.int16),
            "strips": jnp.zeros((b, n, n_kv, self.d2 + MARKER_LANES),
                                jnp.int16),
            "packed_mask": jnp.zeros((b, n), bool),
            "predictor": jnp.zeros((b, n), bool),
            "counter": jnp.full((b,), counter_init, jnp.int32),
            "markers": jnp.asarray(markers.view(np.int32)),
            # device-resident accounting: decode/repack traffic window
            # (folded into the host ledger by `sync_ledger`) and the
            # layout/predictor tallies behind the `stats` property
            "traffic": device_totals(jnp),
            "pred_hits": jnp.zeros((b,), jnp.int32),
            "pred_misses": jnp.zeros((b,), jnp.int32),
            "packed_n": jnp.zeros((), jnp.int32),
            "raw_n": jnp.zeros((), jnp.int32),
        }
        # dirty-group mask: appends are uniform across the batch, so one
        # host-side mask covers every sequence; per-sequence gate flips
        # mark the whole active prefix dirty (rare — full re-layout).
        self._dirty = np.zeros(self.n_groups, bool)
        # groups with data not yet fed to the §VI counter: a gate flip
        # re-dirties the layout but must NOT re-count historical fitness
        # (that would re-apply the whole prefix's fit/unfit balance and
        # could slam the counter straight back across the threshold).
        self._uncounted = np.zeros(self.n_groups, bool)
        self._last_enabled = np.full(batch, policy != "off", bool)
        self._host_stats = KVStats()
        # traffic lands here (consumer "kv"); pass a shared ledger to fold
        # this cache's flows into a launcher-wide accounting
        self.ledger = ledger if ledger is not None else Ledger("kv")
        self.slot_bytes = page * n_kv * self.d2 * 2
        self.strip_bytes = n_kv * (self.d2 + MARKER_LANES) * 2

    @classmethod
    def auto(cls, tuner, k_sample, v_sample, *, max_pages: int, page: int,
             n_kv: int, head_dim: int, **kw):
        """`policy="auto"`: let an `bandwidth.AutoTuner` pick the packing
        layout (off / pair / quad) from a sample of the KV stream, then run
        the §VI dynamic gate over the chosen layout.  Returns (cache,
        PolicyChoice)."""
        d2 = 2 * head_dim
        choice = tuner.choose_kv_packing(
            k=k_sample, v=v_sample, page=page,
            slot_bytes=page * n_kv * d2 * 2,
            strip_bytes=n_kv * (d2 + MARKER_LANES) * 2)
        if choice.choice == "off":
            cache = cls(max_pages, page, n_kv, head_dim,
                        policy="off", packing="pair", **kw)
        else:
            cache = cls(max_pages, page, n_kv, head_dim, policy="auto",
                        packing=choice.choice, **kw)
        return cache, choice

    # legacy pair-era aliases (the default packing is the 2:1 pair layout)
    @property
    def n_pairs(self) -> int:
        return self.n_groups

    @property
    def host_stats(self) -> KVStats:
        """The host dispatch counters ALONE (pack_attempts, pack_calls,
        pack_pairs_processed, …) — NO device sync.  Timed loops that only
        need the python-level repack tallies read this instead of `stats`,
        which pulls four device counters back per access (analysis R3:
        no host syncs inside timed regions)."""
        return self._host_stats

    @property
    def stats(self) -> KVStats:
        """Snapshot of the event counters: host dispatch counters merged
        with the device tallies (the only place those sync back)."""
        from dataclasses import replace

        st = self.state
        return replace(
            self._host_stats,
            packed_pairs=int(st["packed_n"]),
            raw_pairs=int(st["raw_n"]),
            predictor_hits=int(jnp.sum(st["pred_hits"])),
            predictor_misses=int(jnp.sum(st["pred_misses"])))

    def sync_ledger(self) -> None:
        """Window fold: absorb the device traffic accumulator into the
        host ledger (O(1) `Ledger.record` calls however many decode steps
        the window covered), then reset it.  int32 bounds one window at
        2 GiB per event class — report boundaries (`saving`, the serve
        loop's `observe_tiers`/`summary`) fold well before that."""
        tot = np.asarray(self.state["traffic"])
        if tot.any():
            kv_window_fold(self.ledger, tot)
            self.state["traffic"] = device_totals(jnp)

    # ----------------------------------------------------------- appends
    def append(self, k, v):
        """k/v: (B, T, n_kv, d) — or (T, n_kv, d) when batch == 1 — new
        tokens, any float dtype (stored as bf16 bit patterns)."""
        k = jnp.asarray(k, jnp.bfloat16).view(jnp.int16)
        v = jnp.asarray(v, jnp.bfloat16).view(jnp.int16)
        if k.ndim == 3:
            assert self.batch == 1, "batched cache needs (B, T, n_kv, d)"
            k, v = k[None], v[None]
        kv = jnp.concatenate([k, v], axis=-1)        # (B, T, n_kv, d2)
        bsz, t = kv.shape[:2]
        assert bsz == self.batch
        assert self.tokens + t <= self.max_pages * self.page, "cache full"
        self.state["pages"] = _scatter_tokens(
            self.state["pages"], kv, self.tokens)
        span = self.group_lanes * self.page           # tokens per group
        lo = self.tokens // span
        hi = (self.tokens + t - 1) // span
        self._dirty[lo:hi + 1] = True
        self._uncounted[lo:hi + 1] = True
        self.tokens += t

    @property
    def n_pages(self) -> int:
        return (self.tokens + self.page - 1) // self.page

    @property
    def n_active_groups(self) -> int:
        return -(-self.n_pages // self.group_lanes)

    @property
    def n_active_pairs(self) -> int:
        return self.n_active_groups

    def valid_per_page(self) -> np.ndarray:
        """(B, max_pages) int32 valid tokens per logical page."""
        v = np.clip(self.tokens - np.arange(self.max_pages) * self.page,
                    0, self.page).astype(np.int32)
        return np.broadcast_to(v, (self.batch, self.max_pages)).copy()

    def pages_view(self):
        """Logical pages (B, max_pages, page, n_kv, d2)."""
        return self.state["pages"].reshape(
            self.batch, self.max_pages, self.page, self.n_kv, self.d2)

    # ------------------------------------------------------------- packing
    def enabled(self) -> np.ndarray:
        """(B,) bool: per-sequence compression gate (counter MSB, §VI)."""
        if self.policy == "off":
            return np.zeros(self.batch, bool)
        if self.policy == "static":
            return np.ones(self.batch, bool)
        return np.asarray(self.state["counter"]) >= ENABLE_THRESHOLD

    def _pack_window(self, win, idx_j, enabled):
        """Dispatch the dirty window to the layout's pack/raw kernels.

        win: (B, W, lanes, page, n_kv, d2) gathered dirty groups."""
        return kops.layout_window(win, self._marker_lanes[idx_j],
                                  jnp.asarray(enabled),
                                  use_pack=self.policy != "off",
                                  interpret=self.interpret)

    def _book_repack(self, w: int, enabled, lay) -> None:
        """Host dispatch counters + device byte/layout booking for one
        repack window (shared with SlotKVCache.repack)."""
        hs = self._host_stats
        if self.policy == "off":
            hs.pack_skipped_dynamic += self.batch * w
        else:
            hs.pack_attempts += self.batch * w
            hs.pack_skipped_dynamic += int((~enabled).sum()) * w
        hs.pack_calls += 1
        hs.pack_pairs_processed += self.batch * w
        st = self.state
        st["traffic"], st["packed_n"], st["raw_n"] = _book_repack_device(
            st["traffic"], st["packed_n"], st["raw_n"], lay,
            lanes=self.group_lanes, slot_bytes=self.slot_bytes,
            strip_bytes=self.strip_bytes)

    def repack(self):
        """Incrementally re-pack the dirty groups.

        Idempotency cheap-exit: a clean cache returns before touching any
        device state, so back-to-back repacks (attend -> account_step on
        the same decode step) dispatch the pack pipeline exactly once."""
        idx = np.nonzero(self._dirty)[0]
        if idx.size == 0:
            return
        w = int(idx.size)
        enabled = self.enabled()
        idx_j = jnp.asarray(idx, jnp.int32)
        groups = self.pages_view().reshape(
            self.batch, self.n_groups, self.group_lanes, self.page,
            self.n_kv, self.d2)
        win = groups[:, idx_j]                # (B, W, lanes, page, ...)
        slots_w, over_w, strips_w, lay, fit = self._pack_window(
            win, idx_j, enabled)
        st = self.state
        (st["slots"], st["slots_overflow"], st["strips"],
         st["packed_mask"]) = _scatter_window(
            st["slots"], st["slots_overflow"], st["strips"],
            st["packed_mask"], idx_j, slots_w, over_w, strips_w, lay)
        self._book_repack(w, enabled, lay)
        # §VI cost/benefit: fitness of *complete, not-yet-counted* repacked
        # groups drives the per-sequence counter — measured even while
        # disabled (the zeroed layout mask no longer feeds the update), so
        # the gate can re-enable once compressible traffic returns.  Each
        # group is counted exactly once, when it completes: gate-flip
        # re-dirt re-lays groups out but never re-counts their fitness.
        complete = (idx + 1) * self.group_lanes * self.page <= self.tokens
        if self.policy in ("dynamic", "auto"):
            countable = jnp.asarray(complete & self._uncounted[idx])
            fit_n = (fit & countable[None, :]).sum(1)
            unfit_n = ((~fit) & countable[None, :]).sum(1)
            st["counter"] = jnp.clip(
                st["counter"] + (fit_n - unfit_n).astype(jnp.int32),
                0, COUNTER_MAX)
        self._uncounted[idx[complete]] = False
        self._dirty[:] = False
        self._last_enabled = enabled
        flipped = self.enabled() != enabled
        if flipped.any():
            # gate changed for some sequence: its whole layout must be
            # rebuilt under the new gate at the next repack (keeps the
            # incremental state equal to a full rebuild).
            self._dirty[: self.n_active_groups] = True

    def reference_rebuild(self) -> dict:
        """From-scratch full pack of the active groups, per sequence, under
        the gate applied at the last repack — the bit-exactness oracle for
        the incremental path (compare with `active_state`)."""
        lanes = self.group_lanes
        n2 = lanes * self.n_active_groups
        pages = self.pages_view()[:, :n2]
        build = (kops.build_cram_cache if self.packing == "pair"
                 else kops.build_cram_cache_quad)
        out = []
        for bi in range(self.batch):
            if self._last_enabled[bi]:
                c = build(pages[bi], key=self.key, interpret=self.interpret)
            else:
                n = n2 // lanes
                grouped = pages[bi].reshape(
                    n, lanes, self.page, self.n_kv, self.d2)
                over = (grouped[:, 1] if self.packing == "pair"
                        else grouped[:, 1:])
                c = {
                    "slots": grouped[:, 0],
                    "slots_overflow": over,
                    "strips": jnp.zeros(
                        (n, self.n_kv, self.d2 + MARKER_LANES), jnp.int16),
                    "markers": self.state["markers"][:n],
                    "packed_mask": jnp.zeros((n,), bool),
                }
            out.append(c)
        keys = ("slots", "slots_overflow", "strips", "packed_mask")
        ref = {k: jnp.stack([c[k] for c in out]) for k in keys}
        ref["markers"] = self.state["markers"][: n2 // lanes]
        return ref

    def active_state(self) -> dict:
        """The physical cache restricted to the active group prefix."""
        return self._kernel_cache(self.n_active_groups)

    # -------------------------------------------------------------- attend
    def _active_bucket(self) -> int:
        """Active group count rounded up to a power of two: the decode grid
        walks O(sequence) slots, not O(capacity), while the pow2 bucketing
        bounds retraces to log2(capacity) shapes as the sequence grows."""
        n = max(1, self.n_active_groups)
        return min(1 << (n - 1).bit_length(), self.n_groups)

    def _kernel_cache(self, n: int) -> dict:
        return kernel_cache_slice(self.state, n)

    def account_step(self) -> dict:
        """One decode step's bandwidth accounting + LLP predictor update.

        Charges the CRAM byte model (incl. the mispredict re-probe against
        the group-indexed predictor), tallies predictor hits/misses on live
        groups, then lets the predictor observe the actual layout.  All of
        it lands in the device accumulators — no host ledger traffic until
        the next `sync_ledger` window fold.
        """
        self.repack()
        return self._account()

    def _absorb_step(self, raw_seq, cram_seq, valid, n: int) -> dict:
        """Fold one decode step's per-sequence byte columns + predictor
        observation into the device accumulators (one fused dispatch)."""
        st = self.state
        (st["traffic"], st["pred_hits"], st["pred_misses"],
         st["predictor"]) = _absorb_step_device(
            st["traffic"], st["pred_hits"], st["pred_misses"],
            st["predictor"], st["packed_mask"], valid, raw_seq, cram_seq,
            lanes=self.group_lanes, n=n)
        raw_t, cram_t = raw_seq.sum(), cram_seq.sum()
        return {"raw_bytes": raw_t, "cram_bytes": cram_t,
                "raw_per_seq": raw_seq, "cram_per_seq": cram_seq,
                "saving": 1.0 - cram_t / jnp.maximum(raw_t, 1)}

    def _account(self) -> dict:
        st = self.state
        lanes = self.group_lanes
        n = self._active_bucket()
        valid = jnp.asarray(self.valid_per_page()[:, : lanes * n])
        raw_seq, cram_seq = kops.hbm_bytes_moved_device(
            self._kernel_cache(n), valid,
            predictor=st["predictor"][:, :n], lanes=lanes)
        return self._absorb_step(raw_seq, cram_seq, valid, n)

    def attend(self, q, *, account: bool = True):
        """q: (B, Hq, d) one query row per sequence -> (B, Hq, d) float32,
        with per-step bandwidth accounting (`account=False` for parity
        probes that must not charge an extra step).

        One pass over the physical state: the fused kernel walks the slot
        list once and emits the step's byte columns alongside the
        attention output, so accounting adds no second traversal."""
        self.repack()
        q = jnp.asarray(q)
        if q.ndim == 2:
            q = q[None]
        n = self._active_bucket()
        st = self.state
        valid = jnp.asarray(
            self.valid_per_page()[:, : self.group_lanes * n])
        out, raw_seq, cram_seq = kops.decode_attention_fused(
            q, self._kernel_cache(n), valid,
            st["predictor"][:, :n] if account else None,
            lanes=self.group_lanes, interpret=self.interpret)
        if account:
            self._absorb_step(raw_seq, cram_seq, valid, n)
        return out

    def attend_ref(self, q):
        """Oracle (pure jnp) attention over the same physical state."""
        self.repack()
        q = jnp.asarray(q)
        if q.ndim == 2:
            q = q[None]
        n = self._active_bucket()
        decode = (kops.decode_attention_ref_batched
                  if self.packing == "pair"
                  else kops.decode_attention_quad_ref_batched)
        return decode(q, self._kernel_cache(n),
                      self.valid_per_page()[:, : self.group_lanes * n])

    def saving(self) -> float:
        """Cumulative decode-bandwidth saving, read from the ledger (the
        "kv" consumer's read rows: raw layout bytes vs CRAM bytes).  Folds
        the pending device window first, so the number is current."""
        self.sync_ledger()
        return self.ledger.saving("read", consumer="kv")
