"""Level 2: trace the REAL hot entry points to jaxprs and pin what the
interpret-mode benches can't see.

Every entry below is the actual production function (not a test double):
the engine's jitted chunk scan, the fused decode-on-compressed kernel in
its three deployment shapes, the incremental pack window, the serve
tier's donated scatters, the fused serve megastep (append + repack +
booking as one donated dispatch), and the KV cache's device-side booking
jits.
For each, the audit statically asserts:

  * zero `pure_callback`/`io_callback`/`debug_callback` primitives — a
    host callback inside a hot jaxpr is a per-step device->host round
    trip that CPU wall-clock numbers hide;
  * no float64 anywhere in the jaxpr — f64 doubles every DMA the byte
    model charges for and has no TPU lowering;
  * donation taking effect where configured — checked on the lowered
    StableHLO (`tf.aliasing_output`), because a silently-dropped donation
    doubles peak HBM for the KV buffers;
  * a pinned primitive-count budget (exactly ONE `pallas_call` for each
    fused-decode shape; structural `scan`/`while`/`cond` counts) — a
    refactor that splits the fused kernel or sneaks in a host loop moves
    these counts and fails against `tests/golden/jaxpr_audit.json`.

The checkpoint `pack_batch` path is audited for the inverse property: it
is host-resident BY DESIGN (cold path, vectorized numpy), so it must
create zero jax arrays and return numpy.

Regenerate the golden after an intentional kernel change with
`python -m repro.analysis --jaxpr --update-golden`.
"""

from __future__ import annotations

import json
from collections import Counter
from pathlib import Path

import numpy as np

from .engine import REPO_ROOT

GOLDEN_PATH = REPO_ROOT / "tests" / "golden" / "jaxpr_audit.json"

CALLBACK_PRIMITIVES = ("pure_callback", "io_callback", "debug_callback")
PINNED_PRIMITIVES = CALLBACK_PRIMITIVES + ("pallas_call", "scan", "while",
                                           "cond")


def _walk(jaxpr, counts: Counter) -> Counter:
    """Recursive primitive histogram (descends into closed sub-jaxprs)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] += 1
        for v in eqn.params.values():
            for item in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(item, ClosedJaxpr):
                    _walk(item.jaxpr, counts)
                elif isinstance(item, Jaxpr):
                    _walk(item, counts)
    return counts


def _dtypes(jaxpr, acc: set) -> set:
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for v in list(jaxpr.invars) + list(jaxpr.outvars) + list(
            jaxpr.constvars):
        if hasattr(v.aval, "dtype"):
            acc.add(str(v.aval.dtype))
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            if hasattr(v.aval, "dtype"):
                acc.add(str(v.aval.dtype))
        for p in eqn.params.values():
            for item in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(item, ClosedJaxpr):
                    _dtypes(item.jaxpr, acc)
                elif isinstance(item, Jaxpr):
                    _dtypes(item, acc)
    return acc


def _traced_entry(fn, *args, donated_fn=None, donate_args=None,
                  donate_kwargs=None, **kwargs) -> dict:
    """Trace fn(*args, **kwargs); optionally check donation on
    `donated_fn` (a jitted callable lowered with `donate_args` /
    `donate_kwargs` — the latter carries static_argnames)."""
    import jax

    closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    counts = _walk(closed.jaxpr, Counter())
    dts = sorted(_dtypes(closed.jaxpr, set()))
    donation = None
    if donated_fn is not None:
        text = donated_fn.lower(*(donate_args or args),
                                **(donate_kwargs or {})).as_text()
        donation = "tf.aliasing_output" in text
    return {
        "pinned": {p: int(counts.get(p, 0)) for p in PINNED_PRIMITIVES},
        "f64": any("float64" in d for d in dts),
        "donation": donation,
        "info": {"eqns": int(sum(counts.values())), "dtypes": dts,
                 "primitives": {k: int(v) for k, v in sorted(
                     counts.items())}},
    }


# --------------------------------------------------------------- the entries


def _entry_engine_chunk() -> dict:
    """core/engine step: one jitted chunk scan of the cram scheme."""
    import jax.numpy as jnp

    from ..core import schemes as schemes_registry
    from ..core.memsim import SimConfig, _jit_sim_chunked
    from ..core.traces import build_workload

    sch = schemes_registry.resolve("cram")
    init, chunk = _jit_sim_chunked(sch, SimConfig())
    _spec, addrs, wr, pa, pc, qd, _f = build_workload("libq", 256)
    carry = init()
    args = (carry, jnp.asarray(addrs[:64], jnp.int32),
            jnp.asarray(wr[:64]), jnp.asarray(pa), jnp.asarray(pc),
            jnp.asarray(qd))
    return _traced_entry(chunk, *args)


def _fused_decode(lanes: int, batched: bool) -> dict:
    import jax.numpy as jnp

    from ..kernels import ops as kops

    rng = np.random.default_rng(0)
    pages = jnp.asarray(rng.integers(-4, 4, (4, 8, 1, 64)), jnp.int16)
    build = (kops.build_cram_cache if lanes == 2
             else kops.build_cram_cache_quad)
    cache = build(pages, interpret=True)
    if batched:
        cache = {k: (jnp.stack([v, v]) if k != "markers" else v)
                 for k, v in cache.items()}
        q = jnp.zeros((2, 1, 32), jnp.float32)
        vp = jnp.full((2, 4), 8, jnp.int32)
    else:
        q = jnp.zeros((2, 1, 32), jnp.float32)
        vp = jnp.full((4,), 8, jnp.int32)

    def run(q, cache, vp):
        return kops.decode_attention_fused(q, cache, vp, lanes=lanes,
                                           interpret=True)

    return _traced_entry(run, q, cache, vp)


def _entry_pack_window() -> dict:
    """SlotKVCache repack: the jitted incremental pack window."""
    import jax.numpy as jnp

    from ..kernels import ops as kops

    a = jnp.zeros((1, 2, 8, 1, 64), jnp.int16)
    b = jnp.zeros((1, 2, 8, 1, 64), jnp.int16)
    ml = jnp.zeros((2, 2), jnp.int16)
    en = jnp.ones((1,), bool)

    def run(a, b, ml, en):
        return kops.pack_window(a, b, ml, en, interpret=True)

    return _traced_entry(run, a, b, ml, en)


def _kv_fixture():
    """A tiny real cache, one step past prefill (correct shapes/dtypes
    for the inner-jit entries)."""
    import jax.numpy as jnp

    from ..kv import CRAMKVCache, synthetic_kv_stream

    rng = np.random.default_rng(0)
    cache = CRAMKVCache(max_pages=4, page=8, n_kv=1, head_dim=32, batch=2,
                        policy="static")
    cache.append(*synthetic_kv_stream(rng, 2, 16, 1, 32))
    cache.account_step()
    return cache, jnp


def _entry_serve_scatters() -> dict:
    """ServeLoop.step_all inner jits: the donated append scatters.
    Donation is the assertion here (a dropped donation doubles the KV
    buffer's peak HBM); the jaxpr must also stay callback-free."""
    import jax.numpy as jnp

    from ..kv.cache import _scatter_tokens
    from ..serving.slots import _scatter_active

    pages = jnp.zeros((2, 32, 1, 64), jnp.int16)
    kv = jnp.zeros((2, 1, 1, 64), jnp.int16)
    starts = jnp.zeros((2,), jnp.int32)
    active = jnp.ones((2,), bool)
    rep = _traced_entry(_scatter_active, pages, kv, starts, active,
                        donated_fn=_scatter_active)
    tok = _traced_entry(_scatter_tokens, pages, kv, jnp.int32(0),
                        donated_fn=_scatter_tokens)
    rep["pinned"]["scatter_tokens_donation"] = bool(tok["donation"])
    return rep


def _entry_kv_step_booking() -> dict:
    """The device-resident accounting jits (`_absorb_step_device` +
    `_book_repack_device` via a real repack) — PR 7's O(1)-host-record
    invariant depends on these staying callback-free."""
    import jax.numpy as jnp

    from ..kv.cache import _absorb_step_device

    cache, _ = _kv_fixture()
    st = cache.state
    n = cache.n_active_groups
    valid = jnp.asarray(
        cache.valid_per_page()[:, : cache.group_lanes * n])
    raw = jnp.zeros((2,), jnp.int32)

    def run(traffic, hits, misses, predictor, packed_mask, valid, r, c):
        return _absorb_step_device(
            traffic, hits, misses, predictor, packed_mask, valid, r, c,
            lanes=cache.group_lanes, n=n)

    return _traced_entry(run, st["traffic"], st["pred_hits"],
                         st["pred_misses"], st["predictor"],
                         st["packed_mask"], valid, raw, raw)


def _entry_serve_megastep() -> dict:
    """The fused serve decode step (`SlotKVCache._megastep`): append
    scatter, window repack, §VI counter update, byte booking and the LLP
    observation as ONE donated jit.  The zero-stall serving contract —
    zero callbacks, whole-state donation taking effect, exactly one
    pallas_call (the pack kernel's) per step."""
    import jax.numpy as jnp

    from ..kv import synthetic_kv_stream
    from ..serving.slots import SlotKVCache, _megastep

    rng = np.random.default_rng(0)
    cache = SlotKVCache(max_pages=4, page=8, n_kv=1, head_dim=32, batch=2,
                        policy="static", interpret=True)
    k0, v0 = synthetic_kv_stream(rng, 2, 8, 1, 32)
    cache.megastep([0, 1], k0, v0)
    # one decode-token step's arguments, built exactly as the wrapper does
    k, v = synthetic_kv_stream(rng, 2, 1, 1, 32)
    idx = np.array([0], np.int32)
    n = cache._active_bucket()
    kwargs = dict(lanes=cache.group_lanes, slot_bytes=cache.slot_bytes,
                  strip_bytes=cache.strip_bytes, use_pack=True, dyn=False,
                  interpret=True)
    args = (cache.state, cache._marker_lanes, jnp.asarray(k),
            jnp.asarray(v), jnp.asarray([0, 1], jnp.int32),
            jnp.asarray(cache.tokens_b, jnp.int32),
            jnp.ones((2,), bool), jnp.asarray(idx),
            jnp.asarray(cache._gate_b), jnp.zeros((2, 1), bool),
            jnp.asarray(
                cache.valid_per_page()[:, : cache.group_lanes * n]))
    return _traced_entry(_megastep, *args, donated_fn=_megastep,
                         donate_kwargs=kwargs, **kwargs)


def _entry_serve_prefill() -> dict:
    """The fused chunked-prefill ingest (`SlotKVCache._prefill`): prompt
    scatter, bulk pack of every touched page group
    (`kernels.prefill_pack`), byte booking, §VI counter update and LLP
    predictor seeding as ONE donated jit — a whole prompt costs exactly
    one pallas_call (the bulk pack kernel's), zero callbacks, donated
    state."""
    import jax.numpy as jnp

    from ..kv import synthetic_kv_stream
    from ..serving.slots import SlotKVCache, _prefill

    rng = np.random.default_rng(0)
    cache = SlotKVCache(max_pages=4, page=8, n_kv=1, head_dim=32, batch=2,
                        policy="static", interpret=True)
    # one whole-prompt ingest's arguments, built exactly as the wrapper
    # does: two full page groups into slot 0 (T = 32, pow2 token bucket)
    k, v = synthetic_kv_stream(rng, 1, 32, 1, 32)
    idx = np.array([0, 1], np.int32)
    kwargs = dict(lanes=cache.group_lanes, slot_bytes=cache.slot_bytes,
                  strip_bytes=cache.strip_bytes, use_pack=True, dyn=False,
                  interpret=True)
    args = (cache.state, cache._marker_lanes, jnp.asarray(k[0]),
            jnp.asarray(v[0]), jnp.int32(0), jnp.int32(0),
            jnp.asarray(idx), jnp.asarray(cache._gate_b),
            jnp.zeros((2, 2), bool))
    return _traced_entry(_prefill, *args, donated_fn=_prefill,
                         donate_kwargs=kwargs, **kwargs)


def _entry_ckpt_pack_batch() -> dict:
    """checkpoint pack_batch: host-resident by design — zero jax arrays
    created, numpy in, numpy out, for every registered batch codec."""
    import jax

    from ..compression.codecs import codec_names, get_codec

    lines = np.arange(4 * 64, dtype=np.uint8).reshape(4, 64)
    audited, jax_created = [], 0
    before = len(jax.live_arrays())
    for name in codec_names():
        codec = get_codec(name)
        if codec.pack_batch is None:
            continue
        out = codec.pack_batch(lines)
        audited.append(name)
        if not isinstance(out, np.ndarray):
            jax_created += 1
    jax_created += max(0, len(jax.live_arrays()) - before)
    return {
        "pinned": {"jax_arrays_created": jax_created,
                   "codecs_audited": len(audited)},
        "f64": False,
        "donation": None,
        "info": {"codecs": audited},
    }


ENTRIES = {
    "engine_chunk": _entry_engine_chunk,
    "fused_decode_pair": lambda: _fused_decode(2, batched=False),
    "fused_decode_quad": lambda: _fused_decode(4, batched=False),
    "fused_decode_batched": lambda: _fused_decode(2, batched=True),
    "pack_window": _entry_pack_window,
    "serve_scatters": _entry_serve_scatters,
    "serve_megastep": _entry_serve_megastep,
    "serve_prefill": _entry_serve_prefill,
    "kv_step_booking": _entry_kv_step_booking,
    "ckpt_pack_batch": _entry_ckpt_pack_batch,
}


def audit() -> dict:
    """Trace every entry; returns {entry: {pinned, f64, donation, info}}."""
    return {name: build() for name, build in ENTRIES.items()}


def hard_violations(report: dict) -> list[str]:
    """Golden-independent invariants: zero host callbacks, no f64, every
    configured donation taking effect, exactly one pallas_call per fused
    decode.  These hold even right after --update-golden."""
    bad = []
    for name, entry in report.items():
        pinned = entry["pinned"]
        for cb in CALLBACK_PRIMITIVES:
            if pinned.get(cb, 0):
                bad.append(f"{name}: {pinned[cb]} {cb} primitive(s) — "
                           "host round trip inside a hot jaxpr")
        if entry.get("f64"):
            bad.append(f"{name}: float64 promotion in the jaxpr")
        if entry.get("donation") is False:
            bad.append(f"{name}: configured donation not taking effect")
        if name.startswith("fused_decode") and \
                pinned.get("pallas_call") != 1:
            bad.append(f"{name}: expected exactly 1 pallas_call, found "
                       f"{pinned.get('pallas_call')}")
        if name == "serve_megastep" and pinned.get("pallas_call") != 1:
            bad.append(f"{name}: the fused serve step must carry exactly "
                       f"1 pallas_call (the pack kernel), found "
                       f"{pinned.get('pallas_call')}")
        if name == "serve_prefill" and pinned.get("pallas_call") != 1:
            bad.append(f"{name}: the fused prefill ingest must carry "
                       f"exactly 1 pallas_call (the bulk pack kernel), "
                       f"found {pinned.get('pallas_call')}")
    if report.get("ckpt_pack_batch", {})["pinned"].get("jax_arrays_created"):
        bad.append("ckpt_pack_batch: checkpoint batch pack dispatched jax "
                   "work — it is a host-numpy cold path by design")
    return bad


def compare(report: dict, golden: dict) -> list[str]:
    """Pinned-budget drift vs the committed golden."""
    bad = []
    for name, gentry in golden.get("entries", {}).items():
        entry = report.get(name)
        if entry is None:
            bad.append(f"{name}: entry missing from audit")
            continue
        for key, want in gentry["pinned"].items():
            got = entry["pinned"].get(key)
            if got != want:
                bad.append(f"{name}: pinned {key} = {got}, golden pins "
                           f"{want}")
        for key in ("f64", "donation"):
            if entry.get(key) != gentry.get(key):
                bad.append(f"{name}: {key} = {entry.get(key)}, golden "
                           f"pins {gentry.get(key)}")
    return bad


def golden_view(report: dict) -> dict:
    """What --update-golden writes: the compared fields only."""
    return {"entries": {
        name: {"pinned": e["pinned"], "f64": e["f64"],
               "donation": e["donation"]}
        for name, e in report.items()}}


def run(golden_path: Path | None = None, *, update: bool = False) -> dict:
    """Audit + compare; the dict the CLI embeds in the JSON report."""
    golden_path = Path(golden_path or GOLDEN_PATH)
    report = audit()
    mismatches = hard_violations(report)
    if update:
        golden_path.parent.mkdir(parents=True, exist_ok=True)
        golden_path.write_text(json.dumps(golden_view(report), indent=2,
                                          sort_keys=True) + "\n")
    elif golden_path.exists():
        mismatches += compare(report,
                              json.loads(golden_path.read_text()))
    else:
        mismatches.append(f"golden file {golden_path} missing — run "
                          "--jaxpr --update-golden")
    return {"entries": report, "golden": str(golden_path),
            "updated": update, "mismatches": mismatches}
