"""Regression tests for the R3 timed-region fixes (analysis rule R3: no
device->host syncs inside timed hot loops).

Two real findings the static analyzer surfaced on the tree:
  * `launch/serve._timed_decode` used to materialize every decode token
    with `np.asarray(tok)` INSIDE the timed loop (one blocking host sync
    per generated token) and read the wall clock without syncing the last
    step.  The test pins the fixed ordering structurally: between the two
    wall-clock reads there is no host materialization, and
    `block_until_ready` runs before the timer stops.
  * `benchmarks/serve_bench._timed_decode_loop` used to read the
    device-syncing `CRAMKVCache.stats` property (four device counters per
    access) and `int()` the byte duals on every timed step.  The test
    poisons `stats` and runs the loop — the timed region must never touch
    it — and checks the pack tallies still match the device-synced path.
"""

import pathlib
import sys
import time

import jax
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


class _SpyModule:
    """Attribute proxy that logs every access before delegating."""

    def __init__(self, target, log, tag):
        self._target, self._log, self._tag = target, log, tag

    def __getattr__(self, name):
        self._log.append(f"{self._tag}.{name}")
        return getattr(self._target, name)


def test_launch_decode_no_host_sync_in_timed_region(monkeypatch):
    from repro.launch import serve as serve_mod

    log = []
    monkeypatch.setattr(serve_mod, "time", _SpyModule(time, log, "time"))
    monkeypatch.setattr(serve_mod, "np", _SpyModule(np, log, "np"))
    monkeypatch.setattr(serve_mod, "jax", _SpyModule(jax, log, "jax"))

    @jax.jit
    def serve_step(params, tok, cache, i):
        return tok + 1, cache, tok.astype(np.float32)

    prompts = np.arange(12, dtype=np.int32).reshape(3, 4)
    gen, cache, logits, prefill_wall, decode_wall = serve_mod._timed_decode(
        serve_step, None, prompts, {"k": np.zeros(2)}, gen=5)

    # the stub increments the last prompt token once per step
    want = prompts[:, -1:] + 1 + np.arange(5)[None, :]
    np.testing.assert_array_equal(gen, want)
    np.testing.assert_array_equal(logits, want[:, -1:] - 1)
    assert prefill_wall >= 0.0 and decode_wall >= 0.0

    # prefill and decode are SEPARATELY timed regions: four clock reads,
    # each region obeying the R3 discipline on its own
    clocks = [i for i, e in enumerate(log) if e == "time.time"]
    assert len(clocks) == 4, log
    for t0, t1 in ((clocks[0], clocks[1]), (clocks[2], clocks[3])):
        timed = log[t0 + 1:t1]
        # no host materialization between the clock reads ...
        assert not any(e.startswith("np.") for e in timed), timed
        # ... and the device work is synced before the timer stops
        assert "jax.block_until_ready" in timed, timed
    # the host copies happen, but only after the last timed region
    assert any(e.startswith("np.") for e in log[clocks[3]:]), log


def test_serve_bench_timed_loop_never_syncs_stats(monkeypatch):
    import benchmarks.serve_bench as sb
    from repro.kv import CRAMKVCache

    def _make(seed=0):
        rng = np.random.default_rng(seed)
        cache = CRAMKVCache(max_pages=4, page=sb.PAGE, n_kv=sb.HKV,
                            head_dim=sb.HD, batch=1, policy="static")
        cache.append(*sb._stream(rng, 1, 2 * sb.PAGE, True))
        cache.account_step()
        return cache, rng

    # reference run: the device-synced stats path agrees with host_stats
    cache, rng = _make()
    before = cache.stats.pack_pairs_processed
    assert before == cache.host_stats.pack_pairs_processed

    def _poisoned(self):
        raise AssertionError("device-syncing stats read inside timed loop")

    monkeypatch.setattr(CRAMKVCache, "stats", property(_poisoned))
    cache, rng = _make()
    seq_len, pack_pairs, total_pairs, cram_b, raw_b, wall = \
        sb._timed_decode_loop(cache, rng, 1, 3, True)
    assert len(seq_len) == len(cram_b) == len(raw_b) == 3
    assert all(isinstance(v, int) and v > 0 for v in raw_b)
    assert all(isinstance(v, int) and v > 0 for v in cram_b)
    assert all(p >= 0 for p in pack_pairs)
    assert wall >= 0.0


def test_serve_bench_decode_curve_unchanged_values():
    """The R3 restructure must not change what decode_curve reports."""
    import benchmarks.serve_bench as sb

    rep = sb.decode_curve(policy="static", batch=1, prefill_pages=2,
                          decode_steps=4, compressible=True, seed=3)
    assert len(rep["cram_bytes_per_step"]) == 4
    assert rep["seq_len"] == sorted(rep["seq_len"])
    # compressible static stream saves bytes and the duals are consistent
    assert 0.0 < rep["cumulative_saving"] < 1.0
    assert all(c <= r for c, r in zip(rep["cram_bytes_per_step"],
                                      rep["raw_bytes_per_step"],
                                      strict=True))
