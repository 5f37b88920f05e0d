"""The byte and operation count on hand-made layouts."""

import pytest

from harness import work

G = work.Geometry(page=16, lanes=2, n_kv=8, d=128, hq=24)
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def test_sizes():
    assert G.slot_bytes == 16 * 8 * 256 * 2          # one K|V page, bf16
    assert G.strip_bytes == 8 * 258 * 2              # base row + marker
    assert G.token_bytes == 2 * 8 * 128 * 2


def test_all_raw_reads_every_page_with_its_strip():
    # 100 tokens = 7 pages (6 full + 1 partial), nothing packed
    assert work.seq_bytes(100, 0.0, G) == 7 * (G.slot_bytes + G.strip_bytes)


def test_all_packed_reads_one_slot_per_complete_group():
    # 7 pages: 3 complete pairs packed + the partial 7th page raw
    assert work.seq_bytes(100, 1.0, G) == 4 * (G.slot_bytes + G.strip_bytes)


def test_half_packed_is_between():
    raw, packed = work.seq_bytes(128, 0.0, G), work.seq_bytes(128, 1.0, G)
    assert work.seq_bytes(128, 0.5, G) == pytest.approx((raw + packed) / 2)


def test_flops_and_bound():
    b, f = work.attend_work([1000, 3000], 1.0, G)
    assert f == 4 * 24 * 128 * 4000
    t, kind = work.bound(b, f, PEAKS)
    assert kind == "hbm" and t == pytest.approx(b / 819e9)
    t, kind = work.bound(1.0, 1e12, PEAKS)
    assert kind == "flops" and t == pytest.approx(1e12 / 197e12)
