"""The generator gives every (seed, session, position) the same KV and
query, whatever block it was made in: the reference regenerates what the
window fed in other block shapes than the feed used."""

import numpy as np

from harness.kvgen import Generator, root_key


def _gen(dist):
    return Generator(2**31 + 987654321, n_kv=2, d=32, hq=4,
                     kv={"dist": dist, "scale": 0.002})


def test_blocks_agree_bit_for_bit():
    for dist in ("compressible", "unit_normal"):
        g = _gen(dist)
        # decode-shaped: 4 sessions, one token each, at ragged positions
        uids, starts = [3, 5, 7, 3], [100, 517, 1023, 101]
        k1, v1 = g.kv(uids, starts, 1)
        for i, (u, s) in enumerate(zip(uids, starts, strict=True)):
            kc, vc = g.session_kv(u, 1536)
            assert np.array_equal(np.asarray(k1[i, 0]), np.asarray(kc[s]))
            assert np.array_equal(np.asarray(v1[i, 0]), np.asarray(vc[s]))
        # message-shaped: one session, a block of tokens
        km, vm = g.kv([5], [448], 64)
        kc, vc = g.session_kv(5, 1024)
        assert np.array_equal(np.asarray(km[0]), np.asarray(kc[448:512]))
        q1 = g.q([3, 5], [100, 517])
        q2 = g.q([5, 3, 3, 3, 3, 3, 3, 3], [517] + [100] * 7)
        assert np.array_equal(np.asarray(q1[1]), np.asarray(q2[0]))


def test_large_seeds_differ():
    a = np.asarray(root_key(5))
    b = np.asarray(root_key(5 + 2**31))
    assert not np.array_equal(a, b)


def test_compressible_packs_unit_normal_does_not():
    """bf16 deltas within a page pair stay small for the compressible
    stream and do not for unit normals (the property the cells rely on)."""
    for dist, small in (("compressible", True), ("unit_normal", False)):
        k, _ = _gen(dist).kv([1], [0], 32)
        bits = np.asarray(k[0]).view(np.int16).astype(np.int32)
        delta = np.abs(bits[16:] - bits[:16]).max()
        assert (delta < 128) == small, (dist, delta)
