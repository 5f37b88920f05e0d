"""The serve tier's program spans, read back from a profiler trace.

A small `ServeLoop` (2 slots) runs a profiled window on the CPU: three
prefills, so the third evicts a resident; a step that names the spilled
sequence, so it wakes inside the step and evicts the other; attends, and
a retire.  The written `.xplane.pb` must hold every `serve.*` span, each
child inside its parent's interval on the same thread, and the keyword
metadata as event stats under clean event names.
"""

from __future__ import annotations

import glob
from collections import defaultdict

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.kv import synthetic_kv_stream
from repro.serving import ServeLoop

PAGE, HKV, HD, HQ = 8, 1, 16, 2

# every program span, with the spans it may lie inside (None: top level)
PARENTS = {
    "serve.step": None,
    "serve.wake": {"serve.step"},
    "serve.evict": {"serve.wake", "serve.prefill"},
    "serve.prefill": None,
    "serve.retire": None,
    "serve.megastep": {"serve.step"},
    "serve.megastep.dispatch": {"serve.megastep"},
    "serve.attend": None,
    "serve.attend.q": {"serve.attend"},
    "serve.attend.prep": {"serve.attend"},
    "serve.attend.dispatch": {"serve.attend"},
}


def _kv(rng, t):
    k, v = synthetic_kv_stream(rng, 1, t, HKV, HD)
    return k[0], v[0]


def _q(rng):
    return rng.standard_normal((HQ, HD)).astype(np.float32)


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    """{name: [(thread, start_ns, end_ns, stats)]} of the serve.* events
    of one profiled window."""
    rng = np.random.default_rng(0)
    loop = ServeLoop(slots=2, max_pages=4, page=PAGE, n_kv=HKV,
                     head_dim=HD, policy="static")
    tdir = str(tmp_path_factory.mktemp("serve_trace"))
    jax.profiler.start_trace(tdir)
    try:
        loop.prefill(0, *_kv(rng, PAGE + 3))
        loop.prefill(1, *_kv(rng, PAGE))
        loop.step({0: _kv(rng, 1), 1: _kv(rng, 1)})
        jax.block_until_ready(loop.attend({0: _q(rng), 1: _q(rng)}))
        loop.prefill(2, *_kv(rng, PAGE))       # pool full: evicts seq 0
        assert loop.seqs[0].spilled
        loop.step({0: _kv(rng, 1), 2: _kv(rng, 1)})   # wakes 0, evicts 1
        assert loop.seqs[1].spilled and not loop.seqs[0].spilled
        jax.block_until_ready(loop.attend({0: _q(rng), 2: _q(rng)}))
        loop.retire(2)
        loop.spill.flush()
    finally:
        jax.profiler.stop_trace()
    assert loop.counts["evicted"] == 2 and loop.counts["woken"] == 1
    paths = glob.glob(f"{tdir}/plugins/profile/*/*.xplane.pb")
    assert len(paths) == 1, paths
    out = defaultdict(list)
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    a = float(ev.start_ns)
                    out[ev.name].append(((plane.name, line.name), a,
                                         a + float(ev.duration_ns),
                                         dict(ev.stats)))
    return out


def test_every_span_is_in_the_trace(spans):
    assert set(spans) == set(PARENTS)
    assert len(spans["serve.step"]) == 2
    assert len(spans["serve.attend"]) == 2
    assert len(spans["serve.prefill"]) == 3
    assert len(spans["serve.evict"]) == 2
    assert len(spans["serve.wake"]) == 1
    assert len(spans["serve.retire"]) == 1


@pytest.mark.parametrize("child", sorted(n for n, p in PARENTS.items() if p))
def test_each_child_lies_inside_its_parent(spans, child):
    for thread, a, b, _ in spans[child]:
        assert any(t == thread and pa <= a and b <= pb
                   for parent in PARENTS[child]
                   for t, pa, pb, _ in spans[parent]), (child, a, b)


def test_step_and_attend_carry_their_stats(spans):
    steps = sorted(spans["serve.step"], key=lambda s: s[1])
    assert [s[3]["step"] for s in steps] == [1, 2]
    assert [s[3]["n"] for s in steps] == [2, 2]
    assert [s[3]["n"] for s in spans["serve.attend"]] == [2, 2]


def test_sequence_spans_carry_the_sequence(spans):
    by_start = lambda name: [s[3]["seq"]
                             for s in sorted(spans[name], key=lambda s: s[1])]
    assert by_start("serve.prefill") == [0, 1, 2]
    assert by_start("serve.evict") == [0, 1]
    assert by_start("serve.wake") == [0]
    assert by_start("serve.retire") == [2]
