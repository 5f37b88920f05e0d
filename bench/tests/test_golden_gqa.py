"""The GQA kind computes what the harness computed before the attention
kinds were split out of it, bit for bit: its generator's payloads,
queries and reference state, the comparison's per-answer errors, the
bytes and operations of the work count, and the roofline readers on the
recorded v5e trace.  The recorded values (`fixtures/gqa_golden.json`)
were taken from that harness at tiny widths on the CPU."""

import hashlib
import json
from functools import partial
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from harness import check, spec, trace, work
from harness.cell import Run
from harness.runner import Record

FIXTURES = Path(__file__).parent / "fixtures"
GOLD = json.loads((FIXTURES / "gqa_golden.json").read_text())
GQA = spec.kind("gqa")
UIDS, STARTS = [3, 5, 7, 3], [100, 517, 1023, 101]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def _h(x):
    a = np.asarray(x)
    return {"shape": list(a.shape), "dtype": str(a.dtype),
            "sha256": hashlib.sha256(a.tobytes()).hexdigest()}


def _gen(dist):
    g = GOLD["geometry"]
    config = {"num_key_value_heads": g["n_kv"],
              "num_attention_heads": g["hq"],
              "assumed": {"head_dim": g["d"]}}
    return GQA.Generator(GOLD["seed"], config,
                         {"kv": {"dist": dist, "scale": 0.002}})


@pytest.mark.parametrize("dist", ["compressible", "unit_normal"])
def test_generator_matches_recorded(dist):
    gold, g = GOLD["gen"][dist], _gen(dist)
    feed = g.feed_each(UIDS, STARTS, 1)
    assert [[_h(x) for x in p] for p in feed] == gold["feed_decode"]
    feed = g.feed_each([5], [448], 64)
    assert [[_h(x) for x in p] for p in feed] == gold["feed_block"]
    assert [_h(x) for x in g.q_each(UIDS, STARTS)] == gold["q_each"]
    assert _h(g.q([5, 3, 7, 3], [517, 100, 1023, 101])) == gold["q"]
    assert [_h(x) for x in g.session_state(5, 1100)] == gold[
        "session_state"]


def test_compare_matches_recorded():
    """Answers off the reference's by a seeded relative noise, in blocks
    of 8 with a remainder, over three sessions: the same errors, and the
    same control readings."""
    gold = GOLD["compare"]
    g = _gen("compressible")
    ref = spec.Cell("x", 1, {"reference": "gqa_decode_attention"}, {}, {},
                    [], []).reference()
    rng = np.random.default_rng(gold["noise_seed"])
    items = []
    for uid, ctxs in ((int(u), c) for u, c in gold["plan"].items()):
        k, v = g.session_state(uid, max(ctxs))
        for c in ctxs:
            q = g.q([uid], [c - 1])
            r = np.asarray(ref.attend(q, k, v, jnp.asarray([c], jnp.int32)))
            r = r[0]
            o = r * (1 + gold["noise"] * rng.standard_normal(r.shape)
                     ).astype(np.float32)
            items.append((uid, c, o, c % 2 == 0))
    answers = partial(GQA.reference_answers, ref)
    res = check.compare(items, g, answers)
    assert res["errs"] == gold["errs"]
    assert res["attend_rel_err"] == gold["attend_rel_err"]
    assert res["answers_checked"] == gold["answers_checked"]
    assert res["woken_answers_checked"] == gold["woken_answers_checked"]
    ctl = check.compare(items, g, answers, control="bfloat16")
    assert ctl["errs"] == gold["control_errs"]
    assert ctl["control_rel_err"] == gold["control_rel_err"]


@pytest.mark.parametrize("name", sorted(GOLD["work"]))
def test_work_matches_recorded(name):
    gold = GOLD["work"][name]
    geo = GQA.Geometry(*gold["geometry"])
    assert geo.token_bytes == gold["token_bytes"]
    for row in gold["attend_work"]:
        b, f = work.attend_work(row["ctx"], row["share"], geo)
        assert (b, f) == (row["bytes"], row["flops"]), row


@pytest.mark.parametrize("packed", sorted(GOLD["readers"]["by_packed"]))
def test_roofline_readers_match_recorded(packed):
    gold = GOLD["readers"]
    case = gold["by_packed"][packed]
    n = gold["attends"]
    events = trace.read(str(FIXTURES / "trace_v5e.json"))
    rec = Record(t0=0.0, t_end=1.0, tokens=8 * n, steps=n,
                 attends=[(0.0, 0.0, gold["ctx"])] * n, appended=8 * n)
    run = Run(cell="x", geo=GQA.Geometry(16, 2, 8, 128, 24), peaks=PEAKS,
              rec=rec, setup_s=1.0, memory_peak_bytes=1, counts={},
              packed=case["packed"], trace=trace.Trace(events))
    for name, want in case["values"].items():
        got = spec.reader(name).read(run)
        assert (list(got) if isinstance(got, tuple) else got) == want, name
