"""The timed path broken underneath: each fault the cells can have is
planted in the program, a whole run is driven (no chip check), and
`correct` has to come out false.  One chip has no exchange between
chips to leave out."""

import jax
import jax.numpy as jnp
import pytest
from conftest import run_tiny

from repro.serving import loop as loop_mod
from repro.serving import slots as slots_mod

P4 = "phi4_mini.longctx.compressible"
CHAT = "olmoe.chat_sessions.spill"


def _state_unchanged(monkeypatch):
    """The fused step returns the pool state it was given."""
    orig = slots_mod._megastep

    def broken(state, *a, **kw):
        _, raw, cram = orig(jax.tree.map(jnp.copy, state), *a, **kw)
        return state, raw, cram

    monkeypatch.setattr(slots_mod, "_megastep", broken)


def _half_batch(monkeypatch):
    """A step appends only the first half of the sequences it names."""
    orig = loop_mod.ServeLoop.step

    def broken(self, kv_by_seq):
        ids = sorted(kv_by_seq)
        keep = ids[: max(1, len(ids) // 2)]
        return orig(self, {s: kv_by_seq[s] for s in keep})

    monkeypatch.setattr(loop_mod.ServeLoop, "step", broken)


def _token_altered(monkeypatch):
    """The first named sequence's appended token (K and V) is zeroed
    where the step takes it in."""
    orig = loop_mod.ServeLoop.step

    def broken(self, kv_by_seq):
        ids = sorted(kv_by_seq)
        k, v = kv_by_seq[ids[0]]
        return orig(self, {**kv_by_seq,
                           ids[0]: (jnp.zeros_like(k), jnp.zeros_like(v))})

    monkeypatch.setattr(loop_mod.ServeLoop, "step", broken)


def _answer_altered(monkeypatch):
    """One attend output is off by one part in a hundred."""
    orig = loop_mod.ServeLoop.attend

    def broken(self, q_by_seq, **kw):
        out = orig(self, q_by_seq, **kw)
        first = sorted(out)[0]
        out[first] = out[first] * (1 + 1e-2)
        return out

    monkeypatch.setattr(loop_mod.ServeLoop, "attend", broken)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "token_altered": _token_altered, "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = run_tiny(P4, seconds=1.5, seed=2**31 + 7)
    assert not res["correct"], (fault, res["checks"])


def test_chat_state_unchanged_is_not_correct(monkeypatch):
    _state_unchanged(monkeypatch)
    res = run_tiny(CHAT, seconds=1.5, seed=2**31 + 8)
    assert not res["correct"], res["checks"]
