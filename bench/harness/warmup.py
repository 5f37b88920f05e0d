"""Warm-up: run every program shape the window can reach, in set-up.

`ServeLoop`'s programs are compiled per shape: the fused megastep per
(sessions named, tokens appended, dirty-window bucket, attend bucket),
the attend per attend bucket, a wake's repack per the woken session's
page-group count, an evict's capture per page count, and the prefill
per padded prompt length.  A chat mix reaches many of these, so its
warm-up drives each one once through the program's own public calls,
on scratch sessions in the real pool, and retires them before the
cell's sessions are admitted.  A mix where every session decodes every
step reaches one shape of each, and its warm-up is two decode ticks of
the real sessions.
"""

from __future__ import annotations

import math
import sys
import time

import numpy as np

from .runner import Session

SCRATCH_UID = 1 << 24


def pow2ceil(x: int) -> int:
    return 1 << max(0, (int(x) - 1).bit_length())


class Warmer:
    def __init__(self, runner):
        self.d = runner
        self.loop = runner.loop
        c = runner.loop.cache
        self.span = c.group_lanes * c.page        # tokens per page group
        self.n_groups = c.n_groups
        self.uid = SCRATCH_UID
        self.scratch: list = []

    # ------------------------------------------------------------ helpers
    def _new(self, length: int) -> Session:
        s = Session(self.uid, None, answer_left=math.inf)
        self.uid += 1
        self.d.prefill(s, length)
        self.scratch.append(s)
        return s

    def _clear(self) -> None:
        for s in self.scratch:
            self.loop.retire(s.uid)
        self.scratch = []

    def attend_buckets(self, rare: float = 1e-6) -> list:
        """Attend buckets the window can reach.  The bucket follows the
        pool's longest resident, and the pool stays full, so a low bucket
        needs every resident short at once.  A bucket is left out where
        even `slots` sessions drawn from the first-prompt table (contexts
        only grow) would all fit the bucket below it with a chance under
        `rare`."""
        plan = self.d.plan
        sizes = np.sort([s.first_prompt
                         for s in plan.sessions + plan.replacements])
        hi = pow2ceil(math.ceil(plan.capacity / self.span))
        out, n = [], min(hi, self.n_groups)
        while n >= 1:
            out.append(n)
            below = self.span * n // 2            # top of bucket n / 2
            share = float(np.mean(sizes <= below))
            if n == 1 or share ** plan.slots < rare:
                break
            n //= 2
        return sorted(out)

    # -------------------------------------------------------------- parts
    def decode_waves(self, n: int) -> None:
        """Every (sessions named S, dirty-window bucket wb) at attend
        bucket n: the pool is filled with scratch sessions spread over
        w_a columns, the longest ending in column n - 1, and the first S
        of them step one token together."""
        slots = self.d.slots
        wb = 1
        while wb // 2 < min(slots, n):
            w_a = min(wb, slots, n)
            for i in range(slots):
                col = n - 1 - (i % w_a)
                self._new(self.span * col + 1)
            for s_named in range(wb // 2 + 1, slots + 1):
                self.d.decode_wave(self.scratch[:s_named])
            self._clear()
            wb *= 2

    def messages(self, n: int, sizes: list) -> None:
        """Every user-message length, starting on a group boundary and
        off it, at attend bucket n (a holder session keeps the bucket)."""
        holder = self._new(self.span * (n - 1) + 1)
        for u in sizes:
            for off in (0, self.d.loop.cache.page):
                # end short of the bucket's last token: the one-token
                # answer must fit, as every answer in the window does
                p = ((self.span * n - u - off - 1) // self.span) * self.span \
                    + off
                if p < 1:
                    continue
                s = self._new(p)
                s.plan = _OneTurn(u)
                s.due = 0.0
                self.d.start_turn(s, 0.0)
                self.loop.retire(s.uid)
                self.scratch.remove(s)
        del holder
        self._clear()

    def prompts(self, sizes: list) -> None:
        """A prefill per first-prompt length the window can admit."""
        for t in sizes:
            self._new(t)
            self._clear()

    def spill_crossings(self) -> None:
        """An evict and a wake per page-group count a session can hold."""
        plan = self.d.plan
        lo = math.ceil(min(plan.prompt_sizes) / self.span)
        hi = math.ceil(plan.capacity / self.span)
        for g in range(lo, hi + 1):
            s = self._new(self.span * g)
            self.loop.evict(s.uid)
            self.loop.wake(s.uid)
            self._clear()

    # -------------------------------------------------------------- plans
    def run(self) -> None:
        """Warm before the cell's sessions are admitted (chat mixes)."""
        plan = self.d.plan
        if plan.endless:
            return
        buckets = self.attend_buckets()
        t = time.time()
        for n in buckets:
            self.decode_waves(n)
            self.messages(n, plan.user_sizes)
            _log(f"warm-up: attend bucket {n} done at {time.time() - t:.1f} s")
        self.prompts(plan.replacement_prompt_sizes())
        _log(f"warm-up: prompts done at {time.time() - t:.1f} s")
        self.spill_crossings()
        self.loop.spill.flush()
        _log(f"warm-up: spill crossings done at {time.time() - t:.1f} s")

    def run_endless(self, ticks: int = 2) -> None:
        """Warm after admission (every-step mixes): decode ticks of the
        real sessions, whose tokens stay in their contexts."""
        for _ in range(ticks):
            self.d.decode_tick()


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class _OneTurn:
    """A one-turn plan for a scratch session: `u` message tokens and a
    one-token answer."""

    def __init__(self, u: int):
        self.user, self.answer, self.think = [u], [1], [0.0]
