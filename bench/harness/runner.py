"""Set-up and the timed window of one cell, over `repro.serving.ServeLoop`.

Each decoded token is an append of its KV through `ServeLoop.step`, an
`attend` with the session's query heads, and a `block_until_ready` on
the attend output, as a sampler would wait for it.  Where more sessions
decode than there are slots, they run one wave of at most `slots`
sessions at a time, in `step_all`'s order (resident sessions first):
the program has no combined append-and-attend entry over an
oversubscribed pool.  A turn's user message is appended by one `step`
and attended once; that attend yields the turn's first token.

Host spans (`jax.profiler.TraceAnnotation`, named `bench.*`) wrap every
call into the program, so a traced run can attribute device idle time
to what the host was doing.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.profiler import TraceAnnotation

CLOCK = time.perf_counter


@dataclass
class Session:
    uid: int
    plan: object               # traffic.SessionPlan
    tokens: int = 0
    turn: int = 0
    due: float | None = None   # next turn's due time (host clock)
    answer_left: float = 0     # tokens still to decode in this turn
    last_tok: float | None = None


@dataclass
class Record:
    """What the window leaves behind, for the metric readers."""

    t0: float = 0.0
    t_end: float = 0.0
    tokens: int = 0                     # answer tokens attended in window
    steps: int = 0                      # ServeLoop.step calls in window
    turns_due: list = field(default_factory=list)     # due times
    ttft: list = field(default_factory=list)          # s, censored incl.
    censored: int = 0
    itl: list = field(default_factory=list)           # s
    attends: list = field(default_factory=list)   # (t0, t1, [ctx lens])
    appended: int = 0                   # tokens appended in window
    lateness: list = field(default_factory=list)      # turn start - due
    full: int = 0                       # endless sessions that filled up


class Sampler:
    """A seeded sample of the window's answers for the reference: a
    reservoir over every answer, every session's latest (the longest
    context it reached), and a reservoir of answers given right after a
    wake from the spill tier."""

    def __init__(self, seed: int, size: int, wake_size: int):
        self.rng = np.random.default_rng([int(seed), 77])
        self.size, self.wake_size = size, wake_size
        self.seen = self.seen_wake = 0
        self.pool: list = []
        self.wake: list = []
        self.latest: dict = {}
        self.on = False

    @staticmethod
    def _offer(res, item, seen, size, rng):
        if len(res) < size:
            res.append(item)
        else:
            j = int(rng.integers(0, seen))
            if j < size:
                res[j] = item

    def offer(self, uid: int, ctx: int, out, *, woken: bool) -> None:
        if not self.on:
            return
        item = (uid, ctx, out)
        self.seen += 1
        self._offer(self.pool, item, self.seen, self.size, self.rng)
        if woken:
            self.seen_wake += 1
            self._offer(self.wake, item + ("woken",), self.seen_wake,
                        self.wake_size, self.rng)
        self.latest[uid] = item

    def items(self) -> list:
        """(uid, ctx, out, woken) for every distinct sampled answer."""
        got = {}
        for uid, ctx, out, *tag in self.wake:
            got[(uid, ctx)] = (uid, ctx, out, True)
        for uid, ctx, out in self.pool + list(self.latest.values()):
            got.setdefault((uid, ctx), (uid, ctx, out, False))
        return [got[k] for k in sorted(got)]


class Runner:
    """Drives one ServeLoop through a traffic plan."""

    def __init__(self, loop, gen, plan, *, sampler: Sampler):
        self.loop, self.gen, self.plan = loop, gen, plan
        self.slots = plan.slots
        self.sampler = sampler
        self.live: dict[int, Session] = {}
        self.next_uid = len(plan.sessions)
        self.next_spare = 0
        self.rec = Record()
        self.recording = False

    # ------------------------------------------------------------ set-up
    def prefill(self, s: Session, length: int) -> None:
        with TraceAnnotation("bench.gen"):
            k, v = self.gen.kv_each([s.uid], [0], length)
        with TraceAnnotation("bench.prefill"):
            self.loop.prefill(s.uid, k[0], v[0])
        s.tokens = length

    def admit_all(self) -> None:
        for uid, row in enumerate(self.plan.sessions):
            s = Session(uid, row)
            self.prefill(s, row.first_prompt)
            self.live[uid] = s
            if self.plan.endless:
                s.answer_left = math.inf

    # ----------------------------------------------------- per-token work
    def _pad(self, xs: list) -> list:
        return xs + [xs[0]] * (self.slots - len(xs))

    def _attend(self, sessions: list, q, woken: set) -> float:
        """attend + block for `sessions`; q rows aligned; returns the time
        the outputs were ready."""
        ctx = [s.tokens for s in sessions]
        q_by_seq = {s.uid: q[i] for i, s in enumerate(sessions)}
        ta = CLOCK()
        with TraceAnnotation("bench.attend"):
            out = self.loop.attend(q_by_seq)
        with TraceAnnotation("bench.block"):
            jax.block_until_ready(list(out.values()))
        t = CLOCK()
        if self.recording:
            self.rec.attends.append((ta, t, ctx))
        for s in sessions:
            self.sampler.offer(s.uid, s.tokens, out[s.uid],
                               woken=s.uid in woken)
        return t

    def decode_wave(self, wave: list) -> float:
        """One step + attend for up to `slots` sessions, one token each."""
        uids = self._pad([s.uid for s in wave])
        starts = self._pad([s.tokens for s in wave])
        with TraceAnnotation("bench.gen"):
            k, v = self.gen.kv_each(uids, starts, 1)
            q = self.gen.q_each(uids, starts)
        woken = {s.uid for s in wave if self.loop.seqs[s.uid].spilled}
        kv_by_seq = {s.uid: (k[i], v[i]) for i, s in enumerate(wave)}
        with TraceAnnotation("bench.step"):
            self.loop.step(kv_by_seq)
        for s in wave:
            s.tokens += 1
        t = self._attend(wave, q, woken)
        if self.recording:
            self.rec.steps += 1
            self.rec.appended += len(wave)
        for s in wave:
            self._token(s, t)
        return t

    def decode_tick(self) -> None:
        """Every answering session decodes one token: residents first,
        then spilled ones, in waves of at most `slots`."""
        gen = [s for s in self.live.values() if s.answer_left > 0]
        for s in gen:
            if s.tokens >= self.plan.capacity:     # an endless session
                s.answer_left = 0                  # that filled its slot
                self.rec.full += 1
        gen = [s for s in gen if s.answer_left > 0]
        gen.sort(key=lambda s: (self.loop.seqs[s.uid].spilled, s.uid))
        for i in range(0, len(gen), self.slots):
            self.decode_wave(gen[i:i + self.slots])

    def _token(self, s: Session, t: float) -> None:
        if self.recording and t <= self.rec.t_end:
            self.rec.tokens += 1
            if s.last_tok is not None:
                self.rec.itl.append(t - s.last_tok)
        s.last_tok = t
        s.answer_left -= 1
        if s.answer_left <= 0 and not self.plan.endless:
            s.due = t + s.plan.think[s.turn % len(s.plan.think)]
            s.turn += 1
            s.last_tok = None

    def replace(self, s: Session) -> Session:
        """Retire a session whose next turn would pass capacity; a fresh
        one (the next replacement row) takes its turn."""
        with TraceAnnotation("bench.retire"):
            self.loop.retire(s.uid)
        del self.live[s.uid]
        row = self.plan.replacements[self.next_spare % len(
            self.plan.replacements)]
        self.next_spare += 1
        fresh = Session(self.next_uid, row, due=s.due)
        self.next_uid += 1
        self.prefill(fresh, row.first_prompt)
        self.live[fresh.uid] = fresh
        return fresh

    def start_turn(self, s: Session, now: float) -> None:
        """Append the turn's user message in one step, attend once: the
        first answer token."""
        due = s.due
        if self.recording:
            self.rec.lateness.append(now - due)
        row = s.plan
        u, a = row.user[s.turn % len(row.user)], row.answer[s.turn % len(
            row.answer)]
        if s.tokens + u + a > self.plan.capacity:
            s = self.replace(s)
            row = s.plan
            u, a = row.user[0], row.answer[0]
        s.due = None
        with TraceAnnotation("bench.gen"):
            k, v = self.gen.kv_each([s.uid], [s.tokens], u)
            q = self.gen.q_each(self._pad([s.uid]),
                                self._pad([s.tokens + u - 1]))
        woken = {s.uid} if self.loop.seqs[s.uid].spilled else set()
        kv_by_seq = {s.uid: (k[0], v[0])}
        with TraceAnnotation("bench.step"):
            self.loop.step(kv_by_seq)
        s.tokens += u
        t = self._attend([s], q, woken)
        if self.recording:
            self.rec.steps += 1
            self.rec.appended += u
            self.rec.ttft.append(min(t, self.rec.t_end) - due)
            self.rec.censored += t > self.rec.t_end
        s.answer_left = a
        self._token(s, t)

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> Record:
        rec = self.rec
        rec.t0 = t0 = CLOCK()
        rec.t_end = t_end = t0 + seconds
        self.recording = True
        self.sampler.on = True
        for i, s in enumerate(self.live.values()):
            s.last_tok = None
            if not self.plan.endless:
                s.due = t0 + float(self.plan.stagger[i])
        with TraceAnnotation("bench.window"):
            while True:
                now = CLOCK()
                if now >= t_end:
                    break
                due = sorted((s.due, s.uid) for s in self.live.values()
                             if s.due is not None and s.due <= now)
                for _, uid in due:
                    if CLOCK() >= t_end:
                        break
                    rec.turns_due.append(self.live[uid].due)
                    self.start_turn(self.live[uid], CLOCK())
                if any(s.answer_left > 0 for s in self.live.values()):
                    if CLOCK() < t_end:
                        self.decode_tick()
                    continue
                nxt = min((s.due for s in self.live.values()
                           if s.due is not None), default=t_end)
                with TraceAnnotation("bench.idle"):
                    time.sleep(max(0.0, min(nxt, t_end) - CLOCK()))
        self.recording = False
        self.sampler.on = False
        # turns due in the window with no first token by its end count
        # at their censored time
        for s in self.live.values():
            if s.due is not None and s.due < t_end:
                rec.turns_due.append(s.due)
                rec.ttft.append(t_end - s.due)
                rec.censored += 1
        return rec
