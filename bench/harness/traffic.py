"""The one traffic generator: sessions over a pool of slots.

A mix file (`bench/traffic/<mix>.json`) gives the pool, the sessions'
first prompts and, where sessions talk in turns, the turn sizes and think
times.  Sizes and arrivals are drawn once from the mix's own
`sizes_seed`, so every run seed sees the same set of them; the run seed
only reorders which session gets which row, and makes the KV and the
queries.

  turn = null   every session decodes one token per step from the start
                of the window to its end (closed loop, no admits);
  serve = {...} (optional) ServeLoop keyword arguments, e.g. the packing;
                the serve tier's defaults otherwise.
  turn = {...}  closed-loop chat: each turn is a user message of `user`
                tokens appended in one step, then an answer of `answer`
                tokens, one per step, then `think_s` seconds before the
                next turn.  First turns are staggered uniformly over
                `first_turn_stagger_s`.  A session whose next turn would
                pass `capacity_tokens` retires, and a fresh one from the
                replacement rows takes its place for that turn.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


def draw(spec: dict, rng, n: int) -> np.ndarray:
    """n sizes from a size spec: {"choices": [...]} cycles the list;
    {"lognormal": {"median", "sigma"}, "min", "max", "round_up"} clips a
    lognormal draw and rounds it up to a multiple; {"exponential_mean",
    "max"} draws seconds."""
    if "choices" in spec:
        c = np.asarray(spec["choices"])
        return c[np.arange(n) % c.size]
    if "exponential_mean" in spec:
        x = rng.exponential(spec["exponential_mean"], n)
        return np.minimum(x, spec.get("max", np.inf))
    ln = spec["lognormal"]
    x = np.exp(np.log(ln["median"]) + ln["sigma"] * rng.standard_normal(n))
    x = np.clip(x, spec["min"], spec["max"])
    r = int(spec.get("round_up", 1))
    return (np.ceil(x / r) * r).astype(np.int64)


@dataclass
class SessionPlan:
    first_prompt: int
    user: list = field(default_factory=list)      # tokens per turn
    answer: list = field(default_factory=list)    # tokens per turn
    think: list = field(default_factory=list)     # seconds after a turn


@dataclass
class Plan:
    slots: int
    capacity: int
    sessions: list                # SessionPlan, in uid order 0..n-1
    replacements: list            # SessionPlan, taken in order
    stagger: np.ndarray           # first-turn offsets (s), per session
    endless: bool                 # turn = null: decode every step

    @property
    def user_sizes(self) -> list:
        return sorted({u for s in self.sessions + self.replacements
                       for u in s.user})

    @property
    def prompt_sizes(self) -> list:
        return sorted({s.first_prompt
                       for s in self.sessions + self.replacements})

    def replacement_prompt_sizes(self) -> list:
        return sorted({s.first_prompt for s in self.replacements})


def _rows(mix: dict, rng, n: int) -> list:
    prompts = draw(mix["first_prompt"], rng, n)
    turn = mix.get("turn")
    rows = []
    for i in range(n):
        row = SessionPlan(int(prompts[i]))
        if turn is not None:
            k = int(mix["turns_per_session"])
            row.user = [int(x) for x in draw(turn["user"], rng, k)]
            row.answer = [int(x) for x in draw(turn["answer"], rng, k)]
            row.think = [float(x) for x in draw(turn["think_s"], rng, k)]
        rows.append(row)
    return rows


def make_plan(mix: dict, seed: int) -> Plan:
    assert mix["generator"] == "sessions", mix["generator"]
    sizes = np.random.default_rng(int(mix["sizes_seed"]))
    n = int(mix["sessions"])
    rows = _rows(mix, sizes, n)
    spares = _rows(mix, sizes, int(mix.get("replacements", 0)))
    stagger = sizes.uniform(0.0, float(mix.get("first_turn_stagger_s", 0.0)),
                            n)
    run = np.random.default_rng(int(seed))
    rows = [rows[i] for i in run.permutation(n)]
    spares = [spares[i] for i in run.permutation(len(spares))]
    stagger = stagger[run.permutation(n)]
    return Plan(slots=int(mix["slots"]), capacity=int(mix["capacity_tokens"]),
                sessions=rows, replacements=spares, stagger=stagger,
                endless=mix.get("turn") is None)
