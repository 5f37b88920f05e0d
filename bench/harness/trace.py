"""Profiler trace -> events -> busy time, idle gaps, program time.

`load` reads the `.xplane.pb` the JAX profiler wrote and keeps the
device planes' module and op lines and the host's `bench.*` spans, as
(plane, line, name, start_ns, dur_ns) tuples; `Trace` reduces them.  A
test checks the reduction on a small recorded trace
(`bench/tests/fixtures/`), so every later run computes the same numbers
the same way.
"""

from __future__ import annotations

import glob
import json
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def load(trace_dir: str) -> list:
    """Events of the one xplane file under `trace_dir`."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    assert len(paths) == 1, f"expected one trace file, found {paths}"
    pd = ProfileData.from_file(paths[0])
    out = []
    for plane in pd.planes:
        dev = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if dev and line.name not in (MODULE_LINE, OP_LINE):
                continue
            for ev in line.events:
                if not dev and not ev.name.startswith(SPAN_PREFIX):
                    continue
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


def save(events: list, path: str) -> None:
    with open(path, "w") as f:
        json.dump({"events": events}, f)


def read(path: str) -> list:
    with open(path) as f:
        return [tuple(e) for e in json.load(f)["events"]]


def union(intervals: list) -> list:
    """Merge [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def program_name(name: str) -> str:
    """A module event's program name without its run id suffix."""
    return re.sub(r"\(\d+\)$", "", name)


class Trace:
    def __init__(self, events: list):
        self.events = events
        spans = [e for e in events if e[2] == WINDOW_SPAN]
        assert spans, "no bench.window span in the trace"
        w = max(spans, key=lambda e: e[4])
        self.t0, self.t1 = w[3], w[3] + w[4]
        self.devices = sorted({e[0] for e in events
                               if e[0].startswith(DEVICE_PREFIX)})

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _clip(self, a: float, b: float):
        return max(a, self.t0), min(b, self.t1)

    def _device_events(self, plane: str, line: str) -> list:
        return [e for e in self.events if e[0] == plane and e[1] == line]

    def busy(self, plane: str) -> list:
        """Merged busy intervals of one device inside the window: ops,
        or modules where the trace has no op line."""
        evs = (self._device_events(plane, OP_LINE)
               or self._device_events(plane, MODULE_LINE))
        iv = []
        for e in evs:
            a, b = self._clip(e[3], e[3] + e[4])
            if b > a:
                iv.append((a, b))
        return union(iv)

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = sum(b - a for d in self.devices for a, b in self.busy(d))
        return tot * 1e-9 / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def program_s(self, patterns: list) -> float:
        """Device seconds of the programs whose name matches any pattern,
        inside the window, summed over devices."""
        rx = [re.compile(p) for p in patterns]
        tot = 0.0
        for e in self.events:
            if e[1] != MODULE_LINE or not e[0].startswith(DEVICE_PREFIX):
                continue
            if any(r.search(e[2]) for r in rx):
                a, b = self._clip(e[3], e[3] + e[4])
                tot += max(0.0, b - a)
        return tot * 1e-9

    def spans(self, name: str) -> list:
        """(start_ns, dur_ns) of the host spans called `name` that start
        inside the window."""
        return [(e[3], e[4]) for e in self.events
                if e[2] == name and self.t0 <= e[3] < self.t1]

    def top_programs(self, k: int = 10) -> list:
        tot = defaultdict(float)
        for e in self.events:
            if e[1] == MODULE_LINE and e[0].startswith(DEVICE_PREFIX):
                a, b = self._clip(e[3], e[3] + e[4])
                tot[program_name(e[2])] += max(0.0, b - a) * 1e-9
        n = max(1, len(self.devices))
        return sorted(([p, s / n] for p, s in tot.items()),
                      key=lambda x: -x[1])[:k]

    def idle_gaps(self, k: int = 10) -> list:
        """Idle seconds of the first device, summed by the innermost
        bench.* host span that covers each gap's midpoint."""
        if not self.devices:
            return []
        busy = self.busy(self.devices[0])
        gaps, prev = [], self.t0
        for a, b in busy:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        if prev < self.t1:
            gaps.append((prev, self.t1))
        spans = sorted((e for e in self.events
                        if e[2].startswith(SPAN_PREFIX)
                        and e[2] != WINDOW_SPAN
                        and not e[0].startswith(DEVICE_PREFIX)),
                       key=lambda e: (e[3], -e[4]))
        # host spans nest on the driving thread: a stack of open spans
        # swept in time order gives the innermost one at each midpoint
        tot = defaultdict(float)
        stack, j = [], 0
        for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
            mid = 0.5 * (a + b)
            while j < len(spans) and spans[j][3] <= mid:
                while stack and stack[-1][3] + stack[-1][4] <= spans[j][3]:
                    stack.pop()
                stack.append(spans[j])
                j += 1
            while stack and stack[-1][3] + stack[-1][4] <= mid:
                stack.pop()
            who = stack[-1][2] if stack else "none"
            tot["host:" + who] += (b - a) * 1e-9
        return sorted(([n, s] for n, s in tot.items()),
                      key=lambda x: -x[1])[:k]
