"""Reduction of a profiler trace to the numbers the metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
what the benchmark uses: every event on a device plane (``/device:...``,
with its line, name, start, duration and stats) and the benchmark's own
host spans (``bench.*`` ``TraceAnnotation``\\ s, on the host plane). Both
are on the profiler's one clock, in nanoseconds. ``Trace.to_json`` /
``from_json`` store that event list, so a recorded trace can be checked
in and reduced again by the tests without a chip.

The traced window is the ``bench.window`` host span. Busy time is the
union of the device's op intervals inside it, averaged over the devices
that ran any; idle gaps are the spaces between those intervals, each
named by the innermost ``bench.*`` host span active at its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


_LAYOUT = re.compile(r"\{[^{}]*\}")
_KIND = re.compile(r"^(.*?)\s+([\w.-]+)\(")


def short_name(op: str, limit: int = 160) -> str:
    """An HLO op event's name without its operands and layouts:
    ``%sort.3 = (f32[4096,5821], s32[4096,5821]) sort``."""
    lhs, eq, rhs = op.partition(" = ")
    if not eq:
        return op[:limit]
    m = _KIND.match(_LAYOUT.sub("", rhs))
    out = f"{lhs} = {m.group(1)} {m.group(2)}" if m else lhs
    return out[:limit]


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start: float          # ns
    dur: float            # ns
    stats: dict

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    device: list          # Event on device planes
    host: list            # bench.* host spans

    # -- storage ----------------------------------------------------------

    @staticmethod
    def load(trace_dir: str) -> "Trace":
        from jax.profiler import ProfileData

        paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        device, host = [], []
        for path in paths:
            pd = ProfileData.from_file(path)
            for plane in pd.planes:
                is_dev = plane.name.startswith("/device:")
                is_host = plane.name.startswith("/host:")
                if not (is_dev or is_host):
                    continue
                for line in plane.lines:
                    for e in line.events:
                        if is_host and not e.name.startswith("bench."):
                            continue
                        ev = Event(plane.name, line.name, e.name,
                                   float(e.start_ns), float(e.duration_ns),
                                   {k: v for k, v in e.stats
                                    if isinstance(v, (int, float, str))})
                        (device if is_dev else host).append(ev)
        return Trace(device=device, host=host)

    def to_json(self) -> dict:
        row = lambda e: [e.plane, e.line, e.name, e.start, e.dur, e.stats]
        return {"device": [row(e) for e in self.device],
                "host": [row(e) for e in self.host]}

    @staticmethod
    def from_json(d: dict) -> "Trace":
        return Trace(device=[Event(*r) for r in d["device"]],
                     host=[Event(*r) for r in d["host"]])

    # -- reduction ----------------------------------------------------------

    def window(self) -> tuple[float, float]:
        spans = [e for e in self.host if e.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace has no {WINDOW_SPAN} span")
        w = max(spans, key=lambda e: e.dur)
        return w.start, w.end

    def ops(self) -> list:
        """Device op events inside the window (module events where a
        device plane has no op line)."""
        t0, t1 = self.window()
        planes = {e.plane for e in self.device}
        out = []
        for p in sorted(planes):
            evs = [e for e in self.device if e.plane == p]
            line = OPS_LINE if any(e.line == OPS_LINE for e in evs) \
                else MODULES_LINE
            out += [e for e in evs if e.line == line
                    and e.end > t0 and e.start < t1]
        return out

    def modules(self) -> list:
        t0, t1 = self.window()
        return [e for e in self.device if e.line == MODULES_LINE
                and e.end > t0 and e.start < t1]

    def busy_intervals(self, plane: str) -> list[tuple[float, float]]:
        t0, t1 = self.window()
        iv = sorted((max(e.start, t0), min(e.end, t1)) for e in self.ops()
                    if e.plane == plane)
        merged: list[list[float]] = []
        for a, b in iv:
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        return [(a, b) for a, b in merged if b > a]

    def device_planes(self) -> list[str]:
        return sorted({e.plane for e in self.ops()})

    def busy_s(self) -> float:
        planes = self.device_planes()
        if not planes:
            return 0.0
        tot = sum(b - a for p in planes for a, b in self.busy_intervals(p))
        return tot / len(planes) / 1e9

    def window_s(self) -> float:
        t0, t1 = self.window()
        return (t1 - t0) / 1e9

    def host_activity(self, t: float) -> str:
        """Innermost bench.* host span covering time ``t``."""
        live = [e for e in self.host if e.start <= t <= e.end
                and e.name != WINDOW_SPAN]
        if not live:
            return "idle (no bench span)"
        return min(live, key=lambda e: e.dur).name

    def idle_gaps(self, top: int = 10) -> list:
        t0, t1 = self.window()
        planes = self.device_planes()
        if not planes:
            return [["no device op", (t1 - t0) / 1e9]]
        busy = self.busy_intervals(planes[0])
        gaps, prev = [], t0
        for a, b in busy + [(t1, t1)]:
            if a > prev:
                gaps.append((a - prev, prev))
            prev = max(prev, b)
        gaps.sort(reverse=True)
        return [[self.host_activity(s + d / 2), d / 1e9]
                for d, s in gaps[:top]]

    def top_ops(self, top: int = 10) -> list:
        """Ops by self time in the window (a ``while`` op's body ops run
        inside it on the same line: each second is counted once), named
        by ``short_name``."""
        t0, t1 = self.window()
        tot: dict[str, float] = {}
        for p in self.device_planes():
            stack: list[list] = []          # [event, self time]
            evs = sorted((e for e in self.ops() if e.plane == p),
                         key=lambda e: (e.start, -e.dur))
            for e in evs + [None]:
                while stack and (e is None or e.start >= stack[-1][0].end):
                    done, own = stack.pop()
                    tot[done.name] = tot.get(done.name, 0.0) + own
                if e is None:
                    break
                own = min(e.end, t1) - max(e.start, t0)
                if stack:
                    stack[-1][1] -= own
                stack.append([e, own])
        n = max(len(self.device_planes()), 1)
        short: dict[str, float] = {}
        for name, s in tot.items():
            key = short_name(name)
            short[key] = short.get(key, 0.0) + s
        rows = sorted(short.items(), key=lambda kv: -kv[1])[:top]
        return [[name, s / n / 1e9] for name, s in rows]


def save_json(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)


def load_json(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
