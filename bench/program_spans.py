"""The program's own profiler spans and stage scopes in a traced run.

The program opens ``freyja.*`` host spans around its scheduler, engine
and executor work, and traces its pipelines under the stage scopes
``candidates``, ``score`` and ``merge`` (``src/repro/exec/spans.py``,
``src/repro/exec/stages.py``). ``tracing.Trace.load`` keeps only the
benchmark's ``bench.*`` spans and no op metadata, so this module reads the
same ``.xplane.pb`` again:

* host events named ``freyja.*`` or ``bench.window``, with their stats
  (the spans' arguments);
* device events, each with the ``tf_op`` stat of its op's metadata (the
  op's ``op_name``: ``jit(_local_pruned)/score/while/...``) where it has
  one. ``jax.profiler.ProfileData`` does not expose event metadata, so the
  metadata is decoded from the protobuf wire format here (``XSpace`` ->
  ``XPlane.event_metadata`` / ``stat_metadata``), and an op event is
  matched to its metadata by name within its program (the module event
  that holds it).

The result is a ``tracing.Trace`` and reduces with its methods. ``of(run)``
loads it once per run and every reader shares it; a program without the
spans or scopes gives readers nothing to read, and they return None.

    python3 bench/program_spans.py <trace dir> [--fixture <out.json>]

prints the device-idle time of the traced window by innermost ``freyja.*``
span and, with ``--fixture``, writes a short window of the trace as JSON.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import tracing  # noqa: E402
# batches are counted as program_device_ms counts them: pipeline modules
from bench.metrics.program_device_ms import PIPELINES  # noqa: E402

PREFIX = "freyja."
HOST_PATH = ("freyja.engine.", "freyja.executor.")
NO_SPAN = "no freyja span"
FIXTURE_SECONDS = 0.3      # length of the window ``--fixture`` writes
LONG_GAP_MS = 50.0         # ``main`` lists each idle gap at least this long
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


# ---------------------------------------------------------------------------
# protobuf wire format: just enough of XSpace for the ops' metadata
# ---------------------------------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i=0, end=None):
    """(field number, wire type, value) of each field in ``buf[i:end]``;
    a length-delimited value is a memoryview of its bytes."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 2:
            n, i = _varint(buf, i)
            val, i = buf[i:i + n], i + n
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        yield num, wt, val


def _text(b) -> str:
    return bytes(b).decode("utf-8", "replace")


def op_metadata(path: str) -> dict:
    """{(plane name, program id or "", op name): ``tf_op``} for every
    device plane's event metadata in the ``.xplane.pb`` at ``path``.
    XPlane: name 2, lines 3 (skipped), event_metadata 4, stat_metadata 5;
    XEventMetadata: name 2, stats 5; XStat: metadata_id 1, uint64_value
    3, int64_value 4, str_value 5, ref_value 7 (a stat metadata id whose
    name is the value); XStatMetadata: id 1, name 2."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for num, _, plane in _fields(buf):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pn, _, pv in _fields(plane):
            if pn == 2:
                name = _text(pv)
            elif pn == 4:                  # map entry: key 1, value 2
                for en, _, ev in _fields(pv):
                    if en == 2:
                        events.append(ev)
            elif pn == 5:
                for en, _, ev in _fields(pv):
                    if en == 2:
                        sid, sname = 0, ""
                        for sn, _, sv in _fields(ev):
                            if sn == 1:
                                sid = sv
                            elif sn == 2:
                                sname = _text(sv)
                        stat_names[sid] = sname
        if not name.startswith("/device:"):
            continue
        for ev in events:
            ename, stats = "", {}
            for fn, _, fv in _fields(ev):
                if fn == 2:
                    ename = _text(fv)
                elif fn == 5:
                    sid, val = 0, None
                    for sn, _, sv in _fields(fv):
                        if sn == 1:
                            sid = sv
                        elif sn in (3, 4):         # uint64 / int64
                            val = str(sv)
                        elif sn == 5:
                            val = _text(sv)
                        elif sn == 7:
                            val = ("ref", sv)
                    stats[sid] = val
            by_name = {stat_names.get(k): v for k, v in stats.items()}
            value = by_name.get("tf_op")
            if isinstance(value, tuple):
                value = stat_names.get(value[1])
            if value:
                prog = by_name.get("program_id")
                if isinstance(prog, tuple):
                    prog = stat_names.get(prog[1])
                out[(name, str(prog or ""), ename)] = value
    return out


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------

def _label_ops(device: list, meta: dict) -> None:
    """Set ``stats["tf_op"]`` on each device op event whose metadata has
    one, matched within the program of the module event holding it."""
    for plane in {e.plane for e in device}:
        mods = sorted((e for e in device if e.plane == plane
                       and e.line == tracing.MODULES_LINE),
                      key=lambda e: e.start)
        starts = [m.start for m in mods]
        for e in device:
            if e.plane != plane or e.line == tracing.MODULES_LINE:
                continue
            i = bisect.bisect_right(starts, e.start) - 1
            prog = ""
            if i >= 0 and e.start <= mods[i].end:
                m = _PROGRAM_ID.search(mods[i].name)
                prog = m.group(1) if m else ""
            op = meta.get((plane, prog, e.name))
            if op is None:
                op = meta.get((plane, "", e.name))
            if op is not None:
                e.stats["tf_op"] = op


def load(trace_dir: str) -> tracing.Trace:
    """Device events labelled with their ``tf_op`` and the ``freyja.*``
    and ``bench.window`` host spans of the trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    device, host = [], []
    for path in paths:
        meta = op_metadata(path)
        mine = []
        for plane in ProfileData.from_file(path).planes:
            is_dev = plane.name.startswith("/device:")
            if not (is_dev or plane.name.startswith("/host:")):
                continue
            for line in plane.lines:
                for e in line.events:
                    if not is_dev and not (e.name.startswith(PREFIX)
                                           or e.name == tracing.WINDOW_SPAN):
                        continue
                    ev = tracing.Event(
                        plane.name, line.name, e.name, float(e.start_ns),
                        float(e.duration_ns),
                        {k: v for k, v in e.stats
                         if isinstance(v, (int, float, str))})
                    (mine if is_dev else host).append(ev)
        _label_ops(mine, meta)
        device += mine
    return tracing.Trace(device=device, host=host)


def of(run) -> tracing.Trace | None:
    """The program trace of ``run``'s traced window, loaded once per run
    and shared by every reader; None for an untraced run."""
    if getattr(run, "trace", None) is None:
        return None
    cached = getattr(run, "program_trace", None)
    if cached is None:
        from bench import harness

        cached = load(os.path.join(harness.WORK, "trace"))
        run.program_trace = cached
    return cached


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def batches(trace: tracing.Trace) -> int:
    """Pipeline module events in the window."""
    return sum(1 for e in trace.modules()
               if any(p in e.name for p in PIPELINES))


def spans(trace: tracing.Trace) -> list:
    """The ``freyja.*`` host spans."""
    return [e for e in trace.host if e.name.startswith(PREFIX)]


def idle_intervals(trace: tracing.Trace) -> list:
    """Device-idle intervals of the window, on the first device plane (ns)."""
    t0, t1 = trace.window()
    planes = trace.device_planes()
    if not planes:
        return [(t0, t1)]
    out, prev = [], t0
    for a, b in trace.busy_intervals(planes[0]) + [(t1, t1)]:
        if a > prev:
            out.append((prev, a))
        prev = max(prev, b)
    return out


def idle_split(trace: tracing.Trace) -> tuple[dict, float]:
    """Device-idle seconds of the window by innermost ``freyja.*`` span
    open at each instant (``NO_SPAN`` where none is open), and the idle
    seconds during which any ``freyja.engine.*`` / ``freyja.executor.*``
    span is open. Exact: each idle interval is cut at every span
    boundary inside it."""
    marks = []
    for s in spans(trace):
        marks.append((s.start, 1, s))
        marks.append((s.end, 0, s))
    marks.sort(key=lambda m: (m[0], m[1]))
    by_span: dict[str, float] = {}
    host_path = 0.0
    active: dict[int, tracing.Event] = {}
    j = 0

    def charge(d):
        nonlocal host_path
        if d <= 0:
            return
        name = (min(active.values(), key=lambda e: e.dur).name
                if active else NO_SPAN)
        by_span[name] = by_span.get(name, 0.0) + d / 1e9
        if any(e.name.startswith(HOST_PATH) for e in active.values()):
            host_path += d / 1e9

    def step(m):
        if m[1]:
            active[id(m[2])] = m[2]
        else:
            active.pop(id(m[2]), None)

    for a, b in idle_intervals(trace):
        while j < len(marks) and marks[j][0] <= a:
            step(marks[j])
            j += 1
        cur = a
        while j < len(marks) and marks[j][0] < b:
            charge(marks[j][0] - cur)
            cur = marks[j][0]
            step(marks[j])
            j += 1
        charge(b - cur)
    return by_span, host_path


def scope_self_seconds(trace: tracing.Trace, scope: str) -> float | None:
    """Device self time in the window of the ops whose ``tf_op`` path
    holds ``scope`` (a ``while`` op's body ops run inside it on the same
    line: each second is counted once, at the innermost op); None where
    no op of the window is labelled with it."""
    t0, t1 = trace.window()
    total, seen = 0.0, False
    for p in trace.device_planes():
        stack: list[list] = []            # [event, self time]
        evs = sorted((e for e in trace.ops() if e.plane == p),
                     key=lambda e: (e.start, -e.dur))
        for e in evs + [None]:
            while stack and (e is None or e.start >= stack[-1][0].end):
                done, own = stack.pop()
                if scope in done.stats.get("tf_op", "").rstrip(":").split("/"):
                    total += own
                    seen = True
            if e is None:
                break
            own = min(e.end, t1) - max(e.start, t0)
            if stack:
                stack[-1][1] -= own
            stack.append([e, own])
    n = max(len(trace.device_planes()), 1)
    return total / n / 1e9 if seen else None


def cut(trace: tracing.Trace, start: float, seconds: float) -> tracing.Trace:
    """``seconds`` of ``trace`` from ``start`` (ns), with a ``bench.window``
    span of its own; device op names shortened (``tracing.short_name``)."""
    t0, t1 = start, start + seconds * 1e9
    dev = [tracing.Event(e.plane, e.line, tracing.short_name(e.name),
                         e.start, e.dur,
                         {k: v for k, v in e.stats.items() if k == "tf_op"})
           for e in trace.device if e.end > t0 and e.start < t1]
    host = [e for e in spans(trace) if e.end > t0 and e.start < t1]
    window = tracing.Event(host[0].plane if host else "/host:CPU", "bench",
                           tracing.WINDOW_SPAN, t0, t1 - t0, {})
    return tracing.Trace(device=dev, host=host + [window])


def innermost(trace: tracing.Trace, t: float) -> str:
    """Innermost ``freyja.*`` span open at ``t`` (ns)."""
    live = [e for e in spans(trace) if e.start <= t <= e.end]
    return min(live, key=lambda e: e.dur).name if live else NO_SPAN


def main(argv=None) -> int:
    import argparse
    import time

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--fixture", help="write a short window here as JSON")
    args = ap.parse_args(argv)
    t = time.perf_counter()
    trace = load(args.trace_dir)
    print(f"loaded in {time.perf_counter() - t:.2f} s")
    t0, t1 = trace.window()
    by_span, host_path = idle_split(trace)
    idle = sum(by_span.values())
    n = batches(trace)
    print(f"window {(t1 - t0) / 1e9:.3f} s, busy {trace.busy_s():.3f} s, "
          f"idle {idle:.4f} s, {n} batches; idle under the engine or "
          f"executor host path {host_path:.4f} s "
          f"({host_path / max(n, 1) * 1e3:.3f} ms per batch)")
    for name, s in sorted(by_span.items(), key=lambda kv: -kv[1]):
        print(f"  {name:32s} {s:10.4f} s  {100 * s / max(idle, 1e-12):6.2f}%")
    for a, b in idle_intervals(trace):
        if b - a >= LONG_GAP_MS * 1e6:
            print(f"  gap {(b - a) / 1e6:9.3f} ms at +{(a - t0) / 1e9:.3f} s "
                  f"under {innermost(trace, (a + b) / 2)}")
    score = scope_self_seconds(trace, "score")
    if score is not None:
        print(f"score scope self time {score:.4f} s, "
              f"{score / max(n, 1) * 1e3:.3f} ms per batch")
    if args.fixture:
        mid = (t0 + t1) / 2
        tracing.save_json(cut(trace, mid, FIXTURE_SECONDS), args.fixture)
    return 0


if __name__ == "__main__":
    sys.exit(main())
