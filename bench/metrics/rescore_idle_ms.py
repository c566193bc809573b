"""Device-idle time per batch while the executor's exact re-rank holds
the host: idle time of the traced window inside ``freyja.executor.rescore``
spans (the blocking copy of the int8 scan's ids, the host gather of their
fp32 rows, the put and the re-rank's dispatch; ``exec/executor.py``).
Batches are counted as pipeline modules, as ``program_device_ms`` counts
them. None where the trace holds no such span."""
LAYER = "executor host path of the exact re-rank (exec/executor.py)"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "p50_ms"

SPAN = "freyja.executor.rescore"


def idle_inside(idle: list, spans: list) -> float:
    """Seconds of the ``idle`` (start, end) intervals (ns, disjoint,
    sorted) covered by the union of ``spans`` (events)."""
    cover, total = [], 0.0
    for s in sorted(spans, key=lambda e: e.start):
        if cover and s.start <= cover[-1][1]:
            cover[-1][1] = max(cover[-1][1], s.end)
        else:
            cover.append([s.start, s.end])
    j = 0
    for a, b in idle:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            total += min(b, cover[k][1]) - max(a, cover[k][0])
            k += 1
    return total / 1e9


def read(run):
    from bench import program_spans

    trace = program_spans.of(run)
    if trace is None:
        return None
    mine = [e for e in program_spans.spans(trace) if e.name == SPAN]
    n = program_spans.batches(trace)
    if not mine or not n:
        return None
    idle = idle_inside(program_spans.idle_intervals(trace), mine)
    return idle / n * 1e3
