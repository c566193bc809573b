"""Host time the engine spends per batch outside the device call: the
``pin``, ``resolve``, ``plan``, ``candidates`` (query band keys) and
``finalize`` spans. Spans are per-request shares of their batch, so their
sum over all requests is the sum over batches."""
LAYER = "engine (service/engine.py)"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "p50_ms"

HOST = ("pin", "resolve", "plan", "candidates", "finalize")


def read(run):
    total = sum(s["ms"] for r in run.responses() for s in r.trace
                if s["phase"] in HOST)
    batches = run.delta("sched", "batches")
    return total / batches if batches else None
