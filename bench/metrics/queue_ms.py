"""Mean time a request waited in the scheduler before its batch started:
the ``queue`` span the scheduler prepends to every response's trace."""
LAYER = "front end (service/scheduler.py)"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "p50_ms"


def read(run):
    q = [s["ms"] for r in run.responses() for s in r.trace
         if s["phase"] == "queue"]
    return sum(q) / len(q) if q else None
