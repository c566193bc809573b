"""Device time per batch of the executor's compiled programs (the
candidate/score/merge pipeline and the exact re-rank), from the module
events of the traced window. Batches are counted as pipeline modules."""
LAYER = "executor (exec/executor.py)"
SOURCE = "device_trace"
UNIT = "ms"
MOVES = "p50_ms"

PIPELINES = ("_local_tiered", "_local_pruned", "_local_all")
PROGRAMS = PIPELINES + ("_rescore_exact",)


def read(run):
    if run.trace is None:
        return None
    mods = run.trace.modules()
    batches = sum(1 for e in mods if any(p in e.name for p in PIPELINES))
    busy = sum(e.dur for e in mods if any(p in e.name for p in PROGRAMS))
    return busy / batches / 1e6 if batches else None
