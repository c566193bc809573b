"""Live queries over padded batch rows: the scheduler's formed-batch size
histogram over the window, each batch padded up to the smallest bucket of
the ladder that holds it."""
LAYER = "front end (service/scheduler.py)"
SOURCE = "program_counter"
UNIT = "ratio"
MOVES = "qps"


def read(run):
    w = run.window
    before = w.sched_before["batch_size_hist"]
    buckets = sorted(w.sched_after["buckets"])
    live = rows = 0
    for n, c in w.sched_after["batch_size_hist"].items():
        c -= before.get(n, 0)
        if c <= 0:
            continue
        pad = next((b for b in buckets if b >= int(n)), int(n))
        live += int(n) * c
        rows += pad * c
    return live / rows if rows else None
