"""Share of their roofline the LSH probe kernels reach: the least time at
the device's peaks of every probe call in the traced window
(``roofline/lsh_probe.py``, from the call shapes) over the device time
those calls took. Any implementation of the same probe reads the same
work."""
LAYER = "kernels (kernels/lsh_probe.py)"
SOURCE = "device_trace"
UNIT = "%"
MOVES = "p50_ms"


def read(run):
    if run.trace is None or not run.peaks:
        return None
    from bench.roofline import lsh_probe

    t0, t1 = run.trace.window()
    calls = [e for e in run.trace.ops()
             if lsh_probe.match(e.name) and t0 <= e.start and e.end <= t1]
    took = sum(e.dur for e in calls) / 1e9
    if not calls or took <= 0:
        return None
    least = sum(lsh_probe.least_seconds(e.name, run.peaks) for e in calls)
    return 100.0 * least / took
