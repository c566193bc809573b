"""Share of the traced window in which no operation ran on the device:
1 - busy union / window (``tracing.Trace``)."""
LAYER = "device (TPU)"
SOURCE = "device_trace"
UNIT = "%"
MOVES = "qps"


def read(run):
    if run.trace is None:
        return None
    w = run.trace.window_s()
    return 100.0 * (1.0 - run.trace.busy_s() / w) if w > 0 else None
