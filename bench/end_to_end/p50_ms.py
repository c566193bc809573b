"""Median latency of every request of the window, from when it was due
(open loop: its arrival time; closed loop: when its caller got the previous
answer) to when its answer came, so a late generator or a queue counts."""
from bench.harness import quantile

UNIT = "ms"


def read(run):
    lat = run.latencies_ms()
    return quantile(lat, 0.5) if lat.size else None
