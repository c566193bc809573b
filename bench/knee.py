#!/usr/bin/env python3
"""Find a cell's knee: the highest open-loop rate it sustains.

    python3 bench/knee.py --workload <cell> --seed <n> --seconds 4 \\
        --rates 200,300,400

One set-up, then one window per rate (lowest first), each on its own
stream of the seed. A rate is sustained when the window's requests are all
answered within it plus a tenth of a second, and the latency of its last
quarter is not above twice that of its first quarter (no growing backlog).
The knee goes into the configuration's ``knee_qps`` by hand; the cells
offer ``rate_of_knee`` of it. Prints one JSON line per rate.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    import numpy as np

    from bench import harness

    layout = harness.Layout()
    cell = layout.cell(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("knee.py: no TPU; nothing was run", file=sys.stderr)
        return 2
    harness.configure_caches()
    rig = harness.setup(cell, args.seed)
    harness.log(f"setup {time.perf_counter() - T_START:.3f} s")
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            win = harness.run_window(rig, args.seconds, rate=rate, draw=i + 1)
            recs = sorted(win.recs, key=lambda r: r.due)
            lat = np.asarray([(r.done - r.due) * 1e3 if r.done else np.inf
                              for r in recs])
            q = max(len(lat) // 4, 1)
            end = win.t0 + win.seconds + 0.1
            kept = sum(1 for r in recs if r.done and r.done <= end)
            first, last = np.median(lat[:q]), np.median(lat[-q:])
            ok = kept == len(recs) and last <= 2 * first
            print(json.dumps({
                "rate": rate, "requests": len(recs), "answered_in_time": kept,
                "p50_ms": float(np.median(lat)),
                "p99_ms": float(np.quantile(lat, 0.99)),
                "first_quarter_p50_ms": float(first),
                "last_quarter_p50_ms": float(last),
                "batches": harness.Run(cell, win, 0, {}).delta(
                    "sched", "batches"),
                "sustained": bool(ok)}), flush=True)
    finally:
        harness.teardown(rig)
    return 0


if __name__ == "__main__":
    sys.exit(main())
