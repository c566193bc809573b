#!/usr/bin/env python3
"""Run one cell of the FREYJA join-discovery benchmark on this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``. The run builds the
cell's lake and model from the seed, ingests and serves it on the chips of
this machine through the program's scheduler, measures for ``--seconds``,
then compares the window's answers with the plain reference. The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced, ``breakdown``;
its last key, ``checks``, holds each number compared with its limit, which
also end standard error. Without a TPU, or with fewer chips than the cell
asks for, it exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from bench import harness

    layout = harness.Layout()
    try:
        cell = layout.cell(args.workload)
    except KeyError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"sees {len(devs)} {devs[0].platform} device(s); nothing was "
              f"run", file=sys.stderr)
        return 2
    harness.configure_caches()
    out = harness.run(layout, args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
