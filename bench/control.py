#!/usr/bin/env python3
"""Read the two ends of a cell's comparison on the chip.

    python3 bench/control.py --workload <cell> --control 11,12,13 \\
        --program 21,22,23 --faults 31,32,33 --seconds 5

* ``--control``: the plain reference put in the program's place one
  precision below the configuration's float32: the exact scan with
  profiles, distances and tree thresholds in bfloat16. For each seed it
  builds the cell's lake, samples as many query columns as a run of
  ``run_seconds`` samples, answers them with the control and judges those
  answers against the float32 reference exactly as ``check.judge`` judges
  the program's.
* ``--program``: sound runs of the cell, each with a window of
  ``--seconds`` at the cell's own load: the lower readings.
* ``--faults``: the same runs with a fault planted in the program's merge
  (``FAULTS``): the best candidate dropped, or an approximate top k.

Prints one JSON line per reading, with every number a run compares. All
of it runs in this one process; the benchmark's own runs never run this.
"""
import argparse
import contextlib
import dataclasses
import functools
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _drop_best(merge, scores, cids, k: int):
    """The top ``k + 1`` with the first left out: true scores, in order."""
    sc, ids = merge(scores, cids, k + 1)
    return sc[:, 1:], ids[:, 1:]


def _approx_top_k(merge, scores, cids, k: int):
    """``lax.approx_max_k`` in the place of the exact top k."""
    import jax
    import jax.numpy as jnp

    kl = min(k, scores.shape[1])
    sc, pos = jax.lax.approx_max_k(scores, kl, recall_target=0.95)
    if cids.ndim == 1:
        cids = jnp.broadcast_to(cids[None], scores.shape)
    ids = jnp.take_along_axis(cids, pos, axis=1)
    return sc, jnp.where(jnp.isfinite(sc), ids, -1)


FAULTS = {"drop_best": _drop_best, "approx_top_k": _approx_top_k}


@contextlib.contextmanager
def planted(fault: str, cache_dir: str):
    """The program's merge replaced by ``FAULTS[fault]`` while inside.
    Its compiled programs go to ``cache_dir``, so no faulty executable
    reaches the benchmark's own cache, and JAX's in-process caches are
    cleared on the way in and out, so nothing traced before is reused."""
    import jax

    from bench import harness
    from repro.exec import stages

    merge, engine_config = stages.merge_topk, harness.engine_config

    def config(cell, buckets):
        return dataclasses.replace(engine_config(cell, buckets),
                                   executable_cache_dir=cache_dir)

    stages.merge_topk = functools.partial(FAULTS[fault], merge)
    harness.engine_config = config
    jax.clear_caches()
    try:
        yield
    finally:
        stages.merge_topk, harness.engine_config = merge, engine_config
        jax.clear_caches()


def control_readings(cell, seed: int, seconds: float, trees) -> dict:
    import jax.numpy as jnp

    from bench import check, harness, reference
    from bench import lake as lakegen
    from bench import traffic as trafficgen

    cfg, tr = cell.config, cell.traffic
    lake = lakegen.generate(lakegen.LakeSpec.from_config(cfg["lake"]), seed)
    rng = harness.rng_for(seed, 0x7AFF, 0)
    ids = trafficgen.pool(tr, lake, rng)
    if tr["loop"] == "open":
        rate = float(cfg["knee_qps"]) * float(tr.get("rate_of_knee", 1.0))
        qids = trafficgen.open_schedule(tr, rate, seconds, ids, rng).qids
    else:                       # as many requests as the knee rate makes
        stream = trafficgen.stream(tr, ids, rng)
        qids = [next(stream)
                for _ in range(int(float(cfg["knee_qps"]) * seconds))]
    qs = check.sample(qids, int(cfg["reference"]["sample_queries"]), seed)
    block = int(cfg["reference"]["block_columns"])
    t = time.perf_counter()
    exact = reference.ExactScan(lake, trees, block=block)
    scores = exact.scores(qs)
    low = reference.ExactScan(lake, trees, dtype=jnp.bfloat16, block=block,
                              like=exact)
    k = int(cfg["widths"]["k"])
    v = check.judge(check.control_answers(low, qs, k), scores, qs, k,
                    check.sure_partners(lake, qs, cfg["widths"]))
    v.update(kind="control", seed=seed, seconds=time.perf_counter() - t)
    return v


def program_readings(layout, name: str, seed: int, seconds: float,
                     kind: str) -> dict:
    from bench import harness

    out = harness.run(layout, name, seed, seconds, False,
                      time.perf_counter())
    return {"kind": kind, "seed": seed, "correct": out["correct"],
            "attempted": out["attempted"],
            "recall": out["metrics"].get("recall_at_10", {}).get("value"),
            **{k: c["value"] for k, c in out["checks"].items()}}


def seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--program", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from bench import harness
    from bench import model as modelcache

    layout = harness.Layout()
    cell = layout.cell(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control.py: no TPU; nothing was run", file=sys.stderr)
        return 2
    harness.configure_caches()
    _, trees, _ = modelcache.load_or_train(cell.config["model"],
                                           harness.CACHE, harness.SRC,
                                           log=harness.log)
    window = float(layout.spec["run_seconds"])
    os.makedirs(harness.WORK, exist_ok=True)
    for s in seeds(args.control):
        print(json.dumps(control_readings(cell, s, window, trees)),
              flush=True)
    for s in seeds(args.program):
        print(json.dumps(program_readings(layout, args.workload, s,
                                          args.seconds, "program")),
              flush=True)
    for fault in FAULTS if args.faults else ():
        with tempfile.TemporaryDirectory(dir=harness.WORK) as d, \
                planted(fault, d):
            for s in seeds(args.faults):
                print(json.dumps(program_readings(
                    layout, args.workload, s, args.seconds, fault)),
                    flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
