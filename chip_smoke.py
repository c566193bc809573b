#!/usr/bin/env python3
"""Smoke test of FREYJA's ingest and serving path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # only the paths that span four chips

One chip: a planted lake of ``--columns`` (10^5) columns is generated from
``--seed`` (``core.lakegen.generate_scaled_lake`` at the published widths:
21 numeric profile slots, 11 word hashes, 128 permutations / 64 bands, a
16-lane digest), ingested with ``CatalogStore.add_batch`` (profiling and
MinHash run on the chip), opened with ``DiscoveryEngine.from_catalog``
(AOT warmup over a short bucket ladder) and served through
``RequestScheduler`` in ``mode="tiered"`` (int8 sidecar) and ``mode="lsh"``
(hybrid, fp32). The run fails unless:

* served recall@10 is >= 0.9 against the exact ``mode="full"`` f32 scan on
  the chip;
* MinHash signatures and probe hit masks from the chip are bit-identical
  to ``kernels/ref.py`` run on the CPU device of this process;
* exact-scan top-10 scores match ``kernels/ref.py`` scoring of the whole
  lake on the CPU device within 1e-4, and the ids are that top-10 (equal
  up to the order of tied scores);
* each probing plan's compiled program holds a ``tpu_custom_call``.

``--chips 4`` runs only the multi-chip paths and what they are compared
with: a lake four times the one-chip size served through ``mode="lsh"`` and
``mode="sharded"`` on a four-chip mesh at grids 1x4 and 2x2 (ids of the
sharded full scan identical to the one-chip exact scan; lsh recall@10 is
printed at the default candidate cap and checked >= 0.9 at 4x that cap per
data shard, i.e. 16x at 1x4 and 8x at 2x2), and a
four-replica ``EngineFleet`` with one chip per replica (each
replica's resident corpus on its own chip; every accepted request
completes).

Everything runs in this one process. Without a TPU the script exits
non-zero and prints no result. Timings printed before the last line are
set-up and smoke numbers, not benchmark results. The last line on success
is ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
WORK = os.path.join(ROOT, ".smoke")      # catalog directories; removed at exit

K = 10
BATCH = 16
RECALL_MIN = 0.9
SCORE_TOL = 1e-4


class Checks:
    """Collects pass/fail verdicts; every verdict is printed as it lands."""

    def __init__(self):
        self.failed: list[str] = []

    def check(self, ok: bool, what: str) -> bool:
        print(f"check {'PASS' if ok else 'FAIL'}: {what}", flush=True)
        if not ok:
            self.failed.append(what)
        return ok


class CompileCacheCounter:
    """Counts JAX persistent-compilation-cache hits and misses."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def log(msg: str) -> None:
    print(msg, flush=True)


def train_model(seed: int):
    from benchmarks.common import bench_model

    t0 = time.perf_counter()
    model = bench_model(train_seed=100 + seed)
    log(f"model: GBDT {model.gbdt.feats.shape[0]} trees, train R2 "
        f"{model.train_r2:.3f} ({time.perf_counter() - t0:.1f} s)")
    return model


def build_catalog(n_columns: int, seed: int, name: str):
    """Generate the planted lake and ingest it as one segment."""
    from repro.core import ScaledLakeSpec, generate_scaled_lake
    from repro.service import CatalogStore

    t0 = time.perf_counter()
    lake = generate_scaled_lake(ScaledLakeSpec(n_columns=n_columns, seed=seed))
    gen_s = time.perf_counter() - t0
    root = os.path.join(WORK, name)
    shutil.rmtree(root, ignore_errors=True)
    store = CatalogStore(root, n_perm=128)
    t0 = time.perf_counter()
    store.add_batch(lake.batch,
                    [f"t{i}" for i in range(int(lake.table.max()) + 1)])
    log(f"ingest: {lake.n_columns} columns x {lake.spec.row_budget} rows "
        f"in {time.perf_counter() - t0:.2f} s (lake generated in "
        f"{gen_s:.2f} s)")
    return lake, store


def engine_config(mode: str, buckets: tuple, *, dtype: str = "fp32",
                  grid=None):
    from repro.service import EngineConfig, LSHConfig

    return EngineConfig(k=K, mode=mode, profile_dtype=dtype,
                        lsh=LSHConfig(n_bands=64, n_coarse_bands=16),
                        cache_entries=0, batch_buckets=buckets,
                        warmup="serve", grid=grid)


def open_engine(store, model, config, *, mesh=None, label: str):
    from repro.service import DiscoveryEngine

    t0 = time.perf_counter()
    engine = DiscoveryEngine.from_catalog(store, model, config, mesh=mesh)
    rep = engine.warmup_report
    log(f"engine[{label}]: open + warmup {time.perf_counter() - t0:.2f} s "
        f"({rep['n_executables']} executables over buckets "
        f"{rep['buckets']}, compile {rep['compile_ms'] / 1e3:.2f} s)")
    return engine


def serve(engine, requests, buckets: tuple, label: str):
    """Serve ``requests`` through the scheduler in batches of BATCH."""
    from repro.service import RequestScheduler, SchedulerConfig

    out, walls = [], []
    with RequestScheduler(engine, SchedulerConfig(batch_buckets=buckets)) \
            as scheduler:
        for i in range(0, len(requests), BATCH):
            t0 = time.perf_counter()
            futures = [scheduler.submit(r) for r in requests[i:i + BATCH]]
            out += [f.result(timeout=900) for f in futures]
            walls.append(time.perf_counter() - t0)
    log(f"serve[{label}]: {len(out)} requests, per-batch wall ms "
        f"{[round(w * 1e3, 3) for w in walls]}")
    return out


def recall(got, want) -> float:
    hits = total = 0
    for g, w in zip(got, want):
        ws = {m.column_id for m in w.matches[:K]}
        hits += len(ws & {m.column_id for m in g.matches[:K]})
        total += len(ws)
    return hits / max(total, 1)


def probing_programs(engine) -> dict:
    """Compiled executables of the engine's probing plans, by key."""
    out = {}
    for key, exe in engine._executor._compiled.items():
        statics = dict(key[3])
        if (key[0] in ("_local_tiered", "_local_pruned")
                or (key[0] == "sharded" and statics["candidates"] != "all")):
            out[key] = exe
    return out


def check_probe_programs(engine, label: str, checks: Checks) -> None:
    progs = probing_programs(engine)
    ok = bool(progs) and all("tpu_custom_call" in exe.as_text()
                             for exe in progs.values())
    checks.check(ok, f"{label}: {len(progs)} probing executables hold a "
                     f"tpu_custom_call")


def on_cpu(fn, *arrays):
    """Run ``fn`` on the CPU device of this process."""
    import jax

    cpu = jax.devices("cpu")[0]
    args = [jax.device_put(np.asarray(a), cpu) for a in arrays]
    return np.asarray(jax.jit(fn)(*args))


def check_kernel_parity(engine, lake, qids, checks: Checks) -> None:
    """Chip signatures and probe masks vs ``kernels/ref.py`` on the CPU."""
    from repro.kernels import ops, ref
    from repro.kernels.minhash import make_permutations

    snap, lsh = engine.snapshot, engine.lsh
    cols = np.arange(min(1024, snap.n_columns))
    a, b = make_permutations(snap.signatures.shape[1],
                             int(snap.minhash_seed))
    want = on_cpu(ref.minhash_ref, lake.batch.values32[cols], a, b)
    checks.check(np.array_equal(snap.signatures[cols], want),
                 f"minhash: ingest signatures of {cols.size} columns x "
                 f"{want.shape[1]} permutations bit-identical to ref on CPU")

    q = np.asarray(qids[:BATCH])
    sigq = snap.signatures[q]
    n = min(16_384, snap.n_columns)
    for name, qk, ck in (
            ("lsh_probe", lsh.query_keys(sigq), lsh.keys[:n]),
            ("lsh_probe digest", lsh.coarse_query_keys(sigq),
             lsh.coarse[:n])):
        chip = np.asarray(ops.lsh_probe(qk, ck))
        cpu = on_cpu(ref.lsh_probe_ref, qk, ck)
        checks.check(np.array_equal(chip, cpu) and chip.sum() > 0,
                     f"{name}: {qk.shape[0]}x{ck.shape[0]}x{ck.shape[1]} "
                     f"hit mask bit-identical to ref on CPU "
                     f"({int(chip.sum())} hits)")
    # gathered fine probe: each query's planted partners, then filler
    width = min(2048, snap.n_columns)
    pos = (np.arange(width)[None, :] + 997 * np.arange(q.size)[:, None]) \
        % snap.n_columns
    for i, qq in enumerate(q):
        p = lake.partners(int(qq))[:width]
        pos[i, :p.size] = p
    qk = lsh.query_keys(sigq)
    chip = np.asarray(ops.lsh_probe_gathered(qk, lsh.keys[pos]))
    cpu = on_cpu(ref.lsh_probe_gathered_ref, qk, lsh.keys[pos])
    checks.check(np.array_equal(chip, cpu) and chip.sum() > 0,
                 f"lsh_probe_gathered: {q.size}x{width}x{qk.shape[1]} hit "
                 f"mask bit-identical to ref on CPU ({int(chip.sum())} hits)")


def check_exact_scan(engine, model, qids, responses, checks: Checks) -> None:
    """Chip exact-scan top-10 vs ``ref.fused_score_ref`` over the whole
    lake on the CPU, with the engine's exclusions (self, same table)."""
    from repro.kernels import ref

    snap = engine.snapshot
    z = snap.profiles.zscored.astype(np.float32)
    w = snap.profiles.words
    qids = np.asarray(qids)
    feats, thrs, leaves, base = model.gbdt.astuple()
    scores = on_cpu(
        lambda zq, wq, zc, wc, f, t, lv: ref.fused_score_ref(
            zq, wq, zc, wc, f, t, lv, float(base)),
        z[qids], w[qids], z, w, feats, thrs, leaves)
    for i, (q, resp) in enumerate(zip(qids, responses)):
        s = scores[i].copy()
        s[snap.table_ids == snap.table_ids[q]] = -np.inf
        s[q] = -np.inf
        top = np.argsort(-s, kind="stable")[:K]
        ids = np.asarray([m.column_id for m in resp.matches])
        got = np.asarray([m.score for m in resp.matches], np.float32)
        same_scores = (ids.size == K and np.allclose(got, s[top],
                                                     rtol=0, atol=SCORE_TOL))
        same_columns = ids.size == K and np.allclose(s[ids], got, rtol=0,
                                                     atol=SCORE_TOL)
        identical = np.array_equal(ids, top)
        checks.check(same_scores and same_columns,
                     f"exact scan q={q}: top-{K} scores within {SCORE_TOL} "
                     f"of ref on CPU, ids "
                     f"{'identical' if identical else 'equal up to ties'}")


def run_one_chip(args, checks: Checks) -> None:
    from repro.core import select_scaled_queries
    from repro.service import DiscoveryRequest

    buckets = (8, BATCH)
    model = train_model(args.seed)
    lake, store = build_catalog(args.columns, args.seed, "one-chip")
    qids = select_scaled_queries(lake, args.queries, seed=args.seed + 1)
    reqs = [DiscoveryRequest(name=f"q{int(q)}", column_id=int(q))
            for q in qids]

    exact = open_engine(store, model, engine_config("full", buckets),
                        label="full fp32")
    want = serve(exact, reqs, buckets, "full fp32")
    for mode, dtype in (("tiered", "int8"), ("lsh", "fp32")):
        label = f"{mode} {dtype}"
        engine = open_engine(store, model,
                             engine_config(mode, buckets, dtype=dtype),
                             label=label)
        got = serve(engine, reqs, buckets, label)
        r = recall(got, want)
        st = engine.stats()
        log(f"served[{label}]: plans {st['plans']}, dispatch "
            f"{engine._executor.dispatch_stats()}, recall@{K} {r:.4f}")
        checks.check(r >= RECALL_MIN, f"{label}: served recall@{K} {r:.4f} "
                                      f">= {RECALL_MIN} vs the exact scan")
        check_probe_programs(engine, label, checks)
        engine.close()
    check_kernel_parity(exact, lake, qids, checks)
    check_exact_scan(exact, model, qids[:4], want[:4], checks)
    exact.close()


def run_four_chips(args, checks: Checks) -> None:
    import jax

    from repro.core import select_scaled_queries
    from repro.launch.mesh import make_local_mesh
    from repro.service import (DiscoveryRequest, EngineFleet,
                               RequestScheduler, SchedulerConfig)
    from repro.service.fleet import EVICTED, SERVING

    if len(jax.devices()) != 4:
        raise SystemExit(f"--chips 4 needs 4 devices; JAX sees "
                         f"{len(jax.devices())}")
    buckets = (BATCH,)
    model = train_model(args.seed)
    lake, store = build_catalog(4 * args.columns, args.seed, "four-chip")
    qids = select_scaled_queries(lake, args.queries, seed=args.seed + 1)
    reqs = [DiscoveryRequest(name=f"q{int(q)}", column_id=int(q))
            for q in qids]

    exact = open_engine(store, model, engine_config("full", buckets),
                        label="full fp32, one chip")
    want = serve(exact, reqs, buckets, "full fp32, one chip")
    exact.close()
    mesh = make_local_mesh()
    for grid in ((1, 4), (2, 2)):
        g = f"{grid[0]}x{grid[1]}"
        engine = open_engine(store, model,
                             engine_config("sharded", buckets, grid=grid),
                             mesh=mesh, label=f"sharded full {g}")
        got = serve(engine, reqs, buckets, f"sharded full {g}")
        same = all([m.column_id for m in a.matches]
                   == [m.column_id for m in b.matches]
                   for a, b in zip(got, want))
        log(f"served[sharded full {g}]: plans {engine.stats()['plans']}")
        checks.check(same, f"sharded full {g}: top-{K} ids identical to the "
                           f"one-chip exact scan")
        engine.close()
        # The sharded plan splits max_candidates evenly over the data
        # shards, but the lake's planted (joinable) columns all sit in its
        # first rows, on data shard 0, so recall follows the per-shard
        # budget. The default cap is served and its recall printed, as what
        # a user gets today; the check runs with a per-shard budget of 4x
        # the default cap (what one chip needs for the whole 4x lake at the
        # one-chip candidate fraction) times the data shards.
        default_cap = engine_config("lsh", buckets).max_candidates
        for cap in (default_cap, 4 * default_cap * grid[1]):
            label = f"lsh {g} max_candidates {cap}"
            config = dataclasses.replace(
                engine_config("lsh", buckets, grid=grid), max_candidates=cap)
            engine = open_engine(store, model, config, mesh=mesh, label=label)
            got = serve(engine, reqs, buckets, label)
            r = recall(got, want)
            log(f"served[{label}]: plans {engine.stats()['plans']}, grid "
                f"{engine.stats()['last_plan']['grid']}, recall@{K} {r:.4f}")
            if cap != default_cap:
                checks.check(r >= RECALL_MIN,
                             f"{label}: recall@{K} {r:.4f} >= {RECALL_MIN} "
                             f"vs the one-chip scan")
            check_probe_programs(engine, label, checks)
            engine.close()

    t0 = time.perf_counter()
    fleet = EngineFleet.from_catalog(store, model,
                                     engine_config("lsh", buckets),
                                     n_replicas=4, devices=jax.devices())
    try:
        states = {}
        while time.perf_counter() - t0 < 900:
            states = {r.replica_id: r.state for r in fleet.replicas}
            if (all(s == SERVING for s in states.values())
                    or EVICTED in states.values()):
                break
            time.sleep(0.2)
        log(f"fleet: replica states {states} after "
            f"{time.perf_counter() - t0:.2f} s")
        if not checks.check(all(s == SERVING for s in states.values()),
                            "fleet: all 4 replicas reached serving"):
            return
        placed = []
        for r in fleet.replicas:
            devs = r.engine._executor._z.devices()
            log(f"fleet: replica {r.replica_id} resident corpus on {devs}")
            placed.append(devs)
        checks.check(all(len(d) == 1 for d in placed)
                     and len(set.union(*placed)) == 4,
                     "fleet: the 4 replicas' corpora sit on 4 distinct chips")
        # all at once, so that several batches are in flight and the
        # router spreads them over the replicas
        many = reqs * 4
        with RequestScheduler(fleet, SchedulerConfig(batch_buckets=buckets)) \
                as scheduler:
            t1 = time.perf_counter()
            futures = [scheduler.submit(r) for r in many]
            got = [f.result(timeout=900) for f in futures]
        log(f"serve[fleet]: {len(got)} requests in "
            f"{(time.perf_counter() - t1) * 1e3:.3f} ms")
        st = fleet.stats()
        log("fleet: batches served per replica "
            + str({rid: v["batches_served"]
                   for rid, v in st["replicas"].items()}))
        checks.check(len(got) == len(many)
                     and all(len(g.matches) == K for g in got),
                     f"fleet: all {len(many)} accepted requests completed")
    finally:
        fleet.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: ingest + serving on one chip; 4: only the "
                         "sharded grid plans and the replica fleet")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--columns", type=int, default=100_000,
                    help="one-chip lake size (--chips 4 serves 4x this)")
    ap.add_argument("--queries", type=int, default=64)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform}); "
              f"nothing was run", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    cache = CompileCacheCounter()
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {cache_dir}")
    checks = Checks()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(args, checks)
        else:
            run_one_chip(args, checks)
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    log(f"compile cache: {cache.hits} hits, {cache.misses} misses "
        f"({'hit' if cache.hits else 'not hit'}); total "
        f"{time.perf_counter() - t0:.1f} s")
    if checks.failed:
        print(f"chip_smoke: {len(checks.failed)} check(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
