"""Unified query-execution layer: plan selection, stage semantics, executor
parity, and the distributed-LSH acceptance path on a forced 8-device host
mesh (subprocess: jax device count must be set before first init)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.exec import Planner, PlannerConfig, QueryPlan

ROOT = os.path.join(os.path.dirname(__file__), "..")


class _FakeMesh:
    """Planner only reads mesh.shape — keep plan tests jax-free."""

    def __init__(self, **shape):
        self.shape = shape


# ---------------------------------------------------------------------------
# planner: mode mapping + thresholds
# ---------------------------------------------------------------------------

def test_plan_mode_mapping():
    p = Planner(PlannerConfig(k=10))
    mesh = _FakeMesh(data=8, model=1)
    assert p.plan(n_columns=1000, mode="full").kind == "local-all"
    assert p.plan(n_columns=1000, mode="lsh").kind == "local-hybrid"
    assert p.plan(n_columns=1000, mode="lsh", mesh=mesh).kind == \
        "sharded-hybrid"
    assert p.plan(n_columns=1000, mode="sharded", mesh=mesh).kind == \
        "sharded-all"
    with pytest.raises(ValueError):
        p.plan(n_columns=1000, mode="sharded")          # sharded needs a mesh
    with pytest.raises(ValueError):
        p.plan(n_columns=1000, mode="warp")


def test_plan_auto_lake_size_threshold():
    """Tiny lakes: probe+proxy overhead exceeds the pruning savings, the
    cost model must fall back to the brute scan; big lakes must prune
    (hybrid probe or, once the lake dwarfs the survivor budget, the
    tiered coarse-digest pipeline)."""
    p = Planner(PlannerConfig(k=10))
    pruned = ("hybrid", "tiered")
    assert p.plan(n_columns=12, mode="auto").candidates == "all"
    assert p.plan(n_columns=4096, mode="auto").candidates in pruned
    # the crossover is monotone: once pruning wins it keeps winning
    kinds = [p.plan(n_columns=n, mode="auto").candidates
             for n in (8, 64, 512, 4096, 32768)]
    first_pruned = next(i for i, c in enumerate(kinds) if c in pruned)
    assert all(c in pruned for c in kinds[first_pruned:]), kinds
    # without a coarse digest the tier is not a contender
    p0 = Planner(PlannerConfig(k=10, n_coarse_bands=0))
    for n in (8, 64, 512, 4096, 32768):
        assert p0.plan(n_columns=n, mode="auto").candidates != "tiered"


def test_plan_auto_mesh_threshold():
    """Sharding in auto mode is gated on columns-per-shard: a small lake on
    a big mesh stays local, a big lake shards."""
    p = Planner(PlannerConfig(k=10, min_columns_per_shard=64))
    mesh = _FakeMesh(data=8, model=1)
    small = p.plan(n_columns=100, mode="auto", mesh=mesh)
    big = p.plan(n_columns=10_000, mode="auto", mesh=mesh)
    assert not small.sharded and small.n_shards == 1
    assert big.sharded and big.n_shards == 8


def test_plan_budget_clamps():
    p = Planner(PlannerConfig(k=10, candidate_frac=0.2, max_candidates=100))
    assert p.plan(n_columns=20, mode="lsh").budget == 10      # k floor
    assert p.plan(n_columns=200, mode="lsh").budget == 40     # frac
    assert p.plan(n_columns=10_000, mode="lsh").budget == 100  # cap
    assert p.plan(n_columns=5, mode="lsh").budget == 5        # lake size
    # full-scan plans see the whole lake
    assert p.plan(n_columns=200, mode="full").budget == 200


def test_plan_budget_per_shard_and_cost():
    p = Planner(PlannerConfig(k=10, max_candidates=4096))
    mesh = _FakeMesh(data=8, model=1)
    plan = p.plan(n_columns=10_000, mode="lsh", mesh=mesh)
    assert plan.budget_per_shard == -(-plan.budget // 8)
    assert plan.cost["n_shards"] == 8
    assert plan.cost["total_collective_bytes"] > 0           # the all_gather
    local = p.plan(n_columns=10_000, mode="lsh")
    assert local.cost["total_collective_bytes"] == 0.0
    # pruning must model cheaper than the brute scan at this size
    full = p.plan(n_columns=10_000, mode="full")
    assert plan.cost["total_flops"] < full.cost["total_flops"]
    assert set(plan.cost["stages"]) == {"candidates", "score", "merge"}


def test_plan_rejects_unknown_candidate_kind():
    with pytest.raises(ValueError):
        QueryPlan(candidates="psychic", sharded=False, budget=1, k=1)


def test_planner_cost_fn_hook_is_used():
    calls = []

    def fake_cost(nq, nc, **kw):
        calls.append(kw["candidates"])
        # force the opposite decision: make pruning look expensive
        return {"total_flops": 1e18 if kw["candidates"] != "all" else 1.0}

    p = Planner(PlannerConfig(k=10), cost_fn=fake_cost)
    plan = p.plan(n_columns=100_000, mode="auto")
    assert plan.candidates == "all"
    assert "all" in calls and "hybrid" in calls


def test_planner_prefers_measured_total_cost():
    """A calibrated cost_fn reports seconds as total_cost; auto mode must
    decide on it, not on the (contradicting) flop counts."""
    def measured(nq, nc, **kw):
        pruned = kw["candidates"] != "all"
        # flops say "prune"; the measured seconds say the probe dominates
        return {"total_flops": 1.0 if pruned else 1e9,
                "total_cost": 5.0 if pruned else 0.1}

    p = Planner(PlannerConfig(k=10), cost_fn=measured)
    assert p.plan(n_columns=100_000, mode="auto").candidates == "all"


# ---------------------------------------------------------------------------
# calibrated cost model (launch.costmodel.calibrate_stage_costs)
# ---------------------------------------------------------------------------

def _synthetic_bench_record(score_s_per_flop=2e-9, cand_s_per_flop=5e-10,
                            merge_s_per_flop=1e-9, fixed_s=2e-4):
    """A BENCH_service.json-shaped record whose timings follow known
    per-stage constants exactly."""
    from repro.launch.costmodel import discovery_stage_costs
    lakes = []
    for c in (128, 512, 2048, 8192):
        modes = {}
        for mode, cand, budget in (("full", "all", c),
                                   ("lsh", "hybrid", max(10, c // 5))):
            stg = discovery_stage_costs(1, c, budget=budget,
                                        candidates=cand)["stages"]
            s = (fixed_s + cand_s_per_flop * stg["candidates"]["flops"]
                 + score_s_per_flop * stg["score"]["flops"]
                 + merge_s_per_flop * stg["merge"]["flops"])
            modes[mode] = {
                "plan": f"local-{cand}", "plan_budget": budget,
                "batch_ms_per_query": s * 1e3,
            }
        lakes.append({"n_columns": c, "modes": modes})
    return {"lakes": lakes}


def test_calibrate_recovers_planted_constants(tmp_path):
    import json

    from repro.launch.costmodel import calibrate_stage_costs
    record = _synthetic_bench_record()
    path = tmp_path / "BENCH_service.json"
    path.write_text(json.dumps(record))

    constants, cost_fn = calibrate_stage_costs(str(path))
    assert constants["r2"] > 0.999, constants
    assert np.isclose(constants["score_s_per_flop"], 2e-9, rtol=0.05)
    assert np.isclose(constants["fixed_s_per_query"], 2e-4, rtol=0.05)

    c = cost_fn(4, 10_000, budget=2000, candidates="hybrid")
    assert c["calibrated"] and c["total_cost"] > 0
    assert "total_flops" in c            # still a superset of the analytic

    # end-to-end: the planner decides on the measured crossover — on this
    # host pruning wins (hybrid, or tiered once the coarse digest beats
    # the full-lake probe), but a probe-hostile measurement flips the
    # same lake to the brute scan (the analytic flops alone never would)
    p = Planner(PlannerConfig(k=10), cost_fn=cost_fn)
    assert p.plan(n_columns=50_000, mode="auto").candidates in \
        ("hybrid", "tiered")
    _, hostile = calibrate_stage_costs(
        _synthetic_bench_record(cand_s_per_flop=1e-7))
    p2 = Planner(PlannerConfig(k=10), cost_fn=hostile)
    assert p2.plan(n_columns=50_000, mode="auto").candidates == "all"


def test_calibrate_needs_enough_observations():
    from repro.launch.costmodel import calibrate_stage_costs
    with pytest.raises(ValueError, match="observations"):
        calibrate_stage_costs({"lakes": [
            {"n_columns": 10,
             "modes": {"full": {"plan": "local-all", "plan_budget": 10,
                                "batch_ms_per_query": 1.0}}}]})


def test_engine_accepts_calibrated_cost_fn(tmp_path):
    """EngineConfig.cost_fn reaches the planner: a measured model that
    makes pruning look slow flips auto mode to the brute scan."""
    from repro.service import CatalogStore, DiscoveryEngine, DiscoveryRequest, \
        EngineConfig

    def probe_hostile(nq, nc, **kw):
        pruned = kw["candidates"] != "all"
        return {"total_flops": float(nc), "n_queries": nq,
                "total_cost": 9.0 if pruned else 1.0}

    store = CatalogStore(str(tmp_path), n_perm=64)
    store.add_table("t", [("x", [f"v{i}" for i in range(300)]),
                          ("y", [f"w{i}" for i in range(300)])])
    from repro.core import GBDTConfig, LakeSpec, generate_lake, \
        train_quality_model
    lake = generate_lake(LakeSpec(n_domains=4, n_tables=6, row_budget=256,
                                  rows_log_mean=5.0, seed=1))
    model = train_quality_model([lake], GBDTConfig(n_trees=10, depth=3),
                                n_query=16)
    engine = DiscoveryEngine.from_catalog(
        store, model, EngineConfig(k=3, mode="auto",
                                   cost_fn=probe_hostile))
    engine.query(DiscoveryRequest(column_id=0))
    assert engine.stats()["last_plan"]["kind"] == "local-all"


# ---------------------------------------------------------------------------
# stages
# ---------------------------------------------------------------------------

def test_exclusion_mask_semantics():
    import jax.numpy as jnp
    from repro.exec.stages import exclusion_mask
    cids = jnp.asarray([0, 1, 2, -1])        # last column is padding
    tids = jnp.asarray([7, 7, 8, -2])
    tq = jnp.asarray([7, -1])                # row 1: table mask disabled
    qid = jnp.asarray([2, -1])               # row 1: external query
    m = np.asarray(exclusion_mask(cids, tids, tq, qid))
    assert m.tolist() == [[True, True, True, True],     # table 7 + self + pad
                          [False, False, False, True]]  # only padding


def test_merge_topk_id_conventions():
    import jax.numpy as jnp
    from repro.exec.stages import merge_topk
    s = jnp.asarray([[1.0, -jnp.inf, 3.0]])
    cids = jnp.asarray([10, 11, 12])
    sc, ids = merge_topk(s, cids, k=3)
    assert ids.tolist() == [[12, 10, -1]]               # -inf slot -> -1
    # per-query 2-D candidate ids (gathered sets)
    sc2, ids2 = merge_topk(s, jnp.asarray([[10, 11, 12]]), k=2)
    assert ids2.tolist() == [[12, 10]]


def test_candidate_priorities_lsh_vs_hybrid(rng):
    import jax.numpy as jnp
    from repro.exec.stages import candidate_priorities
    c, b, f = 16, 8, 21
    ckeys = rng.integers(0, 2**31, (c, b)).astype(np.uint32)
    qkeys = np.full((1, b), 0xAAAA, np.uint32)
    qkeys[0, 0] = ckeys[3, 0]                # bucket hit on column 3 only
    z = rng.normal(size=(c, f)).astype(np.float32)
    zq = z[3:4]
    cids = jnp.arange(c)
    tids = jnp.zeros((c,), jnp.int32)
    tq = jnp.asarray([-1])
    qid = jnp.asarray([-1])
    lsh = np.asarray(candidate_priorities("lsh", jnp.asarray(zq), qkeys, z,
                                          ckeys, cids, tids, tq, qid,
                                          interpret=True))
    assert np.isfinite(lsh[0, 3]) and np.isinf(lsh[0, :3]).all()
    hyb = np.asarray(candidate_priorities("hybrid", jnp.asarray(zq), qkeys,
                                          z, ckeys, cids, tids, tq, qid,
                                          interpret=True))
    assert np.isfinite(hyb).all()            # proxy fills the whole lake
    assert hyb[0].argmax() == 3              # the bucket hit still outranks
    with pytest.raises(ValueError):
        candidate_priorities("nope", jnp.asarray(zq), qkeys, z, ckeys, cids,
                             tids, tq, qid, interpret=True)


# ---------------------------------------------------------------------------
# executor (single device)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def exec_setup():
    from repro.core import (GBDTConfig, LakeSpec, generate_lake, profile_lake,
                            train_quality_model)
    from repro.exec import Executor
    from repro.service.lsh import band_keys
    from repro.kernels import ops
    lake = generate_lake(LakeSpec(n_domains=10, n_tables=24, row_budget=2048,
                                  rows_log_mean=6.8, coverage_range=(0.5, 1.0),
                                  gran_ratio=(4, 8), seed=7))
    prof = profile_lake(lake.batch)
    model = train_quality_model([lake], GBDTConfig(n_trees=30, depth=4),
                                n_query=64)
    sigs = np.asarray(ops.minhash(lake.batch.values32, n_perm=128, seed=0))
    keys = band_keys(sigs, 64)
    ex = Executor(prof.zscored, prof.words, model.gbdt.astuple(),
                  table_ids=lake.table, band_keys=keys)
    return lake, prof, model, ex, keys


def test_stage_scopes_change_no_compiled_op():
    """``named_scope`` on the candidate/score/merge stages lands in the
    ops' metadata only: the optimized HLO of one bucket of the pruned
    pipeline is the same with and without the scopes, op for op."""
    import jax

    from _fixtures import pruned_hlo

    struct = jax.ShapeDtypeStruct
    kw = dict(q=8, c=1024, budget=256, interpret=True)
    scoped = pruned_hlo(struct, scopes=True, **kw)
    assert scoped == pruned_hlo(struct, scopes=False, **kw)
    assert " gather(" in scoped and " while(" in scoped


def test_executor_full_matches_rank(exec_setup):
    from repro.core import DiscoveryIndex, rank, select_queries
    lake, prof, model, ex, keys = exec_setup
    idx = DiscoveryIndex(profiles=prof, model=model, table_ids=lake.table)
    qids = select_queries(lake, 6)
    plan = Planner(PlannerConfig(k=5)).plan(n_columns=lake.n_columns,
                                            mode="full")
    zq = prof.zscored[qids].astype(np.float32)
    tq = lake.table[qids].astype(np.int32)
    sc, ids, n = ex.execute(plan, zq, prof.words[qids], tq,
                            qids.astype(np.int32))
    s_ref, i_ref = rank(idx, qids, k=5, exclude_same_table=True)
    np.testing.assert_allclose(sc, s_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(ids, i_ref)
    assert (n == lake.n_columns).all()


def test_executor_pruned_recall_and_accounting(exec_setup):
    from repro.core import select_queries
    lake, prof, model, ex, keys = exec_setup
    qids = select_queries(lake, 8)
    planner = Planner(PlannerConfig(k=10, candidate_frac=0.2))
    zq = prof.zscored[qids].astype(np.float32)
    wq = prof.words[qids]
    tq = np.full(len(qids), -1, np.int32)
    qid = qids.astype(np.int32)
    full = planner.plan(n_columns=lake.n_columns, mode="full")
    hyb = planner.plan(n_columns=lake.n_columns, mode="lsh")
    fs, fi, _ = ex.execute(full, zq, wq, tq, qid)
    hs, hi, hn = ex.execute(hyb, zq, wq, tq, qid, qkeys=keys[qids])
    assert (hn <= hyb.budget).all()                  # honest accounting
    rec = np.mean([len(set(a[a >= 0]) & set(b[b >= 0])) /
                   max((b >= 0).sum(), 1) for a, b in zip(hi, fi)])
    assert rec >= 0.9, rec
    # pure-LSH plan scores only bucket hits: strictly fewer than the budget
    lsh = QueryPlan(candidates="lsh", sharded=False, budget=hyb.budget, k=10)
    _, _, ln = ex.execute(lsh, zq, wq, tq, qid, qkeys=keys[qids])
    assert (ln <= hn).all()


def test_executor_missing_keys_raise(exec_setup):
    from repro.exec import Executor
    lake, prof, model, ex, keys = exec_setup
    bare = Executor(prof.zscored, prof.words, model.gbdt.astuple())
    plan = Planner(PlannerConfig(k=3)).plan(n_columns=lake.n_columns,
                                            mode="lsh")
    z1 = prof.zscored[:1].astype(np.float32)
    args = (z1, prof.words[:1], np.asarray([-1], np.int32),
            np.asarray([0], np.int32))
    with pytest.raises(ValueError):
        bare.execute(plan, *args)                    # no corpus band keys
    with pytest.raises(ValueError):
        ex.execute(plan, *args)                      # no query band keys
    with pytest.raises(ValueError):
        plan_sh = QueryPlan(candidates="all", sharded=True,
                            budget=lake.n_columns, k=3)
        ex.execute(plan_sh, *args)                   # no mesh


def test_executor_empty_corpus():
    from repro.core.gbdt import GBDTParams
    from repro.exec import Executor
    gb = GBDTParams(feats=np.zeros((1, 1), np.int32),
                    thrs=np.zeros((1, 1), np.float32),
                    leaves=np.zeros((1, 2), np.float32), base=0.0)
    from repro.core import features as FT
    ex = Executor(np.zeros((0, FT.F_NUM), np.float32),
                  np.zeros((0, FT.F_WORDS), np.uint32), gb.astuple())
    plan = Planner(PlannerConfig(k=4)).plan(n_columns=0, mode="full")
    sc, ids, n = ex.execute(plan, np.zeros((2, FT.F_NUM), np.float32),
                            np.zeros((2, FT.F_WORDS), np.uint32),
                            np.full((2,), -1, np.int32),
                            np.full((2,), -1, np.int32))
    assert sc.shape == (2, 4) and (ids == -1).all() and (n == 0).all()


# ---------------------------------------------------------------------------
# acceptance: distributed LSH on 8 host devices (subprocess)
# ---------------------------------------------------------------------------

def test_sharded_lsh_acceptance_8dev():
    """ISSUE acceptance: mode="lsh" end-to-end on an 8-device mesh —
    per-device bucket probe + single all_gather, recall@10 ≥ 0.9 vs the
    sharded full scan while scoring ≤ 30% of lake columns; plus
    sharded-vs-local LSH parity on the same snapshot."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = """
        import numpy as np, jax
        from repro.core import (GBDTConfig, LakeSpec, generate_lake,
                                select_queries, train_quality_model)
        from repro.core.lakegen import Lake
        from repro.service import (DiscoveryEngine, DiscoveryRequest,
                                   EngineConfig, LSHConfig, measure_recall)
        from repro.service.catalog import CatalogSnapshot, ColumnCatalog, \\
            add_lake
        import tempfile

        assert len(jax.devices()) == 8
        lake = generate_lake(LakeSpec(n_domains=10, n_tables=24,
                                      row_budget=2048, rows_log_mean=6.8,
                                      coverage_range=(0.5, 1.0),
                                      gran_ratio=(4, 8), seed=7))
        model = train_quality_model([lake], GBDTConfig(n_trees=30, depth=4),
                                    n_query=64)
        root = tempfile.mkdtemp(prefix="freyja_shlsh_")
        add_lake(ColumnCatalog(root, n_perm=128), lake)
        snap = ColumnCatalog(root).snapshot()

        mesh = jax.make_mesh((8, 1), ("data", "model"))
        cfg = dict(k=10, lsh=LSHConfig(n_bands=64), candidate_frac=0.2)
        eng_sh = DiscoveryEngine(snap, model,
                                 EngineConfig(mode="lsh", **cfg), mesh=mesh)
        eng_lo = DiscoveryEngine(snap, model, EngineConfig(mode="lsh", **cfg))

        qids = select_queries(lake, 16)
        reqs = [DiscoveryRequest(name=f"q{int(q)}", column_id=int(q))
                for q in qids]
        r_sh = eng_sh.query_batch(reqs)
        r_lo = eng_lo.query_batch(list(reqs))
        assert eng_sh.stats()["last_plan"]["kind"] == "sharded-hybrid"
        assert eng_lo.stats()["last_plan"]["kind"] == "local-hybrid"

        # parity: sharded and local pruning agree on the neighborhoods
        overlap = np.mean([
            len({m.column_id for m in a.matches} &
                {m.column_id for m in b.matches}) /
            max(len(b.matches), 1)
            for a, b in zip(r_sh, r_lo)])
        assert overlap >= 0.8, overlap

        # acceptance: recall vs the SHARDED full scan + pruning bound
        rec = measure_recall(eng_sh, qids, k=10)
        assert rec["plan"] == "sharded-hybrid", rec
        assert rec["baseline_plan"] == "sharded-all", rec
        assert rec["recall"] >= 0.9, rec
        assert rec["scored_fraction"] <= 0.30, rec
        print("OK sharded_lsh", overlap, rec["recall"],
              rec["scored_fraction"])
    """
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "OK sharded_lsh" in r.stdout
