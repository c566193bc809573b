"""Large-lake scale path: tiered LSH candidate generation, quantized
profile matrices (with the exact fp32 re-rank), lazy memory-mapped
snapshots, bulk single-segment ingest, and the scaled lake generator
with planted joinability tiers."""
import warnings

import numpy as np
import pytest

from repro.core import (GBDTConfig, ScaledLakeSpec, generate_scaled_lake,
                        select_scaled_queries, train_quality_model)
from repro.exec.plan import Planner, PlannerConfig
from repro.service import (CatalogReader, ColumnCatalog, DiscoveryEngine,
                           DiscoveryRequest, EngineConfig, LSHConfig,
                           band_keys, coarse_band_keys, measure_recall)
from repro.service import lsh as lsh_mod
from repro.service.scheduler import (DeadlineExpired, RequestScheduler,
                                     SchedulerConfig)

N_SCALED = 4096


@pytest.fixture(scope="module")
def scaled_lake():
    return generate_scaled_lake(ScaledLakeSpec(n_columns=N_SCALED, seed=5))


@pytest.fixture(scope="module")
def model(small_lake):
    return train_quality_model([small_lake], GBDTConfig(n_trees=30, depth=4),
                               n_query=64)


@pytest.fixture(scope="module")
def scaled_root(scaled_lake, tmp_path_factory):
    root = str(tmp_path_factory.mktemp("scaled_catalog"))
    cat = ColumnCatalog(root, n_perm=128)
    n_tables = int(scaled_lake.table.max()) + 1
    cat.add_batch(scaled_lake.batch, [f"t{i}" for i in range(n_tables)])
    return root


@pytest.fixture(scope="module")
def scaled_snapshot(scaled_root):
    return CatalogReader(scaled_root).snapshot(lazy=True)


# ---------------------------------------------------------------------------
# band keys: remainder fold + coarse digest
# ---------------------------------------------------------------------------

def test_band_keys_remainder_folds_and_warns_once(rng):
    sigs = rng.integers(0, 2**32, (6, 100), dtype=np.uint32)
    lsh_mod._REMAINDER_WARNED.discard((100, 16))
    with pytest.warns(RuntimeWarning, match="folding the 4 trailing"):
        keys = band_keys(sigs, 16)          # r = 6, 96 rows used, 4 trail
    assert keys.shape == (6, 16)
    # the warning is once per geometry
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        band_keys(sigs, 16)
    assert not [x for x in w if issubclass(x.category, RuntimeWarning)]
    # trailing rows are folded into the LAST band, not dropped: perturbing
    # a trailing permutation changes only that band's key
    sigs2 = sigs.copy()
    sigs2[:, 98] ^= np.uint32(0x5A5A5A5A)
    keys2 = band_keys(sigs2, 16)
    np.testing.assert_array_equal(keys[:, :-1], keys2[:, :-1])
    assert (keys[:, -1] != keys2[:, -1]).all()


def test_band_keys_exact_division_unchanged(rng):
    sigs = rng.integers(0, 2**32, (4, 128), dtype=np.uint32)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        keys = band_keys(sigs, 64)
    assert not w
    assert keys.shape == (4, 64)


def test_coarse_band_keys_digest(rng):
    sigs = rng.integers(0, 2**32, (8, 128), dtype=np.uint32)
    ck = coarse_band_keys(sigs, 16)
    assert ck.shape == (8, 16) and ck.dtype == np.uint32
    # identical signatures -> identical digests; a changed sampled row
    # flips exactly one digest lane
    np.testing.assert_array_equal(coarse_band_keys(sigs, 16), ck)
    sigs2 = sigs.copy()
    sigs2[:, 0] ^= np.uint32(1)            # row 0 is the first sampled row
    ck2 = coarse_band_keys(sigs2, 16)
    assert (ck2[:, 0] != ck[:, 0]).all()
    np.testing.assert_array_equal(ck2[:, 1:], ck[:, 1:])
    with pytest.raises(ValueError):
        coarse_band_keys(sigs, 200)


# ---------------------------------------------------------------------------
# scaled lake generator
# ---------------------------------------------------------------------------

def test_scaled_lake_planted_jaccard(scaled_lake):
    lake, spec = scaled_lake, scaled_lake.spec
    assert lake.batch.values32.shape == (N_SCALED, spec.row_budget)
    qids = select_scaled_queries(lake, 9, seed=1)
    for q in qids:
        q = int(q)
        partners = lake.partners(q)
        assert partners.size == spec.group_size - 1
        # partners are strided into distinct tables (join partners in the
        # same table would be excluded by the engine's table mask)
        assert lake.table[q] not in lake.table[partners]
        # realized pairwise Jaccard tracks the planted tier
        want = spec.jaccard_tiers[lake.tier[q]]
        a = set(np.unique(lake.batch.values32[q]).tolist())
        b = set(np.unique(lake.batch.values32[int(partners[0])]).tolist())
        j = len(a & b) / len(a | b)
        assert abs(j - want) < 0.25 * want + 0.05


def test_scaled_lake_noise_disjoint(scaled_lake):
    lake = scaled_lake
    noise = np.flatnonzero(lake.group < 0)[:4]
    planted = np.flatnonzero(lake.group >= 0)[:4]
    for n in noise:
        vn = set(np.unique(lake.batch.values32[n]).tolist())
        for p in planted:
            vp = set(np.unique(lake.batch.values32[p]).tolist())
            assert not (vn & vp)


def test_select_scaled_queries_tier_balanced(scaled_lake):
    qids = select_scaled_queries(scaled_lake, 12, seed=3)
    assert len(set(qids.tolist())) == 12
    tiers = scaled_lake.tier[qids]
    counts = np.bincount(tiers, minlength=3)
    assert (counts >= 3).all()             # 12 queries over 3 tiers


# ---------------------------------------------------------------------------
# bulk ingest + lazy snapshots
# ---------------------------------------------------------------------------

def test_add_batch_single_segment(scaled_lake, scaled_root):
    cat = ColumnCatalog(scaled_root)
    assert len(cat.manifest["segments"]) == 1
    snap = cat.snapshot()
    assert snap.n_columns == N_SCALED
    assert len(cat.tables()) == int(scaled_lake.table.max()) + 1


def test_lazy_snapshot_matches_eager(scaled_root):
    reader = CatalogReader(scaled_root)
    lazy = reader.snapshot(lazy=True)
    eager = reader.snapshot(lazy=False)
    assert lazy.lazy and not eager.lazy
    np.testing.assert_array_equal(np.asarray(lazy.signatures),
                                  eager.signatures)
    np.testing.assert_array_equal(np.asarray(lazy.profiles.numeric),
                                  eager.profiles.numeric)
    # lazy stats come from the segment's float64 moments, eager from a
    # float32 pass over the matrix — close, not bit-equal
    np.testing.assert_allclose(lazy.profiles.mean, eager.profiles.mean,
                               rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(lazy.profiles.std, eager.profiles.std,
                               rtol=1e-3, atol=1e-5)
    assert lazy.table_ids.shape == eager.table_ids.shape


def test_lazy_falls_back_on_multi_segment(tmp_path):
    cat = ColumnCatalog(str(tmp_path), n_perm=64)
    cat.add_table("a", [("x", [f"v{i}" for i in range(40)])])
    cat.add_table("b", [("y", [f"w{i}" for i in range(40)])])
    snap = cat.snapshot(lazy=True)         # two segments -> eager load
    assert not snap.lazy
    assert snap.n_columns == 2


def test_lazy_snapshot_survives_concurrent_compaction(tmp_path):
    root = str(tmp_path)
    cat = ColumnCatalog(root, n_perm=64)
    cat.add_table("a", [("x", [f"v{i}" for i in range(64)]),
                        ("y", [f"w{i % 9}" for i in range(64)])])
    cat.add_table("b", [("z", [f"v{i}" for i in range(32)])])
    cat.compact()                          # single segment -> lazy-eligible
    reader = CatalogReader(root, lazy=True)
    pinned = reader.snapshot()
    assert pinned.lazy
    # writer keeps going: drop + compact retires and DELETES the segment
    # files the pinned snapshot memmaps
    cat.drop_table("b")
    cat.compact()
    # POSIX unlink keeps the open mappings valid: every array is still
    # fully readable through the pinned snapshot
    sigs = np.asarray(pinned.signatures)
    nums = np.asarray(pinned.profiles.numeric)
    assert sigs.shape[0] == 3 and np.isfinite(nums).all()
    assert int(sigs.sum()) != 0
    # a fresh snapshot reflects the compacted state
    fresh = reader.snapshot()
    assert fresh.n_columns == 2


# ---------------------------------------------------------------------------
# quantized profile matrices
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_bounds(rng):
    from repro.kernels.profile_distance import (PROFILE_DTYPES, dequantize,
                                                quantize_profiles)
    z = rng.normal(0, 2.0, (257, 21)).astype(np.float32)
    assert set(PROFILE_DTYPES) >= {"fp32", "int8", "fp16"}
    q32, s32 = quantize_profiles(z, "fp32")
    np.testing.assert_array_equal(np.asarray(dequantize(q32, s32)), z)
    q8, s8 = quantize_profiles(z, "int8")
    assert q8.dtype == np.int8
    step = np.abs(z).max(axis=0) / 127.0
    err8 = np.abs(np.asarray(dequantize(q8, s8)) - z).max(axis=0)
    assert (err8 <= step * 0.5 + 1e-6).all()
    q16, s16 = quantize_profiles(z, "fp16")
    assert q16.dtype == np.float16
    err16 = np.abs(np.asarray(dequantize(q16, s16)) - z)
    assert (err16 <= np.abs(z) * 2e-3 + 1e-6).all()
    with pytest.raises(ValueError):
        quantize_profiles(z, "int4")


def test_quantized_topk_parity(small_lake, model, tmp_path):
    """int8/fp16 resident matrices + exact fp32 re-rank reproduce the
    fp32 engine's top-k (the ISSUE parity gate: overlap >= 0.99)."""
    from repro.core import select_queries
    from repro.service import add_lake
    cat = ColumnCatalog(str(tmp_path), n_perm=128)
    add_lake(cat, small_lake)
    snap = cat.snapshot()
    qids = select_queries(small_lake, 16)
    reqs = [DiscoveryRequest(name=f"q{int(q)}", column_id=int(q))
            for q in qids]
    tops = {}
    for dt in ("fp32", "int8", "fp16"):
        eng = DiscoveryEngine(snap, model,
                              EngineConfig(k=10, mode="full",
                                           profile_dtype=dt,
                                           cache_entries=0))
        tops[dt] = [[m.column_id for m in r.matches]
                    for r in eng.query_batch(reqs)]
    for dt in ("int8", "fp16"):
        overlap = np.mean([len(set(a) & set(b)) / max(len(a), 1)
                           for a, b in zip(tops["fp32"], tops[dt])])
        assert overlap >= 0.99, f"{dt} top-k overlap {overlap} vs fp32"


# ---------------------------------------------------------------------------
# tiered candidate generation
# ---------------------------------------------------------------------------

def test_planner_tiered_geometry():
    p = Planner(PlannerConfig())
    # fraction-of-lake sizing with floor / cap / block rounding
    assert p.survivor_budget(1_000_000, 4096) == 2048     # cap
    assert p.survivor_budget(2_000, 400) == 512           # floor
    sb = p.survivor_budget(30_000, 4096)
    assert sb % 32 == 0 and 512 <= sb <= 2048
    assert p.survivor_budget(300, 50) <= 300              # never past lake
    plan = p.plan(n_columns=100_000, n_queries=8, mode="tiered")
    assert plan.candidates == "tiered" and not plan.sharded
    assert plan.survivor_budget == 2048
    # the fine tier never scores wider than the coarse pass gathered
    assert plan.budget <= plan.survivor_budget


def test_tiered_recall_and_events(scaled_snapshot, scaled_lake, model):
    qids = select_scaled_queries(scaled_lake, 12, seed=2)
    engine = DiscoveryEngine(
        scaled_snapshot, model,
        EngineConfig(k=10, mode="tiered", metrics=True,
                     lsh=LSHConfig(n_bands=64, n_coarse_bands=16),
                     candidate_frac=0.2, cache_entries=0))
    rec = measure_recall(engine, qids, k=10)
    assert rec["recall"] >= 0.9
    assert rec["scored_fraction"] < 0.5    # sublinear candidate stage
    assert "tiered" in engine.stats()["last_plan"]["kind"]
    # coarse_pass / fine_probe events folded into the service metrics
    m = engine.metrics.collect()
    assert m["coarse_passes_total"]["values"][""] >= 1
    assert m["fine_probes_total"]["values"][""] >= 1
    hist = m["coarse_survivor_fraction"]["values"]
    assert hist["count"] >= 1
    assert hist["sum"] / hist["count"] <= 0.5


def test_tiered_quantized_matches_tiered_fp32(scaled_snapshot, scaled_lake,
                                              model):
    qids = select_scaled_queries(scaled_lake, 8, seed=4)
    reqs = [DiscoveryRequest(name=f"q{int(q)}", column_id=int(q))
            for q in qids]
    tops = {}
    for dt in ("fp32", "int8"):
        eng = DiscoveryEngine(
            scaled_snapshot, model,
            EngineConfig(k=10, mode="tiered", profile_dtype=dt,
                         lsh=LSHConfig(n_bands=64, n_coarse_bands=16),
                         candidate_frac=0.2, cache_entries=0))
        tops[dt] = [[m.column_id for m in r.matches]
                    for r in eng.query_batch(reqs)]
        # no event bus: the coarse-pass counts still reach the host, in
        # the batch's one blocking copy, and the engine's totals
        tier = eng._executor.last_tier_stats()
        assert tier is not None and tier["hits"].shape == (8,)
        assert tier["reranked"] == (40 if dt == "int8" else 0)
        assert eng.stats()["tiered"]["queries"] == len(reqs)
    overlap = np.mean([len(set(a) & set(b)) / max(len(a), 1)
                       for a, b in zip(tops["fp32"], tops["int8"])])
    assert overlap >= 0.9


# ---------------------------------------------------------------------------
# deadline-aware batch shrink
# ---------------------------------------------------------------------------

def test_scheduler_shrinks_window_to_deadline(scaled_snapshot, model):
    engine = DiscoveryEngine(scaled_snapshot, model,
                             EngineConfig(k=5, cache_entries=0))
    sched = RequestScheduler(engine,
                             SchedulerConfig(max_wait_ms=5_000.0,
                                             max_batch=8))
    try:
        fut = sched.submit(DiscoveryRequest(name="hurry", column_id=0),
                           deadline_ms=80.0)
        # the 5 s coalescing window must be cut to the ~80 ms deadline:
        # the future resolves (either served in time or expired) long
        # before the full window elapses
        try:
            fut.result(timeout=3.0)
        except DeadlineExpired:
            pass
        stats = sched.stats()
        assert stats["window_shrunk"] >= 1
        assert stats["batches"] + stats["expired"] >= 1
    finally:
        sched.close()
