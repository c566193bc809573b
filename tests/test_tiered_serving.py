"""The tiered plan over int8 resident profiles, served through the
normal path (``CatalogStore`` -> ``DiscoveryEngine.from_catalog``): its
answers against the exact f32 scan of the same snapshot, and its
coarse-pass counters against the ``coarse_pass`` events."""
import numpy as np
import pytest

from repro.core import features as FT
from repro.core import (GBDTConfig, ScaledLakeSpec, generate_scaled_lake,
                        select_scaled_queries, train_quality_model)
from repro.service import (CatalogStore, DiscoveryEngine, DiscoveryRequest,
                           EngineConfig, LSHConfig)
from repro.service import events as EV

N_COLUMNS = 4096
SCORE_TOL = 1e-4        # f32 sums of the trees' leaves round at ~1e-6
SURE_JACCARD = 0.5      # the 16-lane digest misses such a pair w.p. 1.5e-5


@pytest.fixture(scope="module")
def lake():
    return generate_scaled_lake(ScaledLakeSpec(n_columns=N_COLUMNS, seed=11))


@pytest.fixture(scope="module")
def model(small_lake):
    return train_quality_model([small_lake], GBDTConfig(n_trees=30, depth=4),
                               n_query=64)


@pytest.fixture(scope="module")
def store(lake, tmp_path_factory):
    store = CatalogStore(str(tmp_path_factory.mktemp("tiered")), n_perm=128)
    n_tables = int(lake.table.max()) + 1
    store.add_batch(lake.batch, [f"t{i}" for i in range(n_tables)])
    return store


def tiered_config(**kw):
    return EngineConfig(k=10, mode="tiered", profile_dtype="int8",
                        lsh=LSHConfig(n_bands=64, n_coarse_bands=16),
                        candidate_frac=0.2, cache_entries=0, **kw)


def requests(qids):
    return [DiscoveryRequest(name=f"q{int(q)}", column_id=int(q))
            for q in qids]


def jaccard_row(values: np.ndarray, sentinel, q: int) -> np.ndarray:
    """Exact value-set Jaccard of column ``q`` with every column."""
    mine = np.setdiff1d(np.unique(values[q]), [sentinel])
    inter = np.zeros(values.shape[0])
    for c in range(values.shape[0]):
        row = np.unique(values[c])
        inter[c] = np.intersect1d(mine, row, assume_unique=True).size
    sizes = np.asarray([np.setdiff1d(np.unique(r), [sentinel]).size
                        for r in values])
    return inter / np.maximum(mine.size + sizes - inter, 1)


def test_tiered_int8_answers_hold_against_the_exact_scan(lake, model, store):
    tiered = DiscoveryEngine.from_catalog(store, model, tiered_config())
    assert tiered.snapshot.lazy        # quantized engines map the segment
    # the exact f32 scan of the same snapshot: every column ranked
    exact = DiscoveryEngine(store.snapshot(lazy=True), model, EngineConfig(
        k=N_COLUMNS, mode="full", cache_entries=0))
    qids = select_scaled_queries(lake, 12, seed=3)
    served = tiered.query_batch(requests(qids))
    truth = exact.query_batch(requests(qids))
    values = np.asarray(lake.batch.values32)
    checked = 0
    assert tiered.stats()["tiered"]["reranked_rows"] == 40 * len(qids)
    for q, got, ref in zip(qids, served, truth):
        score = {m.column_id: m.score for m in ref.matches}
        ids = [m.column_id for m in got.matches]
        assert len(ids) == 10
        # returned scores are the exact f32 scores of those pairs
        np.testing.assert_allclose([m.score for m in got.matches],
                                   [score[c] for c in ids], atol=SCORE_TOL,
                                   rtol=0)
        # no surely-scored partner is left out while it outranks an answer
        floor = min(score[c] for c in ids)
        jac = jaccard_row(values, FT.HASH_SENTINEL, int(q))
        for c in np.flatnonzero(jac >= SURE_JACCARD):
            if c in score and c not in ids:
                checked += 1
                assert score[c] <= floor + SCORE_TOL, (int(q), int(c))
    assert checked > 0             # partners left out, not outranking


def test_tiered_counters_equal_the_coarse_pass_events(lake, model, store):
    qids = select_scaled_queries(lake, 19, seed=4)
    batches = [qids[:5], qids[5:13], qids[13:]]
    with_bus = DiscoveryEngine.from_catalog(store, model,
                                            tiered_config(metrics=True))
    cursor = with_bus.events.subscribe("test")
    for b in batches:
        with_bus.query_batch(requests(b))
    coarse = [e.payload for e in cursor.poll()
              if e.type == EV.COARSE_PASS]
    assert [e["n_queries"] for e in coarse] == [len(b) for b in batches]
    got = with_bus.stats()["tiered"]
    assert got["queries"] == len(qids)
    assert got["digest_hits"] == round(sum(e["hits_mean"] * e["n_queries"]
                                           for e in coarse))
    assert got["digest_survivors"] == round(
        sum(e["survivors_mean"] * e["n_queries"] for e in coarse))
    assert got["survivor_slots"] == sum(e["survivor_budget"] * e["n_queries"]
                                        for e in coarse)
    assert got["reranked_rows"] == 40 * len(qids)
    assert 0 < got["digest_hits"] <= got["digest_survivors"]
    # the same batches without a bus count the same
    no_bus = DiscoveryEngine.from_catalog(store, model, tiered_config())
    assert no_bus.events is None
    for b in batches:
        no_bus.query_batch(requests(b))
    assert no_bus.stats()["tiered"] == got


def test_an_fp32_tiered_scan_counts_without_a_rerank(lake, model, store):
    eng = DiscoveryEngine.from_catalog(store, model, EngineConfig(
        k=10, mode="tiered", lsh=LSHConfig(n_bands=64, n_coarse_bands=16),
        candidate_frac=0.2, cache_entries=0))
    qids = select_scaled_queries(lake, 6, seed=5)
    eng.query_batch(requests(qids))
    got = eng.stats()["tiered"]
    assert got["queries"] == 6 and got["reranked_rows"] == 0
    assert got["digest_hits"] > 0
    # a plan that is not tiered leaves the totals alone
    lsh = DiscoveryEngine.from_catalog(store, model, EngineConfig(k=10))
    lsh.query_batch(requests(qids))
    assert lsh.stats()["tiered"]["queries"] == 0
