"""AOT bucket-ladder warmup and the persistent executable cache.

Covers the zero-compile serving contract end-to-end: a warmed engine
serves every ladder bucket through AOT-dispatched executables (no compile
events, no ``compile_ms`` trace attribution, zero fallback dispatches); a
restarted engine re-warms from the on-disk cache without compiling;
signature drift (jax version, device kind, device count) and corrupt
entries degrade to fresh compiles; two engines share one cache directory;
the scheduler holds batch dispatch while a warmup runs; and the
lazy-snapshot + quantized-sidecar path streams the sidecar off the memmap
without ever materializing the lake-sized fp32 z-score matrix.
"""
import dataclasses
import threading
import time

import numpy as np
import pytest

import repro.core.profiles as core_profiles
from repro.core import GBDTConfig, LakeSpec, generate_lake, train_quality_model
from repro.exec import CANDIDATE_KINDS, ExecutableCache, environment_signature
from repro.kernels.profile_distance import (quantize_profiles,
                                            quantize_profiles_streamed)
from repro.service import (ColumnCatalog, DiscoveryEngine, DiscoveryRequest,
                           EngineConfig, LSHConfig, RequestScheduler,
                           SchedulerConfig, add_lake)

BUCKETS = (4, 8)


@pytest.fixture(scope="module")
def warm_lake():
    return generate_lake(LakeSpec(n_domains=6, n_tables=10, row_budget=512,
                                  seed=5))


@pytest.fixture(scope="module")
def model(warm_lake):
    return train_quality_model([warm_lake], GBDTConfig(n_trees=10, depth=3),
                               n_query=32)


@pytest.fixture(scope="module")
def catalog_dir(tmp_path_factory, warm_lake):
    root = str(tmp_path_factory.mktemp("warm_catalog"))
    cat = ColumnCatalog(root)
    add_lake(cat, warm_lake)
    cat.compact()          # single segment: the lazy fast path needs it
    return root


def _config(**kw):
    kw.setdefault("k", 4)
    kw.setdefault("mode", "lsh")
    kw.setdefault("lsh", LSHConfig(n_bands=16, n_coarse_bands=4))
    kw.setdefault("batch_buckets", BUCKETS)
    return EngineConfig(**kw)


def _engine(catalog_dir, model, **kw):
    return DiscoveryEngine.from_catalog(ColumnCatalog(catalog_dir), model,
                                        _config(**kw))


def _reqs(n):
    return [DiscoveryRequest(name=f"q{i}", column_id=i) for i in range(n)]


def _match_rows(responses):
    return [[(m.column_id, round(m.score, 5)) for m in r.matches]
            for r in responses]


# ---------------------------------------------------------------------------
# warmed serving: no compiles on the request path
# ---------------------------------------------------------------------------

def test_warmed_engine_serves_every_bucket_without_compiles(
        catalog_dir, model, tmp_path):
    eng = _engine(catalog_dir, model, metrics=True, warmup="serve",
                  executable_cache_dir=str(tmp_path / "cache"))
    rep = eng.warmup_report
    assert rep is not None and eng.warm_event.is_set()
    assert rep["scope"] == "serve" and rep["buckets"] == list(BUCKETS)
    assert rep["n_executables"] > 0
    assert rep["cache_misses"] == rep["n_executables"]  # cold start
    assert rep["wall_ms"] > 0

    cursor = eng.events.subscribe("test")     # tails only post-warmup events
    for b in BUCKETS:
        for r in eng.query_batch(_reqs(b)):
            assert not any("compile_ms" in s for s in r.trace), r.trace
    types = [ev.type for ev in cursor.poll()]
    assert "compile_begin" not in types and "compile_end" not in types
    stats = eng._executor.dispatch_stats()
    assert stats["fallback"] == 0 and stats["aot"] > 0


def test_warmup_installs_default_ladder_when_none(catalog_dir, model):
    eng = _engine(catalog_dir, model, batch_buckets=None)
    assert not eng.planner.config.batch_buckets
    rep = eng.warmup("serve")
    from repro.exec import DEFAULT_BATCH_BUCKETS
    assert tuple(eng.planner.config.batch_buckets) == DEFAULT_BATCH_BUCKETS
    assert rep["buckets"] == sorted(DEFAULT_BATCH_BUCKETS)


# ---------------------------------------------------------------------------
# persistent cache: restart, invalidation, corruption, sharing
# ---------------------------------------------------------------------------

def test_restart_reuses_persisted_executables(catalog_dir, model, tmp_path):
    cache_dir = str(tmp_path / "cache")
    e1 = _engine(catalog_dir, model, warmup="serve",
                 executable_cache_dir=cache_dir)
    r1 = e1.warmup_report
    assert r1["cache_misses"] == r1["n_executables"] > 0

    e2 = _engine(catalog_dir, model, warmup="serve",
                 executable_cache_dir=cache_dir)
    r2 = e2.warmup_report
    assert r2["cache_misses"] == 0
    assert r2["cache_hits"] == r1["n_executables"]
    # deserialized executables produce the compiled executables' results
    out1 = _match_rows(e1.query_batch(_reqs(BUCKETS[0])))
    out2 = _match_rows(e2.query_batch(_reqs(BUCKETS[0])))
    assert out1 == out2
    assert e2._executor.dispatch_stats()["fallback"] == 0


@pytest.mark.parametrize("drift", [{"jax": "0.0.0-different"},
                                   {"device_kind": "TPU v9"},
                                   {"n_devices": 1234},
                                   {"sources": "0" * 32}])
def test_environment_drift_invalidates_entries(catalog_dir, model, tmp_path,
                                               drift):
    cache_dir = str(tmp_path / "cache")
    e1 = _engine(catalog_dir, model, warmup="serve",
                 executable_cache_dir=cache_dir)
    n = e1.warmup_report["n_executables"]

    e2 = _engine(catalog_dir, model)
    e2._exec_cache = ExecutableCache(
        cache_dir, env={**environment_signature(), **drift})
    rep = e2.warmup("serve")
    assert rep["cache_hits"] == 0 and rep["cache_misses"] == n


def _digest_with_edit(monkeypatch, is_edited):
    """``aot.source_digest()`` as it reads with a line appended to every
    source file for which ``is_edited(path)`` holds."""
    from pathlib import Path

    from repro.exec import aot

    read = Path.read_bytes

    def edited(path):
        data = read(path)
        return data + b"\n# edited\n" if is_edited(path) else data

    monkeypatch.setattr(Path, "read_bytes", edited)
    aot.source_digest.cache_clear()
    try:
        return aot.source_digest()
    finally:
        monkeypatch.undo()
        aot.source_digest.cache_clear()


def test_edited_pipeline_source_changes_the_key(monkeypatch, tmp_path):
    """An edit to a traced source moves every key: a warm cache can never
    serve the executable of the pipeline as it was before the edit."""
    from repro.exec import aot

    before = aot.environment_signature()
    assert before["sources"] == aot.source_digest()
    after = {**before, "sources": _digest_with_edit(
        monkeypatch, lambda path: path.name == "stages.py")}
    assert after["sources"] != before["sources"]
    sig = dict(name="_local_pruned", statics={"k": 10}, avals=[[[8], "f32"]])
    old, new = (ExecutableCache(tmp_path, env=env) for env in (before, after))
    assert new._path(new.signature(**sig)) != old._path(old.signature(**sig))
    assert aot.environment_signature() == before


@pytest.mark.parametrize("edited_file", ["core/gbdt.py", "core/quality.py",
                                         "service/engine.py"])
def test_edit_outside_exec_and_kernels_changes_the_key(monkeypatch,
                                                       edited_file):
    """The digest covers every source of the package, so an edit to a
    module the pipelines reach through an import (``kernels/ops.py`` ->
    ``core.gbdt``, ``kernels/ref.py`` -> ``core.quality``) moves it too."""
    from pathlib import Path

    from repro.exec import aot

    target = Path(aot.__file__).resolve().parents[1] / edited_file
    assert target.is_file()
    before = aot.source_digest()
    assert _digest_with_edit(monkeypatch, lambda p: p == target) != before
    assert aot.source_digest() == before


def test_corrupt_entries_fall_back_to_fresh_compiles(catalog_dir, model,
                                                     tmp_path):
    cache_dir = tmp_path / "cache"
    e1 = _engine(catalog_dir, model, warmup="serve",
                 executable_cache_dir=str(cache_dir))
    n = e1.warmup_report["n_executables"]
    entries = list(cache_dir.glob("*.exe"))
    assert len(entries) == n
    for p in entries:
        p.write_bytes(b"not a pickled executable")

    e2 = _engine(catalog_dir, model, warmup="serve",
                 executable_cache_dir=str(cache_dir))
    rep = e2.warmup_report
    assert rep["cache_hits"] == 0 and rep["cache_misses"] == n
    assert e2._exec_cache.stats["errors"] >= n
    out1 = _match_rows(e1.query_batch(_reqs(BUCKETS[0])))
    out2 = _match_rows(e2.query_batch(_reqs(BUCKETS[0])))
    assert out1 == out2
    # the fresh compiles re-stored good entries: a third start hits
    e3 = _engine(catalog_dir, model, warmup="serve",
                 executable_cache_dir=str(cache_dir))
    assert e3.warmup_report["cache_hits"] == n


def test_two_engines_share_one_cache_dir(catalog_dir, model, tmp_path):
    cache_dir = str(tmp_path / "cache")
    engines, errors = [None, None], []

    def boot(slot):
        try:
            engines[slot] = _engine(catalog_dir, model, warmup="serve",
                                    executable_cache_dir=cache_dir)
        except BaseException as e:   # surfaced in the main thread below
            errors.append(e)

    threads = [threading.Thread(target=boot, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors
    reps = [e.warmup_report for e in engines]
    for rep in reps:
        assert rep["cache_hits"] + rep["cache_misses"] + \
            rep["already_warm"] == rep["n_executables"]
    outs = [_match_rows(e.query_batch(_reqs(BUCKETS[0]))) for e in engines]
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# scheduler integration + metrics
# ---------------------------------------------------------------------------

def test_scheduler_holds_dispatch_until_warm(catalog_dir, model):
    eng = _engine(catalog_dir, model)
    with RequestScheduler(eng, SchedulerConfig(batch_buckets=BUCKETS,
                                               max_wait_ms=1.0)) as sch:
        eng.warm_event.clear()       # a warmup is "running"
        fut = sch.submit(DiscoveryRequest(name="held", column_id=0))
        time.sleep(0.25)
        assert not fut.done()
        eng.warm_event.set()
        assert fut.result(timeout=30).name == "held"
        assert sch.stats()["warm_held"] >= 1


def test_warmup_metrics_and_exposition(catalog_dir, model, tmp_path):
    eng = _engine(catalog_dir, model, metrics=True, warmup="serve",
                  executable_cache_dir=str(tmp_path / "cache"))
    rep = eng.warmup_report
    snap = eng.metrics.collect()
    assert snap["warmups_total"]["values"][""] == 1.0
    assert snap["executable_cache_misses_total"]["values"][""] == \
        rep["cache_misses"]
    assert snap["warmup_remaining"]["values"][""] == 0.0
    # warmup compiles land in the same compile_ms histogram first-contact
    # serving compiles feed
    assert snap["compile_ms"]["values"]["count"] == rep["cache_misses"]
    from repro.service.metrics import parse_exposition
    parsed = parse_exposition(eng.metrics.render())
    assert "warmup_remaining" in parsed
    assert parsed["executable_cache_misses_total"][""] == rep["cache_misses"]


def test_refresh_rewarms_new_version(catalog_dir, model, tmp_path):
    eng = _engine(catalog_dir, model, metrics=True, warmup="serve",
                  batch_buckets=(4,),
                  executable_cache_dir=str(tmp_path / "cache"))
    writer = ColumnCatalog(catalog_dir)
    if "warm_refresh_demo" not in writer.tables():
        writer.add_table("warm_refresh_demo",
                         [("ids", [f"wr_{i}" for i in range(50)])])
    eng.refresh(ColumnCatalog(catalog_dir).snapshot())
    assert eng.warm_event.is_set()
    assert eng.warmup_report["n_executables"] > 0
    cursor = eng.events.subscribe("test")
    for r in eng.query_batch(_reqs(4)):
        assert not any("compile_ms" in s for s in r.trace)
    types = [ev.type for ev in cursor.poll()]
    assert "compile_begin" not in types


# ---------------------------------------------------------------------------
# plan_set enumeration
# ---------------------------------------------------------------------------

def test_plan_set_serve_scope_covers_served_and_baseline(catalog_dir, model):
    eng = _engine(catalog_dir, model)
    plans = eng.planner.plan_set(n_columns=eng.n_columns, n_queries=4,
                                 mode="lsh", scope="serve")
    kinds = {p.candidates for p in plans}
    assert "all" in kinds            # the recall baseline rides along
    assert len(kinds) == len(plans) == 2


def test_plan_set_full_scope_enumerates_admissible_kinds(catalog_dir, model):
    eng = _engine(catalog_dir, model)
    plans = eng.planner.plan_set(n_columns=eng.n_columns, n_queries=4,
                                 mode="lsh", scope="full")
    kinds = {p.candidates for p in plans}
    assert kinds.issuperset(set(CANDIDATE_KINDS) & {"all", "lsh", "hybrid"})
    assert "tiered" in kinds         # n_coarse_bands > 0 admits it
    keys = [(p.candidates, p.sharded, p.budget, p.k, p.grid,
             p.survivor_budget) for p in plans]
    assert len(keys) == len(set(keys))          # deduped
    with pytest.raises(ValueError):
        eng.planner.plan_set(n_columns=eng.n_columns, scope="everything")


# ---------------------------------------------------------------------------
# lazy snapshots: streamed quantized sidecar, no eager z-score pass
# ---------------------------------------------------------------------------

def test_streamed_quantizer_matches_eager_bytes(catalog_dir):
    prof = ColumnCatalog(catalog_dir).snapshot().profiles
    z = prof.zscored.astype(np.float32)
    for dt in ("int8", "fp16", "fp32"):
        a, sa = quantize_profiles(z, dt)
        b, sb = quantize_profiles_streamed(prof.numeric, prof.mean,
                                           prof.std, dt, block=17)
        assert a.dtype == b.dtype
        assert np.array_equal(a, b) and np.array_equal(sa, sb)
    with pytest.raises(ValueError):
        quantize_profiles_streamed(prof.numeric, prof.mean, prof.std, "int4")


def test_lazy_int8_engine_never_materializes_zscores(catalog_dir, model,
                                                     monkeypatch):
    cat = ColumnCatalog(catalog_dir)
    cat.compact()       # back to one segment (an earlier test may append)
    snap = cat.snapshot(lazy=True)
    assert snap.lazy
    # same arrays + moments through the legacy eager build path
    legacy = dataclasses.replace(snap, lazy=False)

    def boom(self):
        raise AssertionError("lazy path materialized the fp32 z-score "
                             "matrix")

    monkeypatch.setattr(core_profiles.LakeProfiles, "zscored",
                        property(boom))
    e_lazy = DiscoveryEngine(snap, model, _config(profile_dtype="int8"))
    lazy_out = _match_rows(e_lazy.query_batch(_reqs(6)))
    monkeypatch.undo()

    e_legacy = DiscoveryEngine(legacy, model, _config(profile_dtype="int8"))
    assert lazy_out == _match_rows(e_legacy.query_batch(_reqs(6)))


def test_zscore_view_indexing(catalog_dir):
    prof = ColumnCatalog(catalog_dir).snapshot().profiles
    view = prof.zscored_view()
    full = prof.zscored.astype(np.float32)
    assert view.shape == full.shape and len(view) == full.shape[0]
    assert np.array_equal(view[3], full[3])
    idx2d = np.array([[0, 2], [5, 1]])
    assert np.array_equal(view[idx2d], full[idx2d])
    assert view[idx2d].dtype == np.float32
