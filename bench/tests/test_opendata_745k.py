"""The ``opendata-745k`` configuration in small, on the CPU: a pond with
its engine settings (tiered plan, int8 resident profiles, the exact fp32
re-rank, a lazy snapshot) served through the harness like any cell. A
sound run is correct, serving the int8 scan's own scores with the re-rank
bypassed is refused, and the two per-layer metrics of the tiered path
(``coarse_device_ms``, ``rescore_idle_ms``) read exact values on
synthetic traces and stay silent on a trace of a program without their
scope and span; the CPU's traced run holds the re-rank's span."""
import json
import os
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, tracing  # noqa: E402

BENCH = os.path.join(ROOT, "bench")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SECONDS = 1.5
CELL = "opendata-745k.steady"
METRICS = ("coarse_device_ms", "rescore_idle_ms")
US = 1e3                                   # ns per microsecond
DEV, HOST = "/device:TPU:0", "/host:CPU"


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    """The committed configuration with its lake cut to a pond of 3,000
    columns (64 rows) and a model trained on small lakes; engine, widths,
    limits and the traffic mix as committed."""
    root = tmp_path_factory.mktemp("layout")
    bench = root / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", ".cache", ".work", "__pycache__"))
    with open(bench / "configs" / "opendata-745k.json") as f:
        cfg = json.load(f)
    assert cfg["engine"]["mode"] == "tiered"
    assert cfg["engine"]["profile_dtype"] == "int8"
    cfg.update(knee_qps=120.0)
    cfg["lake"].update(n_columns=3000, row_budget=64, vocab_size=40,
                       group_size=8)
    cfg["model"] = {"train_seed": 3, "n_tables": 12, "row_budget": 256,
                    "n_query": 32}
    cfg["reference"] = {"sample_queries": 24, "block_columns": 1024}
    (bench / "configs" / "opendata-745k.json").write_text(json.dumps(cfg))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"] = [w for w in spec["workloads"] if w["name"] == CELL]
    spec["per_layer"] = [m for m in spec["per_layer"]
                         if CELL in m.get("workloads", [CELL])]
    assert {m["name"] for m in spec["per_layer"]} >= set(METRICS)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.Layout(str(root), str(bench))


@pytest.fixture(scope="module", autouse=True)
def private_cache(tmp_path_factory):
    """Model and executables of these runs in a directory of their own."""
    keep = harness.CACHE, harness.WORK
    harness.CACHE = str(tmp_path_factory.mktemp("cache"))
    harness.WORK = str(tmp_path_factory.mktemp("work"))
    yield
    harness.CACHE, harness.WORK = keep


def one_run(layout, seed, hook=None, traced=False):
    return harness.run(layout, CELL, seed, SECONDS, traced,
                       time.perf_counter(), hook=hook)


def test_a_sound_tiered_int8_run_is_correct(layout):
    out = one_run(layout, 2**31 + 29)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 100
    assert set(out["metrics"]) == {"qps", "p50_ms", "recall_at_10",
                                   "setup_s"}
    c = out["checks"]
    assert c["missed_candidates"]["value"] == 0.0
    assert c["bad_answers"]["value"] == 0


def test_serving_the_int8_scan_scores_is_caught(layout):
    """The exact re-rank bypassed: each answer is the int8 scan's own top
    k with its own scores, in falling order. ``score_mismatch_share``
    refuses it."""
    def hook(rig):
        ex = rig.engine._executor
        assert ex.profile_dtype == "int8"

        def bypass(zq, wq, sc, ids, k, extra=()):
            import jax

            sc, ids, *extra = jax.device_get((sc, ids, *extra))
            return sc[:, :k], ids[:, :k], tuple(extra)
        ex._rescore = bypass

    out = one_run(layout, 31, hook=hook)
    assert not out["correct"]
    c = out["checks"]["score_mismatch_share"]
    assert c["value"] > c["limit"]
    assert out["checks"]["bad_answers"]["value"] == 0


def test_a_traced_run_holds_the_rerank_span_in_every_batch(layout,
                                                          monkeypatch):
    """The CPU's trace has no device plane, so both readers read nothing
    there (their exact values are tested on synthetic traces below); the
    program's re-rank span is in it once per batch, around its own
    fetch, put and dispatch."""
    from bench import program_spans

    monkeypatch.setattr(harness.Layout, "peaks", lambda self, kind: {})
    out = one_run(layout, 37, traced=True)
    assert out["correct"], out["checks"]
    assert not set(METRICS) & set(out["metrics"])
    trace = program_spans.load(os.path.join(harness.WORK, "trace"))
    spans = program_spans.spans(trace)
    rerank = [e for e in spans if e.name == "freyja.executor.rescore"]
    executes = [e for e in spans if e.name == "freyja.engine.execute"]
    assert len(rerank) == len(executes) > 0
    for r in rerank:
        inside = {e.name for e in spans if r.start <= e.start
                  and e.end <= r.end and e is not r} - {"freyja.gc"}
        assert inside == {"freyja.executor.fetch", "freyja.executor.put",
                          "freyja.executor.dispatch"}


# ---------------------------------------------------------------------------
# the readers on synthetic and recorded traces
# ---------------------------------------------------------------------------

class _Run:
    def __init__(self, trace):
        self.trace = trace
        self.program_trace = trace


def _read(name, trace):
    return harness.Layout().reader("metrics", name).read(_Run(trace))


def _dev(line, name, a, b, tf_op=None):
    stats = {} if tf_op is None else {"tf_op": tf_op}
    return tracing.Event(DEV, line, name, a * US, (b - a) * US, stats)


def _span(name, a, b):
    return tracing.Event(HOST, "python", name, a * US, (b - a) * US, {})


@pytest.fixture
def tiered_trace():
    """Two tiered batches in a 1,000 us window, each a pipeline module and
    a re-rank module. Device idle: [0, 100], [260, 300], [340, 500],
    [660, 700], [740, 1000]. The re-rank spans hold [250, 310] and
    [650, 720]. The survivor top-k's sort carries no metadata, as XLA's
    TopK splitter leaves it; the relayout copy of the digest keys carries
    the bare name of the argument, ``coarse``."""
    tiered, rescore = "jit__local_tiered(7)", "jit__rescore_exact(9)"
    c = "jit(_local_tiered)/candidates/coarse/"
    device = [
        _dev(tracing.MODULES_LINE, tiered, 100, 260),
        _dev(tracing.MODULES_LINE, rescore, 300, 340),
        _dev(tracing.MODULES_LINE, tiered, 500, 660),
        _dev(tracing.MODULES_LINE, rescore, 700, 740),
        _dev(tracing.OPS_LINE, "%dot", 100, 160, c + "dot_general:"),
        _dev(tracing.OPS_LINE, "%lsh_probe_pallas", 160, 180,
             c + "pallas_call:"),
        _dev(tracing.OPS_LINE, "%sort", 180, 230),
        _dev(tracing.OPS_LINE, "%gather", 230, 260,
             "jit(_local_tiered)/candidates/gather:"),
        _dev(tracing.OPS_LINE, "%while", 300, 340,
             "jit(_rescore_exact)/rescore/score/while:"),
        _dev(tracing.OPS_LINE, "%fusion", 500, 590, c + "sub:"),
        _dev(tracing.OPS_LINE, "%copy", 590, 600, "coarse"),
        _dev(tracing.OPS_LINE, "%fusion.1", 600, 660,
             "jit(_local_tiered)/score/while:"),
        _dev(tracing.OPS_LINE, "%while.1", 700, 740,
             "jit(_rescore_exact)/rescore/score/while:"),
    ]
    host = [
        _span(tracing.WINDOW_SPAN, 0, 1000),
        _span("freyja.engine.execute", 90, 330),
        _span("freyja.executor.rescore", 250, 310),
        _span("freyja.executor.fetch", 250, 265),
        _span("freyja.engine.execute", 480, 730),
        _span("freyja.executor.rescore", 650, 720),
    ]
    return tracing.Trace(device=device, host=host)


def test_coarse_device_ms_counts_the_coarse_scope(tiered_trace):
    # batch 1: 60 + 20 us under coarse and the unlabelled sort's 50 after
    # them; batch 2: 90 us, the argument's copy left out
    assert _read("coarse_device_ms", tiered_trace) == \
        pytest.approx((130 + 90) * 1e-3 / 2, abs=1e-9)


def test_coarse_device_ms_is_silent_on_the_labels_of_a_program_without_it():
    """A tiered pipeline traced without the ``coarse`` scope, as the
    parent's is: its coarse ops sit under ``candidates`` alone, the
    unlabelled sort follows them and the digest keys' copy is labelled
    ``coarse``. Nothing is read."""
    tiered = "jit__local_tiered(7)"
    device = [
        _dev(tracing.MODULES_LINE, tiered, 100, 300),
        _dev(tracing.OPS_LINE, "%copy", 100, 120, "coarse"),
        _dev(tracing.OPS_LINE, "%dot", 120, 180,
             "jit(_local_tiered)/candidates/dot_general:"),
        _dev(tracing.OPS_LINE, "%sort", 180, 260),
        _dev(tracing.OPS_LINE, "%fusion", 260, 300,
             "jit(_local_tiered)/score/while:"),
    ]
    trace = tracing.Trace(device=device,
                          host=[_span(tracing.WINDOW_SPAN, 0, 1000)])
    assert _read("coarse_device_ms", trace) is None


def test_rescore_idle_ms_counts_idle_inside_the_rerank_span(tiered_trace):
    # idle inside [250, 310]: [260, 300]; inside [650, 720]: [660, 700]
    assert _read("rescore_idle_ms", tiered_trace) == \
        pytest.approx((40 + 40) * 1e-3 / 2, abs=1e-9)


def test_the_new_readers_are_silent_on_a_program_without_them():
    """The recorded chip traces of the parent's pruned plan hold neither
    the ``coarse`` scope nor the re-rank span: both readers return None
    and raise nothing."""
    spans = tracing.load_json(os.path.join(DATA, "spans-canada-65k.json"))
    assert spans.host and any(e.stats.get("tf_op") for e in spans.device)
    old = tracing.load_json(os.path.join(DATA, "trace-canada-65k.json"))
    for trace in (spans, old):
        for name in METRICS:
            assert _read(name, trace) is None, name
