"""Whole runs of a small cell on the CPU, past the harness's look for a
chip: a sound run is correct, the bfloat16 control is refused, and a timed
path broken underneath (an answer altered where it is produced, half of a
batch's answers left out, the best candidate dropped in the merge) makes
``correct`` come out false.

The cell is tiny (3,000 columns of 64 rows, a model trained on small
lakes) so that a test run can hold it; its configuration, mix and one
per-layer metric are new files in a temporary layout, which the harness
finds by name like any committed cell."""
import json
import os
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import check, control, harness, reference  # noqa: E402
from bench import lake as lakegen  # noqa: E402
from bench import model as modelcache  # noqa: E402

BENCH = os.path.join(ROOT, "bench")
SECONDS = 1.5


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    root = tmp_path_factory.mktemp("layout")
    bench = root / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", ".cache", ".work", "__pycache__"))
    with open(bench / "configs" / "canada-65k.json") as f:
        cfg = json.load(f)
    cfg.update(name="pond", knee_qps=120.0)
    cfg["lake"].update(n_columns=3000, row_budget=64, vocab_size=40,
                       group_size=8)
    cfg["model"] = {"train_seed": 3, "n_tables": 12, "row_budget": 256,
                    "n_query": 32}
    cfg["reference"] = {"sample_queries": 24, "block_columns": 1024}
    (bench / "configs" / "pond.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "ripple.json").write_text(json.dumps(
        {"loop": "open", "rate_of_knee": 0.8, "buckets": [8, 16],
         "queries": {"pool": "planted", "draw": "cycle"}}))
    (bench / "metrics" / "answers_per_batch.py").write_text(
        'LAYER = "front end (service/scheduler.py)"\n'
        'SOURCE = "program_counter"\nUNIT = "q"\nMOVES = "qps"\n\n\n'
        'def read(run):\n'
        '    return len(run.responses()) / run.delta("sched", "batches")\n')
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"] = [{"name": "pond.ripple", "config": "pond",
                          "traffic": "ripple", "chips": 1, "why": "x"}]
    spec["per_layer"] = [{"name": "answers_per_batch", "unit": "q",
                          "better": "higher", "source": "program_counter",
                          "layer": "front end (service/scheduler.py)",
                          "moves": "qps"}]
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
    return harness.run(layout, "pond.ripple", seed, SECONDS, traced,
                       time.perf_counter(), hook=hook)


def test_a_sound_run_is_correct(layout):
    out = one_run(layout, 2**31 + 11)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 100
    assert set(out["metrics"]) == {"qps", "p50_ms",
                                   "recall_at_10", "setup_s"}
    assert out["checks"]["score_mismatch_share"]["value"] == 0.0
    assert out["checks"]["missed_candidates"]["value"] == 0.0
    assert list(out)[-1] == "checks"


def test_a_traced_run_reads_the_new_metric(layout, monkeypatch):
    monkeypatch.setattr(harness.Layout, "peaks", lambda self, kind: {})
    out = one_run(layout, 5, traced=True)
    assert out["correct"], out["checks"]
    assert out["metrics"]["answers_per_batch"]["value"] >= 1.0
    assert "window_s" in out["device"] and "breakdown" in out


def test_an_answer_altered_where_it_is_produced_is_caught(layout):
    def hook(rig):
        ex = rig.engine._executor
        execute = ex.execute

        def altered(*a, **kw):
            sc, ids, n = execute(*a, **kw)
            ids = ids.copy()
            ids[:, [0, 1]] = ids[:, [1, 0]]      # first two columns swapped
            return sc, ids, n
        ex.execute = altered

    out = one_run(layout, 7, hook=hook)
    assert not out["correct"]
    c = out["checks"]["score_mismatch_share"]
    assert c["value"] > c["limit"]


def test_half_of_each_batch_left_out_is_caught(layout):
    def hook(rig):
        eng = rig.engine
        query_batch = eng.query_batch

        def half(requests, **kw):
            out = query_batch(requests, **kw)
            for r in out[::2]:
                r.matches = []
            return out
        eng.query_batch = half

    out = one_run(layout, 8, hook=hook)
    assert not out["correct"]
    assert out["checks"]["bad_answers"]["value"] > 0


def test_the_best_candidate_dropped_in_the_merge_is_caught(layout,
                                                          tmp_path):
    """A selection fault: every answer holds true scores in falling order,
    only the best candidate is left out; ``missed_candidates`` refuses it
    and the score and shape checks do not notice."""
    with control.planted("drop_best", str(tmp_path)):
        out = one_run(layout, 9)
    assert not out["correct"]
    c = out["checks"]
    assert c["missed_candidates"]["value"] > c["missed_candidates"]["limit"]
    assert c["score_mismatch_share"]["value"] == 0.0
    assert c["bad_answers"]["value"] == 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_bfloat16_control_is_refused(layout, seed):
    cell = layout.cell("pond.ripple")
    cfg = cell.config
    _, trees, _ = modelcache.load_or_train(cfg["model"], harness.CACHE,
                                           harness.SRC, log=lambda m: None)
    lake = lakegen.generate(lakegen.LakeSpec.from_config(cfg["lake"]), seed)
    qs = check.sample(lake.planted(), 64, seed)
    exact = reference.ExactScan(lake, trees, block=1024)
    scores = exact.scores(qs)
    partners = check.sure_partners(lake, qs, cfg["widths"])
    # the reference in the program's place at its own precision passes ...
    ids, sc = reference.top_k(scores, 10)
    own = [(int(q), list(zip(ids[i].tolist(), sc[i].tolist())))
           for i, q in enumerate(qs)]
    v = check.judge(own, scores, qs, 10, partners)
    assert v["score_mismatch_share"] == 0.0 and v["bad_answers"] == 0
    assert v["missed_candidates"] == 0.0
    # ... and one precision lower it is refused
    import jax.numpy as jnp

    low = reference.ExactScan(lake, trees, dtype=jnp.bfloat16, block=1024,
                              like=exact)
    v = check.judge(check.control_answers(low, qs, 10), scores, qs, 10,
                    partners)
    assert v["score_mismatch_share"] > \
        float(cfg["limits"]["score_mismatch_share"])
