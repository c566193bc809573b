"""CPU tests of the benchmark's own machinery: the traffic generator, the
latency clock, finding cells and metrics by name, and the reduction of a
recorded chip trace. Nothing here needs or describes a chip."""
import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, tracing  # noqa: E402
from bench import traffic as trafficgen  # noqa: E402

BENCH = os.path.join(ROOT, "bench")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# a Zipf mix in bursts; no committed cell serves one yet
ZIPF_BURST = {"loop": "open", "rate_of_knee": 0.8,
              "queries": {"pool": "planted", "pool_size": 8192,
                          "draw": "zipf", "s": 1.0},
              "bursts": {"phases": [[1.0, 3.0], [4.0, 0.5]]},
              "buckets": [8, 16, 32]}


def mix(name: str) -> dict:
    if name == "zipf-burst":
        return ZIPF_BURST
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["steady", "zipf-burst"])
def test_open_schedule_same_seed_same_requests(name):
    ids = np.arange(1000, 9000, 3)
    a = trafficgen.open_schedule(mix(name), 240.0, 10.0, ids,
                                 harness.rng_for(2**31 + 7, 1))
    b = trafficgen.open_schedule(mix(name), 240.0, 10.0, ids,
                                 harness.rng_for(2**31 + 7, 1))
    c = trafficgen.open_schedule(mix(name), 240.0, 10.0, ids,
                                 harness.rng_for(2**31 + 8, 1))
    np.testing.assert_array_equal(a.due, b.due)
    np.testing.assert_array_equal(a.qids, b.qids)
    # another seed: the same arrivals, with the queries in another order
    np.testing.assert_array_equal(a.due, c.due)
    assert a.due.size == 2400
    assert not np.array_equal(a.qids, c.qids)
    assert np.all(np.diff(a.due) >= 0) and 0 <= a.due[0] and a.due[-1] < 10
    assert np.isin(a.qids, ids).all()


def test_bursts_hold_the_mean_rate():
    s = trafficgen.open_schedule(mix("zipf-burst"), 100.0, 10.0,
                                 np.arange(50), harness.rng_for(3))
    per_phase, _ = np.histogram(s.due, bins=[0, 1, 5, 6, 10])
    # 1 s at 3x, then 4 s at 0.5x, twice: mean 100 q/s
    np.testing.assert_array_equal(per_phase, [300, 200, 300, 200])


def test_cycle_draw_repeats_only_after_the_whole_pool():
    ids = np.arange(500)
    s = trafficgen.open_schedule(mix("steady"), 120.0, 10.0, ids,
                                 harness.rng_for(11))
    first, second = s.qids[:500], s.qids[500:1000]
    assert np.unique(first).size == 500 and np.unique(second).size == 500


def test_zipf_draw_is_skewed_and_seeded():
    ids = np.arange(10_000, 18_192)
    a = trafficgen.draw(mix("zipf-burst"), ids, 20_000, harness.rng_for(5))
    b = trafficgen.draw(mix("zipf-burst"), ids, 20_000, harness.rng_for(5))
    np.testing.assert_array_equal(a, b)
    _, counts = np.unique(a, return_counts=True)
    top = np.sort(counts)[::-1]
    # rank 1 of Zipf(1.0) over 8192 holds 1/H(8192) ~ 10.5% of requests,
    # and the top 1024 ranks ~ 78%
    assert 0.08 < top[0] / a.size < 0.13
    assert 0.74 < top[:1024].sum() / a.size < 0.82


def test_closed_stream_is_seeded():
    ids = np.arange(300)
    a = trafficgen.stream(mix("closed64"), ids, harness.rng_for(9))
    b = trafficgen.stream(mix("closed64"), ids, harness.rng_for(9))
    xa = [next(a) for _ in range(5000)]
    assert xa == [next(b) for _ in range(5000)]
    assert sorted(xa[:300]) == list(range(300))


def test_latency_is_measured_from_the_due_time():
    recs = [harness.Rec(qid=1, due=10.0, submit=10.5, done=10.6),
            harness.Rec(qid=2, due=11.0, submit=11.0, done=11.2)]
    w = harness.Window(recs=recs, t0=10.0, seconds=2.0, lateness_ms=[],
                       sched_before={}, sched_after={}, engine_before={},
                       engine_after={}, dispatch_before={}, dispatch_after={},
                       compiles=0)
    run = harness.Run(cell=None, window=w, setup_s=0.0, verdict={})
    np.testing.assert_allclose(run.latencies_ms(), [600.0, 200.0])
    assert w.completed_in_window() == 2


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def test_the_committed_cells_resolve():
    layout = harness.Layout()
    for w in layout.spec["workloads"]:
        cell = layout.cell(w["name"])
        assert cell.config["name"] == w["config"]
        names = {m["name"] for m in cell.end_to_end}
        assert {"setup_s", "qps", "p50_ms"} <= names
        for m in cell.per_layer:
            mod = layout.reader("metrics", m["name"])
            assert mod.UNIT == m["unit"] and mod.MOVES == m["moves"]
            assert mod.MOVES in names
        for m in cell.end_to_end:
            assert layout.reader("end_to_end", m["name"]).UNIT == m["unit"]


def test_new_files_alone_add_a_cell_a_mix_and_a_metric(tmp_path):
    """A later change adds a configuration, a traffic mix and a per-layer
    metric as new files only; the harness finds all three by name."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", ".cache", ".work", "__pycache__"))
    with open(bench / "configs" / "canada-65k.json") as f:
        cfg = json.load(f)
    cfg.update(name="portal-9k", knee_qps=123.0)
    cfg["lake"]["n_columns"] = 9000
    (bench / "configs" / "portal-9k.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "trickle.json").write_text(json.dumps(
        {"loop": "open", "rate_of_knee": 0.5, "buckets": [8],
         "queries": {"pool": "planted", "draw": "cycle"}}))
    (bench / "metrics" / "rows_per_batch.py").write_text(
        'LAYER = "front end (service/scheduler.py)"\n'
        'SOURCE = "program_counter"\nUNIT = "rows"\nMOVES = "qps"\n\n\n'
        'def read(run):\n    return 42.0\n')
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "portal-9k", "source": "x",
                            "file": "bench/configs/portal-9k.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "portal-9k.trickle",
                              "config": "portal-9k", "traffic": "trickle",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "rows_per_batch", "unit": "rows",
                              "better": "higher", "source": "program_counter",
                              "layer": "front end (service/scheduler.py)",
                              "moves": "qps",
                              "workloads": ["portal-9k.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    layout = harness.Layout(str(tmp_path), str(bench))
    cell = layout.cell("portal-9k.trickle")
    assert cell.config["lake"]["n_columns"] == 9000
    assert cell.traffic["rate_of_knee"] == 0.5
    assert "rows_per_batch" in [m["name"] for m in cell.per_layer]
    assert "device_idle_share" not in [m["name"] for m in cell.per_layer]
    assert layout.reader("metrics", "rows_per_batch").read(None) == 42.0
    # the existing cells are untouched by the addition
    assert "rows_per_batch" not in [
        m["name"] for m in layout.cell("canada-65k.steady").per_layer]


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        harness.Layout().peaks("cpu")
    assert harness.Layout().peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


# ---------------------------------------------------------------------------
# the reduction of a recorded trace (TPU v5 lite, canada-65k.steady)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chip_trace():
    return tracing.load_json(os.path.join(DATA, "trace-canada-65k.json"))


class _Run:
    def __init__(self, trace):
        self.trace = trace
        self.peaks = harness.Layout().peaks("TPU v5 lite")


def test_busy_and_idle_of_a_recorded_trace(chip_trace):
    t0, t1 = chip_trace.window()
    assert chip_trace.window_s() == pytest.approx(0.4)
    # busy time again on a 1 us grid, independently of the interval union
    grid = np.zeros(int((t1 - t0) / 1e3) + 1, bool)
    for e in chip_trace.ops():
        a = int(max(e.start - t0, 0) / 1e3)
        b = int(min(e.end - t0, t1 - t0) / 1e3)
        grid[a:b] = True
    assert chip_trace.busy_s() == pytest.approx(grid.sum() / 1e6, abs=2e-4)
    gaps = chip_trace.idle_gaps()
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert all(g[0].startswith(("bench.", "idle")) for g in gaps)
    assert sum(g[1] for g in gaps) <= chip_trace.window_s() - \
        chip_trace.busy_s() + 1e-9
    tops = chip_trace.top_ops()
    assert len(tops) == 10
    assert sum(s for _, s in tops) <= chip_trace.busy_s() + 1e-6
    # the scorer's leaf gather, inside its while loop, leads by self time
    assert tops[0][0].startswith("%fusion.41 = f32[131072]")


def test_program_device_ms_of_a_recorded_trace(chip_trace):
    mods = chip_trace.modules()
    want = sum(e.dur for e in mods) / len(mods) / 1e6
    got = harness.Layout().reader("metrics", "program_device_ms").read(
        _Run(chip_trace))
    assert got == pytest.approx(want) and 40 < got < 60


def test_probe_roofline_of_a_recorded_trace(chip_trace):
    from bench.roofline import lsh_probe

    probes = [e for e in chip_trace.ops() if lsh_probe.match(e.name)]
    assert len(probes) == 6
    # (32, 65024) hit mask from 32 x 64 query keys and 65024 x 64 keys,
    # all 4-byte words, bound by HBM at 819 GB/s
    least = 4 * (32 * 64 + 65024 * 64 + 32 * 65024) / 819e9
    assert lsh_probe.work(probes[0].name) == (32 * 65024 * 64,
                                              least * 819e9)
    want = 100 * least * len(probes) / (sum(e.dur for e in probes) / 1e9)
    got = harness.Layout().reader("metrics", "probe_roofline").read(
        _Run(chip_trace))
    assert got == pytest.approx(want) and 0 < got <= 100


# ---------------------------------------------------------------------------
# the reference's value overlap
# ---------------------------------------------------------------------------

def test_value_partners_are_exact_jaccard():
    from bench import lake as lakegen
    from bench import reference

    lake = lakegen.generate(lakegen.LakeSpec(
        n_columns=3000, row_budget=64, vocab_size=40, group_size=8), 2**31 + 3)
    j = reference.sure_jaccard(128, 64, 1e-4)
    # 64 bands of 2 rows miss a pair of Jaccard j with probability 1e-4
    assert (1 - j ** 2) ** 64 == pytest.approx(1e-4)
    qs = np.concatenate([lake.planted()[:30], lake.noise()[:5]])
    sets = [set(r.tolist()) for r in lake.values32]
    for q, got in zip(qs, reference.value_partners(lake, qs, j)):
        want = [c for c, s in enumerate(sets)
                if len(sets[q] & s) / len(sets[q] | s) >= j]
        assert got.tolist() == want
