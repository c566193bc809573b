"""The exact threshold selection behind ``stages.gather_candidates``: equal
to ``lax.top_k`` bit for bit, the shape rule that chooses between the two,
and the executor's count of which one each dispatch ran."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import features as FT
from repro.exec import Executor, QueryPlan, stages


def _rows(kind: str, q: int, c: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "random":
        return rng.standard_normal((q, c)).astype(np.float32)
    if kind == "ties":                      # three values fill the row
        return rng.integers(0, 3, (q, c)).astype(np.float32)
    if kind == "signed_zeros":              # +0.0 next to -0.0, and ones
        x = np.where(rng.random((q, c)) < 0.5, 0.0, -0.0)
        x[rng.random((q, c)) < 0.01] = 1.0
        return x.astype(np.float32)
    if kind == "few_finite":                # fewer than k finite entries
        x = np.full((q, c), -np.inf, np.float32)
        m = rng.random((q, c)) < 0.01
        x[m] = rng.standard_normal(int(m.sum()))
        return x
    if kind == "all_neg_inf":
        return np.full((q, c), -np.inf, np.float32)
    if kind == "nan_inf":                   # both NaNs, both infinities
        x = rng.standard_normal((q, c)).astype(np.float32)
        x[:, ::7] = np.nan
        x[:, 3::11] = -np.nan
        x[:, 5::13] = np.inf
        x[:, 6::17] = -np.inf
        return x
    if kind == "tiered_priorities":         # the coarse pass's bands
        hit = rng.random((q, c)) < 0.002
        block = np.repeat(hit.reshape(q, -1, 32).any(-1), 32, axis=1)
        proxy = rng.standard_normal((q, c)).astype(np.float32)
        x = (np.where(block, 4.0, 0.0) + hit + proxy / (1 + np.abs(proxy)))
        x[rng.random((q, c)) < 0.01] = -np.inf
        return x.astype(np.float32)
    raise ValueError(kind)


@pytest.mark.parametrize("kind,q,c,k", [
    ("random", 4, 1000, 7),
    ("random", 9, 3000, 2048),              # rows not a multiple of 8
    ("ties", 4, 1000, 100),
    ("ties", 3, 5000, 2048),
    ("signed_zeros", 8, 1000, 300),
    ("few_finite", 4, 1000, 64),
    ("all_neg_inf", 3, 700, 50),
    ("nan_inf", 4, 1000, 40),
    ("random", 5, 129, 5),                  # C not a multiple of the block
    ("ties", 2, 300, 128),
    ("random", 16, 1000, 1),                # k = 1
    ("ties", 4, 1000, 1),
    ("random", 2, 300, 300),                # k = C
    ("signed_zeros", 3, 257, 257),
    ("tiered_priorities", 8, 4096, 512),
])
def test_threshold_top_k_equals_lax_top_k(kind, q, c, k):
    x = jnp.asarray(_rows(kind, q, c))
    want_v, want_p = jax.lax.top_k(x, k)
    got_v, got_p = jax.jit(stages.threshold_top_k, static_argnums=1)(x, k)
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
    np.testing.assert_array_equal(np.asarray(got_v).view(np.int32),
                                  np.asarray(want_v).view(np.int32))


def test_order_keys_follow_the_total_order():
    x = np.array([np.nan, np.inf, 1.0, 0.0, -0.0, -1.0, -np.inf, -np.nan],
                 np.float32)
    keys = np.asarray(stages.order_keys(jnp.asarray(x)))
    assert (np.diff(keys) < 0).all(), keys
    back = np.asarray(stages._flip(jnp.asarray(keys)))
    np.testing.assert_array_equal(back, x.view(np.int32))


def test_selection_rule_by_shape():
    wide = stages.THRESHOLD_MIN_COLUMNS
    assert stages.selection(745_000, 2048) == "threshold"   # survivor pick
    assert stages.selection(65_000, 4096) == "threshold"    # candidate pick
    assert stages.selection(2048, 2048) == "top_k"          # fine pass
    assert stages.selection(wide, 4096) == "threshold"
    assert stages.selection(wide - 1, 4096) == "top_k"
    assert stages.selection(wide, wide) == "top_k"          # k = C
    assert stages.selection(10 ** 6, (1 << 15) - 1) == "threshold"
    assert stages.selection(10 ** 6, 1 << 15) == "top_k"    # packed counts


def test_gather_candidates_is_the_same_by_either_selection(monkeypatch):
    prio = jnp.asarray(_rows("tiered_priorities", 8, 2048, seed=3))
    want = stages.gather_candidates(prio, 256)
    monkeypatch.setattr(stages, "THRESHOLD_MIN_COLUMNS", 1024)
    assert stages.selection(2048, 256) == "threshold"
    got = stages.gather_candidates(prio, 256)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _tiered_executor(c: int = 600):
    rng = np.random.default_rng(5)
    z = rng.normal(size=(c, FT.F_NUM)).astype(np.float32)
    w = rng.integers(0, 50, (c, FT.F_WORDS)).astype(np.uint32)
    keys = rng.integers(0, 64, (c, 8)).astype(np.uint32)
    coarse = rng.integers(0, 64, (c, 4)).astype(np.uint32)
    gb = (rng.integers(0, FT.F_DIST, (4, 3)).astype(np.int32),
          rng.normal(size=(4, 3)).astype(np.float32),
          rng.normal(size=(4, 8)).astype(np.float32), 0.5)
    ex = Executor(z, w, gb, table_ids=np.arange(c) // 6, band_keys=keys,
                  coarse_keys=coarse, profile_dtype="int8")
    return ex, z, w, keys, coarse


@pytest.mark.parametrize("rule", ["top_k", "threshold"])
def test_selection_counter_counts_each_batch(monkeypatch, rule):
    """Each pruned or tiered dispatch counts the selection of its
    full-width pick once, by the rule the pipeline itself applies; the
    answers are the same bits whichever selection ran."""
    ex, z, w, keys, coarse = _tiered_executor()
    tiered = QueryPlan(candidates="tiered", sharded=False, budget=64, k=5,
                       survivor_budget=128)
    hybrid = QueryPlan(candidates="hybrid", sharded=False, budget=64, k=5)
    full = QueryPlan(candidates="all", sharded=False, budget=600, k=5)

    def serve():
        out = []
        for b in range(3):
            q = np.arange(4 * b, 4 * b + 4)
            args = (z[q], w[q], np.full(4, -1, np.int32), q.astype(np.int32))
            out.append(ex.execute(tiered, *args, qkeys=keys[q],
                                  qcoarse=coarse[q]))
            out.append(ex.execute(hybrid, *args, qkeys=keys[q]))
            ex.execute(full, *args)             # picks nothing: not counted
        return out

    want = serve()
    assert ex.dispatch_stats()["selection"] == {"threshold": 0, "top_k": 6}
    if rule == "threshold":
        monkeypatch.setattr(stages, "THRESHOLD_MIN_COLUMNS", 512)
        jax.clear_caches()                      # retrace under the new rule
        try:
            got = serve()
        finally:
            jax.clear_caches()
        assert ex.dispatch_stats()["selection"] == {"threshold": 6,
                                                    "top_k": 6}
        for a, b in zip(got, want):
            for x, y in zip(a, b):
                np.testing.assert_array_equal(x, y)
    stats = ex.dispatch_stats()
    stats["selection"]["top_k"] += 100           # a copy, not the counter
    assert ex.dispatch_stats()["selection"]["top_k"] == 6
