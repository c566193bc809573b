"""The served scorer (``core.predictor.gbdt_predict_ref``) against the
per-pair gather formulation it replaced and against the numpy trainer's
``predict_np``.

The leaf of each oblivious tree is picked with a select tree over the level
compares; the gather of one of ``2^D`` leaf values per pair it replaced is
kept here as the oracle. Same compares, same f32 sums in the same order, so
the scores must agree to the bit, at every depth and input rank, with
inputs set on the thresholds and one ulp either side.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import features as FT
from repro.core.gbdt import GBDTParams, predict_np
from repro.core.predictor import gbdt_predict_ref

N_TREES = 8


def gather_predict(params_tuple, x):
    """The former served formulation: pack the level bits into a leaf index
    and gather the leaf value for every pair."""
    feats, thrs, leaves, base = params_tuple
    d = feats.shape[1]

    def tree(carry, tp):
        f_l, t_l, lv = tp
        xf = jnp.take(x, f_l, axis=-1)
        bits = (xf >= t_l).astype(jnp.int32)
        idx = jnp.sum(bits * (2 ** jnp.arange(d, dtype=jnp.int32)), axis=-1)
        return carry + jnp.take(lv, idx, axis=0), None

    out, _ = jax.lax.scan(tree, jnp.full(x.shape[:-1], base, jnp.float32),
                          (jnp.asarray(feats), jnp.asarray(thrs),
                           jnp.asarray(leaves)))
    return out


def _params(depth: int, seed: int = 0) -> GBDTParams:
    rng = np.random.default_rng(seed)
    return GBDTParams(
        feats=rng.integers(0, FT.F_DIST, (N_TREES, depth)).astype(np.int32),
        thrs=rng.normal(0, 1, (N_TREES, depth)).astype(np.float32),
        leaves=rng.uniform(-0.1, 0.1, (N_TREES, 2 ** depth)).astype(np.float32),
        base=0.3)


def _inputs(p: GBDTParams, shape, seed: int = 1) -> np.ndarray:
    """Normal inputs with a third of the rows put on a split's threshold:
    exactly on it, one ulp below or one ulp above."""
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (*shape, FT.F_DIST)).astype(np.float32)
    flat = x.reshape(-1, FT.F_DIST)
    for r in range(0, flat.shape[0], 3):
        t, lvl = rng.integers(0, N_TREES), rng.integers(0, p.depth)
        thr = p.thrs[t, lvl]
        flat[r, p.feats[t, lvl]] = [
            thr, np.nextafter(thr, np.float32(-np.inf)),
            np.nextafter(thr, np.float32(np.inf))][(r // 3) % 3]
    return x


@pytest.mark.parametrize("shape", [(96,), (4, 24)], ids=["N,F", "Q,M,F"])
@pytest.mark.parametrize("depth", [1, 4, 5])
def test_select_leaf_pick_matches_gather_bitwise(depth, shape):
    p = _params(depth)
    x = _inputs(p, shape)
    pt = tuple(map(jnp.asarray, p.astuple()))
    got = np.asarray(gbdt_predict_ref(pt, jnp.asarray(x)))
    want = np.asarray(gather_predict(pt, jnp.asarray(x)))
    assert got.shape == shape
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    np.testing.assert_allclose(
        got.reshape(-1), predict_np(p, x.reshape(-1, FT.F_DIST)),
        rtol=0, atol=1e-6)


def _gathers_reading(jaxpr, var) -> int:
    """Gathers in ``jaxpr`` (nested calls and loops included) whose
    operand is ``var``."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather" and eqn.invars[0] is var:
            n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += sum(_gathers_reading(sub, inner)
                     for outer, inner in zip(eqn.invars, sub.invars)
                     if outer is var)
    return n


@pytest.mark.parametrize("predict,n_leaf_gathers",
                         [(gbdt_predict_ref, 0), (gather_predict, 1)],
                         ids=["served", "gather_oracle"])
def test_tree_loop_reads_no_leaf_row_by_gather(predict, n_leaf_gathers):
    """The tree loop's body holds no gather from the scanned leaf row (the
    oracle's one shows the check can see it)."""
    pt = tuple(map(jnp.asarray, _params(5).astuple()))
    closed = jax.make_jaxpr(lambda x: predict(pt, x))(
        jnp.zeros((4, 24, FT.F_DIST), jnp.float32))
    (scan,) = [e for e in closed.jaxpr.eqns if e.primitive.name == "scan"]
    body = scan.params["jaxpr"].jaxpr
    leaf_row = body.invars[-1]                  # xs = (feats, thrs, leaves)
    assert leaf_row.aval.shape == (32,)
    assert _gathers_reading(body, leaf_row) == n_leaf_gathers
