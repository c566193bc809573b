"""Join-quality prediction from profiles (paper Section IV-B).

Pipeline: z-score numeric profiles lake-wide → per-pair distance vector
(|Δz| per numeric feature + frequent-word overlap + first-word equality) →
regression model (oblivious GBDT; optional MLP) → predicted continuous
quality Q(A,B,s).

The model is trained once on a synthetic lake at s = 0.25 (as the paper's
released model is) and reused across lakes with no fine-tuning; benchmarks
validate the generalization claim on held-out lakes with different seeds and
spec parameters.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import features as FT
from repro.core import quality
from repro.core.gbdt import GBDTConfig, GBDTParams, fit_gbdt, predict_np
from repro.core.lakegen import Lake
from repro.core.profiles import LakeProfiles, profile_lake
from repro.core.sketches import batch_exact_metrics


# ---------------------------------------------------------------------------
# distance features (pure-jnp reference; the Pallas kernel mirrors this)
# ---------------------------------------------------------------------------

def distance_features_ref(z_a, words_a, z_b, words_b):
    """Distance vector for pairs. Shapes: z (…, F_NUM), words (…, F_WORDS).

    Broadcasting: ``z_a``/``words_a`` of shape (Q, 1, F), ``z_b``/``words_b``
    of shape (1, N, F) yield (Q, N, F_DIST).
    """
    d_num = jnp.abs(z_a - z_b)
    top_a = words_a[..., :FT.N_FREQ_WORDS]
    top_b = words_b[..., :FT.N_FREQ_WORDS]
    sent = jnp.uint32(FT.HASH_SENTINEL)
    eq = (top_a[..., :, None] == top_b[..., None, :]) & (top_a[..., :, None] != sent)
    overlap = jnp.sum(eq.any(axis=-1).astype(jnp.float32), axis=-1) / FT.N_FREQ_WORDS
    fw_a = words_a[..., FT.FIRST_WORD]
    fw_b = words_b[..., FT.FIRST_WORD]
    first_eq = ((fw_a == fw_b) & (fw_a != sent)).astype(jnp.float32)
    return jnp.concatenate(
        [d_num, overlap[..., None], first_eq[..., None]], axis=-1)


def pairwise_distances(profiles: LakeProfiles, query_ids: np.ndarray) -> jnp.ndarray:
    """(Q, N, F_DIST) distance tensor for query columns vs the whole lake."""
    z = jnp.asarray(profiles.zscored, jnp.float32)
    w = jnp.asarray(profiles.words)
    zq, wq = z[query_ids], w[query_ids]
    return distance_features_ref(zq[:, None, :], wq[:, None, :], z[None], w[None])


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class JoinQualityModel:
    gbdt: GBDTParams
    strictness: float = quality.DEFAULT_STRICTNESS
    train_r2: float = float("nan")

    def save(self, path: str) -> None:
        np.savez(path, feats=self.gbdt.feats, thrs=self.gbdt.thrs,
                 leaves=self.gbdt.leaves, base=np.float32(self.gbdt.base),
                 strictness=np.float32(self.strictness),
                 train_r2=np.float32(self.train_r2))

    @staticmethod
    def load(path: str) -> "JoinQualityModel":
        z = np.load(path)
        return JoinQualityModel(
            gbdt=GBDTParams(feats=z["feats"], thrs=z["thrs"], leaves=z["leaves"],
                            base=float(z["base"])),
            strictness=float(z["strictness"]), train_r2=float(z["train_r2"]))


def exact_jk(lake: Lake, query_ids: np.ndarray, corpus_ids: np.ndarray | None = None,
             chunk: int = 64):
    """Exact (J, K) for query×corpus pairs from packed sketches (chunked)."""
    p = lake.packed
    cids = np.arange(lake.n_columns) if corpus_ids is None else corpus_ids
    cv, cc = jnp.asarray(p.values[cids]), jnp.asarray(p.counts[cids])
    ccard, crows = jnp.asarray(p.card[cids]), jnp.asarray(p.n_rows[cids])
    out_j, out_k = [], []
    for i in range(0, len(query_ids), chunk):
        q = query_ids[i:i + chunk]
        m = batch_exact_metrics(jnp.asarray(p.values[q]), jnp.asarray(p.counts[q]),
                                jnp.asarray(p.card[q]), jnp.asarray(p.n_rows[q]),
                                cv, cc, ccard, crows)
        out_j.append(np.asarray(m["j_multi"]))
        out_k.append(np.asarray(m["k"]))
    return np.concatenate(out_j), np.concatenate(out_k)


def build_training_set(lake: Lake, profiles: LakeProfiles | None = None,
                       n_query: int = 192, strictness: float = quality.DEFAULT_STRICTNESS,
                       seed: int = 0):
    """(X, y) training pairs: distance features -> continuous quality label."""
    rng = np.random.default_rng(seed)
    profiles = profiles if profiles is not None else profile_lake(lake.batch)
    c = lake.n_columns
    qids = rng.choice(c, size=min(n_query, c), replace=False)
    j, k = exact_jk(lake, qids)                           # (Q, N)
    d = np.asarray(pairwise_distances(profiles, qids))    # (Q, N, F_DIST)
    y = np.asarray(quality.continuous_quality(jnp.asarray(j), jnp.asarray(k), strictness))

    # drop self pairs; subsample the huge zero-quality mass for balance
    qi = np.repeat(qids, c)
    ci = np.tile(np.arange(c), len(qids))
    keep = qi != ci
    x = d.reshape(-1, FT.F_DIST)[keep]
    yy = y.reshape(-1)[keep]
    pos = yy > 0.02
    neg = np.flatnonzero(~pos)
    n_neg = min(len(neg), max(1, 3 * int(pos.sum())))
    sel = np.concatenate([np.flatnonzero(pos), rng.choice(neg, size=n_neg, replace=False)])
    rng.shuffle(sel)
    return x[sel].astype(np.float32), yy[sel].astype(np.float32)


def train_quality_model(lakes: list[Lake], cfg: GBDTConfig = GBDTConfig(),
                        strictness: float = quality.DEFAULT_STRICTNESS,
                        n_query: int = 192, seed: int = 0) -> JoinQualityModel:
    xs, ys = [], []
    for i, lake in enumerate(lakes):
        x, y = build_training_set(lake, n_query=n_query, strictness=strictness,
                                  seed=seed + i)
        xs.append(x)
        ys.append(y)
    x = np.concatenate(xs)
    y = np.concatenate(ys)
    params = fit_gbdt(x, y, cfg)
    pred = predict_np(params, x)
    ss_res = float(np.sum((pred - y) ** 2))
    ss_tot = float(np.sum((y - y.mean()) ** 2)) or 1.0
    return JoinQualityModel(gbdt=params, strictness=strictness,
                            train_r2=1.0 - ss_res / ss_tot)


# ---------------------------------------------------------------------------
# inference: the served scorer (exec/stages.score_columns calls it)
# ---------------------------------------------------------------------------

def _pick_leaf(bits, lv, offset: int, depth: int):
    """Value of the leaf the level ``bits`` pick from the tree's leaf row
    ``lv``: leaf index ``sum(bits[l] << l)``, chosen by selects from the top
    level down (``2^depth - 1`` of them) over static slices of ``lv``."""
    if depth == 0:
        return lv[offset]
    hi = offset + (1 << (depth - 1))
    return jnp.where(bits[depth - 1], _pick_leaf(bits, lv, hi, depth - 1),
                     _pick_leaf(bits, lv, offset, depth - 1))


def gbdt_predict_ref(params_tuple, x: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp oblivious-GBDT inference. x: (..., F) -> (...).

    This is the served scorer: every executor path scores through it (via
    ``exec.stages.score_columns``), on every platform. Each tree's leaf is
    picked with a select tree over its level compares rather than a gather
    from the leaf row: a per-pair gather of one of ``2^D`` values costs a
    TPU v5e ≈ 8 ns per element, the selects a few vector ops. The f32 sums
    are the same (``base``, then one leaf per tree in order), and so are
    the bits."""
    feats, thrs, leaves, base = params_tuple
    d = feats.shape[1]

    def tree(carry, tp):
        f_l, t_l, lv = tp
        xf = jnp.take(x, f_l, axis=-1)                     # (..., D)
        bits = [xf[..., lvl] >= t_l[lvl] for lvl in range(d)]
        return carry + _pick_leaf(bits, lv, 0, d), None

    out, _ = jax.lax.scan(tree, jnp.full(x.shape[:-1], base, jnp.float32),
                          (jnp.asarray(feats), jnp.asarray(thrs), jnp.asarray(leaves)))
    return out


def predict_scores_ref(model: JoinQualityModel, profiles: LakeProfiles,
                       query_ids: np.ndarray) -> np.ndarray:
    """(Q, N) predicted join quality for query columns vs the lake."""
    d = pairwise_distances(profiles, query_ids)
    return np.asarray(gbdt_predict_ref(
        tuple(map(jnp.asarray, model.gbdt.astuple())), d))
