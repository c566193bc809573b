"""The join-quality model every cell serves: the system's weights.

FREYJA ships one model trained offline and reuses it on every lake. The
benchmark trains it once per checkout with the program's own trainer, on
training lakes drawn from a fixed seed that the configuration names (the
recipe below), and keeps the trees in a git-ignored cache keyed by that
recipe and by a hash of the training code. The reference scores with the
same trees, as a served model's reference runs with the same weights.
"""
from __future__ import annotations

import glob
import hashlib
import json
import os

import numpy as np

# the program's trainer: generator families and the modules they run on
TRAINING_CODE = ("core/*.py",)


def training_code_hash(src: str) -> str:
    h = hashlib.blake2b(digest_size=8)
    for pattern in TRAINING_CODE:
        for path in sorted(glob.glob(os.path.join(src, "repro", pattern))):
            h.update(os.path.basename(path).encode())
            with open(path, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def train(recipe: dict):
    """Trees of the model ``recipe`` names, trained with the program's
    trainer: two plain open-data-like lakes and one adversarial lake
    (granularity chains and surface-form collisions), seeds ``train_seed``,
    +1 and +2."""
    from repro.core import (GBDTConfig, LakeSpec, generate_lake,
                            train_quality_model)

    seed = int(recipe["train_seed"])
    n_tables = int(recipe.get("n_tables", 60))
    row_budget = int(recipe.get("row_budget", 2048))
    plain = [LakeSpec(n_domains=20, n_tables=n_tables, row_budget=row_budget,
                      rows_log_mean=6.8, coverage_range=(0.5, 1.0),
                      gran_ratio=(4, 8), seed=seed + i) for i in (0, 1)]
    hard = LakeSpec(n_domains=24, n_tables=n_tables + 10,
                    row_budget=row_budget, rows_log_mean=6.8,
                    coverage_range=(0.6, 1.0), p_multi_gran=0.9,
                    gran_ratio=(4, 10), n_collision_groups=6,
                    collision_frac=0.8, zipf_range=(0.2, 1.6), seed=seed + 2)
    lakes = [generate_lake(s) for s in plain + [hard]]
    model = train_quality_model(lakes, GBDTConfig(),
                                n_query=int(recipe.get("n_query", 128)))
    return model


def load_or_train(recipe: dict, cache_dir: str, src: str, log=print):
    """(JoinQualityModel, trees tuple, trained_now)."""
    from repro.core import GBDTParams, JoinQualityModel

    key = hashlib.blake2b(json.dumps(recipe, sort_keys=True).encode(),
                          digest_size=6).hexdigest()
    path = os.path.join(cache_dir,
                        f"model-{key}-{training_code_hash(src)}.npz")
    if os.path.exists(path):
        z = np.load(path)
        gbdt = GBDTParams(feats=z["feats"], thrs=z["thrs"],
                          leaves=z["leaves"], base=float(z["base"]))
        model = JoinQualityModel(gbdt=gbdt, train_r2=float(z["train_r2"]))
        trained = False
    else:
        model = train(recipe)
        os.makedirs(cache_dir, exist_ok=True)
        tmp = path + ".tmp.npz"
        g = model.gbdt
        np.savez(tmp, feats=g.feats, thrs=g.thrs, leaves=g.leaves,
                 base=np.float32(g.base), train_r2=np.float32(model.train_r2))
        os.replace(tmp, path)
        trained = True
    g = model.gbdt
    trees = (np.asarray(g.feats, np.int32), np.asarray(g.thrs, np.float32),
             np.asarray(g.leaves, np.float32), np.float32(g.base))
    log(f"model: {g.feats.shape[0]} trees of depth {g.feats.shape[1]}, "
        f"train R2 {model.train_r2:.4f} "
        f"({'trained' if trained else 'cached'}: {os.path.basename(path)})")
    return model, trees, trained
