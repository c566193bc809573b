"""The comparison that decides ``correct``.

Once the window has closed, a sample of the distinct query columns the
window asked about is drawn from the seed, the plain reference
(``reference.ExactScan``) scores each of them against the whole lake, and
every answer the window served for a sampled column is judged by what it
says:

* ``score_mismatch_share``: the share of returned columns whose served
  score is off the reference's exact f32 score of that (query, column)
  pair by more than ``SCORE_TOL``. The served path re-ranks in f32 at the
  end (tiered int8 plans too), so a sound answer's scores are the exact
  scores up to the order of f32 additions, except where a last-bit
  difference of a z-score moves a distance across a tree's threshold. The
  widest such gap, ``score_gap``, is reported beside it and not compared:
  it swings by a whole leaf difference whenever one threshold flips.
* ``bad_answers``: answers that are malformed: fewer than ``k`` columns
  where the lake has ``k`` to give, a column twice, the query itself or a
  column of its own table, or scores that do not fall from first to last.
* ``missed_candidates``: the share of answers that leave out a column
  the served plan surely scored, although that column outranks a returned
  one. Such a column shares at least ``reference.sure_jaccard`` of its
  value set with the query, so the LSH probe over the configuration's
  bands misses it with probability ``MISS_PROB`` at most, and LSH hits
  rank first in the candidate budget; it outranks a returned column
  when its reference score beats the lowest reference score of the
  returned columns by more than ``SCORE_TOL``. A merge or top-k that drops
  the best candidate, or keeps an approximate top k, answers with true
  scores in falling order and fails here.
* ``unanswered``: requests of the window that never came back within the
  grace period, or came back with an error.

``recall`` (the share of the reference's top ``k`` that the served answer
holds, over the same answers) is reported beside them: it is an
end-to-end metric with a bound, not a limit, since the served plans prune
candidates by design.
"""
from __future__ import annotations

import numpy as np

from bench import reference

# A served score that differs from the reference's by more than this took
# another path through some tree: f32 sums of 50 leaves round at ~1e-6,
# and the smallest step a flipped threshold makes is a leaf difference.
SCORE_TOL = 1e-4
# The chance that banded MinHash misses a pair that ``missed_candidates``
# counts as surely scored.
MISS_PROB = 1e-4


def sure_partners(lake, qids: np.ndarray, widths: dict) -> list:
    """Per query, the columns the served plan surely scores for it."""
    j = reference.sure_jaccard(int(widths["n_perm"]), int(widths["n_bands"]),
                               MISS_PROB)
    return reference.value_partners(lake, qids, j)


def sample(qids: np.ndarray, n: int, seed: int) -> np.ndarray:
    distinct = np.unique(np.asarray(qids, np.int64))
    rng = np.random.default_rng([int(seed) % (1 << 64), 0xC4EC])
    return np.sort(rng.choice(distinct, size=min(n, distinct.size),
                              replace=False))


def judge(answers: list, scan_scores: np.ndarray, qids: np.ndarray,
          k: int, partners: list) -> dict:
    """``answers``: (query id, [(column id, score), ...]) pairs for the
    sampled queries; ``scan_scores``: the reference's (len(qids), C)
    scores, -inf where a column may not be returned; ``partners``: per
    sampled query, the columns the served plan surely scores for it."""
    row = {int(q): i for i, q in enumerate(qids)}
    ref_ids, _ = reference.top_k(scan_scores, k)
    eligible = np.isfinite(scan_scores).sum(axis=1)
    gap, bad, hits, total, n_matches, off = 0.0, 0, 0, 0, 0, 0
    missed = 0
    for q, matches in answers:
        i = row[int(q)]
        ids = np.asarray([c for c, _ in matches], np.int64)
        sc = np.asarray([s for _, s in matches], np.float64)
        want = min(k, int(eligible[i]))
        ok = (ids.size == want and np.unique(ids).size == ids.size
              and bool(np.all((ids >= 0) & (ids < scan_scores.shape[1]))))
        if ok:
            ref = scan_scores[i, ids].astype(np.float64)
            ok = bool(np.all(np.isfinite(ref)) and np.all(np.diff(sc) <= 0))
            if np.all(np.isfinite(ref)):
                d = np.abs(sc - ref)
                gap = max(gap, float(np.max(d, initial=0.0)))
                off += int(np.sum(d > SCORE_TOL))
                n_matches += ids.size
        bad += not ok
        sure = partners[i][np.isfinite(scan_scores[i, partners[i]])]
        sure = np.setdiff1d(sure, ids)
        if sure.size:
            inside = ids[(ids >= 0) & (ids < scan_scores.shape[1])]
            floor = (float(np.min(scan_scores[i, inside])) if inside.size
                     else -np.inf)
            missed += bool(np.any(scan_scores[i, sure] > floor + SCORE_TOL))
        top = set(ref_ids[i, :want].tolist())
        hits += len(top & set(ids.tolist()))
        total += len(top)
    return {"score_gap": gap, "bad_answers": bad,
            "score_mismatch_share": off / max(n_matches, 1),
            "missed_candidates": missed / max(len(answers), 1),
            "recall": hits / max(total, 1), "n_answers": len(answers),
            "n_matches": n_matches}


def control_answers(scan_lo, qids: np.ndarray, k: int) -> list:
    """The reference put in the program's place one precision lower: its
    own top ``k`` per query, with its own scores."""
    s = scan_lo.scores(qids)
    ids, sc = reference.top_k(s, k)
    return [(int(q), list(zip(ids[i].tolist(), sc[i].tolist())))
            for i, q in enumerate(qids)]
