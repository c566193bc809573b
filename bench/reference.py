"""Plain reference of join discovery: the exact f32 scan.

The same semantics as the served path, written out once with no candidate
pruning, no quantization, no batching buckets and no cache, and sharing no
code with the program: each column's profile (21 numeric slots and 11 word
hashes) is computed from the lake's own arrays, z-scored with the
lake-wide mean and standard deviation, compared with every other column
(|dz| per slot, top-10 word overlap, first-word equality), and scored by
the oblivious-tree ensemble. A query's answer is the top ``k`` columns by
that score, leaving out the query itself and the columns of its table.

Work runs on the default device in column blocks, so that the reference
fits next to nothing at any lake size. ``dtype`` set to bfloat16 computes
the scan one precision below the configuration's float32: the benchmark's
control, which the comparison in ``check.py`` must refuse.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F_NUM = 21
N_FREQ_WORDS = 10
F_WORDS = N_FREQ_WORDS + 1
SENTINEL = np.uint32(0xFFFFFFFF)


# ---------------------------------------------------------------------------
# profiles
# ---------------------------------------------------------------------------

def _masked_stats(x, valid, nf):
    big = jnp.float32(3.4e38)
    mn = jnp.min(jnp.where(valid, x, big))
    mx = jnp.max(jnp.where(valid, x, -big))
    s = jnp.sum(jnp.where(valid, x, 0.0))
    s2 = jnp.sum(jnp.where(valid, x * x, 0.0))
    mean = s / nf
    var = jnp.maximum(s2 / nf - mean * mean, 0.0)
    return mn, mx, mean, jnp.sqrt(var)


def _profile_column(vals, char_len, word_cnt, n):
    """One column -> (21 numeric slots, 11 word hashes).

    Numeric slots: log1p(cardinality), uniqueness, entropy, log1p(min and
    max frequency), max and sd of the frequency shares, 7 interior
    octiles of the frequency distribution, longest / shortest / mean
    characters, mean / min / max / sd of words per value. Words: the 10
    most frequent values, then the smallest value (first-word proxy)."""
    sent = jnp.uint32(SENTINEL)
    r = vals.shape[0]
    idx = jnp.arange(r, dtype=jnp.int32)
    nf = jnp.maximum(n.astype(jnp.float32), 1.0)
    has_rows = n > 0
    sv = jnp.sort(vals)
    is_valid = sv != sent
    is_start = is_valid & ((idx == 0) | (sv != jnp.roll(sv, 1)))
    card = jnp.sum(is_start.astype(jnp.int32))
    # run k of the sorted values spans [start_pos[k], start_pos[k + 1]),
    # the last valid run ending at the number of valid values
    start_pos = jnp.sort(jnp.where(is_start, idx, r))
    n_valid = jnp.sum(is_valid.astype(jnp.int32))
    run_end = jnp.minimum(jnp.concatenate([start_pos[1:], jnp.full(
        (1,), r, start_pos.dtype)]), n_valid)
    counts = jnp.where(idx < card, run_end - start_pos, 0).astype(
        jnp.float32)
    run_vals = jnp.where(jnp.arange(r) < card,
                         sv[jnp.minimum(start_pos, r - 1)], sent)
    cardf = jnp.maximum(card.astype(jnp.float32), 1.0)
    kmask = jnp.arange(r) < card
    big = jnp.float32(3.4e38)
    min_freq = jnp.min(jnp.where(kmask, counts, big))
    max_freq = jnp.max(counts)
    perc = counts / nf
    max_perc = max_freq / nf
    mean_perc = jnp.sum(jnp.where(kmask, perc, 0.0)) / cardf
    sd_perc = jnp.sqrt(jnp.maximum(
        jnp.sum(jnp.where(kmask, (perc - mean_perc) ** 2, 0.0)) / cardf, 0.0))
    entropy = -jnp.sum(jnp.where(kmask & (counts > 0),
                                 perc * jnp.log(perc), 0.0))
    scounts = jnp.sort(counts)
    base = (r - card).astype(jnp.float32)

    def octile(q):
        pos = base + q * (cardf - 1.0)
        lo = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, r - 1)
        hi = jnp.clip(lo + 1, 0, r - 1)
        w = pos - lo.astype(jnp.float32)
        return ((1.0 - w) * scounts[lo] + w * scounts[hi]) / nf

    octs = jnp.stack([octile(jnp.float32(q / 8.0)) for q in range(1, 8)])
    kk = min(N_FREQ_WORDS, r)
    topc, topi = jax.lax.top_k(counts, kk)
    freq_words = jnp.where(topc > 0, run_vals[topi], sent)
    if kk < N_FREQ_WORDS:
        freq_words = jnp.concatenate(
            [freq_words, jnp.full((N_FREQ_WORDS - kk,), sent, jnp.uint32)])
    first_word = jnp.where(has_rows, sv[0], sent)
    valid_row = idx < n
    mn_c, mx_c, mean_c, _ = _masked_stats(char_len, valid_row, nf)
    mn_w, mx_w, mean_w, sd_w = _masked_stats(word_cnt, valid_row, nf)
    z = jnp.float32(0.0)
    numeric = jnp.stack([
        jnp.where(has_rows, jnp.log1p(card.astype(jnp.float32)), z),
        jnp.where(has_rows, card.astype(jnp.float32) / nf, z),
        jnp.where(has_rows, entropy, z),
        jnp.where(has_rows, jnp.log1p(min_freq), z),
        jnp.where(has_rows, jnp.log1p(max_freq), z),
        jnp.where(has_rows, max_perc, z),
        jnp.where(has_rows, sd_perc, z),
        *[jnp.where(has_rows, octs[i], z) for i in range(7)],
        jnp.where(has_rows, mx_c, z),
        jnp.where(has_rows, mn_c, z),
        jnp.where(has_rows, mean_c, z),
        jnp.where(has_rows, mean_w, z),
        jnp.where(has_rows, mn_w, z),
        jnp.where(has_rows, mx_w, z),
        jnp.where(has_rows, sd_w, z),
    ])
    return numeric, jnp.concatenate([freq_words, first_word[None]])


_profile_block = jax.jit(jax.vmap(_profile_column))


def profiles(lake, block: int = 8192):
    """(numeric (C, 21) f32, words (C, 11) u32) of every column."""
    c, r = lake.values32.shape
    rp = max(1 << (max(r, 1) - 1).bit_length(), 16)
    nums, words = [], []
    for i in range(0, c, block):
        j = min(i + block, c)
        v = np.full((block, rp), SENTINEL, np.uint32)
        cl = np.zeros((block, rp), np.float32)
        wc = np.zeros((block, rp), np.float32)
        nr = np.zeros((block,), np.int32)
        v[:j - i, :r] = lake.values32[i:j]
        cl[:j - i, :r] = lake.char_len[i:j]
        wc[:j - i, :r] = lake.word_cnt[i:j]
        nr[:j - i] = lake.n_rows[i:j]
        n, w = _profile_block(v, cl, wc, nr)
        nums.append(np.asarray(n)[:j - i])
        words.append(np.asarray(w)[:j - i])
    return np.concatenate(nums), np.concatenate(words)


def zscore(numeric: np.ndarray) -> np.ndarray:
    """Lake-wide z-scores in f32 (a slot with no spread keeps std 1)."""
    numeric = numeric.astype(np.float32)
    mean = numeric.mean(axis=0).astype(np.float32)
    std = numeric.std(axis=0)
    std = np.where(std < 1e-6, 1.0, std).astype(np.float32)
    return ((numeric - mean) / std).astype(np.float32)


# ---------------------------------------------------------------------------
# scores
# ---------------------------------------------------------------------------

def _leaf(bits, values, offset: int, depth: int):
    """Value of the leaf the level ``bits`` pick: leaf index
    ``sum(bits[l] << l)``, chosen by selects from the top level down."""
    if depth == 0:
        return jnp.float32(values[offset])
    hi = offset + (1 << (depth - 1))
    return jnp.where(bits[depth - 1], _leaf(bits, values, hi, depth - 1),
                     _leaf(bits, values, offset, depth - 1))


def make_scorer(trees, dtype=jnp.float32):
    """Jitted (zq, wq, zc, wc) -> (Q, N) ensemble scores of every (query,
    column) pair of a block, with the trees unrolled as constants.

    ``dtype`` is the precision the profiles, their distances and the
    thresholds are held in; leaves are summed in f32, tree by tree."""
    feats, thrs, leaves, base = (np.asarray(a) for a in trees)
    leaves = leaves.astype(np.float32)

    def score(zq, wq, zc, wc):
        d_num = jnp.abs(zq.astype(dtype)[:, None, :] - zc.astype(dtype)[None])
        tq, tc = wq[:, :N_FREQ_WORDS], wc[:, :N_FREQ_WORDS]
        eq = ((tq[:, None, :, None] == tc[None, :, None, :])
              & (tq[:, None, :, None] != SENTINEL))
        overlap = (jnp.sum(jnp.any(eq, axis=-1).astype(jnp.float32), axis=-1)
                   / N_FREQ_WORDS).astype(dtype)
        fq, fc = wq[:, N_FREQ_WORDS], wc[:, N_FREQ_WORDS]
        first = ((fq[:, None] == fc[None, :]) & (fq[:, None] != SENTINEL)
                 ).astype(dtype)
        x = jnp.concatenate([d_num, overlap[..., None], first[..., None]], -1)
        acc = jnp.full(x.shape[:-1], np.float32(base), jnp.float32)
        for t in range(feats.shape[0]):
            bits = [x[..., int(feats[t, lvl])] >= thrs[t, lvl].astype(dtype)
                    for lvl in range(feats.shape[1])]
            acc = acc + _leaf(bits, leaves[t], 0, len(bits))
        return acc

    return jax.jit(score)


class ExactScan:
    """Exact answers for a set of query columns over the whole lake."""

    def __init__(self, lake, trees, *, dtype=jnp.float32, block=8192,
                 like: "ExactScan | None" = None):
        self.lake = lake
        self.block = block
        self._score = make_scorer(trees, dtype)
        if like is not None:             # same lake: reuse its profiles
            self.z, self.words = like.z, like.words
        else:
            numeric, self.words = profiles(lake)
            self.z = zscore(numeric)

    def scores(self, qids: np.ndarray) -> np.ndarray:
        """(Q, C) f32 scores, -inf where a column may not be returned."""
        qids = np.asarray(qids, np.int64)
        zq = jnp.asarray(self.z[qids])
        wq = jnp.asarray(self.words[qids])
        c = self.lake.n_columns
        out = np.empty((qids.size, c), np.float32)
        for i in range(0, c, self.block):
            j = min(i + self.block, c)
            zc = np.zeros((self.block, F_NUM), np.float32)
            wc = np.full((self.block, F_WORDS), SENTINEL, np.uint32)
            zc[:j - i], wc[:j - i] = self.z[i:j], self.words[i:j]
            s = self._score(zq, wq, jnp.asarray(zc), jnp.asarray(wc))
            out[:, i:j] = np.asarray(s)[:, :j - i]
        table = self.lake.table
        out[table[None, :] == table[qids][:, None]] = -np.inf
        out[np.arange(qids.size), qids] = -np.inf
        return out


# ---------------------------------------------------------------------------
# value overlap
# ---------------------------------------------------------------------------

def sure_jaccard(n_perm: int, n_bands: int, miss: float) -> float:
    """The least value-set Jaccard at which banded MinHash (``n_bands``
    bands of ``n_perm / n_bands`` rows) misses a pair with probability at
    most ``miss``: a pair of Jaccard ``J`` shares no band with probability
    ``(1 - J**r) ** b``."""
    r = n_perm // n_bands
    return float((1.0 - miss ** (1.0 / n_bands)) ** (1.0 / r))


def value_partners(lake, qids: np.ndarray, min_jaccard: float) -> list:
    """For each query, the columns whose value set has Jaccard at least
    ``min_jaccard`` with the query's (the query itself included), exactly,
    from the lake's value arrays."""
    vals = lake.values32
    qsets = [np.setdiff1d(np.unique(vals[q]), [SENTINEL]) for q in qids]
    allq = np.unique(np.concatenate(qsets)) if qsets else np.zeros(0, np.uint32)
    if allq.size == 0:
        return [np.zeros(0, np.int64) for _ in qids]
    # cells whose low bits match a query value, then the exact test on those
    low = np.uint32((1 << 22) - 1)
    seen = np.zeros(1 << 22, bool)
    seen[allq & low] = True
    flat = vals.ravel()
    cells = np.flatnonzero(seen[flat & low])
    at = np.minimum(np.searchsorted(allq, flat[cells]), allq.size - 1)
    cells = cells[allq[at] == flat[cells]]
    cols = np.unique(cells // vals.shape[1])
    sub = np.sort(vals[cols], axis=1)
    first = np.ones(sub.shape, bool)
    first[:, 1:] = sub[:, 1:] != sub[:, :-1]
    first &= sub != SENTINEL
    n_distinct = first.sum(axis=1)
    pv = sub[first]                                  # (value, column) pairs
    pc = np.repeat(np.arange(cols.size), n_distinct)
    order = np.argsort(pv, kind="stable")
    pv, pc = pv[order], pc[order]
    out = []
    for u in qsets:
        lo = np.searchsorted(pv, u, "left")
        hi = np.searchsorted(pv, u, "right")
        idx = np.repeat(lo, hi - lo) + (np.arange(int((hi - lo).sum()))
                                        - np.repeat(np.cumsum(hi - lo)
                                                    - (hi - lo), hi - lo))
        c, inter = np.unique(pc[idx], return_counts=True)
        jac = inter / (u.size + n_distinct[c] - inter)
        out.append(cols[c[jac >= min_jaccard]].astype(np.int64))
    return out


def top_k(scores: np.ndarray, k: int):
    """(ids, scores) of the ``k`` best columns per row, best first; equal
    scores keep the lower column id first."""
    k = min(k, scores.shape[1])
    ids = np.empty((scores.shape[0], k), np.int64)
    for i, row in enumerate(scores):
        kth = np.partition(row, row.size - k)[row.size - k]
        cand = np.flatnonzero(row >= kth)
        ids[i] = cand[np.lexsort((cand, -row[cand]))][:k]
    return ids, np.take_along_axis(scores, ids, axis=1)
