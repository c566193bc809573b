"""Planted lakes made from a seed: the data every cell serves.

A copy of the scaled-lake generating process (join groups whose members
share a value pool, pairwise Jaccard set per group by a tier, the rest
pairwise-disjoint noise), kept with the benchmark so that the data a cell
serves cannot change with the program. The program receives only the
generated arrays, packed into its ``ColumnBatch`` by the harness.

The lake's shape is fixed by the configuration; ``seed`` only draws which
values each column holds, so every seed gives the same amount of work.
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SENTINEL = np.uint32(0xFFFFFFFF)     # padding of the uint32 value space
BLOCK = 8192                         # columns hashed per task


@dataclasses.dataclass(frozen=True)
class LakeSpec:
    n_columns: int
    row_budget: int = 256
    group_size: int = 16
    cols_per_table: int = 8
    joinable_frac: float = 0.12
    jaccard_tiers: tuple = (0.8, 0.4, 0.2)
    vocab_size: int = 160

    @classmethod
    def from_config(cls, lake: dict) -> "LakeSpec":
        kw = {f.name: lake[f.name] for f in dataclasses.fields(cls)
              if f.name in lake}
        if "jaccard_tiers" in kw:
            kw["jaccard_tiers"] = tuple(float(j) for j in kw["jaccard_tiers"])
        return cls(**kw)


@dataclasses.dataclass
class Lake:
    spec: LakeSpec
    values32: np.ndarray    # (C, R) uint32 value hashes
    char_len: np.ndarray    # (C, R) float32 characters per value
    word_cnt: np.ndarray    # (C, R) float32 words per value
    n_rows: np.ndarray      # (C,) int32
    table: np.ndarray       # (C,) int32 owning table
    group: np.ndarray       # (C,) int32 join group, -1 for noise
    tier: np.ndarray        # (C,) int32 index into jaccard_tiers, -1 for noise

    @property
    def n_columns(self) -> int:
        return int(self.values32.shape[0])

    def planted(self) -> np.ndarray:
        """Columns with planted join partners (every query's pool)."""
        return np.flatnonzero(self.group >= 0).astype(np.int64)

    def noise(self) -> np.ndarray:
        return np.flatnonzero(self.group < 0).astype(np.int64)


def fmix32(x: np.ndarray) -> np.ndarray:
    """MurmurHash3's 32-bit finalizer: a bijection of uint32, so distinct
    value ids stay distinct values."""
    h = x.astype(np.uint32)
    h ^= h >> np.uint32(16)
    h *= np.uint32(0x85EBCA6B)
    h ^= h >> np.uint32(13)
    h *= np.uint32(0xC2B2AE35)
    h ^= h >> np.uint32(16)
    return h


def below(bits16: np.ndarray, n: np.ndarray) -> np.ndarray:
    """A uniform draw in ``[0, n)`` from 16 random bits (``n`` < 2**16)."""
    return (bits16 * n) >> np.uint32(16)


def generate(spec: LakeSpec, seed: int, workers: int = 8) -> Lake:
    """Member supports are uniform ``s``-subsets of the group's pool with
    ``s / V = 2J / (1 + J)``, so two members' expected Jaccard is the
    tier's ``J``; every support value fills at least one row, so the value
    *set* is the support itself. Planted columns take the first indices,
    members of a group ``n_groups`` apart, hence in different tables.

    The seeded draws come first; the hashing that follows is elementwise
    and runs in column blocks on ``workers`` threads."""
    rng = np.random.default_rng(seed)
    c, r, v = spec.n_columns, spec.row_budget, spec.vocab_size
    if r < v:
        raise ValueError(f"row_budget ({r}) must be >= vocab_size ({v})")
    tiers = tuple(float(j) for j in spec.jaccard_tiers)
    n_groups = int(c * spec.joinable_frac) // max(spec.group_size, 2)
    n_planted = n_groups * spec.group_size

    group = np.full((c,), -1, np.int32)
    tier = np.full((c,), -1, np.int32)
    p = np.arange(n_planted)
    group[:n_planted] = (p % max(n_groups, 1)).astype(np.int32)
    tier[:n_planted] = (group[:n_planted] % len(tiers)).astype(np.int32)

    # value ids: each group's pool is its own id range, each noise column's
    # rows another, all below 2**32; a seeded key is folded into every id
    # before the bijective hash, so values differ from seed to seed
    base = n_groups * v + 1
    if base + c * r >= 1 << 32:
        raise ValueError(f"{c} x {r} values do not fit 32-bit value ids")
    key = np.uint32(rng.integers(0, 1 << 32, dtype=np.uint64))
    planted = np.empty((n_planted, r), np.uint32)
    for t, j in enumerate(tiers):
        idx = np.flatnonzero(tier == t)
        if idx.size == 0:
            continue
        s = int(np.clip(round(2.0 * j / (1.0 + j) * v), 2, v))
        perms = rng.permuted(np.broadcast_to(
            np.arange(v, dtype=np.uint32), (idx.size, v)).copy(), axis=1)
        sup = perms[:, :s] + (group[idx, None].astype(np.uint32)
                              * np.uint32(v) + np.uint32(1))
        extra = np.take_along_axis(
            sup, rng.integers(0, s, size=(idx.size, r - s)), axis=1)
        planted[idx] = np.concatenate([sup, extra], axis=1)
    rows = np.arange(r, dtype=np.uint32)[None, :]

    values32 = np.empty((c, r), np.uint32)
    char_len = np.empty((c, r), np.float32)
    word_cnt = np.empty((c, r), np.float32)

    def block(i: int) -> None:
        j = min(i + BLOCK, c)
        vids = np.empty((j - i, r), np.uint32)
        k = min(j, n_planted)
        if i < k:
            vids[:k - i] = planted[i:k]
        if j > max(i, n_planted):
            a = max(i, n_planted)
            cols = np.arange(a, j, dtype=np.uint32)[:, None]
            vids[a - i:] = np.uint32(base) + cols * np.uint32(r) + rows
        vids ^= key
        h = fmix32(vids)
        values32[i:j] = np.where(h == SENTINEL, SENTINEL - np.uint32(1), h)
        # string style per owner (the group, or the noise column itself),
        # so group members share syntactic profiles and others differ
        g = group[i:j]
        owner = np.where(g >= 0, g.astype(np.int64),
                         np.int64(n_groups) + np.arange(i, j))
        st = fmix32(owner.astype(np.uint32) ^ np.uint32(0x51AB))
        base_len = (4 + st % np.uint32(13))[:, None]
        spread = (2 + (st >> np.uint32(8)) % np.uint32(9))[:, None]
        wmax = (1 + (st >> np.uint32(16)) % np.uint32(4))[:, None]
        char_len[i:j] = base_len + below(h >> np.uint32(16), spread)
        word_cnt[i:j] = 1 + below(h & np.uint32(0xFFFF), wmax)

    with ThreadPoolExecutor(max_workers=max(1, workers)) as pool:
        for f in [pool.submit(block, i) for i in range(0, c, BLOCK)]:
            f.result()
    return Lake(spec=spec, values32=values32, char_len=char_len,
                word_cnt=word_cnt, n_rows=np.full((c,), r, np.int32),
                table=(np.arange(c) // spec.cols_per_table).astype(np.int32),
                group=group, tier=tier)
