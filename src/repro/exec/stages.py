"""Composable pipeline stages: candidate generation, scoring, top-k merge.

Every discovery query — local or mesh-sharded, pruned or brute — is the
same three-stage pipeline over a (local shard of the) corpus:

1. **candidates** — which columns may the scorer see?  Kinds:
   ``all`` (full-scan mask: every live column), ``lsh`` (banded-MinHash
   bucket probe via the ``lsh_probe`` Pallas kernel), ``hybrid`` (LSH hits
   ranked first, remaining budget filled by profile-space proximity — the
   blocking construction of Flores et al.);
2. **score** — distance features + GBDT over exactly the surviving
   columns (gathered to a fixed budget so shapes stay jit-cacheable);
3. **merge** — local top-k, and on a mesh per-device top-k + one small
   ``all_gather`` (collective bytes O(Q·k·devices), lake-size free).

The functions here are pure jnp/Pallas and run identically inside ``jit``
and inside ``shard_map`` — ``executor.py`` composes them into the local
pipelines, ``sharded.py`` into the per-device bodies.  Column ids are
always *global* (``cids``), so exclusion masks (self, same-table, padding)
work unchanged on a shard.

Each stage function traces under its stage's ``jax.named_scope``
(``candidates``, ``score``, ``merge``), so every op of a compiled pipeline
carries its stage in its HLO metadata (``op_name``) and a device profile
can sum op time by stage under names no compiler edit changes. Scopes
touch metadata only, never the compiled arithmetic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.predictor import distance_features_ref, gbdt_predict_ref
from repro.kernels.lsh_probe import lsh_probe_gathered_tile, lsh_probe_tile

CANDIDATE_KINDS = ("all", "lsh", "hybrid", "tiered")

# LSH hits outrank every profile-proximity score: the proxy is squashed
# into (-1, 1), so any offset > 2 keeps the two bands disjoint.
_LSH_PRIORITY_BOOST = 4.0


def stage(name: str):
    """Trace the decorated function under ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


# ---------------------------------------------------------------------------
# stage 1: candidate generation
# ---------------------------------------------------------------------------

def live_count(cids):
    """Number of live (non-padding) columns on this corpus axis — counts
    ``cids >= 0`` so bucket-padded sentinel rows never inflate per-query
    scored-column accounting."""
    return jnp.sum((cids >= 0).astype(jnp.int32))


def exclusion_mask(cids, tids, tq, qid):
    """(Q, C) bool — True where a column must NOT be returned for a query.

    Masks padding columns (cid < 0), the query itself (global id match;
    qid=-1 marks an external query and matches nothing), and same-table
    columns (tq=-1 disables the table mask for that row).
    """
    pad = (cids < 0)[None, :]
    self_hit = cids[None, :] == qid[:, None]
    same_table = (tq[:, None] >= 0) & (tids[None, :] == tq[:, None])
    return pad | self_hit | same_table


@stage("candidates")
def candidate_priorities(kind: str, zq, qkeys, z, ckeys, cids, tids, tq, qid,
                         *, interpret: bool):
    """(Q, C) float32 priorities; -inf means "never a candidate".

    ``kind``: ``lsh`` — bucket hits only (missing the budget is fine: the
    un-hit remainder stays -inf); ``hybrid`` — hits first, then nearest
    columns in z-scored profile space via one matmul (squared-L2 up to a
    per-query constant — no trees, no word features at this stage).
    """
    excl = exclusion_mask(cids, tids, tq, qid)
    if kind == "lsh":
        hit = lsh_probe_tile(qkeys, ckeys, interpret=interpret)
        prio = jnp.where(hit > 0, 0.0, -jnp.inf)
    elif kind == "hybrid":
        hit = lsh_probe_tile(qkeys, ckeys, interpret=interpret)
        # -||zq - z||² up to a per-query constant: 2·zq@zᵀ - ||z||²
        proxy = 2.0 * zq @ z.T - jnp.sum(z * z, axis=1)[None]
        proxy = proxy / (1.0 + jnp.abs(proxy))            # squash to (-1, 1)
        prio = hit.astype(jnp.float32) * _LSH_PRIORITY_BOOST + proxy
    else:
        raise ValueError(f"unknown candidate kind {kind!r}; "
                         f"want one of {CANDIDATE_KINDS}")
    return jnp.where(excl, -jnp.inf, prio)


@stage("candidates")
def tiered_survivors(qcoarse, coarse, cids, tids, tq, qid, *,
                     survivor_budget: int, interpret: bool,
                     block_c: int = 32, proxy=None):
    """Coarse pass of the tiered candidate stage: pick survivor blocks.

    Probes the small (C, S) super-band digest with the (Q, S) coarse query
    keys, expands column hits to *blocks* of ``block_c`` contiguous
    columns (so the downstream gather reads aligned runs, not scattered
    singletons), and keeps up to ``survivor_budget`` columns per query —
    direct coarse hits ranked above their block-mates.

    ``proxy`` (Q, C), when given, fills survivor-budget slots the digest
    left empty with the proxy-nearest columns (ranked strictly below every
    digest hit, mirroring the ``hybrid`` construction).  The digest only
    sees *value overlap*; the exact GBDT top-k also contains columns that
    are merely profile-similar, and at 10^5 columns the digest's hit set
    is far smaller than the budget — without the fill those slots are
    wasted and tiered recall trails the single-tier hybrid probe.

    Returns ``(pos, valid, n_hits, n_survivors)``: gather positions
    (Q, M') into the local corpus, their validity mask, and per-query
    counts of direct coarse hits and digest-eligible survivor columns (the
    numbers the ``coarse_pass`` event reports — proxy fill does not count
    as a digest survivor).
    """
    c = coarse.shape[0]
    hit = lsh_probe_tile(qcoarse, coarse, interpret=interpret)   # (Q, C)
    pad_c = (-c) % block_c
    hp = jnp.pad(hit, ((0, 0), (0, pad_c)))
    nb = hp.shape[1] // block_c
    block_hit = jnp.any(hp.reshape(hit.shape[0], nb, block_c) > 0, axis=-1)
    block_hit = jnp.repeat(block_hit, block_c, axis=1)[:, :c]     # (Q, C)
    excl = exclusion_mask(cids, tids, tq, qid)
    if proxy is None:
        prio = jnp.where(block_hit, 1.0, -jnp.inf) + hit.astype(jnp.float32)
    else:
        # squashed proxy lives in (-1, 1); the boost keeps every digest
        # hit (and its block-mates) strictly above every proxy-only fill
        prio = (jnp.where(block_hit, _LSH_PRIORITY_BOOST, 0.0)
                + hit.astype(jnp.float32)
                + proxy / (1.0 + jnp.abs(proxy)))
    prio = jnp.where(excl, -jnp.inf, prio)
    pos, valid = gather_candidates(prio, survivor_budget)
    n_hits = jnp.sum((hit > 0) & ~excl, axis=1)
    n_survivors = jnp.sum(block_hit & ~excl, axis=1)
    return pos, valid, n_hits, n_survivors


@stage("candidates")
def tiered_priorities(zq, qkeys, zg, keys_g, valid, *, interpret: bool):
    """Fine pass of the tiered stage over gathered survivors.

    ``zg`` (Q, M', F_NUM) and ``keys_g`` (Q, M', B) are the survivors'
    profiles and fine band keys gathered per query; the skinny-geometry
    probe kernel plus the per-query proxy replace the full-lake hybrid
    pass. Returns (Q, M') priorities with invalid slots at -inf.
    """
    hit = lsh_probe_gathered_tile(qkeys, keys_g, interpret=interpret)
    proxy = 2.0 * jnp.einsum("qf,qmf->qm", zq, zg) - jnp.sum(zg * zg, axis=-1)
    proxy = proxy / (1.0 + jnp.abs(proxy))
    prio = hit.astype(jnp.float32) * _LSH_PRIORITY_BOOST + proxy
    return jnp.where(valid, prio, -jnp.inf)


@stage("candidates")
def gather_candidates(prio, budget: int):
    """Top-``budget`` columns by priority -> (positions (Q, M), valid (Q, M)).

    Positions index the local corpus axis; invalid rows (priority -inf)
    mark budget slots the scorer must ignore.
    """
    pval, pos = jax.lax.top_k(prio, budget)
    return pos, jnp.isfinite(pval)


# ---------------------------------------------------------------------------
# stage 2: scoring
# ---------------------------------------------------------------------------

@stage("score")
def score_columns(zq, wq, zc, wc, gbdt_tuple):
    """GBDT join-quality scores. zc/wc (C, F) -> (Q, C); an extra leading
    axis on zc/wc ((Q, M, F) gathered candidates) scores per-query sets."""
    if zc.ndim == 2:
        zc, wc = zc[None], wc[None]
    d = distance_features_ref(zq[:, None], wq[:, None], zc, wc)
    return gbdt_predict_ref(gbdt_tuple, d)


@stage("score")
def score_streamed(zq, wq, z, w, gbdt_tuple, *, block: int = 4096):
    """Full-corpus scoring, streamed in column blocks of ``block``.

    The jnp mirror of the fused Pallas kernel: the (Q, N, F) distance
    tensor never materializes, so HBM traffic is the profiles themselves
    plus the (Q, N) score row — bandwidth-bound at profile size.
    """
    n = z.shape[0]
    nb = max(n // block, 1)

    def score_blk(args):
        zb, wb = args
        return score_columns(zq, wq, zb, wb, gbdt_tuple)

    if n % block == 0 and n > block:
        zc = z.reshape(nb, block, z.shape[1])
        wc = w.reshape(nb, block, w.shape[1])
        s = jax.lax.map(score_blk, (zc, wc))              # (nb, Q, block)
        return jnp.moveaxis(s, 0, 1).reshape(zq.shape[0], n)
    return score_blk((z, w))


# ---------------------------------------------------------------------------
# stage 3: top-k merge
# ---------------------------------------------------------------------------

@stage("merge")
def merge_topk(scores, cids, k: int):
    """Local top-k -> (scores (Q, k'), global ids (Q, k')), k' = min(k, C).

    ``cids`` is (C,) for a shared corpus axis or (Q, C) for per-query
    gathered candidate sets. Non-finite slots come back with id -1 (the
    caller-visible padding convention)."""
    kl = min(k, scores.shape[1])
    sc, pos = jax.lax.top_k(scores, kl)
    if cids.ndim == 1:
        cids = jnp.broadcast_to(cids[None], scores.shape)
    ids = jnp.take_along_axis(cids, pos, axis=1)
    return sc, jnp.where(jnp.isfinite(sc), ids, -1)


@stage("merge")
def merge_topk_sharded(local_scores, local_ids, k: int, axes):
    """Per-device top-k results -> replicated global top-k.

    One tiled ``all_gather`` per mesh axis moves the (Q, k_local) candidate
    pairs of every shard; a final top-k over the (Q, k_local · devices)
    union re-ranks. Runs inside ``shard_map``.
    """
    all_s, all_i = local_scores, local_ids
    for ax in axes:
        all_s = jax.lax.all_gather(all_s, ax, axis=1, tiled=True)
        all_i = jax.lax.all_gather(all_i, ax, axis=1, tiled=True)
    gs, gp = jax.lax.top_k(all_s, min(k, all_s.shape[1]))
    gi = jnp.take_along_axis(all_i, gp, axis=1)
    return gs, jnp.where(jnp.isfinite(gs), gi, -1)


def assemble_query_shards(scores, ids, n_scored, axes):
    """Phase-2 merge of the 2-D grid: reassemble the query batch.

    After :func:`merge_topk_sharded` reduced over the DATA axis, every
    device holds the finished (Q_local, k) rows of its *query* shard. One
    tiled ``all_gather`` per query axis (row axis 0, so shard order is
    batch order) replicates the full (Q, k) batch — collective bytes
    O(Q·k), independent of both the lake size and the data-axis width.
    ``n_scored`` rides along so per-query accounting follows its row. Runs
    inside ``shard_map``; a no-op when ``axes`` is empty (1-D plans).
    """
    for ax in axes:
        scores = jax.lax.all_gather(scores, ax, axis=0, tiled=True)
        ids = jax.lax.all_gather(ids, ax, axis=0, tiled=True)
        n_scored = jax.lax.all_gather(n_scored, ax, axis=0, tiled=True)
    return scores, ids, n_scored
