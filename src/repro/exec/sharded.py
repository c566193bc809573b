"""Grid-sharded pipeline builders: per-tile stages + a two-phase merge.

The mesh is a 2-D **(query × data) device grid**: column-axis tensors
(profiles, word features, global column ids, table ids, LSH band keys)
shard over the ``data``-like axes with ``shard_map``, and the query batch
shards over the ``query`` axes — each device runs the *same* stage
functions as the local pipelines (``stages.py``) on its
(Q-shard, C-shard) tile:

* ``all``    — streamed full scan of the local columns (brute baseline);
* ``lsh`` / ``hybrid`` — the ``lsh_probe`` Pallas kernel over the local
  (Q/q_shards, B) × (C/d_shards, B) key tile, hybrid priority fill, and
  scoring of at most ``ceil(budget / d_shards)`` local candidates per
  local query — distributed LSH on both axes.

The merge is two-phase: ``merge_topk_sharded`` reduces each query shard's
rows over the DATA axes (one tiled ``all_gather`` of k-row tiles,
collective bytes O(Q_local·k·d_shards)), then ``assemble_query_shards``
re-assembles the batch over the QUERY axes (O(Q·k), lake-size free).
``query_axes=()`` degrades to the 1-D data-sharded pipeline of earlier
revisions: the query batch is replicated and phase 2 is a no-op — the
same code path serves every grid geometry, which is what the
mesh-geometry parity suite (``tests/test_grid.py``) locks in.

``n_scored`` is the **global** count of candidate columns actually scored
per query: per-device counts ``psum`` over the DATA axes only (summing
over the query axes would double-count every query by q_shards), then
ride the phase-2 gather back to batch order — candidate-fraction and
recall accounting stay honest on any grid.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import features as FT
from repro.exec import stages
from repro.kernels.lsh_probe import PAD_CORPUS


def _pad_to(x: np.ndarray, n: int, fill) -> np.ndarray:
    pad = [(0, n - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return np.pad(x, pad, constant_values=fill)


def place_sharded_corpus(mesh: Mesh, shard_axes, z: np.ndarray, w: np.ndarray,
                         table_ids: np.ndarray | None = None,
                         band_keys: np.ndarray | None = None,
                         cids: np.ndarray | None = None) -> dict:
    """Pad the column axis to a multiple of the data-shard count and
    device_put the corpus tensors for a sharded pipeline.

    Returns ``{"z", "w", "cids"[, "tids"][, "ckeys"]}`` — ``cids`` are
    global column ids (-1 on padding; pass ``cids`` explicitly when the
    caller's rows are already bucket-padded with sentinel rows, so
    arange does not assign real ids to them), ``tids`` pad with -2
    (matches no real table and no disabled-query sentinel), ``ckeys``
    pad with the probe kernel's corpus sentinel. On a grid mesh,
    ``P(shard_axes)`` replicates each column shard across the query (and
    model) axes automatically; query-side tensors are placed by the
    executor with the plan's own query-axis sharding.
    """
    n = z.shape[0]
    n_shards = int(np.prod([mesh.shape[a] for a in shard_axes]))
    n_pad = -(-n // n_shards) * n_shards
    shard = NamedSharding(mesh, P(tuple(shard_axes)))
    if cids is None:
        cids = np.arange(n, dtype=np.int32)
    out = {
        "z": jax.device_put(_pad_to(z.astype(np.float32), n_pad, 0.0), shard),
        "w": jax.device_put(_pad_to(w, n_pad, FT.HASH_SENTINEL), shard),
        "cids": jax.device_put(
            _pad_to(np.asarray(cids, np.int32), n_pad, -1), shard),
    }
    if table_ids is not None:
        out["tids"] = jax.device_put(
            _pad_to(np.asarray(table_ids, np.int32), n_pad, -2), shard)
    if band_keys is not None:
        out["ckeys"] = jax.device_put(
            _pad_to(np.asarray(band_keys, np.uint32), n_pad, PAD_CORPUS),
            shard)
    return out


def build_sharded_pipeline(mesh: Mesh, gbdt_tuple, *, candidates: str = "all",
                           k: int, budget_per_shard: int | None = None,
                           interpret: bool, shard_axes=("data",),
                           query_axes=(), block: int = 4096):
    """Jitted grid-sharded candidate→score→merge pipeline over ``mesh``.

    ``candidates="all"``: fn(z, w, cids, tids, zq, wq, tq, qid);
    otherwise:            fn(z, w, cids, tids, ckeys, zq, wq, qkeys, tq, qid).
    Corpus tensors shard over ``shard_axes``; query-side tensors shard
    over ``query_axes`` (replicated when empty — the 1-D pipeline). Both
    forms return replicated (scores (Q, k'), global ids (Q, k'),
    n_scored (Q,)) with k' = min(k, columns visible to the merge); the
    query batch must be divisible by the query-axis size (the executor
    pads).
    """
    axes = tuple(shard_axes)
    qaxes = tuple(query_axes)
    qspec = P(qaxes) if qaxes else P()

    def _merge(s_local, cand_ids, n_local_per_q):
        ls, lids = stages.merge_topk(s_local, cand_ids, k)
        gs, gi = stages.merge_topk_sharded(ls, lids, k, axes)
        n_scored = n_local_per_q
        for ax in axes:                      # DATA axes only — the query
            n_scored = jax.lax.psum(n_scored, ax)   # axis would double-count
        return stages.assemble_query_shards(gs, gi, n_scored, qaxes)

    if candidates == "all":
        def local_fn(z, w, cids, tids, zq, wq, tq, qid):
            s = stages.score_streamed(zq, wq, z, w, gbdt_tuple, block=block)
            s = jnp.where(stages.exclusion_mask(cids, tids, tq, qid),
                          -jnp.inf, s)
            n_live = jnp.sum((cids >= 0).astype(jnp.int32))
            n_per_q = jnp.full((zq.shape[0],), n_live, jnp.int32)
            return _merge(s, cids, n_per_q)

        in_specs = (P(axes), P(axes), P(axes), P(axes),
                    qspec, qspec, qspec, qspec)
    else:
        if budget_per_shard is None:
            raise ValueError("pruned sharded pipeline needs budget_per_shard")

        def local_fn(z, w, cids, tids, ckeys, zq, wq, qkeys, tq, qid):
            prio = stages.candidate_priorities(
                candidates, zq, qkeys, z, ckeys, cids, tids, tq, qid,
                interpret=interpret)
            m = min(budget_per_shard, z.shape[0])
            pos, valid = stages.gather_candidates(prio, m)
            with jax.named_scope("candidates"):
                zc, wc = z[pos], w[pos]
            s = stages.score_columns(zq, wq, zc, wc, gbdt_tuple)
            s = jnp.where(valid, s, -jnp.inf)
            with jax.named_scope("candidates"):
                cand_ids = cids[pos]
            return _merge(s, cand_ids, valid.sum(axis=1).astype(jnp.int32))

        in_specs = (P(axes), P(axes), P(axes), P(axes), P(axes),
                    qspec, qspec, qspec, qspec, qspec)

    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=(P(), P(), P()), check_vma=False)
    return jax.jit(fn)
