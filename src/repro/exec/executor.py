"""Executor: runs a QueryPlan's candidate→score→merge pipeline on a corpus.

One ``Executor`` wraps one immutable corpus view (profiles, table ids,
optional LSH band keys) plus the GBDT parameters, and executes any
:class:`~repro.exec.plan.QueryPlan` against it:

* local plans dispatch to module-level jitted pipelines (cached by jax
  across executors, so a catalog refresh never recompiles);
* sharded plans run on the plan's 2-D ``grid=(q_shards, d_shards)``: the
  executor re-shapes its mesh's devices into a (query × data) grid mesh
  per geometry, places the corpus over each grid's ``data`` axis **once**
  (cached per grid — the seed implementation re-placed per query batch),
  pads the query batch to a multiple of ``q_shards``, shards it over the
  ``query`` axis, and unpads the reassembled result. ``(1, d)`` grids use
  the caller's own mesh and the legacy replicated-query specs, so 1-D
  plans (and multi-axis ``shard_axes`` like the dry-run's pod×data) are
  untouched.

Both ``core.discovery.rank``/``rank_sharded`` and the service's
``DiscoveryEngine`` are thin adapters over this class — the single copy of
the scoring pipeline in the repo.

The returned contract is uniform: ``(scores (Q, k), global ids (Q, k),
n_scored (Q,))`` as numpy, padded with -inf / -1 when fewer than k columns
are rankable, with ``n_scored`` the *global* number of columns the GBDT
actually scored per query (psum-ed over the data axes on a mesh).

With a quantized ``profile_dtype`` (int8/fp16 sidecar + per-feature
dequant scale) the scan streams the small sidecar, over-fetches
``RESCORE_MULT × k`` candidates, and an exact fp32 re-rank of that tiny
gathered set restores the fp32 top-k ordering — returned scores are
always fp32-exact regardless of the resident dtype.
"""
from __future__ import annotations

import threading
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import features as FT
from repro.exec import spans, stages
from repro.exec.aot import tree_aval_descriptors
from repro.exec.plan import QueryPlan
from repro.exec.sharded import (_pad_to, build_sharded_pipeline,
                                place_sharded_corpus)
from repro.kernels.lsh_probe import PAD_CORPUS
from repro.kernels.ops import interpret_mode
from repro.kernels.profile_distance import dequantize, quantize_profiles


# Ingest deltas are padded up to this many rows before the on-device row
# update, so the update executable's shapes come from a tiny fixed set
# (one per grain multiple) instead of one per odd delta size.
DELTA_ROW_GRAIN = 256


class PlacementBundle:
    """Refcounted bundle of device-resident arrays.

    Successor executors built by :meth:`Executor.extended` retain their
    predecessor's immutable bundles (the GBDT parameters) instead of
    re-placing them, while per-version row arrays live in a bundle owned
    by exactly one executor.  ``Executor.close`` releases its bundles;
    device memory is freed only when the last holder releases —
    retiring an old snapshot version never yanks arrays a newer version
    still serves from, and the class-level live count gives leak tests a
    direct handle on how many placements exist.
    """

    _live = 0
    _live_lock = threading.Lock()

    def __init__(self, arrays: dict):
        self.arrays = dict(arrays)
        self.refs = 1
        self._lock = threading.Lock()
        with PlacementBundle._live_lock:
            PlacementBundle._live += 1

    def retain(self) -> "PlacementBundle":
        with self._lock:
            if self.refs <= 0:
                raise RuntimeError("retain() on a released bundle")
            self.refs += 1
        return self

    def release(self) -> None:
        with self._lock:
            self.refs -= 1
            if self.refs > 0:
                return
            self.arrays.clear()
        with PlacementBundle._live_lock:
            PlacementBundle._live -= 1

    def nbytes(self) -> int:
        return sum(int(getattr(a, "nbytes", 0))
                   for a in self.arrays.values() if a is not None)


def live_placement_bundles() -> int:
    """Device placement bundles currently holding memory — bounded by
    (live versions) × (bundles per executor) when nothing leaks."""
    with PlacementBundle._live_lock:
        return PlacementBundle._live


@jax.jit
def _update_rows2(arr, rows, row0):
    """Write ``rows`` into ``arr[row0:row0+len(rows)]`` on device — the
    delta-placement primitive: only ``rows`` crosses the host-device
    link; the unchanged prefix is forked at HBM bandwidth."""
    return jax.lax.dynamic_update_slice(arr, rows, (row0, jnp.int32(0)))


@jax.jit
def _update_rows1(arr, rows, row0):
    return jax.lax.dynamic_update_slice(arr, rows, (row0,))


@jax.jit
def _update_rows_tree(arrs, rows, row0):
    """Fused delta fork: ONE dispatch DUS-forks every array of the
    corpus bundle (dict pytree) — XLA schedules the prefix copies
    together instead of paying per-array dispatch latency."""
    return jax.tree_util.tree_map(
        lambda a, r: jax.lax.dynamic_update_slice(
            a, r, (row0,) if a.ndim == 1 else (row0, jnp.int32(0))),
        arrs, rows)


# quantized scans over-fetch this multiple of k, then an exact fp32
# re-rank of the over-fetched set restores the fp32 top-k ordering —
# GBDT scores are threshold-discontinuous, so even fp16's ~5e-4 profile
# error flips near-boundary ranks that no finer quantizer would fix
RESCORE_MULT = 4


@partial(jax.jit, static_argnames=("k",))
def _rescore_exact(zq, wq, zg, wg, gbdt_tuple, sc_scan, ids, k: int):
    """Re-rank an over-fetched (Q, R) candidate set with exact fp32
    profiles; invalid scan slots (non-finite score) stay excluded. Traced
    under the ``rescore`` scope (its scoring under ``rescore/score``)."""
    with jax.named_scope("rescore"):
        s = stages.score_columns(zq, wq, zg, wg, gbdt_tuple)
        s = jnp.where(jnp.isfinite(sc_scan), s, -jnp.inf)
        sc, pos = jax.lax.top_k(s, min(k, s.shape[1]))
        return sc, jnp.where(jnp.isfinite(sc),
                             jnp.take_along_axis(ids, pos, axis=1), -1)


def pad_rows(arrays, multiple: int):
    """Pad every array's leading (query) axis up to a multiple of
    ``multiple`` by repeating the last row — the repeated rows carry their
    qid/tq along, so masking stays consistent, and the caller slices the
    duplicate results back off. Returns (padded_arrays, original_length)."""
    q = int(np.asarray(arrays[0]).shape[0])
    pad = -(-q // max(multiple, 1)) * max(multiple, 1)
    if pad == q:
        return [np.asarray(a) for a in arrays], q
    rep = lambda a: np.concatenate(
        [np.asarray(a), np.repeat(np.asarray(a)[-1:], pad - q, axis=0)])
    return [rep(a) for a in arrays], q


def pad_topk(scores: np.ndarray, ids: np.ndarray, k: int):
    """Pad (Q, k_eff) top-k results out to k columns (-inf scores, -1 ids)."""
    k_eff = scores.shape[1]
    if k_eff >= k:
        return scores[:, :k], ids[:, :k]
    pad = ((0, 0), (0, k - k_eff))
    return (np.pad(scores, pad, constant_values=-np.inf),
            np.pad(ids, pad, constant_values=-1))


def _new_dispatch_stats() -> dict:
    """Zeroed dispatch counts: AOT executable or jit fallback, and which
    top-k selection (``stages.selection``) each dispatch's full-width
    candidate pick ran."""
    return {"aot": 0, "fallback": 0,
            "selection": {"threshold": 0, "top_k": 0}}


# ---------------------------------------------------------------------------
# local pipelines (jitted once per shape at module level)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k", "block"))
def _local_all(zq, wq, tq, qid, z, zscale, w, cids, tids, gbdt_tuple,
               k: int, block: int):
    s = stages.score_streamed(zq, wq, dequantize(z, zscale), w, gbdt_tuple,
                              block=block)
    s = jnp.where(stages.exclusion_mask(cids, tids, tq, qid), -jnp.inf, s)
    sc, ids = stages.merge_topk(s, cids, k)
    # count live columns, not the (possibly bucket-padded) corpus rows
    n = jnp.full((zq.shape[0],), stages.live_count(cids), jnp.int32)
    return sc, ids, n


@partial(jax.jit, static_argnames=("kind", "k", "budget", "interpret"))
def _local_pruned(zq, wq, qkeys, tq, qid, z, zscale, w, ckeys, cids, tids,
                  gbdt_tuple, kind: str, k: int, budget: int,
                  interpret: bool):
    zf = dequantize(z, zscale)
    prio = stages.candidate_priorities(kind, zq, qkeys, zf, ckeys, cids,
                                       tids, tq, qid, interpret=interpret)
    pos, valid = stages.gather_candidates(prio, budget)
    with jax.named_scope("candidates"):
        zc, wc = zf[pos], w[pos]
    s = stages.score_columns(zq, wq, zc, wc, gbdt_tuple)
    s = jnp.where(valid, s, -jnp.inf)
    with jax.named_scope("candidates"):
        cand_ids = cids[pos]
    sc, ids = stages.merge_topk(s, cand_ids, k)
    return sc, ids, valid.sum(axis=1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("k", "budget", "survivor_budget",
                                   "block_c", "interpret"))
def _local_tiered(zq, wq, qkeys, qcoarse, tq, qid, z, zscale, w, ckeys,
                  coarse, cids, tids, gbdt_tuple, k: int, budget: int,
                  survivor_budget: int, block_c: int, interpret: bool):
    """Two-tier candidate pipeline: coarse digest scan over the full lake,
    then fine probe + proxy + GBDT only over the gathered survivors.  The
    full-lake pass touches the (C, S) digest plus one proxy matmul over
    the resident (quantized) sidecar, which fills budget slots the digest
    left empty with profile-nearest columns — without the fill, the exact
    top-k's profile-similar-but-non-overlapping columns are unreachable
    and large-lake recall trails the single-tier hybrid probe."""
    with jax.named_scope("candidates"), jax.named_scope("coarse"):
        zf = dequantize(z, zscale)
        # -||zq - z||² up to a per-query constant, fused over the sidecar
        fill = 2.0 * zq @ zf.T - jnp.sum(zf * zf, axis=1)[None]
    pos, valid, n_hits, n_surv = stages.tiered_survivors(
        qcoarse, coarse, cids, tids, tq, qid,
        survivor_budget=survivor_budget, block_c=block_c, proxy=fill,
        interpret=interpret)
    with jax.named_scope("candidates"):
        zg = dequantize(z[pos], zscale)                  # (Q, M', F_NUM)
        keys_g = ckeys[pos]
    prio = stages.tiered_priorities(zq, qkeys, zg, keys_g, valid,
                                    interpret=interpret)
    pos2, valid2 = stages.gather_candidates(prio, budget)
    with jax.named_scope("candidates"):
        gpos = jnp.take_along_axis(pos, pos2, axis=1)    # (Q, M) global cols
        zc, wc = dequantize(z[gpos], zscale), w[gpos]
    s = stages.score_columns(zq, wq, zc, wc, gbdt_tuple)
    s = jnp.where(valid2, s, -jnp.inf)
    with jax.named_scope("candidates"):
        cand_ids = cids[gpos]
    sc, ids = stages.merge_topk(s, cand_ids, k)
    return sc, ids, valid2.sum(axis=1).astype(jnp.int32), n_hits, n_surv


# ---------------------------------------------------------------------------
# executor
# ---------------------------------------------------------------------------

class Executor:
    """Executes query plans against one corpus view."""

    def __init__(self, z: np.ndarray, w: np.ndarray, gbdt_tuple,
                 *, table_ids: np.ndarray | None = None,
                 band_keys: np.ndarray | None = None,
                 coarse_keys: np.ndarray | None = None,
                 profile_dtype: str = "fp32", z_scale=None,
                 fp32_rows=None, survivor_block: int = 32,
                 mesh=None, score_block: int = 4096, events=None,
                 exec_cache=None, n_padded: int | None = None):
        # n_live = true resident columns; n_columns = the (optionally
        # bucket-padded) corpus dimension every traced shape and every
        # plan static is computed from.  Pad rows are inert sentinels
        # (cid -1 → exclusion mask → -inf), bought so an ingest delta
        # that stays inside its column bucket changes no compiled shape.
        self.n_live = int(z.shape[0])
        self.n_columns = max(int(n_padded), self.n_live) \
            if n_padded is not None else self.n_live
        self.profile_dtype = str(profile_dtype)
        self.survivor_block = int(survivor_block)
        # the resident profile matrix: quantized sidecar + per-feature
        # dequant scale ("fp32" keeps the identity scale, so every
        # pipeline treats the three dtypes uniformly).  A caller that
        # already quantized (e.g. the engine streaming a memmapped
        # snapshot in chunks) passes the sidecar + its scale directly.
        if z_scale is not None:
            self._z_np = np.asarray(z)
            self._zscale_np = np.asarray(z_scale, np.float32)
            self._zf_np = None        # pre-quantized caller: no fp32 source
        else:
            self._z_np, self._zscale_np = quantize_profiles(
                z, self.profile_dtype)
            # keep the fp32 source (host-side only) when the resident
            # matrix is quantized: quantized scans over-fetch and the
            # exact re-rank gathers these few rows back
            self._zf_np = (None if self.profile_dtype == "fp32"
                           else np.asarray(z, np.float32))
        # exact-rescore row source, in precedence order: an explicit
        # gather callable (``ids -> (…, F) float32`` — the engine streaming
        # a lazy memmapped snapshot re-z-scores just the gathered rows), a
        # host fp32 copy of the corpus, or None (fp32 resident: the scan
        # itself is exact and no re-rank runs)
        if fp32_rows is not None:
            self._fp32_rows = fp32_rows
        elif self._zf_np is not None:
            self._fp32_rows = self._zf_np.__getitem__
        else:
            self._fp32_rows = None
        self._w_np = np.asarray(w)
        self._tids_np = (np.asarray(table_ids, np.int32)
                         if table_ids is not None
                         else np.zeros((self.n_live,), np.int32))
        self._ckeys_np = (np.asarray(band_keys, np.uint32)
                          if band_keys is not None else None)
        self._coarse_np = (np.asarray(coarse_keys, np.uint32)
                           if coarse_keys is not None else None)
        self._cids_np = np.arange(self.n_live, dtype=np.int32)
        if self.n_columns > self.n_live:
            # sentinel pad rows, mirroring place_sharded_corpus: the
            # exclusion mask scores cid < 0 rows -inf everywhere
            n = self.n_columns
            self._z_np = _pad_to(self._z_np, n,
                                 np.zeros((), self._z_np.dtype))
            self._w_np = _pad_to(self._w_np, n, FT.HASH_SENTINEL)
            self._tids_np = _pad_to(self._tids_np, n, -2)
            self._cids_np = _pad_to(self._cids_np, n, -1)
            if self._ckeys_np is not None:
                self._ckeys_np = _pad_to(self._ckeys_np, n, PAD_CORPUS)
            if self._coarse_np is not None:
                self._coarse_np = _pad_to(self._coarse_np, n, PAD_CORPUS)
        # spare-tail claim for the padded host mirrors: the FIRST same-
        # bucket successor writes its delta rows into this executor's pad
        # region in place (safe: cids/tids liveness masks are always per-
        # executor copies, so our views keep masking those rows dead);
        # later forks from the same predecessor fall back to a copy.  The
        # claim cell is SHARED by zero-delta successors (they alias the
        # same buffers, so a claim through either must stick for both).
        self._host_lock = threading.Lock()
        self._host_spare = [False]
        self.mesh = mesh
        # local plans run on the mesh's first device — a one-device mesh
        # pins a fleet replica's corpus and executables to its own chip;
        # no mesh leaves placement to JAX's default device
        self._device = None if mesh is None else mesh.devices.flat[0]
        self._gbdt = tuple(self._put(a) for a in gbdt_tuple)
        self._gbdt_bundle = PlacementBundle(
            {f"gbdt{i}": a for i, a in enumerate(self._gbdt)})
        self.score_block = int(score_block)
        # device-resident copies for the local pipelines
        self._z = self._put(self._z_np)
        self._zscale = self._put(self._zscale_np)
        self._w = self._put(self._w_np)
        self._cids = self._put(self._cids_np)
        self._tids = self._put(self._tids_np)
        self._ckeys = (self._put(self._ckeys_np)
                       if self._ckeys_np is not None else None)
        self._coarse = (self._put(self._coarse_np)
                        if self._coarse_np is not None else None)
        self._rows_bundle = PlacementBundle(dict(
            z=self._z, zscale=self._zscale, w=self._w, cids=self._cids,
            tids=self._tids, ckeys=self._ckeys, coarse=self._coarse))
        # host→device bytes spent placing this corpus view (a successor
        # built by ``extended`` uploads only its delta rows)
        self.bytes_uploaded = self._rows_bundle.nbytes()
        # sharded state, built lazily per placement (shard_axes / grid)
        self._placed: dict[tuple, dict] = {}
        self._pipelines: dict[tuple, object] = {}
        self._grid_meshes: dict[tuple, Mesh] = {}
        # AOT dispatch table: exact-shape executables registered by
        # ``aot_compile`` (fresh lower+compile or a persistent-cache load).
        # ``lower().compile()`` does NOT feed jax's jit call cache, so the
        # serving path must dispatch through this dict to reuse them; a
        # shape with no entry falls back to the plain jitted pipeline.
        self._compiled: dict[tuple, object] = {}
        self._exec_cache = exec_cache
        self._dispatch_stats = _new_dispatch_stats()
        self._closed = False
        # observability: duck-typed event sink (anything with
        # .publish(type, **payload) — service.events.EventBus; exec stays
        # dependency-free) + first-contact tracking so the compile spike
        # a (plan kind, grid, batch shape) pays on its first execution is
        # a visible event, not a mystery p99 outlier
        self._events = events
        self._seen_shapes: set[tuple] = set()
        self._tls = threading.local()

    def _put(self, x, dtype=None):
        """Place a local-pipeline operand on this executor's device."""
        return jnp.asarray(x, dtype, device=self._device)

    def _local_desc(self):
        """Cache-signature geometry of a local unit: the pinned device, so
        one replica never loads an executable built for another's chip."""
        return None if self._device is None else ("device",
                                                  int(self._device.id))

    # -- lifecycle ----------------------------------------------------------

    def close(self) -> None:
        """Release the corpus's device placements (local copies AND the
        cached sharded placement). One executor wraps one immutable catalog
        version; the MVCC engine keeps a per-version executor cache and
        closes each executor when the last in-flight query batch unpins its
        version — so retiring a snapshot actually frees device memory
        instead of leaking one corpus placement per catalog refresh.
        Idempotent; ``execute`` after close raises."""
        if self._closed:
            return
        self._closed = True
        self._placed.clear()
        self._pipelines.clear()
        self._grid_meshes.clear()
        self._compiled.clear()
        self._z = self._w = self._cids = self._tids = self._ckeys = None
        self._zscale = self._coarse = None
        # release the refcounted bundles: the row bundle is owned (freed
        # now unless a successor forked mid-flight), the GBDT bundle is
        # shared across versions and frees only at its last release
        self._rows_bundle.release()
        self._gbdt_bundle.release()

    @property
    def closed(self) -> bool:
        return self._closed

    # -- delta placement ----------------------------------------------------

    def extended(self, z_rows, w_rows, *, table_ids, band_keys=None,
                 coarse_keys=None, fp32_rows=None,
                 n_padded: int | None = None) -> "Executor":
        """Successor executor for an append-only corpus delta.

        Only the new rows (grain-padded to :data:`DELTA_ROW_GRAIN`) cross
        the host-device link: when the padded corpus stays inside the
        same column bucket, every device tensor is forked on-device by
        one ``dynamic_update_slice`` over the predecessor's resident
        array — compiled once per grain multiple and reused for every
        later ingest.  The successor shares the predecessor's GBDT
        placement (refcounted), its AOT dispatch table, pipelines and
        first-contact set, so a same-bucket successor serves with **zero
        recompiles**; a zero-row delta shares the row bundle outright.
        Sharded ``_placed`` corpora are rebuilt lazily on first sharded
        execute.  Crossing a bucket boundary re-places the corpus at the
        new padded size (ideally pre-warmed in the background first).

        ``z_rows`` must be fp32 z-scored rows under the predecessor's
        normalization stats — quantized-resident corpora fall back to a
        full rebuild at the engine layer.
        """
        if self._closed:
            raise RuntimeError("executor is closed")
        if self.profile_dtype != "fp32":
            raise NotImplementedError(
                "delta placement requires an fp32-resident corpus; "
                "quantized corpora take the full-rebuild path")
        z_rows = np.asarray(z_rows, np.float32)
        w_rows = np.asarray(w_rows, self._w_np.dtype)
        d = int(z_rows.shape[0])
        if (self._ckeys_np is None) != (band_keys is None):
            raise ValueError("band_keys must match the predecessor's")
        if (self._coarse_np is None) != (coarse_keys is None):
            raise ValueError("coarse_keys must match the predecessor's")
        n_live2 = self.n_live + d
        n_pad2 = max(int(n_padded), n_live2) if n_padded is not None \
            else max(self.n_columns, n_live2)

        ex = object.__new__(Executor)
        ex.n_live = n_live2
        ex.n_columns = n_pad2
        ex.profile_dtype = self.profile_dtype
        ex.survivor_block = self.survivor_block
        ex.mesh = self.mesh
        ex._device = self._device
        ex.score_block = self.score_block
        ex._zscale_np = self._zscale_np
        ex._zf_np = None
        ex._fp32_rows = fp32_rows
        ex._gbdt = self._gbdt
        ex._gbdt_bundle = self._gbdt_bundle.retain()
        ex._exec_cache = self._exec_cache
        ex._events = self._events
        ex._placed = {}
        ex._pipelines = dict(self._pipelines)
        ex._grid_meshes = dict(self._grid_meshes)
        ex._compiled = dict(self._compiled)
        ex._seen_shapes = set(self._seen_shapes)
        ex._dispatch_stats = _new_dispatch_stats()
        ex._tls = threading.local()
        ex._closed = False

        def cat(old, rows, fill):
            out = np.concatenate([np.asarray(old[:self.n_live]), rows]) \
                if d else np.asarray(old[:self.n_live])
            return _pad_to(out, n_pad2, fill)

        cid_rows = np.arange(self.n_live, n_live2, dtype=np.int32)
        tid_rows = np.asarray(table_ids, np.int32)
        # same-bucket successors write the big value mirrors into the
        # predecessor's spare pad tail in place (first claimant only) —
        # O(delta) instead of an O(bucket) host copy.  The pad rows'
        # VALUES changing under the predecessor is harmless: liveness is
        # decided by cids/tids, which stay per-executor copies below, so
        # every predecessor view keeps masking those rows dead.  A
        # zero-delta same-pad successor aliases the buffers outright and
        # shares the claim cell, so a later claim through either sticks.
        ex._host_lock = threading.Lock()
        same_pad = n_pad2 == self.n_columns
        inplace = False
        if d and same_pad:
            with self._host_lock:
                inplace = not self._host_spare[0]
                if inplace:
                    self._host_spare[0] = True
        ex._host_spare = self._host_spare if (d == 0 and same_pad) \
            else [False]

        def share(old, rows):
            old[self.n_live:n_live2] = rows
            return old

        if d == 0 and same_pad:
            ex._z_np, ex._w_np = self._z_np, self._w_np
            ex._ckeys_np, ex._coarse_np = self._ckeys_np, self._coarse_np
        elif inplace:
            ex._z_np = share(self._z_np, z_rows)
            ex._w_np = share(self._w_np, w_rows)
            ex._ckeys_np = None if band_keys is None else \
                share(self._ckeys_np, np.asarray(band_keys, np.uint32))
            ex._coarse_np = None if coarse_keys is None else \
                share(self._coarse_np, np.asarray(coarse_keys, np.uint32))
        else:
            ex._z_np = cat(self._z_np, z_rows, 0.0)
            ex._w_np = cat(self._w_np, w_rows, FT.HASH_SENTINEL)
            ex._ckeys_np = None if band_keys is None else \
                cat(self._ckeys_np, np.asarray(band_keys, np.uint32),
                    PAD_CORPUS)
            ex._coarse_np = None if coarse_keys is None else \
                cat(self._coarse_np, np.asarray(coarse_keys, np.uint32),
                    PAD_CORPUS)
        if d == 0 and same_pad:
            ex._tids_np, ex._cids_np = self._tids_np, self._cids_np
        else:
            ex._tids_np = cat(self._tids_np, tid_rows, -2)
            ex._cids_np = cat(self._cids_np, cid_rows, -1)

        if d == 0 and n_pad2 == self.n_columns:
            # nothing to upload: share the row bundle outright
            ex._z, ex._zscale, ex._w = self._z, self._zscale, self._w
            ex._cids, ex._tids = self._cids, self._tids
            ex._ckeys, ex._coarse = self._ckeys, self._coarse
            ex._rows_bundle = self._rows_bundle.retain()
            ex.bytes_uploaded = 0
        elif n_pad2 == self.n_columns:
            # same bucket: upload the grain-padded delta, fork on device
            grain = min(-(-d // DELTA_ROW_GRAIN) * DELTA_ROW_GRAIN,
                        n_pad2 - self.n_live)
            row0 = jnp.int32(self.n_live)
            olds: dict = {}
            news: dict = {}

            def stage(key, old_dev, rows, fill):
                olds[key] = old_dev
                news[key] = _pad_to(rows, grain, fill)

            stage("z", self._z, z_rows, 0.0)
            stage("w", self._w, w_rows, FT.HASH_SENTINEL)
            stage("cids", self._cids, cid_rows, -1)
            stage("tids", self._tids, tid_rows, -2)
            if band_keys is not None:
                stage("ckeys", self._ckeys,
                      np.asarray(band_keys, np.uint32), PAD_CORPUS)
            if coarse_keys is not None:
                stage("coarse", self._coarse,
                      np.asarray(coarse_keys, np.uint32), PAD_CORPUS)
            ex.bytes_uploaded = sum(int(v.nbytes) for v in news.values())
            upd = _update_rows_tree(olds, news, row0)
            ex._z, ex._w = upd["z"], upd["w"]
            ex._cids, ex._tids = upd["cids"], upd["tids"]
            ex._ckeys = upd.get("ckeys")
            ex._coarse = upd.get("coarse")
            ex._zscale = self._zscale        # per-feature: no row axis
        else:
            # bucket boundary crossed: re-place at the new padded size
            ex._z = ex._put(ex._z_np)
            ex._zscale = self._zscale
            ex._w = ex._put(ex._w_np)
            ex._cids = ex._put(ex._cids_np)
            ex._tids = ex._put(ex._tids_np)
            ex._ckeys = (ex._put(ex._ckeys_np)
                         if ex._ckeys_np is not None else None)
            ex._coarse = (ex._put(ex._coarse_np)
                          if ex._coarse_np is not None else None)
            ex.bytes_uploaded = sum(
                int(a.nbytes) for a in (ex._z, ex._w, ex._cids, ex._tids,
                                        ex._ckeys, ex._coarse)
                if a is not None)
        ex._rows_bundle = getattr(ex, "_rows_bundle", None) or \
            PlacementBundle(dict(
                z=ex._z, zscale=ex._zscale, w=ex._w, cids=ex._cids,
                tids=ex._tids, ckeys=ex._ckeys, coarse=ex._coarse))
        return ex

    # -- sharded state ------------------------------------------------------

    def _grid_mesh(self, grid: tuple) -> Mesh:
        """(q, d) -> a (query × data × model) mesh over this executor's
        devices (``launch.mesh.make_grid_mesh``, cached per geometry). The
        flat device order is preserved, so a (1, d) grid's data placement
        is byte-identical to the caller's own mesh."""
        from repro.launch.mesh import make_grid_mesh

        grid = tuple(grid)
        if grid not in self._grid_meshes:
            self._grid_meshes[grid] = make_grid_mesh(
                grid[0], grid[1], devices=self.mesh.devices)
        return self._grid_meshes[grid]

    def _plan_mesh_axes(self, plan: QueryPlan):
        """Mesh + (shard_axes, query_axes) a plan executes with.

        (1, d) grids keep the caller's mesh and replicated-query specs —
        the legacy 1-D pipeline, including multi-axis ``shard_axes``;
        q > 1 grids (or a caller mesh that already carries a non-trivial
        ``query`` axis) run on the re-shaped (query × data) grid mesh."""
        names = tuple(getattr(self.mesh, "axis_names", ()))
        premade_q = ("query" in names
                     and int(self.mesh.shape["query"]) > 1)
        if plan.grid[0] == 1 and not premade_q:
            return self.mesh, plan.shard_axes, ()
        return self._grid_mesh(plan.grid), ("data",), ("query",)

    def _corpus(self, plan: QueryPlan) -> dict:
        # one placement per (mesh geometry, data axes): band keys ride
        # along whenever the executor has them, so an "all" plan and a
        # pruned plan (e.g. the recall baseline next to the served plan)
        # share the z/w/cids/tids device copies instead of double-placing
        # the corpus
        mesh, axes, qaxes = self._plan_mesh_axes(plan)
        key = (plan.grid if qaxes else (), axes)
        if key not in self._placed:
            # sharded pipelines run on f32 shards: a quantized sidecar is
            # dequantized once at placement (the per-device shard is what
            # stays resident, so the transient full matrix is host-only)
            z = self._z_np
            if z.dtype != np.float32:
                z = np.asarray(z, np.float32) * self._zscale_np
            self._placed[key] = place_sharded_corpus(
                mesh, axes, z, self._w_np,
                table_ids=self._tids_np, band_keys=self._ckeys_np,
                cids=self._cids_np)
        return self._placed[key]

    def _pipeline(self, plan: QueryPlan):
        mesh, axes, qaxes = self._plan_mesh_axes(plan)
        key = (plan.candidates, plan.k, plan.budget_per_shard, axes,
               plan.grid if qaxes else ())
        if key not in self._pipelines:
            self._pipelines[key] = build_sharded_pipeline(
                mesh, self._gbdt, candidates=plan.candidates,
                k=plan.k,
                budget_per_shard=(plan.budget_per_shard
                                  if plan.candidates != "all" else None),
                shard_axes=axes, query_axes=qaxes, block=self.score_block,
                interpret=interpret_mode())
        return self._pipelines[key]

    # -- AOT warmup ---------------------------------------------------------

    def aot_compile(self, entries, *, cache=None,
                    n_columns: int | None = None) -> dict:
        """AOT-compile (or load from the persistent executable cache) every
        pipeline the ``(plan, padded_batch)`` pairs in ``entries`` would
        touch, register them in the dispatch table, and pre-seed the
        first-contact set — so a warmed shape's first real request carries
        no ``compile_ms`` attribution and no compile event.

        ``jit(...).lower(...).compile()`` bypasses jax's jit call cache,
        which is exactly why the result must be held in ``self._compiled``
        — and why the persistent-cache path is an honest restart
        measurement: nothing in the process jit cache can serve it.

        Publishes ``executable_cache_hit``/``executable_cache_miss`` per
        unit (with a ``remaining`` countdown the metrics layer exposes as
        the ``warmup_remaining`` gauge) and a ``compile_begin``/``end``
        pair for every fresh compile, so warmup compiles land in the same
        ``compile_ms`` histogram first-contact serving compiles do.
        Inadmissible plans (no band keys / coarse digest / mesh) are
        counted as skips, not errors.

        ``n_columns`` pre-warms for a DIFFERENT corpus size than the
        resident one — the background next-column-bucket warm ahead of a
        bucket-boundary crossing.  Corpus avals are shape stand-ins at
        that size (local plans only; sharded plans are skipped), and the
        compiled executables land in both the dispatch table and the
        persistent cache, keyed by the override size — a successor built
        at that bucket inherits them and serves its first request with
        zero compiles.  Returns a report dict."""
        if self._closed:
            raise RuntimeError("executor is closed")
        cache = cache if cache is not None else self._exec_cache
        units, seen_units, planned, skipped = [], set(), [], 0
        for plan, q in entries:
            us = self._plan_units(plan, int(q), n_columns=n_columns)
            if us is None:
                skipped += 1
                continue
            planned.append((plan, int(q)))
            for u in us:
                if u["key"] not in seen_units:
                    seen_units.add(u["key"])
                    units.append(u)
        report = {"n_plans": len(planned), "n_executables": len(units),
                  "skipped_plans": skipped, "cache_hits": 0,
                  "cache_misses": 0, "already_warm": 0, "compile_ms": 0.0}
        remaining = len(units)
        for u in units:
            remaining -= 1
            if u["key"] in self._compiled:
                report["already_warm"] += 1
                continue
            sig = exe = None
            if cache is not None:
                sig = cache.signature(u["name"], u["statics"],
                                      tree_aval_descriptors(u["dyn"]),
                                      u["mesh_desc"])
                exe = cache.load(sig)
            if exe is not None:
                report["cache_hits"] += 1
                if self._events is not None:
                    self._events.publish("executable_cache_hit",
                                         name=u["name"], n_queries=u["q"],
                                         remaining=remaining)
            else:
                if self._events is not None:
                    self._events.publish("compile_begin", plan=u["name"],
                                         grid=[], n_queries=u["q"], k=0,
                                         source="warmup")
                t0 = time.perf_counter()
                exe = u["lower"]().compile()
                ms = (time.perf_counter() - t0) * 1e3
                report["cache_misses"] += 1
                report["compile_ms"] += ms
                if self._events is not None:
                    self._events.publish("executable_cache_miss",
                                         name=u["name"], n_queries=u["q"],
                                         remaining=remaining)
                    self._events.publish("compile_end", plan=u["name"],
                                         grid=[], n_queries=u["q"], k=0,
                                         ms=ms, source="warmup")
                if cache is not None:
                    cache.store(sig, exe)
            self._compiled[u["key"]] = exe
        for plan, q in planned:
            self._seen_shapes.add((plan.kind, plan.k, plan.budget,
                                   plan.grid, q))
        return report

    def _plan_units(self, plan: QueryPlan, q: int,
                    n_columns: int | None = None):
        """Executable units — dispatch key, dynamic avals, lazy ``lower``
        thunk, cache-signature fields — that ``plan`` touches at padded
        batch ``q``: the scan pipeline, plus the exact-rescore re-rank when
        the resident profiles are quantized.  None when this executor
        cannot serve the plan at all.  ``n_columns`` overrides the corpus
        size (next-bucket pre-warm: corpus avals become shape stand-ins;
        local plans only)."""
        c_over = None if n_columns is None or \
            int(n_columns) == self.n_columns else int(n_columns)
        if (self.n_columns == 0 and c_over is None) or q <= 0:
            return None
        if plan.candidates != "all" and self._ckeys_np is None:
            return None
        if plan.candidates == "tiered" and (plan.sharded or
                                            self._coarse_np is None):
            return None
        if plan.sharded and (self.mesh is None or c_over is not None):
            return None
        fnum = int(self._z_np.shape[1])
        fw = int(self._w_np.shape[1])
        wdt = self._w_np.dtype
        S = jax.ShapeDtypeStruct
        units = []
        if plan.sharded:
            mesh, axes, qaxes = self._plan_mesh_axes(plan)
            corpus = self._corpus(plan)
            # _execute_sharded pads the batch to a multiple of q_shards
            qp = -(-q // plan.grid[0]) * plan.grid[0]
            qsh = NamedSharding(mesh, P(qaxes) if qaxes else P())
            sq = lambda shape, dt: S(shape, dt, sharding=qsh)
            if plan.candidates == "all":
                dyn = (corpus["z"], corpus["w"], corpus["cids"],
                       corpus["tids"], sq((qp, fnum), np.float32),
                       sq((qp, fw), wdt), sq((qp,), np.int32),
                       sq((qp,), np.int32))
            else:
                nb = int(self._ckeys_np.shape[1])
                dyn = (corpus["z"], corpus["w"], corpus["cids"],
                       corpus["tids"], corpus["ckeys"],
                       sq((qp, fnum), np.float32), sq((qp, fw), wdt),
                       sq((qp, nb), np.uint32), sq((qp,), np.int32),
                       sq((qp,), np.int32))
            statics = self._sharded_statics(plan)
            fn = self._pipeline(plan)
            mesh_desc = (tuple(str(a) for a in mesh.axis_names),
                         tuple(int(mesh.shape[a]) for a in mesh.axis_names),
                         tuple(axes), tuple(qaxes))
            units.append(dict(
                key=self._exe_key("sharded", qp, statics), name="sharded",
                q=qp, statics=statics, dyn=dyn, mesh_desc=mesh_desc,
                lower=lambda fn=fn, dyn=dyn: fn.lower(*dyn)))
            if self._fp32_rows is not None:
                # the sharded merge returns min(k, k_local · data shards)
                # columns — that width is the rescore gather's R
                d_total = 1
                for a in axes:
                    d_total *= int(mesh.shape[a])
                local_cols = int(corpus["z"].shape[0]) // max(d_total, 1)
                width = (plan.budget_per_shard
                         if plan.candidates != "all" else local_cols)
                r = min(plan.k, min(plan.k, max(width, 1)) * d_total)
                units.append(self._rescore_unit(q, r, plan.k, fnum, fw, wdt))
        else:
            name, fn, statics = self._local_spec(plan, n_columns=c_over)
            zq, wq = S((q, fnum), np.float32), S((q, fw), wdt)
            tqv, qidv = S((q,), np.int32), S((q,), np.int32)
            qk = (S((q, int(self._ckeys_np.shape[1])), np.uint32)
                  if plan.candidates != "all" else None)
            qc = (S((q, int(self._coarse_np.shape[1])), np.uint32)
                  if plan.candidates == "tiered" else None)
            dyn = self._local_dyn(plan, zq, wq, tqv, qidv, qk, qc,
                                  n_columns=c_over)
            units.append(dict(
                key=self._exe_key(name, q, statics, n_columns=c_over),
                name=name, q=q,
                statics=statics, dyn=dyn, mesh_desc=self._local_desc(),
                lower=lambda fn=fn, dyn=dyn, statics=statics:
                    fn.lower(*dyn, **statics)))
            if self._fp32_rows is not None:
                # local scans over-fetch: the pipeline's static k IS the
                # width of the top set handed to the exact re-rank
                units.append(self._rescore_unit(q, int(statics["k"]),
                                                plan.k, fnum, fw, wdt,
                                                n_columns=c_over))
        return units

    def _rescore_unit(self, q, r, k, fnum, fw, wdt, n_columns=None):
        S = jax.ShapeDtypeStruct
        statics = dict(k=k)
        dyn = (S((q, fnum), np.float32), S((q, fw), wdt),
               S((q, r, fnum), np.float32), S((q, r, fw), wdt),
               self._gbdt, S((q, r), np.float32), S((q, r), np.int32))
        return dict(key=self._exe_key("_rescore_exact", q, statics, (r,),
                                      n_columns=n_columns),
                    name="_rescore_exact", q=q, statics=statics, dyn=dyn,
                    mesh_desc=self._local_desc(),
                    lower=lambda dyn=dyn, k=k:
                        _rescore_exact.lower(*dyn, k=k))

    # -- entry point --------------------------------------------------------

    def execute(self, plan: QueryPlan, zq, wq, tq, qid, qkeys=None,
                qcoarse=None):
        """Run ``plan`` for a query batch.

        ``zq`` (Q, F_NUM) float32, ``wq`` (Q, F_WORDS) uint32; ``tq`` (Q,)
        table ids to exclude (-1 disables); ``qid`` (Q,) global column id
        of resident queries (-1 for external); ``qkeys`` (Q, B) LSH band
        keys, required by pruned plans; ``qcoarse`` (Q, S) super-band
        digest keys, required by tiered plans. Returns numpy
        ``(scores (Q, k), ids (Q, k), n_scored (Q,))``.
        """
        if self._closed:
            raise RuntimeError("executor is closed (its snapshot version "
                               "was retired); pin a live version instead")
        q = int(np.asarray(zq).shape[0])
        if self.n_live == 0 or q == 0:
            return (np.full((q, plan.k), -np.inf, np.float32),
                    np.full((q, plan.k), -1, np.int32),
                    np.zeros((q,), np.int32))
        if plan.candidates != "all":
            if self._ckeys_np is None:
                raise ValueError(f"plan {plan.kind!r} needs LSH band keys, "
                                 f"but this executor has none")
            if qkeys is None:
                raise ValueError(f"plan {plan.kind!r} needs query band keys")
        if plan.candidates == "tiered":
            if plan.sharded:
                raise ValueError("tiered plans are local-only")
            if self._coarse_np is None:
                raise ValueError("plan 'tiered' needs a coarse super-band "
                                 "digest, but this executor has none")
            if qcoarse is None:
                raise ValueError("plan 'tiered' needs coarse query keys")
        if plan.sharded and self.mesh is None:
            raise ValueError(f"plan {plan.kind!r} needs a mesh")
        # first contact with this (kind, k, budget, grid, batch shape)
        # pays the jit trace+compile inside the dispatch below — surface
        # it as a compile_begin/end event pair and stash the wall in a
        # thread-local the engine folds into the request trace
        shape_key = (plan.kind, plan.k, plan.budget, plan.grid, q)
        first = shape_key not in self._seen_shapes
        self._seen_shapes.add(shape_key)
        self._tls.compile_ms = None
        if first and self._events is not None:
            self._events.publish("compile_begin", plan=plan.kind,
                                 grid=list(plan.grid), n_queries=q, k=plan.k)
        t0 = time.perf_counter()
        self._tls.tier_stats = None
        tier = None
        if plan.sharded:
            sc, ids, n = self._execute_sharded(plan, zq, wq, tq, qid, qkeys)
        else:
            sc, ids, n, tier = self._execute_local(plan, zq, wq, tq, qid,
                                                   qkeys, qcoarse)
        counts = () if tier is None else (tier["hits"], tier["survivors"])
        if self._fp32_rows is not None:
            # exact fp32 re-rank of the quantized scan's top set (local
            # scans over-fetched RESCORE_MULT × k above; sharded scans
            # re-rank their returned k — ordering repaired, no recovery
            # of ids the quantized scan dropped); the coarse-pass counts
            # ride along in its blocking copy of the scan's ids
            reranked = int(ids.shape[1])
            sc, ids, counts = self._rescore(zq, wq, sc, ids, plan.k, counts)
        else:
            reranked = 0
        with spans.span("executor.fetch"):
            if counts:      # tiered: the counts join this copy (or came
                            # back already with the re-rank's ids)
                sc, ids, n, *counts = jax.device_get((sc, ids, n, *counts))
            sc, ids = pad_topk(np.asarray(sc), np.asarray(ids), plan.k)
            n = np.asarray(n)           # block until ready before timing
        if tier is not None:
            self._tls.tier_stats = dict(
                hits=counts[0], survivors=counts[1],
                survivor_slots=tier["survivor_slots"], reranked=reranked)
        if first:
            wall_ms = (time.perf_counter() - t0) * 1e3
            self._tls.compile_ms = wall_ms
            if self._events is not None:
                self._events.publish("compile_end", plan=plan.kind,
                                     grid=list(plan.grid), n_queries=q,
                                     k=plan.k, ms=wall_ms)
        return sc, ids, n

    def last_compile_ms(self) -> float | None:
        """First-contact compile+execute wall of this thread's most recent
        ``execute`` call, or None when the shape was already warm."""
        return getattr(self._tls, "compile_ms", None)

    def last_tier_stats(self) -> dict | None:
        """Coarse-pass counts of this thread's most recent ``execute``
        call when it ran a tiered plan, else None: per padded query row
        ``hits`` (direct digest hits) and ``survivors`` (digest-eligible
        columns, block-mates included), as numpy; ``survivor_slots``, the
        survivor gather width per query; ``reranked``, the rows per query
        the exact fp32 re-rank scored (0 for an fp32 scan)."""
        return getattr(self._tls, "tier_stats", None)

    # -- internals ----------------------------------------------------------

    def _rescore(self, zq, wq, sc, ids, k: int, extra=()):
        """Gather the scan's candidate rows from the fp32 source and
        re-rank them exactly.  The gather is (Q, R, F) with R a small
        multiple of k, so the cost is independent of the lake size.
        ``extra`` device arrays come to the host in the same blocking
        copy as the scan's ids. Returns the re-rank's (scores, ids) and
        ``extra`` as numpy, all under the ``freyja.executor.rescore``
        span."""
        with spans.span("executor.rescore"):
            with spans.span("executor.fetch"):
                ids_np, sc_np, *extra = jax.device_get((ids, sc, *extra))
            # clip to live rows, not the bucket-padded corpus: the fp32
            # source may be an unpadded view (invalid slots are -1 → row
            # 0, already excluded by the scan's non-finite score)
            safe = np.clip(ids_np, 0, self.n_live - 1)
            with spans.span("executor.put"):
                dyn = (self._put(zq, jnp.float32), self._put(wq),
                       self._put(self._fp32_rows(safe), jnp.float32),
                       self._put(self._w_np[safe]), self._gbdt,
                       self._put(sc_np), self._put(ids_np))
            sc, ids = self._call("_rescore_exact", _rescore_exact, dyn,
                                 dict(k=k), extra=(int(ids_np.shape[1]),))
        return sc, ids, tuple(extra)

    # -- AOT dispatch -------------------------------------------------------

    def _exe_key(self, name: str, q: int, statics: dict, extra=(),
                 n_columns: int | None = None) -> tuple:
        # the corpus dimension is part of the executable's identity: a
        # successor inheriting ``_compiled`` across a bucket crossing must
        # not dispatch an old-bucket executable (same statics, different
        # corpus avals), and next-bucket pre-warm entries must land under
        # keys the post-crossing successor actually looks up
        c = self.n_columns if n_columns is None else int(n_columns)
        return (name, int(q), int(c), tuple(sorted(statics.items())),
                tuple(extra))

    def _call(self, name, fn, dyn, statics: dict, extra=()):
        """Dispatch one pipeline call: the AOT-compiled executable when
        warmup registered this exact shape (statics are baked in, only the
        dynamic args are passed), else the plain jitted fallback."""
        exe = self._compiled.get(
            self._exe_key(name, dyn[0].shape[0], statics, extra))
        self._dispatch_stats["aot" if exe is not None else "fallback"] += 1
        with spans.span("executor.dispatch", program=name,
                        aot=int(exe is not None)):
            if exe is not None:
                return exe(*dyn)
            return fn(*dyn, **statics)

    def dispatch_stats(self) -> dict:
        """AOT vs jit-fallback dispatch counts — a warmed engine serving
        only ladder shapes must show zero fallbacks (test-gated) — and,
        under ``selection``, how many pruned or tiered dispatches picked
        their full-width candidates by ``threshold`` or by ``top_k``."""
        stats = dict(self._dispatch_stats)
        stats["selection"] = dict(stats["selection"])
        return stats

    def _count_selection(self, c: int, budget: int) -> None:
        """Count the selection one dispatch's full-width pick of
        ``budget`` out of ``c`` columns runs (the pipelines decide it by
        the same rule from the same static shape)."""
        self._dispatch_stats["selection"][stages.selection(c, budget)] += 1

    def _local_spec(self, plan: QueryPlan, n_columns: int | None = None):
        """(name, fn, statics) of the local pipeline ``plan`` runs — one
        resolution shared by the serving dispatch and AOT warmup, so their
        dispatch keys agree byte-for-byte.  ``n_columns`` overrides the
        clamp dimension for next-bucket pre-warm."""
        c = self.n_columns if n_columns is None else int(n_columns)
        # quantized scans hand an over-fetched top set to the exact fp32
        # re-rank in execute(); fp32 scans keep k as-is
        k = (plan.k if self._fp32_rows is None
             else max(plan.k, RESCORE_MULT * plan.k))
        if plan.candidates == "all":
            return ("_local_all", _local_all,
                    dict(k=min(k, c), block=self.score_block))
        budget = min(plan.budget, c)
        if plan.candidates == "tiered":
            surv = min(max(plan.survivor_budget, budget), c)
            return ("_local_tiered", _local_tiered,
                    dict(k=min(k, budget, surv), budget=min(budget, surv),
                         survivor_budget=surv, block_c=self.survivor_block,
                         interpret=interpret_mode()))
        return ("_local_pruned", _local_pruned,
                dict(kind=plan.candidates, k=min(k, budget), budget=budget,
                     interpret=interpret_mode()))

    def _local_dyn(self, plan: QueryPlan, zq, wq, tq, qid, qkeys, qcoarse,
                   n_columns: int | None = None):
        """Dynamic-argument tuple of the local pipeline, in call order.
        With ``n_columns`` set, corpus arrays become shape stand-ins at
        that size (next-bucket pre-warm lowers against the future corpus
        shapes without materializing them)."""
        if n_columns is None:
            z, w = self._z, self._w
            cids, tids = self._cids, self._tids
            ckeys, coarse = self._ckeys, self._coarse
        else:
            S = jax.ShapeDtypeStruct
            c = int(n_columns)
            z = S((c, int(self._z_np.shape[1])), self._z_np.dtype)
            w = S((c, int(self._w_np.shape[1])), self._w_np.dtype)
            cids = S((c,), np.int32)
            tids = S((c,), np.int32)
            ckeys = (S((c, int(self._ckeys_np.shape[1])), np.uint32)
                     if self._ckeys_np is not None else None)
            coarse = (S((c, int(self._coarse_np.shape[1])), np.uint32)
                      if self._coarse_np is not None else None)
        if plan.candidates == "all":
            return (zq, wq, tq, qid, z, self._zscale, w, cids, tids,
                    self._gbdt)
        if plan.candidates == "tiered":
            return (zq, wq, qkeys, qcoarse, tq, qid, z, self._zscale,
                    w, ckeys, coarse, cids, tids, self._gbdt)
        return (zq, wq, qkeys, tq, qid, z, self._zscale, w,
                ckeys, cids, tids, self._gbdt)

    def _sharded_statics(self, plan: QueryPlan) -> dict:
        """Identity of a sharded pipeline for dispatch/cache keys — the
        ``_pipeline`` cache key, spelled as a statics mapping."""
        _, axes, qaxes = self._plan_mesh_axes(plan)
        return dict(candidates=plan.candidates, k=plan.k,
                    budget_per_shard=(plan.budget_per_shard
                                      if plan.candidates != "all" else 0),
                    axes=axes, grid=plan.grid if qaxes else ())

    def _execute_local(self, plan, zq, wq, tq, qid, qkeys, qcoarse=None):
        with spans.span("executor.put"):
            zq, wq = self._put(zq, jnp.float32), self._put(wq)
            tq = self._put(tq, jnp.int32)
            qid = self._put(qid, jnp.int32)
            qkeys = self._put(qkeys) if qkeys is not None else None
            qcoarse = self._put(qcoarse) if qcoarse is not None else None
        name, fn, statics = self._local_spec(plan)
        dyn = self._local_dyn(plan, zq, wq, tq, qid, qkeys, qcoarse)
        if plan.candidates != "all":
            # a tiered plan's full-width pick is its survivor pick
            self._count_selection(self.n_columns, statics.get(
                "survivor_budget", statics["budget"]))
        out = self._call(name, fn, dyn, statics)
        if plan.candidates == "tiered":
            # the coarse-pass counts stay on the device here; execute()
            # copies them with the batch's first blocking copy
            sc, ids, n, n_hits, n_surv = out
            return sc, ids, n, dict(
                hits=n_hits, survivors=n_surv,
                survivor_slots=int(statics["survivor_budget"]))
        return (*out, None)

    def _execute_sharded(self, plan, zq, wq, tq, qid, qkeys):
        corpus = self._corpus(plan)
        mesh, axes, qaxes = self._plan_mesh_axes(plan)
        # pad the batch to a multiple of the query-axis size; duplicate
        # results are sliced off below
        if qkeys is not None:
            (zq, wq, tq, qid, qkeys), q = pad_rows(
                (zq, wq, tq, qid, qkeys), plan.grid[0])
        else:
            (zq, wq, tq, qid), q = pad_rows((zq, wq, tq, qid),
                                            plan.grid[0])
        qsharding = NamedSharding(mesh, P(qaxes) if qaxes else P())
        put = lambda a, dt=None: jax.device_put(
            np.asarray(a, dt) if dt else np.asarray(a), qsharding)
        with spans.span("executor.put"):
            if plan.candidates == "all":
                args = (corpus["z"], corpus["w"], corpus["cids"],
                        corpus["tids"], put(zq, np.float32), put(wq),
                        put(tq, np.int32), put(qid, np.int32))
            else:
                args = (corpus["z"], corpus["w"], corpus["cids"],
                        corpus["tids"], corpus["ckeys"],
                        put(zq, np.float32), put(wq),
                        put(qkeys, np.uint32), put(tq, np.int32),
                        put(qid, np.int32))
        if plan.candidates != "all":
            width = corpus["z"].shape[0] // int(np.prod(
                [mesh.shape[a] for a in axes]))
            self._count_selection(width, min(plan.budget_per_shard, width))
        key = self._exe_key("sharded", np.asarray(zq).shape[0],
                            self._sharded_statics(plan))
        exe = self._compiled.get(key)
        self._dispatch_stats["aot" if exe is not None else "fallback"] += 1
        with spans.span("executor.dispatch", program="sharded",
                        aot=int(exe is not None)):
            sc, ids, n = (exe if exe is not None
                          else self._pipeline(plan))(*args)
            return sc[:q], ids[:q], n[:q]
