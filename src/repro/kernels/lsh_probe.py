"""Pallas TPU kernel: batched LSH bucket probe.

Given banded-MinHash bucket keys for a batch of queries (Q, B) and for the
whole catalog (C, B), the probe marks column c a candidate for query q iff
the two share a bucket in at least one band:

    hit[q, c] = any_b (qkeys[q, b] == ckeys[c, b])

This is the candidate-generation stage of the discovery pipeline
(``repro.exec``): an O(Q·C·B) stream of uint32 equality compares (VPU
work, no MXU, no floats) instead of the O(Q·C·F_DIST·T) fused GBDT scan —
the kernel's output mask picks the <<C columns the expensive scorer
actually sees. Under a sharded plan the kernel runs *inside* ``shard_map``
on each device's (C/devices, B) key shard (``exec/sharded.py``), so
candidate generation scales with the lake exactly like scoring; corpus
padding rows use ``PAD_CORPUS`` and never collide with query keys.

Tiling mirrors ``minhash.py``: the grid walks (Q, C) tiles, each program
loads a (Qb, B) and a (Cb, B) key block into VMEM and emits the (Qb, Cb)
int32 hit block. VMEM working set with the defaults (8 × 512 × 64 × 4 B
intermediate) is ~1 MB.

Dispatch: ``interpret=True`` means "no TPU here" (the CPU fallback every
serving path takes in this container), and interpret-mode ``pallas_call``
re-enters the Pallas interpreter once per grid step — at full-lake grids
(hundreds of tiles for 10^5 columns) that overhead outweighs the actual
uint32 compare stream by 30-100×.  The tile entry points therefore lower
to the jnp reference oracle when ``interpret`` is requested: identical
math, one fused XLA op.  ``lsh_probe_pallas`` / ``lsh_probe_gathered_pallas``
keep running the real interpreter so the parity suites still exercise the
kernel bodies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import ref as _ref

# Padding keys: queries and corpus pad with *different* sentinels so padded
# rows never match anything (including each other).
PAD_QUERY = np.uint32(0xFFFFFFFF)
PAD_CORPUS = np.uint32(0xFFFFFFFE)


def _kernel(qk_ref, ck_ref, out_ref):
    q = qk_ref[...]                                     # (Qb, B) u32
    c = ck_ref[...]                                     # (Cb, B) u32
    eq = q[:, None, :] == c[None, :, :]                 # (Qb, Cb, B)
    out_ref[...] = jnp.any(eq, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_q", "block_c", "interpret"))
def lsh_probe_pallas(qkeys, ckeys, *, interpret: bool, block_q: int = 8,
                     block_c: int = 512):
    """qkeys (Q, B) u32, ckeys (C, B) u32 -> (Q, C) int32 hit mask."""
    q, b = qkeys.shape
    c = ckeys.shape[0]
    qp = -(-q // block_q) * block_q
    cp = -(-c // block_c) * block_c
    qk = jnp.pad(qkeys, ((0, qp - q), (0, 0)), constant_values=PAD_QUERY)
    ck = jnp.pad(ckeys, ((0, cp - c), (0, 0)), constant_values=PAD_CORPUS)
    out = pl.pallas_call(
        _kernel,
        grid=(qp // block_q, cp // block_c),
        in_specs=[
            pl.BlockSpec((block_q, b), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, b), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp, cp), jnp.int32),
        interpret=interpret,
    )(qk, ck)
    return out[:q, :c]


def lsh_probe_tile(qkeys, ckeys, *, interpret: bool, block_q: int = 8,
                   block_c: int = 512):
    """Per-(Q-shard, C-shard) tile entry point for grid pipelines.

    Under a 2-D (query × data) ``shard_map`` each device probes only its
    local query shard — often just 1-4 rows when the batch is spread over
    the ``query`` mesh axis. This wrapper clamps the query tile to the
    local shard size (and the corpus tile to the local column count) so a
    q-sharded probe doesn't pad every tiny shard up to the global default
    tile; shapes are static inside ``jit``/``shard_map``, so the clamp
    costs nothing at trace time.

    With ``interpret=True`` (no TPU) the probe lowers to the jnp reference
    instead of the per-tile Pallas interpreter — see the module docstring.
    """
    if interpret:
        return _ref.lsh_probe_ref(qkeys, ckeys)
    bq = max(1, min(int(block_q), int(qkeys.shape[0]) or 1))
    bc = max(1, min(int(block_c), int(ckeys.shape[0]) or 1))
    return lsh_probe_pallas(qkeys, ckeys, block_q=bq, block_c=bc,
                            interpret=interpret)


# ---------------------------------------------------------------------------
# Skinny-survivor geometry: per-query gathered corpora (tiered fine pass)
# ---------------------------------------------------------------------------

def _gathered_kernel(qk_ref, ck_ref, out_ref):
    q = qk_ref[...]                                     # (Qb, B) u32
    c = ck_ref[...]                                     # (Qb, Cb, B) u32
    eq = q[:, None, :] == c                             # (Qb, Cb, B)
    out_ref[...] = jnp.any(eq, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_q", "block_c", "interpret"))
def lsh_probe_gathered_pallas(qkeys, ckeys, *, interpret: bool,
                              block_q: int = 8, block_c: int = 256):
    """Fine probe over per-query gathered survivors.

    ``qkeys`` (Q, B) u32 against ``ckeys`` (Q, C', B) u32 — each query
    brings its *own* gathered key rows (the coarse pass's survivors,
    padded with ``PAD_CORPUS`` up to the static survivor budget C').
    Returns the (Q, C') int32 hit mask.

    The tiered geometry is skinny: C' is a few hundred to a few thousand
    where the full-lake probe sees 10^5+, so the corpus tile defaults much
    smaller (256) and is clamped to C' — one program often covers a whole
    query row's survivors.
    """
    q, b = qkeys.shape
    cprime = ckeys.shape[1]
    bq = max(1, min(int(block_q), q or 1))
    bc = max(1, min(int(block_c), cprime or 1))
    qp = -(-q // bq) * bq
    cp = -(-cprime // bc) * bc
    qk = jnp.pad(qkeys, ((0, qp - q), (0, 0)), constant_values=PAD_QUERY)
    ck = jnp.pad(ckeys, ((0, qp - q), (0, cp - cprime), (0, 0)),
                 constant_values=PAD_CORPUS)
    out = pl.pallas_call(
        _gathered_kernel,
        grid=(qp // bq, cp // bc),
        in_specs=[
            pl.BlockSpec((bq, b), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, bc, b), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp, cp), jnp.int32),
        interpret=interpret,
    )(qk, ck)
    return out[:q, :cprime]


def lsh_probe_gathered_tile(qkeys, ckeys, *, interpret: bool,
                            block_q: int = 8, block_c: int = 256):
    """Dispatching entry point for the gathered fine probe: jnp reference
    when ``interpret`` is requested (CPU fallback), the Pallas kernel when
    compiling natively — same contract as ``lsh_probe_tile``."""
    if interpret:
        return _ref.lsh_probe_gathered_ref(qkeys, ckeys)
    return lsh_probe_gathered_pallas(qkeys, ckeys, block_q=block_q,
                                     block_c=block_c, interpret=interpret)
