"""Pallas TPU kernel: MinHash signatures (the syntactic baseline family).

For every column (tile of ``block_c``) and every permutation p, the kernel
streams value tiles of ``block_r`` through VMEM and keeps a running
element-wise minimum of the universal hash ``h_p(v) = a_p · v + b_p`` (uint32
wrap-around arithmetic — multiply-shift hashing). The output block revisits
the same (Cb, P) tile across the R grid dimension, initialized on the first
visit — the standard Pallas accumulation pattern.

VMEM working set: (block_c, block_r) values + (block_c, block_r, P) hash
intermediate when unchunked; with the defaults (8 × 256 × 128 × 4 B = 1 MB)
it fits comfortably.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import features as FT

_SENT = np.uint32(FT.HASH_SENTINEL)
_UMAX = np.uint32(0xFFFFFFFF)
# Mosaic has no unsigned integer reductions: flipping the sign bit maps
# uint32 order onto int32 order, so the kernel reduces in int32 and the
# wrapper flips the bit back — bit-identical to an unsigned minimum.
_SIGN = np.uint32(0x80000000)
_IMAX = np.int32(0x7FFFFFFF)


def _kernel(vals_ref, a_ref, b_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, _IMAX, jnp.int32)

    v = vals_ref[...]                                  # (Cb, Rb) u32
    a = a_ref[...][0]                                  # (P,) u32
    b = b_ref[...][0]
    h = v[:, :, None] * a[None, None, :] + b[None, None, :]
    h = jnp.where(v[:, :, None] == _SENT, _UMAX, h)
    h = jax.lax.bitcast_convert_type(h ^ _SIGN, jnp.int32)
    m = jnp.min(h, axis=1)                             # (Cb, P) i32
    out_ref[...] = jnp.minimum(out_ref[...], m)


@functools.partial(jax.jit, static_argnames=("block_c", "block_r", "interpret"))
def minhash_pallas(values, a, b, *, interpret: bool, block_c: int = 8,
                   block_r: int = 256):
    """values (C, R) u32 sentinel-padded, a/b (P,) u32 -> (C, P) u32."""
    c, r = values.shape
    p = a.shape[0]
    cp = -(-c // block_c) * block_c
    rp = -(-r // block_r) * block_r
    vp = jnp.pad(values, ((0, cp - c), (0, rp - r)),
                 constant_values=np.uint32(FT.HASH_SENTINEL))
    out = pl.pallas_call(
        _kernel,
        grid=(cp // block_c, rp // block_r),
        in_specs=[
            pl.BlockSpec((block_c, block_r), lambda i, j: (i, j)),
            pl.BlockSpec((1, p), lambda i, j: (0, 0)),
            pl.BlockSpec((1, p), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_c, p), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cp, p), jnp.int32),
        interpret=interpret,
    )(vp, a[None], b[None])
    return jax.lax.bitcast_convert_type(out[:c], jnp.uint32) ^ _SIGN


def make_permutations(n_perm: int = 128, seed: int = 0):
    """Odd multipliers + offsets for multiply-shift universal hashing."""
    rng = np.random.default_rng(seed)
    a = (rng.integers(1, 2 ** 32, size=n_perm, dtype=np.uint64) | 1).astype(np.uint32)
    b = rng.integers(0, 2 ** 32, size=n_perm, dtype=np.uint64).astype(np.uint32)
    return jnp.asarray(a), jnp.asarray(b)
