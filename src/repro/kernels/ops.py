"""Public jit'd entry points for the Pallas kernels.

``interpret_mode()`` decides for every caller whether a kernel compiles
natively (TPU) or runs through the Pallas interpreter (elsewhere — the
kernel body executes as jnp ops, validated against ``ref.py``).

Only the main-path kernels compile for TPU: ``minhash`` (ingest) and the
``lsh_probe`` family (serving). The TPU compiler refuses the five kernels
that serve only tests and the paper-table benchmarks:

* ``fused_score``, ``fused_score_q``, ``profile_distance``, ``gbdt_infer``:
  "Unimplemented primitive in Pallas TPU lowering for KernelType.TC:
  dynamic_slice";
* ``quality_cdf``: "The Pallas TPU lowering currently requires that the
  last two dimensions of your block shape are divisible by 8 and 128
  respectively, or be equal to the respective dimensions of the overall
  array".
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.gbdt import GBDTParams
from repro.kernels.gbdt_infer import gbdt_infer_pallas
from repro.kernels.lsh_probe import (lsh_probe_gathered_pallas,
                                     lsh_probe_pallas)
from repro.kernels.minhash import make_permutations, minhash_pallas
from repro.kernels.profile_distance import (fused_score_pallas,
                                            fused_score_q_pallas,
                                            profile_distance_pallas,
                                            quantize_profiles)
from repro.kernels.quality_cdf import quality_cdf_pallas

__all__ = ["interpret_mode", "gbdt_infer", "profile_distance", "fused_score",
           "fused_score_q", "minhash", "lsh_probe", "lsh_probe_gathered",
           "quality_cdf", "quantize_profiles"]


def interpret_mode() -> bool:
    """True unless the default backend is a TPU: the one place that decides
    whether Pallas kernels compile natively or run through the interpreter
    (on the serving path, the jnp reference)."""
    return jax.default_backend() != "tpu"


def gbdt_infer(x, params: GBDTParams, *, block_n: int = 1024):
    feats, thrs, leaves, base = params.astuple()
    return gbdt_infer_pallas(jnp.asarray(x), jnp.asarray(feats),
                             jnp.asarray(thrs), jnp.asarray(leaves),
                             base=float(base), block_n=block_n,
                             interpret=interpret_mode())


def profile_distance(zq, wq, zc, wc, *, block_q: int = 8, block_n: int = 256):
    return profile_distance_pallas(jnp.asarray(zq), jnp.asarray(wq),
                                   jnp.asarray(zc), jnp.asarray(wc),
                                   block_q=block_q, block_n=block_n,
                                   interpret=interpret_mode())


def fused_score(zq, wq, zc, wc, params: GBDTParams, *, block_q: int = 8,
                block_n: int = 256):
    feats, thrs, leaves, base = params.astuple()
    return fused_score_pallas(jnp.asarray(zq), jnp.asarray(wq),
                              jnp.asarray(zc), jnp.asarray(wc),
                              jnp.asarray(feats), jnp.asarray(thrs),
                              jnp.asarray(leaves), base=float(base),
                              block_q=block_q, block_n=block_n,
                              interpret=interpret_mode())


def fused_score_q(zq, wq, zc, scale, wc, params: GBDTParams, *,
                  block_q: int = 8, block_n: int = 256):
    """Fused scoring over a quantized (int8/fp16) corpus sidecar."""
    feats, thrs, leaves, base = params.astuple()
    return fused_score_q_pallas(jnp.asarray(zq), jnp.asarray(wq),
                                jnp.asarray(zc), jnp.asarray(scale),
                                jnp.asarray(wc), jnp.asarray(feats),
                                jnp.asarray(thrs), jnp.asarray(leaves),
                                base=float(base), block_q=block_q,
                                block_n=block_n, interpret=interpret_mode())


def minhash(values, *, n_perm: int = 128, seed: int = 0,
            block_c: int = 8, block_r: int = 256):
    a, b = make_permutations(n_perm, seed)
    return minhash_pallas(jnp.asarray(values), a, b, block_c=block_c,
                          block_r=block_r, interpret=interpret_mode())


def lsh_probe(qkeys, ckeys, *, block_q: int = 8, block_c: int = 512):
    return lsh_probe_pallas(jnp.asarray(qkeys), jnp.asarray(ckeys),
                            block_q=block_q, block_c=block_c,
                            interpret=interpret_mode())


def lsh_probe_gathered(qkeys, ckeys, *, block_q: int = 8, block_c: int = 256):
    """Fine probe over per-query gathered survivor keys (Q, C', B)."""
    return lsh_probe_gathered_pallas(jnp.asarray(qkeys), jnp.asarray(ckeys),
                                     block_q=block_q, block_c=block_c,
                                     interpret=interpret_mode())


def quality_cdf(j, k, *, strictness: float = 0.25, block: int = 4096):
    return quality_cdf_pallas(jnp.asarray(j), jnp.asarray(k),
                              strictness=strictness, block=block,
                              interpret=interpret_mode())
