"""Decoder-only LM assembly for all families (dense / moe / vlm / ssm /
hybrid). Layers are stacked and scanned (``jax.lax.scan``) so HLO size is
depth-independent; per-block remat (``cfg.remat``) bounds activation memory.

Families:
* dense / vlm  — [norm → GQA attn → +res, norm → (Sw)iGLU MLP → +res] × L
* moe          — MLP replaced by ``moe_ffn_local`` (shard_map, TP or EP)
* ssm (rwkv6)  — RWKV6 time-mix + channel-mix blocks
* hybrid       — zamba2: groups of ``attn_every`` mamba2 blocks followed by
                 a **shared** (single-parameter) attention+MLP block
                 (simplified from the paper's concat+LoRA variant; see
                 DESIGN.md §Arch-applicability)

VLM: ``img`` stub embeddings replace the first ``n_patches`` token
embeddings (the CLIP frontend is out of scope per the assignment).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (apply_mlp, apply_norm, dtype_of, embed_tokens,
                                 init_embed, init_mlp, init_norm, lm_logits,
                                 stack_layers)


def _batch_axes(mesh):
    from repro.dist import sharding as shd
    return shd.batch_axes(mesh)


def constrain(x, mesh, *axes):
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*axes)))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_dense_block(cfg, key, dtype):
    k1, k2 = jax.random.split(key)
    pa, sa = attn.init_attention(cfg, k1, dtype)
    pm, sm = (init_mlp(cfg, k2, dtype) if cfg.family != "moe"
              else moe_mod.init_moe(cfg, k2, dtype))
    pn1, sn1 = init_norm(cfg, dtype)
    pn2, sn2 = init_norm(cfg, dtype)
    return ({"attn": pa, "mlp": pm, "ln1": pn1, "ln2": pn2},
            {"attn": sa, "mlp": sm, "ln1": sn1, "ln2": sn2})


def init_lm(cfg, key):
    dtype = dtype_of(cfg.param_dtype)
    ke, kb, kf = jax.random.split(key, 3)
    pe, se = init_embed(cfg, ke, dtype)
    pn, sn = init_norm(cfg, dtype)
    params: dict[str, Any] = {"embed": pe, "final_norm": pn}
    specs: dict[str, Any] = {"embed": se, "final_norm": sn}

    keys = jax.random.split(kb, max(cfg.n_layers, 1))
    if cfg.family in ("dense", "moe", "vlm"):
        inits = [_init_dense_block(cfg, keys[i], dtype) for i in range(cfg.n_layers)]
        params["blocks"] = stack_layers([p for p, _ in inits])
        specs["blocks"] = jax.tree.map(lambda a: ("layers",) + a, inits[0][1],
                                       is_leaf=lambda x: isinstance(x, tuple))
    elif cfg.family == "ssm":
        inits = [rwkv_mod.init_rwkv_block(cfg, keys[i], dtype)
                 for i in range(cfg.n_layers)]
        params["blocks"] = stack_layers([p for p, _ in inits])
        specs["blocks"] = jax.tree.map(lambda a: ("layers",) + a, inits[0][1],
                                       is_leaf=lambda x: isinstance(x, tuple))
    elif cfg.family == "hybrid":
        inits = [ssm_mod.init_mamba2(cfg, keys[i], dtype)
                 for i in range(cfg.n_layers)]
        params["blocks"] = stack_layers([p for p, _ in inits])
        specs["blocks"] = jax.tree.map(lambda a: ("layers",) + a, inits[0][1],
                                       is_leaf=lambda x: isinstance(x, tuple))
        pshared, sshared = _init_dense_block(
            dataclasses_replace_family(cfg), kf, dtype)
        params["shared"] = pshared
        specs["shared"] = sshared
        pn3, sn3 = init_norm(cfg, dtype)
        params["blocks_norm"] = _stack_norms(cfg, dtype, cfg.n_layers)
        specs["blocks_norm"] = {"scale": ("layers", "embed")} if cfg.norm == "rmsnorm" \
            else {"scale": ("layers", "embed"), "bias": ("layers", "embed")}
        params["shared_norm"] = pn3
        specs["shared_norm"] = sn3
    else:
        raise ValueError(cfg.family)
    return params, specs


def dataclasses_replace_family(cfg):
    import dataclasses
    return dataclasses.replace(cfg, family="dense")


def _stack_norms(cfg, dtype, n):
    p = {"scale": jnp.ones((n, cfg.d_model), dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((n, cfg.d_model), dtype)
    return p


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _moe_apply(pm, cfg, x, mesh):
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    if mesh is None:
        y = moe_mod.moe_ffn_local(pm, cfg, x2, model_axis=None)
    else:
        import numpy as np
        from repro.dist.sharding import spec_of
        ba = _batch_axes(mesh)
        nba = int(np.prod([mesh.shape[a] for a in ba])) if ba else 1
        # tiny decode batches (long_500k B=1) replicate tokens instead
        divisible = (b * s) % nba == 0 and b * s >= nba
        xspec = P(ba, None) if divisible else P(None, None)
        pspecs = jax.tree.map(
            lambda a: spec_of(a, mesh), _moe_specs(cfg),
            is_leaf=lambda v: isinstance(v, tuple))

        from repro.dist.sharding import get_mode
        # dp mode replicates expert weights — no model-axis collective
        maxis = "model" if (get_mode() != "dp" and "model" in mesh.axis_names) \
            else None

        def local_fn(pm_, x_):
            return moe_mod.moe_ffn_local(pm_, cfg, x_, model_axis=maxis)

        fn = jax.shard_map(local_fn, mesh=mesh, in_specs=(pspecs, xspec),
                           out_specs=xspec, check_vma=False)
        y = fn(pm, x2)
    return y.reshape(b, s, d)


def _moe_specs(cfg):
    if cfg.moe_sharding == "ep":
        return {"router": ("none", "none"), "w_gate": ("expert", "none", "none"),
                "w_up": ("expert", "none", "none"),
                "w_down": ("expert", "none", "none")}
    return {"router": ("none", "none"), "w_gate": ("none", "none", "mlp"),
            "w_up": ("none", "none", "mlp"), "w_down": ("none", "mlp", "none")}


def _dense_block_fwd(pl, cfg, x, positions, mesh):
    h = apply_norm(pl["ln1"], x, cfg.norm)
    h = attn.attention_train(pl["attn"], cfg, h, positions)
    x = x + h
    x = constrain(x, mesh, _batch_axes(mesh) if mesh else None)
    h = apply_norm(pl["ln2"], x, cfg.norm)
    if cfg.family == "moe":
        h = _moe_apply(pl["mlp"], cfg, h, mesh)
    else:
        h = apply_mlp(pl["mlp"], h, cfg.act)
    return x + h


def forward(params, cfg, tokens, *, img=None, mesh=None):
    """Training/prefill forward. tokens (B, S) -> logits (B, S, V) f32."""
    cdt = dtype_of(cfg.compute_dtype)
    b, s = tokens.shape
    x = embed_tokens(params["embed"], tokens, cdt)
    if cfg.family == "vlm" and img is not None:
        x = jnp.concatenate([img.astype(cdt), x[:, cfg.n_patches:]], axis=1)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = constrain(x, mesh, _batch_axes(mesh) if mesh else None)

    if cfg.family in ("dense", "moe", "vlm"):
        def body(xc, pl):
            return _dense_block_fwd(pl, cfg, xc, positions, mesh), None
        if cfg.remat == "block":
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = jax.lax.scan(body, x, params["blocks"])
    elif cfg.family == "ssm":
        def body(xc, pl):
            out, _ = rwkv_mod.rwkv_block_forward(pl, cfg, xc)
            return out, None
        if cfg.remat == "block":
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = jax.lax.scan(body, x, params["blocks"])
    elif cfg.family == "hybrid":
        x = _hybrid_forward(params, cfg, x, positions, mesh)
    else:
        raise ValueError(cfg.family)

    x = apply_norm(params["final_norm"], x, cfg.norm)
    return lm_logits(params["embed"], x)


def _hybrid_forward(params, cfg, x, positions, mesh):
    ae = cfg.attn_every
    n_groups = cfg.n_layers // ae
    blocks = jax.tree.map(
        lambda a: a.reshape((n_groups, ae) + a.shape[1:]), params["blocks"])
    norms = jax.tree.map(
        lambda a: a.reshape((n_groups, ae) + a.shape[1:]), params["blocks_norm"])
    shared = params["shared"]
    shared_norm = params["shared_norm"]

    def mamba_body(xc, pl_and_norm):
        pl, nl = pl_and_norm
        h = apply_norm(nl, xc, cfg.norm)
        return xc + ssm_mod.mamba2_forward(pl, cfg, h), None

    if cfg.remat == "block":
        mamba_body = jax.checkpoint(mamba_body, prevent_cse=False)

    def group(xc, grp):
        gp, gn = grp
        xc, _ = jax.lax.scan(mamba_body, xc, (gp, gn))
        # shared attention + MLP block (same params every group)
        h = apply_norm(shared_norm, xc, cfg.norm)
        h = attn.attention_train(shared["attn"], cfg, h, positions)
        xc = xc + h
        h = apply_norm(shared["ln2"], xc, cfg.norm)
        xc = xc + apply_mlp(shared["mlp"], h, cfg.act)
        return xc, None

    x, _ = jax.lax.scan(group, x, (blocks, norms))
    return x


# ---------------------------------------------------------------------------
# prefill: forward that also materializes decode caches
# ---------------------------------------------------------------------------

def _roll_pad(a, cache_size: int):
    """Pack a (B, S, ...) tensor into ``cache_size`` slots (ring layout)."""
    s = a.shape[1]
    if s >= cache_size:
        return jnp.roll(a[:, s - cache_size:], s % cache_size, axis=1)
    pad = [(0, 0), (0, cache_size - s)] + [(0, 0)] * (a.ndim - 2)
    return jnp.pad(a, pad)


def _kv_to_cache(cfg, k, v, cache_size: int):
    """Pack post-RoPE (B, S, nk, dh) K/V into a decode cache (ring layout
    for sliding-window archs; int8 + scales when cfg.kv_quant)."""
    if cfg.kv_quant:
        qk, sk = attn.quantize_kv(k)
        qv, sv = attn.quantize_kv(v)
        return {"k": _roll_pad(qk, cache_size), "v": _roll_pad(qv, cache_size),
                "k_scale": _roll_pad(sk, cache_size),
                "v_scale": _roll_pad(sv, cache_size)}
    return {"k": _roll_pad(k, cache_size), "v": _roll_pad(v, cache_size)}


def forward_with_caches(params, cfg, tokens, cache_size: int, *, img=None,
                        mesh=None):
    """Prefill: returns (logits (B, S, V), decode caches with len = S)."""
    cdt = dtype_of(cfg.compute_dtype)
    b, s = tokens.shape
    x = embed_tokens(params["embed"], tokens, cdt)
    if cfg.family == "vlm" and img is not None:
        x = jnp.concatenate([img.astype(cdt), x[:, cfg.n_patches:]], axis=1)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = constrain(x, mesh, _batch_axes(mesh) if mesh else None)
    slen = jnp.asarray(s, jnp.int32)

    if cfg.family in ("dense", "moe", "vlm"):
        size = cache_size if cfg.sliding_window is None \
            else min(cache_size, cfg.sliding_window)

        def body(xc, pl):
            h = apply_norm(pl["ln1"], xc, cfg.norm)
            h, (k, v) = attn.attention_train(pl["attn"], cfg, h, positions,
                                             return_kv=True)
            xc = xc + h
            h = apply_norm(pl["ln2"], xc, cfg.norm)
            if cfg.family == "moe":
                h = _moe_apply(pl["mlp"], cfg, h, mesh)
            else:
                h = apply_mlp(pl["mlp"], h, cfg.act)
            return xc + h, _kv_to_cache(cfg, k.astype(cdt), v.astype(cdt), size)

        x, kv = jax.lax.scan(body, x, params["blocks"])
        caches = {"kv": kv, "len": slen, "offset": jnp.zeros((), jnp.int32)}
    elif cfg.family == "ssm":
        def body(xc, pl):
            out, st = rwkv_mod.rwkv_block_forward(pl, cfg, xc)
            return out, st
        x, st = jax.lax.scan(body, x, params["blocks"])
        caches = {"rwkv": st, "len": slen}
    elif cfg.family == "hybrid":
        x, caches = _hybrid_prefill(params, cfg, x, positions, cache_size, mesh)
        caches["len"] = slen
    else:
        raise ValueError(cfg.family)

    x = apply_norm(params["final_norm"], x, cfg.norm)
    return lm_logits(params["embed"], x), caches


def _hybrid_prefill(params, cfg, x, positions, cache_size, mesh):
    ae = cfg.attn_every
    n_groups = cfg.n_layers // ae
    blocks = jax.tree.map(
        lambda a: a.reshape((n_groups, ae) + a.shape[1:]), params["blocks"])
    norms = jax.tree.map(
        lambda a: a.reshape((n_groups, ae) + a.shape[1:]), params["blocks_norm"])
    shared = params["shared"]
    cdt = x.dtype

    def mamba_body(xc, pl_and_norm):
        pl, nl = pl_and_norm
        h = apply_norm(nl, xc, cfg.norm)
        out, st = ssm_mod.mamba2_forward(pl, cfg, h, return_state=True)
        return xc + out, st

    def group(xc, grp):
        gp, gn = grp
        xc, st = jax.lax.scan(mamba_body, xc, (gp, gn))
        h = apply_norm(params["shared_norm"], xc, cfg.norm)
        h, (k, v) = attn.attention_train(shared["attn"], cfg, h, positions,
                                         return_kv=True)
        xc = xc + h
        h = apply_norm(shared["ln2"], xc, cfg.norm)
        xc = xc + apply_mlp(shared["mlp"], h, cfg.act)
        kc, vc = _kv_to_cache(cfg, k.astype(cdt), v.astype(cdt), cache_size)
        return xc, (st, {"k": kc, "v": vc})

    x, (mamba, kv) = jax.lax.scan(group, x, (blocks, norms))
    mamba = jax.tree.map(lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), mamba)
    return x, {"mamba": mamba, "kv": kv, "offset": jnp.zeros((), jnp.int32)}


# ---------------------------------------------------------------------------
# decode (one token, stacked caches)
# ---------------------------------------------------------------------------

def init_caches(cfg, batch: int, seq: int):
    """Stacked per-layer decode caches for the given cache length."""
    cdt = dtype_of(cfg.compute_dtype)
    l = cfg.n_layers

    def stackd(make, n):
        one = make()
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), one)

    if cfg.family in ("dense", "moe", "vlm"):
        kv = stackd(lambda: {k: v for k, v in attn.init_kv_cache(cfg, batch, seq, cdt).items()
                             if k not in ("len", "offset")}, l)
        return {"kv": kv, "len": jnp.zeros((), jnp.int32),
                "offset": jnp.zeros((), jnp.int32)}
    if cfg.family == "ssm":
        st = stackd(lambda: rwkv_mod.init_rwkv_state(cfg, batch, cdt), l)
        return {"rwkv": st, "len": jnp.zeros((), jnp.int32)}
    if cfg.family == "hybrid":
        n_groups = cfg.n_layers // cfg.attn_every
        mamba = stackd(lambda: ssm_mod.init_mamba2_state(cfg, batch, cdt), l)
        kv = stackd(lambda: {k: v for k, v in attn.init_kv_cache(cfg, batch, seq, cdt).items()
                             if k in ("k", "v")}, n_groups)
        return {"mamba": mamba, "kv": kv, "len": jnp.zeros((), jnp.int32),
                "offset": jnp.zeros((), jnp.int32)}
    raise ValueError(cfg.family)


def decode_step(params, cfg, tokens, caches, *, mesh=None):
    """tokens (B, 1) -> (logits (B, 1, V), new caches)."""
    cdt = dtype_of(cfg.compute_dtype)
    x = embed_tokens(params["embed"], tokens, cdt)
    x = constrain(x, mesh, _batch_axes(mesh) if mesh else None)

    if cfg.family in ("dense", "moe", "vlm"):
        def body(xc, inp):
            pl, kv = inp
            cache = {**kv, "len": caches["len"], "offset": caches["offset"]}
            h = apply_norm(pl["ln1"], xc, cfg.norm)
            h, nc = attn.attention_decode(pl["attn"], cfg, h, cache)
            xc = xc + h
            h = apply_norm(pl["ln2"], xc, cfg.norm)
            if cfg.family == "moe":
                h = _moe_apply(pl["mlp"], cfg, h, mesh)
            else:
                h = apply_mlp(pl["mlp"], h, cfg.act)
            return xc + h, {k2: nc[k2] for k2 in kv}

        x, new_kv = jax.lax.scan(body, x, (params["blocks"], caches["kv"]))
        new = {"kv": new_kv, "len": caches["len"] + 1, "offset": caches["offset"]}
    elif cfg.family == "ssm":
        def body(xc, inp):
            pl, st = inp
            out, ns = rwkv_mod.rwkv_block_forward(pl, cfg, xc, state=st)
            return out, ns
        x, new_st = jax.lax.scan(body, x, (params["blocks"], caches["rwkv"]))
        new = {"rwkv": new_st, "len": caches["len"] + 1}
    elif cfg.family == "hybrid":
        x, new = _hybrid_decode(params, cfg, x, caches)
    else:
        raise ValueError(cfg.family)

    x = apply_norm(params["final_norm"], x, cfg.norm)
    return lm_logits(params["embed"], x), new


def _hybrid_decode(params, cfg, x, caches):
    ae = cfg.attn_every
    n_groups = cfg.n_layers // ae
    blocks = jax.tree.map(
        lambda a: a.reshape((n_groups, ae) + a.shape[1:]), params["blocks"])
    norms = jax.tree.map(
        lambda a: a.reshape((n_groups, ae) + a.shape[1:]), params["blocks_norm"])
    mamba = jax.tree.map(
        lambda a: a.reshape((n_groups, ae) + a.shape[1:]), caches["mamba"])
    shared = params["shared"]

    def mamba_body(xc, inp):
        pl, nl, st = inp
        h = apply_norm(nl, xc, cfg.norm)
        out, ns = ssm_mod.mamba2_decode(pl, cfg, h, st)
        return xc + out, ns

    def group(carry, inp):
        xc = carry
        gp, gn, gst, kv = inp
        xc, new_st = jax.lax.scan(mamba_body, xc, (gp, gn, gst))
        cache = {"k": kv["k"], "v": kv["v"], "len": caches["len"],
                 "offset": caches["offset"]}
        h = apply_norm(params["shared_norm"], xc, cfg.norm)
        h, nc = attn.attention_decode(shared["attn"], cfg, h, cache)
        xc = xc + h
        h = apply_norm(shared["ln2"], xc, cfg.norm)
        xc = xc + apply_mlp(shared["mlp"], h, cfg.act)
        return xc, (new_st, {"k": nc["k"], "v": nc["v"]})

    x, (new_mamba, new_kv) = jax.lax.scan(group, x, (blocks, norms, mamba, caches["kv"]))
    new_mamba = jax.tree.map(
        lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), new_mamba)
    return x, {"mamba": new_mamba, "kv": new_kv, "len": caches["len"] + 1,
               "offset": caches["offset"]}
