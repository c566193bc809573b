import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

512 placeholder host devices stand in for 2 TPU v5e pods; ``.lower()`` /
``.compile()`` prove the sharding config is coherent (no mismatched specs,
no unsupported collectives, no shape errors) and yield per-device
FLOPs/bytes (cost_analysis), memory (memory_analysis) and the collective
schedule (HLO parse) that EXPERIMENTS.md §Dry-run/§Roofline report.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m \
      --shape train_4k --mesh single            # one cell
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist import sharding as shd
from repro.launch import hlo_analysis as hlo
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.models.config import ArchConfig
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.trainer import build_train_step
from repro.serve.engine import build_serve_step

ARTIFACT_DIR = "artifacts/dryrun"


# ---------------------------------------------------------------------------
# sharding of inputs
# ---------------------------------------------------------------------------

def _batch_spec(mesh, b: int, extra=()):
    ba = shd.batch_axes(mesh)
    nba = int(np.prod([mesh.shape[a] for a in ba])) if ba else 1
    if b % nba == 0 and b >= nba:
        return P(ba, *extra)
    return P(None, *extra)


def cache_shardings(cfg: ArchConfig, caches_sds, b: int, mesh: Mesh):
    """Sharding for decode caches: batch-shard when divisible, else shard
    the sequence axis (flash-decoding style); kv-heads over 'model' when
    divisible (else replicated)."""
    ba = shd.batch_axes(mesh)
    nba = int(np.prod([mesh.shape[a] for a in ba])) if ba else 1
    mp = mesh.shape.get("model", 1)
    batch_ok = b % nba == 0 and b >= nba

    def spec_for(path, leaf):
        shape = leaf.shape
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if leaf.ndim == 0:
            return P()
        axes = [None] * leaf.ndim
        if "kv/k" in name or "kv/v" in name or "cross/" in name:
            # (L, B, S, nk, dh[,or scales (L,B,S,nk)])
            if batch_ok:
                axes[1] = ba
            elif shape[2] % nba == 0:
                axes[2] = ba                      # sequence-sharded cache
            if shape[3] % mp == 0 and shape[3] >= mp:
                axes[3] = "model"                 # kv heads over model
            elif axes[2] is None and shape[2] % mp == 0 and shape[2] >= mp:
                axes[2] = "model"                 # else: sequence over model
                # (flash-decoding combine via SPMD all-reduce)
        elif "mamba/ssm" in name:                 # (L, B, H, N, P)
            if batch_ok:
                axes[1] = ba
            if shape[2] % mp == 0:
                axes[2] = "model"
        elif "mamba/conv" in name:                # (L, B, K-1, C)
            if batch_ok:
                axes[1] = ba
            if shape[3] % mp == 0:
                axes[3] = "model"
        elif "rwkv" in name:
            if batch_ok:
                axes[1] = ba
        return P(*axes)

    flat, treedef = jax.tree_util.tree_flatten_with_path(caches_sds)
    specs = [spec_for(path, leaf) for path, leaf in flat]
    return jax.tree.unflatten(treedef, [NamedSharding(mesh, s) for s in specs])


def batch_shardings(cfg: ArchConfig, specs: dict, b: int, mesh: Mesh):
    out = {}
    for k, v in specs.items():
        if k == "caches":
            out[k] = cache_shardings(cfg, v, b, mesh)
        else:
            extra = (None,) * (v.ndim - 1)
            out[k] = NamedSharding(mesh, _batch_spec(mesh, b, extra))
    return out


# ---------------------------------------------------------------------------
# cell runners
# ---------------------------------------------------------------------------

def _params_sds(cfg):
    holder = {}

    def f():
        p, s = registry.init_params(cfg, jax.random.PRNGKey(0))
        holder["specs"] = s          # static side-channel (specs are strings)
        return p

    params_sds = jax.eval_shape(f)
    return params_sds, holder["specs"]


def lower_cell(arch: str, shape_name: str, mesh: Mesh, *,
               zero1: bool = True, accum: int = 1, kv_quant: bool = False,
               mode: str = "tp", moe_sharding: str | None = None,
               remat: str | None = None):
    """Returns (lowered, aux) for the cell. Raises on unsupported cells."""
    shd.set_mode(mode)
    cfg = registry.get_config(arch)
    if remat is not None:
        cfg = dataclasses.replace(cfg, remat=remat)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    if moe_sharding is not None and cfg.family == "moe":
        cfg = dataclasses.replace(cfg, moe_sharding=moe_sharding)
    ok, reason = registry.cell_supported(cfg, shape_name)
    if not ok:
        raise SkipCell(reason)
    sh = registry.SHAPES[shape_name]
    b, kind = sh["batch"], sh["kind"]

    params_sds, logical_specs = _params_sds(cfg)
    pshard = shd.param_shardings(logical_specs, mesh, params=params_sds)
    in_specs = registry.input_specs(cfg, shape_name)
    bshard = batch_shardings(cfg, in_specs, b, mesh)

    if kind == "train":
        opt_sds = jax.eval_shape(lambda: init_opt_state(params_sds))
        if zero1:
            mspec_named = shd.zero1_shardings(logical_specs, params_sds, mesh)
            mspecs = jax.tree.map(lambda ns: ns.spec, mspec_named)
        else:
            mspec_named = shd.param_shardings(logical_specs, mesh)
            mspecs = jax.tree.map(lambda ns: ns.spec, mspec_named)
        oshard = {"m": mspec_named, "v": mspec_named,
                  "step": NamedSharding(mesh, P())}
        step = build_train_step(cfg, AdamWConfig(), mesh=mesh, accum=accum,
                                moment_specs=mspecs)
        jitted = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(params_sds, opt_sds, in_specs)
    elif kind == "prefill":
        def fwd(params, batch):
            return registry.forward(params, cfg, batch, mesh=mesh)
        jitted = jax.jit(fwd, in_shardings=(pshard, bshard))
        lowered = jitted.lower(params_sds, in_specs)
    else:  # decode
        step = build_serve_step(cfg, mesh=mesh)
        jitted = jax.jit(step, in_shardings=(pshard, bshard["tokens"],
                                             bshard["caches"]),
                         donate_argnums=(2,))
        lowered = jitted.lower(params_sds, in_specs["tokens"],
                               in_specs["caches"])
    return lowered, {"cfg": cfg, "kind": kind, "batch": b, "seq": sh["seq"],
                     "kv_bytes": 1 if kv_quant else 2, "mode": mode}


class SkipCell(Exception):
    pass


def lower_freyja_cell(mesh: Mesh, *, bf16_profiles: bool = False):
    """The paper's own distributed discovery query as a dry-run cell."""
    from repro.configs import freyja_discovery as FD
    from repro.core import features as FT
    from repro.exec import build_sharded_pipeline
    from repro.kernels.ops import interpret_mode
    n, q, k = FD.N_COLUMNS, FD.N_QUERIES, FD.TOP_K
    zdt = jnp.bfloat16 if bf16_profiles else jnp.float32
    ba = shd.batch_axes(mesh)
    gb = (jnp.zeros((50, 5), jnp.int32), jnp.zeros((50, 5), jnp.float32),
          jnp.zeros((50, 32), jnp.float32), jnp.float32(0.5))
    fn = build_sharded_pipeline(mesh, gb, candidates="all", k=k,
                                interpret=interpret_mode(), shard_axes=ba)
    shard = NamedSharding(mesh, P(ba))
    shard2 = NamedSharding(mesh, P(ba, None))
    rep = NamedSharding(mesh, P())
    args = (jax.ShapeDtypeStruct((n, FT.F_NUM), zdt),
            jax.ShapeDtypeStruct((n, FT.F_WORDS), jnp.uint32),
            jax.ShapeDtypeStruct((n,), jnp.int32),      # cids
            jax.ShapeDtypeStruct((n,), jnp.int32),      # tids
            jax.ShapeDtypeStruct((q, FT.F_NUM), zdt),
            jax.ShapeDtypeStruct((q, FT.F_WORDS), jnp.uint32),
            jax.ShapeDtypeStruct((q,), jnp.int32),      # tq
            jax.ShapeDtypeStruct((q,), jnp.int32))      # qid
    jitted = jax.jit(fn, in_shardings=(shard2, shard2, shard, shard,
                                       rep, rep, rep, rep))
    return jitted.lower(*args), {"kind": "discover", "batch": q, "seq": n,
                                 "cfg": None}


# ---------------------------------------------------------------------------
# analysis + driver
# ---------------------------------------------------------------------------

def analyze(lowered, aux, mesh: Mesh, *, zero1: bool = True) -> dict:
    from repro.launch.costmodel import cell_cost

    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):       # older jax returns [dict]
        cost = cost[0] if cost else {}
    flops = float(cost.get("flops", 0.0))
    byt = float(cost.get("bytes accessed", 0.0))
    try:
        mem = compiled.memory_analysis()
        mem_stats = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception:
        mem_stats = {}
    coll = hlo.parse_collectives(compiled.as_text())

    cfg = aux.get("cfg")
    n_dev = int(np.prod(list(mesh.shape.values())))
    result = {
        "kind": aux["kind"], "batch": aux["batch"], "seq": aux["seq"],
        "n_devices": n_dev,
        "mesh": dict(mesh.shape),
        "compile_s": compile_s,
        # raw XLA tool numbers — While bodies counted ONCE (lower bounds for
        # loops, unfused upper bound for bytes); see DESIGN.md §7
        "xla_flops_per_device": flops,
        "xla_bytes_per_device": byt,
        "xla_collective_bytes_per_device": coll.total_bytes,
        "collectives": coll.bytes_by_op,
        "collective_counts": coll.count_by_op,
        "memory": mem_stats,
    }
    if cfg is not None:
        ac = cell_cost(cfg, aux["kind"], aux["batch"], aux["seq"],
                       dict(mesh.shape), zero1=zero1,
                       kv_cache_dtype_bytes=aux.get("kv_bytes", 2),
                       mode=aux.get("mode", "tp"))
        terms = hlo.roofline_terms(ac.flops, ac.hbm_bytes, ac.coll_bytes)
        n_params = cfg.param_count()
        n_active = cfg.active_param_count()
        tokens = aux["batch"] * (aux["seq"] if aux["kind"] != "decode" else 1)
        mult = 6 if aux["kind"] == "train" else 2
        model_flops = mult * n_active * tokens
        t_model = model_flops / n_dev / hlo.PEAK_FLOPS
        result.update(
            flops_per_device=ac.flops,
            bytes_per_device=ac.hbm_bytes,
            collective_bytes_per_device=ac.coll_bytes,
            cost_detail=ac.detail,
            n_params=n_params, n_active_params=n_active,
            model_flops=model_flops,
            useful_flops_ratio=model_flops / (ac.flops * n_dev) if ac.flops else 0.0,
            roofline_fraction=t_model / terms["bound_s"] if terms["bound_s"] else 0.0,
            **terms,
        )
    else:
        terms = hlo.roofline_terms(flops, byt, coll.total_bytes)
        result.update(flops_per_device=flops, bytes_per_device=byt,
                      collective_bytes_per_device=coll.total_bytes, **terms)
    return result


def run_cell(arch: str, shape_name: str, mesh_kind: str, *, zero1=True,
             accum=1, out_dir: str = ARTIFACT_DIR, tag: str = "",
             kv_quant: bool = False, mode: str = "tp",
             moe_sharding: str | None = None, mesh_override: str | None = None,
             freyja_bf16: bool = False, remat: str | None = None) -> dict:
    if mesh_override:
        dims = tuple(int(x) for x in mesh_override.split("x"))
        if mesh_kind == "multi":
            mesh = jax.make_mesh((2,) + dims, ("pod", "data", "model"))
        else:
            mesh = jax.make_mesh(dims, ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    try:
        if arch == "freyja-discovery":
            lowered, aux = lower_freyja_cell(mesh, bf16_profiles=freyja_bf16)
        else:
            lowered, aux = lower_cell(arch, shape_name, mesh, zero1=zero1,
                                      accum=accum, kv_quant=kv_quant,
                                      mode=mode, moe_sharding=moe_sharding,
                                      remat=remat)
        lower_s = time.time() - t0
        result = analyze(lowered, aux, mesh, zero1=zero1)
        result.update(arch=arch, shape=shape_name, mesh_kind=mesh_kind,
                      lower_s=lower_s, status="ok",
                      variant={"kv_quant": kv_quant, "mode": mode,
                               "zero1": zero1, "accum": accum,
                               "moe_sharding": moe_sharding,
                               "mesh_override": mesh_override,
                               "freyja_bf16": freyja_bf16})
    except SkipCell as e:
        result = {"arch": arch, "shape": shape_name, "mesh_kind": mesh_kind,
                  "status": "skip", "reason": str(e)}
    except Exception as e:
        result = {"arch": arch, "shape": shape_name, "mesh_kind": mesh_kind,
                  "status": "error", "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-2000:]}
    os.makedirs(out_dir, exist_ok=True)
    name = f"{arch}__{shape_name}__{mesh_kind}{tag}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(result, f, indent=1, default=str)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--sharding-mode", default="tp", choices=["tp", "fsdp", "dp"])
    ap.add_argument("--moe-sharding", default=None, choices=[None, "tp", "ep"])
    ap.add_argument("--mesh-override", default=None,
                    help="e.g. 64x4 — same chip count, different data×model split")
    ap.add_argument("--freyja-bf16", action="store_true")
    ap.add_argument("--remat", default=None, choices=[None, "block", "none"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--out-dir", default=ARTIFACT_DIR)
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = [(a, s) for a in registry.list_archs() for s in registry.SHAPES]
        cells.append(("freyja-discovery", "query"))
    else:
        shape = args.shape
        if shape is None and args.arch == "freyja-discovery":
            shape = "query"              # the discovery cell's only shape
        cells = [(args.arch, shape)]

    for mk in meshes:
        for arch, shape in cells:
            t0 = time.time()
            r = run_cell(arch, shape, mk, zero1=not args.no_zero1,
                         accum=args.accum, out_dir=args.out_dir, tag=args.tag,
                         kv_quant=args.kv_quant, mode=args.sharding_mode,
                         moe_sharding=args.moe_sharding,
                         mesh_override=args.mesh_override,
                         freyja_bf16=args.freyja_bf16, remat=args.remat)
            status = r["status"]
            extra = ""
            if status == "ok":
                extra = (f"bottleneck={r['bottleneck']} "
                         f"t=({r['t_compute_s']:.3f},{r['t_memory_s']:.3f},"
                         f"{r['t_collective_s']:.3f})s")
            elif status == "skip":
                extra = r["reason"]
            else:
                extra = r["error"][:160]
            print(f"[{mk:6s}] {arch:22s} {str(shape):11s} {status:5s} "
                  f"{time.time()-t0:6.1f}s {extra}", flush=True)


if __name__ == "__main__":
    main()
