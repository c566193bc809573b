"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): 16×16 = 256 chips per pod (TPU v5e), and 2 pods = 512
chips with a leading ``pod`` axis for the multi-pod dry-run.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests)."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"))


def make_replica_meshes(n_replicas: int, *, devices=None,
                        model: int = 1) -> list:
    """Partition the device set into ``n_replicas`` contiguous slices and
    build one ``(data, model)`` sub-mesh per replica.

    The fleet (``repro.service.fleet``) pins each engine replica to its
    own slice so replicas never contend for device memory or compute.
    Slices are contiguous — on real TPU topologies neighbouring device
    ids share ICI links, so a contiguous slice keeps each replica's
    collectives on-chip instead of crossing the fleet boundary.

    A one-device slice (4 chips, 4 replicas) is a ``(1, 1)`` mesh: the
    replica serves local plans, and the executor pins its corpus and
    executables to that device.  When the pool is too small to give every
    replica ``model`` devices (e.g. 4 host devices, 8 replicas) every
    replica gets ``None`` — they share JAX's default device.  A
    non-dividing replica count leaves the trailing remainder devices
    unused rather than building lopsided slices (uneven replicas would
    defeat the router's cost symmetry).
    """
    import numpy as np

    devs = np.asarray(devices if devices is not None else jax.devices())
    devs = devs.reshape(-1)
    n_replicas = int(n_replicas)
    if n_replicas < 1:
        raise ValueError(f"n_replicas must be >= 1; got {n_replicas}")
    per = devs.size // n_replicas
    if per < max(model, 1):
        # not enough devices to give every replica its own slice
        return [None] * n_replicas
    per -= per % model                   # keep the model axis dividing
    meshes = []
    for i in range(n_replicas):
        chunk = devs[i * per:(i + 1) * per]
        meshes.append(jax.sharding.Mesh(
            chunk.reshape(per // model, model), ("data", "model")))
    return meshes


def make_grid_mesh(q_shards: int, d_shards: int, *, devices=None):
    """2-D (query × data) grid mesh for discovery serving.

    ``q_shards`` shards the concurrent query batch, ``d_shards`` the
    lake's column axis — each device owns one (Q-shard, C-shard) tile of
    the scoring problem (``repro.exec.sharded``). Degenerate geometries
    are both useful: ``(1, d)`` is the classic replicated-query data
    sharding, ``(q, 1)`` replicates the corpus to scale concurrent
    batches. ``devices`` defaults to all local devices and must be
    divisible by ``q_shards × d_shards``; the remainder becomes a trailing
    ``model`` axis (replicated by discovery placements).
    """
    import numpy as np

    devs = np.asarray(devices if devices is not None else jax.devices())
    devs = devs.reshape(-1)
    n = q_shards * d_shards
    if n <= 0 or devs.size % n:
        raise ValueError(f"grid ({q_shards}, {d_shards}) does not tile "
                         f"{devs.size} devices")
    return jax.sharding.Mesh(devs.reshape(q_shards, d_shards, devs.size // n),
                             ("query", "data", "model"))
