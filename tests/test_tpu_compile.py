"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Nothing runs on a chip: each kernel is lowered with ``interpret=False`` at
real widths for a *described* ``v5e:2x2`` topology and compiled by the TPU
compiler installed with JAX. That catches the tiling, layout and lowering
refusals interpret mode cannot (an unsigned reduction in ``minhash`` was
one). The topology is described inside a fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.lsh_probe import (lsh_probe_gathered_pallas,
                                     lsh_probe_pallas, lsh_probe_tile)
from repro.kernels.minhash import minhash_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _assert_tpu_kernel(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_compile_lsh_probe_full_lake(one_chip):
    """The full-lake probe: 16 queries × 10^5 columns × 64 bands."""
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.uint32,
                                           sharding=one_chip)
    _assert_tpu_kernel(lsh_probe_pallas.lower(
        s((16, 64)), s((100_000, 64)), interpret=False))


@pytest.mark.parametrize("q,b,c", [
    (16, 16, 100_000),      # tiered coarse pass on the 16-lane digest
    (1, 64, 25_000),        # one-row query shard of a 4-way grid
    (4, 64, 25_000),        # four-row query shard
])
def test_compile_lsh_probe_tile(one_chip, q, b, c):
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.uint32,
                                           sharding=one_chip)
    fn = jax.jit(functools.partial(lsh_probe_tile, interpret=False))
    _assert_tpu_kernel(fn.lower(s((q, b)), s((c, b))))


def test_compile_lsh_probe_gathered(one_chip):
    """Tiered fine pass: per-query gathered survivors (16 × 2048 × 64)."""
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.uint32,
                                           sharding=one_chip)
    _assert_tpu_kernel(lsh_probe_gathered_pallas.lower(
        s((16, 64)), s((16, 2048, 64)), interpret=False))


def test_compile_minhash(one_chip):
    """Ingest signatures: 1024 columns × 2048 values, 128 permutations."""
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.uint32,
                                           sharding=one_chip)
    _assert_tpu_kernel(minhash_pallas.lower(
        s((1024, 2048)), s((128,)), s((128,)), interpret=False))


def test_stage_scopes_change_no_compiled_op(one_chip):
    """The TPU compiler's program for one bucket (8 queries) of the pruned
    pipeline is the same, op for op, with and without the stage scopes:
    they reach the ops' metadata only."""
    from _fixtures import pruned_hlo

    struct = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    kw = dict(q=8, c=8192, budget=1024, interpret=False)
    scoped = pruned_hlo(struct, scopes=True, **kw)
    assert scoped == pruned_hlo(struct, scopes=False, **kw)
    assert "tpu_custom_call" in scoped
