"""Compile-only checks of the main-path Pallas kernels and the scorer for a
TPU v5e.

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
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import features as FT
from repro.exec import stages
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


@pytest.mark.parametrize("program", ["tiered", "rescore"])
def test_coarse_and_rescore_scopes_change_no_compiled_op(one_chip, program):
    """The TPU compiler's programs for one bucket (8 queries) of the tiered
    pipeline over an int8 sidecar and of the exact re-rank are the same,
    op for op, with and without the ``coarse`` / ``rescore`` scopes, and
    the scoped ones carry the labels in their metadata."""
    from _fixtures import rescore_hlo, strip_metadata, tiered_hlo

    struct = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    hlo = (functools.partial(tiered_hlo, struct, q=8, c=8192, survivors=512,
                             interpret=False) if program == "tiered"
           else functools.partial(rescore_hlo, struct, q=8))
    raw = hlo(scopes=True, strip=False)
    label = ("/candidates/coarse/" if program == "tiered"
             else "/rescore/")
    assert label in raw
    scoped = strip_metadata(raw)
    assert scoped == hlo(scopes=False)
    if program == "tiered":
        assert "tpu_custom_call" in scoped


def _gather_operand_shapes(hlo: str) -> list[str]:
    """Shape of the first operand of every gather in an optimized HLO
    text, fused computations included (instruction names are unique in a
    module, so each operand is found by its name)."""
    shape_of = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.-]+) = (\w+\[[\d,]*\])",
                               hlo, re.M))
    return [shape_of[op] for op in
            re.findall(r"= \w+\[[\d,]*\]\S* gather\((%[\w.-]+),", hlo)]


def test_compile_scorer_reads_no_leaf_table_by_gather(one_chip):
    """The scorer at the closed64 bucket (64 queries × 4096 gathered
    candidates, 50 trees of depth 5): the only gather left is the feature
    pick from the (64, 4096, 23) distances; none reads a leaf row."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q, m, t, d = 64, 4096, 50, 5
    gbdt = (s((t, d), jnp.int32), s((t, d), jnp.float32),
            s((t, 2 ** d), jnp.float32), s((), jnp.float32))
    hlo = jax.jit(stages.score_columns).lower(
        s((q, FT.F_NUM), jnp.float32), s((q, FT.F_WORDS), jnp.uint32),
        s((q, m, FT.F_NUM), jnp.float32), s((q, m, FT.F_WORDS), jnp.uint32),
        gbdt).compile().as_text()
    operands = _gather_operand_shapes(hlo)
    assert operands, "the feature pick's gather should be found"
    # a leaf row, or the whole leaf table, has 2^D values on its last axis
    assert not [o for o in operands if re.search(rf"[\[,]{2 ** d}\]$", o)], \
        operands


def _sorts(hlo: str) -> list[tuple[list[int], str]]:
    """(first operand's dims, ``op_name``) of every sort in an optimized
    HLO text that keeps its metadata."""
    out = []
    for line in hlo.splitlines():
        if " sort(" not in line:
            continue
        dims = re.search(r" = \(?\w+\[([\d,]*)\]", line).group(1)
        name = re.search(r'op_name="([^"]*)"', line)
        out.append(([int(d) for d in dims.split(",")],
                    name.group(1) if name else ""))
    return out


def test_compile_tiered_survivor_pick_sorts_no_wide_row(one_chip):
    """``_local_tiered`` at bucket 32 over 745,000 columns, survivor budget
    2,048: the survivor pick runs the threshold selection, so no sort in
    the program is wider than 2,048 (``lax.top_k`` there was split into
    sorts of ``[Q·128,5821]`` and ``[Q,262144]``); the fine pass's
    (32, 2,048) ``top_k`` is kept."""
    from _fixtures import tiered_hlo

    assert stages.selection(745_000, 2048) == "threshold"
    struct = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    sorts = _sorts(tiered_hlo(struct, q=32, c=745_000, survivors=2048,
                              interpret=False, scopes=True, strip=False))
    assert sorts and all(d[-1] <= 2048 for d, _ in sorts), sorts
    fine = [d for d, name in sorts
            if name.endswith("/candidates/top_k") and "/coarse/" not in name]
    assert fine == [[32, 2048]], sorts
    assert any("/candidates/coarse/" in name for _, name in sorts), sorts


def test_compile_pruned_candidate_pick_sorts_no_wide_row(one_chip):
    """``_local_pruned`` at the ``closed64`` bucket (64 queries over 65,000
    columns, budget 4,096): the candidate pick runs the threshold
    selection, so no sort is wider than the budget (``lax.top_k`` sorted
    the whole (64, 65,000) row)."""
    from _fixtures import pruned_hlo

    assert stages.selection(65_000, 4096) == "threshold"
    struct = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    sorts = _sorts(pruned_hlo(struct, q=64, c=65_000, budget=4096,
                              interpret=False, scopes=True))
    assert sorts and all(d[-1] <= 4096 for d, _ in sorts), sorts
