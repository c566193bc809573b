"""Shared deterministic test fixtures: fake clock + seeded randomness.

The flakiest tests in this suite were the ones that raced wall time —
``time.sleep(0.05)`` hoping a 5 ms deadline lapsed, negative lease TTLs
standing in for expiry.  Both runtimes take injectable clocks
(``SchedulerConfig.clock``, ``WriterLease(clock=)``,
``FleetConfig.clock``), so tests advance a :class:`FakeClock` instead of
sleeping: deterministic on any host, zero wall-clock wait.

Imported by ``conftest.py`` so ``fake_clock`` / ``seeded_rng`` are plain
fixture arguments everywhere; ``FakeClock`` itself is importable for
tests that need several independently-ticking clocks.
"""
import threading
import zlib

import numpy as np
import pytest


class FakeClock:
    """A callable, manually-advanced clock.

    Drop-in for ``time.perf_counter`` / ``time.time`` style sources:
    calling it returns the current fake seconds; :meth:`advance` moves
    it forward (thread-safe — worker threads read while the test
    advances).  It never moves on its own, so pair it with components
    configured not to *wait on it* (``max_wait_ms=0`` for the
    scheduler's coalescing window, which derives timeouts from the
    injected clock).
    """

    def __init__(self, start: float = 1_000.0):
        self._t = float(start)
        self._lock = threading.Lock()

    def __call__(self) -> float:
        with self._lock:
            return self._t

    def advance(self, seconds: float) -> float:
        """Move time forward; returns the new reading."""
        if seconds < 0:
            raise ValueError("a monotonic clock cannot go backwards")
        with self._lock:
            self._t += float(seconds)
            return self._t


@pytest.fixture()
def fake_clock() -> FakeClock:
    return FakeClock()


@pytest.fixture()
def seeded_rng(request) -> np.random.Generator:
    """Per-test deterministic generator: seeded from the test's nodeid,
    so every test gets a distinct but reproducible stream (no cross-test
    coupling through a shared session rng)."""
    seed = zlib.crc32(request.node.nodeid.encode()) & 0xFFFFFFFF
    return np.random.default_rng(seed)


def _gbdt_structs(struct, trees: int = 50, depth: int = 5):
    import jax.numpy as jnp

    return (struct((trees, depth), jnp.int32),
            struct((trees, depth), jnp.float32),
            struct((trees, 2 ** depth), jnp.float32),
            struct((), jnp.float32))


def optimized_hlo(raw_fn, args, statics: dict, *, scopes: bool,
                  strip: bool = True) -> str:
    """Optimized HLO text of a fresh ``jit`` of ``raw_fn`` (a pipeline's
    undecorated function) at ``args``; ``scopes=False`` traces with every
    ``jax.named_scope`` a no-op. ``strip`` removes the metadata
    (``op_name`` scopes, source lines) and the stack-frame tables."""
    import contextlib

    import jax

    # a fresh jit of the raw pipeline, so neither trace is served from the
    # other's cache
    fn = jax.jit(lambda *a, **kw: raw_fn(*a, **kw),
                 static_argnames=tuple(statics))
    named_scope = jax.named_scope
    if not scopes:
        jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        text = fn.lower(*args, **statics).compile().as_text()
    finally:
        jax.named_scope = named_scope
    return strip_metadata(text) if strip else text


def strip_metadata(text: str) -> str:
    """HLO text without its metadata and stack-frame tables."""
    import re

    # the stack-frame tables (FileNames ... StackFrames: header lines and
    # numbered rows) name source lines; the ops follow them
    text = re.sub(r"(?m)^(FileNames|FunctionNames|FileLocations|StackFrames"
                  r"|\d+ .*)\n", "", text)
    return re.sub(r",? metadata=\{[^}]*\}", "", text)


def pruned_hlo(struct, *, q: int, c: int, budget: int, interpret: bool,
               scopes: bool) -> str:
    """Optimized HLO text of the ``_local_pruned`` pipeline (hybrid, k=10)
    at batch ``q`` over ``c`` columns, with its metadata (``op_name``
    scopes, source lines) and stack-frame tables stripped. ``struct(shape,
    dtype)`` makes each argument's ``ShapeDtypeStruct``; ``scopes=False``
    traces with every ``jax.named_scope`` a no-op."""
    import jax.numpy as jnp

    from repro.exec import executor

    f_num, f_words, bands = 21, 11, 64
    args = (struct((q, f_num), jnp.float32), struct((q, f_words), jnp.uint32),
            struct((q, bands), jnp.uint32), struct((q,), jnp.int32),
            struct((q,), jnp.int32), struct((c, f_num), jnp.float32),
            struct((f_num,), jnp.float32), struct((c, f_words), jnp.uint32),
            struct((c, bands), jnp.uint32), struct((c,), jnp.int32),
            struct((c,), jnp.int32), _gbdt_structs(struct))
    statics = dict(kind="hybrid", k=10, budget=budget, interpret=interpret)
    return optimized_hlo(executor._local_pruned.__wrapped__, args, statics,
                         scopes=scopes)


def tiered_hlo(struct, *, q: int, c: int, survivors: int, interpret: bool,
               scopes: bool, strip: bool = True) -> str:
    """Optimized HLO text of the ``_local_tiered`` pipeline over an int8
    sidecar (k = 40, the re-rank's over-fetch; budget = survivor budget =
    ``survivors``, 16-lane digest, 32-column blocks) at batch ``q`` over
    ``c`` columns; arguments as :func:`pruned_hlo`'s."""
    import jax.numpy as jnp

    from repro.exec import executor

    f_num, f_words, bands, lanes = 21, 11, 64, 16
    args = (struct((q, f_num), jnp.float32), struct((q, f_words), jnp.uint32),
            struct((q, bands), jnp.uint32), struct((q, lanes), jnp.uint32),
            struct((q,), jnp.int32), struct((q,), jnp.int32),
            struct((c, f_num), jnp.int8), struct((f_num,), jnp.float32),
            struct((c, f_words), jnp.uint32), struct((c, bands), jnp.uint32),
            struct((c, lanes), jnp.uint32), struct((c,), jnp.int32),
            struct((c,), jnp.int32), _gbdt_structs(struct))
    statics = dict(k=40, budget=survivors, survivor_budget=survivors,
                   block_c=32, interpret=interpret)
    return optimized_hlo(executor._local_tiered.__wrapped__, args, statics,
                         scopes=scopes, strip=strip)


def rescore_hlo(struct, *, q: int, r: int = 40, scopes: bool,
                strip: bool = True) -> str:
    """Optimized HLO text of ``_rescore_exact`` (k = 10) over a (q, r)
    over-fetched candidate set; arguments as :func:`pruned_hlo`'s."""
    import jax.numpy as jnp

    from repro.exec import executor

    f_num, f_words = 21, 11
    args = (struct((q, f_num), jnp.float32), struct((q, f_words), jnp.uint32),
            struct((q, r, f_num), jnp.float32),
            struct((q, r, f_words), jnp.uint32), _gbdt_structs(struct),
            struct((q, r), jnp.float32), struct((q, r), jnp.int32))
    return optimized_hlo(executor._rescore_exact.__wrapped__, args,
                         dict(k=10), scopes=scopes, strip=strip)
