"""Profiler spans of the serving path, on the profiler's own clock.

``span(name, **args)`` opens a ``jax.profiler.TraceAnnotation`` named
``freyja.<name>``. Such a span is recorded only while a profiler session
is active (``jax.profiler.start_trace`` / ``trace``): its host event then
lands on the same clock as the device's op events, and its keyword
arguments become the event's stats. With no session active a span costs
one inactive TraceMe enter and exit; there is no switch to turn them off.

The spans the serving path opens (names without the ``freyja.`` prefix):

* ``scheduler.wait`` / ``scheduler.coalesce`` (``queued``) /
  ``scheduler.batch`` (``batch``, ``n``, ``bucket``) /
  ``scheduler.deliver`` (``n``) — ``service/scheduler.py``;
* ``engine.{pin,resolve,plan,candidates,execute,finalize}`` — the phases
  of ``DiscoveryEngine.query_batch``, opened by the same
  :class:`PhaseClock` that times the per-request ``trace`` phases;
  ``execute`` carries the scheduler's ``batch``;
* ``executor.put`` / ``executor.dispatch`` (``program``, ``aot``) /
  ``executor.fetch`` — host-to-device copies of the query arrays, calls
  into compiled programs, blocking copies of results to the host;
* ``gc`` (``generation``) — each collection of the interpreter's garbage
  collector, once :func:`install_gc_spans` has run.
"""
from __future__ import annotations

import gc
import threading
import time

from jax.profiler import TraceAnnotation

PREFIX = "freyja."


def span(name: str, **args) -> TraceAnnotation:
    """Context manager of the profiler span ``freyja.<name>``."""
    return TraceAnnotation(PREFIX + name, **args)


class PhaseClock:
    """Contiguous named phases of one call, on ``time.perf_counter``.

    ``next(phase)`` ends the running phase and starts ``phase``; ``close``
    ends the last one. ``marks`` holds one ``(phase, end)`` pair per ended
    phase, so ``t0`` and the marks partition ``[t0, end of the last
    phase]`` exactly. Each phase is also the profiler span
    ``freyja.<scope>.<phase>``, opened and closed at its marks, so the
    spans and the marks cannot disagree on names or boundaries."""

    def __init__(self, scope: str, first: str, **args):
        self._scope = scope
        self.marks: list[tuple[str, float]] = []
        self.t0 = time.perf_counter()
        self._open(first, args)

    def _open(self, phase: str, args: dict) -> None:
        self._phase = phase
        self._span = span(f"{self._scope}.{phase}", **args)
        self._span.__enter__()

    def _end(self) -> float:
        t = time.perf_counter()
        self._span.__exit__(None, None, None)
        self.marks.append((self._phase, t))
        self._phase = self._span = None
        return t

    def next(self, phase: str, **args) -> None:
        """End the running phase now and start ``phase``."""
        if self._span is not None:
            self._end()
        self._open(phase, args)

    def close(self) -> float:
        """End the running phase (no-op once closed); returns the end of
        the last phase."""
        if self._span is not None:
            return self._end()
        return self.marks[-1][1] if self.marks else self.t0


_gc_lock = threading.Lock()
_gc_installed = False
_gc_local = threading.local()


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        s = TraceAnnotation(PREFIX + "gc", generation=info["generation"])
        s.__enter__()
        _gc_local.span = s
    else:
        s = getattr(_gc_local, "span", None)
        if s is not None:
            _gc_local.span = None
            s.__exit__(None, None, None)


def install_gc_spans() -> None:
    """Open a ``freyja.gc`` span around every garbage collection of this
    process (``gc.callbacks``). Idempotent: the serving path calls it
    whenever it builds an engine."""
    global _gc_installed
    with _gc_lock:
        if not _gc_installed:
            gc.callbacks.append(_on_gc)
            _gc_installed = True
