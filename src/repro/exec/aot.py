"""Persistent AOT executable cache for zero-compile serving restarts.

``Executor.aot_compile`` lowers every admissible (bucket × grid × plan
kind) pipeline ahead of traffic via ``jit(...).lower(...).compile()``.
That kills the in-process cold start, but a *restarted* engine would
still re-trace and re-compile the whole bucket ladder.  This module
persists the compiled executables across processes:

* entries are keyed by a blake2b digest over a **signature dict** —
  jax version, backend, device kind and count, the XLA flags, a digest
  of the package's sources (the pipelines are traced from them), the mesh
  geometry (axis names/sizes + shard/query axes for sharded pipelines),
  the pipeline name with its static arguments (plan kind, k, budget,
  survivor geometry, kernel tile/block config), and the shapes+dtypes of
  every dynamic argument.  Any environment or plan drift lands on a
  different digest, so stale entries are simply never found;
* the payload is ``jax.experimental.serialize_executable.serialize``'s
  ``(payload, in_tree, out_tree)`` triple, pickled together with the full
  signature dict.  ``load`` re-checks the stored signature against the
  requested one (digest collisions, hand-edited files) and treats *any*
  failure — unreadable file, unpickling error, deserialization error —
  as a miss, so a corrupt entry always falls back to a fresh compile;
* writes go through a per-process temp file + ``os.replace`` (the
  ``CatalogStore`` publish idiom), so engines sharing one cache
  directory never observe torn entries; last writer of an identical
  signature wins, which is harmless because the payloads are equivalent.

Deserialization is one in_tree/out_tree reconstruction plus an XLA
executable load — measured 10-30× cheaper than the trace+compile it
replaces on this container's CPU backend — which is what makes a warm
restart land in milliseconds.
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import pickle
import tempfile
from pathlib import Path

import jax

# Bump when the entry format changes: schema 3 = keys carry the digest
# of the program's sources.
_SCHEMA = 3


@functools.lru_cache(maxsize=None)
def source_digest() -> str:
    """blake2b digest of every ``.py`` file under ``src/repro``: statics
    and avals do not move when a source a pipeline is traced from is
    edited, so the key carries this digest and an edited pipeline never
    meets a stale entry. Digesting the whole package, not a list of the
    pipelines' imports, means no import can be missed."""
    root = Path(__file__).resolve().parents[1]
    h = hashlib.blake2b(digest_size=16)
    for path in sorted(root.rglob("*.py")):
        h.update(path.relative_to(root).as_posix().encode())
        h.update(path.read_bytes())
    return h.hexdigest()


def environment_signature() -> dict:
    """The process-environment half of every cache key: anything that can
    change the compiled artifact between runs without the plan moving."""
    devs = jax.devices()
    return {
        "schema": _SCHEMA,
        "sources": source_digest(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": devs[0].device_kind if devs else "none",
        "n_devices": len(devs),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }


def tree_aval_descriptors(tree) -> list:
    """(shape, dtype) per leaf of a pytree of arrays/ShapeDtypeStructs —
    the dynamic-argument half of a cache key."""
    return [[list(int(s) for s in leaf.shape), str(leaf.dtype)]
            for leaf in jax.tree_util.tree_leaves(tree)]


class ExecutableCache:
    """On-disk store of serialized XLA executables, shared across engine
    processes.  All failures degrade to a miss; ``store`` is best-effort
    (a read-only or full disk never breaks serving)."""

    def __init__(self, root: str | os.PathLike, *, env: dict | None = None):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        # injectable for tests simulating a jax-version / device mismatch
        self.env = dict(env) if env is not None else environment_signature()
        self.stats = {"hits": 0, "misses": 0, "stores": 0, "errors": 0}

    # -- keys ---------------------------------------------------------------

    def signature(self, name: str, statics, avals, mesh_desc=None) -> dict:
        """Full signature dict for one executable unit.  ``statics`` is the
        pipeline's static-argument mapping, ``avals`` the descriptor list
        from :func:`tree_aval_descriptors`, ``mesh_desc`` the mesh geometry
        for sharded units (None for local pipelines)."""
        return {
            **self.env,
            "name": str(name),
            "statics": repr(tuple(sorted(dict(statics).items()))),
            "avals": list(avals),
            "mesh": repr(mesh_desc),
        }

    def _path(self, sig: dict) -> Path:
        blob = json.dumps(sig, sort_keys=True).encode()
        digest = hashlib.blake2b(blob, digest_size=16).hexdigest()
        return self.root / f"{digest}.exe"

    # -- load / store -------------------------------------------------------

    def load(self, sig: dict):
        """Deserialized executable for ``sig``, or None on miss/corruption
        (the caller then compiles fresh and usually ``store``s)."""
        from jax.experimental import serialize_executable as se

        path = self._path(sig)
        try:
            with open(path, "rb") as f:
                entry = pickle.load(f)
            if entry.get("sig") != sig:     # digest collision / stale file
                raise ValueError("signature mismatch")
            exe = se.deserialize_and_load(entry["payload"],
                                          entry["in_tree"],
                                          entry["out_tree"])
        except FileNotFoundError:
            self.stats["misses"] += 1
            return None
        except Exception:
            self.stats["errors"] += 1
            self.stats["misses"] += 1
            return None
        self.stats["hits"] += 1
        return exe

    def store(self, sig: dict, compiled) -> bool:
        """Persist a compiled executable under ``sig``, atomically; best
        effort (False on any failure — serving proceeds uncached)."""
        from jax.experimental import serialize_executable as se

        path = self._path(sig)
        try:
            payload, in_tree, out_tree = se.serialize(compiled)
            blob = pickle.dumps({"sig": sig, "payload": payload,
                                 "in_tree": in_tree, "out_tree": out_tree})
            fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)       # atomic: readers see old or new
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        except Exception:
            self.stats["errors"] += 1
            return False
        self.stats["stores"] += 1
        return True
