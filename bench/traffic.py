"""The one traffic generator: a mix file's parameters -> requests.

A traffic mix is a JSON file under ``traffic/`` (see that directory). Two
loop kinds exist:

* ``open``: independent users. Requests are due on a fixed schedule,
  whether or not earlier ones have finished. The mean rate is
  ``rate_of_knee`` times the configuration's ``knee_qps``; ``bursts``
  modulate it in phases of ``[seconds, rate multiplier]`` repeated for the
  whole window. Each phase holds a fixed number of requests (its mean rate
  times its length), due at uniform order statistics inside the phase:
  Poisson arrivals conditioned on their count. The arrival times are one
  fixed draw of the mix (``ARRIVALS``), the same for every seed, and the
  seed orders the queries over them: every seed offers the same work at
  the same moments, so the spread between runs is the system's own.
* ``closed``: ``in_flight`` callers that each send their next request the
  moment the previous one is answered.

Queries are column ids of the lake. ``pool`` names the columns drawn from
(``planted``: every column with planted partners) and ``pool_size`` can
take a seeded subset of it; ``draw`` is ``cycle`` (a seeded permutation,
repeated, so a column comes back only after the whole pool has) or
``zipf`` with exponent ``s`` over a seeded ranking of the pool.
"""
from __future__ import annotations

import dataclasses

import numpy as np

ARRIVALS = 0xA221       # the stream the open loop's arrival times come from


@dataclasses.dataclass
class Schedule:
    due: np.ndarray     # (N,) seconds after the window opens (open loop)
    qids: np.ndarray    # (N,) column id of each request, in order


def pool(traffic: dict, lake, rng: np.random.Generator) -> np.ndarray:
    q = traffic.get("queries", {})
    kind = q.get("pool", "planted")
    if kind != "planted":
        raise ValueError(f"unknown query pool {kind!r}")
    ids = lake.planted()
    size = q.get("pool_size")
    if size is not None:
        ids = np.sort(rng.choice(ids, size=min(int(size), ids.size),
                                 replace=False))
    return ids


def draw(traffic: dict, ids: np.ndarray, n: int,
         rng: np.random.Generator) -> np.ndarray:
    """``n`` query column ids drawn from ``ids`` as the mix says."""
    q = traffic.get("queries", {})
    how = q.get("draw", "cycle")
    if how == "cycle":
        reps = -(-n // ids.size)
        return np.concatenate([rng.permutation(ids)
                               for _ in range(reps)])[:n]
    if how == "zipf":
        ranked = rng.permutation(ids)
        p = 1.0 / np.arange(1, ids.size + 1, dtype=np.float64) ** float(
            q.get("s", 1.0))
        return ranked[rng.choice(ids.size, size=n, p=p / p.sum())]
    raise ValueError(f"unknown query draw {how!r}")


def phases(traffic: dict) -> list[tuple[float, float]]:
    b = traffic.get("bursts")
    if not b:
        return [(1.0, 1.0)]
    out = [(float(d), float(m)) for d, m in b["phases"]]
    mean = sum(d * m for d, m in out) / sum(d for d, _ in out)
    return [(d, m / mean) for d, m in out]     # the mean rate is the rate


def open_schedule(traffic: dict, rate: float, seconds: float,
                  ids: np.ndarray, rng: np.random.Generator,
                  stream: int = 0) -> Schedule:
    """Arrival times from the mix's fixed stream number ``stream``; the
    queries over them from the seed's ``rng``."""
    clock = np.random.default_rng([ARRIVALS, int(stream)])
    due = []
    t = 0.0
    ph = phases(traffic)
    while t < seconds - 1e-9:
        for d, mult in ph:
            d = min(d, seconds - t)
            if d <= 0:
                break
            n = int(round(rate * mult * d))
            due.append(t + np.sort(clock.uniform(0.0, d, size=n)))
            t += d
    due = np.concatenate(due) if due else np.zeros((0,))
    return Schedule(due=due, qids=draw(traffic, ids, due.size, rng))


def stream(traffic: dict, ids: np.ndarray, rng: np.random.Generator,
           chunk: int = 4096):
    """Endless query ids for a closed loop, drawn ``chunk`` at a time."""
    while True:
        yield from draw(traffic, ids, chunk, rng).tolist()
