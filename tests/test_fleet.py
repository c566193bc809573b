"""Engine-replica fleet: lifecycle, load-aware routing, fault injection.

Two layers of hardening for the concurrent subsystem:

* **fault injection** — :class:`FaultInjector` kills or hangs replica
  workers at named points (mid-batch, mid-warmup, mid-drain); the
  invariants under every fault are that each accepted future resolves
  (a re-dispatched result or a clean ``SchedulerOverloadError``), no
  batch is silently dropped, and an evicted replica's pinned snapshots
  are released (refcounts return to zero, executor closed);
* **routing invariants** — hypothesis property tests over arbitrary
  synthetic replica states: the router never places on a non-SERVING
  replica, placement is deterministic, and queue-depth spread stays
  bounded (no ready replica starves).
"""
import threading
import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.service import (ColumnCatalog, DiscoveryEngine, DiscoveryRequest,
                           EngineConfig, EngineFleet, EventBus, FaultInjector,
                           FleetConfig, FleetRouter, ReplicaSnapshot,
                           RequestScheduler, SchedulerConfig,
                           SchedulerOverloadError)
from repro.service.fleet import DRAINING, EVICTED, SERVING, WARMING, _FleetBatch
from repro.service.scheduler import _Item
from concurrent.futures import Future


def _tiny_model():
    from repro.core.gbdt import GBDTParams
    from repro.core.predictor import JoinQualityModel
    p = GBDTParams(feats=np.zeros((1, 1), np.int32),
                   thrs=np.zeros((1, 1), np.float32),
                   leaves=np.zeros((1, 2), np.float32), base=0.0)
    return JoinQualityModel(gbdt=p)


@pytest.fixture(scope="module")
def snapshot(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("fleet_catalog"))
    cat = ColumnCatalog(root, n_perm=64)
    for t in range(4):
        cat.add_table(f"t{t}",
                      [(f"c{t}a", [f"v{t}_{i}" for i in range(60)]),
                       (f"c{t}b", [f"w{i % 11}" for i in range(40)])])
    return cat.snapshot()


MODEL = _tiny_model()


def _make_fleet(snapshot, n=2, injector=None, bus=None, **cfg):
    engines = [DiscoveryEngine(snapshot, MODEL,
                               EngineConfig(k=3, mode="full",
                                            cache_entries=0), events=bus)
               for _ in range(n)]
    cfg.setdefault("health_interval_s", 0.05)
    return EngineFleet(engines, FleetConfig(**cfg), events=bus,
                       injector=injector)


def _reqs(prefix, n):
    return [DiscoveryRequest(name=f"{prefix}{i}", column_id=i % 8)
            for i in range(n)]


def _wait_until(pred, timeout=20.0):
    t0 = time.monotonic()
    while not pred():
        if time.monotonic() - t0 > timeout:
            raise AssertionError("condition not reached in time")
        time.sleep(0.01)


def _assert_pins_released(replica):
    """The eviction contract: engine closed, head refcount zero, its
    executor closed, and no other live version remains."""
    eng = replica.engine
    assert eng.closed
    _wait_until(lambda: eng._head.refs == 0)
    assert eng._head.executor.closed
    assert not eng._live


class _Gate:
    """Stall one replica engine's batch path under test control."""

    def __init__(self, engine):
        self.release = threading.Event()
        self.entered = threading.Event()
        real = engine.query_batch

        def wrapped(reqs, **kw):
            self.entered.set()
            assert self.release.wait(30)
            return real(reqs, **kw)

        engine.query_batch = wrapped


# ---------------------------------------------------------------------------
# lifecycle + serving
# ---------------------------------------------------------------------------

def test_fleet_serves_through_scheduler_with_parity(snapshot):
    direct = DiscoveryEngine(snapshot, MODEL,
                             EngineConfig(k=3, mode="full", cache_entries=0))
    baseline = {r.name: r for r in direct.query_batch(_reqs("q", 12))}
    fleet = _make_fleet(snapshot, n=2)
    try:
        with RequestScheduler(fleet, SchedulerConfig(max_wait_ms=1.0)) as sch:
            futs = [sch.submit(r) for r in _reqs("q", 12)]
            outs = [f.result(timeout=60) for f in futs]
        for r in outs:
            want = baseline[r.name]
            assert [m.column_id for m in r.matches] == \
                [m.column_id for m in want.matches]
            assert r.queue_ms >= 0.0 and r.compute_ms > 0.0
            assert r.latency_ms == pytest.approx(r.queue_ms + r.compute_ms)
        st_ = fleet.stats()
        assert st_["completed"] == 12
        assert st_["scheduler"]["completed"] == 12
        assert st_["scheduler"]["failed"] == 0
        assert all(v["state"] == SERVING for v in st_["replicas"].values())
    finally:
        fleet.close()
    # close() retires every replica and releases every pinned snapshot
    for r in fleet.replicas:
        assert r.state == EVICTED
        _assert_pins_released(r)


def test_fleet_query_batch_direct_no_scheduler(snapshot):
    fleet = _make_fleet(snapshot, n=2)
    try:
        outs = fleet.query_batch(_reqs("d", 5), timeout=60)
        assert [r.name for r in outs] == [f"d{i}" for i in range(5)]
    finally:
        fleet.close()


def test_replica_state_events_on_shared_bus(snapshot):
    bus = EventBus(capacity=512)
    cur = bus.subscribe("test")
    fleet = _make_fleet(snapshot, n=2, bus=bus)
    try:
        _wait_until(lambda: all(r.state == SERVING for r in fleet.replicas))
        fleet.query_batch(_reqs("e", 3), timeout=60)
    finally:
        fleet.close()
    evs = cur.poll()
    flips = [e.payload for e in evs if e.type == "replica_state"]
    assert sum(1 for p in flips if p["state"] == SERVING) == 2
    assert sum(1 for p in flips if p["state"] == EVICTED) == 2
    routed = [e for e in evs if e.type == "batch_routed"]
    assert routed and all("replica" in e.payload for e in routed)


def test_drain_lifecycle_releases_engine_and_traffic_moves(snapshot):
    fleet = _make_fleet(snapshot, n=2)
    try:
        fleet.query_batch(_reqs("w", 2), timeout=60)
        fleet.drain_replica(0)
        _wait_until(lambda: fleet.replicas[0].state == EVICTED)
        _assert_pins_released(fleet.replicas[0])
        served_before = fleet.replicas[1].batches_served
        outs = fleet.query_batch(_reqs("x", 3), timeout=60)
        assert len(outs) == 3
        assert fleet.replicas[1].batches_served == served_before + 1
        assert fleet.replicas[0].batches_served <= 1  # nothing post-drain
    finally:
        fleet.close()


def test_install_buckets_propagates_to_every_replica(snapshot):
    fleet = _make_fleet(snapshot, n=3)
    try:
        with RequestScheduler(fleet,
                              SchedulerConfig(max_wait_ms=0.0,
                                              batch_buckets=(4, 8))):
            for r in fleet.replicas:
                assert r.engine.config.batch_buckets == (4, 8)
                assert r.engine.planner.config.batch_buckets == (4, 8)
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# fault injection
# ---------------------------------------------------------------------------

def test_kill_mid_batch_redispatches_everything(snapshot):
    inj = FaultInjector()
    inj.arm("mid_batch", mode="kill")
    fleet = _make_fleet(snapshot, n=2, injector=inj)
    try:
        with RequestScheduler(fleet, SchedulerConfig(max_wait_ms=1.0)) as sch:
            futs = [sch.submit(r) for r in _reqs("k", 10)]
            outs = [f.result(timeout=60) for f in futs]   # ALL resolve
        assert [r.name for r in outs] == [f"k{i}" for i in range(10)]
        st_ = fleet.stats()
        assert st_["evictions"] == 1 and st_["redispatches"] >= 1
        # no batch silently dropped: every submission is accounted for
        assert st_["scheduler"]["completed"] == 10
        assert st_["scheduler"]["failed"] == 0
        killed = [r for r in fleet.replicas if r.state == EVICTED]
        assert len(killed) == 1
        _assert_pins_released(killed[0])
        assert inj.fired and inj.fired[0][2] == "kill"
    finally:
        fleet.close()


def test_kill_mid_warmup_survivor_serves(snapshot):
    inj = FaultInjector()
    inj.arm("mid_warmup", replica=0, mode="kill")
    fleet = _make_fleet(snapshot, n=2, injector=inj)
    try:
        _wait_until(lambda: fleet.replicas[0].state == EVICTED)
        _assert_pins_released(fleet.replicas[0])
        outs = fleet.query_batch(_reqs("s", 4), timeout=60)
        assert len(outs) == 4
        assert fleet.replicas[1].state == SERVING
        assert fleet.warm_event.is_set()
    finally:
        fleet.close()


def test_hang_mid_batch_health_evicts_and_redispatches(snapshot):
    inj = FaultInjector()
    inj.arm("mid_batch", mode="hang")
    fleet = _make_fleet(snapshot, n=2, injector=inj,
                        health_interval_s=0.05, hang_timeout_s=0.25)
    try:
        with RequestScheduler(fleet, SchedulerConfig(max_wait_ms=1.0)) as sch:
            futs = [sch.submit(r) for r in _reqs("h", 8)]
            outs = [f.result(timeout=60) for f in futs]   # ALL resolve
        assert len(outs) == 8
        st_ = fleet.stats()
        assert st_["evictions"] == 1 and st_["redispatches"] >= 1
        hung = [r for r in fleet.replicas if r.state == EVICTED][0]
        assert hung.engine.closed
    finally:
        inj.release_hangs()               # let the hung worker exit
        fleet.close()
    # the un-hung worker finds its engine closed and exits without
    # corrupting anything; its pin count still returns to zero
    _assert_pins_released([r for r in fleet.replicas
                           if r.batches_served == 0][0])


def test_hang_mid_warmup_evicted_by_health_check(snapshot):
    inj = FaultInjector()
    inj.arm("mid_warmup", replica=0, mode="hang")
    fleet = _make_fleet(snapshot, n=2, injector=inj,
                        health_interval_s=0.05, hang_timeout_s=10.0,
                        warmup_timeout_s=0.25)
    try:
        _wait_until(lambda: fleet.replicas[0].state == EVICTED)
        outs = fleet.query_batch(_reqs("wh", 3), timeout=60)
        assert len(outs) == 3
    finally:
        inj.release_hangs()
        fleet.close()


def test_kill_mid_drain_redispatches_queued_batch(snapshot):
    inj = FaultInjector()
    inj.arm("mid_drain", replica=0, mode="kill")
    fleet = _make_fleet(snapshot, n=2, injector=inj)
    try:
        _wait_until(lambda: all(r.state == SERVING for r in fleet.replicas))
        gate = _Gate(fleet.replicas[0].engine)

        def item(name):
            return _Item(request=DiscoveryRequest(name=name, column_id=0),
                         future=Future(), t_submit=time.perf_counter(),
                         deadline=None, trace_id=name)

        # stage directly on replica 0 (bypassing the router) so a batch
        # is QUEUED behind the gated in-flight one when the drain begins
        b1, b2 = _FleetBatch([item("b1")]), _FleetBatch([item("b2")])
        assert fleet.replicas[0].enqueue(b1)
        assert gate.entered.wait(30)
        assert fleet.replicas[0].enqueue(b2)
        fleet.drain_replica(0)
        gate.release.set()
        # b1 finishes on replica 0; b2 hits mid_drain -> kill -> the
        # fleet re-dispatches it to the surviving replica
        assert b1.items[0].future.result(timeout=60).name == "b1"
        assert b2.items[0].future.result(timeout=60).name == "b2"
        _wait_until(lambda: fleet.replicas[0].state == EVICTED)
        _assert_pins_released(fleet.replicas[0])
        assert fleet.stats()["redispatches"] == 1
        assert fleet.replicas[1].requests_served >= 1
    finally:
        fleet.close()


def test_every_replica_killed_fails_futures_cleanly(snapshot):
    """With every replica repeatedly killed, accepted futures must still
    ALL resolve — with a clean SchedulerOverloadError, never a hang."""
    inj = FaultInjector()
    inj.arm("mid_batch", mode="kill", times=99)
    fleet = _make_fleet(snapshot, n=2, injector=inj, max_redispatch=2)
    try:
        with RequestScheduler(fleet, SchedulerConfig(max_wait_ms=1.0)) as sch:
            futs = [sch.submit(r) for r in _reqs("x", 6)]
            outcomes = []
            for f in futs:
                try:
                    outcomes.append(("ok", f.result(timeout=60)))
                except SchedulerOverloadError:
                    outcomes.append(("overload", None))
        assert len(outcomes) == 6                      # nothing hung
        assert all(kind == "overload" for kind, _ in outcomes)
        st_ = fleet.stats()
        assert st_["evictions"] == 2
        assert st_["scheduler"]["failed"] == 6         # nothing dropped
        for r in fleet.replicas:
            _assert_pins_released(r)
        # late submissions fail fast instead of queueing forever
        with pytest.raises((SchedulerOverloadError, RuntimeError)):
            fleet.query_batch(_reqs("late", 1), timeout=10)
    finally:
        fleet.close()


def test_fault_injector_validates_points_and_modes():
    inj = FaultInjector()
    with pytest.raises(ValueError, match="point"):
        inj.arm("mid_nothing")
    with pytest.raises(ValueError, match="mode"):
        inj.arm("mid_batch", mode="explode")
    inj.arm("mid_batch", replica=3, times=2)
    inj.check("mid_batch", 1)             # wrong replica: no fire
    assert not inj.fired


# ---------------------------------------------------------------------------
# routing invariants (property tests)
# ---------------------------------------------------------------------------

def _random_snapshots(rnd, n_replicas):
    states = (WARMING, SERVING, DRAINING, EVICTED)
    return [ReplicaSnapshot(replica_id=i,
                            state=rnd.choice(states),
                            queue_depth=rnd.randrange(0, 500),
                            cost_per_item=rnd.uniform(1e-4, 10.0))
            for i in range(n_replicas)]


@settings(max_examples=60)
@given(st.randoms(), st.integers(1, 8), st.integers(0, 64))
def test_router_never_places_on_non_serving(rnd, n_replicas, spread):
    snaps = _random_snapshots(rnd, n_replicas)
    rid = FleetRouter(max_depth_spread=spread).choose(
        snaps, n_items=rnd.randrange(1, 65))
    if rid is None:
        assert all(s.state != SERVING for s in snaps)
    else:
        assert snaps[rid].state == SERVING


@settings(max_examples=60)
@given(st.randoms(), st.integers(1, 8), st.integers(0, 64))
def test_router_is_deterministic(rnd, n_replicas, spread):
    snaps = _random_snapshots(rnd, n_replicas)
    n = rnd.randrange(1, 65)
    router = FleetRouter(max_depth_spread=spread)
    first = router.choose(snaps, n_items=n)
    assert all(router.choose(list(snaps), n_items=n) == first
               for _ in range(5))


@settings(max_examples=40)
@given(st.randoms(), st.integers(2, 6), st.integers(0, 32))
def test_router_bounds_queue_depth_spread(rnd, n_replicas, spread):
    """Over any placement sequence (no consumption — worst case), the
    depth gap between the most- and least-loaded SERVING replicas never
    exceeds ``max_depth_spread + n_max`` — the no-starvation bound."""
    router = FleetRouter(max_depth_spread=spread)
    costs = [rnd.uniform(1e-3, 5.0) for _ in range(n_replicas)]
    depths = [0] * n_replicas
    n_max = 0
    for _ in range(100):
        n = rnd.randrange(1, 9)
        n_max = max(n_max, n)
        snaps = [ReplicaSnapshot(i, SERVING, depths[i], costs[i])
                 for i in range(n_replicas)]
        rid = router.choose(snaps, n_items=n)
        assert rid is not None
        # eligibility bound at choose time
        assert depths[rid] <= min(depths) + spread
        depths[rid] += n
        assert max(depths) - min(depths) <= spread + n_max


@settings(max_examples=40)
@given(st.randoms(), st.integers(2, 8))
def test_router_equal_cost_is_least_loaded_round_robin(rnd, n_replicas):
    """Equal costs + equal batch sizes: each of the first ``n_replicas``
    placements lands on a distinct replica (nobody starves while an
    idle peer exists)."""
    router = FleetRouter(max_depth_spread=64)
    cost = rnd.uniform(1e-3, 5.0)
    depths = [0] * n_replicas
    hit = []
    for _ in range(n_replicas):
        snaps = [ReplicaSnapshot(i, SERVING, depths[i], cost)
                 for i in range(n_replicas)]
        rid = router.choose(snaps, n_items=4)
        hit.append(rid)
        depths[rid] += 4
    assert sorted(hit) == list(range(n_replicas))


def test_router_empty_and_all_evicted():
    r = FleetRouter()
    assert r.choose([], n_items=1) is None
    assert r.choose([ReplicaSnapshot(0, EVICTED, 0, 1.0),
                     ReplicaSnapshot(1, DRAINING, 0, 1.0)]) is None


def test_router_prefers_cheap_replica_under_load():
    """A 2x-faster replica absorbs more work until depths rebalance."""
    r = FleetRouter(max_depth_spread=64)
    snaps = [ReplicaSnapshot(0, SERVING, 10, 1.0),
             ReplicaSnapshot(1, SERVING, 10, 0.25)]
    assert r.choose(snaps, n_items=8) == 1
    # but the spread cap still overrides raw cost
    snaps = [ReplicaSnapshot(0, SERVING, 0, 1.0),
             ReplicaSnapshot(1, SERVING, 100, 0.001)]
    assert r.choose(snaps, n_items=8) == 0


# ---------------------------------------------------------------------------
# replica placement: one device per replica
# ---------------------------------------------------------------------------

_PLACEMENT_SCRIPT = r"""
import jax, numpy as np
from repro.core import features as FT
from repro.exec import Executor, Planner, PlannerConfig
from repro.launch.mesh import make_replica_meshes

devs = jax.devices()
assert len(devs) == 4, devs
assert make_replica_meshes(8) == [None] * 8
meshes = make_replica_meshes(4)
assert [m.devices.size for m in meshes] == [1, 1, 1, 1]
rng = np.random.default_rng(0)
c = 300
z = rng.normal(size=(c, FT.F_NUM)).astype(np.float32)
w = rng.integers(0, 50, (c, FT.F_WORDS)).astype(np.uint32)
keys = rng.integers(0, 2**31, (c, 8)).astype(np.uint32)
gb = (rng.integers(0, FT.F_DIST, (4, 3)).astype(np.int32),
      rng.normal(size=(4, 3)).astype(np.float32),
      rng.normal(size=(4, 8)).astype(np.float32), 0.5)
q = np.arange(5)
plan = Planner(PlannerConfig(k=5)).plan(n_columns=c, n_queries=5, mode="lsh")
want = Executor(z, w, gb, band_keys=keys).execute(
    plan, z[q], w[q], np.full(5, -1, np.int32), q.astype(np.int32), keys[q])
for i, m in enumerate(meshes):
    ex = Executor(z, w, gb, band_keys=keys, mesh=m)
    assert ex._z.devices() == {devs[i]}, (i, ex._z.devices())
    ex.aot_compile([(plan, 5)])
    got = ex.execute(plan, z[q], w[q], np.full(5, -1, np.int32),
                     q.astype(np.int32), keys[q])
    assert ex.dispatch_stats() == {"aot": 1, "fallback": 0}
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
print("ok")
"""


def test_one_device_replica_slices_pin_their_device():
    """Four replicas on four devices each get a one-device mesh, and the
    executor places corpus and AOT executables on that device (with the
    same results as default placement); more replicas than devices still
    share the default device."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(root, "src")
    r = subprocess.run([sys.executable, "-c", _PLACEMENT_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0 and "ok" in r.stdout, r.stdout + r.stderr
