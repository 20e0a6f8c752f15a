"""Tier-1 chaos suite for the out-of-process worker pool
(`repro.runtime.workers`) and its service/queue integration: exact counts
across the process boundary, a REAL SIGKILL mid-bucket recovered with
bit-identical results and zero lost / zero double-counted requests, a
genuinely hung worker SIGKILLed by the wall-clock watchdog, the
vector→ref degradation ladder, and pool-backed queue draining.

These tests spawn real processes (multiprocessing "spawn" context — each
worker pays a jax import + Dataset build at startup), so they share one
module-scoped pool where possible and keep graphs/queries small."""
import time

import pytest

from repro.api import MatchOptions
from repro.core import random_walk_query, synthetic_labeled_graph
from repro.core.ref_engine import cemr_match
from repro.runtime.ft import FaultInjector
from repro.runtime.queue import MatchQueueRuntime, QueryItem
from repro.runtime.service import MatchService, ServiceConfig
from repro.runtime.workers import (BucketResult, ChipPlacementError,
                                   WorkerOutcome, WorkerPool, as_triples,
                                   worker_chip_env)

# real-process operations (spawn + jax import + first compile) get a
# generous wall budget; the assertions below are on *behavior*, not speed
WAIT_S = 120.0


@pytest.fixture(scope="module")
def data():
    return synthetic_labeled_graph(60, 5.0, 3, seed=0, power_law=False)


@pytest.fixture(scope="module")
def queries(data):
    return [random_walk_query(data, 4, seed=s) for s in range(8)]


@pytest.fixture(scope="module")
def expected(data, queries):
    return [cemr_match(q, data, limit=10**9).count for q in queries]


@pytest.fixture(scope="module")
def pool(data):
    with WorkerPool(data, 2, deadline_s=60.0) as p:
        yield p


def _items(queries):
    return [QueryItem(query_id=i, query=q, limit=10**9, max_steps=None)
            for i, q in enumerate(queries)]


def _await_ticket(pool, ticket):
    """Poll until `ticket`'s result (or death) surfaces."""
    deadline = time.monotonic() + WAIT_S
    while time.monotonic() < deadline:
        for res in pool.poll(0.05):
            if res.ticket == ticket:
                return res
    raise AssertionError(f"ticket {ticket} never surfaced")


def _await_full_size(pool):
    deadline = time.monotonic() + WAIT_S
    while pool.alive_count() < pool.size and time.monotonic() < deadline:
        pool.poll(0.05)
    return pool.alive_count()


# ------------------------------------------------------------------ adapters
def test_as_triples_shapes():
    items = ["req-a", "req-b"]
    res = BucketResult(ticket=0, items=items, engine=None,
                       counts=[(3, False), (None, True)], exec_s=0.5)
    triples = as_triples(res)
    # executed bucket: worker-measured exec time amortized per item, a
    # None count (the item raised in the worker) stays a death for it
    assert triples[0] == ("req-a", WorkerOutcome(3, False), 0.25)
    assert triples[1][1] is None
    dead = BucketResult(ticket=1, items=items, engine=None,
                        worker_died=True)
    assert [o for _, o, _ in as_triples(dead)] == [None, None]


# -------------------------------------------------------------- pool basics
@pytest.mark.parametrize("platform", ["cpu", "gpu"])
def test_chip_placement_off_tpu_pins_nothing(platform):
    """Off the TPU workers share the host: no environment, whatever the
    chip count or the parent's state."""
    assert worker_chip_env(platform, 0, 3, False) == [{}, {}, {}]
    assert worker_chip_env(platform, 1, 2, True) == [{}, {}]


@pytest.mark.parametrize("n_workers", [1, 2, 4])
def test_chip_placement_pins_worker_i_to_chip_i(n_workers):
    envs = worker_chip_env("tpu", 4, n_workers, False)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == \
        [str(i) for i in range(n_workers)]
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
               and e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    ports = {e["TPU_PROCESS_PORT"] for e in envs}
    assert len(ports) == n_workers          # no two workers share a port


@pytest.mark.parametrize("n_chips,n_workers,holds,match", [
    (4, 5, False, "5 workers but 4 TPU chips"),
    (0, 1, False, "1 workers but 0 TPU chips"),
    (4, 1, True, "already initialised the TPU backend"),
    (4, 5, True, "already initialised the TPU backend"),
])
def test_chip_placement_refuses(n_chips, n_workers, holds, match):
    with pytest.raises(ChipPlacementError, match=match):
        worker_chip_env("tpu", n_chips, n_workers, holds)


def test_pool_counts_bit_identical_to_oracle(pool, queries, expected):
    res = pool.run_sync(_items(queries))
    assert not res.worker_died
    assert [c for c, _ in res.counts] == expected
    assert not any(t for _, t in res.counts)
    assert res.exec_s > 0.0                # worker-measured execution time
    assert pool.alive_count() == pool.size


def test_pool_real_sigkill_mid_bucket_recovers(pool, queries, expected):
    """SIGKILL the worker actually executing a bucket: the death surfaces
    as a `worker_died` result (pipe EOF / torn frame), the pool respawns
    back to configured size, and a replay yields bit-identical counts."""
    items = _items(queries[:3])
    deaths0 = pool.stats["deaths"]
    ticket = None
    while ticket is None:
        ticket = pool.dispatch(items)
        if ticket is None:
            pool.poll(0.05)                # workers still starting
    assert pool.kill_ticket(ticket)        # real SIGKILL, mid-bucket
    res = _await_ticket(pool, ticket)
    assert res.worker_died and not res.hung
    assert res.counts is None              # nothing partial crosses over
    assert pool.stats["deaths"] == deaths0 + 1
    # replay the lost bucket: exact counts, zero lost
    res2 = pool.run_sync(items)
    assert [c for c, _ in res2.counts] == expected[:3]
    # the pool returned to its configured size
    assert _await_full_size(pool) == pool.size
    assert pool.stats["respawned"] >= 1


def test_pool_watchdog_kills_hung_worker(pool, queries, expected):
    """A worker wedged past its bucket deadline (real sleep injected into
    the worker loop) is SIGKILLed by the wall-clock watchdog and the
    bucket comes back `hung` for re-issue."""
    items = _items(queries[:1])
    kills0 = pool.stats["watchdog_kills"]
    ticket = None
    while ticket is None:
        ticket = pool.dispatch(items, deadline_s=1.0, hang_s=300.0)
        if ticket is None:
            pool.poll(0.05)
    t0 = time.monotonic()
    res = _await_ticket(pool, ticket)
    assert res.worker_died and res.hung
    assert time.monotonic() - t0 < WAIT_S / 2   # the watchdog, not the sleep
    assert pool.stats["watchdog_kills"] == kills0 + 1
    # the hung bucket re-executes exactly after the kill
    res2 = pool.run_sync(items)
    assert [c for c, _ in res2.counts] == expected[:1]
    assert _await_full_size(pool) == pool.size


def test_pool_health_check_respawns_dead_idle_worker(pool):
    # silently kill an idle worker (no in-flight bucket) — the heartbeat
    # sweep must notice and respawn it without any bucket traffic
    deadline = time.monotonic() + WAIT_S
    while pool.idle_count() == 0 and time.monotonic() < deadline:
        pool.poll(0.05)
    victim = next(w for w in pool._workers if w.state == "idle")
    victim.proc.kill()
    victim.proc.join(timeout=10.0)
    assert pool.check_health() >= 1
    assert _await_full_size(pool) == pool.size


def test_pool_rejects_bad_config(data):
    with pytest.raises(ValueError):
        WorkerPool(data, 0)


# -------------------------------------------------- service integration
def test_service_sigkill_mid_bucket_bit_identical(data, queries, expected):
    """Acceptance: a real worker process is SIGKILLed mid-bucket inside a
    live MatchService drain. Final counts are bit-identical to the
    sequential oracle, every admitted request executed exactly once, and
    the pool is back to its configured size."""
    cfg = ServiceConfig(workers=2, bucket_size=4, worker_deadline_s=60.0,
                        retry_backoff_s=0.01)
    inj = FaultInjector(kill_worker_at={0})
    with MatchService(data, config=cfg) as svc:
        tickets = [svc.submit(q, limit=10**9, max_steps=None,
                              deadline_s=600.0) for q in queries]
        counts = svc.drain(injector=inj)
        assert [counts[t.request_id] for t in tickets] == expected
        # exactly-once: every request completed once, none lost, none
        # double-finalized, none permanently failed
        assert svc.stats["completed"] == len(queries)
        assert svc.stats["failed"] == svc.stats["shed_expired"] == 0
        assert svc.stats["reissued"] >= 1      # the killed bucket replayed
        assert svc.pool.stats["chaos_kills"] == 1
        assert svc.pool.stats["deaths"] >= 1
        assert _await_full_size(svc.pool) == svc.pool.size


def test_service_hang_past_deadline_bit_identical(data, queries, expected):
    """Acceptance: a worker hangs past `worker_deadline_s` mid-drain; the
    watchdog SIGKILLs it, the bucket replays, and final counts are
    bit-identical with zero lost / zero double-counted requests."""
    cfg = ServiceConfig(workers=2, bucket_size=4, worker_deadline_s=2.0,
                        retry_backoff_s=0.01)
    inj = FaultInjector(hang_at={0: 300.0})
    with MatchService(data, config=cfg) as svc:
        tickets = [svc.submit(q, limit=10**9, max_steps=None,
                              deadline_s=600.0) for q in queries]
        counts = svc.drain(injector=inj)
        assert [counts[t.request_id] for t in tickets] == expected
        assert svc.stats["completed"] == len(queries)
        assert svc.stats["failed"] == 0
        assert svc.pool.stats["watchdog_kills"] == 1
        assert _await_full_size(svc.pool) == svc.pool.size


def test_service_degradation_ladder_vector_to_ref(data, queries, expected):
    """Two real worker deaths under engine="vector" degrade the bucket to
    engine="ref" for its final attempt (instead of burning the budget on
    the faulting engine), and the completion records the degraded
    engine."""
    cfg = ServiceConfig(workers=1, bucket_size=2, max_attempts=3,
                        degrade_after=2, retry_backoff_s=0.01,
                        worker_deadline_s=60.0)
    inj = FaultInjector(kill_worker_at={0, 1})
    with MatchService(data, config=cfg,
                      options=MatchOptions(engine="vector")) as svc:
        t0 = svc.submit(queries[0], limit=10**9, max_steps=None,
                        deadline_s=600.0)
        t1 = svc.submit(queries[1], limit=10**9, max_steps=None,
                        deadline_s=600.0)
        counts = svc.drain(injector=inj)
        r0 = svc.result(t0.request_id)
        assert r0.ok and r0.attempts == 3 and r0.engine == "ref"
        assert counts[t0.request_id] == expected[0]
        assert counts[t1.request_id] == expected[1]
        assert svc.stats["degraded"] == 2      # both bucket members
        assert svc.stats["failed"] == 0
        assert svc.pool.stats["chaos_kills"] == 2


def test_service_rejects_fail_hook_with_pool(data, queries):
    cfg = ServiceConfig(workers=1)
    with MatchService(data, config=cfg) as svc:
        svc.submit(queries[0], limit=10**9, max_steps=None,
                   deadline_s=600.0)
        with pytest.raises(ValueError, match="process boundary"):
            svc.step(force=True, fail_hook=lambda req: None)


# ---------------------------------------------------- queue integration
def test_queue_runtime_drains_through_pool(data, queries, expected):
    with MatchQueueRuntime(data, workers=2) as rt:
        rt.submit(list(queries), limit=10**9)
        results = rt.run()
        assert [results[i] for i in range(len(queries))] == expected
        assert rt.stats["completed"] == len(queries)
        assert rt.stats["failed"] == 0
        assert rt.pool.alive_count() == 2
