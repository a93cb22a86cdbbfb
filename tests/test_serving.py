"""Traffic-serving front end: micro-batching, single-flight dedup,
backpressure, graceful drain — and the thread-safety contract of the
underlying scheduler (concurrent ``schedule_many`` + ``clear_cache``).

The hard guarantees under test:

* service output is BIT-identical to ``schedule_many`` on the same
  graphs (the service changes when work runs, never what runs);
* >= 8 submitter threads with overlapping duplicate graphs lose no
  result, duplicate no result, and the counter invariant
  ``hits + misses + dedups + failed == requests`` holds on a drained
  service;
* ``clear_cache`` racing a ``schedule_many`` fill never corrupts
  results or raises.
"""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from repro.core import RespectScheduler, sample_dag, validate_monotone
from repro.core.costmodel import PipelineSystem
from repro.serving import (SchedulerService, ServiceClosedError,
                           ServiceOverloadedError)

HIDDEN = 32
N_STAGES = 4


@pytest.fixture(scope="module")
def sched():
    """One scheduler per module: the decoder's compile LRU stays warm
    across tests, so each test pays dispatch, not XLA compiles."""
    s = RespectScheduler.init(seed=0, hidden=HIDDEN)
    rng = np.random.default_rng(123)
    # pre-warm the (bucket_n=16, bucket_b in {1..16}) fused programs the
    # tests below will route through
    for b in (1, 2, 4, 8, 16):
        gs = [sample_dag(rng, n=int(rng.integers(9, 15)), deg=3)
              for _ in range(b)]
        s.schedule_many(gs, N_STAGES, use_cache=False)
    return s


@pytest.fixture(scope="module")
def pool():
    rng = np.random.default_rng(7)
    return [sample_dag(rng, n=int(rng.integers(9, 15)), deg=3)
            for _ in range(5)]


@pytest.fixture(scope="module")
def reference(sched, pool):
    """content_hash -> assignment from an INDEPENDENT engine instance
    (fresh decoder, fresh caches) sharing only the params."""
    fresh = RespectScheduler(sched.params)
    return {
        g.content_hash(): r.assignment
        for g, r in zip(pool, fresh.schedule_many(
            pool, N_STAGES, use_cache=False))
    }


class _SlowScheduler:
    """Delay wrapper: makes in-flight windows wide enough to test
    single-flight dedup and queue backpressure deterministically."""

    def __init__(self, inner, delay_s, gate: threading.Event | None = None):
        self._inner = inner
        self._delay_s = delay_s
        self._gate = gate

    def schedule_many(self, *args, **kw):
        if self._gate is not None:
            self._gate.wait(timeout=30)
        time.sleep(self._delay_s)
        return self._inner.schedule_many(*args, **kw)

    @property
    def _decoder(self):
        return self._inner._decoder


# --------------------------------------------------------------------- #
# exactness
# --------------------------------------------------------------------- #
def test_service_output_bit_identical_to_schedule_many(sched, pool):
    trace = [pool[i % len(pool)] for i in range(23)]
    with SchedulerService(sched, max_batch=8, max_wait_ms=2) as svc:
        futs = [svc.submit(g, N_STAGES) for g in trace]
        got = [f.result(timeout=120) for f in futs]
    reference = RespectScheduler(sched.params)   # fresh engine, same params
    exp = reference.schedule_many(trace, N_STAGES, use_cache=False)
    for g, a, b in zip(trace, got, exp):
        assert np.array_equal(a.assignment, b.assignment)
        assert np.array_equal(a["order"], b["order"])
        assert validate_monotone(g, a.assignment, N_STAGES)


def test_waiter_results_are_private_copies(sched, pool):
    """Coalesced duplicates must not share arrays: mutating one caller's
    result cannot leak into another's."""
    gate = threading.Event()
    slow = _SlowScheduler(sched, 0.0, gate)
    g = pool[0]
    with SchedulerService(slow, max_batch=1, max_wait_ms=0) as svc:
        f1 = svc.submit(g, N_STAGES)
        f2 = svc.submit(g, N_STAGES)   # attaches while f1 is gated
        gate.set()
        r1, r2 = f1.result(timeout=60), f2.result(timeout=60)
    expected = r2.assignment.copy()
    r1.assignment[:] = -9
    r1["order"][:] = -9
    assert np.array_equal(r2.assignment, expected)
    assert (r2["order"] >= 0).all()


# --------------------------------------------------------------------- #
# concurrency hammer
# --------------------------------------------------------------------- #
def test_concurrent_submitters_no_lost_or_duplicated_results(
        sched, pool, reference):
    """>= 8 threads, overlapping duplicate graphs: every future resolves
    to the correct result, stats stay consistent, each distinct graph is
    solved at most once (single-flight + schedule cache)."""
    sched.clear_cache()
    n_threads, per_thread = 8, 12
    barrier = threading.Barrier(n_threads)
    results: list[list] = [[] for _ in range(n_threads)]
    errors: list[Exception] = []

    with SchedulerService(sched, max_batch=8, max_wait_ms=1,
                          max_queue=512) as svc:
        def hammer(tid):
            rng = np.random.default_rng(tid)
            barrier.wait()
            futs = []
            for _ in range(per_thread):
                g = pool[int(rng.integers(0, len(pool)))]
                futs.append((g, svc.submit(g, N_STAGES)))
            for g, f in futs:
                try:
                    results[tid].append((g, f.result(timeout=120)))
                except Exception as e:      # pragma: no cover
                    errors.append(e)

        threads = [threading.Thread(target=hammer, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        st = svc.stats()

    assert not errors
    flat = [rg for tr in results for rg in tr]
    assert len(flat) == n_threads * per_thread          # nothing lost
    for g, res in flat:
        assert np.array_equal(res.assignment, reference[g.content_hash()])
    # counter invariants on the drained service
    assert st.requests == n_threads * per_thread
    assert st.completed == st.requests and st.failed == 0
    assert st.cache_hits + st.cache_misses + st.dedup_hits == st.requests
    assert st.queue_depth == 0 and st.inflight_keys == 0
    # single-flight + schedule cache: each distinct (graph, stages) pair
    # is computed exactly once across all 96 requests
    assert st.cache_misses == len(pool)
    assert sched.cache_stats()["misses"] == len(pool)


def test_concurrent_schedule_many_direct_stats_consistent(
        sched, pool, reference):
    """The raw scheduler hammered from 8 threads (no service): results
    correct and hits + misses == total scheduled graphs."""
    sched.clear_cache()
    n_threads, reps = 8, 6
    barrier = threading.Barrier(n_threads)
    errors: list[Exception] = []

    def worker(tid):
        rng = np.random.default_rng(100 + tid)
        barrier.wait()
        try:
            for _ in range(reps):
                gs = [pool[int(rng.integers(0, len(pool)))]
                      for _ in range(3)]
                for g, r in zip(gs, sched.schedule_many(gs, N_STAGES)):
                    assert np.array_equal(
                        r.assignment, reference[g.content_hash()])
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not errors
    stats = sched.cache_stats()
    assert stats["hits"] + stats["misses"] == n_threads * reps * 3


def test_clear_cache_racing_fill_never_corrupts(sched, pool, reference):
    """clear_cache() storms while other threads schedule: no exception,
    every result stays correct (an in-progress fill re-inserts into the
    emptied cache; it must never KeyError or hand back a wrong entry)."""
    stop = threading.Event()
    errors: list[Exception] = []

    def clearer():
        while not stop.is_set():
            sched.clear_cache()
            time.sleep(1e-4)

    def scheduler_user(tid):
        rng = np.random.default_rng(200 + tid)
        try:
            for _ in range(8):
                gs = [pool[int(rng.integers(0, len(pool)))]
                      for _ in range(2)]
                for g, r in zip(gs, sched.schedule_many(gs, N_STAGES)):
                    assert np.array_equal(
                        r.assignment, reference[g.content_hash()])
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=scheduler_user, args=(t,))
               for t in range(4)]
    tc = threading.Thread(target=clearer)
    tc.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    stop.set()
    tc.join(timeout=30)
    assert not errors


# --------------------------------------------------------------------- #
# single-flight dedup
# --------------------------------------------------------------------- #
def test_single_flight_duplicates_attach_to_running_computation(sched, pool):
    gate = threading.Event()
    slow = _SlowScheduler(sched, 0.0, gate)
    sched.clear_cache()
    g = pool[1]
    n_dups = 9
    with SchedulerService(slow, max_batch=1, max_wait_ms=0) as svc:
        futs = [svc.submit(g, N_STAGES) for _ in range(n_dups)]
        st_mid = svc.stats()
        gate.set()
        res = [f.result(timeout=60) for f in futs]
        st = svc.stats()
    assert st_mid.dedup_hits >= 1          # attached while in flight
    assert st.requests == n_dups
    assert st.cache_hits + st.cache_misses + st.dedup_hits == n_dups
    assert sched.cache_stats()["misses"] == 1     # solved exactly once
    for r in res:
        assert np.array_equal(r.assignment, res[0].assignment)


def test_dedup_keys_distinguish_stages(sched, pool):
    """Same graph at different n_stages must NOT coalesce."""
    sched.clear_cache()
    g = pool[2]
    with SchedulerService(sched, max_batch=4, max_wait_ms=1) as svc:
        r4 = svc.submit(g, 4).result(timeout=60)
        r5 = svc.submit(g, 5).result(timeout=60)
        st = svc.stats()
    assert st.dedup_hits == 0
    assert r4["n_stages"] == 4 and r5["n_stages"] == 5
    assert sched.cache_stats()["misses"] == 2


# --------------------------------------------------------------------- #
# micro-batcher
# --------------------------------------------------------------------- #
def test_flush_on_max_batch_and_on_deadline(sched, pool):
    gate = threading.Event()
    slow = _SlowScheduler(sched, 0.0, gate)
    distinct = [sample_dag(np.random.default_rng(50 + i), n=12, deg=2)
                for i in range(4)]
    with SchedulerService(slow, max_batch=4, max_wait_ms=5000,
                          dedup=False) as svc:
        futs = [svc.submit(g, N_STAGES) for g in distinct]
        gate.set()
        for f in futs:
            f.result(timeout=60)
        st_full = svc.stats()
        # now a single trickle request: only the deadline can flush it
        gate.clear()
        svc.max_wait_s = 0.01
        f = svc.submit(distinct[0], N_STAGES)
        gate.set()
        f.result(timeout=60)
        st = svc.stats()
    assert st_full.flush_full >= 1
    assert st_full.max_batch_observed == 4
    assert st.flush_deadline >= 1


def test_mixed_stage_requests_in_one_flush_grouped_correctly(sched, pool):
    gate = threading.Event()
    slow = _SlowScheduler(sched, 0.0, gate)
    g = pool[3]
    with SchedulerService(slow, max_batch=8, max_wait_ms=50,
                          dedup=False) as svc:
        f4 = svc.submit(g, 4)
        f5 = svc.submit(g, 5)
        gate.set()
        r4, r5 = f4.result(timeout=60), f5.result(timeout=60)
    assert r4["n_stages"] == 4 and r5["n_stages"] == 5
    assert int(r4.assignment.max()) <= 3
    assert int(r5.assignment.max()) <= 4


# --------------------------------------------------------------------- #
# backpressure + lifecycle
# --------------------------------------------------------------------- #
def test_backpressure_queue_full_raises_overloaded(sched, pool):
    gate = threading.Event()
    slow = _SlowScheduler(sched, 0.0, gate)
    distinct = [sample_dag(np.random.default_rng(80 + i), n=10, deg=2)
                for i in range(6)]
    svc = SchedulerService(slow, max_batch=1, max_wait_ms=0,
                           max_queue=2, dedup=False)
    try:
        futs = []
        with pytest.raises(ServiceOverloadedError):
            for g in distinct:       # worker gated: queue must overflow
                futs.append(svc.submit(g, N_STAGES, timeout=0.01))
        gate.set()
        for f in futs:               # accepted requests still complete
            assert f.result(timeout=60)["cache_hit"] is False
        assert svc.stats().failed >= 1
    finally:
        gate.set()
        svc.close()


def test_hot_key_waiter_flood_hits_backpressure(sched, pool):
    """Duplicates coalescing onto one in-flight computation are bounded
    by max_waiters — a hot-key flood cannot grow memory off the bounded
    queue; it overflows like any other traffic."""
    gate = threading.Event()
    slow = _SlowScheduler(sched, 0.0, gate)
    g = pool[2]
    svc = SchedulerService(slow, max_batch=1, max_wait_ms=0, max_waiters=3)
    try:
        futs = [svc.submit(g, N_STAGES) for _ in range(4)]  # primary + 3
        with pytest.raises(ServiceOverloadedError):
            svc.submit(g, N_STAGES)                         # 4th waiter
        gate.set()
        for f in futs:
            assert f.result(timeout=60) is not None
        st = svc.stats()
        assert st.failed == 1 and st.dedup_hits == 3
        assert (st.cache_hits + st.cache_misses + st.dedup_hits + st.failed
                == st.requests)
    finally:
        gate.set()
        svc.close()


def test_close_drains_pending_and_rejects_new(sched, pool):
    gate = threading.Event()
    slow = _SlowScheduler(sched, 0.0, gate)
    svc = SchedulerService(slow, max_batch=2, max_wait_ms=1000, dedup=False)
    distinct = [sample_dag(np.random.default_rng(90 + i), n=10, deg=2)
                for i in range(5)]
    futs = [svc.submit(g, N_STAGES) for g in distinct]
    gate.set()
    assert svc.close() is True        # must drain all five, then join
    assert all(f.done() for f in futs)
    for g, f in zip(distinct, futs):
        assert validate_monotone(g, f.result().assignment, N_STAGES)
    with pytest.raises(ServiceClosedError):
        svc.submit(distinct[0], N_STAGES)
    svc.close()                       # idempotent
    st = svc.stats()
    assert st.completed == len(distinct) and st.queue_depth == 0


def test_worker_exception_propagates_and_service_survives(sched, pool):
    class _FailOnce:
        def __init__(self, inner):
            self._inner = inner
            self.tripped = False

        def schedule_many(self, *args, **kw):
            if not self.tripped:
                self.tripped = True
                raise ValueError("injected solver failure")
            return self._inner.schedule_many(*args, **kw)

        @property
        def _decoder(self):
            return self._inner._decoder

    failing = _FailOnce(sched)
    g = pool[4]
    # degrade=None pins the fail-fast contract: flush errors propagate to
    # the affected futures (the ladder path is covered in test_faults.py)
    with SchedulerService(failing, max_batch=1, max_wait_ms=0,
                          degrade=None) as svc:
        f_bad = svc.submit(g, N_STAGES)
        with pytest.raises(ValueError, match="injected solver failure"):
            f_bad.result(timeout=60)
        f_ok = svc.submit(g, N_STAGES)      # service keeps serving
        assert validate_monotone(g, f_ok.result(timeout=60).assignment,
                                 N_STAGES)
        st = svc.stats()
    assert st.failed == 1 and st.completed == 1


def test_error_path_reclassifies_waiters_keeps_invariant(sched, pool):
    """Duplicates coalesced onto a computation that ERRORS terminate as
    failed, not as served dedups: hits+misses+dedups+failed == requests
    must hold even on the failure path."""
    gate = threading.Event()

    class _GatedFail:
        def __init__(self, inner):
            self._inner = inner

        def schedule_many(self, *args, **kw):
            gate.wait(timeout=30)
            raise ValueError("gated failure")

        @property
        def _decoder(self):
            return self._inner._decoder

    g = pool[0]
    with SchedulerService(_GatedFail(sched), max_batch=1,
                          max_wait_ms=0, degrade=None) as svc:
        futs = [svc.submit(g, N_STAGES) for _ in range(4)]
        gate.set()
        for f in futs:
            with pytest.raises(ValueError, match="gated failure"):
                f.result(timeout=60)
        st = svc.stats()
    assert st.requests == 4
    assert st.failed == 4 and st.completed == 0 and st.dedup_hits == 0
    assert (st.cache_hits + st.cache_misses + st.dedup_hits + st.failed
            == st.requests)


# --------------------------------------------------------------------- #
# warmup + metrics
# --------------------------------------------------------------------- #
def test_warmup_precompiles_expected_bucket_shapes(pool):
    s = RespectScheduler.init(seed=1, hidden=HIDDEN)
    svc = SchedulerService(s)
    try:
        # (n, batch) specs compile synthetic stand-ins; a CompGraph spec
        # compiles the exact program that graph's live traffic will hit
        shapes = svc.warmup([(12, 2), pool[0]], n_stages=N_STAGES)
        fused = [k for k in shapes if len(k) == 6]   # fused program keys
        assert any(k[0] == 16 and k[1] == 2 for k in fused)
        assert any(k[0] == 16 and k[1] == 1 for k in fused)
        # warmup must not pollute the schedule cache
        stats = s.cache_stats()
        assert stats == {"hits": 0, "misses": 0, "size": 0,
                         "programs_built": len(shapes),
                         "programs_evicted": 0}
        # a live request of a warmed shape compiles nothing new
        n_before = len(shapes)
        svc.submit(pool[0], N_STAGES).result(timeout=60)
        assert len(s._decoder.compiled_shapes) == n_before
        assert s.cache_stats()["programs_built"] == n_before
    finally:
        svc.close()


def test_stats_percentiles_sane_after_traffic(sched, pool):
    with SchedulerService(sched, max_batch=4, max_wait_ms=1) as svc:
        futs = [svc.submit(pool[i % len(pool)], N_STAGES)
                for i in range(12)]
        for f in futs:
            f.result(timeout=120)
        st = svc.stats()
    assert np.isfinite(st.p50_ms) and np.isfinite(st.p99_ms)
    assert st.p50_ms <= st.p99_ms + 1e-9
    assert st.mean_ms > 0
    assert 1 <= st.max_batch_observed <= 4
    assert st.batches >= 1
    d = st.as_dict()
    assert d["requests"] == 12


def test_submit_future_type_and_timing_fields(sched, pool):
    with SchedulerService(sched, max_batch=2, max_wait_ms=1) as svc:
        f = svc.submit(pool[0], N_STAGES,
                       system=PipelineSystem(n_stages=N_STAGES))
        assert isinstance(f, Future)
        res = f.result(timeout=60)
    assert res["model"] == pool[0].model_name
    assert res["n_stages"] == N_STAGES
