"""Scheduler wall for the continuous-batching AsyncBatchedSampler: policy
logic under a fake clock (no real sleeps), liveness (a lone request never
starves), thread-safe submission (no lost or duplicated tickets under
concurrent submit stress), clean shutdown with in-flight work, chunk-
scoped failure isolation, and ``flush()`` — the caller-thread path sync
callers use — sharing the queue and compiled buckets with the drain
thread."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import OracleDenoiser, run_flush
from repro.serving import (
    AsyncBatchedSampler,
    FusedExecutor,
    SampleRequest,
    SchedulerPolicy,
    result_keys as K,
)

D_MODEL = OracleDenoiser.D_MODEL


def make_engine(analytic, buckets=(2, 4, 8)):
    return FusedExecutor(
        OracleDenoiser(analytic), analytic.schedule, batch_buckets=buckets
    )


def req(seed, seq_len=6, nfe=8, batch=1):
    return SampleRequest(batch=batch, seq_len=seq_len, nfe=nfe, seed=seed)


# ---------------------------------------------------------------------------
# policy logic (pure, no engine)
# ---------------------------------------------------------------------------


def test_policy_target_rows():
    assert SchedulerPolicy(target_occupancy=1.0).target_rows(8) == 8
    assert SchedulerPolicy(target_occupancy=0.5).target_rows(8) == 4
    assert SchedulerPolicy(target_occupancy=0.01).target_rows(8) == 1
    # bucketless engines have no occupancy trigger: deadline only
    assert SchedulerPolicy().target_rows(None) is None


def test_policy_should_launch():
    p = SchedulerPolicy(max_wait_ms=10.0, target_occupancy=1.0)
    # below target and before the oldest request's deadline: hold
    assert not p.should_launch(now=1.0, oldest_t=1.0, rows=3, max_bucket=8)
    # occupancy reached: launch immediately, no matter the clock
    assert p.should_launch(now=1.0, oldest_t=1.0, rows=8, max_bucket=8)
    # deadline reached: launch whatever is there (deadline promotion)
    assert p.should_launch(now=1.0101, oldest_t=1.0, rows=1, max_bucket=8)
    # bucketless: only the deadline can trigger
    assert not p.should_launch(now=1.0, oldest_t=1.0, rows=100, max_bucket=None)
    assert p.should_launch(now=1.011, oldest_t=1.0, rows=1, max_bucket=None)


# ---------------------------------------------------------------------------
# scheduling decisions under a fake clock (no thread, no sleeps)
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def test_deadline_launch_under_fake_clock(analytic):
    clock = FakeClock()
    sched = AsyncBatchedSampler(
        make_engine(analytic),
        params=None,
        policy=SchedulerPolicy(max_wait_ms=50.0),
        clock=clock,
    )
    fut = sched.submit(req(seed=1))
    # before the deadline and below occupancy: nothing may launch
    assert sched.drain_once(now=clock.now + 0.049) == 0
    assert not fut.done()
    # one tick past max_wait: the lone request is promoted and launches
    assert sched.drain_once(now=clock.now + 0.051) == 1
    assert fut.done()
    assert fut.result().x0.shape == (1, 6, D_MODEL)


def test_occupancy_launch_under_fake_clock(analytic):
    clock = FakeClock()
    sched = AsyncBatchedSampler(
        make_engine(analytic),
        params=None,
        policy=SchedulerPolicy(max_wait_ms=1e6, target_occupancy=0.5),
        clock=clock,
    )
    futs = [sched.submit(req(seed=s)) for s in range(3)]
    assert sched.drain_once(now=clock.now) == 0  # 3 rows < target 4
    futs.append(sched.submit(req(seed=3)))
    # target occupancy hit: launches with the deadline nowhere near
    assert sched.drain_once(now=clock.now) == 1
    assert all(f.done() for f in futs)
    assert futs[0].result().padded_batch == 4


def test_oldest_queue_served_first(analytic, monkeypatch):
    """Deadline promotion is oldest-arrival-first across shape queues."""
    clock = FakeClock()
    engine = make_engine(analytic)
    sched = AsyncBatchedSampler(
        engine,
        params=None,
        policy=SchedulerPolicy(max_wait_ms=10.0),
        clock=clock,
    )
    order = []
    orig = engine.run_chunk

    def recording(params, seq_len, nfe, chunk, results, pad=True):
        order.append((seq_len, nfe))
        return orig(params, seq_len, nfe, chunk, results, pad=pad)

    monkeypatch.setattr(engine, "run_chunk", recording)
    sched.submit(req(seed=0, seq_len=4))
    clock.now += 0.002
    sched.submit(req(seed=1, seq_len=6))
    clock.now += 0.002
    sched.submit(req(seed=2, seq_len=8))
    assert sched.drain_once(now=clock.now + 0.02) == 3
    assert order == [(4, 8), (6, 8), (8, 8)]


def test_launch_takes_at_most_one_max_bucket(analytic):
    """A deadline launch takes one largest-bucket's worth of rows; the
    remainder keeps its arrival time for the next launch."""
    clock = FakeClock()
    engine = make_engine(analytic, buckets=(4,))
    sched = AsyncBatchedSampler(
        engine,
        params=None,
        policy=SchedulerPolicy(max_wait_ms=10.0, target_occupancy=1e9),
        clock=clock,
    )
    futs = [sched.submit(req(seed=s)) for s in range(6)]
    assert sched.drain_once(now=clock.now + 0.02) == 1  # 4 of 6 rows
    assert sum(f.done() for f in futs) == 4
    assert sched.pending == 2
    assert sched.drain_once(now=clock.now + 0.04) == 1
    assert all(f.done() for f in futs)
    assert futs[0].result().padded_batch == 4


def test_chunk_failure_is_isolated(analytic, monkeypatch):
    """A failed launch fails only its own chunk's futures; the scheduler
    keeps serving other queues."""
    clock = FakeClock()
    engine = make_engine(analytic)
    sched = AsyncBatchedSampler(
        engine,
        params=None,
        policy=SchedulerPolicy(max_wait_ms=10.0),
        clock=clock,
    )
    orig = engine.run_chunk

    def flaky(params, seq_len, nfe, chunk, results, pad=True):
        if seq_len == 4:
            raise RuntimeError("injected kernel failure")
        return orig(params, seq_len, nfe, chunk, results, pad=pad)

    monkeypatch.setattr(engine, "run_chunk", flaky)
    bad = sched.submit(req(seed=0, seq_len=4))
    good = sched.submit(req(seed=1, seq_len=6))
    assert sched.drain_once(now=clock.now + 0.02) == 2
    with pytest.raises(RuntimeError, match="injected"):
        bad.result(timeout=0)
    assert not bool(jnp.any(jnp.isnan(good.result(timeout=0).x0)))


# ---------------------------------------------------------------------------
# liveness and thread safety (real drain thread)
# ---------------------------------------------------------------------------


def test_lone_request_is_not_starved(analytic):
    """max_wait_ms bounds a lone request's queue time: with no other traffic
    ever arriving, the future still resolves."""
    engine = make_engine(analytic)
    with AsyncBatchedSampler(
        engine, params=None, policy=SchedulerPolicy(max_wait_ms=5.0)
    ) as sched:
        fut = sched.submit(req(seed=42))
        res = fut.result(timeout=60)
    assert res.x0.shape == (1, 6, D_MODEL)
    assert sched.stats()[K.BATCHES] == 1


def test_concurrent_submit_stress_no_lost_or_duplicate_tickets(analytic):
    """N client threads submitting concurrently: every future resolves to
    its own request's result (seed-correct rows), and the scheduler's
    accounting sees exactly one ticket per submit."""
    engine = make_engine(analytic)
    n_threads, per_thread = 4, 6
    futures: dict[int, object] = {}
    lock = threading.Lock()

    with AsyncBatchedSampler(
        engine,
        params=None,
        policy=SchedulerPolicy(max_wait_ms=3.0, target_occupancy=0.5),
    ) as sched:

        def client(tid):
            for i in range(per_thread):
                seed = 1000 * tid + i
                fut = sched.submit(req(seed=seed))
                with lock:
                    futures[seed] = fut
                time.sleep(0.001 * (tid % 3))

        threads = [
            threading.Thread(target=client, args=(t,)) for t in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        results = {s: f.result(timeout=60) for s, f in futures.items()}

    total = n_threads * per_thread
    assert len(results) == total
    stats = sched.stats()
    assert stats["submitted"] == total
    assert stats["rows"] == total  # no row lost, none launched twice
    # spot-check isolation: each future resolved to ITS request's samples
    # (bit-identical to a solo run of the same seed), not a batch-mate's
    solo = FusedExecutor(
        OracleDenoiser(analytic), analytic.schedule, batch_buckets=None
    )
    for seed in (0, 1003, 3005):
        ref = run_flush(solo, [req(seed=seed)])[0].x0
        np.testing.assert_array_equal(
            np.asarray(results[seed].x0), np.asarray(ref)
        )


def test_clean_shutdown_flushes_in_flight_work(analytic):
    """stop() with queued work resolves every outstanding future before
    returning, and later submits are rejected."""
    engine = make_engine(analytic)
    sched = AsyncBatchedSampler(
        engine,
        params=None,
        # deadline far away: the requests are still queued when stop() runs
        policy=SchedulerPolicy(max_wait_ms=60_000.0),
    ).start()
    futs = [sched.submit(req(seed=s)) for s in range(3)]
    sched.stop()
    assert all(f.done() for f in futs)
    for f in futs:
        assert f.result(timeout=0).x0.shape == (1, 6, D_MODEL)
    with pytest.raises(RuntimeError, match="stopped"):
        sched.submit(req(seed=9))


def test_stop_without_start_flushes(analytic):
    sched = AsyncBatchedSampler(make_engine(analytic), params=None)
    fut = sched.submit(req(seed=5))
    sched.stop()
    assert fut.result(timeout=0).x0.shape == (1, 6, D_MODEL)


def test_schedulers_are_one_shot(analytic):
    """start() after stop() fails loudly instead of spawning a thread that
    exits immediately and leaves submits mysteriously rejected."""
    sched = AsyncBatchedSampler(make_engine(analytic), params=None).start()
    sched.stop()
    with pytest.raises(RuntimeError, match="one-shot"):
        sched.start()
    sched.stop()  # idempotent: a second stop is a no-op, not a crash


def test_cancelled_future_does_not_kill_the_drain_thread(analytic):
    """A client that times out and cancels its future must not crash the
    launch that later tries to deliver to it — co-batched waiters and all
    later traffic still get results."""
    engine = make_engine(analytic)
    with AsyncBatchedSampler(
        engine,
        params=None,
        policy=SchedulerPolicy(max_wait_ms=20.0),
    ) as sched:
        gone = sched.submit(req(seed=0))
        assert gone.cancel()  # impatient client gives up pre-launch
        survivor = sched.submit(req(seed=1))
        assert survivor.result(timeout=60).x0.shape == (1, 6, D_MODEL)
        # the thread survived delivery-to-cancelled: it still serves
        later = sched.submit(req(seed=2))
        assert later.result(timeout=60).x0.shape == (1, 6, D_MODEL)


def test_engine_drain_tolerates_cancelled_future(analytic):
    """A future cancelled before ``flush()`` must not break the flush: the
    co-batched request still gets its result and the launch is counted."""
    sched = AsyncBatchedSampler(make_engine(analytic), params=None)
    fut1 = sched.submit(req(seed=0))
    fut2 = sched.submit(req(seed=1))
    assert fut1.cancel()
    assert sched.flush() == 1
    assert sched.stats()[K.ROWS] == 2  # the cancelled row still ran
    assert fut2.result(timeout=0).x0.shape == (1, 6, D_MODEL)


def test_submit_is_atomic_under_concurrent_flushes(analytic):
    """A flush loop racing submitters can never orphan a result: the Future
    comes back from the same locked section that enqueues the ticket."""
    sched = AsyncBatchedSampler(make_engine(analytic), params=None)
    stop = threading.Event()

    def flush_loop():
        while not stop.is_set():
            sched.flush()

    th = threading.Thread(target=flush_loop)
    th.start()
    try:
        futs = [sched.submit(req(seed=s)) for s in range(8)]
        for f in futs:
            assert f.result(timeout=60).x0.shape == (1, 6, D_MODEL)
    finally:
        stop.set()
        th.join()


def test_sync_and_async_paths_share_compiled_buckets(analytic):
    """The drain thread reuses the program ``flush()`` compiled — same
    bucket, same program, zero extra compiles."""
    engine = make_engine(analytic, buckets=(4,))
    run_flush(engine, [req(seed=0)])
    cached = set(engine.compile_cache())
    with AsyncBatchedSampler(
        engine, params=None, policy=SchedulerPolicy(max_wait_ms=2.0)
    ) as sched:
        futs = [sched.submit(req(seed=s)) for s in range(3)]
        for f in futs:
            f.result(timeout=60)
    assert set(engine.compile_cache()) == cached


def test_flush_serves_mixed_groups_on_caller_thread(analytic, monkeypatch):
    """``flush()`` runs every queued fuse group on the calling thread, one
    chunk per group, with no drain thread started; a request submitted
    after it waits for the next flush."""
    engine = make_engine(analytic)
    threads = []
    orig = engine.run_chunk

    def recording(params, seq_len, nfe, chunk, results, pad=True):
        threads.append(threading.current_thread())
        return orig(params, seq_len, nfe, chunk, results, pad=pad)

    monkeypatch.setattr(engine, "run_chunk", recording)
    sched = AsyncBatchedSampler(engine, params=None)
    mixed = [
        sched.submit(req(seed=0, seq_len=4)),
        sched.submit(req(seed=1, seq_len=6)),
        sched.submit(req(seed=2, seq_len=4, batch=2)),
        sched.submit(req(seed=3, seq_len=6, nfe=10)),
    ]
    assert sched.flush() == 3  # (4, 8), (6, 8) and (6, 10)
    assert threads == [threading.current_thread()] * 3
    assert [f.result(timeout=0).padded_batch for f in mixed] == [4, 2, 4, 2]
    assert sched.pending == 0

    late = sched.submit(req(seed=4))
    assert not late.done()
    assert sched.flush() == 1
    assert late.result(timeout=0).x0.shape == (1, 6, D_MODEL)
    assert sched.stats()[K.BATCHES] == 4
