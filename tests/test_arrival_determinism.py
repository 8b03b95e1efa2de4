"""Arrival-order determinism wall for the continuous-batching scheduler.

The serving contract: a seeded request's ``x0`` is **bit-identical**
whether it runs

* via the scheduler's ``flush()`` on the caller's thread (fused with
  whoever was queued) — the "sync" path below,
* via the scheduler's drain thread under an arbitrary arrival
  interleaving — client threads racing, random delays, whatever batch
  compositions the policy happens to form — the "async" path, or
* solo, flushed alone through an exact-size engine (no padding).

Per-sample ERS is what makes this hold (each row's delta_eps measurement
and Lagrange base selection read only its own row), and this property is
what makes continuous batching correctness-preserving at all: scheduler
timing must never leak into results.  Randomized over seq_len / nfe / seeds
/ arrival delays with hypothesis, and re-checked on the 8-virtual-device
mesh fixture.

PR-4 extends the wall to **mixed-solver streams**: requests routed to
different registry solvers (`era` / `ddim` / `dpm_solver_pp2m`) interleave
in one scheduler, batch per (solver, seq_len, nfe) queue, and every
request's x0 still matches its flushed and solo runs bit-for-bit.

PR-5 extends it to **mixed-seq-len streams**: with `seq_buckets` the
scheduler queues key on the seq *bucket*, so requests of different lengths
share fused batches (right-padded + length-masked), and every request's x0
still matches its exact-shape solo run bit-for-bit under any arrival
interleaving (see also `tests/test_seq_bucketing.py`).

PR-10 extends it to **mixed-NFE streams**: with `nfe_buckets` the queues
key on the NFE *bucket*, so 10/18/25-NFE requests share step-masked fused
batches.  The step-masked contract is composition-shaped: a request's x0
depends only on the compiled batch shape it ran at — never on its
batch-mates' values, NFEs, or row order — so async results are bitwise
equal to the flush whenever the scheduler formed the same batch
bucket, and within float tolerance (last-ulp transcendental rounding on
batch-shaped time columns) when it formed a different one (see also
`tests/test_nfe_bucketing.py`).
"""

import random
import threading
import time

import numpy as np

from hypothesis import given, settings, strategies as st
from conftest import AnalyticGaussian, OracleDenoiser, run_flush
from repro.core import ERAConfig
from repro.serving import (
    AsyncBatchedSampler,
    FusedExecutor,
    SampleRequest,
    SchedulerPolicy,
)

# module-level: the shim's `given` produces zero-arg tests, so no fixtures
ANALYTIC = AnalyticGaussian()

# solvers a mixed stream cycles through (None = the engine default, era)
MIXED_SOLVERS = (None, "ddim", "dpm_solver_pp2m", "era")


def _requests(n, seq_len, nfe, seed0, mixed=False):
    return [
        SampleRequest(
            batch=1,
            seq_len=seq_len,
            nfe=nfe,
            solver=MIXED_SOLVERS[i % len(MIXED_SOLVERS)] if mixed else None,
            seed=seed0 + i,
        )
        for i in range(n)
    ]


def _engine(mesh=None, seq_buckets=None, nfe_buckets=None):
    return FusedExecutor(
        OracleDenoiser(ANALYTIC),
        ANALYTIC.schedule,
        batch_buckets=(2, 4, 8),
        mesh=mesh,
        seq_buckets=seq_buckets,
        nfe_buckets=nfe_buckets,
    )


def _sync_results(reqs, mesh=None, seq_buckets=None, nfe_buckets=None):
    return run_flush(_engine(mesh, seq_buckets, nfe_buckets), reqs)


def _sync_x0(reqs, mesh=None, seq_buckets=None):
    return [
        np.asarray(r.x0)
        for r in _sync_results(reqs, mesh=mesh, seq_buckets=seq_buckets)
    ]


def _async_results(
    reqs, delay_seed, mesh=None, seq_buckets=None, nfe_buckets=None
):
    """Run through the scheduler with racing client threads and randomized
    submission delays — arbitrary arrival interleavings and batch
    compositions."""
    engine = _engine(mesh, seq_buckets, nfe_buckets)
    rng = random.Random(delay_seed)
    futures: dict[int, object] = {}
    lock = threading.Lock()
    with AsyncBatchedSampler(
        engine,
        params=None,
        policy=SchedulerPolicy(max_wait_ms=2.0, target_occupancy=0.5),
    ) as sched:

        def client(my_reqs):
            for i, r in my_reqs:
                time.sleep(rng.uniform(0.0, 0.004))
                fut = sched.submit(r)
                with lock:
                    futures[i] = fut

        indexed = list(enumerate(reqs))
        threads = [
            threading.Thread(target=client, args=(indexed[0::2],)),
            threading.Thread(target=client, args=(indexed[1::2],)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        out = {i: f.result(timeout=120) for i, f in futures.items()}
    return [out[i] for i in range(len(reqs))]


def _async_x0(reqs, delay_seed, mesh=None, seq_buckets=None):
    return [
        np.asarray(r.x0)
        for r in _async_results(
            reqs, delay_seed, mesh=mesh, seq_buckets=seq_buckets
        )
    ]


def _solo_x0(reqs):
    engine = FusedExecutor(
        OracleDenoiser(ANALYTIC),
        ANALYTIC.schedule,
        solver_config=ERAConfig(per_sample=True),
        batch_buckets=None,
    )
    return [np.asarray(run_flush(engine, [r])[0].x0) for r in reqs]


@settings(max_examples=4, deadline=None)
@given(
    st.integers(min_value=2, max_value=5),       # co-arriving requests
    st.integers(min_value=2, max_value=8),       # seq_len
    st.integers(min_value=0, max_value=4),       # nfe headroom above k=4
    st.integers(min_value=0, max_value=10_000),  # request seed base
    st.integers(min_value=0, max_value=10_000),  # arrival-delay seed
)
def test_x0_bit_identical_across_sync_async_and_solo(
    n, seq_len, extra, seed0, delay_seed
):
    reqs = _requests(n, seq_len, nfe=5 + extra, seed0=seed0)
    sync = _sync_x0(reqs)
    asyn = _async_x0(reqs, delay_seed)
    solo = _solo_x0(reqs)
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(
            asyn[i],
            sync[i],
            err_msg=f"async vs sync diverged for seed {r.seed} "
            f"(n={n}, seq_len={seq_len}, nfe={r.nfe})",
        )
        np.testing.assert_array_equal(
            asyn[i],
            solo[i],
            err_msg=f"async vs solo diverged for seed {r.seed} "
            f"(n={n}, seq_len={seq_len}, nfe={r.nfe})",
        )


@settings(max_examples=3, deadline=None)
@given(
    st.integers(min_value=3, max_value=6),       # co-arriving requests
    st.integers(min_value=2, max_value=8),       # seq_len
    st.integers(min_value=0, max_value=4),       # nfe headroom above k=4
    st.integers(min_value=0, max_value=10_000),  # request seed base
    st.integers(min_value=0, max_value=10_000),  # arrival-delay seed
)
def test_x0_bit_identical_for_mixed_solver_streams(
    n, seq_len, extra, seed0, delay_seed
):
    """The same wall with requests routed to different solvers: the
    scheduler batches per (solver, seq_len, nfe) queue, and no request's
    result depends on which solvers its neighbours asked for."""
    reqs = _requests(n, seq_len, nfe=5 + extra, seed0=seed0, mixed=True)
    sync = _sync_x0(reqs)
    asyn = _async_x0(reqs, delay_seed)
    solo = _solo_x0(reqs)
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(
            asyn[i],
            sync[i],
            err_msg=f"async vs sync diverged for solver {r.solver} "
            f"seed {r.seed} (n={n}, seq_len={seq_len}, nfe={r.nfe})",
        )
        np.testing.assert_array_equal(
            asyn[i],
            solo[i],
            err_msg=f"async vs solo diverged for solver {r.solver} "
            f"seed {r.seed} (n={n}, seq_len={seq_len}, nfe={r.nfe})",
        )


@settings(max_examples=3, deadline=None)
@given(
    st.integers(min_value=3, max_value=6),       # co-arriving requests
    st.integers(min_value=1, max_value=8),       # first request's seq_len
    st.integers(min_value=0, max_value=4),       # nfe headroom above k=4
    st.integers(min_value=0, max_value=10_000),  # request seed base
    st.integers(min_value=0, max_value=10_000),  # arrival-delay seed
)
def test_x0_bit_identical_for_mixed_seq_len_streams(
    n, seq0, extra, seed0, delay_seed
):
    """The same wall with requests of *different* seq_lens fusing into one
    seq-bucketed batch: the scheduler queues key on the bucket, so any
    arrival interleaving can mix lengths in a chunk — and no request's x0
    may depend on which lengths its batch-mates brought, nor on how far it
    was padded."""
    nfe = 5 + extra
    buckets = (4, 8)
    reqs = [
        SampleRequest(
            batch=1,
            seq_len=(seq0 + 3 * i) % 8 + 1,
            nfe=nfe,
            seed=seed0 + i,
        )
        for i in range(n)
    ]
    sync = _sync_x0(reqs, seq_buckets=buckets)
    asyn = _async_x0(reqs, delay_seed, seq_buckets=buckets)
    solo = _solo_x0(reqs)  # exact-shape, no bucketing anywhere
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(
            asyn[i],
            sync[i],
            err_msg=f"async vs sync diverged for seq_len {r.seq_len} "
            f"seed {r.seed} (n={n}, nfe={r.nfe})",
        )
        np.testing.assert_array_equal(
            asyn[i],
            solo[i],
            err_msg=f"bucketed async vs exact-shape solo diverged for "
            f"seq_len {r.seq_len} seed {r.seed} (n={n}, nfe={r.nfe})",
        )


NFE_BUCKETS = (18, 32)
NFE_STREAM = (10, 18, 25)  # 10/18 share the 18-bucket; 25 rides the 32


def _assert_composition_shaped(asyn, sync, label):
    """The step-masked determinism contract: bitwise whenever the
    scheduler formed the same batch bucket as the flush, float-
    tolerance (last-ulp transcendental rounding) when it formed a
    different one."""
    for i, (a, s) in enumerate(zip(asyn, sync)):
        if a.padded_batch == s.padded_batch:
            np.testing.assert_array_equal(
                np.asarray(a.x0), np.asarray(s.x0),
                err_msg=f"{label}: async vs sync diverged at identical "
                f"batch bucket {a.padded_batch} (request {i})",
            )
        else:
            np.testing.assert_allclose(
                np.asarray(a.x0), np.asarray(s.x0), atol=1e-6,
                err_msg=f"{label}: async (bucket {a.padded_batch}) vs "
                f"sync (bucket {s.padded_batch}) exceeded the cross-"
                f"composition tolerance (request {i})",
            )


@settings(max_examples=3, deadline=None)
@given(
    st.integers(min_value=3, max_value=6),       # co-arriving requests
    st.integers(min_value=2, max_value=8),       # seq_len
    st.integers(min_value=0, max_value=10_000),  # request seed base
    st.integers(min_value=0, max_value=10_000),  # arrival-delay seed
)
def test_x0_deterministic_for_mixed_nfe_streams(n, seq_len, seed0, delay_seed):
    """The wall with requests of *different* NFEs fusing into shared
    step-masked buckets: the scheduler queues key on the NFE bucket, so
    any arrival interleaving can mix 10/18/25-NFE requests in a chunk —
    and no request's x0 may depend on which NFEs its batch-mates brought,
    nor on how far its steps were padded."""
    reqs = [
        SampleRequest(
            batch=1,
            seq_len=seq_len,
            nfe=NFE_STREAM[i % len(NFE_STREAM)],
            seed=seed0 + i,
        )
        for i in range(n)
    ]
    sync = _sync_results(reqs, nfe_buckets=NFE_BUCKETS)
    asyn = _async_results(reqs, delay_seed, nfe_buckets=NFE_BUCKETS)
    for i, r in enumerate(reqs):
        # every request rode a bucketed (step-masked) program
        assert asyn[i].padded_nfe in NFE_BUCKETS, r.nfe
        assert sync[i].padded_nfe == asyn[i].padded_nfe
    _assert_composition_shaped(
        asyn, sync, f"mixed-NFE (n={n}, seq_len={seq_len}, seed0={seed0})"
    )
    # and the scalar-time solo runs anchor correctness to float tolerance
    solo = _solo_x0(reqs)
    for i, r in enumerate(reqs):
        np.testing.assert_allclose(
            np.asarray(asyn[i].x0), solo[i], atol=1e-6,
            err_msg=f"bucketed async vs exact-NFE solo diverged for "
            f"nfe {r.nfe} seed {r.seed}",
        )


def test_mixed_nfe_arrival_determinism_on_mesh(mesh8):
    """The mixed-NFE wall on the 8-virtual-device mesh: step-mask pspecs
    ride the carry, and scheduler timing must not leak into results when
    the step-masked batch is sharded across devices."""
    reqs = [
        SampleRequest(
            batch=1, seq_len=6, nfe=NFE_STREAM[i % len(NFE_STREAM)],
            seed=300 + i,
        )
        for i in range(6)
    ]
    sync_mesh = _sync_results(reqs, mesh=mesh8, nfe_buckets=NFE_BUCKETS)
    async_mesh = _async_results(
        reqs, delay_seed=5, mesh=mesh8, nfe_buckets=NFE_BUCKETS
    )
    _assert_composition_shaped(async_mesh, sync_mesh, "mesh mixed-NFE")
    single = _sync_results(reqs, nfe_buckets=NFE_BUCKETS)
    for i, r in enumerate(reqs):
        np.testing.assert_allclose(
            np.asarray(async_mesh[i].x0), np.asarray(single[i].x0),
            atol=1e-5,
            err_msg=f"mesh vs single-device mixed-NFE diverged for "
            f"nfe {r.nfe} seed {r.seed}",
        )


def test_arrival_determinism_on_mesh(mesh8):
    """The same wall on the 8-virtual-device mesh: scheduler timing must not
    leak into results when the fused batch is sharded across devices."""
    reqs = _requests(5, seq_len=6, nfe=8, seed0=77)
    sync_mesh = _sync_x0(reqs, mesh=mesh8)
    async_mesh = _async_x0(reqs, delay_seed=3, mesh=mesh8)
    single = _sync_x0(reqs)
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(
            async_mesh[i],
            sync_mesh[i],
            err_msg=f"mesh async vs mesh sync diverged for seed {r.seed}",
        )
        np.testing.assert_allclose(
            async_mesh[i],
            single[i],
            atol=1e-5,
            err_msg=f"mesh async vs single-device diverged for seed {r.seed}",
        )
