"""Batched sampling engine: fused-step parity, platform kernel selection,
compile-once-per-bucket, batch-of-N == N-independent-runs equivalence
(per-sample ERS on), padding invariance, and mesh-sharded parity.  Requests
queue on the scheduler and run on the test's thread through ``flush()``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import OracleDenoiser, run_flush, run_mesh_subprocess
from repro.core import ERAConfig, get_solver
from repro.kernels import ops
from repro.serving import AsyncBatchedSampler, FusedExecutor, SampleRequest

D_MODEL = OracleDenoiser.D_MODEL


@pytest.fixture()
def engine(analytic):
    return FusedExecutor(
        OracleDenoiser(analytic), analytic.schedule, batch_buckets=(2, 4, 8)
    )


# ---------------------------------------------------------------------------
# fused default path numerics (acceptance: <= 1e-5 in f32, interpret mode)
# ---------------------------------------------------------------------------


def test_fused_step_parity_within_1e5():
    for shape in ((4, 96), (2, 8, 8), (130,)):
        for k in (3, 4, 6):
            err = ops.fused_step_parity(shape=shape, k=k)
            assert err <= 1e-5, (shape, k, err)


def test_cpu_runs_the_kernel_in_interpret_mode_without_fallback(analytic):
    """The platform picks the kernel, not a probe: off TPU the ERA step
    traces to the Pallas kernel (interpret mode) on both the shared and the
    per-sample path, and only the explicit reference config leaves it."""
    assert jax.default_backend() == "cpu" and ops.interpret_mode()
    x = jnp.zeros((2, 6, D_MODEL), jnp.float32)

    def jaxpr(cfg):
        return str(
            jax.make_jaxpr(
                lambda x: get_solver("era")(
                    analytic.eps, x, analytic.schedule, cfg
                ).x0
            )(x)
        )

    for per_sample in (False, True):
        cfg = ERAConfig(nfe=6, per_sample=per_sample)
        assert "pallas_call" in jaxpr(cfg)
        reference = dataclasses.replace(cfg, use_fused_update=False)
        assert "pallas_call" not in jaxpr(reference)


# ---------------------------------------------------------------------------
# batched engine semantics
# ---------------------------------------------------------------------------


def test_submit_drain_shapes_and_metadata(engine, analytic):
    queue = AsyncBatchedSampler(engine, None)
    f1 = queue.submit(SampleRequest(batch=1, seq_len=6, nfe=8, seed=1))
    f2 = queue.submit(SampleRequest(batch=3, seq_len=6, nfe=8, seed=2))
    assert queue.pending == 2
    assert queue.flush() == 1  # one fused batch
    assert queue.pending == 0
    r1, r2 = f1.result(timeout=0), f2.result(timeout=0)
    assert r1.x0.shape == (1, 6, D_MODEL)
    assert r2.x0.shape == (3, 6, D_MODEL)
    # 1 + 3 samples pad to the 4-bucket, fused into one batch
    assert r1.padded_batch == 4
    assert r1.batch_wall_s == r2.batch_wall_s
    assert r1.latency_s >= r1.batch_wall_s
    for res in (r1, r2):
        assert not bool(jnp.any(jnp.isnan(res.x0)))
        assert "delta_eps_history" in res.aux
        # diagnostics are scoped to the request's own rows, not the padded
        # batch (no batch-mate rows, no pad rows in the mean)
        assert res.aux["delta_eps_history_per_sample"].shape == (
            8,
            res.x0.shape[0],
        )
        assert res.aux["delta_eps_history"].shape == (8,)


def test_batch_of_n_equals_independent_runs(engine, analytic):
    """Co-batched requests (per-sample ERS) match solo ERA-Solver runs."""
    seeds = [3, 4, 5]
    results = run_flush(
        engine,
        [SampleRequest(batch=1, seq_len=6, nfe=10, seed=s) for s in seeds],
    )
    cfg = ERAConfig(nfe=10, per_sample=True)
    for s, res in zip(seeds, results):
        x_init = jax.random.normal(
            jax.random.PRNGKey(s), (1, 6, D_MODEL), jnp.float32
        )
        solo = get_solver("era")(analytic.eps, x_init, analytic.schedule, cfg)
        np.testing.assert_allclose(
            np.asarray(res.x0),
            np.asarray(solo.x0),
            atol=1e-5,
        )


def test_compile_once_per_bucket(engine):
    """Fluctuating request sizes within one bucket reuse one XLA program."""
    queue = AsyncBatchedSampler(engine, None)
    for seed, batch in enumerate((1, 2, 1, 2, 1)):
        queue.submit(SampleRequest(batch=batch, seq_len=6, nfe=8, seed=seed))
        queue.flush()
    cache = engine.compile_cache()
    assert len(cache) == 1  # batches 1 and 2 share the 2-bucket
    (runner,) = cache.values()
    # the cache holds AOT-compiled executables, not lazy jit wrappers, so
    # one entry *is* one compile; the remaining flushes were memory hits
    assert isinstance(runner, jax.stages.Compiled)
    stats = engine.compile_stats()
    assert stats["fresh"] + stats["disk"] == 1
    assert stats["memory"] == 4


def test_distinct_buckets_compile_separately(engine):
    res = run_flush(
        engine,
        [
            SampleRequest(batch=1, seq_len=6, nfe=8, seed=0),
            SampleRequest(batch=1, seq_len=4, nfe=8, seed=1),
            SampleRequest(batch=1, seq_len=6, nfe=12, seed=2),
        ],
    )
    assert len(res) == 3
    assert len(engine.compile_cache()) == 3  # (seq 6, 8) / (seq 4, 8) / (seq 6, 12)


def test_oversize_request_chunks_to_max_bucket(engine):
    big, small = run_flush(
        engine,
        [
            SampleRequest(batch=5, seq_len=6, nfe=8, seed=0),
            SampleRequest(batch=2, seq_len=6, nfe=8, seed=1),
        ],
    )
    assert big.x0.shape == (5, 6, D_MODEL)
    assert small.x0.shape == (2, 6, D_MODEL)


def test_drain_chunk_failure_resolves_futures_and_spares_other_chunks(
    engine, analytic, monkeypatch
):
    """A chunk that fails in a flush must not orphan any waiter: its
    futures carry the exception, other chunks still deliver, and the
    scheduler serves the next flush.  Regression: a raise used to skip the
    future-resolution loop entirely, hanging cross-thread waiters forever."""
    orig = engine.run_chunk

    def flaky(params, seq_len, nfe, chunk, results, pad=True):
        if seq_len == 4:
            raise RuntimeError("injected chunk failure")
        return orig(params, seq_len, nfe, chunk, results, pad=pad)

    monkeypatch.setattr(engine, "run_chunk", flaky)
    queue = AsyncBatchedSampler(engine, None)
    bad_fut = queue.submit(SampleRequest(batch=1, seq_len=4, nfe=8, seed=0))
    good_fut = queue.submit(SampleRequest(batch=1, seq_len=6, nfe=8, seed=1))
    assert queue.flush() == 2  # the failure is delivered, not raised
    with pytest.raises(RuntimeError, match="injected"):
        bad_fut.result(timeout=0)
    assert good_fut.result(timeout=0).x0.shape == (1, 6, D_MODEL)
    assert queue.pending == 0
    # the failed chunk leaves the scheduler usable
    late = queue.submit(SampleRequest(batch=1, seq_len=6, nfe=8, seed=2))
    queue.flush()
    assert late.result(timeout=0).x0.shape == (1, 6, D_MODEL)


def test_shared_delta_config_not_fused(analytic):
    """Paper-default (shared delta_eps) configs couple the batch through one
    global error norm, so the engine must serve them unfused and unpadded —
    each request's result matches a solo run of exactly that request."""
    eng = FusedExecutor(
        OracleDenoiser(analytic),
        analytic.schedule,
        solver_config=ERAConfig(per_sample=False),
        batch_buckets=(8,),
    )
    r1, r2 = run_flush(
        eng,
        [
            SampleRequest(batch=2, seq_len=6, nfe=10, seed=11),
            SampleRequest(batch=1, seq_len=6, nfe=10, seed=12),
        ],
    )
    assert r1.padded_batch == 2  # exact size, no pad, no fusion
    assert r2.padded_batch == 1
    for seed, res, batch in ((11, r1, 2), (12, r2, 1)):
        x_init = jax.random.normal(
            jax.random.PRNGKey(seed), (batch, 6, D_MODEL), jnp.float32
        )
        solo = get_solver("era")(
            analytic.eps, x_init, analytic.schedule, ERAConfig(nfe=10)
        )
        np.testing.assert_allclose(
            np.asarray(res.x0), np.asarray(solo.x0), atol=1e-5
        )


def test_padding_rows_do_not_leak(engine, analytic):
    """A request fused with pad rows equals the same request run alone."""
    req = SampleRequest(batch=1, seq_len=6, nfe=8, seed=7)
    (padded,) = run_flush(engine, [req])
    assert padded.padded_batch == 2
    solo_engine = FusedExecutor(
        OracleDenoiser(analytic), analytic.schedule, batch_buckets=None
    )
    (solo,) = run_flush(solo_engine, [req])
    assert solo.padded_batch == 1
    np.testing.assert_allclose(
        np.asarray(padded.x0), np.asarray(solo.x0), atol=1e-5
    )


@pytest.mark.parametrize("bucket", [8, 64])
def test_padding_invariance_at_serving_buckets(bucket, analytic):
    """Flushed results are identical whether a request's group was padded
    up to the serving bucket (8 or 64) or run exact-size — the pad rows are
    inert for every real row."""
    padded_eng = FusedExecutor(
        OracleDenoiser(analytic), analytic.schedule, batch_buckets=(bucket,)
    )
    exact_eng = FusedExecutor(
        OracleDenoiser(analytic), analytic.schedule, batch_buckets=None
    )
    reqs = [
        SampleRequest(batch=b, seq_len=6, nfe=6, seed=s)
        for b, s in [(2, 21), (3, 22)]  # 5 rows -> 3 or 61 pad rows
    ]
    res_p = run_flush(padded_eng, reqs)
    res_e = run_flush(exact_eng, reqs)
    for req, rp, re_ in zip(reqs, res_p, res_e):
        assert rp.padded_batch == bucket
        assert re_.padded_batch == 5  # the fused exact group
        assert rp.x0.shape == (req.batch, 6, D_MODEL)
        np.testing.assert_allclose(
            np.asarray(rp.x0),
            np.asarray(re_.x0),
            atol=1e-6,
        )


# ---------------------------------------------------------------------------
# mesh-sharded execution (parity with the single-device engine on 8
# virtual CPU devices)
# ---------------------------------------------------------------------------


def test_shared_delta_on_mesh_rejects_non_dp_batches(mesh8, analytic):
    """Shared-delta (per_sample=False) requests run exact-size — padding
    would change the global error norm — so on a mesh their batch must be a
    dp multiple.  Regression: this used to bypass dp rounding and silently
    degrade the whole batch to replicated placement."""
    eng = FusedExecutor(
        OracleDenoiser(analytic),
        analytic.schedule,
        solver_config=ERAConfig(per_sample=False),
        batch_buckets=(8,),
        mesh=mesh8,
    )
    queue = AsyncBatchedSampler(eng, None)
    with pytest.raises(ValueError, match="data-parallel"):
        queue.submit(SampleRequest(batch=3, seq_len=6, nfe=10, seed=0))
    assert queue.pending == 0  # the rejected request never queued

    # a dp-multiple batch is accepted, runs exact-size AND sharded, and
    # matches the single-device engine
    req = SampleRequest(batch=8, seq_len=6, nfe=10, seed=1)
    fut = queue.submit(req)
    queue.flush()
    res = fut.result(timeout=0)
    assert res.padded_batch == 8
    assert len(res.x0.sharding.device_set) == 8  # not replicated
    solo = FusedExecutor(
        OracleDenoiser(analytic),
        analytic.schedule,
        solver_config=ERAConfig(per_sample=False),
        batch_buckets=None,
    )
    np.testing.assert_allclose(
        np.asarray(res.x0),
        np.asarray(run_flush(solo, [req])[0].x0),
        atol=1e-5,
    )


def test_shared_delta_off_mesh_accepts_any_batch(analytic):
    """dp=1 (no mesh): every batch is a dp multiple, nothing is rejected."""
    eng = FusedExecutor(
        OracleDenoiser(analytic),
        analytic.schedule,
        solver_config=ERAConfig(per_sample=False),
        batch_buckets=(8,),
    )
    (res,) = run_flush(eng, [SampleRequest(batch=3, seq_len=6, nfe=10, seed=0)])
    assert res.x0.shape == (3, 6, D_MODEL)


@pytest.mark.parametrize("solver", ["dpm_solver_pp2m"])
def test_non_era_mesh_drain_parity_with_single_device(mesh8, analytic, solver):
    """Every program (not just ERA) gets mesh-sharded fused batches — an
    8-way mesh run of a non-ERA solver matches the single-device engine,
    with the batch genuinely spread over the mesh."""
    meshed = FusedExecutor(
        OracleDenoiser(analytic), analytic.schedule, mesh=mesh8
    )
    single = FusedExecutor(
        OracleDenoiser(analytic), analytic.schedule, batch_buckets=None
    )
    reqs = [
        SampleRequest(batch=b, seq_len=6, nfe=8, solver=solver, seed=s)
        for b, s in [(1, 3), (3, 4), (4, 5)]  # 8 rows: one dp-rounded bucket
    ]
    res_m = run_flush(meshed, reqs)
    res_s = run_flush(single, reqs)
    for rm, rs in zip(res_m, res_s):
        np.testing.assert_allclose(
            np.asarray(rm.x0), np.asarray(rs.x0), atol=1e-5
        )
    assert res_m[0].padded_batch == 8
    full = SampleRequest(batch=8, seq_len=6, nfe=8, solver=solver, seed=9)
    x0 = run_flush(meshed, [full])[0].x0
    assert len(x0.sharding.device_set) == 8  # sharded, not replicated


def test_mesh_drain_parity_with_single_device_engine():
    """8-device mesh run == single-device run within 1e-5, with batch
    buckets rounded to dp multiples and rows spread over all devices.

    Runs in-process when launched under
    XLA_FLAGS=--xla_force_host_platform_device_count=8 (the CI sharded
    job); otherwise re-runs itself in a flagged subprocess, so the parity
    wall holds in default single-device collection too."""
    if jax.device_count() >= 8:
        import _mesh_parity_main

        rec = _mesh_parity_main.run_parity()
    else:
        rec = run_mesh_subprocess("_mesh_parity_main.py")
    assert rec["devices"] >= 8  # make_sampler_mesh(8) caps bigger hosts
    assert rec["dp"] == 8
    assert rec["buckets"] == [8, 64]      # 1/8/64 dp-rounded
    assert rec["padded_batch"] == 8       # 6 mixed rows pad to the 8-bucket
    assert rec["padded_batch"] % rec["dp"] == 0
    assert rec["x0_devices"] == 8         # batch really spread over the mesh
    assert rec["max_diff"] <= 1e-5
