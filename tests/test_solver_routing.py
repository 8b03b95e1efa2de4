"""Per-request solver routing through the solver-agnostic serving stack.

PR-4 regression wall: ``SampleRequest.solver`` used to be accepted at
submit and silently ignored — every request ran the engine's default
solver.  After the solver-program refactor it routes: each request runs
its named registry solver's program, mixed-solver traffic batches per
solver (never cross-contaminating a fused bucket), unknown names are
rejected at ``submit()``, and each program's own ``validate`` enforces its
(batch, nfe) constraints with a solver-specific message.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import OracleDenoiser, run_flush
from repro.core import ERAConfig, default_config, get_solver
from repro.serving import (
    AsyncBatchedSampler,
    EngineConfig,
    FusedExecutor,
    SampleRequest,
    SchedulerPolicy,
    build_engine,
)

D_MODEL = OracleDenoiser.D_MODEL


@pytest.fixture()
def engine(analytic):
    return FusedExecutor(
        OracleDenoiser(analytic), analytic.schedule, batch_buckets=(2, 4, 8)
    )


def _x_init(seed, batch, seq_len=6):
    return jax.random.normal(
        jax.random.PRNGKey(seed), (batch, seq_len, D_MODEL), jnp.float32
    )


def _solo(analytic, solver, seed, batch, nfe, seq_len=6):
    """Reference run of one request through the engine-default config of
    its solver (per-sample ERS for era — the serving default)."""
    cfg = default_config(solver, nfe=nfe)
    if solver == "era":
        cfg = dataclasses.replace(cfg, per_sample=True)
    return get_solver(solver)(
        analytic.eps, _x_init(seed, batch, seq_len), analytic.schedule, cfg
    )


# ---------------------------------------------------------------------------
# routing (the satellite regression: req.solver used to be ignored)
# ---------------------------------------------------------------------------


def test_mixed_solver_requests_in_one_drain_route_correctly(engine, analytic):
    """Two requests with different ``solver`` fields in one flush() come
    back from *their own* solvers, not the engine default."""
    r_era, r_ddim, r_pp2m = run_flush(engine, [
        SampleRequest(batch=1, seq_len=6, nfe=8, seed=1),
        SampleRequest(batch=2, seq_len=6, nfe=8, solver="ddim", seed=2),
        SampleRequest(batch=1, seq_len=6, nfe=8, solver="dpm_solver_pp2m", seed=3),
    ])
    for res, solver, seed, batch in (
        (r_era, "era", 1, 1),
        (r_ddim, "ddim", 2, 2),
        (r_pp2m, "dpm_solver_pp2m", 3, 1),
    ):
        ref = _solo(analytic, solver, seed, batch, nfe=8)
        np.testing.assert_allclose(
            np.asarray(res.x0),
            np.asarray(ref.x0),
            atol=1e-5,
            err_msg=f"{solver} request did not route to {solver}",
        )
    # and the solvers genuinely differ (routing is observable)
    assert (
        np.max(
            np.abs(
                np.asarray(r_ddim.x0[:1])
                - np.asarray(r_pp2m.x0)
            )
        )
        > 1e-4
    )


def test_mixed_solver_requests_never_share_a_fused_chunk(
    engine, analytic, monkeypatch
):
    """Same shape, different solvers: the queue groups per solver, so each
    executed chunk is solver-homogeneous (no bucket cross-contamination)."""
    chunks = []
    orig = engine.run_chunk

    def recording(params, seq_len, nfe, chunk, results, pad=True):
        chunks.append({req.solver or "era" for _, req, _ in chunk})
        return orig(params, seq_len, nfe, chunk, results, pad=pad)

    monkeypatch.setattr(engine, "run_chunk", recording)
    run_flush(engine, [
        SampleRequest(batch=1, seq_len=6, nfe=8, solver=solver, seed=seed)
        for seed, solver in enumerate([None, "ddim", None, "ddim", "era"])
    ])
    assert len(chunks) == 2  # one era chunk (None+era), one ddim chunk
    for solvers in chunks:
        assert len(solvers) == 1


def test_unknown_solver_rejected_at_submit_not_drain(engine):
    with pytest.raises(ValueError, match="unknown solver"):
        engine.validate(
            SampleRequest(batch=1, seq_len=6, nfe=8, solver="nope")
        )


def test_unknown_solver_rejected_at_scheduler_submit(engine):
    sched = AsyncBatchedSampler(engine, params=None)
    with pytest.raises(ValueError, match="unknown solver"):
        sched.submit(SampleRequest(batch=1, seq_len=6, nfe=8, solver="nope"))
    assert sched.pending == 0  # nothing queued to poison a launch
    sched.stop()


def test_jit_cache_keys_carry_the_solver(engine):
    """Same (batch, seq_len, nfe) bucket, different solvers -> different
    compiled programs, each compiled once."""
    for solver in (None, "ddim", None, "ddim"):
        run_flush(engine, [
            SampleRequest(batch=1, seq_len=6, nfe=8, solver=solver, seed=0)
        ])
    cache = engine.compile_cache()
    assert sorted(k[0] for k in cache) == ["ddim", "era"]
    # entries are AOT-compiled executables: one entry == one compile; the
    # repeat submits above were memory hits, not recompiles
    for runner in cache.values():
        assert isinstance(runner, jax.stages.Compiled)
    stats = engine.compile_stats()
    assert stats["fresh"] + stats["disk"] == 2
    assert stats["memory"] == 2


def test_sampler_service_routes_request_solver(analytic):
    """An exact-size engine whose default is the paper's shared-delta ERA
    (the one-shot CLI mode's engine) serves a request naming a different
    solver — per-request routing reaches that path too."""
    engine = build_engine(
        OracleDenoiser(analytic),
        analytic.schedule,
        EngineConfig(per_sample=False, batch_buckets=None),
    )
    (res,) = run_flush(engine, [
        SampleRequest(batch=2, seq_len=6, nfe=8, solver="ddim", seed=5)
    ])
    x0 = res.x0
    ref = get_solver("ddim")(
        analytic.eps, _x_init(5, 2), analytic.schedule,
        default_config("ddim", nfe=8),
    )
    np.testing.assert_allclose(np.asarray(x0), np.asarray(ref.x0), atol=1e-5)


# ---------------------------------------------------------------------------
# per-program validate (the satellite: constraints moved out of the executor)
# ---------------------------------------------------------------------------


def test_era_validate_rejects_nfe_below_k(engine):
    with pytest.raises(ValueError, match="nfe >= k"):
        engine.validate(SampleRequest(batch=1, seq_len=6, nfe=3, solver="era"))


def test_pece_validate_rejects_sub_budget_nfe(engine):
    with pytest.raises(ValueError, match="2 NFE per PECE step"):
        engine.validate(
            SampleRequest(batch=1, seq_len=6, nfe=1, solver="implicit_adams_pece")
        )
    # nfe=2 (one PECE step) is the smallest legal budget
    (res,) = run_flush(engine, [
        SampleRequest(batch=1, seq_len=6, nfe=2, solver="implicit_adams_pece")
    ])
    assert res.x0.shape == (1, 6, D_MODEL)


def test_pp2m_validate_rejects_warmup_starved_nfe(engine):
    with pytest.raises(ValueError, match="order-1 warmup"):
        engine.validate(
            SampleRequest(batch=1, seq_len=6, nfe=1, solver="dpm_solver_pp2m")
        )


def test_batch_and_nfe_floor_validation(engine):
    with pytest.raises(ValueError, match="batch must be >= 1"):
        engine.validate(SampleRequest(batch=0, seq_len=6, nfe=8))
    with pytest.raises(ValueError, match="nfe must be >= 1"):
        engine.validate(SampleRequest(batch=1, seq_len=6, nfe=0, solver="ddim"))


def test_shared_delta_era_route_is_not_fusable(analytic):
    """A request routed to the engine's shared-delta ERA config still runs
    exact-size/unfused (program.fusable consults the routed config)."""
    eng = FusedExecutor(
        OracleDenoiser(analytic),
        analytic.schedule,
        solver_config=ERAConfig(per_sample=False),
        batch_buckets=(8,),
    )
    r1, r2 = run_flush(eng, [
        SampleRequest(batch=2, seq_len=6, nfe=10, solver="era", seed=1),
        SampleRequest(batch=1, seq_len=6, nfe=10, solver="ddim", seed=2),
    ])
    assert r1.padded_batch == 2  # exact size: era not fusable here
    assert r2.padded_batch == 8  # ddim stays fusable: pads to bucket


# ---------------------------------------------------------------------------
# scheduler: mixed-solver continuous batching
# ---------------------------------------------------------------------------


def test_scheduler_serves_mixed_solver_stream(engine, analytic):
    """A mixed era/ddim/dpm++2m stream through the async scheduler: every
    future resolves to its own solver's result."""
    stream = [
        ("era", 0), ("ddim", 1), ("dpm_solver_pp2m", 2),
        ("ddim", 3), ("era", 4), ("dpm_solver_pp2m", 5),
    ]
    with AsyncBatchedSampler(
        engine,
        params=None,
        policy=SchedulerPolicy(max_wait_ms=2.0, target_occupancy=0.5),
    ) as sched:
        futs = [
            sched.submit(
                SampleRequest(batch=1, seq_len=6, nfe=8, solver=s, seed=seed)
            )
            for s, seed in stream
        ]
        results = [f.result(timeout=120) for f in futs]
    for (solver, seed), res in zip(stream, results):
        ref = _solo(analytic, solver, seed, batch=1, nfe=8)
        np.testing.assert_allclose(
            np.asarray(res.x0), np.asarray(ref.x0), atol=1e-5,
            err_msg=f"scheduler misrouted {solver} seed={seed}",
        )


def test_no_era_special_cases_left_in_serving_layer():
    """Acceptance wall: the serving layer is solver-agnostic — no
    isinstance(..., ERAConfig) (or any ERAConfig import) survives in
    serving/."""
    import os

    import repro.serving as serving_pkg

    serving_dir = os.path.dirname(serving_pkg.__file__)
    for fname in os.listdir(serving_dir):
        if not fname.endswith(".py"):
            continue
        with open(os.path.join(serving_dir, fname)) as f:
            for line in f:
                code = line.split("#", 1)[0]
                assert not (
                    "isinstance" in code and "ERAConfig" in code
                ), f"{fname}: {line.strip()}"
                assert not (
                    "import" in code and "ERAConfig" in code
                ), f"{fname} still imports ERAConfig: {line.strip()}"
