"""Correctness wall for the PID-controlled adaptive DPM-Solver program.

``dpm_adaptive`` runs the k-diffusion-style accept/reject loop as one
fixed-shape ``lax.scan`` (the request's ``nfe`` is an eval *budget*, 2 per
iteration) with per-row early exit, so it serves through the fused engine
— and through NFE bucketing — like any fixed-grid solver.  Walled here:

* convergence — a loose-tolerance run lands near the tight-tolerance
  reference on the analytic oracle and on a seeded toy DiffusionLM, with
  error shrinking as rtol tightens;
* determinism — for a fixed seed the realized step count and x0 are
  bit-identical under jit, across repeated jit calls, and vs. eager
  (the lambda endpoints are pinned behind an optimization barrier so
  XLA's constant folder cannot flip threshold comparisons);
* monotone control — tightening rtol or atol never *decreases* any
  row's realized NFE (more rejects, smaller steps);
* serveability — ``validate`` rejects unserveable tolerance configs at
  submit (not at drain, where they would poison co-batched neighbours),
  and a wire request through the unchanged front door returns 200 with
  the per-row realized NFE in ``info``.
"""

import jax
import numpy as np
import pytest

from conftest import AnalyticGaussian, OracleDenoiser, run_flush
from repro.core import AdaptiveDPMConfig, get_solver
from repro.serving import (
    FrontDoorClient,
    FusedExecutor,
    SampleRequest,
    SchedulerPolicy,
    result_keys as K,
    serve_frontdoor,
)

ANALYTIC = AnalyticGaussian()

X_INIT = jax.random.normal(jax.random.PRNGKey(0), (2, 4))


def _run(cfg, x=X_INIT, eps=None):
    return get_solver("dpm_adaptive")(
        eps or ANALYTIC.eps, x, ANALYTIC.schedule, cfg
    )


def _tight_reference(x=X_INIT, eps=None):
    return _run(
        AdaptiveDPMConfig(nfe=300, rtol=1e-4, atol=1e-4), x=x, eps=eps
    )


def test_converges_to_tight_tolerance_reference_on_analytic_oracle():
    """The default-tolerance run lands near the tight-tolerance reference
    at a fraction of its budget, and tightening rtol closes the gap."""
    ref = _tight_reference()
    out = _run(AdaptiveDPMConfig(nfe=40))
    err = float(
        np.abs(np.asarray(out.x0) - np.asarray(ref.x0)).max()
    )
    assert err < 0.15, err  # observed ~8.6e-2 at rtol=0.05
    realized = np.asarray(out.aux["realized_nfe"])
    assert realized.shape == (2,)
    assert (realized <= 40).all() and (realized >= 2).all()
    assert (realized % 2 == 0).all()  # 2 evals per iteration, always
    # an order of magnitude tighter rtol: an order of magnitude closer
    out2 = _run(AdaptiveDPMConfig(nfe=200, rtol=0.005, atol=1e-4))
    err2 = float(
        np.abs(np.asarray(out2.x0) - np.asarray(ref.x0)).max()
    )
    assert err2 < 0.02, err2  # observed ~8.9e-3
    assert err2 < err


def test_converges_on_seeded_toy_diffusion_lm():
    from repro.configs import get_config
    from repro.models import build_model
    from repro.models.diffusion import DiffusionLM

    cfg = get_config("llama3.2-1b", smoke=True)
    dlm = DiffusionLM(build_model(cfg))
    params = dlm.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, cfg.d_model))

    def eps(xx, t):
        return dlm.eps(params, xx, t)

    ref = _tight_reference(x=x, eps=eps)
    out = _run(AdaptiveDPMConfig(nfe=200), x=x, eps=eps)
    err = float(np.abs(np.asarray(out.x0) - np.asarray(ref.x0)).max())
    assert err < 0.15, err  # observed ~5.5e-2 at rtol=0.05
    assert (
        np.asarray(out.aux["realized_nfe"])
        < np.asarray(ref.aux["realized_nfe"])
    ).all()


def test_realized_nfe_and_x0_deterministic_under_jit():
    """Fixed seed => fixed trajectory: repeated jit calls are bitwise
    identical, and the jitted run matches eager — realized step counts
    included (accept/reject must not flip under XLA's fusion choices)."""
    cfg = AdaptiveDPMConfig(nfe=40)

    @jax.jit
    def jf(xx):
        out = get_solver("dpm_adaptive")(
            ANALYTIC.eps, xx, ANALYTIC.schedule, cfg
        )
        return out.x0, out.aux["realized_nfe"]

    x1, r1 = jf(X_INIT)
    x2, r2 = jf(X_INIT)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    eager = _run(cfg)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(eager.x0))
    np.testing.assert_array_equal(
        np.asarray(r1), np.asarray(eager.aux["realized_nfe"])
    )


def test_tightening_tolerances_monotonically_raises_realized_nfe():
    """The controller honors rtol/atol monotonically: a tighter tolerance
    can only add rejects and shrink steps, so no row's realized NFE may
    drop.  (Budget is large enough that no run exhausts it.)"""
    prev = None
    for rtol in (0.5, 0.05, 0.005, 5e-4):
        out = _run(AdaptiveDPMConfig(nfe=200, rtol=rtol, atol=1e-4))
        realized = np.asarray(out.aux["realized_nfe"])
        assert (realized < 200).all()
        if prev is not None:
            assert (realized >= prev).all(), (rtol, realized, prev)
        prev = realized
    prev = None
    for atol in (0.5, 0.05, 0.005):
        out = _run(AdaptiveDPMConfig(nfe=200, rtol=1e-4, atol=atol))
        realized = np.asarray(out.aux["realized_nfe"])
        if prev is not None:
            assert (realized >= prev).all(), (atol, realized, prev)
        prev = realized


def _engine(config=None, **kw):
    kw.setdefault("batch_buckets", (2,))
    return FusedExecutor(
        OracleDenoiser(ANALYTIC),
        ANALYTIC.schedule,
        solver="dpm_adaptive",
        solver_config=config,
        **kw,
    )


def test_validate_rejects_unserveable_configs_at_submit():
    req = SampleRequest(batch=1, seq_len=4, nfe=12)
    with pytest.raises(ValueError, match="budget must be >= 2"):
        _engine().validate(SampleRequest(batch=1, seq_len=4, nfe=1))
    with pytest.raises(ValueError, match="must be positive"):
        _engine(AdaptiveDPMConfig(rtol=-0.1)).validate(req)
    with pytest.raises(ValueError, match="below the serveable floor"):
        _engine(AdaptiveDPMConfig(rtol=1e-6, atol=1e-6)).validate(req)
    with pytest.raises(ValueError, match="limiter ceiling"):
        _engine(AdaptiveDPMConfig(accept_safety=2.8)).validate(req)
    # the floor is per-pair: one serveable tolerance is enough
    _engine(AdaptiveDPMConfig(rtol=1e-6, atol=0.01)).validate(req)


def test_adaptive_serves_mixed_budgets_under_nfe_bucketing():
    """Mixed adaptive budgets fuse into one bucketed chunk; every request
    reports its own realized NFE, capped by its own budget — not the
    bucket's."""
    engine = _engine(batch_buckets=(2, 4), nfe_buckets=(32,))
    ra, rb = run_flush(engine, [
        SampleRequest(batch=1, seq_len=4, nfe=10, seed=1),
        SampleRequest(batch=2, seq_len=4, nfe=25, seed=2),
    ])
    assert ra.padded_nfe == 32
    for res, budget, rows in ((ra, 10, 1), (rb, 25, 2)):
        realized = np.asarray(res.aux["realized_nfe"])
        assert realized.shape == (rows,)
        assert (realized >= 2).all() and (realized <= budget).all()
        assert res.info[K.REALIZED_NFE] is res.aux["realized_nfe"]


def test_adaptive_serves_through_front_door_with_realized_nfe():
    """The acceptance check: an adaptive request through the unchanged
    front door returns 200 with the per-row realized NFE in ``info``,
    bit-identical to the in-process flush."""
    door = serve_frontdoor(
        _engine(nfe_buckets=(16,)), params=None,
        policy=SchedulerPolicy(max_wait_ms=5.0),
    )
    try:
        req = SampleRequest(batch=1, seq_len=4, nfe=12, seed=3)
        wire = FrontDoorClient(door.url, timeout=60).sample(req)
    finally:
        door.stop()
    realized = np.asarray(wire.info[K.REALIZED_NFE])
    assert realized.shape == (1,)
    assert 2 <= int(realized[0]) <= 12 and int(realized[0]) % 2 == 0
    assert wire.info[K.PADDED_NFE] == 16

    (local,) = run_flush(_engine(nfe_buckets=(16,)), [req])
    np.testing.assert_array_equal(np.asarray(local.x0), wire.x0)
    np.testing.assert_array_equal(
        np.asarray(local.aux["realized_nfe"]), realized
    )
