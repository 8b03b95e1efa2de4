"""Padding-invariance wall for mixed-NFE fusion (NFE bucketing).

The serving contract: with ``nfe_buckets`` configured, requests whose
``nfe`` differ fuse into one compiled batch — the scan runs to the
bucket's step count and each request row carries its own step budget and
its own exact-NFE time grid through a per-row :class:`StepMask` — and a
request drained at its exact NFE (a ladder whose bucket equals its nfe:
every step active) is **bit-identical** to the same request right-padded
to a coarser bucket and co-fused with mixed-NFE batch-mates.  What makes
the bitwise claim hold (not just "close"): every row's active prefix
gathers the very same per-row time grid floats in both runs, and a spent
row's update is an exact ``jnp.where`` freeze of its whole carry —
latents, Lagrange eps history, ERS selection state — never a re-derived
value (see ``program.step_active`` / each program's step-masked scan).

Also walled here: the compile count is bounded by the nfe-bucket ladder
(not by distinct nfes), over-ladder requests are rejected at submit with
an actionable message, solvers without a step-masked scan (and
non-fusable configs) fall back to exact-NFE grouping on the
``sampler_masked_fallback_total`` canary, wasted pad step-rows are counted
on ``sampler_nfe_padding_rows_total``, step-stacked aux is scoped back to
each request's own step count, ``padded_nfe`` is surfaced through results
and the info dict, and the mesh8 mixed-NFE run matches.
"""

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from conftest import AnalyticGaussian, OracleDenoiser, run_flush
from repro.core import ERAConfig, solver_names
from repro.serving import (
    AsyncBatchedSampler,
    FusedExecutor,
    SampleRequest,
    result_keys as K,
)

# module-level: the shim's `given` produces zero-arg tests, so no fixtures
ANALYTIC = AnalyticGaussian()

SEQ_BUCKETS = (4, 8)

# solvers with a step-masked scan (SolverProgram.supports_steps) fuse
# across NFEs; the rest group by exact NFE.  The completeness test below
# forces every future registry solver to be classified here — and thereby
# through the padding-invariance wall.
STEPPED_SOLVERS = (
    "ddim",
    "dpm_adaptive",
    "dpm_solver_pp2m",
    "era",
    "explicit_adams",
    "implicit_adams_pece",
)
UNSTEPPED_SOLVERS = ("dpm_solver_2", "dpm_solver_fast")


def _engine(nfe_buckets, mesh=None, **kw):
    return FusedExecutor(
        OracleDenoiser(ANALYTIC),
        ANALYTIC.schedule,
        batch_buckets=(2, 4),
        seq_buckets=SEQ_BUCKETS,
        nfe_buckets=nfe_buckets,
        mesh=mesh,
        **kw,
    )


def _drain_one(engine, req, mates=()):
    return run_flush(engine, [req, *mates])[0]


@settings(max_examples=2, deadline=None)
@given(
    st.integers(min_value=8, max_value=16),      # request nfe
    st.integers(min_value=1, max_value=8),       # request seq_len
    st.integers(min_value=0, max_value=10_000),  # request seed base
)
def test_nfe_padding_invariance_bitwise(nfe, seq0, seed0):
    """For every step-masked solver: a request drained at its exact NFE
    vs. right-padded to a coarser NFE bucket (co-fused with a batch-mate
    at a different nfe) yields bit-identical x0, per-sample delta_eps
    histories, and ERA basis selections."""
    for solver in STEPPED_SOLVERS:
        req = SampleRequest(
            batch=1, seq_len=seq0, nfe=nfe, solver=solver, seed=seed0
        )
        # reference: exact-NFE drain — a ladder whose bucket == nfe, so
        # the step-masked scan runs with every step active
        ref = _drain_one(_engine((nfe, nfe + 40)), req)
        assert ref.padded_nfe == nfe
        # padded: a coarser ladder right-pads the request's steps, fused
        # with a mate at a different nfe (same bucket) so the chunk is a
        # genuinely mixed-NFE batch.  The mate keeps both runs on the same
        # batch bucket — the bitwise contract holds between step-masked
        # runs of the same compiled batch shape (different batch shapes
        # may vectorize the schedule transcendentals differently)
        mate = SampleRequest(
            batch=1, seq_len=seq0, nfe=nfe + 3, solver=solver,
            seed=seed0 + 1,
        )
        got = _drain_one(_engine((nfe + 7, nfe + 40)), req, mates=(mate,))
        assert got.padded_nfe == nfe + 7
        assert got.info[K.PADDED_NFE] == nfe + 7
        np.testing.assert_array_equal(
            np.asarray(got.x0), np.asarray(ref.x0),
            err_msg=f"x0 diverged under NFE padding (solver={solver}, "
            f"nfe={nfe} -> bucket {got.padded_nfe}, seed={seed0})",
        )
        if solver == "era":
            np.testing.assert_array_equal(
                np.asarray(got.aux["ers_selection_history"]),
                np.asarray(ref.aux["ers_selection_history"]),
                err_msg=f"ERS basis selection flipped under NFE padding "
                f"(nfe={nfe} -> bucket {got.padded_nfe})",
            )
            np.testing.assert_array_equal(
                np.asarray(got.aux["delta_eps_history_per_sample"]),
                np.asarray(ref.aux["delta_eps_history_per_sample"]),
                err_msg="per-sample delta_eps diverged under NFE padding",
            )
        if solver == "dpm_adaptive":
            np.testing.assert_array_equal(
                np.asarray(got.aux["realized_nfe"]),
                np.asarray(ref.aux["realized_nfe"]),
                err_msg="adaptive realized NFE diverged under NFE padding",
            )


def test_every_registry_solver_is_classified():
    """Every registry solver is either step-masked (and walled by the
    invariance test above) or an explicit exact-NFE fallback — a new
    solver cannot ship unclassified."""
    assert set(STEPPED_SOLVERS) | set(UNSTEPPED_SOLVERS) == set(
        solver_names()
    )
    engine = _engine((8, 16))
    for s in STEPPED_SOLVERS:
        assert engine.nfe_masked(s) is True, s
    for s in UNSTEPPED_SOLVERS:
        assert engine.nfe_masked(s) is False, s


def test_unstepped_solver_falls_back_to_exact_nfe():
    """A solver without a step-masked scan groups by exact NFE on a
    laddered engine — bit-identical to the ladder-free engine — and its
    verdict is counted once on the fallback canary."""
    engine = _engine((12, 25))
    for solver in UNSTEPPED_SOLVERS:
        assert engine.nfe_masked(solver) is False
        req = SampleRequest(
            batch=1, seq_len=5, nfe=10, solver=solver, seed=77
        )
        assert engine.group_key(req) == (solver, 8, 10)
        got = _drain_one(engine, req)
        assert got.padded_nfe == 10  # exact, not a ladder bucket
        ref = _drain_one(_engine(None), req)
        np.testing.assert_array_equal(
            np.asarray(got.x0), np.asarray(ref.x0),
            err_msg=f"exact-NFE fallback diverged (solver={solver})",
        )
    counter = engine.metrics.get("sampler_masked_fallback_total")
    assert counter.value(
        impl="nfe-bucketing", reason="program-no-steps"
    ) == len(UNSTEPPED_SOLVERS)
    # the verdict is cached per solver: re-asking does not re-count
    assert engine.nfe_masked("dpm_solver_fast") is False
    assert counter.value(
        impl="nfe-bucketing", reason="program-no-steps"
    ) == len(UNSTEPPED_SOLVERS)


def test_shared_delta_era_falls_back_to_exact_nfe():
    """Shared-delta ERA (per_sample=False) cannot pad in steps any more
    than in rows: exact-NFE grouping, counted as non-fusable-config."""
    engine = FusedExecutor(
        OracleDenoiser(ANALYTIC),
        ANALYTIC.schedule,
        solver_config=ERAConfig(nfe=6, k=3, per_sample=False),
        batch_buckets=(2, 4),
        nfe_buckets=(8, 16),
    )
    assert engine.nfe_masked("era") is False
    counter = engine.metrics.get("sampler_masked_fallback_total")
    assert counter.value(
        impl="nfe-bucketing", reason="non-fusable-config"
    ) == 1
    assert engine.group_key(
        SampleRequest(batch=1, seq_len=5, nfe=6)
    ) == ("era", 5, 6)


def test_mixed_nfes_fuse_into_one_chunk_per_bucket():
    """Distinct nfes inside one bucket share a fused batch and one
    compiled program; the jit cache is keyed by the ladder."""
    engine = _engine((8, 12))
    reqs = [
        SampleRequest(batch=1, seq_len=4, nfe=n, seed=10 + i)
        for i, n in enumerate([5, 7, 8, 6])  # all bucket to 8
    ]
    for res in run_flush(engine, reqs):
        assert res.padded_nfe == 8
        assert res.padded_batch == 4  # one fused chunk of 4 rows
    keys = set(engine.compile_cache())
    assert len(keys) == 1
    (key,) = keys
    # (solver, cfg, batch, seq, dp, masked, stepped): the cfg's nfe is the
    # group's bucket and the program is the step-masked variant
    assert key[1].nfe == 8 and key[6] is True

    # a second wave spanning both buckets: cfg nfes stay on the ladder
    more = [
        SampleRequest(batch=1, seq_len=4, nfe=n, seed=50 + i)
        for i, n in enumerate([6, 9, 12, 10])
    ]
    results = run_flush(engine, more)
    assert {r.padded_nfe for r in results} == {8, 12}
    assert {k[1].nfe for k in engine.compile_cache()} <= {8, 12}
    compiled = len(engine.compile_cache())

    # a third wave of previously-unseen nfes that lands on the same
    # (batch bucket, nfe bucket) compositions compiles nothing new — the
    # cache is bounded by the ladder, not by distinct nfes
    third = [
        SampleRequest(batch=1, seq_len=4, nfe=n, seed=80 + i)
        for i, n in enumerate([4, 5, 7, 6, 11, 9, 10])
    ]
    run_flush(engine, third)
    assert len(engine.compile_cache()) == compiled


def test_nfe_above_ladder_rejected_at_submit():
    engine = _engine((8, 12))
    with pytest.raises(ValueError, match="exceeds the largest nfe bucket"):
        engine.validate(SampleRequest(batch=1, seq_len=4, nfe=13))
    # the scheduler rejects at submit (same validate path)
    sched = AsyncBatchedSampler(engine, params=None)
    with pytest.raises(ValueError, match="exceeds the largest nfe bucket"):
        sched.submit(SampleRequest(batch=1, seq_len=4, nfe=40))
    sched.stop()
    # engines without a ladder accept the same nfe
    sched = AsyncBatchedSampler(_engine(None), params=None)
    sched.submit(SampleRequest(batch=1, seq_len=4, nfe=13))
    assert sched.pending == 1


def test_nfe_padding_rows_counter_counts_wasted_step_rows():
    """``sampler_nfe_padding_rows_total`` counts request rows that ran
    with padded (inert) steps — the ladder-tuning signal — and stays
    silent for traffic landing exactly on a bucket."""
    engine = _engine((8,))
    sched = AsyncBatchedSampler(engine, params=None)
    sched.submit(SampleRequest(batch=1, seq_len=4, nfe=5, seed=1))
    sched.submit(SampleRequest(batch=2, seq_len=4, nfe=8, seed=2))
    sched.flush()
    counter = engine.metrics.get("sampler_nfe_padding_rows_total")
    assert counter is not None
    # only the 5-NFE request's single row padded; the 8-NFE rows ran
    # exactly, and the batch pad row runs the full bucket grid by design
    assert counter.value(solver="era") == 1

    sched.submit(SampleRequest(batch=2, seq_len=4, nfe=8, seed=3))
    sched.flush()
    assert counter.value(solver="era") == 1  # fully-active batch: no-op


def test_step_stacked_aux_scoped_to_request_nfe():
    """Step-stacked aux (trajectory, ERS histories) drops the inert pad
    tail: a 5-NFE request fused into an 8-NFE bucket gets histories at
    its own step count, same as its unpadded run."""
    engine = FusedExecutor(
        OracleDenoiser(ANALYTIC),
        ANALYTIC.schedule,
        solver_config=ERAConfig(per_sample=True, return_trajectory=True),
        batch_buckets=(4,),
        seq_buckets=SEQ_BUCKETS,
        nfe_buckets=(8,),
    )
    ra, rb = run_flush(engine, [
        SampleRequest(batch=1, seq_len=3, nfe=5, seed=0),
        SampleRequest(batch=2, seq_len=7, nfe=8, seed=1),
    ])
    # trajectory: x_init + one entry per *own* step, not per bucket step
    assert ra.aux["trajectory"].shape == (
        6, 1, 3, OracleDenoiser.D_MODEL
    )
    assert rb.aux["trajectory"].shape == (
        9, 2, 7, OracleDenoiser.D_MODEL
    )
    assert ra.aux["ers_selection_history"].shape[0] == 5
    assert ra.aux["delta_eps_history_per_sample"].shape[0] == 5
    assert rb.aux["ers_selection_history"].shape[0] == 8


def test_mesh_mixed_nfe_drain_parity(mesh8):
    """Mixed-NFE fused batches on the 8-device mesh: bit-identical to the
    mesh exact-NFE-bucket runs, and matching the single-device bucketed
    run to float tolerance (the established mesh-parity bar)."""
    reqs = [
        SampleRequest(batch=1, seq_len=5, nfe=n, seed=900 + i)
        for i, n in enumerate([6, 10, 13])
    ]
    ladder = (16,)
    fused = run_flush(_engine(ladder, mesh=mesh8), reqs)
    sres = run_flush(_engine(ladder), reqs)
    for got, single, req in zip(fused, sres, reqs):
        # mesh reference: same request run at its exact NFE bucket
        ref = _drain_one(_engine((req.nfe, 16), mesh=mesh8), req)
        np.testing.assert_array_equal(
            np.asarray(got.x0), np.asarray(ref.x0),
            err_msg=f"mesh NFE-padded vs mesh exact-bucket diverged "
            f"(nfe={req.nfe})",
        )
        np.testing.assert_allclose(
            np.asarray(got.x0), np.asarray(single.x0),
            atol=1e-5,
            err_msg=f"mesh vs single-device bucketed diverged "
            f"(nfe={req.nfe})",
        )
