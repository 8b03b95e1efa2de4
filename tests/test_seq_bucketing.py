"""Padding-invariance wall for mixed-seq-len fusion (seq bucketing).

The serving contract: with ``seq_buckets`` configured, requests whose
``seq_len`` differ fuse into one compiled batch — each request's rows are
right-padded to the smallest bucket that fits, the denoiser masks pad keys,
and the solver masks its sequence reductions — and a request's ``x0`` and
per-sample ERS basis selections are **bit-identical** to its exact-shape
solo run.  What makes the bitwise claim hold (not just "close"): the
denoiser's pad-key attention bias adds exact ``0.0`` to valid scores, and
ERA's error norms reduce features at fixed per-position shape and then
accumulate positions with a strictly sequential scan, so zero-masked pad
positions append exact ``acc + 0.0`` no-ops instead of re-associating the
reduction (see ``era._seq_sq_sums``).

Also walled here: the compile count is bounded by the bucket ladder (not by
distinct seq_lens), over-ladder requests are rejected at submit with an
actionable message, ``padded_seq_len`` is surfaced through results and
their ``info`` dict, unmaskable denoisers / non-fusable configs fall back to
exact-shape grouping, and the mesh8 mixed-length run matches.
"""

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from conftest import AnalyticGaussian, OracleDenoiser, run_flush
from repro.core import ERAConfig
from repro.serving import (
    AsyncBatchedSampler,
    FusedExecutor,
    SampleRequest,
    result_keys as K,
)

# module-level: the shim's `given` produces zero-arg tests, so no fixtures
ANALYTIC = AnalyticGaussian()

SEQ_BUCKETS = (4, 8)


def _bucketed_engine(mesh=None, seq_buckets=SEQ_BUCKETS, **kw):
    return FusedExecutor(
        OracleDenoiser(ANALYTIC),
        ANALYTIC.schedule,
        batch_buckets=(2, 4, 8),
        seq_buckets=seq_buckets,
        mesh=mesh,
        **kw,
    )


def _exact_engine(mesh=None):
    return FusedExecutor(
        OracleDenoiser(ANALYTIC),
        ANALYTIC.schedule,
        batch_buckets=None,
        mesh=mesh,
    )


def _solo(req, mesh=None):
    """Exact-shape solo run of one request (no seq bucketing anywhere)."""
    return run_flush(_exact_engine(mesh=mesh), [req])[0]


@settings(max_examples=4, deadline=None)
@given(
    st.integers(min_value=2, max_value=4),       # co-arriving requests
    st.integers(min_value=1, max_value=8),       # first request's seq_len
    st.integers(min_value=0, max_value=3),       # nfe headroom above k=4
    st.integers(min_value=0, max_value=10_000),  # request seed base
)
def test_padding_invariance_bitwise(n, seq0, extra, seed0):
    """A request padded from L to bucket(L) inside a fused mixed-length
    batch produces bit-identical x0, delta_eps history, and ERS basis
    selections to its exact-shape solo run."""
    nfe = 5 + extra
    # a mix of lengths that spans both buckets and hits the bucket edges
    lens = [(seq0 + 3 * i) % 8 + 1 for i in range(n)]
    reqs = [
        SampleRequest(batch=1 + (i % 2), seq_len=lens[i], nfe=nfe,
                      seed=seed0 + i)
        for i in range(n)
    ]
    fused = run_flush(_bucketed_engine(), reqs)
    for got, req in zip(fused, reqs):
        ref = _solo(req)
        assert got.x0.shape == (req.batch, req.seq_len,
                                OracleDenoiser.D_MODEL)
        np.testing.assert_array_equal(
            np.asarray(got.x0), np.asarray(ref.x0),
            err_msg=f"x0 diverged for seq_len={req.seq_len} "
            f"(padded to {got.padded_seq_len}, seed={req.seed})",
        )
        np.testing.assert_array_equal(
            np.asarray(got.aux["ers_selection_history"]),
            np.asarray(ref.aux["ers_selection_history"]),
            err_msg=f"ERS basis selection flipped under padding "
            f"(seq_len={req.seq_len} -> {got.padded_seq_len})",
        )
        np.testing.assert_array_equal(
            np.asarray(got.aux["delta_eps_history_per_sample"]),
            np.asarray(ref.aux["delta_eps_history_per_sample"]),
            err_msg="per-sample delta_eps diverged under padding",
        )


def test_mixed_lengths_fuse_into_one_chunk_per_bucket():
    """Distinct seq_lens inside one bucket share a fused batch and one
    compiled program; the jit cache is keyed by the ladder."""
    engine = _bucketed_engine()
    reqs = [
        SampleRequest(batch=1, seq_len=L, nfe=6, seed=10 + i)
        for i, L in enumerate([1, 3, 4, 2])  # all bucket to 4
    ]
    for res in run_flush(engine, reqs):
        assert res.padded_seq_len == 4
        assert res.padded_batch == 4  # one fused chunk of 4 rows
    keys = set(engine.compile_cache())
    assert len(keys) == 1
    (key,) = keys
    assert key[3] == 4 and key[5] is True  # (.., seq_bucket, dp, masked)

    # a second wave spanning both buckets: seq keys stay on the ladder
    more = [
        SampleRequest(batch=1, seq_len=L, nfe=6, seed=50 + i)
        for i, L in enumerate([2, 4, 6, 8, 5])
    ]
    results = run_flush(engine, more)
    assert {r.padded_seq_len for r in results} == {4, 8}
    assert {k[3] for k in engine.compile_cache()} <= set(SEQ_BUCKETS)
    compiled = len(engine.compile_cache())

    # a third wave of previously-unseen lengths that lands on the same
    # (batch bucket, seq bucket) compositions compiles nothing new — the
    # cache is bounded by the ladder, not by distinct seq_lens
    third = [
        SampleRequest(batch=1, seq_len=L, nfe=6, seed=80 + i)
        for i, L in enumerate([1, 2, 5, 6, 7, 8])
    ]
    run_flush(engine, third)
    assert len(engine.compile_cache()) == compiled


def test_seq_len_above_ladder_rejected_at_submit():
    engine = _bucketed_engine()
    with pytest.raises(ValueError, match="exceeds the largest seq bucket"):
        engine.validate(SampleRequest(batch=1, seq_len=9, nfe=6))
    # the scheduler rejects at submit (same validate path)
    sched = AsyncBatchedSampler(engine, params=None)
    with pytest.raises(ValueError, match="exceeds the largest seq bucket"):
        sched.submit(SampleRequest(batch=1, seq_len=64, nfe=6))
    sched.stop()
    # engines without a ladder accept any length
    sched = AsyncBatchedSampler(_exact_engine(), params=None)
    sched.submit(SampleRequest(batch=1, seq_len=64, nfe=6))
    assert sched.pending == 1


def test_padded_seq_len_surfaced_in_results_and_facade_info():
    req = SampleRequest(batch=1, seq_len=3, nfe=6, seed=1)
    (res,) = run_flush(_bucketed_engine(), [req])
    assert res.padded_seq_len == 4
    assert res.padded_batch >= 1

    (res,) = run_flush(
        _exact_engine(), [SampleRequest(batch=2, seq_len=6, nfe=6)]
    )
    assert res.info[K.PADDED_SEQ_LEN] == 6  # no ladder: exact shape
    assert res.info[K.PADDED_BATCH] == 2
    assert res.x0.shape == (2, 6, OracleDenoiser.D_MODEL)


def test_unmaskable_denoiser_falls_back_to_exact_shape():
    """A denoiser that cannot guarantee masked parity serves exact-shape
    groups even when a ladder is configured."""
    dlm = OracleDenoiser(ANALYTIC)
    dlm.supports_length_masking = False
    engine = FusedExecutor(
        dlm, ANALYTIC.schedule, batch_buckets=(2, 4),
        seq_buckets=SEQ_BUCKETS,
    )
    assert engine.seq_masked("era") is False
    assert engine.group_key(
        SampleRequest(batch=1, seq_len=3, nfe=6)
    ) == ("era", 3, 6)
    sched = AsyncBatchedSampler(engine, params=None)
    fut = sched.submit(SampleRequest(batch=1, seq_len=3, nfe=6, seed=0))
    sched.flush()
    assert fut.result().padded_seq_len == 3  # exact shape, no masking
    # the ladder still bounds accepted lengths (serving contract)
    with pytest.raises(ValueError, match="exceeds the largest seq bucket"):
        sched.submit(SampleRequest(batch=1, seq_len=99, nfe=6))


def test_non_fusable_config_falls_back_to_exact_shape():
    """Shared-delta ERA couples rows through one error norm — it cannot pad
    (rows or positions), so its traffic groups by exact seq_len."""
    engine = FusedExecutor(
        OracleDenoiser(ANALYTIC),
        ANALYTIC.schedule,
        solver_config=ERAConfig(per_sample=False),
        batch_buckets=(2, 4),
        seq_buckets=SEQ_BUCKETS,
    )
    assert engine.seq_masked("era") is False
    assert engine.group_key(
        SampleRequest(batch=2, seq_len=5, nfe=6)
    ) == ("era", 5, 6)


def test_trajectory_aux_sliced_to_request_seq_len():
    engine = FusedExecutor(
        OracleDenoiser(ANALYTIC),
        ANALYTIC.schedule,
        solver_config=ERAConfig(per_sample=True, return_trajectory=True),
        batch_buckets=(4,),
        seq_buckets=SEQ_BUCKETS,
    )
    ra, rb = run_flush(engine, [
        SampleRequest(batch=1, seq_len=3, nfe=6, seed=0),
        SampleRequest(batch=2, seq_len=7, nfe=6, seed=1),
    ])
    assert ra.aux["trajectory"].shape == (
        7, 1, 3, OracleDenoiser.D_MODEL
    )
    assert rb.aux["trajectory"].shape == (
        7, 2, 7, OracleDenoiser.D_MODEL
    )
    # per-sample aux keeps per-request rows only
    assert rb.aux["ers_selection_history"].shape[1] == 2


def test_mixed_solver_mixed_length_routing():
    """Seq bucketing composes with per-request solver routing: groups key
    on (solver, bucket, nfe), and every solver's padded run matches its
    exact-shape solo run bitwise."""
    engine = _bucketed_engine()
    reqs = [
        SampleRequest(batch=1, seq_len=L, nfe=6, solver=s, seed=500 + i)
        for i, (L, s) in enumerate(
            [(3, None), (5, "ddim"), (2, "dpm_solver_pp2m"),
             (4, "ddim"), (7, None)]
        )
    ]
    fused = run_flush(engine, reqs)
    for got, req in zip(fused, reqs):
        ref = _solo(req)
        np.testing.assert_array_equal(
            np.asarray(got.x0), np.asarray(ref.x0),
            err_msg=f"solver={req.solver} seq_len={req.seq_len}",
        )
    solvers_compiled = {k[0] for k in engine.compile_cache()}
    assert solvers_compiled == {"era", "ddim", "dpm_solver_pp2m"}


def test_denoiser_length_mask_parity_real_attention():
    """The DiffusionLM masking contract on a real dense-attention stack:
    valid positions of a masked padded batch reproduce the exact-shape
    eps, and pad positions come back exactly zero."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import build_model
    from repro.models.diffusion import DiffusionLM

    cfg = get_config("llama3.2-1b", smoke=True)
    dlm = DiffusionLM(build_model(cfg))
    assert dlm.supports_length_masking
    params = dlm.init(jax.random.PRNGKey(0))
    b, l_exact, l_pad = 3, 5, 8
    x = jax.random.normal(jax.random.PRNGKey(1), (b, l_exact, cfg.d_model))
    xp = jnp.concatenate(
        [x, jnp.zeros((b, l_pad - l_exact, cfg.d_model))], axis=1
    )
    t = jnp.float32(0.7)
    e_exact = np.asarray(dlm.eps(params, x, t))
    e_mask = np.asarray(
        dlm.eps(params, xp, t, lengths=jnp.full((b,), l_exact, jnp.int32))
    )
    np.testing.assert_allclose(
        e_mask[:, :l_exact], e_exact, atol=1e-6,
        err_msg="masked padded eps diverged from exact-shape eps",
    )
    assert (e_mask[:, l_exact:] == 0.0).all()

    # SSM / MLA stacks are maskable too: directional scans are right-pad
    # prefix-safe and MLA threads the kv mask (tests/test_prefix_safety.py)
    for name in ("xlstm-350m", "hymba-1.5b", "deepseek-v2-lite-16b"):
        cfg2 = get_config(name, smoke=True)
        assert DiffusionLM(build_model(cfg2)).supports_length_masking, name


def _real_dlm_engine(arch: str):
    import jax

    from repro.configs import get_config
    from repro.core import linear_schedule
    from repro.models import build_model
    from repro.models.diffusion import DiffusionLM

    cfg = get_config(arch, smoke=True)
    dlm = DiffusionLM(build_model(cfg))
    params = dlm.init(jax.random.PRNGKey(0))
    schedule = linear_schedule()
    engine = FusedExecutor(
        dlm, schedule, batch_buckets=(2, 4), seq_buckets=SEQ_BUCKETS
    )
    exact = FusedExecutor(dlm, schedule, batch_buckets=None)
    return engine, exact, params


@pytest.mark.parametrize("arch", ["xlstm-350m", "deepseek-v2-lite-16b"])
def test_real_denoiser_padding_invariance_wall(arch):
    """The full padding-invariance wall (x0 + per-sample ERS selections) on
    real SSM (xlstm) and MLA (deepseek-v2-lite) DiffusionLM stacks — the
    block kinds PR 5 excluded from fusion.  A mixed-length fused batch must
    match each request's exact-shape solo run at the real-denoiser parity
    bar (atol=1e-6; observed bit-identical on CPU smoke shapes), with
    bitwise-identical ERS basis selections."""
    engine, exact, params = _real_dlm_engine(arch)
    assert engine.seq_masked("era") is True
    reqs = [
        SampleRequest(batch=1, seq_len=L, nfe=5, seed=700 + i)
        for i, L in enumerate([3, 8, 5])
    ]
    fused = run_flush(engine, reqs, params)
    for got, req in zip(fused, reqs):
        assert got.padded_seq_len == (4 if req.seq_len <= 4 else 8)
        (ref,) = run_flush(exact, [req], params)
        np.testing.assert_allclose(
            np.asarray(got.x0), np.asarray(ref.x0), atol=1e-6,
            err_msg=f"{arch}: fused padded x0 diverged from exact-shape "
            f"solo run (seq_len={req.seq_len})",
        )
        np.testing.assert_array_equal(
            np.asarray(got.aux["ers_selection_history"]),
            np.asarray(ref.aux["ers_selection_history"]),
            err_msg=f"{arch}: ERS basis selection flipped under padding "
            f"(seq_len={req.seq_len})",
        )
    # the canary: a fully-maskable stack runs masked fused traffic with
    # zero fast-path fallbacks
    counter = engine.metrics.get("sampler_masked_fallback_total")
    assert counter is not None
    assert not counter._values, dict(counter._values)


def test_masked_fallback_counter_counts_engine_fallbacks():
    """An unmaskable denoiser's exact-shape verdict increments the
    ``sampler_masked_fallback_total`` canary with the engine label."""
    dlm = OracleDenoiser(ANALYTIC)
    dlm.supports_length_masking = False
    engine = FusedExecutor(
        dlm, ANALYTIC.schedule, batch_buckets=(2, 4), seq_buckets=SEQ_BUCKETS
    )
    assert engine.seq_masked("era") is False
    counter = engine.metrics.get("sampler_masked_fallback_total")
    assert counter.value(impl="seq-bucketing", reason="denoiser-unmaskable") == 1
    # the verdict is cached per solver: re-asking does not re-count
    assert engine.seq_masked("era") is False
    assert counter.value(impl="seq-bucketing", reason="denoiser-unmaskable") == 1


def test_mesh_mixed_length_drain_parity(mesh8):
    """Mixed-length fused batches on the 8-device mesh: bit-identical to
    the mesh exact-shape runs, and matching the single-device bucketed run
    to float tolerance (the established mesh-parity bar)."""
    reqs = [
        SampleRequest(batch=1, seq_len=L, nfe=7, seed=900 + i)
        for i, L in enumerate([2, 5, 8, 3, 6])
    ]
    fused = run_flush(_bucketed_engine(mesh=mesh8), reqs)
    sres = run_flush(_bucketed_engine(), reqs)
    for got, single, req in zip(fused, sres, reqs):
        ref = _solo(req, mesh=mesh8)
        np.testing.assert_array_equal(
            np.asarray(got.x0), np.asarray(ref.x0),
            err_msg=f"mesh bucketed vs mesh exact diverged "
            f"(seq_len={req.seq_len})",
        )
        np.testing.assert_allclose(
            np.asarray(got.x0), np.asarray(single.x0),
            atol=1e-5,
            err_msg=f"mesh vs single-device bucketed diverged "
            f"(seq_len={req.seq_len})",
        )
