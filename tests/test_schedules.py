import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import cosine_schedule, linear_schedule, timesteps

SCHEDULES = [linear_schedule(), cosine_schedule()]


@pytest.mark.parametrize("sched", SCHEDULES, ids=lambda s: s.name)
def test_vp_identity(sched):
    t = jnp.linspace(1e-4, 1.0, 101)
    a, s = sched.alpha(t), sched.sigma(t)
    np.testing.assert_allclose(a * a + s * s, 1.0, atol=1e-5)


@pytest.mark.parametrize("sched", SCHEDULES, ids=lambda s: s.name)
def test_monotone(sched):
    t = jnp.linspace(1e-4, 1.0, 200)
    assert np.all(np.diff(np.asarray(sched.alpha(t))) <= 1e-6)
    assert np.all(np.diff(np.asarray(sched.sigma(t))) >= -1e-6)
    assert np.all(np.diff(np.asarray(sched.lam(t))) < 0)


@settings(max_examples=25, deadline=None)
@given(st.floats(1e-3, 0.999))
def test_linear_inv_lam_roundtrip(t):
    sched = linear_schedule()
    lam = sched.lam(jnp.float32(t))
    t2 = sched.inv_lam(lam)
    assert abs(float(t2) - t) < 1e-3


def test_cosine_inv_lam_bisection():
    sched = cosine_schedule()
    for t in (0.05, 0.3, 0.9):
        lam = sched.lam(jnp.float32(t))
        assert abs(float(sched.inv_lam(lam)) - t) < 1e-3


@pytest.mark.parametrize("scheme", ["uniform", "quadratic", "logsnr"])
def test_timestep_grids(scheme):
    sched = linear_schedule()
    ts = np.asarray(timesteps(sched, 17, scheme))
    assert ts.shape == (18,)
    assert abs(ts[0] - sched.t_begin) < 1e-5
    assert abs(ts[-1] - sched.t_end) < 1e-5
    assert np.all(np.diff(ts) < 0), "grid must be strictly decreasing"


def test_ddim_coeffs_endpoint():
    sched = linear_schedule()
    # at t==t' update is the identity
    cx, ce = sched.ddim_coeffs(jnp.float32(0.5), jnp.float32(0.5))
    assert abs(float(cx) - 1.0) < 1e-6 and abs(float(ce)) < 1e-6


def test_discrete_adapter():
    sched = linear_schedule(num_train_steps=1000)
    assert int(sched.discrete_t(jnp.float32(1.0))) == 999
    assert int(sched.discrete_t(jnp.float32(1e-4))) == 0
