import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.lagrange import (
    ers_select,
    fixed_select,
    interpolate,
    lagrange_weights,
)


def test_weights_partition_of_unity():
    t = jnp.array([0.9, 0.7, 0.4, 0.1])
    w = lagrange_weights(t, 0.25)
    assert abs(float(jnp.sum(w)) - 1.0) < 1e-5


def test_weights_at_nodes():
    t = jnp.array([0.9, 0.7, 0.4, 0.1])
    for i in range(4):
        w = np.asarray(lagrange_weights(t, t[i]))
        expect = np.zeros(4)
        expect[i] = 1.0
        np.testing.assert_allclose(w, expect, atol=1e-5)


@settings(max_examples=30, deadline=None)
@given(
    st.lists(
        st.floats(0.01, 1.0), min_size=3, max_size=5, unique=True
    ).map(sorted),
    st.floats(-2.0, 2.0),
    st.floats(-2.0, 2.0),
    st.floats(-2.0, 2.0),
)
def test_interpolation_exact_on_polynomials(nodes, c0, c1, c2):
    """Degree<=k-1 polynomials are reproduced exactly (hypothesis)."""
    t = jnp.asarray(nodes, jnp.float32)
    poly = lambda x: c0 + c1 * x + c2 * x * x
    values = poly(t)[:, None]          # (k, 1) "eps" values
    t_eval = 0.5 * (nodes[0] + nodes[-1]) - 0.3
    got = interpolate(values, t, jnp.float32(t_eval))
    assert abs(float(got[0]) - float(poly(jnp.float32(t_eval)))) < 1e-2


@settings(max_examples=50, deadline=None)
@given(st.integers(3, 40), st.integers(2, 6), st.floats(0.01, 20.0))
def test_ers_select_invariants(i, k, power):
    """Indices are strictly increasing, within [0, i] (any error power)."""
    if i < k:
        return
    tau = np.asarray(ers_select(jnp.int32(i), k, jnp.float32(power)))
    assert tau.shape == (k,)
    assert np.all(np.diff(tau) >= 1), tau
    assert tau[0] >= 0 and tau[-1] <= i


def test_ers_uniform_at_power_one():
    """Power 1 (delta_eps == lambda init) -> uniform coverage incl. latest."""
    tau = np.asarray(ers_select(jnp.int32(12), 4, jnp.float32(1.0)))
    np.testing.assert_array_equal(tau, [3, 6, 9, 12])


def test_ers_biases_early_when_error_high():
    """Large measured error (power >> 1) pushes bases toward the early,
    more accurate, part of the buffer (paper Fig. 3)."""
    lo = np.asarray(ers_select(jnp.int32(20), 4, jnp.float32(1.0)))
    hi = np.asarray(ers_select(jnp.int32(20), 4, jnp.float32(6.0)))
    assert np.sum(hi[:-1]) < np.sum(lo[:-1])


def test_fixed_select_last_k():
    tau = np.asarray(fixed_select(jnp.int32(10), 3))
    np.testing.assert_array_equal(tau, [8, 9, 10])
