"""Property wall around the batching engine's core invariant.

The whole fused serving path rests on one claim: a batch-of-N ERA run with
per-sample ERS equals N independent single-sample runs (paper Alg. 1 per
row).  This is what makes request fusion, bucket padding, and mesh batch
sharding all correctness-preserving.  Checked here over randomized
seq_len / nfe / k / seed with hypothesis.
"""

import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st
from conftest import AnalyticGaussian
from repro.core import ERAConfig, get_solver

# module-level: the shim's `given` produces zero-arg tests, so no fixtures
ANALYTIC = AnalyticGaussian()
D_MODEL = 4


@settings(max_examples=5, deadline=None)
@given(
    st.integers(min_value=2, max_value=3),    # N co-batched samples
    st.integers(min_value=2, max_value=8),    # seq_len
    st.integers(min_value=2, max_value=4),    # Lagrange order k
    st.integers(min_value=0, max_value=6),    # nfe headroom above k
    st.integers(min_value=0, max_value=10_000),  # x_T seed
)
def test_batch_of_n_equals_n_single_runs(n, seq_len, k, extra, seed):
    cfg = ERAConfig(nfe=k + 1 + extra, k=k, per_sample=True)
    x = jax.random.normal(
        jax.random.PRNGKey(seed), (n, seq_len, D_MODEL), jnp.float32
    )
    era = get_solver("era")
    batched = era(ANALYTIC.eps, x, ANALYTIC.schedule, cfg)
    assert not bool(jnp.any(jnp.isnan(batched.x0)))
    for i in range(n):
        solo = era(ANALYTIC.eps, x[i : i + 1], ANALYTIC.schedule, cfg)
        np.testing.assert_allclose(
            np.asarray(batched.x0[i : i + 1]),
            np.asarray(solo.x0),
            atol=1e-5,
            err_msg=f"row {i} of batch-of-{n} diverged from its solo run "
            f"(seq_len={seq_len}, k={k}, nfe={cfg.nfe}, seed={seed})",
        )
        # the per-row ERS diagnostics must decouple the same way
        np.testing.assert_allclose(
            np.asarray(batched.aux["delta_eps_history_per_sample"][:, i]),
            np.asarray(solo.aux["delta_eps_history_per_sample"][:, 0]),
            atol=1e-4,
            err_msg=f"row {i} delta_eps history diverged",
        )
