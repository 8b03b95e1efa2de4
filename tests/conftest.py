import glob
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest
from hypothesis import settings

from repro.core import linear_schedule

# property tests draw the same examples on every run, so a suite that
# passed once passes again on the same code
settings.register_profile("repro", derandomize=True)
settings.load_profile("repro")

MESH_DEVICES = 8
MESH_XLA_FLAG = f"--xla_force_host_platform_device_count={MESH_DEVICES}"


class AnalyticGaussian:
    """Gaussian-data diffusion with closed-form optimal eps predictor.

    x0 ~ N(mu, s^2 I)  =>  eps*(x,t) = (x - alpha(t) mu) sigma(t) /
                                        (alpha^2 s^2 + sigma^2)
    """

    def __init__(self, mu=1.5, s=0.5, schedule=None):
        self.mu, self.s = mu, s
        self.schedule = schedule or linear_schedule()

    def eps(self, x, t):
        a = self.schedule.alpha(t)
        sg = self.schedule.sigma(t)
        return (x - a * self.mu) * sg / (a * a * self.s**2 + sg * sg)

    def noisy(self, scale, seed=42, late_boost=4.0):
        """eps* + noise whose magnitude grows as t->0 (paper Fig. 1)."""

        def fn(x, t):
            key = jax.random.fold_in(
                jax.random.PRNGKey(seed), (t * 1e6).astype(jnp.int32)
            )
            mag = scale * (1.0 + late_boost * jnp.exp(-6.0 * t))
            return self.eps(x, t) + mag * jax.random.normal(key, x.shape)

        return fn


class OracleDenoiser:
    """DiffusionLM-shaped wrapper around the analytic eps oracle, so engine
    tests are exact and fast (no network params).

    The oracle is positionwise (no cross-position mixing at all), so
    length masking is trivially supported: pad positions cannot influence
    valid ones, and the solver-side masked ERS norms do the rest.  The
    ``lengths`` argument is therefore accepted and ignored."""

    D_MODEL = 8
    supports_length_masking = True

    def __init__(self, analytic):
        self.analytic = analytic
        self.config = types.SimpleNamespace(d_model=self.D_MODEL)

    def eps_fn(self, params, lengths=None):
        return self.analytic.eps


def run_flush(engine, reqs, params=None):
    """Queue ``reqs`` on a scheduler over ``engine`` (a ``FusedExecutor``),
    flush them on this thread, and return their results in submit order."""
    from repro.serving import AsyncBatchedSampler

    queue = AsyncBatchedSampler(engine, params)
    futs = [queue.submit(r) for r in reqs]
    queue.flush()
    return [f.result(timeout=0) for f in futs]


def host_spans(log_dir, prefix="sampler."):
    """Every host event of the profiler trace under ``log_dir`` whose name
    starts with ``prefix``: name, thread, start and end in ns, and its
    metadata, in order of start."""
    import warnings

    out = []
    pattern = os.path.join(log_dir, "**", "*.xplane.pb")
    for path in glob.glob(pattern, recursive=True):
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith(prefix):
                        continue
                    with warnings.catch_warnings():
                        # iterating stats warns from inside JAX's bindings
                        warnings.simplefilter("ignore", DeprecationWarning)
                        stats = {k: v for k, v in e.stats}
                    out.append({
                        "name": e.name, "line": line.name, "t": e.start_ns,
                        "end": e.start_ns + e.duration_ns, "stats": stats,
                    })
    return sorted(out, key=lambda s: (s["t"], -s["end"]))


@pytest.fixture(scope="session")
def mesh8():
    """8-virtual-CPU-device ("data",) mesh for sharded serving tests.

    Env guard: only materializes when the process was launched with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI sharded
    job does).  Single-device runs skip these cases — the same mesh parity
    is still covered there through the ``run_mesh_subprocess`` tests, which
    re-run the check in a flagged child process.
    """
    if jax.device_count() < MESH_DEVICES:
        pytest.skip(
            f"needs >= {MESH_DEVICES} devices; launch pytest with "
            f"XLA_FLAGS={MESH_XLA_FLAG}"
        )
    from repro.launch.mesh import make_sampler_mesh

    return make_sampler_mesh(MESH_DEVICES)


def run_mesh_subprocess(script: str, timeout: int = 600) -> dict:
    """Run a tests/ script under the 8-virtual-device XLA flag; parse the
    JSON record it prints on its last stdout line."""
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(tests_dir)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + MESH_XLA_FLAG).strip()
    # the virtual-device flag only multiplies CPU-platform devices; pin the
    # child to CPU so a GPU/TPU jax install still gets an 8-device mesh
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(root, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(tests_dir, script)],
        capture_output=True, text=True, timeout=timeout, cwd=root, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="session")
def analytic():
    return AnalyticGaussian()


@pytest.fixture(scope="session")
def xT():
    return jax.random.normal(jax.random.PRNGKey(0), (64, 8))


@pytest.fixture(scope="session")
def reference_x0(analytic, xT):
    from repro.core import default_config, get_solver

    return get_solver("ddim")(
        analytic.eps, xT, analytic.schedule, default_config("ddim", nfe=2000)
    ).x0
