"""Shared benchmark substrate.

FID on LSUN/Cifar10 is not computable in this container (no datasets/GPUs,
see DESIGN.md §1); every paper table is reproduced as the corresponding
*solver-quality* measurement:

  err(solver, NFE) = RMSE( x0_solver , x0_reference )

where the reference is a 400-2000 step DDIM solution of the SAME ODE (same
eps model, same x_T) — i.e. exactly the quantity FID ranks in the paper's
tables, minus the Inception embedding.  Two eps models are used:

  * ``analytic(scale)`` — closed-form optimal eps for a Gaussian-mixture
    target + controlled error injection that grows as t->0 (paper Fig. 1);
  * ``trained()``       — a small diffusion-LM trained in-repo (cached),
    whose noise estimates carry *real* learned error.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import default_config, get_solver, linear_schedule
from repro.data import DataConfig, GaussianMixtureLatents
from repro.models import build_model
from repro.models.diffusion import DiffusionLM
from repro.training import (
    OptimizerConfig,
    checkpoint as ck,
    make_diffusion_train_step,
    train,
)

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")
SCHEDULE = linear_schedule()


class AnalyticMixture:
    """Two-mode Gaussian mixture in R^d with exact eps* (multi-modal, so
    high-order solvers actually have curvature to exploit)."""

    def __init__(self, d=16, sep=2.0, s=0.35):
        # component means: +/- sep along the first axis
        self.c = jnp.zeros((2, d)).at[0, 0].set(sep).at[1, 0].set(-sep)
        self.s = s
        self.d = d

    def eps(self, x, t):
        a = SCHEDULE.alpha(t)
        sg = SCHEDULE.sigma(t)
        var = a * a * self.s**2 + sg * sg
        # posterior-weighted mixture score
        logw = -0.5 * jnp.sum(
            (x[..., None, :] - a * self.c) ** 2, -1
        ) / var
        w = jax.nn.softmax(logw, axis=-1)[..., None]
        mean = jnp.sum(w * (a * self.c), axis=-2)
        return (x - mean) * sg / var

    def noisy(self, scale, seed=17, late=4.0):
        def fn(x, t):
            key = jax.random.fold_in(
                jax.random.PRNGKey(seed), (t * 1e6).astype(jnp.int32)
            )
            mag = scale * (1.0 + late * jnp.exp(-6.0 * t))
            return self.eps(x, t) + mag * jax.random.normal(key, x.shape)

        return fn


@functools.lru_cache(maxsize=1)
def trained_model(steps: int = 150):
    """Train (or load) the small in-repo diffusion-LM used by benches."""
    cfg = get_config("llama3.2-1b", smoke=True)
    dlm = DiffusionLM(build_model(cfg))
    path = os.path.join(ART, "bench_denoiser.npz")
    dc = DataConfig(vocab_size=1, seq_len=8, batch_size=16, kind="diffusion",
                    d_model=cfg.d_model, num_modes=2, seed=3)
    data = GaussianMixtureLatents(dc)
    if os.path.exists(path):
        tree, _ = ck.restore(path)
        params = jax.tree.map(jnp.asarray, tree["params"])
    else:
        params = dlm.init(jax.random.PRNGKey(0))
        step = make_diffusion_train_step(
            dlm, OptimizerConfig(lr=2e-3, warmup_steps=5, total_steps=steps),
            SCHEDULE,
        )
        res = train(step, params, data.batches(), steps, log_every=1000,
                    print_fn=lambda s: None)
        params = res.params
        os.makedirs(ART, exist_ok=True)
        ck.save(path, {"params": params}, steps)
    return dlm, params, data, cfg


def rmse(a, b) -> float:
    return float(jnp.sqrt(jnp.mean((a - b) ** 2)))


def reference_solution(eps_fn, xT, nfe=800):
    return get_solver("ddim")(
        eps_fn, xT, SCHEDULE, default_config("ddim", nfe=nfe)
    ).x0


def solve(eps_fn, xT, solver: str, nfe: int, **kw):
    cfg = default_config(solver, nfe=nfe, **kw) if solver == "era" else (
        default_config(solver, nfe=nfe)
    )
    return get_solver(solver)(eps_fn, xT, SCHEDULE, cfg).x0


def emit(name: str, value_us: float, derived: str = "") -> None:
    """Scaffold contract: ``name,us_per_call,derived`` CSV lines."""
    print(f"{name},{value_us:.1f},{derived}")
