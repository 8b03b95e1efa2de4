"""ERA-Solver (the paper's contribution, Algorithm 1).

Implicit-Adams (Adams--Moulton order 4) corrector whose unobserved term is
predicted by a Lagrange interpolation over an error-robustly selected subset
of previously observed network noises.  1 NFE per step (like DDIM), high
order (like implicit Adams), robust to noise-estimation error (the ERS
strategy).

Structure of one step i (i >= k-1; the first k-1 steps are DDIM warmup while
the Lagrange buffer fills):

  1. select bases  tau_{1..k}  via ERS (Eq. 16/17) using delta_eps
  2. predict       eps_bar_{i+1} = L_eps(t_{i+1})            (Eq. 13/14)
  3. correct       eps_ti = (9 eps_bar_{i+1} + 19 eps_i - 5 eps_{i-1}
                             + eps_{i-2}) / 24               (Eq. 11)
  4. x-update      x_{i+1} = DDIM(x_i, eps_ti)               (Eq. 8)
  5. observe       eps_{i+1} = eps_theta(x_{i+1}, t_{i+1})   (1 NFE)
  6. measure       delta_eps = || eps_{i+1} - eps_bar_{i+1} ||_2   (Eq. 15)

The final iteration skips step 5/6 (the sample is finished), so a run with N
steps costs exactly N NFE (1 initial eval + N-1 in-loop evals).

Engine notes (serving path):

* The loop is a single ``jax.lax.scan`` over the step grid, so one jit
  compile covers a whole (sample-shape, nfe, k) bucket and XLA can reuse the
  Lagrange buffers in place.
* :func:`sample_scan` takes the eps/t buffers as explicit arguments so a
  jitting caller (``repro.serving.FusedExecutor``) can donate them.
* Steps 2-4 run the fused Pallas kernel (``repro.kernels.era_update``) —
  one HBM round trip per operand instead of ~(k+5).  The platform picks how
  it runs: compiled by Mosaic on TPU, in interpret mode everywhere else.
  There is no runtime probe and no fallback: a kernel that fails to lower
  or compile raises.  ``ERAConfig.use_fused_update=False`` selects the
  pure-jnp reference combine, which the parity tests and the chip smoke
  check compare the kernel against.
* :func:`sample_scan` optionally takes explicit carry ``shardings``
  (``parallel.sharding.sampler_shardings``): latents and Lagrange buffers
  batch-sharded over a mesh's data axes, t grid replicated.  With
  ``per_sample=True`` every step's ERS math is row-local, so the sharded
  scan runs with **zero cross-device collectives inside the loop** (the only
  batch reduction, the delta_eps diagnostic mean, happens once after it).
  The fused step then runs per batch shard under ``shard_map``
  (``parallel.sharding.per_batch_shard``): XLA cannot partition a Mosaic
  kernel.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import lagrange
from repro.core.program import (
    SolverProgram,
    StepMask,
    step_active,
    step_row_times,
)
from repro.core.schedules import NoiseSchedule, timesteps
from repro.core.solver_base import (
    EpsFn,
    SolverConfig,
    SolverOutput,
    buffer_append,
    buffer_init,
    ddim_step,
    step_grid,
)
from repro.parallel.sharding import per_batch_shard

Array = jax.Array

# Adams--Moulton order-4 corrector coefficients (paper Eq. 10/11).
AM4 = (9.0 / 24.0, 19.0 / 24.0, -5.0 / 24.0, 1.0 / 24.0)

# Name scopes of a step's device ops (op metadata only: the same ops and
# fusions), so a device trace splits the solver's time from the denoiser's:
# ERS (basis selection and the error norm) and the update (Lagrange
# predictor, AM4 corrector, DDIM x-update, history append).
ERS_SCOPE = "era.ers"
UPDATE_SCOPE = "era.update"


@dataclasses.dataclass(frozen=True)
class ERAConfig(SolverConfig):
    """ERA-Solver options (defaults follow the paper's main setting)."""

    k: int = 4                     # Lagrange interpolation order
    lam: float = 5.0               # power-scale hyperparameter (Eq. 17)
    selection: str = "ers"         # "ers" | "fixed" | "const"
    const_power: float = 1.0       # used when selection == "const"
    error_norm: str = "global"     # "global" (Eq. 15) | "mean" (per-sample mean)
    # False = the pure-jnp reference combine (kernel parity checks only)
    use_fused_update: bool = True
    # beyond-paper: independent delta_eps + base selection per batch element
    # (the paper shares one scalar across the batch)
    per_sample: bool = False


def _fixed_order_sum(v: Array) -> Array:
    """Sum over the last axis in an order fixed by that axis alone.

    XLA picks a reduction's association order, and inside a larger program
    that order can change with the leading (batch, sequence) shape.  Halving
    with elementwise adds (after zero-padding to a power of two, which adds
    exact zeros) gives every row the same float result at any leading
    shape."""
    n = v.shape[-1]
    width = 1 << max(n - 1, 0).bit_length()
    if width != n:
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, width - n)])
    while v.shape[-1] > 1:
        half = v.shape[-1] // 2
        v = v[..., :half] + v[..., half:]
    return v[..., 0]


def _seq_sq_sums(d: Array, valid: Array | None) -> Array:
    """Per-row sum of squared entries, accumulated position-by-position.

    The mixed-seq-len serving path right-pads samples from length L to a
    seq bucket L' and must leave every valid row's delta_eps — and hence
    its ERS Lagrange-basis selection — **bit-identical** to the exact-shape
    run.  A plain ``jnp.sum`` over the padded layout cannot promise that:
    XLA may re-associate a size-L' reduction differently from a size-L one
    even when the extra entries are exact zeros.  So the reduction here is
    (a) features first, in a fixed order (:func:`_fixed_order_sum`), then
    (b) a strictly sequential ``lax.scan`` over positions — appending
    zero-masked pad positions only appends ``acc + 0.0`` steps, which are
    exact no-ops.
    The accumulation is elementwise per row, so a batch-sharded run stays
    collective-free.  Rank-2 inputs (no sequence axis) keep the plain
    squared norm.
    """
    d = d.astype(jnp.float32)
    if d.ndim < 3:
        return jnp.sum(d.reshape(d.shape[0], -1) ** 2, axis=-1)
    p = _fixed_order_sum(d.reshape(d.shape[0], d.shape[1], -1) ** 2)  # (B, S)
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    total, _ = jax.lax.scan(
        lambda acc, ps: (acc + ps, None),
        jnp.zeros(d.shape[0], jnp.float32),
        p.T,
    )
    return total


def _delta_eps(
    e_obs: Array, e_pred: Array, mode: str, valid: Array | None = None
) -> Array:
    if mode == "global":
        d = (e_obs - e_pred).astype(jnp.float32)
        if valid is None:
            return jnp.linalg.norm(d.reshape(-1))
        # masked Eq. 15: pad positions contribute exactly zero
        return jnp.sqrt(jnp.sum(_seq_sq_sums(d, valid)))
    if mode == "mean":  # per-sample L2, averaged — batch-size invariant
        return jnp.mean(_delta_eps_batch(e_obs, e_pred, valid))
    raise ValueError(f"unknown error_norm {mode!r}")


def _delta_eps_batch(
    e_obs: Array, e_pred: Array, valid: Array | None = None
) -> Array:
    """Per-sample L2 errors, (B,), reduced only over valid positions."""
    return jnp.sqrt(_seq_sq_sums(e_obs - e_pred, valid))


def era_combine(
    eps_sel: Array,      # (k, *x) selected buffer noises
    t_sel: Array,        # (k,) their times
    e_hist: Array,       # (3, *x) eps at steps i, i-1, i-2
    t_next: Array,
) -> tuple[Array, Array]:
    """Predictor + corrector combine: returns (eps_bar_next, eps_corr).

    Kept as a standalone function so the Pallas fused kernel
    (repro.kernels.era_update) can be validated against it and swapped in.
    """
    eps_bar = lagrange.interpolate(eps_sel, t_sel, t_next)
    c0, c1, c2, c3 = AM4
    eps_corr = c0 * eps_bar + c1 * e_hist[0] + c2 * e_hist[1] + c3 * e_hist[2]
    return eps_bar, eps_corr


def alloc_buffers(
    x: Array, config: ERAConfig, shardings=None
) -> tuple[Array, Array]:
    """Fresh Lagrange eps/t buffers sized for ``config.nfe`` steps.

    Callers that jit :func:`sample_scan` can allocate these outside the
    compiled function and donate them (``donate_argnums``) — the scan then
    updates them in place for the whole sampling run.

    With ``shardings`` (see :func:`sample_scan`), the eps buffer — the
    largest array in a sampling run — is created batch-sharded in place
    rather than materialized on one device and redistributed.
    """
    return buffer_init(x, config.nfe + 1, config.solver_dtype, shardings)


def sample(
    eps_fn: EpsFn,
    x_init: Array,
    schedule: NoiseSchedule,
    config: ERAConfig,
) -> SolverOutput:
    """Self-contained entry: allocates buffers, then runs the scan loop."""
    eps_buf, t_buf = alloc_buffers(x_init, config)
    return sample_scan(eps_fn, x_init, eps_buf, t_buf, schedule, config)


def sample_scan(
    eps_fn: EpsFn,
    x_init: Array,
    eps_buf: Array,      # (nfe+1, *x.shape) zeros, donatable
    t_buf: Array,        # (nfe+1,) zeros, donatable
    schedule: NoiseSchedule,
    config: ERAConfig,
    shardings=None,      # optional carry placement, duck-typed with fields
                         # .x/.eps_buf/.t_buf/.delta_eps (NamedShardings) —
                         # see parallel.sharding.sampler_shardings
    lengths: Array | None = None,  # (B,) valid seq lengths of a right-
                                   # padded mixed-seq-len batch; masks the
                                   # ERS error norms so pad positions can
                                   # never flip a basis selection
    steps: StepMask | None = None,  # mixed-NFE channel: per-row step
                                    # counts + per-row time grids; a row's
                                    # carry freezes bitwise once spent
) -> SolverOutput:
    n = config.nfe
    k = config.k
    if n < k:
        raise ValueError(f"ERA-Solver needs nfe >= k ({n} < {k})")
    if steps is not None and not config.per_sample:
        raise ValueError(
            "mixed-NFE step masking needs per-sample ERS (per_sample=True):"
            " a shared delta_eps would couple rows with different horizons"
        )
    if lengths is not None and x_init.ndim < 3:
        raise ValueError(
            "lengths masking needs batch-of-sequences latents (B, S, ...); "
            f"got x of rank {x_init.ndim}"
        )
    if eps_buf.shape != (n + 1,) + x_init.shape:
        raise ValueError(
            f"eps buffer shape {eps_buf.shape} != {(n + 1,) + x_init.shape}"
        )
    if t_buf.shape != (n + 1,):
        raise ValueError(f"t buffer shape {t_buf.shape} != {(n + 1,)}")
    if steps is None:
        ts = timesteps(schedule, n, config.scheme, t_end=config.t_end)
        t0 = ts[0]
    else:
        # each row starts on its own grid; the shared t_buf goes unused
        # under step masking (Lagrange node times gather from steps.ts,
        # which holds exactly the floats an exact run appends to t_buf)
        ts = None
        t0 = steps.ts[:, 0].reshape((-1,) + (1,) * (x_init.ndim - 1))
    dt = config.solver_dtype
    if config.use_fused_update:
        from repro.kernels import ops as kops  # kernels import core.lagrange
    else:
        kops = None

    def per_shard(fn, *args, batch_dims):
        # on a mesh the kernel runs per batch shard: XLA cannot partition it
        if shardings is None:
            return fn(*args)
        return per_batch_shard(shardings.x, fn, *args, batch_dims=batch_dims)

    am4 = jnp.asarray(AM4, jnp.float32)
    valid = (
        None
        if lengths is None
        else jnp.arange(x_init.shape[1], dtype=jnp.int32) < lengths[:, None]
    )  # (B, S) position-validity mask for the error norms

    x = x_init.astype(dt)
    if shardings is not None:
        x = jax.lax.with_sharding_constraint(x, shardings.x)
        eps_buf = jax.lax.with_sharding_constraint(eps_buf, shardings.eps_buf)
        t_buf = jax.lax.with_sharding_constraint(t_buf, shardings.t_buf)
    # Alg. 1 line 2/3: delta_eps initialized to lambda (power = 1, uniform
    # selection); initial observation appended at index 0.
    e0 = eps_fn(x, t0).astype(dt)
    eps_buf, t_buf = buffer_append(
        eps_buf, t_buf, jnp.int32(0), e0,
        jnp.float32(0.0) if steps is not None else ts[0],
    )
    delta_eps = (
        jnp.full((x.shape[0],), config.lam, jnp.float32)
        if config.per_sample
        else jnp.float32(config.lam)
    )
    if shardings is not None:
        delta_eps = jax.lax.with_sharding_constraint(
            delta_eps, shardings.delta_eps
        )

    # ERS selections are emitted per step (warmup steps emit the zero
    # placeholder) so callers can assert two runs selected identical bases
    tau_shape = (x.shape[0], k) if config.per_sample else (k,)

    def warm_branch(ops):
        x, eps_buf, t_buf, de, i, t_cur, t_next = ops
        with jax.named_scope(UPDATE_SCOPE):
            e_cur = jax.lax.dynamic_index_in_dim(eps_buf, i, 0, keepdims=False)
            x_next = ddim_step(schedule, x, e_cur, t_cur, t_next)
        # prediction placeholder: the DDIM-held noise; no selection yet
        return x_next, e_cur, jnp.zeros(tau_shape, jnp.int32)

    def main_branch(ops):
        x, eps_buf, t_buf, de, i, t_cur, t_next = ops
        with jax.named_scope(UPDATE_SCOPE):
            e_hist = jnp.stack(
                [
                    jax.lax.dynamic_index_in_dim(
                        eps_buf, i - j, 0, keepdims=False
                    )
                    for j in range(3)
                ]
            )
        with jax.named_scope(ERS_SCOPE):
            if config.per_sample:
                # beyond-paper: each batch element selects its own bases
                # from its own measured error
                tau = jax.vmap(
                    lambda d: lagrange.select_bases(
                        i, k, d, config.lam, config.selection,
                        config.const_power,
                    )
                )(de)                                        # (B, k)
            else:
                tau = lagrange.select_bases(
                    i, k, de, config.lam, config.selection, config.const_power
                )
        with jax.named_scope(UPDATE_SCOPE):
            x_next, eps_bar = update(x, eps_buf, t_buf, e_hist, t_cur, t_next, tau)
        return x_next, eps_bar, tau

    def update(x, eps_buf, t_buf, e_hist, t_cur, t_next, tau):
        """Lagrange predictor, AM4 corrector and DDIM x-update on the
        bases ``tau``: returns ``(x_next, eps_bar)``."""
        if config.per_sample:
            if steps is None:
                t_sel = jnp.take(t_buf, tau, axis=0)         # (B, k)
            else:
                # per-row grids: node times come from the row's own grid
                # (identical floats to the exact run's t_buf entries)
                t_sel = jax.vmap(
                    lambda ts_r, tau_r: jnp.take(ts_r, tau_r, axis=0)
                )(steps.ts, tau)                             # (B, k)
            # per-sample gather from the (cap, B, ...) buffer
            eps_sel = jax.vmap(
                lambda tau_b, buf_b: jnp.take(buf_b, tau_b, axis=0),
                in_axes=(0, 1),
                out_axes=0,
            )(tau, eps_buf)                                  # (B, k, ...)
            e_hist_b = jnp.moveaxis(e_hist, 1, 0)            # (B, 3, ...)
            cx, ce = schedule.ddim_coeffs(t_cur, t_next)
            if kops is not None:
                # fused per-sample step: vmap the Pallas kernel over the
                # batch (each element carries its own Lagrange nodes; with
                # per-row grids, also its own times and DDIM coefficients)
                r = None if steps is None else 0
                rows = jax.vmap(
                    lambda xb, es, tn, eh, tnb, cxb, ceb: kops.era_step(
                        xb, es, tn, eh, tnb, cxb, ceb, am4
                    ),
                    in_axes=(0, 0, 0, 0, r, r, r),
                )
                if steps is not None:
                    t_next, cx, ce = (a.reshape(-1) for a in (t_next, cx, ce))
                return per_shard(
                    rows, x, eps_sel, t_sel, e_hist_b, t_next, cx, ce,
                    batch_dims=(0, 0, 0, 0, r, r, r),
                )
            if steps is None:
                eps_bar, eps_corr = jax.vmap(
                    era_combine, in_axes=(0, 0, 0, None)
                )(eps_sel, t_sel, e_hist_b, t_next)
            else:
                eps_bar, eps_corr = jax.vmap(era_combine)(
                    eps_sel, t_sel, e_hist_b, t_next.reshape(-1)
                )
            return ddim_step(schedule, x, eps_corr, t_cur, t_next), eps_bar
        t_sel = jnp.take(t_buf, tau, axis=0)
        eps_sel = jnp.take(eps_buf, tau, axis=0)
        if kops is not None:
            # fused step: predictor combine + AM4 corrector + DDIM x-update
            # in one HBM pass
            cx, ce = schedule.ddim_coeffs(t_cur, t_next)
            return per_shard(
                lambda *a: kops.era_step(*a, am4),
                x, eps_sel, t_sel, e_hist, t_next, cx, ce,
                batch_dims=(0, 1, None, 1, None, None, None),
            )
        eps_bar, eps_corr = era_combine(eps_sel, t_sel, e_hist, t_next)
        return ddim_step(schedule, x, eps_corr, t_cur, t_next), eps_bar

    def step(carry, inp):
        x, eps_buf, t_buf, de = carry
        if steps is None:
            i, t_cur, t_next = inp
        else:
            i = inp
            t_cur, t_next = step_row_times(steps, i, x.ndim)
        ops = (x, eps_buf, t_buf, de, i, t_cur, t_next)
        x_next, eps_bar, tau = jax.lax.cond(
            i < k - 1, warm_branch, main_branch, ops
        )
        if steps is not None:
            # a spent row's latents freeze bitwise for the rest of the scan
            with jax.named_scope(UPDATE_SCOPE):
                x_next = jnp.where(step_active(steps, i, x.ndim), x_next, x)

        # Observe eps at the new point — except on the final step, whose
        # x_next is the output (keeps total cost at exactly `nfe` evals).
        # Under step masking the skip becomes per-row: each row's last
        # *own* step appends zeros and keeps its delta_eps, exactly like
        # the exact-shape run's final step (the whole-batch cond still
        # spares the bucket's terminal eval).
        def observe(_):
            e_new = eps_fn(x_next, t_next).astype(dt)
            with jax.named_scope(ERS_SCOPE):
                if config.per_sample:
                    de_new = _delta_eps_batch(e_new, eps_bar, valid)
                else:
                    de_new = _delta_eps(
                        e_new, eps_bar, config.error_norm, valid
                    )
            if steps is not None:
                obs = (i + 1) < steps.active_steps           # (B,)
                with jax.named_scope(UPDATE_SCOPE):
                    e_new = jnp.where(
                        obs.reshape((-1,) + (1,) * (e_new.ndim - 1)),
                        e_new, 0.0,
                    )
                de_new = jnp.where(obs, de_new, de)
            return e_new, de_new

        def skip(_):
            return jnp.zeros_like(x_next), de

        e_new, de_new = jax.lax.cond(i + 1 < n, observe, skip, None)
        # Alg. 1 line 16: delta_eps only updates once predictions are real.
        de = jnp.where(i >= k - 1, de_new, de)
        with jax.named_scope(UPDATE_SCOPE):
            eps_buf, t_buf = buffer_append(
                eps_buf, t_buf, i + 1, e_new,
                jnp.float32(0.0) if steps is not None else t_next,
            )
        traj_x = x_next if config.return_trajectory else None
        # per-sample: emit the raw (B,) errors and reduce after the scan, so
        # a batch-sharded run keeps the loop body free of collectives
        return (x_next, eps_buf, t_buf, de), (de, tau, traj_x)

    grid = (
        step_grid(ts) if steps is None else jnp.arange(n, dtype=jnp.int32)
    )
    (x, eps_buf, t_buf, delta_eps), (de_hist, tau_hist, traj_tail) = (
        jax.lax.scan(step, (x, eps_buf, t_buf, delta_eps), grid)
    )
    aux: dict[str, Any] = {}
    if config.per_sample:
        aux["delta_eps_history_per_sample"] = de_hist        # (nfe, B)
        aux["delta_eps_history"] = jnp.mean(de_hist, axis=-1)
        # per-row selected Lagrange bases per step — the engine's padding-
        # invariance wall asserts these match the exact-shape run exactly
        aux["ers_selection_history"] = tau_hist              # (nfe, B, k)
    else:
        aux["delta_eps_history"] = de_hist
    if config.return_trajectory:
        aux["trajectory"] = jnp.concatenate(
            [x_init.astype(dt)[None], traj_tail], axis=0
        )
    return SolverOutput(x0=x.astype(x_init.dtype), nfe=jnp.int32(n), aux=aux)


class ERAProgram(SolverProgram):
    """ERA-Solver as a serving program.

    The paper-default config shares one scalar delta_eps across the batch —
    every row couples through that global error norm, so such configs are
    not fusable (strangers or pad rows would change each request's result).
    The engine default turns on per-sample ERS, which makes a batch-of-N
    run equivalent to N independent runs and the program fully fusable."""

    name = "era"
    config_cls = ERAConfig
    aux_row_axes = {
        "trajectory": 1,
        "delta_eps_history_per_sample": 1,
        "ers_selection_history": 1,
    }
    aux_step_axes = {
        "trajectory": 0,
        "delta_eps_history": 0,
        "delta_eps_history_per_sample": 0,
        "ers_selection_history": 0,
    }

    def engine_config(self) -> ERAConfig:
        # per-sample ERS isolates co-batched requests from each other
        return ERAConfig(per_sample=True)

    def fusable(self, cfg: ERAConfig) -> bool:
        return cfg.per_sample

    def per_sample_state(self, cfg: ERAConfig) -> bool:
        return cfg.per_sample

    def supports_lengths(self, cfg: ERAConfig) -> bool:
        """ERA's only cross-position math is the ERS error norm, which
        ``sample_scan`` masks (position-sequential accumulation, so padded
        and exact-shape runs agree bitwise); everything else — Lagrange
        predictor, AM4 corrector, DDIM update — is elementwise."""
        return True

    def supports_steps(self, cfg: ERAConfig) -> bool:
        """Mixed-NFE step masking needs per-sample ERS: each row carries
        its own delta_eps and basis selections, so freezing a spent row
        can never perturb a live one (a shared scalar delta_eps would
        couple rows with different horizons)."""
        return cfg.per_sample

    def validate(self, req, cfg: ERAConfig, dp: int = 1) -> None:
        super().validate(req, cfg, dp=dp)
        if req.nfe < cfg.k:
            raise ValueError(
                f"ERA-Solver needs nfe >= k ({req.nfe} < {cfg.k}); "
                "lower k in the engine's solver_config or raise nfe"
            )

    def num_buffers(self, cfg: ERAConfig) -> int:
        return 2

    def alloc_buffers(self, x_like, cfg: ERAConfig, shardings=None):
        return alloc_buffers(x_like, cfg, shardings)

    def sample_scan(
        self, eps_fn, x_init, buffers, schedule, cfg, shardings=None,
        lengths=None, steps=None,
    ):
        eps_buf, t_buf = buffers
        return sample_scan(
            eps_fn, x_init, eps_buf, t_buf, schedule, cfg,
            shardings=shardings, lengths=lengths, steps=steps,
        )

    def scope_aux(
        self,
        aux: dict,
        off: int,
        batch: int,
        seq_len: int | None = None,
        n_steps: int | None = None,
        padded_steps: int | None = None,
    ) -> dict:
        scoped = super().scope_aux(
            aux, off, batch, seq_len=seq_len,
            n_steps=n_steps, padded_steps=padded_steps,
        )
        if scoped is not aux and "delta_eps_history_per_sample" in scoped:
            # the batch-mean diagnostic must cover only this request's rows
            # (pad rows would dilute it; batch-mates would leak into it)
            scoped["delta_eps_history"] = jnp.mean(
                scoped["delta_eps_history_per_sample"], axis=-1
            )
        return scoped
