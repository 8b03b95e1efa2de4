"""Solver programs — the uniform compiled-sampling contract of the engine.

A :class:`SolverProgram` is what the serving stack knows about a solver.
Every registry solver (ERA and every baseline the paper compares against)
implements the same surface, so `repro.serving.FusedExecutor` can fuse,
shard, donate buffers for, and route requests to *any* solver without
solver-specific branches:

* ``alloc_buffers(x_like, cfg, shardings)`` — fixed-capacity history
  buffers (the Lagrange/Adams eps+t buffers), allocated outside the jitted
  program so the caller can donate them (``donate_argnums``) and XLA
  updates them in place across the whole sampling scan.  Solvers without
  history state return ``()``.  ``abstract_buffers`` is the
  ``ShapeDtypeStruct`` mirror ahead-of-time compilation lowers against.
* ``sample_scan(eps_fn, x_init, buffers, schedule, cfg, shardings)`` — the
  single-``lax.scan``(-or-unrolled) XLA program over the step grid.  One
  jit compile covers a whole (sample-shape, nfe) bucket.  Carry
  initialization lives inside (it may spend an NFE on the first
  observation), so there is no separate ``init_carry`` hook.
* ``carry_pspecs`` / ``carry_shardings`` — mesh placement for the scan
  carry (latents batch-sharded over the data axes, history buffers
  batch-sharded on axis 1, time grid replicated), derived from
  ``per_sample_state`` so per-sample solver state shards with its rows.
* ``fusable(cfg)`` / ``validate(req, cfg, dp)`` — request policy: can
  strangers (and pad rows) share a batch under this config, and which
  (batch, nfe) requests are legal (ERA's ``nfe >= k``, PECE's 2-NFE/step
  budget, DPM++(2M)'s multistep warmup).  ``req`` is duck-typed (needs
  ``.batch`` and ``.nfe``) so core stays import-free of the serving layer.
* ``scope_aux(aux, off, batch, seq_len=...)`` + ``aux_row_axes`` /
  ``aux_seq_axes`` — aux-scoping metadata: which diagnostics carry a
  padded-batch axis and which carry a padded-sequence axis, so a
  co-batched request sees only its own rows and valid positions (no
  batch-mate/tenant, pad-row, or pad-position leakage).
* ``supports_lengths(cfg)`` + the ``lengths`` argument of ``sample_scan``
  — the length-mask channel for mixed-seq-len fusion: the serving engine
  right-pads each request's sample from its ``seq_len`` to a shared seq
  bucket and passes the per-row valid lengths through the compiled
  program.  A program that supports lengths guarantees pad positions can
  never change a valid position's math (elementwise solvers get this for
  free; ERA masks its ERS error norms so a pad token can never flip a
  Lagrange-basis selection).

Concrete programs live next to their solver math (``DDIMProgram`` in
``ddim.py``, ...) and are registered in :mod:`repro.core.registry`.
"""

from __future__ import annotations

from typing import Any, Mapping, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.schedules import NoiseSchedule, timesteps
from repro.core.solver_base import EpsFn, SolverConfig, SolverOutput

Array = jax.Array


class StepMask(NamedTuple):
    """The mixed-NFE mask channel: per-row step activity for a batch whose
    rows run different step counts inside one compiled scan.

    The scan itself always runs the bucket's full ``n_steps`` iterations;
    a row whose request needs fewer steps goes inert once its own count is
    spent — step ``i`` is **active** for row ``r`` iff
    ``i < active_steps[r]``, and an inactive step must leave that row's
    entire carry (latents, history buffers, per-sample solver state)
    bitwise unchanged.  Each row also carries its *own* time grid: row
    ``r``'s real grid (``step_times`` for its exact NFE) occupies
    ``ts[r, : active_steps[r] + 1]``, with the terminal time repeated
    through the padded tail so inactive steps still see finite times.
    Both arrays are built host-side by the serving executor with the same
    ``timesteps`` call an exact-shape run uses, which is what makes the
    active prefix of a padded row bitwise identical to the unpadded run.
    """

    #: (B,) int32 — per-row count of real solver steps
    active_steps: Array
    #: (B, n_steps + 1) float32 — per-row time grids, terminal-padded
    ts: Array


def step_active(steps: StepMask, i: Array, x_ndim: int = 3) -> Array:
    """Per-row activity predicate for scan step ``i``, broadcastable
    against ``(B,) + trailing`` carries: shape ``(B,) + (1,) * (x_ndim-1)``."""
    act = i < steps.active_steps
    return act.reshape(act.shape + (1,) * (x_ndim - 1))


def step_row_times(steps: StepMask, i: Array, x_ndim: int = 3):
    """Row times ``(t_cur, t_next)`` for scan step ``i`` under a step
    mask, shaped ``(B,) + (1,) * (x_ndim - 1)`` so schedule coefficients
    broadcast per row exactly like the scalar-time fast path."""
    trail = (1,) * (x_ndim - 1)
    t_cur = jax.lax.dynamic_index_in_dim(steps.ts, i, axis=1, keepdims=False)
    t_next = jax.lax.dynamic_index_in_dim(
        steps.ts, i + 1, axis=1, keepdims=False
    )
    return (
        t_cur.reshape(t_cur.shape + trail),
        t_next.reshape(t_next.shape + trail),
    )


class SolverProgram:
    """Base solver program: a fusable, bufferless, batch-row-independent
    solver.  Subclasses override the hooks their solver needs."""

    #: registry name (set by each concrete program)
    name: str = ""
    #: config dataclass this program consumes
    config_cls: type[SolverConfig] = SolverConfig
    #: aux keys whose value carries the padded batch on the given axis
    aux_row_axes: Mapping[str, int] = {"trajectory": 1}
    #: aux keys whose value carries the padded sequence on the given axis
    aux_seq_axes: Mapping[str, int] = {"trajectory": 2}
    #: aux keys whose value is stacked over scan steps on the given axis
    #: (scoped to a request's real step count under NFE bucketing)
    aux_step_axes: Mapping[str, int] = {"trajectory": 0}

    # ---- configs ---------------------------------------------------------
    def default_config(self, **kw) -> SolverConfig:
        """The paper-default config (what ``core.default_config`` returns)."""
        return self.config_cls(**kw)

    def engine_config(self) -> SolverConfig:
        """The serving-engine default config.  Programs whose paper default
        couples batch rows override this with an isolation-safe variant
        (ERA turns on per-sample ERS)."""
        return self.config_cls()

    # ---- request policy --------------------------------------------------
    def fusable(self, cfg: SolverConfig) -> bool:
        """Can strangers (and pad rows) share a fused batch under ``cfg``?
        True whenever every batch row's math reads only its own row."""
        return True

    def per_sample_state(self, cfg: SolverConfig) -> bool:
        """Does the scan carry per-sample ``(B,)``-shaped solver state that
        should shard with its rows (ERA's per-sample delta_eps)?"""
        return False

    def supports_lengths(self, cfg: SolverConfig) -> bool:
        """Can this program run a right-padded mixed-seq-len batch with a
        per-row ``lengths`` vector such that every valid position's math is
        exactly what an unpadded run would compute?

        True is correct whenever the solver's own math is elementwise over
        positions (DDIM / Adams / DPM updates touch each position
        independently, so a pad position can never leak into a valid one —
        the *denoiser* mask is the engine's responsibility).  A program
        whose per-step math reduces over the sequence (ERA's ERS error
        norm) must mask that reduction to return True."""
        return True

    def supports_steps(self, cfg: SolverConfig) -> bool:
        """Can this program run a mixed-NFE batch under a :class:`StepMask`
        — scanning to a bucketed max step count with per-row activity —
        such that a row's active steps compute exactly what an exact-NFE
        run would, and its inactive steps leave its carry bitwise
        unchanged?  Requires the scan form (Python-unrolled solvers whose
        step *plan* depends on the NFE, like dpm_solver_fast, cannot) plus
        per-row times threaded through every schedule coefficient."""
        return False

    def steps_for_nfe(self, nfe: int, cfg: SolverConfig) -> int:
        """How many scan steps a request with NFE budget ``nfe`` runs
        (PECE spends 2 NFE per step; the adaptive program turns the budget
        into an iteration cap).  This is the unit ``StepMask.active_steps``
        counts in — scan steps, not NFE."""
        return nfe

    def step_times(
        self, schedule: NoiseSchedule, nfe: int, cfg: SolverConfig
    ) -> Array:
        """The exact time grid a request with budget ``nfe`` steps through
        — ``(steps_for_nfe(nfe) + 1,)`` decreasing.  The serving executor
        builds each row of ``StepMask.ts`` with this hook so a padded
        row's grid prefix is the very floats the unpadded run uses;
        programs that pin a scheme in their scan (DPM++'s logsnr grid)
        override it to match."""
        return timesteps(
            schedule, self.steps_for_nfe(nfe, cfg), cfg.scheme,
            t_end=cfg.t_end,
        )

    def validate(self, req: Any, cfg: SolverConfig, dp: int = 1) -> None:
        """Reject an illegal request at submit time.  ``req`` needs
        ``.batch`` and ``.nfe``.  Base rule: a non-fusable config runs
        unpadded (exact size), so on a mesh its batch must split evenly
        over the data axes."""
        if req.nfe < 1:
            raise ValueError(f"nfe must be >= 1, got {req.nfe}")
        if not self.fusable(cfg) and dp > 1 and req.batch % dp:
            raise ValueError(
                f"{self.name} requests under this config are not fusable and "
                f"run unpadded, so on a mesh their batch must be a multiple "
                f"of the data-parallel size ({dp}); got batch={req.batch}."
            )

    # ---- buffers / placement --------------------------------------------
    def num_buffers(self, cfg: SolverConfig) -> int:
        """How many donatable buffer arrays ``alloc_buffers`` returns
        (static per config — the jit donate_argnums depend on it)."""
        return 0

    def alloc_buffers(
        self, x_like: Array, cfg: SolverConfig, shardings=None
    ) -> tuple[Array, ...]:
        """Fresh donatable history buffers for one sampling run (empty for
        history-free solvers).  With ``shardings``, buffers are created
        batch-sharded in place instead of materialized on one device."""
        return ()

    def abstract_buffers(
        self, x_like, cfg: SolverConfig, shardings=None
    ) -> tuple[jax.ShapeDtypeStruct, ...]:
        """Abstract (``ShapeDtypeStruct``) mirror of :meth:`alloc_buffers`
        — what an ahead-of-time caller lowers against instead of
        materializing zero buffers.  ``x_like`` may itself be abstract.

        Derived by shape-evaluating the unsharded allocation, so programs
        never implement it twice.  With ``shardings``, the buffers carry
        the same placement :meth:`alloc_buffers` commits them to — the
        ``(eps_buf, t_buf)`` convention every buffered program's
        ``buffer_init`` follows; a program with a different buffer layout
        must override this to place them itself."""
        shapes = jax.eval_shape(
            lambda x: self.alloc_buffers(x, cfg, None), x_like
        )
        if not shapes:
            return ()
        if shardings is None:
            return tuple(
                jax.ShapeDtypeStruct(s.shape, s.dtype) for s in shapes
            )
        placed = (shardings.eps_buf, shardings.t_buf)
        if len(shapes) != len(placed):
            raise NotImplementedError(
                f"{type(self).__name__} allocates {len(shapes)} buffers, "
                f"not the (eps_buf, t_buf) pair the base abstract_buffers "
                f"knows how to place — override abstract_buffers"
            )
        return tuple(
            jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h)
            for s, h in zip(shapes, placed)
        )

    def carry_pspecs(self, cfg: SolverConfig, mesh, *, batch=None, x_ndim=3):
        """PartitionSpecs for this program's scan carry on ``mesh``."""
        from repro.parallel.sharding import solver_carry_pspecs

        return solver_carry_pspecs(
            mesh, self, cfg, batch=batch, x_ndim=x_ndim
        )

    def carry_shardings(self, cfg: SolverConfig, mesh, *, batch=None, x_ndim=3):
        """``carry_pspecs`` bound to ``mesh`` as NamedShardings — what
        ``sample_scan`` takes as its ``shardings`` argument."""
        from repro.parallel.sharding import solver_carry_shardings

        return solver_carry_shardings(
            mesh, self, cfg, batch=batch, x_ndim=x_ndim
        )

    # ---- compiled entry --------------------------------------------------
    def sample_scan(
        self,
        eps_fn: EpsFn,
        x_init: Array,
        buffers: tuple[Array, ...],
        schedule: NoiseSchedule,
        cfg: SolverConfig,
        shardings=None,
        lengths: Array | None = None,
        steps: StepMask | None = None,
    ) -> SolverOutput:
        """The solver loop as one XLA program, with ``buffers`` threaded in
        explicitly so a jitting caller can donate them.

        ``lengths`` is the mixed-seq-len mask channel: a per-row ``(B,)``
        int32 vector of valid sequence lengths for a right-padded batch
        (None = every position valid).  Programs whose math is elementwise
        over positions may ignore it; programs with sequence reductions
        must mask them (see :meth:`supports_lengths`).

        ``steps`` is the mixed-NFE mask channel (see :class:`StepMask`):
        when given, the scan runs ``cfg.nfe``'s bucketed step count, each
        row reads its times from its own ``steps.ts`` row, and a row's
        carry freezes bitwise once ``i >= steps.active_steps[row]``.  Only
        programs returning True from :meth:`supports_steps` receive it."""
        raise NotImplementedError

    def sample(
        self,
        eps_fn: EpsFn,
        x_init: Array,
        schedule: NoiseSchedule,
        cfg: SolverConfig,
    ) -> SolverOutput:
        """Self-contained entry: allocates buffers, then runs the program
        (the ``get_solver(name)(...)`` back-compat surface)."""
        return self.sample_scan(
            eps_fn, x_init, self.alloc_buffers(x_init, cfg), schedule, cfg
        )

    # ---- aux scoping -----------------------------------------------------
    def scope_aux(
        self,
        aux: dict,
        off: int,
        batch: int,
        seq_len: int | None = None,
        n_steps: int | None = None,
        padded_steps: int | None = None,
    ) -> dict:
        """Scope solver diagnostics to one request's rows inside a fused
        padded batch, per :attr:`aux_row_axes` — and, for a seq-bucketed
        batch, to the request's valid positions per :attr:`aux_seq_axes`
        (``seq_len`` = the request's unpadded length; None = the batch ran
        at the request's exact shape).  A co-batched request must see only
        its own rows and positions — not its batch-mates' (tenant
        isolation), not the pad rows, and not the pad positions.

        Under NFE bucketing the scan ran ``padded_steps`` iterations but
        this request only took ``n_steps`` real ones, so every
        :attr:`aux_step_axes` entry drops its ``padded_steps - n_steps``
        inert tail along its step axis (preserving any off-by-one framing
        like the trajectory's initial-state frame)."""
        row_hit = {
            k: ax for k, ax in self.aux_row_axes.items()
            if aux.get(k) is not None
        }
        seq_hit = (
            {}
            if seq_len is None
            else {
                k: ax for k, ax in self.aux_seq_axes.items()
                if aux.get(k) is not None
            }
        )
        pad_steps = (
            0
            if n_steps is None or padded_steps is None
            else padded_steps - n_steps
        )
        step_hit = (
            {}
            if pad_steps <= 0
            else {
                k: ax for k, ax in self.aux_step_axes.items()
                if aux.get(k) is not None
            }
        )
        if not row_hit and not seq_hit and not step_hit:
            return aux
        scoped = dict(aux)
        for key, axis in row_hit.items():
            idx = (slice(None),) * axis + (slice(off, off + batch),)
            scoped[key] = scoped[key][idx]
        for key, axis in seq_hit.items():
            idx = (slice(None),) * axis + (slice(0, seq_len),)
            scoped[key] = scoped[key][idx]
        for key, axis in step_hit.items():
            keep = scoped[key].shape[axis] - pad_steps
            idx = (slice(None),) * axis + (slice(0, keep),)
            scoped[key] = scoped[key][idx]
        return scoped


def constrain_x(x: Array, shardings) -> Array:
    """Pin the latents' sharding inside a program (no-op off-mesh)."""
    if shardings is None:
        return x
    return jax.lax.with_sharding_constraint(x, shardings.x)


def constrain_buffers(
    eps_buf: Array, t_buf: Array, shardings
) -> tuple[Array, Array]:
    """Pin the eps/t history buffers' shardings (no-op off-mesh)."""
    if shardings is None:
        return eps_buf, t_buf
    return (
        jax.lax.with_sharding_constraint(eps_buf, shardings.eps_buf),
        jax.lax.with_sharding_constraint(t_buf, shardings.t_buf),
    )


def trajectory_aux(
    x_init: Array, traj_tail: Array | None, enabled: bool, dtype=None
) -> dict[str, Array]:
    """Assemble the ``trajectory`` aux from a scan's stacked per-step
    latents (ys), prepending the initial state."""
    if not enabled or traj_tail is None:
        return {}
    x0 = x_init if dtype is None else x_init.astype(dtype)
    return {"trajectory": jnp.concatenate([x0[None], traj_tail], axis=0)}
