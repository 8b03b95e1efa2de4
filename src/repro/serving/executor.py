"""Thread-safe fused-execution core of the diffusion sampling engine.

:class:`FusedExecutor` owns everything below the request queue: request
validation, bucket selection, mesh placement, the jit cache (one compiled
program per (solver, config, padded-batch, seq_len) bucket), chunk
execution, and per-request aux scoping.  The one request queue in front
of it is :class:`~repro.serving.scheduler.AsyncBatchedSampler`, whose
background drain thread fuses requests across arrival time and whose
``flush()`` runs everything queued on the caller's thread.

The executor is **solver-agnostic**: every registry solver is a
:class:`~repro.core.SolverProgram` (scan entry + donatable buffers + carry
shardings + request policy), so there are no solver-specific branches here.
``SampleRequest.solver`` routes each request to its program — one executor
serves a mixed ``era`` / ``ddim`` / ``dpm_solver_pp2m`` / ... stream, with
requests batched per solver (the jit cache and the scheduler's fuse queues
key on ``(solver, seq_len, nfe)``, so mixed traffic never cross-contaminates
a bucket).

**Seq-len bucketing** (``seq_buckets=(64, 128, ...)``): requests whose
``seq_len`` differ can fuse into one compiled batch.  Each request's rows
are right-padded on the host from their exact length to the smallest bucket
that fits, a per-row ``lengths`` vector rides through the compiled program,
the denoiser masks pad keys out of every attention softmax
(``DiffusionLM.eps(lengths=...)``), and length-aware solver programs mask
their own sequence reductions (ERA's ERS error norms, which accumulate
positions sequentially so padding cannot re-associate them).  Padded runs
are therefore *mathematically* identical to exact-shape runs everywhere,
and **bit-identical** wherever the denoiser itself adds no
padded-length reductions — positionwise denoisers (the property walls),
and in practice the attention stacks on the CPU test shapes; the
guaranteed bar for attention denoisers is the 1e-6 parity wall, since XLA
may re-associate a softmax reduction over a padded key axis.
The group key then carries the *bucketed* length, bounding the compile
count by the bucket ladder rather than by distinct seq_lens.  Bucketing
silently falls back to exact-shape grouping per solver when masking can't
be guaranteed: non-fusable configs (exact-size runs can't pad), programs
that don't support lengths, or denoisers whose block stack isn't maskable
(``DiffusionLM.supports_length_masking``).

**NFE bucketing** (``nfe_buckets=(16, 32, ...)``): requests whose ``nfe``
differ can also fuse into one compiled batch.  The fuse key carries the
request's NFE *bucket* (the smallest ladder entry >= its nfe), the
compiled scan runs the bucket's step count, and a per-row
:class:`~repro.core.program.StepMask` rides through the program: each
row carries its own step count and its own time grid (the exact
``step_times`` floats its unpadded run uses, terminal-padded), and a row
whose steps are spent freezes **bitwise** — its remaining scan iterations
leave its entire carry unchanged.  The jit cache and warmup grid are then
bounded by ``|solvers| x |seq_buckets| x |nfe_buckets|`` instead of by
distinct request NFEs.  With a ladder configured, *all* of a
steps-capable solver's traffic routes through the step-masked program
(uniform batches run fully active) — the bitwise invariance bar holds
between step-masked runs at one padded batch bucket, so the engine never
mixes the scalar-time static path into a bucketed stream.  Per-solver
fallback to exact-NFE grouping mirrors seq bucketing: non-fusable
configs, and programs without a step-masked scan
(``SolverProgram.supports_steps``; e.g. the Python-unrolled
``dpm_solver_fast`` plan), counted on ``sampler_masked_fallback_total``
with ``impl="nfe-bucketing"``.  ``sampler_nfe_padding_rows_total``
counts rows that ran a larger bucket than they asked for (the padding
waste a too-coarse ladder buys).

All mutable state (jit cache, shardings cache, param replication cache) is
guarded by one re-entrant lock, and chunk execution itself is serialized
under the same lock — a drain thread and ``flush()`` callers, of one
scheduler or several, can share an executor without double-compiling a
bucket or interleaving donated-buffer executions.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import threading
import time
import weakref
from concurrent.futures import Future, InvalidStateError
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import NoiseSchedule, SolverConfig, get_program
from repro.core.program import SolverProgram, StepMask
from repro.models import attention as _attention
from repro.models.diffusion import DiffusionLM
from repro.parallel.sharding import (
    ParamReplicator,
    dp_size,
    per_batch_shard,
    round_to_dp,
)
from repro.serving import result_keys as K
from repro.serving.compile_cache import disk_cache_hits
from repro.serving.metrics import MetricsRegistry

Array = jax.Array

#: ``jax.random.PRNGKey`` folds the seed into an int64 — anything outside
#: this range raises OverflowError at *drain* time, inside a fused batch,
#: which would fail every co-batched request.  validate() rejects it at
#: submit instead (JSON ints are unbounded, so the wire can send anything).
SEED_MIN = -(2**63)
SEED_MAX = 2**63 - 1

#: Server-side ceilings on the wire-exposed resource fields.  Without
#: them a single request (``batch=10**8``, ``nfe=10**7``, or an enormous
#: ``seq_len`` on an engine with no seq-bucket ladder) forces a multi-GB
#: host allocation, a pathological XLA compile, or an unbounded jit cache
#: at drain time — after admission, where the failure takes down
#: batch-mates.  Engines accept ``None`` to opt out (trusted in-process
#: callers); the defaults are far above every serving shape in the repo.
DEFAULT_MAX_BATCH = 4096
DEFAULT_MAX_NFE = 1000
DEFAULT_MAX_SEQ_LEN = 8192


@dataclasses.dataclass(frozen=True)
class SampleRequest:
    """One sampling request, as submitted to any serving entry point.

    Immutable and hashable — safe to share across threads, reuse for
    resubmission, and use in test fixtures.  Requests are validated at
    ``submit()`` (never at drain time): unknown ``solver`` names, per-solver
    ``(batch, nfe)`` constraints, seeds outside the int64 range
    ``PRNGKey`` accepts, the engine's ``max_batch`` / ``max_nfe`` /
    ``max_seq_len`` resource ceilings, and — when the engine has seq
    buckets — ``seq_len`` above the largest bucket are all rejected there,
    so an invalid request can never poison a fused batch for its
    co-batched neighbours.

    ``seed`` fully determines the request's initial noise: ``x_T`` is drawn
    as ``jax.random.normal(PRNGKey(seed), (batch, seq_len, d_model))``
    regardless of which fused batch, seq bucket, or mesh the request lands
    in — this is what the arrival-determinism and padding-invariance walls
    pin down.

    ``priority`` and ``deadline_ms`` are scheduling hints honored by the
    continuous-batching drain policy (and carried verbatim over the wire
    by the front door): when a fuse-group queue launches, higher-priority
    requests board the batch first; a request still queued
    ``deadline_ms`` after submit fails fast with
    :class:`~repro.serving.scheduler.DeadlineExceededError` instead of
    occupying a fused batch.  Neither field affects results — a request's
    ``x0`` depends only on ``(seed, seq_len, nfe, solver)``.
    """

    batch: int
    seq_len: int
    nfe: int = 10
    # registry solver this request routes to; None = the engine's default
    # solver.  Unknown names are rejected at submit(), not drain time.
    solver: str | None = None
    seed: int = 0
    # scheduling hints (continuous-batching drain policy; see class doc)
    priority: int = 0
    deadline_ms: float | None = None


@dataclasses.dataclass
class SampleResult:
    """Per-request output of a drained batch.

    Delivered through the request's Future by whichever thread drained the
    fused batch.  ``x0`` and every ``aux`` entry are scoped to this
    request alone: its own rows (no batch-mates, no pad rows) and — under
    seq bucketing — its own ``seq_len`` positions (no pad positions).
    ``batch_wall_s`` / ``padded_batch`` / ``padded_seq_len`` describe the
    fused batch the request rode in and are shared by its batch-mates;
    ``latency_s`` is this request's own submit→result wall time.

    This is the **one** result type across the stack: the scheduler's
    futures and the front door's wire schema carry exactly this
    dataclass.  :attr:`info` flattens the telemetry fields plus ``aux``
    into one dict under the documented :mod:`~repro.serving.result_keys`
    keys.
    """

    x0: Array                # (batch, seq_len, d_model)
    aux: dict[str, Any]      # solver diagnostics, scoped to this request's
                             # rows (per-sample histories / trajectories
                             # exclude batch-mates and pad rows) and valid
                             # positions (trajectories exclude pad tail)
    latency_s: float         # submit -> result wall time
    batch_wall_s: float      # wall time of the fused batch this rode in
    padded_batch: int        # batch bucket size the batch ran at
    padded_seq_len: int      # seq length the batch ran at (== seq bucket
                             # under seq bucketing, else the exact seq_len)
    padded_nfe: int          # NFE budget the batch scanned to (== nfe
                             # bucket under NFE bucketing, else exact nfe;
                             # this request's surplus steps were inert)

    @property
    def info(self) -> dict[str, Any]:
        """Engine telemetry + solver ``aux`` as one dict, keyed by the
        :mod:`~repro.serving.result_keys` constants."""
        return {
            K.WALL_S: self.batch_wall_s,
            K.LATENCY_S: self.latency_s,
            K.PADDED_BATCH: self.padded_batch,
            K.PADDED_SEQ_LEN: self.padded_seq_len,
            K.PADDED_NFE: self.padded_nfe,
            **self.aux,
        }


# A queued request: (ticket, request, submit-time), as the scheduler's
# per-group queues carry it and the executor runs it.
QueueItem = tuple[int, SampleRequest, float]


def resolve_future(fut: Future, result=None, exception=None) -> None:
    """Resolve a delivery future, tolerating client-side cancellation.

    A waiter that gave up (``fut.cancel()`` after a result() timeout) leaves
    the future in CANCELLED state; ``set_result``/``set_exception`` on it
    raises InvalidStateError, which must not take down the scheduler — the
    other requests in the batch still have live waiters.
    """
    try:
        if exception is not None:
            fut.set_exception(exception)
        else:
            fut.set_result(result)
    except InvalidStateError:
        pass


def _denoiser_scope(eps_fn):
    """``eps_fn`` with its ops under the ``denoiser`` name scope (op
    metadata only: the same ops and fusions), so a device trace splits
    each NFE into denoiser and solver time."""

    def scoped(x, t):
        with jax.named_scope("denoiser"):
            return eps_fn(x, t)

    return scoped


class FusedExecutor:
    """Fused-chunk runner behind the scheduler's request queue.

    Thread-safety contract: every public method may be called from any
    thread.  Reads of the jit / shardings / replication caches and chunk
    execution itself serialize under one re-entrant lock, so ``flush()``
    callers and the scheduler's drain thread share compiled
    buckets without double-compiling or interleaving donated-buffer
    executions; ``run_chunk`` blocks for the whole fused execution
    (device-synchronous — it calls ``block_until_ready``).

    ``seq_buckets`` (e.g. ``(64, 128, 256, 512)``) opts into mixed-seq-len
    fusion: see the module docstring for the masking contract and the
    exact-shape fallbacks.  ``None`` (default) groups by exact ``seq_len``.
    """

    def __init__(
        self,
        dlm: DiffusionLM,
        schedule: NoiseSchedule,
        solver: str = "era",
        solver_config: SolverConfig | None = None,
        batch_buckets: tuple[int, ...] | None = (1, 8, 64),
        mesh: Mesh | None = None,
        seq_buckets: tuple[int, ...] | None = None,
        nfe_buckets: tuple[int, ...] | None = None,
        metrics: MetricsRegistry | None = None,
        max_batch: int | None = DEFAULT_MAX_BATCH,
        max_nfe: int | None = DEFAULT_MAX_NFE,
        max_seq_len: int | None = DEFAULT_MAX_SEQ_LEN,
    ):
        self.dlm = dlm
        self.max_batch = max_batch
        self.max_nfe = max_nfe
        self.max_seq_len = max_seq_len
        self.schedule = schedule
        self.solver_name = solver
        # per-solver engine configs: the constructor pins the default
        # solver's config; other solvers a request routes to lazily get
        # their program's engine default (e.g. per-sample ERS for ERA)
        self._configs: dict[str, SolverConfig] = {}
        self._configs[solver] = (
            get_program(solver).engine_config()
            if solver_config is None
            else solver_config
        )
        self.mesh = mesh
        self.dp = dp_size(mesh) if mesh is not None else 1
        if batch_buckets:
            # every fused batch must split evenly over the data axes, so
            # buckets round up to dp multiples (1/8/64 on dp=8 -> 8/64)
            batch_buckets = sorted({round_to_dp(b, mesh) for b in batch_buckets})
        self.batch_buckets = tuple(batch_buckets) if batch_buckets else None
        self.seq_buckets = tuple(sorted(seq_buckets)) if seq_buckets else None
        self.nfe_buckets = tuple(sorted(nfe_buckets)) if nfe_buckets else None
        # per-solver verdict: may this solver's traffic seq-bucket at all?
        self._seq_masked: dict[str, bool] = {}
        # per-solver verdict: may this solver's traffic nfe-bucket at all?
        self._nfe_masked: dict[str, bool] = {}
        # host-side (solver, nfe) -> per-row time grid cache (the StepMask
        # rows every chunk of that solver/nfe reuses)
        self._row_times: dict[tuple[str, int], np.ndarray] = {}
        self._jitted: dict[Any, Any] = {}
        self._shardings_cache: dict[Any, Any] = {}
        self._replicate = ParamReplicator(mesh) if mesh is not None else None
        self._lock = threading.RLock()
        # one registry per executor: the scheduler and front door instrument
        # into the same scrape (get-or-create registration, so sharing is
        # idempotent).  Everything below is cheap host-side accounting.
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._m_compile_programs = self.metrics.counter(
            "sampler_compile_programs_total",
            "program acquisitions by source: memory (in-process "
            "executable cache), disk (persistent compilation cache), "
            "fresh (real XLA compile)",
        )
        self._m_compile_wall = self.metrics.histogram(
            "sampler_compile_seconds",
            "wall time of each lower+compile at the AOT boundary",
            buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0),
        )
        self._m_warmup_total = self.metrics.gauge(
            "sampler_warmup_grid_programs",
            "programs in the configured warmup grid (0 until warmup() runs)",
        )
        self._m_warmup_done = self.metrics.gauge(
            "sampler_warmup_compiled_programs",
            "warmup grid programs compiled so far",
        )
        self._m_warmup_inflight = self.metrics.gauge(
            "sampler_warmup_in_progress",
            "1 while warmup() is compiling the grid",
        )
        self._m_warmup_wall = self.metrics.gauge(
            "sampler_warmup_duration_seconds",
            "wall time of the last completed warmup()",
        )
        self._m_warmup_programs = self.metrics.counter(
            "sampler_warmup_programs_total",
            "programs compiled by warmup(), by solver",
        )
        # plain-python mirror of the source-labelled compile counters, for
        # callers (tests) that want exact counts without
        # scraping label combinations out of the registry
        self._compile_counts = {"fresh": 0, "disk": 0, "memory": 0}
        self._warmup_state: dict[str, Any] = {"state": "none", "done": 0, "total": 0}
        self._m_batches = self.metrics.counter(
            "sampler_batches_total", "fused batches executed"
        )
        self._m_rows = self.metrics.counter(
            "sampler_batch_rows_total",
            "real (non-pad) request rows executed across fused batches",
        )
        self._m_occupancy = self.metrics.histogram(
            "sampler_fuse_occupancy_ratio",
            "real rows / padded rows per fused batch (1.0 = no pad waste)",
            buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0),
        )
        self._m_wall = self.metrics.histogram(
            "sampler_batch_wall_seconds",
            "host-clock time per fused batch, from dispatch to ready",
        )
        self._m_assembly = self.metrics.histogram(
            "sampler_assembly_seconds",
            "host-clock time per fused batch from the chunk's start to the "
            "program call: noise draw, host copy, padding, step mask, upload",
        )
        # the permanent canary that masked (mixed-seq-len) traffic regressed
        # off the fast path.  Two sources feed it: sdpa rewriting a requested
        # fast impl to chunked (impl = the requested attention kernel; fires
        # at trace time, one count per compiled program that materialized on
        # the slow path), and the engine's seq-bucketing verdict falling back
        # to exact-shape grouping (impl = "seq-bucketing"; once per solver).
        # A healthy dense/pallas deployment holds this at zero.
        self._m_masked_fallback = self.metrics.counter(
            "sampler_masked_fallback_total",
            "masked-traffic fast-path fallbacks by requested impl and "
            "reason: sdpa fast-kernel rewrites to chunked, and engine "
            "seq-bucketing / nfe-bucketing verdicts that force exact-shape "
            "or exact-NFE grouping",
        )
        # NFE-padding waste: real request rows that ran a larger nfe bucket
        # than they asked for (their tail steps are per-row frozen no-ops).
        # A ladder tuned to the traffic holds this near zero.
        self._m_nfe_pad_rows = self.metrics.counter(
            "sampler_nfe_padding_rows_total",
            "request rows padded to a larger NFE bucket than requested "
            "(per-row step masks freeze their surplus steps)",
        )
        # weakref so a dropped executor never keeps itself alive through the
        # module-level observer list; a dead ref unregisters itself on fire
        self_ref = weakref.ref(self)

        def _on_sdpa_fallback(impl: str, reason: str) -> None:
            ex = self_ref()
            if ex is None:
                _attention.unregister_fallback_observer(_on_sdpa_fallback)
                return
            ex._m_masked_fallback.inc(impl=impl, reason=reason)

        _attention.register_fallback_observer(_on_sdpa_fallback)
        self._sdpa_fallback_observer = _on_sdpa_fallback

    # ---- solver routing --------------------------------------------------
    def resolve_solver(self, req: SampleRequest) -> str:
        """The registry name this request routes to."""
        return req.solver or self.solver_name

    def program_for(self, solver: str | None) -> SolverProgram:
        return get_program(solver or self.solver_name)

    def config_for(self, solver: str | None) -> SolverConfig:
        name = solver or self.solver_name
        cfg = self._configs.get(name)
        if cfg is None:
            cfg = self._configs[name] = get_program(name).engine_config()
        return cfg

    @property
    def solver_config(self) -> SolverConfig:
        """The engine's default solver's config (back-compat surface)."""
        return self.config_for(self.solver_name)

    # ---- request policy --------------------------------------------------
    @property
    def fusable(self) -> bool:
        """Can strangers (and pad rows) share a batch under the default
        solver's config?  (Per-request: :meth:`fusable_for`.)"""
        return self.fusable_for(None)

    def fusable_for(self, solver: str | None) -> bool:
        return self.program_for(solver).fusable(self.config_for(solver))

    @property
    def max_bucket(self) -> int | None:
        return self.batch_buckets[-1] if self.batch_buckets else None

    # ---- seq-len bucketing ----------------------------------------------
    def seq_masked(self, solver: str | None) -> bool:
        """Does this solver's traffic fuse across seq_lens (padded +
        length-masked), or fall back to exact-shape grouping?

        Requires *every* layer of the masking contract: an engine bucket
        ladder, a fusable config (exact-size runs cannot pad), a program
        that guarantees pad positions never leak into valid ones
        (``SolverProgram.supports_lengths``), and a denoiser whose block
        stack can be masked exactly
        (``DiffusionLM.supports_length_masking``)."""
        if not self.seq_buckets:
            return False
        name = solver or self.solver_name
        verdict = self._seq_masked.get(name)
        if verdict is None:
            program = self.program_for(name)
            cfg = self.config_for(name)
            fusable = program.fusable(cfg)
            lengths_ok = program.supports_lengths(cfg)
            maskable = bool(getattr(self.dlm, "supports_length_masking", False))
            verdict = self._seq_masked[name] = (
                fusable and lengths_ok and maskable
            )
            if not verdict:
                # exact-shape grouping is the engine-level slow path; count
                # it on the same canary the sdpa kernel fallbacks feed
                reason = (
                    "non-fusable-config" if not fusable
                    else "program-no-lengths" if not lengths_ok
                    else "denoiser-unmaskable"
                )
                self._m_masked_fallback.inc(impl="seq-bucketing", reason=reason)
        return verdict

    def bucket_seq(self, n: int) -> int:
        """Smallest seq bucket >= n (requests above the ladder are rejected
        at submit, so this never falls off the end)."""
        for s in self.seq_buckets:
            if n <= s:
                return s
        raise ValueError(
            f"seq_len {n} exceeds the largest seq bucket "
            f"{self.seq_buckets[-1]}"
        )

    # ---- NFE bucketing ---------------------------------------------------
    def nfe_masked(self, solver: str | None) -> bool:
        """Does this solver's traffic fuse across NFEs (scanning to the
        bucketed step count under a per-row step mask), or fall back to
        exact-NFE grouping?

        Requires an engine nfe-bucket ladder, a fusable config (exact-size
        runs cannot pad — in steps any more than in rows), and a program
        with a step-masked scan (``SolverProgram.supports_steps``: per-row
        times through every coefficient, spent rows frozen bitwise)."""
        if not self.nfe_buckets:
            return False
        name = solver or self.solver_name
        verdict = self._nfe_masked.get(name)
        if verdict is None:
            program = self.program_for(name)
            cfg = self.config_for(name)
            fusable = program.fusable(cfg)
            steps_ok = program.supports_steps(cfg)
            verdict = self._nfe_masked[name] = fusable and steps_ok
            if not verdict:
                # exact-NFE grouping is the engine-level slow path; count it
                # on the same canary the seq-bucketing fallbacks feed
                reason = (
                    "non-fusable-config" if not fusable
                    else "program-no-steps"
                )
                self._m_masked_fallback.inc(impl="nfe-bucketing", reason=reason)
        return verdict

    def bucket_nfe(self, n: int) -> int:
        """Smallest nfe bucket >= n (requests above the ladder are rejected
        at submit, so this never falls off the end)."""
        for b in self.nfe_buckets:
            if n <= b:
                return b
        raise ValueError(
            f"nfe {n} exceeds the largest nfe bucket {self.nfe_buckets[-1]}"
        )

    def group_key(self, req: SampleRequest) -> tuple[str, int, int]:
        """The fuse-group key ``(solver, seq, nfe)`` — what the scheduler's
        queues and the jit cache batch by.
        Under seq bucketing ``seq`` is the request's seq *bucket*, so
        mixed-length traffic shares a group and the compile count is
        bounded by the ladder; otherwise it is the exact ``seq_len``.
        Under NFE bucketing ``nfe`` is likewise the request's NFE *bucket*,
        so mixed-NFE traffic shares a group (and one compiled, step-masked
        program); otherwise it is the exact ``nfe``."""
        solver = self.resolve_solver(req)
        seq = (
            self.bucket_seq(req.seq_len)
            if self.seq_masked(solver)
            else req.seq_len
        )
        nfe = (
            self.bucket_nfe(req.nfe)
            if self.nfe_masked(solver)
            else req.nfe
        )
        return (solver, seq, nfe)

    def validate(self, req: SampleRequest) -> None:
        """Reject an invalid request at submit time, not drain time — a bad
        request must not poison the queue for its co-batched neighbours.
        Unknown solver names fail here; per-solver (batch, nfe) constraints
        live in each program's ``validate``."""
        if req.batch < 1:
            raise ValueError(f"batch must be >= 1, got {req.batch}")
        if self.max_batch is not None and req.batch > self.max_batch:
            raise ValueError(
                f"batch {req.batch} exceeds the engine's max_batch "
                f"{self.max_batch}"
            )
        if req.seq_len < 1:
            raise ValueError(f"seq_len must be >= 1, got {req.seq_len}")
        if self.max_nfe is not None and req.nfe > self.max_nfe:
            raise ValueError(
                f"nfe {req.nfe} exceeds the engine's max_nfe {self.max_nfe}"
            )
        if self.nfe_buckets and req.nfe > self.nfe_buckets[-1]:
            # same serving contract as the seq ladder: an over-budget
            # request would need its own compiled step count, which is
            # exactly the fragmentation NFE bucketing exists to prevent
            raise ValueError(
                f"nfe {req.nfe} exceeds the largest nfe bucket "
                f"{self.nfe_buckets[-1]}; extend nfe_buckets or submit "
                f"requests within the ladder"
            )
        if self.seq_buckets and req.seq_len > self.seq_buckets[-1]:
            # the bucket ladder is the engine's serving contract: an
            # over-long request would need its own compiled shape, which is
            # exactly the fragmentation bucketing exists to prevent
            raise ValueError(
                f"seq_len {req.seq_len} exceeds the largest seq bucket "
                f"{self.seq_buckets[-1]}; extend seq_buckets or submit "
                f"requests within the ladder"
            )
        if (
            not self.seq_buckets
            and self.max_seq_len is not None
            and req.seq_len > self.max_seq_len
        ):
            # no ladder bounds the compile cache here — every distinct
            # seq_len compiles its own program, so cap the axis outright
            raise ValueError(
                f"seq_len {req.seq_len} exceeds the engine's max_seq_len "
                f"{self.max_seq_len}"
            )
        if not isinstance(req.seed, int) or isinstance(req.seed, bool):
            raise ValueError(f"seed must be an int, got {req.seed!r}")
        if not SEED_MIN <= req.seed <= SEED_MAX:
            # PRNGKey(seed) overflows outside int64 — at drain time, inside
            # a fused batch, failing every co-batched neighbour
            raise ValueError(
                f"seed must fit in a signed 64-bit integer "
                f"({SEED_MIN} <= seed <= {SEED_MAX}), got {req.seed}"
            )
        if not isinstance(req.priority, int) or isinstance(req.priority, bool):
            raise ValueError(
                f"priority must be an int, got {req.priority!r}"
            )
        if req.deadline_ms is not None:
            ok = (
                isinstance(req.deadline_ms, (int, float))
                and not isinstance(req.deadline_ms, bool)
                and math.isfinite(req.deadline_ms)
                and req.deadline_ms > 0
            )
            if not ok:
                raise ValueError(
                    f"deadline_ms must be a positive finite number of "
                    f"milliseconds (or None), got {req.deadline_ms!r}"
                )
        program = self.program_for(req.solver)  # unknown solver raises here
        program.validate(req, self.config_for(req.solver), dp=self.dp)

    def pack(self, items: list[QueueItem]) -> list[tuple[list[QueueItem], bool]]:
        """Split same-(solver, seq_len, nfe) items into executable chunks.

        Fusable configs pack greedily up to the largest batch bucket;
        non-fusable configs get one exact-size (unpadded) chunk per request.
        Returns ``(chunk, pad)`` pairs.
        """
        if not items:
            return []
        if not self.fusable_for(items[0][1].solver):
            return [([item], False) for item in items]
        chunks: list[tuple[list[QueueItem], bool]] = []
        chunk: list[QueueItem] = []
        total = 0
        for item in items:
            b = item[1].batch
            if chunk and self.max_bucket and total + b > self.max_bucket:
                chunks.append((chunk, True))
                chunk, total = [], 0
            chunk.append(item)
            total += b
        if chunk:
            chunks.append((chunk, True))
        return chunks

    # ---- fused execution -----------------------------------------------
    def bucket_batch(self, n: int) -> int:
        if not self.batch_buckets:
            return round_to_dp(n, self.mesh)
        for b in self.batch_buckets:
            if n <= b:
                return b
        # oversize request: exact-size compile (dp-rounded on a mesh)
        return round_to_dp(n, self.mesh)

    # ---- mesh placement ------------------------------------------------
    def _shardings(self, program: SolverProgram, cfg: SolverConfig, batch: int):
        """Carry shardings for a padded batch (None off-mesh), via the
        program's carry-pspec hook."""
        if self.mesh is None:
            return None
        key = (batch, program.per_sample_state(cfg))
        if key not in self._shardings_cache:
            self._shardings_cache[key] = program.carry_shardings(
                cfg, self.mesh, batch=batch
            )
        return self._shardings_cache[key]

    def run_chunk(
        self,
        params,
        seq_len: int,
        nfe: int,
        chunk: list[QueueItem],
        results: dict[int, SampleResult],
        pad: bool = True,
    ) -> None:
        """Run one chunk as a single fused program; fill ``results`` by
        ticket.  All requests in a chunk share one group key (the
        scheduler's queues key on it): one solver, and one seq length —
        exact, or the shared seq bucket ``seq_len`` each request's rows are
        right-padded up to.  Serialized under the executor lock — safe
        to call from the scheduler's drain thread and ``flush()``
        callers concurrently; blocks until the fused result is on host.

        Profiler spans (no-ops unless a ``jax.profiler`` session is active):
        ``sampler.chunk`` covers the call, the wait for the lock included,
        and carries the chunk's tickets and group key; inside it
        ``sampler.assemble`` (up to the program call), ``sampler.execute``
        (the interval ``batch_wall_s`` times) and ``sampler.scatter``
        (per-request results)."""
        solver = self.resolve_solver(chunk[0][1])
        with jax.profiler.TraceAnnotation(
            "sampler.chunk",
            tickets=" ".join(str(ticket) for ticket, _, _ in chunk),
            key=f"{solver}/{seq_len}/{nfe}",
        ), self._lock:
            self._run_chunk_locked(params, seq_len, nfe, chunk, results, pad)

    def _step_times_host(self, solver: str, nfe: int) -> np.ndarray:
        """The host-side per-row time grid for one (solver, nfe) — the
        exact ``step_times`` floats an unpadded run of that budget steps
        through, cached so chunk assembly never re-derives a grid."""
        key = (solver, nfe)
        ts = self._row_times.get(key)
        if ts is None:
            program = self.program_for(solver)
            cfg = self.config_for(solver)
            ts = self._row_times[key] = np.asarray(
                program.step_times(self.schedule, nfe, cfg), np.float32
            )
        return ts

    def _run_chunk_locked(self, params, seq_len, nfe, chunk, results, pad):
        t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation("sampler.assemble"):
            d = self.dlm.config.d_model
            solver = self.resolve_solver(chunk[0][1])
            program = self.program_for(solver)
            masked = self.seq_masked(solver)
            stepped = self.nfe_masked(solver)
            total = sum(req.batch for _, req, _ in chunk)
            padded = self.bucket_batch(total) if pad else total
            # assemble the batch on the host: eager jnp.concatenate would XLA-
            # compile once per chunk *composition* (request sizes + pad rows),
            # and under continuous batching every drain can have a new
            # composition — 40-90ms of compile against a ~10ms solver run.
            # Per-request noise stays jax.random (seed-deterministic across
            # batch compositions); numpy does the composition-shaped work.
            # Seq bucketing: each request's noise is drawn at its *exact*
            # (batch, seq_len, d) shape — identical to its solo run — and
            # right-padded with zero rows up to the chunk's seq bucket.
            parts = []
            row_lengths: list[int] = []
            for _, req, _ in chunk:
                noise = np.asarray(
                    jax.random.normal(
                        jax.random.PRNGKey(req.seed),
                        (req.batch, req.seq_len, d),
                        jnp.float32,
                    )
                )
                if req.seq_len < seq_len:
                    noise = np.concatenate(
                        [
                            noise,
                            np.zeros(
                                (req.batch, seq_len - req.seq_len, d), np.float32
                            ),
                        ],
                        axis=1,
                    )
                parts.append(noise)
                row_lengths += [req.seq_len] * req.batch
            if padded > total:
                parts.append(np.zeros((padded - total, seq_len, d), np.float32))
                # pad rows are fully "valid": their lanes run ordinary (masked)
                # math on zeros and are sliced away, never a 0-length edge case
                row_lengths += [seq_len] * (padded - total)
            x_init = jnp.asarray(np.concatenate(parts, axis=0))
            lengths = (
                jnp.asarray(np.asarray(row_lengths, np.int32)) if masked else None
            )

            cfg = dataclasses.replace(self.config_for(solver), nfe=nfe)
            # mixed-NFE fusion: assemble the per-row StepMask on the host.  The
            # chunk's ``nfe`` is the group's NFE *bucket*; each request row
            # carries its own step count and its own exact-NFE time grid
            # (terminal-padded to the bucket's step count), so its active
            # prefix computes the very floats its unpadded run would.  Batch
            # pad rows run fully active on the bucket grid — ordinary masked
            # math on zeros, never a 0-step edge case.
            steps = None
            if stepped:
                cap = program.steps_for_nfe(nfe, cfg)
                acts: list[int] = []
                rows_ts: list[np.ndarray] = []
                nfe_padded_rows = 0
                for _, req, _ in chunk:
                    n_r = program.steps_for_nfe(req.nfe, cfg)
                    ts_r = self._step_times_host(solver, req.nfe)
                    if n_r < cap:
                        ts_r = np.concatenate(
                            [ts_r, np.full((cap - n_r,), ts_r[-1], np.float32)]
                        )
                        nfe_padded_rows += req.batch
                    acts += [n_r] * req.batch
                    rows_ts += [ts_r] * req.batch
                if padded > total:
                    bucket_ts = self._step_times_host(solver, nfe)
                    acts += [cap] * (padded - total)
                    rows_ts += [bucket_ts] * (padded - total)
                steps = StepMask(
                    active_steps=jnp.asarray(np.asarray(acts, np.int32)),
                    ts=jnp.asarray(np.stack(rows_ts, axis=0)),
                )
                if nfe_padded_rows:
                    self._m_nfe_pad_rows.inc(nfe_padded_rows, solver=solver)
            shardings = self._shardings(program, cfg, padded)
            if shardings is not None:
                x_init = jax.device_put(x_init, shardings.x)
                if lengths is not None:
                    lengths = jax.device_put(lengths, shardings.lengths)
                if steps is not None:
                    steps = StepMask(
                        active_steps=jax.device_put(
                            steps.active_steps, shardings.active_steps
                        ),
                        ts=jax.device_put(steps.ts, shardings.step_ts),
                    )
                params = self._replicate(params)
            run = self._jit_for(solver, cfg, padded, seq_len, masked, stepped, params)
        with jax.profiler.TraceAnnotation("sampler.execute"):
            t0 = time.perf_counter()
            buffers = program.alloc_buffers(x_init, cfg, shardings)
            x0, aux = run(params, x_init, lengths, steps, *buffers)
            x0 = jax.block_until_ready(x0)
            wall = time.perf_counter() - t0
        self._m_assembly.observe(t0 - t_start, solver=solver)
        self._m_batches.inc()
        self._m_rows.inc(total)
        self._m_occupancy.observe(total / padded, solver=solver)
        self._m_wall.observe(wall, solver=solver)
        with jax.profiler.TraceAnnotation("sampler.scatter"):
            done = time.perf_counter()
            off = 0
            for ticket, req, t_submit in chunk:
                x0_req = x0[off : off + req.batch]
                scope_seq = None
                if masked and req.seq_len < seq_len:
                    x0_req = x0_req[:, : req.seq_len]
                    scope_seq = req.seq_len
                results[ticket] = SampleResult(
                    x0=x0_req,
                    aux=program.scope_aux(
                        aux, off, req.batch, seq_len=scope_seq,
                        # under NFE bucketing the scan ran the bucket's step
                        # count; step-stacked aux drops this request's inert
                        # tail so histories match the unpadded run's shape
                        n_steps=(
                            program.steps_for_nfe(req.nfe, cfg)
                            if stepped else None
                        ),
                        padded_steps=(
                            program.steps_for_nfe(nfe, cfg) if stepped else None
                        ),
                    ),
                    latency_s=done - t_submit,
                    batch_wall_s=wall,
                    padded_batch=padded,
                    padded_seq_len=seq_len,
                    padded_nfe=nfe,
                )
                off += req.batch

    def _jit_for(
        self, solver: str, cfg: SolverConfig, batch: int, seq_len: int,
        masked: bool, stepped: bool, params,
    ):
        """One compiled executable per (solver, config, padded-batch,
        seq_len) bucket — with ``seq_len`` a ladder bucket under seq
        bucketing, so the cache size is bounded by the ladder, not by
        distinct request lengths.  The per-row ``lengths`` vector is a
        runtime *argument* of the compiled program (None on unmasked
        buckets), so any mix of request lengths reuses one executable.

        Programs are compiled ahead of time (``lower().compile()`` at this
        boundary, in :meth:`_compile`) rather than deferred to a lazy
        ``jax.jit`` wrapper's first call — so ``warmup()`` can populate
        the same cache from abstract shapes without sampling, and a cache
        miss here *is* the compile, correctly labelled ``disk`` vs
        ``fresh``.

        Under NFE bucketing the per-row :class:`StepMask` is likewise a
        runtime argument (None on unstepped buckets): ``cfg.nfe`` is the
        group's NFE *bucket*, so any mix of request NFEs within the bucket
        reuses one executable and the cache stays bounded by
        ``|solvers| x |seq_buckets| x |nfe_buckets|``.

        Mesh-aware: the key carries the data-parallel size so an engine
        rebuilt on a different mesh never aliases a cached program; it also
        carries ``masked`` / ``stepped`` so an exact-shape or exact-NFE
        group never aliases a masked/step-masked program of the same
        shape."""
        key = (solver, cfg, batch, seq_len, self.dp, masked, stepped)
        cached = self._jitted.get(key)
        if cached is not None:
            self._m_compile_programs.inc(solver=solver, source="memory")
            self._compile_counts["memory"] += 1
            return cached
        compiled, _ = self._compile(key, params)
        return compiled

    @functools.partial(jax.profiler.annotate_function, name="sampler.compile")
    def _compile(self, key, params):
        """Lower and compile one bucket program from abstract shapes — no
        sampling, no params traffic — and cache the executable under
        ``key``.  Returns ``(compiled, source)`` with ``source`` ``"disk"``
        (served by the persistent compilation cache) or ``"fresh"`` (real
        XLA compile).  Callers hold the executor lock.  A profiler span,
        ``sampler.compile``, so a compile names its own gap."""
        solver, cfg, batch, seq_len, _, masked, stepped = key
        program = self.program_for(solver)
        shardings = self._shardings(program, cfg, batch)

        def run(params, x_init, lengths, steps, *buffers):
            eps_fn = _denoiser_scope(self._eps_fn(params, lengths, shardings))
            out = program.sample_scan(
                eps_fn,
                x_init,
                buffers,
                self.schedule,
                cfg,
                shardings=shardings,
                lengths=lengths,
                steps=steps,
            )
            return out.x0, out.aux

        # donate x + the program's history buffers so XLA reuses them
        # in place (CPU ignores donation and would warn, so gate it);
        # args 2/3 (lengths, steps) are never donated
        nbuf = program.num_buffers(cfg)
        donate = (
            (1,) + tuple(range(4, 4 + nbuf))
            if jax.default_backend() != "cpu"
            else ()
        )
        avals = self._abstract_inputs(
            program, cfg, batch, seq_len, masked, stepped, params, shardings
        )
        # XLA exposes no per-call "came from the persistent cache" signal;
        # the hit counter moving across this compile is that signal.  Take
        # the baseline *after* lowering: tracing evaluates `timesteps`
        # grids eagerly (`ensure_compile_time_eval`), and those tiny
        # eager compiles can themselves hit the persistent cache — a
        # trace-time hit must not label the program compile "disk"
        t0 = time.perf_counter()
        lowered = jax.jit(run, donate_argnums=donate).lower(*avals)
        disk_before = disk_cache_hits()
        compiled = lowered.compile()
        wall = time.perf_counter() - t0
        source = "disk" if disk_cache_hits() > disk_before else "fresh"
        self._jitted[key] = compiled
        self._compile_counts[source] += 1
        self._m_compile_programs.inc(solver=solver, source=source)
        self._m_compile_wall.observe(wall, solver=solver, source=source)
        return compiled, source

    def _eps_fn(self, params, lengths, shardings):
        """The denoiser as the program calls it, ``eps_fn(x, t)``.  On a
        mesh it runs per batch shard (``per_batch_shard``): XLA cannot
        partition its Pallas kernels, and every op in it is row-local."""
        if shardings is None:
            if lengths is None:
                return self.dlm.eps_fn(params)
            return self.dlm.eps_fn(params, lengths=lengths)

        def rows(p, x, t, *ln):
            return self.dlm.eps_fn(p, *ln)(x, t)

        def eps_fn(x, t):
            # t is shared by the batch, or one time per row
            t_dim = 0 if jnp.ndim(t) and jnp.shape(t)[0] == x.shape[0] else None
            ln = () if lengths is None else (lengths,)
            return per_batch_shard(
                shardings.x, rows, params, x, t, *ln,
                batch_dims=(None, 0, t_dim) + (0,) * len(ln),
            )

        return eps_fn

    def _abstract_inputs(
        self, program, cfg, batch, seq_len, masked, stepped, params, shardings
    ):
        """``ShapeDtypeStruct`` avals matching exactly what
        :meth:`_run_chunk_locked` passes the compiled program: the params
        tree (shapes only — no device traffic), the fused ``x_init``, the
        per-row ``lengths`` vector (masked buckets only, else None), the
        per-row :class:`StepMask` (stepped buckets only, else None), and
        the program's history buffers.  On a mesh every aval carries the
        same NamedSharding the run path commits its array to, so the AOT
        executable accepts those arrays without resharding."""
        d = self.dlm.config.d_model
        sds = jax.ShapeDtypeStruct
        x = sds(
            (batch, seq_len, d),
            jnp.float32,
            sharding=None if shardings is None else shardings.x,
        )
        lengths = None
        if masked:
            lengths = sds(
                (batch,),
                jnp.int32,
                sharding=None if shardings is None else shardings.lengths,
            )
        steps = None
        if stepped:
            # cfg.nfe is the bucket: the scan runs its step count, so the
            # per-row grids span steps+1 knots
            n_steps = program.steps_for_nfe(cfg.nfe, cfg)
            steps = StepMask(
                active_steps=sds(
                    (batch,),
                    jnp.int32,
                    sharding=(
                        None if shardings is None else shardings.active_steps
                    ),
                ),
                ts=sds(
                    (batch, n_steps + 1),
                    jnp.float32,
                    sharding=(
                        None if shardings is None else shardings.step_ts
                    ),
                ),
            )
        p_sharding = None if self._replicate is None else self._replicate.sharding
        p_avals = jax.tree.map(
            lambda a: sds(np.shape(a), jnp.result_type(a), sharding=p_sharding),
            params,
        )
        buffers = program.abstract_buffers(x, cfg, shardings)
        return (p_avals, x, lengths, steps, *buffers)

    # ---- ahead-of-time warmup ------------------------------------------
    def warmup(
        self,
        params,
        *,
        solvers: tuple[str, ...] | None = None,
        seq_lens: tuple[int, ...] | None = None,
        nfes: tuple[int, ...] | None = None,
        progress=None,
    ) -> dict[str, Any]:
        """Ahead-of-time compile the configured program grid — **no params
        traffic, no sampling, no drains**: every grid point is lowered from
        abstract shapes and compiled into the same ``_jitted`` cache live
        traffic reads, so the first real request of any warmed shape runs
        the solver, not the compiler.

        Grid, per solver in ``solvers`` (default: the engine's default
        solver):

        * **nfe**: the nfe-bucket ladder when this solver's traffic
          nfe-buckets (``nfe_masked``) — explicit ``nfes`` are folded onto
          their buckets, since those are the only step counts a bucketed
          stream ever compiles; otherwise ``nfes`` verbatim (default: the
          solver config's nfe).
        * **seq**: the seq-bucket ladder when this solver's traffic
          seq-buckets (``seq_masked``); otherwise traffic groups by exact
          seq_len, so the caller names the expected lengths via
          ``seq_lens`` (falling back to the ladder values as plain
          lengths, or raising when the engine has neither).
        * **batch**: the batch-bucket ladder for fusable configs;
          non-fusable configs run exact-size (their requests compile their
          own shapes at drain time), so only the smallest legal batch is
          warmed.

        Every grid point is validated through the program's own request
        policy first, so an unserveable grid (e.g. ``nfe < k`` for ERA)
        fails the boot loudly instead of compiling programs no request
        could ever use.

        ``progress`` (optional ``fn(done, total)``) and the
        ``sampler_warmup_*`` instruments report progress while compiling —
        the front door's ``/readyz`` surfaces :meth:`warmup_status`.
        Returns a report dict: grid size, per-source compile counts
        (``fresh`` / ``disk`` / ``memory``), wall seconds, and the grid
        itself.
        """
        solver_list = tuple(solvers) if solvers else (self.solver_name,)
        grid: list[tuple[str, SolverConfig, int, int, bool, bool]] = []
        seen: set[Any] = set()
        for solver in solver_list:
            program = self.program_for(solver)  # unknown solver raises
            base = self.config_for(solver)
            masked = self.seq_masked(solver)
            stepped = self.nfe_masked(solver)
            seqs = (
                self.seq_buckets
                if masked
                else (tuple(seq_lens) if seq_lens else self.seq_buckets)
            )
            if not seqs:
                raise ValueError(
                    f"warmup needs seq_lens= when the engine has no "
                    f"seq-bucket ladder (solver {solver!r} groups by exact "
                    f"seq_len)"
                )
            if self.batch_buckets and program.fusable(base):
                batches = self.batch_buckets
            else:
                # exact-size traffic: warm the smallest legal batch
                # (requests compile their own exact shapes at drain time)
                batches = (round_to_dp(1, self.mesh),)
            if stepped:
                # bucketed traffic only ever compiles the ladder's step
                # counts — fold explicit nfes onto their buckets so the
                # grid is |nfe_buckets| wide, not |nfes|
                nfe_points = (
                    tuple(sorted({self.bucket_nfe(n) for n in nfes}))
                    if nfes
                    else self.nfe_buckets
                )
            else:
                nfe_points = tuple(nfes) if nfes else (base.nfe,)
            for nfe in nfe_points:
                cfg = dataclasses.replace(base, nfe=nfe)
                for seq in seqs:
                    for b in batches:
                        # an unserveable grid point must fail the boot
                        # loudly, not compile a program no request can use
                        program.validate(
                            SampleRequest(
                                batch=b, seq_len=seq, nfe=nfe, solver=solver
                            ),
                            cfg,
                            dp=self.dp,
                        )
                        point = (solver, cfg, b, seq, masked, stepped)
                        if point not in seen:
                            seen.add(point)
                            grid.append(point)

        total = len(grid)
        counts = {"fresh": 0, "disk": 0, "memory": 0}
        t0 = time.perf_counter()
        with self._lock:
            self._warmup_state = {"state": "running", "total": total, "done": 0}
        self._m_warmup_total.set(total)
        self._m_warmup_done.set(0)
        self._m_warmup_inflight.set(1)
        done = 0
        try:
            for solver, cfg, b, seq, masked, stepped in grid:
                key = (solver, cfg, b, seq, self.dp, masked, stepped)
                with self._lock:
                    if key in self._jitted:
                        # already compiled — live traffic got there first
                        counts["memory"] += 1
                    else:
                        _, source = self._compile(key, params)
                        counts[source] += 1
                        self._m_warmup_programs.inc(solver=solver)
                    done += 1
                    self._warmup_state["done"] = done
                self._m_warmup_done.set(done)
                if progress is not None:
                    progress(done, total)
            wall = time.perf_counter() - t0
            with self._lock:
                self._warmup_state = {
                    "state": "done",
                    "total": total,
                    "done": done,
                    K.WALL_S: wall,
                    **counts,
                }
            self._m_warmup_wall.set(wall)
        except BaseException as e:
            with self._lock:
                self._warmup_state = {
                    "state": "failed",
                    "total": total,
                    "done": done,
                    "error": f"{type(e).__name__}: {e}",
                }
            raise
        finally:
            self._m_warmup_inflight.set(0)
        return {
            "programs": total,
            K.WALL_S: wall,
            "grid": [
                {"solver": s, "batch": b, "seq_len": q, "nfe": c.nfe}
                for s, c, b, q, _, _ in grid
            ],
            **counts,
        }

    def warmup_status(self) -> dict[str, Any]:
        """Warmup progress snapshot (what ``/readyz`` reports): ``state``
        none|running|done|failed plus done/total counters, and per-source
        compile counts + wall seconds once done."""
        with self._lock:
            return dict(self._warmup_state)

    # ---- introspection (tests / benchmarks) ----------------------------
    def compile_cache(self) -> dict[Any, Any]:
        """Bucket-key -> compiled executable map (each program is lowered
        and compiled exactly once, by warmup or by its first chunk)."""
        with self._lock:
            return dict(self._jitted)

    def compile_stats(self) -> dict[str, int]:
        """Program-acquisition counts by source since boot: ``fresh`` XLA
        compiles, ``disk`` persistent-cache loads, and ``memory``
        in-process executable-cache hits (one per fused chunk served)."""
        with self._lock:
            return dict(self._compile_counts)
