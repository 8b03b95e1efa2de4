"""Diffusion sampling service: ERA-Solver (or any registered solver) driving
a DiffusionLM denoiser — the paper's deployment shape, grown into a
request-batching engine.

Architecture:

* :class:`~repro.serving.executor.FusedExecutor` — the thread-safe
  execution core: one jitted XLA program per (sample-shape, nfe, k) bucket
  (``jax.lax.scan`` over NFE steps inside; eps/t Lagrange buffers donated on
  accelerator backends), mesh placement, chunk packing, and per-request aux
  scoping.  The jit cache is keyed by bucket, so a steady request stream
  compiles exactly once per bucket no matter how batch sizes fluctuate.
* :class:`BatchedSampler` — the sync engine.  ``submit()`` enqueues requests
  (from any thread) and returns a ticket whose :class:`~concurrent.futures.
  Future` resolves at drain time; ``drain()`` groups pending requests by
  (solver, seq_len, nfe), pads each group's batch up to a shape bucket, and
  runs each chunk through the shared executor.
* **Per-request solver routing** — ``SampleRequest.solver`` names any
  registry solver (``era``, ``ddim``, ``dpm_solver_pp2m``, ...); the
  executor routes each request to that solver's
  :class:`~repro.core.SolverProgram` (None = the engine's default solver).
  Every program gets the same engine treatment ERA does: a single-scan
  compile per bucket, donated history buffers, mesh-sharded carries, and
  per-request aux scoping — there is no solver-specific code in serving/.
* :class:`~repro.serving.scheduler.AsyncBatchedSampler` — the
  continuous-batching front end over the same executor: a background drain
  thread batches requests across arrival time under a
  :class:`~repro.serving.scheduler.SchedulerPolicy`.
* Per-request isolation inside a fused batch comes from per-sample ERS
  (``ERAConfig.per_sample=True``, the engine default for ERA): every sample
  row measures its own delta_eps and selects its own Lagrange bases, so a
  batch-of-N run is equivalent to N independent runs.  Configs with the
  paper's shared scalar delta_eps couple the batch, so the engine serves
  them one exact-size request at a time instead of fusing (and, on a mesh,
  only at dp-multiple batches — exact-size runs cannot round up).
* ERA's step runs the fused Pallas kernel, compiled on TPU and in
  interpret mode elsewhere (``kernels.ops.interpret_mode``); there is no
  runtime probe and no fallback path.
* Mesh mode (``mesh=`` a ``jax.sharding.Mesh``): the engine batch-shards the
  latents and Lagrange eps buffer over the mesh's data axes
  (``parallel.sharding.sampler_shardings``) and replicates the denoiser
  params, so one fused drain spreads its rows across every device.  Batch
  buckets round up to multiples of the data-parallel size (no ragged
  shards), and per-sample ERS keeps each row's error measurement and base
  selection local to its shard — the solver loop runs collective-free.
* :class:`SamplerService` — the original one-call facade, now a thin
  future-consuming client over the engine with exact-size buckets.
"""

from __future__ import annotations

import threading
import time
import warnings
from concurrent.futures import Future

import jax
from jax.sharding import Mesh

from repro.core import NoiseSchedule, SolverConfig, get_program
from repro.models.diffusion import DiffusionLM
from repro.serving.executor import (
    DEFAULT_MAX_BATCH,
    DEFAULT_MAX_NFE,
    DEFAULT_MAX_SEQ_LEN,
    FusedExecutor,
    QueueItem,
    SampleRequest,
    SampleResult,
    resolve_future,
)
from repro.serving.metrics import MetricsRegistry

Array = jax.Array

class BatchedSampler:
    """Request-batching diffusion sampling engine (submit/drain).

    Thread-safety: ``submit`` / ``submit_with_future`` / ``future`` /
    ``pending`` may be called from any thread; concurrent ``drain()``
    callers are safe (each drains whatever is pending when it takes the
    queue, and chunk execution serializes inside the shared executor).
    ``drain()`` blocks until every chunk it took has finished on device.

    ``seq_buckets`` opts into mixed-seq-len fusion (see
    :class:`~repro.serving.executor.FusedExecutor`): requests whose
    ``seq_len`` differs fuse into one compiled batch, right-padded and
    length-masked, with exact-shape fallback when masking is unsupported.

    ``nfe_buckets`` opts into mixed-NFE fusion the same way: requests
    whose ``nfe`` differs fuse into one compiled batch that scans to the
    bucketed max step count, with per-row step masks freezing each row
    bitwise once its own budget is spent; solvers without a step-masked
    scan fall back to exact-NFE grouping.
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
        self.executor = FusedExecutor(
            dlm, schedule, solver, solver_config, batch_buckets, mesh,
            seq_buckets=seq_buckets, nfe_buckets=nfe_buckets,
            metrics=metrics,
            max_batch=max_batch, max_nfe=max_nfe, max_seq_len=max_seq_len,
        )
        self._queue_lock = threading.Lock()
        self._pending: list[QueueItem] = []
        self._futures: dict[int, Future] = {}
        self._next_ticket = 0

    # engine surface mirrored from the executor (tests/benchmarks read these)
    @property
    def dlm(self) -> DiffusionLM:
        return self.executor.dlm

    @property
    def schedule(self) -> NoiseSchedule:
        return self.executor.schedule

    @property
    def solver_name(self) -> str:
        return self.executor.solver_name

    @property
    def solver_config(self) -> SolverConfig:
        return self.executor.solver_config

    @property
    def mesh(self) -> Mesh | None:
        return self.executor.mesh

    @property
    def dp(self) -> int:
        return self.executor.dp

    @property
    def batch_buckets(self) -> tuple[int, ...] | None:
        return self.executor.batch_buckets

    @property
    def seq_buckets(self) -> tuple[int, ...] | None:
        return self.executor.seq_buckets

    @property
    def nfe_buckets(self) -> tuple[int, ...] | None:
        return self.executor.nfe_buckets

    @property
    def metrics(self) -> MetricsRegistry:
        return self.executor.metrics

    # ---- request queue -------------------------------------------------
    def submit(self, req: SampleRequest) -> int:
        """Deprecated: enqueue a request and return its int ticket for the
        drain() result map.

        The int-ticket surface predates futures and cannot express
        off-thread waiting safely (with concurrent drains, the window
        between ``submit()`` and ``future()`` is wide enough for delivery
        to pop the Future first) — use :meth:`submit_with_future`, whose
        Future is also what the scheduler and the front door deliver
        through.  Thread-safe; invalid requests are rejected here, not at
        drain time.
        """
        warnings.warn(
            "BatchedSampler.submit (int tickets) is deprecated; use "
            "submit_with_future() and wait on the returned Future",
            DeprecationWarning,
            stacklevel=2,
        )
        return self.submit_with_future(req)[0]

    def submit_with_future(self, req: SampleRequest) -> tuple[int, Future]:
        """Atomically enqueue a request and hand back its delivery Future —
        no concurrent ``drain()`` can resolve-and-pop the ticket in
        between."""
        self.executor.validate(req)
        with self._queue_lock:
            ticket = self._next_ticket
            self._next_ticket += 1
            self._pending.append((ticket, req, time.perf_counter()))
            fut = self._futures[ticket] = Future()
        return ticket, fut

    def future(self, ticket: int) -> Future:
        """The Future that ``drain()`` resolves for this ticket.

        Grab it between ``submit()`` and the drain: delivery pops the
        Future (the engine does not pin results), so asking for an
        already-delivered ticket is an error, not a silent re-wait.
        """
        with self._queue_lock:
            if ticket not in self._futures:
                raise KeyError(
                    f"ticket {ticket} has no outstanding future — its result "
                    "was already delivered by drain(); call future() before "
                    "the drain that serves the ticket"
                )
            return self._futures[ticket]

    @property
    def pending(self) -> int:
        with self._queue_lock:
            return len(self._pending)

    def drain(self, params) -> dict[int, SampleResult]:
        """Run all pending requests, fused per (solver, seq, nfe) group
        (seq = seq bucket under mixed-seq-len fusion, exact seq_len
        otherwise).

        Also resolves each drained ticket's Future, so a drain from one
        thread delivers results to submitters waiting on other threads.
        A chunk that fails fails only its own tickets: their Futures get
        the exception (no waiter hangs), every other chunk still runs and
        delivers, and the first failure re-raises at the end for the
        drain() caller.
        """
        with self._queue_lock:
            pending, self._pending = self._pending, []
        # only same-group-key requests can fuse into one compiled bucket:
        # (solver, seq, nfe), where seq is the seq *bucket* when the engine
        # does mixed-seq-len fusion and the exact seq_len otherwise —
        # mixed-solver traffic batches per solver either way
        groups: dict[tuple[str, int, int], list[QueueItem]] = {}
        for item in pending:
            _, req, _ = item
            groups.setdefault(self.executor.group_key(req), []).append(item)

        results: dict[int, SampleResult] = {}
        failure: Exception | None = None
        for (_solver, seq_len, nfe), items in groups.items():
            for chunk, pad in self.executor.pack(items):
                try:
                    self.executor.run_chunk(
                        params, seq_len, nfe, chunk, results, pad=pad
                    )
                except Exception as e:  # noqa: BLE001 - delivered via futures
                    if failure is None:
                        failure = e
                    with self._queue_lock:
                        futs = [
                            self._futures.pop(t) for t, _, _ in chunk
                        ]
                    for fut in futs:
                        resolve_future(fut, exception=e)
        with self._queue_lock:
            futures = {t: self._futures.pop(t) for t in results}
        for ticket, fut in futures.items():
            resolve_future(fut, results[ticket])
        if failure is not None:
            raise failure
        return results

    # ---- cold start -----------------------------------------------------
    def warmup(
        self,
        params,
        *,
        solvers: tuple[str, ...] | None = None,
        seq_lens: tuple[int, ...] | None = None,
        nfes: tuple[int, ...] | None = None,
        progress=None,
    ):
        """Ahead-of-time compile the configured (solver × batch-bucket ×
        seq-bucket × nfe) program grid — no sampling, no drains; see
        :meth:`FusedExecutor.warmup`.  After this returns, the first real
        request of any warmed shape runs the solver, not the compiler.
        Returns the warmup report dict."""
        return self.executor.warmup(
            params, solvers=solvers, seq_lens=seq_lens, nfes=nfes,
            progress=progress,
        )

    def warmup_status(self):
        """Warmup progress snapshot (``/readyz`` payload material)."""
        return self.executor.warmup_status()

    # ---- introspection (tests / benchmarks) ----------------------------
    def compile_cache(self):
        """Bucket-key -> compiled executable map (each program is lowered
        and compiled exactly once, by warmup or by its first chunk)."""
        return self.executor.compile_cache()

    def compile_stats(self):
        """Program-acquisition counts by source: fresh / disk / memory."""
        return self.executor.compile_stats()


class SamplerService:
    """One-call facade over :class:`BatchedSampler` (exact-size buckets).

    ``sample()`` is synchronous and blocking: it submits, drains, and
    returns the finished :class:`~repro.serving.executor.SampleResult` —
    the same type every other entry point delivers.  ``result.x0`` is the
    latents; ``result.info`` flattens the engine telemetry
    (:data:`~repro.serving.result_keys.INFO_KEYS`: ``wall_s`` /
    ``latency_s`` / ``padded_batch`` / ``padded_seq_len``) together with
    every solver diagnostic from ``result.aux`` (``delta_eps_history``,
    ``ers_selection_history``, ...), scoped to this request.  The
    pre-unification ``x0, info = svc.sample(...)`` tuple unpacking still
    works as a deprecation shim.

    It is thread-safe (the underlying engine is), but callers wanting
    concurrency should use :class:`BatchedSampler` or the async scheduler
    directly — the facade runs one exact-size batch per call and never
    fuses strangers.

    ``engine=`` injects a pre-built :class:`BatchedSampler` (e.g. from
    :func:`repro.serving.factory.build_engine`) instead of constructing a
    private exact-size one — the facade then inherits that engine's
    buckets, mesh, and metrics registry.
    """

    def __init__(
        self,
        dlm: DiffusionLM | None = None,
        schedule: NoiseSchedule | None = None,
        solver: str = "era",
        solver_config: SolverConfig | None = None,
        mesh: Mesh | None = None,
        engine: BatchedSampler | None = None,
    ):
        if engine is None:
            if dlm is None or schedule is None:
                raise ValueError(
                    "SamplerService needs (dlm, schedule) or a pre-built "
                    "engine="
                )
            if solver_config is None:
                # the facade defaults to the paper config (shared-delta
                # ERA), not the engine's fusable serving default — it runs
                # exact-size
                solver_config = get_program(solver).default_config()
            engine = BatchedSampler(
                dlm, schedule, solver, solver_config,
                batch_buckets=None, mesh=mesh,
            )
        self._engine = engine
        self.dlm = engine.dlm
        self.schedule = engine.schedule
        self.solver_name = engine.solver_name
        self.solver_config = engine.solver_config

    def sample(self, params, req: SampleRequest) -> SampleResult:
        """Generate ``req.batch`` sequences of latents via the solver;
        blocking.  Returns the request's :class:`SampleResult`."""
        _, fut = self._engine.submit_with_future(req)
        self._engine.drain(params)
        return fut.result()

    # ---- dry-run hook: the full solver loop as one lowerable program ----
    def sample_program(self):
        sample_fn = get_program(self.solver_name).sample
        cfg = self.solver_config

        def program(params, x_init):
            return sample_fn(
                self.dlm.eps_fn(params), x_init, self.schedule, cfg
            ).x0

        return program
