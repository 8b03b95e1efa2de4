"""Continuous-batching scheduler: the one request queue in front of the
:class:`~repro.serving.executor.FusedExecutor`.

Every caller goes through :class:`AsyncBatchedSampler`.  Online callers
start its background drain thread; sync callers (scripts, tests, the
one-shot CLI mode) submit and then call :meth:`AsyncBatchedSampler.flush`,
which runs everything queued on the caller's thread.  The queue has the
standard continuous-batching shape for fixed-cost (known-NFE) solvers:

* ``submit()`` is callable from any thread and returns a
  :class:`concurrent.futures.Future` that resolves to a
  :class:`~repro.serving.executor.SampleResult`;
* requests land in per-(solver, seq, nfe) queues — the executor's group
  key, where ``seq`` is the request's seq *bucket* when the engine does
  mixed-seq-len fusion (the exact ``seq_len`` otherwise), and ``nfe`` is
  likewise the request's NFE *bucket* when the engine does mixed-NFE
  fusion (the exact ``nfe`` otherwise).  Only same-group requests can
  fuse into one compiled bucket: a mixed ``era`` / ``ddim`` / ... stream
  batches per solver instead of cross-contaminating a bucket, while
  (under seq / nfe bucketing) requests of *different* lengths and step
  budgets share a queue, a batch, and a compiled program;
* a background drain thread launches a queue when it reaches the policy's
  target bucket occupancy, or when its oldest request has waited
  ``max_wait_ms`` (deadline promotion — a lone request can never starve);
* ready queues are served highest-priority-first (a queue's priority is
  its most urgent pending request's), then oldest-request-first; within a
  queue, higher-``priority`` requests board a launch first (FIFO among
  equal priorities), and each launch takes at most one largest-bucket's
  worth of rows (the rest keep their original arrival times for the next
  launch).

**Admission control** (``SchedulerPolicy.max_queue_rows``): each
fuse-group queue is bounded — a ``submit()`` that would push a queue past
the limit raises :class:`QueueFullError` immediately (the front door maps
it to HTTP 429 + ``Retry-After``) instead of growing an unbounded backlog.
**Deadlines** (``SampleRequest.deadline_ms``): a request still queued past
its deadline fails fast with :class:`DeadlineExceededError` at the next
drain pass — it never occupies a seat in a fused batch it can no longer
use.  Both are pure queue policy: neither affects any admitted request's
results.

Execution goes through the thread-safe executor, so the compiled-bucket
cache, mesh placement, and per-sample ERS isolation are shared by every
scheduler over one executor — a request's ``x0`` is bit-identical whether
it runs via ``flush()``, via the drain thread under any arrival
interleaving, or solo.

All policy decisions read an injectable ``clock`` and are reachable via
:meth:`AsyncBatchedSampler.drain_once`, so the scheduling logic is testable
with a fake clock and no background thread or real sleeps.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Callable

import jax

from repro.serving import result_keys as K
from repro.serving.executor import (
    FusedExecutor,
    QueueItem,
    SampleRequest,
    SampleResult,
    resolve_future,
)


class QueueFullError(RuntimeError):
    """Admission control rejected a submit: the request's fuse-group queue
    is at ``SchedulerPolicy.max_queue_rows``.  ``retry_after_s`` is the
    server's backoff hint (the front door sends it as ``Retry-After``).

    ``message`` overrides the formatted text — the wire client rebuilds
    this exception from a 429 whose body carries the *server's* message
    (the client has no queue key or row counts of its own), so the
    override keeps remote diagnostics as informative as in-process ones.
    """

    def __init__(
        self,
        key,
        rows: int,
        limit: int,
        retry_after_s: float,
        message: str | None = None,
    ):
        self.key = key
        self.rows = rows
        self.limit = limit
        self.retry_after_s = retry_after_s
        super().__init__(
            message
            if message is not None
            else f"queue {key} is full ({rows} rows >= limit {limit}); "
            f"retry in {retry_after_s:.1f}s"
        )


class DeadlineExceededError(RuntimeError):
    """A request spent longer than its ``deadline_ms`` in the queue and was
    failed fast instead of boarding a fused batch it can no longer use.

    ``message`` overrides the formatted text — the wire client rebuilds
    this exception from a 504 whose body carries the server's message
    (including the actual waited time, which the client cannot know).
    """

    def __init__(
        self, req: SampleRequest, waited_ms: float, message: str | None = None
    ):
        self.req = req
        self.waited_ms = waited_ms
        super().__init__(
            message
            if message is not None
            else f"request (seed={req.seed}, "
            f"solver={req.solver or 'default'}) "
            f"expired in queue: waited {waited_ms:.1f}ms > "
            f"deadline_ms={req.deadline_ms:g}"
        )


def open_loop(gaps, emit, clock=time.perf_counter, sleep=time.sleep) -> float:
    """Drive an open-loop client: call ``emit(i)`` at each cumulative
    arrival offset of ``gaps``.  Sleeps only while ahead of schedule and
    catches up by emitting back-to-back when behind — a per-arrival sleep
    would floor the deliverable rate at the timer resolution.  When behind,
    ``sleep(0)`` still runs so a client colocated with the drain thread
    yields the interpreter instead of contending with it.  Returns the
    stream start time (same ``clock``), for makespan accounting.
    """
    t_start = clock()
    offset = 0.0
    for i, gap in enumerate(gaps):
        offset += gap
        delay = t_start + offset - clock()
        sleep(delay if delay > 0 else 0.0)
        emit(i)
    return t_start


@dataclasses.dataclass(frozen=True)
class SchedulerPolicy:
    """When does a queue of compatible requests launch as one fused batch?

    * ``max_wait_ms`` — upper bound on how long any request waits in the
      queue before its shape group is force-launched (deadline promotion).
      Lower = better p99 latency, higher = fuller batches / more throughput.
    * ``target_occupancy`` — fraction of the engine's largest batch bucket
      at which a queue launches immediately instead of waiting out the
      deadline.  1.0 waits for a completely full bucket; 0.25 launches as
      soon as a quarter-bucket of rows is pending.
    * ``max_queue_rows`` — admission bound per fuse-group queue: a submit
      that would push a queue's pending rows past this raises
      :class:`QueueFullError` (HTTP 429 at the front door) instead of
      queueing.  ``None`` = unbounded (in-process callers that manage
      their own backpressure).
    """

    max_wait_ms: float = 10.0
    target_occupancy: float = 1.0
    max_queue_rows: int | None = None

    def target_rows(self, max_bucket: int | None) -> int | None:
        """Row count that triggers an immediate launch (None = deadline
        only, for engines with no batch buckets)."""
        if max_bucket is None:
            return None
        return max(1, math.ceil(self.target_occupancy * max_bucket))

    def deadline(self, oldest_t: float) -> float:
        return oldest_t + self.max_wait_ms / 1e3

    def should_launch(
        self, now: float, oldest_t: float, rows: int, max_bucket: int | None
    ) -> bool:
        target = self.target_rows(max_bucket)
        if target is not None and rows >= target:
            return True
        return now >= self.deadline(oldest_t)

    def retry_after_s(self) -> float:
        """Backoff hint for an admission-rejected client: by the time one
        launch deadline has passed, the rejected queue has had a chance to
        drain at least once."""
        return max(1.0, self.max_wait_ms / 1e3)


class AsyncBatchedSampler:
    """The request queue in front of a
    :class:`~repro.serving.executor.FusedExecutor`.

    ``submit()`` from any thread; a background drain thread (``start()`` /
    ``stop()``, or use as a context manager) fuses requests across arrival
    time, and ``flush()`` runs everything queued on the caller's thread.

    Thread-safety and blocking behavior: ``submit`` / ``pending`` /
    ``stats`` are non-blocking and callable from any thread (results are
    delivered through futures); execution happens on the drain thread, or
    on the caller's thread for ``flush()`` and ``drain_once()``.  Several
    schedulers over one executor are safe — chunks serialize in the
    executor and share its compile cache.  ``stop()`` blocks: it joins the
    drain thread and flushes every queued request (all futures resolve);
    schedulers are one-shot.

    ``params`` is bound at construction: the drain thread launches batches
    on its own schedule, so it must not depend on caller state at drain
    time.
    """

    def __init__(
        self,
        executor: FusedExecutor,
        params,
        policy: SchedulerPolicy | None = None,
        clock: Callable[[], float] = time.perf_counter,
    ):
        self.executor = executor
        self.params = params
        self.policy = policy or SchedulerPolicy()
        self._clock = clock
        self._cv = threading.Condition()
        # fuse queues keyed by the executor's group key (solver, seq, nfe):
        # only requests that may share a compiled bucket share a queue (seq
        # is the seq bucket under mixed-seq-len fusion, else exact seq_len;
        # nfe is the NFE bucket under mixed-NFE fusion, else exact nfe)
        self._queues: dict[
            tuple[str, int, int], deque[tuple[QueueItem, Future]]
        ] = {}
        self._next_ticket = 0
        self._thread: threading.Thread | None = None
        self._stopping = False
        # telemetry: running counters (a serving process launches batches
        # for its whole lifetime — no per-batch history is kept)
        self._batches = 0
        self._rows = 0
        # Prometheus-style instruments, registered into the shared executor
        # registry (get-or-create: every scheduler over one executor scrapes
        # the same /metrics)
        m = executor.metrics
        self._m_depth = m.gauge(
            "sampler_queue_depth_rows",
            "pending request rows per fuse-group queue (solver, seq, nfe)",
        )
        self._m_submitted = m.counter(
            "sampler_requests_submitted_total", "requests admitted by submit()"
        )
        self._m_rejects = m.counter(
            "sampler_admission_rejects_total",
            "submits rejected by the max_queue_rows admission bound",
        )
        self._m_expired = m.counter(
            "sampler_deadline_expired_total",
            "queued requests failed fast past their deadline_ms",
        )
        self._m_latency = m.histogram(
            "sampler_request_latency_seconds",
            "arrival-to-result latency per delivered request",
        )
        self._m_queue_wait = m.histogram(
            "sampler_queue_wait_seconds",
            "submit-to-launch wait per request, by fuse group "
            "(solver, seq, nfe)",
        )

    # ---- client surface -------------------------------------------------
    def submit(self, req: SampleRequest) -> Future:
        """Enqueue from any thread; never blocks on execution (the drain
        thread runs batches).  The returned Future resolves to a
        :class:`~repro.serving.executor.SampleResult` (or raises, if the
        fused launch it rode in failed, or with
        :class:`DeadlineExceededError` if the request expired in queue);
        ``Future.result(timeout=...)`` is the blocking wait.  Invalid
        requests — unknown solver, per-solver (batch, nfe) constraints,
        seq_len above the engine's largest seq bucket, bad
        priority/deadline — raise here, at submit, so they can never
        poison a fused batch.  Raises :class:`QueueFullError` when the
        request's fuse-group queue is at the policy's admission bound, and
        RuntimeError after ``stop()``."""
        self.executor.validate(req)
        fut: Future = Future()
        key = self.executor.group_key(req)
        label = self._key_labels(key)
        with self._cv:
            if self._stopping:
                raise RuntimeError("scheduler is stopped")
            limit = self.policy.max_queue_rows
            if limit is not None:
                q = self._queues.get(key)
                rows = sum(item[1].batch for item, _ in q) if q else 0
                if rows + req.batch > limit:
                    self._m_rejects.inc(**label)
                    raise QueueFullError(
                        key, rows, limit, self.policy.retry_after_s()
                    )
            ticket = self._next_ticket
            self._next_ticket += 1
            item: QueueItem = (ticket, req, self._clock())
            self._queues.setdefault(key, deque()).append((item, fut))
            self._m_submitted.inc()
            self._set_depth_locked(key)
            self._cv.notify()
        return fut

    @staticmethod
    def _key_labels(key) -> dict:
        solver, seq, nfe = key
        return {"solver": solver, "seq": seq, "nfe": nfe}

    def _set_depth_locked(self, key) -> None:
        q = self._queues.get(key)
        rows = sum(item[1].batch for item, _ in q) if q else 0
        self._m_depth.set(rows, **self._key_labels(key))

    @property
    def pending(self) -> int:
        with self._cv:
            return sum(len(q) for q in self._queues.values())

    def stats(self) -> dict:
        with self._cv:
            batches, rows = self._batches, self._rows
            submitted = self._next_ticket
        return {
            K.SUBMITTED: submitted,
            K.BATCHES: batches,
            K.ROWS: rows,
            K.MEAN_BATCH_ROWS: (rows / batches) if batches else 0.0,
        }

    # ---- cold start ------------------------------------------------------
    def warmup(
        self,
        *,
        solvers: tuple[str, ...] | None = None,
        seq_lens: tuple[int, ...] | None = None,
        nfes: tuple[int, ...] | None = None,
        progress=None,
    ):
        """Ahead-of-time compile the engine's program grid with this
        scheduler's bound ``params`` — no sampling, no drains (see
        :meth:`FusedExecutor.warmup`).  Safe to run concurrently with live
        traffic (grid points a request compiled first are skipped); the
        front door runs this on a background thread at boot and gates
        ``/readyz`` on it."""
        return self.executor.warmup(
            self.params, solvers=solvers, seq_lens=seq_lens, nfes=nfes,
            progress=progress,
        )

    def warmup_status(self) -> dict:
        """Warmup progress of the underlying executor (what ``/readyz``
        reports)."""
        return self.executor.warmup_status()

    # ---- lifecycle (one-shot: stop() is final; build a new scheduler to
    # serve again) ---------------------------------------------------------
    def start(self) -> "AsyncBatchedSampler":
        with self._cv:
            if self._stopping:
                raise RuntimeError(
                    "scheduler is stopped — schedulers are one-shot, "
                    "construct a new AsyncBatchedSampler to serve again"
                )
            if self._thread is not None:
                raise RuntimeError("scheduler already started")
            self._thread = threading.Thread(
                target=self._loop, name="era-scheduler", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        """Clean shutdown: join the drain thread, then flush every queued
        request (their futures all resolve)."""
        with self._cv:
            self._stopping = True
            self._cv.notify_all()
            thread, self._thread = self._thread, None
        if thread is not None:
            thread.join()
        self.flush()

    def flush(self) -> int:
        """Run every queued request now, on the caller's thread, and return
        the number of fused batches launched.  Requests past their deadline
        fail first; the rest are grouped by the executor's group key and
        packed into chunks exactly as the drain thread does.  A failed
        chunk fails only its own futures.  The scheduler stays usable: a
        request submitted afterwards waits for the next flush (or the drain
        thread)."""
        with self._cv:
            now = self._clock()
            expired = self._expire_locked(now)
            batches = self._pop_all()
        self._fail_expired(expired, now)
        return self._run_batches(batches)

    def __enter__(self) -> "AsyncBatchedSampler":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ---- scheduling core (fake-clock testable, no thread required) ------
    def drain_once(self, now: float | None = None) -> int:
        """Fail every queued request past its deadline, then launch every
        queue the policy deems ready at ``now``; returns the number of
        fused batches launched.  This is the drain thread's step function,
        exposed for manual pumping and fake-clock tests."""
        with self._cv:
            t = self._clock() if now is None else now
            expired = self._expire_locked(t)
            batches = self._pop_ready(t)
        self._fail_expired(expired, t)
        return self._run_batches(batches)

    def _expire_locked(self, now: float):
        """Remove deadline-expired requests from every queue (fail-fast:
        they never occupy a fused batch).  Returns the removed entries for
        delivery outside the lock."""
        expired: list[tuple[QueueItem, Future]] = []
        for key, q in self._queues.items():
            if not q:
                continue
            keep = deque()
            for entry in q:
                (_, req, t_submit), _ = entry
                if (
                    req.deadline_ms is not None
                    and now - t_submit > req.deadline_ms / 1e3
                ):
                    expired.append(entry)
                else:
                    keep.append(entry)
            if len(keep) != len(q):
                self._queues[key] = keep
                self._set_depth_locked(key)
        return expired

    def _fail_expired(self, expired, now: float) -> None:
        for (_, req, t_submit), fut in expired:
            self._m_expired.inc()
            resolve_future(
                fut,
                exception=DeadlineExceededError(req, (now - t_submit) * 1e3),
            )

    def _pop_ready(self, now: float):
        """Pop ready chunks under the lock: highest-priority queue first
        (a queue's priority is its most urgent pending request's), oldest
        arrival breaking ties."""
        exe = self.executor
        ready: list[tuple[int, float, tuple[str, int, int]]] = []
        for key, q in self._queues.items():
            if not q:
                continue
            rows = sum(item[1].batch for item, _ in q)
            oldest = q[0][0][2]
            if self.policy.should_launch(now, oldest, rows, exe.max_bucket):
                prio = max(item[1].priority for item, _ in q)
                ready.append((-prio, oldest, key))
        ready.sort()
        batches = []
        for _, _, key in ready:
            batches.extend(self._pop_chunks(key, full_queue=False))
        return batches

    def _pop_all(self):
        batches = []
        for key in list(self._queues):
            batches.extend(self._pop_chunks(key, full_queue=True))
        return batches

    def _pop_chunks(self, key, full_queue: bool):
        """Take rows from one queue: up to one largest bucket per launch,
        boarding higher-``priority`` requests first (FIFO among equal
        priorities — with no priorities set this is exactly arrival
        order); the remainder keeps its arrival times for the next launch.
        On flush the whole queue goes.  Non-fusable configs split into
        exact-size solo chunks."""
        exe = self.executor
        entries = list(self._queues[key])
        order = sorted(
            range(len(entries)),
            key=lambda i: (-entries[i][0][1].priority, i),
        )
        taken_idx: list[int] = []
        total = 0
        for i in order:
            b = entries[i][0][1].batch
            if (
                not full_queue
                and taken_idx
                and exe.max_bucket
                and total + b > exe.max_bucket
            ):
                break
            taken_idx.append(i)
            total += b
        taken_set = set(taken_idx)
        # chunks assemble in boarding (priority) order; leftovers keep
        # their original arrival order and times
        taken = [entries[i] for i in taken_idx]
        now = self._clock()
        label = self._key_labels(key)
        for (_, _, t_submit), _ in taken:
            self._m_queue_wait.observe(now - t_submit, **label)
        self._queues[key] = deque(
            e for i, e in enumerate(entries) if i not in taken_set
        )
        self._set_depth_locked(key)
        futures = {item[0]: fut for item, fut in taken}
        return [
            (key, chunk, pad, futures)
            for chunk, pad in exe.pack([item for item, _ in taken])
        ]

    def _run_batches(self, batches) -> int:
        """Execute popped chunks outside the queue lock and resolve their
        futures; a failed launch fails only its own chunk's futures."""
        for (_solver, seq_len, nfe), chunk, pad, futures in batches:
            results: dict[int, SampleResult] = {}
            try:
                self.executor.run_chunk(
                    self.params, seq_len, nfe, chunk, results, pad=pad
                )
            except Exception as e:  # noqa: BLE001 - delivered via futures
                for ticket, _, _ in chunk:
                    resolve_future(futures[ticket], exception=e)
                continue
            with self._cv:
                self._batches += 1
                self._rows += sum(req.batch for _, req, _ in chunk)
            for ticket, _, _ in chunk:
                self._m_latency.observe(results[ticket].latency_s)
                resolve_future(futures[ticket], results[ticket])
        return len(batches)

    def _next_deadline_s(self, now: float) -> float | None:
        """Seconds until the nearest wakeup: a queue's launch deadline or a
        request's expiry deadline, whichever comes first (None = nothing
        queued)."""
        deadlines = []
        for q in self._queues.values():
            if not q:
                continue
            deadlines.append(self.policy.deadline(q[0][0][2]))
            for (_, req, t_submit), _ in q:
                if req.deadline_ms is not None:
                    deadlines.append(t_submit + req.deadline_ms / 1e3)
        if not deadlines:
            return None
        return max(0.0, min(deadlines) - now)

    def _loop(self) -> None:
        while True:
            with self._cv:
                while not self._stopping:
                    now = self._clock()
                    expired = self._expire_locked(now)
                    batches = self._pop_ready(now)
                    if batches or expired:
                        break
                    with jax.profiler.TraceAnnotation("sampler.wait"):
                        self._cv.wait(timeout=self._next_deadline_s(now))
                else:
                    return  # stop() flushes what is left
            self._fail_expired(expired, now)
            self._run_batches(batches)
