"""Offline batches: a closed loop over ``AsyncBatchedSampler.submit``.

The engine is ``build_engine`` with the cell's buckets; its whole program
grid is compiled ahead of time (``warmup``) and one request of the
traffic's shape runs once before the window.  In the window the loop keeps
``in_flight`` requests outstanding: when one completes and its ``x0`` has
reached the host, the next is submitted, until the window's seconds are
up.  Every request submitted by then runs to the end and is counted, and
the window closes at the last completion.
"""

from __future__ import annotations

import time
from collections import deque

import jax
import numpy as np

from bench import traffic


def _sample_request(r):
    from repro.serving import SampleRequest

    return SampleRequest(batch=r.rows, seq_len=r.seq_len, nfe=r.nfe,
                         solver=r.solver, seed=r.seed)


def run(ctx) -> dict:
    from repro.core import linear_schedule
    from repro.serving import AsyncBatchedSampler, build_engine, warmup_kwargs

    cfg = ctx.engine_config()
    engine = build_engine(ctx.dlm, linear_schedule(), cfg)
    sched = AsyncBatchedSampler(engine, ctx.params, ctx.policy())
    sched.warmup(**warmup_kwargs(cfg))
    sched.start()
    gen = traffic.generate(ctx.traffic, ctx.seed, ctx.seq_divisor)
    in_flight_n = int(ctx.traffic["arrivals"]["in_flight"])
    try:
        warm = next(gen)
        np.asarray(sched.submit(_sample_request(warm)).result().x0)

        completed, attempted, failed = [], 0, 0
        pending = deque()
        with ctx.window() as w:
            deadline = w["start"] + ctx.seconds

            def submit():
                r = next(gen)
                with jax.profiler.TraceAnnotation("bench.submit"):
                    pending.append((r, time.perf_counter(), sched.submit(_sample_request(r))))

            for _ in range(in_flight_n):
                submit()
            while pending:
                r, t_sub, fut = pending.popleft()
                attempted += 1
                try:
                    with jax.profiler.TraceAnnotation("bench.result_wait"):
                        res = fut.result()
                    with jax.profiler.TraceAnnotation("bench.host_copy"):
                        x0 = np.asarray(res.x0)
                except Exception as e:  # noqa: BLE001 - counted, not raised
                    failed += 1
                    completed.append({"req": r, "x0": None, "error": repr(e)})
                    continue
                t_done = time.perf_counter()
                completed.append({
                    "req": r, "x0": x0, "t_submit": t_sub, "t_done": t_done,
                    "latency_s": res.latency_s, "batch_wall_s": res.batch_wall_s,
                    "padded_batch": res.padded_batch,
                    "padded_seq_len": res.padded_seq_len,
                    "padded_nfe": res.padded_nfe,
                })
                w["end"] = t_done
                if t_done < deadline:
                    submit()
    finally:
        sched.stop()
    return {
        "entry": "offline", "completed": completed,
        "attempted": attempted, "failed": failed,
    }
