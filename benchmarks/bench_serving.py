"""Serving-engine benchmark: per-request latency and throughput of the
batched ERA sampling engine (`repro.serving.BatchedSampler`) at batch sizes
1 / 8 / 64, optionally swept across mesh sizes, plus a Poisson-arrival
continuous-batching sweep.

Each closed-loop scenario submits `bs` single-sample requests, drains them
as one fused batch (per-sample ERS, fused Pallas step), and reports:

  * lat_ms  — mean submit->result latency per request
  * thpt    — samples per second over the drain wall time

The first drain per bucket compiles; a warmup drain is excluded from the
timed runs, so numbers reflect the steady compiled path.

Poisson sweep (`--poisson`): an open-loop client issues single-sample
requests with exponential inter-arrival gaps at several load factors (rate =
load / single-request service time) against two servers at the same NFE:

  * baseline — per-request drains in arrival order (batch-of-1, what a
    steady stream degenerates to without continuous batching);
  * async    — the continuous-batching `AsyncBatchedSampler`, which fuses
    requests across arrival time under a `SchedulerPolicy`.

Each mode reports p50/p99 arrival-to-result latency and throughput over the
stream makespan, and the whole sweep is written as a JSON artifact
(`BENCH_serving.json` by default — the CI bench-smoke job uploads it).

Mesh sweep (`--mesh`): a CPU tool.  It reruns the scenarios on 1 vs 8
virtual host devices (`XLA_FLAGS=--xla_force_host_platform_device_count=8`,
one child process per device count since the flag binds at jax init, each
pinned to `JAX_PLATFORMS=cpu`) with the engine batch-sharded over a
("data",) mesh — the placement a TPU slice would use, but never on the chip:
the chip's mesh path is `python chip_smoke.py --chips 4`.

Solver sweep (`--solver-sweep`): runs **every registry solver** through one
engine via per-request `solver=` routing (the PR-4 solver-program refactor:
each baseline gets the same single-scan compile, donated buffers, and
bucketed batching ERA has) at batch sizes 1 and 8, and writes
`BENCH_solvers.json` — steady-state walltime/throughput and the number of
XLA programs compiled per solver (the CI bench-smoke job uploads it).
This is the engine-side substrate for the paper's comparison tables: every
solver rides the same serving path, so walltime differences are solver
math, not engine favoritism.

Seq-mix sweep (`--seq-mix`): an open-loop Poisson client draws each
request's `seq_len` from a mixed distribution and streams it at two
continuous-batching servers:

  * exact — grouping by exact `(solver, seq_len, nfe)`: realistic
    heterogeneous traffic fragments into per-length queues that rarely
    fill a bucket, and every distinct length compiles its own programs;
  * fused — seq bucketing (`seq_buckets=` ladder): mixed lengths
    right-pad into shared length-masked batches, so queues fill across
    lengths and the compile count is bounded by the ladder.

Both modes report p50/p99 latency, throughput, mean fused batch rows, and
compiled-program counts; the sweep is written as `BENCH_seqmix.json` (the
CI bench-smoke job uploads it).  See `docs/serving.md` for the masking
contract that makes fused results bit-identical to exact-shape runs.

NFE-mix sweep (`--nfe-mix`): an open-loop Poisson client draws each
request's NFE budget from a mixed distribution (all at one seq_len) and
streams it at two continuous-batching servers:

  * exact — grouping by exact `(solver, seq_len, nfe)`: every distinct
    budget fragments into its own queue and compiles its own programs;
  * fused — NFE bucketing (`nfe_buckets=` ladder): mixed budgets scan to
    the bucketed max NFE with per-row step masks, so queues fill across
    budgets and the compile count is bounded by the ladder.

Both modes report p50/p99 latency, throughput, compiled-program counts,
and the wasted padding step-rows counter; the sweep is written as
`BENCH_nfemix.json` (the CI bench-smoke job uploads it).  Unlike the
seq-mix warnings, the ladder bound is enforced: the sweep exits non-zero
if fused traffic compiles more programs than |nfe_buckets| x
|batch_buckets| or compiles any off-ladder NFE.

Front-door sweep (`--frontdoor`): boots the real HTTP server as a
subprocess (`python -m repro.launch.serve --listen --port 0`, waiting on
its `FRONTDOOR READY <url>` line) from a parent that never touches a JAX
backend — a chip belongs to one process, and the server child must get
it — then drives an open-loop Poisson client
over the wire — every request pays JSON + base64 + loopback TCP, and
concurrent wire requests fuse in the server's scheduler exactly like
in-process submits.  Reports wire p50/p99 arrival-to-result latency and
throughput per load, scrapes `/metrics` and asserts the serving
instruments are present, and writes `BENCH_frontdoor.json` (the CI
bench-smoke job uploads it).
"""

import argparse
import json
import os
import queue
import subprocess
import sys
import threading
import time

import numpy as np

from benchmarks import common as C
from repro.core import solver_names
from repro.serving import (
    AsyncBatchedSampler,
    BatchedSampler,
    FrontDoorClient,
    SampleRequest,
    SchedulerPolicy,
    open_loop,
    result_keys as K,
)

MESH_SWEEP_DEVICES = (1, 8)
POISSON_LOADS = (4.0, 8.0)  # arrival rate as a multiple of 1/t_single
POISSON_REPEATS = 2         # streams per mode; best-throughput run reported


def run(mesh=None) -> None:
    dlm, params, data, cfg = C.trained_model(30 if C.SMOKE else 150)
    nfe = 6 if C.SMOKE else 10
    seq = 8
    batch_sizes = (1, 8) if C.SMOKE else (1, 8, 64)
    engine = BatchedSampler(
        dlm, C.SCHEDULE, batch_buckets=tuple(batch_sizes), mesh=mesh
    )
    tag = f"serving/era/dp{engine.dp}" if mesh is not None else "serving/era"

    for bs in batch_sizes:
        def drain_once(offset: int):
            tickets = [
                engine.submit_with_future(
                    SampleRequest(batch=1, seq_len=seq, nfe=nfe, seed=offset + i)
                )[0]
                for i in range(bs)
            ]
            t0 = time.perf_counter()
            results = engine.drain(params)
            wall = time.perf_counter() - t0
            return tickets, results, wall

        drain_once(0)  # compile warmup for this bucket
        repeats = 1 if C.SMOKE else 3
        best_wall, lat = float("inf"), 0.0
        for r in range(repeats):
            tickets, results, wall = drain_once(1000 * (r + 1))
            if wall < best_wall:
                best_wall = wall
                lat = sum(results[t].latency_s for t in tickets) / bs
        thpt = bs / best_wall
        C.emit(
            f"{tag}/bs{bs}",
            best_wall * 1e6,
            f"lat_ms={lat * 1e3:.2f},thpt={thpt:.1f}/s",
        )

    # compile-cache sanity: one program per bucket regardless of traffic
    C.emit(
        f"{tag}/compiled_buckets",
        float(len(engine.compile_cache())),
        f"buckets={sorted(k[2] for k in engine.compile_cache())}",
    )


def _percentiles(lats_s) -> dict:
    arr = np.asarray(lats_s) * 1e3
    return {
        "p50_ms": float(np.percentile(arr, 50)),
        "p99_ms": float(np.percentile(arr, 99)),
    }


def _poisson_gaps(rng, n: int, rate: float):
    return rng.exponential(1.0 / rate, n)


def _request(seq: int, nfe: int, seed: int) -> SampleRequest:
    return SampleRequest(batch=1, seq_len=seq, nfe=nfe, seed=seed)


def _run_baseline(engine, params, gaps, seq, nfe):
    """Per-request drain server: arrivals queue FIFO, one batch-of-1 drain
    each — the shape a steady stream degenerates to without continuous
    batching.  Returns (per-request latencies, makespan)."""
    work: queue.Queue = queue.Queue()
    lats = []

    def server():
        while True:
            item = work.get()
            if item is None:
                return
            t_arrive, req = item
            engine.submit_with_future(req)
            engine.drain(params)
            lats.append(time.perf_counter() - t_arrive)

    th = threading.Thread(target=server)
    th.start()
    t_start = open_loop(
        gaps,
        lambda i: work.put((time.perf_counter(), _request(seq, nfe, 2000 + i))),
    )
    work.put(None)
    th.join()
    return lats, time.perf_counter() - t_start


def _run_async(engine, params, gaps, seq, nfe, policy):
    """Open-loop client against the continuous-batching scheduler."""
    futures = []
    with AsyncBatchedSampler(engine, params, policy) as sched:
        t_start = open_loop(
            gaps,
            lambda i: futures.append(sched.submit(_request(seq, nfe, 2000 + i))),
        )
        results = [f.result() for f in futures]
        makespan = time.perf_counter() - t_start
        stats = sched.stats()
    return [r.latency_s for r in results], makespan, stats


def run_poisson(out_path: str = "BENCH_serving.json") -> None:
    """Continuous batching vs per-request drains under Poisson arrivals."""
    dlm, params, data, cfg = C.trained_model(30 if C.SMOKE else 150)
    nfe = 6 if C.SMOKE else 10
    seq = 8
    n_req = 32 if C.SMOKE else 96
    # finer buckets than the closed-loop bench: continuous batching launches
    # whatever accumulated, so a half-full largest bucket must not pay
    # full-bucket padding cost
    buckets = (1, 2, 4, 8) if C.SMOKE else (1, 2, 4, 8, 16, 64)
    engine = BatchedSampler(dlm, C.SCHEDULE, batch_buckets=buckets)

    # compile every bucket program before any timed stream
    for bucket in buckets:
        for i in range(bucket):
            engine.submit_with_future(_request(seq, nfe, 9000 + i))
        engine.drain(params)

    # single-request service time anchors the arrival rates
    t_single = float("inf")
    for r in range(3):
        engine.submit_with_future(_request(seq, nfe, 9100 + r))
        t0 = time.perf_counter()
        engine.drain(params)
        t_single = min(t_single, time.perf_counter() - t0)

    policy = SchedulerPolicy(
        max_wait_ms=max(1.0, 2 * t_single * 1e3), target_occupancy=1.0
    )
    record = {
        "bench": "serving/poisson",
        "smoke": C.SMOKE,
        "nfe": nfe,
        "seq_len": seq,
        "requests": n_req,
        "buckets": list(buckets),
        "t_single_s": t_single,
        "policy": {
            "max_wait_ms": policy.max_wait_ms,
            "target_occupancy": policy.target_occupancy,
        },
        "sweep": [],
    }
    rng = np.random.default_rng(0)
    for load in POISSON_LOADS:
        rate = load / t_single
        gaps = _poisson_gaps(rng, n_req, rate)
        # repeat each stream and keep the best-throughput run: an open-loop
        # stream is one realization, and a CPU-contended repeat would
        # otherwise masquerade as a scheduling result
        base = asyn = None
        for _ in range(POISSON_REPEATS):
            lats, span = _run_baseline(engine, params, gaps, seq, nfe)
            cand = {"throughput_rps": n_req / span, **_percentiles(lats)}
            if base is None or cand["throughput_rps"] > base["throughput_rps"]:
                base = cand
        for _ in range(POISSON_REPEATS):
            lats, span, stats = _run_async(
                engine, params, gaps, seq, nfe, policy
            )
            cand = {
                "throughput_rps": n_req / span,
                K.MEAN_BATCH_ROWS: stats[K.MEAN_BATCH_ROWS],
                K.BATCHES: stats[K.BATCHES],
                **_percentiles(lats),
            }
            if asyn is None or cand["throughput_rps"] > asyn["throughput_rps"]:
                asyn = cand
        entry = {
            "load": load,
            "rate_rps": rate,
            "baseline": base,
            "async": asyn,
            "speedup": asyn["throughput_rps"] / base["throughput_rps"],
        }
        record["sweep"].append(entry)
        for mode, rec in (("baseline", base), ("async", asyn)):
            C.emit(
                f"serving/era/poisson/load{load:g}/{mode}",
                rec["p50_ms"] * 1e3,
                f"p99_ms={rec['p99_ms']:.2f},thpt={rec['throughput_rps']:.1f}/s",
            )
        C.emit(
            f"serving/era/poisson/load{load:g}/speedup",
            entry["speedup"] * 1e6,
            f"async_thpt/base_thpt={entry['speedup']:.2f}x,"
            f"mean_batch_rows={asyn[K.MEAN_BATCH_ROWS]:.1f}",
        )

    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"# wrote {out_path}")
    worst = min(e["speedup"] for e in record["sweep"])
    if worst <= 1.0:
        print(
            f"# WARNING: async throughput did not beat the per-request "
            f"baseline at some load (min speedup {worst:.2f}x)"
        )


def run_solver_sweep(out_path: str = "BENCH_solvers.json") -> None:
    """Every registry solver through the engine at bs 1 / 8 via per-request
    routing: steady-state walltime + compile count per solver."""
    dlm, params, data, cfg = C.trained_model(30 if C.SMOKE else 150)
    nfe = 6 if C.SMOKE else 10
    seq = 8
    batch_sizes = (1, 8)
    engine = BatchedSampler(dlm, C.SCHEDULE, batch_buckets=batch_sizes)
    record = {
        "bench": "serving/solver-sweep",
        "smoke": C.SMOKE,
        "nfe": nfe,
        "seq_len": seq,
        "batch_sizes": list(batch_sizes),
        "solvers": {},
    }

    for solver in solver_names():
        compiled_before = len(engine.compile_cache())
        entry = {"buckets": {}}
        for bs in batch_sizes:

            def drain_once(offset: int):
                tickets = [
                    engine.submit_with_future(
                        SampleRequest(
                            batch=1,
                            seq_len=seq,
                            nfe=nfe,
                            solver=solver,
                            seed=offset + i,
                        )
                    )[0]
                    for i in range(bs)
                ]
                t0 = time.perf_counter()
                results = engine.drain(params)
                wall = time.perf_counter() - t0
                return tickets, results, wall

            drain_once(0)  # compile warmup for this (solver, bucket)
            repeats = 1 if C.SMOKE else 3
            best_wall, lat = float("inf"), 0.0
            for r in range(repeats):
                tickets, results, wall = drain_once(1000 * (r + 1))
                if wall < best_wall:
                    best_wall = wall
                    lat = sum(results[t].latency_s for t in tickets) / bs
            entry["buckets"][str(bs)] = {
                K.WALL_S: best_wall,
                "lat_ms": lat * 1e3,
                "throughput_rps": bs / best_wall,
            }
            C.emit(
                f"serving/sweep/{solver}/bs{bs}",
                best_wall * 1e6,
                f"lat_ms={lat * 1e3:.2f},thpt={bs / best_wall:.1f}/s",
            )
        # compile accounting: each solver should add exactly one XLA program
        # per batch bucket it ran at, and no solver recompiles another's
        entry["compiled_programs"] = len(engine.compile_cache()) - compiled_before
        record["solvers"][solver] = entry

    record["total_compiled_programs"] = len(engine.compile_cache())
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"# wrote {out_path}")
    expected = len(batch_sizes)
    for solver, entry in record["solvers"].items():
        if entry["compiled_programs"] > expected:
            print(
                f"# WARNING: {solver} compiled {entry['compiled_programs']} "
                f"programs (expected <= {expected} — one per bucket)"
            )


def run_seq_mix(out_path: str = "BENCH_seqmix.json") -> None:
    """Mixed-seq-len open-loop sweep: seq bucketing + padding masks vs
    exact-shape grouping, same traffic, same policy, same NFE."""
    dlm, params, data, cfg = C.trained_model(30 if C.SMOKE else 150)
    nfe = 6 if C.SMOKE else 10
    n_req = 24 if C.SMOKE else 96
    batch_buckets = (1, 2, 4, 8)
    if C.SMOKE:
        seq_lens = (2, 3, 4, 6, 8)
        seq_buckets = (4, 8)
    else:
        seq_lens = (4, 6, 8, 12, 16, 20, 28, 32)
        seq_buckets = (8, 16, 32)
    rng = np.random.default_rng(0)
    lengths = [int(x) for x in rng.choice(seq_lens, n_req)]

    # service-time anchor: a single largest-length request, exact shape
    anchor = BatchedSampler(dlm, C.SCHEDULE, batch_buckets=batch_buckets)
    t_single = float("inf")
    for r in range(3):
        anchor.submit_with_future(_request(max(seq_lens), nfe, 9500 + r))
        t0 = time.perf_counter()
        anchor.drain(params)
        t_single = min(t_single, time.perf_counter() - t0)

    load = 4.0
    gaps = _poisson_gaps(rng, n_req, load / t_single)
    policy = SchedulerPolicy(
        max_wait_ms=max(1.0, 2 * t_single * 1e3), target_occupancy=1.0
    )
    record = {
        "bench": "serving/seq-mix",
        "smoke": C.SMOKE,
        "nfe": nfe,
        "requests": n_req,
        "load": load,
        "t_single_s": t_single,
        "seq_len_distribution": list(seq_lens),
        "seq_buckets": list(seq_buckets),
        "batch_buckets": list(batch_buckets),
        "policy": {
            "max_wait_ms": policy.max_wait_ms,
            "target_occupancy": policy.target_occupancy,
        },
        "modes": {},
    }

    def stream(engine):
        futures = []
        with AsyncBatchedSampler(engine, params, policy) as sched:
            t_start = open_loop(
                gaps,
                lambda i: futures.append(
                    sched.submit(_request(lengths[i], nfe, 3000 + i))
                ),
            )
            results = [f.result() for f in futures]
            makespan = time.perf_counter() - t_start
            stats = sched.stats()
        return [r.latency_s for r in results], makespan, stats

    for mode, ladder in (("exact", None), ("fused", seq_buckets)):
        engine = BatchedSampler(
            dlm, C.SCHEDULE, batch_buckets=batch_buckets, seq_buckets=ladder
        )
        stream(engine)  # untimed warm stream: compiles the hot buckets
        best = None
        for _ in range(POISSON_REPEATS):
            lats, span, stats = stream(engine)
            cand = {
                "throughput_rps": n_req / span,
                K.MEAN_BATCH_ROWS: stats[K.MEAN_BATCH_ROWS],
                K.BATCHES: stats[K.BATCHES],
                **_percentiles(lats),
            }
            if best is None or cand["throughput_rps"] > best["throughput_rps"]:
                best = cand
        best["compiled_programs"] = len(engine.compile_cache())
        best["compiled_seq_lens"] = sorted({k[3] for k in engine.compile_cache()})
        record["modes"][mode] = best
        C.emit(
            f"serving/seqmix/{mode}",
            best["p50_ms"] * 1e3,
            f"p99_ms={best['p99_ms']:.2f},thpt={best['throughput_rps']:.1f}/s,"
            f"compiles={best['compiled_programs']},"
            f"rows/batch={best[K.MEAN_BATCH_ROWS]:.1f}",
        )

    fused, exact = record["modes"]["fused"], record["modes"]["exact"]
    record["speedup"] = fused["throughput_rps"] / exact["throughput_rps"]
    C.emit(
        "serving/seqmix/speedup",
        record["speedup"] * 1e6,
        f"fused_thpt/exact_thpt={record['speedup']:.2f}x,"
        f"compiles_fused={fused['compiled_programs']},"
        f"compiles_exact={exact['compiled_programs']}",
    )
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"# wrote {out_path}")
    # the two structural claims of seq bucketing, checked on every run
    max_fused = len(seq_buckets) * len(batch_buckets)
    if fused["compiled_programs"] > max_fused:
        print(
            f"# WARNING: fused mode compiled {fused['compiled_programs']} "
            f"programs (> ladder x batch buckets = {max_fused})"
        )
    if not set(fused["compiled_seq_lens"]) <= set(seq_buckets):
        print(
            f"# WARNING: fused mode compiled off-ladder seq lens "
            f"{fused['compiled_seq_lens']}"
        )
    if record["speedup"] <= 1.0:
        print(
            f"# WARNING: fused mixed-length throughput did not beat the "
            f"exact-shape baseline (speedup {record['speedup']:.2f}x)"
        )


def run_nfe_mix(out_path: str = "BENCH_nfemix.json") -> None:
    """Mixed-NFE open-loop sweep: NFE bucketing + per-row step masks vs
    exact-NFE grouping, same traffic, same policy, same seq_len.

    Exits non-zero if the fused mode compiles more programs than the
    ladder bounds (|nfe_buckets| x |batch_buckets|) or compiles any
    off-ladder NFE — the structural claim NFE bucketing makes to CI.
    """
    dlm, params, data, cfg = C.trained_model(30 if C.SMOKE else 150)
    seq = 4 if C.SMOKE else 16
    n_req = 24 if C.SMOKE else 96
    batch_buckets = (1, 2, 4, 8)
    if C.SMOKE:
        nfes = (4, 5, 6)  # ERA floor: nfe >= k (engine default k=4)
        nfe_buckets = (4, 6)
    else:
        nfes = (10, 14, 18, 22, 25)
        nfe_buckets = (18, 32)
    rng = np.random.default_rng(0)
    budgets = [int(x) for x in rng.choice(nfes, n_req)]

    # service-time anchor: a single largest-budget request, exact shape
    anchor = BatchedSampler(dlm, C.SCHEDULE, batch_buckets=batch_buckets)
    t_single = float("inf")
    for r in range(3):
        anchor.submit_with_future(_request(seq, max(nfes), 9600 + r))
        t0 = time.perf_counter()
        anchor.drain(params)
        t_single = min(t_single, time.perf_counter() - t0)

    load = 4.0
    gaps = _poisson_gaps(rng, n_req, load / t_single)
    policy = SchedulerPolicy(
        max_wait_ms=max(1.0, 2 * t_single * 1e3), target_occupancy=1.0
    )
    record = {
        "bench": "serving/nfe-mix",
        "smoke": C.SMOKE,
        "seq_len": seq,
        "requests": n_req,
        "load": load,
        "t_single_s": t_single,
        "nfe_distribution": list(nfes),
        "nfe_buckets": list(nfe_buckets),
        "batch_buckets": list(batch_buckets),
        "policy": {
            "max_wait_ms": policy.max_wait_ms,
            "target_occupancy": policy.target_occupancy,
        },
        "modes": {},
    }

    def stream(engine):
        futures = []
        with AsyncBatchedSampler(engine, params, policy) as sched:
            t_start = open_loop(
                gaps,
                lambda i: futures.append(
                    sched.submit(_request(seq, budgets[i], 3500 + i))
                ),
            )
            results = [f.result() for f in futures]
            makespan = time.perf_counter() - t_start
            stats = sched.stats()
        return [r.latency_s for r in results], makespan, stats

    for mode, ladder in (("exact", None), ("fused", nfe_buckets)):
        engine = BatchedSampler(
            dlm, C.SCHEDULE, batch_buckets=batch_buckets, nfe_buckets=ladder
        )
        stream(engine)  # untimed warm stream: compiles the hot buckets
        best = None
        for _ in range(POISSON_REPEATS):
            lats, span, stats = stream(engine)
            cand = {
                "throughput_rps": n_req / span,
                K.MEAN_BATCH_ROWS: stats[K.MEAN_BATCH_ROWS],
                K.BATCHES: stats[K.BATCHES],
                **_percentiles(lats),
            }
            if best is None or cand["throughput_rps"] > best["throughput_rps"]:
                best = cand
        best["compiled_programs"] = len(engine.compile_cache())
        # the fuse key carries the scanned-to NFE in its config slot
        best["compiled_nfes"] = sorted(
            {k[1].nfe for k in engine.compile_cache()}
        )
        pad_rows = engine.executor.metrics.get("sampler_nfe_padding_rows_total")
        best["nfe_padding_rows"] = (
            pad_rows.value(solver=engine.executor.solver_name)
            if pad_rows
            else 0.0
        )
        record["modes"][mode] = best
        C.emit(
            f"serving/nfemix/{mode}",
            best["p50_ms"] * 1e3,
            f"p99_ms={best['p99_ms']:.2f},thpt={best['throughput_rps']:.1f}/s,"
            f"compiles={best['compiled_programs']},"
            f"rows/batch={best[K.MEAN_BATCH_ROWS]:.1f}",
        )

    fused, exact = record["modes"]["fused"], record["modes"]["exact"]
    record["speedup"] = fused["throughput_rps"] / exact["throughput_rps"]
    C.emit(
        "serving/nfemix/speedup",
        record["speedup"] * 1e6,
        f"fused_thpt/exact_thpt={record['speedup']:.2f}x,"
        f"compiles_fused={fused['compiled_programs']},"
        f"compiles_exact={exact['compiled_programs']}",
    )
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"# wrote {out_path}")
    # the structural claims of NFE bucketing, enforced (not just warned):
    # mixed-NFE traffic must never compile past the ladder
    failures = []
    max_fused = len(nfe_buckets) * len(batch_buckets)
    if fused["compiled_programs"] > max_fused:
        failures.append(
            f"fused mode compiled {fused['compiled_programs']} programs "
            f"(> nfe ladder x batch buckets = {max_fused})"
        )
    if not set(fused["compiled_nfes"]) <= set(nfe_buckets):
        failures.append(
            f"fused mode compiled off-ladder NFEs {fused['compiled_nfes']}"
        )
    if record["speedup"] <= 1.0:
        print(
            f"# WARNING: fused mixed-NFE throughput did not beat the "
            f"exact-NFE baseline (speedup {record['speedup']:.2f}x)"
        )
    for msg in failures:
        print(f"# FAIL: {msg}")
    if failures:
        raise SystemExit(1)


FRONTDOOR_LOADS = (2.0, 4.0)
# instruments the /metrics scrape must expose (acceptance contract —
# see docs/serving.md)
FRONTDOOR_REQUIRED_METRICS = (
    "sampler_queue_depth_rows",
    "sampler_fuse_occupancy_ratio",
    "sampler_compile_programs_total",
    "sampler_compile_seconds",
    "sampler_warmup_grid_programs",
    "sampler_warmup_compiled_programs",
    "sampler_warmup_in_progress",
    "sampler_warmup_duration_seconds",
    "sampler_warmup_programs_total",
    "sampler_admission_rejects_total",
    "sampler_masked_fallback_total",
    "sampler_nfe_padding_rows_total",
    "sampler_request_latency_seconds",
    "sampler_queue_wait_seconds",
    "sampler_assembly_seconds",
    "frontdoor_http_requests_total",
)


def _boot_frontdoor_server(nfe: int, seq: int, max_wait_ms: float):
    """Launch `repro.launch.serve --listen --port 0` as a subprocess and
    wait for its `FRONTDOOR READY <url>` sentinel.  Returns (proc, url).

    The child needs the accelerator, which a parent that has initialized a
    JAX backend would hold, so that case is refused up front."""
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "the front-door sweep's parent has initialized a JAX backend, so "
            "the server child could not get the device; run it alone: "
            "python -m benchmarks.bench_serving --frontdoor"
        )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "repro.launch.serve",
            "--arch", "llama3.2-1b", "--smoke", "--mode", "diffusion",
            "--listen", "--port", "0", "--nfe", str(nfe), "--seq", str(seq),
            "--max-wait-ms", str(max_wait_ms),
            # finer ladder than the serving default: an open-loop stream
            # launches whatever accumulated (same reasoning as --poisson),
            # and the warmup only has these buckets to compile
            "--batch-buckets", "1,2,4,8",
        ],
        stdout=subprocess.PIPE,
        text=True,
        cwd=root,
        env=env,
    )
    try:
        for line in proc.stdout:
            if line.startswith("FRONTDOOR READY "):
                return proc, line.split()[-1].strip()
        raise RuntimeError(
            f"server exited (rc={proc.wait()}) before the ready line"
        )
    except Exception:
        proc.terminate()
        raise


def run_frontdoor(out_path: str = "BENCH_frontdoor.json") -> None:
    """Open-loop Poisson sweep over the wire: the real HTTP server in a
    subprocess, one client thread per in-flight request, every sample
    paying JSON + base64 + loopback TCP on top of the engine."""
    nfe = 6 if C.SMOKE else 10
    seq = 8
    n_req = 24 if C.SMOKE else 96
    proc, url = _boot_frontdoor_server(nfe, seq, max_wait_ms=25.0)
    try:
        client = FrontDoorClient(url, timeout=600.0)

        # the ready line means *bound*, not *warm* — the AOT warmup grid
        # compiles on a background thread behind /readyz.  Wait it out so
        # t_single anchors on solver time, not the compile wall.
        t_deadline = time.perf_counter() + 600.0
        while not client.readyz()["ready"]:
            if time.perf_counter() > t_deadline:
                raise RuntimeError(f"server never ready: {client.readyz()}")
            time.sleep(0.25)

        # single-request wire service time anchors the arrival rates
        t_single = float("inf")
        for i in range(3):
            t0 = time.perf_counter()
            client.sample(_request(seq, nfe, 9200 + i))
            t_single = min(t_single, time.perf_counter() - t0)

        def stream(gaps, seed0: int):
            lats = [None] * len(gaps)
            threads = []

            def fire(i: int):
                def call():
                    t0 = time.perf_counter()
                    client.sample(_request(seq, nfe, seed0 + i))
                    lats[i] = time.perf_counter() - t0

                th = threading.Thread(target=call)
                th.start()
                threads.append(th)

            t_start = open_loop(gaps, fire)
            for th in threads:
                th.join()
            return lats, time.perf_counter() - t_start

        record = {
            "bench": "serving/frontdoor",
            "smoke": C.SMOKE,
            "nfe": nfe,
            "seq_len": seq,
            "requests": n_req,
            "t_single_wire_s": t_single,
            "url": url,
            "sweep": [],
        }
        rng = np.random.default_rng(0)
        for load in FRONTDOOR_LOADS:
            rate = load / t_single
            best = None
            for r in range(POISSON_REPEATS):
                lats, span = stream(
                    _poisson_gaps(rng, n_req, rate), 4000 + 1000 * r
                )
                cand = {"throughput_rps": n_req / span, **_percentiles(lats)}
                if best is None or cand["throughput_rps"] > best["throughput_rps"]:
                    best = cand
            record["sweep"].append({"load": load, "rate_rps": rate, **best})
            C.emit(
                f"serving/era/frontdoor/load{load:g}",
                best["p50_ms"] * 1e3,
                f"p99_ms={best['p99_ms']:.2f},thpt={best['throughput_rps']:.1f}/s",
            )

        # /metrics scrape: the serving instruments must all be present
        scrape = client.metrics()
        missing = [m for m in FRONTDOOR_REQUIRED_METRICS if m not in scrape]
        if missing:
            raise RuntimeError(f"/metrics is missing instruments: {missing}")
        record["metrics_ok"] = True
        record["healthz"] = client.healthz()["stats"]
        record["readyz_warmup"] = client.readyz()["warmup"]
    finally:
        proc.terminate()
        proc.wait()

    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"# wrote {out_path}")


def run_on_local_mesh() -> None:
    """Child entry for the mesh sweep: engine sharded over all local devices
    (a 1-device mesh degenerates to the plain path, same program)."""
    import jax

    from repro.launch.mesh import make_sampler_mesh

    print(f"# mesh child: {jax.device_count()} device(s)", flush=True)
    run(mesh=make_sampler_mesh())


def run_mesh_sweep() -> None:
    """1 vs N virtual CPU devices, one subprocess per device count (XLA_FLAGS
    must be set before jax initializes).  CPU only: each child is pinned to
    ``JAX_PLATFORMS=cpu``, so this never takes the chip."""
    for n in MESH_SWEEP_DEVICES:
        env = dict(os.environ)
        flags = f"--xla_force_host_platform_device_count={n}"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flags).strip()
        # the flag only multiplies CPU devices; pin the child to CPU so the
        # sweep neither benches a 1-chip mesh twice nor contends for a chip
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_serving", "--mesh-child"],
            env=env,
        )
        if proc.returncode != 0:
            raise RuntimeError(f"mesh sweep child (devices={n}) failed")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--mesh",
        action="store_true",
        help="sweep the engine over 1 vs 8 virtual host devices",
    )
    ap.add_argument(
        "--mesh-child",
        action="store_true",
        help="(internal) run sharded over whatever devices this process has",
    )
    ap.add_argument(
        "--poisson",
        action="store_true",
        help="open-loop Poisson-arrival sweep: continuous batching vs "
        "per-request drains",
    )
    ap.add_argument(
        "--solver-sweep",
        action="store_true",
        help="run every registry solver through the engine at bs 1/8 via "
        "per-request routing; writes walltime + compile count per solver",
    )
    ap.add_argument(
        "--seq-mix",
        action="store_true",
        help="open-loop mixed-seq-len sweep: seq bucketing + padding masks "
        "vs exact-shape grouping; writes BENCH_seqmix.json",
    )
    ap.add_argument(
        "--nfe-mix",
        action="store_true",
        help="open-loop mixed-NFE sweep: NFE bucketing + per-row step masks "
        "vs exact-NFE grouping; writes BENCH_nfemix.json and fails if "
        "fused traffic compiles more programs than the ladder bounds",
    )
    ap.add_argument(
        "--frontdoor",
        action="store_true",
        help="open-loop Poisson sweep over the wire against a subprocess "
        "HTTP front-door server; writes BENCH_frontdoor.json",
    )
    ap.add_argument(
        "--out",
        default=None,
        help="JSON artifact path (default BENCH_serving.json for --poisson, "
        "BENCH_solvers.json for --solver-sweep, BENCH_seqmix.json for "
        "--seq-mix, BENCH_nfemix.json for --nfe-mix, BENCH_frontdoor.json "
        "for --frontdoor)",
    )
    args = ap.parse_args()
    if args.mesh:
        run_mesh_sweep()
    elif args.mesh_child:
        run_on_local_mesh()
    elif args.poisson:
        run_poisson(args.out or "BENCH_serving.json")
    elif args.solver_sweep:
        run_solver_sweep(args.out or "BENCH_solvers.json")
    elif args.seq_mix:
        run_seq_mix(args.out or "BENCH_seqmix.json")
    elif args.nfe_mix:
        run_nfe_mix(args.out or "BENCH_nfemix.json")
    elif args.frontdoor:
        run_frontdoor(args.out or "BENCH_frontdoor.json")
    else:
        run()
