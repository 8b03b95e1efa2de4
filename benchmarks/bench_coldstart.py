"""Cold-start benchmark: what does a replica boot cost, and what do AOT
warmup + the persistent compilation cache buy back?

Four boot scenarios over the same engine shape (trained smoke denoiser,
batch-bucket x seq-bucket x nfe grid), measured from engine construction:

* ``cold``        — no warmup: the first request of every shape pays its
  own XLA compile at drain time (the pre-warmup serving behavior).
* ``aot``         — ``BatchedSampler.warmup()``: the grid is lowered and
  compiled from abstract shapes before the first request (no sampling).
* ``cache_cold``  — AOT warmup with an *empty* persistent compilation
  cache: same compile wall as ``aot``, but every program is written to
  disk (the first deploy of a fleet).  The cache lives where
  :func:`repro.serving.cache_dir` says: ``$JAX_COMPILATION_CACHE_DIR`` when
  set — which this bench never clears, so the record says whether the
  scenario really started cold — else the checkout's ``.jax_cache``, which
  it clears first.
* ``cache_warm``  — AOT warmup against the now-populated cache dir: the
  redeploy path, where warmup is disk loads instead of XLA compiles.

Reported per scenario (all seconds from engine construction):

* ``time_to_first_request_s`` — build + (warmup) + one batch=1 request at
  the smallest grid shape, drained to host.
* ``time_to_full_throughput_s`` — ... + one drain per remaining grid cell
  (after it, no shape in the configured grid can hit a compile).
* compile-source counts (``fresh`` / ``disk`` / ``memory``) at both
  marks, plus ``request_path_fresh_compiles`` — fresh compiles paid
  *after* boot warmup, i.e. on the serving path.  The acceptance bar:
  AOT and cache-warm boots serve their first request with strictly fewer
  request-path fresh compiles than a cold boot (0 vs 1).

The persistent-cache config is process-global (``jax.config``), so the
cache-less scenarios run first, and the cache is switched off again at
exit.  All four engines live in one process: the in-process ``_jitted``
executable cache is per-engine, so a later scenario never reuses an
earlier scenario's executables — only the on-disk cache carries over,
which is exactly the effect under measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks import common as C  # noqa: E402

from repro.serving import (  # noqa: E402
    BatchedSampler,
    SampleRequest,
    configure_persistent_cache,
)
from repro.serving.compile_cache import CACHE_DIR_ENV, cache_dir  # noqa: E402
from repro.serving import result_keys as K  # noqa: E402

BATCH_BUCKETS = (1, 2) if C.SMOKE else (1, 4, 8)
SEQ_BUCKETS = (4, 8) if C.SMOKE else (8, 16)
NFES = (5,) if C.SMOKE else (6, 10)


def _grid():
    return [
        (b, s, n) for n in NFES for s in SEQ_BUCKETS for b in BATCH_BUCKETS
    ]


def boot(mode: str, dlm, params) -> dict:
    """One engine boot under ``mode``'s warmup policy; returns the
    scenario record (see module docstring for the fields)."""
    t0 = time.perf_counter()
    engine = BatchedSampler(
        dlm, C.SCHEDULE,
        batch_buckets=BATCH_BUCKETS, seq_buckets=SEQ_BUCKETS,
    )
    build_s = time.perf_counter() - t0
    warm_rep = None
    if mode != "cold":
        warm_rep = engine.warmup(params, nfes=NFES)
    stats_boot = engine.compile_stats()

    grid = _grid()
    first = grid[0]
    seed = iter(range(1, len(grid) + 1))

    def serve(b, s, n):
        _, fut = engine.submit_with_future(
            SampleRequest(batch=b, seq_len=s, nfe=n, seed=next(seed))
        )
        engine.drain(params)
        fut.result()

    serve(*first)
    ttfr = time.perf_counter() - t0
    stats_ttfr = engine.compile_stats()
    for cell in grid[1:]:
        serve(*cell)
    ttft = time.perf_counter() - t0
    stats_ttft = engine.compile_stats()

    return {
        "mode": mode,
        "build_s": build_s,
        "warmup": warm_rep
        and {
            k: warm_rep[k]
            for k in ("programs", "fresh", "disk", "memory", K.WALL_S)
        },
        "time_to_first_request_s": ttfr,
        "time_to_full_throughput_s": ttft,
        "compiles_at_boot": stats_boot,
        "compiles_at_first_request": stats_ttfr,
        "compiles_at_full_throughput": stats_ttft,
        # fresh compiles the *serving path* paid (boot warmup excluded)
        "request_path_fresh_compiles": stats_ttfr["fresh"]
        - stats_boot["fresh"],
        "request_path_fresh_compiles_full": stats_ttft["fresh"]
        - stats_boot["fresh"],
    }


def run(out: str = "BENCH_coldstart.json") -> None:
    dlm, params, _, _ = C.trained_model(30 if C.SMOKE else 150)
    scenarios = []
    # order matters: the persistent-cache config is process-global, so the
    # cache-less boots must run before the cache dir is enabled
    for mode in ("cold", "aot"):
        scenarios.append(boot(mode, dlm, params))
    # an outside-placed cache is never cleared: it may hold other programs
    cleared = not os.environ.get(CACHE_DIR_ENV)
    if cleared:
        shutil.rmtree(cache_dir(), ignore_errors=True)
    try:
        configure_persistent_cache()
        for mode in ("cache_cold", "cache_warm"):
            scenarios.append(boot(mode, dlm, params))
    finally:
        # the cache config is process-global: unless the environment placed
        # it, later suites of a benchmarks.run invocation compile without it
        if cleared:
            import jax
            from jax._src import compilation_cache as _cc

            jax.config.update("jax_compilation_cache_dir", None)
            _cc.reset_cache()

    by_mode = {s["mode"]: s for s in scenarios}
    record = {
        "bench": "serving/coldstart",
        "smoke": C.SMOKE,
        "grid": {
            "batch_buckets": list(BATCH_BUCKETS),
            "seq_buckets": list(SEQ_BUCKETS),
            "nfes": list(NFES),
            "programs": len(_grid()),
        },
        "cache_dir": cache_dir(),
        "cache_cleared_before_cache_cold": cleared,
        "scenarios": scenarios,
    }

    for s in scenarios:
        C.emit(
            f"serving/coldstart/{s['mode']}/ttfr",
            s["time_to_first_request_s"] * 1e6,
            f"fresh_on_request_path={s['request_path_fresh_compiles']}",
        )
        C.emit(
            f"serving/coldstart/{s['mode']}/full",
            s["time_to_full_throughput_s"] * 1e6,
            f"fresh_on_request_path={s['request_path_fresh_compiles_full']}",
        )

    # acceptance: warmed boots must serve their first request with strictly
    # fewer request-path fresh compiles than a cold boot
    cold_fresh = by_mode["cold"]["request_path_fresh_compiles"]
    for mode in ("aot", "cache_warm"):
        if by_mode[mode]["request_path_fresh_compiles"] >= cold_fresh:
            print(
                f"# WARNING: {mode} boot paid "
                f"{by_mode[mode]['request_path_fresh_compiles']} fresh "
                f"compiles at first request (cold paid {cold_fresh}) — "
                f"warmup did not cover the grid"
            )
    warm = by_mode["cache_warm"]["warmup"]
    if warm and warm["disk"] == 0:
        print(
            "# WARNING: cache_warm warmup loaded 0 programs from the "
            "persistent cache — jax_compilation_cache_dir is not taking "
            "effect"
        )

    with open(out, "w") as f:
        json.dump(record, f, indent=2)
    print(f"# wrote {out}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_coldstart.json")
    run(ap.parse_args().out)


if __name__ == "__main__":
    main()
