"""Subprocess child for the persistent compile-cache round-trip test.

One replica boot: build the Oracle engine through ``build_engine`` with
``warmup="grid"`` and the persistent compile cache on (placed by the
parent through ``JAX_COMPILATION_CACHE_DIR``), run the grid warmup, serve
one request, and print a JSON record of the warmup report /
compile-source counters / an x0 checksum.  The parent runs this twice
against the same cache dir and asserts the second boot's warmup came from
disk, with bit-identical sampling output.
"""

import json

# sys.path[0] is this script's dir (tests/), so conftest resolves; the
# parent provides src/ on PYTHONPATH
from conftest import AnalyticGaussian, OracleDenoiser, run_flush

from repro.serving import (
    EngineConfig,
    SampleRequest,
    build_engine,
    warmup_kwargs,
)


def main() -> None:
    analytic = AnalyticGaussian()
    cfg = EngineConfig(
        nfe=6,
        k=3,
        batch_buckets=(1, 2),
        seq_buckets=(4, 8),
        warmup="grid",
        compile_cache=True,
    )
    engine = build_engine(OracleDenoiser(analytic), analytic.schedule, cfg)
    report = engine.warmup(None, **warmup_kwargs(cfg))

    (res,) = run_flush(engine, [SampleRequest(batch=2, seq_len=8, nfe=6, seed=7)])
    x0 = res.x0

    print(
        json.dumps(
            {
                "warmup": {
                    k: report[k]
                    for k in ("programs", "fresh", "disk", "memory")
                },
                "compile_stats": engine.compile_stats(),
                "x0_sum": float(x0.sum()),
            }
        )
    )


if __name__ == "__main__":
    main()
