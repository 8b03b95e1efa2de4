"""Mesh-vs-single-device parity check for the batched sampling engine.

Run as a script under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(prints one JSON record on stdout), or import :func:`run_parity` from a test
process that already has >= 8 devices.  Either way it flushes the same mixed
request stream through an 8-way mesh-sharded engine and a plain single-
device engine and reports the max output difference plus placement facts.
"""

from __future__ import annotations

import json


def run_parity(seq_len: int = 6, nfe: int = 10) -> dict:
    import jax
    import numpy as np

    from conftest import AnalyticGaussian, OracleDenoiser, run_flush
    from repro.launch.mesh import make_sampler_mesh
    from repro.serving import FusedExecutor, SampleRequest

    analytic = AnalyticGaussian()
    mesh = make_sampler_mesh(8)
    meshed = FusedExecutor(
        OracleDenoiser(analytic), analytic.schedule, mesh=mesh
    )
    single = FusedExecutor(
        OracleDenoiser(analytic), analytic.schedule, batch_buckets=None
    )

    # mixed sizes: 1 + 3 + 2 = 6 rows, padding to the dp-rounded 8-bucket
    reqs = [
        SampleRequest(batch=b, seq_len=seq_len, nfe=nfe, seed=s)
        for b, s in [(1, 3), (3, 4), (2, 5)]
    ]
    res_m = run_flush(meshed, reqs)
    res_s = run_flush(single, reqs)

    max_diff = 0.0
    for rm, rs in zip(res_m, res_s):
        diff = np.max(np.abs(np.asarray(rm.x0) - np.asarray(rs.x0)))
        max_diff = max(max_diff, float(diff))

    # a full-bucket request, to read the placement off an unsliced result
    full = SampleRequest(batch=8, seq_len=seq_len, nfe=nfe, seed=9)
    (full_m,) = run_flush(meshed, [full])
    (full_s,) = run_flush(single, [full])
    max_diff = max(
        max_diff,
        float(np.max(np.abs(np.asarray(full_m.x0) - np.asarray(full_s.x0)))),
    )
    return {
        "devices": jax.device_count(),
        "dp": meshed.dp,
        "buckets": list(meshed.batch_buckets),
        "padded_batch": res_m[0].padded_batch,
        "x0_devices": len(full_m.x0.sharding.device_set),
        "max_diff": max_diff,
    }


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    print(json.dumps(run_parity()))
