"""Solver program: device wall time of one NFE, the chunk's batch_wall_s over its scanned NFE, median over the window's chunks."""

from __future__ import annotations

from bench.metrics import common

def read(record, trace):
    if record["entry"] != "offline":
        return None
    return common.median(c["batch_wall_s"] / c["padded_nfe"] * 1e3 for c in common.chunks(record))
