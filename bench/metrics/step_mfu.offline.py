"""Denoiser: useful forward flops (bench/flops.py; each request's rows at its own length, no pad rows) times NFE, per second of the window, over the chips' bf16 peak."""

from __future__ import annotations

from bench.metrics import common

from bench import flops


def read(record, trace):
    if record["entry"] != "offline" or not record.get("peaks"):
        return None
    cfg = record["config"]
    work = sum(
        flops.forward_flops(cfg, c["req"].rows, c["req"].seq_len) * c["req"].nfe
        for c in common.answered(record)
    )
    peak = record["chips"] * record["peaks"]["bf16_flops_per_s"]
    return 100.0 * work / record["window_s"] / peak
