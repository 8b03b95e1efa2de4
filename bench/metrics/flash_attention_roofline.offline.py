"""Kernel: the flash-attention calls' least time on the chip (the larger of their flops over the bf16 peak and their bytes over HBM bandwidth; compute bounds them at these shapes) over their traced device time."""

from __future__ import annotations

from bench.metrics import common

from bench import flops


def read(record, trace):
    if record["entry"] != "offline" or not trace or not record.get("peaks"):
        return None
    k = trace["kernels"].get("flash_attention")
    if not k or not k["count"]:
        return None
    cfg, pk = record["config"], record["peaks"]
    c = common.chunks(record)[0]
    calls = flops.flash_work(cfg, c["padded_batch"], c["padded_seq_len"])
    least = sum(
        n * max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"]) for f, b, n in calls
    ) / sum(n for _, _, n in calls)
    return 100.0 * k["count"] * least / k["s"]
