"""Offline: samples whose x0 reached the host, over the window from its start to the last completion."""

from __future__ import annotations

from bench.metrics import common

def read(record, trace):
    if record["entry"] != "offline":
        return None
    return sum(c["req"].rows for c in common.answered(record)) / record["window_s"]
