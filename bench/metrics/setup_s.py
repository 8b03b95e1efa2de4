"""Set-up: process start to the window's first request (TPU init, weights, program warmup and load, the warm requests)."""

from __future__ import annotations

def read(record, trace):
    return record["setup_s"]
