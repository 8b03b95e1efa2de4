"""Helpers the metric readers share."""

from __future__ import annotations

import statistics


def answered(record):
    return [c for c in record["completed"] if c.get("x0") is not None]


def median(values):
    values = list(values)
    return statistics.median(values) if values else None


def chunks(record):
    """One entry per fused chunk of the window: its batch_wall_s names it
    (batch-mates share the float), with its padded shape."""
    seen = {}
    for c in answered(record):
        key = (c["batch_wall_s"], c["padded_batch"], c["padded_seq_len"])
        seen.setdefault(key, c)
    return list(seen.values())
