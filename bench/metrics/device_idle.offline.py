"""Device: share of the traced window in which no op ran on the chip (averaged over chips)."""

from __future__ import annotations

def read(record, trace):
    if record["entry"] != "offline" or not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
