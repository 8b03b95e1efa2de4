"""Whether the window's answers are right.

Every answer the window produced must have its request's shape and be
finite, and no request may fail.  A sample of the answers, drawn from the
run's seed, is then held to the float32 reference (``bench/reference.py``)
at each request's exact shape: each sampled row's relative error
``||x0 - x0_ref|| / ||x0_ref||``, and the number compared is the worst.
The sample is ``requests`` of the window's first ``OFFLINE_FIRST`` answers,
so that it does not turn on how many answers the window held, and
``rows_per_request`` rows of each: always the first and the last row, which
sit at the two ends of their fused batch, and the rest drawn at random.

The limit (``check.limit`` in the cell's file, ``rehearse_limit`` at the
smoke sizes) lies between the largest error sound runs read and the
smallest the control reads: the reference computed with float8 matrix
products put in the program's place (``bench/calibrate.py``; ``PERF.md``
gives the readings).  A cell without a limit is never correct.
"""

from __future__ import annotations

import numpy as np

from bench import reference
from bench.traffic import run_rng

CHECK_STREAM = 3
OFFLINE_FIRST = 8


def relerr(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def pick(record: dict, spec: dict, seed: int) -> list[tuple[dict, list[int]]]:
    """The sampled answers: (completed request, its row indices)."""
    done = [c for c in record["completed"] if c.get("x0") is not None][:OFFLINE_FIRST]
    rng = run_rng(seed, CHECK_STREAM)
    idx = rng.choice(len(done), size=min(int(spec["requests"]), len(done)), replace=False)
    out = []
    for i in sorted(idx):
        rows = done[i]["req"].rows
        k = min(int(spec["rows_per_request"]), rows)
        chosen = {0, rows - 1}
        others = list(range(1, rows - 1))
        if k > len(chosen) and others:
            chosen |= set(rng.choice(others, size=min(k - len(chosen), len(others)),
                                     replace=False).tolist())
        out.append((done[i], sorted(int(r) for r in chosen)))
    return out


def reference_rows(params, cfg: dict, req, rows: list[int], nfe: int,
                   precision: str = "f32") -> np.ndarray:
    x_t = reference.request_noise(req.seed, req.rows, req.seq_len, cfg["hidden_size"])
    return reference.sample(params, x_t[rows], cfg, nfe, precision=precision)


def control(record: dict, params, cfg: dict, spec: dict, seed: int) -> dict:
    """The record with the control's answers in the program's place: each
    sampled row as the float8 reference computes it."""
    swapped = {}
    for c, rows in pick(record, spec, seed):
        req = c["req"]
        x0 = np.array(c["x0"], copy=True)
        x0[rows] = reference_rows(params, cfg, req, rows, req.nfe, "fp8")
        swapped[id(c)] = dict(c, x0=x0)
    completed = [swapped.get(id(c), c) for c in record["completed"]]
    return dict(record, completed=completed)


def run(record: dict, params, cfg: dict, spec: dict, seed: int, rehearse: bool) -> dict:
    """The numbers compared, each with its limit."""
    bad = 0
    for c in record["completed"]:
        x0 = c.get("x0")
        want = (c["req"].rows, c["req"].seq_len, cfg["hidden_size"])
        if x0 is None or x0.shape != want or not np.all(np.isfinite(x0)):
            bad += 1
    limit = spec.get("rehearse_limit" if rehearse else "limit")
    errs = []
    if bad == 0:
        for c, rows in pick(record, spec, seed):
            req = c["req"]
            ref = reference_rows(params, cfg, req, rows, req.nfe)
            errs += [relerr(c["x0"][r], ref[j]) for j, r in enumerate(rows)]
    return {
        "failed_requests": {"value": record["failed"], "limit": 0},
        "bad_answers": {"value": bad, "limit": 0},
        "x0_relerr_max": {
            "value": max(errs) if errs else None,
            "limit": None if limit is None else float(limit),
        },
    }


def correct(compared: dict) -> bool:
    """Every number read and limited, and none above its limit."""
    return all(
        c["value"] is not None and c["limit"] is not None and c["value"] <= c["limit"]
        for c in compared.values()
    )
