"""The benchmark's one traffic generator.

A traffic mix is a data file, ``bench/traffic/<name>.json``, read here:

* ``arrivals``: ``{"kind": "closed", "in_flight": n}`` keeps ``n`` requests
  outstanding and sends the next one when one completes (offline batch
  jobs).
* ``rows``: ``{"values": [...], "weights": [...]}``, the rows (samples) of
  one request.
* ``seq_len``: ``{"kind": "fixed", "value": n}``, or ``{"kind":
  "choice", "values": [...], "weights": [...]}``.
* ``nfe`` and ``solver``.

Every seed gets the same work: the sizes repeat a block drawn from a fixed
stream, and the run's ``--seed`` only shuffles their order and picks each
request's noise seed.  So two seeds differ in order, not in the amount of
work, and their runs can be compared.
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: request noise seeds lie in [0, SEED_SPACE): ``jax.random.PRNGKey`` takes
#: them as they are
SEED_SPACE = 2**31 - 1
#: the fixed stream every seed's block of sizes comes from
SHAPE_STREAM = 20240613
#: closed-loop traffic repeats a shuffled block of this many sizes
CLOSED_BLOCK = 64


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    rows: int
    seq_len: int
    nfe: int
    solver: str
    seed: int        # x_T = normal(PRNGKey(seed), (rows, seq_len, d))


def run_rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one purpose of one run; any whole ``seed``."""
    return np.random.default_rng([stream, int(seed) % 2**64])


def _choice(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    w = np.asarray(spec["weights"], np.float64)
    return rng.choice(np.asarray(spec["values"]), size=n, p=w / w.sum())


def _sizes(spec: dict, n: int, rng: np.random.Generator, seq_divisor: int):
    rows = _choice(spec["rows"], n, rng)
    s = spec["seq_len"]
    if s["kind"] == "fixed":
        seq = np.full(n, s["value"])
    elif s["kind"] == "choice":
        seq = _choice(s, n, rng)
    else:
        raise ValueError(f"unknown seq_len kind {s['kind']!r}")
    return rows.astype(int), (seq // seq_divisor).astype(int)


def generate(spec: dict, seed: int, seq_divisor: int = 1):
    """The requests of one run: an endless iterator (the entry stops asking
    when its window closes)."""
    kind = spec["arrivals"]["kind"]
    if kind != "closed":
        raise ValueError(f"unknown arrivals kind {kind!r}")
    rows, seq = _sizes(spec, CLOSED_BLOCK, np.random.default_rng(SHAPE_STREAM), seq_divisor)
    perm = run_rng(seed, 1).permutation(CLOSED_BLOCK)
    seed_rng = run_rng(seed, 2)
    nfe, solver = int(spec["nfe"]), spec.get("solver", "era")
    i = 0
    while True:
        j = perm[i % CLOSED_BLOCK]
        yield Request(i, int(rows[j]), int(seq[j]), nfe, solver,
                      int(seed_rng.integers(SEED_SPACE)))
        i += 1
