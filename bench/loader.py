"""Finds a cell's files by name.

``BENCHMARK.json`` at the root names the cells and metrics.  Each cell has
``bench/workloads/<cell>.json`` (its configuration, traffic, chips, entry,
engine settings and correctness check), which names
``bench/configs/<config>.json`` and ``bench/traffic/<traffic>.json``.  Its
entry is ``bench/entries/<entry>.py`` and each metric has a reader,
``bench/metrics/<metric>.py``.  Each kind in a configuration's
``layer_types`` has a module, ``bench/layers/<kind>.py``, that holds its
reference, its work counts and the program keys it is held to
(``bench/layers/__init__.py`` gives the interface).

Adding a cell or a metric adds files; no file here changes.  So does a
configuration of a new architecture: ``bench/configs/<config>.json``, one
``bench/layers/<kind>.py`` for each kind the benchmark has no module for,
and its workload and traffic files.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(*parts) -> dict:
    path = os.path.join(BENCH, *parts)
    if not os.path.isfile(path):
        raise SystemExit(f"no such file: {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(name: str) -> dict:
    """A cell's file; a cell without a correctness limit is refused."""
    w = _json("workloads", f"{name}.json")
    check = w.get("check", {})
    if not all(isinstance(check.get(k), (int, float)) for k in ("limit", "rehearse_limit")):
        raise SystemExit(f"bench/workloads/{name}.json: check.limit and "
                         "check.rehearse_limit must be numbers")
    return w


def config(name: str) -> dict:
    return _json("configs", f"{name}.json")


def traffic(name: str) -> dict:
    return _json("traffic", f"{name}.json")


def peaks(device_kind: str) -> dict:
    table = _json("peaks.json")
    if device_kind not in table or device_kind.startswith("_"):
        raise SystemExit(
            f"device kind {device_kind!r} is not in bench/peaks.json; known: "
            f"{sorted(k for k in table if not k.startswith('_'))}"
        )
    return table[device_kind]


def _module(kind: str, name: str):
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"no {kind[:-1]} named {name!r} ({os.path.relpath(path, ROOT)})")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def entry(name: str):
    """The module that drives a cell's window: ``run(ctx) -> record``."""
    return _module("entries", name)


def metric(name: str):
    """A metric's reader: ``read(record, trace) -> float | None``."""
    return _module("metrics", name)


@functools.cache
def layer(kind: str):
    """A layer kind's module (``bench/layers/__init__.py``); an unknown
    kind is an error that names the missing file."""
    return _module("layers", kind)


def layers(cfg: dict) -> dict:
    """The module of every kind in ``cfg["layer_types"]``, by kind."""
    return {kind: layer(kind) for kind, _count in cfg["layer_types"]}


def cell_metrics(bench: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """The end-to-end and per-layer metrics a cell reports."""

    def has(m):
        return cell in m.get("workloads", [cell])

    ends = [m for m in bench["end_to_end"] if has(m)]
    reported = {m["name"] for m in ends}
    layers = [
        m for m in bench["per_layer"]
        if cell in m.get("workloads", [cell]) and m["moves"] in reported
    ]
    return ends, layers
