"""Profiler traces: capture a window, and reduce it to numbers.

``capture`` writes JAX's profiler trace of the measured window into a
directory of the checkout; ``load`` reads its ``.xplane.pb`` back into
plain events; ``reduce`` turns them into what the per-layer readers need:

* the window, found as the benchmark's own ``bench.window`` host span;
* device busy time: the union of the intervals of the device's op events
  in the window (averaged over the chips used), and so the idle share;
* per-op self time on the device (an op's duration less the ops nested in
  it on the same line), for the breakdown;
* each kernel's total time and call count (``KERNELS``);
* idle gaps, each named by the innermost host event that covers most of it.

The reduction works on a list of events, so it is tested on a small trace
recorded on the chip (``bench/testdata``).
"""

from __future__ import annotations

import contextlib
import glob
import os
import re

WINDOW_SPAN = "bench.window"
#: idle gaps shorter than this are not named one by one
MIN_GAP_NS = 50_000
#: device lines that hold ops (TPU), in order of preference
OP_LINES = ("XLA Ops",)
SKIP_LINES = ("Steps", "XLA Modules", "Framework Ops", "Framework Name Scope",
              "Source code", "XLA TraceMe", "Sparse Core Ops")
#: how each kernel's events are found.  On the TPU an op event is named by
#: its HLO text.  The flash kernel's custom call carries its Pallas name;
#: the fused ERA step runs inside a jitted helper, so its custom call is
#: named after the call site, and is found by its operands instead: the
#: three scalar-prefetch vectors (Lagrange weights, AM4, (cx, ce)) lead.
KERNELS = {
    "flash_attention": re.compile(r"flash_attention"),
    "era_update": re.compile(
        r"era_update|custom-call\(f32\[\d+\]\S* \S+, f32\[4\]\S* \S+, "
        r"f32\[2\]\S* \S+, .*tpu_custom_call"
    ),
}
#: the host thread XLA's CPU backend runs ops on, and the plane its ops
#: are filed under (rehearsals only: a CPU run measures nothing)
CPU_OPS_LINE = "tf_XLAPjRtCpuClient"
CPU_DEVICE = "/device:CPU:0"
def options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # no per-call Python events: too costly
    opts.host_tracer_level = 2     # TraceMe annotations, the bench's spans
    return opts


@contextlib.contextmanager
def capture(log_dir: str):
    import jax

    os.makedirs(log_dir, exist_ok=True)
    with jax.profiler.trace(log_dir, profiler_options=options()):
        yield


def load(log_dir: str) -> list[dict]:
    """Every event of the trace under ``log_dir``: plane, line, name, and
    start ``t`` and duration ``d`` in ns."""
    import jax

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    events = []
    for path in files:
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            pname = plane.name
            device = pname.startswith("/device:")
            if not (device or pname.startswith("/host:")):
                continue
            for line in plane.lines:
                lname = line.name
                if device and lname in SKIP_LINES:
                    continue
                # on the CPU (rehearsals) XLA's ops run on a host thread
                cpu_ops = not device and lname.startswith(CPU_OPS_LINE)
                for e in line.events:
                    if cpu_ops and "hlo_op" not in dict(e.stats):
                        continue
                    events.append({
                        "plane": CPU_DEVICE if cpu_ops else pname, "line": lname,
                        "name": e.name, "t": int(e.start_ns), "d": int(e.duration_ns),
                    })
    return events


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(ev, lo, hi):
    s, e = max(ev["t"], lo), min(ev["t"] + ev["d"], hi)
    return (s, e) if e > s else None


def device_planes(events) -> list[str]:
    planes = sorted({e["plane"] for e in events if e["plane"].startswith("/device:")})
    # a TPU trace names chips /device:TPU:0 ..; other device planes (a
    # host-offload or sparse core) are not chips
    tpu = [p for p in planes if p.startswith("/device:TPU:") and p[12:].isdigit()]
    return tpu or planes


def _op_events(events, plane):
    evs = [e for e in events if e["plane"] == plane]
    lines = {e["line"] for e in evs}
    for pref in OP_LINES:
        if pref in lines:
            return [e for e in evs if e["line"] == pref]
    return evs


def _self_times(ops):
    """Per-op self time: duration less the ops nested inside it (same
    line), so a loop or a conditional does not count its body twice."""
    out = []
    by_line = {}
    for e in ops:
        by_line.setdefault(e["line"], []).append(e)
    for evs in by_line.values():
        evs = sorted(evs, key=lambda e: (e["t"], -e["d"]))
        stack = []   # [end, index into out]
        for e in evs:
            end = e["t"] + e["d"]
            while stack and stack[-1][0] <= e["t"]:
                stack.pop()
            if stack:
                out[stack[-1][1]][1] -= e["d"]
            out.append([e, e["d"]])
            stack.append((end, len(out) - 1))
    return out


def window(events) -> tuple[int, int]:
    spans = [e for e in events if e["name"] == WINDOW_SPAN]
    if not spans:
        raise RuntimeError(f"the trace holds no {WINDOW_SPAN} span")
    span = max(spans, key=lambda e: e["d"])
    return span["t"], span["t"] + span["d"]


def op_name(event) -> str:
    """An op's short name: its HLO text up to the ``=``."""
    return event["name"].split(" = ", 1)[0]


def kernel_calls(events, plane, kernel: str, lo: int, hi: int):
    """(count, total ns) of one kernel's events on one device in the window."""
    n, total = 0, 0
    pattern = KERNELS[kernel]
    for e in _op_events(events, plane):
        if pattern.search(e["name"]):
            c = _clip(e, lo, hi)
            if c:
                n += 1
                total += c[1] - c[0]
    return n, total


def reduce(events: list[dict], kernels=tuple(KERNELS)) -> dict:
    lo, hi = window(events)
    planes = device_planes(events)
    if not planes:
        raise RuntimeError("the trace holds no device plane")
    busy, gaps = [], []
    ops_self: dict[str, float] = {}
    kern = {k: [0, 0] for k in kernels}
    for plane in planes:
        ops = _op_events(events, plane)
        merged = _union([c for c in (_clip(e, lo, hi) for e in ops) if c])
        busy.append(sum(e - s for s, e in merged))
        clipped = []
        for e in ops:
            c = _clip(e, lo, hi)
            if c:
                clipped.append(dict(e, t=c[0], d=c[1] - c[0]))
        for e, self_ns in _self_times(clipped):
            name = op_name(e)
            ops_self[name] = ops_self.get(name, 0) + max(self_ns, 0)
        for k in kernels:
            n, t = kernel_calls(events, plane, k, lo, hi)
            kern[k][0] += n
            kern[k][1] += t
        if plane == planes[0]:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [
                (edges[i], edges[i + 1])
                for i in range(0, len(edges), 2)
                if edges[i + 1] - edges[i] >= MIN_GAP_NS
            ]
    named = name_gaps(events, gaps)
    n = len(planes)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "devices": n,
        "kernels": {k: {"count": c, "s": t / 1e9} for k, (c, t) in kern.items()},
        "device_ops": sorted(
            ((k, v / 1e9) for k, v in ops_self.items()), key=lambda kv: -kv[1]
        )[:10],
        "idle_gaps": sorted(named.items(), key=lambda kv: -kv[1])[:10],
    }


def name_gaps(events, gaps, top: int = 200) -> dict[str, float]:
    """Idle seconds by what the host was doing: each of the ``top``
    longest gaps is named by the innermost host event that covers at least
    half of it (else the one that covers most of it); shorter gaps are
    summed apart."""
    import numpy as np

    host = [e for e in events if e["plane"].startswith("/host:")
            and e["name"] != WINDOW_SPAN and e["d"] > 0]
    t = np.asarray([h["t"] for h in host], np.int64)
    end = t + np.asarray([h["d"] for h in host], np.int64)
    names = [h["name"] for h in host]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    out: dict[str, float] = {}
    for i, (s, e) in enumerate(gaps):
        if i >= top:
            label = "shorter gaps"
        elif not host:
            label = "no host event"
        else:
            ov = np.minimum(end, e) - np.maximum(t, s)
            dur = np.where(ov >= (e - s) / 2, end - t, np.iinfo(np.int64).max)
            if dur.min() < np.iinfo(np.int64).max:
                label = names[int(dur.argmin())]
            elif ov.max() > 0:
                label = names[int(ov.argmax())]
            else:
                label = "no host event"
        out[label] = out.get(label, 0.0) + (e - s) / 1e9
    return out
