"""The program's own spans and name scopes, read from a profiler trace.

The sampler marks its host work with ``sampler.*`` spans
(``jax.profiler.TraceAnnotation``: host events of the trace, found by
name) and its device ops with name scopes (``jax.named_scope``:
``denoiser``, ``era.ers``, ``era.update``).  A scope is op metadata.  In
the raw trace neither the op events' stats nor a line of the device plane
carry it: only the HLO of each program does, one ``Hlo Proto`` stat per
program on the ``/host:metadata`` plane, which ``ProfileData`` does not
expose.  :func:`hlo_op_names` decodes those few protobuf fields by hand
(no protobuf library), and :func:`load` gives each device op event of
:func:`tracing.load` the ``scope`` its HLO instruction was traced under.

:func:`summary` reduces such events to

* ``spans``: for each ``sampler.*`` span name, the count of its events in
  the window, their seconds clipped to it, and the device idle seconds
  (first chip, as ``tracing.reduce``'s gaps) that fall inside them;
* ``scopes``: the device self time (``tracing._self_times``) of the ops
  under each scope in the window, averaged over the chips; an op XLA
  made, which has no scope of its own, counts under the op that encloses
  it on the device's timeline.

A name absent from the trace is absent from the summary, so a trace of a
program without the spans or scopes gives empty maps.  The same events
reduce to the same ``tracing.reduce`` numbers: ``scope`` is one more key.
"""

from __future__ import annotations

import bisect
import glob
import os

from bench import tracing

SPAN_PREFIX = "sampler."
SCOPES = ("denoiser", "era.ers", "era.update")
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
MODULE_LINE = "XLA Modules"


# ---------------------------------------------------------------------------
# protobuf wire format: just enough to walk XSpace -> HloProto
# ---------------------------------------------------------------------------


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: varints as ints,
    length-delimited fields as memoryview slices; fixed-width fields are
    skipped."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} is not supported")
        yield field, value


def _first(buf, number: int, default=None):
    for field, value in _fields(buf):
        if field == number:
            return value
    return default


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace") if value is not None else ""


def _module_op_names(hlo_proto) -> dict[str, str]:
    """HLO instruction name -> its ``op_name`` metadata, for one
    ``HloProto`` (hlo_module = 1; computations = 3; instructions = 2;
    instruction name = 1, metadata = 7; op_name = 2)."""
    out = {}
    module = _first(hlo_proto, 1)
    if module is None:
        return out
    for field, comp in _fields(module):
        if field != 3:
            continue
        for f, instr in _fields(comp):
            if f != 2:
                continue
            name, meta = None, None
            for g, v in _fields(instr):
                if g == 1:
                    name = _text(v)
                elif g == 7:
                    meta = v
            if name and meta is not None:
                op_name = _text(_first(meta, 2))
                if op_name:
                    out[name] = op_name
    return out


def hlo_op_names(log_dir: str) -> dict[str, dict[str, str]]:
    """Program name (as the trace's ``XLA Modules`` line names it, e.g.
    ``jit_run(123)``) -> instruction name -> ``op_name``, from the
    ``Hlo Proto`` stats of the ``/host:metadata`` plane (XSpace.planes = 1;
    XPlane name = 2, event_metadata = 4, stat_metadata = 5; map entries
    key = 1, value = 2; XEventMetadata name = 2, stats = 5; XStat
    metadata_id = 1, bytes_value = 6)."""
    out: dict[str, dict[str, str]] = {}
    for path in sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)):
        with open(path, "rb") as f:
            space = f.read()
        for field, plane in _fields(space):
            if field != 1 or _text(_first(plane, 2)) != METADATA_PLANE:
                continue
            stat_ids = {
                _first(_first(entry, 2, b""), 1, 0)
                for f, entry in _fields(plane)
                if f == 5 and _text(_first(_first(entry, 2, b""), 2)) == HLO_PROTO_STAT
            }
            for f, entry in _fields(plane):
                if f != 4:
                    continue
                meta = _first(entry, 2, b"")
                name = _text(_first(meta, 2))
                for g, stat in _fields(meta):
                    if g == 5 and _first(stat, 1, 0) in stat_ids:
                        out[name] = _module_op_names(_first(stat, 6, b""))
    return out


def module_spans(log_dir: str) -> dict[str, list[tuple[int, int, str]]]:
    """Device plane -> its ``XLA Modules`` events, ``(start, end, name)``
    in order of start: which program each op event ran in."""
    import jax

    out: dict[str, list] = {}
    for path in sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)):
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    out.setdefault(plane.name, []).extend(
                        (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                        for e in line.events
                    )
    for spans in out.values():
        spans.sort()
    return out


# ---------------------------------------------------------------------------
# events -> scopes
# ---------------------------------------------------------------------------


def scope_of(op_name: str | None) -> str | None:
    """The innermost of :data:`SCOPES` on an ``op_name`` path
    (``jit(run)/while/body/.../era.update/...``), or None."""
    for part in reversed((op_name or "").split("/")):
        if part in SCOPES:
            return part
    return None


def annotate(events: list[dict], op_names: dict[str, dict[str, str]],
             modules: dict[str, list[tuple[int, int, str]]]) -> list[dict]:
    """Give each device op event its ``scope``: its HLO instruction's
    ``op_name`` in the program whose ``XLA Modules`` event covers it (else
    in the one program that has an instruction of that name)."""
    owners: dict[str, list[str]] = {}
    for module, names in op_names.items():
        for instr in names:
            owners.setdefault(instr, []).append(module)
    starts = {p: [s for s, _, _ in spans] for p, spans in modules.items()}
    for e in events:
        if not e["plane"].startswith("/device:"):
            continue
        instr = tracing.op_name(e).lstrip("%")
        module = None
        spans = modules.get(e["plane"], ())
        k = bisect.bisect_right(starts.get(e["plane"], ()), e["t"]) - 1
        if k >= 0 and spans[k][1] >= e["t"]:
            module = spans[k][2]
        elif len(owners.get(instr, ())) == 1:
            module = owners[instr][0]
        e["scope"] = scope_of(op_names.get(module, {}).get(instr))
    return events


def load(log_dir: str) -> list[dict]:
    """:func:`tracing.load`'s events, each device op with its ``scope``."""
    return annotate(tracing.load(log_dir), hlo_op_names(log_dir), module_spans(log_dir))


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


def _idle(events, plane, lo, hi):
    """The first chip's idle intervals in the window, as ``tracing.reduce``
    finds its gaps."""
    merged = tracing._union(
        [c for c in (tracing._clip(e, lo, hi) for e in tracing._op_events(events, plane)) if c]
    )
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] >= tracing.MIN_GAP_NS]


def spans(events, lo: int, hi: int, idle=()) -> dict[str, dict]:
    """Count, seconds and idle seconds of each ``sampler.*`` span in the
    window (a span's events are disjoint: one thread runs one chunk)."""
    out: dict[str, dict] = {}
    for e in events:
        if not (e["plane"].startswith("/host:") and e["name"].startswith(SPAN_PREFIX)):
            continue
        c = tracing._clip(e, lo, hi)
        if not c:
            continue
        s = out.setdefault(e["name"], {"count": 0, "s": 0.0, "idle_s": 0.0})
        s["count"] += 1
        s["s"] += (c[1] - c[0]) / 1e9
        s["idle_s"] += sum(max(0, min(b, c[1]) - max(a, c[0])) for a, b in idle) / 1e9
    return out


def _inherit(ops):
    """Each op with the scope it ran under: its own, else that of the
    innermost op that encloses it on its line.  XLA's own ops (layout
    copies, memory-space prefetches, some multi-output fusions) carry no
    scope, and run inside the loop or conditional that needs them."""
    out = []
    by_line: dict[str, list] = {}
    for e in ops:
        by_line.setdefault(e["line"], []).append(e)
    for evs in by_line.values():
        stack = []   # (end, scope)
        for e in sorted(evs, key=lambda e: (e["t"], -e["d"])):
            while stack and stack[-1][0] <= e["t"]:
                stack.pop()
            scope = e.get("scope") or (stack[-1][1] if stack else None)
            stack.append((e["t"] + e["d"], scope))
            out.append(dict(e, scope=scope))
    return out


def scopes(events, lo: int, hi: int) -> dict[str, float]:
    """Device self seconds of the ops under each scope in the window
    (:func:`_inherit`), averaged over the chips; a scope with no op of
    its own is left out."""
    planes = tracing.device_planes(events)
    total: dict[str, float] = {}
    for plane in planes:
        clipped = []
        for e in tracing._op_events(events, plane):
            c = tracing._clip(e, lo, hi)
            if c:
                clipped.append(dict(e, t=c[0], d=c[1] - c[0]))
        for e, self_ns in tracing._self_times(_inherit(clipped)):
            scope = e["scope"]
            if scope:
                total[scope] = total.get(scope, 0.0) + max(self_ns, 0) / 1e9
    return {k: v / len(planes) for k, v in total.items()}


def summary(events: list[dict]) -> dict:
    """``spans`` and ``scopes`` of the window (module docstring)."""
    lo, hi = tracing.window(events)
    planes = tracing.device_planes(events)
    idle = _idle(events, planes[0], lo, hi) if planes else ()
    return {"spans": spans(events, lo, hi, idle), "scopes": scopes(events, lo, hi)}
