"""The program's spans and scopes in a profiler trace (bench/program_trace.py):
the protobuf walk to each op's scope, the reduction's arithmetic on
hand-built traces, and a slice recorded on a v5e chip."""

import glob
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import program_trace as pt  # noqa: E402
from bench import tracing  # noqa: E402

DEV = "/device:TPU:0"
OPS = "XLA Ops"
HOST = "/host:CPU"
US = 1000


def ev(plane, line, name, t, d, scope=None):
    e = {"plane": plane, "line": line, "name": name, "t": t, "d": d}
    if plane.startswith("/device:"):
        e["scope"] = scope
    return e


def chunk_trace():
    """One chunk boundary: the window 0..1000 us; a chunk's assembly
    (100..300, device idle), then its program (300..900)."""
    return [
        ev(HOST, "python", "bench.window", 0, 1000 * US),
        ev(HOST, "python", "bench.result_wait", 0, 1000 * US),
        ev(HOST, "python", "sampler.chunk", 100 * US, 850 * US),
        ev(HOST, "python", "sampler.assemble", 100 * US, 200 * US),
        ev(HOST, "python", "np.asarray(jax.Array)", 150 * US, 60 * US),
        ev(HOST, "python", "sampler.execute", 300 * US, 620 * US),
        ev(HOST, "python", "sampler.scatter", 920 * US, 30 * US),
        # the previous chunk's scatter, cut by the window's start
        ev(HOST, "python", "sampler.scatter", -20 * US, 40 * US),
        ev(DEV, OPS, "%fusion.1 = f32[8] fusion()", 0, 100 * US, "era.update"),
        # the scan: unscoped, holding a denoiser forward and the ERA step
        ev(DEV, OPS, "%while.2 = (s32[]) while()", 300 * US, 600 * US),
        ev(DEV, OPS, "%cond.3 = (f32[8]) conditional()", 310 * US, 400 * US),
        ev(DEV, OPS, "%convolution.4 = bf16[8] convolution()", 320 * US, 300 * US, "denoiser"),
        # a copy XLA put in the forward: no scope of its own
        ev(DEV, OPS, "%copy-done.8 = f32[8] copy-done()", 330 * US, 10 * US),
        ev(DEV, OPS, "%reduce.5 = f32[8] reduce()", 650 * US, 50 * US, "era.ers"),
        ev(DEV, OPS, "%while.6 = (f32[8]) while()", 720 * US, 100 * US, "era.update"),
        ev(DEV, OPS, "%custom-call.7 = f32[8] custom-call()", 730 * US, 60 * US, "era.update"),
        ev(DEV, "XLA Modules", "jit_run(1)", 300 * US, 600 * US),
    ]


def test_spans_by_hand():
    s = pt.summary(chunk_trace())["spans"]
    assert set(s) == {"sampler.chunk", "sampler.assemble", "sampler.execute", "sampler.scatter"}
    assert s["sampler.assemble"] == {"count": 1, "s": pytest.approx(200e-6),
                                     "idle_s": pytest.approx(200e-6)}
    # the scatter cut by the window counts, with its 20 us inside it
    assert s["sampler.scatter"]["count"] == 2
    assert s["sampler.scatter"]["s"] == pytest.approx(50e-6)
    # idle 100..300 and 900..1000: the chunk holds 200 + 50 of it
    assert s["sampler.chunk"]["idle_s"] == pytest.approx(250e-6)
    assert s["sampler.execute"]["idle_s"] == pytest.approx(20e-6)


def test_scopes_are_self_times():
    sc = pt.summary(chunk_trace())["scopes"]
    # the forward's self time and the copy nested in it
    assert sc["denoiser"] == pytest.approx(290e-6 + 10e-6)
    assert sc["era.ers"] == pytest.approx(50e-6)
    # the ERA loop's own time and its kernel: 40 + 60; plus the op before
    assert sc["era.update"] == pytest.approx(100e-6 + 100e-6)
    # the unscoped scan and cond keep their own self time
    assert sum(sc.values()) < tracing.reduce(chunk_trace())["busy_s"]


def test_scopes_average_over_chips():
    events = chunk_trace()
    events += [dict(e, plane="/device:TPU:1") for e in events if e["plane"] == DEV]
    events.append(ev("/device:TPU:1", OPS, "%dot.9 = bf16[8] dot()", 950 * US, 20 * US, "denoiser"))
    one = pt.summary(chunk_trace())["scopes"]
    two = pt.summary(events)["scopes"]
    assert two["denoiser"] == pytest.approx(one["denoiser"] + 10e-6)
    assert two["era.ers"] == pytest.approx(one["era.ers"])


def test_absent_spans_and_scopes_are_empty():
    """A trace of a program without the spans and scopes (or of events
    never annotated) reduces to empty maps, never to zeros."""
    bare = [dict(e) for e in chunk_trace() if not e["name"].startswith("sampler.")]
    for e in bare:
        e.pop("scope", None)
    assert pt.summary(bare) == {"spans": {}, "scopes": {}}


def test_summary_leaves_reduce_alone():
    events = chunk_trace()
    before = tracing.reduce([{k: v for k, v in e.items() if k != "scope"} for e in events])
    pt.summary(events)
    assert tracing.reduce(events) == before


@pytest.mark.parametrize("op_name,scope", [
    ("jit(run)/while/body/cond/branch_1_fun/denoiser/dot_general", "denoiser"),
    ("jit(run)/while/body/era.update/vmap(jit(era_step))/pallas_call", "era.update"),
    ("jit(run)/while/body/cond/branch_1_fun/era.ers/sqrt", "era.ers"),
    ("jit(run)/while/body/denoiser/era.ers/add", "era.ers"),
    ("jit(run)/while/body/add", None),
    ("jit(run)/denoiser_like/add", None),
    (None, None),
])
def test_scope_of_takes_the_innermost(op_name, scope):
    assert pt.scope_of(op_name) == scope


def test_annotate_finds_each_op_in_its_program():
    names = {
        "jit_run(1)": {"fusion.1": "jit(run)/denoiser/mul", "fusion.2": "jit(run)/era.ers/add"},
        "jit__normal(2)": {"fusion.1": "jit(_normal)/mul"},
    }
    modules = {DEV: [(0, 100, "jit__normal(2)"), (200, 900, "jit_run(1)")]}
    events = [
        ev(DEV, OPS, "%fusion.1 = f32[2] fusion()", 10, 5),
        ev(DEV, OPS, "%fusion.1 = f32[2] fusion()", 300, 5),
        # outside every module event: found by its unique name
        ev(DEV, OPS, "%fusion.2 = f32[2] fusion()", 950, 5),
        # ambiguous and outside: no scope
        ev(DEV, OPS, "%fusion.1 = f32[2] fusion()", 950, 5),
        ev(HOST, "python", "sampler.chunk", 0, 1000),
    ]
    pt.annotate(events, names, modules)
    assert [e.get("scope") for e in events] == [None, "denoiser", "era.ers", None, None]
    assert "scope" not in events[-1]


# ---- the protobuf walk ------------------------------------------------------


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields):
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def _xspace(programs):
    """An XSpace with a /host:metadata plane holding one HloProto per
    program, and a fixed64 field the walk must skip."""
    hlo_stat = 7
    plane = [(1, 3), (2, pt.METADATA_PLANE),
             (5, _msg((1, hlo_stat), (2, _msg((1, hlo_stat), (2, pt.HLO_PROTO_STAT)))))]
    for pid, (name, instrs) in enumerate(programs.items()):
        comp = _msg((1, "body"), *[
            (2, _msg((1, i), (2, "fusion"), (7, _msg((1, "mul"), (2, op)))))
            for i, op in instrs.items()
        ])
        proto = _msg((1, _msg((1, "jit_run"), (3, comp))))
        meta = _msg((1, pid), (2, name), (5, _msg((1, hlo_stat), (6, proto))))
        plane.append((4, _msg((1, pid), (2, meta))))
    fixed = _varint(9 << 3 | 1) + b"\0" * 8
    return _msg((1, _msg((2, "/host:CPU"))), (1, _msg(*plane) + fixed))


def test_hlo_op_names_walks_the_xspace(tmp_path):
    programs = {"jit_run(7)": {"fusion.1": "jit(run)/denoiser/mul",
                               "fusion.2": "jit(run)/while/era.update/add"}}
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_xspace(programs))
    assert pt.hlo_op_names(str(tmp_path)) == programs


def test_hlo_op_names_on_a_real_trace(tmp_path):
    """The walk reads the HloProto JAX itself writes (CPU trace)."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("denoiser"):
            y = jnp.sin(x) @ x
        with jax.named_scope("era.update"):
            return y * 2.0 + 1.0

    g = jax.jit(f)
    x = jnp.ones((16, 16))
    g(x).block_until_ready()
    with tracing.capture(str(tmp_path)):
        g(x).block_until_ready()
    names = pt.hlo_op_names(str(tmp_path))
    (program,) = [v for k, v in names.items() if k.startswith("jit_f(")]
    scopes = {pt.scope_of(op) for op in program.values()}
    assert {"denoiser", "era.update"} <= scopes


# ---- a slice recorded on the chip -------------------------------------------

SCOPED = sorted(p for p in glob.glob(os.path.join(ROOT, "bench", "testdata", "*.json"))
                if "scoped" in os.path.basename(p))


@pytest.mark.parametrize("path", SCOPED, ids=os.path.basename)
def test_recorded_scoped_slice(path):
    """A v5e slice around a chunk boundary: the spans and every scope are
    found, and each ``sampler.execute`` span holds its chunk's denoiser
    ops, so the host spans and the device ops share one clock."""
    with open(path) as f:
        events = json.load(f)
    s = pt.summary(events)
    assert {"sampler.chunk", "sampler.assemble", "sampler.execute"} <= set(s["spans"])
    assert set(s["scopes"]) == set(pt.SCOPES)
    executes = [e for e in events if e["name"] == "sampler.execute"]
    denoiser = [e for e in events if e.get("scope") == "denoiser"]
    assert executes and denoiser
    for op in denoiser:
        assert any(x["t"] <= op["t"] and op["t"] + op["d"] <= x["t"] + x["d"]
                   for x in executes), op["name"][:60]
    # the assembly between two chunks: the device is idle under it
    assemble = s["spans"]["sampler.assemble"]
    assert assemble["idle_s"] > 0.5 * assemble["s"]


def test_unscoped_recording_reduces_to_nothing():
    """The slice recorded before the program had spans and scopes."""
    with open(os.path.join(ROOT, "bench", "testdata", "qwen2-1.5b.offline.v5e.json")) as f:
        events = json.load(f)
    assert pt.summary(events) == {"spans": {}, "scopes": {}}
