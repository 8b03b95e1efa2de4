"""Front-door wall: the HTTP serving surface and the queue policies under
it.

* wire schema — versioned round-trip of the SampleRequest/SampleResult
  dataclass pair, unknown-field and version rejection, bit-exact array
  codec;
* admission control — burst past ``max_queue_rows`` yields 429 +
  ``Retry-After`` while already-admitted requests still complete;
* deadlines — a queued request past ``deadline_ms`` fails fast with
  DeadlineExceededError (504 over the wire), without poisoning the queue;
* priority — higher-priority requests board a launch first under a fake
  clock (``drain_once()``, no threads, no sleeps);
* loopback end-to-end — a wire request's x0 is bit-identical to the same
  seed flushed in-process, through the same ``build_engine`` factory path;
* observability — /metrics exposes the serving instruments, /healthz
  reports scheduler stats, errors map to typed JSON;
* the CLI server — ``repro.launch.serve --listen`` boots in a subprocess,
  prints its ready line and serves a wire request.

All engine tests use the analytic OracleDenoiser: exact, fast, no params.
"""

import json
import os
import queue
import subprocess
import sys
import threading
import time
from http.client import HTTPConnection

import jax
import numpy as np
import pytest

from conftest import AnalyticGaussian, OracleDenoiser, host_spans, run_flush
from repro.core import linear_schedule
from repro.serving import (
    AsyncBatchedSampler,
    DeadlineExceededError,
    EngineConfig,
    FrontDoor,
    FrontDoorClient,
    QueueFullError,
    SampleRequest,
    SchedulerPolicy,
    SchemaError,
    build_engine,
    decode_request,
    decode_result,
    encode_request,
    encode_result,
    serve_frontdoor,
    result_keys as K,
)
from repro.serving.frontdoor import SCHEMA_VERSION, decode_array, encode_array

ANALYTIC = AnalyticGaussian()
D_MODEL = OracleDenoiser.D_MODEL
CFG = EngineConfig(nfe=6, k=3, batch_buckets=(1, 2, 4))


def make_engine(**overrides):
    cfg = EngineConfig(
        **{**{f: getattr(CFG, f) for f in CFG.__dataclass_fields__},
           **overrides}
    )
    return build_engine(OracleDenoiser(ANALYTIC), linear_schedule(), cfg)


def req(seed=0, batch=1, seq_len=6, nfe=6, **kw):
    return SampleRequest(batch=batch, seq_len=seq_len, nfe=nfe, seed=seed, **kw)


# ---------------------------------------------------------------------------
# wire schema (pure: no server, no engine)
# ---------------------------------------------------------------------------


def test_array_codec_bit_exact():
    for arr in (
        np.random.default_rng(0).standard_normal((3, 4, 5)).astype(np.float32),
        np.arange(7, dtype=np.int32),
        np.array([np.nan, np.inf, -0.0, 1e-45], dtype=np.float32),
        np.random.default_rng(1).standard_normal((2, 2)),  # float64
    ):
        back = decode_array(encode_array(arr))
        assert back.dtype == arr.dtype and back.shape == arr.shape
        np.testing.assert_array_equal(
            back.view(np.uint8), arr.view(np.uint8)
        )  # bit-exact, NaNs included


def test_request_round_trip_exact_fields():
    r = req(seed=9, batch=3, solver="ddim", priority=2, deadline_ms=125.0)
    wire = json.loads(json.dumps(encode_request(r)))
    assert wire["v"] == SCHEMA_VERSION
    assert decode_request(wire) == r


def test_request_unknown_field_rejected():
    wire = encode_request(req())
    wire["prioritty"] = 7  # misspelled: must NOT silently sample at default
    with pytest.raises(SchemaError, match="prioritty"):
        decode_request(wire)


def test_request_version_rejected():
    wire = encode_request(req())
    for v in (None, 0, SCHEMA_VERSION + 1, "1"):
        bad = {**wire, "v": v}
        with pytest.raises(SchemaError, match="schema version"):
            decode_request(bad)
    with pytest.raises(SchemaError):
        decode_request([wire])  # non-object payload


def test_request_field_types_validated():
    wire = encode_request(req())
    for field, bad in (
        ("batch", "2"), ("seed", 1.5), ("priority", True),
        ("deadline_ms", "soon"), ("solver", 3),
    ):
        with pytest.raises(SchemaError, match=field):
            decode_request({**wire, field: bad})
    with pytest.raises(SchemaError):  # missing required field
        decode_request({k: v for k, v in wire.items() if k != "batch"})


def test_result_round_trip_bit_exact():
    (res,) = run_flush(make_engine(), [req(seed=3, batch=2)])
    back = decode_result(json.loads(json.dumps(encode_result(res))))
    np.testing.assert_array_equal(np.asarray(res.x0), back.x0)
    assert set(back.aux) == set(res.aux)
    for k in res.aux:
        np.testing.assert_array_equal(np.asarray(res.aux[k]), back.aux[k])
    assert back.latency_s == res.latency_s
    assert back.padded_batch == res.padded_batch
    wire = encode_result(res)
    with pytest.raises(SchemaError, match="unknown result"):
        decode_result({**wire, "extra": 1})
    with pytest.raises(SchemaError, match="missing result"):
        decode_result({k: v for k, v in wire.items() if k != "x0"})


# ---------------------------------------------------------------------------
# queue policy: priority + deadlines under a fake clock (no threads)
# ---------------------------------------------------------------------------


def make_manual_sched(policy=None, **engine_overrides):
    """Unstarted scheduler + fake clock: submit stamps arrival at clk[0],
    drain_once(now=...) is the only pump."""
    clk = [0.0]
    sched = AsyncBatchedSampler(
        make_engine(**engine_overrides),
        params=None,
        policy=policy or SchedulerPolicy(max_wait_ms=10.0),
        clock=lambda: clk[0],
    )
    return sched, clk


def test_priority_boards_first():
    """Three 1-row requests, bucket ladder max 2: the priority-5 request
    boards the first (full) launch even though it arrived last; the
    middle arrival overflows to a second launch."""
    sched, clk = make_manual_sched(batch_buckets=(1, 2))
    futs = [
        sched.submit(req(seed=0, priority=0)),
        sched.submit(req(seed=1, priority=0)),
        sched.submit(req(seed=2, priority=5)),
    ]
    clk[0] = 1.0  # past max_wait_ms -> queue is ready
    # one max-bucket chunk per queue per pass; the overflow row launches
    # on the next pass
    assert sched.drain_once(now=clk[0]) == 1
    assert sched.drain_once(now=clk[0]) == 1
    sizes = [f.result(timeout=5).padded_batch for f in futs]
    # boarding order (-priority, arrival): [2, 0] fuse, [1] overflows
    assert sizes == [2, 1, 2]


def test_priority_orders_ready_queues():
    """Two ready fuse-group queues: the one holding the most urgent
    request launches first (observable through batch completion order via
    the shared executor's serialized run)."""
    sched, clk = make_manual_sched(batch_buckets=(1, 2))
    order = []
    lo = sched.submit(req(seed=0, nfe=6, priority=0))
    hi = sched.submit(req(seed=1, nfe=7, priority=3))  # different fuse group
    lo.add_done_callback(lambda f: order.append("lo"))
    hi.add_done_callback(lambda f: order.append("hi"))
    clk[0] = 1.0
    sched.drain_once(now=clk[0])
    assert order == ["hi", "lo"]


def test_deadline_expired_fails_fast():
    sched, clk = make_manual_sched()
    doomed = sched.submit(req(seed=0, deadline_ms=50.0))
    healthy = sched.submit(req(seed=1))
    clk[0] = 0.2  # 200ms > 50ms deadline
    sched.drain_once(now=clk[0])
    with pytest.raises(DeadlineExceededError, match="expired in queue"):
        doomed.result(timeout=5)
    assert healthy.result(timeout=5).x0.shape == (1, 6, D_MODEL)
    m = sched.executor.metrics.get("sampler_deadline_expired_total")
    assert m.value() == 1.0


def test_deadline_not_expired_is_untouched():
    sched, clk = make_manual_sched()
    fut = sched.submit(req(seed=0, deadline_ms=500.0))
    clk[0] = 0.1  # inside the deadline
    sched.drain_once(now=clk[0])
    assert fut.result(timeout=5).x0.shape == (1, 6, D_MODEL)


def test_deadline_validated_at_submit():
    sched = AsyncBatchedSampler(make_engine(), params=None)
    for bad in (0.0, -5.0, float("inf"), float("nan"), "soon"):
        with pytest.raises(ValueError, match="deadline_ms"):
            sched.submit(req(deadline_ms=bad))
    for bad in (1.5, "high", True):
        with pytest.raises(ValueError, match="priority"):
            sched.submit(req(priority=bad))
    assert sched.pending == 0


def test_seed_validated_at_submit():
    """A seed PRNGKey cannot fold (outside int64 — JSON ints are
    unbounded) must be rejected at submit, not explode at drain time
    inside a fused batch."""
    sched = AsyncBatchedSampler(make_engine(), params=None)
    for bad in (2**63, -(2**63) - 1, 2**200):
        with pytest.raises(ValueError, match="seed"):
            sched.submit(req(seed=bad))
    for bad in (1.5, "7", True):
        with pytest.raises(ValueError, match="seed"):
            sched.submit(req(seed=bad))
    # the extremes of the accepted range sample fine
    for ok in (2**63 - 1, -(2**63)):
        fut = sched.submit(req(seed=ok))
        sched.flush()
        assert fut.result().x0.shape == (1, 6, D_MODEL)


def test_resource_caps_validated_at_submit():
    """Server-side maxima on wire-exposed resource fields: an admitted
    request must never be able to force a multi-GB allocation or an
    unbounded jit cache at drain."""
    sched = AsyncBatchedSampler(
        make_engine(max_batch=4, max_nfe=8, max_seq_len=16), params=None
    )
    with pytest.raises(ValueError, match="max_batch"):
        sched.submit(req(batch=5))
    with pytest.raises(ValueError, match="max_nfe"):
        sched.submit(req(nfe=9))
    with pytest.raises(ValueError, match="max_seq_len"):
        sched.submit(req(seq_len=17))
    # at the caps everything still runs
    fut = sched.submit(req(batch=4, nfe=8, seq_len=16))
    sched.flush()
    assert fut.result().x0.shape == (4, 16, D_MODEL)
    # a seq-bucket ladder takes over bounding the sequence axis: the
    # ladder top (not max_seq_len) is the contract
    bucketed = AsyncBatchedSampler(
        make_engine(max_seq_len=16, seq_buckets=(8,), batch_buckets=(1, 2)),
        params=None,
    )
    with pytest.raises(ValueError, match="seq bucket"):
        bucketed.submit(req(seq_len=9))
    # caps are opt-out for trusted in-process callers
    unbounded = make_engine(max_batch=None, max_nfe=None, max_seq_len=None)
    (res,) = run_flush(unbounded, [req(batch=5, nfe=9, seq_len=17)])
    assert res.x0.shape == (5, 17, D_MODEL)


def test_admission_bound_rejects_then_recovers():
    """Burst past max_queue_rows: the overflow submit raises QueueFullError
    (with a retry hint) while admitted requests complete; afterwards the
    drained queue admits again."""
    sched, clk = make_manual_sched(
        policy=SchedulerPolicy(max_wait_ms=10.0, max_queue_rows=2)
    )
    admitted = [sched.submit(req(seed=s)) for s in range(2)]
    with pytest.raises(QueueFullError) as ei:
        sched.submit(req(seed=9))
    assert ei.value.rows == 2 and ei.value.limit == 2
    assert ei.value.retry_after_s >= 1.0
    clk[0] = 1.0
    sched.drain_once(now=clk[0])
    for f in admitted:
        assert f.result(timeout=5).x0.shape == (1, 6, D_MODEL)
    fut = sched.submit(req(seed=10))  # drained queue admits again
    clk[0] = 2.0
    sched.drain_once(now=clk[0])
    assert fut.result(timeout=5).x0.shape == (1, 6, D_MODEL)
    m = sched.executor.metrics.get("sampler_admission_rejects_total")
    assert m.value(solver="era", seq=6, nfe=6) == 1.0


# ---------------------------------------------------------------------------
# HTTP server: loopback end-to-end
# ---------------------------------------------------------------------------


@pytest.fixture()
def door():
    d = serve_frontdoor(
        make_engine(), params=None, policy=SchedulerPolicy(max_wait_ms=5.0)
    )
    yield d
    d.stop()


def test_wire_matches_in_process_bit_identical(door):
    """The acceptance check: a loopback wire request returns x0 bit-
    identical to the same request flushed in-process, both engines built
    by the same factory config."""
    r = req(seed=7, batch=2)
    wire = FrontDoorClient(door.url, timeout=60).sample(r)
    (local,) = run_flush(make_engine(), [r])
    np.testing.assert_array_equal(np.asarray(local.x0), wire.x0)
    assert wire.x0.dtype == np.asarray(local.x0).dtype
    for k in local.aux:
        np.testing.assert_array_equal(
            np.asarray(local.aux[k]), wire.aux[k]
        )
    assert wire.info[K.PADDED_BATCH] == 2


def test_wire_concurrent_requests_fuse_and_stay_isolated(door):
    """Concurrent wire requests fuse in the server's scheduler, and each
    still gets its own seed's solo-identical rows."""
    client = FrontDoorClient(door.url, timeout=60)
    out = {}

    def call(seed):
        out[seed] = client.sample(req(seed=seed))

    threads = [threading.Thread(target=call, args=(s,)) for s in (11, 12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for seed in (11, 12):
        (solo,) = run_flush(make_engine(), [req(seed=seed)])
        np.testing.assert_array_equal(np.asarray(solo.x0), out[seed].x0)


def test_wire_deadline_maps_to_504():
    """A wire request whose deadline expires in queue gets the typed 504.
    Unstarted scheduler: the handler blocks while we expire the queue by
    hand — deterministic, no racing the drain thread."""
    clk = [0.0]
    sched = AsyncBatchedSampler(
        make_engine(), params=None,
        policy=SchedulerPolicy(max_wait_ms=10.0), clock=lambda: clk[0],
    )
    with FrontDoor(sched) as d:
        client = FrontDoorClient(d.url, timeout=60)
        err = {}

        def call():
            try:
                client.sample(req(seed=0, deadline_ms=20.0))
            except Exception as e:  # noqa: BLE001 - asserting on it below
                err["e"] = e

        th = threading.Thread(target=call)
        th.start()
        deadline = time.time() + 10
        while sched.pending == 0 and time.time() < deadline:
            time.sleep(0.005)
        clk[0] = 1.0  # way past 20ms
        sched.drain_once(now=clk[0])
        th.join(timeout=10)
    assert isinstance(err.get("e"), DeadlineExceededError)
    # the reconstructed exception carries the server's message (with the
    # actual waited time), not a client-side "waited nanms" placeholder
    assert "expired in queue" in str(err["e"])
    assert "nan" not in str(err["e"])


def test_wire_burst_429_while_inflight_completes():
    """Burst beyond the policy's queue depth over HTTP: overflow requests
    get 429 + Retry-After while the admitted in-flight requests complete
    with 200.  Unstarted scheduler makes the full/drained states exact."""
    clk = [0.0]
    sched = AsyncBatchedSampler(
        make_engine(), params=None,
        policy=SchedulerPolicy(max_wait_ms=10.0, max_queue_rows=2),
        clock=lambda: clk[0],
    )
    with FrontDoor(sched) as d:
        client = FrontDoorClient(d.url, timeout=60)
        results, errors = {}, {}

        def call(seed):
            try:
                results[seed] = client.sample(req(seed=seed))
            except Exception as e:  # noqa: BLE001 - asserting on it below
                errors[seed] = e

        inflight = [
            threading.Thread(target=call, args=(s,)) for s in (0, 1)
        ]
        for t in inflight:
            t.start()
        deadline = time.time() + 10
        while sched.pending < 2 and time.time() < deadline:
            time.sleep(0.005)
        assert sched.pending == 2

        # raw HTTP for the overflow: assert status + Retry-After header
        conn = HTTPConnection(d.host, d.port, timeout=30)
        conn.request(
            "POST", "/v1/sample",
            json.dumps(encode_request(req(seed=9))).encode(),
        )
        resp = conn.getresponse()
        assert resp.status == 429
        assert int(resp.getheader("Retry-After")) >= 1
        body = json.loads(resp.read())
        assert body["error"]["type"] == "queue_full"
        conn.close()

        # and via the client: the typed exception, carrying the *server's*
        # message (queue key + row counts), not placeholder attributes
        with pytest.raises(QueueFullError) as ei:
            client.sample(req(seed=10))
        assert "is full" in str(ei.value)
        assert "None" not in str(ei.value) and "-1" not in str(ei.value)
        assert ei.value.retry_after_s >= 1.0

        clk[0] = 1.0
        sched.drain_once(now=clk[0])  # in-flight completes
        for t in inflight:
            t.join(timeout=30)
    assert not errors
    assert sorted(results) == [0, 1]
    for seed, res in results.items():
        (solo,) = run_flush(make_engine(), [req(seed=seed)])
        np.testing.assert_array_equal(np.asarray(solo.x0), res.x0)


def test_http_error_mapping(door):
    conn = HTTPConnection(door.host, door.port, timeout=30)
    # bad JSON -> 400
    conn.request("POST", "/v1/sample", b"{not json")
    r = conn.getresponse()
    assert r.status == 400
    assert json.loads(r.read())["error"]["type"] == "invalid_request"
    # unknown field -> 400
    conn.request(
        "POST", "/v1/sample",
        json.dumps({**encode_request(req()), "bogus": 1}).encode(),
    )
    r = conn.getresponse()
    assert r.status == 400 and r.read()
    # semantic validation (unknown solver) -> 400, at submit, server-side
    conn.request(
        "POST", "/v1/sample",
        json.dumps({**encode_request(req()), "solver": "nope"}).encode(),
    )
    r = conn.getresponse()
    assert r.status == 400 and r.read()
    # unknown route -> 404
    conn.request("GET", "/nope")
    r = conn.getresponse()
    assert r.status == 404
    assert json.loads(r.read())["error"]["type"] == "not_found"
    conn.close()


def test_wire_poison_request_400_not_500(door):
    """A request that used to explode at drain time (seed past int64 —
    JSON ints are unbounded — or an allocation-bomb batch/nfe) now gets a
    400 at admission, and a co-batched innocent request still completes:
    the 'invalid requests raise at submit' invariant holds on the wire."""
    client = FrontDoorClient(door.url, timeout=60)
    out = {}

    def good():
        out["res"] = client.sample(req(seed=21))

    th = threading.Thread(target=good)
    th.start()
    conn = HTTPConnection(door.host, door.port, timeout=30)
    for field, value in (
        ("seed", 2**63), ("seed", -(2**63) - 1),
        ("batch", 10**8), ("nfe", 10**7), ("seq_len", 10**6),
    ):
        conn.request(
            "POST", "/v1/sample",
            json.dumps({**encode_request(req()), field: value}).encode(),
        )
        r = conn.getresponse()
        body = json.loads(r.read())
        assert r.status == 400, (field, value)
        assert body["error"]["type"] == "invalid_request"
    conn.close()
    th.join(timeout=60)
    (solo,) = run_flush(make_engine(), [req(seed=21)])
    np.testing.assert_array_equal(np.asarray(solo.x0), out["res"].x0)


def test_idle_keepalive_connection_reclaimed():
    """An idle persistent connection (or one trickling a body) must not
    pin a handler thread forever: past idle_timeout_s the server closes
    the socket.  In-flight samples are unaffected — they block on the
    scheduler Future, not the socket."""
    import socket

    sched = AsyncBatchedSampler(
        make_engine(), params=None, policy=SchedulerPolicy(max_wait_ms=5.0)
    )
    sched.start()
    try:
        with FrontDoor(sched, idle_timeout_s=0.3) as d:
            # a request on a keep-alive connection still works...
            conn = HTTPConnection(d.host, d.port, timeout=30)
            conn.request(
                "POST", "/v1/sample",
                json.dumps(encode_request(req(seed=31))).encode(),
            )
            r = conn.getresponse()
            assert r.status == 200
            r.read()
            # ...then the idle connection is closed by the server
            sock = conn.sock
            sock.settimeout(10)
            assert sock.recv(1) == b""  # EOF, not a hang
            conn.close()
            # raw socket that never sends a request line: same reclaim
            s = socket.create_connection((d.host, d.port), timeout=10)
            assert s.recv(1) == b""
            s.close()
    finally:
        sched.stop()


class _FakeHandler:
    """Just enough of BaseHTTPRequestHandler for FrontDoor._handle: records
    status codes sent, optionally blows up mid-body-write."""

    def __init__(self, path, fail_body_write=False):
        self.path = path
        self.headers = {}
        self.close_connection = False
        self.codes = []
        self._fail = fail_body_write
        outer = self

        class _W:
            def write(self, data):
                if outer._fail:
                    raise ConnectionResetError("peer reset mid-body")

        self.wfile = _W()

    def send_response(self, code):
        self.codes.append(code)

    def send_header(self, *a):
        pass

    def end_headers(self):
        pass


def test_partial_response_failure_does_not_append_500():
    """A socket failure after the 200 status line has been sent must not
    append a second status line (stream corruption on a keep-alive
    connection): the server just drops the connection.  A failure *before*
    any response still gets the 500 body."""
    sched = AsyncBatchedSampler(
        make_engine(), params=None, policy=SchedulerPolicy(max_wait_ms=5.0)
    )
    door = FrontDoor(sched)
    try:
        # mid-write failure: exactly one status line, connection dropped
        h = _FakeHandler("/healthz", fail_body_write=True)
        door._handle(h, "GET")
        assert h.codes == [200]
        assert h.close_connection is True
        # pre-response failure: the 500 reply is still sent
        door.scheduler.stats = lambda: (_ for _ in ()).throw(
            RuntimeError("boom")
        )
        h2 = _FakeHandler("/healthz")
        door._handle(h2, "GET")
        assert h2.codes == [500]
    finally:
        door._server.server_close()
        sched.stop()


def test_metrics_and_healthz(door):
    client = FrontDoorClient(door.url, timeout=60)
    client.sample(req(seed=1))
    health = client.healthz()
    assert health["ok"] is True
    assert health["stats"][K.SUBMITTED] >= 1
    text = client.metrics()
    for name in (
        "sampler_queue_depth_rows",
        "sampler_fuse_occupancy_ratio",
        "sampler_compile_programs_total",
        "sampler_compile_seconds",
        "sampler_warmup_grid_programs",
        "sampler_warmup_compiled_programs",
        "sampler_warmup_in_progress",
        "sampler_warmup_duration_seconds",
        "sampler_warmup_programs_total",
        "sampler_admission_rejects_total",
        "sampler_requests_submitted_total",
        "sampler_request_latency_seconds_bucket",
        "sampler_queue_wait_seconds_bucket",
        "sampler_assembly_seconds_bucket",
        "frontdoor_http_requests_total",
    ):
        assert name in text, name
    # exposition format: HELP/TYPE headers and histogram plumbing
    assert "# TYPE sampler_request_latency_seconds histogram" in text
    assert 'le="+Inf"' in text
    assert text.endswith("\n")


def test_serve_listen_subprocess_boots_and_serves():
    """``python -m repro.launch.serve --listen --port 0`` in a child
    process: it prints ``FRONTDOOR READY <url>``, warms its grid behind
    /readyz, serves a wire request and exposes the serving instruments
    on /metrics."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "repro.launch.serve",
            "--arch", "qwen2-1.5b", "--smoke", "--mode", "diffusion",
            "--listen", "--port", "0", "--nfe", "6", "--k", "3",
            "--seq", "8", "--batch-buckets", "1",
        ],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=root, env=env,
    )
    lines: queue.Queue = queue.Queue()
    threading.Thread(
        target=lambda: [lines.put(ln) for ln in proc.stdout] + [lines.put(None)],
        daemon=True,
    ).start()
    try:
        seen = []
        while True:
            line = lines.get(timeout=300)
            assert line is not None, f"server exited before ready: {seen}"
            seen.append(line)
            if line.startswith("FRONTDOOR READY "):
                url = line.split()[-1]
                break
        client = FrontDoorClient(url, timeout=300)
        t_deadline = time.monotonic() + 300
        while not client.readyz()["ready"]:
            assert time.monotonic() < t_deadline, client.readyz()
            time.sleep(0.1)
        res = client.sample(req(seed=5, seq_len=8))
        assert res.x0.shape[:2] == (1, 8)
        assert np.isfinite(res.x0).all()
        text = client.metrics()
        for name in (
            "sampler_queue_depth_rows",
            "sampler_compile_programs_total",
            "sampler_warmup_programs_total",
            "sampler_request_latency_seconds_bucket",
            "frontdoor_http_requests_total",
        ):
            assert name in text, name
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def test_codec_spans(door, tmp_path):
    """Request decode and result encode are profiler spans on the
    handler thread, apart from the wait for the result."""
    client = FrontDoorClient(door.url, timeout=60)
    client.sample(req(seed=2))   # compiled before the trace
    with jax.profiler.trace(str(tmp_path)):
        client.sample(req(seed=3))
    spans = host_spans(str(tmp_path), prefix="frontdoor.")
    assert [s["name"] for s in spans] == ["frontdoor.decode", "frontdoor.encode"]
    decode, encode = spans
    assert decode["line"] == encode["line"] and decode["end"] <= encode["t"]


def test_client_rejects_non_http_url():
    with pytest.raises(ValueError, match="base_url"):
        FrontDoorClient("ftp://example:1")
