"""HTTP front door for the sampling engine — the network wire path.

Turns the in-process serving stack into an actual service: a
:class:`FrontDoor` wraps an
:class:`~repro.serving.scheduler.AsyncBatchedSampler` with a stdlib
``ThreadingHTTPServer`` (no new dependencies) speaking a **versioned JSON
schema** that round-trips exactly the
:class:`~repro.serving.executor.SampleRequest` /
:class:`~repro.serving.executor.SampleResult` dataclass pair — no
parallel wire types.  Endpoints:

* ``POST /v1/sample`` — submit one :class:`SampleRequest`; blocks the
  connection's handler thread until the result is drained, then returns
  the encoded :class:`SampleResult`.  Arrays travel as base64-encoded raw
  buffers (dtype + shape + bytes), so a wire result is **bit-identical**
  to the in-process one.  Admission control maps
  :class:`~repro.serving.scheduler.QueueFullError` to **429** with a
  ``Retry-After`` header; an expired ``deadline_ms`` maps to **504** with
  a typed ``deadline_exceeded`` error; validation failures map to **400**.
* ``GET /metrics`` — the engine's Prometheus text exposition
  (:mod:`repro.serving.metrics`): queue depth per fuse group, fuse
  occupancy, compile source counts (memory/disk/fresh) and warmup
  progress, admission rejects, deadline expirations, arrival-to-result
  latency histogram, HTTP request counts.
* ``GET /healthz`` — pure **liveness** + scheduler stats as JSON: 200 as
  soon as the listener is up, even while programs are still compiling.
  Wire an LB's health check here only to detect dead processes.
* ``GET /readyz`` — **readiness**: 503 with warmup progress JSON until
  the AOT warmup grid is compiled, 200 after (immediately, when the front
  door was built without a warmup).  Point traffic routing here, so a
  replica only receives requests once they won't eat a multi-second
  compile.

:class:`FrontDoorClient` is the matching stdlib client (used by
``launch/serve.py --connect`` and ``chip_smoke.py``); it maps
the typed wire errors back to the same exception classes the in-process
scheduler raises, so retry logic is transport-agnostic.

Error responses are JSON: ``{"v": 1, "error": {"type": ..., "message":
...}}`` with ``type`` one of ``invalid_request`` / ``queue_full`` /
``deadline_exceeded`` / ``not_found`` / ``internal``.
"""

from __future__ import annotations

import base64
import dataclasses
import json
import math
import threading
from http.client import HTTPConnection
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import urlsplit

import jax
import numpy as np

from repro.serving.executor import SampleRequest, SampleResult
from repro.serving.scheduler import (
    AsyncBatchedSampler,
    DeadlineExceededError,
    QueueFullError,
)

#: wire schema version; bump on any incompatible request/response change
SCHEMA_VERSION = 1

METRICS_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

_REQUEST_FIELDS = {f.name: f for f in dataclasses.fields(SampleRequest)}
_RESULT_FIELDS = {f.name: f for f in dataclasses.fields(SampleResult)}
_INT_FIELDS = ("batch", "seq_len", "nfe", "seed", "priority")


class SchemaError(ValueError):
    """The payload does not conform to the versioned wire schema."""


# ---------------------------------------------------------------------------
# wire schema: SampleRequest / SampleResult <-> JSON
# ---------------------------------------------------------------------------


def encode_array(x) -> dict:
    """Array -> JSON-safe dict.  Raw little-endian bytes in base64 (not
    decimal strings), so decode is bit-exact for every dtype."""
    a = np.ascontiguousarray(np.asarray(x))
    return {
        "__nd__": True,
        "dtype": a.dtype.str,  # byte-order explicit, e.g. "<f4"
        "shape": list(a.shape),
        "data": base64.b64encode(a.tobytes()).decode("ascii"),
    }


def decode_array(d: dict) -> np.ndarray:
    if not (isinstance(d, dict) and d.get("__nd__")):
        raise SchemaError(f"expected an encoded array, got {type(d).__name__}")
    buf = base64.b64decode(d["data"])
    return np.frombuffer(buf, dtype=np.dtype(d["dtype"])).reshape(d["shape"])


def _check_version(payload) -> dict:
    if not isinstance(payload, dict):
        raise SchemaError(
            f"payload must be a JSON object, got {type(payload).__name__}"
        )
    v = payload.get("v")
    if v != SCHEMA_VERSION:
        raise SchemaError(
            f"unsupported schema version {v!r}; this endpoint speaks "
            f"v={SCHEMA_VERSION}"
        )
    return {k: payload[k] for k in payload if k != "v"}


def encode_request(req: SampleRequest) -> dict:
    """``SampleRequest`` -> versioned JSON body (exactly its fields)."""
    return {"v": SCHEMA_VERSION, **dataclasses.asdict(req)}


def decode_request(payload) -> SampleRequest:
    """Versioned JSON body -> ``SampleRequest``.

    Rejects (``SchemaError``): wrong/missing ``v``, unknown fields (a
    misspelled ``prioritty`` must not silently sample at default
    priority), and non-numeric/non-string field types.  Range validation
    (batch >= 1, known solver, deadline > 0, ...) stays where it lives for
    in-process callers: ``FusedExecutor.validate`` at submit.
    """
    body = _check_version(payload)
    unknown = set(body) - set(_REQUEST_FIELDS)
    if unknown:
        raise SchemaError(
            f"unknown request fields {sorted(unknown)}; the v{SCHEMA_VERSION} "
            f"schema has {sorted(_REQUEST_FIELDS)}"
        )
    for name in _INT_FIELDS:
        if name in body and (
            isinstance(body[name], bool) or not isinstance(body[name], int)
        ):
            raise SchemaError(f"field {name!r} must be an integer")
    if "solver" in body and not (
        body["solver"] is None or isinstance(body["solver"], str)
    ):
        raise SchemaError("field 'solver' must be a string or null")
    if "deadline_ms" in body and not (
        body["deadline_ms"] is None
        or (
            isinstance(body["deadline_ms"], (int, float))
            and not isinstance(body["deadline_ms"], bool)
        )
    ):
        raise SchemaError("field 'deadline_ms' must be a number or null")
    try:
        return SampleRequest(**body)
    except TypeError as e:  # missing required fields
        raise SchemaError(str(e)) from None


def _encode_value(v):
    if hasattr(v, "shape"):
        return encode_array(v)
    if isinstance(v, dict):
        return {k: _encode_value(x) for k, x in v.items()}
    return v


def _decode_value(v):
    if isinstance(v, dict):
        if v.get("__nd__"):
            return decode_array(v)
        return {k: _decode_value(x) for k, x in v.items()}
    return v


def encode_result(res: SampleResult) -> dict:
    """``SampleResult`` -> versioned JSON body.  Field-generic over the
    dataclass (the wire schema IS the dataclass, no parallel type); arrays
    — including inside ``aux`` — go base64, scalars pass through."""
    return {
        "v": SCHEMA_VERSION,
        **{f: _encode_value(getattr(res, f)) for f in _RESULT_FIELDS},
    }


def decode_result(payload) -> SampleResult:
    """Versioned JSON body -> ``SampleResult`` with numpy arrays (bit-
    identical to the server-side result).  Unknown fields are rejected —
    the client must not silently drop data a newer server sent."""
    body = _check_version(payload)
    unknown = set(body) - set(_RESULT_FIELDS)
    if unknown:
        raise SchemaError(
            f"unknown result fields {sorted(unknown)}; the v{SCHEMA_VERSION} "
            f"schema has {sorted(_RESULT_FIELDS)}"
        )
    missing = set(_RESULT_FIELDS) - set(body)
    if missing:
        raise SchemaError(f"missing result fields {sorted(missing)}")
    return SampleResult(**{f: _decode_value(v) for f, v in body.items()})


def encode_error(kind: str, message: str) -> dict:
    return {"v": SCHEMA_VERSION, "error": {"type": kind, "message": message}}


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------


class FrontDoor:
    """HTTP server over an :class:`AsyncBatchedSampler`.

    One handler thread per connection (``ThreadingHTTPServer``); a
    ``POST /v1/sample`` handler blocks on the request's Future while the
    scheduler's drain thread fuses and runs batches — so concurrent wire
    requests batch together exactly like in-process submits.

    ``port=0`` binds an ephemeral port (tests);  :attr:`url` reports the
    bound address.  ``idle_timeout_s`` bounds how long a keep-alive
    connection may sit idle (or trickle a request) before its handler
    thread is reclaimed — it never limits an in-flight sample, which
    blocks on the scheduler Future, not the socket (``None`` = no
    timeout, trusted clients only).  ``start()``/``stop()`` (or use as a context manager)
    run the accept loop on a daemon thread; ``stop()`` also stops the
    scheduler when the front door owns it
    (:func:`serve_frontdoor` sets that up).

    ``warmup`` (a zero-arg callable, typically
    ``lambda: scheduler.warmup(...)``) gates readiness: ``start()`` runs
    it on a background daemon thread — the listener binds and ``/healthz``
    answers immediately — and ``/readyz`` serves 503 with
    ``scheduler.warmup_status()`` progress until it returns, 200 after.
    If it raises, the replica stays NOT ready and ``/readyz`` carries the
    error (a failed warmup on a broken build must not attract traffic).
    ``None`` (default) = ready from the first byte.
    """

    def __init__(
        self,
        scheduler: AsyncBatchedSampler,
        host: str = "127.0.0.1",
        port: int = 0,
        owns_scheduler: bool = False,
        idle_timeout_s: float | None = 30.0,
        warmup=None,
    ):
        self.scheduler = scheduler
        self._owns_scheduler = owns_scheduler
        self._warmup_fn = warmup
        self._warmup_thread: threading.Thread | None = None
        self._warmup_error: str | None = None
        self._ready = threading.Event()
        if warmup is None:
            self._ready.set()
        self._m_http = scheduler.executor.metrics.counter(
            "frontdoor_http_requests_total",
            "HTTP requests served, by route and status code",
        )
        frontdoor = self

        class Handler(BaseHTTPRequestHandler):
            # Socket timeout for *reading* a request (the next request
            # line on a keep-alive connection, or a trickling body).
            # Without one, every idle persistent connection pins a
            # handler thread forever — an unbounded thread/socket leak
            # for any client that doesn't close per request.  The
            # in-flight sample wait is unaffected: the handler blocks on
            # the scheduler Future, not the socket, so a fused batch may
            # take arbitrarily long.  http.server turns a timed-out read
            # into close_connection, ending the handler cleanly.
            timeout = idle_timeout_s
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # noqa: A003 - BaseHTTP API
                pass  # metrics, not stderr spam

            def do_GET(self):  # noqa: N802 - BaseHTTP API
                frontdoor._handle(self, "GET")

            def do_POST(self):  # noqa: N802 - BaseHTTP API
                frontdoor._handle(self, "POST")

        self._server = ThreadingHTTPServer((host, port), Handler)
        self._server.daemon_threads = True
        self._thread: threading.Thread | None = None

    # ---- lifecycle ------------------------------------------------------
    @property
    def host(self) -> str:
        return self._server.server_address[0]

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def start(self) -> "FrontDoor":
        if self._thread is not None:
            raise RuntimeError("front door already started")
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name="era-frontdoor",
            daemon=True,
        )
        self._thread.start()
        if self._warmup_fn is not None and self._warmup_thread is None:
            # warm in the background: the listener is already accepting, so
            # /healthz (liveness) answers during the compile wall and
            # /readyz flips 503 -> 200 when the grid is in
            self._warmup_thread = threading.Thread(
                target=self._run_warmup, name="era-warmup", daemon=True
            )
            self._warmup_thread.start()
        return self

    def _run_warmup(self) -> None:
        try:
            self._warmup_fn()
        except Exception as e:  # noqa: BLE001 - surfaced via /readyz
            self._warmup_error = f"{type(e).__name__}: {e}"
        else:
            self._ready.set()

    @property
    def ready(self) -> bool:
        """Has the boot warmup finished (or was none configured)?"""
        return self._ready.is_set()

    def readiness(self) -> dict:
        """The ``/readyz`` payload: ``ready`` flag + the scheduler's
        warmup progress (+ ``error`` if the warmup raised)."""
        payload = {
            "v": SCHEMA_VERSION,
            "ready": self.ready,
            "warmup": self.scheduler.warmup_status(),
        }
        if self._warmup_error is not None:
            payload["error"] = self._warmup_error
        return payload

    def stop(self) -> None:
        """Stop accepting, join the accept loop, and (when owning it)
        stop the scheduler — which flushes every queued request."""
        if self._thread is not None:
            self._server.shutdown()
            self._thread.join()
            self._thread = None
        self._server.server_close()
        if self._owns_scheduler:
            self.scheduler.stop()

    def __enter__(self) -> "FrontDoor":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ---- request handling ----------------------------------------------
    def _handle(self, handler: BaseHTTPRequestHandler, method: str) -> None:
        route = urlsplit(handler.path).path
        handler._response_started = False  # set by _respond_text
        try:
            if method == "POST" and route == "/v1/sample":
                self._handle_sample(handler, route)
            elif method == "GET" and route == "/metrics":
                self._respond_text(
                    handler, route, 200,
                    self.scheduler.executor.metrics.render(),
                    METRICS_CONTENT_TYPE,
                )
            elif method == "GET" and route == "/healthz":
                # pure liveness: 200 from the first byte, even mid-warmup
                self._respond_json(
                    handler, route, 200,
                    {"v": SCHEMA_VERSION, "ok": True,
                     "stats": self.scheduler.stats()},
                )
            elif method == "GET" and route == "/readyz":
                payload = self.readiness()
                self._respond_json(
                    handler, route, 200 if payload["ready"] else 503, payload
                )
            else:
                self._respond_json(
                    handler, route, 404,
                    encode_error("not_found", f"no route {method} {route}"),
                )
        except BrokenPipeError:
            pass  # client hung up mid-response; nothing to deliver to
        except Exception as e:  # noqa: BLE001 - must answer, not crash
            if handler._response_started:
                # a response (possibly a 200) was partially written:
                # appending a 500 status line here would corrupt the HTTP
                # stream on this connection — just drop the connection so
                # the client sees a truncated response, not a forged one
                handler.close_connection = True
                return
            try:
                self._respond_json(
                    handler, route, 500, encode_error("internal", str(e))
                )
            except Exception:  # noqa: BLE001 - socket already gone
                pass

    def _handle_sample(self, handler, route: str) -> None:
        length = int(handler.headers.get("Content-Length") or 0)
        raw = handler.rfile.read(length) if length else b""
        try:
            with jax.profiler.TraceAnnotation("frontdoor.decode"):
                req = decode_request(json.loads(raw.decode("utf-8")))
        except (UnicodeDecodeError, json.JSONDecodeError) as e:
            self._respond_json(
                handler, route, 400,
                encode_error("invalid_request", f"body is not JSON: {e}"),
            )
            return
        except (SchemaError, ValueError) as e:
            self._respond_json(
                handler, route, 400, encode_error("invalid_request", str(e))
            )
            return
        try:
            fut = self.scheduler.submit(req)
        except ValueError as e:
            self._respond_json(
                handler, route, 400, encode_error("invalid_request", str(e))
            )
            return
        except QueueFullError as e:
            self._respond_json(
                handler, route, 429, encode_error("queue_full", str(e)),
                headers={"Retry-After": str(max(1, math.ceil(e.retry_after_s)))},
            )
            return
        try:
            res = fut.result()
        except DeadlineExceededError as e:
            self._respond_json(
                handler, route, 504, encode_error("deadline_exceeded", str(e))
            )
            return
        except Exception as e:  # noqa: BLE001 - chunk failure -> typed 500
            self._respond_json(
                handler, route, 500, encode_error("internal", str(e))
            )
            return
        with jax.profiler.TraceAnnotation("frontdoor.encode"):
            body = json.dumps(encode_result(res))
        self._respond_text(handler, route, 200, body, "application/json")

    # ---- response plumbing ----------------------------------------------
    def _respond_text(
        self, handler, route, code, text: str, content_type: str,
        headers: dict | None = None,
    ) -> None:
        body = text.encode("utf-8")
        # from here on a failure must not trigger a second status line
        handler._response_started = True
        handler.send_response(code)
        handler.send_header("Content-Type", content_type)
        handler.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            handler.send_header(k, v)
        handler.end_headers()
        handler.wfile.write(body)
        self._m_http.inc(route=route, code=str(code))

    def _respond_json(
        self, handler, route, code, payload: dict,
        headers: dict | None = None,
    ) -> None:
        self._respond_text(
            handler, route, code, json.dumps(payload),
            "application/json", headers,
        )


def serve_frontdoor(
    engine,
    params,
    policy=None,
    host: str = "127.0.0.1",
    port: int = 0,
    warmup=None,
) -> FrontDoor:
    """One-call server bring-up: start a scheduler over ``engine`` and a
    :class:`FrontDoor` that owns it.  ``stop()`` on the returned front
    door tears both down (flushing queued requests).

    ``warmup`` gates ``/readyz`` (see :class:`FrontDoor`): a dict is
    keyword arguments for the scheduler's AOT grid warmup
    (``scheduler.warmup(solvers=..., seq_lens=..., nfes=...)`` — what
    :func:`~repro.serving.factory.warmup_kwargs` produces), a callable is
    run as-is, ``None`` means ready immediately.  Either way the warmup
    runs on a background thread, so this returns as soon as the listener
    is bound."""
    scheduler = AsyncBatchedSampler(engine, params, policy).start()
    warmup_fn = warmup
    if isinstance(warmup, dict):
        kw = dict(warmup)

        def warmup_fn():
            return scheduler.warmup(**kw)

    try:
        return FrontDoor(
            scheduler, host=host, port=port, owns_scheduler=True,
            warmup=warmup_fn,
        ).start()
    except Exception:
        scheduler.stop()
        raise


# ---------------------------------------------------------------------------
# client
# ---------------------------------------------------------------------------


class FrontDoorClient:
    """Stdlib HTTP client for the front door.

    ``sample()`` re-raises the server's typed errors as the same exception
    classes the in-process scheduler uses (:class:`QueueFullError` with
    ``retry_after_s`` from the header, :class:`DeadlineExceededError`,
    ``ValueError`` for 400s), so callers keep one error-handling path for
    loopback and wire.  One connection per call — handlers block for the
    whole sample, so pooling would just pin sockets.
    """

    def __init__(self, base_url: str, timeout: float | None = None):
        parts = urlsplit(base_url)
        if parts.scheme != "http" or not parts.netloc:
            raise ValueError(
                f"base_url must be http://host:port, got {base_url!r}"
            )
        self._netloc = parts.netloc
        self._timeout = timeout

    def _request(self, method: str, path: str, body: bytes | None = None):
        conn = HTTPConnection(self._netloc, timeout=self._timeout)
        try:
            headers = {"Content-Type": "application/json"} if body else {}
            conn.request(method, path, body=body, headers=headers)
            resp = conn.getresponse()
            return resp.status, dict(resp.getheaders()), resp.read()
        finally:
            conn.close()

    @staticmethod
    def _error_payload(raw: bytes) -> dict:
        try:
            payload = json.loads(raw.decode("utf-8"))
            return payload.get("error") or {}
        except (UnicodeDecodeError, json.JSONDecodeError):
            return {}

    def sample(self, req: SampleRequest) -> SampleResult:
        """POST the request; block until the wire result arrives, decoded
        back into a :class:`SampleResult` (numpy ``x0``/``aux``)."""
        body = json.dumps(encode_request(req)).encode("utf-8")
        status, headers, raw = self._request("POST", "/v1/sample", body)
        if status == 200:
            return decode_result(json.loads(raw.decode("utf-8")))
        err = self._error_payload(raw)
        message = err.get("message", f"HTTP {status}")
        # reconstructed exceptions carry the *server's* message: the queue
        # key / row counts / waited time live server-side, so the
        # placeholder attributes here (key=None, waited_ms=nan) must not
        # leak into what retry paths log
        if status == 429:
            retry = float(headers.get("Retry-After", "1"))
            raise QueueFullError(
                key=None, rows=-1, limit=-1, retry_after_s=retry,
                message=message,
            )
        if status == 504:
            raise DeadlineExceededError(
                req, waited_ms=float("nan"), message=message
            )
        if status == 400:
            raise ValueError(message)
        raise RuntimeError(f"front door error {status}: {message}")

    def metrics(self) -> str:
        status, _, raw = self._request("GET", "/metrics")
        if status != 200:
            raise RuntimeError(f"/metrics returned HTTP {status}")
        return raw.decode("utf-8")

    def healthz(self) -> dict:
        """GET /healthz — pure liveness (200 even while warming up)."""
        status, _, raw = self._request("GET", "/healthz")
        if status != 200:
            raise RuntimeError(f"/healthz returned HTTP {status}")
        return json.loads(raw.decode("utf-8"))

    def readyz(self) -> dict:
        """GET /readyz — the readiness payload.  A 503 (still warming, or
        warmup failed) is a *state*, not a transport error, so both 200
        and 503 return the parsed payload — check ``payload["ready"]``;
        any other status raises."""
        status, _, raw = self._request("GET", "/readyz")
        if status not in (200, 503):
            raise RuntimeError(f"/readyz returned HTTP {status}")
        payload = json.loads(raw.decode("utf-8"))
        payload["ready"] = bool(payload.get("ready")) and status == 200
        return payload
