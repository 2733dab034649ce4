"""Minimal routed HTTP server shared by every kubeml-tpu service.

The reference's services are Go mux routers (gorilla/mux) and Flask apps speaking
JSON with the ``{error, code}`` envelope on failure (reference:
ml/pkg/controller/api.go:16-42, ml/environment/server.py:133-151). Flask is not a
dependency here; this is a small stdlib ``ThreadingHTTPServer`` with:

* pattern routes with ``{param}`` captures, per-method handlers
* automatic JSON body/response handling
* ``KubeMLError`` -> envelope serialization, generic exceptions -> 500 envelope
* a ``/health`` route on every service by default
* resilience middleware (utils.resilience): ``x-kubeml-deadline`` enforcement
  (already-expired requests are rejected with 504 before any work, and the
  remaining budget binds to the handler thread so downstream hops inherit
  it), idempotency replay (a retried keyed POST is answered from the recorded
  response, not re-executed), and env-gated chaos injection
  (delay/500/connection-reset per route — the network-level complement of
  engine.failures.FailureInjector's worker masks)
"""

from __future__ import annotations

import json
import logging
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from ..api.errors import KubeMLError

log = logging.getLogger("kubeml.httpd")

Handler = Callable[["Request"], Any]


class _Replayed(Exception):
    """Control-flow marker: the response came from the replay cache."""


class Request:
    """Parsed incoming request handed to route handlers."""

    def __init__(self, method: str, path: str, params: Dict[str, str], query: Dict[str, List[str]], body: bytes, headers):
        self.method = method
        self.path = path
        self.params = params  # {param} captures from the route pattern
        self.query = query
        self.body = body
        self.headers = headers

    def json(self) -> Any:
        if not self.body:
            return None
        try:
            return json.loads(self.body)
        except ValueError as e:
            raise KubeMLError(f"invalid JSON body: {e}", 400)

    def arg(self, name: str, default: Optional[str] = None) -> Optional[str]:
        vals = self.query.get(name)
        return vals[0] if vals else default


class Response:
    """Explicit response when a handler needs a non-200 code, raw bytes, or
    extra headers (e.g. ``Retry-After`` on a 429)."""

    def __init__(self, body: Any = None, status: int = 200,
                 content_type: str = "application/json",
                 headers: Optional[Dict[str, str]] = None):
        self.body = body
        self.status = status
        self.content_type = content_type
        self.headers = dict(headers or {})


class StreamResponse(Response):
    """Chunked-transfer response: ``items`` yields JSON-serializable objects
    (each becomes one newline-terminated JSON line) or raw ``bytes``. Errors
    raised mid-stream can't change the status line (headers are gone), so
    they surface as a final ``{"error": ...}`` line before close — clients
    must check the last line."""

    def __init__(self, items, content_type: str = "application/x-ndjson"):
        super().__init__(body=None, status=200, content_type=content_type)
        self.items = items


class Router:
    def __init__(self, name: str):
        from .resilience import ReplayCache

        self.name = name
        self._routes: List[Tuple[str, re.Pattern, Handler]] = []
        # idempotency replay: keyed POST retries answer from the record
        self.replay = ReplayCache()
        self.route("GET", "/health", lambda req: {"status": "ok", "service": name})

    def route(self, method: str, pattern: str, handler: Handler) -> None:
        regex = re.sub(r"\{(\w+)\}", r"(?P<\1>[^/]+)", pattern)
        self._routes.append((method.upper(), re.compile(f"^{regex}$"), handler))

    def dispatch(self, method: str, path: str, query, body: bytes, headers) -> Response:
        matched_path = False
        for m, rx, handler in self._routes:
            match = rx.match(path)
            if match:
                matched_path = True
                if m == method:
                    req = Request(method, path, match.groupdict(), query, body, headers)
                    result = handler(req)
                    if isinstance(result, Response):
                        return result
                    return Response(result if result is not None else {})
        if matched_path:
            raise KubeMLError(f"method {method} not allowed for {path}", 405)
        raise KubeMLError(f"no route for {path}", 404)


class _Server(ThreadingHTTPServer):
    """The stdlib server with a deeper accept queue: its default of 5 resets
    a connection whenever more clients than that connect between two
    accepts. A closed loop's 80 clients open at once, each with a prompt of
    some 25 KB to upload: 6 of one window's 249 requests ended in
    ``ConnectionResetError`` before the program saw them (PR 44's chip run;
    PR 33 had lost 2 of 480 the same way)."""

    request_queue_size = 128


class Service:
    """One HTTP service: a Router bound to a port, run on a daemon thread."""

    def __init__(self, router: Router, host: str, port: int):
        self.router = router
        self.host = host
        self.port = port
        self._server: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "Service":
        router = self.router

        class _Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # route access logs into logging
                log.debug("%s %s", router.name, fmt % args)

            def _respond(self, resp: Response):
                if isinstance(resp, StreamResponse):
                    return self._respond_stream(resp)
                if isinstance(resp.body, (bytes, bytearray)):
                    payload = bytes(resp.body)
                else:
                    payload = json.dumps(resp.body).encode()
                self.send_response(resp.status)
                self.send_header("Content-Type", resp.content_type)
                self.send_header("Content-Length", str(len(payload)))
                for k, v in resp.headers.items():
                    self.send_header(k, str(v))
                self.end_headers()
                self.wfile.write(payload)

            def _chunk(self, data: bytes):
                self.wfile.write(b"%x\r\n" % len(data) + data + b"\r\n")

            def _respond_stream(self, resp: StreamResponse):
                self.send_response(resp.status)
                self.send_header("Content-Type", resp.content_type)
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()
                try:
                    for item in resp.items:
                        data = (bytes(item) if isinstance(item, (bytes, bytearray))
                                else json.dumps(item).encode() + b"\n")
                        if data:
                            self._chunk(data)
                        self.wfile.flush()
                except BrokenPipeError:
                    return  # client went away mid-stream
                except KubeMLError as e:
                    self._chunk(json.dumps(e.to_dict()).encode() + b"\n")
                except Exception as e:
                    log.exception("%s: error mid-stream", router.name)
                    self._chunk(json.dumps({"error": str(e), "code": 500}).encode() + b"\n")
                self.wfile.write(b"0\r\n\r\n")

            def _inject_chaos(self, path: str) -> Optional[str]:
                """Env-gated chaos middleware (utils.resilience.chaos): maybe
                delay, and return "error"/"reset" when the request must fail
                instead of dispatching. Runs BEFORE dispatch so an injected
                fault never leaves half-applied server state — a retried
                request is always safe."""
                from . import resilience

                fault = resilience.chaos().server_fault(path)
                if fault is None:
                    return None
                mode, delay = fault
                if mode == "delay":
                    time.sleep(delay)
                    return None
                return mode

            def _chaos_reset(self):
                """Abort the connection without a response: the client sees a
                reset/EOF mid-exchange (requests.ConnectionError)."""
                import socket as _socket

                self.close_connection = True
                try:
                    self.connection.shutdown(_socket.SHUT_RDWR)
                except OSError:
                    pass

            def _handle(self, method: str):
                from . import resilience, tracing

                replayed = False
                replay_owner = False
                idem_key = None
                try:
                    parsed = urlparse(self.path)
                    length = int(self.headers.get("Content-Length") or 0)
                    body = self.rfile.read(length) if length else b""
                    internal = parsed.path in ("/health", "/metrics")
                    if not internal:
                        # chaos first: an injected reset must also hit
                        # requests that would have been rejected/replayed
                        chaos_mode = self._inject_chaos(parsed.path)
                        if chaos_mode == "reset":
                            self._chaos_reset()
                            return
                        if chaos_mode == "error":
                            raise KubeMLError("chaos: injected server fault",
                                              500)
                    # deadline enforcement: reject work nobody is waiting for
                    deadline = resilience.parse_deadline(
                        self.headers.get(resilience.DEADLINE_HEADER))
                    if (deadline is not None and not internal
                            and deadline <= time.time()):
                        resilience.incr("kubeml_http_deadline_rejected_total",
                                        router.name)
                        raise KubeMLError(
                            f"deadline expired {parsed.path} "
                            f"({router.name})", 504)
                    # idempotency replay: a retried keyed POST answers from
                    # the recorded response instead of re-executing; a
                    # duplicate racing the still-running original WAITS for
                    # it rather than executing the side effect twice
                    idem_key = self.headers.get(resilience.IDEMPOTENCY_HEADER)
                    if idem_key and method == "POST":
                        state, val = router.replay.acquire(
                            method, parsed.path, idem_key)
                        if state == "wait":
                            # the original is mid-flight: wait it out (up to
                            # the request's own remaining deadline — a slow
                            # keyed op like quantize legitimately runs for
                            # minutes), then replay its record — or execute
                            # ourselves if it abandoned (non-2xx left no
                            # side effects behind)
                            wait_s = 30.0
                            if deadline is not None:
                                wait_s = min(
                                    max(deadline - time.time(), 1.0), 600.0)
                            val.wait(timeout=wait_s)
                            val = router.replay.get(method, parsed.path,
                                                    idem_key)
                            state = "replay" if val is not None else "owner"
                        if state == "replay":
                            resilience.incr(
                                "kubeml_http_idempotent_replays_total",
                                router.name)
                            replayed = True
                            resp = val
                            raise _Replayed()
                        replay_owner = True
                    # distributed tracing: bind the inbound W3C context to
                    # this handler thread (downstream hops forward it even
                    # when local recording is off) and record a server span
                    # per request. /health and /metrics are excluded —
                    # liveness polls and Prometheus scrapes would otherwise
                    # dominate (and slowly evict) every trace buffer.
                    ctx = tracing.parse_traceparent(
                        self.headers.get("traceparent"))
                    tracer = tracing.get_tracer()
                    with tracing.use_context(ctx), \
                            resilience.bind_deadline(deadline):
                        if internal:
                            resp = router.dispatch(
                                method, parsed.path, parse_qs(parsed.query),
                                body, self.headers)
                        else:
                            with tracer.span(
                                    f"{router.name} {method} {parsed.path}",
                                    service=router.name, method=method,
                                    path=parsed.path):
                                resp = router.dispatch(
                                    method, parsed.path, parse_qs(parsed.query),
                                    body, self.headers)
                except _Replayed:
                    pass
                except KubeMLError as e:
                    headers = {}
                    retry_after = getattr(e, "retry_after", None)
                    if retry_after is not None:
                        headers["Retry-After"] = str(int(retry_after))
                    resp = Response(e.to_dict(), status=e.status_code,
                                    headers=headers)
                except BrokenPipeError:
                    if replay_owner:  # release any duplicate waiting on us
                        router.replay.settle(method, urlparse(self.path).path,
                                             idem_key)
                    return
                except Exception as e:  # generic 500 envelope (server.py:133-151)
                    log.exception("%s: unhandled error on %s %s", router.name, method, self.path)
                    resp = Response({"error": str(e), "code": 500}, status=500)
                if replay_owner:
                    # record SUCCESSES only: replay exists to stop a retried
                    # delivery from re-running side effects, and only a 2xx
                    # has them. A 4xx/5xx left no state behind and may be
                    # transient (momentary 404/409), so re-executing is both
                    # safe and more accurate than a stale cached verdict;
                    # streams can't be replayed at all. Settling also wakes
                    # any duplicate delivery that waited on this execution.
                    ok = (not isinstance(resp, StreamResponse)
                          and resp.status < 300)
                    router.replay.settle(method, urlparse(self.path).path,
                                         idem_key, resp if ok else None)
                try:
                    self._respond(resp)
                except BrokenPipeError:
                    pass

            def do_GET(self):
                self._handle("GET")

            def do_POST(self):
                self._handle("POST")

            def do_PUT(self):
                self._handle("PUT")

            def do_DELETE(self):
                self._handle("DELETE")

        self._server = _Server((self.host, self.port), _Handler)
        self._server.daemon_threads = True
        if self.port == 0:
            self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, name=f"httpd-{self.router.name}", daemon=True
        )
        self._thread.start()
        log.info("%s listening on %s:%d", self.router.name, self.host, self.port)
        return self

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def stop(self) -> None:
        if self._server:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
