"""Threaded HTTP server shell around PromHttpApi.

ref: http/.../FiloHttpServer.scala:85 — binds the route tree, started by the
standalone FiloServer.  Python stdlib ThreadingHTTPServer is the transport;
all route logic lives in routes.py.

The stdlib server answers HTTP/1.0: a connection a request, a thread a
connection.  The whole life of a connection lies under spans, so that what
a client waits for outside the door `http.request` has a name:

    acceptor thread   conn.accept        accept()'s return -> the end of
                                         process_request: the handler
                                         thread's creation and start, and
                                         the acceptor's wait to run again
    handler thread    conn.serve         the thread's outermost span; its
                                         self time: the socket's files, the
                                         handler object, the glue
                        conn.read_request  the request line, the headers
                        http.request       the door, as ever (traced routes)
                        conn.close         the files' flush and close, the
                                           socket's shutdown: after the
                                           answer, so outside the client's
                                           latency, inside the lock

The two threads overlap (the handler runs before `Thread.start()` has
returned to the acceptor), so what a CLIENT waits for between them is
booked apart, one stage after the other by construction:
`conn_handover_seconds_total`, accept()'s return to the handler thread's
first act.  `conn.*` counts every connection, the operator's untraced
routes (`/metrics`, `/admin/*`) too.  A thread's outermost span books its
CPU time (utils/metrics.span): `span_conn_serve_cpu_seconds_total /
span_conn_serve_calls_total` is how much of the interpreter lock a request
takes.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from filodb_tpu.http.routes import PromHttpApi
from filodb_tpu.utils.metrics import (mint_trace_id, parse_traceparent,
                                      registry, span, trace_context)

# the data-plane doors: a request to one of these opens (or continues, from
# a W3C `traceparent` header) a trace whose root span is `http.request`.
# The operator's plane (/metrics, /admin, health probes) is not traced: a
# scrape every few seconds would recycle the bounded trace ring and book
# itself into the request spans' counters
_TRACED_PREFIXES = ("/api/", "/promql/", "/influx/")


def _encode_json(payload) -> bytes:
    """A JSON route's body.  `json.dumps` walks the payload; the rows a
    range query's envelope carries beside it (`_rendered`: already their
    JSON text) close its `data` object, unwalked."""
    rendered = payload.pop("_rendered", None) \
        if isinstance(payload, dict) else None
    if rendered is None:
        return json.dumps(payload).encode()
    data = json.dumps(payload.pop("data"))
    envelope = json.dumps(payload)
    return (f'{envelope[:-1]}, "data": {data[:-1]}, "result": '
            f'{rendered.text}}}}}').encode()


def _no_span(name):
    """In `span`'s place for a request that is not traced."""
    return contextlib.nullcontext()


class _Server(ThreadingHTTPServer):
    """ThreadingHTTPServer with the connection's hand-over under spans."""

    def __init__(self, *args):
        super().__init__(*args)
        self._accepted = {}     # socket -> accept()'s return, ns
        self._handover = registry.counter("conn_handover_seconds")

    def get_request(self):
        got = super().get_request()
        # closed by process_request, the next thing the acceptor does
        # (verify_request, between the two, admits everything)
        self._accepting = span("conn.accept").__enter__()
        self._accepted[got[0]] = time.perf_counter_ns()
        return got

    def process_request(self, request, client_address):
        try:
            super().process_request(request, client_address)
        except BaseException:
            self._accepted.pop(request, None)   # no thread took it over
            raise
        finally:
            self._accepting.__exit__(None, None, None)

    def process_request_thread(self, request, client_address):
        """ThreadingMixIn's, under `conn.serve`; the handler's finish()
        has shut the socket down unless setup() raised."""
        waited = time.perf_counter_ns() - self._accepted.pop(request)
        with span("conn.serve"):
            try:
                self.finish_request(request, client_address)
            except Exception:  # noqa: BLE001 — as the stdlib's
                self.handle_error(request, client_address)
            finally:
                if request.fileno() != -1:
                    self.shutdown_request(request)
        self._handover.increment(waited * 1e-9)


class FiloHttpServer:

    def __init__(self, api: PromHttpApi, host: str = "127.0.0.1",
                 port: int = 8080):
        self.api = api
        api_ref = api

        class _Handler(BaseHTTPRequestHandler):
            def handle_one_request(self):
                """The request line's read and the headers' parse under
                `conn.read_request`: closed by parse_request's end, or
                here where the line never came."""
                self._reading = span("conn.read_request").__enter__()
                try:
                    super().handle_one_request()
                finally:
                    self._end_read()

            def parse_request(self):
                try:
                    return super().parse_request()
                finally:
                    self._end_read()

            def _end_read(self):
                reading, self._reading = self._reading, None
                if reading is not None:
                    reading.__exit__(None, None, None)

            def finish(self):
                with span("conn.close"):
                    super().finish()
                    self.server.shutdown_request(self.request)

            def _serve(self, method: str):
                parsed = urllib.parse.urlsplit(self.path)
                if not parsed.path.startswith(_TRACED_PREFIXES):
                    return self._answer(method, parsed, _no_span)
                # the root of the request's span tree: body read, routing,
                # JSON encode and the socket write all lie inside it
                tid = parse_traceparent(self.headers.get("traceparent")) \
                    or mint_trace_id()
                with trace_context(tid), span("http.request"):
                    self._answer(method, parsed, span)

            def _answer(self, method: str, parsed, sp):
                multi = urllib.parse.parse_qs(parsed.query)
                params = {k: v[-1] for k, v in multi.items()}
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else b""
                # form-decode only for the API routes: write endpoints
                # (/influx, /admin) carry raw line-protocol / text bodies
                # even when clients default the form content-type.  The
                # BINARY api/v1 endpoints (remote read/write: snappy
                # protobuf) are excluded too — simple clients POST them
                # with the default form content-type, and utf-8-decoding
                # compressed bytes must be a clean 400 at worst, never a
                # crashed handler
                if method == "POST" and body and \
                        parsed.path.startswith(("/promql", "/api")) and \
                        not parsed.path.endswith(("/read", "/write")) and \
                        self.headers.get("Content-Type", "").startswith(
                            "application/x-www-form-urlencoded"):
                    try:
                        form_multi = urllib.parse.parse_qs(body.decode())
                    except UnicodeDecodeError:
                        self.send_response(400)
                        blob = (b'{"status":"error","errorType":"bad_data",'
                                b'"error":"form-encoded body is not utf-8"}')
                        self.send_header("Content-Type", "application/json")
                        self.send_header("Content-Length", str(len(blob)))
                        self.end_headers()
                        self.wfile.write(blob)
                        return
                    form = {k: v[-1] for k, v in form_multi.items()}
                    params = {**form, **params}
                    multi = {**form_multi, **multi}
                    body = b""
                # bind the client socket for the duration of the request
                # so a query registered on this thread carries it: the
                # disconnect watcher (query/activequeries.py) detects the
                # peer closing mid-query and trips the CancellationToken
                # — abandoned dashboard polls stop consuming the
                # concurrency semaphore and device time
                from filodb_tpu.query.activequeries import bind_client_conn
                with bind_client_conn(self.connection), sp("http.route"):
                    status, payload = api_ref.route(
                        method, parsed.path, params, body,
                        multi_params=multi, headers=dict(self.headers))
                extra_headers = {}
                if isinstance(payload, bytes):      # binary (remote-read)
                    blob = payload
                    ctype = "application/x-protobuf"
                    extra_headers["Content-Encoding"] = "snappy"
                elif isinstance(payload, str):      # text routes (/metrics)
                    blob = payload.encode()
                    # routes may carry a negotiated content type (the
                    # OpenMetrics exposition); plain strings keep the
                    # Prometheus text type
                    ctype = getattr(payload, "content_type",
                                    "text/plain; version=0.0.4")
                else:
                    if isinstance(payload, dict) and "_headers" in payload:
                        extra_headers.update(payload.pop("_headers"))
                    if status == 204:
                        blob = b""
                    else:
                        with sp("http.encode"):
                            blob = _encode_json(payload)
                    ctype = "application/json"
                try:
                    with sp("http.write"):
                        self.send_response(status)
                        self.send_header("Content-Type", ctype)
                        for k, v in extra_headers.items():
                            self.send_header(k, v)
                        self.send_header("Content-Length", str(len(blob)))
                        self.end_headers()
                        if blob:
                            self.wfile.write(blob)
                except (BrokenPipeError, ConnectionResetError):
                    # the client hung up mid-request — routine since the
                    # disconnect watcher aborts abandoned queries (their
                    # canceled response has nowhere to go); the stdlib
                    # handler would traceback to stderr on every one
                    self.close_connection = True

            def do_GET(self):       # noqa: N802 — BaseHTTPRequestHandler API
                self._serve("GET")

            def do_POST(self):      # noqa: N802
                self._serve("POST")

            def log_message(self, fmt, *args):
                pass                 # quiet; observability goes via metrics

        self._httpd = _Server((host, port), _Handler)
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    def start(self) -> None:
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        # stdlib shutdown() BLOCKS until serve_forever acknowledges —
        # forever if the serving thread was never started
        if self._thread is not None:
            self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread:
            self._thread.join(timeout=5)
