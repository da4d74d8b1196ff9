"""Minimal threaded HTTP service kit (routing + JSON + multipart).

Built on http.server.ThreadingHTTPServer — the control plane is not the
benchmark surface; the data plane stays on big bodies where Python's
overhead amortizes.
"""

from __future__ import annotations

import json
import re
import os
import socket
import threading
import urllib.parse

from seaweedfs_tpu.security import tls as _tls
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable


class Request:
    def __init__(self, handler: BaseHTTPRequestHandler, match: re.Match) -> None:
        self.handler = handler
        self.match = match
        parsed = urllib.parse.urlparse(handler.path)
        self.path = parsed.path
        self.raw_query = parsed.query  # exact bytes: fastlane profile keys
        self.query = {
            k: v[0] for k, v in urllib.parse.parse_qs(parsed.query).items()
        }
        self.headers = handler.headers
        self.method = handler.command
        ca = handler.client_address
        # AF_UNIX peers have no address tuple (same-host by construction)
        self.remote_ip = ca[0] if isinstance(ca, tuple) and ca else "unix"

        self._body: bytes | None = None

    @property
    def body(self) -> bytes:
        if self._body is None:
            length = int(self.headers.get("Content-Length") or 0)
            self._body = self.handler.rfile.read(length) if length else b""
        return self._body

    def json(self) -> dict:
        if not self.body:
            return {}
        return json.loads(self.body)

    def multipart_file(self) -> tuple[str, str, bytes] | None:
        """Parse the first file part of a multipart/form-data body ->
        (filename, content_type, data); None if not multipart."""
        ctype = self.headers.get("Content-Type", "")
        m = re.search(r'boundary="?([^";]+)"?', ctype)
        if "multipart/form-data" not in ctype or not m:
            return None
        boundary = m.group(1).encode()
        parts = self.body.split(b"--" + boundary)
        for part in parts:
            if b"\r\n\r\n" not in part:
                continue
            head, _, data = part.partition(b"\r\n\r\n")
            if data.endswith(b"\r\n"):
                data = data[:-2]
            head_s = head.decode("utf-8", "replace")
            fm = re.search(r'filename="([^"]*)"', head_s)
            if fm is None:
                continue
            cm = re.search(r"Content-Type:\s*([^\r\n]+)", head_s, re.I)
            return fm.group(1), (cm.group(1).strip() if cm else ""), data
        return None


    def multipart_form(self) -> tuple[dict, tuple[str, str, bytes] | None]:
        """Parse a multipart/form-data body -> ({field: value}, file_part)
        where file_part is (filename, content_type, data) for the part named
        "file" (or any part carrying a filename). Browser-POST uploads
        (S3 post-policy) arrive this way."""
        ctype = self.headers.get("Content-Type", "")
        m = re.search(r'boundary="?([^";]+)"?', ctype)
        fields: dict = {}
        if "multipart/form-data" not in ctype or not m:
            return fields, None
        boundary = m.group(1).encode()
        file_part = None
        for part in self.body.split(b"--" + boundary):
            if b"\r\n\r\n" not in part:
                continue
            head, _, data = part.partition(b"\r\n\r\n")
            if data.endswith(b"\r\n"):
                data = data[:-2]
            head_s = head.decode("utf-8", "replace")
            nm = re.search(r'name="([^"]*)"', head_s)
            if nm is None:
                continue
            fm = re.search(r'filename="([^"]*)"', head_s)
            if fm is not None:
                cm = re.search(r"Content-Type:\s*([^\r\n]+)", head_s, re.I)
                file_part = (
                    fm.group(1), (cm.group(1).strip() if cm else ""), data
                )
            else:
                fields[nm.group(1)] = data.decode("utf-8", "replace")
        return fields, file_part


class Response:
    def __init__(
        self,
        body: bytes | str | dict | None = None,
        status: int = 200,
        headers: dict | None = None,
        content_type: str | None = None,
    ) -> None:
        self.status = status
        self.headers = dict(headers or {})
        if isinstance(body, dict):
            self.body = json.dumps(body).encode()
            self.headers.setdefault("Content-Type", "application/json")
        elif isinstance(body, str):
            self.body = body.encode()
            self.headers.setdefault("Content-Type", "text/plain; charset=utf-8")
        else:
            self.body = body or b""
            if content_type:
                self.headers.setdefault("Content-Type", content_type)


class HTTPService:
    """Route table + server lifecycle. Routes are (method, regex) -> fn(req)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0) -> None:
        self.host = host
        self.port = port
        self.routes: list[tuple[str, re.Pattern, Callable[[Request], Response]]] = []
        self.guard = None  # security.Guard — 403s non-whitelisted IPs when set
        self.metrics_role: str | None = None  # instrument requests when set
        self.trace_role: str | None = None  # record request spans when set
        self._httpd: ThreadingHTTPServer | None = None
        self._thread: threading.Thread | None = None

    def enable_metrics(self, role: str, serve_route: bool = True) -> None:
        """Count + time every request under this role label and (unless the
        main port has a catch-all route, like the filer) serve Prometheus
        text format on /metrics (`weed/stats/metrics.go`)."""
        from seaweedfs_tpu.stats import default_registry

        self.metrics_role = role
        reg = default_registry()
        self._m_total = reg.counter(
            "SeaweedFS_http_request_total", "requests", ("role", "method", "code")
        )
        # exemplars: each latency sample carries the active trace id, so
        # a cluster.top p99 row links straight to the trace that landed
        # in that bucket (/debug/traces?id= point lookup)
        self._m_seconds = reg.histogram(
            "SeaweedFS_http_request_seconds", "request latency",
            ("role", "method"), exemplars=True,
        )
        # the handler thread's own CPU beside its wall seconds: whether a
        # slow request computed or waited (for the interpreter, a lock, I/O)
        self._m_cpu = reg.counter(
            "SeaweedFS_http_request_cpu_seconds_total",
            "thread CPU seconds of request handlers", ("role", "method"),
        )
        if serve_route:
            @self.route("GET", r"/metrics")
            def metrics(req: Request) -> Response:
                return Response(
                    reg.render().encode(),
                    content_type="text/plain; version=0.0.4; charset=utf-8",
                )
        # process identity: start time (the history ring's restart signal)
        # and a build_info series per role — cluster.top's uptime/version
        import seaweedfs_tpu
        from seaweedfs_tpu.stats import alerts as alerts_mod
        from seaweedfs_tpu.stats import history as history_mod
        from seaweedfs_tpu.stats.metrics import PROCESS_START_TIME

        # whole seconds: an integer renders exactly in the exposition
        # (uptime math off a digit-clipped float put starts in the future)
        reg.gauge(
            "SeaweedFS_process_start_time_seconds",
            "unix time this process started (counter-reset detection)",
        ).set(int(PROCESS_START_TIME))
        reg.gauge(
            "SeaweedFS_build_info",
            "constant 1, labeled with the build version and server role",
            ("version", "role"),
        ).labels(seaweedfs_tpu.__version__, role).set(1)
        # the self-scraping history ring + alert engine + flight recorder
        # start with the first metered server in the process (library
        # imports pay nothing)
        from seaweedfs_tpu.stats import events as events_mod

        from seaweedfs_tpu.stats import heat as heat_mod
        from seaweedfs_tpu.stats import usage as usage_mod

        history_mod.default_history().start()
        alerts_mod.engine()
        events_mod.enable()
        usage_mod.enable()
        heat_mod.enable()
        self.enable_tracing(role)

    def enable_tracing(self, role: str) -> None:
        """Record a span for every request under this role (inheriting the
        caller's trace via X-Sw-Trace-Id/X-Sw-Span) and serve the shared
        ring buffer on /debug/traces + /debug/requests. Idempotent. Like
        the request histograms, spans cover the Python path only — requests
        the native engine serves never reach _dispatch."""
        if self.trace_role is not None:
            return
        self.trace_role = role
        _register_debug_routes(self)

    def serve_debug_routes(self) -> None:
        """Expose /debug/traces + /debug/requests without per-request
        spans (standalone listeners like MetricsService)."""
        _register_debug_routes(self)

    def route(self, method: str, pattern: str):
        compiled = re.compile(pattern)

        def deco(fn):
            self.routes.append((method, compiled, fn))
            return fn

        return deco

    def _dispatch(self, handler: BaseHTTPRequestHandler) -> None:
        import time as _time

        start = _time.monotonic()
        cpu_start = _time.thread_time()
        path = urllib.parse.urlparse(handler.path).path
        span = None
        if self.trace_role is not None:
            from seaweedfs_tpu.stats import trace as _trace

            span = _trace.begin_server_span(
                self.trace_role, handler.command, path, handler.headers
            )
        peer_ok = True
        # unix-socket peers are same-host-trusted by construction: neither
        # the mTLS CN gate (no TLS on AF_UNIX) nor the IP guard applies
        if getattr(self, "_tls_on", False) and not getattr(
                handler, "_unix_peer", False):
            try:
                peer_ok = _tls.peer_allowed(
                    handler.connection.getpeercert(), self._allowed_cns
                )
            except Exception:
                peer_ok = False
        if not peer_ok:
            req = None
            resp = Response({"error": "client certificate CN not allowed"}, 403)
        elif self.guard is not None and isinstance(
            handler.client_address, tuple
        ) and handler.client_address and not self.guard.is_allowed(
            handler.client_address[0]
        ):  # unix-socket peers are same-host: the IP whitelist is N/A
            req = None
            resp = Response({"error": "forbidden"}, 403)
        else:
            for method, pattern, fn in self.routes:
                if method != handler.command:
                    continue
                m = pattern.fullmatch(path)
                if m is None:
                    continue
                req = Request(handler, m)
                try:
                    resp = fn(req)
                except Exception as e:  # uniform JSON error surface
                    from seaweedfs_tpu.util.sentry import capture_exception

                    capture_exception(e, path=path, method=handler.command)
                    resp = Response({"error": str(e)}, status=500)
                break
            else:
                req = None
                resp = Response({"error": f"no route {handler.command} {path}"}, 404)
        if self.metrics_role is not None:
            # a QoS shed (X-Sw-Qos-Reason rides every one) is a
            # deliberate refusal AHEAD of service, not a service
            # failure: counting its 503 in http_request_total would
            # burn the very availability SLO the actuator watches and
            # the shed would sustain itself — locally and cluster-wide,
            # since telemetry frames ship these counters to the master.
            # SeaweedFS_qos_shed_total is the canonical record.
            if "X-Sw-Qos-Reason" not in resp.headers:
                self._m_total.labels(
                    self.metrics_role, handler.command, str(resp.status)
                ).inc()
                self._m_seconds.labels(
                    self.metrics_role, handler.command
                ).observe(_time.monotonic() - start)
                self._m_cpu.labels(
                    self.metrics_role, handler.command
                ).inc(_time.thread_time() - cpu_start)
        if span is not None:
            from seaweedfs_tpu.stats import trace as _trace

            resp.headers.setdefault(_trace.TRACE_HEADER, span.trace_id)
            _trace.end_server_span(span, resp.status)
        # drain an unread request body before responding — on a keep-alive
        # connection leftover body bytes would desynchronize the next request
        length = int(handler.headers.get("Content-Length") or 0)
        if length and (req is None or req._body is None):
            try:
                handler.rfile.read(length)
            except Exception:
                pass
        try:
            handler.send_response(resp.status)
            body = resp.body
            # a handler may pre-set Content-Length (HEAD responses advertise
            # the entity size while sending no body)
            if "Content-Length" not in resp.headers:
                handler.send_header("Content-Length", str(len(body)))
            for k, v in resp.headers.items():
                handler.send_header(k, v)
            handler.end_headers()
            if handler.command != "HEAD":
                handler.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            pass

    _SWITCH_INTERVAL_SET = False

    def start(self) -> None:
        # Many handler threads on few cores convoy badly on the default 5ms
        # GIL switch interval (p99 explodes, throughput collapses ~2-4x on a
        # single-core host). Request serving is IO-and-syscall heavy and the
        # compute kernels release the GIL in C, so a sub-ms interval is the
        # right trade for every server in this process. Override:
        # SEAWEEDFS_TPU_SWITCH_INTERVAL (seconds; "0" leaves the default).
        if not HTTPService._SWITCH_INTERVAL_SET:
            HTTPService._SWITCH_INTERVAL_SET = True
            import sys as _sys

            val = os.environ.get("SEAWEEDFS_TPU_SWITCH_INTERVAL", "0.0005")
            try:
                if float(val) > 0:
                    _sys.setswitchinterval(float(val))
            except ValueError:
                pass
        service = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            disable_nagle_algorithm = True  # response headers+body are
            # separate writes; Nagle would stall keep-alive clients ~40ms

            def log_message(self, fmt, *args):  # silent
                pass

            def _handle(self):
                service._dispatch(self)

            do_GET = do_POST = do_PUT = do_DELETE = do_HEAD = _handle
            # WebDAV verbs (webdav_server.go surface)
            do_OPTIONS = do_PROPFIND = do_PROPPATCH = do_MKCOL = _handle
            do_MOVE = do_COPY = do_LOCK = do_UNLOCK = _handle

        # plain_backend: this listener sits BEHIND the native engine, which
        # terminates mTLS and enforces the CN gate itself; serve plaintext
        # on loopback only (never on an external interface)
        plain_backend = getattr(self, "plain_backend", False)
        ctx = None if plain_backend else _tls.server_context()
        self._tls_on = ctx is not None
        self._allowed_cns = _tls.allowed_cn_patterns()
        bind_host = "127.0.0.1" if plain_backend else self.host
        if ctx is None:
            self._httpd = ThreadingHTTPServer((bind_host, self.port), Handler)
        else:
            # mTLS on every listener (`weed/security/tls.go` semantics).
            # The accepted socket is wrapped WITHOUT handshaking: the
            # handshake runs lazily on first read inside the per-connection
            # handler thread, so a stalled client cannot pin the accept loop.
            class TLSHTTPServer(ThreadingHTTPServer):
                def get_request(inner):
                    sock, addr = inner.socket.accept()
                    sock.settimeout(60)
                    return (
                        ctx.wrap_socket(
                            sock, server_side=True,
                            do_handshake_on_connect=False,
                        ),
                        addr,
                    )

            self._httpd = TLSHTTPServer((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]
        self._handler_cls = Handler
        self._thread = threading.Thread(target=self._httpd.serve_forever, daemon=True)
        self._thread.start()

    def enable_unix_socket(self, path: str) -> None:
        """Extra AF_UNIX listener sharing this service's routes — the
        `-filer.localSocket` feature (`weed/command/filer.go`): same-host
        clients (mounts especially) skip the TCP stack. The unix path is
        same-host-trusted, like the reference's — no TLS/guard applies,
        and requests bypass any engine front (they reach Python directly).
        Call after start()."""
        import socketserver

        class handler(self._handler_cls):
            # TCP_NODELAY does not exist on AF_UNIX sockets
            disable_nagle_algorithm = False
            _unix_peer = True  # exempt from the mTLS CN gate (same-host)

        class UnixHTTPServer(ThreadingHTTPServer):
            address_family = socket.AF_UNIX

            def server_bind(inner):
                try:
                    os.unlink(path)
                except FileNotFoundError:
                    pass
                # skip HTTPServer.server_bind: it unpacks server_address
                # as (host, port), which a unix path is not
                socketserver.TCPServer.server_bind(inner)
                inner.server_name = "localhost"
                inner.server_port = 0

        srv = UnixHTTPServer(path, handler)
        self._unix_httpd = srv
        self._unix_path = path
        threading.Thread(target=srv.serve_forever, daemon=True).start()

    @property
    def unix_url(self) -> str | None:
        """http+unix:// URL for the local-socket listener, or None."""
        path = getattr(self, "_unix_path", None)
        if path is None:
            return None
        return "http+unix://" + urllib.parse.quote(path, safe="")

    def stop(self) -> None:
        if self._httpd:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        unix = getattr(self, "_unix_httpd", None)
        if unix is not None:
            unix.shutdown()
            unix.server_close()
            self._unix_httpd = None
            try:
                os.unlink(self._unix_path)
            except OSError:
                pass
            self._unix_path = None  # unix_url must stop advertising it

    @property
    def url(self) -> str:
        scheme = "https" if getattr(self, "_tls_on", False) else "http"
        return f"{scheme}://{self.host}:{self.port}"


def _since_param(query: dict):
    """Parse the shared `?since=` incremental cursor (None when absent;
    ValueError on anything non-finite — the routes turn that into a 400,
    never an unhandled 500). Both /debug/metrics/history and
    /debug/events use this: pass the previous response's unrounded
    `watermark` back and only strictly-newer items ship."""
    import math

    since = query.get("since")
    if since is None:
        return None
    since = float(since)
    if not math.isfinite(since):
        raise ValueError(since)
    return since


def _register_debug_routes(service: "HTTPService") -> None:
    """`/debug/traces` (recent finished traces, JSON; ?limit= & ?min_ms=),
    `/debug/requests` (in-flight spans; ?limit=), and the profiling
    surface: `/debug/pprof/profile` (?seconds= & ?hz=; collapsed-stack
    text, ?format=json for the structured form), `/debug/pprof/threads`
    (instant all-thread dump), `/debug/pprof/device` (jax.profiler trace
    tarball; 501 without jax), plus the PR-4 history/alert surface:
    `/debug/metrics/history` (?family= & ?window= & ?samples=; the
    self-scraped ring with windowed counter rates) and `/debug/alerts`
    (?window=; every rule's firing state). Registered by enable_tracing, so on
    catch-all namespaces (the filer) they precede — and shadow —
    same-named file paths. Malformed numeric query params are a 400 with
    a JSON error, never an unhandled 500."""
    from seaweedfs_tpu.stats import trace as trace_mod

    col = trace_mod.collector()

    @service.route("GET", r"/debug/traces")
    def debug_traces(req: Request) -> Response:
        import math

        trace_id = req.query.get("id")
        if trace_id is not None:
            # exact-lookup (?id=): exemplar links and cluster.why resolve
            # one trace without paging the whole ring. Malformed ids are
            # a 400 with a JSON error, consistent with the other routes.
            if not re.fullmatch(r"[0-9a-f]{1,32}", trace_id):
                return Response(
                    {"error": f"malformed trace id {trace_id!r}"
                              " (lowercase hex)"}, 400
                )
            spans = col.trace_spans(trace_id)
            return Response({
                "trace_id": trace_id,
                "found": bool(spans),
                "spans": spans,
            })
        try:
            limit = int(req.query.get("limit", 20))
            min_ms = float(req.query.get("min_ms", 0))
            if not math.isfinite(min_ms):
                raise ValueError(min_ms)
        except ValueError:
            return Response(
                {"error": "limit/min_ms must be finite numbers"}, 400
            )
        return Response({
            "traces": col.traces(limit=limit, min_ms=min_ms),
            "capacity": col.max_spans,
        })

    @service.route("GET", r"/debug/requests")
    def debug_requests(req: Request) -> Response:
        try:
            limit = int(req.query.get("limit", 0))
        except ValueError:
            return Response({"error": "limit must be numeric"}, 400)
        in_flight = col.inflight()
        if limit > 0:
            in_flight = in_flight[:limit]
        return Response({"in_flight": in_flight})

    @service.route("GET", r"/debug/pprof/profile")
    def debug_pprof_profile(req: Request) -> Response:
        from seaweedfs_tpu.stats import profiler as prof_mod

        try:
            seconds = prof_mod.clamp_seconds(req.query.get("seconds", 2))
            hz = int(req.query.get("hz", 100))
        except ValueError:
            return Response({"error": "seconds/hz must be finite numbers"}, 400)
        try:
            out = prof_mod.profile(seconds=seconds, hz=hz)
        except prof_mod.ProfilerBusy as e:
            return Response({"error": str(e)}, 429)
        out["role"] = service.trace_role or service.metrics_role
        out["proc"] = prof_mod.PROCESS_TOKEN  # cluster.profile dedup key
        if req.query.get("format") == "json":
            return Response(out)
        return Response(prof_mod.render_collapsed(out["stacks"]))

    @service.route("GET", r"/debug/pprof/threads")
    def debug_pprof_threads(req: Request) -> Response:
        from seaweedfs_tpu.stats import profiler as prof_mod

        return Response({
            "role": service.trace_role or service.metrics_role,
            "threads": prof_mod.threads_dump(),
        })

    @service.route("GET", r"/debug/metrics/history")
    def debug_metrics_history(req: Request) -> Response:
        import math

        from seaweedfs_tpu.stats import history as history_mod
        from seaweedfs_tpu.stats import profiler as prof_mod

        hist = history_mod.default_history()
        try:
            window = float(req.query.get("window", hist.retention_seconds))
            max_samples = int(req.query.get("samples", 16))
            if not math.isfinite(window) or window <= 0:
                raise ValueError(window)
            # ?since=<mono_ts>: incremental cursor — ship only samples
            # after the caller's watermark (the previous response's
            # "watermark" field), not the full ring every poll
            since = _since_param(req.query)
        except ValueError:
            return Response(
                {"error": "window/samples/since must be finite numbers"},
                400,
            )
        hist.ensure_fresh()
        from seaweedfs_tpu.stats import default_registry as _dr

        return Response({
            "interval": hist.interval,
            "slots": hist.slots,
            "window": window,
            "scrapes": hist.scrapes_total,
            # pass this back as ?since= for the next incremental poll.
            # Unrounded on purpose: sample timestamps are rounded to 3
            # decimals for display, so a rounded-DOWN watermark could sit
            # below the exact stored timestamp of the scrape it names and
            # re-ship that scrape's samples on the next poll.
            "watermark": hist.last_scrape,
            "proc": prof_mod.PROCESS_TOKEN,  # cluster.top dedup key
            "series": hist.snapshot(
                family=req.query.get("family") or None,
                window=window,
                max_samples=max(0, max_samples),
                since=since,
            ),
            # histogram exemplars ride here, not in the 0.0.4 text format
            # (which has no exemplar syntax): per (labels, upper bucket),
            # the freshest sample's trace id — the p99 -> trace join
            "exemplars": _dr().exemplars(
                family=req.query.get("family") or None
            ),
        })

    @service.route("GET", r"/debug/alerts")
    def debug_alerts(req: Request) -> Response:
        import math

        from seaweedfs_tpu.stats import alerts as alerts_mod
        from seaweedfs_tpu.stats import profiler as prof_mod

        window = req.query.get("window")
        try:
            if window is not None:
                window = float(window)
                if not math.isfinite(window) or window <= 0:
                    raise ValueError(window)
        except ValueError:
            return Response(
                {"error": "window must be a positive finite number"}, 400
            )
        out = alerts_mod.engine().status(window=window)
        out["proc"] = prof_mod.PROCESS_TOKEN
        return Response(out)

    @service.route("GET", r"/debug/events")
    def debug_events(req: Request) -> Response:
        """The flight-recorder journal (stats/events.py): typed events
        with correlation keys, filterable by ?type= / ?volume= /
        ?trace= / ?since= (+ ?limit=). `?since=` is the same strictly-
        after cursor /debug/metrics/history carries: pass the previous
        response's unrounded `watermark` back and a watch-mode poller
        stops re-shipping the whole ring. cluster.why fans this out
        across every node and assembles the causal timeline."""
        from seaweedfs_tpu.stats import events as events_mod
        from seaweedfs_tpu.stats import profiler as prof_mod

        q = req.query
        try:
            limit = int(q.get("limit", 256))
            volume = int(q["volume"]) if "volume" in q else None
            since = _since_param(q)
        except ValueError:
            return Response(
                {"error": "limit/volume/since must be finite numbers"}, 400
            )
        type_ = q.get("type") or None
        if type_ is not None and type_ not in events_mod.EVENT_TYPES:
            return Response(
                {"error": f"unknown event type {type_!r}",
                 "types": sorted(events_mod.EVENT_TYPES)}, 400
            )
        rec = events_mod.recorder()
        return Response({
            "proc": prof_mod.PROCESS_TOKEN,  # cluster.why dedup key
            "role": service.trace_role or service.metrics_role,
            "enabled": rec.enabled,
            "capacity": rec.capacity,
            "recorded": rec.recorded_total,
            "dropped": rec.dropped_total,
            # pass back as ?since= next poll. Unrounded on purpose: event
            # ts are rounded to 6 decimals for display, and a rounded-
            # DOWN watermark would re-ship its own newest event.
            "watermark": rec.last_wall,
            "events": rec.events(type=type_, volume=volume,
                                 trace=q.get("trace") or None,
                                 since=since,
                                 collection=q.get("collection") or None,
                                 limit=limit),
        })

    @service.route("GET", r"/debug/usage")
    def debug_usage(req: Request) -> Response:
        """The bounded-cardinality tenant accountant (stats/usage.py):
        top-K collections by requests/bytes/errors, the `_other` fold,
        and the sketch's exported error bound. ?n= caps the tenant rows."""
        from seaweedfs_tpu.stats import profiler as prof_mod
        from seaweedfs_tpu.stats import usage as usage_mod

        try:
            n = int(req.query["n"]) if "n" in req.query else None
            if n is not None and n < 1:
                raise ValueError(n)
        except ValueError:
            return Response({"error": "n must be a positive integer"}, 400)
        out = usage_mod.accountant().snapshot(n=n)
        out["proc"] = prof_mod.PROCESS_TOKEN
        out["role"] = service.trace_role or service.metrics_role
        return Response(out)

    @service.route("GET", r"/debug/heat")
    def debug_heat(req: Request) -> Response:
        """The heat engine's view (stats/heat.py): per-volume heat
        scores, per-node/dir days-to-full forecasts, and — on a master —
        the heartbeat-fed collection/node rollup. ?n= caps each list."""
        from seaweedfs_tpu.stats import heat as heat_mod
        from seaweedfs_tpu.stats import profiler as prof_mod

        try:
            n = int(req.query["n"]) if "n" in req.query else None
            if n is not None and n < 1:
                raise ValueError(n)
        except ValueError:
            return Response({"error": "n must be a positive integer"}, 400)
        out = heat_mod.engine().snapshot()
        rollup_colls, rollup_nodes = [], []
        for ru in heat_mod.rollups():
            snap = ru.snapshot()
            rollup_colls.extend(snap["collections"])
            rollup_nodes.extend(snap["nodes"])
        if rollup_colls or rollup_nodes:
            out["collections"] = rollup_colls
            out["nodes"] = rollup_nodes
        if n is not None:
            for k in ("volumes", "forecast", "collections", "nodes"):
                if k in out:
                    out[k] = out[k][:n]
        out["proc"] = prof_mod.PROCESS_TOKEN
        out["role"] = service.trace_role or service.metrics_role
        return Response(out)

    @service.route("GET", r"/qos/limits")
    def qos_limits_get(req: Request) -> Response:
        """This process's admission-control state (qos/admission.py):
        limits, gates, queue bounds, admitted/queued/shed counters and
        live bucket levels. `/debug/qos` is the same payload."""
        from seaweedfs_tpu.qos import admission as qos_mod
        from seaweedfs_tpu.stats import profiler as prof_mod

        out = qos_mod.controller().status()
        act = None
        from seaweedfs_tpu.qos import actuator as act_mod

        a = act_mod.actuator()
        if a is not None:
            act = {"level": a.level, "burn": round(a.last_burn, 3),
                   "fast_burn": a.fast_burn}
        out["actuator"] = act
        out["proc"] = prof_mod.PROCESS_TOKEN
        out["role"] = service.trace_role or service.metrics_role
        return Response(out)

    service.route("GET", r"/debug/qos")(qos_limits_get)

    @service.route("POST", r"/qos/limits")
    def qos_limits_post(req: Request) -> Response:
        """Runtime limit updates for THIS process — the cluster.qos verb
        fans this out across discovered gateways. Body (all optional):
          {"limits": {"tenant-a": 100, "tenant-b": [50, 200]},
           "default": 25, "queue_depth": 32, "queue_wait": 0.25,
           "spec": "tenant-a=100,*=25"}
        `limits`/`spec` replace the whole table (declarative, like the
        CLI flag); values are rps or [rps, burst]. Posting any config
        arms admission on a metered server."""
        from seaweedfs_tpu.qos import admission as qos_mod

        p = req.json()
        ctl = qos_mod.controller()
        try:
            limits, default = p.get("limits"), p.get("default")
            if "spec" in p:
                limits, default = qos_mod.parse_limits_spec(p["spec"])
            ctl.set_limits(limits=limits, default=default,
                           queue_depth=p.get("queue_depth"),
                           queue_wait=p.get("queue_wait"))
            qos_mod.enable()
        except (ValueError, TypeError) as e:
            return Response({"error": str(e)}, 400)
        return Response({"ok": True, "armed": ctl.armed,
                         "limits": ctl.status()["limits"],
                         "default": ctl.status()["default"]})

    @service.route("GET", r"/debug/faults")
    def debug_faults_get(req: Request) -> Response:
        from seaweedfs_tpu.util import faults as faults_mod

        snap = faults_mod.snapshot()
        return Response({
            "points": snap,
            "declared": list(faults_mod.ALL_POINTS),
            "armed": sum(1 for p in snap if p["armed"] is not None),
        })

    @service.route("POST", r"/debug/faults")
    def debug_faults_post(req: Request) -> Response:
        """Runtime fault arming for THIS process — the cluster.faults
        verb fans this out across discovered nodes. Body:
          {"action": "arm", "point": ..., "mode": ...,
           "rate"/"ms"/"frac"/"count"/"key": ...}
          {"action": "disarm", "point": ...}
          {"action": "disarm_all"}
        Engine-side points additionally try the optional
        sw_fl_inject_fault ABI via the serving fastlane when one exists
        (hasattr-degraded: absence is reported, never an error)."""
        from seaweedfs_tpu.util import faults as faults_mod

        if not faults_mod.runtime_arming_enabled():
            # mutating route on every role: 403 unless the operator
            # opted this process in (-faults flag, even bare, or
            # SEAWEEDFS_TPU_FAULTS=1) — a reachable port must not be
            # enough to arm torn writes on a production server
            return Response(
                {"error": "fault injection disabled for this process"
                          " (start with -faults or SEAWEEDFS_TPU_FAULTS=1)"},
                403,
            )
        p = req.json()
        action = p.get("action", "arm")
        try:
            if action == "arm":
                spec = faults_mod.arm(
                    p["point"], p["mode"],
                    rate=p.get("rate", 1.0), ms=p.get("ms", 0.0),
                    frac=p.get("frac", 0.5), count=p.get("count", -1),
                    key=p.get("key", ""), after=p.get("after", 0),
                )
                return Response({"ok": True, "point": p["point"],
                                 "armed": spec.to_dict()})
            if action == "disarm":
                return Response({
                    "ok": True, "point": p["point"],
                    "was_armed": faults_mod.disarm(p["point"]),
                })
            if action == "disarm_all":
                return Response({"ok": True,
                                 "disarmed": faults_mod.disarm_all()})
        except (KeyError, ValueError) as e:
            return Response({"error": str(e)}, 400)
        return Response({"error": f"unknown action {action!r}"}, 400)

    @service.route("GET", r"/debug/pprof/device")
    def debug_pprof_device(req: Request) -> Response:
        from seaweedfs_tpu.stats import profiler as prof_mod

        try:
            seconds = prof_mod.clamp_seconds(req.query.get("seconds", 2))
        except ValueError:
            return Response({"error": "seconds must be a finite number"}, 400)
        try:
            data = prof_mod.device_trace(seconds)
        except prof_mod.DeviceProfilerUnavailable as e:
            return Response({"error": str(e)}, 501)
        except prof_mod.ProfilerBusy as e:
            return Response({"error": str(e)}, 429)
        return Response(
            data,
            content_type="application/gzip",
            headers={
                "Content-Disposition": 'attachment; filename="jax-trace.tar.gz"'
            },
        )


class MetricsService(HTTPService):
    """Standalone /metrics listener for servers whose main port has a
    catch-all namespace (the filer) — the reference's `-metricsPort`."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0) -> None:
        super().__init__(host, port)
        from seaweedfs_tpu.stats import default_registry

        reg = default_registry()

        @self.route("GET", r"/metrics")
        def metrics(req: Request) -> Response:
            return Response(
                reg.render().encode(),
                content_type="text/plain; version=0.0.4; charset=utf-8",
            )

        self.serve_debug_routes()


def peer_url(hostport: str) -> str:
    """Scheme-qualify another node's advertised host:port. Heartbeats and
    lookups carry bare addresses; when process-wide mTLS is configured
    (`security.tls`), every peer listener is TLS too."""
    if hostport.startswith(("http://", "https://")):
        return hostport
    scheme = "https" if _tls.client_context() is not None else "http"
    return f"{scheme}://{hostport}"


# --- client helpers -----------------------------------------------------------
# The one-shot client (`http_request`, `get_json`, `post_json`) is
# `util.http_client`, a module the admin shell can import without this one;
# servers and every other caller keep taking the names from here.
from seaweedfs_tpu.util.http_client import (  # noqa: E402,F401
    DEFAULT_TIMEOUT as _DEFAULT_TIMEOUT,
    get_json,
    http_request,
    post_json,
)


class PooledHTTP:
    """Thread-local keep-alive connections per endpoint.

    urllib opens (and tears down) a TCP connection per call, so hot
    small-request paths — `weed benchmark`'s 1KB writes/reads, replication
    fan-outs — end up measuring connection setup instead of the server.
    The reference's Go clients all reuse connections; this is the
    equivalent for the data-plane hot paths. Honors process mTLS."""

    def __init__(self, timeout: float = _DEFAULT_TIMEOUT) -> None:
        import weakref

        self._tl = threading.local()
        self.timeout = timeout
        # weak: a dead handler thread's conns must not be pinned forever —
        # GC of its thread-local dict lets the sockets finalize
        self._all = weakref.WeakSet()
        self._all_mu = threading.Lock()

    def request(
        self,
        method: str,
        url: str,
        body: bytes | None = None,
        headers: dict | None = None,
        idempotent: bool = False,
    ) -> tuple[int, dict, bytes]:
        import http.client
        import ssl as _ssl

        from seaweedfs_tpu.stats import trace as _trace

        headers = _trace.with_trace_headers(headers)
        u = urllib.parse.urlsplit(url)
        key = f"{u.scheme}://{u.netloc}"
        pool = getattr(self._tl, "conns", None)
        if pool is None:
            pool = self._tl.conns = {}
        path = u.path + (f"?{u.query}" if u.query else "")
        last: Exception | None = None
        # stale-socket retry only when a re-send cannot duplicate a side
        # effect: GET/HEAD always; writes only when the caller declares
        # them idempotent (fid-addressed chunk uploads are)
        attempts = (0, 1) if method in ("GET", "HEAD") or idempotent else (0,)
        for attempt in attempts:
            conn = pool.get(key)
            if conn is None:
                if u.scheme == "https":
                    ctx = _tls.client_context() or _ssl.create_default_context()
                    conn = http.client.HTTPSConnection(
                        u.netloc, timeout=self.timeout, context=ctx
                    )
                else:
                    conn = http.client.HTTPConnection(
                        u.netloc, timeout=self.timeout
                    )
                pool[key] = conn
                with self._all_mu:
                    self._all.add(conn)
            try:
                if conn.sock is None:
                    conn.connect()
                    # headers and body go out as separate writes; without
                    # TCP_NODELAY Nagle + delayed ACK adds ~40ms per request
                    conn.sock.setsockopt(
                        socket.IPPROTO_TCP, socket.TCP_NODELAY, 1
                    )
                conn.request(method, path, body=body, headers=headers or {})
                resp = conn.getresponse()
                data = resp.read()
                return resp.status, dict(resp.headers), data
            except (http.client.HTTPException, OSError) as e:
                last = e
                conn.close()
                pool.pop(key, None)
                with self._all_mu:
                    self._all.discard(conn)
        raise last  # type: ignore[misc]

    def close(self) -> None:
        """Close every connection this pool ever opened, across threads
        (worker threads exit without closing their thread-locals)."""
        import weakref

        with self._all_mu:
            conns = list(self._all)
            self._all = weakref.WeakSet()
        for conn in conns:
            try:
                conn.close()
            except Exception:
                pass
        pool = getattr(self._tl, "conns", None)
        if pool:
            pool.clear()
