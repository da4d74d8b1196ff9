"""The one-shot HTTP client every outbound call in this repo goes through
(or `server.httpd.PooledHTTP`, for the data plane's keep-alive hot paths):
`http_request`, `get_json`, `post_json`.

It lives apart from `server.httpd` because its two users want opposite
things of a module's top: a server loads everything before its first
request, a client (the admin shell is one process per script) loads nothing
it will not call. So this imports `http.client`, `urllib.parse` and `json`,
and neither `http.server` nor `urllib.request`; `server.httpd` takes the
three functions from here and keeps their names.

Each call is one connection, closed after the response, with the active
trace context in its headers (`stats.trace.with_trace_headers`). The
default timeout is the shared RetryPolicy one, so no call anywhere can hang
a worker forever: callers pass their own only to tighten (heartbeats) or
loosen (volume copies).
"""

from __future__ import annotations

import http.client
import json
import socket
import urllib.parse

from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.util.retry import DEFAULT_TIMEOUT

# what `urllib.request` followed, and so what this follows
_REDIRECTS = (301, 302, 303, 307, 308)
_MAX_REDIRECTS = 10

# the process's mutual-TLS client context, kept here so that a process that was
# given no [tls] section never imports `security`: `security.tls.configure`
# sets it, `reset` clears it, and `security.tls.client_context()` reads it
tls_context = None


def set_tls_context(ctx) -> None:
    global tls_context
    tls_context = ctx


def http_request(
    method: str,
    url: str,
    body: bytes | None = None,
    headers: dict | None = None,
    timeout: float = DEFAULT_TIMEOUT,
) -> tuple[int, dict, bytes]:
    """-> (status, headers, body); an HTTP error status is returned, not
    raised. A failed connection raises OSError, a broken response
    `http.client.HTTPException`."""
    headers = trace.with_trace_headers(headers)
    if url.startswith("http+unix://"):
        return _unix_http_request(method, url, body, headers, timeout)
    send = {"Connection": "close"}
    send.update(headers or {})
    if body is not None and not any(k.lower() == "content-type" for k in send):
        # what urllib sent for a body without a type: servers store it
        send["Content-Type"] = "application/x-www-form-urlencoded"
    for _ in range(_MAX_REDIRECTS):
        u = urllib.parse.urlsplit(url)
        if u.scheme == "https":
            conn = http.client.HTTPSConnection(
                u.netloc, timeout=timeout, context=tls_context)
        else:
            conn = http.client.HTTPConnection(u.netloc, timeout=timeout)
        try:
            conn.request(
                method, (u.path or "/") + (f"?{u.query}" if u.query else ""),
                body=body, headers=send)
            resp = conn.getresponse()
            status, got, data = resp.status, dict(resp.headers), resp.read()
        finally:
            conn.close()
        target = resp.headers.get("Location")
        follow = method in ("GET", "HEAD") or (
            method == "POST" and status in (301, 302, 303))
        if status not in _REDIRECTS or not target or not follow:
            break
        url = urllib.parse.urljoin(url, target)
        if method == "POST":  # as a browser does: a GET, the body not re-sent
            method, body = "GET", None
            send = {k: v for k, v in send.items()
                    if k.lower() not in ("content-type", "content-length")}
    return status, got, data


def _unix_http_request(
    method: str, url: str, body: bytes | None, headers: dict | None,
    timeout: float,
) -> tuple[int, dict, bytes]:
    """HTTP over a unix domain socket. URL form
    `http+unix://<percent-encoded-socket-path><request-path>` — the same
    convention requests-unix-socket/docker clients use. Server side:
    HTTPService.enable_unix_socket (`-filer.localSocket`)."""
    rest = url[len("http+unix://"):]
    sock_quoted, _, path_qs = rest.partition("/")
    sock_path = urllib.parse.unquote(sock_quoted)

    class _Conn(http.client.HTTPConnection):
        def __init__(self) -> None:
            super().__init__("localhost", timeout=timeout)

        def connect(self) -> None:
            s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            s.settimeout(timeout)
            s.connect(sock_path)
            self.sock = s

    conn = _Conn()
    try:
        conn.request(method, "/" + path_qs, body=body,
                     headers=dict(headers or {}))
        resp = conn.getresponse()
        return resp.status, dict(resp.headers), resp.read()
    finally:
        conn.close()


def get_json(url: str, timeout: float = DEFAULT_TIMEOUT) -> dict:
    status, _, body = http_request("GET", url, timeout=timeout)
    data = json.loads(body) if body else {}
    if status >= 400:
        raise IOError(f"GET {url} -> {status}: {data}")
    return data


def post_json(url: str, payload: dict | None = None,
              timeout: float = DEFAULT_TIMEOUT) -> dict:
    body = json.dumps(payload or {}).encode()
    status, _, out = http_request(
        "POST", url, body, {"Content-Type": "application/json"}, timeout
    )
    data = json.loads(out) if out else {}
    if status >= 400:
        raise IOError(f"POST {url} -> {status}: {data}")
    return data
