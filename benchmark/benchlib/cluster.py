"""The system under test as the benchmark drives it: one `server` child that
holds the chip, `shell` children for the verbs, and plain HTTP."""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time

EC_ENV = "SEAWEEDFS_TPU_EC_BACKEND"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
MEMSTAT_ENV = "BENCH_MEMSTAT_FILE"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # benchmark/
ROOT = os.path.dirname(HERE)


class RunError(RuntimeError):
    """The run cannot go on (no result line is printed)."""


def http_call(method: str, hostport: str, path: str, body: bytes | None = None,
              timeout: float = 600.0) -> tuple[int, bytes]:
    conn = http.client.HTTPConnection(hostport, timeout=timeout)
    try:
        headers = {"Content-Type": "application/octet-stream"} if body else {}
        conn.request(method, path, body=body, headers=headers)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def get_json(hostport: str, path: str) -> dict:
    status, body = http_call("GET", hostport, path)
    if status != 200:
        raise RunError(f"GET {hostport}{path}: {status} {body[:200]!r}")
    return json.loads(body)


def get_text(hostport: str, path: str) -> str:
    status, body = http_call("GET", hostport, path)
    if status != 200:
        raise RunError(f"GET {hostport}{path}: {status} {body[:200]!r}")
    return body.decode()


def post_json(hostport: str, path: str, payload: dict) -> dict:
    status, body = http_call("POST", hostport, path, json.dumps(payload).encode())
    if status != 200:
        raise RunError(f"POST {hostport}{path}: {status} {body[:200]!r}")
    return json.loads(body)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(base: dict, own_jax_platforms: bool) -> dict:
    """The environment of a child: the runner's, without a JAX_PLATFORMS
    that the runner set only for itself."""
    env = dict(base)
    if own_jax_platforms:
        env.pop("JAX_PLATFORMS", None)
    return env


class Server:
    """`python -m seaweedfs_tpu.command.main server` (master + volume) as a
    child, with the EC pipeline set to the device in the child's environment
    only."""

    def __init__(self, workdir: str, env: dict, log_path: str) -> None:
        self.dir = os.path.join(workdir, "srv")
        os.makedirs(self.dir)
        self.master = f"127.0.0.1:{free_port()}"
        self.volume_port = free_port()
        self.memstat_path = os.path.join(workdir, "memstat.json")
        self.env = {
            **env,
            EC_ENV: "jax",
            # inside the checkout, at a fixed path: part of the cache's key
            CACHE_ENV: os.path.join(ROOT, ".jax_cache"),
            MEMSTAT_ENV: self.memstat_path,
            "PYTHONPATH": os.pathsep.join(
                [os.path.join(HERE, "childhook")]
                + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]),
        }
        self.shell_env = dict(env)
        self._log = open(log_path, "w")
        self.log_path = log_path
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu.command.main", "server",
             "-dir", self.dir, "-master.port", self.master.rsplit(":", 1)[1],
             "-volume.port", str(self.volume_port)],
            cwd=ROOT, env=self.env, stdout=self._log, stderr=subprocess.STDOUT,
            stdin=subprocess.DEVNULL,
        )
        self.volume = ""  # host:port, known after the first assign

    def assign(self, count: int, collection: str = "",
               deadline_s: float = 180.0) -> dict:
        """`/dir/assign` of `count` keys in a volume of the collection; the
        first one is also the wait for the server to be up."""
        query = f"count={count}" + (f"&collection={collection}" if collection else "")
        deadline = time.monotonic() + deadline_s
        while True:
            if self.proc.poll() is not None:
                raise RunError(f"server exited {self.proc.returncode} at boot;"
                               f" see {self.log_path}")
            try:
                out = get_json(self.master, f"/dir/assign?{query}")
                if "fid" in out:
                    self.volume = out["url"]
                    return out
            except (OSError, RunError, ValueError):
                pass
            if time.monotonic() > deadline:
                raise RunError("server did not come up")
            time.sleep(0.2)

    def shell(self, script: str, log_path: str, timeout: float = 900.0
              ) -> tuple[int, str, float]:
        """One `shell` child run to its end: (exit code, output, seconds as
        the operator waits for it)."""
        t0 = time.perf_counter()
        with open(log_path, "w") as out:
            proc = subprocess.Popen(
                [sys.executable, "-m", "seaweedfs_tpu.command.main", "shell",
                 "-master", self.master],
                cwd=ROOT, env=self.shell_env, stdout=out,
                stderr=subprocess.STDOUT, stdin=subprocess.PIPE, text=True,
            )
            try:
                proc.communicate(script, timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        seconds = time.perf_counter() - t0
        with open(log_path) as f:
            return proc.returncode, f.read(), seconds

    def metrics(self) -> str:
        return get_text(self.volume, "/metrics")

    def status(self) -> dict:
        return get_json(self.volume, "/status")

    def memory_peak_bytes(self) -> int | None:
        """Peak device memory of the server process, read by the hook the
        benchmark put on the child's path (benchmark/childhook): the chip
        belongs to that process, so only it can ask."""
        try:
            os.unlink(self.memstat_path)
        except FileNotFoundError:
            pass
        if self.proc.poll() is not None:
            return None
        os.kill(self.proc.pid, signal.SIGUSR1)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            try:
                with open(self.memstat_path) as f:
                    stats = json.load(f)
                break
            except (FileNotFoundError, ValueError):
                time.sleep(0.05)
        else:
            return None
        peaks = [int(d.get("peak_bytes_in_use", 0))
                 for d in stats.get("devices", []) if isinstance(d, dict)]
        return max(peaks) if peaks else None

    def stop(self) -> int:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        return self.proc.returncode
