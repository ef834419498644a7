"""The system under test as a child process, and a plain HTTP client.

Copied from ``chip_smoke.py`` (the yardstick may not import what later
PRs edit) and cut to what the benchmark needs: start the server through
``benchmark/launcher.py``, find it through its own log, stop it with
SIGTERM. This process never imports jax: the chip belongs to the child.
"""

from __future__ import annotations

import json
import os
import re
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

START_LIMIT_S = 900  # weights + pool + "gateway listening", cold
DRAIN_LIMIT_S = 120


class BenchFailure(Exception):
    """The run cannot produce a result; the message says why."""


def cache_entries(cache_dir: str | None) -> int | None:
    """Files under the compile cache (None: the server named none)."""
    if not cache_dir:
        return None
    return sum(len(files) for _, _, files in os.walk(cache_dir))


def http(method: str, url: str, body: dict | None = None,
         timeout: float = 60, headers: dict | None = None):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read().decode("utf-8", "replace")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8", "replace")


def get_json(url: str, timeout: float = 60) -> dict:
    status, text = http("GET", url, None, timeout)
    if status != 200:
        raise BenchFailure(f"GET {url} -> {status}: {text[:400]}")
    return json.loads(text)


def metric(text: str, name: str, labels: str = "") -> float:
    """Sum of the samples of ``name`` whose label set contains
    ``labels``; 0.0 where the family has no such sample yet (a counter
    that never moved is not exported)."""
    total = 0.0
    for line in text.splitlines():
        m = re.match(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})?\s+(\S+)$", line)
        if m and m.group(1) == name and labels in (m.group(2) or ""):
            total += float(m.group(3))
    return total


class Server:
    """One server child, started through the launcher with the
    configuration's file, found through its log, stopped with SIGTERM."""

    def __init__(self, config_path: str, out_dir: str, extra: list[str]):
        os.makedirs(out_dir, exist_ok=True)
        self.log_path = os.path.join(out_dir, "server.log")
        self.exit_path = os.path.join(out_dir, "server_exit.json")
        if os.path.exists(self.exit_path):
            os.remove(self.exit_path)
        self.cmd = [
            sys.executable, os.path.join(BENCH_DIR, "launcher.py"),
            config_path, "--exit-file", self.exit_path, *extra,
        ]
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env.setdefault("LLM_CONSENSUS_LOG", "info")
        # Every compile writes a cache entry, so "the cache gained no
        # entry during the window" means "nothing compiled in it".
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
        self._log = open(self.log_path, "w")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            self.cmd, cwd=ROOT, env=env,
            stdout=self._log, stderr=subprocess.STDOUT,
        )
        self.base = ""
        self.host = ""
        self.port = 0
        self.cache_dir: str | None = None

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def fail(self, why: str) -> BenchFailure:
        tail = "\n".join(self.log_text().splitlines()[-40:])
        return BenchFailure(f"server: {why}\n--- {self.log_path} ---\n{tail}")

    def wait_listening(self) -> float:
        while True:
            text = self.log_text()
            m = re.search(r"gateway listening on ([\w.\-]+):(\d+)", text)
            if m:
                self.host, self.port = m.group(1), int(m.group(2))
                self.base = f"http://{self.host}:{self.port}"
                c = re.search(r"compile cache: (\S+)", text)
                self.cache_dir = c.group(1) if c else None
                return time.monotonic() - self.t0
            rc = self.proc.poll()
            if rc is not None:
                raise self.fail(f"exited with rc {rc} before listening")
            if time.monotonic() - self.t0 > START_LIMIT_S:
                raise self.fail(f"not listening after {START_LIMIT_S}s")
            time.sleep(0.2)

    def drain(self) -> dict:
        """SIGTERM, wait for rc 0, return what the launcher wrote on its
        way out (the device's memory peak)."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=DRAIN_LIMIT_S)
        except subprocess.TimeoutExpired:
            raise self.fail(f"no exit {DRAIN_LIMIT_S}s after SIGTERM") from None
        if rc != 0:
            raise self.fail(f"rc {rc} after SIGTERM (want a clean drain, 0)")
        try:
            with open(self.exit_path) as f:
                return json.load(f)
        except (OSError, ValueError) as e:
            raise self.fail(f"launcher left no exit file: {e}") from None

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
        self._log.close()
