"""The gateway child as the parent sees it: start, wait for the
listeners, scrape ``/metrics`` and ``/introspect``, talk to the side
thread, drain. The parent never imports jax."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


class BenchFailure(Exception):
    """A phase could not run to its end; the message says what it found."""


def free_ports(n: int) -> list[int]:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


class Gateway:
    def __init__(self, argv: list[str], out_dir: str, control: str = "",
                 fault: str = "", env: dict | None = None,
                 settings: dict | None = None):
        os.makedirs(out_dir, exist_ok=True)
        self.log_path = os.path.join(out_dir, "gateway.log")
        self.mport, self.sport, self.cport = free_ports(3)
        to_child, self._control = os.pipe()
        self._reply, from_child = os.pipe()
        own = [str(to_child), str(from_child)]
        if settings:
            own += ["--settings", json.dumps(settings)]
        if control:
            own += ["--control", control]
        if fault:
            own += ["--fault", fault]
        cmd = [sys.executable, os.path.join(HERE, "gateway_child.py"), *own,
               "--", *argv,
               "-mport", str(self.mport),
               "-sa", f"127.0.0.1:{self.sport}",
               "-ca", f"127.0.0.1:{self.cport}",
               "-profilepath", os.path.join(out_dir, "profiles")]
        self._log = open(self.log_path, "w")
        self.started = time.monotonic()
        self.proc = subprocess.Popen(
            cmd, cwd=REPO, stdout=self._log, stderr=subprocess.STDOUT,
            pass_fds=(to_child, from_child), env=env)
        os.close(to_child)
        os.close(from_child)
        self._control_w = os.fdopen(self._control, "w")
        self._reply_r = os.fdopen(self._reply, "r")

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def wait_listening(self, timeout: float) -> None:
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            if self.log_text().count("listening for") >= 2:
                return
            if self.proc.poll() is not None:
                raise BenchFailure(
                    f"gateway exited {self.proc.returncode} during boot; log "
                    f"tail:\n{self.log_text()[-3000:]}")
            time.sleep(0.2)
        raise BenchFailure(f"gateway not listening after {timeout:.0f}s; log "
                           f"tail:\n{self.log_text()[-3000:]}")

    def _get(self, path: str) -> bytes:
        url = f"http://127.0.0.1:{self.mport}{path}"
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.read()

    def metrics(self) -> dict:
        """``/metrics`` as ``{(sample name, sorted label items): value}``."""
        from prometheus_client.parser import text_string_to_metric_families

        out: dict = {}
        for family in text_string_to_metric_families(
                self._get("/metrics").decode()):
            for s in family.samples:
                out[(s.name, tuple(sorted(s.labels.items())))] = s.value
        return out

    def introspect(self) -> dict:
        return json.loads(self._get("/introspect"))

    def ask(self, command: str) -> dict:
        """One command to the child's side thread, and its answer."""
        self._control_w.write(command + "\n")
        self._control_w.flush()
        line = self._reply_r.readline()
        if not line:
            raise BenchFailure(f"the gateway child did not answer {command!r}")
        return json.loads(line)

    def drain(self, timeout: float = 60.0) -> int:
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            raise BenchFailure(
                f"gateway still alive {timeout:.0f}s after SIGTERM") from None

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        for f in (self._log, self._control_w, self._reply_r):
            try:
                f.close()
            except OSError:
                pass


def total(samples: dict, name: str, **labels) -> float:
    """Sum of every sample ``name`` whose labels include ``labels``."""
    want = set(labels.items())
    return sum(v for (n, lab), v in samples.items()
               if n == name and want.issubset(lab))


def delta(now: dict, base: dict) -> dict:
    return {k: v - base.get(k, 0.0) for k, v in now.items()}
