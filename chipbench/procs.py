"""Child processes of a run: each in its own process group, logging to a
file, stopped and waited for before the run ends."""

from __future__ import annotations

import os
import signal
import subprocess
import time
import urllib.error
import urllib.request


class RunFailure(Exception):
    """The run cannot produce a result: exit non-zero, print no result."""


class Child:
    def __init__(self, name: str, argv: list, log_dir: str, cwd: str,
                 env: dict | None = None):
        self.name = name
        self.log = os.path.join(log_dir, f"{name}.log")
        self._fh = open(self.log, "w")
        self.proc = subprocess.Popen(
            argv, cwd=cwd, env=env, stdout=self._fh,
            stderr=subprocess.STDOUT, start_new_session=True)

    def alive(self) -> bool:
        return self.proc.poll() is None

    def _group_gone(self) -> bool:
        try:
            os.killpg(self.proc.pid, 0)
        except ProcessLookupError:
            return True
        except PermissionError:
            pass
        return False

    def _signal(self, sig) -> None:
        try:
            os.killpg(self.proc.pid, sig)
        except ProcessLookupError:
            pass

    def stop(self, grace: float = 20.0) -> None:
        """SIGTERM the group, wait, SIGKILL what is left, wait again."""
        if not self._group_gone():
            self._signal(signal.SIGTERM)
        try:
            self.proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            pass
        deadline = time.monotonic() + 2.0
        while not self._group_gone() and time.monotonic() < deadline:
            time.sleep(0.05)
        if not self._group_gone():
            self._signal(signal.SIGKILL)
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        self._fh.close()

    def tail(self, n: int = 40) -> str:
        try:
            with open(self.log, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""


def http(method: str, url: str, body: bytes | None = None,
         timeout: float = 30.0) -> tuple[int, bytes]:
    """(status, body); a connection error is status 0."""
    req = urllib.request.Request(
        url, data=body, method=method,
        headers={"Content-Type": "application/json"} if body else {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()
    except (urllib.error.URLError, OSError):
        return 0, b""


def wait_ok(url: str, child: Child, timeout: float, what: str) -> None:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if not child.alive():
            raise RunFailure(f"{child.name} exited {child.proc.returncode} "
                             f"before {what}\n{child.tail()}")
        status, _ = http("GET", url, timeout=5.0)
        if status == 200:
            return
        time.sleep(0.25)
    raise RunFailure(f"{what} not reached in {timeout:.0f}s\n{child.tail()}")
