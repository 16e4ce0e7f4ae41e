"""Engine-server process lifecycle.

A chip belongs to one process at a time, so a server that lingers after
SIGTERM — or leaves a child behind — keeps every later process off it.
These tests run the REAL server process (CPU backend) and assert SIGTERM
terminates it cleanly both while serving and during startup, and that a
warm-up that raises takes the process down instead of reporting ready.

Reference behavior being mirrored: vLLM engines exit on SIGTERM so K8s
`terminationGracePeriodSeconds` works (the chart's probes assume it);
reference chart: helm/templates/deployment-vllm-multi.yaml probe blocks.
"""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_server(port: int) -> subprocess.Popen:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [sys.executable, "-m", "production_stack_tpu.engine.server",
         "--model", "tiny-llama", "--port", str(port), "--skip-warmup",
         "--num-blocks", "256", "--max-num-seqs", "4"],
        env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


def _wait_healthy(port: int, proc: subprocess.Popen, timeout: float = 180.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            out = proc.stdout.read() if proc.stdout else ""
            raise AssertionError(f"server died during startup:\n{out}")
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/health", timeout=1
            ) as resp:
                if resp.status == 200:
                    return
        except Exception:
            time.sleep(0.2)
    raise AssertionError("server never became healthy")


def test_sigterm_while_serving_exits_promptly():
    port = _free_port()
    proc = _spawn_server(port)
    try:
        _wait_healthy(port, proc)
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=30)
        # aiohttp's GracefulExit path exits 0 after on_cleanup ran
        assert rc == 0, f"expected clean exit, got rc={rc}"
        # no orphaned child still holds the port. SO_REUSEADDR lets the
        # probe bind over kernel TIME_WAIT remnants of the health-check
        # connections (the server may win the close race and leave one),
        # but still fails EADDRINUSE against a live LISTEN socket.
        s = socket.socket()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        try:
            s.bind(("127.0.0.1", port))
        finally:
            s.close()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


import pytest


@pytest.mark.parametrize("run", range(5))
def test_no_orphan_children_after_exit(run):
    """No descendant process survives the server (chip-hygiene gate).

    An orphaned child holding a JAX backend keeps the chip from the next
    process. Looped for flake-free repetition: descendants are
    snapshotted via psutil BEFORE SIGTERM, and every one of them must be
    gone after the parent exits. Determinism: the snapshot is taken
    after /health returns, so no startup race; psutil.Process identity
    (pid+create_time) can't confuse pid reuse."""
    import psutil

    port = _free_port()
    proc = _spawn_server(port)
    try:
        _wait_healthy(port, proc)
        parent = psutil.Process(proc.pid)
        children = parent.children(recursive=True)
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=30)
        assert rc == 0, f"expected clean exit, got rc={rc}"
        gone, alive = psutil.wait_procs(children, timeout=10)
        assert not alive, (
            f"orphaned children survived server exit (run {run}): "
            f"{[(p.pid, ' '.join(p.cmdline())[:80]) for p in alive]}"
        )
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def test_sigterm_during_startup_exits_promptly():
    """The pre-loop handler covers signals before the aiohttp loop runs."""
    port = _free_port()
    proc = _spawn_server(port)
    try:
        time.sleep(1.0)  # mid-construction: engine build / backend init
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
        # before main() installs the handler the default disposition
        # (-SIGTERM) applies — equally fine, nothing is leaked that early
        assert rc in (0, 1, 128 + signal.SIGTERM,
                      -signal.SIGTERM), f"rc={rc}"
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def test_failed_warmup_never_ready_and_exits_nonzero():
    """A warm-up that raises (a kernel the compiler refuses would) must
    not turn the engine "ready": /ready never answers 200 and the process
    exits non-zero, saying why."""
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    code = (
        "from production_stack_tpu.engine import engine, server\n"
        "def boom(self):\n"
        "    raise RuntimeError('mosaic refused the kernel')\n"
        "engine.LLMEngine.warmup = boom\n"
        f"server.main(['--model', 'tiny-llama', '--port', '{port}', "
        "'--num-blocks', '256', '--max-num-seqs', '4'])\n"
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", code], env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        seen_ready = False
        deadline = time.monotonic() + 120
        while proc.poll() is None and time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/ready", timeout=1
                ) as resp:
                    seen_ready = seen_ready or resp.status == 200
            except Exception:
                pass  # refused / 503: not ready, as it must stay
            time.sleep(0.05)
        rc = proc.wait(timeout=30)
        out = proc.stdout.read()
        assert not seen_ready, "/ready answered 200 after a failed warm-up"
        assert rc not in (0, None), f"rc={rc}\n{out}"
        assert "mosaic refused the kernel" in out
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def _run_py(code: str, **env_over) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_over)
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)


def test_compile_cache_is_placed_from_outside_or_fixed(tmp_path):
    """The persistent compile cache lives where JAX_COMPILATION_CACHE_DIR
    says (nothing is set in code then), else at ONE fixed in-checkout path
    — the same from every process, because the path is part of the key."""
    code = (
        "from production_stack_tpu.compile_cache import "
        "configure_compile_cache as c\n"
        "import jax\n"
        "print(c()); print(jax.config.jax_compilation_cache_dir)\n"
    )
    a, b = _run_py(code), _run_py(code)
    assert a.returncode == 0 and b.returncode == 0, a.stderr + b.stderr
    fixed = os.path.join(REPO, ".jax_compile_cache")
    assert a.stdout.split() == [fixed, fixed]
    assert b.stdout == a.stdout
    placed = _run_py(code, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert placed.stdout.split() == [str(tmp_path), str(tmp_path)]


def test_chip_smoke_refuses_cpu():
    """chip_smoke.py must not pass without an accelerator: non-zero exit,
    the platform named, and no result line on stdout."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], env=env,
        cwd=REPO, capture_output=True, text=True, timeout=180)
    assert proc.returncode != 0
    assert "platform is 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout
