"""Multi-host serving: 2-process CPU group must be token-identical to a
single process (VERDICT r3 #2).

The leader runs the real LLMEngine with its runner wrapped in
MirroredRunner; the follower replays the authenticated step-plan
broadcast against its own shard of the 4-device global mesh
(2 processes x 2 virtual CPU devices, jax.distributed over localhost —
the same multi-controller runtime a GKE multi-host TPU slice uses).

Also pins the control-plane security contract: no secret -> refuse; bad
secret -> connection rejected; forbidden pickle types -> rejected.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _env(process_id: int, coord_port: int, control_port: int,
         devices: int = 2, secret: str = "test-secret") -> dict:
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "PSTPU_COORDINATOR": f"127.0.0.1:{coord_port}",
        "PSTPU_NUM_PROCESSES": "2",
        "PSTPU_PROCESS_ID": str(process_id),
        "PSTPU_CONTROL_PORT": str(control_port),
        "PSTPU_CONTROL_SECRET": secret,
    })
    return env


def _single_process_reference() -> dict:
    """Same engine config and prompts, one process, 4 local devices."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    code = (
        "import jax, json; jax.config.update('jax_platforms', 'cpu'); "
        "from production_stack_tpu.testing import multihost_harness as h; "
        "from production_stack_tpu.engine.engine import LLMEngine; "
        "cfg = h.engine_config(); "
        "eng = LLMEngine(cfg, num_blocks=cfg.cache.num_blocks); "
        "print('TOKENS ' + json.dumps(h.generate_greedy(eng)))"
    )
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          timeout=420, stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True)
    assert proc.returncode == 0, proc.stdout[-3000:]
    return _tokens_from(proc.stdout)


def _tokens_from(stdout: str) -> dict:
    for line in stdout.splitlines():
        if line.startswith("TOKENS "):
            return json.loads(line[len("TOKENS "):])
    raise AssertionError(f"no TOKENS line in output:\n{stdout[-3000:]}")


@pytest.mark.slow
def test_two_process_group_token_identical():
    coord, control = _free_port(), _free_port()
    cmd = [sys.executable, "-m",
           "production_stack_tpu.testing.multihost_harness"]
    leader = subprocess.Popen(cmd, env=_env(0, coord, control), cwd=REPO,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
    follower = subprocess.Popen(cmd, env=_env(1, coord, control), cwd=REPO,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
    try:
        l_out, _ = leader.communicate(timeout=420)
        f_out, _ = follower.communicate(timeout=60)
    finally:
        for p in (leader, follower):
            if p.poll() is None:
                p.kill()
                p.wait()
    assert leader.returncode == 0, f"leader:\n{l_out[-3000:]}"
    assert follower.returncode == 0, f"follower:\n{f_out[-3000:]}"
    assert "FOLLOWER DONE" in f_out
    multi = _tokens_from(l_out)
    single = _single_process_reference()
    assert multi == single, (multi, single)


def test_control_plane_refuses_without_secret():
    from production_stack_tpu.engine.multihost import control_secret

    old = os.environ.pop("PSTPU_CONTROL_SECRET", None)
    try:
        with pytest.raises(ValueError, match="PSTPU_CONTROL_SECRET"):
            control_secret()
    finally:
        if old is not None:
            os.environ["PSTPU_CONTROL_SECRET"] = old


def test_leader_rejects_wrong_secret_and_accepts_right_one():
    import threading

    from production_stack_tpu.engine.multihost import (
        _CONFIRM,
        _HELLO,
        _NONCE_BYTES,
        LeaderBroadcaster,
        _recv_frame,
        _send_frame,
        _session_key,
    )

    port = _free_port()
    bcast = LeaderBroadcaster(port, num_followers=1, secret=b"right",
                              bind_host="127.0.0.1", accept_timeout=10.0)
    t = threading.Thread(target=bcast.wait_for_followers, daemon=True)
    t.start()
    try:
        # wrong secret: frame fails HMAC, connection dropped
        bad = socket.create_connection(("127.0.0.1", port), timeout=5)
        _send_frame(bad, _HELLO + b"\x00" * _NONCE_BYTES, b"wrong")
        assert bad.recv(1) == b""  # leader closed on us
        bad.close()
        # right secret: accepted, nonce exchanged, receives a broadcast
        # authenticated under the derived SESSION key (not the base
        # secret — r4 advisor: cross-session replay)
        good = socket.create_connection(("127.0.0.1", port), timeout=5)
        f_nonce = b"\x01" * _NONCE_BYTES
        _send_frame(good, _HELLO + f_nonce, b"right")
        good.settimeout(5)
        l_nonce = _recv_frame(good, b"right")
        assert l_nonce is not None and len(l_nonce) == _NONCE_BYTES
        key = _session_key(b"right", f_nonce, l_nonce)
        _send_frame(good, _CONFIRM, key)
        t.join(timeout=10)
        assert not t.is_alive()
        bcast.broadcast("drop_kv", (), {})
        payload = _recv_frame(good, key)
        assert payload is not None
        # the same frame does NOT authenticate under the base secret or
        # under a different session's key — recorded streams are dead
        good.close()
    finally:
        bcast.close()


def test_broadcast_frames_do_not_authenticate_under_base_secret():
    """Cross-session replay pin (r4 advisor): step-plan frames are MAC'd
    with the per-session key, so a stream recorded in one session fails
    HMAC at a follower whose handshake produced a different key."""
    import threading

    from production_stack_tpu.engine.multihost import (
        _CONFIRM,
        _HELLO,
        _NONCE_BYTES,
        LeaderBroadcaster,
        _recv_frame,
        _send_frame,
        _session_key,
    )

    port = _free_port()
    bcast = LeaderBroadcaster(port, num_followers=1, secret=b"s",
                              bind_host="127.0.0.1", accept_timeout=10.0)
    t = threading.Thread(target=bcast.wait_for_followers, daemon=True)
    t.start()
    conn = socket.create_connection(("127.0.0.1", port), timeout=5)
    try:
        f_nonce = b"\x02" * _NONCE_BYTES
        _send_frame(conn, _HELLO + f_nonce, b"s")
        conn.settimeout(5)
        l_nonce = _recv_frame(conn, b"s")
        key = _session_key(b"s", f_nonce, l_nonce)
        _send_frame(conn, _CONFIRM, key)
        t.join(timeout=10)
        bcast.broadcast("drop_kv", (), {})
        with pytest.raises(ConnectionError, match="HMAC"):
            _recv_frame(conn, b"s")  # base secret must NOT verify
        # a fresh session derives a different key for the same secret
        other = _session_key(b"s", b"\x03" * _NONCE_BYTES, l_nonce)
        assert other != key
    finally:
        conn.close()
        bcast.close()


def test_leader_rejects_replayed_hello_without_session_confirm():
    """A recorded HELLO replayed at a fresh leader must NOT be counted
    as a follower: the attacker can't produce the session-key confirm
    frame (needs the secret to derive the key)."""
    import threading

    from production_stack_tpu.engine.multihost import (
        _HELLO,
        _NONCE_BYTES,
        LeaderBroadcaster,
        _recv_frame,
        _send_frame,
    )

    port = _free_port()
    bcast = LeaderBroadcaster(port, num_followers=1, secret=b"s",
                              bind_host="127.0.0.1", accept_timeout=10.0)
    t = threading.Thread(target=bcast.wait_for_followers, daemon=True)
    t.start()
    try:
        replayer = socket.create_connection(("127.0.0.1", port), timeout=5)
        # the recorded frame authenticates (attacker has the bytes, not
        # the secret) ...
        _send_frame(replayer, _HELLO + b"\x07" * _NONCE_BYTES, b"s")
        replayer.settimeout(5)
        assert _recv_frame(replayer, b"s") is not None  # leader's nonce
        # ... but the attacker cannot confirm: wrong-key frame -> dropped
        _send_frame(replayer, b"garbage-confirm", b"not-the-secret")
        assert replayer.recv(1) == b""  # leader closed on us
        replayer.close()
        assert t.is_alive()  # never counted toward num_followers
    finally:
        bcast.close()
        t.join(timeout=1)


def test_follower_replay_handles_ndarray_tokens_dev():
    """A host np.ndarray tokens_dev goes over the wire as it is;
    the sentinel check must not trip numpy's elementwise == (ambiguous
    truth ValueError — r4 advisor)."""
    import numpy as np

    from production_stack_tpu.engine.multihost import FollowerReplayer

    calls = []

    class Runner:
        def prepare_decode(self, *a, **kw):
            return lambda tok=None: calls.append(tok) or ("sampled", "next")

    rep = FollowerReplayer(Runner())
    arr = np.arange(4, dtype=np.int32)
    rep.replay("prepare_decode", (arr,), {"tokens_dev": True})
    rep.replay("launch_decode", (), {"tokens_dev": arr})
    assert calls[0] is arr  # passed through, no ValueError


def test_a_prepared_decode_is_mirrored_in_both_halves_and_dropped_alike():
    """The engine prepares a decode dispatch while the one before runs and
    launches it at the landing, or drops it for an arrival. Committing
    the packed inputs is a step the whole group takes together, so the
    followers prepare when the leader does, launch when it does, with
    their own copy of the device's tokens for the sentinel, and a
    prepared step the leader dropped is never launched by them."""
    import numpy as np

    from production_stack_tpu.engine.multihost import (
        FollowerReplayer,
        MirroredRunner,
    )

    sent, ran = [], []

    class Bcast:
        def broadcast(self, method, args, kwargs):
            sent.append((method, args, dict(kwargs)))

    class Runner:
        def __init__(self, who):
            self.who, self.n = who, 0

        def prepare_decode(self, *a, tokens_dev=False, **kw):
            self.n += 1
            n = self.n
            return lambda tok=None: ran.append((self.who, n, tok)) or (
                "sampled", f"next-{n}-on-{self.who}")

    leader = MirroredRunner(Runner("leader"), Bcast())
    arr = np.arange(4, dtype=np.int32)
    first = leader.prepare_decode(arr, greedy_only=True)
    assert [m for m, _, _ in sent] == ["prepare_decode"]
    tok = first()[1]
    leader.prepare_decode(arr, tokens_dev=True)  # dropped at the landing
    second = leader.prepare_decode(arr, tokens_dev=True, greedy_only=True)
    second(tok)
    assert [(m, kw.get("tokens_dev")) for m, _, kw in sent] == [
        ("prepare_decode", None), ("launch_decode", None),
        ("prepare_decode", True), ("prepare_decode", True),
        ("launch_decode", "__pstpu_chained_next_tok__")]
    follower = FollowerReplayer(Runner("follower"))
    for method, args, kwargs in sent:
        follower.replay(method, args, kwargs)
    assert ran == [("leader", 1, None), ("leader", 3, "next-1-on-leader"),
                   ("follower", 1, None),
                   ("follower", 3, "next-1-on-follower")]


def test_restricted_unpickler_blocks_forbidden_types():
    import pickle

    import numpy as np

    from production_stack_tpu.engine.multihost import _dumps, _loads

    # step-plan shapes round-trip
    seq, method, args, kwargs = _loads(_dumps(
        (1, "decode_multi", (np.arange(4, dtype=np.int32),),
         {"fetch": False, "tokens_dev": "__pstpu_chained_next_tok__"})
    ))
    assert method == "decode_multi" and kwargs["fetch"] is False
    assert args[0].dtype == np.int32

    # arbitrary callables do NOT (the r3 advisor's RCE vector)
    evil = pickle.dumps(eval)
    with pytest.raises(pickle.UnpicklingError, match="forbidden"):
        _loads(evil)

    class Payload:
        def __reduce__(self):
            return (os.system, ("true",))

    with pytest.raises(pickle.UnpicklingError, match="forbidden"):
        _loads(pickle.dumps(Payload()))


def test_replay_rejected():
    """A replayed (non-increasing seq) frame must hard-fail the follower
    loop's ordering check."""
    from production_stack_tpu.engine.multihost import _dumps, _loads

    frame1 = _dumps((5, "drop_kv", (), {}))
    seq1, *_ = _loads(frame1)
    seq2, *_ = _loads(_dumps((4, "drop_kv", (), {})))
    assert seq1 == 5 and seq2 == 4  # follower_loop enforces seq > last


def test_frame_size_cap_rejects_unauthenticated_giant_header():
    """The length header arrives before authentication: a huge value must
    be rejected up front, not buffered (r4 review)."""
    import struct
    import threading

    from production_stack_tpu.engine.multihost import (
        _LEN,
        _recv_frame,
    )

    a, b = socket.socketpair()
    try:
        a.sendall(_LEN.pack(1 << 40))  # 1 TiB claim, no body needed
        b.settimeout(5)
        with pytest.raises(ConnectionError, match="cap"):
            _recv_frame(b, b"secret")
    finally:
        a.close()
        b.close()


@pytest.mark.slow
def test_real_server_two_process_group_serves_completions():
    """The ACTUAL server binary in both roles (caught a follower-path
    import bug the harness test couldn't): leader serves /v1/completions,
    follower reports follower status on /health."""
    import urllib.request

    coord, control, lport, fport = (_free_port() for _ in range(4))
    base = [sys.executable, "-m", "production_stack_tpu.engine.server",
            "--model", "tiny-llama",
            "--num-blocks", "128", "--max-num-seqs", "4",
            "--tensor-parallel-size", "2", "--data-parallel-size", "2"]
    follower = subprocess.Popen(
        base + ["--port", str(fport)],
        env=_env(1, coord, control), cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    leader = subprocess.Popen(
        base + ["--port", str(lport), "--skip-warmup"],
        env=_env(0, coord, control), cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            for p, out in ((leader, "leader"), (follower, "follower")):
                if p.poll() is not None:
                    raise AssertionError(
                        f"{out} died: {p.stdout.read()[-3000:]}")
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{lport}/health", timeout=2
                ) as r:
                    if r.status == 200:
                        break
            except Exception:
                time.sleep(1.0)
        req = urllib.request.Request(
            f"http://127.0.0.1:{lport}/v1/completions",
            data=json.dumps({"model": "tiny-llama", "prompt": "hi there",
                             "max_tokens": 4, "temperature": 0}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=180) as r:
            body = json.loads(r.read())
        assert body["usage"]["completion_tokens"] == 4
        with urllib.request.urlopen(
            f"http://127.0.0.1:{fport}/health", timeout=5
        ) as r:
            f_health = json.loads(r.read())
        assert f_health == {"status": "follower", "process_id": 1}
    finally:
        for p in (leader, follower):
            if p.poll() is None:
                p.terminate()
                try:
                    p.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()
