"""Leader→follower step-plan broadcast for multi-host serving.

In JAX's multi-controller model every process must issue the SAME device
programs in the SAME order. Serving is asymmetric — only one process sees
HTTP requests and runs the scheduler — so the leader (process 0) mirrors
every ModelRunner call to the followers over a tiny authenticated
length-prefixed frame protocol, and followers replay the identical call
against their local runner shard. All runner inputs are host numpy arrays
that are REPLICATED by construction (token ids, block tables, sampling
params), so replaying the call on each process feeds jit the same global
values; the sharded params/KV supply each process's local shards.

This replaces the reference's Ray object/RPC control plane for
cross-node pipeline parallelism (reference:
helm/templates/ray-cluster.yaml:332-335 — Ray head/worker groups;
SURVEY.md §2.9 PP row). Data-plane collectives never touch this channel:
they ride ICI/DCN inside XLA programs. The broadcast carries only step
plans — a few KB per step.

Security (r3+r4 advisors): the handshake exchanges fresh nonces (HELLO
carries the follower's, the leader answers with its own) and every
subsequent frame is authenticated with HMAC-SHA256 under the derived
per-session key (shared secret ``PSTPU_CONTROL_SECRET``, injected by
the chart from a Kubernetes Secret), payloads are deserialized by a
restricted unpickler that admits only numpy arrays / scalars / builtin
containers / ``TokenFsm``, a per-connection monotonically increasing
sequence number rejects replayed frames within a session, and the
session key rejects frames recorded from any OTHER session. Multi-host
serving REFUSES to start without a secret.

Device-resident tokens: the engine prepares a decode dispatch while the
one before runs (``prepare_decode``) and launches it on that one's
un-fetched ``next_tok`` device array (engine.py _run_decode), or drops
it unlaunched. The leader mirrors both halves as it runs them; device
arrays can't cross the wire, so a sentinel stands for the tokens of a
launch and each follower substitutes its OWN cached ``next_tok`` from
its replay of the launch before (identical by the SPMD contract).
"""

from __future__ import annotations

import hashlib
import hmac
import io
import logging
import os
import pickle
import socket
import struct
import threading
import time
from typing import Optional

logger = logging.getLogger(__name__)

_LEN = struct.Struct("!Q")
_MAC_BYTES = 32  # HMAC-SHA256
_HELLO = b"pstpu-multihost-v2"
_NONCE_BYTES = 16
# frame-size ceiling: the length header arrives BEFORE authentication, so
# an unauthenticated peer must not be able to make us buffer unbounded
# data. Step plans are KBs; KV-import frames reach tens of MB — the cap
# leaves headroom (overridable for exotic block sizes).
_MAX_FRAME = int(os.environ.get("PSTPU_CONTROL_MAX_FRAME",
                                str(256 * 1024 * 1024)))
_MAX_HELLO = 1024  # pre-auth handshake frames are tiny
# sentinel for a device-resident arg the follower reconstructs locally
_CHAINED_NEXT_TOK = "__pstpu_chained_next_tok__"
# third handshake frame, MAC'd under the DERIVED session key: proves the
# follower computed it (knows the secret AND saw this session's nonces).
# Without it, a recorded HELLO replayed at a fresh leader would be
# counted as a live follower and receive step-plan payloads.
_CONFIRM = b"pstpu-mh-confirm"

# methods the leader mirrors: every runner entry point that issues device
# work. Host-only accessors (num_blocks, tp, ...) are not mirrored.
# ``sample``/``decode`` are NOT mirrored: their hot-path callers pass
# device arrays (unpicklable) and the engine never calls them — the fused
# decode step is the decode path (r3 advisor), mirrored in its two halves
# (``MirroredRunner.prepare_decode``).
MIRRORED_METHODS = (
    "ragged_step",
    "set_count_row", "register_grammar", "register_lora",
    "unregister_lora", "export_blocks", "export_blocks_range",
    "import_blocks", "import_blocks_range", "drop_kv", "restore_kv",
    "drop_params", "restore_params", "pooled_embed", "sequence_logprobs",
    "prompt_logprobs",
)


def control_secret() -> bytes:
    """The shared control-plane secret (PSTPU_CONTROL_SECRET).

    Raises when unset: an unauthenticated step-plan channel would hand
    arbitrary deserialization to any peer that can reach the port."""
    s = os.environ.get("PSTPU_CONTROL_SECRET", "")
    if not s:
        raise ValueError(
            "multi-host serving needs PSTPU_CONTROL_SECRET (shared "
            "control-plane secret; the chart injects it from a Kubernetes "
            "Secret — helm/templates/secrets.yaml)"
        )
    return s.encode()


class _RestrictedUnpickler(pickle.Unpickler):
    """Admit only the types step plans actually carry."""

    _ALLOWED = {
        ("builtins", "tuple"), ("builtins", "list"), ("builtins", "dict"),
        ("builtins", "set"), ("builtins", "frozenset"),
        ("builtins", "bytes"), ("builtins", "bytearray"),
        ("builtins", "str"), ("builtins", "int"), ("builtins", "float"),
        ("builtins", "bool"), ("builtins", "complex"),
        ("builtins", "slice"), ("builtins", "NoneType"),
        ("numpy", "ndarray"), ("numpy", "dtype"),
        ("numpy.core.multiarray", "_reconstruct"),
        ("numpy._core.multiarray", "_reconstruct"),
        ("numpy.core.multiarray", "scalar"),
        ("numpy._core.multiarray", "scalar"),
        ("numpy.core.numeric", "_frombuffer"),
        ("numpy._core.numeric", "_frombuffer"),
        ("production_stack_tpu.engine.grammar", "TokenFsm"),
    }

    def find_class(self, module, name):
        # explicit allowlist ONLY — a module-wide numpy wildcard would
        # admit callables like np.load(allow_pickle=True), re-opening the
        # unrestricted-pickle door this class exists to close
        if (module, name) in self._ALLOWED:
            return super().find_class(module, name)
        raise pickle.UnpicklingError(
            f"step-plan payload requested forbidden type {module}.{name}"
        )


def _session_key(secret: bytes, follower_nonce: bytes,
                 leader_nonce: bytes) -> bytes:
    """Per-session frame-MAC key (r4 advisor: replay across sessions).

    BOTH sides contribute a nonce: a leader-only nonce would still let an
    on-path attacker replay a recorded leader stream (nonce frame
    included) at a freshly started follower. Mixing the follower's fresh
    nonce in means recorded frames can never authenticate to a new
    session in either direction."""
    return hmac.new(secret, b"pstpu-mh-skey|" + follower_nonce +
                    leader_nonce, hashlib.sha256).digest()


def _dumps(obj) -> bytes:
    return pickle.dumps(obj, protocol=5)


def _loads(data: bytes):
    return _RestrictedUnpickler(io.BytesIO(data)).load()


def _send_frame(sock: socket.socket, payload: bytes, secret: bytes) -> None:
    mac = hmac.new(secret, payload, hashlib.sha256).digest()
    sock.sendall(_LEN.pack(len(payload)) + mac + payload)


def _recv_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    buf = io.BytesIO()
    got = 0
    while got < n:
        chunk = sock.recv(min(1 << 20, n - got))
        if not chunk:
            return None
        buf.write(chunk)
        got += len(chunk)
    return buf.getvalue()


def _recv_frame(sock: socket.socket, secret: bytes,
                max_len: int = _MAX_FRAME) -> Optional[bytes]:
    hdr = _recv_exact(sock, _LEN.size)
    if hdr is None:
        return None
    (n,) = _LEN.unpack(hdr)
    if n > max_len:
        raise ConnectionError(
            f"control-plane frame of {n} bytes exceeds the {max_len}-byte "
            "cap (unauthenticated length header — refusing to buffer)"
        )
    mac = _recv_exact(sock, _MAC_BYTES)
    if mac is None:
        return None
    payload = _recv_exact(sock, n)
    if payload is None:
        return None
    want = hmac.new(secret, payload, hashlib.sha256).digest()
    if not hmac.compare_digest(mac, want):
        raise ConnectionError("control-plane frame failed HMAC check")
    return payload


class LeaderBroadcaster:
    """Accepts one authenticated connection per follower, then fans out
    step plans with a per-connection sequence number."""

    def __init__(self, port: int, num_followers: int,
                 secret: Optional[bytes] = None,
                 bind_host: Optional[str] = None,
                 accept_timeout: float = 300.0):
        self.secret = secret if secret is not None else control_secret()
        self.num_followers = num_followers
        bind = (bind_host if bind_host is not None
                else os.environ.get("PSTPU_CONTROL_BIND", "0.0.0.0"))
        self.server = socket.create_server((bind, port), backlog=16)
        self.server.settimeout(accept_timeout)
        # (socket, per-session frame-MAC key) — see _session_key
        self.conns: list[tuple[socket.socket, bytes]] = []  # guarded-by: lock
        # stackcheck: disable=lock-across-await — threading.Lock (not
        # asyncio) is correct here: broadcast() runs on the engine's sync
        # worker thread (no event loop), and the critical section is pure
        # socket sendall + counter bump with no await reachable while held
        self.lock = threading.Lock()
        self.seq = 0  # guarded-by: lock

    def wait_for_followers(self) -> None:
        while len(self.conns) < self.num_followers:
            conn, addr = self.server.accept()
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            # authenticate before counting: the follower's first frame
            # must be HELLO || follower-nonce under the shared secret;
            # we answer with our nonce and both sides derive the
            # session key (recorded sessions can't replay — r4 advisor)
            try:
                conn.settimeout(30.0)
                hello = _recv_frame(conn, self.secret, max_len=_MAX_HELLO)
            except (ConnectionError, OSError) as e:
                logger.warning("rejecting connection from %s: %s", addr, e)
                conn.close()
                continue
            if (hello is None
                    or len(hello) != len(_HELLO) + _NONCE_BYTES
                    or not hmac.compare_digest(hello[:len(_HELLO)], _HELLO)):
                logger.warning("rejecting connection from %s: bad hello",
                               addr)
                conn.close()
                continue
            f_nonce = hello[len(_HELLO):]
            l_nonce = os.urandom(_NONCE_BYTES)
            key = _session_key(self.secret, f_nonce, l_nonce)
            try:
                _send_frame(conn, l_nonce, self.secret)
                # the confirm frame verifies under the session key ONLY
                # if the peer derived it — a replayed HELLO can't
                confirm = _recv_frame(conn, key, max_len=_MAX_HELLO)
            except (ConnectionError, OSError) as e:
                logger.warning("handshake to %s failed: %s", addr, e)
                conn.close()
                continue
            if confirm != _CONFIRM:
                logger.warning("rejecting connection from %s: bad session "
                               "confirm (replayed HELLO?)", addr)
                conn.close()
                continue
            conn.settimeout(None)
            logger.info("follower connected from %s (%d/%d)", addr,
                        len(self.conns) + 1, self.num_followers)
            # under the lock: broadcast() iterates conns under it from
            # the worker thread, and a list.append racing that iteration
            # is exactly the torn read the guarded-by annotation forbids
            with self.lock:
                self.conns.append((conn, key))

    def broadcast(self, method: str, args: tuple, kwargs: dict) -> None:
        with self.lock:
            self.seq += 1
            payload = _dumps((self.seq, method, args, kwargs))
            for conn, key in self.conns:
                _send_frame(conn, payload, key)

    def close(self) -> None:
        try:
            self.broadcast("_shutdown", (), {})
        except Exception:
            logger.debug("shutdown broadcast to followers failed",
                         exc_info=True)
        for conn, _key in self.conns:
            try:
                conn.close()
            except Exception:
                logger.debug("follower socket close failed", exc_info=True)
        self.server.close()


class MirroredRunner:
    """Leader-side runner wrapper: broadcast the call, then run it locally.

    The broadcast happens BEFORE the local dispatch so followers can
    overlap deserialization with the leader's own host work; ordering per
    follower is the TCP stream order, which equals the leader's program
    order — the SPMD contract."""

    def __init__(self, inner, broadcaster: LeaderBroadcaster):
        self._inner = inner
        self._bcast = broadcaster
        for name in MIRRORED_METHODS:
            if hasattr(inner, name):
                setattr(self, name, self._make_mirror(name))

    def _make_mirror(self, name: str):
        fn = getattr(self._inner, name)

        def mirrored(*args, **kwargs):
            self._bcast.broadcast(name, args, kwargs)
            return fn(*args, **kwargs)

        mirrored.__name__ = name
        return mirrored

    def prepare_decode(self, *args, **kwargs):
        """Both halves are mirrored, each when the leader runs it:
        committing the packed inputs to a mesh that spans processes is
        itself a step every process takes together. A prepared step that
        the leader drops is dropped by the followers' next one."""
        self._bcast.broadcast("prepare_decode", args, kwargs)
        launch = self._inner.prepare_decode(*args, **kwargs)

        def mirrored(device_tokens=None):
            self._bcast.broadcast("launch_decode", (), {
                "tokens_dev": (None if device_tokens is None
                               else _CHAINED_NEXT_TOK)})
            return launch(device_tokens)

        return mirrored

    def __getattr__(self, name):  # host-only attrs pass straight through
        return getattr(self._inner, name)


class FollowerReplayer:
    """Replays mirrored calls against the local runner shard.

    Keeps the launch of the last ``prepare_decode`` replayed and the
    device-resident ``next_tok`` of the last launch, so the leader's
    launches on device tokens (tokens_dev sentinel) resolve to this
    process's own copy — identical across processes by the SPMD
    contract. Other outputs are discarded: with the runner's
    multihost replicated out_shardings every result is addressable on the
    leader, and followers only need to keep the SPMD program order."""

    def __init__(self, runner):
        self.runner = runner
        self._launch = self._next_tok = None

    def replay(self, method: str, args: tuple, kwargs: dict) -> None:
        if method != "launch_decode":
            result = getattr(self.runner, method)(*args, **kwargs)
            if method == "prepare_decode":
                self._launch = result
            return
        # isinstance gate first: a host np.ndarray tokens_dev goes
        # through verbatim, and ndarray == str is an elementwise
        # comparison (ambiguous-truth ValueError under numpy>=1.25) —
        # r4 advisor
        td = kwargs.get("tokens_dev")
        if isinstance(td, str) and td == _CHAINED_NEXT_TOK:
            if self._next_tok is None:
                raise RuntimeError(
                    "a decode launch on device tokens replayed without a "
                    "cached next_tok — the SPMD order is broken"
                )
            td = self._next_tok
        # (sampled, next_tok, ...) device arrays, un-fetched
        self._next_tok = self._launch(td)[1]


def follower_loop(runner, leader_host: str, control_port: int,
                  secret: Optional[bytes] = None,
                  connect_timeout: float = 300.0) -> None:
    """Replay the leader's runner calls against the local shard forever."""
    secret = secret if secret is not None else control_secret()
    deadline = time.monotonic() + connect_timeout
    sock = None
    while True:
        try:
            sock = socket.create_connection((leader_host, control_port),
                                            timeout=5.0)
            break
        except OSError:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"could not reach leader at {leader_host}:{control_port}"
                )
            # stackcheck: disable=async-blocking — follower bootstrap runs
            # on a dedicated sync thread before any event loop exists; a
            # 0.5 s connect-retry backoff here blocks nothing but itself
            time.sleep(0.5)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    f_nonce = os.urandom(_NONCE_BYTES)
    sock.settimeout(30.0)
    _send_frame(sock, _HELLO + f_nonce, secret)
    l_nonce = _recv_frame(sock, secret, max_len=_MAX_HELLO)
    if l_nonce is None or len(l_nonce) != _NONCE_BYTES:
        raise ConnectionError("leader handshake returned no session nonce")
    key = _session_key(secret, f_nonce, l_nonce)
    _send_frame(sock, _CONFIRM, key)  # prove we derived the session key
    sock.settimeout(None)
    logger.info("connected to leader %s:%d", leader_host, control_port)
    replayer = FollowerReplayer(runner)
    last_seq = 0
    while True:
        payload = _recv_frame(sock, key)
        if payload is None:
            logger.info("leader closed the control channel; exiting")
            return
        seq, method, args, kwargs = _loads(payload)
        if seq <= last_seq:
            raise ConnectionError(
                f"control-plane frame replayed or reordered "
                f"(seq {seq} after {last_seq})"
            )
        last_seq = seq
        if method == "_shutdown":
            logger.info("shutdown from leader")
            return
        try:
            replayer.replay(method, args, kwargs)
        except Exception:
            logger.exception("follower replay of %s failed — the SPMD "
                             "order is broken; exiting", method)
            raise
