"""Integration tests for the compiled C++ gateway endpoint picker
(native/gateway_picker) — the TPU stack's equivalent of the reference's Go
EPP plugins (src/gateway_inference_extension/*_picker.go), driven over HTTP
as kgateway/Envoy would."""

import json
import socket
import subprocess
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
PICKER_DIR = ROOT / "native" / "gateway_picker"


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(scope="module")
def binary():
    subprocess.run(["make", "-C", str(PICKER_DIR)], check=True,
                   capture_output=True)
    return PICKER_DIR / "picker_server"


def start_picker(binary, *args):
    port = free_port()
    proc = subprocess.Popen(
        [str(binary), "--port", str(port), *args],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    for _ in range(100):
        try:
            req("GET", port, "/healthz")
            break
        except OSError:
            time.sleep(0.05)
    else:
        proc.kill()
        raise RuntimeError("picker did not come up")
    return proc, port


def req(method, port, path, body=None):
    r = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode() if body is not None else None,
        method=method,
    )
    with urllib.request.urlopen(r, timeout=5) as resp:
        return resp.status, dict(resp.headers), resp.read().decode()


def pick(port, prompt, endpoints, model="m"):
    _, headers, body = req("POST", port, "/pick",
                           {"model": model, "prompt": prompt,
                            "endpoints": endpoints})
    data = json.loads(body)
    assert headers["x-gateway-destination-endpoint"] == data["endpoint"]
    return data


def test_roundrobin_cycles_sorted(binary):
    proc, port = start_picker(binary, "--picker", "roundrobin")
    try:
        eps = ["http://b:1", "http://a:1", "http://c:1"]
        got = [pick(port, "p", eps)["endpoint"] for _ in range(6)]
        assert got == ["http://a:1", "http://b:1", "http://c:1"] * 2
    finally:
        proc.kill()


def test_prefix_stickiness_and_metrics(binary):
    proc, port = start_picker(binary, "--picker", "prefix",
                              "--chunk-size", "8")
    try:
        eps = ["http://b:1", "http://a:1"]
        first = pick(port, "x" * 24, eps)
        assert first["matched"] == 0  # cold trie: fallback pick
        again = pick(port, "x" * 24 + "tail", eps)
        assert again["endpoint"] == first["endpoint"]
        assert again["matched"] >= 24
        assert again["matched_unit"] == "chars"
        _, _, metrics = req("GET", port, "/metrics")
        assert "picker_picks_total" in metrics
    finally:
        proc.kill()


def test_process_returns_ext_proc_header_mutation(binary):
    proc, port = start_picker(binary, "--picker", "roundrobin")
    try:
        _, headers, body = req("POST", port, "/process",
                               {"prompt": "p", "endpoints": ["http://a:1"]})
        env = json.loads(body)
        sh = env["response"]["header_mutation"]["set_headers"][0]["header"]
        assert sh["key"] == "x-gateway-destination-endpoint"
        assert sh["value"] == "http://a:1"
        assert headers["x-gateway-destination-endpoint"] == "http://a:1"
    finally:
        proc.kill()


def test_static_endpoints_flag(binary):
    proc, port = start_picker(binary, "--picker", "roundrobin",
                              "--endpoints", "http://s1:1,http://s2:1")
    try:
        # no endpoints in body -> the configured pool is used
        _, _, body = req("POST", port, "/pick", {"prompt": "p"})
        assert json.loads(body)["endpoint"] in ("http://s1:1", "http://s2:1")
    finally:
        proc.kill()


def test_prompt_cannot_shadow_endpoints_key(binary):
    """A prompt containing the literal text '"endpoints": [...]' must not
    override the real endpoint pool (structure-aware JSON parsing)."""
    proc, port = start_picker(binary, "--picker", "roundrobin")
    try:
        evil = 'see "endpoints": ["http://attacker:1"] here'
        got = pick(port, evil, ["http://real:8000"])
        assert got["endpoint"] == "http://real:8000"
    finally:
        proc.kill()


def test_endpoint_header_injection_stripped(binary):
    """CRLF in an endpoint string must not split response headers."""
    proc, port = start_picker(binary, "--picker", "roundrobin")
    try:
        _, headers, body = req(
            "POST", port, "/pick",
            {"prompt": "p",
             "endpoints": ["http://a:1\r\nSet-Cookie: pwned=1"]},
        )
        assert "Set-Cookie" not in headers
        assert json.loads(body)["endpoint"] == "http://a:1Set-Cookie:pwned=1"
    finally:
        proc.kill()


class FakeEngine(BaseHTTPRequestHandler):
    matched = 0
    total = 10

    def do_POST(self):
        n = int(self.headers.get("Content-Length", 0))
        self.rfile.read(n)
        if self.path == "/kv/lookup":
            payload = json.dumps({
                "matched_tokens": self.server.matched,  # type: ignore
                "total_tokens": self.server.total,  # type: ignore
            }).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)
        else:
            self.send_response(404)
            self.end_headers()

    def log_message(self, *a):
        pass


def start_fake_engine(matched, total):
    srv = ThreadingHTTPServer(("127.0.0.1", 0), FakeEngine)
    srv.matched = matched  # type: ignore
    srv.total = total  # type: ignore
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


def test_kvaware_routes_to_deepest_match(binary):
    cold_srv, cold = start_fake_engine(matched=0, total=40)
    warm_srv, warm = start_fake_engine(matched=36, total=40)
    proc, port = start_picker(binary, "--picker", "kvaware",
                              "--threshold", "8")
    try:
        got = pick(port, "some long prompt", [cold, warm])
        assert got["endpoint"] == warm
        assert got["matched"] == 36
        assert got["matched_unit"] == "tokens"
    finally:
        proc.kill()
        cold_srv.shutdown()
        warm_srv.shutdown()


def test_kvaware_falls_back_to_roundrobin_below_threshold(binary):
    a_srv, a = start_fake_engine(matched=5, total=40)  # remainder 35 > 8
    proc, port = start_picker(binary, "--picker", "kvaware",
                              "--threshold", "8")
    try:
        eps = sorted([a, "http://zzz:1"])
        got = [pick(port, "p", [a, "http://zzz:1"])["endpoint"]
               for _ in range(2)]
        assert got == eps  # round-robin order, not the shallow match
    finally:
        proc.kill()
        a_srv.shutdown()


def test_kvaware_against_real_engine(binary):
    """End-to-end: a real tiny engine serves a prompt, then the picker's
    /kv/lookup probe finds the cached prefix and routes back to it."""
    import asyncio

    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from production_stack_tpu.engine.server import EngineServer
    from production_stack_tpu.parallel.mesh import MeshConfig

    async def main():
        cfg = EngineConfig(
            model=ModelConfig.from_pretrained("tiny-llama"),
            cache=CacheConfig(block_size=4, num_blocks=128),
            scheduler=SchedulerConfig(max_num_seqs=2,
                                  max_num_batched_tokens=64),
            mesh=MeshConfig(data=1, tensor=1),
        )
        server = EngineServer(cfg)
        from aiohttp import web
        runner = web.AppRunner(server.build_app())
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        eng_port = site._server.sockets[0].getsockname()[1]
        url = f"http://127.0.0.1:{eng_port}"

        prompt = "the quick brown fox jumps over the lazy dog"
        import aiohttp
        async with aiohttp.ClientSession() as s:
            await s.post(f"{url}/v1/completions",
                         json={"prompt": prompt, "max_tokens": 2,
                               "temperature": 0, "ignore_eos": True})

        proc, port = start_picker(binary, "--picker", "kvaware",
                                  "--threshold", "64")
        try:
            got = await asyncio.to_thread(
                pick, port, prompt, [url, "http://127.0.0.1:9"])
            assert got["endpoint"] == url
            assert got["matched"] > 0
        finally:
            proc.kill()
        await runner.cleanup()

    asyncio.run(main())


def test_session_picker_sticky_and_fallback(binary):
    """session mode: same session_key -> same endpoint stably; no key ->
    round-robin fallback (a 4th picker beyond the reference's three)."""
    proc, port = start_picker(binary, "--picker", "session")
    try:
        eps = ["http://b:1", "http://a:1", "http://c:1"]
        first = pick(port, "p", eps)
        # stickiness across repeats and prompt changes
        got = {json.loads(req("POST", port, "/pick",
                              {"session_key": "user-42", "prompt": f"p{i}",
                               "endpoints": eps})[2])["endpoint"]
               for i in range(5)}
        assert len(got) == 1
        # different keys spread across the pool
        spread = {json.loads(req("POST", port, "/pick",
                                 {"session_key": f"user-{i}", "prompt": "p",
                                  "endpoints": eps})[2])["endpoint"]
                  for i in range(20)}
        assert len(spread) > 1
        # no session_key -> round-robin actually ADVANCES
        a = pick(port, "p", eps)["endpoint"]
        b = pick(port, "p", eps)["endpoint"]
        assert a != b
        # consistent-hash property: removing one endpoint keeps every
        # session NOT on the removed pod where it was (minimal remap)
        keys = [f"user-{i}" for i in range(30)]
        def place(pool, key):
            return json.loads(req("POST", port, "/pick",
                                  {"session_key": key, "prompt": "p",
                                   "endpoints": pool})[2])["endpoint"]
        before = {k: place(eps, k) for k in keys}
        removed = before[keys[0]]
        smaller = [e for e in eps if e != removed]
        moved = sum(1 for k in keys
                    if before[k] != removed
                    and place(smaller, k) != before[k])
        assert moved == 0, f"{moved} unaffected sessions remapped"
    finally:
        proc.kill()
