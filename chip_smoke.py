#!/usr/bin/env python3
"""Smoke test of the serving path on the attached TPU.

Drives the README quick start once, at the full width and depth of one
model, with seeded random weights and the byte tokenizer (no network):

    python chip_smoke.py                      # llama-3b-class, one chip
    python chip_smoke.py --model llama-3-8b --tensor-parallel-size 4

Phases, each of which must pass:

1. kernels   a child opens the chip, reports what it is, and checks the
             three Pallas kernels of the serving path (ragged attention,
             batched paged decode, DMA-ring KV write) COMPILED on the chip
             against the XLA references in ops/paged_attention.py, at the
             model's per-shard serving geometry; then, a child each, at
             the geometries of the benchmark's cells (CELL_GEOMETRIES).
2. engine    ``python -m production_stack_tpu.engine.server`` with server
             defaults (warm-up on); waits for ``/ready``.
3. router    ``python -m production_stack_tpu.router.app`` in front.
4. requests  sent to the ROUTER: a completion, a streamed chat completion,
             a ~3000-token prompt twice (prefix cache), a burst of 8.
5. report    the engine must say it ran on a TPU, through the ragged
             Pallas path, with zero post-warm-up recompiles.
6. shutdown  every child is stopped and checked gone; a fresh process must
             then be able to open the chip.

One process holds the chip at a time: this parent never imports jax, and
the children that do (kernel check, engine, final probe) run one after
the other. The last line of stdout is one JSON object, printed only when
every phase passed; any failure exits non-zero without it.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import functools
import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
# the driver's limit is 1200 s for the whole script, compiles included
READY_TIMEOUT_S = 900.0
# kernel vs float32 reference on bf16 outputs: |got - want| / max(1, |want|).
# One bf16 rounding is 2^-9 relative; the kernels also round the softmax
# weights to bf16 inside the MXU. A wrong mask or block shows as O(1).
KERNEL_TOL = 1e-2
# (KH, G, environment) of the benchmark's configurations on one chip, head
# size 128: qwen3-8b-l16 with the libtpu flag its manifest sets
# (chipbench/configs/qwen3-8b-l16/manifest.json engine_env: the ragged
# kernel asks for more scoped VMEM than the default at KH=8, G=4), and
# olmoe-1b-7b-l8 under the defaults
CELL_GEOMETRIES = (
    (8, 4, {"LIBTPU_INIT_ARGS": "--xla_tpu_scoped_vmem_limit_kib=32768"}),
    (16, 1, {}),
    # solar-open2-250b-ep16-l8's attention layers: KH=8, G=8, the same flag
    (8, 8, {"LIBTPU_INIT_ARGS": "--xla_tpu_scoped_vmem_limit_kib=32768"}),
)


class SmokeFailure(Exception):
    pass


# ---------------------------------------------------------------------------
# children that open the chip (the only code here that imports jax)
# ---------------------------------------------------------------------------

def _device_report(t0: float) -> dict:
    import importlib.metadata as md

    import jax

    devs = jax.devices()
    report = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "backend_open_s": round(time.monotonic() - t0, 2),
        "jax": jax.__version__,
        "jaxlib": md.version("jaxlib"),
    }
    try:
        report["libtpu"] = md.version("libtpu")
    except md.PackageNotFoundError:
        report["libtpu"] = None
    return report


def child_probe() -> int:
    """Open the backend and say what it is (the post-shutdown check)."""
    print(json.dumps(_device_report(time.monotonic())), flush=True)
    return 0


def _kernel_cases(KH: int, G: int, D: int, bs: int, dtype):
    """The three kernel checks at one (KH, G, D, bs) geometry. Each case
    is (name, thunk) with the thunk returning (scaled_err, detail)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.ops.paged_attention import (
        combine_kv,
        paged_attention,
        ragged_paged_attention,
        write_kv,
    )
    from production_stack_tpu.ops.paged_attention_pallas import (
        kv_cache_write_pallas,
        paged_decode_attention_pallas,
    )
    from production_stack_tpu.ops.ragged_paged_attention_pallas import (
        ragged_paged_attention_pallas,
    )

    H = KH * G
    L, N = 2, 1024
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    cache = jax.random.normal(key, (L, N, bs, 2 * KH, D), jnp.float32)
    cache = cache.astype(dtype)

    def scaled_err(got, want):
        return float((np.abs(got - want)
                      / np.maximum(1.0, np.abs(want))).max())

    def tables_for(ctx_lens, M):
        # read-only checks: rows may share blocks; block 0 stays the pad
        t = np.zeros((len(ctx_lens), M), np.int32)
        for s, c in enumerate(ctx_lens):
            nb = -(-c // bs)
            t[s, :nb] = rng.integers(1, N, nb)
        return t

    def ragged(q_lens, ctxs, T, M):
        tables = tables_for(ctxs, M)
        cu = np.zeros(len(q_lens) + 1, np.int32)
        cu[1:] = np.cumsum(q_lens)
        live = int(cu[-1])
        q = jax.random.normal(jax.random.PRNGKey(1), (T, H, D),
                              jnp.float32).astype(dtype)
        got = jax.jit(
            lambda q, c, bt, cu, cl: ragged_paged_attention_pallas(
                q, c, bt, cu, cl, layer_idx=1)
        )(q, cache, jnp.asarray(tables), jnp.asarray(cu),
          jnp.asarray(ctxs, jnp.int32))
        got = np.asarray(got.astype(jnp.float32))
        # reference in 64-token chunks: the XLA path gathers each token's
        # whole padded context, (chunk, M*bs, 2KH, D)
        seq_ids = np.repeat(np.arange(len(q_lens)), q_lens).astype(np.int32)
        q_pos = np.concatenate([np.arange(c - n, c) for n, c
                                in zip(q_lens, ctxs)]).astype(np.int32)
        C = 64
        pad = -live % C
        seq_ids = np.pad(seq_ids, (0, pad))
        q_pos = np.pad(q_pos, (0, pad), constant_values=-1)
        ref_fn = jax.jit(
            lambda q, c, sid, pos: ragged_paged_attention(
                q, c[1], jnp.asarray(tables),
                jnp.asarray(ctxs, jnp.int32), sid, pos))
        want = []
        qp = jnp.pad(q[:live], ((0, pad), (0, 0), (0, 0)))
        with jax.default_matmul_precision("highest"):
            for i in range(0, live + pad, C):
                want.append(np.asarray(ref_fn(
                    qp[i:i + C], cache, jnp.asarray(seq_ids[i:i + C]),
                    jnp.asarray(q_pos[i:i + C])).astype(jnp.float32)))
        want = np.concatenate(want)[:live]
        if not np.isfinite(got).all():
            raise SmokeFailure("ragged kernel produced non-finite values")
        # (a stream filled to its last token has no tail)
        tail = float(np.abs(got[live:]).max()) if live < T else 0.0
        if tail != 0.0:
            raise SmokeFailure(f"ragged tail padding not zero ({tail})")
        return (scaled_err(got[:live], want),
                f"T={T} live={live} S={len(q_lens)} M={M}")

    def decode():
        B, M = 64, 128
        base = [0, 1, 15, 16, 17, 128, 129, 500, 1000, 2047, 2048, 333]
        ctxs = [base[i % len(base)] for i in range(B)]
        tables = tables_for(ctxs, M)
        q = jax.random.normal(jax.random.PRNGKey(2), (B, H, D),
                              jnp.float32).astype(dtype)
        cl = jnp.asarray(ctxs, jnp.int32)
        got = jax.jit(
            lambda q, c, bt, cl: paged_decode_attention_pallas(
                q, c, bt, cl, layer_idx=1)
        )(q, cache, jnp.asarray(tables), cl)
        got = np.asarray(got.astype(jnp.float32))
        with jax.default_matmul_precision("highest"):
            want = jax.jit(
                lambda q, c, bt, cl: paged_attention(
                    q[:, None], c[1], bt, cl, (cl - 1)[:, None])
            )(q, cache, jnp.asarray(tables), cl)
        want = np.asarray(want.astype(jnp.float32))[:, 0]
        if not np.isfinite(got).all():
            raise SmokeFailure("decode kernel produced non-finite values")
        liverows = np.asarray(ctxs) > 0  # dead slots: the kernel writes 0
        return scaled_err(got[liverows], want[liverows]), f"B={B} M={M}"

    def kv_write():
        # the server's default token budget in one call, pad slots skipped
        T = 2048
        slots = rng.permutation(N * bs)[:T].astype(np.int32)
        slots[rng.integers(0, T, 97)] = -1
        k = jax.random.normal(jax.random.PRNGKey(3), (T, KH, D),
                              jnp.float32).astype(dtype)
        v = jax.random.normal(jax.random.PRNGKey(4), (T, KH, D),
                              jnp.float32).astype(dtype)
        sm = jnp.asarray(slots)
        got = jax.jit(
            lambda c, k, v, sm: kv_cache_write_pallas(
                c, combine_kv(k, v), sm, layer_idx=1),
        )(cache, k, v, sm)
        want = jax.jit(
            lambda c, k, v, sm: write_kv(c, jnp.int32(1), k, v, sm)
        )(cache, k, v, sm)
        err = scaled_err(np.asarray(got.astype(jnp.float32)),
                         np.asarray(want.astype(jnp.float32)))
        return err, f"T={T} skipped={int((slots < 0).sum())}"

    # FUZZ_CASES of tests/test_ragged_attention.py scaled to serving
    # tiles: a mid-prompt chunk across three q-tiles, decode rows, empty
    # slots, a fresh whole-prompt chunk, a verify-shaped span, spans
    # straddling tile edges, and a padded tail tile (5 q-tiles of 128;
    # live tokens end inside the 4th)
    mixed = functools.partial(
        ragged, [300, 0, 1, 1, 130, 5, 1, 0, 64, 1, 0, 0, 1, 7, 0, 0],
        [700, 0, 513, 17, 130, 260, 1, 0, 1088, 128, 0, 0, 300, 7, 0, 0],
        640, 72)
    # a decode-heavy cell's ragged step: 64 one-token spans at contexts
    # 300-1000 (the kernel's narrow row block) and one 160-token prompt
    # (its full tile), in the server's 2048-token stream
    decode_mix = functools.partial(
        ragged, [1] * 64 + [160],
        [int(c) for c in np.linspace(300, 1000, 64)] + [160], 2048, 64)

    # a prefill-heavy cell's ragged step: a lone 2048-token chunk behind
    # 1024 tokens of context (every tile owned whole: the kernel's
    # interior window body up to each tile's diagonal), and the same
    # stream shared with 10 decode rows at context 3072
    def prefill_full():
        runs = [ragged([2048], [3072], 2048, 192),
                ragged([1] * 10 + [2038], [3072] * 11, 2048, 192)]
        return (max(err for err, _ in runs),
                "; ".join(detail for _, detail in runs))

    return [("ragged_paged_attention", mixed),
            ("ragged_paged_attention.decode_mix", decode_mix),
            ("ragged_paged_attention.prefill_full", prefill_full),
            ("paged_decode_attention", decode),
            ("kv_cache_write", kv_write)]


def child_check(model: str, tp: int, geometry: str | None = None) -> int:
    """Report the device, then check the kernels compiled on it, at the
    model's per-shard geometry or at ``geometry`` ("KH,G", head size
    128)."""
    t0 = time.monotonic()
    from production_stack_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    report = _device_report(t0)
    print(json.dumps({"device": report}), flush=True)
    if report["platform"] != "tpu":
        print(f"chip_smoke: JAX platform is {report['platform']!r}, not "
              "'tpu' — this check only passes on the accelerator",
              file=sys.stderr)
        return 1
    import traceback

    import jax.numpy as jnp

    from production_stack_tpu.engine.config import CacheConfig, ModelConfig

    if geometry:
        KH, G = (int(x) for x in geometry.split(","))
        D = 128
    else:
        cfg = ModelConfig.from_pretrained(model)
        KH, G, D = cfg.num_kv_heads // tp, cfg.q_per_kv, cfg.head_dim
    bs = CacheConfig().block_size
    results, ok = [], True
    for name, thunk in _kernel_cases(KH, G, D, bs, jnp.bfloat16):
        t = time.monotonic()
        try:
            err, detail = thunk()
            passed = err <= KERNEL_TOL
            results.append({"kernel": name, "scaled_err": err,
                            "tol": KERNEL_TOL, "ok": passed,
                            "detail": detail,
                            "seconds": round(time.monotonic() - t, 2)})
        except Exception:  # noqa: BLE001 — reported, and the phase fails
            passed = False
            # Mosaic errors embed the serialized kernel: keep the prose
            tb = re.sub(r"[A-Za-z0-9+/=]{120,}", "<mlir>",
                        traceback.format_exc())
            tb = tb if len(tb) < 6000 else tb[:3000] + "\n...\n" + tb[-3000:]
            results.append({"kernel": name, "ok": False, "error": tb})
            print(f"--- {name} FAILED ---\n{tb}", file=sys.stderr, flush=True)
        ok = ok and passed
    print(json.dumps({"geometry": {"KH": KH, "G": G, "D": D,
                                   "bs": bs, "dtype": "bfloat16"},
                      "kernels": results}), flush=True)
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# parent: process management and HTTP (no jax here)
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(method: str, url: str, body=None, timeout: float = 600.0):
    """(status, bytes); connection errors are status 0."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"} if data else {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()
    except (urllib.error.URLError, OSError):
        return 0, b""


def _tail(path: str, n: int = 60) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


class Child:
    """A subprocess in its own process group, logging to a file."""

    def __init__(self, name: str, argv: list, log_dir: str, env=None):
        self.name = name
        self.log = os.path.join(log_dir, f"{name}.log")
        self._fh = open(self.log, "w")
        # environment passed through unchanged (JAX_COMPILATION_CACHE_DIR,
        # JAX_PLATFORMS and TPU_* reach the children as the caller set
        # them), with ``env`` laid over it
        self.proc = subprocess.Popen(
            argv, cwd=HERE, stdout=self._fh, stderr=subprocess.STDOUT,
            start_new_session=True, env={**os.environ, **(env or {})})

    def alive(self) -> bool:
        return self.proc.poll() is None

    def group_gone(self) -> bool:
        try:
            os.killpg(self.proc.pid, 0)
        except ProcessLookupError:
            return True
        except PermissionError:
            return False
        return False

    def stop(self, grace: float = 40.0) -> None:
        """SIGTERM the group, wait, then SIGKILL whatever is left."""
        if not self.group_gone():
            try:
                os.killpg(self.proc.pid, signal.SIGTERM)
            except ProcessLookupError:
                pass
        try:
            self.proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            pass
        if not self.group_gone():
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        self._fh.close()


def _run_child_mode(mode: str, args, log_dir: str, timeout: float,
                    geometry: str | None = None, env=None) -> list:
    """Run this script in a child mode to completion; return the JSON
    objects it printed. Raises SmokeFailure on non-zero exit."""
    argv = [sys.executable, os.path.abspath(__file__), "--child", mode,
            "--model", args.model,
            "--tensor-parallel-size", str(args.tensor_parallel_size)]
    if geometry:
        argv += ["--geometry", geometry]
    child = Child(f"{mode}-{geometry}" if geometry else mode, argv, log_dir,
                  env)
    try:
        try:
            rc = child.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{mode} child exceeded {timeout:.0f}s\n"
                               + _tail(child.log))
    finally:
        child.stop(grace=5.0)
    out = []
    with open(child.log, errors="replace") as f:
        for line in f:
            line = line.strip()
            if line.startswith("{"):
                try:
                    out.append(json.loads(line))
                except ValueError:
                    pass
    if rc != 0:
        raise SmokeFailure(f"{mode} child exited {rc}\n" + _tail(child.log))
    return out


def _wait_http_ok(url: str, child: Child, timeout: float, what: str) -> float:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if not child.alive():
            raise SmokeFailure(
                f"{child.name} exited {child.proc.returncode} before "
                f"{what}\n" + _tail(child.log))
        status, _ = _http("GET", url, timeout=5.0)
        if status == 200:
            return time.monotonic() - t0
        time.sleep(1.0)
    raise SmokeFailure(f"{what} not 200 after {timeout:.0f}s\n"
                       + _tail(child.log))


def _expect_completion(status: int, raw: bytes, want_tokens: int,
                       what: str) -> dict:
    if status != 200:
        raise SmokeFailure(f"{what}: HTTP {status} {raw[:400]!r}")
    body = json.loads(raw)
    got = body["usage"]["completion_tokens"]
    if got != want_tokens:
        raise SmokeFailure(
            f"{what}: completion_tokens {got} != max_tokens {want_tokens}")
    return body


def _send_requests(router: str, model: str) -> dict:
    facts = {}
    # 1. non-streaming completion
    status, raw = _http("POST", f"{router}/v1/completions", {
        "model": model, "prompt": "The quick brown fox", "max_tokens": 16,
        "temperature": 0, "ignore_eos": True})
    _expect_completion(status, raw, 16, "completion")

    # 2. streaming chat completion, terminated by data: [DONE]
    status, raw = _http("POST", f"{router}/v1/chat/completions", {
        "model": model, "stream": True, "max_tokens": 16,
        "temperature": 0.7, "seed": 1, "ignore_eos": True,
        "stream_options": {"include_usage": True},
        "messages": [{"role": "user", "content": "Say hello."}]})
    if status != 200:
        raise SmokeFailure(f"chat stream: HTTP {status} {raw[:400]!r}")
    events = [ln[len("data: "):] for ln in raw.decode().splitlines()
              if ln.startswith("data: ")]
    if not events or events[-1] != "[DONE]":
        raise SmokeFailure("chat stream did not end in data: [DONE]")
    usage = [json.loads(e).get("usage") for e in events[:-1]]
    usage = [u for u in usage if u]
    if not usage or usage[-1]["completion_tokens"] != 16:
        raise SmokeFailure(f"chat stream usage {usage[-1:]} != 16 tokens")
    facts["stream_events"] = len(events)

    # 3. a prompt that crosses the 2048-token step budget, twice: the
    # second must be served from the prefix cache
    long_prompt = ("All work and no play makes Jack a dull boy. " * 80)[:3000]
    req = {"model": model, "prompt": long_prompt, "max_tokens": 8,
           "temperature": 0, "ignore_eos": True}
    first = _expect_completion(
        *_http("POST", f"{router}/v1/completions", req), 8, "long prompt")
    second = _expect_completion(
        *_http("POST", f"{router}/v1/completions", req), 8,
        "long prompt (repeat)")
    n = second["usage"]["prompt_tokens"]
    cached = second["usage"]["prompt_tokens_details"]["cached_tokens"]
    if n < 2900 or cached < 0.95 * n - 32:
        raise SmokeFailure(
            f"prefix cache: {cached} cached of {n} prompt tokens")
    if first["choices"][0]["text"] != second["choices"][0]["text"]:
        raise SmokeFailure("greedy output changed when served from cache")
    facts["long_prompt_tokens"] = n
    facts["cached_tokens"] = cached

    # 4. a burst of 8: prefill chunks and decode rows share ragged
    # dispatches, and the pure-decode steps after them run decode_multi
    def one(i: int):
        return _http("POST", f"{router}/v1/completions", {
            "model": model, "prompt": f"request {i}: " + "lorem ipsum " * (9 * i),
            "max_tokens": 32, "temperature": 0, "ignore_eos": True})

    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        for i, (status, raw) in enumerate(pool.map(one, range(8))):
            _expect_completion(status, raw, 32, f"burst request {i}")
    return facts


def _metric_total(text: str, name: str) -> float:
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            total += float(line.rsplit(" ", 1)[1])
            seen = True
    if not seen:
        raise SmokeFailure(f"metric {name} not exported")
    return total


def _check_engine_report(engine: str, device: dict, tp: int) -> dict:
    status, raw = _http("GET", f"{engine}/debug/perf")
    if status != 200:
        raise SmokeFailure(f"/debug/perf: HTTP {status}")
    perf = json.loads(raw)
    fp = perf.get("fingerprint") or {}
    want = {"platform": "tpu", "chip": device["kind"],
            "n_devices": device["count"], "attention_impl": "ragged",
            "use_pallas": True, "n_chips": tp, "tensor_parallel": tp}
    bad = {k: (fp.get(k), v) for k, v in want.items() if fp.get(k) != v}
    if bad:
        raise SmokeFailure(f"engine fingerprint (got, want): {bad}")
    status, raw = _http("GET", f"{engine}/metrics")
    if status != 200:
        raise SmokeFailure(f"/metrics: HTTP {status}")
    text = raw.decode()
    recompiles = _metric_total(text, "vllm:unexpected_recompiles_total")
    if recompiles != 0:
        raise SmokeFailure(
            f"{recompiles:g} unexpected recompiles after warm-up: "
            + json.dumps([e for e in perf["compile"]["recent"]
                          if e["unexpected"]]))
    comp = perf["compile"]
    if not comp["steady"]:
        raise SmokeFailure("engine never marked warm-up complete")
    kinds = {k.split(":")[0] for k in comp["counts"]}
    if not {"ragged", "decode_multi"} <= kinds:
        raise SmokeFailure(f"programs compiled: {sorted(kinds)} — the "
                           "ragged and decode_multi programs must both run")
    facts = {
        "fingerprint": fp,
        "startup_seconds": perf.get("startup_seconds"),
        "unexpected_recompiles": 0,
        "compile_events": comp["total_events"],
        "compile_seconds": comp["total_seconds"],
        "programs": [f"{e['kind']}:{e['bucket']} {e['seconds']:.1f}s"
                     for e in comp["recent"]],
        "ragged_dispatches": _metric_total(
            text, "vllm:ragged_dispatches_total"),
    }
    per_dev = perf["hbm_bytes"].get("devices") or []
    if tp > 1:
        used = [d["bytes_in_use"] for d in per_dev]
        if len(used) != tp or min(used) <= 0:
            raise SmokeFailure(f"per-device memory report: {per_dev}")
        spread = (max(used) - min(used)) / max(used)
        facts["hbm_in_use_per_device"] = used
        facts["hbm_spread"] = round(spread, 4)
        if spread > 0.05:
            raise SmokeFailure(
                f"device memory not evenly sharded over {tp} chips: {used}")
    return facts


def run(args, log_dir: str) -> dict:
    if not os.path.isdir(os.path.join(HERE, "production_stack_tpu")):
        raise SmokeFailure(
            "production_stack_tpu/ is not next to chip_smoke.py: run it "
            "from the root of a checkout")
    phases = {}
    children: list[Child] = []
    tp = args.tensor_parallel_size
    try:
        t = time.monotonic()
        out = _run_child_mode("check", args, log_dir, timeout=600.0)
        device = next(o["device"] for o in out if "device" in o)
        print(f"device: {json.dumps(device)}")
        checks = [next(o for o in out if "kernels" in o)]
        for KH, G, env in CELL_GEOMETRIES:
            out = _run_child_mode("check", args, log_dir, timeout=600.0,
                                  geometry=f"{KH},{G}", env=env)
            checks.append(next(o for o in out if "kernels" in o))
        phases["kernels_s"] = round(time.monotonic() - t, 1)
        for kernels in checks:
            print(f"kernel geometry: {json.dumps(kernels['geometry'])}")
            for k in kernels["kernels"]:
                print(f"  {k['kernel']}: scaled_err {k['scaled_err']:.3g} "
                      f"(tol {k['tol']}) {k['detail']} [{k['seconds']}s]")
        if device["count"] < tp:
            raise SmokeFailure(
                f"--tensor-parallel-size {tp} needs {tp} devices, the "
                f"backend has {device['count']}")

        eport, rport = _free_port(), _free_port()
        engine_url = f"http://127.0.0.1:{eport}"
        router_url = f"http://127.0.0.1:{rport}"
        t = time.monotonic()
        engine = Child("engine", [
            sys.executable, "-m", "production_stack_tpu.engine.server",
            "--model", args.model, "--tensor-parallel-size", str(tp),
            "--host", "127.0.0.1", "--port", str(eport)], log_dir)
        children.append(engine)
        _wait_http_ok(f"{engine_url}/ready", engine, READY_TIMEOUT_S,
                      "engine /ready")
        phases["engine_ready_s"] = round(time.monotonic() - t, 1)
        for line in _tail(engine.log, 400).splitlines():
            if line.startswith("engine startup:") or "warmup" in line:
                print(f"  {line}")

        t = time.monotonic()
        router = Child("router", [
            sys.executable, "-m", "production_stack_tpu.router.app",
            "--host", "127.0.0.1", "--port", str(rport),
            "--static-backends", engine_url,
            "--static-models", args.model,
            "--routing-logic", "roundrobin"], log_dir)
        children.append(router)
        _wait_http_ok(f"{router_url}/health", router, 60.0,
                      "router /health")
        phases["router_ready_s"] = round(time.monotonic() - t, 1)

        t = time.monotonic()
        facts = _send_requests(router_url, args.model)
        phases["requests_s"] = round(time.monotonic() - t, 1)
        if not engine.alive() or not router.alive():
            raise SmokeFailure("a server died while serving\n"
                               + _tail(engine.log) + _tail(router.log))
        facts.update(_check_engine_report(engine_url, device, tp))
        print(f"engine report: {json.dumps(facts, indent=1)}")
    finally:
        t = time.monotonic()
        for child in reversed(children):
            child.stop()
        phases["shutdown_s"] = round(time.monotonic() - t, 1)
    left = [c.name for c in children if not c.group_gone()]
    if left:
        raise SmokeFailure(f"processes still alive after shutdown: {left}")
    if engine.proc.returncode != 0:
        raise SmokeFailure(
            f"engine exited {engine.proc.returncode} on SIGTERM")

    # the chip must be free again: a fresh process opens it in seconds
    t = time.monotonic()
    after = _run_child_mode("probe", args, log_dir, timeout=120.0)[-1]
    phases["reopen_s"] = round(time.monotonic() - t, 1)
    if (after["platform"], after["count"]) != (device["platform"],
                                               device["count"]):
        raise SmokeFailure(f"after shutdown the backend is {after}")
    print(f"phases: {json.dumps(phases)}")
    return {"ok": True,
            "device": {"platform": device["platform"],
                       "kind": device["kind"], "count": device["count"]}}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--model", default="llama-3b-class")
    p.add_argument("--tensor-parallel-size", type=int, default=1)
    p.add_argument("--log-dir", default=None,
                   help="keep the children's logs here (default: a "
                        "temporary directory, removed on exit)")
    p.add_argument("--child", choices=["check", "probe"], default=None,
                   help=argparse.SUPPRESS)
    p.add_argument("--geometry", default=None, help=argparse.SUPPRESS)
    args = p.parse_args()
    if args.child == "check":
        return child_check(args.model, args.tensor_parallel_size,
                           args.geometry)
    if args.child == "probe":
        return child_probe()
    # a terminated parent must still stop its children (they have their
    # own process groups): leave through the finally blocks
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(128 + signal.SIGTERM))
    t0 = time.monotonic()
    try:
        if args.log_dir:
            os.makedirs(args.log_dir, exist_ok=True)
            result = run(args, args.log_dir)
        else:
            with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
                result = run(args, tmp)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED after {time.monotonic() - t0:.0f}s: {e}",
              file=sys.stderr)
        return 1
    print(f"chip_smoke passed in {time.monotonic() - t0:.0f}s")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
