#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of BENCHMARK.json's ``workloads``: a configuration
(``chipbench/configs/<config>/``) under a traffic mix
(``chipbench/traffic/<mix>.json``). The run starts one engine server and
the router in front of it through their normal entry points, warms the
cell's own shapes, checks the served log-probabilities against the plain
reference, ramps the load up, measures for ``--seconds`` and prints one
JSON object as the last line of its standard output (the lines before it
say how the engine started and why ``correct`` is what it is). With ``--trace 0``
the metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics (a few seconds of the window are profiled).

This process generates the load and never imports JAX; the engine, the
reference check and the trace reduction are children that run one after
the other, because one process holds the chip at a time. Anything that
keeps a result from being trusted (no TPU, too few chips, a child that
dies) exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import asyncio
import io
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tarfile
import time
import types

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from chipbench import layers, prom, stats  # noqa: E402
from chipbench.loadgen import LoadGen  # noqa: E402
from chipbench.procs import Child, RunFailure, http, wait_ok  # noqa: E402
from chipbench.stats import Record  # noqa: E402
from chipbench import traffic  # noqa: E402

READY_TIMEOUT_S = 1100.0   # a cell's first run in a checkout compiles
PROFILE_MS = 4000          # traced share of the window (--trace 1)
# Served (bf16, kernels, paged cache) against reference (float32,
# "highest") log-probabilities of the top tokens: the largest and the mean
# absolute difference allowed. Over the seeds run so far the bf16 path
# reads at most 0.069 and 0.014 (the reading depends on the seed alone);
# int8 weights read 0.26 (PERF.md section 2). The largest difference finds
# a fault in one place (a wrong block, a dropped chunk), the mean a loss
# of precision everywhere.
LOGPROB_TOL = 0.15
LOGPROB_MEAN_TOL = 0.03
# The probe, as (prompt tokens, output tokens): a short prompt, and one of
# one and a half token budgets, so that its prefill is cut into two chunks
# (the second reads the first one's paged KV), followed by 64 decode steps
# at that depth, which cross four block boundaries of the paged cache.
PROBE_SHORT = (150, 6)
PROBE_LONG_BUDGETS, PROBE_LONG_OUTPUTS = 1.5, 64
PROBE_TOP = 5


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def load_cell(workload: str, benchmark: str) -> types.SimpleNamespace:
    with open(benchmark) as f:
        bm = json.load(f)
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise RunFailure(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    cfg_dir = os.path.dirname(os.path.join(ROOT, cfg["file"]))
    with open(os.path.join(ROOT, cfg["file"])) as f:
        hf = json.load(f)
    with open(os.path.join(cfg_dir, "manifest.json")) as f:
        manifest = json.load(f)

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]
    return types.SimpleNamespace(
        name=workload, chips=int(cell["chips"]), config=cell["config"],
        config_file=os.path.join(ROOT, cfg["file"]), hf=hf,
        manifest=manifest, mix=traffic.load_mix(cell["traffic"]),
        end_to_end=mine(bm["end_to_end"]), per_layer=mine(bm["per_layer"]),
        all_per_layer=bm["per_layer"])


def prepare_model_dir(cell, work: str) -> str:
    """The directory the engine is pointed at: the configuration file as
    committed, no safetensors (so the engine makes seeded random weights on
    the device), and a one-word-per-id tokenizer so that every generated
    token streams as a chunk of its own (the byte tokenizer the engine
    falls back to renders ids above 255 as nothing, and a chunk with no
    text is not sent)."""
    d = os.path.join(work, "model")
    os.makedirs(d, exist_ok=True)
    shutil.copyfile(cell.config_file, os.path.join(d, "config.json"))
    tok = os.path.join(d, "tokenizer.json")
    if not os.path.exists(tok):
        vocab = {f"t{i}": i for i in range(int(cell.hf["vocab_size"]))}
        with open(tok + ".tmp", "w") as f:
            json.dump({"version": "1.0", "truncation": None, "padding": None,
                       "added_tokens": [], "normalizer": None,
                       "pre_tokenizer": {"type": "WhitespaceSplit"},
                       "post_processor": None, "decoder": None,
                       "model": {"type": "WordLevel", "vocab": vocab,
                                 "unk_token": "t0"}}, f)
        os.replace(tok + ".tmp", tok)
        with open(os.path.join(d, "tokenizer_config.json"), "w") as f:
            json.dump({"tokenizer_class": "PreTrainedTokenizerFast"}, f)
    return d


class Stack:
    """Engine and router as real processes, stopped on every way out."""

    def __init__(self, cell, seed: int, work: str):
        self.cell, self.work = cell, work
        self.engine_seed = seed % (2 ** 31)
        self.model_dir = prepare_model_dir(cell, work)
        self.engine_port, self.router_port = free_port(), free_port()
        self.engine_url = f"http://127.0.0.1:{self.engine_port}"
        self.router_url = f"http://127.0.0.1:{self.router_port}"
        self.children: list[Child] = []

    def start(self) -> None:
        py = sys.executable
        self.engine = Child("engine", [
            py, "-m", "production_stack_tpu.engine.server",
            "--model", self.model_dir, "--served-model-name",
            self.cell.config, "--host", "127.0.0.1",
            "--port", str(self.engine_port), "--seed", str(self.engine_seed),
            "--skip-warmup", "--flight-recorder-size", "16384",
            *self.cell.manifest["engine_flags"]], self.work, ROOT,
            self.engine_env())
        self.children.append(self.engine)
        self.router = Child("router", [
            py, "-m", "production_stack_tpu.router.app",
            "--host", "127.0.0.1", "--port", str(self.router_port),
            "--static-backends", self.engine_url,
            "--static-models", self.cell.config,
            "--routing-logic", "roundrobin"], self.work, ROOT)
        self.children.append(self.router)
        wait_ok(self.engine_url + "/ready", self.engine, READY_TIMEOUT_S,
                "engine /ready")
        wait_ok(self.router_url + "/health", self.router, 60.0,
                "router /health")

    def engine_env(self) -> dict:
        """The caller's environment plus the configuration's own settings;
        a variable both set is joined with a space (LIBTPU_INIT_ARGS)."""
        env = dict(os.environ)
        for k, v in self.cell.manifest.get("engine_env", {}).items():
            env[k] = f"{env[k]} {v}" if env.get(k) else v
        return env

    def get_json(self, path: str) -> dict:
        status, body = http("GET", self.engine_url + path, timeout=60.0)
        if status != 200:
            raise RunFailure(f"engine GET {path} -> {status}\n"
                             + self.engine.tail())
        return json.loads(body)

    def metrics(self) -> dict:
        status, body = http("GET", self.engine_url + "/metrics", timeout=30.0)
        if status != 200:
            raise RunFailure(f"engine /metrics -> {status}")
        return prom.parse(body.decode())

    def stop(self) -> None:
        for c in reversed(self.children):
            c.stop()
        self.children = []


def run_child(name: str, argv: list, work: str, timeout: float,
              env: dict | None = None) -> str:
    """Run a child to its end; return its log. Non-zero is a RunFailure."""
    child = Child(name, argv, work, ROOT, env)
    try:
        try:
            rc = child.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise RunFailure(f"{name} exceeded {timeout:.0f}s\n{child.tail()}")
    finally:
        child.stop(grace=5.0)
    if rc != 0:
        raise RunFailure(f"{name} exited {rc}\n{child.tail()}")
    with open(child.log, errors="replace") as f:
        return f.read()


def last_json_line(text: str) -> dict:
    for line in reversed(text.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise RunFailure("child printed no JSON line:\n" + text[-2000:])


async def measure(cell, stack: Stack, args) -> types.SimpleNamespace:
    """Warm, probe, ramp, measure, drain. Returns what was observed."""
    mix, seconds = cell.mix, float(args.seconds)
    vocab = int(cell.hf["vocab_size"])
    obs = types.SimpleNamespace(polls=[], trace_tar=None, probe=[])
    async with LoadGen(stack.router_url, cell.config, vocab, args.seed) as lg:
        loop = asyncio.get_running_loop()

        async def scrape() -> dict:
            return await loop.run_in_executor(None, stack.metrics)

        # 1. warm the cell's shapes: greedy requests like its own, alone
        #    and then together, until no program is compiled any more
        lg.t_open = time.monotonic()
        warm_n, compiles = 0, -1.0
        for burst in (1, 4, 4):
            recs = [Record(-1, f"cb-warm-{warm_n + i}", 24 + 8 * i, 4,
                           lg.now()) for i in range(burst)]
            await asyncio.gather(*[
                lg.send(r, traffic.prompt_tokens(
                    args.seed, -1 - warm_n - i, r.prompt_len, vocab))
                for i, r in enumerate(recs)])
            warm_n += burst
            bad = [r for r in recs if not r.ok]
            if bad:
                raise RunFailure(f"warm-up request failed: {bad[0].status} "
                                 f"{bad[0].error}\n{stack.engine.tail()}")
            seen = (await scrape()).get("vllm:compile_events_total", 0.0)
            if burst > 1 and seen == compiles:
                break
            compiles = seen
        obs.t_warm = time.monotonic()
        # 2. correctness probe: served log-probabilities, kept for the
        #    reference child that runs once the engine has left the chip
        long_probe = (int(PROBE_LONG_BUDGETS * cell.manifest["token_budget"]),
                      PROBE_LONG_OUTPUTS)
        for idx, (plen, olen) in enumerate((PROBE_SHORT, long_probe)):
            ids = traffic.prompt_tokens(args.seed, -1000 - idx, plen, vocab)
            rec = await lg.send(Record(idx, f"cb-probe-{idx}", plen, olen,
                                       lg.now()), ids, logprobs=PROBE_TOP)
            if not rec.ok or len(rec.probe["tokens"]) != olen:
                raise RunFailure(f"probe request failed: {rec.status} "
                                 f"{rec.error} {rec.probe}")
            obs.probe.append({
                "index": idx, "prompt": ids,
                "tokens": [int(t[1:]) for t in rec.probe["tokens"]],
                "token_logprobs": rec.probe["token_logprobs"],
                "top_logprobs": [[[int(k[1:]), v] for k, v in top.items()]
                                 for top in rec.probe["top_logprobs"]]})
        lg.records.clear()
        obs.t_probe = time.monotonic()
        # 3. ramp and window, on one clock
        ramp = float(mix["ramp_s"])
        lg.t_open = time.monotonic() + ramp
        obs.setup_s = lg.t_open - T_START
        load = asyncio.ensure_future(lg.run(mix, seconds))

        async def at(t: float) -> None:
            await asyncio.sleep(max(0.0, t - lg.now()))

        async def profile() -> None:
            await at(seconds / 2 - PROFILE_MS / 2e3)
            status, body = await loop.run_in_executor(None, lambda: http(
                "POST", stack.engine_url + "/debug/profile",
                json.dumps({"duration_ms": PROFILE_MS}).encode(), 300.0))
            if status != 200:
                raise RunFailure(f"/debug/profile -> {status} {body[:300]}")
            obs.trace_tar = body

        prof = asyncio.ensure_future(profile()) if args.trace else None
        await at(0.0)
        obs.prom_open = await scrape()
        t = 1.0
        while t < seconds:
            await at(t)
            obs.polls.append(await scrape())
            t += 1.0
        await at(seconds)
        obs.prom_close = await scrape()
        await load          # drain: requests in flight run to their end
        if prof is not None:
            await prof
        obs.records = lg.records
    obs.polls = [obs.prom_open, *obs.polls, obs.prom_close]
    return obs


def reduce_trace(cell, obs, work: str) -> dict | None:
    if obs.trace_tar is None:
        return None
    tdir = os.path.join(work, "trace")
    shutil.rmtree(tdir, ignore_errors=True)
    with tarfile.open(fileobj=io.BytesIO(obs.trace_tar), mode="r:gz") as tar:
        tar.extractall(tdir, filter="data")
    found = [os.path.join(r, f) for r, _, fs in os.walk(tdir)
             for f in fs if f.endswith(".xplane.pb")]
    if not found:
        raise RunFailure("the profile holds no .xplane.pb")
    programs = {}
    for m in cell.all_per_layer:  # label every program any metric knows
        spec = layers.load_spec(m["name"])
        if "program" in spec:
            # the rule's regexes may name sizes of the configuration
            sizes = {**cell.hf, **cell.manifest}
            programs[spec["program"]] = {
                k: spec[k].format(**sizes)
                for k in ("module", "contains_op") if k in spec}
    out = os.path.join(work, "trace_summary.json")
    argv = [sys.executable, os.path.join(HERE, "trace_reduce.py"), found[0],
            out, json.dumps(programs),
            os.path.join(ROOT, "production_stack_tpu")]
    run_child("trace_reduce", argv, work, 300.0,
              {**os.environ, "JAX_PLATFORMS": "cpu"})
    with open(out) as f:
        return json.load(f)


def check_reference(cell, stack: Stack, obs, work: str) -> dict:
    probe_path = os.path.join(work, "probe.json")
    with open(probe_path, "w") as f:
        json.dump(obs.probe, f)
    flags = cell.manifest["engine_flags"]
    argv = [sys.executable, os.path.join(HERE, "reference", "compare.py"),
            "--model-dir", stack.model_dir,
            "--reference", cell.manifest["reference"],
            "--engine-seed", str(stack.engine_seed),
            "--tp", str(cell.manifest["expect"]["tensor_parallel"]),
            "--probe", probe_path]
    if "--dtype" in flags:
        argv += ["--dtype", flags[flags.index("--dtype") + 1]]
    return last_json_line(run_child("reference", argv, work, 600.0,
                                    stack.engine_env()))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="flow check without a chip: the line it prints is "
                         "marked as a rehearsal and is never correct")
    ap.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="the file that lists the cells (the tests have a "
                         "toy one of their own)")
    args = ap.parse_args()
    cell = load_cell(args.workload, args.benchmark)
    if os.environ.get("JAX_PLATFORMS", "") == "cpu" and not args.rehearse_on_cpu:
        raise RunFailure("JAX_PLATFORMS=cpu: a measured run needs the TPU")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks_table = json.load(f)["peaks"]
    work = os.path.join(HERE, ".work", cell.name)
    os.makedirs(work, exist_ok=True)
    stack = Stack(cell, args.seed, work)

    def on_term(signum, frame):
        raise SystemExit(128 + signum)
    signal.signal(signal.SIGTERM, on_term)
    try:
        stack.start()
        perf = stack.get_json("/debug/perf")
        fp = perf["fingerprint"]
        platform, n_dev = fp["platform"], int(fp.get("n_devices", 0))
        if not args.rehearse_on_cpu:
            if platform != "tpu":
                raise RunFailure(f"the engine runs on {platform!r}, not a TPU")
            if n_dev < cell.chips:
                raise RunFailure(f"{n_dev} chips found, the cell needs "
                                 f"{cell.chips}")
            if fp["chip"] not in peaks_table:
                raise RunFailure(f"no peaks for device kind {fp['chip']!r}")
        print(json.dumps({"startup_seconds": perf["startup_seconds"],
                          "fingerprint": fp}), flush=True)
        t_ready = time.monotonic()
        obs = asyncio.run(measure(cell, stack, args))
        perf = stack.get_json("/debug/perf")
        flight = stack.get_json("/debug/requests")["requests"]
    finally:
        stack.stop()
    ref = check_reference(cell, stack, obs, work)
    trace = reduce_trace(cell, obs, work)

    seconds = float(args.seconds)
    e2e = stats.end_to_end(obs.records, seconds, cell.chips)
    e2e["setup_s"] = obs.setup_s
    expect = cell.manifest["expect"]
    compiled = sum(
        (prom.delta(obs.prom_open, obs.prom_close, n) or 0.0)
        for n in ("vllm:compile_events_total",
                  "vllm:unexpected_recompiles_total"))
    checks = {
        "all_tokens_returned": e2e["attempted"] > 0 and e2e["failed"] == 0,
        "nothing_compiled_in_window": compiled == 0,
        "tpu_pallas_ragged_tp": (
            platform == "tpu" and fp.get("use_pallas") is True
            and fp["attention_impl"] == expect["attention_impl"]
            and int(fp["tensor_parallel"]) == expect["tensor_parallel"]),
        "logprobs_match_reference": (
            ref["n_compared"] > 0 and ref["max_abs_err"] <= LOGPROB_TOL
            and ref["mean_abs_err"] <= LOGPROB_MEAN_TOL),
    }
    hbm = perf.get("hbm_bytes", {})
    device = {"platform": platform, "kind": fp["chip"], "count": n_dev,
              "memory_peak_bytes": max(
                  [int(hbm.get("peak", 0))]
                  + [int(d["bytes_in_use"]) for d in hbm.get("devices", [])])}
    result = {"correct": all(checks.values()) and not args.rehearse_on_cpu,
              "attempted": e2e["attempted"], "failed": e2e["failed"]}
    if args.trace:
        ctx = types.SimpleNamespace(
            records=obs.records, seconds=seconds, prom_open=obs.prom_open,
            prom_close=obs.prom_close, polls=obs.polls, flight=flight,
            trace=trace, hf=cell.hf, manifest=cell.manifest, mix=cell.mix,
            chips=cell.chips, peaks=peaks_table.get(fp["chip"]))
        metrics = {}
        for m in cell.per_layer:
            v = layers.read(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if trace:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            result["breakdown"] = {
                "device_ops": [[o[0], o[1]] for o in trace["ops"][:10]],
                "idle_gaps": trace["idle_gaps"][:10]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
    result.update(metrics=metrics, device=device)
    # why `correct` is what it is, on a line of its own before the result
    t_open = T_START + obs.setup_s
    phases = {"start_to_ready": t_ready - T_START,
              "warm_up": obs.t_warm - t_ready,
              "probe": obs.t_probe - obs.t_warm,
              "ramp": t_open - obs.t_probe}
    print(json.dumps({"checks": checks, "reference": ref,
                      "setup_phases": phases,
                      "rehearsal": args.rehearse_on_cpu}), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except RunFailure as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
