"""The per-layer metrics that read the engine's step clock, its kernel
names and its first-chunk stamp: their files, their patterns on hand-made
observations, and one CPU rehearsal in which the program really exports
what they read (its values are a CPU's and mean nothing)."""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import layers
from chipbench.stats import Record

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "chipbench", "tests", "BENCHMARK.tracing.json")
COUNTERS = ("step_host_ms", "device_wait_pct", "step_host_oncpu_pct")
KERNELS = {"decode_attn_busy_pct": "paged_decode_attention",
           "ragged_attn_busy_pct": "ragged_paged_attention",
           "kv_write_busy_pct": "kv_cache_write"}


def ctx(**kw):
    base = dict(records=[], seconds=10.0, prom_open={}, prom_close={},
                polls=[], flight=[], trace=None, hf={}, manifest={},
                mix={}, chips=1, peaks=None)
    base.update(kw)
    return types.SimpleNamespace(**base)


@pytest.mark.parametrize("name", [*COUNTERS, *KERNELS, "server_deliver_ms"])
def test_metric_file_loads_and_matches_benchmark_json(name):
    spec = layers.load_spec(name)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == name]
    assert (spec["layer"], spec["unit"], spec["source"]) == (
        entry["layer"], entry["unit"], entry["source"])
    own = os.path.exists(os.path.join(layers.DIR, name + ".py"))
    assert own == (name == "server_deliver_ms")
    assert own or spec["reader"] in layers.READERS


def test_counter_metrics_on_hand_made_scrapes():
    host, wait = ("vllm:engine_host_seconds_total",
                  "vllm:engine_device_wait_seconds_total")
    c = ctx(prom_open={host: 10.0, wait: 1.0,
                       "vllm:engine_host_cpu_seconds_total": 5.0,
                       "vllm:ragged_dispatches_total": 10.0,
                       "vllm:decode_dispatches_total": 100.0},
            prom_close={host: 13.0, wait: 2.0,
                        "vllm:engine_host_cpu_seconds_total": 7.4,
                        "vllm:ragged_dispatches_total": 20.0,
                        "vllm:decode_dispatches_total": 190.0})
    assert layers.read("step_host_ms", c) == pytest.approx(30.0)
    assert layers.read("device_wait_pct", c) == pytest.approx(25.0)
    assert layers.read("step_host_oncpu_pct", c) == pytest.approx(80.0)
    # a program without the step clock exports none of them: left out
    old = ctx(prom_open={"vllm:ragged_dispatches_total": 1.0},
              prom_close={"vllm:ragged_dispatches_total": 9.0})
    assert [layers.read(n, old) for n in COUNTERS] == [None] * 3


def test_kernel_patterns_pick_the_instruction_not_its_mentions():
    ops = [
        ["a", 0.30, 9, "%paged_decode_attention.1 = bf16[64,8,4,128]{3,2,1,0} "
                       "custom-call(s32[64,512]{1,0} %copy.3)"],
        ["b", 0.20, 9, "%ragged_paged_attention.7 = bf16[16,512,8,128]{3} "
                       "custom-call(%p, %kv_cache_write.2)"],
        ["c", 0.05, 9, "%kv_cache_write.2 = bf16[16,4859,16,16,128]{4} "
                       "custom-call(%x)"],
        # names the kernels as operands only
        ["d", 0.25, 9, "%fusion.9 = bf16[64,4096]{1,0} fusion("
                       "%paged_decode_attention.1, %ragged_paged_attention.7)"],
        ["e", 0.10, 9, "%AllocateBuffer.1 = s32[8]{0} custom-call()"],
        ["f", 0.10, 9, "%paged_decode_attention_helper = f32[] constant(0)"],
    ]
    c = ctx(trace={"busy_s": 1.0, "window_s": 2.0, "ops": ops,
                   "programs": {}})
    got = {n: layers.read(n, c) for n in KERNELS}
    assert got == pytest.approx({"decode_attn_busy_pct": 30.0,
                                 "ragged_attn_busy_pct": 20.0,
                                 "kv_write_busy_pct": 5.0})
    # the accepted sum counts every custom-call, AllocateBuffer among them
    assert layers.read("attn_kernel_busy_pct", c) == pytest.approx(65.0)
    # before the kernels had names there is nothing to read: 0, not a crash
    unnamed = ctx(trace={"busy_s": 1.0, "window_s": 2.0, "programs": {},
                         "ops": [["x", 0.5, 1, "%closed_call.36 = bf16[8]{0} "
                                  "custom-call(%q)"]]})
    assert [layers.read(n, unnamed) for n in KERNELS] == [0.0] * 3
    assert [layers.read(n, ctx()) for n in KERNELS] == [None] * 3


def test_server_deliver_ms_on_a_toy_flight():
    def rec(i, due, ok=True):
        r = Record(i, f"cb-{i}", 8, 4, due)
        r.sent, r.status, r.done = due, (200 if ok else 500), ok
        r.token_times = [due + 0.5 + 0.1 * k for k in range(4)] if ok else []
        return r

    def flight(i, first, written=None):
        tl = {"received": 0.0, "first_token": first}
        if written is not None:
            tl["first_chunk_written"] = written
        return {"client_request_id": f"cb-{i}", "timeline": tl}

    records = [rec(0, 1.0), rec(1, 2.0), rec(2, 3.0), rec(3, 4.0, ok=False),
               rec(4, 99.0)]                        # due after the window
    fl = [flight(0, 1.0, 1.010), flight(1, 2.0, 2.030), flight(2, 3.0, 3.020),
          flight(3, 4.0, 4.5), flight(4, 99.0, 99.9), flight(5, 1.0)]
    mod_spec = importlib.util.spec_from_file_location(
        "sdm", os.path.join(layers.DIR, "server_deliver_ms.py"))
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    c = ctx(records=records, flight=fl)
    assert mod.read(c, {}) == pytest.approx(20.0)
    assert layers.read("server_deliver_ms", c) == pytest.approx(20.0)
    # a program that takes no such stamp: nothing to read
    assert layers.read("server_deliver_ms", ctx(
        records=records, flight=[flight(i, 1.0) for i in range(3)])) is None


def test_cpu_rehearsal_prints_the_counter_metrics_and_the_deliver_span():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "tiny-qwen3.tiny", "--seed", str(2 ** 31 + 24),
         "--seconds", "5", "--trace", "1", "--rehearse-on-cpu",
         "--benchmark", TOY],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["correct"] is False
    assert set(line["metrics"]) == {*COUNTERS, "server_deliver_ms"}
    for name, m in line["metrics"].items():
        assert isinstance(m["value"], float) and m["value"] >= 0.0, name
    assert line["metrics"]["step_host_ms"]["value"] > 0.0
    assert 0.0 < line["metrics"]["step_host_oncpu_pct"]["value"] <= 100.5
    assert line["metrics"]["device_wait_pct"]["value"] < 100.0
