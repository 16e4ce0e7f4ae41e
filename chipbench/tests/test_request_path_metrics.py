"""The per-layer metrics that read a request's time to first token in
parts, the event loop's heartbeat and the slow-step counter: their files,
their readers on hand-made observations, what they read from a program
that has none of it (the parent: nothing, without an error), and one CPU
rehearsal in which the program really exports what they read (its values
are a CPU's and mean nothing)."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import layers, timeline
from chipbench.stats import Record

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "chipbench", "tests", "BENCHMARK.request_path.json")
SPANS = {"intake_wait_ms": ("enqueued", "arrival"),
         "stream_wait_ms": ("admitted", "first_launch"),
         "prefill_steps_ms": ("first_launch", "first_token")}
COUNTERS = ("slow_step_time_pct", "server_loop_lag_ms",
            "server_loop_lag_in_wait_pct", "server_loop_busy_pct")
NEW = (*SPANS, "router_to_handler_ms", "intake_behind_ragged_pct", *COUNTERS)
LOOP_CPU, LOOP_WALL = ("vllm:server_loop_cpu_seconds_total",
                       "vllm:server_loop_wall_seconds_total")
LAG, TICKS, IN_WAIT, SLOW, HOST, WAIT = (
    "vllm:server_loop_lag_seconds_total", "vllm:server_loop_ticks_total",
    "vllm:server_loop_lag_in_wait_seconds_total",
    "vllm:engine_slow_step_seconds_total", "vllm:engine_host_seconds_total",
    "vllm:engine_device_wait_seconds_total")


def ctx(**kw):
    base = dict(records=[], seconds=10.0, prom_open={}, prom_close={},
                polls=[], flight=[], trace=None, hf={}, manifest={},
                mix={}, chips=1, peaks=None)
    base.update(kw)
    return types.SimpleNamespace(**base)


def rec(i, due, ok=True):
    r = Record(i, f"cb-{i}", 8, 4, due)
    r.sent, r.status, r.done = due, (200 if ok else 500), ok
    r.token_times = [due + 0.5 + 0.1 * k for k in range(4)] if ok else []
    return r


def flight(i, base, after="decode", hop=0.004, **gaps):
    """An engine record whose stamps lie ``gaps`` seconds apart."""
    tl, t = {"received": base}, base
    for stamp, gap in (("enqueued", 0.0001), ("arrival", 0.003),
                       ("admitted", 0.0002), ("first_launch", 0.001),
                       ("first_token", 0.085)):
        t += gaps.get(stamp, gap)
        tl[stamp] = t
    return {"client_request_id": f"cb-{i}", "timeline": tl,
            "intake_after": after, "received_unix": 1.7e9 + base,
            "router_sent_unix": 1.7e9 + base - hop}


def parent_flight(i, base):
    """What the parent's /debug/requests holds: none of the new stamps."""
    return {"client_request_id": f"cb-{i}", "received_unix": 1.7e9 + base,
            "timeline": {"received": base, "admitted": base + 0.004,
                         "first_token": base + 0.09,
                         "first_chunk_written": base + 0.093}}


RECORDS = [rec(0, 1.0), rec(1, 2.0), rec(2, 3.0), rec(3, 4.0, ok=False),
           rec(4, 99.0)]                            # due after the window


@pytest.mark.parametrize("name", NEW)
def test_metric_file_loads_names_a_reader_and_lists_cells_that_exist(name):
    spec = layers.load_spec(name)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    (entry,) = [m for m in bm["per_layer"] if m["name"] == name]
    assert (spec["layer"], spec["unit"], spec["source"]) == (
        entry["layer"], entry["unit"], entry["source"])
    assert entry["moves"] == "ttft_p50_ms"
    assert entry["workloads"] == [w["name"] for w in bm["workloads"]]
    own = os.path.exists(os.path.join(layers.DIR, name + ".py"))
    assert own == (name not in COUNTERS)
    assert own or spec["reader"] in layers.READERS
    with open(TOY) as f:
        assert name in [m["name"] for m in json.load(f)["per_layer"]]


@pytest.mark.parametrize("name", NEW)
def test_the_parents_records_and_scrapes_give_nothing_to_read(name):
    old = ctx(records=RECORDS,
              flight=[parent_flight(i, float(i)) for i in range(5)],
              prom_open={HOST: 1.0, WAIT: 9.0},
              prom_close={HOST: 2.0, WAIT: 19.0})
    assert layers.read(name, old) is None
    assert layers.read(name, ctx()) is None        # nothing at all


@pytest.mark.parametrize("name", SPANS)
def test_span_metrics_are_medians_over_the_counted_requests(name):
    start, end = SPANS[name]
    fl = [flight(0, 1.0, **{end: 0.010}), flight(1, 2.0, **{end: 0.030}),
          flight(2, 3.0, **{end: 0.020}),
          flight(3, 4.0, **{end: 5.0}),     # its request failed
          flight(4, 99.0, **{end: 7.0}),    # due after the window
          flight(5, 1.0, **{end: 9.0})]     # nobody's request
    c = ctx(records=RECORDS, flight=fl)
    assert layers.read(name, c) == pytest.approx(20.0)
    spec = layers.load_spec(name)
    assert (spec["start"], spec["end"]) == (start, end)
    assert timeline.span_median_ms(c, spec) == pytest.approx(20.0)
    # a record that lacks one of the two stamps is left out, not guessed
    del fl[1]["timeline"][start]
    assert layers.read(name, c) == pytest.approx(15.0)


def test_router_to_handler_ms_reads_the_records_two_wall_clock_stamps():
    fl = [flight(0, 1.0, hop=0.002), flight(1, 2.0, hop=0.012),
          flight(2, 3.0, hop=0.026)]
    c = ctx(records=RECORDS, flight=fl)
    assert layers.read("router_to_handler_ms", c) == pytest.approx(12.0,
                                                                   abs=1e-3)
    del fl[2]["router_sent_unix"]       # a request that came past the router
    assert layers.read("router_to_handler_ms", c) == pytest.approx(7.0,
                                                                   abs=1e-3)


def test_intake_behind_ragged_pct_is_a_share_of_the_counted_requests():
    fl = [flight(0, 1.0, after="ragged"), flight(1, 2.0, after="decode"),
          flight(2, 3.0, after="idle"), flight(3, 4.0, after="ragged"),
          flight(4, 99.0, after="ragged")]
    c = ctx(records=RECORDS, flight=fl)
    assert layers.read("intake_behind_ragged_pct", c) == pytest.approx(
        100.0 / 3)
    assert len(timeline.joined(c)) == 3
    for f in fl:
        f["intake_after"] = "decode"
    assert layers.read("intake_behind_ragged_pct", c) == 0.0


def test_counter_metrics_on_hand_made_scrapes():
    c = ctx(prom_open={LAG: 1.0, TICKS: 100.0, IN_WAIT: 0.5, SLOW: 0.0,
                       HOST: 10.0, WAIT: 80.0, LOOP_CPU: 3.0,
                       LOOP_WALL: 20.0},
            prom_close={LAG: 7.0, TICKS: 500.0, IN_WAIT: 5.0, SLOW: 0.5,
                        HOST: 15.0, WAIT: 125.0, LOOP_CPU: 18.0,
                        LOOP_WALL: 70.0})
    assert layers.read("server_loop_busy_pct", c) == pytest.approx(30.0)
    assert layers.read("server_loop_lag_ms", c) == pytest.approx(15.0)
    assert layers.read("server_loop_lag_in_wait_pct", c) == pytest.approx(75.0)
    assert layers.read("slow_step_time_pct", c) == pytest.approx(1.0)
    # a sound run: the family is exported at 0 and the metric reads 0
    c.prom_close[SLOW] = 0.0
    assert layers.read("slow_step_time_pct", c) == 0.0


def test_cpu_rehearsal_prints_every_new_metric():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "tiny-qwen3.tiny", "--seed", str(2 ** 31 + 41),
         "--seconds", "5", "--trace", "1", "--rehearse-on-cpu",
         "--benchmark", TOY],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["correct"] is False
    assert set(line["metrics"]) == set(NEW)
    for name, m in line["metrics"].items():
        assert isinstance(m["value"], float) and m["value"] >= 0.0, name
    assert line["metrics"]["prefill_steps_ms"]["value"] > 0.0
    assert line["metrics"]["server_loop_lag_ms"]["value"] > 0.0
    for name in ("intake_behind_ragged_pct", "slow_step_time_pct",
                 "server_loop_lag_in_wait_pct", "server_loop_busy_pct"):
        assert line["metrics"][name]["value"] <= 100.0, name
