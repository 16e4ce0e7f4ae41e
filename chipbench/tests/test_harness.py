"""The harness end to end: it refuses a measured run without a TPU, and
one CPU rehearsal of a toy cell prints a last line of the agreed shape
(its values are a CPU's and mean nothing)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = [sys.executable, os.path.join(ROOT, "chipbench", "run.py")]
TINY = os.path.join(ROOT, "chipbench", "tests", "BENCHMARK.tiny.json")
CPU = {**os.environ, "JAX_PLATFORMS": "cpu"}


def test_a_measured_run_refuses_the_cpu():
    p = subprocess.run(
        RUN + ["--workload", "qwen3-8b-l16.decode-heavy", "--seed", "1",
               "--seconds", "1", "--trace", "0"],
        env=CPU, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_an_unknown_workload_is_refused():
    p = subprocess.run(
        RUN + ["--workload", "no-such.cell", "--seed", "1", "--seconds", "1",
               "--trace", "0"], env=CPU, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("workload,trace", [
    ("tiny-qwen3.tiny", 0),           # closed loop, end-to-end metrics
    ("tiny-qwen3.tiny-open", 1),      # open loop, per-layer metrics
    ("tiny-qwen3-tp2.tiny", 0),       # sharded path on two virtual devices
])
def test_cpu_rehearsal_prints_a_line_of_the_agreed_shape(workload, trace):
    p = subprocess.run(
        RUN + ["--workload", workload, "--seed", str(2 ** 31 + 11),
               "--seconds", "5", "--trace", str(trace), "--rehearse-on-cpu",
               "--benchmark", TINY],
        env=CPU, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()]
    line, why = lines[-1], lines[-2]
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    # a CPU run is never correct, and says what it ran on
    assert line["correct"] is False and why["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert why["checks"]["logprobs_match_reference"] is True
    assert why["checks"]["nothing_compiled_in_window"] is True
    with open(TINY) as f:
        bm = json.load(f)
    section = bm["per_layer"] if trace else bm["end_to_end"]
    units = {m["name"]: m["unit"] for m in section}
    assert line["metrics"]
    for name, m in line["metrics"].items():
        assert m["unit"] == units[name] and isinstance(m["value"], float)
    if trace:
        # no device plane in a CPU trace: trace metrics are left out
        assert "device_idle_pct" not in line["metrics"]
        assert "queue_wait_ms" in line["metrics"]
    else:
        assert set(line["metrics"]) == set(units)
    # nothing outlives a run
    out = subprocess.run(["pgrep", "-f", "chipbench/.work/" + workload],
                         capture_output=True, text=True).stdout
    assert out.strip() == ""
