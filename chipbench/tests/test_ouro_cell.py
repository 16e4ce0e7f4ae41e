"""The Ouro cell: its files as the benchmark finds them, the two loop
per-layer metrics on hand-made observations, and one CPU rehearsal of the
cell at toy size (``tests/configs/tiny-ouro``: the reference child holds
the served log-probabilities against ``reference/ouro.py``; the values
are a CPU's and mean nothing)."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import layers, shapes, shapes_loop

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "chipbench", "tests", "BENCHMARK.ouro.json")
CELL = "ouro-2.6b.decode-pool-bound"
LOOP = ("loop_decode_hbm_floor_pct", "loop_passes_per_layer_step")
P = "vllm:loop_layer_"
# the catalog's config (model-configs guide, Ouro-2.6B), as
# https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json
CATALOG = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


def published() -> dict:
    with open(os.path.join(ROOT, "chipbench", "configs", "ouro-2.6b",
                           "config.json")) as f:
        return json.load(f)


def ctx(**kw):
    base = dict(records=[], seconds=10.0, prom_open={}, prom_close={},
                polls=[], flight=[], trace=None, hf=published(),
                manifest={"decode_slots": 64, "token_budget": 2048},
                mix={}, chips=1,
                peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_the_cell_and_its_metrics_are_listed_as_the_issue_says():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    (cell,) = [w for w in bm["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b", "decode-pool-bound", 1)
    assert bm["workloads"][-1] is cell  # appended, nothing moved
    (cfg,) = [c for c in bm["configs"] if c["name"] == "ouro-2.6b"]
    assert cfg["reduced"] == [] and cfg["source"].endswith(
        "ByteDance/Ouro-2.6B/blob/main/config.json")
    listed = {m["name"] for m in bm["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(LOOP) <= listed
    assert [m["name"] for m in bm["per_layer"][-2:]] == list(LOOP)
    for m in bm["per_layer"][-2:]:
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
    assert listed - set(LOOP) == {
        "frontend_overhead_ms", "server_deliver_ms", "queue_wait_ms",
        "decode_attn_busy_pct", "ragged_attn_busy_pct",
        "ragged_attn_narrow_walk_pct"}
    # readers that find nothing in this cell: one counts one pass; two
    # find their program by a (rows, hidden) bf16 activation, and this
    # stack's residual stream is float32 (PERF.md section 7)
    assert not listed & {"decode_hbm_floor_pct", "decode_step_dev_ms",
                         "ragged_step_dev_ms", "prefix_hit_pct"}
    # the rate spread by 2.0-2.15 % over two sets of six seeds on the chip,
    # half its bound and more (the requests that start in a window differ
    # by ten with the seed's order, a 293 ms stall each): the cell does not
    # report it, nor the layer metrics that move it (PERF.md section 2)
    reported = {m["name"] for m in bm["end_to_end"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert reported == {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}
    assert not [m["name"] for m in bm["per_layer"]
                if CELL in m.get("workloads", ())
                and m["moves"] not in reported]
    mix = json.load(open(os.path.join(ROOT, "chipbench", "traffic",
                                      "decode-pool-bound.json")))
    assert (mix["loop"], mix["callers"], mix["pairs"], mix["ramp_s"]) == (
        "closed", 12, 128, 20)
    assert (mix["prompt_len"], mix["output_len"]) == (
        {"kind": "uniform", "min": 64, "max": 128},
        {"kind": "uniform", "min": 96, "max": 224})


def test_the_configuration_is_the_catalog_file_key_for_key():
    hf = published()
    assert hf == CATALOG
    with open(os.path.join(ROOT, "chipbench", "configs", "ouro-2.6b",
                           "manifest.json")) as f:
        man = json.load(f)
    assert man["reduced"] == {} and man["reference"] == "ouro"
    assert {"weights", "residual_stream", "max_model_len", "tensor_names",
            "exit_gate", "from_the_implementation",
            "logprob_tolerance"} <= set(man["assumed"])
    # the manifest's and the issue's arithmetic
    assert shapes.layer_params(hf) == 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert shapes_loop.decode_weight_bytes(hf) == pytest.approx(
        19.93e9, rel=1e-3)  # 4 x 4.93 GB of layers + 0.20 GB of head
    assert shapes_loop.kv_bytes_per_token(hf) == 1_572_864
    assert 12 * (128 + 224) // 16 == 264  # blocks at the cell's worst


def test_passes_per_layer_step_on_a_recorded_counter_delta():
    """Counter values as LoopCounters.record leaves them after 100 decode
    steps and 10 ragged steps: 48 layers, 4 passes each."""
    forwards = 100 + 10
    close = {P + "passes_total": 48 * 4.0 * forwards,
             P + "steps_total": 48.0 * forwards}
    c = ctx(prom_open=dict.fromkeys(close, 0.0), prom_close=close)
    assert layers.read("loop_passes_per_layer_step", c) == 4.0
    # one pass in four left out of the decode steps would show
    short = dict(close)
    short[P + "passes_total"] -= 48.0 * 100
    c = ctx(prom_open=dict.fromkeys(close, 0.0), prom_close=short)
    assert layers.read("loop_passes_per_layer_step", c) == pytest.approx(
        4.0 - 100 / 110)
    # a program without the counters (the parent): left out, no crash
    old = ctx(prom_open={}, prom_close={"vllm:ragged_dispatches_total": 9.0})
    assert [layers.read(n, old) for n in LOOP] == [None, None]


def test_decode_floor_on_hand_made_operations():
    polls = [{"vllm:kv_blocks_total": 330.0, "vllm:kv_blocks_free": f}
             for f in (190.0, 200.0, 210.0)]  # 130 blocks = 2,080 tokens
    trace = {"busy_s": 3.8, "window_s": 4.0, "ops": [],
             "programs": {"loop_decode": {"count": 3, "total_s": 0.15,
                                          "durations_ms": [49.0, 50.0, 51.0]},
                          "other": {"count": 9, "total_s": 3.0,
                                    "durations_ms": [330.0] * 9}}}
    c = ctx(trace=trace, polls=polls)
    floor = shapes_loop.decode_step_floor_s(c.hf, 2080, 1, 819e9)
    assert floor == pytest.approx((19.93e9 + 2080 * 1_572_864) / 819e9,
                                  rel=1e-3)
    got = layers.read("loop_decode_hbm_floor_pct", c)
    assert got == pytest.approx(100.0 * floor / 0.050)
    assert 55.0 < got < 60.0
    # four fifths of those bytes are there only because of the loop
    once = shapes.decode_step_floor_s(c.hf, 2080 / 4, 1, 819e9)
    assert once / floor < 0.26
    # no trace, no such program, or a stack run once: nothing to read
    assert layers.read("loop_decode_hbm_floor_pct", ctx(polls=polls)) is None
    assert layers.read("loop_decode_hbm_floor_pct", ctx(
        trace={**trace, "programs": {"decode": trace["programs"]["other"]}},
        polls=polls)) is None
    qwen = {k: v for k, v in c.hf.items() if k != "total_ut_steps"}
    assert layers.read("loop_decode_hbm_floor_pct", ctx(
        trace=trace, polls=polls, hf=qwen)) is None
    # the program is found by its module's name, after the rules of the
    # metrics that were there (a first match wins)
    spec = layers.load_spec("loop_decode_hbm_floor_pct")
    assert spec["program"] == "loop_decode"
    assert spec["module"] == "decode_multi_step"
    from chipbench import trace_reduce

    dev = {"modules": [["jit_decode_multi_step(77)", 0, 1000],
                       ["jit_ragged_step(78)", 2000, 1000]],
           "ops": [["%fusion.1 = f32[64,1,2048]{2,0,1} fusion(%p)", 10, 100],
                   ["%fusion.2 = f32[1,2048,2048]{2,1,0} fusion(%p)", 2010,
                    100]]}
    rules = {"decode": {"contains_op": r"bf16\[64,1,2048\]"},
             "ragged": {"contains_op": r"bf16\[1,2048,2048\]"},
             "loop_decode": {"module": spec["module"]}}
    assert trace_reduce.classify_modules(dev, rules) == {
        "jit_decode_multi_step(77)": "loop_decode",
        "jit_ragged_step(78)": "other"}


def test_cpu_rehearsal_of_the_looped_cell():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "tiny-ouro.tiny", "--seed", str(2 ** 31 + 31),
         "--seconds", "5", "--trace", "1", "--rehearse-on-cpu",
         "--benchmark", TOY],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()]
    line, why = lines[-1], lines[-2]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["correct"] is False
    assert why["checks"]["logprobs_match_reference"] is True
    assert why["checks"]["nothing_compiled_in_window"] is True
    assert why["reference"]["max_abs_err"] < 1e-3  # float32 on both sides
    # no device plane in a CPU trace: the trace metric is left out
    assert set(line["metrics"]) == {
        "step_host_ms", "stream_fill_pct", "loop_passes_per_layer_step"}
    assert line["metrics"]["loop_passes_per_layer_step"]["value"] == 4.0
