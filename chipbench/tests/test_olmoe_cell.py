"""The OLMoE cell: its files as the benchmark finds them, the four MoE
per-layer metrics on hand-made observations, and one CPU rehearsal of the
cell at toy size (``tests/configs/tiny-olmoe``: the reference child holds
the served log-probabilities against ``reference/olmoe.py``; the values
are a CPU's and mean nothing)."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import layers, shapes_moe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "chipbench", "tests", "BENCHMARK.olmoe.json")
CELL = "olmoe-1b-7b-l8.decode-heavy"
MOE = ("moe_busy_pct", "moe_expert_hbm_floor_pct", "moe_load_max_over_mean",
       "moe_padding_rows_pct")
P = "vllm:moe_"


def published() -> dict:
    with open(os.path.join(ROOT, "chipbench", "configs", "olmoe-1b-7b-l8",
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
        "olmoe-1b-7b-l8", "decode-heavy", 1)
    (cfg,) = [c for c in bm["configs"] if c["name"] == "olmoe-1b-7b-l8"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    listed = {m["name"] for m in bm["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(MOE) <= listed
    # a reader that counts a dense FFN, and a metric no cell asked for
    assert not listed & {"decode_hbm_floor_pct", "attn_kernel_busy_pct"}
    for m in bm["end_to_end"]:
        assert "workloads" not in m or CELL in m["workloads"], m["name"]


def test_the_configuration_is_the_catalog_file_less_eight_layers():
    hf = published()
    catalog = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 16,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    assert {k for k in catalog if hf.get(k) != catalog[k]} == {
        "num_hidden_layers"}
    assert hf["num_hidden_layers"] == 8
    # the manifest's arithmetic
    assert shapes_moe.layer_params(hf) == pytest.approx(419.6e6, rel=1e-3)
    assert shapes_moe.weight_bytes(hf) == pytest.approx(7.1e9, rel=0.01)
    assert shapes_moe.weight_bytes({**hf, "num_hidden_layers": 16}) == (
        pytest.approx(13.8e9, rel=0.01))


def test_counter_metrics_on_a_recorded_counter_delta():
    """Counter values as MoeCounters.record leaves them after 100 decode
    steps of 64 live rows and 10 ragged steps of 250 live tokens, 8
    layers, 8 experts a token, every expert touched in every layer."""
    live = 100 * 64 + 10 * 250
    rows = 100 * 64 + 10 * 2048
    close = {P + "routed_tokens_total": live * 8 * 8.0,
             P + "padding_rows_total": (rows - live) * 8.0,
             P + "expert_load_max_total": 1.5 * live * 8 * 8 / 64,
             P + "expert_load_mean_total": live * 8 * 8 / 64,
             P + "decode_experts_touched_total": 100 * 8 * 64.0,
             P + "decode_layer_steps_total": 100 * 8.0}
    c = ctx(prom_open=dict.fromkeys(close, 0.0), prom_close=close)
    assert layers.read("moe_load_max_over_mean", c) == pytest.approx(1.5)
    pad = layers.read("moe_padding_rows_pct", c)
    assert pad == pytest.approx(100.0 * (rows - live) / rows)
    # padding and live rows account for every row of the stream
    assert pad + 100.0 * live / rows == pytest.approx(100.0)
    # a program without the counters (the parent): left out, no crash
    old = ctx(prom_open={}, prom_close={"vllm:ragged_dispatches_total": 9.0})
    assert [layers.read(n, old) for n in MOE] == [None] * 4


def test_trace_metrics_on_hand_made_operations():
    meta = "%ragged-dot-metadata = (s32[65]{0}, s32[64]{0}) custom-call(%gs)"
    ops = [
        # the decode program's grouped matmuls: 512 rows; 0.96 ms a run
        ["a", 0.96e-3 * 800, 800, "%ragged-dot-none.2 = bf16[512,1024]{1,0} "
                                  "custom-call(%get-tuple-element.1, %x)"],
        ["b", 0.96e-3 * 800, 800, "%ragged-dot-none.1 = bf16[512,1024]{1,0} "
                                  "custom-call(%get-tuple-element.1, %x)"],
        ["c", 0.96e-3 * 800, 800, "%ragged-dot-none = bf16[512,2048]{1,0} "
                                  "custom-call(%get-tuple-element.1, %h)"],
        # the ragged program's: 16384 rows, not the decode step's
        ["d", 0.5, 80, "%ragged-dot-none.5 = bf16[16384,1024]{1,0} "
                       "custom-call(%get-tuple-element.9, %x)"],
        ["e", 0.01, 880, meta],
        # mentions a grouped matmul as an operand only
        ["f", 0.3, 800, "%multiply_multiply_fusion = bf16[512,1024]{1,0} "
                        "fusion(%ragged-dot-none.2, %ragged-dot-none.1)"],
    ]
    close = {P + "decode_experts_touched_total": 100 * 8 * 64.0,
             P + "decode_layer_steps_total": 100 * 8.0}
    c = ctx(trace={"busy_s": 4.0, "window_s": 4.4, "ops": ops,
                   "programs": {}},
            prom_open=dict.fromkeys(close, 0.0), prom_close=close)
    busy = layers.read("moe_busy_pct", c)
    assert busy == pytest.approx(100.0 * (3 * 0.768 + 0.5 + 0.01) / 4.0)
    # 64 experts x 4.19 MB + rows in and out, at 819 GB/s = 0.3316 ms
    floor = shapes_moe.grouped_matmul_floor_s(c.hf, 64, 512, c.peaks)
    assert floor == pytest.approx(
        (64 * 2 * 2048 * 1024 + 2 * 512 * 3072) / 819e9)
    got = layers.read("moe_expert_hbm_floor_pct", c)
    assert got == pytest.approx(100.0 * floor / 0.96e-3)
    assert 30.0 < got < 40.0
    # no trace, or a trace without such operations: nothing to read
    assert layers.read("moe_expert_hbm_floor_pct", ctx(
        prom_open=c.prom_open, prom_close=close)) is None
    assert layers.read("moe_expert_hbm_floor_pct", ctx(
        trace={"busy_s": 1.0, "window_s": 1.0, "ops": ops[3:],
               "programs": {}},
        prom_open=c.prom_open, prom_close=close)) is None


def test_cpu_rehearsal_of_the_moe_cell():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "tiny-olmoe.tiny", "--seed", str(2 ** 31 + 26),
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
    # no device plane in a CPU trace: the two trace metrics are left out
    assert set(line["metrics"]) == {
        "step_host_ms", "stream_fill_pct", "moe_load_max_over_mean",
        "moe_padding_rows_pct"}
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["moe_load_max_over_mean"] >= 1.0
    assert 0.0 < m["moe_padding_rows_pct"] < 100.0
