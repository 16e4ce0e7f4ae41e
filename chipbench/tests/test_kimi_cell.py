"""The Kimi-Linear cell: its files as the benchmark finds them,
shapes_kimi_linear's arithmetic against the issue's, the three new
per-layer metrics on hand-made operations and counters, and one CPU
rehearsal of the cell at toy size (``tests/configs/tiny-kimi-linear``:
the reference child holds the served log-probabilities against
``reference/kimi_linear.py``; the values are a CPU's and mean nothing)."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import layers, shapes_kimi_linear, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "chipbench", "tests", "BENCHMARK.kimi.json")
CONFIG = "kimi-linear-48b-a3b-ep16"
CELL = CONFIG + ".long-decode"
NEW = ("kda_mla_decode_hbm_floor_pct", "hybrid_mla_attn_roofline_pct",
       "recurrent_state_cache_share_pct")
# the accepted metrics whose readers, unedited, read this configuration
# right and that move an end-to-end metric the cell reports
JOINED = {"decode_step_dev_ms", "kda_decode_busy_pct",
          "kda_decode_hbm_floor_pct", "mla_attn_busy_pct", "moe_busy_pct",
          "moe_held_pairs_pct", "moe_grouped_kernel_path_pct"}
REDUCED = {"vocab_size": 20480}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def config() -> dict:
    with open(os.path.join(ROOT, "chipbench", "configs", CONFIG,
                           "config.json")) as f:
        return json.load(f)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def ctx(**kw):
    base = dict(records=[], seconds=10.0, prom_open={}, prom_close={},
                polls=[], flight=[], trace=None, hf=config(),
                manifest={"decode_slots": 64, "token_budget": 2048,
                          "block_size": 16},
                mix={}, chips=1, peaks=PEAKS)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_the_cell_and_its_metrics_are_listed_as_the_issue_says():
    bm = benchmark()
    (cell,) = [w for w in bm["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "long-decode", 1)
    (cfg,) = [c for c in bm["configs"] if c["name"] == CONFIG]
    assert cfg["source"].endswith(
        "moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    assert set(cfg["reduced"]) == {"n_routed_experts_held", "vocab_size"}
    assert not [w for w in bm["workloads"] if w["chips"] != 1]
    # membership, not counts or positions: later PRs append
    new = [m for m in bm["per_layer"] if m["name"] in NEW]
    assert len(new) == 3 and all(CELL in m["workloads"] for m in new)
    reported = {m["name"] for m in bm["end_to_end"]
                if "workloads" not in m or CELL in m["workloads"]}
    # NOT as the issue wrote it (the gap, the rate and set-up): under its
    # own rule (listed iff the spread is under half the bound in each of
    # the builder's two sets of six) the rate read 1.28 and 2.35 % against
    # 2.0 and the gap 3.11 and 0.83 % against 2.5; over the twelve seeds
    # the gap spreads 1.15 %, the rate 1.9 %, so the gap is listed and the
    # rate is not (PERF.md sections 2 and 7, PR 49)
    assert reported == {"tpot_p50_ms", "setup_s"}
    mine = {m["name"] for m in bm["per_layer"]
            if CELL in m.get("workloads", ())}
    assert mine == set(NEW) | JOINED
    # a layer metric lists only cells that report what it moves: the KDA
    # span kernel's three move ttft_p50_ms and the six scheduler, KV,
    # step-loop and device metrics the rate, which this cell does not list
    assert not [m["name"] for m in bm["per_layer"]
                if CELL in m.get("workloads", ())
                and m["moves"] not in reported]
    for name in NEW:
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "layer_metrics", name + ".json"))


def test_the_traffic_is_the_accepted_mix_unedited():
    mix = traffic.load_mix("long-decode")
    assert {k: mix[k] for k in ("loop", "callers", "pairs", "ramp_s",
                                "prompt_len", "output_len", "think_s",
                                "prefix_sharing")} == {
        "loop": "closed", "callers": 64, "pairs": 128, "ramp_s": 20,
        "prompt_len": {"kind": "uniform", "min": 1024, "max": 2048},
        "output_len": {"kind": "uniform", "min": 768, "max": 1536},
        "think_s": {"kind": "uniform", "min": 0.0, "max": 0.25},
        "prefix_sharing": "none"}


def test_the_configuration_keeps_every_published_number():
    from tests.test_kimi_linear import CATALOG  # the catalog row's config

    hf = config()
    assert {k: v for k, v in hf.items() if k in CATALOG} == {
        **CATALOG, **REDUCED}
    assert {k: v for k, v in hf.items() if k not in CATALOG} == {
        "n_routed_experts_held": 16, "routed_expert_offset": 0}
    with open(os.path.join(ROOT, "chipbench", "configs", CONFIG,
                           "manifest.json")) as f:
        man = json.load(f)
    assert set(man["reduced"]) == {"n_routed_experts_held", "vocab_size"}
    assert (man["reduced"]["vocab_size"]["published"],
            man["reduced"]["vocab_size"]["run"]) == (163840, 20480)
    assert (man["reduced"]["n_routed_experts_held"]["published"],
            man["reduced"]["n_routed_experts_held"]["run"]) == (256, 16)
    assert man["published"]["num_hidden_layers"] == hf["num_hidden_layers"]
    assert man["reference"] == "kimi_linear"
    assert "16 that share each layer" in man["deployment"]
    assert "no layer left out" in man["deployment"]
    assert "engine_env" not in man
    assert man["engine_flags"] == ["--max-model-len", "8192",
                                   "--tensor-parallel-size", "1"]
    assert (man["token_budget"], man["decode_slots"], man["block_size"]) == (
        2048, 64, 16)
    assert {"weights", "kda_block", "kda_neg_eigval", "mla", "head_dim",
            "attention_form", "router", "dtype", "max_model_len", "vmem",
            "logprob_tolerance"} <= set(man["assumed"])
    assert set(man["bytes"]) == {"weights", "recurrent_state", "latent_pool"}


def test_shapes_kimi_linear_holds_the_issues_arithmetic():
    hf = config()
    s = shapes_kimi_linear
    assert s.layer_counts(hf) == (7, 20)
    assert s.shapes_kda.kda_layer_params(hf) == pytest.approx(39.5e6, rel=2e-3)
    assert s.mla_layer_params(hf) == pytest.approx(29.1e6, rel=1e-3)
    assert s.dense_mlp_params(hf) == pytest.approx(63.7e6, rel=1e-3)
    assert s.shapes_kda.expert_params(hf) == pytest.approx(7.08e6, rel=1e-3)
    assert s.model_params(hf) == pytest.approx(4296e6, rel=1e-3)
    assert 2 * s.model_params(hf) == pytest.approx(8.59e9, rel=1e-3)
    whole = {**hf, "n_routed_experts_held": 256, "vocab_size": 163840}
    assert s.model_params(whole) == pytest.approx(49.1e9, rel=2e-3)
    assert s.latent_bytes_per_token(hf) == 2 * 7 * 576 == 8064
    assert s.latent_bytes_per_token(hf, lanes=640) == 8960
    assert 64 * s.recurrent_bytes_per_slot(hf) == pytest.approx(2.78e9,
                                                                rel=2e-3)
    # the issue's decode step: 64 live slots, 1.0-3.6 k of context (2.3 k
    # a slot here), every held expert touched: 8.5 GB of weights, 5.6 GB
    # of state and tails, 1.2 GB of latent rows, ~18 ms at 819 GB/s
    step = s.decode_step_bytes(hf, 16, 64, 64 * 2300)
    assert step == pytest.approx(15.2e9, rel=0.01)
    assert s.decode_step_floor_s(hf, 16, 64, 64 * 2300, 819e9) == (
        pytest.approx(18.6e-3, rel=0.01))
    state = 64 * 2 * s.recurrent_bytes_per_slot(hf)
    latent = 64 * 2300 * s.latent_bytes_per_token(hf)
    mixers = 2 * (20 * s.shapes_kda.kda_layer_params(hf)
                  + 7 * s.mla_layer_params(hf))
    assert (state + latent + mixers) / step == pytest.approx(0.57, abs=0.01)


DECODE_MS = [28.0, 27.0, 29.0]


def _trace(ops, window_s=4.0):
    return {"busy_s": 3.5, "window_s": window_s, "ops": ops,
            "programs": {"decode": {"count": 3, "durations_ms": DECODE_MS}}}


def test_the_decode_floor_on_hand_made_counters():
    counters = {"vllm:moe_decode_experts_touched_total": 14.0 * 26 * 100,
                "vllm:moe_decode_layer_steps_total": 26.0 * 100}
    polls = [{"vllm:num_requests_running": 64.0,
              "vllm:kv_blocks_total": 12288.0,
              "vllm:kv_blocks_free": 12288.0 - 64 * 2300 / 16}] * 3
    c = ctx(trace=_trace([]), prom_close=counters, polls=polls)
    want = 100 * shapes_kimi_linear.decode_step_floor_s(
        c.hf, 14, 64, 64 * 2300, 819e9) / 28.0e-3
    got = layers.read("kda_mla_decode_hbm_floor_pct", c)
    assert got == pytest.approx(want) and 50 < got < 100
    # another configuration, the parent (no counters), no trace: nothing
    solar = {**c.hf, "linear_attn_config": {"num_heads": 64, "head_dim": 128,
                                            "short_conv_kernel_size": 4}}
    assert layers.read("kda_mla_decode_hbm_floor_pct", ctx(
        trace=_trace([]), prom_close=counters, polls=polls, hf=solar)) is None
    assert layers.read("kda_mla_decode_hbm_floor_pct", ctx(
        trace=_trace([]), polls=polls)) is None
    assert layers.read("kda_mla_decode_hbm_floor_pct", ctx(
        prom_close=counters, polls=polls)) is None


LATENT_OPS = [
    ["latent_paged_attention.16", 0.56, 280,
     "%latent_paged_attention.16 = bf16[4,512,512]{2,1,0} custom-call(%q)"],
    ["latent_paged_attention.14", 0.14, 70,
     "%latent_paged_attention.14 = bf16[4,512,512]{2,1,0} custom-call(%q)"],
    ["fusion.9", 1.0, 900, "%fusion.9 = bf16[64,2304] fusion(%p)"]]


def test_the_latent_roofline_counts_calls_by_the_mla_layers():
    # the profiled seconds: 4 s mid-window of 51, polls 24 and 28. There:
    # 80 decode dispatches of 7 MLA layers and 10 ragged ones: 630 calls
    # of 2 ms (0.70 s over 350 executions in the trace), whose one-token
    # spans read 4.0e8 rows: the rows' bytes bound them
    there = {"vllm:mla_scored_pairs_total": 4.0e8,
             "vllm:mla_context_rows_total": 4.0e8,
             "vllm:mla_query_tokens_total": 80 * 64 * 7.0,
             "vllm:ragged_dispatches_total": 10.0,
             "vllm:decode_attn_calls_total": 560.0}
    ps = [{n: v * k / 4 for n, v in there.items()} for k in range(-96, 112, 4)]
    c = ctx(trace=_trace(LATENT_OPS), seconds=51.0, polls=ps,
            prom_open=ps[0], prom_close=ps[-1])
    floor, by = shapes_kimi_linear.shapes_mla.mla_attn_floor_s(
        c.hf, 4.0e8, 4.0e8, 80 * 64 * 7.0, PEAKS)
    assert by == "bytes"
    want = 100 * (floor / (10 * 7 + 560)) / 0.002
    got = layers.read("hybrid_mla_attn_roofline_pct", c)
    assert got == pytest.approx(want) and 0 < got < 100
    # the accepted reader multiplies the ragged dispatches by the stack's
    # 27 layers: 830 calls where there were 630
    assert layers.read("mla_attn_roofline_pct", c) == pytest.approx(
        want * 630 / 830)
    # Pangu's file (no layer lists), the parent (no counters): nothing
    pangu = {k: v for k, v in c.hf.items() if k != "linear_attn_config"}
    assert layers.read("hybrid_mla_attn_roofline_pct", ctx(
        trace=_trace(LATENT_OPS), seconds=51.0, polls=ps, hf=pangu)) is None
    bare = [{"vllm:ragged_dispatches_total": 9.0 * k} for k in range(52)]
    assert layers.read("hybrid_mla_attn_roofline_pct", ctx(
        trace=_trace(LATENT_OPS), seconds=51.0, polls=bare)) is None
    assert layers.read("hybrid_mla_attn_roofline_pct", ctx(polls=ps)) is None


def test_the_state_share_reads_the_two_gauges():
    c = ctx(prom_close={"vllm:recurrent_state_bytes": 2.78e9,
                        "vllm:kv_pool_bytes": 1.73e9})
    assert layers.read("recurrent_state_cache_share_pct", c) == (
        pytest.approx(100 * 2.78 / 4.51))
    # the parent exports no pool gauge, a dense model no state gauge
    assert layers.read("recurrent_state_cache_share_pct", ctx(
        prom_close={"vllm:recurrent_state_bytes": 2.78e9})) is None
    assert layers.read("recurrent_state_cache_share_pct", ctx(
        prom_close={"vllm:kv_pool_bytes": 1.73e9})) is None


def test_cpu_rehearsal_of_the_cell():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "tiny-kimi-linear.tiny",
         "--seed", str(2 ** 31 + 49), "--seconds", "5", "--trace", "1",
         "--rehearse-on-cpu", "--benchmark", TOY],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()]
    line, why = lines[-1], lines[-2]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["correct"] is False
    assert why["checks"]["logprobs_match_reference"] is True
    assert why["checks"]["nothing_compiled_in_window"] is True
    assert why["reference"]["max_abs_err"] < 1e-3  # float32 on both sides
    # no device plane in a CPU trace: the trace metrics are left out
    assert set(line["metrics"]) == {
        "step_host_ms", "stream_fill_pct", "moe_held_pairs_pct",
        "kv_used_peak_pct", "recurrent_state_cache_share_pct"}
    assert 35 < line["metrics"]["moe_held_pairs_pct"]["value"] < 65  # 4 of 8
