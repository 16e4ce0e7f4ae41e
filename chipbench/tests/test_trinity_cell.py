"""The Trinity-Large-Preview cell: its files as the benchmark finds them,
shapes_swa's arithmetic against the issue's table, the four new per-layer
metrics on hand-made operations and counters, and one CPU rehearsal of the
cell at toy size (``tests/configs/tiny-afmoe``: the reference child holds
the served log-probabilities against ``reference/afmoe.py``; the values are
a CPU's and mean nothing)."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import layers, shapes_swa, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "chipbench", "tests", "BENCHMARK.trinity.json")
CONFIG = "trinity-large-preview-ep16-l8"
CELL = CONFIG + ".long-context"
NEW = ("window_kv_used_peak_pct", "mixed_decode_attn_hbm_floor_pct",
       "mixed_ragged_attn_roofline_pct", "swa_moe_decode_hbm_floor_pct")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the catalog row's config (model-configs guide, Trinity-Large-Preview)
CATALOG = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 3072, "intermediate_size": 12288,
    "layer_types": PERIOD * 15, "load_balance_coeff": 5e-05,
    "max_position_embeddings": 262144, "model_type": "afmoe",
    "moe_intermediate_size": 3072, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 48, "num_dense_layers": 6,
    "num_expert_groups": 1, "num_experts": 256, "num_experts_per_tok": 4,
    "num_hidden_layers": 60, "num_key_value_heads": 8,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.448, "score_func": "sigmoid", "sliding_window": 4096,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}


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
                manifest={"decode_slots": 16, "token_budget": 4096,
                          "block_size": 16},
                mix={}, chips=1, peaks=PEAKS)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_the_cell_and_its_metrics_are_listed_as_the_issue_says():
    bm = benchmark()
    (cell,) = [w for w in bm["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "long-context", 1)
    assert "16x its share" in cell["why"]
    (cfg,) = [c for c in bm["configs"] if c["name"] == CONFIG]
    assert cfg["source"].endswith(
        "arcee-ai/Trinity-Large-Preview/blob/main/config.json")
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_dense_layers", "n_routed_experts_held",
                              "vocab_size"]
    # membership, not counts or positions: later PRs append
    new = [m for m in bm["per_layer"] if m["name"] in NEW]
    assert len(new) == 4 and all(m["workloads"] == [CELL] for m in new)
    assert {m["moves"] for m in new} == {"tpot_p50_ms"}
    reported = {m["name"] for m in bm["end_to_end"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert {"tpot_p50_ms", "setup_s"} <= reported
    # a layer metric lists the cell only where the cell reports what it
    # moves, and the accepted readers read this stack as it is
    mine = {m["name"]: m["moves"] for m in bm["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(NEW) | {"decode_step_dev_ms", "decode_attn_busy_pct",
                       "decode_attn_slab_path_pct", "window_attn_read_pct",
                       "moe_busy_pct", "moe_held_pairs_pct",
                       "moe_grouped_kernel_path_pct",
                       "decode_prepared_launch_pct",
                       "decode_ahead_launch_pct", "attn_kernel_busy_pct",
                       "moe_held_expert_hbm_floor_pct", "start_to_ready_s",
                       "programs_built",
                       "compile_cache_miss_pct"} <= set(mine)
    assert set(mine.values()) <= reported
    for name in NEW:
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "layer_metrics", name + ".json"))


def test_the_traffic_is_the_issues():
    mix = traffic.load_mix("long-context")
    assert {k: mix[k] for k in ("loop", "callers", "pairs", "ramp_s",
                                "prompt_len", "output_len", "think_s",
                                "prefix_sharing")} == {
        "loop": "closed", "callers": 16, "pairs": 64, "ramp_s": 20,
        "prompt_len": {"kind": "uniform", "min": 8192, "max": 16384},
        "output_len": {"kind": "uniform", "min": 256, "max": 768},
        "think_s": {"kind": "uniform", "min": 0.0, "max": 0.25},
        "prefix_sharing": "none"}


def test_the_configuration_keeps_every_published_number():
    hf = config()
    assert hf == {**CATALOG, "architectures": ["AfmoeForCausalLM"],
                  "num_hidden_layers": 8, "layer_types": PERIOD * 2,
                  "num_dense_layers": 1, "vocab_size": 25024,
                  "n_routed_experts_held": 16, "routed_expert_offset": 0}
    with open(os.path.join(ROOT, "chipbench", "configs", CONFIG,
                           "manifest.json")) as f:
        man = json.load(f)
    assert set(man["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "n_routed_experts_held", "vocab_size"}
    for key, published in (("num_hidden_layers", 60), ("num_dense_layers", 6),
                           ("vocab_size", 200192)):
        assert man["reduced"][key]["published"] == published
        assert man["published"][key] == published
    assert man["reduced"]["n_routed_experts_held"]["run"] == 16
    assert man["published"]["num_experts"] == 256
    assert man["reference"] == "afmoe"
    assert "one of 16 chips" in man["deployment"]
    assert "pipeline stages" in man["deployment"]
    assert man["engine_flags"] == [
        "--max-model-len", "20480", "--max-num-batched-tokens", "4096",
        "--max-num-seqs", "16", "--tensor-parallel-size", "1"]
    assert man["engine_env"] == {
        "LIBTPU_INIT_ARGS": "--xla_tpu_scoped_vmem_limit_kib=32768"}
    assert (man["token_budget"], man["decode_slots"], man["block_size"]) == (
        4096, 16, 16)
    assert man["expect"]["use_pallas"] is True
    assert {"attention_gate", "rotation_by_layer_kind", "qk_norm",
            "sandwich_norm", "router", "embedding_factor", "weights",
            "dtype", "prefix_cache", "max_model_len", "token_budget",
            "decode_slots", "tokenizer", "libtpu_scoped_vmem_limit",
            "logprob_tolerance"} <= set(man["assumed"])


def test_shapes_swa_holds_the_issues_cut():
    hf, s = config(), shapes_swa
    # parameters, in millions: attention, dense MLP, an expert, the router
    assert [round(x / 1e6, 1) for x in (
        s.attn_params(hf), s.dense_mlp_params(hf), s.expert_params(hf),
        s.router_params(hf))] == [62.9, 113.2, 28.3, 0.8]
    gb = 2 / 1e9
    dense = s.outside_experts_params(hf, True) * gb
    sparse = (s.outside_experts_params(hf, False)
              + 16 * s.expert_params(hf)) * gb
    head = 2 * s.head_params(hf) * gb
    assert [round(x, 2) for x in (dense, sparse, head)] == [0.35, 1.09, 0.31]
    assert 2 * s.total_params(hf) == pytest.approx(8.29e9, rel=0.005)
    # one whole expert layer: no chip holds one
    assert 256 * s.expert_params(hf) * gb == pytest.approx(14.5, rel=0.01)
    # the pools: 4,096 B a token and layer
    assert s.kv_bytes_per_token_layer(hf) == 4096
    # a 4096-token chunk that ends a 12 k context: the window layers score
    # a third of what the full layers do, and operations bound it
    full = 4096 * 8192 + 4096 * 4097 // 2
    window = 4096 * 4096 - 4095 * 4096 // 2 + 4096 * 4095 // 2  # ~4096^2
    floor, bound = s.attn_floor_s(hf, rows=6 * 8191 + 2 * 12288,
                                  pairs=6 * window + 2 * full,
                                  query_rows=8 * 4096, peaks=PEAKS)
    assert bound == "flops" and floor == pytest.approx(
        4 * (6 * window + 2 * full) * 48 * 128 / 197e12)


DECODE_MS = [14.0, 15.0, 16.0]
OPS = [
    ["paged_decode_attention.3", 0.60, 1200,
     "%paged_decode_attention.3 = bf16[16,48,128] custom-call(%a)"],
    ["paged_decode_attention.9", 0.30, 400,
     "%paged_decode_attention.9 = bf16[16,48,128] custom-call(%a)"],
    ["ragged_paged_attention.5", 1.2, 48,
     "%ragged_paged_attention.5 = bf16[4096,48,128] custom-call(%b)"],
    ["fusion.9", 1.0, 900, "%fusion.9 = bf16[16,3072] fusion(%p)"]]


def _trace(ops=OPS, window_s=4.0):
    return {"busy_s": 3.5, "window_s": window_s, "ops": ops,
            "programs": {"decode": {"count": 3, "durations_ms": DECODE_MS}}}


def _polls(per_second: dict, n=11) -> list:
    return [{k: v * i for k, v in per_second.items()} for i in range(n)]


def test_the_window_pools_peak_on_hand_made_polls():
    polls = [{"vllm:window_kv_blocks_total": 4656.0,
              "vllm:window_kv_blocks_free": free}
             for free in (4656.0, 900.0, 1200.0)]
    assert layers.read("window_kv_used_peak_pct", ctx(polls=polls)) == \
        pytest.approx(100 * (1 - 900 / 4656))
    # the parent exports no such gauge: nothing, and no error
    assert layers.read("window_kv_used_peak_pct", ctx(
        polls=[{"vllm:kv_blocks_free": 1.0}])) is None


def test_the_decode_kernels_floor_on_hand_made_counters():
    # 50 dispatches a second at 16 slots x 12 k: eight calls each
    rows = 50 * 16 * (6 * 4096 + 2 * 12_000)
    polls = _polls({"vllm:decode_attn_rows_needed_total": rows,
                    "vllm:decode_attn_pairs_needed_total": rows,
                    "vllm:decode_attn_calls_total": 50 * 8})
    c = ctx(trace=_trace(), polls=polls)
    floor, bound = shapes_swa.attn_floor_s(
        c.hf, 4 * rows, 4 * rows, 4 * 50 * 8 * 16, PEAKS)
    assert bound == "bytes"
    want = 100 * (floor / (4 * 50 * 8)) / (0.90 / 1600)
    got = layers.read("mixed_decode_attn_hbm_floor_pct", c)
    assert got == pytest.approx(want) and 50 < got < 100
    # another configuration's file, no trace, the parent's counters: nothing
    qwen = {k: v for k, v in c.hf.items() if k != "sliding_window"}
    assert layers.read("mixed_decode_attn_hbm_floor_pct", ctx(
        trace=_trace(), polls=polls, hf=qwen)) is None
    assert layers.read("mixed_decode_attn_hbm_floor_pct",
                       ctx(polls=polls)) is None
    assert layers.read("mixed_decode_attn_hbm_floor_pct", ctx(
        trace=_trace(),
        polls=_polls({"vllm:decode_attn_calls_total": 400}))) is None


def test_the_ragged_kernels_roofline_on_hand_made_counters():
    # 1.5 full-budget chunks a second, each ending a 12 k context
    pairs = 1.5 * (6 * 4096 * 4096 + 2 * (4096 * 8192 + 4096 * 4097 / 2))
    polls = _polls({"vllm:ragged_attn_pairs_needed_total": pairs,
                    "vllm:ragged_attn_rows_needed_total":
                        1.5 * (6 * 8191 + 2 * 12_288),
                    "vllm:ragged_live_tokens_total": 1.5 * 4096,
                    "vllm:ragged_dispatches_total": 1.5})
    c = ctx(trace=_trace(), polls=polls)
    floor, bound = shapes_swa.attn_floor_s(
        c.hf, 4 * 1.5 * (6 * 8191 + 2 * 12_288), 4 * pairs,
        4 * 1.5 * 4096 * 8, PEAKS)
    assert bound == "flops"
    want = 100 * (floor / (4 * 1.5 * 8)) / (1.2 / 48)
    got = layers.read("mixed_ragged_attn_roofline_pct", c)
    assert got == pytest.approx(want) and 0 < got < 100
    assert layers.read("mixed_ragged_attn_roofline_pct", ctx(
        trace=_trace(),
        polls=_polls({"vllm:ragged_dispatches_total": 1.5}))) is None


def test_the_whole_steps_floor_on_hand_made_counters():
    rows = 50 * 16 * (6 * 4096 + 2 * 12_000)
    polls = _polls({"vllm:decode_attn_rows_needed_total": rows,
                    "vllm:decode_dispatches_total": 50,
                    "vllm:moe_decode_experts_touched_total": 50 * 7 * 4.0,
                    "vllm:moe_decode_layer_steps_total": 50 * 7})
    c = ctx(trace=_trace(), polls=polls)
    want = 100 * shapes_swa.decode_step_floor_s(
        c.hf, 4.0, rows / 50, 819e9) / 15.0e-3
    got = layers.read("swa_moe_decode_hbm_floor_pct", c)
    assert got == pytest.approx(want) and 40 < got < 100
    solar = {k: v for k, v in c.hf.items() if k != "layer_types"}
    assert layers.read("swa_moe_decode_hbm_floor_pct", ctx(
        trace=_trace(), polls=polls, hf=solar)) is None
    assert layers.read("swa_moe_decode_hbm_floor_pct",
                       ctx(polls=polls)) is None


def test_cpu_rehearsal_of_the_cell():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "tiny-afmoe.tiny",
         "--seed", str(2 ** 31 + 59), "--seconds", "5", "--trace", "1",
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
    # the long probe runs past the window: 72 + 64 rows against 32
    assert why["reference"]["per_request"][1]["prompt_tokens"] == 72
    # no device plane in a CPU trace: the trace metrics are left out
    assert set(line["metrics"]) == {
        "step_host_ms", "stream_fill_pct", "kv_used_peak_pct",
        "window_attn_read_pct", "moe_held_pairs_pct",
        "window_kv_used_peak_pct"}
