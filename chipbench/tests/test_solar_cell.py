"""The Solar-Open2 cell: its files as the benchmark finds them, the new
per-layer metrics on hand-made observations, shapes_kda's arithmetic
against the issue's, and one CPU rehearsal of the cell at toy size
(``tests/configs/tiny-solar-open2``: the reference child holds the served
log-probabilities against ``reference/solar_open2.py``; the values are a
CPU's and mean nothing)."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import layers, shapes_kda, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "chipbench", "tests", "BENCHMARK.solar.json")
CONFIG = "solar-open2-250b-ep16-l8"
CELL = CONFIG + ".decode-heavy"
NEW = ("kda_decode_busy_pct", "kda_decode_hbm_floor_pct",
       "kda_chunk_busy_pct", "kda_chunk_roofline_pct",
       "hybrid_decode_hbm_floor_pct", "moe_held_pairs_pct",
       "kda_state_resets_per_request", "moe_held_expert_hbm_floor_pct")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
# the catalog's config (model-configs guide, Solar-Open2-250B), as
# https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json
CATALOG = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "n_routed_experts": 320, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}


def config() -> dict:
    with open(os.path.join(ROOT, "chipbench", "configs", CONFIG,
                           "config.json")) as f:
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
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    (cell,) = [w for w in bm["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "decode-heavy", 1)
    (cfg,) = [c for c in bm["configs"] if c["name"] == CONFIG]
    assert cfg["source"].endswith("upstage/Solar-Open2-250B/blob/main/"
                                  "config.json")
    assert set(cfg["reduced"]) == {"num_hidden_layers", "gqa_layers",
                                   "n_routed_experts_held", "vocab_size"}
    assert len(bm["workloads"]) == 5 and not [
        w for w in bm["workloads"] if w["chips"] != 1]
    new = [m for m in bm["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in new] == list(NEW)
    assert all(m["workloads"] == [CELL] for m in new)
    listed = {m["name"] for m in bm["per_layer"]
              if CELL in m.get("workloads", ())}
    assert "step_host_oncpu_pct" in listed
    # wrong keys for this configuration: OLMoE's and the dense stack's
    assert not listed & {"moe_expert_hbm_floor_pct", "decode_hbm_floor_pct",
                         "prefix_hit_pct", "loop_decode_hbm_floor_pct"}
    reported = {m["name"] for m in bm["end_to_end"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert not [m["name"] for m in bm["per_layer"]
                if CELL in m.get("workloads", ())
                and m["moves"] not in reported]
    for name in NEW:
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "layer_metrics", name + ".json"))


def test_the_configuration_keeps_every_published_number():
    hf = config()
    cut = {"num_hidden_layers": 8, "gqa_layers": [0, 4], "vocab_size": 24576}
    assert {k: v for k, v in hf.items() if k in CATALOG} == {**CATALOG, **cut}
    assert {k: v for k, v in hf.items() if k not in CATALOG} == {
        "n_routed_experts_held": 20, "routed_expert_offset": 0}
    with open(os.path.join(ROOT, "chipbench", "configs", CONFIG,
                           "manifest.json")) as f:
        man = json.load(f)
    assert set(man["reduced"]) == {"num_hidden_layers", "gqa_layers",
                                   "n_routed_experts_held", "vocab_size"}
    assert man["reference"] == "solar_open2" and "16-chip" in man["deployment"]
    assert {"weights", "router", "kda_low_rank", "kda_block", "kda_decay",
            "kda_conv", "gqa_gate", "dtype", "max_model_len",
            "libtpu_scoped_vmem_limit", "logprob_tolerance"} <= set(
                man["assumed"])


def test_shapes_kda_holds_the_issues_arithmetic():
    hf = config()
    assert shapes_kda.layer_counts(hf) == (2, 6)
    assert shapes_kda.expert_params(hf) == 3 * 4096 * 1280
    assert shapes_kda.state_bytes_per_slot(hf) == 64 * 128 * 128 * 4
    assert shapes_kda.conv_tail_bytes_per_slot(hf) == 3 * 3 * 64 * 128 * 2
    assert shapes_kda.kv_bytes_per_token(hf) == 8192
    # the issue's 10.1 GB / 12.4 ms a decode step, a per cent or two apart
    step = shapes_kda.decode_step_bytes(hf, 16.1, 64, 64 * 600)
    assert step == pytest.approx(10.1e9, rel=0.03)
    state = 6 * 64 * 2 * shapes_kda.state_bytes_per_slot(hf)
    experts = 8 * 16.1 * 2 * shapes_kda.expert_params(hf)
    assert state == pytest.approx(3.2e9, rel=0.01)
    assert experts == pytest.approx(4.1e9, rel=0.02)
    kv = 64 * 600 * 8192
    assert kv / step < 0.04 and (state + experts) / step > 0.7
    # more experts touched than held cannot be counted
    assert shapes_kda.decode_step_bytes(hf, 99, 64, 0) == \
        shapes_kda.decode_step_bytes(hf, 20, 64, 0)
    # one decode kernel call: the 64 slots' states read and written
    assert shapes_kda.kda_decode_floor_s(hf, 64, PEAKS) == pytest.approx(
        (64 * 2 * 4194304 + 64 * 6 * 64 * 128 * 4) / 819e9)


def _trace(ops, programs=None):
    return {"busy_s": 2.0, "window_s": 4.0, "ops": ops,
            "programs": programs or {}}


KDA_OPS = [
    ["kda_decode_step.3", 0.60, 600,
     "%kda_decode_step.3 = (f32[64,64,128], f32[6,64,64,128,128]) "
     "custom-call(%a)"],
    ["kda_chunk_scan.1", 0.30, 60,
     "%kda_chunk_scan.1 = (f32[64,2048,128], f32[6,64,64,128,128]) "
     "custom-call(%a)"],
    ["fusion.9", 1.1, 900, "%fusion.9 = bf16[64,1,4096] fusion(%p)"]]
RUNNING = [{"vllm:num_requests_running": 64.0,
            "vllm:kv_blocks_total": 30000.0,
            "vllm:kv_blocks_free": 30000.0 - 2400.0}] * 3


def test_the_kda_kernel_metrics_on_hand_made_operations():
    c = ctx(trace=_trace(KDA_OPS), polls=RUNNING)
    assert layers.read("kda_decode_busy_pct", c) == pytest.approx(30.0)
    assert layers.read("kda_chunk_busy_pct", c) == pytest.approx(15.0)
    # 1 ms a call against a floor of 0.671 ms
    assert layers.read("kda_decode_hbm_floor_pct", c) == pytest.approx(
        100 * shapes_kda.kda_decode_floor_s(c.hf, 64, PEAKS) / 1e-3)
    assert 60 < layers.read("kda_decode_hbm_floor_pct", c) < 70
    close = {"vllm:kda_chunk_tokens_total": 2250.0,
             "vllm:kda_chunk_spans_total": 640.0,
             "vllm:ragged_dispatches_total": 10.0}
    c = ctx(trace=_trace(KDA_OPS), polls=RUNNING, prom_close=close,
            prom_open=dict.fromkeys(close, 0.0))
    want = 100 * shapes_kda.kda_chunk_floor_s(c.hf, 225, 64, PEAKS) / 5e-3
    assert layers.read("kda_chunk_roofline_pct", c) == pytest.approx(want)
    assert 0 < want < 100
    # a trace without the kernels (the parent, another model): a share of
    # 0.0 from trace_op_share, nothing from the readers of this PR
    other = ctx(trace=_trace(KDA_OPS[2:]), polls=RUNNING, prom_close=close)
    assert layers.read("kda_decode_busy_pct", other) == 0.0
    assert layers.read("kda_decode_hbm_floor_pct", other) is None
    assert layers.read("kda_chunk_roofline_pct", other) is None
    qwen = {k: v for k, v in c.hf.items() if k != "linear_attn_config"}
    assert [layers.read(n, ctx(trace=_trace(KDA_OPS), polls=RUNNING, hf=qwen))
            for n in ("kda_decode_hbm_floor_pct", "kda_chunk_roofline_pct",
                      "hybrid_decode_hbm_floor_pct")] == [None] * 3


def test_the_hybrid_decode_floor_on_hand_made_operations():
    close = {"vllm:moe_decode_experts_touched_total": 16.0 * 8 * 100,
             "vllm:moe_decode_layer_steps_total": 8.0 * 100}
    programs = {"decode": {"count": 3, "total_s": 0.06,
                           "durations_ms": [19.0, 20.0, 21.0]}}
    c = ctx(trace=_trace(KDA_OPS, programs), polls=RUNNING,
            prom_close=close, prom_open=dict.fromkeys(close, 0.0))
    floor = shapes_kda.decode_step_floor_s(c.hf, 16.0, 64, 2400 * 16, 819e9)
    got = layers.read("hybrid_decode_hbm_floor_pct", c)
    assert got == pytest.approx(100 * floor / 0.020) and 55 < got < 70
    # under its own label too, and nothing without the program or counters
    own = {"hybrid_decode": programs["decode"]}
    assert layers.read("hybrid_decode_hbm_floor_pct", ctx(
        trace=_trace(KDA_OPS, own), polls=RUNNING, prom_close=close)) == got
    assert layers.read("hybrid_decode_hbm_floor_pct", ctx(
        trace=_trace(KDA_OPS), polls=RUNNING, prom_close=close)) is None
    assert layers.read("hybrid_decode_hbm_floor_pct", ctx(
        trace=_trace(KDA_OPS, programs), polls=RUNNING)) is None
    # the decode module keeps the label of the rule that was there first
    spec = layers.load_spec("hybrid_decode_hbm_floor_pct")
    dev = {"modules": [["jit_decode_multi_step(7)", 0, 1000]],
           "ops": [["%fusion.1 = bf16[64,1,4096]{2,0,1} fusion(%p)", 10, 9]]}
    rules = {"decode": {"contains_op": r"bf16\[64,1,4096\]"},
             spec["program"]: {"module": spec["module"]}}
    assert trace_reduce.classify_modules(dev, rules) == {
        "jit_decode_multi_step(7)": "decode"}


def test_the_counter_metrics_on_recorded_deltas():
    close = {"vllm:moe_held_pairs_total": 625.0,
             "vllm:moe_routed_tokens_total": 10000.0,
             "vllm:recurrent_state_resets_total": 140.0,
             "vllm:request_queue_time_seconds_count": 140.0}
    c = ctx(prom_open=dict.fromkeys(close, 0.0), prom_close=close)
    assert layers.read("moe_held_pairs_pct", c) == 6.25
    assert layers.read("kda_state_resets_per_request", c) == 1.0
    old = ctx(prom_close={"vllm:ragged_dispatches_total": 9.0})
    assert [layers.read(n, old) for n in (
        "moe_held_pairs_pct", "kda_state_resets_per_request")] == [None, None]


def test_the_held_experts_floor_on_hand_made_operations():
    """The decode program's grouped matmuls (512 rows) and not the ragged
    program's (16,384): 16 held experts touched a layer-step, one
    projection's matrix each, against 0.6 ms a call."""
    ops = [["ragged-dot-none.2", 0.60, 1000,
            "%ragged-dot-none.2 = bf16[512,1280]{1,0} custom-call(%r, %w)"],
           ["ragged-dot-none.5", 0.90, 100,
            "%ragged-dot-none.5 = bf16[16384,1280]{1,0} custom-call(%r, %w)"]]
    close = {"vllm:moe_decode_experts_touched_total": 16.0 * 8 * 100,
             "vllm:moe_decode_layer_steps_total": 8.0 * 100}
    c = ctx(trace=_trace(ops), prom_close=close,
            prom_open=dict.fromkeys(close, 0.0))
    floor = 16 * 4096 * 1280 * 2 / 819e9
    assert shapes_kda.held_grouped_matmul_floor_s(c.hf, 16.0, PEAKS) == \
        pytest.approx(floor)
    got = layers.read("moe_held_expert_hbm_floor_pct", c)
    assert got == pytest.approx(100 * floor / 0.6e-3) and 30 < got < 40
    # no more experts than are held; nothing without the counters, the
    # kernels, or for a configuration that holds every expert (OLMoE)
    assert shapes_kda.held_grouped_matmul_floor_s(c.hf, 99, PEAKS) == \
        shapes_kda.held_grouped_matmul_floor_s(c.hf, 20, PEAKS)
    assert layers.read("moe_held_expert_hbm_floor_pct",
                       ctx(trace=_trace(ops))) is None
    assert layers.read("moe_held_expert_hbm_floor_pct", ctx(
        trace=_trace(KDA_OPS), prom_close=close)) is None
    olmoe = {k: v for k, v in c.hf.items() if k != "n_routed_experts_held"}
    assert layers.read("moe_held_expert_hbm_floor_pct", ctx(
        trace=_trace(ops), prom_close=close, hf=olmoe)) is None


def test_cpu_rehearsal_of_the_hybrid_cell():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "tiny-solar-open2.tiny", "--seed", str(2 ** 31 + 34),
         "--seconds", "5", "--trace", "1", "--rehearse-on-cpu",
         "--benchmark", TOY],
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
        "kda_state_resets_per_request"}
    assert 15 < line["metrics"]["moe_held_pairs_pct"]["value"] < 35  # 4 of 16
