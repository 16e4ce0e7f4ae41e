"""The Falcon-H1 cell: its files as the benchmark finds them, shapes_ssd's
arithmetic against the issue's table, the five new per-layer metrics on
hand-made operations and counters, and one CPU rehearsal of the cell at toy
size (``tests/configs/tiny-falcon-h1``: the reference child holds the
served log-probabilities against ``reference/falcon_h1.py``; the values are
a CPU's and mean nothing)."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import layers, shapes_ssd, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "chipbench", "tests", "BENCHMARK.falcon.json")
CONFIG = "falcon-h1-34b-l6"
CELL = CONFIG + ".decode-heavy"
NEW = ("ssd_decode_busy_pct", "ssd_decode_hbm_floor_pct",
       "ssd_chunk_busy_pct", "ssd_chunk_roofline_pct",
       "parallel_hybrid_decode_hbm_floor_pct")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
# the catalog row's config (model-configs guide, Falcon-H1-34B-Instruct)
CATALOG = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120}


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
        CONFIG, "decode-heavy", 1)
    (cfg,) = [c for c in bm["configs"] if c["name"] == CONFIG]
    assert cfg["source"].endswith(
        "tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json")
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert not [w for w in bm["workloads"] if w["chips"] != 1]
    # membership, not counts or positions: later PRs append
    new = [m for m in bm["per_layer"] if m["name"] in NEW]
    assert len(new) == 5 and all(m["workloads"] == [CELL] for m in new)
    assert {m["moves"] for m in new} == {"tpot_p50_ms"}
    reported = {m["name"] for m in bm["end_to_end"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert {"tpot_p50_ms", "setup_s"} <= reported
    # a layer metric lists the cell only where the cell reports what it
    # moves, and the accepted decode-side readers read this stack as it is
    mine = {m["name"]: m["moves"] for m in bm["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(NEW) | {"decode_step_dev_ms", "decode_attn_busy_pct",
                       "decode_attn_slab_path_pct",
                       "decode_prepared_launch_pct",
                       "recurrent_state_cache_share_pct"} <= set(mine)
    assert set(mine.values()) <= reported
    for name in NEW:
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "layer_metrics", name + ".json"))


def test_the_traffic_is_the_accepted_mix_unedited():
    mix = traffic.load_mix("decode-heavy")
    assert {k: mix[k] for k in ("loop", "callers", "pairs", "ramp_s",
                                "prompt_len", "output_len", "think_s")} == {
        "loop": "closed", "callers": 64, "pairs": 128, "ramp_s": 20,
        "prompt_len": {"kind": "uniform", "min": 64, "max": 256},
        "output_len": {"kind": "uniform", "min": 256, "max": 768},
        "think_s": {"kind": "uniform", "min": 0.0, "max": 0.25}}


def test_the_configuration_keeps_every_published_number():
    hf = config()
    assert hf == {**CATALOG, "num_hidden_layers": 6}
    with open(os.path.join(ROOT, "chipbench", "configs", CONFIG,
                           "manifest.json")) as f:
        man = json.load(f)
    assert set(man["reduced"]) == {"num_hidden_layers"}
    assert (man["reduced"]["num_hidden_layers"]["published"],
            man["reduced"]["num_hidden_layers"]["run"]) == (72, 6)
    assert man["published"]["num_hidden_layers"] == 72
    assert man["reference"] == "falcon_h1"
    assert "twelve pipeline stages of six layers" in man["deployment"]
    assert "engine_env" not in man  # no libtpu flag
    assert man["engine_flags"] == ["--max-model-len", "8192",
                                   "--tensor-parallel-size", "1"]
    assert (man["token_budget"], man["decode_slots"], man["block_size"]) == (
        2048, 64, 16)
    assert man["expect"]["use_pallas"] is True
    assert {"weights", "mechanism", "dtype", "padding", "max_model_len",
            "vmem", "logprob_tolerance"} <= set(man["assumed"])


def test_shapes_ssd_holds_the_issues_table():
    hf, s = config(), shapes_ssd
    assert s.layer_params(hf) == 430_120_032
    assert s.total_params(hf) == 5_254_594_112
    assert 2 * s.total_params(hf) == pytest.approx(10.51e9, rel=1e-3)
    whole = {**hf, "num_hidden_layers": 72}
    assert 2 * 72 * s.layer_params(whole) == pytest.approx(61.9e9, rel=1e-3)
    # a decode step at 64 slots and a mean context of 600
    state = 64 * 6 * 2 * s.state_bytes_per_slot(hf)
    mixer = 2 * 6 * s.ssd_params(hf)
    attn = 2 * 6 * s.attn_params(hf)
    kv = 64 * 600 * s.kv_bytes_per_token(hf)
    mlp, head = 2 * 6 * s.mlp_params(hf), 2 * s.head_params(hf)
    assert [round(x / 1e9, 2) for x in (state, mixer, attn, kv, mlp, head)] \
        == [3.22, 0.82, 0.38, 0.47, 3.96, 2.67]
    step = s.decode_step_bytes(hf, 64, 64 * 600)
    tails = 64 * 6 * 2 * s.conv_tail_bytes_per_slot(hf)
    assert step == state + tails + mixer + attn + kv + mlp + head + 2 * 6 * (
        2 * hf["hidden_size"])
    assert step == pytest.approx(11.5e9, rel=0.01)
    assert s.decode_step_floor_s(hf, 64, 64 * 600, 819e9) == pytest.approx(
        14.1e-3, rel=0.01)
    # what this PR adds is the step's largest part
    assert (state + mixer + attn + kv) / step == pytest.approx(0.42, abs=0.01)
    # one call of the one-row kernel: the state in and out, bound by bytes
    assert s.ssd_decode_floor_s(hf, 64, PEAKS) == pytest.approx(
        64 * 2 * 4_194_304 / 819e9, rel=0.01)
    assert s.scan_flops(hf, 1) == 32 * 4 * 128 * 256
    # a span is bound by its bytes at any length (its rows are float32: 37
    # kB a token against 4.2 M operations), the state's at a short one
    long_ = s.ssd_chunk_floor_s(hf, 2048, 1, PEAKS)
    rows = 4 * (2 * 4096 + 2 * 512 + 32)
    assert long_ == pytest.approx((2 * 4_194_304 + 2048 * rows) / 819e9)
    assert long_ > s.scan_flops(hf, 2048) / 197e12
    assert s.ssd_chunk_floor_s(hf, 64, 1, PEAKS) == pytest.approx(
        (2 * 4_194_304 + 64 * rows) / 819e9)


DECODE_MS = [21.0, 20.0, 22.0]
OPS = [
    ["ssd_decode_step.3", 1.2, 1500,
     "%ssd_decode_step.3 = (f32[64,2,16,128], f32[6,64,32,256,128]) "
     "custom-call(%a)"],
    ["ssd_chunk_scan.5", 0.02, 60,
     "%ssd_chunk_scan.5 = (f32[32,512,128], f32[6,64,32,256,128]) "
     "custom-call(%b)"],
    ["fusion.9", 1.0, 900, "%fusion.9 = bf16[64,5120] fusion(%p)"]]


def _trace(ops=OPS, window_s=4.0):
    return {"busy_s": 3.5, "window_s": window_s, "ops": ops,
            "programs": {"decode": {"count": 3, "durations_ms": DECODE_MS}}}


POLLS = [{"vllm:num_requests_running": 60.0, "vllm:kv_blocks_total": 9000.0,
          "vllm:kv_blocks_free": 9000.0 - 60 * 600 / 16}] * 3


def test_the_kernels_shares_of_busy_time():
    c = ctx(trace=_trace())
    assert layers.read("ssd_decode_busy_pct", c) == pytest.approx(
        100 * 1.2 / 3.5)
    assert layers.read("ssd_chunk_busy_pct", c) == pytest.approx(
        100 * 0.02 / 3.5)
    assert layers.read("ssd_decode_busy_pct", ctx()) is None


def test_the_decode_kernels_floor_on_hand_made_polls():
    c = ctx(trace=_trace(), polls=POLLS)
    want = 100 * shapes_ssd.ssd_decode_floor_s(c.hf, 60, PEAKS) / (1.2 / 1500)
    got = layers.read("ssd_decode_hbm_floor_pct", c)
    assert got == pytest.approx(want) and 50 < got < 100
    # another configuration's file, no trace, no polls: nothing
    qwen = {k: v for k, v in c.hf.items() if not k.startswith("mamba_")}
    assert layers.read("ssd_decode_hbm_floor_pct", ctx(
        trace=_trace(), polls=POLLS, hf=qwen)) is None
    assert layers.read("ssd_decode_hbm_floor_pct", ctx(polls=POLLS)) is None
    assert layers.read("ssd_decode_hbm_floor_pct", ctx(
        trace=_trace())) is None


def test_the_span_kernels_roofline_on_hand_made_counters():
    close = {"vllm:ssd_chunk_tokens_total": 160.0 * 50,
             "vllm:ssd_chunk_spans_total": 50.0,
             "vllm:ragged_dispatches_total": 50.0}
    c = ctx(trace=_trace(), prom_open={k: 0.0 for k in close},
            prom_close=close)
    want = 100 * shapes_ssd.ssd_chunk_floor_s(c.hf, 160, 1, PEAKS) / (
        0.02 / 60)
    got = layers.read("ssd_chunk_roofline_pct", c)
    assert got == pytest.approx(want) and 0 < got < 100
    # the parent exports no such counters: nothing, and no error
    assert layers.read("ssd_chunk_roofline_pct", ctx(
        trace=_trace(), prom_open={"vllm:ragged_dispatches_total": 0.0},
        prom_close={"vllm:ragged_dispatches_total": 50.0})) is None


def test_the_whole_steps_floor_on_hand_made_polls():
    c = ctx(trace=_trace(), polls=POLLS)
    want = 100 * shapes_ssd.decode_step_floor_s(
        c.hf, 60, 60 * 600, 819e9) / 21.0e-3
    got = layers.read("parallel_hybrid_decode_hbm_floor_pct", c)
    assert got == pytest.approx(want) and 50 < got < 100
    phi = {**c.hf, "mb_per_layer": 2}
    del phi["mamba_n_heads"]
    assert layers.read("parallel_hybrid_decode_hbm_floor_pct", ctx(
        trace=_trace(), polls=POLLS, hf=phi)) is None
    assert layers.read("parallel_hybrid_decode_hbm_floor_pct", ctx(
        polls=POLLS)) is None


def test_cpu_rehearsal_of_the_cell():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "tiny-falcon-h1.tiny",
         "--seed", str(2 ** 31 + 52), "--seconds", "5", "--trace", "1",
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
        "step_host_ms", "stream_fill_pct", "kv_used_peak_pct",
        "recurrent_state_cache_share_pct"}
