"""The Olmo-Hybrid cell: its files as the benchmark finds them, shapes_gdn's
arithmetic against the issue's table, the five new per-layer metrics on
hand-made operations and counters, and one CPU rehearsal of the cell at toy
size (``tests/configs/tiny-olmo-hybrid``: the reference child holds the
served log-probabilities against ``reference/olmo_hybrid.py``; the values
are a CPU's and mean nothing)."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import layers, shapes_gdn, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "chipbench", "tests", "BENCHMARK.olmo-hybrid.json")
CONFIG = "olmo-hybrid-7b-l16"
CELL = CONFIG + ".decode-heavy"
NEW = ("gdn_decode_busy_pct", "gdn_decode_hbm_floor_pct",
       "gdn_chunk_busy_pct", "gdn_chunk_roofline_pct",
       "gdn_hybrid_decode_hbm_floor_pct")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# the catalog row's config (model-configs guide, Olmo-Hybrid-7B)
CATALOG = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


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
        "allenai/Olmo-Hybrid-7B/blob/main/config.json")
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
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
    assert hf == {**CATALOG, "num_hidden_layers": 16,
                  "layer_types": PERIOD * 4}
    with open(os.path.join(ROOT, "chipbench", "configs", CONFIG,
                           "manifest.json")) as f:
        man = json.load(f)
    assert set(man["reduced"]) == {"num_hidden_layers", "layer_types"}
    assert (man["reduced"]["num_hidden_layers"]["published"],
            man["reduced"]["num_hidden_layers"]["run"]) == (32, 16)
    assert man["reduced"]["layer_types"]["published"] == PERIOD * 8
    assert man["published"]["num_hidden_layers"] == 32
    assert man["reference"] == "olmo_hybrid"
    assert "two pipeline stages of sixteen layers" in man["deployment"]
    assert "engine_env" not in man  # no libtpu flag
    assert man["engine_flags"] == ["--max-model-len", "8192",
                                   "--tensor-parallel-size", "1"]
    assert (man["token_budget"], man["decode_slots"], man["block_size"]) == (
        2048, 64, 16)
    assert man["expect"]["use_pallas"] is True
    assert {"block", "qk_norm", "positional", "gdn_form", "decay_standin",
            "weights", "state_layout", "padding", "dtype", "max_model_len",
            "vmem", "logprob_tolerance"} <= set(man["assumed"])


def test_shapes_gdn_holds_the_issues_table():
    hf, s = config(), shapes_gdn
    assert (s.gdn_params(hf), s.attn_params(hf), s.mlp_params(hf)) == (
        88_750_332, 58_990_080, 126_812_160)
    assert s.total_params(hf) == 4_100_788_944
    assert s.total_params(CATALOG) == 7_430_870_688
    assert 2 * s.total_params(hf) == pytest.approx(8.20e9, rel=1e-3)
    # a decode step at 64 slots and a mean context of 600
    state = 64 * 12 * 2 * s.state_bytes_per_slot(hf)
    mixer = 2 * 12 * s.gdn_params(hf)
    attn = 2 * 4 * s.attn_params(hf)
    kv = 64 * 600 * s.kv_bytes_per_token(hf)
    mlp, head = 2 * 16 * s.mlp_params(hf), 2 * s.head_params(hf)
    assert [round(x / 1e9, 2) for x in (state, mixer, attn, kv, mlp, head)] \
        == [3.4, 2.13, 0.47, 2.36, 4.06, 0.77]
    step = s.decode_step_bytes(hf, 64, 64 * 600)
    tails = 64 * 12 * 2 * s.conv_tail_bytes_per_slot(hf)
    assert step == state + tails + mixer + attn + kv + mlp + head + 2 * 16 * (
        2 * hf["hidden_size"])
    assert step == pytest.approx(13.3e9, rel=0.01)
    assert s.decode_step_floor_s(hf, 64, 64 * 600, 819e9) == pytest.approx(
        16.2e-3, rel=0.01)
    # what this PR adds is most of the step
    assert (state + mixer + attn + kv) / step == pytest.approx(0.63, abs=0.01)
    # one call of the one-row kernel: the state in and out, bound by bytes
    assert s.gdn_decode_floor_s(hf, 64, PEAKS) == pytest.approx(
        64 * 2 * 2_211_840 / 819e9, rel=0.02)
    assert s.recurrence_flops(hf, 1) == 30 * 7 * 96 * 192
    # a span is bound by its bytes at any length (its rows are float32: 81
    # kB a token against 3.9 M operations), the state's at a short one
    rows = 4 * 30 * (3 * 96 + 2 * 192 + 1)
    long_ = s.gdn_chunk_floor_s(hf, 2048, 1, PEAKS)
    assert long_ == pytest.approx((2 * 2_211_840 + 2048 * rows) / 819e9)
    assert long_ > s.recurrence_flops(hf, 2048) / 197e12
    assert s.gdn_chunk_floor_s(hf, 64, 1, PEAKS) == pytest.approx(
        (2 * 2_211_840 + 64 * rows) / 819e9)


DECODE_MS = [21.0, 20.0, 22.0]
OPS = [
    ["gdn_decode_step.3", 1.2, 3000,
     "%gdn_decode_step.3 = (f32[64,15,1,384], f32[12,64,15,96,384]) "
     "custom-call(%a)"],
    ["gdn_chunk_scan.5", 0.12, 120,
     "%gdn_chunk_scan.5 = (f32[15,3,512,128], f32[12,64,15,96,384]) "
     "custom-call(%b)"],
    ["fusion.9", 1.0, 900, "%fusion.9 = bf16[64,3840] fusion(%p)"]]


def _trace(ops=OPS, window_s=4.0):
    return {"busy_s": 3.5, "window_s": window_s, "ops": ops,
            "programs": {"decode": {"count": 3, "durations_ms": DECODE_MS}}}


POLLS = [{"vllm:num_requests_running": 60.0, "vllm:kv_blocks_total": 3000.0,
          "vllm:kv_blocks_free": 3000.0 - 60 * 600 / 16}] * 3


def test_the_kernels_shares_of_busy_time():
    c = ctx(trace=_trace())
    assert layers.read("gdn_decode_busy_pct", c) == pytest.approx(
        100 * 1.2 / 3.5)
    assert layers.read("gdn_chunk_busy_pct", c) == pytest.approx(
        100 * 0.12 / 3.5)
    assert layers.read("gdn_decode_busy_pct", ctx()) is None


def test_the_decode_kernels_floor_on_hand_made_polls():
    c = ctx(trace=_trace(), polls=POLLS)
    want = 100 * shapes_gdn.gdn_decode_floor_s(c.hf, 60, PEAKS) / (1.2 / 3000)
    got = layers.read("gdn_decode_hbm_floor_pct", c)
    assert got == pytest.approx(want) and 50 < got < 100
    # another configuration's file, no trace, no polls: nothing
    qwen = {k: v for k, v in c.hf.items() if not k.startswith("linear_")}
    assert layers.read("gdn_decode_hbm_floor_pct", ctx(
        trace=_trace(), polls=POLLS, hf=qwen)) is None
    assert layers.read("gdn_decode_hbm_floor_pct", ctx(polls=POLLS)) is None
    assert layers.read("gdn_decode_hbm_floor_pct", ctx(
        trace=_trace())) is None


def test_the_span_kernels_roofline_on_hand_made_counters():
    close = {"vllm:gdn_chunk_tokens_total": 160.0 * 50,
             "vllm:gdn_chunk_spans_total": 50.0,
             "vllm:ragged_dispatches_total": 50.0}
    c = ctx(trace=_trace(), prom_open={k: 0.0 for k in close},
            prom_close=close)
    want = 100 * shapes_gdn.gdn_chunk_floor_s(c.hf, 160, 1, PEAKS) / (
        0.12 / 120)
    got = layers.read("gdn_chunk_roofline_pct", c)
    assert got == pytest.approx(want) and 0 < got < 100
    # the parent exports no such counters: nothing, and no error
    assert layers.read("gdn_chunk_roofline_pct", ctx(
        trace=_trace(), prom_open={"vllm:ragged_dispatches_total": 0.0},
        prom_close={"vllm:ragged_dispatches_total": 50.0})) is None


def test_the_whole_steps_floor_on_hand_made_polls():
    c = ctx(trace=_trace(), polls=POLLS)
    want = 100 * shapes_gdn.decode_step_floor_s(
        c.hf, 60, 60 * 600, 819e9) / 21.0e-3
    got = layers.read("gdn_hybrid_decode_hbm_floor_pct", c)
    assert got == pytest.approx(want) and 50 < got < 100
    falcon = {k: v for k, v in c.hf.items() if not k.startswith("linear_")}
    assert layers.read("gdn_hybrid_decode_hbm_floor_pct", ctx(
        trace=_trace(), polls=POLLS, hf=falcon)) is None
    assert layers.read("gdn_hybrid_decode_hbm_floor_pct", ctx(
        polls=POLLS)) is None


def test_cpu_rehearsal_of_the_cell():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "tiny-olmo-hybrid.tiny",
         "--seed", str(2 ** 31 + 57), "--seconds", "5", "--trace", "1",
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
