"""The Phi-4-mini-flash-reasoning cell: its files as the benchmark finds
them, the new per-layer metrics on hand-made observations, shapes_mamba's
arithmetic against the issue's, and one CPU rehearsal of the cell at toy
size (``tests/configs/tiny-phi4-flash``: the reference child holds the
served log-probabilities against ``reference/phi4_flash.py``; the values
are a CPU's and mean nothing)."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import layers, shapes_mamba, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "chipbench", "tests", "BENCHMARK.phi4.json")
CONFIG = "phi-4-mini-flash-reasoning"
CELL = CONFIG + ".long-decode"
NEW = ("mamba_decode_busy_pct", "mamba_decode_hbm_floor_pct",
       "mamba_chunk_busy_pct", "mamba_chunk_roofline_pct",
       "window_attn_read_pct", "ssm_hybrid_decode_hbm_floor_pct")
JOINED = ("decode_step_dev_ms", "decode_attn_busy_pct",
          "decode_attn_slab_path_pct", "kv_write_busy_pct",
          "kv_used_peak_pct", "stream_fill_pct", "device_idle_pct",
          "step_host_ms", "device_wait_pct", "step_host_oncpu_pct")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
# the catalog's config (model-configs guide, Phi-4-mini-flash-reasoning), as
# https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json
CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}


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
        CONFIG, "long-decode", 1)
    (cfg,) = [c for c in bm["configs"] if c["name"] == CONFIG]
    assert cfg["reduced"] == []
    assert cfg["source"].endswith(
        "microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json")
    assert len(cfg["why"]) <= 200 and len(cell["why"]) <= 200
    new = [m for m in bm["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in new] == list(NEW)
    assert all(CELL in m["workloads"] and m["layer"] == "kernels"
               for m in new)
    listed = {m["name"] for m in bm["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(NEW) | set(JOINED)
    reported = {m["name"] for m in bm["end_to_end"]
                if "workloads" not in m or CELL in m["workloads"]}
    # the issue's rule: no ttft_p50_ms unless it spreads under half its bound
    assert reported == {"tpot_p50_ms", "output_tok_s_chip", "setup_s"}
    assert not [m["name"] for m in bm["per_layer"]
                if CELL in m.get("workloads", ())
                and m["moves"] not in reported]
    for name in NEW:
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "layer_metrics", name + ".json"))


def test_the_configuration_is_the_published_file_with_no_key_changed():
    assert config() == CATALOG
    with open(os.path.join(ROOT, "chipbench", "configs", CONFIG,
                           "manifest.json")) as f:
        man = json.load(f)
    assert man["reduced"] == {} and man["reference"] == "phi4_flash"
    assert man["engine_flags"] == ["--max-model-len", "8192",
                                   "--tensor-parallel-size", "1"]
    assert (man["token_budget"], man["decode_slots"], man["block_size"]) == (
        2048, 64, 16)
    assert "replicas" in man["deployment"]
    assert {"mamba_sizes", "layer_kinds", "differential_attention",
            "packed_heads", "weights", "dtype", "max_model_len",
            "libtpu_scoped_vmem_limit", "logprob_tolerance"} <= set(
                man["assumed"])
    assert man["engine_env"] == {
        "LIBTPU_INIT_ARGS": "--xla_tpu_scoped_vmem_limit_kib=32768"}


def test_the_mix_is_the_issues():
    mix = traffic.load_mix("long-decode")
    assert (mix["loop"], mix["callers"], mix["pairs"], mix["ramp_s"]) == (
        "closed", 64, 128, 20)
    assert mix["prompt_len"] == {"kind": "uniform", "min": 1024, "max": 2048}
    assert mix["output_len"] == {"kind": "uniform", "min": 768, "max": 1536}
    assert mix["think_s"] == {"kind": "uniform", "min": 0.0, "max": 0.25}
    assert mix["prefix_sharing"] == "none"
    # 64 x the longest request fits 8192 positions and the two pools
    assert 2048 + 1536 < 8192


def _trace(ops, programs=None):
    return {"busy_s": 2.0, "window_s": 4.0, "ops": ops,
            "programs": programs or {}}


OPS = [
    ["mamba_decode_step.3", 0.06, 600,
     "%mamba_decode_step.3 = (f32[16,4,5120], f32[9,64,16,5120]) "
     "custom-call(%a)"],
    ["mamba_chunk_scan.1", 0.30, 60,
     "%mamba_chunk_scan.1 = (f32[2048,5120], f32[9,64,16,5120]) "
     "custom-call(%a)"],
    ["fusion.9", 1.1, 900, "%fusion.9 = bf16[64,1,2560] fusion(%p)"]]
POLLS = [{"vllm:num_requests_running": 64.0,
          "vllm:kv_blocks_total": 40000.0,
          "vllm:kv_blocks_free": 40000.0 - 8192.0,
          "vllm:window_kv_blocks_total": 2400.0,
          "vllm:window_kv_blocks_free": 2400.0 - 2048.0}] * 3


def test_the_mamba_kernel_metrics_on_hand_made_operations():
    c = ctx(trace=_trace(OPS), polls=POLLS)
    assert layers.read("mamba_decode_busy_pct", c) == pytest.approx(3.0)
    assert layers.read("mamba_chunk_busy_pct", c) == pytest.approx(15.0)
    # 0.1 ms a call against a floor of 64 x (2 x 328 kB + rows) / 819 GB/s
    floor = shapes_mamba.mamba_decode_floor_s(c.hf, 64, PEAKS)
    assert floor == pytest.approx(
        (64 * (2 * 327680 + 4 * (3 * 5120 + 32)) + 327680) / 819e9)
    got = layers.read("mamba_decode_hbm_floor_pct", c)
    assert got == pytest.approx(100 * floor / 1e-4) and 50 < got < 60
    close = {"vllm:mamba_chunk_tokens_total": 15000.0,
             "vllm:mamba_chunk_spans_total": 20.0,
             "vllm:ragged_dispatches_total": 10.0}
    c = ctx(trace=_trace(OPS), polls=POLLS, prom_close=close,
            prom_open=dict.fromkeys(close, 0.0))
    want = 100 * shapes_mamba.mamba_chunk_floor_s(c.hf, 1500, 2, PEAKS) / 5e-3
    assert layers.read("mamba_chunk_roofline_pct", c) == pytest.approx(want)
    assert 0 < want < 100
    # a trace without the kernels (the parent, another model): a share of
    # 0.0 from trace_op_share, nothing from the readers of this PR
    other = ctx(trace=_trace(OPS[2:]), polls=POLLS, prom_close=close)
    assert layers.read("mamba_decode_busy_pct", other) == 0.0
    assert layers.read("mamba_decode_hbm_floor_pct", other) is None
    assert layers.read("mamba_chunk_roofline_pct", other) is None
    qwen = {k: v for k, v in c.hf.items() if k != "mb_per_layer"}
    assert [layers.read(n, ctx(trace=_trace(OPS), polls=POLLS, hf=qwen,
                               prom_close=close))
            for n in ("mamba_decode_hbm_floor_pct", "mamba_chunk_roofline_pct",
                      "ssm_hybrid_decode_hbm_floor_pct")] == [None] * 3


def test_the_decode_floor_and_the_window_share_on_hand_made_numbers():
    programs = {"decode": {"count": 3, "total_s": 0.12,
                           "durations_ms": [39.0, 40.0, 41.0]}}
    c = ctx(trace=_trace(OPS, programs), polls=POLLS)
    floor = shapes_mamba.decode_step_floor_s(c.hf, 64, 2048 * 16, 8192 * 16,
                                             819e9)
    got = layers.read("ssm_hybrid_decode_hbm_floor_pct", c)
    assert got == pytest.approx(100 * floor / 0.040) and 40 < got < 50
    # nothing without the program, or without either pool's gauges
    assert layers.read("ssm_hybrid_decode_hbm_floor_pct",
                       ctx(trace=_trace(OPS), polls=POLLS)) is None
    one_pool = [{k: v for k, v in p.items() if "window" not in k}
                for p in POLLS]
    assert layers.read("ssm_hybrid_decode_hbm_floor_pct",
                       ctx(trace=_trace(OPS, programs), polls=one_pool)) is None
    # the decode module has one label, the one decode_step_dev_ms reads
    spec = layers.load_spec("ssm_hybrid_decode_hbm_floor_pct")
    assert (spec["program"], spec["module"]) == tuple(
        layers.load_spec("decode_step_dev_ms")[k]
        for k in ("program", "module"))
    close = {"vllm:window_attn_read_tokens_total": 530.0 * 64,
             "vllm:window_attn_context_tokens_total": 2048.0 * 64}
    c = ctx(prom_close=close, prom_open=dict.fromkeys(close, 0.0))
    assert layers.read("window_attn_read_pct", c) == pytest.approx(
        100 * 530 / 2048)
    assert layers.read("window_attn_read_pct", ctx()) is None


def test_shapes_mamba_holds_the_issues_arithmetic():
    hf = config()
    assert dict(shapes_mamba.layer_counts(hf)) == {
        "mamba": 9, "swa": 8, "full": 1, "gmu": 7, "cross": 7}
    assert shapes_mamba.total_params(hf) == pytest.approx(3.853e9, rel=1e-3)
    # weights 7.71 GB + state 0.38 GB + window rows 1.34 GB + eight reads
    # of one cache 5.4 GB = 14.8 GB, 18 ms
    weights = 2 * shapes_mamba.total_params(hf)
    state = 9 * 64 * 2 * shapes_mamba.state_bytes_per_slot(hf)
    tails = 9 * 64 * 2 * shapes_mamba.conv_tail_bytes_per_slot(hf)
    assert weights == pytest.approx(7.71e9, rel=2e-3)
    assert state == pytest.approx(0.38e9, rel=0.01) and tails < 0.04e9
    assert 8 * 64 * 512 * 5120 == pytest.approx(1.34e9, rel=2e-3)
    step = shapes_mamba.decode_step_bytes(hf, 64, 64 * 512, 64 * 2048)
    assert step == pytest.approx(14.8e9, rel=5e-3)
    assert (step - weights) / step > 0.47  # half the bytes are this PR's
    # if the window did not bind, the window layers would read 5.4 GB
    assert shapes_mamba.decode_step_bytes(hf, 64, 64 * 2048, 64 * 2048) \
        - step == pytest.approx(8 * 64 * 1536 * 5120)


def test_cpu_rehearsal_of_the_cell():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "tiny-phi4-flash.tiny", "--seed", str(2 ** 31 + 45),
         "--seconds", "8", "--trace", "1", "--rehearse-on-cpu",
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
    # the long probe is one and a half budgets: two chunks, twelve windows
    assert why["reference"]["per_request"][1]["prompt_tokens"] == 96
    # no device plane in a CPU trace: the trace metrics are left out
    assert set(line["metrics"]) == {"step_host_ms", "stream_fill_pct",
                                    "window_attn_read_pct"}
    assert 0 < line["metrics"]["window_attn_read_pct"]["value"] < 100
