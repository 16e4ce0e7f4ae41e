"""The openPangu-Ultra-MoE cell and the Solar-Open2 prefill cell that waited
for it: their files as the benchmark finds them, shapes_mla's arithmetic
against the issue's, the two new per-layer metrics on hand-made operations
and recorded counter deltas, and one CPU rehearsal of the cell at toy size
(``tests/configs/tiny-pangu-ultra-moe``: the reference child holds the
served log-probabilities against ``reference/openpangu_ultra_moe.py``; the
values are a CPU's and mean nothing)."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import layers, shapes_mla, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "chipbench", "tests", "BENCHMARK.pangu.json")
CONFIG = "openpangu-ultra-moe-718b-ep16-l5"
CELL = CONFIG + ".long-prompt"
WAITED = "solar-open2-250b-ep16-l8.prefill-heavy"
NEW = ("mla_attn_busy_pct", "mla_attn_roofline_pct")
# what every cell lists of the server, scheduler, step loop and device
GENERIC = {
    "frontend_overhead_ms", "queue_wait_ms", "server_deliver_ms",
    "intake_wait_ms", "stream_wait_ms", "prefill_steps_ms",
    "intake_behind_ragged_pct", "slow_step_time_pct", "server_loop_lag_ms",
    "server_loop_lag_in_wait_pct", "router_to_handler_ms",
    "server_loop_busy_pct", "ragged_narrow_step_pct", "ragged_step_dev_ms",
    "stream_fill_pct", "kv_used_peak_pct", "device_idle_pct", "step_host_ms",
    "device_wait_pct"}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "num_nextn_predict_layers": 0, "vocab_size": 19200}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
# the catalog's config (model-configs guide, openPangu-Ultra-MoE-718B), as
# https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B/blob/main/config.json
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}


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


def listed(bm, cell) -> set:
    return {m["name"] for m in bm["per_layer"]
            if cell in m.get("workloads", ())}


def reported(bm, cell) -> set:
    return {m["name"] for m in bm["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}


def test_the_cell_and_its_metrics_are_listed_as_the_issue_says():
    bm = benchmark()
    (cell,) = [w for w in bm["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "long-prompt", 1)
    (cfg,) = [c for c in bm["configs"] if c["name"] == CONFIG]
    assert cfg["source"].endswith(
        "FreedomIntelligence/openPangu-Ultra-MoE-718B/blob/main/config.json")
    assert set(cfg["reduced"]) == set(REDUCED) | {"n_routed_experts_held"}
    assert not [w for w in bm["workloads"] if w["chips"] != 1]
    new = [m for m in bm["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in new] == list(NEW) == [
        m["name"] for m in bm["per_layer"][-2:]]  # appended, at the end
    assert all(m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
               and m["layer"] == "kernels" and m["source"] == "device_trace"
               for m in new)
    # NOT as the issue wrote it (first-token time and the rate). The
    # driver's check refused the cell on ttft_p50_ms (4.3 and 5.2 % in two
    # sets of six seeds, 5 allowed): a window holds ~70 of the mix's 128
    # pairs, a first token waits out the eight prompts of its callers' turn
    # less fifteen steps of decoding, so the seed's draw of lengths reaches
    # the median 2.7-fold (5.9 % from seed to seed by the step model, 21
    # chip runs agree: PERF.md section 6, PR 43). The rate's 2.3 % and a
    # stall in one run of seven pass two sets in about half of the tries.
    # The gap between tokens is ONE full ragged step here (every step
    # carries a 2048-token chunk), reads the step the latent kernel is half
    # of, and spreads 1.4-1.7 %
    assert reported(bm, CELL) == {"tpot_p50_ms", "setup_s"}
    mine = listed(bm, CELL)
    assert mine == set(NEW) | {"moe_busy_pct", "moe_held_pairs_pct"}
    # a layer metric lists only cells that report what it moves
    # (test_benchmark_json.py): the server, scheduler, step-loop and device
    # metrics move ttft_p50_ms or output_tok_s_chip and are read in
    # PERF.md section 5 from this PR's traced runs instead
    assert not mine & GENERIC
    assert not [m["name"] for m in bm["per_layer"]
                if CELL in m.get("workloads", ())
                and m["moves"] not in reported(bm, CELL)]
    for name in NEW:
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "layer_metrics", name + ".json"))


def test_the_traffic_is_the_issues_letter_for_letter():
    mix = traffic.load_mix("long-prompt")
    assert {k: mix[k] for k in ("loop", "callers", "pairs", "ramp_s",
                                "prompt_len", "output_len",
                                "prefix_sharing")} == {
        "loop": "closed", "callers": 8, "pairs": 128, "ramp_s": 12,
        "prompt_len": {"kind": "uniform", "min": 4096, "max": 8192},
        "output_len": {"kind": "fixed", "value": 16},
        "prefix_sharing": "none"}
    assert "think_s" not in mix
    lens = traffic.quantile_lengths(mix["prompt_len"], mix["pairs"])
    assert 4096 <= min(lens) and max(lens) <= 8192
    # the issue's arithmetic: E[P^2] / 2 = 19.6 M pairs, mean P 6144
    assert sum(lens) / len(lens) == pytest.approx(6144, rel=0.01)
    assert sum(p * p for p in lens) / len(lens) / 2 == pytest.approx(
        19.6e6, rel=0.01)


def test_the_waiting_cell_joined_with_its_mix_unchanged():
    bm = benchmark()
    (cell,) = [w for w in bm["workloads"] if w["name"] == WAITED]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "solar-open2-250b-ep16-l8", "prefill-heavy", 1)
    mix = traffic.load_mix("prefill-heavy")
    assert (mix["callers"], mix["pairs"], mix["ramp_s"]) == (16, 128, 8)
    assert mix["prompt_len"] == {"kind": "uniform", "min": 2048, "max": 4096}
    # as the issue wrote it. One run in six met a stall of ~3.8 s, which
    # the rate sees (2.8 % over six seeds) and the median first-token time
    # does not (0.1 %); a check's spread leaves out a set's farthest run,
    # and so reads 1.25 % where 2.0 is allowed (PERF.md section 6, PR 43).
    # slow_step_time_pct is the listed number that names such a stall
    assert reported(bm, WAITED) == {"ttft_p50_ms", "output_tok_s_chip",
                                    "setup_s"}
    theirs = listed(bm, WAITED)
    assert {"kda_chunk_busy_pct", "kda_chunk_roofline_pct",
            "kda_state_resets_per_request", "ragged_attn_busy_pct",
            "ragged_attn_narrow_walk_pct", "ragged_attn_interior_window_pct",
            "moe_load_max_over_mean", "moe_padding_rows_pct",
            "kv_write_busy_pct", "step_host_oncpu_pct",
            "slow_step_time_pct"} | GENERIC <= theirs
    assert not theirs & set(NEW)
    assert not [m["name"] for m in bm["per_layer"]
                if WAITED in m.get("workloads", ())
                and m["moves"] not in reported(bm, WAITED)]


def test_the_configuration_keeps_every_published_number():
    hf = config()
    assert {k: v for k, v in hf.items() if k in CATALOG} == {
        **CATALOG, **REDUCED}
    assert {k: v for k, v in hf.items() if k not in CATALOG} == {
        "n_routed_experts_held": 16, "routed_expert_offset": 0}
    with open(os.path.join(ROOT, "chipbench", "configs", CONFIG,
                           "manifest.json")) as f:
        man = json.load(f)
    assert set(man["reduced"]) == set(REDUCED) | {"n_routed_experts_held"}
    for k, v in man["reduced"].items():
        want = 256 if k == "n_routed_experts_held" else CATALOG[k]
        assert (v["published"], v["run"]) == (want, hf[k])
    assert man["reference"] == "openpangu_ultra_moe"
    assert "16 that share each layer" in man["deployment"]
    assert "engine_env" not in man
    assert man["engine_flags"] == ["--max-model-len", "9216",
                                   "--tensor-parallel-size", "1"]
    assert man["expect"] == {"tensor_parallel": 1, "attention_impl": "ragged",
                             "use_pallas": True}
    assert (man["token_budget"], man["decode_slots"], man["block_size"]) == (
        2048, 64, 16)
    assert {"weights", "router", "rope", "attention_form", "mtp", "dtype",
            "latent_row", "max_model_len", "vmem", "logprob_tolerance"} <= set(
                man["assumed"])


def test_shapes_mla_holds_the_issues_arithmetic():
    hf = config()
    assert shapes_mla.absorbed_pair_ops(hf) == 2 * 128 * (576 + 512) == 278528
    assert shapes_mla.published_pair_ops(hf) == 2 * 128 * 320 == 81920
    assert shapes_mla.absorbed_pair_ops(hf) / shapes_mla.published_pair_ops(
        hf) == pytest.approx(3.4)
    assert shapes_mla.attn_params(hf) == pytest.approx(196.6e6, rel=1e-3)
    assert shapes_mla.dense_layer_params(hf) == pytest.approx(621.3e6, rel=1e-3)
    assert shapes_mla.expert_layer_params(hf) == pytest.approx(1000.8e6,
                                                               rel=1e-3)
    assert shapes_mla.expert_params(hf) == 3 * 7680 * 2048
    assert shapes_mla.model_params(hf) == pytest.approx(4.92e9, rel=2e-3)
    assert 2 * shapes_mla.model_params(hf) == pytest.approx(9.84e9, rel=2e-3)
    # whole, one expert layer is 12.3 B = 24.7 GB: no chip holds one
    assert shapes_mla.expert_layer_params(hf, held=256) == pytest.approx(
        12.3e9, rel=5e-3)
    assert shapes_mla.latent_row_values(hf) == 576
    assert shapes_mla.kv_bytes_per_token(hf) == 5760
    assert shapes_mla.kv_bytes_per_token(hf, lanes=640) == 6400
    # a prompt of the cell: 19.6 M pairs a layer, five layers, absorbed:
    # ~27.3 T operations of ~48 T (the issue's ~57 %)
    attn = 19.6e6 * 5 * shapes_mla.absorbed_pair_ops(hf)
    rest = 2 * (5 * shapes_mla.attn_params(hf)
                + 3 * 7680 * 18432
                + 4 * (1.5 * shapes_mla.expert_params(hf) + 7680 * 256)) * 6144
    assert attn == pytest.approx(27.3e12, rel=0.01)
    assert attn / (attn + rest) == pytest.approx(0.57, abs=0.01)
    # the floor: operations bound a prefill span, bytes a decode step's rows
    floor, by = shapes_mla.mla_attn_floor_s(hf, 1e9, 8192 * 5, 2048 * 5, PEAKS)
    assert by == "ops" and floor == pytest.approx(1e9 * 81920 / 197e12)
    floor, by = shapes_mla.mla_attn_floor_s(hf, 64 * 6000, 64 * 6000, 64, PEAKS)
    assert by == "bytes" and floor == pytest.approx(
        2 * (64 * 6000 * 576 + 64 * 128 * 320) / 819e9)


LATENT_OPS = [
    ["latent_paged_attention.14", 1.60, 40,
     "%latent_paged_attention.14 = bf16[128,2048,512]{2,1,0} custom-call(%q)"],
    ["latent_paged_attention.13", 0.40, 10,
     "%latent_paged_attention.13 = bf16[128,2048,512]{2,1,0} custom-call(%q)"],
    ["fusion.9", 1.0, 900, "%fusion.9 = bf16[2048,7680] fusion(%p)"]]


def _trace(ops):
    return {"busy_s": 4.0, "window_s": 4.0, "ops": ops, "programs": {}}


def test_the_latent_kernel_metrics_on_hand_made_operations():
    c = ctx(trace=_trace(LATENT_OPS))
    assert layers.read("mla_attn_busy_pct", c) == pytest.approx(50.0)
    # 200 ragged dispatches of five layers, no decode-only step: 1000
    # calls of 40 ms (2.0 s over 50 executions in the trace); 2.5e10
    # pairs in the published form's operations
    # the profiled seconds: 4 s mid-window of 51, polls 24 and 28 of the
    # 52 (open, one a second, close). There: 16 ragged dispatches of five
    # layers, no decode-only step: 80 calls of 40 ms (2.0 s over 50
    # executions in the trace); 2.0e9 pairs in the published form
    there = {"vllm:mla_scored_pairs_total": 2.0e9,
             "vllm:mla_context_rows_total": 4.0e5,
             "vllm:mla_query_tokens_total": 1.6e5,
             "vllm:ragged_dispatches_total": 16.0,
             "vllm:decode_attn_calls_total": 0.0}

    def polls(there, before=3.0, after=0.5, n=52, a=24, b=28):
        """Counters that rise by ``there`` between polls a and b and at
        other rates (shorter contexts, then longer) outside them."""
        out = []
        for k in range(n):
            scale = (before * k / a if k <= a
                     else before + (k - a) / (b - a) if k <= b
                     else before + 1 + after * (k - b))
            out.append({name: v * scale for name, v in there.items()})
        return out

    ps = polls(there)
    c = ctx(trace=_trace(LATENT_OPS), seconds=51.0, polls=ps,
            prom_open=ps[0], prom_close=ps[-1])
    want = 100 * (2.0e9 * 81920 / 197e12 / 80) / 0.040
    got = layers.read("mla_attn_roofline_pct", c)
    assert got == pytest.approx(want) and 0 < got < 100 / 3.4
    # what the rest of the window scored does not move it (the whole
    # window's mean call would: PR 43 read 12.0 and 9.4 on the same code)
    ps2 = polls(there, before=1.0, after=2.0)
    assert layers.read("mla_attn_roofline_pct", ctx(
        trace=_trace(LATENT_OPS), seconds=51.0, polls=ps2, prom_open=ps2[0],
        prom_close=ps2[-1])) == pytest.approx(want)
    # decode-only steps add calls (here as many again), not work
    ps3 = polls({**there, "vllm:decode_attn_calls_total": 80.0})
    assert layers.read("mla_attn_roofline_pct", ctx(
        trace=_trace(LATENT_OPS), seconds=51.0, polls=ps3, prom_open=ps3[0],
        prom_close=ps3[-1])) == pytest.approx(want / 2)
    # a window shorter than the profile: open to close
    short = [dict.fromkeys(there, 0.0), there]
    assert layers.read("mla_attn_roofline_pct", ctx(
        trace=_trace(LATENT_OPS), seconds=1.0, polls=short)) == pytest.approx(
            want)
    # the parent, or another model: a share of 0.0 from trace_op_share,
    # nothing from this PR's reader (no counters, no kernel, other keys)
    other = ctx(trace=_trace(LATENT_OPS[2:]), seconds=51.0, polls=ps)
    assert layers.read("mla_attn_busy_pct", other) == 0.0
    assert layers.read("mla_attn_roofline_pct", other) is None
    bare = [{"vllm:ragged_dispatches_total": 9.0 * k} for k in range(52)]
    assert layers.read("mla_attn_roofline_pct", ctx(
        trace=_trace(LATENT_OPS), seconds=51.0, polls=bare)) is None
    qwen = {k: v for k, v in c.hf.items() if k != "kv_lora_rank"}
    assert layers.read("mla_attn_roofline_pct", ctx(
        trace=_trace(LATENT_OPS), seconds=51.0, polls=ps, hf=qwen)) is None
    assert layers.read("mla_attn_roofline_pct", ctx(polls=ps)) is None
    assert layers.read("mla_attn_roofline_pct", ctx(
        trace=_trace(LATENT_OPS))) is None  # no polls at all


def test_cpu_rehearsal_of_the_latent_cell():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "tiny-pangu-ultra-moe.tiny",
         "--seed", str(2 ** 31 + 43), "--seconds", "5", "--trace", "1",
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
    assert set(line["metrics"]) == {"step_host_ms", "stream_fill_pct",
                                    "moe_held_pairs_pct"}
    assert 15 < line["metrics"]["moe_held_pairs_pct"]["value"] < 35  # 4 of 16
