"""The trace reduction: interval arithmetic on hand-made events, and the
numbers of a small recorded trace (0.35 s cut from a v5e trace of
qwen3-8b-l16.chat-steady, PR 23's pilot run)."""

import os

import pytest

from chipbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "..", "testdata", "v5e_chat_steady_0p35s.json.gz")
PROGRAMS = {"ragged": {"contains_op": r"bf16\[1,2048,4096\]"},
            "decode": {"contains_op": r"bf16\[64,1,4096\]"}}
OWN = {"engine.py", "model_runner.py"}


def test_union_and_self_time():
    assert tr.union_ns([(0, 10), (5, 20), (30, 40), (32, 35)]) == 30
    # a while [0, 100) holds two children and one grandchild
    evs = [["while", 0, 100], ["a", 10, 30], ["b", 50, 20], ["a2", 15, 10]]
    assert sorted(tr.self_times(evs)) == [
        ["a", 20], ["a2", 10], ["b", 20], ["while", 50]]


def test_op_label_from_hlo_text():
    assert tr.op_label(
        "%closed_call.30 = bf16[16,512,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
        "custom-call(s32[64,512]{1,0:T(8,128)S(1)} %copy)"
    ) == "closed_call.30_custom-call_bf16_16_512_8_128"
    assert tr.op_label(
        "%while.84 = (s32[]{:T(128)}, bf16[1,2048,4096]{2,1,0:T(8,128)(2,1)})"
        " while(%tuple)") == "while.84_while_s32"
    assert tr.op_label("no hlo here") == "no_hlo_here"


def test_synthetic_two_programs_gap_and_idle():
    ms = 1_000_000
    dev = {"modules": [["jit__unknown(1)", 0, 10 * ms],
                       ["jit_convert(3)", 10 * ms + 10, 500],
                       ["jit__unknown(2)", 14 * ms, 6 * ms]],
           "ops": [["%w = (s32[]) while(%t)", 0, 10 * ms],
                   ["%f = bf16[1,2048,4096]{2,1,0} fusion(%x)", 0, 4 * ms],
                   ["%k = bf16[16,512,8,128]{3} custom-call(%q)", 4 * ms, 6 * ms],
                   ["%g = bf16[64,1,4096]{2,0,1} fusion(%y)", 14 * ms, 6 * ms]]}
    host = {"python3": [["$engine.py:405 step", 0, 20 * ms],
                        ["$model_runner.py:641 decode_multi", 11 * ms, 2 * ms],
                        ["$api.py:1 device_put", 11 * ms + 5, ms],
                        ["PjitFunction(x)", 13 * ms, ms]]}
    r = tr.reduce({"devices": {"/device:TPU:0": dev}, "host": host},
                  PROGRAMS, OWN)
    assert r["window_s"] == pytest.approx(0.020)
    assert r["busy_s"] == pytest.approx(0.016)
    assert r["programs"]["ragged"]["durations_ms"] == [10.0]
    assert r["programs"]["decode"]["durations_ms"] == [6.0]
    ops = {o[0]: o[1] for o in r["ops"]}
    assert ops["k_custom-call_bf16_16_512_8_128"] == pytest.approx(0.006)
    assert ops["w_while_s32"] == pytest.approx(0.0)   # all time is its children's
    # the 0.5 us cast bounds no gap; the gap is named by the program's frame
    assert r["idle_gaps"] == [[
        "after_ragged_before_decode__model_runner.py:641_decode_multi",
        pytest.approx(0.004)]]


def test_recorded_trace_known_numbers():
    events = tr.load_events(RECORDED)
    r = tr.reduce(events, PROGRAMS, tr.own_python_files(
        os.path.join(HERE, "..", "..", "production_stack_tpu")))
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(0.34800376)
    assert r["busy_s"] == pytest.approx(0.286218277)
    # busy time by a second method: sweep over the end points
    ops = events["devices"]["/device:TPU:0"]["ops"]
    pts = sorted([(s, 1) for _, s, d in ops] + [(s + d, -1) for _, s, d in ops])
    busy = depth = 0
    last = None
    for x, k in pts:
        if depth > 0:
            busy += x - last
        depth, last = depth + k, x
    assert busy / 1e9 == pytest.approx(r["busy_s"])
    progs = {k: (v["count"], v["total_s"]) for k, v in r["programs"].items()}
    assert progs["ragged"] == (2, pytest.approx(0.209484845))
    assert progs["decode"] == (3, pytest.approx(0.07673233))
    assert progs["other"][0] == 24
    top = r["ops"][0]
    assert top[0] == "closed_call.30_custom-call_bf16_16_512_8_128"
    assert top[1] == pytest.approx(0.05805337) and top[2] == 32
    assert r["ops"][1][0] == "closed_call.36_custom-call_bf16_64_8_4_128"
    assert r["ops"][1][1] == pytest.approx(0.038423336)
    # self times add up to the busy time (nothing counted twice)
    assert sum(o[1] for o in r["ops"]) == pytest.approx(r["busy_s"], rel=1e-3)
    assert r["idle_gaps"][0] == [
        "after_decode_before_ragged__model_runner.py:727_ragged_step",
        pytest.approx(0.029345334)]
    assert sum(g[1] for g in r["idle_gaps"]) <= r["window_s"] - r["busy_s"]
