"""The general per-layer readers on hand-made observations."""

import types

import pytest

from chipbench import layers, shapes


def ctx(**kw):
    base = dict(records=[], seconds=10.0, prom_open={}, prom_close={},
                polls=[], flight=[], trace=None, hf={}, manifest={},
                mix={}, chips=1, peaks=None)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_prom_ratio_and_missing_counter():
    c = ctx(prom_open={"a_sum": 1.0, "a_count": 2.0},
            prom_close={"a_sum": 4.0, "a_count": 8.0})
    spec = {"num": ["a_sum"], "den": ["a_count"], "scale": 1000.0}
    assert layers.prom_ratio(c, spec) == pytest.approx(500.0)
    assert layers.prom_ratio(c, {"num": ["nope"], "den": ["a_count"]}) is None
    c2 = ctx(prom_open={"live": 0, "n": 0}, prom_close={"live": 2048, "n": 2},
             manifest={"token_budget": 2048})
    assert layers.prom_ratio(c2, {"num": ["live"], "den": ["n"], "scale": 100.0,
                                  "den_manifest_key": "token_budget"}) == 50.0


def test_gauge_used_peak():
    c = ctx(polls=[{"free": 90, "total": 100}, {"free": 40, "total": 100},
                   {"free": 70, "total": 100}])
    assert layers.gauge_used_peak(c, {"free": "free", "total": "total"}) == 60.0
    assert layers.gauge_used_peak(ctx(), {"free": "free", "total": "total"}) is None


def test_trace_readers_and_nothing_to_read():
    tr = {"window_s": 2.0, "busy_s": 1.5,
          "programs": {"decode": {"durations_ms": [10.0, 12.0, 50.0]}},
          "ops": [["k_custom-call_bf16_8", 0.6, 9, "%k = bf16[8]{0} custom-call(%q)"],
                  ["f_fusion_bf16_8", 0.3, 9, "%f = bf16[8]{0} fusion(%x)"],
                  ["ar_all-reduce_bf16_8", 0.15, 9,
                   "%ar = bf16[8]{0} all-reduce(%y)"]]}
    c = ctx(trace=tr)
    assert layers.trace_idle(c, {}) == pytest.approx(25.0)
    assert layers.trace_program_median(c, {"program": "decode"}) == 12.0
    assert layers.trace_program_median(c, {"program": "ragged"}) is None
    share = lambda name: layers.trace_op_share(  # noqa: E731
        c, {"op": layers.load_spec(name)["op"]})
    assert share("attn_kernel_busy_pct") == pytest.approx(40.0)
    assert layers.trace_op_share(
        c, {"op": r" all-reduce\("}) == pytest.approx(10.0)
    for f in (layers.trace_idle, layers.trace_program_median,
              layers.trace_op_share):
        assert f(ctx(), {"program": "x", "op": "x"}) is None


def test_decode_floor_from_shapes():
    hf = {"hidden_size": 4096, "head_dim": 128, "num_attention_heads": 32,
          "num_key_value_heads": 8, "intermediate_size": 12288,
          "num_hidden_layers": 16, "vocab_size": 151936}
    assert shapes.layer_params(hf) == 192937984
    assert shapes.kv_bytes_per_token(hf) == 65536
    assert shapes.decode_weight_bytes(hf) == 2 * (16 * 192937984 + 4096 * 151936)
    t = shapes.decode_step_floor_s(hf, 64 * 600, 1, 819e9)
    assert t == pytest.approx((7418675200 + 64 * 600 * 65536) / 819e9)
    # four chips share weights and KV by head
    assert shapes.decode_step_floor_s(hf, 0, 4, 819e9) == pytest.approx(
        shapes.decode_step_floor_s(hf, 0, 1, 819e9) / 4)
