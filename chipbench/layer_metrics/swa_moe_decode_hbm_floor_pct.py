"""A window / full attention stack's decode step's share of its HBM
roofline (bound by bytes: at 16 rows a step every matmul runs under the
chip's 240 FLOP a byte, and the attention rows are read once a layer)."""

import statistics

from chipbench import prom, shapes_swa
from chipbench.layer_metrics.mla_attn_roofline_pct import profiled_polls


def read(ctx, spec):
    if not ctx.trace or not ctx.peaks or "layer_types" not in ctx.hf \
            or "sliding_window" not in ctx.hf or len(ctx.polls) < 2:
        return None
    prog = ctx.trace["programs"].get(spec["program"])
    a, b = profiled_polls(ctx)
    d = {n: prom.delta(a, b, f"vllm:{n}_total") for n in (
        "moe_decode_experts_touched", "moe_decode_layer_steps",
        "decode_attn_rows_needed", "decode_dispatches")}
    if not prog or any(v is None for v in d.values()) \
            or not d["moe_decode_layer_steps"] or not d["decode_dispatches"]:
        return None
    step_s = statistics.median(prog["durations_ms"]) / 1e3
    if not step_s:
        return None
    floor_s = shapes_swa.decode_step_floor_s(
        ctx.hf, d["moe_decode_experts_touched"] / d["moe_decode_layer_steps"],
        d["decode_attn_rows_needed"] / d["decode_dispatches"],
        ctx.peaks["hbm_bytes_per_s"]) / ctx.chips
    return 100.0 * floor_s / step_s
