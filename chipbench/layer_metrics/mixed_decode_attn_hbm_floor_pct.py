"""The decode attention kernel's share of its HBM floor, in a stack whose
attention layers differ in what a row sees (window layers read the last
``sliding_window`` rows, full layers all of them), over the seconds the
profile covered: what the calls had to read comes from the program's
counters as the once-a-second polls saw them, how long a call took from the
trace of the same seconds."""

import re

from chipbench import prom, shapes_swa
from chipbench.layer_metrics.mla_attn_roofline_pct import profiled_polls

COUNTERS = ("decode_attn_rows_needed", "decode_attn_pairs_needed",
            "decode_attn_calls")


def read(ctx, spec):
    if not ctx.trace or not ctx.peaks or "layer_types" not in ctx.hf \
            or "sliding_window" not in ctx.hf or len(ctx.polls) < 2:
        return None
    a, b = profiled_polls(ctx)
    d = {n: prom.delta(a, b, f"vllm:{n}_total") for n in COUNTERS}
    if any(v is None for v in d.values()) or not d["decode_attn_calls"]:
        return None
    op = re.compile(spec["op"])
    hits = [(sec, n) for _, sec, n, hlo in ctx.trace["ops"] if op.search(hlo)]
    runs = sum(n for _, n in hits)
    if not runs:
        return None
    # a call's query rows: the live slots', one a slot (a pair a row it
    # sees, so pairs / rows ~ 1: the slots' count is not needed closer)
    floor_s, _ = shapes_swa.attn_floor_s(
        ctx.hf, d["decode_attn_rows_needed"], d["decode_attn_pairs_needed"],
        d["decode_attn_calls"] * ctx.manifest["decode_slots"], ctx.peaks)
    return 100.0 * (floor_s / d["decode_attn_calls"]) / (
        sum(sec for sec, _ in hits) / runs)
