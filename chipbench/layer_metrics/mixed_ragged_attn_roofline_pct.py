"""The ragged attention kernel's share of its roofline, in a stack whose
attention layers differ in what a row sees, over the seconds the profile
covered: the pairs inside the window or the causal reach and the rows a
span's queries can see come from the program's counters as the
once-a-second polls saw them, how long a call took from the trace of the
same seconds."""

import re

from chipbench import prom, shapes_swa
from chipbench.layer_metrics.mla_attn_roofline_pct import profiled_polls

COUNTERS = ("ragged_attn_pairs_needed", "ragged_attn_rows_needed",
            "ragged_live_tokens", "ragged_dispatches")


def read(ctx, spec):
    if not ctx.trace or not ctx.peaks or "layer_types" not in ctx.hf \
            or "sliding_window" not in ctx.hf or len(ctx.polls) < 2:
        return None
    a, b = profiled_polls(ctx)
    d = {n: prom.delta(a, b, f"vllm:{n}_total") for n in COUNTERS}
    if any(v is None for v in d.values()) or not d["ragged_dispatches"]:
        return None
    op = re.compile(spec["op"])
    hits = [(sec, n) for _, sec, n, hlo in ctx.trace["ops"] if op.search(hlo)]
    runs = sum(n for _, n in hits)
    layers = len(ctx.hf["layer_types"])  # every layer is attention
    calls = d["ragged_dispatches"] * layers
    if not runs or not d["ragged_attn_pairs_needed"]:
        return None
    floor_s, _ = shapes_swa.attn_floor_s(
        ctx.hf, d["ragged_attn_rows_needed"], d["ragged_attn_pairs_needed"],
        d["ragged_live_tokens"] * layers, ctx.peaks)
    return 100.0 * (floor_s / calls) / (sum(sec for sec, _ in hits) / runs)
