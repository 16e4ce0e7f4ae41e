"""The latent attention kernel's share of its roofline over the seconds
the profile covered, in a stack whose latent attention layers are named
one by one: ``mla_attn_roofline_pct``'s reading with the kernel's calls
counted by those layers, not by the stack's depth."""

import re

from chipbench import prom, shapes_mla
from chipbench.layer_metrics.mla_attn_roofline_pct import (
    COUNTERS,
    profiled_polls,
)


def read(ctx, spec):
    lin = ctx.hf.get("linear_attn_config") or {}
    if not ctx.trace or not ctx.peaks or "full_attn_layers" not in lin \
            or "kv_lora_rank" not in ctx.hf or len(ctx.polls) < 2:
        return None
    a, b = profiled_polls(ctx)
    d = {n: prom.delta(a, b, f"vllm:{n}_total") for n in COUNTERS}
    if any(v is None for v in d.values()) or not d["mla_scored_pairs"]:
        return None
    op = re.compile(spec["op"])
    hits = [(sec, n) for _, sec, n, hlo in ctx.trace["ops"] if op.search(hlo)]
    runs = sum(n for _, n in hits)
    calls = (d["ragged_dispatches"] * len(lin["full_attn_layers"])
             + d["decode_attn_calls"])
    if not runs or not calls:
        return None
    floor_s, _ = shapes_mla.mla_attn_floor_s(
        ctx.hf, d["mla_scored_pairs"], d["mla_context_rows"],
        d["mla_query_tokens"], ctx.peaks)
    return 100.0 * (floor_s / calls) / (sum(sec for sec, _ in hits) / runs)
