"""The Gated DeltaNet span kernel's share of its roofline at the ragged
steps the window had: a call's live tokens and spans come from the
counters."""

import re

from chipbench import prom, shapes_gdn


def read(ctx, spec):
    if (not ctx.trace or not ctx.peaks
            or "linear_value_head_dim" not in ctx.hf):
        return None
    d = {n: prom.delta(ctx.prom_open, ctx.prom_close, f"vllm:{n}_total")
         for n in ("gdn_chunk_tokens", "gdn_chunk_spans", "ragged_dispatches")}
    if not all(d.values()):
        return None
    op = re.compile(spec["op"])
    hits = [(sec, n) for _, sec, n, hlo in ctx.trace["ops"] if op.search(hlo)]
    runs = sum(n for _, n in hits)
    if not runs:
        return None
    floor_s = shapes_gdn.gdn_chunk_floor_s(
        ctx.hf, d["gdn_chunk_tokens"] / d["ragged_dispatches"],
        d["gdn_chunk_spans"] / d["ragged_dispatches"], ctx.peaks)
    return 100.0 * floor_s / (sum(sec for sec, _ in hits) / runs)
