"""The latent attention kernel's share of its roofline over the seconds
the profile covered: what it was asked to score there comes from the
program's counters as the once-a-second polls saw them, how long a call
took from the trace of the same seconds."""

import re

from chipbench import prom, shapes_mla

COUNTERS = ("mla_scored_pairs", "mla_context_rows", "mla_query_tokens",
            "ragged_dispatches", "decode_attn_calls")
# the profile is asked for half its length before mid-window and the
# tracer takes about a quarter of a second to start
START_LAG_S = 0.25


def profiled_polls(ctx) -> tuple[dict, dict]:
    """The two polls that bracket the profiled seconds: poll k is taken k
    seconds into the window, the first and last are its open and close.
    Contexts differ several-fold between a window's prompts, so a call's
    least time must come from the steps whose calls the trace timed, not
    from the window's mean."""
    length = min(max(1, round(ctx.trace["window_s"])), len(ctx.polls) - 1)
    first = round(ctx.seconds / 2 - length / 2 + START_LAG_S)
    first = max(0, min(first, len(ctx.polls) - 1 - length))
    return ctx.polls[first], ctx.polls[first + length]


def read(ctx, spec):
    if not ctx.trace or not ctx.peaks or "kv_lora_rank" not in ctx.hf:
        return None
    if len(ctx.polls) < 2:
        return None
    a, b = profiled_polls(ctx)
    d = {n: prom.delta(a, b, f"vllm:{n}_total") for n in COUNTERS}
    if any(v is None for v in d.values()) or not d["mla_scored_pairs"]:
        return None
    op = re.compile(spec["op"])
    hits = [(sec, n) for _, sec, n, hlo in ctx.trace["ops"] if op.search(hlo)]
    runs = sum(n for _, n in hits)
    calls = (d["ragged_dispatches"] * ctx.hf["num_hidden_layers"]
             + d["decode_attn_calls"])
    if not runs or not calls:
        return None
    floor_s, _ = shapes_mla.mla_attn_floor_s(
        ctx.hf, d["mla_scored_pairs"], d["mla_context_rows"],
        d["mla_query_tokens"], ctx.peaks)
    return 100.0 * (floor_s / calls) / (sum(sec for sec, _ in hits) / runs)
