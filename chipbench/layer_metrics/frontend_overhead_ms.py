"""Client TTFT minus the engine's received-to-first-token time, per
request, joined by x-request-id; the median over the counted requests."""

import statistics

from chipbench import stats


def read(ctx, spec):
    engine = {}
    for rec in ctx.flight:
        tl = rec.get("timeline", {})
        if "first_token" in tl and "received" in tl:
            engine[rec.get("client_request_id")] = (
                tl["first_token"] - tl["received"])
    over = [(r.token_times[0] - r.sent - engine[r.rid]) * 1e3
            for r in stats.counted(ctx.records, ctx.seconds)
            if r.ok and r.rid in engine]
    return statistics.median(over) if over else None
