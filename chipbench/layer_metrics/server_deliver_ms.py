"""From the engine thread stamping a request's first token to the event
loop having written its first chunk to the socket, per request, from the
engine's own record; the median over the counted requests. A program
that takes no ``first_chunk_written`` stamp gives nothing to read."""

import statistics

from chipbench import stats


def read(ctx, spec):
    held = {}
    for rec in ctx.flight:
        tl = rec.get("timeline", {})
        if "first_chunk_written" in tl and "first_token" in tl:
            held[rec.get("client_request_id")] = (
                tl["first_chunk_written"] - tl["first_token"])
    over = [held[r.rid] * 1e3
            for r in stats.counted(ctx.records, ctx.seconds)
            if r.ok and r.rid in held]
    return statistics.median(over) if over else None
