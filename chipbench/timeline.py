"""What the readers of the engine's request records share: the requests
of a run that count (due inside the window, answered in full), each
beside the engine's own record of it (``/debug/requests``, joined by
x-request-id), and a median or a share over them. A program that takes
no such stamp, or keeps no such field, leaves a reader nothing: None."""

from __future__ import annotations

import statistics

from chipbench import stats


def joined(ctx) -> list:
    """The engine's record of every counted request that succeeded and
    has one."""
    held = {rec.get("client_request_id"): rec for rec in ctx.flight}
    return [held[r.rid] for r in stats.counted(ctx.records, ctx.seconds)
            if r.ok and r.rid in held]


def span_median_ms(ctx, spec) -> float | None:
    """Median, in ms, of ``spec["end"]`` minus ``spec["start"]`` over the
    records that hold both: stamps of the record's ``timeline``, or where
    ``spec["of"]`` is "record" fields of the record itself."""
    spans = []
    for rec in joined(ctx):
        held = rec if spec.get("of") == "record" else rec.get("timeline", {})
        if spec["start"] in held and spec["end"] in held:
            spans.append((held[spec["end"]] - held[spec["start"]]) * 1e3)
    return statistics.median(spans) if spans else None


def share_pct(ctx, spec) -> float | None:
    """100 x the share of the records that hold ``spec["field"]`` in
    which it equals ``spec["equals"]``."""
    values = [rec[spec["field"]] for rec in joined(ctx)
              if rec.get(spec["field"]) is not None]
    if not values:
        return None
    return 100.0 * sum(v == spec["equals"] for v in values) / len(values)
