"""What the readers of the engine's start share: the engine's ``/metrics``
as scraped at the window's open (``ctx.prom_open``, a family summed over
its labels), read as absolute values. Everything a start and the harness's
warm-up, probe and ramp built lies before that scrape, and nothing may be
built after it, so a delta over the window would read 0. A program that
exports none of a metric's families (one older than they are) leaves its
reader nothing: None, and the line leaves the metric out."""

from __future__ import annotations


def read(ctx, spec) -> float | None:
    """scale x sum of ``spec["num"]`` [/ sum of ``spec["den"]``]; None
    where a family is missing or the denominator is 0."""
    def total(names):
        held = [ctx.prom_open.get(n) for n in names]
        return None if any(v is None for v in held) else sum(held)

    num, scale = total(spec["num"]), spec.get("scale", 1.0)
    if num is None:
        return None
    if "den" not in spec:
        return scale * num
    den = total(spec["den"])
    return scale * num / den if den else None
