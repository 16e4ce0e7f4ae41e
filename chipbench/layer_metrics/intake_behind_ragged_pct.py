"""The share of the counted requests whose record says one thing
(``chipbench/timeline.py`` ``share_pct``): which field and which value,
the metric's file says."""

from chipbench import timeline


def read(ctx, spec):
    return timeline.share_pct(ctx, spec)
