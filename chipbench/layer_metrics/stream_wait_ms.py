"""A span of the engine's own record of a request (``chipbench/timeline.py``
``span_median_ms``): which two stamps, the metric's file says."""

from chipbench import timeline


def read(ctx, spec):
    return timeline.span_median_ms(ctx, spec)
