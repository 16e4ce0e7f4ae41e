"""What the engine's /metrics held at the window's open (``chipbench/at_open.py``
``read``): which families, the metric's file says."""

from chipbench import at_open


def read(ctx, spec):
    return at_open.read(ctx, spec)
