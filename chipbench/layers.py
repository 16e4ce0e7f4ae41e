"""Per-layer metrics, each found by its name: ``layer_metrics/<name>.json``
says where the number comes from, and an optional ``<name>.py`` beside it
holds a reader of its own (``read(ctx, spec) -> float | None``). A reader
that finds nothing to read returns None and the metric is left out of the
line. Adding a metric adds files here and an entry in BENCHMARK.json.

``ctx`` (a ``types.SimpleNamespace``) carries what a run observed:

    records     client-side request records (chipbench.stats.Record)
    seconds     length of the window
    prom_open / prom_close   engine /metrics at window open and close
    polls       engine /metrics scraped about once a second in between
    flight      engine /debug/requests records (joined by x-request-id)
    trace       chipbench.trace_reduce.reduce() output, or None
    hf, manifest, mix        configuration file, its manifest, the mix
    chips, peaks             chips of the cell; peaks of this device kind
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import statistics

from chipbench import prom

HERE = os.path.dirname(os.path.abspath(__file__))
DIR = os.path.join(HERE, "layer_metrics")


def load_spec(name: str) -> dict:
    with open(os.path.join(DIR, f"{name}.json")) as f:
        return json.load(f)


def read(name: str, ctx) -> float | None:
    spec = load_spec(name)
    own = os.path.join(DIR, f"{name}.py")
    if os.path.exists(own):
        mod_spec = importlib.util.spec_from_file_location(
            "chipbench_layer_" + re.sub(r"\W", "_", name), own)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read(ctx, spec)
    return READERS[spec["reader"]](ctx, spec)


# -- general readers, chosen by the "reader" key of the metric's file -------

def prom_ratio(ctx, spec) -> float | None:
    """scale x sum(delta of ``num``) / (sum(delta of ``den``) x const)."""
    def total(names):
        ds = [prom.delta(ctx.prom_open, ctx.prom_close, n) for n in names]
        return None if any(d is None for d in ds) else sum(ds)
    num, den = total(spec["num"]), total(spec["den"])
    if num is None or not den:
        return None
    const = ctx.manifest[spec["den_manifest_key"]] \
        if "den_manifest_key" in spec else 1.0
    return spec.get("scale", 1.0) * num / (den * const)


def gauge_used_peak(ctx, spec) -> float | None:
    """100 x (1 - min(free) / total) over the polls of the window."""
    free = [p[spec["free"]] for p in ctx.polls if spec["free"] in p]
    total = [p[spec["total"]] for p in ctx.polls if spec["total"] in p]
    if not free or not total or not total[0]:
        return None
    return 100.0 * (1.0 - min(free) / total[0])


def trace_program_median(ctx, spec) -> float | None:
    """Median device time (ms) of one execution of a program."""
    if not ctx.trace:
        return None
    p = ctx.trace["programs"].get(spec["program"])
    return statistics.median(p["durations_ms"]) if p else None


def trace_op_share(ctx, spec) -> float | None:
    """100 x device self time of the operations matching ``op`` / busy."""
    if not ctx.trace or not ctx.trace["busy_s"]:
        return None
    t = sum(sec for _, sec, _, hlo in ctx.trace["ops"]
            if re.search(spec["op"], hlo))
    return 100.0 * t / ctx.trace["busy_s"]


def trace_idle(ctx, spec) -> float | None:
    if not ctx.trace or not ctx.trace["window_s"]:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])


READERS = {f.__name__: f for f in (
    prom_ratio, gauge_used_peak, trace_program_median, trace_op_share,
    trace_idle)}
