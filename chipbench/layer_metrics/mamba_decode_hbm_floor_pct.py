"""The Mamba decode kernel's share of its roofline (bound by bytes: a
slot's state is 328 kB a layer, read and written once, against ~0.4 M
elementwise operations)."""

import re

from chipbench import shapes_mamba


def read(ctx, spec):
    if not ctx.trace or not ctx.peaks or "mb_per_layer" not in ctx.hf:
        return None
    op = re.compile(spec["op"])
    hits = [(sec, n) for _, sec, n, hlo in ctx.trace["ops"] if op.search(hlo)]
    runs = sum(n for _, n in hits)
    slots = shapes_mamba.mean_live_slots(ctx.polls,
                                         ctx.manifest["decode_slots"])
    if not runs or not slots:
        return None
    floor_s = shapes_mamba.mamba_decode_floor_s(ctx.hf, slots, ctx.peaks)
    return 100.0 * floor_s / (sum(sec for sec, _ in hits) / runs)
