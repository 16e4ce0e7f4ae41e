"""The Gated DeltaNet decode kernel's share of its roofline (bound by
bytes: a slot's state is 2.21 MB a layer, read and written once, against
3.9 M operations)."""

import re

from chipbench import shapes_gdn


def read(ctx, spec):
    if (not ctx.trace or not ctx.peaks
            or "linear_value_head_dim" not in ctx.hf):
        return None
    op = re.compile(spec["op"])
    hits = [(sec, n) for _, sec, n, hlo in ctx.trace["ops"] if op.search(hlo)]
    runs = sum(n for _, n in hits)
    slots = shapes_gdn.mean_live_slots(ctx.polls,
                                       ctx.manifest["decode_slots"])
    if not runs or not slots:
        return None
    floor_s = shapes_gdn.gdn_decode_floor_s(ctx.hf, slots, ctx.peaks)
    return 100.0 * floor_s / (sum(sec for sec, _ in hits) / runs)
