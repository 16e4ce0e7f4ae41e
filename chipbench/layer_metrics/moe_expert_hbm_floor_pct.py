"""The grouped matmul's share of its roofline in the decode step (bound
by bytes: at 8 rows an expert a weight byte is used for 8 FLOP, under the
chip's 240 FLOP a byte). The decode program's grouped matmuls are told
from the ragged program's by their row count; nothing here needs the
trace's programs to be classified."""

import re

from chipbench import prom, shapes_moe


def read(ctx, spec):
    if not ctx.trace or not ctx.peaks:
        return None
    touched = prom.delta(ctx.prom_open, ctx.prom_close,
                         "vllm:moe_decode_experts_touched_total")
    layer_steps = prom.delta(ctx.prom_open, ctx.prom_close,
                             "vllm:moe_decode_layer_steps_total")
    if not touched or not layer_steps:
        return None
    rows = ctx.manifest["decode_slots"] * ctx.hf["num_experts_per_tok"]
    op = re.compile(spec["op"].format(rows=rows))
    hits = [(sec, n) for _, sec, n, hlo in ctx.trace["ops"] if op.search(hlo)]
    runs = sum(n for _, n in hits)
    if not runs:
        return None
    floor_s = shapes_moe.grouped_matmul_floor_s(
        ctx.hf, touched / layer_steps, rows, ctx.peaks)
    return 100.0 * floor_s / (sum(sec for sec, _ in hits) / runs)
