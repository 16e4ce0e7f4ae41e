"""The grouped matmul's share of its roofline in a decode step that
holds a share of the routed experts (bound by bytes: ~1.6 rows an expert
held). The decode program's grouped matmuls are told from the ragged
program's by their row count, as moe_expert_hbm_floor_pct does; the
bytes are ``shapes_kda.py``'s, from this configuration's own keys."""

import re

from chipbench import prom, shapes_kda


def read(ctx, spec):
    if not ctx.trace or not ctx.peaks \
            or "n_routed_experts_held" not in ctx.hf:
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
    floor_s = shapes_kda.held_grouped_matmul_floor_s(
        ctx.hf, touched / layer_steps, ctx.peaks)
    return 100.0 * floor_s / (sum(sec for sec, _ in hits) / runs)
