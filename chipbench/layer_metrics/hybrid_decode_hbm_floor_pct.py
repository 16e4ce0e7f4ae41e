"""A hybrid stack's decode step's share of its HBM roofline (bound by
bytes: at 64 rows a step every matmul runs under the chip's 240 FLOP a
byte, and the recurrent state is read and written whole)."""

import statistics

from chipbench import prom, shapes_kda


def read(ctx, spec):
    if not ctx.trace or not ctx.peaks or "linear_attn_config" not in ctx.hf:
        return None
    programs = ctx.trace["programs"]
    prog = programs.get(spec["program"]) or programs.get("decode")
    touched = prom.delta(ctx.prom_open, ctx.prom_close,
                         "vllm:moe_decode_experts_touched_total")
    layer_steps = prom.delta(ctx.prom_open, ctx.prom_close,
                             "vllm:moe_decode_layer_steps_total")
    slots = shapes_kda.mean_live_slots(ctx.polls,
                                       ctx.manifest["decode_slots"])
    # live KV: the blocks in use, averaged over the polls of the window
    used = [p["vllm:kv_blocks_total"] - p["vllm:kv_blocks_free"]
            for p in ctx.polls
            if "vllm:kv_blocks_free" in p and "vllm:kv_blocks_total" in p]
    if not prog or not layer_steps or touched is None or not slots \
            or not used:
        return None
    step_s = statistics.median(prog["durations_ms"]) / 1e3
    if not step_s:
        return None
    live_tokens = statistics.fmean(used) * ctx.manifest.get("block_size", 16)
    floor_s = shapes_kda.decode_step_floor_s(
        ctx.hf, touched / layer_steps, slots, live_tokens,
        ctx.peaks["hbm_bytes_per_s"]) / ctx.chips
    return 100.0 * floor_s / step_s
