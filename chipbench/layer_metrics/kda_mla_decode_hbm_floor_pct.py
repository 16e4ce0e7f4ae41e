"""The decode step's share of its HBM roofline for a stack of KDA and
latent attention layers (bound by bytes: at 64 rows a step every matmul
runs under the chip's 240 FLOP a byte, the recurrent state is read and
written whole and every live token's latent row is read once an MLA
layer)."""

import statistics

from chipbench import prom, shapes_kda, shapes_kimi_linear


def read(ctx, spec):
    lin = ctx.hf.get("linear_attn_config") or {}
    if not ctx.trace or not ctx.peaks or "full_attn_layers" not in lin:
        return None
    prog = ctx.trace["programs"].get(spec["program"])
    touched = prom.delta(ctx.prom_open, ctx.prom_close,
                         "vllm:moe_decode_experts_touched_total")
    layer_steps = prom.delta(ctx.prom_open, ctx.prom_close,
                             "vllm:moe_decode_layer_steps_total")
    slots = shapes_kda.mean_live_slots(ctx.polls,
                                       ctx.manifest["decode_slots"])
    # live latent rows: the blocks in use, averaged over the polls
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
    floor_s = shapes_kimi_linear.decode_step_floor_s(
        ctx.hf, touched / layer_steps, slots, live_tokens,
        ctx.peaks["hbm_bytes_per_s"]) / ctx.chips
    return 100.0 * floor_s / step_s
