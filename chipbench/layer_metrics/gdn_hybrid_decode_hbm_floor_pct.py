"""A Gated DeltaNet / full-attention stack's decode step's share of its HBM
roofline (bound by bytes: at 64 rows a step every matmul runs under the
chip's 240 FLOP a byte, and the delta-rule state is read and written
whole)."""

import statistics

from chipbench import shapes_gdn


def read(ctx, spec):
    if (not ctx.trace or not ctx.peaks
            or "linear_value_head_dim" not in ctx.hf):
        return None
    prog = ctx.trace["programs"].get(spec["program"])
    slots = shapes_gdn.mean_live_slots(ctx.polls,
                                       ctx.manifest["decode_slots"])
    # live KV: the blocks in use, averaged over the polls of the window
    used = [p["vllm:kv_blocks_total"] - p["vllm:kv_blocks_free"]
            for p in ctx.polls
            if "vllm:kv_blocks_free" in p and "vllm:kv_blocks_total" in p]
    if not prog or not slots or not used:
        return None
    step_s = statistics.median(prog["durations_ms"]) / 1e3
    if not step_s:
        return None
    live_tokens = statistics.fmean(used) * ctx.manifest.get("block_size", 16)
    floor_s = shapes_gdn.decode_step_floor_s(
        ctx.hf, slots, live_tokens,
        ctx.peaks["hbm_bytes_per_s"]) / ctx.chips
    return 100.0 * floor_s / step_s
