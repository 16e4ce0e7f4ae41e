"""A looped stack's decode step's share of its HBM roofline (bound by
bytes, not by operations: at 64 rows a step the matmuls run at 64 FLOP a
weight byte, under the chip's 240 FLOP a byte, however often the same
matrices are read)."""

import statistics

from chipbench import shapes_loop


def read(ctx, spec):
    if not ctx.trace or not ctx.peaks or "total_ut_steps" not in ctx.hf:
        return None
    prog = ctx.trace["programs"].get(spec["program"])
    if not prog:
        return None
    step_s = statistics.median(prog["durations_ms"]) / 1e3
    # live KV: the blocks in use, averaged over the polls of the window
    used = [p["vllm:kv_blocks_total"] - p["vllm:kv_blocks_free"]
            for p in ctx.polls
            if "vllm:kv_blocks_free" in p and "vllm:kv_blocks_total" in p]
    if not used or not step_s:
        return None
    live_tokens = statistics.fmean(used) * ctx.manifest.get("block_size", 16)
    floor_s = shapes_loop.decode_step_floor_s(
        ctx.hf, live_tokens, ctx.chips, ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s / step_s
