"""The decode step's share of its HBM roofline (bound by bytes, not by
operations: at 64 rows a step the matmuls run at 64 FLOP a weight byte,
under the chip's 240 FLOP a byte)."""

import statistics

from chipbench import shapes


def read(ctx, spec):
    if not ctx.trace or not ctx.peaks:
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
    floor_s = shapes.decode_step_floor_s(
        ctx.hf, live_tokens, ctx.chips, ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s / step_s
