"""A decoder-hybrid-decoder stack's decode step's share of its HBM
roofline (bound by bytes: at 64 rows a step every matmul runs under the
chip's 240 FLOP a byte; the scan state is read and written whole, and the
one full-attention cache is read once a layer that attends over it)."""

import statistics

from chipbench import shapes_mamba


def _used(polls, total, free):
    """Blocks of a pool in use, averaged over the polls of the window."""
    used = [p[total] - p[free] for p in polls if total in p and free in p]
    return statistics.fmean(used) if used else None


def read(ctx, spec):
    if not ctx.trace or not ctx.peaks or "mb_per_layer" not in ctx.hf:
        return None
    prog = ctx.trace["programs"].get(spec["program"])
    slots = shapes_mamba.mean_live_slots(ctx.polls,
                                         ctx.manifest["decode_slots"])
    full = _used(ctx.polls, "vllm:kv_blocks_total", "vllm:kv_blocks_free")
    window = _used(ctx.polls, "vllm:window_kv_blocks_total",
                   "vllm:window_kv_blocks_free")
    if not prog or not slots or full is None or window is None:
        return None
    step_s = statistics.median(prog["durations_ms"]) / 1e3
    if not step_s:
        return None
    bs = ctx.manifest.get("block_size", 16)
    floor_s = shapes_mamba.decode_step_floor_s(
        ctx.hf, slots, window * bs, full * bs,
        ctx.peaks["hbm_bytes_per_s"]) / ctx.chips
    return 100.0 * floor_s / step_s
