"""Recurrent state over state plus paged pool, from the two gauges the
engine sets at start-up; None where it exports either not."""


def read(ctx, spec):
    state = ctx.prom_close.get("vllm:recurrent_state_bytes")
    pool = ctx.prom_close.get("vllm:kv_pool_bytes")
    if not state or not pool:
        return None
    return 100.0 * state / (state + pool)
