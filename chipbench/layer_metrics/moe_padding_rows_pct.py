"""Share of the rows carried through MoE layers that were padding. The
live share is 100 minus this: the two counters account for every row."""

from chipbench import prom


def read(ctx, spec):
    pad = prom.delta(ctx.prom_open, ctx.prom_close,
                     "vllm:moe_padding_rows_total")
    pairs = prom.delta(ctx.prom_open, ctx.prom_close,
                       "vllm:moe_routed_tokens_total")
    if pad is None or pairs is None:
        return None
    live = pairs / ctx.hf["num_experts_per_tok"]
    return 100.0 * pad / (pad + live) if pad + live else None
