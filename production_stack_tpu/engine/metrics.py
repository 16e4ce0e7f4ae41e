"""Engine Prometheus metrics — the exact exposition contract the reference
router scrapes and re-derives (reference names parsed in
src/vllm_router/stats/engine_stats.py:63-76; dashboard KPIs README.md:93-101).

Gauges/counters that mirror engine state are emitted by a custom collector
reading ``LLMEngine.stats()`` at scrape time (no update thread to drift);
latency histograms are observed inline by the server.
"""

from __future__ import annotations

import time
from typing import TYPE_CHECKING

from prometheus_client import CollectorRegistry, Counter, Histogram
from prometheus_client.core import (
    CounterMetricFamily,
    GaugeMetricFamily,
    HistogramMetricFamily,
    SummaryMetricFamily,
)

from production_stack_tpu.tenancy import fold_records

if TYPE_CHECKING:
    from production_stack_tpu.engine.engine import LLMEngine


class EngineStatsCollector:
    def __init__(self, engine: "LLMEngine", model_name: str):
        self.engine = engine
        self.model_name = model_name

    def collect(self):
        s = self.engine.stats()
        labels = ["model_name"]
        lv = [self.model_name]

        def gauge(name, doc, value):
            g = GaugeMetricFamily(name, doc, labels=labels)
            g.add_metric(lv, value)
            return g

        def counter(name, doc, value):
            c = CounterMetricFamily(name, doc, labels=labels)
            c.add_metric(lv, value)
            return c

        hits = s["gpu_prefix_cache_hits_total"]
        queries = s["gpu_prefix_cache_queries_total"]
        yield gauge(
            "vllm:num_requests_running",
            "Number of requests currently running on TPU",
            s["num_requests_running"],
        )
        yield gauge(
            "vllm:num_requests_waiting",
            "Number of requests waiting to be processed",
            s["num_requests_waiting"],
        )
        yield gauge(
            "vllm:gpu_cache_usage_perc",
            "KV-cache usage (1 = 100%); TPU HBM block pool",
            s["gpu_cache_usage_perc"],
        )
        yield gauge(
            "vllm:gpu_prefix_cache_hit_rate",
            "Prefix cache block hit rate",
            hits / queries if queries else 0.0,
        )
        yield counter(
            "vllm:gpu_prefix_cache_hits", "Prefix cache block hits", hits
        )
        yield counter(
            "vllm:gpu_prefix_cache_queries", "Prefix cache block queries", queries
        )
        # host-DRAM KV tier (LMCache CPU-offload equivalent)
        yield gauge(
            "vllm:cpu_cache_usage_perc",
            "Host-DRAM KV offload tier usage (1 = 100%)",
            s.get("cpu_cache_usage_perc", 0.0),
        )
        yield counter(
            "vllm:cpu_prefix_cache_hits",
            "Host-tier prefix block hits",
            s.get("cpu_prefix_cache_hits_total", 0),
        )
        yield counter(
            "vllm:cpu_prefix_cache_queries",
            "Host-tier prefix block queries",
            s.get("cpu_prefix_cache_queries_total", 0),
        )
        # n-gram speculative decoding (vLLM spec-decode metric names)
        yield counter(
            "vllm:spec_decode_num_draft_tokens",
            "Speculative draft tokens proposed",
            s.get("spec_decode_num_draft_tokens_total", 0),
        )
        yield counter(
            "vllm:spec_decode_num_accepted_tokens",
            "Speculative draft tokens accepted",
            s.get("spec_decode_num_accepted_tokens_total", 0),
        )
        yield gauge(
            "vllm:spec_decode_acceptance_rate",
            "Draft acceptance rate (accepted / proposed, cumulative)",
            s.get("spec_decode_acceptance_rate", 0.0),
        )
        yield gauge(
            "vllm:spec_decode_tokens_per_step",
            "Mean tokens emitted per verified speculative span "
            "(1 guaranteed + accepted drafts)",
            s.get("spec_decode_tokens_per_step", 0.0),
        )
        yield counter(
            "vllm:aborted_seqs",
            "Sequences aborted (client disconnect / deadline expiry); "
            "KV blocks freed before natural completion",
            s.get("aborted_seqs_total", 0),
        )
        yield counter(
            "vllm:num_preemptions",
            "Sequences preempted because a block pool ran dry: their "
            "blocks freed, recomputed from position 0 on re-admission",
            s.get("num_preemptions_total", 0),
        )
        yield counter(
            "vllm:spliced_seqs",
            "Pushed P→D transfers attached as decode-ready sequences "
            "(disaggregated serving: each one is a skipped re-prefill)",
            s.get("spliced_seqs_total", 0),
        )
        yield counter(
            "vllm:prompt_tokens", "Cumulative prompt tokens", s["prompt_tokens_total"]
        )
        yield counter(
            "vllm:generation_tokens",
            "Cumulative generated tokens",
            s["generation_tokens_total"],
        )
        # request-lifecycle observability: per-step batch/KV-pool utilization
        yield gauge(
            "vllm:batch_occupancy",
            "Running sequences / max_num_seqs (decode-slot utilization)",
            s.get("batch_occupancy", 0.0),
        )
        yield gauge(
            "vllm:kv_blocks_total",
            "KV block pool capacity (HBM)",
            s.get("kv_blocks_total", 0),
        )
        yield gauge(
            "vllm:kv_pool_bytes",
            "Bytes the paged pool holds on the device (blocks x block size "
            "x vllm:kv_bytes_per_token), fixed at start-up; beside "
            "vllm:recurrent_state_bytes where a model keeps both",
            s.get("kv_pool_bytes", 0),
        )
        yield gauge(
            "vllm:kv_blocks_free",
            "Free KV blocks (allocatable right now)",
            s.get("kv_blocks_free", 0),
        )
        # unified ragged attention path: mixed prefill+decode dispatches,
        # those that ran at a narrow stream width, and how much of the
        # token budget carried live tokens (how full the scheduler's
        # steps are)
        yield counter(
            "vllm:ragged_dispatches",
            "Unified mixed prefill+decode dispatches issued",
            s.get("ragged_dispatches_total", 0),
        )
        yield counter(
            "vllm:ragged_narrow_dispatches",
            "Ragged dispatches that ran under the token budget's width: "
            "at a narrow stream width that held their live tokens",
            s.get("ragged_narrow_dispatches_total", 0),
        )
        yield counter(
            "vllm:ragged_live_tokens",
            "Live (unpadded) tokens packed into ragged dispatches",
            s.get("ragged_live_tokens_total", 0),
        )
        yield counter(
            "vllm:ragged_attn_walks",
            "(tile, span) context walks of the ragged attention kernel "
            "over the dispatched span offsets",
            s.get("ragged_attn_walks_total", 0),
        )
        yield counter(
            "vllm:ragged_attn_narrow_walks",
            "Walks whose rows fit the kernel's narrow row block (decode "
            "rows, verify spans, short chunk heads and tails)",
            s.get("ragged_attn_narrow_walks_total", 0),
        )
        yield counter(
            "vllm:ragged_attn_windows",
            "Context windows (8 KV blocks) the ragged attention kernel's "
            "walks stream, up to each walk's causal reach",
            s.get("ragged_attn_windows_total", 0),
        )
        yield counter(
            "vllm:ragged_attn_interior_windows",
            "Windows of full-tile walks that nothing can mask: the ones "
            "the kernel runs through its interior body",
            s.get("ragged_attn_interior_windows_total", 0),
        )
        yield counter(
            "vllm:decode_dispatches",
            "decode_multi dispatches issued (decode-only steps)",
            s.get("decode_dispatches_total", 0),
        )
        yield counter(
            "vllm:decode_prepared_launches",
            "Of them, launched at the landing of the dispatch before from "
            "inputs built and committed while it ran, its tokens left on "
            "the device",
            s.get("decode_prepared_launches_total", 0),
        )
        yield counter(
            "vllm:decode_ahead_launches",
            "Of those, queued behind the dispatch before a lead ahead of "
            "its landing, the intake asked and empty",
            s.get("decode_ahead_launches_total", 0),
        )
        yield counter(
            "vllm:engine_intake_requests",
            "Requests the engine thread took in from its intake queue",
            s.get("intake_requests_total", 0),
        )
        yield counter(
            "vllm:arrivals_behind_queued_decode",
            "Of them, those that reached the intake before a landing at "
            "which the next decode program already stood queued: their "
            "ragged step would have been launched at that landing",
            s.get("arrivals_behind_queued_decode_total", 0),
        )
        yield counter(
            "vllm:ragged_landing_arrivals",
            "Ragged steps at whose landing a request had reached the "
            "intake, so that no decode program was launched before the "
            "ragged step that takes it in",
            s.get("ragged_landing_arrivals_total", 0),
        )
        yield counter(
            "vllm:decode_attn_calls",
            "Attention calls of the decode dispatches (fused iterations x "
            "cache layers a dispatch)",
            s.get("decode_attn_calls_total", 0),
        )
        yield counter(
            "vllm:decode_attn_slab_calls",
            "Those in which the Pallas decode kernel scored a window from "
            "the slab as stored (bf16 cache, 128-wide heads: MHA's slab "
            "body, the grouped-query body)",
            s.get("decode_attn_slab_calls_total", 0),
        )
        # the engine thread's step clock (engine/tracing.py): where its
        # wall time goes, by the kind of step and the phase of the loop.
        # Waiting for the device, idling on the intake queue and on-CPU
        # time are families of their own, so that a sum over one
        # family's labels is one quantity; every kind is printed from the
        # first scrape, at 0
        phases = s.get("step_phases")
        if phases:
            host = CounterMetricFamily(
                "vllm:engine_host_seconds",
                "Engine-thread wall seconds by step kind and loop phase "
                "(every phase but wait and idle)",
                labels=["model_name", "kind", "phase"],
            )
            cpu = CounterMetricFamily(
                "vllm:engine_host_cpu_seconds",
                "Engine-thread on-CPU seconds (time.thread_time) over the "
                "phases of vllm:engine_host_seconds; wall minus this is "
                "time the thread wanted to run and did not (GIL waits, "
                "blocking calls)",
                labels=["model_name", "kind"],
            )
            wait = CounterMetricFamily(
                "vllm:engine_device_wait_seconds",
                "Engine-thread wall seconds blocked on device results "
                "(jax.device_get) by step kind",
                labels=["model_name", "kind"],
            )
            for kind, by_phase in phases["seconds"].items():
                on_cpu = 0.0
                for phase, sec in by_phase.items():
                    if phase == "wait":
                        wait.add_metric([self.model_name, kind], sec["wall"])
                    else:
                        host.add_metric([self.model_name, kind, phase],
                                        sec["wall"])
                        on_cpu += sec["cpu"]
                cpu.add_metric([self.model_name, kind], on_cpu)
            yield host
            yield cpu
            yield wait
            yield counter(
                "vllm:engine_idle_seconds",
                "Engine-thread wall seconds blocked on the intake queue "
                "with nothing unfinished",
                phases["idle_seconds"],
            )
            slow = CounterMetricFamily(
                "vllm:engine_slow_step_seconds",
                "Seconds of the steps that took over twice the median of "
                "the 32 steps like them before them (same kind, after the "
                "same kind of step), by the phase "
                "that overran most (wait: the device, or whatever blocks "
                "the fetch) or compile if a program compiled inside",
                labels=["model_name", "kind", "cause"],
            )
            for kind, by_cause in s.get("slow_step_seconds", {}).items():
                for cause, sec in by_cause.items():
                    slow.add_metric([self.model_name, kind, cause], sec)
            yield slow
        # MoE routing (engine/tracing.py MoeCounters): exported by MoE
        # models only, summed over layers and dispatches
        if "moe_routed_tokens_total" in s:
            yield counter(
                "vllm:moe_routed_tokens",
                "(token, choice) pairs sent to experts, live rows only",
                s["moe_routed_tokens_total"],
            )
            yield counter(
                "vllm:moe_held_pairs",
                "Of vllm:moe_routed_tokens, the pairs on experts this "
                "engine holds (all of them unless it holds a share of a "
                "wider expert layer)",
                s["moe_held_pairs_total"],
            )
            yield counter(
                "vllm:moe_padding_rows",
                "Stream rows masked out of MoE routing (padding of the "
                "ragged stream, idle decode slots)",
                s["moe_padding_rows_total"],
            )
            yield counter(
                "vllm:moe_expert_load_max",
                "Pairs received by the busiest expert of each MoE layer",
                s["moe_expert_load_max_total"],
            )
            yield counter(
                "vllm:moe_expert_load_mean",
                "Pairs received per expert of each MoE layer "
                "(pairs / experts)",
                s["moe_expert_load_mean_total"],
            )
            yield counter(
                "vllm:moe_decode_experts_touched",
                "Experts that received a pair, per MoE layer of decode steps",
                s["moe_decode_experts_touched_total"],
            )
            yield counter(
                "vllm:moe_decode_layer_steps",
                "MoE layers run by decode steps (layers x fused iterations "
                "x dispatches): the denominator of "
                "vllm:moe_decode_experts_touched",
                s["moe_decode_layer_steps_total"],
            )
            yield counter(
                "vllm:moe_layer_steps",
                "MoE layers run by the step programs of every kind (layers "
                "x forwards x dispatches)",
                s["moe_layer_steps_total"],
            )
            yield counter(
                "vllm:moe_grouped_kernel_layer_steps",
                "Of vllm:moe_layer_steps, those of programs whose grouped "
                "matmuls are the Pallas kernel %moe_grouped_matmul and not "
                "jax.lax.ragged_dot (the runner's choice: TPU, unquantized "
                "experts, no sharded expert axis)",
                s["moe_grouped_kernel_layer_steps_total"],
            )
        # latent attention (engine/tracing.py LatentCounters): exported by
        # models that keep a latent cache only
        if "mla_scored_pairs_total" in s:
            for name, key, doc in (
                ("vllm:mla_query_tokens", "mla_query_tokens_total",
                 "Query tokens the latent attention kernel took, by step "
                 "kind, times cache layers"),
                ("vllm:mla_scored_pairs", "mla_scored_pairs_total",
                 "(query token, context row) pairs the latent attention "
                 "kernel scored causally, by step kind, times cache "
                 "layers: q (ctx - q) + q (q + 1) / 2 a span"),
                ("vllm:mla_expanded_pairs", "mla_expanded_pairs_total",
                 "Of those pairs, the ones of spans long enough in their "
                 "step that the kernel scored them in the published, "
                 "expanded form (ops/latent_paged_attention_pallas.py "
                 "EXPAND_ROWS); none in a decode step"),
                ("vllm:mla_decode_body_pairs", "mla_decode_body_pairs_total",
                 "Of those pairs, the ones of decode dispatches whose "
                 "program is the kernel's decode body (one-token spans, "
                 "several sequences a grid cell, 512-row windows); none "
                 "in a ragged step, whose decode rows walk the stream's "
                 "tile, nor where the programs are the XLA form"),
                ("vllm:mla_context_rows", "mla_context_rows_total",
                 "Context rows the spans of the latent attention kernel "
                 "reach, each once a span and cache layer, by step kind"),
            ):
                fam = CounterMetricFamily(
                    name, doc, labels=["model_name", "kind"])
                for kind, value in s[key].items():
                    fam.add_metric([self.model_name, kind], value)
                yield fam
            yield gauge(
                "vllm:kv_bytes_per_token",
                "Bytes one token of context holds in the paged pool, all "
                "cache layers (a latent row padded to whole lane tiles)",
                s["kv_bytes_per_token"],
            )
        # a window that binds (engine/tracing.py WindowCounters): exported
        # by models whose window layers have a block pool of their own
        if "window_kv_blocks_total" in s:
            yield counter(
                "vllm:window_attn_context_tokens",
                "Tokens of context the window layers' attention calls "
                "would stream if no window bound, each once a call",
                s["window_attn_context_tokens_total"],
            )
            yield counter(
                "vllm:window_attn_read_tokens",
                "Tokens of context the window layers' attention calls "
                "stream: from the block (a ragged walk: the context window) "
                "that holds a row's floor to its causal reach",
                s["window_attn_read_tokens_total"],
            )
            yield counter(
                "vllm:shared_kv_attn_calls",
                "Attention calls of cross-attention layers, which read "
                "the one full-attention layer's cache rows and write none",
                s["shared_kv_attn_calls_total"],
            )
            yield gauge(
                "vllm:window_kv_blocks_total",
                "Block pool capacity of the window layers (HBM)",
                s["window_kv_blocks_total"],
            )
            yield gauge(
                "vllm:window_kv_blocks_free",
                "Free blocks of the window layers' pool",
                s["window_kv_blocks_free"],
            )
            yield counter(
                "vllm:window_kv_blocks_released",
                "Window blocks that live sequences gave back because no "
                "row to come can see them",
                s["window_kv_blocks_released_total"],
            )
            yield counter(
                "vllm:window_kv_block_waits",
                "Times a sequence found the window layers' pool dry (0 by "
                "the pool's size rule, kv_cache.window_pool_blocks)",
                s["window_kv_block_waits_total"],
            )
            # the same by step program, its name in the series' name (the
            # benchmark's reader sums a family over its labels): what each
            # program's attention calls stream and have to
            for program, by in s["window_attn_by_program"].items():
                for series, key, doc in (
                    ("window_attn_context_tokens", "context_tokens",
                     "vllm:window_attn_context_tokens_total of the "
                     f"{program} program alone"),
                    ("window_attn_read_tokens", "read_tokens",
                     "vllm:window_attn_read_tokens_total of the "
                     f"{program} program alone"),
                    ("full_attn_read_tokens", "full_read_tokens",
                     "Tokens of context the attention calls that see every "
                     f"row stream in the {program} program (the full "
                     "layers'; a cross-attention layer's), each once a "
                     "call"),
                    ("attn_rows_needed", "rows_needed",
                     f"Rows the {program} program's attention calls have "
                     "to read whatever implements them: a window layer's "
                     "from its first query's floor to its reach, a full "
                     "layer's its reach, each once a call"),
                    ("attn_pairs_needed", "pairs_needed",
                     f"(query, key) pairs the {program} program's "
                     "attention calls have to score: inside the window or "
                     "the causal reach, all attention layers"),
                ):
                    yield counter(f"vllm:{program}_{series}", doc, by[key])
            yield gauge(
                "vllm:kv_bytes_per_token",
                "Bytes one token of context holds in the paged pool for "
                "the whole context (the window layers' rows live in a pool "
                "of their own for sliding_window rows)",
                s["kv_bytes_per_token"],
            )
        # state-space layers of a hybrid stack (RecurrentCounters, kind
        # "mamba"): the KDA counters' meanings under their own names
        if "mamba_decode_calls_total" in s:
            yield counter(
                "vllm:mamba_decode_calls",
                "State-space decode steps the decode program ran (decode "
                "dispatches x fused iterations x Mamba layers); a ragged "
                "dispatch runs one more a layer for its decode rows",
                s["mamba_decode_calls_total"],
            )
            yield counter(
                "vllm:mamba_chunk_tokens",
                "Rows of the ragged dispatches that the state-space "
                "layers' span scan carried: every span's but the decode "
                "rows'",
                s["mamba_chunk_tokens_total"],
            )
            yield counter(
                "vllm:mamba_chunk_spans",
                "Spans of the ragged dispatches that the span scan carried: "
                "each loads and stores its slot's state once a layer and "
                "channel block",
                s["mamba_chunk_spans_total"],
            )
        # state-space mixers with heads (RecurrentCounters, kind "ssd"):
        # the same three meanings under their own names
        if "ssd_decode_calls_total" in s:
            yield counter(
                "vllm:ssd_decode_calls",
                "State-space decode steps the decode program ran (decode "
                "dispatches x fused iterations x layers, each of which "
                "holds a mixer with heads); a ragged dispatch runs one more "
                "a layer for its decode rows",
                s["ssd_decode_calls_total"],
            )
            yield counter(
                "vllm:ssd_chunk_tokens",
                "Rows of the ragged dispatches that the state-space "
                "mixers' span scan carried: every span's but the decode "
                "rows'",
                s["ssd_chunk_tokens_total"],
            )
            yield counter(
                "vllm:ssd_chunk_spans",
                "Spans of the ragged dispatches that the span scan carried: "
                "each loads and stores its slot's state once a layer",
                s["ssd_chunk_spans_total"],
            )
        # Gated DeltaNet layers (RecurrentCounters, kind "gdn"): the same
        # three meanings under their own names
        if "gdn_decode_calls_total" in s:
            yield counter(
                "vllm:gdn_decode_calls",
                "Gated DeltaNet decode steps the decode program ran (decode "
                "dispatches x fused iterations x GDN layers); a ragged "
                "dispatch runs one more a GDN layer for its decode rows",
                s["gdn_decode_calls_total"],
            )
            yield counter(
                "vllm:gdn_chunk_tokens",
                "Rows of the ragged dispatches that the Gated DeltaNet "
                "layers' span scan carried: every span's but the decode "
                "rows' (one row that continues a state: the decode step "
                "takes those)",
                s["gdn_chunk_tokens_total"],
            )
            yield counter(
                "vllm:gdn_chunk_spans",
                "Spans of the ragged dispatches that the span scan carried: "
                "each loads and stores its slot's state once a layer and "
                "pair of heads",
                s["gdn_chunk_spans_total"],
            )
        # recurrent-state layers (engine/tracing.py RecurrentCounters):
        # exported by hybrid stacks only
        if "kda_decode_calls_total" in s:
            yield counter(
                "vllm:kda_decode_calls",
                "Recurrent decode steps the decode program ran (decode "
                "dispatches x fused iterations x KDA layers); a ragged "
                "dispatch runs one more a KDA layer for its decode rows",
                s["kda_decode_calls_total"],
            )
            yield counter(
                "vllm:kda_chunk_tokens",
                "Rows of the ragged dispatches that the recurrent layers' "
                "span scan carried: every span's but the decode rows' (one "
                "row that continues a state: the decode step takes those)",
                s["kda_chunk_tokens_total"],
            )
            yield counter(
                "vllm:kda_chunk_spans",
                "Spans of the ragged dispatches that the span scan carried: "
                "each loads and stores its slot's recurrent state once a "
                "layer",
                s["kda_chunk_spans_total"],
            )
            yield counter(
                "vllm:kda_chunk_block_rows",
                "Rows of the blocks the span scan ran for those spans: a "
                "span of n rows takes ceil(n / C) blocks of C rows "
                "(ops/kda_pallas.py CHUNK), the last one padded",
                s["kda_chunk_block_rows_total"],
            )
        if "recurrent_state_bytes" in s:
            yield counter(
                "vllm:recurrent_state_resets",
                "Sequences started from a zero recurrent state (new, or "
                "recomputed after preemption)",
                s["recurrent_state_resets_total"],
            )
            yield gauge(
                "vllm:recurrent_state_bytes",
                "Bytes the recurrent layers' per-slot state and conv "
                "tails hold on the device",
                s["recurrent_state_bytes"],
            )
        if "prefix_lookups_bypassed_total" in s:
            yield counter(
                "vllm:prefix_lookups_bypassed",
                "Prefix-cache lookups answered as misses because the "
                "model keeps recurrent state no cached block can restore, "
                "or window layers that hold the last rows alone",
                s["prefix_lookups_bypassed_total"],
            )
        # looped stacks (engine/tracing.py LoopCounters): exported by
        # models whose layers run more than once a forward
        if "loop_layer_passes_total" in s:
            yield counter(
                "vllm:loop_layer_passes",
                "Layer executions of a looped stack (layers x passes run), "
                "summed over forwards and dispatches",
                s["loop_layer_passes_total"],
            )
            yield counter(
                "vllm:loop_layer_steps",
                "Layers x forwards (fused decode iterations and ragged "
                "steps) of a looped stack: the denominator of "
                "vllm:loop_layer_passes",
                s["loop_layer_steps_total"],
            )
        yield gauge(
            "vllm:ragged_stream_utilization",
            "Cumulative live tokens of the ragged dispatches over the "
            "token budget (live tokens / dispatches x "
            "max_num_batched_tokens), whatever width each ran at",
            s.get("ragged_stream_utilization", 0.0),
        )
        # goodput accounting (engine/perf_accounting.py): live roofline
        # utilization, phase throughput, HBM occupancy, compile events
        perf = s.get("perf")
        if perf:
            # utilization gauges exist only where the device's peaks are
            # known (perf_accounting.DEVICE_PEAKS or --perf-peak-*)
            if perf["mfu"] is not None:
                yield gauge(
                    "vllm:model_flops_utilization",
                    "Model FLOPs utilization over the accounting window "
                    "(goodput: live tokens only, padding waste excluded)",
                    perf["mfu"],
                )
            if perf["hbm_bw_util"] is not None:
                yield gauge(
                    "vllm:hbm_bandwidth_utilization",
                    "Estimated HBM bandwidth utilization over the window",
                    perf["hbm_bw_util"],
                )
            tps = GaugeMetricFamily(
                "vllm:tokens_per_second",
                "Live (unpadded) tokens per second by phase",
                labels=["model_name", "phase"],
            )
            tps.add_metric([self.model_name, "prefill"],
                           perf["prefill_tps"])
            tps.add_metric([self.model_name, "decode"], perf["decode_tps"])
            yield tps
            yield gauge("vllm:hbm_bytes_used",
                        "Device HBM bytes in use (memory_stats)",
                        perf["hbm_bytes_used"])
            yield gauge("vllm:hbm_bytes_total",
                        "Device HBM bytes available (memory_stats limit)",
                        perf["hbm_bytes_total"])
            yield gauge("vllm:hbm_bytes_peak",
                        "Peak device HBM bytes observed",
                        perf["hbm_bytes_peak"])
            # multi-chip ICI roofline (zero series on a 1-chip mesh):
            # collective bytes are per-chip wire traffic derived from the
            # sharding degree + model geometry, costed against the
            # per-chip ICI link bandwidth
            if perf.get("ici_bw_util") is not None:
                yield gauge(
                    "vllm:ici_bandwidth_utilization",
                    "Estimated per-chip ICI bandwidth utilization over the "
                    "window (collective bytes from the sharding spec + "
                    "model geometry vs the per-chip link peak)",
                    perf["ici_bw_util"],
                )
            coll = CounterMetricFamily(
                "vllm:collective_bytes",
                "Estimated per-chip collective bytes on the ICI by op "
                "(all_reduce: row-parallel matmul outputs; all_gather: "
                "vocab-sharded logits at consumed stream positions)",
                labels=["model_name", "op"],
            )
            for op, n in sorted(
                    (perf.get("collective_bytes") or {}).items()):
                coll.add_metric([self.model_name, op], n)
            yield coll
            compiles = CounterMetricFamily(
                "vllm:compile_events",
                "jit compile events per program kind and shape bucket",
                labels=["model_name", "kind", "bucket"],
            )
            for (kind, bucket), n in sorted(perf["compile_counts"].items()):
                compiles.add_metric([self.model_name, kind, bucket], n)
            yield compiles
            yield counter(
                "vllm:compile_time_seconds",
                "Cumulative wall seconds spent in jit compiles "
                "(first-call time per new program signature)",
                perf["compile_seconds_total"],
            )
            # a program's first call in stages (perf_accounting.py
            # BuildStages): the tracked programs by kind, the others as
            # kind `other`
            by_stage = CounterMetricFamily(
                "vllm:program_build_seconds",
                "Seconds of programs' first calls by program kind and "
                "stage: trace, lower, compile (the persistent cache did "
                "not answer), cache_load (it did), first_run (the rest of "
                "the call: transfer, dispatch, execution)",
                labels=["model_name", "kind", "stage"],
            )
            # a stage over all kinds is a family of its own beside it
            stage_families = (
                ("vllm:program_trace_seconds", "trace"),
                ("vllm:program_lower_seconds", "lower"),
                ("vllm:program_compile_seconds", "compile"),
                ("vllm:program_cache_load_seconds", "cache_load"),
                ("vllm:program_first_run_seconds", "first_run"))
            totals = {stage: 0.0 for _, stage in stage_families}
            for kind, stages in sorted(
                    perf["program_build_seconds"].items()):
                for stage, sec in stages.items():
                    by_stage.add_metric([self.model_name, kind, stage], sec)
                    totals[stage] += sec
            yield by_stage
            for name, stage in stage_families:
                yield counter(
                    name,
                    f"Seconds of programs' first calls spent in the stage "
                    f"{stage}, all kinds (vllm:program_build_seconds)",
                    totals[stage],
                )
            built = CounterMetricFamily(
                "vllm:program_builds",
                "Programs built (first calls of a new signature) by kind; "
                "`other`: backend compile requests of untracked programs",
                labels=["model_name", "kind"],
            )
            for kind, n in sorted(perf["program_builds"].items()):
                built.add_metric([self.model_name, kind], n)
            yield built
            yield counter(
                "vllm:compile_cache_hits",
                "Backend compile requests the persistent compile cache "
                "answered",
                perf["compile_cache_hits"],
            )
            yield counter(
                "vllm:compile_cache_misses",
                "Backend compile requests it did not answer: the program "
                "was compiled (a cold cache, a program it did not keep or "
                "did not find again, or no cache)",
                perf["compile_cache_misses"],
            )
            yield counter(
                "vllm:unexpected_recompiles",
                "Compiles observed after warmup marked the engine steady "
                "— a shape leaked past warmup (bug signal)",
                perf["unexpected_recompiles"],
            )
        # tenant attribution plane (production_stack_tpu/tenancy.py):
        # per-tenant consumption, label set bounded by the top-K +
        # tenant="other" policy. The engine folds before exporting;
        # fold_records here is defense-in-depth (idempotent) so this
        # exposition can never exceed top_k+1 tenant label values even
        # if an upstream snapshot ever arrives unfolded.
        tn = s.get("tenants")
        if tn and tn.get("enabled") and tn.get("tenants"):
            folded = fold_records(tn["tenants"], k=tn.get("top_k", 8),
                                  weight_key="chip_seconds")
            tok = CounterMetricFamily(
                "vllm:tenant_tokens",
                "Live tokens attributed per tenant and phase (prefill "
                "chunk tokens / decode goodput incl. accepted drafts); "
                "sums to the vllm:tokens_per_second totals",
                labels=["model_name", "tenant", "phase"],
            )
            chip = CounterMetricFamily(
                "vllm:tenant_chip_seconds",
                "Chip-seconds attributed per tenant: each dispatch's wall "
                "time split by the tenant's live-token share of the packed "
                "stream (conserves: per-tenant sum == total dispatch "
                "seconds)",
                labels=["model_name", "tenant"],
            )
            kvb = GaugeMetricFamily(
                "vllm:tenant_kv_blocks",
                "KV blocks currently held by each tenant's live sequences",
                labels=["model_name", "tenant"],
            )
            queue = SummaryMetricFamily(
                "vllm:tenant_queue_time_seconds",
                "Queue wait (arrival to scheduler admission) per tenant "
                "over finished requests",
                labels=["model_name", "tenant"],
            )
            for tenant, row in sorted(folded.items()):
                tok.add_metric([self.model_name, tenant, "prefill"],
                               row.get("prefill_tokens", 0))
                tok.add_metric([self.model_name, tenant, "decode"],
                               row.get("decode_tokens", 0))
                chip.add_metric([self.model_name, tenant],
                                row.get("chip_seconds", 0.0))
                kvb.add_metric([self.model_name, tenant],
                               row.get("kv_blocks", 0))
                queue.add_metric([self.model_name, tenant],
                                 row.get("requests", 0),
                                 row.get("queue_seconds_sum", 0.0))
            yield tok
            yield chip
            yield kvb
            yield queue
        # tiered KV cache (engine/kv_offload.py): per-tier hit ratios and
        # byte-accounted traffic the router's tier-weighted prefix scoring
        # scrapes, plus the async prefetch pipeline's latency histogram
        kv_tier = s.get("kv_tier")
        if kv_tier:
            ratio = GaugeMetricFamily(
                "vllm:kv_tier_hit_ratio",
                "Cumulative prefix-block hit ratio per KV tier "
                "(hbm = on-device pool, host = DRAM store, remote = shared "
                "kv_server)",
                labels=["model_name", "tier"],
            )
            for tier, t in sorted(kv_tier["tiers"].items()):
                q = t.get("queries", 0)
                ratio.add_metric([self.model_name, tier],
                                 t.get("hits", 0) / q if q else 0.0)
            yield ratio
            tier_bytes = CounterMetricFamily(
                "vllm:kv_tier_bytes",
                "KV slab bytes moved per tier and direction (from the HBM "
                "pool's perspective: in = promotion/prefetch import, out = "
                "demotion/offload export)",
                labels=["model_name", "tier", "direction"],
            )
            for key, nbytes in sorted(kv_tier["bytes"].items()):
                tier, direction = key.rsplit("_", 1)
                tier_bytes.add_metric(
                    [self.model_name, tier, direction], nbytes)
            yield tier_bytes
            pf = kv_tier.get("prefetch")
            if pf:
                # cumulative le-bucket form from the engine's per-bucket
                # counts (last count is the +Inf overflow)
                edges = pf["hist_buckets"]
                counts = pf["hist_counts"]
                acc, buckets = 0, []
                for edge, n in zip(edges, counts):
                    acc += n
                    buckets.append((str(edge), acc))
                buckets.append(("+Inf", acc + counts[-1]))
                hist = HistogramMetricFamily(
                    "vllm:kv_prefetch_seconds",
                    "Warm-tier prefix fetch latency (admission → staged "
                    "slabs ready to commit); overlapped with serving, "
                    "never blocking the loop",
                    labels=["model_name"],
                )
                hist.add_metric(lv, buckets, pf["seconds_sum"])
                yield hist
                yield gauge(
                    "vllm:kv_prefetch_overlap_fraction",
                    "Share of prefetch wall time overlapped with useful "
                    "engine work (1.0 = the serving loop never waited on "
                    "a tier fetch)",
                    pf.get("overlap_fraction", 1.0),
                )


class RequestPathCollector:
    """A request's time to first token in parts and the event loop's
    heartbeat (engine/tracing.py ``TtftParts``, ``LoopLag``): plain
    numbers the server's event loop adds up, read here at scrape time on
    that same loop."""

    def __init__(self, ttft, loop_lag, model_name: str):
        self.ttft, self.loop_lag = ttft, loop_lag
        self.model_name = model_name

    def collect(self):
        def family(cls, name, doc, label, values):
            fam = cls(name, doc, labels=["model_name", *label])
            for key, value in values:
                fam.add_metric([self.model_name, *key], value)
            return fam

        yield family(
            CounterMetricFamily, "vllm:request_ttft_part_seconds",
            "Seconds from the handler's entry to the first chunk written, "
            "by part, over the requests that got a first token: "
            "server_prep (received to enqueued), intake_wait (to the "
            "engine thread's intake), queue_wait (to admission), "
            "stream_wait (to the first launch that carried the prompt), "
            "prefill_steps (to the first token), server_deliver (to the "
            "first chunk written)", ["part"],
            (((p,), sec) for p, sec in self.ttft.seconds.items()))
        yield family(
            CounterMetricFamily, "vllm:request_ttft_parts",
            "Requests counted in vllm:request_ttft_part_seconds_total, by "
            "part (a response that is not streamed has no server_deliver)",
            ["part"], (((p,), n) for p, n in self.ttft.count.items()))
        lag = self.loop_lag
        yield family(
            CounterMetricFamily, "vllm:server_loop_lag_seconds",
            "Seconds the server's event loop ran behind its heartbeat, by "
            "the engine thread's phase at the instant the beat was due "
            "(a host phase, wait, idle, or none where it is not known)",
            ["during"],
            (((d,), sec) for d, sec in sorted(lag.lag_seconds.items())))
        yield family(
            CounterMetricFamily, "vllm:server_loop_lag_in_wait_seconds",
            "vllm:server_loop_lag_seconds_total{during=\"wait\"} as a "
            "family of its own: the beats due while the engine thread "
            "waited for the device", [],
            [((), lag.lag_seconds.get("wait", 0.0))])
        yield family(
            CounterMetricFamily, "vllm:server_loop_ticks",
            "Heartbeats of the server's event loop (about ten a second, "
            "re-armed from the wake): lag seconds over ticks is the mean "
            "wait of an arrival for the loop", [], [((), lag.ticks)])
        yield family(
            CounterMetricFamily, "vllm:server_loop_wall_seconds",
            "Wall seconds of the server's event loop, heartbeat start to "
            "its last wake", [], [((), lag.wall_seconds)])
        yield family(
            CounterMetricFamily, "vllm:server_loop_cpu_seconds",
            "On-CPU seconds of the event loop's thread over "
            "vllm:server_loop_wall_seconds_total: a loop that lags and is "
            "on the CPU has work of its own (a step's chunks to write), "
            "one that lags and is not was kept from running", [],
            [((), lag.cpu_seconds)])
        yield family(
            GaugeMetricFamily, "vllm:server_loop_lag_max_seconds",
            "Largest heartbeat lag since the last scrape", [],
            [((), lag.take_max())])


class LifecycleCollector:
    """Drain / watchdog lifecycle families, read at scrape time from a
    server-provided snapshot callable — the drain state machine and the
    stuck-step watchdog live on ``EngineServer``, not ``LLMEngine``, so
    they can't ride ``EngineStatsCollector``."""

    def __init__(self, source, model_name: str):
        self.source = source
        self.model_name = model_name

    def collect(self):
        s = self.source()
        labels = ["model_name"]
        lv = [self.model_name]

        def gauge(name, doc, value):
            g = GaugeMetricFamily(name, doc, labels=labels)
            g.add_metric(lv, value)
            return g

        def counter(name, doc, value):
            c = CounterMetricFamily(name, doc, labels=labels)
            c.add_metric(lv, value)
            return c

        yield gauge(
            "vllm:drain_state",
            "1 while the engine is DRAINING (readiness 503, new requests "
            "refused, in-flight sequences finishing under the drain "
            "deadline)",
            1.0 if s["draining"] else 0.0,
        )
        yield counter(
            "vllm:drain_rejected_requests",
            "Generation requests refused with 503 + Retry-After because "
            "the engine was draining",
            s["drain_rejected_total"],
        )
        yield counter(
            "vllm:drain_aborted_seqs",
            "Straggler sequences aborted when the drain deadline expired "
            "(KV blocks freed; also counted in vllm:aborted_seqs_total)",
            s["drain_aborted_total"],
        )
        yield gauge(
            "vllm:watchdog_stalled",
            "1 while the stuck-step watchdog sees no scheduler-step "
            "progress with work queued (readiness answers 503)",
            1.0 if s["watchdog_stalled"] else 0.0,
        )
        yield counter(
            "vllm:watchdog_stalls",
            "Stall episodes the stuck-step watchdog has detected",
            s["watchdog_stalls_total"],
        )
        yield gauge(
            "vllm:engine_warming",
            "1 while the engine runs its warmup compiles (readiness "
            "answers 503 \"warming\"; the router keeps the replica out "
            "of rotation until this clears)",
            1.0 if s.get("warming") else 0.0,
        )
        # a replica's start in parts (engine/tracing.py StartClock): plain
        # floats, fixed once the replica is ready
        start = s["start_seconds"]
        yield gauge(
            "vllm:engine_warmup_seconds",
            "Wall time the completed warmup (all shape variants) took; "
            "0 until it finishes",
            start["warmup"],
        )
        phases = GaugeMetricFamily(
            "vllm:engine_start_seconds",
            "Seconds of this replica's start by phase: process (creation "
            "to main()), backend_open, engine_build, server_bind and "
            "warmup follow each other; tokenizer, weights.make, "
            "weights.quantize, weights.lay_out, kv_pool, device_drain and "
            "engine_build.self (the rest) make up engine_build. A span "
            "ends at the host's return; device_drain is the one wait for "
            "the device",
            labels=["model_name", "phase"],
        )
        for phase, seconds in start.items():
            phases.add_metric([self.model_name, phase], seconds)
        yield phases
        # the phases a benchmark metric reads alone, each a family of its
        # own (a scraper that sums a family over its labels can read them)
        for name, what, parts in (
                ("vllm:engine_start_process_seconds", "the process's "
                 "creation to main()'s entry: the interpreter and the "
                 "imports", ("process",)),
                ("vllm:engine_start_backend_open_seconds", "jax.devices(): "
                 "the backend's opening", ("backend_open",)),
                ("vllm:engine_start_tokenizer_seconds", "reading the "
                 "model directory's tokenizer, the import of the library "
                 "that reads it included (engine/tokenizer.py "
                 "load_tokenizer_dir)", ("tokenizer",)),
                ("vllm:engine_start_weights_seconds", "making (or "
                 "loading), quantizing and laying out the weights, to the "
                 "host's return",
                 ("weights.make", "weights.quantize", "weights.lay_out")),
                ("vllm:engine_start_kv_pool_seconds", "sizing and "
                 "allocating the KV pool, a window pool and recurrent "
                 "state, to the host's return", ("kv_pool",))):
            yield gauge(name,
                        f"Seconds of this replica's start spent in {what}",
                        sum(start[p] for p in parts))
        yield gauge(
            "vllm:engine_start_to_ready_seconds",
            "The process's creation to the instant /ready first would "
            "answer 200; 0 until then",
            s["start_to_ready_seconds"],
        )


class DiagnosticsCollector:
    """Anomaly-capture families (engine tier), read at scrape time from
    ``DiagnosticsManager.stats()`` — same snapshot-callable pattern as
    ``LifecycleCollector`` so the capture thread never touches
    prometheus objects directly."""

    def __init__(self, source, model_name: str):
        self.source = source
        self.model_name = model_name

    def collect(self):
        s = self.source()
        bundles = CounterMetricFamily(
            "vllm:diagnostic_bundles",
            "Diagnostic bundles captured on an anomaly trigger "
            "(GET /debug/diagnostics indexes them)",
            labels=["model_name", "trigger", "tier"],
        )
        for trigger, count in sorted(s["bundles_total"].items()):
            bundles.add_metric([self.model_name, trigger, "engine"], count)
        yield bundles
        dropped = CounterMetricFamily(
            "vllm:diagnostic_bundles_dropped",
            "Capture requests skipped by the cooldown or the "
            "single-flight gate (evidence already being captured)",
            labels=["model_name", "trigger", "tier"],
        )
        for trigger, count in sorted(s["dropped_total"].items()):
            dropped.add_metric([self.model_name, trigger, "engine"], count)
        yield dropped
        seconds = SummaryMetricFamily(
            "vllm:diagnostic_capture_seconds",
            "Wall time spent capturing diagnostic bundles (off the "
            "serving path: capture runs on its own thread)",
            labels=["model_name", "tier"],
        )
        seconds.add_metric([self.model_name, "engine"],
                           s["capture_seconds_count"],
                           s["capture_seconds_sum"])
        yield seconds


class OverloadCollector:
    """Brownout / fair-share families (engine tier), read at scrape time
    from ``EngineServer._overload_snapshot`` — same snapshot-callable
    pattern as ``LifecycleCollector``. The router exports the same
    ``vllm:brownout_*`` families with ``tier="router"`` from the default
    registry (router/metrics.py); the tier label keeps a shared scrape
    collision-free. Per-tenant deficits come from the scheduler's DRR
    state, whose tenant set is already bounded (deficits exist only for
    tenants with pending work) and folded upstream via fold_records'
    top-k discipline on the attribution plane."""

    def __init__(self, source, model_name: str):
        self.source = source
        self.model_name = model_name

    def collect(self):
        s = self.source()
        b = s.get("brownout") or {}
        stage = GaugeMetricFamily(
            "vllm:brownout_stage",
            "Current staged-degradation level (0 healthy; 1 spec-decode "
            "grants shed; 2 + max_tokens clamped, KV prefetch paused; "
            "3 + over-weight tenants' new admissions shed)",
            labels=["model_name", "tier"],
        )
        stage.add_metric([self.model_name, "engine"],
                         float(b.get("stage", 0)))
        yield stage
        sheds = CounterMetricFamily(
            "vllm:brownout_sheds",
            "Work shed by the brownout ladder, by reason (spec grants "
            "suppressed, max_tokens clamps, prefetches skipped, tenant "
            "admissions refused)",
            labels=["model_name", "reason", "tier"],
        )
        for reason, count in sorted((b.get("sheds") or {}).items()):
            sheds.add_metric([self.model_name, reason, "engine"], count)
        yield sheds
        fair = s.get("fair_share") or {}
        deficit = GaugeMetricFamily(
            "vllm:fair_share_deficit",
            "Carried deficit-round-robin credit per tenant, in stream "
            "tokens (positive = the tenant is owed budget next dispatch)",
            labels=["model_name", "tenant"],
        )
        for tenant, value in sorted((fair.get("deficits") or {}).items()):
            deficit.add_metric([self.model_name, tenant], value)
        yield deficit


_BUCKETS_TTFT = (
    0.001, 0.005, 0.01, 0.02, 0.04, 0.06, 0.08, 0.1, 0.25, 0.5, 0.75,
    1.0, 2.5, 5.0, 7.5, 10.0,
)
_BUCKETS_E2E = (0.3, 0.5, 0.8, 1.0, 1.5, 2.0, 2.5, 5.0, 10.0, 15.0, 20.0, 30.0,
                40.0, 50.0, 60.0)


class ServerMetrics:
    """Engine-local metrics on a private CollectorRegistry: an engine pod is
    its own process in production, and a private registry keeps in-process
    test topologies (router + engines in one interpreter) collision-free."""

    def __init__(self, engine: "LLMEngine", model_name: str):
        self.registry = CollectorRegistry()
        self.collector = EngineStatsCollector(engine, model_name)
        self.registry.register(self.collector)
        self.model_name = model_name

        def hist(name, doc, buckets):
            return Histogram(name, doc, ["model_name"], buckets=buckets,
                             registry=self.registry)

        self.ttft = hist(
            "vllm:time_to_first_token_seconds", "Time to first token", _BUCKETS_TTFT
        )
        self.tpot = hist(
            "vllm:time_per_output_token_seconds",
            "Time per output token",
            (0.01, 0.025, 0.05, 0.075, 0.1, 0.15, 0.2, 0.3, 0.4, 0.5, 0.75, 1.0, 2.5),
        )
        self.e2e = hist(
            "vllm:e2e_request_latency_seconds",
            "End-to-end request latency",
            _BUCKETS_E2E,
        )
        # per-stage decomposition (queue → prefill → decode), observed from
        # the sequence lifecycle stamps carried on finished RequestOutputs
        self.queue_time = hist(
            "vllm:request_queue_time_seconds",
            "Time from arrival to scheduler admission (queue wait)",
            _BUCKETS_TTFT,
        )
        self.prefill_time = hist(
            "vllm:request_prefill_time_seconds",
            "Time from admission to first token (prefill incl. chunking)",
            _BUCKETS_TTFT,
        )
        self.decode_time = hist(
            "vllm:request_decode_time_seconds",
            "Time from first token to finish (decode)",
            _BUCKETS_E2E,
        )
        self.itl = hist(
            "vllm:inter_token_latency_seconds",
            "Mean inter-token latency per finished request",
            (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.075, 0.1, 0.15,
             0.2, 0.3, 0.4, 0.5, 0.75, 1.0, 2.5),
        )
        self.step_duration = hist(
            "vllm:scheduler_step_duration_seconds",
            "Engine step wall time (schedule + dispatch + postprocess)",
            (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
             0.5, 1.0, 2.5, 5.0),
        )
        # disaggregated P→D KV handoff (engine/kv_transfer.py): wire bytes
        # and wall time per transfer, labelled by which side this engine
        # played (push = prefill streaming out, recv = decode landing it,
        # export = pull-served /kv/export, import = pull-side /kv/export
        # consumption)
        self.kv_transfer_bytes = Counter(
            "vllm:kv_transfer_bytes",
            "KV bytes moved between engines for disaggregated serving, "
            "by direction (push/recv/export/import)",
            ["model_name", "direction"],
            registry=self.registry,
        )
        self.kv_transfer_seconds = Histogram(
            "vllm:kv_transfer_seconds",
            "Wall time of one KV transfer leg (gather + wire + scatter, "
            "overlapped), by direction",
            ["model_name", "direction"],
            buckets=(0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                     5.0, 10.0, 30.0, 60.0, 120.0),
            registry=self.registry,
        )
        #: per-direction {bytes, seconds, count} mirror of the transfer
        #: counters, for JSON debug surfaces
        self.transfer_totals: dict = {}

    def register_request_path(self, ttft, loop_lag) -> None:
        """Attach the time-to-first-token parts and the event loop's
        heartbeat, which EngineServer owns."""
        self.registry.register(
            RequestPathCollector(ttft, loop_lag, self.model_name))

    def register_lifecycle(self, source) -> None:
        """Attach the drain/watchdog snapshot source (EngineServer
        provides it after it builds its lifecycle state)."""
        self.registry.register(LifecycleCollector(source, self.model_name))

    def register_diagnostics(self, source) -> None:
        """Attach the anomaly-capture stats source
        (DiagnosticsManager.stats on EngineServer)."""
        self.registry.register(DiagnosticsCollector(source, self.model_name))

    def register_overload(self, source) -> None:
        """Attach the brownout/fair-share snapshot source
        (EngineServer._overload_snapshot)."""
        self.registry.register(OverloadCollector(source, self.model_name))

    def generate(self) -> bytes:
        from prometheus_client import generate_latest

        return generate_latest(self.registry)

    def ensure_registered(self) -> None:
        pass  # private registry — nothing global to re-register

    def unregister(self) -> None:
        pass

    def observe_request(self, start: float, first_token: float | None,
                        end: float, n_output: int) -> None:
        if first_token is not None:
            self.ttft.labels(self.model_name).observe(first_token - start)
            if n_output > 1:
                self.tpot.labels(self.model_name).observe(
                    (end - first_token) / (n_output - 1)
                )
        self.e2e.labels(self.model_name).observe(end - start)

    def observe_stages(self, out) -> None:
        """Per-stage decomposition from a FINISHED RequestOutput's lifecycle
        stamps (all monotonic, stamped scheduler/engine-side). Partial
        stamps — e.g. an abort before first token — observe only the stages
        that completed."""
        lv = self.model_name
        if out.arrival_time is not None and out.admit_time is not None:
            self.queue_time.labels(lv).observe(
                max(0.0, out.admit_time - out.arrival_time))
        if out.admit_time is not None and out.first_token_time is not None:
            self.prefill_time.labels(lv).observe(
                max(0.0, out.first_token_time - out.admit_time))
        if out.first_token_time is not None and out.finish_time is not None:
            decode = max(0.0, out.finish_time - out.first_token_time)
            self.decode_time.labels(lv).observe(decode)
            if out.num_output_tokens > 1:
                self.itl.labels(lv).observe(decode /
                                            (out.num_output_tokens - 1))

    def observe_transfer(self, direction: str, nbytes: int,
                         seconds: float) -> None:
        self.kv_transfer_bytes.labels(self.model_name, direction).inc(nbytes)
        self.kv_transfer_seconds.labels(self.model_name,
                                        direction).observe(seconds)
        # plain-dict mirror for /debug/perf and /debug/fleet (a labeled
        # Counter can only be read back via a scrape)
        t = self.transfer_totals.setdefault(
            direction, {"bytes": 0, "seconds": 0.0, "count": 0})
        t["bytes"] += nbytes
        t["seconds"] += seconds
        t["count"] += 1

    def observe_step(self, duration: float) -> None:
        self.step_duration.labels(self.model_name).observe(duration)
