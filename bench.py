"""Single-chip engine benchmark: throughput + TTFT + scenario sweep.

Measures, on the largest bf16 Llama that fits one v5e chip (llama-3b-class,
Llama-3.2-3B geometry, random-init weights — perf is weight-value
independent):

  1. short-context throughput (the headline): N concurrent requests,
     128-token prompts, 128 output tokens, greedy — sustained output
     tok/s/chip plus per-request TTFT p50/p99.
  2. long-context: 4k-token prompts — prefill throughput and TTFT.
  3. multi-round prefix reuse: second round of identical-prefix
     conversations — prefix-cache hit rate and the TTFT improvement the
     KV reuse buys (the reference's multi-round-qa win, its README's
     headline scenario).
  4. mixed steady-state chat, 5. speculative decoding,
  6. multi-chip TP: the ragged dispatch sharded across the named mesh at
     TP=4/8 — tok/s/chip, greedy bit-identity vs single-chip, zero
     post-warmup recompiles, and the ICI roofline utilization, and
  7. disaggregated prefill/decode: the same streamed requests through
     the orchestrated router over a 1-prefill + 1-decode pool vs one
     unified engine — TTFT/ITL p50/p95, the P→D transfer cost per
     request, and greedy bit-identity of every stream pair,
  8. tiered KV cache on multi-round QA: turn-N TTFT with the host tier
     off vs on under HBM eviction pressure, tier hit ratios, and
  9. noisy-neighbor fair-share: 8 tenants, one submitting 10x a
     victim's request count — victim TTFT/ITL p95 with the scheduler's
     DRR fair-share pass off vs on, greedy bit-identity across the
     toggle (fairness is pure host-side ordering).

Prints ONE JSON line: the headline metric/value/unit/vs_baseline plus the
scenario numbers as extra keys, stamped with the device it ran on.

vs_baseline normalises against the north-star target of 2,000 output
tok/s/chip (BASELINE.json; defined for Llama-3-8B on v5e-16 — this
single-chip 3B number is the per-chip proxy). The north-star p50 TTFT
target is 200 ms.

One process, one run: it opens the chip itself, refuses any backend that
is not a TPU (a CPU timing under these metric names would be a lie), and
any failure is a traceback and a non-zero exit. Run it through the chip
tool; ``chip_smoke.py`` is the quicker proof that the system starts.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np


def pctl(xs, p):
    return float(np.percentile(np.asarray(xs), p)) if xs else 0.0


def run_bench() -> None:
    from production_stack_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench.py measures the accelerator: the JAX backend here is "
            f"{jax.default_backend()!r}, not 'tpu'. Run it through the chip "
            "tool; CPU runs belong to the test suite.")

    from production_stack_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        ModelConfig,
        SchedulerConfig,
    )
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.sampling import SamplingParams
    from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

    model = "llama-3b-class"
    num_seqs = 192
    prompt_len = 128
    out_len = 128
    long_prompt_len = 4096
    long_n = 16

    # The headline config serves int8 W8A8 (engine/quant.py; labeled in the
    # metric string): decode is weight-bandwidth bound and int8 halves the
    # weight stream. PSTPU_BENCH_QUANT="" re-runs bf16.
    quant = os.environ.get("PSTPU_BENCH_QUANT", "int8") or None
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained(model, quant=quant),
        cache=CacheConfig(block_size=16),
        # VMEM envelope: the Pallas KV write stages a step's prefill_batch x
        # bucket token slabs in scoped VMEM at 4 KiB per token (KH=8,
        # D=128), so 4096 tokens fill the 16 MiB scoped limit. Long prompts
        # chunk through the 512 bucket instead of compiling bigger buckets.
        scheduler=SchedulerConfig(
            max_num_seqs=num_seqs,
            max_num_batched_tokens=1024,
            prefill_buckets=(128, 256, 512),
            multi_step=16,
            prefill_batch=8,
        ),
        mesh=MeshConfig(data=1, tensor=1),
    )
    mesh = build_mesh(cfg.mesh, devices=jax.devices()[:1])
    num_blocks = None  # sized from the device's free HBM
    engine = LLMEngine(cfg, mesh=mesh, num_blocks=num_blocks)

    rng = np.random.default_rng(0)

    def run_batch(tag: str, prompts: list, max_tokens: int):
        """Submit all prompts, drain. Returns (elapsed, produced, ttfts,
        cached, outputs, last_first): per-request generated tokens and the
        time from start to the LAST first-token (= end of prefill work)."""
        sp = SamplingParams(temperature=0.0, max_tokens=max_tokens,
                            ignore_eos=True)
        submit: dict[str, float] = {}
        first: dict[str, float] = {}
        cached: dict[str, int] = {}
        outputs: dict[str, list] = {}
        t0 = time.perf_counter()
        for i, toks in enumerate(prompts):
            rid = f"{tag}-{i}"
            engine.add_request(rid, prompt_token_ids=toks, sampling=sp)
            submit[rid] = time.perf_counter()
            outputs[rid] = []
        produced = 0
        while engine.has_unfinished():
            for out in engine.step():
                produced += len(out.new_token_ids)
                outputs.setdefault(out.request_id, []).extend(
                    out.new_token_ids)
                if out.request_id not in first and out.new_token_ids:
                    first[out.request_id] = time.perf_counter()
                    cached[out.request_id] = out.num_cached_tokens
        elapsed = time.perf_counter() - t0
        ttfts = [(first[r] - submit[r]) * 1000.0 for r in first]
        last_first = (max(first.values()) - t0) if first else elapsed
        return elapsed, produced, ttfts, cached, outputs, last_first

    def prompt(n):
        return rng.integers(10, cfg.model.vocab_size - 10, n).tolist()

    # compile all programs out of the timed region — cover every pow-2
    # prefill row-count variant the scenarios will hit (P=8@128, P=4@256,
    # P=2@512 via the long prompts, P=1) plus the decode program
    run_batch("warmup", [prompt(prompt_len)] * 8, 8)
    run_batch("warmup-4", [prompt(256)] * 4, 4)
    run_batch("warmup-long", [prompt(long_prompt_len)] * 2, 4)

    # 1) headline short-context throughput
    elapsed, produced, ttfts, _, _, _ = run_batch(
        "bench", [prompt(prompt_len) for _ in range(num_seqs)], out_len
    )
    tok_per_s = produced / elapsed

    # 2) long-context prefill: time to the LAST first-token (prefill work
    # only — draining decode tokens would dilute the rate)
    long_prompts = [prompt(long_prompt_len) for _ in range(long_n)]
    _, _, l_ttfts, _, _, l_last_first = run_batch("long", long_prompts, 2)
    prefill_tok_s = long_n * long_prompt_len / l_last_first

    # 3) multi-round prefix reuse: shared 1k-token context per user; round
    # 2 re-sends the FULL round-1 conversation (context + question +
    # generated answer) plus a new question — the reference's
    # multi-round-qa scenario
    ctx_len = 1024
    n_users = 32
    contexts = [prompt(ctx_len) for _ in range(n_users)]
    r1 = [c + prompt(32) for c in contexts]
    _, _, r1_ttfts, _, r1_out, _ = run_batch("round1", r1, 16)
    alloc = engine.scheduler.allocator
    hits0, queries0 = alloc.prefix_hits, alloc.prefix_queries
    r2 = [r1[i] + r1_out[f"round1-{i}"] + prompt(32)
          for i in range(n_users)]
    _, _, r2_ttfts, r2_cached, _, _ = run_batch("round2", r2, 16)
    # round-2-only counters (cumulative ones include every earlier phase)
    hits = alloc.prefix_hits - hits0
    queries = alloc.prefix_queries - queries0

    # 4) mixed steady-state chat: long decodes in flight while short
    # prompts keep arriving — the regime the ragged unified dispatch is
    # for (prefill chunks ride the same token-budget step as the decode
    # rows instead of stalling them behind bucketed prefill phases).
    # Throughput counts EVERY generated token; MFU comes from the live
    # goodput accountant over the scenario window.
    mix_long_n = 32
    mix_long_prompt = 512
    mix_long_out = 256
    mix_short_n = 64
    mix_short_out = 16
    mix_every = 4  # steps between short-prompt arrivals
    sp_long = SamplingParams(temperature=0.0, max_tokens=mix_long_out,
                             ignore_eos=True)
    sp_short = SamplingParams(temperature=0.0, max_tokens=mix_short_out,
                              ignore_eos=True)
    if engine.perf is not None:
        engine.perf._events.clear()  # scope the MFU window to this scenario
    mix_t0 = time.perf_counter()
    for i in range(mix_long_n):
        engine.add_request(f"mix-long-{i}",
                           prompt_token_ids=prompt(mix_long_prompt),
                           sampling=sp_long)
    mix_produced = 0
    mix_injected = 0
    mix_steps = 0
    while engine.has_unfinished():
        if mix_injected < mix_short_n and mix_steps % mix_every == 0:
            engine.add_request(f"mix-short-{mix_injected}",
                               prompt_token_ids=prompt(prompt_len),
                               sampling=sp_short)
            mix_injected += 1
        for out in engine.step():
            mix_produced += len(out.new_token_ids)
        mix_steps += 1
    mix_elapsed = time.perf_counter() - mix_t0
    mix_tok_s = mix_produced / mix_elapsed
    mix_mfu = (engine.perf.stats_fields()["mfu"]
               if engine.perf is not None else 0.0)
    mix_impl = engine.attention_impl

    # 5) speculative decoding on repetitive traffic: motif-loop prompts
    # (the multi-round verbatim re-feed shape — greedy continuations fall
    # into short cycles the n-gram proposer then predicts) at modest
    # batch, spec off then on, SAME prompts — decode tok/s isolated from
    # prefill, plus the acceptance the EWMA controller settled at. Both
    # runs force the ragged impl (verification is fused into the ragged
    # dispatch; speculation never runs bucketed) and bf16 weights: int8's
    # quantization noise puts the decode and ragged programs on opposite
    # sides of argmax near-ties, which would mis-read as a spec-identity
    # failure when it is cross-program rounding (present with spec off
    # too). The stream budget shrinks to the spans actually packed so
    # verify steps don't pay for 1024 budget-padded lanes.
    import dataclasses
    import gc

    spec_k = int(os.environ.get("PSTPU_BENCH_SPEC_K", "4"))
    spec_n = 32
    spec_out = 128
    spec_budget = 256
    motifs = [rng.integers(10, cfg.model.vocab_size - 10, 8).tolist()
              for _ in range(spec_n)]
    spec_prompts = [m * 8 for m in motifs]  # 64-token looping prompts

    del engine
    gc.collect()

    def spec_run(k: int):
        nonlocal engine
        sched = dataclasses.replace(cfg.scheduler, spec_ngram_k=k,
                                    max_num_seqs=max(spec_n, 4),
                                    max_num_batched_tokens=spec_budget)
        engine = LLMEngine(
            dataclasses.replace(
                cfg, scheduler=sched, attention_impl="ragged",
                model=dataclasses.replace(cfg.model, quant=None),
            ),
            mesh=mesh, num_blocks=num_blocks,
        )
        run_batch(f"spec-warm-{k}", [prompt(prompt_len)] * 2, 8)
        elapsed, produced, _, _, outs, last_first = run_batch(
            f"spec-{k}", [list(p) for p in spec_prompts], spec_out
        )
        decode_s = max(elapsed - last_first, 1e-9)
        decode_tok_s = (produced - spec_n) / decode_s
        stats = engine.stats()
        del engine
        gc.collect()
        engine = None
        # strip the tag prefix so off/on runs compare by prompt index
        toks = [outs[f"spec-{k}-{i}"] for i in range(spec_n)]
        return decode_tok_s, toks, stats

    spec_off_tok_s, spec_off_out, _ = spec_run(0)
    spec_on_tok_s, spec_on_out, spec_stats = spec_run(spec_k)

    # 6) multi-chip TP: the ragged unified dispatch sharded across the
    # named mesh (docs/roofline.md "Multi-chip") — the SAME greedy
    # prompts at TP=1 then TP=4/8, reporting tok/s/chip (the honest
    # multi-chip number), greedy bit-identity vs the single-chip run,
    # the post-warmup unexpected-recompile count (must stay 0: the
    # sharded signature is warmed exactly like the unsharded one), and
    # the ICI roofline utilization the accountant prices from the
    # sharding spec. KV heads must divide the tensor axis for the paged
    # KV pool to actually shard (llama-3b-class KH=8 covers TP=4/8).
    # bf16: int8 cross-program rounding would mis-read as a sharding
    # identity failure, same argmax-near-tie caveat as scenario 5.
    mc_n = 32
    mc_out = 64
    mc_prompt = 128
    mc_model = dataclasses.replace(cfg.model, quant=None)
    mc_sched = dataclasses.replace(
        cfg.scheduler, max_num_seqs=max(mc_n, 4),
        max_num_batched_tokens=256,
        prefill_buckets=(128,),
    )
    mc_prompts = [prompt(mc_prompt) for _ in range(mc_n)]
    ndev = len(jax.devices())

    def mc_run(tp: int):
        nonlocal engine
        engine = LLMEngine(
            dataclasses.replace(cfg, model=mc_model, scheduler=mc_sched,
                                attention_impl="ragged",
                                mesh=MeshConfig(data=1, tensor=tp)),
            mesh=build_mesh(MeshConfig(data=1, tensor=tp),
                            devices=jax.devices()[:tp]),
            num_blocks=num_blocks,
        )
        engine.warmup()  # covers the sharded signature + marks steady
        if engine.perf is not None:
            engine.perf._events.clear()  # scope the window to the run
        elapsed, produced, _, _, outs, _ = run_batch(
            f"mc{tp}", [list(p) for p in mc_prompts], mc_out)
        snap = engine.perf.snapshot() if engine.perf is not None else {}
        del engine
        gc.collect()
        engine = None
        toks = [outs[f"mc{tp}-{i}"] for i in range(mc_n)]
        coll = snap.get("collective_bytes_total") or {}
        return {
            "tp": tp,
            "tok_s": round(produced / elapsed, 1),
            "tok_s_chip": round(produced / elapsed / tp, 1),
            "ici_bandwidth_utilization": round(
                snap.get("ici_bandwidth_utilization", 0.0), 6),
            "collective_bytes_total": {k: round(v, 1)
                                       for k, v in sorted(coll.items())},
            "unexpected_recompiles": (snap.get("compile") or {}).get(
                "unexpected_recompiles", 0),
        }, toks

    mc_base, mc_base_out = mc_run(1)
    mc_runs = [mc_base]
    for mc_tp in (4, 8):
        if mc_tp > ndev:
            continue
        row, out_tp = mc_run(mc_tp)
        row["greedy_identical"] = out_tp == mc_base_out
        mc_runs.append(row)

    # 7) disaggregated prefill/decode vs unified: the SAME streamed
    # greedy requests twice through the real router — once over a
    # 1-prefill + 1-decode pool (orchestrated two-hop: first token from
    # the prefill engine, KV pushed to /kv/recv, decode spliced in with
    # no re-prefill), once over one unified engine (same router in the
    # path, so the delta is disaggregation, not proxy overhead).
    # Reports TTFT and ITL p50/p95 per side, the wire cost of the
    # handoff (seconds and MB per request from the prefill engine's
    # transfer accounting — the same numbers /debug/perf kv_transfer
    # serves), the router's per-outcome disagg counters, and greedy
    # bit-identity of every stream pair. bf16 for the same
    # argmax-near-tie reason as scenarios 5/6.
    import asyncio

    dis_n = 8
    dis_out = 64
    dis_reps = 4
    dis_prompts = [f"request {i}: " + "lorem ipsum dolor sit amet " * dis_reps
                   for i in range(dis_n)]

    async def _sse_events(resp):
        buf = b""
        async for chunk in resp.content.iter_any():
            buf += chunk
            while b"\n\n" in buf:
                block, buf = buf.split(b"\n\n", 1)
                if block.startswith(b"data: "):
                    data = block[len(b"data: "):]
                    if data == b"[DONE]":
                        return
                    yield json.loads(data), time.perf_counter()

    async def disagg_vs_unified():
        import aiohttp
        from aiohttp.test_utils import TestServer

        from production_stack_tpu.engine.server import EngineServer
        from production_stack_tpu.router.app import RouterApp, build_parser
        from production_stack_tpu.router.metrics import disagg_snapshot

        def mk_server(role):
            scfg = EngineConfig(
                model=dataclasses.replace(cfg.model, quant=None),
                cache=CacheConfig(block_size=16, num_blocks=512),
                scheduler=dataclasses.replace(
                    cfg.scheduler, max_num_seqs=max(dis_n, 4),
                    max_num_batched_tokens=256, prefill_buckets=(256,)),
                mesh=MeshConfig(data=1, tensor=1),
                role=role,
            )
            return EngineServer(scfg)

        async def start_stack(roles, extra_router_args):
            servers = [mk_server(r) for r in roles]
            sites = []
            urls = []
            for es in servers:
                ts = TestServer(es.build_app())
                await ts.start_server()
                sites.append(ts)
                urls.append(f"http://127.0.0.1:{ts.port}")
            args = build_parser().parse_args([
                "--service-discovery", "static",
                "--static-backends", ",".join(urls),
                "--static-models", ",".join([model] * len(urls)),
            ] + extra_router_args)
            router_ts = TestServer(RouterApp(args).build_app())
            await router_ts.start_server()
            return servers, sites, router_ts

        async def one_request(session, base, text, timings=None):
            payload = {"model": model, "prompt": text,
                       "max_tokens": dis_out, "temperature": 0,
                       "ignore_eos": True, "stream": True}
            t0 = time.perf_counter()
            out, usage, stamps = "", None, []
            async with session.post(f"{base}/v1/completions",
                                    json=payload) as r:
                assert r.status == 200, await r.text()
                async for ev, t in _sse_events(r):
                    if ev.get("choices"):
                        out += ev["choices"][0]["text"]
                        stamps.append(t)
                    if ev.get("usage"):
                        usage = ev["usage"]
            if timings is not None and stamps:
                timings["ttft"].append((stamps[0] - t0) * 1000.0)
                timings["gaps"].extend(
                    (b - a) * 1000.0 for a, b in zip(stamps, stamps[1:]))
            return out, usage

        async def measure(base):
            async with aiohttp.ClientSession() as session:
                # out-of-band warmup request compiles both sides' programs
                await one_request(session, base, "warmup " * dis_reps)
                timings = {"ttft": [], "gaps": []}
                results = await asyncio.gather(*[
                    one_request(session, base, p, timings)
                    for p in dis_prompts])
            texts = [r[0] for r in results]
            usages = [r[1] for r in results]
            return {
                "ttft_p50_ms": round(pctl(timings["ttft"], 50), 1),
                "ttft_p95_ms": round(pctl(timings["ttft"], 95), 1),
                "itl_p50_ms": round(pctl(timings["gaps"], 50), 2),
                "itl_p95_ms": round(pctl(timings["gaps"], 95), 2),
            }, texts, usages

        out0 = disagg_snapshot()
        servers, sites, router_ts = await start_stack(
            ["prefill", "decode"],
            ["--static-backend-roles", "prefill,decode",
             "--routing-logic", "disaggregated_prefill_orchestrated"])
        try:
            d_lat, d_texts, d_usages = await measure(
                f"http://127.0.0.1:{router_ts.port}")
            push = dict(servers[0].metrics.transfer_totals.get("push") or {})
            spliced = servers[1].engine.stats().get("spliced_seqs_total", 0)
        finally:
            await router_ts.close()
            for ts in sites:
                await ts.close()
        outcomes = {k: v - out0.get(k, 0)
                    for k, v in disagg_snapshot().items()
                    if v - out0.get(k, 0)}

        servers, sites, router_ts = await start_stack(
            ["unified"], ["--routing-logic", "roundrobin"])
        try:
            u_lat, u_texts, u_usages = await measure(
                f"http://127.0.0.1:{router_ts.port}")
        finally:
            await router_ts.close()
            for ts in sites:
                await ts.close()

        pushes = max(push.get("count", 0), 1)
        return {
            "requests": dis_n,
            "out_len": dis_out,
            "disagg": d_lat,
            "unified": u_lat,
            "transfer": {
                "pushes": push.get("count", 0),
                "seconds_per_request": round(
                    push.get("seconds", 0.0) / pushes, 4),
                "mb_per_request": round(
                    push.get("bytes", 0) / pushes / 1e6, 3),
            },
            "spliced_seqs": spliced,
            "outcomes": outcomes,
            "greedy_identical": d_texts == u_texts,
            "usage_identical": d_usages == u_usages,
        }

    disagg_row = asyncio.run(disagg_vs_unified())

    # 8) tiered KV cache on multi-round QA (docs/kv_tiering.md): the SAME
    # multi-round conversations twice — once with the host tier + async
    # prefetch on, once with HBM only — over a DELIBERATELY small HBM
    # pool, so round-N re-admissions miss in HBM. With tiering off the
    # miss recomputes the whole conversation; with tiering on the
    # evicted/offloaded blocks prefetch back from host DRAM while the
    # sequence parks in PREFETCHING (the serving loop never blocks).
    # Reports turn-1 vs turn-N TTFT per side, the tiered engine's
    # per-tier hit ratios + byte flows + prefetch overlap fraction, and
    # greedy bit-identity of every answer (the warm tiers must be
    # invisible to outputs). Users run one at a time within a round to
    # maximise LRU churn between a user's turns. bf16 for the same
    # argmax-near-tie reason as scenarios 5-7.
    t8_users = 8
    t8_rounds = 3
    t8_ctx = 512
    t8_q = 32
    t8_out = 32
    t8_blocks = 256  # small pool: force HBM eviction
    t8_contexts = [prompt(t8_ctx) for _ in range(t8_users)]
    t8_questions = [[prompt(t8_q) for _ in range(t8_rounds)]
                    for _ in range(t8_users)]
    t8_sched = dataclasses.replace(
        cfg.scheduler, max_num_seqs=4, max_num_batched_tokens=256,
        prefill_buckets=(256,))

    def tier_run(tiered: bool):
        nonlocal engine
        t8_cache = dataclasses.replace(
            cfg.cache,
            kv_host_cache_bytes=(1 << 30) if tiered else 0,
            kv_prefetch_workers=1)
        engine = LLMEngine(
            dataclasses.replace(
                cfg, cache=t8_cache, scheduler=t8_sched,
                model=dataclasses.replace(cfg.model, quant=None)),
            mesh=mesh, num_blocks=t8_blocks,
        )
        run_batch(f"t8-warm-{tiered}", [prompt(prompt_len)] * 2, 4)
        convs = [list(c) for c in t8_contexts]
        ttft_by_round: list[list[float]] = [[] for _ in range(t8_rounds)]
        answers = []
        for r in range(t8_rounds):
            for u in range(t8_users):
                convs[u] = convs[u] + t8_questions[u][r]
                _, _, ttfts_u, _, outs_u, _ = run_batch(
                    f"t8-{int(tiered)}-r{r}-u{u}", [list(convs[u])], t8_out)
                ttft_by_round[r].extend(ttfts_u)
                ans = outs_u[f"t8-{int(tiered)}-r{r}-u{u}-0"]
                answers.append(ans)
                convs[u] = convs[u] + ans
        tier_snap = (engine.stats() or {}).get("kv_tier")
        del engine
        gc.collect()
        engine = None
        return ttft_by_round, answers, tier_snap

    off_ttfts, off_answers, _ = tier_run(False)
    on_ttfts, on_answers, t8_tier = tier_run(True)
    t8_tiers = (t8_tier or {}).get("tiers") or {}
    t8_host = t8_tiers.get("host") or {}
    t8_pf = (t8_tier or {}).get("prefetch") or {}

    def _hit_ratio(t):
        return round(t.get("hits", 0) / max(t.get("queries", 0), 1), 3)

    tier_row = {
        "users": t8_users,
        "rounds": t8_rounds,
        "context_len": t8_ctx,
        "hbm_blocks": t8_blocks,
        "turn1_ttft_p50_ms": {
            "tiering_off": round(pctl(off_ttfts[0], 50), 1),
            "tiering_on": round(pctl(on_ttfts[0], 50), 1),
        },
        "turnN_ttft_p50_ms": {
            "tiering_off": round(pctl(off_ttfts[-1], 50), 1),
            "tiering_on": round(pctl(on_ttfts[-1], 50), 1),
        },
        "turnN_speedup": round(
            pctl(off_ttfts[-1], 50) / max(pctl(on_ttfts[-1], 50), 1e-9), 3),
        "tier_hit_ratio": {name: _hit_ratio(t8_tiers.get(name) or {})
                           for name in ("hbm", "host", "remote")},
        "host_bytes_used": t8_host.get("bytes_used", 0),
        "hbm_demotions": (t8_tiers.get("hbm") or {}).get("demotions", 0),
        "prefetch": {
            "committed": t8_pf.get("committed", 0),
            "dropped": t8_pf.get("dropped", 0),
            "blocks": t8_pf.get("blocks", 0),
            "overlap_fraction": round(t8_pf.get("overlap_fraction", 0.0), 3),
        },
        "greedy_identical": on_answers == off_answers,
    }

    # 9) noisy-neighbor fair-share: 8 tenants, one submitting 10x a
    # victim's request count into a scheduler with room for only a few
    # concurrent sequences — the FIFO admission queue makes every victim
    # wait out the noisy tenant's backlog. With --fair-share the stride
    # dequeue + DRR token split serve victims at their weight instead.
    # Fairness is pure host-side ordering, so every tenant's greedy
    # output must be bit-identical across the toggle.
    nn_victims = 7
    nn_victim_reqs = 2
    nn_noisy_reqs = 10 * nn_victim_reqs
    nn_prompt = 256
    nn_out = 32
    nn_sched = dataclasses.replace(
        cfg.scheduler, max_num_seqs=8,
        max_num_batched_tokens=256,
        prefill_buckets=(256,))
    nn_noisy_prompts = [prompt(nn_prompt) for _ in range(nn_noisy_reqs)]
    nn_victim_prompts = [[prompt(nn_prompt) for _ in range(nn_victim_reqs)]
                         for _ in range(nn_victims)]

    # the enforcement run gates submissions through the REAL router-tier
    # QuotaManager (submission is this harness' admission point): noisy's
    # bucket holds 2 requests with ~zero refill, so 8 of its 10 burst
    # requests are rejected before ever touching the engine
    from production_stack_tpu.router.quota import QuotaManager

    nn_noisy_budget = 2
    nn_quota = QuotaManager.from_json(json.dumps({"tenants": {"noisy": {
        "rps": 0.001, "burst_s": nn_noisy_budget / 0.001}}}))

    def fairness_run(fair: bool, quota=None):
        nonlocal engine
        engine = LLMEngine(
            dataclasses.replace(
                cfg, scheduler=dataclasses.replace(nn_sched,
                                                   fair_share=fair),
                model=dataclasses.replace(cfg.model, quant=None)),
            mesh=mesh, num_blocks=num_blocks,
        )
        run_batch(f"nn-warm-{fair}", [prompt(nn_prompt)] * 2, 4)
        sp = SamplingParams(temperature=0.0, max_tokens=nn_out,
                            ignore_eos=True)
        submit: dict[str, float] = {}
        stamps: dict[str, list] = {}
        outs: dict[str, list] = {}
        rejections: dict[str, int] = {}

        def _admit(rid, toks, tenant):
            if quota is not None:
                verdict = quota.check(tenant, nn_prompt + nn_out,
                                      now=time.monotonic())
                if not verdict.allowed:
                    rejections[tenant] = rejections.get(tenant, 0) + 1
                    return
            engine.add_request(rid, prompt_token_ids=toks, sampling=sp,
                               tenant=tenant)
            submit[rid] = time.perf_counter()

        # the noisy tenant's burst lands first: without enforcement every
        # victim queues behind all of it
        for i in range(nn_noisy_reqs):
            _admit(f"nn-noisy-{i}", nn_noisy_prompts[i], "noisy")
        for v in range(nn_victims):
            for i in range(nn_victim_reqs):
                _admit(f"nn-v{v}-{i}", nn_victim_prompts[v][i],
                       f"tenant-{v}")
        while engine.has_unfinished():
            for out in engine.step():
                if out.new_token_ids:
                    stamps.setdefault(out.request_id, []).append(
                        time.perf_counter())
                    outs.setdefault(out.request_id, []).extend(
                        out.new_token_ids)
        victim = [r for r in stamps if not r.startswith("nn-noisy")]
        noisy = [r for r in stamps if r.startswith("nn-noisy")]

        def _ttfts(rids):
            return [(stamps[r][0] - submit[r]) * 1000.0 for r in rids]

        def _itls(rids):
            return [(b - a) * 1000.0 for r in rids
                    for a, b in zip(stamps[r], stamps[r][1:])]

        row = {
            "victim_ttft_p95_ms": round(pctl(_ttfts(victim), 95), 1),
            "victim_itl_p95_ms": round(pctl(_itls(victim), 95), 1),
            "noisy_ttft_p95_ms": round(pctl(_ttfts(noisy), 95), 1),
            "victim_itl_p95_ms_by_tenant": {
                f"tenant-{v}": round(pctl(_itls(
                    [r for r in victim if r.startswith(f"nn-v{v}-")]),
                    95), 1)
                for v in range(nn_victims)},
        }
        del engine
        gc.collect()
        engine = None
        return row, outs, rejections

    nn_off, nn_off_outs, _ = fairness_run(False)
    nn_on, nn_on_outs, nn_rejections = fairness_run(True, quota=nn_quota)
    fair_row = {
        "tenants": nn_victims + 1,
        "noisy_over_victim_requests": nn_noisy_reqs // nn_victim_reqs,
        "victim_ttft_p95_ms": {
            "enforcement_off": nn_off["victim_ttft_p95_ms"],
            "enforcement_on": nn_on["victim_ttft_p95_ms"],
        },
        "victim_itl_p95_ms": {
            "enforcement_off": nn_off["victim_itl_p95_ms"],
            "enforcement_on": nn_on["victim_itl_p95_ms"],
        },
        "victim_itl_p95_ms_by_tenant": {
            t: {"enforcement_off": nn_off["victim_itl_p95_ms_by_tenant"][t],
                "enforcement_on": nn_on["victim_itl_p95_ms_by_tenant"][t]}
            for t in nn_off["victim_itl_p95_ms_by_tenant"]},
        "noisy_ttft_p95_ms": {
            "enforcement_off": nn_off["noisy_ttft_p95_ms"],
            "enforcement_on": nn_on["noisy_ttft_p95_ms"],
        },
        "victim_ttft_speedup": round(
            nn_off["victim_ttft_p95_ms"]
            / max(nn_on["victim_ttft_p95_ms"], 1e-9), 3),
        "quota": {"noisy_budget_requests": nn_noisy_budget,
                  "rejections": nn_rejections},
        # every request admitted under enforcement (all victims + noisy's
        # in-budget head) generated the same greedy tokens as the
        # enforcement-off run — fairness/quota are pure admission +
        # ordering, never a dispatch-shape change
        "greedy_identical_in_budget": all(
            nn_on_outs[r] == nn_off_outs[r] for r in nn_on_outs),
    }

    # config-cohort stamp (perf_ledger.fingerprint): what this ran on
    from production_stack_tpu import perf_ledger as _pl

    _dev = jax.local_devices()[0]
    bench_fp = _pl.fingerprint(
        model=model, role="unified", tensor_parallel=1,
        attention_impl=cfg.attention_impl, dtype=cfg.model.dtype,
        quantization=quant or "", speculative=False, n_chips=1,
        jax_version=str(jax.__version__), platform=str(_dev.platform),
        chip=str(getattr(_dev, "device_kind", "") or ""))

    target = 2000.0
    print(json.dumps({
        "metric": f"output throughput ({model}, {quant or 'bf16'}, "
                  f"{num_seqs} concurrent, "
                  f"{prompt_len}p/{out_len}o, 1 chip)",
        "status": "ok",
        "ts": time.time(),
        "fingerprint": bench_fp,
        "value": round(tok_per_s, 1),
        "unit": "tok/s/chip",
        "vs_baseline": round(tok_per_s / target, 3),
        "ttft_p50_ms": round(pctl(ttfts, 50), 1),
        "ttft_p99_ms": round(pctl(ttfts, 99), 1),
        "long_context": {
            "prompt_len": long_prompt_len,
            "concurrent": long_n,
            "prefill_tok_s": round(prefill_tok_s, 1),
            "ttft_p50_ms": round(pctl(l_ttfts, 50), 1),
            "ttft_p99_ms": round(pctl(l_ttfts, 99), 1),
        },
        "multi_round": {
            "users": n_users,
            "context_len": ctx_len,
            "round1_ttft_p50_ms": round(pctl(r1_ttfts, 50), 1),
            "round2_ttft_p50_ms": round(pctl(r2_ttfts, 50), 1),
            "round2_cached_tokens_p50": int(np.median(
                list(r2_cached.values()) or [0])),
            "prefix_cache_hit_rate": round(hits / max(queries, 1), 3),
        },
        "mixed_chat": {
            "attention_impl": mix_impl,
            "long_decoders": mix_long_n,
            "long_out": mix_long_out,
            "short_arrivals": mix_injected,
            "short_out": mix_short_out,
            "tok_s_chip": round(mix_tok_s, 1),
            "mfu": round(mix_mfu, 4),
        },
        "speculative": {
            "attention_impl": "ragged",
            "k": spec_k,
            "seqs": spec_n,
            "out_len": spec_out,
            "decode_tok_s_off": round(spec_off_tok_s, 1),
            "decode_tok_s_on": round(spec_on_tok_s, 1),
            "speedup": round(spec_on_tok_s / max(spec_off_tok_s, 1e-9), 3),
            "acceptance_rate": round(
                spec_stats.get("spec_decode_acceptance_rate", 0.0), 3),
            "tokens_per_step": round(
                spec_stats.get("spec_decode_tokens_per_step", 0.0), 3),
            "greedy_identical": spec_on_out == spec_off_out,
        },
        "multichip": {
            "attention_impl": "ragged",
            "model": mc_model.name,
            "devices_available": ndev,
            "seqs": mc_n,
            "prompt_len": mc_prompt,
            "out_len": mc_out,
            "runs": mc_runs,
        },
        "disagg": disagg_row,
        "kv_tiering": tier_row,
        "noisy_neighbor": fair_row,
    }))


def main() -> None:
    run_bench()


if __name__ == "__main__":
    main()
