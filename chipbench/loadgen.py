"""The load generator: OpenAI-style streaming /v1/completions with
token-id prompts, open or closed loop, one asyncio thread, no JAX.

All times are seconds on this process's monotonic clock relative to the
moment the measured window opens (``t_open``); the ramp runs at negative
times. An open-loop request is timed from when it was DUE, so a stall is
charged to every request it delays; how late the generator itself ran is
kept per request (``sent - due``).
"""

from __future__ import annotations

import asyncio
import itertools
import json
import random
import time

import aiohttp

from chipbench import traffic
from chipbench.stats import Record

# closed loop: callers start over this share of the ramp, so that the rest
# of it runs at full concurrency before the window opens
STAGGER_SHARE = 0.5


class LoadGen:
    def __init__(self, base_url: str, model: str, vocab: int, seed: int):
        self.url = base_url.rstrip("/") + "/v1/completions"
        self.model = model
        self.vocab = vocab
        self.seed = seed
        self.records: list[Record] = []
        self.t_open = 0.0  # monotonic time of window open
        self._session: aiohttp.ClientSession | None = None

    def now(self) -> float:
        return time.monotonic() - self.t_open

    async def __aenter__(self):
        self._session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=None, sock_read=300))
        return self

    async def __aexit__(self, *exc):
        await self._session.close()

    async def send(self, rec: Record, prompt_ids: list[int],
                   logprobs: int | None = None) -> Record:
        """One streamed request; fills ``rec`` and never raises for a
        failed request (the record says what happened)."""
        body = {"model": self.model, "prompt": prompt_ids,
                "max_tokens": rec.output_len, "temperature": 0,
                "ignore_eos": True, "stream": True,
                "stream_options": {"continuous_usage_stats": True}}
        if logprobs is not None:
            body["logprobs"] = logprobs
            rec.probe = {"tokens": [], "token_logprobs": [],
                         "top_logprobs": []}
        self.records.append(rec)
        rec.sent = self.now()
        seen = 0
        try:
            async with self._session.post(
                    self.url, json=body,
                    headers={"x-request-id": rec.rid}) as resp:
                rec.status = resp.status
                if resp.status != 200:
                    rec.error = (await resp.text())[:300]
                    return rec
                async for raw in resp.content:
                    if not raw.startswith(b"data:"):
                        continue
                    t = self.now()
                    payload = raw[5:].strip()
                    if payload == b"[DONE]":
                        rec.done = True
                        break
                    chunk = json.loads(payload)
                    if "error" in chunk:
                        rec.error = str(chunk["error"])[:300]
                        continue
                    usage = chunk.get("usage")
                    if usage:
                        n = int(usage["completion_tokens"])
                        rec.token_times.extend([t] * (n - seen))
                        seen = n
                    if rec.probe is not None:
                        for ch in chunk.get("choices", ()):
                            lp = ch.get("logprobs")
                            if lp:
                                for k in rec.probe:
                                    rec.probe[k].extend(lp.get(k, ()))
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError,
                ValueError) as e:
            rec.error = f"{type(e).__name__}: {e}"[:300]
        return rec

    def _record(self, index: int, prompt_len: int, output_len: int,
                due: float) -> Record:
        return Record(index, f"cb-{self.seed}-{index}", prompt_len,
                      output_len, due)

    async def run_open(self, mix: dict, seconds: float) -> None:
        """Send the schedule; returns when every request has ended."""
        sched = traffic.open_loop_schedule(mix, self.seed, seconds)
        prompts = [traffic.prompt_tokens(self.seed, r.index, r.prompt_len,
                                         self.vocab) for r in sched]
        tasks = []
        for r, ids in zip(sched, prompts):
            delay = r.due - self.now()
            if delay > 0:
                await asyncio.sleep(delay)
            rec = self._record(r.index, r.prompt_len, r.output_len, r.due)
            tasks.append(asyncio.ensure_future(self.send(rec, ids)))
        await asyncio.gather(*tasks)

    async def run_closed(self, mix: dict, seconds: float) -> None:
        """``callers`` callers, each sending its next request when the
        last one ended, until the window closes; the request in flight
        then is allowed to finish. Callers start staggered over the ramp
        and each caller's first request has its output cut at random, so
        completions are spread out before the window opens."""
        queue = traffic.closed_loop_queue(mix, self.seed)
        rng = random.Random(f"{self.seed}:callers")
        ramp = float(mix["ramp_s"])
        counter = itertools.count()

        async def caller(start: float, first_cut: float) -> None:
            delay = start - self.now()
            if delay > 0:
                await asyncio.sleep(delay)
            first = True
            while self.now() < seconds:
                i = next(counter)
                p, o = queue[i % len(queue)]
                if first:
                    o = max(2, int(o * first_cut))
                    first = False
                ids = traffic.prompt_tokens(self.seed, i, p, self.vocab)
                await self.send(self._record(i, p, o, self.now()), ids)

        stagger = STAGGER_SHARE * ramp
        await asyncio.gather(*[
            caller(-ramp + rng.uniform(0.0, stagger), rng.uniform(0.125, 1.0))
            for _ in range(int(mix["callers"]))])

    async def run(self, mix: dict, seconds: float) -> None:
        if mix["loop"] == "open":
            await self.run_open(mix, seconds)
        else:
            await self.run_closed(mix, seconds)
