"""Percentiles and window accounting over client-side request records.

A record is what the load generator keeps for one request (all times are
seconds on the load generator's monotonic clock, relative to window open):

    due         when the request was due to be sent
    sent        when it was sent
    token_times arrival time of every output token
    ok          200, the stream ended in [DONE], exactly max_tokens tokens
    counted     due inside [0, seconds)
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field


@dataclass
class Record:
    index: int
    rid: str
    prompt_len: int
    output_len: int
    due: float
    sent: float = math.nan
    token_times: list = field(default_factory=list)
    status: int = 0
    done: bool = False   # stream terminated by [DONE]
    error: str = ""
    probe: dict | None = None  # logprob entries, correctness probe only

    @property
    def ok(self) -> bool:
        return (self.status == 200 and self.done
                and len(self.token_times) == self.output_len)

    @property
    def ttft(self) -> float:
        return self.token_times[0] - self.due

    @property
    def tpot(self) -> float | None:
        n = len(self.token_times)
        if n < 2:
            return None
        return (self.token_times[-1] - self.token_times[0]) / (n - 1)


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def counted(records, seconds: float):
    """Requests whose due time falls in the window; they may finish after it."""
    return [r for r in records if 0.0 <= r.due < seconds]


def tokens_in_window(records, seconds: float) -> int:
    """Output tokens delivered inside the window, whoever sent the request."""
    return sum(1 for r in records for t in r.token_times if 0.0 <= t < seconds)


def end_to_end(records, seconds: float, chips: int) -> dict:
    """Every client-side end-to-end number this benchmark knows, by name.
    The caller reports the ones its cell lists."""
    win = counted(records, seconds)
    good = [r for r in win if r.ok]
    out = {"attempted": len(win), "failed": len(win) - len(good)}
    if good:
        ttfts = [r.ttft * 1e3 for r in good]
        out["ttft_p50_ms"] = percentile(ttfts, 50)
        tpots = [r.tpot * 1e3 for r in good if r.tpot is not None]
        if tpots:
            out["tpot_p50_ms"] = percentile(tpots, 50)
    toks = tokens_in_window(records, seconds)
    if toks:
        out["output_tok_s_chip"] = toks / seconds / chips
    return out
