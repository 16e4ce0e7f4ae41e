"""`kda_chunk_fill_pct`: the rows the KDA span kernel carried over the
rows of the blocks it ran for them, read by `prom_ratio` from two counters
on recorded `/metrics` text. A span of n rows takes ceil(n / C) blocks of
C rows, the last one padded: near 100 where long prompts fill the stream
(one partial block in ~1400 rows), lower where the spans are short. Never
`None` for a program that exports both series; nothing, and no error, from
one that lacks the second (the parent of the PR that added it)."""

import json
import os
import types

from chipbench import layers, prom

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "kda_chunk_fill_pct"

# what a hybrid stack's /metrics carries of the span scan, at two scrapes
HEAD = """\
# HELP vllm:kda_chunk_tokens_total Rows of the ragged dispatches that the recurrent layers' span scan carried
# TYPE vllm:kda_chunk_tokens_total counter
vllm:kda_chunk_tokens_total{model_name="m"} %(tokens)s
# HELP vllm:kda_chunk_spans_total Spans of the ragged dispatches that the span scan carried
# TYPE vllm:kda_chunk_spans_total counter
vllm:kda_chunk_spans_total{model_name="m"} %(spans)s
"""
BLOCKS = """\
# HELP vllm:kda_chunk_block_rows_total Rows of the blocks the span scan ran for those spans
# TYPE vllm:kda_chunk_block_rows_total counter
vllm:kda_chunk_block_rows_total{model_name="m"} %(block_rows)s
"""


def _ctx(open_text, close_text):
    return types.SimpleNamespace(
        prom_open=prom.parse(open_text), prom_close=prom.parse(close_text),
        manifest={})


def _scrape(tokens, spans, block_rows):
    return (HEAD + BLOCKS) % {"tokens": float(tokens), "spans": float(spans),
                              "block_rows": float(block_rows)}


def test_reads_the_live_share_of_the_blocks_rows():
    # a window's spans: 1403 + 597 rows in blocks of 64 -> 1408 + 640
    c = _ctx(_scrape(100, 2, 128), _scrape(2100, 4, 2176))
    assert layers.read(NAME, c) == 100.0 * 2000 / 2048


def test_reads_100_where_every_span_is_whole_blocks():
    c = _ctx(_scrape(0, 0, 0), _scrape(2048, 2, 2048))
    assert layers.read(NAME, c) == 100.0


def test_reads_nothing_from_a_program_without_the_block_counter():
    old = HEAD % {"tokens": 5.0, "spans": 1.0}
    later = HEAD % {"tokens": 905.0, "spans": 3.0}
    assert layers.read(NAME, _ctx(old, later)) is None


def test_file_matches_its_benchmark_entry():
    spec = layers.load_spec(NAME)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    (entry,) = [m for m in bm["per_layer"] if m["name"] == NAME]
    assert (spec["layer"], spec["unit"], spec["source"], spec["reader"]) == (
        entry["layer"], entry["unit"], entry["source"], "prom_ratio")
    assert (entry["moves"], entry["better"]) == ("ttft_p50_ms", "higher")
    # the cells whose programs hold the kernel, and no other
    assert entry["workloads"] == [
        "solar-open2-250b-ep16-l8.decode-heavy",
        "solar-open2-250b-ep16-l8.prefill-heavy"]
    # appended: behind every metric the benchmark had before it
    names = [m["name"] for m in bm["per_layer"]]
    assert names.index(NAME) > names.index("mla_attn_roofline_pct")
    assert not os.path.exists(os.path.join(layers.DIR, NAME + ".py"))
