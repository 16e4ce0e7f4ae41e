"""`start_tokenizer_s` (PR 56), the twelfth metric of the layer `engine
start`: its files against BENCHMARK.json, its reader on a hand-made scrape
of the window's open, and nothing (None) for a program that does not export
its family, as the commit before it does not. A file of its own beside
test_start_metrics.py, whose count of eleven is the accepted benchmark's
to correct (a PR that claims a gain edits no file the benchmark has)."""

import json
import os

import pytest

from chipbench import layers
from chipbench.tests.test_start_metrics import OPEN, ROOT, START, ctx

NAME = "start_tokenizer_s"
FAMILY = START + "tokenizer_seconds"


def test_the_entry_is_the_last_and_lists_every_accepted_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    entry = bm["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "s", "better": "lower",
        "source": "program_span", "layer": "engine start",
        "moves": "setup_s",
        "workloads": [w["name"] for w in bm["workloads"]]}
    spec = layers.load_spec(NAME)
    assert (spec["layer"], spec["unit"], spec["source"], spec["num"]) == (
        entry["layer"], entry["unit"], entry["source"], [FAMILY])
    assert os.path.exists(os.path.join(layers.DIR, NAME + ".py"))


def test_reader_reads_the_family_at_the_windows_open():
    # the close of the window is not read: a delta would be 0
    assert layers.read(NAME, ctx({**OPEN, FAMILY: 0.25}, {})) \
        == pytest.approx(0.25)


def test_reader_finds_nothing_in_a_program_without_the_family():
    assert layers.read(NAME, ctx(OPEN)) is None
