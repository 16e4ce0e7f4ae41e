"""BENCHMARK.json against the contract's own rules, and against the files
the harness finds by name."""

import json
import os
import re

import pytest

from chipbench import layers, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bm():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cells_of(metric, bm):
    return metric.get("workloads") or [w["name"] for w in bm["workloads"]]


def test_keys_names_units(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= bm["run_seconds"] <= 51
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in bm[sec]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bm["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert "setup_s" in [m["name"] for m in bm["end_to_end"]]
    for w in bm["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    four = sum(w["chips"] == 4 for w in bm["workloads"])
    assert four <= max(1, len(bm["workloads"]) // 4)


def test_every_cell_reports_setup_another_metric_and_a_layer(bm):
    for w in bm["workloads"]:
        e2e = [m["name"] for m in bm["end_to_end"]
               if w["name"] in cells_of(m, bm)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(w["name"] in cells_of(m, bm) for m in bm["per_layer"])


def test_a_layer_metric_moves_a_metric_its_cells_report(bm):
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        assert set(cells_of(m, bm)) <= set(cells_of(e2e[m["moves"]], bm)), m


def test_files_found_by_name(bm):
    for c in bm["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            hf = json.load(f)
        d = os.path.dirname(os.path.join(ROOT, c["file"]))
        with open(os.path.join(d, "manifest.json")) as f:
            man = json.load(f)
        assert sorted(man["reduced"]) == sorted(c["reduced"])
        assert man["source"] == c["source"]
        for k, v in man["reduced"].items():
            assert hf[k] == v["run"] != v["published"]
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "reference", man["reference"] + ".py"))
    for w in bm["workloads"]:
        assert traffic.load_mix(w["traffic"])["loop"] in ("open", "closed")
    for m in bm["per_layer"]:
        spec = layers.load_spec(m["name"])
        assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
        assert spec["source"] == m["source"]
        own = os.path.join(layers.DIR, m["name"] + ".py")
        assert os.path.exists(own) or spec["reader"] in layers.READERS


def test_every_file_under_traffic_and_layer_metrics_belongs_to_a_cell(bm):
    """Only proved cells leave files here (the tests' toys aside)."""
    with open(os.path.join(ROOT, "chipbench", "tests",
                           "BENCHMARK.tiny.json")) as f:
        tiny = json.load(f)
    mixes = {w["traffic"] for b in (bm, tiny) for w in b["workloads"]}
    metrics = {m["name"] for m in bm["per_layer"]}
    configs = {c["name"] for b in (bm, tiny) for c in b["configs"]}
    d = os.path.join(ROOT, "chipbench")
    assert {f[:-5] for f in os.listdir(os.path.join(d, "traffic"))} == mixes
    assert {f.rsplit(".", 1)[0] for f in os.listdir(
        os.path.join(d, "layer_metrics")) if not f.startswith("__")} == metrics
    assert set(os.listdir(os.path.join(d, "configs"))) == configs
