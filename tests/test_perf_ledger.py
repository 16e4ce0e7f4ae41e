"""Performance regression sentinel: durable perf ledger, roofline
cost-model drift detection, and the perfdiff / CI-gate tooling.

Four layers, mirroring the subsystem (docs/observability.md "Perf ledger
& cost-model drift"):

* ``PerfLedger`` / record schema unit contracts — rotation, IO-error
  counting, corrupt-line-tolerant round-trip, fingerprint cohorts,
  last-known-good semantics, engine-stats flattening.
* Drift-detector units on a directly-driven ``PerfAccountant`` —
  baseline freeze after steady state, in-band quiet, exactly one
  anomaly per out-of-band episode, band<=1 = detection off.
* ``tools/perfdiff.py`` and ``scripts/perf_ci_gate.py`` rc/threshold
  semantics on synthetic ledger segments.
* CPU e2e drill through the real ``EngineServer`` over aiohttp: a fault
  knob inflating measured dispatch time trips the drift gauge, captures
  a ``costmodel_drift`` diagnostics bundle, journals a second ledger
  segment, and perfdiff exits 2 between the segments — while greedy
  outputs stay bit-identical to a drift-plane-off server with zero
  unexpected recompiles (observe-only by construction).
"""

import asyncio
import importlib.util
import json
import os
import time
from pathlib import Path

import pytest

from production_stack_tpu import perf_ledger as pl
from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.engine.perf_accounting import (
    DEVICE_PEAKS,
    PerfAccountant,
)

# the cost model predicts against explicit peaks (the CPU these tests run
# on has no entry in DEVICE_PEAKS): use the v5e's
V5E_TFLOPS, V5E_HBM_GBPS, V5E_ICI_GBPS = DEVICE_PEAKS["TPU v5 lite"]

REPO = Path(__file__).resolve().parent.parent


def tiny_cfg() -> ModelConfig:
    return ModelConfig(
        vocab_size=64, hidden_size=8, intermediate_size=16, num_layers=2,
        num_heads=2, num_kv_heads=1, head_dim=4, dtype="bfloat16",
    )


def make_accountant(**kw) -> PerfAccountant:
    kw.setdefault("param_count", 1000)
    kw.setdefault("param_bytes", 2000)
    kw.setdefault("window", 60.0)
    kw.setdefault("peak_tflops", V5E_TFLOPS)
    kw.setdefault("peak_hbm_gbps", V5E_HBM_GBPS)
    kw.setdefault("peak_ici_gbps", V5E_ICI_GBPS)
    return PerfAccountant(tiny_cfg(), **kw)


def fp(**kw) -> dict:
    base = dict(model="tiny-llama", attention_impl="ragged",
                dtype="bfloat16", platform="cpu")
    base.update(kw)
    return pl.fingerprint(**base)


def engine_marks(**kw) -> dict:
    marks = {"prompt_tokens_total": 500, "generation_tokens_total": 200,
             "ragged_dispatches_total": 40, "ragged_live_tokens_total": 700,
             "ragged_stream_utilization": 0.6, "unexpected_recompiles": 0,
             "mfu": 0.4, "decode_tps": 1000.0, "prefill_tps": 5000.0,
             "costmodel_drift_ratio": {"prefill": 1.0, "decode": 1.0},
             "costmodel_episodes": 0}
    marks.update(kw)
    return marks


def load_ci_gate():
    spec = importlib.util.spec_from_file_location(
        "perf_ci_gate", str(REPO / "scripts" / "perf_ci_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# PerfLedger + record schema unit contracts
# ---------------------------------------------------------------------------

def test_perf_ledger_appends_rotates_and_roundtrips(tmp_path):
    path = tmp_path / "perf.jsonl"
    ledger = pl.PerfLedger(str(path), max_bytes=1, backups=2)
    assert ledger.max_bytes == 4096  # UsageLedger floor, not zero
    for i in range(50):
        assert ledger.append_engine_snapshot(
            1000.0 + i, fp(), engine_marks(), reason="interval")
    assert ledger.append_bench(2000.0, fp(), {"status": "ok", "value": 1234.0})
    assert ledger.records_written == 51
    assert ledger.rotations >= 1
    assert path.exists() and (tmp_path / "perf.jsonl.1").exists()
    assert not (tmp_path / "perf.jsonl.3").exists()

    records, skipped = pl.read_records(str(path), backups=2)
    assert skipped == 0
    # rotation loses the oldest generation, never the newest records
    assert 10 < len(records) <= 51
    assert records[-1]["kind"] == pl.BENCH_KIND
    assert records[-1]["marks"]["value_tok_s_chip"] == 1234.0
    assert all(r["schema"] == pl.SCHEMA for r in records)
    # ts stays monotonic across the backup-then-live read order
    ts = [r["ts"] for r in records]
    assert ts == sorted(ts)


def test_perf_ledger_io_errors_counted_not_raised(tmp_path):
    ledger = pl.PerfLedger(str(tmp_path / "no-such-dir" / "perf.jsonl"))
    assert ledger.append_engine_snapshot(1.0, fp(), engine_marks()) is False
    assert ledger.write_errors == 1
    stats = ledger.stats()
    assert stats["records_written"] == 0 and stats["write_errors"] == 1


def test_read_records_skips_damage_not_raises(tmp_path):
    path = tmp_path / "perf.jsonl"
    good = pl.engine_snapshot_record(1.0, fp(), engine_marks())
    path.write_text(
        json.dumps(good) + "\n"
        + '{"truncated": \n'        # crash mid-append
        + '[1, 2, 3]\n'             # JSON but not an object
        + '{"no": "kind"}\n'        # object but not a ledger record
        + json.dumps(good) + "\n")
    records, skipped = pl.read_records(str(path))
    assert len(records) == 2 and skipped == 3
    # a missing file is empty history, not an error
    records, skipped = pl.read_records(str(tmp_path / "absent.jsonl"))
    assert records == [] and skipped == 0


def test_fingerprint_cohorts_split_on_perf_envelope_fields():
    a, b = fp(), fp()
    assert pl.fingerprint_id(a) == pl.fingerprint_id(b)
    assert pl.fingerprint_id(fp(quantization="int8")) != pl.fingerprint_id(a)
    assert pl.fingerprint_id(fp(tensor_parallel=4)) != pl.fingerprint_id(a)
    assert pl.fingerprint_id(fp(speculative=True)) != pl.fingerprint_id(a)

    # group_by_cohort recomputes the id when a record lacks it
    rec = pl.engine_snapshot_record(1.0, a, engine_marks())
    del rec["fingerprint_id"]
    cohorts = pl.group_by_cohort([rec])
    assert list(cohorts) == [pl.fingerprint_id(a)]


def test_last_known_good_skips_failures_dates_staleness():
    good_fp = fp()
    fpid = pl.fingerprint_id(good_fp)
    records = [
        pl.engine_snapshot_record(100.0, good_fp, engine_marks()),
        pl.bench_record(200.0, good_fp, {"status": "ok", "value": 1234.0}),
        pl.bench_record(300.0, good_fp, {
            "status": "infra_failure",
            "failure_class": "backend-init-timeout"}),
    ]
    best = pl.last_known_good(records, fpid)
    assert best["kind"] == pl.BENCH_KIND and best["ts"] == 200.0
    # a cohort that only ever failed has no baseline at all
    assert pl.last_known_good(records[2:], fpid) is None
    assert pl.last_known_good(records, "feedfeedfeed") is None


def test_bench_record_schemas():
    ok = pl.bench_record(1.0, fp(), {
        "status": "ok", "value": 1234.0,
        "scenarios": {"decode_heavy": {"tok_s_chip": 4000.0, "mfu": 0.41,
                                       "p50_ms": 12.0, "p99_ms": 40.0}}})
    assert ok["status"] == "ok"
    assert ok["marks"]["value_tok_s_chip"] == 1234.0
    assert ok["marks"]["decode_heavy.tok_s_chip"] == 4000.0
    assert ok["marks"]["decode_heavy.p99_ms"] == 40.0

    failed = pl.bench_record(2.0, fp(), {
        "status": "infra_failure", "failure_class": "compile-error"})
    assert failed["status"] == "infra_failure"
    assert failed["failure_class"] == "compile-error"
    assert failed["marks"] == {}  # failures never contribute marks


def test_marks_from_engine_stats_flattens_both_families():
    stats = {
        "prompt_tokens_total": 11, "generation_tokens_total": 22,
        "ragged_dispatches_total": 3, "ragged_live_tokens_total": 33,
        "ragged_stream_utilization": 0.5,
        "perf": {"mfu": 0.1, "decode_tps": 10.0, "chips": 1,
                 "unexpected_recompiles": 0, "dispatches_total": 3,
                 "costmodel": {"drift_ratio": {"decode": 2.0},
                               "predicted_seconds": {"decode": 1.0},
                               "measured_seconds": {"decode": 2.0},
                               "episodes": 1}},
    }
    marks = pl.marks_from_engine_stats(stats)
    assert marks["prompt_tokens_total"] == 11
    assert marks["mfu"] == 0.1 and marks["dispatches_total"] == 3
    assert marks["costmodel_drift_ratio"] == {"decode": 2.0}
    assert marks["costmodel_episodes"] == 1
    # perf accounting off: the invariant marks still journal
    marks = pl.marks_from_engine_stats(
        {"prompt_tokens_total": 1, "perf": None})
    assert marks == {"prompt_tokens_total": 1}


# ---------------------------------------------------------------------------
# Drift-detector units (directly-driven accountant)
# ---------------------------------------------------------------------------

def drive_decode(acct, n, *, t0, seconds=0.01, step=0.2):
    t = t0
    for _ in range(n):
        acct.record_decode(8, 1, 800, ts=t, seconds=seconds)
        t += step
    return t


def test_drift_baseline_freezes_and_inband_stays_quiet():
    acct = make_accountant()
    acct.costmodel_drift_band = 4.0
    acct.costmodel_min_events = 4
    fired = []
    acct.anomaly_hook = lambda name, d: fired.append(name)
    t0 = time.time()
    # before steady state: counters accumulate, no baseline, no alerts
    t = drive_decode(acct, 6, t0=t0)
    cm = acct.stats_fields()["costmodel"]
    assert cm["predicted_seconds"]["decode"] > 0
    assert cm["measured_seconds"]["decode"] > 0
    assert cm["baseline"] == {} and not fired

    acct.mark_steady()
    t = drive_decode(acct, 6, t0=t)
    cm = acct.stats_fields()["costmodel"]
    assert cm["baseline"]["decode"] > 0
    assert cm["out_of_band"] == [] and cm["episodes"] == 0
    assert not fired
    # the windowed ratio is measured/predicted for the phase
    assert cm["drift_ratio"]["decode"] == pytest.approx(
        cm["measured_seconds"]["decode"] / cm["predicted_seconds"]["decode"],
        rel=0.2)


def test_drift_fires_exactly_once_per_episode():
    acct = make_accountant(window=30.0)
    acct.costmodel_drift_band = 4.0
    acct.costmodel_min_events = 4
    fired = []
    acct.anomaly_hook = lambda name, d: fired.append((name, d))
    acct.mark_steady()
    t = drive_decode(acct, 8, t0=time.time())

    acct.measured_time_scale = 50.0
    t = drive_decode(acct, 40, t0=t)
    names = [n for n, _ in fired]
    assert names == ["costmodel_drift"], "one anomaly per episode, not per window"
    detail = fired[0][1]
    assert detail["phase"] == "decode" and detail["relative"] > 4.0
    cm = acct.stats_fields()["costmodel"]
    assert cm["episodes"] == 1 and cm["out_of_band"] == ["decode"]

    # back in band: the episode closes silently (no recovery anomaly)...
    acct.measured_time_scale = 1.0
    t = drive_decode(acct, 400, t0=t)
    cm = acct.stats_fields()["costmodel"]
    assert cm["out_of_band"] == [] and len(fired) == 1
    # ...and a second excursion is a NEW episode with its own anomaly
    acct.measured_time_scale = 50.0
    drive_decode(acct, 40, t0=t)
    assert [n for n, _ in fired] == ["costmodel_drift", "costmodel_drift"]
    assert acct.stats_fields()["costmodel"]["episodes"] == 2


def test_drift_band_zero_means_detection_off_gauges_still_export():
    acct = make_accountant()  # default band 0.0
    fired = []
    acct.anomaly_hook = lambda name, d: fired.append(name)
    acct.mark_steady()
    t = drive_decode(acct, 8, t0=time.time())
    acct.measured_time_scale = 1000.0
    drive_decode(acct, 20, t0=t)
    cm = acct.stats_fields()["costmodel"]
    assert not fired and cm["episodes"] == 0 and cm["baseline"] == {}
    assert cm["band"] == 0.0
    assert cm["measured_seconds"]["decode"] > 0  # gauges export regardless


def test_ragged_split_conserves_measured_seconds():
    """A fused ragged dispatch splits its one wall time across the two
    phase events by predicted share — the measured total is conserved."""
    acct = make_accountant()
    acct.record_ragged(32, 64, 2, 4, 400, ts=time.time(), seconds=0.5)
    cm = acct.stats_fields()["costmodel"]
    total = (cm["measured_seconds"]["prefill"]
             + cm["measured_seconds"]["decode"])
    assert total == pytest.approx(0.5)
    assert cm["measured_seconds"]["prefill"] > 0
    assert cm["measured_seconds"]["decode"] > 0


# ---------------------------------------------------------------------------
# perfdiff rc / threshold semantics
# ---------------------------------------------------------------------------

def write_segment(path, marks, n=3, t0=100.0, fingerprint=None):
    ledger = pl.PerfLedger(str(path))
    for i in range(n):
        ledger.append_engine_snapshot(t0 + i, fingerprint or fp(),
                                      engine_marks(**marks))
    return str(path)


def test_perfdiff_detects_regression_and_thresholds(tmp_path, capsys):
    import tools.perfdiff as perfdiff

    base = write_segment(tmp_path / "base.jsonl", {})
    same = write_segment(tmp_path / "same.jsonl", {})
    slow = write_segment(tmp_path / "slow.jsonl", {"decode_tps": 400.0})

    assert perfdiff.main([base, same]) == 0
    assert perfdiff.main([base, slow]) == 2
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "decode_tps" in out
    # a generous threshold override waves the same delta through
    assert perfdiff.main([base, slow, "--threshold",
                          "decode_tps=0.9"]) == 0
    # disjoint cohorts cannot be compared: usage error, not a pass
    other = write_segment(tmp_path / "other.jsonl", {},
                          fingerprint=fp(quantization="int8"))
    assert perfdiff.main([base, other]) == 1
    with pytest.raises(SystemExit):
        perfdiff.parse_thresholds(["not_a_metric=0.5"])


def test_perfdiff_drift_marks_and_promotion(tmp_path, capsys):
    import tools.perfdiff as perfdiff

    base = write_segment(tmp_path / "base.jsonl", {})
    drifted = write_segment(tmp_path / "drift.jsonl", {
        "costmodel_drift_ratio": {"prefill": 1.0, "decode": 60.0},
        "costmodel_episodes": 2})
    promoted = tmp_path / "promoted.jsonl"
    # episodes appearing from zero is a regression even with ratio slack
    assert perfdiff.main([base, drifted, "--promote",
                          str(promoted)]) == 2
    assert not promoted.exists()  # promotion only on success
    same = write_segment(tmp_path / "same.jsonl", {})
    assert perfdiff.main([base, same, "--promote", str(promoted),
                          "--json"]) == 0
    assert promoted.exists()
    assert promoted.read_text() == Path(same).read_text()


def test_perfdiff_accepts_single_json_bench_artifact(tmp_path):
    import tools.perfdiff as perfdiff

    artifact = {"status": "ok", "value": 1234.0, "ts": 50.0,
                "fingerprint": fp()}
    a = tmp_path / "a.json"
    a.write_text(json.dumps(artifact))
    b = tmp_path / "b.json"
    b.write_text(json.dumps(dict(artifact, value=1000.0)))
    assert perfdiff.main([str(a), str(a)]) == 0
    assert perfdiff.main([str(a), str(b)]) == 2


# ---------------------------------------------------------------------------
# perf_ci_gate: CPU-stable invariants
# ---------------------------------------------------------------------------

def test_ci_gate_pins_recompiles_util_band_and_identity(tmp_path):
    gate = load_ci_gate()
    clean = write_segment(tmp_path / "clean.jsonl", {})
    assert gate.main([clean]) == 0

    recompiled = write_segment(tmp_path / "recompiled.jsonl",
                               {"unexpected_recompiles": 2})
    assert gate.main([recompiled]) == 2

    sparse = write_segment(tmp_path / "sparse.jsonl",
                           {"ragged_stream_utilization": 0.001})
    assert gate.main([sparse]) == 2
    assert gate.main([sparse, "--util-band", "0.0001,1.0"]) == 0

    # two segments, identical scheduled-token counts: identity holds
    again = write_segment(tmp_path / "again.jsonl", {})
    assert gate.main([clean, again]) == 0
    # a drifted dispatch count between builds is a behavior change
    drifted = write_segment(tmp_path / "drifted.jsonl",
                            {"ragged_dispatches_total": 41})
    assert gate.main([clean, drifted]) == 2
    with pytest.raises(SystemExit):
        gate.main([str(tmp_path / "empty-nothing.jsonl")])


# ---------------------------------------------------------------------------
# stacktop --history rendering
# ---------------------------------------------------------------------------

def test_stacktop_history_renders_trajectory_with_staleness():
    from tools.stacktop import render_history

    good_fp = fp()
    records = [
        pl.engine_snapshot_record(time.time() - 7200, good_fp,
                                  engine_marks(chips=1)),
        pl.bench_record(time.time() - 3600, good_fp,
                        {"status": "ok", "value": 1234.0}),
        pl.bench_record(time.time(), good_fp, {
            "status": "infra_failure",
            "failure_class": "backend-init-timeout"}),
    ]
    text = render_history(records, skipped=2)
    assert pl.fingerprint_id(good_fp) in text
    assert "1234" in text
    assert "backend-init-ti" in text  # NOTE column truncates at 16 chars
    assert "last known good" in text
    assert "2 corrupt line(s) skipped" in text
    assert "no ledger records" in render_history([])


# ---------------------------------------------------------------------------
# CPU e2e drill: the whole loop through a real EngineServer
# ---------------------------------------------------------------------------

GREEDY = {"model": "tiny-llama", "prompt": "hello world", "max_tokens": 6,
          "temperature": 0, "ignore_eos": True}


def make_server(tmp_path, **cfg_kw):
    from production_stack_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        PerfConfig,
        SchedulerConfig,
    )
    from production_stack_tpu.engine.diagnostics import DiagnosticsConfig
    from production_stack_tpu.engine.server import EngineServer
    from production_stack_tpu.parallel.mesh import MeshConfig

    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=512),
        scheduler=SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=64,
                                  prefill_buckets=(32, 64)),
        mesh=MeshConfig(data=1, tensor=1),
        perf=PerfConfig(peak_tflops=V5E_TFLOPS, peak_hbm_gbps=V5E_HBM_GBPS,
                        peak_ici_gbps=V5E_ICI_GBPS),
        **cfg_kw,
    )
    return EngineServer(cfg, diagnostics=DiagnosticsConfig(
        dir=str(tmp_path / "diag"), cooldown=0.0, profile_seconds=0.0))


async def greedy_text(client) -> str:
    r = await client.post("/v1/completions", json=GREEDY)
    assert r.status == 200
    return (await r.json())["choices"][0]["text"]


def test_costmodel_drift_e2e_drill(tmp_path):
    """The acceptance drill: inflate measured dispatch time via the fault
    knob on a live server -> the drift gauge leaves the band, a
    costmodel_drift bundle lands on disk, the ledger gains a drifted
    segment, and perfdiff exits 2 between the segments. Greedy output
    stays bit-identical to a drift-plane-off server throughout, with
    zero unexpected recompiles."""
    from aiohttp.test_utils import TestClient, TestServer

    seg1 = tmp_path / "seg1.jsonl"
    seg2 = tmp_path / "seg2.jsonl"

    async def plain_run():
        es = make_server(tmp_path / "plain")
        client = TestClient(TestServer(es.build_app()))
        await client.start_server()
        try:
            return await greedy_text(client)
        finally:
            await client.close()

    async def drill():
        es = make_server(
            tmp_path / "drill",
            perf_ledger_path=str(seg1),
            perf_ledger_interval=3600.0,  # journal explicitly, not by timer
        )
        es.engine.perf.costmodel_drift_band = 4.0
        client = TestClient(TestServer(es.build_app()))
        await client.start_server()
        try:
            perf = es.engine.perf
            perf.costmodel_min_events = 2
            await greedy_text(client)       # warm every serving shape
            perf.mark_steady()

            texts = {await greedy_text(client) for _ in range(3)}
            cm = perf.stats_fields()["costmodel"]
            assert cm["baseline"], "steady traffic froze no baseline"
            assert cm["out_of_band"] == [] and cm["episodes"] == 0
            es._journal_perf("baseline")
            assert es.perf_ledger.records_written == 1

            # second ledger segment + the fault knob: measured dispatch
            # time inflates x50, predictions (and outputs) unchanged
            es.perf_ledger = pl.PerfLedger(str(seg2))
            perf.measured_time_scale = 50.0
            for _ in range(20):
                texts.add(await greedy_text(client))
                if perf.stats_fields()["costmodel"]["out_of_band"]:
                    break
            cm = perf.stats_fields()["costmodel"]
            assert cm["out_of_band"], "x50 inflation never left the band"
            assert cm["episodes"] >= 1

            # the anomaly captured a diagnostics bundle
            for _ in range(100):
                r = await client.get("/debug/diagnostics")
                idx = await r.json()
                rows = [b for b in idx["bundles"]
                        if b["trigger"] == "costmodel_drift"]
                if rows:
                    break
                await asyncio.sleep(0.05)
            assert rows, "no costmodel_drift bundle captured"
            assert rows[0]["detail"]["relative"] > 4.0

            # drift plane surfaces: /debug/perf block + /metrics families
            r = await client.get("/debug/perf")
            snap = await r.json()
            assert snap["costmodel"]["out_of_band"]
            assert snap["perf_ledger"]["enabled"] is True
            r = await client.get("/metrics")
            exposition = await r.text()
            assert "vllm:costmodel_drift_ratio" in exposition
            assert "vllm:costmodel_drift_episodes_total" in exposition
            assert "vllm:costmodel_predicted_seconds_total" in exposition

            es._journal_perf("drifted")

            # observe-only: one greedy text across plain/baseline/drifted
            # servers and zero unexpected recompiles after warmup
            stats = es.engine.stats()
            assert stats["perf"]["unexpected_recompiles"] == 0
            return texts
        finally:
            await client.close()

    plain = asyncio.run(plain_run())
    texts = asyncio.run(drill())
    assert texts == {plain}, "drift plane perturbed greedy decoding"

    # the two journaled segments disagree exactly the way perfdiff pins
    import tools.perfdiff as perfdiff
    assert perfdiff.main([str(seg1), str(seg2)]) == 2
    gate = load_ci_gate()
    assert gate.main([str(seg1), "--util-band", "0.0,1.0"]) == 0

    records, skipped = pl.read_records(str(seg2), include_backups=False)
    assert skipped == 0 and records[-1]["reason"] == "drifted"
    assert records[-1]["marks"]["costmodel_episodes"] >= 1
    assert records[-1]["fingerprint"]["model"] == "tiny-llama"
