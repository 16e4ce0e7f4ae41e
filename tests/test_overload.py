"""Overload protection plane units (docs/resilience.md "Overload &
fairness"): the hysteretic brownout ladder, the over-weight shed set,
the router's token-bucket quotas, the scheduler's weighted-fair
admission dequeue + deficit-round-robin prefill split, the derived
Retry-After, and the observe-only bit-identity pins the acceptance
gate requires (fairness off — and fairness on with a single tenant —
schedules exactly like the pre-existing FCFS path)."""

import pytest

from production_stack_tpu.engine.config import CacheConfig, SchedulerConfig
from production_stack_tpu.engine.metrics import OverloadCollector
from production_stack_tpu.engine.overload import (
    MAX_STAGE,
    BrownoutConfig,
    BrownoutController,
    PressureSignals,
    SHED_MAX_TOKENS,
    SHED_SPEC,
    overweight_tenants,
)
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Scheduler
from production_stack_tpu.engine.sequence import Sequence
from production_stack_tpu.router.quota import (
    QuotaManager,
    TokenBucket,
    estimate_tokens,
)

HOT = PressureSignals(queue_fraction=0.9)
CALM = PressureSignals()


def make_ctl(**kw):
    kw.setdefault("enabled", True)
    return BrownoutController(BrownoutConfig(**kw))


# ---- brownout ladder -------------------------------------------------------

def test_disabled_controller_never_leaves_stage_zero():
    ctl = BrownoutController(BrownoutConfig(enabled=False))
    for t in range(10):
        assert ctl.evaluate(HOT, float(t)) == 0
    actions = ctl.snapshot()["actions"]
    assert not any(actions.values())


def test_ladder_climbs_one_stage_per_sustained_hot_run():
    ctl = make_ctl(up_evals=2, calm_evals=3)
    assert ctl.evaluate(HOT, 0.0) == 0   # one hot eval is not sustained
    assert ctl.evaluate(HOT, 1.0) == 1
    assert ctl.evaluate(HOT, 2.0) == 1   # each stage needs a fresh streak
    assert ctl.evaluate(HOT, 3.0) == 2
    assert ctl.evaluate(HOT, 4.0) == 2
    assert ctl.evaluate(HOT, 5.0) == 3
    for t in range(6, 16):               # capped at MAX_STAGE
        assert ctl.evaluate(HOT, float(t)) == MAX_STAGE
    assert ctl.transitions == 3


def test_single_noisy_sample_neither_browns_out_nor_recovers():
    ctl = make_ctl(up_evals=2, calm_evals=2)
    ctl.evaluate(HOT, 0.0)
    ctl.evaluate(CALM, 1.0)              # hot streak broken
    assert ctl.stage == 0
    ctl.evaluate(HOT, 2.0)
    ctl.evaluate(HOT, 3.0)
    assert ctl.stage == 1
    ctl.evaluate(CALM, 4.0)
    ctl.evaluate(HOT, 5.0)               # calm streak broken
    assert ctl.stage == 1


def test_recovery_unwinds_one_stage_per_calm_run():
    ctl = make_ctl(up_evals=1, calm_evals=2)
    for t in range(3):
        ctl.evaluate(HOT, float(t))
    assert ctl.stage == 3
    stages = [ctl.evaluate(CALM, 10.0 + t) for t in range(6)]
    assert stages == [3, 2, 2, 1, 1, 0]


def test_stage_action_table_matches_docs():
    ctl = make_ctl(up_evals=1, max_tokens_clamp=128)
    assert (ctl.shed_spec, ctl.max_tokens_clamp,
            ctl.pause_prefetch, ctl.shed_overweight) == (False, 0, False,
                                                         False)
    ctl.evaluate(HOT, 0.0)               # stage 1: spec grants only
    assert ctl.shed_spec
    assert ctl.max_tokens_clamp == 0 and not ctl.pause_prefetch
    ctl.evaluate(HOT, 1.0)               # stage 2: clamp + prefetch pause
    assert ctl.max_tokens_clamp == 128 and ctl.pause_prefetch
    assert not ctl.shed_overweight
    ctl.evaluate(HOT, 2.0)               # stage 3: tenant shed
    assert ctl.shed_overweight


def test_hot_reasons_vocabulary_is_closed():
    ctl = make_ctl()
    every = PressureSignals(queue_fraction=1.0, hbm_fraction=0.99,
                            watchdog_stalled=True, burn_page=True)
    assert ctl.hot_reasons(every) == [
        "queue_depth", "hbm_pressure", "watchdog_stall", "burn_page"]
    assert ctl.hot_reasons(CALM) == []
    # below-threshold pressure is calm, not hot
    assert ctl.hot_reasons(PressureSignals(queue_fraction=0.49,
                                           hbm_fraction=0.5)) == []


def test_record_shed_accumulates_into_snapshot():
    ctl = make_ctl()
    ctl.record_shed(SHED_SPEC)
    ctl.record_shed(SHED_SPEC, 4)
    ctl.record_shed(SHED_MAX_TOKENS, 2)
    assert ctl.snapshot()["sheds"] == {SHED_SPEC: 5, SHED_MAX_TOKENS: 2}


# ---- over-weight shed set --------------------------------------------------

def test_overweight_lone_tenant_never_shed():
    assert overweight_tenants({"only": 1000.0}) == []


def test_overweight_flags_the_dominator_only():
    assert overweight_tenants({"noisy": 90.0, "a": 5.0, "b": 5.0}) == \
        ["noisy"]


def test_overweight_equal_shares_shed_nobody():
    assert overweight_tenants({"a": 5.0, "b": 5.0, "c": 5.0}) == []


def test_overweight_respects_configured_weights():
    loads = {"big": 80.0, "small": 20.0}
    # equal weights: 80% > 1.5 x 50% -> shed
    assert overweight_tenants(loads) == ["big"]
    # big paid for a 3x weight: 80% < 1.5 x 75% -> within its share
    assert overweight_tenants(loads, {"big": 3.0, "small": 1.0}) == []


# ---- token buckets + quota manager -----------------------------------------

def test_token_bucket_starts_full_then_meters():
    b = TokenBucket(rate=1.0, burst=2.0, now=0.0)
    assert b.try_take(1, now=0.0) == 0.0
    assert b.try_take(1, now=0.0) == 0.0
    assert b.try_take(1, now=0.0) == pytest.approx(1.0)  # 1-token deficit
    assert b.try_take(1, now=1.5) == 0.0                 # refilled


def test_token_bucket_retry_is_the_actual_refill_time():
    b = TokenBucket(rate=2.0, burst=4.0, now=0.0)
    assert b.try_take(4, 0.0) == 0.0
    assert b.try_take(3, 0.0) == pytest.approx(1.5)      # 3 tokens / 2 per s
    # a one-shot request larger than the bucket: capped at full-fill time
    assert b.try_take(100, 0.0) == pytest.approx(2.0)


def test_token_bucket_zero_rate_never_refills():
    b = TokenBucket(rate=0.0, burst=1.0, now=0.0)
    b.tokens = 0.0
    assert b.try_take(1, now=10.0) == float("inf")


def test_quota_from_json_default_off():
    assert QuotaManager.from_json(None) is None
    assert QuotaManager.from_json("") is None
    assert QuotaManager.from_json("  ") is None
    assert QuotaManager.from_json("{}") is None
    assert QuotaManager.from_json('{"default": {"rps": 1}}') is not None


def test_quota_unlimited_default_admits_everything():
    qm = QuotaManager({}, now=0.0)
    assert all(qm.check("t", 100_000, now=0.0).allowed for _ in range(50))
    assert qm.rejection_counts() == {}


def test_quota_rps_limit_rejects_with_derived_retry_after():
    qm = QuotaManager(
        {"tenants": {"noisy": {"rps": 1, "burst_s": 1.0}}}, now=0.0)
    assert qm.check("noisy", 0, now=0.0).allowed
    v = qm.check("noisy", 0, now=0.0)
    assert not v.allowed and v.reason == "rps"
    assert v.retry_after == pytest.approx(1.0)  # 1-token deficit at 1 rps
    # after exactly that refill the tenant admits again...
    assert qm.check("noisy", 0, now=1.0).allowed
    # ...and everyone else rides the unlimited default throughout
    assert qm.check("calm", 0, now=0.0).allowed


def test_quota_tps_reject_refunds_the_rps_charge():
    qm = QuotaManager(
        {"tenants": {"t": {"rps": 10, "tps": 100, "burst_s": 1.0}}},
        now=0.0)
    assert qm.check("t", 100, now=0.0).allowed   # drains the tps bucket
    v = qm.check("t", 100, now=0.0)
    assert not v.allowed and v.reason == "tps"
    # rejected work consumed nothing: only the admitted request's rps
    # charge stands
    rps_bucket = qm._buckets["t"][0]
    assert rps_bucket.tokens == pytest.approx(9.0)


def test_quota_identity_bound_folds_spun_tenants_into_other():
    qm = QuotaManager({"default": {"rps": 1, "burst_s": 1.0}}, now=0.0)
    for i in range(qm.cap):
        assert qm.check(f"t{i}", 0, now=0.0).allowed
    # past the cap, novel tenant ids share ONE overflow bucket pair
    assert qm.check("spun-1", 0, now=0.0).allowed
    v = qm.check("spun-2", 0, now=0.0)
    assert not v.allowed                   # spun-1 drained the shared bucket
    assert "spun-1" not in qm._buckets and "spun-2" not in qm._buckets
    assert "other" in qm._buckets
    assert len(qm._buckets) <= qm.cap + 1


def test_quota_rejection_counts_fold_to_top_k():
    qm = QuotaManager({"default": {"rps": 1, "burst_s": 1.0}}, top_k=2,
                      now=0.0)
    for i in range(8):
        qm.check(f"t{i}", 0, now=0.0)
        qm.check(f"t{i}", 0, now=0.0)      # second request -> 429
    counts = qm.rejection_counts()
    assert len(counts) <= 3                # top-2 + "other"
    assert sum(counts.values()) == 8.0


def test_quota_weights_surface_for_fair_share():
    qm = QuotaManager({"tenants": {"a": {"weight": 4}, "b": {}}}, now=0.0)
    assert qm.weights() == {"a": 4.0, "b": 1.0}


def test_estimate_tokens_prompt_messages_and_default():
    assert estimate_tokens({"prompt": "x" * 400, "max_tokens": 10}) == 110
    assert estimate_tokens(
        {"messages": [{"role": "user", "content": "y" * 40}]}) == 10 + 16
    assert estimate_tokens({}) == 16       # the OpenAI-API default budget


# ---- scheduler: fair dequeue + DRR prefill + derived Retry-After -----------

def make_sched(budget=16, max_seqs=8, fair=False, weights=None):
    sched = Scheduler(
        SchedulerConfig(
            max_num_seqs=max_seqs, max_num_batched_tokens=budget,
            fair_share=fair, tenant_weights=weights or {},
        ),
        CacheConfig(block_size=4, num_blocks=512),
        num_blocks=512, max_model_len=1024,
    )
    sched.unified = True
    return sched


def make_seq(rid, n, t=0.0, tenant="anonymous", max_tokens=8):
    return Sequence(request_id=rid, prompt_token_ids=list(range(1, n + 1)),
                    sampling=SamplingParams(max_tokens=max_tokens,
                                            ignore_eos=True),
                    arrival_time=t, tenant=tenant)


def chunks(out):
    return [(sp.seq.request_id, sp.chunk_len) for sp in out.prefills]


def advance(out):
    for sp in out.prefills:
        sp.seq.num_computed_tokens += sp.chunk_len


def test_fair_prefill_splits_budget_by_weight():
    sched = make_sched(budget=16, fair=True,
                       weights={"a": 3.0, "b": 1.0})
    sched.add(make_seq("a1", 64, t=1.0, tenant="a"))
    sched.add(make_seq("b1", 64, t=2.0, tenant="b"))
    assert dict(chunks(sched.schedule())) == {"a1": 12, "b1": 4}


def test_fair_prefill_deficit_carry_converges_to_weights():
    """Fractional quanta carry across dispatches: over 4 dispatches of a
    10-token budget at weights 1:3 the split is exactly 10:30."""
    sched = make_sched(budget=10, fair=True,
                       weights={"a": 1.0, "b": 3.0})
    sched.add(make_seq("a1", 500, t=1.0, tenant="a"))
    sched.add(make_seq("b1", 500, t=2.0, tenant="b"))
    total = {"a1": 0, "b1": 0}
    for _ in range(4):
        out = sched.schedule()
        for rid, n in chunks(out):
            total[rid] += n
        advance(out)
    assert total == {"a1": 10, "b1": 30}


def test_fair_prefill_redistributes_unused_share():
    """A light tenant's unusable quantum goes to tenants still pending —
    fairness re-orders who prefills, it never strands budget."""
    sched = make_sched(budget=16, fair=True)
    sched.add(make_seq("a1", 100, t=1.0, tenant="a"))
    sched.add(make_seq("b1", 2, t=2.0, tenant="b"))
    assert dict(chunks(sched.schedule())) == {"a1": 14, "b1": 2}


def test_fair_prefill_idle_tenant_banks_no_credit():
    sched = make_sched(budget=16, fair=True)
    sched._deficits["ghost"] = 12.0        # tenant with no pending work
    sched.add(make_seq("a1", 50, t=1.0, tenant="a"))
    sched.add(make_seq("b1", 50, t=2.0, tenant="b"))
    sched.schedule()
    assert "ghost" not in sched._deficits


def test_fair_prefill_deficit_capped_at_one_budget():
    sched = make_sched(budget=16, fair=True)
    sched._deficits["a"] = 1e9             # absurd carried credit
    sched.add(make_seq("a1", 500, t=1.0, tenant="a"))
    sched.add(make_seq("b1", 500, t=2.0, tenant="b"))
    sched.schedule()
    assert all(d <= 16.0 for d in sched._deficits.values())


def test_fair_dequeue_flooder_queues_behind_victims():
    """Six queued requests from one tenant vs one from another, two
    decode slots: stride admission interleaves instead of letting the
    flood hold both slots, and stays FCFS within each tenant."""
    sched = make_sched(budget=8, max_seqs=2, fair=True)
    for i in range(6):
        sched.add(make_seq(f"n{i}", 4, t=float(i), tenant="noisy"))
    sched.add(make_seq("v1", 4, t=10.0, tenant="victim"))
    sched.schedule()
    assert set(sched.seqs) == {"n0", "v1"}


def test_fair_dequeue_off_is_pure_fifo():
    sched = make_sched(budget=8, max_seqs=2, fair=False)
    for i in range(3):
        sched.add(make_seq(f"n{i}", 4, t=float(i), tenant="noisy"))
    sched.add(make_seq("v1", 4, t=10.0, tenant="victim"))
    sched.schedule()
    assert set(sched.seqs) == {"n0", "n1"}


def _trace(fair, seqs, steps=6):
    sched = make_sched(budget=16, fair=fair)
    for s in seqs:
        sched.add(s)
    trace = []
    for _ in range(steps):
        out = sched.schedule()
        trace.append((chunks(out),
                      [d.request_id for d in out.decodes]))
        advance(out)
    return trace


def test_single_tenant_fairness_on_is_bit_identical():
    """The observe-only pin: with one tenant, the fairness-on scheduler
    falls through to the exact FCFS loop — every dispatch identical."""
    mk = lambda: [make_seq("a", 30, t=1.0), make_seq("b", 5, t=2.0),
                  make_seq("c", 11, t=3.0)]
    assert _trace(True, mk()) == _trace(False, mk())


def test_multi_tenant_fairness_off_is_bit_identical_fifo():
    """Fairness off is the untouched pre-existing path even with many
    tenants riding the sequences (tenant tags are observe-only)."""
    mk_tagged = lambda: [make_seq("a", 30, t=1.0, tenant="x"),
                         make_seq("b", 5, t=2.0, tenant="y"),
                         make_seq("c", 11, t=3.0, tenant="z")]
    mk_plain = lambda: [make_seq("a", 30, t=1.0), make_seq("b", 5, t=2.0),
                        make_seq("c", 11, t=3.0)]
    assert _trace(False, mk_tagged()) == _trace(False, mk_plain())


def test_fairness_never_costs_throughput():
    """Same total tokens scheduled per dispatch with fairness on and
    off — the DRR pass only re-orders who gets the budget."""
    mk = lambda: [make_seq("a1", 200, t=1.0, tenant="a"),
                  make_seq("a2", 200, t=2.0, tenant="a"),
                  make_seq("b1", 200, t=3.0, tenant="b")]
    on = _trace(True, mk(), steps=8)
    off = _trace(False, mk(), steps=8)
    for (on_chunks, _), (off_chunks, _) in zip(on, off):
        assert (sum(n for _, n in on_chunks)
                == sum(n for _, n in off_chunks))


def test_retry_after_hint_floor_without_history():
    sched = make_sched()
    assert sched.retry_after_hint(floor=2.5) == 2.5


def test_retry_after_hint_derives_from_depth_over_drain_rate():
    sched = make_sched()
    sched._admit_stamps.extend([0.0, 1.0, 2.0, 3.0])  # 1 admission/sec
    for i in range(20):
        sched.waiting.append(make_seq(f"w{i}", 4))
    hint = sched.retry_after_hint(floor=1.0, ceiling=60.0, now=4.0)
    assert hint == pytest.approx(20.0)     # 20 waiting / (4 per 4s)
    # the ceiling bounds what a huge backlog can tell clients
    assert sched.retry_after_hint(floor=1.0, ceiling=10.0, now=4.0) == 10.0
    # drained queue: the floor still applies
    sched.waiting.clear()
    assert sched.retry_after_hint(floor=1.0, ceiling=60.0, now=4.0) == 1.0


def test_spec_shed_zeroes_grants_and_counts():
    sched = make_sched(budget=16)
    sched.spec_grant_fn = lambda s: 4
    s = make_seq("d", 4, t=1.0)
    sched.add(s)
    out = sched.schedule()
    for _ in range(3):                     # prefill -> running -> decode
        if out.decodes:
            break
        advance(out)
        out = sched.schedule()
    assert out.decodes and s.spec_grant == 4
    sched.spec_shed = True
    before = sched.spec_shed_count
    out = sched.schedule()
    assert out.decodes and s.spec_grant == 0
    assert sched.spec_shed_count == before + len(out.decodes)


def test_fair_share_snapshot_shape():
    sched = make_sched(fair=True)
    snap = sched.fair_share_snapshot()
    assert snap == {"enabled": True, "deficits": {}, "admit_pass": {}}


# ---- metric export ---------------------------------------------------------

def test_overload_collector_exports_all_three_families():
    snap = {
        "brownout": {"stage": 2, "sheds": {"spec": 5, "max_tokens": 3}},
        "fair_share": {"deficits": {"acme": 12.5}},
    }
    fams = {f.name: f for f in
            OverloadCollector(lambda: snap, "m").collect()}
    assert set(fams) == {"vllm:brownout_stage", "vllm:brownout_sheds",
                         "vllm:fair_share_deficit"}
    stage = fams["vllm:brownout_stage"].samples[0]
    assert stage.value == 2.0
    assert stage.labels == {"model_name": "m", "tier": "engine"}
    shed_values = {s.labels["reason"]: s.value
                   for s in fams["vllm:brownout_sheds"].samples}
    assert shed_values == {"spec": 5.0, "max_tokens": 3.0}
    deficit = fams["vllm:fair_share_deficit"].samples[0]
    assert deficit.labels["tenant"] == "acme" and deficit.value == 12.5


# ---- router admission check ------------------------------------------------

def make_service(**kw):
    from production_stack_tpu.router.request_service import RequestService
    return RequestService(**kw)


def test_router_admission_check_admits_without_quota_or_brownout():
    svc = make_service()
    assert svc._admission_check("anyone", {"prompt": "hi"}, {}) is None


def test_router_quota_429_carries_derived_retry_after():
    qm = QuotaManager({"tenants": {"noisy": {"rps": 1, "burst_s": 1.0}}})
    svc = make_service(quota=qm)
    assert svc._admission_check("noisy", {}, {}) is None
    rec = {}
    resp = svc._admission_check("noisy", {}, rec)
    assert resp is not None and resp.status == 429
    assert rec["outcome"] == "over_quota"
    assert float(resp.headers["Retry-After"]) > 0
    # in-budget tenants are untouched by the noisy tenant's 429s
    assert svc._admission_check("calm", {}, {}) is None


def test_router_stage3_brownout_sheds_overweight_tenant():
    ctl = make_ctl(up_evals=1)
    for t in range(3):
        ctl.evaluate(HOT, float(t))
    assert ctl.stage == 3
    svc = make_service(brownout=ctl)
    svc.brownout_shed = {"noisy"}
    rec = {}
    resp = svc._admission_check("noisy", {}, rec)
    assert resp is not None and resp.status == 429
    assert rec["outcome"] == "brownout_shed"
    assert ctl.sheds.get("tenant") == 1
    # tenants inside their fair share keep flowing at stage 3
    assert svc._admission_check("victim", {}, {}) is None


def test_router_below_stage3_never_sheds_tenants():
    ctl = make_ctl(up_evals=1)
    ctl.evaluate(HOT, 0.0)
    ctl.evaluate(HOT, 1.0)
    assert ctl.stage == 2
    svc = make_service(brownout=ctl)
    svc.brownout_shed = {"noisy"}          # stale set: stage gate wins
    assert svc._admission_check("noisy", {}, {}) is None
