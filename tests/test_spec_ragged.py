"""Speculative decoding v2 — verification fused into the ragged dispatch.

What PR-level property each block pins down:

- bit-identity: with drafts riding the packed stream as verify spans,
  greedy output is STILL token-for-token the spec-free engine's output,
  including under staggered mixed traffic (chunked prefill + decode +
  verify spans in one dispatch);
- per-sequence eligibility: a sampled row in the batch no longer turns
  speculation off batch-wide — greedy rows keep speculating in the SAME
  dispatch (the old engine fell back to plain decode for those steps);
- KV rollback: rejected drafts leave garbage KV above ``num_computed``
  which must never be committed — a warm engine re-serving extended
  prompts (prefix-cache content addressing) must match a cold engine,
  fuzzed over random traffic with planted repetition;
- compile stability: the verify-bearing dispatch is the SAME steady-state
  signature warmup already compiled (``verify_idx`` rides every dispatch
  when spec is on), so live speculation causes zero unexpected
  recompiles — the PR 6 gate, now with spec enabled;
- scheduler reservation: draft grants append KV blocks so spans are not
  silently truncated at a block boundary, and clamp exactly to the
  table's capacity when the pool runs dry.
"""

import dataclasses

import numpy as np
import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Scheduler
from production_stack_tpu.engine.sequence import Sequence, SequenceStatus
from production_stack_tpu.engine.weights import init_or_load
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh


@pytest.fixture(scope="module")
def setup():
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=256),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=32,
            ),
        mesh=MeshConfig(data=1, tensor=1),
        )
    mesh = build_mesh(cfg.mesh)
    params = init_or_load(cfg.model, mesh, seed=0)
    return cfg, mesh, params


def make_engine(setup, spec_k=0, **sched_overrides):
    cfg, mesh, params = setup
    sched = dataclasses.replace(cfg.scheduler, spec_ngram_k=spec_k,
                                **sched_overrides)
    cfg = dataclasses.replace(cfg, scheduler=sched)
    return LLMEngine(cfg, mesh=mesh, params=params,
                     num_blocks=cfg.cache.num_blocks)


def _drain(eng, reqs, stagger_at=()):
    """Submit requests (optionally staggered mid-flight); collect tokens."""
    toks = {rid: [] for rid, _, _ in reqs}
    queue = list(reqs)
    if not stagger_at:
        for r, pr, s in queue:
            eng.add_request(r, prompt_token_ids=pr, sampling=s)
        queue = []
    else:
        r, pr, s = queue.pop(0)
        eng.add_request(r, prompt_token_ids=pr, sampling=s)
    n = 0
    while True:
        outs = eng.step()
        n += 1
        if queue and n in stagger_at:
            r, pr, s = queue.pop(0)
            eng.add_request(r, prompt_token_ids=pr, sampling=s)
        for o in outs:
            toks[o.request_id].extend(o.new_token_ids)
        if not eng.has_unfinished() and not queue:
            break
    return toks


GREEDY = SamplingParams(temperature=0.0, max_tokens=16, ignore_eos=True)

# repetitive (drafts accept), structured (partial accepts), irregular
REPS = [7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8]
SEMI = [1, 2, 3, 4, 1, 2, 5, 6, 1, 2]
WILD = [11, 23, 5, 301, 42, 17]


# ---- bit-identity under mixed + staggered traffic --------------------------


def test_spec_ragged_bit_identity_staggered(setup):
    """Staggered arrivals force dispatches that mix chunked prefill,
    plain decode rows, and verify spans — every greedy token must still
    be the spec-free engine's."""
    reqs = [
        ("long", list(range(1, 50)), GREEDY),  # > budget: chunked prefill
        ("rep", list(REPS), GREEDY),
        ("wild", list(WILD), GREEDY),
        ("semi", list(SEMI), GREEDY),
    ]
    ref = _drain(make_engine(setup, spec_k=0), list(reqs),
                 stagger_at=(2, 3, 4))
    spec = make_engine(setup, spec_k=4)
    out = _drain(spec, list(reqs), stagger_at=(2, 3, 4))
    assert out == ref
    for rid in out:
        assert len(out[rid]) == GREEDY.max_tokens
    assert spec.spec_drafted > 0
    assert spec.spec_accepted > 0


# ---- per-sequence eligibility ----------------------------------------------


def test_mixed_batch_greedy_rows_still_speculate(setup):
    """A sampled row in the batch must NOT silence speculation for the
    greedy rows sharing the dispatch (the pre-fusion engine fell back to
    plain decode whenever any row was ineligible)."""
    glong = SamplingParams(temperature=0.0, max_tokens=16, ignore_eos=True)
    # build a prompt whose FIRST decode token already has an earlier
    # occurrence in the history, so the proposer provably matches on the
    # very first (full-EWMA) grant — no reliance on probe timing
    probe = make_engine(setup, spec_k=0)
    cont = probe.generate([SEMI], SamplingParams(
        temperature=0.0, max_tokens=24, ignore_eos=True))["offline-0"]
    i = next(j for j in range(1, len(cont))
             if cont[j] in SEMI + cont[:j])
    gp = SEMI + cont[:i]
    spec = make_engine(setup, spec_k=4)
    sampled = SamplingParams(temperature=0.8, max_tokens=16, seed=7,
                             ignore_eos=True)
    # same max_tokens: the sampled row is present for EVERY decode step,
    # so any drafting at all happened in a mixed batch
    reqs = [("g", list(gp), glong), ("s", list(WILD), sampled)]
    out = _drain(spec, reqs)
    assert len(out["g"]) == 16 and len(out["s"]) == 16
    assert spec.spec_drafted > 0, (
        "greedy row never speculated while sharing the batch with a "
        "sampled row — eligibility regressed to batch-wide"
    )
    ref = _drain(make_engine(setup, spec_k=0), [("g", list(gp), glong)])
    assert out["g"] == ref["g"]


def test_ineligible_rows_never_granted(setup):
    """Rows with sampling/penalties/logprobs decode normally: an all-
    ineligible batch proposes nothing."""
    spec = make_engine(setup, spec_k=4)
    reqs = [
        ("s1", list(REPS),
         SamplingParams(temperature=0.8, max_tokens=8, seed=1,
                        ignore_eos=True)),
        ("p1", list(REPS),
         SamplingParams(temperature=0.0, max_tokens=8,
                        presence_penalty=0.5, ignore_eos=True)),
        ("l1", list(REPS),
         SamplingParams(temperature=0.0, max_tokens=8, logprobs=2,
                        ignore_eos=True)),
    ]
    _drain(spec, reqs)
    assert spec.spec_drafted == 0


# ---- KV rollback fuzz ------------------------------------------------------


@pytest.mark.parametrize("seed", range(4))
def test_spec_rollback_fuzz_warm_matches_cold(setup, seed):
    """Multi-round fuzz: rounds extend earlier context (prefix-cache
    content addressing over blocks that carried rejected-draft garbage
    above ``num_computed``), so any KV slot committed past the accepted
    prefix shows up as a warm-vs-cold divergence."""
    rng = np.random.default_rng(1000 + seed)
    spec = make_engine(setup, spec_k=4)
    base = make_engine(setup, spec_k=0)
    # planted repetition: motif loops make drafts fire, random splices
    # make some of them WRONG (rejections → rollback actually exercised)
    motif = rng.integers(1, 64, 3).tolist()
    prompt = (motif * 3 + rng.integers(1, 64, 2).tolist())[: 11]
    for rnd in range(3):
        n_out = int(rng.integers(4, 10))
        sp = SamplingParams(temperature=0.0, max_tokens=n_out,
                            ignore_eos=True)
        out_spec = spec.generate([prompt], sp)["offline-0"]
        out_base = base.generate([prompt], sp)["offline-0"]
        assert out_spec == out_base, f"round {rnd} diverged"
        splice = rng.integers(1, 64, int(rng.integers(1, 3))).tolist()
        prompt = prompt + out_spec + splice + motif
    assert spec.spec_drafted > 0
    # the fuzz is only meaningful if rejections happened somewhere
    assert spec.spec_accepted < spec.spec_drafted


def test_rejected_drafts_not_committed_at_finish(setup):
    """A sequence finishing right after a heavy-rejection step: the warm
    engine must re-serve the extension from the model, not from garbage
    KV committed past the accepted prefix."""
    spec = make_engine(setup, spec_k=4)
    sp = SamplingParams(temperature=0.0, max_tokens=5, ignore_eos=True)
    p = list(REPS)
    out1 = spec.generate([p], sp)["offline-0"]
    ext = p + out1 + [2, 7, 2, 7]
    sp2 = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)
    warm = spec.generate([ext], sp2)["offline-0"]
    cold = make_engine(setup, spec_k=0).generate([ext], sp2)["offline-0"]
    assert warm == cold


# ---- compile stability -----------------------------------------------------


def test_spec_no_recompiles_after_warmup(setup):
    """The verify-bearing dispatch is the same steady-state signature as
    plain ragged decode (``verify_idx`` rides every dispatch when spec is
    on, drafts or not): live speculation after warmup compiles nothing."""
    eng = make_engine(setup, spec_k=4)
    assert eng.perf is not None
    eng.warmup()
    assert eng.perf.stats_fields()["unexpected_recompiles"] == 0
    glong = SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True)
    reqs = [
        ("rep", list(SEMI), glong),
        ("s", list(WILD),
         SamplingParams(temperature=0.7, max_tokens=8, ignore_eos=True)),
        ("g2", list(REPS), glong),
    ]
    _drain(eng, reqs, stagger_at=(2, 3))
    fields = eng.perf.stats_fields()
    assert fields["unexpected_recompiles"] == 0, fields["compile_counts"]
    # speculation genuinely ran at steady state
    assert eng.spec_drafted > 0
    s = eng.stats()
    assert s["spec_decode_acceptance_rate"] >= 0.0
    assert s["spec_decode_tokens_per_step"] >= 1.0


# ---- scheduler draft reservation -------------------------------------------


def _sched(num_blocks, spec_k=4, budget=16, max_seqs=2):
    sched = Scheduler(
        SchedulerConfig(max_num_seqs=max_seqs,
                        max_num_batched_tokens=budget,
                        spec_ngram_k=spec_k),
        CacheConfig(block_size=4, num_blocks=num_blocks),
        num_blocks=num_blocks, max_model_len=64,
    )
    sched.unified = True
    sched.spec_grant_fn = lambda seq: spec_k
    return sched


def _running_seq(sched, n_prompt):
    seq = Sequence(request_id="r", prompt_token_ids=list(range(1, n_prompt + 1)),
                   sampling=SamplingParams(max_tokens=8, ignore_eos=True),
                   arrival_time=1.0)
    sched.add(seq)
    out = sched.schedule()
    assert out.prefills and out.prefills[0].seq is seq
    seq.num_computed_tokens = n_prompt
    seq.status = SequenceStatus.RUNNING
    return seq


def test_grant_appends_blocks_past_boundary():
    """pos at an exact block boundary (8 = 2 full blocks of 4): a grant
    of 4 needs capacity through position 12, i.e. TWO more blocks — the
    old batch-wide path would have clamped the drafts to what the table
    already held."""
    sched = _sched(num_blocks=128)
    seq = _running_seq(sched, 8)
    assert len(seq.block_ids) == 2
    out = sched.schedule()
    assert out.decodes == [seq]
    assert seq.spec_grant == 4
    # span occupies positions 8..12 → 13 slots → 4 blocks
    assert len(seq.block_ids) * 4 >= seq.num_computed_tokens + 1 + 4


def test_grant_clamps_exactly_when_pool_dry():
    """Pool of 3 blocks: prefill takes 2, the decode horizon takes the
    3rd, and the grant finds nothing left to append — it must clamp to
    exactly the table's remaining slots (12 - 8 - 1 = 3), never preempt,
    and never hand out capacity the KV write would silently drop."""
    sched = _sched(num_blocks=3)
    seq = _running_seq(sched, 8)
    out = sched.schedule()
    assert out.decodes == [seq] and not out.preempted
    assert len(seq.block_ids) == 3
    assert seq.spec_grant == 3  # min(4, 3*4 - 8 - 1)


def test_grant_charges_budget_fcfs():
    """Grants are FCFS and budget-bounded: with budget 6 and two decode
    rows both asking for 4, the older row gets 4 and the younger the
    remaining 2 — after each row's guaranteed stream token is charged."""
    sched = _sched(num_blocks=128, budget=8)
    a = Sequence(request_id="a", prompt_token_ids=[1, 2, 3, 4],
                 sampling=SamplingParams(max_tokens=8, ignore_eos=True),
                 arrival_time=1.0)
    b = Sequence(request_id="b", prompt_token_ids=[5, 6, 7, 8],
                 sampling=SamplingParams(max_tokens=8, ignore_eos=True),
                 arrival_time=2.0)
    for s in (a, b):
        sched.add(s)
    out = sched.schedule()
    for sp in out.prefills:
        sp.seq.num_computed_tokens = sp.chunk_len
        sp.seq.status = SequenceStatus.RUNNING
    out = sched.schedule()
    assert sorted(s.request_id for s in out.decodes) == ["a", "b"]
    # budget 8 - 2 decode tokens = 6 for drafts
    assert a.spec_grant == 4 and b.spec_grant == 2
