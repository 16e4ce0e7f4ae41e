"""Multi-chip ragged serving on the CPU host-device mesh (tier-1).

The tentpole contract (docs/roofline.md "Multi-chip"): the ragged
unified dispatch runs sharded across the named mesh as the default
multi-chip path — TP-sharded weights AND TP-sharded paged KV (pages
partitioned over the KV-head axis; the packed token stream, verify
spans and sampling state replicated) — and greedy decoding stays
bit-identical to the single-chip engine, for mixed prefill+decode AND
speculative-verify traffic. Plus:

- the KV pool's NamedSharding really partitions the KV-head axis when
  the geometry divides, and falls back to replication when it doesn't
  (tiny-llama's KH=2 at tensor=4);
- zero ``vllm:unexpected_recompiles_total`` after warmup at TP=4 — the
  sharded signature set is warmed exactly like the unsharded one;
- the ICI roofline arithmetic in PerfAccountant: per-chip collective
  bytes derived from the sharding spec + model geometry, the per-axis
  roofline breakdown in the /debug/perf snapshot, and ``from_runner``'s
  chips/tensor-parallel derivation.

Runs on the XLA-forced 8-device CPU host platform (tests/conftest.py),
same lever the CI tier uses — no TPU required.
"""

import dataclasses

import jax
import numpy as np
import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.perf_accounting import (
    DEVICE_PEAKS,
    PerfAccountant,
)
from production_stack_tpu.engine.sampling import SamplingParams

# tiny-llama's KH=2 cannot shard at tensor=4; this geometry keeps the
# same budget-friendly size but makes every head axis divisible, so the
# paged KV pool genuinely partitions instead of silently replicating
SHARDABLE = dataclasses.replace(
    ModelConfig.from_pretrained("tiny-llama"),
    num_heads=8, num_kv_heads=8, head_dim=16,
)

GREEDY = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)


def _cfg(tp, **sched):
    kw = dict(max_num_seqs=8, max_num_batched_tokens=32)
    kw.update(sched)
    from production_stack_tpu.parallel.mesh import MeshConfig

    return EngineConfig(
        model=SHARDABLE,
        cache=CacheConfig(block_size=4, num_blocks=256),
        scheduler=SchedulerConfig(**kw),
        mesh=MeshConfig(data=1, tensor=tp),
        )


def _engine(tp, **sched):
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.parallel.mesh import build_mesh

    cfg = _cfg(tp, **sched)
    mesh = build_mesh(cfg.mesh, devices=jax.devices()[:tp])
    return LLMEngine(cfg, mesh=mesh, num_blocks=cfg.cache.num_blocks)


def _drain(eng, reqs, stagger_at=()):
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


# the mixed-traffic shape both engines replay: chunked long prefill,
# short prefills, staggered arrivals — prefill chunks and decode rows
# share dispatches throughout
MIXED = [
    ("r0", [1, 5, 9, 13, 2, 6], GREEDY),
    ("r1", list(range(1, 70)), GREEDY),
    ("r2", [3, 7, 11], GREEDY),
    ("r3", [2, 4], GREEDY),
]


@pytest.fixture(scope="module")
def tp1_tokens():
    eng = _engine(1)
    return _drain(eng, MIXED, stagger_at=(2, 4, 6))


def test_sharded_greedy_identity_mixed_traffic(tp1_tokens):
    """TP=4 over the CPU mesh, same staggered mixed traffic, greedy
    outputs bit-identical to the single-device engine."""
    eng = _engine(4)
    assert eng.mesh.devices.size == 4
    t4 = _drain(eng, MIXED, stagger_at=(2, 4, 6))
    assert t4 == tp1_tokens


def test_sharded_spec_verify_identity():
    """Speculative n-gram verify spans ride the sharded ragged dispatch:
    greedy outputs at TP=4 match TP=1 with speculation ON both sides
    (and the proposer actually fired — accepted tokens > 0)."""
    motif = [7, 11, 13, 17, 19, 23]
    reqs = [
        ("m0", motif * 6, GREEDY),
        ("m1", [2, 4] + motif * 4, GREEDY),
    ]
    outs = {}
    stats = {}
    for tp in (1, 4):
        eng = _engine(tp, spec_ngram_k=3)
        outs[tp] = _drain(eng, reqs)
        stats[tp] = eng.stats()
    assert outs[4] == outs[1]
    assert stats[4].get("spec_decode_num_accepted_tokens_total", 0) > 0


def test_kv_pool_shards_over_kv_heads():
    """The paged KV pool's NamedSharding partitions the fused 2*KH axis
    over the tensor mesh axis — each device holds 1/tp of the KV heads,
    not a replica of the whole pool."""
    from production_stack_tpu.parallel.mesh import AXIS_TENSOR

    eng = _engine(4)
    kv = eng.runner.kv
    spec = kv.sharding.spec
    assert spec[3] == AXIS_TENSOR
    full = kv.shape
    assert full[3] == 2 * SHARDABLE.num_kv_heads
    for shard in kv.addressable_shards:
        assert shard.data.shape[3] == full[3] // 4


def test_indivisible_kv_heads_fall_back_to_replication():
    """tiny-llama (KH=2) on a tensor=4 mesh: the KV-head rule resolves
    to None (replication), never a crash or a wrong partition."""
    from production_stack_tpu.parallel import shardings as ln
    from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
    from production_stack_tpu.parallel.shardings import rules_for_model

    mesh = build_mesh(MeshConfig(data=1, tensor=4))
    tiny = ModelConfig.from_pretrained("tiny-llama")
    rules = rules_for_model(tiny, mesh)
    assert rules.rules.get(ln.KV_HEADS) is None
    # heads=4 still divides, so the sharded matmuls (and their
    # collectives) remain: the accountant's tp derivation keys on HEADS
    assert rules.rules.get(ln.HEADS) is not None


def test_zero_unexpected_recompiles_after_warmup_tp4():
    """Warmup covers the sharded signature set: live mixed traffic on
    the TP=4 mesh after warmup() hits only pre-compiled programs —
    vllm:unexpected_recompiles_total stays 0 (the regression the
    tentpole must hold at TP=4/8 just as at TP=1)."""
    eng = _engine(4, max_num_seqs=4, max_num_batched_tokens=16)
    assert eng.perf is not None
    eng.warmup()
    assert eng.perf.stats_fields()["unexpected_recompiles"] == 0
    reqs = [
        ("g", list(range(1, 40)), GREEDY),
        ("s", [4, 8, 12],
         SamplingParams(temperature=0.7, max_tokens=8, ignore_eos=True)),
        ("g2", [3, 5], GREEDY),
    ]
    _drain(eng, reqs, stagger_at=(2, 3))
    assert eng.perf.stats_fields()["unexpected_recompiles"] == 0


def test_zero_unexpected_recompiles_at_both_stream_widths_tp4():
    """A budget with a narrow stream width (128 of 512; PR 42): warmup
    compiles the sharded signatures at both, so a narrow step and then a
    wide one, greedy, sampled and with logprobs, hit compiled programs,
    and the replicated packed buffer takes either length."""
    eng = _engine(4, max_num_seqs=4, max_num_batched_tokens=512)
    assert eng.config.scheduler.ragged_stream_widths == (128, 512)
    eng.warmup()
    fields = eng.perf.stats_fields()
    assert fields["unexpected_recompiles"] == 0
    # a program's name is ``w<width>:<variant>``: six variants a width
    widths = [bucket.split(":")[0] for (kind, bucket), n
              in fields["compile_counts"].items() if kind == "ragged"]
    assert sorted(widths) == ["w128"] * 6 + ["w512"] * 6
    rng = np.random.default_rng(5)
    narrow = eng.ragged_narrow_dispatches
    for i, n in enumerate((20, 200)):
        before = eng.ragged_dispatches
        _drain(eng, [
            (f"g{i}", rng.integers(1, 200, n).tolist(), GREEDY),
            (f"s{i}", rng.integers(1, 200, n).tolist(),
             SamplingParams(temperature=0.7, max_tokens=4, ignore_eos=True,
                            logprobs=3)),
            (f"l{i}", rng.integers(1, 200, n).tolist(),
             dataclasses.replace(GREEDY, logprobs=3)),
        ], stagger_at=(3, 6))
        assert eng.ragged_dispatches > before
    # the 20-token prompts ran narrow, the 200-token ones did not
    assert eng.ragged_narrow_dispatches - narrow == 3
    assert eng.perf.stats_fields()["unexpected_recompiles"] == 0


# ---- ICI roofline accounting (unit) ---------------------------------------

V5E_TFLOPS, V5E_HBM_GBPS, V5E_PEAK_ICI_GBPS = DEVICE_PEAKS["TPU v5 lite"]


def _accountant(tp, n_chips=None):
    cfg = dataclasses.replace(SHARDABLE, dtype="bfloat16")
    return PerfAccountant(cfg, param_count=1000, param_bytes=2000,
                          window=60.0, n_chips=n_chips or tp,
                          tensor_parallel=tp, peak_tflops=V5E_TFLOPS,
                          peak_hbm_gbps=V5E_HBM_GBPS,
                          peak_ici_gbps=V5E_PEAK_ICI_GBPS)


def test_collective_bytes_formulas():
    """Ring all-reduce moves 2(tp-1)/tp of the payload per chip; the
    vocab-sharded logits all-gather moves (tp-1)/tp of the fp32 row."""
    acc = _accountant(4)
    m = SHARDABLE
    ar_fac = 2.0 * 3 / 4
    # two row-parallel matmuls per layer (attn out-proj + MLP down-proj)
    assert acc._ar_bytes_per_tok == pytest.approx(
        2 * m.num_layers * m.hidden_size * 2 * ar_fac)
    assert acc._ag_bytes_per_row == pytest.approx(m.vocab_size * 4 * 3 / 4)
    # tp=1: nothing crosses the wire, whatever the chip count
    acc1 = _accountant(1, n_chips=4)
    assert acc1._ar_bytes_per_tok == 0.0
    assert acc1._ag_bytes_per_row == 0.0


def test_ici_window_rates_and_collective_totals():
    acc = _accountant(4)
    # two fused decode dispatches, 8 seqs x 1 step = 8 tokens each
    acc.record_decode(8, 1, 64, ts=100.0)
    acc.record_decode(8, 1, 64, ts=130.0)
    rates = acc._window_rates(now=130.0)
    # span = now - oldest event; BOTH dispatches' bytes land in it
    expect = 2 * 8 * (acc._ar_bytes_per_tok + acc._ag_bytes_per_row)
    assert rates["ici_bw_util"] == pytest.approx(
        expect / (30.0 * V5E_PEAK_ICI_GBPS * 1e9))
    coll = acc.stats_fields()["collective_bytes"]
    assert coll["all_reduce"] == pytest.approx(2 * 8 * acc._ar_bytes_per_tok)
    assert coll["all_gather"] == pytest.approx(2 * 8 * acc._ag_bytes_per_row)


def test_snapshot_rooflines_per_axis():
    """/debug/perf carries the per-axis breakdown: FLOP/HBM ceilings
    aggregate over the mesh (global costs), ICI stays per chip."""
    acc = _accountant(4)
    acc.record_decode(4, 1, 32, ts=100.0)
    snap = acc.snapshot()
    assert snap["chips"] == 4 and snap["tensor_parallel"] == 4
    roofs = snap["rooflines"]
    assert set(roofs) == {"flop", "hbm", "ici"}
    for axis in roofs.values():
        assert {"peak_per_s", "achieved_per_s", "utilization"} <= set(axis)
    assert roofs["ici"]["peak_per_s"] == V5E_PEAK_ICI_GBPS * 1e9
    # FLOP peak scaled by chips: 4x the single-chip accountant's
    assert snap["peaks"]["flops"] == 4 * _accountant(1, n_chips=1).peak_flops
    assert set(snap["collective_bytes_total"]) == {"all_gather",
                                                   "all_reduce"}
    assert "ici_bandwidth_utilization" in snap


def test_from_runner_derives_chips_and_tp():
    """The accountant wired into a TP=4 engine reads chips from the
    mesh and the collective degree from the resolved sharding rules."""
    eng = _engine(4)
    assert eng.perf is not None
    assert eng.perf.n_chips == 4
    assert eng.perf.tp == 4
    snap = eng.perf.snapshot()
    assert snap["chips"] == 4 and snap["tensor_parallel"] == 4
