"""Solar-Open2's hybrid stack (gated NoPE GQA layers among gated delta-rule
KDA layers, a sparse block with sigmoid routing, a shared expert and a
share of the routed experts) through the shared stack and the serving
engine, against the plain reference the benchmark uses on the chip
(chipbench/reference/solar_open2.py), on seeded random weights at test
size (chipbench/tests/configs/tiny-solar-open2: two periods, hidden 128,
4 of 16 routed experts held).
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import solar_open2 as reference
from production_stack_tpu.engine.config import (
    MODEL_PRESETS,
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.kv_cache import (
    PrefixCachingBlockAllocator,
    init_kv_cache,
    kv_cache_bytes_per_block,
)
from production_stack_tpu.engine.metrics import EngineStatsCollector
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Scheduler
from production_stack_tpu.engine.weights import init_random
from production_stack_tpu.models import llama
from production_stack_tpu.ops import kda, kda_pallas
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "chipbench", "tests", "configs",
                       "tiny-solar-open2", "config.json")) as f:
    HF = json.load(f)
# float32 on the CPU on both sides; the served path differs from the
# reference in the order of its sums only: log-probabilities agree to
# ~1e-5. Computing the state or the router in bfloat16 reads over it.
LOGPROB_TOL = 3e-4
# chipbench/run.py's limits, which every cell's `correct` is held to
CELL_TOL, CELL_MEAN_TOL = 0.15, 0.03
BUDGET = 32  # tokens a ragged step: the 50-token prompt takes two chunks


def tiny_cfg(**over) -> ModelConfig:
    return dataclasses.replace(ModelConfig.from_hf_config(HF, "tiny-solar"),
                               dtype="float32", **over)


def one_device():
    return build_mesh(MeshConfig(), devices=jax.devices()[:1])


def engine(cfg=None, params=None, num_blocks=64, slots=4) -> LLMEngine:
    return LLMEngine(
        EngineConfig(
            model=cfg or tiny_cfg(),
            cache=CacheConfig(block_size=16, num_blocks=num_blocks),
            scheduler=SchedulerConfig(max_num_seqs=slots,
                                      max_num_batched_tokens=BUDGET),
            mesh=MeshConfig(data=1, tensor=1)),
        mesh=one_device(), params=params)


def serve(eng, prompts, max_tokens=6):
    """{request: (tokens, [logprob of each token])} through the engine."""
    for name, ids in prompts.items():
        eng.add_request(name, prompt_token_ids=list(ids),
                        sampling=SamplingParams(
                            temperature=0.0, max_tokens=max_tokens,
                            logprobs=3, ignore_eos=True))
    toks, lps = {n: [] for n in prompts}, {n: [] for n in prompts}
    while eng.has_unfinished():
        for o in eng.step():
            toks[o.request_id] += o.new_token_ids
            lps[o.request_id] += [lp for lp, _ in o.new_logprobs or ()]
    return {n: (toks[n], lps[n]) for n in prompts}


def made(eng):
    """The tree as it is made, which the reference reads by shape; the
    runner keeps its own laid out (engine/weights.py ``lay_out``), made
    from the same seed: what chipbench/reference/compare.py does."""
    r = eng.runner
    return init_random(r.cfg, r.mesh, r.rules, r.config.seed)


def errors(hf, params, prompt, toks, lps, **control):
    """|served - reference| log-probability of each generated token."""
    ids = list(prompt) + toks
    want = np.asarray(reference.logprobs(hf, params, ids[:-1],
                                         len(prompt) - 1, **control))
    return np.abs(np.array([want[j, t] for j, t in enumerate(toks)])
                  - np.array(lps))


PROMPTS = {
    "long": [int(t) for t in np.random.default_rng(0).integers(0, 512, 50)],
    "short": [int(t) for t in np.random.default_rng(1).integers(0, 512, 7)],
    "mid": [int(t) for t in np.random.default_rng(2).integers(0, 512, 23)],
}


@pytest.fixture(scope="module")
def served():
    eng = engine()
    return eng, serve(eng, PROMPTS)


# -- the served path against the reference ------------------------------------

@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_served_logprobs_match_the_reference(served, name):
    """Ragged prefill (the long prompt in two chunks: the second continues
    the first one's state and conv tail), then decode through the paged
    cache and the recurrent state."""
    eng, out = served
    toks, lps = out[name]
    err = errors(HF, made(eng), PROMPTS[name], toks, lps)
    assert len(toks) == 6 and err.max() < LOGPROB_TOL, err


def over_a_limit(err, margin=1.5) -> bool:
    """`correct` would be false, and not by a hair: one of the cell's two
    limits is passed by half again."""
    return bool(err.max() > margin * CELL_TOL
                or err.mean() > margin * CELL_MEAN_TOL)


def _beta_not_doubled(eng):
    return {**HF, "kda_allow_neg_eigval": False}, made(eng)


def _an_expert_dropped(eng):
    params = jax.tree.map(lambda a: a, made(eng))
    layers = dict(params["layers"])
    # the second held expert of every layer answers nothing
    layers["w_down"] = layers["w_down"].at[:, 1].set(0.0)
    return HF, {**params, "layers": layers}


@pytest.mark.parametrize("fault,margin", [(_beta_not_doubled, 1.5),
                                          (_an_expert_dropped, 1.0)])
def test_a_fault_reads_over_the_benchmarks_limits(served, fault, margin):
    """The served path against a reference that differs from it by one
    fault: a limit of the cell is passed (readings at these weights: beta
    0.11 / 0.065; one of the four held experts 0.115 / 0.037, the smallest
    fault there is: it touches only the rows routed to it), and the clean
    path's reading by thousands of times."""
    eng, out = served
    hf, params = fault(eng)
    toks, lps = out["long"]
    err = errors(hf, params, PROMPTS["long"], toks, lps)
    assert over_a_limit(err, margin) and err.max() > 100 * LOGPROB_TOL, err


def test_state_dropped_at_a_chunk_boundary_reads_over_the_limits(
        served, monkeypatch):
    """A second chunk that starts from zeros instead of from what the first
    one left (every span of more than one row called fresh)."""
    real = kda.stream_spans

    def forgetful(cu_q_lens, context_lens, T):
        slot, off, live, q_len, _ = real(cu_q_lens, context_lens, T)
        return slot, off, live, q_len, q_len > 1

    monkeypatch.setattr(kda, "stream_spans", forgetful)
    eng = engine(params=served[0].runner.params)
    toks, lps = serve(eng, {"long": PROMPTS["long"]})["long"]
    err = errors(HF, made(eng), PROMPTS["long"], toks, lps)
    assert over_a_limit(err, 2.0), err  # reads 0.35 / 0.149


@pytest.mark.parametrize("control", ["state_dtype", "router_dtype"])
def test_a_reference_in_lower_precision_reads_as_not_correct(served, control):
    """The benchmark's control (chipbench/reference/control.py): the served
    path against a reference whose recurrent state, or router, is bfloat16
    where the configuration states float32. It reads 0.0044 / 0.0013 and
    0.0068 / 0.0032 here, ten times over this file's float32 agreement and
    far UNDER the cell's 0.15 / 0.03, which are sized for bfloat16
    activations: what the cell's probe reads on the chip is in PERF.md."""
    eng, out = served
    toks, lps = out["long"]
    err = errors(HF, made(eng), PROMPTS["long"], toks, lps,
                 **{control: "bfloat16"})
    assert err.max() > 10 * LOGPROB_TOL, err
    assert not over_a_limit(err, 1.0), err


def test_a_bfloat16_state_fails_the_float32_agreement(monkeypatch):
    """The recurrent state rounded to bfloat16 after every row, where the
    configuration states float32, reads over the float32 path's agreement
    with the reference by an order of magnitude: the comparison sees it."""
    cfg = tiny_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    want = np.asarray(reference.logprobs(HF, params, PROMPTS["long"], 0))
    real, bf = kda.delta_step, jnp.bfloat16

    def rounded(S, *row):
        S, o = real(S.astype(bf).astype(jnp.float32), *row)
        return S.astype(bf).astype(jnp.float32), o

    def served():
        got = llama.forward_dense(cfg, params, jnp.asarray([PROMPTS["long"]]))
        return np.abs(np.asarray(jax.nn.log_softmax(got[0], -1)) - want).max()

    assert served() < LOGPROB_TOL
    monkeypatch.setattr(kda, "delta_step", rounded)
    assert served() > 10 * LOGPROB_TOL


def test_a_bfloat16_router_fails_the_float32_agreement():
    """Router scores from bfloat16 inputs move the routing weights in the
    third digit; the block's agreement with the reference is 2e-5."""
    cfg, params, x, bias = _block(seed=11)
    lp = {k: v[0] for k, v in params["layers"].items()}
    lp["router_bias"] = bias
    experts = {k: params["layers"][k] for k in llama._EXPERT_WEIGHTS}
    want = _reference_block(cfg, lp, x)
    shared = llama._mlp(cfg, {"w_gate": lp["shared_gate"],
                              "w_up": lp["shared_up"],
                              "w_down": lp["shared_down"]}, x)
    bf = jnp.bfloat16

    def block(router, rows):
        logits_from = rows.astype(router.dtype)
        out, _ = llama._moe_mlp(cfg, router, experts, 0, x, bias=bias) \
            if router.dtype != bf else _bf16_routed(cfg, router, experts, x,
                                                    bias, logits_from)
        return float(jnp.abs(out + shared - want).max())

    assert block(lp["router"], x) < 2e-5
    assert block(lp["router"].astype(bf), x) > 2e-4


def _bf16_routed(cfg, router, experts, x, bias, x_bf):
    """_moe_mlp with the router's logits computed from bfloat16 inputs:
    the experts still see the float32 rows."""
    real = jnp.einsum

    def einsum(spec, a, b, **kw):
        if spec == "te,ex->tx":
            a = x_bf
        return real(spec, a, b, **kw)

    llama.jnp.einsum = einsum
    try:
        return llama._moe_mlp(cfg, router, experts, 0, x, bias=bias)
    finally:
        llama.jnp.einsum = real


def test_a_slot_reused_by_a_new_sequence_starts_from_zero(served):
    """The engine has served three sequences; the next one takes a slot
    whose state and conv tail another sequence left behind."""
    eng, _ = served
    resets = eng.stats()["recurrent_state_resets_total"]
    prompt = [int(t) for t in np.random.default_rng(9).integers(0, 512, 19)]
    toks, lps = serve(eng, {"again": prompt})["again"]
    assert float(jnp.abs(eng.runner.kv["state"]).max()) > 0
    assert errors(HF, made(eng), prompt, toks, lps).max() < LOGPROB_TOL
    assert eng.stats()["recurrent_state_resets_total"] == resets + 1


def test_preemption_then_recompute_equals_an_undisturbed_run(served):
    """A sequence preempted mid-decode loses its blocks; readmitted, it
    prefills prompt + outputs from position 0, which resets its slot's
    state: the tokens and log-probabilities are an undisturbed run's."""
    first, out = served
    eng = engine(params=first.runner.params)
    eng.add_request("long", prompt_token_ids=PROMPTS["long"],
                    sampling=SamplingParams(temperature=0.0, max_tokens=6,
                                            logprobs=3, ignore_eos=True))
    toks, lps = [], []
    preempted = False
    while eng.has_unfinished():
        for o in eng.step():
            toks += o.new_token_ids
            lps += [lp for lp, _ in o.new_logprobs or ()]
        seq = eng.scheduler.seqs.get("long")
        if seq is not None and len(toks) == 3 and not preempted:
            eng.scheduler._preempt(seq)
            preempted = True
    assert preempted and toks == out["long"][0]
    np.testing.assert_allclose(lps, out["long"][1], atol=LOGPROB_TOL)
    assert eng.stats()["recurrent_state_resets_total"] == 2


# -- ops/kda.py: the stream's forms against token by token ---------------------

def _stream(seed=0, T=24, H=4, D=32, S=5, Lk=2, strong_decay=False,
            neg_eigval=True):
    """(a, kb, k, q, vb), the stored states, and the log-decay g that the
    span kernel takes in a's place. ``strong_decay``: a quarter of the
    channels lose up to e^-30 a row, so that a block's running sum of g
    leaves the range float32's exp can invert."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    q, k, v = (jax.random.normal(ks[i], (T, H, D)) for i in range(3))
    g = -jnp.exp(jax.random.normal(ks[3], (T, H, D)) - 3)
    if strong_decay:
        g = jnp.where(jax.random.uniform(ks[6], (1, H, D)) < 0.25,
                      -30 * jax.random.uniform(ks[7], (T, H, D)), g)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    state = jax.random.normal(ks[5], (Lk, S, H, D, D)) * 0.1
    return kda.prepare(q, k, v, g, beta, neg_eigval), state, g


# spans (start, end) in slot order and each slot's context after its span:
# a span as long as its context starts its sequence, a shorter one
# continues the stored state; slot 1 has no rows, slot 3 is a decode row
SPANS = [(0, 5), (5, 5), (5, 17), (17, 18), (18, 21)]
CONTEXT = [5, 0, 30, 9, 3]


def _token_by_token(prep, state, layer):
    outs, states = {}, {}
    for s, (a0, a1) in enumerate(SPANS):
        fresh = a1 > a0 and CONTEXT[s] == a1 - a0
        S = jnp.zeros_like(state[layer, s]) if fresh else state[layer, s]
        rows = []
        for t in range(a0, a1):
            S, o = kda.delta_step(S, *(x[t] for x in prep))
            rows.append(o)
        outs[s], states[s] = rows, S
    return outs, states


@pytest.mark.parametrize("form", ["xla", "pallas", "pallas_split"])
def test_the_stream_forms_equal_token_by_token(form):
    """Spans of mixed lengths in one stream, one that continues a stored
    state, a decode row, one slot with no rows, rows of padding behind the
    last span; the span kernel alone, and as a ragged step calls it: the
    decode row through the decode kernel."""
    prep, state, g = _stream()
    cu = jnp.asarray([0] + [e for _, e in SPANS], jnp.int32)
    ctx = jnp.asarray(CONTEXT, jnp.int32)
    if form == "xla":
        o, new = kda.recurrence_ragged(state, 1, *prep, cu, ctx)
    else:
        fn = (kda_pallas.kda_chunk_scan if form == "pallas"
              else kda_pallas.kda_ragged)
        o, new = fn(state, 1, g, *prep[1:], cu, ctx, interpret=True)
    outs, states = _token_by_token(prep, state, 1)
    for s, (a0, a1) in enumerate(SPANS):
        if a1 > a0:
            np.testing.assert_allclose(o[a0:a1], jnp.stack(outs[s]),
                                       atol=1e-5)
        np.testing.assert_allclose(new[1, s], states[s], atol=1e-5)
    np.testing.assert_array_equal(new[0], state[0])  # the other layer
    np.testing.assert_array_equal(o[21:], 0)


_C = kda_pallas.CHUNK
# the spans' lengths in slot order (they lie one behind the other in a
# stream of 4 blocks' rows), each slot's context after its span (a longer
# one continues the stored state), and what else the case changes
BLOCKED = {
    "a_span_of_one_block": ([_C], [_C], {}),
    "one_row_over_a_block": ([_C + 1], [_C + 1], {}),
    "one_row_under_three_blocks": ([3 * _C - 1], [3 * _C - 1], {}),
    "from_mid_block_continuing_a_state":
        ([_C // 2 + 3, 2 * _C - 20], [_C // 2 + 3, 5 * _C], {}),
    # a span's last block reaches over the next span's rows
    "two_partial_last_blocks_side_by_side":
        ([_C + 7, _C + 18, 9], [_C + 7, 9 * _C, 9], {}),
    # the last block's window would pass the stream's end
    "a_span_up_to_the_last_row":
        ([7, 0, 4 * _C - 7], [40, 0, 5 * _C], {}),
    "beta_up_to_two":
        ([2 * _C + 5, _C - 5], [2 * _C + 5, 7 * _C], {"neg_eigval": True}),
    "strong_decay":
        ([2 * _C + 5, 2 * _C - 6], [2 * _C + 5, 7 * _C],
         {"strong_decay": True}),
}


@pytest.mark.parametrize("case", sorted(BLOCKED))
def test_the_span_kernel_takes_a_span_a_block_at_a_time(case):
    """The chunked form at the served head size and the shipped block,
    against the row-by-row recurrence: whole and partial blocks, windows
    that start mid-block, reach over the next span or would pass the
    stream's end, and a running sum of g past what exp can invert. This,
    not the benchmark's probe, holds the kernel's precision (float32
    state, products at HIGHEST): PERF.md section 7."""
    lens, context, kwargs = BLOCKED[case]
    T, S = 4 * _C, len(lens) + 1  # the last slot has no span
    prep, state, g = _stream(seed=len(case), T=T, H=1, D=128, S=S,
                             **{"neg_eigval": False, **kwargs})
    cu = jnp.cumsum(jnp.asarray([0] + lens + [0], jnp.int32))
    ctx = jnp.asarray(context + [0], jnp.int32)
    want_o, want_s = kda.recurrence_ragged(state, 1, *prep, cu, ctx)
    o, new = kda_pallas.kda_chunk_scan(state, 1, g, *prep[1:], cu, ctx,
                                       interpret=True)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(new).all())
    np.testing.assert_allclose(o, want_o, rtol=1e-4, atol=2e-6)
    np.testing.assert_allclose(new, want_s, rtol=1e-4, atol=2e-6)
    np.testing.assert_array_equal(o[sum(lens):], 0)
    np.testing.assert_array_equal(new[0], state[0])  # the other layer
    np.testing.assert_array_equal(new[1, -1], state[1, -1])  # no span


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_the_decode_step_moves_live_slots_only(form):
    prep, state, _ = _stream(seed=1)
    rows = tuple(x[:5] for x in prep)
    active = jnp.asarray([True, False, True, True, False])
    step = (kda.recurrence_decode if form == "xla" else
            lambda *a: kda_pallas.kda_decode_step(*a, interpret=True))
    o, new = step(state, 0, *rows, active)
    want_S, want_o = kda.delta_step(state[0], *rows)
    live = np.asarray(active)
    np.testing.assert_allclose(o[live], want_o[live], atol=1e-5)
    np.testing.assert_allclose(new[0][live], want_S[live], atol=1e-5)
    np.testing.assert_array_equal(new[0, 1], state[0, 1])
    np.testing.assert_array_equal(new[1], state[1])


def test_the_conv_tail_carries_a_span_into_the_next():
    """A sequence convolved in two spans (and a one-row decode step)
    equals the sequence convolved whole; a fresh span ignores the tail."""
    C, K = 12, 4
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(ks[0], (11, C))
    w = jax.random.normal(ks[1], (K, C))
    whole = kda.conv_dense(x[None], w)[0]
    tail = jax.random.normal(ks[2], (2, K - 1, C))  # what others left
    # slot 1 takes rows 0..5 fresh, then rows 6..9, then row 10 as decode
    first, tail = kda.conv_ragged(
        jnp.pad(x[:6], ((2, 0), (0, 0))), w, tail,
        jnp.asarray([0, 2, 8], jnp.int32), jnp.asarray([9, 6], jnp.int32))
    np.testing.assert_allclose(first[2:8], whole[:6], atol=1e-5)
    second, tail = kda.conv_ragged(
        x[6:10], w, tail, jnp.asarray([0, 0, 4], jnp.int32),
        jnp.asarray([0, 10], jnp.int32))
    np.testing.assert_allclose(second, whole[6:10], atol=1e-5)
    last, tail = kda.conv_decode(
        jnp.stack([x[10], x[10]]), w, tail, jnp.asarray([False, True]))
    np.testing.assert_allclose(last[1], whole[10], atol=1e-5)
    np.testing.assert_allclose(tail[1], x[8:11], atol=1e-6)


# -- the sparse block ----------------------------------------------------------

def _block(seed=0):
    cfg = tiny_cfg(experts_held=0, expert_offset=0)  # all 16 held
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (10, cfg.hidden_size))
    bias = 0.4 * jax.random.normal(jax.random.PRNGKey(seed + 2),
                                   (cfg.num_experts,))
    return cfg, params, x, bias


def _reference_block(cfg, lp, x, first=0, held=None):
    with jax.default_matmul_precision("highest"):
        return reference._sparse(
            x, lp, top_k=cfg.num_experts_per_tok, renormalise=True,
            scaling=1.0, first=first, held=held or cfg.num_experts)


def test_the_sixteen_shares_and_the_shared_expert_once_add_up():
    """The share test: each of 16 engines holds one expert of 16 and
    computes the pairs that fall on it; their routed parts, plus the
    shared expert ONCE, are the uncut layer's output."""
    cfg, params, x, bias = _block()
    lp = {k: v[1] for k, v in params["layers"].items()}
    lp["router_bias"] = bias
    total = llama._mlp(cfg, {"w_gate": lp["shared_gate"],
                             "w_up": lp["shared_up"],
                             "w_down": lp["shared_down"]}, x)
    pairs = 0
    for share in range(16):
        part = dataclasses.replace(cfg, experts_held=1, expert_offset=share)
        experts = {k: params["layers"][k][:, share:share + 1]
                   for k in llama._EXPERT_WEIGHTS}
        out, hist = llama._moe_mlp(part, lp["router"], experts, 1, x,
                                   bias=bias)
        total = total + out
        pairs += int(hist[0])
        assert int(hist.sum()) == 10 * cfg.num_experts_per_tok
    assert pairs == 10 * cfg.num_experts_per_tok  # every pair on one chip
    np.testing.assert_allclose(total, _reference_block(cfg, lp, x),
                               atol=2e-5)


def test_a_share_equals_the_references_share():
    cfg, params, x, bias = _block(seed=5)
    lp = {k: v[0] for k, v in params["layers"].items()}
    lp["router_bias"] = bias
    part = dataclasses.replace(cfg, experts_held=4, expert_offset=8)
    experts = {k: params["layers"][k][:, 8:12]
               for k in llama._EXPERT_WEIGHTS}
    out, hist = llama._sparse_block(part, lp, experts, 0, x, None)
    held = {k: (v[8:12] if k in llama._EXPERT_WEIGHTS else v)
            for k, v in lp.items()}
    np.testing.assert_allclose(
        out, _reference_block(cfg, held, x, first=8, held=4), atol=2e-5)
    assert hist.shape == (4 + 2,) and int(hist[-1]) == 0


def test_the_selection_bias_changes_the_choice_but_not_the_weights():
    cfg, params, x, _ = _block(seed=7)
    lp = {k: v[0] for k, v in params["layers"].items()}
    experts = {k: params["layers"][k] for k in llama._EXPERT_WEIGHTS}
    zero = jnp.zeros(cfg.num_experts)
    favour = zero.at[3].set(10.0)  # expert 3 is chosen by every token
    _, plain = llama._moe_mlp(cfg, lp["router"], experts, 0, x, bias=zero)
    out, hist = llama._moe_mlp(cfg, lp["router"], experts, 0, x, bias=favour)
    assert int(hist[3]) == 10 and int(plain[3]) < 10
    # weighed by the scores alone: the reference with the same bias
    lp["router_bias"] = favour
    np.testing.assert_allclose(
        out + llama._mlp(cfg, {"w_gate": lp["shared_gate"],
                               "w_up": lp["shared_up"],
                               "w_down": lp["shared_down"]}, x),
        _reference_block(cfg, lp, x), atol=2e-5)


# -- configuration, refusals, cache, counters ---------------------------------

def test_from_hf_config_reads_the_published_keys():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "solar-open2-250b-ep16-l8", "config.json")) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))
    assert (cfg.architecture, cfg.num_layers, cfg.attn_period) == (
        "solar_open2", 8, 4)
    assert (cfg.num_attn_layers, cfg.num_kda_layers, cfg.cache_layers) == (
        2, 6, 2)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (64, 8, 128)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_rank, cfg.kda_conv) == (
        64, 128, 128, 4)
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok,
            cfg.intermediate_size, cfg.shared_expert_size) == (
        320, 20, 8, 1280, 1280)
    assert (cfg.moe_scoring, cfg.attn_gate, cfg.kda_neg_eigval,
            cfg.vocab_size) == ("sigmoid", True, True, 24576)
    assert cfg.kv_bytes_per_token == 8192
    assert cfg.recurrent_state_bytes(64) == 6 * 64 * (
        64 * 128 * 128 * 4 + 3 * 3 * 64 * 128 * 2)
    assert not any(m.architecture == "solar_open2"
                   for m in MODEL_PRESETS.values())  # ROADMAP D15


@pytest.mark.parametrize("over,message", [
    ({"kda_use_full_proj": True}, "kda_use_full_proj: true is not supported"),
    ({"first_k_dense_replace": 1}, "first_k_dense_replace=1 is not supported"),
    ({"n_group": 4}, "n_group=4 is not supported"),
    ({"use_rope": True}, "use_rope: true .* is not supported"),
    ({"use_rope": True, "partial_rotary_factor": 0.5},
     r"partial_rotary_factor=0.5\) is not supported"),
    ({"gqa_layers": [0, 5]}, "gqa_layers=[0, 5] is not supported"),
    ({"num_hidden_layers": 6, "gqa_layers": [0, 4]}, "whole periods"),
    ({"n_routed_experts_held": 4, "routed_expert_offset": 14},
     "is not a share of n_routed_experts=16"),
    ({"linear_attn_config": {**HF["linear_attn_config"], "num_kv_heads": 2}},
     "linear_attn_config.num_kv_heads=2 is not supported"),
])
def test_from_hf_config_refuses_by_name(over, message):
    with pytest.raises(ValueError, match=message.replace("[", r"\[")
                       .replace("]", r"\]")):
        ModelConfig.from_hf_config({**HF, **over})


def _engine_config(**kw):
    cfg = EngineConfig(model=tiny_cfg(),
                       cache=CacheConfig(block_size=16, num_blocks=32),
                       scheduler=SchedulerConfig(max_num_seqs=2,
                                                 max_num_batched_tokens=32),
                       mesh=MeshConfig(data=1, tensor=1))
    for k, v in kw.items():
        obj, _, field = k.rpartition(".")
        setattr(getattr(cfg, obj) if obj else cfg, field, v)
    return cfg


@pytest.mark.parametrize("over,message", [
    ({"scheduler.spec_ngram_k": 2}, "n-gram speculative decoding"),
    ({"role": "prefill"}, "role=prefill: a P->D transfer"),
    ({"cache.kv_host_cache_bytes": 1 << 20}, "a host or remote KV tier"),
    ({"cache.remote_kv_url": "http://kv"}, "a host or remote KV tier"),
])
def test_the_engine_refuses_what_would_move_or_skip_the_state(over, message):
    with pytest.raises(ValueError, match=message):
        LLMEngine(_engine_config(**over), mesh=one_device())


def test_more_than_one_device_is_refused_by_name():
    cfg = _engine_config()
    two = jax.devices()[:2]
    cfg.mesh = MeshConfig(data=1, tensor=2)
    with pytest.raises(ValueError, match="a mesh of 2 devices"):
        LLMEngine(cfg, mesh=build_mesh(cfg.mesh, devices=two))


def test_a_checkpoint_is_refused_not_guessed_at(tmp_path):
    from production_stack_tpu.engine.weights import load_safetensors

    cfg = tiny_cfg(weights_path=str(tmp_path))
    with pytest.raises(ValueError, match="tensor names are not mapped"):
        load_safetensors(cfg, one_device(), None)


def test_the_cache_is_two_kinds_and_the_bytes_count_both():
    cfg = tiny_cfg()
    cache = CacheConfig(block_size=16, num_blocks=8)
    kv = init_kv_cache(cfg, cache, one_device(), slots=3)
    assert set(kv) == {"kv", "state", "conv"}
    assert kv["kv"].shape == (2, 8, 16, 2 * 2, 32)       # attention layers
    assert kv["state"].shape == (6, 3, 4, 32, 32)        # KDA layers, slots
    assert kv["state"].dtype == jnp.float32
    assert kv["conv"].shape == (6, 3, 3, 3 * 4 * 32)
    assert kv_cache_bytes_per_block(cfg, cache) == kv["kv"].nbytes // 8
    assert cfg.recurrent_state_bytes(3) == (kv["state"].nbytes
                                            + kv["conv"].nbytes)
    dense = MODEL_PRESETS["tiny-qwen3"]
    assert not isinstance(
        init_kv_cache(dense, cache, one_device()), dict)


@pytest.mark.parametrize("recurrent", [True, False])
def test_prefix_lookups_are_bypassed_for_recurrent_state_only(recurrent):
    """The same 40-token prompt twice: a model with recurrent layers gets
    no cached tokens and counts two bypassed lookups; any other model
    (Qwen3's path) hits its first two blocks, as before."""
    sched = Scheduler(SchedulerConfig(max_num_seqs=2),
                      CacheConfig(block_size=16), 16,
                      recurrent_state=recurrent)
    alloc = sched.allocator
    tokens = list(range(40))
    blocks, cached = alloc.allocate_sequence(tokens)
    alloc.commit_full_blocks(tokens, blocks)
    alloc.free_blocks(blocks)
    _, cached = alloc.allocate_sequence(tokens)
    assert cached == (0 if recurrent else 32)
    assert alloc.lookups_bypassed == (2 if recurrent else 0)
    assert alloc.prefix_hits == (0 if recurrent else 2)
    assert isinstance(alloc, PrefixCachingBlockAllocator)


COUNTERS = ("kda_decode_calls", "kda_chunk_tokens", "kda_chunk_spans",
            "kda_chunk_block_rows", "recurrent_state_resets", "prefix_lookups_bypassed",
            "moe_held_pairs", "moe_routed_tokens",
            "moe_decode_experts_touched", "moe_decode_layer_steps")


def test_the_counters_are_exported_and_add_up(served):
    eng, out = served
    s = eng.stats()
    assert s["recurrent_state_bytes"] == eng.config.model.recurrent_state_bytes(4)
    assert s["kda_decode_calls_total"] == 6 * s["decode_dispatches_total"]
    # the span scan carries the prompts' chunks (the long one in two), not
    # the decode rows packed beside them: those go through the decode step
    assert s["kda_chunk_tokens_total"] == s["prompt_tokens_total"]
    assert s["kda_chunk_tokens_total"] < s["ragged_live_tokens_total"]
    assert s["kda_chunk_spans_total"] > s["recurrent_state_resets_total"]
    # whole blocks of the span kernel's: at least one a span, and none
    # more than the spans' rows need
    blocks, rem = divmod(s["kda_chunk_block_rows_total"], kda_pallas.CHUNK)
    assert rem == 0 and s["kda_chunk_spans_total"] <= blocks
    assert blocks < (s["kda_chunk_tokens_total"] / kda_pallas.CHUNK
                     + s["kda_chunk_spans_total"])
    assert s["recurrent_state_resets_total"] >= 3
    assert s["prefix_lookups_bypassed_total"] >= 3
    assert s["gpu_prefix_cache_hits_total"] == 0
    # 4 of 16 experts held: a quarter of the pairs under an even routing
    assert 0.1 < s["moe_held_pairs_total"] / s["moe_routed_tokens_total"] < 0.4
    # experts touched a decode layer-step are of the 4 held
    assert s["moe_decode_experts_touched_total"] <= (
        4 * s["moe_decode_layer_steps_total"])
    names = {m.name for m in EngineStatsCollector(eng, "tiny-solar").collect()}
    assert {"vllm:" + c for c in COUNTERS} | {
        "vllm:recurrent_state_bytes"} <= names


def test_the_span_scans_counters_leave_out_the_decode_rows():
    """One ragged dispatch: a decode row, a 5-row chunk that continues a
    prompt, an idle slot, a one-token prompt at position 0. The span
    kernel carries the chunk and the first token (kda_ragged's split)."""
    from production_stack_tpu.engine.tracing import RecurrentCounters

    q_len, ctx = np.array([1, 5, 0, 1]), np.array([9, 37, 0, 1])
    one = kda.continues_one_row(q_len, ctx)
    assert one.tolist() == [True, False, False, False]
    c = RecurrentCounters(kda_layers=6, state_bytes=0)
    c.record_ragged(q_len, one, resets=1)
    s = c.snapshot(0)
    assert (s["kda_chunk_tokens_total"], s["kda_chunk_spans_total"],
            s["recurrent_state_resets_total"]) == (6, 2, 1)


def test_the_block_rows_counter_counts_by_the_kernels_block():
    """A mix of spans and decode rows in one ragged dispatch: a span of n
    rows is ceil(n / C) blocks of the span kernel's C rows, the decode
    rows and the idle slots none; `kda_chunk_fill_pct` is the rows over
    them."""
    from production_stack_tpu.engine.tracing import RecurrentCounters

    C = kda_pallas.CHUNK
    q_len = np.array([1, C, 0, C + 1, 1, 3 * C - 1, 5])
    ctx = np.array([70, C, 0, 4 * C, 1, 3 * C - 1, 900])
    one = kda.continues_one_row(q_len, ctx)
    assert one.tolist() == [True] + [False] * 6
    c = RecurrentCounters(kda_layers=6, state_bytes=0)
    c.record_ragged(q_len, one, resets=3)
    c.record_ragged(np.array([1, 1]), np.array([True, True]), resets=0)
    s = c.snapshot(0)
    assert s["kda_chunk_tokens_total"] == C + C + 1 + 1 + 3 * C - 1 + 5
    assert s["kda_chunk_spans_total"] == 5
    assert s["kda_chunk_block_rows_total"] == (1 + 2 + 1 + 3 + 1) * C


def test_the_span_kernels_products_are_float32_at_highest():
    """Every product of the chunked form on float32 operands with
    ``Precision.HIGHEST`` (a default-precision product is one bf16 pass
    on the chip, and the CPU's interpret mode would not show it)."""
    prep, state, g = _stream(T=kda_pallas.CHUNK, H=1, D=128)
    cu = jnp.asarray([0, 5, 5, 17, 18, 21], jnp.int32)
    jaxpr = jax.make_jaxpr(lambda *a: kda_pallas.kda_chunk_scan(
        state, 1, *a, cu, jnp.asarray(CONTEXT, jnp.int32)))(g, *prep[1:])

    def dots(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dots(sub)

    found = list(dots(jaxpr.jaxpr))
    assert len(found) >= 4  # at the least the block against its state
    for eqn in found:
        assert {v.aval.dtype for v in eqn.invars} == {jnp.dtype("float32")}
        assert eqn.params["preferred_element_type"] == jnp.float32
        assert set(eqn.params["precision"]) == {jax.lax.Precision.HIGHEST}


def test_a_dense_model_exports_none_of_them():
    from production_stack_tpu.engine.tracing import MoeCounters

    m = MoeCounters(8, 2)
    m.record("decode", np.array([[1, 1, 0, 0, 0, 0, 0, 0, 2]]))
    assert m.snapshot()["moe_held_pairs_total"] == 2 == m.routed_tokens
    share = MoeCounters(2, 2, share=True)
    share.record("decode", np.array([[1, 0, 5, 2]]))  # held, absent, padding
    snap = share.snapshot()
    assert (snap["moe_held_pairs_total"], snap["moe_routed_tokens_total"],
            snap["moe_padding_rows_total"],
            snap["moe_decode_experts_touched_total"]) == (1, 6, 1, 1)
