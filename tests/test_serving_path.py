"""The serving path end to end on the tiny models: every prompt through
the ragged step, every decode-only step through the fused decode program,
on the CPU as on the chip.

What the engine serves does not depend on how the scheduler batched it:
chunked and mixed with decode rows against each request alone and
unchunked, feature by feature and family by family, the families also
against the dense forward; and zero unexpected recompiles after warmup
(ONE steady-state signature set). The kernel, the scheduler's policy and
the accountant's split have their units in tests/test_ragged_attention.py
(one file is one worker's load: the kernel's interpret-mode cases alone are
most of a run).
"""

import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.sampling import SamplingParams


# ---- end-to-end on the tiny model -----------------------------------------

@pytest.fixture(scope="module")
def setup():
    from production_stack_tpu.engine.weights import init_or_load
    from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=256),
        scheduler=SchedulerConfig(
            max_num_seqs=8, max_num_batched_tokens=32,
            ),
        mesh=MeshConfig(data=1, tensor=4),
    )
    mesh = build_mesh(cfg.mesh)
    params = init_or_load(cfg.model, mesh, seed=0)
    return cfg, mesh, params


def make_engine(setup, **overrides):
    from production_stack_tpu.engine.engine import LLMEngine

    cfg, mesh, params = setup
    cfg = dataclasses.replace(cfg, **overrides) if overrides else cfg
    return LLMEngine(cfg, mesh=mesh, params=params,
                     num_blocks=cfg.cache.num_blocks)


def _drain(eng, reqs, stagger_at=(), abort_at=None, top=False):
    """Submit requests (optionally staggered mid-flight), collect tokens
    and token-logprobs per request id (with ``top`` the whole entries:
    the chosen token's and the top list). A request is ``(id, prompt,
    sampling)`` or ``(id, prompt, sampling, adapter_slot)``;
    ``abort_at=(step, id)`` aborts that request between two steps. An
    engine marked ``alone`` (the ``pair`` fixture's second) serves each
    request by itself, to its end, before it takes the next."""
    toks = {rid: [] for rid, *_ in reqs}
    lps = {rid: [] for rid, *_ in reqs}

    def submit(rid, prompt, sampling, slot=0):
        eng.add_request(rid, prompt_token_ids=prompt, sampling=sampling,
                        adapter_slot=slot)

    def collect(outs):
        for o in outs:
            toks[o.request_id].extend(o.new_token_ids)
            if o.new_logprobs:
                lps[o.request_id].extend(
                    e if top else e[0] for e in o.new_logprobs)

    if getattr(eng, "alone", False):
        for req in reqs:
            submit(*req)
            n = 0
            while eng.has_unfinished():
                collect(eng.step())
                n += 1
                if abort_at == (n, req[0]):
                    assert eng.abort_request(req[0])
        return toks, lps
    queue = list(reqs)
    if not stagger_at:  # submit everything up front
        for req in queue:
            submit(*req)
        queue = []
    else:  # first request now, the rest at the named step numbers
        submit(*queue.pop(0))
    n = 0
    while True:
        outs = eng.step()
        n += 1
        if queue and n in stagger_at:
            submit(*queue.pop(0))
        collect(outs)
        if abort_at and n == abort_at[0]:
            assert eng.abort_request(abort_at[1])
        if not eng.has_unfinished() and not queue:
            break
    return toks, lps


GREEDY = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)


def test_ragged_requires_budget_at_least_max_seqs(setup):
    with pytest.raises(ValueError, match="max_num_batched_tokens"):
        make_engine(
            setup,
            scheduler=SchedulerConfig(max_num_seqs=8,
                                      max_num_batched_tokens=4),
        )


def test_a_default_engine_serves_a_prompt_through_the_ragged_step(setup):
    """On the CPU too: the ragged step over the XLA attention forms (the
    kernels' reference), and no other program that runs a prompt."""
    eng = make_engine(setup)
    assert eng.runner.use_pallas is False
    toks, _ = _drain(eng, [("r", list(range(1, 40)), GREEDY)])
    assert len(toks["r"]) == 12 and eng.ragged_dispatches > 0
    kinds = {kind for kind, _ in eng.perf.stats_fields()["compile_counts"]}
    assert kinds == {"ragged", "decode_multi"}


def test_ragged_no_recompiles_after_warmup(setup):
    eng = make_engine(
        setup,
        scheduler=SchedulerConfig(max_num_seqs=4,
                                  max_num_batched_tokens=16),
    )
    assert eng.perf is not None
    eng.warmup()
    assert eng.perf.stats_fields()["unexpected_recompiles"] == 0
    # live mixed traffic after warmup: staggered greedy + sampled +
    # chunked prefill must all hit pre-compiled signatures
    reqs = [
        ("g", list(range(1, 40)), GREEDY),
        ("s", [4, 8, 12],
         SamplingParams(temperature=0.7, max_tokens=8, ignore_eos=True)),
        ("g2", [3, 5], GREEDY),
    ]
    _drain(eng, reqs, stagger_at=(2, 3))
    fields = eng.perf.stats_fields()
    assert fields["unexpected_recompiles"] == 0, fields["compile_counts"]
    # the unified program was actually exercised (and tracked)
    assert any(kind == "ragged" for kind, _ in fields["compile_counts"])
    assert eng.ragged_dispatches > 0
    stats = eng.stats()
    assert 0.0 < stats["ragged_stream_utilization"] <= 1.0


# ---- the serving path, feature by feature ----------------------------------
# Every cell of the benchmark serves through the ragged step + decode_multi,
# and so does every test on the CPU. What a request gets must not depend on
# what the scheduler put beside it: each case below sends the same requests
# through an engine of the 32-token budget (the long prompt chunked, its
# chunks mixed with the others' decode rows) and through an engine of the
# same configuration that serves each request alone, its prompt in one
# chunk, and asks for equal tokens (log-probabilities within 1e-3). The
# model families are also held to the dense forward without a cache, which
# owes nothing to either. A configuration's two engines are built once and
# shared by its cases; both see the same history, so what an earlier case
# left in the prefix cache is the same on both sides.

def _sp(max_tokens=8, temperature=0.0, ignore_eos=True, **kw):
    return SamplingParams(max_tokens=max_tokens, temperature=temperature,
                          ignore_eos=ignore_eos, **kw)


SHORT = [1, 5, 9, 13, 2, 6]
LONG = list(range(1, 70))  # more than the 32-token step budget
ALONE_BUDGET = 128  # holds every prompt of a case in one chunk
FAMILIES = ("tiny-gemma", "tiny-gemma2", "tiny-qwen3", "tiny-phi3",
            "tiny-mistral", "tiny-mixtral")
# the families that joined after the first six, as their own test files
# build them: the 64-expert block, the looped stack, the KDA hybrid, the
# latent cache, the Mamba / window / shared-cache stack, KDA state beside a
# latent pool (the scatter into a pool that rides a cache pytree), two
# mixers a layer (a state AND a cache layer from every layer, G = 5).
# (model, block size)


def _solar_open2():
    from tests.test_solar_open2 import tiny_cfg

    return tiny_cfg()


def _falcon_h1():
    from tests.test_falcon_h1 import CFG

    return CFG


def _olmo_hybrid():
    from tests.test_olmo_hybrid import CFG

    return CFG


LATER_FAMILIES = {
    "tiny-olmoe": (lambda: ModelConfig.from_pretrained("tiny-olmoe"), 4),
    "tiny-ouro": (lambda: ModelConfig.from_pretrained("tiny-ouro"), 4),
    "tiny-solar-open2": (_solar_open2, 16),
    "tiny-pangu": (lambda: ModelConfig.from_pretrained("tiny-pangu"), 16),
    "tiny-phi4flash": (
        lambda: ModelConfig.from_pretrained("tiny-phi4flash"), 4),
    "tiny-kimi-linear": (
        lambda: ModelConfig.from_pretrained("tiny-kimi-linear"), 16),
    "tiny-falcon-h1": (_falcon_h1, 4),
    "tiny-olmo-hybrid": (_olmo_hybrid, 4),
}


@pytest.fixture(scope="module")
def pair(setup):
    """``pair(name)`` -> (the batching engine, the engine that serves each
    request alone, the (config, mesh, params) both were built from)."""
    from production_stack_tpu.engine.weights import init_or_load
    from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

    built = {}

    def build(name):
        base, over = setup, {}
        if name == "small-pool":  # the case's 3 sequences need 16 blocks
            over = {"cache": CacheConfig(block_size=4, num_blocks=12)}
        elif name != "llama":
            # its own weights: one device, so that every head count divides
            if name in LATER_FAMILIES:
                model, block = LATER_FAMILIES[name]
                model = model()
                over = {"cache": CacheConfig(block_size=block,
                                             num_blocks=256)}
            else:
                model = (
                    ModelConfig.from_pretrained("tiny-llama", quant="int8")
                    if name == "int8" else ModelConfig.from_pretrained(name))
            cfg = dataclasses.replace(setup[0], model=model,
                                      mesh=MeshConfig(data=1, tensor=1))
            mesh = build_mesh(cfg.mesh, devices=jax.devices()[:1])
            base = (cfg, mesh, init_or_load(cfg.model, mesh, seed=0))
        batching = make_engine(base, **over)
        alone = make_engine(base, scheduler=dataclasses.replace(
            base[0].scheduler, max_num_batched_tokens=ALONE_BUDGET), **over)
        alone.alone = True
        return batching, alone, base

    def get(name):
        if name not in built:
            built[name] = build(name)
        return built[name]

    return get


def _serve(reqs, **kw):
    return lambda eng: _drain(eng, list(reqs), **kw)


def _stop_at_fourth_token(eng):
    free, _ = _drain(eng, [("free", SHORT, _sp(12))])
    stop = free["free"][3]
    toks, _ = _drain(eng, [("stop", SHORT, SamplingParams(
        temperature=0.0, max_tokens=12, stop_token_ids=[stop]))])
    assert toks["stop"] and toks["stop"][-1] == stop
    assert len(toks["stop"]) <= 4
    return free, toks


def _lora_beside_plain(eng):
    import shutil

    from production_stack_tpu.engine.lora import LoraManager
    from tests.test_lora import make_adapter_dir

    lora = LoraManager(eng)
    path = make_adapter_dir(eng.config.model, seed=1)
    try:
        lora.load("parity-adapter", path)
        slot = lora.slot_of("parity-adapter")
        toks, _ = _drain(eng, [("plain", SHORT, _sp(8)),
                               ("lora", SHORT, _sp(8), slot)])
    finally:
        lora.unload("parity-adapter")
        shutil.rmtree(path)
    assert toks["plain"] != toks["lora"]  # the adapter was applied
    return toks


def _prefix_hit(eng):
    """The same prompt twice: the second time its full blocks come from
    the prefix cache, unless the model keeps recurrent state (its lookups
    are answered as misses); the tokens are the first time's either way."""
    prompt = [int(t) for t in
              np.random.default_rng(3).integers(1, 500, 40)]
    first, _ = _drain(eng, [("first", prompt, _sp(8))])
    hits = eng.stats()["gpu_prefix_cache_hits_total"]
    second, _ = _drain(eng, [("second", prompt, _sp(8))])
    hit = eng.stats()["gpu_prefix_cache_hits_total"] > hits
    assert hit != eng.config.model.has_recurrent_state
    assert first["first"] == second["second"]
    return second


def _preempt_and_recompute(eng):
    sched = eng.scheduler
    with mock.patch.object(sched, "_preempt", wraps=sched._preempt) as spy:
        toks, _ = _drain(eng, [("a", SHORT, _sp(12)),
                               ("b", [3, 3, 3, 100, 200], _sp(12)),
                               ("c", list(range(42, 51)), _sp(12))])
    # a request served alone has the pool to itself
    assert spy.called != getattr(eng, "alone", False), (
        "the pool was meant to be too small for the batch, and for it only")
    assert all(len(t) == 12 for t in toks.values())
    return toks


def _abort_between_steps(eng):
    free = eng.scheduler.num_free_blocks
    toks, _ = _drain(eng, [("keep", SHORT, _sp(10)),
                           ("gone", LONG, _sp(10)),
                           ("keep2", [2, 4], _sp(10))],
                     abort_at=(2, "gone"))
    assert len(toks.pop("gone")) < 10
    # what the aborted sequence held is back in the pool (cached prefix
    # blocks count as free)
    assert eng.scheduler.num_free_blocks == free
    return toks


def _guided_choice(eng):
    return eng.choice_logprobs([5, 6, 7, 8], [[10, 11], [12], [13, 14, 15]])


JSON_SCHEMA = {"type": "object",
               "properties": {"sentiment": {"enum": ["pos", "neg"]},
                              "score": {"type": "integer"}}}

# name -> (configuration, what to run on each of its two engines)
PARITY_CASES = {
    "logprobs_top5_chunked_prompt": ("llama", _serve(
        [("lp", LONG, _sp(8, logprobs=5)), ("side", SHORT, _sp(8))],
        top=True)),
    "seeded_sampling": ("llama", _serve(
        [("s", SHORT, _sp(10, temperature=0.8, top_p=0.9, top_k=20,
                          seed=1234)),
         ("s2", LONG, _sp(10, temperature=1.0, top_k=5, seed=7))])),
    "guided_regex": ("llama", _serve(
        [("g", [5, 6, 7], SamplingParams(
            temperature=0.0, max_tokens=16,
            guided_regex=r"(yes|no)( indeed)?")),
         ("free", SHORT, _sp(8))])),
    "guided_json": ("llama", _serve(
        [("j", [9, 8, 7, 6], SamplingParams(
            temperature=0.9, seed=3, max_tokens=48,
            guided_json=JSON_SCHEMA))])),
    "guided_choice": ("llama", _guided_choice),
    "logit_bias": ("llama", _serve(
        [("bias", SHORT, _sp(8, logit_bias={7: 0.3, 93: -5.0})),
         ("plain", SHORT, _sp(8))])),
    "allowed_token_ids": ("llama", _serve(
        [("allow", SHORT, _sp(8, temperature=1.0, seed=5,
                              allowed_token_ids=[3, 5, 9, 200]))])),
    "stop_token_ids": ("llama", _stop_at_fourth_token),
    "max_tokens_1": ("llama", _serve(
        [("one", SHORT, _sp(1)), ("one_long", LONG, _sp(1, logprobs=2))])),
    "penalties": ("llama", _serve(
        [("pen", [5, 6, 7, 8], _sp(10, presence_penalty=0.8,
                                   frequency_penalty=0.3))])),
    "lora_beside_plain": ("llama", _lora_beside_plain),
    "int8_weights": ("int8", _serve(
        [("q", SHORT, _sp(8)), ("q_long", LONG, _sp(8, logprobs=1))])),
    "prefix_cache_hit": ("llama", _prefix_hit),
    "preempt_and_recompute": ("small-pool", _preempt_and_recompute),
    "abort_between_steps": ("llama", _abort_between_steps),
    **{name: (name, _serve([("g", SHORT, _sp(8)), ("g_long", LONG, _sp(8))]))
       for name in FAMILIES},
    **{name: (name, _serve([("g", SHORT, _sp(8, logprobs=2)),
                            ("g_long", LONG, _sp(8, logprobs=2))]))
       for name in LATER_FAMILIES},
    # every family sampled under a seed, and its second sight of a prompt
    # (a hit in the prefix cache, or the lookup a recurrent-state model
    # answers as a miss); the later families' first token alone, an abort
    # that gives a slot with state in it to the next request, and a stop
    # token that ends a request mid-dispatch
    **{f"seeded_sampling-{name}": (name, _serve(
        [("s", SHORT, _sp(8, temperature=0.8, top_p=0.9, top_k=20,
                          seed=1234)),
         ("s2", LONG, _sp(8, temperature=1.0, top_k=5, seed=7))]))
       for name in FAMILIES + tuple(LATER_FAMILIES)},
    **{f"same_prompt_again-{name}": (name, _prefix_hit)
       for name in FAMILIES + tuple(LATER_FAMILIES)},
    **{f"max_tokens_1-{name}": (name, _serve(
        [("one", SHORT, _sp(1, logprobs=2)), ("one_long", LONG, _sp(1))]))
       for name in LATER_FAMILIES},
    **{f"abort_between_steps-{name}": (name, _abort_between_steps)
       for name in LATER_FAMILIES},
    **{f"stop_token_ids-{name}": (name, _stop_at_fourth_token)
       for name in LATER_FAMILIES},
}
DENSE_CASES = FAMILIES + tuple(LATER_FAMILIES)  # requests "g" and "g_long"
DENSE_LEN = 80  # one length for every dense pass: LONG and its 8 tokens


def _assert_same(got, want):
    """Tokens (ints) equal, log-probabilities (floats) within 1e-3, over
    whatever nesting of dicts, lists and tuples a case returns."""
    if isinstance(want, dict):
        assert got.keys() == want.keys()
        for k in want:
            _assert_same(got[k], want[k])
    elif isinstance(want, (list, tuple)):
        assert len(got) == len(want)
        for g, w in zip(got, want):
            _assert_same(g, w)
    elif isinstance(want, float):
        assert got == pytest.approx(want, abs=1e-3)
    else:
        assert got == want


def _dense_logprobs(cfg, params, prompt, toks, mesh):
    """The dense forward's log-probability of each of ``toks`` after
    ``prompt``: one pass over the whole sequence, no cache."""
    from production_stack_tpu.models import llama

    ids = list(prompt) + list(toks)
    ids = jnp.asarray([ids + [0] * (DENSE_LEN - len(ids))], jnp.int32)
    with jax.set_mesh(mesh):
        logp = jax.nn.log_softmax(jax.jit(
            llama.forward_dense, static_argnums=0)(cfg, params, ids)[0], -1)
    first = len(prompt) - 1
    return [float(logp[first + i, t]) for i, t in enumerate(toks)]


@pytest.mark.parametrize("case", PARITY_CASES)
def test_serving_path_is_batching_invariant(pair, case):
    from tests.test_engine import naive_greedy

    config, run = PARITY_CASES[case]
    batching, alone, (cfg, mesh, params) = pair(config)
    before = batching.ragged_dispatches
    want = run(alone)
    got = run(batching)
    assert not batching.has_unfinished() and not alone.has_unfinished()
    # guided choice scores in one dense program
    assert batching.ragged_dispatches > before or case == "guided_choice"
    _assert_same(got, want)
    if case in DENSE_CASES:
        toks, lps = got
        for rid, prompt in (("g", SHORT), ("g_long", LONG)):
            assert toks[rid] == naive_greedy(cfg.model, params, prompt, 8,
                                             mesh, pad_to=DENSE_LEN)
            if lps[rid]:
                assert lps[rid] == pytest.approx(_dense_logprobs(
                    cfg.model, params, prompt, toks[rid], mesh), abs=1e-3)
