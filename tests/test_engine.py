"""End-to-end engine correctness: continuous batching + paged attention +
chunked prefill + prefix cache + preemption must all reproduce naive dense
greedy generation exactly (float32, CPU)."""

import dataclasses

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
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.weights import init_or_load
from production_stack_tpu.models import llama
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh


@pytest.fixture(scope="module")
def setup():
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=256),
        scheduler=SchedulerConfig(
            max_num_seqs=8, max_num_batched_tokens=64,
            ),
        mesh=MeshConfig(data=1, tensor=4),
    )
    mesh = build_mesh(cfg.mesh)
    params = init_or_load(cfg.model, mesh, seed=0)
    return cfg, mesh, params


def naive_greedy(cfg, params, prompt, n_tokens, mesh, pad_to=None):
    """Reference: full dense forward each step, argmax. ``pad_to``: run
    every step at that one length, the tokens padded behind (attention is
    causal: what follows a row cannot reach it), so that a caller with many
    prompts compiles once a model, not once a length."""
    toks = list(prompt)
    with jax.set_mesh(mesh):
        for _ in range(n_tokens):
            ids = toks + [0] * max((pad_to or 0) - len(toks), 0)
            logits = jax.jit(llama.forward_dense, static_argnums=0)(
                cfg, params, jnp.asarray([ids], jnp.int32)
            )
            toks.append(int(jnp.argmax(logits[0, len(toks) - 1])))
    return toks[len(prompt):]


def make_engine(setup, **overrides):
    cfg, mesh, params = setup
    cfg = dataclasses.replace(cfg, **overrides) if overrides else cfg
    return LLMEngine(cfg, mesh=mesh, params=params, num_blocks=cfg.cache.num_blocks)


GREEDY = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)
PROMPTS = [
    [1, 5, 9, 13, 2, 7],
    [3, 3, 3, 100, 200],
    [42, 17, 80, 81, 82, 83, 84, 85, 86],
]


def test_single_greedy_matches_dense(setup):
    cfg, mesh, params = setup
    eng = make_engine(setup)
    got = eng.generate([PROMPTS[0]], GREEDY)["offline-0"]
    want = naive_greedy(cfg.model, params, PROMPTS[0], 8, mesh)
    assert got == want


def test_batched_mixed_lengths_match_dense(setup):
    cfg, mesh, params = setup
    eng = make_engine(setup)
    got = eng.generate(PROMPTS, GREEDY)
    for i, p in enumerate(PROMPTS):
        want = naive_greedy(cfg.model, params, p, 8, mesh)
        assert got[f"offline-{i}"] == want, f"prompt {i} diverged"


def test_chunked_prefill_matches_dense(setup):
    cfg, mesh, params = setup
    # the budget is at least the slots (every decode row claims a token)
    sched = dataclasses.replace(
        cfg.scheduler, max_num_batched_tokens=8
    )
    eng = make_engine(setup, scheduler=sched)
    assert len(PROMPTS[2]) > 8  # served in chunks
    got = eng.generate([PROMPTS[2]], GREEDY)["offline-0"]
    want = naive_greedy(cfg.model, params, PROMPTS[2], 8, mesh)
    assert got == want


def test_prefix_cache_hit_and_identical_output(setup):
    cfg, mesh, params = setup
    eng = make_engine(setup)
    long_prompt = list(np.random.default_rng(3).integers(1, 500, 24))
    first = eng.generate([long_prompt], GREEDY)["offline-0"]
    stats0 = eng.stats()
    second = eng.generate([long_prompt], GREEDY)["offline-0"]
    stats1 = eng.stats()
    assert first == second
    assert stats1["gpu_prefix_cache_hits_total"] > stats0["gpu_prefix_cache_hits_total"]


def test_preemption_recompute_matches_dense(setup):
    cfg, mesh, params = setup
    # tiny pool: 3 seqs × growing decode forces preemption
    eng = make_engine(setup, cache=CacheConfig(block_size=4, num_blocks=18))
    long = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    got = eng.generate(PROMPTS, long)
    for i, p in enumerate(PROMPTS):
        want = naive_greedy(cfg.model, params, p, 12, mesh)
        assert got[f"offline-{i}"] == want, f"prompt {i} diverged under preemption"


def test_multi_step_decode_matches_dense(setup):
    """K fused decode iterations per dispatch must not change results."""
    cfg, mesh, params = setup
    sched = dataclasses.replace(cfg.scheduler, multi_step=4)
    eng = make_engine(setup, scheduler=sched)
    got = eng.generate(PROMPTS, SamplingParams(temperature=0.0, max_tokens=10,
                                               ignore_eos=True))
    for i, p in enumerate(PROMPTS):
        want = naive_greedy(cfg.model, params, p, 10, mesh)
        assert got[f"offline-{i}"] == want, f"prompt {i} diverged with multi_step"
        assert len(got[f"offline-{i}"]) == 10  # surplus discarded exactly


def test_seeded_sampling_reproducible(setup):
    eng = make_engine(setup)
    sp = SamplingParams(temperature=0.8, top_p=0.9, seed=1234, max_tokens=10,
                        ignore_eos=True)
    a = eng.generate([PROMPTS[0]], sp)["offline-0"]
    b = eng.generate([PROMPTS[0]], sp)["offline-0"]
    assert a == b
    greedy = eng.generate([PROMPTS[0]], GREEDY)["offline-0"]
    assert len(a) == 10 and a != greedy[: len(a)]


def test_engine_metrics_contract(setup):
    eng = make_engine(setup)
    eng.add_request("r1", prompt_token_ids=PROMPTS[0], sampling=GREEDY)
    assert eng.stats()["num_requests_waiting"] == 1
    eng.step()  # prefill
    s = eng.stats()
    assert s["num_requests_running"] == 1
    assert 0 < s["gpu_cache_usage_perc"] < 1
    while eng.has_unfinished():
        eng.step()
    assert eng.stats()["num_requests_running"] == 0


def test_max_model_len_rejection(setup):
    eng = make_engine(setup)
    with pytest.raises(ValueError):
        eng.add_request("big", prompt_token_ids=list(range(600)))


@pytest.mark.parametrize("axis", ["stage", "seq"])
def test_refused_mesh_axes(setup, axis):
    """Nothing shards over the stage or the seq axis any more (pipeline
    stages and ring prefill were removed): a mesh that sets one above 1
    is refused by name, not served on a path that ignores it."""
    cfg, _, _ = setup
    mesh_cfg = MeshConfig(data=1, tensor=1, **{axis: 2})
    mesh = build_mesh(mesh_cfg, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=f"{axis}=2 is not supported"):
        LLMEngine(dataclasses.replace(cfg, mesh=mesh_cfg), mesh=mesh,
                  num_blocks=64)


@pytest.mark.parametrize("value", ["auto", "ragged", "bucketed"])
def test_attention_impl_flag(value, capsys):
    """``--attention-impl`` names the one family there is: the two values
    still accepted change nothing, the removed one is refused by name."""
    from production_stack_tpu.engine.server import (
        build_parser,
        config_from_args,
    )

    base = ["--model", "tiny-llama", "--max-num-seqs", "4"]
    plain = config_from_args(build_parser().parse_args(base))
    if value == "bucketed":
        with pytest.raises(SystemExit):
            build_parser().parse_args(base + ["--attention-impl", value])
        assert "bucketed prefill family was removed" in " ".join(
            capsys.readouterr().err.split())
        return
    args = build_parser().parse_args(base + ["--attention-impl", value])
    assert config_from_args(args) == plain
    assert not hasattr(plain, "attention_impl")
