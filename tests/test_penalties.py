"""Presence/frequency penalties: repeated tokens get suppressed on device
across multi-step decode dispatches."""

import dataclasses

import numpy as np

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.weights import init_or_load
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh


def make_engine(multi_step=3):
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=256),
        scheduler=SchedulerConfig(max_num_seqs=2, multi_step=multi_step),
        mesh=MeshConfig(data=1, tensor=1),
    )
    mesh = build_mesh(cfg.mesh)
    params = init_or_load(cfg.model, mesh, seed=0)
    return LLMEngine(cfg, mesh=mesh, params=params, num_blocks=256)


def test_frequency_penalty_suppresses_repeats():
    prompt = [7, 7, 7, 7, 7]
    n = 24

    eng = make_engine()
    plain = eng.generate(
        [prompt],
        SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True),
    )["offline-0"]

    eng2 = make_engine()
    penalised = eng2.generate(
        [prompt],
        SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True,
                       frequency_penalty=2.0, presence_penalty=1.0),
    )["offline-0"]

    # greedy tiny models loop hard; the penalty must break repetition
    def max_run(toks):
        best = run = 1
        for a, b in zip(toks, toks[1:]):
            run = run + 1 if a == b else 1
            best = max(best, run)
        return best

    assert len(set(penalised)) > len(set(plain)) or max_run(penalised) < max_run(plain), (
        plain, penalised,
    )
    # and the unpenalised path is untouched (still deterministic greedy)
    eng3 = make_engine()
    again = eng3.generate(
        [prompt], SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True)
    )["offline-0"]
    assert again == plain
