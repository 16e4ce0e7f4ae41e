"""One packed host-to-device transfer per step (engine/model_runner.py
``StepLayout``, ``_commit``) over a real tiny engine on the CPU: a
steady-state decode or ragged step makes one transfer and nothing else
crosses to the device between ``commit`` and ``launch``; the packed buffer
round-trips every field bit for bit; the pack is the snapshot of the
engine's in-place-rewritten host arrays; and what is generated, greedy or
seeded, is what the per-array path of the parent commit generated."""

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
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.model_runner import (
    _DECODE_INPUTS,
    _RAGGED_INPUTS,
    StepLayout,
)
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

PROMPTS = ["hello world", "the quick brown fox jumps over the lazy dog"]
# tokens of the parent commit (82627bb, per-array transfers) for PROMPTS on
# tiny-llama, engine seed 0, 12 tokens, ignore_eos; the same under the
# ragged and the bucketed attention path (computed from an unpacked
# `git archive` of the parent, on the CPU). GREEDY's are the ones
# tests/test_step_tracing.py pins; SEEDED's request seed does not fit 31 bits
GREEDY = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
GREEDY_TOKENS = [
    [263, 351, 351, 351, 358, 351, 351, 351, 351, 351, 263, 331],
    [218, 400, 218, 400, 218, 400, 430, 36, 319, 218, 400, 218],
]
SEEDED = SamplingParams(max_tokens=12, temperature=0.8, top_k=30, top_p=0.95,
                        seed=3_000_000_007, ignore_eos=True)
SEEDED_TOKENS = [
    [400, 430, 20, 321, 351, 248, 91, 429, 212, 434, 364, 33],
    [74, 219, 262, 291, 263, 46, 20, 458, 376, 196, 4, 228],
]
# every head axis divides tensor=2, so the KV pool really partitions
SHARDABLE = dataclasses.replace(
    ModelConfig.from_pretrained("tiny-llama"),
    num_heads=8, num_kv_heads=8, head_dim=16,
)


def make_engine(tp: int = 1, model=None, **sched) -> LLMEngine:
    kw = dict(max_num_seqs=4, max_num_batched_tokens=64)
    kw.update(sched)
    cfg = EngineConfig(
        model=model or ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=512),
        scheduler=SchedulerConfig(**kw),
        mesh=MeshConfig(data=1, tensor=tp))
    return LLMEngine(cfg, mesh=build_mesh(cfg.mesh,
                                          devices=jax.devices()[:tp]))


def drain(eng, limit=200) -> None:
    for _ in range(limit):
        if not eng.has_unfinished():
            return
        eng.step()
    raise AssertionError("engine did not drain")


# -- (a) one transfer, nothing else dispatched to the device -------------------

@pytest.mark.parametrize("tp", [1, 2])
def test_a_steady_state_step_makes_one_transfer(tp, monkeypatch):
    eng = make_engine(tp, model=SHARDABLE if tp > 1 else None)
    runner = eng.runner
    eng.generate(PROMPTS, GREEDY)  # compiles both programs, makes constants
    steps = eng.clock.steps
    before = steps["ragged"] + steps["decode"]
    commits, launches = [], []
    real_commit = runner._commit

    def commit(buf):
        assert eng.clock._phase == "commit"
        commits.append(buf)
        with jax.transfer_guard_host_to_device("allow"):
            return real_commit(buf)

    monkeypatch.setattr(runner, "_commit", commit)
    for attr in ("_ragged", "_decode_multi"):
        real = getattr(runner, attr)

        def program(*a, _real=real, _attr=attr, **kw):
            assert eng.clock._phase == "launch"
            # every array argument is on the device already
            assert not any(isinstance(x, np.ndarray)
                           for x in jax.tree.leaves((a, kw)))
            launches.append(_attr)
            return _real(*a, **kw)

        monkeypatch.setattr(runner, attr, program)
    eng.add_request("steady", prompt_token_ids=[5, 6, 7, 8], sampling=GREEDY)
    # whatever else reaches for the device from the host in a step, a
    # jnp.asarray, a jnp.zeros placeholder, a device_put, raises here
    with jax.transfer_guard_host_to_device("disallow_explicit"):
        drain(eng)
    assert launches[0] == "_ragged" and "_decode_multi" in launches
    assert len(commits) == len(launches) == (
        steps["ragged"] + steps["decode"] - before)
    for buf in commits:
        assert buf.dtype == np.int32 and buf.ndim == 1
        assert buf.flags.c_contiguous and buf.flags.owndata


def test_a_launch_with_and_without_device_tokens_share_one_executable():
    """``tokens_dev`` rides every decode dispatch (a constant when the
    launch is given none), so a step launched prepared compiles nothing
    new: after warm-up neither the tracker nor jit's own cache sees a new
    entry."""
    eng = make_engine(multi_step=2)
    eng.warmup()
    runner = eng.runner
    sizes = (runner._ragged.fn._cache_size(),
             runner._decode_multi.fn._cache_size())
    on_device = []
    real = runner.prepare_decode

    def spy(*a, **kw):
        launch = real(*a, **kw)

        def launched(tokens=None):
            assert (tokens is not None) == kw["tokens_dev"]
            on_device.append(tokens is not None)
            return launch(tokens)

        return launched

    runner.prepare_decode = spy
    eng.generate(PROMPTS, GREEDY)
    eng.generate(PROMPTS, SEEDED)
    assert True in on_device and False in on_device
    assert eng.perf.stats_fields()["unexpected_recompiles"] == 0
    assert (runner._ragged.fn._cache_size(),
            runner._decode_multi.fn._cache_size()) == sizes


# -- (b) pack -> unpack is bit-exact, field by field ---------------------------

def _field_values(shape, dt: str, rng) -> np.ndarray:
    if dt == "float32":
        # values whose bit patterns a lossy route would not keep
        pool = np.array([0.0, -0.0, 0.7, 0.95, 1.0, 1e-38, 3.4e38,
                         np.float32(1) / 3], np.float32)
        return rng.choice(pool, size=shape)
    if dt == "uint32":  # seeds at and above 2**31
        return rng.integers(2**31, 2**32, size=shape, dtype=np.uint64) \
            .astype(np.uint32)
    return rng.integers(-1, 2**31 - 1, size=shape, dtype=np.int64) \
        .astype(np.int32)


def _layout_and_arrays(kind: str):
    B, M, T, W = 4, 8, 16, 3
    shapes = {"decode": {"block_tables": (B, M), "tokens_on_device": (1,)},
              "ragged": {"tokens": (1, T), "positions": (1, T),
                         "block_tables": (B, M), "cu_q_lens": (B + 1,),
                         "slot_mapping": (T,), "verify_idx": (B, W)}}[kind]
    spec = {"decode": _DECODE_INPUTS, "ragged": _RAGGED_INPUTS}[kind]
    rng = np.random.default_rng(25)
    arrays = [_field_values(shapes.get(name, (B,)), dt, rng)
              for name, dt in spec]
    return StepLayout.of(spec, arrays), arrays


@pytest.mark.parametrize("kind,field", [
    (kind, name)
    for kind, spec in (("decode", _DECODE_INPUTS), ("ragged", _RAGGED_INPUTS))
    for name, _ in spec])
def test_pack_unpack_round_trips_every_field_bit_for_bit(kind, field):
    layout, arrays = _layout_and_arrays(kind)
    buf = layout.pack(arrays)
    assert buf.dtype == np.int32
    assert buf.shape == (sum(a.size for a in arrays),)
    got = jax.jit(layout.unpack)(jax.device_put(buf))[field]
    (want,) = [a for a, (name, _, _) in zip(arrays, layout.fields)
               if name == field]
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.asarray(got).tobytes() == want.tobytes()


def test_layout_follows_from_shapes_alone():
    layout, arrays = _layout_and_arrays("ragged")
    assert layout == StepLayout.of(_RAGGED_INPUTS,
                                   [np.zeros_like(a) for a in arrays])
    assert hash(layout) == hash(StepLayout(layout.fields))
    with pytest.raises(ValueError):  # verify_idx missing for its spec
        StepLayout.of(_RAGGED_INPUTS, arrays[:-1])


# -- (c) the pack is the snapshot ----------------------------------------------

@pytest.mark.parametrize("method,sampling,want,in_order", [
    ("ragged_step", GREEDY, GREEDY_TOKENS, False),
    ("prepare_decode", SEEDED, SEEDED_TOKENS, False),
    ("prepare_decode", SEEDED, SEEDED_TOKENS, True)],
    ids=["ragged_step", "prepare_decode-prepared", "prepare_decode-in_order"])
def test_host_arrays_may_be_rewritten_once_the_call_returns(method, sampling,
                                                            want, in_order):
    """With the fetch deferred the step may still be pending when the
    engine rewrites its host arrays in place, and a decode step's inputs
    are packed before the step ahead of it has landed. Scribble over every
    one of them the moment the runner returns (``prepare_decode``: before
    its launch, too), let the step finish, then put them back: the results
    must not have read the scribble. Neither call fetches: the engine
    does, a step later."""
    eng = make_engine()
    if in_order:
        eng.arrival_probe = lambda: True
    runner = eng.runner
    real = getattr(runner, method)
    calls = []

    def scribbling(*arrays, **kw):
        assert kw.get("fetch", False) is False
        result = real(*arrays, **kw)
        mutable = [a for a in (*arrays, kw.get("verify_idx"))
                   if isinstance(a, np.ndarray)]
        kept = [a.copy() for a in mutable]
        for a in mutable:
            a[...] = 3

        def restore(done):
            jax.block_until_ready(done)
            for a, k in zip(mutable, kept):
                a[...] = k
            calls.append(len(mutable))
            return done

        if method == "ragged_step":
            return restore(result)
        return lambda tokens=None: restore(result(tokens))

    setattr(runner, method, scribbling)
    assert list(eng.generate(PROMPTS, sampling).values()) == want
    assert calls and min(calls) >= 10


# -- (d) same tokens as the per-array path -------------------------------------

@pytest.mark.parametrize("sched", [{}, {"multi_step": 2}, {"in_order": True},
                                   {"spec_ngram_k": 3}],
                         ids=["default", "two_a_step", "in_order", "spec"])
@pytest.mark.parametrize("sampling,want", [(GREEDY, GREEDY_TOKENS),
                                           (SEEDED, SEEDED_TOKENS)],
                         ids=["greedy", "seeded"])
def test_tokens_equal_the_parents(sched, sampling, want):
    in_order = sched.pop("in_order", False)
    eng = make_engine(**sched)
    if in_order:  # every decode step built after the landing before it
        eng.arrival_probe = lambda: True
    assert list(eng.generate(PROMPTS, sampling).values()) == want
    assert (eng.decode_prepared_launches > 0) == (
        not in_order and "spec_ngram_k" not in sched)
