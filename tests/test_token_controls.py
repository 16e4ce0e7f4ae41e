"""Structured-decoding primitives: logit_bias + allowed_token_ids.

OpenAI's ``logit_bias`` (string token-id keys, additive) and vLLM's
``allowed_token_ids`` (sampling whitelist) run ON DEVICE inside the fused
multi-step decode loop — a sparse (B, K) id/value scatter plus whitelist
mask per iteration, compiled only into the variant a controlled batch uses
(mirrors the penalties plumbing). The reference gets these from vLLM's
OpenAI server; here the engine owns them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine import sampling as sm
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh


def test_make_token_controls():
    assert sm.make_token_controls(SamplingParams(), 512) is None
    ids, vals, mode = sm.make_token_controls(
        SamplingParams(logit_bias={7: 2.5, 9: -1.0}), 512
    )
    assert mode == sm.CTRL_BIAS
    assert list(ids[:2]) == [7, 9] and ids[2] == -1
    assert vals[0] == 2.5 and vals[1] == -1.0
    ids, vals, mode = sm.make_token_controls(
        SamplingParams(allowed_token_ids=[3, 5], logit_bias={5: 1.0}), 512
    )
    assert mode == sm.CTRL_ALLOW
    assert list(ids[:2]) == [3, 5] and vals[1] == 1.0
    with pytest.raises(ValueError, match="out of range"):
        sm.make_token_controls(SamplingParams(logit_bias={600: 1.0}), 512)
    with pytest.raises(ValueError, match="too many"):
        sm.make_token_controls(
            SamplingParams(allowed_token_ids=list(range(100))), 512
        )
    # bias keys validate even when a whitelist takes the mode decision
    with pytest.raises(ValueError, match="out of range"):
        sm.make_token_controls(
            SamplingParams(allowed_token_ids=[5], logit_bias={9999: 1.0}), 512
        )


def test_parse_logit_bias_rejects_non_dict():
    from production_stack_tpu.engine.server import _parse_logit_bias

    assert _parse_logit_bias(None) is None
    assert _parse_logit_bias({"7": 1}) == {7: 1.0}
    with pytest.raises(ValueError, match="must be a map"):
        _parse_logit_bias(["50256"])


def test_apply_token_controls_math():
    logits = jnp.zeros((2, 10), jnp.float32)
    ids = jnp.asarray([[3, -1], [4, 5]], jnp.int32)
    vals = jnp.asarray([[2.0, 0.0], [0.5, 0.0]], jnp.float32)
    mode = jnp.asarray([sm.CTRL_BIAS, sm.CTRL_ALLOW], jnp.int32)
    out = np.asarray(sm.apply_token_controls(logits, ids, vals, mode))
    # row 0: bias only on token 3
    assert out[0, 3] == 2.0 and out[0, 0] == 0.0
    # row 1: whitelist {4, 5} with bias 0.5 on 4; everything else -inf
    assert out[1, 4] == 0.5 and out[1, 5] == 0.0
    assert out[1, 0] <= sm.NEG_INF


def _engine(multi_step=1):
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=128),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=32,
            multi_step=multi_step,
        ),
        mesh=MeshConfig(data=1, tensor=1),
    )
    mesh = build_mesh(cfg.mesh, devices=jax.devices()[:1])
    return LLMEngine(cfg, mesh=mesh, num_blocks=128)


def _run(engine, sp, prompt=(1, 2, 3, 4, 5)):
    engine.add_request("r", prompt_token_ids=list(prompt), sampling=sp)
    out = []
    steps = 0
    while engine.has_unfinished() and steps < 64:
        for o in engine.step():
            out.extend(o.new_token_ids)
        steps += 1
    return out


@pytest.mark.parametrize("multi_step", [1, 4])
def test_allowed_token_ids_restricts_all_outputs(multi_step):
    """Whitelist holds for the prefill-sampled first token AND every fused
    decode step."""
    allowed = {11, 22, 33}
    sp = SamplingParams(
        temperature=0.8, seed=7, max_tokens=8, ignore_eos=True,
        allowed_token_ids=sorted(allowed),
    )
    out = _run(_engine(multi_step=multi_step), sp)
    assert len(out) == 8
    assert set(out) <= allowed, out


def test_logit_bias_forces_token_greedy():
    """A +1e9 bias dominates every logit, so greedy must emit that token."""
    sp = SamplingParams(
        temperature=0.0, max_tokens=6, ignore_eos=True,
        logit_bias={123: 1e9},
    )
    out = _run(_engine(multi_step=2), sp)
    assert out == [123] * 6


def test_logit_bias_ban_token():
    """OpenAI -100-style ban: the otherwise-greedy token never appears."""
    base = _run(_engine(), SamplingParams(
        temperature=0.0, max_tokens=6, ignore_eos=True))
    banned = base[0]
    out = _run(_engine(), SamplingParams(
        temperature=0.0, max_tokens=6, ignore_eos=True,
        logit_bias={banned: -1e9},
    ))
    assert banned not in out


def test_mixed_batch_controls_only_affect_their_request():
    """One controlled + one plain request in the same batch: the plain one
    must match its solo (uncontrolled) run exactly."""
    engine = _engine(multi_step=2)
    solo = _run(_engine(multi_step=2), SamplingParams(
        temperature=0.0, max_tokens=6, ignore_eos=True))
    sp_plain = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)
    sp_forced = SamplingParams(
        temperature=0.0, max_tokens=6, ignore_eos=True,
        logit_bias={123: 1e9},
    )
    engine.add_request("plain", prompt_token_ids=[1, 2, 3, 4, 5],
                       sampling=sp_plain)
    engine.add_request("forced", prompt_token_ids=[9, 8, 7],
                       sampling=sp_forced)
    out = {}
    steps = 0
    while engine.has_unfinished() and steps < 64:
        for o in engine.step():
            out.setdefault(o.request_id, []).extend(o.new_token_ids)
        steps += 1
    assert out["forced"] == [123] * 6
    assert out["plain"] == solo


def test_controls_compose_with_penalties():
    """allowed_token_ids + presence penalty in one request: outputs stay in
    the whitelist and the penalty still discourages repeats."""
    sp = SamplingParams(
        temperature=0.7, seed=3, max_tokens=8, ignore_eos=True,
        allowed_token_ids=[5, 6, 7, 8], presence_penalty=1.5,
    )
    out = _run(_engine(multi_step=2), sp)
    assert set(out) <= {5, 6, 7, 8}
