"""int8 W8A8 quantization (engine/quant.py).

The reference's engines serve quantized checkpoints via vLLM's
``--quantization`` flag (the stack passes it through); here the engine owns
the scheme — per-channel weight scales + dynamic per-token activation
scales on the MXU's native int8 path. These tests pin the math (per-matmul
error, batched/MoE scale broadcasting), the full-forward accuracy, and the
serving integration (engine e2e, pipeline stages, sleep/wake restore).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine import quant
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.models import llama
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh


def test_quantize_roundtrip_error_bounded():
    w = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 32), jnp.float32)
    qw = quant.quantize_array(w, (1,))
    assert qw["q"].dtype == jnp.int8
    assert qw["s"].shape == (8, 1, 32)  # keepdims scale
    back = quant.dequantize_array(qw)
    # symmetric rounding: |err| <= s/2 elementwise
    assert float(jnp.max(jnp.abs(back - w) / qw["s"])) <= 0.5 + 1e-6


@pytest.mark.parametrize(
    "eq,x_shape,w_shape,contract",
    [
        ("...te,ehd->...thd", (2, 5, 64), (64, 4, 16), (0,)),   # qkv
        ("...thd,hde->...te", (2, 5, 4, 16), (4, 16, 64), (0, 1)),  # wo
        ("...te,ef->...tf", (2, 5, 64), (64, 96), (0,)),         # mlp
        ("xce,xef->xcf", (4, 6, 32), (4, 32, 20), (1,)),         # MoE batched
    ],
)
def test_quant_einsum_matches_dense(eq, x_shape, w_shape, contract):
    x = jax.random.normal(jax.random.PRNGKey(1), x_shape, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), w_shape, jnp.float32) * 0.1
    ref = jnp.einsum(eq, x, w)
    got = quant.quant_einsum(eq, x, quant.quantize_array(w, contract))
    rel = float(jnp.linalg.norm(ref - got) / jnp.linalg.norm(ref))
    assert rel < 0.02, rel


def test_quant_einsum_plain_weight_passthrough():
    x = jnp.ones((2, 3, 8), jnp.float32)
    w = jnp.ones((8, 4), jnp.float32)
    np.testing.assert_allclose(
        quant.quant_einsum("...te,ef->...tf", x, w),
        jnp.einsum("...te,ef->...tf", x, w),
    )


@pytest.mark.parametrize("preset", ["tiny-llama", "tiny-mixtral", "tiny-qwen2"])
def test_forward_dense_quant_close(preset):
    cfg = ModelConfig.from_pretrained(preset)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    qparams = quant.quantize_params(cfg, params)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, cfg.vocab_size)
    a = np.asarray(llama.forward_dense(cfg, params, toks), np.float32)
    b = np.asarray(llama.forward_dense(cfg, qparams, toks), np.float32)
    a2 = a.reshape(-1, cfg.vocab_size)
    b2 = b.reshape(-1, cfg.vocab_size)
    cos = np.sum(a2 * b2, -1) / (
        np.linalg.norm(a2, axis=-1) * np.linalg.norm(b2, axis=-1)
    )
    assert cos.min() > 0.99, cos.min()


def test_quantize_params_structure():
    cfg = ModelConfig.from_pretrained("tiny-mixtral")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    qp = quant.quantize_params(cfg, params)
    assert quant.is_quantized(qp["layers"]["wq"])
    assert quant.is_quantized(qp["layers"]["w_gate"])  # MoE experts too
    assert not quant.is_quantized(qp["layers"]["router"])  # router stays
    assert not quant.is_quantized(qp["layers"]["attn_norm"])
    assert quant.is_quantized(qp["embed"])
    # MoE expert scale keeps the batched layout: (L, X, 1, F)
    X, F = cfg.num_experts, cfg.intermediate_size
    assert qp["layers"]["w_gate"]["s"].shape == (cfg.num_layers, X, 1, F)


def test_maybe_quantize_gate():
    cfg = ModelConfig.from_pretrained("tiny-llama")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    assert quant.maybe_quantize(cfg, params) is params  # off by default
    qcfg = dataclasses.replace(cfg, quant="int8")
    qp = quant.maybe_quantize(qcfg, params)
    assert quant.params_quantized(qp)
    assert quant.maybe_quantize(qcfg, qp) is qp  # idempotent
    with pytest.raises(ValueError):
        quant.maybe_quantize(dataclasses.replace(cfg, quant="fp4"), params)


def _make_engine(quant_mode=None, model="tiny-llama"):
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained(model, quant=quant_mode),
        cache=CacheConfig(block_size=4, num_blocks=128),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=32
        ),
        mesh=MeshConfig(data=1, tensor=1),
    )
    mesh = build_mesh(cfg.mesh, devices=jax.devices()[:1])
    return LLMEngine(cfg, mesh=mesh, num_blocks=128)


PROMPTS = [[1, 2, 3, 4, 5], [9, 8, 7], [50, 51, 52, 53, 54, 55, 56]]


def _run(engine, prompts):
    sp = SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True)
    for i, p in enumerate(prompts):
        engine.add_request(f"r{i}", prompt_token_ids=p, sampling=sp)
    out = {}
    steps = 0
    while engine.has_unfinished() and steps < 64:
        for o in engine.step():
            out.setdefault(o.request_id, []).extend(o.new_token_ids)
        steps += 1
    assert not engine.has_unfinished()
    return out


def test_engine_int8_greedy_deterministic():
    a = _run(_make_engine("int8"), PROMPTS)
    b = _run(_make_engine("int8"), PROMPTS)
    assert a == b
    assert all(len(v) == 4 for v in a.values())


def test_engine_int8_sleep_wake_restores_quantized():
    engine = _make_engine("int8")
    before = _run(engine, [PROMPTS[0]])
    engine.runner.drop_params()  # sleep level 2 drops weights
    engine.runner.restore_params()
    assert quant.params_quantized(engine.runner.params)
    sp = SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True)
    engine.add_request("again", prompt_token_ids=PROMPTS[0], sampling=sp)
    out = []
    steps = 0
    while engine.has_unfinished() and steps < 64:
        for o in engine.step():
            out.extend(o.new_token_ids)
        steps += 1
    assert out == before["r0"]


def test_quant_einsum_w8a16_above_token_threshold(monkeypatch):
    """Phase-adaptive selection (docs/roofline.md: int8's -14% prefill
    regression): prefill-sized token counts skip activation quantization
    and run the fused weight-dequant (W8A16) path — strictly MORE
    accurate than W8A8, and bit-matching the explicit dequant einsum."""
    import numpy as np

    eq = "...te,ef->...tf"
    w = jax.random.normal(jax.random.PRNGKey(2), (64, 96), jnp.float32) * 0.1
    qw = quant.quantize_array(w, (0,))

    x_big = jax.random.normal(jax.random.PRNGKey(1), (8, 128, 64),
                              jnp.float32)  # 1024 tokens >= 512
    got = quant.quant_einsum(eq, x_big, qw)
    dequant_ref = jnp.einsum(eq, x_big, qw["q"].astype(jnp.float32)
                             * qw["s"].astype(jnp.float32))
    assert np.allclose(np.asarray(got), np.asarray(dequant_ref), atol=1e-5)
    # W8A16 must be at least as close to the dense reference as W8A8
    ref = jnp.einsum(eq, x_big, w)
    monkeypatch.setenv("PSTPU_QUANT_A16_THRESHOLD", "1000000")
    a8 = quant.quant_einsum(eq, x_big, qw)  # forced W8A8 at this size
    monkeypatch.delenv("PSTPU_QUANT_A16_THRESHOLD")
    err16 = float(jnp.linalg.norm(ref - got))
    err8 = float(jnp.linalg.norm(ref - a8))
    assert err16 <= err8 * 1.01, (err16, err8)
    # 0 disables the W8A16 path entirely
    monkeypatch.setenv("PSTPU_QUANT_A16_THRESHOLD", "0")
    forced_a8 = quant.quant_einsum(eq, x_big, qw)
    assert np.allclose(np.asarray(forced_a8), np.asarray(a8), atol=1e-6)


def test_a16_threshold_env_robustness(monkeypatch):
    """Unparseable values warn and keep the default; negative and zero
    both disable; scientific notation parses (r5 review)."""
    monkeypatch.setenv("PSTPU_QUANT_A16_THRESHOLD", "junk")
    assert quant._a16_threshold() == 512
    monkeypatch.setenv("PSTPU_QUANT_A16_THRESHOLD", "-1")
    assert quant._a16_threshold() == 0
    monkeypatch.setenv("PSTPU_QUANT_A16_THRESHOLD", "1e6")
    assert quant._a16_threshold() == 1_000_000
    monkeypatch.delenv("PSTPU_QUANT_A16_THRESHOLD")
    assert quant._a16_threshold() == 512


def test_ragged_quant_dot_matches_a_per_group_quant_einsum():
    """The MoE experts' grouped matmul with int8 weights is W8A8 with each
    row's own activation scale and its group's weight scale: the same
    numbers as quant_einsum on each group's rows alone (forced W8A8)."""
    w = jax.random.normal(jax.random.PRNGKey(2), (4, 32, 20),
                          jnp.float32) * 0.1
    qw = quant.quantize_array(w, (1,))
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 32), jnp.float32)
    sizes = jnp.asarray([5, 0, 11, 4], jnp.int32)  # 4 rows past the groups
    group = jnp.repeat(jnp.arange(5), jnp.asarray([5, 0, 11, 4, 4]),
                       total_repeat_length=24)
    got = quant.ragged_quant_dot(x, qw, sizes, group)
    plain = quant.ragged_quant_dot(x, w, sizes, group)
    lo = 0
    for g, n in enumerate([5, 0, 11, 4]):
        one = {"q": qw["q"][g], "s": qw["s"][g]}
        want = quant.quant_einsum("te,ef->tf", x[lo:lo + n], one)
        assert np.allclose(np.asarray(got[lo:lo + n]), np.asarray(want),
                           atol=1e-6)
        ref = x[lo:lo + n] @ w[g]
        assert np.allclose(np.asarray(plain[lo:lo + n]), np.asarray(ref),
                           atol=1e-5)
        lo += n
