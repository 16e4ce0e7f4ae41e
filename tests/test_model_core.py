"""Core model tests: mesh construction, sharded init, dense forward
invariance under tensor parallelism, ragged segment attention correctness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig, ModelConfig
from production_stack_tpu.engine.weights import init_or_load
from production_stack_tpu.models import llama
from production_stack_tpu.ops.attention import (
    dense_causal_attention,
    segment_causal_attention,
)
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh


def ref_attention(q, k, v):
    """Naive numpy reference: per-head causal attention with GQA."""
    B, S, H, D = q.shape
    KH = k.shape[2]
    out = np.zeros_like(q, dtype=np.float32)
    for b in range(B):
        for h in range(H):
            kh = h // (H // KH)
            scores = (q[b, :, h].astype(np.float32) @ k[b, :, kh].astype(np.float32).T) * D**-0.5
            mask = np.tril(np.ones((S, S), dtype=bool))
            scores = np.where(mask, scores, -1e30)
            p = np.exp(scores - scores.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[b, :, h] = p @ v[b, :, kh].astype(np.float32)
    return out


def test_mesh_resolution():
    cfg = MeshConfig(data=2, tensor=-1).resolved(8)
    assert cfg.tensor == 4 and cfg.shape == (2, 1, 1, 4, 1)
    with pytest.raises(ValueError):
        MeshConfig(data=3, tensor=-1).resolved(8)


def test_dense_causal_attention_matches_reference():
    rng = np.random.default_rng(0)
    B, S, H, KH, D = 2, 9, 4, 2, 16
    q = rng.standard_normal((B, S, H, D), dtype=np.float32)
    k = rng.standard_normal((B, S, KH, D), dtype=np.float32)
    v = rng.standard_normal((B, S, KH, D), dtype=np.float32)
    got = np.asarray(dense_causal_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    want = ref_attention(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_segment_attention_matches_per_sequence_dense():
    """Two packed sequences must attend only within themselves."""
    rng = np.random.default_rng(1)
    H, KH, D = 4, 2, 8
    s1, s2 = 5, 7
    T = s1 + s2 + 4  # includes padding
    q = rng.standard_normal((T, H, D), dtype=np.float32)
    k = rng.standard_normal((T, KH, D), dtype=np.float32)
    v = rng.standard_normal((T, KH, D), dtype=np.float32)
    segs = np.array([0] * s1 + [1] * s2 + [-1] * 4, dtype=np.int32)
    pos = np.array(list(range(s1)) + list(range(s2)) + [0] * 4, dtype=np.int32)

    got = np.asarray(
        segment_causal_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(pos), jnp.asarray(pos), jnp.asarray(segs), jnp.asarray(segs),
        )
    )
    for start, length in ((0, s1), (s1, s2)):
        want = ref_attention(
            q[None, start : start + length],
            k[None, start : start + length],
            v[None, start : start + length],
        )[0]
        np.testing.assert_allclose(got[start : start + length], want, rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module")
def tiny_setup(request):
    cfg = EngineConfig.for_model("tiny-llama").model
    mesh = build_mesh(MeshConfig(data=2, tensor=4))
    params = init_or_load(cfg, mesh, seed=0)
    return cfg, mesh, params


def test_sharded_init_shapes(tiny_setup):
    cfg, mesh, params = tiny_setup
    assert params["embed"].shape == (cfg.vocab_size, cfg.hidden_size)
    assert params["layers"]["wq"].shape == (
        cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.head_dim,
    )
    # wq must actually be sharded over the tensor axis (heads dim)
    sharding = params["layers"]["wq"].sharding
    assert sharding.spec[2] == "tensor"


def test_dense_forward_tp_invariance(tiny_setup):
    """Logits under a (2 data, 4 tensor) mesh must match single-device run."""
    cfg, mesh, params = tiny_setup
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, cfg.vocab_size, (2, 12)), jnp.int32
    )

    with jax.set_mesh(mesh):
        sharded = jax.jit(llama.forward_dense, static_argnums=0)(cfg, params, tokens)

    single = build_mesh(MeshConfig(data=1, tensor=1), devices=jax.devices()[:1])
    params_local = jax.device_put(
        jax.tree.map(np.asarray, params), jax.devices()[0]
    )
    with jax.set_mesh(single):
        local = jax.jit(llama.forward_dense, static_argnums=0)(cfg, params_local, tokens)

    np.testing.assert_allclose(
        np.asarray(sharded), np.asarray(local), rtol=2e-4, atol=2e-4
    )


def test_qwen2_bias_engine_matches_dense():
    """Qwen2-family (QKV biases) through the full paged engine vs dense."""
    from production_stack_tpu.engine.config import CacheConfig, SchedulerConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.sampling import SamplingParams

    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-qwen2"),
        cache=CacheConfig(block_size=4, num_blocks=128),
        scheduler=SchedulerConfig(max_num_seqs=2,
                                  max_num_batched_tokens=64),
    )
    mesh = build_mesh(MeshConfig(data=1, tensor=2))
    params = init_or_load(cfg.model, mesh, seed=0)
    assert "bq" in params["layers"]
    eng = LLMEngine(cfg, mesh=mesh, params=params, num_blocks=128)
    prompt = [5, 9, 2, 44, 7]
    got = eng.generate(
        [prompt], SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)
    )["offline-0"]

    toks = list(prompt)
    with jax.set_mesh(mesh):
        for _ in range(6):
            logits = jax.jit(llama.forward_dense, static_argnums=0)(
                cfg.model, params, jnp.asarray([toks], jnp.int32)
            )
            toks.append(int(jnp.argmax(logits[0, -1])))
    assert got == toks[len(prompt):]


def test_mixtral_moe_forward_runs():
    cfg = ModelConfig.from_pretrained("tiny-mixtral")
    mesh = build_mesh(MeshConfig(data=1, tensor=4, expert=2))
    params = init_or_load(cfg, mesh, seed=0)
    tokens = jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32)
    with jax.set_mesh(mesh):
        logits = jax.jit(llama.forward_dense, static_argnums=0)(cfg, params, tokens)
    assert logits.shape == (1, 5, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
