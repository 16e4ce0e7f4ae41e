"""Gemma family (1 and 2) — exactness against HuggingFace transformers.

The reference serves Gemma via vLLM's model zoo; here the shared layer
stack grows ModelConfig knobs (GeGLU, (1+w) RMSNorm, sqrt(E) embedding
scale, tied head, Gemma-2 post-norms / query scaling / logit softcaps /
sliding-window gate). These tests build tiny random HF checkpoints with
transformers, save them to disk, load them through our safetensors path and
require logits to match HF to float32 tolerance — then run the serving
engine (paged attention path) against HF greedy generation.
"""

import dataclasses

import numpy as np
import pytest


torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from production_stack_tpu.engine.config import (  # noqa: E402
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine  # noqa: E402
from production_stack_tpu.engine.sampling import SamplingParams  # noqa: E402
from production_stack_tpu.engine.weights import init_or_load  # noqa: E402
from production_stack_tpu.models import llama  # noqa: E402
from production_stack_tpu.parallel.mesh import (  # noqa: E402
    MeshConfig,
    build_mesh,
)


def _mk_checkpoint(tmpdir, family: str):
    """Random tiny HF Gemma checkpoint on disk + the HF model itself."""
    common = dict(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=512, rms_norm_eps=1e-6, rope_theta=10000.0,
        tie_word_embeddings=True, hidden_activation="gelu_pytorch_tanh",
    )
    torch.manual_seed(0)
    if family == "gemma":
        cfg = transformers.GemmaConfig(
            num_key_value_heads=1, head_dim=48, **common
        )
        hf = transformers.GemmaForCausalLM(cfg)
    else:
        cfg = transformers.Gemma2Config(
            num_key_value_heads=2, head_dim=32, query_pre_attn_scalar=64,
            attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
            sliding_window=512, **common
        )
        hf = transformers.Gemma2ForCausalLM(cfg)
    hf = hf.eval().float()
    hf.save_pretrained(str(tmpdir), safe_serialization=True)
    return hf


@pytest.fixture(scope="module", params=["gemma", "gemma2"])
def family_ckpt(request, tmp_path_factory):
    tmp = tmp_path_factory.mktemp(request.param)
    hf = _mk_checkpoint(tmp, request.param)
    return request.param, str(tmp), hf


def test_logits_match_hf(family_ckpt):
    family, path, hf = family_ckpt
    cfg = ModelConfig.from_pretrained(path, dtype="float32")
    assert cfg.architecture == family
    assert cfg.act == "gelu_tanh" and cfg.norm_offset == 1.0
    assert cfg.embed_scale and cfg.tie_word_embeddings
    if family == "gemma2":
        assert cfg.post_norms
        assert cfg.attn_logit_softcap == 50.0
        assert cfg.final_logit_softcap == 30.0
        assert cfg.query_scale == pytest.approx(64.0 ** -0.5)
    toks = torch.randint(0, cfg.vocab_size, (2, 16), generator=torch.Generator().manual_seed(1))
    with torch.no_grad():
        ref = hf(toks).logits.numpy()
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    with jax.set_mesh(mesh):
        params = init_or_load(cfg, mesh)
    got = np.asarray(llama.forward_dense(cfg, params, jnp.asarray(toks.numpy())))
    np.testing.assert_allclose(got, ref, atol=3e-5, rtol=1e-4)


def test_engine_matches_hf_greedy(family_ckpt):
    """The serving engine (paged-attention path, chunked prefill + decode)
    must emit the same greedy continuation HF generate does."""
    family, path, hf = family_ckpt
    prompt = list(range(40, 60))
    with torch.no_grad():
        out = hf.generate(
            torch.tensor([prompt]), max_new_tokens=6, do_sample=False,
        )
    want = out[0, len(prompt):].tolist()

    cfg = EngineConfig(
        model=ModelConfig.from_pretrained(path, dtype="float32"),
        cache=CacheConfig(block_size=4, num_blocks=256),
        scheduler=SchedulerConfig(
            max_num_seqs=2, max_num_batched_tokens=32,
            multi_step=2,
        ),
        mesh=MeshConfig(data=1, tensor=1),
    )
    mesh = build_mesh(cfg.mesh, devices=jax.devices()[:1])
    engine = LLMEngine(cfg, mesh=mesh, num_blocks=256)
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)
    engine.add_request("g", prompt_token_ids=prompt, sampling=sp)
    got = []
    steps = 0
    while engine.has_unfinished() and steps < 64:
        for o in engine.step():
            got.extend(o.new_token_ids)
        steps += 1
    assert got == want


def test_sliding_window_exactness_gate():
    cfg = ModelConfig.from_pretrained("tiny-gemma2")
    bad = dataclasses.replace(cfg, max_model_len=cfg.sliding_window * 2)
    ecfg = EngineConfig(
        model=bad, cache=CacheConfig(block_size=4, num_blocks=64),
        scheduler=SchedulerConfig(max_num_seqs=2),
        mesh=MeshConfig(),
    )
    mesh = build_mesh(ecfg.mesh, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="local-attention window"):
        LLMEngine(ecfg, mesh=mesh, num_blocks=64)


def test_hf_window_clamps_max_len():
    """from_hf_config clamps max_model_len into the gemma2 window."""
    cfg = ModelConfig.from_hf_config(
        {
            "architectures": ["Gemma2ForCausalLM"],
            "vocab_size": 512, "hidden_size": 128,
            "intermediate_size": 256, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 32, "max_position_embeddings": 8192,
            "sliding_window": 4096,
        }
    )
    assert cfg.max_model_len == 4096 and cfg.sliding_window == 4096


def test_gemma3_rejected_not_misloaded():
    """Gemma-3 (QK-norm, per-layer rope/window) must raise, not silently
    load as gemma-1 with its extra tensors dropped."""
    with pytest.raises(ValueError, match="unsupported Gemma variant"):
        ModelConfig.from_hf_config(
            {
                "architectures": ["Gemma3ForCausalLM"],
                "vocab_size": 512, "hidden_size": 128,
                "intermediate_size": 256, "num_hidden_layers": 2,
                "num_attention_heads": 4,
            }
        )


def test_gemma_int8_quant_composes():
    """int8 W8A8 over the Gemma stack (tied quantized head + embed scale)."""
    from production_stack_tpu.engine import quant

    cfg = ModelConfig.from_pretrained("tiny-gemma")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    qparams = quant.quantize_params(cfg, params)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0, cfg.vocab_size)
    a = np.asarray(llama.forward_dense(cfg, params, toks), np.float32)
    b = np.asarray(llama.forward_dense(cfg, qparams, toks), np.float32)
    a2 = a.reshape(-1, cfg.vocab_size)
    b2 = b.reshape(-1, cfg.vocab_size)
    cos = np.sum(a2 * b2, -1) / (
        np.linalg.norm(a2, axis=-1) * np.linalg.norm(b2, axis=-1)
    )
    assert cos.min() > 0.99
