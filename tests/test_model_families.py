"""Mistral / Phi-3 / Qwen3 / OLMoE families — exactness against HF
transformers.

The reference serves these via vLLM's model zoo; here the shared Llama
stack grows the deltas as ModelConfig knobs (Mistral: all-layer sliding
window under the exactness gate; Phi-3: fused HF qkv/gate_up checkpoint
layout split at load; Qwen3: per-head QK RMSNorm pre-rope; OLMoE: QK
RMSNorm over the whole projection, 8 experts, top-2, weights not
renormalised, ``mlp.experts.N.*`` tensor names). Tiny random HF
checkpoints are saved to disk, loaded through our safetensors path, and
logits must match HF to float32 tolerance — then the serving engine (paged
path) must reproduce HF greedy generation.
"""

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

COMMON = dict(
    vocab_size=512, hidden_size=128, intermediate_size=256,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    max_position_embeddings=512, rms_norm_eps=1e-6, rope_theta=10000.0,
    hidden_act="silu",
)


def _mk_checkpoint(tmpdir, family: str):
    torch.manual_seed(0)
    if family == "mistral":
        cfg = transformers.MistralConfig(
            sliding_window=512, tie_word_embeddings=False, **COMMON
        )
        hf = transformers.MistralForCausalLM(cfg)
    elif family == "phi3":
        cfg = transformers.Phi3Config(
            tie_word_embeddings=False, pad_token_id=0, bos_token_id=1,
            eos_token_id=2, **COMMON
        )
        hf = transformers.Phi3ForCausalLM(cfg)
    elif family == "olmoe":
        cfg = transformers.OlmoeConfig(
            **{**COMMON, "intermediate_size": 64, "num_key_value_heads": 4,
               "rms_norm_eps": 1e-5},
            num_experts=8, num_experts_per_tok=2, norm_topk_prob=False,
            tie_word_embeddings=False, pad_token_id=0,
        )
        hf = transformers.OlmoeForCausalLM(cfg)
        with torch.no_grad():  # HF initialises norms to 1: make them count
            for name, p in hf.named_parameters():
                if name.endswith(("q_norm.weight", "k_norm.weight")):
                    p.uniform_(0.5, 1.5)
    else:  # qwen3
        cfg = transformers.Qwen3Config(
            head_dim=32, tie_word_embeddings=True, **COMMON
        )
        hf = transformers.Qwen3ForCausalLM(cfg)
    hf = hf.eval().float()
    hf.save_pretrained(str(tmpdir), safe_serialization=True)
    return hf


@pytest.fixture(scope="module",
                params=["mistral", "phi3", "qwen3", "olmoe"])
def family_ckpt(request, tmp_path_factory):
    tmp = tmp_path_factory.mktemp(request.param)
    hf = _mk_checkpoint(tmp, request.param)
    return request.param, str(tmp), hf


def test_logits_match_hf(family_ckpt):
    family, path, hf = family_ckpt
    cfg = ModelConfig.from_pretrained(path, dtype="float32")
    if family == "mistral":
        assert cfg.architecture == "llama"
        assert cfg.sliding_window == 512  # gate: serve within the window
    elif family == "phi3":
        assert cfg.architecture == "phi3"
    elif family == "olmoe":
        assert cfg.architecture == "olmoe" and cfg.qk_norm_kind == "full"
        assert (cfg.num_experts, cfg.num_experts_per_tok) == (8, 2)
        assert not cfg.norm_topk_prob
    else:
        assert cfg.qk_norm and cfg.tie_word_embeddings
    toks = torch.randint(0, cfg.vocab_size, (2, 16),
                         generator=torch.Generator().manual_seed(1))
    with torch.no_grad():
        ref = hf(toks).logits.numpy()
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    with jax.set_mesh(mesh):
        params = init_or_load(cfg, mesh)
    got = np.asarray(llama.forward_dense(cfg, params, jnp.asarray(toks.numpy())))
    np.testing.assert_allclose(got, ref, atol=3e-5, rtol=1e-4)
    if family == "olmoe":
        # every HF tensor was placed (mlp.gate, mlp.experts.N.*_proj, the
        # whole-projection norms by head), and the benchmark's plain
        # reference is the published model too
        from production_stack_tpu.engine.weights import _hf_key_map

        assert cfg.num_layers * len(_hf_key_map(cfg, 0)) + 3 == len(
            hf.state_dict())
        assert params["layers"]["w_down"].shape == (2, 8, 64, 128)
        assert params["layers"]["q_norm"].shape == (2, 4, 32)
        import json
        import os

        from chipbench.reference import olmoe as reference

        with open(os.path.join(path, "config.json")) as f:
            hf_cfg = json.load(f)
        want = torch.log_softmax(torch.from_numpy(ref[0]), -1).numpy()
        plain = np.asarray(reference.logprobs(
            hf_cfg, params, toks[0].tolist(), 0))
        np.testing.assert_allclose(plain, want, atol=5e-5, rtol=1e-4)


def test_engine_matches_hf_greedy(family_ckpt):
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


def test_phi3_longrope_rejected():
    """LongRoPE checkpoints must refuse to load, not serve garbage."""
    with pytest.raises(ValueError, match="LongRoPE"):
        ModelConfig.from_hf_config(
            {
                "architectures": ["Phi3ForCausalLM"],
                "vocab_size": 512, "hidden_size": 128,
                "intermediate_size": 256, "num_hidden_layers": 2,
                "num_attention_heads": 4,
                "rope_scaling": {"type": "longrope",
                                 "long_factor": [1.0], "short_factor": [1.0]},
            }
        )


def test_unsupported_variants_rejected():
    """Phi-3-small / Qwen3-MoE layouts differ structurally — they must
    refuse at config parse, not KeyError mid-load."""
    base = {
        "vocab_size": 512, "hidden_size": 128, "intermediate_size": 256,
        "num_hidden_layers": 2, "num_attention_heads": 4,
    }
    with pytest.raises(ValueError, match="unsupported Phi-3 variant"):
        ModelConfig.from_hf_config(
            {**base, "architectures": ["Phi3SmallForCausalLM"]}
        )
    with pytest.raises(ValueError, match="unsupported Qwen3 variant"):
        ModelConfig.from_hf_config(
            {**base, "architectures": ["Qwen3MoeForCausalLM"],
             "num_experts": 64}
        )


def test_qwen2_style_disabled_window_not_clamped():
    """Qwen2/3 checkpoints carry sliding_window but disable it — the
    exactness gate must not clamp their max_model_len."""
    cfg = ModelConfig.from_hf_config(
        {
            "architectures": ["Qwen3ForCausalLM"],
            "vocab_size": 512, "hidden_size": 128,
            "intermediate_size": 256, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "max_position_embeddings": 8192,
            "sliding_window": 4096, "use_sliding_window": False,
        }
    )
    assert cfg.max_model_len == 8192 and cfg.sliding_window == 0


def test_mistral_window_clamps_max_len():
    cfg = ModelConfig.from_hf_config(
        {
            "architectures": ["MistralForCausalLM"],
            "vocab_size": 512, "hidden_size": 128,
            "intermediate_size": 256, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "max_position_embeddings": 32768, "sliding_window": 4096,
        }
    )
    assert cfg.max_model_len == 4096 and cfg.sliding_window == 4096


def test_qwen3_spec_decode_composes(family_ckpt):
    """Speculation must stay token-identical on a qk-norm model too."""
    family, path, hf = family_ckpt
    if family != "qwen3":
        pytest.skip("one family suffices")
    prompts = [[7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8]]
    sp = SamplingParams(temperature=0.0, max_tokens=16, ignore_eos=True)

    def run(spec_k):
        cfg = EngineConfig(
            model=ModelConfig.from_pretrained(path, dtype="float32"),
            cache=CacheConfig(block_size=4, num_blocks=256),
            scheduler=SchedulerConfig(
                max_num_seqs=2, max_num_batched_tokens=32,
                spec_ngram_k=spec_k,
            ),
            mesh=MeshConfig(data=1, tensor=1),
        )
        mesh = build_mesh(cfg.mesh, devices=jax.devices()[:1])
        eng = LLMEngine(cfg, mesh=mesh, num_blocks=256)
        return eng.generate(prompts, sp)

    assert run(4) == run(0)
