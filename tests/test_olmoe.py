"""OLMoE through the shared Llama stack and the serving engine, against
the plain reference the benchmark uses on the chip
(chipbench/reference/olmoe.py), on seeded random weights at test size
(``tiny-olmoe``: 2 layers, hidden 128, 8 experts of 64, top-2, MHA). The
block alone is in tests/test_moe.py; exactness against HF transformers'
OlmoeForCausalLM and the HF tensor names in tests/test_model_families.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import olmoe as reference
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.metrics import EngineStatsCollector
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.weights import init_or_load
from production_stack_tpu.models import llama
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

# the catalog's config (model-configs guide, OLMoE-1B-7B-0125-Instruct),
# as https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/main/config.json
CATALOG = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304,
}
# float32 on the CPU on both sides; the served path differs from the
# reference in the order of its sums only (grouped rows, k terms against
# X, chunked attention): log-probabilities of size ~6 agree to ~1e-5.
# Computing in bfloat16 instead reads ~3e-2 here and fails.
LOGPROB_TOL = 2e-4


def hf_of(cfg: ModelConfig) -> dict:
    """What the reference reads of a configuration file."""
    return {"rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "num_hidden_layers": cfg.num_layers,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "tie_word_embeddings": cfg.tie_word_embeddings}


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig.from_pretrained("tiny-olmoe")
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    return cfg, mesh, init_or_load(cfg, mesh, seed=7)


def make_engine(cfg, mesh, params, **sched) -> LLMEngine:
    kw = dict(max_num_seqs=4, max_num_batched_tokens=16)
    kw.update(sched)
    ecfg = EngineConfig(
        model=cfg, cache=CacheConfig(block_size=4, num_blocks=256),
        scheduler=SchedulerConfig(**kw), mesh=MeshConfig(data=1, tensor=1))
    return LLMEngine(ecfg, mesh=mesh, params=params)


def run(eng, limit=400) -> dict:
    out: dict = {}
    for _ in range(limit):
        if not eng.has_unfinished():
            return out
        for o in eng.step():
            rec = out.setdefault(o.request_id, {"tokens": [], "lp": []})
            rec["tokens"] += o.new_token_ids
            rec["lp"] += o.new_logprobs or []
    raise AssertionError("engine did not drain")


# -- (a) the stack's forward against the reference ---------------------------

def test_forward_matches_the_plain_reference(tiny):
    cfg, mesh, params = tiny
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, 48)
    with jax.set_mesh(mesh):
        logits = jax.jit(llama.forward_dense, static_argnums=0)(
            cfg, params, jnp.asarray(toks[None], jnp.int32))[0]
    got = np.asarray(jax.nn.log_softmax(logits, -1))
    want = np.asarray(reference.logprobs(hf_of(cfg), params, list(toks), 0))
    assert np.abs(got - want).max() < LOGPROB_TOL
    # tight enough to refuse the next precision down
    bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    with jax.set_mesh(mesh):
        low = jax.jit(llama.forward_dense, static_argnums=0)(
            cfg, bf16, jnp.asarray(toks[None], jnp.int32))[0]
    low = np.asarray(jax.nn.log_softmax(low.astype(jnp.float32), -1))
    assert np.abs(low - want).max() > 10 * LOGPROB_TOL


# -- (b) chunked prefill, then decode through the paged cache ----------------

@pytest.mark.parametrize("order", ["prepared", "in_order"])
def test_prefill_in_two_chunks_then_decode_matches_the_reference(tiny, order):
    cfg, mesh, params = tiny
    eng = make_engine(cfg, mesh, params)
    if order == "in_order":  # every prepared decode step is dropped
        eng.arrival_probe = lambda: True
    prompt = [int(t) for t in np.random.default_rng(1).integers(
        0, cfg.vocab_size, 27)]  # 16 + 11: two chunks of the 16-token budget
    eng.add_request("p", prompt_token_ids=prompt, sampling=SamplingParams(
        max_tokens=9, temperature=0.0, logprobs=5, ignore_eos=True))
    got = run(eng)["p"]
    assert len(got["tokens"]) == 9  # first token + 8 decode steps
    assert eng.clock.steps["ragged"] >= 2 and eng.clock.steps["decode"] >= 8
    toks = prompt + got["tokens"]
    want = np.asarray(reference.logprobs(
        hf_of(cfg), params, toks[:-1], len(prompt) - 1))
    errs = [abs(want[j, tid] - lp)
            for j, (tok, (tok_lp, top)) in enumerate(
                zip(got["tokens"], got["lp"]))
            for tid, lp in [(tok, tok_lp), *top[:5]]]
    assert len(errs) == 9 * 6 and max(errs) < LOGPROB_TOL
    # log-probabilities keep a batch in order; the same prompt without
    # them runs its decode steps prepared, and generates the same
    before = eng.decode_prepared_launches
    eng.add_request("q", prompt_token_ids=prompt, sampling=SamplingParams(
        max_tokens=9, temperature=0.0, ignore_eos=True))
    assert run(eng)["q"]["tokens"] == got["tokens"]
    assert before == 0
    assert (eng.decode_prepared_launches > 0) == (order == "prepared")


# -- (d) padding is not routed, and the counters say so ----------------------

def test_padding_is_counted_and_not_routed(tiny):
    cfg, mesh, params = tiny
    eng = make_engine(cfg, mesh, params)
    rng = np.random.default_rng(2)
    lens = [(5, 4), (19, 3), (9, 6)]
    for i, (p, o) in enumerate(lens):
        eng.add_request(f"r{i}", prompt_token_ids=[
            int(t) for t in rng.integers(0, cfg.vocab_size, p)],
            sampling=SamplingParams(max_tokens=o, temperature=0.0,
                                    ignore_eos=True))
    run(eng)
    moe, k, L = eng.runner.moe, cfg.num_experts_per_tok, cfg.num_layers
    # every token but a request's last goes through the model exactly once
    live_rows = sum(p + o - 1 for p, o in lens)
    assert moe.routed_tokens == live_rows * k * L
    sched = eng.config.scheduler
    all_rows = (eng.ragged_dispatches * sched.max_num_batched_tokens
                + eng.decode_dispatches * max(sched.multi_step, 1)
                * sched.max_num_seqs)
    assert moe.padding_rows + moe.routed_tokens // k == all_rows * L
    assert moe.padding_rows > 0
    assert moe.snapshot()["moe_expert_load_mean_total"] == pytest.approx(
        moe.routed_tokens / cfg.num_experts)
    assert moe.expert_load_max >= moe.routed_tokens / cfg.num_experts
    assert 0 < moe.decode_experts_touched <= (
        eng.decode_dispatches * max(sched.multi_step, 1) * L
        * cfg.num_experts)
    # exported under the names the benchmark reads
    assert eng.stats()["moe_routed_tokens_total"] == moe.routed_tokens
    names = {m.name for m in EngineStatsCollector(eng, "tiny-olmoe").collect()}
    assert {"vllm:moe_routed_tokens", "vllm:moe_padding_rows",
            "vllm:moe_expert_load_max", "vllm:moe_expert_load_mean",
            "vllm:moe_decode_experts_touched", "vllm:moe_layer_steps",
            "vllm:moe_grouped_kernel_layer_steps"} <= names
    # layer-steps of every kind of dispatch; on the CPU none of them ran
    # the Pallas grouped matmul (the runner's choice: model_runner.py)
    assert moe.layer_steps == L * (
        eng.ragged_dispatches
        + eng.decode_dispatches * max(sched.multi_step, 1))
    assert moe.decode_layer_steps < moe.layer_steps
    assert moe.snapshot()["moe_grouped_kernel_layer_steps_total"] == 0


def test_a_dense_model_exports_no_moe_counters():
    cfg = ModelConfig.from_pretrained("tiny-llama")
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    eng = make_engine(cfg, mesh, None)
    eng.add_request("d", prompt_token_ids=[3, 4, 5], sampling=SamplingParams(
        max_tokens=3, temperature=0.0, ignore_eos=True))
    assert len(run(eng)["d"]["tokens"]) == 3
    assert eng.runner.moe is None
    assert not [k for k in eng.stats() if k.startswith("moe_")]


# -- (f) the configuration file ----------------------------------------------

def test_from_hf_config_reads_the_catalog_file_as_moe():
    cfg = ModelConfig.from_hf_config(CATALOG, name="olmoe")
    assert cfg.architecture == "olmoe" and cfg.is_moe
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (64, 8)
    assert cfg.norm_topk_prob is False
    assert cfg.qk_norm and cfg.qk_norm_kind == "full"
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (16, 16, 128)
    assert (cfg.intermediate_size, cfg.max_model_len) == (1024, 4096)
    assert not cfg.tie_word_embeddings and not cfg.qkv_bias
    # the shipped preset is the same model
    preset = ModelConfig.from_pretrained("olmoe-1b-7b")
    for f in ("hidden_size", "num_layers", "vocab_size", "num_experts",
              "num_experts_per_tok", "norm_topk_prob", "qk_norm_kind",
              "rope_theta", "rms_norm_eps", "intermediate_size"):
        assert getattr(preset, f) == getattr(cfg, f), f
    # the architectures key alone says so too
    named = {k: v for k, v in CATALOG.items() if k != "model_type"}
    named["architectures"] = ["OlmoeForCausalLM"]
    assert ModelConfig.from_hf_config(named).architecture == "olmoe"


@pytest.mark.parametrize("bad,match", [
    ({"clip_qkv": 8.0}, "clip_qkv"),
    # another MoE family under keys this stack would read as dense
    ({"model_type": "qwen2_moe", "architectures": ["Qwen2MoeForCausalLM"]},
     "unsupported MoE"),
    ({"model_type": "deepseek_v2", "architectures": ["DeepseekV2ForCausalLM"],
      "n_routed_experts": 64, "num_experts": None}, "unsupported MoE"),
])
def test_from_hf_config_refuses_what_it_would_serve_wrongly(bad, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config({**CATALOG, **bad})


def test_mixtral_files_still_read_as_before():
    cfg = ModelConfig.from_hf_config({
        "architectures": ["MixtralForCausalLM"], "vocab_size": 512,
        "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_local_experts": 4, "num_experts_per_tok": 2})
    assert cfg.architecture == "mixtral" and cfg.is_moe
    assert cfg.num_experts == 4 and cfg.norm_topk_prob and not cfg.qk_norm


# -- what the accountant reckons ---------------------------------------------

def test_perf_accounting_reckons_active_parameters_and_touched_experts():
    from production_stack_tpu.engine.perf_accounting import (
        PerfAccountant,
        estimate_param_count,
    )

    cfg = ModelConfig.from_pretrained("olmoe-1b-7b")
    n = estimate_param_count(cfg)
    assert n == pytest.approx(6.92e9, rel=0.01)  # held: every expert
    acct = PerfAccountant(cfg, param_count=n, param_bytes=2 * n)
    # 8 of 64 experts a token: 1.28 B active, as the model's name says
    assert acct.active_param_count == pytest.approx(1.28e9, rel=0.02)
    one = acct._weight_bytes(1)
    assert one == pytest.approx(2 * acct.active_param_count, rel=1e-6)
    assert one < acct._weight_bytes(8) < acct._weight_bytes(64)
    assert acct._weight_bytes(64) == pytest.approx(2 * n, rel=0.001)
    dense = ModelConfig.from_pretrained("tiny-llama")
    d = PerfAccountant(dense, param_count=1000, param_bytes=2000)
    assert d.active_param_count == 1000 and d._weight_bytes(3) == 2000
