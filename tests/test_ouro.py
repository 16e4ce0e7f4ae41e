"""Ouro (a weight-tied stack run ``loop_passes`` times) through the shared
Llama stack and the serving engine, against the plain reference the
benchmark uses on the chip (chipbench/reference/ouro.py), on seeded random
weights at test size (``tiny-ouro``: 3 layers run 4 times, hidden 128,
MHA, norms before and after each sublayer).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import ouro as reference
from chipbench.reference.qwen3 import F32, _head, _rms, _rope
from production_stack_tpu.engine.config import (
    MODEL_PRESETS,
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.kv_cache import (
    init_kv_cache,
    kv_cache_bytes_per_block,
    resolve_num_blocks,
)
from production_stack_tpu.engine.metrics import EngineStatsCollector
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.weights import init_or_load
from production_stack_tpu.models import llama
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

# the catalog's config (model-configs guide, Ouro-2.6B), as
# https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json
CATALOG = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}
# float32 on the CPU on both sides; the served path differs from the
# reference in the order of its sums only (chunked attention, the scan):
# log-probabilities of size ~6 agree to ~1e-5. Computing in bfloat16
# instead reads ~1e-2 here and fails.
LOGPROB_TOL = 2e-4
# chipbench/run.py's limits, which every cell's `correct` is held to
CELL_TOL, CELL_MEAN_TOL = 0.15, 0.03


def hf_of(cfg: ModelConfig) -> dict:
    """What the reference reads of a configuration file."""
    return {"rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "num_hidden_layers": cfg.num_layers,
            "total_ut_steps": cfg.loop_passes,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "tie_word_embeddings": cfg.tie_word_embeddings}


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshConfig(), devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def tiny(mesh):
    cfg = ModelConfig.from_pretrained("tiny-ouro")
    return cfg, mesh, init_or_load(cfg, mesh, seed=7)


def make_engine(cfg, mesh, params, role="unified", **sched) -> LLMEngine:
    kw = dict(max_num_seqs=4, max_num_batched_tokens=16)
    kw.update(sched)
    ecfg = EngineConfig(
        model=cfg, cache=CacheConfig(block_size=4, num_blocks=256),
        scheduler=SchedulerConfig(**kw), mesh=MeshConfig(data=1, tensor=1),
        role=role)
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


def dense_logprobs(cfg, mesh, params, toks):
    with jax.set_mesh(mesh):
        logits = jax.jit(llama.forward_dense, static_argnums=0)(
            cfg, params, jnp.asarray(np.asarray(toks)[None], jnp.int32))[0]
    return np.asarray(jax.nn.log_softmax(logits.astype(jnp.float32), -1))


# -- (a) the stack's forward against the reference ---------------------------

def test_forward_matches_the_plain_reference(tiny):
    cfg, mesh, params = tiny
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, 48)
    got = dense_logprobs(cfg, mesh, params, toks)
    want = np.asarray(reference.logprobs(hf_of(cfg), params, list(toks), 0))
    assert np.abs(got - want).max() < LOGPROB_TOL
    # tight enough to refuse the next precision down
    low = dense_logprobs(
        cfg, mesh, jax.tree.map(lambda a: a.astype(jnp.bfloat16), params),
        toks)
    assert np.abs(low - want).max() > 10 * LOGPROB_TOL


# -- (b) chunked prefill, then decode through the paged cache ----------------

@pytest.mark.parametrize("order", ["prepared", "in_order"])
def test_prefill_in_two_chunks_then_decode_matches_the_reference(tiny, order):
    cfg, mesh, params = tiny
    eng = make_engine(cfg, mesh, params)
    if order == "in_order":  # every prepared decode step is dropped
        eng.arrival_probe = lambda: True
    assert eng.runner.kv.shape[0] == 12  # a cache layer per (pass, layer)
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
    # every forward ran every pass, and the counters say so
    loop = eng.runner.loop
    forwards = (eng.ragged_dispatches + eng.decode_dispatches
                * max(eng.config.scheduler.multi_step, 1))
    assert loop.layer_steps == cfg.num_layers * forwards
    assert loop.layer_passes == cfg.loop_passes * loop.layer_steps
    assert eng.stats()["loop_layer_passes_total"] == loop.layer_passes
    names = {m.name for m in EngineStatsCollector(eng, "tiny-ouro").collect()}
    assert {"vllm:loop_layer_passes", "vllm:loop_layer_steps"} <= names


def test_a_stack_run_once_exports_no_loop_counters(mesh):
    cfg = ModelConfig.from_pretrained("tiny-llama")
    eng = make_engine(cfg, mesh, None)
    eng.add_request("d", prompt_token_ids=[3, 4, 5], sampling=SamplingParams(
        max_tokens=3, temperature=0.0, ignore_eos=True))
    assert len(run(eng)["d"]["tokens"]) == 3
    assert eng.runner.loop is None
    assert not [k for k in eng.stats() if k.startswith("loop_")]
    names = {m.name for m in EngineStatsCollector(eng, "tiny-llama").collect()}
    assert not {n for n in names if n.startswith("vllm:loop_")}


# -- (c) run once, the same preset is the plain stack ------------------------

def test_one_pass_of_the_same_preset_is_the_plain_post_norms_stack(tiny):
    """``loop_passes`` = 1 takes the path every other family takes: the
    same weights through a plain ``post_norms`` stack (architecture
    "llama", no float32 stream) give the same logits to the bit; four
    passes give others."""
    cfg, mesh, params = tiny
    toks = np.random.default_rng(3).integers(0, cfg.vocab_size, 24)
    once = dataclasses.replace(cfg, loop_passes=1)
    plain = dataclasses.replace(once, architecture="llama",
                                residual_f32=False)
    assert plain.cache_layers == plain.num_layers == 3
    a = dense_logprobs(once, mesh, params, toks)
    b = dense_logprobs(plain, mesh, params, toks)
    np.testing.assert_array_equal(a, b)
    assert np.abs(dense_logprobs(cfg, mesh, params, toks) - a).max() > 0.05


# -- (d) the comparison finds faults -----------------------------------------

@functools.partial(jax.jit, static_argnames=("eps", "theta"))
def _faulty_layer(x, lp, kv, *, eps, theta):
    """The reference's layer, written again; ``kv`` (k, v), when given,
    takes the place of this layer's own keys and values. Returns (x, the
    layer's own (k, v))."""
    lp = jax.tree_util.tree_map(lambda a: a.astype(F32), lp)
    pos = jnp.arange(x.shape[0])
    h = _rms(x, lp["attn_norm"], eps)
    q = _rope(jnp.einsum("te,ehd->thd", h, lp["wq"]), pos, theta)
    own = (_rope(jnp.einsum("te,ehd->thd", h, lp["wk"]), pos, theta),
           jnp.einsum("te,ehd->thd", h, lp["wv"]))
    k, v = own if kv is None else kv
    s = jnp.einsum("thd,shd->hts", q, k) * (q.shape[-1] ** -0.5)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    x = x + _rms(jnp.einsum("thd,hde->te", a, lp["wo"]),
                 lp["post_attn_norm"], eps)
    n = _rms(x, lp["mlp_norm"], eps)
    m = (jax.nn.silu(n @ lp["w_gate"]) * (n @ lp["w_up"])) @ lp["w_down"]
    return x + _rms(m, lp["post_mlp_norm"], eps), own


def faulty_logprobs(hf, params, tokens, first, fault=None):
    """A copy of the reference's two loops with one of three faults: the
    last pass skipped; pass u attending over the keys and values pass
    u - 1 computed (a cache layer index that forgets the pass); the norm
    between passes left out. ``fault=None`` is the reference again."""
    eps, theta = float(hf["rms_norm_eps"]), float(hf["rope_theta"])
    norm = params["final_norm"].astype(F32)
    L, U = int(hf["num_hidden_layers"]), int(hf["total_ut_steps"])
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        cache = {}
        for u in range(U):
            if fault == "pass_skipped" and u == U - 1:
                continue
            if u and fault != "no_norm_between_passes":
                x = _rms(x, norm, eps)
            for i in range(L):
                lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
                stale = (cache.get((u - 1, i))
                         if fault == "stale_cache_layers" else None)
                x, cache[u, i] = _faulty_layer(x, lp, stale, eps=eps,
                                               theta=theta)
        return _head(x[first:], norm, params["lm_head"], eps=eps)


@pytest.fixture(scope="module")
def probe(mesh):
    """A looped stack deep enough for rounding to add up (16 layers x 4
    passes, hidden 256, two heads of 128) in bfloat16 with the float32
    stream, at the weights ``init_params`` makes for a looped family; 96
    tokens, the top 5 log-probabilities of the last 48 positions, as the
    chip's probe takes them."""
    cfg = dataclasses.replace(
        ModelConfig.from_pretrained("ouro-2.6b"), hidden_size=256,
        intermediate_size=704, num_heads=2, num_kv_heads=2, num_layers=16,
        vocab_size=1024, max_model_len=512)
    params = init_or_load(cfg, mesh, seed=1)
    toks = list(np.random.default_rng(1).integers(0, cfg.vocab_size, 96))
    first = 48
    served = dense_logprobs(cfg, mesh, params, toks)[first:]
    top = np.argsort(-served, -1)[:, :5]
    want = np.asarray(reference.logprobs(hf_of(cfg), params, toks, first))

    def errs(got):
        d = np.abs(np.take_along_axis(np.asarray(got), top, -1)
                   - np.take_along_axis(want, top, -1))
        return float(d.max()), float(d.mean())
    return cfg, params, toks, first, served, errs


def test_bf16_reads_under_half_of_the_cells_limits(probe):
    cfg, params, toks, first, served, errs = probe
    assert cfg.dtype == "bfloat16" and cfg.residual_f32
    worst, mean = errs(served)  # 0.050 / 0.0093 when written
    assert worst < CELL_TOL / 2 and mean < CELL_MEAN_TOL / 2
    # the copy below, with no fault seeded, is the reference
    again = faulty_logprobs(hf_of(cfg), params, toks, first)
    assert errs(again)[0] < 1e-4


@pytest.mark.parametrize("fault", ["pass_skipped", "stale_cache_layers",
                                   "no_norm_between_passes"])
def test_a_seeded_fault_reads_over_both_limits(probe, fault):
    """0.44-1.5 / 0.12-0.40 when written: three times over at the least,
    where rounding reads under half. The limits can tell them apart."""
    cfg, params, toks, first, _, errs = probe
    worst, mean = errs(faulty_logprobs(hf_of(cfg), params, toks, first,
                                       fault))
    assert worst > 2 * CELL_TOL and mean > 2 * CELL_MEAN_TOL


def test_the_stream_in_bf16_reads_over_half_the_mean_limit(probe, mesh):
    """Why the looped family carries its residual stream in float32: the
    same weights with the stream in the model dtype."""
    cfg, params, toks, first, _, errs = probe
    low = dataclasses.replace(cfg, residual_f32=False)
    _, mean = errs(dense_logprobs(low, mesh, params, toks)[first:])
    assert mean > CELL_MEAN_TOL / 2


# -- (e) the pool ------------------------------------------------------------

def test_the_pool_carries_a_cache_layer_per_pass_and_layer(mesh):
    cfg = ModelConfig.from_hf_config(CATALOG, name="ouro")
    cache = CacheConfig()
    assert (cfg.num_layers, cfg.loop_passes, cfg.cache_layers) == (48, 4, 192)
    per_block = kv_cache_bytes_per_block(cfg, cache)
    assert per_block == 2 * 192 * 16 * 16 * 128 * 2 == 25_165_824
    assert per_block // cache.block_size == 1_572_864  # bytes a token
    # ~9 GB free after the weights, at 0.9: ~320 blocks, ~5,100 tokens
    assert resolve_num_blocks(cfg, cache, 9_000_000_000) == 321
    small = dataclasses.replace(cfg, num_kv_heads=1, head_dim=8)
    kv = init_kv_cache(small, cache, mesh, num_blocks=2)
    assert kv.shape == (192, 2, 16, 2, 8)


def test_a_transfer_lands_every_cache_layer(tiny):
    """P->D frames and the host tier frame the pool by cache layers: the
    streamed export of a looped model's blocks carries all 12, in the
    default two groups, and lands them in another engine's pool."""
    import asyncio

    from production_stack_tpu.engine.kv_transfer import (
        consume_frames,
        default_group,
        produce_frames,
    )

    cfg, mesh, params = tiny
    src, dst = (make_engine(cfg, mesh, params) for _ in range(2))
    src.add_request("p", prompt_token_ids=list(range(1, 14)),
                    sampling=SamplingParams(max_tokens=2, temperature=0.0,
                                            ignore_eos=True))
    run(src)
    blocks = [0, 1, 2]
    full = src.runner.export_blocks(blocks)
    assert full.shape[0] == cfg.cache_layers == 12
    # every (pass, layer) pair wrote keys and values of its own
    flat = full.reshape(12, -1)
    assert all(np.abs(flat[i]).max() > 0 for i in range(12))
    assert len({flat[i].tobytes() for i in range(12)}) == 12

    async def main():
        async def on(eng, fn):
            return fn(eng)

        chunks = [f async for f in produce_frames(
            functools.partial(on, src), blocks, cfg.cache_layers)]
        assert len(chunks) == 2 + 1  # two groups of 6 layers, and the end
        assert default_group(cfg.cache_layers) == 6

        class Pipe:
            def __init__(self, data):
                self.data, self.off = data, 0

            async def readexactly(self, n):
                out = self.data[self.off:self.off + n]
                self.off += n
                return out

        await consume_frames(Pipe(b"".join(chunks)),
                             functools.partial(on, dst), [7, 8, 9],
                             full.shape, str(full.dtype), 6)
    asyncio.run(main())
    np.testing.assert_array_equal(dst.runner.export_blocks([7, 8, 9]), full)


# -- (f) the configuration file ----------------------------------------------

def test_from_hf_config_reads_the_catalog_file_as_a_looped_stack():
    cfg = ModelConfig.from_hf_config(CATALOG, name="ouro")
    assert cfg.architecture == "ouro" and cfg.loop_passes == 4
    assert cfg.post_norms and cfg.residual_f32 and cfg.norm_offset == 0.0
    assert not cfg.qk_norm and not cfg.qkv_bias and not cfg.is_moe
    assert not cfg.tie_word_embeddings and cfg.sliding_window == 0
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (16, 16, 128)
    assert (cfg.intermediate_size, cfg.max_model_len) == (5632, 65536)
    assert cfg.rope_theta == 1000000 and cfg.rms_norm_eps == 1e-6
    # the shipped preset is the same model
    preset = ModelConfig.from_pretrained("ouro-2.6b")
    for f in dataclasses.fields(ModelConfig):
        if f.name != "name":
            assert getattr(preset, f.name) == getattr(cfg, f.name), f.name
    # the architectures key alone says so too
    named = {k: v for k, v in CATALOG.items() if k != "model_type"}
    named["architectures"] = ["OuroForCausalLM"]
    assert ModelConfig.from_hf_config(named).loop_passes == 4
    # every other family runs its layers once (a patterned stack keeps a
    # cache layer for the layers that own keys and values alone)
    assert all(c.loop_passes == 1
               and c.cache_layers == (c.num_attn_layers
                                      if c.has_recurrent_state
                                      else c.num_layers)
               for n, c in MODEL_PRESETS.items() if "ouro" not in n)


@pytest.mark.parametrize("bad,match", [
    ({"early_exit_threshold": 0.9}, "early_exit_threshold"),
    ({"layer_types": ["full_attention"] * 47 + ["sliding_attention"]},
     "layer_types"),
    ({"use_sliding_window": True, "sliding_window": 4096},
     "use_sliding_window"),
])
def test_from_hf_config_refuses_what_it_would_serve_wrongly(bad, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config({**CATALOG, **bad})
    with pytest.raises(ValueError):
        reference.check({**CATALOG, **bad})


# -- (g) what the accountant reckons -----------------------------------------

def test_perf_accounting_counts_the_passes():
    from production_stack_tpu.engine.perf_accounting import (
        PerfAccountant,
        estimate_param_count,
    )

    cfg = ModelConfig.from_pretrained("ouro-2.6b")
    n = estimate_param_count(cfg)
    assert n == pytest.approx(2.668e9, rel=0.001)  # held once
    acct = PerfAccountant(cfg, param_count=n, param_bytes=2 * n)
    stack = 48 * (4 * 2048 ** 2 + 3 * 2048 * 5632)
    # a token multiplies with the layers four times, with the rest once
    assert acct.active_param_count == pytest.approx(n + 3 * stack)
    # a decode step reads 4 x 4.93 GB of layers + embedding and head
    assert acct._weight_bytes(12) == pytest.approx(2 * (n + 3 * stack))
    assert acct._weight_bytes(12) == pytest.approx(20.1e9, rel=0.01)
    assert acct._kv_bytes_per_tok == 1_572_864
    model = acct.snapshot()["model"]
    assert (model["loop_passes"], model["cache_layers"],
            model["kv_bytes_per_token"]) == (4, 192, 1_572_864)
    # a stack run once reckons as before
    once = dataclasses.replace(cfg, loop_passes=1)
    plain = PerfAccountant(once, param_count=n, param_bytes=2 * n)
    assert plain.active_param_count == n and plain._weight_bytes(12) == 2 * n
    assert plain._kv_bytes_per_tok == 1_572_864 // 4


# -- (h) a checkpoint under the tensor names written down from memory --------

def _hf_tensors(cfg, params) -> dict:
    """Our pytree under the HF names engine/weights.py expects of Ouro."""
    E, H, D = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    lay = {k: np.asarray(v) for k, v in params["layers"].items()}
    out = {"model.embed_tokens.weight": np.asarray(params["embed"]),
           "model.norm.weight": np.asarray(params["final_norm"]),
           "lm_head.weight": np.asarray(params["lm_head"]).T,
           # read if present, never evaluated
           "model.early_exit_gate.weight": np.zeros((1, E), np.float32),
           "model.early_exit_gate.bias": np.zeros((1,), np.float32)}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                             ("wv", "v_proj")):
            out[f"{p}self_attn.{theirs}.weight"] = (
                lay[ours][i].transpose(1, 2, 0).reshape(H * D, E))
        out[p + "self_attn.o_proj.weight"] = (
            lay["wo"][i].transpose(2, 0, 1).reshape(E, H * D))
        for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                             ("w_down", "down_proj")):
            out[f"{p}mlp.{theirs}.weight"] = lay[ours][i].T
        for ours, theirs in (("attn_norm", "input_layernorm"),
                             ("post_attn_norm", "input_layernorm_2"),
                             ("mlp_norm", "post_attention_layernorm"),
                             ("post_mlp_norm", "post_attention_layernorm_2")):
            out[f"{p}{theirs}.weight"] = lay[ours][i]
    return {k: np.ascontiguousarray(v) for k, v in out.items()}


def test_a_checkpoint_loads_and_an_unknown_tensor_name_fails_loudly(
        tiny, tmp_path):
    import json

    from safetensors.numpy import save_file

    cfg, mesh, params = tiny
    # distinct norm weights, so that a swapped pair of names would show
    rng = np.random.default_rng(5)
    params = {**params, "layers": {
        k: (jnp.asarray(rng.uniform(0.5, 1.5, v.shape), v.dtype)
            if k.endswith("norm") else v)
        for k, v in params["layers"].items()}}
    hf = {"architectures": ["OuroForCausalLM"], "model_type": "ouro",
          "vocab_size": 512, "hidden_size": 128, "intermediate_size": 256,
          "num_hidden_layers": 3, "num_attention_heads": 4,
          "num_key_value_heads": 4, "head_dim": 32, "rope_theta": 1000000,
          "rms_norm_eps": 1e-6, "max_position_embeddings": 512,
          "tie_word_embeddings": False, "total_ut_steps": 4,
          "early_exit_threshold": 1, "use_sliding_window": False,
          "layer_types": ["full_attention"] * 3}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    tensors = _hf_tensors(cfg, params)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    loaded_cfg = ModelConfig.from_pretrained(str(tmp_path), dtype="float32")
    assert loaded_cfg.loop_passes == 4 and loaded_cfg.post_norms
    loaded = init_or_load(loaded_cfg, mesh)
    toks = np.random.default_rng(6).integers(0, cfg.vocab_size, 20)
    np.testing.assert_array_equal(
        dense_logprobs(loaded_cfg, mesh, loaded, toks),
        dense_logprobs(cfg, mesh, params, toks))
    for broken, match in (
            ({**tensors, "model.layers.0.mlp.extra_proj.weight":
              np.zeros((2, 2), np.float32)}, "does not know.*extra_proj"),
            ({k: v for k, v in tensors.items()
              if "layers.2.input_layernorm_2" not in k},
             "did not find.*layers.2.input_layernorm_2")):
        save_file(broken, str(tmp_path / "model.safetensors"))
        with pytest.raises(ValueError, match=match):
            init_or_load(loaded_cfg, mesh)
