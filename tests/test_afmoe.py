"""afmoe's stack (Arcee Trinity: grouped-query attention in every layer,
three of four within a window that binds, rotated, the fourth over all rows
with nothing rotated, each with QK-norm a head and a sigmoid gate; norms on
both sides of every sublayer; the embedding times sqrt(hidden); a leading
dense layer, then sigmoid-routed experts with a selection bias beside a
shared one, of which the engine holds a share) through the shared stack
walker and the serving engine with its two block pools, against the plain
reference the benchmark uses on the chip (chipbench/reference/afmoe.py), on
seeded random weights at test size (chipbench/tests/configs/tiny-afmoe: 2
periods of (swa, swa, swa, full), window 32 in blocks of 16, 6 query heads
over 2 KV heads of 16, 1 dense layer, 8 experts top-2 of which 4 are held),
float32, CPU.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import shapes_swa
from chipbench.reference import afmoe as reference
from production_stack_tpu.engine import kv_cache as kvmod
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.metrics import EngineStatsCollector
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Scheduler
from production_stack_tpu.engine.sequence import Sequence
from production_stack_tpu.models import llama
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(ROOT, "chipbench", "tests", "configs", "tiny-afmoe")
with open(os.path.join(ROOT, "chipbench", "configs",
                       "trinity-large-preview-ep16-l8", "config.json")) as f:
    CUT = json.load(f)
with open(os.path.join(TINY, "config.json")) as f:
    HF = json.load(f)
# the published file: the cut's, its pattern repeated to 60 layers
PUBLISHED = {
    **{k: v for k, v in CUT.items()
       if k not in ("n_routed_experts_held", "routed_expert_offset")},
    "num_hidden_layers": 60, "layer_types": CUT["layer_types"][:4] * 15,
    "num_dense_layers": 6, "vocab_size": 200192}
CFG = dataclasses.replace(
    ModelConfig.from_hf_config(HF, "tiny-afmoe"), dtype="float32")
# tokens a block of either pool; tokens a ragged step: a 110-token prompt is
# cut into chunks of 48 that straddle the 32-row window
BLOCK, BUDGET, WINDOW = 16, 48, 32
# float32 on the CPU on both sides; the served path differs from the
# reference in the order of its sums only (it read 2e-6)
LOGPROB_TOL = 1e-4
# a planted fault has to read over the tolerance, and not by a hair
FAULT_TOL = 100 * LOGPROB_TOL
# chipbench/run.py's limits, which the bfloat16 path is held to
RUN_TOL, RUN_MEAN_TOL = 0.15, 0.03
F32 = jnp.float32


def one_device():
    return build_mesh(MeshConfig(), devices=jax.devices()[:1])


def make_params(seed=0, cfg=CFG):
    """The stand-in's weights with the norm weights it sets to a constant
    drawn instead, so that a norm left out or misplaced shows."""
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    ks = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 16))

    def drawn(a, gain=1.0):
        return (gain * (1 + 0.3 * jax.random.normal(next(ks), a.shape))
                ).astype(a.dtype)

    for stack in ("layers", "dense"):
        lp = params[stack]
        params[stack] = {
            **lp, "attn_norm": drawn(lp["attn_norm"]),
            "mlp_norm": drawn(lp["mlp_norm"]),
            "post_attn_norm": drawn(lp["post_attn_norm"], 0.25),
            "post_mlp_norm": drawn(lp["post_mlp_norm"], 0.25)}
    gp = params["gqa"]
    params["gqa"] = {**gp, "q_norm": drawn(gp["q_norm"]),
                     "k_norm": drawn(gp["k_norm"])}
    return params


def engine(params=None, slots=4, num_blocks=64, budget=BUDGET, cfg=CFG):
    return LLMEngine(
        EngineConfig(
            model=cfg,
            cache=CacheConfig(block_size=BLOCK, num_blocks=num_blocks),
            scheduler=SchedulerConfig(max_num_seqs=slots,
                                      max_num_batched_tokens=budget),
            mesh=MeshConfig(data=1, tensor=1)),
        mesh=one_device(), params=params)


def serve(eng, prompts, max_tokens=12, after_step=None):
    """{request: (tokens, [logprob of each token])} through the engine."""
    for name, ids in prompts.items():
        eng.add_request(name, prompt_token_ids=list(ids),
                        sampling=SamplingParams(
                            temperature=0.0, max_tokens=max_tokens,
                            logprobs=3, ignore_eos=True))
    toks, lps = {n: [] for n in prompts}, {n: [] for n in prompts}
    while eng.has_unfinished():
        for o in eng.step():
            toks[o.request_id] += o.new_token_ids
            lps[o.request_id] += [lp for lp, _ in o.new_logprobs or ()]
        if after_step is not None:
            after_step(eng)
    return {n: (toks[n], lps[n]) for n in prompts}


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).tolist()


def errors(params, ids, toks, lps, hf=HF):
    """|served - reference| log-probability of every generated token."""
    full = list(ids) + list(toks)
    want = np.asarray(reference.logprobs(hf, params, full[:-1], len(ids) - 1))
    return np.abs(np.asarray([want[j, t] for j, t in enumerate(toks)])
                  - np.asarray(lps))


def dense_errors(params, ids, cfg=CFG, hf=HF):
    """|dense forward - reference| over every row and vocabulary entry."""
    got = llama.forward_dense(cfg, params, jnp.asarray([ids]))
    want = np.asarray(reference.logprobs(hf, params, ids, 0))
    return np.abs(np.asarray(jax.nn.log_softmax(got[0], -1)) - want)


# -- the configuration ---------------------------------------------------------

def test_the_published_file_gives_the_stack_the_issue_describes():
    cfg = ModelConfig.from_hf_config(PUBLISHED, "trinity")
    assert cfg.architecture == "afmoe"
    assert cfg.layer_kinds == ("swa", "swa", "swa", "full") * 15
    assert cfg.rope_kinds == ("swa",) and cfg.sliding_window == 4096
    assert cfg.window_binds and cfg.patterned
    assert not cfg.has_recurrent_state
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.q_per_kv) == (3072, 48, 8, 128, 6)
    assert (cfg.dense_layers, cfg.dense_intermediate_size,
            cfg.intermediate_size, cfg.shared_expert_size) == (
        6, 12288, 3072, 3072)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_scoring,
            cfg.routed_scaling, cfg.norm_topk_prob) == (
        256, 4, "sigmoid", 2.448, True)
    assert cfg.attn_gate and cfg.qk_norm and cfg.qk_norm_kind == "head"
    assert cfg.norms == "both" and cfg.embed_scale
    assert not cfg.tie_word_embeddings and cfg.experts_held == 0
    # the periods that hold dense layers are runs of their own
    period = ("swa", "swa", "swa", "full")
    assert cfg.stack_segments == ((period, 1), (period, 1), (period, 13))
    # two pools: 45 window layers' rows, 15 full layers' rows, 4,096 B a
    # token and layer (8 KV heads x 2 x 128 x bf16)
    assert cfg.kv_pool_shape(1, 16, window=True)[0] == 45
    assert cfg.kv_pool_shape(1, 16)[0] == 15
    assert cfg.window_kv_bytes_per_token == 45 * 4096


def test_the_cut_is_the_issues():
    cfg = ModelConfig.from_hf_config(CUT, "cut")
    assert cfg.layer_kinds == ("swa", "swa", "swa", "full") * 2
    assert (cfg.dense_layers, cfg.num_expert_layers, cfg.experts_held,
            cfg.expert_offset, cfg.vocab_size) == (1, 7, 16, 0, 25024)
    assert (cfg.window_kv_bytes_per_token, cfg.kv_bytes_per_token) == (
        24_576, 8_192)
    # the manifest's slots and budget: 16 x 259 + 512 blocks, 1.83 GB
    blocks = kvmod.window_pool_blocks(cfg, 16, 16, 4096)
    assert blocks == 16 * 259 + 512 == 4656
    assert blocks * 16 * 24_576 == pytest.approx(1.83e9, rel=0.01)
    specs = llama.param_specs(cfg)
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.structure(shapes) == jax.tree.structure(
        jax.tree.map(lambda _: 0, specs,
                     is_leaf=lambda x: isinstance(x, tuple)))
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert total == shapes_swa.total_params(CUT)
    assert 2 * total == pytest.approx(8.29e9, rel=0.01)


@pytest.mark.parametrize("change,match", [
    ({"n_group": 2}, "n_group=2"),
    ({"num_expert_groups": 4}, "no group-limited routing"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"layer_types": ["linear_attention"] + PUBLISHED["layer_types"][1:]},
     "linear_attention"),
    ({"layer_types": ["full_attention"] * 60}, "closed by one"),
    ({"layer_types": PUBLISHED["layer_types"][:59]}, "closed by one"),
    ({"global_attn_every_n_layers": 1}, "closed by one"),
    ({"sliding_window": None}, "sliding_window=None"),
    ({"score_func": "softmax"}, "score_func='softmax'"),
    ({"num_dense_layers": 60}, "leaves no expert layer"),
    ({"attention_bias": True}, "attention_bias"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"n_routed_experts_held": 300}, "is not a share"),
])
def test_what_is_not_computed_is_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config({**PUBLISHED, **change})


def test_what_would_move_or_guess_at_a_pool_is_refused():
    def config(**over):
        kw = {"model": CFG, "mesh": MeshConfig(data=1, tensor=1),
              "cache": CacheConfig(block_size=BLOCK, num_blocks=16),
              "scheduler": SchedulerConfig(max_num_seqs=2,
                                           max_num_batched_tokens=BUDGET)}
        kw.update(over)
        return EngineConfig(**kw)

    for cfg, match in (
            (config(scheduler=SchedulerConfig(
                max_num_seqs=2, max_num_batched_tokens=BUDGET,
                spec_ngram_k=2)), "n-gram"),
            (config(role="prefill"), "P->D"),
            (config(cache=CacheConfig(block_size=BLOCK, num_blocks=16,
                                      host_offload_blocks=4)), "tier"),
            (config(model=dataclasses.replace(CFG, quant="int8")), "quant")):
        with pytest.raises(ValueError, match="blocks of two kinds.*" + match):
            LLMEngine(cfg, mesh=one_device())


def test_a_checkpoint_is_refused_not_guessed_at(tmp_path):
    from production_stack_tpu.engine.weights import load_safetensors

    cfg = dataclasses.replace(CFG, weights_path=str(tmp_path))
    with pytest.raises(ValueError, match="afmoe checkpoint"):
        load_safetensors(cfg, one_device(), None)


def test_the_stand_in_draws_a_selection_bias_and_small_post_norms():
    params = llama.init_params(CFG, jax.random.PRNGKey(3))
    bias = np.asarray(params["layers"]["router_bias"])
    assert bias.dtype == np.float32 and bias.shape == (7, 8)
    assert 0.005 < bias.std() < 0.05
    gain = 0.25 * 16 ** -0.5
    for stack in ("layers", "dense"):
        # the attention sublayers carry the stream, the MLPs stay small
        assert np.allclose(params[stack]["post_attn_norm"], 4 * gain)
        assert np.allclose(params[stack]["post_mlp_norm"], gain)
        assert np.allclose(params[stack]["attn_norm"], 1.0)
    # unit RMS behind the factor sqrt(hidden)
    x = llama.embed_tokens(CFG, params, jnp.arange(256))
    assert float(jnp.sqrt(jnp.mean(x * x))) == pytest.approx(1.0, rel=0.05)
    # every other family's selection bias stays zeros
    pangu = ModelConfig.from_pretrained("tiny-pangu")
    assert not np.asarray(llama.init_params(
        pangu, jax.random.PRNGKey(0))["layers"]["router_bias"]).any()


# -- the dense forward and the served path against the reference --------------

@pytest.mark.parametrize("seed", [0, 1])
def test_dense_forward_matches_the_reference(seed):
    err = dense_errors(make_params(seed), prompt(130, seed))
    assert err.max() < LOGPROB_TOL, err.max()


def test_more_than_one_leading_dense_layer_matches_the_reference():
    hf = {**HF, "num_dense_layers": 5}  # a second period that is half dense
    cfg = dataclasses.replace(ModelConfig.from_hf_config(hf), dtype="float32")
    assert len(cfg.stack_segments) == 2 and cfg.num_expert_layers == 3
    err = dense_errors(make_params(0, cfg), prompt(70, 5), cfg, hf)
    assert err.max() < LOGPROB_TOL, err.max()


@pytest.fixture(scope="module")
def served():
    """A 110-token prompt (three chunks of the 48-token budget, each
    straddling the 32-row window) and a 21-token one interleaved in one
    stream, then 24 decode steps through both pools, the two slots at
    different positions; the long one gives window blocks back all along."""
    eng = engine(make_params(0))
    prompts = {"long": prompt(110, 10), "short": prompt(21, 11)}
    return eng, prompts, serve(eng, prompts, max_tokens=24)


@pytest.mark.parametrize("name", ["long", "short"])
def test_served_logprobs_match_the_reference_at_every_row(served, name):
    eng, prompts, out = served
    toks, lps = out[name]
    err = errors(eng.runner.params, prompts[name], toks, lps)
    assert len(toks) == 24 and err.max() < LOGPROB_TOL, err
    assert eng.decode_dispatches > 0 and eng.ragged_dispatches > 0


def test_served_in_bfloat16_reads_under_the_runs_limits():
    """The same flow in the configuration's own types (bfloat16 weights,
    activations and pools; float32 router, softmax state and norms) against
    the float32 reference on the same weights: under chipbench/run.py's
    limits, as the cell's probe has to read on the chip."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    eng = engine(llama.init_params(cfg, jax.random.PRNGKey(0)), cfg=cfg)
    prompts = {"long": prompt(110, 10), "short": prompt(21, 11)}
    out = serve(eng, prompts, max_tokens=24)
    err = np.concatenate([errors(eng.runner.params, prompts[n], *out[n])
                          for n in prompts])
    assert err.max() < RUN_TOL and err.mean() < RUN_MEAN_TOL, (
        err.max(), err.mean())
    assert eng.runner.kv["win"].dtype == jnp.bfloat16


def test_the_cache_is_two_pools_and_no_state(served):
    eng = served[0]
    kv = eng.runner.kv
    assert set(kv) == {"kv", "win"}
    # per the rule: 4 slots x (32 / 16 + 3) + (48 + 32) / 16 blocks
    assert eng.runner.window_blocks == 4 * 5 + 5 == 25
    assert kv["win"].shape == (6, 25, BLOCK, 2 * 2, 16)
    assert kv["kv"].shape == (2, 64, BLOCK, 2 * 2, 16)
    assert eng.recurrent is None


def test_both_pools_are_whole_again_when_the_requests_end(served):
    eng = served[0]
    assert eng.scheduler.allocator.num_free_blocks == 64
    assert eng.scheduler.window_allocator.num_free_blocks == 25


def test_the_counters_say_what_ran(served):
    eng, prompts, _ = served
    s = eng.stats()
    assert s["prefix_lookups_bypassed_total"] == 2
    assert s["gpu_prefix_cache_hits_total"] == 0
    assert s["window_kv_block_waits_total"] == 0
    # the long request ran to 110 + 24 rows: the blocks wholly below row
    # 133 - 31 went back while it lived; the short one's 45 rows: none
    assert s["window_kv_blocks_released_total"] == (133 - 31) // BLOCK
    by = s["window_attn_by_program"]
    for program in ("ragged", "decode"):
        b = by[program]
        assert 0 < b["read_tokens"] <= b["context_tokens"]
        assert b["full_read_tokens"] * 3 == b["context_tokens"]  # 2 : 6
        assert 0 < b["rows_needed"] <= b["read_tokens"] + b["full_read_tokens"]
    assert (by["ragged"]["context_tokens"] + by["decode"]["context_tokens"]
            == s["window_attn_context_tokens_total"])
    assert (by["ragged"]["read_tokens"] + by["decode"]["read_tokens"]
            == s["window_attn_read_tokens_total"])
    # a decode row scores one pair a key it sees: its rows are its pairs
    assert by["decode"]["pairs_needed"] == by["decode"]["rows_needed"]
    # the prompts' pairs, exactly: six layers within the window, two not
    def pairs(n, w):
        return sum(min(t + 1, w) for t in range(n))
    assert by["ragged"]["pairs_needed"] == sum(
        6 * pairs(len(p), WINDOW) + 2 * pairs(len(p), 1 << 30)
        for p in prompts.values())
    assert not [k for k in s if k.startswith(("kda_", "mamba_", "ssd_",
                                              "gdn_", "recurrent_"))]
    assert s["moe_held_pairs_total"] > 0
    text = "".join(
        f"{m.name} {[x.value for x in m.samples]}\n"
        for m in EngineStatsCollector(eng, "tiny").collect())
    for name in ("vllm:window_attn_context_tokens",
                 "vllm:window_attn_read_tokens",
                 "vllm:window_kv_blocks_total", "vllm:window_kv_blocks_free",
                 "vllm:window_kv_blocks_released",
                 "vllm:window_kv_block_waits",
                 "vllm:prefix_lookups_bypassed",
                 *(f"vllm:{p}_{k}" for p in ("ragged", "decode")
                   for k in ("window_attn_context_tokens",
                             "window_attn_read_tokens",
                             "full_attn_read_tokens", "attn_rows_needed",
                             "attn_pairs_needed"))):
        assert text.count(name + " ") == 1, name
    assert "vllm:recurrent_state_bytes" not in text


def test_a_steps_launch_says_how_many_layers_of_each_kind(served):
    assert served[0].runner._launch_attrs == {"layers_swa": 6,
                                              "layers_full": 2}
    assert engine_of("tiny-llama").runner._launch_attrs == {}


def engine_of(preset):
    return LLMEngine(
        EngineConfig(model=ModelConfig.from_pretrained(preset),
                     cache=CacheConfig(block_size=BLOCK, num_blocks=16),
                     scheduler=SchedulerConfig(max_num_seqs=2,
                                               max_num_batched_tokens=32),
                     mesh=MeshConfig(data=1, tensor=1)), mesh=one_device())


# -- two pools: no wait, nothing read after it is given back, both freed ------

def poison_what_is_given_back(eng):
    """Every window block that goes back to the pool is overwritten on the
    device with values no softmax survives: a program that reads a row it
    gave back reads this."""
    alloc = eng.scheduler.window_allocator
    real, freed = alloc.free_blocks, []

    def free_blocks(ids):
        freed.extend(ids)
        real(ids)

    alloc.free_blocks = free_blocks

    def after_step(eng):
        if freed:
            kv = eng.runner.kv
            eng.runner.kv = {**kv, "win": kv["win"].at[
                :, np.asarray(freed)].set(50.0)}
            freed.clear()
    return after_step


def test_no_row_is_read_after_its_block_went_back():
    eng = engine(make_params(0), slots=2)
    ids = prompt(110, 10)
    toks, lps = serve(eng, {"a": ids}, max_tokens=24,
                      after_step=poison_what_is_given_back(eng))["a"]
    assert eng.scheduler.window_blocks_released == 6
    assert errors(eng.runner.params, ids, toks, lps).max() < LOGPROB_TOL


def test_a_full_house_never_waits_for_a_window_block():
    """Four slots, every one a prompt of several windows and a long answer,
    two more requests queued behind them: the pool sized by the rule is
    never dry, and is whole again at the end."""
    eng = engine(make_params(0), slots=4, num_blocks=96)
    prompts = {f"r{i}": prompt(90 + 17 * i, 20 + i) for i in range(6)}
    out = serve(eng, prompts, max_tokens=40)
    sched = eng.scheduler
    assert sched.window_block_waits == 0
    assert sched.window_allocator.num_free_blocks == 25
    assert sched.allocator.num_free_blocks == 96
    for name in ("r0", "r5"):
        assert errors(eng.runner.params, prompts[name],
                      *out[name]).max() < LOGPROB_TOL


def test_a_dry_window_pool_is_counted_as_a_wait():
    sched = Scheduler(SchedulerConfig(max_num_seqs=2,
                                      max_num_batched_tokens=BUDGET),
                      CacheConfig(block_size=BLOCK), num_blocks=64,
                      window=WINDOW, window_blocks=2)
    assert sched.bypass_prefix and not sched.recurrent_state
    sched.add(Sequence("a", prompt(60), SamplingParams(max_tokens=4)))
    out = sched.schedule()
    assert not out.prefills and sched.window_block_waits == 1


def test_preemption_frees_both_pools_and_recomputes_exactly():
    """A pool of the full layers too small for two growing sequences: the
    younger is preempted, gives back its blocks of BOTH kinds, and is
    recomputed from position 0 to the same log-probabilities."""
    eng = engine(make_params(0), slots=2, num_blocks=12)
    prompts = {"a": prompt(75, 30), "b": prompt(70, 31)}
    out = serve(eng, prompts, max_tokens=40)
    assert eng.stats()["num_preemptions_total"] > 0
    sched = eng.scheduler
    assert sched.allocator.num_free_blocks == 12
    assert sched.window_allocator.num_free_blocks == eng.runner.window_blocks
    for name in prompts:
        assert errors(eng.runner.params, prompts[name],
                      *out[name]).max() < LOGPROB_TOL


def test_a_window_pool_that_starves_the_other_is_refused_at_start():
    cut = ModelConfig.from_hf_config(CUT, "trinity-cut")
    cut = dataclasses.replace(cut, max_model_len=20480)
    cache = CacheConfig(block_size=16)
    # a v5e after 8.29 GB of weights, a ragged step's temporaries and the
    # 2 GiB reserve: what both pools share
    shared = int(15.75 * 2 ** 30 - 8.29e9 - 1.2e9 - 2 * 2 ** 30)

    def free(slots):
        sched = SchedulerConfig(max_num_seqs=slots,
                                max_num_batched_tokens=4096)
        window = (kvmod.window_pool_blocks(cut, 16, slots, 4096) * 16
                  * cut.window_kv_bytes_per_token)
        return sched, shared - window

    kvmod.refuse_if_window_pool_starves(cut, cache, *free(16))  # starts
    with pytest.raises(ValueError) as refusal:
        kvmod.refuse_if_window_pool_starves(cut, cache, *free(64))
    said = str(refusal.value)
    assert "trinity-cut: at --max-num-seqs 64" in said
    assert "takes 6.72 GB" in said and "24576 B a row" in said
    assert "--max-model-len 20480" in said
    fit = int(said.rsplit("--max-num-seqs ", 1)[1].split()[0])
    assert 16 <= fit < 64
    kvmod.refuse_if_window_pool_starves(cut, cache, *free(fit))
    with pytest.raises(ValueError):
        kvmod.refuse_if_window_pool_starves(cut, cache, *free(fit + 1))
    # Phi-4-mini-flash at the engine's 64 slots starts as it does
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "phi-4-mini-flash-reasoning",
                           "config.json")) as f:
        phi4 = dataclasses.replace(ModelConfig.from_hf_config(json.load(f)),
                                   max_model_len=8192)
    sched = SchedulerConfig(max_num_seqs=64, max_num_batched_tokens=2048)
    kvmod.refuse_if_window_pool_starves(
        phi4, cache, sched, int(15.75 * 2 ** 30 - 7.71e9 - 3e9
                                - 2 * 2 ** 30 - 1.89e9))
    # no window that binds: nothing to refuse, whatever is left
    kvmod.refuse_if_window_pool_starves(
        ModelConfig.from_pretrained("tiny-llama"), cache, sched, -1)


def test_the_runner_refuses_where_it_sizes_the_pool():
    """Through the runner's own sizing (the CPU sizes against a v5e that
    holds the weights alone): a window of 8,192 rows at 1,024 slots."""
    cfg = dataclasses.replace(CFG, sliding_window=8192, max_model_len=16384)
    with pytest.raises(ValueError, match="--max-num-seqs 1024.*would fit"):
        LLMEngine(
            EngineConfig(model=cfg, cache=CacheConfig(block_size=BLOCK),
                         scheduler=SchedulerConfig(
                             max_num_seqs=1024, max_num_batched_tokens=2048),
                         mesh=MeshConfig(data=1, tensor=1)),
            mesh=one_device(), params=make_params(0))


# -- the share: the sixteen chips' parts add up to the uncut layer ------------

def test_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer():
    hf = {**HF, "num_experts": 16, "n_routed_experts_held": 16,
          "num_experts_per_tok": 4}
    whole = dataclasses.replace(ModelConfig.from_hf_config(hf),
                                dtype="float32")
    assert whole.experts_held == 0
    params = llama.init_params(whole, jax.random.PRNGKey(7))
    lp = jax.tree.map(lambda a: a[2], params["layers"])  # one sparse layer
    experts = {k: params["layers"][k] for k in llama._EXPERT_WEIGHTS}
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 40, whole.hidden_size))
    uncut, hist = llama._sparse_block(whole, lp, experts, 2, x, None)
    assert int(hist[:16].sum()) == 40 * 4
    shared = llama._mlp(whole, {"w_gate": lp["shared_gate"],
                                "w_up": lp["shared_up"],
                                "w_down": lp["shared_down"]}, x)
    parts, pairs = shared, 0
    for chip in range(16):
        share = dataclasses.replace(whole, experts_held=1, expert_offset=chip)
        held = {k: v[:, chip:chip + 1] for k, v in experts.items()}
        routed, hist = llama._moe_mlp(share, lp["router"], held, 2, x,
                                      bias=lp["router_bias"])
        parts = parts + routed
        pairs += int(hist[0])
        # the plain reference, given the same share, leaves out the same
        want = reference._sparse(
            x[0], {**lp, **{k: v[2, chip:chip + 1]
                            for k, v in experts.items()}},
            top_k=4, renormalise=True, scaling=whole.routed_scaling,
            first=chip, held=1)
        assert np.abs(np.asarray(routed[0] + shared[0] - want)).max() < 1e-5
    assert pairs == 40 * 4  # every pair fell on exactly one chip
    assert np.abs(np.asarray(parts - uncut)).max() < 1e-5


# -- planted faults: each reads over the tolerance, and not by a hair ---------

class _Over:
    """A module with some of its names replaced."""

    def __init__(self, real, **over):
        self._real, self._over = real, over

    def __getattr__(self, name):
        return self._over.get(name) or getattr(self._real, name)


def _bias_weighs(monkeypatch):
    """The experts weighed by score + bias, as they are chosen."""
    chosen = []

    def top_k(x, k):
        values, idx = jax.lax.top_k(x, k)
        chosen.append(values)
        return values, idx

    def take_along_axis(a, idx, axis):
        return chosen.pop() if chosen else jnp.take_along_axis(a, idx, axis)

    monkeypatch.setattr(llama, "lax", _Over(jax.lax, top_k=top_k))
    monkeypatch.setattr(llama, "jnp", _Over(
        jnp, take_along_axis=take_along_axis))


FAULTS = {
    "the window ignored": {
        "cfg": dataclasses.replace(CFG, sliding_window=4096)},
    "rope on a full layer": {
        "cfg": dataclasses.replace(CFG, rope_kinds=("swa", "full"))},
    "no rope on a window layer": {
        "cfg": dataclasses.replace(CFG, rope_kinds=())},
    "the gate dropped": {"cfg": dataclasses.replace(CFG, attn_gate=False)},
    "route_scale dropped": {
        "cfg": dataclasses.replace(CFG, routed_scaling=1.0)},
    "the bias weighing and not only choosing": {"patch": _bias_weighs},
    "the embedding factor dropped": {
        "cfg": dataclasses.replace(CFG, embed_scale=False)},
    "the post-norms dropped": {"cfg": dataclasses.replace(CFG, norms="pre")},
    "QK-norm left out": {"cfg": dataclasses.replace(CFG, qk_norm=False)},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_reads_over_the_tolerance(monkeypatch, fault):
    plan = FAULTS[fault]
    params, ids = make_params(0), prompt(130, 2)
    if "patch" in plan:
        plan["patch"](monkeypatch)
    err = dense_errors(params, ids, plan.get("cfg", CFG))
    assert err.max() > FAULT_TOL, (fault, err.max())


def test_fault_a_window_block_given_back_one_early(monkeypatch):
    """``_trim_window`` counting the window one block short: the rows it
    gives back are still seen, and read what was written over them."""
    real = Scheduler._trim_window

    def early(self, seq):
        self.window -= BLOCK
        try:
            real(self, seq)
        finally:
            self.window += BLOCK

    monkeypatch.setattr(Scheduler, "_trim_window", early)
    eng = engine(make_params(0), slots=2)
    ids = prompt(110, 10)
    toks, lps = serve(eng, {"a": ids}, max_tokens=24,
                      after_step=poison_what_is_given_back(eng))["a"]
    assert errors(eng.runner.params, ids, toks, lps).max() > FAULT_TOL


def test_the_reference_without_the_window_is_another_result():
    """``chipbench/reference/control_window.py`` at test size: the window
    layers of the reference seeing every row, against the reference: over
    the run's own limits, as the probe has to find it on the chip."""
    params, ids = make_params(0), prompt(130, 3)
    want = np.asarray(reference.logprobs(HF, params, ids, 0))
    got = np.asarray(reference.logprobs(HF, params, ids, 0,
                                        ignore_window=True))
    assert np.abs(got - want)[:WINDOW].max() < 1e-5  # inside the window
    assert np.abs(got - want).max() > RUN_TOL


def test_the_reference_in_lower_precision_is_another_result():
    """``chipbench/reference/control.py bf16_state`` at test size: the
    softmax's state, the norms' statistics and the rotation's angles kept
    in bfloat16. Over the first rows the control reads what a rounding
    reads (0.004); an angle's error grows with the position (a bfloat16
    holds no odd position past 256), the window layers' angles are soon
    off by radians and the run's limits find it (0.17 mean past row
    300)."""
    params, ids = make_params(0), prompt(400, 4)
    want = np.asarray(reference.logprobs(HF, params, ids, 0))
    got = np.asarray(reference.logprobs(HF, params, ids, 0,
                                        state_dtype="bfloat16"))
    err = np.abs(got - want)
    assert err[:WINDOW].mean() < RUN_MEAN_TOL / 3
    assert err[300:].max() > RUN_TOL and err[300:].mean() > RUN_MEAN_TOL


# -- the benchmark's arithmetic ------------------------------------------------

def test_shapes_swa_counts_the_published_model():
    s = shapes_swa
    assert s.attn_params(PUBLISHED) == 62_914_560 + 2 * 128
    assert s.dense_mlp_params(PUBLISHED) == 113_246_208
    assert s.expert_params(PUBLISHED) == 28_311_552
    assert s.router_params(PUBLISHED) == 786_432 + 256
    assert s.kv_bytes_per_token_layer(PUBLISHED) == 4096
    # 400 B: the card's count
    assert s.total_params(PUBLISHED) == pytest.approx(400e9, rel=0.02)
    # the issue's table: a decode step at 16 slots x 12.5 k tokens, ~4
    # experts touched a layer
    reads = s.row_reads(CUT, window_rows=16 * 4096, full_rows=16 * 12_500)
    step = s.decode_step_bytes(CUT, 4.0, sum(reads.values()))
    assert step["total"] == pytest.approx(6.5e9, rel=0.05)
    row = s.kv_bytes_per_token_layer(CUT)
    assert reads["window_rows"] * row / step["total"] == pytest.approx(
        0.25, abs=0.03)
    assert reads["full_rows"] * row / step["total"] == pytest.approx(
        0.25, abs=0.03)
    assert step["experts"] / step["total"] == pytest.approx(0.22, abs=0.03)
    assert step["weights"] / step["total"] == pytest.approx(0.28, abs=0.03)
    # attention's least time is its rows' bytes in a decode step, its
    # pairs' operations in a long prompt's chunk
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert s.attn_floor_s(CUT, rows=1e6, pairs=1e6, query_rows=16,
                          peaks=peaks)[1] == "bytes"
    assert s.attn_floor_s(CUT, rows=8 * 8192, pairs=8 * 4096 * 6144,
                          query_rows=8 * 4096, peaks=peaks)[1] == "flops"
