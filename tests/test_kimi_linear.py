"""Kimi-Linear's stack (KDA layers and no-rope latent attention in one
patterned stack, a leading dense layer inside the first period, a short
last period, per-slot recurrent state beside a latent paged pool, a share
of the routed experts) through the shared stack walker and the serving
engine, against the plain reference the benchmark uses on the chip
(chipbench/reference/kimi_linear.py: a loop over tokens for the
recurrence, the PUBLISHED, expanded form of the attention), on seeded
random weights at test size: the ``tiny-kimi-linear`` preset, 7 layers =
(dense kda, kda, kda, mla) + (kda, kda, mla), hidden 128, 4 heads, a 32 +
16-value latent row in 128 lanes, 4 of 8 routed experts held
(chipbench/tests/configs/tiny-kimi-linear).
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import kimi_linear as reference
from production_stack_tpu.engine.config import (
    MODEL_PRESETS,
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.kv_cache import init_kv_cache
from production_stack_tpu.engine.metrics import EngineStatsCollector
from production_stack_tpu.engine.model_runner import ModelRunner
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.weights import load_safetensors
from production_stack_tpu.models import llama
from production_stack_tpu.ops import kda
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "chipbench", "tests", "configs",
                       "tiny-kimi-linear", "config.json")) as f:
    HF = json.load(f)
# float32 on the CPU on both sides. The served path differs from the
# reference in the order of its sums and in its FORM: the recurrence runs
# in blocks and from a stored state where the reference takes one row
# after the other, and the attention scores absorbed ((W_UK^T q) . c where
# the reference has q . (W_UK c)), which moves a float32 log-probability
# in the sixth digit: they agree to ~3e-6 here, and the tolerance leaves
# that an order of magnitude. A state, a latent row or a router in
# bfloat16 reads tens of times over it.
LOGPROB_TOL = 3e-5
# chipbench/run.py's limits, which the cell's `correct` is held to
CELL_TOL, CELL_MEAN_TOL = 0.15, 0.03
BUDGET = 32  # tokens a ragged step: the 70-token prompt takes three chunks
STEPS = 20   # decode steps from position 70, across the block boundary at 80
PROBE_TOP = 5  # chipbench/run.py asks the probe for as many


def tiny_cfg(**over) -> ModelConfig:
    return dataclasses.replace(
        ModelConfig.from_hf_config(HF, "tiny-kimi-linear"), dtype="float32",
        **over)


def deeper(hf=HF) -> dict:
    """The same widths at 15 layers: the dense period, TWO full periods
    (one scan over them), the short one."""
    lin = {**hf["linear_attn_config"],
           "full_attn_layers": [4, 8, 12, 15],
           "kda_layers": [l for l in range(1, 16) if l not in (4, 8, 12, 15)]}
    return {**hf, "num_hidden_layers": 15, "linear_attn_config": lin}


def one_device():
    return build_mesh(MeshConfig(), devices=jax.devices()[:1])


def engine_config(cfg=None, num_blocks=64, slots=4, **over) -> EngineConfig:
    return EngineConfig(
        model=cfg or tiny_cfg(),
        cache=CacheConfig(block_size=16, num_blocks=num_blocks),
        scheduler=SchedulerConfig(max_num_seqs=slots,
                                  max_num_batched_tokens=BUDGET),
        mesh=MeshConfig(data=1, tensor=1), **over)


def engine(cfg=None, params=None, **kw) -> LLMEngine:
    return LLMEngine(engine_config(cfg, **kw), mesh=one_device(),
                     params=params)


def serve(eng, prompts, max_tokens=STEPS):
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
    return {n: (toks[n], lps[n]) for n in prompts}


def errors(hf, params, prompt, toks, lps, **control):
    """|served - reference| log-probability of each generated token."""
    ids = list(prompt) + toks
    want = np.asarray(reference.logprobs(hf, params, ids[:-1],
                                         len(prompt) - 1, **control))
    return np.abs(np.array([want[j, t] for j, t in enumerate(toks)])
                  - np.array(lps))


def over_a_limit(err) -> bool:
    """`correct` would be false: one of the cell's two limits is passed."""
    return bool(err.max() > CELL_TOL or err.mean() > CELL_MEAN_TOL)


def _ids(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 512, n)]


# "long" and "short" decode side by side in two slots at different lengths
PROMPTS = {"long": _ids(0, 70), "short": _ids(1, 7), "mid": _ids(2, 23)}


@pytest.fixture(scope="module")
def served():
    eng = engine()
    return eng, serve(eng, PROMPTS)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(tiny_cfg(), jax.random.PRNGKey(3))


# -- (a) the dense forward against the reference -------------------------------

@pytest.mark.parametrize("hf", [HF, deeper()], ids=["7-layers", "15-layers"])
def test_the_dense_forward_equals_the_reference(hf):
    """The stack walker over (dense kda, kda, kda, mla) [+ two scanned full
    periods] + (kda, kda, mla), whole sequences from an empty past."""
    cfg = dataclasses.replace(ModelConfig.from_hf_config(hf, "tiny"),
                              dtype="float32")
    assert [n for _, n in cfg.stack_segments] == (
        [1, 1] if hf is HF else [1, 2, 1])
    p = llama.init_params(cfg, jax.random.PRNGKey(5))
    ids = PROMPTS["long"]
    got = jax.nn.log_softmax(
        llama.forward_dense(cfg, p, jnp.asarray([ids]))[0], -1)
    want = reference.logprobs(hf, p, ids, 0)
    assert float(jnp.abs(got - want).max()) < LOGPROB_TOL
    # the histogram has a row a SPARSE layer: the dense layer routes nothing
    pos = jnp.arange(len(ids), dtype=jnp.int32)[None]
    _, _, hist = llama.forward_tokens(
        cfg, p, jnp.asarray([ids]), pos, llama.dense_attend(cfg), None,
        moe_hist=True)
    assert hist.shape == (cfg.num_layers - 1, 4 + 2)
    assert int(hist[:, :5].sum()) == (cfg.num_layers - 1) * len(ids) * 2


# -- (b) the served path against the reference ---------------------------------

@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_served_logprobs_match_the_reference(served, name):
    """Chunked ragged prefill (the long prompt cut in three chunks: the
    later ones continue the first one's state and conv tail in the KDA
    layers and read its latent rows from the pool in the MLA layers), then
    decode through the paged latent pool and the slot state, the slots at
    different lengths, against the reference's full forward."""
    eng, out = served
    toks, lps = out[name]
    err = errors(HF, eng.runner.params, PROMPTS[name], toks, lps)
    assert len(toks) == STEPS and err.max() < LOGPROB_TOL, err


@pytest.mark.parametrize("how", [
    {}, dict(expand_rows=16, expand_q_rows=8, expand_windows=2,
             expand_heads=2)], ids=["absorbed", "long-spans-expanded"])
def test_served_through_the_kernels_matches_the_reference(served,
                                                          monkeypatch, how):
    """The same engine with the Pallas kernels (interpreted) in both step
    programs: the latent attention kernel over the pool that rides the
    cache pytree, the KDA span and decode kernels over the slot state.
    With the latent kernel's crossover at 16 rows (its own is 256: nothing
    here is that long) the prompt's chunks of 16 tokens or more are scored
    in the published form, here without a rotated part."""
    from production_stack_tpu.ops import kda_pallas
    from production_stack_tpu.ops import latent_paged_attention_pallas as lat

    first, _ = served
    monkeypatch.setattr(
        lat, "latent_paged_attention_pallas",
        functools.partial(lat.latent_paged_attention_pallas,
                          interpret=True, q_tile=4, windows=2, **how))
    # the decode program's call: the kernel's decode body
    monkeypatch.setattr(
        lat, "latent_decode_attention_pallas",
        functools.partial(lat.latent_decode_attention_pallas,
                          interpret=True, windows=2))
    for name in ("kda_ragged", "kda_decode_step"):
        monkeypatch.setattr(kda_pallas, name, functools.partial(
            getattr(kda_pallas, name), interpret=True))
    eng = engine(params=first.runner.params)
    eng.runner.use_pallas = True  # read where the programs are traced
    prompts = {"long": PROMPTS["long"], "short": PROMPTS["short"]}
    out = serve(eng, prompts, max_tokens=6)
    for name, (toks, lps) in out.items():
        err = errors(HF, eng.runner.params, prompts[name], toks, lps)
        assert len(toks) == 6 and err.max() < LOGPROB_TOL, (name, err)
        assert toks == served[1][name][0][:6]


def test_the_cache_is_a_latent_pool_beside_per_slot_state():
    """One cache pytree: the latent pool of the MLA layers alone (a cache
    layer an MLA layer, one row of whole lane tiles a token) and the KDA
    layers' state and conv tails a slot; the byte counts hold for both."""
    cfg = tiny_cfg()
    assert cfg.is_latent and cfg.has_recurrent_state
    assert (cfg.cache_layers, cfg.num_kda_layers) == (2, 5)
    caches = init_kv_cache(cfg, CacheConfig(block_size=16, num_blocks=8),
                           one_device(), slots=3)
    assert caches["kv"].shape == cfg.kv_pool_shape(8, 16) == (2, 8, 16, 128)
    assert caches["state"].shape == (5, 3, 4, 16, 16)
    assert caches["conv"].shape == (5, 3, 3, 3 * 4 * 16)
    assert cfg.kv_bytes_per_token == 2 * 128 * 4
    assert cfg.recurrent_state_bytes(3) == (
        caches["state"].nbytes + caches["conv"].nbytes)
    real = ModelConfig.from_hf_config(CATALOG, "kimi")
    assert (real.latent_width, real.latent_lanes) == (576, 640)
    assert real.kv_bytes_per_token == 7 * 640 * 2 == 8960
    assert real.kv_pool_shape(100, 16) == (7, 100, 16, 640)
    assert real.recurrent_state_bytes(64) == 64 * 20 * (
        32 * 128 * 128 * 4 + 3 * 3 * 32 * 128 * 2) == 2_778_726_400


def test_both_caches_counters_are_live_in_one_engine(served):
    """LatentCounters count by the 2 cache layers (not the 7 layers),
    RecurrentCounters by the 5 KDA layers; prefix lookups answer "miss";
    the two gauges say what each cache holds."""
    eng, _ = served
    s = eng.stats()
    assert eng.latent.cache_layers == 2 and eng.recurrent.kda_layers == 5
    tokens = sum(len(p) for p in PROMPTS.values())
    assert s["kda_chunk_tokens_total"] == tokens
    # a prompt token is a query token once a cache layer
    assert s["mla_query_tokens_total"]["ragged"] >= 2 * tokens
    assert s["mla_query_tokens_total"]["decode"] % 2 == 0
    assert s["prefix_lookups_bypassed_total"] == len(PROMPTS)
    assert s["gpu_prefix_cache_hits_total"] == 0
    assert s["recurrent_state_bytes"] == eng.config.model.recurrent_state_bytes(4)
    assert s["kv_pool_bytes"] == 64 * 16 * 2 * 128 * 4
    text = "\n".join(
        f"{m.name} {smp.value}" for m in EngineStatsCollector(
            eng, "tiny-kimi-linear").collect() for smp in m.samples)
    for name in ("vllm:recurrent_state_bytes", "vllm:kv_pool_bytes",
                 "vllm:mla_scored_pairs", "vllm:kda_decode_calls"):
        assert name in text, name


# -- (c) the shares add up ------------------------------------------------------

@pytest.mark.parametrize("kind", ["kda", "mla"])
def test_the_shares_add_up_to_the_uncut_references_layer_output(kind):
    """One layer, a KDA and an MLA one. What the two chips that share it
    compute (each its 4 of the 8 routed experts' pairs, through the
    program's block at its offset), with what every chip computes alike
    (the mixer, the shared expert) counted once, is the uncut reference's
    layer output."""
    whole_hf = {k: v for k, v in HF.items()
                if k not in ("n_routed_experts_held", "routed_expert_offset")}
    whole = dataclasses.replace(ModelConfig.from_hf_config(whole_hf, "tiny"),
                                dtype="float32")
    full = llama.init_params(whole, jax.random.PRNGKey(11))
    eps = whole.rms_norm_eps
    x = jax.random.normal(jax.random.PRNGKey(12), (1, 24, whole.hidden_size))
    lp = {k: v[0] for k, v in full["layers"].items()}
    mp = jax.tree.map(lambda a: a[1], full[kind])
    normed = llama.rms_norm(x, lp["attn_norm"], eps)
    if kind == "kda":
        o, _ = llama._kda_mixer(whole, mp, normed, llama._recur_dense, None, 1)
    else:
        o, _ = llama._mla_mixer(whole, mp, normed, None,
                                llama.dense_attend(whole), None, 1)
    h = x + o
    normed2 = llama.rms_norm(h, lp["mlp_norm"], eps)
    routed = 0
    for share in range(2):
        c = dataclasses.replace(whole, experts_held=4, expert_offset=4 * share)
        experts = {k: full["layers"][k][:, 4 * share:4 * share + 4]
                   for k in llama._EXPERT_WEIGHTS}
        out, hist = llama._moe_mlp(c, lp["router"], experts, 0, normed2,
                                   bias=lp["router_bias"])
        routed = routed + out
        assert hist.shape == (4 + 2,) and int(hist[:5].sum()) == 24 * 2
    shared = llama._mlp(whole, {"w_gate": lp["shared_gate"],
                                "w_up": lp["shared_up"],
                                "w_down": lp["shared_down"]}, normed2)
    with jax.default_matmul_precision("highest"):
        u = reference._rms(x[0], lp["attn_norm"], eps)
        hr = x[0] + (reference._kda(u, mp, eps=eps, neg_eigval=False)
                     if kind == "kda" else reference._mla(u, mp, eps=eps))
        want = hr + reference._sparse(
            reference._rms(hr, lp["mlp_norm"], eps), lp, top_k=2,
            renormalise=True, scaling=2.446, first=0, held=8)
    np.testing.assert_allclose((h + routed + shared)[0], want, atol=2e-5)
    assert float(jnp.abs(want - x[0]).max()) > 0.1


# -- (d) planted faults ----------------------------------------------------------

def _dense_positions(x):
    return jnp.broadcast_to(jnp.arange(x.shape[-2], dtype=jnp.int32),
                            x.shape[:-1])


def _faulty_mixer(fault, cfg, lp, x, positions, attend, caches, cache_layer):
    """models/llama.py _mla_mixer with one fault planted."""
    if fault == "k_pe rotated":
        real = llama.apply_rope
        llama.apply_rope = lambda y, *a: (  # the one shared key alone
            real(y, *a) if y.shape[-2] == 1 else y)
        try:
            return llama_mla(dataclasses.replace(cfg, mla_rope=True), lp, x,
                             _dense_positions(x), attend, caches, cache_layer)
        finally:
            llama.apply_rope = real
    if fault == "the query's two parts swapped":
        # a head's 48 values read as [16 shared-key | 32 unrotated-key]
        H, nope = cfg.num_heads, cfg.qk_nope_head_dim
        w = jnp.concatenate([lp["wq_nope"].reshape(-1, H, nope),
                             lp["wq_rope"].reshape(-1, H, 48 - nope)], -1)
        lp = {**lp, "wq_rope": w[..., :48 - nope].reshape(-1, H * (48 - nope)),
              "wq_nope": w[..., 48 - nope:].reshape(-1, H * nope)}
    return llama_mla(cfg, lp, x, positions, attend, caches, cache_layer)


llama_mla = llama._mla_mixer


def probe_errors(cfg, served_params, prompt, hf=HF, ref_params=None):
    """What chipbench/reference/compare.py measures of a run's probe, and
    no more: the program (its dense forward: the same mixers and stack
    the step programs run) decodes STEPS tokens greedily after the
    prompt, and each token's log-probability and those of its five most
    likely tokens are held against the reference's for the same token
    sequence."""
    logprobs = jax.jit(lambda ids: jax.nn.log_softmax(
        llama.forward_dense(cfg, served_params, ids[None])[0], -1))
    ids = list(prompt) + [0] * STEPS  # causal: what follows moves nothing
    got = []
    for j in range(STEPS):
        row = np.asarray(logprobs(jnp.asarray(ids)))[len(prompt) - 1 + j]
        top = np.argsort(-row)[:PROBE_TOP]
        ids[len(prompt) + j] = int(top[0])
        got.append([(int(t), float(row[t])) for t in [top[0], *top]])
    want = np.asarray(reference.logprobs(
        hf, served_params if ref_params is None else ref_params, ids[:-1],
        len(prompt) - 1))
    return np.array([abs(want[j, t] - v)
                     for j, pairs in enumerate(got) for t, v in pairs])


def test_the_probes_measure_reads_a_sound_program_as_sound(params):
    err = probe_errors(tiny_cfg(), params, PROMPTS["long"])
    assert err.shape == (STEPS * (1 + PROBE_TOP),)
    assert err.max() < LOGPROB_TOL, err.max()


@pytest.mark.parametrize("fault", ["k_pe rotated",
                                   "the query's two parts swapped"])
def test_a_fault_in_the_mla_mixer_as_the_benchmark_would_read_it(
        params, monkeypatch, fault):
    """Readings here, largest / mean of the 120 values against the cell's
    0.15 / 0.03: k_pe rotated 0.38 / 0.046, the parts swapped 0.29 /
    0.076 (a sound program 1e-6)."""
    monkeypatch.setattr(llama, "_mla_mixer",
                        functools.partial(_faulty_mixer, fault))
    err = probe_errors(tiny_cfg(), params, PROMPTS["long"])
    assert err.max() > 1000 * LOGPROB_TOL and over_a_limit(err), (
        fault, err.max(), err.mean())


def _layer_1_run_sparse(params):
    """The reference told that no layer is dense: layer 1 then takes a
    sparse block (the first expert layer's, with the dense layer's own
    norms) where the program ran its MLP."""
    dense, layers = params["dense"], params["layers"]
    first = {k: jnp.concatenate([dense[k] if k in ("attn_norm", "mlp_norm")
                                 else v[:1], v]) for k, v in layers.items()}
    return tiny_cfg(), params, {**HF, "first_k_dense_replace": 0}, {
        **params, "layers": first}


def _the_short_period_read_as_a_full_one(params):
    """The reference walks the rule "attention closes every period of
    four": the 7-layer stack's last, short period (kda, kda, mla) is then
    the head of a full one, (kda, kda, kda), and layer 7 is a KDA layer
    (a sixth one, the fifth's weights) where the program ran attention."""
    lin = {**HF["linear_attn_config"], "full_attn_layers": [4],
           "kda_layers": [1, 2, 3, 5, 6, 7]}
    six = jax.tree.map(lambda a: jnp.concatenate([a, a[-1:]]), params["kda"])
    return tiny_cfg(), params, {**HF, "linear_attn_config": lin}, {
        **params, "kda": six}


def _neg_eigval_on(params):
    return tiny_cfg(kda_neg_eigval=True), params, HF


@pytest.mark.parametrize("fault", [
    _layer_1_run_sparse, _the_short_period_read_as_a_full_one,
    _neg_eigval_on])
def test_a_fault_in_the_stack_as_the_benchmark_would_read_it(params, fault):
    """One side of the comparison differs from the other by one fault of
    the stack around the mixers. Readings here, largest / mean against
    0.15 / 0.03: layer 1 sparse 0.93 / 0.25, the short period read as a
    full one 0.42 / 0.13, beta doubled 0.58 / 0.13."""
    cfg, served_params, hf, *ref_params = fault(params)
    err = probe_errors(cfg, served_params, PROMPTS["long"], hf,
                       ref_params[0] if ref_params else params)
    assert err.max() > 1000 * LOGPROB_TOL and over_a_limit(err), (
        fault.__name__, err.max(), err.mean())


def _forgetful_spans(monkeypatch):
    """A second chunk starts from zeros instead of from what the first one
    left (every span of more than one row called fresh)."""
    real = kda.stream_spans

    def forgetful(cu_q_lens, context_lens, T):
        slot, off, live, q_len, _ = real(cu_q_lens, context_lens, T)
        return slot, off, live, q_len, q_len > 1

    monkeypatch.setattr(kda, "stream_spans", forgetful)


def _every_mla_layer_on_cache_layer_0(monkeypatch):
    """Both MLA layers write and read cache layer 0: the second one's rows
    lie where the first one's were when the next chunk reads them."""
    monkeypatch.setattr(
        llama, "_mla_mixer",
        lambda cfg, lp, x, pos, attend, caches, layer: llama_mla(
            cfg, lp, x, pos, attend, caches, 0))


@pytest.mark.parametrize("fault", [_forgetful_spans,
                                   _every_mla_layer_on_cache_layer_0])
def test_a_fault_in_the_paged_path_reads_over_the_limits(
        served, monkeypatch, fault):
    """Faults only the step programs can have, through the engine: the
    long prompt's three chunks, then decode. Readings here, largest / mean
    of the 20 served tokens' values: the state not carried 0.62 / 0.23,
    every MLA layer on cache layer 0 0.151 / 0.070 (over both limits, the
    largest by a hair: the fault reaches the rows of one of two MLA
    layers behind a chunk boundary)."""
    fault(monkeypatch)
    eng = engine(params=served[0].runner.params)
    toks, lps = serve(eng, {"long": PROMPTS["long"]})["long"]
    err = errors(HF, eng.runner.params, PROMPTS["long"], toks, lps)
    assert over_a_limit(err), (fault.__name__, err.max(), err.mean())


@pytest.mark.parametrize("control", ["latent_dtype", "state_dtype",
                                     "router_dtype"])
def test_a_reference_in_lower_precision_reads_over_the_float32_agreement(
        served, control):
    """The benchmark's control (chipbench/reference/control.py): the
    served path against a reference whose latent rows, recurrent and
    softmax state, or router scores are bfloat16 where this configuration
    states float32, each several times over this file's float32
    agreement and far under the cell's limits (as for the other hybrid
    stacks: PERF.md section 7). Readings here, largest / mean: latent rows
    2.5e-4 / 1.0e-4, the states 6.3e-3 / 1.9e-3, the router 1.2e-2 /
    5.4e-3 (0.97 / 0.069 before the stand-in's routed experts wrote at
    1 / routed_scaling: models/llama.py HYBRID_INIT)."""
    eng, out = served
    toks, lps = out["long"]
    err = errors(HF, eng.runner.params, PROMPTS["long"], toks, lps,
                 **{control: "bfloat16"})
    assert err.max() > 3 * LOGPROB_TOL and not over_a_limit(err), err


# -- (e) the published file ------------------------------------------------------

# the catalog's config (model-configs guide, Kimi-Linear-48B-A3B-Instruct), as
# https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}


def test_from_hf_config_reads_the_published_keys():
    cfg = ModelConfig.from_hf_config(CATALOG, "kimi")
    assert cfg.architecture == "kimi_linear"
    kinds = cfg.layer_kinds
    assert (kinds.count("kda"), kinds.count("mla")) == (20, 7)
    assert [l + 1 for l, k in enumerate(kinds) if k == "mla"] == [
        4, 8, 12, 16, 20, 24, 27]
    # the dense layer's period, five alike (one scan), the short one
    assert cfg.stack_segments == (
        (("kda", "kda", "kda", "mla"), 1), (("kda", "kda", "kda", "mla"), 5),
        (("kda", "kda", "mla"), 1))
    assert (cfg.dense_layers, cfg.num_expert_layers,
            cfg.dense_intermediate_size) == (1, 26, 9216)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.experts_held,
            cfg.shared_expert_size, cfg.intermediate_size) == (
                256, 8, 0, 1024, 1024)
    assert (cfg.moe_scoring, cfg.norm_topk_prob, cfg.routed_scaling) == (
        "sigmoid", True, 2.446)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv, cfg.kda_rank,
            cfg.kda_neg_eigval) == (32, 128, 4, 128, False)
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.head_dim,
            cfg.mla_rope) == (512, 0, 128, 64, 128, 192, False)
    assert cfg.max_model_len == 1048576 and not cfg.tie_word_embeddings
    assert cfg.is_latent and cfg.has_recurrent_state and cfg.is_moe
    # the chip's share, and the preset the tests run
    held = ModelConfig.from_hf_config(
        {**CATALOG, "n_routed_experts_held": 16, "routed_expert_offset": 32},
        "kimi")
    assert (held.experts_held, held.expert_offset) == (16, 32)
    whole = {k: v for k, v in HF.items()
             if k not in ("n_routed_experts_held", "routed_expert_offset")}
    assert dataclasses.replace(
        ModelConfig.from_hf_config(whole, "tiny-kimi-linear"),
        dtype="float32") == MODEL_PRESETS["tiny-kimi-linear"]


@pytest.mark.parametrize("over,words", [
    ({"mla_use_nope": False}, "mla_use_nope: false"),
    ({"q_lora_rank": 1536}, "q_lora_rank=1536"),
    ({"rope_scaling": {"type": "yarn", "factor": 32}}, "rope_scaling"),
    ({"num_expert_group": 8}, "num_expert_group=8"),
    ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers=1"),
    ({"first_k_dense_replace": 2}, "first_k_dense_replace=2"),
    ({"moe_layer_freq": 2}, "moe_layer_freq=2"),
    ({"moe_router_activation_func": "softmax"},
     "moe_router_activation_func='softmax'"),
    ({"linear_attn_config": {**CATALOG["linear_attn_config"],
                             "full_attn_layers": [4, 8, 12, 16, 20, 24]}},
     "do not name every layer"),
    ({"linear_attn_config": {**CATALOG["linear_attn_config"],
                             "kda_layers": CATALOG["linear_attn_config"][
                                 "kda_layers"] + [4]}},
     "do not name every layer"),
    ({"n_routed_experts_held": 16, "routed_expert_offset": 250},
     "not a share"),
])
def test_what_the_file_asks_for_and_is_not_computed_is_refused(over, words):
    with pytest.raises(ValueError, match="kimi_linear") as refusal:
        ModelConfig.from_hf_config({**CATALOG, **over}, "kimi")
    assert words in str(refusal.value)


def _two_devices():
    return build_mesh(MeshConfig(tensor=2), devices=jax.devices()[:2])


@pytest.mark.parametrize("words,over,mesh,refuse", [
    ("latent cache", {}, _two_devices, "_refuse_for_latent_cache"),
    ("recurrent state", {}, _two_devices, "_refuse_for_recurrent_state"),
    ("role=prefill", {"role": "prefill"}, one_device,
     "_refuse_for_latent_cache"),
    ("role=prefill", {"role": "prefill"}, one_device,
     "_refuse_for_recurrent_state"),
])
def test_both_refusals_apply(words, over, mesh, refuse):
    """The model keeps a latent cache AND recurrent state: what either
    refuses is refused."""
    with pytest.raises(ValueError, match=words):
        getattr(ModelRunner, refuse)(engine_config(**over), mesh())


def test_a_checkpoint_is_refused(tmp_path):
    cfg = dataclasses.replace(tiny_cfg(), weights_path=str(tmp_path))
    with pytest.raises(ValueError, match="kimi_linear checkpoint"):
        load_safetensors(cfg, one_device(), None)
