"""Falcon-H1's stack (a Mamba-2 state-space mixer with heads and rotated
grouped-query attention side by side in EVERY layer, the family's fourteen
scalars on every path) through the shared stack walker and the serving
engine, against the plain reference the benchmark uses on the chip
(chipbench/reference/falcon_h1.py) and against the family's published
modelling code, on seeded random weights at test size
(chipbench/tests/configs/tiny-falcon-h1: 3 layers, 6 state-space heads of 8
channels in 2 groups with a 16-value state, so P != N, 10 query heads over
2 KV heads, so G = 5, every scalar different from 1 and from the others),
float32, CPU.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import shapes_ssd
from chipbench.reference import falcon_h1 as reference
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.kv_cache import init_kv_cache
from production_stack_tpu.engine.metrics import EngineStatsCollector
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.models import falcon_h1, llama
from production_stack_tpu.ops import ssd, ssd_pallas
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(ROOT, "chipbench", "tests", "configs", "tiny-falcon-h1")
with open(os.path.join(ROOT, "chipbench", "configs", "falcon-h1-34b-l6",
                       "config.json")) as f:
    PUBLISHED = json.load(f)
with open(os.path.join(TINY, "config.json")) as f:
    HF = json.load(f)
CFG = dataclasses.replace(
    ModelConfig.from_hf_config(HF, "tiny-falcon-h1"), dtype="float32")
BLOCK, BUDGET = 4, 16  # tokens a KV block, tokens a ragged step
# float32 on the CPU on both sides; the served path differs from the
# reference in the order of its sums only (it read 1e-6)
LOGPROB_TOL = 1e-4
# a planted fault has to read over the tolerance, and not by a hair
FAULT_TOL = 100 * LOGPROB_TOL
F32 = jnp.float32

# the fourteen published scalars, as ModelConfig holds them
SCALARS = ("ssd_in_multiplier", "ssd_out_multiplier", "attn_in_multiplier",
           "attn_out_multiplier", "key_multiplier", "mlp_gate_multiplier",
           "mlp_down_multiplier", "embedding_multiplier",
           "lm_head_multiplier")
SSM_SECTIONS = ("z", "x", "B", "C", "dt")


def one_device():
    return build_mesh(MeshConfig(), devices=jax.devices()[:1])


def make_params(seed=0):
    """The stand-in's weights with the parameters it sets to a constant
    (conv bias 0, D and norm weights 1) drawn instead, so that leaving one
    out shows."""
    params = llama.init_params(CFG, jax.random.PRNGKey(seed))
    ks = jax.random.split(jax.random.PRNGKey(seed + 100), 4)
    sp = params["ssd"]
    params["ssd"] = {
        **sp,
        "conv_bias": 0.3 * jax.random.normal(ks[0], sp["conv_bias"].shape),
        "norm": 1 + 0.3 * jax.random.normal(ks[1], sp["norm"].shape),
        "d": 1 + 0.3 * jax.random.normal(ks[2], sp["d"].shape)}
    params["layers"] = {
        **params["layers"],
        "attn_norm": 1 + 0.3 * jax.random.normal(
            ks[3], params["layers"]["attn_norm"].shape)}
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


def serve(eng, prompts, max_tokens=12):
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


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).tolist()


def errors(params, ids, toks, lps):
    """|served - reference| log-probability of every generated token."""
    full = list(ids) + list(toks)
    want = np.asarray(reference.logprobs(HF, params, full[:-1], len(ids) - 1))
    return np.abs(np.asarray([want[j, t] for j, t in enumerate(toks)])
                  - np.asarray(lps))


def dense_errors(params, ids, cfg=CFG, served_params=None):
    """|dense forward - reference| over every row and vocabulary entry."""
    got = llama.forward_dense(cfg, served_params or params,
                              jnp.asarray([ids]))
    want = np.asarray(reference.logprobs(HF, params, ids, 0))
    return np.abs(np.asarray(jax.nn.log_softmax(got[0], -1)) - want)


# -- the configuration ---------------------------------------------------------

def test_the_published_file_gives_the_stack_the_issue_describes():
    cfg = ModelConfig.from_hf_config(PUBLISHED, "falcon")
    assert cfg.architecture == "falcon_h1"
    assert cfg.layer_kinds == ("parallel",) * 6
    assert cfg.stack_segments == ((("parallel",), 6),)  # one scan
    # every layer counts on both sides
    assert (cfg.num_attn_layers, cfg.cache_layers,
            cfg.num_recurrent_layers) == (6, 6, 6)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.q_per_kv, cfg.head_dim) == (
        20, 4, 5, 128)
    assert (cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_state, cfg.ssd_groups,
            cfg.ssd_conv, cfg.ssd_inner, cfg.ssd_conv_dim) == (
        32, 128, 256, 2, 4, 4096, 5120)
    assert cfg.kv_pool_shape(7, 16) == (6, 7, 16, 8, 128)
    assert cfg.kv_bytes_per_token == 12288
    assert cfg.recurrent_state_bytes(64) == 64 * 6 * (4194304 + 30720)
    assert (cfg.rope_theta, cfg.rms_norm_eps, cfg.tie_word_embeddings) == (
        1e11, 1e-5, False)
    # all fourteen scalars, from the file
    assert cfg.ssd_multipliers == tuple(PUBLISHED["ssm_multipliers"])
    assert (cfg.mlp_gate_multiplier, cfg.mlp_down_multiplier) == tuple(
        PUBLISHED["mlp_multipliers"])
    assert {k: getattr(cfg, k) for k in SCALARS
            if not k.startswith("mlp_")} == {
        "ssd_in_multiplier": 0.25, "ssd_out_multiplier": 0.08838834764831845,
        "attn_in_multiplier": 1.0, "attn_out_multiplier": 0.0375,
        "key_multiplier": 0.011048543456039804,
        "embedding_multiplier": 5.656854249492381,
        "lm_head_multiplier": 0.0078125}
    # the cut is depth alone
    assert PUBLISHED["num_hidden_layers"] == 6
    uncut = ModelConfig.from_hf_config({**PUBLISHED, "num_hidden_layers": 72})
    assert uncut.recurrent_state_bytes(1) == 72 * (4194304 + 30720)


def test_the_programs_own_parameter_count_is_the_issues():
    cfg = ModelConfig.from_hf_config(PUBLISHED, "falcon")
    shapes = jax.eval_shape(lambda: llama.init_params(cfg,
                                                      jax.random.PRNGKey(0)))
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert total == 5_254_594_112 == shapes_ssd.total_params(PUBLISHED)
    assert shapes_ssd.layer_params(PUBLISHED) == 430_120_032
    # ... and the specs name every leaf
    specs = llama.param_specs(cfg)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, tuple))


def test_other_families_carry_no_scalar():
    dense = ModelConfig()
    assert all(getattr(dense, k) == 1 for k in SCALARS)
    assert dense.ssd_multipliers == (1.0,) * 5 and not dense.ssd_heads
    assert not dense.has_recurrent_state


@pytest.mark.parametrize("change,match", [
    ({"mamba_use_mlp": False}, "mamba_use_mlp"),
    ({"mamba_norm_before_gate": True}, "mamba_norm_before_gate"),
    ({"mamba_rms_norm": False}, "mamba_rms_norm"),
    ({"attn_layer_indices": [0, 2]}, "attn_layer_indices"),
    ({"rope_scaling": {"type": "linear", "factor": 2}}, "rope_scaling"),
    ({"attention_bias": True}, "attention_bias"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"projectors_bias": True}, "projectors_bias"),
    ({"mamba_d_ssm": 4000}, "mamba_n_heads 32 x mamba_d_head 128"),
    ({"mamba_n_groups": 3}, "no multiple of mamba_n_groups"),
])
def test_what_is_not_computed_is_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config({**PUBLISHED, **change})


def test_a_null_d_ssm_means_expand_times_hidden():
    hf = {**PUBLISHED, "mamba_d_ssm": None, "mamba_n_heads": 80}
    assert ModelConfig.from_hf_config(hf).ssd_inner == 2 * 5120


def test_what_would_move_or_guess_at_state_is_refused():
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
        with pytest.raises(ValueError, match=match):
            LLMEngine(cfg, mesh=one_device())


def test_a_checkpoint_is_refused_not_guessed_at(tmp_path):
    from production_stack_tpu.engine.weights import load_safetensors

    cfg = dataclasses.replace(CFG, weights_path=str(tmp_path))
    with pytest.raises(ValueError, match="falcon_h1 checkpoint"):
        load_safetensors(cfg, one_device(), None)


# -- the scan: three forms and two kernels ------------------------------------

H, P, N, G, SLOTS = 4, 8, 16, 2, 4


def _rows(key, T):
    ks = jax.random.split(key, 4)
    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[0], (T, H)))
    return (g, jax.random.normal(ks[1], (T, H, P)),
            jax.random.normal(ks[2], (T, G, N)),
            jax.random.normal(ks[3], (T, G, N)))


RAGGED = {"xla": ssd.scan_ragged,
          "kernel": lambda *a: ssd_pallas.ssd_ragged(*a, interpret=True),
          "span-kernel": lambda *a: ssd_pallas.ssd_chunk_scan(
              *a, interpret=True)}
DECODE = {"xla": ssd.scan_decode,
          "kernel": lambda *a: ssd_pallas.ssd_decode_step(*a, interpret=True)}


def _dense(rows):
    return ssd.scan_dense(*(r[None] for r in rows))[0]


@pytest.mark.parametrize("impl", sorted(RAGGED))
@pytest.mark.parametrize("cuts", [(150, 290), (1, 2), (128, 256), (7, 135)])
def test_a_span_continues_its_slots_state_across_chunks(impl, cuts):
    """One 300-row sequence (more than two of the span kernel's 128-row
    blocks) fed to slot 2 in three chunks, other slots' spans beside it:
    the state crosses blocks inside a span and chunks between calls, and
    the whole reads as the dense form does."""
    T = 300
    rows = _rows(jax.random.PRNGKey(sum(cuts)), T)
    want = _dense(rows)
    state = jax.random.normal(jax.random.PRNGKey(9), (2, SLOTS, H, N, P))
    other, got, start = state, [], 0
    for end in (*cuts, T):
        n = end - start
        # slot 0 holds a 5-row span of a sequence 40 rows long, slot 2 ours
        extra = _rows(jax.random.PRNGKey(end), 5)
        packed = [jnp.concatenate([e, r[start:end]]) for e, r in
                  zip(extra, rows)]
        cu = jnp.asarray([0, 5, 5, 5 + n, 5 + n], jnp.int32)
        ctx = jnp.asarray([40, 0, end, 0], jnp.int32)
        y, state = RAGGED[impl](state, 1, *packed, cu, ctx)
        got.append(y[5:5 + n])
        start = end
    np.testing.assert_allclose(jnp.concatenate(got), want, atol=2e-4,
                               rtol=1e-4)
    # a slot without a span, and the other layer, keep what they held
    np.testing.assert_array_equal(state[1, (1, 3)], other[1, (1, 3)])
    np.testing.assert_array_equal(state[0], other[0])


@pytest.mark.parametrize("impl", sorted(RAGGED))
def test_a_reused_slot_starts_from_zeros(impl):
    rows = _rows(jax.random.PRNGKey(3), 20)
    dirty = jax.random.normal(jax.random.PRNGKey(4), (1, SLOTS, H, N, P))
    cu = jnp.asarray([0, 0, 20, 20, 20], jnp.int32)
    ctx = jnp.asarray([0, 20, 0, 0], jnp.int32)  # as long as its span
    y, _ = RAGGED[impl](dirty, 0, *rows, cu, ctx)
    np.testing.assert_allclose(y, _dense(rows), atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("impl", sorted(DECODE))
def test_the_decode_form_moves_live_slots_alone(impl):
    """A sequence's rows one a step through slot 1 of layer 1 read as the
    dense form; idle slots and the other layer keep their state."""
    rows = _rows(jax.random.PRNGKey(5), 6)
    want = _dense(rows)
    state0 = jax.random.normal(jax.random.PRNGKey(6), (2, SLOTS, H, N, P))
    state = state0.at[1, 1].set(0.0)
    active = jnp.asarray([False, True, False, True])
    for t in range(6):
        step = [jnp.broadcast_to(r[t], (SLOTS, *r.shape[1:])) for r in rows]
        y, state = DECODE[impl](state, 1, *step, active)
        np.testing.assert_allclose(y[1], want[t], atol=2e-5, rtol=1e-5)
    np.testing.assert_array_equal(state[1, (0, 2)], state0[1, (0, 2)])
    np.testing.assert_array_equal(state[0], state0[0])


def test_the_ragged_kernel_sends_decode_rows_through_the_decode_kernel():
    """One-row spans that continue a state and a fresh one-row span side by
    side: the first through ``ssd_decode_step``, the second through the
    span kernel, both as the row-by-row form has them."""
    rows = _rows(jax.random.PRNGKey(7), 3)
    state = jax.random.normal(jax.random.PRNGKey(8), (1, SLOTS, H, N, P))
    cu = jnp.asarray([0, 1, 2, 2, 3], jnp.int32)
    ctx = jnp.asarray([9, 1, 0, 30], jnp.int32)
    want_y, want_s = ssd.scan_ragged(state, 0, *rows, cu, ctx)
    y, s = ssd_pallas.ssd_ragged(state, 0, *rows, cu, ctx, interpret=True)
    np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=1e-5)


# -- the model -----------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_dense_forward_matches_the_reference(seed):
    err = dense_errors(make_params(seed), prompt(45, seed))
    assert err.max() < LOGPROB_TOL, err.max()


def test_the_reference_matches_the_published_code():
    """``transformers.models.falcon_h1`` built from the same tiny file in
    float32 with the reference's weights copied in (its ``torch_forward``
    path needs no kernels): the same logits. This is the test that keeps a
    guessed mechanism out from under a real model's name."""
    torch = pytest.importorskip("torch")
    module = pytest.importorskip("transformers.models.falcon_h1")
    params = make_params(0)
    config = module.FalconH1Config(**{
        k: v for k, v in HF.items()
        if k not in ("architectures", "model_type")})
    config._attn_implementation = "eager"
    model = module.FalconH1ForCausalLM(config).float().eval()

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    E, D, Hq = CFG.hidden_size, CFG.head_dim, CFG.num_heads
    new = {"model.embed_tokens.weight": t(params["embed"]),
           "lm_head.weight": t(params["lm_head"].T),
           "model.final_layernorm.weight": t(params["final_norm"])}
    for l in range(CFG.num_layers):
        lp, gp, sp = (jax.tree.map(lambda a: a[l], params[k])
                      for k in ("layers", "gqa", "ssd"))
        p = f"model.layers.{l}."
        new.update({
            p + "input_layernorm.weight": t(lp["attn_norm"]),
            p + "pre_ff_layernorm.weight": t(lp["mlp_norm"]),
            p + "feed_forward.gate_proj.weight": t(lp["w_gate"].T),
            p + "feed_forward.up_proj.weight": t(lp["w_up"].T),
            p + "feed_forward.down_proj.weight": t(lp["w_down"].T),
            p + "self_attn.q_proj.weight": t(gp["wq_t"]),
            p + "self_attn.k_proj.weight": t(gp["wk_t"]),
            p + "self_attn.v_proj.weight": t(gp["wv_t"]),
            p + "self_attn.o_proj.weight": t(gp["wo"].reshape(Hq * D, E).T),
            p + "mamba.in_proj.weight": t(
                jnp.concatenate([sp["w_in"], sp["w_dt"]], axis=1).T),
            # torch's taps run oldest first, over (channels, 1, K)
            p + "mamba.conv1d.weight": t(sp["conv"][::-1].T[:, None, :]),
            p + "mamba.conv1d.bias": t(sp["conv_bias"]),
            p + "mamba.dt_bias": t(sp["dt_bias"]),
            p + "mamba.A_log": t(sp["a_log"]),
            p + "mamba.D": t(sp["d"]),
            p + "mamba.norm.weight": t(sp["norm"]),
            p + "mamba.out_proj.weight": t(sp["w_out"].T)})
    theirs = model.state_dict()
    assert set(new) == set(theirs)  # every published tensor has a source
    model.load_state_dict(new)
    ids = prompt(37, 1)
    with torch.no_grad():
        logits = model(torch.tensor(ids)[None]).logits[0].numpy()
    got = np.asarray(jax.nn.log_softmax(jnp.asarray(logits), -1))
    want = np.asarray(reference.logprobs(HF, params, ids, 0))
    assert np.abs(got - want).max() < LOGPROB_TOL


@pytest.fixture(scope="module")
def served():
    """A 41-token prompt (three chunks of the 16-token budget) and a
    5-token one interleaved in one stream, then 24 decode steps through the
    KV pool and the state, the two slots at different positions."""
    eng = engine(make_params(0))
    prompts = {"long": prompt(41, 10), "short": prompt(5, 11)}
    return eng, prompts, serve(eng, prompts, max_tokens=24)


@pytest.mark.parametrize("name", ["long", "short"])
def test_served_logprobs_match_the_reference_at_every_row(served, name):
    eng, prompts, out = served
    toks, lps = out[name]
    err = errors(eng.runner.params, prompts[name], toks, lps)
    assert len(toks) == 24 and err.max() < LOGPROB_TOL, err
    assert eng.decode_dispatches > 0 and eng.ragged_dispatches > 0


def test_the_cache_is_a_pool_and_per_slot_state(served):
    eng = served[0]
    kv = eng.runner.kv
    assert set(kv) == {"kv", "state", "conv"}
    assert kv["kv"].shape == (3, 64, BLOCK, 2 * 2, 16)
    assert kv["state"].shape == (3, 4, 6, 16, 8)
    assert kv["state"].dtype == jnp.float32
    assert kv["conv"].shape == (3, 4, 3, 48 + 2 * 2 * 16)
    cold = init_kv_cache(CFG, CacheConfig(block_size=BLOCK), one_device(),
                         num_blocks=8, slots=2)
    assert cold["state"].shape[1] == 2


def test_the_counters_say_what_ran(served):
    eng = served[0]
    s = eng.stats()
    assert s["ssd_decode_calls_total"] == 3 * eng.decode_dispatches
    assert s["ssd_chunk_tokens_total"] == 41 + 5
    assert s["ssd_chunk_spans_total"] == 3 + 1
    assert s["recurrent_state_resets_total"] == 2
    assert s["recurrent_state_bytes"] == CFG.recurrent_state_bytes(4)
    assert s["kv_pool_bytes"] == 64 * BLOCK * CFG.kv_bytes_per_token
    assert s["prefix_lookups_bypassed_total"] >= 2
    assert not [k for k in s if k.startswith(("kda_", "mamba_"))]
    text = "".join(
        f"{m.name} {[x.value for x in m.samples]}\n"
        for m in EngineStatsCollector(eng, "tiny").collect())
    for name in ("vllm:ssd_decode_calls", "vllm:ssd_chunk_tokens",
                 "vllm:ssd_chunk_spans", "vllm:recurrent_state_bytes",
                 "vllm:recurrent_state_resets", "vllm:kv_pool_bytes"):
        assert name + " " in text, name
    assert "vllm:kda_decode_calls" not in text
    assert "vllm:mamba_decode_calls" not in text


# -- planted faults: each reads over the tolerance, and not by a hair ---------

def _drop(field, index=None):
    """The configuration with one published scalar dropped (1)."""
    if index is None:
        return dataclasses.replace(CFG, **{field: 1.0})
    ms = list(CFG.ssd_multipliers)
    ms[index] = 1.0
    return dataclasses.replace(CFG, ssd_multipliers=tuple(ms))


def _without(name):
    def params():
        p = make_params(0)
        return {**p, "ssd": {**p["ssd"],
                             name: jnp.zeros_like(p["ssd"][name])}}
    return params


def _wrong_group(monkeypatch):
    real = ssd.by_head
    monkeypatch.setattr(
        ssd, "by_head", lambda v, heads: real(v[..., ::-1, :], heads))


def _gate_after_norm(monkeypatch):
    def late(cfg, y, z, weight):
        u = y.astype(F32).reshape(*y.shape[:-1], cfg.ssd_groups, -1)
        u = u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True)
                              + cfg.rms_norm_eps)
        return (u.reshape(y.shape) * weight.astype(F32)
                * jax.nn.silu(z.astype(F32)))

    monkeypatch.setattr(falcon_h1, "gated_group_norm", late)


def _one_norm(monkeypatch):
    real = falcon_h1.gated_group_norm
    monkeypatch.setattr(
        falcon_h1, "gated_group_norm", lambda cfg, *a: real(
            dataclasses.replace(cfg, ssd_groups=1), *a))


def _sequential(monkeypatch):
    """Attention fed the row AFTER the state-space output was added to it,
    one mixer behind the other instead of side by side."""
    real_mixer, real_times, last = falcon_h1.ssd_mixer, llama.times, {}

    def mixer(*a):
        last["o"], caches = real_mixer(*a)
        return last["o"], caches

    def times(x, scalar):
        if scalar == CFG.attn_in_multiplier:
            x = x + CFG.ssd_out_multiplier * last["o"]
        return real_times(x, scalar)

    monkeypatch.setattr(falcon_h1, "ssd_mixer", mixer)
    monkeypatch.setattr(llama, "times", times)


FAULTS = {
    **{f"{name} dropped": {"cfg": _drop(name)} for name in SCALARS},
    **{f"ssm_multipliers[{s}] dropped": {"cfg": _drop(None, i)}
       for i, s in enumerate(SSM_SECTIONS)},
    "B and C from the wrong group": {"patch": _wrong_group},
    "the gate after the norm": {"patch": _gate_after_norm},
    "one norm over all channels": {"patch": _one_norm},
    "D x left out": {"params": _without("d")},
    "dt_bias left out": {"params": _without("dt_bias")},
    "the conv bias left out": {"params": _without("conv_bias")},
    "attention behind the state-space mixer": {"patch": _sequential},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_reads_over_the_tolerance(monkeypatch, fault):
    plan = FAULTS[fault]
    params, ids = make_params(0), prompt(24, 2)
    if "patch" in plan:
        plan["patch"](monkeypatch)
    served = plan["params"]() if "params" in plan else None
    err = dense_errors(params, ids, plan.get("cfg", CFG), served)
    assert err.max() > FAULT_TOL, (fault, err.max())


def test_the_fourteen_scalars_are_all_planted():
    assert len([f for f in FAULTS if f.endswith("dropped")]) == 14
    values = [getattr(CFG, k) for k in SCALARS] + list(CFG.ssd_multipliers)
    assert len(set(values)) == 14 and 1.0 not in values


def test_fault_state_not_carried_across_a_chunk(monkeypatch):
    real = ssd.stream_spans

    def forgetful(cu_q_lens, context_lens, T):
        slot, off, live, q_len, fresh = real(cu_q_lens, context_lens, T)
        return slot, off, live, q_len, fresh | (q_len > 1)

    def served_errors():
        eng = engine(make_params(0))
        ids = prompt(41, 10)
        toks, lps = serve(eng, {"a": ids}, max_tokens=8)["a"]
        return errors(eng.runner.params, ids, toks, lps)

    monkeypatch.setattr(ssd, "stream_spans", forgetful)
    assert served_errors().max() > FAULT_TOL


def test_the_reference_in_a_bfloat16_state_is_another_result():
    """``chipbench/reference/control.py bf16_state`` at test size: the
    scan's state kept in bfloat16 after every token, all else float32,
    against the float32 reference. With the stand-in's slow decay
    (``falcon_h1.STANDIN_A``) the state carries y, so its rounding reaches
    the logits: over the planted faults' tolerance, as the benchmark's
    probe has to find it over its own on the chip (PERF.md section 2)."""
    params, ids = make_params(0), prompt(192, 3)
    want = np.asarray(reference.logprobs(HF, params, ids, 0))
    got = np.asarray(reference.logprobs(HF, params, ids, 0,
                                        state_dtype="bfloat16"))
    assert np.abs(got - want).max() > FAULT_TOL
    assert float(np.exp(params["ssd"]["a_log"]).max()) == pytest.approx(
        falcon_h1.STANDIN_A)


# -- the benchmark's arithmetic ------------------------------------------------

def test_shapes_ssd_counts_the_published_model():
    s, hf = shapes_ssd, PUBLISHED
    assert s.ssd_params(hf) == 47_349_760 + 29_792 + 20_971_520
    assert s.attn_params(hf) == 31_457_280
    assert s.mlp_params(hf) == 330_301_440
    assert 72 * s.layer_params(hf) * 2 == pytest.approx(61.9e9, rel=1e-3)
    assert s.state_bytes_per_slot(hf) == 4_194_304
    assert s.conv_tail_bytes_per_slot(hf) == 30_720
    assert s.kv_bytes_per_token(hf) == 12_288
    cfg = ModelConfig.from_hf_config(hf)
    assert 64 * 6 * (s.state_bytes_per_slot(hf)
                     + s.conv_tail_bytes_per_slot(hf)) == (
        cfg.recurrent_state_bytes(64))
