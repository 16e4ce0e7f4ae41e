"""Olmo-Hybrid's stack (Gated DeltaNet layers three to one with full
multi-head attention that rotates nothing, QK-norm over the whole
projections, a norm AFTER each sublayer and none before) through the shared
stack walker and the serving engine, against the plain reference the
benchmark uses on the chip (chipbench/reference/olmo_hybrid.py), and that
reference's pieces against the published modelling code that is installed
(``transformers.models.qwen3_next`` for the recurrence and the gated norm,
``transformers.models.olmo3`` for the attention block), on seeded random
weights at test size (chipbench/tests/configs/tiny-olmo-hybrid: 2 periods
of (gdn, gdn, gdn, gqa), 4 GDN heads with d_k 12 and d_v 20, neither a
multiple of the other's tile, 6 attention heads = 6 KV heads of 16 that the
cache holds as 8), float32, CPU.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import shapes_gdn
from chipbench.reference import olmo_hybrid as reference
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
from production_stack_tpu.models import llama, olmo_hybrid
from production_stack_tpu.ops import gdn, gdn_pallas, kda
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(ROOT, "chipbench", "tests", "configs", "tiny-olmo-hybrid")
with open(os.path.join(ROOT, "chipbench", "configs", "olmo-hybrid-7b-l16",
                       "config.json")) as f:
    CUT = json.load(f)
with open(os.path.join(TINY, "config.json")) as f:
    HF = json.load(f)
# the published file: the cut's, its pattern repeated to 32 layers
PUBLISHED = {**CUT, "num_hidden_layers": 32,
             "layer_types": CUT["layer_types"] * 2}
CFG = dataclasses.replace(
    ModelConfig.from_hf_config(HF, "tiny-olmo-hybrid"), dtype="float32")
BLOCK, BUDGET = 4, 16  # tokens a KV block, tokens a ragged step
# float32 on the CPU on both sides; the served path differs from the
# reference in the order of its sums only (it read 6e-6)
LOGPROB_TOL = 1e-4
# a planted fault has to read over the tolerance, and not by a hair
FAULT_TOL = 100 * LOGPROB_TOL
F32 = jnp.float32


def one_device():
    return build_mesh(MeshConfig(), devices=jax.devices()[:1])


def make_params(seed=0):
    """The stand-in's weights with the norm weights it sets to a constant
    drawn instead, so that a norm left out or misplaced shows."""
    params = llama.init_params(CFG, jax.random.PRNGKey(seed))
    ks = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 8))

    def drawn(a, gain=1.0):
        return gain * (1 + 0.3 * jax.random.normal(next(ks), a.shape))

    lp, ap, gp = params["layers"], params["gqa"], params["gdn"]
    params["layers"] = {
        **lp, "post_attn_norm": drawn(lp["post_attn_norm"], 0.25),
        "post_mlp_norm": drawn(lp["post_mlp_norm"], 0.25)}
    params["gqa"] = {**ap, "q_norm": drawn(ap["q_norm"]),
                     "k_norm": drawn(ap["k_norm"])}
    params["gdn"] = {**gp, "o_norm": drawn(gp["o_norm"])}
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
    cfg = ModelConfig.from_hf_config(PUBLISHED, "olmo")
    period = ("gdn", "gdn", "gdn", "gqa")
    assert cfg.architecture == "olmo_hybrid"
    assert cfg.layer_kinds == period * 8
    assert cfg.stack_segments == ((period, 8),)  # one scan
    assert (cfg.num_attn_layers, cfg.cache_layers,
            cfg.num_recurrent_layers, cfg.num_kda_layers) == (8, 8, 24, 0)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.q_per_kv, cfg.head_dim) == (
        30, 30, 1, 128)
    assert (cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_conv,
            cfg.gdn_conv_dim, cfg.gdn_neg_eigval) == (
        30, 96, 192, 4, 11520, True)
    # a norm after each sublayer and none before; QK-norm over the whole
    # projections; an untied head
    assert (cfg.norms, cfg.pre_norms, cfg.post_norms) == ("post", False, True)
    assert (cfg.qk_norm, cfg.qk_norm_kind) == (True, "full")
    assert (cfg.rms_norm_eps, cfg.tie_word_embeddings,
            cfg.max_model_len) == (1e-6, False, 65536)
    # 30 KV heads are 60 rows a token: held as 32 heads, whole 8-row tiles
    assert cfg.cache_kv_heads == 32
    assert cfg.kv_pool_shape(7, 16) == (8, 7, 16, 64, 128)
    assert cfg.kv_bytes_per_token == 8 * 16384
    assert cfg.recurrent_state_bytes(1) == 24 * (2_211_840 + 69_120)
    # the cut is depth alone: four whole periods
    cut = ModelConfig.from_hf_config(CUT, "olmo")
    assert cut.stack_segments == ((period, 4),)
    assert {k: v for k, v in CUT.items()
            if k not in ("num_hidden_layers", "layer_types")} == {
        k: v for k, v in PUBLISHED.items()
        if k not in ("num_hidden_layers", "layer_types")}
    assert cut.kv_bytes_per_token == 65_536
    assert cut.recurrent_state_bytes(64) == 64 * 12 * (2_211_840 + 69_120)


@pytest.mark.parametrize("hf,total", [(PUBLISHED, 7_430_870_688),
                                      (CUT, 4_100_788_944)])
def test_the_programs_own_parameter_count_is_the_issues(hf, total):
    cfg = ModelConfig.from_hf_config(hf, "olmo")
    shapes = jax.eval_shape(lambda: llama.init_params(cfg,
                                                      jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(shapes)) == total
    assert shapes_gdn.total_params(hf) == total
    # ... and the specs name every leaf
    specs = llama.param_specs(cfg)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, tuple))


def test_the_norms_have_one_description():
    """``norms`` says where a block's norms sit; the two properties are
    read off it, and the families that had ``post_norms`` keep theirs."""
    assert ModelConfig().norms == "pre" and not ModelConfig().post_norms
    for name in ("tiny-ouro", "tiny-pangu"):
        cfg = ModelConfig.from_pretrained(name)
        assert (cfg.norms, cfg.pre_norms, cfg.post_norms) == (
            "both", True, True)
    assert "post_norms" not in {f.name for f in dataclasses.fields(CFG)}
    assert not CFG.has_recurrent_state or CFG.norms == "post"


@pytest.mark.parametrize("heads,held", [(1, 1), (2, 2), (4, 4), (8, 8),
                                        (16, 16), (12, 12), (6, 8),
                                        (20, 20), (30, 32)])
def test_more_than_four_kv_heads_fill_up_to_whole_tiles(heads, held):
    cfg = ModelConfig(num_heads=heads, num_kv_heads=heads)
    assert cfg.cache_kv_heads == held


@pytest.mark.parametrize("change,match", [
    ({"linear_num_key_heads": 15}, "linear_num_key_heads 15 != "),
    ({"linear_num_key_heads": 15, "linear_num_value_heads": 15}, "odd"),
    ({"rope_parameters": {"rope_theta": 500000.0}}, "rope_theta=500000.0"),
    ({"rope_theta": 10000.0, "rope_parameters": None}, "rope_theta=10000.0"),
    ({"attention_bias": True}, "attention_bias"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"layer_types": PUBLISHED["layer_types"][:30],
      "num_hidden_layers": 30}, "no whole number of one period"),
    ({"layer_types": ["full_attention"] * 32}, "no whole number of one"),
    ({"layer_types": ["linear_attention"] * 32}, "no whole number of one"),
    ({"layer_types": PUBLISHED["layer_types"][:28]
      + ["linear_attention", "linear_attention", "full_attention",
         "full_attention"]}, "no whole number of one period"),
    ({"layer_types": PUBLISHED["layer_types"][:16]}, "of 16 entries for"),
    ({"layer_types": ["linear_attention", "sliding_attention"] * 16},
     "sliding_attention"),
    ({"layer_types": ["linear_attention", "chunked_attention"] * 16},
     "chunked_attention"),
])
def test_what_is_not_computed_is_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config({**PUBLISHED, **change})


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
    with pytest.raises(ValueError, match="olmo_hybrid checkpoint"):
        load_safetensors(cfg, one_device(), None)


# -- the recurrence: three forms and two kernels ------------------------------

H, DK, DV, SLOTS = 4, 12, 20, 4


def _rows(key, T):
    """(g (T, H, 1), a, kb, k, q, vb) of T random rows, beta in (0, 2)."""
    ks = jax.random.split(key, 5)
    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[0], (T, H, 1)))
    q, k = (jax.random.normal(ks[i], (T, H, DK)) for i in (1, 2))
    v = jax.random.normal(ks[3], (T, H, DV))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    return g, kda.prepare(q, k, v, g, beta, True)


def _xla(form):
    """An XLA form takes the decay where a kernel takes what it is handed:
    the ragged kernels the log-decay, everything else exp of it."""
    return lambda state, layer, g, a, *rest: form(state, layer, a, *rest)


RAGGED = {
    "xla": _xla(gdn.recurrence_ragged),
    "kernel": lambda state, layer, g, a, *rest: gdn_pallas.gdn_ragged(
        state, layer, g, *rest, interpret=True),
    "span-kernel": lambda state, layer, g, a, *rest:
        gdn_pallas.gdn_chunk_scan(state, layer, g, *rest, interpret=True)}
DECODE = {
    "xla": gdn.recurrence_decode,
    "kernel": lambda *a: gdn_pallas.gdn_decode_step(*a, interpret=True)}


def _dense(prepared):
    return gdn.recurrence_dense(*(r[None] for r in prepared))[0]


def _state(key, layers):
    return jax.random.normal(key, (layers, SLOTS, H // 2, DK, 2 * DV))


def test_two_heads_lie_side_by_side_on_the_lanes():
    S = jax.random.normal(jax.random.PRNGKey(0), (3, H, DK, DV))
    packed = gdn.pack(S)
    assert packed.shape == (3, H // 2, DK, 2 * DV)
    np.testing.assert_array_equal(packed[:, 1, :, :DV], S[:, 2])
    np.testing.assert_array_equal(packed[:, 1, :, DV:], S[:, 3])
    np.testing.assert_array_equal(gdn.unpack(packed), S)


@pytest.mark.parametrize("impl", sorted(RAGGED))
@pytest.mark.parametrize("cuts", [(150, 290), (1, 2), (128, 256), (7, 135)])
def test_a_span_continues_its_slots_state_across_chunks(impl, cuts):
    """One 300-row sequence (more than four of the span kernel's 64-row
    blocks) fed to slot 2 in three chunks, other slots' spans beside it:
    the state crosses blocks inside a span and chunks between calls, and
    the whole reads as the dense form does. The cuts fall inside blocks,
    on block boundaries and after one row."""
    T = 300
    g, rows = _rows(jax.random.PRNGKey(sum(cuts)), T)
    want = _dense(rows)
    state = _state(jax.random.PRNGKey(9), 2)
    other, got, start = state, [], 0
    for end in (*cuts, T):
        n = end - start
        # slot 0 holds a 5-row span of a sequence 40 rows long, slot 2 ours
        g5, extra = _rows(jax.random.PRNGKey(end), 5)
        packed = [jnp.concatenate([e, r[start:end]]) for e, r in
                  zip((g5, *extra), (g, *rows))]
        cu = jnp.asarray([0, 5, 5, 5 + n, 5 + n], jnp.int32)
        ctx = jnp.asarray([40, 0, end, 0], jnp.int32)
        o, state = RAGGED[impl](state, 1, *packed, cu, ctx)
        got.append(o[5:5 + n])
        start = end
    np.testing.assert_allclose(jnp.concatenate(got), want, atol=2e-4,
                               rtol=1e-4)
    # a slot without a span, and the other layer, keep what they held
    np.testing.assert_array_equal(state[1, (1, 3)], other[1, (1, 3)])
    np.testing.assert_array_equal(state[0], other[0])


@pytest.mark.parametrize("impl", sorted(RAGGED))
def test_a_reused_slot_starts_from_zeros(impl):
    g, rows = _rows(jax.random.PRNGKey(3), 20)
    dirty = _state(jax.random.PRNGKey(4), 1)
    cu = jnp.asarray([0, 0, 20, 20, 20], jnp.int32)
    ctx = jnp.asarray([0, 20, 0, 0], jnp.int32)  # as long as its span
    o, _ = RAGGED[impl](dirty, 0, g, *rows, cu, ctx)
    np.testing.assert_allclose(o, _dense(rows), atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("impl", sorted(DECODE))
def test_the_decode_form_moves_live_slots_alone(impl):
    """A sequence's rows one a step through slot 1 of layer 1 read as the
    dense form; idle slots and the other layer keep their state."""
    _, rows = _rows(jax.random.PRNGKey(5), 6)
    want = _dense(rows)
    state0 = _state(jax.random.PRNGKey(6), 2)
    state = state0.at[1, 1].set(0.0)
    active = jnp.asarray([False, True, False, True])
    for t in range(6):
        step = [jnp.broadcast_to(r[t], (SLOTS, *r.shape[1:])) for r in rows]
        o, state = DECODE[impl](state, 1, *step, active)
        np.testing.assert_allclose(o[1], want[t], atol=2e-5, rtol=1e-5)
    np.testing.assert_array_equal(state[1, (0, 2)], state0[1, (0, 2)])
    np.testing.assert_array_equal(state[0], state0[0])


def test_the_ragged_kernel_sends_decode_rows_through_the_decode_kernel(
        monkeypatch):
    """One-row spans that continue a state and a fresh one-row span side by
    side: the first through ``gdn_decode_step``, the second through the
    span kernel, both as the row-by-row form has them."""
    g, rows = _rows(jax.random.PRNGKey(7), 3)
    state = _state(jax.random.PRNGKey(8), 1)
    cu = jnp.asarray([0, 1, 2, 2, 3], jnp.int32)
    ctx = jnp.asarray([9, 1, 0, 30], jnp.int32)
    want_o, want_s = gdn.recurrence_ragged(state, 0, *rows, cu, ctx)
    taken, real = [], gdn_pallas.gdn_decode_step

    def spy(*a, **kw):
        taken.append(np.asarray(a[7]))
        return real(*a, **kw)

    monkeypatch.setattr(gdn_pallas, "gdn_decode_step", spy)
    o, s = gdn_pallas.gdn_ragged(state, 0, g, *rows[1:], cu, ctx,
                                 interpret=True)
    np.testing.assert_array_equal(taken[0], [True, False, False, True])
    np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=1e-5)


# -- the reference against the published code ----------------------------------

def test_the_recurrence_is_the_published_gated_delta_rule():
    """``torch_recurrent_gated_delta_rule(..., use_qk_l2norm_in_kernel=
    True)`` of ``transformers.models.qwen3_next`` with beta doubled (the
    negative eigenvalues), d_k != d_v: the reference's token loop, and
    through it every other form."""
    torch = pytest.importorskip("torch")
    module = pytest.importorskip(
        "transformers.models.qwen3_next.modeling_qwen3_next")
    T = 40
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k = (jax.random.normal(ks[i], (T, H, DK)) for i in (0, 1))
    v = jax.random.normal(ks[2], (T, H, DV))
    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[3], (T, H)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))[None]

    theirs, _ = module.torch_recurrent_gated_delta_rule(
        t(q), t(k), t(v), g=t(g), beta=t(beta), initial_state=None,
        output_final_state=False, use_qk_l2norm_in_kernel=True)
    ours = reference.recurrence(reference.l2norm(q) * DK ** -0.5,
                                reference.l2norm(k), v, g, beta)
    np.testing.assert_allclose(ours, theirs[0].numpy(), atol=2e-6,
                               rtol=1e-5)


def test_the_gated_norm_is_the_published_one():
    torch = pytest.importorskip("torch")
    module = pytest.importorskip(
        "transformers.models.qwen3_next.modeling_qwen3_next")
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    o, gate = (jax.random.normal(k, (7, H, DV)) for k in ks[:2])
    w = 1 + 0.3 * jax.random.normal(ks[2], (DV,))
    norm = module.Qwen3NextRMSNormGated(DV, eps=1e-6)
    norm.weight.data = torch.tensor(np.asarray(w))
    with torch.no_grad():
        theirs = norm(torch.tensor(np.asarray(o)),
                      torch.tensor(np.asarray(gate))).numpy()
    np.testing.assert_allclose(reference.gated_norm(o, gate, w, 1e-6),
                               theirs, atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(olmo_hybrid.gated_norm(o, gate, w, 1e-6),
                               theirs, atol=2e-6, rtol=1e-5)


def test_the_attention_block_is_olmo_3s_with_nothing_rotated():
    """``Olmo3DecoderLayer``: QK-norm over the whole projections, a norm
    after each sublayer and none before. Its rotation is handed cos = 1,
    sin = 0 (the identity: this family's ``rope_theta`` is null)."""
    torch = pytest.importorskip("torch")
    module = pytest.importorskip("transformers.models.olmo3.modeling_olmo3")
    from transformers.models.olmo3 import Olmo3Config

    params = make_params(0)
    E, Hq, D = CFG.hidden_size, CFG.num_heads, CFG.head_dim
    config = Olmo3Config(
        vocab_size=CFG.vocab_size, hidden_size=E,
        intermediate_size=CFG.intermediate_size, num_hidden_layers=1,
        num_attention_heads=Hq, num_key_value_heads=CFG.num_kv_heads,
        rms_norm_eps=CFG.rms_norm_eps, layer_types=["full_attention"],
        attention_bias=False)
    config._attn_implementation = "eager"
    layer = module.Olmo3DecoderLayer(config, 0).float().eval()

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    ap, lp = (jax.tree.map(lambda a: a[1], params[k])
              for k in ("gqa", "layers"))
    lp = jax.tree.map(lambda a: a[7], params["layers"])  # the 2nd gqa layer
    new = {
        "self_attn.q_proj.weight": t(ap["wq"].T),
        "self_attn.k_proj.weight": t(ap["wk"].reshape(E, -1).T),
        "self_attn.v_proj.weight": t(ap["wv"].reshape(E, -1).T),
        "self_attn.o_proj.weight": t(ap["wo"].reshape(Hq * D, E).T),
        "self_attn.q_norm.weight": t(ap["q_norm"].reshape(-1)),
        "self_attn.k_norm.weight": t(ap["k_norm"].reshape(-1)),
        "post_attention_layernorm.weight": t(lp["post_attn_norm"]),
        "post_feedforward_layernorm.weight": t(lp["post_mlp_norm"]),
        "mlp.gate_proj.weight": t(lp["w_gate"].T),
        "mlp.up_proj.weight": t(lp["w_up"].T),
        "mlp.down_proj.weight": t(lp["w_down"].T)}
    assert set(new) == set(layer.state_dict())
    layer.load_state_dict(new)
    T = 23
    x = jax.random.normal(jax.random.PRNGKey(2), (T, E))
    causal = torch.full((T, T), float("-inf")).triu(1)[None, None]
    with torch.no_grad():
        theirs = layer(
            t(x)[None], attention_mask=causal,
            position_embeddings=(torch.ones(1, T, D), torch.zeros(1, T, D)))
    theirs = (theirs[0] if isinstance(theirs, tuple) else theirs)[0].numpy()
    with jax.default_matmul_precision("highest"):
        ours = reference.block(
            x, reference._attention(x, ap, eps=CFG.rms_norm_eps), lp,
            CFG.rms_norm_eps)
    np.testing.assert_allclose(ours, theirs, atol=2e-5, rtol=1e-4)


# -- the model -----------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_dense_forward_matches_the_reference(seed):
    err = dense_errors(make_params(seed), prompt(45, seed))
    assert err.max() < LOGPROB_TOL, err.max()


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


def test_served_through_both_kernels_matches_the_reference(monkeypatch):
    """The same flow with the runner on its Pallas path for the recurrent
    layers (both kernels in interpret mode; attention stays on the XLA
    forms, which the CPU serves through)."""
    from production_stack_tpu.engine import model_runner

    def interpreted(fn):
        return lambda *a, **kw: fn(*a, **{**kw, "interpret": True})

    for name in ("gdn_ragged", "gdn_decode_step"):
        monkeypatch.setattr(gdn_pallas, name,
                            interpreted(getattr(gdn_pallas, name)))
    real = model_runner.ModelRunner._recur

    def recur(self, *a, **kw):
        was, self.use_pallas = self.use_pallas, True
        try:
            return real(self, *a, **kw)
        finally:
            self.use_pallas = was

    monkeypatch.setattr(model_runner.ModelRunner, "_recur", recur)
    eng = engine(make_params(0))
    prompts = {"long": prompt(41, 10), "short": prompt(5, 11)}
    out = serve(eng, prompts, max_tokens=8)
    for name, (toks, lps) in out.items():
        err = errors(eng.runner.params, prompts[name], toks, lps)
        assert err.max() < LOGPROB_TOL, (name, err)


def test_the_cache_is_a_pool_and_per_slot_state(served):
    eng = served[0]
    kv = eng.runner.kv
    assert set(kv) == {"kv", "state", "conv"}
    # 6 KV heads held as 8: a 16-row slab a token and attention layer
    assert kv["kv"].shape == (2, 64, BLOCK, 2 * 8, 16)
    assert kv["state"].shape == (6, 4, 2, 12, 40)  # two heads side by side
    assert kv["state"].dtype == jnp.float32
    assert kv["conv"].shape == (6, 4, 3, 4 * (12 + 12 + 20))
    cold = init_kv_cache(CFG, CacheConfig(block_size=BLOCK), one_device(),
                         num_blocks=8, slots=2)
    assert cold["state"].shape[1] == 2


def test_the_filled_heads_hold_zeros(served):
    """Heads 6 and 7 of the cache (keys) and 14, 15 (values) are the empty
    ones: nothing is ever written there."""
    kv = np.asarray(served[0].runner.kv["kv"])
    assert np.abs(kv[..., :6, :]).max() > 0
    assert not kv[..., 6:8, :].any() and not kv[..., 14:16, :].any()


def test_the_counters_say_what_ran(served):
    eng = served[0]
    s = eng.stats()
    assert s["gdn_decode_calls_total"] == 6 * eng.decode_dispatches
    assert s["gdn_chunk_tokens_total"] == 41 + 5
    assert s["gdn_chunk_spans_total"] == 3 + 1
    assert s["recurrent_state_resets_total"] == 2
    assert s["recurrent_state_bytes"] == CFG.recurrent_state_bytes(4) == (
        4 * 6 * (4 * 12 * 20 * 4 + 3 * 176 * 4))
    assert s["kv_pool_bytes"] == 64 * BLOCK * CFG.kv_bytes_per_token
    assert s["prefix_lookups_bypassed_total"] >= 2
    assert not [k for k in s if k.startswith(("kda_", "mamba_", "ssd_"))]
    text = "".join(
        f"{m.name} {[x.value for x in m.samples]}\n"
        for m in EngineStatsCollector(eng, "tiny").collect())
    for name in ("vllm:gdn_decode_calls", "vllm:gdn_chunk_tokens",
                 "vllm:gdn_chunk_spans", "vllm:recurrent_state_bytes",
                 "vllm:recurrent_state_resets", "vllm:kv_pool_bytes"):
        assert name + " " in text, name
    for other in ("kda", "mamba", "ssd"):
        assert f"vllm:{other}_decode_calls" not in text


# -- planted faults: each reads over the tolerance, and not by a hair ---------

def _prepare(**change):
    """``kda.prepare`` with one step of it changed."""
    def patch(monkeypatch):
        real = kda.prepare

        def prepare(q, k, v, g, beta, neg_eigval):
            if "beta" in change:      # not doubled
                neg_eigval = False
            if "decay" in change:     # dropped: alpha = 1
                g = jnp.zeros_like(g)
            a, kb, k1, q1, vb = real(q, k, v, g, beta, neg_eigval)
            if "scale" in change:     # q not scaled by d_k^-1/2
                q1 = q1 * q.shape[-1] ** 0.5
            return a, kb, k1, q1, vb

        monkeypatch.setattr(kda, "prepare", prepare)
    return patch


def _no_l2norm(monkeypatch):
    monkeypatch.setattr(kda, "l2norm", lambda x, eps=1e-6: x)


def _sigmoid_gate(monkeypatch):
    monkeypatch.setattr(
        olmo_hybrid, "gated_norm",
        lambda o, gate, w, eps: olmo_hybrid.rms_norm(
            o.astype(F32), w.astype(F32), eps) * jax.nn.sigmoid(
                gate.astype(F32)))


def _gate_before_norm(monkeypatch):
    monkeypatch.setattr(
        olmo_hybrid, "gated_norm",
        lambda o, gate, w, eps: olmo_hybrid.rms_norm(
            o.astype(F32) * jax.nn.silu(gate.astype(F32)), w.astype(F32),
            eps))


def _rotated(monkeypatch):
    """Rope on the attention layers' q and k."""
    real = llama._rms_norm_heads

    def rotate(x, w, eps):
        x = real(x, w, eps)
        pos = jnp.broadcast_to(jnp.arange(x.shape[-3], dtype=jnp.int32),
                               x.shape[:-2])
        return llama.apply_rope(x, pos, 10000.0, 1.0)

    monkeypatch.setattr(llama, "_rms_norm_heads", rotate)


def _per_head_qk_norm(monkeypatch):
    def per_head(x, w, eps):
        xf = x.astype(F32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        return (xf * jax.lax.rsqrt(var + eps) * w.astype(F32)).astype(x.dtype)

    monkeypatch.setattr(llama, "_rms_norm_heads", per_head)


def _pre_norm_params():
    """The weights as a block with its norms BEFORE the sublayers would
    hold them."""
    p = make_params(0)
    lp = p["layers"]
    return {**p, "layers": {
        **{k: v for k, v in lp.items() if not k.startswith("post_")},
        "attn_norm": lp["post_attn_norm"], "mlp_norm": lp["post_mlp_norm"]}}


FAULTS = {
    "beta not doubled": {"patch": _prepare(beta=1)},
    "the decay dropped": {"patch": _prepare(decay=1)},
    "q not scaled": {"patch": _prepare(scale=1)},
    "the L2 norm dropped": {"patch": _no_l2norm},
    "the gate a sigmoid": {"patch": _sigmoid_gate},
    "the gate before the norm": {"patch": _gate_before_norm},
    "the norms before the sublayers": {
        "cfg": dataclasses.replace(CFG, norms="pre"),
        "params": _pre_norm_params},
    "QK-norm per head": {"patch": _per_head_qk_norm},
    "a rotation applied": {"patch": _rotated},
    "QK-norm left out": {"cfg": dataclasses.replace(CFG, qk_norm=False)},
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


def test_fault_state_not_carried_across_a_chunk(monkeypatch):
    real = kda.stream_spans

    def forgetful(cu_q_lens, context_lens, T):
        slot, off, live, q_len, fresh = real(cu_q_lens, context_lens, T)
        return slot, off, live, q_len, fresh | (q_len > 1)

    def served_errors():
        eng = engine(make_params(0))
        ids = prompt(41, 10)
        toks, lps = serve(eng, {"a": ids}, max_tokens=8)["a"]
        return errors(eng.runner.params, ids, toks, lps)

    monkeypatch.setattr(kda, "stream_spans", forgetful)
    assert served_errors().max() > FAULT_TOL


def test_the_reference_in_a_bfloat16_state_is_another_result():
    """``chipbench/reference/control.py bf16_state`` at test size: the
    delta rule computed in bfloat16 (its state after every token, the
    decay, beta and the delta), all else float32, against the float32
    reference. With the stand-in's slow decay (``olmo_hybrid.STANDIN_A``)
    what the state holds reaches the logits, and exp(g) of a slow head
    rounds to 1: over the planted faults' tolerance, as the benchmark's
    probe has to find it over its own on the chip (PERF.md section 2)."""
    params, ids = make_params(0), prompt(192, 3)
    want = np.asarray(reference.logprobs(HF, params, ids, 0))
    got = np.asarray(reference.logprobs(HF, params, ids, 0,
                                        state_dtype="bfloat16"))
    assert np.abs(got - want).max() > FAULT_TOL
    assert float(np.exp(params["gdn"]["a_log"]).max()) == pytest.approx(
        olmo_hybrid.STANDIN_A)


# -- the benchmark's arithmetic ------------------------------------------------

def test_shapes_gdn_counts_the_published_model():
    s, hf = shapes_gdn, PUBLISHED
    assert s.gdn_params(hf) == 88_750_332
    assert s.attn_params(hf) == 58_990_080
    assert s.mlp_params(hf) == 126_812_160
    assert (s.layer_params(hf, "linear_attention"),
            s.layer_params(hf, "full_attention")) == (215_570_172,
                                                      185_809_920)
    assert s.head_params(hf) * 2 + hf["hidden_size"] == 770_707_200
    assert s.state_bytes_per_slot(hf) == 2_211_840
    assert s.conv_tail_bytes_per_slot(hf) == 69_120
    # as published: 30 heads' keys and values; the cache holds 32
    assert s.kv_bytes_per_token(CUT) == 61_440
    cfg = ModelConfig.from_hf_config(CUT)
    assert cfg.kv_bytes_per_token == 65_536
    assert 64 * 12 * (s.state_bytes_per_slot(hf)
                      + s.conv_tail_bytes_per_slot(hf)) == (
        cfg.recurrent_state_bytes(64))
    # the issue's table: a decode step at 64 slots and ~600 tokens each
    step = s.decode_step_bytes(CUT, 64, 64 * 600)
    assert step == pytest.approx(13.2e9, rel=0.02)
