"""Phi-4-mini-flash-reasoning's stack (SambaY: state-space layers, window
and full differential attention, cross-attention over one shared cache,
gated memory units) through the shared stack walker and the serving
engine, against the plain reference the benchmark uses on the chip
(chipbench/reference/phi4_flash.py), on seeded random weights at test size
(the ``tiny-phi4flash`` preset: 12 blocks = 3 x (Mamba, window 8) +
(Mamba, full) + 2 x (GMU, cross), blocks of 4 tokens), float32, CPU.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import shapes_mamba
from chipbench.reference import phi4_flash as reference
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
    window_pool_blocks,
)
from production_stack_tpu.engine.metrics import EngineStatsCollector
from production_stack_tpu.engine.model_runner import ModelRunner
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Scheduler
from production_stack_tpu.engine.sequence import Sequence
from production_stack_tpu.engine.tracing import WindowCounters
from production_stack_tpu.engine.weights import init_random
from production_stack_tpu.models import llama, sambay
from production_stack_tpu.ops import kda, mamba, mamba_pallas
from production_stack_tpu.ops.attention import dense_causal_attention
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "chipbench", "configs",
                       "phi-4-mini-flash-reasoning", "config.json")) as f:
    PUBLISHED = json.load(f)
with open(os.path.join(ROOT, "chipbench", "tests", "configs",
                       "tiny-phi4-flash", "config.json")) as f:
    HF = json.load(f)
CFG = MODEL_PRESETS["tiny-phi4flash"]
WINDOW, BLOCK = CFG.sliding_window, 4
# float32 on the CPU on both sides; the served path differs from the
# reference in the order of its sums only
LOGPROB_TOL = 1e-4
# chipbench/run.py's limits, which every cell's `correct` is held to
CELL_TOL, CELL_MEAN_TOL = 0.15, 0.03
BUDGET = 16  # tokens a ragged step: two windows, four blocks
F32 = jnp.float32


def one_device():
    return build_mesh(MeshConfig(), devices=jax.devices()[:1])


def make_params(seed=0):
    return llama.init_params(CFG, jax.random.PRNGKey(seed))


def engine(params=None, slots=4, num_blocks=64, budget=BUDGET, **over):
    return LLMEngine(
        EngineConfig(
            model=dataclasses.replace(CFG, **over),
            cache=CacheConfig(block_size=BLOCK, num_blocks=num_blocks),
            scheduler=SchedulerConfig(max_num_seqs=slots,
                                      max_num_batched_tokens=budget),
            mesh=MeshConfig(data=1, tensor=1)),
        mesh=one_device(), params=params)


def serve(eng, prompts, max_tokens=12, watch=None):
    """{request: (tokens, [logprob of each token])} through the engine;
    ``watch(eng)`` runs after every step."""
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
        if watch is not None:
            watch(eng)
    return {n: (toks[n], lps[n]) for n in prompts}


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).tolist()


def made(eng):
    """The tree as it is made, which the reference reads by shape; the
    runner keeps its own laid out (engine/weights.py ``lay_out``), made
    from the same seed: what chipbench/reference/compare.py does."""
    r = eng.runner
    return init_random(r.cfg, r.mesh, r.rules, r.config.seed)


def errors(params, ids, toks, lps, hf=HF):
    """|served - reference| log-probability of every generated token."""
    full = list(ids) + list(toks)
    want = np.asarray(reference.logprobs(hf, params, full[:-1], len(ids) - 1))
    return np.abs(np.asarray([want[j, t] for j, t in enumerate(toks)])
                  - np.asarray(lps))


def dense_errors(params, ids, cfg=CFG):
    """|dense forward - reference| over every row and vocabulary entry."""
    got = llama.forward_dense(cfg, params, jnp.asarray([ids]))
    want = np.asarray(reference.logprobs(HF, params, ids, 0))
    return np.abs(np.asarray(jax.nn.log_softmax(got[0], -1)) - want)


def over_a_limit(err, margin=1.5) -> bool:
    """`correct` would be false, and not by a hair: one of the cell's two
    limits is passed by half again."""
    return bool(err.max() > margin * CELL_TOL
                or err.mean() > margin * CELL_MEAN_TOL)


# -- the configuration ---------------------------------------------------------

def test_the_published_file_gives_the_stack_the_issue_describes():
    cfg = ModelConfig.from_hf_config(PUBLISHED, "phi")
    kinds = cfg.layer_kinds
    assert len(kinds) == 32 and cfg.architecture == "phi4flash"
    assert kinds[0:18:2] == ("mamba",) * 9 and kinds[1:16:2] == ("swa",) * 8
    assert kinds[17] == "full"
    assert kinds[18::2] == ("gmu",) * 7 and kinds[19::2] == ("cross",) * 7
    assert cfg.stack_segments == ((("mamba", "swa"), 8),
                                  (("mamba", "full"), 1),
                                  (("gmu", "cross"), 7))
    assert (cfg.mamba_inner, cfg.mamba_state, cfg.mamba_conv,
            cfg.mamba_dt_rank) == (5120, 16, 4, 160)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (40, 20, 64)
    assert cfg.sliding_window == 512 and cfg.window_binds
    assert cfg.cache_layers == 9 and cfg.num_recurrent_layers == 9
    # a pair is one cache head of 128; 10 pairs filled up to 12 (whole
    # 8-row tiles of a token's 2 x heads slab), G = 4 as before
    assert (cfg.cache_kv_heads, cfg.cache_head_dim, cfg.q_per_kv) == (
        12, 128, 4)
    assert cfg.kv_pool_shape(7, 16) == (1, 7, 16, 24, 128)
    assert cfg.kv_pool_shape(7, 16, window=True) == (8, 7, 16, 24, 128)
    assert cfg.kv_bytes_per_token == 6144
    assert cfg.window_kv_bytes_per_token == 8 * 6144
    assert cfg.recurrent_state_bytes(64) == 9 * 64 * 5120 * (16 * 4 + 3 * 2)
    assert cfg.tie_word_embeddings and cfg.layer_norm and cfg.diff_attn


def test_the_tiny_preset_follows_the_published_rule():
    assert CFG.layer_kinds == ("mamba", "swa") * 3 + ("mamba", "full") + (
        "gmu", "cross") * 2
    assert ModelConfig.from_hf_config(HF).layer_kinds == CFG.layer_kinds
    assert reference.kinds(HF) == list(CFG.layer_kinds)
    assert shapes_mamba.layer_kinds(HF) == list(CFG.layer_kinds)


@pytest.mark.parametrize("name,segments,attn,cache,recurrent", [
    ("tiny-llama", None, 2, 2, 0), ("tiny-ouro", None, 3, 12, 0),
    ("tiny-pangu", None, 3, 3, 0), ("tiny-phi4flash", 3, 4, 4, 4)])
def test_one_description_of_layer_kinds_yields_the_counts(
        name, segments, attn, cache, recurrent):
    cfg = MODEL_PRESETS[name]
    assert (cfg.num_attn_layers, cfg.cache_layers,
            cfg.num_recurrent_layers) == (attn, cache, recurrent)
    assert cfg.has_recurrent_state == bool(recurrent)
    if segments is None:
        assert set(cfg.layer_kinds) == {"attn"} and not cfg.window_binds
    else:
        assert len(cfg.stack_segments) == segments


def test_solar_open2s_pattern_comes_from_the_same_description():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "solar-open2-250b-ep16-l8", "config.json")) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))
    assert cfg.stack_segments == ((("gqa", "kda", "kda", "kda"), 2),)
    assert (cfg.num_attn_layers, cfg.num_kda_layers, cfg.cache_layers) == (
        2, 6, 2)
    assert cfg.kv_bytes_per_token == 8192 and not cfg.window_binds
    assert cfg.recurrent_state_bytes(64) == 1667235840


@pytest.mark.parametrize("change,match", [
    ({"mb_per_layer": 4}, "mb_per_layer=4"),
    ({"num_hidden_layers": 30}, "num_hidden_layers=30"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"num_key_value_heads": 5}, "do not pair up"),
    ({"tie_word_embeddings": False}, "untied head")])
def test_what_is_not_computed_is_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config({**PUBLISHED, **change})


def test_a_window_that_binds_is_served_past_the_window_and_others_not():
    assert engine().config.model.max_model_len > WINDOW
    with pytest.raises(ValueError, match="exceeds the local-attention"):
        ModelRunner(EngineConfig(model=dataclasses.replace(
            MODEL_PRESETS["tiny-mistral"], max_model_len=1024),
            cache=CacheConfig(num_blocks=16)), one_device())


def _engine_config(**over):
    cfg = EngineConfig(
        model=CFG, cache=CacheConfig(block_size=BLOCK, num_blocks=32),
        scheduler=SchedulerConfig(max_num_seqs=2, max_num_batched_tokens=16),
        mesh=MeshConfig(data=1, tensor=1))
    for k, v in over.items():
        obj, _, field = k.rpartition(".")
        setattr(getattr(cfg, obj) if obj else cfg, field, v)
    return cfg


@pytest.mark.parametrize("over,match", [
    ({"scheduler.spec_ngram_k": 2}, "n-gram speculative"),
    ({"role": "prefill"}, "role=prefill"),
    ({"cache.host_offload_blocks": 8}, "host or remote KV tier"),
    ({"cache.remote_kv_url": "http://x"}, "host or remote KV tier")])
def test_what_would_move_or_guess_at_state_or_blocks_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        LLMEngine(_engine_config(**over), mesh=one_device())


def test_quant_a_mesh_and_lora_are_refused_by_name():
    cfg = _engine_config()
    cfg.model = dataclasses.replace(CFG, quant="int8")
    with pytest.raises(ValueError, match="quant=int8"):
        LLMEngine(cfg, mesh=one_device())
    cfg = _engine_config()
    cfg.mesh = MeshConfig(data=1, tensor=2)
    with pytest.raises(ValueError, match="a mesh of 2 devices"):
        LLMEngine(cfg, mesh=build_mesh(cfg.mesh, devices=jax.devices()[:2]))
    with pytest.raises(ValueError, match="LoRA adapters"):
        engine().runner.register_lora(1, {})


def test_a_checkpoint_is_refused_not_guessed_at(tmp_path):
    from production_stack_tpu.engine.weights import load_safetensors

    cfg = dataclasses.replace(CFG, weights_path=str(tmp_path))
    with pytest.raises(ValueError, match="tensor names are not mapped"):
        load_safetensors(cfg, one_device(), None)


# -- the scan in its three forms, and the kernels ------------------------------

def _rows(key, T, di=256, N=16):
    ks = jax.random.split(key, 4)
    return (jax.random.normal(ks[0], (T, di), F32),
            jax.nn.softplus(jax.random.normal(ks[1], (T, di), F32) - 2),
            jax.random.normal(ks[2], (T, N), F32),
            jax.random.normal(ks[3], (T, N), F32))


A = -jnp.arange(1, 17, dtype=F32)[:, None] * jnp.ones((1, 256), F32)
RAGGED = {"xla": mamba.scan_ragged,
          "pallas": lambda *a: mamba_pallas.mamba_ragged(*a, interpret=True)}
DECODE = {"xla": mamba.scan_decode,
          "pallas": lambda *a: mamba_pallas.mamba_decode_step(
              *a, interpret=True)}


@pytest.mark.parametrize("impl", sorted(RAGGED))
@pytest.mark.parametrize("cuts", [(17, 39), (1, 2), (8, 16), (33, 34)])
def test_a_span_continues_its_slots_state_across_chunks(impl, cuts):
    """40 rows of one sequence in slot 1 as three spans (a chunk, a chunk,
    and the rest: a one-row span goes through the decode kernel), beside a
    fresh 5-row sequence in slot 3, against the dense scan."""
    rows, other = _rows(jax.random.PRNGKey(0), 40), _rows(
        jax.random.PRNGKey(1), 5)
    want = mamba.scan_dense(A, *(r[None] for r in rows))[0]
    want_other = mamba.scan_dense(A, *(r[None] for r in other))[0]
    state = jnp.full((2, 4, 16, 256), 7.0, F32)  # what a reset must clear
    lo = 0
    for i, hi in enumerate((*cuts, 40)):
        part = [r[lo:hi] for r in rows]
        if i == 0:
            part = [jnp.concatenate([p, o]) for p, o in zip(part, other)]
        n, extra = hi - lo, 5 if i == 0 else 0
        cu = jnp.asarray([0, 0, n, n, n + extra], jnp.int32)
        ctx = jnp.asarray([0, hi, 0, extra], jnp.int32)
        part = [jnp.pad(p, ((0, 48 - p.shape[0]), (0, 0))) for p in part]
        y, state = RAGGED[impl](state, 1, A, *part, cu, ctx)
        np.testing.assert_allclose(y[:n], want[lo:hi], rtol=2e-5, atol=2e-5)
        if extra:
            np.testing.assert_allclose(y[n:n + 5], want_other, rtol=2e-5,
                                       atol=2e-5)
        assert float(jnp.abs(y[n + extra:]).max()) == 0.0
        lo = hi
    # the other layer and the slots without a span are as they were
    assert float(state[0].min()) == 7.0 and float(state[1, 0].min()) == 7.0


@pytest.mark.parametrize("impl", sorted(DECODE))
def test_the_decode_form_moves_live_slots_alone(impl):
    rows = _rows(jax.random.PRNGKey(2), 6)
    want = mamba.scan_dense(A, *(r[None] for r in rows))[0]
    state = jnp.zeros((2, 4, 16, 256), F32).at[1, 0].set(3.0)
    live = jnp.asarray([False, False, True, False])
    for t in range(6):
        part = [jnp.zeros((4, r.shape[1]), F32).at[2].set(r[t]) for r in rows]
        y, state = DECODE[impl](state, 1, A, *part, live)
        np.testing.assert_allclose(y[2], want[t], rtol=2e-5, atol=2e-5)
        assert float(jnp.abs(y[jnp.asarray([0, 1, 3])]).max()) == 0.0
    assert float(state[1, 0].min()) == 3.0 and float(
        jnp.abs(state[0]).max()) == 0.0


# -- a window in both attention kernels (interpret mode) against the XLA forms --

def _paged_case(window, q_lens, ctx):
    """A pool whose blocks wholly below each walk's floor are poisoned
    with NaN: a kernel that fetched or scored one would return NaN."""
    from production_stack_tpu.ops.paged_attention import (
        paged_attention,
        ragged_paged_attention,
    )

    KH, G, D, bs, N, S, M = 2, 4, 128, 16, 64, 4, 16
    cache = jax.random.normal(jax.random.PRNGKey(0), (2, N, bs, 2 * KH, D),
                              F32)
    tables = np.random.default_rng(0).permutation(N)[:S * M].reshape(S, M)
    poisoned = np.array(cache)
    for s in range(S):
        if window and q_lens[s]:
            floor = max(ctx[s] - q_lens[s] - (window - 1), 0)
            poisoned[:, tables[s, :floor // bs]] = np.nan
    return (KH * G, D, bs, cache, jnp.asarray(poisoned),
            jnp.asarray(tables, jnp.int32), paged_attention,
            ragged_paged_attention)


@pytest.mark.parametrize("q_tile", [None, 32])
@pytest.mark.parametrize("window", [0, 24, 40, 100])
def test_the_ragged_kernel_walks_from_the_windows_floor(window, q_tile):
    from production_stack_tpu.ops.ragged_paged_attention_pallas import (
        count_windows,
        ragged_paged_attention_pallas,
    )

    # a 70-row chunk ending at 150, a decode row at 97, an idle slot, a
    # fresh 30-row prompt
    q_lens, ctx = np.array([70, 1, 0, 30]), np.array([150, 97, 0, 30])
    H, D, bs, cache, poisoned, tables, _, xla = _paged_case(
        window, q_lens, ctx)
    cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
    T = 128
    pos, sid = np.full(T, -1, np.int32), np.zeros(T, np.int32)
    for s in range(4):
        pos[cu[s]:cu[s + 1]] = np.arange(ctx[s] - q_lens[s], ctx[s])
        sid[cu[s]:cu[s + 1]] = s
    q = jax.random.normal(jax.random.PRNGKey(1), (T, H, D), F32)
    want = xla(q, cache[1], tables, jnp.asarray(ctx, jnp.int32),
               jnp.asarray(sid), jnp.asarray(pos), window=window)
    got = ragged_paged_attention_pallas(
        q, poisoned, tables, jnp.asarray(cu), jnp.asarray(ctx, jnp.int32), 1,
        q_tile=q_tile, windows=2, interpret=True, window=window)
    assert float(jnp.abs(got - want)[pos >= 0].max()) < 2e-5
    # the host's count follows the same floor: fewer windows, none interior
    full = count_windows(cu, ctx, T, 4, bs, windows=2)
    mine = count_windows(cu, ctx, T, 4, bs, windows=2, window=window)
    assert mine == full if not window else (
        mine[0] <= full[0] and mine[1] == 0)
    if window == 24:
        assert mine[0] < full[0]


@pytest.mark.parametrize("window", [0, 24, 40, 100])
def test_the_decode_kernel_walks_from_each_sequences_floor(window):
    from production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_pallas,
    )

    # one cell of four sequences of very different lengths: each starts
    # at the context window that holds its own floor
    ctx = np.array([150, 97, 0, 5])
    H, D, bs, cache, poisoned, tables, xla, _ = _paged_case(
        window + 1 if window else 0, np.ones(4, int), ctx)
    q = jax.random.normal(jax.random.PRNGKey(2), (4, H, D), F32)
    want = xla(q[:, None], cache[1], tables, jnp.asarray(ctx, jnp.int32),
               jnp.asarray(ctx - 1, jnp.int32)[:, None], window=window)[:, 0]
    got = paged_decode_attention_pallas(
        q, poisoned, tables, jnp.asarray(ctx, jnp.int32), 1, windows=2,
        interpret=True, window=window)
    assert float(jnp.abs(got - want)[ctx > 0].max()) < 2e-5


# -- differential attention: the packed heads against four softmaxes ------------

@pytest.mark.parametrize("window", [0, WINDOW])
@pytest.mark.parametrize("depth", [1, 7, 11])
def test_packed_heads_give_the_four_softmax_form(window, depth):
    params = make_params(3)
    ap = jax.tree.map(lambda a: a[1], params["attn"])
    x = jax.random.normal(jax.random.PRNGKey(depth), (1, 30, 128), F32)
    q = sambay.packed_queries(CFG, ap, x)
    k, v = sambay.packed_keys_values(CFG, ap, x)
    assert q.shape == (1, 30, 16, 32) and k.shape == (1, 30, 4, 32)
    # the empty heads hold zeros
    assert float(jnp.abs(q[..., 4:, :]).max()) == 0.0
    assert float(jnp.abs(k[..., 1:, :]).max()) == 0.0
    o = dense_causal_attention(q, k, v, window=window)
    got = sambay.diff_combine(CFG, ap, o, depth)[0]
    rk, rv = reference._keys_values(x[0], ap)
    want = reference._diff_attention(
        x[0], ap, rk, rv, 0.8 - 0.6 * np.exp(-0.3 * depth), window=window,
        eps=1e-5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


# -- the dense forward and the served path against the reference ----------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_dense_forward_matches_the_reference(seed):
    err = dense_errors(make_params(seed), prompt(45, seed))
    assert err.max() < LOGPROB_TOL, err.max()


@pytest.fixture(scope="module")
def served():
    """A 41-token prompt (five windows: three chunks of the 16-token budget)
    and a 5-token one interleaved in one stream, then 24 decode steps: the
    long context ends eight windows, sixteen blocks long."""
    eng = engine()
    prompts = {"long": prompt(41, 10), "short": prompt(5, 11)}
    return eng, prompts, serve(eng, prompts, max_tokens=24)


@pytest.mark.parametrize("name", ["long", "short"])
def test_served_logprobs_match_the_reference_at_every_row(served, name):
    eng, prompts, out = served
    toks, lps = out[name]
    err = errors(made(eng), prompts[name], toks, lps)
    assert len(toks) == 24 and err.max() < LOGPROB_TOL, err


@pytest.mark.parametrize("budget,plen", [(8, 41), (16, 33), (32, 70),
                                         (16, 16), (12, 9)])
def test_chunk_block_and_window_edges(budget, plen):
    """Chunks that end on and off block and window edges (block 4, window
    8), the decode rows after them crossing more of both."""
    eng = engine(budget=budget)
    ids = prompt(plen, plen)
    toks, lps = serve(eng, {"a": ids}, max_tokens=10)["a"]
    assert errors(made(eng), ids, toks, lps).max() < LOGPROB_TOL


def test_the_decode_program_and_the_ragged_one_agree(served):
    """Both step programs ran: a decode-only step takes the decode
    program, a step with a prompt's chunk the ragged one."""
    eng = served[0]
    assert eng.decode_dispatches > 0 and eng.ragged_dispatches > 0


# -- blocks of two kinds --------------------------------------------------------

def test_the_cache_is_of_two_pools_and_per_slot_state():
    cache = CacheConfig(block_size=BLOCK, num_blocks=8)
    kv = init_kv_cache(CFG, cache, one_device(), slots=3, window_blocks=5)
    assert set(kv) == {"kv", "win", "state", "conv"}
    assert kv["kv"].shape == (1, 8, 4, 2 * 4, 32)    # the full layer's
    assert kv["win"].shape == (3, 5, 4, 2 * 4, 32)   # the window layers'
    assert kv["state"].shape == (4, 3, 16, 256)
    assert kv["state"].dtype == jnp.float32
    assert kv["conv"].shape == (4, 3, 3, 256)
    assert CFG.recurrent_state_bytes(3) == (kv["state"].nbytes
                                            + kv["conv"].nbytes)
    assert CFG.kv_bytes_per_token * 8 * 4 == kv["kv"].nbytes
    assert CFG.window_kv_bytes_per_token * 5 * 4 == kv["win"].nbytes


def test_the_window_pool_is_sized_from_the_configuration():
    pub = ModelConfig.from_hf_config(PUBLISHED)
    assert window_pool_blocks(pub, 16, 64, 2048) == 64 * 35 + 160 == 2400
    assert window_pool_blocks(CFG, BLOCK, 4, 16) == 4 * 5 + 6
    assert window_pool_blocks(MODEL_PRESETS["tiny-llama"], 16, 64, 2048) == 0
    assert engine().runner.window_blocks == 26


def test_window_blocks_are_held_to_the_bound_and_given_back(served):
    held = {"decode": 0, "prefill": 0, "global": 0}

    def watch(eng):
        for s in eng.scheduler.seqs.values():
            n = len(s.window_block_ids) - s.window_released
            # a decode step trims before it grows; what a prompt's last
            # chunk held stays until the sequence's first decode step
            kind = "decode" if len(s.output_token_ids) > 1 else "prefill"
            held[kind] = max(held[kind], n)
            held["global"] = max(held["global"], len(s.block_ids))

    eng = engine()
    serve(eng, {"long": prompt(41, 10), "short": prompt(5, 11)},
          max_tokens=24, watch=watch)
    # a decoding sequence: window / block + 2; a chunk: (chunk + window - 1)
    # / block + 1; the other pool keeps every block of the context
    assert 0 < held["decode"] <= WINDOW // BLOCK + 2
    assert held["prefill"] <= (BUDGET + WINDOW - 1) // BLOCK + 1
    # (the last token sampled is never fed back: 41 + 23 rows)
    assert held["global"] == -(-(41 + 23) // BLOCK)
    sched = eng.scheduler
    assert sched.window_allocator.num_free_blocks == 26
    assert sched.allocator.num_free_blocks == 64


@pytest.mark.parametrize("chunk", [1, 3, 4, 8, 16])
def test_trimming_keeps_what_the_next_row_can_see(chunk):
    sched = Scheduler(SchedulerConfig(max_num_seqs=2,
                                      max_num_batched_tokens=16),
                      CacheConfig(block_size=BLOCK), 64, window=WINDOW,
                      window_blocks=64)
    seq = Sequence("a", prompt(120), SamplingParams(max_tokens=4))
    for computed in range(0, 100, chunk):
        seq.num_computed_tokens = computed
        assert sched._extend(seq, computed + chunk)
        first_seen = max(computed - (WINDOW - 1), 0)
        assert seq.window_released == first_seen // BLOCK
        assert len(seq.window_block_ids) == len(seq.block_ids) == -(
            -(computed + chunk) // BLOCK)
        assert (len(seq.window_block_ids) - seq.window_released
                <= (chunk + WINDOW - 1) // BLOCK + 2)
        live = seq.window_block_ids[seq.window_released:]
        assert len(set(live)) == len(live)
    sched._release(seq)
    assert sched.window_allocator.num_free_blocks == 64


def test_a_dry_window_pool_makes_a_decode_row_preempt_and_both_kinds_go():
    sched = Scheduler(SchedulerConfig(max_num_seqs=2,
                                      max_num_batched_tokens=16),
                      CacheConfig(block_size=BLOCK), 64, window=WINDOW,
                      window_blocks=3)
    seq = Sequence("a", prompt(30), SamplingParams(max_tokens=4))
    assert not sched._extend(seq, 16)  # 4 blocks of a pool of 3
    assert len(seq.block_ids) == 4 and len(seq.window_block_ids) == 3
    sched._release(seq)
    assert (sched.allocator.num_free_blocks,
            sched.window_allocator.num_free_blocks) == (64, 3)


def test_preemption_frees_both_kinds_and_resumes_exactly():
    """A global pool too small for two long sequences: the younger is
    preempted while decoding, gives back blocks of both kinds, starts
    again from position 0 with a zero state, and both read as the
    reference says."""
    params = make_params(5)
    eng = engine(params=params, slots=2, num_blocks=22)
    prompts = {"a": prompt(30, 1), "b": prompt(30, 2)}
    out = serve(eng, prompts, max_tokens=20)
    assert eng.stats()["recurrent_state_resets_total"] > 2  # a resume
    for name, ids in prompts.items():
        toks, lps = out[name]
        assert len(toks) == 20
        assert errors(params, ids, toks, lps).max() < LOGPROB_TOL
    assert eng.scheduler.window_allocator.num_free_blocks == (
        eng.runner.window_blocks)


def test_prefix_lookups_are_answered_miss(served):
    eng = served[0]
    assert eng.scheduler.allocator.bypass_prefix
    assert eng.scheduler.window_allocator.bypass_prefix
    assert eng.stats()["prefix_lookups_bypassed_total"] >= 2


# -- what the engine counts -----------------------------------------------------

def test_the_counters_say_what_ran(served):
    eng = served[0]
    s = eng.stats()
    steps = eng.decode_dispatches
    assert s["mamba_decode_calls_total"] == 4 * steps
    assert s["mamba_chunk_tokens_total"] == 41 + 5
    assert s["mamba_chunk_spans_total"] == 3 + 1
    assert s["recurrent_state_resets_total"] == 2
    assert s["recurrent_state_bytes"] == CFG.recurrent_state_bytes(4)
    assert "kda_decode_calls_total" not in s
    assert s["shared_kv_attn_calls_total"] == 2 * (
        steps + eng.ragged_dispatches)
    assert (0 < s["window_attn_read_tokens_total"]
            < s["window_attn_context_tokens_total"])
    assert s["kv_bytes_per_token"] == CFG.kv_bytes_per_token
    assert s["window_kv_blocks_total"] == 26
    assert s["decode_attn_calls_total"] == 6 * steps  # 3 + 1 + 2 cross


@pytest.mark.parametrize("plain,windowed,calls", [
    (False, False, 0), (False, True, 3), (True, False, 3), (True, True, 6)])
def test_slab_calls_are_counted_by_call_kind(plain, windowed, calls):
    """vllm:decode_attn_slab_calls_total where a stack's layers differ: the
    three window layers' calls count when the kernel's predicate holds
    under the window, the full layer's and the two cross layers' when it
    holds without one (on the CPU the runner says neither: set by hand)."""
    eng = engine()
    assert (eng.runner.decode_attn_slab,
            eng.runner.decode_attn_slab_windowed) == (False, False)
    eng.runner.decode_attn_slab = plain
    eng.runner.decode_attn_slab_windowed = windowed
    serve(eng, {"one": prompt(9, 3)}, max_tokens=5)
    s = eng.stats()
    assert eng.decode_dispatches > 0
    assert s["decode_attn_calls_total"] == 6 * eng.decode_dispatches
    assert s["decode_attn_slab_calls_total"] == calls * eng.decode_dispatches


@pytest.mark.parametrize("ctx,read", [(5, 8), (8, 8), (9, 12), (64, 8),
                                      (66, 12)])
def test_a_decode_rows_window_walk_in_tokens(ctx, read):
    w = WindowCounters(CFG, BLOCK)
    w.record_decode(np.asarray([ctx, 0]), iterations=2)
    layers = 3 * 2
    assert w.read_tokens == layers * read
    assert w.context_tokens == layers * -(-ctx // BLOCK) * BLOCK
    assert w.shared_kv_calls == 2 * 2


def test_the_metrics_export_the_new_families(served):
    eng = served[0]
    text = "".join(
        f"{m.name} {[s.value for s in m.samples]}\n"
        for m in EngineStatsCollector(eng, "tiny").collect())
    for name in ("vllm:mamba_decode_calls", "vllm:mamba_chunk_tokens",
                 "vllm:mamba_chunk_spans", "vllm:recurrent_state_bytes",
                 "vllm:recurrent_state_resets",
                 "vllm:window_attn_context_tokens",
                 "vllm:window_attn_read_tokens", "vllm:shared_kv_attn_calls",
                 "vllm:window_kv_blocks_total", "vllm:window_kv_blocks_free",
                 "vllm:kv_bytes_per_token"):
        assert name + " " in text, name
    assert "vllm:kda_decode_calls" not in text


# -- planted faults: each reads over the cell's limits by a stated margin -------

def test_fault_the_window_ignored(monkeypatch):
    def attend(cfg):
        def call(q, k, v, caches, layer_idx, kind=None):
            return dense_causal_attention(q, k, v), caches
        return call

    params, ids = make_params(0), prompt(45, 0)
    assert dense_errors(params, ids).max() < LOGPROB_TOL
    monkeypatch.setattr(llama, "dense_attend", attend)
    assert over_a_limit(dense_errors(params, ids), FAULT_MARGIN["window"])


def test_fault_lambda_init_constant(monkeypatch):
    monkeypatch.setattr(sambay, "lambda_init",
                        lambda depth: jnp.asarray(0.8, F32))
    assert over_a_limit(dense_errors(make_params(0), prompt(45, 0)),
                        FAULT_MARGIN["lambda_init"])


def test_fault_m_taken_after_the_gate(monkeypatch):
    real = sambay.mamba_mixer

    def gated(cfg, mp, x, recur, caches, m_idx):
        out, y, caches = real(cfg, mp, x, recur, caches, m_idx)
        z = jnp.einsum("...te,ef->...tf", x, mp["w_in"])[..., cfg.mamba_inner:]
        return out, y * jax.nn.silu(z), caches

    monkeypatch.setattr(sambay, "mamba_mixer", gated)
    assert over_a_limit(dense_errors(make_params(0), prompt(45, 0)),
                        FAULT_MARGIN["m_after_gate"])


def _served_errors(params=None, plen=41):
    eng = engine(params=params if params is not None else make_params(0))
    ids = prompt(plen, 10)
    toks, lps = serve(eng, {"a": ids}, max_tokens=24)["a"]
    return errors(eng.runner.params, ids, toks, lps)  # the handed tree


def test_fault_state_not_carried_across_a_chunk(monkeypatch):
    real = mamba.stream_spans

    def forgetful(cu_q_lens, context_lens, T):
        slot, off, live, q_len, fresh = real(cu_q_lens, context_lens, T)
        return slot, off, live, q_len, fresh | (q_len > 1)

    # with the skip term D x off, so that a state-space layer's output is
    # its state's alone (at D = 1 the stand-in's rows lean on the skip term
    # and this fault reads AT the limits, 0.08-0.12 / 0.024-0.033: PERF.md
    # section 7)
    params = make_params(0)
    params["mamba"] = {**params["mamba"],
                       "d": jnp.zeros_like(params["mamba"]["d"])}
    assert _served_errors(params).max() < LOGPROB_TOL
    monkeypatch.setattr(mamba, "stream_spans", forgetful)
    assert over_a_limit(_served_errors(params), FAULT_MARGIN["state"])


def test_fault_cross_blocks_read_a_window_layers_cache(monkeypatch):
    real = ModelRunner._attend_kind

    def wrong(self, attend, kind, q, k, v, caches, layer_idx, *a, **kw):
        if kind != "cross":
            return real(self, attend, kind, q, k, v, caches, layer_idx, *a,
                        **kw)
        # the last window layer's rows, by the window pool's table
        out, _ = real(self, attend, "swa", q, k, v, caches, 2, *a, **kw)
        return out, caches

    monkeypatch.setattr(ModelRunner, "_attend_kind", wrong)
    # a cross layer must not write; with "swa" it would: keep the pool
    monkeypatch.setattr(
        "production_stack_tpu.engine.model_runner.write_kv",
        lambda caches, *a, **kw: caches, raising=True)
    assert over_a_limit(_served_errors(), FAULT_MARGIN["shared_cache"])


# by how much over a limit (x the limit) each planted fault has to read.
# Read on the CPU, float32, seed 0 (largest / mean; limits 0.15 / 0.03):
# the window ignored 1.42 / 0.175; lambda_init constant 1.12 / 0.162; m
# taken after the gate 0.74 / 0.101; the cross blocks reading a window
# layer's cache 0.89 / 0.325; the state not carried across a chunk (skip
# term off) 0.48 / 0.092
FAULT_MARGIN = {"window": 4.0, "lambda_init": 4.0, "m_after_gate": 3.0,
                "state": 2.0, "shared_cache": 4.0}


# -- chipbench/shapes_mamba.py against the issue's parameter table ---------------

def test_shapes_mamba_counts_the_published_model():
    s = shapes_mamba
    assert dict(s.layer_counts(PUBLISHED)) == {
        "mamba": 9, "swa": 8, "full": 1, "gmu": 7, "cross": 7}
    mlp = s.mlp_params(PUBLISHED)
    assert mlp == 78_643_200
    per = s.mixer_params(PUBLISHED)
    table = {"mamba": 119.9, "swa": 98.3, "full": 98.3, "gmu": 104.9,
             "cross": 91.8}
    for kind, millions in table.items():
        assert (per[kind] + mlp) / 1e6 == pytest.approx(millions, abs=0.06)
    assert s.total_params(PUBLISHED) / 1e6 == pytest.approx(3853, abs=1.5)
    assert s.state_bytes_per_slot(PUBLISHED) == 5120 * 16 * 4
    assert s.conv_tail_bytes_per_slot(PUBLISHED) == 3 * 5120 * 2
    assert s.kv_bytes_per_token_layer(PUBLISHED) == 5120
    # the issue's decode step: 64 rows at a mean context of 2 k
    step = s.decode_step_bytes(PUBLISHED, 64, 64 * 512, 64 * 2048)
    assert step / 1e9 == pytest.approx(14.8, abs=0.1)
    assert s.decode_step_floor_s(PUBLISHED, 64, 64 * 512, 64 * 2048,
                                 819e9) * 1e3 == pytest.approx(18.1, abs=0.1)


def test_the_programs_own_parameter_count_is_shapes_mambas():
    cfg = ModelConfig.from_hf_config(PUBLISHED)
    shapes = jax.eval_shape(lambda k: llama.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    norms = (32 * 4 + 2) * 2560
    assert total - norms == shapes_mamba.total_params(PUBLISHED)
    specs = llama.param_specs(cfg)
    assert jax.tree.structure(
        jax.tree.map(lambda a: 0, shapes)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, specs,
                     is_leaf=lambda x: isinstance(x, tuple)))
