"""openPangu-Ultra-MoE's stack (latent attention over a one-row-a-token
cache, norms before and after each sublayer, a leading dense layer, a
sparse block with sigmoid routing, a shared expert and a share of the
routed experts) through the shared stack and the serving engine, against
the plain reference the benchmark uses on the chip
(chipbench/reference/openpangu_ultra_moe.py: the PUBLISHED, expanded form
of the attention, where the program serves the absorbed one), on seeded
random weights at test size (chipbench/tests/configs/tiny-pangu-ultra-moe:
one dense and two expert layers, hidden 128, 4 heads, a 32 + 16-value
latent row in 128 lanes, 4 of 16 routed experts held).
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import openpangu_ultra_moe as reference
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
)
from production_stack_tpu.engine.metrics import EngineStatsCollector
from production_stack_tpu.engine.model_runner import ModelRunner
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.tracing import LatentCounters
from production_stack_tpu.models import llama
from production_stack_tpu.ops import latent_paged_attention_pallas as kernel
from production_stack_tpu.ops.paged_attention import (
    latent_ragged_paged_attention,
)
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "chipbench", "tests", "configs",
                       "tiny-pangu-ultra-moe", "config.json")) as f:
    HF = json.load(f)
# float32 on the CPU on both sides. The served path differs from the
# reference in the order of its sums, and in its FORM: it scores absorbed
# ((W_UK^T q) . c where the reference has q . (W_UK c)), which moves a
# float32 log-probability in the sixth digit: they agree to ~2e-6, and
# the tolerance leaves that an order of magnitude. A latent row, a softmax
# state or a router in bfloat16 reads tens of times over it.
LOGPROB_TOL = 3e-5
# chipbench/run.py's limits, which every cell's `correct` is held to
CELL_TOL, CELL_MEAN_TOL = 0.15, 0.03
BUDGET = 32  # tokens a ragged step: the 70-token prompt takes three chunks


def tiny_cfg(**over) -> ModelConfig:
    return dataclasses.replace(ModelConfig.from_hf_config(HF, "tiny-pangu"),
                               dtype="float32", **over)


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


def serve(eng, prompts, max_tokens=6):
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


def _ids(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 512, n)]


# "long": three chunks of the 32-token budget, then 20 decode steps from
# position 70 across the block boundary at 80
PROMPTS = {"long": _ids(0, 70), "short": _ids(1, 7), "mid": _ids(2, 23)}
STEPS = 20


@pytest.fixture(scope="module")
def served():
    eng = engine()
    return eng, serve(eng, PROMPTS, max_tokens=STEPS)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(tiny_cfg(), jax.random.PRNGKey(3))


# -- the served path against the reference ------------------------------------

@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_served_logprobs_match_the_reference(served, name):
    """Ragged prefill (the long prompt cut in three chunks: the later ones
    read the earlier ones' latent rows from the pool), then decode through
    the latent cache across a block boundary: the XLA form of the kernel."""
    eng, out = served
    toks, lps = out[name]
    err = errors(HF, eng.runner.params, PROMPTS[name], toks, lps)
    assert len(toks) == STEPS and err.max() < LOGPROB_TOL, err


# the expanded body at toy size: a span of 16 rows or more (the long
# prompt's chunks of 32 or 25 with the short prompt beside them, not its
# last 6 or 13, nor the 7-token prompt) in query blocks of 8, windows of
# two blocks, two heads a step
EXPANDED_AT_16 = dict(expand_rows=16, expand_q_rows=8, expand_windows=2,
                      expand_heads=2)


@pytest.mark.parametrize("how", [{}, EXPANDED_AT_16],
                         ids=["absorbed", "long-spans-expanded"])
def test_served_through_the_kernel_matches_the_reference(served, monkeypatch,
                                                         how):
    """The same engine with the Pallas kernel (interpreted) in both step
    programs: the ragged stream's chunks and the decode step's one-token
    spans; with the crossover at the kernel's own 256 rows nothing here is
    long, with it at 16 the prompt's chunks are scored in the published
    form, the later ones over the earlier ones' rows, then over a cached
    prefix."""
    first, _ = served
    traced = []  # (rows, given the heads' own queries; None: decode) a trace

    def call(q, *args, expand=None, **kw):
        traced.append((q.shape[0], expand is not None))
        return kernel_call(q, *args, expand=expand, interpret=True,
                           q_tile=4, windows=2, **how, **kw)

    def decode_call(q, *args, **kw):
        traced.append((q.shape[0], None))
        return decode_kernel_call(q, *args, interpret=True, windows=2, **kw)

    kernel_call = kernel.latent_paged_attention_pallas
    decode_kernel_call = kernel.latent_decode_attention_pallas
    monkeypatch.setattr(kernel, "latent_paged_attention_pallas", call)
    monkeypatch.setattr(kernel, "latent_decode_attention_pallas", decode_call)
    eng = engine(params=first.runner.params)
    eng.runner.use_pallas = True  # read where the programs are traced
    prompts = {"long": PROMPTS["long"], "short": PROMPTS["short"]}
    out = serve(eng, prompts, max_tokens=6)
    # the ragged program hands the kernel both forms' inputs; the decode
    # program (a row a slot) calls the decode body and nothing else
    assert {rows for rows, both in traced if both is None} == {4}
    assert {rows for rows, both in traced if both is not None} == {BUDGET}
    assert all(both for _, both in traced if both is not None)
    for name, (toks, lps) in out.items():
        err = errors(HF, eng.runner.params, prompts[name], toks, lps)
        assert len(toks) == 6 and err.max() < LOGPROB_TOL, (name, err)
        assert toks == served[1][name][0][:6]
    if how:  # four cached blocks, then a 25-row span over them
        prompt = PROMPTS["long"][:64] + _ids(7, 25)
        toks, lps = serve(eng, {"again": prompt})["again"]
        assert eng.stats()["gpu_prefix_cache_hits_total"] == 4
        err = errors(HF, eng.runner.params, prompt, toks, lps)
        assert err.max() < LOGPROB_TOL, err


def test_a_shared_prefix_hits_the_cache_and_changes_nothing(served):
    """A second request that shares 64 tokens (four blocks) with the long
    prompt takes them from the prefix cache (a latent row is a row a
    token: blocks hash and share as keys and values do) and reads as the
    reference does."""
    eng, _ = served
    before = eng.stats()
    prompt = PROMPTS["long"][:64] + _ids(7, 9)
    toks, lps = serve(eng, {"again": prompt})["again"]
    after = eng.stats()
    assert (after["gpu_prefix_cache_hits_total"]
            - before["gpu_prefix_cache_hits_total"]) == 4  # blocks
    err = errors(HF, eng.runner.params, prompt, toks, lps)
    assert err.max() < LOGPROB_TOL, err


def test_absorbed_scoring_equals_expanded_scoring(params):
    """One attention sublayer alone: the program's absorbed mixer through
    dense attention against the reference's expanded form, float32."""
    cfg = tiny_cfg()
    lp = jax.tree.map(lambda a: a[0], params["dense"])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 40, cfg.hidden_size))
    pos = jnp.arange(40, dtype=jnp.int32)[None]

    def attend(q, k, v, caches, layer_idx, **_):
        return llama.dense_causal_attention(q, k, v), caches

    got, _ = llama._mla_mixer(cfg, lp, x, pos, attend, None, 0)
    with jax.default_matmul_precision("highest"):
        want = reference._attn(x[0], lp, eps=cfg.rms_norm_eps,
                               theta=cfg.rope_theta)
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    assert float(jnp.abs(want).max()) > 0.1


# -- the kernel against its XLA form -------------------------------------------

MIXED = ([37, 20, 1, 0, 3, 1], [37, 70, 33, 0, 50, 128])


def _stream(seed=0, spans=MIXED, T=80, H=4):
    """Mixed spans in one stream: a fresh chunk, a chunk that continues a
    context, a decode row, an idle slot, a three-token span, a decode row
    deep in its context; rows of padding behind the last span. ``spans``:
    (query rows, context) a slot, of another stream."""
    rng = np.random.default_rng(seed)
    width, lanes, V, bs = 48, 128, 32, 16
    q_lens, ctxs = spans
    L, N, S, M = 2, 64, len(q_lens), 8
    cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
    bt = np.zeros((S, M), np.int32)
    free = iter(rng.permutation(N - 1) + 1)
    seq_ids, pos = np.zeros(T, np.int32), -np.ones(T, np.int32)
    for s in range(S):
        nb = -(-ctxs[s] // bs)
        bt[s, :nb] = [next(free) for _ in range(nb)]
        for i in range(q_lens[s]):
            seq_ids[cu[s] + i] = s
            pos[cu[s] + i] = ctxs[s] - q_lens[s] + i
    pool = jnp.asarray(rng.normal(size=(L, N, bs, lanes)), jnp.float32)
    pool = pool.at[..., width:].set(0)
    q = jnp.asarray(rng.normal(size=(T, H, lanes)), jnp.float32)
    return q, pool, bt, cu, np.array(ctxs, np.int32), seq_ids, pos, V


@pytest.mark.parametrize("q_tile,windows", [(1, 2), (4, 2), (16, 1), (16, 8)])
def test_the_kernel_equals_its_xla_form(q_tile, windows):
    q, pool, bt, cu, ctx, seq_ids, pos, V = _stream()
    want = latent_ragged_paged_attention(q, pool[1], bt, ctx, seq_ids, pos, V)
    got = kernel.latent_paged_attention_pallas(
        q, pool, bt, cu, ctx, 1, value_dim=V, q_tile=q_tile,
        windows=windows, interpret=True)
    live = pos >= 0
    np.testing.assert_allclose(got[live], want[live], atol=3e-6)
    assert float(jnp.abs(got[~live]).max()) == 0.0  # padding reads zeros


def _decode_rows(ctxs, H, seed=0):
    """A decode dispatch: slot s one query row at the end of ``ctxs[s]``
    rows (0: a dead slot). Every pool block no live row reaches holds NaN
    (but block 0, which the table's padding names and the XLA form
    gathers): a kernel that fetched and summed one would read it."""
    rng = np.random.default_rng(seed)
    width, lanes, V, bs = 48, 128, 32, 16
    # one table width and pool size for every case: cases of one batch and
    # cell size then share a trace
    B, M, N = len(ctxs), 16, 64
    bt = np.zeros((B, M), np.int32)
    free = iter(rng.permutation(N - 1) + 1)
    for s, c in enumerate(ctxs):
        nb = -(-c // bs)
        bt[s, :nb] = [next(free) for _ in range(nb)]
    pool = rng.normal(size=(2, N, bs, lanes)).astype(np.float32)
    pool[..., width:] = 0
    pool[:, np.setdiff1d(np.arange(1, N), bt)] = np.nan
    q = jnp.asarray(rng.normal(size=(B, H, lanes)), jnp.float32)
    return q, jnp.asarray(pool), bt, np.array(ctxs, np.int32), V


# a window is two blocks (32 rows). (heads, contexts a slot, sequences a
# cell)
DECODE_CASES = {
    "a dead slot among live ones": (4, [33, 0, 1, 100], 2),
    "every slot dead": (4, [0, 0, 0], 2),
    "one row of context": (4, [1, 1], 2),
    "one short of, at and one past a block": (4, [15, 16, 17], 1),
    "one short of, at and one past a window": (4, [31, 32, 33], 1),
    "one short of, at and one past two windows": (4, [63, 64, 65], 1),
    "three windows and more: whole ones ahead": (4, [96, 97, 130], 1),
    "very different lengths in one cell": (4, [200, 3, 17, 65], 4),
    "a batch that is no multiple of the cell": (4, [40, 70, 9, 0, 64], 2),
    "a batch smaller than the cell": (4, [75], 4),
    "32 heads": (32, [50, 0, 129, 32], 2),
    "128 heads": (128, [34, 95], 2),
    "heads that are no multiple of 8": (6, [47, 0, 64, 10, 111], 2),
    "the call's own sequences a cell": (4, [90, 5, 0, 64, 33, 128], None),
}


@pytest.mark.parametrize("name", sorted(DECODE_CASES))
def test_the_decode_body_equals_its_xla_form(name):
    """The decode program's call (one-token spans, several sequences a
    grid cell, long windows, a mask only where the context ends) against
    the XLA form on the same rows; a dead slot reads zeros; the NaN that
    fills every block no live row reaches is never fetched into a sum."""
    H, ctxs, spb = DECODE_CASES[name]
    q, pool, bt, ctx, V = _decode_rows(ctxs, H)
    B = len(ctxs)
    pos = np.where(ctx > 0, ctx - 1, -1).astype(np.int32)
    want = latent_ragged_paged_attention(
        q, pool[1], bt, ctx, np.arange(B, dtype=np.int32), pos, V)
    got = kernel.latent_decode_attention_pallas(
        q, pool, bt, ctx, 1, value_dim=V, windows=2, seqs_per_cell=spb,
        interpret=True)
    assert got.shape == (B, H, V)
    live = ctx > 0
    np.testing.assert_allclose(got[live], want[live], atol=3e-6)
    assert float(jnp.abs(got[~live]).max(initial=0.0)) == 0.0


def test_the_decode_body_reads_what_the_stream_kernel_reads():
    """The same one-token spans through both bodies: the decode body and
    the stream's tile (``cu_q_lens = arange``), which the ragged program's
    decode rows still walk."""
    q, pool, bt, ctx, V = _decode_rows([33, 0, 1, 100, 64, 17], 4)
    got = kernel.latent_decode_attention_pallas(
        q, pool, bt, ctx, 1, value_dim=V, windows=2, interpret=True)
    tile = kernel.latent_paged_attention_pallas(
        q, pool, bt, np.arange(len(ctx) + 1, dtype=np.int32), ctx, 1,
        value_dim=V, q_tile=4, windows=2, interpret=True)
    np.testing.assert_allclose(got, tile, atol=3e-6)


def test_sequences_a_cell_follow_the_calls_shapes():
    """From the heads, the lanes and the window's bytes against a VMEM
    budget: 4 at Kimi-Linear's 32 heads, 2 at Pangu's 128 (where the fixed
    inputs read best, PERF.md section 6, PR 60), never more than
    ``DECODE_SEQS``, never fewer than one."""
    pick = kernel._decode_seqs_per_cell
    assert pick(32, 640, 512, 512, 2) == 4
    assert pick(128, 640, 512, 512, 2) == 2
    assert pick(4, 128, 32, 32, 4) == kernel.DECODE_SEQS
    assert pick(128, 640, 512, 8192, 2) == 1


def _published_form(q_nope, q_rope, w_uk, w_uv, layer, bt, ctx, seq_ids, pos,
                    C, rope, scale):
    """A token's output a head, (T, H, v), as the model is published:
    head h's key ``[W_UK_h c; r]``, its value ``W_UV_h c``; numpy,
    float64, a token at a time."""
    layer, bt = np.asarray(layer, np.float64), np.asarray(bt)
    q_nope, q_rope, w_uk, w_uv = (np.asarray(a, np.float64)
                                  for a in (q_nope, q_rope, w_uk, w_uv))
    out = np.zeros((*q_nope.shape[:2], w_uv.shape[-1]))
    for t in np.flatnonzero(pos >= 0):
        rows = layer[bt[seq_ids[t]]].reshape(-1, layer.shape[-1])[
            :min(ctx[seq_ids[t]], pos[t] + 1)]
        c, r = rows[:, :C], rows[:, C:C + rope]
        keys = np.einsum("rc,hcd->hrd", c, w_uk)
        sc = (np.einsum("hd,hrd->hr", q_nope[t], keys)
              + q_rope[t] @ r.T) * scale
        w = np.exp(sc - sc.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        out[t] = np.einsum("hr,rc,hcd->hd", w, c, w_uv)
    return out


# (what the stream holds, (query rows, context) a slot, stream width): the
# crossover at 16 rows, query blocks of 8, windows of two blocks (32
# rows), so a block of a long span's rows is skipped above the diagonal,
# masked on it and unmasked below it
TWO_FORM_STREAMS = {
    "one long span alone": (([64], [64]), 64),
    "a later chunk of its prompt": (([40], [120]), 48),
    "a chunk behind a cached prefix of whole blocks": (([25], [89]), 32),
    "two long spans": (([30, 34], [30, 100]), 64),
    "long, decode rows and short in one stream": (MIXED, 80),
    "one row under the crossover and one at it": (
        ([15, 16, 1], [15, 16, 99]), 32),
    "a span that ends mid-window and mid-block": (([37, 2], [93, 45]), 48),
}


@pytest.mark.parametrize("heads,rope", [(4, 16), (32, 0)],
                         ids=["rope", "32-heads-no-rope"])
@pytest.mark.parametrize("name", sorted(TWO_FORM_STREAMS))
def test_the_two_forms_in_one_call_equal_the_xla_form(name, heads, rope):
    """Spans at or over the crossover scored in the published form, the
    others absorbed, in one call, against the absorbed XLA form and
    against the published form computed plainly. ``rope`` 0: Kimi-Linear's
    latent attention, whose rows end with the latent."""
    spans, T = TWO_FORM_STREAMS[name]
    _, pool, bt, cu, ctx, seq_ids, pos, C = _stream(spans=spans, T=T)
    lanes, nope, v = pool.shape[-1], 24, 40
    pool = pool.at[..., C + rope:].set(0)
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    q_nope = jax.random.normal(k[0], (T, heads, nope))
    q_rope = jax.random.normal(k[1], (T, heads, lanes - C)).at[
        ..., rope:].set(0)
    w_uk = jax.random.normal(k[2], (heads, C, nope)) * C ** -0.5
    w_uv = jax.random.normal(k[3], (heads, C, v)) * C ** -0.5
    scale = (nope + rope) ** -0.5
    q = jnp.concatenate([jnp.einsum("thd,hcd->thc", q_nope, w_uk), q_rope],
                        axis=-1) * scale * lanes ** 0.5
    o_lat, o_own, scored = kernel.latent_paged_attention_pallas(
        q, pool, bt, cu, ctx, 1, value_dim=C,
        expand=(jnp.concatenate([q_nope, q_rope], -1).swapaxes(0, 1), w_uk,
                w_uv, scale),
        q_tile=4, windows=2, interpret=True, **EXPANDED_AT_16)
    q_lens = np.diff(cu)
    assert (np.asarray(scored) == ((q_lens[seq_ids] >= 16) & (pos >= 0))).all()
    assert scored.sum() > 0
    got = jnp.where(scored[:, None, None], o_own.swapaxes(0, 1),
                    jnp.einsum("thc,hcd->thd", o_lat, w_uv))
    live = pos >= 0
    absorbed = jnp.einsum("thc,hcd->thd", latent_ragged_paged_attention(
        q, pool[1], bt, ctx, seq_ids, pos, C), w_uv)
    np.testing.assert_allclose(got[live], absorbed[live], atol=3e-6)
    published = _published_form(q_nope, q_rope[..., :rope], w_uk, w_uv,
                                pool[1], bt, ctx, seq_ids, pos, C, rope, scale)
    np.testing.assert_allclose(got[live], published[live], atol=3e-6)
    assert float(jnp.abs(got[~live]).max(initial=0.0)) == 0.0


# -- the cache kind ------------------------------------------------------------

def test_the_pool_is_one_row_a_token_and_its_bytes_follow_from_its_shape():
    cfg = tiny_cfg()
    assert (cfg.latent_width, cfg.latent_lanes, cfg.cache_layers) == (48, 128, 3)
    assert cfg.kv_pool_shape(64, 16) == (3, 64, 16, 128)
    assert cfg.kv_bytes_per_token == 3 * 128 * 4  # float32 at test size
    cache = CacheConfig(block_size=16, num_blocks=64)
    pool = init_kv_cache(cfg, cache, one_device())
    assert pool.shape == (3, 64, 16, 128)
    assert kv_cache_bytes_per_block(cfg, cache) * 64 == pool.nbytes
    # every other pool is what it was: (layers, N, bs, 2*KH, D)
    dense = MODEL_PRESETS["tiny-llama"]
    assert dense.kv_pool_shape(8, 16) == (2, 8, 16, 4, 32)
    assert dense.kv_bytes_per_token == 2 * 2 * 2 * 32 * 4
    # the published widths: 576 values in 640 lanes, five layers, bf16
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "openpangu-ultra-moe-718b-ep16-l5",
                           "config.json")) as f:
        real = ModelConfig.from_hf_config(json.load(f), "pangu")
    assert (real.latent_width, real.latent_lanes) == (576, 640)
    assert real.kv_bytes_per_token == 5 * 640 * 2 == 6400
    assert real.kv_pool_shape(100, 16) == (5, 100, 16, 640)


# -- the sparse block's share and the leading dense layer ----------------------

def test_the_shares_add_up_to_the_uncut_layer(params):
    """What the four chips that share a layer compute (each its 4 of the
    16 routed experts' pairs), with what every chip computes alike (the
    shared expert) counted once, is the uncut reference's whole block."""
    cfg = tiny_cfg()
    whole = dataclasses.replace(cfg, experts_held=0, expert_offset=0)
    key = jax.random.PRNGKey(11)
    full = llama.init_params(whole, key)["layers"]
    x = jax.random.normal(jax.random.PRNGKey(12), (1, 24, cfg.hidden_size))
    lp = {k: v[0] for k, v in full.items()}
    routed = 0
    for share in range(4):
        c = dataclasses.replace(cfg, experts_held=4, expert_offset=4 * share)
        experts = {k: full[k][:, 4 * share:4 * share + 4]
                   for k in llama._EXPERT_WEIGHTS}
        out, hist = llama._moe_mlp(c, lp["router"], experts, 0, x,
                                   bias=lp["router_bias"])
        routed = routed + out
        assert hist.shape == (4 + 2,) and int(hist[:5].sum()) == 24 * 4
    shared = llama._mlp(cfg, {"w_gate": lp["shared_gate"],
                              "w_up": lp["shared_up"],
                              "w_down": lp["shared_down"]}, x)
    with jax.default_matmul_precision("highest"):
        want = reference._sparse(
            x[0], lp, top_k=4, renormalise=True, scaling=2.5, first=0,
            held=16)
    np.testing.assert_allclose((routed + shared)[0], want, atol=2e-5)


def test_the_leading_dense_layer_routes_nothing(served):
    """Layer 0 has a SwiGLU of intermediate_size and no router; the
    routing histogram has a row for each EXPERT layer alone, and every
    routed pair the counters saw is one of theirs."""
    eng, _ = served
    cfg, p = eng.config.model, eng.runner.params
    assert (cfg.dense_layers, cfg.num_expert_layers) == (1, 2)
    assert "router" not in p["dense"] and p["dense"]["w_gate"].shape == (
        1, 128, 256)
    assert p["layers"]["w_gate"].shape == (2, 4, 128, 32)
    toks = jnp.asarray([PROMPTS["mid"]])
    pos = jnp.arange(toks.shape[1], dtype=jnp.int32)[None]

    def attend(q, k, v, caches, layer_idx, **_):
        return llama.dense_causal_attention(q, k, v), caches

    _, _, hist = llama.forward_tokens(cfg, p, toks, pos, attend, None,
                                      moe_hist=True)
    assert hist.shape == (2, 4 + 2)
    assert int(hist[:, :5].sum()) == 2 * 23 * 4  # two layers, 4 a token


# -- planted faults -------------------------------------------------------------

def over_a_limit(err) -> bool:
    """`correct` would be false: one of the cell's two limits is passed."""
    return bool(err.max() > CELL_TOL or err.mean() > CELL_MEAN_TOL)


def _faulty_mixer(fault, cfg, lp, x, positions, attend, caches, cache_layer):
    """models/llama.py _mla_mixer with one fault planted."""
    if fault == "rope part left out of the score":
        lp = {**lp, "wq_rope": jnp.zeros_like(lp["wq_rope"])}
    elif fault == "the row's own width ** -1/2 as the scale":
        # 576^-1/2 at the published widths, where 192^-1/2 is published
        # (here 48 is both, so the fault takes the lanes, as a kernel's
        # own default does: the fold left out)
        cfg = dataclasses.replace(cfg, head_dim=cfg.latent_lanes)
    elif fault == "the kv_a norm skipped":
        real, llama.rms_norm = llama.rms_norm, (
            lambda v, w, eps, *a: v if w is lp["kv_a_norm"]
            else real(v, w, eps, *a))
        try:
            return llama_mla(cfg, lp, x, positions, attend, caches,
                             cache_layer)
        finally:
            llama.rms_norm = real
    elif fault == "the value read over all the row's lanes":
        # the rotated key's lanes join the value
        def leaky(q, k, v, c, i, **kw):
            out, c = attend(q, k, k[..., :cfg.latent_width], c, i, **kw)
            extra = out[..., cfg.kv_lora_rank:]
            return out[..., :cfg.kv_lora_rank].at[
                ..., :extra.shape[-1]].add(extra), c
        return llama_mla(cfg, lp, x, positions, leaky, caches, cache_layer)
    return llama_mla(cfg, lp, x, positions, attend, caches, cache_layer)


llama_mla = llama._mla_mixer
# (the fault, whether the probe's measure reads it over a limit of the
# cell at this size). Read here, largest / mean of the 120 values against
# 0.15 / 0.03: 0.222 / 0.061, 0.168 / 0.044, 0.340 / 0.089; the skipped
# norm 0.121 / 0.029: with stand-in weights kv_a's output has an RMS near
# 1 of itself, so the norm it skips changes little. What the same faults
# read at the published widths on the chip: PERF.md section 6, PR 43
MIXER_FAULTS = [("rope part left out of the score", True),
                ("the row's own width ** -1/2 as the scale", True),
                ("the value read over all the row's lanes", True),
                ("the kv_a norm skipped", False)]


def _dense_logprobs(cfg, params, ids):
    got = llama.forward_dense(cfg, params, jnp.asarray([ids]))
    return np.asarray(jax.nn.log_softmax(got[0], -1))


PROBE_TOP = 5  # chipbench/run.py asks the probe for as many


def probe_errors(cfg, served_params, prompt, hf=HF, ref_params=None):
    """What chipbench/reference/compare.py measures of a run's probe, and
    no more: the program (its dense forward: the same mixer and stack the
    step programs run) decodes STEPS tokens greedily after the prompt, and
    each token's log-probability and those of its five most likely tokens
    are held against the reference's for the same token sequence: 6 values
    a decode position, none at a prompt position, none elsewhere in the
    vocabulary."""
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


@pytest.mark.parametrize("fault,caught", MIXER_FAULTS)
def test_a_fault_in_the_mixer_as_the_benchmark_would_read_it(
        params, monkeypatch, fault, caught):
    """One fault planted in the mixer, read as the benchmark reads a run.
    Every one reads thousands of times over a sound program's agreement
    (LOGPROB_TOL); those marked pass a limit of the cell too."""
    monkeypatch.setattr(llama, "_mla_mixer",
                        functools.partial(_faulty_mixer, fault))
    err = probe_errors(tiny_cfg(), params, PROMPTS["long"])
    assert err.max() > 1000 * LOGPROB_TOL, (fault, err.max())
    assert over_a_limit(err) == caught, (fault, err.max(), err.mean())


def _a_post_norm_skipped(params):
    return dataclasses.replace(tiny_cfg(), norms="pre"), params, HF


def _layer_0_run_sparse(params):
    """The reference told that no layer is dense: layer 0 then takes the
    first expert layer's sparse block where the program ran its MLP."""
    dense, layers = params["dense"], params["layers"]
    first = {k: jnp.concatenate([dense.get(k, v)[:1] if k not in (
        "w_gate", "w_up", "w_down") else v[:1], v]) for k, v in layers.items()}
    return tiny_cfg(), params, {**HF, "first_k_dense_replace": 0}, {
        **params, "layers": first}


def _a_held_expert_dropped(params):
    layers = dict(params["layers"])
    layers["w_down"] = layers["w_down"].at[:, 1].set(0.0)
    return tiny_cfg(), {**params, "layers": layers}, HF


@pytest.mark.parametrize("fault,caught", [
    (_a_post_norm_skipped, True), (_layer_0_run_sparse, True),
    (_a_held_expert_dropped, False)])
def test_a_fault_in_the_stack_as_the_benchmark_would_read_it(
        params, fault, caught):
    """One side of the comparison differs from the other by one fault of
    the stack around the mixer (2.97 / 0.83 and 0.366 / 0.112 against the
    cell's 0.15 / 0.03). One of the four held experts is the smallest
    fault there is: it touches only the rows routed to it, and reads
    0.124 / 0.018 here, thousands of times a sound program's agreement
    and under both limits of the cell."""
    cfg, served_params, hf, *ref_params = fault(params)
    err = probe_errors(cfg, served_params, PROMPTS["long"], hf,
                       ref_params[0] if ref_params else params)
    assert err.max() > 1000 * LOGPROB_TOL, (fault.__name__, err.max())
    assert over_a_limit(err) == caught, (err.max(), err.mean())


@pytest.mark.parametrize("control", ["latent_dtype", "state_dtype",
                                     "router_dtype"])
def test_a_reference_in_lower_precision_reads_as_not_correct(served, control):
    """The benchmark's control (chipbench/reference/control.py): the
    served path against a reference whose latent rows, softmax state or
    router scores are bfloat16 where this configuration states float32.
    Each reads tens of times over this file's float32 agreement, and
    hundreds of times UNDER the cell's limits: a control in bfloat16 does
    not come out as not correct, here or on the chip, where the served
    path's own bfloat16 activations are most of what is read (PERF.md
    sections 2 and 6)."""
    eng, out = served
    toks, lps = out["long"]
    err = errors(HF, eng.runner.params, PROMPTS["long"], toks, lps,
                 **{control: "bfloat16"})
    assert 10 * LOGPROB_TOL < err.max() < CELL_TOL / 10, err


# -- what is refused -------------------------------------------------------------

@pytest.mark.parametrize("key,value,words", [
    ("n_group", 8, "group-limited routing"),
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling"),
    ("sandwich_norm", False, "sandwich_norm: false"),
    ("num_nextn_predict_layers", 1, "multi-token-prediction"),
    ("first_k_dense_replace", 3, "leaves no expert layer"),
    ("n_routed_experts_held", 5, "is not a share"),
])
def test_what_the_file_asks_for_and_is_not_computed_is_refused(
        key, value, words):
    hf = {**HF, key: value}
    if key == "n_routed_experts_held":
        hf["routed_expert_offset"] = 12
    with pytest.raises(ValueError, match=words):
        ModelConfig.from_hf_config(hf, "tiny-pangu")


REFUSALS = ("a mesh of", "quant=", "n-gram speculative", "role=",
            "a host or remote KV tier", "LoRA adapters")


def _two_devices():
    return build_mesh(MeshConfig(tensor=2), devices=jax.devices()[:2])


@pytest.mark.parametrize("words,over,mesh", [
    ("a mesh of 2 devices", {}, _two_devices),
    ("quant=int8", {"cfg": {"quant": "int8"}}, one_device),
    ("n-gram speculative decoding", {"spec": 2}, one_device),
    ("role=prefill", {"role": "prefill"}, one_device),
    ("a host or remote KV tier", {"host": 8}, one_device),
    ("LoRA adapters", {"lora": True}, one_device),
])
def test_refuse_for_latent_cache_names_what_it_refuses(words, over, mesh):
    config = engine_config(tiny_cfg(**over.get("cfg", {})),
                           **({"role": over["role"]} if "role" in over
                              else {}))
    config.scheduler.spec_ngram_k = over.get("spec", 0)
    config.cache.host_offload_blocks = over.get("host", 0)
    with pytest.raises(ValueError, match="keeps a latent cache") as e:
        ModelRunner._refuse_for_latent_cache(config, mesh(),
                                             lora=over.get("lora", False))
    said = str(e.value).split("not supported with it: ")[1]
    assert said.startswith(words), said
    # nothing else is named
    assert sum(w in said for w in REFUSALS) == 1, said


def test_the_engine_refuses_at_start_up_and_an_adapter_when_it_comes(served):
    config = engine_config()
    config.scheduler.spec_ngram_k = 2
    with pytest.raises(ValueError, match="n-gram speculative decoding"):
        LLMEngine(config, mesh=one_device())
    with pytest.raises(ValueError, match="LoRA adapters"):
        served[0].runner.register_lora(1, {})
    # nothing of an allowed configuration is refused
    ModelRunner._refuse_for_latent_cache(engine_config(), one_device())


def test_a_checkpoint_is_refused(tmp_path):
    from production_stack_tpu.engine import weights

    cfg = dataclasses.replace(tiny_cfg(), weights_path=str(tmp_path))
    with pytest.raises(ValueError, match="pangu_ultra_moe checkpoint"):
        weights.load_safetensors(cfg, one_device(), None)


# -- counters --------------------------------------------------------------------

@pytest.mark.parametrize("expand_rows,expanded", [
    (None, 0),  # the XLA path: nothing is scored expanded
    (5, 0), (4, 4 * 5 // 2), (3, 4 * 5 // 2 + 3 * 10 + 3 * 4 // 2)])
def test_latent_counters_count_pairs_from_spans(expand_rows, expanded):
    c = LatentCounters(cache_layers=5, kv_bytes_per_token=6400,
                       expand_rows=expand_rows)
    # a fresh 4-token chunk, a 3-token chunk that continues 10, a decode
    # row at context 8, an idle slot
    c.record("ragged", [4, 3, 1, 0], [4, 13, 8, 99])
    pairs = (4 * 5 // 2) + (3 * 10 + 3 * 4 // 2) + 8
    assert c.scored_pairs == {"ragged": 5 * pairs, "decode": 0}
    # the spans at or over the crossover, their pairs whole
    assert c.expanded_pairs == {"ragged": 5 * expanded, "decode": 0}
    assert c.query_tokens["ragged"] == 5 * 8
    assert c.context_rows["ragged"] == 5 * (4 + 13 + 8)
    # two live slots, three fused iterations: contexts grow by one each
    c.record("decode", [1, 0, 1], [7, 0, 20], iterations=3)
    assert c.scored_pairs["decode"] == 5 * (27 + 29 + 31)
    assert c.query_tokens["decode"] == 5 * 2 * 3
    assert c.expanded_pairs["decode"] == 0  # one-token spans never are
    # the decode body's where the programs are the kernel's: all of a
    # decode dispatch's, fused iterations and all, none of a ragged one's
    assert c.decode_body_pairs == {
        "ragged": 0,
        "decode": 0 if expand_rows is None else c.scored_pairs["decode"]}
    snap = c.snapshot()
    assert snap["mla_decode_body_pairs_total"] == c.decode_body_pairs
    assert snap["kv_bytes_per_token"] == 6400
    assert snap["mla_scored_pairs_total"]["ragged"] == 5 * pairs
    assert snap["mla_expanded_pairs_total"] == c.expanded_pairs


def test_the_counters_move_in_both_step_kinds_and_are_exported(served):
    eng, _ = served
    s = eng.stats()
    assert s["mla_scored_pairs_total"]["ragged"] > 0
    assert s["mla_scored_pairs_total"]["decode"] > 0
    assert s["mla_query_tokens_total"]["decode"] > 0
    assert s["kv_bytes_per_token"] == 3 * 128 * 4
    # the long prompt alone: 70 * 71 / 2 pairs a layer in its chunks
    assert s["mla_scored_pairs_total"]["ragged"] >= 3 * 70 * 71 // 2
    # the ragged kernel's walks are another kernel's: they stay where
    # they were
    assert s["ragged_attn_walks_total"] == 0
    text = "\n".join(
        f"{sample.name} {sample.labels} {sample.value}"
        for m in EngineStatsCollector(eng, "tiny-pangu").collect()
        for sample in m.samples)
    for name in ("vllm:mla_query_tokens_total", "vllm:mla_scored_pairs_total",
                 "vllm:mla_expanded_pairs_total",
                 "vllm:mla_decode_body_pairs_total",
                 "vllm:mla_context_rows_total", "vllm:kv_bytes_per_token"):
        assert name in text
    assert "'kind': 'ragged'" in text and "'kind': 'decode'" in text
    # the CPU's ragged program is the XLA form: absorbed throughout
    assert eng.latent.expand_rows is None
    assert s["mla_expanded_pairs_total"] == {"ragged": 0, "decode": 0}
    assert s["mla_decode_body_pairs_total"] == {"ragged": 0, "decode": 0}


def test_the_engine_counts_the_pairs_of_its_long_spans(served, monkeypatch):
    """Where the runner's ragged program is the kernel's, the engine counts
    by the kernel's own crossover: the long prompt's chunks of 32 tokens
    over a crossover moved to 16 rows are counted whole, its last 6-token
    chunk and the decode dispatches not at all; the decode dispatches'
    pairs are the decode body's, all of them."""
    from production_stack_tpu.engine import model_runner

    first, _ = served
    monkeypatch.setattr(kernel, "EXPAND_ROWS", 16)
    monkeypatch.setattr(
        kernel, "latent_paged_attention_pallas",
        functools.partial(kernel.latent_paged_attention_pallas,
                          interpret=True, q_tile=4, windows=2,
                          **EXPANDED_AT_16))
    monkeypatch.setattr(
        kernel, "latent_decode_attention_pallas",
        functools.partial(kernel.latent_decode_attention_pallas,
                          interpret=True, windows=2))
    monkeypatch.setattr(model_runner, "_pallas_ok", lambda *a: True)
    eng = engine(params=first.runner.params)
    assert eng.latent.expand_rows == 16
    serve(eng, {"long": PROMPTS["long"]}, max_tokens=3)
    s = eng.stats()
    chunks = 32 * 33 // 2 + (32 * 32 + 32 * 33 // 2)  # the first two of 70
    assert s["mla_expanded_pairs_total"] == {"ragged": 3 * chunks,
                                             "decode": 0}
    assert (s["mla_scored_pairs_total"]["ragged"]
            >= 3 * (chunks + 6 * 64 + 6 * 7 // 2))
    # the decode program is the kernel's decode body: its pairs are
    assert s["mla_decode_body_pairs_total"] == {
        "ragged": 0, "decode": s["mla_scored_pairs_total"]["decode"]}
    assert s["mla_scored_pairs_total"]["decode"] > 0


@pytest.mark.parametrize("metric,counter", [
    ("mla_expanded_pairs_pct", "vllm:mla_expanded_pairs_total"),
    ("mla_decode_body_pairs_pct", "vllm:mla_decode_body_pairs_total")])
@pytest.mark.parametrize("counted,want", [
    ((0.0, 0.0), 0.0),       # nothing long, no decode body: 0, not nothing
    ((600.0, 0.0), 50.0),    # of ALL pairs, the other step kind's too
    (None, None),            # a program without the counter: left out
])
def test_the_benchmark_reads_a_counters_share_of_all_pairs(metric, counter,
                                                           counted, want):
    """Both shares of the kernel's pairs, each a data file: the ragged
    program's expanded spans and the decode program's decode body (PR 60;
    the parent has no such counter, and the line leaves the metric out)."""
    import types

    from chipbench import layers, prom

    with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    assert (spec["reader"], spec["scale"], spec["num"], spec["den"]) == (
        "prom_ratio", 100.0, [counter], ["vllm:mla_scored_pairs_total"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"] if m["name"] == metric]
    assert entry == [{
        "name": metric, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "tpot_p50_ms", "workloads": [
            "openpangu-ultra-moe-718b-ep16-l5.long-prompt",
            "kimi-linear-48b-a3b-ep16.long-decode"]}]

    def scrape(scored, counted):
        lines = [f'vllm:mla_scored_pairs_total{{model_name="m",kind="{k}"}} '
                 f'{v}' for k, v in zip(("ragged", "decode"), scored)]
        if counted is not None:
            lines += [
                f'{counter}{{model_name="m",kind="{k}"}}'
                f' {v}' for k, v in zip(("ragged", "decode"), counted)]
        return prom.parse("\n".join(lines) + "\n")

    ctx = types.SimpleNamespace(
        prom_open=scrape((100.0, 50.0), counted and (0.0, 0.0)),
        prom_close=scrape((900.0, 450.0), counted), manifest={})
    assert layers.read(metric, ctx) == want
