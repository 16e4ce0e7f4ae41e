"""The MoE block's Pallas grouped matmul (ops/moe_grouped_matmul_pallas.py)
in interpret mode on the CPU against ``jax.lax.ragged_dot``; ``_moe_mlp``
through either; the runner's choice between them and the counter that says
how often the kernel engages; and the windowed stream decoder that keeps
the server's event loop ahead of the tokens a faster step hands it."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine import quant
from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.engine.tracing import MoeCounters
from production_stack_tpu.engine.weights import init_or_load
from production_stack_tpu.models import llama
from production_stack_tpu.ops import moe_grouped_matmul_pallas as gmm
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

KERNEL = functools.partial(gmm.moe_grouped_matmul, interpret=True)
X = 6   # groups of a layer in the cases below
TM = 16  # their row tile


def stack(layer: int, layers: int, sizes) -> list:
    """A layer's X sizes where ``_moe_mlp`` puts them: at layer * X of the
    stack's layers * X groups, every other layer's group empty."""
    full = [0] * (layers * X)
    full[layer * X:(layer + 1) * X] = sizes
    return full


# name -> (rows M, group sizes over the stack, (tm, tn) or None)
CASES = {
    "layer 0 of a stack": (64, stack(0, 3, [5, 9, 1, 20, 3, 8]), (TM, 128)),
    "layer 2 of a stack": (64, stack(2, 3, [5, 9, 1, 20, 3, 8]), (TM, 128)),
    "empty groups among full ones": (64, stack(1, 2, [0, 30, 0, 0, 34, 0]),
                                     (TM, 128)),
    "one group holds every row": (64, stack(1, 2, [0, 0, 64, 0, 0, 0]),
                                  (TM, 128)),
    "groups of 1 .. tile + 1 rows": (96, stack(1, 2, [1, 2, 15, 16, 17, 31]),
                                     (TM, 128)),
    "no row at all": (64, stack(1, 2, [0] * X), (TM, 128)),
    "a null-group tail past the last group": (
        128, stack(1, 2, [3, 0, 7, 11, 0, 2]), (TM, 128)),
    # a share of the experts: the pairs on absent experts and the idle
    # rows, two null groups, lie behind the held experts' rows
    "a held share's two null groups": (
        128, stack(0, 2, [2, 0, 1, 0, 3, 1]), (TM, 128)),
    "N in two tiles": (64, stack(1, 2, [5, 9, 1, 20, 3, 8]), (TM, 128)),
    "N whole, the default tiling": (64, stack(1, 2, [5, 9, 1, 20, 3, 8]),
                                    None),
    "rows that are no multiple of the tile": (
        50, stack(1, 2, [5, 9, 1, 20, 3, 8]), (TM, 256)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_equals_ragged_dot(case, dtype):
    M, sizes, tiling = CASES[case]
    K, N = 384, 256  # three lane tiles of K in one block, N in one or two
    k1, k2 = jax.random.split(jax.random.PRNGKey(len(case)))
    x = jax.random.normal(k1, (M, K), dtype)
    w = jax.random.normal(k2, (len(sizes), K, N), dtype) * K ** -0.5
    gs = jnp.asarray(sizes, jnp.int32)
    got = gmm.moe_grouped_matmul(x, w, gs, tiling=tiling, interpret=True)
    want = jax.lax.ragged_dot(x, w, gs)
    assert got.shape == want.shape == (M, N) and got.dtype == dtype
    n = sum(sizes)  # rows past the last group are never read back
    # float32: the same products in another order; bf16: one rounding of
    # sums of size ~1
    tol = (dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32
           else dict(rtol=0, atol=2 ** -7))
    np.testing.assert_allclose(np.asarray(got[:n], np.float32),
                               np.asarray(want[:n], np.float32), **tol)
    # the output block that holds the last live rows is written whole:
    # what lies behind them in it is zeros, not an earlier group's rows
    tm = (tiling or gmm.tiling_for(M, K, N))[0]
    assert not np.asarray(got[n:min(-(-n // tm) * tm, M)], np.float32).any()


def test_the_work_list_visits_each_tile_group_pair_once():
    sizes = jnp.asarray(stack(1, 2, [1, 2, 15, 16, 17, 31]), jnp.int32)
    gid, tid, starts, ends, n = (np.asarray(a) for a in gmm.visit_metadata(
        sizes, 96, TM))
    n = int(n)
    pairs = list(zip(tid[:n].tolist(), (gid[:n] - X).tolist()))
    # rows 0, 1-2, 3-17, 18-33, 34-50, 51-81 in tiles of 16
    assert pairs == [(0, 0), (0, 1), (0, 2), (1, 2), (1, 3), (2, 3), (2, 4),
                     (3, 4), (3, 5), (4, 5), (5, 5)]
    assert len(gid) == 96 // TM + 2 * X - 1
    # what lies past the count is never run and still in bounds
    assert gid.max() < 2 * X and tid.max() < 96 // TM
    np.testing.assert_array_equal(starts[X:], [0, 1, 3, 18, 34, 51])
    np.testing.assert_array_equal(ends[X:], [1, 3, 18, 34, 51, 82])


def test_tiling_at_the_cells_widths():
    # OLMoE's and Solar-Open2's matrices go whole, the Pangu share's in
    # halves of N; K is never cut
    assert gmm.tiling_for(512, 2048, 1024) == (128, 1024)
    assert gmm.tiling_for(512, 1024, 2048) == (128, 2048)
    assert gmm.tiling_for(4096, 4096, 1280) == (128, 1280)
    assert gmm.tiling_for(16384, 1280, 4096) == (128, 4096)
    assert gmm.tiling_for(16384, 7680, 2048) == (128, 1024)
    assert gmm.tiling_for(16384, 2048, 7680) == (128, 3840)
    assert gmm.tiling_for(12, 64, 32) == (16, 32)
    assert all(gmm.grouped_kernel_path(k, n) for k, n in (
        (2048, 1024), (1024, 2048), (4096, 1280), (7680, 2048),
        (4096, 14336)))
    # no whole lane tiles, or no cut of N whose block fits
    assert not gmm.grouped_kernel_path(64, 32)
    assert not gmm.grouped_kernel_path(2048, 1000)
    assert not gmm.grouped_kernel_path(2 ** 17, 128)


# -- the block through either grouped matmul -----------------------------

LAYER = 1


def _block(preset, **replace):
    cfg = dataclasses.replace(ModelConfig.from_pretrained(preset), **replace)
    mesh = build_mesh(MeshConfig(data=1, tensor=1, expert=1),)
    whole = ModelConfig.from_pretrained(preset)
    layers = init_or_load(whole, mesh, seed=0)["layers"]
    lp = jax.tree.map(lambda a: a[LAYER], layers)
    experts = {k: layers[k] for k in llama._EXPERT_WEIGHTS}
    if cfg.experts_held:
        lo = cfg.expert_offset
        experts = {k: v[:, lo:lo + cfg.experts_held]
                   for k, v in experts.items()}
    return cfg, lp, experts


@pytest.mark.parametrize("preset,replace,tokens,live", [
    ("tiny-mixtral", {}, 12, None),
    ("tiny-olmoe", {}, 12, None),
    ("tiny-olmoe", {}, 64, None),
    ("tiny-olmoe", {}, 40, 9),      # padding rows: a null group
    ("tiny-olmoe", {"experts_held": 2, "expert_offset": 4}, 40, 9),
    ("tiny-olmoe", {"experts_held": 4, "expert_offset": 0}, 16, None),
])
def test_the_block_is_the_same_through_either_grouped_matmul(
        preset, replace, tokens, live):
    cfg, lp, experts = _block(preset, **replace)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((tokens, cfg.hidden_size)),
                    jnp.float32)
    mask = None
    if live is not None:
        mask = np.zeros(tokens, bool)
        mask[rng.choice(tokens, live, replace=False)] = True
        mask = jnp.asarray(mask)
    want, want_hist = llama._moe_mlp(cfg, lp["router"], experts, LAYER, x,
                                     mask)
    got, hist = llama._moe_mlp(cfg, lp["router"], experts, LAYER, x, mask,
                               grouped_matmul=KERNEL)
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(want_hist))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(want).max()) > 1e-3  # something was computed


def test_a_forward_is_the_same_through_either_grouped_matmul():
    cfg = ModelConfig.from_pretrained("tiny-olmoe")
    mesh = build_mesh(MeshConfig(data=1, tensor=1, expert=1),)
    params = init_or_load(cfg, mesh, seed=0)
    tokens = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8, 9]], jnp.int32)
    pos = jnp.arange(9, dtype=jnp.int32)[None]

    def attend(q, k, v, caches, layer_idx):
        from production_stack_tpu.ops.attention import dense_causal_attention

        return dense_causal_attention(q, k, v), caches

    want, _ = llama.forward_tokens(cfg, params, tokens, pos, attend)
    got, _ = llama.forward_tokens(cfg, params, tokens, pos, attend,
                                  grouped_matmul=KERNEL)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_int8_experts_keep_ragged_dot_whatever_is_handed_in():
    """The W8A8 branch never reaches the kernel: a grouped matmul that
    raises proves it."""
    def never(*_):
        raise AssertionError("int8 experts went to the kernel")

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 64, 32)), jnp.float32)
    sizes = jnp.asarray([10, 0, 14], jnp.int32)
    group = jnp.repeat(jnp.arange(3), sizes, total_repeat_length=24)
    qw = quant.quantize_array(w, (1,))
    got = quant.ragged_quant_dot(x, qw, sizes, group, never)
    want = quant.ragged_quant_dot(x, qw, sizes, group)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    plain = quant.ragged_quant_dot(x, w, sizes, group, KERNEL)
    np.testing.assert_allclose(
        np.asarray(plain), np.asarray(jax.lax.ragged_dot(x, w, sizes)),
        rtol=1e-5, atol=1e-5)


# -- the runner's choice, and the counter -----------------------------------

def _ok(monkeypatch, backend, mesh_cfg, quantize=False, preset="olmoe-1b-7b"):
    """``_moe_grouped_kernel_ok`` for a runner that would hold the
    preset's experts (shapes alone) on a mesh of the CPU's devices."""
    from production_stack_tpu.engine import model_runner as mr
    from production_stack_tpu.parallel.shardings import rules_for_model

    cfg = ModelConfig.from_pretrained(preset)
    mesh = build_mesh(mesh_cfg)
    L, Xn = 2, cfg.num_experts
    E, F = cfg.hidden_size, cfg.intermediate_size

    def leaf(shape):
        w = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        if quantize:
            return {"q": jax.ShapeDtypeStruct(shape, jnp.int8), "s": w}
        return w

    params = {"layers": {"w_gate": leaf((L, Xn, E, F)),
                         "w_down": leaf((L, Xn, F, E))}}
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    return mr._moe_grouped_kernel_ok(cfg, mesh, rules_for_model(cfg, mesh),
                                     params)


def test_the_runner_takes_the_kernel_on_an_unsharded_tpu_alone(monkeypatch):
    one = MeshConfig(data=1, tensor=1, expert=1)
    assert _ok(monkeypatch, "tpu", one)
    assert not _ok(monkeypatch, "cpu", one)
    assert not _ok(monkeypatch, "tpu", one, quantize=True)
    # GSPMD cannot partition the custom call: a mesh that splits the
    # experts or an expert's width keeps ragged_dot
    assert not _ok(monkeypatch, "tpu", MeshConfig(data=1, tensor=1, expert=2))
    assert not _ok(monkeypatch, "tpu", MeshConfig(data=1, tensor=2, expert=1))
    # a dense model has no grouped matmul to choose
    assert not _ok(monkeypatch, "tpu", one, preset="tiny-llama")
    # widths that are no whole lane tiles stay with ragged_dot
    assert not _ok(monkeypatch, "tpu", one, preset="tiny-olmoe")


def test_a_cpu_runner_builds_its_programs_with_ragged_dot():
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.model_runner import ModelRunner

    config = EngineConfig(model=ModelConfig.from_pretrained("tiny-olmoe"))
    mesh = build_mesh(MeshConfig(data=1, tensor=1, expert=1),
                      devices=jax.devices()[:1])
    runner = ModelRunner(config, mesh, num_blocks=64)
    assert runner.moe_grouped_matmul is None
    assert runner.moe.grouped_kernel is False


@pytest.mark.parametrize("kernel", [False, True])
def test_layer_steps_are_counted_for_every_kind_and_by_what_ran_them(kernel):
    moe = MoeCounters(num_experts=4, top_k=2, grouped_kernel=kernel)
    hist = np.array([[3, 0, 5, 0, 8], [1, 1, 1, 5, 8]])  # two layers
    moe.record("decode", np.stack([hist, hist, hist]))  # three iterations
    moe.record("ragged", hist)
    s = moe.snapshot()
    assert s["moe_decode_layer_steps_total"] == 6
    assert s["moe_layer_steps_total"] == 8
    assert s["moe_grouped_kernel_layer_steps_total"] == (8 if kernel else 0)


def test_the_metric_reads_the_counters_and_nothing_where_there_are_none():
    """chipbench's ``moe_grouped_kernel_path_pct`` over the counters'
    deltas (the export itself: tests/test_olmoe.py); a program without
    them, the parent of the PR that added them, gives it nothing to read
    and nothing is raised."""
    import json
    import os
    import types

    from chipbench import layers

    with open(os.path.join(os.path.dirname(layers.__file__), "layer_metrics",
                           "moe_grouped_kernel_path_pct.json")) as f:
        spec = json.load(f)

    def read(before, after):
        return layers.prom_ratio(types.SimpleNamespace(
            prom_open=before, prom_close=after, manifest={}), spec)

    steps, on = (f"vllm:moe{k}_layer_steps_total"
                 for k in ("", "_grouped_kernel"))
    assert read({steps: 8.0, on: 8.0}, {steps: 40.0, on: 40.0}) == 100.0
    assert read({steps: 8.0, on: 0.0}, {steps: 40.0, on: 0.0}) == 0.0
    assert read({}, {"vllm:decode_dispatches_total": 5.0}) is None
    assert read({steps: 8.0, on: 8.0}, {steps: 8.0, on: 8.0}) is None


# -- the stream decoder -----------------------------------------------------

def _word_tokenizer(tmp_path, vocab=64):
    """chipbench's one-word-per-id tokenizer (chipbench/run.py
    prepare_model_dir), as the engine loads it."""
    import json

    from production_stack_tpu.engine.tokenizer import load_tokenizer_dir

    with open(tmp_path / "tokenizer.json", "w") as f:
        json.dump({"version": "1.0", "truncation": None, "padding": None,
                   "added_tokens": [], "normalizer": None,
                   "pre_tokenizer": {"type": "WhitespaceSplit"},
                   "post_processor": None, "decoder": None,
                   "model": {"type": "WordLevel",
                             "vocab": {f"t{i}": i for i in range(vocab)},
                             "unk_token": "t0"}}, f)
    with open(tmp_path / "tokenizer_config.json", "w") as f:
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast"}, f)
    return load_tokenizer_dir(str(tmp_path))


def test_the_windowed_decoder_reads_what_a_whole_decode_reads(tmp_path):
    tk = _word_tokenizer(tmp_path)
    calls = []

    def decode(ids):
        calls.append(len(ids))
        return tk.decode(ids)

    from production_stack_tpu.engine.tokenizer import WindowedDecoder

    text_of = WindowedDecoder(decode)
    ids = [int(i) for i in np.random.default_rng(0).integers(0, 64, 300)]
    for n in range(1, len(ids) + 1, 1 if len(ids) < 50 else 3):
        assert text_of(ids[:n]) == tk.decode(ids[:n])
    # and never decoded more than a few tokens at once
    assert max(calls) <= 6


def test_the_windowed_decoder_holds_back_an_unfinished_character():
    from production_stack_tpu.engine.tokenizer import (
        ByteTokenizer,
        WindowedDecoder,
    )

    tk = ByteTokenizer()
    text_of = WindowedDecoder(tk.decode)
    ids = list("a€b😀c".encode())
    seen = [text_of(ids[:n]) for n in range(1, len(ids) + 1)]
    assert all("�" not in s for s in seen)
    assert all(b.startswith(a) for a, b in zip(seen, seen[1:]))
    assert seen[-1] == "a€b😀c" == tk.decode(ids)
    assert seen[1] == seen[2] == "a" and seen[3] == "a€"
    # the byte tokenizer itself decodes whole lists: nothing to window
    assert tk.stream_decoder() == tk.decode
