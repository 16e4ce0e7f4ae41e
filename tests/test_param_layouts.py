"""In which order of bytes a runner keeps a weight is the model's to say
(``models/llama.py`` ``param_layouts``) and ``engine/weights.py``'s to do
(``lay_out``): the loaded tree keeps its keys, shapes and values (the
benchmark's references read it), the kept tree holds the same values with
the contracted axis last, and the forward takes either. What the order is
FOR (no step program copies a stack whole) is
tests/test_kernel_names_v5e.py ``test_step_program_copies_no_weight_stack``.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine import weights
from production_stack_tpu.engine.config import (
    MODEL_PRESETS,
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.quant import is_quantized, quantize_params
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.models import llama
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
from production_stack_tpu.parallel.shardings import rules_for_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chipbench_tiny(name):
    with open(os.path.join(ROOT, "chipbench", "tests", "configs", name,
                           "config.json")) as f:
        return dataclasses.replace(
            ModelConfig.from_hf_config(json.load(f), name), dtype="float32")


# every family the shared stack runs: the presets, and the hybrid stacks
# that have none
CONFIGS = {**{k: v for k, v in MODEL_PRESETS.items()
              if v.architecture != "whisper"},
           "tiny-solar-open2": _chipbench_tiny("tiny-solar-open2"),
           "tiny-falcon-h1": _chipbench_tiny("tiny-falcon-h1")}


def _is_axes(x):
    return isinstance(x, tuple)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_layouts_mirror_the_specs(name):
    cfg = CONFIGS[name]
    specs, layouts = llama.param_specs(cfg), llama.param_layouts(cfg)
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    flat_specs, tree = jax.tree.flatten(specs, is_leaf=_is_axes)
    flat_layouts = tree.flatten_up_to(layouts)  # raises on another tree
    assert tree == jax.tree.structure(shapes)
    for axes, order, leaf in zip(flat_specs, flat_layouts,
                                 jax.tree.leaves(shapes)):
        assert order is None or sorted(order) == list(range(leaf.ndim)), axes
        if order is not None:
            # the rule: the contracted axis, EMBED, last (models/sambay.py
            # names the layer axis alone); the rest in order
            assert axes[order[-1]] in ("embed", None) and order[0] == 0
            assert list(order[:-1]) == sorted(order[:-1])
    kept = jax.eval_shape(lambda: weights.lay_out(
        cfg, llama.init_params(cfg, jax.random.PRNGKey(0))))
    n = sum(o is not None for o in flat_layouts)
    assert sum(k.endswith("_t") and k[:-2] in specs[stack]
               for stack in kept if isinstance(kept[stack], dict)
               for k in kept[stack]) == n
    assert (sum(a.size for a in jax.tree.leaves(kept))
            == sum(a.size for a in jax.tree.leaves(shapes)))


def test_the_cells_attention_projections_are_laid_out():
    """What the survey found copied (PERF.md section 5, PR 53), by name."""
    lay = llama.param_layouts(MODEL_PRESETS["tiny-qwen3"])["layers"]
    assert lay["wq"] == lay["wk"] == lay["wv"] == (0, 2, 3, 1)
    assert lay["wo"] is None and lay["w_gate"] is None
    gqa = llama.param_layouts(CONFIGS["tiny-solar-open2"])["gqa"]
    assert gqa["wq"] == gqa["wg"] == (0, 2, 1)
    assert gqa["wk"] == gqa["wv"] == (0, 2, 3, 1) and gqa["wo"] is None
    phi = llama.param_layouts(MODEL_PRESETS["tiny-phi4flash"])
    assert phi["attn"]["wq"] == phi["cross"]["wq"] == (0, 2, 1)
    assert phi["attn"]["wo"] is None and phi["mamba"]["w_in"] is None
    # made transposed already, or never copied: nothing to lay out
    for name in ("tiny-falcon-h1", "tiny-kimi-linear", "tiny-pangu"):
        assert not any(jax.tree.leaves(llama.param_layouts(CONFIGS[name])))


def _mesh(tp=1):
    return build_mesh(MeshConfig(data=1, tensor=tp), jax.devices()[:tp])


def _init(cfg, mesh, seed=3):
    return weights.init_random(cfg, mesh, rules_for_model(cfg, mesh), seed)


def _logits(cfg, params, mesh):
    tokens = (jnp.arange(24, dtype=jnp.int32).reshape(2, 12) * 7
              % cfg.vocab_size)
    with jax.set_mesh(mesh):
        return np.asarray(jax.jit(
            lambda p: llama.forward_dense(cfg, p, tokens))(params))


@pytest.mark.parametrize("name,tp", [
    ("tiny-qwen3", 1), ("tiny-olmoe", 1), ("tiny-solar-open2", 1),
    ("tiny-phi4flash", 1), ("tiny-qwen3", 2)])
def test_laying_out_changes_no_value(name, tp):
    cfg, mesh = CONFIGS[name], _mesh(tp)
    rules = rules_for_model(cfg, mesh)
    made = _init(cfg, mesh)
    want = _logits(cfg, made, mesh)
    host = jax.tree.map(np.asarray, made)
    orders = llama.param_layouts(cfg)
    with jax.set_mesh(mesh):
        kept = weights.lay_out(cfg, made, mesh, rules)
    laid = 0
    for stack, leaves in host.items():
        if not isinstance(leaves, dict):
            assert kept[stack] is made[stack]
            continue
        for k, a in leaves.items():
            order = orders[stack][k]
            if order is None:
                assert kept[stack][k] is made[stack][k]
                continue
            laid += 1
            assert k not in kept[stack] and made[stack][k].is_deleted()
            t = kept[stack][k + "_t"]
            np.testing.assert_array_equal(
                np.asarray(t), a.transpose(order).reshape(t.shape))
            # the heads' axis is still the sharded one
            assert (t.sharding.spec[1] == "tensor") == (
                "tensor" in made[stack][k].sharding.spec)
    assert laid >= 3
    # the same sums, which the CPU's float32 product takes in another order
    np.testing.assert_allclose(_logits(cfg, kept, mesh), want, rtol=0,
                               atol=3e-5)


def test_an_int8_tree_keeps_its_own_einsum(monkeypatch):
    """A quantized stack is a container whose einsum contracts its own
    axes: ``lay_out`` leaves it as it is and the forward hands it to
    ``quant_einsum`` as before."""
    cfg, mesh = MODEL_PRESETS["tiny-qwen3"], _mesh()
    q = quantize_params(cfg, _init(cfg, mesh))
    assert is_quantized(q["layers"]["wq"])
    want = _logits(cfg, q, mesh)
    kept = weights.lay_out(cfg, q, mesh, rules_for_model(cfg, mesh))
    assert kept["layers"]["wq"] is q["layers"]["wq"]
    assert not any(k.endswith("_t") for k in kept["layers"])
    seen = []
    real = llama.quant_einsum
    monkeypatch.setattr(
        llama, "quant_einsum",
        lambda eq, x, w, *a: (seen.append((eq, is_quantized(w))),
                              real(eq, x, w, *a))[1])
    np.testing.assert_array_equal(_logits(cfg, kept, mesh), want)
    assert seen.count(("...te,ehd->...thd", True)) == 3  # q, k, v: one trace


def _engine(cfg, params=None, **over):
    return LLMEngine(
        EngineConfig(model=dataclasses.replace(cfg, **over),
                     cache=CacheConfig(block_size=16, num_blocks=32),
                     scheduler=SchedulerConfig(max_num_seqs=2,
                                               max_num_batched_tokens=64),
                     mesh=MeshConfig(data=1, tensor=1)),
        mesh=_mesh(), params=params)


def _generate(eng, n=6):
    eng.add_request("a", prompt_token_ids=[5, 9, 2, 77, 31, 8, 40],
                    sampling=SamplingParams(temperature=0.0, max_tokens=n,
                                            logprobs=2, ignore_eos=True))
    toks, lps = [], []
    while eng.has_unfinished():
        for out in eng.step():
            toks += out.new_token_ids
            lps += [lp[0] for lp in (out.new_logprobs or [])]
    return toks, lps


@pytest.mark.parametrize("name", ["tiny-qwen3", "tiny-solar-open2"])
def test_a_runner_lays_out_what_it_loads_and_serves_what_it_is_handed(name):
    """The runner's own tree is laid out; a tree handed in is served as it
    is (its caller keeps it); both step programs give the same tokens and
    log-probabilities either way."""
    cfg = CONFIGS[name]
    eng = _engine(cfg)
    stack = "gqa" if "gqa" in eng.runner.params else "layers"
    assert {"wq_t", "wk_t", "wv_t"} <= set(eng.runner.params[stack])
    assert "wq" not in eng.runner.params[stack]
    made = _init(cfg, _mesh(), seed=eng.config.seed)
    handed = _engine(cfg, params=made)
    assert handed.runner.params[stack]["wq"] is made[stack]["wq"]
    toks, lps = _generate(eng)
    toks2, lps2 = _generate(handed)
    assert toks == toks2 and len(toks) == 6
    np.testing.assert_allclose(lps, lps2, rtol=0, atol=1e-5)


def test_wake_brings_the_weights_back_as_they_lay():
    eng = _engine(MODEL_PRESETS["tiny-qwen3"])

    def described(tree):
        return jax.tree.map(lambda a: (a.shape, a.dtype, a.sharding), tree)

    before = described(eng.runner.params)
    assert before["layers"]["wq_t"][0] == (2, 128, 128)
    eng.sleep_mode(level=2)
    assert not eng.runner.params_alive
    eng.wake_mode()
    assert described(eng.runner.params) == before
