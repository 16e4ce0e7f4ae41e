"""The MoE block (models/llama.py ``_moe_mlp``): dropless sort and grouped
matmul against "every expert on every token, weighted", in both routing
orders, under skew and with padding rows; and sharded over the expert
axis."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import olmoe as reference
from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.engine.weights import init_or_load
from production_stack_tpu.models import llama
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

# float32 on the CPU on both sides: what differs is the order of the sums
# (grouped rows against whole matrices, k terms against X), a few ulp of
# outputs of size ~1
TOL = dict(rtol=2e-4, atol=2e-5)


def dense_reference(cfg, lp, x):
    """All experts on all tokens; top-k of the logits, then softmax over
    the k chosen (Mixtral's own wording of its routing)."""
    logits = jnp.einsum("te,ex->tx", x, lp["router"]).astype(jnp.float32)
    top_vals, top_idx = jax.lax.top_k(logits, cfg.num_experts_per_tok)
    weights = jax.nn.softmax(top_vals, axis=-1)
    gate = jnp.einsum("te,xef->txf", x, lp["w_gate"])
    up = jnp.einsum("te,xef->txf", x, lp["w_up"])
    expert_out = jnp.einsum("txf,xfe->txe", jax.nn.silu(gate) * up, lp["w_down"])
    picked = jnp.take_along_axis(
        expert_out, top_idx[:, :, None], axis=1
    )  # (T, k, E)
    return jnp.sum(picked * weights[:, :, None].astype(x.dtype), axis=1)


def olmoe_reference(cfg, lp, x):
    """chipbench's plain OLMoE block: softmax over all experts, top-k kept
    as they are."""
    return reference.moe_block(x, lp, cfg.num_experts_per_tok,
                               cfg.norm_topk_prob)


LAYER = 1  # the block reads one layer's experts out of the whole stack


def layer0(preset: str, seed: int = 0):
    """(cfg, the parameters of layer LAYER alone, the stacked layers)."""
    cfg = ModelConfig.from_pretrained(preset)
    mesh = build_mesh(MeshConfig(data=1, tensor=1, expert=1),)
    layers = init_or_load(cfg, mesh, seed=seed)["layers"]
    return cfg, jax.tree.map(lambda a: a[LAYER], layers), layers


def moe(cfg, lp, layers, x, live=None):
    """The served block on layer LAYER of ``layers``, router from ``lp``."""
    experts = {k: layers[k] for k in llama._EXPERT_WEIGHTS}
    return llama._moe_mlp(cfg, lp["router"], experts, LAYER, x, live)


@pytest.mark.parametrize("preset,want_fn", [
    ("tiny-mixtral", dense_reference),   # top-k, then softmax over the k
    ("tiny-olmoe", olmoe_reference),     # softmax over all, then top-k
    # Mixtral's order written OLMoE's way: renormalised weights are the same
    ("tiny-mixtral", olmoe_reference),
])
def test_dispatch_matches_dense_reference(preset, want_fn):
    cfg, lp, layers = layer0(preset)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((12, cfg.hidden_size)), jnp.float32)
    got, hist = moe(cfg, lp, layers, x)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want_fn(cfg, lp, x)), **TOL)
    assert int(hist.sum()) == 12 * cfg.num_experts_per_tok
    assert int(hist[-1]) == 0  # no padding rows


def test_routing_orders_differ_only_by_the_renormalisation():
    cfg, lp, layers = layer0("tiny-olmoe")
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (12, cfg.hidden_size)), jnp.float32)
    plain, _ = moe(cfg, lp, layers, x)
    renorm, _ = moe(dataclasses.replace(cfg, norm_topk_prob=True), lp,
                    layers, x)
    assert float(jnp.max(jnp.abs(plain - renorm))) > 1e-3
    np.testing.assert_allclose(
        np.asarray(renorm), np.asarray(dense_reference(cfg, lp, x)), **TOL)


@pytest.mark.parametrize("preset", ["tiny-mixtral", "tiny-olmoe"])
def test_dropless_under_skew(preset):
    """A router that sends EVERY token to expert 0 (one of its k choices):
    64 pairs on one expert. The capacity dispatch this block replaced held
    2 * T * k / X pairs an expert: 32 for tiny-olmoe, so it dropped half."""
    cfg, lp, layers = layer0(preset)
    T = 64
    x = jnp.abs(jnp.asarray(np.random.default_rng(2).standard_normal(
        (T, cfg.hidden_size)), jnp.float32))  # all positive
    lp = dict(lp, router=lp["router"].at[:, 0].set(1.0))  # logit 0 = sum(x)
    got, hist = moe(cfg, lp, layers, x)
    assert int(hist[0]) == T and int(hist.sum()) == T * cfg.num_experts_per_tok
    if preset == "tiny-olmoe":
        assert int(hist[0]) == 2 * int(
            2.0 * T * cfg.num_experts_per_tok / cfg.num_experts)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(olmoe_reference(cfg, lp, x)), **TOL)


def test_padding_rows_are_not_routed():
    """Rows marked not live change no live row's output, load no expert,
    come back as zeros and are counted in the null group."""
    cfg, lp, layers = layer0("tiny-olmoe")
    rng = np.random.default_rng(3)
    T, k = 40, cfg.num_experts_per_tok
    live = np.zeros(T, bool)
    live[rng.choice(T, 9, replace=False)] = True
    x = rng.standard_normal((T, cfg.hidden_size)).astype(np.float32)
    x[~live] *= 1e3  # garbage where nothing lives
    got, hist = moe(cfg, lp, layers, jnp.asarray(x), jnp.asarray(live))
    alone, hist_alone = moe(cfg, lp, layers, jnp.asarray(x[live]))
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(alone),
                               rtol=1e-6, atol=1e-6)
    assert not np.asarray(got)[~live].any()
    np.testing.assert_array_equal(np.asarray(hist[:-1]),
                                  np.asarray(hist_alone[:-1]))
    assert int(hist[-1]) == (T - 9) * k and int(hist[:-1].sum()) == 9 * k


def test_int8_experts_run_the_grouped_matmul():
    """W8A8 experts (per-row activation scale, per-expert weight scale)
    stay close to the float block."""
    from production_stack_tpu.engine import quant

    cfg = ModelConfig.from_pretrained("tiny-olmoe")
    mesh = build_mesh(MeshConfig(data=1, tensor=1, expert=1),)
    params = init_or_load(cfg, mesh, seed=0)
    qparams = quant.quantize_params(cfg, params)
    assert quant.is_quantized(qparams["layers"]["w_down"])
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (16, cfg.hidden_size)), jnp.float32)
    lp = jax.tree.map(lambda a: a[LAYER], params["layers"])
    want, _ = moe(cfg, lp, params["layers"], x)
    got, _ = moe(cfg, lp, qparams["layers"], x)
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err < 0.03, err


def test_moe_forward_sharded_over_expert_axis():
    cfg = ModelConfig.from_pretrained("tiny-mixtral")
    mesh = build_mesh(MeshConfig(data=1, tensor=2, expert=2))
    params = init_or_load(cfg, mesh, seed=0)
    tokens = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    with jax.set_mesh(mesh):
        sharded = jax.jit(llama.forward_dense, static_argnums=0)(cfg, params, tokens)

    single = build_mesh(MeshConfig(data=1, tensor=1),
                        devices=jax.devices()[:1])
    params_local = jax.device_put(jax.tree.map(np.asarray, params),
                                  jax.devices()[0])
    with jax.set_mesh(single):
        local = jax.jit(llama.forward_dense, static_argnums=0)(
            cfg, params_local, tokens
        )
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(local),
                               rtol=2e-4, atol=2e-4)
