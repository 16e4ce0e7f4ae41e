"""MoE dispatch correctness: the capacity-dispatch block must equal an
explicit dense top-k reference when capacity is sufficient, and must run
sharded over the expert axis."""

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.engine.weights import init_or_load
from production_stack_tpu.models import llama
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh


def dense_reference(cfg, lp, x):
    """All-experts dense compute + top-k combine (the exact semantics)."""
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


def test_dispatch_matches_dense_reference():
    cfg = ModelConfig.from_pretrained("tiny-mixtral")
    mesh = build_mesh(MeshConfig(data=1, tensor=1, expert=1),)
    params = init_or_load(cfg, mesh, seed=0)
    lp = jax.tree.map(lambda a: a[0], params["layers"])  # layer 0
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((12, cfg.hidden_size)), jnp.float32)
    got = llama._moe_mlp(cfg, lp, x)
    want = dense_reference(cfg, lp, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


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
