"""Pipeline parallelism: 4-stage pipelined forward must equal the
sequential run of all layers."""

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
from production_stack_tpu.parallel.pipeline import (
    pipelined_forward,
    split_layers_into_stages,
)


def layer_fn(lp, h):
    # simple MLP-ish layer: h @ W + residual with nonlinearity
    return h + jnp.tanh(h @ lp["w"] + lp["b"])


def test_pipelined_matches_sequential():
    rng = np.random.default_rng(0)
    L, E = 8, 16
    M, mb = 6, 4  # 6 microbatches of 4 rows
    params = {
        "w": jnp.asarray(rng.standard_normal((L, E, E)) * 0.1, jnp.float32),
        "b": jnp.asarray(rng.standard_normal((L, E)) * 0.1, jnp.float32),
    }
    x = jnp.asarray(rng.standard_normal((M, mb, E)), jnp.float32)

    # sequential reference
    def seq_forward(x2):
        h = x2
        for i in range(L):
            h = layer_fn(jax.tree.map(lambda a: a[i], params), h)
        return h

    want = jax.vmap(seq_forward)(x)

    mesh = build_mesh(MeshConfig(data=1, stage=4, tensor=2))
    staged = split_layers_into_stages(params, 4)
    with jax.set_mesh(mesh):
        got = jax.jit(
            lambda p, xx: pipelined_forward(layer_fn, p, xx, mesh, "stage")
        )(staged, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_single_stage_degenerates():
    rng = np.random.default_rng(1)
    L, E, M, mb = 4, 8, 2, 3
    params = {
        "w": jnp.asarray(rng.standard_normal((L, E, E)) * 0.1, jnp.float32),
        "b": jnp.zeros((L, E), jnp.float32),
    }
    x = jnp.asarray(rng.standard_normal((M, mb, E)), jnp.float32)
    mesh = build_mesh(MeshConfig(stage=1, tensor=1),)
    staged = split_layers_into_stages(params, 1)

    def seq_forward(x2):
        h = x2
        for i in range(L):
            h = layer_fn(jax.tree.map(lambda a: a[i], params), h)
        return h

    with jax.set_mesh(mesh):
        got = pipelined_forward(layer_fn, staged, x, mesh, "stage")
    want = jax.vmap(seq_forward)(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
