"""Ring attention over a 4-way seq axis must equal dense causal attention."""

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.ops.attention import dense_causal_attention
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
from production_stack_tpu.parallel.ring_attention import ring_causal_attention


def test_ring_matches_dense_causal():
    mesh = build_mesh(MeshConfig(data=1, seq=4, tensor=2))
    rng = np.random.default_rng(0)
    B, S, H, KH, D = 2, 32, 4, 2, 16  # S=32 over 4 shards → 8 local
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KH, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KH, D)), jnp.float32)

    with jax.set_mesh(mesh):
        got = jax.jit(
            lambda q, k, v: ring_causal_attention(q, k, v, mesh, "seq")
        )(q, k, v)
    want = dense_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_single_shard_degenerates():
    mesh = build_mesh(MeshConfig(data=1, seq=1, tensor=1))
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 8, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 8, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 8, 2, 8)), jnp.float32)
    with jax.set_mesh(mesh):
        got = ring_causal_attention(q, k, v, mesh, "seq")
    want = dense_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
