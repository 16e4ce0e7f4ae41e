"""Pipeline parallelism over the ``stage`` mesh axis.

The reference gets PP by handing vLLM a Ray cluster
(helm/templates/ray-cluster.yaml + --pipeline-parallel-size there). Here PP
is a mesh axis, no Ray: layers are split into S stages (leading axis of the
stacked layer params is sharded over ``stage``), a batch is cut into M
microbatches, and a shard_map runs the classic pipeline schedule — at step
t every stage processes microbatch (t - stage) while activations rotate to
the next stage via ``ppermute`` over ICI/DCN. S + M - 1 steps total; the
bubble shrinks as M grows.

``pipelined_forward`` is the generic building block (used by the multichip
dryrun and tests); serving-engine integration (per-stage KV pools) is the
follow-on.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from jax import lax
from jax.sharding import Mesh, PartitionSpec as P



def _stage_body(layer_fn: Callable, params_stage, x):
    """Run this stage's stacked layers (L_stage, ...) over x via scan."""
    def step(h, lp):
        return layer_fn(lp, h), None

    out, _ = lax.scan(step, x, params_stage)
    return out


def pipelined_forward(
    layer_fn: Callable,  # (layer_params, activations (mb, ...)) -> activations
    stage_params,  # pytree, leaves (S, L_per_stage, ...) sharded over "stage"
    x: jnp.ndarray,  # (M, mb, ...) microbatched input
    mesh: Mesh,
    axis_name: str = "stage",
):
    """Pipeline-parallel forward. Returns (M, mb, ...) outputs."""
    n_stages = mesh.shape[axis_name]
    M = x.shape[0]

    def per_stage(params_local, x_local):
        # params_local: (1, L_per_stage, ...) this stage's layers
        # x_local: full (M, mb, ...) — only stage 0 reads it
        params_local = jax.tree.map(lambda a: a[0], params_local)
        stage = lax.axis_index(axis_name)
        steps = M + n_stages - 1
        mb_shape = x_local.shape[1:]

        def body(carry, t):
            buf, outputs = carry
            # stage 0 feeds microbatch t; others use what arrived on the ring
            feed = lax.dynamic_index_in_dim(
                x_local, jnp.minimum(t, M - 1), 0, keepdims=False
            )
            h_in = jnp.where(stage == 0, feed, buf)
            active = (t - stage >= 0) & (t - stage < M)
            h_out = _stage_body(layer_fn, params_local, h_in)
            h_out = jnp.where(active, h_out, buf)
            # last stage records its finished microbatch (index t - S + 1)
            done_idx = t - (n_stages - 1)
            outputs = lax.cond(
                (stage == n_stages - 1) & (done_idx >= 0),
                lambda o: lax.dynamic_update_index_in_dim(
                    o, h_out, jnp.maximum(done_idx, 0), 0
                ),
                lambda o: o,
                outputs,
            )
            # rotate activations to the next stage
            buf_next = lax.ppermute(
                h_out, axis_name,
                [(s, (s + 1) % n_stages) for s in range(n_stages)],
            )
            return (buf_next, outputs), None

        init = (
            jnp.zeros(mb_shape, x_local.dtype),
            jnp.zeros((M, *mb_shape), x_local.dtype),
        )
        (buf, outputs), _ = lax.scan(body, init, jnp.arange(steps))
        # every stage returns `outputs`; only the last stage's is real —
        # broadcast it back around the ring so all shards agree
        outputs = lax.ppermute(
            outputs, axis_name,
            [(s, (s + 1) % n_stages) for s in range(n_stages)],
        )  # last stage's buffer arrives at stage 0
        outputs = jax.lax.all_gather(outputs, axis_name)[0]
        return outputs

    stage_specs = jax.tree.map(lambda _: P(axis_name), stage_params)
    # stackcheck: disable=jit-cache-hygiene — pipelined_forward is only
    # called at trace time under the caller's jit (pp_runner compiles it
    # into per-stage step programs), so this shard_map is constructed
    # once per enclosing trace, not per dispatch
    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(stage_specs, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)


def split_layers_into_stages(stacked_params, n_stages: int):
    """(L, ...) stacked layer params → (S, L/S, ...) for the stage axis."""
    def _split(a):
        L = a.shape[0]
        assert L % n_stages == 0, f"{L} layers not divisible by {n_stages} stages"
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])

    return jax.tree.map(_split, stacked_params)
