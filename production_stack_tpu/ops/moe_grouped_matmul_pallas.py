"""The MoE block's grouped matmul as one Pallas TPU kernel.

``moe_grouped_matmul(x, w, group_sizes)`` is ``jax.lax.ragged_dot`` for
the shapes ``models/llama.py`` ``_moe_mlp`` hands it: rows ``x`` (M, K)
sorted by group, the stacked experts ``w`` (G, K, N) of ALL the stack's
layers where they lie (G = L * X, every other layer's group empty), the
G group sizes; bf16 operands, float32 accumulation, one (M, N) result.
Rows past ``sum(group_sizes)`` (the block's null groups: idle slots,
padding, pairs on experts held elsewhere) are never visited and left
undefined, as ``ragged_dot`` leaves them.

Why not XLA's own ``ragged-dot`` kernel: at a decode step's handful of
rows a group it reads the experts at a third of the HBM's pace (PERF.md
section 5). The call is bound by the experts' bytes, so the kernel is
built around their DMA: a visit is one (row tile, group) pair that
intersect, its weight block is megabytes ((K, tn) of the group's matrix,
picked out of the whole stack by the BlockSpec's index map from
scalar-prefetched metadata, double-buffered by the pipeline), and the
row tile is small enough that the MXU's work on it hides under that DMA.
Empty groups cost no visit and the grid ends at the last live row: its
middle dimension is the metadata's count of visits, a traced scalar.

Grid (N tiles, visits). Consecutive visits of one group (a group that
crosses a row-tile boundary) keep their weight block, and consecutive
visits of one row tile keep the output block in VMEM: each visit merges
its group's rows into it under a row mask.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
# the largest weight block the pipeline double-buffers: OLMoE's 2048 x
# 1024 projection (4 MB) and Solar-Open2's 4096 x 1280 (10.5 MB) go whole,
# the Pangu share's 7680 x 2048 (31 MB) in two halves of N
BLOCK_BYTES = 16 * 2 ** 20
# rows a visit: a decode step's groups hold 1-8, and up to 128 rows the
# MXU's pass over a weight block costs what the block's DMA hides
ROW_TILE = 128


def _n_tile(k: int, n: int, itemsize: int) -> int:
    """The widest cut of N, a multiple of 128 lanes that divides it, at
    which a (K, tn) block is at most ``BLOCK_BYTES``; N itself if that
    fits; 0 if no such cut exists."""
    if k * n * itemsize <= BLOCK_BYTES:
        return n
    cuts = [t for t in range(128, n, 128)
            if n % t == 0 and k * t * itemsize <= BLOCK_BYTES]
    return cuts[-1] if cuts else 0


def grouped_kernel_path(k: int, n: int, itemsize: int = 2) -> bool:
    """Whether the kernel serves a projection of (K, N) matrices: whole
    lane tiles both ways, and a weight block that fits. At every shape
    of the fixed-input table (PERF.md section 5: 512, 4096 and 16,384
    rows at the three MoE configurations' widths) it is at least twice as
    fast as XLA's ``ragged-dot``, so no row count is left to that."""
    return k % 128 == 0 and n % 128 == 0 and _n_tile(k, n, itemsize) > 0


def tiling_for(m: int, k: int, n: int, itemsize: int = 2) -> tuple:
    """(tm, tn) from the call's static shapes. K is never cut: no
    accumulator, a group's second visit re-reads nothing, and the sums
    come out as ``ragged_dot``'s to the bit (a K cut changes their order
    and was slower at every shape tried: PERF.md section 5)."""
    return min(ROW_TILE, -(-m // 16) * 16), _n_tile(k, n, itemsize) or n


def visit_metadata(group_sizes: jnp.ndarray, m: int, tm: int):
    """The kernel's work list, from the G group sizes.

    A visit is a (row tile, group) pair with a row in common, in row
    order. Returns (group_ids (V,), tile_ids (V,), starts (G,), ends (G,),
    num_visits ()), all int32, V = row tiles + G - 1 the most there can
    be; entries past ``num_visits`` repeat valid indices and are never
    run."""
    G = group_sizes.shape[0]
    tiles_m = -(-m // tm)
    V = tiles_m + G - 1
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    starts = ends - sizes
    first_tile = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    visits_end = jnp.cumsum(tiles, dtype=jnp.int32)
    v = jnp.arange(V, dtype=jnp.int32)
    # the group of visit v: how many groups' visits end at or before it
    group_ids = jnp.minimum(
        jnp.sum(visits_end[None, :] <= v[:, None], axis=1, dtype=jnp.int32),
        G - 1)
    tile_ids = jnp.clip(
        first_tile[group_ids] + v - (visits_end - tiles)[group_ids],
        0, tiles_m - 1)
    return group_ids, tile_ids, starts, ends, visits_end[-1]


def _kernel(group_ids, tile_ids, starts, ends, x_ref, w_ref, o_ref, *,
            tm: int):
    v = pl.program_id(1)
    g, tile = group_ids[v], tile_ids[v]
    rows = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=F32)
    row = tile * tm + jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
    mine = (row >= starts[g]) & (row < ends[g])
    # the first visit of a row tile finds nothing of an earlier group in
    # the output block
    fresh = (v == 0) | (tile != tile_ids[jnp.maximum(v - 1, 0)])
    held = jnp.where(fresh, 0.0, o_ref[...].astype(F32))
    o_ref[...] = jnp.where(mine, rows, held).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def moe_grouped_matmul(x: jnp.ndarray, w: jnp.ndarray,
                       group_sizes: jnp.ndarray, *, tiling=None,
                       interpret: bool = False) -> jnp.ndarray:
    """x (M, K) rows sorted by group, w (G, K, N), group_sizes (G,)
    int32: row i times the matrix of its group, float32 accumulation,
    (M, N) in x's dtype; rows past sum(group_sizes) undefined.
    ``tiling`` (tm, tn) overrides ``tiling_for``; tn divides N."""
    M, K = x.shape
    G, _, N = w.shape
    tm, tn = tiling or tiling_for(M, K, N, w.dtype.itemsize)
    assert N % tn == 0, (N, tn)
    *meta, num_visits = visit_metadata(group_sizes, M, tm)
    item = x.dtype.itemsize
    blocks = (2 * (K * tn * w.dtype.itemsize + tm * K * item + tm * tn * item)
              + 3 * tm * tn * 4)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N // tn, num_visits),
            in_specs=[
                pl.BlockSpec((tm, K), lambda n, v, gid, tid, *_: (tid[v], 0)),
                pl.BlockSpec((None, K, tn),
                             lambda n, v, gid, *_: (gid[v], 0, n)),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n, v, gid, tid, *_: (tid[v], n)),
        ),
        # the kernel's own limit, so that no configuration needs a libtpu
        # flag for it: its blocks, double-buffered, and some room
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(blocks + 4 * 2 ** 20, 16 * 2 ** 20)),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(*meta, x, w)
