"""Pallas TPU kernels for the state-space scan with heads (see ops/ssd.py
for the equations and for what ``g, dx, B, C`` are). The state lies
(layers, slots, H, N, P) float32: a head's N = 256 values of a channel on
the sublanes, its P = 128 channels on the lanes, so ``B_t`` and ``C_t`` come
as columns (N, 1) that broadcast along the lanes, ``d x`` as a row (1, P)
that broadcasts along the sublanes, the decay is one number a head, and
``y`` is a sum over sublanes: no transposes and no cross-lane reductions
in the one-row kernel.

- ``ssd_decode_step``: one token a slot. A grid cell holds one slot's
  states of ``hb`` heads of ONE group (so the cell's ``B`` and ``C`` are
  one column each); each (slot, head) state is read from HBM once and
  written once, in place (the state array is aliased input to output and
  indexed by the layer, so the donated array of all layers is never sliced
  or copied). The pipelined blocks hide the state's DMA. Bound by bytes:
  8.4 MB a slot and layer at Falcon-H1-34B's 32 heads of 256 x 128.
- ``ssd_chunk_scan``: the packed ragged stream, in the blocked (matmul)
  form of the recurrence (ops/ssd.py's header has its equations). A grid
  cell holds ``SCAN_HEADS_PER_CELL`` heads of one group; it walks the spans
  (``cu_q_lens``) in slot order, loads a span's states (zeros where the
  span starts its sequence), takes the span ``CHUNK`` rows at a time and
  stores the states back. A block is a window of ``CHUNK`` rows of the
  stream as they lie; rows of the window that are not the block's (before
  a span's first row of it, past the span's end, or, where the window was
  moved back to end at the stream's last row, before the block) are masked
  to steps that change nothing (g = 0, B = 0) and are not written. Per
  block: ``C B^T`` once for the cell's heads (they share the group's ``B``
  and ``C``); per head the running sum of ``g`` (a product with a
  triangle of ones), the decays ``exp(G_t - G_i)`` of the pairs i <= t from
  differences that are at most 0, and three products with the state and
  the rows. Everything float32, every product on the MXU at
  ``Precision.HIGHEST``; the state is carried in float32 across blocks,
  spans and the chunks of a prompt.
- ``ssd_ragged`` is what a ragged step calls: the stream's decode rows
  (one-row spans that continue a state) go through ``ssd_decode_step`` and
  every other span through ``ssd_chunk_scan``, which waits for each span's
  state to land and to leave (``kda_pallas.kda_ragged``'s split, for its
  reason).

Both names reach a device trace as ``%ssd_decode_step[.N]`` and
``%ssd_chunk_scan[.N]`` (chipbench/layer_metrics/ssd_*.json).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops import kda
from production_stack_tpu.ops.kda_pallas import _dot, _dot_nt

F32 = jnp.float32
# heads a decode grid cell holds: 16 states of (256, 128) float32 are
# 2 MiB, in and out and double-buffered 8 MiB of the 16 MiB of scoped VMEM
DECODE_HEADS_PER_CELL = 16
# rows of a span the span kernel takes at a time (the published code's
# mamba_chunk_size; whole (128, 128) tiles for the pairs' decays), and heads
# a grid cell holds: their d x and y blocks are (T, P) float32 each, 1 MiB
# at 2048 rows, beside the group's B and C, (T, N) each
CHUNK = 128
SCAN_HEADS_PER_CELL = 2


def _cell_heads(heads: int, groups: int, most: int) -> int:
    """Heads a grid cell holds: of one group, at most ``most``."""
    return math.gcd(heads // groups, most)


def _decode_kernel(layer_ref, active_ref, a_ref, dx_ref, b_ref, c_ref, s_ref,
                   y_ref, so_ref, *, hb: int):
    del layer_ref  # used by the index maps
    live = active_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _():
        B, C = b_ref[...], c_ref[...]  # (N, 1): the cell's group's
        for h in range(hb):
            S = a_ref[h:h + 1, :] * s_ref[h] + B * dx_ref[h:h + 1, :]
            so_ref[h] = S
            y_ref[h:h + 1, :] = jnp.sum(S * C, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        so_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def ssd_decode_step(state, layer, g, dx, B, C, active, *,
                    interpret: bool = False):
    """state (L, S, H, N, P) float32, donated and updated in place at
    ``layer``; g (S, H) the log-decay, dx (S, H, P), B and C (S, G, N)
    float32; active (S,) bool. Returns (y (S, H, P) float32, state)."""
    _, S, H, N, P = state.shape
    G = B.shape[1]
    hb = _cell_heads(H, G, DECODE_HEADS_PER_CELL)
    nhb, per_group = H // hb, H // G // hb

    def cells(x):  # (S, H, P) -> (S, nhb, hb, P)
        return x.astype(F32).reshape(S, nhb, hb, P)

    row_spec = pl.BlockSpec((None, None, hb, P),
                            lambda s, j, li, act: (s, j, 0, 0))
    col_spec = pl.BlockSpec((None, None, N, 1),
                            lambda s, j, li, act: (s, j // per_group, 0, 0))
    state_spec = pl.BlockSpec((None, None, hb, N, P),
                              lambda s, j, li, act: (li[0], s, j, 0, 0))
    # the decay, one number a head, as a row of the head's lanes
    a = jnp.broadcast_to(jnp.exp(g.astype(F32))[..., None], (S, H, P))
    y, state = pl.pallas_call(
        functools.partial(_decode_kernel, hb=hb),
        out_shape=(jax.ShapeDtypeStruct((S, nhb, hb, P), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S, nhb),
            in_specs=[row_spec, row_spec, col_spec, col_spec, state_spec],
            out_specs=(row_spec, state_spec)),
        input_output_aliases={6: 1},  # state in -> state out
        interpret=interpret,
        name="ssd_decode_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), active.astype(jnp.int32),
      cells(a), cells(dx), B.astype(F32)[..., None], C.astype(F32)[..., None],
      state)
    return y.reshape(S, H, P), state


def _chunk_kernel(layer_ref, cu_ref, ctx_ref, skip_ref, g_ref, dx_ref, b_ref,
                  c_ref, state_hbm, y_ref, state_out, st, sem, *, slots: int,
                  hb: int):
    del state_hbm  # aliased to state_out
    layer, cell = layer_ref[0], pl.program_id(0)
    y_ref[...] = jnp.zeros_like(y_ref)
    T, Cn = y_ref.shape[1], CHUNK
    iota = jax.lax.broadcasted_iota
    row, col = iota(jnp.int32, (Cn, Cn), 0), iota(jnp.int32, (Cn, Cn), 1)
    eye, lower = row == col, row >= col
    tri = lower.astype(F32)
    r1 = iota(jnp.int32, (Cn, 1), 0)

    def lanes(ref, at):
        """Rows ``at`` of B or C, whose N values lie a lane tile at a time
        (a window starts at any row, and such a load is one tile wide)."""
        return jnp.concatenate([ref[j, at, :] for j in range(ref.shape[0])],
                               axis=1)

    def whole_row(x):
        """(1, Cn), one number on every lane -> (1, P)."""
        P = y_ref.shape[2]
        return x[:, :P] if P <= Cn else jnp.concatenate([x] * (P // Cn), 1)

    def span(s, _):
        start, end = cu_ref[s], cu_ref[s + 1]

        @pl.when((end > start) & (skip_ref[s] == 0))
        def _():
            heads = state_out.at[layer, s, pl.ds(cell * hb, hb)]
            load = pltpu.make_async_copy(heads, st, sem)
            load.start()
            load.wait()
            fresh = ctx_ref[s] == end - start

            def block(b, Ss):
                # a window of Cn rows from the block's first, or the
                # stream's last Cn where that would pass its end
                lo = start + b * Cn
                base = jnp.minimum(lo, T - Cn)
                at = pl.ds(base, Cn)
                live = (r1 + base >= lo) & (r1 + base < end)
                Bm = jnp.where(live, lanes(b_ref, at), 0.0)   # (Cn, N)
                Cm = lanes(c_ref, at)
                CB = _dot_nt(Cm, Bm)                          # (Cn, Cn)
                gw = jnp.where(live, g_ref[at, :], 0.0)       # (Cn, hb)
                out = []
                for h, S in enumerate(Ss):
                    # the running sum of g over the window's rows, on every
                    # lane (a whole-tile product with the triangle of
                    # ones), then as a column and, off the diagonal, a row
                    Gb = _dot(tri, jnp.broadcast_to(gw[:, h:h + 1],
                                                    (Cn, Cn)))
                    Gc = Gb[:, 0:1]                           # (Cn, 1)
                    Gr = jnp.sum(jnp.where(eye, Gb, 0.0), axis=0,
                                 keepdims=True)               # (1, Cn)
                    pairs = jnp.exp(jnp.where(lower, Gc - Gr, -jnp.inf))
                    X = dx_ref[h, at, :]                      # (Cn, P)
                    y = (_dot(CB * pairs, X)
                         + _dot(Cm * jnp.exp(Gc), S))
                    y_ref[h, at, :] = jnp.where(live, y, y_ref[h, at, :])
                    # the block's whole decay, on every lane of a row
                    Gend = Gb[Cn - 1:Cn, :]                   # (1, Cn)
                    rest = (Gend - Gb)[:, 0:1]                # (Cn, 1)
                    out.append(whole_row(jnp.exp(Gend)) * S
                               + _dot((Bm * jnp.exp(rest)).T, X))
                return tuple(out)

            Ss = jax.lax.fori_loop(
                0, pl.cdiv(end - start, Cn), block,
                tuple(jnp.where(fresh, 0.0, st[h]) for h in range(hb)))
            for h, S in enumerate(Ss):
                st[h] = S
            store = pltpu.make_async_copy(st, heads, sem)
            store.start()
            store.wait()

        return 0

    jax.lax.fori_loop(0, slots, span, 0)


# jitted so that a program's layers share one trace of the kernel's body
# (``kda_pallas.kda_chunk_scan``'s reason)
@functools.partial(jax.jit, static_argnames="interpret")
def ssd_chunk_scan(state, layer, g, dx, B, C, cu_q_lens, context_lens,
                   skip=None, *, interpret: bool = False):
    """state (L, S, H, N, P) float32, donated and updated in place at
    ``layer``; g (T, H) the log-decay (<= 0), dx (T, H, P), B and C (T, G,
    N) float32, the packed stream; cu_q_lens (S + 1,) span offsets in slot
    order, context_lens (S,) each slot's context after its span (a span as
    long as its context starts from zeros); skip (S,) bool: spans left as
    they are, their rows read zero. Returns (y (T, H, P) float32, state)."""
    _, S, H, N, P = state.shape
    G = B.shape[1]
    if skip is None:
        skip = jnp.zeros((S,), jnp.bool_)
    T = dx.shape[0]
    assert P <= CHUNK or P % CHUNK == 0, P
    Tp = max(-(-T // 8) * 8, CHUNK)  # whole sublane tiles, one block or more

    def rows(x, axis):
        x = x.astype(F32)
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, Tp - T)
        return jnp.pad(x, pad) if Tp > T else x

    hb = _cell_heads(H, G, SCAN_HEADS_PER_CELL)
    per_group = H // G // hb
    head_spec = pl.BlockSpec((hb, Tp, P), lambda c, *_: (c, 0, 0))
    nt = max(N // 128, 1)  # lane tiles of a B or C row
    group_spec = pl.BlockSpec((None, nt, Tp, N // nt),
                              lambda c, *_: (c // per_group, 0, 0, 0))

    def tiles(x):  # (T, G, N) -> (G, nt, Tp, N / nt)
        return rows(x.reshape(T, G, nt, N // nt).transpose(1, 2, 0, 3), 2)

    y, state = pl.pallas_call(
        functools.partial(_chunk_kernel, slots=S, hb=hb),
        out_shape=(jax.ShapeDtypeStruct((H, Tp, P), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(H // hb,),
            in_specs=[pl.BlockSpec((None, Tp, hb), lambda c, *_: (c, 0, 0)),
                      head_spec, group_spec, group_spec,
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(head_spec, pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[pltpu.VMEM((hb, N, P), F32),
                            pltpu.SemaphoreType.DMA(())]),
        input_output_aliases={8: 1},  # state in -> state out
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret,
        name="ssd_chunk_scan",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      cu_q_lens.astype(jnp.int32), context_lens.astype(jnp.int32),
      skip.astype(jnp.int32),
      # a cell's heads' log-decays side by side, (cells, T, hb)
      rows(g.astype(F32).reshape(T, H // hb, hb).transpose(1, 0, 2), 1),
      rows(dx.transpose(1, 0, 2), 1), tiles(B), tiles(C), state)
    return y[:, :T].transpose(1, 0, 2), state


def ssd_ragged(state, layer, g, dx, B, C, cu_q_lens, context_lens, *,
               interpret: bool = False):
    """The packed stream (arguments as ``ssd_chunk_scan``'s): decode rows
    through the decode kernel, every other span through the span kernel,
    one after the other on the same donated state."""
    T = dx.shape[0]
    q_len = cu_q_lens[1:] - cu_q_lens[:-1]
    one = kda.continues_one_row(q_len, context_lens)
    first = jnp.minimum(cu_q_lens[:-1], T - 1)
    y_one, state = ssd_decode_step(
        state, layer, *(a[first] for a in (g, dx, B, C)), one,
        interpret=interpret)
    y, state = ssd_chunk_scan(state, layer, g, dx, B, C, cu_q_lens,
                              context_lens, skip=one, interpret=interpret)
    # a skipped span's row reads zero in ``y``, an idle slot's in ``y_one``
    return y.at[first].add(y_one), state
