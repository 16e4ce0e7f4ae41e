"""Pallas TPU kernels for the Gated DeltaNet recurrence (see ops/gdn.py for
the equations, the block form and what ``a, kb, k, q, vb`` are). The state
lies (layers, slots, H / 2, d_k, 2 d_v) float32: a PAIR of heads side by
side on the lanes (2 x 192 = 384 = three whole lane tiles at Olmo-Hybrid's
96 x 192, where a head alone would pad to 256), the key channels on the
sublanes.

- ``gdn_decode_step``: one token a slot. A grid cell holds one slot's
  states of ``pb`` pairs; each is read from HBM once and written once, in
  place (the state array is aliased input to output and indexed by the
  layer, so the donated array of all layers is never sliced or copied).
  The per-key-channel vectors (kb, k, q) come in with the channel on the
  sublanes and the cell's heads on the lanes, so a pair's two columns
  spread over its lanes by a select of two lane broadcasts; the decay and
  ``vb`` come as rows over the pair's lanes; the two contractions over d_k
  are sums over sublanes: no transposes and no cross-lane reductions.
- ``gdn_chunk_scan``: the packed ragged stream in the block form. A grid
  cell holds one pair of heads; it walks the spans (``cu_q_lens``) in slot
  order, loads a span's pair of states (zeros where the span starts its
  sequence), takes the span ``CHUNK`` rows at a time and stores the states
  back. A block is a window of ``CHUNK`` rows of the stream as they lie;
  rows of the window that are not the block's (before a span's first row of
  it, past the span's end, or, where the window was moved back to end at
  the stream's last row, before the block) are masked to steps that change
  nothing (g = 0, kb = k = q = 0) and are not written. Per block and head,
  everything float32, every product on the MXU at ``Precision.HIGHEST``:
  the running sum of ``g`` (a product with a triangle of ones), ``Gamma``
  from differences that are at most 0, ``[Kb; Q] K^T`` in one product,
  ``(I + A)^-1`` level by level (``inv <- inv - inv A_s inv``: the block
  inverse of two halves whose own inverses are known, from single rows
  up: log2(CHUNK) levels, never CHUNK dependent row steps), then the
  products with the state. What touches the value lanes runs on the PAIR
  (the two heads' results selected by lane): the value rows and the state
  are then whole tiles as they lie and nothing is sliced at lane 192.
- ``gdn_ragged`` is what a ragged step calls: the stream's decode rows
  (one-row spans that continue a state) go through ``gdn_decode_step``,
  whose pipelined blocks hide the state's DMA, and every other span through
  ``gdn_chunk_scan``, which waits for each span's state to land and to
  leave (``kda_pallas.kda_ragged``'s split, for its reason).

Both names reach a device trace as ``%gdn_decode_step[.N]`` and
``%gdn_chunk_scan[.N]`` (chipbench/layer_metrics/gdn_*.json).
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
# pairs of heads a decode grid cell holds: 15 pairs of (96, 384) float32 are
# 2.1 MiB, in and out and double-buffered 8.4 MiB of the 16 MiB of scoped
# VMEM (the other kernels' cells are 2 MiB too)
DECODE_PAIRS_PER_CELL = 15
# rows of a span the span kernel takes at a time (KDA's block)
CHUNK = 64


def _decode_kernel(layer_ref, active_ref, a_ref, kb_ref, k_ref, q_ref,
                   vb_ref, s_ref, o_ref, so_ref, *, pb: int):
    del layer_ref  # used by the index maps
    live = active_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _():
        lanes = s_ref.shape[-1]
        first = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) < lanes // 2
        for p in range(pb):
            def cols(ref):
                """The pair's two heads' columns (d_k, 1), each over its
                own head's lanes."""
                return jnp.where(first, ref[:, 2 * p:2 * p + 1],
                                 ref[:, 2 * p + 1:2 * p + 2])

            S = s_ref[p] * a_ref[p]
            w = vb_ref[p] - jnp.sum(S * cols(kb_ref), axis=0, keepdims=True)
            S = S + cols(k_ref) * w
            so_ref[p] = S
            o_ref[p] = jnp.sum(S * cols(q_ref), axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def gdn_decode_step(state, layer, a, kb, k, q, vb, active, *,
                    interpret: bool = False):
    """state (Lg, S, H / 2, dk, 2 dv) float32, donated and updated in place
    at ``layer``; a (S, H, 1) the decay, kb, k, q (S, H, dk) and vb (S, H,
    dv) float32; active (S,) bool. Returns (o (S, H, dv) float32, state)."""
    _, S, P, dk, dv2 = state.shape
    H, dv = 2 * P, dv2 // 2
    pb = math.gcd(P, DECODE_PAIRS_PER_CELL)
    nb = P // pb

    def cols(x):  # (S, H, dk) -> (S, nb, dk, 2 pb): channel on sublanes
        return x.astype(F32).reshape(S, nb, 2 * pb, dk).transpose(0, 1, 3, 2)

    def rows(x):  # (S, H, dv) -> (S, P, 1, 2 dv): a pair's lanes
        return x.astype(F32).reshape(S, P, 1, dv2)

    col_spec = pl.BlockSpec((None, None, dk, 2 * pb),
                            lambda s, j, li, act: (s, j, 0, 0))
    row_spec = pl.BlockSpec((None, pb, 1, dv2),
                            lambda s, j, li, act: (s, j, 0, 0))
    state_spec = pl.BlockSpec((None, None, pb, dk, dv2),
                              lambda s, j, li, act: (li[0], s, j, 0, 0))
    # the decay, one number a head, on every lane of the head
    a = jnp.broadcast_to(a.astype(F32).reshape(S, H, 1), (S, H, dv))
    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, pb=pb),
        out_shape=(jax.ShapeDtypeStruct((S, P, 1, dv2), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S, nb),
            in_specs=[row_spec] + [col_spec] * 3 + [row_spec, state_spec],
            out_specs=(row_spec, state_spec)),
        input_output_aliases={7: 1},  # state in -> state out
        interpret=interpret,
        name="gdn_decode_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), active.astype(jnp.int32),
      rows(a), cols(kb), cols(k), cols(q), rows(vb), state)
    return o.reshape(S, H, dv), state


def _chunk_kernel(layer_ref, cu_ref, ctx_ref, skip_ref, g_ref, kb_ref, k_ref,
                  q_ref, vb_ref, state_hbm, o_ref, state_out, st, sem, *,
                  slots: int):
    del state_hbm  # aliased to state_out
    layer, cell = layer_ref[0], pl.program_id(0)
    o_ref[...] = jnp.zeros_like(o_ref)
    (nt, T, tile), C = o_ref.shape, CHUNK
    lanes = nt * tile
    iota = jax.lax.broadcasted_iota
    row, col = iota(jnp.int32, (C, C), 0), iota(jnp.int32, (C, C), 1)
    eye, lower = row == col, row >= col
    tri, eye_f = lower.astype(F32), eye.astype(F32)
    r1 = iota(jnp.int32, (C, 1), 0)
    first = iota(jnp.int32, (1, lanes), 1) < lanes // 2
    # level s of the block's triangle: (t, i) with t in the upper and i in
    # the lower half of the same group of 2 s rows
    levels = [1 << n for n in range(C.bit_length() - 1)]
    pairs = {s: ((row & s) != 0) & ((row ^ col) & -s == s) for s in levels}

    def by_head(a, b):
        """Head 0's result on its lanes, head 1's on the others."""
        return jnp.where(first, a, b)

    def whole_row(x):
        """(1, C), one number on every lane -> (1, lanes)."""
        x = jnp.concatenate([x] * -(-lanes // C), axis=1)
        return x if x.shape[1] == lanes else x[:, :lanes]

    def head(h, at, live):
        """What head ``h`` of the pair makes of the window's rows alone:
        its rows against the state (exp(G) Kb, exp(G) Q: (2 C, dk)),
        (I + A)^-1, B, the rows that build the next state and the block's
        whole decay."""
        g = jnp.where(live, g_ref[at, h:h + 1], 0.0)          # (C, 1)
        kb, k, q = (jnp.where(live, x[h, at, :], 0.0)
                    for x in (kb_ref, k_ref, q_ref))          # (C, dk)
        # the running sum of g over the window's rows, on every lane (a
        # whole-tile product with the triangle of ones), then as a column
        # and, off the diagonal, a row
        Gb = _dot(tri, jnp.broadcast_to(g, (C, C)))
        Gc = Gb[:, 0:1]
        Gr = jnp.sum(jnp.where(eye, Gb, 0.0), axis=0, keepdims=True)
        gamma = jnp.exp(jnp.where(lower, Gc - Gr, -jnp.inf))
        AB = _dot_nt(jnp.concatenate([kb, q]), k) * jnp.concatenate(
            [gamma, gamma])
        A = jnp.where(eye, 0.0, AB[:C])
        # (I + A)^-1, level by level; single rows are their own inverse,
        # so the first level is I - A_1
        inv = eye_f - jnp.where(pairs[1], A, 0.0)
        for s in levels[1:]:
            inv = inv - _dot(inv, _dot(jnp.where(pairs[s], A, 0.0), inv))
        dec = jnp.exp(Gc)
        Gend = Gb[C - 1:C, :]                  # (1, C), the same on every lane
        return (jnp.concatenate([kb * dec, q * dec]), inv, AB[C:],
                (k * jnp.exp(Gend[:, 0:1] - Gc)).T, whole_row(jnp.exp(Gend)))

    def block(at, live, S):
        """The rows ``live`` of the C-row window ``at``: the pair's state
        S (dk, 2 dv) -> S after them, their outputs stored."""
        (x0, inv0, B0, kt0, e0), (x1, inv1, B1, kt1, e1) = (
            head(h, at, live) for h in (0, 1))
        # the value rows lie a lane tile at a time (a window starts at any
        # row, and such a load is one tile wide)
        vb = jnp.where(live, jnp.concatenate(
            [vb_ref[j, at, :] for j in range(nt)], axis=1), 0.0)  # (C, 2 dv)
        on_state = _dot(jnp.concatenate([x0, x1]), S)          # (4 C, 2 dv)
        r = vb - by_head(on_state[:C], on_state[2 * C:3 * C])
        w = by_head(_dot(inv0, r), _dot(inv1, r))
        o = by_head(on_state[C:2 * C] + _dot(B0, w),
                    on_state[3 * C:] + _dot(B1, w))
        for j in range(nt):
            o_ref[j, at, :] = jnp.where(
                live, o[:, j * tile:(j + 1) * tile], o_ref[j, at, :])
        return by_head(e0, e1) * S + by_head(_dot(kt0, w), _dot(kt1, w))

    def span(s, _):
        start, end = cu_ref[s], cu_ref[s + 1]

        @pl.when((end > start) & (skip_ref[s] == 0))
        def _():
            held = state_out.at[layer, s, pl.ds(cell, 1)]
            load = pltpu.make_async_copy(held, st, sem)
            load.start()
            load.wait()
            fresh = ctx_ref[s] == end - start

            def blocks(b, S):
                # a window of C rows from the block's first, or the
                # stream's last C where that would pass its end
                lo = start + b * C
                base = jnp.minimum(lo, T - C)
                live = (r1 + base >= lo) & (r1 + base < end)
                return block(pl.ds(base, C), live, S)

            st[0] = jax.lax.fori_loop(
                0, pl.cdiv(end - start, C), blocks,
                jnp.where(fresh, 0.0, st[0]))
            store = pltpu.make_async_copy(st, held, sem)
            store.start()
            store.wait()

        return 0

    jax.lax.fori_loop(0, slots, span, 0)


# jitted so that a program's layers share one trace of the kernel's body
# (``kda_pallas.kda_chunk_scan``'s reason)
@functools.partial(jax.jit, static_argnames="interpret")
def gdn_chunk_scan(state, layer, g, kb, k, q, vb, cu_q_lens, context_lens,
                   skip=None, *, interpret: bool = False):
    """state (Lg, S, H / 2, dk, 2 dv) float32, donated and updated in place
    at ``layer``; g (T, H, 1) the log-decay (<= 0), kb, k, q (T, H, dk) and
    vb (T, H, dv) float32, the packed stream; cu_q_lens (S + 1,) span
    offsets in slot order, context_lens (S,) each slot's context after its
    span (a span as long as its context starts from zeros); skip (S,) bool:
    spans left as they are, their rows read zero. Returns (o (T, H, dv)
    float32, state)."""
    _, S, P, dk, dv2 = state.shape
    H = 2 * P
    if skip is None:
        skip = jnp.zeros((S,), jnp.bool_)
    T = k.shape[0]
    Tp = max(-(-T // 8) * 8, CHUNK)  # whole sublane tiles, one block or more

    def rows(x, axis):
        x = x.astype(F32)
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, Tp - T)
        return jnp.pad(x, pad) if Tp > T else x

    def heads_first(x):  # (T, H, dk) -> (H, Tp, dk)
        return rows(x.transpose(1, 0, 2), 1)

    nt = dv2 // 128 if dv2 % 128 == 0 else 1  # lane tiles of a pair's rows

    def tiles(x):  # (T, H, dv) -> (P, nt, Tp, 2 dv / nt): a pair's lanes
        return rows(x.reshape(T, P, nt, dv2 // nt).transpose(1, 2, 0, 3), 2)

    head_spec = pl.BlockSpec((2, Tp, dk), lambda c, *_: (c, 0, 0))
    tile_spec = pl.BlockSpec((None, nt, Tp, dv2 // nt),
                             lambda c, *_: (c, 0, 0, 0))

    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, slots=S),
        out_shape=(jax.ShapeDtypeStruct((P, nt, Tp, dv2 // nt), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(P,),
            in_specs=[pl.BlockSpec((None, Tp, 2), lambda c, *_: (c, 0, 0))]
            + [head_spec] * 3
            + [tile_spec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(tile_spec, pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[pltpu.VMEM((1, dk, dv2), F32),
                            pltpu.SemaphoreType.DMA(())]),
        input_output_aliases={9: 1},  # state in -> state out
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret,
        name="gdn_chunk_scan",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      cu_q_lens.astype(jnp.int32), context_lens.astype(jnp.int32),
      skip.astype(jnp.int32),
      # a pair's log-decays side by side, (P, Tp, 2)
      rows(g.reshape(T, P, 2).transpose(1, 0, 2), 1),
      *(heads_first(x) for x in (kb, k, q)), tiles(vb), state)
    return o[:, :, :T].transpose(2, 0, 1, 3).reshape(T, H, dv2 // 2), state


def gdn_ragged(state, layer, g, kb, k, q, vb, cu_q_lens, context_lens, *,
               interpret: bool = False):
    """The packed stream (arguments as ``gdn_chunk_scan``'s): decode rows
    through the decode kernel, every other span through the span kernel,
    one after the other on the same donated state."""
    T = k.shape[0]
    q_len = cu_q_lens[1:] - cu_q_lens[:-1]
    one = kda.continues_one_row(q_len, context_lens)
    first = jnp.minimum(cu_q_lens[:-1], T - 1)
    o_one, state = gdn_decode_step(
        state, layer, jnp.exp(g[first].astype(F32)),
        *(x[first] for x in (kb, k, q, vb)), one, interpret=interpret)
    o, state = gdn_chunk_scan(state, layer, g, kb, k, q, vb, cu_q_lens,
                              context_lens, skip=one, interpret=interpret)
    # a skipped span's row reads zero in ``o``, an idle slot's in ``o_one``
    return o.at[first].add(o_one), state
