"""Pallas TPU kernels for the KDA recurrence (see ops/kda.py for the
equations and for what ``a, kb, k, q, vb`` are).

- ``kda_decode_step``: one token a slot. A grid cell holds one slot's
  states of ``hb`` heads; each (slot, head) state (d_k, d_v) float32 is
  read from HBM once and written once, in place (the state array is
  aliased input to output and indexed by the layer, so the donated array
  of all layers is never sliced or copied). The per-key-channel vectors
  (a, kb, k, q) come in with the channel on the sublanes, (d_k, hb) a
  cell, so that each scales the state's rows by a lane broadcast and the
  two contractions over d_k are sums over sublanes: no transposes and no
  cross-lane reductions in the kernel.
- ``kda_chunk_scan``: the packed ragged stream, in the chunked (matmul)
  form of the recurrence (ops/kda.py's header has its equations). A grid
  cell holds ``SCAN_HEADS_PER_CELL`` heads; it walks the spans
  (``cu_q_lens``) in slot order, loads a span's states (zeros where the
  span starts its sequence), takes the span ``CHUNK`` rows at a time and
  stores the states back. A block is a window of ``CHUNK`` rows of the
  stream as they lie; rows of the window that are not the block's (before
  a span's first row of it, past the span's end, or, where the window was
  moved back to end at the stream's last row, before the block) are
  masked to steps that change nothing (g = 0, kb = k = q = 0) and are not
  written. Per block and head, everything in float32, every product on
  the MXU at ``Precision.HIGHEST``:

  * no decay is ever divided by (``exp(-G)`` overflows): a ratio
    ``exp(G_t - G_i)``, t >= i, comes from sums of ``g`` over the rows
    between the two, summed as they stand. Inside a sub-block of ``SUB``
    rows, pair by pair: sub-diagonal ``j`` of ``A`` and ``B`` is a lane
    reduction of ``x_t * k_{t-j} * exp(g_t + ... + g_{t-j+1})``. Between
    sub-blocks, level by level (groups of s = SUB, 2 SUB, ... rows pair
    up): with ``head_t`` the sum of g from the group's first row to t and
    ``rest_i`` from i + 1 to the group's last, the pairs of two
    neighbouring groups are ``(x * exp(head)) (k * exp(rest))^T``, both
    factors at most 1, one product a level for all groups;
  * ``(I + A)^-1``: the sub-blocks on the diagonal by substitution, all
    of them at once (row t of each from its rows before t, sublane rolls
    and the sub-diagonals), then level by level
    ``inv <- inv - inv A_s inv`` (the block inverse of two halves whose
    own inverses are known), never C dependent row steps;
  * ``W = inv Vb - (inv (exp(G) Kb)) S_0``, ``O = (exp(G) Q) S_0 + B W``,
    ``S_C = Diag(exp(G_C)) S_0 + (exp(G_C - G) K)^T W``. The state is kept
    transposed while a span runs, so that the block's total decay scales
    it as a row of the stream lies.
- ``kda_ragged`` is what a ragged step calls: the stream's decode rows
  (one-row spans that continue a state) go through ``kda_decode_step``,
  whose pipelined blocks hide the state's DMA, and every other span
  through ``kda_chunk_scan``, which waits for each span's state to land
  and to leave (64 one-row spans a head cost it 16 ms a layer on the
  chip, 0.8 ms through the decode kernel; PERF.md section 6, PR 34).

Both names reach a device trace as ``%kda_decode_step[.N]`` and
``%kda_chunk_scan[.N]`` (chipbench/layer_metrics/kda_*.json).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops import kda

F32 = jnp.float32
# heads a decode grid cell holds: 32 states of (128, 128) float32 are
# 2 MiB, in and out and double-buffered 8 MiB of the 16 MiB of scoped VMEM
DECODE_HEADS_PER_CELL = 32
# rows of a span the span kernel takes at a time (a block), rows of a block
# it pairs off one by one (a sub-block), and heads a grid cell holds (their
# blocks run side by side, two chains of products for the scheduler to
# interleave). Constants from fixed-input runs on a v5e, not settings
# (PERF.md section 6, PR 44): a block of 128 never paid (one more level of
# products, worse fill of short spans); a sub-block of 32 ran 9 % faster
# but doubles the unrolled body, which every start traces (``setup_s``)
CHUNK = 64
SUB = 16
SCAN_HEADS_PER_CELL = 2


def _decode_kernel(layer_ref, active_ref, a_ref, kb_ref, k_ref, q_ref,
                   vb_ref, s_ref, o_ref, so_ref, *, hb: int):
    del layer_ref  # used by the index maps
    live = active_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _():
        for h in range(hb):
            S = s_ref[h] * a_ref[:, h:h + 1]
            w = vb_ref[h:h + 1, :] - jnp.sum(
                S * kb_ref[:, h:h + 1], axis=0, keepdims=True)
            S = S + k_ref[:, h:h + 1] * w
            so_ref[h] = S
            o_ref[h:h + 1, :] = jnp.sum(S * q_ref[:, h:h + 1], axis=0,
                                        keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def kda_decode_step(state, layer, a, kb, k, q, vb, active, *,
                    interpret: bool = False):
    """state (Lk, S, H, dk, dv) float32, donated and updated in place at
    ``layer``; a, kb, k, q (S, H, dk) and vb (S, H, dv) float32; active
    (S,) bool. Returns (o (S, H, dv) float32, state)."""
    _, S, H, dk, dv = state.shape
    hb = min(H, DECODE_HEADS_PER_CELL)
    assert H % hb == 0, (H, hb)
    nhb = H // hb

    def cols(x):  # (S, H, dk) -> (S, nhb, dk, hb): channel on sublanes
        return x.astype(F32).reshape(S, nhb, hb, dk).transpose(0, 1, 3, 2)

    col_spec = pl.BlockSpec((None, None, dk, hb),
                            lambda s, j, li, act: (s, j, 0, 0))
    row_spec = pl.BlockSpec((None, hb, dv), lambda s, j, li, act: (s, j, 0))
    state_spec = pl.BlockSpec((None, None, hb, dk, dv),
                              lambda s, j, li, act: (li[0], s, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, hb=hb),
        out_shape=(jax.ShapeDtypeStruct((S, H, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S, nhb),
            in_specs=[col_spec] * 4 + [row_spec, state_spec],
            out_specs=(row_spec, state_spec)),
        input_output_aliases={7: 1},  # state in -> state out
        interpret=interpret,
        name="kda_decode_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), active.astype(jnp.int32),
      cols(a), cols(kb), cols(k), cols(q), vb.astype(F32), state)
    return o, state


def _chunk_kernel(layer_ref, cu_ref, ctx_ref, skip_ref, g_ref, kb_ref, k_ref,
                  q_ref, vb_ref, state_hbm, o_ref, state_out, st, sem, *,
                  slots: int):
    del state_hbm  # aliased to state_out
    layer, cell = layer_ref[0], pl.program_id(0)
    o_ref[...] = jnp.zeros_like(o_ref)
    (hb, T, _), C, c = o_ref.shape, CHUNK, SUB
    iota = jax.lax.broadcasted_iota
    row, col = iota(jnp.int32, (C, C), 0), iota(jnp.int32, (C, C), 1)
    eye = (row == col).astype(F32)
    r1 = iota(jnp.int32, (C, 1), 0)
    rsub = r1 & (c - 1)               # a row's place in its sub-block
    # level s of the block's triangle: (t, i) with t in the upper and i in
    # the lower half of the same group of 2 s rows (C, c, s: powers of two)
    levels = [s for s in (c << n for n in range(C.bit_length())) if s < C]
    upper = {s: (r1 & s) != 0 for s in levels}
    pairs = {s: ((row & s) != 0) & ((row ^ col) & -s == s) for s in levels}

    def block(h, at, live, St):
        """The rows ``live`` of the C-row window ``at`` of head ``h``'s
        stream: St (dv, dk) -> St after them, their outputs stored. The
        other rows of the window are masked to steps that leave the state
        alone (g = 0, kb = k = q = 0)."""
        g, kb, k, q = (jnp.where(live, x[h, at, :], 0.0)
                       for x in (g_ref, kb_ref, k_ref, q_ref))
        vb = vb_ref[h, at, :]

        # inside a sub-block, pair by pair: sub-diagonal j holds
        # x_t . (k_{t-j} exp(g_t + ... + g_{t-j+1})), the exponent summed
        # as it stands
        a_sub, b_sub = [None], [jnp.sum(q * k, axis=1, keepdims=True)]
        gsum = g
        for j in range(1, c):
            if j > 1:
                gsum = gsum + pltpu.roll(g, j - 1, 0)
            kd = pltpu.roll(k, j, 0) * jnp.exp(gsum)
            inside = rsub >= j
            a_sub.append(jnp.where(
                inside, jnp.sum(kb * kd, axis=1, keepdims=True), 0.0))
            b_sub.append(jnp.where(
                inside, jnp.sum(q * kd, axis=1, keepdims=True), 0.0))

        # (I + A)^-1 of the sub-blocks on the diagonal, all at once, by
        # substitution: row t of a sub-block from its rows before t
        inv = eye
        for t in range(1, c):
            acc = a_sub[1] * pltpu.roll(inv, 1, 0)
            for j in range(2, t + 1):
                acc = acc + a_sub[j] * pltpu.roll(inv, j, 0)
            inv = jnp.where(rsub == t, eye - acc, inv)
        B = b_sub[0] * eye
        for j in range(1, c):
            B = jnp.where(row - col == j, b_sub[j], B)

        # running sums of g inside a sub-block: ``head`` from its first row
        # to this one, ``rest`` from the next row to its last
        head = g
        rest = jnp.where(rsub < c - 1, pltpu.roll(g, C - 1, 0), 0.0)
        n = 1
        while n < c:
            head = head + jnp.where(rsub >= n, pltpu.roll(head, n, 0), 0.0)
            rest = rest + jnp.where(rsub < c - n,
                                    pltpu.roll(rest, C - n, 0), 0.0)
            n *= 2

        # between sub-blocks, level by level: groups of s rows pair up,
        # the decay from row i to row t goes through the boundary between
        # the two halves, exp(head_t) exp(rest_i), both <= 1
        for s in levels:
            dec = jnp.exp(head)
            AB = _dot_nt(jnp.concatenate([kb * dec, q * dec]),
                         k * jnp.exp(rest))
            B = jnp.where(pairs[s], AB[C:], B)
            inv = inv - _dot(inv, _dot(jnp.where(pairs[s], AB[:C], 0.0), inv))
            # the same sums over groups of 2 s rows: a row of the upper
            # half adds the lower half's total, and the other way round
            total = head + rest
            head = head + jnp.where(upper[s], pltpu.roll(total, s, 0), 0.0)
            rest = rest + jnp.where(upper[s], 0.0,
                                    pltpu.roll(total, C - s, 0))

        # the block against the state it starts from
        dec = jnp.exp(head)
        d = vb.shape[1]
        solved = _dot(inv, jnp.concatenate([vb, kb * dec], axis=1))
        on_state = _dot_nt(jnp.concatenate([solved[:, d:], q * dec]), St)
        w = solved[:, :d] - on_state[:C]
        o_ref[h, at, :] = jnp.where(live, on_state[C:] + _dot(B, w),
                                    o_ref[h, at, :])
        return (St * jnp.exp(head[C - 1:C, :])
                + _dot(w.T, k * jnp.exp(rest)))

    def span(s, _):
        start, end = cu_ref[s], cu_ref[s + 1]

        @pl.when((end > start) & (skip_ref[s] == 0))
        def _():
            heads = state_out.at[layer, s, pl.ds(cell * hb, hb)]
            load = pltpu.make_async_copy(heads, st, sem)
            load.start()
            load.wait()
            fresh = ctx_ref[s] == end - start

            def blocks(b, Sts):
                # a window of C rows from the block's first, or the
                # stream's last C where that would pass its end
                lo = start + b * C
                base = jnp.minimum(lo, T - C)
                live = (r1 + base >= lo) & (r1 + base < end)
                return tuple(block(h, pl.ds(base, C), live, St)
                             for h, St in enumerate(Sts))

            # transposed, (dv, dk): the decay of a whole block then scales
            # the state's columns as a row of the stream lies
            Sts = jax.lax.fori_loop(
                0, pl.cdiv(end - start, C), blocks,
                tuple(jnp.where(fresh, 0.0, st[h]).T for h in range(hb)))
            for h, St in enumerate(Sts):
                st[h] = St.T
            store = pltpu.make_async_copy(st, heads, sem)
            store.start()
            store.wait()

        return 0

    jax.lax.fori_loop(0, slots, span, 0)


def _dot(x, y):
    return jnp.dot(x, y, preferred_element_type=F32,
                   precision=jax.lax.Precision.HIGHEST)


def _dot_nt(x, y):
    """x y^T."""
    return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                               preferred_element_type=F32,
                               precision=jax.lax.Precision.HIGHEST)


# jitted so that a program's KDA layers share one trace of the kernel's
# body: its unrolled loops are ~2,000 operations, and a step program with
# three KDA layers in its scan's body would trace and lower them three
# times at every start, compile cache or none (3.1 s of host time a
# program against 1.25 s jitted and the row-by-row kernel's 1.1 s;
# PERF.md section 6, PR 44)
@functools.partial(jax.jit, static_argnames="interpret")
def kda_chunk_scan(state, layer, g, kb, k, q, vb, cu_q_lens, context_lens,
                   skip=None, *, interpret: bool = False):
    """state (Lk, S, H, d, d) float32, donated and updated in place at
    ``layer``; g (the log-decay, <= 0), kb, k, q, vb (T, H, d) float32,
    the packed stream; cu_q_lens (S + 1,) span offsets in slot order,
    context_lens (S,) each slot's context after its span (a span as long
    as its context starts from zeros); skip (S,) bool: spans left as they
    are, their rows read zero. Returns (o (T, H, d) float32, state)."""
    _, S, H, dk, dv = state.shape
    if skip is None:
        skip = jnp.zeros((S,), jnp.bool_)
    assert dk == dv, "the state is kept transposed in one scratch"
    T = k.shape[0]
    Tp = max(T, CHUNK)  # a stream under one block wide is padded to one

    def heads_first(x):
        x = x.astype(F32).transpose(1, 0, 2)
        return jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0))) if Tp > T else x

    hb = math.gcd(H, SCAN_HEADS_PER_CELL)
    row_spec = pl.BlockSpec((hb, Tp, dk), lambda h, *_: (h, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, slots=S),
        out_shape=(jax.ShapeDtypeStruct((H, Tp, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(H // hb,),
            in_specs=[row_spec] * 5 + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(row_spec, pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), F32),
                            pltpu.SemaphoreType.DMA(())]),
        input_output_aliases={9: 1},  # state in -> state out
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret,
        name="kda_chunk_scan",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      cu_q_lens.astype(jnp.int32), context_lens.astype(jnp.int32),
      skip.astype(jnp.int32),
      *(heads_first(x) for x in (g, kb, k, q, vb)), state)
    return o[:, :T].transpose(1, 0, 2), state


def kda_ragged(state, layer, g, kb, k, q, vb, cu_q_lens, context_lens, *,
               interpret: bool = False):
    """The packed stream (arguments as ``kda_chunk_scan``'s): decode rows
    through the decode kernel, every other span through the span kernel,
    one after the other on the same donated state."""
    T = k.shape[0]
    q_len = cu_q_lens[1:] - cu_q_lens[:-1]
    one = kda.continues_one_row(q_len, context_lens)
    first = jnp.minimum(cu_q_lens[:-1], T - 1)
    o_one, state = kda_decode_step(
        state, layer, jnp.exp(g[first].astype(F32)),
        *(x[first] for x in (kb, k, q, vb)), one, interpret=interpret)
    o, state = kda_chunk_scan(state, layer, g, kb, k, q, vb, cu_q_lens,
                              context_lens, skip=one, interpret=interpret)
    # a skipped span's row reads zero in ``o``, an idle slot's in ``o_one``
    return o.at[first].add(o_one), state
