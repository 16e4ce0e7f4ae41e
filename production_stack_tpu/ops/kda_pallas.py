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
- ``kda_chunk_scan``: the packed ragged stream. One grid cell a head; it
  walks the spans (``cu_q_lens``) in slot order, loads a span's state
  (zeros where the span starts its sequence), runs the span's rows one
  after the other and stores the state back. The rows are sequential
  inside the kernel: there is no chunked (matmul) form yet (PERF.md
  section 7). The state is kept transposed while a span runs, so that
  the per-row vectors are used as they lie in the stream, (1, d_k) rows.
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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops import kda

F32 = jnp.float32
# heads a decode grid cell holds: 32 states of (128, 128) float32 are
# 2 MiB, in and out and double-buffered 8 MiB of the 16 MiB of scoped VMEM
DECODE_HEADS_PER_CELL = 32


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


def _chunk_kernel(layer_ref, cu_ref, ctx_ref, skip_ref, a_ref, kb_ref, k_ref,
                  q_ref, vb_ref, state_hbm, o_ref, state_out, st, sem, *,
                  slots: int):
    del state_hbm  # aliased to state_out
    layer, h = layer_ref[0], pl.program_id(0)
    o_ref[...] = jnp.zeros_like(o_ref)
    d = st.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1))

    def span(s, _):
        start, end = cu_ref[s], cu_ref[s + 1]

        @pl.when((end > start) & (skip_ref[s] == 0))
        def _():
            load = pltpu.make_async_copy(state_out.at[layer, s, h], st, sem)
            load.start()
            load.wait()
            fresh = ctx_ref[s] == end - start
            # transposed, (dv, dk): a row of the stream then scales the
            # state's columns as it lies
            St = jnp.where(fresh, 0.0, st[...]).T

            def row(t, St):
                at = pl.ds(t, 1)
                St = St * a_ref[at, :]
                # column w = vb - St kb, vb moved onto the sublanes by the
                # diagonal mask
                w = jnp.sum(jnp.where(eye, vb_ref[at, :], 0.0)
                            - St * kb_ref[at, :], axis=1, keepdims=True)
                St = St + w * k_ref[at, :]
                o = jnp.sum(St * q_ref[at, :], axis=1, keepdims=True)
                o_ref[at, :] = jnp.sum(jnp.where(eye, o, 0.0), axis=0,
                                       keepdims=True)
                return St

            st[...] = jax.lax.fori_loop(start, end, row, St).T
            store = pltpu.make_async_copy(st, state_out.at[layer, s, h], sem)
            store.start()
            store.wait()

        return 0

    jax.lax.fori_loop(0, slots, span, 0)


def kda_chunk_scan(state, layer, a, kb, k, q, vb, cu_q_lens, context_lens,
                   skip=None, *, interpret: bool = False):
    """state (Lk, S, H, d, d) float32, donated and updated in place at
    ``layer``; a, kb, k, q, vb (T, H, d) float32, the packed stream;
    cu_q_lens (S + 1,) span offsets in slot order, context_lens (S,) each
    slot's context after its span (a span as long as its context starts
    from zeros); skip (S,) bool: spans left as they are, their rows read
    zero. Returns (o (T, H, d) float32, state)."""
    _, S, H, dk, dv = state.shape
    if skip is None:
        skip = jnp.zeros((S,), jnp.bool_)
    assert dk == dv, "the diagonal mask moves vectors between axes"
    T = k.shape[0]

    def heads_first(x):
        return x.astype(F32).transpose(1, 0, 2)

    row_spec = pl.BlockSpec((None, T, dk), lambda h, *_: (h, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, slots=S),
        out_shape=(jax.ShapeDtypeStruct((H, T, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(H,),
            in_specs=[row_spec] * 5 + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(row_spec, pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[pltpu.VMEM((dk, dv), F32),
                            pltpu.SemaphoreType.DMA(())]),
        input_output_aliases={9: 1},  # state in -> state out
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret,
        name="kda_chunk_scan",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      cu_q_lens.astype(jnp.int32), context_lens.astype(jnp.int32),
      skip.astype(jnp.int32),
      *(heads_first(x) for x in (a, kb, k, q, vb)), state)
    return o.transpose(1, 0, 2), state


def kda_ragged(state, layer, a, kb, k, q, vb, cu_q_lens, context_lens, *,
               interpret: bool = False):
    """The packed stream (arguments as ``kda_chunk_scan``'s): decode rows
    through the decode kernel, every other span through the span kernel,
    one after the other on the same donated state."""
    T = k.shape[0]
    q_len = cu_q_lens[1:] - cu_q_lens[:-1]
    one = kda.continues_one_row(q_len, context_lens)
    first = jnp.minimum(cu_q_lens[:-1], T - 1)
    o_one, state = kda_decode_step(
        state, layer, *(x[first] for x in (a, kb, k, q, vb)), one,
        interpret=interpret)
    o, state = kda_chunk_scan(state, layer, a, kb, k, q, vb, cu_q_lens,
                              context_lens, skip=one, interpret=interpret)
    # a skipped span's row reads zero in ``o``, an idle slot's in ``o_one``
    return o.at[first].add(o_one), state
