"""Pallas TPU kernels for the selective scan (see ops/mamba.py for the
equations and for what ``A, x, delta, B, C`` are). The state lies
(layers, slots, N, d_i) float32: a slot's N = 16 values of a channel on
the sublanes (two float32 tiles), the channels on the lanes, so the decay,
the update and the read are elementwise over (N, d_i) tiles and ``y`` is
a sum over sublanes. ``B_t`` and ``C_t`` scale the state's rows, so they
come as columns (N, 1) that broadcast along the lanes.

- ``mamba_decode_step``: one token a slot. A grid cell holds
  ``DECODE_SLOTS_PER_CELL`` slots' states; each is read from HBM once and
  written once, in place (the state array is aliased input to output and
  indexed by the layer, so the donated array of all layers is never sliced
  or copied). The pipelined blocks hide the state's DMA.
- ``mamba_chunk_scan``: the packed ragged stream. A grid cell holds
  ``SCAN_CHANNELS`` channels; it walks the spans (``cu_q_lens``) in slot
  order, loads a span's state (zeros where the span starts its sequence:
  one DMA a span and channel block), takes the span's rows one after the
  other with the state in VMEM, and stores the state back. A first form:
  a row is ~10 elementwise passes over (N, SCAN_CHANNELS), and a span of
  n rows is n dependent steps (PERF.md section 7 has the chunked form as
  a lead, as PR 44 gave the KDA span kernel).
- ``mamba_ragged`` is what a ragged step calls: the stream's decode rows
  (one-row spans that continue a state) go through ``mamba_decode_step``
  and every other span through ``mamba_chunk_scan``, which waits for each
  span's state to land and to leave (``kda_pallas.kda_ragged``'s split,
  for its reason).

Both names reach a device trace as ``%mamba_decode_step[.N]`` and
``%mamba_chunk_scan[.N]`` (chipbench/layer_metrics/mamba_*.json).
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
# slots a decode grid cell holds: 4 states of (16, 5120) float32 are
# 1.25 MiB, in and out and double-buffered 5 MiB of the 16 MiB of scoped
# VMEM
DECODE_SLOTS_PER_CELL = 4
# channels a span-kernel grid cell holds: the stream's x, Delta and y
# blocks are (T, SCAN_CHANNELS) float32 each, 4 MiB at 2048 rows, 24 MiB
# double-buffered (the kernel sets its own scoped-VMEM limit)
SCAN_CHANNELS = 512


def _column(row, eye):
    """A row (1, N) as a column (N, 1): the diagonal of its broadcast."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _decode_kernel(layer_ref, active_ref, a_ref, x_ref, d_ref, b_ref, c_ref,
                   s_ref, y_ref, so_ref, *, sb: int):
    del layer_ref  # used by the index maps
    cell = pl.program_id(0)
    for s in range(sb):
        live = active_ref[cell * sb + s] != 0

        @pl.when(live)
        def _():
            d = d_ref[s:s + 1, :]
            S = (jnp.exp(d * a_ref[...]) * s_ref[s]
                 + (d * x_ref[s:s + 1, :]) * b_ref[s])
            so_ref[s] = S
            y_ref[s:s + 1, :] = jnp.sum(S * c_ref[s], axis=0, keepdims=True)

        @pl.when(jnp.logical_not(live))
        def _():
            so_ref[s] = s_ref[s]
            y_ref[s:s + 1, :] = jnp.zeros((1, y_ref.shape[1]), F32)


def mamba_decode_step(state, layer, A, x, delta, B, C, active, *,
                      interpret: bool = False):
    """state (Lm, S, N, d_i) float32, donated and updated in place at
    ``layer``; A (N, d_i); x, delta (S, d_i) and B, C (S, N) float32;
    active (S,) bool. Returns (y (S, d_i) float32, state)."""
    _, S, N, di = state.shape
    sb = math.gcd(S, DECODE_SLOTS_PER_CELL)
    # a cell's rows as a block of their own (sb < 8 is no whole sublane
    # tile of a (S, d_i) array)
    row_spec = pl.BlockSpec((None, sb, di), lambda c, li, act: (c, 0, 0))
    col_spec = pl.BlockSpec((sb, N, 1), lambda c, li, act: (c, 0, 0))
    state_spec = pl.BlockSpec((None, sb, N, di),
                              lambda c, li, act: (li[0], c, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_decode_kernel, sb=sb),
        out_shape=(jax.ShapeDtypeStruct((S // sb, sb, di), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S // sb,),
            in_specs=[pl.BlockSpec((N, di), lambda c, li, act: (0, 0)),
                      row_spec, row_spec, col_spec, col_spec, state_spec],
            out_specs=(row_spec, state_spec)),
        input_output_aliases={7: 1},  # state in -> state out
        interpret=interpret,
        name="mamba_decode_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), active.astype(jnp.int32),
      A.astype(F32), x.astype(F32).reshape(S // sb, sb, di),
      delta.astype(F32).reshape(S // sb, sb, di),
      B.astype(F32)[..., None], C.astype(F32)[..., None], state)
    return y.reshape(S, di), state


def _chunk_kernel(layer_ref, cu_ref, ctx_ref, skip_ref, a_ref, x_ref, d_ref,
                  b_ref, c_ref, state_hbm, y_ref, state_out, st, sem, *,
                  slots: int):
    del state_hbm  # aliased to state_out
    layer, cell = layer_ref[0], pl.program_id(0)
    N, cb = st.shape
    y_ref[...] = jnp.zeros_like(y_ref)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1))

    def span(s, _):
        start, end = cu_ref[s], cu_ref[s + 1]

        @pl.when((end > start) & (skip_ref[s] == 0))
        def _():
            mine = state_out.at[layer, s, :,
                                pl.ds(pl.multiple_of(cell * cb, 128), cb)]
            load = pltpu.make_async_copy(mine, st, sem)
            load.start()
            load.wait()
            fresh = ctx_ref[s] == end - start
            A = a_ref[...]

            def row(t, S):
                at = pl.ds(t, 1)
                d = d_ref[at, :]
                S = (jnp.exp(d * A) * S
                     + (d * x_ref[at, :]) * _column(b_ref[at, :], eye))
                y_ref[at, :] = jnp.sum(S * _column(c_ref[at, :], eye),
                                       axis=0, keepdims=True)
                return S

            st[...] = jax.lax.fori_loop(
                start, end, row, jnp.where(fresh, 0.0, st[...]))
            store = pltpu.make_async_copy(st, mine, sem)
            store.start()
            store.wait()

        return 0

    jax.lax.fori_loop(0, slots, span, 0)


# jitted so that a program's state-space layers share one trace of the
# kernel (``kda_pallas.kda_chunk_scan``'s reason)
@functools.partial(jax.jit, static_argnames="interpret")
def mamba_chunk_scan(state, layer, A, x, delta, B, C, cu_q_lens,
                     context_lens, skip=None, *, interpret: bool = False):
    """state (Lm, S, N, d_i) float32, donated and updated in place at
    ``layer``; A (N, d_i); x, delta (T, d_i) and B, C (T, N) float32, the
    packed stream; cu_q_lens (S + 1,) span offsets in slot order,
    context_lens (S,) each slot's context after its span (a span as long
    as its context starts from zeros); skip (S,) bool: spans left as they
    are, their rows read zero. Returns (y (T, d_i) float32, state)."""
    _, S, N, di = state.shape
    if skip is None:
        skip = jnp.zeros((S,), jnp.bool_)
    T = x.shape[0]
    Tp = -(-T // 8) * 8  # whole float32 sublane tiles

    def rows(a):
        a = a.astype(F32)
        return jnp.pad(a, ((0, Tp - T), (0, 0))) if Tp > T else a

    cb = math.gcd(di, SCAN_CHANNELS)
    chan_spec = pl.BlockSpec((Tp, cb), lambda c, *_: (0, c))
    vec_spec = pl.BlockSpec((Tp, N), lambda c, *_: (0, 0))
    y, state = pl.pallas_call(
        functools.partial(_chunk_kernel, slots=S),
        out_shape=(jax.ShapeDtypeStruct((Tp, di), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(di // cb,),
            in_specs=[pl.BlockSpec((N, cb), lambda c, *_: (0, c)),
                      chan_spec, chan_spec, vec_spec, vec_spec,
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(chan_spec, pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[pltpu.VMEM((N, cb), F32),
                            pltpu.SemaphoreType.DMA(())]),
        input_output_aliases={9: 1},  # state in -> state out
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret,
        name="mamba_chunk_scan",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      cu_q_lens.astype(jnp.int32), context_lens.astype(jnp.int32),
      skip.astype(jnp.int32), A.astype(F32), rows(x), rows(delta), rows(B),
      rows(C), state)
    return y[:T], state


def mamba_ragged(state, layer, A, x, delta, B, C, cu_q_lens, context_lens,
                 *, interpret: bool = False):
    """The packed stream (arguments as ``mamba_chunk_scan``'s): decode rows
    through the decode kernel, every other span through the span kernel,
    one after the other on the same donated state."""
    T = x.shape[0]
    q_len = cu_q_lens[1:] - cu_q_lens[:-1]
    one = kda.continues_one_row(q_len, context_lens)
    first = jnp.minimum(cu_q_lens[:-1], T - 1)
    y_one, state = mamba_decode_step(
        state, layer, A, *(a[first] for a in (x, delta, B, C)), one,
        interpret=interpret)
    y, state = mamba_chunk_scan(state, layer, A, x, delta, B, C, cu_q_lens,
                                context_lens, skip=one, interpret=interpret)
    # a skipped span's row reads zero in ``y``, an idle slot's in ``y_one``
    return y.at[first].add(y_one), state
