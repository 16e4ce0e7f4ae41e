"""The Pallas kernels keep their names through the TPU compiler.

A profiler trace names a device operation by its HLO text, and a Pallas
kernel appears there as ``%<name>.N = ... custom-call(...)``: the
instruction's name is the only place the kernel's name can reach a trace
reduction (chipbench/layer_metrics/*_attn_busy_pct.json match on it).
Each kernel is compiled for a described v5e chip, with no chip attached,
at a geometry that fits the default 16 MiB of scoped VMEM (KH=8, G=3,
D=128, block 16: PR 21's kernel check). Nothing runs, so nothing here is
a measurement.

The topology is described inside a fixture, never at import: only one
process may load libtpu, and every xdist worker imports this file.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from production_stack_tpu.ops.paged_attention_pallas import (
    kv_cache_write_pallas,
    paged_decode_attention_pallas,
)
from production_stack_tpu.ops.ragged_paged_attention_pallas import (
    ragged_paged_attention_pallas,
)

KH, G, D, BS = 8, 3, 128, 16
H = KH * G
L, N = 2, 1024     # layers, blocks of the paged cache
SLOTS, M = 16, 72  # sequences, block-table width


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


CACHE = ((L, N, BS, 2 * KH, D), jnp.bfloat16)
I32 = jnp.int32

CASES = {
    "ragged_paged_attention": (
        lambda q, c, bt, cu, cl: ragged_paged_attention_pallas(
            q, c, bt, cu, cl, layer_idx=1),
        (((640, H, D), jnp.bfloat16), CACHE, ((SLOTS, M), I32),
         ((SLOTS + 1,), I32), ((SLOTS,), I32))),
    "paged_decode_attention": (
        lambda q, c, bt, cl: paged_decode_attention_pallas(
            q, c, bt, cl, layer_idx=1),
        (((SLOTS, H, D), jnp.bfloat16), CACHE, ((SLOTS, M), I32),
         ((SLOTS,), I32))),
    "kv_cache_write": (
        lambda c, new, sm: kv_cache_write_pallas(c, new, sm, layer_idx=1),
        (CACHE, ((64, 2 * KH, D), jnp.bfloat16), ((64,), I32))),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_is_a_named_custom_call(one_chip, name):
    fn, shapes = CASES[name]
    text = _compiled_text(fn, one_chip, *shapes)
    heads = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? custom-call\(",
                       text, flags=re.M)
    named = [h for h in heads if re.fullmatch(name + r"[.\d]*", h)]
    assert named, f"no custom-call headed %{name}.N among {heads}"
    # and no kernel of the program is left with a name XLA made up
    assert not [h for h in heads if "unknown" in h or "closed_call" in h]


# -- OLMoE-1B-7B on one chip (chipbench olmoe-1b-7b-l8): MHA, KH=16, G=1 ----
# the cell's own shapes: a 2048-token stream, 64 slots, 4096 positions in
# blocks of 16, 8 layers; both attention kernels and the KV write have to
# fit the DEFAULT 16 MiB of scoped VMEM (the configuration sets no libtpu
# flag), and the MoE block's grouped matmul has to reach the trace under a
# name chipbench/layer_metrics/moe_*.json can match

MHA_CACHE = ((8, 2048, BS, 2 * 16, D), jnp.bfloat16)
MHA_CASES = {
    "ragged_paged_attention": (
        lambda q, c, bt, cu, cl: ragged_paged_attention_pallas(
            q, c, bt, cu, cl, layer_idx=1),
        (((2048, 16, D), jnp.bfloat16), MHA_CACHE, ((64, 256), I32),
         ((65,), I32), ((64,), I32))),
    "kv_cache_write": (
        lambda c, new, sm: kv_cache_write_pallas(c, new, sm, layer_idx=1),
        (MHA_CACHE, ((2048, 2 * 16, D), jnp.bfloat16), ((2048,), I32))),
}


# the decode kernel at the shapes that take its slab body
# (ops/paged_attention_pallas.py decode_slab_path: G = 1, bf16, D = 128,
# 16 or 32 KV heads), 64 slots of 4096 positions each: OLMoE's cell,
# Ouro-2.6B's (192 cache layers of 330 blocks) and an MHA stack of 32
# heads. All under the default scoped-VMEM limit.
def _decode_case(layers, blocks, kh):
    return (
        lambda q, c, bt, cl: paged_decode_attention_pallas(
            q, c, bt, cl, layer_idx=1),
        (((64, kh, D), jnp.bfloat16),
         ((layers, blocks, BS, 2 * kh, D), jnp.bfloat16),
         ((64, 256), I32), ((64,), I32)))


MHA_CASES["paged_decode_attention"] = _decode_case(*MHA_CACHE[0][:2], 16)
MHA_CASES["paged_decode_attention.ouro"] = _decode_case(192, 330, 16)
MHA_CASES["paged_decode_attention.kh32"] = _decode_case(4, 1024, 32)


# the decode kernel at the shapes that take its grouped body
# (decode_window_body: grouped queries, bf16, D = 128), 64 slots, each
# cell's own pool: Qwen3-8B cut to 16 layers (KH 8, G 4), Solar-Open2's two
# GQA layers (KH 8, G 8), Phi-4-mini-flash's 12 cache heads at G 4 with and
# without its 512-row window, and the shapes no cell has (Llama-3.2-3B's
# G 3, a TP-2 shard's KH 4, Qwen2-7B's G 7). All under the DEFAULT 16 MiB
# of scoped VMEM, whatever flag the cells' manifests set for the ragged
# kernel.
def _grouped_decode_case(layers, blocks, kh, group, window=0):
    return (
        lambda q, c, bt, cl: paged_decode_attention_pallas(
            q, c, bt, cl, layer_idx=1, window=window),
        (((64, kh * group, D), jnp.bfloat16),
         ((layers, blocks, BS, 2 * kh, D), jnp.bfloat16),
         ((64, 256), I32), ((64,), I32)))


GROUPED_DECODE_CASES = {
    "qwen3-8b-l16": _grouped_decode_case(16, 4859, 8, 4),
    "solar-open2-gqa": _grouped_decode_case(2, 4859, 8, 8),
    "phi-4-mini-flash-full": _grouped_decode_case(1, 14000, 12, 4),
    "phi-4-mini-flash-window": _grouped_decode_case(8, 2400, 12, 4, 512),
    "qwen3-window": _grouped_decode_case(16, 4859, 8, 4, 512),
    "llama-3b-g3": _grouped_decode_case(2, 1024, 8, 3),
    "tp2-shard-kh4": _grouped_decode_case(2, 1024, 4, 4),
    "qwen2-7b-kh4-g7": _grouped_decode_case(2, 1024, 4, 7),
}


@pytest.mark.parametrize("name", sorted(GROUPED_DECODE_CASES))
def test_grouped_decode_body_compiles_under_default_vmem(one_chip, name):
    from production_stack_tpu.ops.paged_attention_pallas import (
        decode_window_body,
    )

    fn, shapes = GROUPED_DECODE_CASES[name]
    (_, heads, _), _ = shapes[0]
    (_, _, _, rows, _), dtype = shapes[1]
    assert decode_window_body(rows // 2, heads // (rows // 2), D,
                              dtype) == "grouped"
    text = _compiled_text(fn, one_chip, *shapes)
    assert re.search(
        r"^\s*(?:ROOT )?%paged_decode_attention[.\d]* = .*? custom-call\(",
        text, flags=re.M)


@pytest.mark.parametrize("name", sorted(MHA_CASES))
def test_kernel_compiles_at_olmoe_geometry_under_default_vmem(one_chip, name):
    fn, shapes = MHA_CASES[name]
    text = _compiled_text(fn, one_chip, *shapes)
    kernel = name.split(".")[0]
    assert re.search(rf"^\s*(?:ROOT )?%{kernel}[.\d]* = .*? custom-call\(",
                     text, flags=re.M)


def _olmoe_block(one_chip, rows, grouped_matmul, int8=False):
    """``_moe_mlp`` at OLMoE's widths over an 8-layer stack, compiled for
    the chip with default options (16 MiB of scoped VMEM)."""
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.models import llama

    cfg = ModelConfig.from_pretrained("olmoe-1b-7b")
    E, F, X = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    T = rows // cfg.num_experts_per_tok
    bf = jnp.bfloat16
    def sds(shape, dt=bf):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def stacked(k, n):
        if int8:
            return {"q": sds((8, X, k, n), jnp.int8),
                    "s": sds((8, X, 1, n), jnp.float32)}
        return sds((8, X, k, n))

    experts = {"w_gate": stacked(E, F), "w_up": stacked(E, F),
               "w_down": stacked(F, E)}
    return jax.jit(
        lambda router, experts, layer, x, live: llama._moe_mlp(
            cfg, router, experts, layer, x, live,
            grouped_matmul=grouped_matmul)
    ).lower(sds((E, X)), experts, sds((), I32), sds((T, E)),
            sds((T,), jnp.bool_)).compile()


def _grouped_matmul_heads(text: str, rows: int) -> list:
    """The block's grouped-matmul instructions as chipbench's rooflines
    match them (layer_metrics/moe_expert_hbm_floor_pct.json): a name, one
    bf16 array of ``rows`` rows."""
    return re.findall(
        rf"^\s*(?:ROOT )?%((?:ragged-dot-none|moe_grouped_matmul)[.\d]*)"
        rf" = bf16\[{rows},", text, flags=re.M)


# decode step, narrow and full stream; what the runner hands the block on
# an unsharded TPU (the kernel, at every row count: PERF.md section 5's
# table) and what it hands it anywhere else
@pytest.mark.parametrize("rows", [64 * 8, 512 * 8, 2048 * 8])
@pytest.mark.parametrize("impl", ["kernel", "ragged_dot"])
def test_moe_block_compiles_to_named_grouped_matmuls(one_chip, rows, impl):
    """Three grouped matmuls a layer over the sorted (token, choice) rows,
    each one instruction with one array result: ``%moe_grouped_matmul[.N]``
    where the Pallas kernel runs them, ``%ragged-dot-none[.N]`` (XLA's own
    Mosaic kernel for ``jax.lax.ragged_dot``) where it does not. No dense
    expansion over the 64 experts, no one-hot dispatch, and no copy of a
    layer's experts out of the 8-layer stack (3 x 268 MB a layer if the
    grouped matmul were handed a slice)."""
    from production_stack_tpu.ops.moe_grouped_matmul_pallas import (
        moe_grouped_matmul,
    )

    compiled = _olmoe_block(one_chip, rows,
                            moe_grouped_matmul if impl == "kernel" else None)
    heads = _grouped_matmul_heads(compiled.as_text(), rows)
    want = "moe_grouped_matmul" if impl == "kernel" else "ragged-dot-none"
    assert len(heads) == 3 and all(h.startswith(want) for h in heads), heads
    # the block's transients are its sorted rows (64 MB each at stream
    # size), never a layer's experts (805 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 400 * 2 ** 20


def test_int8_experts_compile_to_ragged_dot_whatever_is_handed_in(one_chip):
    """The W8A8 branch of ``ragged_quant_dot`` keeps ``jax.lax.ragged_dot``
    (int8 x int8 -> int32): the kernel serves experts in the model dtype."""
    from production_stack_tpu.ops.moe_grouped_matmul_pallas import (
        moe_grouped_matmul,
    )

    text = _olmoe_block(one_chip, 512, moe_grouped_matmul,
                        int8=True).as_text()
    assert not re.search(r"%moe_grouped_matmul", text)
    assert len(re.findall(r"^\s*(?:ROOT )?%ragged-dot-none[.\d]* = s32\[512,",
                          text, flags=re.M)) == 3


# the kernel alone at the other two MoE cells' geometries: Solar-Open2's
# share (20 held experts a layer, 8 layers, 4096 x 1280) at a decode step's
# and the two streams' rows, the Pangu share's (16 held, 4 expert layers,
# 7680 x 2048) at its stream's; gate / up and down. It carries its own
# VMEM limit (two weight blocks of up to 16 MiB), so none of them needs a
# libtpu flag: all compile with default options
GROUPED_CASES = {
    f"{name}.{rows}.{proj}": (rows, groups, k, n)
    for name, groups, e, f, row_counts in (
        ("solar", 8 * 20, 4096, 1280, (512, 4096, 16384)),
        ("pangu", 4 * 16, 7680, 2048, (16384,)))
    for rows in row_counts
    for proj, (k, n) in (("gate", (e, f)), ("down", (f, e)))
}


@pytest.mark.parametrize("case", sorted(GROUPED_CASES))
def test_grouped_matmul_compiles_at_the_held_shares_geometries(
        one_chip, case):
    from production_stack_tpu.ops.moe_grouped_matmul_pallas import (
        grouped_kernel_path,
        moe_grouped_matmul,
    )

    rows, groups, k, n = GROUPED_CASES[case]
    assert grouped_kernel_path(k, n)
    compiled = jax.jit(moe_grouped_matmul).lower(
        *(jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
            ((rows, k), jnp.bfloat16), ((groups, k, n), jnp.bfloat16),
            ((groups,), I32)))).compile()
    heads = _grouped_matmul_heads(compiled.as_text(), rows)
    assert len(heads) == 1 and heads[0].startswith("moe_grouped_matmul")
    # the experts are read where they lie: no copy of the stack (1.7 GB,
    # 2 GB) among the temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


# -- Solar-Open2's share on one chip (chipbench solar-open2-250b-ep16-l8) ----
# the two KDA kernels at the cell's shapes (6 KDA layers, 64 slots, 64 heads
# of 128, a 2048-token stream), under the names chipbench/layer_metrics/
# kda_*.json match, and the attention kernels at its third geometry, KH=8,
# G=8, under the 32 MiB scoped-VMEM limit the configuration's manifest sets
# (the ragged kernel keeps G=4's 512 rows a tile: q_tile_for)

KDA_STATE = ((6, 64, 64, D, D), jnp.float32)
# Kimi-Linear's share on one chip (chipbench kimi-linear-48b-a3b-ep16): 20
# KDA layers of 32 heads, the same kernels at half the heads
KDA_STATE_H32 = ((20, 64, 32, D, D), jnp.float32)


def _kda_cases():
    from production_stack_tpu.ops.kda_pallas import (
        kda_chunk_scan,
        kda_decode_step,
    )

    def step(state):
        heads = state[0][2]
        return (
            lambda st, a, kb, k, q, vb, act: kda_decode_step(
                st, 3, a, kb, k, q, vb, act),
            (state, *[((64, heads, D), jnp.float32)] * 5,
             ((64,), jnp.bool_)))

    def scan(width, state=KDA_STATE):  # the ragged program's stream widths
        heads = state[0][2]
        return (
            lambda st, g, kb, k, q, vb, cu, ctx: kda_chunk_scan(
                st, 3, g, kb, k, q, vb, cu, ctx),
            (state, *[((width, heads, D), jnp.float32)] * 5,
             ((65,), I32), ((64,), I32)))

    return {
        "kda_decode_step": step(KDA_STATE),
        "kda_chunk_scan": scan(2048),
        "kda_chunk_scan@512": scan(512),
        "kda_decode_step@h32": step(KDA_STATE_H32),
        "kda_chunk_scan@h32": scan(2048, KDA_STATE_H32),
        "kda_chunk_scan@512h32": scan(512, KDA_STATE_H32),
    }


@pytest.mark.parametrize(
    "case", ["kda_chunk_scan", "kda_chunk_scan@512", "kda_decode_step",
             "kda_chunk_scan@h32", "kda_chunk_scan@512h32",
             "kda_decode_step@h32"])
def test_kda_kernel_is_a_named_custom_call_at_the_cells_shapes(one_chip, case):
    fn, shapes = _kda_cases()[case]
    name = case.partition("@")[0]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn, donate_argnums=0).lower(*args).compile()
    assert re.search(rf"^\s*(?:ROOT )?%{name}[.\d]* = .*? custom-call\(",
                     compiled.as_text(), flags=re.M)
    # the state of all layers is updated in place: no second copy of it
    # (1.6 GB; Kimi-Linear's 2.7 GB) among the program's temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 600 * 2 ** 20


G8_CACHE = ((2, 4096, BS, 2 * 8, D), jnp.bfloat16)
G8_CASES = {
    "ragged_paged_attention": (
        lambda q, c, bt, cu, cl: ragged_paged_attention_pallas(
            q, c, bt, cu, cl, layer_idx=1),
        (((2048, 64, D), jnp.bfloat16), G8_CACHE, ((64, 512), I32),
         ((65,), I32), ((64,), I32))),
    "paged_decode_attention": (
        lambda q, c, bt, cl: paged_decode_attention_pallas(
            q, c, bt, cl, layer_idx=1),
        (((64, 64, D), jnp.bfloat16), G8_CACHE, ((64, 512), I32),
         ((64,), I32))),
    "kv_cache_write": (
        lambda c, new, sm: kv_cache_write_pallas(c, new, sm, layer_idx=1),
        (G8_CACHE, ((2048, 2 * 8, D), jnp.bfloat16), ((2048,), I32))),
}


@pytest.mark.parametrize("name", sorted(G8_CASES))
def test_kernel_compiles_at_kh8_g8_under_the_manifests_vmem_limit(
        one_chip, name):
    fn, shapes = G8_CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_tpu_scoped_vmem_limit_kib": 32768}).as_text()
    assert re.search(rf"^\s*(?:ROOT )?%{name}[.\d]* = .*? custom-call\(",
                     text, flags=re.M)


# -- Trinity-Large-Preview's share on one chip (chipbench
# trinity-large-preview-ep16-l8.long-context): KH = 8, G = 6, D = 128, a
# geometry no other cell has, with a 4096-row window and without, 16 slots
# of 20,480 positions, a 4096-token stream and its 1024-token narrow one;
# six layers in the window pool. The decode kernel (from the slab as
# stored) and the KV write fit the DEFAULT 16 MiB; the ragged kernel's tile
# is 80 tokens = 480 rows and it asks 19.26 MiB at the wide stream (17.39
# at the narrow one), window or none: Qwen3's 32 MiB flag, in the
# manifest's engine_env. Under the names every accepted metric matches.

G6_KH, G6 = 8, 6
G6_CACHE = ((6, 4656, BS, 2 * G6_KH, D), jnp.bfloat16)
G6_RAGGED_VMEM_MIB = {4096: 19.26, 1024: 17.39}


def _g6_cases(window):
    h, how = G6_KH * G6, ({"window": window} if window else {})
    table = ((16, 1280), I32)

    def ragged(width):
        return (
            lambda q, c, bt, cu, cl: ragged_paged_attention_pallas(
                q, c, bt, cu, cl, layer_idx=1, **how),
            (((width, h, D), jnp.bfloat16), G6_CACHE, table, ((17,), I32),
             ((16,), I32)))

    return {
        "ragged_paged_attention@4096": ragged(4096),
        "ragged_paged_attention@1024": ragged(1024),
        "paged_decode_attention": (
            lambda q, c, bt, cl: paged_decode_attention_pallas(
                q, c, bt, cl, layer_idx=1, **how),
            (((16, h, D), jnp.bfloat16), G6_CACHE, table, ((16,), I32))),
        "kv_cache_write": (
            lambda c, new, sm: kv_cache_write_pallas(c, new, sm, layer_idx=1),
            (G6_CACHE, ((4096, 2 * G6_KH, D), jnp.bfloat16), ((4096,), I32))),
    }


@pytest.mark.parametrize("window", [0, 4096])
@pytest.mark.parametrize("case", sorted(_g6_cases(0)))
def test_attention_kernels_compile_at_kh8_g6(one_chip, case, window):
    fn, shapes = _g6_cases(window)[case]
    name, _, width = case.partition("@")
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    lowered = jax.jit(fn).lower(*args)
    head = rf"^\s*(?:ROOT )?%{name}[.\d]* = .*? custom-call\("
    if name != "ragged_paged_attention":
        assert re.search(head, lowered.compile().as_text(), flags=re.M)
        return
    text = lowered.compile(compiler_options={
        "xla_tpu_scoped_vmem_limit_kib": 32768}).as_text()
    assert re.search(head, text, flags=re.M)
    assert _asks_at_most(lowered, G6_RAGGED_VMEM_MIB[int(width)])


def test_the_g6_geometry_takes_the_slab_path_and_an_80_token_tile():
    from production_stack_tpu.ops.paged_attention_pallas import (
        decode_slab_path,
    )
    from production_stack_tpu.ops.ragged_paged_attention_pallas import (
        ROW_ALIGN,
        q_tile_for,
    )

    assert q_tile_for(G6, G6_KH) == 80 and 80 * G6 % ROW_ALIGN == 0
    assert all(decode_slab_path(G6_KH, G6, D, jnp.bfloat16, window)
               for window in (0, 4096))


def test_the_ragged_tile_is_keyed_by_the_group():
    """128 stream tokens a tile up to G = 4 (Qwen3's and OLMoE's programs
    are what they were), 512 rows a tile past it."""
    from production_stack_tpu.ops.ragged_paged_attention_pallas import (
        Q_TILE,
        q_tile_for,
    )

    assert [q_tile_for(g) for g in (1, 3, 4)] == [Q_TILE] * 3 == [128] * 3
    assert (q_tile_for(8), q_tile_for(16)) == (64, 32)


def test_the_accepted_geometries_ragged_tiles_are_what_they_were():
    """The tile is keyed by the KV heads too since Olmo-Hybrid's 32 held
    heads (PR 57): past 16 it shrinks so that tokens x KV heads stay what
    16 heads have. Every cell the benchmark had keeps its tile: (KV heads
    as the cache holds them, group) -> tokens."""
    from production_stack_tpu.ops.ragged_paged_attention_pallas import (
        ROW_ALIGN,
        q_tile_for,
    )

    accepted = {(8, 4): 128,    # qwen3-8b-l16
                (16, 1): 128,   # olmoe-1b-7b-l8, ouro-2.6b
                (8, 8): 64,     # solar-open2-250b-ep16-l8
                (12, 4): 128,   # phi-4-mini-flash-reasoning (packed pairs)
                (4, 5): 96,     # falcon-h1-34b-l6
                (8, 3): 128}    # the kernel check's own geometry
    assert {k: q_tile_for(k[1], k[0]) for k in accepted} == accepted
    # keyed by the group alone the answers are the same
    assert all(q_tile_for(g) == q_tile_for(g, kh) for kh, g in accepted)
    assert q_tile_for(1, 32) == 64  # olmo-hybrid-7b-l16: 30 heads held as 32
    assert all(q_tile_for(g, kh) * g % ROW_ALIGN == 0
               for kh in (20, 24, 32, 64) for g in (1, 2, 4, 8))


# -- the ragged kernel's two window bodies at the cells' three geometries ----
# A 2048-token stream over 64 slots of 8192 positions, as the engines run
# it: KH=8, G=4 (qwen3-8b-l16) and KH=8, G=8 (solar-open2-250b-ep16-l8)
# under the 32 MiB their manifests set, KH=16, G=1 (olmoe-1b-7b-l8,
# ouro-2.6b) under the DEFAULT limit. The interior body (strided 32-bit
# loads of the landed slab, lane-wide flash state) has to get through
# Mosaic at each, and may ask no more scoped VMEM than the kernel did
# with one body: 18.50 MiB at 512 rows a tile, 10.60 MiB at KH=16, G=1
# (the compiler's own count, read off its refusal at a limit just below).

RAGGED_VMEM_MIB = {(8, 4): 18.50, (16, 1): 10.60, (8, 8): 18.50}


@pytest.mark.parametrize("kh,g", sorted(RAGGED_VMEM_MIB))
def test_ragged_kernel_compiles_at_the_cells_geometries(one_chip, kh, g):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((2048, kh * g, D), jnp.bfloat16),
        ((2, 2048, BS, 2 * kh, D), jnp.bfloat16),
        ((64, 512), I32), ((65,), I32), ((64,), I32))]
    lowered = jax.jit(
        lambda q, c, bt, cu, cl: ragged_paged_attention_pallas(
            q, c, bt, cu, cl, layer_idx=1)).lower(*args)
    limit_kib = 16384 if g == 1 else 32768
    text = lowered.compile(compiler_options={
        "xla_tpu_scoped_vmem_limit_kib": limit_kib}).as_text()
    assert re.search(r"^\s*(?:ROOT )?%ragged_paged_attention[.\d]* = "
                     r".*? custom-call\(", text, flags=re.M)
    # what it asks: the compiler names the size where the limit is short
    ask = RAGGED_VMEM_MIB[kh, g]
    with pytest.raises(Exception, match="Scoped allocation") as refusal:
        lowered.compile(compiler_options={
            "xla_tpu_scoped_vmem_limit_kib": int((ask - 0.5) * 1024)})
    size = re.search(r"Scoped allocation with size ([\d.]+)M",
                     str(refusal.value))
    assert size and float(size.group(1)) <= ask


# -- openPangu-Ultra-MoE's share on one chip (chipbench
# openpangu-ultra-moe-718b-ep16-l5): the latent attention kernel at 128
# heads over a 640-lane row, a 2048-token stream and a decode step's 64
# one-token spans, under the name chipbench/layer_metrics/mla_attn_*.json
# match on; the row write that keeps the donated pool where it lies ------

LATENT_POOL = ((5, 4096, BS, 640), jnp.bfloat16)
LATENT_VMEM_MIB = 18.98
# with the expanded form's blocks beside the absorbed tile's: four heads'
# queries, outputs and flash state for the whole 2048-row stream, a
# 512-row window of the pool twice and its expansion for one head
LATENT_TWO_FORM_VMEM_MIB = 42.64


def _latent():
    from production_stack_tpu.ops import latent_paged_attention_pallas as k

    return k


# Kimi-Linear's share on one chip: 32 heads over a pool of 7 cache layers,
# at the ragged program's two stream widths and the decode program's 64
# one-token spans (the kernel's first full-batch decode cell)
LATENT_POOL_H32 = ((7, 12288, BS, 640), jnp.bfloat16)


def _latent_shapes(tokens, heads, expand):
    """The call's arguments at a cell's shapes: the ragged program's
    (``expand``: the heads' own 128 + 64 + 64-wide queries, head-major,
    ``W_UK``, ``W_UV``) or the decode program's."""
    shapes = [((tokens, heads, 640), jnp.bfloat16),
              LATENT_POOL if heads == 128 else LATENT_POOL_H32,
              ((64, 576 if heads == 128 else 512), I32), ((65,), I32),
              ((64,), I32)]
    if expand:
        shapes += [((heads, tokens, 256), jnp.bfloat16),
                   ((heads, 512, 128), jnp.bfloat16),
                   ((heads, 512, 128), jnp.bfloat16)]
    return shapes


def _latent_call(q, c, bt, cu, cl, *expand):
    return _latent().latent_paged_attention_pallas(
        q, c, bt, cu, cl, layer_idx=1, value_dim=512,
        expand=(*expand, 192 ** -0.5) if expand else None)


def _latent_decode_call(q, c, bt, cu, cl):
    """The decode program's: a slot a row, the decode body."""
    return _latent().latent_decode_attention_pallas(
        q, c, bt, cl, layer_idx=1, value_dim=512)


# what the three trace reductions of the kernel match an instruction by
# (chipbench/layer_metrics/mla_attn_busy_pct.json, mla_attn_roofline_pct.py,
# hybrid_mla_attn_roofline_pct.py), dividing by the ragged dispatches x
# layers + the decode calls: a decode call under another name would stay in
# the denominator and leave the numerator, and the share read impossible
LATENT_OP = r"^%latent_paged_attention[.\d]* = "


@pytest.mark.parametrize("tokens,heads,expand", [
    (2048, 128, True), (512, 128, True), (64, 128, False),
    (2048, 32, True), (512, 32, True), (64, 32, False)])
def test_latent_kernel_is_a_named_custom_call_at_the_cells_shapes(
        one_chip, tokens, heads, expand):
    """The ragged program's call (both stream widths) holds its two forms
    in ONE custom call under the one name the trace reductions match on:
    a second call, under this name or another, would halve or hide the
    time ``mla_attn_roofline_pct`` divides by. The decode program's call
    (64 one-token spans: the decode body, PR 60) is one custom call too,
    one output, under the SAME name: renamed, it fails here."""
    text = _compiled_text(_latent_call if expand else _latent_decode_call,
                          one_chip, *_latent_shapes(tokens, heads, expand))
    calls = re.findall(
        r"^\s*(?:ROOT )?(%[\w.-]+) = (\(?).*? custom-call\(.*"
        r"custom_call_target=\"tpu_custom_call\"", text, flags=re.M)
    assert len(calls) == 1 and re.fullmatch(
        r"%latent_paged_attention[.\d]*", calls[0][0]), calls
    assert (calls[0][1] == "(") == expand  # two outputs, or the one
    with open(os.path.join(os.path.dirname(__file__), "..", "chipbench",
                           "layer_metrics", "mla_attn_busy_pct.json")) as f:
        assert json.load(f)["op"] == LATENT_OP
    assert re.match(LATENT_OP, calls[0][0] + " = ")


# the decode body's cells: 4 sequences of 32 heads, 2 of 128 (two 512-row
# landing buffers a sequence, its state, the query and output blocks):
# under the default 16 MiB and far inside the call's own limit
LATENT_DECODE_VMEM_MIB = {32: 5.38, 128: 3.25}


@pytest.mark.parametrize("heads", sorted(LATENT_DECODE_VMEM_MIB))
def test_latent_decode_call_asks_for_scoped_vmem_inside_its_limit(
        one_chip, monkeypatch, heads):
    """As the ragged program's call below: no libtpu flag, the call's own
    limit, and what it asks read from the compiler's refusal of less."""
    k, mib = _latent(), LATENT_DECODE_VMEM_MIB[heads]
    assert k.VMEM_LIMIT_BYTES >= 4 * mib * 2 ** 20
    monkeypatch.setattr(k, "VMEM_LIMIT_BYTES", int((mib - 0.5) * 2 ** 20))
    with pytest.raises(Exception, match="Scoped allocation") as refusal:
        # unjitted, under a function of its own: jit would hand back the
        # other test's trace, made under the real limit
        _compiled_text(
            lambda q, c, bt, cu, cl:
            k.latent_decode_attention_pallas.__wrapped__(
                q, c, bt, cl, 1, value_dim=512),
            one_chip, *_latent_shapes(64, heads, False))
    size = re.search(r"Scoped allocation with size ([\d.]+)M",
                     str(refusal.value))
    assert size and float(size.group(1)) <= mib


@pytest.mark.parametrize("heads", [32, 128])
def test_latent_decode_layer_leaves_the_donated_pool_in_place(one_chip,
                                                              heads):
    """A cache layer of the decode program: the slots' rows scattered into
    the donated pool, then the one call that reads it. Nothing the size of
    the pool is copied."""
    from production_stack_tpu.ops.paged_attention import write_latent

    def layer(c, rows, sm, q, bt, cl):
        c = write_latent(c, 1, rows, sm)
        return c, _latent().latent_decode_attention_pallas(
            q, c, bt, cl, layer_idx=1, value_dim=512)

    q, pool, bt, _, cl = (
        jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
        for s, dt in _latent_shapes(64, heads, False))
    rows, sm = (jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in (((64, 640), jnp.bfloat16), ((64,), I32)))
    compiled = jax.jit(layer, donate_argnums=0).lower(
        pool, rows, sm, q, bt, cl).compile()
    nbytes = 2 * 640 * BS * pool.shape[0] * pool.shape[1]
    assert compiled.memory_analysis().temp_size_in_bytes < nbytes // 100
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          compiled.as_text())) == 1


@pytest.mark.parametrize("expand,mib", [(False, LATENT_VMEM_MIB),
                                        (True, LATENT_TWO_FORM_VMEM_MIB)])
def test_latent_kernel_asks_for_its_own_scoped_vmem(one_chip, monkeypatch,
                                                    expand, mib):
    """No libtpu flag in the configuration's manifest: the call carries
    its limit. What it asks: the compiler names the size where the limit
    is short."""
    k = _latent()
    assert k.VMEM_LIMIT_BYTES >= mib * 2 ** 20
    monkeypatch.setattr(k, "VMEM_LIMIT_BYTES", int((mib - 0.5) * 2 ** 20))
    with pytest.raises(Exception, match="Scoped allocation") as refusal:
        # a function of its own: jit would hand back the other test's trace
        _compiled_text(lambda *a: _latent_call(*a), one_chip,
                       *_latent_shapes(2048, 128, expand))
    size = re.search(r"Scoped allocation with size ([\d.]+)M",
                     str(refusal.value))
    assert size and float(size.group(1)) <= mib


@pytest.mark.parametrize("lanes,in_place,in_pytree", [
    (640, True, False), (576, False, False), (640, True, True)])
def test_latent_row_write_keeps_the_pool_in_place_at_whole_lane_tiles(
        one_chip, lanes, in_place, in_pytree):
    """The rows go in by XLA's scatter. At 640 lanes (whole tiles) the
    donated pool is updated where it lies; at the row's own 576 the
    compiler copies the whole pool into a 640-lane layout first: why
    ``ModelConfig.latent_lanes`` pads. ``in_pytree``: the pool rides a
    cache pytree beside per-slot state (Kimi-Linear's ``{"kv", "state"}``)
    and is still updated where it lies, the state untouched."""
    from production_stack_tpu.ops.paged_attention import write_latent

    pool = (5, 4096, BS, lanes)
    shapes = [(pool, jnp.bfloat16), ((2048, lanes), jnp.bfloat16),
              ((2048,), I32)]
    caches, rows, sm = (jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                        for s, dt in shapes)
    if in_pytree:
        caches = {"kv": caches, "state": jax.ShapeDtypeStruct(
            (2, 64, 32, D, D), jnp.float32, sharding=one_chip)}

    def fn(c, rows, sm):
        if in_pytree:
            return {**c, "kv": write_latent(c["kv"], 1, rows, sm)}
        return write_latent(c, 1, rows, sm)

    compiled = jax.jit(fn, donate_argnums=0).lower(caches, rows,
                                                   sm).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    nbytes = 2 * 5 * 4096 * BS * lanes
    assert (temp < nbytes // 100) if in_place else (temp > nbytes)


# -- Phi-4-mini-flash-reasoning on one chip (chipbench
# phi-4-mini-flash-reasoning.long-decode): the state-space kernels at 9
# layers x 64 slots of a (16, 5120) float32 state, under the names
# chipbench/layer_metrics/mamba_*.json match on; both attention kernels and
# the KV write at the packed geometry (10 head pairs filled up to 12 cache
# heads of 128, G = 4; ModelConfig.cache_kv_heads), with the 512-row window
# and without; and what of it needs the manifest's 32 MiB flag ---------------

SSM_STATE = ((9, 64, 16, 5120), jnp.float32)


def _mamba_cases():
    from production_stack_tpu.ops.mamba_pallas import (
        mamba_decode_step,
        mamba_ragged,
    )

    def rows(t):
        return [((t, 5120), jnp.float32)] * 2 + [((t, 16), jnp.float32)] * 2

    def ragged(width):  # the ragged program's stream widths (PR 42)
        return (
            lambda st, a, x, d, b, c, cu, ctx: mamba_ragged(
                st, 3, a, x, d, b, c, cu, ctx),
            (SSM_STATE, ((16, 5120), jnp.float32), *rows(width),
             ((65,), I32), ((64,), I32)))

    return {
        "mamba_decode_step": (
            lambda st, a, x, d, b, c, act: mamba_decode_step(
                st, 3, a, x, d, b, c, act),
            (SSM_STATE, ((16, 5120), jnp.float32), *rows(64),
             ((64,), jnp.bool_))),
        "mamba_chunk_scan": ragged(2048),
        "mamba_chunk_scan@512": ragged(512),
    }


@pytest.mark.parametrize(
    "case", ["mamba_chunk_scan", "mamba_chunk_scan@512", "mamba_decode_step"])
def test_mamba_kernel_is_a_named_custom_call_at_the_cells_shapes(
        one_chip, case):
    """Under the DEFAULT scoped-VMEM limit: the decode kernel's blocks fit
    it and the span kernel sets its own."""
    fn, shapes = _mamba_cases()[case]
    name = case.partition("@")[0]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn, donate_argnums=0).lower(*args).compile()
    assert re.search(rf"^\s*(?:ROOT )?%{name}[.\d]* = .*? custom-call\(",
                     compiled.as_text(), flags=re.M)
    # the state of all layers (189 MB) is updated in place: no second copy
    # of it among the program's temporaries (a stream's float32 rows are)
    assert compiled.memory_analysis().temp_size_in_bytes < 150 * 2 ** 20


PACKED_KH, PACKED_G = 12, 4
PACKED_CACHE = ((8, 2400, BS, 2 * PACKED_KH, D), jnp.bfloat16)
PACKED_RAGGED_VMEM_MIB = 27.45


def _packed_cases(window):
    h = PACKED_KH * PACKED_G
    return {
        "ragged_paged_attention": (
            lambda q, c, bt, cu, cl: ragged_paged_attention_pallas(
                q, c, bt, cu, cl, layer_idx=1, window=window),
            (((2048, h, D), jnp.bfloat16), PACKED_CACHE, ((64, 512), I32),
             ((65,), I32), ((64,), I32))),
        "paged_decode_attention": (
            lambda q, c, bt, cl: paged_decode_attention_pallas(
                q, c, bt, cl, layer_idx=1, window=window),
            (((64, h, D), jnp.bfloat16), PACKED_CACHE, ((64, 512), I32),
             ((64,), I32))),
        "kv_cache_write": (
            lambda c, new, sm: kv_cache_write_pallas(c, new, sm, layer_idx=1),
            (PACKED_CACHE, ((2048, 2 * PACKED_KH, D), jnp.bfloat16),
             ((2048,), I32))),
    }


@pytest.mark.parametrize("window", [0, 512])
@pytest.mark.parametrize("name", sorted(_packed_cases(0)))
def test_attention_kernels_compile_at_the_packed_geometry(one_chip, name,
                                                          window):
    """The decode kernel and the KV write fit the default 16 MiB; the
    ragged kernel asks 27.45 MiB at its 512-row tile of 12 cache heads,
    window or none: Qwen3's 32 MiB flag, in the manifest's engine_env."""
    fn, shapes = _packed_cases(window)[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    lowered = jax.jit(fn).lower(*args)
    head = rf"^\s*(?:ROOT )?%{name}[.\d]* = .*? custom-call\("
    if name != "ragged_paged_attention":
        assert re.search(head, lowered.compile().as_text(), flags=re.M)
        return
    text = lowered.compile(compiler_options={
        "xla_tpu_scoped_vmem_limit_kib": 32768}).as_text()
    assert re.search(head, text, flags=re.M)
    with pytest.raises(Exception, match="Scoped allocation") as refusal:
        lowered.compile(compiler_options={
            "xla_tpu_scoped_vmem_limit_kib": 17000})
    size = re.search(r"Scoped allocation with size ([\d.]+)M",
                     str(refusal.value))
    assert size and float(size.group(1)) <= PACKED_RAGGED_VMEM_MIB


def test_ten_head_pairs_unpadded_are_refused_by_the_tpu_compiler(one_chip):
    """Why ``ModelConfig.cache_kv_heads`` fills 10 pairs up to 12: a
    token's slab of 20 rows is no whole 8-row tile, and the kernels' DMAs
    of it do not get through Mosaic."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((64, 40, D), jnp.bfloat16), ((1, 256, BS, 20, D), jnp.bfloat16),
        ((64, 512), I32), ((64,), I32))]
    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(lambda q, c, bt, cl: paged_decode_attention_pallas(
            q, c, bt, cl, layer_idx=0)).lower(*args).compile()


# -- Falcon-H1-34B's six layers on one chip (chipbench
# falcon-h1-34b-l6.decode-heavy): the state-space kernels at 6 layers x 64
# slots of 32 heads of a (256, 128) float32 state, under the names
# chipbench/layer_metrics/ssd_*.json match on, and both attention kernels
# and the KV write at a geometry no other cell has, KH = 4, G = 5: a
# token's slab is ONE 8-row tile, and the group divides neither 16 nor 512.
# Everything under the DEFAULT 16 MiB of scoped VMEM (the manifest sets no
# libtpu flag); the span kernel sets its own limit -------------------------

SSD_STATE = ((6, 64, 32, 256, D), jnp.float32)
G5_KH, G5 = 4, 5
G5_CACHE = ((6, 8192, BS, 2 * G5_KH, D), jnp.bfloat16)
# what the ragged kernel asks at its 480-row tile of 4 KV heads, and
# ssd_decode_step at 16 states a cell (the compiler's own counts)
G5_RAGGED_VMEM_MIB, SSD_DECODE_VMEM_MIB = 9.20, 8.03


def _ssd_cases():
    from production_stack_tpu.ops.ssd_pallas import (
        ssd_decode_step,
        ssd_ragged,
    )

    def rows(t):
        return [((t, 32), jnp.float32), ((t, 32, D), jnp.float32),
                ((t, 2, 256), jnp.float32), ((t, 2, 256), jnp.float32)]

    def ragged(width):  # the ragged program's stream widths
        return (
            lambda st, g, dx, b, c, cu, ctx: ssd_ragged(
                st, 3, g, dx, b, c, cu, ctx),
            (SSD_STATE, *rows(width), ((65,), I32), ((64,), I32)))

    return {
        "ssd_decode_step": (
            lambda st, g, dx, b, c, act: ssd_decode_step(
                st, 3, g, dx, b, c, act),
            (SSD_STATE, *rows(64), ((64,), jnp.bool_))),
        "ssd_chunk_scan": ragged(2048),
        "ssd_chunk_scan@512": ragged(512),
    }


def _g5_cases():
    h = G5_KH * G5
    return {
        "ragged_paged_attention": (
            lambda q, c, bt, cu, cl: ragged_paged_attention_pallas(
                q, c, bt, cu, cl, layer_idx=1),
            (((2048, h, D), jnp.bfloat16), G5_CACHE, ((64, 512), I32),
             ((65,), I32), ((64,), I32))),
        "ragged_paged_attention@512": (
            lambda q, c, bt, cu, cl: ragged_paged_attention_pallas(
                q, c, bt, cu, cl, layer_idx=1),
            (((512, h, D), jnp.bfloat16), G5_CACHE, ((64, 512), I32),
             ((65,), I32), ((64,), I32))),
        "paged_decode_attention": (
            lambda q, c, bt, cl: paged_decode_attention_pallas(
                q, c, bt, cl, layer_idx=1),
            (((64, h, D), jnp.bfloat16), G5_CACHE, ((64, 512), I32),
             ((64,), I32))),
        "kv_cache_write": (
            lambda c, new, sm: kv_cache_write_pallas(c, new, sm, layer_idx=1),
            (G5_CACHE, ((2048, 2 * G5_KH, D), jnp.bfloat16), ((2048,), I32))),
    }


def _asks_at_most(lowered, mib):
    """The compiler names the size where the limit is just short."""
    with pytest.raises(Exception, match="Scoped allocation") as refusal:
        lowered.compile(compiler_options={
            "xla_tpu_scoped_vmem_limit_kib": int((mib - 0.5) * 1024)})
    size = re.search(r"Scoped allocation with size ([\d.]+)M",
                     str(refusal.value))
    return bool(size) and float(size.group(1)) <= mib


@pytest.mark.parametrize(
    "case", ["ssd_chunk_scan", "ssd_chunk_scan@512", "ssd_decode_step"])
def test_ssd_kernel_is_a_named_custom_call_at_the_cells_shapes(one_chip,
                                                               case):
    fn, shapes = _ssd_cases()[case]
    name = case.partition("@")[0]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    lowered = jax.jit(fn, donate_argnums=0).lower(*args)
    compiled = lowered.compile()  # default options: 16 MiB
    text = compiled.as_text()
    assert re.search(rf"^\s*(?:ROOT )?%{name}[.\d]* = .*? custom-call\(",
                     text, flags=re.M)
    # a ragged step runs BOTH kernels, its decode rows through the one-row
    # kernel; the state of all layers (1.6 GB) is updated in place: no
    # second copy of it among the program's temporaries
    if name == "ssd_chunk_scan":
        assert re.search(r"^\s*(?:ROOT )?%ssd_decode_step[.\d]* = ", text,
                         flags=re.M)
    else:
        assert _asks_at_most(lowered, SSD_DECODE_VMEM_MIB)
    assert compiled.memory_analysis().temp_size_in_bytes < 300 * 2 ** 20


@pytest.mark.parametrize("case", sorted(_g5_cases()))
def test_attention_kernels_compile_at_kh4_g5_under_default_vmem(one_chip,
                                                                case):
    fn, shapes = _g5_cases()[case]
    name = case.partition("@")[0]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    lowered = jax.jit(fn).lower(*args)
    assert re.search(rf"^\s*(?:ROOT )?%{name}[.\d]* = .*? custom-call\(",
                     lowered.compile().as_text(), flags=re.M)
    if name == "ragged_paged_attention":
        assert _asks_at_most(lowered, G5_RAGGED_VMEM_MIB)


def test_a_group_that_divides_no_power_of_two_keeps_a_narrow_block():
    """G = 5: 128 x 4 // 5 = 102 tokens would be 510 rows a tile, no
    multiple of ROW_ALIGN, so ``narrow_walk`` would answer False for every
    walk and every decode row of a ragged step would take the whole tile's
    body. The tile shrinks to whole ROW_ALIGN tiles instead (96 tokens, 480
    rows); the powers of two keep what they had; the decode kernel's
    grouped body takes all 20 query rows a block where 16 % G != 0."""
    import numpy as np

    from production_stack_tpu.ops.paged_attention_pallas import (
        decode_window_body,
    )
    from production_stack_tpu.ops.ragged_paged_attention_pallas import (
        ROW_ALIGN,
        count_walks,
        narrow_walk,
        q_tile_for,
    )

    assert {g: q_tile_for(g) for g in (5, 6, 7, 8, 12, 16)} == {
        5: 96, 6: 80, 7: 64, 8: 64, 12: 40, 16: 32}
    assert all(q_tile_for(g) * g % ROW_ALIGN == 0 and q_tile_for(g) * g <= 512
               for g in range(5, 33))
    assert narrow_walk(0, 1, 5, 102 * 5) == (False, 0)  # the tile it was
    narrow, r0 = narrow_walk(np.int64(3), np.int64(4), 5, 96 * 5, xp=np)
    assert narrow and r0 == 0
    # a decode step's 64 one-row spans in the 512-wide stream: all narrow
    cu = np.minimum(np.arange(65), 64)
    assert count_walks(cu, 512, 5) == (64, 64)
    assert decode_window_body(G5_KH, G5, D, jnp.bfloat16) == "grouped"


# -- Olmo-Hybrid on one chip (chipbench olmo-hybrid-7b-l16): the two Gated
# DeltaNet kernels at 12 layers x 64 slots of 15 pairs of (96, 384) states,
# and both attention kernels and the KV write at KH 30 held as 32, G 1,
# D 128, 4 cache layers: all under the DEFAULT 16 MiB of scoped VMEM (the
# configuration sets no libtpu flag; gdn_chunk_scan sets its own limit) ------

GDN_STATE = ((12, 64, 15, 96, 384), jnp.float32)
KH32_CACHE = ((4, 2560, BS, 2 * 32, D), jnp.bfloat16)
# what each asks (the compiler's own counts): the ragged kernel at the
# 64-token tile 32 KV heads get (at 128 tokens: 21.18, refused), the decode
# kernel's slab body, gdn_decode_step at 15 pairs a cell
KH32_RAGGED_VMEM_MIB, KH32_DECODE_VMEM_MIB, GDN_DECODE_VMEM_MIB = (
    12.51, 8.06, 8.44)


def _gdn_cases():
    from production_stack_tpu.ops.gdn_pallas import (
        gdn_decode_step,
        gdn_ragged,
    )

    def rows(t):
        return [((t, 30, 1), jnp.float32), *[((t, 30, 96), jnp.float32)] * 3,
                ((t, 30, 192), jnp.float32)]

    def ragged(width):  # the ragged program's stream widths
        return (
            lambda st, g, kb, k, q, vb, cu, ctx: gdn_ragged(
                st, 3, g, kb, k, q, vb, cu, ctx),
            (GDN_STATE, *rows(width), ((65,), I32), ((64,), I32)))

    return {
        "gdn_decode_step": (
            lambda st, a, kb, k, q, vb, act: gdn_decode_step(
                st, 3, a, kb, k, q, vb, act),
            (GDN_STATE, *rows(64), ((64,), jnp.bool_))),
        "gdn_chunk_scan": ragged(2048),
        "gdn_chunk_scan@512": ragged(512),
    }


def _kh32_cases():
    def ragged(width):
        return (
            lambda q, c, bt, cu, cl: ragged_paged_attention_pallas(
                q, c, bt, cu, cl, layer_idx=1),
            (((width, 32, D), jnp.bfloat16), KH32_CACHE, ((64, 512), I32),
             ((65,), I32), ((64,), I32)))

    return {
        "ragged_paged_attention": ragged(2048),
        "ragged_paged_attention@512": ragged(512),
        "paged_decode_attention": (
            lambda q, c, bt, cl: paged_decode_attention_pallas(
                q, c, bt, cl, layer_idx=1),
            (((64, 32, D), jnp.bfloat16), KH32_CACHE, ((64, 512), I32),
             ((64,), I32))),
        "kv_cache_write": (
            lambda c, new, sm: kv_cache_write_pallas(c, new, sm, layer_idx=1),
            (KH32_CACHE, ((2048, 2 * 32, D), jnp.bfloat16), ((2048,), I32))),
    }


@pytest.mark.parametrize(
    "case", ["gdn_chunk_scan", "gdn_chunk_scan@512", "gdn_decode_step"])
def test_gdn_kernel_is_a_named_custom_call_at_the_cells_shapes(one_chip,
                                                               case):
    fn, shapes = _gdn_cases()[case]
    name = case.partition("@")[0]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    lowered = jax.jit(fn, donate_argnums=0).lower(*args)
    compiled = lowered.compile()  # default options: 16 MiB
    text = compiled.as_text()
    assert re.search(rf"^\s*(?:ROOT )?%{name}[.\d]* = .*? custom-call\(",
                     text, flags=re.M)
    # a ragged step runs BOTH kernels, its decode rows through the one-row
    # kernel; the state of all layers (1.7 GB) is updated in place: no
    # second copy of it among the program's temporaries
    if name == "gdn_chunk_scan":
        assert re.search(r"^\s*(?:ROOT )?%gdn_decode_step[.\d]* = ", text,
                         flags=re.M)
    else:
        assert _asks_at_most(lowered, GDN_DECODE_VMEM_MIB)
    assert compiled.memory_analysis().temp_size_in_bytes < 300 * 2 ** 20


@pytest.mark.parametrize("case", sorted(_kh32_cases()))
def test_attention_kernels_compile_at_kh32_g1_under_default_vmem(one_chip,
                                                                 case):
    fn, shapes = _kh32_cases()[case]
    name = case.partition("@")[0]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    lowered = jax.jit(fn).lower(*args)
    assert re.search(rf"^\s*(?:ROOT )?%{name}[.\d]* = .*? custom-call\(",
                     lowered.compile().as_text(), flags=re.M)
    ask = {"ragged_paged_attention": KH32_RAGGED_VMEM_MIB,
           "paged_decode_attention": KH32_DECODE_VMEM_MIB}.get(name)
    if ask:
        assert _asks_at_most(lowered, ask)


def test_the_ragged_kernel_at_kh32_and_the_old_tile_is_refused(one_chip):
    """Why the tile shrinks: 128 tokens of 32 KV heads ask 21.18 MiB."""
    fn, shapes = _kh32_cases()["ragged_paged_attention"]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    with pytest.raises(Exception, match="Scoped allocation with size 21"):
        jax.jit(lambda q, c, bt, cu, cl: ragged_paged_attention_pallas(
            q, c, bt, cu, cl, layer_idx=1, q_tile=128)).lower(
                *args).compile()


# The engine's own step programs at the published widths, whole, for the
# described v5e: configuration (chipbench/configs/<name>) -> the blocks its
# pool holds on the chip (the ledger's kv_cache_write shapes; a pool larger
# than the chip's memory is refused at compile time).
STEP_CONFIGS = {
    "qwen3-8b-l16": 4859,
    "olmoe-1b-7b-l8": 8192,
    "ouro-2.6b": 330,
    "solar-open2-250b-ep16-l8": 4096,
    "openpangu-ultra-moe-718b-ep16-l5": 4096,
    "phi-4-mini-flash-reasoning": 4096,
    "kimi-linear-48b-a3b-ep16": 4096,
    "falcon-h1-34b-l6": 8192,
    "olmo-hybrid-7b-l16": 2560,
    # the full layers' pool: what 0.9 of the memory left beside 8.29 GB of
    # weights and the 1.83 GB window pool holds, ~3 GB of 131,072 B blocks
    "trinity-large-preview-ep16-l8": 23040,
}
# a whole weight stack is tens of MiB and up; a step's own rows are not
COPY_FLOOR = 16 * 2 ** 20


def _chipbench_cfg(config):
    """(the configuration as its cell's engine reads it: the published
    config.json, ``--max-model-len`` from the manifest's engine flags; the
    compiler options its manifest sets in the engine's environment)."""
    import dataclasses
    import json
    import os

    from production_stack_tpu.engine.config import ModelConfig

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "configs", config)
    with open(os.path.join(root, "config.json")) as f:
        cfg = ModelConfig.from_hf_config(json.load(f), config)
    with open(os.path.join(root, "manifest.json")) as f:
        manifest = json.load(f)
    flags = manifest["engine_flags"]
    # LIBTPU_INIT_ARGS=--xla_tpu_scoped_vmem_limit_kib=N, as an option
    options = {
        k.lstrip("-"): int(v) for k, _, v in (
            a.partition("=") for a in manifest.get("engine_env", {}).get(
                "LIBTPU_INIT_ARGS", "").split())}
    return dataclasses.replace(
        cfg, max_model_len=int(flags[flags.index("--max-model-len") + 1])
    ), options, (manifest["decode_slots"], manifest["token_budget"])


def _cache_shapes(cfg, blocks, slots, one_chip, budget=2048):
    """The cache pytree ``engine/kv_cache.py`` ``init_kv_cache`` makes (it
    allocates, so it cannot be asked for shapes alone)."""
    from production_stack_tpu.engine import kv_cache as kvmod

    def sds(shape, dt=cfg.jax_dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds(cfg.kv_pool_shape(blocks, BS))
    if not cfg.has_recurrent_state:
        if not cfg.window_binds:
            return pool
        return {"kv": pool, "win": sds(cfg.kv_pool_shape(
            kvmod.window_pool_blocks(cfg, BS, slots, budget), BS,
            window=True))}
    if cfg.mamba_period:
        n, di = cfg.count_layers("mamba"), cfg.mamba_inner
        state, conv = (cfg.mamba_state, di), (cfg.mamba_conv - 1, di)
    elif cfg.ssd_heads:
        n = cfg.count_layers("parallel")
        state = (cfg.ssd_heads, cfg.ssd_state, cfg.ssd_head_dim)
        conv = (cfg.ssd_conv - 1, cfg.ssd_conv_dim)
    elif cfg.gdn_heads:
        n = cfg.count_layers("gdn")
        state = (cfg.gdn_heads // 2, cfg.gdn_key_dim, 2 * cfg.gdn_value_dim)
        conv = (cfg.gdn_conv - 1, cfg.gdn_conv_dim)
    else:
        n, h, d = cfg.num_kda_layers, cfg.kda_heads, cfg.kda_head_dim
        state, conv = (h, d, d), (cfg.kda_conv - 1, 3 * h * d)
    caches = {"kv": pool, "state": sds((n, slots, *state), jnp.float32),
              "conv": sds((n, slots, *conv))}
    if cfg.window_binds:
        caches["win"] = sds(cfg.kv_pool_shape(
            kvmod.window_pool_blocks(cfg, BS, slots, budget), BS,
            window=True))
    return caches


def _step_program(one_chip, config, program, layouts=True):
    """``_decode_multi_step`` ("decode") or ``_ragged_step`` ("ragged512",
    "ragged2048": the stream's width) of a chipbench configuration,
    compiled as the runner jits it, without a runner: no weights are made
    and no cache. The parameters are the tree a runner keeps
    (``engine/weights.py`` ``lay_out``; ``layouts`` False: the tree as it is
    made, what every runner kept before PR 53 and PERF.md section 5's
    survey lists under "parent")."""
    import functools
    import types

    import numpy as np

    from production_stack_tpu.engine import model_runner as mr
    from production_stack_tpu.engine import weights
    from production_stack_tpu.models.registry import get_model
    from production_stack_tpu.ops.moe_grouped_matmul_pallas import (
        grouped_kernel_path,
        moe_grouped_matmul,
    )

    (cfg, options, (slots, budget)), blocks = (
        _chipbench_cfg(config), STEP_CONFIGS[config])

    def made():
        tree = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
        return weights.lay_out(cfg, tree) if layouts else tree

    shapes = jax.eval_shape(made)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes)
    caches = _cache_shapes(cfg, blocks, slots, one_chip, budget)
    # the runner's stateful calls without a runner: one chip, so no
    # shard_map (``tp`` reads the rules and finds no axis split)
    me = object.__new__(mr.ModelRunner)
    me.cfg, me.use_pallas = cfg, True
    me.rules = types.SimpleNamespace(rules={})
    recur = (me._recur_mamba if cfg.mamba_period
             else me._recur_ssd if cfg.ssd_heads else me._recur)
    grouped = (moe_grouped_matmul if cfg.is_moe and all(
        grouped_kernel_path(*shapes["layers"][w].shape[-2:], 2)
        for w in ("w_gate", "w_down")) else None)
    table = (slots, (-(-cfg.max_model_len // BS) + 7) // 8 * 8)
    i32, f32, u32 = np.int32, np.float32, np.uint32
    if program == "decode":
        arrays = [np.zeros(s, dt) for s, dt in (
            (slots, i32), (slots, i32), (table, i32), (slots, i32),
            (slots, i32), (slots, f32), (slots, f32), (slots, i32),
            (slots, u32), (slots, i32), (1, i32))]
        spec, window = mr._DECODE_INPUTS, [np.zeros(table, i32),
                                           np.zeros(slots, i32)]
        step = functools.partial(
            mr._decode_multi_step, cfg, me._attend_decode, 1, 0,
            recur_impl=(functools.partial(recur, False)
                        if cfg.has_recurrent_state else None),
            grouped_matmul=grouped)
        static = ("layout", "block_size", "greedy_only", "want_logprobs")
        # the device tokens of a chained dispatch
        behind = [jax.ShapeDtypeStruct((slots, 1), I32, sharding=one_chip)]
        flags = {"block_size": BS, "want_logprobs": False}
    else:
        width = int(program.removeprefix("ragged"))
        arrays = [np.zeros(s, dt) for s, dt in (
            ((1, width), i32), ((1, width), i32), (table, i32),
            (slots, i32), (slots + 1, i32), (width, i32), (slots, i32),
            (slots, f32), (slots, f32), (slots, f32), (slots, i32),
            (slots, u32), (slots, i32))]
        spec, window = mr._RAGGED_INPUTS[:len(arrays)], [
            np.zeros(table, i32), np.zeros(width, i32)]
        step = functools.partial(
            mr._ragged_step, cfg, me._attend_ragged, 0, 0,
            recur_impl=(functools.partial(recur, True)
                        if cfg.has_recurrent_state else None),
            grouped_matmul=grouped)
        static = ("layout", "greedy_only")
        behind, flags = [], {}
    if cfg.window_binds:
        arrays, spec = arrays + window, spec + mr._WINDOW_INPUTS
    layout = mr.StepLayout.of(spec, arrays)
    packed = jax.ShapeDtypeStruct(layout.pack(arrays).shape, I32,
                                  sharding=one_chip)
    return jax.jit(step, donate_argnums=(1,), static_argnames=static).lower(
        params, caches, packed, *behind, layout=layout, greedy_only=True,
        **flags).compile(compiler_options=options)


def _parameter_copies(text):
    """[(instruction, its operand, bytes, layout)] of every ``copy`` of a
    leaf of the program's ``params`` argument in compiled HLO text (the
    entry computation names it by its path, ``%params__layers____wq__``):
    what a step does to a weight stack that does not lie as the program
    reads it."""
    sizes = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "f16": 2}
    found = []
    for m in re.finditer(
            r"^\s*%(copy[.\d]*) = (\w+)\[([\d,]*)\](\{[^}]*\})? "
            r"copy\([^%]*%(params__[\w.]*)\)", text, flags=re.M):
        name, dt, dims, lay, operand = m.groups()
        n = sizes.get(dt, 4)
        for d in filter(None, dims.split(",")):
            n *= int(d)
        found.append((name, operand, n, lay))
    return found


# temporaries a program may hold, MiB: a step's own rows. Before PR 53
# Qwen3's decode program held 768.8 (W_q, W_k, W_v copied whole), Ouro's
# 1,152.9 and its 512-wide ragged program 1,157.0, OLMoE's 199.1,
# Solar-Open2's 365.6; Falcon-H1's 781 before PR 52
STEP_TEMP_MIB = {
    ("qwen3-8b-l16", "decode"): 32,
    ("ouro-2.6b", "decode"): 32,
    ("ouro-2.6b", "ragged512"): 32,
    ("olmoe-1b-7b-l8", "decode"): 32,
    ("solar-open2-250b-ep16-l8", "decode"): 100,
    ("falcon-h1-34b-l6", "decode"): 32,
    ("olmo-hybrid-7b-l16", "decode"): 32,
}
# whole stacks still copied (ROADMAP S20): a width that is no whole 128-lane
# tiles (the router's 320 experts, W_x's 192 columns), as Falcon-H1's W_in
# was before it was split; and the one leading dense layer's rotated query
# columns of a latent stack, 24 MiB of a ~150 ms step, which
# ``param_layouts`` does not name
STILL_COPIED = {
    ("solar-open2-250b-ep16-l8", "decode"): "params__layers____router__",
    ("phi-4-mini-flash-reasoning", "decode"): "params__mamba____w_x__",
    ("openpangu-ultra-moe-718b-ep16-l5", "ragged2048"):
        "params__dense____wq_rope__",
}
TRINITY = "trinity-large-preview-ep16-l8"
STEP_CASES = [(c, p) for c in STEP_CONFIGS if c != TRINITY
              for p in ("decode", "ragged512")]
# 16 slots and a 4096-token budget: its streams are 1024 and 4096 wide
STEP_CASES += [(TRINITY, "decode"), (TRINITY, "ragged4096")]
# the configurations whose cells fill the wide stream (prompts of 1024
# tokens and more)
STEP_CASES += [(c, "ragged2048") for c in (
    "qwen3-8b-l16", "solar-open2-250b-ep16-l8",
    "openpangu-ultra-moe-718b-ep16-l5", "phi-4-mini-flash-reasoning",
    "kimi-linear-48b-a3b-ep16")]


@pytest.mark.parametrize("config,program", STEP_CASES,
                         ids=[f"{c}-{p}" for c, p in STEP_CASES])
def test_step_program_copies_no_weight_stack(one_chip, config, program):
    """The TPU compiler lays a projection's stack out as the step's rows
    like it and, handed another, copies the WHOLE stack at the start of
    every step, outside the layer scan: Falcon-H1's W_in at its published
    9248 columns (no whole 128-lane tiles) 568 MB and W_q / W_k / W_v as
    (E, H D) 220 MB (PERF.md section 6, PR 52: 7 % of the chip's busy time
    went to the first); Qwen3's W_q / W_k / W_v as (E, H, D) 805 MB, Ouro's
    1.2 GB (PR 53). So a runner keeps an attention projection's stack
    with its contracted axis last (``llama.param_layouts``,
    ``weights.lay_out``): the program's temporaries are a step's rows,
    nothing more."""
    compiled = _step_program(one_chip, config, program)
    text = compiled.as_text()
    kernels = {
        ("falcon-h1-34b-l6", "decode"): (
            "ssd_decode_step", "paged_decode_attention", "kv_cache_write"),
        ("olmo-hybrid-7b-l16", "decode"): (
            "gdn_decode_step", "paged_decode_attention", "kv_cache_write"),
        ("olmo-hybrid-7b-l16", "ragged512"): (
            "gdn_decode_step", "gdn_chunk_scan", "ragged_paged_attention",
            "kv_cache_write"),
        # the latent cells' decode programs: the decode body, by the name
        # the trace reductions match (``LATENT_OP``)
        ("kimi-linear-48b-a3b-ep16", "decode"): (
            "latent_paged_attention", "kda_decode_step"),
        ("openpangu-ultra-moe-718b-ep16-l5", "decode"): (
            "latent_paged_attention",),
        (TRINITY, "decode"): (
            "paged_decode_attention", "kv_cache_write", "moe_grouped_matmul"),
        (TRINITY, "ragged4096"): (
            "ragged_paged_attention", "kv_cache_write", "moe_grouped_matmul"),
    }.get((config, program), ())
    for name in kernels:
        assert re.search(rf"^\s*(?:ROOT )?%{name}[.\d]* = ", text,
                         flags=re.M), name
    whole = [c for c in _parameter_copies(text) if c[2] >= COPY_FLOOR
             and not c[1].startswith(STILL_COPIED.get((config, program), "-"))]
    assert not whole, whole
    bound = STEP_TEMP_MIB.get((config, program))
    if bound is not None:
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < bound * 2 ** 20, temp / 2 ** 20
