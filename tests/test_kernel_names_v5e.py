"""The four Pallas kernels keep their names through the TPU compiler.

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

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from production_stack_tpu.ops.paged_attention_pallas import (
    kv_cache_write_pallas,
    paged_decode_attention_pallas,
    paged_prefill_attention_pallas,
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
    "paged_prefill_attention": (
        lambda q, c, bt, qs, ct: paged_prefill_attention_pallas(
            q, c, bt, qs, ct, layer_idx=1),
        (((2, 256, H, D), jnp.bfloat16), CACHE, ((2, M), I32),
         ((2,), I32), ((2,), I32))),
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
