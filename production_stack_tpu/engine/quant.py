"""int8 quantization (W8A8) for the serving forward pass.

TPU-first design: the decode step is weight-bandwidth bound (docs/roofline.md
— the bf16 matmul stack reads 6.4 GB/step on the 3B flagship, ~60 % of v5e
HBM bandwidth), so the highest-leverage lever is to stream weights from HBM
at half the width. Rather than weight-only dequantization (whose benefit
depends on XLA fusing the int8→bf16 convert into the dot's operand read —
not guaranteed, and a materialised bf16 temp would *add* traffic), both
operands are quantized and the MXU's native int8 path does the matmul:

- **weights**: per-output-channel symmetric int8, scales computed over the
  contracted axes at load time (``quantize_params``). Scales keep their
  reduced axes as size-1 dims so they broadcast straight into the matmul
  output — including batched-dim cases like MoE expert stacks.
- **activations**: dynamic per-token symmetric int8, scale from the token's
  absmax over the contracted axes, computed inside the jitted step (a fused
  elementwise pass, negligible next to the matmul).
- accumulation in int32 (``preferred_element_type``), rescale in f32, cast
  back to the model dtype.

This is the scheme vLLM ships as "int8 w8a8 dynamic" (per-channel weight /
per-token activation); it also doubles MXU throughput on v5e (197 bf16 →
394 int8 TOPS), so prefill gains too. Opt-in via ``ModelConfig.quant``
(server flag ``--quantization int8``); norms, biases, MoE routers and the
LoRA bank stay in the model dtype.

Reference parity: the reference's engines (vLLM) serve quantized checkpoints
the same opt-in way; the stack itself has no quantization code (it has no
engine). This is engine-native capability per SURVEY.md §7 step 1.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp

# A quantized weight is a plain pytree node: {"q": int8, "s": f32 broadcastable
# scale}. Plain dicts keep lax.scan layer-slicing, sharding propagation and
# orbax serialisation working unchanged.
QuantizedWeight = dict

_EPS = 1e-8

# Intensity-adaptive kernel selection (docs/roofline.md int8 section):
# W8A8's per-token activation quantize + int32 rescale is noise next to
# a bandwidth-bound matmul but measured −14% on compute-bound 4k
# prefill. A matmul's arithmetic intensity is its token count (weight
# bytes amortise over tokens), and that count is STATIC at trace time,
# so the mode picks itself per compiled program: at or above this many
# tokens the contraction is compute-bound and runs W8A16 — activations
# stay in the model dtype and the int8 weights dequantize INTO the dot
# (XLA fuses the convert+scale into the operand read; worst case it
# materialises one tile, still amortised over >=512 tokens) — below it,
# the bandwidth-bound regime keeps native W8A8. This is deliberately
# NOT a prefill/decode switch: a 512-sequence decode batch has the same
# intensity as a 512-token prefill and takes the same branch (the
# measured prefill regression is evidence for W8A16 in exactly that
# regime). MoE expert matmuls (``ragged_quant_dot``) always run W8A8:
# an expert's intensity is the rows routed to it, which no shape says.
# Override: PSTPU_QUANT_A16_THRESHOLD (values <= 0 disable W8A16).
def _a16_threshold() -> int:
    import os

    raw = os.environ.get("PSTPU_QUANT_A16_THRESHOLD", "512")
    try:
        val = int(float(raw))
    except ValueError:
        import logging

        logging.getLogger(__name__).warning(
            "unparseable PSTPU_QUANT_A16_THRESHOLD=%r; using 512", raw)
        return 512
    return max(val, 0)  # <= 0 means "never use W8A16"


def is_quantized(w: Any) -> bool:
    return isinstance(w, dict) and "q" in w and "s" in w


@functools.partial(jax.jit, static_argnames="contract_axes")
def _quantize_leaf(w: jnp.ndarray, contract_axes: Tuple[int, ...]) -> dict:
    wf = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(wf), axis=contract_axes, keepdims=True)
    s = jnp.maximum(s, _EPS) / 127.0
    q = jnp.clip(jnp.round(wf / s), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s}


def quantize_array(w: jnp.ndarray, contract_axes: Tuple[int, ...]) -> dict:
    """Symmetric int8 over ``contract_axes`` (the matmul-contracted dims).

    The scale keeps reduced axes as size-1 (keepdims), so ``q * s`` — and the
    matmul-output rescale — broadcast with no per-site reshape logic, even
    for batched weights like the MoE (X, E, F) expert stack.

    Jitted (XLA fuses the f32 convert/round/clip — eager ops materialised a
    full f32 copy per stage) and synchronised per leaf: quantizing a
    multi-GB stack is async-dispatched, and letting every leaf's
    transients queue unfetched stacked >HBM of temporaries at engine init
    (observed as a RESOURCE_EXHAUSTED on the first prefill fetch, v5e 3B).
    """
    return jax.block_until_ready(_quantize_leaf(w, tuple(contract_axes)))


def dequantize_array(w: dict) -> jnp.ndarray:
    return w["q"].astype(jnp.float32) * w["s"]


def as_stored(p: dict, name: str) -> Tuple[str, Any]:
    """(the einsum equation that takes x (..., T, E) through it, the
    matrix) for the projection ``name`` of ``p``: ``p[name]`` (E, F) as it
    is made, or ``p[name + "_t"]`` (F, E) as a runner keeps it
    (engine/weights.py ``lay_out``)."""
    if name + "_t" in p:
        return "...te,fe->...tf", p[name + "_t"]
    return "...te,ef->...tf", p[name]


def quant_einsum(eq: str, x: jnp.ndarray, w: Any,
                 out_dtype=None) -> jnp.ndarray:
    """``jnp.einsum(eq, x, w)`` accepting a quantized ``w``.

    With a plain array this is exactly ``jnp.einsum``. With a quantized
    weight the kernel is intensity-adaptive (see ``_a16_threshold``):
    below the token threshold the activation is dynamically quantized
    per token (absmax over its contracted axes), the contraction runs
    int8×int8→int32 on the MXU, and the result is rescaled by
    (activation scale × weight scale); at/above it the weights
    fused-dequantize into a model-dtype contraction (W8A16).

    Supported equations: activation first, any leading ``...`` batch dims,
    every non-contracted explicit activation letter appearing as a prefix of
    the output letters (true of every matmul in the model stack, including
    the batched MoE forms).
    """
    if not is_quantized(w):
        out = jnp.einsum(eq, x, w)
        return out if out_dtype is None else out.astype(out_dtype)
    lhs, out_spec = eq.split("->")
    x_spec, w_spec = lhs.split(",")
    x_letters = x_spec.replace(".", "")
    out_letters = out_spec.replace(".", "")
    contracted = [c for c in x_letters if c not in out_letters]
    n = len(x_letters)
    cax = tuple(i - n for i, c in enumerate(x_letters) if c in contracted)

    # intensity-adaptive: compute-bound (many-token) contractions skip
    # the activation quantize and run W8A16 — see _a16_threshold
    contracted_sizes = 1
    for i in cax:
        contracted_sizes *= x.shape[i]
    tokens = x.size // max(contracted_sizes, 1)
    thresh = _a16_threshold()
    if thresh and tokens >= thresh:
        # multiply q*s in f32, round ONCE into the model dtype — the
        # same fidelity a bf16 checkpoint would hold (casting the scale
        # to bf16 first would round twice)
        wd = dequantize_array(w).astype(x.dtype)
        out = jnp.einsum(eq, x, wd)
        return out.astype(out_dtype if out_dtype is not None else x.dtype)

    xf = x.astype(jnp.float32)
    sx = jnp.max(jnp.abs(xf), axis=cax) / 127.0  # (..., surviving)
    sx = jnp.maximum(sx, _EPS)
    xq = jnp.clip(
        jnp.round(xf / jnp.expand_dims(sx, cax)), -127, 127
    ).astype(jnp.int8)
    acc = jnp.einsum(eq, xq, w["q"], preferred_element_type=jnp.int32)
    # surviving activation letters are an output prefix; weight-born output
    # letters are the suffix — pad the activation scale with that many
    # trailing singleton dims, and the (keepdims) weight scale broadcasts
    # from the right on its own.
    n_w_out = len(out_letters) - (len(x_letters) - len(contracted))
    sx_b = sx.reshape(sx.shape + (1,) * n_w_out)
    # lay the weight scale out along the output letters: transpose its
    # letters into output order (contracted size-1 dims to the back), then
    # reshape to one dim per output letter (1 where the letter is
    # activation-born). Rank ≤ out rank, so leading ``...`` batch dims
    # broadcast from the right — correct even for batched/MoE equations
    # where a shared batch letter sits left of activation-only letters.
    w_letters = w_spec.replace(".", "")
    src = {c: i for i, c in enumerate(w_letters)}
    order = [src[c] for c in out_letters if c in src] + [
        i for i, c in enumerate(w_letters) if c not in out_letters
    ]
    sizes = [w["s"].shape[src[c]] if c in src else 1 for c in out_letters]
    w_s = jnp.transpose(w["s"], order).reshape(sizes)
    out = acc.astype(jnp.float32) * sx_b * w_s
    return out.astype(out_dtype if out_dtype is not None else x.dtype)


def ragged_quant_dot(x: jnp.ndarray, w: Any, group_sizes: jnp.ndarray,
                     group_of_row: jnp.ndarray,
                     grouped_matmul=None) -> jnp.ndarray:
    """The MoE block's grouped matmul, accepting a quantized ``w``: x
    (M, K) rows sorted by group, w (G, K, N), row i multiplied by the
    matrix of its group; rows past sum(group_sizes) are left undefined.

    ``grouped_matmul`` is what runs it on unquantized experts: the
    runner's choice for the program it builds, the Pallas kernel of
    ``ops/moe_grouped_matmul_pallas.py`` (``(x, w, group_sizes) ->
    (M, N)``) where that serves, else None: ``jax.lax.ragged_dot``, XLA's
    own ``ragged-dot`` kernel on the TPU and a masked loop elsewhere.
    Both take bf16 operands, accumulate in float32 and give the same
    numbers. A quantized weight always goes through ``ragged_dot``, W8A8
    (rows quantized per row, int8 x int8 -> int32), rescaled by each
    row's own scale and its group's weight scale (G, 1, N), looked up
    through ``group_of_row`` (M,), which may point one past the last
    group for the undefined rows."""
    if not is_quantized(w):
        if grouped_matmul is not None:
            return grouped_matmul(x, w, group_sizes)
        return jax.lax.ragged_dot(x, w, group_sizes)
    xf = x.astype(jnp.float32)
    sx = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0,
                     _EPS)
    xq = jnp.clip(jnp.round(xf / sx), -127, 127).astype(jnp.int8)
    acc = jax.lax.ragged_dot(xq, w["q"], group_sizes,
                             preferred_element_type=jnp.int32)
    sw = w["s"][:, 0, :][jnp.clip(group_of_row, 0, w["s"].shape[0] - 1)]
    return (acc.astype(jnp.float32) * sx * sw).astype(x.dtype)


def embed_lookup(embed: Any, tokens: jnp.ndarray, dtype) -> jnp.ndarray:
    """Token-embedding gather accepting a quantized table (rows dequantize
    after the gather — per-row scale, so only the gathered rows are read)."""
    if not is_quantized(embed):
        return embed.astype(dtype)[tokens]
    q = embed["q"][tokens].astype(jnp.float32)
    s = embed["s"][tokens]  # (..., 1) — keepdims scale rides the gather
    return (q * s).astype(dtype)


def head_from_embed(embed: Any) -> Any:
    """The tied-embedding LM head (embed.T), preserving quantization."""
    if not is_quantized(embed):
        return embed.T
    return {"q": embed["q"].T, "s": embed["s"].T}


# contracted axes per weight, in the stacked (L, ...) layer layout
_LAYER_CONTRACT = {
    "wq": (1,),      # (L, E, H, D)  contract E
    "wk": (1,),
    "wv": (1,),
    "wo": (1, 2),    # (L, H, D, E)  contract H, D
    "w_gate": (1,),  # (L, E, F)     contract E
    "w_up": (1,),
    "w_down": (1,),  # (L, F, E)     contract F
}
_MOE_CONTRACT = {
    "w_gate": (2,),  # (L, X, E, F)  contract E
    "w_up": (2,),
    "w_down": (2,),  # (L, X, F, E)  contract F
}


def params_quantized(params: dict) -> bool:
    return is_quantized(params.get("layers", {}).get("wq"))


def maybe_quantize(cfg, params: dict) -> dict:
    """Apply ``cfg.quant`` to a loaded pytree (idempotent; no-op when off).

    The single entry point every params-materialisation path goes through
    (ModelRunner init/restore, per-stage PP slices), so sleep/wake and
    pipeline stages can't silently drop back to bf16.
    """
    if getattr(cfg, "quant", None) in (None, "", "none"):
        return params
    if cfg.quant != "int8":
        raise ValueError(f"unsupported quantization mode: {cfg.quant!r}")
    if params_quantized(params):
        return params
    return quantize_params(cfg, params)


def quantize_params(cfg, params: dict) -> dict:
    """Quantize a loaded parameter pytree in place of its matmul weights.

    Norms, QKV biases and the MoE router (tiny, accuracy-sensitive) stay in
    the model dtype. Works on host or device arrays; on device each leaf
    quantizes as an elementwise+reduce op, so shardings propagate and a 70B
    never gathers to one host.
    """
    contract = dict(_LAYER_CONTRACT)
    if cfg.is_moe:
        contract.update(_MOE_CONTRACT)
    layers = dict(params["layers"])
    for name, axes in contract.items():
        if name in layers:
            layers[name] = quantize_array(layers[name], axes)
    out = dict(params)
    out["layers"] = layers
    if "embed" in params:  # absent on interior pipeline-stage slices
        out["embed"] = quantize_array(params["embed"], (1,))  # (V, E)
    if "lm_head" in params:
        out["lm_head"] = quantize_array(params["lm_head"], (0,))  # (E, V)
    return out
