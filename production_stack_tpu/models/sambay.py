"""The token mixers of the decoder-hybrid-decoder stack (SambaY,
arXiv:2507.06607; ``model_type: phi4flash``), their parameters and random
stand-in weights. The stack itself is walked by ``models/llama.py``
``_forward_hybrid`` (``ModelConfig.layer_kinds`` / ``stack_segments``);
every block is ``h += Mixer(LN1(h)); h += MLP(LN2(h))`` with LayerNorm
(weight and bias) and the shared SwiGLU MLP, and nothing applies a
positional encoding: order comes from the scan.

- "mamba": a Mamba-1 state-space layer (``ops/mamba.py`` has the scan's
  equations): ``[x; z] = W_in u``; the stateful part (short convolution
  with bias, SiLU, the projections of Delta, B and C, the scan, the skip
  ``D x``) gives ``y``; out = ``W_out (y * silu(z))``. The layer before the
  one full-attention layer also hands ``y`` on, as ``m``.
- "swa" / "full": differential attention (arXiv:2410.05258) over keys and
  values of its own, with a window of ``sliding_window`` rows or none.
  Query and key heads pair up (2i, 2i + 1); with ``A_a = softmax(q_a
  k_a^T / sqrt(D) + mask)`` and ``V = [v_1, v_2]`` (2 D wide), ``o =
  RMSNorm(A_1 V - lambda A_2 V) * (1 - lambda_init)``, ``lambda =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 -
  0.6 exp(-0.3 l)`` at depth ``l``; out = ``W_o concat(o) + b_o``.
- "cross": the same with ``W_q``, ``W_o``, the lambdas and the norm only:
  its keys and values are the "full" layer's.
- "gmu": a gated memory unit, out = ``W_out (silu(W_in u) * m)``.

**One kernel for both softmaxes.** With ``q'_1 = [q_1, 0]``, ``q'_2 = [0,
q_2]``, ``k' = [k_1, k_2]``, ``v' = [v_1, v_2]``, plain grouped-query
attention over heads of 2 D gives ``q'_a . k' = q_a . k_a``, so its output
for ``q'_a`` is ``A_a V`` exactly: the attention kernels, the cache layout
(``ModelConfig.cache_kv_heads`` heads of ``cache_head_dim``) and the block
tables are the shared ones, and the combine is elementwise after the
call. The kernels scale scores by their head's width ** -0.5, (2 D) ** -0.5
here, so ``q'`` carries sqrt(2), folded in float32.
tests/test_phi4_flash.py holds this against the four-softmax form.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.engine.quant import as_stored, quant_einsum
from production_stack_tpu.ops import kda, mamba
from production_stack_tpu.ops.norms import rms_norm
from production_stack_tpu.parallel import shardings as lax_names

F32 = jnp.float32
# the stack of parameters a layer kind's mixer is taken from (the window
# layers and the full one are one stack, in depth order)
STACK_OF = {"mamba": "mamba", "swa": "attn", "full": "attn", "gmu": "gmu",
            "cross": "cross"}
# MambaFn, a state-space layer's stateful call, as AttendFn is an
# attention layer's: (the layer's parameters, its rows before the
# convolution (..., T, d_i), caches, index among the state-space layers)
# -> (y (..., T, d_i) float32 with the skip term, new caches)
MambaFn = Callable[..., Tuple[jnp.ndarray, Any]]


def param_specs(cfg: ModelConfig) -> dict:
    """The mixers' stacks, each (layers of its kind, ...). One chip holds
    the model whole (engine/model_runner.py refuses a mesh), so only the
    layer axis is named."""
    L = lax_names.LAYERS
    attn = {"wq": (L, None, None), "bq": (L, None), "wo": (L, None, None),
            "bo": (L, None), "subln": (L, None),
            **{f"lambda_{n}": (L, None) for n in ("q1", "k1", "q2", "k2")}}
    return {
        "mamba": {"w_in": (L, None, None), "conv": (L, None, None),
                  "conv_bias": (L, None), "w_x": (L, None, None),
                  "w_dt": (L, None, None), "dt_bias": (L, None),
                  "a_log": (L, None, None), "d": (L, None),
                  "w_out": (L, None, None)},
        "attn": {**attn, "wk": (L, None, None), "bk": (L, None),
                 "wv": (L, None, None), "bv": (L, None)},
        "gmu": {"w_in": (L, None, None), "w_out": (L, None, None)},
        "cross": attn,
    }


def param_layouts(cfg: ModelConfig) -> dict:
    """The order of axes in which a runner keeps each leaf of
    ``param_specs`` (models/llama.py ``param_layouts``): the projections
    from the embedding onto all heads, (n, E, H * D), with the embedding
    axis last, as ``wq_t`` (n, H * D, E). A decode step copied the four
    stacks whole as they are made, 328 MB (PERF.md section 5, PR 53)."""
    layouts = jax.tree.map(lambda _: None, param_specs(cfg),
                           is_leaf=lambda x: isinstance(x, tuple))
    for stack, names in (("attn", ("wq", "wk", "wv")), ("cross", ("wq",))):
        layouts[stack] = {**layouts[stack],
                          **dict.fromkeys(names, (0, 2, 1))}
    return layouts


# Random stand-in weights of a state-space layer: ``dt`` log-uniform in
# [KDA_DT_MIN, KDA_DT_MAX] a channel with ``dt_bias`` its inverse softplus
# (models/llama.py KDA_DT_MIN/MAX: the initialisation state-space models
# use, so that Delta = dt e^z for the projection's unit-variance z),
# ``A_log = log(1..N)`` a channel (a channel's N values of state forget at
# N rates), ``D`` 1. ``a_log`` is stored (N, d_i), as the scan reads it.

def init_params(cfg: ModelConfig, key: jax.Array, normal, out: int,
                dt_range: tuple) -> dict:
    """``normal(key, shape, fan_in)``; ``out``: what the fan-in of the
    matrices that write into the residual stream is multiplied by
    (models/llama.py HYBRID_INIT)."""
    E, H, KH, D = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                   cfg.head_dim)
    di, N, K, R = (cfg.mamba_inner, cfg.mamba_state, cfg.mamba_conv,
                   cfg.mamba_dt_rank)
    dt = cfg.jax_dtype
    Lm, Lg, Lc = (cfg.count_layers(k) for k in ("mamba", "gmu", "cross"))
    La = cfg.count_layers("swa", "full")
    ks = iter(jax.random.split(key, 32))

    def attn(n):
        return {
            "wq": normal(next(ks), (n, E, H * D), E),
            "bq": normal(next(ks), (n, H * D), E),
            "wo": normal(next(ks), (n, H * D, E), H * D * out),
            "bo": jnp.zeros((n, E), dt),
            "subln": jnp.ones((n, 2 * D), dt),
            **{f"lambda_{name}": 0.1 * jax.random.normal(
                next(ks), (n, D), F32) for name in ("q1", "k1", "q2", "k2")},
        }

    step = jnp.exp(jax.random.uniform(
        next(ks), (Lm, di), F32, jnp.log(dt_range[0]), jnp.log(dt_range[1])))
    return {
        "mamba": {
            "w_in": normal(next(ks), (Lm, E, 2 * di), E),
            # depthwise taps over the row; tap 0 is the current row
            "conv": normal(next(ks), (Lm, K, di), K),
            "conv_bias": jnp.zeros((Lm, di), dt),
            "w_x": normal(next(ks), (Lm, di, R + 2 * N), di),
            "w_dt": normal(next(ks), (Lm, R, di), R),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
            "a_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=F32))[None, :, None],
                (Lm, N, di)),
            "d": jnp.ones((Lm, di), F32),
            "w_out": normal(next(ks), (Lm, di, E), di * out),
        },
        "attn": {**attn(La),
                 "wk": normal(next(ks), (La, E, KH * D), E),
                 "bk": normal(next(ks), (La, KH * D), E),
                 "wv": normal(next(ks), (La, E, KH * D), E),
                 "bv": normal(next(ks), (La, KH * D), E)},
        "gmu": {"w_in": normal(next(ks), (Lg, E, di), E),
                "w_out": normal(next(ks), (Lg, di, E), di * out)},
        "cross": attn(Lc),
    }


# -- the mixers ---------------------------------------------------------------

def mamba_dense(cfg: ModelConfig, mp, xs, caches, m_idx):
    """A state-space layer over whole sequences, xs (B, T, d_i), from an
    empty past: no cache is read or written (the dense forward, and the
    definition the cached forms are held against)."""
    return mamba.mix(mp, xs, cfg.mamba_state, kda.conv_dense,
                     mamba.scan_dense), caches


def mamba_mixer(cfg: ModelConfig, mp: dict, x: jnp.ndarray, recur: MambaFn,
                caches, m_idx) -> Tuple[jnp.ndarray, jnp.ndarray, Any]:
    """Returns (out (..., T, E), y (..., T, d_i) in the model dtype: what
    the gated memory units read where this is the layer that feeds them,
    caches)."""
    xz = quant_einsum("...te,ef->...tf", x, mp["w_in"])
    di = cfg.mamba_inner
    y, caches = recur(mp, xz[..., :di], caches, m_idx)
    gated = (y * jax.nn.silu(xz[..., di:].astype(F32))).astype(x.dtype)
    return (quant_einsum("...tf,fe->...te", gated, mp["w_out"]),
            y.astype(x.dtype), caches)


def gmu_mixer(gp: dict, x: jnp.ndarray, m: jnp.ndarray) -> jnp.ndarray:
    gate = quant_einsum("...te,ef->...tf", x, gp["w_in"])
    return quant_einsum("...tf,fe->...te", jax.nn.silu(gate) * m, gp["w_out"])


def lambda_init(depth) -> jnp.ndarray:
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, F32))


def packed_queries(cfg: ModelConfig, ap: dict, x: jnp.ndarray) -> jnp.ndarray:
    """(..., T, H, 2 D): head 2i as ``[q, 0]``, head 2i + 1 as ``[0, q]``,
    times sqrt(2) (see the header)."""
    H, D = cfg.num_heads, cfg.head_dim
    eq, wq = as_stored(ap, "wq")
    q = (jnp.einsum(eq, x, wq, preferred_element_type=F32)
         + ap["bq"].astype(F32)) * 2.0 ** 0.5
    q = q.reshape(*q.shape[:-1], H, D).astype(x.dtype)
    first = (jnp.arange(H) % 2 == 0)[:, None]
    zeros = jnp.zeros_like(q)
    packed = jnp.concatenate([jnp.where(first, q, zeros),
                              jnp.where(first, zeros, q)], axis=-1)
    return _pad_heads(packed, cfg.q_per_kv * cfg.cache_kv_heads)


def _pad_heads(x: jnp.ndarray, heads: int) -> jnp.ndarray:
    """(..., h, d) -> (..., heads, d): empty heads behind the real ones
    (``ModelConfig.cache_kv_heads``)."""
    pad = heads - x.shape[-2]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pad), (0, 0)]) \
        if pad else x


def packed_keys_values(cfg: ModelConfig, ap: dict, x: jnp.ndarray):
    """k', v' (..., T, cache_kv_heads, 2 D): a pair's two heads side by
    side, then the empty heads."""
    def heads(w, b):
        eq, matrix = as_stored(ap, w)
        y = quant_einsum(eq, x, matrix) + ap[b]
        return _pad_heads(
            y.reshape(*y.shape[:-1], -1, cfg.cache_head_dim),
            cfg.cache_kv_heads)

    return heads("wk", "bk"), heads("wv", "bv")


def diff_combine(cfg: ModelConfig, ap: dict, o: jnp.ndarray, depth
                 ) -> jnp.ndarray:
    """The packed heads' outputs (..., T, H, 2 D) = [A_1 V, A_2 V] a pair
    -> W_o concat(RMSNorm(A_1 V - lambda A_2 V) * (1 - lambda_init)) +
    b_o. Lambda, the difference and the norm are float32."""
    H, D = cfg.num_heads, cfg.head_dim
    pair = o[..., :H, :].astype(F32).reshape(*o.shape[:-2], H // 2, 2, 2 * D)
    init = lambda_init(depth)
    lam = (jnp.exp(jnp.sum(ap["lambda_q1"] * ap["lambda_k1"]))
           - jnp.exp(jnp.sum(ap["lambda_q2"] * ap["lambda_k2"])) + init)
    diff = rms_norm(pair[..., 0, :] - lam * pair[..., 1, :], ap["subln"],
                    cfg.rms_norm_eps) * (1.0 - init)
    diff = diff.reshape(*diff.shape[:-2], H * D).astype(o.dtype)
    return quant_einsum("...tf,fe->...te", diff, ap["wo"]) + ap["bo"]
