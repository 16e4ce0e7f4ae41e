"""The state-space mixer of the parallel hybrid stack (Falcon-H1,
``model_type: falcon_h1``), its parameters and random stand-in weights. The
stack itself is walked by ``models/llama.py`` ``_forward_hybrid``
(``ModelConfig.layer_kinds``: every layer is "parallel"); with ``n =
RMSNorm_1(h)`` a layer is

    h += ssm_out * SSM(n) + attention_out * ATT(attention_in * n)
    h += MLP(RMSNorm_2(h))

BOTH mixers read the same ``n`` and their outputs are summed before the one
residual add. ``ATT`` is rotated grouped-query attention whose keys carry
``key_multiplier`` (the "gqa" stack, Solar-Open2's layout); ``SSM`` is a
Mamba-2 mixer with heads (``ops/ssd.py`` has the scan's equations):

    [z | xBC | dt] = (W_in (ssm_in * n)) * mup          (``mup_vector``)
        (W_in lies as two matrices, its [z | xBC] columns and its dt
        columns: the first is then whole 128-lane tiles wide; at the
        published 9248 columns the TPU compiler copied the whole stack, 568
        MB, at the start of every decode step; PERF.md section 6, PR 52)
    y = the stateful part: short convolution with bias, SiLU, the scan, D x
    u = y * silu(z);  u <- grouped RMSNorm(u) * w_norm;  SSM = W_out u

the gate BEFORE the norm (``mamba_norm_before_gate`` false) and the norm
over each of the ``ssd_groups`` groups of channels on its own. The scan, the
gate and the norm are float32.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.engine.quant import quant_einsum
from production_stack_tpu.ops import kda, ssd
from production_stack_tpu.ops.scalars import over, times
from production_stack_tpu.parallel import shardings as lax_names

F32 = jnp.float32
# SsdFn, the mixer's stateful call, as AttendFn is an attention layer's:
# (the layer's parameters, its rows before the convolution (..., T, H*P +
# 2*G*N) = [x | B | C], the raw step dt (..., T, H), caches, index among
# the layers) -> (y (..., T, H*P) float32 with the skip term, new caches)
SsdFn = Callable[..., Tuple[jnp.ndarray, Any]]


def param_specs(cfg: ModelConfig) -> dict:
    """The two mixers' stacks, each (layers, ...). One chip holds the model
    whole (engine/model_runner.py refuses a mesh), so only the layer axis
    is named."""
    L = lax_names.LAYERS
    return {
        "gqa": {"wq_t": (L, None, None), "wk_t": (L, None, None),
                "wv_t": (L, None, None), "wo": (L, None, None, None)},
        "ssd": {"w_in": (L, None, None), "w_dt": (L, None, None),
                "conv": (L, None, None),
                "conv_bias": (L, None), "dt_bias": (L, None),
                "a_log": (L, None), "d": (L, None), "norm": (L, None),
                "w_out": (L, None, None)},
    }


def mup_vector(cfg: ModelConfig) -> np.ndarray:
    """The multipliers of the input projection's columns, [z | x | B | C |
    dt], as published (``compute_mup_vector``)."""
    gn = cfg.ssd_groups * cfg.ssd_state
    widths = (cfg.ssd_inner, cfg.ssd_inner, gn, gn, cfg.ssd_heads)
    return np.concatenate([np.full(w, m, np.float32)
                           for w, m in zip(widths, cfg.ssd_multipliers)])


# Random stand-in weights. The family's published scalars are small where
# its trained matrices are large; drawn at the usual normal(0, fan_in^-0.5)
# the scalars would leave the stand-in flat (keys x 0.011: every softmax
# uniform; the head x 0.0078: every log-probability ~ -log V; the mixers
# and the MLP vanishing beside the stream) and the benchmark's probe would
# pass whatever the layers compute. ONE rule: a matrix whose output (or
# input) a published scalar multiplies is drawn at its usual size OVER that
# scalar (``over``), so that scalar x matrix has the shared stack's scale
# and the scalars are still applied on the served path and in the
# reference, where a dropped one shows. Everything else is HYBRID_INIT as
# it is (models/llama.py: a unit-RMS embedding, the matrices that write
# into the stream at 1 / sqrt(2 x layers)). The state-space parameters are
# the family's own initialisation (``D`` 1, ``dt_bias`` the inverse softplus
# of a log-uniform step, conv bias 0, ``w_norm`` 1) but for the decay:
# ``A`` = STANDIN_A every head, where the family starts at 1..H. A head
# forgets in 1 / (step x A) tokens: 0.3 to 1,000 at A = 1..32 and steps of
# 0.1 to 0.001, a median of ~6, so an untrained stack's state holds
# nothing its 4-row convolution does not, y is ``D x`` to a tenth, and
# the benchmark's probe could not tell a bfloat16 state from a float32 one
# (0.048 / 0.0113 against the sound runs' 0.043 / 0.0103; PERF.md section
# 6, PR 52). A trained mixer is kept for what it remembers. At A = 1 / 4 a
# head remembers 40 to 4,000 tokens, log-uniform over heads and layers with
# its step: the cell's contexts (to 1,024) and the probe's (3,136) lie
# inside, the state carries y (D x is a tenth of it), and what rounding it
# to bfloat16 after every token leaves is the probe's to see.
STANDIN_A = 0.25


def init_params(cfg: ModelConfig, key: jax.Array, normal, out: int,
                dt_range: tuple) -> dict:
    """``normal(key, shape, fan_in)``; ``out``: what the fan-in of the
    matrices that write into the residual stream is multiplied by
    (models/llama.py HYBRID_INIT)."""
    E, H, KH, D = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                   cfg.head_dim)
    di, cd, K, Hs = cfg.ssd_inner, cfg.ssd_conv_dim, cfg.ssd_conv, cfg.ssd_heads
    Ln, dt = cfg.num_layers, cfg.jax_dtype
    ks = iter(jax.random.split(key, 12))
    a_in = cfg.attn_in_multiplier
    step = jnp.exp(jax.random.uniform(
        next(ks), (Ln, Hs), F32, jnp.log(dt_range[0]), jnp.log(dt_range[1])))
    w_in = over(normal(next(ks), (Ln, E, di + cd + Hs), E),
                cfg.ssd_in_multiplier * mup_vector(cfg))
    return {
        "gqa": {
            # transposed, (H * D, E): models/llama.py _forward_hybrid gqa
            "wq_t": over(normal(next(ks), (Ln, H * D, E), E), a_in),
            "wk_t": over(normal(next(ks), (Ln, KH * D, E), E),
                         a_in * cfg.key_multiplier),
            "wv_t": over(normal(next(ks), (Ln, KH * D, E), E), a_in),
            "wo": over(normal(next(ks), (Ln, H, D, E), H * D * out),
                       cfg.attn_out_multiplier),
        },
        "ssd": {
            "w_in": w_in[..., :di + cd],
            "w_dt": w_in[..., di + cd:],
            # depthwise taps over [x | B | C]; tap 0 is the current row
            "conv": normal(next(ks), (Ln, K, cd), K),
            "conv_bias": jnp.zeros((Ln, cd), dt),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
            "a_log": jnp.full((Ln, Hs), np.log(STANDIN_A), F32),
            "d": jnp.ones((Ln, Hs), F32),
            "norm": jnp.ones((Ln, di), dt),
            "w_out": over(normal(next(ks), (Ln, di, E), di * out),
                          cfg.ssd_out_multiplier),
        },
    }


# -- the mixer ----------------------------------------------------------------

def ssd_dense(cfg: ModelConfig, sp, xbc, dt, caches, idx):
    """The stateful part over whole sequences, xbc (B, T, ...), from an
    empty past: no cache is read or written (the dense forward, and the
    definition the cached forms are held against)."""
    return ssd.mix(sp, xbc, dt, cfg.ssd_heads, cfg.ssd_groups, cfg.ssd_state,
                   kda.conv_dense, ssd.scan_dense), caches


def gated_group_norm(cfg: ModelConfig, y: jnp.ndarray, z: jnp.ndarray,
                     weight: jnp.ndarray) -> jnp.ndarray:
    """``y * silu(z)``, then RMSNorm over each group of channels on its
    own, float32."""
    u = y.astype(F32) * jax.nn.silu(z.astype(F32))
    grouped = u.reshape(*u.shape[:-1], cfg.ssd_groups, -1)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True)
        + cfg.rms_norm_eps)
    return grouped.reshape(u.shape) * weight.astype(F32)


def ssd_mixer(cfg: ModelConfig, sp: dict, x: jnp.ndarray, recur: SsdFn,
              caches, idx) -> Tuple[jnp.ndarray, Any]:
    """Returns (SSM(x) (..., T, E) before ``ssm_out_multiplier``, caches)."""
    x_in, mup = times(x, cfg.ssd_in_multiplier), mup_vector(cfg)
    di, cd = cfg.ssd_inner, cfg.ssd_conv_dim
    p = quant_einsum("...te,ef->...tf", x_in, sp["w_in"])
    p = p * jnp.asarray(mup[:di + cd], p.dtype)
    dt = quant_einsum("...te,ef->...tf", x_in, sp["w_dt"])
    dt = dt * jnp.asarray(mup[di + cd:], dt.dtype)
    y, caches = recur(sp, p[..., di:], dt, caches, idx)
    u = gated_group_norm(cfg, y, p[..., :di], sp["norm"]).astype(x.dtype)
    return quant_einsum("...tf,fe->...te", u, sp["w_out"]), caches
