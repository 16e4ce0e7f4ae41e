"""The Gated DeltaNet mixer of the Olmo-Hybrid stack (``model_type:
olmo_hybrid``), its parameters and random stand-in weights. The stack
itself is walked by ``models/llama.py`` ``_forward_hybrid``
(``ModelConfig.layer_kinds``: "gdn" and "gqa" by the file's
``layer_types``), in the Olmo 3 family's block, a norm AFTER each sublayer
and none before (``ModelConfig.norms`` "post"):

    x <- x + RMSNorm_a(MIX(x))
    x <- x + RMSNorm_m(MLP(x))

``MIX`` of a "gqa" layer is full multi-head attention with RMSNorm over the
WHOLE q and k projections (OLMoE's), nothing rotated, no gate (``gqa`` in
``_forward_hybrid``). ``MIX`` of a "gdn" layer (per head h; ``ops/gdn.py``
has the recurrence):

    [q~ | k~ | v~] = silu(conv([W_q x | W_k x | W_v x]))     (2 H d_k + H d_v
                                                  channels, causal, no bias)
    q_h = l2norm(q~_h) d_k^-1/2,  k_h = l2norm(k~_h)
    beta_h = sigmoid((W_b x)_h)            (x 2 with ``gdn_neg_eigval``)
    g_h = -exp(A_log_h) softplus((W_a x)_h + dt_bias_h)      (ONE a head)
    o_h = the gated delta rule over (q, k, v, exp(g), beta)   (float32)
    o_h <- RMSNorm(o_h; w over d_v) * silu((W_g x)_h);   MIX = W_o o

the norm BEFORE the gate and the gate a SiLU (the published
``Qwen3NextRMSNormGated``; KDA's gate is a sigmoid). Decay, beta, the
recurrence and the gated norm are float32.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.engine.quant import quant_einsum
from production_stack_tpu.ops import gdn, kda
from production_stack_tpu.ops.norms import rms_norm
from production_stack_tpu.parallel import shardings as lax_names

F32 = jnp.float32


def param_specs(cfg: ModelConfig) -> dict:
    """The GDN mixers' stack, (GDN layers, ...). One chip holds the model
    whole (engine/model_runner.py refuses a mesh), so only the layer axis
    is named. The projections onto all heads are plain matrices whose
    widths are whole 128-lane tiles (11,520 and 5,760 at the published
    sizes): models/llama.py ``param_specs`` says what a (E, H, D) stack
    cost."""
    L = lax_names.LAYERS
    return {
        "w_qkv": (L, None, None),  # [q | k | v]: H d_k, H d_k, H d_v
        "w_a": (L, None, None),
        "w_b": (L, None, None),
        "w_g_t": (L, None, None),  # (H d_v, E): transposed, see init_params
        "conv": (L, None, None),   # (K, 2 H d_k + H d_v), tap 0 = current
        "a_log": (L, None),
        "dt_bias": (L, None),
        "o_norm": (L, None),
        "wo": (L, None, None),
    }


# Random stand-in weights: the decay, and how much a GDN mixer weighs.
# ``dt_bias`` is the inverse softplus of a step drawn log-uniformly a head
# and layer (``dt_range``: KDA's 0.001 to 0.1), as state-space models
# initialise theirs, and ``A`` = STANDIN_A every head: with W_a x of unit
# size a head forgets in ~1 / (1.6 step A) tokens, 400 to 40,000 over the
# heads, so the cell's contexts (to 1,024) and the probe's (3,136) lie
# inside what every head remembers and what the state holds reaches the
# logits. A decay so strong that a state a few rows old is zero would leave
# the mixer a 4-row convolution. Why slower than Falcon-H1's 40 to 4,000: a
# delta rule whose writes are as large as what it holds (beta ~ 1)
# overwrites its state in ~d_k / beta tokens whatever the decay, so rounding
# the STATE alone to bfloat16 costs no more than the bfloat16 rows that are
# written into it do: at A = 1 / 4 the benchmark's control with the state
# alone in bfloat16 read 0.059 / 0.0155 against the sound runs' 0.049 /
# 0.0127 on the chip. What tells a bfloat16 recurrence apart is the DECAY:
# exp(g) of a slow head lies within 2^-9 of 1 and rounds to 1.
# STANDIN_MIXER_GAIN: the norm after a GDN mixer weighs that much over the
# stack's other norms after sublayers (models/llama.py ``init_params``). At
# equal weight the control read AT the limits (0.115 / 0.0314 and 0.144 /
# 0.0291 against 0.15 / 0.03, twice the sound runs' 0.05 / 0.014): a limit
# wants room on both sides, and three of four mixers here are GDN, whose
# state is all that carries order (nothing is rotated). PERF.md section 6,
# PR 57, has the readings.
STANDIN_A = 1 / 64
STANDIN_MIXER_GAIN = 1.4


def init_params(cfg: ModelConfig, key: jax.Array, normal, out: int,
                dt_range: tuple) -> dict:
    """``normal(key, shape, fan_in)``; ``out``: what the fan-in of the
    matrices that write into the residual stream is multiplied by
    (models/llama.py HYBRID_INIT)."""
    E, H, K = cfg.hidden_size, cfg.gdn_heads, cfg.gdn_conv
    dv, cd, n = cfg.gdn_value_dim, cfg.gdn_conv_dim, cfg.count_layers("gdn")
    ks = jax.random.split(key, 7)
    step = jnp.exp(jax.random.uniform(
        ks[0], (n, H), F32, jnp.log(dt_range[0]), jnp.log(dt_range[1])))
    return {
        "w_qkv": normal(ks[1], (n, E, cd), E),
        "w_a": normal(ks[2], (n, E, H), E),
        "w_b": normal(ks[3], (n, E, H), E),
        # W_g lies transposed, (H d_v, E), the order of bytes the TPU
        # compiler wants for a decode step's 64 rows: handed (E, H d_v) it
        # copied the whole stack, 531 MB, at the start of every step (the
        # compiler's own count, tests/test_kernel_names_v5e.py; W_q, W_k,
        # W_v as one (E, 11,520) matrix it reads as they lie)
        "w_g_t": normal(ks[4], (n, H * dv, E), E),
        "conv": normal(ks[5], (n, K, cd), K),
        "a_log": jnp.full((n, H), np.log(STANDIN_A), F32),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
        "o_norm": jnp.ones((n, dv), cfg.jax_dtype),
        "wo": normal(ks[6], (n, H * dv, E), H * dv * out),
    }


def recur_dense(cfg: ModelConfig, conv_w, qkv, g, beta, caches, idx, *,
                neg_eigval):
    """A GDN layer over whole sequences, qkv (B, T, 2 H d_k + H d_v), from
    an empty past: no cache is read or written (the dense forward, and the
    definition the cached forms are held against)."""
    q, k, v = gdn.split_heads(kda.conv_dense(qkv, conv_w), cfg.gdn_heads,
                              cfg.gdn_key_dim)
    return gdn.recurrence_dense(
        *kda.prepare(q, k, v, g, beta, neg_eigval)), caches


def gated_norm(o: jnp.ndarray, gate: jnp.ndarray, weight: jnp.ndarray,
               eps: float) -> jnp.ndarray:
    """RMSNorm over a head's d_v values, its weight, THEN ``silu(gate)``,
    float32."""
    return rms_norm(o.astype(F32), weight.astype(F32), eps) * jax.nn.silu(
        gate.astype(F32))


def gdn_mixer(cfg: ModelConfig, gp: dict, x: jnp.ndarray, recur, caches,
              idx) -> Tuple[jnp.ndarray, Any]:
    """One Gated DeltaNet layer on the stream as it is: projections, the
    scalar decay and beta, the stateful part (``recur``, a ``RecurFn`` as a
    KDA layer's: short convolution, SiLU, L2 norm and the recurrence;
    ``g`` handed over as (..., T, H, 1)), the gated norm, W_o."""
    qkv = quant_einsum("...te,ef->...tf", x, gp["w_qkv"])
    g = -jnp.exp(gp["a_log"].astype(F32)) * jax.nn.softplus(
        jnp.einsum("...te,eh->...th", x, gp["w_a"]).astype(F32)
        + gp["dt_bias"].astype(F32))
    beta = jax.nn.sigmoid(
        jnp.einsum("...te,eh->...th", x, gp["w_b"]).astype(F32))
    o, caches = recur(gp["conv"], qkv, g[..., None], beta, caches, idx,
                      neg_eigval=cfg.gdn_neg_eigval)
    gate = quant_einsum("...te,fe->...tf", x, gp["w_g_t"])
    o = gated_norm(o, gate.reshape(o.shape), gp["o_norm"], cfg.rms_norm_eps)
    o = o.reshape(*o.shape[:-2], -1).astype(x.dtype)
    return quant_einsum("...tf,fe->...te", o, gp["wo"]), caches
