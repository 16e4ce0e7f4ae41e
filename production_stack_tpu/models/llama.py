"""Llama-family decoder as functional JAX.

Design (TPU-first, not a torch port):

- Parameters are a plain pytree of ``jnp`` arrays; decoder layers are
  *stacked* along a leading L axis and iterated with ``lax.scan`` — one trace
  regardless of depth, so a 80-layer 70B compiles as fast as a 2-layer test
  model.
- Every parameter has a *logical axes* annotation (see
  ``parallel/shardings.py``); pjit + GSPMD insert the tensor-parallel
  collectives over ICI. No NCCL, no manual all-reduce.
- Attention is injected as a callback so the same layer stack serves three
  paths: dense whole-prompt forward (tests/graft entry), ragged chunked
  prefill against the paged KV cache, and single-token paged decode.

Reference parity: the reference stack has no model code (it shells out to
vLLM, SURVEY.md §7 step 1); this module is the TPU-native bottom layer the
reference assumes exists.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.engine.quant import (
    as_stored,
    embed_lookup,
    head_from_embed,
    is_quantized,
    quant_einsum,
    ragged_quant_dot,
)
from production_stack_tpu.models import falcon_h1, olmo_hybrid, sambay
from production_stack_tpu.ops import kda
from production_stack_tpu.ops.attention import dense_causal_attention
from production_stack_tpu.ops.norms import layer_norm, rms_norm
from production_stack_tpu.ops.rope import apply_rope
from production_stack_tpu.ops.scalars import over, times
from production_stack_tpu.parallel import shardings as lax_names

# AttendFn: (q, k, v, layer_cache, layer_idx) -> (attn_out, new_layer_cache);
# keywords it must take or drop: ``kind`` from a stack whose attention
# layers differ (models/sambay.py), ``expand`` from a latent layer
# (_mla_mixer)
AttendFn = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray, Any, jnp.ndarray], Tuple[jnp.ndarray, Any]]
# RecurFn, a recurrent (KDA) layer's stateful call, as AttendFn is an
# attention layer's: (conv taps (K, 3*H*D), the projected rows
# (..., T, 3*H*D) = [q | k | v], log-decay g (..., T, H, D), beta
# (..., T, H), caches, index among the KDA layers) -> (o (..., T, H, D)
# float32, new caches). ``caches`` is the hybrid
# stack's whole cache pytree {"kv", "state", "conv"} (engine/kv_cache.py)
RecurFn = Callable[..., Tuple[jnp.ndarray, Any]]
# GroupedMatmulFn, the MoE block's grouped matmul on unquantized experts:
# (rows (M, K) sorted by group, matrices (G, K, N), group sizes (G,)) ->
# (M, N); None leaves it to jax.lax.ragged_dot (engine/quant.py
# ragged_quant_dot)
GroupedMatmulFn = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray],
                           jnp.ndarray]


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------

def _latent_layer_specs(cfg: ModelConfig, sparse: bool) -> dict:
    """One stack of latent-attention (MLA) layers: the mixer's two low-rank
    paths with their norms, the four norms around the sublayers, and the
    sparse block or a dense MLP."""
    L = lax_names
    layer = {
        "attn_norm": (L.LAYERS, L.EMBED),
        "wq_a": (L.LAYERS, L.EMBED, None),
        "q_a_norm": (L.LAYERS, None),
        # W_qb and W_kvb split by what each part feeds, so that no step
        # slices a stacked matrix: the unrotated and the rotated columns of
        # the heads' queries, plain matrices (rank, H * d); a head's key
        # and value expansions of the latent, head-major (H, rank, d), as
        # the matmuls batched over heads read them (laid out otherwise the
        # TPU compiler copied ~100 MB of them a layer and step)
        "wq_nope": (L.LAYERS, None, L.HEADS),
        "wq_rope": (L.LAYERS, None, L.HEADS),
        "wkv_a": (L.LAYERS, L.EMBED, None),
        "kv_a_norm": (L.LAYERS, None),
        "w_uk": (L.LAYERS, L.HEADS, None, L.HEAD_DIM),
        "w_uv": (L.LAYERS, L.HEADS, None, L.HEAD_DIM),
        "wo": (L.LAYERS, L.HEADS, L.HEAD_DIM, L.EMBED),
        "post_attn_norm": (L.LAYERS, L.EMBED),
        "mlp_norm": (L.LAYERS, L.EMBED),
        "post_mlp_norm": (L.LAYERS, L.EMBED),
    }
    if not sparse:
        layer.update({
            "w_gate": (L.LAYERS, L.EMBED, L.MLP),
            "w_up": (L.LAYERS, L.EMBED, L.MLP),
            "w_down": (L.LAYERS, L.MLP, L.EMBED),
        })
        return layer
    layer.update({
        "router_bias": (L.LAYERS, L.EXPERTS),
        "shared_gate": (L.LAYERS, L.EMBED, L.MLP),
        "shared_up": (L.LAYERS, L.EMBED, L.MLP),
        "shared_down": (L.LAYERS, L.MLP, L.EMBED),
        "router": (L.LAYERS, L.EMBED, L.EXPERTS),
        "w_gate": (L.LAYERS, L.EXPERTS, L.EMBED, L.MLP),
        "w_up": (L.LAYERS, L.EXPERTS, L.EMBED, L.MLP),
        "w_down": (L.LAYERS, L.EXPERTS, L.MLP, L.EMBED),
    })
    return layer


def _kda_specs() -> dict:
    """The KDA mixers' stack, (KDA layers, ...)."""
    L = lax_names
    return {
        "w_qkv": (L.LAYERS, L.EMBED, None),  # [q | k | v], each H*D
        "wo": (L.LAYERS, L.HEADS, L.HEAD_DIM, L.EMBED),
        "conv": (L.LAYERS, None, None),  # (K, 3*H*D), tap 0 = current
        "a_log": (L.LAYERS, L.HEADS),
        "dt_bias": (L.LAYERS, L.HEADS, L.HEAD_DIM),
        "f_down": (L.LAYERS, L.EMBED, None),
        "f_up": (L.LAYERS, None, L.HEADS, L.HEAD_DIM),
        "w_beta": (L.LAYERS, L.EMBED, L.HEADS),
        "g_down": (L.LAYERS, L.EMBED, None),
        "g_up": (L.LAYERS, None, L.HEADS, L.HEAD_DIM),
        "o_norm": (L.LAYERS, L.HEAD_DIM),
    }


def param_specs(cfg: ModelConfig) -> dict:
    """Pytree of logical-axes tuples mirroring the param pytree, as
    ``init_params`` makes it and a checkpoint loads into it. Which leaves a
    runner then keeps in another order of bytes, and why, is
    ``param_layouts``: the attention projections from the embedding onto
    heads (``wq``, ``wk``, ``wv``; a hybrid stack's ``gqa.wq``, ``wg``)."""
    L = lax_names
    if cfg.is_latent and not cfg.has_recurrent_state:
        # the leading dense layers are a stack of their own, "dense",
        # before the expert layers' "layers"
        specs = {
            "embed": (L.VOCAB, L.EMBED),
            "layers": _latent_layer_specs(cfg, sparse=True),
            "final_norm": (L.EMBED,),
        }
        if cfg.dense_layers:
            specs["dense"] = _latent_layer_specs(cfg, sparse=False)
        if not cfg.tie_word_embeddings:
            specs["lm_head"] = (L.EMBED, L.VOCAB)
        return specs
    layer = {
        "attn_norm": (L.LAYERS, L.EMBED),
        "wq": (L.LAYERS, L.EMBED, L.HEADS, L.HEAD_DIM),
        "wk": (L.LAYERS, L.EMBED, L.KV_HEADS, L.HEAD_DIM),
        "wv": (L.LAYERS, L.EMBED, L.KV_HEADS, L.HEAD_DIM),
        "wo": (L.LAYERS, L.HEADS, L.HEAD_DIM, L.EMBED),
        "mlp_norm": (L.LAYERS, L.EMBED),
    }
    if cfg.qkv_bias:  # Qwen2 family
        layer.update(
            {
                "bq": (L.LAYERS, L.HEADS, L.HEAD_DIM),
                "bk": (L.LAYERS, L.KV_HEADS, L.HEAD_DIM),
                "bv": (L.LAYERS, L.KV_HEADS, L.HEAD_DIM),
            }
        )
    if cfg.qk_norm and cfg.qk_norm_kind == "full":
        # OLMoE: RMSNorm over the whole projected q / k vector; the weight
        # (H*D,) is kept as (H, D) so that it shards with the heads
        layer.update(
            {
                "q_norm": (L.LAYERS, L.HEADS, L.HEAD_DIM),
                "k_norm": (L.LAYERS, L.KV_HEADS, L.HEAD_DIM),
            }
        )
    elif cfg.qk_norm:  # Qwen3 family: per-head q/k RMSNorm over head_dim
        layer.update(
            {
                "q_norm": (L.LAYERS, L.HEAD_DIM),
                "k_norm": (L.LAYERS, L.HEAD_DIM),
            }
        )
    if cfg.post_norms:  # Gemma-2: norms on the attn/MLP outputs too
        layer.update(
            {
                "post_attn_norm": (L.LAYERS, L.EMBED),
                "post_mlp_norm": (L.LAYERS, L.EMBED),
            }
        )
    if not cfg.pre_norms:  # the Olmo 3 block: those two alone
        del layer["attn_norm"], layer["mlp_norm"]
    mixers = {}
    if cfg.mamba_period:
        # every block's norms (LayerNorm: a bias each) and MLP stay in
        # "layers"; the mixers are stacked by kind (models/sambay.py)
        layer = {k: layer[k] for k in ("attn_norm", "mlp_norm")}
        layer.update({"attn_norm_b": (L.LAYERS, L.EMBED),
                      "mlp_norm_b": (L.LAYERS, L.EMBED)})
        mixers = sambay.param_specs(cfg)
    elif cfg.ssd_heads:
        # every layer has both mixers, each a stack over the layers
        # (models/falcon_h1.py); norms and the MLP stay in "layers"
        for k in ("wq", "wk", "wv", "wo"):
            del layer[k]
        mixers = falcon_h1.param_specs(cfg)
    elif cfg.patterned:
        # the token mixers differ by layer kind and are stacked by kind:
        # "gqa" over the periods (over every layer where all are attention
        # and differ in what a row sees: cfg.window_layers), "kda" over
        # the KDA layers; what every
        # layer has (norms, the sparse block) stays in "layers"
        # projections onto all heads at once are kept as plain matrices,
        # (E, H*D): handed a (E, H, D) stack indexed by layer, the TPU
        # compiler chose a per-head layout for it and copied the whole
        # stack at the start of every decode step (5 copies, 1.5 GB, at
        # the published widths; PR 34). That cured the KDA stacks; the
        # attention's (E, H*D) stacks were still copied (to E minor-most:
        # ``wq`` and ``wg`` 268 MB a step) until a runner kept them so
        # (``param_layouts``, PR 53)
        attn = {k: layer.pop(k) for k in ("wq", "wk", "wv", "wo")}
        if cfg.is_latent:
            # "mla" over the latent-attention layers: _latent_layer_specs'
            # mixer with one direct query projection (cfg.q_lora_rank 0)
            latent = _latent_layer_specs(cfg, sparse=False)
            mixers["mla"] = {
                **{k: latent[k] for k in (
                    "wkv_a", "kv_a_norm", "w_uk", "w_uv", "wo")},
                "wq_nope": (L.LAYERS, L.EMBED, L.HEADS),
                "wq_rope": (L.LAYERS, L.EMBED, L.HEADS),
            }
        else:
            mixers["gqa"] = {**attn, "wq": (L.LAYERS, L.EMBED, L.HEADS)}
            if cfg.qk_norm:  # of the kind made above
                mixers["gqa"].update(
                    {k: layer.pop(k) for k in ("q_norm", "k_norm")})
        if cfg.gdn_heads:
            mixers["gdn"] = olmo_hybrid.param_specs(cfg)
        elif cfg.kda_heads:
            mixers["kda"] = _kda_specs()
        if cfg.attn_gate:
            mixers["gqa"]["wg"] = (L.LAYERS, L.EMBED, L.HEADS)
        if cfg.dense_layers:
            # a leading dense layer's norms and MLP, a stack of their own
            # before the expert layers' "layers"; its mixer lies with its
            # kind's
            mixers["dense"] = {
                "attn_norm": (L.LAYERS, L.EMBED),
                "mlp_norm": (L.LAYERS, L.EMBED),
                "w_gate": (L.LAYERS, L.EMBED, L.MLP),
                "w_up": (L.LAYERS, L.EMBED, L.MLP),
                "w_down": (L.LAYERS, L.MLP, L.EMBED),
            }
            if cfg.post_norms:
                mixers["dense"].update({
                    "post_attn_norm": (L.LAYERS, L.EMBED),
                    "post_mlp_norm": (L.LAYERS, L.EMBED)})
    if cfg.moe_scoring == "sigmoid":
        layer["router_bias"] = (L.LAYERS, L.EXPERTS)
    if cfg.shared_expert_size:
        layer.update(
            {
                "shared_gate": (L.LAYERS, L.EMBED, L.MLP),
                "shared_up": (L.LAYERS, L.EMBED, L.MLP),
                "shared_down": (L.LAYERS, L.MLP, L.EMBED),
            }
        )
    if cfg.is_moe:
        layer.update(
            {
                "router": (L.LAYERS, L.EMBED, L.EXPERTS),
                "w_gate": (L.LAYERS, L.EXPERTS, L.EMBED, L.MLP),
                "w_up": (L.LAYERS, L.EXPERTS, L.EMBED, L.MLP),
                "w_down": (L.LAYERS, L.EXPERTS, L.MLP, L.EMBED),
            }
        )
    else:
        layer.update(
            {
                "w_gate": (L.LAYERS, L.EMBED, L.MLP),
                "w_up": (L.LAYERS, L.EMBED, L.MLP),
                "w_down": (L.LAYERS, L.MLP, L.EMBED),
            }
        )
    specs = {
        "embed": (L.VOCAB, L.EMBED),
        "layers": layer,
        **mixers,
        "final_norm": (L.EMBED,),
    }
    if cfg.layer_norm:
        specs["final_norm_b"] = (L.EMBED,)
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = (L.EMBED, L.VOCAB)
    return specs


# the projections from the embedding onto heads, by stack: the leaves a
# decode step's 64 rows multiply whole, a layer at a time
_ATTN_PROJECTIONS = {"layers": ("wq", "wk", "wv"),
                     "gqa": ("wq", "wk", "wv", "wg")}


def param_layouts(cfg: ModelConfig) -> dict:
    """Pytree shaped like ``param_specs(cfg)``: the order of its axes,
    major to minor, in which a runner keeps a leaf on the device, or None
    for the order ``init_params`` makes it in. ``engine/weights.py``
    ``lay_out`` turns a loaded tree into the kept one: a leaf ``w`` with an
    order becomes ``w_t``, transposed to it, the axes between the stack's
    and the last as one: ``wq`` (L, E, H, D) -> ``wq_t`` (L, H * D, E), the
    form Falcon-H1's stacks are made in. ``init_params``, the checkpoint
    loaders and whoever reads their tree by shape (the benchmark's
    references) keep the logical tree; the forward takes either
    (``_onto_heads``, ``_forward_hybrid``'s ``gqa``).

    One rule: a stack of projections from the embedding onto heads lies
    with its contracted axis (EMBED, found in the leaf's spec) minor-most.
    That is how the TPU compiler wants a matrix that a decode step's 64
    rows multiply; handed the stack in another order it copied it WHOLE at
    the start of every step, outside the layer scan: W_q, W_k and W_v of
    Qwen3's sixteen layers 805 MB a step, Ouro's 1.2 GB (in its 512-wide
    ragged program too), OLMoE's 201 MB, Solar-Open2's W_q and W_g 268 MB,
    Phi-4-mini-flash's 328 MB; the ragged programs re-laid a layer's slice
    inside the scan (PERF.md section 5, PR 53). Why a stored transpose and
    not ``jax.experimental.layout``: an array made or placed with a
    ``Format`` by a program that jax 0.9.0 loads from its persistent
    compile cache comes back DESCRIBED row-major while its bytes are not,
    and every program that then reads it computes on the wrong values (my
    chip runs, PR 53). The sub-modules answer for their own stacks."""
    L = lax_names
    specs = param_specs(cfg)
    layouts = jax.tree.map(lambda _: None, specs,
                           is_leaf=lambda x: isinstance(x, tuple))
    for stack, names in _ATTN_PROJECTIONS.items():
        for name in names:
            axes = specs.get(stack, {}).get(name)
            if axes is not None and L.EMBED in axes:
                e = axes.index(L.EMBED)
                layouts[stack][name] = (
                    *(i for i in range(len(axes)) if i != e), e)
    if cfg.mamba_period:
        layouts.update(sambay.param_layouts(cfg))
    return layouts


# Random stand-in weights of a looped stack (cfg.loop_passes > 1): the gain
# of the norms AFTER each sublayer. Every pass renormalises the stream to
# unit size and then adds 2 x num_layers sublayer outputs to it; at gain 1
# each of those is as large as the stream, and a random stack iterated on
# its own output is then a chaotic map (measured: four passes multiply a
# bf16 rounding error ~2.4x a pass, past any tolerance that still tells a
# fault from rounding; PERF.md section 6, PR 31). At 0.05 a 48-layer pass
# still moves the stream by half its size (sqrt(96) x 0.05), so a pass
# left out or fed the wrong cache reads three times over the benchmark's
# limits (tests/test_ouro.py), and the iteration is stable, as a trained
# looped model's is under its own: bf16 reads 0.03-0.04 / 0.009 against
# the float32 reference on the chip, under half of them.
LOOPED_POST_NORM_GAIN = 0.05

# Random stand-in weights of a sigmoid router: the standard deviation of the
# selection bias, by architecture (0: zeros, a router whose balancing never
# ran). A trained afmoe router carries a nonzero one; at 0.02, a step or two
# of the spacing of the top sigmoid scores of 256, the experts chosen by
# score + bias differ from the top scores' in a share of the rows, so that a
# bias that weighed as well as chose reads in the log-probabilities
# (tests/test_afmoe.py), on the chip's probe as in the tests.
STANDIN_ROUTER_BIAS = {"afmoe": 0.02}

# Random stand-in weights of a stack whose attention layers differ by kind
# (``cfg.window_layers``): the norm after an attention sublayer weighs this
# many times the norm after an MLP (0.25 beside 0.0625 at eight layers), so
# that the attention sublayers together move the stream by 0.7 of the
# embedding's size. At one gain for both, 0.0625, all sixteen sublayers were
# a quarter of the stream and a reference whose window layers saw every row
# read 0.153 / 0.041 against the chip's served 6,208-token probe, over
# chipbench/run.py's 0.15 by a hair; at 4 x it reads 0.58-0.70 / 0.12-0.14
# while the bfloat16 path's own reading stays where it was (0.03-0.045 /
# 0.007: it comes from the stream's and the head's rounding, not from the
# attention sublayers' size). The MLP's norm stays small for the latent
# stacks' reason (a near-tied expert picked the other way turns the sparse
# block's output: at 0.25 for both, the reference with bfloat16 activations
# read up to 0.116 on one row). PERF.md section 6, PR 59.
STANDIN_WINDOW_ATTN_GAIN = 4.0


# Random stand-in weights of a latent-attention stack with norms after
# each sublayer: the gain of those norms, and an embedding of unit RMS. At
# the shared stack's values (gain 1, embedding rows of RMS hidden^-1/2)
# the first sublayer's output buries the embedding and every later one is
# as large as the stream it joins, as HYBRID_INIT found of a stack without
# such norms: the served bf16 path then read 0.21-0.46 / 0.012-0.017
# against the float32 reference (CPU, width 512; limits 0.15 / 0.03). A
# post norm fixes its sublayer's size at the gain whatever the matrices
# are, so the gain is what HYBRID_INIT's 1 / sqrt(2 x layers) on the
# writing matrices is there. It is set by the sparse block: a routed
# expert weighs routed_scaling / top_k = 0.31 of the shared one here
# (Solar-Open2: 0.125), so where bf16 rounding picks the other of two
# near-tied experts the block's output turns by a third, and the norm
# behind it hands the stream that third at the gain's size whatever the
# experts' matrices are. With the 2 x layers sublayers together moving
# the stream by half its size (gain 0.158 at five layers) one position of
# a probe read up to 0.142 on the chip (six seeds 0.063-0.142, mean
# 0.0062-0.0076); by a quarter of its size it reads half that (PERF.md
# section 6, PR 43).


def _latent_post_norm_gain(cfg: ModelConfig) -> float:
    return 0.25 * (2 * cfg.num_layers) ** -0.5


def _init_latent(cfg: ModelConfig, key: jax.Array) -> dict:
    E, H, V = cfg.hidden_size, cfg.num_heads, cfg.vocab_size
    Rq, C = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    dt = cfg.jax_dtype
    gain = _latent_post_norm_gain(cfg)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    def stack(k, n, sparse):
        ks = jax.random.split(k, 16)
        layers = {
            "attn_norm": jnp.ones((n, E), dt),
            "wq_a": normal(ks[0], (n, E, Rq), E),
            "q_a_norm": jnp.ones((n, Rq), dt),
            "wq_nope": normal(ks[1], (n, Rq, H * nope), Rq),
            "wq_rope": normal(ks[2], (n, Rq, H * rope), Rq),
            "wkv_a": normal(ks[3], (n, E, C + rope), E),
            "kv_a_norm": jnp.ones((n, C), dt),
            "w_uk": normal(ks[4], (n, H, C, nope), C),
            "w_uv": normal(ks[5], (n, H, C, vd), C),
            "wo": normal(ks[6], (n, H, vd, E), H * vd),
            "post_attn_norm": jnp.full((n, E), gain, dt),
            "mlp_norm": jnp.ones((n, E), dt),
            "post_mlp_norm": jnp.full((n, E), gain, dt),
        }
        if not sparse:
            Fd = cfg.dense_intermediate_size
            layers.update({
                "w_gate": normal(ks[7], (n, E, Fd), E),
                "w_up": normal(ks[8], (n, E, Fd), E),
                "w_down": normal(ks[9], (n, Fd, E), Fd),
            })
            return layers
        F, Fs = cfg.intermediate_size, cfg.shared_expert_size
        X, Xh = cfg.num_experts, cfg.num_held_experts
        layers.update({
            # no selection bias is published for this family: zeros
            "router_bias": jnp.zeros((n, X), jnp.float32),
            "shared_gate": normal(ks[10], (n, E, Fs), E),
            "shared_up": normal(ks[11], (n, E, Fs), E),
            "shared_down": normal(ks[12], (n, Fs, E), Fs),
            "router": normal(ks[13], (n, E, X), E),
            "w_gate": normal(ks[7], (n, Xh, E, F), E),
            "w_up": normal(ks[8], (n, Xh, E, F), E),
            "w_down": normal(ks[9], (n, Xh, F, E), F),
        })
        return layers

    keys = jax.random.split(key, 4)
    params = {
        "embed": normal(keys[0], (V, E), 1),
        "layers": stack(keys[1], cfg.num_expert_layers, True),
        "final_norm": jnp.ones((E,), dt),
    }
    if cfg.dense_layers:
        params["dense"] = stack(keys[2], cfg.dense_layers, False)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = normal(keys[3], (E, V), E)
    return params


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """Random-init parameters (tests / synthetic benchmarks; real weights come
    from safetensors via engine/weights.py)."""
    if cfg.is_latent and not cfg.has_recurrent_state:
        return _init_latent(cfg, key)
    E, H, KH, D, F, LN, V = (
        cfg.hidden_size,
        cfg.num_heads,
        cfg.num_kv_heads,
        cfg.head_dim,
        cfg.intermediate_size,
        cfg.num_layers,
        cfg.vocab_size,
    )
    dt = cfg.jax_dtype
    keys = jax.random.split(key, 16)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * (fan_in**-0.5)).astype(dt)

    # a hybrid stack's stand-in: see HYBRID_INIT (a SambaY stack's:
    # SAMBAY_INIT)
    hybrid = cfg.patterned
    out = 2 * LN if hybrid else 1  # x the fan-in of what writes the stream

    # stored norm weight giving an effective scale of 1 (Gemma stores
    # zero-centred weights; forward adds cfg.norm_offset)
    norm_one = 1.0 - cfg.norm_offset
    # "layers" of a patterned stack with leading dense layers holds the
    # expert layers alone (no other family here has dense layers)
    # (a patterned stack's attention mixers are a stack of their own, of
    # which there are more than expert layers where every layer is
    # attention and the first is dense: La)
    Ln = cfg.num_expert_layers
    La = max(Ln, cfg.num_attn_layers) if hybrid else Ln
    layers = {
        "attn_norm": jnp.full((Ln, E), norm_one, dt),
        "wq": normal(keys[0], (La, E, H, D), E),
        "wk": normal(keys[1], (La, E, KH, D), E),
        "wv": normal(keys[2], (La, E, KH, D), E),
        "wo": normal(keys[3], (La, H, D, E), H * D * out),
        "mlp_norm": jnp.full((Ln, E), norm_one, dt),
    }
    if cfg.qkv_bias:
        layers.update(
            {
                "bq": normal(keys[10], (Ln, H, D), E),
                "bk": normal(keys[11], (Ln, KH, D), E),
                "bv": normal(keys[12], (Ln, KH, D), E),
            }
        )
    if cfg.qk_norm:
        full = cfg.qk_norm_kind == "full"
        layers.update(
            {
                "q_norm": jnp.full((La, H, D) if full else (La, D),
                                   norm_one, dt),
                "k_norm": jnp.full((La, KH, D) if full else (La, D),
                                   norm_one, dt),
            }
        )
    if cfg.post_norms:
        # Gemma stores zero-centred norm weights (forward adds norm_offset)
        # (a patterned stack with norms on both sides: the latent stacks'
        # gain, for the latent stacks' reason, a sparse block behind a norm)
        gain = (LOOPED_POST_NORM_GAIN if cfg.loop_passes > 1
                else out ** -0.5 if not cfg.pre_norms
                else _latent_post_norm_gain(cfg) if hybrid else 1.0)
        # (where the attention layers differ by kind: STANDIN_WINDOW_ATTN_GAIN)
        post_gains = {
            "post_attn_norm": gain * (STANDIN_WINDOW_ATTN_GAIN
                                      if cfg.window_layers else 1.0),
            "post_mlp_norm": gain}
        layers.update({k: jnp.full((Ln, E), g - cfg.norm_offset, dt)
                       for k, g in post_gains.items()})
    if not cfg.pre_norms:
        del layers["attn_norm"], layers["mlp_norm"]
    mixers = {}
    if cfg.mamba_period:
        layers = {k: layers[k] for k in ("attn_norm", "mlp_norm")}
        layers.update({"attn_norm_b": jnp.zeros((Ln, E), dt),
                       "mlp_norm_b": jnp.zeros((Ln, E), dt)})
        mixers = sambay.init_params(cfg, keys[13], normal, out,
                                    (KDA_DT_MIN, KDA_DT_MAX))
        # SAMBAY_INIT: block 0 writes at the usual size
        mixers["mamba"]["w_out"] = _first_unscaled(
            mixers["mamba"]["w_out"], out)
    elif cfg.ssd_heads:
        for k in ("wq", "wk", "wv", "wo"):
            del layers[k]
        mixers = falcon_h1.init_params(cfg, keys[13], normal, out,
                                       (KDA_DT_MIN, KDA_DT_MAX))
    elif hybrid:
        Pn = cfg.num_attn_layers
        attn = {k: layers.pop(k) for k in ("wq", "wk", "wv", "wo")}
        if cfg.is_latent:
            mixers["mla"] = _init_mla(cfg, keys[14], normal, out)
        else:
            mixers["gqa"] = {k: v[:Pn] for k, v in attn.items()}
            mixers["gqa"]["wq"] = mixers["gqa"]["wq"].reshape(Pn, E, H * D)
            if cfg.qk_norm:
                mixers["gqa"].update({k: layers.pop(k)[:Pn]
                                      for k in ("q_norm", "k_norm")})
        if cfg.gdn_heads:
            mixers["gdn"] = olmo_hybrid.init_params(
                cfg, keys[13], normal, out, (KDA_DT_MIN, KDA_DT_MAX))
            # the norm after a GDN mixer weighs more (STANDIN_MIXER_GAIN)
            is_gdn = jnp.asarray([k == "gdn" for k in cfg.layer_kinds])
            layers["post_attn_norm"] = (
                layers["post_attn_norm"].astype(jnp.float32) * jnp.where(
                    is_gdn, olmo_hybrid.STANDIN_MIXER_GAIN, 1.0)[:, None]
            ).astype(dt)
        elif cfg.kda_heads:
            mixers["kda"] = _init_kda(cfg, keys[13], normal, out)
        if cfg.attn_gate:
            mixers["gqa"]["wg"] = normal(keys[14], (Pn, E, H * D), E)
        if cfg.dense_layers:
            Dn, Fd = cfg.dense_layers, cfg.dense_intermediate_size
            ks = jax.random.split(keys[12], 3)
            mixers["dense"] = {
                "attn_norm": jnp.ones((Dn, E), dt),
                "mlp_norm": jnp.ones((Dn, E), dt),
                "w_gate": normal(ks[0], (Dn, E, Fd), E),
                "w_up": normal(ks[1], (Dn, E, Fd), E),
                "w_down": normal(ks[2], (Dn, Fd, E), Fd * out),
            }
            if cfg.post_norms:
                mixers["dense"].update({
                    k: jnp.full((Dn, E), g - cfg.norm_offset, dt)
                    for k, g in post_gains.items()})
    if cfg.moe_scoring == "sigmoid":
        # the selection bias (balancing state of a trained router): zeros,
        # but for the family whose stand-in draws it (STANDIN_ROUTER_BIAS)
        std = STANDIN_ROUTER_BIAS.get(cfg.architecture)
        layers["router_bias"] = (
            std * jax.random.normal(jax.random.fold_in(keys[4], 1),
                                    (Ln, cfg.num_experts), jnp.float32)
            if std else jnp.zeros((Ln, cfg.num_experts), jnp.float32))
    if cfg.shared_expert_size:
        Fs = cfg.shared_expert_size
        ks = jax.random.split(keys[15], 3)
        layers.update(
            {
                "shared_gate": normal(ks[0], (Ln, E, Fs), E),
                "shared_up": normal(ks[1], (Ln, E, Fs), E),
                "shared_down": normal(ks[2], (Ln, Fs, E), Fs * out),
            }
        )
    if cfg.is_moe:
        # the experts held here of num_experts routed over
        X, Xh = cfg.num_experts, cfg.num_held_experts
        layers.update(
            {
                "router": normal(keys[4], (Ln, E, X), E),
                "w_gate": normal(keys[5], (Ln, Xh, E, F), E),
                "w_up": normal(keys[6], (Ln, Xh, E, F), E),
                # HYBRID_INIT: a chosen expert weighs routed_scaling /
                # top_k of the shared one; the stand-in's routed experts
                # write at 1 / routed_scaling besides, so that it is
                # 1 / top_k whatever the family's factor
                "w_down": normal(keys[7], (Ln, Xh, F, E),
                                 F * out * (cfg.routed_scaling ** 2
                                            if hybrid else 1)),
            }
        )
    else:
        layers.update(
            {
                # ``over``: models/falcon_h1.py's one rule for a family
                # with published scalars (1, and nothing, for the others)
                "w_gate": over(normal(keys[5], (Ln, E, F), E),
                               cfg.mlp_gate_multiplier),
                "w_up": normal(keys[6], (Ln, E, F), E),
                "w_down": over(normal(keys[7], (Ln, F, E), F * out),
                               cfg.mlp_down_multiplier),
            }
        )
        if cfg.mamba_period:
            layers["w_down"] = _first_unscaled(layers["w_down"], out)
    params = {
        # (unit RMS as the stack reads it: a family that multiplies by
        # sqrt(E) draws rows of RMS E^-1/2)
        "embed": over(normal(keys[8], (V, E),
                             1 if hybrid and not (cfg.mamba_period
                                                  or cfg.embed_scale) else E),
                      cfg.embedding_multiplier),
        "layers": layers,
        **mixers,
        "final_norm": jnp.full((E,), norm_one, dt),
    }
    if cfg.layer_norm:
        params["final_norm_b"] = jnp.zeros((E,), dt)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = over(normal(keys[9], (E, V), E),
                                 cfg.lm_head_multiplier)
    return params


# Random stand-in weights of a KDA layer: the decay. A trained layer's
# per-channel decay rates spread over orders of magnitude; the stand-in
# draws ``dt`` log-uniformly in [DT_MIN, DT_MAX] per (head, channel), as
# state-space models initialise theirs, stores its inverse softplus as
# ``dt_bias`` and sets ``A_log`` 0, so that with the low-rank projection's
# unit-variance input alpha = exp(-softplus(z + dt_bias)) lies mostly in
# 0.9 to 0.999: a state that remembers tens to a thousand tokens, neither
# dead after a few rows nor (alpha < 1, |eig(I - beta k k^T)| <= 1) able
# to grow
KDA_DT_MIN, KDA_DT_MAX = 1e-3, 1e-1


# HYBRID_INIT. Random stand-in weights of a hybrid stack: how large what is
# added to the residual stream is beside what is in it. With the shared
# stack's values (embedding rows of RMS hidden^-1/2, every sublayer's
# output of RMS ~0.5) the first sublayer's output buries the embedding and
# each later one is as large as the stream it joins; with sixteen
# sublayers of routing, recurrences and gates in a row, a bf16 rounding of
# one of them then grows through the rest: the served bf16 path read 0.34 /
# 0.066 against the float32 reference on the chip (limits 0.15 / 0.03) and
# 0.37-0.42 / 0.07 on the CPU at width 512, where rounding ONE kind of
# sublayer alone still read 0.035 mean. No tolerance could then tell a
# fault from rounding (PERF.md section 6, PR 34; PR 31 met the same with a
# looped stack). So the stand-in is given what a trained stack has: an
# embedding of unit RMS, and matrices that write into the stream (W_o, the
# experts' and the shared expert's down projections) at 1 / sqrt(2 x
# layers) of the usual size, the residual scaling GPT-2 initialises with.
# At width 512 that reads 0.04-0.05 / 0.008. Every other family keeps the
# shared values: its programs and its cells are what they were. One more
# term since PR 49, general and 1 for Solar-Open2: the routed experts' down
# projections write at 1 / routed_scaling besides. A chosen expert weighs
# routed_scaling / top_k of the shared one, and where bf16 rounding picks
# the other of two near-tied experts (rank 8 and 9 of 256 lie ~0.06 apart
# in logit, the rounded stream moves a logit by a third of that) a whole
# expert's output comes or goes: at Kimi-Linear's 2.446 / 8 the probe's
# largest difference read 0.066 / 0.072 / 0.078 / 0.114 on four seeds on
# the chip (mean 0.012-0.015; limit 0.15) and 0.061-0.088 on the CPU at
# width 512, with the term 0.046-0.055 there: Solar-Open2's 1 / 8.
# Where a block's norms come AFTER its sublayers and none before
# (``cfg.norms`` "post", Olmo-Hybrid), a sublayer's size is its norm's gain
# whatever its matrices are, so the 1 / sqrt(2 x layers) goes on the gain of
# those norms (``init_params``), as ``_latent_post_norm_gain`` does.


# SAMBAY_INIT. Random stand-in weights of a SambaY stack, whose head is
# TIED: HYBRID_INIT's unit-RMS embedding would, as the head, score the
# final hidden row (which that embedding dominates) at sqrt(E) for the
# input token against ~1 for every other, so the stand-in would repeat its
# input whatever its layers compute and no fault in a layer would read in
# the log-probabilities (a state not carried across a chunk read 2e-4 on
# the CPU; the chip's probe 0.161 / 0.077). With the shared stack's values
# instead (embedding rows of RMS hidden^-1/2, every sublayer's output as
# large as the stream it joins) 64 sublayers in a row amplified bf16
# rounding: 0.161 / 0.039 on the chip against limits of 0.15 / 0.03, and
# 0.18-0.21 / 0.046-0.050 on the CPU at width 512, a float32 residual
# stream or none (PERF.md section 6, PR 45). So the stream's scale is set
# by BLOCK 0: its two writers (the Mamba W_out, the MLP's W_down) keep the
# usual size and bury the small embedding at once, and every later writer
# has HYBRID_INIT's 1 / sqrt(2 x layers): each later sublayer then adds
# ~7 % to the stream, as a trained stack's do, and nothing of the stream
# points at the input token's embedding row. At width 512 that reads
# 0.041 / 0.011.

def _first_unscaled(w: jnp.ndarray, out: int) -> jnp.ndarray:
    """A stack of writing matrices drawn at ``out`` x the fan-in, layer 0
    brought back to the fan-in itself."""
    scale = jnp.ones((w.shape[0],), jnp.float32).at[0].set(out ** 0.5)
    return (w.astype(jnp.float32) * scale[:, None, None]).astype(w.dtype)


def _init_mla(cfg: ModelConfig, key: jax.Array, normal, out: int) -> dict:
    """The latent-attention mixers of a patterned stack (HYBRID_INIT: W_o
    writes at 1 / sqrt(out) of the usual size), _init_latent's with one
    direct query projection."""
    E, H, C = cfg.hidden_size, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    n = cfg.count_layers("mla")
    ks = jax.random.split(key, 6)
    return {
        "wq_nope": normal(ks[0], (n, E, H * nope), E),
        "wq_rope": normal(ks[1], (n, E, H * rope), E),
        "wkv_a": normal(ks[2], (n, E, C + rope), E),
        "kv_a_norm": jnp.ones((n, C), cfg.jax_dtype),
        "w_uk": normal(ks[3], (n, H, C, nope), C),
        "w_uv": normal(ks[4], (n, H, C, vd), C),
        "wo": normal(ks[5], (n, H, vd, E), H * vd * out),
    }


def _init_kda(cfg: ModelConfig, key: jax.Array, normal, out: int) -> dict:
    E, H, D = cfg.hidden_size, cfg.kda_heads, cfg.kda_head_dim
    K, R, Lk = cfg.kda_conv, cfg.kda_rank, cfg.num_kda_layers
    ks = jax.random.split(key, 11)
    dt = jnp.exp(jax.random.uniform(
        ks[9], (Lk, H, D), jnp.float32, jnp.log(KDA_DT_MIN),
        jnp.log(KDA_DT_MAX)))
    return {
        "w_qkv": normal(ks[0], (Lk, E, 3 * H * D), E),
        "wo": normal(ks[3], (Lk, H, D, E), H * D * out),
        # depthwise taps over the [q | k | v] row; tap 0 is the current row
        "conv": normal(ks[4], (Lk, K, 3 * H * D), K),
        "a_log": jnp.zeros((Lk, H), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "f_down": normal(ks[5], (Lk, E, R), E),
        "f_up": normal(ks[6], (Lk, R, H, D), R),
        "w_beta": normal(ks[7], (Lk, E, H), E),
        "g_down": normal(ks[8], (Lk, E, R), E),
        "g_up": normal(ks[10], (Lk, R, H, D), R),
        "o_norm": jnp.ones((Lk, D), cfg.jax_dtype),
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _mlp(cfg: ModelConfig, lp: dict, x: jnp.ndarray, lb=None,
         onehot=None) -> jnp.ndarray:
    gate = quant_einsum("...te,ef->...tf", x, lp["w_gate"])
    up = quant_einsum("...te,ef->...tf", x, lp["w_up"])
    if lb is not None:
        if "w_gate" in lb:
            gate = gate + _lora_delta(x, onehot, *lb["w_gate"])
        if "w_up" in lb:
            up = up + _lora_delta(x, onehot, *lb["w_up"])
    hidden2 = _act(cfg)(times(gate, cfg.mlp_gate_multiplier)) * up
    out = quant_einsum("...tf,fe->...te", hidden2, lp["w_down"])
    if lb is not None and "w_down" in lb:
        out = out + _lora_delta(hidden2, onehot, *lb["w_down"])
    return times(out, cfg.mlp_down_multiplier)


def _act(cfg: ModelConfig):
    # Gemma is GeGLU (tanh-approx gelu on the gate); Llama/Qwen are SwiGLU
    return (jax.nn.silu if cfg.act == "silu"
            else functools.partial(jax.nn.gelu, approximate=True))


# the MoE block's expert matrices, (L, X, in, out) in the stacked layers
_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _moe_mlp(cfg: ModelConfig, router: jnp.ndarray, experts: dict,
             layer_idx, x: jnp.ndarray, live: Optional[jnp.ndarray] = None,
             bias: Optional[jnp.ndarray] = None, grouped_matmul=None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sparse MoE block (Mixtral, OLMoE), dropless: route, sort the
    (token, choice) pairs by expert, one grouped matmul per projection
    over the sorted rows, unsort and combine.

    Every pair is computed whatever the routing looks like: there is no
    capacity, so the block equals "every expert on every token, weighted
    by the routing weight" exactly. The grouped matmul is
    ``engine/quant.py`` ``ragged_quant_dot``'s: ``grouped_matmul`` where
    the runner hands one in (the Pallas kernel of
    ``ops/moe_grouped_matmul_pallas.py``, the instruction
    ``%moe_grouped_matmul`` of a device trace), else
    ``jax.lax.ragged_dot`` (on TPU XLA's own ``%ragged-dot`` Mosaic
    kernel, elsewhere a masked loop). Either way three a layer, each one
    (rows, width) array.

    ``router`` (E, X) is this layer's; ``experts`` holds the expert
    matrices of ALL the stack's layers, (L, X, in, out) each, and
    ``layer_idx`` says which layer's to use. The grouped matmul runs over
    the L * X groups of the whole stack with every other layer's group
    empty: it reads the experts where they lie. Handing it one layer's
    slice instead makes XLA copy that slice out first, 3 x 268 MB a layer
    at OLMoE's widths, as long again as the matmuls themselves (measured,
    PERF.md section 6, PR 26).

    Routing as published: router logits in float32, softmax over ALL
    experts, top-k of the probabilities; ``cfg.norm_topk_prob``
    renormalises the k chosen (Mixtral), OLMoE uses them as they are.
    With ``cfg.moe_scoring`` "sigmoid" the scores are sigmoids, the k are
    chosen by score + ``bias`` (X,) and weighed by the score alone, then
    renormalised and scaled by ``cfg.routed_scaling``.

    ``live`` (bool, x's leading shape) marks the rows that are tokens.
    The others (the tail of a padded ragged stream, idle decode slots)
    go to a null group behind the last expert: they are sorted past the
    rows the grouped matmul covers, add nothing to any expert's load and
    come back as zeros.

    Where the engine holds a share of the experts (``cfg.experts_held``
    of them from ``cfg.expert_offset``; ``experts`` then holds those
    alone), the routing is still over all ``cfg.num_experts``; a pair on
    an expert that lies elsewhere joins a null group of its own and adds
    nothing here: its chip's part of the sum.

    Returns (out like x, histogram int32: pairs received by each expert
    held, then, of a share, the pairs on absent experts, then the pairs
    of the null group; (X + 1,) where every expert is held)."""
    E = x.shape[-1]
    xt = x.reshape(-1, E)  # (T, E) flattened tokens
    T = xt.shape[0]
    k = cfg.num_experts_per_tok
    X = cfg.num_held_experts  # groups of the grouped matmul, a layer
    nulls = 2 if cfg.experts_held else 1

    logits = jnp.einsum("te,ex->tx", xt, router,
                        preferred_element_type=jnp.float32)
    if cfg.moe_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, top_idx = lax.top_k(scores + bias.astype(jnp.float32), k)
        weights = jnp.take_along_axis(scores, top_idx, axis=-1)
    else:
        weights, top_idx = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if cfg.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if cfg.routed_scaling != 1.0:
        weights = weights * cfg.routed_scaling

    expert = top_idx.reshape(T * k).astype(jnp.int32)
    if cfg.experts_held:
        local = expert - cfg.expert_offset
        expert = jnp.where((local >= 0) & (local < X), local, X)
    if live is not None:
        expert = jnp.where(jnp.repeat(live.reshape(T), k), expert,
                           X + nulls - 1)
    hist = jnp.sum(
        expert[:, None] == jnp.arange(X + nulls, dtype=jnp.int32),
        axis=0, dtype=jnp.int32)
    order = jnp.argsort(expert, stable=True)  # pair indices, by expert
    rows = xt[order // k]  # (T*k, E) each pair's token, grouped by expert
    sorted_expert = expert[order]
    # groups of the whole stack: this layer's X sizes at layer_idx * X
    first = jnp.asarray(layer_idx, jnp.int32) * X
    num_layers = jax.tree.leaves(experts["w_gate"])[0].shape[0]
    sizes = lax.dynamic_update_slice(
        jnp.zeros(num_layers * X, jnp.int32), hist[:X], (first,))
    group = first + sorted_expert

    def grouped(y, name):
        w = jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), experts[name])
        return ragged_quant_dot(y, w, sizes, group, grouped_matmul)

    out = grouped(_act(cfg)(grouped(rows, "w_gate")) * grouped(rows, "w_up"),
                  "w_down")
    # rows past the last group are not written by the grouped matmul
    out = jnp.where((sorted_expert < X)[:, None],
                    out.astype(jnp.float32)
                    * weights.reshape(T * k)[order][:, None], 0.0)
    inverse = jnp.zeros(T * k, jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    out = jnp.sum(out[inverse].reshape(T, k, E), axis=1)
    return out.astype(x.dtype).reshape(x.shape), hist


def _rms_norm_heads(x: jnp.ndarray, weight: jnp.ndarray,
                    eps: float) -> jnp.ndarray:
    """RMSNorm of (..., H, D) over heads and head_dim together, i.e. over
    the projected vector before its head split; ``weight`` is (H, D)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=(-2, -1), keepdims=True)
    return (xf * lax.rsqrt(var + eps)
            * weight.astype(jnp.float32)).astype(x.dtype)


def _onto_heads(x: jnp.ndarray, lp: dict, name: str, head_dim: int
                ) -> jnp.ndarray:
    """x (..., T, E) through the projection ``name`` of ``lp`` onto heads,
    (..., T, heads, head_dim): ``lp[name]`` (E, heads, head_dim) as
    ``init_params`` makes it (or its quantized container), or
    ``lp[name + "_t"]`` (heads * head_dim, E) as a runner keeps it
    (``param_layouts``): one product over E, the PRODUCT reshaped."""
    if name + "_t" not in lp:
        return quant_einsum("...te,ehd->...thd", x, lp[name])
    y = quant_einsum("...te,fe->...tf", x, lp[name + "_t"])
    return y.reshape(*y.shape[:-1], -1, head_dim)


def _lora_delta(x: jnp.ndarray, onehot: jnp.ndarray, A: jnp.ndarray,
                B: jnp.ndarray) -> jnp.ndarray:
    """Per-token LoRA delta with a bank of N adapters.

    x: (..., T, E); onehot: (..., T, N) adapter selector per token;
    A: (N, E, R); B: (N, R, *out). Computes every adapter's low-rank path
    (rank*N is ~2% of the base matmul FLOPs) and selects per token — static
    shapes, no gather of weight tensors.
    """
    xa = jnp.einsum("...te,ner->...tnr", x, A)
    if B.ndim == 4:  # (N, R, H, D) attention projections
        out = jnp.einsum("...tnr,nrhd->...tnhd", xa, B)
        return jnp.einsum("...tnhd,...tn->...thd", out, onehot)
    out = jnp.einsum("...tnr,nrf->...tnf", xa, B)  # (N, R, F) mlp/down
    return jnp.einsum("...tnf,...tn->...tf", out, onehot)


def forward_tokens(
    cfg: ModelConfig,
    params: dict,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    attend: AttendFn,
    kv_caches: Any = None,
    lora: Any = None,
    live: Optional[jnp.ndarray] = None,
    moe_hist: bool = False,
    loop_count: bool = False,
    recur: Optional[RecurFn] = None,
    grouped_matmul: Optional[GroupedMatmulFn] = None,
) -> Tuple[jnp.ndarray, Any]:
    """Embed tokens then run the decoder stack (see forward_hidden)."""
    x = embed_tokens(cfg, params, tokens)
    return forward_hidden(cfg, params, x, positions, attend, kv_caches, lora,
                          live, moe_hist, loop_count, recur, grouped_matmul)


def embed_tokens(cfg: ModelConfig, params: dict, tokens: jnp.ndarray) -> jnp.ndarray:
    """Token embedding incl. the Gemma sqrt(E) scale — the ONE site for the
    normalizer semantics."""
    x = embed_lookup(params["embed"], tokens, cfg.jax_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, cfg.jax_dtype)
    return times(x, cfg.embedding_multiplier)


def forward_hidden(
    cfg: ModelConfig,
    params: dict,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    attend: AttendFn,
    kv_caches: Any = None,
    lora: Any = None,
    live: Optional[jnp.ndarray] = None,
    moe_hist: bool = False,
    loop_count: bool = False,
    recur: Optional[RecurFn] = None,
    grouped_matmul: Optional[GroupedMatmulFn] = None,
) -> Tuple[jnp.ndarray, Any]:
    """Run the decoder stack from pre-embedded activations.

    x: (..., T, E); positions: (..., T) int32.
    kv_caches: the cache pytree (leading layer axis) or None. It
    rides the scan *carry*, not ys: while-loop carries alias in place under
    XLA, so a donated multi-GiB HBM pool is updated without ever being
    copied (scan ys would allocate a fresh stacked output every step —
    measured as 2× cache HLO-temp on v5e). ``attend`` receives the cache
    plus the layer index and returns the updated cache.
    ``live`` (bool, like positions) marks the rows that are tokens; by
    default those with a position >= 0 (the ragged stream pads its tail
    with -1). Only the MoE block reads it: other rows are kept out of the
    routing. Returns (hidden (..., T, E), new_kv_caches), and with
    ``moe_hist`` a third value: the MoE block's per-layer routing
    histogram (L, X + 1), see _moe_mlp.

    A looped stack (``cfg.loop_passes`` = U > 1) runs the scan over the
    SAME stacked weights U times, an outer scan around the one over
    layers: pass u's layer l writes and reads cache layer u * L + l, and
    the final norm closes every pass (the last pass's is
    ``logits_from_hidden``'s). ``loop_count`` appends the number of
    passes run, int32, the outer scan's own carry; the histogram then has
    a leading pass axis.

    A hybrid stack (``cfg.attn_period`` > 1) scans over PERIODS of its
    layer pattern, see ``_forward_hybrid``; ``recur`` is its recurrent
    layers' stateful call (default: whole sequences from a zero state).
    ``grouped_matmul`` is the MoE block's (a GroupedMatmulFn; default:
    ``jax.lax.ragged_dot``).
    """
    layers, experts = params["layers"], None
    if cfg.is_moe:
        if live is None:
            live = positions >= 0
        # the expert matrices stay whole, outside the scan (see _moe_mlp)
        experts = {k: layers[k] for k in _EXPERT_WEIGHTS}
        layers = {k: v for k, v in layers.items() if k not in experts}
    onehot = None if lora is None else lora["onehot"].astype(cfg.jax_dtype)
    if cfg.residual_f32:
        x = x.astype(jnp.float32)

    def pre_norm(h, weight, bias=None):
        if cfg.layer_norm:
            normed = layer_norm(h, weight, bias, cfg.rms_norm_eps)
        else:
            normed = rms_norm(h, weight, cfg.rms_norm_eps, cfg.norm_offset)
        # a float32 stream feeds the matmuls in the model dtype
        return normed.astype(cfg.jax_dtype) if cfg.residual_f32 else normed

    if cfg.patterned:
        if lora is not None:
            raise ValueError("LoRA on a hybrid (recurrent-state) stack is "
                             "not supported")
        x, new_caches, hists = _forward_hybrid(
            cfg, params, layers, experts, x, attend,
            recur or (functools.partial(sambay.mamba_dense, cfg)
                      if cfg.mamba_period
                      else functools.partial(falcon_h1.ssd_dense, cfg)
                      if cfg.ssd_heads
                      else functools.partial(olmo_hybrid.recur_dense, cfg)
                      if cfg.gdn_heads else _recur_dense),
            kv_caches, live, pre_norm, grouped_matmul, positions)
        out = (x, new_caches)
        if moe_hist:
            out += (hists,)
        return out

    def layer_fn(carry, scanned, first_cache_layer=None, sparse=cfg.is_moe):
        h, layer_idx, caches = carry
        lp, lb = scanned  # layer params, per-layer lora bank (or None)
        normed = pre_norm(h, lp["attn_norm"])
        cache_layer = (layer_idx if first_cache_layer is None
                       else first_cache_layer + layer_idx)
        if cfg.is_latent:
            with jax.named_scope("mla"):
                o, caches = _mla_mixer(cfg, lp, normed, positions, attend,
                                       caches, cache_layer)
            return mlp_half(h, o, lp, lb, layer_idx, caches, sparse)
        q, k, v = (_onto_heads(normed, lp, w, cfg.head_dim)
                   for w in ("wq", "wk", "wv"))
        if lb is not None:
            if "wq" in lb:
                q = q + _lora_delta(normed, onehot, *lb["wq"])
            if "wk" in lb:
                k = k + _lora_delta(normed, onehot, *lb["wk"])
            if "wv" in lb:
                v = v + _lora_delta(normed, onehot, *lb["wv"])
        if cfg.qkv_bias:
            q = q + lp["bq"]
            k = k + lp["bk"]
            v = v + lp["bv"]
        if cfg.qk_norm and cfg.qk_norm_kind == "full":
            # OLMoE: RMSNorm over the whole projection (H*D), pre-rope
            q = _rms_norm_heads(q, lp["q_norm"], cfg.rms_norm_eps)
            k = _rms_norm_heads(k, lp["k_norm"], cfg.rms_norm_eps)
        elif cfg.qk_norm:  # Qwen3: per-head RMSNorm over head_dim, pre-rope
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps, cfg.norm_offset)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps, cfg.norm_offset)
        if cfg.query_scale:
            # fold a non-default score scale (Gemma-2 query_pre_attn_scalar)
            # into q: attention impls keep their head_dim**-0.5
            q = q * jnp.asarray(
                cfg.query_scale * cfg.head_dim ** 0.5, q.dtype
            )
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
        attn, caches = _attend_filled(cfg, attend, q, k, v, caches,
                                      cache_layer)
        o = quant_einsum("...thd,hde->...te", attn, lp["wo"])
        if lb is not None and "wo" in lb:
            flat = attn.reshape(*attn.shape[:-2], -1)  # (..., T, H*D)
            o = o + _lora_delta(flat, onehot, *lb["wo"])
        return mlp_half(h, o, lp, lb, layer_idx, caches, sparse)

    def mlp_half(h, o, lp, lb, layer_idx, caches, sparse):
        """A layer from its mixer's output on: the stream takes it, then
        the MLP or the sparse block."""
        if cfg.post_norms:
            o = rms_norm(o, lp["post_attn_norm"], cfg.rms_norm_eps,
                         cfg.norm_offset)
        h = h + o
        normed2 = pre_norm(h, lp["mlp_norm"])
        hist = None
        if sparse:  # LoRA on MoE experts: not supported yet
            with jax.named_scope("moe"):
                mlp_out, hist = _sparse_block(cfg, lp, experts, layer_idx,
                                              normed2, live, grouped_matmul)
        else:
            mlp_out = _mlp(cfg, lp, normed2, lb=lb, onehot=onehot)
        if cfg.post_norms:
            mlp_out = rms_norm(mlp_out, lp["post_mlp_norm"],
                               cfg.rms_norm_eps, cfg.norm_offset)
        h = h + mlp_out
        return (h, layer_idx + 1, caches), hist

    bank = None if lora is None else lora["bank"]
    first = None
    if cfg.dense_layers:
        # the leading dense layers, cache layers 0.. in order, before the
        # scan over the (like) expert layers; they route nothing and have
        # no histogram row
        (x, _, kv_caches), _ = lax.scan(
            functools.partial(layer_fn, sparse=False),
            (x, jnp.int32(0), kv_caches), (params["dense"], None))
        first = cfg.dense_layers
    if cfg.loop_passes == 1:
        (x, _, new_caches), hists = lax.scan(
            functools.partial(layer_fn, first_cache_layer=first),
            (x, jnp.int32(0), kv_caches), (layers, bank))
        passes = jnp.int32(1)
    else:
        def pass_fn(carry, u):
            h, passes, caches = carry
            # the final norm closes a pass before the next one opens. The
            # pass index comes in as a scanned value, not as the carried
            # count: with `where(count > 0, ...)` on the carry the TPU
            # compiler (jax 0.9.0, v5e) built a program whose second pass
            # already read wrongly, in float32 too, where the CPU's was
            # right to the last bit (PERF.md section 6, PR 31)
            h = jnp.where(u > 0,
                          rms_norm(h, params["final_norm"], cfg.rms_norm_eps,
                                   cfg.norm_offset), h)
            (h, _, caches), hists = lax.scan(
                functools.partial(layer_fn,
                                  first_cache_layer=u * cfg.num_layers),
                (h, jnp.int32(0), caches), (layers, bank))
            return (h, passes + 1, caches), hists

        (x, passes, new_caches), hists = lax.scan(
            pass_fn, (x, jnp.int32(0), kv_caches),
            jnp.arange(cfg.loop_passes, dtype=jnp.int32))
    out = (x, new_caches)
    if moe_hist:
        out += (hists,)
    if loop_count:
        out += (passes,)
    return out


class ExpandedRows(NamedTuple):
    """What a latent ``attend`` returns where it scored some rows of the
    stream in the published form (``_mla_mixer``)."""
    absorbed: jnp.ndarray  # (..., T, H, kv_lora_rank); those rows undefined
    heads: jnp.ndarray  # (..., T, H, v_head_dim), of those rows
    rows: jnp.ndarray  # (..., T) bool: which


def _mla_mixer(cfg: ModelConfig, lp: dict, x: jnp.ndarray, positions,
               attend: AttendFn, caches, cache_layer
               ) -> Tuple[jnp.ndarray, Any]:
    """Latent attention (MLA). As published: a query passes a low-rank
    path with a norm (``cfg.q_lora_rank`` 0: one direct projection) and
    splits a head into an unrotated and a rotated part (``cfg.mla_rope``
    False: nothing is rotated, ``positions`` is not read and the "rotated"
    parts are plain values); a token's keys and values pass another,
    ``c`` (normed) and one rotated key ``r`` all heads share; head i's key
    is ``[W_UK_i c; r]``, its value ``W_UV_i c``, the score scale
    ``head_dim ** -0.5``. The cache row is ``[c; r]`` either way, and the
    scores have TWO forms, the same numbers up to rounding
    (tests/test_pangu_ultra_moe.py). ABSORBED: head i's query
    ``[W_UK_i^T q_nope_i; q_rope_i]`` scores the row itself, the weighted
    rows' first ``kv_lora_rank`` values go through ``W_UV_i``: 2176
    operations a pair and head where the published form has 640, and no
    work a context row; the form of decode rows, short spans, dense
    forwards and the XLA path. EXPANDED, the published form: a context row
    is expanded to a head's key and value once a span and head, which a
    span of many query rows shares; the Pallas kernel of the ragged program
    scores its long spans so (ops/latent_paged_attention_pallas.py
    ``EXPAND_ROWS``), from ``expand`` below.

    ``attend`` takes the absorbed queries (..., T, H, latent_lanes), the
    rows as the one key head (..., T, 1, latent_lanes), both padded with
    zeros to the pool's whole lane tiles, and the rows' latent part as
    its value, and returns (..., T, H, kv_lora_rank). Attention
    implementations scale by their query's width ** -0.5, so the score
    scale is folded into the query, accumulated in float32, as
    ``query_scale`` is. It is also handed ``expand`` = (q_nope, q_rope,
    ``W_UK``, ``W_UV``), which most implementations drop; one that scores
    some rows expanded returns ``ExpandedRows`` in place of the array."""
    f32, C, H = jnp.float32, cfg.kv_lora_rank, cfg.num_heads
    eps, theta = cfg.rms_norm_eps, cfg.rope_theta
    c_q = x
    if cfg.q_lora_rank:
        c_q = rms_norm(quant_einsum("...te,er->...tr", x, lp["wq_a"]),
                       lp["q_a_norm"], eps)

    def heads(y):  # (..., T, H * d) -> (..., T, H, d)
        return y.reshape(*y.shape[:-1], H, -1)

    def rotated(y):
        return apply_rope(y, positions, theta) if cfg.mla_rope else y

    q_nope = heads(quant_einsum("...tr,rf->...tf", c_q, lp["wq_nope"]))
    q_rope = rotated(
        heads(quant_einsum("...tr,rf->...tf", c_q, lp["wq_rope"])))
    kv = quant_einsum("...te,ec->...tc", x, lp["wkv_a"])
    c = rms_norm(kv[..., :C], lp["kv_a_norm"], eps)
    r = rotated(kv[..., None, C:])  # one head
    lanes = cfg.latent_lanes

    def padded(*parts):
        width = sum(p.shape[-1] for p in parts)
        return jnp.concatenate(
            [*parts, jnp.zeros((*parts[0].shape[:-1], lanes - width),
                               parts[0].dtype)], axis=-1)

    row = padded(c[..., None, :], r)
    q_lat = jnp.einsum("...thd,hcd->...thc", q_nope, lp["w_uk"],
                       preferred_element_type=f32)
    fold = cfg.head_dim ** -0.5 * lanes ** 0.5
    q = (padded(q_lat, q_rope.astype(f32)) * fold).astype(x.dtype)
    o_lat, caches = attend(
        q, row, row[..., :C], caches, cache_layer,
        expand=(q_nope, q_rope, lp["w_uk"], lp["w_uv"]))
    scored = o_lat if isinstance(o_lat, ExpandedRows) else None
    o = jnp.einsum("...thc,hcd->...thd",
                   o_lat if scored is None else scored.absorbed, lp["w_uv"])
    if scored is not None:
        o = jnp.where(scored.rows[..., None, None], scored.heads, o)
    return quant_einsum("...thd,hde->...te", o, lp["wo"]), caches


def _sparse_block(cfg: ModelConfig, lp: dict, experts: dict, layer_idx,
                  x: jnp.ndarray, live, grouped_matmul=None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The routed experts and, where the family has one, the shared
    expert every token passes through, added once."""
    out, hist = _moe_mlp(cfg, lp["router"], experts, layer_idx, x, live,
                         bias=lp.get("router_bias"),
                         grouped_matmul=grouped_matmul)
    if cfg.shared_expert_size:
        out = out + _mlp(cfg, {"w_gate": lp["shared_gate"],
                               "w_up": lp["shared_up"],
                               "w_down": lp["shared_down"]}, x)
    return out, hist


def _gated(attn: jnp.ndarray, x: jnp.ndarray, gp: dict) -> jnp.ndarray:
    """The attention output times sigmoid(W_g x), elementwise over heads
    and head_dim, before the output projection; W_g as made, (E, H * D),
    or as a runner keeps it, ``wg_t`` (``param_layouts``)."""
    eq, wg = as_stored(gp, "wg")
    gate = quant_einsum(eq, x, wg).reshape(attn.shape)
    return (attn.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(attn.dtype)


def _recur_dense(conv_w, qkv, g, beta, caches, k_idx, *, neg_eigval):
    """A KDA layer over whole sequences, qkv (B, T, 3*H*D), from an empty
    past: no cache is read or written (the dense forward, and the
    definition the cached forms are held against)."""
    q, k, v = kda.split_heads(kda.conv_dense(qkv, conv_w), g.shape[-2])
    return kda.recurrence_dense(
        *kda.prepare(q, k, v, g, beta, neg_eigval)), caches


def _kda_mixer(cfg: ModelConfig, kp: dict, x: jnp.ndarray, recur: RecurFn,
               caches, k_idx) -> Tuple[jnp.ndarray, Any]:
    """One gated delta-rule layer: projections, the low-rank decay and
    gate, the stateful part (``recur``: short convolution, SiLU, L2 norm
    and the recurrence), per-head RMSNorm, the output gate, W_o. Decay,
    beta and the recurrence are float32."""
    f32 = jnp.float32
    qkv = quant_einsum("...te,ef->...tf", x, kp["w_qkv"])
    z = jnp.einsum("...tr,rhd->...thd",
                   jnp.einsum("...te,er->...tr", x, kp["f_down"]),
                   kp["f_up"])
    g = -jnp.exp(kp["a_log"].astype(f32))[:, None] * jax.nn.softplus(
        z.astype(f32) + kp["dt_bias"].astype(f32))
    beta = jax.nn.sigmoid(
        jnp.einsum("...te,eh->...th", x, kp["w_beta"]).astype(f32))
    o, caches = recur(kp["conv"], qkv, g, beta, caches, k_idx,
                      neg_eigval=cfg.kda_neg_eigval)
    o = rms_norm(o, kp["o_norm"], cfg.rms_norm_eps, cfg.norm_offset)
    gate = jnp.einsum("...tr,rhd->...thd",
                      jnp.einsum("...te,er->...tr", x, kp["g_down"]),
                      kp["g_up"])
    o = (o * jax.nn.sigmoid(gate.astype(f32))).astype(x.dtype)
    return quant_einsum("...thd,hde->...te", o, kp["wo"]), caches


def _attend_filled(cfg: ModelConfig, attend: AttendFn, q, k, v, kv, i,
                   **how):
    """``attend`` over the heads as the cache holds them: where it fills
    the KV heads up with empty ones (``ModelConfig.cache_kv_heads``: 30
    heads lie as 32), zero heads behind the real ones on q, k and v, and
    the real heads' outputs back. Without filling, the call as it is.
    ``how``: the call's keywords (a layer's ``kind``)."""
    held = cfg.cache_kv_heads
    if held == cfg.num_kv_heads or cfg.diff_attn:  # a packed stack fills
        return attend(q, k, v, kv, i, **how)       # its own heads
    attn, kv = attend(sambay._pad_heads(q, held * cfg.q_per_kv),
                      sambay._pad_heads(k, held), sambay._pad_heads(v, held),
                      kv, i, **how)
    return attn[..., :cfg.num_heads, :], kv


def _forward_hybrid(cfg: ModelConfig, params: dict, layers: dict,
                    experts: dict, x, attend: AttendFn,
                    recur, caches, live, pre_norm, grouped_matmul=None,
                    positions=None):
    """A patterned stack (``cfg.layer_kinds``): one scan over the periods
    of each run of like periods (``cfg.stack_segments``; a run of one
    period is not scanned). Solar-Open2 is one run of (gqa, kda, kda, kda):
    in each period the softmax-attention layer first (no positional
    encoding: order comes from the recurrence), then the KDA layers, every
    one followed by the sparse block. Kimi-Linear is three: (kda, kda, kda,
    mla) once with its leading dense layer (an MLP in place of the sparse
    block), the same scanned, and a short (kda, kda, mla); the latent
    attention rotates nothing and shares ``caches["kv"]``, a latent pool.
    An afmoe stack is runs of (swa, swa, swa, full), grouped-query attention
    within the window (rotated) or over all rows (nothing rotated), gated,
    norms on both sides of every sublayer, the periods that hold leading
    dense layers runs of their own. A SambaY stack (models/sambay.py) is
    three: (mamba, swa) periods, one (mamba, full), (gmu, cross) periods,
    every block followed by the MLP. Falcon-H1 is one run of ("parallel",):
    a state-space mixer with heads and rotated grouped-query attention
    (``positions``: only this kind rotates) on one normed row, summed
    (models/falcon_h1.py); what the second run hands the third
    (``m``: the state-space layer's scan output; the full layer's keys and
    values, which a dense forward's cross layers attend over, a paged one's
    read from the cache) enters the third's scan as constants.

    ``layers`` holds what every layer has, (L, ...); ``params[<stack>]``
    the mixers of a kind, (layers of the kind, ...) (``sambay.STACK_OF``;
    "gqa" over the periods, "kda" over the KDA layers); ``experts`` the
    routed experts of all layers, outside the scan (see _moe_mlp).
    ``caches`` is the stack's whole cache pytree (engine/kv_cache.py:
    {"kv", "state", "conv"}, and "win" where the window binds) or None; it
    rides the scan carry whole. ``recur`` is the recurrent layers' stateful
    call (a RecurFn, or a ``sambay.MambaFn``). An attention layer of a
    SambaY stack calls ``attend`` with the whole pytree and its ``kind``.
    Returns (hidden, caches, routing histograms (L, ...) or None).

    A scan runs over the period's index alone and every layer takes its
    own parameters out of the stacks by its absolute index, one dynamic
    slice a layer, which XLA fuses into the matmul that reads it, as it
    does with a scan's own slicing. Scanning over stacks reshaped to
    (periods, layers a period, ...) made the compiler materialise a whole
    period's slice first: 3 x 200 MB of copies a period in the decode
    step, counted by the TPU compiler at the published widths."""
    stack_of = {"gqa": "gqa", "kda": "kda", "mla": "mla", "parallel": "gqa",
                "gdn": "gdn", **sambay.STACK_OF}
    if cfg.window_layers:  # grouped-query attention of two kinds
        stack_of.update(swa="gqa", full="gqa")
    dense = cfg.dense_layers

    def gqa(gp, normed, caches, i, rotate=False, kind=None):
        """Grouped-query attention over cache layer ``i``: Solar-Open2's
        (nothing rotated, a sigmoid gate), Falcon-H1's (``rotate``: rope
        on q and k, the keys times ``key_multiplier``; no gate),
        Olmo-Hybrid's (nothing rotated, no gate, RMSNorm over the whole q
        and k projections) or afmoe's (``kind`` "swa" / "full": ``i`` is
        the layer's place among its kind's, ``attend`` is handed the whole
        cache pytree and the kind and finds the pool and the window;
        RMSNorm a head on q and k, a gate)."""
        if "wq_t" in gp:
            # W_q, W_k, W_v lie transposed, (H * D, E): the order of bytes
            # the TPU compiler wants for a decode step's 64 rows; handed
            # (E, H * D) it copied all three stacks whole, 220 MB, at the
            # start of every decode step (PERF.md section 6, PR 52).
            # Falcon-H1's stacks are MADE so (models/falcon_h1.py);
            # Solar-Open2's are made as below, (E, H * D), the shape the
            # benchmark's references read (chipbench/reference/*.py take
            # ``init_random``'s tree by shape), and a runner keeps them
            # transposed (``param_layouts``, PR 53): its decode program
            # copied wq and wg whole, 268 MB a step. One made form once a
            # ``benchmark`` issue lets the references read it: ROADMAP S20
            q, k, v = (quant_einsum("...te,fe->...tf", normed, gp[w])
                       for w in ("wq_t", "wk_t", "wv_t"))
            k, v = (y.reshape(*y.shape[:-1], cfg.num_kv_heads, cfg.head_dim)
                    for y in (k, v))
        else:
            q = quant_einsum("...te,ef->...tf", normed, gp["wq"])
            k = quant_einsum("...te,ehd->...thd", normed, gp["wk"])
            v = quant_einsum("...te,ehd->...thd", normed, gp["wv"])
        q = q.reshape(*q.shape[:-1], cfg.num_heads, cfg.head_dim)
        if cfg.qk_norm and cfg.qk_norm_kind == "full":
            # Olmo-Hybrid: over the whole projections (OLMoE's)
            q = _rms_norm_heads(q, gp["q_norm"], cfg.rms_norm_eps)
            k = _rms_norm_heads(k, gp["k_norm"], cfg.rms_norm_eps)
        elif cfg.qk_norm:  # a head at a time (Qwen3's)
            q = rms_norm(q, gp["q_norm"], cfg.rms_norm_eps, cfg.norm_offset)
            k = rms_norm(k, gp["k_norm"], cfg.rms_norm_eps, cfg.norm_offset)
        if rotate:
            k = times(k, cfg.key_multiplier)
            q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
            k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
        if kind is not None:
            attn, caches = _attend_filled(cfg, attend, q, k, v, caches, i,
                                          kind=kind)
        else:
            kv = None if caches is None else caches["kv"]
            attn, kv = _attend_filled(cfg, attend, q, k, v, kv, i)
            if caches is not None:
                caches = {**caches, "kv": kv}
        if cfg.attn_gate:
            attn = _gated(attn, normed, gp)
        return quant_einsum("...thd,hde->...te", attn, gp["wo"]), caches

    def at(tree, i):
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)

    def stream_in(h):
        """The stream as a sublayer without a norm before it reads it."""
        return h.astype(cfg.jax_dtype) if cfg.residual_f32 else h

    def sublayer_out(o, lp, norm):
        """A sublayer's output as the stream takes it: through the norm
        AFTER it where the block has one (``cfg.norms`` "post"; the other
        and "both"; the other patterned stacks have none and add ``o`` as
        it is)."""
        if not cfg.post_norms:
            return o
        return rms_norm(o, lp[norm], cfg.rms_norm_eps, cfg.norm_offset)

    def period_fn(kinds, l0, before, seen, shared, carry, p):
        """Period ``p`` of a run of ``kinds`` periods that starts at layer
        ``l0`` with ``before[stack]`` layers of each stack, and
        ``seen[kind]`` of each kind, ahead of it."""
        h, _, caches = carry
        hists = []
        stacks = [stack_of[k] for k in kinds]
        for j, kind in enumerate(kinds):
            depth = p * len(kinds) + (l0 + j)
            # a leading dense layer's norms and MLP come from "dense", the
            # others' from the expert layers' "layers" behind them; its
            # period is a run of its own and not scanned
            # (cfg.stack_segments), so which layer is dense is known here
            # (with no dense layer the index is ``depth`` itself, so that
            # the other stacks' traced programs stay what they were)
            is_dense = l0 + j < dense
            lp = (at(params["dense"], l0 + j) if is_dense
                  else at(layers, depth - dense if dense else depth))
            # the Olmo 3 block has no norm before a sublayer: it reads the
            # stream as it is, and the norm follows it (``sublayer_out``)
            normed = (pre_norm(h, lp["attn_norm"], lp.get("attn_norm_b"))
                      if cfg.pre_norms else stream_in(h))
            with jax.named_scope(kind):
                # this layer's place in its kind's stack: the period's own
                # layers of the kind, behind those of the periods before
                i, first = stacks.count(stacks[j]), (
                    before[stacks[j]] + stacks[:j].count(stacks[j]))
                i = p if (i, first) == (1, 0) else p * i + first
                if kind == "gqa":
                    o, caches = gqa(at(params["gqa"], i), normed, caches, i)
                elif stacks[j] == "gqa" and kind in ("swa", "full"):
                    # its cache layer: its place among its KIND's layers,
                    # in that kind's pool
                    ik = (p * kinds.count(kind) + seen[kind]
                          + kinds[:j].count(kind))
                    o, caches = gqa(at(params["gqa"], i), normed, caches, ik,
                                    rotate=kind in cfg.rope_kinds, kind=kind)
                elif kind == "parallel":  # both mixers read ``normed``
                    with jax.named_scope("ssd"):
                        o, caches = falcon_h1.ssd_mixer(
                            cfg, at(params["ssd"], i), normed, recur,
                            caches, i)
                    with jax.named_scope("gqa"):
                        a, caches = gqa(
                            at(params["gqa"], i),
                            times(normed, cfg.attn_in_multiplier), caches, i,
                            rotate=True)
                    o = (times(o, cfg.ssd_out_multiplier)
                         + times(a, cfg.attn_out_multiplier))
                elif kind == "kda":
                    o, caches = _kda_mixer(cfg, at(params["kda"], i),
                                           normed, recur, caches, i)
                elif kind == "gdn":
                    o, caches = olmo_hybrid.gdn_mixer(
                        cfg, at(params["gdn"], i), normed, recur, caches, i)
                elif kind == "mla":  # rotates nothing: no positions
                    kv = None if caches is None else caches["kv"]
                    o, kv = _mla_mixer(cfg, at(params["mla"], i), normed,
                                       None, attend, kv, i)
                    if caches is not None:
                        caches = {**caches, "kv": kv}
                elif kind == "mamba":
                    o, y, caches = sambay.mamba_mixer(
                        cfg, at(params["mamba"], i), normed, recur, caches, i)
                    if "full" in kinds:  # the layer the memory units read
                        shared["m"] = y
                elif kind == "gmu":
                    o = sambay.gmu_mixer(at(params["gmu"], i), normed,
                                         shared["m"])
                else:  # "swa", "full", "cross": differential attention
                    ap = at(params[stacks[j]], i)
                    q = sambay.packed_queries(cfg, ap, normed)
                    if kind == "cross":
                        k, v = shared["kv"]
                    else:
                        k, v = sambay.packed_keys_values(cfg, ap, normed)
                    if kind == "full":
                        shared["kv"] = (k, v)
                    # a window layer's place in its pool; the other pool
                    # holds the one full layer
                    attn, caches = attend(q, k, v, caches,
                                          i if kind == "swa" else 0,
                                          kind=kind)
                    o = sambay.diff_combine(cfg, ap, attn, depth)
            h = h + sublayer_out(o, lp, "post_attn_norm")
            if not cfg.pre_norms:
                mlp_out = _mlp(cfg, lp, stream_in(h))
            elif is_dense:
                with jax.named_scope("dense_mlp"):
                    mlp_out = _mlp(cfg, lp, pre_norm(h, lp["mlp_norm"]))
            elif cfg.is_moe:
                with jax.named_scope("moe"):
                    mlp_out, hist = _sparse_block(
                        cfg, lp, experts, p * len(kinds) + (l0 + j - dense),
                        pre_norm(h, lp["mlp_norm"]), live, grouped_matmul)
                hists.append(hist)
            else:
                mlp_out = _mlp(cfg, lp, pre_norm(h, lp["mlp_norm"],
                                                 lp.get("mlp_norm_b")))
            h = h + sublayer_out(mlp_out, lp, "post_mlp_norm")
        return (h, p + 1, caches), (jnp.stack(hists) if hists else None)

    l0, before, shared, hists = 0, dict.fromkeys(stack_of.values(), 0), {}, []
    seen = dict.fromkeys(stack_of, 0)
    for kinds, count in cfg.stack_segments:
        run = functools.partial(period_fn, kinds, l0, dict(before),
                                dict(seen), shared)
        if count == 1:
            (x, _, caches), hist = run((x, 0, caches), 0)
            hist = None if hist is None else hist[None]
        else:
            (x, _, caches), hist = lax.scan(
                run, (x, jnp.int32(0), caches),
                jnp.arange(count, dtype=jnp.int32))
        hists.append(hist)
        l0 += count * len(kinds)
        for k in kinds:
            before[stack_of[k]] += count
            seen[k] += count
    if not cfg.is_moe:
        return x, caches, None
    # a row a sparse layer: a period with a dense layer has one fewer, a
    # period of dense layers alone none
    hists = [h.reshape(-1, h.shape[-1]) for h in hists if h is not None]
    return x, caches, hists[0] if len(hists) == 1 else jnp.concatenate(hists)


def logits_from_hidden(cfg: ModelConfig, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
    if cfg.layer_norm:
        hidden = layer_norm(hidden, params["final_norm"],
                            params["final_norm_b"], cfg.rms_norm_eps)
    else:
        hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps,
                          cfg.norm_offset)
    if cfg.residual_f32:
        hidden = hidden.astype(cfg.jax_dtype)
    head = (head_from_embed(params["embed"]) if cfg.tie_word_embeddings
            else params["lm_head"])
    if not is_quantized(head):
        head = head.astype(cfg.jax_dtype)
    logits = times(quant_einsum("...te,ev->...tv", hidden, head,
                                jnp.float32), cfg.lm_head_multiplier)
    if cfg.final_logit_softcap:  # Gemma-2
        cap = cfg.final_logit_softcap
        logits = cap * jnp.tanh(logits / cap)
    return logits


def dense_attend(cfg: ModelConfig) -> AttendFn:
    """A dense forward's attention call, for any family: causal over the
    rows given, within the window for a "swa" layer of a stack whose
    window binds (``kind``: models/sambay.py)."""
    def attend(q, k, v, caches, layer_idx, kind=None, expand=None):
        return dense_causal_attention(
            q, k, v, soft_cap=cfg.attn_logit_softcap,
            window=cfg.sliding_window if kind == "swa" else 0), caches

    return attend


def forward_dense(
    cfg: ModelConfig,
    params: dict,
    tokens: jnp.ndarray,
    positions: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Whole-prompt causal forward: tokens (B, S) -> logits (B, S, V)."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    hidden, _ = forward_tokens(cfg, params, tokens, positions,
                               dense_attend(cfg), None)
    return logits_from_hidden(cfg, params, hidden)
