"""Kimi-Linear (KDA layers and no-rope latent attention in one stack, a
leading dense layer, sparse MLP) forward pass, plain: straightforward
``jax.numpy`` in float32 with "highest" matmul precision; no cache, no
kernels, no batching, no sort, and the PUBLISHED (expanded) form of the
latent attention, where the program serves the absorbed one. One sequence
in, log-probabilities of every position out.

    h = embed[tokens]
    for l in 1 .. num_hidden_layers:            # 1-based, as the lists are
        x = RMSNorm(h; input_layernorm_l)
        h = h + (KDA_l(x) if l in kda_layers else MLA_l(x))
        x = RMSNorm(h; post_attention_layernorm_l)
        h = h + (SwiGLU_l(x) if l <= first_k_dense_replace
                 else sum_e w_e(x) SwiGLU_e(x) + SwiGLU_shared(x))
    logits = RMSNorm(h; norm) @ lm_head

No positional encoding anywhere: order comes from the KDA recurrence.

*KDA layer* (``linear_attn_config``: H heads of d, conv width K): the
equations of ``solar_open2.py`` (whose KDA block is Kimi Linear's), with
beta = sigmoid(W_beta x) NOT doubled: the file has no key for it.

*MLA layer* (``full_attn_layers``; H heads, a query head of
``qk_nope_head_dim`` + ``qk_rope_head_dim`` values, a value head of
``v_head_dim``), at position t of input u: [q_nope_i; q_pe_i] = W_q u for
every head i, one direct projection (``q_lora_rank: null``); [c_t; k_pe_t]
= W_kva u, c_t <- RMSNorm(c_t) (``kv_lora_rank``), k_pe_t of
``qk_rope_head_dim`` values ALL heads share; NOTHING is rotated
(``mla_use_nope``); [k_nope_i,s; v_i,s] = W_kvb c_s; score (q_nope_i .
k_nope_i,s + q_pe_i . k_pe_s) / sqrt(qk_nope + qk_rope), causal softmax
over s <= t, o_i = sum_s p v_i,s, out = W_o concat_i(o_i). The scores of
all positions are formed, a block of heads at a time.

*Sparse block*: s = sigmoid(W_r x) over all ``num_experts`` in float32;
the ``num_experts_per_token`` largest of s + b are chosen (b:
``e_score_correction_bias``, used to choose only; one group:
``num_expert_group`` 1); w = s[chosen] / sum (``moe_renormalize``) x
``routed_scaling_factor``, zero off the chosen. EVERY expert held is
applied to EVERY position and weighted by w; plus ``num_shared_experts``
shared SwiGLU expert of ``moe_intermediate_size`` every position passes.

What the configuration file states: every width, the two layer lists,
``mla_use_nope``, ``q_lora_rank: null``, conv width 4,
``first_k_dense_replace``, 256 experts of 1024, 8 a token, 1 shared,
sigmoid scores, ``moe_renormalize``, ``routed_scaling_factor`` 2.446,
``num_expert_group`` 1. NOT a key of that file (the manifest's
``assumed`` says why each): the KDA block's inner equations (SiLU after
the convolution, L2 norm of q and k, q scaled by d^-1/2, the per-head
RMSNorm, the sigmoid gate pair, the low-rank width d of the decay and gate
pairs, beta in (0, 1)); the file's ``head_dim`` 72 = hidden / heads sizes
nothing here.

Departures, each a cut of the run and not of the equations: the engine
holds ``n_routed_experts_held`` of the routed experts from
``routed_expert_offset`` (the share of one chip of sixteen), so the sum
over experts runs over those alone, still weighted by the routing over
all 256: the other chips' terms are absent on both sides; the vocabulary
is the configuration file's (a slice of the published one), so the
log-softmax is over the slice. Storage, not arithmetic: the program keeps
W_q as its two parts' columns (``wq_nope``, ``wq_rope``) and W_kvb as its
key and value columns (``w_uk``, ``w_uv``), a head at a time.

Parameters are the program's own pytree (``embed`` (V, E); ``dense``, the
leading dense layers' ``attn_norm``, ``mlp_norm`` (Ld, E), ``w_gate`` /
``w_up`` (Ld, E, F), ``w_down`` (Ld, F, E); ``layers``, the expert layers
behind them, as ``solar_open2.py``'s; ``kda`` by KDA layer, as
``solar_open2.py``'s; ``mla`` by MLA layer: ``wq_nope`` (Lm, E, H * nope),
``wq_rope`` (Lm, E, H * rope) (columns by head), ``wkv_a`` (Lm, E, C +
rope), ``kv_a_norm`` (Lm, C), ``w_uk`` (Lm, H, C, nope), ``w_uv``
(Lm, H, C, v), ``wo`` (Lm, H, v, E); ``final_norm``; ``lm_head`` (E, V)),
upcast one layer (one expert) at a time so no second copy of the model
exists on the device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.qwen3 import F32, _head, _rms
from chipbench.reference.solar_open2 import _kda, _kept_as, _sparse, _swiglu

HEAD_BLOCK = 8  # heads whose (T, T) scores exist at once


def layer_kinds(hf: dict) -> list:
    """"kda" or "mla" for layers 1 .. num_hidden_layers, from the two
    1-based lists; refuses lists that do not name every layer once."""
    lin = hf["linear_attn_config"]
    kda, mla = list(lin["kda_layers"]), list(lin["full_attn_layers"])
    n = int(hf["num_hidden_layers"])
    if sorted(kda + mla) != list(range(1, n + 1)):
        raise ValueError("kda_layers and full_attn_layers do not name "
                         "every layer once")
    return ["mla" if l in mla else "kda" for l in range(1, n + 1)]


def check(hf: dict) -> None:
    """Refuse what the equations above do not describe."""
    if not hf.get("mla_use_nope"):
        raise ValueError("mla_use_nope is not set: not this reference (NoPE)")
    if hf.get("q_lora_rank") is not None:
        raise ValueError("q_lora_rank is set: a low-rank query path")
    if int(hf.get("num_expert_group", 1) or 1) > 1:
        raise ValueError("num_expert_group > 1: group-limited routing")
    if int(hf.get("num_nextn_predict_layers", 0) or 0):
        raise ValueError("num_nextn_predict_layers > 0")
    if hf.get("moe_router_activation_func", "sigmoid") != "sigmoid":
        raise ValueError("moe_router_activation_func is not sigmoid")


@functools.partial(jax.jit, static_argnames=(
    "eps", "state_dtype", "latent_dtype"))
def _mla(u, mp, *, eps, state_dtype=F32, latent_dtype=F32):
    """The published form: every head's keys and values expanded from the
    latent of every position; nothing rotated."""
    T = u.shape[0]
    pos = jnp.arange(T)
    C = mp["kv_a_norm"].shape[0]
    H = mp["wo"].shape[0]
    kv = u @ mp["wkv_a"].astype(F32)
    # what a cache would hold of a token: control ``latent_dtype``
    c = _kept_as(_rms(kv[:, :C], mp["kv_a_norm"].astype(F32), eps),
                 latent_dtype)
    k_pe = _kept_as(kv[:, C:], latent_dtype)
    wq_nope = mp["wq_nope"].reshape(u.shape[-1], H, -1)
    wq_pe = mp["wq_rope"].reshape(u.shape[-1], H, -1)
    scale = (wq_nope.shape[-1] + wq_pe.shape[-1]) ** -0.5
    causal = pos[:, None] >= pos[None, :]
    keep = functools.partial(_kept_as, dtype=state_dtype)
    out = jnp.zeros_like(u)
    for h0 in range(0, H, HEAD_BLOCK):
        hb = slice(h0, h0 + HEAD_BLOCK)
        q_nope = jnp.einsum("te,ehd->thd", u, wq_nope[:, hb].astype(F32))
        q_pe = jnp.einsum("te,ehd->thd", u, wq_pe[:, hb].astype(F32))
        k_nope = jnp.einsum("sc,hcd->shd", c, mp["w_uk"][hb].astype(F32))
        v = jnp.einsum("sc,hcd->shd", c, mp["w_uv"][hb].astype(F32))
        s = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
             + jnp.einsum("thd,sd->hts", q_pe, k_pe)) * scale
        p = keep(jax.nn.softmax(jnp.where(causal, keep(s), -jnp.inf), -1))
        o = jnp.einsum("hts,shd->thd", p, v)
        out = out + jnp.einsum("thd,hde->te", o, mp["wo"][hb].astype(F32))
    return out


@jax.jit
def _dense_mlp(x, lp):
    return _swiglu(x, *(lp[k].astype(F32)
                        for k in ("w_gate", "w_up", "w_down")))


def logprobs(hf: dict, params: dict, tokens, first: int, *,
             state_dtype=F32, router_dtype=F32, latent_dtype=F32):
    """log p(. | tokens[:t+1]) for t in [first, len(tokens)), shape
    (len(tokens) - first, V). ``hf`` is the configuration file's dict.

    The three dtypes are float32, as the configuration states. Lower ones
    are the controls of the comparison (``reference/control.py``): the KDA
    state after every token and the scores and weights of the softmax
    kept in ``state_dtype``, the router's scores computed in
    ``router_dtype``, what a cache would hold of a token ([c; k_pe]) kept
    in ``latent_dtype``; such a reference has to read as not correct."""
    check(hf)
    eps = float(hf["rms_norm_eps"])
    dense = int(hf.get("first_k_dense_replace", 0))
    routed = int(hf["num_experts"])
    sparse = dict(
        top_k=int(hf["num_experts_per_token"]),
        renormalise=bool(hf.get("moe_renormalize", True)),
        scaling=float(hf.get("routed_scaling_factor", 1.0)),
        first=int(hf.get("routed_expert_offset", 0)),
        held=int(hf.get("n_routed_experts_held", routed)))

    def at(tree, i):
        return jax.tree_util.tree_map(lambda a: a[i], tree)

    seen = {"kda": 0, "mla": 0}
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(F32)
        for i, kind in enumerate(layer_kinds(hf)):
            lp = (at(params["dense"], i) if i < dense
                  else at(params["layers"], i - dense))
            x = _rms(h, lp["attn_norm"].astype(F32), eps)
            mp = at(params[kind], seen[kind])
            seen[kind] += 1
            if kind == "kda":
                h = h + _kda(x, mp, eps=eps, neg_eigval=False,
                             state_dtype=state_dtype)
            else:
                h = h + _mla(x, mp, eps=eps, state_dtype=state_dtype,
                             latent_dtype=latent_dtype)
            x = _rms(h, lp["mlp_norm"].astype(F32), eps)
            h = h + (_dense_mlp(x, lp) if i < dense
                     else _sparse(x, lp, router_dtype=router_dtype, **sparse))
        head = (params["embed"].T if hf.get("tie_word_embeddings")
                else params["lm_head"])
        return _head(h[first:], params["final_norm"], head, eps=eps)
