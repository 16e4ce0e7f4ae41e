"""openPangu-Ultra-MoE (latent attention, sandwich norms, a leading dense
layer, sparse MLP) forward pass, plain: straightforward ``jax.numpy`` in
float32 with "highest" matmul precision; no cache, no kernels, no batching,
no sort, and the PUBLISHED (expanded) form of the attention, where the
program serves the absorbed one. One sequence in, log-probabilities of
every position out.

    h = embed[tokens]
    for l in range(num_hidden_layers):
        h = h + RMSNorm(Attn_l(RMSNorm(h; input_layernorm_l));
                        post_attention_layernorm_l)
        m = MLP_l if l < first_k_dense_replace else Sparse_l
        h = h + RMSNorm(m(RMSNorm(h; pre_mlp_layernorm_l)); post_mlp_layernorm_l)
    logits = RMSNorm(h; norm) @ lm_head

(``sandwich_norm``: a norm before AND after each sublayer, eps
``rms_norm_eps``.)

*Attn* (H heads; a query head is ``qk_nope_head_dim`` unrotated and
``qk_rope_head_dim`` rotated values, a value head ``v_head_dim``), at
position t of input u: c_q = RMSNorm(W_qa u) (``q_lora_rank``);
[q_nope_i; q_rope_i] = W_qb c_q for every head i; [c_t; r_t] = W_kva u,
c_t <- RMSNorm(c_t) (``kv_lora_rank``), r_t of ``qk_rope_head_dim``;
rotary position encoding (``rope_theta``, no scaling) on q_rope_i and on
the ONE r_t all heads share; [k_nope_i,s; v_i,s] = W_kvb c_s; score
(q_nope_i . k_nope_i,s + q_rope_i . r_s) / sqrt(qk_nope + qk_rope), causal
softmax over s <= t, o_i = sum_s p v_i,s, out = W_o concat_i(o_i). The
scores of all positions are formed, a block of heads at a time.

*MLP* of the leading dense layers: SwiGLU of ``intermediate_size``.

*Sparse block*: s = sigmoid(W_r x) over all ``n_routed_experts`` in
float32; the ``num_experts_per_tok`` largest are chosen; w = s[chosen] /
sum (``norm_topk_prob``) x ``routed_scaling_factor``, zero off the chosen;
EVERY expert held is applied to EVERY position and weighted by w; plus one
shared SwiGLU expert of ``moe_intermediate_size`` every position passes.

What the configuration file states: every width, ``first_k_dense_replace``,
``sandwich_norm``, 256 routed experts of 2048, 8 a token, 1 shared,
``norm_topk_prob``, ``routed_scaling_factor`` 2.5, ``rope_theta``. NOT a
key of that file (the manifest's ``assumed`` says why each): scores are
sigmoids (DeepSeek-V3's router, which the family's ``routed_scaling_factor``
and ``norm_topk_prob`` belong to) with no selection bias and no groups
(the file has no ``n_group``, ``topk_group`` or ``scoring_func``; the
program's ``router_bias`` is zeros and is added where the k are chosen, as
the program does); the rotary pairs are (j, j + d/2), the program's
convention, which with random weights is a permutation of W_qb's and
W_kva's columns; the multi-token-prediction module is no part of this
forward pass.

Departures, each a cut of the run and not of the equations: the engine
holds ``n_routed_experts_held`` of the routed experts from
``routed_expert_offset`` (the share of one chip of sixteen), so the sum
over experts runs over those alone, still weighted by the routing over
all 256: the other chips' terms are absent on both sides; the vocabulary
is the configuration file's (a slice of the published one), so the
log-softmax is over the slice. Storage, not arithmetic: the program keeps
W_qb as its unrotated and rotated columns (``wq_nope``, ``wq_rope``) and
W_kvb as its key and value columns (``w_uk``, ``w_uv``), a head at a
time.

Parameters are the program's own pytree (``embed`` (V, E); ``dense`` and
``layers``, the leading dense and the expert layers, each with a leading
layer axis: ``attn_norm``, ``post_attn_norm``, ``mlp_norm``,
``post_mlp_norm`` (L, E), ``wq_a`` (L, E, Rq), ``q_a_norm`` (L, Rq),
``wq_nope`` (L, Rq, H * nope), ``wq_rope`` (L, Rq, H * rope) (columns by
head), ``wkv_a`` (L, E, C + rope), ``kv_a_norm`` (L, C), ``w_uk``
(L, H, C, nope), ``w_uv`` (L, H, C, v), ``wo`` (L, H, v, E); ``dense`` with ``w_gate``/``w_up``
(L, E, F), ``w_down`` (L, F, E); ``layers`` with ``router`` (L, E, X),
``router_bias`` (L, X), ``w_gate``/``w_up`` (L, Xh, E, F), ``w_down``
(L, Xh, F, E), ``shared_gate``/``shared_up`` (L, E, F), ``shared_down``
(L, F, E); ``final_norm``; ``lm_head`` (E, V)), upcast one tensor (one
expert) at a time, so no second copy of the model exists on the device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.qwen3 import F32, _head, _rms, _rope
from chipbench.reference.solar_open2 import _kept_as

HEAD_BLOCK = 8  # heads whose (T, T) scores exist at once


def check(hf: dict) -> None:
    """Refuse what the equations above do not describe."""
    if not hf.get("sandwich_norm"):
        raise ValueError("sandwich_norm is not set")
    if hf.get("rope_scaling"):
        raise ValueError("rope_scaling is set")
    if int(hf.get("n_group", 1) or 1) > 1:
        raise ValueError("n_group > 1: group-limited routing")
    if int(hf.get("num_nextn_predict_layers", 0) or 0):
        raise ValueError("num_nextn_predict_layers > 0")


def _swiglu(x, w_gate, w_up, w_down):
    return ((jax.nn.silu(x @ w_gate.astype(F32)) * (x @ w_up.astype(F32)))
            @ w_down.astype(F32))


@functools.partial(jax.jit, static_argnames=(
    "eps", "theta", "state_dtype", "latent_dtype"))
def _attn(u, lp, *, eps, theta, state_dtype=F32, latent_dtype=F32):
    """The published form: every head's keys and values expanded from the
    latent of every position."""
    T = u.shape[0]
    pos = jnp.arange(T)
    C = lp["kv_a_norm"].shape[0]
    c_q = _rms(u @ lp["wq_a"].astype(F32), lp["q_a_norm"].astype(F32), eps)
    kv = u @ lp["wkv_a"].astype(F32)
    # what a cache would hold of a token: control ``latent_dtype``
    c = _kept_as(_rms(kv[:, :C], lp["kv_a_norm"].astype(F32), eps),
                 latent_dtype)
    r = _kept_as(_rope(kv[:, None, C:], pos, theta)[:, 0], latent_dtype)
    H = lp["wo"].shape[0]
    wq_nope = lp["wq_nope"].reshape(c_q.shape[-1], H, -1)
    wq_rope = lp["wq_rope"].reshape(c_q.shape[-1], H, -1)
    scale = (wq_nope.shape[-1] + wq_rope.shape[-1]) ** -0.5
    causal = pos[:, None] >= pos[None, :]
    keep = functools.partial(_kept_as, dtype=state_dtype)
    out = jnp.zeros_like(u)
    for h0 in range(0, H, HEAD_BLOCK):
        hb = slice(h0, h0 + HEAD_BLOCK)
        q_nope = jnp.einsum("tr,rhd->thd", c_q, wq_nope[:, hb].astype(F32))
        q_rope = _rope(jnp.einsum("tr,rhd->thd", c_q,
                                  wq_rope[:, hb].astype(F32)), pos, theta)
        k_nope = jnp.einsum("sc,hcd->shd", c, lp["w_uk"][hb].astype(F32))
        v = jnp.einsum("sc,hcd->shd", c, lp["w_uv"][hb].astype(F32))
        s = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
             + jnp.einsum("thd,sd->hts", q_rope, r)) * scale
        p = keep(jax.nn.softmax(jnp.where(causal, keep(s), -jnp.inf), -1))
        o = jnp.einsum("hts,shd->thd", p, v)
        out = out + jnp.einsum("thd,hde->te", o, lp["wo"][hb].astype(F32))
    return out


@functools.partial(jax.jit, static_argnames=(
    "top_k", "renormalise", "scaling", "first", "held", "router_dtype"))
def _sparse(x, lp, *, top_k, renormalise, scaling, first, held,
            router_dtype=F32):
    """Every held expert on every position + the shared expert."""
    r = functools.partial(_kept_as, dtype=router_dtype)
    s = r(jax.nn.sigmoid(r(r(x) @ r(lp["router"].astype(F32)))))   # (T, X)
    _, idx = jax.lax.top_k(s + lp["router_bias"].astype(F32), top_k)
    w = jnp.take_along_axis(s, idx, -1)
    if renormalise:
        w = w / jnp.sum(w, -1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    w = jnp.zeros_like(s).at[rows, idx].set(w * scaling)[:, first:first + held]

    def one_expert(acc, xs):  # upcast here: one expert in float32 at a time
        w_gate, w_up, w_down, w_e = xs
        return acc + w_e[:, None] * _swiglu(x, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (lp["w_gate"], lp["w_up"], lp["w_down"], w.T))
    return out + _swiglu(x, lp["shared_gate"], lp["shared_up"],
                         lp["shared_down"])


@jax.jit
def _dense_mlp(x, lp):
    return _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def logprobs(hf: dict, params: dict, tokens, first: int, *,
             state_dtype=F32, router_dtype=F32, latent_dtype=F32):
    """log p(. | tokens[:t+1]) for t in [first, len(tokens)), shape
    (len(tokens) - first, V). ``hf`` is the configuration file's dict.

    The three dtypes are float32, as the configuration states of the
    softmax state and the router's scores (and of everything here). Lower
    ones are the controls of the comparison (``reference/control.py``):
    the scores and weights of the softmax kept in ``state_dtype``, the
    router's scores computed in ``router_dtype``, what a cache would hold
    of a token ([c; r]) kept in ``latent_dtype``."""
    check(hf)
    eps, theta = float(hf["rms_norm_eps"]), float(hf["rope_theta"])
    dense = int(hf.get("first_k_dense_replace", 0))
    routed = int(hf["n_routed_experts"])
    sparse = dict(
        top_k=int(hf["num_experts_per_tok"]),
        renormalise=bool(hf.get("norm_topk_prob", True)),
        scaling=float(hf.get("routed_scaling_factor", 1.0)),
        first=int(hf.get("routed_expert_offset", 0)),
        held=int(hf.get("n_routed_experts_held", routed)))

    def norm(x, w):
        return _rms(x, w.astype(F32), eps)

    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(F32)
        for i in range(int(hf["num_hidden_layers"])):
            stack, j = (("dense", i) if i < dense else ("layers", i - dense))
            lp = jax.tree_util.tree_map(lambda a: a[j], params[stack])
            a = _attn(norm(h, lp["attn_norm"]), lp, eps=eps, theta=theta,
                      state_dtype=state_dtype, latent_dtype=latent_dtype)
            h = h + norm(a, lp["post_attn_norm"])
            x = norm(h, lp["mlp_norm"])
            m = (_dense_mlp(x, lp) if i < dense
                 else _sparse(x, lp, router_dtype=router_dtype, **sparse))
            h = h + norm(m, lp["post_mlp_norm"])
        head = (params["embed"].T if hf.get("tie_word_embeddings")
                else params["lm_head"])
        return _head(h[first:], params["final_norm"], head, eps=eps)
