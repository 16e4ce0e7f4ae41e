"""OLMoE forward pass, plain: straightforward ``jax.numpy`` in float32
with "highest" matmul precision; no cache, no kernels, no batching, no
sort. One sequence in, log-probabilities of every position out.

Follows the published architecture (OlmoeForCausalLM): pre-norm decoder
blocks; multi-head attention with RMSNorm on the WHOLE projected q and k
vectors (all heads together, before the head split), then rotary
embedding (half-split rotation, theta from the config); a sparse
feed-forward block with no shared expert: router logits, softmax over all
experts in float32, the ``num_experts_per_tok`` largest probabilities
kept as they are (renormalised to sum to 1 only if ``norm_topk_prob``),
SwiGLU experts. Here EVERY expert is applied to EVERY position and its
output weighted by the routing weight, which is zero off the top-k: the
definition the served block's sort and grouped matmul have to equal.
Untied output head unless ``tie_word_embeddings``. No departures;
``clip_qkv`` is null in the published file and refused if set.

Parameters are the program's own pytree (``embed`` (V, E); ``layers``
with a leading layer axis: ``wq`` (L, E, H, D), ``wk``/``wv``
(L, E, KH, D), ``wo`` (L, H, D, E), ``q_norm`` (L, H, D) and ``k_norm``
(L, KH, D) (the published (H*D,) weight, by head), ``router`` (L, E, X),
``w_gate``/``w_up`` (L, X, E, F), ``w_down`` (L, X, F, E), norms;
``lm_head`` (E, V)), upcast one layer at a time so no second copy of the
model exists on the device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.qwen3 import F32, _head, _rms, _rope


def _rms_whole(x, w, eps):
    """RMSNorm of (T, H, D) over H and D together; w is (H, D)."""
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, (-2, -1), keepdims=True) + eps) * w


def routing_weights(logits, top_k: int, renormalise: bool):
    """(T, X) float32 logits -> (T, X) weights, zero off each row's top-k."""
    probs = jax.nn.softmax(logits.astype(F32), -1)
    vals, idx = jax.lax.top_k(probs, top_k)
    if renormalise:
        vals = vals / jnp.sum(vals, -1, keepdims=True)
    rows = jnp.arange(probs.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(vals)


def moe_block(h, lp, top_k: int, renormalise: bool):
    """Every expert on every position, weighted: (T, E) -> (T, E)."""
    weights = routing_weights(h @ lp["router"], top_k, renormalise)

    def one_expert(acc, xs):
        w_gate, w_up, w_down, w = xs
        y = (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down
        return acc + w[:, None] * y, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (lp["w_gate"], lp["w_up"], lp["w_down"], weights.T))
    return out


@functools.partial(jax.jit,
                   static_argnames=("eps", "theta", "top_k", "renormalise"))
def _layer(x, lp, *, eps, theta, top_k, renormalise):
    lp = jax.tree_util.tree_map(lambda a: a.astype(F32), lp)
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, lp["attn_norm"], eps)
    q = jnp.einsum("te,ehd->thd", h, lp["wq"])
    k = jnp.einsum("te,ehd->thd", h, lp["wk"])
    v = jnp.einsum("te,ehd->thd", h, lp["wv"])
    q = _rope(_rms_whole(q, lp["q_norm"], eps), pos, theta)
    k = _rope(_rms_whole(k, lp["k_norm"], eps), pos, theta)
    g = q.shape[1] // k.shape[1]
    causal = pos[:, None] >= pos[None, :]
    heads = []  # one KV head and its g query heads at a time: (g, T, T) scores
    for j in range(k.shape[1]):
        s = jnp.einsum("tgd,sd->gts", q[:, j * g:(j + 1) * g], k[:, j])
        s = jnp.where(causal, s * (q.shape[-1] ** -0.5), -jnp.inf)
        heads.append(jnp.einsum("gts,sd->tgd", jax.nn.softmax(s, -1), v[:, j]))
    a = jnp.concatenate(heads, axis=1)
    x = x + jnp.einsum("thd,hde->te", a, lp["wo"])
    h = _rms(x, lp["mlp_norm"], eps)
    return x + moe_block(h, lp, top_k, renormalise)


def logprobs(hf: dict, params: dict, tokens, first: int):
    """log p(. | tokens[:t+1]) for t in [first, len(tokens)), shape
    (len(tokens) - first, V). ``hf`` is the configuration file's dict."""
    if hf.get("clip_qkv") is not None:
        raise ValueError("clip_qkv is set: not the published OLMoE block")
    eps, theta = float(hf["rms_norm_eps"]), float(hf["rope_theta"])
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        for i in range(int(hf["num_hidden_layers"])):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            x = _layer(x, lp, eps=eps, theta=theta,
                       top_k=int(hf["num_experts_per_tok"]),
                       renormalise=bool(hf.get("norm_topk_prob", False)))
        head = (params["embed"].T if hf.get("tie_word_embeddings")
                else params["lm_head"])
        return _head(x[first:], params["final_norm"], head, eps=eps)
