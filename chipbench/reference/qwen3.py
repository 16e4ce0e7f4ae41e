"""Qwen3 (dense) forward pass, plain: straightforward ``jax.numpy`` in
float32 with "highest" matmul precision; no cache, no kernels, no
batching. One sequence in, log-probabilities of every position out.

Follows the published architecture (Qwen3ForCausalLM): pre-norm decoder
blocks; grouped-query attention with per-head RMSNorm on q and k over the
head dimension, applied before rotary embedding (half-split rotation,
theta from the config); SwiGLU feed-forward; untied output head unless
``tie_word_embeddings``. No departures. Parameters are the program's own
pytree (``embed`` (V, E); ``layers`` with a leading layer axis: ``wq``
(L, E, H, D), ``wk``/``wv`` (L, E, KH, D), ``wo`` (L, H, D, E),
``w_gate``/``w_up`` (L, E, F), ``w_down`` (L, F, E), norms; ``lm_head``
(E, V)), upcast one layer at a time so no second copy of the model
exists on the device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv            # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta"))
def _layer(x, lp, *, eps, theta):
    lp = jax.tree_util.tree_map(lambda a: a.astype(F32), lp)
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, lp["attn_norm"], eps)
    q = jnp.einsum("te,ehd->thd", h, lp["wq"])
    k = jnp.einsum("te,ehd->thd", h, lp["wk"])
    v = jnp.einsum("te,ehd->thd", h, lp["wv"])
    q = _rope(_rms(q, lp["q_norm"], eps), pos, theta)
    k = _rope(_rms(k, lp["k_norm"], eps), pos, theta)
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
    return x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, *, eps):
    logits = _rms(x, norm.astype(F32), eps) @ head.astype(F32)
    return jax.nn.log_softmax(logits, -1)


def logprobs(hf: dict, params: dict, tokens, first: int):
    """log p(. | tokens[:t+1]) for t in [first, len(tokens)), shape
    (len(tokens) - first, V). ``hf`` is the configuration file's dict."""
    eps, theta = float(hf["rms_norm_eps"]), float(hf["rope_theta"])
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        for i in range(int(hf["num_hidden_layers"])):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            x = _layer(x, lp, eps=eps, theta=theta)
        head = (params["embed"].T if hf.get("tie_word_embeddings")
                else params["lm_head"])
        return _head(x[first:], params["final_norm"], head, eps=eps)
