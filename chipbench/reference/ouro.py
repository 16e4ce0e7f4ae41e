"""Ouro (looped language model) forward pass, plain: straightforward
``jax.numpy`` in float32 with "highest" matmul precision, as two Python
loops; no scan, no cache, no kernels, no batching. One sequence in,
log-probabilities of every position out.

    h = embed[tokens]
    for u in range(total_ut_steps):            # the SAME layers every pass
        for l in range(num_hidden_layers):
            a = Attention_l(RMSNorm(h; input_layernorm_l))
            h = h + RMSNorm(a; input_layernorm_2_l)
            n = RMSNorm(h; post_attention_layernorm_l)
            m = W_down_l(silu(W_gate_l n) * (W_up_l n))
            h = h + RMSNorm(m; post_attention_layernorm_2_l)
        h = RMSNorm(h; norm)                   # after every pass
    logits = h @ lm_head                       # of the last pass

Attention is causal multi-head attention with rotary embedding over the
whole head (half-split rotation, theta from the config), no biases, no
QK-norm; pass u's layer l attends over the keys and values that pass u's
layer l itself computed from this sequence (in a serving system: a cache
layer per (pass, layer) pair). Untied output head unless
``tie_word_embeddings``.

What the configuration file (``config.json``) states: the widths, 48
layers, ``total_ut_steps`` 4, ``early_exit_threshold`` 1, every layer
``full_attention``, no sliding window. What is taken from the model's
published implementation and NOT from a key of that file:

- the norm after each sublayer (``input_layernorm_2``,
  ``post_attention_layernorm_2``; tensor names from memory);
- the final norm after every pass, the last included;
- keys and values of its own for every (pass, layer) pair.

Departure: the exit gate (``early_exit_gate``, a linear map of each
pass's normed state to one number) is not evaluated. At the published
threshold of 1 it selects nothing: every token runs all passes and the
answer is the last pass's. A threshold below 1 is refused.

Parameters are the program's own pytree (``embed`` (V, E); ``layers``
with a leading layer axis: ``wq``/``wk``/``wv`` (L, E, H, D), ``wo``
(L, H, D, E), ``w_gate``/``w_up`` (L, E, F), ``w_down`` (L, F, E),
``attn_norm``, ``post_attn_norm``, ``mlp_norm``, ``post_mlp_norm``
(L, E); ``final_norm`` (E,); ``lm_head`` (E, V)), upcast one layer at a
time so no second copy of the model exists on the device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.qwen3 import F32, _head, _rms, _rope


@functools.partial(jax.jit, static_argnames=("eps", "theta"))
def _layer(x, lp, *, eps, theta):
    lp = jax.tree_util.tree_map(lambda a: a.astype(F32), lp)
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, lp["attn_norm"], eps)
    q = _rope(jnp.einsum("te,ehd->thd", h, lp["wq"]), pos, theta)
    k = _rope(jnp.einsum("te,ehd->thd", h, lp["wk"]), pos, theta)
    v = jnp.einsum("te,ehd->thd", h, lp["wv"])
    causal = pos[:, None] >= pos[None, :]
    heads = []  # one head at a time: (T, T) scores
    for j in range(q.shape[1]):
        s = jnp.einsum("td,sd->ts", q[:, j], k[:, j]) * (q.shape[-1] ** -0.5)
        s = jnp.where(causal, s, -jnp.inf)
        heads.append(jax.nn.softmax(s, -1) @ v[:, j])
    a = jnp.einsum("thd,hde->te", jnp.stack(heads, axis=1), lp["wo"])
    x = x + _rms(a, lp["post_attn_norm"], eps)
    n = _rms(x, lp["mlp_norm"], eps)
    m = (jax.nn.silu(n @ lp["w_gate"]) * (n @ lp["w_up"])) @ lp["w_down"]
    return x + _rms(m, lp["post_mlp_norm"], eps)


def check(hf: dict) -> None:
    """Refuse what the equations above do not describe."""
    if float(hf.get("early_exit_threshold", 1.0)) < 1.0:
        raise ValueError("early_exit_threshold < 1: the exit gate would "
                         "select among passes; not this reference")
    if any(t != "full_attention" for t in hf.get("layer_types") or ()):
        raise ValueError("a layer_types entry other than full_attention")
    if hf.get("use_sliding_window"):
        raise ValueError("use_sliding_window is set")
    if hf["num_key_value_heads"] != hf["num_attention_heads"]:
        raise ValueError("grouped KV heads: not the published Ouro block")


def logprobs(hf: dict, params: dict, tokens, first: int):
    """log p(. | tokens[:t+1]) for t in [first, len(tokens)), shape
    (len(tokens) - first, V). ``hf`` is the configuration file's dict."""
    check(hf)
    eps, theta = float(hf["rms_norm_eps"]), float(hf["rope_theta"])
    norm = params["final_norm"].astype(F32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        for u in range(int(hf["total_ut_steps"])):
            if u:
                x = _rms(x, norm, eps)
            for i in range(int(hf["num_hidden_layers"])):
                lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
                x = _layer(x, lp, eps=eps, theta=theta)
        head = (params["embed"].T if hf.get("tie_word_embeddings")
                else params["lm_head"])
        return _head(x[first:], norm, head, eps=eps)  # the last pass's norm
