"""Solar-Open2 (hybrid KDA / gated-NoPE-GQA, sparse MLP) forward pass,
plain: straightforward ``jax.numpy`` in float32 with "highest" matmul
precision; no cache, no kernels, no batching, no sort. One sequence in,
log-probabilities of every position out.

    h = embed[tokens]
    for l in range(num_hidden_layers):
        x = RMSNorm(h; input_layernorm_l)
        h = h + (GQA_l(x) if l % (gqa_interval + 1) == 0 else KDA_l(x))
        x = RMSNorm(h; post_attention_layernorm_l)
        h = h + sum_e w_e(x) SwiGLU_e(x) + SwiGLU_shared(x)
    logits = RMSNorm(h; norm) @ lm_head

*GQA layer* (H query heads, KH key/value heads, head size D): q, k, v =
W_q x, W_k x, W_v x with NO positional encoding (``use_rope`` false);
a = softmax(q k^T / sqrt(D), causal) v; a <- a * sigmoid(W_gate x),
elementwise over the H * D outputs (``use_gqa_gate``); out = W_o a.

*KDA layer* (H heads, d = 128 keys and values a head), per token t:
q^, k^, v^ = W_q x, W_k x, W_v x; a causal depthwise convolution of width
4 over time on every channel of each, then SiLU; q = L2norm(q~) d^-1/2,
k = L2norm(k~), v = v~; per-channel decay g = -exp(A_log_h) softplus(
W_f_up W_f_down x + dt_bias), alpha = exp(g); beta = sigmoid(W_beta x),
doubled where ``kda_allow_neg_eigval``; state S (d, d), zero before the
first token:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

out = W_o (RMSNorm_head(o_t) * sigmoid(W_g_up W_g_down x)). The loop over
tokens is a loop, one row after the other.

*Sparse block*: s = sigmoid(W_r x) over all ``n_routed_experts`` in
float32; the ``num_experts_per_tok`` largest of s + b are chosen (b: a
per-expert selection bias, used to choose only); w = s[chosen] / sum
(``norm_topk_prob``) x ``routed_scaling_factor``, zero off the chosen.
EVERY expert held is applied to EVERY position and weighted by w.

What the configuration file states: the widths, the layer pattern
(``gqa_layers``, ``gqa_interval``), ``use_rope`` false, ``use_gqa_gate``,
``kda_use_full_proj`` false, ``kda_allow_neg_eigval``, conv width 4, 320
routed experts of 1280, 8 a token, 1 shared, ``norm_topk_prob``,
``routed_scaling_factor`` 1. Taken from the family's published
descriptions and NOT from a key of that file (the manifest's ``assumed``
says why each): the router is sigmoid with a selection bias (GLM-4-MoE's,
which Solar Open uses); the low-rank width of the decay and gate pairs
is the KDA head size (Kimi Linear's); SiLU after the convolution, L2
norm of q and k, the per-head RMSNorm and the sigmoid gate on the KDA
output (Kimi Linear's); the GQA gate is elementwise from a projection of
its own; no QK-norm.

Departures, each a cut of the run and not of the equations: the engine
holds ``n_routed_experts_held`` of the routed experts from
``routed_expert_offset`` (the share of one chip of sixteen), so the sum
over experts runs over those alone, still weighted by the routing over
all 320: the other chips' terms are absent on both sides; the vocabulary
is the configuration file's (a slice of the published one), so the
log-softmax is over the slice.

Parameters are the program's own pytree (``embed`` (V, E); ``layers``
with a leading layer axis: ``attn_norm``, ``mlp_norm`` (L, E), ``router``
(L, E, X), ``router_bias`` (L, X), ``w_gate``/``w_up`` (L, Xh, E, F),
``w_down`` (L, Xh, F, E), ``shared_gate``/``shared_up`` (L, E, F),
``shared_down`` (L, F, E); ``gqa`` by period: ``wq``, ``wg``
(P, E, H*D) (columns by head), ``wk``/``wv`` (P, E, KH, D), ``wo``
(P, H, D, E); ``kda`` by KDA layer: ``w_qkv`` (Lk, E, 3*H*d) (the three
projections side by side, each by head), ``wo`` (Lk, H, d, E), ``conv``
(Lk, 4, 3*H*d) (the taps of q, k, v as ``w_qkv``'s columns lie; tap 0 on
the current row), ``a_log``
(Lk, H), ``dt_bias`` (Lk, H, d), ``f_down``/``g_down`` (Lk, E, R),
``f_up``/``g_up`` (Lk, R, H, d), ``w_beta`` (Lk, E, H), ``o_norm``
(Lk, d); ``final_norm``; ``lm_head`` (E, V)), upcast one layer at a time
so no second copy of the model exists on the device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.qwen3 import F32, _head, _rms


def check(hf: dict) -> None:
    """Refuse what the equations above do not describe."""
    if hf.get("use_rope"):
        raise ValueError("use_rope is set: not this reference (NoPE)")
    if hf.get("kda_use_full_proj"):
        raise ValueError("kda_use_full_proj is set")
    if int(hf.get("first_k_dense_replace", 0)):
        raise ValueError("first_k_dense_replace > 0")
    period = int(hf["gqa_interval"]) + 1
    if list(hf["gqa_layers"]) != list(
            range(0, int(hf["num_hidden_layers"]), period)):
        raise ValueError("gqa_layers is not one layer in every period")


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _kept_as(x, dtype):
    """``x`` with the exponent and mantissa of ``dtype``, still float32: a
    value as a narrower type would keep it. ``lax.reduce_precision``,
    because the TPU compiler drops a convert to bfloat16 and back
    (``xla_allow_excess_precision``) and the control would test nothing."""
    fi = jnp.finfo(dtype)
    if fi.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, fi.nexp, fi.nmant)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


@functools.partial(jax.jit, static_argnames=(
    "top_k", "renormalise", "scaling", "first", "held", "router_dtype"))
def _sparse(x, lp, *, top_k, renormalise, scaling, first, held,
            router_dtype=F32):
    """Every held expert on every position + the shared expert."""
    lp = _f32(lp)
    r = functools.partial(_kept_as, dtype=router_dtype)
    s = r(jax.nn.sigmoid(r(r(x) @ r(lp["router"]))))          # (T, X)
    _, idx = jax.lax.top_k(s + lp["router_bias"], top_k)
    w = jnp.take_along_axis(s, idx, -1)
    if renormalise:
        w = w / jnp.sum(w, -1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    w = jnp.zeros_like(s).at[rows, idx].set(w * scaling)[:, first:first + held]

    def one_expert(acc, xs):
        w_gate, w_up, w_down, w_e = xs
        return acc + w_e[:, None] * _swiglu(x, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (lp["w_gate"], lp["w_up"], lp["w_down"], w.T))
    return out + _swiglu(x, lp["shared_gate"], lp["shared_up"],
                         lp["shared_down"])


@jax.jit
def _gqa(x, gp):
    gp = _f32(gp)
    T = x.shape[0]
    H, D = gp["wo"].shape[:2]
    q = (x @ gp["wq"]).reshape(T, H, D)
    k = jnp.einsum("te,ehd->thd", x, gp["wk"])
    v = jnp.einsum("te,ehd->thd", x, gp["wv"])
    g = q.shape[1] // k.shape[1]
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    heads = []  # one KV head and its g query heads at a time: (g, T, T) scores
    for j in range(k.shape[1]):
        s = jnp.einsum("tgd,sd->gts", q[:, j * g:(j + 1) * g], k[:, j])
        s = jnp.where(causal, s * (q.shape[-1] ** -0.5), -jnp.inf)
        heads.append(jnp.einsum("gts,sd->tgd", jax.nn.softmax(s, -1), v[:, j]))
    a = jnp.concatenate(heads, axis=1)
    a = a * jax.nn.sigmoid((x @ gp["wg"]).reshape(T, H, D))
    return jnp.einsum("thd,hde->te", a, gp["wo"])


def _conv_silu(x, taps):
    """x (T, H, d), taps (K, H, d): sum_i taps[i] * x[t - i], then SiLU."""
    T = x.shape[0]
    y = sum(taps[i] * jnp.pad(x, ((i, 0), (0, 0), (0, 0)))[:T]
            for i in range(taps.shape[0]))
    return jax.nn.silu(y)


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


@functools.partial(jax.jit, static_argnames=("eps", "neg_eigval",
                                             "state_dtype"))
def _kda(x, kp, *, eps, neg_eigval, state_dtype=F32):
    kp = _f32(kp)
    H, d = kp["wo"].shape[:2]
    T = x.shape[0]
    # W_q, W_k, W_v and their taps lie side by side, each (.., H*d)
    w_q, w_k, w_v = jnp.split(kp["w_qkv"], 3, axis=-1)
    c_q, c_k, c_v = (c.reshape(-1, H, d)
                     for c in jnp.split(kp["conv"], 3, axis=-1))
    q = _conv_silu((x @ w_q).reshape(T, H, d), c_q)
    k = _conv_silu((x @ w_k).reshape(T, H, d), c_k)
    v = _conv_silu((x @ w_v).reshape(T, H, d), c_v)
    q, k = _unit(q) * d ** -0.5, _unit(k)
    z = jnp.einsum("tr,rhd->thd", x @ kp["f_down"], kp["f_up"])
    alpha = jnp.exp(-jnp.exp(kp["a_log"])[:, None]
                    * jax.nn.softplus(z + kp["dt_bias"]))
    beta = jax.nn.sigmoid(jnp.einsum("te,eh->th", x, kp["w_beta"]))
    if neg_eigval:
        beta = 2.0 * beta

    def token(S, row):  # S (H, d_k, d_v), one row after the other
        q_t, k_t, v_t, a_t, b_t = row
        S = a_t[:, :, None] * S                              # Diag(alpha) S
        kS = jnp.einsum("hk,hkv->hv", k_t, S)                # k^T S
        S = S + b_t[:, None, None] * k_t[:, :, None] * (v_t - kS)[:, None, :]
        S = _kept_as(S, state_dtype)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)           # S^T q

    _, o = jax.lax.scan(token, jnp.zeros((H, d, d), F32),
                        (q, k, v, alpha, beta))
    o = _rms(o, kp["o_norm"], eps)
    gate = jnp.einsum("tr,rhd->thd", x @ kp["g_down"], kp["g_up"])
    return jnp.einsum("thd,hde->te", o * jax.nn.sigmoid(gate), kp["wo"])


def logprobs(hf: dict, params: dict, tokens, first: int, *,
             state_dtype=F32, router_dtype=F32):
    """log p(. | tokens[:t+1]) for t in [first, len(tokens)), shape
    (len(tokens) - first, V). ``hf`` is the configuration file's dict.

    ``state_dtype`` and ``router_dtype`` are float32, as the
    configuration states. Lower ones are the controls of the comparison
    (``reference/control.py``, PERF.md section 2): the recurrent state
    kept in that type after every token, the router's scores computed in
    it; such a reference has to read as not correct."""
    check(hf)
    eps = float(hf["rms_norm_eps"])
    period = int(hf["gqa_interval"]) + 1
    routed = int(hf["n_routed_experts"])
    sparse = dict(
        top_k=int(hf["num_experts_per_tok"]),
        renormalise=bool(hf.get("norm_topk_prob", True)),
        scaling=float(hf.get("routed_scaling_factor", 1.0)),
        first=int(hf.get("routed_expert_offset", 0)),
        held=int(hf.get("n_routed_experts_held", routed)))

    def at(tree, i):
        return jax.tree_util.tree_map(lambda a: a[i], tree)

    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(F32)
        for i in range(int(hf["num_hidden_layers"])):
            lp = at(params["layers"], i)
            x = _rms(h, lp["attn_norm"].astype(F32), eps)
            if i % period == 0:
                h = h + _gqa(x, at(params["gqa"], i // period))
            else:
                j = (i // period) * (period - 1) + i % period - 1
                h = h + _kda(x, at(params["kda"], j), eps=eps,
                             neg_eigval=bool(hf.get("kda_allow_neg_eigval")),
                             state_dtype=state_dtype)
            x = _rms(h, lp["mlp_norm"].astype(F32), eps)
            h = h + _sparse(x, lp, router_dtype=router_dtype, **sparse)
        head = (params["embed"].T if hf.get("tie_word_embeddings")
                else params["lm_head"])
        return _head(h[first:], params["final_norm"], head, eps=eps)
