"""Phi-4-mini-flash-reasoning (``model_type: phi4flash``; the SambaY
decoder-hybrid-decoder of arXiv:2507.06607 with differential attention,
arXiv:2410.05258) forward pass, plain: straightforward ``jax.numpy`` in
float32 with "highest" matmul precision; no cache, no kernels, no batching,
no packing of head pairs. One sequence in, log-probabilities of every
position out.

    h = embed[tokens]                            (no positional encoding)
    for l in range(num_hidden_layers):
        h = h + Mixer_l(LayerNorm(h; ln1_l))
        h = h + W_down (silu(W_gate x) * W_up x),  x = LayerNorm(h; ln2_l)
    logits = LayerNorm(h; norm) @ embed^T        (tied head)

Which mixer a block has (``mb_per_layer`` 2; n = num_hidden_layers): even
blocks up to n / 2 are *Mamba* layers, odd ones below n / 2 + 1 *window
attention*, block n / 2 + 1 *full attention*; from block n / 2 + 2 on (the
cross-decoder) even blocks are *gated memory units* and odd ones
*cross-attention*.

*Mamba* (d_i = expand x hidden, N = d_state, R = dt_rank, conv width K):
[x; z] = W_in u; x <- silu(sum_i taps_i * x[t - i] + b_c) (depthwise,
causal); [r; B; C] = W_x x; Delta = softplus(W_dt r + b_dt); A = -exp(A_log);
state S (d_i, N), zero before the first token:

    S_t = exp(Delta_t A) * S_{t-1} + (Delta_t x_t) B_t^T
    y_t = S_t C_t + D * x_t

out = W_out (y_t * silu(z_t)). The loop over tokens is a loop, one row
after the other. Block n / 2 also hands m_t = y_t on (before the gate).

*Differential attention* (H query / KH key-value heads of D, in pairs
(2i, 2i + 1); query pair i reads key-value pair i // (H / KH)): with q_a,
k_a, v_a (a = 1, 2) a pair's heads, four softmax attentions as published,
``att(q, k, v) = softmax(q k^T / sqrt(D) + mask) v``:

    A1 = [att(q_1, k_1, v_1), att(q_1, k_1, v_2)]      (2 D wide)
    A2 = [att(q_2, k_2, v_1), att(q_2, k_2, v_2)]
    o = RMSNorm_2D(A1 - lambda A2) * (1 - lambda_init)
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    lambda_init = 0.8 - 0.6 exp(-0.3 l)                 (l: the block)

out = W_o concat(o) + b_o; W_q, W_k, W_v have biases. The mask is causal;
in a window layer row t sees rows t - sliding_window + 1 .. t (the window
counts the row itself). Scores are formed a block of rows at a time.

*Cross-attention*: the same with W_q, W_o, the lambdas and the norm of its
own, but k and v are block n / 2 + 1's (of the same sequence), causal, no
window. *Gated memory unit*: out = W_out (silu(W_in u) * m_t), m from
block n / 2, same token.

What the configuration file states: the widths, ``mb_per_layer``,
``sliding_window``, ``layer_norm_eps``, the tied head. Taken from the
family's published modelling code and the two papers and NOT from a key of
that file (the manifest's ``assumed`` says where each comes from): d_state
16, d_conv 4, expand 2, dt_rank ceil(hidden / 16); which block is which;
differential attention, its pairing and constants; biases on the attention
projections; no positional encoding.

Departures from the published description: none in the equations. Two of
storage, both exact: ``A_log`` lies (N, d_i), and W_gate / W_up are two
matrices where the checkpoint fuses them.

Parameters are the program's own pytree (``embed`` (V, E); ``layers``
(n, ...): attn_norm(_b), mlp_norm(_b), w_gate, w_up, w_down; ``mamba``,
``attn`` (the window layers, then the full one), ``gmu``, ``cross``: a
stack a kind, in depth order; ``final_norm(_b)``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference.qwen3 import F32, _rms
from chipbench.reference.solar_open2 import _f32, _kept_as

ROWS = 512  # rows of scores formed at a time


def kinds(hf: dict) -> list[str]:
    """Each block's mixer, from the published rule."""
    n, period = int(hf["num_hidden_layers"]), int(hf["mb_per_layer"])
    if period != 2:
        raise ValueError("mb_per_layer is not 2: not this reference")
    full = n // 2 + 1
    return [("mamba" if l < full else "gmu") if l % 2 == 0
            else "swa" if l < full else "full" if l == full else "cross"
            for l in range(n)]


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


@functools.partial(jax.jit, static_argnames=("state_dtype",))
def _mamba(u, mp, *, state_dtype=F32):
    """Returns (out (T, E), y (T, d_i))."""
    mp = _f32(mp)
    T, di = u.shape[0], mp["d"].shape[0]
    N = mp["a_log"].shape[0]
    xz = u @ mp["w_in"]
    x, z = xz[:, :di], xz[:, di:]
    taps = mp["conv"]
    x = jax.nn.silu(sum(taps[i] * jnp.pad(x, ((i, 0), (0, 0)))[:T]
                        for i in range(taps.shape[0])) + mp["conv_bias"])
    rbc = x @ mp["w_x"]
    R = rbc.shape[1] - 2 * N
    delta = jax.nn.softplus(rbc[:, :R] @ mp["w_dt"] + mp["dt_bias"])
    A = -jnp.exp(mp["a_log"]).T                       # (d_i, N)

    def token(S, row):  # S (d_i, N), one row after the other
        x_t, d_t, B_t, C_t = row
        S = jnp.exp(d_t[:, None] * A) * S + (d_t * x_t)[:, None] * B_t[None]
        S = _kept_as(S, state_dtype)
        return S, S @ C_t

    _, y = jax.lax.scan(token, jnp.zeros((di, N), F32),
                        (x, delta, rbc[:, R:R + N], rbc[:, R + N:]))
    y = y + mp["d"] * x
    return (y * jax.nn.silu(z)) @ mp["w_out"], y


def _att(q, k, v, window: int):
    """softmax(q k^T / sqrt(D) + mask) v for (T, P, D) heads, a block of
    rows at a time; ``window`` 0 = causal only."""
    T, scale = q.shape[0], q.shape[-1] ** -0.5
    out = []
    for r0 in range(0, T, ROWS):
        r1 = min(r0 + ROWS, T)
        t, s = jnp.arange(r0, r1)[:, None], jnp.arange(r1)[None, :]
        seen = s <= t
        if window:
            seen &= s > t - window
        sc = jnp.einsum("tpd,spd->pts", q[r0:r1], k[:r1]) * scale
        sc = jnp.where(seen, sc, -jnp.inf)
        out.append(jnp.einsum("pts,spd->tpd", jax.nn.softmax(sc, -1),
                              v[:r1]))
    return jnp.concatenate(out)


@jax.jit
def _keys_values(u, ap):
    ap = _f32(ap)
    D = ap["lambda_q1"].shape[0]
    k = (u @ ap["wk"] + ap["bk"]).reshape(u.shape[0], -1, 2, D)
    v = (u @ ap["wv"] + ap["bv"]).reshape(u.shape[0], -1, 2, D)
    return k, v



@functools.partial(jax.jit, static_argnames=("window", "eps"))
def _diff_attention(u, ap, k, v, lam_init, *, window, eps):
    """k, v (T, KH / 2, 2, D): the key-value pairs this layer reads."""
    ap = _f32(ap)
    T, D = u.shape[0], ap["lambda_q1"].shape[0]
    q = (u @ ap["wq"] + ap["bq"]).reshape(T, -1, 2, D)
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    q1, q2, k1, k2 = q[:, :, 0], q[:, :, 1], k[:, :, 0], k[:, :, 1]
    v1, v2 = v[:, :, 0], v[:, :, 1]
    a1 = jnp.concatenate([_att(q1, k1, v1, window), _att(q1, k1, v2, window)],
                         -1)
    a2 = jnp.concatenate([_att(q2, k2, v1, window), _att(q2, k2, v2, window)],
                         -1)
    lam = (jnp.exp(jnp.sum(ap["lambda_q1"] * ap["lambda_k1"]))
           - jnp.exp(jnp.sum(ap["lambda_q2"] * ap["lambda_k2"])) + lam_init)
    o = _rms(a1 - lam * a2, ap["subln"], eps) * (1.0 - lam_init)
    return o.reshape(T, -1) @ ap["wo"] + ap["bo"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _mlp(h, lp, *, eps):
    lp = _f32(lp)
    x = _ln(h, lp["mlp_norm"], lp["mlp_norm_b"], eps)
    return h + (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, bias, embed, *, eps):
    logits = _ln(x, norm.astype(F32), bias.astype(F32), eps) @ embed.astype(F32).T
    return jax.nn.log_softmax(logits, -1)


def logprobs(hf: dict, params: dict, tokens, first: int, *,
             state_dtype=F32):
    """log p(. | tokens[:t+1]) for t in [first, len(tokens)), shape
    (len(tokens) - first, V). ``hf`` is the configuration file's dict.

    ``state_dtype`` is float32, as the configuration states. A lower one
    is the control of the comparison (``reference/control.py bf16_state``):
    the scan's state kept in that type after every token."""
    eps = float(hf["layer_norm_eps"])
    window = int(hf["sliding_window"])

    def at(tree, i):
        return jax.tree_util.tree_map(lambda a: a[i], tree)

    seen = dict.fromkeys(("mamba", "attn", "gmu", "cross"), 0)
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(F32)
        for l, kind in enumerate(kinds(hf)):
            lp = at(params["layers"], l)
            u = _ln(h, lp["attn_norm"].astype(F32),
                    lp["attn_norm_b"].astype(F32), eps)
            stack = "attn" if kind in ("swa", "full") else kind
            mp = at(params[stack], seen[stack])
            seen[stack] += 1
            lam_init = 0.8 - 0.6 * math.exp(-0.3 * l)
            if kind == "mamba":
                out, y = _mamba(u, mp, state_dtype=state_dtype)
                if l == int(hf["num_hidden_layers"]) // 2:
                    m = y
            elif kind == "gmu":
                mp = _f32(mp)
                out = (jax.nn.silu(u @ mp["w_in"]) * m) @ mp["w_out"]
            else:
                if kind != "cross":
                    k, v = _keys_values(u, mp)
                    if kind == "full":
                        shared = (k, v)
                else:
                    k, v = shared
                out = _diff_attention(
                    u, mp, k, v, lam_init, eps=eps,
                    window=window if kind == "swa" else 0)
            h = _mlp(h + out, lp, eps=eps)
        return _head(h[first:], params["final_norm"], params["final_norm_b"],
                     params["embed"], eps=eps)
