"""Falcon-H1 (``model_type: falcon_h1``; a Mamba-2 state-space mixer with
heads and grouped-query attention side by side in every layer) forward
pass, plain: straightforward ``jax.numpy`` in float32 with "highest" matmul
precision; no cache, no kernels, no batching, no blocks of rows in the
scan. One sequence in, log-probabilities of every position out.

    h = embedding_multiplier * embed[tokens]
    for l in range(num_hidden_layers):
        n = RMSNorm(h; ln1_l)
        h = h + ssm_out_multiplier * SSM_l(n)
              + attention_out_multiplier * ATT_l(attention_in_multiplier * n)
        u = RMSNorm(h; ln2_l)
        h = h + mlp_multipliers[1] * W_down (W_up u * silu(mlp_multipliers[0] * W_gate u))
    logits = lm_head_multiplier * (RMSNorm(h; norm) @ lm_head)

BOTH mixers read the same ``n``; their outputs are summed before the one
residual add.

*ATT* (H query / KH key-value heads of D): q = W_q n, k = key_multiplier *
W_k n, v = W_v n; rotary embedding on q and k (half-split rotation,
``rope_theta``); softmax(q k^T / sqrt(D)) causal; W_o. No bias, no norm on
q or k, no gate, no window.

*SSM* (Hs heads of P channels, d_ssm = Hs P; N = ``mamba_d_state``; G =
``mamba_n_groups``; conv width K):

    p = W_in (ssm_in_multiplier * n);  [z | xBC | dt] = p * mup
        mup = ssm_multipliers[0..4] over the columns [z | x | B | C | dt]
    xBC <- silu(sum_i taps_i * xBC[t - i] + b_c)    (depthwise, causal, over
                                                     d_ssm + 2 G N channels)
    [x | B | C] = xBC;  head h reads B, C of group h // (Hs / G)
    d = softplus(dt + dt_bias);  A = -exp(A_log)            (one a head)
    S_t = exp(d_t A) S_{t-1} + (d_t x_t) B_t^T              (S: (P, N) a head,
    y_t = S_t C_t + D x_t                                    zero before token 0)
    u = y * silu(z);  u <- u / rms(u over each of the G groups of d_ssm / G
                            channels; eps rms_norm_eps) * w_norm
    SSM = W_out u

The loop over tokens is a loop, one row after the other.

Held against the family's published modelling code
(``transformers.models.falcon_h1``, its ``torch_forward`` path) at test
size in tests/test_falcon_h1.py: the same logits to ~1e-4 in float32.

Departures from the published code: none in the equations. Of computation
only: the MLP runs a block of its ``intermediate_size`` columns at a time
and the head a block of the vocabulary at a time (the sums and the
log-softmax are over all of them), so that the float32 copies of a
10.5 GB model's largest matrices never lie on the device whole; the
published code's ``mamba_chunk_size`` blocks its scan and changes no
result, this one has no blocks.

Parameters are the program's own pytree (``embed`` (V, E); ``layers``
(n, ...): attn_norm, mlp_norm, w_gate, w_up, w_down; ``gqa``: wq_t (n,
H D, E), wk_t, wv_t (n, KH D, E), the three TRANSPOSED, wo (n, H, D, E);
``ssd``: W_in as its [z | xBC] columns w_in (n, E, 2 d_ssm + 2 G N) and its
dt columns w_dt (n, E, Hs), conv (n, K, d_ssm + 2 G N) with tap 0 on the
current row,
conv_bias, dt_bias, a_log, d (n, Hs), norm (n, d_ssm), w_out (n, d_ssm, E);
``final_norm``; ``lm_head`` (E, V)).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.qwen3 import F32, _rms, _rope
from chipbench.reference.solar_open2 import _f32, _kept_as

MLP_COLUMNS = 8192    # of intermediate_size at a time
HEAD_COLUMNS = 32768  # of the vocabulary at a time


@functools.partial(jax.jit,
                   static_argnames=("sizes", "multipliers", "eps",
                                    "state_dtype"))
def _ssm(n, sp, *, sizes, multipliers, eps, state_dtype=F32):
    """SSM(n) (T, E), before ``ssm_out_multiplier``. ``sizes`` = (heads,
    P, N, groups); ``multipliers`` = (ssm_in, z, x, B, C, dt)."""
    sp = _f32(sp)
    T = n.shape[0]
    Hs, P, N, G = sizes
    di, gn = Hs * P, G * N
    m_in, mz, mx, mb, mc, mdt = multipliers
    p = (n * m_in) @ jnp.concatenate([sp["w_in"], sp["w_dt"]], axis=1)
    z = p[:, :di] * mz
    xbc = p[:, di:2 * di + 2 * gn] * jnp.concatenate(
        [jnp.full(di, mx, F32), jnp.full(gn, mb, F32), jnp.full(gn, mc, F32)])
    dt = p[:, 2 * di + 2 * gn:] * mdt
    taps = sp["conv"]
    xbc = jax.nn.silu(sum(taps[i] * jnp.pad(xbc, ((i, 0), (0, 0)))[:T]
                          for i in range(taps.shape[0])) + sp["conv_bias"])
    x = xbc[:, :di].reshape(T, Hs, P)
    B = jnp.repeat(xbc[:, di:di + gn].reshape(T, G, N), Hs // G, axis=1)
    C = jnp.repeat(xbc[:, di + gn:].reshape(T, G, N), Hs // G, axis=1)
    d = jax.nn.softplus(dt + sp["dt_bias"])               # (T, Hs)
    A = -jnp.exp(sp["a_log"])                             # (Hs,)

    def token(S, row):  # S (Hs, P, N), one row after the other
        x_t, d_t, B_t, C_t = row
        S = (jnp.exp(d_t * A)[:, None, None] * S
             + (d_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        S = _kept_as(S, state_dtype)
        return S, jnp.einsum("hpn,hn->hp", S, C_t)

    _, y = jax.lax.scan(token, jnp.zeros((Hs, P, N), F32), (x, d, B, C))
    y = (y + sp["d"][:, None] * x).reshape(T, di)
    u = (y * jax.nn.silu(z)).reshape(T, G, di // G)
    u = u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True) + eps)
    return (u.reshape(T, di) * sp["norm"]) @ sp["w_out"]


@functools.partial(jax.jit, static_argnames=("theta", "key_multiplier"))
def _att(n, gp, *, theta, key_multiplier):
    """ATT(n) (T, E), before ``attention_out_multiplier``."""
    gp = _f32(gp)
    T, D = n.shape[0], gp["wo"].shape[1]
    pos = jnp.arange(T)
    q = _rope((n @ gp["wq_t"].T).reshape(T, -1, D), pos, theta)
    k = _rope((n @ gp["wk_t"].T).reshape(T, -1, D) * key_multiplier,
              pos, theta)
    v = (n @ gp["wv_t"].T).reshape(T, -1, D)
    g = q.shape[1] // k.shape[1]
    causal = pos[:, None] >= pos[None, :]
    heads = []  # one KV head and its g query heads at a time
    for j in range(k.shape[1]):
        s = jnp.einsum("tgd,sd->gts", q[:, j * g:(j + 1) * g], k[:, j])
        s = jnp.where(causal, s * D ** -0.5, -jnp.inf)
        heads.append(jnp.einsum("gts,sd->tgd", jax.nn.softmax(s, -1),
                                v[:, j]))
    return jnp.einsum("thd,hde->te", jnp.concatenate(heads, axis=1), gp["wo"])


@functools.partial(jax.jit, static_argnames=("gate_multiplier",))
def _mlp_columns(u, w_gate, w_up, w_down, *, gate_multiplier):
    w_gate, w_up, w_down = _f32((w_gate, w_up, w_down))
    return ((u @ w_up) * jax.nn.silu((u @ w_gate) * gate_multiplier)) @ w_down


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(h, w, *, eps):
    return _rms(h, w.astype(F32), eps)


@jax.jit
def _logits_columns(x, head):
    return x @ head.astype(F32)


def logprobs(hf: dict, params: dict, tokens, first: int, *,
             state_dtype=F32):
    """log p(. | tokens[:t+1]) for t in [first, len(tokens)), shape
    (len(tokens) - first, V). ``hf`` is the configuration file's dict.

    ``state_dtype`` is float32, as the configuration states. A lower one
    is the control of the comparison (``reference/control.py bf16_state``):
    the scan's state kept in that type after every token."""
    eps, theta = float(hf["rms_norm_eps"]), float(hf["rope_theta"])
    gate_m, down_m = (float(m) for m in hf["mlp_multipliers"])
    sizes = tuple(int(hf[k]) for k in ("mamba_n_heads", "mamba_d_head",
                                       "mamba_d_state", "mamba_n_groups"))
    multipliers = (float(hf["ssm_in_multiplier"]),
                   *(float(m) for m in hf["ssm_multipliers"]))

    def at(tree, i):
        return jax.tree_util.tree_map(lambda a: a[i], tree)

    with jax.default_matmul_precision("highest"):
        h = (params["embed"][jnp.asarray(tokens)].astype(F32)
             * float(hf["embedding_multiplier"]))
        for l in range(int(hf["num_hidden_layers"])):
            lp = at(params["layers"], l)
            n = _normed(h, lp["attn_norm"], eps=eps)
            h = (h
                 + float(hf["ssm_out_multiplier"]) * _ssm(
                     n, at(params["ssd"], l), sizes=sizes,
                     multipliers=multipliers, eps=eps,
                     state_dtype=state_dtype)
                 + float(hf["attention_out_multiplier"]) * _att(
                     n * float(hf["attention_in_multiplier"]),
                     at(params["gqa"], l), theta=theta,
                     key_multiplier=float(hf["key_multiplier"])))
            u = _normed(h, lp["mlp_norm"], eps=eps)
            F = lp["w_gate"].shape[-1]
            h = h + down_m * sum(
                _mlp_columns(u, lp["w_gate"][:, c:c + MLP_COLUMNS],
                             lp["w_up"][:, c:c + MLP_COLUMNS],
                             lp["w_down"][c:c + MLP_COLUMNS],
                             gate_multiplier=gate_m)
                for c in range(0, F, MLP_COLUMNS))
        x = _normed(h[first:], params["final_norm"], eps=eps)
        head = (params["embed"].T if hf.get("tie_word_embeddings")
                else params["lm_head"])
        logits = jnp.concatenate(
            [_logits_columns(x, head[:, c:c + HEAD_COLUMNS])
             for c in range(0, head.shape[1], HEAD_COLUMNS)], axis=1)
        return jax.nn.log_softmax(logits * float(hf["lm_head_multiplier"]),
                                  -1)
