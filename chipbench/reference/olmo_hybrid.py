"""Olmo-Hybrid (``model_type: olmo_hybrid``; Gated DeltaNet layers three to
one with full multi-head attention, in the Olmo 3 family's block) forward
pass, plain: straightforward ``jax.numpy`` in float32 with "highest" matmul
precision; no cache, no kernels, no batching, no blocks of rows in the
recurrence. One sequence in, log-probabilities of every position out.

    h = embed[tokens]
    for l in range(num_hidden_layers):
        h = h + RMSNorm(MIX_l(h); post_attention)         (no norm BEFORE a
        h = h + RMSNorm(W_down(silu(W_gate h) * W_up h); post_feedforward)
    logits = RMSNorm(h; norm) @ lm_head                     sublayer)

``layer_types[l]`` says which ``MIX``.

*full_attention* (H = KH heads of D): q = RMSNorm(W_q h; q_norm), k =
RMSNorm(W_k h; k_norm), each over the WHOLE projection (H D values, a
weight each), v = W_v h; NOTHING rotated (``rope_parameters.rope_theta`` is
null); softmax(q k^T / sqrt(D)) causal; W_o. No bias, no gate, no window.

*linear_attention* (Gated DeltaNet; H heads, d_k =
``linear_key_head_dim``, d_v = ``linear_value_head_dim``, conv width K):

    [q | k | v] <- silu(sum_i taps_i * [W_q h | W_k h | W_v h][t - i])
                                        (depthwise, causal, no bias)
    q = q / |q| * d_k^-1/2,  k = k / |k|     (per head; |.|^2 + 1e-6)
    beta = sigmoid(W_b h) (x 2 with ``linear_allow_neg_eigval``)
    g = -exp(A_log) * softplus(W_a h + dt_bias)        (one a head)
    S <- exp(g) S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q
                             (S: (d_k, d_v) a head, zero before token 0)
    o <- RMSNorm(o; w over d_v) * silu(W_g h);   MIX = W_o o

The loop over tokens is a loop, one row after the other, in the published
order of operations (``torch_recurrent_gated_delta_rule``).

``transformers.models.olmo_hybrid`` is not installed here, so the pieces
are held against the published code that IS (tests/test_olmo_hybrid.py):
``recurrence`` against ``transformers.models.qwen3_next``'s
``torch_recurrent_gated_delta_rule(..., use_qk_l2norm_in_kernel=True)``
with beta doubled, ``gated_norm`` against ``Qwen3NextRMSNormGated``, the
attention layer (QK-norm, norms after the sublayers) against
``transformers.models.olmo3``'s ``Olmo3DecoderLayer`` with the rotation
set to the identity.

Departures from the published description: none in the equations. What is
the family's convention and not in the file (the block's norm placement and
the QK-norm: Olmo 3's code; no positional encoding: the null
``rope_theta``; the conv without a bias and the gated norm's form and eps:
Qwen3-Next's code) is listed under ``assumed`` in the configuration's
manifest. Of computation only: the MLP runs a block of its
``intermediate_size`` columns at a time and the head a block of the
vocabulary at a time (the sums and the log-softmax are over all of them),
so that the float32 copies of an 8 GB model's largest matrices never lie
on the device whole; attention scores one head at a time.

Parameters are the program's own pytree (``embed`` (V, E); ``layers``
(n, ...): post_attn_norm, post_mlp_norm, w_gate, w_up, w_down; ``gqa``
(full layers, ...): wq (E, H D), wk, wv (E, KH, D), wo (H, D, E), q_norm
(H, D), k_norm (KH, D); ``gdn`` (linear layers, ...): w_qkv (E, 2 H d_k +
H d_v) = [q | k | v], conv (K, the same) with tap 0 on the current row,
w_a, w_b (E, H), a_log, dt_bias (H,), w_g_t (H d_v, E), TRANSPOSED,
o_norm (d_v,), wo (H d_v, E); ``final_norm``; ``lm_head`` (E, V)).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.qwen3 import F32, _rms
from chipbench.reference.solar_open2 import _f32, _kept_as

MLP_COLUMNS = 8192    # of intermediate_size at a time
HEAD_COLUMNS = 32768  # of the vocabulary at a time
KINDS = {"linear_attention": "gdn", "full_attention": "gqa"}


def recurrence(q, k, v, g, beta, state_dtype=F32):
    """The gated delta rule, one row after the other: q, k (T, H, d_k)
    after the L2 norm (q scaled), v (T, H, d_v), g (T, H) the log-decay,
    beta (T, H). Returns o (T, H, d_v). ``state_dtype`` below float32 (the
    control): the state, the decay, beta and the delta are kept in it."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def kept(x):
        return _kept_as(x, state_dtype)

    def token(S, row):  # S (H, d_k, d_v)
        q_t, k_t, v_t, g_t, b_t = row
        S = kept(S * kept(jnp.exp(g_t))[:, None, None])
        mem = jnp.einsum("hkv,hk->hv", S, k_t)
        delta = kept((v_t - mem) * kept(b_t)[:, None])
        S = kept(S + k_t[:, :, None] * delta[:, None, :])
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    return jax.lax.scan(token, jnp.zeros((H, dk, dv), F32),
                        (q, k, v, g, beta))[1]


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def gated_norm(o, gate, weight, eps):
    """Norm, weight, THEN silu(gate)."""
    return _rms(o, weight, eps) * jax.nn.silu(gate)


@functools.partial(jax.jit, static_argnames=("sizes", "eps", "neg_eigval",
                                             "state_dtype"))
def _gdn(x, gp, *, sizes, eps, neg_eigval, state_dtype=F32):
    """MIX(x) (T, E) of a linear_attention layer. ``sizes`` = (H, d_k,
    d_v)."""
    gp = _f32(gp)
    T = x.shape[0]
    H, dk, dv = sizes
    rows = x @ gp["w_qkv"]
    taps = gp["conv"]
    rows = jax.nn.silu(sum(taps[i] * jnp.pad(rows, ((i, 0), (0, 0)))[:T]
                           for i in range(taps.shape[0])))
    q = rows[:, :H * dk].reshape(T, H, dk)
    k = rows[:, H * dk:2 * H * dk].reshape(T, H, dk)
    v = rows[:, 2 * H * dk:].reshape(T, H, dv)
    beta = jax.nn.sigmoid(x @ gp["w_b"]) * (2.0 if neg_eigval else 1.0)
    g = -jnp.exp(gp["a_log"]) * jax.nn.softplus(x @ gp["w_a"]
                                                + gp["dt_bias"])
    o = recurrence(l2norm(q) * dk ** -0.5, l2norm(k), v, g, beta,
                   state_dtype)
    o = gated_norm(o, (x @ gp["w_g_t"].T).reshape(T, H, dv), gp["o_norm"], eps)
    return o.reshape(T, H * dv) @ gp["wo"]


def _rms_whole(x, w, eps):
    """RMSNorm of (T, H D) over the whole projection; w is (H, D)."""
    return _rms(x, w.reshape(-1), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _attention(x, ap, *, eps):
    """MIX(x) (T, E) of a full_attention layer."""
    ap = _f32(ap)
    T = x.shape[0]
    H, D = ap["wo"].shape[:2]
    q = _rms_whole(x @ ap["wq"], ap["q_norm"], eps).reshape(T, H, D)
    k = _rms_whole(x @ ap["wk"].reshape(x.shape[1], -1), ap["k_norm"],
                   eps).reshape(T, -1, D)
    v = jnp.einsum("te,ehd->thd", x, ap["wv"])
    g = H // k.shape[1]
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    heads = []  # one KV head and its g query heads at a time
    for j in range(k.shape[1]):
        s = jnp.einsum("tgd,sd->gts", q[:, j * g:(j + 1) * g], k[:, j])
        s = jnp.where(causal, s * D ** -0.5, -jnp.inf)
        heads.append(jnp.einsum("gts,sd->tgd", jax.nn.softmax(s, -1),
                                v[:, j]))
    return jnp.einsum("thd,hde->te", jnp.concatenate(heads, axis=1),
                      ap["wo"])


@jax.jit
def _mlp_columns(x, w_gate, w_up, w_down):
    w_gate, w_up, w_down = _f32((w_gate, w_up, w_down))
    return ((x @ w_up) * jax.nn.silu(x @ w_gate)) @ w_down


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(h, w, *, eps):
    return _rms(h, w.astype(F32), eps)


@jax.jit
def _logits_columns(x, head):
    return x @ head.astype(F32)


def block(h, mix_out, lp, eps):
    """The Olmo 3 block from its mixer's output on: h + norm(MIX), then
    h + norm(MLP(h)), the MLP reading the stream as it is."""
    h = h + _normed(mix_out, lp["post_attn_norm"], eps=eps)
    F = lp["w_gate"].shape[-1]
    mlp = sum(_mlp_columns(h, lp["w_gate"][:, c:c + MLP_COLUMNS],
                           lp["w_up"][:, c:c + MLP_COLUMNS],
                           lp["w_down"][c:c + MLP_COLUMNS])
              for c in range(0, F, MLP_COLUMNS))
    return h + _normed(mlp, lp["post_mlp_norm"], eps=eps)


def logprobs(hf: dict, params: dict, tokens, first: int, *,
             state_dtype=F32):
    """log p(. | tokens[:t+1]) for t in [first, len(tokens)), shape
    (len(tokens) - first, V). ``hf`` is the configuration file's dict.

    ``state_dtype`` is float32, as the configuration states. A lower one
    is the control of the comparison (``reference/control.py bf16_state``):
    the delta rule computed in that type, i.e. its state after every token
    AND what moves it (the decay exp(g), beta, the delta), all of which the
    configuration states as float32; everything else stays float32."""
    eps = float(hf["rms_norm_eps"])
    sizes = (int(hf["linear_num_value_heads"]),
             int(hf["linear_key_head_dim"]), int(hf["linear_value_head_dim"]))
    neg = bool(hf.get("linear_allow_neg_eigval", False))

    def at(tree, i):
        return jax.tree_util.tree_map(lambda a: a[i], tree)

    seen = {"gdn": 0, "gqa": 0}
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(F32)
        for l, kind in enumerate(KINDS[t] for t in hf["layer_types"]):
            mp = at(params[kind], seen[kind])
            seen[kind] += 1
            mix = (_gdn(h, mp, sizes=sizes, eps=eps, neg_eigval=neg,
                        state_dtype=state_dtype) if kind == "gdn"
                   else _attention(h, mp, eps=eps))
            h = block(h, mix, at(params["layers"], l), eps)
        x = _normed(h[first:], params["final_norm"], eps=eps)
        head = params["lm_head"]
        logits = jnp.concatenate(
            [_logits_columns(x, head[:, c:c + HEAD_COLUMNS])
             for c in range(0, head.shape[1], HEAD_COLUMNS)], axis=1)
        return jax.nn.log_softmax(logits, -1)
