"""afmoe (Arcee Trinity: window and full attention mixed by layer, gated,
sandwich norms, sigmoid-routed sparse MLP) forward pass, plain:
straightforward ``jax.numpy`` in float32 with "highest" matmul precision;
no cache, no kernels, no batching, no sort. One sequence in,
log-probabilities of every position out. With N = RMSNorm:

    h = embed[tokens] * sqrt(hidden_size)                  (mup_enabled)
    for l in range(num_hidden_layers):
        h = h + N(Attn_l(N(h; input_layernorm_l)); post_attention_layernorm_l)
        h = h + N(F_l(N(h; pre_mlp_layernorm_l)); post_mlp_layernorm_l)
    logits = N(h; norm) @ lm_head

*Attention* (H query heads, KH key/value heads, head size D): q, k, v, g =
W_q x, W_k x, W_v x, W_g x, no biases; q, k <- RMSNorm over D, a head at a
time, one weight a projection. Where ``layer_types[l]`` is
``sliding_attention``: q and k are rotated (half-split rotation over the
whole head, ``rope_theta``, no scaling) and row t sees rows s with
t - sliding_window < s <= t (the row itself counted). Where it is
``full_attention``: NOTHING is rotated and row t sees every s <= t. Masks
are built from positions. a = softmax(q k^T / sqrt(D) + mask) v;
out = W_o (a * sigmoid(g)), elementwise over the H * D outputs.

*F_l*, l < ``num_dense_layers``: SwiGLU of width ``intermediate_size``.
Else the sparse block: s = sigmoid(W_r x) over all ``num_experts`` in
float32; the ``num_experts_per_tok`` largest of s + b are chosen (b: a
per-expert selection bias, used to choose only); w = s[chosen] / (sum +
1e-20) (``route_norm``) x ``route_scale``, zero off the chosen. EVERY
expert held is applied to EVERY position and weighted by w, plus the shared
expert (SwiGLU of ``moe_intermediate_size``, like every routed one).

What the configuration file states: the widths, ``layer_types``,
``sliding_window``, ``rope_theta``, ``num_dense_layers``, 256 experts of
3072, 4 a token, 1 shared, ``score_func`` sigmoid, ``route_norm``,
``route_scale``, ``mup_enabled``, ``n_group`` 1. Taken from the family's
published modelling code (``modeling_afmoe.py``) and NOT from a key of that
file (the manifest's ``assumed`` says each): the gate and where it sits,
QK-norm a head before the rotation, that full layers rotate nothing, the
four norms a block, the bias choosing and not weighing, the embedding
factor sqrt(hidden_size).

Departures, each noted at its line below, each a cut of the run and not of
the equations: the engine holds ``n_routed_experts_held`` of the routed
experts from ``routed_expert_offset`` (the share of one chip of sixteen),
so the sum over experts runs over those alone, still weighted by the
routing over all of them: the other chips' terms are absent on both sides;
the vocabulary is the configuration file's (a slice of the published one),
so the log-softmax is over the slice; attention is computed a block of
query rows at a time (the same numbers; 6,208 rows x 6,208 keys x 48 heads
of scores at once do not fit beside the weights).

Parameters are the program's own pytree (``embed`` (V, E); ``gqa`` by
layer: ``wq``, ``wg`` (L, E, H*D) (columns by head), ``wk``/``wv``
(L, E, KH, D), ``wo`` (L, H, D, E), ``q_norm``/``k_norm`` (L, D);
``dense`` by dense layer: the four norms (Ld, E), ``w_gate``/``w_up``
(Ld, E, F), ``w_down`` (Ld, F, E); ``layers`` by expert layer: the four
norms, ``router`` (Le, E, X), ``router_bias`` (Le, X), ``w_gate``/``w_up``
(Le, Xh, E, Fm), ``w_down`` (Le, Xh, Fm, E), ``shared_*``; ``final_norm``;
``lm_head`` (E, V)), upcast one layer at a time so no second copy of the
model exists on the device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.qwen3 import F32, _head
from chipbench.reference.solar_open2 import _f32, _kept_as, _swiglu

ROWS = 512  # query rows a block of attention scores


def check(hf: dict) -> None:
    """Refuse what the equations above do not describe."""
    period = int(hf["global_attn_every_n_layers"])
    want = ["full_attention" if l % period == period - 1
            else "sliding_attention"
            for l in range(int(hf["num_hidden_layers"]))]
    if list(hf["layer_types"]) != want:
        raise ValueError("layer_types is not sliding layers closed by one "
                         "full layer in every period")
    if hf.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is set")
    if any(int(hf.get(k, 1) or 1) > 1 for k in ("n_group", "topk_group")):
        raise ValueError("group-limited routing")
    if hf.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError("score_func is not sigmoid")


def _norm(x, w, eps, dtype=F32):
    """RMSNorm; ``dtype``: the type its statistics are kept in (float32 as
    the configuration states; lower: the control)."""
    k = functools.partial(_kept_as, dtype=dtype)
    return k(x * k(jax.lax.rsqrt(k(jnp.mean(k(x * x), -1, keepdims=True))
                                 + eps))) * w


def _rope(x, pos, theta, dtype=F32):
    """Half-split rotation over the whole head (``qwen3._rope`` with the
    angles' type a parameter); ``dtype``: the type the angles (position x
    inverse frequency) are kept in (float32 as the configuration states;
    lower: the control, where no odd position past 256 is held)."""
    k = functools.partial(_kept_as, dtype=dtype)
    d = x.shape[-1]
    inv = k(1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d)))
    ang = k(k(pos.astype(F32))[:, None] * inv)              # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("window", "rotate", "theta",
                                             "eps", "state_dtype"))
def _attention(x, gp, *, window, rotate, theta, eps, state_dtype=F32):
    """``window`` 0: every row up to the query's own."""
    gp = _f32(gp)
    T = x.shape[0]
    H, D = gp["wo"].shape[:2]
    keep = functools.partial(_kept_as, dtype=state_dtype)
    q = (x @ gp["wq"]).reshape(T, H, D)
    k = jnp.einsum("te,ehd->thd", x, gp["wk"])
    v = jnp.einsum("te,ehd->thd", x, gp["wv"])
    q = _norm(q, gp["q_norm"], eps, state_dtype)
    k = _norm(k, gp["k_norm"], eps, state_dtype)
    if rotate:
        pos = jnp.arange(T)
        q = _rope(q, pos, theta, state_dtype)
        k = _rope(k, pos, theta, state_dtype)
    g = H // k.shape[1]
    out = []
    # departure: a block of query rows at a time (the same numbers)
    for r0 in range(0, T, ROWS):
        r1 = min(r0 + ROWS, T)
        s0 = max(0, r0 - window + 1) if window else 0
        t, s = jnp.arange(r0, r1)[:, None], jnp.arange(s0, r1)[None, :]
        seen = s <= t
        if window:
            seen &= s > t - window
        heads = []  # one KV head and its g query heads at a time
        for j in range(k.shape[1]):
            sc = jnp.einsum("tgd,sd->gts", q[r0:r1, j * g:(j + 1) * g],
                            k[s0:r1, j]) * D ** -0.5
            p = keep(jax.nn.softmax(jnp.where(seen, keep(sc), -jnp.inf), -1))
            heads.append(keep(jnp.einsum("gts,sd->tgd", p, v[s0:r1, j])))
        out.append(jnp.concatenate(heads, axis=1))
    a = jnp.concatenate(out)
    a = a * jax.nn.sigmoid((x @ gp["wg"]).reshape(T, H, D))
    return jnp.einsum("thd,hde->te", a, gp["wo"])


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
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    rows = jnp.arange(x.shape[0])[:, None]
    # departure: the chip's share, experts [first, first + held) alone
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
def _dense(x, lp):
    lp = _f32(lp)
    return _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def logprobs(hf: dict, params: dict, tokens, first: int, *,
             state_dtype=F32, router_dtype=F32, ignore_window: bool = False):
    """log p(. | tokens[:t+1]) for t in [first, len(tokens)), shape
    (len(tokens) - first, V). ``hf`` is the configuration file's dict.

    ``state_dtype`` and ``router_dtype`` are float32, as the configuration
    states. Lower ones are the controls of the comparison
    (``reference/control.py``, PERF.md section 2): the softmax's scores,
    probabilities and sums, every norm's statistics and the rotation's
    angles kept in that type, the router's scores computed in it. ``ignore_window`` is the control of
    what this family adds (``reference/control_window.py``): the window
    layers see every row. Such a reference has to read as not correct."""
    check(hf)
    eps, theta = float(hf["rms_norm_eps"]), float(hf["rope_theta"])
    window = 0 if ignore_window else int(hf["sliding_window"])
    dense = int(hf.get("num_dense_layers", 0))
    routed = int(hf["num_experts"])
    sparse = dict(
        top_k=int(hf["num_experts_per_tok"]),
        renormalise=bool(hf.get("route_norm", True)),
        scaling=float(hf.get("route_scale", 1.0)),
        first=int(hf.get("routed_expert_offset", 0)),
        held=int(hf.get("n_routed_experts_held", routed)))
    norm = functools.partial(_norm, eps=eps, dtype=state_dtype)

    def at(tree, i):
        return jax.tree_util.tree_map(lambda a: a[i], tree)

    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(F32)
        if hf.get("mup_enabled"):
            h = h * float(hf["hidden_size"]) ** 0.5
        for l, kind in enumerate(hf["layer_types"]):
            lp = (at(params["dense"], l) if l < dense
                  else at(params["layers"], l - dense))
            swa = kind == "sliding_attention"
            a = _attention(norm(h, lp["attn_norm"].astype(F32)),
                           at(params["gqa"], l), window=window if swa else 0,
                           rotate=swa, theta=theta, eps=eps,
                           state_dtype=state_dtype)
            h = h + norm(a, lp["post_attn_norm"].astype(F32))
            x = norm(h, lp["mlp_norm"].astype(F32))
            f = (_dense(x, lp) if l < dense
                 else _sparse(x, lp, router_dtype=router_dtype, **sparse))
            h = h + norm(f, lp["post_mlp_norm"].astype(F32))
        # departure: the log-softmax is over the file's slice of the
        # vocabulary
        return _head(h[first:], params["final_norm"], params["lm_head"],
                     eps=eps)
