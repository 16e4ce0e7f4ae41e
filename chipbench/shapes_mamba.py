"""Operations and bytes of the decoder-hybrid-decoder stack (SambaY,
``model_type: phi4flash``: Mamba-1 state-space layers, differential
attention within a window and without, cross-attention layers that read
the one full-attention cache, gated memory units), computed from shapes,
for roofline shares. Kept with the benchmark so that no PR that claims a
gain can change them. ``shapes.py`` is the dense stack's, ``shapes_kda.py``
the KDA hybrid's.

Keys are those of the configuration file, the published config.json's
(``hidden_size``, ``intermediate_size``, ``num_attention_heads``,
``num_key_value_heads``, ``num_hidden_layers``, ``mb_per_layer``,
``sliding_window``, ``vocab_size``). The state-space sizes are not keys of
that file: the family's modelling code's defaults (d_state 16, d_conv 4,
expand 2, dt_rank ceil(hidden / 16)), read from the file where it gives
them. Only bytes that must move are counted: a floor is a lower bound. A
token's keys and values count at the published 2 x heads x head size (the
program fills the head pairs up to whole tiles and moves a fifth more).
"""

from __future__ import annotations

from collections import Counter

from chipbench.shapes_kda import mean_live_slots  # noqa: F401  (readers)

BF16, F32 = 2, 4


def layer_kinds(hf: dict) -> list[str]:
    """Each block's mixer: even blocks are state-space layers up to block
    n / 2 and gated memory units from n / 2 + 2; odd ones window attention
    below n / 2 + 1, the one full-attention layer there, cross-attention
    above."""
    n = int(hf["num_hidden_layers"])
    full = n // 2 + 1
    return [("mamba" if l < full else "gmu") if l % 2 == 0
            else "swa" if l < full else "full" if l == full else "cross"
            for l in range(n)]


def layer_counts(hf: dict) -> Counter:
    return Counter(layer_kinds(hf))


def _ssm(hf: dict) -> tuple[int, int, int, int]:
    """(d_i, N, conv width, dt rank) of the state-space layers."""
    e = int(hf["hidden_size"])
    return (int(hf.get("mamba_expand", 2)) * e,
            int(hf.get("mamba_d_state", 16)), int(hf.get("mamba_d_conv", 4)),
            int(hf.get("mamba_dt_rank") or -(-e // 16)))


def _head_dim(hf: dict) -> int:
    return int(hf.get("head_dim")
               or hf["hidden_size"] // hf["num_attention_heads"])


# -- parameters ---------------------------------------------------------------

def mlp_params(hf: dict) -> int:
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def mamba_params(hf: dict) -> int:
    """W_in, the conv taps and bias, W_x, W_dt and its bias, A_log, D,
    W_out."""
    e = hf["hidden_size"]
    di, n, k, r = _ssm(hf)
    return (e * 2 * di + (k + 1) * di + di * (r + 2 * n) + (r + 1) * di
            + di * n + di + di * e)


def cross_params(hf: dict) -> int:
    """W_q and W_o with their biases, four lambda vectors, the sub-norm."""
    e, d = hf["hidden_size"], _head_dim(hf)
    hd = hf["num_attention_heads"] * d
    return e * hd + hd + hd * e + e + 4 * d + 2 * d


def attn_params(hf: dict) -> int:
    """A cross layer's, and W_k and W_v with their biases."""
    kd = hf["num_key_value_heads"] * _head_dim(hf)
    return cross_params(hf) + 2 * (hf["hidden_size"] * kd + kd)


def gmu_params(hf: dict) -> int:
    return 2 * hf["hidden_size"] * _ssm(hf)[0]


def mixer_params(hf: dict) -> dict:
    return {"mamba": mamba_params(hf), "swa": attn_params(hf),
            "full": attn_params(hf), "gmu": gmu_params(hf),
            "cross": cross_params(hf)}


def total_params(hf: dict) -> int:
    """Every block's mixer and MLP, the (tied) embedding once; norms left
    out."""
    per = mixer_params(hf)
    return (sum(per[k] + mlp_params(hf) for k in layer_kinds(hf))
            + hf["vocab_size"] * hf["hidden_size"])


# -- the state-space kernels --------------------------------------------------

def state_bytes_per_slot(hf: dict) -> int:
    """One layer's scan state of one decode slot, float32."""
    di, n, _, _ = _ssm(hf)
    return F32 * n * di


def conv_tail_bytes_per_slot(hf: dict) -> int:
    di, _, k, _ = _ssm(hf)
    return BF16 * (k - 1) * di


def _row_bytes(hf: dict) -> int:
    """A token's float32 rows in and out of a scan kernel: x, Delta and y
    (d_i each), B and C (N each)."""
    di, n, _, _ = _ssm(hf)
    return F32 * (3 * di + 2 * n)


def mamba_decode_floor_s(hf: dict, live_slots: float, peaks: dict) -> float:
    """Least time of one ``mamba_decode_step`` call (one layer, one token a
    slot): each live slot's state read once and written once, its rows,
    and A once. Bound by bytes: 2 x 328 kB a slot against ~0.4 M
    elementwise operations."""
    nbytes = (live_slots * (2 * state_bytes_per_slot(hf) + _row_bytes(hf))
              + state_bytes_per_slot(hf))
    return nbytes / peaks["hbm_bytes_per_s"]


def mamba_chunk_floor_s(hf: dict, tokens: float, spans: float,
                        peaks: dict) -> float:
    """Least time of one ``mamba_chunk_scan`` call (one layer of one ragged
    step) from its bytes: a state read and written per span the kernel
    carries, the rows of those spans' tokens, A once, over the HBM peak.
    The scan is elementwise work and ``peaks.json`` has no vector rate, so
    this is the memory floor alone: the share reads low while the scan is
    bound by its row steps, and can never pass 100."""
    nbytes = (spans * 2 * state_bytes_per_slot(hf) + tokens * _row_bytes(hf)
              + state_bytes_per_slot(hf))
    return nbytes / peaks["hbm_bytes_per_s"]


# -- the whole decode step ----------------------------------------------------

def kv_bytes_per_token_layer(hf: dict) -> int:
    """Keys and values a token holds in ONE attention layer that owns
    them, as published."""
    return BF16 * 2 * hf["num_key_value_heads"] * _head_dim(hf)


def decode_step_bytes(hf: dict, live_slots: float, window_tokens: float,
                      full_tokens: float) -> float:
    """Bytes one decode step must move: every block's mixer and MLP and
    the head once; the live slots' scan state and conv tails read and
    written in every state-space layer; ``window_tokens`` (the rows the
    live sequences hold in the window layers' pool, at most ~a window
    each) once a window layer; ``full_tokens`` (their whole contexts) once
    for the full-attention layer and once more for EVERY cross-attention
    layer, which reads the same rows again."""
    counts = layer_counts(hf)
    state = counts["mamba"] * live_slots * 2 * (
        state_bytes_per_slot(hf) + conv_tail_bytes_per_slot(hf))
    row = kv_bytes_per_token_layer(hf)
    return (BF16 * total_params(hf) + state
            + counts["swa"] * window_tokens * row
            + (1 + counts["cross"]) * full_tokens * row)


def decode_step_floor_s(hf: dict, live_slots: float, window_tokens: float,
                        full_tokens: float, hbm_bytes_per_s: float) -> float:
    return decode_step_bytes(hf, live_slots, window_tokens,
                             full_tokens) / hbm_bytes_per_s
