"""Operations and bytes of the parallel hybrid stack (Falcon-H1,
``model_type: falcon_h1``: a Mamba-2 state-space mixer with heads and
grouped-query attention side by side in every layer), computed from
shapes, for roofline shares. Kept with the benchmark so that no PR that
claims a gain can change them. ``shapes.py`` is the dense stack's,
``shapes_mamba.py`` the Mamba-1 stack's.

Keys are those of the configuration file, the published config.json's
(``hidden_size``, ``intermediate_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``num_hidden_layers``,
``vocab_size``, ``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``,
``mamba_n_groups``, ``mamba_d_conv``). Only bytes that must move and
operations the recurrence is are counted, the SAME work whatever implements
the kernels: a floor is a lower bound, and a blocked form of the scan does
no less of either.
"""

from __future__ import annotations

from chipbench.shapes_kda import mean_live_slots  # noqa: F401  (readers)

BF16, F32 = 2, 4


def _ssd(hf: dict) -> tuple[int, int, int, int, int]:
    """(heads, P, N, groups, conv width) of the state-space mixer."""
    return (int(hf["mamba_n_heads"]), int(hf["mamba_d_head"]),
            int(hf["mamba_d_state"]), int(hf.get("mamba_n_groups", 1)),
            int(hf.get("mamba_d_conv", 4)))


def conv_channels(hf: dict) -> int:
    """[x | B | C]: what the short convolution runs over."""
    h, p, n, g, _ = _ssd(hf)
    return h * p + 2 * g * n


# -- parameters ---------------------------------------------------------------

def ssd_params(hf: dict) -> int:
    """W_in ([z | xBC | dt]), the conv taps and bias, A_log, D, dt_bias,
    the gated norm's weight, W_out."""
    e = hf["hidden_size"]
    h, p, _, _, k = _ssd(hf)
    cd = conv_channels(hf)
    return (e * (h * p + cd + h) + (k + 1) * cd + 3 * h + h * p
            + h * p * e)


def attn_params(hf: dict) -> int:
    """W_q, W_o; W_k, W_v. No bias, no norm."""
    e, d = hf["hidden_size"], int(hf["head_dim"])
    return 2 * e * hf["num_attention_heads"] * d \
        + 2 * e * hf["num_key_value_heads"] * d


def mlp_params(hf: dict) -> int:
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def layer_params(hf: dict) -> int:
    """Both mixers, the MLP and the two norms."""
    return (ssd_params(hf) + attn_params(hf) + mlp_params(hf)
            + 2 * hf["hidden_size"])


def head_params(hf: dict) -> int:
    return hf["vocab_size"] * hf["hidden_size"]


def total_params(hf: dict) -> int:
    """Every layer, the embedding, the untied head and the final norm."""
    return (hf["num_hidden_layers"] * layer_params(hf)
            + 2 * head_params(hf) + hf["hidden_size"])


# -- the state-space kernels --------------------------------------------------

def state_bytes_per_slot(hf: dict) -> int:
    """One layer's scan state of one decode slot, float32."""
    h, p, n, _, _ = _ssd(hf)
    return F32 * h * p * n


def conv_tail_bytes_per_slot(hf: dict) -> int:
    return BF16 * (_ssd(hf)[4] - 1) * conv_channels(hf)


def kv_bytes_per_token(hf: dict) -> int:
    """Keys and values a token holds, all layers, as published."""
    return (BF16 * 2 * hf["num_key_value_heads"] * int(hf["head_dim"])
            * hf["num_hidden_layers"])


def _row_bytes(hf: dict) -> int:
    """A token's float32 rows in and out of a scan kernel: d x and y (H P
    each), B and C (G N each), the decay (H)."""
    h, p, n, g, _ = _ssd(hf)
    return F32 * (2 * h * p + 2 * g * n + h)


def scan_flops(hf: dict, tokens: float) -> float:
    """The recurrence itself: a token and head is the update (a multiply
    and an add a state value) and the read-out (another pair), 4 P N."""
    h, p, n, _, _ = _ssd(hf)
    return tokens * h * 4 * p * n


def ssd_decode_floor_s(hf: dict, live_slots: float, peaks: dict) -> float:
    """Least time of one ``ssd_decode_step`` call (one layer, one token a
    slot): each live slot's state read once and written once, and its
    rows. Bound by bytes: 2 x 4.19 MB a slot against 4.2 M operations."""
    nbytes = live_slots * (2 * state_bytes_per_slot(hf) + _row_bytes(hf))
    return nbytes / peaks["hbm_bytes_per_s"]


def ssd_chunk_floor_s(hf: dict, tokens: float, spans: float,
                      peaks: dict) -> float:
    """Least time of one ``ssd_chunk_scan`` call (one layer of one ragged
    step): a state read and written per span the kernel carries and the
    rows of those spans' tokens, over the HBM peak; or the recurrence's
    operations over the bf16 peak, if larger. ``tokens`` and ``spans``
    leave out the stream's one-row decode rows, which the program sends
    through ``ssd_decode_step``."""
    nbytes = spans * 2 * state_bytes_per_slot(hf) + tokens * _row_bytes(hf)
    return max(nbytes / peaks["hbm_bytes_per_s"],
               scan_flops(hf, tokens) / peaks["bf16_flops_per_s"])


# -- the whole decode step ----------------------------------------------------

def decode_step_bytes(hf: dict, live_slots: float,
                      live_tokens: float) -> float:
    """Bytes one decode step must move: every layer's two mixers and MLP
    and the head once (the embedding gives a row a slot: left out); the
    live slots' scan state and conv tails read and written in every layer;
    ``live_tokens`` (the live sequences' whole contexts) once a layer."""
    layers = hf["num_hidden_layers"]
    state = layers * live_slots * 2 * (state_bytes_per_slot(hf)
                                       + conv_tail_bytes_per_slot(hf))
    return (BF16 * (layers * layer_params(hf) + head_params(hf)) + state
            + live_tokens * kv_bytes_per_token(hf))


def decode_step_floor_s(hf: dict, live_slots: float, live_tokens: float,
                        hbm_bytes_per_s: float) -> float:
    return decode_step_bytes(hf, live_slots, live_tokens) / hbm_bytes_per_s
