"""Operations and bytes of the Olmo-Hybrid stack (``model_type:
olmo_hybrid``: Gated DeltaNet layers and full multi-head attention by the
file's ``layer_types``, a dense SwiGLU in every layer), computed from
shapes, for roofline shares. Kept with the benchmark so that no PR that
claims a gain can change them. ``shapes_kda.py`` is the KDA stacks',
``shapes_ssd.py`` the parallel hybrid stack's.

Keys are those of the configuration file, the published config.json's
(``hidden_size``, ``intermediate_size``, ``num_attention_heads``,
``num_key_value_heads``, ``num_hidden_layers``, ``layer_types``,
``vocab_size``, ``linear_num_value_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``, ``linear_conv_kernel_dim``). Only bytes that
must move and operations the recurrence is are counted, as PUBLISHED: the
state is 96 x 192 float32 a head and a token's keys and values are 30
heads', whatever layout or filling the program keeps them in (a padded
layout then reads under 100 % by what it pads), and the SAME work whatever
implements the kernels: a floor is a lower bound, and the block form of the
delta rule does no less of either.
"""

from __future__ import annotations

from chipbench.shapes_kda import mean_live_slots  # noqa: F401  (readers)

BF16, F32 = 2, 4


def _gdn(hf: dict) -> tuple[int, int, int, int]:
    """(heads, d_k, d_v, conv width) of a Gated DeltaNet layer."""
    return (int(hf["linear_num_value_heads"]), int(hf["linear_key_head_dim"]),
            int(hf["linear_value_head_dim"]),
            int(hf.get("linear_conv_kernel_dim", 4)))


def _head_dim(hf: dict) -> int:
    """An attention head's size (``head_dim`` is null in the file)."""
    return int(hf.get("head_dim")
               or hf["hidden_size"] // hf["num_attention_heads"])


def conv_channels(hf: dict) -> int:
    """[q | k | v]: what the short convolution runs over."""
    h, dk, dv, _ = _gdn(hf)
    return h * (2 * dk + dv)


def count_layers(hf: dict, kind: str) -> int:
    return sum(t == kind for t in hf["layer_types"])


# -- parameters ---------------------------------------------------------------

def gdn_params(hf: dict) -> int:
    """W_q, W_k, W_v (onto the conv's channels), W_g and W_o, W_a and W_b,
    the conv taps, A_log, dt_bias, the gated norm's weight."""
    e = hf["hidden_size"]
    h, _, dv, k = _gdn(hf)
    cd = conv_channels(hf)
    return e * cd + 2 * e * h * dv + 2 * e * h + k * cd + 2 * h + dv


def attn_params(hf: dict) -> int:
    """W_q, W_o; W_k, W_v; the norms over the whole q and k projections.
    No bias."""
    e, d = hf["hidden_size"], _head_dim(hf)
    hq, kh = hf["num_attention_heads"] * d, hf["num_key_value_heads"] * d
    return 2 * e * hq + 2 * e * kh + hq + kh


def mlp_params(hf: dict) -> int:
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def layer_params(hf: dict, kind: str) -> int:
    """A layer of ``kind`` (an entry of ``layer_types``): its mixer, the
    MLP and the two norms after the sublayers."""
    mixer = gdn_params(hf) if kind == "linear_attention" else attn_params(hf)
    return mixer + mlp_params(hf) + 2 * hf["hidden_size"]


def head_params(hf: dict) -> int:
    return hf["vocab_size"] * hf["hidden_size"]


def stack_params(hf: dict) -> int:
    return sum(layer_params(hf, t) for t in hf["layer_types"])


def total_params(hf: dict) -> int:
    """Every layer, the embedding, the untied head and the final norm."""
    return stack_params(hf) + 2 * head_params(hf) + hf["hidden_size"]


# -- the delta-rule kernels ---------------------------------------------------

def state_bytes_per_slot(hf: dict) -> int:
    """One layer's state of one decode slot, float32, as published."""
    h, dk, dv, _ = _gdn(hf)
    return F32 * h * dk * dv


def conv_tail_bytes_per_slot(hf: dict) -> int:
    return BF16 * (_gdn(hf)[3] - 1) * conv_channels(hf)


def kv_bytes_per_token(hf: dict) -> int:
    """Keys and values a token holds, all full-attention layers, as
    published (the program's cache fills 30 heads up to 32)."""
    return (BF16 * 2 * hf["num_key_value_heads"] * _head_dim(hf)
            * count_layers(hf, "full_attention"))


def _row_bytes(hf: dict) -> int:
    """A token's float32 rows in and out of a recurrence kernel: kb, k, q
    (H d_k each), vb and o (H d_v each), the decay (H)."""
    h, dk, dv, _ = _gdn(hf)
    return F32 * h * (3 * dk + 2 * dv + 1)


def recurrence_flops(hf: dict, tokens: float) -> float:
    """The delta rule itself: a token and head is the decay (a multiply a
    state value), S^T k, the rank-one update and S^T q (a multiply and an
    add a state value each): 7 d_k d_v."""
    h, dk, dv, _ = _gdn(hf)
    return tokens * h * 7 * dk * dv


def gdn_decode_floor_s(hf: dict, live_slots: float, peaks: dict) -> float:
    """Least time of one ``gdn_decode_step`` call (one layer, one token a
    slot): each live slot's state read once and written once, and its
    rows. Bound by bytes: 2 x 2.21 MB a slot against 3.9 M operations."""
    nbytes = live_slots * (2 * state_bytes_per_slot(hf) + _row_bytes(hf))
    return nbytes / peaks["hbm_bytes_per_s"]


def gdn_chunk_floor_s(hf: dict, tokens: float, spans: float,
                      peaks: dict) -> float:
    """Least time of one ``gdn_chunk_scan`` call (one layer of one ragged
    step): a state read and written per span the kernel carries and the
    rows of those spans' tokens, over the HBM peak; or the recurrence's
    operations over the bf16 peak, if larger. ``tokens`` and ``spans``
    leave out the stream's one-row decode rows, which the program sends
    through ``gdn_decode_step``."""
    nbytes = spans * 2 * state_bytes_per_slot(hf) + tokens * _row_bytes(hf)
    return max(nbytes / peaks["hbm_bytes_per_s"],
               recurrence_flops(hf, tokens) / peaks["bf16_flops_per_s"])


# -- the whole decode step ----------------------------------------------------

def decode_step_bytes(hf: dict, live_slots: float,
                      live_tokens: float) -> float:
    """Bytes one decode step must move: every layer's mixer and MLP and the
    head once (the embedding gives a row a slot: left out); the live
    slots' states and conv tails read and written in every Gated DeltaNet
    layer; ``live_tokens`` (the live sequences' whole contexts) once a
    full-attention layer."""
    state = (count_layers(hf, "linear_attention") * live_slots * 2
             * (state_bytes_per_slot(hf) + conv_tail_bytes_per_slot(hf)))
    return (BF16 * (stack_params(hf) + head_params(hf)) + state
            + live_tokens * kv_bytes_per_token(hf))


def decode_step_floor_s(hf: dict, live_slots: float, live_tokens: float,
                        hbm_bytes_per_s: float) -> float:
    return decode_step_bytes(hf, live_slots, live_tokens) / hbm_bytes_per_s
