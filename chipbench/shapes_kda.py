"""Operations and bytes of a hybrid stack (gated delta-rule KDA layers
among softmax-attention layers, a sparse block with a share of the routed
experts and a shared one), computed from shapes, for roofline shares.
Kept with the benchmark so that no PR that claims a gain can change them.
``shapes.py`` is the dense stack's, ``shapes_moe.py`` reads OLMoE's keys
and is not used here.

Keys are those of the configuration file: the published config.json's
(``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``linear_attn_config``, ``moe_intermediate_size``,
``n_routed_experts``, ``n_shared_experts``, ``gqa_interval``,
``num_hidden_layers``, ``vocab_size``) and the one that states the chip's
share, ``n_routed_experts_held``. Only bytes that must move are counted:
a floor is a lower bound.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def _kda(hf: dict) -> tuple[int, int, int]:
    """(heads, head size, conv width) of the KDA layers."""
    lin = hf["linear_attn_config"]
    return (int(lin["num_heads"]), int(lin["head_dim"]),
            int(lin["short_conv_kernel_size"]))


def layer_counts(hf: dict) -> tuple[int, int]:
    """(attention layers, KDA layers): one attention layer first in every
    ``gqa_interval + 1``."""
    n = hf["num_hidden_layers"]
    attn = n // (hf["gqa_interval"] + 1)
    return attn, n - attn


def mean_live_slots(polls: list, decode_slots: int) -> float | None:
    """Decode slots in use over a window: ``vllm:num_requests_running``
    averaged over the polls, ``decode_slots`` at most; None without it."""
    running = [p["vllm:num_requests_running"] for p in polls
               if "vllm:num_requests_running" in p]
    if not running:
        return None
    return min(sum(running) / len(running), decode_slots)


# -- the KDA kernels ----------------------------------------------------------

def state_bytes_per_slot(hf: dict) -> int:
    """One layer's recurrent state of one decode slot, float32."""
    h, d, _ = _kda(hf)
    return F32 * h * d * d


def conv_tail_bytes_per_slot(hf: dict) -> int:
    """One layer's conv tail of one slot: the last K - 1 projected rows of
    q, k and v, in the model dtype."""
    h, d, k = _kda(hf)
    return BF16 * (k - 1) * 3 * h * d


def kda_step_flops(hf: dict, rows: float) -> float:
    """The recurrence on ``rows`` (token, layer) rows: per head the decay
    (d^2), S^T k (2 d^2), the rank-1 update (2 d^2), S^T q (2 d^2)."""
    h, d, _ = _kda(hf)
    return rows * h * 7.0 * d * d


def kda_decode_floor_s(hf: dict, live_slots: float, peaks: dict) -> float:
    """Least time of one ``kda_decode_step`` call (one layer, one token a
    slot): each live slot's state read once and written once, its five
    float32 input rows (a, beta k, k, q, beta v) read and its output row
    written; or the operations, if larger."""
    h, d, _ = _kda(hf)
    nbytes = live_slots * (2 * state_bytes_per_slot(hf) + F32 * 6 * h * d)
    return max(nbytes / peaks["hbm_bytes_per_s"],
               kda_step_flops(hf, live_slots) / peaks["bf16_flops_per_s"])


def kda_chunk_floor_s(hf: dict, tokens: float, spans: float,
                      peaks: dict) -> float:
    """Least time of one ``kda_chunk_scan`` call (one layer of one ragged
    step): a state read and written per span the kernel carries, six
    float32 rows a token of those spans; or the operations, if larger.
    ``tokens`` and ``spans`` leave out the stream's one-row decode rows,
    which the program sends through ``kda_decode_step``."""
    h, d, _ = _kda(hf)
    nbytes = spans * 2 * state_bytes_per_slot(hf) + tokens * F32 * 6 * h * d
    return max(nbytes / peaks["hbm_bytes_per_s"],
               kda_step_flops(hf, tokens) / peaks["bf16_flops_per_s"])


# -- the whole decode step ----------------------------------------------------

def expert_params(hf: dict) -> int:
    """One routed (or shared) expert: gate, up, down."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def held_grouped_matmul_floor_s(hf: dict, experts_touched: float,
                                peaks: dict) -> float:
    """Least time of one grouped matmul (one projection of one layer) at
    a share of the experts: one matrix, a third of ``expert_params``, of
    each held expert that received a row. The rows themselves are left
    out: of the pairs a step routes only those on held experts must move
    (32 of 512 here, 0.3 MB against 169 MB of matrices), and at under 240
    rows an expert the bytes bound it, not the operations."""
    touched = min(experts_touched, hf["n_routed_experts_held"])
    return BF16 * touched * expert_params(hf) / 3 / peaks["hbm_bytes_per_s"]


def attn_layer_params(hf: dict) -> int:
    """A gated GQA layer's mixer: W_q, W_gate, W_k, W_v, W_o."""
    e, d = hf["hidden_size"], hf["head_dim"]
    h, kh = hf["num_attention_heads"], hf["num_key_value_heads"]
    return e * d * (2 * h + 2 * kh) + h * d * e


def kda_layer_params(hf: dict) -> int:
    """A KDA layer's mixer: W_q, W_k, W_v, W_o, the two low-rank pairs
    (inner width = the head size), W_beta, the conv taps."""
    e = hf["hidden_size"]
    h, d, k = _kda(hf)
    return (4 * e * h * d + 2 * (e * d + d * h * d) + e * h
            + k * 3 * h * d)


def dense_params_per_layer(hf: dict) -> int:
    """What every layer reads whatever the routing: the router and the
    shared expert(s)."""
    return (hf["hidden_size"] * hf["n_routed_experts"]
            + hf["n_shared_experts"] * expert_params(hf))


def kv_bytes_per_token(hf: dict) -> int:
    """Keys and values a token of context holds: the attention layers'."""
    attn, _ = layer_counts(hf)
    return BF16 * 2 * attn * hf["num_key_value_heads"] * hf["head_dim"]


def decode_step_bytes(hf: dict, experts_touched: float, live_slots: float,
                      live_kv_tokens: float) -> float:
    """Bytes one decode step must move: every layer's mixer, router and
    shared expert once; the routed experts TOUCHED (a layer's mean, from
    the counters; of the ``n_routed_experts_held`` at most); the head; the
    live slots' recurrent state read and written and their conv tails
    read and written, in every KDA layer; the live keys and values."""
    attn, kda = layer_counts(hf)
    layers = attn + kda
    weights = (attn * attn_layer_params(hf) + kda * kda_layer_params(hf)
               + layers * dense_params_per_layer(hf)
               + layers * min(experts_touched, hf["n_routed_experts_held"])
               * expert_params(hf)
               + hf["hidden_size"] * hf["vocab_size"])
    state = kda * live_slots * 2 * (state_bytes_per_slot(hf)
                                    + conv_tail_bytes_per_slot(hf))
    return BF16 * weights + state + live_kv_tokens * kv_bytes_per_token(hf)


def decode_step_floor_s(hf: dict, experts_touched: float, live_slots: float,
                        live_kv_tokens: float, hbm_bytes_per_s: float
                        ) -> float:
    return decode_step_bytes(hf, experts_touched, live_slots,
                             live_kv_tokens) / hbm_bytes_per_s
