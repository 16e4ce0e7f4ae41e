"""Operations and bytes of the afmoe stack (``model_type: afmoe``: gated
grouped-query attention in every layer, within a window or over all rows
by the file's ``layer_types``; leading dense layers, then a sparse block
with a shared expert), computed from shapes, for roofline shares. Kept with
the benchmark so that no PR that claims a gain can change them.
``shapes_moe.py`` is the plain sparse stack's, ``shapes_kda.py`` the KDA
stacks'.

Keys are those of the configuration file, the published config.json's
(``hidden_size``, ``head_dim``, ``num_attention_heads``,
``num_key_value_heads``, ``intermediate_size``, ``moe_intermediate_size``,
``num_experts``, ``num_shared_experts``, ``num_dense_layers``,
``num_hidden_layers``, ``layer_types``, ``sliding_window``,
``vocab_size``) and the chip's share (``n_routed_experts_held``). Only
bytes that must move and operations the attention is are counted: a window
layer's call needs the rows its queries can see and no others, whatever
blocks or context windows an implementation streams to get them, and the
SAME work whatever implements the kernels: a floor is a lower bound.
"""

from __future__ import annotations

from chipbench.shapes_kda import mean_live_slots  # noqa: F401  (readers)

BF16 = 2


def count_layers(hf: dict, kind: str) -> int:
    return sum(t == kind for t in hf["layer_types"])


def held_experts(hf: dict) -> int:
    return int(hf.get("n_routed_experts_held", hf["num_experts"]))


# -- parameters ---------------------------------------------------------------

def attn_params(hf: dict) -> int:
    """W_q, W_g (the gate) and W_o; W_k, W_v; the two norms a head. No
    bias."""
    e, d = hf["hidden_size"], hf["head_dim"]
    hq, kh = hf["num_attention_heads"] * d, hf["num_key_value_heads"] * d
    return 3 * e * hq + 2 * e * kh + 2 * d


def dense_mlp_params(hf: dict) -> int:
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def expert_params(hf: dict) -> int:
    """One routed expert; the shared one is as wide."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def router_params(hf: dict) -> int:
    """W_r and the selection bias."""
    return (hf["hidden_size"] + 1) * hf["num_experts"]


def outside_experts_params(hf: dict, dense: bool) -> int:
    """What every token of a step reads of a layer: attention, the four
    norms, and the dense MLP or the router and the shared experts."""
    mlp = (dense_mlp_params(hf) if dense else router_params(hf)
           + int(hf.get("num_shared_experts", 0)) * expert_params(hf))
    return attn_params(hf) + 4 * hf["hidden_size"] + mlp


def head_params(hf: dict) -> int:
    return hf["vocab_size"] * hf["hidden_size"]


def total_params(hf: dict) -> int:
    """Every layer with the experts HELD, the embedding, the untied head
    and the final norm."""
    dense = int(hf.get("num_dense_layers", 0))
    sparse = int(hf["num_hidden_layers"]) - dense
    return (dense * outside_experts_params(hf, True)
            + sparse * (outside_experts_params(hf, False)
                        + held_experts(hf) * expert_params(hf))
            + 2 * head_params(hf) + hf["hidden_size"])


# -- attention ----------------------------------------------------------------

def kv_bytes_per_token_layer(hf: dict) -> int:
    """Keys and values a token holds in one attention layer."""
    return BF16 * 2 * hf["num_key_value_heads"] * hf["head_dim"]


def attn_flops(hf: dict, pairs: float) -> float:
    """q k^T and p v of every (query, key) pair and query head: a multiply
    and an add a value each."""
    return 4.0 * pairs * hf["num_attention_heads"] * hf["head_dim"]


def attn_floor_s(hf: dict, rows: float, pairs: float, query_rows: float,
                 peaks: dict) -> tuple[float, str]:
    """(least seconds, which bound) of attention calls that must read
    ``rows`` rows of context (each once a call: keys and values of one
    layer), score ``pairs`` (query, key) pairs, and read the queries and
    write the outputs of ``query_rows`` query rows (each once a call): the
    pairs' operations over the bf16 peak, or the bytes over the HBM peak,
    whichever is longer. The counts already run over the layers."""
    q = BF16 * 2 * query_rows * hf["num_attention_heads"] * hf["head_dim"]
    by_bytes = (rows * kv_bytes_per_token_layer(hf) + q) / peaks[
        "hbm_bytes_per_s"]
    by_flops = attn_flops(hf, pairs) / peaks["bf16_flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops,
                                                             "flops")


# -- the whole decode step ----------------------------------------------------

def row_reads(hf: dict, window_rows: float, full_rows: float) -> dict:
    """Rows of context an attention call of every layer reads, by kind of
    layer: ``window_rows`` (the live slots' rows inside the window, summed)
    once a window layer, ``full_rows`` (their whole contexts) once a full
    layer."""
    return {"window_rows": count_layers(hf, "sliding_attention") * window_rows,
            "full_rows": count_layers(hf, "full_attention") * full_rows}


def decode_step_bytes(hf: dict, touched: float, attn_row_reads: float
                      ) -> dict:
    """Bytes one decode step must move, by part: the weights outside the
    routed experts and the head once (the embedding gives a row a slot:
    left out); ``touched`` routed experts a sparse layer (what received a
    row: ``vllm:moe_decode_experts_touched_total`` over the layer-steps);
    ``attn_row_reads``: rows of context its attention calls must read,
    each once a call, summed over the layers (``row_reads``;
    ``vllm:decode_attn_rows_needed_total`` a dispatch). The live rows'
    activations are left out (kilobytes)."""
    dense = int(hf.get("num_dense_layers", 0))
    sparse = int(hf["num_hidden_layers"]) - dense
    parts = {
        "weights": BF16 * (dense * outside_experts_params(hf, True)
                           + sparse * outside_experts_params(hf, False)
                           + head_params(hf)),
        "experts": BF16 * sparse * touched * expert_params(hf),
        "attn_rows": attn_row_reads * kv_bytes_per_token_layer(hf),
    }
    parts["total"] = sum(parts.values())
    return parts


def decode_step_floor_s(hf: dict, touched: float, attn_row_reads: float,
                        hbm_bytes_per_s: float) -> float:
    return decode_step_bytes(hf, touched,
                             attn_row_reads)["total"] / hbm_bytes_per_s
