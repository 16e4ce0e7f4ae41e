"""Operations and bytes of the sparse expert block, computed from shapes,
for roofline shares. Kept with the benchmark so that no PR that claims a
gain can change them. ``shapes.py`` is the dense stack's.

Keys are those of the configuration file (the published config.json):
``intermediate_size`` is the width of ONE expert, ``num_experts`` how many
a layer holds, ``num_experts_per_tok`` how many a token is routed to.
"""

from __future__ import annotations

BF16 = 2


def expert_projection_bytes(hf: dict) -> int:
    """One expert's matrix of one projection (gate, up or down): what one
    grouped matmul reads for each expert that received a row."""
    return BF16 * hf["hidden_size"] * hf["intermediate_size"]


def grouped_matmul_bytes(hf: dict, experts_touched: float, rows: float) -> float:
    """Bytes one grouped matmul (one projection of one layer) must move:
    the matrices of the experts touched, the rows in and the rows out."""
    io = BF16 * rows * (hf["hidden_size"] + hf["intermediate_size"])
    return experts_touched * expert_projection_bytes(hf) + io


def grouped_matmul_flops(hf: dict, rows: float) -> float:
    return 2.0 * rows * hf["hidden_size"] * hf["intermediate_size"]


def grouped_matmul_floor_s(hf: dict, experts_touched: float, rows: float,
                           peaks: dict) -> float:
    """Least time of one grouped matmul: the larger of bytes over the HBM
    peak and operations over the bf16 peak (at 8 rows an expert it is the
    bytes by 30 to 1)."""
    return max(
        grouped_matmul_bytes(hf, experts_touched, rows)
        / peaks["hbm_bytes_per_s"],
        grouped_matmul_flops(hf, rows) / peaks["bf16_flops_per_s"])


def layer_params(hf: dict) -> int:
    """Attention, router and every expert of one layer."""
    e, d = hf["hidden_size"], hf["hidden_size"] // hf["num_attention_heads"]
    h, kh = hf["num_attention_heads"], hf["num_key_value_heads"]
    return (e * d * (h + 2 * kh) + h * d * e + e * hf["num_experts"]
            + hf["num_experts"] * 3 * e * hf["intermediate_size"])


def weight_bytes(hf: dict) -> int:
    """Everything held: layers, embedding, and the head unless tied."""
    emb = hf["hidden_size"] * hf["vocab_size"]
    return BF16 * (hf["num_hidden_layers"] * layer_params(hf)
                   + emb * (1 if hf.get("tie_word_embeddings") else 2))
