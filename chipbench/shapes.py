"""Operations and bytes computed from shapes, for roofline shares. Kept
with the benchmark so that no PR that claims a gain can change them.

Keys are those of the configuration file (the published config.json).
"""

from __future__ import annotations

BF16 = 2


def layer_params(hf: dict) -> int:
    e, d = hf["hidden_size"], hf["head_dim"]
    h, kh, f = (hf["num_attention_heads"], hf["num_key_value_heads"],
                hf["intermediate_size"])
    return e * d * (h + 2 * kh) + h * d * e + 3 * e * f


def decode_weight_bytes(hf: dict) -> int:
    """Weight bytes one decode step must read: every layer's matrices and
    the output head. The embedding is a gather of a few rows; norms are
    negligible and left out (the floor is a lower bound)."""
    head = hf["hidden_size"] * hf["vocab_size"]
    return BF16 * (hf["num_hidden_layers"] * layer_params(hf) + head)


def kv_bytes_per_token(hf: dict) -> int:
    return (BF16 * 2 * hf["num_hidden_layers"] * hf["num_key_value_heads"]
            * hf["head_dim"])


def decode_step_floor_s(hf: dict, live_kv_tokens: float, chips: int,
                        hbm_bytes_per_s: float) -> float:
    """Least time of one decode step on each of ``chips`` chips that share
    weights and KV by head: bytes per chip over one chip's HBM peak."""
    total = decode_weight_bytes(hf) + live_kv_tokens * kv_bytes_per_token(hf)
    return total / chips / hbm_bytes_per_s
