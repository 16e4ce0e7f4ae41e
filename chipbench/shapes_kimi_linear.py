"""Operations, bytes and parameters of a stack of KDA layers and latent
attention (MLA) layers behind a leading dense layer, with a share of the
routed experts, computed from shapes, for roofline shares and for the
arithmetic of the cut. Kept with the benchmark so that no PR that claims a
gain can change them. The KDA layers' own numbers are ``shapes_kda.py``'s
(the same block), a latent attention call's ``shapes_mla.py``'s.

Keys are those of the configuration file: the published config.json's
(``hidden_size``, ``num_attention_heads``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``linear_attn_config`` with its two 1-based layer lists,
``intermediate_size``, ``moe_intermediate_size``, ``num_experts``,
``num_shared_experts``, ``first_k_dense_replace``, ``num_hidden_layers``,
``vocab_size``) and the one that states the chip's share,
``n_routed_experts_held``. Only bytes that must move are counted: a floor
is a lower bound.
"""

from __future__ import annotations

from chipbench import shapes_kda, shapes_mla

BF16 = 2


def layer_counts(hf: dict) -> tuple[int, int]:
    """(MLA layers, KDA layers), from the two lists."""
    lin = hf["linear_attn_config"]
    return len(lin["full_attn_layers"]), len(lin["kda_layers"])


def mla_layer_params(hf: dict) -> int:
    """An MLA layer's mixer with a direct query projection: W_q, W_kva,
    W_kvb (keys and values), W_o."""
    e, h = hf["hidden_size"], hf["num_attention_heads"]
    nope, rope, v = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                     hf["v_head_dim"])
    c = hf["kv_lora_rank"]
    return e * h * (nope + rope) + e * (c + rope) + c * h * (nope + v) \
        + h * v * e


def dense_mlp_params(hf: dict) -> int:
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def sparse_fixed_params(hf: dict) -> int:
    """What every sparse layer reads whatever the routing: the router and
    the shared expert(s)."""
    return (hf["hidden_size"] * hf["num_experts"]
            + hf["num_shared_experts"] * shapes_kda.expert_params(hf))


def model_params(hf: dict) -> int:
    """Everything the engine holds: embedding, head, the mixers, the dense
    layers' MLPs and the sparse layers' routers, shared and HELD experts."""
    mla, kda = layer_counts(hf)
    dense = hf["first_k_dense_replace"]
    held = hf.get("n_routed_experts_held", hf["num_experts"])
    return (2 * hf["vocab_size"] * hf["hidden_size"]
            + kda * shapes_kda.kda_layer_params(hf)
            + mla * mla_layer_params(hf) + dense * dense_mlp_params(hf)
            + (mla + kda - dense) * (sparse_fixed_params(hf)
                                     + held * shapes_kda.expert_params(hf)))


def latent_bytes_per_token(hf: dict, lanes: int | None = None) -> int:
    """A token's latent rows, all MLA layers, bf16, at the published
    ``kv_lora_rank + qk_rope_head_dim`` values; ``lanes``: as the pool
    stores a row (padded to whole 128-lane tiles)."""
    mla, _ = layer_counts(hf)
    return BF16 * mla * (lanes or shapes_mla.latent_row_values(hf))


def recurrent_bytes_per_slot(hf: dict) -> int:
    """A decode slot's float32 state and conv tails, all KDA layers."""
    _, kda = layer_counts(hf)
    return kda * (shapes_kda.state_bytes_per_slot(hf)
                  + shapes_kda.conv_tail_bytes_per_slot(hf))


def decode_step_bytes(hf: dict, experts_touched: float, live_slots: float,
                      live_tokens: float) -> float:
    """Bytes one decode step must move: every mixer, the dense MLP, every
    sparse layer's router and shared expert once; the routed experts
    TOUCHED (a sparse layer's mean, from the counters; of the
    ``n_routed_experts_held`` at most); the head; the live slots' state
    and conv tails read and written, in every KDA layer; the live tokens'
    latent rows once an MLA layer."""
    mla, kda = layer_counts(hf)
    dense = hf["first_k_dense_replace"]
    sparse = mla + kda - dense
    weights = (kda * shapes_kda.kda_layer_params(hf)
               + mla * mla_layer_params(hf) + dense * dense_mlp_params(hf)
               + sparse * sparse_fixed_params(hf)
               + sparse * min(experts_touched, hf["n_routed_experts_held"])
               * shapes_kda.expert_params(hf)
               + hf["hidden_size"] * hf["vocab_size"])
    return (BF16 * weights + live_slots * 2 * recurrent_bytes_per_slot(hf)
            + live_tokens * latent_bytes_per_token(hf))


def decode_step_floor_s(hf: dict, experts_touched: float, live_slots: float,
                        live_tokens: float, hbm_bytes_per_s: float) -> float:
    return decode_step_bytes(hf, experts_touched, live_slots,
                             live_tokens) / hbm_bytes_per_s
