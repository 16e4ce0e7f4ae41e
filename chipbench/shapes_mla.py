"""Operations, bytes and parameters of a latent-attention (MLA) stack with
leading dense layers and a share of the routed experts, computed from
shapes, for roofline shares and for the arithmetic of the cut. Kept with
the benchmark so that no PR that claims a gain can change them.

Keys are those of the configuration file: the published config.json's
(``hidden_size``, ``num_attention_heads``, ``q_lora_rank``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``intermediate_size``, ``moe_intermediate_size``,
``n_routed_experts``, ``n_shared_experts``, ``first_k_dense_replace``,
``num_hidden_layers``, ``vocab_size``) and the one that states the chip's
share, ``n_routed_experts_held``.

A (query token, context row) pair is counted in the PUBLISHED form of the
attention, whatever form the program scores it in: a head's score over
``qk_nope + qk_rope`` values and its weighted sum over ``v_head_dim``,
no up-projection of the latent. The absorbed form the program serves
(``absorbed_pair_ops``) does 3.4 x as many operations a pair, so a kernel
that scores absorbed reads at most ~29 % of this roofline, and a later one
that expands the context a chunk can never pass 100 %.
"""

from __future__ import annotations

BF16 = 2


def _heads(hf: dict) -> int:
    return int(hf["num_attention_heads"])


def published_pair_ops(hf: dict) -> int:
    """Operations of one pair in one layer, published (expanded) form:
    2 x heads x (qk_nope + qk_rope + v)."""
    return 2 * _heads(hf) * (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]
                             + hf["v_head_dim"])


def absorbed_pair_ops(hf: dict) -> int:
    """Operations of one pair in one layer as the program scores it: every
    head against the latent row (kv_lora_rank + qk_rope) and its weighted
    sum over the latent (kv_lora_rank)."""
    return 2 * _heads(hf) * (2 * hf["kv_lora_rank"] + hf["qk_rope_head_dim"])


def latent_row_values(hf: dict) -> int:
    """What a token of context holds in one cache layer: the latent and the
    shared rotated key."""
    return hf["kv_lora_rank"] + hf["qk_rope_head_dim"]


def kv_bytes_per_token(hf: dict, lanes: int | None = None) -> int:
    """A token's latent rows, all layers, bf16; ``lanes``: as the pool
    stores a row (padded to whole 128-lane tiles)."""
    return BF16 * hf["num_hidden_layers"] * (lanes or latent_row_values(hf))


def attn_params(hf: dict) -> int:
    """One layer outside its MLP: q_a, q_b, kv_a, kv_b, o."""
    e, h = hf["hidden_size"], _heads(hf)
    qk = hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]
    return (e * hf["q_lora_rank"] + hf["q_lora_rank"] * h * qk
            + e * latent_row_values(hf)
            + hf["kv_lora_rank"] * h * (hf["qk_nope_head_dim"]
                                        + hf["v_head_dim"])
            + h * hf["v_head_dim"] * e)


def expert_params(hf: dict) -> int:
    """One routed (or shared) expert: gate, up, down."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def dense_layer_params(hf: dict) -> int:
    """A leading dense layer: attention and a SwiGLU of intermediate_size."""
    return attn_params(hf) + 3 * hf["hidden_size"] * hf["intermediate_size"]


def expert_layer_params(hf: dict, held: int | None = None) -> int:
    """An expert layer: attention, the router, the shared expert(s) and
    ``held`` routed experts (default: those the file says are held)."""
    held = hf.get("n_routed_experts_held", hf["n_routed_experts"]) \
        if held is None else held
    return (attn_params(hf) + hf["hidden_size"] * hf["n_routed_experts"]
            + (hf["n_shared_experts"] + held) * expert_params(hf))


def model_params(hf: dict) -> int:
    """Everything the engine holds: embedding, head and the layers."""
    dense = hf["first_k_dense_replace"]
    return (2 * hf["vocab_size"] * hf["hidden_size"]
            + dense * dense_layer_params(hf)
            + (hf["num_hidden_layers"] - dense) * expert_layer_params(hf))


def mla_attn_floor_s(hf: dict, pairs: float, context_rows: float,
                     query_tokens: float, peaks: dict) -> tuple[float, str]:
    """(least seconds, which peak bounds it) of latent attention calls
    that score ``pairs`` pairs for ``query_tokens`` query tokens whose
    spans reach ``context_rows`` rows of context (all three already summed
    over the calls' cache layers): the published form's operations over
    the bf16 peak, or the bytes that must move over the HBM peak, each
    span's context rows once and a token's query and output rows in the
    published form's sizes."""
    h = _heads(hf)
    ops = published_pair_ops(hf) * pairs
    nbytes = BF16 * (context_rows * latent_row_values(hf) + query_tokens * h * (
        hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"] + hf["v_head_dim"]))
    by_ops, by_bytes = (ops / peaks["bf16_flops_per_s"],
                        nbytes / peaks["hbm_bytes_per_s"])
    return max(by_ops, by_bytes), "ops" if by_ops >= by_bytes else "bytes"
