"""Model registry: architecture name → functional model module.

Every module exposes ``param_specs(cfg)``, ``init_params(cfg, key)``,
``forward_tokens(cfg, params, tokens, positions, attend, kv_caches)``,
``logits_from_hidden(cfg, params, hidden)`` and ``forward_dense(...)``.
Mixtral and OLMoE reuse the Llama stack (the MoE block replaces the MLP
wherever ``cfg.num_experts`` > 0).
"""

from __future__ import annotations

from types import ModuleType

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.models import llama, whisper

_REGISTRY: dict[str, ModuleType] = {
    "llama": llama,
    # shared stack; the MoE block is chosen by cfg.is_moe, the
    # architecture names the HF tensor layout (engine/weights.py)
    "mixtral": llama,
    "olmoe": llama,
    # Gemma runs the shared stack too: GeGLU / (1+w) norms / embed scale /
    # softcaps / post-norms are ModelConfig knobs inside the layer code
    "gemma": llama,
    "gemma2": llama,
    # Phi-3 is the Llama stack too; only its HF checkpoint layout differs
    # (fused qkv_proj / gate_up_proj, split at load in engine/weights.py)
    "phi3": llama,
    # Ouro: the shared stack run cfg.loop_passes times over the same
    # weights, with Gemma-2's norms after each sublayer
    "ouro": llama,
    # Solar-Open2: the shared file's hybrid stack (cfg.attn_period > 1):
    # periods of one gated NoPE attention layer and KDA layers, the
    # sparse block with sigmoid routing, a shared expert and, where the
    # engine holds a share of the experts, only the pairs that fall on it
    "solar_open2": llama,
    # openPangu-Ultra-MoE: the shared file's latent-attention (MLA) mixer
    # over a one-row-a-token cache (cfg.kv_lora_rank > 0), leading dense
    # layers before the scanned expert layers, Ouro's norms after each
    # sublayer and Solar-Open2's sparse block. Served on one chip at the
    # published widths: chipbench cell
    # openpangu-ultra-moe-718b-ep16-l5.long-prompt (PERF.md, PR 43)
    "pangu_ultra_moe": llama,
    # Phi-4-mini-flash-reasoning (SambaY): the shared file's walker of
    # patterned stacks (cfg.mamba_period > 0) over models/sambay.py's
    # mixers: state-space layers, differential attention within a window
    # that binds, one full-attention cache that the cross-attention layers
    # read, gated memory units; LayerNorm, a tied head. Served uncut on one
    # chip: chipbench cell phi-4-mini-flash-reasoning.long-decode (PR 45)
    "phi4flash": llama,
    # Kimi-Linear: the same walker over KDA layers and latent-attention
    # layers that rotate nothing (cfg.mla_layers), the latent pool and the
    # per-slot state in one cache pytree, a leading dense layer inside the
    # first period, a short last period. Served with all 27 layers on one
    # chip as one of sixteen: chipbench cell
    # kimi-linear-48b-a3b-ep16.long-decode (PR 49)
    "kimi_linear": llama,
    # Falcon-H1: the same walker over ONE kind of layer that holds two
    # mixers (cfg.ssd_heads > 0): a Mamba-2 state-space mixer with heads
    # (models/falcon_h1.py, ops/ssd.py) and rotated grouped-query attention
    # on the same normed row, summed; every layer owns a cache layer and a
    # per-slot state, and the family's fourteen scalars lie on every path.
    # Served with six whole layers and the whole vocabulary on one chip:
    # chipbench cell falcon-h1-34b-l6.decode-heavy (PR 52)
    "falcon_h1": llama,
    # Olmo-Hybrid: the same walker over Gated DeltaNet layers named layer
    # by layer (cfg.gdn_layers; models/olmo_hybrid.py, ops/gdn.py: one
    # scalar decay a head, a 96 x 192 state) and full multi-head attention
    # that rotates nothing, QK-norm over the whole projections, in the Olmo
    # 3 block: a norm AFTER each sublayer and none before (cfg.norms
    # "post"). 30 KV heads lie in the cache as 32. Served with sixteen
    # whole layers and the whole vocabulary on one chip: chipbench cell
    # olmo-hybrid-7b-l16.decode-heavy (PR 57)
    "olmo_hybrid": llama,
    # afmoe (Arcee Trinity): the same walker over grouped-query attention
    # layers of two kinds named layer by layer (cfg.window_layers): a
    # window that binds and rope on three of four, all rows and no rope on
    # the fourth, each with QK-norm a head and a sigmoid gate, norms on
    # both sides of every sublayer, the embedding times sqrt(hidden), a
    # leading dense layer and Solar-Open2's sparse block. Two block pools,
    # "win" and "kv", and no per-slot state. Served with eight layers as
    # one chip of sixteen: chipbench cell
    # trinity-large-preview-ep16-l8.long-context (PR 59)
    "afmoe": llama,
    # encoder-decoder audio transcription: exposes its own forward
    # surface (encode/cross_kv/decode_tokens) instead of the decoder-only
    # protocol; shares param_specs/init_params so weights.py works
    "whisper": whisper,
}


def get_model(cfg: ModelConfig) -> ModuleType:
    try:
        return _REGISTRY[cfg.architecture]
    except KeyError:
        raise ValueError(
            f"unknown architecture {cfg.architecture!r}; known: {sorted(_REGISTRY)}"
        ) from None
