"""Engine configuration.

The reference stack passes engine knobs straight through to vLLM
(helm/templates/deployment-vllm-multi.yaml:170-213 — --tensor-parallel-size,
--max-model-len, dtype, ...). Here the engine is ours, so the config is
first-class: model architecture, paged-KV cache geometry, scheduler limits and
the device-mesh shape all live here.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Optional  # noqa: F401

import jax.numpy as jnp

from production_stack_tpu.parallel.mesh import MeshConfig


def _expert_share(what: str, cfg: dict, experts_key: str) -> tuple:
    """(routed experts, those this engine holds, the first one's index) of
    a file that may state the chip's share of a wider expert layer
    (``n_routed_experts_held`` from ``routed_expert_offset``; absent: all
    of them), refused where it is no share."""
    experts = int(cfg[experts_key])
    held = int(cfg.get("n_routed_experts_held", experts))
    offset = int(cfg.get("routed_expert_offset", 0))
    if not 0 < held <= experts or not 0 <= offset <= experts - held:
        raise ValueError(
            f"{what}: n_routed_experts_held={held} from "
            f"routed_expert_offset={offset} is not a share of "
            f"{experts_key}={experts}")
    return experts, held, offset


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "tiny-llama"
    # "llama" | "mixtral" | "olmoe" | "gemma" | "gemma2" | "phi3" | "ouro"
    # | "solar_open2" | "pangu_ultra_moe" | "phi4flash" | "kimi_linear"
    # | "falcon_h1" | "olmo_hybrid" | "afmoe"
    # — Mistral and Qwen run as "llama" (their deltas are knobs:
    # sliding_window, qkv_bias, qk_norm); "phi3" differs only in its fused
    # HF weight layout, "mixtral" and "olmoe" in their HF tensor names
    # (the MoE block itself is chosen by num_experts > 0, see is_moe),
    # "ouro" in its tensor names and in loop_passes > 1, "solar_open2" in
    # its layer pattern (attn_period > 1) and its sparse block's knobs,
    # "pangu_ultra_moe" in its latent attention (kv_lora_rank > 0) and its
    # leading dense layers, "phi4flash" in its layer pattern (mamba_period
    # > 0: state-space, window, full and cross-attention layers, gated
    # memory units), differential attention and LayerNorm, "kimi_linear" in
    # KDA and latent-attention layers in one stack (mla_layers) behind a
    # leading dense layer, "falcon_h1" in a state-space mixer with heads
    # and grouped-query attention side by side in every layer (ssd_heads
    # > 0) and the family's multipliers, "olmo_hybrid" in Gated DeltaNet
    # layers named layer by layer (gdn_layers) beside full attention, and
    # norms AFTER each sublayer alone (norms "post"), "afmoe" in attention
    # layers that differ by kind (window_layers: a window that binds and
    # rope on those, all rows and no rope on the others), every one gated,
    # with norms "both", an embedding factor, a leading dense layer and
    # the sigmoid-routed sparse block
    architecture: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 22
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 64
    rope_theta: float = 10000.0
    rope_scaling: float = 1.0
    rms_norm_eps: float = 1e-5
    max_model_len: int = 4096
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    # MoE (mixtral, olmoe): experts of width intermediate_size, no shared
    # expert. Routing weights are softmax over ALL experts, then top-k;
    # norm_topk_prob renormalises the k chosen to sum to 1 (Mixtral: the
    # same as its top-k-then-softmax) — OLMoE uses them as they are
    num_experts: int = 0
    num_experts_per_tok: int = 2
    norm_topk_prob: bool = True
    # the chip's share of a wider expert layer: the router scores all
    # num_experts, this engine holds experts [expert_offset, expert_offset
    # + experts_held) and computes only the (token, choice) pairs that fall
    # on them; the other pairs add nothing here (their chips' part of the
    # sum). 0 = every expert is held
    experts_held: int = 0
    expert_offset: int = 0
    # "softmax" over all experts then top-k (Mixtral, OLMoE), or "sigmoid"
    # scores with a per-expert bias that is added to CHOOSE the k and not
    # to weigh them (the GLM-4-MoE / DeepSeek-V3 router)
    moe_scoring: str = "softmax"
    routed_scaling: float = 1.0  # factor on the routed experts' sum
    # width of the shared expert (a dense SwiGLU every token passes
    # through, added to the routed sum); 0 = none
    shared_expert_size: int = 0
    # hybrid stacks: layer l is softmax attention where l % attn_period
    # == 0 and a gated delta-rule (KDA) linear-attention layer elsewhere;
    # 0 = every layer is attention. A KDA layer keeps a recurrent state
    # (kda_heads, kda_head_dim, kda_head_dim) float32 and a short-conv
    # tail per decode slot instead of keys and values per token
    attn_period: int = 0
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4        # width of the causal depthwise convolution
    kda_rank: int = 0        # low-rank width of the decay and gate pairs
    kda_neg_eigval: bool = False  # beta in (0, 2) instead of (0, 1)
    # of a hybrid stack's attention layers, which apply no positional
    # encoding (the shared stack's own layers rotate and have no gate)
    attn_gate: bool = False  # sigmoid(W x) on the attention output
    # a hybrid stack whose attention layers are latent attention (MLA) and
    # are named one by one ("kimi_linear"): the 0-based layers that are
    # MLA, every other layer KDA. attn_period is then the period the stack
    # is walked in (the first MLA layer closes the first), and the last
    # period may be short. () = the attn_period rule
    mla_layers: tuple = ()
    # a hybrid stack whose recurrent layers are Gated DeltaNet
    # ("olmo_hybrid"; ops/gdn.py): the 0-based layers that are "gdn", every
    # other layer full attention ("gqa": nothing rotated, no gate), walked
    # in periods of attn_period. A GDN layer is the delta rule with ONE
    # scalar decay a head and token and a state that need not be square,
    # (gdn_heads, gdn_key_dim, gdn_value_dim) float32 a decode slot, beside
    # a conv tail over 2 H d_k + H d_v channels. () = no such stack
    gdn_layers: tuple = ()
    gdn_heads: int = 0
    gdn_key_dim: int = 0     # d_k: q and k of a head
    gdn_value_dim: int = 0   # d_v: v and the output of a head
    gdn_conv: int = 4        # width of the causal depthwise convolution
    gdn_neg_eigval: bool = False  # beta in (0, 2) instead of (0, 1)
    # a stack of grouped-query attention layers that differ in what a row
    # sees, named one by one ("afmoe"): the 0-based layers that are "swa"
    # (row t sees rows t - sliding_window + 1 .. t, from the window pool:
    # ``window_binds``), every other layer "full" (all rows, the other
    # pool), walked in periods of attn_period. rope_kinds says which kinds
    # rotate q and k: both unless the family says otherwise (afmoe: the
    # window layers alone; a full layer applies no positional encoding).
    # () = no such stack
    window_layers: tuple = ()
    rope_kinds: tuple = ("swa", "full")
    # the decoder-hybrid-decoder stack (SambaY, "phi4flash"): layer l is a
    # Mamba-1 state-space layer where l % mamba_period == 0 and attention
    # elsewhere, with a window of sliding_window rows, up to layer
    # num_layers // 2 + 1, the ONE attention layer without a window, whose
    # keys and values every later attention layer reads in place of its
    # own (cross-attention: W_q and W_o only); from layer num_layers // 2
    # + 2 on, a state-space layer's place is taken by a gated memory unit
    # that reads the scan output of layer num_layers // 2 (``layer_kinds``).
    # 0 = no such stack. A state-space layer keeps a state (mamba_state,
    # mamba_expand x hidden_size) float32 and a conv tail per decode slot
    mamba_period: int = 0
    mamba_state: int = 16    # N, values of state a channel
    mamba_conv: int = 4      # width of the causal depthwise convolution
    mamba_expand: int = 2    # inner width over hidden_size
    mamba_dt_rank: int = 0   # low-rank width of the Delta projection
    # the parallel hybrid stack ("falcon_h1"): EVERY layer runs a Mamba-2
    # state-space mixer with heads (SSD; ops/ssd.py) and rotated
    # grouped-query attention on the same normed row and adds the two
    # (``layer_kinds``: "parallel"), so every layer owns a cache layer AND
    # a per-slot state: (ssd_heads, ssd_state, ssd_head_dim) float32, one
    # scalar decay a head and token, B and C shared by the heads of a
    # group, beside a conv tail over ssd_conv_dim channels. 0 = no such
    # stack
    ssd_heads: int = 0
    ssd_head_dim: int = 0    # P, channels a head
    ssd_state: int = 0       # N, values of state a channel
    ssd_groups: int = 1      # groups of heads that share B and C
    ssd_conv: int = 4        # width of the causal depthwise convolution
    # the family's published scalars (muP multipliers), each applied where
    # the published code applies it; they come from the file and are 1 for
    # every other family. ssd_multipliers scales the sections [z | x | B |
    # C | dt] of the state-space mixer's input projection
    ssd_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    ssd_in_multiplier: float = 1.0
    ssd_out_multiplier: float = 1.0
    attn_in_multiplier: float = 1.0
    attn_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    mlp_gate_multiplier: float = 1.0
    mlp_down_multiplier: float = 1.0
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    # differential attention: query and key heads pair up (2i, 2i + 1),
    # a pair's two softmaxes weigh the pair's values (2 x head_dim wide)
    # and the second is subtracted, lambda times; then RMSNorm and W_o.
    # The cache holds a pair as one head of 2 x head_dim (cache_kv_heads,
    # which also says why there are empty heads; cache_head_dim;
    # models/sambay.py has the identity)
    diff_attn: bool = False
    # LayerNorm with weight and bias where the other families have RMSNorm
    layer_norm: bool = False
    # Qwen2-family: biases on the QKV projections
    qkv_bias: bool = False
    # RMSNorm on q and k, pre-rope. "head": one weight of head_dim shared
    # by every head, statistics per head (Qwen3); "full": over the whole
    # projected vector before the head split, a weight per (head, dim)
    # (OLMoE)
    qk_norm: bool = False
    qk_norm_kind: str = "head"
    # Gemma family knobs (all default to the Llama behaviour)
    act: str = "silu"  # MLP gate activation: "silu" | "gelu_tanh" (GeGLU)
    norm_offset: float = 0.0  # RMSNorm scales by (offset + weight); Gemma: 1
    embed_scale: bool = False  # multiply embeddings by sqrt(hidden_size)
    attn_logit_softcap: float = 0.0  # cap*tanh(s/cap) on attention scores
    final_logit_softcap: float = 0.0  # same on the LM-head logits
    # where a block's norms sit, the one description of it: "pre", a norm
    # BEFORE each sublayer (the Llama block); "both", before and after
    # (Gemma-2, Ouro, openPangu: ``post_attn_norm`` / ``post_mlp_norm``
    # beside ``attn_norm`` / ``mlp_norm``); "post", AFTER each sublayer
    # alone (the Olmo 3 family: x + norm(f(x)); the sublayer reads the
    # stream as it is and the layer has the two post norms only)
    norms: str = "pre"
    query_scale: float = 0.0  # score scale; 0 → head_dim**-0.5
    # local-attention window (Gemma-2 alternates local/global layers). We
    # serve such models exactly ONLY within the window: max_model_len is
    # required to be <= sliding_window (enforced at engine init), where
    # local and global attention coincide. A "phi4flash" stack's window
    # layers are served AS windows (``window_binds``): row t sees rows
    # t - sliding_window + 1 .. t, in blocks of a pool of their own that
    # a sequence gives back once no row to come can see them
    sliding_window: int = 0
    # latent attention (MLA; "pangu_ultra_moe"): queries and keys-values
    # pass through low-rank projections with a norm each; a head's key is
    # qk_nope_head_dim values expanded from the kv_lora_rank-wide latent
    # plus qk_rope_head_dim rotated values all heads share. The cache
    # holds ONE row of kv_lora_rank + qk_rope_head_dim values a token and
    # cache layer, keys and values in one (latent_lanes in the pool), and
    # attention runs absorbed: the query is carried into the latent space
    # (models/llama.py _mla_mixer). head_dim is then the query head's
    # qk_nope + qk_rope and num_kv_heads what the file publishes; neither
    # sizes the cache. 0 = keys and values per head
    kv_lora_rank: int = 0
    q_lora_rank: int = 0  # 0: one direct query projection, no norm
    mla_rope: bool = True  # False: nothing is rotated (a NoPE stack's MLA)
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # leading dense layers: the first dense_layers of num_layers have a
    # plain MLP of dense_intermediate_size in place of the sparse block and
    # run before the scan over the expert layers, cache layers 0.. in order
    # (in a patterned stack they keep their place in the pattern, their
    # period a run of its own: stack_segments)
    dense_layers: int = 0
    dense_intermediate_size: int = 0
    # weight-tied stacks ("ouro"): every token passes through the SAME
    # num_layers layers loop_passes times; the final norm closes each pass
    # and every (pass, layer) pair attends over keys and values of its
    # own, so the paged cache holds cache_layers = num_layers x
    # loop_passes layers where the weights hold num_layers. 1 for every
    # other family
    loop_passes: int = 1
    # carry the residual stream between sublayers in float32 (the matmuls
    # keep the model dtype). A looped stack adds loop_passes x 2 x
    # num_layers sublayer outputs to one stream, and each pass feeds the
    # next: bf16 rounding of the stream is what the passes amplify
    residual_f32: bool = False
    # Whisper family (architecture == "whisper": encoder-decoder audio
    # transcription, models/whisper.py). num_heads doubles as both
    # encoder and decoder head count (equal in every Whisper size);
    # num_layers is the DECODER depth; max_model_len is the decoder's
    # max_target_positions (448). Special-token ids follow the
    # multilingual vocab layout (derived in from_hf_config).
    num_mel_bins: int = 80
    encoder_layers: int = 0  # 0 on non-whisper architectures
    n_audio_ctx: int = 1500  # encoder positions; input frames = 2x this
    sot_id: int = 0          # <|startoftranscript|>
    eot_id: int = 0          # <|endoftext|> — also the lowest special id
    lang_base_id: int = 0    # first language token (<|en|>)
    n_langs: int = 0
    translate_id: int = 0
    transcribe_id: int = 0
    sot_prev_id: int = 0     # <|startofprev|> (prompt conditioning)
    notimestamps_id: int = 0
    # weight/activation quantization: None (model dtype) or "int8"
    # (W8A8 — per-channel weight + dynamic per-token activation scales on
    # the MXU's native int8 path; engine/quant.py)
    quant: Optional[str] = None
    # where to load weights from (safetensors dir); None → random init
    weights_path: Optional[str] = None
    tokenizer: Optional[str] = None  # HF tokenizer path; None → byte tokenizer

    @property
    def jax_dtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}[
            self.dtype
        ]

    @property
    def pre_norms(self) -> bool:
        return self.norms != "post"

    @property
    def post_norms(self) -> bool:
        return self.norms != "pre"

    @property
    def q_per_kv(self) -> int:
        """Query heads a head of the cache serves (the attention kernels'
        group)."""
        return self.num_heads // (self.num_kv_heads // 2 if self.diff_attn
                                  else self.num_kv_heads)

    @property
    def cache_kv_heads(self) -> int:
        """Key / value heads as the cache holds them: a differential pair
        is one head, and more than four heads are filled up with empty
        heads to a multiple of four: a token's slab of keys and values,
        (2 x heads, head size), is then whole 8-row tiles, which the
        kernels' DMAs need (10 pairs' 20 rows are refused by the TPU
        compiler: "Slice shape along dimension 3 must be aligned to tiling
        (8)"; 12 heads' 24 rows pass; Olmo-Hybrid's 30 heads' 60 rows are
        held as 32 heads' 64). Up to four heads lie as they are (a slab
        under a tile high is a tile of its own height). The empty heads
        hold zeros and their (zero) queries read zeros."""
        heads = self.num_kv_heads // 2 if self.diff_attn else self.num_kv_heads
        if heads <= 4 and not self.diff_attn:
            return heads
        return -(-heads // 4) * 4

    @property
    def cache_head_dim(self) -> int:
        return self.head_dim * 2 if self.diff_attn else self.head_dim

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def num_held_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def layer_kinds(self) -> tuple:
        """What each layer's token mixer is, the one description of a
        stack's pattern: "attn" (every layer of the shared stack); "gqa" /
        "kda" (attn_period); "mla" / "kda" (mla_layers); "mamba", "swa"
        (window attention), "full", "gmu" and "cross" (mamba_period);
        "parallel" (ssd_heads: a state-space mixer AND attention, counted
        among the recurrent layers and among the attention layers); "gdn" /
        "gqa" (gdn_layers); "swa" / "full" (window_layers: grouped-query
        attention within the window or over all rows)."""
        n = self.num_layers
        if self.window_layers:
            return tuple("swa" if l in self.window_layers else "full"
                         for l in range(n))
        if self.ssd_heads:
            return ("parallel",) * n
        if self.gdn_layers:
            return tuple("gdn" if l in self.gdn_layers else "gqa"
                         for l in range(n))
        if self.mla_layers:
            return tuple("mla" if l in self.mla_layers else "kda"
                         for l in range(n))
        if self.mamba_period:
            full = n // 2 + 1  # the last layer before the cross-decoder
            return tuple(
                ("mamba" if l < full else "gmu") if l % self.mamba_period == 0
                else "swa" if l < full else "full" if l == full else "cross"
                for l in range(n))
        if self.attn_period > 1:
            return tuple("gqa" if l % self.attn_period == 0 else "kda"
                         for l in range(n))
        return ("attn",) * n

    def count_layers(self, *kinds: str) -> int:
        return sum(k in kinds for k in self.layer_kinds)

    @property
    def stack_segments(self) -> tuple:
        """A patterned stack as runs of like periods, ((kinds of a period,
        periods), ...): one scan each (models/llama.py _forward_hybrid). A
        period that holds a leading dense layer is like no other and a run
        of its own; the last period may be short."""
        kinds = self.layer_kinds
        period = self.mamba_period or self.attn_period or 1
        runs = []
        for i in range(0, len(kinds), period):
            p = kinds[i:i + period]
            if (runs and runs[-1][0] == p
                    and i - period >= self.dense_layers):
                runs[-1][1] += 1
            else:
                runs.append([p, 1])
        return tuple((p, n) for p, n in runs)

    @property
    def has_recurrent_state(self) -> bool:
        return self.num_recurrent_layers > 0

    @property
    def patterned(self) -> bool:
        """Whether the layers differ by kind and the stack is walked by
        periods (models/llama.py ``_forward_hybrid``), its mixers stacked
        by kind beside what every layer has."""
        return self.has_recurrent_state or bool(self.window_layers)

    @property
    def num_recurrent_layers(self) -> int:
        return self.count_layers("kda", "mamba", "parallel", "gdn")

    @property
    def window_binds(self) -> bool:
        """Whether the window layers are served as windows (past
        ``sliding_window`` positions), from a block pool of their own."""
        return self.sliding_window > 0 and (
            self.mamba_period > 0 or bool(self.window_layers))

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def ssd_inner(self) -> int:
        """Channels of the state-space mixer with heads (``mamba_d_ssm``)."""
        return self.ssd_heads * self.ssd_head_dim

    @property
    def ssd_conv_dim(self) -> int:
        """Channels its convolution runs over: [x | B | C]."""
        return self.ssd_inner + 2 * self.ssd_groups * self.ssd_state

    @property
    def gdn_conv_dim(self) -> int:
        """Channels a GDN layer's convolution runs over: [q | k | v]."""
        return self.gdn_heads * (2 * self.gdn_key_dim + self.gdn_value_dim)

    @property
    def is_latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_width(self) -> int:
        """Values of a latent cache row: the latent and the shared rotated
        key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self) -> int:
        """A latent row as the pool holds it: ``latent_width`` padded with
        zeros to whole 128-lane tiles (576 -> 640). At 576 the TPU
        compiler copied the whole pool into a 640-lane layout at every
        update (PERF.md section 6, PR 43)."""
        return -(-self.latent_width // 128) * 128

    @property
    def num_expert_layers(self) -> int:
        return self.num_layers - self.dense_layers

    @property
    def num_attn_layers(self) -> int:
        """Attention layers that own keys and values (a cross-attention
        layer reads another's)."""
        return self.count_layers("attn", "gqa", "mla", "swa", "full",
                                 "parallel")

    @property
    def num_kda_layers(self) -> int:
        return self.count_layers("kda")

    @property
    def cache_layers(self) -> int:
        """Layers of KV cache: one per (pass, attention layer) pair."""
        return self.num_attn_layers * self.loop_passes

    def kv_pool_shape(self, num_blocks: int, block_size: int,
                      window: bool = False) -> tuple:
        """The paged pool's shape, the one place that says what a token of
        context holds: a ``(2*KH, D)`` slab of keys and values a cache
        layer, or with latent attention one row of ``latent_lanes``. Where
        the window binds there are two pools: the window layers' (``window``
        True), whose rows live while a row to come can see them, and the
        pool of the layers whose rows live for the whole context."""
        token = ((self.latent_lanes,) if self.is_latent
                 else (2 * self.cache_kv_heads, self.cache_head_dim))
        layers = self.cache_layers
        if self.window_binds:
            layers = (self.count_layers("swa") if window
                      else layers - self.count_layers("swa"))
        return (layers, num_blocks, block_size, *token)

    def _token_bytes(self, window: bool) -> int:
        n = 1
        for d in self.kv_pool_shape(1, 1, window):
            n *= d
        return n * jnp.dtype(self.jax_dtype).itemsize

    @property
    def kv_bytes_per_token(self) -> int:
        """What one token of context holds in the pool, all cache layers
        (where the window binds: for the whole context, the other pool's
        ``window_kv_bytes_per_token`` for ``sliding_window`` rows)."""
        return self._token_bytes(False)

    @property
    def window_kv_bytes_per_token(self) -> int:
        return self._token_bytes(True) if self.window_binds else 0

    def recurrent_state_bytes(self, slots: int) -> int:
        """What the recurrent layers keep for ``slots`` decode slots: a
        float32 state and a conv tail in the model dtype, a KDA or GDN
        layer's per head, a state-space layer's per channel (with heads:
        per head and channel, the tail over [x | B | C])."""
        item = jnp.dtype(self.jax_dtype).itemsize
        h, d = self.kda_heads, self.kda_head_dim
        kda = h * d * d * 4 + (self.kda_conv - 1) * 3 * h * d * item
        mamba = self.mamba_inner * (self.mamba_state * 4
                                    + (self.mamba_conv - 1) * item)
        ssd = (self.ssd_inner * self.ssd_state * 4
               + (self.ssd_conv - 1) * self.ssd_conv_dim * item)
        # a GDN layer's: d_k x d_v a head (not square), the tail over
        # 2 H d_k + H d_v channels
        gdn = (self.gdn_heads * self.gdn_key_dim * self.gdn_value_dim * 4
               + (self.gdn_conv - 1) * self.gdn_conv_dim * item)
        return slots * (self.num_kda_layers * kda
                        + self.count_layers("mamba") * mamba
                        + self.count_layers("parallel") * ssd
                        + self.count_layers("gdn") * gdn)

    @staticmethod
    def from_hf_config(cfg: dict[str, Any], name: str = "") -> "ModelConfig":
        """Build from a HuggingFace config.json dict (LlamaForCausalLM /
        MixtralForCausalLM style keys)."""
        arch = "llama"
        archs = cfg.get("architectures") or []
        if any("Whisper" in a for a in archs):
            return ModelConfig._whisper_from_hf(cfg, name)
        if any("Mixtral" in a for a in archs) or "num_local_experts" in cfg:
            arch = "mixtral"
        elif (any(a == "OlmoeForCausalLM" for a in archs)
              or cfg.get("model_type") == "olmoe"):
            if cfg.get("clip_qkv") is not None:
                # null in every published OLMoE file; a clamp on q/k/v
                # that is silently skipped would serve another model
                raise ValueError(
                    f"OLMoE with clip_qkv={cfg['clip_qkv']!r} is not "
                    "supported (only clip_qkv: null)")
            arch = "olmoe"
        elif (any(a == "OuroForCausalLM" for a in archs)
              or cfg.get("model_type") == "ouro"):
            # a weight-tied stack run total_ut_steps times. What is served
            # is every token through every pass, each layer attending over
            # the whole context: anything else is refused by name rather
            # than served as another model
            if float(cfg.get("early_exit_threshold", 1.0)) < 1.0:
                raise ValueError(
                    f"Ouro with early_exit_threshold="
                    f"{cfg['early_exit_threshold']!r} is not supported: the "
                    "exit gate is not evaluated, every token runs all "
                    "total_ut_steps passes (only early_exit_threshold >= 1)")
            other = sorted({t for t in cfg.get("layer_types") or ()
                            if t != "full_attention"})
            if other:
                raise ValueError(
                    f"Ouro with layer_types {other} is not supported (only "
                    "full_attention layers)")
            if cfg.get("use_sliding_window"):
                raise ValueError(
                    "Ouro with use_sliding_window: true is not supported "
                    "(every layer attends over the whole context)")
            arch = "ouro"
        elif cfg.get("model_type") == "solar_open2":
            return ModelConfig._solar_open2_from_hf(cfg, name)
        elif cfg.get("model_type") == "pangu_ultra_moe":
            return ModelConfig._pangu_ultra_moe_from_hf(cfg, name)
        elif cfg.get("model_type") == "phi4flash":
            return ModelConfig._phi4flash_from_hf(cfg, name)
        elif cfg.get("model_type") == "kimi_linear":
            return ModelConfig._kimi_linear_from_hf(cfg, name)
        elif cfg.get("model_type") == "falcon_h1":
            return ModelConfig._falcon_h1_from_hf(cfg, name)
        elif cfg.get("model_type") == "olmo_hybrid":
            return ModelConfig._olmo_hybrid_from_hf(cfg, name)
        elif cfg.get("model_type") == "afmoe":
            return ModelConfig._afmoe_from_hf(cfg, name)
        elif any("Phi3" in a for a in archs):
            # only the standard Phi-3 maps onto the fused-Llama layout;
            # Phi-3-small (query_key_value naming, gegelu, blocksparse)
            # would die mid-load with an opaque KeyError — refuse up front
            if not all(a == "Phi3ForCausalLM" for a in archs if "Phi3" in a):
                raise ValueError(
                    f"unsupported Phi-3 variant {archs}; supported: "
                    "Phi3ForCausalLM"
                )
            # Llama stack with fused HF qkv/gate_up weight layout; LongRoPE
            # extension factors are not implemented — serve within the
            # original context only
            if cfg.get("rope_scaling"):
                raise ValueError(
                    "Phi-3 LongRoPE rope_scaling is not supported; use a "
                    "checkpoint without rope_scaling (e.g. the 4k variants)"
                )
            arch = "phi3"
        elif any("Gemma2" in a for a in archs):
            arch = "gemma2"
        elif any(a.startswith("Gemma") and "Gemma2" not in a for a in archs):
            # only Gemma 1 maps onto the gemma knobs; Gemma-3 adds QK-norm
            # and per-layer rope/window layouts we don't implement — loading
            # it as gemma-1 would silently drop tensors and serve garbage
            if not all(a.startswith(("GemmaModel", "GemmaFor"))
                       for a in archs if "Gemma" in a):
                raise ValueError(
                    f"unsupported Gemma variant {archs}; supported: "
                    "GemmaForCausalLM (gemma), Gemma2ForCausalLM (gemma2)"
                )
            arch = "gemma"
        qkv_bias = any("Qwen2" in a for a in archs) or bool(
            cfg.get("attention_bias", False)
        )
        if any("Qwen3Moe" in a for a in archs):
            # Qwen3-MoE stores mlp.experts.N.* under the num_experts key
            # (not Mixtral's num_local_experts/block_sparse_moe layout) —
            # parsing it as dense would KeyError mid-load
            raise ValueError(
                f"unsupported Qwen3 variant {archs}; supported: "
                "Qwen3ForCausalLM (dense)"
            )
        if arch not in ("mixtral", "olmoe") and any(
                cfg.get(k) for k in ("num_experts", "n_routed_experts",
                                     "moe_num_experts")):
            # any other MoE family (Qwen2-MoE, DeepSeek, ...) has shared
            # experts, other routing or other tensor names: read as dense
            # it would serve a different model
            raise ValueError(
                f"unsupported MoE architecture {archs or cfg.get('model_type')}"
                "; supported: MixtralForCausalLM, OlmoeForCausalLM")
        qk_norm = any("Qwen3" in a for a in archs) or arch == "olmoe"
        hidden = cfg["hidden_size"]
        heads = cfg["num_attention_heads"]
        gemma = arch in ("gemma", "gemma2")
        hf_act = cfg.get("hidden_activation") or cfg.get("hidden_act") or "silu"
        qpas = cfg.get("query_pre_attn_scalar", 0)
        # local-attention window: Gemma-2 alternates local/global, Mistral
        # and Phi-3 window every layer — either way exact serving holds only
        # within the window (the ModelConfig.sliding_window gate). Qwen2/3
        # checkpoints carry a sliding_window value but disable it.
        window = int(cfg.get("sliding_window") or 0)
        if not cfg.get("use_sliding_window", True):
            window = 0
        max_len = cfg.get("max_position_embeddings", 4096)
        if window:
            # exact-serving gate: local and global attention coincide only
            # within the window (see ModelConfig.sliding_window)
            max_len = min(max_len, window)
        return ModelConfig(
            qkv_bias=qkv_bias,
            qk_norm=qk_norm,
            qk_norm_kind="full" if arch == "olmoe" else "head",
            name=name or cfg.get("_name_or_path", "hf-model"),
            architecture=arch,
            vocab_size=cfg["vocab_size"],
            hidden_size=hidden,
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads", heads),
            # some checkpoints write an explicit null here
            head_dim=cfg.get("head_dim") or hidden // heads,
            rope_theta=cfg.get("rope_theta", 10000.0),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_model_len=max_len,
            tie_word_embeddings=cfg.get("tie_word_embeddings", gemma),
            num_experts=(cfg["num_experts"] if arch == "olmoe"
                         else cfg.get("num_local_experts", 0)),
            num_experts_per_tok=cfg.get("num_experts_per_tok", 2),
            norm_topk_prob=(bool(cfg.get("norm_topk_prob", False))
                            if arch == "olmoe" else True),
            act="gelu_tanh" if "gelu" in hf_act else "silu",
            norm_offset=1.0 if gemma else 0.0,
            embed_scale=gemma,
            attn_logit_softcap=float(
                cfg.get("attn_logit_softcapping") or 0.0),
            final_logit_softcap=float(
                cfg.get("final_logit_softcapping") or 0.0),
            norms="both" if arch in ("gemma2", "ouro") else "pre",
            query_scale=(qpas ** -0.5) if qpas else 0.0,
            sliding_window=window,
            loop_passes=(int(cfg.get("total_ut_steps", 1))
                         if arch == "ouro" else 1),
            residual_f32=arch == "ouro",
        )

    @staticmethod
    def _solar_open2_from_hf(cfg: dict, name: str = "") -> "ModelConfig":
        """``model_type: solar_open2``: one softmax GQA layer (no rope, a
        sigmoid output gate) in every ``gqa_interval + 1`` layers, gated
        delta-rule (KDA) layers between them, and in every layer a sparse
        block with sigmoid routing and a shared expert. Two keys of this
        repo state the chip's share of the routed experts:
        ``n_routed_experts_held`` and ``routed_expert_offset`` (absent:
        all of them). What is not computed is refused by name."""
        what = "solar_open2"
        if cfg.get("kda_use_full_proj"):
            raise ValueError(
                f"{what} with kda_use_full_proj: true is not supported "
                "(only the low-rank decay and gate projections)")
        if int(cfg.get("first_k_dense_replace", 0)) > 0:
            raise ValueError(
                f"{what} with first_k_dense_replace="
                f"{cfg['first_k_dense_replace']} is not supported (every "
                "layer's MLP is the sparse block)")
        if int(cfg.get("n_group", 1) or 1) > 1:
            raise ValueError(
                f"{what} with n_group={cfg['n_group']} is not supported "
                "(no group-limited routing)")
        if cfg.get("use_rope", False):
            raise ValueError(
                f"{what} with use_rope: true (partial_rotary_factor="
                f"{cfg.get('partial_rotary_factor', 1)}) is not supported: "
                "the hybrid stack's attention layers apply no positional "
                "encoding")
        layers = int(cfg["num_hidden_layers"])
        period = int(cfg.get("gqa_interval", 3)) + 1
        want = list(range(0, layers, period))
        if list(cfg.get("gqa_layers", want)) != want or layers % period:
            raise ValueError(
                f"{what} with gqa_layers={cfg.get('gqa_layers')} is not "
                f"supported: only one attention layer first in every "
                f"gqa_interval + 1 = {period} layers of "
                f"{layers} (whole periods)")
        lin = cfg.get("linear_attn_config") or {}
        heads = cfg["num_attention_heads"]
        kda_heads = int(lin.get("num_heads", heads))
        if lin.get("num_kv_heads") not in (None, kda_heads):
            raise ValueError(
                f"{what} with linear_attn_config.num_kv_heads="
                f"{lin['num_kv_heads']} is not supported (a key and value "
                "head per query head)")
        experts, held, offset = _expert_share(what, cfg, "n_routed_experts")
        kda_dim = int(lin.get("head_dim", cfg["head_dim"]))
        return ModelConfig(
            name=name or cfg.get("_name_or_path", "hf-model"),
            architecture="solar_open2",
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["moe_intermediate_size"],
            num_layers=layers,
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads", heads),
            head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
            rope_theta=cfg.get("rope_theta", 10000.0),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_model_len=cfg.get("max_position_embeddings", 4096),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            num_experts=experts,
            num_experts_per_tok=cfg.get("num_experts_per_tok", 8),
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            experts_held=held if held < experts else 0,
            expert_offset=offset,
            moe_scoring="sigmoid",
            routed_scaling=float(cfg.get("routed_scaling_factor", 1.0)),
            shared_expert_size=(int(cfg.get("n_shared_experts", 0))
                                * cfg["moe_intermediate_size"]),
            attn_period=period,
            kda_heads=kda_heads,
            kda_head_dim=kda_dim,
            kda_conv=int(lin.get("short_conv_kernel_size", 4)),
            # not a key of the published file: the low-rank width of the
            # decay and output-gate pairs is the KDA head size
            kda_rank=kda_dim,
            kda_neg_eigval=bool(cfg.get("kda_allow_neg_eigval", False)),
            attn_gate=bool(cfg.get("use_gqa_gate", False)),
        )

    @staticmethod
    def _pangu_ultra_moe_from_hf(cfg: dict, name: str = "") -> "ModelConfig":
        """``model_type: pangu_ultra_moe``: latent attention (MLA) in every
        layer, norms before and after each sublayer, ``first_k_dense_replace``
        leading dense layers and then sparse blocks with sigmoid routing and
        a shared expert. ``n_routed_experts_held`` / ``routed_expert_offset``
        state the chip's share of the routed experts, as for solar_open2.
        What is not computed is refused by name."""
        what = "pangu_ultra_moe"
        if int(cfg.get("n_group", 1) or 1) > 1:
            raise ValueError(
                f"{what} with n_group={cfg['n_group']} is not supported "
                "(no group-limited routing)")
        if cfg.get("rope_scaling"):
            raise ValueError(
                f"{what} with rope_scaling={cfg['rope_scaling']!r} is not "
                "supported (plain rotary frequencies from rope_theta only)")
        if not cfg.get("sandwich_norm", False):
            raise ValueError(
                f"{what} with sandwich_norm: false is not supported (a norm "
                "before and after each sublayer, as published)")
        if int(cfg.get("num_nextn_predict_layers", 0) or 0) > 0:
            raise ValueError(
                f"{what} with num_nextn_predict_layers="
                f"{cfg['num_nextn_predict_layers']} is not supported: the "
                "multi-token-prediction module is not loaded and nothing "
                "drafts with it (serve the file with it set to 0)")
        if cfg.get("attention_bias", False):
            raise ValueError(
                f"{what} with attention_bias: true is not supported")
        layers = int(cfg["num_hidden_layers"])
        dense = int(cfg.get("first_k_dense_replace", 0))
        if not 0 <= dense < layers:
            raise ValueError(
                f"{what}: first_k_dense_replace={dense} leaves no expert "
                f"layer of num_hidden_layers={layers}")
        experts, held, offset = _expert_share(what, cfg, "n_routed_experts")
        heads = cfg["num_attention_heads"]
        nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
        return ModelConfig(
            name=name or cfg.get("_name_or_path", "hf-model"),
            architecture="pangu_ultra_moe",
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["moe_intermediate_size"],
            num_layers=layers,
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads", heads),
            head_dim=nope + rope,
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_model_len=cfg.get("max_position_embeddings", 4096),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            num_experts=experts,
            num_experts_per_tok=cfg.get("num_experts_per_tok", 8),
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            experts_held=held if held < experts else 0,
            expert_offset=offset,
            moe_scoring="sigmoid",
            routed_scaling=float(cfg.get("routed_scaling_factor", 1.0)),
            shared_expert_size=(int(cfg.get("n_shared_experts", 0))
                                * cfg["moe_intermediate_size"]),
            norms="both",
            kv_lora_rank=int(cfg["kv_lora_rank"]),
            q_lora_rank=int(cfg["q_lora_rank"]),
            qk_nope_head_dim=nope,
            qk_rope_head_dim=rope,
            v_head_dim=int(cfg["v_head_dim"]),
            dense_layers=dense,
            dense_intermediate_size=cfg["intermediate_size"] if dense else 0,
        )

    @staticmethod
    def _kimi_linear_from_hf(cfg: dict, name: str = "") -> "ModelConfig":
        """``model_type: kimi_linear``: KDA layers and latent-attention
        (MLA) layers in one stack, each named in a 1-based list of
        ``linear_attn_config`` (``kda_layers``, ``full_attn_layers``); the
        MLA layers project their queries directly (``q_lora_rank: null``)
        and rotate nothing (``mla_use_nope``); ``first_k_dense_replace``
        leading dense layers, then sparse blocks with sigmoid routing and
        a shared expert. ``n_routed_experts_held`` /
        ``routed_expert_offset`` state the chip's share of the routed
        experts, as for solar_open2. What is not computed is refused by
        name."""
        what = "kimi_linear"
        if not cfg.get("mla_use_nope", False):
            raise ValueError(
                f"{what} with mla_use_nope: false is not supported: this "
                "stack's latent attention rotates nothing (order comes "
                "from the KDA layers)")
        if cfg.get("q_lora_rank") is not None:
            raise ValueError(
                f"{what} with q_lora_rank={cfg['q_lora_rank']!r} is not "
                "supported: no test holds the low-rank query path inside a "
                "patterned stack (only q_lora_rank: null)")
        if cfg.get("rope_scaling"):
            raise ValueError(
                f"{what} with rope_scaling={cfg['rope_scaling']!r} is not "
                "supported (nothing is rotated)")
        if int(cfg.get("num_expert_group", 1) or 1) > 1:
            raise ValueError(
                f"{what} with num_expert_group={cfg['num_expert_group']} is "
                "not supported (no group-limited routing)")
        if int(cfg.get("num_nextn_predict_layers", 0) or 0) > 0:
            raise ValueError(
                f"{what} with num_nextn_predict_layers="
                f"{cfg['num_nextn_predict_layers']} is not supported: the "
                "multi-token-prediction module is not loaded and nothing "
                "drafts with it (serve the file with it set to 0)")
        dense = int(cfg.get("first_k_dense_replace", 0))
        if dense > 1:
            raise ValueError(
                f"{what} with first_k_dense_replace={dense} is not "
                "supported: no test holds more than one leading dense layer "
                "inside a patterned stack")
        if int(cfg.get("moe_layer_freq", 1)) != 1:
            raise ValueError(
                f"{what} with moe_layer_freq={cfg['moe_layer_freq']} is not "
                "supported (every layer behind the dense ones is sparse)")
        if cfg.get("moe_router_activation_func", "sigmoid") != "sigmoid":
            raise ValueError(
                f"{what} with moe_router_activation_func="
                f"{cfg['moe_router_activation_func']!r} is not supported "
                "(sigmoid scores with a selection bias)")
        layers = int(cfg["num_hidden_layers"])
        lin = cfg.get("linear_attn_config") or {}
        kda = [int(l) for l in lin.get("kda_layers") or ()]
        mla = [int(l) for l in lin.get("full_attn_layers") or ()]
        if sorted(kda + mla) != list(range(1, layers + 1)) or not (kda
                                                                  and mla):
            raise ValueError(
                f"{what}: linear_attn_config.kda_layers={kda} and "
                f"full_attn_layers={mla} do not name every layer 1.."
                f"{layers} once, with a layer of each kind")
        heads = cfg["num_attention_heads"]
        kda_heads = int(lin.get("num_heads", heads))
        experts, held, offset = _expert_share(what, cfg, "num_experts")
        kda_dim = int(lin.get("head_dim", cfg["head_dim"]))
        nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
        return ModelConfig(
            name=name or cfg.get("_name_or_path", "hf-model"),
            architecture="kimi_linear",
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["moe_intermediate_size"],
            num_layers=layers,
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads", heads),
            # the query head's two parts; the file's head_dim (hidden /
            # heads) sizes nothing
            head_dim=nope + rope,
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_model_len=(cfg.get("max_position_embeddings")
                           or cfg.get("model_max_length", 4096)),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            num_experts=experts,
            num_experts_per_tok=cfg.get("num_experts_per_token", 8),
            norm_topk_prob=bool(cfg.get("moe_renormalize", True)),
            experts_held=held if held < experts else 0,
            expert_offset=offset,
            moe_scoring="sigmoid",
            routed_scaling=float(cfg.get("routed_scaling_factor", 1.0)),
            shared_expert_size=(int(cfg.get("num_shared_experts", 0))
                                * cfg["moe_intermediate_size"]),
            # the first MLA layer closes the first period
            attn_period=min(mla),
            mla_layers=tuple(l - 1 for l in sorted(mla)),
            kda_heads=kda_heads,
            kda_head_dim=kda_dim,
            kda_conv=int(lin.get("short_conv_kernel_size", 4)),
            # not a key of the published file: the low-rank width of the
            # decay and output-gate pairs is the KDA head size
            kda_rank=kda_dim,
            kv_lora_rank=int(cfg["kv_lora_rank"]),
            qk_nope_head_dim=nope,
            qk_rope_head_dim=rope,
            v_head_dim=int(cfg["v_head_dim"]),
            mla_rope=False,
            dense_layers=dense,
            dense_intermediate_size=cfg["intermediate_size"] if dense else 0,
        )

    @staticmethod
    def _phi4flash_from_hf(cfg: dict, name: str = "") -> "ModelConfig":
        """``model_type: phi4flash`` (SambaY, arXiv:2507.06607): see
        ``mamba_period``. The file states the widths, ``mb_per_layer``, the
        window and the norm's eps; the state-space sizes are the family's
        modelling code's defaults (d_state 16, d_conv 4, expand 2, dt_rank
        ceil(hidden / 16)) unless the file gives them. No positional
        encoding anywhere. What is not computed is refused by name."""
        what = "phi4flash"
        layers, period = int(cfg["num_hidden_layers"]), int(cfg["mb_per_layer"])
        if period != 2 or layers % 4 or layers < 8:
            raise ValueError(
                f"{what} with mb_per_layer={period}, num_hidden_layers="
                f"{layers} is not supported: only a state-space layer "
                "before every attention layer (mb_per_layer 2) and a depth "
                "that splits into whole (state-space, attention) pairs on "
                "both sides of the cross-decoder's start, layers // 2 + 2")
        if cfg.get("mlp_bias") or cfg.get("lm_head_bias"):
            raise ValueError(
                f"{what} with mlp_bias / lm_head_bias is not supported")
        heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        if heads % 2 or kv % 2 or (heads // 2) % (kv // 2):
            raise ValueError(
                f"{what}: differential attention pairs heads (2i, 2i + 1); "
                f"{heads} query / {kv} key-value heads do not pair up")
        if not cfg.get("tie_word_embeddings", True):
            raise ValueError(f"{what} with an untied head is not supported")
        hidden = cfg["hidden_size"]
        return ModelConfig(
            name=name or cfg.get("_name_or_path", "hf-model"),
            architecture="phi4flash",
            vocab_size=cfg["vocab_size"],
            hidden_size=hidden,
            intermediate_size=cfg["intermediate_size"],
            num_layers=layers,
            num_heads=heads,
            num_kv_heads=kv,
            head_dim=cfg.get("head_dim") or hidden // heads,
            rms_norm_eps=cfg.get("layer_norm_eps", 1e-5),
            max_model_len=cfg.get("max_position_embeddings", 4096),
            tie_word_embeddings=True,
            qkv_bias=True,
            sliding_window=int(cfg.get("sliding_window") or 0),
            mamba_period=period,
            mamba_state=int(cfg.get("mamba_d_state", 16)),
            mamba_conv=int(cfg.get("mamba_d_conv", 4)),
            mamba_expand=int(cfg.get("mamba_expand", 2)),
            mamba_dt_rank=int(cfg.get("mamba_dt_rank") or -(-hidden // 16)),
            diff_attn=True,
            layer_norm=True,
        )

    @staticmethod
    def _falcon_h1_from_hf(cfg: dict, name: str = "") -> "ModelConfig":
        """``model_type: falcon_h1``: see ``ssd_heads``. The file states
        every width, the state-space sizes and the fourteen scalars. What
        is not computed is refused by name."""
        what = "falcon_h1"
        hidden = int(cfg["hidden_size"])
        heads, p = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
        groups = int(cfg.get("mamba_n_groups", 1))
        d_ssm = cfg.get("mamba_d_ssm")
        d_ssm = (int(cfg.get("mamba_expand", 2) * hidden) if d_ssm is None
                 else int(d_ssm))
        refused = [
            why for bad, why in (
                (not cfg.get("mamba_use_mlp", True),
                 "mamba_use_mlp: false (a layer without its MLP)"),
                (cfg.get("mamba_norm_before_gate", False),
                 "mamba_norm_before_gate: true (only the gate before the "
                 "grouped norm)"),
                (not cfg.get("mamba_rms_norm", True),
                 "mamba_rms_norm: false (only the gated grouped RMSNorm)"),
                (cfg.get("attn_layer_indices") is not None,
                 f"attn_layer_indices={cfg.get('attn_layer_indices')!r} "
                 "(only attention in every layer: null)"),
                (cfg.get("rope_scaling") is not None,
                 f"rope_scaling={cfg.get('rope_scaling')!r} (only null)"),
                *((bool(cfg.get(k)), f"{k}: true (no projection has a bias)")
                  for k in ("attention_bias", "mlp_bias", "mamba_proj_bias",
                            "projectors_bias")),
                (not cfg.get("mamba_conv_bias", True),
                 "mamba_conv_bias: false (the convolution has a bias)"),
                (heads * p != d_ssm,
                 f"mamba_n_heads {heads} x mamba_d_head {p} != mamba_d_ssm "
                 f"{d_ssm}"),
                (groups < 1 or heads % groups != 0,
                 f"mamba_n_heads {heads} is no multiple of mamba_n_groups "
                 f"{groups}"),
                ((cfg.get("hidden_act") or "silu") != "silu",
                 f"hidden_act={cfg.get('hidden_act')!r} (only silu)"),
                (len(cfg.get("ssm_multipliers") or [1] * 5) != 5
                 or len(cfg.get("mlp_multipliers") or [1] * 2) != 2,
                 "ssm_multipliers / mlp_multipliers of another length than "
                 "5 / 2"),
            ) if bad]
        if refused:
            raise ValueError(f"{what} is not supported with: "
                             + "; ".join(refused))
        n_heads = int(cfg["num_attention_heads"])
        gate, down = (float(m) for m in cfg.get("mlp_multipliers") or (1, 1))
        return ModelConfig(
            name=name or cfg.get("_name_or_path", "hf-model"),
            architecture="falcon_h1",
            vocab_size=cfg["vocab_size"],
            hidden_size=hidden,
            intermediate_size=cfg["intermediate_size"],
            num_layers=int(cfg["num_hidden_layers"]),
            num_heads=n_heads,
            num_kv_heads=cfg.get("num_key_value_heads", n_heads),
            head_dim=cfg.get("head_dim") or hidden // n_heads,
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_model_len=cfg.get("max_position_embeddings", 4096),
            tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
            ssd_heads=heads,
            ssd_head_dim=p,
            ssd_state=int(cfg["mamba_d_state"]),
            ssd_groups=groups,
            ssd_conv=int(cfg.get("mamba_d_conv", 4)),
            ssd_multipliers=tuple(
                float(m) for m in cfg.get("ssm_multipliers") or (1,) * 5),
            ssd_in_multiplier=float(cfg.get("ssm_in_multiplier", 1)),
            ssd_out_multiplier=float(cfg.get("ssm_out_multiplier", 1)),
            attn_in_multiplier=float(cfg.get("attention_in_multiplier", 1)),
            attn_out_multiplier=float(cfg.get("attention_out_multiplier", 1)),
            key_multiplier=float(cfg.get("key_multiplier", 1)),
            mlp_gate_multiplier=gate,
            mlp_down_multiplier=down,
            embedding_multiplier=float(cfg.get("embedding_multiplier", 1)),
            lm_head_multiplier=float(cfg.get("lm_head_multiplier", 1)),
        )

    @staticmethod
    def _olmo_hybrid_from_hf(cfg: dict, name: str = "") -> "ModelConfig":
        """``model_type: olmo_hybrid``: Gated DeltaNet layers and full
        multi-head attention, named layer by layer in ``layer_types`` (one
        period repeated: the full layer closes it), in the Olmo 3 family's
        block: a norm AFTER each sublayer and none before, RMSNorm over the
        whole q and k projections, and nothing rotated
        (``rope_parameters.rope_theta`` null). What is not computed is
        refused by name."""
        what = "olmo_hybrid"
        layers = int(cfg["num_hidden_layers"])
        types = list(cfg.get("layer_types") or ())
        known = {"linear_attention": "gdn", "full_attention": "gqa"}
        kinds = [known.get(t) for t in types]
        # one period, repeated: up to and with the first full layer
        period = (types.index("full_attention") + 1
                  if "full_attention" in types else 0)
        whole = (period >= 2 and layers % period == 0
                 and types == types[:period] * (layers // period))
        heads, kv = (int(cfg[k]) for k in ("linear_num_key_heads",
                                           "linear_num_value_heads"))
        theta = (cfg.get("rope_parameters") or {}).get(
            "rope_theta", cfg.get("rope_theta"))
        refused = [
            why for bad, why in (
                ("sliding_attention" in types,
                 "a sliding_attention entry in layer_types (only "
                 "linear_attention and full_attention layers)"),
                (None in kinds and "sliding_attention" not in types,
                 f"layer_types entries {sorted(set(types) - set(known))} "
                 "(only linear_attention and full_attention layers)"),
                (len(types) != layers,
                 f"layer_types of {len(types)} entries for "
                 f"num_hidden_layers={layers}"),
                (not whole,
                 "a layer_types that is no whole number of one period "
                 "(linear_attention layers closed by one full_attention "
                 "layer)"),
                (heads != kv,
                 f"linear_num_key_heads {heads} != linear_num_value_heads "
                 f"{kv} (a key head a value head)"),
                (heads % 2 != 0,
                 f"linear_num_value_heads {kv} odd (the state lies two "
                 "heads side by side: ops/gdn.py)"),
                (theta is not None,
                 f"rope_theta={theta!r} (only null: nothing is rotated)"),
                (bool(cfg.get("attention_bias")),
                 "attention_bias: true (no projection has a bias)"),
                (bool(cfg.get("tie_word_embeddings")),
                 "tie_word_embeddings: true (only an untied head)"),
                ((cfg.get("hidden_act") or "silu") != "silu",
                 f"hidden_act={cfg.get('hidden_act')!r} (only silu)"),
            ) if bad]
        if refused:
            raise ValueError(f"{what} is not supported with: "
                             + "; ".join(refused))
        hidden, n_heads = int(cfg["hidden_size"]), cfg["num_attention_heads"]
        return ModelConfig(
            name=name or cfg.get("_name_or_path", "hf-model"),
            architecture="olmo_hybrid",
            vocab_size=cfg["vocab_size"],
            hidden_size=hidden,
            intermediate_size=cfg["intermediate_size"],
            num_layers=layers,
            num_heads=n_heads,
            num_kv_heads=cfg.get("num_key_value_heads", n_heads),
            head_dim=cfg.get("head_dim") or hidden // n_heads,
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_model_len=cfg.get("max_position_embeddings", 4096),
            qk_norm=True,
            qk_norm_kind="full",
            norms="post",
            attn_period=period,
            gdn_layers=tuple(l for l, k in enumerate(kinds) if k == "gdn"),
            gdn_heads=heads,
            gdn_key_dim=int(cfg["linear_key_head_dim"]),
            gdn_value_dim=int(cfg["linear_value_head_dim"]),
            gdn_conv=int(cfg.get("linear_conv_kernel_dim", 4)),
            gdn_neg_eigval=bool(cfg.get("linear_allow_neg_eigval", False)),
        )

    @staticmethod
    def _afmoe_from_hf(cfg: dict, name: str = "") -> "ModelConfig":
        """``model_type: afmoe`` (Arcee Trinity): grouped-query attention
        in every layer, named layer by layer in ``layer_types``: a
        ``sliding_attention`` layer rotates q and k and sees
        ``sliding_window`` rows, a ``full_attention`` layer (the last of
        every ``global_attn_every_n_layers``) rotates nothing and sees all
        rows; per-head RMSNorm on q and k, a sigmoid gate on the attention
        output, a norm before AND after each sublayer, the embedding times
        sqrt(hidden) (``mup_enabled``), ``num_dense_layers`` leading dense
        layers and then sparse blocks with sigmoid routing, a selection
        bias and a shared expert. ``n_routed_experts_held`` /
        ``routed_expert_offset`` state the chip's share of the routed
        experts, as for solar_open2. What is not computed is refused by
        name."""
        what = "afmoe"
        layers = int(cfg["num_hidden_layers"])
        types = list(cfg.get("layer_types") or ())
        period = int(cfg.get("global_attn_every_n_layers", 4))
        want = ["full_attention" if l % period == period - 1
                else "sliding_attention" for l in range(layers)]
        dense = int(cfg.get("num_dense_layers", 0))
        window = int(cfg.get("sliding_window") or 0)
        other = sorted(set(types) - set(want))
        refused = [
            why for bad, why in (
                (any(int(cfg.get(k, 1) or 1) > 1 for k in (
                    "n_group", "num_expert_groups", "topk_group",
                    "num_limited_groups")),
                 f"n_group={cfg.get('n_group')} / num_expert_groups="
                 f"{cfg.get('num_expert_groups')} (no group-limited "
                 "routing)"),
                (cfg.get("rope_scaling") is not None,
                 f"rope_scaling={cfg.get('rope_scaling')!r} (plain rotary "
                 "frequencies from rope_theta only)"),
                (bool(other),
                 f"layer_types entries {other} (only sliding_attention and "
                 "full_attention layers)"),
                (period < 2 or types != want,
                 f"a layer_types that is not num_hidden_layers={layers} "
                 "entries of sliding_attention layers closed by one "
                 f"full_attention layer in every global_attn_every_n_layers"
                 f"={period}"),
                (window <= 0, f"sliding_window={cfg.get('sliding_window')!r} "
                 "(the window layers need a window)"),
                (cfg.get("score_func", "sigmoid") != "sigmoid",
                 f"score_func={cfg.get('score_func')!r} (sigmoid scores "
                 "with a selection bias)"),
                (not 0 <= dense < layers,
                 f"num_dense_layers={dense} leaves no expert layer of "
                 f"num_hidden_layers={layers}"),
                (bool(cfg.get("attention_bias")),
                 "attention_bias: true (no projection has a bias)"),
                (bool(cfg.get("tie_word_embeddings")),
                 "tie_word_embeddings: true (only an untied head)"),
                ((cfg.get("hidden_act") or "silu") != "silu",
                 f"hidden_act={cfg.get('hidden_act')!r} (only silu)"),
            ) if bad]
        if refused:
            raise ValueError(f"{what} is not supported with: "
                             + "; ".join(refused))
        experts, held, offset = _expert_share(what, cfg, "num_experts")
        hidden, heads = int(cfg["hidden_size"]), cfg["num_attention_heads"]
        return ModelConfig(
            name=name or cfg.get("_name_or_path", "hf-model"),
            architecture="afmoe",
            vocab_size=cfg["vocab_size"],
            hidden_size=hidden,
            intermediate_size=cfg["moe_intermediate_size"],
            num_layers=layers,
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads", heads),
            head_dim=cfg.get("head_dim") or hidden // heads,
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_model_len=cfg.get("max_position_embeddings", 4096),
            num_experts=experts,
            num_experts_per_tok=cfg.get("num_experts_per_tok", 4),
            norm_topk_prob=bool(cfg.get("route_norm", True)),
            experts_held=held if held < experts else 0,
            expert_offset=offset,
            moe_scoring="sigmoid",
            routed_scaling=float(cfg.get("route_scale", 1.0)),
            shared_expert_size=(int(cfg.get("num_shared_experts", 0))
                                * cfg["moe_intermediate_size"]),
            attn_period=period,
            window_layers=tuple(l for l, t in enumerate(types)
                                if t == "sliding_attention"),
            rope_kinds=("swa",),
            sliding_window=window,
            attn_gate=True,
            qk_norm=True,
            qk_norm_kind="head",
            norms="both",
            embed_scale=bool(cfg.get("mup_enabled", False)),
            dense_layers=dense,
            dense_intermediate_size=cfg["intermediate_size"] if dense else 0,
        )

    @staticmethod
    def _whisper_from_hf(cfg: dict, name: str = "") -> "ModelConfig":
        """WhisperForConditionalGeneration config.json → ModelConfig.

        Multilingual vocabularies only (51865 = v1/v2 with 99 language
        tokens, 51866 = large-v3 with 100): the English-only `.en`
        checkpoints lay their special tokens out differently and a
        multilingual model transcribes English anyway. Special-token
        ids are derived from the fixed vocab layout: text tokens, then
        <|endoftext|>, <|startoftranscript|>, the languages,
        <|translate|>, <|transcribe|>, <|startoflm|>, <|startofprev|>,
        <|nospeech|>, <|notimestamps|>, timestamps."""
        vocab = cfg["vocab_size"]
        if vocab < 51865:
            raise ValueError(
                f"unsupported Whisper vocabulary size {vocab}: only the "
                "multilingual checkpoints (51865/51866) are supported — "
                "use e.g. openai/whisper-small instead of whisper-small.en"
            )
        n_langs = vocab - 51766  # 51865 -> 99, 51866 -> 100
        eot = int(cfg.get("eos_token_id") or 50257)
        sot = int(cfg.get("decoder_start_token_id") or 50258)
        lang_base = sot + 1
        translate = lang_base + n_langs
        transcribe = translate + 1
        sot_prev = transcribe + 2  # <|startoflm|> sits between
        notimestamps = sot_prev + 2  # <|nospeech|> sits between
        heads = cfg["decoder_attention_heads"]
        hidden = cfg["d_model"]
        return ModelConfig(
            name=name or cfg.get("_name_or_path", "whisper"),
            architecture="whisper",
            vocab_size=vocab,
            hidden_size=hidden,
            intermediate_size=cfg.get("decoder_ffn_dim", hidden * 4),
            num_layers=cfg["decoder_layers"],
            encoder_layers=cfg["encoder_layers"],
            num_heads=heads,
            num_kv_heads=heads,
            head_dim=hidden // heads,
            max_model_len=cfg.get("max_target_positions", 448),
            n_audio_ctx=cfg.get("max_source_positions", 1500),
            num_mel_bins=cfg.get("num_mel_bins", 80),
            tie_word_embeddings=True,
            sot_id=sot, eot_id=eot, lang_base_id=lang_base,
            n_langs=n_langs, translate_id=translate,
            transcribe_id=transcribe, sot_prev_id=sot_prev,
            notimestamps_id=notimestamps,
        )

    @staticmethod
    def from_pretrained(path_or_preset: str, **overrides) -> "ModelConfig":
        """Resolve a preset name or a local HF model directory."""
        if path_or_preset in MODEL_PRESETS:
            base = MODEL_PRESETS[path_or_preset]
        else:
            cfg_path = os.path.join(path_or_preset, "config.json")
            with open(cfg_path) as f:
                base = ModelConfig.from_hf_config(json.load(f), name=path_or_preset)
            base = dataclasses.replace(
                base, weights_path=path_or_preset, tokenizer=path_or_preset
            )
        return dataclasses.replace(base, **overrides) if overrides else base


MODEL_PRESETS: dict[str, ModelConfig] = {
    # tiny configs for tests / CI (CPU-friendly)
    "tiny-llama": ModelConfig(
        name="tiny-llama", vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32, max_model_len=512,
        dtype="float32",
    ),
    "tiny-mixtral": ModelConfig(
        name="tiny-mixtral", architecture="mixtral", vocab_size=512, hidden_size=128,
        intermediate_size=256, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32,
        max_model_len=512, num_experts=4, num_experts_per_tok=2, dtype="float32",
    ),
    "tiny-olmoe": ModelConfig(
        # OLMoE's block at test size: MHA, QK-norm over the whole
        # projection, 8 experts of 64, top-2, weights not renormalised
        name="tiny-olmoe", architecture="olmoe", vocab_size=512,
        hidden_size=128, intermediate_size=64, num_layers=2, num_heads=4,
        num_kv_heads=4, head_dim=32, max_model_len=512, num_experts=8,
        num_experts_per_tok=2, norm_topk_prob=False, qk_norm=True,
        qk_norm_kind="full", dtype="float32",
    ),
    # real shapes (weights random-initialised unless weights_path given)
    "llama-3-8b": ModelConfig(
        name="llama-3-8b", vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=500000.0, max_model_len=8192,
    ),
    "llama-3b-class": ModelConfig(
        # Llama-3.2-3B geometry: the largest bf16 Llama that fits a single
        # v5e chip (16 GiB HBM) with a useful KV pool (chip_smoke.py's
        # model).
        name="llama-3b-class", vocab_size=128256, hidden_size=3072,
        intermediate_size=8192, num_layers=28, num_heads=24, num_kv_heads=8,
        head_dim=128, rope_theta=500000.0, max_model_len=8192,
    ),
    "llama-3-70b": ModelConfig(
        name="llama-3-70b", vocab_size=128256, hidden_size=8192, intermediate_size=28672,
        num_layers=80, num_heads=64, num_kv_heads=8, head_dim=128,
        rope_theta=500000.0, max_model_len=8192,
    ),
    "qwen2-7b-class": ModelConfig(
        # Qwen2-7B geometry: Llama stack + QKV biases + large rope theta
        name="qwen2-7b-class", vocab_size=152064, hidden_size=3584,
        intermediate_size=18944, num_layers=28, num_heads=28, num_kv_heads=4,
        head_dim=128, rope_theta=1000000.0, max_model_len=32768,
        qkv_bias=True, tie_word_embeddings=False,
    ),
    "tiny-qwen2": ModelConfig(
        name="tiny-qwen2", vocab_size=512, hidden_size=128,
        intermediate_size=256, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=32, max_model_len=512, qkv_bias=True, dtype="float32",
    ),
    "tiny-gemma": ModelConfig(
        name="tiny-gemma", architecture="gemma", vocab_size=512,
        hidden_size=128, intermediate_size=256, num_layers=2, num_heads=4,
        num_kv_heads=1, head_dim=48, max_model_len=512, dtype="float32",
        tie_word_embeddings=True, act="gelu_tanh", norm_offset=1.0,
        embed_scale=True,
    ),
    "tiny-gemma2": ModelConfig(
        name="tiny-gemma2", architecture="gemma2", vocab_size=512,
        hidden_size=128, intermediate_size=256, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=32, max_model_len=512, dtype="float32",
        tie_word_embeddings=True, act="gelu_tanh", norm_offset=1.0,
        embed_scale=True, norms="both", attn_logit_softcap=50.0,
        final_logit_softcap=30.0, query_scale=64.0 ** -0.5,
        sliding_window=512,  # query_pre_attn_scalar 64 ≠ head_dim 32
    ),
    "gemma-7b-class": ModelConfig(
        # Gemma-7B geometry: GeGLU, (1+w) RMSNorm, sqrt(E)-scaled embeds,
        # tied head, head_dim 256 ≠ E/H
        name="gemma-7b-class", architecture="gemma", vocab_size=256000,
        hidden_size=3072, intermediate_size=24576, num_layers=28,
        num_heads=16, num_kv_heads=16, head_dim=256, max_model_len=8192,
        tie_word_embeddings=True, act="gelu_tanh", norm_offset=1.0,
        embed_scale=True, rms_norm_eps=1e-6,
    ),
    "gemma2-9b-class": ModelConfig(
        # Gemma-2-9B geometry; served within the 4096 local-attention
        # window where local/global layers coincide (exactness gate)
        name="gemma2-9b-class", architecture="gemma2", vocab_size=256000,
        hidden_size=3584, intermediate_size=14336, num_layers=42,
        num_heads=16, num_kv_heads=8, head_dim=256, max_model_len=4096,
        tie_word_embeddings=True, act="gelu_tanh", norm_offset=1.0,
        embed_scale=True, norms="both", attn_logit_softcap=50.0,
        final_logit_softcap=30.0, query_scale=256.0 ** -0.5,
        sliding_window=4096, rms_norm_eps=1e-6,
    ),
    "tiny-mistral": ModelConfig(
        name="tiny-mistral", vocab_size=512, hidden_size=128,
        intermediate_size=256, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=32, max_model_len=512, sliding_window=512, dtype="float32",
    ),
    "mistral-7b-class": ModelConfig(
        # Mistral-7B geometry; every layer windows at 4096, so the
        # exactness gate serves max_model_len <= window
        name="mistral-7b-class", vocab_size=32000, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        head_dim=128, rope_theta=10000.0, max_model_len=4096,
        sliding_window=4096,
    ),
    "tiny-phi3": ModelConfig(
        name="tiny-phi3", architecture="phi3", vocab_size=512,
        hidden_size=128, intermediate_size=256, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=32, max_model_len=512, dtype="float32",
    ),
    "phi3-mini-class": ModelConfig(
        # Phi-3-mini-4k geometry (fused HF qkv/gate_up layout, plain rope);
        # every layer windows at 2047, so the exactness gate serves
        # max_model_len <= window
        name="phi3-mini-class", architecture="phi3", vocab_size=32064,
        hidden_size=3072, intermediate_size=8192, num_layers=32,
        num_heads=32, num_kv_heads=32, head_dim=96, max_model_len=2047,
        sliding_window=2047,
    ),
    "tiny-qwen3": ModelConfig(
        name="tiny-qwen3", vocab_size=512, hidden_size=128,
        intermediate_size=256, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=32, max_model_len=512, qk_norm=True,
        tie_word_embeddings=True, dtype="float32",
    ),
    "qwen3-8b-class": ModelConfig(
        # Qwen3-8B geometry: QK-norm, no biases, head_dim 128 ≠ E/H
        name="qwen3-8b-class", vocab_size=151936, hidden_size=4096,
        intermediate_size=12288, num_layers=36, num_heads=32, num_kv_heads=8,
        head_dim=128, rope_theta=1000000.0, max_model_len=32768,
        qk_norm=True, rms_norm_eps=1e-6,
    ),
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b", architecture="mixtral", vocab_size=32000, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        head_dim=128, rope_theta=1000000.0, max_model_len=32768, num_experts=8,
        num_experts_per_tok=2,
    ),
    "olmoe-1b-7b": ModelConfig(
        # allenai/OLMoE-1B-7B-0125-Instruct geometry: 64 experts of 1024,
        # 8 per token, MHA, QK-norm over the whole projection
        name="olmoe-1b-7b", architecture="olmoe", vocab_size=50304,
        hidden_size=2048, intermediate_size=1024, num_layers=16,
        num_heads=16, num_kv_heads=16, head_dim=128, rope_theta=10000.0,
        max_model_len=4096, num_experts=64, num_experts_per_tok=8,
        norm_topk_prob=False, qk_norm=True, qk_norm_kind="full",
    ),
    "tiny-ouro": ModelConfig(
        # Ouro's block at test size: MHA, norms before and after each
        # sublayer, 3 weight-tied layers run 4 times (12 cache layers)
        name="tiny-ouro", architecture="ouro", vocab_size=512,
        hidden_size=128, intermediate_size=256, num_layers=3, num_heads=4,
        num_kv_heads=4, head_dim=32, rope_theta=1000000.0,
        rms_norm_eps=1e-6, max_model_len=512, norms="both",
        loop_passes=4, residual_f32=True, dtype="float32",
    ),
    "ouro-2.6b": ModelConfig(
        # ByteDance/Ouro-2.6B geometry: 48 weight-tied layers run 4 times
        # (192 cache layers, 1.57 MB of KV a token), MHA, untied head
        name="ouro-2.6b", architecture="ouro", vocab_size=49152,
        hidden_size=2048, intermediate_size=5632, num_layers=48,
        num_heads=16, num_kv_heads=16, head_dim=128, rope_theta=1000000.0,
        rms_norm_eps=1e-6, max_model_len=65536, norms="both",
        loop_passes=4, residual_f32=True,
    ),
    "tiny-pangu": ModelConfig(
        # openPangu-Ultra-MoE's block at test size: latent attention (a
        # 32 + 16 = 48-value cache row, 128 lanes in the pool), norms before
        # and after each sublayer, one leading dense layer, then sigmoid
        # routing over 8 experts (2 a token) beside a shared one
        name="tiny-pangu", architecture="pangu_ultra_moe", vocab_size=512,
        hidden_size=128, intermediate_size=64, num_layers=3, num_heads=4,
        num_kv_heads=4, head_dim=48, rope_theta=25600000.0,
        max_model_len=512, num_experts=8, num_experts_per_tok=2,
        moe_scoring="sigmoid", routed_scaling=2.5, shared_expert_size=64,
        norms="both", kv_lora_rank=32, q_lora_rank=48,
        qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
        dense_layers=1, dense_intermediate_size=256, dtype="float32",
    ),
    "tiny-phi4flash": ModelConfig(
        # Phi-4-mini-flash's stack at test size under the published rule
        # (cross-decoder from block 12 // 2 + 2 = 8): 3 x (Mamba, window) +
        # (Mamba, full) + 2 x (GMU, cross), window 8 in blocks of 4,
        # differential attention over 4 query / 2 key-value heads of 16
        # (one cache head of 32), LayerNorm, a tied head
        name="tiny-phi4flash", architecture="phi4flash", vocab_size=512,
        hidden_size=128, intermediate_size=256, num_layers=12, num_heads=4,
        num_kv_heads=2, head_dim=16, max_model_len=512,
        tie_word_embeddings=True, qkv_bias=True, sliding_window=8,
        mamba_period=2, mamba_dt_rank=8, diff_attn=True, layer_norm=True,
        dtype="float32",
    ),
    "tiny-kimi-linear": ModelConfig(
        # Kimi-Linear's stack at test size: (dense kda, kda, kda, mla) +
        # (kda, kda, mla), the short last period; 4 KDA heads of 16, MLA
        # over a 32 + 16 = 48-value cache row that nothing rotates, a
        # direct query projection; one leading dense layer, then sigmoid
        # routing over 8 experts (2 a token) beside a shared one
        name="tiny-kimi-linear", architecture="kimi_linear", vocab_size=512,
        hidden_size=128, intermediate_size=64, num_layers=7, num_heads=4,
        num_kv_heads=4, head_dim=48, max_model_len=512, num_experts=8,
        num_experts_per_tok=2, moe_scoring="sigmoid", routed_scaling=2.446,
        shared_expert_size=64, attn_period=4, mla_layers=(3, 6),
        kda_heads=4, kda_head_dim=16, kda_rank=16, kv_lora_rank=32,
        qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
        mla_rope=False, dense_layers=1, dense_intermediate_size=256,
        dtype="float32",
    ),
    "tiny-whisper": ModelConfig(
        # CPU-testable Whisper: 1 s audio window (n_audio_ctx 50 -> 100
        # input frames), byte-ish vocab with the multilingual special-
        # token ORDER preserved above eot (the suppression rule "mask
        # ids > eot except eot" must hold exactly as in the real vocab)
        name="tiny-whisper", architecture="whisper", vocab_size=416,
        hidden_size=64, intermediate_size=128, num_layers=2,
        encoder_layers=2, num_heads=4, num_kv_heads=4, head_dim=16,
        num_mel_bins=20, n_audio_ctx=50, max_model_len=32,
        dtype="float32", tie_word_embeddings=True,
        eot_id=400, sot_id=401, lang_base_id=402, n_langs=4,
        translate_id=406, transcribe_id=407, sot_prev_id=409,
        notimestamps_id=411,
    ),
    "whisper-small-class": ModelConfig(
        # openai/whisper-small geometry (multilingual v2 vocab)
        name="whisper-small-class", architecture="whisper",
        vocab_size=51865, hidden_size=768, intermediate_size=3072,
        num_layers=12, encoder_layers=12, num_heads=12, num_kv_heads=12,
        head_dim=64, num_mel_bins=80, n_audio_ctx=1500, max_model_len=448,
        tie_word_embeddings=True,
        eot_id=50257, sot_id=50258, lang_base_id=50259, n_langs=99,
        translate_id=50358, transcribe_id=50359, sot_prev_id=50361,
        notimestamps_id=50363,
    ),
    "whisper-large-v3-class": ModelConfig(
        # openai/whisper-large-v3 geometry (128 mels, 100 languages)
        name="whisper-large-v3-class", architecture="whisper",
        vocab_size=51866, hidden_size=1280, intermediate_size=5120,
        num_layers=32, encoder_layers=32, num_heads=20, num_kv_heads=20,
        head_dim=64, num_mel_bins=128, n_audio_ctx=1500, max_model_len=448,
        tie_word_embeddings=True,
        eot_id=50257, sot_id=50258, lang_base_id=50259, n_langs=100,
        translate_id=50359, transcribe_id=50360, sot_prev_id=50362,
        notimestamps_id=50364,
    ),
    "opt-125m-class": ModelConfig(
        # The reference's minimal example serves facebook/opt-125m
        # (BASELINE.json configs[0]); we use an equivalent-scale llama-arch
        # model as the minimal-footprint config.
        name="opt-125m-class", vocab_size=50272, hidden_size=768, intermediate_size=3072,
        num_layers=12, num_heads=12, num_kv_heads=12, head_dim=64, max_model_len=2048,
    ),
}


@dataclasses.dataclass
class CacheConfig:
    """Paged KV cache geometry (HBM tier; host/remote tiers in kv_offload)."""

    block_size: int = 16  # tokens per block
    num_blocks: int = -1  # -1 → size from hbm_utilization
    hbm_utilization: float = 0.9
    enable_prefix_caching: bool = True
    # host-DRAM offload tier (LMCache CPU-offload equivalent)
    host_offload_blocks: int = 0
    # host tier capacity in BYTES — the authoritative knob
    # (--kv-host-cache-bytes); when set it overrides host_offload_blocks,
    # which remains as a block-count convenience converted via
    # kv_cache_bytes_per_block at engine init
    kv_host_cache_bytes: int = 0
    # shared remote tier (production_stack_tpu/kv_server URL; LMCache remote
    # cache-server equivalent)
    remote_kv_url: Optional[str] = None
    # background threads for the async tier-prefetch pipeline (host/remote
    # lookups + fetches run here; the serving thread only commits results)
    kv_prefetch_workers: int = 2


# a narrow ragged stream is a whole number of the ragged attention kernel's
# largest tile (ops/ragged_paged_attention_pallas.py Q_TILE; q_tile_for
# gives divisors of it)
STREAM_WIDTH_ALIGN = 128


@dataclasses.dataclass
class SchedulerConfig:
    max_num_seqs: int = 64  # decode slots
    max_num_batched_tokens: int = 2048  # prefill chunk budget per step
    max_queue_len: int = 4096
    # decode iterations fused into one device dispatch (vLLM's
    # num-scheduler-steps): amortises host→device dispatch latency; stop
    # conditions are checked every multi_step tokens, surplus is discarded
    multi_step: int = 1
    # n-gram (prompt-lookup) speculative decoding: propose up to this many
    # draft tokens per step from the sequence's own token history and
    # verify them inside the ragged unified dispatch (vLLM's ngram
    # --speculative-config equivalent). 0 = off. Eligibility is per
    # sequence — greedy rows speculate while sampled/penalised/controlled
    # rows in the SAME batch decode normally — and a per-sequence
    # acceptance EWMA adapts the width downward on cold sequences
    # (spec.SpecController). Decode is
    # weight-bandwidth bound at moderate batch, so accepting n drafts
    # multiplies tokens per weight read by (n+1); the verify span's extra
    # FLOPs ride the MXU headroom (docs/roofline.md).
    spec_ngram_k: int = 0
    # longest/shortest n-gram to match against the history (longest first)
    spec_ngram_max: int = 3
    spec_ngram_min: int = 1
    # how many trailing history tokens the proposer searches
    spec_window: int = 4096
    # per-tenant fair share (ROADMAP item 3: "a noisy tenant must not
    # starve others' ITL"). When on AND >=2 tenants are present, the
    # unified prefill budget is split deficit-round-robin by tenant
    # weight and the waiting queue dequeues weighted-fair instead of
    # FIFO. Default OFF, and with a single tenant both paths reduce to
    # the exact FCFS schedule (bit-identity pinned in
    # tests/test_fair_share.py) — fairness is pure host-side ordering,
    # never a new dispatch signature.
    fair_share: bool = False
    # tenant -> relative weight (default 1.0 per tenant). Unknown
    # tenants weigh 1.0; weights only matter relative to each other.
    # Shared with the stage-3 brownout over-weight shed set.
    tenant_weights: dict = dataclasses.field(default_factory=dict)

    def tenant_weight(self, tenant: str) -> float:
        try:
            w = float(self.tenant_weights.get(tenant, 1.0))
        except (TypeError, ValueError):
            return 1.0
        return w if w > 0 else 1.0

    @property
    def ragged_stream_widths(self) -> tuple[int, ...]:
        """The widths the ragged program exists at, ascending, the token
        budget last: a ragged step runs at the first that holds the tokens
        it carries (``LLMEngine._run_ragged``), one compile signature a
        width. One narrow width, a quarter of the budget cut to a multiple
        of the ragged kernel's tile: 512 at 2048 / 64 holds 63 decode rows
        and two average short prompts, and the layer matmuls of a stream
        that carries them cost a quarter of the budget-wide ones (PERF.md
        section 6, PR 42). A function of the two numbers beside it and
        nothing else. The budget alone where the narrow width would be
        under two rows a slot (a step of decode rows and one short prompt
        would not fit; and ``max_num_seqs`` itself is never a width: a
        stream that wide has the decode program's row count) or under one
        tile: every tiny configuration."""
        budget = self.max_num_batched_tokens
        narrow = budget // 4 // STREAM_WIDTH_ALIGN * STREAM_WIDTH_ALIGN
        if narrow < max(2 * self.max_num_seqs, 1):
            return (budget,)
        return (narrow, budget)

    def stream_width_for(self, tokens: int) -> int:
        """The narrowest of ``ragged_stream_widths`` that holds ``tokens``
        (the scheduler never packs more than the budget, the last)."""
        return next(w for w in self.ragged_stream_widths if w >= tokens)

    @property
    def decode_horizon(self) -> int:
        """Tokens of block capacity a decode dispatch may consume past
        ``num_computed_tokens`` (multi-step iterations). Speculative
        spans reserve their own capacity per granted draft width in
        ``Scheduler._grant_spec_drafts`` — they are NOT part of this
        blanket horizon."""
        return max(self.multi_step, 1)


@dataclasses.dataclass
class PerfConfig:
    """Goodput accounting (engine/perf_accounting.py): live MFU / HBM
    bandwidth estimates plus jit compile-event tracking."""
    enabled: bool = True
    # sliding window the utilization gauges are computed over, seconds
    window: float = 60.0
    # 0 = look the peak up by device_kind (perf_accounting.DEVICE_PEAKS);
    # a device with no entry then reports no utilization. The FLOP/HBM
    # peaks are per chip — the accountant scales them by the mesh size;
    # the ICI peak stays per chip (the collective cost model counts
    # per-chip wire bytes).
    peak_tflops: float = 0.0
    peak_hbm_gbps: float = 0.0
    peak_ici_gbps: float = 0.0
    # how often device.memory_stats() is sampled for the HBM gauges
    hbm_poll_interval: float = 5.0


@dataclasses.dataclass
class EngineConfig:
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    cache: CacheConfig = dataclasses.field(default_factory=CacheConfig)
    scheduler: SchedulerConfig = dataclasses.field(default_factory=SchedulerConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    perf: PerfConfig = dataclasses.field(default_factory=PerfConfig)
    # disaggregated serving role (docs/architecture.md "Disaggregated
    # prefill/decode"): "prefill" engines run requests to first token and
    # push the paged KV to the chosen decode engine; "decode" engines
    # accept POST /kv/recv transfers and splice the sequence in
    # decode-ready; "unified" does both phases locally (the default)
    role: str = "unified"  # "unified" | "prefill" | "decode"
    # P→D transfer tuning (engine/kv_transfer.py): layer-group size
    # (0 = half the stack), producer-side in-flight gather window, and
    # digest-mismatch/connection retries per push
    kv_transfer_group_layers: int = 0
    kv_transfer_window: int = 2
    kv_transfer_retries: int = 3
    # seconds an un-attached /kv/recv transfer may hold pool blocks
    # before the sweep reclaims them (leaked-transfer backstop)
    kv_transfer_ttl: float = 120.0
    seed: int = 0
    # multi-LoRA bank: slot 0 is the base model, adapters occupy 1..max-1
    max_loras: int = 4
    max_lora_rank: int = 16
    # constrained-decoding grammar bank (engine/grammar.py): distinct
    # concurrent grammars and the per-grammar DFA state budget. HBM cost
    # when first used: max_grammars x max_grammar_states x vocab x 2 B
    # (int16 transition tables; 8 x 128 x 128k = 256 MB)
    max_grammars: int = 8
    max_grammar_states: int = 128
    # tenant attribution plane (production_stack_tpu/tenancy.py):
    # per-tenant token/chip-second metering in the perf accountant plus
    # the per-request usage ledger. Observe-only — disabling it changes
    # no scheduling decision and no fleet-total metric value.
    tenant_metering: bool = True
    # top-K label bound for every per-tenant export (remainder folds
    # into tenant="other" — the cardinality policy)
    tenant_top_k: int = 8
    # durable usage ledger: rotating JSONL of per-request usage records;
    # empty path = ledger off (metering gauges still work)
    tenant_ledger_path: str = ""
    tenant_ledger_max_bytes: int = 16 << 20

    @staticmethod
    def for_model(name: str, **kw) -> "EngineConfig":
        model_kw = {k: v for k, v in kw.items() if hasattr(ModelConfig, k) and k != "mesh"}
        cfg = EngineConfig(model=ModelConfig.from_pretrained(name, **model_kw))
        for field in ("cache", "scheduler", "mesh", "seed"):
            if field in kw:
                setattr(cfg, field, kw[field])
        return cfg
