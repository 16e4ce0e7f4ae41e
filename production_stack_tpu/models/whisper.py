"""Whisper-family encoder-decoder as functional JAX (audio transcription).

The reference serves ``/v1/audio/transcriptions`` by deploying vLLM
Whisper pods behind the router (reference:
tutorials/23-whisper-api-transcription.md, src/vllm_router — the router
only proxies). This stack serves the modality natively: this module is
the model, ``engine/whisper_runner.py`` drives it, and the engine
server exposes the endpoint.

TPU-first design, same idioms as models/llama.py:

- Whisper's fixed 30 s window is a gift to XLA: every clip becomes
  (n_mels, 3000) → encoder (B, 1500, E) — ONE static shape, one
  compile, MXU-sized matmuls throughout.
- Encoder and decoder layer stacks are scanned (``lax.scan`` over a
  leading L axis): whisper-large's 32 layers trace as fast as a
  2-layer test model.
- Decoding runs as a ``lax.while_loop`` over single-token steps inside
  one jit — no per-token host round-trips. The runner calls it in
  bounded chunks so streaming responses get real incremental text.
- Cross-attention K/V are computed once per request from the encoder
  output and reused every decode step; self-attention K/V live in a
  dense (L, 2, B, T_max, H, D) cache updated with
  ``lax.dynamic_update_slice`` — T_max is 448, so paging buys nothing.
- Parameters carry the same logical-axes annotations as the Llama
  stack; pjit/GSPMD shard heads/MLP over the tensor axis for free.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.ops.norms import layer_norm
from production_stack_tpu.parallel import shardings as L

# Whisper's ordered language list (position defines the language token:
# id = lang_base_id + index). First 99 are the multilingual v1/v2 set;
# "yue" is appended in large-v3 vocabularies (n_langs == 100).
LANGUAGES = (
    "en", "zh", "de", "es", "ru", "ko", "fr", "ja", "pt", "tr", "pl",
    "ca", "nl", "ar", "sv", "it", "id", "hi", "fi", "vi", "he", "uk",
    "el", "ms", "cs", "ro", "da", "hu", "ta", "no", "th", "ur", "hr",
    "bg", "lt", "la", "mi", "ml", "cy", "sk", "te", "fa", "lv", "bn",
    "sr", "az", "sl", "kn", "et", "mk", "br", "eu", "is", "hy", "ne",
    "mn", "bs", "kk", "sq", "sw", "gl", "mr", "pa", "si", "km", "sn",
    "yo", "so", "af", "oc", "ka", "be", "tg", "sd", "gu", "am", "yi",
    "lo", "uz", "fo", "ht", "ps", "tk", "nn", "mt", "sa", "lb", "my",
    "bo", "tl", "mg", "as", "tt", "haw", "ln", "ha", "ba", "jw", "su",
    "yue",
)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def _block_specs() -> dict:
    """Logical axes for one attention + MLP block (stacked on LAYERS)."""
    return {
        "attn_norm_w": (L.LAYERS, L.EMBED),
        "attn_norm_b": (L.LAYERS, L.EMBED),
        "wq": (L.LAYERS, L.EMBED, L.HEADS, L.HEAD_DIM),
        "bq": (L.LAYERS, L.HEADS, L.HEAD_DIM),
        "wk": (L.LAYERS, L.EMBED, L.HEADS, L.HEAD_DIM),  # no k bias
        "wv": (L.LAYERS, L.EMBED, L.HEADS, L.HEAD_DIM),
        "bv": (L.LAYERS, L.HEADS, L.HEAD_DIM),
        "wo": (L.LAYERS, L.HEADS, L.HEAD_DIM, L.EMBED),
        "bo": (L.LAYERS, L.EMBED),
        "mlp_norm_w": (L.LAYERS, L.EMBED),
        "mlp_norm_b": (L.LAYERS, L.EMBED),
        "fc1": (L.LAYERS, L.EMBED, L.MLP),
        "fc1_b": (L.LAYERS, L.MLP),
        "fc2": (L.LAYERS, L.MLP, L.EMBED),
        "fc2_b": (L.LAYERS, L.EMBED),
    }


def param_specs(cfg: ModelConfig) -> dict:
    enc_layer = _block_specs()
    dec_layer = _block_specs()
    # cross-attention block (decoder only): same shapes, "c" prefix
    dec_layer.update({
        "cross_norm_w": (L.LAYERS, L.EMBED),
        "cross_norm_b": (L.LAYERS, L.EMBED),
        "cwq": (L.LAYERS, L.EMBED, L.HEADS, L.HEAD_DIM),
        "cbq": (L.LAYERS, L.HEADS, L.HEAD_DIM),
        "cwk": (L.LAYERS, L.EMBED, L.HEADS, L.HEAD_DIM),
        "cwv": (L.LAYERS, L.EMBED, L.HEADS, L.HEAD_DIM),
        "cbv": (L.LAYERS, L.HEADS, L.HEAD_DIM),
        "cwo": (L.LAYERS, L.HEADS, L.HEAD_DIM, L.EMBED),
        "cbo": (L.LAYERS, L.EMBED),
    })
    return {
        "enc": {
            "conv1_w": (None, None, L.EMBED),  # (k, n_mels, E)
            "conv1_b": (L.EMBED,),
            "conv2_w": (None, L.EMBED, L.EMBED),  # (k, E, E) stride 2
            "conv2_b": (L.EMBED,),
            "layers": enc_layer,
            "final_norm_w": (L.EMBED,),
            "final_norm_b": (L.EMBED,),
        },
        "dec": {
            "embed": (L.VOCAB, L.EMBED),  # lm_head is tied to this
            "pos": (None, L.EMBED),  # (max_target_positions, E) learned
            "layers": dec_layer,
            "final_norm_w": (L.EMBED,),
            "final_norm_b": (L.EMBED,),
        },
    }


def _init_block(cfg: ModelConfig, n_layers: int, key, cross: bool) -> dict:
    E, H, D, F = (cfg.hidden_size, cfg.num_heads, cfg.head_dim,
                  cfg.intermediate_size)
    dt = cfg.jax_dtype
    ks = jax.random.split(key, 12)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    Ln = n_layers
    block = {
        "attn_norm_w": jnp.ones((Ln, E), dt),
        "attn_norm_b": jnp.zeros((Ln, E), dt),
        "wq": normal(ks[0], (Ln, E, H, D), E),
        "bq": jnp.zeros((Ln, H, D), dt),
        "wk": normal(ks[1], (Ln, E, H, D), E),
        "wv": normal(ks[2], (Ln, E, H, D), E),
        "bv": jnp.zeros((Ln, H, D), dt),
        "wo": normal(ks[3], (Ln, H, D, E), H * D),
        "bo": jnp.zeros((Ln, E), dt),
        "mlp_norm_w": jnp.ones((Ln, E), dt),
        "mlp_norm_b": jnp.zeros((Ln, E), dt),
        "fc1": normal(ks[4], (Ln, E, F), E),
        "fc1_b": jnp.zeros((Ln, F), dt),
        "fc2": normal(ks[5], (Ln, F, E), F),
        "fc2_b": jnp.zeros((Ln, E), dt),
    }
    if cross:
        block.update({
            "cross_norm_w": jnp.ones((Ln, E), dt),
            "cross_norm_b": jnp.zeros((Ln, E), dt),
            "cwq": normal(ks[6], (Ln, E, H, D), E),
            "cbq": jnp.zeros((Ln, H, D), dt),
            "cwk": normal(ks[7], (Ln, E, H, D), E),
            "cwv": normal(ks[8], (Ln, E, H, D), E),
            "cbv": jnp.zeros((Ln, H, D), dt),
            "cwo": normal(ks[9], (Ln, H, D, E), H * D),
            "cbo": jnp.zeros((Ln, E), dt),
        })
    return block


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    E, V = cfg.hidden_size, cfg.vocab_size
    dt = cfg.jax_dtype
    k = jax.random.split(key, 8)

    def normal(kk, shape, fan_in):
        return (jax.random.normal(kk, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    return {
        "enc": {
            "conv1_w": normal(k[0], (3, cfg.num_mel_bins, E),
                              3 * cfg.num_mel_bins),
            "conv1_b": jnp.zeros((E,), dt),
            "conv2_w": normal(k[1], (3, E, E), 3 * E),
            "conv2_b": jnp.zeros((E,), dt),
            "layers": _init_block(cfg, cfg.encoder_layers, k[2], cross=False),
            "final_norm_w": jnp.ones((E,), dt),
            "final_norm_b": jnp.zeros((E,), dt),
        },
        "dec": {
            "embed": normal(k[3], (V, E), E),
            "pos": normal(k[4], (cfg.max_model_len, E), E),
            "layers": _init_block(cfg, cfg.num_layers, k[5], cross=True),
            "final_norm_w": jnp.ones((E,), dt),
            "final_norm_b": jnp.zeros((E,), dt),
        },
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _attention(q, k, v, mask=None) -> jnp.ndarray:
    """(B, Tq, H, D) x (B, Tk, H, D) → (B, Tq, H, D); scores in f32."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _sinusoid_pos(length: int, channels: int) -> np.ndarray:
    """Whisper's fixed encoder position embedding (log-spaced sinusoids)."""
    log_timescale = np.log(10_000.0) / (channels // 2 - 1)
    inv = np.exp(-log_timescale * np.arange(channels // 2))
    scaled = np.arange(length)[:, None] * inv[None, :]
    return np.concatenate([np.sin(scaled), np.cos(scaled)], axis=1)


def encode(cfg: ModelConfig, params: dict, mel: jnp.ndarray) -> jnp.ndarray:
    """(B, n_mels, 2 * n_audio_ctx frames) → (B, n_audio_ctx, E)."""
    p = params["enc"]
    x = mel.astype(cfg.jax_dtype).transpose(0, 2, 1)  # (B, T, n_mels)
    dn = ("NWC", "WIO", "NWC")  # feature-last: TPU-native conv layout
    # exact (erf) GELU throughout: Whisper was trained with nn.GELU, and
    # the tanh approximation drifts logits enough to flip borderline
    # tokens in quiet segments
    x = jax.nn.gelu(lax.conv_general_dilated(
        x, p["conv1_w"].astype(cfg.jax_dtype), window_strides=(1,),
        padding=((1, 1),), dimension_numbers=dn) + p["conv1_b"],
        approximate=False)
    x = jax.nn.gelu(lax.conv_general_dilated(
        x, p["conv2_w"].astype(cfg.jax_dtype), window_strides=(2,),
        padding=((1, 1),), dimension_numbers=dn) + p["conv2_b"],
        approximate=False)
    pos = jnp.asarray(_sinusoid_pos(cfg.n_audio_ctx, cfg.hidden_size),
                      cfg.jax_dtype)
    x = x + pos[None]

    B, H, D = x.shape[0], cfg.num_heads, cfg.head_dim

    def layer_fn(h, lp):
        n = layer_norm(h, lp["attn_norm_w"], lp["attn_norm_b"])
        q = jnp.einsum("bte,ehd->bthd", n, lp["wq"]) + lp["bq"]
        k = jnp.einsum("bte,ehd->bthd", n, lp["wk"])
        v = jnp.einsum("bte,ehd->bthd", n, lp["wv"]) + lp["bv"]
        a = _attention(q, k, v)
        h = h + jnp.einsum("bthd,hde->bte", a, lp["wo"]) + lp["bo"]
        n2 = layer_norm(h, lp["mlp_norm_w"], lp["mlp_norm_b"])
        m = jax.nn.gelu(jnp.einsum("bte,ef->btf", n2, lp["fc1"])
                        + lp["fc1_b"], approximate=False)
        h = h + jnp.einsum("btf,fe->bte", m, lp["fc2"]) + lp["fc2_b"]
        return h, None

    x, _ = lax.scan(layer_fn, x, p["layers"])
    return layer_norm(x, p["final_norm_w"], p["final_norm_b"])


def cross_kv(cfg: ModelConfig, params: dict,
             enc_out: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Precompute per-layer cross-attention K/V from the encoder output:
    (Ld, B, S_enc, H, D) each — computed once per request, read every
    decode step."""
    lp = params["dec"]["layers"]
    ck = jnp.einsum("bse,lehd->lbshd", enc_out, lp["cwk"])
    cv = jnp.einsum("bse,lehd->lbshd", enc_out, lp["cwv"]) + \
        lp["cbv"][:, None, None]
    return ck, cv


def init_self_kv(cfg: ModelConfig, batch: int, max_len: int) -> jnp.ndarray:
    """(Ld, 2, B, T_max, H, D) dense decoder self-attention cache."""
    return jnp.zeros(
        (cfg.num_layers, 2, batch, max_len, cfg.num_heads, cfg.head_dim),
        cfg.jax_dtype,
    )


def decode_tokens(
    cfg: ModelConfig,
    params: dict,
    tokens: jnp.ndarray,       # (B, T) int32 — new tokens this call
    offset: jnp.ndarray,       # (B,) int32 — tokens already in the cache
    self_kv: jnp.ndarray,      # (Ld, 2, B, T_max, H, D)
    ck: jnp.ndarray,
    cv: jnp.ndarray,
    valid_len: jnp.ndarray,    # (B,) int32 — valid prefix of `tokens`
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Run the decoder over T new tokens, appending to the cache.

    Right-padded prompts are handled by ``valid_len``: the key mask
    bounds every query's reachable keys at ``offset + valid_len``, so
    padding K/V — though written to cache slots — are never attended
    to, and later calls overwrite those slots (the next call's
    ``offset`` is ``offset + valid_len``). Returns
    (logits (B, T, V), updated self_kv).
    """
    p = params["dec"]
    B, T = tokens.shape
    T_max = self_kv.shape[3]
    H, D = cfg.num_heads, cfg.head_dim

    positions = offset[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    x = p["embed"][tokens].astype(cfg.jax_dtype)
    x = x + p["pos"][jnp.clip(positions, 0, cfg.max_model_len - 1)].astype(
        cfg.jax_dtype)

    # query i may attend keys at absolute positions <= offset + i, and
    # only keys that hold REAL tokens (key_pos < offset + valid_len)
    key_pos = jnp.arange(T_max, dtype=jnp.int32)[None, None]  # (1, 1, K)
    q_abs = positions[:, :, None]                             # (B, T, 1)
    limit = (offset + valid_len)[:, None, None]
    self_mask = ((key_pos <= q_abs) & (key_pos < limit))[:, None]  # (B,1,T,K)

    def layer_fn(carry, lp):
        h, li, kv = carry
        n = layer_norm(h, lp["attn_norm_w"], lp["attn_norm_b"])
        q = jnp.einsum("bte,ehd->bthd", n, lp["wq"]) + lp["bq"]
        k = jnp.einsum("bte,ehd->bthd", n, lp["wk"])
        v = jnp.einsum("bte,ehd->bthd", n, lp["wv"]) + lp["bv"]
        # append this call's K/V at [offset, offset+T) per batch row
        def upd(cache, new):  # cache (B, T_max, H, D), new (B, T, H, D)
            iota = jnp.arange(T_max, dtype=jnp.int32)[None, :, None, None]
            idx = iota - offset[:, None, None, None]  # slot -> new index
            inside = (idx >= 0) & (idx < T)
            gathered = jnp.take_along_axis(
                new, jnp.clip(idx, 0, T - 1), axis=1)
            return jnp.where(inside, gathered, cache)
        kc = upd(kv[li, 0], k)
        vc = upd(kv[li, 1], v)
        kv = kv.at[li, 0].set(kc).at[li, 1].set(vc)
        a = _attention(q, kc, vc, self_mask)
        h = h + jnp.einsum("bthd,hde->bte", a, lp["wo"]) + lp["bo"]
        # cross-attention over the (static) encoder sequence
        nc = layer_norm(h, lp["cross_norm_w"], lp["cross_norm_b"])
        cq = jnp.einsum("bte,ehd->bthd", nc, lp["cwq"]) + lp["cbq"]
        ca = _attention(cq, ck[li], cv[li])
        h = h + jnp.einsum("bthd,hde->bte", ca, lp["cwo"]) + lp["cbo"]
        n2 = layer_norm(h, lp["mlp_norm_w"], lp["mlp_norm_b"])
        m = jax.nn.gelu(jnp.einsum("bte,ef->btf", n2, lp["fc1"])
                        + lp["fc1_b"], approximate=False)
        h = h + jnp.einsum("btf,fe->bte", m, lp["fc2"]) + lp["fc2_b"]
        return (h, li + 1, kv), None

    (x, _, self_kv), _ = lax.scan(
        layer_fn, (x, jnp.int32(0), self_kv), p["layers"])
    x = layer_norm(x, p["final_norm_w"], p["final_norm_b"])
    logits = jnp.einsum("bte,ve->btv", x,
                        p["embed"].astype(cfg.jax_dtype)).astype(jnp.float32)
    return logits, self_kv
