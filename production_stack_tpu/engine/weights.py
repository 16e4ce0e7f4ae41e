"""Weight materialisation: random-init or HF safetensors → sharded pytree.

Model-weight delivery in the reference is PVC/NFS + an HF-downloader sidecar
(SURVEY.md §5.4; scripts/huggingface_downloader.py in the reference). Here the
engine loads safetensors straight from a local path (the chart mounts the same
PVC) and shards each tensor onto the mesh as it is loaded, so a 70B never
materialises unsharded on one host.
"""

from __future__ import annotations

import glob
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.engine.quant import is_quantized
from production_stack_tpu.models.registry import get_model
from production_stack_tpu.parallel.shardings import (
    ShardingRules,
    logical_to_sharding,
    rules_for_model,
)


def _is_orbax_path(path: str) -> bool:
    """gs:// URIs go straight to Orbax (tensorstore's gcs driver); local
    dirs are Orbax when they carry the checkpoint metadata marker."""
    if path.startswith("gs://"):
        return True
    return os.path.isfile(os.path.join(path, "_CHECKPOINT_METADATA"))


def init_or_load(
    cfg: ModelConfig,
    mesh: Mesh,
    rules: Optional[ShardingRules] = None,
    seed: int = 0,
) -> dict:
    rules = rules or rules_for_model(cfg, mesh)
    if cfg.weights_path:
        if _is_orbax_path(cfg.weights_path):
            return load_orbax(cfg, mesh, rules, cfg.weights_path)
        if glob.glob(os.path.join(cfg.weights_path, "*.safetensors")):
            return load_safetensors(cfg, mesh, rules)
    return init_random(cfg, mesh, rules, seed)


# --- Orbax checkpoints (the TPU-native weight tier: GCS or PVC) -------------
# Reference weight delivery is PVC/NFS + an HF downloader sidecar
# (scripts/huggingface_downloader.py:14-30 there); the TPU-native format is
# an Orbax checkpoint, loaded sharded (each host reads only its shards —
# tensorstore reads ranges, so a 70B from gs:// never materialises whole).

def save_orbax(params: dict, path: str) -> None:
    """Write a sharded Orbax checkpoint (serving-format export)."""
    import orbax.checkpoint as ocp

    with ocp.StandardCheckpointer() as ck:
        ck.save(path, params)
        ck.wait_until_finished()


def load_orbax(cfg: ModelConfig, mesh: Mesh, rules: ShardingRules,
               path: str) -> dict:
    """Restore directly into this mesh's shardings."""
    import orbax.checkpoint as ocp

    import functools

    model = get_model(cfg)
    specs = model.param_specs(cfg)
    shapes = jax.eval_shape(
        functools.partial(model.init_params, cfg), jax.random.PRNGKey(0)
    )
    abstract = jax.tree_util.tree_map(
        lambda axes, sds: jax.ShapeDtypeStruct(
            sds.shape, sds.dtype,
            sharding=logical_to_sharding(axes, mesh, rules),
        ),
        specs, shapes,
        is_leaf=lambda x: isinstance(x, tuple),
    )
    with ocp.StandardCheckpointer() as ck:
        return ck.restore(path, abstract)


def init_random(cfg: ModelConfig, mesh: Mesh, rules: ShardingRules, seed: int) -> dict:
    """Random weights made on the device, each leaf straight into its
    sharding, in the logical tree ``param_specs`` describes (the
    benchmark's references read it by shape). A runner keeps some leaves
    in another order of bytes: ``lay_out``."""
    model = get_model(cfg)
    specs = model.param_specs(cfg)
    out_shardings = jax.tree_util.tree_map(
        lambda axes: logical_to_sharding(axes, mesh, rules),
        specs,
        is_leaf=lambda x: isinstance(x, tuple),
    )
    # stackcheck: disable=jit-cache-hygiene — one-shot weight init at
    # model load: jit here exists to materialise params directly into
    # their shardings (no host round-trip), and runs once per process
    init_fn = jax.jit(model.init_params, static_argnums=0, out_shardings=out_shardings)
    return init_fn(cfg, jax.random.PRNGKey(seed))


def lay_out(cfg: ModelConfig, params: dict, mesh: Optional[Mesh] = None,
            rules: Optional[ShardingRules] = None) -> dict:
    """A loaded tree as a runner keeps it: each leaf the model names an
    order of axes for (``param_layouts``: the attention projections'
    stacks, the axis a step contracts last, so that no step program copies
    a stack whole or re-lays a layer's slice) becomes ``<name>_t``,
    transposed to that order with the axes between the stack's and the
    last as one. Every other leaf, and a quantized container (its einsum
    contracts the container's own axes), is the same object. With a mesh
    each leaf is transposed on the device by a program of its own, lands
    in the loaded leaf's sharding by its axes, and the loaded leaf is
    deleted (no second resident copy; the transient is one leaf): ``params``
    is spent. Without a mesh the transposes are plain operations (shapes
    by ``jax.eval_shape``)."""
    model = get_model(cfg)

    def kept(w, order, axes):
        def transpose(a):
            t = jnp.transpose(a, order)
            return t.reshape(t.shape[0], -1, t.shape[-1])

        if mesh is None:
            return transpose(w)
        axes = [axes[i] for i in order]
        # stackcheck: disable=jit-cache-hygiene — one-shot at model load
        # (and wake): one program a laid-out leaf, called once
        t = jax.jit(transpose, out_shardings=logical_to_sharding(
            (axes[0], axes[1], axes[-1]), mesh, rules))(w)
        # a transpose cannot write over what it reads, so donating would
        # free nothing; the loaded leaf goes as soon as the program has
        # read it, before the next leaf's runs
        w.delete()
        return t

    def walk(tree, orders, specs):
        out = {}
        for k, v in tree.items():
            if isinstance(v, dict) and not is_quantized(v):
                out[k] = walk(v, orders[k], specs[k])
            elif orders.get(k) is None or is_quantized(v):
                out[k] = v
            else:
                out[k + "_t"] = kept(v, orders[k], specs[k])
        return out

    return walk(params, model.param_layouts(cfg), model.param_specs(cfg))


# --- HF checkpoint mapping (Llama/Mixtral family) ---------------------------

def _hf_key_map(cfg: ModelConfig, i: int) -> dict:
    """HF tensor name → (our layer param name, reshape rule) for layer i.
    A value may also be a LIST of (name, rule) pairs when one HF tensor
    feeds several of our params (Phi-3's fused projections)."""
    m = {
        f"model.layers.{i}.input_layernorm.weight": ("attn_norm", "copy"),
        f"model.layers.{i}.self_attn.q_proj.weight": ("wq", "proj_q"),
        f"model.layers.{i}.self_attn.k_proj.weight": ("wk", "proj_kv"),
        f"model.layers.{i}.self_attn.v_proj.weight": ("wv", "proj_kv"),
        f"model.layers.{i}.self_attn.o_proj.weight": ("wo", "proj_o"),
        f"model.layers.{i}.post_attention_layernorm.weight": ("mlp_norm", "copy"),
    }
    if cfg.architecture == "phi3":
        # fused layouts: qkv_proj rows are [q | k | v], gate_up_proj rows
        # are [gate | up] (reference models: HF Phi3ForCausalLM)
        for key in (f"model.layers.{i}.self_attn.q_proj.weight",
                    f"model.layers.{i}.self_attn.k_proj.weight",
                    f"model.layers.{i}.self_attn.v_proj.weight"):
            del m[key]
        m[f"model.layers.{i}.self_attn.qkv_proj.weight"] = [
            ("wq", "fused_q"), ("wk", "fused_k"), ("wv", "fused_v"),
        ]
        m[f"model.layers.{i}.mlp.gate_up_proj.weight"] = [
            ("w_gate", "fused_gate"), ("w_up", "fused_up"),
        ]
        m[f"model.layers.{i}.mlp.down_proj.weight"] = ("w_down", "t")
    if cfg.qk_norm:  # Qwen3: (D,); OLMoE: (H*D,) and (KH*D,), kept by head
        full = cfg.qk_norm_kind == "full"
        m[f"model.layers.{i}.self_attn.q_norm.weight"] = (
            "q_norm", "bias_q" if full else "copy")
        m[f"model.layers.{i}.self_attn.k_norm.weight"] = (
            "k_norm", "bias_kv" if full else "copy")
    if cfg.architecture == "ouro":
        # the norms AFTER each sublayer carry a "_2" (names from the
        # model's published implementation, from memory); the norms before
        # them keep the Llama names set above
        m[f"model.layers.{i}.input_layernorm_2.weight"] = (
            "post_attn_norm", "copy")
        m[f"model.layers.{i}.post_attention_layernorm_2.weight"] = (
            "post_mlp_norm", "copy")
    elif cfg.norms == "both":
        # Gemma-2 block: HF "post_attention_layernorm" is the norm on the
        # ATTENTION OUTPUT (our post_attn_norm); the pre-MLP norm is
        # "pre_feedforward_layernorm" and the MLP output norm
        # "post_feedforward_layernorm"
        m[f"model.layers.{i}.post_attention_layernorm.weight"] = (
            "post_attn_norm", "copy")
        m[f"model.layers.{i}.pre_feedforward_layernorm.weight"] = (
            "mlp_norm", "copy")
        m[f"model.layers.{i}.post_feedforward_layernorm.weight"] = (
            "post_mlp_norm", "copy")
    if cfg.qkv_bias:  # Qwen2 family
        m[f"model.layers.{i}.self_attn.q_proj.bias"] = ("bq", "bias_q")
        m[f"model.layers.{i}.self_attn.k_proj.bias"] = ("bk", "bias_kv")
        m[f"model.layers.{i}.self_attn.v_proj.bias"] = ("bv", "bias_kv")
    if cfg.is_moe:
        # Mixtral: block_sparse_moe.{gate, experts.N.{w1,w3,w2}};
        # OLMoE: mlp.{gate, experts.N.{gate,up,down}_proj}
        moe, names = {
            "mixtral": ("block_sparse_moe", ("w1", "w3", "w2")),
            "olmoe": ("mlp", ("gate_proj", "up_proj", "down_proj")),
        }[cfg.architecture]
        m[f"model.layers.{i}.{moe}.gate.weight"] = ("router", "t")
        for x in range(cfg.num_experts):
            for ours, theirs in zip(("w_gate", "w_up", "w_down"), names):
                m[f"model.layers.{i}.{moe}.experts.{x}.{theirs}.weight"] = (
                    f"{ours}.{x}", "t")
    elif cfg.architecture != "phi3":  # phi3's MLP keys are set above
        m[f"model.layers.{i}.mlp.gate_proj.weight"] = ("w_gate", "t")
        m[f"model.layers.{i}.mlp.up_proj.weight"] = ("w_up", "t")
        m[f"model.layers.{i}.mlp.down_proj.weight"] = ("w_down", "t")
    return m


def _convert(name_rule: str, w: np.ndarray, cfg: ModelConfig) -> np.ndarray:
    H, KH, D, E = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.hidden_size
    if name_rule == "copy":
        return w
    if name_rule == "t":  # HF linear stores (out, in); we use (in, out)
        return w.T
    if name_rule == "proj_q":  # (H*D, E) -> (E, H, D)
        return w.reshape(H, D, E).transpose(2, 0, 1)
    if name_rule == "proj_kv":  # (KH*D, E) -> (E, KH, D)
        return w.reshape(KH, D, E).transpose(2, 0, 1)
    if name_rule == "proj_o":  # (E, H*D) -> (H, D, E)
        return w.reshape(E, H, D).transpose(1, 2, 0)
    if name_rule == "bias_q":  # (H*D,) -> (H, D)
        return w.reshape(H, D)
    if name_rule == "bias_kv":  # (KH*D,) -> (KH, D)
        return w.reshape(KH, D)
    # Phi-3 fused layouts: qkv_proj rows [q | k | v], gate_up [gate | up]
    if name_rule == "fused_q":
        return _convert("proj_q", w[: H * D], cfg)
    if name_rule == "fused_k":
        return _convert("proj_kv", w[H * D : H * D + KH * D], cfg)
    if name_rule == "fused_v":
        return _convert("proj_kv", w[H * D + KH * D :], cfg)
    if name_rule == "fused_gate":
        return w[: w.shape[0] // 2].T
    if name_rule == "fused_up":
        return w[w.shape[0] // 2 :].T
    raise ValueError(name_rule)


def _whisper_block_map(prefix: str, i: int, cross: bool) -> dict:
    """HF Whisper layer tensor names → (ours, rule) for one block.
    ``prefix`` is ``model.encoder.layers`` / ``model.decoder.layers``."""
    b = f"{prefix}.{i}"
    m = {
        f"{b}.self_attn_layer_norm.weight": ("attn_norm_w", "copy"),
        f"{b}.self_attn_layer_norm.bias": ("attn_norm_b", "copy"),
        f"{b}.self_attn.q_proj.weight": ("wq", "proj_q"),
        f"{b}.self_attn.q_proj.bias": ("bq", "bias_q"),
        f"{b}.self_attn.k_proj.weight": ("wk", "proj_q"),  # H == KH
        f"{b}.self_attn.v_proj.weight": ("wv", "proj_q"),
        f"{b}.self_attn.v_proj.bias": ("bv", "bias_q"),
        f"{b}.self_attn.out_proj.weight": ("wo", "proj_o"),
        f"{b}.self_attn.out_proj.bias": ("bo", "copy"),
        f"{b}.final_layer_norm.weight": ("mlp_norm_w", "copy"),
        f"{b}.final_layer_norm.bias": ("mlp_norm_b", "copy"),
        f"{b}.fc1.weight": ("fc1", "t"),
        f"{b}.fc1.bias": ("fc1_b", "copy"),
        f"{b}.fc2.weight": ("fc2", "t"),
        f"{b}.fc2.bias": ("fc2_b", "copy"),
    }
    if cross:
        m.update({
            f"{b}.encoder_attn_layer_norm.weight": ("cross_norm_w", "copy"),
            f"{b}.encoder_attn_layer_norm.bias": ("cross_norm_b", "copy"),
            f"{b}.encoder_attn.q_proj.weight": ("cwq", "proj_q"),
            f"{b}.encoder_attn.q_proj.bias": ("cbq", "bias_q"),
            f"{b}.encoder_attn.k_proj.weight": ("cwk", "proj_q"),
            f"{b}.encoder_attn.v_proj.weight": ("cwv", "proj_q"),
            f"{b}.encoder_attn.v_proj.bias": ("cbv", "bias_q"),
            f"{b}.encoder_attn.out_proj.weight": ("cwo", "proj_o"),
            f"{b}.encoder_attn.out_proj.bias": ("cbo", "copy"),
        })
    return m


def _load_whisper_safetensors(cfg: ModelConfig, mesh: Mesh,
                              rules: ShardingRules, get, specs) -> dict:
    """WhisperForConditionalGeneration safetensors → our pytree.
    The encoder's sinusoidal embed_positions and the tied proj_out are
    not loaded (computed / tied in models/whisper.py)."""
    dt = cfg.jax_dtype

    def put(arr: np.ndarray, axes) -> jax.Array:
        return jax.device_put(
            jnp.asarray(arr, dtype=dt), logical_to_sharding(axes, mesh, rules)
        )

    def stack_layers(prefix: str, n: int, cross: bool, block_specs) -> dict:
        per: dict[str, list] = {}
        for i in range(n):
            for hf_name, (ours, rule) in _whisper_block_map(
                    prefix, i, cross).items():
                per.setdefault(ours, []).append(
                    _convert(rule, get(hf_name), cfg))
        return {k: put(np.stack(v), block_specs[k]) for k, v in per.items()}

    enc_s, dec_s = specs["enc"], specs["dec"]
    return {
        "enc": {
            # HF conv weight is (out, in, k); ours (k, in, out)
            "conv1_w": put(get("model.encoder.conv1.weight")
                           .transpose(2, 1, 0), enc_s["conv1_w"]),
            "conv1_b": put(get("model.encoder.conv1.bias"),
                           enc_s["conv1_b"]),
            "conv2_w": put(get("model.encoder.conv2.weight")
                           .transpose(2, 1, 0), enc_s["conv2_w"]),
            "conv2_b": put(get("model.encoder.conv2.bias"),
                           enc_s["conv2_b"]),
            "layers": stack_layers("model.encoder.layers",
                                   cfg.encoder_layers, False,
                                   enc_s["layers"]),
            "final_norm_w": put(get("model.encoder.layer_norm.weight"),
                                enc_s["final_norm_w"]),
            "final_norm_b": put(get("model.encoder.layer_norm.bias"),
                                enc_s["final_norm_b"]),
        },
        "dec": {
            "embed": put(get("model.decoder.embed_tokens.weight"),
                         dec_s["embed"]),
            "pos": put(get("model.decoder.embed_positions.weight"),
                       dec_s["pos"]),
            "layers": stack_layers("model.decoder.layers", cfg.num_layers,
                                   True, dec_s["layers"]),
            "final_norm_w": put(get("model.decoder.layer_norm.weight"),
                                dec_s["final_norm_w"]),
            "final_norm_b": put(get("model.decoder.layer_norm.bias"),
                                dec_s["final_norm_b"]),
        },
    }


def _check_ouro_names(cfg: ModelConfig, index: dict) -> None:
    """Ouro's tensor names are written down from memory (see _hf_key_map):
    a checkpoint that holds a tensor this loader does not know, or lacks
    one it expects, fails here and is not served with a layer missing.
    The exit gate is known and not loaded: it is not evaluated (at the
    published early_exit_threshold of 1 it selects nothing)."""
    known = {"model.embed_tokens.weight", "model.norm.weight",
             "lm_head.weight", "model.early_exit_gate.weight",
             "model.early_exit_gate.bias"}
    for i in range(cfg.num_layers):
        known.update(_hf_key_map(cfg, i))
    unknown = sorted(k for k in index
                     if k not in known and not k.endswith("rotary_emb.inv_freq"))
    optional = {"model.early_exit_gate.weight", "model.early_exit_gate.bias"}
    if cfg.tie_word_embeddings:
        optional.add("lm_head.weight")
    missing = sorted(known - set(index) - optional)
    if unknown or missing:
        raise ValueError(
            f"Ouro checkpoint {cfg.weights_path}: tensor names this loader "
            f"does not know {unknown[:8]}, names it expects and did not "
            f"find {missing[:8]}")


def load_safetensors(cfg: ModelConfig, mesh: Mesh, rules: ShardingRules) -> dict:
    if cfg.architecture in ("solar_open2", "pangu_ultra_moe", "phi4flash",
                            "kimi_linear", "falcon_h1", "olmo_hybrid",
                            "afmoe"):
        # the published tensor names (the KDA and GDN layers' conv, decay
        # and gate tensors, the router's bias; the latent paths'
        # projections and norms; the state-space layers' and the fused
        # attention and MLP tensors) are not known here and there is no
        # network to read them from: a guessed map would load silently
        # wrong or die mid-load, so a checkpoint is refused up front
        raise ValueError(
            f"{cfg.name}: loading a {cfg.architecture} checkpoint is not supported "
            "(its tensor names are not mapped); serve it with random "
            "weights from a directory that holds config.json alone")
    from safetensors import safe_open

    model = get_model(cfg)
    specs = model.param_specs(cfg)
    dt = cfg.jax_dtype

    # gather all tensors lazily across shards
    files = sorted(glob.glob(os.path.join(cfg.weights_path, "*.safetensors")))
    handles = [safe_open(f, framework="np") for f in files]
    index: dict[str, int] = {}
    for fi, h in enumerate(handles):
        for k in h.keys():
            index[k] = fi

    def get(name: str) -> np.ndarray:
        return handles[index[name]].get_tensor(name)

    if cfg.architecture == "whisper":
        try:
            return _load_whisper_safetensors(cfg, mesh, rules, get, specs)
        finally:
            for h in handles:
                del h

    def put(arr: np.ndarray, axes) -> jax.Array:
        return jax.device_put(
            jnp.asarray(arr, dtype=dt), logical_to_sharding(axes, mesh, rules)
        )

    if cfg.architecture == "ouro":
        _check_ouro_names(cfg, index)
    params: dict = {
        "embed": put(get("model.embed_tokens.weight"), specs["embed"]),
        "final_norm": put(get("model.norm.weight"), specs["final_norm"]),
    }
    if not cfg.tie_word_embeddings:
        head = get("lm_head.weight").T if "lm_head.weight" in index else get(
            "model.embed_tokens.weight"
        ).T
        params["lm_head"] = put(head, specs["lm_head"])

    layers: dict[str, list] = {}
    for i in range(cfg.num_layers):
        per_expert: dict[str, list] = {}
        for hf_name, targets in _hf_key_map(cfg, i).items():
            if isinstance(targets, tuple):
                targets = [targets]
            src = get(hf_name)
            for ours, rule in targets:
                w = _convert(rule, src, cfg)
                if "." in ours:  # expert weights collected then stacked
                    base, xi = ours.split(".")
                    per_expert.setdefault(base, []).append((int(xi), w))
                else:
                    layers.setdefault(ours, []).append(w)
        for base, items in per_expert.items():
            items.sort()
            layers.setdefault(base, []).append(np.stack([w for _, w in items]))

    params["layers"] = {
        k: put(np.stack(v), specs["layers"][k]) for k, v in layers.items()
    }
    for h in handles:
        del h
    return params
