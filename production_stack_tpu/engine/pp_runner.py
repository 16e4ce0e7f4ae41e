"""Pipeline-parallel serving runner: one submesh + KV pool per stage.

The reference serves pipeline-parallel fleets by handing vLLM a Ray cluster
(reference: helm/templates/ray-cluster.yaml --pipeline-parallel-size). The
TPU-native equivalent: the ``stage`` mesh axis partitions devices into S
submeshes; stage s holds layers [s*L/S, (s+1)*L/S), its own sharded params
slice (tensor parallelism *within* a stage still rides GSPMD on the
submesh), and its own paged KV pool with L/S layers — the per-stage KV
pools. The host relays activations between stage submeshes (DCN/ICI
transfer via ``jax.device_put``), which is the same host-mediated handoff a
multi-host PP deployment performs between slices.

Decode under PP costs S dispatches per token (the sampled token must return
to stage 0); prefill chunks stream through the stages the same way. Batch
overlap across stages (classic 1F1B-style pipelining of independent
requests) is a scheduler-level optimisation on top of this runner.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.model_runner import ModelRunner, _make_lora
from production_stack_tpu.engine.quant import maybe_quantize
from production_stack_tpu.engine.tracing import StepClock
from production_stack_tpu.models.registry import get_model
from production_stack_tpu.parallel.mesh import AXIS_STAGE, MESH_AXES
from production_stack_tpu.parallel.shardings import (
    logical_to_sharding,
    rules_for_model,
)



def _replicated(mesh: Mesh):
    """Fully-replicated sharding on a stage submesh (activation handoff)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


class StagedModelRunner:
    """ModelRunner-compatible facade over S per-stage runners.

    Public surface mirrors ModelRunner (prefill, decode_multi, block
    export/import, LoRA bank, sleep hooks) so LLMEngine is oblivious to
    whether it serves over one mesh or a staged pipeline.
    """

    def __init__(
        self,
        config: EngineConfig,
        mesh: Mesh,
        params: Optional[dict] = None,
        num_blocks: Optional[int] = None,
    ):
        self.config = config
        # the stages relay through the host inside one call, so the whole
        # call is the step clock's `launch` phase (engine/tracing.py)
        self.clock = StepClock()
        self.cfg = config.model
        self.mesh = mesh
        S = mesh.shape[AXIS_STAGE]
        assert S > 1, "StagedModelRunner requires a stage axis > 1"
        if self.cfg.has_recurrent_state:
            raise ValueError(
                f"{self.cfg.name} keeps recurrent state per decode slot; "
                "not supported with pipeline stages "
                f"(--pipeline-parallel-size {S}): the staged step programs "
                "carry no recurrent state")
        if self.cfg.loop_passes > 1:
            # the stages slice the layers once; a looped stack would have
            # to cycle the activations through them loop_passes times
            raise ValueError(
                f"{self.cfg.name}: loop_passes={self.cfg.loop_passes} is "
                "not supported with pipeline stages "
                f"(--pipeline-parallel-size {S}); serve a looped stack "
                "without a stage axis")
        L = self.cfg.num_layers
        assert L % S == 0, f"{L} layers not divisible by {S} stages"
        self.n_stages = S
        self.layers_per_stage = L // S
        self.stage_cfg = dataclasses.replace(self.cfg, num_layers=L // S)

        # stage submeshes: slice the stage axis out of the device array,
        # keeping the full 5-axis shape with stage=1
        dev = mesh.devices  # (data, stage, seq, tensor, expert)
        self.submeshes = [
            Mesh(dev[:, s : s + 1], MESH_AXES) for s in range(S)
        ]

        full_params = self._materialize_full(params)
        self.stages: list[ModelRunner] = []
        resolved_blocks = num_blocks
        for s in range(S):
            stage_params = self._slice_stage_params(full_params, s)
            runner = ModelRunner(
                dataclasses.replace(config, model=self.stage_cfg),
                self.submeshes[s],
                params=stage_params,
                num_blocks=resolved_blocks,
            )
            if resolved_blocks is None:
                # stage 0 resolves from free HBM; later stages must agree on
                # the block count (the allocator is shared)
                resolved_blocks = runner.num_blocks
            self.stages.append(runner)
        del full_params
        self.num_blocks = resolved_blocks
        self.max_blocks_per_seq = self.stages[0].max_blocks_per_seq
        self.rules = self.stages[0].rules

        self._compile_steps()

    # -- params ------------------------------------------------------------
    def _materialize_full(self, params: Optional[dict]) -> dict:
        from production_stack_tpu.engine.weights import init_or_load

        if params is not None:
            return params
        full_rules = rules_for_model(self.cfg, self.mesh)
        with jax.set_mesh(self.mesh):
            # LAYERS→stage rule shards the stacked layer axis across stage
            # devices, so each stage's slice already lives on its submesh
            return init_or_load(self.cfg, self.mesh, full_rules,
                                self.config.seed)

    def _slice_stage_params(self, full: dict, s: int) -> dict:
        model = get_model(self.cfg)
        specs = model.param_specs(self.cfg)
        sub = self.submeshes[s]
        srules = rules_for_model(self.stage_cfg, sub)
        lo = s * self.layers_per_stage
        hi = lo + self.layers_per_stage

        def put(arr, axes):
            return jax.device_put(
                arr, logical_to_sharding(axes, sub, srules)
            )

        p = {
            "layers": {
                k: put(v[lo:hi], specs["layers"][k])
                for k, v in full["layers"].items()
            }
        }
        if s == 0:
            p["embed"] = put(full["embed"], specs["embed"])
        if s == self.n_stages - 1:
            p["final_norm"] = put(full["final_norm"], specs["final_norm"])
            if self.cfg.tie_word_embeddings:
                p["embed"] = put(full["embed"], specs["embed"])
            else:
                p["lm_head"] = put(full["lm_head"], specs["lm_head"])
        # full params stay in model dtype (raw arrays slice by layer range);
        # each stage quantizes its own slice, so sleep/restore re-applies too
        return maybe_quantize(self.stage_cfg, p)

    # -- compiled stage steps ----------------------------------------------
    def _compile_steps(self) -> None:
        cfg = self.stage_cfg
        self._stage_prefills = []
        self._stage_decodes = []
        for s, runner in enumerate(self.stages):
            first = s == 0
            last = s == self.n_stages - 1
            self._stage_prefills.append(jax.jit(
                functools.partial(
                    _stage_prefill, cfg, runner._attend_prefill, first, last
                ),
                donate_argnums=(1,),
                static_argnames=("greedy_only", "use_controls"),
            ))
            self._stage_decodes.append(jax.jit(
                functools.partial(
                    _stage_decode, cfg, runner._attend_decode, first, last
                ),
                donate_argnums=(1,),
                static_argnames=("greedy_only", "use_penalties",
                                 "use_controls"),
            ))

    # -- public step API (ModelRunner-compatible) --------------------------
    def prefill(self, tokens, positions, block_tables, context_lens,
                slot_mapping, last_idx, temps, top_ps, top_ks, seeds,
                greedy_only: bool = True, adapter_ids=None, ctrl=None,
                g_ids=None, fetch: bool = True):
        self.clock.launch()
        x = jnp.asarray(tokens)  # stage 0 consumes token ids
        common = (
            jnp.asarray(positions), jnp.asarray(block_tables),
            jnp.asarray(context_lens), jnp.asarray(slot_mapping),
        )
        sample_args = (
            jnp.asarray(last_idx), jnp.asarray(temps), jnp.asarray(top_ps),
            jnp.asarray(top_ks), jnp.asarray(seeds),
        )
        for s, runner in enumerate(self.stages):
            use_lora = adapter_ids is not None and runner.lora_bank is not None
            if s > 0:
                x = jax.device_put(
                    x, _replicated(self.submeshes[s]))
            with jax.set_mesh(self.submeshes[s]):
                runner.kv, x = self._stage_prefills[s](
                    runner.params, runner.kv, x, *common, *sample_args,
                    lora_bank=runner.lora_bank if use_lora else None,
                    adapter_ids=(jnp.asarray(adapter_ids, jnp.int32)
                                 if use_lora else None),
                    ctrl=(tuple(jnp.asarray(c) for c in ctrl)
                          if ctrl is not None else None),
                    greedy_only=greedy_only,
                    use_controls=ctrl is not None,
                )
        if not fetch:
            return x  # last stage's sampled tokens, un-fetched
        return np.asarray(jax.device_get(x))

    supports_chaining = False  # stages relay through the host each step
    supports_logprobs = False  # per-stage programs emit sampled tokens only

    def decode_multi(self, tokens, positions, block_tables, context_lens,
                     slot_mapping, temps, top_ps, top_ks, seeds, steps,
                     greedy_only: bool = False,
                     presence=None, frequency=None,
                     adapter_ids=None, ctrl=None, tokens_dev=None,
                     g_ids=None, g_states=None, fetch: bool = True,
                     want_logprobs: bool = False) -> np.ndarray:
        """K single decode steps, each relayed through the stages. The host
        advances positions/slots between steps (the sampled token must come
        back to stage 0, so cross-step fusion can't live in one program)."""
        self.clock.launch()
        K = max(self.config.scheduler.multi_step, 1)
        B = tokens.shape[0]
        bs = self.config.cache.block_size
        use_penalties = presence is not None
        last = self.stages[-1]
        if use_penalties:
            last._ensure_counts()
        tok = tokens.copy()
        pos = positions.copy()
        ctx = context_lens.copy()
        slots = slot_mapping.copy()
        step_ctr = np.asarray(steps).copy()
        active = context_lens > 0
        bt = jnp.asarray(block_tables)
        sampled_all = np.zeros((K, B), np.int32)

        for k in range(K):
            x = jnp.asarray(tok[:, None])
            for s, runner in enumerate(self.stages):
                use_lora = (adapter_ids is not None
                            and runner.lora_bank is not None)
                is_last = s == self.n_stages - 1
                extra = {}
                if is_last:
                    counts = (last.token_counts if use_penalties else
                              jnp.zeros((B, 1), jnp.int32))
                    extra = dict(
                        temps=jnp.asarray(temps), top_ps=jnp.asarray(top_ps),
                        top_ks=jnp.asarray(top_ks), seeds=jnp.asarray(seeds),
                        steps=jnp.asarray(step_ctr), counts=counts,
                        presence=jnp.asarray(
                            presence if use_penalties else np.zeros(B, np.float32)),
                        frequency=jnp.asarray(
                            frequency if use_penalties else np.zeros(B, np.float32)),
                    )
                if s > 0:
                    x = jax.device_put(
                    x, _replicated(self.submeshes[s]))
                with jax.set_mesh(self.submeshes[s]):
                    if is_last:
                        (runner.kv, new_counts), x = self._stage_decodes[s](
                            runner.params, runner.kv, x,
                            jnp.asarray(pos[:, None]), bt, jnp.asarray(ctx),
                            jnp.asarray(slots),
                            lora_bank=runner.lora_bank if use_lora else None,
                            adapter_ids=(jnp.asarray(adapter_ids, jnp.int32)
                                         if use_lora else None),
                            greedy_only=greedy_only,
                            use_penalties=use_penalties,
                            ctrl=(tuple(jnp.asarray(c) for c in ctrl)
                                  if ctrl is not None else None),
                            use_controls=ctrl is not None,
                            **extra,
                        )
                        if use_penalties:
                            last.token_counts = new_counts
                    else:
                        runner.kv, x = self._stage_decodes[s](
                            runner.params, runner.kv, x,
                            jnp.asarray(pos[:, None]), bt, jnp.asarray(ctx),
                            jnp.asarray(slots),
                            lora_bank=runner.lora_bank if use_lora else None,
                            adapter_ids=(jnp.asarray(adapter_ids, jnp.int32)
                                         if use_lora else None),
                            greedy_only=greedy_only,
                            use_penalties=use_penalties,
                        )
            sampled = np.asarray(jax.device_get(x))
            sampled_all[k] = sampled
            pos = np.where(active, pos + 1, pos)
            ctx = np.where(active, ctx + 1, ctx)
            block = np.asarray(block_tables)[
                np.arange(B), np.clip(pos, 0, None) // bs
            ]
            slots = np.where(active, block * bs + pos % bs, -1).astype(np.int32)
            tok = np.where(active, sampled, tok).astype(np.int32)
            step_ctr = step_ctr + 1
        return sampled_all

    # -- penalties ---------------------------------------------------------
    def set_count_row(self, slot: int, token_ids: list[int]) -> None:
        self.stages[-1].set_count_row(slot, token_ids)

    @property
    def token_counts(self):
        return self.stages[-1].token_counts

    # -- LoRA bank (sliced per stage along the layer axis) ------------------
    @property
    def lora_bank(self):
        return self.stages[0].lora_bank

    def register_lora(self, slot: int, bank_np: dict) -> None:
        Lps = self.layers_per_stage
        for s, runner in enumerate(self.stages):
            sliced = {
                k: (A[s * Lps : (s + 1) * Lps], B[s * Lps : (s + 1) * Lps])
                for k, (A, B) in bank_np.items()
            }
            runner.register_lora(slot, sliced)

    def unregister_lora(self, slot: int) -> None:
        for runner in self.stages:
            runner.unregister_lora(slot)

    # -- KV block export/import (layer axis concatenated across stages) ----
    def export_blocks(self, block_ids: list[int]) -> np.ndarray:
        return np.concatenate(
            [r.export_blocks(block_ids) for r in self.stages], axis=0
        )

    def import_blocks(self, block_ids: list[int], data: np.ndarray) -> None:
        Lps = self.layers_per_stage
        for s, runner in enumerate(self.stages):
            runner.import_blocks(block_ids, data[s * Lps : (s + 1) * Lps])

    def export_blocks_range(self, block_ids: list[int], layer_lo: int,
                            n_layers: int) -> np.ndarray:
        Lps = self.layers_per_stage
        parts = []
        for s, runner in enumerate(self.stages):
            lo = max(layer_lo, s * Lps)
            hi = min(layer_lo + n_layers, (s + 1) * Lps)
            if lo < hi:
                parts.append(
                    runner.export_blocks_range(block_ids, lo - s * Lps,
                                               hi - lo)
                )
        return np.concatenate(parts, axis=0)

    def import_blocks_range(self, block_ids: list[int], layer_lo: int,
                            data: np.ndarray) -> None:
        Lps = self.layers_per_stage
        off = 0
        for s, runner in enumerate(self.stages):
            lo = max(layer_lo, s * Lps)
            hi = min(layer_lo + data.shape[0], (s + 1) * Lps)
            if lo < hi:
                runner.import_blocks_range(
                    block_ids, lo - s * Lps, data[off : off + hi - lo]
                )
                off += hi - lo

    # -- sleep mode hooks ---------------------------------------------------
    def drop_kv(self) -> None:
        for r in self.stages:
            r.kv = None

    def restore_kv(self) -> None:
        from production_stack_tpu.engine import kv_cache as kvmod

        for r in self.stages:
            if r.kv is None:
                r.kv = kvmod.init_kv_cache(
                    r.cfg, r.config.cache, r.mesh, r.rules, r.num_blocks
                )

    def drop_params(self) -> None:
        for r in self.stages:
            r.params = None

    def restore_params(self) -> None:
        if any(r.params is None for r in self.stages):
            full = self._materialize_full(None)
            for s, r in enumerate(self.stages):
                r.params = self._slice_stage_params(full, s)

    @property
    def params_alive(self) -> bool:
        return all(r.params is not None for r in self.stages)

    @property
    def kv_alive(self) -> bool:
        return all(r.kv is not None for r in self.stages)

    # -- dense forward chained through the stages ---------------------------
    def _ensure_stage_fns(self) -> None:
        if getattr(self, "_pooled_stage_fns", None) is not None:
            return
        from production_stack_tpu.ops.attention import (
            dense_causal_attention,
        )

        model = get_model(self.stage_cfg)
        cfg = self.stage_cfg

        def stage_fwd(first, params, x, positions):
            def attend(q, k, v, caches, layer_idx):
                return dense_causal_attention(
                    q, k, v, soft_cap=cfg.attn_logit_softcap
                ), caches

            if first:
                x = model.embed_tokens(cfg, params, x)
            hidden, _ = model.forward_hidden(
                cfg, params, x, positions, attend, None
            )
            return hidden

        self._pooled_stage_fns = [
            jax.jit(functools.partial(stage_fwd, s == 0))
            for s in range(self.n_stages)
        ]

    def pipe_hidden(self, tokens: np.ndarray) -> jnp.ndarray:
        """Dense causal forward chained through the stages → final hidden
        (the pooled-embedding and guided-choice scoring backbone)."""
        self._ensure_stage_fns()
        S = tokens.shape[1]
        positions = np.broadcast_to(np.arange(S, dtype=np.int32),
                                    tokens.shape)
        x = jnp.asarray(tokens)
        for s, runner in enumerate(self.stages):
            if s > 0:
                x = jax.device_put(x, _replicated(self.submeshes[s]))
            with jax.set_mesh(self.submeshes[s]):
                x = self._pooled_stage_fns[s](
                    runner.params, x, jnp.asarray(positions)
                )
        return x

    # -- dense pooled embedding (the /v1/embeddings surface) ----------------
    def pooled_embed(self, tokens: np.ndarray, mask: np.ndarray) -> np.ndarray:
        x = self.pipe_hidden(tokens)
        m = np.asarray(mask)[:, :, None].astype(np.float32)
        h = np.asarray(jax.device_get(x)).astype(np.float32)
        pooled = (h * m).sum(axis=1) / np.maximum(m.sum(axis=1), 1.0)
        return pooled

    # -- teacher-forced sequence scoring (guided choice) ---------------------

    def sequence_logprobs(self, tokens: np.ndarray,
                          cont_mask: np.ndarray) -> np.ndarray:
        """ModelRunner.sequence_logprobs over the staged pipeline: hidden
        states stream through the stages, the last stage scores."""
        hidden = self.pipe_hidden(tokens)
        model = get_model(self.stage_cfg)
        cfg = self.stage_cfg
        last = self.stages[-1]
        if getattr(self, "_seqlp_tail_fn", None) is None:
            def _tail(params, hidden, tokens, cont_mask):
                logits = model.logits_from_hidden(cfg, params, hidden)
                logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
                tgt = tokens[:, 1:]
                picked = jnp.take_along_axis(
                    logp, tgt[..., None], axis=-1
                )[..., 0]
                return jnp.sum(
                    picked * cont_mask[:, 1:].astype(jnp.float32), axis=-1
                )

            self._seqlp_tail_fn = jax.jit(_tail)
        sub = self.submeshes[-1]
        with jax.set_mesh(sub):
            out = self._seqlp_tail_fn(
                last.params, hidden,
                jax.device_put(jnp.asarray(tokens), _replicated(sub)),
                jax.device_put(jnp.asarray(cont_mask), _replicated(sub)),
            )
        return np.asarray(jax.device_get(out))


# ---------------------------------------------------------------------------
# pure per-stage device functions
# ---------------------------------------------------------------------------

def _stage_prefill(cfg, attend_impl, first: bool, last: bool, params, kv,
                   x, positions, block_tables, context_lens, slot_mapping,
                   last_idx, temps, top_ps, top_ks, seeds,
                   lora_bank=None, adapter_ids=None, ctrl=None,
                   greedy_only: bool = False, use_controls: bool = False):
    """One stage of a batched prefill chunk.

    Stage 0 receives token ids (P, S) and embeds; later stages receive
    hidden activations (P, S, E). The last stage samples each chunk's next
    token and returns (kv, sampled (P,)); others return (kv, hidden)."""
    from production_stack_tpu.engine.sampling import sample_tokens

    model = get_model(cfg)

    def attend(q, k, v, caches, layer_idx):
        return attend_impl(
            q, k, v, caches, layer_idx, block_tables, context_lens,
            positions, slot_mapping,
        )

    if first:
        x = model.embed_tokens(cfg, params, x)
    hidden, kv = model.forward_hidden(
        cfg, params, x, positions, attend, kv,
        lora=_make_lora(lora_bank, adapter_ids, positions.shape[1]),
    )
    if not last:
        return kv, hidden
    last_hidden = jnp.take_along_axis(
        hidden, last_idx[:, None, None], axis=1
    )[:, 0]
    logits = model.logits_from_hidden(cfg, params, last_hidden[:, None])[:, 0]
    if use_controls:
        from production_stack_tpu.engine.sampling import apply_token_controls

        logits = apply_token_controls(logits, *ctrl)
    if greedy_only:
        sampled = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        sampled = sample_tokens(
            logits, temps, top_ps, top_ks, seeds, jnp.zeros_like(last_idx)
        )
    return kv, sampled


def _stage_decode(cfg, attend_impl, first: bool, last: bool, params, kv,
                  x, positions, block_tables, context_lens, slot_mapping,
                  lora_bank=None, adapter_ids=None,
                  temps=None, top_ps=None, top_ks=None, seeds=None,
                  steps=None, counts=None, presence=None, frequency=None,
                  ctrl=None,
                  greedy_only: bool = False, use_penalties: bool = False,
                  use_controls: bool = False):
    """One stage of a single fused decode step (B, 1).

    Last stage samples (with optional presence/frequency penalties, counts
    carried on device) and returns ((kv, counts), sampled (B,))."""
    from production_stack_tpu.engine.sampling import (
        penalize_logits,
        sample_tokens,
    )

    model = get_model(cfg)

    def attend(q, k, v, caches, layer_idx):
        return attend_impl(
            q, k, v, caches, layer_idx, block_tables, context_lens,
            positions, slot_mapping,
        )

    if first:
        x = model.embed_tokens(cfg, params, x)
    hidden, kv = model.forward_hidden(
        cfg, params, x, positions, attend, kv,
        lora=_make_lora(lora_bank, adapter_ids, 1),
    )
    if not last:
        return kv, hidden
    logits = model.logits_from_hidden(cfg, params, hidden)[:, 0]
    if use_penalties:
        logits = penalize_logits(logits, counts, presence, frequency)
    if use_controls:
        from production_stack_tpu.engine.sampling import apply_token_controls

        logits = apply_token_controls(logits, *ctrl)
    if greedy_only:
        sampled = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        sampled = sample_tokens(logits, temps, top_ps, top_ks, seeds, steps)
    if use_penalties:
        B = sampled.shape[0]
        active = context_lens > 0
        counts = counts.at[jnp.arange(B), sampled].add(
            active.astype(counts.dtype)
        )
    return (kv, counts), sampled
