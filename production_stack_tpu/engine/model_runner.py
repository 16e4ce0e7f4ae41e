"""Jitted device steps over the paged cache: the ragged step, which runs
every prompt, and the fused decode step.

Static-shape discipline (XLA traces once per shape):

- **ragged step** (``ragged_step``): ONE program consumes a packed token
  stream ``tokens (1, T)`` covering prefill chunks AND decode rows in the
  same dispatch — per-slot spans described by ``cu_q_lens (S+1,)`` with
  ``S = max_num_seqs`` slots in slot order (decode rows span 1 token,
  prefilling slots span their chunk, inactive slots span 0). ``T`` is one
  of the scheduler's few stream widths
  (``SchedulerConfig.ragged_stream_widths``: the token budget
  ``max_num_batched_tokens`` and, where it pays, one narrow width), the
  narrowest that holds the step's tokens, and the program reads it off
  its input: the steady-state compile-signature space is ONE signature a
  width per static-flag variant, and a step carries prompts and decode
  rows together. Sampling happens per slot at each span's last token;
  rows whose sample is not consumed (mid-prompt chunks, inactive slots)
  produce masked garbage the host discards.
- **decode step** (``prepare_decode``): a step of decode rows alone is one
  compiled program of ``multi_step`` fused decode + sample iterations
  over a fixed (max_num_seqs, 1) batch. Block tables are always
  (B, max_blocks_per_seq).
- KV cache buffers are donated through every step, so XLA updates them in
  place in HBM — the pool is allocated once at startup and never copied.

Attention backend selection (``use_pallas``, decided by ``_pallas_ok``):
Pallas kernels on TPU (wrapped in shard_map over the tensor axis when
tp > 1 — heads are independent, so the kernels need no cross-chip
traffic); the XLA gather forms of ops/paged_attention.py on the CPU, and
on an accelerator for a geometry the kernels cannot take. The same two
programs either way: the XLA forms are the kernels' reference.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from production_stack_tpu.engine.config import EngineConfig, ModelConfig
from production_stack_tpu.engine import kv_cache as kvmod
from production_stack_tpu.engine.quant import is_quantized, maybe_quantize
from production_stack_tpu.engine.sampling import sample_tokens
from production_stack_tpu.engine.tracing import (
    LoopCounters,
    MoeCounters,
    StartClock,
    StepClock,
)
from production_stack_tpu.ops import kda
from production_stack_tpu.engine.weights import init_or_load, lay_out
from production_stack_tpu.models.registry import get_model
from production_stack_tpu.ops.paged_attention import (
    combine_kv,
    paged_attention,
    write_kv,
)
from production_stack_tpu.ops.moe_grouped_matmul_pallas import (
    grouped_kernel_path,
    moe_grouped_matmul,
)
from production_stack_tpu.ops.paged_attention_pallas import decode_slab_path
from production_stack_tpu.parallel.mesh import AXIS_TENSOR
from production_stack_tpu.parallel.shardings import rules_for_model

_log = logging.getLogger(__name__)


def _named_partial(fn, *bound, **bound_kw):
    """functools.partial that keeps the function's name, so XLA names the
    jitted program after it (``jit_ragged_step``; a bare partial compiles
    to ``jit__unknown``) and a profiler trace can tell the programs apart."""
    part = functools.partial(fn, *bound, **bound_kw)
    part.__name__ = fn.__name__.lstrip("_")
    return part


@dataclasses.dataclass(frozen=True)
class StepLayout:
    """Where each always-present host input of a step lies in the ONE
    packed int32 buffer the runner transfers per dispatch.

    ``fields`` is ((name, shape, dtype name), ...) in buffer order, every
    dtype 4 bytes wide: floats and the uint32 seeds travel as their bit
    patterns (``view`` on the host, ``bitcast_convert_type`` in the
    program), so a round trip is bit-exact. The layout follows from the
    inputs' shapes alone — every static variant of a program reads the
    same buffer — and is a static (hashable) argument of the jitted step,
    which unpacks with static slices. ``ModelRunner`` owns it; the
    engine and the other runners never see the buffer."""

    fields: tuple

    @classmethod
    def of(cls, spec, arrays) -> "StepLayout":
        """Layout of ``arrays`` under ``spec`` ((name, dtype name), ...)."""
        return cls(tuple((name, tuple(np.shape(a)), dt)
                         for (name, dt), a in zip(spec, arrays, strict=True)))

    def pack(self, arrays) -> np.ndarray:
        """A fresh contiguous buffer holding ``arrays``: the copy is also
        the snapshot of host arrays the caller rewrites in place."""
        return np.concatenate([
            np.asarray(a, dt).reshape(-1).view(np.int32)
            for a, (_, _, dt) in zip(arrays, self.fields, strict=True)])

    def unpack(self, buf) -> dict:
        """name -> array, traced inside the jitted program: static slices,
        reshapes and bitcasts (microseconds on the device)."""
        out, off = {}, 0
        for name, shape, dt in self.fields:
            n = math.prod(shape)
            x = buf[off:off + n].reshape(shape)
            if dt != "int32":
                x = jax.lax.bitcast_convert_type(x, jnp.dtype(dt))
            out[name] = x
            off += n
        return out


_DECODE_INPUTS = (
    ("tokens", "int32"), ("positions", "int32"), ("block_tables", "int32"),
    ("context_lens", "int32"), ("slot_mapping", "int32"),
    ("temps", "float32"), ("top_ps", "float32"), ("top_ks", "int32"),
    ("seeds", "uint32"), ("steps", "int32"),
    # 1 = this dispatch's input tokens are the device-resident next_tok of
    # the previous one (chained decode), 0 = the ``tokens`` field above
    ("tokens_on_device", "int32"),
)
_RAGGED_INPUTS = (
    ("tokens", "int32"), ("positions", "int32"), ("block_tables", "int32"),
    ("context_lens", "int32"), ("cu_q_lens", "int32"),
    ("slot_mapping", "int32"), ("last_idx", "int32"),
    ("sample_mask", "float32"), ("temps", "float32"), ("top_ps", "float32"),
    ("top_ks", "int32"), ("seeds", "uint32"), ("steps", "int32"),
    ("verify_idx", "int32"),  # only with speculation compiled in
)


# where a model's window binds (ModelConfig.window_binds), behind the step's
# own: the window layers' pool has a block table and a slot mapping of its
# own, shaped like ``block_tables`` and ``slot_mapping``
_WINDOW_INPUTS = (("window_block_tables", "int32"),
                  ("window_slot_mapping", "int32"))


def _window_kw(f: dict, slots=None) -> dict:
    """An attention call's window inputs out of a step's unpacked fields
    (``slots``: the slot mapping where the step advances its own); nothing
    for a model with one pool."""
    if "window_block_tables" not in f:
        return {}
    return {"window_tables": f["window_block_tables"],
            "window_slots": (f["window_slot_mapping"] if slots is None
                             else slots)}


def _owning_slots(cu_q_lens, tokens: int, slots: int):
    """Owning slot per token of a packed stream, recovered from the span
    offsets (the XLA attention forms ask it; the kernels walk the offsets)."""
    return jnp.clip(jnp.searchsorted(
        cu_q_lens, jnp.arange(tokens, dtype=jnp.int32), side="right"
    ).astype(jnp.int32) - 1, 0, slots - 1)


def _pallas_ok(cfg: ModelConfig, mesh: Mesh, block_size: int) -> bool:
    """Whether the Pallas attention kernels can serve this model here. On
    an accelerator a False is a slow path (XLA gather attention), so the
    failed condition is logged."""
    backend = jax.default_backend()
    if backend == "cpu":
        return False
    tp = mesh.shape[AXIS_TENSOR]
    # Mosaic tiling: head_dim must fill the 128-lane dim, block_size the
    # sublane dim (8 f32 / 16 bf16). A latent pool's rows are whole lane
    # tiles as stored and no head of it shards: the block alone decides
    failed = [
        what for ok, what in ((
            (block_size % 16 == 0, f"block_size {block_size} % 16 != 0"),
        ) if cfg.is_latent else (
            (cfg.num_kv_heads % tp == 0,
             f"num_kv_heads {cfg.num_kv_heads} % tensor {tp} != 0"),
            (cfg.num_heads % tp == 0,
             f"num_heads {cfg.num_heads} % tensor {tp} != 0"),
            # as the cache holds a head: a differential pair of 64-wide
            # heads is one of 128 (models/sambay.py)
            (cfg.cache_head_dim % 128 == 0,
             f"head_dim {cfg.cache_head_dim} % 128 != 0"),
            (block_size % 16 == 0, f"block_size {block_size} % 16 != 0"),
        )) if not ok
    ]
    if failed:
        _log.warning(
            "%s on %s: Pallas attention kernels unusable (%s) — serving "
            "through XLA gather attention", cfg.name, backend,
            "; ".join(failed))
    return not failed


def _moe_grouped_kernel_ok(cfg: ModelConfig, mesh: Mesh, rules,
                           params: dict) -> bool:
    """Whether the MoE block's grouped matmuls run the Pallas kernel
    (ops/moe_grouped_matmul_pallas.py) in this runner's step programs, from
    what can be observed here: a TPU backend, experts in the model dtype
    (int8 experts keep ``ragged_dot``'s W8A8), no mesh axis that splits an
    axis of the expert matrices (GSPMD cannot partition the custom call),
    and both projections' (K, N) within the kernel's own shape rule."""
    if not cfg.is_moe or jax.default_backend() != "tpu":
        return False
    from production_stack_tpu.parallel import shardings as ln

    w_gate, w_down = (params["layers"][k] for k in ("w_gate", "w_down"))
    if is_quantized(w_gate):
        return False
    split = [a for a in (ln.LAYERS, ln.EXPERTS, ln.EMBED, ln.MLP)
             if rules.rules.get(a) is not None
             and mesh.shape[rules.rules[a]] > 1]
    return not split and all(
        grouped_kernel_path(*w.shape[-2:], w.dtype.itemsize)
        for w in (w_gate, w_down))


class ModelRunner:
    """Owns params, the KV block pool and the compiled step functions."""

    def __init__(
        self,
        config: EngineConfig,
        mesh: Mesh,
        params: Optional[dict] = None,
        num_blocks: Optional[int] = None,
        start: Optional[StartClock] = None,
    ):
        self.config = config
        self.cfg = config.model
        self.mesh = mesh
        # the start clock of whoever builds this runner (the engine's:
        # these spans are children of its `engine_build`), else one of its
        # own that nobody reads
        self.start = start if start is not None else StartClock()
        # the engine's step clock (engine/tracing.py); the engine replaces
        # this one with its own, a runner driven alone keeps it
        self.clock = StepClock()
        if (self.cfg.sliding_window and not self.cfg.window_binds
                and self.cfg.max_model_len > self.cfg.sliding_window):
            # local/global attention layers coincide only within the window;
            # beyond it the global-attention approximation would silently
            # diverge from the model's semantics — refuse instead
            raise ValueError(
                f"{self.cfg.name}: max_model_len {self.cfg.max_model_len} "
                f"exceeds the local-attention window "
                f"{self.cfg.sliding_window}; serve with max_model_len <= "
                "window (exactness gate, see ModelConfig.sliding_window)"
            )
        if self.cfg.has_recurrent_state:
            self._refuse_for_recurrent_state(config, mesh)
        elif self.cfg.window_binds:
            self._refuse_for_two_pools(config, mesh)
        self.rules = rules_for_model(self.cfg, mesh)
        self.model = get_model(self.cfg)
        # a looped stack's step programs return the passes they made, one
        # int32 a forward, after the histogram: same fetch, same route
        self.loop = (LoopCounters(self.cfg.num_layers)
                     if self.cfg.loop_passes > 1 else None)
        # what `step.launch` says of such a stack in a trace
        self._launch_attrs = ({"passes": self.cfg.loop_passes}
                              if self.loop is not None else {})
        if self.cfg.window_layers:  # attention layers of two kinds: how
            # many of each the step's program walks
            self._launch_attrs.update(
                {"layers_" + kind: self.cfg.count_layers(kind)
                 for kind in ("swa", "full")})
        # a tree handed in is served as it is (the caller keeps it); one
        # loaded here is laid out as the step programs read it
        if params is None:
            self.params = self._loaded_params()
        else:
            with jax.set_mesh(mesh), self.start.span("weights.quantize"):
                self.params = maybe_quantize(self.cfg, params)
        self.use_pallas = _pallas_ok(self.cfg, mesh, config.cache.block_size)
        # what runs the MoE block's grouped matmuls in every step program
        # built here: the Pallas kernel, or None for jax.lax.ragged_dot
        # (vllm:moe_grouped_kernel_layer_steps_total)
        self.moe_grouped_matmul = (
            moe_grouped_matmul if _moe_grouped_kernel_ok(
                self.cfg, mesh, self.rules, self.params) else None)
        # routing counters of an MoE model (engine/tracing.py): the step
        # programs then return a per-layer routing histogram as their last
        # result leaf, which is fetched with the step's own results
        self.moe = (MoeCounters(
            self.cfg.num_held_experts, self.cfg.num_experts_per_tok,
            share=bool(self.cfg.experts_held),
            grouped_kernel=self.moe_grouped_matmul is not None)
            if self.cfg.is_moe else None)
        # whether a decode step's attention calls have the Pallas decode
        # kernel score a window from the slab as stored: the kernel's own
        # predicate at this runner's per-shard geometry, for the calls
        # without a window and for the window layers' ("swa", _attend_kind)
        # (vllm:decode_attn_slab_calls_total)
        self.decode_attn_slab, self.decode_attn_slab_windowed = (
            self.use_pallas and not self.cfg.is_latent and decode_slab_path(
                self.cfg.cache_kv_heads // self.tp, self.cfg.q_per_kv,
                self.cfg.cache_head_dim, self.cfg.jax_dtype, window)
            for window in (0, self.cfg.sliding_window))
        # the window layers' pool (0 where no window binds) follows from the
        # configuration; the other pool takes what memory is left
        self.window_blocks = kvmod.window_pool_blocks(
            self.cfg, config.cache.block_size,
            config.scheduler.max_num_seqs,
            config.scheduler.max_num_batched_tokens)
        # sizing reads the device's memory_stats(), which waits for nothing:
        # the weights' programs may still run (the engine's `device_drain`)
        with self.start.span("kv_pool"):
            self.num_blocks = self._resolve_num_blocks(num_blocks)
            self.kv = self._init_cache()
        # block-table width padded to a multiple of the kernels' DMA window
        # (they read whole windows; tables are 0-padded past the live blocks)
        mbs = -(-self.cfg.max_model_len // config.cache.block_size)
        self.max_blocks_per_seq = (mbs + 7) // 8 * 8
        from production_stack_tpu.engine.tokenizer import get_tokenizer

        # bound into the compiled programs: grammar masking must know where
        # EOS lives (allowed exactly in accepting FSM states)
        self._eos_id = get_tokenizer(config.model.tokenizer).eos_id

        # result-replication gate: on ANY multi-device mesh — one process
        # driving TP over ICI or many controller processes (multihost,
        # engine/multihost.py contract) — every result the controller
        # fetches must come out fully REPLICATED so jax.device_get is one
        # local host copy: a partially-sharded output would either not be
        # addressable (multihost) or force a cross-chip gather on the
        # host path every step (single-process TP). The (None, repl)
        # prefix keeps the donated KV pool on its own sharding (auto —
        # KV heads stay partitioned over the tensor axis) and replicates
        # only the small result leaves (sampled tokens, verify columns,
        # logprobs). Single chip: no gate.
        from production_stack_tpu.parallel.shardings import replicated

        self._replicate_results = jax.process_count() > 1
        self._multi_device = mesh.devices.size > 1
        self._repl = replicated(mesh)
        if self._multi_device:
            self._mh_gate = {"out_shardings": (None, self._repl)}
            self._mh_gate_all = {"out_shardings": self._repl}
        else:
            self._mh_gate = {}
            self._mh_gate_all = {}

        recurrent = self.cfg.has_recurrent_state
        recur = (self._recur_mamba if self.cfg.mamba_period
                 else self._recur_ssd if self.cfg.ssd_heads else self._recur)
        self._decode_multi = jax.jit(
            _named_partial(
                _decode_multi_step, self.cfg, self._attend_decode,
                max(config.scheduler.multi_step, 1), self._eos_id,
                recur_impl=(functools.partial(recur, False)
                            if recurrent else None),
                grouped_matmul=self.moe_grouped_matmul,
            ),
            donate_argnums=(1,),
            static_argnames=("layout", "block_size", "greedy_only",
                             "use_penalties", "use_controls",
                             "want_logprobs", "use_grammar"),
            **self._mh_gate,
        )
        # speculative verify is FUSED into the ragged program: the draft
        # width is baked in as a compile-time constant, so the one
        # steady-state signature covers plain decode, mixed prefill+decode
        # and verify-bearing steps alike (no separate _verify program, no
        # lazy verify compile after warmup)
        self.spec_width = max(config.scheduler.spec_ngram_k, 0)
        self._ragged = jax.jit(
            _named_partial(_ragged_step, self.cfg,
                           self._attend_ragged, self._eos_id,
                           self.spec_width,
                           recur_impl=(functools.partial(recur, True)
                                       if recurrent else None),
                           grouped_matmul=self.moe_grouped_matmul),
            donate_argnums=(1,),
            static_argnames=("layout", "greedy_only", "use_penalties",
                             "use_controls", "use_grammar"),
            **self._mh_gate,
        )
        self._sample = jax.jit(sample_tokens)
        # per-slot output-token counts for presence/frequency penalties
        # ((B, V) int32; allocated on first penalised batch)
        self.token_counts = None
        # the decode program takes device tokens on every dispatch, so a
        # launch that is given them and one that is not share ONE
        # executable; the latter reads the packed tokens and ignores this
        # constant (placed like the program's own next_tok output)
        self._no_tokens_dev = jax.device_put(
            np.zeros((config.scheduler.max_num_seqs, 1), np.int32),
            self._repl)
        # multi-LoRA bank: target -> (A (L, N, in, R), B (L, N, R, *out));
        # slot 0 stays zeros (base model)
        self.lora_bank: Optional[dict] = None
        # constrained-decoding grammar bank: (G, S, V) int16 token
        # transition tables + (G, S) accept flags, lazily allocated on the
        # first guided request (engine/grammar.py). The FSM advances INSIDE
        # the fused decode loop — zero host round trips per token.
        self.grammar_bank = None
        self.grammar_accept = None

    @staticmethod
    def _refuse_for_recurrent_state(config: EngineConfig, mesh: Mesh,
                                    lora: bool = False) -> None:
        """A model with recurrent (KDA or state-space) layers keeps state
        per decode slot that only the ragged and decode step programs of
        ONE chip carry; one whose window binds keeps blocks of two kinds
        besides. Whatever would split, move, skip or guess at either is
        refused here by name, not served wrongly (``lora``: an adapter is
        being loaded)."""
        name = config.model.name
        refused = [
            what for bad, what in (
                (mesh.devices.size > 1,
                 f"a mesh of {mesh.devices.size} devices (tensor "
                 "parallelism): the recurrent kernels are not partitioned"),
                (config.model.quant is not None,
                 f"quant={config.model.quant}: the recurrent layers' "
                 "projections are not quantized"),
                (lora,
                 "LoRA adapters: the stack walker of a patterned stack "
                 "applies none"),
                (config.scheduler.spec_ngram_k > 0,
                 "n-gram speculative decoding (spec_ngram_k > 0): a "
                 "rejected draft cannot be taken out of the state again"),
                (config.role != "unified",
                 f"role={config.role}: a P->D transfer frames keys and "
                 "values, not recurrent state"),
                (bool(config.cache.host_offload_blocks
                      or config.cache.kv_host_cache_bytes
                      or config.cache.remote_kv_url),
                 "a host or remote KV tier: a block fetched back has no "
                 "recurrent state to resume from"),
            ) if bad]
        if refused:
            raise ValueError(
                f"{name} keeps recurrent state per decode slot; not "
                "supported with it: " + "; ".join(refused))

    @staticmethod
    def _refuse_for_two_pools(config: EngineConfig, mesh: Mesh,
                              lora: bool = False) -> None:
        """A stack of attention layers whose window binds keeps blocks of
        two kinds, each pool with an allocator and a block table of its
        own, that only ONE chip's ragged and decode step programs read and
        write. Whatever would frame, move, draft over or guess at one pool
        as if it were the cache is refused here by name, not served
        wrongly (``lora``: an adapter is being loaded)."""
        name = config.model.name
        refused = [
            what for bad, what in (
                (mesh.devices.size > 1,
                 f"a mesh of {mesh.devices.size} devices (tensor "
                 "parallelism): no test holds two pools sharded"),
                (config.model.quant is not None,
                 f"quant={config.model.quant}: the gate's projection and "
                 "the patterned stack's mixers are not quantized"),
                (lora,
                 "LoRA adapters: the stack walker of a patterned stack "
                 "applies none"),
                (config.scheduler.spec_ngram_k > 0,
                 "n-gram speculative decoding (spec_ngram_k > 0): a draft's "
                 "rows reserve blocks of one pool alone"),
                (config.role != "unified",
                 f"role={config.role}: a P->D transfer frames one pool's "
                 "blocks, and the window layers hold the last rows alone"),
                (bool(config.cache.host_offload_blocks
                      or config.cache.kv_host_cache_bytes
                      or config.cache.remote_kv_url),
                 "a host or remote KV tier: a block fetched back has no "
                 "window rows beside it"),
            ) if bad]
        if refused:
            raise ValueError(
                f"{name} keeps blocks of two kinds (a window pool beside "
                "the full layers' pool); not supported with it: "
                + "; ".join(refused))

    @staticmethod
    def _refuse_for_latent_cache(config: EngineConfig, mesh: Mesh,
                                 lora: bool = False) -> None:
        """A latent-attention (MLA) model keeps one row a token that every
        query head reads, written and read by ONE chip's ragged and decode
        step programs. Whatever would shard, frame, quantize or draft over
        that row in another form is refused here by name, not served
        wrongly (``lora``: an adapter is being loaded)."""
        name = config.model.name
        refused = [
            what for bad, what in (
                (mesh.devices.size > 1,
                 f"a mesh of {mesh.devices.size} devices (tensor "
                 "parallelism): the heads would shard and the one latent "
                 "row would not"),
                (config.model.quant is not None,
                 f"quant={config.model.quant}: the low-rank projections "
                 "and the absorbed expansions are not quantized"),
                (lora,
                 "LoRA adapters: their wq / wk / wv targets do not exist "
                 "on the low-rank query and key-value paths"),
                (config.scheduler.spec_ngram_k > 0,
                 "n-gram speculative decoding (spec_ngram_k > 0): verify "
                 "spans through the latent kernel are not held against "
                 "plain decoding by any test"),
                (config.role != "unified",
                 f"role={config.role}: a P->D transfer frames (2*KH, D) "
                 "slabs of keys and values, not latent rows"),
                (bool(config.cache.host_offload_blocks
                      or config.cache.kv_host_cache_bytes
                      or config.cache.remote_kv_url),
                 "a host or remote KV tier: the tiers hold (2*KH, D) "
                 "slabs of keys and values, not latent rows"),
            ) if bad]
        if refused:
            raise ValueError(
                f"{name} keeps a latent cache (one row a token for all "
                "heads); not supported with it: " + "; ".join(refused))

    def _init_cache(self):
        return kvmod.init_kv_cache(
            self.cfg, self.config.cache, self.mesh, self.rules,
            self.num_blocks, slots=self.config.scheduler.max_num_seqs,
            window_blocks=self.window_blocks)

    def install_compile_observer(self, observer) -> None:
        """Proxy every jitted program through a compile tracker so the
        perf accountant sees one event per (program, argument-signature)
        — i.e. per XLA compile (engine/perf_accounting.py)."""
        from production_stack_tpu.engine.perf_accounting import (
            wrap_runner_programs,
        )

        wrap_runner_programs(self, observer)

    # -- sizing ------------------------------------------------------------
    def _prefill_temp_bytes(self) -> int:
        """Worst-case transient of a ragged step, per attention backend.

        The token budget is the single source of shape truth — the stream
        is at most ``max_num_batched_tokens`` wide (a narrower width of
        ``ragged_stream_widths`` needs less, so the pool is sized for the
        budget). Pallas keeps KV windows in VMEM scratch, so only
        hidden/logits-scale HBM transients remain; the XLA form gathers
        each token's full context."""
        sched = self.config.scheduler
        T = min(sched.max_num_batched_tokens, self.cfg.max_model_len)
        hidden = T * self.cfg.hidden_size * 4
        logits = sched.max_num_seqs * self.cfg.vocab_size * 4
        if self.cfg.is_moe:
            # the MoE block's sorted (token, choice) rows: gathered
            # input, gate, up, their product and the output in the
            # model dtype, the weighted output in float32
            E, F = self.cfg.hidden_size, self.cfg.intermediate_size
            hidden += (T * self.cfg.num_experts_per_tok
                       * (2 * (2 * E + 3 * F) + 4 * E)) // 8
        if self.cfg.has_recurrent_state:
            # a KDA layer's rows: q, k, v before and after the
            # convolution in the model dtype, the five float32 inputs
            # of the recurrence, head-major copies of them for the
            # kernel, its output both ways
            hidden += (T * self.cfg.kda_heads * self.cfg.kda_head_dim
                       * (6 * 2 + 12 * 4)) // 8
            # a state-space layer's rows: [x; z] and the convolved x
            # in the model dtype, x, Delta and y in float32 as the
            # span kernel takes and returns them, the gated product
            hidden += (T * self.cfg.mamba_inner * (4 * 2 + 5 * 4)
                       if self.cfg.mamba_period else 0) // 8
            # a mixer with heads: [z | xBC | dt] and the convolved xBC in
            # the model dtype; x, d x, y and the gated product in float32,
            # head-major copies of d x and y for the span kernel
            hidden += (T * self.cfg.ssd_conv_dim * (4 * 2 + 7 * 4)) // 8
            # a GDN layer's rows: [q | k | v] before and after the
            # convolution and the gate in the model dtype; the five float32
            # inputs of the recurrence, head-major copies of them for the
            # kernel, its output both ways and the gated product
            hidden += (T * self.cfg.gdn_conv_dim * (3 * 2 + 9 * 4)) // 8
        if self.cfg.is_latent:
            # a latent layer's rows of all heads: the two parts of the
            # query, the absorbed query at the pool's lanes, the
            # kernel's output and its expansion to values, the heads'
            # own queries as the kernel's expanded form takes them, in
            # the model dtype; the absorbed query once more in float32
            c = self.cfg
            hidden += (T * c.num_heads * (
                2 * (c.head_dim + c.latent_lanes + c.kv_lora_rank
                     + c.v_head_dim + c.qk_nope_head_dim
                     + c.latent_lanes - c.kv_lora_rank)
                + 4 * c.latent_lanes)) // 8
        if self.use_pallas:
            return int(8 * hidden + 4 * logits)
        ctx = self.cfg.max_model_len
        if self.cfg.is_latent:  # one row of latent_lanes, all heads
            scores = T * ctx * self.cfg.num_heads * 4
            gather = T * ctx * self.cfg.latent_lanes * (2 + 4)
        else:
            scores = (T * ctx * self.cfg.num_kv_heads
                      * self.cfg.q_per_kv * 4)
            gather = (2 * T * ctx * self.cfg.num_kv_heads
                      * self.cfg.head_dim * 2)
        return int(3.5 * scores + 2 * gather + 8 * hidden + 4 * logits)

    def _resolve_num_blocks(self, explicit: Optional[int]) -> int:
        if explicit is not None:
            return explicit
        if self.config.cache.num_blocks > 0:
            return self.config.cache.num_blocks
        per_block = kvmod.kv_cache_bytes_per_block(self.cfg, self.config.cache)
        param_bytes = sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(self.params)
        )
        multihost = jax.process_count() > 1
        stats = None if multihost else jax.local_devices()[0].memory_stats()
        if stats:
            hbm = stats["bytes_limit"]
            used = stats["bytes_in_use"]
        elif multihost or jax.default_backend() == "cpu":
            # multihost: every process must size the SAME pool and local
            # memory_stats can differ across hosts; CPU: the backend
            # reports no stats. Both size deterministically against a
            # v5e-sized 15.75 GiB device holding only the params.
            hbm = int(15.75 * 1024**3)
            used = param_bytes
        else:
            raise RuntimeError(
                f"{jax.default_backend()} device reports no memory_stats(): "
                "cannot size the KV pool — pass --num-blocks")
        # the recurrent layers' per-slot state and the window layers' pool
        # come out of the same memory
        free = (hbm - used - self._prefill_temp_bytes() - 2 * 1024**3
                - self.cfg.recurrent_state_bytes(
                    self.config.scheduler.max_num_seqs)
                - (self.window_blocks * self.config.cache.block_size
                   * self.cfg.window_kv_bytes_per_token))
        n_dev = max(self.mesh.devices.size, 1)
        total_free = free * n_dev  # cache is sharded over the mesh
        kvmod.refuse_if_window_pool_starves(
            self.cfg, self.config.cache, self.config.scheduler, total_free)
        return max(int(total_free * self.config.cache.hbm_utilization) // per_block, 16)

    # -- attention backends -------------------------------------------------
    # ``caches`` is the fused (L, N, bs, 2KH, D) pool riding the layer-scan
    # carry; ONE update per layer at layer_idx keeps the donated pool in
    # place (see kv_cache.py / models/llama.py forward_tokens).
    @property
    def tp(self) -> int:
        """KV shard-grouping factor: the mesh tensor size when KV heads are
        actually sharded, 1 when the rules fell back to replication (GQA
        head counts not divisible — e.g. KH=2 under tensor=4)."""
        from production_stack_tpu.parallel import shardings as ln

        if self.rules.rules.get(ln.KV_HEADS) is None:
            return 1
        return self.mesh.shape[AXIS_TENSOR]

    def _sharded(self, inner):
        """shard_map wrapper over the tensor axis, for the (T, H, D)
        queries of both step forms."""
        if self.tp == 1:
            return inner
        q_spec = P(None, AXIS_TENSOR, None)
        in_specs = (
            q_spec,
            P(None, AXIS_TENSOR, None),  # newkv (T, 2KH, D)
            P(None, None, None, AXIS_TENSOR, None),  # cache
            P(None, None),  # block tables
            P(None),  # context lens
            P(None),  # slot mapping
            P(),  # layer idx
            P(None),  # q_starts / unused
        )
        out_specs = (q_spec, P(None, None, None, AXIS_TENSOR, None))
        # stackcheck: disable=jit-cache-hygiene — _sharded is only ever
        # called at TRACE time inside the jitted step programs (ragged/
        # decode), so the shard_map it builds is baked into the caller's
        # cached trace; no per-dispatch reconstruction happens
        return jax.shard_map(
            inner, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

    def _commit(self, buf: np.ndarray):
        """A step's packed host inputs → device, in ONE transfer. On a
        multi-device mesh the buffer is committed fully replicated: an
        uncommitted host array leaves the placement decision to GSPMD per
        program; committing up front pins the sharded steady-state
        signature — stream replicated, KV/weights partitioned — so TP=4/8
        dispatches retrace exactly as often as single-chip ones (never,
        after warmup). Single chip: a plain put."""
        if self._multi_device:
            return jax.device_put(buf, self._repl)
        return jax.device_put(buf)

    def _optional_inputs(self, presence, frequency, adapter_ids, ctrl,
                         g_ids, g_states) -> dict:
        """The rare per-request inputs (penalties, LoRA ids, token
        controls, grammar state) as arguments of the variants that use
        them. They stay outside the packed buffer — folding them in would
        make its layout depend on the variant — and are copied first: the
        engine rewrites its host arrays in place while a deferred step
        may still be pending, and a jax.Array can alias numpy memory."""
        def dev(x, dtype=None):
            return jnp.asarray(np.array(x, dtype))

        use_lora = adapter_ids is not None and self.lora_bank is not None
        use_grammar = g_ids is not None and self.grammar_bank is not None
        if presence is not None:
            self._ensure_counts()
        return dict(
            token_counts=None if presence is None else self.token_counts,
            presence=None if presence is None else dev(presence),
            frequency=None if presence is None else dev(frequency),
            lora_bank=self.lora_bank if use_lora else None,
            adapter_ids=dev(adapter_ids, np.int32) if use_lora else None,
            ctrl=None if ctrl is None else tuple(dev(c) for c in ctrl),
            grammar=(
                (self.grammar_bank, self.grammar_accept,
                 dev(g_ids, np.int32), dev(g_states, np.int32))
                if use_grammar else None
            ),
            use_penalties=presence is not None,
            use_controls=ctrl is not None,
            use_grammar=use_grammar,
        )

    def _xla_attend(self, q, caches, layer_idx, block_tables, context_lens,
                    q_positions, **window):
        layer = jax.lax.dynamic_index_in_dim(caches, layer_idx, 0, keepdims=False)
        return paged_attention(
            q, layer, block_tables, context_lens, q_positions, tp=self.tp,
            soft_cap=self.cfg.attn_logit_softcap, **window,
        )

    def _attend_kind(self, attend, kind, q, k, v, caches, layer_idx,
                     block_tables, context_lens, q_positions, slot_mapping,
                     *cu_q_lens, window_tables, window_slots):
        """An attention layer of a stack whose layers differ in where their
        keys and values live (``kind``, models/llama.py _forward_hybrid):
        a "swa" layer writes and reads the window pool by its own table,
        within the window; the "full" layer the other pool; a "cross"
        layer reads the full layer's rows there and writes none.
        ``attend`` is the step form's call (``_attend_decode`` /
        ``_attend_ragged``), ``caches`` the whole cache pytree."""
        if kind == "swa":
            pool, tables, slots = "win", window_tables, window_slots
            how = {"window": self.cfg.sliding_window}
        else:
            pool, tables, slots = "kv", block_tables, slot_mapping
            how = {} if kind == "full" else {"write": False}
        out, cache = attend(q, k, v, caches[pool], layer_idx, tables,
                            context_lens, q_positions, slots, *cu_q_lens,
                            **how)
        return out, {**caches, pool: cache}

    def _attend_latent(self, q, rows, caches, layer_idx, block_tables,
                       context_lens, q_positions, slot_mapping, cu_q_lens,
                       expand=None):
        """Latent attention over a packed stream: absorbed queries q
        (T, H, latent_lanes), the tokens' own rows (T, latent_lanes) to
        write first, spans by cu_q_lens (S+1,), or None for the decode
        program's one-token spans, a slot a row. Returns ((T, H,
        kv_lora_rank), caches). The rows go in by the XLA scatter on the
        chip too: a (16, lanes) bf16 block is whole tiles, two tokens a
        sublane, so no DMA writes one token's row, and the scatter keeps
        the donated pool in place (tests/test_kernel_names_v5e.py).

        Two forms of the same scores (models/llama.py _mla_mixer). A
        decode step and the XLA path are absorbed throughout; on the chip
        the decode program's call takes the kernel's decode body (several
        sequences a grid cell, 512-row windows), by the shape of its call
        and nothing else. The ragged program's kernel, given ``expand``
        (the heads' own (T, H, nope) and (T, H, rope) queries, ``W_UK``,
        ``W_UV``), scores the spans of ``EXPAND_ROWS`` query rows or more
        in the published form in the same call, and ``llama.ExpandedRows``
        comes back in the array's place."""
        from production_stack_tpu.ops.paged_attention import (
            latent_ragged_paged_attention,
            write_latent,
        )

        C = self.cfg.kv_lora_rank
        caches = write_latent(caches, layer_idx, rows, slot_mapping)
        if self.use_pallas:
            from production_stack_tpu.ops import (
                latent_paged_attention_pallas as kernel,
            )

            if cu_q_lens is None:
                return kernel.latent_decode_attention_pallas(
                    q, caches, block_tables, context_lens, layer_idx,
                    value_dim=C), caches
            from production_stack_tpu.models.llama import ExpandedRows

            q_nope, q_rope, w_uk, w_uv = expand
            # a head's query as the pool's row lies: [nope; rope; zeros]
            q_own = jnp.concatenate(
                [q_nope, q_rope, jnp.zeros(
                    (*q_rope.shape[:-1],
                     q.shape[-1] - C - q_rope.shape[-1]), q_rope.dtype)],
                axis=-1).swapaxes(0, 1)
            o_lat, o_own, scored = kernel.latent_paged_attention_pallas(
                q, caches, block_tables, cu_q_lens, context_lens, layer_idx,
                value_dim=C, expand=(q_own, w_uk, w_uv,
                                     self.cfg.head_dim ** -0.5))
            return ExpandedRows(o_lat, o_own.swapaxes(0, 1), scored), caches
        S = block_tables.shape[0]
        seq_ids = (jnp.arange(S, dtype=jnp.int32) if cu_q_lens is None
                   else _owning_slots(cu_q_lens, q.shape[0], S))
        layer = jax.lax.dynamic_index_in_dim(caches, layer_idx, 0, False)
        return latent_ragged_paged_attention(
            q, layer, block_tables, context_lens, seq_ids, q_positions,
            value_dim=C), caches

    def _attend_decode(self, q, k, v, caches, layer_idx, block_tables,
                       context_lens, q_positions, slot_mapping, *,
                       window: int = 0, write: bool = True, kind=None,
                       expand=None, **window_inputs):
        """``window`` > 0: the query sees the last ``window`` rows;
        ``write`` False: the keys and values are another layer's, already
        in ``caches``, and k, v are not used; ``expand``: a latent layer's
        published form, which one-token spans never take."""
        if kind is not None:
            return self._attend_kind(
                self._attend_decode, kind, q, k, v, caches, layer_idx,
                block_tables, context_lens, q_positions, slot_mapping,
                **window_inputs)
        if self.cfg.is_latent:
            # one-token spans, a slot a row; an idle slot (context 0,
            # position < 0) fetches nothing
            out, caches = self._attend_latent(
                q[:, 0], k[:, 0, 0], caches, layer_idx, block_tables,
                context_lens, jnp.where(context_lens > 0,
                                        q_positions[:, 0], -1),
                slot_mapping, None)
            return out[:, None], caches
        how = {"window": window} if window else {}
        if not self.use_pallas:
            if write:
                caches = write_kv(caches, layer_idx, k[:, 0], v[:, 0],
                                  slot_mapping, self.tp)
            out = self._xla_attend(q, caches, layer_idx, block_tables,
                                   context_lens, q_positions, **how)
            return out, caches

        from production_stack_tpu.ops.paged_attention_pallas import (
            kv_cache_write_pallas,
            paged_decode_attention_pallas,
        )

        if write:
            newkv = combine_kv(k[:, 0].astype(caches.dtype),
                               v[:, 0].astype(caches.dtype), self.tp)
        else:  # one chip (no shard_map below splits this placeholder)
            newkv = slot_mapping = jnp.zeros((1,), jnp.int32)

        def inner(q3, nk, fused, bt, cl, sm, li, _unused):
            if write:
                fused = kv_cache_write_pallas(fused, nk, sm, li)
            out = paged_decode_attention_pallas(
                q3, fused, bt, cl, li,
                soft_cap=self.cfg.attn_logit_softcap, **how,
            )
            return out, fused

        out, caches = self._sharded(inner)(
            q[:, 0], newkv, caches, block_tables, context_lens, slot_mapping,
            layer_idx, jnp.zeros((1,), jnp.int32),
        )
        return out[:, None], caches

    def _attend_ragged(self, q, k, v, caches, layer_idx, block_tables,
                       context_lens, q_positions, slot_mapping, cu_q_lens, *,
                       window: int = 0, write: bool = True, kind=None,
                       expand=None, **window_inputs):
        """Unified ragged step: q (1, T, H, D) over the packed mixed
        prefill+decode stream; per-slot spans via cu_q_lens (S+1,).
        q_positions (1, T) carries each token's absolute position (-1 pad)
        for the XLA reference path; the Pallas kernel derives positions
        from cu_q_lens/context_lens on its own. ``window``, ``write``: as
        ``_attend_decode``'s."""
        if kind is not None:
            return self._attend_kind(
                self._attend_ragged, kind, q, k, v, caches, layer_idx,
                block_tables, context_lens, q_positions, slot_mapping,
                cu_q_lens, **window_inputs)
        T = q.shape[1]
        if self.cfg.is_latent:
            out, caches = self._attend_latent(
                q[0], k[0, :, 0], caches, layer_idx, block_tables,
                context_lens, q_positions[0], slot_mapping, cu_q_lens,
                expand=expand and (expand[0][0], expand[1][0], *expand[2:]))
            return jax.tree.map(lambda a: a[None], out), caches
        how = {"window": window} if window else {}
        if write:
            k_flat = k.reshape(T, -1, self.cfg.cache_head_dim)
            v_flat = v.reshape(T, -1, self.cfg.cache_head_dim)
        if not self.use_pallas:
            from production_stack_tpu.ops.paged_attention import (
                ragged_paged_attention,
            )

            if write:
                caches = write_kv(caches, layer_idx, k_flat, v_flat,
                                  slot_mapping, self.tp)
            layer = jax.lax.dynamic_index_in_dim(
                caches, layer_idx, 0, keepdims=False
            )
            seq_ids = _owning_slots(cu_q_lens, T, block_tables.shape[0])
            out = ragged_paged_attention(
                q[0], layer, block_tables, context_lens, seq_ids,
                q_positions[0], tp=self.tp,
                soft_cap=self.cfg.attn_logit_softcap, **how,
            )
            return out[None], caches

        from production_stack_tpu.ops.paged_attention_pallas import (
            kv_cache_write_pallas,
        )
        from production_stack_tpu.ops.ragged_paged_attention_pallas import (
            ragged_paged_attention_pallas,
        )

        if write:
            newkv = combine_kv(k_flat.astype(caches.dtype),
                               v_flat.astype(caches.dtype), self.tp)
        else:  # one chip (no shard_map below splits this placeholder)
            newkv = slot_mapping = jnp.zeros((1,), jnp.int32)

        def inner(q3, nk, fused, bt, cl, sm, li, cu):
            if write:
                fused = kv_cache_write_pallas(fused, nk, sm, li)
            out = ragged_paged_attention_pallas(
                q3, fused, bt, cu, cl, li,
                soft_cap=self.cfg.attn_logit_softcap, **how,
            )
            return out, fused

        out, caches = self._sharded(inner)(
            q[0], newkv, caches, block_tables, context_lens, slot_mapping,
            layer_idx, cu_q_lens,
        )
        return out[None], caches

    # -- recurrent (KDA) layers: the stateful call, as _attend_* is an
    # attention layer's. ``caches`` is the hybrid stack's whole cache
    # pytree; "state" and "conv" ride the scan carry like the KV pool and
    # are updated in place at the KDA layer's index
    def _recur(self, ragged: bool, conv_w, qkv, g, beta, caches, k_idx,
               *step_inputs, neg_eigval):
        """The packed stream (``ragged``: qkv (1, T, [q | k | v]),
        ``step_inputs`` the span offsets and context lengths; a span
        continues its slot's state and conv tail, or starts from zeros at
        position 0), or one row a slot (qkv (B, 1, .), ``step_inputs`` the
        live-slot mask; idle slots keep what they hold). One call for both
        delta rules: KDA's (``g`` (.., H, D), a decay a key channel; a
        square state a head) and Gated DeltaNet's (``g`` (.., H, 1), one a
        head; ``ops/gdn.py``'s state of two heads side by side), which
        share the convolutions, ``prepare`` and the span bookkeeping."""
        cfg = self.cfg
        if cfg.gdn_heads:
            from production_stack_tpu.ops import gdn as rule, gdn_pallas

            ragged_kernel, decode_kernel = (gdn_pallas.gdn_ragged,
                                            gdn_pallas.gdn_decode_step)
            split = functools.partial(rule.split_heads, heads=cfg.gdn_heads,
                                      key_dim=cfg.gdn_key_dim)
        else:
            from production_stack_tpu.ops import kda_pallas

            rule = kda
            ragged_kernel, decode_kernel = (kda_pallas.kda_ragged,
                                            kda_pallas.kda_decode_step)
            split = functools.partial(kda.split_heads, heads=g.shape[-2])
        axis = 0 if ragged else 1  # of the axis the step form lacks
        conv, xla, pallas = (
            (kda.conv_ragged, rule.recurrence_ragged, ragged_kernel)
            if ragged else (kda.conv_decode, rule.recurrence_decode,
                            decode_kernel))
        tail = jax.lax.dynamic_index_in_dim(caches["conv"], k_idx, 0, False)
        x, tail = conv(jnp.squeeze(qkv, axis), conv_w, tail, *step_inputs)
        g = jnp.squeeze(g, axis)
        a, *prep = kda.prepare(*split(x), g, jnp.squeeze(beta, axis),
                               neg_eigval)
        if ragged and self.use_pallas:
            # the span kernels sum the log-decay themselves (a ratio of two
            # products of ``a`` would overflow): ops/kda_pallas.py
            a = g.astype(jnp.float32)
        o, state = (pallas if self.use_pallas else xla)(
            caches["state"], k_idx, a, *prep, *step_inputs)
        conv = jax.lax.dynamic_update_index_in_dim(
            caches["conv"], tail, k_idx, 0)
        return (jnp.expand_dims(o, axis),
                {**caches, "state": state, "conv": conv})

    def _state_space(self, forms, mix, caches, idx, step_inputs):
        """A state-space layer's stateful part over this runner's caches:
        ``forms`` = (the convolution, the scan in XLA, the scan's Pallas
        kernel) of the step form, ``mix(conv, scan)`` the family's mixer
        (``ops/mamba.py`` / ``ops/ssd.py`` ``mix``) given the two calls,
        each over its own state at the layer's index ``idx``. Returns (y,
        caches)."""
        conv_impl, xla, pallas = forms
        new = {}

        def conv(x, taps):
            tail = jax.lax.dynamic_index_in_dim(caches["conv"], idx, 0,
                                                False)
            out, new["tail"] = conv_impl(x, taps, tail, *step_inputs)
            return out

        def scan(*rows):
            y, new["state"] = (pallas if self.use_pallas else xla)(
                caches["state"], idx, *rows, *step_inputs)
            return y

        y = mix(conv, scan)
        conv_state = jax.lax.dynamic_update_index_in_dim(
            caches["conv"], new["tail"], idx, 0)
        return y, {**caches, "state": new["state"], "conv": conv_state}

    def _recur_mamba(self, ragged: bool, mp, xs, caches, m_idx,
                     *step_inputs):
        """A state-space layer's stateful call (a ``sambay.MambaFn`` with
        the step's inputs bound behind it), as ``_recur`` is a KDA
        layer's: ``xs`` (1, T, d_i) the packed stream, or (B, 1, d_i) one
        row a slot."""
        from production_stack_tpu.ops import mamba, mamba_pallas

        axis = 0 if ragged else 1  # of the axis the step form lacks
        y, caches = self._state_space(
            (kda.conv_ragged, mamba.scan_ragged, mamba_pallas.mamba_ragged)
            if ragged else (kda.conv_decode, mamba.scan_decode,
                            mamba_pallas.mamba_decode_step),
            lambda conv, scan: mamba.mix(
                mp, jnp.squeeze(xs, axis), self.cfg.mamba_state, conv, scan),
            caches, m_idx, step_inputs)
        return jnp.expand_dims(y, axis), caches

    def _recur_ssd(self, ragged: bool, sp, xbc, dt, caches, idx,
                   *step_inputs):
        """The stateful call of a state-space mixer with heads (a
        ``falcon_h1.SsdFn`` with the step's inputs bound behind it):
        ``xbc`` (1, T, H*P + 2*G*N) and ``dt`` (1, T, H) the packed
        stream, or (B, 1, ...) one row a slot."""
        from production_stack_tpu.ops import ssd, ssd_pallas

        axis = 0 if ragged else 1  # of the axis the step form lacks
        y, caches = self._state_space(
            (kda.conv_ragged, ssd.scan_ragged, ssd_pallas.ssd_ragged)
            if ragged else (kda.conv_decode, ssd.scan_decode,
                            ssd_pallas.ssd_decode_step),
            lambda conv, scan: ssd.mix(
                sp, jnp.squeeze(xbc, axis), jnp.squeeze(dt, axis),
                self.cfg.ssd_heads, self.cfg.ssd_groups, self.cfg.ssd_state,
                conv, scan),
            caches, idx, step_inputs)
        return jnp.expand_dims(y, axis), caches

    # -- public step API (host numpy in, device out) -------------------------
    def _ensure_counts(self):
        if self.token_counts is None:
            with jax.set_mesh(self.mesh):
                self.token_counts = jnp.zeros(
                    (self.config.scheduler.max_num_seqs, self.cfg.vocab_size),
                    jnp.int32,
                )
            # jitted once; compiled per shape, not per call
            self._set_count_row_fn = jax.jit(
                lambda c, slot, row: c.at[slot].set(row),
                donate_argnums=(0,),
            )

    def set_count_row(self, slot: int, token_ids: list[int]) -> None:
        """(Re)build one slot's output-token counts — fresh sequences count
        their prefill-sampled first token; preemption-recompute restores the
        whole history so penalties don't forget."""
        self._ensure_counts()
        row = np.zeros(self.cfg.vocab_size, np.int32)
        for t in token_ids:
            if 0 <= t < self.cfg.vocab_size:
                row[t] += 1
        with jax.set_mesh(self.mesh):
            self.token_counts = self._set_count_row_fn(
                self.token_counts, jnp.asarray(slot, jnp.int32),
                jnp.asarray(row),
            )

    def prepare_decode(self, tokens, positions, block_tables, context_lens,
                       slot_mapping, temps, top_ps, top_ks, seeds, steps,
                       greedy_only: bool = False,
                       presence=None, frequency=None,
                       adapter_ids=None, ctrl=None, tokens_dev: bool = False,
                       g_ids=None, g_states=None,
                       want_logprobs: bool = False, window=None):
        """Pack and commit the inputs of multi_step fused decode+sample
        iterations and return their launch, a call that takes the device
        tokens: everything a decode dispatch costs the host but the launch
        itself, so that the engine does it while the dispatch before
        still runs, and launches (or drops the call) once that one has
        landed. ``tokens_dev``: the launch will be given the previous
        dispatch's device-resident ``next_tok`` as the batch's input
        tokens (no host round trip between two dispatches), which the
        packed buffer says by a flag; without it the packed ``tokens``
        are the input. ``greedy_only`` selects the argmax-only compiled
        variant; presence/frequency arrays activate the penalised variant
        (counts tracked on device); ``want_logprobs`` the variant that
        also returns log-probabilities.

        The launch returns without waiting: ``(sampled (num_steps, B),
        next_tok, counters[, tok_lp (K, B), ids (K, B, N), lps (K, B,
        N)])``, all still on the device. ``counters`` is (the routing
        histogram of an MoE model, the passes a looped stack made), None
        where the model has no such thing; the caller fetches them with
        the sampled tokens and hands them to ``record_counters``.

        The ten always-present inputs reach the device as ONE packed
        buffer in one transfer (``StepLayout``, ``_commit``); packing
        copies them, so the engine may rewrite its host arrays as soon as
        this returns."""
        arrays = (tokens, positions, block_tables, context_lens,
                  slot_mapping, temps, top_ps, top_ks, seeds, steps,
                  np.full(1, tokens_dev, np.int32),
                  *(window or ()))
        layout = StepLayout.of(
            _DECODE_INPUTS + (_WINDOW_INPUTS if window else ()), arrays)
        buf = layout.pack(arrays)
        self.clock.enter("commit")
        with jax.set_mesh(self.mesh):
            opt = self._optional_inputs(presence, frequency, adapter_ids,
                                        ctrl, g_ids, g_states)
            packed = self._commit(buf)

        def launch(device_tokens=None):
            with jax.set_mesh(self.mesh):
                self.clock.launch(**self._launch_attrs)
                (self.kv, new_counts), (sampled, next_tok, *lp) = (
                    self._decode_multi(
                        self.params, self.kv, packed,
                        (self._no_tokens_dev if device_tokens is None
                         else device_tokens),
                        **opt, layout=layout,
                        block_size=self.config.cache.block_size,
                        greedy_only=greedy_only,
                        want_logprobs=want_logprobs))
            if opt["use_penalties"]:
                self.token_counts = new_counts
            return (sampled, next_tok, self._split_counters(lp), *lp)

        return launch

    def ragged_step(self, tokens, positions, block_tables, context_lens,
                    cu_q_lens, slot_mapping, last_idx, sample_mask,
                    temps, top_ps, top_ks, seeds, steps,
                    greedy_only: bool = False,
                    presence=None, frequency=None,
                    adapter_ids=None, ctrl=None,
                    g_ids=None, g_states=None,
                    verify_idx=None,
                    fetch: bool = True, window=None):
        """ONE unified dispatch over the packed mixed prefill+decode stream.

        tokens/positions: (1, T) with T the stream width the engine chose
        for this step, one of ``ragged_stream_widths`` (-1 position = tail
        padding); block_tables (S, M), context_lens (S,), cu_q_lens (S+1,)
        per-slot span offsets in slot order; slot_mapping (T,) flat KV
        slots (-1 = skip); last_idx (S,) stream index of each slot's final
        token (sampling point); sample_mask (S,) 1.0 where the sample is
        actually consumed this step (decode rows + prompt-completing
        chunks) — it gates the on-device penalty-count update only.
        adapter_ids is PER-TOKEN (T,) — spans of different slots can carry
        different adapters in the same stream.

        With speculation compiled in (``spec_width > 0``) ``verify_idx``
        (S, spec_width) carries the stream indices of each slot's draft
        positions (clamped/zero for rows with fewer or no drafts) and the
        result tuple gains the greedy argmax at those positions,
        (S, spec_width), right after ``sampled``; an MoE model appends its
        routing histogram (L, X + 1) and a looped stack its pass count as
        the last leaves, which ``take_counters`` takes off a fetched
        tuple. verify_idx rides EVERY dispatch so verify-bearing steps
        share the one steady-state signature with plain ones.

        Returns (sampled (S,)[, verify (S, W)], tok_lp (S,),
        top_ids (S, N), top_lps (S, N)) on host — or the un-fetched
        device tuple with ``fetch=False`` so the dispatch overlaps the
        host's next-step work. S never changes between dispatches and T
        is one of a few widths (``StepLayout.of`` keys the layout by the
        arrays' shapes, so a width is a signature of the one program):
        ONE steady-state compile signature a width per static-flag
        variant, each compiled by warmup (CompileTracker treats any
        post-warmup fresh signature here as a bug signal).

        The always-present inputs (verify_idx included when compiled in)
        reach the device as ONE packed buffer in one transfer
        (``StepLayout``, ``_commit``); packing copies them, so the engine
        may rewrite its host arrays as soon as this returns.

        Sharded-signature contract (multi-chip mesh): this one program IS
        the multi-chip serving path. Weights and the paged KV pool are
        partitioned over the ``tensor`` axis (KV pages by KV head —
        kv_cache.py); the packed buffer that holds the token stream, span
        offsets, verify columns and every other always-present host-built
        input is committed fully REPLICATED (``_commit``), and the result
        leaves come back replicated (``out_shardings`` gate in
        ``__init__``) so the fetch is a local host copy — no per-step
        cross-chip sync on the host path, and the fused KV-write + verify
        columns run inside the same ``shard_map`` as single-chip. Warmup
        exercises exactly these signatures, so steady state must tick zero
        ``vllm:unexpected_recompiles_total`` at TP=4/8 just as at TP=1
        (regression-tested in tests/test_multichip_ragged.py)."""
        arrays = [tokens, positions, block_tables, context_lens, cu_q_lens,
                  slot_mapping, last_idx, sample_mask, temps, top_ps, top_ks,
                  seeds, steps]
        if self.spec_width > 0:
            arrays.append(
                np.zeros((context_lens.shape[0], self.spec_width), np.int32)
                if verify_idx is None else verify_idx)
        spec = _RAGGED_INPUTS[:len(arrays)]
        if window:  # ``window``: (the window pool's block tables (S, M),
            # its slot mapping): a model whose window binds
            arrays += window
            spec += _WINDOW_INPUTS
        layout = StepLayout.of(spec, arrays)
        buf = layout.pack(arrays)
        self.clock.enter("commit")
        with jax.set_mesh(self.mesh):
            opt = self._optional_inputs(presence, frequency, adapter_ids,
                                        ctrl, g_ids, g_states)
            packed = self._commit(buf)
            self.clock.launch(**self._launch_attrs)
            (self.kv, new_counts), result = self._ragged(
                self.params, self.kv, packed, **opt,
                layout=layout, greedy_only=greedy_only,
            )
        if opt["use_penalties"]:
            self.token_counts = new_counts
        if not fetch:
            return result
        self.clock.wait("ragged")
        return self.take_counters(
            tuple(np.asarray(x) for x in jax.device_get(result)))

    def _split_counters(self, leaves: list) -> tuple:
        """Take what the model's step programs append to their results off
        the end of ``leaves``: (an MoE model's routing histogram, a looped
        stack's pass count), None where the model has no such thing."""
        passes = leaves.pop() if self.loop is not None else None
        moe_hist = leaves.pop() if self.moe is not None else None
        return moe_hist, passes

    def record_counters(self, kind: str, counters: tuple) -> None:
        """Fold one dispatch's fetched ``_split_counters`` pair into the
        always-on counters (engine/tracing.py)."""
        moe_hist, passes = counters
        if moe_hist is not None:
            self.moe.record(kind, moe_hist)
        if passes is not None:
            self.loop.record(passes)

    def take_counters(self, fetched: tuple) -> tuple:
        """A ragged step's results on the host without the counters an MoE
        model or a looped stack appends (recorded here); any other model's
        results as they are."""
        if self.moe is None and self.loop is None:
            return fetched
        leaves = list(fetched)
        self.record_counters("ragged", self._split_counters(leaves))
        return tuple(leaves)

    # -- sleep mode hooks ----------------------------------------------------
    def drop_kv(self) -> None:
        self.kv = None

    def restore_kv(self) -> None:
        if self.kv is None:
            self.kv = self._init_cache()

    def drop_params(self) -> None:
        self.params = None

    def _loaded_params(self) -> dict:
        """The weights from the checkpoint or the seed, quantized where the
        model says so, then in the order of bytes the step programs read
        (engine/weights.py ``lay_out``; a quantized stack stays as it is)."""
        span = self.start.span
        with jax.set_mesh(self.mesh):
            with span("weights.make"):
                params = init_or_load(self.cfg, self.mesh, self.rules,
                                      self.config.seed)
            with span("weights.quantize"):
                params = maybe_quantize(self.cfg, params)
            with span("weights.lay_out"):
                return lay_out(self.cfg, params, self.mesh, self.rules)

    def restore_params(self) -> None:
        if self.params is None:
            self.params = self._loaded_params()

    @property
    def params_alive(self) -> bool:
        return self.params is not None

    @property
    def kv_alive(self) -> bool:
        return self.kv is not None

    # -- dense pooled embedding (the /v1/embeddings surface) ----------------
    def pooled_embed(self, tokens: np.ndarray, mask: np.ndarray) -> np.ndarray:
        """Mean-pooled final hidden state over a dense causal forward."""
        if getattr(self, "_pooled_fn", None) is None:
            from production_stack_tpu.models.llama import dense_attend

            model = self.model
            cfg = self.cfg

            def _embed(params, tokens, mask):
                attend = dense_attend(cfg)

                S = tokens.shape[1]
                positions = jnp.broadcast_to(
                    jnp.arange(S, dtype=jnp.int32), tokens.shape
                )
                hidden, _ = model.forward_tokens(
                    cfg, params, tokens, positions, attend, None
                )
                m = mask[:, :, None].astype(jnp.float32)
                pooled = jnp.sum(hidden.astype(jnp.float32) * m, axis=1)
                return pooled / jnp.maximum(jnp.sum(m, axis=1), 1.0)

            self._pooled_fn = jax.jit(_embed, **self._mh_gate_all)
        with jax.set_mesh(self.mesh):
            out = self._pooled_fn(
                self.params, jnp.asarray(tokens), jnp.asarray(mask)
            )
        return np.asarray(jax.device_get(out))

    # -- teacher-forced sequence scoring (guided choice) ---------------------
    def sequence_logprobs(self, tokens: np.ndarray,
                          cont_mask: np.ndarray) -> np.ndarray:
        """Sum log P(token_j | tokens_<j) over positions where
        ``cont_mask`` is set — the exact score of a continuation given its
        prompt, teacher-forced in one dense causal pass per row.

        tokens: (N, S) int32, 0-padded; cont_mask: (N, S) bool marking the
        CONTINUATION token positions (their probabilities come from the
        logits one position earlier). Returns (N,) float32 sums.
        """
        if getattr(self, "_seqlp_fn", None) is None:
            from production_stack_tpu.models.llama import dense_attend

            model = self.model
            cfg = self.cfg

            def _score(params, tokens, cont_mask):
                attend = dense_attend(cfg)

                S = tokens.shape[1]
                positions = jnp.broadcast_to(
                    jnp.arange(S, dtype=jnp.int32), tokens.shape
                )
                hidden, _ = model.forward_tokens(
                    cfg, params, tokens, positions, attend, None
                )
                logits = model.logits_from_hidden(cfg, params, hidden)
                logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
                tgt = tokens[:, 1:]
                picked = jnp.take_along_axis(
                    logp, tgt[..., None], axis=-1
                )[..., 0]  # (N, S-1): logP of token j+1 given prefix
                return jnp.sum(
                    picked * cont_mask[:, 1:].astype(jnp.float32), axis=-1
                )

            self._seqlp_fn = jax.jit(_score, **self._mh_gate_all)
        with jax.set_mesh(self.mesh):
            out = self._seqlp_fn(
                self.params, jnp.asarray(tokens), jnp.asarray(cont_mask)
            )
        return np.asarray(jax.device_get(out))

    # -- teacher-forced per-position prompt logprobs (completions echo) -----
    def prompt_logprobs(self, tokens: np.ndarray):
        """Per-position next-token logprobs of a prompt, teacher-forced in
        one dense causal pass. tokens (1, S) 0-padded; returns
        (tok_lps (S-1,), top_ids (S-1, N), top_lps (S-1, N)) where row p
        describes position p's prediction of token p+1 (the raw model
        distribution, same convention as generation logprobs). Rows at/past
        the live length are garbage the caller slices off."""
        if getattr(self, "_prompt_lp_fn", None) is None:
            from production_stack_tpu.engine.sampling import compute_logprobs
            from production_stack_tpu.models.llama import dense_attend

            model = self.model
            cfg = self.cfg

            def _score(params, tokens):
                attend = dense_attend(cfg)

                S = tokens.shape[1]
                positions = jnp.broadcast_to(
                    jnp.arange(S, dtype=jnp.int32), tokens.shape
                )
                hidden, _ = model.forward_tokens(
                    cfg, params, tokens, positions, attend, None
                )
                targets = tokens[0, 1:]  # (S-1,)
                # chunked unembedding: per-position map would re-stream the
                # full (E, V) head once per token; per-chunk it reads the
                # head S/C times with a bounded (C, V) logits buffer
                C = min(128, S - 1)
                pad = -(S - 1) % C
                h = jnp.pad(hidden[0, :-1], ((0, pad), (0, 0)))
                t = jnp.pad(targets, (0, pad))
                E = h.shape[-1]

                def one_chunk(args):
                    h_c, t_c = args  # (C, E), (C,)
                    logits = model.logits_from_hidden(
                        cfg, params, h_c[None]
                    )[0]  # (C, V)
                    return compute_logprobs(logits, t_c)

                tok_lp, ids, lps = jax.lax.map(
                    one_chunk, (h.reshape(-1, C, E), t.reshape(-1, C))
                )
                n = tok_lp.shape[0] * C
                return (tok_lp.reshape(n)[: S - 1],
                        ids.reshape(n, -1)[: S - 1],
                        lps.reshape(n, -1)[: S - 1])

            self._prompt_lp_fn = jax.jit(_score, **self._mh_gate_all)
        with jax.set_mesh(self.mesh):
            out = self._prompt_lp_fn(self.params, jnp.asarray(tokens))
        return tuple(np.asarray(x) for x in jax.device_get(out))

    # -- constrained-decoding grammar bank -----------------------------------
    def register_grammar(self, slot: int, fsm) -> None:
        """Upload one TokenFsm's transition table into bank slot ``slot``
        (padded to the configured state budget)."""
        G = self.config.max_grammars
        S = self.config.max_grammar_states
        V = self.cfg.vocab_size
        if fsm.n_states > S:
            raise ValueError(
                f"grammar needs {fsm.n_states} states > budget {S}"
            )
        if self.grammar_bank is None:
            with jax.set_mesh(self.mesh):
                self.grammar_bank = jnp.full((G, S, V), -1, jnp.int16)
                self.grammar_accept = jnp.zeros((G, S), jnp.bool_)
            self._set_grammar_fn = jax.jit(
                lambda b, a, i, t, acc: (b.at[i].set(t), a.at[i].set(acc)),
                donate_argnums=(0, 1),
            )
        table = np.full((S, V), -1, np.int16)
        table[: fsm.n_states] = fsm.trans.astype(np.int16)
        acc = np.zeros(S, bool)
        acc[: fsm.n_states] = fsm.accept
        with jax.set_mesh(self.mesh):
            self.grammar_bank, self.grammar_accept = self._set_grammar_fn(
                self.grammar_bank, self.grammar_accept,
                jnp.asarray(slot, jnp.int32), jnp.asarray(table),
                jnp.asarray(acc),
            )

    # -- multi-LoRA bank -----------------------------------------------------
    def register_lora(self, slot: int, bank_np: dict) -> None:
        """Write an adapter's stacked (A, B) pairs into bank slot ``slot``."""
        if self.cfg.is_latent:
            self._refuse_for_latent_cache(self.config, self.mesh, lora=True)
        if self.cfg.has_recurrent_state:
            self._refuse_for_recurrent_state(self.config, self.mesh,
                                             lora=True)
        elif self.cfg.window_binds:
            self._refuse_for_two_pools(self.config, self.mesh, lora=True)
        N = self.config.max_loras
        dt = self.cfg.jax_dtype
        if self.lora_bank is None:
            self.lora_bank = {}
        with jax.set_mesh(self.mesh):
            for key, (A_st, B_st) in bank_np.items():
                if key not in self.lora_bank:
                    L = A_st.shape[0]
                    self.lora_bank[key] = (
                        jnp.zeros((L, N, *A_st.shape[1:]), dt),
                        jnp.zeros((L, N, *B_st.shape[1:]), dt),
                    )
                A_dev, B_dev = self.lora_bank[key]
                self.lora_bank[key] = (
                    A_dev.at[:, slot].set(jnp.asarray(A_st, dt)),
                    B_dev.at[:, slot].set(jnp.asarray(B_st, dt)),
                )

    def unregister_lora(self, slot: int) -> None:
        if self.lora_bank is None:
            return
        with jax.set_mesh(self.mesh):
            for key, (A_dev, B_dev) in self.lora_bank.items():
                self.lora_bank[key] = (
                    A_dev.at[:, slot].set(0.0),
                    B_dev.at[:, slot].set(0.0),
                )

    # -- KV block export/import (disagg P→D transfer + tier movement) -------
    def _io_fns(self):
        """Jitted whole-layer gather/scatter, cached on self: a fresh
        jax.jit wrapper per call has its own empty trace cache, so every
        tier demotion/prefetch-commit would recompile (~60 ms each — the
        entire warm-tier win). One wrapper reuses traces per block-count."""
        cache = getattr(self, "_io_fn_cache", None)
        if cache is None:
            def _gather(kv, i):
                return kv[:, i]

            def _scatter(kv, i, d):
                return kv.at[:, i].set(d.astype(kv.dtype))

            cache = self._io_fn_cache = (
                jax.jit(_gather, **self._mh_gate_all),
                jax.jit(_scatter, donate_argnums=(0,)),
            )
        return cache

    def export_blocks(self, block_ids: list[int]) -> np.ndarray:
        """Gather blocks out of HBM → host (L, n, bs, 2KH, D) array."""
        idx = jnp.asarray(block_ids, jnp.int32)
        gather_fn, _ = self._io_fns()
        with jax.set_mesh(self.mesh):
            data = gather_fn(self.kv, idx)
        return np.asarray(jax.device_get(data))

    def _range_fns(self, n_layers: int):
        """Jitted export/import for one group size, cached on self — a
        fresh jax.jit wrapper per frame would retrace every dispatch."""
        cache = getattr(self, "_range_fn_cache", None)
        if cache is None:
            cache = self._range_fn_cache = {}
        if n_layers not in cache:
            def _slice(kv, i, lo):
                grp = jax.lax.dynamic_slice_in_dim(kv, lo, n_layers, axis=0)
                return grp[:, i]

            def _scatter(kv, i, d, lo):
                cur = jax.lax.dynamic_slice_in_dim(kv, lo, n_layers, axis=0)
                cur = cur.at[:, i].set(d.astype(kv.dtype))
                return jax.lax.dynamic_update_slice_in_dim(kv, cur, lo,
                                                           axis=0)

            cache[n_layers] = (
                jax.jit(_slice, **self._mh_gate_all),
                jax.jit(_scatter, donate_argnums=(0,)),
            )
        return cache[n_layers]

    def export_blocks_range(self, block_ids: list[int], layer_lo: int,
                            n_layers: int) -> np.ndarray:
        """Gather one layer GROUP of the requested blocks — the unit of the
        chunked streaming transfer (kv_transfer.py): fetching layer groups
        lets device gather, network send, and remote scatter overlap
        instead of serialising a full-pool device_get."""
        idx = jnp.asarray(block_ids, jnp.int32)
        slice_fn, _ = self._range_fns(n_layers)
        with jax.set_mesh(self.mesh):
            data = slice_fn(self.kv, idx, jnp.asarray(layer_lo, jnp.int32))
        return np.asarray(jax.device_get(data))

    def import_blocks(self, block_ids: list[int], data: np.ndarray) -> None:
        """Scatter transferred blocks into this engine's pool (donated)."""
        idx = jnp.asarray(block_ids, jnp.int32)
        _, scatter_fn = self._io_fns()
        with jax.set_mesh(self.mesh):
            self.kv = scatter_fn(self.kv, idx, jnp.asarray(data))

    def import_blocks_range(self, block_ids: list[int], layer_lo: int,
                            data: np.ndarray) -> None:
        """Scatter one streamed layer group into the pool (donated)."""
        idx = jnp.asarray(block_ids, jnp.int32)
        _, scatter_fn = self._range_fns(int(data.shape[0]))
        with jax.set_mesh(self.mesh):
            self.kv = scatter_fn(
                self.kv, idx, jnp.asarray(data),
                jnp.asarray(layer_lo, jnp.int32),
            )

    def sample(self, logits, temps, top_ps, top_ks, seeds, steps) -> np.ndarray:
        with jax.set_mesh(self.mesh):
            toks = self._sample(
                logits, jnp.asarray(temps), jnp.asarray(top_ps),
                jnp.asarray(top_ks), jnp.asarray(seeds), jnp.asarray(steps),
            )
        return np.asarray(jax.device_get(toks))


# ---------------------------------------------------------------------------
# pure device functions (cfg static, attend closed over)
# ---------------------------------------------------------------------------

def _grammar_mask(logits, bank, accept, g_ids, g_states, eos_id):
    """Hard-constrain logits to the FSM's outgoing transitions.

    Rows with g_id < 0 pass through. Returns the masked logits and each
    row's transition row (the sampled token indexes it for the in-loop
    state advance). EOS is allowed exactly in accepting states."""
    from production_stack_tpu.engine.sampling import NEG_INF

    gi = jnp.clip(g_ids, 0, None)
    st = jnp.clip(g_states, 0, None)
    row_t = bank[gi, st]  # (B, V) int16
    allowed = row_t >= 0
    if eos_id is not None:
        allowed = allowed.at[:, eos_id].max(accept[gi, st])
        # dead-end guard: build_token_fsm prunes unreachable-acceptance
        # states, so a fully-masked row should be impossible — but if one
        # ever appears (tokenizer drift vs a cached FSM), degrade to EOS
        # instead of letting argmax silently emit token 0 (r2 advisor)
        dead = ~allowed.any(axis=-1, keepdims=True)
        allowed = allowed | (
            dead & (jnp.arange(allowed.shape[-1]) == eos_id)[None, :]
        )
    con = (g_ids >= 0)[:, None]
    return jnp.where(con & ~allowed, NEG_INF, logits), row_t


def _recur_kw(recur_impl, *step_inputs) -> dict:
    """``forward_tokens``' ``recur`` argument of a hybrid stack: the
    runner's recurrent call with this step's own inputs bound behind the
    model's (an idle-slot mask, or the span offsets); nothing for a model
    without recurrent layers."""
    if recur_impl is None:
        return {}

    def recur(*layer_inputs, **kw):
        return recur_impl(*layer_inputs, *step_inputs, **kw)

    return {"recur": recur}


def _make_lora(lora_bank, adapter_ids, T: int):
    """Build the forward-pass lora pytree (or None)."""
    if lora_bank is None or adapter_ids is None:
        return None
    N = next(iter(lora_bank.values()))[0].shape[1]
    oh = jax.nn.one_hot(adapter_ids, N, dtype=jnp.float32)  # (P, N)
    onehot = jnp.broadcast_to(oh[:, None, :], (oh.shape[0], T, N))
    return {"onehot": onehot, "bank": lora_bank}


def _decode_multi_step(cfg: ModelConfig, attend_impl, num_steps: int, eos_id,
                       params, kv, packed, tokens_dev,
                       token_counts=None, presence=None, frequency=None,
                       lora_bank=None, adapter_ids=None, ctrl=None,
                       grammar=None, *, layout: StepLayout,
                       recur_impl=None, grouped_matmul=None,
                       block_size: int, greedy_only: bool = False,
                       use_penalties: bool = False,
                       use_controls: bool = False,
                       want_logprobs: bool = False,
                       use_grammar: bool = False):
    """``num_steps`` fused decode+sample iterations in ONE dispatch.

    The token sampled at iteration i feeds iteration i+1 entirely on device;
    positions/context lens/slot mappings advance on device too (the host
    pre-allocated ``num_steps`` tokens of block capacity per sequence).
    Amortises host→device dispatch latency — the dominant decode cost on
    single-chip serving. ``packed`` holds the always-present host inputs
    (``layout``, ``_DECODE_INPUTS``); ``tokens_dev`` (B, 1) is the previous
    dispatch's ``next_tok``, read when the packed ``tokens_on_device`` flag
    is set. ``token_counts``/``presence``/``frequency`` exist only under
    ``use_penalties``. Returns ((new_kv, counts), (sampled (num_steps, B),
    next_tok (B, 1)[, logprobs]))."""
    from production_stack_tpu.engine.sampling import sample_tokens
    from production_stack_tpu.models.registry import get_model

    model = get_model(cfg)
    f = layout.unpack(packed)
    block_tables, context_lens = f["block_tables"], f["context_lens"]
    temps, top_ps, top_ks, seeds = (
        f["temps"], f["top_ps"], f["top_ks"], f["seeds"])
    tokens = jnp.where(f["tokens_on_device"] != 0, tokens_dev[:, 0],
                       f["tokens"])
    B = tokens.shape[0]
    active = context_lens > 0
    if use_grammar:
        g_bank, g_accept, g_ids, g_states0 = grammar
    else:
        g_ids = g_states0 = jnp.zeros(B, jnp.int32)  # carry placeholder

    def one(kv, tok, pos, ctx, slots, wslots, step_ctr, counts, g_state):
        def attend(q, k, v, caches, layer_idx, **how):
            return attend_impl(
                q, k, v, caches, layer_idx, block_tables, ctx, pos[:, None],
                slots, **(_window_kw(f, wslots) if "kind" in how else {}),
                **how,
            )

        # idle slots stay out of an MoE model's routing, whose per-layer
        # histogram joins the results, as a looped stack's pass count does;
        # a hybrid stack's recurrent layers leave their state as it is
        hidden, kv, *moe_hist = model.forward_tokens(
            cfg, params, tok[:, None], pos[:, None], attend, kv,
            lora=_make_lora(lora_bank, adapter_ids, 1),
            live=active[:, None], moe_hist=cfg.is_moe,
            loop_count=cfg.loop_passes > 1, grouped_matmul=grouped_matmul,
            **_recur_kw(recur_impl, active),
        )
        logits = model.logits_from_hidden(cfg, params, hidden)[:, 0]
        raw_logits = logits  # logprobs report the raw model distribution
        if use_penalties:
            from production_stack_tpu.engine.sampling import penalize_logits

            logits = penalize_logits(logits, counts, presence, frequency)
        if use_controls:
            from production_stack_tpu.engine.sampling import (
                apply_token_controls,
            )

            logits = apply_token_controls(logits, *ctrl)
        if use_grammar:
            logits, row_t = _grammar_mask(
                logits, g_bank, g_accept, g_ids, g_state, eos_id
            )
        if greedy_only:
            sampled = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            sampled = sample_tokens(logits, temps, top_ps, top_ks, seeds, step_ctr)
        if use_grammar:
            # advance the FSM on device: next dispatch's state comes back
            # through the host mirror, but within this fused loop the
            # transition row the token was sampled FROM defines it
            nxt = jnp.take_along_axis(
                row_t, sampled[:, None], axis=-1
            )[:, 0].astype(jnp.int32)
            g_state = jnp.where((g_ids >= 0) & active, nxt, g_state)
        if want_logprobs:
            from production_stack_tpu.engine.sampling import compute_logprobs

            return kv, g_state, (
                sampled, *compute_logprobs(raw_logits, sampled), *moe_hist)
        return kv, g_state, (sampled, *moe_hist)

    def body(carry, _):
        kv, tok, pos, ctx, slots, wslots, step_ctr, counts, g_state = carry
        kv, g_state, (sampled, *lp) = one(
            kv, tok, pos, ctx, slots, wslots, step_ctr, counts, g_state
        )
        new_pos = jnp.where(active, pos + 1, pos)
        new_ctx = jnp.where(active, ctx + 1, ctx)
        block = block_tables[jnp.arange(B), jnp.clip(new_pos, 0, None) // block_size]
        # positions at/past max_model_len have no allocated slot: the
        # clamped table lookup would alias another position's block, and a
        # stray KV write there would be committed to the prefix cache when
        # the (finishing) sequence's blocks are content-addressed
        valid = active & (new_pos < cfg.max_model_len)
        new_slots = jnp.where(
            valid, block * block_size + new_pos % block_size, -1
        )
        if wslots is not None:  # the window layers' pool, by its own table
            block = f["window_block_tables"][
                jnp.arange(B), jnp.clip(new_pos, 0, None) // block_size]
            wslots = jnp.where(
                valid, block * block_size + new_pos % block_size, -1)
        tok = jnp.where(active, sampled, tok)
        if use_penalties:
            counts = counts.at[jnp.arange(B), sampled].add(
                active.astype(counts.dtype)
            )
        return (
            (kv, tok, new_pos, new_ctx, new_slots, wslots, step_ctr + 1,
             counts, g_state),
            (sampled, *lp),
        )

    init = (kv, tokens, f["positions"], context_lens, f["slot_mapping"],
            f.get("window_slot_mapping"), f["steps"], token_counts, g_states0)
    (kv, _, _, _, _, _, _, counts, _), (sampled, *lp) = jax.lax.scan(
        body, init, None, length=num_steps
    )
    # next_tok comes out of the SAME program: an eager slice on the result
    # would cost extra dispatches on the chained-decode hot path
    next_tok = sampled[-1][:, None]  # (B, 1) input for a chained dispatch
    # sampled: (num_steps, B); lp (when requested): tok_lp (K, B),
    # top_ids (K, B, N), top_lps (K, B, N); then an MoE model's routing
    # histogram (K, L, X + 1), then a looped stack's pass counts (K,)
    return (kv, counts), (sampled, next_tok, *lp)


def _ragged_step(cfg: ModelConfig, attend_impl, eos_id, spec_width, params, kv,
                 packed,
                 token_counts=None, presence=None, frequency=None,
                 lora_bank=None, adapter_ids=None, ctrl=None, grammar=None,
                 *, layout: StepLayout, recur_impl=None, grouped_matmul=None,
                 greedy_only: bool = False,
                 use_penalties: bool = False,
                 use_controls: bool = False,
                 use_grammar: bool = False):
    """The unified mixed prefill+decode step: ONE forward over the packed
    token stream, then one sample per slot at its span's last token.

    ``packed`` holds the always-present host inputs (``layout``,
    ``_RAGGED_INPUTS``), unpacked here: tokens/positions: (1, T); cu_q_lens (S+1,) span offsets in slot order
    (decode rows span 1 token — or 1 + drafts when speculating, prefilling
    slots their chunk, inactive 0); last_idx (S,) stream index of each
    slot's final token; sample_mask (S,) gates the on-device penalty-count
    update to rows whose sample is actually consumed. Logprobs ride every
    dispatch: one (S, V) top-k next to the stream
    forward is noise, and it keeps the want_logprobs compile variant from
    existing on the unified path.

    Speculative verification is fused here (spec_width is a compile-time
    constant from SchedulerConfig.spec_ngram_k, partial-bound at jit
    construction): verify_idx (S, spec_width) indexes the stream at each
    slot's draft positions, and the greedy argmax of the RAW logits there
    joins the result. Raw is correct because only rows without penalties/
    controls/grammar are spec-eligible, and for those sampling is argmax
    of the same raw logits — which is what makes greedy output with
    speculation bit-identical to without. Rows with fewer (or no) drafts
    point verify_idx at harmless in-span indices and the host ignores the
    extra columns. The per-position LM head runs under ``lax.map`` so the
    (S, spec_width, V) logits cube is never materialised.

    Returns ((new_kv, new_counts),
    (sampled (S,)[, verify (S, spec_width)], tok_lp, ids, lps[, an MoE
    model's routing histogram (L, X + 1)][, a looped stack's pass
    count]))."""
    from production_stack_tpu.engine.sampling import (
        compute_logprobs,
        sample_tokens,
    )
    from production_stack_tpu.models.registry import get_model

    model = get_model(cfg)
    f = layout.unpack(packed)
    tokens, positions, last_idx = f["tokens"], f["positions"], f["last_idx"]

    def attend(q, k, v, caches, layer_idx, **how):
        return attend_impl(
            q, k, v, caches, layer_idx, f["block_tables"],
            f["context_lens"], positions, f["slot_mapping"], f["cu_q_lens"],
            **(_window_kw(f) if "kind" in how else {}), **how,
        )

    lora = None
    if lora_bank is not None and adapter_ids is not None:
        # PER-TOKEN adapters: spans of different slots share the stream
        N = next(iter(lora_bank.values()))[0].shape[1]
        onehot = jax.nn.one_hot(adapter_ids, N, dtype=jnp.float32)[None]
        lora = {"onehot": onehot, "bank": lora_bank}
    # an MoE model keeps the stream's padding (position -1) out of its
    # routing and returns a per-layer histogram, which joins the results,
    # as a looped stack's pass count does
    hidden, new_kv, *moe_hist = model.forward_tokens(
        cfg, params, tokens, positions, attend, kv, lora=lora,
        moe_hist=cfg.is_moe, loop_count=cfg.loop_passes > 1,
        grouped_matmul=grouped_matmul,
        **_recur_kw(recur_impl, f["cu_q_lens"], f["context_lens"]),
    )
    last_hidden = jnp.take(hidden[0], last_idx, axis=0)  # (S, E)
    logits = model.logits_from_hidden(cfg, params, last_hidden[:, None])[:, 0]
    raw_logits = logits  # logprobs report the raw model distribution
    if use_penalties:
        from production_stack_tpu.engine.sampling import penalize_logits

        logits = penalize_logits(logits, token_counts, presence, frequency)
    if use_controls:
        from production_stack_tpu.engine.sampling import apply_token_controls

        logits = apply_token_controls(logits, *ctrl)
    if use_grammar:
        # decode rows constrain at their mirrored FSM state; a slot whose
        # prompt completes this step starts at state 0 (host sets g_states)
        g_bank, g_accept, g_ids, g_states = grammar
        logits, _ = _grammar_mask(
            logits, g_bank, g_accept, g_ids, g_states, eos_id
        )
    if greedy_only:
        sampled = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        sampled = sample_tokens(logits, f["temps"], f["top_ps"], f["top_ks"],
                                f["seeds"], f["steps"])
    if use_penalties:
        S = sampled.shape[0]
        token_counts = token_counts.at[jnp.arange(S), sampled].add(
            f["sample_mask"].astype(token_counts.dtype)
        )
    lp = compute_logprobs(raw_logits, sampled)
    if spec_width > 0:
        def one_col(idx):  # (S,) stream indices of draft column j
            h = jnp.take(hidden[0], idx, axis=0)  # (S, E)
            col = model.logits_from_hidden(cfg, params, h[:, None])[:, 0]
            return jnp.argmax(col, axis=-1).astype(jnp.int32)

        verify = jax.lax.map(one_col, f["verify_idx"].T).T  # (S, spec_width)
        return (new_kv, token_counts), (sampled, verify, *lp, *moe_hist)
    return (new_kv, token_counts), (sampled, *lp, *moe_hist)
