"""Paged KV cache: device-side block pool + host-side block allocator.

TPU-first design:

- The device cache is of three kinds. Latent-attention layers keep one
  row a token, keys and values in one: ``(L, num_blocks, block_size,
  lanes)``, ``lanes`` the latent and the shared rotated key padded to
  whole 128-lane tiles (``ModelConfig.kv_pool_shape`` gives every pool's
  shape). Other attention layers keep paged keys and
  values: one fused array ``(L, num_blocks, block_size, 2*KH, D)`` living
  in HBM (L is ``ModelConfig.cache_layers``: the ATTENTION layers; a
  looped stack keeps a cache layer for every (pass, layer) pair),
  KV-heads sharded over the ``tensor`` mesh axis. Recurrent (KDA) layers
  keep state per decode SLOT, not per token: ``state`` (KDA layers,
  slots, H, d, d) float32 and the short convolution's tail ``conv``
  (KDA layers, slots, K-1, 3*H*d). A model without recurrent layers has
  the paged array alone, as it is; a hybrid stack a dict ``{"kv",
  "state", "conv"}`` (``init_kv_cache``), whose ``"kv"`` is a latent pool
  of the latent-attention layers alone where those are its attention
  layers. State-space (Mamba) layers keep
  ``state`` (layers, slots, N, d_i) float32 and ``conv`` (layers, slots,
  K-1, d_i) the same way, and Gated DeltaNet layers ``state`` (GDN layers,
  slots, H / 2, d_k, 2 d_v) float32, two heads side by side on the lanes
  (ops/gdn.py), and ``conv`` (GDN layers, slots, K-1, 2 H d_k + H d_v).
  Where a model's window binds
  (``ModelConfig.window_binds``) the window layers' keys and values are a
  second pool, ``"win"``, of blocks of the same size from an allocator of
  its own (``window_pool_blocks``): a sequence holds a window block only
  while a row to come can still see it (engine/scheduler.py), and ``"kv"``
  holds the layers whose rows live for the whole context; a stack of
  attention layers alone whose window binds has the dict ``{"kv", "win"}``.
  A slot's state
  belongs to the
  sequence that holds the slot and is taken as zeros by a span that
  starts at position 0, so nothing on the host resets it. Block tables
  and slot mappings are tiny int32 host arrays recomputed each step — all
  device shapes stay static, so the serving step never retraces.
- The allocator runs on host Python (control plane, off the hot device path)
  and implements vLLM-style *prefix caching*: full blocks are content-hashed
  by their token chain; a new request reuses any cached prefix blocks
  (refcount++) and only computes the tail. Hit/query counters feed the
  ``vllm:gpu_prefix_cache_{hits,queries}_total`` metrics the reference router
  scrapes (reference: src/vllm_router/stats/engine_stats.py:63-76).
- Freed blocks with refcount 0 stay in the hash map on an LRU list (the HBM
  tier of the KV-reuse hierarchy; host-DRAM and remote tiers build on the
  same block identity in kv_offload.py).
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from production_stack_tpu.engine.config import CacheConfig, ModelConfig
from production_stack_tpu.parallel import shardings as ln
from production_stack_tpu.parallel.shardings import ShardingRules, logical_to_sharding


def kv_cache_logical_axes():
    # ONE fused (L, N, block, 2*KH, D) array: a token's K+V for all heads is
    # one contiguous (2KH, D) slab — the exact bf16 (16,128) tile at KH=8 —
    # so Pallas writes/reads slice only leading dims and one DMA moves K and
    # V together. A single buffer with a single scatter per layer is also
    # what XLA keeps aliased through a donated scan carry (two buffers or two
    # scatters cost a full pool copy per step; measured v5e). The 2KH dim is
    # shard-grouped [K_s0, V_s0, K_s1, V_s1, ...] so tensor-parallel sharding
    # hands each shard its own [K_local, V_local] halves
    # (see ops/paged_attention.py combine_kv).
    return (ln.LAYERS, ln.KV_BLOCKS, ln.BLOCK, ln.KV_HEADS, ln.HEAD_DIM)


def init_kv_cache(
    model: ModelConfig,
    cache: CacheConfig,
    mesh: Mesh,
    rules: Optional[ShardingRules] = None,
    num_blocks: Optional[int] = None,
    slots: int = 0,
    window_blocks: int = 0,
):
    """Allocate the fused HBM block pool, sharded over the mesh; for a
    model with recurrent layers, the pool and the per-slot state of
    ``slots`` decode slots as {"kv", "state", "conv"}, and where its
    window binds the window layers' pool of ``window_blocks`` as "win"."""
    from production_stack_tpu.parallel.shardings import rules_for_model

    rules = rules or rules_for_model(model, mesh)
    n = num_blocks if num_blocks is not None else cache.num_blocks
    if n <= 0:
        raise ValueError("num_blocks must be resolved before init (see sizing)")
    # KV cache never shards the layer axis onto pipeline stages here; when
    # stage > 1 the per-stage engine owns its own slice of layers.
    # A latent pool, (L, N, block, lanes), is one row a token that every
    # head reads: nothing of it shards (one device, model_runner.py
    # _refuse_for_latent_cache)
    axes = ((None,) * 4 if model.is_latent
            else (None, None, None, ln.KV_HEADS, ln.HEAD_DIM))
    sharding = logical_to_sharding(axes, mesh, rules)
    shape = model.kv_pool_shape(n, cache.block_size)
    dt = model.jax_dtype

    def _pool(shape):
        def _zeros():
            return jnp.zeros(shape, dt)

        # stackcheck: disable=jit-cache-hygiene — one-shot pool
        # allocation at engine startup: the wrapper exists only to apply
        # out_shardings and is called exactly once, so there is no trace
        # cache to lose
        return jax.jit(_zeros, out_shardings=sharding)()

    with jax.set_mesh(mesh):
        pool = _pool(shape)
        if not model.has_recurrent_state:
            if not model.window_binds:
                return pool
            # attention layers of two kinds and no per-slot state: the two
            # pools, sharded alike
            return {"kv": pool, "win": _pool(model.kv_pool_shape(
                window_blocks, cache.block_size, window=True))}
        if slots <= 0:
            raise ValueError("a recurrent-state model needs its slot count")
        if model.mamba_period:
            lm, di = model.count_layers("mamba"), model.mamba_inner
            caches = {
                "kv": pool,
                "state": jnp.zeros((lm, slots, model.mamba_state, di),
                                   jnp.float32),
                "conv": jnp.zeros((lm, slots, model.mamba_conv - 1, di), dt),
            }
            if model.window_binds:
                caches["win"] = jnp.zeros(model.kv_pool_shape(
                    window_blocks, cache.block_size, window=True), dt)
            return caches
        if model.ssd_heads:
            ls = model.count_layers("parallel")
            return {
                "kv": pool,
                "state": jnp.zeros((ls, slots, model.ssd_heads,
                                    model.ssd_state, model.ssd_head_dim),
                                   jnp.float32),
                "conv": jnp.zeros((ls, slots, model.ssd_conv - 1,
                                   model.ssd_conv_dim), dt),
            }
        if model.gdn_heads:
            # two heads side by side on the lanes (ops/gdn.py: 2 x 192 =
            # three whole lane tiles where a head alone would pad to 256)
            lg, h = model.count_layers("gdn"), model.gdn_heads
            return {
                "kv": pool,
                "state": jnp.zeros((lg, slots, h // 2, model.gdn_key_dim,
                                    2 * model.gdn_value_dim), jnp.float32),
                "conv": jnp.zeros((lg, slots, model.gdn_conv - 1,
                                   model.gdn_conv_dim), dt),
            }
        h, d = model.kda_heads, model.kda_head_dim
        lk = model.num_kda_layers
        return {
            "kv": pool,
            "state": jnp.zeros((lk, slots, h, d, d), jnp.float32),
            "conv": jnp.zeros((lk, slots, model.kda_conv - 1, 3 * h * d), dt),
        }


def kv_cache_bytes_per_block(model: ModelConfig, cache: CacheConfig) -> int:
    return cache.block_size * model.kv_bytes_per_token


def window_pool_blocks(model: ModelConfig, block_size: int, slots: int,
                       token_budget: int) -> int:
    """Blocks of the window layers' pool, from the configuration alone: a
    sequence that computes rows ``c .. c + n`` holds the blocks from the
    one with row ``c - (window - 1)`` to the one with row ``c + n``, at
    most ``(n + window) / block + 2``; a step's ``n`` add up to the token
    budget at most. So every slot can hold its ``window / block + 2`` (and
    one spare) and a step its chunks whatever the others hold: no sequence
    ever waits for a window block. 0 where no window binds."""
    if not model.window_binds:
        return 0
    per_slot = -(-model.sliding_window // block_size) + 3
    return slots * per_slot + -(-(token_budget + model.sliding_window)
                                // block_size)


def refuse_if_window_pool_starves(model: ModelConfig, cache: CacheConfig,
                                  sched, free_bytes: int) -> None:
    """``free_bytes``: what is left for the other pool once the window
    layers' pool (``window_pool_blocks``: sized by the rule that no
    sequence ever waits for a window block) is taken. Where that holds
    under one sequence of ``max_model_len`` tokens, the engine would start
    and preempt or starve every long request: refused by name, with both
    pools' bytes and the slot count that would fit. Nothing where no
    window binds."""
    if not model.window_binds:
        return
    bs, slots = cache.block_size, sched.max_num_seqs
    budget = sched.max_num_batched_tokens

    def window_bytes(s: int) -> int:
        return (window_pool_blocks(model, bs, s, budget) * bs
                * model.window_kv_bytes_per_token)

    # one sequence's blocks of the other pool, before hbm_utilization
    need = int(-(-model.max_model_len // bs) * bs * model.kv_bytes_per_token
               / cache.hbm_utilization)
    if free_bytes >= need:
        return
    before = free_bytes + window_bytes(slots)  # what both pools share
    fit = max((s for s in range(1, slots) if before - window_bytes(s) >= need),
              default=0)
    raise ValueError(
        f"{model.name}: at --max-num-seqs {slots} the window layers' pool "
        f"takes {window_bytes(slots) / 1e9:.2f} GB ({slots} slots x "
        f"({model.sliding_window} / {bs} + 3) blocks + a step's "
        f"{budget} + {model.sliding_window} rows, "
        f"{model.window_kv_bytes_per_token} B a row: no sequence ever waits "
        "for a window block) and leaves the other layers' pool "
        f"{max(free_bytes, 0) / 1e9:.2f} GB, under the {need / 1e9:.2f} GB "
        f"of one sequence of --max-model-len {model.max_model_len}; "
        + (f"--max-num-seqs {fit} would fit" if fit else
           "no slot count fits: lower --max-model-len"))


def resolve_num_blocks(
    model: ModelConfig, cache: CacheConfig, hbm_free_bytes: int
) -> int:
    usable = int(hbm_free_bytes * cache.hbm_utilization)
    return max(usable // kv_cache_bytes_per_block(model, cache), 16)


# ---------------------------------------------------------------------------
# Host-side allocator with prefix caching
# ---------------------------------------------------------------------------

_HASH_SEED = 0x9E3779B97F4A7C15


def _chain_hash(prev: int, tokens: tuple[int, ...]) -> int:
    return hash((prev, tokens)) & 0x7FFFFFFFFFFFFFFF


@dataclasses.dataclass
class Block:
    block_id: int
    ref_count: int = 0
    content_hash: Optional[int] = None  # set only for full, hashable blocks


class PrefixCachingBlockAllocator:
    """Block pool with content-hash prefix reuse and LRU eviction.

    Semantics mirror what the reference stack *measures* (prefix-cache hit
    counters) and what its prefix/KV-aware routing exists to exploit
    (SURVEY.md §5.7): same-prefix requests landing on this engine skip
    recompute for every full cached block.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 enable_prefix_caching: bool = True,
                 bypass_prefix: bool = False):
        self.num_blocks = num_blocks
        self.block_size = block_size
        # ``bypass_prefix``: the model keeps recurrent state per slot, and
        # no cached block holds the state at its boundary, so a sequence
        # cannot resume behind a cached prefix: every lookup is answered
        # "miss" (and counted), nothing is content-addressed
        self.bypass_prefix = bypass_prefix
        self.lookups_bypassed = 0
        self.enable_prefix_caching = (enable_prefix_caching
                                      and not bypass_prefix)
        self.blocks = [Block(i) for i in range(num_blocks)]
        self.free_ids: collections.deque[int] = collections.deque(range(num_blocks))
        self.hash_to_block: dict[int, int] = {}
        self.lru: collections.OrderedDict[int, None] = collections.OrderedDict()
        # metrics
        self.prefix_queries = 0
        self.prefix_hits = 0
        self.evictions = 0
        # demotion hook, fired with (block_id, content_hash) while the
        # evicted block's KV is still intact in HBM — the engine exports
        # the slab to the host tier here (eviction becomes demotion). The
        # hook must not allocate from this pool (it only reads the device
        # block and writes host-side dicts).
        self.evict_hook = None

    # -- internals ---------------------------------------------------------
    def _evict_one(self) -> bool:
        if not self.lru:
            return False
        bid, _ = self.lru.popitem(last=False)
        blk = self.blocks[bid]
        assert blk.ref_count == 0
        if blk.content_hash is not None:
            if self.evict_hook is not None:
                self.evict_hook(bid, blk.content_hash)
            self.hash_to_block.pop(blk.content_hash, None)
            blk.content_hash = None
        self.free_ids.append(bid)
        self.evictions += 1
        return True

    def _pop_free(self) -> Optional[int]:
        if not self.free_ids and not self._evict_one():
            return None
        bid = self.free_ids.popleft()
        blk = self.blocks[bid]
        blk.ref_count = 1
        blk.content_hash = None
        return bid

    def _take_cached(self, bid: int) -> None:
        blk = self.blocks[bid]
        if blk.ref_count == 0:
            self.lru.pop(bid, None)
        blk.ref_count += 1

    # -- public API --------------------------------------------------------
    @property
    def num_free_blocks(self) -> int:
        return len(self.free_ids) + len(self.lru)

    @property
    def usage(self) -> float:
        return 1.0 - self.num_free_blocks / max(self.num_blocks, 1)

    def match_prefix(self, tokens: Sequence[int]) -> tuple[list[int], int]:
        """Longest chain of cached full blocks for this token sequence.
        Returns (block_ids, num_cached_tokens). Does not take references."""
        if not self.enable_prefix_caching:
            return [], 0
        matched: list[int] = []
        prev = _HASH_SEED
        n_full = len(tokens) // self.block_size
        for i in range(n_full):
            chunk = tuple(tokens[i * self.block_size : (i + 1) * self.block_size])
            prev = _chain_hash(prev, chunk)
            bid = self.hash_to_block.get(prev)
            if bid is None:
                break
            matched.append(bid)
        return matched, len(matched) * self.block_size

    def allocate_sequence(
        self, tokens: Sequence[int]
    ) -> Optional[tuple[list[int], int]]:
        """Allocate blocks to cover ``tokens`` (a prompt), reusing cached
        prefix blocks. Returns (block_ids, num_cached_tokens) or None if out
        of blocks (caller preempts/queues). At least one token is always left
        uncached so the forward pass emits a next-token logit."""
        needed_blocks = max((len(tokens) + self.block_size - 1) // self.block_size, 1)
        self.lookups_bypassed += self.bypass_prefix
        matched, cached_tokens = self.match_prefix(tokens)
        self.prefix_queries += len(tokens) // self.block_size
        # never treat the whole prompt as cached: recompute the last token
        max_matched = max((len(tokens) - 1) // self.block_size, 0)
        matched = matched[:max_matched]
        cached_tokens = len(matched) * self.block_size
        self.prefix_hits += len(matched)

        fresh_needed = needed_blocks - len(matched)
        if fresh_needed > self.num_free_blocks:
            return None
        for bid in matched:
            self._take_cached(bid)
        block_ids = list(matched)
        for _ in range(fresh_needed):
            bid = self._pop_free()
            if bid is None:  # shouldn't happen after the check above
                self.free_blocks(block_ids)
                return None
            block_ids.append(bid)
        return block_ids, cached_tokens

    def append_block(self) -> Optional[int]:
        """One more block for a growing (decoding) sequence."""
        return self._pop_free()

    def take_free_blocks(self, n: int) -> Optional[list[int]]:
        """n fresh blocks (refcount 1) for KV import; None if unavailable."""
        if n > self.num_free_blocks:
            return None
        out = []
        for _ in range(n):
            bid = self._pop_free()
            if bid is None:
                self.free_blocks(out)
                return None
            out.append(bid)
        return out

    def commit_full_blocks(
        self, tokens: Sequence[int], block_ids: Sequence[int]
    ) -> None:
        """Register content hashes for every now-full block of a sequence so
        future requests can prefix-match them."""
        if not self.enable_prefix_caching:
            return
        prev = _HASH_SEED
        n_full = len(tokens) // self.block_size
        for i in range(min(n_full, len(block_ids))):
            chunk = tuple(tokens[i * self.block_size : (i + 1) * self.block_size])
            prev = _chain_hash(prev, chunk)
            blk = self.blocks[block_ids[i]]
            if blk.content_hash is None and prev not in self.hash_to_block:
                blk.content_hash = prev
                self.hash_to_block[prev] = blk.block_id

    def pin_blocks(self, block_ids: Sequence[int]) -> None:
        """Take a reference on blocks (e.g. for the duration of a streamed
        KV export) so eviction/reallocation can't tear the data mid-use.
        Release with free_blocks."""
        for bid in block_ids:
            self._take_cached(bid)

    def free_blocks(self, block_ids: Sequence[int]) -> None:
        for bid in block_ids:
            blk = self.blocks[bid]
            blk.ref_count -= 1
            assert blk.ref_count >= 0, f"double free of block {bid}"
            if blk.ref_count == 0:
                if blk.content_hash is not None:
                    self.lru[bid] = None  # reusable, evictable
                else:
                    self.free_ids.append(bid)

    def reset_metrics(self) -> tuple[int, int]:
        h, q = self.prefix_hits, self.prefix_queries
        return h, q


def slot_mapping_for(
    block_ids: Sequence[int], start: int, count: int, block_size: int
) -> np.ndarray:
    """Flat cache-slot index (block*block_size + offset) for token positions
    [start, start+count) of a sequence."""
    positions = np.arange(start, start + count)
    blocks = np.asarray(block_ids, np.int32)[positions // block_size]
    return (blocks * block_size + positions % block_size).astype(np.int32)
