"""Two-process multi-host serving harness (CPU, virtual devices).

Runs ONE controller process of a multi-process serving group on the CPU
platform with forced virtual devices — the same shape the chart deploys
on a real multi-host TPU slice (StatefulSet pod ordinal = process id).
The leader builds the full LLMEngine, wraps its runner in
``MirroredRunner`` and generates greedily; followers build the identical
runner shard and replay the step-plan broadcast
(``engine/multihost.py``). The leader prints ``TOKENS <json>`` so the
test can compare against a single-process reference run token by token.

Used by tests/test_multihost.py; also runnable by hand:

    XLA_FLAGS=--xla_force_host_platform_device_count=2 \
    JAX_PLATFORMS=cpu PSTPU_CONTROL_SECRET=dev \
    PSTPU_COORDINATOR=127.0.0.1:19701 PSTPU_NUM_PROCESSES=2 \
    PSTPU_PROCESS_ID=0 PSTPU_CONTROL_PORT=19702 \
    python -m production_stack_tpu.testing.multihost_harness
"""

from __future__ import annotations

import json
import sys


PROMPTS = ([3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8])
MAX_TOKENS = 6


def engine_config():
    from production_stack_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        ModelConfig,
        SchedulerConfig,
    )
    from production_stack_tpu.parallel.mesh import MeshConfig

    # data=-1 absorbs whatever the process group provides: the mesh MUST
    # span every process's devices (a mesh covering only the leader's
    # devices leaves followers with zero addressable shards — replicated
    # outputs included — and their replay fetches fail)
    return EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=128),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=32,
            ),
        mesh=MeshConfig(data=-1, tensor=2),
    )


def generate_greedy(engine) -> dict:
    from production_stack_tpu.engine.sampling import SamplingParams

    sp = SamplingParams(temperature=0.0, max_tokens=MAX_TOKENS,
                        ignore_eos=True)
    for i, toks in enumerate(PROMPTS):
        engine.add_request(f"mh-{i}", prompt_token_ids=list(toks),
                           sampling=sp)
    out: dict = {}

    def drain(what: str) -> None:
        steps = 0
        while engine.has_unfinished() and steps < 64:
            for o in engine.step():
                out.setdefault(o.request_id, []).extend(o.new_token_ids)
            steps += 1
        assert not engine.has_unfinished(), f"{what} did not finish"

    drain("generation")
    # guided decoding exercises the control-plane's richest payload: the
    # TokenFsm (numpy transition tables) crosses the authenticated wire
    # via register_grammar, and per-step FSM states ride every decode plan
    sp_g = SamplingParams(temperature=0.0, max_tokens=4,
                          guided_regex="[ab]+")
    engine.add_request("mh-guided", prompt_token_ids=[5, 3], sampling=sp_g)
    drain("guided generation")
    # KV block export smokes the replicated-output gate on the gather path
    # (disaggregated-prefill's building block under multihost)
    blocks = engine.export_kv([0, 1])
    out["kv-export-shape"] = list(blocks.shape)
    return out


def main() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")

    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.multihost import (
        LeaderBroadcaster,
        MirroredRunner,
        follower_loop,
    )
    from production_stack_tpu.parallel.distributed import (
        DistributedConfig,
        initialize_distributed,
    )

    dist = DistributedConfig.from_env()
    initialize_distributed(dist)
    cfg = engine_config()
    if dist.is_leader:
        engine = LLMEngine(cfg, num_blocks=cfg.cache.num_blocks)
        bcast = LeaderBroadcaster(dist.control_port,
                                  dist.num_processes - 1,
                                  bind_host="127.0.0.1")
        bcast.wait_for_followers()
        engine.runner = MirroredRunner(engine.runner, bcast)
        out = generate_greedy(engine)
        bcast.close()
        print("TOKENS " + json.dumps(out), flush=True)
    else:
        from production_stack_tpu.engine.model_runner import ModelRunner
        from production_stack_tpu.parallel.mesh import build_mesh

        mesh = build_mesh(cfg.mesh)
        runner = ModelRunner(cfg, mesh, None, cfg.cache.num_blocks)
        follower_loop(runner, dist.coordinator_host, dist.control_port)
        print("FOLLOWER DONE", flush=True)
    sys.exit(0)


if __name__ == "__main__":
    main()
