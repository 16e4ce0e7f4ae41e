"""Child process: hold the served log-probabilities of the correctness
probe against the plain reference, on the same seeded weights.

Runs after the engine has exited (one process holds the chip at a time),
so the weights made here by the program's own ``weights.init_random``
from the engine's seed are the only copy on the device. Prints one JSON
object: {"max_abs_err": ..., "mean_abs_err": ..., "n_compared": ...,
"worst": {...}, "per_request": [...]}.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model-dir", required=True)
    ap.add_argument("--reference", required=True)
    ap.add_argument("--engine-seed", type=int, required=True)
    ap.add_argument("--tp", type=int, required=True)
    ap.add_argument("--dtype", default=None)
    ap.add_argument("--probe", required=True)
    args = ap.parse_args()

    from production_stack_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax
    import numpy as np

    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.engine.weights import init_random
    from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
    from production_stack_tpu.parallel.shardings import rules_for_model

    ref = importlib.import_module(f"chipbench.reference.{args.reference}")
    with open(os.path.join(args.model_dir, "config.json")) as f:
        hf = json.load(f)
    with open(args.probe) as f:
        probe = json.load(f)
    over = {"dtype": args.dtype} if args.dtype else {}
    cfg = ModelConfig.from_pretrained(args.model_dir, **over)
    mesh = build_mesh(MeshConfig(tensor=args.tp))
    params = init_random(cfg, mesh, rules_for_model(cfg, mesh),
                         args.engine_seed)
    worst, n, total, per_request = {"err": 0.0}, 0, 0.0, []
    for p in probe:
        toks = p["prompt"] + p["tokens"]
        first = len(p["prompt"]) - 1
        want = np.asarray(ref.logprobs(hf, params, toks[:-1], first))
        errs = []
        for j, (tok, lp, top) in enumerate(zip(
                p["tokens"], p["token_logprobs"], p["top_logprobs"])):
            for tid, got in [(tok, lp)] + [(int(t), v) for t, v in top]:
                err = abs(float(want[j, tid]) - got)
                errs.append(err)
                if err > worst["err"]:
                    worst = {"err": err, "request": p["index"], "step": j,
                             "token": tid, "served": got,
                             "reference": float(want[j, tid])}
        n += len(errs)
        total += sum(errs)
        per_request.append({"prompt_tokens": len(p["prompt"]),
                            "steps": len(p["tokens"]), "max": max(errs),
                            "mean": sum(errs) / len(errs)})
    print(json.dumps({"max_abs_err": worst["err"], "n_compared": n,
                      "mean_abs_err": total / max(n, 1),
                      "worst": worst, "per_request": per_request,
                      "platform": jax.devices()[0].platform}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
