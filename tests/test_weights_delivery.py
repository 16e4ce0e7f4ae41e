"""Weight delivery tier: Orbax checkpoint save/restore-sharded, the
downloader one-shot + sidecar service, and the chart's modelURI wiring
(reference: scripts/huggingface_downloader.py + PVC/NFS mounts there)."""

import asyncio
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))



def test_orbax_roundtrip_sharded(tmp_path, mesh8):
    import dataclasses

    import jax

    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.engine.weights import (
        init_or_load, load_orbax, save_orbax,
    )
    from production_stack_tpu.parallel.shardings import rules_for_model

    cfg = dataclasses.replace(
        ModelConfig.from_pretrained("tiny-llama"),
        weights_path=None,
    )
    rules = rules_for_model(cfg, mesh8)
    with jax.set_mesh(mesh8):
        params = init_or_load(cfg, mesh8, rules, seed=3)
    path = str(tmp_path / "ckpt")
    save_orbax(params, path)
    assert os.path.isfile(os.path.join(path, "_CHECKPOINT_METADATA"))

    restored = load_orbax(cfg, mesh8, rules, path)
    flat_a = jax.tree_util.tree_leaves(params)
    flat_b = jax.tree_util.tree_leaves(restored)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert b.sharding == a.sharding  # restored INTO the mesh shardings

    # init_or_load auto-detects the checkpoint directory
    cfg2 = dataclasses.replace(cfg, weights_path=path)
    auto = init_or_load(cfg2, mesh8, rules)
    np.testing.assert_array_equal(
        np.asarray(jax.tree_util.tree_leaves(auto)[0]),
        np.asarray(flat_a[0]),
    )


def test_downloader_oneshot_local_and_idempotent(tmp_path):
    from scripts.model_downloader import download

    src = tmp_path / "src"
    src.mkdir()
    (src / "model.safetensors").write_bytes(b"weights")
    (src / "config.json").write_text("{}")
    dest = tmp_path / "dest"

    out = download(f"file://{src}", str(dest))
    assert (dest / "model.safetensors").read_bytes() == b"weights"
    assert (dest / ".ready").exists()

    # idempotent: marker short-circuits (source removed, still succeeds)
    (src / "model.safetensors").unlink()
    assert download(f"file://{src}", str(dest)) == out


def test_downloader_missing_source_errors(tmp_path):
    from scripts.model_downloader import DownloadError, download

    with pytest.raises(DownloadError):
        download("file:///nonexistent/path", str(tmp_path / "d"))


def test_downloader_sidecar_service(tmp_path):
    from scripts.model_downloader import build_app

    async def main():
        from aiohttp.test_utils import TestClient, TestServer

        src = tmp_path / "hub"
        src.mkdir()
        (src / "w.bin").write_bytes(b"x" * 10)
        async with TestClient(TestServer(build_app(str(tmp_path)))) as c:
            r = await c.get("/health")
            assert r.status == 200
            r = await c.post("/model/download",
                             json={"uri": f"file://{src}",
                                   "local_dir": "m1"})
            assert r.status == 200, await r.text()
            assert (tmp_path / "m1" / "w.bin").exists()
            # path traversal rejected — including the sibling-dir bypass
            # of a bare prefix check (/models -> /models-evil)
            for evil in ("../../etc", f"../{tmp_path.name}-evil"):
                r = await c.post("/model/download",
                                 json={"uri": f"file://{src}",
                                       "local_dir": evil})
                assert r.status == 400, evil

    asyncio.run(main())


def _render_engine(model_uri):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    from minihelm import render_objects

    HELM = os.path.join(os.path.dirname(__file__), "..", "helm")
    objs = render_objects(HELM, {
        "servingEngineSpec": {"modelSpec": [{
            "name": "llama3-8b",
            "modelRef": "llama-3-8b",
            "modelURI": model_uri,
            "replicaCount": 1,
            "tpu": {"accelerator": "tpu-v5-lite-podslice",
                    "topology": "2x4", "chips": 8},
            "engineConfig": {"maxModelLen": 8192, "maxNumSeqs": 64,
                             "dtype": "bfloat16", "tensorParallelSize": 8},
        }]},
    })
    eng = [o for o in objs if o.get("kind") == "Deployment"
           and o["metadata"]["labels"].get("app.kubernetes.io/component")
           == "serving-engine"][0]
    return eng["spec"]["template"]["spec"]


def test_chart_hf_uri_renders_init_container():
    pod = _render_engine("hf://meta-llama/Llama-3.1-8B")
    init = pod["initContainers"][0]
    assert init["name"] == "model-downloader"
    assert init["args"] == ["--uri", "hf://meta-llama/Llama-3.1-8B",
                            "--dest", "/models/llama3-8b"]
    assert init["imagePullPolicy"]
    assert init["volumeMounts"][0]["mountPath"] == "/models"
    args = pod["containers"][0]["args"]
    assert args[args.index("--model") + 1] == "/models/llama3-8b"
    vol = next(v for v in pod["volumes"] if v["name"] == "models")
    assert "emptyDir" in vol  # no PVC configured: per-pod staging


def test_chart_gcs_uri_passes_through_unstaged():
    """gs:// Orbax checkpoints restore sharded straight from the bucket —
    no downloader init container, no staging volume."""
    pod = _render_engine("gs://my-bucket/llama3-8b-orbax")
    assert "initContainers" not in pod
    args = pod["containers"][0]["args"]
    assert args[args.index("--model") + 1] == "gs://my-bucket/llama3-8b-orbax"
    assert not any(v["name"] == "models" for v in pod.get("volumes", []))
