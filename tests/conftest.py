"""Test config: force CPU with 8 virtual devices so every sharding/mesh test
runs without TPU hardware (mirrors the reference's no-GPU router CI,
SURVEY.md §4). Must run before jax is imported anywhere."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / long-compile tests"
    )


@pytest.fixture(scope="session")
def mesh8():
    import jax
    from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

    assert jax.device_count() == 8
    return build_mesh(MeshConfig(data=2, tensor=4))


@pytest.fixture(scope="session")
def tp_mesh():
    from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

    return build_mesh(MeshConfig(tensor=-1))
