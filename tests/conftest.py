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


@pytest.fixture(scope="module", autouse=True)
def compiled_programs_freed_with_their_module():
    """Every program XLA:CPU compiles stays mapped (three mappings a
    module) while a cache holds it, and a process may hold 65,530 mappings
    (``vm.max_map_count``): ``tests/test_serving_path.py`` alone leaves a
    worker at 62,000, and the next file's compile then died inside
    ``backend_compile_and_load`` (PR 59: a segfault or an abort in whatever
    test came next). Instantiated first in its module, so finalised after
    the module's own fixtures have let their engines go."""
    yield
    import gc

    import jax

    jax.clear_caches()
    gc.collect()
