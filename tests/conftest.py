"""Test configuration: force an 8-device virtual CPU mesh before jax import.

Mirrors the reference's multi-node-less testing gap (SURVEY.md §4): the engine's
multi-chip sharding logic is exercised on a virtual device mesh
(``xla_force_host_platform_device_count``) so no TPU pod is needed for CI.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_enable_x64", True)

# Persist XLA compilations across test sessions: the engine jit-compiles its
# kernels per shape bucket, and tiny-SF tests revisit the same buckets. Same
# placement as every entry point (config.compile_cache_dir).
from nds_tpu.config import maybe_enable_compile_cache  # noqa: E402

maybe_enable_compile_cache()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _isolate_shared_programs():
    """Tests register different data under identical table names/schemas;
    cross-session program adoption would couple their capacity schedules.
    Correctness would survive (schedule checks re-record on drift) but test
    expectations about compile modes would not — keep cases independent."""
    from nds_tpu.engine.jax_backend.executor import clear_shared_programs
    clear_shared_programs()
    yield
    clear_shared_programs()
