"""Bring-up contracts (PR 21): nothing hides the device, one process per
chip, a compile cache placed from outside. Pure-function and monkeypatch
tests — no device, no warehouse, near-free."""
import json
import os
import subprocess
import sys
import time

import jax
import pyarrow as pa
import pytest

from nds_tpu import config
from nds_tpu.config import EngineConfig
from nds_tpu.engine import Session
from nds_tpu.engine.jax_backend.executor import CompiledQuery, NotJittable
from nds_tpu.resilience import (ChipPlacementError, RetryPolicy,
                                check_child_placement)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402  (stdlib only: the smoke's parent never imports jax)


# -- compile-cache placement --------------------------------------------------

def test_cache_dir_comes_from_outside_or_the_checkout():
    assert config.compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/x"}) \
        is None
    assert config.compile_cache_dir({}) == os.path.join(REPO, ".jax_cache")
    # no second way to name a directory
    assert config.compile_cache_dir({"NDS_TPU_COMPILE_CACHE": "/y"}) == \
        os.path.join(REPO, ".jax_cache")


def test_env_placed_cache_sets_no_directory_in_code(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    monkeypatch.delenv("NDS_TPU_COMPILE_CACHE", raising=False)
    config.maybe_enable_compile_cache()
    assert calls and "jax_compilation_cache_dir" not in dict(calls)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    calls.clear()
    config.maybe_enable_compile_cache()
    assert dict(calls)["jax_compilation_cache_dir"] == \
        os.path.join(REPO, ".jax_cache")


def test_cache_opt_out_mints_no_directory(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("NDS_TPU_COMPILE_CACHE", "0")
    config.maybe_enable_compile_cache()
    assert calls == [("jax_enable_compilation_cache", False)]
    assert os.listdir(tmp_path) == []
    monkeypatch.setenv("NDS_TPU_COMPILE_CACHE", "./mycache")
    with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
        config.maybe_enable_compile_cache()


def test_record_backend_is_kept_whatever_the_platform_list():
    w = config.with_host_platform
    assert w(None) is None and w("") == ""
    assert w("tpu") == "tpu,cpu"
    assert w("tpu,cpu") == "tpu,cpu" and w("cpu") == "cpu"


# -- no CPU rescue of failed device programs ---------------------------------

@pytest.fixture()
def recorded():
    """A session whose query has its schedule recorded but not compiled."""
    s = Session(EngineConfig())
    s.register_arrow("t", pa.table({"k": [1, 2, 1, 3], "v": [10, 20, 30, 40]}))
    q = "SELECT k, SUM(v) AS s FROM t GROUP BY k ORDER BY k"
    s.sql(q, backend="jax")
    assert s.last_exec_stats["mode"] == "record"
    return s, q


def test_device_runtime_error_reaches_the_runner(recorded, monkeypatch):
    s, q = recorded
    runs = []

    def boom(self, *a, **k):
        runs.append(1)
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of HBM")
    monkeypatch.setattr(CompiledQuery, "run", boom)
    monkeypatch.setattr(type(s._jax_executor()), "_eager_ent",
                        lambda *a: pytest.fail("answered from the host"))
    with pytest.raises(jax.errors.JaxRuntimeError, match="out of HBM"):
        s.sql(q, backend="jax")
    assert runs == [1]          # no blind second attempt either


def test_untraceable_plan_is_a_visible_fallback(recorded, monkeypatch):
    s, q = recorded

    def refuse(self, *a, **k):
        raise NotJittable("needs host data")
    monkeypatch.setattr(CompiledQuery, "run", refuse)
    for _ in range(2):          # the sighting that finds out, and the next
        assert s.sql(q, backend="jax").num_rows == 3
        assert s.last_exec_stats["mode"] == "eager"
        assert "needs host data" in s.last_exec_stats["nojit_reason"]
        # what --strict looks at
        assert any(f.startswith("nojit:") for f in s.last_fallbacks)


# -- one process for each chip ------------------------------------------------

def test_process_mode_refuses_up_front_without_a_chip_per_child(monkeypatch):
    from nds_tpu.throughput import run_throughput
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(ChipPlacementError, match="one process at a time"):
        run_throughput("/nonexistent", "/nonexistent", [1, 2],
                       "/nonexistent/logs", mode="process")
    assert not os.path.exists("/nonexistent")
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(ChipPlacementError):
        check_child_placement("x")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    check_child_placement("x")      # children pinned to the host: fine
    # a property of the launch environment: retrying cannot help
    assert RetryPolicy(max_attempts=3).classify(
        ChipPlacementError("x")) == "fatal"


# -- chip_smoke.py ------------------------------------------------------------

def test_chip_smoke_refuses_the_cpu_before_generating_anything(tmp_path):
    out = tmp_path / "out"
    t0 = time.monotonic()
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py"),
                        "--out", str(out)], capture_output=True, text=True,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=60)
    assert p.returncode != 0
    assert time.monotonic() - t0 < 10        # ~0.1 s: no jax import at all
    assert not out.exists() and '"ok"' not in p.stdout


def _summary(**over):
    s = {"queryStatus": ["Completed"], "exceptions": [], "taskFailures": [],
         "env": {"host": {"jax_backend": "tpu"}},
         "execStats": [{"mode": "compiled", "device_ms": 12.5}]}
    s.update(over)
    return s


@pytest.mark.parametrize("bad, why", [
    ({"queryStatus": ["Failed"], "exceptions": ["boom"]}, "status Failed"),
    ({"queryStatus": ["CompletedWithTaskFailures"],
      "taskFailures": ["device fallback: nojit: x"]}, "taskFailures"),
    ({"execStats": [{"mode": "eager", "nojit_reason": "x"}]}, "mode 'eager'"),
    ({"execStats": [{"mode": "record"}]}, "mode 'record'"),
    ({"execStats": [{"mode": "compiled",
                     "fallback_reasons": ["SortNode: y"]}]}, "left the device"),
    ({"env": {"host": {"jax_backend": "cpu"}}}, "backend 'cpu'"),
])
def test_chip_smoke_fails_a_unit_that_left_the_device(tmp_path, bad, why):
    def write(unit, summary):
        with open(tmp_path / f"power-{unit}-1.json", "w") as f:
            json.dump(summary, f)
    write("query3", _summary())
    write("query7", _summary())
    assert chip_smoke.check_summaries(str(tmp_path), ["query3", "query7"],
                                      "compiled") == []
    write("query7", _summary(**bad))
    problems = chip_smoke.check_summaries(str(tmp_path),
                                          ["query3", "query7"], "compiled")
    assert problems and all(p.startswith("query7") for p in problems)
    assert any(why in p for p in problems), problems
    # a unit with no summary at all, and a streamed unit that uploaded nothing
    assert chip_smoke.check_summaries(str(tmp_path), ["query9"], "compiled")
    write("query9", _summary(execStats=[{"mode": "streaming"}]))
    assert any("streamed no bytes" in p for p in chip_smoke.check_summaries(
        str(tmp_path), ["query9"], "streaming"))


def test_chip_smoke_counts_validate_skips_as_misses():
    ok = "query3: Pass\n2 passed, 0 failed, 0 skipped\n"
    assert chip_smoke.check_validate(ok, 2) == []
    assert chip_smoke.check_validate(ok, 3)
    assert chip_smoke.check_validate("1 passed, 0 failed, 1 skipped", 2)
    assert chip_smoke.check_validate("1 passed, 1 failed, 0 skipped", 2)
    assert chip_smoke.check_validate("", 2)


def test_gspmd_mesh_on_an_accelerator_refuses_by_name(monkeypatch):
    """Found on four v5e chips: the host record pass cannot feed shard_map
    over a chip mesh. Until R7 that is an error that says so, not a device
    mismatch from deep inside JAX."""
    from nds_tpu.engine.jax_backend import JaxExecutor
    from nds_tpu.parallel import make_mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="mesh_shards"):
        JaxExecutor(lambda name: None, mesh=make_mesh(2))
    JaxExecutor(lambda name: None)        # no mesh: records on the host
