"""Shared by the benchmark's tests: paths and a run of ``benchmark/run.py``
as the driver runs it — a new process, here told the CPU by name."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(*args: str, cpu: bool = True, timeout: float = 600):
    """(exit code, last stdout line parsed or None, stderr)."""
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), *args]
    if cpu:
        cmd += ["--platform", "cpu", "--scale", "0.01"]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(cmd, cwd=ROOT, env=env, text=True,
                          capture_output=True, timeout=timeout)
    lines = proc.stdout.strip().splitlines()
    return proc.returncode, (json.loads(lines[-1]) if lines else None), \
        proc.stderr
