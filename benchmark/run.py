#!/usr/bin/env python3
"""One cell, one run: ``python3 benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.

A new process that holds the cell's chip, sets up (warehouse, statements from
the seed, session, record, compile, one warm pass over every statement),
measures for ``--seconds``, reads the device's memory peak, frees the
program's state, runs the plain reference over what the window returned and
prints the contract's result as the last line of standard output. It fails,
and never falls back, when JAX finds no TPU, fewer chips than the cell asks
for, or a device kind the peaks table does not list.

Everything that belongs to one cell is data found by name from
``BENCHMARK.json``: ``configs/<config>.json``, ``traffic/<mix>.json`` (which
names its window driver and its ``units/<unit>.tpl`` + ``.py``) and
``layer_metrics/<metric>.json``. ``--platform cpu``, ``--scale`` and
``--manifest`` exist for the tests under ``tests/benchmark`` only; ``--control 1`` runs the
configuration's lower-precision control (see PERF.md) and must come out as
not correct.
"""
from __future__ import annotations

import time

_T0 = time.monotonic()      # set-up is everything before the window

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# The compile cache lives at one fixed path inside the checkout, whatever the
# machine's environment names: the program takes JAX_COMPILATION_CACHE_DIR
# where it is set, so the benchmark sets it (before anything imports jax).
# A size bound is dropped with it: under JAX_COMPILATION_CACHE_MAX_SIZE every
# cache write rereads the access time of every entry, and this program caches
# thousands of small host kernels, so set-up turns quadratic (PERF.md, PR 24:
# a cold run had not recorded its first statement after 40 minutes).
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)

from benchmark import compare, readers, refdata, traffic  # noqa: E402

#: refuse: no accelerator, too few chips, an unknown device kind
EXIT_NO_DEVICE = 3
DATA_DIR = os.path.join(ROOT, ".benchmark_data")


def load_manifest(path: str | None = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json")


def load_peaks() -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        return json.load(f)


def peak_for(kind: str) -> dict:
    """The published peaks of a device kind; one the table lacks is an
    error, never a default."""
    peaks = load_peaks()
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.json")
    return peaks[kind]


def hold_device(platform: str, chips: int) -> dict:
    """The device as JAX reports it; exits where it is not what the cell
    needs. This is the first touch of JAX: from here the process holds the
    chip."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"benchmark: JAX found no device: {e}", file=sys.stderr)
        raise SystemExit(EXIT_NO_DEVICE)
    found = devices[0].platform
    if found != platform:
        print(f"benchmark: needs platform {platform!r}, JAX found "
              f"{found!r}", file=sys.stderr)
        raise SystemExit(EXIT_NO_DEVICE)
    if len(devices) < chips:
        print(f"benchmark: the cell needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(EXIT_NO_DEVICE)
    kind = devices[0].device_kind
    if platform == "tpu" and kind not in load_peaks():
        print(f"benchmark: device kind {kind!r} is not in peaks.json",
              file=sys.stderr)
        raise SystemExit(EXIT_NO_DEVICE)
    return {"platform": found, "kind": kind, "count": len(devices)}


def ensure_warehouse(scale: str) -> str:
    """The ``--use_decimal`` Parquet warehouse at ``scale``, made once per
    checkout by the program's own generator and transcoder (host-only child
    processes that have exited before this returns)."""
    base = os.path.join(DATA_DIR, f"sf{scale}")
    wh, done = os.path.join(base, "wh"), os.path.join(base, "complete")
    if os.path.exists(done):
        return wh
    import shutil
    shutil.rmtree(base, ignore_errors=True)
    os.makedirs(base)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    raw = os.path.join(base, "raw")
    for cmd in (
            [sys.executable, "-m", "nds_tpu.datagen", "local", raw,
             "--scale", scale, "--parallel",
             str(min(os.cpu_count() or 2, 8)), "--overwrite"],
            [sys.executable, "-m", "nds_tpu.transcode", raw, wh,
             os.path.join(base, "load_report.txt"), "--no_partition",
             "--use_decimal"]):
        proc = subprocess.run(cmd, cwd=ROOT, env=env, text=True,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT)
        if proc.returncode != 0:
            print(proc.stdout[-3000:], file=sys.stderr)
            raise SystemExit(f"benchmark: {' '.join(cmd[:3])} failed")
    shutil.rmtree(raw)
    with open(done, "w") as f:
        f.write("ok\n")
    return wh


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def decide(config: dict, stmts, answers, control: bool):
    """Reference over every distinct answer the window returned.

    ``answers``: [(unit index, pyarrow table, times returned)]. Returns the
    Comparison and the set of (unit index, answer index) found wrong."""
    cmp_ = compare.Comparison(
        decimal_exact=config["precision"]["decimal"] == "exact_i64",
        limits=config["limits"])
    wh = refdata.Warehouse(config["_warehouse"])
    refs: dict = {}
    wrong = set()
    for n, (ui, table, _count) in enumerate(answers):
        st = stmts[ui]
        if ui not in refs:
            mod = importlib.import_module(f"benchmark.units.{st.unit}")
            wh.unit = st.unit
            refs[ui] = mod.reference(wh, st.params)
        rows = compare.table_rows(table)
        if control and config["control"]["kind"] == "reference_bf16":
            rows = compare.as_bf16(refs[ui])
        if not cmp_.check(f"{st.unit}#{n}", rows, refs[ui]):
            wrong.add(n)
    return cmp_, wrong, wh.reads


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="benchmark/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    p.add_argument("--platform", default="tpu")
    p.add_argument("--scale", default=None)
    p.add_argument("--manifest", default=None)
    a = p.parse_args(argv)

    # a test that calls main() again in one process starts its own clock
    t_start = _T0 if argv is None else time.monotonic()
    manifest = load_manifest(a.manifest)
    cell = find_cell(manifest, a.workload)
    config = traffic.load_json("configs", cell["config"])
    mix = traffic.load_json("traffic", cell["traffic"])
    if not os.path.isdir(os.path.join(ROOT, "nds_tpu")):
        print("benchmark: no nds_tpu package beside the benchmark",
              file=sys.stderr)
        return 2
    device = hold_device(a.platform, cell["chips"])
    stmts = traffic.statements(mix, a.seed)

    from benchmark import drivers
    if mix["driver"] not in drivers.DRIVERS:
        raise SystemExit(f"benchmark: no window driver {mix['driver']!r}")
    obs = readers.Observations(trace=bool(a.trace))
    t0 = time.monotonic()
    config["_warehouse"] = ensure_warehouse(a.scale or config["scale"])
    driver = drivers.DRIVERS[mix["driver"]](config, mix, stmts, obs,
                                            control=bool(a.control))
    try:
        driver.load()
        obs.clocks["load_s"] = time.monotonic() - t0
        driver.warm()
        setup_s = time.monotonic() - t_start
        window = driver.window(a.seconds)
        peak = memory_peak_bytes()
    finally:
        driver.close()      # frees the program's state, ends the clients

    cmp_, wrong, reads = decide(config, stmts, window.answers,
                                bool(a.control))
    numbers = cmp_.numbers()
    limits = config["limits"]
    compared = {k: {"value": v, "limit": limits[k]}
                for k, v in numbers.items()}
    wrong_n = sum(window.answers[n][2] for n in wrong)
    failed = window.failed + wrong_n
    correct = failed == 0 and window.attempted > 0 and all(
        c["value"] <= c["limit"] for c in compared.values())

    values = dict(driver.end_to_end(window, wrong_n), setup_s=setup_s)
    listed = manifest["per_layer"] if a.trace else manifest["end_to_end"]
    names = [m["name"] for m in listed
             if a.workload in m.get("workloads", [a.workload])]
    device_out = dict(device, memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": window.attempted,
              "failed": failed}
    if a.trace:
        obs.scan_reads = reads
        obs.window = window
        obs.device_kind = device["kind"]
        obs.end_to_end = values
        values = readers.read_all(names, obs)
        print("spans (setup | window, ms): " + json.dumps(
            readers.span_table(obs)), file=sys.stderr)
        print("counters (window): " + json.dumps(obs.counters),
              file=sys.stderr)
        if obs.trace_summary:
            device_out["busy_s"] = obs.trace_summary["busy_s"]
            device_out["window_s"] = obs.trace_summary["window_s"]
    units = {m["name"]: m["unit"] for m in listed}
    result["metrics"] = {k: {"value": values[k], "unit": units[k]}
                         for k in names if k in values}
    result["device"] = device_out
    if a.trace and obs.trace_summary:
        result["breakdown"] = {
            "device_ops": obs.trace_summary["device_ops"][:10],
            "idle_gaps": obs.trace_summary["idle_gaps"][:10]}
    result["compared"] = compared
    for note in (cmp_.notes + window.notes)[:20]:
        print(f"benchmark: {note}", file=sys.stderr)
    print("compared: " + "  ".join(
        f"{k}={c['value']!r} (limit {c['limit']!r})"
        for k, c in compared.items()) + f"  failed={failed}",
        file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
