#!/usr/bin/env python3
"""Chip smoke: the NDS power, streamed and served paths, end to end on a TPU.

The quickest proof that the system still starts on the chip it was written
for. Drives the CLIs a user drives at TPC-DS SF1 (all 24 tables, decimal
warehouse, exact ``--decimal i64``), each in its own subprocess, one after
another — a chip belongs to one process at a time, so this parent never
imports jax (nor anything that does) and every chip-needing step has exited
before the next starts:

  Leg A  lifecycle slice, in-core: datagen (builds ndsdgen from source) ->
         transcode -> streams -> ``nds_tpu.power --warmup 2 --strict`` on the
         bench units -> the same stream on the numpy oracle -> validate.
  Leg B  streamed path: the power CLI on query3 + query9 with a property
         file that sends store_sales (2.88M rows) through the morsel
         pipeline SF100 uses (Parquet decode, pack_table, narrow lanes,
         dictionary/RLE encodings, the fused 15-branch q9 program).
  Leg C  served path: ``scripts/frontdoor_server.py`` over the same
         warehouse at production chunk sizes, three statements twice each
         through FlightClient, compared with the oracle output.
  Leg D  (four or more chips only) Leg B's query9 with ``--mesh_shards 4``,
         bit-identical to the one-chip answer. (GSPMD ``mesh_shape`` does not
         run on real chips yet — PERF.md PR 21, ROADMAP R7 — so it has no leg.)

The CLIs exit 0 whatever happened to a query, so the smoke checks what they
do not (``check_summaries``): every JSON summary ``Completed`` with no
exceptions or task failures, on the ``tpu`` backend, the timed run
``compiled`` (Leg A) or ``streaming`` (Leg B) — never eager, record or
carrying a ``nojit_reason`` — and validate's Pass count equal to the units
run with none skipped. Any miss is a non-zero exit.

Without a TPU it exits non-zero before generating anything and prints no
result. On success the LAST stdout line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
These are smoke timings, not benchmark numbers. Needs no network and no git;
everything is generated from fixed seeds; summaries, time logs and answers
land in ``--out``, the bulk data in ``.chip_smoke_data/`` until the run ends.
"""
from __future__ import annotations

import argparse
import csv
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SCALE = "1"
RNGSEED = "778"
#: the five units of ``power_resident_sf1`` and the pre-PR-1 chip records
LEG_A_UNITS = ["query1", "query3", "query7", "query9", "query10"]
LEG_B_UNITS = ["query3", "query9"]
LEG_C_UNITS = ["query3", "query7", "query10"]
#: units dropped from a leg to fit the time limit (the SF is never cut)
CUT_UNITS: list[str] = []
#: wall budget for the whole script, compilation included (contract: 1200 s)
BUDGET_S = 1140.0
#: EngineConfig's own defaults — the server's flag defaults are demo sizes
PRODUCTION_CHUNK_ROWS = 1 << 22
PRODUCTION_OOC_MIN_ROWS = 48_000_000

_DEVICE_PROBE = (
    "import json, jax; d = jax.devices(); "
    "print('DEVICE ' + json.dumps({'platform': d[0].platform, "
    "'kind': d[0].device_kind, 'count': len(d)}))")

_T0 = time.monotonic()


class LegFailed(Exception):
    """One phase of a leg failed its command or its checks."""


def _remaining() -> float:
    return BUDGET_S - (time.monotonic() - _T0)


def _host_env() -> dict:
    """Environment of a step that must stay off the chip (datagen, the numpy
    oracle, validate, the served path's client)."""
    return dict(os.environ, JAX_PLATFORMS="cpu")


def run(cmd: list[str], log_path: str, env: dict | None = None) -> tuple:
    """One subprocess to completion within the remaining budget; returns
    (seconds, output). Output is kept in ``log_path``; a non-zero exit or a
    timeout fails the leg with the tail of it."""
    left = _remaining()
    if left <= 0:
        raise LegFailed(f"time budget spent before: {' '.join(cmd[:4])}")
    t0 = time.monotonic()
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, text=True,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, timeout=left)
        out, rc = proc.stdout, proc.returncode
    except subprocess.TimeoutExpired as e:
        out = e.stdout or ""
        out = out.decode() if isinstance(out, bytes) else out
        rc = "timeout"
    secs = time.monotonic() - t0
    os.makedirs(os.path.dirname(log_path), exist_ok=True)
    with open(log_path, "w") as f:
        f.write(out)
    if rc != 0:
        raise LegFailed(f"{' '.join(cmd[:4])} ... -> {rc} after "
                        f"{secs:.0f}s; tail of {log_path}:\n{out[-3000:]}")
    return secs, out


def probe_device() -> dict:
    """The device as JAX reports it, asked in a child that exits again."""
    proc = subprocess.run([sys.executable, "-c", _DEVICE_PROBE], text=True,
                          capture_output=True, timeout=300)
    for line in proc.stdout.splitlines():
        if line.startswith("DEVICE "):
            return json.loads(line[len("DEVICE "):])
    raise SystemExit("chip_smoke: JAX did not start:\n"
                     + (proc.stderr or proc.stdout)[-2000:])


# -- what the CLIs do not check ---------------------------------------------

def check_summaries(json_dir: str, units: list[str], want_mode: str,
                    want_backend: str = "tpu") -> list[str]:
    """Problems in one power run's JSON summaries; [] when every unit's
    timed run completed cleanly on the device in the wanted mode."""
    problems = []
    for unit in units:
        paths = glob.glob(os.path.join(json_dir, f"power-{unit}-*.json"))
        if len(paths) != 1:
            problems.append(f"{unit}: {len(paths)} JSON summaries")
            continue
        with open(paths[0]) as f:
            s = json.load(f)
        status = (s.get("queryStatus") or ["missing"])[-1]
        if status != "Completed":
            problems.append(f"{unit}: status {status}")
        for key in ("exceptions", "taskFailures"):
            if s.get(key):
                problems.append(f"{unit}: {key} {s[key]}")
        backend = s.get("env", {}).get("host", {}).get("jax_backend")
        if backend != want_backend:
            problems.append(f"{unit}: ran on backend {backend!r}, "
                            f"not {want_backend!r}")
        stats = (s.get("execStats") or [{}])[-1]
        if stats.get("mode") != want_mode:
            problems.append(f"{unit}: timed run mode {stats.get('mode')!r}, "
                            f"not {want_mode!r}")
        if stats.get("nojit_reason") or stats.get("fallback_reasons"):
            problems.append(
                f"{unit}: left the device: "
                f"{stats.get('nojit_reason') or stats['fallback_reasons']}")
        if want_mode == "streaming" and not stats.get("bytes_uploaded"):
            problems.append(f"{unit}: streamed no bytes")
    return problems


def check_validate(output: str, n_units: int) -> list[str]:
    """validate exits 0 on skips; require every unit passed, none skipped."""
    m = re.search(r"(\d+) passed, (\d+) failed, (\d+) skipped", output)
    if not m:
        return ["validate printed no tally"]
    passed, failed, skipped = map(int, m.groups())
    if (passed, failed, skipped) != (n_units, 0, 0):
        return [f"validate: {passed} passed, {failed} failed, {skipped} "
                f"skipped of {n_units} units"]
    return []


def sub_stream(stream_file: str, units: list[str], path: str) -> str:
    """A stream file holding only `units` (validate walks every query of
    the stream it is given)."""
    with open(stream_file) as f:
        text = f.read()
    parts = re.split(r"(?m)^(?=--\s*start query \d+ using template)", text)
    keep = [p for p in parts if any(
        re.match(rf"--\s*start query \d+ using template {u}\.tpl", p)
        for u in units)]
    if len(keep) != len(units):
        raise LegFailed(f"stream {stream_file} lacks some of {units}")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write("".join(keep))
    return path


def timed_s(time_log: str) -> float:
    """Sum of the per-query timed runs in a power time log, seconds."""
    with open(time_log) as f:
        return sum(int(r[3]) for r in csv.reader(f)
                   if r and re.match(r"query\d", r[0])) / 1000.0


def precompile_split(output: str) -> tuple[float, float]:
    """(record s, compile s) from the power runner's precompile line."""
    m = re.search(r"precompile: recorded \d+ queries in ([\d.]+)s; "
                  r"compiled (\d+)/(\d+) programs in ([\d.]+)s", output)
    if not m:
        raise LegFailed("power printed no precompile line")
    if m.group(2) != m.group(3):
        raise LegFailed(f"precompile compiled {m.group(2)}/{m.group(3)} "
                        "programs")
    return float(m.group(1)), float(m.group(4))


# -- legs --------------------------------------------------------------------

class Smoke:
    def __init__(self, out: str, work: str, want_backend: str = "tpu",
                 scale: str = SCALE):
        self.out = os.path.abspath(out)     # summaries, time logs, answers
        self.want_backend = want_backend
        self.scale = scale
        self.data = os.path.join(work, "data")          # bulk: raw data
        self.wh = os.path.join(work, "warehouse")       # bulk: parquet
        self.streams = os.path.join(self.out, "streams")
        self.stream = os.path.join(self.streams, "query_0.sql")
        self.oracle = os.path.join(self.out, "oracle", "out")
        self.times: dict[str, dict] = {}

    def log(self, name: str) -> str:
        return os.path.join(self.out, "logs", name + ".log")

    def prepare(self) -> None:
        """datagen -> transcode -> streams -> numpy oracle (host only)."""
        py, env = sys.executable, _host_env()
        par = str(min(os.cpu_count() or 2, 8))
        t_gen, out = run([py, "-m", "nds_tpu.datagen", "local", self.data,
                          "--scale", self.scale, "--parallel", par,
                          "--overwrite"], self.log("datagen"), env)
        for line in out.splitlines():
            if line.startswith("built ndsdgen:"):
                print(line, flush=True)
        t_load, _ = run([py, "-m", "nds_tpu.transcode", self.data, self.wh,
                         os.path.join(self.out, "load_report.txt"),
                         "--no_partition", "--use_decimal"],
                        self.log("transcode"), env)
        run([py, "-m", "nds_tpu.streams", self.streams, "--streams", "1",
             "--rngseed", RNGSEED], self.log("streams"), env)
        units = sorted(set(LEG_A_UNITS + LEG_B_UNITS + LEG_C_UNITS),
                       key=lambda u: int(u[5:]))
        t_oracle, _ = run(
            [py, "-m", "nds_tpu.power", self.wh, self.stream,
             os.path.join(self.out, "oracle", "time.csv"),
             "--backend", "numpy", "--decimal", "i64",
             "--sub_queries", ",".join(units),
             "--output_prefix", self.oracle], self.log("oracle"), env)
        self.times["prepare"] = {"datagen_s": t_gen, "load_s": t_load,
                                 "oracle_s": t_oracle}

    def power_leg(self, leg: str, units: list[str], want_mode: str,
                  extra: list[str]) -> None:
        """One ``nds_tpu.power`` run on the chip + its checks + validate."""
        py = sys.executable
        d = os.path.join(self.out, leg)
        time_log = os.path.join(d, "time.csv")
        t_power, out = run(
            [py, "-m", "nds_tpu.power", self.wh, self.stream, time_log,
             "--decimal", "i64", "--warmup", "2", "--strict",
             "--sub_queries", ",".join(units),
             "--json_summary_folder", os.path.join(d, "json"),
             "--output_prefix", os.path.join(d, "out")] + extra,
            self.log(leg))
        record_s, compile_s = precompile_split(out)
        timed = timed_s(time_log)
        self.times[leg] = {
            "record_s": record_s, "compile_s": compile_s,
            "timed_run_s": timed,
            "other_s": t_power - record_s - compile_s - timed,
            "process_s": t_power}
        problems = check_summaries(os.path.join(d, "json"), units,
                                   want_mode, self.want_backend)
        problems += self.validate(
            leg, units, ["--use_decimal", "--json_summary_folder",
                         os.path.join(d, "json")])
        if problems:
            raise LegFailed("; ".join(problems))

    def validate(self, leg: str, units: list[str], extra: list[str],
                 answers: str = "out") -> list:
        """``nds_tpu.validate`` of a leg's answers against the oracle's."""
        d = os.path.join(self.out, leg)
        try:
            _, vout = run(
                [sys.executable, "-m", "nds_tpu.validate", self.oracle,
                 os.path.join(d, answers),
                 sub_stream(self.stream, units,
                            os.path.join(d, "stream.sql"))] + extra,
                self.log(f"{leg}_validate_{answers}"), _host_env())
        except LegFailed as e:          # exit 1 = some unit failed
            return [str(e)]
        return check_validate(vout, len(units))

    def leg_a(self) -> None:
        self.power_leg("leg_a", LEG_A_UNITS, "compiled", [])

    def stream_props(self) -> str:
        path = os.path.join(self.out, "stream.properties")
        with open(path, "w") as f:
            f.write("nds.tpu.out_of_core_min_rows=1000000\n"
                    "nds.tpu.chunk_rows=1048576\n")
        return path

    def leg_b(self) -> None:
        self.power_leg("leg_b", LEG_B_UNITS, "streaming",
                       ["--property_file", self.stream_props()])

    def leg_c(self) -> None:
        """The served path: one engine server process on the chip, a
        JAX-free client beside it (this script again, ``--client``)."""
        d = os.path.join(self.out, "leg_c")
        os.makedirs(d, exist_ok=True)
        tables = sorted(
            t for t in os.listdir(self.wh)
            if os.path.isdir(os.path.join(self.wh, t, "data")))
        cmd = [sys.executable,
               os.path.join(REPO, "scripts", "frontdoor_server.py"),
               "--chunk_rows", str(PRODUCTION_CHUNK_ROWS),
               "--out_of_core_min_rows", str(PRODUCTION_OOC_MIN_ROWS)]
        for t in tables:
            cmd += ["--table", f"{t}={os.path.join(self.wh, t, 'data')}"]
        t0 = time.monotonic()
        with open(self.log("leg_c_server"), "w") as err:
            server = subprocess.Popen(cmd, cwd=REPO, text=True, stderr=err,
                                      stdin=subprocess.PIPE,
                                      stdout=subprocess.PIPE)
        try:
            line = server.stdout.readline()
            if not line.startswith("FRONTDOOR "):
                raise LegFailed(f"server printed {line!r}, see "
                                f"{self.log('leg_c_server')}")
            info = json.loads(line[len("FRONTDOOR "):])
            t_start = time.monotonic() - t0
            print(f"leg_c: server on {info['device']}, x64={info['x64']} "
                  "(decimals run as "
                  f"{'f64' if info['x64'] else 'f32'} on the device)",
                  flush=True)
            if info["device"]["platform"] != self.want_backend:
                raise LegFailed(f"server runs on {info['device']}")
            t_client, cout = run(
                [sys.executable, os.path.abspath(__file__), "--client",
                 info["host"], str(info["port"]), self.stream, d,
                 ",".join(LEG_C_UNITS)],
                self.log("leg_c_client"), _host_env())
            server.stdin.close()
            rc = server.wait(timeout=max(_remaining(), 30))
            if rc != 0:
                raise LegFailed(f"server exit code {rc}")
        finally:
            if server.poll() is None:
                server.kill()
                server.wait()
        first = [float(x) for x in
                 re.findall(r"CLIENT \S+ first ([\d.]+)s", cout)]
        again = [float(x) for x in
                 re.findall(r"CLIENT \S+ .* again ([\d.]+)s", cout)]
        self.times["leg_c"] = {
            "server_start_s": t_start, "first_requests_s": sum(first),
            "repeat_requests_s": sum(again), "client_s": t_client,
            "x64": info["x64"]}
        # the first answer comes from the host record pass, the repeat from
        # the compiled program on the chip: both must match the oracle
        problems = self.validate("leg_c", LEG_C_UNITS, [], "first") + \
            self.validate("leg_c", LEG_C_UNITS, [], "again")
        if problems:
            raise LegFailed("; ".join(problems))

    def leg_d(self) -> None:
        """Four chips: sharded morsels (mesh_shards) bit-identical to Leg
        B's one-chip answer."""
        self.power_leg("leg_d_morsels", ["query9"], "streaming",
                       ["--property_file", self.stream_props(),
                        "--mesh_shards", "4"])
        with open(glob.glob(os.path.join(
                self.out, "leg_d_morsels", "json", "power-query9-*"))[0]) as f:
            stats = json.load(f)["execStats"][-1]
        if stats.get("mesh_shards") != 4 or not stats.get("sharded_groups"):
            raise LegFailed(f"query9 did not shard: {stats}")
        one, four = (os.path.join(self.out, leg, "out", "query9",
                                  "part-0.parquet")
                     for leg in ("leg_b", "leg_d_morsels"))
        _, out = run([sys.executable, "-c",
                      "import sys, pyarrow.parquet as pq; "
                      "a, b = (pq.read_table(p) for p in sys.argv[1:]); "
                      "print('IDENTICAL' if a.equals(b) else 'DIFFERENT')",
                      one, four], self.log("leg_d_identity"), _host_env())
        if "IDENTICAL" not in out:
            raise LegFailed("query9 on four chips differs from one chip")


def client_main(argv: list[str]) -> int:
    """``--client``: the served path's client process (host only). Sends
    each unit's statement twice through FlightClient and writes the first
    and the repeat answer where ``nds_tpu.validate`` finds them; the repeat
    must have run as a compiled program."""
    host, port, stream_file, out_dir, units = argv
    import pyarrow.parquet as pq

    from nds_tpu.power import ensure_valid_column_names, gen_sql_from_stream
    from nds_tpu.service.frontdoor import FlightClient

    with open(stream_file) as f:
        queries = gen_sql_from_stream(f.read())
    # one request may hold a whole cold XLA:TPU compile
    client = FlightClient(host, int(port), timeout_s=BUDGET_S)
    try:
        for unit in units.split(","):
            answers = {}
            t0 = time.monotonic()
            answers["first"], _resp = client.query(queries[unit], label=unit)
            t1 = time.monotonic()
            answers["again"], resp = client.query(queries[unit], label=unit)
            t2 = time.monotonic()
            mode = resp["stats"]["mode"]
            print(f"CLIENT {unit} first {t1 - t0:.2f}s "
                  f"rows {answers['again'].num_rows} again {t2 - t1:.2f}s "
                  f"mode {mode}", flush=True)
            if mode in (None, "eager", "record"):
                print(f"CLIENT {unit}: repeat request ran {mode}, not a "
                      "compiled program")
                return 1
            for which, table in answers.items():
                table = table.rename_columns(
                    ensure_valid_column_names(table.column_names))
                d = os.path.join(out_dir, which, unit)
                os.makedirs(d, exist_ok=True)
                pq.write_table(table, os.path.join(d, "part-0.parquet"))
    finally:
        client.close()
    return 0


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv[:1] == ["--client"]:
        return client_main(argv[1:])
    p = argparse.ArgumentParser(prog="chip_smoke.py", description=__doc__,
                                formatter_class=argparse.RawTextHelpFormatter)
    p.add_argument("--out", default=os.path.join(REPO, "chiprun_out",
                                                 "chip_smoke"),
                   help="output directory (data, summaries, time logs)")
    a = p.parse_args(argv)

    # no accelerator, no run: refuse before anything is generated
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu":
        print("chip_smoke: JAX_PLATFORMS pins the CPU; this smoke needs "
              "a TPU", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(REPO, "nds_tpu")):
        print(f"chip_smoke: no nds_tpu package beside {__file__}",
              file=sys.stderr)
        return 2
    device = probe_device()
    print(f"platform: {device['platform']}  device_kind: {device['kind']}  "
          f"devices: {device['count']}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 2
    if CUT_UNITS:
        print(f"units cut to fit the time limit: {', '.join(CUT_UNITS)} "
              "(the scale factor is never cut)", flush=True)

    # bulk data lives beside the checkout's other build products and goes
    # away again; --out keeps only summaries, logs and answers
    work = os.path.join(REPO, ".chip_smoke_data")
    for d in (a.out, work):
        shutil.rmtree(d, ignore_errors=True)     # a re-run starts clean
    smoke = Smoke(a.out, work)
    legs = [("prepare", smoke.prepare), ("leg_a", smoke.leg_a),
            ("leg_b", smoke.leg_b), ("leg_c", smoke.leg_c)]
    if device["count"] >= 4:
        legs.append(("leg_d", smoke.leg_d))
    status = {}
    for name, leg in legs:
        t0 = time.monotonic()
        try:
            leg()
            status[name] = "pass"
        except LegFailed as e:
            status[name] = "FAIL"
            print(f"{name}: FAIL: {e}", flush=True)
        print(f"{name}: {status[name]} in {time.monotonic() - t0:.1f}s "
              + json.dumps({k: round(v, 1) if isinstance(v, float) else v
                            for leg_name, t in smoke.times.items()
                            if leg_name.startswith(name)
                            for k, v in t.items()}), flush=True)
        if status[name] == "FAIL" and name == "prepare":
            break
    shutil.rmtree(work, ignore_errors=True)
    ok = all(v == "pass" for v in status.values()) and \
        len(status) == len(legs)
    record = {"ok": ok, "device": device, "legs": status,
              "seconds": smoke.times, "scale": SCALE, "cut_units": CUT_UNITS,
              "total_s": round(time.monotonic() - _T0, 1),
              "compile_cache": os.environ.get("JAX_COMPILATION_CACHE_DIR")
              or os.path.join(REPO, ".jax_cache")}
    os.makedirs(smoke.out, exist_ok=True)
    with open(os.path.join(smoke.out, "chip_smoke.json"), "w") as f:
        json.dump(record, f, indent=2)
    print(f"total: {record['total_s']}s (smoke timings, not benchmark "
          "numbers)", flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
