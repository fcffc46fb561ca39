"""Whole-process benchmark orchestrator: YAML-driven phases + primary metric.

Capability parity with the reference orchestrator (reference
nds/nds_bench.py): run steps 0-7 with per-step ``skip`` flags (bench.yml:
8-40), scrape report files for times and the load-end RNGSEED (:60-123),
split streams into halves for the two throughput/maintenance rounds
(get_stream_range :126-135), throughput elapsed = max(end)-min(start) over
stream logs (:138-157), maintenance = sum of refresh times (:176-196),
round every elapsed up to 0.1 s (:207-208), and compute the primary metric
``SF * (Sq*99) / (Tpt*Ttt*Tdm*Tld)^(1/4)`` in decimal hours with
Tpt=Tpower*Sq and Tld=0.01*Sq*Tload (get_perf_metric :334-357), writing
metrics.csv (:360-364).

Differences by design: phases run in-process (no subprocess/file contract
needed between layers), and the config is one YAML with per-phase
sections instead of the template zoo.
"""
from __future__ import annotations

import argparse
import csv
import math
import os
import sys

import yaml

from . import datagen, maintenance, streams, transcode
from .power import run_query_stream
from .resilience import RetryPolicy
from .throughput import run_throughput, stream_log_path, throughput_elapsed


def round_up_tenth(seconds: float) -> float:
    """Round an elapsed time up to the nearest 0.1 s (nds_bench.py:207)."""
    return math.ceil(seconds * 10.0) / 10.0


def get_stream_range(num_streams: int, first_or_second: int) -> list[int]:
    """Stream ids for throughput/maintenance round 1 or 2.

    Stream 0 is the power stream; rounds split the rest in half
    (nds_bench.py:126-135). num_streams must be odd and >= 3.
    """
    if num_streams < 3 or num_streams % 2 == 0:
        raise ValueError("num_streams must be an odd number >= 3")
    half = num_streams // 2
    if first_or_second == 1:
        return list(range(1, half + 1))
    return list(range(half + 1, num_streams))


def get_load_time(report_path: str) -> float:
    _require_report(report_path, "load_test")
    with open(report_path) as f:
        for line in f:
            if line.startswith("Load Test Time:"):
                return float(line.split(":")[1].split()[0])
    raise ValueError(f"no Load Test Time in {report_path}")


def get_load_end_timestamp(report_path: str) -> int:
    """RNGSEED scraped from the load report (nds_bench.py:60-76)."""
    _require_report(report_path, "load_test")
    with open(report_path) as f:
        for line in f:
            if line.startswith("RNGSEED used:"):
                return int(line.split(":")[1].strip().replace(" ", ""))
    raise ValueError(f"no RNGSEED in {report_path}")


def _require_report(path: str, phase: str):
    """Clear failure when a skipped phase's report is absent: skip means
    'already ran' (restartable split runs, reference bench.yml skip flags) —
    point the config at the prior run's report_dir or unskip the phase."""
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"{phase} report {path!r} is missing: the phase was skipped but "
            f"never ran — unskip it or reuse a report_dir that has it")


def get_power_time(time_log: str) -> float:
    _require_report(time_log, "power_test")
    with open(time_log) as f:
        for row in csv.reader(f):
            if row and row[0] == "Power Test Time":
                return int(row[3]) / 1000.0
    raise ValueError(f"no Power Test Time in {time_log}")


def get_maintenance_time(time_log: str) -> float:
    """Sum of refresh-function times, seconds (nds_bench.py:176-196)."""
    _require_report(time_log, "maintenance_test")
    total_ms = 0
    seen = False
    with open(time_log) as f:
        for row in csv.reader(f):
            if not row or row[0] in ("query",) or row[0].startswith(
                    "Maintenance"):
                continue
            total_ms += int(row[3])
            seen = True
    if not seen:
        raise ValueError(f"no refresh rows in {time_log}")
    return total_ms / 1000.0


def get_perf_metric(scale_factor: float, num_streams: int, t_load: float,
                    t_power: float, t_tt1: float, t_tt2: float,
                    t_dm1: float, t_dm2: float) -> float:
    """Primary NDS metric (nds_bench.py:334-357).

    All t_* in seconds; internally converted to decimal hours. Sq is the
    per-round stream count (num_streams // 2).
    """
    sq = num_streams // 2
    to_hours = 1.0 / 3600.0
    t_ld = 0.01 * sq * t_load * to_hours
    t_pt = t_power * sq * to_hours
    t_tt = (t_tt1 + t_tt2) * to_hours
    t_dm = (t_dm1 + t_dm2) * to_hours
    denom = (t_pt * t_tt * t_dm * t_ld) ** 0.25
    return math.floor(scale_factor * (sq * 99) / denom)


def write_metrics_report(path: str, rows: list[list]) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w", newline="") as f:
        csv.writer(f).writerows(rows)


def _skip(section: dict) -> bool:
    return bool(section.get("skip", False))


def run_full_bench(cfg: dict) -> dict:
    """Run every phase per the YAML config; returns the collected times."""
    from .config import maybe_enable_compile_cache

    maybe_enable_compile_cache()
    sf = float(cfg["data_gen"]["scale_factor"])
    num_streams = int(cfg["generate_query_stream"]["num_streams"])
    sq = num_streams // 2
    data_path = cfg["data_gen"]["data_path"]
    warehouse = cfg["load_test"]["warehouse_path"]
    stream_dir = cfg["generate_query_stream"]["stream_path"]
    report_dir = cfg.get("report_dir", "./nds_report")
    backend = cfg.get("backend")
    decimal = cfg.get("decimal")
    if decimal and decimal not in ("f64", "i64"):
        raise ValueError(f"bench config: unknown decimal {decimal!r} "
                         "(expected f64 or i64)")
    if decimal == "i64" and not cfg["load_test"].get("use_decimal", False):
        raise ValueError(
            "bench config: decimal: i64 requires load_test.use_decimal: true"
            " — an f64-loaded warehouse has no decimal columns to bind, so"
            " the run would silently measure f64")
    sub_queries = cfg.get("sub_queries")
    input_format = cfg["load_test"].get("format", "parquet")

    # step 0: data generation — source set + one refresh set per non-power
    # stream (reference run_data_gen generates the update sets the two
    # maintenance rounds consume, nds_bench.py:211-229)
    gen_cfg = cfg["data_gen"]
    if not _skip(gen_cfg):
        parallel = int(gen_cfg.get("parallel", 2))
        datagen.generate_data_local(data_path, sf, parallel, overwrite=True)
        for s in range(1, num_streams):
            datagen.generate_data_local(_refresh_dir(data_path, s), sf,
                                        parallel, update=s, overwrite=True)

    # step 1: load test (transcode into the warehouse)
    load_cfg = cfg["load_test"]
    load_report = os.path.join(report_dir, "load_report.txt")
    if not _skip(load_cfg):
        transcode.transcode(data_path, warehouse, load_report,
                            use_decimal=load_cfg.get("use_decimal", False))
    t_load = get_load_time(load_report)

    # step 2: query streams seeded by the load end timestamp
    qs_cfg = cfg["generate_query_stream"]
    if not _skip(qs_cfg):
        rngseed = qs_cfg.get("rngseed")
        if rngseed is None:  # an explicit seed of 0 must be honored
            rngseed = get_load_end_timestamp(load_report)
        streams.generate_query_streams(stream_dir, streams=num_streams,
                                       rngseed=int(rngseed))

    # step 3: power test = stream 0, serial
    power_cfg = cfg.get("power_test", {})
    power_log = os.path.join(report_dir, "power.csv")
    if not _skip(power_cfg):
        run_query_stream(warehouse, os.path.join(stream_dir, "query_0.sql"),
                         power_log, input_format=input_format,
                         output_prefix=power_cfg.get("output_prefix"),
                         json_summary_folder=power_cfg.get(
                             "json_summary_folder"),
                         sub_queries=sub_queries,
                         property_file=power_cfg.get("property_file"),
                         backend=backend, decimal=decimal,
                         warmup=int(power_cfg.get("warmup", 0)))
    t_power = get_power_time(power_log)

    # steps 4+6: throughput rounds; steps 5+7: maintenance rounds.
    # Phase-level retry (resilience: {phase_attempts: N, phase_backoff_s}):
    # a round that fails transiently — a permanently failed stream, a
    # device runtime error — re-runs whole up to N times with backoff
    # before the bench aborts. Stream logs are rewritten per attempt, so a
    # retried round scrapes only its own successful run.
    res_cfg = cfg.get("resilience", {})
    phase_policy = RetryPolicy(
        max_attempts=max(1, int(res_cfg.get("phase_attempts", 1))),
        backoff_s=float(res_cfg.get("phase_backoff_s", 1.0)))
    tt_cfg = cfg.get("throughput_test", {})
    dm_cfg = cfg.get("maintenance_test", {})
    t_tt: dict[int, float] = {}
    t_dm: dict[int, float] = {}
    for rnd in (1, 2):
        ids = get_stream_range(num_streams, rnd)
        if not _skip(tt_cfg):
            phase_policy.call(
                run_throughput, warehouse, stream_dir, ids, report_dir,
                label=f"throughput round {rnd}",
                input_format=input_format,
                sub_queries=sub_queries, backend=backend,
                mode=tt_cfg.get("mode", "thread"),
                warmup=int(tt_cfg.get("warmup", 0)),
                decimal=decimal,
                max_attempts=tt_cfg.get("stream_attempts"),
                stream_timeout=tt_cfg.get("stream_timeout"))
        tt_logs = [stream_log_path(report_dir, s) for s in ids]
        for lg in tt_logs:
            _require_report(lg, "throughput_test")
        t_tt[rnd] = throughput_elapsed(tt_logs)
        dm_total = 0.0
        for s in ids:
            dm_log = os.path.join(report_dir, f"maintenance_{s}.csv")
            if not _skip(dm_cfg):
                phase_policy.call(
                    maintenance.run_maintenance,
                    warehouse, _refresh_dir(data_path, s), dm_log,
                    label=f"maintenance stream {s}",
                    backend=backend, decimal=decimal)
            dm_total += get_maintenance_time(dm_log)
        t_dm[rnd] = dm_total

    times = {
        "load": round_up_tenth(t_load),
        "power": round_up_tenth(t_power),
        "throughput1": round_up_tenth(t_tt[1]),
        "throughput2": round_up_tenth(t_tt[2]),
        "maintenance1": round_up_tenth(t_dm[1]),
        "maintenance2": round_up_tenth(t_dm[2]),
    }
    metric = get_perf_metric(sf, num_streams, times["load"], times["power"],
                             times["throughput1"], times["throughput2"],
                             times["maintenance1"], times["maintenance2"])
    rows = [["scale_factor", sf], ["num_streams", num_streams], ["Sq", sq]]
    rows += [[k, v] for k, v in times.items()]
    rows.append(["perf_metric", metric])
    write_metrics_report(cfg.get("metrics_path",
                                 os.path.join(report_dir, "metrics.csv")),
                         rows)
    return {**times, "metric": metric}


def _refresh_dir(data_path: str, stream: int) -> str:
    return f"{data_path.rstrip('/')}_update_{stream}"


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="nds_tpu.bench")
    p.add_argument("yaml_config")
    a = p.parse_args(argv)
    with open(a.yaml_config) as f:
        cfg = yaml.safe_load(f)
    result = run_full_bench(cfg)
    print(f"perf metric: {result['metric']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
