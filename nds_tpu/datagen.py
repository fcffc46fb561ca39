"""Data-generation CLI: drives the native ndsdgen generator.

Capability parity with the reference data-gen front-end
(reference nds/nds_gen_data.py): local process-parallel generation
(generate_data_local :183-244 forks one dsdgen per chunk), per-table output
directories, incremental --range generation (:155-174), --update refresh
sets (:220-229 in nds_bench.py), and the delete-date table placement
(move_delete_date_tables :119-127). The cluster path is a host-list fanout
instead of a Hadoop MR job (SURVEY.md §2 parallelism table).
"""
from __future__ import annotations

import argparse
import os
import shutil
import subprocess
import sys

SOURCE_TABLES = [
    "call_center", "catalog_page", "catalog_returns", "catalog_sales",
    "customer", "customer_address", "customer_demographics", "date_dim",
    "dbgen_version", "household_demographics", "income_band", "inventory",
    "item", "promotion", "reason", "ship_mode", "store", "store_returns",
    "store_sales", "time_dim", "warehouse", "web_page", "web_returns",
    "web_sales", "web_site",
]
MAINTENANCE_TABLES = [
    "s_purchase_lineitem", "s_purchase", "s_catalog_order", "s_web_order",
    "s_catalog_order_lineitem", "s_web_order_lineitem", "s_store_returns",
    "s_catalog_returns", "s_web_returns", "s_inventory", "delete",
    "inventory_delete",
]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BINARY = os.path.join(_REPO_ROOT, "native", "bin", "ndsdgen")


_SRC_DIR = os.path.join(_REPO_ROOT, "native", "datagen")
_SOURCES = ("gen.cpp", "gen.h", "schema_def.inc")


def _stale(binary: str) -> bool:
    """Is the generator missing or older than the sources it is built from?"""
    if not os.path.exists(binary):
        return True
    built = os.path.getmtime(binary)
    return any(os.path.getmtime(os.path.join(_SRC_DIR, s)) > built
               for s in _SOURCES)


def check_build(binary: str = DEFAULT_BINARY) -> str:
    """Locate the native generator, (re)building it from native/datagen
    when it is missing or older than its sources — the binary is a build
    product (native/bin/ is git-ignored), so the data always comes from
    the gen.cpp in the tree (reference check.py:47-66 checks the
    jar/dsdgen build). An explicit non-default ``binary`` is used as is.

    Concurrent callers (test workers, parallel CLIs) serialise on a lock
    file and the compiler writes a temp name that is renamed into place,
    so nobody ever executes a half-written binary."""
    if binary != DEFAULT_BINARY or not os.path.isdir(_SRC_DIR):
        if os.path.exists(binary):
            return binary
        raise FileNotFoundError(f"ndsdgen binary not found at {binary}")
    if not _stale(binary):
        return binary
    import fcntl
    os.makedirs(os.path.dirname(binary), exist_ok=True)
    with open(binary + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if _stale(binary):      # re-check: a concurrent caller may have built
            tmp = f"{binary}.{os.getpid()}.tmp"
            try:
                made = subprocess.run(["make", f"BIN={tmp}"], cwd=_SRC_DIR,
                                      capture_output=True, text=True)
                if made.returncode != 0:
                    raise RuntimeError(
                        "building ndsdgen failed (a C++17 compiler and make "
                        f"are required):\n{made.stdout}{made.stderr}")
                for line in made.stdout.splitlines():
                    if " -o " in line:
                        print(f"built ndsdgen: {line.strip()}", flush=True)
                os.replace(tmp, binary)
            finally:
                if os.path.exists(tmp):
                    os.remove(tmp)
    return binary


def valid_range(r: str, parallel: int) -> tuple[int, int]:
    """Parse --range 'first,last' (1-based chunk indexes, reference
    check.py:88-123)."""
    try:
        first, last = (int(x) for x in r.split(","))
    except ValueError:
        raise ValueError(f"bad range {r!r}: expected 'first,last'")
    if not (1 <= first <= last <= parallel):
        raise ValueError(f"range {r!r} outside 1..{parallel}")
    return first, last


def generate_data_local(data_dir: str, scale: float, parallel: int,
                        chunk_range: tuple[int, int] | None = None,
                        update: int = 0,
                        binary: str | None = None,
                        overwrite: bool = False) -> None:
    """Fork one generator process per chunk and lay out per-table dirs."""
    binary = binary or check_build()
    first, last = chunk_range if chunk_range else (1, parallel)
    if chunk_range is None:
        if os.path.exists(data_dir):
            if not overwrite and os.listdir(data_dir):
                raise FileExistsError(
                    f"{data_dir} is not empty; pass overwrite to replace")
            shutil.rmtree(data_dir, ignore_errors=True)
        work = os.path.join(data_dir, "_raw_")
    else:
        # incremental range runs append into a shared data_dir (possibly
        # concurrently from several hosts): never wipe it, and keep a
        # range-private work dir so parallel runs don't race on cleanup
        work = os.path.join(data_dir, f"_raw_{first}_{last}_")
    os.makedirs(work, exist_ok=True)
    procs = []
    for child in range(first, last + 1):
        cmd = [binary, "-scale", str(scale), "-dir", work,
               "-parallel", str(parallel), "-child", str(child)]
        if update:
            cmd += ["-update", str(update)]
        procs.append((child, subprocess.Popen(cmd)))
    failed = [c for c, p in procs if p.wait() != 0]
    if failed:
        raise RuntimeError(f"generator chunks failed: {failed}")

    tables = MAINTENANCE_TABLES if update else SOURCE_TABLES
    for table in tables:
        tdir = os.path.join(data_dir, table)
        os.makedirs(tdir, exist_ok=True)
        if parallel > 1:
            for child in range(first, last + 1):
                src = os.path.join(work, f"{table}_{child}_{parallel}.dat")
                # small tables leave some chunks empty; don't ship those
                if os.path.exists(src) and os.path.getsize(src) > 0:
                    os.rename(src, os.path.join(tdir, os.path.basename(src)))
        else:
            src = os.path.join(work, f"{table}.dat")
            if os.path.exists(src):
                os.rename(src, os.path.join(tdir, f"{table}.dat"))
    shutil.rmtree(work, ignore_errors=True)

    # verify non-empty output (reference nds_gen_data.py:199-206); a range
    # subset legitimately leaves small single-chunk tables to other ranges,
    # so full verification only applies to whole runs
    if chunk_range is None:
        for table in tables:
            tdir = os.path.join(data_dir, table)
            if not os.listdir(tdir):
                raise RuntimeError(f"no output produced for table {table}")
    elif not any(os.listdir(os.path.join(data_dir, t)) for t in tables
                 if os.path.isdir(os.path.join(data_dir, t))):
        raise RuntimeError(
            f"range {first},{last} produced no output for any table")


def generate_data_hosts(data_dir: str, scale: float, parallel: int,
                        hosts: list[str], update: int = 0,
                        overwrite: bool = False) -> None:
    """Multi-host fanout: assign chunk ranges to hosts via ssh.

    The TPU-native replacement for the reference's Hadoop MR wrapper
    (GenTable.java): no cluster framework, one ssh per host with a chunk
    range; hosts share a filesystem or sync afterwards. The coordinator
    prepares the shared dir ONCE (range runs never wipe it — a stale dir
    mixed with new chunks would duplicate rows downstream).
    """
    if os.path.exists(data_dir) and os.listdir(data_dir):
        if not overwrite:
            raise FileExistsError(
                f"{data_dir} is not empty; pass overwrite to replace")
        shutil.rmtree(data_dir, ignore_errors=True)
    os.makedirs(data_dir, exist_ok=True)
    n = len(hosts)
    procs = []
    for i, host in enumerate(hosts):
        first = parallel * i // n + 1
        last = parallel * (i + 1) // n
        if first > last:
            continue
        # NOTE: no --overwrite — range runs append into the shared dir; a
        # wipe here would race the other hosts' output away
        sub = (f"python -m nds_tpu.datagen local {data_dir} --scale {scale} "
               f"--parallel {parallel} --range {first},{last}")
        if update:
            sub += f" --update {update}"
        procs.append(subprocess.Popen(["ssh", host, sub]))
    failed = [p.args for p in procs if p.wait() != 0]
    if failed:
        raise RuntimeError(f"host generation failed: {failed}")


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="nds_tpu.datagen",
        description="Generate NDS benchmark data with the native generator")
    p.add_argument("mode", choices=["local", "hosts"],
                   help="local: fork processes; hosts: ssh fanout")
    p.add_argument("data_dir")
    p.add_argument("--scale", type=float, default=1.0)
    p.add_argument("--parallel", type=int, default=os.cpu_count() or 1)
    p.add_argument("--range", dest="range_", default=None,
                   help="chunk subrange 'first,last' for incremental runs")
    p.add_argument("--update", type=int, default=0,
                   help="generate refresh (maintenance) set K instead")
    p.add_argument("--overwrite", action="store_true")
    p.add_argument("--hosts", default="",
                   help="comma-separated host list for hosts mode")
    a = p.parse_args(argv)

    rng = valid_range(a.range_, a.parallel) if a.range_ else None
    if a.mode == "local":
        generate_data_local(a.data_dir, a.scale, a.parallel, rng,
                            a.update, overwrite=a.overwrite)
    else:
        hosts = [h for h in a.hosts.split(",") if h]
        if not hosts:
            p.error("hosts mode requires --hosts")
        generate_data_hosts(a.data_dir, a.scale, a.parallel, hosts, a.update,
                            overwrite=a.overwrite)
    return 0


if __name__ == "__main__":
    sys.exit(main())
