"""Session: the user-facing entry point of the SQL engine.

Plays the role SparkSession plays in the reference's workload jobs
(reference nds_power.py:221-245 builds the session and registers temp views;
run_one_query at :124-134 is `spark.sql(q).collect()`). Here tables register
from Arrow/Parquet and `sql()` parses, plans, and executes on the JAX engine.
"""
from __future__ import annotations

import os
import threading
from typing import Callable, Optional

import pyarrow as pa
import pyarrow.dataset as pa_dataset

from ..config import EngineConfig
from ..obs import metrics as _metrics
from ..obs.stats import ExecStats
from ..obs.trace import TRACER
from ..sql import parse_sql
from .column import Table
from .executor import Executor
from .planner import Catalog, Planner
from . import arrow_bridge


def _engine_table_stats(t: Table) -> dict:
    """{column: (lo, hi)} for an already-materialized engine Table (view
    registrations): engine units by construction."""
    import numpy as np

    from .column import is_dec

    out: dict = {}
    for name, c in zip(t.names, t.columns):
        if not (c.dtype in ("int", "date") or is_dec(c.dtype)):
            continue
        data = np.asarray(c.data)[c.validity]
        if data.size:
            out[name] = (int(data.min()), int(data.max()))
    return out


def _enc_tag(enc) -> str:
    """Human/JSON-stable encoding tag for stats/bench reporting."""
    if isinstance(enc, tuple):
        return f"{enc[0]}[{enc[1]}]"
    return str(enc)


def _engine_col_enc_stat(t: Table, col: str):
    """Encoding stats (cardinality/runs) for one column of an engine
    Table (view registrations): engine units by construction."""
    from .column import is_dec

    i = t.names.index(col)
    c = t.columns[i]
    if not (c.dtype in ("int", "date") or is_dec(c.dtype)):
        return None
    import numpy as np

    return arrow_bridge.column_enc_stat_values(
        np.asarray(c.data), c.validity)


def _and_conjuncts(node):
    """Top-level AND conjuncts of a WHERE AST (shared by the partition and
    file-stats delete pruners)."""
    from ..sql import ast_nodes as A
    if isinstance(node, A.BinOp) and node.op == "and":
        yield from _and_conjuncts(node.left)
        yield from _and_conjuncts(node.right)
    else:
        yield node


class Session:
    def __init__(self, config: Optional[EngineConfig] = None):
        self.config = config or EngineConfig()
        # -- concurrency contract (the query service, nds_tpu/service) ------
        # _sql_lock serializes whole statements: sql()/execute() bodies run
        # one at a time, so the executor, streaming state, and the
        # last_exec_stats* views stay consistent under multi-threaded entry
        # (service_run returns result+stats atomically under it).
        # _lock guards the lazily-built shared caches that CONCURRENT
        # non-statement work reads/writes — the service's planner threads
        # hit column_stats/column_enc_stats/load_table while the device
        # lane executes; both locks are RLocks, ordering _sql_lock -> _lock.
        self._sql_lock = threading.RLock()
        self._lock = threading.RLock()
        if self.config.use_jax:
            # the record pass needs the host CPU backend next to the
            # accelerator, however JAX_PLATFORMS was set
            from ..config import ensure_host_backend
            ensure_host_backend()
        if self.config.fault_points:
            # arm the engine-level fault registry from config/property file
            # (nds.tpu.fault_points=point:action,...): the resilience layer's
            # injectable failures — see nds_tpu/resilience.py
            from ..resilience import FAULTS
            FAULTS.configure(self.config.fault_points)
        if self.config.query_log or self.config.query_log_path:
            # arm the process-wide durable query log (obs/query_log.py);
            # clear=False — a second session must not wipe the ring the
            # first one already filled
            from ..obs.query_log import QUERY_LOG
            QUERY_LOG.configure(
                enabled=True, capacity=self.config.query_log_capacity,
                path=self.config.query_log_path or None,
                max_bytes=self.config.query_log_max_bytes,
                max_files=self.config.query_log_max_files, clear=False)
        self.warehouse = None  # attached via attach_warehouse for DML
        self._loaders: dict[str, Callable[[], Table]] = {}
        self._schemas: dict[str, tuple[list[str], list[str]]] = {}
        self._est_rows: dict[str, int] = {}
        # declared single-column unique keys per table (late-materialization
        # legality); NDS table names default from schema.UNIQUE_KEYS
        self._unique_cols: dict[str, frozenset] = {}
        self._cache: dict[str, Table] = {}
        # optional streaming readers for out-of-core scans: name ->
        # fn(columns) yielding arrow tables/batches
        self._batch_sources: dict = {}
        # per-table column value-range stats for narrow-lane planning:
        # name -> callable() -> {column: (lo, hi) in engine units}, lazily
        # evaluated and cached (column_stats); registration/drop invalidates
        self._stats_sources: dict = {}
        self._col_stats: dict[str, dict] = {}
        # per-table per-column ENCODING stats (cardinality + run counts)
        # for encoded-execution planning: name -> callable(column) ->
        # {"distinct": ..., "runs": ...} or None, lazily evaluated and
        # cached per column (column_enc_stats); registration invalidates
        self._enc_stats_sources: dict = {}
        self._enc_stats: dict[str, dict] = {}
        # device-backend fallback observability, reset per sql() call
        self.last_fallbacks: list[str] = []
        # execution-mode/timing observability for the last sql() call:
        # last_exec_stats is the backward-compatible DICT VIEW of the typed
        # record in last_exec_stats_typed — both are installed by the single
        # builder _finish_exec_stats (obs.stats.ExecStats)
        self.last_exec_stats: dict = {}
        self.last_exec_stats_typed: Optional[ExecStats] = None
        # EXPLAIN ANALYZE (obs/profile.py): the PlanProfile of the last
        # profiled execution (explain_analyze() or config.profile_plans);
        # None until a statement runs profiled
        self.last_profile = None
        # raw per-run collection the streamed path always records (cheap
        # host counters it computes anyway: per-group walls + rows, per-
        # job partial/final rows, finalize wall) — the streamed profile
        # and ExecStats.node_stats are built from it
        self._last_stream_profile: Optional[dict] = None
        # label of the in-flight sql() call (runners pass the query name);
        # compiled programs inherit it: their spans, host annotations and
        # HLO module names carry it. _label_auto: no caller gave it, it is
        # _auto_label's hash of the SQL text (programs are then named from
        # their plan's fingerprint, executor.program_name)
        self._active_label: str = ""
        self._label_auto: bool = False
        # query-log statement context (_sql_locked sets both per call):
        # wall start + whether this statement cuts its own log row
        self._stmt_t0: float = 0.0
        self._stmt_log: bool = True
        # catalog generation: bumped on any (re-)registration so the device
        # executor's scan cache and compiled plans never serve stale data
        self._generation = 0
        # per-table generations beside the global counter: the semantic
        # result cache invalidates entries by the generations of the base
        # tables a plan actually touches, so re-registering table A never
        # evicts cached results over table B (the global counter stays the
        # stream-cache/compiled-plan key — those embed cross-table state)
        self._table_generations: dict[str, int] = {}
        # snapshot-pinned warehouse reads (warehouse.py _snapshots log):
        # per-table MANIFEST versions of the pinned warehouse version
        # (Warehouse.register_all fills both; empty/None when unpinned —
        # no snapshot log, warehouse_transactions off, or the writer
        # session mid-transaction). The result cache stamps entries with
        # these, so a cached result is provably from the snapshot the
        # reader pinned, not merely "same session generation".
        self._table_snapshot_versions: dict[str, int] = {}
        self._warehouse_version: Optional[int] = None
        # source-content fingerprints for warehouse registrations: lets
        # Warehouse.register_all skip tables whose snapshot files did not
        # change (a maintenance INSERT into store_sales must not bump the
        # other 23 tables' generations and cold their caches)
        self._source_files: dict[str, tuple] = {}
        # maintenance-delta subscribers (result_cache IVM): called as
        # fn(table, inserts=arrow|None, deletes=arrow|None) AFTER the
        # warehouse commit re-registers, under the statement lock
        self._delta_subscribers: list = []
        # optional attached semantic result cache (engine/result_cache.py)
        self.result_cache = None
        self._jax_exec = None
        self._jax_exec_gen = -1
        # out-of-core: per-query streaming state (rewritten plan + compiled
        # morsel programs + executor with its scan cache); None = known
        # not-streamable. Invalidated when the catalog generation moves OR
        # any streaming-relevant config field changes (_stream_config_key):
        # cached plans/sentinels embed late_materialization, chunk_rows,
        # shared_scan..., so a live-session toggle must not replay them.
        self._stream_cache: dict[str, Optional[dict]] = {}
        self._stream_cache_cfg: Optional[tuple] = None
        # sharded morsel execution (config.mesh_shards): the data-parallel
        # replica mesh streamed scan groups dispatch over, built lazily
        self._morsel_mesh_obj = None
        # morsel-boundary preemption (service fair scheduler): the query
        # service installs a hook the streamed path calls between scan
        # groups / morsels; None (the default) keeps the streamed loop
        # bit-identical to before the hook existed (one attribute read).
        # _in_preempt guards against recursive preemption while a nested
        # statement runs inside preempt_scope on the SAME thread (the
        # RLocks make the nested entry legal; depth stays <= 1).
        self._preempt_hook = None
        self._in_preempt = False

    def _morsel_shards(self) -> int:
        """Effective replica count for sharded morsel execution: 0 when the
        knob is off (mesh_shards unset or 1) — the single-chip path then
        runs bit-identically to before the knob existed."""
        n = int(self.config.mesh_shards or 0)
        return n if n > 1 else 0

    def _morsel_mesh(self):
        """The data-parallel "shards" mesh streamed morsels partition over
        (parallel/mesh.make_mesh — the standalone primitives' mesh is now
        the engine's entry point). Raises ValueError when the backend has
        fewer devices than config.mesh_shards (for virtual-device testing
        set XLA_FLAGS=--xla_force_host_platform_device_count)."""
        n = self._morsel_shards()
        if not n:
            return None
        if self._morsel_mesh_obj is None or \
                self._morsel_mesh_obj.devices.size != n:
            from ..parallel import make_mesh
            self._morsel_mesh_obj = make_mesh(n)
        return self._morsel_mesh_obj

    def _device_mesh(self):
        """Build the SPMD mesh from config.mesh_shape (None = single device).

        Multi-chip execution shards fact scans over this mesh and lets
        GSPMD partition the compiled plan (all_to_all = shuffle, all_gather
        = broadcast join, psum = partial-aggregate merge — the XLA-native
        equivalents of Spark's executor shuffle, SURVEY.md §5)."""
        if not self.config.mesh_shape:
            return None
        import numpy as np

        import jax
        from jax.sharding import Mesh
        shape = tuple(self.config.mesh_shape)
        n = int(np.prod(shape))
        devices = np.asarray(jax.devices()[:n]).reshape(shape)
        return Mesh(devices, self.config.mesh_axis_names[:len(shape)])

    def _jax_executor(self):
        """The session-held device executor: device-resident scan cache and
        compiled plans persist across the whole query stream (the reference
        keeps tables hot on the executors across the 103-query power run)."""
        cfg = self.config
        if self._jax_exec is None or self._jax_exec_gen != self._generation:
            from .jax_backend import JaxExecutor
            self._jax_exec = JaxExecutor(
                self.load_table, jit_plans=cfg.jit_plans,
                mesh=self._device_mesh(),
                shard_min_rows=cfg.shard_min_rows,
                segment_plan_nodes=cfg.segment_plan_nodes,
                segment_min_cte_nodes=cfg.segment_min_cte_nodes,
                segment_cache_entries=cfg.segment_cache_entries,
                scan_budget_bytes=int(cfg.scan_budget_gb * (1 << 30)))
            self._jax_exec_gen = self._generation
        return self._jax_exec

    def _dec_as_int(self) -> bool:
        """decimal_physical="i64": decimal columns load as exact scaled
        int64 ("decN" logical dtype) instead of f64 (SURVEY.md §7 scaled-
        int64 decimal plan; reference DecimalType, nds/nds_schema.py:43-47).
        """
        return self.config.decimal_physical == "i64"

    def _set_unique_cols(self, name: str, col_names,
                         unique_cols) -> None:
        """Record the table's declared single-column unique keys.

        None (the default) consults schema.UNIQUE_KEYS — NDS dimension
        surrogate keys are unique by the TPC-DS spec, so warehouse/power
        registrations get them automatically; an explicit tuple (possibly
        empty) overrides, so synthetic tables opt in or out deliberately."""
        if unique_cols is None:
            from ..schema import UNIQUE_KEYS
            unique_cols = UNIQUE_KEYS.get(name, ())
        have = set(col_names)
        self._unique_cols[name] = frozenset(
            c for c in unique_cols if c in have)

    def _bump_generation(self, name: str) -> None:
        """One (re-)registration or drop of `name`: the global generation
        moves (stream cache / compiled plans / executor scan cache) AND the
        table's own generation moves (result-cache invalidation scope)."""
        self._generation += 1
        self._table_generations[name] = \
            self._table_generations.get(name, 0) + 1

    def table_generation(self, name: str) -> int:
        """Current per-table catalog generation (0 = never registered)."""
        return self._table_generations.get(name, 0)

    def table_snapshot_version(self, name: str) -> Optional[int]:
        """Manifest version of `name` under the pinned warehouse
        snapshot, or None when the table's registration is unpinned
        (non-warehouse source, no snapshot log, or mid-transaction)."""
        return self._table_snapshot_versions.get(name)

    def warehouse_version(self) -> Optional[int]:
        """The warehouse version this session's registrations are
        pinned to (None = unpinned/manifest-latest)."""
        return self._warehouse_version

    def attach_result_cache(self, cache) -> None:
        """Bind a semantic ResultCache (engine/result_cache.py): the cache
        reads per-table generations for invalidation and subscribes to
        maintenance deltas for incremental view maintenance. Idempotent."""
        self.result_cache = cache
        if cache.apply_delta not in self._delta_subscribers:
            self._delta_subscribers.append(cache.apply_delta)

    def _publish_table_delta(self, table: str, inserts=None,
                             deletes=None) -> None:
        """Hand one maintenance statement's row delta to every subscriber
        (called after the warehouse commit re-registered the table, so
        subscribers see the post-statement catalog generations). Subscriber
        failures degrade to invalidation inside the subscriber — a delta
        must never fail the DML statement that produced it."""
        if not self._delta_subscribers or (inserts is None
                                           and deletes is None):
            return
        for fn in list(self._delta_subscribers):
            fn(table, inserts=inserts, deletes=deletes)

    # -- registration -------------------------------------------------------
    def register_arrow(self, name: str, table: pa.Table,
                       est_rows: Optional[int] = None,
                       unique_cols: Optional[tuple] = None) -> None:
        dec = self._dec_as_int()
        names, dtypes = arrow_bridge.engine_schema(table.schema, dec)
        self._schemas[name] = (names, dtypes)
        self._set_unique_cols(name, names, unique_cols)
        self._est_rows[name] = est_rows if est_rows is not None else table.num_rows
        self._loaders[name] = lambda columns=None, t=table, dec=dec: \
            arrow_bridge.from_arrow(t.select(list(columns)) if columns else t,
                                    dec)

        def batches(columns, t=table):
            yield t.select(list(columns)) if columns else t
        self._batch_sources[name] = batches
        self._stats_sources[name] = \
            lambda t=table, dec=dec: arrow_bridge.table_column_stats(t, dec)
        self._enc_stats_sources[name] = \
            lambda col, t=table, dec=dec: \
            arrow_bridge.column_enc_stat(t.column(col), dec)
        self._drop_cached(name)
        self._bump_generation(name)

    def register_parquet(self, name: str, path: str,
                         est_rows: Optional[int] = None,
                         unique_cols: Optional[tuple] = None) -> None:
        """Register a parquet file or partitioned directory as a table."""
        dataset = pa_dataset.dataset(path, format="parquet",
                                     partitioning="hive")
        # re-open with dictionary pass-through for the fully dictionary-
        # encoded string columns (metadata probe): the staging thread then
        # receives codes + dictionary instead of re-encoding every morsel
        fmt = arrow_bridge.parquet_dataset_format(list(dataset.files))
        if fmt is not None:
            dataset = pa_dataset.dataset(path, format=fmt,
                                         partitioning="hive")
        schema = dataset.schema
        dec = self._dec_as_int()
        names, dtypes = arrow_bridge.engine_schema(schema, dec)
        self._schemas[name] = (names, dtypes)
        self._set_unique_cols(name, names, unique_cols)
        if est_rows is None:
            est_rows = dataset.count_rows()
        self._est_rows[name] = est_rows

        def load(columns=None, ds=dataset, dec=dec):
            cols = list(columns) if columns is not None else None
            return arrow_bridge.from_arrow(ds.to_table(columns=cols), dec)
        self._loaders[name] = load

        def batches(columns, ds=dataset):
            cols = list(columns) if columns is not None else None
            yield from ds.to_batches(columns=cols)
        self._batch_sources[name] = batches
        # parquet row-group METADATA carries per-column min/max: lane
        # planning costs one metadata pass, no data read
        self._stats_sources[name] = \
            lambda ds=dataset, dec=dec: arrow_bridge.parquet_column_stats(
                list(ds.files), dec)
        # encoding stats need the values (cardinality/runs have no parquet
        # metadata): ONE vectorized single-column read, cached per column
        # per registration generation
        self._enc_stats_sources[name] = \
            lambda col, ds=dataset, dec=dec: arrow_bridge.column_enc_stat(
                ds.to_table(columns=[col]).column(col), dec)
        self._drop_cached(name)
        self._bump_generation(name)

    def register_csv(self, name: str, path: str, schema: pa.Schema,
                     est_rows: Optional[int] = None,
                     delimiter: str = "|",
                     unique_cols: Optional[tuple] = None) -> None:
        """Register a pipe-delimited file or directory of files lazily
        (the reference registers raw CSV as Spark temp views with explicit
        schema, nds_power.py:78-105)."""
        import pyarrow.csv as pa_csv

        files = ([os.path.join(path, f) for f in sorted(os.listdir(path))]
                 if os.path.isdir(path) else [path])
        dec = self._dec_as_int()
        names, dtypes = arrow_bridge.engine_schema(schema, dec)
        self._schemas[name] = (names, dtypes)
        self._set_unique_cols(name, names, unique_cols)
        self._est_rows[name] = est_rows if est_rows is not None else 10000

        def load(columns=None, files=tuple(files), schema=schema, dec=dec):
            convert = pa_csv.ConvertOptions(
                column_types={f.name: f.type for f in schema},
                null_values=[""], strings_can_be_null=True,
                include_columns=list(columns) if columns else None)
            read = pa_csv.ReadOptions(column_names=[f.name for f in schema])
            parse = pa_csv.ParseOptions(delimiter=delimiter)
            parts = [pa_csv.read_csv(f, read_options=read,
                                     parse_options=parse,
                                     convert_options=convert)
                     for f in files if os.path.getsize(f) > 0]
            return arrow_bridge.from_arrow(pa.concat_tables(parts), dec)
        self._loaders[name] = load

        def batches(columns, files=tuple(files), schema=schema):
            convert = pa_csv.ConvertOptions(
                column_types={f.name: f.type for f in schema},
                null_values=[""], strings_can_be_null=True,
                include_columns=list(columns) if columns else None)
            read = pa_csv.ReadOptions(column_names=[f.name for f in schema])
            parse = pa_csv.ParseOptions(delimiter=delimiter)
            for f in files:
                if os.path.getsize(f) > 0:
                    yield pa_csv.read_csv(f, read_options=read,
                                          parse_options=parse,
                                          convert_options=convert)
        self._batch_sources[name] = batches
        self._drop_cached(name)
        self._bump_generation(name)

    def register_view(self, name: str, table: Table,
                      dtypes: Optional[list[str]] = None,
                      unique_cols: Optional[tuple] = None) -> None:
        """Register an engine Table (e.g. a temp view) directly."""
        dts = dtypes or [c.dtype for c in table.columns]
        self._schemas[name] = (list(table.names), dts)
        self._set_unique_cols(name, table.names, unique_cols)
        self._est_rows[name] = table.num_rows
        self._loaders[name] = lambda columns=None, t=table: \
            t if columns is None else t.select(list(columns))
        self._stats_sources[name] = lambda t=table: _engine_table_stats(t)
        self._enc_stats_sources[name] = \
            lambda col, t=table: _engine_col_enc_stat(t, col)
        self._drop_cached(name)
        self._cache[(name, None)] = table
        self._bump_generation(name)

    def drop(self, name: str) -> None:
        self._schemas.pop(name, None)
        self._loaders.pop(name, None)
        self._batch_sources.pop(name, None)
        self._stats_sources.pop(name, None)
        self._enc_stats_sources.pop(name, None)
        self._drop_cached(name)
        self._est_rows.pop(name, None)
        self._unique_cols.pop(name, None)
        self._source_files.pop(name, None)
        self._table_snapshot_versions.pop(name, None)
        self._bump_generation(name)

    def table_names(self) -> list[str]:
        return list(self._schemas)

    def _drop_cached(self, name: str) -> None:
        with self._lock:
            for k in [k for k in self._cache if k[0] == name]:
                del self._cache[k]
            self._col_stats.pop(name, None)
            self._enc_stats.pop(name, None)

    def column_stats(self, name: str) -> dict:  # lint: thread-entry (service planner threads read stats concurrently)
        """{column: (lo, hi)} value-range stats in ENGINE units (scaled
        ints for decimals, epoch days for dates) for a registered table;
        {} when the registration has no stats source. Lazily computed and
        cached per registration generation — streaming derives the static
        per-column upload lane spec from these (device.plan_lanes), and the
        plan verifier proves declared lanes against the same ranges.
        Thread-safe: the generation cache is read and written under the
        session state lock (service planner threads race the device lane)."""
        with self._lock:
            if name in self._col_stats:
                return self._col_stats[name]
            src = self._stats_sources.get(name)
            stats = {}
            if src is not None:
                try:
                    stats = src() or {}
                except Exception:
                    stats = {}  # stats are an optimization, never a failure
            self._col_stats[name] = stats
            return stats

    def column_enc_stats(self, name: str, columns=None) -> dict:  # lint: thread-entry (service planner threads read stats concurrently)
        """{column: {"distinct": sorted int64 array or None, "runs": int}}
        encoding stats for (a subset of) a registered table's columns, in
        ENGINE units; {} when the registration has no encoding-stats
        source. Lazily computed and cached PER COLUMN per registration
        generation — only the columns a scan group actually streams pay
        the (one-time) cardinality/run pass. Feeds device.plan_encodings
        and the verifier's "encoding" findings. Thread-safe like
        column_stats: cache writes happen under the session state lock."""
        with self._lock:
            src = self._enc_stats_sources.get(name)
            if src is None:
                return {}
            if columns is None:
                columns = self._schemas.get(name, ([], []))[0]
            cache = self._enc_stats.setdefault(name, {})
            for c in columns:
                if c in cache:
                    continue
                try:
                    cache[c] = src(c)
                except Exception:
                    cache[c] = None  # stats are an optimization, never fatal
            return {c: cache[c] for c in columns if cache.get(c)}

    @staticmethod
    def _manifest_enc_source(wt, files, dataset, dec):
        """Per-column encoding-stats source for a warehouse registration:
        manifest-recorded per-file stats aggregate with no data read;
        columns the manifest predates fall back to one vectorized
        single-column dataset read."""
        agg: dict = {}

        def src(col):
            if "done" not in agg:
                try:
                    agg["stats"] = wt.column_enc_stats(list(files))
                except Exception:
                    agg["stats"] = {}
                agg["done"] = True
            st = agg["stats"].get(col)
            if st is not None:
                return st
            return arrow_bridge.column_enc_stat(
                dataset.to_table(columns=[col]).column(col), dec)
        return src

    def iter_morsels(self, name: str, columns: list[str], rows: int):
        """Yield host Tables of at most `rows` rows each, WITHOUT
        materializing the whole table (out-of-core scans). Parquet datasets
        stream record batches; arrow tables slice zero-copy; CSV falls back
        to per-file reads."""
        import pyarrow as pa

        def flush(pending):
            # a single pending slice (aligned source batches, the common
            # parquet row-group case) passes through zero-copy — concat
            # would re-chunk and copy for nothing
            return pending[0] if len(pending) == 1 \
                else pa.concat_tables(pending)

        consumed = 0    # source batches pulled so far (morsel.read's attr)

        def emit(batches):
            """Re-chunk a stream of arrow tables into `rows`-sized morsels."""
            nonlocal consumed
            pending: list[pa.Table] = []
            count = 0
            for b in batches:
                consumed += 1
                t = pa.Table.from_batches([b]) if isinstance(
                    b, pa.RecordBatch) else b
                while t.num_rows:
                    take = min(rows - count, t.num_rows)
                    pending.append(t.slice(0, take))
                    t = t.slice(take)
                    count += take
                    if count == rows:
                        yield flush(pending)
                        pending, count = [], 0
            if pending:
                yield flush(pending)

        src = self._batch_sources.get(name)
        if src is not None:
            batches = src(columns)
        else:  # fallback: full load, sliced (correct, not memory-bounded)
            batches = [arrow_bridge.to_arrow(self.load_table(name, columns))]
        # an explicit next() so that each span closes before the yield: the
        # read is the main thread's wait for the next re-chunked Arrow part
        # (the dataset scan's batches, slice, concat), the conversion the
        # dictionary / validity / decimal materialization under the GIL
        parts = emit(batches)
        dec = self._dec_as_int()
        while True:
            before = consumed
            with TRACER.span("morsel.read", cat="host", table=name) as sp:
                part = next(parts, None)
                nrows, nbytes = (part.num_rows, part.nbytes) \
                    if part is not None else (0, 0)
                sp.set(rows=nrows, bytes=nbytes, batches=consumed - before)
            if part is None:
                return
            _metrics.BYTES_DECODED.inc(nbytes)
            with TRACER.span("morsel.from_arrow", cat="host",
                             rows=part.num_rows,
                             columns=part.num_columns) as sp:
                table = arrow_bridge.from_arrow(part, dec, span=sp)
            yield table

    def load_table(self, name: str, columns=None) -> Table:  # lint: thread-entry (streaming staging threads + service lanes load concurrently)
        """Load a table, optionally projected to `columns` (scan pruning:
        fact tables carry ~23 columns but a query touches a handful — the
        reference gets this from parquet column projection in Spark scans).
        Cached per projection; a cached full table serves any subset.
        Thread-safe: the projection cache is populated under the session
        state lock (staging threads and service lanes load concurrently)."""
        with self._lock:
            key = (name, tuple(columns) if columns is not None else None)
            if key in self._cache:
                return self._cache[key]
            if columns is not None and (name, None) in self._cache:
                full = self._cache[(name, None)]
                idx = {n: i for i, n in enumerate(full.names)}
                sub = Table(list(columns),
                            [full.columns[idx[c]] for c in columns])
                self._cache[key] = sub
                return sub
            self._cache[key] = self._loaders[name](columns)
            return self._cache[key]

    # -- query --------------------------------------------------------------
    def _catalog(self) -> Catalog:
        return Catalog({name: (sch[0], sch[1],
                               self._est_rows.get(name, 1000))
                        for name, sch in self._schemas.items()},
                       dec_enabled=self._dec_as_int(),
                       unique_cols=dict(self._unique_cols),
                       late_mat=self.config.late_materialization,
                       late_mat_min_rows=self.config.late_mat_min_rows,
                       verify_plans=self.config.verify_plans,
                       stats_source=self.column_stats)

    def sql(self, query: str, backend: Optional[str] = None,  # lint: thread-entry (service clients call sql concurrently)
            label: Optional[str] = None) -> Table:
        """Run a query; backend "jax" (device) or "numpy" (host oracle).

        Defaults to the config's use_jax flag — the device path is the
        product path, the numpy path is the differential-validation oracle
        (the role CPU-Spark plays against GPU-Spark in the reference,
        nds/nds_validate.py).

        label: human-stable query name for observability (runners pass
        "query9" etc.); spans and per-program device-time attribution key
        on it. Defaults to a short content hash of the SQL text.

        Thread-safe: concurrent callers serialize on _sql_lock (whole
        statements are the unit). Note last_exec_stats* describe the last
        COMPLETED statement of ANY caller — concurrent callers wanting
        their own stats use service_run (result + stats atomically).

        ``system.*`` statements (obs/system_tables.py) route to the
        host-only introspection path WITHOUT taking the statement lock:
        an operator poll must answer while the device lane is mid-
        statement, and must never perturb the workload it measures. The
        disabled-path cost is this one substring branch.
        """
        if "system." in query or "SYSTEM." in query:
            result = self._maybe_system_query(query, label)
            if result is not None:
                return result
        with self._sql_lock:
            return self._sql_locked(query, backend, label)

    def abandon_inflight(self) -> None:
        """A deadline just ABANDONED a worker thread mid-statement
        (resilience.run_with_deadline: python threads cannot be killed).
        The zombie may still hold this session's statement/state locks —
        install fresh ones so the stream continues immediately instead of
        queueing behind the zombie's hang. The zombie then races the next
        statement exactly as it did before the locks existed (the
        documented containment posture: bounded by the hang, the caller
        already recorded the query Failed); runners that cannot accept
        that race should use process isolation (throughput process mode).
        """
        self._sql_lock = threading.RLock()
        self._lock = threading.RLock()

    def service_run(self, query: str, backend: Optional[str] = None,
                    label: Optional[str] = None, plan=None):
        """Query-service entry: like sql() but returns (Table, ExecStats)
        ATOMICALLY (per-query state isolation under multi-client entry —
        reading last_exec_stats after sql() returns races other clients),
        and accepts a pre-built plan from the service's planner stage so
        a first-sighting execution skips re-parsing/re-planning."""
        with self._sql_lock:
            # log_row=False: the SERVICE cuts the query-log row per ticket
            # (tenant/template/phase walls/error class), so the session
            # must not log a bare duplicate of the same statement
            table = self._sql_locked(query, backend, label, plan=plan,
                                     log_row=False)
            return table, self.last_exec_stats_typed

    # -- morsel-boundary preemption (service fair scheduler) ------------------
    def _maybe_preempt(self) -> None:
        """Yield point the streamed path calls between scan groups and
        between morsels: when the query service installed a preemption
        hook, hand the device lane over so short interactive tickets run
        NOW instead of convoying behind this scan's whole wall. No hook
        (the default) is one attribute read — the streamed loop stays
        bit-identical to before the hook existed. Never re-enters while a
        preempted statement is already running (depth <= 1)."""
        hook = self._preempt_hook
        if hook is not None and not self._in_preempt:
            hook()

    def preempt_scope(self):
        """Context manager the service wraps around a NESTED statement
        dispatched at a yield point: saves/restores every statement-scoped
        attribute ``_sql_locked`` writes (the outer streamed statement
        must resume exactly the view it had) plus the device-memory peak
        window, and arms ``_in_preempt`` so the nested statement cannot
        itself be preempted. The nested dispatch runs on the SAME thread
        that holds ``_sql_lock`` — the RLock re-entry is what makes the
        yield legal without unwinding the outer stream's state."""
        import contextlib

        @contextlib.contextmanager
        def _scope():
            from ..obs.profile import DEVICE_MEM
            saved = (self.last_fallbacks, self.last_exec_stats,
                     self.last_exec_stats_typed, self.last_profile,
                     self._last_stream_profile, self._active_label,
                     self._label_auto, self._stmt_t0, self._stmt_log)
            win = DEVICE_MEM.window_peak()
            self._in_preempt = True
            try:
                yield self
            finally:
                self._in_preempt = False
                (self.last_fallbacks, self.last_exec_stats,
                 self.last_exec_stats_typed, self.last_profile,
                 self._last_stream_profile, self._active_label,
                 self._label_auto, self._stmt_t0, self._stmt_log) = saved
                # restore the outer statement's peak window: the nested
                # statement re-marked it, and the outer stream's
                # mem_peak_bytes must cover its own whole wall
                DEVICE_MEM.restore_window(win)
        return _scope()

    def explain_analyze(self, query: str, backend: Optional[str] = None,
                        label: Optional[str] = None):
        """EXPLAIN ANALYZE: execute ``query`` in profiled mode and return
        its :class:`~nds_tpu.obs.profile.PlanProfile` — the annotated plan
        tree (per-node wall/rows/bytes with stable TypeName#k identities),
        the estimate-vs-actual cardinality audit, and the device-memory
        watermark block. The result Table rides on ``profile.table`` and
        is BIT-IDENTICAL to ``sql(query)``: in-core plans walk the same
        executor eagerly node by node (children memoized, so each node's
        wall is its own work), streamed plans run the unchanged morsel
        path and only read counters. One statement only; the standing
        flag is ``EngineConfig.profile_plans`` (``power --explain``)."""
        with self._sql_lock:
            prev = self.config.profile_plans
            self.config.profile_plans = True
            try:
                self._sql_locked(query, backend, label)
            finally:
                self.config.profile_plans = prev
            return self.last_profile

    # -- system tables (obs/system_tables.py) --------------------------------
    def _maybe_system_query(self, query: str,
                            label: Optional[str]) -> Optional[Table]:
        """Route a statement that mentions ``system.`` — returns the
        result Table when every referenced table is a system table, None
        when none is (caller proceeds on the normal path; the marker was
        a literal/comment), and raises on a mix: the host snapshot
        executor must never pull warehouse-scale user tables."""
        from ..obs import system_tables as _st
        ast = parse_sql(query)
        refs = _st.collect_table_refs(ast)
        sys_refs = {r for r in refs if _st.is_system_table(r)}
        if not sys_refs:
            return None
        if refs - sys_refs:
            raise ValueError(
                "system.* tables cannot join user tables "
                f"(statement references {sorted(refs - sys_refs)}); "
                "run the introspection query separately")
        return self._system_query_ast(ast, sys_refs, label)

    def system_query(self, query: str, label: Optional[str] = None
                     ) -> Table:
        """Run one ``system.*`` introspection statement on the HOST
        executor over atomic registry snapshots — no statement lock, no
        planner workers, no device dispatch, so it answers during
        overload, open circuits, and mid-statement device work without
        perturbing any of them. Raises when the statement touches a
        non-system table."""
        from ..obs import system_tables as _st
        ast = parse_sql(query)
        refs = _st.collect_table_refs(ast)
        bad = {r for r in refs if not _st.is_system_table(r)}
        if bad or not refs:
            raise ValueError(
                f"system_query serves system.* tables only (got "
                f"{sorted(refs) or 'no tables'})")
        return self._system_query_ast(ast, refs, label)

    def _system_query_ast(self, ast, refs: set,
                          label: Optional[str]) -> Table:
        """Plan against the dedicated system catalog and execute on the
        host backend over per-statement snapshots. Deliberately out of
        band: no QUERIES_RUN/last_exec_stats/query-log movement — an
        operator poll must not clobber a concurrent client's stats view
        or log itself into the surface it is reading."""
        from ..obs import system_tables as _st
        _metrics.SYSTEM_QUERIES.inc()
        with TRACER.span("system_query", label=label or "system"):
            catalog = Catalog(_st.catalog_entries(), dec_enabled=False,
                              late_mat=False, verify_plans="off")
            plan = Planner(catalog).plan_query(ast)
            # snapshots cut NOW, one per referenced table, each under its
            # own registry lock (atomic rows; see system_tables docstring)
            snaps = {name: _st.snapshot_engine_table(name, self)
                     for name in refs}

            def load(name, columns=None):
                t = snaps[name]
                if columns is None:
                    return t
                idx = {n: i for i, n in enumerate(t.names)}
                return Table(list(columns),
                             [t.columns[idx[c]] for c in columns])
            return Executor(load).execute(plan)

    def _sql_locked(self, query: str, backend: Optional[str],
                    label: Optional[str], plan=None,
                    log_row: bool = True) -> Table:
        import time as _time
        use_jax = (backend == "jax") if backend else self.config.use_jax
        self.last_fallbacks = []
        self.last_exec_stats = {}
        self.last_exec_stats_typed = None
        self._active_label = label or self._auto_label(query)
        self._label_auto = not label
        # query-log context for _finish_exec_stats: statement wall start
        # + whether THIS statement cuts its own row (the service logs per
        # ticket instead — richer context, no duplicates)
        self._stmt_t0 = _time.perf_counter()
        self._stmt_log = log_row
        from ..obs.profile import DEVICE_MEM
        DEVICE_MEM.mark_window()   # per-query device-memory peak window
        _metrics.QUERIES_RUN.inc()
        if self.config.profile_plans and plan is None:
            return self._profiled_locked(query, use_jax)
        with TRACER.span("query", label=self._active_label,
                         backend="jax" if use_jax else "numpy"):
            if use_jax:
                from .jax_backend import to_host
                if self.config.out_of_core:
                    result = self._sql_streaming(query)
                    if result is not None:
                        return result
                jexec = self._jax_executor()
                jexec.query_label = self._active_label
                jexec.query_label_auto = self._label_auto

                def factory():
                    if plan is not None:
                        return plan
                    with TRACER.span("plan", label=self._active_label):
                        with TRACER.span("parse"):
                            ast = parse_sql(query)
                        return Planner(self._catalog()).plan_query(ast)
                result = to_host(jexec.run_query(("sql", query), factory))
                self.last_fallbacks = list(jexec.fallback_nodes)
                # the REASON a query is not fully on-device (operator + why)
                # rides the stats so runners can enumerate the remaining
                # host/in-core queries per run without scraping status text
                self._finish_exec_stats(ExecStats.from_executor(
                    jexec.last_stats, self.last_fallbacks),
                    rows=result.num_rows)
                return result
            with TRACER.span("plan", label=self._active_label):
                if plan is None:
                    plan = Planner(self._catalog()).plan_query(
                        parse_sql(query))
            executor = Executor(self.load_table)
            return executor.execute(plan)

    @staticmethod
    def _auto_label(query: str) -> str:
        import hashlib
        return "q" + hashlib.sha1(query.encode()).hexdigest()[:8]

    def _name_fingerprint(self, plans) -> Optional[str]:
        """For a morsel program's name (executor.program_name): the plans'
        fingerprint where the statement's label is the hash of its text,
        None where the caller gave the label."""
        if not self._label_auto:
            return None
        from .jax_backend.executor import _plan_fingerprint
        return _plan_fingerprint(plans, mat_by_identity=False)

    # -- EXPLAIN ANALYZE (obs/profile.py) ------------------------------------
    def _profiled_locked(self, query: str, use_jax: bool) -> Table:
        """Profiled execution of one statement (config.profile_plans /
        explain_analyze): a streamable query runs the UNCHANGED morsel
        path (bit-identity by construction — profiling only reads the
        counters the stream already computes), everything else walks the
        plan eagerly node by node through the existing executor. Installs
        self.last_profile and returns the result Table."""
        import time as _time

        _metrics.PROFILED_QUERIES.inc()
        with TRACER.span("query", label=self._active_label,
                         backend="jax" if use_jax else "numpy",
                         profiled=True):
            if use_jax and self.config.out_of_core:
                t0 = _time.perf_counter()
                result = self._sql_streaming(query)
                if result is not None:
                    prof = self._stream_profile(
                        result, (_time.perf_counter() - t0) * 1000.0)
                    return self._finish_profile(prof, result)
            with TRACER.span("plan", label=self._active_label):
                plan = Planner(self._catalog()).plan_query(parse_sql(query))
            prof, result = self._profile_walk(plan, use_jax)
        return self._finish_profile(prof, result)

    def _finish_profile(self, prof, result: Table) -> Table:
        """Audit + memory block + metrics for a freshly built profile;
        installs it as last_profile."""
        from ..obs import profile as _prof

        prof.findings = _prof.cardinality_audit(
            prof, self.config.profile_misestimate_ratio)
        if prof.findings:
            _metrics.CARDINALITY_MISESTIMATES.inc(len(prof.findings))
        st = self.last_exec_stats_typed
        prof.memory = _prof.memory_block(
            int(self.config.scan_budget_gb * (1 << 30))
            if self.config.scan_budget_gb > 0 else None)
        if st is not None and st.mem_peak_bytes is not None:
            prof.memory["query_peak_bytes"] = st.mem_peak_bytes
        prof.table = result
        self.last_profile = prof
        return result

    def _profile_walk(self, plan, use_jax: bool):
        """The eager node-by-node profiled walk: children-first execution
        through the EXISTING executor, so each node's wall measures only
        its own work (children are memoized) and the root result is the
        same eager evaluation a first-sighting record pass performs —
        bit-identical to compiled replay by the engine's record/replay
        discipline. Per-node rows are exact (alive counts); bytes are the
        node's device (or host) output footprint."""
        import contextlib
        import time as _time

        from ..obs import profile as _prof
        from ..obs.stats import ExecStats

        labels, children, order = _prof.plan_tree(plan)
        ests = _prof.estimate_rows(
            plan, lambda t: self._est_rows.get(t))
        prof = _prof.PlanProfile(
            query=self._active_label,
            backend="jax" if use_jax else "numpy",
            mode="in-core" if use_jax else "numpy",
            root=labels[id(plan)])
        node_rows: dict = {}
        t_all = _time.perf_counter()
        if use_jax:
            import jax as _jax

            from .jax_backend import to_host
            from .jax_backend.device import device_bytes
            jexec = self._jax_executor()
            jexec.query_label = self._active_label
            jexec.query_label_auto = self._label_auto
            jexec.fallback_nodes = []
            jexec._begin(plan)
            ctx = _jax.default_device(jexec._eager_device) \
                if jexec._eager_device is not None \
                else contextlib.nullcontext()
            with ctx:
                for node in order:
                    t0 = _time.perf_counter()
                    out = jexec.execute(node)
                    _jax.block_until_ready(out)
                    # the alive-count sync is profiled-mode work this node
                    # caused: it stays inside the node's wall, so per-node
                    # walls sum to the profiled total (>= 90% acceptance)
                    rows = int(_jax.device_get(out.count()))
                    wall = (_time.perf_counter() - t0) * 1000.0
                    lbl = labels[id(node)]
                    node_rows[lbl] = rows
                    prof.nodes[lbl] = _prof.NodeStat(
                        label=lbl, op=type(node).__name__,
                        detail=_prof.node_detail(node),
                        est_rows=ests.get(id(node)), rows=rows,
                        wall_ms=round(wall, 3), bytes=device_bytes(out),
                        children=children.get(lbl, []))
            prof.total_ms = round((_time.perf_counter() - t_all) * 1000.0,
                                  3)
            result = to_host(out)
            self.last_fallbacks = list(jexec.fallback_nodes)
        else:
            executor = Executor(self.load_table)
            for node in order:
                t0 = _time.perf_counter()
                out = executor.execute(node)
                wall = (_time.perf_counter() - t0) * 1000.0
                lbl = labels[id(node)]
                node_rows[lbl] = out.num_rows
                prof.nodes[lbl] = _prof.NodeStat(
                    label=lbl, op=type(node).__name__,
                    detail=_prof.node_detail(node),
                    est_rows=ests.get(id(node)), rows=out.num_rows,
                    wall_ms=round(wall, 3),
                    bytes=sum(getattr(c.data, "nbytes", 0)
                              for c in out.columns),
                    children=children.get(lbl, []))
            result = out
        if not prof.total_ms:
            prof.total_ms = round((_time.perf_counter() - t_all) * 1000.0,
                                  3)
        stats = ExecStats(mode="profiled", node_stats=node_rows,
                          device_ms=round(prof.profiled_ms(), 3),
                          fallback_reasons=list(self.last_fallbacks))
        self._finish_exec_stats(stats)
        return prof, result

    def _stream_profile(self, result: Table, total_ms: float):
        """Build the streamed-execution profile from the counters the
        morsel path just recorded (_last_stream_profile): per-group walls
        land on the group's scan nodes, per-job merge/final walls on the
        original aggregate nodes, the finalize wall on the root. Row
        counts are exact (host-side morsel/partial/final counts); nodes
        the stream never materializes individually carry no wall."""
        from ..obs import profile as _prof

        rec = self._last_stream_profile or {}
        plan = rec.get("plan")
        prof = _prof.PlanProfile(query=self._active_label, backend="jax",
                                 mode="streaming", total_ms=round(
                                     total_ms, 3))
        if plan is None:
            return prof
        from .plan import ScanNode
        labels, children, order = _prof.plan_tree(plan)
        ests = _prof.estimate_rows(plan, lambda t: self._est_rows.get(t))
        prof.root = labels[id(plan)]
        group_rows = {g["table"]: g for g in rec.get("groups", ())}
        agg_stats = {aid: j for j in rec.get("jobs", ())
                     for aid in [j["agg_id"]]}
        walled: set[str] = set()   # group wall lands on ONE scan per table
        for node in order:
            lbl = labels[id(node)]
            ns = _prof.NodeStat(
                label=lbl, op=type(node).__name__,
                detail=_prof.node_detail(node),
                est_rows=ests.get(id(node)),
                children=children.get(lbl, []))
            if isinstance(node, ScanNode) and node.table in group_rows:
                g = group_rows[node.table]
                ns.rows = g["rows"]
                if node.table not in walled:
                    walled.add(node.table)
                    ns.wall_ms = g["wall_ms"]
                    ns.bytes = g.get("bytes")
            elif id(node) in agg_stats:
                j = agg_stats[id(node)]
                ns.rows = j["final_rows"]
                ns.wall_ms = j["wall_ms"]
            if id(node) == id(plan):
                ns.rows = result.num_rows
                ns.wall_ms = (ns.wall_ms or 0.0) + rec.get(
                    "finalize_ms", 0.0)
            prof.nodes[lbl] = ns
        return prof

    def _finish_exec_stats(self, stats: ExecStats,
                           rows: Optional[int] = None,
                           log: Optional[bool] = None) -> None:
        """THE single point where a query's execution stats land (both the
        in-core executor path and the streaming path build an ExecStats and
        come through here): installs the typed record, its backward-
        compatible dict view, rolls the run into the process-wide
        metrics registry, and — when the durable query log is enabled —
        flattens the record into one O(row) log row (``rows`` carries the
        result row count when the caller has it; ``log`` overrides the
        statement's log_row flag — the service passes False for its
        last-dispatch view and logs per ticket instead)."""
        from ..obs.profile import DEVICE_MEM
        # device-memory watermarks: the statement's peak window was opened
        # in _sql_locked; headroom is measured against the HBM scan budget
        stats.mem_peak_bytes = DEVICE_MEM.window_peak()
        stats.mem_live_bytes = DEVICE_MEM.live
        if self.config.scan_budget_gb > 0:
            stats.mem_headroom_bytes = \
                int(self.config.scan_budget_gb * (1 << 30)) - \
                stats.mem_peak_bytes
        self.last_exec_stats_typed = stats
        self.last_exec_stats = stats.to_dict()
        from ..obs.query_log import QUERY_LOG
        if QUERY_LOG.enabled and \
                (self._stmt_log if log is None else log):
            import time as _time
            QUERY_LOG.record(
                stats, source="session", label=self._active_label,
                wall_ms=round((_time.perf_counter() - self._stmt_t0)
                              * 1000.0, 3) if self._stmt_t0 else None,
                rows=rows)
        if stats.fallback_reasons:
            _metrics.HOST_FALLBACKS.inc(len(stats.fallback_reasons))
        if stats.prefetch_error_details:
            _metrics.PREFETCH_ERRORS.inc(len(stats.prefetch_error_details))
        if stats.scan_passes:
            _metrics.SCAN_PASSES.inc(stats.scan_passes)
        if stats.morsels:
            _metrics.MORSELS.inc(stats.morsels)
        if stats.bytes_uploaded:
            _metrics.BYTES_UPLOADED.inc(stats.bytes_uploaded)
        if stats.re_records:
            _metrics.MORSEL_RE_RECORDS.inc(stats.re_records)
        if stats.collective_bytes:
            _metrics.COLLECTIVE_BYTES.inc(stats.collective_bytes)
        if stats.host_decode_ms:
            # the staging-thread wall, registry-visible per process (the
            # per-table split stays in the stats record)
            _metrics.HOST_DECODE_MS.inc(
                round(sum(stats.host_decode_ms.values()), 3))

    def _stream_config_key(self) -> tuple:
        """Streaming-state cache validity fingerprint: the cached rewritten
        plans, scan groups, compiled morsel programs, and not-streamable
        sentinels are all functions of the catalog generation AND these
        config fields — toggling any of them on a live session (A/B runs,
        tests) must not replay a stale entry."""
        cfg = self.config
        return (self._generation, cfg.out_of_core_min_rows, cfg.chunk_rows,
                cfg.stream_compact_rows, cfg.shared_scan,
                cfg.stream_fusion_max_branches, cfg.late_materialization,
                cfg.late_mat_min_rows, cfg.decimal_physical, cfg.use_jax,
                cfg.narrow_lanes, cfg.encoded_exec, tuple(cfg.mesh_shape),
                int(cfg.mesh_shards or 0))

    def _sql_streaming(self, query: str):  # lint: thread-entry (called under _sql_lock; stream-cache writes additionally take the state lock)
        """Out-of-core execution (generalized round 5, shared-scan round 7):
        every MAXIMAL streamable aggregate subtree in the plan — top-level,
        below joins, inside CTE bodies, scalar subqueries, with UNION ALL
        fact-channel branches — streams its big scan(s) through the device
        in chunk_rows morsels. All branches of a query that scan the SAME
        big table form one ScanGroup (streaming.plan_scan_groups): the
        union of their pruned column sets uploads once per morsel and each
        branch reads zero-copy views of the staged buffer, so q9-class
        plans with 15 scalar-subquery jobs over store_sales pay the scan +
        upload cost once instead of 15 times. Per-morsel partial aggregates
        merge on host (periodically compacted to bound memory for
        customer-grained groups), and a MaterializedNode replaces each
        aggregate subtree before the remaining (small) plan runs in-core.
        Reference analog: maxPartitionBytes chunked scans + shuffle spill,
        power_run_gpu.template. Returns None if nothing is streamable."""
        from . import streaming

        cfg_key = self._stream_config_key()
        with self._lock:
            if self._stream_cache_cfg != cfg_key:
                self._stream_cache = {}
                self._stream_cache_cfg = cfg_key
            sent = self._stream_cache.get(query, "miss")
        if sent is None:          # known not-streamable: skip the re-plan
            return None
        if sent == "miss":
            with TRACER.span("plan", label=self._active_label):
                with TRACER.span("parse"):
                    ast = parse_sql(query)
                plan = Planner(self._catalog()).plan_query(ast)
            jobs = streaming.find_streaming_jobs(
                plan, lambda t: self._est_rows.get(t, 0),
                self.config.out_of_core_min_rows)
            if not jobs:
                with self._lock:
                    self._stream_cache[query] = None
                return None
            groups = streaming.plan_scan_groups(jobs,
                                                self.config.shared_scan)
            if self.config.narrow_lanes:
                # choose each group's per-column upload lanes ONCE from
                # table-wide column stats: static for every morsel of the
                # pass (a per-morsel choice would be a width change =
                # recompile mid-stream), recorded on the morsel ScanNodes
                # so the verifier can prove them against the same stats
                from .jax_backend.device import (bucket, plan_encodings,
                                                 plan_lanes)
                for g in groups:
                    st = self.column_stats(g.table)
                    streaming.set_group_lanes(g, plan_lanes(
                        g.dtypes, [st.get(c) for c in g.columns]))
                    if not self.config.encoded_exec or g.lanes is None:
                        continue
                    # generalize lanes from width to ENCODING: dictionary
                    # codes / run-length pairs chosen once per group from
                    # cardinality/run stats, static like the lanes are
                    est = self.column_enc_stats(g.table, g.columns)
                    planned = plan_encodings(
                        g.dtypes, g.lanes, [est.get(c) for c in g.columns],
                        bucket(self.config.chunk_rows))
                    if planned is not None:
                        streaming.set_group_encodings(g, *planned)
            if self.config.verify_plans == "per-pass":
                # fused shared-scan partial plans are plan-IR rewrites that
                # never pass through planner.PassPipeline — verify them here
                streaming.verify_groups(groups, col_stats=self.column_stats,
                                        enc_stats=self.column_enc_stats)
            # ONE executor serves every group of every job: groups run
            # sequentially, and sharing the scan cache uploads each
            # dimension table once instead of per branch
            shared = self._new_stream_executor()
            sent = {"plan": plan, "jobs": jobs, "groups": groups,
                    "exec": shared,
                    # "tight": None until a whole pass has been seen, then
                    # whether the programs are sized from it (_stream_group)
                    "gstates": [{"cqs": None, "ents": None, "fused": False,
                                 "tight": None} for _ in groups]}
            with self._lock:
                self._stream_cache[query] = sent

        plan, jobs, groups = sent["plan"], sent["jobs"], sent["groups"]
        import time as _time

        from .jax_backend.device import decode_stats
        dec0 = decode_stats()
        # per-run profile collection (cheap: host counters the loop already
        # computes + one perf_counter pair per group/job) — feeds
        # ExecStats.node_stats on every streamed run and the full
        # PlanProfile under EXPLAIN ANALYZE (_stream_profile)
        stream_rec: dict = {"plan": plan, "groups": [], "jobs": [],
                            "finalize_ms": 0.0}
        self._last_stream_profile = stream_rec  # lint: lock-exempt (statement-scoped: written and read under _sql_lock)
        mapping: dict = {}
        total_morsels = 0
        re_records = 0
        bytes_uploaded = 0
        fused_groups = 0
        sharded_groups = 0
        shard_stats: dict = {}   # collective_bytes / collective_ms across groups
        morsels_per_table: dict[str, int] = {}
        host_decode_ms: dict[str, float] = {}
        enc_bytes_saved = 0
        prefetch_errs: list[str] = []
        from .plan import MaterializedNode
        partials: list[list] = [[] for _ in jobs]
        for ji, job in enumerate(jobs):
            for branch in job.branches:
                if branch.big_table is None:
                    # no big scan in this branch: one-shot in-core partial —
                    # on the DEVICE when the session runs jax (a just-under-
                    # threshold channel can still be tens of millions of
                    # rows; the host executor is the 1-core fallback)
                    partials[ji].append(arrow_bridge.to_arrow(
                        self._incore_partial(sent["exec"], branch)))
        for group, gstate in zip(groups, sent["gstates"]):
            sinks = [(jobs[ji], partials[ji]) for ji, _bi in group.members]
            # scan-group boundary: yield the device lane to preempting
            # tickets (no hook installed = one attribute read, no-op)
            self._maybe_preempt()
            g_t0 = _time.perf_counter()
            out = self._stream_group(group, sent["exec"], gstate, sinks,
                                     prefetch_errs, shard_stats)
            if out is None:
                with self._lock:
                    self._stream_cache[query] = None
                return None     # not device-runnable: in-core path
            morsels_run, rr, ub, sharded, host_ms, rows_streamed = out
            stream_rec["groups"].append({
                "table": group.table, "rows": rows_streamed, "bytes": ub,
                "wall_ms": round((_time.perf_counter() - g_t0) * 1000, 3)})
            total_morsels += morsels_run
            re_records += rr
            bytes_uploaded += ub
            fused_groups += 1 if gstate["fused"] else 0
            sharded_groups += 1 if sharded else 0
            morsels_per_table[group.table] = \
                morsels_per_table.get(group.table, 0) + morsels_run
            host_decode_ms[group.table] = round(
                host_decode_ms.get(group.table, 0.0) + host_ms, 3)
            if group.encodings is not None and group.plain_lanes is not None:
                from .jax_backend.device import (bucket, enc_lane_bytes,
                                                 lane_bytes)
                cap = bucket(self.config.chunk_rows)
                enc_bytes_saved += morsels_run * (
                    lane_bytes(group.plain_lanes, cap) -
                    enc_lane_bytes(group.lanes, cap, group.encodings))
        for ji, job in enumerate(jobs):
            if not partials[ji]:
                with self._lock:
                    self._stream_cache[query] = None
                return None
            j_t0 = _time.perf_counter()
            with TRACER.span("merge.partials", job=ji,
                             parts=len(partials[ji])):
                merged_arrow = pa.concat_tables(partials[ji],
                                                promote_options="permissive")
                merged = arrow_bridge.from_arrow(merged_arrow,
                                                 self._dec_as_int())
                mat = MaterializedNode(table=merged,
                                       label="streamed-partials",
                                       out_names=list(job.partial_names),
                                       out_dtypes=list(job.partial_dtypes))
                final_sub = job.build_final(mat)
                sub_res = Executor(self.load_table).execute(final_sub)
            stream_rec["jobs"].append({
                "agg_id": id(job.agg), "partial_rows": merged.num_rows,
                "final_rows": sub_res.num_rows,
                "wall_ms": round((_time.perf_counter() - j_t0) * 1000, 3)})
            mat_node = MaterializedNode(
                table=sub_res, label="streamed-agg",
                out_names=list(job.agg.out_names),
                out_dtypes=list(job.agg.out_dtypes))
            if job.join_patch is not None:
                # semi/anti build side: probe the materialized key set
                from .plan import BCol
                keys = [BCol(job.agg.out_dtypes[i], i, job.agg.out_names[i])
                        for i in range(len(job.join_patch.right_keys))]
                mapping[id(job.join_patch)] = {"right": mat_node,
                                               "right_keys": keys}
            else:
                mapping[id(job.agg)] = mat_node
        final_plan = streaming.substitute_nodes(plan, mapping)
        f_t0 = _time.perf_counter()
        with TRACER.span("finalize", label=self._active_label,
                         jobs=len(jobs)):
            result = Executor(self.load_table).execute(final_plan)
        stream_rec["finalize_ms"] = round(
            (_time.perf_counter() - f_t0) * 1000, 3)
        # scan_passes counts morsel loops (== tables_streamed when
        # shared_scan serves every branch from one pass; == branches_served
        # per-branch without it); lane_spec records which physical lane each
        # streamed column rode (bytes_uploaded measures the win); EVERY
        # prefetch failure is recorded — they degrade to synchronous staging,
        # correct but slower, so the degradation must be observable
        dec1 = decode_stats()
        self._finish_exec_stats(ExecStats.streaming(
            jobs=len(jobs),
            morsels=total_morsels,
            morsel_rows=self.config.chunk_rows,
            re_records=re_records,
            shared_scan=bool(self.config.shared_scan),
            scan_passes=len(groups),
            tables_streamed=len(morsels_per_table),
            branches_served=sum(len(g.members) for g in groups),
            fused_groups=fused_groups,
            bytes_uploaded=bytes_uploaded,
            morsels_per_table=morsels_per_table,
            narrow_lanes=bool(self.config.narrow_lanes),
            lane_spec={g.table: dict(zip(g.columns, g.lanes))
                       for g in groups if g.lanes is not None},
            encoded_exec=bool(self.config.encoded_exec
                              and self.config.narrow_lanes),
            enc_spec={g.table: dict(zip(g.columns, [_enc_tag(e) for e in
                                                    g.encodings]))
                      for g in groups if g.encodings is not None} or None,
            enc_bytes_saved=enc_bytes_saved or None,
            decode_sites=dec1["sites"] - dec0["sites"],
            decode_rows=dec1["rows"] - dec0["rows"],
            host_decode_ms=host_decode_ms,
            mesh_shards=self._morsel_shards() if sharded_groups else None,
            sharded_groups=sharded_groups or None,
            collective_bytes=shard_stats.get("collective_bytes"),
            collective_ms=shard_stats.get("collective_ms"),
            node_stats=self._stream_node_stats(plan, stream_rec, result),
            prefetch_error_details=prefetch_errs,
            fallbacks=self.last_fallbacks), rows=result.num_rows)
        return result

    def _stream_node_stats(self, plan, rec: dict, result: Table) -> dict:
        """{TypeName#k: actual rows} a streamed run records for free —
        rows streamed per big scan, final group counts per streamed
        aggregate, result rows at the root. Labels are verify.node_labels
        over the session's plan, the same identities profiles and
        verifier findings use (obs/profile.plan_tree)."""
        from .plan import ScanNode, iter_plan_nodes
        from .verify import node_labels
        labels = node_labels(plan)
        rows_by_table = {g["table"]: g["rows"] for g in rec["groups"]}
        out: dict = {}
        for n in iter_plan_nodes(plan):
            if isinstance(n, ScanNode) and n.table in rows_by_table:
                out[labels[id(n)]] = rows_by_table[n.table]
        for j in rec["jobs"]:
            lbl = labels.get(j["agg_id"])
            if lbl is not None:     # synthesized semi-join aggs are not
                out[lbl] = j["final_rows"]   # nodes of the session plan
        out[labels[id(plan)]] = result.num_rows
        return out

    def _new_stream_executor(self) -> dict:
        """One JaxExecutor (+ morsel slot) shared by every streamed branch
        of a query; kept across repeated executions."""
        from . import streaming
        from .jax_backend import JaxExecutor

        current: dict = {}

        def load(name, columns=None):
            if name == streaming.MORSEL_TABLE:
                t = current["table"]
                return t.select(list(columns)) if columns else t
            return self.load_table(name, columns)

        cfg = self.config
        jexec = JaxExecutor(
            load, jit_plans=True, mesh=self._device_mesh(),
            shard_min_rows=cfg.shard_min_rows,
            segment_plan_nodes=cfg.segment_plan_nodes,
            segment_min_cte_nodes=cfg.segment_min_cte_nodes,
            segment_cache_entries=cfg.segment_cache_entries,
            scan_budget_bytes=int(cfg.scan_budget_gb * (1 << 30)))
        return {"jexec": jexec, "current": current}

    def _incore_partial(self, shared: dict, branch):
        """One-shot partial aggregate for a branch without a big scan."""
        if not self.config.use_jax:
            return Executor(self.load_table).execute(branch.partial_plan)
        from .jax_backend import to_host
        from .jax_backend.executor import _plan_fingerprint
        jexec = shared["jexec"]
        jexec.query_label = self._active_label
        jexec.query_label_auto = self._label_auto
        key = ("stream-incore", _plan_fingerprint(branch.partial_plan))
        out = jexec.run_query(key, lambda: branch.partial_plan)
        return to_host(out)

    def _combine_partials(self, job, partials: list) -> "pa.Table":
        """Re-aggregate accumulated partial tables into one (partial-schema
        preserving; associative, so repeatable)."""
        from .plan import MaterializedNode
        merged_arrow = pa.concat_tables(partials,
                                        promote_options="permissive")
        merged = arrow_bridge.from_arrow(merged_arrow, self._dec_as_int())
        mat = MaterializedNode(table=merged, label="stream-compact",
                               out_names=list(job.partial_names),
                               out_dtypes=list(job.partial_dtypes))
        out = Executor(self.load_table).execute(job.build_combine(mat))
        return arrow_bridge.to_arrow(out)

    def _stream_group(self, group, shared: dict, state: dict,
                      sinks: list, prefetch_errs: list, shard_stats: dict):
        """Morsel loop for one shared-scan group: ONE morsel iterator and
        ONE double-buffered upload per morsel serve EVERY member branch (a
        worker thread packs + stages morsel i+1 while the device runs
        morsel i — host decode + pack + upload overlap device execution).
        Member partial programs read
        zero-copy views of the staged union buffer; a group within the
        fusion budget runs as ONE multi-output program per morsel (one
        dispatch for all members, streaming.fuse_group + multi-plan
        CompiledQuery), larger groups run per-member programs over the
        same buffer. `sinks[i]` is (job, partials_list) for member i:
        per-morsel partial arrow tables append there, compacting IN the
        loop whenever a job's accumulated rows outgrow stream_compact_rows
        (q4-class customer-grained groups at SF100 would otherwise peak
        host memory before any compaction ran). Worker-thread staging
        failures are recorded into `prefetch_errs` (the morsel restages
        synchronously — a silent degradation otherwise, ADVICE r5).
        With mesh_shards > 1 the group dispatches SHARDED: the staged
        morsel upload lands row-sharded over the replica mesh (one
        device_put of per-replica packed payload blocks), every replica
        replays the same recorded per-morsel schedule on its rows inside
        shard_map, and one all_gather moves the bounded decomposed
        partials before the unchanged host merge
        (jax_backend/shard_exec.ShardedMorselQuery).
        The group's schedule is the morsel bound only until one whole pass
        has been seen. On the first sighting every cap of the recorded
        schedule(s) is raised to the bound (streaming.inflate_schedule: one
        program has to serve morsels nobody has seen), and every replay's
        check scalars — on the host anyway, for the schedule check — are
        max-merged into `state["obs"]`, per member, index-aligned with the
        decisions. When the loop has reached the end of the table with no
        ReplayMismatch, the programs are replaced by ones whose caps are
        those maxima (tighten): `state` lives in a stream-cache entry keyed
        by the statement's text and the catalog generation, so every later
        sighting from it meets the same rows and the maxima are exact. The
        tight programs compile at the second sighting; a statement seen once
        behaves as it always did. The schedule check stays as the net: a
        tight replay that overflows re-records that morsel eagerly, and the
        group goes back to the bound for the rest of the entry's life.
        Returns (morsels,
        re_records, bytes_uploaded, sharded, host_decode_ms, rows_streamed)
        or None when some member is not device-runnable."""
        import threading

        from . import streaming
        from .jax_backend import to_host
        from .jax_backend.device import (bucket, device_bytes, free_dtable,
                                         pack_table, to_device)
        from .jax_backend.executor import CompiledQuery, ReplayMismatch

        morsel_rows = self.config.chunk_rows
        cap = bucket(morsel_rows)
        n_shards = self._morsel_shards()
        mesh = self._morsel_mesh() if n_shards else None
        shard_cap = streaming.shard_capacity(morsel_rows, n_shards) \
            if mesh is not None else None
        jexec, current = shared["jexec"], shared["current"]
        mkey = group.morsel_key
        morsels = self.iter_morsels(group.table, group.columns, morsel_rows)
        fuse_max = self.config.stream_fusion_max_branches
        fuse = len(group.plans) > 1 and \
            (fuse_max <= 0 or len(group.plans) <= fuse_max)
        re_records = 0
        count = 0
        bytes_uploaded = 0
        rows_streamed = 0

        # what a replica's (one chip: the morsel's) capacities are bounded by
        bound = shard_cap if mesh is not None else morsel_rows

        def build(schedules: list) -> list:
            """The group's programs from one schedule per member (fused:
            one program over every plan): shard_map-dispatched under a
            mesh, each with its own schedule, all resolving the shared
            staged buffer through the same morsel scan key."""
            plans = [list(group.plans)] if fuse else list(group.plans)
            cqs = []
            for bi, (p, decisions) in enumerate(zip(plans, schedules)):
                label = f"{self._active_label}/morsel:{group.table}" + \
                    ("" if fuse else f"#{bi}")
                scan_keys = state["ents"][bi]["scan_keys"]
                fp = self._name_fingerprint(p)
                if mesh is not None:
                    from .jax_backend.shard_exec import ShardedMorselQuery
                    cqs.append(ShardedMorselQuery(
                        p, decisions, scan_keys, mesh, mkey, label=label,
                        name_fingerprint=fp))
                else:
                    cqs.append(CompiledQuery(
                        p, decisions, scan_keys, mesh=jexec._mesh,
                        shard_min_rows=jexec._shard_min_rows, label=label,
                        name_fingerprint=fp))
            return cqs

        def record_first(morsel) -> bool:
            """Record the schedule(s) on the first morsel and build the
            group's first programs. Under a mesh the record pass runs on a
            representative shard-sized slice (shard-local gates: no data-
            dependent tier probes, so later replicas/morsels verify against
            capacity bounds only). The raw decisions stay in the state:
            they seed the observed maxima and are what a later schedule is
            built from (tighten)."""
            kw = {}
            current["table"] = morsel
            if mesh is not None:
                spans = streaming.partition_morsel_rows(morsel.num_rows,
                                                        n_shards)
                current["table"] = morsel.slice(0, spans[0][1])
                kw = {"shard_local": True}
            jexec.fallback_nodes = []
            if fuse:
                recs = [jexec.record_plans(group.plans, **kw)]
            else:
                # fusion over budget (or single member): per-member programs
                recs = []
                for p in group.plans:
                    recs.append(jexec.record_plan(p, **kw))
                    if jexec.fallback_nodes:
                        return False
            if jexec.fallback_nodes:
                return False
            raws = [decisions for _out, decisions, _keys in recs]
            ents = [{"scan_keys": keys} for _out, _decisions, keys in recs]
            state["raw"], state["ents"], state["fused"] = raws, ents, fuse
            state["obs"] = [[int(v) for _k, v in d] for d in raws]
            state["cqs"] = build([streaming.inflate_schedule(d, bound)
                                  for d in raws])
            return True

        def tighten() -> None:
            """A whole clean pass has been seen: for as long as this cache
            entry lives the statement meets the same rows, so the pass's
            per-decision maxima are exact for every later replay. Replace
            the programs by ones whose caps are those maxima
            (streaming.adapt_schedule); they compile at the next sighting.
            Where no capacity bucket would change the programs stay."""
            schedules = [streaming.adapt_schedule(d, bound, o)
                         for d, o in zip(state["raw"], state["obs"])]
            state["tight"] = any(
                streaming.schedule_shape(s) !=
                streaming.schedule_shape(cq.decisions)
                for s, cq in zip(schedules, state["cqs"]))
            if state["tight"]:
                state["cqs"] = build(schedules)

        def stage(morsel):
            """Pack + upload one union-column morsel into a fresh buffer
            (group.lanes = the static narrow-lane spec, group.encodings =
            the static dict/rle encoding spec; None = legacy layouts under
            --no_narrow_lanes / --no_encoded_exec). Sharded mode uploads
            the same payload row-sharded over the replica mesh instead."""
            if mesh is not None:
                from .jax_backend.shard_exec import stage_sharded
                sub = morsel.select(group.columns)
                return stage_sharded(sub, mesh, shard_cap,
                                     lanes=group.lanes,
                                     encs=group.encodings,
                                     codebooks=group.codebooks)
            with TRACER.span("morsel.stage", cat="upload",
                             table=group.table, rows=morsel.num_rows):
                sub = morsel.select(group.columns)
                packed = pack_table(sub, capacity=cap, lanes=group.lanes,
                                    encs=group.encodings,
                                    codebooks=group.codebooks)
                return packed if packed is not None else \
                    to_device(sub, capacity=cap)

        def merge_obs(member: int, actuals) -> None:
            """Elementwise max-merge one replay/record pass's per-decision
            actuals into the group's observation rows."""
            row = [int(a) for a in actuals]
            prev = state["obs"][member]
            if len(prev) == len(row):
                row = [max(a, b) for a, b in zip(prev, row)]
            state["obs"][member] = row

        def run_one(member: int, cq, ent):
            """One member dispatch. The pre-seeded decision_rows key pulls
            the replay's check scalars back out (under a mesh the max over
            replicas): the host has fetched them for the schedule check
            anyway, so observing costs no copy and no sync."""
            st = shard_stats if mesh is not None else {}
            st["decision_rows"] = None
            out = cq.run(jexec._scans_for(ent), stats=st)
            merge_obs(member, st.pop("decision_rows"))
            return out

        def run_members():
            """Every member program against the staged buffer: one fused
            dispatch, or per-member dispatches. Returns member outputs in
            group.plans order."""
            nonlocal re_records
            try:
                if state["fused"]:
                    outs = list(run_one(0, state["cqs"][0],
                                        state["ents"][0]))
                else:
                    outs = [run_one(bi, cq, ent)
                            for bi, (cq, ent) in enumerate(zip(
                                state["cqs"], state["ents"]))]
                if state["tight"]:
                    _metrics.TIGHT_MORSEL_REPLAYS.inc()
                return outs
            except ReplayMismatch:
                # a morsel genuinely exceeded the schedule (the inflated
                # bound or a tightened cap): run it eagerly after evicting
                # stale record-side buffers — correctness never depends on
                # the schedule.
                free_dtable(jexec._scan_cache_rec.pop(mkey, None))
                re_records += 1
                _metrics.REPLAY_MISMATCHES.inc()
                if state["tight"]:
                    # what the whole pass saw did not hold: back to the
                    # bound for the rest of this cache entry's life
                    state["cqs"] = build([
                        streaming.inflate_schedule(d, bound)
                        for d in state["raw"]])
                # a pass with a mismatch sizes nothing, now or later
                state["tight"] = False
                if state["fused"]:
                    return jexec.record_plans(group.plans)[0]
                return [jexec.record_plan(p)[0] for p in group.plans]

        staged = {}
        stage_thread = None
        host_ms = 0.0

        def pull(it):
            """Next morsel, with the host-side Arrow->engine decode wall
            (IO + dictionary/validity materialization, arrow_bridge.
            from_arrow inside iter_morsels) accounted per table — the
            staging-thread bottleneck encoded execution is shrinking must
            be measurable (ExecStats.host_decode_ms)."""
            nonlocal host_ms
            import time as _time
            with TRACER.span("morsel.decode", cat="host",
                             table=group.table) as sp:
                t0 = _time.perf_counter()
                m = next(it, None)
                host_ms += (_time.perf_counter() - t0) * 1000.0
                sp.set(rows=m.num_rows if m is not None else 0)
            return m

        def join_stage(at: int) -> None:
            """The main thread blocked on the staging thread (no thread, no
            span): after morsel `at`'s partials, or on the way out."""
            nonlocal stage_thread
            if stage_thread is None:
                return
            with TRACER.span("morsel.stage_wait", cat="host",
                             table=group.table, morsel=at):
                stage_thread.join()
            stage_thread = None

        try:
            it = iter(morsels)
            morsel = pull(it)
            while morsel is not None:
                # morsel boundary: the stage thread is joined and the
                # previous morsel's partials are on the host — yield the
                # device lane to preempting tickets before the next run
                self._maybe_preempt()
                if state["cqs"] is None and not record_first(morsel):
                    return None
                if "buf" in staged:
                    buf = staged.pop("buf")
                else:
                    err = staged.pop("err", None)
                    if err is not None:
                        prefetch_errs.append(
                            f"{type(err).__name__}: {err}")
                    # a stage the main thread pays itself: a group's first
                    # morsel, or the one after a failed prefetch
                    with TRACER.span("morsel.stage_sync", cat="upload",
                                     table=group.table,
                                     prefetch_error=err is not None):
                        buf = stage(morsel)
                nxt = pull(it)
                if nxt is not None:
                    # stage the NEXT morsel concurrently with this run
                    def work(m=nxt):
                        try:
                            staged["buf"] = stage(m)
                        except BaseException as e:  # surfaced via prefetch_errs
                            staged["err"] = e
                    stage_thread = threading.Thread(target=work, daemon=True)
                    stage_thread.start()
                buf_bytes = device_bytes(buf)
                bytes_uploaded += buf_bytes
                prev = jexec._scan_cache.get(mkey)
                jexec._scan_cache[mkey] = buf
                current["table"] = morsel
                with TRACER.span("morsel.exec", cat="device",
                                 table=group.table, morsel=count,
                                 rows=morsel.num_rows, bytes=buf_bytes):
                    outs = run_members()
                with TRACER.span("morsel.partials", cat="host",
                                 members=len(sinks)) as sp:
                    free_dtable(prev)
                    rows = 0
                    for (job, plist), out in zip(sinks, outs):
                        plist.append(arrow_bridge.to_arrow(to_host(out)))
                        rows += plist[-1].num_rows
                        if sum(p.num_rows for p in plist) > \
                                self.config.stream_compact_rows:
                            plist[:] = [self._combine_partials(job, plist)]
                    sp.set(rows=rows)
                rows_streamed += morsel.num_rows
                join_stage(count)
                count += 1
                morsel = nxt
        finally:
            # free every morsel-sized buffer even on a mid-stream failure
            # (device OOM on the next query otherwise): the current buffer,
            # the record-side copy, the host morsel reference, and whatever
            # the staging thread uploaded
            join_stage(count)
            free_dtable(staged.pop("buf", None))
            free_dtable(jexec._scan_cache.pop(mkey, None))
            free_dtable(jexec._scan_cache_rec.pop(mkey, None))
            current.pop("table", None)
        if count == 0:
            return None   # empty source: the in-core path handles it
        if state["tight"] is None:      # a whole pass, and no mismatch
            tighten()
        return (count, re_records, bytes_uploaded, mesh is not None,
                host_ms, rows_streamed)

    def sql_arrow(self, query: str) -> pa.Table:
        return arrow_bridge.to_arrow(self.sql(query))

    # -- statements (DML/DDL for the maintenance test) -----------------------
    def attach_warehouse(self, warehouse,
                         at_version: Optional[int] = None) -> None:
        """Bind a Warehouse so INSERT/DELETE statements commit snapshots
        (the reference runs these against Iceberg/Delta catalogs,
        nds_maintenance.py:107-116). With a published snapshot log the
        registrations pin to ONE warehouse version; ``at_version`` time-
        travels the whole warehouse to an older published version
        (``AS OF``-style reads — the rollback machinery generalized to
        warehouse level, read-only: no new snapshot is committed)."""
        self.warehouse = warehouse
        warehouse.register_all(self, at_version=at_version)

    def refresh_warehouse(self) -> None:
        """Advance a snapshot-pinned reader to the latest PUBLISHED
        warehouse version. Serialized on the statement lock, so an
        in-flight statement finishes against the snapshot it pinned and
        the next statement resolves against the new one."""
        if self.warehouse is None:
            return
        with self._sql_lock:
            self.warehouse.register_all(self)

    def execute(self, sql_text: str, backend: Optional[str] = None):
        """Execute one or more ';'-separated statements; returns the last
        query's Table (or None for pure DML). Serialized on _sql_lock like
        sql() — statements are the unit of the concurrency contract."""
        with self._sql_lock:
            return self._execute_locked(sql_text, backend)

    def _execute_locked(self, sql_text: str, backend: Optional[str]):
        from ..sql import parse_statements
        from ..sql.ast_nodes import CreateView, Delete, DropView, Insert, Query

        result = None
        for stmt in parse_statements(sql_text):
            if isinstance(stmt, Query):
                result = self._run_query_ast(stmt, backend)
            elif isinstance(stmt, CreateView):
                table = self._run_query_ast(stmt.query, backend)
                self.register_view(stmt.name, table)
            elif isinstance(stmt, DropView):
                self.drop(stmt.name)
            elif isinstance(stmt, Insert):
                self._insert(stmt, backend)
            elif isinstance(stmt, Delete):
                self._delete(stmt, backend)
            else:
                raise TypeError(type(stmt).__name__)
        return result

    def _run_query_ast(self, ast, backend: Optional[str]):
        planner = Planner(self._catalog())
        plan = planner.plan_query(ast)
        use_jax = (backend == "jax") if backend else self.config.use_jax
        if use_jax:
            from .jax_backend import to_host
            jexec = self._jax_executor()
            # one-shot statements (DML bodies, view definitions) skip the
            # compiled-plan cache: key=None runs the recorded eager path
            out = to_host(jexec.run_query(None, lambda: plan))
            self.last_fallbacks = list(jexec.fallback_nodes)
            return out
        return Executor(self.load_table).execute(plan)

    def _insert(self, stmt, backend: Optional[str]) -> None:
        if self.warehouse is None:
            raise RuntimeError("INSERT requires an attached warehouse")
        rows = self._run_query_ast(stmt.query, backend)
        target_names, _ = self._schemas[stmt.table]
        data = arrow_bridge.to_arrow(rows).rename_columns(target_names)
        self.warehouse.table(stmt.table).insert(data)
        self.warehouse.register_all(self)  # refresh snapshot binding
        # LF_* delta publication: the inserted rows ARE the delta —
        # subscribers (result-cache IVM) merge per-group partials from
        # them instead of recomputing the warm dashboards they feed
        self._publish_table_delta(stmt.table, inserts=data)

    def _delete(self, stmt, backend: Optional[str]) -> None:
        """DELETE FROM <table> WHERE <pred>: rewrite warehouse files keeping
        rows that do NOT satisfy the predicate (NULL predicate => kept,
        standard SQL DELETE semantics). Subqueries in the predicate see the
        session's other registered tables."""
        if self.warehouse is None:
            raise RuntimeError("DELETE requires an attached warehouse")
        import numpy as np

        from ..sql import parse_sql

        wt = self.warehouse.table(stmt.table)
        # DF_* delta publication: wrap the keep filter so the rows each
        # batch DROPS are captured as the statement's delete delta
        # (subscribers recompute only delta-touched groups); capture only
        # when someone is listening — the rows are otherwise dead weight
        deleted_parts: list = []

        def capture_deletes(t: pa.Table, keep):
            if self._delta_subscribers:
                import pyarrow.compute as pc
                dropped = t.filter(pc.invert(pa.array(keep,
                                                      type=pa.bool_())))
                if dropped.num_rows:
                    deleted_parts.append(dropped)
            return keep

        def publish_deletes():
            if deleted_parts:
                self._publish_table_delta(
                    stmt.table,
                    deletes=pa.concat_tables(deleted_parts,
                                             promote_options="permissive"))

        if stmt.where is None:
            wt.delete_where(lambda t: capture_deletes(
                t, pa.array([False] * t.num_rows)))
            self.warehouse.register_all(self)
            publish_deletes()
            return

        def _references_target(node) -> bool:
            """Does the WHERE reference the target table (via a subquery)?
            Batched evaluation would then see only a slice of the table and
            compute the subquery wrongly — force one whole-table batch."""
            import dataclasses as _dc

            from ..sql import ast_nodes as A
            stack = [node]
            while stack:
                x = stack.pop()
                if isinstance(x, A.TableRef) and x.name == stmt.table:
                    return True
                if _dc.is_dataclass(x):
                    stack.extend(getattr(x, f.name) for f in _dc.fields(x))
                elif isinstance(x, (list, tuple)):
                    stack.extend(x)
            return False

        batch_rows = (2 ** 62 if _references_target(stmt.where)
                      else 4_000_000)
        part_prune = self._partition_prune(stmt.table, stmt.where,
                                           _references_target)

        def keep_filter(t: pa.Table):
            # per-file scoped session: the target table IS this file's rows,
            # extended with a rowid so the engine tells us which rows matched
            tmp = Session(self.config)
            for other in self._schemas:
                if other == stmt.table:
                    continue
                tmp._schemas[other] = self._schemas[other]
                tmp._loaders[other] = self._loaders[other]
                tmp._est_rows[other] = self._est_rows.get(other, 1000)
            with_id = t.append_column(
                "__rowid", pa.array(np.arange(t.num_rows, dtype=np.int64)))
            tmp.register_arrow(stmt.table, with_id)
            q = parse_sql(f"SELECT __rowid FROM {stmt.table}")
            q.body.where = stmt.where
            hit = tmp._run_query_ast(q, backend="numpy")
            deleted = np.zeros(t.num_rows, dtype=bool)
            ids = np.asarray(hit.columns[0].data, dtype=np.int64)
            deleted[ids[hit.columns[0].validity]] = True
            return capture_deletes(t, pa.array(~deleted))

        # skip the (subquery-evaluating) stats analysis entirely when the
        # warehouse predates file stats — nothing could prune
        stats_prune = self._stats_prune(
            stmt.table, stmt.where, _references_target) \
            if wt.file_stats() else None
        wt.delete_where(keep_filter, batch_rows=batch_rows,
                        part_prune=part_prune, stats_prune=stats_prune)
        self.warehouse.register_all(self)
        publish_deletes()

    def _stats_prune(self, table: str, where, _references_target):
        """File-stats pruning rule for a DELETE: if some AND-conjunct is
        `col IN (subquery|list)` over a stats-tracked integer column
        (ticket/order numbers), files whose recorded [min, max] for that
        column contains NONE of the values provably hold no deletable
        rows. Returns callable(stats dict|None) -> process?, or None.
        The DF_* ticket-number deletes cannot date-prune — per-file column
        metrics are the reference's remaining Iceberg lever
        (nds/nds_maintenance.py:146-185)."""
        import numpy as np

        from ..sql import ast_nodes as A
        from ..warehouse import TABLE_PARTITIONING

        if where is None or _references_target(where):
            return None
        part_col = TABLE_PARTITIONING.get(table)

        for c in _and_conjuncts(where):
            col = None
            values = None
            if isinstance(c, A.InSubquery) and not c.negated and \
                    isinstance(c.expr, A.ColumnRef):
                col = c.expr.name
                if col == part_col:
                    continue        # partition pruning already covers it
                out = self._run_query_ast(c.query, backend="numpy")
                oc = out.columns[0]
                vals = np.asarray(oc.data)
                if oc.validity is not None:
                    vals = vals[oc.validity]
                values = vals
            elif isinstance(c, A.InList) and not c.negated and \
                    isinstance(c.expr, A.ColumnRef) and \
                    all(isinstance(i, A.Literal) and
                        isinstance(i.value, int) for i in c.items):
                col = c.expr.name
                if col == part_col:
                    continue
                values = np.asarray([i.value for i in c.items])
            if col is None or values is None:
                continue
            if not np.issubdtype(values.dtype, np.integer):
                continue
            svals = np.sort(values)

            def prune(st, col=col, svals=svals):
                if st is None or col not in st:
                    return True          # no stats: must process
                mn, mx = st[col]
                lo = np.searchsorted(svals, mn, side="left")
                hi = np.searchsorted(svals, mx, side="right")
                return bool(hi > lo)     # some value inside [mn, mx]
            return prune
        return None

    def _partition_prune(self, table: str, where, _references_target):
        """File-level pruning rule for a DELETE over a partitioned fact
        table: if some AND-conjunct of the predicate constrains the
        partition key to a computable value set/range, files of other
        partition values provably hold no deletable rows (a false/NULL
        conjunct makes the whole predicate non-TRUE). Returns
        callable(part_val_str) -> process?, or None when no conjunct is
        prunable. The DF_* refresh deletes are `key IN (SELECT d_date_sk
        ...)` — the date-partitioned layout makes them metadata-pruned like
        the reference's Iceberg deletes (nds/nds_maintenance.py:146-185)."""
        import numpy as np

        from ..sql import ast_nodes as A
        from ..warehouse import TABLE_PARTITIONING

        part_col = TABLE_PARTITIONING.get(table)
        if part_col is None or where is None:
            return None
        if _references_target(where):
            # keep_filter's whole-table-batch invariant: a self-referencing
            # subquery anywhere in the predicate must see EVERY file, so no
            # conjunct may prune the read set
            return None

        def is_part_col(e) -> bool:
            return isinstance(e, A.ColumnRef) and e.name == part_col

        def lit(e):
            return e.value if isinstance(e, A.Literal) else None

        for c in _and_conjuncts(where):
            if isinstance(c, A.InSubquery) and not c.negated and \
                    is_part_col(c.expr):
                # evaluate ONCE in this session, where the full target
                # table is still registered (uncorrelated per-file)
                out = self._run_query_ast(c.query, backend="numpy")
                col = out.columns[0]
                vals = np.asarray(col.data)[col.validity] \
                    if col.validity is not None else np.asarray(col.data)
                allowed = {str(v) for v in vals.tolist()}
                # v None = unpartitioned file: could hold anything, process.
                # The "null" partition never matches IN/=/BETWEEN: prune.
                return lambda v: v is None or v in allowed
            if isinstance(c, A.InList) and not c.negated and \
                    is_part_col(c.expr) and \
                    all(isinstance(i, A.Literal) for i in c.items):
                allowed = {str(lit(i)) for i in c.items}
                return lambda v: v is None or v in allowed
            if isinstance(c, A.Between) and not c.negated and \
                    is_part_col(c.expr) and lit(c.low) is not None \
                    and lit(c.high) is not None:
                lo, hi = lit(c.low), lit(c.high)

                def in_range(v, lo=lo, hi=hi):
                    if v is None:
                        return True
                    if v == "null":
                        return False       # NULL key never matches BETWEEN
                    try:
                        return lo <= int(v) <= hi
                    except (TypeError, ValueError):
                        return True        # unparseable: process the file
                return in_range
            if isinstance(c, A.BinOp) and c.op == "=":
                pair = ((c.left, c.right) if is_part_col(c.left)
                        else (c.right, c.left) if is_part_col(c.right)
                        else None)
                if pair is not None and lit(pair[1]) is not None:
                    allowed = {str(lit(pair[1]))}
                    return lambda v: v is None or v in allowed
        return None

    def explain(self, query: str) -> str:
        ast = parse_sql(query)
        planner = Planner(self._catalog())
        plan = planner.plan_query(ast)
        lines: list[str] = []

        def render(node, depth):
            label = type(node).__name__.replace("Node", "")
            detail = ""
            if hasattr(node, "table"):
                detail = f" {getattr(node, 'table', '')}"
            if hasattr(node, "kind"):
                detail = f" [{node.kind}]"
            lines.append("  " * depth + f"{label}{detail}"
                         f" -> {len(node.out_names)} cols")
            for f in ("child", "left", "right"):
                sub = getattr(node, f, None)
                if sub is not None and hasattr(sub, "out_names"):
                    render(sub, depth + 1)
        render(plan, 0)
        return "\n".join(lines)
