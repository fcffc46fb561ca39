"""Engine-wide configuration.

One typed config object replaces the reference's three-tier config zoo
(argparse + bash template `SPARK_CONF` arrays + key=value property files,
see reference nds/base.template and nds/nds_power.py:306-312). Property files
are still accepted for interface parity (`load_properties`).
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field


def _env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.lower() not in ("0", "false", "no")


@dataclass
class EngineConfig:
    # Physical type for DECIMAL columns:
    #   "f64" (default) — doubles; exact enough under the validator epsilon
    #   "i64" — exact scaled-int64 ("decN" engine dtype): sums/compares on
    #           integers, SURVEY.md §7's decimal plan (requires x64 for the
    #           full int64 range; TPU runs S64 as emulated dual-i32)
    decimal_physical: str = "f64"
    # device mesh axis for data-parallel table sharding
    mesh_shape: tuple[int, ...] = ()
    mesh_axis_names: tuple[str, ...] = ("shards",)
    # multi-chip sharded morsel execution: partition every streamed scan
    # group's morsels across this many data-parallel replicas of the device
    # mesh ("shards" axis, parallel/mesh.make_mesh). Each morsel's packed
    # upload lands row-sharded (NamedSharding; the narrow-lane buffer
    # shards as equal per-replica payload blocks) and every replica runs
    # the same compiled per-morsel program via shard_map on its rows, with
    # device-local partial aggregation and ONE all_gather of the bounded
    # decomposed partials before the existing host-side final merge.
    # 0 / 1 = off: the single-chip path, bit-identical to before the knob
    # existed. Only out-of-core streamed queries shard; in-core queries
    # keep the single-chip (or mesh_shape/GSPMD) path. Virtual-device
    # testing: XLA_FLAGS=--xla_force_host_platform_device_count=8.
    # Property: nds.tpu.mesh_shards; runners expose --mesh_shards.
    mesh_shards: int = 0
    # rows per morsel when streaming host->device. Sized to amortize the
    # fixed per-morsel cost (stage, dispatch, partial fetch; an SF100 scan
    # is hundreds of morsels) while keeping the record pass and device
    # working set bounded.
    chunk_rows: int = 1 << 22
    # out-of-core execution: stream aggregates over one large scan in
    # chunk_rows morsels (bounded peak memory; SURVEY.md §5 long-context
    # analog). Eligible plans only; others run in-core. Default ON with a
    # big-table threshold well above SF10 fact sizes, so small scales keep
    # the scan-resident fast path and SF100-class scans stream.
    out_of_core: bool = True
    # a scan streams (rather than pinning device-resident) when its table
    # exceeds this row count
    out_of_core_min_rows: int = 48_000_000
    # accumulated streamed-partial rows that trigger a host-side compaction
    # (partial-schema-preserving re-aggregation): bounds host memory when
    # group cardinality is large (customer-grained q4-class aggregates)
    stream_compact_rows: int = 8_000_000
    # shared-scan morsel fusion: ALL streaming branches of one query that
    # scan the same big table share ONE morsel pass — the union of their
    # pruned column sets packs/uploads once per morsel and each branch reads
    # its subset as zero-copy views of the staged buffer. q9-class plans
    # carry 15 scalar-subquery jobs over store_sales; without sharing the
    # dominant scan+upload cost is paid 15 times per query. Property:
    # nds.tpu.shared_scan; the power runner exposes --no_shared_scan for A/B.
    shared_scan: bool = True
    # fuse a shared-scan group's per-branch partial programs into a single
    # multi-output per-morsel XLA program (the fixed per-dispatch cost
    # is then paid once per morsel, not once per branch per morsel) when the
    # group has at most this many branches; larger groups keep per-branch
    # programs over the shared staged buffer (bounded compile time).
    # 0 = fuse unconditionally.
    stream_fusion_max_branches: int = 16
    # narrow-lane packed uploads + encoded execution: streamed morsels pack
    # each column at its minimal physical width (u8/u16/u32/i32 lanes chosen
    # statically from per-table column min/max stats + bit-packed validity,
    # device.plan_lanes/pack_table) instead of widening everything to int64,
    # and columns whose range fits 32 bits execute on i32 device arrays —
    # widening to 64-bit happens only at arithmetic/aggregation sites.
    # 2-4x fewer uploaded bytes per morsel on NDS fact tables, compounding
    # with shared-scan fusion. Property: nds.tpu.narrow_lanes; the power
    # runner exposes --no_narrow_lanes restoring the wide int64 layout
    # bit-identically for A/B runs.
    narrow_lanes: bool = True
    # encoded execution end-to-end (the narrow-lane machinery generalized
    # from width to ENCODING, device.plan_encodings): low-cardinality
    # int/date/decimal columns upload as dictionary CODES on u8/u16 lanes
    # plus a once-per-group host codebook, and clustered columns upload as
    # (value, run-length) pairs expanded on device — chosen statically per
    # scan group from per-table cardinality/run stats
    # (Session.column_enc_stats). Execution stays on codes where legality
    # allows (equality/IN filters remap literals through the dictionary at
    # trace time, join/group keys factorize codes directly, sorts ride the
    # order-preserving dictionary); device.decode_col materializes values
    # only at arithmetic/aggregate/output sites. Bit-identical on/off;
    # requires narrow_lanes (encodings extend the packed layout). Property:
    # nds.tpu.encoded_exec; the power runner exposes --no_encoded_exec for
    # A/B runs.
    encoded_exec: bool = True
    # late materialization for join-heavy aggregates (planner.
    # _late_materialization): group by the dimension's surrogate join key and
    # gather dimension attributes AFTER aggregation instead of materializing
    # them at fact scale (q72-class 16M-row gathers). Property:
    # nds.tpu.late_materialization; runners expose --no_late_mat for A/B.
    late_materialization: bool = True
    # the rewrite only fires when some scan under the aggregate is at least
    # this big (small plans gain nothing and pay an extra small join + merge
    # aggregate). 0 fires unconditionally.
    late_mat_min_rows: int = 1 << 20
    # EXPLAIN ANALYZE: profiled execution mode (obs/profile.py). When on,
    # every sql() statement executes node-by-node EAGERLY through the
    # existing executor (children memoized, so each node's wall is its
    # own work) with exact per-node row counts, output bytes, a static-
    # estimate-vs-actual cardinality audit, and device-memory watermarks
    # — results BIT-IDENTICAL to normal execution (streamed queries run
    # their unchanged morsel path and only read counters). The profile
    # lands on Session.last_profile / ExecStats.node_stats; render via
    # PlanProfile.render() / scripts/explain_report.py. OFF by default:
    # the disabled path adds zero counters and zero per-node work.
    # Property: nds.tpu.profile_plans; power exposes --explain;
    # Session.explain_analyze() profiles one statement without the flag.
    profile_plans: bool = False
    # cardinality-audit threshold: a node whose actual row count diverges
    # from the planner's static estimate by at least this ratio (either
    # direction) is flagged as a misestimate finding
    profile_misestimate_ratio: float = 8.0
    # static plan-IR verification between planner rewrite passes
    # (engine/verify.py via planner.PassPipeline):
    #   "off"      — zero verification cost (bench/production default)
    #   "final"    — verify the fully rewritten plan once per statement
    #   "per-pass" — verify between every rewrite pass, with shared-node
    #                freeze checks and pass attribution (PlanVerifyError
    #                names the node and the pass that introduced it)
    # Property: nds.tpu.verify_plans; NDS_TPU_VERIFY_PLANS sets the default
    # (CI exports "final"; bench runs keep "off").
    verify_plans: str = field(default_factory=lambda: os.environ.get(
        "NDS_TPU_VERIFY_PLANS", "off"))
    # run jitted per-op kernels (True) or pure-numpy fallback (False, debug only)
    use_jax: bool = True
    # compile whole plans to one XLA program on re-execution (record/replay);
    # NDS_TPU_JIT_PLANS=0 disables globally (e.g. compile-bound CI runs)
    jit_plans: bool = field(default_factory=lambda: _env_bool(
        "NDS_TPU_JIT_PLANS", True))
    # CTE-boundary compile segmentation: plans with at least this many nodes
    # split each sufficiently large CTE subtree into its own XLA program
    # whose output stays device-resident (bounds q4-class compile times and
    # shares materialized CTEs across q14/q23 parts). 0 disables.
    # 18: every CTE-bearing NDS plan with a >= 8-node CTE segments — the
    # whole-plan compile pathology (q4/q11/q74 year_total class) scales
    # with the CTE body, not the total node count
    segment_plan_nodes: int = 18
    segment_min_cte_nodes: int = 8
    # device-resident segment outputs kept before LRU eviction
    segment_cache_entries: int = 16
    # row-shard a scan over the mesh only above this row count; smaller
    # tables replicate (the broadcast-join layout: building a replicated
    # join LUT from a SHARDED build side costs dim-sized collectives, so
    # dimension tables — date_dim 73k, item 204k at SF100 — stay whole)
    shard_min_rows: int = 1 << 18
    # HBM budget (GB) for device-resident scans + segment outputs; the
    # least-recently-used unpinned entries evict when the cap is exceeded
    # (reference analog: Spark executors bound storage memory and re-read
    # from the warehouse; here eviction forces a re-upload on next use).
    # 0 disables eviction.
    scan_budget_gb: float = 10.0
    # -- transactional warehouse (warehouse.py _snapshots log) -------------
    # wrap each LF_*/DF_* maintenance function in ONE atomic multi-table
    # warehouse transaction (write-ahead intent record, fsync-atomic
    # CURRENT publication, crash recovery at next open) and PIN reader
    # registrations to the latest published warehouse version, so a
    # statement never sees table A at version k beside table B at k+1.
    # False = the pre-transactional per-table commit path, bit-identical
    # behavior, no _snapshots log ever created, and all three txn_*
    # counters stay zero. Property: nds.tpu.warehouse_transactions.
    warehouse_transactions: bool = True
    # -- semantic result cache (engine/result_cache.py) --------------------
    # cross-client result reuse keyed by parameterized-plan fingerprint +
    # parameter vector: a repeat dashboard load is answered from the cache
    # without touching the planner or the device. Invalidated by per-table
    # catalog generations (Session.table_generation) and the optional TTL;
    # bit-identical to recompute by construction (the entry IS a previous
    # execution's result). All tiers are OPT-IN — the default engine
    # behaves exactly as before. Property: nds.tpu.result_cache; the
    # query service reads these when ServiceConfig.result_cache is unset.
    result_cache: bool = False
    # cached entries before LRU eviction (capacity bound)
    result_cache_entries: int = 256
    # seconds before a cached entry expires (0 = no TTL)
    result_cache_ttl_s: float = 0.0
    # subsumption tier: answer a provably-narrower filter/date-window over
    # the same group keys by re-filtering a cached coarser aggregate on
    # host (the PR 4 verifier fingerprint machinery is the proof engine);
    # falls back to normal execution on any proof failure
    result_cache_subsumption: bool = False
    # incremental view maintenance: entries for decomposable aggregates
    # keep the mergeable partial state streaming._decompose produces, and
    # LF_*/DF_* maintenance deltas UPDATE those partials (merge inserted-
    # row partials; recompute only delta-touched groups for deletes)
    # instead of invalidating — dashboards stay warm across maintenance
    result_cache_ivm: bool = False
    # -- durable query log + system tables (obs/query_log.py, obs/
    #    system_tables.py) ---------------------------------------------------
    # append one flat row per completed statement to the in-memory ring
    # system.query_log serves SQL over (O(row) dict flattening at
    # _finish_exec_stats time, no plan walk). OFF by default: the
    # disabled path is one branch per statement and zero new counters.
    # Property: nds.tpu.query_log; runners expose --query_log PATH
    # (which also sets query_log_path). The system.* catalog itself is
    # always queryable — only the log rows are opt-in.
    query_log: bool = False
    # ring rows kept for live system.query_log SQL
    query_log_capacity: int = 4096
    # opt-in durable JSONL sink ("" = ring only): buffered appends with
    # size-capped rotation (<path>.1, .2, ... monotonic; oldest deleted
    # past query_log_max_files) so a long service run cannot grow the
    # log unboundedly
    query_log_path: str = ""
    query_log_max_bytes: int = 64 << 20
    query_log_max_files: int = 4
    # -- resilience (nds_tpu/resilience.py) --------------------------------
    # per-query wall-clock budget in seconds; an overrun abandons the query
    # and records Failed (DeadlineExceeded). 0 = unbounded.
    query_timeout_s: float = 0.0
    # timed attempts per query: transient failures retry with exponential
    # backoff before the query records Failed. 1 = no retry.
    query_attempts: int = 1
    # base backoff between retry attempts (doubles per attempt, capped)
    retry_backoff_s: float = 0.1
    # per-stream wall-clock budget for the throughput supervisor; a stream
    # past it is killed (process mode) or abandoned (thread mode). 0 = none.
    stream_timeout_s: float = 0.0
    # spawn attempts per throughput stream (crash/timeout => restart with
    # backoff until exhausted). 1 = no restart.
    stream_attempts: int = 1
    # armed fault-injection specs, e.g. ("jax.execute:hang:5#1",
    # "arrow.read:raise@0.1") — see resilience.FaultSpec for the grammar;
    # property file: nds.tpu.fault_points=point:action,point:action
    fault_points: tuple[str, ...] = ()

    @staticmethod
    def from_property_file(path: str | None) -> "EngineConfig":
        cfg = EngineConfig()
        for k, v in load_properties(path).items():
            key = k.replace("nds.tpu.", "").replace(".", "_")
            if not hasattr(cfg, key):
                continue
            cur = getattr(cfg, key)
            if isinstance(cur, bool):
                setattr(cfg, key, v.lower() in ("1", "true", "yes"))
            elif isinstance(cur, int):
                setattr(cfg, key, int(v))
            elif isinstance(cur, float):
                setattr(cfg, key, float(v))
            elif isinstance(cur, str):
                setattr(cfg, key, v)
            elif isinstance(cur, tuple):
                parts = [x.strip() for x in v.split(",") if x.strip()]
                try:
                    setattr(cfg, key, tuple(int(x) for x in parts))
                except ValueError:
                    setattr(cfg, key, tuple(parts))
        return cfg


def load_properties(path: str | None) -> dict[str, str]:
    """Parse a java-style key=value property file (reference nds_power.py:306-312)."""
    props: dict[str, str] = {}
    if not path:
        return props
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            name, _, value = line.partition("=")
            props[name.strip()] = value.strip()
    return props


def enable_x64() -> None:
    """Enable 64-bit JAX types; required for int64 keys and f64 decimals on CPU."""
    import jax

    jax.config.update("jax_enable_x64", True)


def with_host_platform(platforms: str | None) -> str | None:
    """``jax_platforms`` with the host CPU appended where an explicit list
    leaves it out (the first entry stays the default backend); unset or
    already listing cpu -> unchanged. Pure."""
    if platforms and "cpu" not in [p.strip() for p in platforms.split(",")]:
        return platforms + ",cpu"
    return platforms


def ensure_host_backend() -> None:
    """Keep the host CPU backend initialised beside the accelerator.

    The record pass ALWAYS runs on the CPU backend when the default device
    is an accelerator (JaxExecutor._eager_device). JAX initialises only the
    platforms ``JAX_PLATFORMS`` lists, so a launch environment naming the
    accelerator alone (``JAX_PLATFORMS=tpu``) would leave nothing to record
    on: the list gains cpu here, before the first backend init
    (Session.__init__). One behaviour however the variable was set."""
    import jax

    have = jax.config.jax_platforms
    want = with_host_platform(have)
    if want != have:
        jax.config.update("jax_platforms", want)


def apply_decimal(config: "EngineConfig", decimal: str | None) -> None:
    """Apply a runner-level decimal override and its preconditions.

    i64 (exact scaled-int64 decimals, the spec-faithful measured
    configuration; reference DecimalType nds_schema.py:43-47) needs 64-bit
    lanes. One shared helper so every runner enforces the same rules."""
    if decimal:
        if decimal not in ("f64", "i64"):
            raise ValueError(f"unknown decimal physical type {decimal!r} "
                             "(expected f64 or i64)")
        config.decimal_physical = decimal
    if config.decimal_physical == "i64":
        enable_x64()


#: the repository checkout this package runs from (compile-cache anchor)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir(environ=None) -> str | None:
    """The persistent-compile-cache directory this program sets IN CODE.

    None where ``JAX_COMPILATION_CACHE_DIR`` is set: JAX reads that variable
    itself, so whoever launches the program (the chip tool, a CI job) places
    the cache from outside and no code path names another directory.
    Otherwise ONE fixed path inside the checkout, ``<checkout>/.jax_cache``
    (git-ignored) — never a home directory, host hash, pid or temp name, so
    every CLI, server and test process of a checkout shares one cache and a
    second run finds what the first compiled. Pure: no jax import."""
    env = os.environ if environ is None else environ
    if env.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return os.path.join(_CHECKOUT, ".jax_cache")


def maybe_enable_compile_cache() -> None:
    """Default-on persistent compile cache for every entry point (runners,
    orchestrator, front-door server, bench, tests) — the reference reuses
    Spark's compiled plans across the whole stream (nds/nds_power.py:
    124-134); recompiling per process would bill XLA compile time to every
    phase. Placement: ``compile_cache_dir``. ``NDS_TPU_COMPILE_CACHE`` is a
    boolean opt-out only (0/false/no/off); it never names a directory."""
    import jax

    raw = os.environ.get("NDS_TPU_COMPILE_CACHE", "1")
    v = raw.lower()
    if v in ("0", "false", "no", "off"):
        jax.config.update("jax_enable_compilation_cache", False)
        return
    if v not in ("1", "true", "yes", "on"):
        raise ValueError(
            f"NDS_TPU_COMPILE_CACHE={raw!r}: use 0/1/true/false/on/off "
            "(place the cache with JAX_COMPILATION_CACHE_DIR)")
    path = compile_cache_dir()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however small or quick to compile: a query
    # stream is hundreds of sub-second kernels whose sum is the cold path
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
