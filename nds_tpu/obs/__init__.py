"""Observability layer: span tracing, metrics, the device trace's names.

Built BEFORE the kernel/sharding work (ROADMAP items 1-2) because the
engine could not say which operator in which query burns the chip's time
— this package is the instrument those PRs are measured with.

- :mod:`.trace`   — lifecycle span tracer (parse -> plan passes ->
  compile -> upload -> per-morsel exec -> finalize) with Chrome-trace /
  JSONL / aggregate exporters; near-zero cost disabled. On, every span
  is also a ``jax.profiler.TraceAnnotation`` ``nds.<span>[:<label>]`` on
  its thread, XLA's trace / lower / compile phases arrive as ``xla.*``
  spans, and the exports carry the clock anchor that lays them over a
  device trace (the span names are listed in the module's docstring).
- :mod:`.metrics` — process-wide typed counter/gauge/histogram registry
  every layer writes through (one shared value lock per registry: every
  snapshot is an atomic cut); histograms carry {tenant, template} labels
  so per-tenant p50/p95/p99 read live; Prometheus/JSON exporters.
  ``xla_compiles`` / ``xla_cache_hits`` / ``xla_cache_misses`` count
  XLA's own compile and persistent-cache events (``jax.monitoring``),
  always on, host kernels of the record pass included.
- :mod:`.flight`  — bounded ring of query-lifecycle events, JSONL-dumped
  on demand, on rejection storms, or when a fault point fires (the
  post-mortem artifact chaos runs assert against).
- :mod:`.xplane`  — a ``jax.profiler`` trace reduced under the program's
  own names: device time per program (every plan program is the HLO module
  ``jit_nds_<query>_<unit>``: ``jit_nds_query9_root``, ``..._seg_3fa91c02``,
  ``..._root_batch4``, ``..._morsel_store_sales``, ``..._local`` /
  ``..._gather`` when sharded; ``executor.program_name``), idle gaps by
  the covering ``nds.`` span, and the check that the two clocks agree.
  One merged picture: ``power --trace T.json --profile_folder P`` (the
  front-door server takes the same two flags), then
  ``scripts/trace_report.py --xplane P/.../*.xplane.pb T.json``.
- :mod:`.stats`   — the typed ``ExecStats`` replacing the untyped
  ``last_exec_stats`` dict (dict view preserved).
- :mod:`.profile` — EXPLAIN ANALYZE: per-plan-node runtime profiles
  under the verifier's stable TypeName#k identities, the
  estimate-vs-actual cardinality audit, and the device-memory watermark
  accountant (``DEVICE_MEM``) the upload paths write through.
- :mod:`.query_log` — durable query log: one flat row per completed
  statement (bounded ring + opt-in rotating JSONL) — the
  ``system.query_log`` source.
- :mod:`.system_tables` — the ``system`` catalog: metrics, histograms,
  query log, programs, result cache, device memory, flight ring, and
  catalog generations as SQL-queryable tables on the host-only path.
- :mod:`.scrape`  — stdlib-http scrape endpoint (``/metrics``,
  ``/healthz``, ``/query?sql=...``): the first wire-visible operator
  surface.
- :mod:`.log`     — ``logging``-based diagnostics channel with one
  verbosity knob, replacing raw stderr writes.
"""
from .trace import TRACER, span                                  # noqa: F401
from .metrics import METRICS                                     # noqa: F401
from .flight import FLIGHT                                       # noqa: F401
from .query_log import QUERY_LOG                                 # noqa: F401
from .stats import ExecStats                                     # noqa: F401
from .profile import DEVICE_MEM, PlanProfile                     # noqa: F401
from .log import get_logger                                      # noqa: F401
