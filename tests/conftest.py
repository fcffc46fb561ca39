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


# Two cases of tests/benchmark/test_benchmark_cell_streamed_x4_cpu.py pin what
# ISSUE 29 changes, in a file that only a `benchmark` PR may edit: that PR
# 28's four per-layer metrics are the LAST of BENCHMARK.json (any metric
# appended after them breaks it), and that a timed query3 on four chips
# gathers over 5 MB of partials a statement (a second or later sighting now
# gathers what its first whole pass saw). They are marked as expected to
# fail, strictly — the day a `benchmark` PR restates them they pass, the
# marker turns that into a failure, and this block goes — and are restated
# relative to the committed manifest in
# tests/benchmark/test_benchmark_tight_morsels_cpu.py.
_STALE_SINCE_PR_29 = tuple(
    "test_benchmark_cell_streamed_x4_cpu.py::" + name for name in (
        "test_the_four_new_metrics_are_data_over_the_readers_that_are_there",
        "test_the_traced_run_prints_the_four_new_metrics"))


# The same for ISSUE 31, which appends `mask_carried_filters_per_pass`: one
# case of tests/benchmark/test_benchmark_tight_morsels_cpu.py pins that
# `tight_morsels_per_pass` is the LAST of `per_layer`; restated in
# tests/benchmark/test_benchmark_mask_carried_cpu.py.
_STALE_SINCE_PR_31 = (
    "test_benchmark_tight_morsels_cpu.py::"
    "test_the_metric_is_data_appended_after_pr_28s_four",)


# And for ISSUE 35, which appends `direct_joins_per_pass` and
# `sorted_joins_per_pass`: one case of
# tests/benchmark/test_benchmark_cell_strata_cpu.py pins that PR 32's four
# counters are the LAST of `per_layer`; restated in
# tests/benchmark/test_benchmark_join_paths_cpu.py.
_STALE_SINCE_PR_35 = (
    "test_benchmark_cell_strata_cpu.py::"
    "test_the_four_counters_are_data_over_the_reader_that_is_there",)


# And for ISSUE 38, which sizes the direct-address join's table from the span
# of the live build keys: one parametrised case of
# tests/benchmark/test_benchmark_join_paths_cpu.py pins that a filtered
# dimension takes the sort-based path on one chip (`streamed_scan_sf1` at no
# direct and two sorted joins a morsel); restated, with the four-chip case
# beside it, in tests/benchmark/test_benchmark_wide_span_joins_cpu.py.
_STALE_SINCE_PR_38 = (
    "test_benchmark_join_paths_cpu.py::"
    "test_the_streamed_mix_counts_a_morsels_joins_once_a_dispatch"
    "[streamed_scan_sf1]",)


# And for ISSUE 40, which appends `decode_view_cols_per_pass`: one case of
# tests/benchmark/test_benchmark_stream_main_cpu.py pins that PR 39's nine
# metrics are the LAST of `per_layer`; restated in
# tests/benchmark/test_benchmark_view_cols_cpu.py.
_STALE_SINCE_PR_40 = (
    "test_benchmark_stream_main_cpu.py::"
    "test_the_nine_are_appended_after_what_was_there_in_the_issues_order",)


# And for ISSUE 42, which appends the cell `power_inventory_sf1` and four row
# counters: three cases pin what a sixth cell changes, in files only a
# `benchmark` PR may edit — that `power_stratified_sf1` is the LAST cell to
# report `pass_s`, that `direct_joins_per_pass` / `sorted_joins_per_pass`
# list exactly four cells (and `outer_joins_per_pass` the strata cell alone),
# and the half rule's arithmetic, which adds four cells in all and so holds
# up to five committed ones. Restated, relative to the committed manifest,
# in tests/benchmark/test_benchmark_cell_inventory_cpu.py.
_STALE_SINCE_PR_42 = (
    "test_benchmark_cell_strata_cpu.py::"
    "test_the_cell_stands_after_the_accepted_four_on_one_chip",
    "test_benchmark_join_paths_cpu.py::"
    "test_the_two_metrics_are_data_appended_after_pr_32s_four_counters",
    "test_benchmark_cell_streamed_x4_cpu.py::"
    "test_the_half_rule_and_the_24_count_from_what_is_committed"
    "[one_over_half]")


# And for ISSUE 43, which appends `star_joins_per_pass`: one case of
# tests/benchmark/test_benchmark_cell_inventory_cpu.py pins that PR 42's four
# row counters are the LAST of `per_layer`; restated in
# tests/benchmark/test_benchmark_star_joins_cpu.py.
_STALE_SINCE_PR_43 = (
    "test_benchmark_cell_inventory_cpu.py::"
    "test_the_four_row_counters_are_data_appended_after_what_was_there",)


def pytest_collection_modifyitems(items):
    for item in items:
        for stale, issue, restated in (
                (_STALE_SINCE_PR_29, 29, "test_benchmark_tight_morsels_cpu"),
                (_STALE_SINCE_PR_31, 31, "test_benchmark_mask_carried_cpu"),
                (_STALE_SINCE_PR_35, 35, "test_benchmark_join_paths_cpu"),
                (_STALE_SINCE_PR_38, 38, "test_benchmark_wide_span_joins_cpu"),
                (_STALE_SINCE_PR_40, 40, "test_benchmark_view_cols_cpu"),
                (_STALE_SINCE_PR_42, 42,
                 "test_benchmark_cell_inventory_cpu"),
                (_STALE_SINCE_PR_43, 43, "test_benchmark_star_joins_cpu")):
            if item.nodeid.endswith(stale):
                item.add_marker(pytest.mark.xfail(
                    reason=f"pins what ISSUE {issue} changes; restated in "
                           f"{restated}.py",
                    strict=True, raises=AssertionError))
