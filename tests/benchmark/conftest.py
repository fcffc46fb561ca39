"""Four cases of the benchmark's older test files count from "three committed
cells, none on four chips", and fail by construction once a fourth cell is
committed, whatever it is. A PR that adds a cell may add files here and edit
none, so the cases are marked as expected to fail, strictly (the day a
``benchmark`` PR rewrites their tables to count from what is committed they
pass, the marker turns that into a failure, and this file goes). What they
guarded is restated, relative to the committed manifest, in
``test_benchmark_cell_streamed_x4_cpu.py``."""
import pytest

WHY = ("counts from three committed cells with none on four chips; restated "
       "relative to the committed manifest in "
       "test_benchmark_cell_streamed_x4_cpu.py")
_HALF = "test_benchmark_manifest.py::" \
    "test_four_chip_cells_are_at_most_half_and_cells_at_most_24"
STALE = (
    _HALF + "[21-12-[]]",
    _HALF + "[4-4-['4 of 7 cells ask for 4 chips']]",
    _HALF + "[22-1-['25 cells, at most 24']]",
    "test_benchmark_add_by_files.py::"
    "test_the_appended_entries_keep_the_manifests_rules",
)


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(STALE):
            item.add_marker(pytest.mark.xfail(
                reason=WHY, strict=True, raises=AssertionError))
