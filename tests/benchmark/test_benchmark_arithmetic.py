"""The end-to-end arithmetic is taken over the whole window: a stall
injected into one pass or one request moves the numbers."""
import math
import time

import pytest
from bench_helpers import ROOT  # noqa: F401  (puts the repo on sys.path)

from benchmark import compare, drivers, readers
from benchmark.refdata import DECIMAL, EXACT, FLOAT, Answer


class _Loop(drivers.PassLoop):
    """PassLoop over statements that only sleep."""

    def __init__(self, stall_in_pass=None, pause=0.02):
        super().__init__({}, {}, ["a", "b"], readers.Observations(False))
        self.stall_in_pass, self.pause, self.calls = stall_in_pass, pause, 0

    def _open_window(self):
        pass

    def _close_window(self):
        pass

    def _run(self, st, window, ui=0):
        time.sleep(self.pause)
        if self.stall_in_pass is not None and \
                self.calls == 2 * self.stall_in_pass:
            time.sleep(0.3)
        self.calls += 1
        window.attempted += 1


def _pass_s(loop, seconds):
    w = loop.window(seconds)
    return w, loop.end_to_end(w, 0)["pass_s"]


def test_pass_s_is_the_window_over_its_passes_and_a_stall_moves_it():
    steady, steady_s = _pass_s(_Loop(), 0.5)
    assert steady_s == pytest.approx(0.04, abs=0.01)
    assert steady.work == steady.attempted // 2 >= 8
    stalled, stalled_s = _pass_s(_Loop(stall_in_pass=2), 0.5)
    # the 0.3 s stall is spread over every pass of the window: no median of
    # passes would show it
    assert stalled_s * stalled.work == pytest.approx(
        0.04 * stalled.work + 0.3, abs=0.03)
    assert stalled_s > 1.5 * steady_s


def test_no_pass_starts_that_would_end_after_the_window():
    w, pass_s = _pass_s(_Loop(pause=0.1), 0.5)
    assert w.work == 2 and pass_s * w.work <= 0.5
    assert _pass_s(_Loop(pause=0.2), 0.1)[0].work == 1   # never fewer than one


@pytest.mark.parametrize("q,want", [(50, 5), (95, 10), (100, 10), (10, 1)])
def test_percentile_is_nearest_rank_over_all_values(q, want):
    assert drivers.percentile(list(range(10, 0, -1)), q) == want


def test_served_numbers_count_every_request_and_a_stall_moves_the_tail():
    lat = [100.0] * 99
    calm = drivers.served_values(lat + [100.0], good=100, seconds=10.0)
    assert calm == {"served_qps": 10.0, "served_ms_p50": 100.0,
                    "served_ms_p95": 100.0}
    stalled = drivers.served_values(lat[:93] + [2000.0] * 7, good=100,
                                    seconds=10.0)
    assert stalled["served_ms_p50"] == 100.0
    assert stalled["served_ms_p95"] == 2000.0
    # a failed request is infinitely late and is not served
    failed = drivers.served_values(lat[:90] + [math.inf] * 10, good=90,
                                   seconds=10.0)
    assert failed["served_qps"] == 9.0 and failed["served_ms_p95"] == math.inf


EXACT_LIMITS = {"wrong_cells": 0, "decimal_err": 0, "float_rel_err": 1e-13}
F32_LIMITS = {"wrong_cells": 0, "float_rel_err": 1e-4}


def _q3_like():
    rows = [(1998, i, f"brand{i}", (1000 - i, 2)) for i in range(1, 104)]
    return Answer(["d_year", "brand_id", "brand", "sum_agg"],
                  [EXACT, EXACT, EXACT, DECIMAL], rows, limit=100,
                  sort_cols=(0, 3, 1))


def _got(ans, n=100):
    from decimal import Decimal
    return [(r[0], r[1], r[2], Decimal(r[3][0]).scaleb(-r[3][1]))
            for r in ans.rows[:n]]


def test_comparison_passes_the_exact_answer_and_counts_each_fault():
    ans = _q3_like()
    ok = compare.Comparison(True, EXACT_LIMITS)
    assert ok.check("q", _got(ans), ans) and ok.numbers() == {
        "wrong_cells": 0, "float_rel_err": 0.0, "decimal_err": 0.0}
    short = compare.Comparison(True, EXACT_LIMITS)
    assert not short.check("q", _got(ans, 99), ans)
    swapped = compare.Comparison(True, EXACT_LIMITS)
    rows = _got(ans)
    rows[5] = rows[5][:2] + ("other",) + rows[5][3:]
    assert not swapped.check("q", rows, ans)
    # a row from beyond the cut in place of one before it
    beyond = compare.Comparison(True, EXACT_LIMITS)
    rows = _got(ans)[:-1] + _got(ans, 103)[-1:]
    assert not beyond.check("q", rows, ans)


def test_a_double_is_not_an_exact_decimal():
    ans = _q3_like()
    as_float = [(r[0], r[1], r[2], r[3][0] / 100 + 1e-9) for r in ans.rows]
    c = compare.Comparison(True, EXACT_LIMITS)
    assert not c.check("q", as_float[:100], ans)          # limit 0: fails
    assert c.wrong_cells == 0 and c.decimal_err > 0
    loose = compare.Comparison(False, F32_LIMITS)
    assert loose.check("q", as_float[:100], ans)
    assert loose.wrong_cells == 0 and 0 < loose.float_rel_err < 1e-9


def test_rows_that_tie_at_the_cut_may_swap_only_within_the_tie():
    rows = [(i, (500, 2)) for i in range(1, 4)] + [(9, (100, 2))]
    ans = Answer(["k", "s"], [EXACT, DECIMAL], rows, limit=2, sort_cols=(1,))
    from decimal import Decimal
    five = Decimal("5.00")
    for pick in ([(1, five), (2, five)], [(3, five), (1, five)]):
        assert compare.Comparison(True, EXACT_LIMITS).check("q", pick, ans)
    assert not compare.Comparison(True, EXACT_LIMITS).check(
        "q", [(1, five), (9, Decimal("1.00"))], ans)


def test_bf16_control_fails_the_float32_limit():
    ans = Answer(["k", "avg"], [EXACT, FLOAT],
                 [("a", 123.456789), ("b", 0.5)])
    c = compare.Comparison(False, F32_LIMITS)
    assert not c.check("q", compare.as_bf16(ans), ans)
    assert c.wrong_cells == 0 and 1e-4 < c.float_rel_err < 8e-3
    assert compare._bf16(0.5) == 0.5 and compare._bf16(1.0 + 2 ** -9) == 1.0
