"""The comparison that decides ``correct``: what the timed path returned
against the plain reference's answer.

Three numbers, each with a limit of its own (``configs/<config>.json``
``limits``):

``wrong_cells``    rows missing, extra or out of place at a LIMIT, and cells
                   of integers, strings and NULLs that differ. Limit 0.
``decimal_err``    exact-decimal configurations only: the largest distance
                   of a DECIMAL cell from the reference, in units of the
                   column's last place. Limit 0: a double is not a decimal.
``float_rel_err``  averages (and decimals where the configuration computes
                   them in floats): largest |got - ref| / max(|ref|, 1).
"""
from __future__ import annotations

from decimal import Decimal
from fractions import Fraction

from benchmark.refdata import DECIMAL, EXACT, FLOAT, Answer


def ipc_bytes(table) -> bytes:
    """A pyarrow table as Arrow IPC stream bytes (how a served client hands
    its answers back, and the key that tells distinct answers apart)."""
    import pyarrow as pa
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, table.schema) as writer:
        writer.write_table(table)
    return sink.getvalue().to_pybytes()


def ipc_table(data: bytes):
    import pyarrow as pa
    return pa.ipc.open_stream(data).read_all()


def table_rows(table) -> list[tuple]:
    """A pyarrow table as tuples of plain Python cells, by position."""
    return list(zip(*[c.to_pylist() for c in table.columns])) \
        if table.num_columns else []


def _numeric(ref, kind):
    """The reference cell as an exact Fraction in natural units."""
    if kind == DECIMAL:
        return Fraction(ref[0], 10 ** ref[1])
    return Fraction(ref)


def _as_fraction(v):
    return Fraction(v) if isinstance(v, (int, float, Decimal)) else None


class Comparison:
    def __init__(self, decimal_exact: bool, limits: dict):
        self.decimal_exact = decimal_exact
        self.limits = limits
        #: ORDER BY keys computed in floats tie within this relative gap
        self.tie_tol = 0.0 if decimal_exact else limits["float_rel_err"]
        self.wrong_cells = 0
        self.decimal_err = 0.0
        self.float_rel_err = 0.0
        self.notes: list[str] = []

    def _note(self, text: str) -> None:
        self.wrong_cells += 1
        if len(self.notes) < 8:
            self.notes.append(text)

    def _near(self, a, b, kind) -> bool:
        if kind == EXACT or a is None or b is None:
            return a == b
        fa, fb = _numeric(a, kind), _numeric(b, kind)
        return abs(fa - fb) <= self.tie_tol * max(abs(fb), 1)

    def _window(self, ans: Answer) -> tuple[int, int]:
        """(required, allowed): rows [0, required) must be returned and only
        rows [0, allowed) may be — they differ where rows tie at the cut."""
        n, lim = len(ans.rows), ans.limit
        if lim is None or n <= lim:
            return n, n
        edge = ans.rows[lim - 1]

        def ties(row):
            return all(self._near(row[c], edge[c], ans.kinds[c])
                       for c in ans.sort_cols)
        lo = lim - 1
        while lo > 0 and ties(ans.rows[lo - 1]):
            lo -= 1
        hi = lim
        while hi < n and ties(ans.rows[hi]):
            hi += 1
        return (lo if hi > lim else lim), hi

    def check(self, what: str, got_rows: list[tuple], ans: Answer) -> bool:
        """Compare one answer; True when it is within every limit."""
        before = self.wrong_cells
        worst = (self.decimal_err, self.float_rel_err)
        self.decimal_err = self.float_rel_err = 0.0
        required, allowed = self._window(ans)
        want = len(ans.rows) if ans.limit is None \
            else min(ans.limit, len(ans.rows))
        if len(got_rows) != want:
            self._note(f"{what}: {len(got_rows)} rows, reference {want}")
        exact = [i for i, k in enumerate(ans.kinds) if k == EXACT]
        pool: dict = {}
        for idx in range(allowed):
            row = ans.rows[idx]
            pool.setdefault(tuple(row[i] for i in exact), []).append(idx)
        used = set()
        for got in got_rows:
            if len(got) != len(ans.kinds):
                self._note(f"{what}: {len(got)} columns, reference "
                           f"{len(ans.kinds)}")
                continue
            cands = pool.get(tuple(got[i] for i in exact))
            if not cands:
                self._note(f"{what}: row {got!r} not in the reference")
                continue
            idx = cands.pop(0)
            used.add(idx)
            self._cells(what, got, ans.rows[idx], ans.kinds)
        missing = [i for i in range(required) if i not in used]
        if missing and len(got_rows) == want:
            self._note(f"{what}: reference row {ans.rows[missing[0]]!r} "
                       "not returned")
        ok = self.wrong_cells == before and \
            self.decimal_err <= self.limits.get("decimal_err", 0) and \
            self.float_rel_err <= self.limits["float_rel_err"]
        self.decimal_err = max(self.decimal_err, worst[0])
        self.float_rel_err = max(self.float_rel_err, worst[1])
        return ok

    def _cells(self, what, got, ref, kinds) -> None:
        for g, r, kind in zip(got, ref, kinds):
            if kind == EXACT:
                continue              # matched through the key
            if g is None or r is None:
                if g is not r:
                    self._note(f"{what}: {g!r} where the reference has {r!r}")
                continue
            fg = _as_fraction(g)
            if fg is None:
                self._note(f"{what}: {g!r} is no number")
                continue
            fr = _numeric(r, kind)
            if kind == DECIMAL and self.decimal_exact:
                self.decimal_err = max(self.decimal_err,
                                       float(abs(fg - fr) * 10 ** r[1]))
            else:
                self.float_rel_err = max(
                    self.float_rel_err,
                    float(abs(fg - fr) / max(abs(fr), 1)))

    def numbers(self) -> dict:
        out = {"wrong_cells": self.wrong_cells,
               "float_rel_err": self.float_rel_err}
        if self.decimal_exact:
            out["decimal_err"] = self.decimal_err
        return out


def _bf16(x: float) -> float:
    """``x`` rounded to the nearest bfloat16 (ties to even)."""
    import struct
    bits = struct.unpack("<I", struct.pack("<f", x))[0]
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return struct.unpack("<f", struct.pack("<I", bits))[0]


def as_bf16(ans: Answer) -> list[tuple]:
    """The lower-precision control of a float32 configuration: the
    reference's own answer with every computed number rounded to bfloat16,
    the least error a computation in bfloat16 could make."""
    rows = ans.rows if ans.limit is None else ans.rows[:ans.limit]
    out = []
    for row in rows:
        cells = []
        for v, kind in zip(row, ans.kinds):
            if v is not None and kind != EXACT:
                v = _bf16(float(_numeric(v, kind)))
            cells.append(v)
        out.append(tuple(cells))
    return out
