"""What the plain references share: the warehouse read straight from its
Parquet files into pandas, and the shape an answer takes.

Nothing here imports the program. Decimals become exact ``int64`` counts of
their last place (cents for the NDS ``decimal(7,2)`` columns), so sums are
integer sums; nullable integers and strings keep their NULLs (pandas
``Int64`` / ``string``). An average is the exact rational ``sum / count``
rounded once to a double (Python's int / int is correctly rounded).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from decimal import Decimal

import pandas as pd
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq


class Warehouse:
    """Reads ``<root>/<table>/data/*.parquet``; each (table, columns) once."""

    def __init__(self, root: str):
        self.root = root
        self._frames: dict = {}
        #: the unit whose reference is reading, and every read it made as
        #: (unit, table, bytes the scan must read): what scan_roofline_pct
        #: counts, see ``scan_bytes``
        self.unit = ""
        self.reads: list = []

    def table(self, name: str, columns: list[str]) -> pd.DataFrame:
        key = (name, tuple(columns))
        if key not in self._frames:
            t = pq.read_table(os.path.join(self.root, name, "data"),
                              columns=columns)
            self._frames[key] = (_to_frame(t), scan_bytes(t))
        frame, nbytes = self._frames[key]
        self.reads.append((self.unit, name, nbytes))
        return frame


def scan_bytes(t: pa.Table) -> int:
    """The fewest bytes a scan of these columns has to read: each integer at
    its Parquet width, a decimal at the narrowest integer that holds its
    precision (4 bytes to 9 digits, 8 to 18), a string at its own bytes."""
    total = 0
    for col in t.columns:
        typ = col.type
        if pa.types.is_decimal(typ):
            width = 4 if typ.precision <= 9 else \
                8 if typ.precision <= 18 else 16
            total += width * len(col)
        elif pa.types.is_string(typ) or pa.types.is_large_string(typ):
            total += pc.sum(pc.binary_length(col)).as_py() or 0
        else:
            total += typ.bit_width // 8 * len(col)
    return int(total)


def _to_frame(t: pa.Table) -> pd.DataFrame:
    cols = {}
    for name, col in zip(t.column_names, t.columns):
        typ = col.type
        if pa.types.is_decimal(typ):
            unit = pa.scalar(Decimal(10) ** typ.scale)
            col = pc.multiply(col, unit).cast(pa.int64())
            typ = pa.int64()
        if pa.types.is_integer(typ):
            cols[name] = col.to_pandas(types_mapper={
                typ: pd.Int64Dtype()}.get).astype("Int64")
        elif pa.types.is_string(typ) or pa.types.is_large_string(typ):
            cols[name] = col.to_pandas().astype("string")
        else:
            cols[name] = col.to_pandas()
    return pd.DataFrame(cols)


#: how a column of an answer is compared
EXACT = "exact"        # integers, strings, NULLs: equal or wrong
DECIMAL = "decimal"    # (unscaled int, scale): exact where the
#                        configuration states exact decimals
FLOAT = "float"        # an average: a double, compared by relative error


@dataclass
class Answer:
    """A reference result: ``names``/``kinds`` per column and ``rows`` as
    tuples in the statement's ORDER BY order, *not yet cut* by ``limit``
    (the comparison needs the rows just past the cut to judge ties).
    A DECIMAL cell is ``(unscaled, scale)``; ``sort_cols`` are the
    positions of the ORDER BY columns, for the tie rule at the cut."""
    names: list
    kinds: list
    rows: list
    limit: int | None = None
    sort_cols: tuple = ()


def ratio(num, den, scale: int = 0):
    """``num / (den * 10**scale)`` rounded once to a double; NULL on 0."""
    if den is None or pd.isna(den) or int(den) == 0 or pd.isna(num):
        return None
    return int(num) / (int(den) * 10 ** scale)


def null_first(v):
    """Sort key for an ascending column with NULLs first (the engine's and
    Spark's order)."""
    return (0, 0) if v is None else (1, v)


def cell(v):
    """pandas scalar -> plain Python (None for NULL)."""
    if v is None or v is pd.NA or (isinstance(v, float) and v != v):
        return None
    return v.item() if hasattr(v, "item") else v
