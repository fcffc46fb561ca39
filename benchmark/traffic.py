"""The one traffic generator: a mix's data file + ``--seed`` -> statements.

A mix (``traffic/<mix>.json``) names its window ``driver`` and its ``units``;
a unit is ``units/<unit>.tpl`` — SQL with ``[NAME]`` holes, each defined on a
leading ``-- define [NAME] = uniform_int(lo, hi) | choice(...) |
choice_n(k, ...)`` line (TPC-DS's own parameter domains) — with its plain
reference ``units/<unit>.py`` beside it.

``--seed`` orders the work and nothing else: the order of the units in a pass,
the permutation the served clients walk. The parameters are drawn once, from
the mix's own ``param_seed``: the program compiles a statement's parameters
into its programs (on the v5e a new draw recompiled nearly everything: 100 to
200 s more set-up, PERF.md PR 24), and a run has to find every program in the
cache after the checkout's first. The data has no seed either (the native
generator's salts are fixed), so every seed sees the same warehouse.
"""
from __future__ import annotations

import ast
import hashlib
import json
import os
import random
import re
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
_DEFINE = re.compile(r"^--\s*define\s+\[(\w+)\]\s*=\s*(\w+)\((.*)\)\s*$")


@dataclass
class Statement:
    unit: str            # names units/<unit>.tpl and units/<unit>.py
    params: dict         # NAME -> the text put into the hole
    sql: str


def load_json(kind: str, name: str) -> dict:
    """``<kind>/<name>.json`` of the benchmark; an unknown name is an error."""
    path = os.path.join(HERE, kind, name + ".json")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no {kind}/{name}.json")
    with open(path) as f:
        return json.load(f)


def _rng(seed: int, *what) -> random.Random:
    h = hashlib.sha256("/".join(map(str, (seed,) + what)).encode()).digest()
    return random.Random(int.from_bytes(h[:8], "little"))


def _draw(fn: str, args: tuple, rng: random.Random) -> str:
    if fn == "uniform_int":
        return str(rng.randint(args[0], args[1]))
    if fn == "choice":
        return str(rng.choice(args))
    if fn == "choice_n":
        return ", ".join(f"'{v}'" for v in rng.sample(args[1:], args[0]))
    raise ValueError(f"unsupported parameter domain {fn}")


def instantiate(unit: str, seed: int) -> Statement:
    path = os.path.join(HERE, "units", unit + ".tpl")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no units/{unit}.tpl")
    params, body = {}, []
    with open(path) as f:
        for line in f:
            m = _DEFINE.match(line.strip())
            if m:
                args = ast.literal_eval("(" + m.group(3) + ",)")
                params[m.group(1)] = _draw(m.group(2), args,
                                           _rng(seed, unit, m.group(1)))
            elif not line.lstrip().startswith("--"):
                body.append(line.rstrip("\n"))
    sql = "\n".join(body).strip().rstrip(";")
    for name, value in params.items():
        sql = sql.replace(f"[{name}]", value)
    hole = re.search(r"\[([A-Z_0-9]+)\]", sql)
    if hole:
        raise ValueError(f"{unit}.tpl: [{hole.group(1)}] has no define")
    return Statement(unit, params, sql)


def statements(mix: dict, seed: int) -> list[Statement]:
    """The mix's units with the mix's parameters, in the seed's order."""
    out = [instantiate(u, mix["param_seed"]) for u in mix["units"]]
    _rng(seed, "order").shuffle(out)
    return out


def client_walk(n_units: int, client: int) -> list[int]:
    """The cycle a served client walks again and again: the statements in
    the run's order, begun at the client's own offset. Every seed therefore
    sends the same set of walks, with the statements in another order."""
    return [(client + i) % n_units for i in range(n_units)]
