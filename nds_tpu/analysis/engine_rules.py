"""ENG001/ENG002 — the original per-file engine-discipline rules.

ENG001 — frozen plan IR. Plan nodes and bound expressions (engine/plan.py
dataclasses) are treated as immutable everywhere: rewrite passes rebuild
copy-on-write (``dataclasses.replace``), because plans are DAGs — a node
reachable from several parents (shared CTE subtrees, segment-cache slots)
that is mutated in place silently shifts positional bindings for every
other consumer. Flags attribute assignments, augmented assignments,
subscript stores, and mutating container calls on plan-IR fields, except
builder-style writes to objects constructed in the same function,
``self.<field>`` in non-IR classes, and ``# lint: frozen-exempt`` lines.

ENG002 — cross-thread writes take the lock. Functions handed to worker
threads (``threading.Thread(target=...)``, ``pool.submit/map``) — or
marked concurrently-entered with the ``# lint: thread-entry`` def-header
pragma — must write shared attributes under a lock-shaped ``with``;
thread-local objects (constructed in-function) and
``# lint: lock-exempt`` lines pass.

Unlike the pre-package linter, pragma'd sites still EMIT findings, with
``suppressed=True`` — the runner filters them from output, and the
ENG007 hygiene pass uses them as proof the pragma is not stale.
"""
from __future__ import annotations

import ast

from .base import (Finding, def_header_pragma, dotted, has_pragma,
                   lock_ctx_name, root_name, suggestion_for)

# Plan-IR dataclass fields whose names are distinctive enough to identify a
# plan node / bound expression at a write site (engine/plan.py; keep in
# sync when the IR grows fields). Deliberately excludes names too generic
# to attribute (table, plan, index, dtype, name, value, op, args, extra,
# func, arg, kind, label, key, n, all, distinct, asc, left, right).
PLAN_FIELDS = frozenset({
    "out_names", "out_dtypes", "child", "predicate", "exprs",
    "left_keys", "right_keys", "residual", "null_aware", "late_mat",
    "star_build",
    "group_exprs", "aggs", "rollup", "rollup_levels", "funcs", "keys",
    "columns", "partition_by", "order_by", "nulls_first", "cte_segments",
})

# classes whose OWN attributes legitimately carry plan-field names: the IR
# dataclasses themselves (self-writes inside them are still flagged)
IR_CLASSES = frozenset({
    "PlanNode", "ScanNode", "FilterNode", "ProjectNode", "JoinNode",
    "AggregateNode", "WindowNode", "SortNode", "LimitNode", "DistinctNode",
    "SetOpNode", "MaterializedNode", "VirtualScanNode", "BExpr", "BCol",
    "BLit", "BCall", "BParam", "BScalarSubquery", "AggSpec", "SortKey",
    "WindowFunc",
})

MUTATOR_METHODS = frozenset({
    "append", "extend", "insert", "pop", "remove", "clear", "sort",
    "reverse", "update", "setdefault",
})


class _FunctionInfo:
    """Per-function facts shared by both rules."""

    def __init__(self, fn: ast.AST):
        self.fn = fn
        # local names bound from a direct ClassName(...) constructor call:
        # attribute writes through them are builder-style initialization
        self.owned: set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call) and \
                    isinstance(node.value.func, ast.Name) and \
                    node.value.func.id[:1].isupper():
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        self.owned.add(t.id)


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, src: str, engine_scope: bool):
        self.path = path
        self.lines = src.splitlines()
        self.engine_scope = engine_scope   # rule ENG001 applies here
        self.findings: list[Finding] = []
        self._class_stack: list[str] = []
        self._fn_stack: list[_FunctionInfo] = []
        # thread-target function names collected in a pre-pass
        self.thread_targets: set[str] = set()
        self._thread_depth = 0
        self._lock_depth = 0

    # -- helpers -------------------------------------------------------------
    def _add(self, node, rule: str, message: str, pragma: str) -> None:
        self.findings.append(Finding(
            self.path, node.lineno, node.col_offset, rule, message,
            suggestion=suggestion_for(rule),
            suppressed=has_pragma(self.lines, node.lineno, pragma)))

    def _owned(self, root: str) -> bool:
        return any(root in fi.owned for fi in self._fn_stack)

    def _in_ir_class(self) -> bool:
        return bool(self._class_stack) and \
            self._class_stack[-1] in IR_CLASSES

    # -- pre-pass: thread targets ---------------------------------------------
    def collect_thread_targets(self, tree: ast.AST) -> None:
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            cands: list[ast.expr] = []
            if isinstance(node.func, ast.Attribute):
                if node.func.attr == "Thread" or \
                        dotted(node.func).endswith("threading.Thread"):
                    cands += [k.value for k in node.keywords
                              if k.arg == "target"]
                elif node.func.attr in ("submit", "map") and node.args:
                    # pool.submit(fn, ...) / pool.map(fn, it): first arg
                    cands.append(node.args[0])
            elif isinstance(node.func, ast.Name) and \
                    node.func.id == "Thread":
                cands += [k.value for k in node.keywords
                          if k.arg == "target"]
            for c in cands:
                if isinstance(c, ast.Name):
                    self.thread_targets.add(c.id)
                elif isinstance(c, ast.Attribute):
                    self.thread_targets.add(c.attr)

    # -- traversal -------------------------------------------------------------
    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._class_stack.append(node.name)
        self.generic_visit(node)
        self._class_stack.pop()

    def _visit_fn(self, node) -> None:
        entered_thread = node.name in self.thread_targets \
            or def_header_pragma(self.lines, node, "thread-entry")
        self._fn_stack.append(_FunctionInfo(node))
        if entered_thread:
            self._thread_depth += 1
        self.generic_visit(node)
        if entered_thread:
            self._thread_depth -= 1
        self._fn_stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def visit_With(self, node: ast.With) -> None:
        locked = any(lock_ctx_name(i.context_expr) for i in node.items)
        if locked:
            self._lock_depth += 1
        self.generic_visit(node)
        if locked:
            self._lock_depth -= 1

    # -- write sites ------------------------------------------------------------
    def _check_store(self, target, stmt) -> None:
        # unwrap subscript stores: node.out_names[0] = x mutates out_names
        sub = target
        while isinstance(sub, ast.Subscript):
            sub = sub.value
        if isinstance(sub, ast.Attribute):
            self._check_attr_write(sub, stmt,
                                   subscript=sub is not target)
        # plain Name / Tuple targets mutate no object attribute

    def _check_attr_write(self, attr: ast.Attribute, stmt,
                          subscript: bool = False) -> None:
        root = root_name(attr.value)
        # ENG001: frozen plan IR
        if self.engine_scope and attr.attr in PLAN_FIELDS:
            allowed = (root == "self" and not self._in_ir_class()) or \
                (root != "self" and self._owned(root))
            if not allowed:
                how = "subscript store into" if subscript else \
                    "in-place assignment to"
                self._add(stmt, "ENG001",
                          f"{how} plan-IR field "
                          f"'{dotted(attr) or attr.attr}': plan nodes and "
                          "bound expressions are frozen — rebuild "
                          "copy-on-write (dataclasses.replace), or mark a "
                          "sanctioned builder with "
                          "'# lint: frozen-exempt (<reason>)'",
                          "frozen-exempt")
        # ENG002: unlocked write from a thread-target function
        if self._thread_depth > 0 and self._lock_depth == 0:
            if root and root != "self" and self._owned(root):
                return          # thread-local object, not shared state
            self._add(stmt, "ENG002",
                      f"attribute write '{dotted(attr) or attr.attr}' in "
                      "a thread-target function outside any lock: shared "
                      "session/streaming state must be written under its "
                      "lock ('with <lock>:'), or mark thread-local state "
                      "with '# lint: lock-exempt (<reason>)'",
                      "lock-exempt")

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            self._check_store(t, node)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._check_store(node.target, node)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self._check_store(node.target, node)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        # mutating container calls on plan-IR fields:
        # node.out_names.append(x)
        f = node.func
        if self.engine_scope and isinstance(f, ast.Attribute) and \
                f.attr in MUTATOR_METHODS and \
                isinstance(f.value, ast.Attribute) and \
                f.value.attr in PLAN_FIELDS:
            root = root_name(f.value.value)
            allowed = (root == "self" and not self._in_ir_class()) or \
                (root != "self" and self._owned(root))
            if not allowed:
                self._add(node, "ENG001",
                          f"mutating call '{dotted(f)}()' on a plan-IR "
                          "field: plan nodes are frozen — rebuild the list "
                          "copy-on-write", "frozen-exempt")
        self.generic_visit(node)


def lint_source_all(path: str, src: str,
                    engine_scope: bool | None = None) -> list[Finding]:
    """Per-file rules INCLUDING pragma-suppressed findings (the hygiene
    pass's evidence that a pragma still fires)."""
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 0, 0, "ENG000",
                        f"syntax error: {e.msg}")]
    if engine_scope is None:
        engine_scope = True      # plan IR may be touched from anywhere
    linter = _Linter(path, src, engine_scope)
    linter.collect_thread_targets(tree)
    linter.visit(tree)
    return sorted(linter.findings, key=lambda f: (f.path, f.line, f.col))


def lint_source(path: str, src: str,
                engine_scope: bool | None = None) -> list[Finding]:
    """Lint one file's source with the per-file rules (ENG001/ENG002);
    engine_scope controls ENG001. Pragma-suppressed findings are
    filtered — the historical single-file contract."""
    return [f for f in lint_source_all(path, src, engine_scope)
            if not f.suppressed]
