"""AST-level repo lint: source-side invariants the jaxpr census cannot
see.

The trace-level rules (:mod:`repro.analysis.rules`) prove properties of
*programs that got traced*; this module proves properties of the
*source tree* — that nobody even wrote the code that would break them.
It replaces the historical grep gate in ``tests/test_engine.py`` and is
exposed as a CLI via ``tools/lint_invariants.py``.

Rule catalogue (scopes are repo-relative directory prefixes):

``loop-shell``
    no ``lax.while_loop`` / ``lax.scan`` shells in solver code outside
    ``core/engine.py`` — every bulk-synchronous loop must run on the
    sweep engine so the ScanChunkShape contract stays provable.
    (``fori_loop`` is allowed: it has no carry-pytree surface and the
    engine deliberately does not wrap it.)
``interpret-literal``
    no hardcoded ``interpret=True`` anywhere in ``src/repro`` — backend
    resolution belongs to ``kernels.runtime.resolve_interpret``.
``host-sync``
    no ``block_until_ready`` / ``jax.device_get`` under ``core/`` or
    ``kernels/`` — host synchronisation is the serving/launch tiers'
    decision, never the solver's.
``int64-state-cast``
    a cast of a *state-named* array (res/res0/e/h/b/excess/state.*) to
    int64 in solver code must sit in a function that also narrows
    through ``as_state_dtype`` (the blessed widen-compute-narrow
    pattern), or carry an explicit ``# lint-ok: int64-state-cast``
    pragma stating it stays host-side.
``bare-assert``
    no message-less ``assert`` in library code: the ``-O`` CI lane
    strips asserts, so a bare one is a check that silently stopped
    existing and a debugging session when it would have fired.
``private-walker``
    no ad-hoc jaxpr walking in ``tests/`` or ``benchmarks/`` — no
    ``.eqns`` attribute access, no ``count_jaxpr_eqns`` imports; all
    trace-shape assertions go through :mod:`repro.analysis.ir`.

Suppression: append ``# lint-ok: <rule>[, <rule>...]`` to the offending
line.  Each pragma is a visible, greppable waiver — the point is that
exceptions are declared, not silent.
"""
from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Iterable, Iterator

__all__ = ["LintFinding", "run_lint", "lint_file", "RULE_SCOPES"]

_PRAGMA_RE = re.compile(r"#\s*lint-ok:\s*([\w-]+(?:\s*,\s*[\w-]+)*)")

#: array names that hold solver state (the int32 device lattice)
_STATE_NAMES = frozenset({
    "res", "res0", "res2", "e", "h", "b", "excess", "state",
    "prev_res", "prev_e", "prev_h",
})

#: rule name -> (included path prefixes, excluded exact paths)
RULE_SCOPES = {
    "loop-shell": (("src/repro/core", "src/repro/kernels",
                    "src/repro/streaming", "src/repro/serving"),
                   ("src/repro/core/engine.py",)),
    "interpret-literal": (("src/repro",),
                          ("src/repro/kernels/runtime.py",)),
    "host-sync": (("src/repro/core", "src/repro/kernels"), ()),
    "int64-state-cast": (("src/repro/core", "src/repro/streaming",
                          "src/repro/serving", "src/repro/api"), ()),
    "bare-assert": (("src/repro/core", "src/repro/kernels",
                     "src/repro/streaming", "src/repro/serving",
                     "src/repro/api", "src/repro/obs",
                     "src/repro/graphs", "src/repro/analysis"), ()),
    "private-walker": (("tests", "benchmarks"), ()),
}


@dataclasses.dataclass(frozen=True)
class LintFinding:
    """One source-level invariant violation."""

    rule: str
    path: str  # repo-relative
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _pragmas(source: str) -> dict[int, frozenset[str]]:
    out = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _PRAGMA_RE.search(line)
        if m:
            out[i] = frozenset(p.strip() for p in m.group(1).split(","))
    return out


def _in_scope(rule: str, rel: str) -> bool:
    include, exclude = RULE_SCOPES[rule]
    if rel in exclude:
        return False
    return any(rel == p or rel.startswith(p + "/") for p in include)


def _attr_chain(node) -> tuple[str, ...]:
    """``jax.lax.while_loop`` -> ('jax', 'lax', 'while_loop'); empty
    tuple for anything that is not a plain name/attribute chain."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return ()


def _root_state_name(node) -> str | None:
    """The state name a cast source resolves to: bare ``res``, attribute
    ``state.res`` / ``self._res``, or a subscript of either."""
    while isinstance(node, (ast.Subscript, ast.Call)):
        node = node.value if isinstance(node, ast.Subscript) else node
        if isinstance(node, ast.Call):  # e.g. res.copy() — unwrap method
            if isinstance(node.func, ast.Attribute):
                node = node.func.value
            else:
                return None
    if isinstance(node, ast.Attribute):
        name = node.attr
    elif isinstance(node, ast.Name):
        name = node.id
    else:
        return None
    return name if name.lstrip("_") in _STATE_NAMES else None


def _is_int64_dtype(node) -> bool:
    chain = _attr_chain(node)
    if chain and chain[-1] in ("int64", "uint64"):
        return True
    return isinstance(node, ast.Constant) and node.value in ("int64",
                                                             "uint64")


def _int64_cast_source(call: ast.Call):
    """The array being cast, when ``call`` is an int64 cast — either
    ``X.astype(int64)`` or ``np.(as)array(X, int64)``; else None."""
    if (isinstance(call.func, ast.Attribute) and call.func.attr == "astype"
            and call.args and _is_int64_dtype(call.args[0])):
        return call.func.value
    chain = _attr_chain(call.func)
    if chain and chain[-1] in ("asarray", "array", "ascontiguousarray"):
        dtype_args = list(call.args[1:]) + [
            kw.value for kw in call.keywords if kw.arg == "dtype"]
        if call.args and any(_is_int64_dtype(a) for a in dtype_args):
            return call.args[0]
    return None


class _Visitor(ast.NodeVisitor):
    def __init__(self, rel: str, pragmas: dict[int, frozenset[str]]):
        self.rel = rel
        self.pragmas = pragmas
        self.findings: list[LintFinding] = []
        # functions (by line span) that call as_state_dtype — the blessed
        # narrowing for the int64 widen-compute-narrow pattern
        self._blessed_spans: list[tuple[int, int]] = []
        self._pending_casts: list[tuple[int, str]] = []
        self._fn_stack: list[tuple[int, int]] = []

    def _flag(self, rule: str, node, message: str):
        line = node if isinstance(node, int) else getattr(node, "lineno", 0)
        if rule in self.pragmas.get(line, ()):
            return
        if not _in_scope(rule, self.rel):
            return
        self.findings.append(LintFinding(rule=rule, path=self.rel,
                                         line=line, message=message))

    # -- loop shells / host sync / interpret / int64 casts --------------

    def visit_Call(self, node: ast.Call):
        chain = _attr_chain(node.func)
        if chain:
            tail = chain[-1]
            if tail in ("while_loop", "scan") and "lax" in chain[:-1]:
                self._flag("loop-shell", node,
                           f"lax.{tail} shell outside core/engine.py — "
                           "run it on engine.run_bulk_loop / "
                           "run_to_fixpoint so the ScanChunkShape "
                           "contract stays provable")
            if tail == "block_until_ready" or chain in (
                    ("jax", "device_get"), ("device_get",)):
                self._flag("host-sync", node,
                           f"{'.'.join(chain)} in solver code — host "
                           "synchronisation belongs to the serving/"
                           "launch tiers")
            if tail == "as_state_dtype":
                if self._fn_stack:
                    self._blessed_spans.append(self._fn_stack[-1])
            src = _int64_cast_source(node)
            if src is not None:
                state = _root_state_name(src)
                if state is not None:
                    self._pending_casts.append(
                        (node.lineno,
                         f"int64 cast of state array {state!r} without "
                         "an as_state_dtype narrowing in the same "
                         "function; widen-compute-narrow through "
                         "as_state_dtype, or declare the host-side "
                         "exception with '# lint-ok: int64-state-cast'"))
        for kw in node.keywords:
            if (kw.arg == "interpret"
                    and isinstance(kw.value, ast.Constant)
                    and kw.value.value is True):
                self._flag("interpret-literal", kw.value,
                           "hardcoded interpret=True — pass interpret="
                           "None and let kernels.runtime."
                           "resolve_interpret pick the backend")
        self.generic_visit(node)

    # -- bare asserts ----------------------------------------------------

    def visit_Assert(self, node: ast.Assert):
        if node.msg is None:
            self._flag("bare-assert", node,
                       "message-less assert in library code (stripped "
                       "under -O); raise a typed error or attach a "
                       "message")
        self.generic_visit(node)

    # -- private jaxpr walkers in tests/benchmarks -----------------------

    def visit_Attribute(self, node: ast.Attribute):
        if node.attr == "eqns":
            self._flag("private-walker", node,
                       "ad-hoc jaxpr walk (.eqns access) — use the "
                       "shared census in repro.analysis.ir instead")
        elif node.attr == "count_jaxpr_eqns":
            self._flag("private-walker", node,
                       "count_jaxpr_eqns moved to repro.analysis.ir."
                       "count_eqns; use that")
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name):
        if node.id == "count_jaxpr_eqns":
            self._flag("private-walker", node,
                       "count_jaxpr_eqns moved to repro.analysis.ir."
                       "count_eqns; import it from there")
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom):
        for alias in node.names:
            if alias.name == "count_jaxpr_eqns":
                self._flag("private-walker", node,
                           "count_jaxpr_eqns moved to repro.analysis."
                           "ir.count_eqns; import it from there")

    # -- function span tracking (for the blessed-narrowing check) --------

    def visit_FunctionDef(self, node):
        self._visit_fn(node)

    def visit_AsyncFunctionDef(self, node):
        self._visit_fn(node)

    def _visit_fn(self, node):
        span = (node.lineno, max(
            (n.lineno for n in ast.walk(node) if hasattr(n, "lineno")),
            default=node.lineno))
        self._fn_stack.append(span)
        self.generic_visit(node)
        self._fn_stack.pop()

    def finish(self):
        for line, message in self._pending_casts:
            if any(lo <= line <= hi for lo, hi in self._blessed_spans):
                continue
            self._flag("int64-state-cast", line, message)


def lint_file(path: Path, root: Path) -> list[LintFinding]:
    rel = path.relative_to(root).as_posix()
    if not any(_in_scope(rule, rel) for rule in RULE_SCOPES):
        return []
    source = path.read_text()
    try:
        tree = ast.parse(source, filename=str(path))
    except SyntaxError as ex:
        return [LintFinding(rule="parse-error", path=rel,
                            line=ex.lineno or 0, message=str(ex))]
    v = _Visitor(rel, _pragmas(source))
    v.visit(tree)
    v.finish()
    return sorted(v.findings, key=lambda f: (f.path, f.line, f.rule))


def _iter_py(root: Path, subdirs: Iterable[str]) -> Iterator[Path]:
    for sub in subdirs:
        base = root / sub
        if base.is_dir():
            yield from sorted(base.rglob("*.py"))


def run_lint(root: Path | str,
             subdirs: Iterable[str] = ("src", "tests", "benchmarks"),
             ) -> list[LintFinding]:
    """Lint the repo tree; returns all findings, stably ordered."""
    root = Path(root)
    out: list[LintFinding] = []
    for path in _iter_py(root, subdirs):
        out.extend(lint_file(path, root))
    return out
