"""The repo rule registry: this codebase's historical bug classes as lint rules.

Every rule id appeared as a real defect in PRs 1-9 (CHANGES.md) before it
became a rule; the fixtures under ``staticcheck/fixtures/`` are distilled
reproductions that the gate self-tests against (each fixture must fail its
rule, or the rule has rotted).

  RS001  bare ``assert`` guarding a runtime invariant in non-test code —
         stripped by ``python -O``, so the invariant silently vanishes in
         the optimized drivers CI runs; raise instead.
  RS002  ``np.empty`` for slot/index buffers: unwritten slots are garbage
         a later gather will happily read (the PR 4 slot-corruption bug).
  RS003  truthiness on int-or-None config fields (``max_k`` etc.):
         ``max_k or n`` coerces the valid value 0 into "unbounded"
         (the PR 6 ``max_k=0`` bug); compare against None.
  RS004  ``os.environ["XLA_..."] = ...`` overwrite: clobbers flags the
         caller already set; append to the existing value.
  RS005  implicit host<->device conversion (``jnp.asarray`` on host-mirror
         np state, ``np.asarray`` on device arrays) inside a registered
         streaming/serving hot path; only explicit ``jax.device_put`` /
         ``jax.device_get`` keep the steady state clean under
         ``jax.transfer_guard`` (the Layer-3 contract).

Suppression: append ``# staticcheck: disable=RSnnn`` (comma-separate for
several ids) to the flagged line or the line above it, next to a comment
that justifies why the rule does not apply.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Callable, Dict, List, Optional, Set, Union

from .report import Finding

__all__ = ["Rule", "RULES", "HOT_PATHS", "rule_ids", "LintContext",
           "INT_OR_NONE_CONFIG_FIELDS", "HOT_PATH_PRAGMA"]

# module-path suffix -> hot function names ("*" = every function in the
# file).  These are the steady-state loops the Layer-3 audit runs under
# transfer guards; RS005 keeps them statically free of implicit conversions.
HOT_PATHS: Dict[str, Union[str, Set[str]]] = {
    "repro/streaming/window.py": {"push"},
    "repro/streaming/miner.py": {"push", "mine_window", "_mine_window",
                                 "advance"},
    "repro/core/engine.py": {"expand", "_compact", "_take"},
    "repro/core/triangular.py": {"cooccurrence_counts"},
    "repro/core/eclat.py": {"run_bottom_up"},
    # the serving read path answers from host snapshots by design: any
    # device conversion at all is a regression
    "repro/serving/snapshot.py": "*",
    "repro/serving/stream_query.py": "*",
}

# files outside the registry can declare themselves hot (the fixtures do)
HOT_PATH_PRAGMA = "# staticcheck: hot-path"

# config fields that are int-or-None where 0 is a *valid int*, not "unset"
INT_OR_NONE_CONFIG_FIELDS = {
    "max_k", "cand_chunk", "block_w", "top_k", "keep_versions",
    "kill_after", "checkpoint_every", "max_batches",
}

_JNP_NAMES = {"jnp"}
_NP_NAMES = {"np", "numpy"}
_JNP_CONVERSIONS = {"asarray", "array", "int32", "int64", "uint32",
                    "float32", "float64"}
_NP_CONVERSIONS = {"asarray"}
_INT_DTYPE_ATTRS = {"int8", "int16", "int32", "int64", "uint8", "uint16",
                    "uint32", "uint64", "intp", "int_", "longlong"}


@dataclasses.dataclass
class LintContext:
    """Everything one rule pass needs about one file."""

    path: str                       # repo-relative, forward slashes
    tree: ast.AST
    lines: List[str]                # raw source lines (1-indexed via [i-1])
    suppressed: Dict[int, Set[str]]  # line -> rule ids disabled there
    is_test: bool                   # tests/ or test_*.py / conftest.py
    hot_functions: Union[str, Set[str], None]   # "*" | set | None
    func_of: Dict[int, str]         # id(node) -> innermost enclosing def

    def enclosing(self, node: ast.AST) -> Optional[str]:
        return self.func_of.get(id(node))

    def in_hot_function(self, node: ast.AST) -> Optional[str]:
        fn = self.enclosing(node)
        if self.hot_functions == "*":
            return fn or "<module>"
        if fn is not None and self.hot_functions and \
                fn in self.hot_functions:
            return fn
        return None

    def is_suppressed(self, rule_id: str, line: int) -> bool:
        for ln in (line, line - 1):
            if rule_id in self.suppressed.get(ln, set()):
                return True
        return False


@dataclasses.dataclass
class Rule:
    id: str
    title: str
    rationale: str
    check: Callable[[LintContext], List[Finding]]


def _finding(ctx: LintContext, rule_id: str, node: ast.AST,
             message: str) -> List[Finding]:
    line = getattr(node, "lineno", 0)
    if ctx.is_suppressed(rule_id, line):
        return []
    return [Finding(rule=rule_id, path=ctx.path, line=line, message=message)]


def _dotted(node: ast.AST) -> str:
    """'os.environ.get' for nested Attribute/Name chains, '' otherwise."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


# -- RS001 ------------------------------------------------------------------

def _check_rs001(ctx: LintContext) -> List[Finding]:
    if ctx.is_test:
        return []
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assert):
            out += _finding(
                ctx, "RS001", node,
                "bare `assert` guards a runtime invariant but is stripped "
                "under `python -O` (the CI optimized-build smokes); raise "
                "RuntimeError/ValueError with a diagnostic message instead")
    return out


# -- RS002 ------------------------------------------------------------------

def _is_int_dtype_expr(node: Optional[ast.AST]) -> bool:
    if node is None:
        return False
    if isinstance(node, ast.Attribute) and node.attr in _INT_DTYPE_ATTRS:
        return True
    if isinstance(node, ast.Name) and node.id in _INT_DTYPE_ATTRS:
        return True
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value.lstrip("u").lstrip("int").isdigit() or \
            node.value in _INT_DTYPE_ATTRS
    return False


def _check_rs002(ctx: LintContext) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "empty"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in _NP_NAMES):
            continue
        if node.args and isinstance(node.args[0], ast.Constant) \
                and node.args[0].value == 0:
            continue  # zero-length: nothing to leave uninitialized
        dtype = node.args[1] if len(node.args) > 1 else None
        for kw in node.keywords:
            if kw.arg == "dtype":
                dtype = kw.value
        if not _is_int_dtype_expr(dtype):
            continue
        out += _finding(
            ctx, "RS002", node,
            "np.empty(...) integer slot/index buffer: any slot the fill "
            "loop misses is garbage that a later gather reads as a valid "
            "index (silently wrong supports); use np.zeros, or suppress "
            "with a justification that every slot is provably written")
    return out


# -- RS003 ------------------------------------------------------------------

def _truthiness_name(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Name) and node.id in INT_OR_NONE_CONFIG_FIELDS:
        return node.id
    if isinstance(node, ast.Attribute) and \
            node.attr in INT_OR_NONE_CONFIG_FIELDS:
        return node.attr
    return None


def _check_rs003(ctx: LintContext) -> List[Finding]:
    # dedup by source position: a BoolOp inside an if-test is reachable
    # both as the test and as a walked BoolOp node
    hits: Dict[tuple, ast.AST] = {}

    def mark(node: ast.AST):
        name = _truthiness_name(node)
        if name is not None:
            hits[(node.lineno, node.col_offset)] = node

    def mark_test(test: ast.AST):
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            mark_test(test.operand)
        elif isinstance(test, ast.BoolOp):
            for v in test.values:
                mark_test(v)
        else:
            mark(test)

    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.If, ast.While, ast.IfExp)):
            mark_test(node.test)
        elif isinstance(node, ast.BoolOp):
            # `max_k or default`: every non-last operand is truthiness-tested
            for v in node.values[:-1]:
                mark(v)
    out: List[Finding] = []
    for _, node in sorted(hits.items()):
        name = _truthiness_name(node)
        out.extend(_finding(
            ctx, "RS003", node,
            f"truthiness on int-or-None field `{name}` treats the valid "
            f"value 0 as unset (`{name}=0` silently becomes unbounded); "
            f"compare `is None` / `is not None` explicitly"))
    return out


# -- RS004 ------------------------------------------------------------------

def _environ_key(node: ast.AST) -> Optional[str]:
    """The constant key of an ``os.environ[...]`` subscript, else None."""
    if not isinstance(node, ast.Subscript):
        return None
    if _dotted(node.value) not in ("os.environ", "environ"):
        return None
    sl = node.slice
    if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
        return sl.value
    return None


def _reads_same_key(rhs: ast.AST, key: str) -> bool:
    for sub in ast.walk(rhs):
        if _environ_key(sub) == key:
            return True
        if isinstance(sub, ast.Call) and \
                _dotted(sub.func) in ("os.environ.get", "environ.get") and \
                sub.args and isinstance(sub.args[0], ast.Constant) and \
                sub.args[0].value == key:
            return True
    return False


def _check_rs004(ctx: LintContext) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            key = _environ_key(tgt)
            if key is None or not key.startswith("XLA"):
                continue
            if _reads_same_key(node.value, key):
                continue
            out += _finding(
                ctx, "RS004", node,
                f"os.environ[{key!r}] overwritten — any value the caller "
                f"already exported (device counts, dump flags) is silently "
                f"clobbered; append: os.environ.get({key!r}, '') + ' ...'")
    return out


# -- RS005 ------------------------------------------------------------------

def _check_rs005(ctx: LintContext) -> List[Finding]:
    if not ctx.hot_functions:
        return []
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)):
            continue
        mod, attr = node.func.value.id, node.func.attr
        bad = (mod in _JNP_NAMES and attr in _JNP_CONVERSIONS) or \
              (mod in _NP_NAMES and attr in _NP_CONVERSIONS)
        if not bad:
            continue
        fn = ctx.in_hot_function(node)
        if fn is None:
            continue
        out += _finding(
            ctx, "RS005", node,
            f"implicit host<->device conversion `{mod}.{attr}` in hot path "
            f"`{fn}` — the steady-state slide/serve loop must only move "
            f"data via explicit jax.device_put / jax.device_get (the "
            f"Layer-3 transfer-guard contract)")
    return out


RULES: List[Rule] = [
    Rule("RS001", "bare assert guarding a runtime invariant",
         "python -O strips asserts; CI runs optimized-build smokes",
         _check_rs001),
    Rule("RS002", "np.empty for integer slot/index buffers",
         "unwritten slots are garbage later gathers read (PR 4 bug class)",
         _check_rs002),
    Rule("RS003", "truthiness on int-or-None config fields",
         "`max_k or n` coerces the valid 0 into unbounded (PR 6 bug class)",
         _check_rs003),
    Rule("RS004", "XLA env var overwritten instead of appended",
         "clobbers flags the caller exported",
         _check_rs004),
    Rule("RS005", "implicit host<->device conversion in a hot path",
         "only explicit transfers keep slides clean under transfer guards",
         _check_rs005),
]


def rule_ids() -> List[str]:
    return [r.id for r in RULES]
