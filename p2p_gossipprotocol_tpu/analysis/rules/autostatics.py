"""Rule ``auto-statics``: each -1-auto static has one rule, in one module.

Every ``-1``-auto performance static (``contracts.AUTO_STATICS`` —
frontier_mode, prefetch_depth, block_perm, serve_chunk, ...) is
decided by one ``heuristic_*`` function of ``autostatics.py``
(``contracts.AUTO_RULES_MODULE``), called from one place per engine.
An auto-sentinel test on one of those statics anywhere else —
``X == -1`` or ``X < 0`` — is a second rule for the same static: the
two forks drift, and the engines stop agreeing on what auto means.

Exempt, because they are validation rather than resolution:

* membership tests (``X not in (-1, 0, 1)`` guards) — different AST
  shape, never matched;
* comparisons inside an ``if`` whose body only raises (the
  fail-fast-on-bad-value idiom);
* the rule module itself, located by its package-relative path
  (fixtures mimic the layout), where the ``heuristic_*`` functions
  legitimately test the sentinel.
"""

from __future__ import annotations

import ast

from p2p_gossipprotocol_tpu.analysis.contracts import (AUTO_RULES_MODULE,
                                                       AUTO_STATICS)
from p2p_gossipprotocol_tpu.analysis.core import Finding, rule

_FUNC = (ast.FunctionDef, ast.AsyncFunctionDef)


def _const_val(node: ast.AST):
    """The literal value of a Constant, including the ``-1`` spelling
    (UnaryOp(USub, Constant(1)) in the AST)."""
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub) \
            and isinstance(node.operand, ast.Constant):
        v = node.operand.value
        return -v if isinstance(v, (int, float)) else None
    if isinstance(node, ast.Constant):
        return node.value
    return None


def _static_name(node: ast.AST) -> str | None:
    """The terminal name of ``X`` / ``obj.X`` when it is a known auto
    static."""
    name = None
    if isinstance(node, ast.Name):
        name = node.id
    elif isinstance(node, ast.Attribute):
        name = node.attr
    return name if name in AUTO_STATICS else None


def _is_sentinel_test(cmp: ast.Compare) -> str | None:
    """The static name when ``cmp`` is an auto-sentinel resolution
    test: ``<static> == -1`` or ``<static> < 0`` (and their mirrored
    spellings)."""
    if len(cmp.ops) != 1 or len(cmp.comparators) != 1:
        return None
    op = cmp.ops[0]
    left, right = cmp.left, cmp.comparators[0]
    # mirrored constant-first spelling: -1 == X
    if _static_name(left) is None and _static_name(right) is not None:
        left, right = right, left
        if isinstance(op, ast.Lt):      # 0 < X is not a sentinel test
            return None
    name = _static_name(left)
    if name is None:
        return None
    val = _const_val(right)
    if isinstance(op, ast.Eq) and val == -1:
        return name
    if isinstance(op, ast.Lt) and val == 0:
        return name
    return None


def _raise_only_tests(tree: ast.Module) -> set[int]:
    """ids of Compare nodes inside ``if`` tests whose body only raises
    (validation guards, exempt)."""
    out: set[int] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.If):
            continue
        body = [s for s in node.body
                if not (isinstance(s, ast.Expr)
                        and isinstance(s.value, ast.Constant))]
        if body and all(isinstance(s, ast.Raise) for s in body):
            for sub in ast.walk(node.test):
                if isinstance(sub, ast.Compare):
                    out.add(id(sub))
    return out


@rule("auto-statics",
      "-1-auto performance statics are decided only by the heuristic_* "
      "rules of autostatics.py, nowhere else")
def check(tree):
    findings = []
    for src in tree.package_sources():
        if src.rel == AUTO_RULES_MODULE:
            continue
        exempt = _raise_only_tests(src.tree)
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Compare) or id(node) in exempt:
                continue
            name = _is_sentinel_test(node)
            if name is None:
                continue
            findings.append(Finding(
                "auto-statics", src.rel, node.lineno,
                f"auto sentinel of {name!r} resolved outside "
                "autostatics.py — call its heuristic_* rule (or add "
                "one there) so each static keeps one rule"))
    return findings
