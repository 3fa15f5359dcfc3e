"""Rule ``write-discipline``: artifacts land tmp+rename or O_APPEND.

PR 3's torn-write rules (a reader must never see a half-written
manifest) and PR 9/10's O_APPEND row discipline (concurrent writers
never interleave partial lines) are load-bearing for every resume and
every results table.  The mechanical form: a bare ``open(path, "w")``
is only legal

* inside the blessed helper files (``utils/checkpoint.py``,
  ``utils/logging.py`` — the one implementation everything delegates
  to), or
* in a function that also calls ``os.replace(...)`` — the inline
  tmp+rename idiom (heartbeats, flight dumps).

Everything else writes an artifact a crash can tear — flagged.  Scope
includes ``benchmarks/``: its runs write the results tables the docs
quote.
"""

from __future__ import annotations

import ast

from p2p_gossipprotocol_tpu.analysis.contracts import WRITE_HELPER_FILES
from p2p_gossipprotocol_tpu.analysis.core import (Finding, dotted, rule,
                                                  walk_calls)

_FUNC = (ast.FunctionDef, ast.AsyncFunctionDef)


def _open_write_mode(call: ast.Call) -> str | None:
    if not (isinstance(call.func, ast.Name) and call.func.id == "open"):
        return None
    mode = None
    if len(call.args) >= 2 and isinstance(call.args[1], ast.Constant):
        mode = call.args[1].value
    for kw in call.keywords:
        if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
            mode = kw.value.value
    if isinstance(mode, str) and "w" in mode:
        return mode
    return None


def _functions_with_replace(src) -> set[int]:
    """ids of function nodes whose subtree calls os.replace/os.rename
    (the inline tmp+rename idiom)."""
    out = set()
    for node in ast.walk(src.tree):
        if not isinstance(node, _FUNC):
            continue
        for call in walk_calls(node):
            if (dotted(call.func) or "") in ("os.replace", "os.rename"):
                out.add(id(node))
                break
    return out


@rule("write-discipline",
      "no bare open(path, 'w') outside utils/checkpoint.py / "
      "utils/logging.py or an inline tmp+rename function")
def check(tree):
    findings = []
    for src in tree.sources:
        if src.rel.endswith(WRITE_HELPER_FILES):
            continue
        atomic_fns = _functions_with_replace(src)
        # map call -> enclosing function ids
        stack = []

        def visit(node):
            is_fn = isinstance(node, _FUNC)
            if is_fn:
                stack.append(id(node))
            if isinstance(node, ast.Call):
                mode = _open_write_mode(node)
                if mode is not None and not any(
                        fid in atomic_fns for fid in stack):
                    findings.append(Finding(
                        "write-discipline", src.rel, node.lineno,
                        f"bare open(..., {mode!r}) — artifacts are "
                        "written tmp+rename (utils.logging."
                        "write_atomic / utils.checkpoint._write_atomic"
                        ") or O_APPEND (utils.logging.append_line/"
                        "append_jsonl); a crash here leaves a torn "
                        "file a reader can see"))
            for child in ast.iter_child_nodes(node):
                visit(child)
            if is_fn:
                stack.pop()

        visit(src.tree)
    return findings
