"""gossip-lint: tier-1 enforcement + the rule engine's own tests.

Three layers:

* **enforcement** — the whole suite runs over the repo at HEAD with the
  committed baseline and must be clean: this is how the contracts in
  docs/STATIC_ANALYSIS.md are CI-enforced through the existing pytest
  command;
* **per-rule fixtures** — every rule is demonstrated on a minimal
  violating snippet (tests/fixtures/analysis/<rule>_violation/) and
  stays quiet on its clean twin, including the lock-discipline rule
  flagging a reproduction of the PR 9 scheduler double-rid race;
* **baseline round-trip** — add a violation, suppress it, then fix it
  and watch the suppression go stale (stale entries fail the run, so
  the baseline cannot rot).

No jax anywhere in this module — the linter is stdlib-ast only and
this file must stay cheap inside the 870 s tier-1 budget.
"""

from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import pytest

from p2p_gossipprotocol_tpu.analysis import (RULES, apply_baseline,
                                             load_baseline, load_tree,
                                             run_rules)
from p2p_gossipprotocol_tpu.analysis.callgraph import traced_functions

REPO = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).resolve().parent / "fixtures" / "analysis"

EXPECTED_RULES = {
    "tracing-safety", "lock-discipline", "clamp-chokepoint",
    "fingerprint-exclusion", "packer-signature", "write-discipline",
    "telemetry-imports", "config-drift", "auto-statics",
}


def _fixture(case: str, rule: str):
    tree = load_tree(FIXTURES / case)
    return run_rules(tree, rule_ids={rule})


#: the HEAD tree parsed once per session — three tests read it and the
#: repo does not change mid-run (keeps this module's tier-1 cost down)
_HEAD_TREE = []


def _head_tree():
    if not _HEAD_TREE:
        _HEAD_TREE.append(load_tree(REPO))
    return _HEAD_TREE[0]


# ---------------------------------------------------------------- HEAD
def test_tree_is_clean_at_head():
    """THE enforcement test: every rule over the real repo, committed
    baseline applied — zero unsuppressed findings, zero stale
    suppressions.  A red here names the contract you broke (or the
    baseline entry you must now delete)."""
    raw = run_rules(_head_tree())
    findings, stale = apply_baseline(raw, load_baseline())
    msg = "\n".join(f.render() for f in findings)
    assert not findings, f"gossip-lint findings at HEAD:\n{msg}"
    assert not stale


def test_rule_catalog_complete():
    """All nine contract rules are registered, each with a one-line
    contract string (the --list-rules surface)."""
    assert EXPECTED_RULES <= set(RULES)
    for rid, (fn, contract) in RULES.items():
        assert callable(fn) and contract, rid


def test_cli_clean_exit_zero():
    """The CLI entry the Makefile calls: exit 0 on a clean
    tree.  Scoped to a fixture root for tier-1 cost — the whole-repo
    equivalent is test_tree_is_clean_at_head in-process (same rules,
    same baseline), and `make lint` runs the full CLI form."""
    proc = subprocess.run(
        [sys.executable, "-m", "p2p_gossipprotocol_tpu.analysis",
         "--root", str(FIXTURES / "locks_clean"),
         "--rules", "lock-discipline", "--no-baseline"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stderr


def test_traced_set_covers_every_engine():
    """The tracing rule is only as good as its call-graph reach: the
    walk out of the jit/pallas/shard_map entry points must land in
    every engine family (a refactor that breaks entry discovery would
    otherwise silently turn the rule off)."""
    ts = traced_functions(_head_tree())
    files = {t.source.rel for t in ts}
    for needle in ("aligned.py", "sim.py", "ops/aligned_kernel.py",
                   "fleet/engine.py", "parallel/aligned_sharded.py",
                   "parallel/aligned_2d.py", "parallel/sharded_sim.py",
                   "aligned_sir.py"):
        assert any(f.endswith(needle) for f in files), (needle, files)


# ------------------------------------------------------- rule fixtures
def test_tracing_rule_flags_host_escapes():
    fs = _fixture("tracing_violation", "tracing-safety")
    msgs = " ".join(f.message for f in fs)
    assert "time.time" in msgs
    assert "np.random" in msgs
    assert ".item()" in msgs
    # reached through the call graph, not just the jitted root
    assert any("_helper" in f.message for f in fs)


def test_tracing_rule_quiet_on_host_side_clocks():
    assert _fixture("tracing_clean", "tracing-safety") == []


def test_lock_rule_flags_pr9_double_rid_race():
    """The acceptance fixture: the pre-fix PR 9 scheduler shape —
    ``_next_rid`` read outside the lock that owns it — must be
    flagged, at the racy read's line."""
    fs = _fixture("locks_violation", "lock-discipline")
    assert any("_next_rid" in f.message and "read" in f.message
               for f in fs), [f.render() for f in fs]
    (hit,) = [f for f in fs if "_next_rid" in f.message]
    src = (FIXTURES / "locks_violation" / hit.file).read_text()
    assert "RACE" in src.splitlines()[hit.line - 1]


def test_lock_rule_quiet_on_fixed_scheduler():
    assert _fixture("locks_clean", "lock-discipline") == []


def test_clamp_rule_flags_silent_degrade_and_rogue_emit():
    fs = _fixture("clamps_violation", "clamp-chokepoint")
    assert any("overlap_mode" in f.message and "without a recorded"
               in f.message for f in fs)
    assert any("sneaky_site" in f.message for f in fs)


def test_clamp_rule_quiet_on_recorded_degrade():
    assert _fixture("clamps_clean", "clamp-chokepoint") == []


def test_fingerprint_rule_flags_both_directions():
    fs = _fixture("fingerprint_violation", "fingerprint-exclusion")
    msgs = [f.message for f in fs]
    assert any("'telemetry'" in m and "exempt" in m for m in msgs)
    assert any("'mystery_knob'" in m and "neither" in m for m in msgs)


def test_fingerprint_rule_quiet_when_classified():
    assert _fixture("fingerprint_clean", "fingerprint-exclusion") == []


def test_packer_rule_flags_missing_static_and_ghost():
    fs = _fixture("packer_violation", "packer-signature")
    msgs = [f.message for f in fs]
    assert any("_new_static" in m for m in msgs)
    assert any("_ghost_static" in m and "never assigns" in m
               for m in msgs)


def test_packer_rule_quiet_when_covered():
    assert _fixture("packer_clean", "packer-signature") == []


def test_write_rule_flags_bare_open_w():
    fs = _fixture("writes_violation", "write-discipline")
    assert len(fs) == 1 and "open" in fs[0].message


def test_write_rule_allows_tmp_rename():
    assert _fixture("writes_clean", "write-discipline") == []


def test_import_rule_flags_top_level_and_lazy_jax():
    fs = _fixture("imports_violation", "telemetry-imports")
    assert len(fs) == 2          # import jax AND from jax import ...
    assert all("telemetry" in f.message for f in fs)


def test_import_rule_quiet_on_host_only_module():
    assert _fixture("imports_clean", "telemetry-imports") == []


def test_config_drift_three_directions():
    fs = _fixture("configdrift_violation", "config-drift")
    msgs = [f.message for f in fs]
    assert any("'ghost_key'" in m and "never mentioned" in m
               for m in msgs)
    assert any("'phantom_key'" in m and "does not parse" in m
               for m in msgs)
    assert any("'unused_key'" in m and "parsed-then-ignored" in m
               for m in msgs)


def test_config_drift_quiet_when_reconciled():
    assert _fixture("configdrift_clean", "config-drift") == []


def test_tuning_rule_flags_inline_auto_resolution():
    """Both sentinel spellings — ``X == -1`` and ``X < 0`` — on known
    auto statics are flagged outside the rule module, while the
    ``not in (-1, 0, 2)`` validation guard in the same fixture stays
    quiet."""
    fs = _fixture("tuning_violation", "auto-statics")
    msgs = [f.message for f in fs]
    assert any("'prefetch_depth'" in m for m in msgs), msgs
    assert any("'frontier_mode'" in m for m in msgs), msgs
    assert any("'block_perm'" in m for m in msgs), msgs
    assert len(fs) == 3, [f.render() for f in fs]


def test_tuning_rule_quiet_on_resolver_and_validation():
    """The clean twin: sentinel tests inside autostatics.py (the
    heuristic_* rules) and raise-only validation branches are exempt
    by contract."""
    assert _fixture("tuning_clean", "auto-statics") == []


# ---------------------------------------------------- baseline machine
def test_baseline_round_trip_add_suppress_stale(tmp_path):
    """add → suppress → stale: a violation is found, a baseline entry
    suppresses it, and once the violation is fixed (the clean fixture)
    the same entry comes back as a stale-suppression finding."""
    baseline = tmp_path / "baseline.txt"
    baseline.write_text(
        "lock-discipline | p2p_gossipprotocol_tpu/sched.py | "
        "_next_rid is read | fixture: justified for the round-trip\n")
    entries = load_baseline(baseline)

    # add: the violation exists and the entry suppresses it
    dirty = run_rules(load_tree(FIXTURES / "locks_violation"),
                      rule_ids={"lock-discipline"})
    assert dirty
    left, stale = apply_baseline(dirty, load_baseline(baseline))
    assert left == [] and stale == []

    # fix: same baseline over the clean tree -> the entry is stale and
    # the run FAILS (stale entries are findings)
    clean = run_rules(load_tree(FIXTURES / "locks_clean"),
                      rule_ids={"lock-discipline"})
    left, stale = apply_baseline(clean, entries)
    assert len(stale) == 1
    assert [f.rule for f in left] == ["stale-suppression"]
    assert "matches no current finding" in left[0].message


def test_baseline_rejects_unjustified_entries(tmp_path):
    """A suppression without a justification is itself a finding —
    the baseline cannot absorb violations silently."""
    baseline = tmp_path / "baseline.txt"
    baseline.write_text(
        "lock-discipline | p2p_gossipprotocol_tpu/sched.py | "
        "_next_rid is read |\n")
    left, _ = apply_baseline([], load_baseline(baseline))
    assert [f.rule for f in left] == ["baseline-format"]


def test_committed_baseline_entries_all_live():
    """Every entry in the committed baseline still matches a real
    finding (no rot) and carries a justification."""
    entries = load_baseline()
    assert entries, "committed baseline should document the known "\
                    "intentional exceptions"
    for e in entries:
        assert e.rule in RULES, e.rule
        assert len(e.why) > 20, f"thin justification: {e.why!r}"
    findings = run_rules(_head_tree())
    _, stale = apply_baseline(findings, entries)
    assert stale == [], [e.match for e in stale]


def test_cli_reports_findings_nonzero(tmp_path):
    """CLI contract on a dirty tree: findings printed file:line, exit
    1 (callers key off the exit code)."""
    proc = subprocess.run(
        [sys.executable, "-m", "p2p_gossipprotocol_tpu.analysis",
         "--root", str(FIXTURES / "locks_violation"),
         "--rules", "lock-discipline", "--no-baseline"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "lock-discipline" in proc.stdout
    assert "sched.py:" in proc.stdout


def test_parse_error_is_a_finding(tmp_path):
    bad = tmp_path / "p2p_gossipprotocol_tpu"
    bad.mkdir()
    (bad / "broken.py").write_text("def oops(:\n")
    findings = run_rules(load_tree(tmp_path))
    assert [f.rule for f in findings] == ["parse-error"]
