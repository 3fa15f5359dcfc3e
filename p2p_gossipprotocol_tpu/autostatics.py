"""The one home of every ``-1``-auto performance static's rule.

Each auto static is resolved once, by one function below, called from
one place per engine: ``AlignedSimulator.__post_init__`` and
``from_config`` (the overlay statics ``block_perm``/``rowblk``),
``AlignedSIRSimulator.__post_init__``, ``RealGraphSimulator
.__post_init__`` and the serving loop's constructor.  gossip-lint's
``auto-statics`` rule keeps it that way: an auto-sentinel test on a
known static anywhere else in the package is a finding.

Every rule honours an explicit configured value and maps ``-1`` to one
value.  The 0/1 schedule statics follow one platform rule: on when the
kernels compile for the chip, off under interpret, where their extra
XLA-side work only slows the CPU path (the round-6/8/10 inversions).
The schedule statics pick HOW the same blocks move, never what a round
computes, so either value is bitwise-identical (the parity suites:
tests/test_frontier.py, test_prefetch.py, test_overlap.py,
test_hier.py, test_halving.py, test_sir_fuse.py).  ``block_perm`` and
``rowblk`` shape the overlay itself, so they pick the trajectory.

stdlib-only (no jax): the config and telemetry planes may import it.
"""

from __future__ import annotations

#: the serving loop's admission cadence (rounds per chunk) on auto
SERVE_CHUNK_DEFAULT = 8

#: the frontier delta-exchange capacity on auto, as a fraction of each
#: shard's packed words (aligned.FRONTIER_THRESHOLD_DEFAULT re-exports
#: this value; the derivation lives there).
FRONTIER_THRESHOLD_DEFAULT = 1.0 / 64.0

#: realgraph_pack_width's auto value (realgraph/pack.py derives it:
#: wide enough for >99% of power-law vertices in one row, narrow
#: enough that a hub can't widen everyone's lane)
REALGRAPH_PACK_WIDTH_DEFAULT = 256


def heuristic_on(requested: int, interpret: bool) -> bool:
    """The shared auto rule for the 0/1 schedule knobs (frontier_mode,
    frontier_algo, overlap_mode, hier_mode): -1 = on for the compiled
    path, off under interpret; 0/1 force."""
    return requested == 1 or (requested == -1 and not interpret)


def heuristic_prefetch(requested: int, interpret: bool) -> int:
    """prefetch_depth auto rule: the manual double-buffered stream (2)
    on the compiled path, the BlockSpec pipeline (0) under interpret."""
    if requested == 2 or (requested == -1 and not interpret):
        return 2
    return 0


def heuristic_sir_fuse(requested: int, interpret: bool,
                       has_ytab: bool) -> bool:
    """sir_fuse auto rule: fuse on the compiled path when the overlay
    carries the block-perm index table (the permute prep only vanishes
    with ytab)."""
    return (requested == 1
            or (requested == -1 and not interpret and has_ytab))


def heuristic_block_perm(requested: int, n_words: int, mode: str,
                         n_slots: int, roll_groups: int | None,
                         min_words: int = 4) -> bool:
    """from_config's fused-overlay auto rule (round 6, measured: -43%
    ms/round at W=8, a wash at W=1 — ``min_words`` is
    aligned.AUTO_BLOCK_PERM_MIN_WORDS).  The block-granular
    permutation draws a different overlay, so it picks the
    trajectory."""
    if requested < 0:
        return (n_words >= min_words and mode != "pull"
                and n_slots >= 2
                and (roll_groups is None or roll_groups >= 2))
    return bool(requested)


def heuristic_rowblk(n_words: int, budget: int, cap: int) -> int:
    """The VMEM-budget row-block rule (round 6: wide blocks at small W
    — ``budget``/``cap`` are aligned.MAX_WORDS_X_ROWBLK (halved under
    fuse_update) and aligned.MAX_CONFIG_ROWBLK).  The row-block grid
    shapes the block-roll neighbor map, so it picks the trajectory."""
    return min(cap, max(8, budget // n_words // 8 * 8))


def heuristic_frontier_threshold(requested: float) -> float:
    """frontier_threshold auto rule: -1 = the 1/64 capacity default
    (aligned.FRONTIER_THRESHOLD_DEFAULT has the derivation)."""
    return (FRONTIER_THRESHOLD_DEFAULT if requested == -1
            else float(requested))


def heuristic_serve_chunk(requested: int) -> int:
    """serve_chunk auto rule: -1 = the 8-round admission cadence the
    serving plane shipped with."""
    return SERVE_CHUNK_DEFAULT if requested == -1 else int(requested)


def heuristic_realgraph_pack_width(requested: int) -> int:
    """realgraph_pack_width auto rule: -1 = the 256-slot degree-bucket
    cap the realgraph engine shipped with (hubs beyond it split into
    multiple rows — semantics-free under the boolean OR)."""
    return (REALGRAPH_PACK_WIDTH_DEFAULT if requested == -1
            else int(requested))


def heuristic_realgraph_scatter(requested: int,
                                dst_static: bool) -> int:
    """realgraph_scatter auto rule: the packed gather (0) whenever the
    overlay's ``dst`` is static (the gather tables pre-resolve edge
    ids, so rewiring would stale them — realgraph.engine.dst_is_static
    is the predicate), the inherited edge scatter (1) otherwise."""
    if requested != -1:
        return int(requested)
    return 0 if dst_static else 1
