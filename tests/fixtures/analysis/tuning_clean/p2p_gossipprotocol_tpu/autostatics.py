"""Clean fixture's rule module — at the package-relative path of
autostatics.py, so the sentinel tests in its heuristic_* rules are
where the auto-statics contract says they belong."""


def heuristic_prefetch(prefetch_depth, interpret):
    return 2 if prefetch_depth == -1 and not interpret else 0


def heuristic_block_perm(block_perm, n_words):
    if block_perm < 0:
        return n_words >= 4
    return bool(block_perm)
