"""Violating fixture: -1-auto statics resolved inline, outside the
autostatics.py rule module — a second rule for each static."""


class Engine:
    def __init__(self, prefetch_depth=-1, frontier_mode=-1,
                 interpret=True):
        if prefetch_depth not in (-1, 0, 2):
            raise ValueError("prefetch_depth must be -1, 0, or 2")
        # VIOLATION: the auto sentinel resolved here — a second rule
        self._prefetch = (2 if prefetch_depth == -1 and not interpret
                          else 0)
        # VIOLATION: same scatter, the block_perm < 0 spelling
        self._frontier = (frontier_mode == -1 and not interpret)


def pick_block_perm(block_perm, n_words):
    if block_perm < 0:          # VIOLATION: inline auto-select
        return n_words >= 4
    return bool(block_perm)
