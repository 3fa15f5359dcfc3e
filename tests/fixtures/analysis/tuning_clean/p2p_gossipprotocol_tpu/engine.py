"""Clean fixture: auto statics delegate to the rule module's
heuristics; validation guards (membership tests and
raise-only branches) are exempt by contract."""

from p2p_gossipprotocol_tpu.autostatics import heuristic_prefetch


class Engine:
    def __init__(self, prefetch_depth=-1, serve_chunk=-1,
                 interpret=True):
        if prefetch_depth not in (-1, 0, 2):
            raise ValueError("prefetch_depth must be -1, 0, or 2")
        if serve_chunk == -1:
            # raise-only validation branch: exempt (not a resolution)
            raise ValueError("this surface needs an explicit chunk")
        self._prefetch = heuristic_prefetch(prefetch_depth, interpret)
        self._chunk = serve_chunk
