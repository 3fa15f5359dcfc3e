"""Print what each benchmark cell's build resolves, for comparing trees.

For every workload of ``BENCHMARK.json`` (or those named), the cell's
config and traffic are built through ``engines.build_simulator`` as
``gossipbench`` builds them, with the kernels' ``interpret`` forced off
(the chip's side of every auto rule, also on a CPU host).  One JSON
line per cell: the resolved private statics, the schedule fields, the
overlay's row block, and a SHA-256 of every overlay leaf (``ytab``
included).  Two checkouts that print the same lines build the same
compiled round.

    PYTHONPATH=<checkout> python benchmarks/resolved_statics.py \\
        [--n-peers N] [cell ...]

``--n-peers`` shrinks every overlay (a CPU host cannot hold the 16M
ones); the statics do not depend on it.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys

PRIVATES = ("_frontier_skip", "_frontier_delta", "_frontier_algo",
            "_prefetch", "_overlap", "_hier", "_pull_slots")
FIELDS = ("interpret", "n_msgs", "n_words", "mode", "frontier_mode",
          "frontier_threshold", "frontier_algo", "prefetch_depth",
          "overlap_mode", "hier_mode", "hier_hosts", "hier_devs",
          "fuse_update", "pull_window")


def _digest(x) -> str | None:
    if x is None:
        return None
    import numpy as np

    a = np.asarray(x)
    h = hashlib.sha256(f"{a.dtype}{a.shape}".encode())
    h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


def resolved(sim) -> dict:
    topo = sim.topo
    leaves = {}
    for f in dataclasses.fields(topo):
        v = getattr(topo, f.name)
        leaves[f.name] = (_digest(v) if hasattr(v, "shape") or v is None
                          else v)
    return {"privates": {k: getattr(sim, k) for k in PRIVATES},
            "fields": {k: getattr(sim, k) for k in FIELDS},
            "rowblk": topo.rowblk, "overlay": leaves}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--n-peers", type=int, default=None)
    args = ap.parse_args(argv)

    from gossipbench import spec, window

    from p2p_gossipprotocol_tpu import aligned
    from p2p_gossipprotocol_tpu.engines import build_simulator

    aligned.interpret_for = lambda backend: False
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        names = args.cells or [w["name"] for w in json.load(f)["workloads"]]
    for name in names:
        cfg = window.network_config(spec.load_cell(name))
        sim, engine = build_simulator(cfg, n_peers=args.n_peers)
        print(json.dumps({"cell": name, "engine": engine, **resolved(sim)},
                         sort_keys=True), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
