"""Summarize a jax.profiler trace: top ops by total device time.

Point it at the `.trace.json.gz` a capture wrote — the CLI's
``--profile-dir`` keeps the same profiler trace that
``python3 -m gossipbench --trace 1`` takes (the benchmark reads its own
in place and removes it) — and compare the dominant kernels' share of
the round against the traffic model's per-term accounting:

    python -m p2p_gossipprotocol_tpu.cli examples/scale.txt \
        --backend jax --profile-dir chiprun_out/prof
    python benchmarks/trace_top.py chiprun_out/prof [N]

Accepts a trace directory (finds the newest *.trace.json.gz under it)
or a direct file path.  Prints one JSON line per leaf op: name, calls,
total ms, share of the traced device time.  On a chip trace, one JSON
line per phase of the round follows (the ``jax.named_scope`` each op
ran under: churn, liveness, prep, push, pull, update, census, ...; under
a fault plan also faults, the plane's work inside those phases).
When the trace holds the program's own spans (``gossip.<name>``, as a
CLI ``--profile-dir`` capture does), one JSON line per span name
follows: count, total ms, and the ms in which the device was idle with
that span the innermost open.

The summarizer itself lives in
``p2p_gossipprotocol_tpu/telemetry/traceview.py`` now (this script
delegates), so the serve server's on-demand ``profile`` document
round-trips captures through the SAME accounting — one summarizer, two
surfaces.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from p2p_gossipprotocol_tpu.telemetry.traceview import (  # noqa: E402
    find_trace, program_spans, scopes, summarize)

__all__ = ["find_trace", "program_spans", "scopes", "summarize", "main"]


def main() -> int:
    if len(sys.argv) < 2:
        print(__doc__, file=sys.stderr)
        return 1
    trace_file = find_trace(sys.argv[1])
    top_n = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    print(json.dumps({"trace": trace_file}))
    for row in summarize(trace_file, top_n):
        print(json.dumps(row))
    for row in scopes(trace_file) + program_spans(trace_file):
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
