"""Profiler-trace summarizer: top ops by device time, and program spans.

The library home of what ``benchmarks/trace_top.py`` has always done
(that script now delegates here, keeping its CLI), so the serving
plane's on-demand ``profile`` document can round-trip a bounded
``jax.profiler`` capture through the same summarizer the offline
post-mortems use — one accounting, two surfaces.

``summarize`` keeps only the "XLA Ops" lanes when the trace has them
(device traces nest module/step spans around the op spans — summing
every lane would double-count device time and halve each kernel's
share) and falls back to the everything-but-python filter for CPU
rehearsal traces.  Within a lane only leaf events count: a ``while``
holds its body's ops and a ``conditional`` its branch's on the same
lane, so an event that holds another event of its lane is a container
and is dropped (the rule ``gossipbench/trace.py`` applies too).

``scopes`` groups the same leaf time by the phase of the round each op
ran in: the chip's trace gives every op the name stack it was traced
under (its ``tf_op`` argument, ``jit(looped)/while/body/census/...``),
and ``aligned_round`` runs each phase under a ``jax.named_scope``.

``program_spans`` reads the program's own telemetry spans, which the
trace mirror writes as ``gossip.<name>`` host events on the device's
clock (``device.install_trace_mirror``), and charges each idle gap of
the first device to the innermost span open at that moment.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
from collections import defaultdict

from p2p_gossipprotocol_tpu.telemetry.recorder import TRACE_PREFIX


def find_trace(path: str) -> str:
    """``path`` itself when it is a file, else the newest
    ``*.trace.json.gz`` under it (raises SystemExit when none)."""
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.trace.json.gz"),
                            recursive=True), key=os.path.getmtime)
    if not hits:
        raise SystemExit(f"no *.trace.json.gz under {path!r}")
    return hits[-1]


def _load(trace_file: str) -> list[dict]:
    with gzip.open(trace_file, "rt") as f:
        return json.load(f).get("traceEvents", [])


def leaves(events: list[dict]) -> list[dict]:
    """The complete events that hold no other event of their lane."""
    by_lane = defaultdict(list)
    for e in events:
        by_lane[(e.get("pid"), e.get("tid"))].append(e)
    out = []
    for lane in by_lane.values():
        lane.sort(key=lambda e: (e["ts"], -e["dur"]))
        container = set()
        stack = []
        for i, e in enumerate(lane):
            while stack and (lane[stack[-1]]["ts"] + lane[stack[-1]]["dur"]
                             <= e["ts"]):
                stack.pop()
            if stack and (e["ts"] + e["dur"]
                          <= lane[stack[-1]]["ts"] + lane[stack[-1]]["dur"]):
                container.add(stack[-1])
            stack.append(i)
        out += [e for i, e in enumerate(lane) if i not in container]
    return out


def _device_ops(events: list[dict]) -> list[dict]:
    """Complete events of the op lanes, or of every non-python lane
    when the trace has no op lane (a CPU rehearsal trace); the
    program's own spans are never ops."""
    op_lanes = {(e.get("pid"), e.get("tid")) for e in events
                if e.get("ph") == "M" and e.get("name") == "thread_name"
                and "XLA Ops" in e.get("args", {}).get("name", "")}
    pid_names = {e.get("pid"): e.get("args", {}).get("name", "")
                 for e in events
                 if e.get("ph") == "M" and e.get("name") == "process_name"}
    out = []
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        if op_lanes:
            if (e.get("pid"), e.get("tid")) not in op_lanes:
                continue
        elif "python" in pid_names.get(e.get("pid"), "").lower():
            continue
        name = e.get("name", "?")
        if name.startswith("$") or name.startswith(TRACE_PREFIX):
            continue            # python source spans, program spans
        out.append(e)
    return out


def summarize(trace_file: str, top_n: int = 20) -> list[dict]:
    """Top-``top_n`` leaf ops by total device time: one dict per op with
    name, call count, total ms, and share of the traced device time."""
    dur_by_name: dict[str, float] = defaultdict(float)
    calls: dict[str, int] = defaultdict(int)
    for e in leaves(_device_ops(_load(trace_file))):
        name = e.get("name", "?")
        dur_by_name[name] += e["dur"]          # microseconds
        calls[name] += 1
    total = sum(dur_by_name.values()) or 1.0
    return [{"op": k, "calls": calls[k],
             "total_ms": round(v / 1e3, 3),
             "share": round(v / total, 4)}
            for k, v in sorted(dur_by_name.items(),
                               key=lambda kv: -kv[1])[:top_n]]


def _scope(op_name: str) -> str:
    """The phase an op ran in: the name-stack component under the loop
    body (``push`` of ``jit(looped)/while/body/push/push/pallas_call``),
    except that the fault plane's work, which nests inside the phase it
    runs in (``.../body/churn/faults/lt``), is ``faults``; else the
    outermost component (``jit(looped)`` for the loop's own control,
    ``jit(ne)`` for an eager op)."""
    parts = op_name.rstrip(":").split("/")
    if "body" in parts[:-1]:
        below = parts[parts.index("body") + 1:]
        return "faults" if "faults" in below[:-1] else below[0]
    return parts[0] or "?"


def scopes(trace_file: str) -> list[dict]:
    """Leaf device time by phase (:func:`_scope` of each op's
    ``tf_op``), one dict per phase with call count and total ms, on the
    first device; ops without a name stack (copies the compiler adds)
    go to ``(none)``.  Empty when no op carries a name stack (a CPU
    trace)."""
    ops = leaves(_device_ops(_load(trace_file)))
    if not any("tf_op" in o.get("args", {}) for o in ops):
        return []
    first = min(o["pid"] for o in ops)
    dur: dict[str, float] = defaultdict(float)
    calls: dict[str, int] = defaultdict(int)
    for o in ops:
        if o["pid"] != first:
            continue
        op_name = o.get("args", {}).get("tf_op")
        key = _scope(op_name) if op_name else "(none)"
        dur[key] += o["dur"]
        calls[key] += 1
    return [{"scope": k, "calls": calls[k], "total_ms": round(v / 1e3, 3)}
            for k, v in sorted(dur.items(), key=lambda kv: -kv[1])]


def _busy(ops: list[dict]) -> list[tuple[float, float]]:
    """Union of the ops' intervals, in microseconds."""
    merged = []
    for s, e in sorted((o["ts"], o["ts"] + o["dur"]) for o in ops):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def program_spans(trace_file: str) -> list[dict]:
    """One dict per program span name (``gossip.<name>`` host events):
    how many, their total ms, and the ms of them in which the first
    device ran no op, each idle moment charged to the innermost
    program span open then (spans on one thread nest).  Empty when the
    trace holds no program span."""
    events = _load(trace_file)
    spans = sorted((e for e in events
                    if e.get("ph") == "X" and "dur" in e
                    and e.get("name", "").startswith(TRACE_PREFIX)),
                   key=lambda e: (e["ts"], -e["dur"]))
    if not spans:
        return []
    ops = leaves(_device_ops(events))
    if ops:
        first = min(o["pid"] for o in ops)
        ops = [o for o in ops if o["pid"] == first]
    busy = _busy(ops)
    starts = [s["ts"] for s in spans]

    def innermost(t):
        # the latest-starting span that still holds t: spans nest
        for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
            if spans[i]["ts"] + spans[i]["dur"] >= t:
                return spans[i]
        return None

    rows = defaultdict(lambda: {"count": 0, "total_ms": 0.0,
                                "idle_ms": 0.0})
    for s in spans:
        row = rows[s["name"][len(TRACE_PREFIX):]]
        row["count"] += 1
        row["total_ms"] += s["dur"] / 1e3
    span_edges = sorted({t for s in spans
                         for t in (s["ts"], s["ts"] + s["dur"])})
    lo, hi = span_edges[0], span_edges[-1]
    edges = [lo] + [x for b in busy for x in b] + [hi]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        g0, g1 = max(g0, lo), min(g1, hi)
        if g1 <= g0:
            continue
        # cut the idle gap at the spans' edges, then charge each piece
        cuts = [g0] + span_edges[bisect.bisect_right(span_edges, g0):
                                 bisect.bisect_left(span_edges, g1)] + [g1]
        for p0, p1 in zip(cuts, cuts[1:]):
            s = innermost(0.5 * (p0 + p1))
            if s is not None:
                rows[s["name"][len(TRACE_PREFIX):]]["idle_ms"] += (
                    (p1 - p0) / 1e3)
    return [{"span": k, "count": v["count"],
             "total_ms": round(v["total_ms"], 3),
             "idle_ms": round(v["idle_ms"], 3)}
            for k, v in sorted(rows.items(),
                               key=lambda kv: -kv[1]["total_ms"])]
