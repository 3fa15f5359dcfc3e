"""From a profiler trace to device time, kernel time and idle gaps.

The trace is JAX's ``*.trace.json.gz``.  Device ops sit on each
``/device:TPU:<i>`` process's "XLA Ops" line.  That line nests
container events around the ops they run: a ``while`` holds its body,
a ``conditional`` its branch.  Summing the line counts that time twice,
so only leaf events count: an event that holds another event of its
line is a container.  A leaf whose ``hlo_category`` is ``custom-call``
is a kernel (Pallas calls lower to one); every other leaf is XLA work.
A leaf's ``tf_op`` is its name stack, which names the round scope it
ran in; a leaf named or categorized as a collective (``all-gather``,
``all-reduce``, ``collective-permute``, ``all-to-all``,
``reduce-scatter``, or the ``-start``/``-done`` half of one) moves
data between chips.  Every
total is kept for each chip; kernel and XLA time are means over the
chips, the top ops and idle gaps chip 0's.  The harness's own host
spans (``gossipbench.init``, ``gossipbench.run``) lie on the same
clock, so each idle gap of the device is charged to what the host was
doing then.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re
from collections import defaultdict

SPAN_PREFIX = "gossipbench."
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute", "all-to-all",
               "reduce-scatter")
_COLLECTIVE = re.compile(r"^(?P<kind>%s)(-start|-done)?(\.\d+)?$"
                         % "|".join(COLLECTIVES))


@dataclasses.dataclass
class Op:
    chip: int
    name: str
    category: str
    start: float      # seconds on the trace's clock
    end: float
    tf_op: str = ""   # the op's name stack, where the trace gives one


@dataclasses.dataclass
class Span:
    name: str         # "init" or "run"
    index: int        # the scenario's index in the window
    start: float
    end: float


def _category(args: dict) -> str:
    cat = args.get("hlo_category")
    if cat:
        return cat
    long = args.get("long_name", "")
    return "custom-call" if " custom-call(" in long else "unknown"


def read_events(doc: dict) -> tuple[list[Op], list[Span]]:
    """Every event of the device op lines (containers included) and the
    harness's host spans, in seconds."""
    events = doc.get("traceEvents", [])
    chips = {}
    host = set()
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pname = e.get("args", {}).get("name", "")
            m = _DEVICE.match(pname)
            if m:
                chips[e["pid"]] = int(m.group(1))
            elif pname.startswith("/host:"):
                host.add(e["pid"])
    op_lines = {(e["pid"], e["tid"]) for e in events
                if e.get("ph") == "M" and e.get("name") == "thread_name"
                and e["pid"] in chips
                and e.get("args", {}).get("name") == "XLA Ops"}
    ops, spans = [], []
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        key = (e.get("pid"), e.get("tid"))
        start, end = e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6
        if key in op_lines:
            args = e.get("args", {})
            ops.append(Op(chips[key[0]], e.get("name", "?"), _category(args),
                          start, end, args.get("tf_op", "")))
        elif e.get("pid") in host and e.get("name", "").startswith(SPAN_PREFIX):
            name, _, index = e["name"][len(SPAN_PREFIX):].partition(":")
            spans.append(Span(name, int(index or -1), start, end))
    spans.sort(key=lambda s: s.start)
    return ops, spans


def leaves(ops: list[Op]) -> list[Op]:
    """Drop container events: those that hold another event of the
    same chip's line."""
    out = []
    by_chip = defaultdict(list)
    for op in ops:
        by_chip[op.chip].append(op)
    for chip_ops in by_chip.values():
        chip_ops.sort(key=lambda o: (o.start, -o.end))
        container = set()
        stack = []
        for i, op in enumerate(chip_ops):
            while stack and chip_ops[stack[-1]].end <= op.start:
                stack.pop()
            if stack and op.end <= chip_ops[stack[-1]].end:
                container.add(stack[-1])
            stack.append(i)
        out += [op for i, op in enumerate(chip_ops) if i not in container]
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _minus(a, b):
    """Length of the union ``a`` outside the union ``b`` (both sorted
    and disjoint)."""
    out, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        t, k = s, j
        while k < len(b) and b[k][0] < e:
            out += max(b[k][0] - t, 0.0)
            t = max(t, b[k][1])
            k += 1
        out += max(e - t, 0.0)
    return out


def scope(tf_op: str) -> str:
    """The round scope an op ran in: the name-stack component under the
    loop body (``push`` of ``jit(looped)/while/body/push/push/
    pallas_call``), except that the fault plane's work, which nests
    inside the scope it runs in (``.../body/churn/faults/lt``), is
    ``faults``; else the outermost component; ``(none)`` for an op
    with no name stack (copies the compiler adds).  Copied from the
    program's ``telemetry/traceview.py``."""
    if not tf_op:
        return "(none)"
    parts = tf_op.rstrip(":").split("/")
    if "body" in parts[:-1]:
        below = parts[parts.index("body") + 1:]
        return "faults" if "faults" in below[:-1] else below[0]
    return parts[0] or "?"


def collective(op: Op) -> str | None:
    """The collective an op is, by its name or, where XLA kept the
    name of the JAX call (``psum.31``), by its category."""
    for text in (op.name, op.category):
        m = _COLLECTIVE.match(text)
        if m:
            return m.group("kind")
    return None


def _gap_label(span, t, op_starts, ends_max) -> str:
    """What the host was doing at ``t``, an idle moment of the chip: in a
    run span, before its first op is the dispatch and after its last op
    the fetch of the round count."""
    if span is None:
        return "between scenarios"
    if span.name != "run":
        return span.name
    i0 = bisect.bisect_left(op_starts, span.start)
    i1 = bisect.bisect_right(op_starts, span.end) - 1
    if i1 < i0 or t < op_starts[i0]:
        return "dispatch"
    if t > ends_max[i1]:
        return "fetch"
    return "inside the round program"


@dataclasses.dataclass
class Summary:
    window_s: float           # first harness span's start to the last's end
    busy_s: float             # union of leaf ops in the window, mean over chips
    kernel_s: float           # custom-call leaf time inside run spans, mean over chips
    xla_s: float              # other leaf time inside run spans, mean over chips
    scenarios: set            # indices of the scenarios whose run span is traced
    top_ops: list             # chip 0's [[name (category), seconds], ...]
    idle_gaps: list           # chip 0's [[what the host was doing, seconds], ...]
    # per chip, over the window: {chip: {key: seconds}}
    op_s: dict                # by "name (category)", every leaf op
    scope_s: dict             # by round scope (``scope``)
    collective_s: dict        # exposed time, by collective (``collective``)


def summarize(ops: list[Op], spans: list[Span], n_chips: int = 1,
              top: int = 10) -> Summary:
    if not spans:
        raise ValueError("the trace holds no harness span")
    lo, hi = spans[0].start, spans[-1].end
    leaf = [o for o in leaves(ops) if o.end > lo and o.start < hi]
    chips = sorted({o.chip for o in leaf})[:max(n_chips, 1)] or [0]
    busy = 0.0
    per_chip = {}
    for c in chips:
        iv = union(_clip([(o.start, o.end) for o in leaf if o.chip == c], lo, hi))
        per_chip[c] = iv
        busy += sum(e - s for s, e in iv)
    busy /= len(chips)

    starts = [s.start for s in spans]

    def span_at(t):
        i = bisect.bisect_right(starts, t) - 1
        return spans[i] if i >= 0 and t <= spans[i].end else None

    kernel = xla = 0.0
    op_s, scope_s, collective_s = {}, {}, {}
    for c in chips:
        chip_ops = sorted((o for o in leaf if o.chip == c), key=lambda o: o.start)
        totals, scopes, coll = defaultdict(float), defaultdict(float), defaultdict(list)
        other = []
        for o in chip_ops:
            totals[f"{o.name} ({o.category})"] += o.end - o.start
            scopes[scope(o.tf_op)] += o.end - o.start
            kind = collective(o)
            if kind:
                coll[kind].append((o.start, o.end))
            else:
                other.append((o.start, o.end))
            span = span_at(0.5 * (o.start + o.end))
            if span is not None and span.name == "run":
                if o.category == "custom-call":
                    kernel += o.end - o.start
                else:
                    xla += o.end - o.start
        busy_other = union(_clip(other, lo, hi))
        op_s[c], scope_s[c] = dict(totals), dict(scopes)
        collective_s[c] = {k: _minus(union(_clip(iv, lo, hi)), busy_other)
                           for k, iv in coll.items()}
    kernel /= len(chips)
    xla /= len(chips)
    c0 = chips[0]
    c0_ops = sorted((o for o in leaf if o.chip == c0), key=lambda o: o.start)

    # idle gaps of chip 0, charged to what the host was doing
    op_starts = [o.start for o in c0_ops]
    ends_max = []
    for o in c0_ops:
        ends_max.append(max(o.end, ends_max[-1]) if ends_max else o.end)
    gaps = defaultdict(float)
    iv = per_chip.get(c0, [])
    edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        # cut the gap at the host spans' edges, then charge each piece
        cuts = [g0, g1]
        for sp in spans[max(bisect.bisect_right(starts, g0) - 1, 0):]:
            if sp.start >= g1:
                break
            cuts += [t for t in (sp.start, sp.end) if g0 < t < g1]
        cuts.sort()
        for p0, p1 in zip(cuts, cuts[1:]):
            gaps[_gap_label(span_at(0.5 * (p0 + p1)), 0.5 * (p0 + p1),
                            op_starts, ends_max)] += p1 - p0
    order = lambda d: sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])[:top]
    return Summary(window_s=hi - lo, busy_s=busy, kernel_s=kernel, xla_s=xla,
                   scenarios={s.index for s in spans if s.name == "run"},
                   top_ops=order(op_s.get(c0, {})), idle_gaps=order(gaps),
                   op_s=op_s, scope_s=scope_s, collective_s=collective_s)


def load(trace_dir: str) -> dict:
    hits = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                     recursive=True)
    if not hits:
        raise FileNotFoundError(f"no *.trace.json.gz under {trace_dir}")
    with gzip.open(max(hits, key=os.path.getmtime), "rt") as f:
        return json.load(f)
