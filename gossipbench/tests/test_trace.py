"""The trace reduction on slices of recorded TPU traces."""

import gzip
import json
import os

import pytest

from gossipbench import trace as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "testdata")


def _doc(name):
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        return json.load(f)


def test_containers_are_not_double_counted():
    # r5_10m_pullwin: the while loop (49 ms) holds every op of the scan,
    # and a liveness round's conditional holds its kernel
    ops, spans = tr.read_events(_doc("r5_10m_pullwin_slice.trace.json.gz"))
    assert spans == []
    cats = {o.category for o in ops}
    assert {"while", "conditional", "custom-call"} <= cats
    leaf = tr.leaves(ops)
    assert {o.category for o in leaf} == cats - {"while", "conditional"}
    assert len(leaf) == len(ops) - 2
    # with the containers the lane sums to far more than its span
    lo, hi = min(o.start for o in ops), max(o.end for o in ops)
    assert sum(o.end - o.start for o in ops) > hi - lo
    leaf_lo = min(o.start for o in leaf)
    leaf_hi = max(o.end for o in leaf)
    assert sum(o.end - o.start for o in leaf) <= leaf_hi - leaf_lo


def test_custom_calls_are_kernel_time():
    ops, _ = tr.read_events(_doc("r5_10m_pullwin_slice.trace.json.gz"))
    leaf = tr.leaves(ops)
    lo = min(o.start for o in ops) - 1
    hi = max(o.end for o in ops) + 1
    s = tr.summarize(ops, [tr.Span("run", 0, lo, hi)])
    want_k = sum(o.end - o.start for o in leaf if o.category == "custom-call")
    want_x = sum(o.end - o.start for o in leaf if o.category != "custom-call")
    assert s.kernel_s == pytest.approx(want_k)
    assert s.xla_s == pytest.approx(want_x)
    assert 4.2e-3 < s.kernel_s < 4.3e-3       # the slice's four kernels
    assert s.scenarios == {0}


def test_idle_union_and_gap_attribution():
    ops = [tr.Op(0, "a", "loop fusion", 1.0, 2.0),
           tr.Op(0, "b", "custom-call", 1.5, 2.5),     # overlaps a
           tr.Op(0, "w", "while", 3.0, 6.0),           # container
           tr.Op(0, "c", "custom-call", 3.5, 4.0),
           tr.Op(0, "d", "loop fusion", 4.0, 5.0)]
    spans = [tr.Span("init", 0, 0.0, 2.6), tr.Span("run", 0, 2.7, 7.0)]
    s = tr.summarize(ops, spans)
    assert s.window_s == pytest.approx(7.0)
    assert s.busy_s == pytest.approx(1.5 + 1.5)        # [1, 2.5] and [3.5, 5]
    assert s.kernel_s == pytest.approx(0.5)            # c only: b is in init
    assert s.xla_s == pytest.approx(1.0)
    gaps = dict(s.idle_gaps)
    assert gaps["init"] == pytest.approx(1.0 + 0.1)    # [0, 1] and [2.5, 2.6]
    assert gaps["between scenarios"] == pytest.approx(0.1)
    assert gaps["dispatch"] == pytest.approx(0.8)      # [2.7, 3.5]
    assert gaps["fetch"] == pytest.approx(2.0)         # [5, 7]
    assert tr.union([(0, 1), (0.5, 2), (3, 4)]) == [(0, 2), (3, 4)]


def test_chip_trace_scenario():
    # one scenario of scale-1m traced on a v5e (my chip run, PR 22)
    ops, spans = tr.read_events(_doc("v5e_scale1m_scenario.trace.json.gz"))
    assert [s.name for s in spans] == ["init", "run"]
    s = tr.summarize(ops, spans)
    assert 0 < s.busy_s < s.window_s
    assert s.kernel_s > 0 and s.xla_s > 0
    assert s.scenarios == {1}
    assert any("custom-call" in name for name, _ in s.top_ops)
    assert len(s.top_ops) <= 10 and len(s.idle_gaps) <= 10


# the parent reduction's readings of the committed traces, pinned so
# that a change of the reduction cannot move a cell's kernel, XLA,
# top-op or idle-gap reading unseen
_PINNED = {
    "r5_10m_pullwin_slice.trace.json.gz": (
        0.004278379922000003, 0.0020881551560000267,
        [['branch_1_fun.1 (custom-call)', 0.0026797699220000026],
         ['closed_call.45 (custom-call)', 0.0015986076559999923],
         ['copy.49 (data formatting)', 0.00048008242200001106],
         ['copy.48 (data formatting)', 0.0004798163279999962],
         ['convert_reduce_fusion (loop fusion)', 0.00021495992199999092],
         ['fusion.119 (loop fusion)', 0.00019191625000000712],
         ['fusion.120 (loop fusion)', 0.00017288492200000227],
         ['bitcast_select_fusion.3 (loop fusion)', 0.0001267049219999955],
         ['fusion.122 (custom fusion)', 0.00012570124999999877],
         ['fusion.1 (custom fusion)', 0.00010692109400001204]],
        [['dispatch', 1.036248991328], ['fetch', 1.006460833594],
         ['inside the round program', 1.1162499999745057e-06]]),
    "v5e_scale1m_scenario.trace.json.gz": (
        0.009771781794000192, 0.00943695359400211,
        [['body.16 (custom-call)', 0.005794277342000012],
         ['fusion.172 (loop fusion)', 0.003185353983999939],
         ['branch_1_fun.2 (custom-call)', 0.0027436437500000105],
         ['add_maximum_fusion.3 (loop fusion)', 0.0019537599219999896],
         ['body.17 (custom-call)', 0.001462409454000016],
         ['fusion.168 (custom fusion)', 0.00045661640599999054],
         ['fusion.164 (custom fusion)', 0.00045234586000006405],
         ['slice.125 (slice)', 0.00032255429799993296],
         ['copy-done.2 (copy-done)', 0.0002616338280000269],
         ['compare_reduce_fusion.6 (loop fusion)', 0.00025147203200007184]],
        [['init', 0.015310712029999882],
         ['inside the round program', 0.002384559611997683],
         ['fetch', 0.0002407161719999995],
         ['dispatch', 7.80000092293065e-11]]),
}


@pytest.mark.parametrize("name", sorted(_PINNED))
def test_one_chip_readings_unmoved(name):
    ops, spans = tr.read_events(_doc(name))
    if not spans:
        spans = [tr.Span("run", 0, min(o.start for o in ops) - 1,
                         max(o.end for o in ops) + 1)]
    s = tr.summarize(ops, spans)
    kernel_s, xla_s, top_ops, idle_gaps = _PINNED[name]
    assert (s.kernel_s, s.xla_s) == (kernel_s, xla_s)
    assert s.top_ops == top_ops and s.idle_gaps == idle_gaps
    # every op's total is kept, the top ten among them
    assert set(s.op_s) == {0}
    assert dict(top_ops).items() <= s.op_s[0].items()
    assert sum(s.op_s[0].values()) == pytest.approx(
        sum(s.scope_s[0].values()))
    assert s.collective_s == {0: {}}


def _four_chip_doc():
    """Four chips, each running one round in a harness run span (times
    in microseconds): prep, a push kernel that takes 20 + c, an
    all-gather whose start and done halves bracket XLA work, a census
    all-reduce, a ``psum`` that only its category names a collective, a
    fault-plane op nested in churn, and the while loop that holds them
    all."""
    body = "jit(looped)/while/body/"
    meta = [{"ph": "M", "name": "process_name", "pid": 100,
             "args": {"name": "/host:CPU"}}]
    ev = []
    for c in range(4):
        pid = 1 + c
        meta += [{"ph": "M", "name": "process_name", "pid": pid,
                  "args": {"name": f"/device:TPU:{c}"}},
                 {"ph": "M", "name": "thread_name", "pid": pid, "tid": 1,
                  "args": {"name": "XLA Ops"}}]
        for name, cat, t0, t1, scope in [
                ("while.1", "while", 5, 95, "while"),
                ("fusion.1", "loop fusion", 10, 20, "prep/and"),
                ("push.8", "custom-call", 20, 40 + c, "push/push/pallas_call"),
                ("all-gather-start.2", "all-gather", 41 + c, 43 + c,
                 "exchange/all-gather"),
                ("fusion.2", "loop fusion", 44 + c, 52, "update/or"),
                ("all-gather-done.2", "all-gather", 50, 56,
                 "exchange/all-gather"),
                ("all-reduce.3", "all-reduce", 60, 65, "census/psum"),
                ("psum.4", "all-reduce", 66, 67, "exchange/psum"),
                ("fusion.3", "loop fusion", 70, 72, "churn/faults/lt")]:
            ev.append({"ph": "X", "pid": pid, "tid": 1, "name": name,
                       "ts": t0, "dur": t1 - t0,
                       "args": {"hlo_category": cat, "tf_op": body + scope}})
    ev.append({"ph": "X", "pid": 100, "tid": 7, "name": "gossipbench.run:0",
               "ts": 0, "dur": 100})
    return {"traceEvents": meta + ev}


def test_four_chips_scopes_and_collectives():
    s = tr.summarize(*tr.read_events(_four_chip_doc()), n_chips=4)
    us = 1e-6
    assert set(s.op_s) == set(s.scope_s) == set(s.collective_s) == {0, 1, 2, 3}
    for c in range(4):
        want = {"prep": 10, "push": 20 + c, "exchange": 2 + 6 + 1,
                "update": 8 - c, "census": 5, "faults": 2}
        assert s.scope_s[c] == pytest.approx({k: v * us for k, v in want.items()})
        assert s.op_s[c]["push.8 (custom-call)"] == pytest.approx((20 + c) * us)
        # the start half runs alone; the done half overlaps fusion.2 for
        # 2 us; the all-reduce and the psum run alone
        assert s.collective_s[c] == pytest.approx(
            {"all-gather": (2 + 4) * us, "all-reduce": (5 + 1) * us})
    # kernel and XLA time are means over the chips
    assert s.kernel_s == pytest.approx(21.5 * us)
    assert s.xla_s == pytest.approx((10 + 2 + 6.5 + 6 + 5 + 1 + 2) * us)
    # the breakdown stays chip 0's
    assert s.top_ops[0] == ["push.8 (custom-call)", pytest.approx(20 * us)]
    assert s.busy_s == pytest.approx(
        sum(10 + 20 + c + 2 + 12 - c + 5 + 1 + 2 for c in range(4)) / 4 * us)


# names and categories as a v5e's trace of the sharded engine gives them
@pytest.mark.parametrize("name,category,kind", [
    ("all-gather.26", "all-gather", "all-gather"),
    ("all-gather-start", "async-start", "all-gather"),
    ("all-reduce-done.12", "async-done", "all-reduce"),
    ("psum.31", "all-reduce", "all-reduce"),
    ("collective-permute-start.1", "collective-permute-start",
     "collective-permute"),
    ("all-to-all.2", "all-to-all", "all-to-all"),
    ("reduce-scatter.7", "reduce-scatter", "reduce-scatter"),
    ("fusion.9", "custom fusion", None), ("slice-done.33", "async-done", None),
    ("copy-done.2", "copy-done", None)])
def test_collective_names(name, category, kind):
    assert tr.collective(tr.Op(0, name, category, 0.0, 1.0)) == kind


@pytest.mark.parametrize("tf_op,want", [
    ("jit(looped)/while/body/push/push/pallas_call", "push"),
    ("jit(looped)/while/body/churn/faults/lt", "faults"),
    ("jit(looped)/while/body/liveness/while/body/add", "liveness"),
    ("jit(looped)/while/cond/lt", "jit(looped)"),
    ("", "(none)")])
def test_round_scope(tf_op, want):
    assert tr.scope(tf_op) == want
