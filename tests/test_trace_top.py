"""trace_top.py: the trace summarizer feeding the traffic-model
reconciliation must keep only the XLA Ops lanes (device traces nest
module/step spans around the op spans — summing every lane would
double-count and halve each kernel's share)."""

import gzip
import json
import subprocess
import sys


def _write_trace(path, events):
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)


def test_xla_ops_lane_filter(tmp_path):
    trace = tmp_path / "t.trace.json.gz"
    _write_trace(str(trace), [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 10,
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 11,
         "args": {"name": "XLA Modules"}},
        # the module span ENCLOSES the two op spans — it must not count
        {"ph": "X", "pid": 1, "tid": 11, "name": "jit_scan",
         "ts": 0, "dur": 1000},
        {"ph": "X", "pid": 1, "tid": 10, "name": "fusion.1",
         "ts": 0, "dur": 600},
        {"ph": "X", "pid": 1, "tid": 10, "name": "dynamic-gather.2",
         "ts": 600, "dur": 400},
    ])
    proc = subprocess.run(
        [sys.executable, "/root/repo/benchmarks/trace_top.py",
         str(trace)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    rows = {r["op"]: r for r in lines[1:]}
    assert "jit_scan" not in rows            # module lane excluded
    assert rows["fusion.1"]["share"] == 0.6  # shares of OP time only
    assert rows["dynamic-gather.2"]["share"] == 0.4


def test_fallback_without_op_lanes(tmp_path):
    """CPU rehearsal traces have no XLA Ops lanes; the summarizer falls
    back to the everything-but-python filter instead of printing
    nothing."""
    trace = tmp_path / "t.trace.json.gz"
    _write_trace(str(trace), [
        {"ph": "M", "name": "process_name", "pid": 2,
         "args": {"name": "python"}},
        {"ph": "X", "pid": 2, "tid": 1, "name": "$pjit.py:1 cache_miss",
         "ts": 0, "dur": 500},
        {"ph": "X", "pid": 3, "tid": 1, "name": "PjitFunction(f)",
         "ts": 0, "dur": 300},
    ])
    proc = subprocess.run(
        [sys.executable, "/root/repo/benchmarks/trace_top.py",
         str(trace)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    rows = {r["op"]: r for r in lines[1:]}
    assert rows == {"PjitFunction(f)": rows["PjitFunction(f)"]}


def _device_lane(pid=1, tid=10):
    return [{"ph": "M", "name": "process_name", "pid": pid,
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
             "args": {"name": "XLA Ops"}}]


def test_containers_do_not_count(tmp_path):
    """A ``while`` on the XLA Ops lane holds the ops of its body: only
    the two ops count, so device time is not doubled."""
    from p2p_gossipprotocol_tpu.telemetry.traceview import summarize

    trace = tmp_path / "t.trace.json.gz"
    _write_trace(str(trace), _device_lane() + [
        {"ph": "X", "pid": 1, "tid": 10, "name": "while.6",
         "ts": 0, "dur": 1000},
        {"ph": "X", "pid": 1, "tid": 10, "name": "push.8",
         "ts": 0, "dur": 700},
        {"ph": "X", "pid": 1, "tid": 10, "name": "fusion.3",
         "ts": 750, "dur": 250},
    ])
    rows = {r["op"]: r for r in summarize(str(trace))}
    assert set(rows) == {"push.8", "fusion.3"}
    assert rows["push.8"]["total_ms"] == 0.7
    assert rows["push.8"]["share"] == round(700 / 950, 4)


def test_program_spans_charge_idle_to_the_innermost(tmp_path):
    """The program's ``gossip.`` host spans nest; each idle moment of
    the device goes to the innermost span open then, and the spans
    themselves are never ops."""
    from p2p_gossipprotocol_tpu.telemetry.traceview import (program_spans,
                                                            summarize)

    host = [{"ph": "M", "name": "process_name", "pid": 7,
             "args": {"name": "/host:CPU"}}]
    span = lambda name, ts, dur: {"ph": "X", "pid": 7, "tid": 1,  # noqa
                                  "name": f"gossip.{name}", "ts": ts,
                                  "dur": dur}
    trace = tmp_path / "t.trace.json.gz"
    _write_trace(str(trace), _device_lane() + host + [
        span("run_to_coverage", 0, 1000),
        span("dispatch", 0, 100),      # idle 0-100, all of it
        span("fetch", 100, 800),       # device busy 300-900: idle 200
        {"ph": "X", "pid": 1, "tid": 10, "name": "push.8",
         "ts": 300, "dur": 600},
        # 900-1000: only run_to_coverage itself is open
    ])
    rows = {r["span"]: r for r in program_spans(str(trace))}
    assert rows["run_to_coverage"] == {"span": "run_to_coverage",
                                       "count": 1, "total_ms": 1.0,
                                       "idle_ms": 0.1}
    assert rows["dispatch"]["idle_ms"] == 0.1
    assert rows["fetch"]["idle_ms"] == 0.2
    assert [r["op"] for r in summarize(str(trace))] == ["push.8"]


def test_no_program_spans(tmp_path):
    from p2p_gossipprotocol_tpu.telemetry.traceview import program_spans

    trace = tmp_path / "t.trace.json.gz"
    _write_trace(str(trace), _device_lane() + [
        {"ph": "X", "pid": 1, "tid": 10, "name": "fusion.1",
         "ts": 0, "dur": 10}])
    assert program_spans(str(trace)) == []


def test_scopes_group_leaf_time_by_phase(tmp_path):
    """A chip trace gives each op its name stack (``tf_op``): leaf time
    groups by the phase under the loop body, the fault plane's nested
    scope on its own; the loop's own control and eager ops keep their
    outermost name."""
    from p2p_gossipprotocol_tpu.telemetry.traceview import scopes

    op = lambda name, ts, dur, tf_op=None: {  # noqa: E731
        "ph": "X", "pid": 1, "tid": 10, "name": name, "ts": ts,
        "dur": dur, "args": {"tf_op": tf_op} if tf_op else {}}
    body = "jit(looped)/while/body"
    trace = tmp_path / "t.trace.json.gz"
    _write_trace(str(trace), _device_lane() + [
        op("while.1", 0, 100, "jit(looped)/while"),
        op("push.8", 0, 40, f"{body}/push/push/pallas_call:"),
        op("popcnt_reduce_fusion.5", 40, 10, f"{body}/census/reduce_sum:"),
        op("compare_fusion.2", 50, 5, f"{body}/census/eq:"),
        op("liveness.2", 55, 20,
           f"{body}/liveness/cond/branch_1_fun/liveness/pallas_call:"),
        op("add_maximum_fusion.3", 85, 3, f"{body}/churn/faults/lt:"),
        op("copy.86", 75, 5),
        op("lt.3", 80, 1, "jit(looped)/while/lt:"),
        op("ne", 200, 2, "jit(ne)/ne:"),
    ])
    rows = {r["scope"]: (r["calls"], r["total_ms"])
            for r in scopes(str(trace))}
    assert rows == {"push": (1, 0.04), "census": (2, 0.015),
                    "liveness": (1, 0.02), "faults": (1, 0.003),
                    "(none)": (1, 0.005),
                    "jit(looped)": (1, 0.001), "jit(ne)": (1, 0.002)}


def test_no_scopes_without_name_stacks(tmp_path):
    from p2p_gossipprotocol_tpu.telemetry.traceview import scopes

    trace = tmp_path / "t.trace.json.gz"
    _write_trace(str(trace), _device_lane() + [
        {"ph": "X", "pid": 1, "tid": 10, "name": "fusion.1",
         "ts": 0, "dur": 10}])
    assert scopes(str(trace)) == []
