"""The per-pass kernel readers: named kernels in, nothing from unnamed."""

import gzip
import json
import os

import pytest

from gossipbench import spec, trace as tr
from gossipbench.window import Scenario

DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "testdata")
READERS = ("push_ms_per_round", "pull_ms_per_round", "liveness_ms_per_round")


class _Run:
    def __init__(self, summary, scenarios):
        self.trace = summary
        self.scenarios = scenarios


def _read(name, run):
    return spec.metric_reader(name)(run)


def test_named_kernels_split_the_kernel_time():
    # a traced scenario of 4 rounds: push every round, pull every round,
    # liveness on one round of three (a conditional holds it)
    ops, t = [], 0.0
    for r in range(4):
        ops.append(tr.Op(0, "push.8", "custom-call", t, t + 3e-3))
        ops.append(tr.Op(0, "fusion.12", "loop fusion", t + 3e-3, t + 4e-3))
        ops.append(tr.Op(0, "pull.8", "custom-call", t + 4e-3, t + 5e-3))
        if r % 3 == 0:
            ops.append(tr.Op(0, "conditional.2", "conditional",
                             t + 5e-3, t + 7e-3))
            ops.append(tr.Op(0, "liveness.2", "custom-call",
                             t + 5e-3, t + 7e-3))
        t += 8e-3
    s = tr.summarize(ops, [tr.Span("init", 0, -1e-3, 0.0),
                           tr.Span("run", 0, 0.0, t)])
    run = _Run(s, [Scenario(0, 1e-3, t, 4, False)])
    got = {name: _read(name, run) for name in READERS}
    assert got["push_ms_per_round"] == pytest.approx(3.0)
    assert got["pull_ms_per_round"] == pytest.approx(1.0)
    assert got["liveness_ms_per_round"] == pytest.approx(2 * 2.0 / 4)
    assert sum(got.values()) == pytest.approx(1000 * s.kernel_s / 4)


def test_push_self_counts_as_push():
    ops = [tr.Op(0, "push_self.3", "custom-call", 0.0, 1e-3),
           tr.Op(0, "push.4", "custom-call", 1e-3, 3e-3)]
    s = tr.summarize(ops, [tr.Span("run", 0, 0.0, 4e-3)])
    run = _Run(s, [Scenario(0, 0.0, 4e-3, 1, False)])
    assert _read("push_ms_per_round", run) == pytest.approx(3.0)
    assert _read("pull_ms_per_round", run) is None


def test_unnamed_kernels_read_nothing():
    # one scenario of scale-1m traced on one v5e chip before the kernels
    # had names: body.16, body.17, branch_1_fun.2
    with gzip.open(os.path.join(
            DATA, "v5e_scale1m_scenario.trace.json.gz"), "rt") as f:
        s = tr.summarize(*tr.read_events(json.load(f)))
    run = _Run(s, [Scenario(1, 0.015, 0.05, 36, False)])
    assert s.kernel_s > 0
    for name in READERS:
        assert _read(name, run) is None
    assert _read("push_ms_per_round", _Run(None, [])) is None


def test_pass_time_averages_over_chips():
    # two chips, one round: chip 0's push takes 3 ms, chip 1's 4 ms
    body = "jit(looped)/while/body/"
    ops = [tr.Op(c, "push.8", "custom-call", 0.0, (3 + c) * 1e-3,
                 body + "push/push/pallas_call") for c in range(2)]
    s = tr.summarize(ops, [tr.Span("run", 0, 0.0, 8e-3)], n_chips=2)
    run = _Run(s, [Scenario(0, 0.0, 8e-3, 1, False)])
    assert _read("push_ms_per_round", run) == pytest.approx(3.5)
    assert _read("pull_ms_per_round", run) is None
