"""A cell of four chips on four of the CPU's virtual devices.

The cell is data alone: a config with ``mesh_devices=4`` and a
``chips: 4`` entry in a copy of BENCHMARK.json, at 2^15 peers (four
shards of 64 rows, one row block each), with the frontier-sparse
exchange and its recursive-halving butterfly switched on, as the chip
runs them.  The run must read correct, and read not correct once the
timed path is broken underneath in each way a sharded cell can be
broken: the state left unchanged, half the rows left out, the exchange
between chips left out, a bit altered where it is produced.
"""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gossipbench import ROOT, control, run, spec, window
from gossipbench.references import aligned_faults, aligned_gossip as ref

CELL = "tiny-32k-4x.stagger16"
SEED = 2_400_000_041


def _checkout(root, extra: str = "", chips: int = 4) -> str:
    """A checkout-shaped directory with the four-chip cell; ``extra``
    lines go into its config."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    with open(os.path.join(ROOT, "gossipbench/configs/dht-16m.txt")) as f:
        conf = f.read().replace("n_peers=16777216", "n_peers=32768")
    conf += "mesh_devices=4\nfrontier_mode=1\nfrontier_algo=1\n" + extra
    os.makedirs(root / "gossipbench/configs")
    os.makedirs(root / "gossipbench/traffic")
    (root / "gossipbench/configs/tiny-32k-4x.txt").write_text(conf)
    (root / "gossipbench/traffic/stagger16.txt").write_text(
        "n_messages=16\nmessage_stagger=1\ncoverage_target=0.99\n"
        "max_rounds=64\n")
    b["configs"].append({"name": "tiny-32k-4x", "source": "test",
                         "file": "gossipbench/configs/tiny-32k-4x.txt",
                         "reduced": ["n_peers"], "why": "test"})
    b["workloads"].append({"name": CELL, "config": "tiny-32k-4x",
                           "traffic": "stagger16", "chips": chips,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps(b))
    return str(path)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return _checkout(tmp_path_factory.mktemp("bench4"))


def _run(bench, trace=0):
    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=0.5,
                              trace=trace)
    return run.execute(args, require_tpu=False, bench_path=bench)


def test_four_chip_cell_is_correct(bench):
    line, err = _run(bench)
    assert line["correct"] is True, err
    assert line["checks"]["mismatch"] == {"value": 0, "limit": 0}
    assert line["checks"]["coverage_gap"] == {"value": 0.0, "limit": 0.0}
    assert line["attempted"] >= 1 and line["failed"] == 0


def test_engine_follows_the_chips(bench):
    cell = spec.load_cell(CELL, bench)
    assert window.engine_for(cell) == "aligned-sharded-4"
    setup = window.build(cell)
    assert setup.sim.n_shards == 4 and setup.sim._frontier
    one = spec.load_cell("dht-16m.stagger32")
    assert window.engine_for(one) == "aligned"


def test_chips_and_mesh_devices_must_agree(tmp_path):
    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=0.5, trace=0)
    with pytest.raises(spec.SpecError, match="2 chips.*mesh_devices=4"):
        run.execute(args, require_tpu=False,
                    bench_path=_checkout(tmp_path, chips=2))


@pytest.mark.parametrize("key", ["msg_shards", "hier_hosts", "hier_devs"])
def test_harness_runs_the_flat_engine_only(tmp_path, key):
    cell = spec.load_cell(CELL, _checkout(tmp_path, f"{key}=2\n"))
    with pytest.raises(spec.SpecError, match=key):
        window.engine_for(cell)


@pytest.mark.parametrize("key", ["msg_shards", "hier_hosts", "hier_devs"])
def test_reference_refuses_other_factorizations(tmp_path, key):
    cell = spec.load_cell(CELL, _checkout(tmp_path, f"{key}=2\n"))
    with pytest.raises(ValueError, match=key):
        ref.deployment(cell.kv())


def test_reference_refuses_other_row_counts():
    # 1,100 peers fill 9 rows; four equal shards need 12
    kv = spec.read_kv(os.path.join(ROOT, "gossipbench/configs/dht-16m.txt"))
    kv.update(n_peers="1100", mesh_devices="4", n_messages="16",
              message_stagger="1", coverage_target="0.99", max_rounds="64")
    with pytest.raises(ValueError, match="row count"):
        ref.deployment(kv)


def test_control_is_not_correct(bench):
    r = control.readings(CELL, SEED + 2, bench_path=bench)
    assert r["correct"] is False
    assert r["numbers"]["mismatch"] > 0 and r["parts"]["lanes"] > 0


def _break(monkeypatch, fault):
    from p2p_gossipprotocol_tpu.parallel.aligned_sharded import \
        AlignedShardedSimulator as Sharded

    if fault == "exchange":
        # no data crosses between chips: a gather repeats the chip's own
        # block, a permute hands a chip its own buffer back
        def all_gather(x, axis_name, *, axis=0, tiled=False, **_):
            n = jax.lax.axis_size(axis_name)
            if tiled:
                return jnp.concatenate([x] * n, axis=axis)
            return jnp.stack([x] * n, axis=axis)

        monkeypatch.setattr(jax.lax, "all_gather", all_gather)
        monkeypatch.setattr(jax.lax, "ppermute", lambda x, *a, **k: x)
    elif fault == "altered":
        real = Sharded.run_to_coverage

        def run_to_coverage(self, *a, **kw):
            st, tp, rounds, wall = real(self, *a, **kw)
            flip = st.seen_w.at[0, 0, 0].set(st.seen_w[0, 0, 0] ^ 1)
            return st.replace(seen_w=flip), tp, rounds, wall

        monkeypatch.setattr(Sharded, "run_to_coverage", run_to_coverage)
    else:
        real = Sharded._step_local

        def step(self, state, topo, fr=None):
            out = real(self, state, topo, fr)
            st = out[0]
            if fault == "unchanged":
                st = state.replace(round=st.round)
            else:
                # the lower half of each shard's rows keep last round's bits
                rows = jnp.arange(st.seen_w.shape[1])[None, :, None]
                keep = rows < st.seen_w.shape[1] // 2
                st = st.replace(
                    seen_w=jnp.where(keep, st.seen_w, state.seen_w),
                    frontier_w=jnp.where(keep, st.frontier_w,
                                         state.frontier_w))
            return (st,) + tuple(out[1:])

        monkeypatch.setattr(Sharded, "_step_local", step)


@pytest.mark.parametrize("fault", ["unchanged", "half", "exchange", "altered"])
def test_broken_timed_path_is_not_correct(bench, monkeypatch, fault):
    _break(monkeypatch, fault)
    line, err = _run(bench)
    assert line["correct"] is False, err
    assert line["checks"]["mismatch"]["value"] > 0


def test_sharded_state_flattens_to_the_reference_layout(bench):
    cell = spec.load_cell(CELL, bench)
    setup = window.build(cell)
    key = window.scenario_key(SEED, 0)
    _, st, tp = window.run_scenario(setup, key, 0)
    assert len(st.seen_w.sharding.device_set) == 4
    from gossipbench import check

    rounds = int(jax.device_get(st.round))
    prog = check.flatten(st, tp, rounds, setup.coverage(st, tp))
    scen = ref.Scenario(ref.deployment(cell.kv()))
    want = scen.run(key)
    assert np.array_equal(prog["seen"], want.seen)
    assert np.array_equal(prog["lanes"], want.lanes)
    assert prog["seen"].shape == (32768, 1)
    assert prog["coverage"] == want.coverage


def _deployment(mod, extra: dict):
    kv = spec.read_kv(os.path.join(ROOT, "gossipbench/configs/dht-16m.txt"))
    kv.update(n_peers="32768", mesh_devices="4", n_messages="16",
              message_stagger="1", coverage_target="0.99", max_rounds="64",
              **extra)
    return mod.deployment(kv)


@pytest.mark.parametrize("mod,extra", [
    (ref, {}),
    (aligned_faults, {"fault_link_drop": "0.2", "fault_delay": "0.1",
                      "fault_partition": "2:5", "fault_crash": "3:0.3",
                      "fault_recover": "6:0.5"})], ids=["plain", "faults"])
def test_reference_spread_over_four_devices_is_bitwise(mod, extra):
    dep = _deployment(mod, extra)
    key = window.scenario_key(SEED + 4, 0)
    one = mod.Scenario(dep).run(key)
    four = mod.Scenario(dep, devices=jax.devices()[:4]).run(key)
    for f in ("seen", "frontier", "alive", "strikes", "lanes"):
        assert np.array_equal(getattr(one, f), getattr(four, f)), f
    assert (one.rounds, one.coverage) == (four.rounds, four.coverage)


@pytest.mark.parametrize("n", [32768, 100_000, 1 << 20, 3 << 20])
@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_reference_layout_is_the_programs(n, shards):
    from p2p_gossipprotocol_tpu.aligned import build_aligned

    # the shapes build_aligned lays out; its tables need not be drawn
    for rowblk in (2048, 512):
        try:
            topo = build_aligned(seed=0, n=n, n_slots=2, rowblk=rowblk,
                                 n_shards=shards)
        except ValueError:
            continue
        assert ref.layout(n, rowblk, shards) == (topo.rows, topo.rowblk)
