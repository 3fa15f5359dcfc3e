"""The unified fault-injection plane (faults.FaultPlan) on the
simulation engines: seed determinism, partition isolation, convergence
under link loss, delayed relays, crash/recovery schedules, and the
bitwise sharded-vs-unsharded contracts.  Everything here is sized for
the tier-1 CPU run (n <= 2048, <= 16 rounds per case)."""

import jax
import numpy as np
import pytest

from p2p_gossipprotocol_tpu import graph as G
from p2p_gossipprotocol_tpu.aligned import AlignedSimulator, build_aligned
from p2p_gossipprotocol_tpu.faults import FaultPlan
from p2p_gossipprotocol_tpu.sim import Simulator


def _full_plan(**over):
    kw = dict(link_drop=0.2, delay=0.1, partitions=((2, 5),),
              partition_groups=2, crash=((3, 0.2),), recover=((8, 0.5),),
              seed=5)
    kw.update(over)
    return FaultPlan(**kw).validate()


# -- plan declaration / parsing ---------------------------------------

def test_plan_parse_roundtrip():
    spec = ("drop=0.2,delay=0.1,dup=0.05,partition=4:12+20:24,groups=4,"
            "crash=3:0.3,recover=16:0.5,byz=0.1,seed=7")
    plan = FaultPlan.parse(spec)
    assert plan.link_drop == 0.2 and plan.duplicate == 0.05
    assert plan.partitions == ((4, 12), (20, 24))
    assert plan.crash == ((3, 0.3),) and plan.recover == ((16, 0.5),)
    assert FaultPlan.parse(plan.to_spec()) == plan


@pytest.mark.parametrize("bad", [
    "drop=1.5",                      # probability out of range
    "warp=0.1",                      # unknown key
    "partition=9",                   # not start:heal
    "partition=5:3",                 # heal before start
    "partition=0:4,groups=3",        # non-power-of-two groups
    "partition=0:4,groups=256",      # groups > 128 breaks the lane rule
    "crash=-1:0.5",                  # negative round
])
def test_plan_rejects_bad_specs(bad):
    with pytest.raises(ValueError):
        FaultPlan.parse(bad)


def test_config_fault_keys(tmp_path):
    from p2p_gossipprotocol_tpu.config import ConfigError, NetworkConfig
    from p2p_gossipprotocol_tpu.faults import plan_from_config

    cfg = tmp_path / "net.txt"
    cfg.write_text("10.0.0.1:8000\nfault_link_drop=0.2\n"
                   "fault_partition=4:12\nfault_partition_groups=2\n"
                   "fault_crash=3:0.3+9:0.1\nfault_recover=16:0.5\n"
                   "fault_seed=7\n")
    plan = plan_from_config(NetworkConfig(str(cfg)))
    assert plan.link_drop == 0.2 and plan.partitions == ((4, 12),)
    assert plan.crash == ((3, 0.3), (9, 0.1)) and plan.seed == 7
    # no fault keys -> no plan -> the engines compile the plain round
    cfg.write_text("10.0.0.1:8000\n")
    assert plan_from_config(NetworkConfig(str(cfg))) is None
    # bad values surface as ConfigError (the config system's contract)
    cfg.write_text("10.0.0.1:8000\nfault_link_drop=2.0\n")
    with pytest.raises(ConfigError):
        NetworkConfig(str(cfg))
    cfg.write_text("10.0.0.1:8000\nfault_partition=0:4\n"
                   "fault_partition_groups=3\n")
    with pytest.raises(ConfigError):
        NetworkConfig(str(cfg))


# -- determinism (acceptance: same seed => bitwise-identical) ----------

def test_edges_faulted_run_is_seed_deterministic():
    topo = G.erdos_renyi(seed=0, n=1024, avg_degree=10)
    mk = lambda: Simulator(topo=topo, n_msgs=8, mode="pushpull",
                           faults=_full_plan(), seed=1)
    r1, r2 = mk().run(12), mk().run(12)
    assert (np.asarray(r1.state.seen) == np.asarray(r2.state.seen)).all()
    assert (np.asarray(r1.state.alive) == np.asarray(r2.state.alive)).all()
    np.testing.assert_array_equal(r1.coverage, r2.coverage)
    np.testing.assert_array_equal(r1.redeliveries, r2.redeliveries)


def test_aligned_faulted_run_is_seed_deterministic():
    topo = build_aligned(seed=0, n=1024, n_slots=8, roll_groups=4)
    mk = lambda: AlignedSimulator(topo=topo, n_msgs=8, mode="pushpull",
                                  faults=_full_plan(), seed=1)
    r1, r2 = mk().run(12), mk().run(12)
    assert (np.asarray(r1.state.seen_w)
            == np.asarray(r2.state.seen_w)).all()
    np.testing.assert_array_equal(r1.coverage, r2.coverage)


def test_plan_machinery_leaves_unfaulted_run_untouched():
    """faults=None and an all-zero plan must both reproduce the exact
    pre-fault-plane trajectory (the plan draws from its own key chain,
    never the simulation's)."""
    topo = G.erdos_renyi(seed=0, n=512, avg_degree=8)
    base = Simulator(topo=topo, n_msgs=4, mode="pushpull", seed=3).run(8)
    noop = Simulator(topo=topo, n_msgs=4, mode="pushpull", seed=3,
                     faults=FaultPlan()).run(8)
    assert (np.asarray(base.state.seen)
            == np.asarray(noop.state.seen)).all()
    np.testing.assert_array_equal(base.coverage, noop.coverage)


# -- partition isolation (acceptance: cross-partition coverage 0) ------

def _cross_group_seen(state_seen, src, groups=2):
    n = state_seen.shape[0]
    other = (np.arange(n) % groups) != (src % groups)
    return int(state_seen[other].sum())


def test_edges_partition_isolates_until_heal():
    plan = FaultPlan(partitions=((0, 6),), partition_groups=2)
    topo = G.erdos_renyi(seed=0, n=1024, avg_degree=10)
    sim = Simulator(topo=topo, n_msgs=1, mode="pushpull", faults=plan,
                    seed=0)
    src = int(np.nonzero(np.asarray(sim.init_state().seen)[:, 0])[0][0])
    res = sim.run(6)
    assert _cross_group_seen(np.asarray(res.state.seen)[:, 0], src) == 0
    res2 = sim.run(14)
    after = _cross_group_seen(np.asarray(res2.state.seen)[:, 0], src)
    assert after > 0, "no cross-partition recovery after heal"
    assert res2.coverage[-1] > 0.99


def test_aligned_partition_isolates_until_heal():
    plan = FaultPlan(partitions=((0, 6),), partition_groups=2)
    topo = build_aligned(seed=0, n=1024, n_slots=10)
    sim = AlignedSimulator(topo=topo, n_msgs=1, mode="pushpull",
                           faults=plan, seed=0)
    seen0 = np.asarray(sim.init_state().seen_w)[0].reshape(-1)
    src = int(np.nonzero(seen0)[0][0])
    lanes = np.arange(128)
    other_l = (lanes % 2) != (src % 2)    # group = peer_id % 2 = lane % 2
    res = sim.run(6)
    assert np.count_nonzero(
        np.asarray(res.state.seen_w)[0][:, other_l]) == 0
    res2 = sim.run(14)
    assert np.count_nonzero(
        np.asarray(res2.state.seen_w)[0][:, other_l]) > 0
    assert res2.coverage[-1] > 0.99


# -- convergence under loss (acceptance: 20% drop still reaches 99%) ---

def test_edges_converges_under_20pct_link_drop():
    plan = FaultPlan(link_drop=0.2, seed=1)
    topo = G.erdos_renyi(seed=0, n=2048, avg_degree=10)
    res = Simulator(topo=topo, n_msgs=8, mode="pushpull", faults=plan,
                    seed=0).run(16)
    assert res.coverage[-1] >= 0.99, res.coverage[-1]
    assert res.redeliveries.sum() > 0   # loss was routed around, at a cost


def test_aligned_converges_under_20pct_link_drop():
    plan = FaultPlan(link_drop=0.2, seed=1)
    topo = build_aligned(seed=0, n=2048, n_slots=10, roll_groups=4)
    res = AlignedSimulator(topo=topo, n_msgs=8, mode="pushpull",
                           faults=plan, seed=0).run(16)
    assert res.coverage[-1] >= 0.99, res.coverage[-1]
    assert res.redeliveries.sum() > 0


def test_delayed_relays_deliver_one_round_late():
    """delay defers, never drops: a pure-push flood with heavy delay
    still reaches every peer (deferred bits re-enter the frontier)."""
    plan = FaultPlan(delay=0.5, seed=2)
    topo = G.erdos_renyi(seed=0, n=512, avg_degree=8)
    slow = Simulator(topo=topo, n_msgs=4, mode="push", faults=plan,
                     seed=0).run(24)
    fast = Simulator(topo=topo, n_msgs=4, mode="push", seed=0).run(24)
    assert slow.coverage[-1] == 1.0
    # delay slows dissemination, measurably
    assert slow.rounds_to(0.99) >= fast.rounds_to(0.99)


def test_crash_and_recovery_schedules():
    plan = FaultPlan(crash=((3, 0.5),), recover=((8, 0.9),), seed=4)
    topo = G.erdos_renyi(seed=0, n=1024, avg_degree=10)
    res = Simulator(topo=topo, n_msgs=4, mode="pushpull", faults=plan,
                    seed=0).run(14)
    live = res.live_peers
    assert live[3] < 700, "crash schedule did not fire"       # ~50% die
    assert live[-1] > live[3] + 200, "recovery schedule did not fire"
    # the aligned engine honors the same schedule shape
    atopo = build_aligned(seed=0, n=1024, n_slots=10)
    ares = AlignedSimulator(topo=atopo, n_msgs=4, mode="pushpull",
                            faults=plan, seed=0).run(14)
    assert ares.live_peers[3] < 700
    assert ares.live_peers[-1] > ares.live_peers[3] + 200


# -- sharded parity (acceptance: bitwise sharded vs unsharded) ---------

def test_aligned_sharded_bitwise_parity_under_faults(devices8):
    from p2p_gossipprotocol_tpu.parallel import (AlignedShardedSimulator,
                                                 make_mesh)

    plan = _full_plan()
    kw = dict(n_msgs=32, mode="pushpull", faults=plan, seed=1)
    topo = build_aligned(seed=0, n=1024, n_slots=6, n_shards=4,
                         roll_groups=3, n_msgs=32)
    un = AlignedSimulator(topo=topo, **kw).run(10)
    sh = AlignedShardedSimulator(topo=topo, mesh=make_mesh(4), **kw).run(10)
    assert (np.asarray(un.state.seen_w)
            == np.asarray(sh.state.seen_w)).all()
    assert (np.asarray(un.state.alive_b)
            == np.asarray(sh.state.alive_b)).all()
    np.testing.assert_allclose(un.coverage, sh.coverage, rtol=1e-6)
    np.testing.assert_allclose(un.redeliveries, sh.redeliveries,
                               rtol=1e-6)


def test_aligned_2d_bitwise_parity_under_faults(devices8):
    from p2p_gossipprotocol_tpu.parallel import (Aligned2DShardedSimulator,
                                                 make_mesh_2d)

    plan = _full_plan()
    kw = dict(n_msgs=64, mode="pushpull", faults=plan, seed=1)
    topo = build_aligned(seed=0, n=1024, n_slots=6, n_shards=4,
                         roll_groups=3, n_msgs=64)
    un = AlignedSimulator(topo=topo, **kw).run(8)
    s2 = Aligned2DShardedSimulator(topo=topo, mesh=make_mesh_2d(2, 4),
                                   **kw).run(8)
    assert (np.asarray(un.state.seen_w)
            == np.asarray(s2.state.seen_w)).all()
    np.testing.assert_allclose(un.coverage, s2.coverage, rtol=1e-6)


def test_edges_sharded_shard_count_invariance_under_faults(devices8):
    from p2p_gossipprotocol_tpu.parallel import ShardedSimulator, make_mesh

    plan = _full_plan()
    topo = G.erdos_renyi(seed=0, n=512, avg_degree=8)
    kw = dict(n_msgs=8, mode="pushpull", faults=plan, seed=1)
    e1 = ShardedSimulator(topo=topo, mesh=make_mesh(1), **kw).run(10)
    e8 = ShardedSimulator(topo=topo, mesh=make_mesh(8), **kw).run(10)
    assert (np.asarray(e1.state.seen) == np.asarray(e8.state.seen)).all()
    np.testing.assert_allclose(e1.coverage, e8.coverage, rtol=1e-6)
    np.testing.assert_allclose(e1.redeliveries, e8.redeliveries,
                               rtol=1e-6)


# -- kernel fault gate ------------------------------------------------

def test_kernel_fault_gate_identity_and_full_drop():
    """threshold 0 == the unfaulted pass bit-for-bit; threshold 2^31-1
    drops every link (the receive words go dark)."""
    import jax.numpy as jnp

    from p2p_gossipprotocol_tpu.ops.aligned_kernel import (LANES,
                                                           gossip_pass)

    topo = build_aligned(seed=0, n=512, n_slots=4, rowblk=2)
    R = topo.rows
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.integers(-2**31, 2**31, size=(1, R, LANES)),
                    jnp.int32)
    gbase = jnp.arange(R, dtype=jnp.int32)[::topo.rowblk]
    base = gossip_pass(y, topo.colidx, topo.deg, topo.rolls,
                       topo.subrolls, rowblk=topo.rowblk, interpret=True)
    for thresh, expect in ((0, "same"), (2**31 - 1, "dark")):
        meta = jnp.array([3, 42, thresh, 0, 0], jnp.int32)
        out = gossip_pass(y, topo.colidx, topo.deg, topo.rolls,
                          topo.subrolls, fault_meta=meta, gbase=gbase,
                          rowblk=topo.rowblk, interpret=True)
        if expect == "same":
            assert (np.asarray(out) == np.asarray(base)).all()
        else:
            assert np.count_nonzero(np.asarray(out)) == 0


def test_fault_keep_hash_statistics():
    """The in-register keep hash is a fair Bernoulli: at threshold p the
    keep fraction lands near 1-p (the jnp ground-truth twin)."""
    import jax.numpy as jnp

    from p2p_gossipprotocol_tpu.ops.aligned_kernel import fault_keep

    grows = jnp.arange(64)
    for p in (0.1, 0.5):
        thresh = int(p * 2**31)
        frac = float(fault_keep(grows, 8, 3, 42, thresh).mean())
        assert abs(frac - (1 - p)) < 0.01, (p, frac)


# -- surfaces ----------------------------------------------------------

def test_degradation_summary():
    from p2p_gossipprotocol_tpu.utils import metrics

    plan = FaultPlan(link_drop=0.2, crash=((3, 0.3),), seed=1)
    topo = G.erdos_renyi(seed=0, n=1024, avg_degree=10)
    res = Simulator(topo=topo, n_msgs=4, mode="pushpull", faults=plan,
                    seed=0).run(16)
    summ = metrics.degradation_summary(res, target=0.99, plan=plan)
    assert summ["final_coverage"] >= 0.99
    assert summ["rounds_to_0.99"] > 0
    assert summ["total_redeliveries"] > 0
    assert summ["min_live_peers"] < 1024
    assert summ["fault_plan"] == plan.to_spec()


def test_from_config_builds_faulted_engines(tmp_path):
    from p2p_gossipprotocol_tpu.config import NetworkConfig
    from p2p_gossipprotocol_tpu.engines import build_simulator

    cfg = tmp_path / "net.txt"
    cfg.write_text("10.0.0.1:8000\nbackend=jax\nn_peers=512\n"
                   "mode=pushpull\nfault_link_drop=0.2\nfault_seed=3\n")
    sim, engine = build_simulator(NetworkConfig(str(cfg)))
    assert engine == "edges" and sim.faults.link_drop == 0.2
    cfg.write_text("10.0.0.1:8000\nbackend=jax\nn_peers=4096\n"
                   "engine=aligned\nmode=pushpull\n"
                   "fault_link_drop=0.2\nfault_seed=3\n")
    asim, engine = build_simulator(NetworkConfig(str(cfg)))
    assert engine == "aligned" and asim.faults.link_drop == 0.2
    res = asim.run(12)
    assert res.coverage[-1] >= 0.99


# -- the program against the benchmark's plain reference ---------------
# gossipbench/references/aligned_faults.py writes the fault plane from
# the documented rules and imports nothing of the program; the timed
# path (build_simulator -> init_state -> run_to_coverage) must end in
# its state exactly.  The schedule sits early (split 1-4, crash at 1,
# recovery at 3) so that every fault acts in a 16k-peer scenario.

_PLAN_KEYS = {
    "drop": {"fault_link_drop": "0.2"},
    "delay": {"fault_delay": "0.1"},
    "partition": {"fault_partition": "1:4", "fault_partition_groups": "2"},
    "crash_recover": {"fault_crash": "1:0.3", "fault_recover": "3:0.5"},
}
_PLAN_KEYS["full"] = {k: v for kv in _PLAN_KEYS.values() for k, v in kv.items()}
#: W=1 on the row-perm overlay; W=4 on the fused block-perm overlay
_LAYOUTS = {"w1_rowperm": (32, False), "w4_fused": (128, True)}


def _cell(tmp_path, n_peers, n_msgs, plan_keys, fault_seed=0):
    """A faulted deployment as the benchmark holds one: dht-16m's keys
    at ``n_peers``, the plan, burst traffic.  Returns (kv, config)."""
    import os

    from gossipbench import ROOT, spec
    from p2p_gossipprotocol_tpu.config import NetworkConfig

    with open(os.path.join(ROOT, "gossipbench/configs/dht-16m.txt")) as f:
        text = f.read().replace("n_peers=16777216", f"n_peers={n_peers}")
    keys = {**plan_keys, "fault_seed": str(fault_seed),
            "n_messages": str(n_msgs), "message_stagger": "0",
            "coverage_target": "0.85", "max_rounds": "32"}
    path = tmp_path / "cell.txt"
    path.write_text(text + "".join(f"{k}={v}\n" for k, v in keys.items()))
    return spec.read_kv(str(path)), NetworkConfig(str(path))


def _against_reference(kv, cfg, seed):
    import jax.numpy as jnp

    from gossipbench import check, window
    from gossipbench.references import aligned_faults as ref
    from p2p_gossipprotocol_tpu.aligned import aligned_coverage
    from p2p_gossipprotocol_tpu.engines import build_simulator

    sim, engine = build_simulator(cfg)
    assert engine == "aligned" and sim.faults is not None
    key = window.scenario_key(seed, 0)
    st = sim.init_state().replace(key=jnp.asarray(key))
    st, tp, rounds, _ = sim.run_to_coverage(
        target=float(kv["coverage_target"]), max_rounds=int(kv["max_rounds"]),
        state=st, warmup=False)
    prog = check.flatten(st, tp, rounds, aligned_coverage(sim, st, tp))
    scen = ref.Scenario(ref.deployment(kv))
    numbers, parts = check.compare(prog, scen.run(key), scen.ov)
    return sim, rounds, numbers, parts


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
@pytest.mark.parametrize("kind", sorted(_PLAN_KEYS))
def test_program_matches_fault_reference(tmp_path, kind, layout):
    n_msgs, fused = _LAYOUTS[layout]
    kv, cfg = _cell(tmp_path, 16384, n_msgs, _PLAN_KEYS[kind])
    sim, rounds, numbers, parts = _against_reference(kv, cfg, 2_400_000_029)
    assert (sim.topo.ytab is not None) == fused
    assert rounds > 3, "the scenario ended before every fault acted"
    assert numbers == {"mismatch": 0, "coverage_gap": 0.0}, parts


def test_skipped_fault_gate_is_not_correct(tmp_path, monkeypatch):
    """A program whose kernels skip the link gate (a zero drop threshold
    and no partition, whatever the plan says) reads not correct through
    the harness, on a tiny faulted cell added to BENCHMARK.json as data."""
    import argparse
    import json
    import os

    from gossipbench import ROOT, run
    from p2p_gossipprotocol_tpu import faults as faults_lib

    real = faults_lib.kernel_meta

    def ungated(plan, round_idx, pass_tag):
        return real(plan, round_idx, pass_tag).at[2:].set(0)

    monkeypatch.setattr(faults_lib, "kernel_meta", ungated)
    root = tmp_path / "bench"
    os.makedirs(root / "gossipbench/configs")
    os.makedirs(root / "gossipbench/traffic")
    with open(os.path.join(ROOT, "gossipbench/configs/dht-16m-faults.txt")) as f:
        conf = f.read().replace("n_peers=16777216", "n_peers=8192")
    (root / "gossipbench/configs/tiny-8k-faults.txt").write_text(conf)
    (root / "gossipbench/traffic/burst64.txt").write_text(
        "n_messages=64\nmessage_stagger=0\ncoverage_target=0.85\n"
        "max_rounds=32\n")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "tiny-8k-faults", "source": "test",
                         "file": "gossipbench/configs/tiny-8k-faults.txt",
                         "reduced": ["n_peers"], "why": "test"})
    b["workloads"].append({"name": "tiny-8k-faults.burst64",
                           "config": "tiny-8k-faults", "traffic": "burst64",
                           "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    args = argparse.Namespace(workload="tiny-8k-faults.burst64",
                              seed=2_400_000_031, seconds=0.5, trace=0)
    line, err = run.execute(args, require_tpu=False,
                            bench_path=str(root / "BENCHMARK.json"))
    assert line["correct"] is False, err
    assert line["checks"]["mismatch"]["value"] > 0


def test_sir_rejects_fault_plan(tmp_path):
    from p2p_gossipprotocol_tpu.config import NetworkConfig
    from p2p_gossipprotocol_tpu.engines import build_simulator

    cfg = tmp_path / "net.txt"
    cfg.write_text("10.0.0.1:8000\nbackend=jax\nn_peers=512\nmode=sir\n"
                   "fault_link_drop=0.2\n")
    with pytest.raises(ValueError, match="gossip modes"):
        build_simulator(NetworkConfig(str(cfg)))


def test_cli_fault_plan_flag(tmp_path):
    """--fault-plan threads the spec end to end: the CLI run completes
    under 20% link drop and reports full coverage (the tier-1 FaultPlan
    smoke the CI satellite asks for)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "p2p_gossipprotocol_tpu.cli",
         os.path.join(repo, "network.txt"), "--backend", "jax",
         "--n-peers", "1024", "--rounds", "16", "--mode", "pushpull",
         "--fault-plan", "drop=0.2,crash=3:0.2,recover=8:0.5,seed=7",
         "--quiet"],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": repo, "JAX_PLATFORMS": "cpu",
             "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "HOME": os.environ.get("HOME", "/root")}, cwd=repo)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert '"final_coverage": 1.0' in proc.stdout, proc.stdout
