"""Real-graph sparse engine (realgraph/): ingest -> pack -> SpMV rounds.

The load-bearing contract (PR 19): ``engine=realgraph`` is the edges
engine's bitwise twin on the SAME topology — state, mutated topology,
and every per-round metric — because the packed gather computes the
exact boolean OR ``ops.propagate.edge_or_scatter`` computes, in an
order-independent reduction.  On top of that: the ingest artifact is
torn-write-safe with named CRC errors (the utils.checkpoint
discipline), packing is deterministic with a static compile-reuse
signature, realgraph scenarios batch and serve through the fleet
machinery with zero admission recompiles, and the CLI surface reaches
all of it from a config file alone.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from p2p_gossipprotocol_tpu import graph as G
from p2p_gossipprotocol_tpu.config import ConfigError, NetworkConfig
from p2p_gossipprotocol_tpu.faults import FaultPlan
from p2p_gossipprotocol_tpu.liveness import ChurnConfig
from p2p_gossipprotocol_tpu.realgraph import (GraphFormatError,
                                              RealGraphSimulator,
                                              ingest_edge_list,
                                              load_artifact,
                                              load_graph_file,
                                              pack_signature,
                                              pack_topology, rmat_edges,
                                              shard_partition,
                                              write_artifact,
                                              write_edge_file)
from p2p_gossipprotocol_tpu.sim import Simulator
from p2p_gossipprotocol_tpu.utils.checkpoint import CorruptCheckpoint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STATE_LEAVES = ("seen", "frontier", "alive", "byzantine", "edge_strikes",
                "key", "round")
TOPO_LEAVES = ("src", "dst", "edge_mask", "row_ptr")
METRICS = ("coverage", "deliveries", "frontier_size", "live_peers",
           "evictions", "redeliveries")


def _rmat_topo(n_log2=8, n_edges=2000, seed=1):
    src, dst = rmat_edges(n_log2, n_edges, seed=seed)
    return G._pad_and_build(1 << n_log2, src, dst)


def _assert_bitwise(a, b, what):
    for k in METRICS:
        assert np.array_equal(np.asarray(getattr(a, k)),
                              np.asarray(getattr(b, k))), (what, k)
    for k in STATE_LEAVES:
        assert np.array_equal(
            np.asarray(jax.device_get(getattr(a.state, k))),
            np.asarray(jax.device_get(getattr(b.state, k)))), (
                what, "state." + k)
    for k in TOPO_LEAVES:
        assert np.array_equal(
            np.asarray(jax.device_get(getattr(a.topo, k))),
            np.asarray(jax.device_get(getattr(b.topo, k)))), (
                what, "topo." + k)


# ---------------------------------------------------------------------
# ingest: formats, artifact round-trip, named failure modes
# ---------------------------------------------------------------------

def test_rmat_deterministic_and_in_range():
    a = rmat_edges(8, 4000, seed=7)
    b = rmat_edges(8, 4000, seed=7)
    c = rmat_edges(8, 4000, seed=8)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not (np.array_equal(a[0], c[0]) and np.array_equal(a[1], c[1]))
    assert a[0].shape == (4000,) and a[1].shape == (4000,)
    assert a[0].min() >= 0 and a[0].max() < 256
    assert a[1].min() >= 0 and a[1].max() < 256


@pytest.mark.parametrize("fmt", ["ws", "csv", "snap"])
def test_ingest_round_trip(tmp_path, fmt):
    src, dst = rmat_edges(7, 600, seed=3)
    path = str(tmp_path / f"graph.{fmt}")
    write_edge_file(path, src, dst, fmt=fmt)
    art = str(tmp_path / "art")
    manifest = ingest_edge_list(path, art, fmt=fmt)
    topo, fp, manifest2 = load_artifact(art)
    assert manifest["n_peers"] == topo.n_peers
    assert fp and manifest2["n_edges"] == manifest["n_edges"]
    # the artifact's canonical arrays ARE _pad_and_build's
    ref = G._pad_and_build(topo.n_peers, src, dst)
    for k in TOPO_LEAVES:
        assert np.array_equal(np.asarray(getattr(topo, k)),
                              np.asarray(getattr(ref, k))), k


def test_ingest_auto_sniffs_and_chunks(tmp_path):
    # tiny chunk size forces the carry-over seam between read chunks
    src, dst = rmat_edges(7, 500, seed=4)
    path = str(tmp_path / "graph.csv")
    write_edge_file(path, src, dst, fmt="csv")
    art = str(tmp_path / "art")
    ingest_edge_list(path, art, fmt="auto", chunk_bytes=64)
    topo, _, _ = load_artifact(art)
    ref = G._pad_and_build(topo.n_peers, src, dst)
    assert np.array_equal(np.asarray(topo.src), np.asarray(ref.src))
    assert np.array_equal(np.asarray(topo.dst), np.asarray(ref.dst))


def test_ingest_bad_line_names_line_number(tmp_path):
    path = str(tmp_path / "bad.txt")
    with open(path, "w") as fp:
        fp.write("0 1\n1 2\nnot-an-edge\n")
    with pytest.raises(GraphFormatError, match="line 3"):
        ingest_edge_list(path, str(tmp_path / "art"))


def test_artifact_crc_catches_torn_leaf(tmp_path):
    src, dst = rmat_edges(6, 200, seed=5)
    art = str(tmp_path / "art")
    write_artifact(art, 64, src, dst)
    # corrupt one payload leaf AFTER the manifest committed — the
    # classic torn write the CRC discipline exists for
    victim = os.path.join(art, "dst.npy")
    blob = bytearray(open(victim, "rb").read())
    blob[-1] ^= 0xFF
    open(victim, "wb").write(bytes(blob))
    with pytest.raises(CorruptCheckpoint, match="dst"):
        load_artifact(art)


def test_artifact_missing_leaf_is_named(tmp_path):
    src, dst = rmat_edges(6, 200, seed=5)
    art = str(tmp_path / "art")
    write_artifact(art, 64, src, dst)
    os.remove(os.path.join(art, "deg_in.npy"))
    with pytest.raises(CorruptCheckpoint, match="deg_in"):
        load_artifact(art)


def test_load_graph_file_caches_and_revalidates(tmp_path):
    src, dst = rmat_edges(6, 200, seed=6)
    path = str(tmp_path / "g.txt")
    write_edge_file(path, src, dst)
    t1, fp1, _ = load_graph_file(path)
    assert os.path.isdir(path + ".csr")
    manifest_path = os.path.join(path + ".csr", "graph_manifest.json")
    stat_before = os.stat(manifest_path).st_mtime_ns
    t2, fp2, _ = load_graph_file(path)          # cache hit: no rewrite
    assert fp1 == fp2
    assert os.stat(manifest_path).st_mtime_ns == stat_before
    assert np.array_equal(np.asarray(t1.dst), np.asarray(t2.dst))
    # a touched source re-ingests (size+mtime key on the manifest)
    time.sleep(0.01)
    with open(path, "a") as fp:
        fp.write("0 3\n")
    t3, _, _ = load_graph_file(path)
    assert int(t3.n_edges()) == int(t1.n_edges()) + 1


# ---------------------------------------------------------------------
# pack: determinism, signature stability, coverage, sharding seam
# ---------------------------------------------------------------------

def test_pack_deterministic():
    topo = _rmat_topo()
    a, b = pack_topology(topo), pack_topology(topo)
    assert pack_signature(a) == pack_signature(b)
    for ba, bb in zip(a.blocks, b.blocks):
        for k in ("eid", "src", "vtx", "valid"):
            assert np.array_equal(np.asarray(getattr(ba, k)),
                                  np.asarray(getattr(bb, k)))


def test_pack_signature_is_shape_only():
    # two graphs with the same degree histogram share a signature
    # (compile reuse); the graph CONTENT rides the bucket signature's
    # fingerprint, not the pack signature
    t1 = _rmat_topo(seed=1)
    e = int(t1.n_edges())
    perm = np.random.default_rng(0).permutation(256)
    src = perm[np.asarray(t1.src)[:e]]
    dst = perm[np.asarray(t1.dst)[:e]]
    t2 = G._pad_and_build(256, src, dst)
    s1 = pack_signature(pack_topology(t1))
    s2 = pack_signature(pack_topology(t2))
    assert s1 == s2


def test_pack_covers_every_masked_edge_once():
    topo = _rmat_topo(7, 900, seed=9)
    packed = pack_topology(topo, width_cap=8)   # narrow cap: hubs split
    seen = []
    for b in packed.blocks:
        assert b.width <= 8
        eid, valid = np.asarray(b.eid), np.asarray(b.valid)
        seen.append(eid[valid])
    seen = np.sort(np.concatenate(seen))
    expect = np.nonzero(np.asarray(topo.edge_mask))[0]
    assert np.array_equal(seen, np.sort(expect))


def test_pack_rejects_non_pow2_width():
    with pytest.raises(ValueError, match="power of two"):
        pack_topology(_rmat_topo(), width_cap=48)


def test_shard_partition_balances_edge_work():
    topo = _rmat_topo()
    deg_in = np.zeros(256, np.int64)
    m = np.asarray(topo.edge_mask)
    np.add.at(deg_in, np.asarray(topo.dst)[m], 1)
    bounds = shard_partition(deg_in, 4)
    assert bounds[0] == 0 and bounds[-1] == 256
    assert (np.diff(bounds) >= 0).all()
    per = [int(deg_in[bounds[k]:bounds[k + 1]].sum()) for k in range(4)]
    assert max(per) <= int(deg_in.sum()) // 4 + int(deg_in.max()) + 1


# ---------------------------------------------------------------------
# THE parity contract: realgraph == edges, bitwise, everywhere
# ---------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["push", "pull", "pushpull"])
def test_parity_modes(mode):
    topo = _rmat_topo()
    kw = dict(topo=topo, n_msgs=4, mode=mode, seed=3)
    _assert_bitwise(RealGraphSimulator(**kw).run(12),
                    Simulator(**kw).run(12), mode)


def test_parity_gather_and_scatter_paths():
    topo = _rmat_topo()
    base = dict(topo=topo, n_msgs=4, mode="pushpull", seed=3)
    ref = Simulator(**base).run(12)
    g = RealGraphSimulator(**base, scatter=0)
    s = RealGraphSimulator(**base, scatter=1)
    assert g.transport.use_gather and not s.transport.use_gather
    _assert_bitwise(g.run(12), ref, "gather")
    _assert_bitwise(s.run(12), ref, "scatter")


def test_parity_churn_faults_byzantine_stagger():
    topo = _rmat_topo()
    plan = FaultPlan(link_drop=0.1, crash=((3, 0.2),),
                     recover=((7, 0.5),), seed=11)
    kw = dict(topo=topo, n_msgs=4, mode="pushpull", seed=3,
              churn=ChurnConfig(rate=0.05, revive=0.1),
              byzantine_fraction=0.1, message_stagger=1, faults=plan)
    rg = RealGraphSimulator(**kw)
    assert not rg.transport.use_gather   # dst mutates -> scatter path
    _assert_bitwise(rg.run(12), Simulator(**kw).run(12), "kitchen-sink")


def test_explicit_gather_clamps_on_dst_mutation():
    sim = RealGraphSimulator(topo=_rmat_topo(), n_msgs=4, seed=3,
                             scatter=0, churn=ChurnConfig(rate=0.1))
    assert not sim.transport.use_gather
    assert any("realgraph_scatter" in c for c in sim._clamps)


def test_gather_legal_with_rewire_off():
    # rewire=False makes dst static even under churn — gather stays
    topo = _rmat_topo()
    kw = dict(topo=topo, n_msgs=4, seed=3, rewire=False,
              churn=ChurnConfig(rate=0.1))
    rg = RealGraphSimulator(**kw)
    assert rg.transport.use_gather
    _assert_bitwise(rg.run(10), Simulator(**kw).run(10), "rewire-off")


@pytest.mark.slow
def test_parity_broad_matrix():
    topo = _rmat_topo()
    plans = [None, FaultPlan(link_drop=0.15, seed=2),
             FaultPlan(crash=((2, 0.3),), recover=((6, 0.8),), seed=4)]
    churns = [ChurnConfig(), ChurnConfig(rate=0.08, revive=0.2)]
    for mode in ("push", "pull", "pushpull"):
        for plan in plans:
            for churn in churns:
                kw = dict(topo=topo, n_msgs=6, mode=mode, seed=5,
                          churn=churn, byzantine_fraction=0.12,
                          message_stagger=2, faults=plan)
                _assert_bitwise(
                    RealGraphSimulator(**kw).run(16),
                    Simulator(**kw).run(16),
                    (mode, plan is not None, churn.rate))


def test_sir_from_config_routes_ingested_topology(tmp_path):
    src, dst = rmat_edges(7, 700, seed=3)
    gf = str(tmp_path / "g.txt")
    write_edge_file(gf, src, dst)
    p = tmp_path / "net.txt"
    p.write_text("127.0.0.1:8000\nbackend=jax\nengine=realgraph\n"
                 f"mode=sir\nn_messages=4\ngraph_file={gf}\n")
    from p2p_gossipprotocol_tpu.engines import build_simulator

    sim, engine = build_simulator(NetworkConfig(str(p)))
    assert engine == "realgraph"
    topo, _, _ = load_graph_file(gf)
    assert sim.topo.n_peers == topo.n_peers
    res = sim.run(8)
    assert res.susceptible.shape == (8,)


# ---------------------------------------------------------------------
# frontier regime series + traffic model (the sharded-seam economics)
# ---------------------------------------------------------------------

def test_frontier_regime_series_parity():
    topo = _rmat_topo()
    kw = dict(topo=topo, n_msgs=4, mode="pushpull", seed=3)
    rg = RealGraphSimulator(**kw)
    a = rg.run(16)
    b = Simulator(**kw).run(16)
    sa = rg.frontier_regime_series(np.asarray(a.frontier_size), 4)
    sb = rg.frontier_regime_series(np.asarray(b.frontier_size), 4)
    # the metric is engine-identical, so the regime series is EXACTLY
    # identical — not statistically similar
    assert sa["capacity"] == sb["capacity"] > 0
    assert np.array_equal(sa["worst_delta"], sb["worst_delta"])
    assert np.array_equal(sa["sparse"], sb["sparse"])
    assert sa["sparse_rounds"] == sb["sparse_rounds"]
    assert len(sa["sparse"]) == 16


def test_traffic_model_closed_form():
    rg = RealGraphSimulator(topo=_rmat_topo(), n_msgs=4, seed=3)
    tm = rg.traffic_model(1)
    assert tm["path"] == "gather"
    assert tm["local_total_bytes"] > 0
    tm4 = rg.traffic_model(4, frontier_fill=0.5)
    assert "exchange" in tm4
    bounds = rg.shard_bounds(4)
    assert bounds[0] == 0 and bounds[-1] == rg.topo.n_peers


# ---------------------------------------------------------------------
# fleet + serve: realgraph scenarios batch and serve, zero recompiles
# ---------------------------------------------------------------------

def _graph_cfg(tmp_path, extra=""):
    src, dst = rmat_edges(7, 800, seed=5)
    gf = str(tmp_path / "graph.txt")
    write_edge_file(gf, src, dst)
    p = tmp_path / "net.txt"
    p.write_text("127.0.0.1:8000\nbackend=jax\nn_messages=4\n"
                 f"rounds=24\nprng_seed=1\ngraph_file={gf}\n" + extra)
    return NetworkConfig(str(p)), gf


def test_fleet_bucket_batched_equals_solo():
    from p2p_gossipprotocol_tpu.fleet.engine import bucket_class_for
    from p2p_gossipprotocol_tpu.realgraph.engine import RealGraphBucket

    topo = _rmat_topo(7, 800, seed=5)
    sims = [RealGraphSimulator(topo=topo, n_msgs=4, seed=s,
                               message_stagger=1) for s in range(3)]
    cls = bucket_class_for(sims[0])
    assert cls is RealGraphBucket
    res = cls(sims).run(10)
    for i in range(3):
        solo = RealGraphSimulator(topo=topo, n_msgs=4, seed=i,
                                  message_stagger=1).run(10)
        _assert_bitwise(res.results[i], solo, f"bucket[{i}]")


def test_sweep_routes_graph_file_scenarios(tmp_path):
    from p2p_gossipprotocol_tpu.fleet.packer import (bucket_signature,
                                                     pack)
    from p2p_gossipprotocol_tpu.fleet.spec import build_scenarios

    cfg, gf = _graph_cfg(tmp_path)
    cfg.graph_file = ""            # base stays aligned; lines opt in
    specs = [{"prng_seed": 0, "graph_file": gf},
             {"prng_seed": 1, "graph_file": gf},
             {"prng_seed": 2}]
    scens = build_scenarios(cfg, specs, n_peers=256)
    assert type(scens[0].sim).__name__ == "RealGraphSimulator"
    assert type(scens[2].sim).__name__ == "AlignedSimulator"
    sigs = [bucket_signature(s.sim) for s in scens]
    assert sigs[0] == sigs[1] != sigs[2]
    assert sigs[0][0] == "realgraph"
    assert pack([s.sim for s in scens]) == [[0, 1], [2]]


def test_serve_slot_reuse_and_zero_recompiles(tmp_path):
    from p2p_gossipprotocol_tpu.fleet.spec import build_scenarios
    from p2p_gossipprotocol_tpu.serve import GossipService

    cfg, _gf = _graph_cfg(tmp_path)
    svc = GossipService(cfg, slots=2, target=0.99).start()
    lines = [{"prng_seed": s} for s in range(4)]
    rids = [svc.submit(ov) for ov in lines]
    rows = [svc.result(r, timeout=300) for r in rids]
    for row, ov in zip(rows, lines):
        res = svc.sim_result(row["request"])
        solo = build_scenarios(cfg, [ov])[0].sim.run(row["rounds_run"])
        _assert_bitwise(res, solo, f"serve scenario {ov}")
    st = svc.drain()
    assert st["done"] == 4 and st["failed"] == 0
    # 4 same-graph requests through 2-slot buckets: the service may
    # open a second same-signature bucket under queue pressure, but
    # admission NEVER retraces — same pack signature, same program
    # (the resident-slot contract)
    assert st["admission_recompiles"] == 0
    assert 1 <= st["buckets"] <= 2


# ---------------------------------------------------------------------
# config / engines / checkpoint surface
# ---------------------------------------------------------------------

def test_config_validates_realgraph_keys(tmp_path):
    p = tmp_path / "net.txt"
    p.write_text("127.0.0.1:8000\nbackend=jax\n"
                 "realgraph_pack_width=48\n")
    with pytest.raises(ConfigError, match="realgraph_pack_width"):
        NetworkConfig(str(p))
    p.write_text("127.0.0.1:8000\nbackend=jax\n"
                 "realgraph_format=tsv\n")
    with pytest.raises(ConfigError, match="realgraph_format"):
        NetworkConfig(str(p))


def test_engines_rejects_mesh_for_realgraph(tmp_path):
    cfg, _ = _graph_cfg(tmp_path, extra="engine=realgraph\n")
    from p2p_gossipprotocol_tpu.engines import build_simulator

    with pytest.raises(ValueError, match="single-device"):
        build_simulator(cfg, mesh_devices=2)


def test_from_config_n_peers_conflict(tmp_path):
    cfg, _ = _graph_cfg(tmp_path)
    with pytest.raises(ValueError, match="n_peers"):
        RealGraphSimulator.from_config(cfg, n_peers=999)


def test_graph_identity_enters_fingerprint_not_pack_knobs(tmp_path):
    from p2p_gossipprotocol_tpu.engines import config_keys

    cfg, gf = _graph_cfg(tmp_path)
    keys = config_keys(cfg)
    assert keys["graph_file"] == gf
    # pack width / delivery path are bitwise knobs — deliberately
    # absent from the trajectory identity (analysis/contracts.py)
    assert "realgraph_pack_width" not in keys
    assert "realgraph_scatter" not in keys


def test_checkpoint_family_is_edges():
    from p2p_gossipprotocol_tpu.utils.checkpoint import (_FAMILIES,
                                                         _SCHEDULES)

    assert _FAMILIES["RealGraphSimulator"] == _FAMILIES["Simulator"] \
        == "edges"
    assert _SCHEDULES["RealGraphSimulator"] == \
        _SCHEDULES["Simulator"] == "edges-exact"


def test_edges_checkpoint_resumes_under_realgraph():
    # bidirectional bitwise resume: an edges canonical checkpoint IS a
    # realgraph one (same family, same key schedule, same leaves)
    from p2p_gossipprotocol_tpu.utils import checkpoint as ck

    topo = _rmat_topo(7, 800, seed=5)
    kw = dict(topo=topo, n_msgs=4, mode="pushpull", seed=3)
    edges = Simulator(**kw)
    full = edges.run(12)
    half = edges.run(6)
    rg = RealGraphSimulator(**kw)
    _sim, state, topo2 = ck.from_canonical(
        rg, ck.to_canonical(edges, half.state, half.topo))
    rest = rg.run(6, state=state, topo=topo2)
    for k in STATE_LEAVES:
        assert np.array_equal(
            np.asarray(jax.device_get(getattr(rest.state, k))),
            np.asarray(jax.device_get(getattr(full.state, k)))), k
    # and back: a realgraph canonical restores under edges
    _sim, state_b, topo_b = ck.from_canonical(
        edges, ck.to_canonical(rg, half.state, half.topo))
    rest_b = edges.run(6, state=state_b, topo=topo_b)
    assert np.array_equal(
        np.asarray(jax.device_get(rest_b.state.seen)),
        np.asarray(jax.device_get(full.state.seen)))


# ---------------------------------------------------------------------
# CLI end-to-end: --graph-file, kill/resume, SIGTERM exit 75
# ---------------------------------------------------------------------

def _cli_cmd(net, gf, ck, *extra):
    return [sys.executable, "-m", "p2p_gossipprotocol_tpu.cli",
            str(net), "--quiet", "--graph-file", gf,
            "--checkpoint-dir", ck, *extra]


def _cli_env(kill=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("GOSSIP_CKPT_KILL", None)
    if kill:
        env["GOSSIP_CKPT_KILL"] = kill
    return env


@pytest.fixture()
def cli_graph(tmp_path):
    src, dst = rmat_edges(7, 800, seed=5)
    gf = str(tmp_path / "graph.txt")
    write_edge_file(gf, src, dst)
    net = tmp_path / "net.txt"
    net.write_text("127.0.0.1:9001\nbackend=jax\nn_messages=8\n"
                   "mode=pushpull\nchurn_rate=0.05\nprng_seed=1\n")
    return net, gf, str(tmp_path / "ck")


@pytest.mark.slow
def test_cli_e2e_and_kill_resume(cli_graph):
    net, gf, ck = cli_graph

    def run(*extra, kill=None):
        return subprocess.run(
            _cli_cmd(net, gf, ck, "--rounds", "8",
                     "--checkpoint-every", "2", *extra),
            capture_output=True, text=True, timeout=180,
            env=_cli_env(kill), cwd=REPO)

    clean = run()
    assert clean.returncode == 0, clean.stderr
    ref = json.loads(clean.stdout.strip().splitlines()[-1])
    assert ref["engine"] == "realgraph"

    # SIGKILL mid-manifest-write at round 4, then --resume: the
    # completed run must be bitwise the uninterrupted one
    shutil.rmtree(ck)
    torn = run(kill="manifest:4")
    assert torn.returncode != 0
    resumed = run("--resume")
    assert resumed.returncode == 0, resumed.stderr
    got = json.loads(resumed.stdout.strip().splitlines()[-1])
    assert got["final_coverage"] == ref["final_coverage"]
    assert got["total_deliveries"] == ref["total_deliveries"]


@pytest.mark.slow
def test_cli_sigterm_salvages_and_exits_75(cli_graph):
    net, gf, ck = cli_graph
    from p2p_gossipprotocol_tpu.utils import checkpoint

    p = subprocess.Popen(
        _cli_cmd(net, gf, ck, "--rounds", "600",
                 "--checkpoint-every", "1"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=_cli_env(), cwd=REPO)
    try:
        for _ in range(300):                    # wait for first persist
            if os.path.isdir(ck) and any(
                    f.startswith("manifest") for f in os.listdir(ck)):
                break
            time.sleep(0.2)
        else:
            pytest.fail("no checkpoint appeared before the signal")
        p.send_signal(signal.SIGTERM)
        _, err = p.communicate(timeout=120)
    finally:
        if p.poll() is None:
            p.kill()
    assert p.returncode == checkpoint.EX_RESUMABLE == 75, err
    assert "salvage" in err


# ---------------------------------------------------------------------
# hygiene: the stale sparse/ shell must never come back
# ---------------------------------------------------------------------

def test_no_moduleless_subpackage_dirs():
    """Every directory under the package holds real sources — a dir
    whose only content is __pycache__ is an orphaned shell (the
    pre-PR-19 ``sparse/`` residue) and would shadow imports."""
    pkg = os.path.join(REPO, "p2p_gossipprotocol_tpu")
    for root, dirs, files in os.walk(pkg):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        if root == pkg:
            continue
        assert any(f.endswith((".py", ".cpp", ".hpp", ".txt", ".json",
                               ".md", ".sh")) for f in files), (
            f"module-less package dir: {root}")
