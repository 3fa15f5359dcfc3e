"""Hierarchical two-tier exchange (round 11): dense over ICI, frontier
deltas over DCN — BITWISE-IDENTICAL to the flat exchange, because the
hierarchy changes ROUTING only (aligned._frontier_exchange's
hierarchical path + _hier_gather have the argument: every staged
gather/scatter reassembles the exact flat all_gather, and the DCN tier
runs the SAME per-device census and capacity as the flat exchange, so
even the fr_sparse regime diagnostic matches bit-for-bit).

This suite pins that as exact equality of the final state AND every
per-round metric across (hosts x devs) factorizations of the same
device count, crossed with modes x the full fault plane x churn x
byzantine x frontier regimes x 2-D meshes x fleet buckets, plus the
mid-flight elastic migration 2x4 -> 4x2 -> flat.

Budget note: the sharded runs dominate, so the flat pushpull+faults
reference run is computed ONCE (module fixture) and shared."""

import numpy as np
import pytest

import jax

from p2p_gossipprotocol_tpu.aligned import (AlignedSimulator,
                                            build_aligned,
                                            project_exchange,
                                            resolve_hier)
from p2p_gossipprotocol_tpu.faults import FaultPlan
from p2p_gossipprotocol_tpu.liveness import ChurnConfig
from p2p_gossipprotocol_tpu.parallel import (AlignedShardedSimulator,
                                             make_hier_mesh, make_mesh)
from p2p_gossipprotocol_tpu.parallel.aligned_2d import (
    Aligned2DShardedSimulator, make_mesh_2d)
from p2p_gossipprotocol_tpu.parallel.mesh import (HOST_AXIS, PEER_AXIS,
                                                  is_hier_mesh,
                                                  make_survivor_mesh)

STATE_LEAVES = ("seen_w", "frontier_w", "alive_b", "byz_w", "key",
                "round")
METRICS = ("coverage", "deliveries", "frontier_size", "live_peers",
           "evictions", "redeliveries")

KW = dict(n_msgs=8, mode="pushpull",
          churn=ChurnConfig(rate=0.05, kill_round=1),
          byzantine_fraction=0.1, n_honest_msgs=6, max_strikes=2, seed=3)

# the full fault plane: link drops, relay delay (the deferred-bit
# OR-idempotence of the replica update), a partition window, scheduled
# crash + recovery — all inside the 8-round window
PLAN = FaultPlan.parse(
    "drop=0.1,delay=0.1,partition=2:5,crash=3:0.2,recover=6:0.5")
ROUNDS = 8
FR = dict(frontier_mode=1, frontier_threshold=1.0)


@pytest.fixture(scope="module")
def topo8():
    # rowblk=1 -> many row blocks per shard, so rolls, skip remaps and
    # both tiers' scatters cross device AND host boundaries for real
    return build_aligned(seed=5, n=2048, n_slots=6, rowblk=1, n_shards=8)


@pytest.fixture(scope="module")
def flat8(devices8, topo8):
    """THE reference: flat frontier-sparse pushpull under the full
    fault plane on 8 devices — every hier run must equal it bitwise."""
    return AlignedShardedSimulator(
        topo=topo8, mesh=make_mesh(8), **FR,
        **dict(KW, faults=PLAN)).run(ROUNDS)


def mk_hier(topo, hosts, devs, **overrides):
    kw = dict(KW, faults=PLAN, **FR)
    kw.update(overrides)
    return AlignedShardedSimulator(
        topo=topo, mesh=make_hier_mesh(hosts, devs), hier_mode=1, **kw)


def assert_same(a, b, diagnostics=True):
    for k in STATE_LEAVES:
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(getattr(a.state, k))),
            np.asarray(jax.device_get(getattr(b.state, k))), err_msg=k)
    sa, sb = a.state.strikes, b.state.strikes
    assert (sa is None) == (sb is None)
    if sa is not None:
        np.testing.assert_array_equal(np.asarray(jax.device_get(sa)),
                                      np.asarray(jax.device_get(sb)))
    np.testing.assert_array_equal(np.asarray(a.topo.colidx),
                                  np.asarray(b.topo.colidx))
    for k in METRICS:
        np.testing.assert_array_equal(np.asarray(getattr(a, k)),
                                      np.asarray(getattr(b, k)),
                                      err_msg=k)
    if diagnostics:
        # the DCN tier reads the SAME per-device census and capacity
        # as the flat exchange — its regime trajectory and the worst
        # changed-word series are bitwise flat, not just the state
        for k in ("fr_sparse", "fr_words"):
            np.testing.assert_array_equal(np.asarray(getattr(a, k)),
                                          np.asarray(getattr(b, k)),
                                          err_msg=k)


# ------------------------------------------------------------ mesh unit


def test_make_hier_mesh_shapes(devices8):
    m = make_hier_mesh(2, 4)
    assert m.axis_names == (HOST_AXIS, PEER_AXIS)
    assert m.devices.shape == (2, 4)
    assert is_hier_mesh(m) and not is_hier_mesh(make_mesh(8))
    # host-major flat order: device (h, d) is flat device h*D + d
    flat = make_mesh(8).devices.reshape(-1)
    np.testing.assert_array_equal(m.devices.reshape(-1), flat)
    with pytest.raises(ValueError):
        make_hier_mesh(0, 4)
    with pytest.raises(ValueError):
        make_hier_mesh(4, 400)


def test_survivor_mesh_rederives_hier(devices8):
    """Shrink-to-survivors on a hierarchical job: the survivor set
    forms the host axis, so recovery keeps the two-tier routing."""
    m = make_survivor_mesh(2, 4, hier=True)
    assert is_hier_mesh(m) and m.devices.shape == (2, 4)
    shrunk = make_survivor_mesh(1, 4, hier=True)
    assert is_hier_mesh(shrunk) and shrunk.devices.shape == (1, 4)
    # the degenerate 1-host survivor mesh still runs (two-tier
    # resolves off on it: hier needs > 1 host)
    sim = AlignedShardedSimulator(
        topo=build_aligned(seed=5, n=1024, n_slots=6, rowblk=1,
                           n_shards=4),
        mesh=shrunk, hier_mode=1, n_msgs=8, seed=3)
    assert not sim._hier
    assert not is_hier_mesh(make_survivor_mesh(2, 4))


def test_resolve_hier_clamps():
    clamps = []
    assert resolve_hier(2, 0, 8, clamps) == (2, 4) and not clamps
    assert resolve_hier(2, 4, 8, clamps) == (2, 4) and not clamps
    assert resolve_hier(3, 0, 8, clamps) == (0, 0)
    assert "does not factorize" in clamps[-1]
    assert resolve_hier(2, 3, 8, clamps) == (0, 0)
    assert resolve_hier(2, 0, 1, clamps) == (0, 0)
    assert "single-device" in clamps[-1]
    assert resolve_hier(0, 4, 8, clamps) == (0, 0)
    assert "without hier_hosts" in clamps[-1]
    assert resolve_hier(0, 0, 8, []) == (0, 0)


def test_hier_mode_validation(topo8):
    with pytest.raises(ValueError):
        AlignedSimulator(topo=topo8, hier_mode=2, **KW)
    with pytest.raises(ValueError):
        AlignedSimulator(topo=topo8, hier_hosts=-1, **KW)


# ------------------------------------------------- factorization parity


@pytest.mark.parametrize("hosts,devs", [(2, 4), (4, 2)])
def test_hier_equals_flat(flat8, devices8, topo8, hosts, devs):
    """THE round-11 contract: every (hosts x devs) factorization of
    the same 8 devices — two-tier exchange ON — is bitwise the flat
    run: state, every metric, and the DCN regime/census diagnostics."""
    hier = mk_hier(topo8, hosts, devs).run(ROUNDS)
    assert_same(flat8, hier)
    # the switch really flipped on BOTH tiers (threshold=1.0 engages
    # sparse from round 1 after the hysteresis entry round)
    assert hier.fr_sparse[0] == 0 and hier.fr_sparse[1:].sum() > 0
    assert hier.fr_sparse_ici[1:].sum() > 0


def test_hier_equals_flat_8x1(flat8, devices8, topo8):
    """The degenerate every-device-its-own-host factorization: the DCN
    tier carries the whole exchange, the ICI tier is size-1."""
    assert_same(flat8, mk_hier(topo8, 8, 1).run(ROUNDS))


@pytest.mark.parametrize("mode", ["push", "pull"])
def test_hier_other_modes(devices8, topo8, mode):
    """Pure push (no replica carried) and pure pull (replica only) —
    the two degenerate carry layouts, now with regime_ici riding."""
    kw = dict(KW, mode=mode, faults=PLAN)
    flat = AlignedShardedSimulator(topo=topo8, mesh=make_mesh(8), **FR,
                                   **kw).run(ROUNDS)
    hier = AlignedShardedSimulator(topo=topo8, mesh=make_hier_mesh(2, 4),
                                   hier_mode=1, **FR, **kw).run(ROUNDS)
    assert_same(flat, hier)


def test_tight_capacity_forces_dense_tiers(flat8, devices8, topo8):
    """A capacity the peak frontier cannot fit forces dense rounds on
    BOTH tiers (correctness over savings) — still bitwise, and the DCN
    regime still tracks the flat run's (same census, same K)."""
    tight_flat = AlignedShardedSimulator(
        topo=topo8, mesh=make_mesh(8), frontier_mode=1,
        frontier_threshold=0.002, **dict(KW, faults=PLAN)).run(ROUNDS)
    tight = mk_hier(topo8, 2, 4, frontier_mode=1,
                    frontier_threshold=0.002).run(ROUNDS)
    assert_same(tight_flat, tight)
    assert (tight.fr_sparse == 0).any()


def test_hier_off_is_the_flat_exchange(flat8, devices8, topo8):
    """hier_mode=0 on a hierarchical mesh runs the FLAT exchange over
    the factorized axis pair — the routing A/B measure_round11 runs is
    a pure A/B, nothing else differs."""
    off = AlignedShardedSimulator(
        topo=topo8, mesh=make_hier_mesh(2, 4), hier_mode=0, **FR,
        **dict(KW, faults=PLAN))
    assert not off._hier and off._hier_mesh
    assert_same(flat8, off.run(ROUNDS))


def test_hier_dense_path_without_frontier(devices8, topo8):
    """Frontier OFF on a hier mesh: the legacy dense gathers route
    through the staged _hier_gather — pure data movement, bitwise."""
    kw = dict(KW, faults=PLAN)
    flat = AlignedShardedSimulator(topo=topo8, mesh=make_mesh(8),
                                   **kw).run(ROUNDS)
    hier = AlignedShardedSimulator(topo=topo8, mesh=make_hier_mesh(4, 2),
                                   hier_mode=1, **kw).run(ROUNDS)
    assert_same(flat, hier, diagnostics=False)


# ------------------------------------------------------ elastic migrate


def test_midflight_migration_across_factorizations(flat8, devices8,
                                                   topo8):
    """The acceptance migration: a run moves 2x4 -> 4x2 -> flat 8
    mid-flight through the place_state partition hook (the canonical-
    checkpoint seam) and lands bitwise on the uninterrupted flat run —
    hier_* can never enter a checkpoint fingerprint because the
    trajectory provably doesn't depend on it."""
    legs = [(3, lambda: mk_hier(topo8, 2, 4)),
            (3, lambda: mk_hier(topo8, 4, 2)),
            (ROUNDS - 6, lambda: AlignedShardedSimulator(
                topo=topo8, mesh=make_mesh(8), **FR,
                **dict(KW, faults=PLAN)))]
    state, topo, hists = None, None, {k: [] for k in METRICS}
    for rounds, mk in legs:
        eng = mk()
        res = eng.run(rounds,
                      state=None if state is None
                      else eng.place_state(state),
                      topo=topo)
        state, topo = res.state, res.topo
        for k in METRICS:
            hists[k].append(np.asarray(getattr(res, k)))
    for k in STATE_LEAVES:
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(getattr(flat8.state, k))),
            np.asarray(jax.device_get(getattr(state, k))), err_msg=k)
    for k in METRICS:
        np.testing.assert_array_equal(
            np.asarray(getattr(flat8, k)),
            np.concatenate(hists[k]), err_msg=k)


# ------------------------------------------------------------- coverage


def test_run_to_coverage_with_hier(devices8, topo8):
    """Both tiers' hysteresis lives inside the compiled coverage loop
    (the FrontierCarry extra carry now holds regime_ici too)."""
    kw = dict(topo=topo8, **KW)
    st_f, _, rounds_f, _ = AlignedShardedSimulator(
        mesh=make_mesh(8), **FR, **kw).run_to_coverage(
        target=0.9, max_rounds=32, check_every=4)
    st_h, _, rounds_h, _ = AlignedShardedSimulator(
        mesh=make_hier_mesh(2, 4), hier_mode=1, **FR,
        **kw).run_to_coverage(target=0.9, max_rounds=32, check_every=4)
    assert rounds_f == rounds_h
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(st_f.seen_w)),
        np.asarray(jax.device_get(st_h.seen_w)))


# ------------------------------------------------------------------ 2-D


def test_2d_hier_equals_2d_flat(devices8):
    """The msgs x hosts x devs mesh: the peer sub-axes carry the
    two-tier exchange, the msg axis stays exchange-free."""
    topo = build_aligned(seed=5, n=2048, n_slots=6, rowblk=1,
                         n_shards=4, n_msgs=64)
    kw = dict(KW, n_msgs=64, n_honest_msgs=48, faults=PLAN)
    flat = Aligned2DShardedSimulator(topo=topo, mesh=make_mesh_2d(2, 4),
                                     **FR, **kw).run(ROUNDS)
    hier = Aligned2DShardedSimulator(
        topo=topo, mesh=make_mesh_2d(2, 4, n_hosts=2), hier_mode=1,
        **FR, **kw).run(ROUNDS)
    assert_same(flat, hier)
    assert hier.fr_sparse_ici[1:].sum() > 0
    with pytest.raises(ValueError):
        make_mesh_2d(2, 4, n_hosts=3)   # does not factorize peer axis


# ---------------------------------------------------------------- fleet


def test_fleet_signature_splits_hier_statics(topo8):
    """The packer's one-program-per-bucket discipline: resolved hier
    statics ride the signature, so a sweep mixing hier and flat lines
    never shares a bucket."""
    from p2p_gossipprotocol_tpu.fleet.packer import bucket_signature, pack

    flat = AlignedSimulator(topo=topo8, **KW)
    hier = AlignedSimulator(topo=topo8, hier_hosts=2, hier_devs=4,
                            hier_mode=1, **KW)
    assert bucket_signature(flat) != bucket_signature(hier)
    assert len(pack([flat, hier])) == 2
    same = AlignedSimulator(topo=topo8, hier_hosts=2, hier_devs=4,
                            hier_mode=1, **dict(KW, seed=9))
    assert len(pack([hier, same])) == 1   # seeds vary, program doesn't


# --------------------------------------------------------------- config


def test_config_hier_keys_and_clamps(tmp_path, devices8):
    """The config surface end-to-end: hier_* keys parse, a resolvable
    factorization builds the hier engine, and illegal combinations
    degrade to flat with a recorded clamp (the PR 2 precedent), never
    a crash."""
    from p2p_gossipprotocol_tpu.config import ConfigError, NetworkConfig
    from p2p_gossipprotocol_tpu.engines import build_simulator

    def cfg_with(extra):
        p = tmp_path / f"net{abs(hash(extra)) % 997}.txt"
        p.write_text("127.0.0.1:9001\nbackend=jax\nengine=aligned\n"
                     "n_peers=1024\nn_messages=8\nmode=pushpull\n"
                     + extra)
        return NetworkConfig(str(p))

    cfg = cfg_with("mesh_devices=8\nhier_hosts=2\nhier_mode=1\n")
    assert (cfg.hier_hosts, cfg.hier_devs, cfg.hier_mode) == (2, 0, 1)
    clamps = []
    sim, name = build_simulator(cfg, clamps=clamps)
    assert name == "aligned-hier-2x4" and not clamps
    assert sim._hier and sim.n_hosts == 2 and sim.devs_per_host == 4

    clamps = []
    sim, name = build_simulator(
        cfg_with("mesh_devices=8\nhier_hosts=3\n"), clamps=clamps)
    assert name == "aligned-sharded-8"
    assert any("does not factorize" in c for c in clamps)

    clamps = []
    sim, name = build_simulator(cfg_with("hier_hosts=2\n"),
                                clamps=clamps)
    assert name == "aligned"
    assert any("single-device" in c for c in clamps)

    with pytest.raises(ConfigError):
        cfg_with("hier_mode=5\n")
    with pytest.raises(ConfigError):
        cfg_with("hier_hosts=-2\n")
