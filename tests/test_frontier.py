"""Frontier-sparse rounds (round 8): the sparse execution path —
in-kernel dead-block skipping + the delta-compressed cross-chip
exchange with its per-chip seen replica and two-regime switch — is
BITWISE-IDENTICAL to the dense path, by seen-set monotonicity
(aligned._frontier_exchange has the argument).  This suite pins that as
exact equality of the final state AND every per-round metric, across
modes x faults x churn x byzantine x sharded/2-D x fleet, plus the
mid-run regime-switch checkpoint-resume contract (FrontierCarry is
derived state — a resume restarts dense and stays bitwise).

Budget note: the sharded runs dominate tier-1 cost here, so the
pushpull+faults dense/sparse pair is computed ONCE (module fixtures)
and shared by every assertion that reads it."""

import numpy as np
import pytest

import jax

from p2p_gossipprotocol_tpu.aligned import (AlignedSimulator,
                                            build_aligned,
                                            frontier_capacity)
from p2p_gossipprotocol_tpu.faults import FaultPlan
from p2p_gossipprotocol_tpu.liveness import ChurnConfig
from p2p_gossipprotocol_tpu.parallel import (AlignedShardedSimulator,
                                             make_mesh)
from p2p_gossipprotocol_tpu.parallel.aligned_2d import (
    Aligned2DShardedSimulator, make_mesh_2d)

STATE_LEAVES = ("seen_w", "frontier_w", "alive_b", "byz_w", "key",
                "round")
METRICS = ("coverage", "deliveries", "frontier_size", "live_peers",
           "evictions", "redeliveries")

KW = dict(n_msgs=8, mode="pushpull",
          churn=ChurnConfig(rate=0.05, kill_round=1),
          byzantine_fraction=0.1, n_honest_msgs=6, max_strikes=2, seed=3)

# the full fault plane in one plan: link drops, relay delay (exercises
# the deferred-bit OR-idempotence of the replica update), a partition
# window, scheduled crash + recovery — all events land within 8 rounds
PLAN = FaultPlan.parse(
    "drop=0.1,delay=0.1,partition=2:5,crash=3:0.2,recover=6:0.5")
ROUNDS = 8


@pytest.fixture(scope="module")
def topo8():
    # rowblk=1 -> many row blocks per shard, so block rolls, the skip
    # remap and the delta scatter all cross shard boundaries for real
    return build_aligned(seed=5, n=2048, n_slots=6, rowblk=1, n_shards=8)


@pytest.fixture(scope="module")
def pair8(devices8, topo8):
    """(dense, sparse) sharded pushpull runs under the full fault
    plane — THE shared pair most sharded assertions read.
    threshold=1.0 makes the sparse regime engage from round 1
    (capacity == local words), so nearly the whole run exercises the
    compacted scatter path."""
    kw = dict(KW, faults=PLAN)
    dense = AlignedShardedSimulator(topo=topo8, mesh=make_mesh(8),
                                    **kw).run(ROUNDS)
    sparse = AlignedShardedSimulator(topo=topo8, mesh=make_mesh(8),
                                     frontier_mode=1,
                                     frontier_threshold=1.0,
                                     **kw).run(ROUNDS)
    return dense, sparse


def assert_same(a, b):
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


# ----------------------------------------------------------------- solo


@pytest.mark.parametrize("mode", ["push", "pushpull"])
def test_solo_block_skip_bitwise(topo8, mode):
    """In-kernel dead-block skipping on the solo engine: gated blocks
    OR in zero, so the run is exact whatever the frontier's width."""
    dense = AlignedSimulator(topo=topo8, **dict(KW, mode=mode)).run(ROUNDS)
    sparse = AlignedSimulator(topo=topo8, frontier_mode=1,
                              **dict(KW, mode=mode)).run(ROUNDS)
    assert_same(dense, sparse)


def test_solo_skip_composes_with_everything(topo8):
    """Skip x fanout x stagger x faults x fuse_update in one scenario —
    the compositions each add kernel operands next to the skip tables."""
    kw = dict(KW, mode="pushpull", fanout=2, message_stagger=2,
              faults=PLAN, fuse_update=True)
    dense = AlignedSimulator(topo=topo8, **kw).run(10)
    sparse = AlignedSimulator(topo=topo8, frontier_mode=1, **kw).run(10)
    assert_same(dense, sparse)


def test_solo_skip_on_block_perm_overlay():
    topo = build_aligned(seed=5, n=2048, n_slots=6, rowblk=1,
                         roll_groups=3, block_perm=True)
    kw = dict(KW, mode="pushpull", fuse_update=True)
    dense = AlignedSimulator(topo=topo, **kw).run(ROUNDS)
    sparse = AlignedSimulator(topo=topo, frontier_mode=1, **kw).run(ROUNDS)
    assert_same(dense, sparse)


def test_frontier_mode_validation(topo8):
    with pytest.raises(ValueError):
        AlignedSimulator(topo=topo8, frontier_mode=2, **KW)
    with pytest.raises(ValueError):
        AlignedSimulator(topo=topo8, frontier_threshold=0.0, **KW)


def test_capacity_is_static_and_aligned():
    assert frontier_capacity(1 / 64, 1 << 20) == (1 << 20) // 64
    assert frontier_capacity(1 / 64, 256) == 128      # floor
    assert frontier_capacity(1.0, 4096) == 4096       # cap at L
    assert frontier_capacity(0.001, 1 << 20) % 128 == 0


# -------------------------------------------------------------- sharded


def test_sharded_delta_bitwise_pushpull_faults(pair8):
    """Delta exchange vs the legacy dense gather under the full fault
    plane + churn + byzantine (the shared pair)."""
    dense, sparse = pair8
    assert_same(dense, sparse)
    # the switch really flipped: round 0 is dense (hysteresis enters
    # AFTER an under-threshold round), the rest ran sparse
    assert sparse.fr_sparse[0] == 0
    assert sparse.fr_sparse[1:].sum() > 0


@pytest.mark.parametrize("mode", ["push", "pull"])
def test_sharded_delta_bitwise_other_modes(devices8, topo8, mode):
    """Pure push (no replica carried at all) and pure pull (replica is
    the only consumer) — the two degenerate carry layouts."""
    kw = dict(KW, mode=mode, faults=PLAN)
    dense = AlignedShardedSimulator(topo=topo8, mesh=make_mesh(8),
                                    **kw).run(ROUNDS)
    sparse = AlignedShardedSimulator(topo=topo8, mesh=make_mesh(8),
                                     frontier_mode=1,
                                     frontier_threshold=1.0,
                                     **kw).run(ROUNDS)
    assert_same(dense, sparse)
    assert sparse.fr_sparse[1:].sum() > 0


def test_sharded_frontier_equals_solo(pair8, topo8):
    """The frontier-sparse sharded engine still computes the SAME
    global function as the unsharded engine (the PR 1-4 contract)."""
    solo = AlignedSimulator(topo=topo8, **dict(KW, faults=PLAN)).run(ROUNDS)
    assert_same(solo, pair8[1])


def test_sharded_shard_count_invariance(devices8, topo8):
    """Bitwise-invariant to the shard count WITH the frontier path on —
    the regime trajectories may differ (the worst-shard signal depends
    on the partitioning) but the simulation cannot."""
    s1 = AlignedShardedSimulator(topo=topo8, mesh=make_mesh(1),
                                 frontier_mode=1, frontier_threshold=1.0,
                                 **dict(KW, faults=PLAN)).run(ROUNDS)
    s8 = AlignedShardedSimulator(topo=topo8, mesh=make_mesh(8),
                                 frontier_mode=1, frontier_threshold=1.0,
                                 **dict(KW, faults=PLAN)).run(ROUNDS)
    assert_same(s1, s8)


def test_tight_capacity_forces_dense_rounds(pair8, devices8, topo8):
    """A capacity the peak frontier cannot fit must force dense rounds
    (correctness over savings) and still land bitwise."""
    tight = AlignedShardedSimulator(topo=topo8, mesh=make_mesh(8),
                                    frontier_mode=1,
                                    frontier_threshold=0.002,
                                    **dict(KW, faults=PLAN)).run(ROUNDS)
    assert_same(pair8[0], tight)
    # K (the 128-word floor) < the peak frontier width -> at least one
    # round was forced dense while the feature was on
    assert (tight.fr_sparse == 0).any()


def test_run_to_coverage_with_frontier(devices8, topo8):
    """The regime hysteresis lives inside the compiled coverage loop
    (build_coverage_loop's extra carry): same rounds, same state."""
    kw = dict(topo=topo8, mesh=make_mesh(8), **KW)
    st_d, _, rounds_d, _ = AlignedShardedSimulator(
        **kw).run_to_coverage(target=0.9, max_rounds=32, check_every=4)
    st_s, _, rounds_s, _ = AlignedShardedSimulator(
        frontier_mode=1, frontier_threshold=1.0,
        **kw).run_to_coverage(target=0.9, max_rounds=32, check_every=4)
    assert rounds_d == rounds_s
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(st_d.seen_w)),
        np.asarray(jax.device_get(st_s.seen_w)))


def test_midrun_regime_switch_checkpoint_resume(pair8, devices8, topo8):
    """A run interrupted AFTER the regime switched sparse resumes
    bitwise — on a fresh sparse engine AND on a dense one (the
    cross-path migration that keeps frontier keys out of checkpoint
    fingerprints): FrontierCarry is derived state, the replica
    re-initializes from the checkpointed seen planes, the regime
    restarts dense, and the trajectory cannot tell."""
    full = pair8[1]
    half = ROUNDS // 2
    mk_sparse = lambda: AlignedShardedSimulator(
        topo=topo8, mesh=make_mesh(8), frontier_mode=1,
        frontier_threshold=1.0, **dict(KW, faults=PLAN))
    first = mk_sparse().run(half)
    assert first.fr_sparse[1:].sum() > 0     # the switch DID happen
    mk_dense = lambda: AlignedShardedSimulator(
        topo=topo8, mesh=make_mesh(8), **dict(KW, faults=PLAN))
    for mk in (mk_sparse, mk_dense):
        eng = mk()                           # fresh engine, no carry
        resumed = eng.run(ROUNDS - half,
                          state=eng.place_state(first.state),
                          topo=first.topo)
        for k in STATE_LEAVES:
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(getattr(full.state, k))),
                np.asarray(jax.device_get(getattr(resumed.state, k))),
                err_msg=k)
        for k in METRICS:
            np.testing.assert_array_equal(
                np.asarray(getattr(full, k))[half:],
                np.asarray(getattr(resumed, k)), err_msg=k)


# ------------------------------------------------------------------ 2-D


def test_2d_delta_bitwise(devices8):
    topo = build_aligned(seed=5, n=2048, n_slots=6, rowblk=1,
                         n_shards=4, n_msgs=64)
    kw = dict(KW, n_msgs=64, n_honest_msgs=48, faults=PLAN)
    dense = Aligned2DShardedSimulator(topo=topo, mesh=make_mesh_2d(2, 4),
                                      **kw).run(ROUNDS)
    sparse = Aligned2DShardedSimulator(topo=topo, mesh=make_mesh_2d(2, 4),
                                       frontier_mode=1,
                                       frontier_threshold=1.0,
                                       **kw).run(ROUNDS)
    assert_same(dense, sparse)
    assert sparse.fr_sparse[1:].sum() > 0


# ---------------------------------------------------------------- fleet


def test_fleet_bucket_with_frontier_skip(topo8):
    """Fleet batching composes with the skip tables (per-scenario
    activity -> batched prefetch operands): every scenario in the
    bucket stays bitwise-identical to its solo frontier run, and the
    packer refuses to mix skip and no-skip scenarios in one bucket."""
    from p2p_gossipprotocol_tpu.fleet import FleetBucket
    from p2p_gossipprotocol_tpu.fleet.packer import pack

    sims = [AlignedSimulator(topo=topo8, frontier_mode=1,
                             **dict(KW, seed=s)) for s in (3, 4)]
    bres = FleetBucket(sims).run(6)
    for i, sim in enumerate(sims):
        solo = sim.run(6)
        res = bres.results[i]
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(solo.state.seen_w)),
            np.asarray(jax.device_get(res.state.seen_w)))
        np.testing.assert_array_equal(np.asarray(solo.coverage),
                                      np.asarray(res.coverage))
    mixed = sims + [AlignedSimulator(topo=topo8, **dict(KW, seed=9))]
    assert len(pack(mixed)) == 2   # skip flag splits the signature
