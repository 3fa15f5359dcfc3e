"""Sparse allreduce for the frontier exchange (round 16): the
recursive-halving execution of the sparse regime
(aligned._halving_allreduce — log2(M) ppermute pairwise merges of the
compacted delta tables) is BITWISE-IDENTICAL to the round-8 table
gather AND to the dense all_gather reference: final state, every
metric, and — stronger — the fr_sparse/fr_words regime series, because
``frontier_algo`` only picks HOW the sparse regime moves its bytes
(the regime predicate, capacity rule, and hysteresis are shared, and a
round whose merged total overflows the capacity falls back to the
gather execution inside the sparse branch).

Budget note (the PR 5/11 rule): the halving-vs-gather sharded pair is
computed ONCE (module fixture) and shared."""

import numpy as np
import pytest

import jax

from p2p_gossipprotocol_tpu.aligned import (AlignedSimulator,
                                            _merge_tables, build_aligned,
                                            frontier_capacity,
                                            halving_steps)
from p2p_gossipprotocol_tpu.faults import FaultPlan
from p2p_gossipprotocol_tpu.liveness import ChurnConfig
from p2p_gossipprotocol_tpu.parallel import (AlignedShardedSimulator,
                                             make_mesh)
from p2p_gossipprotocol_tpu.parallel.aligned_2d import (
    Aligned2DShardedSimulator, make_mesh_2d)
from p2p_gossipprotocol_tpu.parallel.mesh import make_hier_mesh

STATE_LEAVES = ("seen_w", "frontier_w", "alive_b", "byz_w", "key",
                "round")
METRICS = ("coverage", "deliveries", "frontier_size", "live_peers",
           "evictions", "redeliveries")

# message_stagger keeps the post-peak frontier tiny-but-nonzero for
# many rounds, so the butterfly runs with REAL table content (not just
# empty merges after convergence); the fault plan covers the full
# plane like test_frontier's
PLAN = FaultPlan.parse(
    "drop=0.1,delay=0.1,partition=2:5,crash=3:0.2,recover=6:0.5")
KW = dict(n_msgs=8, mode="pushpull",
          churn=ChurnConfig(rate=0.05, kill_round=1),
          byzantine_fraction=0.1, n_honest_msgs=6, max_strikes=2,
          message_stagger=2, seed=3, faults=PLAN)
ROUNDS = 14


@pytest.fixture(scope="module")
def topo8():
    # rowblk=1 -> block rolls, skip remaps and the delta scatter all
    # cross shard boundaries for real (the test_frontier overlay)
    return build_aligned(seed=5, n=2048, n_slots=6, rowblk=1, n_shards=8)


def _sharded(topo, algo, mesh=None, **over):
    kw = {"frontier_threshold": 1.0, **KW, **over}
    return AlignedShardedSimulator(
        topo=topo, mesh=make_mesh(8) if mesh is None else mesh,
        frontier_mode=1, frontier_algo=algo, **kw)


@pytest.fixture(scope="module")
def pair8(devices8, topo8):
    """(gather, halving) sharded pushpull runs under the full fault
    plane — THE shared pair most assertions read.  threshold=1.0
    engages the sparse regime early; the stagger tail keeps merged
    totals under capacity so the butterfly genuinely executes."""
    return (_sharded(topo8, 0).run(ROUNDS),
            _sharded(topo8, 1).run(ROUNDS))


def assert_same(a, b, regime=True):
    for k in STATE_LEAVES:
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(getattr(a.state, k))),
            np.asarray(jax.device_get(getattr(b.state, k))), err_msg=k)
    sa, sb = a.state.strikes, b.state.strikes
    assert (sa is None) == (sb is None)
    if sa is not None:
        np.testing.assert_array_equal(np.asarray(jax.device_get(sa)),
                                      np.asarray(jax.device_get(sb)))
    for k in METRICS:
        np.testing.assert_array_equal(np.asarray(getattr(a, k)),
                                      np.asarray(getattr(b, k)),
                                      err_msg=k)
    if regime:
        # the regime SERIES is part of the round-16 contract: halving
        # never perturbs when the sparse regime runs, only how
        np.testing.assert_array_equal(a.fr_sparse, b.fr_sparse)
        np.testing.assert_array_equal(a.fr_words, b.fr_words)


# ------------------------------------------------------------ the merge


def test_merge_tables_sorted_or_combine():
    """One butterfly step's reduction: sorted-index union, OR-combine
    of duplicate indices, invalid slots dropped, count exact."""
    ia = np.array([3, 9, 7, 7], np.int32)    # slots >= count are junk
    va = np.array([1, 2, 9, 9], np.int32)
    ib = np.array([1, 9, 12, 7], np.int32)
    vb = np.array([4, 8, 16, 9], np.int32)
    oi, ov, cnt = _merge_tables(ia, va, np.int32(2), ib, vb, np.int32(3),
                                4)
    assert int(cnt) == 4
    np.testing.assert_array_equal(np.asarray(oi), [1, 3, 9, 12])
    np.testing.assert_array_equal(np.asarray(ov), [4, 1, 2 | 8, 16])


def test_merge_tables_empty_inputs():
    z = np.zeros(4, np.int32)
    oi, ov, cnt = _merge_tables(z, z, np.int32(0), z, z, np.int32(0), 4)
    assert int(cnt) == 0
    ia = np.array([5, 0, 0, 0], np.int32)
    va = np.array([3, 0, 0, 0], np.int32)
    oi, ov, cnt = _merge_tables(ia, va, np.int32(1), z, z, np.int32(0), 4)
    assert int(cnt) == 1 and int(oi[0]) == 5 and int(ov[0]) == 3


def test_halving_steps_rule():
    assert halving_steps(1) == 0
    assert halving_steps(2) == 1
    assert halving_steps(8) == 3
    assert halving_steps(64) == 6
    assert halving_steps(6) is None and halving_steps(12) is None


def test_frontier_algo_validation(topo8):
    with pytest.raises(ValueError):
        AlignedSimulator(topo=topo8, frontier_algo=2,
                         **dict(KW, faults=None))


# -------------------------------------------------------------- sharded


def test_sharded_halving_bitwise_pushpull_faults(pair8):
    """Halving vs gather under the full fault plane + churn + byz +
    stagger — state, metrics AND the regime series, bit for bit."""
    gather, halving = pair8
    assert_same(gather, halving)
    # the butterfly genuinely ran (sparse rounds whose merged total
    # fit the capacity), and the gather run never set the flag
    assert gather.fr_halving.sum() == 0
    assert halving.fr_halving.sum() > 0
    # ... with real content: at least one halving round merged a
    # nonzero frontier (fr_words > 0 -> non-empty tables crossed)
    assert ((halving.fr_halving != 0)
            & (np.asarray(halving.fr_words) > 0)).any()


def test_sharded_halving_equals_dense_reference(devices8, topo8):
    """halving == the dense all_gather(seen) reference (frontier off),
    the acceptance chain's third leg.  No stagger here: the dense
    path's coverage denominator under stagger differs on the frontier
    path for BOTH algos (pre-existing, algo-independent)."""
    kw = dict(KW, message_stagger=0)
    dense = AlignedShardedSimulator(topo=topo8, mesh=make_mesh(8),
                                    **kw).run(ROUNDS)
    halving = AlignedShardedSimulator(
        topo=topo8, mesh=make_mesh(8), frontier_mode=1,
        frontier_threshold=1.0, frontier_algo=1, **kw).run(ROUNDS)
    assert_same(dense, halving, regime=False)


def test_sharded_halving_overflow_falls_back_to_gather(devices8, topo8):
    """A sparse round whose MERGED total overflows the shared capacity
    must execute by gather inside the sparse regime (fr_sparse == 1,
    fr_halving == 0) — correctness over savings, and the regime series
    still bitwise the gather run's."""
    # tight capacity: the 128-word floor. Early rounds run sparse with
    # per-shard changed <= K but merged total > K -> the fallback path.
    tight_g = _sharded(topo8, 0, frontier_threshold=0.002).run(ROUNDS)
    tight_h = _sharded(topo8, 1, frontier_threshold=0.002).run(ROUNDS)
    assert_same(tight_g, tight_h)
    fs = np.asarray(tight_h.fr_sparse) != 0
    fh = np.asarray(tight_h.fr_halving) != 0
    assert (fs & ~fh).any()          # sparse round executed by gather
    # capacity overflow still forces DENSE exactly like today (worst
    # shard beyond K): at least one on-regime round ran dense
    assert (~fs).any()


@pytest.mark.parametrize("mode", ["push", "pull"])
def test_sharded_halving_other_modes(devices8, topo8, mode):
    """Pure push (no replica carried) and pure pull (replica the only
    consumer) — the degenerate carry layouts.  Slow: the pushpull
    fixture pair covers the shared-path plumbing in tier-1."""
    kw = dict(mode=mode)
    gather = _sharded(topo8, 0, **kw).run(ROUNDS)
    halving = _sharded(topo8, 1, **kw).run(ROUNDS)
    assert_same(gather, halving)
    assert halving.fr_halving.sum() > 0


def test_sharded_shard_count_invariance_with_halving(devices8, topo8):
    """Bitwise-invariant to the shard count with halving on: M=1 is
    the structural no-butterfly degenerate, M=8 the real one."""
    s1 = _sharded(topo8, 1, mesh=make_mesh(1)).run(ROUNDS)
    s8 = _sharded(topo8, 1, mesh=make_mesh(8)).run(ROUNDS)
    assert_same(s1, s8, regime=False)    # regime signal is per-shard
    assert s1.fr_halving.sum() == 0      # M=1: nothing to exchange


def test_non_power_of_two_axis_keeps_gather(devices8):
    """A 6-shard mesh cannot tile the butterfly: frontier_algo=1 runs,
    bitwise the gather, with fr_halving pinned to zero (the structural
    fallback the from_config clamp records)."""
    topo = build_aligned(seed=5, n=1536, n_slots=6, rowblk=1, n_shards=6)
    kw = dict(KW, faults=None)
    gather = AlignedShardedSimulator(
        topo=topo, mesh=make_mesh(6), frontier_mode=1,
        frontier_threshold=1.0, frontier_algo=0, **kw).run(ROUNDS)
    halving = AlignedShardedSimulator(
        topo=topo, mesh=make_mesh(6), frontier_mode=1,
        frontier_threshold=1.0, frontier_algo=1, **kw).run(ROUNDS)
    assert_same(gather, halving)
    assert halving.fr_halving.sum() == 0


def test_midrun_switch_resume_both_directions(pair8, devices8, topo8):
    """A run interrupted after the regime switched resumes bitwise on
    a HALVING engine from a gather-written half, and on a GATHER
    engine from a halving-written half — the cross-execution migration
    that keeps frontier_algo out of checkpoint fingerprints."""
    full = pair8[1]
    half = ROUNDS // 2
    first_g = _sharded(topo8, 0).run(half)
    first_h = _sharded(topo8, 1).run(half)
    assert first_h.fr_sparse[1:].sum() > 0        # the switch happened
    for first, algo in ((first_g, 1), (first_h, 0)):
        eng = _sharded(topo8, algo)               # fresh engine
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


# ----------------------------------------------------------------- hier


def test_hier_halving_bitwise_2x4(devices8, topo8):
    """Both tiers take the butterfly independently on the 2x4 hier
    mesh (DCN at H=2 degenerates to one pairwise exchange — the
    butterfly IS the gather there — while ICI at D=4 runs 2 steps):
    bitwise the gather-execution hier run, regime series of both tiers
    included."""
    mk = lambda algo: AlignedShardedSimulator(
        topo=topo8, mesh=make_hier_mesh(2, 4), hier_mode=1,
        frontier_mode=1, frontier_threshold=1.0, frontier_algo=algo,
        **KW)
    gather = mk(0).run(ROUNDS)
    halving = mk(1).run(ROUNDS)
    assert_same(gather, halving)
    np.testing.assert_array_equal(gather.fr_sparse_ici,
                                  halving.fr_sparse_ici)
    assert halving.fr_halving.sum() > 0
    assert halving.fr_halving_ici.sum() > 0


def test_hier_halving_bitwise_4x2_equals_flat(devices8, topo8):
    """The other factorization, anchored to the FLAT halving run (the
    hier == flat contract composed with the algo contract)."""
    flat = _sharded(topo8, 1).run(ROUNDS)
    hier = AlignedShardedSimulator(
        topo=topo8, mesh=make_hier_mesh(4, 2), hier_mode=1,
        frontier_mode=1, frontier_threshold=1.0, frontier_algo=1,
        **KW).run(ROUNDS)
    assert_same(flat, hier)


# ------------------------------------------------------------------ 2-D


def test_2d_halving_bitwise(devices8):
    """The 2-D engine's butterfly runs per message shard over the peer
    axis, fit census reduced over BOTH axes."""
    topo = build_aligned(seed=5, n=2048, n_slots=6, rowblk=1,
                         n_shards=4, n_msgs=64)
    kw = dict(KW, n_msgs=64, n_honest_msgs=48)
    mk = lambda algo: Aligned2DShardedSimulator(
        topo=topo, mesh=make_mesh_2d(2, 4), frontier_mode=1,
        frontier_threshold=1.0, frontier_algo=algo, **kw)
    gather = mk(0).run(ROUNDS)
    halving = mk(1).run(ROUNDS)
    assert_same(gather, halving)
    assert halving.fr_halving.sum() > 0


# ---------------------------------------------- resolution and packing


def test_run_to_coverage_with_halving(devices8, topo8):
    """The fit census and nested conditional live inside the compiled
    coverage loop: same rounds, same state as the gather execution."""
    kw = dict(KW, faults=None, message_stagger=0)
    st_g, _, rounds_g, _ = AlignedShardedSimulator(
        topo=topo8, mesh=make_mesh(8), frontier_mode=1,
        frontier_threshold=1.0, **kw).run_to_coverage(
            target=0.9, max_rounds=32, check_every=4)
    st_h, _, rounds_h, _ = AlignedShardedSimulator(
        topo=topo8, mesh=make_mesh(8), frontier_mode=1,
        frontier_threshold=1.0, frontier_algo=1, **kw).run_to_coverage(
            target=0.9, max_rounds=32, check_every=4)
    assert rounds_g == rounds_h
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(st_g.seen_w)),
        np.asarray(jax.device_get(st_h.seen_w)))


def test_packer_signature_carries_algo(topo8):
    """Scenarios with different resolved frontier_algo never share a
    compiled bucket (the zero-admission-recompile discipline the
    serve router inherits from bucket_signature)."""
    from p2p_gossipprotocol_tpu.fleet.packer import pack

    kw = dict(KW, faults=None)
    sims = [AlignedSimulator(topo=topo8, frontier_mode=1,
                             frontier_algo=a, **kw) for a in (0, 1, 1)]
    assert len(pack(sims)) == 2


def test_from_config_resolves_and_clamps(tmp_path):
    """The config surface: -1 auto resolves by its rule (gather under
    interpret); an explicit 1 on a
    non-power-of-two shard count is recorded, never silent."""
    from p2p_gossipprotocol_tpu.config import NetworkConfig

    def cfg(extra=""):
        p = tmp_path / f"net{len(extra)}.txt"
        p.write_text("10.0.0.1:9000\nbackend=jax\nengine=aligned\n"
                     "n_peers=4096\n" + extra)
        return NetworkConfig(str(p))

    clamps = []
    sim = AlignedSimulator.from_config(cfg(), n_peers=4096, n_shards=8,
                                       clamps=clamps)
    assert sim.frontier_algo == 0 and sim._frontier_algo is False
    clamps = []
    sim = AlignedSimulator.from_config(cfg("frontier_algo=1\n"),
                                       n_peers=4096, n_shards=8,
                                       clamps=clamps)
    assert sim.frontier_algo == 1 and sim._frontier_algo is True
    assert not clamps
    clamps = []
    AlignedSimulator.from_config(cfg("frontier_algo=1\nfanout=0\n"),
                                 n_peers=6144, n_shards=6,
                                 clamps=clamps)
    assert any("non-power-of-two" in c for c in clamps)
