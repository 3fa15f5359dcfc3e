"""Hardware-aligned engine tests.

The pallas kernel runs in interpret mode on the CPU test mesh; its output
is checked EXACTLY against a numpy evaluation of the composite neighbor
map (the ground truth the overlay family is defined by), and the engine's
dissemination behavior is validated statistically against the exact
edge-list engine on a comparable random graph.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_gossipprotocol_tpu import graph
from p2p_gossipprotocol_tpu.aligned import (AlignedSimulator, build_aligned)
from p2p_gossipprotocol_tpu.ops.aligned_kernel import LANES, gossip_pass
from p2p_gossipprotocol_tpu.sim import Simulator


def _numpy_pass(y, colidx, gate, rolls, subrolls, rowblk, pull):
    """Ground-truth OR-accumulation over slots (y is [W, R, C])."""
    W, R, C = y.shape
    D = colidx.shape[0]
    blk = min(rowblk, R)
    T = R // blk
    acc = np.zeros((W, R, C), np.int32)
    r = np.arange(R)
    for d in range(D):
        src_row = (((r // blk + rolls[d]) % T) * blk
                   + (r % blk + subrolls[d]) % blk)
        mask = (gate == d) if pull else (d < gate)
        for w in range(W):
            z = y[w][src_row[:, None], colidx[d].astype(np.int64)]
            acc[w] |= np.where(mask, z, 0)
    return acc


@pytest.fixture(scope="module")
def small_tables():
    rng = np.random.default_rng(3)
    R, D, W = 16, 5, 3   # multi-word: 3 message planes
    y = rng.integers(0, 2**31, size=(W, R, LANES), dtype=np.int32)
    colidx = rng.integers(0, LANES, size=(D, R, LANES), dtype=np.int8)
    deg = rng.integers(0, D + 1, size=(R, LANES), dtype=np.int8)
    rolls = rng.integers(0, 2, size=D, dtype=np.int32)  # T = 2 for blk=8
    subrolls = rng.integers(0, 8, size=D, dtype=np.int32)
    return y, colidx, deg, rolls, subrolls


def test_push_pass_matches_ground_truth(small_tables):
    y, colidx, deg, rolls, subrolls = small_tables
    out = gossip_pass(jnp.asarray(y), jnp.asarray(colidx), jnp.asarray(deg),
                      jnp.asarray(rolls), jnp.asarray(subrolls),
                      pull=False, rowblk=8, interpret=True)
    ref = _numpy_pass(y, colidx, deg, rolls, subrolls, rowblk=8,
                      pull=False)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_pull_pass_matches_ground_truth(small_tables):
    y, colidx, _, rolls, subrolls = small_tables
    rng = np.random.default_rng(7)
    delta = rng.integers(0, 6, size=y.shape[1:], dtype=np.int8)
    out = gossip_pass(jnp.asarray(y), jnp.asarray(colidx),
                      jnp.asarray(delta), jnp.asarray(rolls),
                      jnp.asarray(subrolls), pull=True,
                      rowblk=8, interpret=True)
    ref = _numpy_pass(y, colidx, delta, rolls, subrolls, rowblk=8,
                      pull=True)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_neighbor_ids_consistent_with_pass(small_tables):
    """gossip_pass over perm-gathered words == direct gather over the
    exported neighbor map — the interop bridge must match the kernel
    EXACTLY, not just in shape."""
    y, colidx, deg, rolls, subrolls = small_tables
    from p2p_gossipprotocol_tpu.ops.aligned_kernel import neighbor_ids
    perm = np.random.default_rng(0).permutation(16).astype(np.int32)
    nbr = np.asarray(neighbor_ids(jnp.asarray(perm), jnp.asarray(rolls),
                                  jnp.asarray(subrolls),
                                  jnp.asarray(colidx), rowblk=8))
    assert nbr.shape == (5, 16, LANES)
    assert nbr.min() >= 0 and nbr.max() < 16 * LANES

    out = np.asarray(gossip_pass(
        jnp.asarray(y[:, perm]), jnp.asarray(colidx), jnp.asarray(deg),
        jnp.asarray(rolls), jnp.asarray(subrolls), pull=False, rowblk=8,
        interpret=True))
    ref = np.zeros_like(out)
    for w in range(y.shape[0]):
        flat = y[w].reshape(-1)
        for d in range(nbr.shape[0]):
            ref[w] |= np.where(d < deg, flat[nbr[d]], 0)
    np.testing.assert_array_equal(out, ref)


def test_flood_reaches_everyone():
    topo = build_aligned(seed=1, n=1024, n_slots=6)
    sim = AlignedSimulator(topo=topo, n_msgs=4, mode="push", seed=0)
    res = sim.run(12)
    assert res.coverage[-1] == pytest.approx(1.0)
    # flood-once: frontier empties once everyone has everything
    assert res.frontier_size[-1] == 0


def test_pushpull_converges_and_deterministic():
    topo = build_aligned(seed=2, n=1024, n_slots=4)
    a = AlignedSimulator(topo=topo, n_msgs=8, mode="pushpull", seed=5)
    b = AlignedSimulator(topo=topo, n_msgs=8, mode="pushpull", seed=5)
    ra = a.run(10)
    rb = b.run(10)
    np.testing.assert_array_equal(ra.coverage, rb.coverage)
    np.testing.assert_array_equal(np.asarray(ra.state.seen_w),
                                  np.asarray(rb.state.seen_w))
    assert ra.coverage[-1] > 0.99


def test_full_32_message_pack_floods():
    """Bit 31 (the int32 sign bit) must seed and propagate like any other
    message — regression for the scatter-max seeding that dropped it."""
    topo = build_aligned(seed=6, n=1024, n_slots=6)
    sim = AlignedSimulator(topo=topo, n_msgs=32, mode="push", seed=0)
    st = sim.init_state()
    seeded = np.asarray(st.seen_w).view(np.uint32)
    popc = np.unpackbits(seeded.view(np.uint8)).sum()
    assert popc == 32  # every message seeded exactly once
    res = sim.run(14)
    assert res.coverage[-1] == pytest.approx(1.0)


def test_powerlaw_degree_law():
    topo = build_aligned(seed=3, n=4096, n_slots=12,
                        degree_law="powerlaw", powerlaw_alpha=2.5)
    deg = np.asarray(topo.deg)
    valid = np.asarray(topo.valid_w) != 0
    assert deg[valid].min() >= 1
    assert deg[valid].max() <= 12
    assert deg[~valid].sum() == 0  # padding peers listen to no one


def test_run_to_coverage_honest_rounds():
    topo = build_aligned(seed=4, n=1024, n_slots=6)
    sim = AlignedSimulator(topo=topo, n_msgs=4, mode="push", seed=0)
    st, _topo, rounds, wall = sim.run_to_coverage(0.99, max_rounds=64)
    assert 0 < rounds < 64
    assert wall > 0
    # agreement with the fixed-round path
    res = sim.run(rounds)
    assert res.coverage[-1] >= 0.99
    assert res.coverage[rounds - 2] < 0.99 if rounds > 1 else True


def test_popcount_pair_exact_at_the_64m_boundary():
    """popcount(alive plane) = 32 bits x peers hits EXACTLY 2^31 at 64M
    peers (R = 524288 rows) — the flat int32 sum wraps to -2^31 there,
    which collapsed n_ok to 1 and reported coverage 8.0 on the 64M
    hardware probe.  The [hi, lo] pair must stay exact."""
    from p2p_gossipprotocol_tpu.aligned import (_pair_int, _popcount_pair,
                                                _popcount_sum)
    R = 524288                       # 64M peers / 128 lanes
    plane = jnp.full((R, 128), -1, jnp.int32)
    assert _pair_int(jax.device_get(_popcount_pair(plane))) == 1 << 31
    # and the flat sum really does wrap (the failure mode being pinned)
    assert int(jax.device_get(_popcount_sum(plane))) == -(1 << 31)


def test_run_to_coverage_check_every_parity():
    """check_every=K runs the SAME rounds in K-chunks: the final state is
    bitwise-identical to the classic per-round loop when convergence
    lands on a chunk boundary, and otherwise overshoots by < K rounds —
    never stops early, never diverges from the deterministic stream."""
    topo = build_aligned(seed=4, n=1024, n_slots=6)
    sim = AlignedSimulator(topo=topo, n_msgs=4, mode="push", seed=0)
    st1, _t1, r1, _w1 = sim.run_to_coverage(0.99, max_rounds=64)
    for k in (2, 3):
        stk, _tk, rk, _wk = sim.run_to_coverage(0.99, max_rounds=64,
                                                check_every=k)
        assert r1 <= rk < r1 + k
        # round rk state must equal the free-running engine at rk
        ref = sim.run(rk)
        assert int(jax.device_get(stk.round)) == rk
        np.testing.assert_array_equal(np.asarray(stk.seen_w),
                                      np.asarray(ref.state.seen_w))
    # max_rounds stays a HARD cap even when it is not a chunk multiple
    st5, _t5, r5, _w5 = sim.run_to_coverage(0.99, max_rounds=r1 - 1,
                                            check_every=3)
    assert r5 == r1 - 1
    with pytest.raises(ValueError):
        sim.run_to_coverage(0.99, check_every=0)


def test_dissemination_matches_exact_engine_statistically():
    """Aligned overlay (regular, avg degree 8) vs exact ER engine with the
    same average degree: rounds-to-99% must agree within a small margin —
    the aligned family's structural correlations must not change the
    dissemination dynamics."""
    n, d = 4096, 8
    topo_a = build_aligned(seed=11, n=n, n_slots=d)
    sim_a = AlignedSimulator(topo=topo_a, n_msgs=8, mode="push", seed=0)
    res_a = sim_a.run(32)
    r_aligned = int(np.argmax(res_a.coverage >= 0.99)) + 1

    topo_e = graph.erdos_renyi(11, n, avg_degree=d)
    sim_e = Simulator(topo=topo_e, n_msgs=8, mode="push", seed=0)
    res = sim_e.run(32)
    r_exact = res.rounds_to(0.99)

    assert abs(r_aligned - r_exact) <= 3, (r_aligned, r_exact)


# ----------------------------------------------------------------------
# Liveness / churn / byzantine (BASELINE config 4 on the scale engine)

def _numpy_liveness(y_alive, colidx, strikes, rand, deg, rolls, subrolls,
                    rowblk, max_strikes):
    """Ground truth for liveness_pass: per-slot neighbor-alive gather,
    strike accumulation, first-crossing eviction, in-row lane rewire."""
    R, C = y_alive.shape
    D = colidx.shape[0]
    blk = min(rowblk, R)
    T = R // blk
    r = np.arange(R)
    col_out = colidx.copy()
    s_out = np.zeros_like(strikes)
    evict_out = np.zeros_like(strikes)
    for d in range(D):
        src_row = (((r // blk + rolls[d]) % T) * blk
                   + (r % blk + subrolls[d]) % blk)
        y = y_alive[src_row]
        nbr_alive = np.take_along_axis(
            y, colidx[d].astype(np.int64), axis=1) != 0
        is_edge = d < deg
        dead_obs = is_edge & ~nbr_alive
        s_new = np.where(dead_obs,
                         np.minimum(strikes[d] + 1, max_strikes + 1), 0)
        evict = s_new >= max_strikes
        cand_alive = np.take_along_axis(
            y, rand[d].astype(np.int64), axis=1) != 0
        take = evict & cand_alive
        col_out[d] = np.where(take, rand[d], colidx[d])
        s_out[d] = np.where(take, 0, s_new)
        evict_out[d] = (s_new == max_strikes).astype(np.int8)
    return col_out, s_out, evict_out


def test_liveness_pass_matches_ground_truth():
    """The kernel's in-register candidate hash must agree with the jnp
    reference (rewire_candidates) and the strike/evict/rewire semantics
    with the numpy ground truth."""
    from p2p_gossipprotocol_tpu.ops.aligned_kernel import (
        liveness_pass, rewire_candidates)

    rng = np.random.default_rng(13)
    R, D, max_strikes = 16, 4, 3
    round_idx, seed = 7, 42
    y_alive = np.where(rng.uniform(size=(R, LANES)) < 0.6, -1,
                       0).astype(np.int32)
    colidx = rng.integers(0, LANES, size=(D, R, LANES), dtype=np.int8)
    strikes = rng.integers(0, max_strikes + 2, size=(D, R, LANES),
                           dtype=np.int8)
    deg = rng.integers(0, D + 1, size=(R, LANES), dtype=np.int8)
    rolls = rng.integers(0, 2, size=D, dtype=np.int32)
    subrolls = rng.integers(0, 8, size=D, dtype=np.int32)
    grows = jnp.arange(R, dtype=jnp.int32)

    col_k, s_k, ev_k = liveness_pass(
        jnp.asarray(y_alive), jnp.asarray(colidx), jnp.asarray(strikes),
        jnp.asarray(deg), jnp.asarray(rolls), jnp.asarray(subrolls),
        gbase=grows[::8], round_idx=round_idx, hash_seed=seed,
        max_strikes=max_strikes, rowblk=8, interpret=True)
    rand = np.asarray(rewire_candidates(grows, D, round_idx, seed))
    assert rand.min() >= 0 and rand.max() < LANES
    assert len(np.unique(rand)) > LANES // 2     # hash actually spreads
    col_n, s_n, ev_n = _numpy_liveness(
        y_alive, colidx, strikes, rand, deg, rolls, subrolls,
        rowblk=8, max_strikes=max_strikes)
    np.testing.assert_array_equal(np.asarray(col_k), col_n)
    np.testing.assert_array_equal(np.asarray(s_k), s_n)
    # evictions: one int32 (8, 128) tile per row block, summing to the
    # plane's count of first crossings
    assert ev_k.shape == (R // 8, 8, LANES) and ev_k.dtype == jnp.int32
    assert int(ev_n.sum()) > 0
    assert int(jnp.sum(ev_k)) == int(ev_n.sum())


def test_liveness_pass_donated_matches_undonated():
    """The pass updates ``colidx`` and ``strikes`` in place (aliased to
    its first two outputs): under ``jax.jit`` with both donated, as the
    round's loop carry hands them over, it returns bitwise what the
    undonated call returns, and the caller's undonated copies stay
    intact."""
    from p2p_gossipprotocol_tpu.ops.aligned_kernel import liveness_pass

    rng = np.random.default_rng(29)
    R, D, blk = 128, 4, 64
    y_alive = jnp.asarray(np.where(rng.uniform(size=(R, LANES)) < 0.6, -1,
                                   0).astype(np.int32))
    colidx = rng.integers(0, LANES, size=(D, R, LANES), dtype=np.int8)
    strikes = rng.integers(0, 5, size=(D, R, LANES), dtype=np.int8)
    deg = jnp.asarray(rng.integers(0, D + 1, size=(R, LANES),
                                   dtype=np.int8))
    rolls = jnp.asarray(rng.integers(0, 2, size=D, dtype=np.int32))
    subrolls = jnp.asarray(np.array([3, 0, 17, 40], np.int32))
    gbase = jnp.arange(0, R, blk, dtype=jnp.int32)

    def sweep(col, stk):
        return liveness_pass(y_alive, col, stk, deg, rolls, subrolls,
                             gbase=gbase, round_idx=4, hash_seed=11,
                             rowblk=blk, interpret=True)

    col0, stk0 = jnp.asarray(colidx), jnp.asarray(strikes)
    want = jax.jit(sweep)(col0, stk0)
    got = jax.jit(sweep, donate_argnums=(0, 1))(jnp.asarray(colidx),
                                                jnp.asarray(strikes))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert int(jnp.sum(want[2])) > 0
    np.testing.assert_array_equal(np.asarray(col0), colidx)
    np.testing.assert_array_equal(np.asarray(stk0), strikes)


# ----------------------------------------------------------------------
# The rolled read: blocks of several vregs (the toy tests above use one)

@functools.cache
def _rolled_outputs(shape, n, ring):
    """``_rolled_reader`` over one ``shape`` = (..., blk, 128) block,
    once per each of ``n`` shifts (a grid step each): read from the
    BlockSpec buffer, or from a slot of a two-slot VMEM ring filled by
    DMA, as the prefetch_depth=2 stream fills it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from p2p_gossipprotocol_tpu.ops.aligned_kernel import _rolled_reader

    blk = shape[-2]
    zeros = (0,) * len(shape)

    def body(s_ref, x_ref, o_ref, *scratch):
        i = pl.program_id(0)
        src = x_ref
        if ring:
            buf, sem = scratch
            slot = i % 2
            cp = pltpu.make_async_copy(x_ref, buf.at[slot], sem.at[slot])
            cp.start()
            cp.wait()
            src = buf.at[slot]
        o_ref[0] = _rolled_reader(s_ref[i], blk)(src)

    spec = (pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY) if ring
            else pl.BlockSpec(shape, lambda i, s: zeros))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(n,), in_specs=[spec],
        out_specs=pl.BlockSpec((1,) + shape, lambda i, s: (i,) + zeros),
        scratch_shapes=([pltpu.VMEM((2,) + shape, jnp.int32),
                         pltpu.SemaphoreType.DMA((2,))] if ring else []))
    return jax.jit(pl.pallas_call(
        body, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n,) + shape, jnp.int32),
        interpret=True))


@pytest.mark.parametrize("source", ["block", "ring"])
@pytest.mark.parametrize("blk", [8, 24, 64, 512, 1552, 2048])
def test_rolled_reader_matches_np_roll(blk, source):
    """Row i of the read is row (i + s) % blk of the block, for every
    shift (a sample of them past 512 rows, where loads take runs of
    vregs): the addressed loads, the per-vreg rotate and the blend,
    against ``np.roll``.  The BlockSpec case reads a single plane, the
    ring case two planes of one slot."""
    rng = np.random.default_rng(blk)
    planes = (2,) if source == "ring" else ()
    x = rng.integers(-2**31, 2**31, size=planes + (blk, LANES),
                     dtype=np.int64).astype(np.int32)
    if blk > 512:
        shifts = np.unique(np.concatenate([
            [0, 1, 7, 8, 9, 31, 32, 33, blk // 2, blk - 8, blk - 1],
            rng.integers(0, blk, size=21)]))
    else:
        shifts = np.arange(blk)
    run = _rolled_outputs(x.shape, 64, source == "ring")
    for lo in range(0, len(shifts), 64):
        chunk = shifts[lo:lo + 64]
        got = np.asarray(run(jnp.asarray(np.resize(chunk, 64), jnp.int32),
                             jnp.asarray(x)))
        for s, g in zip(chunk, got):
            np.testing.assert_array_equal(g, np.roll(x, -s, axis=-2),
                                          err_msg=f"shift {s}")


@pytest.mark.parametrize("prefetch", [0, 2])
@pytest.mark.parametrize("pull", [False, True], ids=["push", "pull"])
def test_pass_matches_ground_truth_multi_vreg(pull, prefetch):
    """gossip_pass at 64-row blocks (8 vregs, 2 row blocks), with and
    without the prefetch ring, against the numpy neighbor map."""
    rng = np.random.default_rng(17)
    R, D, W, blk = 128, 6, 3, 64
    y = rng.integers(0, 2**31, size=(W, R, LANES), dtype=np.int32)
    colidx = rng.integers(0, LANES, size=(D, R, LANES), dtype=np.int8)
    gate = rng.integers(0, D + 1, size=(R, LANES), dtype=np.int8)
    rolls = rng.integers(0, 2, size=D, dtype=np.int32)
    subrolls = np.array([0, 7, 8, 9, 63, 36], np.int32)
    out = gossip_pass(jnp.asarray(y), jnp.asarray(colidx), jnp.asarray(gate),
                      jnp.asarray(rolls), jnp.asarray(subrolls), pull=pull,
                      prefetch_depth=prefetch, rowblk=blk, interpret=True)
    ref = _numpy_pass(y, colidx, gate, rolls, subrolls, rowblk=blk,
                      pull=pull)
    np.testing.assert_array_equal(np.asarray(out), ref)


@pytest.mark.parametrize("prefetch", [0, 2])
def test_fused_pass_matches_ground_truth_multi_vreg(prefetch):
    """The block-perm pass at 64-row blocks: y blocks by the ytab table,
    the source's send mask ANDed per gathered lane (the folded slot
    mask), against numpy."""
    rng = np.random.default_rng(19)
    R, D, W, blk = 256, 5, 2, 64
    T = R // blk
    y = rng.integers(0, 2**31, size=(W, R, LANES), dtype=np.int32)
    ok = np.where(rng.uniform(size=(R, LANES)) < 0.7, -1, 0).astype(np.int32)
    colidx = rng.integers(0, LANES, size=(D, R, LANES), dtype=np.int8)
    gate = rng.integers(0, D + 1, size=(R, LANES), dtype=np.int8)
    ytab = rng.integers(0, T, size=(D, T), dtype=np.int32)
    subrolls = rng.integers(0, blk, size=D, dtype=np.int32)
    out = gossip_pass(jnp.asarray(y), jnp.asarray(colidx), jnp.asarray(gate),
                      jnp.zeros(D, jnp.int32), jnp.asarray(subrolls),
                      ytab=jnp.asarray(ytab), src_ok=jnp.asarray(ok),
                      prefetch_depth=prefetch, rowblk=blk, interpret=True)
    r = np.arange(R)
    ref = np.zeros((W, R, LANES), np.int32)
    for d in range(D):
        src = ytab[d, r // blk] * blk + (r % blk + subrolls[d]) % blk
        lanes = colidx[d].astype(np.int64)
        sent = ok[src[:, None], lanes]
        for w in range(W):
            ref[w] |= np.where(d < gate, y[w][src[:, None], lanes] & sent, 0)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_liveness_and_count_match_ground_truth_multi_vreg():
    """liveness_pass and count_pass at 64-row blocks against numpy."""
    from p2p_gossipprotocol_tpu.ops.aligned_kernel import (
        count_pass, liveness_pass, rewire_candidates)

    rng = np.random.default_rng(23)
    R, D, blk, max_strikes = 128, 4, 64, 3
    y_alive = np.where(rng.uniform(size=(R, LANES)) < 0.6, -1,
                       0).astype(np.int32)
    colidx = rng.integers(0, LANES, size=(D, R, LANES), dtype=np.int8)
    strikes = rng.integers(0, max_strikes + 2, size=(D, R, LANES),
                           dtype=np.int8)
    deg = rng.integers(0, D + 1, size=(R, LANES), dtype=np.int8)
    rolls = rng.integers(0, 2, size=D, dtype=np.int32)
    subrolls = np.array([5, 8, 0, 61], np.int32)
    grows = jnp.arange(R, dtype=jnp.int32)
    args = [jnp.asarray(a) for a in (rolls, subrolls)]
    got = liveness_pass(
        jnp.asarray(y_alive), jnp.asarray(colidx), jnp.asarray(strikes),
        jnp.asarray(deg), *args, gbase=grows[::blk], round_idx=5,
        hash_seed=9, max_strikes=max_strikes, rowblk=blk, interpret=True)
    rand = np.asarray(rewire_candidates(grows, D, 5, 9))
    want = _numpy_liveness(y_alive, colidx, strikes, rand, deg, rolls,
                           subrolls, rowblk=blk, max_strikes=max_strikes)
    for g, w in zip(got[:2], want[:2]):
        np.testing.assert_array_equal(np.asarray(g), w)
    assert got[2].shape == (R // blk, 8, LANES)
    assert int(jnp.sum(got[2])) == int(want[2].sum()) > 0

    counts = count_pass(jnp.asarray(y_alive), jnp.asarray(colidx),
                        jnp.asarray(deg), *args, rowblk=blk, interpret=True)
    r = np.arange(R)
    ref = np.zeros((R, LANES), np.int32)
    for d in range(D):
        src = ((r // blk + rolls[d]) % 2) * blk + (r % blk + subrolls[d]) % blk
        z = y_alive[src[:, None], colidx[d].astype(np.int64)] & 1
        ref += np.where(d < deg, z, 0)
    np.testing.assert_array_equal(np.asarray(counts), ref)


def test_churn_kills_then_network_recovers():
    """5% one-shot churn at round 1 (BASELINE config 4 semantics): live
    count drops, strikes evict dead-pointing slots, rewire routes around
    them, and coverage over LIVE peers still converges."""
    from p2p_gossipprotocol_tpu.liveness import ChurnConfig

    topo = build_aligned(seed=7, n=2048, n_slots=8)
    sim = AlignedSimulator(topo=topo, n_msgs=8, mode="pushpull",
                           churn=ChurnConfig(rate=0.05, kill_round=1),
                           max_strikes=3, seed=1)
    res = sim.run(20)
    n = topo.n_peers
    assert res.live_peers[0] == n                 # churn fires at round 1
    assert n * 0.93 < res.live_peers[-1] < n      # ~5% died, none revived
    assert res.evictions.sum() > 0                # strikes actually fired
    assert res.coverage[-1] > 0.99                # live peers converge
    # rewire changed lane choices (colidx actually mutated)
    assert (np.asarray(res.topo.colidx) !=
            np.asarray(topo.colidx)).any()


def test_churn_run_deterministic_and_resumable_topology():
    """Same seed → bitwise-identical runs including the rewired topology
    (the carried colidx is part of the determinism contract)."""
    from p2p_gossipprotocol_tpu.liveness import ChurnConfig

    topo = build_aligned(seed=8, n=1024, n_slots=6)
    mk = lambda: AlignedSimulator(  # noqa: E731
        topo=topo, n_msgs=4, mode="pushpull",
        churn=ChurnConfig(rate=0.1, kill_round=2), seed=4)
    ra, rb = mk().run(12), mk().run(12)
    np.testing.assert_array_equal(np.asarray(ra.state.seen_w),
                                  np.asarray(rb.state.seen_w))
    np.testing.assert_array_equal(np.asarray(ra.topo.colidx),
                                  np.asarray(rb.topo.colidx))
    np.testing.assert_array_equal(ra.live_peers, rb.live_peers)


def test_byzantine_suppression_recovers_honest_coverage():
    """10% byzantine suppressors + junk injection: honest coverage over
    live honest peers still converges (the recovery BASELINE config 5
    measures), and junk never spreads beyond the byzantine peers
    themselves (suppressors don't relay — gossip.py semantics)."""
    topo = build_aligned(seed=9, n=2048, n_slots=8)
    sim = AlignedSimulator(topo=topo, n_msgs=12, mode="pushpull",
                           byzantine_fraction=0.1, n_honest_msgs=8,
                           seed=2)
    st = sim.init_state()
    byz_b = np.asarray(st.byz_w) != 0
    assert 0.05 < byz_b.mean() < 0.2
    # honest sources only
    seeded = np.asarray(st.seen_w) != 0
    assert not (seeded & byz_b).any()
    res = sim.run(20)
    assert res.coverage[-1] > 0.99
    # junk columns stay confined to byzantine peers
    junk_mask = np.asarray(sim._junk_mask)[:, None, None]
    junk_seen = np.asarray(res.state.seen_w) & junk_mask
    assert not (junk_seen & ~np.where(byz_b, -1, 0)[None]).any()


def test_churn_dynamics_match_exact_engine_statistically():
    """The flagship scenario (pushpull + one-shot churn + strikes/rewire)
    must show the same rounds-to-99% as the exact edge engine on a
    comparable overlay — extends the clean-network statistical check to
    the BASELINE config-4 dynamics."""
    from p2p_gossipprotocol_tpu.liveness import ChurnConfig

    n, d = 4096, 8
    churn = ChurnConfig(rate=0.05, kill_round=1)
    topo_a = build_aligned(seed=21, n=n, n_slots=d)
    sim_a = AlignedSimulator(topo=topo_a, n_msgs=8, mode="pushpull",
                             churn=churn, max_strikes=3, seed=0)
    res_a = sim_a.run(32)
    assert res_a.coverage[-1] >= 0.99
    r_aligned = int(np.argmax(res_a.coverage >= 0.99)) + 1

    topo_e = graph.erdos_renyi(21, n, avg_degree=d)
    sim_e = Simulator(topo=topo_e, n_msgs=8, mode="pushpull", churn=churn,
                      max_strikes=3, rewire=True, seed=0)
    res_e = sim_e.run(32)
    r_exact = res_e.rounds_to(0.99)
    assert r_exact > 0
    assert abs(r_aligned - r_exact) <= 3, (r_aligned, r_exact)


def test_small_n_converges():
    """Regression: the layout used to force >= 8 rows, making most rows
    black-hole padding at small n — at n=256 every peer averaged under
    one live in-neighbor and dissemination died entirely (round-3 find).
    Small overlays now get exact row counts and must converge."""
    for n, slots in [(128, 8), (256, 8), (512, 6)]:
        topo = build_aligned(seed=1, n=n, n_slots=slots,
                             degree_law="regular")
        assert topo.rows == max(1, -(-n // 128))
        sim = AlignedSimulator(topo=topo, n_msgs=4, mode="pushpull",
                               seed=1)
        res = sim.run(24)
        assert float(res.coverage[-1]) == 1.0, (n, slots)


def test_tpu_path_rejects_sub_tile_layouts():
    """The real-TPU (non-interpret) kernel tiles (8, 128) sublanes: both
    a sub-8-row overlay and a non-8-aligned row block must fail loudly at
    construction, not compile-error deep inside mosaic."""
    topo = build_aligned(seed=1, n=256, n_slots=4)
    with pytest.raises(ValueError, match="8 rows"):
        AlignedSimulator(topo=topo, n_msgs=4, interpret=False)
    # rows=8 but rowblk=1 (an 8-shard layout of 1024 peers): also rejected
    topo = build_aligned(seed=1, n=1024, n_slots=4, n_shards=8)
    assert topo.rows == 8 and topo.rowblk == 1
    with pytest.raises(ValueError, match="row block"):
        AlignedSimulator(topo=topo, n_msgs=4, interpret=False)


def test_pull_mode_converges():
    """Pure anti-entropy pull (no push pass): one random contact per peer
    per round must still reach full coverage, just more slowly than
    pushpull (gossip.py test_pushpull_faster_than_pull analogue)."""
    topo = build_aligned(seed=3, n=2048, n_slots=8, degree_law="regular")
    pull = AlignedSimulator(topo=topo, n_msgs=4, mode="pull", seed=3)
    res_pull = pull.run(64)
    assert float(res_pull.coverage[-1]) > 0.99
    pp = AlignedSimulator(topo=topo, n_msgs=4, mode="pushpull", seed=3)
    res_pp = pp.run(64)
    assert res_pp.rounds_to(0.99) <= res_pull.rounds_to(0.99)


# ----------------------------------------------------------------------
# Multi-word message planes (> 32 messages — reference peer.cpp:357-366's
# growing per-peer rumor universe; round-3 verdict item #1)

def _unpack_seen(seen_w, n, n_msgs):
    """bool[n, n_msgs] view of the bit-packed [W, R, 128] planes."""
    u = np.asarray(seen_w).view(np.uint32)
    out = np.zeros((n, n_msgs), bool)
    for m in range(n_msgs):
        plane = u[m // 32].reshape(-1)[:n]
        out[:, m] = (plane >> np.uint32(m % 32)) & np.uint32(1)
    return out


def test_multiword_seed_and_flood():
    topo = build_aligned(seed=12, n=1024, n_slots=6)
    sim = AlignedSimulator(topo=topo, n_msgs=80, mode="push", seed=0)
    assert sim.n_words == 3
    st = sim.init_state()
    assert st.seen_w.shape == (3, topo.rows, LANES)
    seeded = np.asarray(st.seen_w).view(np.uint32)
    assert np.unpackbits(seeded.view(np.uint8)).sum() == 80
    res = sim.run(14)
    assert res.coverage[-1] == pytest.approx(1.0)
    assert res.frontier_size[-1] == 0


def test_multiword_pushpull_deterministic():
    topo = build_aligned(seed=2, n=1024, n_slots=4)
    mk = lambda: AlignedSimulator(topo=topo, n_msgs=65, mode="pushpull",  # noqa: E731
                                  seed=5)
    ra, rb = mk().run(12), mk().run(12)
    np.testing.assert_array_equal(np.asarray(ra.state.seen_w),
                                  np.asarray(rb.state.seen_w))
    assert ra.coverage[-1] > 0.99


def test_multiword_exact_parity_with_edges_engine():
    """Flood dissemination is deterministic given graph + sources, so the
    exact edge-list engine consuming the SAME overlay (via the
    neighbor_ids bridge) with the SAME source placement must produce the
    IDENTICAL per-message spread at W > 1 — bit-for-bit, not
    statistically."""
    from p2p_gossipprotocol_tpu.graph import _pad_and_build

    n, n_msgs, rounds = 512, 48, 8
    topo = build_aligned(seed=13, n=n, n_slots=4)
    sim_a = AlignedSimulator(topo=topo, n_msgs=n_msgs, mode="push", seed=0)
    st_a = sim_a.init_state()
    seen0 = _unpack_seen(st_a.seen_w, n, n_msgs)
    assert (seen0.sum(axis=0) == 1).all()      # every message seeded once
    sources = np.argmax(seen0, axis=0)

    nbr = np.asarray(topo.neighbor_ids())      # [D, R, 128] in-edges
    deg = np.asarray(topo.deg)
    peer = np.arange(topo.rows * LANES).reshape(topo.rows, LANES)
    srcs, dsts = [], []
    for d in range(nbr.shape[0]):
        live = d < deg
        srcs.append(nbr[d][live])
        dsts.append(peer[live])
    topo_e = _pad_and_build(n, np.concatenate(srcs), np.concatenate(dsts))

    res_a = sim_a.run(rounds)
    sim_e = Simulator(topo=topo_e, n_msgs=n_msgs, mode="push", seed=0)
    st_e = sim_e.init_state(sources=jnp.asarray(sources))
    res_e = sim_e.run(rounds, state=st_e)

    np.testing.assert_array_equal(
        _unpack_seen(res_a.state.seen_w, n, n_msgs),
        np.asarray(res_e.state.seen))
    np.testing.assert_allclose(res_a.coverage, res_e.coverage, atol=1e-6)
    np.testing.assert_array_equal(res_a.deliveries, res_e.deliveries)


def test_multiword_byzantine_junk_confined():
    """Junk columns spilling into a SECOND plane (bits 40-49 live in plane
    1) stay confined to byzantine peers, and honest coverage converges."""
    topo = build_aligned(seed=14, n=2048, n_slots=8)
    sim = AlignedSimulator(topo=topo, n_msgs=50, mode="pushpull",
                           byzantine_fraction=0.1, n_honest_msgs=40,
                           seed=2)
    assert sim.n_words == 2
    st = sim.init_state()
    byz_b = np.asarray(st.byz_w) != 0
    seeded = np.asarray(st.seen_w) != 0
    assert not (seeded & byz_b[None]).any()    # honest sources only
    res = sim.run(20)
    assert res.coverage[-1] > 0.99
    junk_mask = np.asarray(sim._junk_mask)
    assert junk_mask[0] == 0 and junk_mask[1] != 0   # junk is plane-1 only
    junk_seen = np.asarray(res.state.seen_w) & junk_mask[:, None, None]
    assert not (junk_seen & ~np.where(byz_b, -1, 0)[None]).any()


def _numpy_init(sim):
    """The round-0 state built in numpy from the sim's cached plan: each
    honest column's bit at its source unless generation is staggered,
    the byzantine marks, every valid peer alive, zero strikes."""
    byz_b, src = (np.asarray(a) for a in sim._message_plan())
    seen = np.zeros((sim.n_words, sim.topo.rows * LANES), np.uint32)
    if sim.message_stagger <= 0:
        for m in range(sim._n_honest):
            seen[m // 32, src[m]] |= np.uint32(1) << np.uint32(m % 32)
    seen = seen.view(np.int32).reshape(sim.n_words, sim.topo.rows, LANES)
    strikes = (np.zeros((sim.topo.n_slots, sim.topo.rows, LANES), np.int8)
               if sim._liveness else None)
    key = np.asarray(jax.random.split(jax.random.PRNGKey(sim.seed))[1])
    return dict(seen_w=seen, frontier_w=seen,
                alive_b=np.asarray(sim.topo.valid_w) != 0,
                byz_w=np.where(byz_b, -1, 0).astype(np.int32),
                strikes=strikes, key=key, round=np.int32(0))


@pytest.mark.parametrize("n,kw", [
    (100, dict(n_msgs=128)),                    # 128 rumors on 100 peers
    (1024, dict(n_msgs=80)),                    # W=3, bit 31 of each word
    (1024, dict(n_msgs=32, message_stagger=2)),  # nothing seeded
    (2048, dict(n_msgs=40, byzantine_fraction=0.2, n_honest_msgs=30)),
    (1024, dict(n_msgs=8, churn_rate=0.0)),     # no liveness: no strikes
], ids=["colliding", "w3", "stagger", "byzantine", "no_churn"])
def test_init_state_exact(n, kw):
    """The compiled round-0 program builds, leaf for leaf, the state the
    plan describes."""
    from p2p_gossipprotocol_tpu.liveness import ChurnConfig

    rate = kw.pop("churn_rate", 0.05)
    topo = build_aligned(seed=4, n=n, n_slots=6, n_msgs=kw["n_msgs"])
    sim = AlignedSimulator(topo=topo, churn=ChurnConfig(rate=rate), seed=7,
                           **kw)
    src = np.asarray(sim._message_plan()[1])[:sim._n_honest]
    if n == 100:
        assert len(set(src.tolist())) < len(src)    # sources collide
    if sim.byzantine_fraction:
        assert np.asarray(sim._message_plan()[0]).any()
    st = sim.init_state()
    want = _numpy_init(sim)
    assert (st.strikes is None) == (rate == 0.0)
    for name, ref in want.items():
        got = getattr(st, name)
        if ref is None:
            assert got is None, name
            continue
        assert got.dtype == ref.dtype, name
        np.testing.assert_array_equal(np.asarray(got), ref, err_msg=name)


def test_init_state_compiles_once_per_shape(monkeypatch):
    """Two calls, and a second sim of the same shape with another seed,
    share one compiled init program."""
    from p2p_gossipprotocol_tpu import aligned, telemetry

    monkeypatch.setattr(aligned, "_INIT_PROGRAMS", {})
    rec = telemetry.recorder()
    mirror = rec._mirror
    rec.configure(enabled=False)
    rec.reset()
    try:
        rec.configure(enabled=True)
        topo = build_aligned(seed=5, n=1024, n_slots=4)
        a = AlignedSimulator(topo=topo, n_msgs=8, seed=1)
        b = AlignedSimulator(topo=topo, n_msgs=8, seed=2)
        a.init_state()
        a.init_state()
        st = b.init_state()
        assert rec.counters()["init_state_compiles_total"] == 1
        assert len(rec.spans("compile")) == 1
        assert len(rec.spans("dispatch")) == 3
        np.testing.assert_array_equal(np.asarray(st.seen_w),
                                      _numpy_init(b)["seen_w"])
    finally:
        rec.configure(enabled=False)
        rec.reset()
        rec.set_mirror(mirror)


def test_init_state_traced_then_real():
    """jax.eval_shape of init_state caches no tracer: the real call after
    it returns concrete arrays."""
    topo = build_aligned(seed=6, n=1024, n_slots=4)
    sim = AlignedSimulator(topo=topo, n_msgs=40, message_stagger=1, seed=3)
    shapes = jax.eval_shape(sim.init_state)
    st = sim.init_state()
    for s, x in zip(jax.tree.leaves(shapes), jax.tree.leaves(st)):
        assert not isinstance(x, jax.core.Tracer)
        assert (s.shape, s.dtype) == (x.shape, x.dtype)
    for x in jax.tree.leaves(sim._message_plan()):
        assert not isinstance(x, jax.core.Tracer)
    np.testing.assert_array_equal(np.asarray(st.seen_w), 0)


def test_vmem_budget_guard():
    """Wide message sets must shrink the kernel row block; an over-budget
    (rowblk, W) combination fails at construction with the fix named, not
    deep inside Mosaic."""
    topo = build_aligned(seed=1, n=1 << 19, n_slots=2)
    assert topo.rowblk == 512
    with pytest.raises(ValueError, match="VMEM"):
        AlignedSimulator(topo=topo, n_msgs=512, interpret=False)
    topo2 = build_aligned(seed=1, n=1 << 19, n_slots=2, n_msgs=512)
    assert topo2.rowblk * 16 <= 4096
    AlignedSimulator(topo=topo2, n_msgs=512, interpret=False)


# ----------------------------------------------------------------------
# Bounded fanout (rumor mongering) on the aligned engine — round-3
# verdict item #4; the reference's flood (peer.cpp:310-312) is fanout=deg.

def test_fanout_window_kernel_ground_truth(small_tables):
    y, colidx, deg, rolls, subrolls = small_tables
    rng = np.random.default_rng(17)
    shift = (rng.integers(0, 1 << 30, size=deg.shape)
             % np.maximum(deg, 1)).astype(np.int8)
    fanout = 2
    out = np.asarray(gossip_pass(
        jnp.asarray(y), jnp.asarray(colidx), jnp.asarray(deg),
        jnp.asarray(rolls), jnp.asarray(subrolls), pull=False,
        fanout=fanout, shift=jnp.asarray(shift), rowblk=8, interpret=True))
    W, R, C = y.shape
    D = colidx.shape[0]
    blk, T = 8, R // 8
    r = np.arange(R)
    ref = np.zeros_like(out)
    for d in range(D):
        src_row = (((r // blk + rolls[d]) % T) * blk
                   + (r % blk + subrolls[d]) % blk)
        g = deg.astype(np.int64)
        mask = (d < g) & (((d - shift) % np.maximum(g, 1)) < fanout)
        for w in range(W):
            z = y[w][src_row[:, None], colidx[d].astype(np.int64)]
            ref[w] |= np.where(mask, z, 0)
    np.testing.assert_array_equal(out, ref)


def test_fanout_convergence_matches_edges_engine():
    """Rumor mongering at the same fanout must show the same
    rounds-to-99% as the exact engine's sender-side fanout (within the
    statistical margin the flood comparison uses), and lower fanout must
    converge no faster than higher.  Mode is pushpull: bounded-fanout
    pure push is one-shot bond percolation (each edge flips a p=f/deg
    coin exactly once, while the frontier passes) and plateaus below
    full coverage in BOTH engines — anti-entropy is what makes rumor
    mongering converge, and is what the BASELINE configs run."""
    n, d = 4096, 12
    rounds = {}
    for fanout in (2, 6):
        topo_a = build_aligned(seed=23, n=n, n_slots=d)
        sim_a = AlignedSimulator(topo=topo_a, n_msgs=8, mode="pushpull",
                                 fanout=fanout, seed=0)
        res_a = sim_a.run(48)
        assert res_a.coverage[-1] > 0.99, fanout
        rounds[fanout] = int(np.argmax(res_a.coverage >= 0.99)) + 1

        topo_e = graph.erdos_renyi(23, n, avg_degree=d)
        sim_e = Simulator(topo=topo_e, n_msgs=8, mode="pushpull",
                          fanout=fanout, seed=0)
        res_e = sim_e.run(48)
        r_exact = res_e.rounds_to(0.99)
        assert r_exact > 0
        assert abs(rounds[fanout] - r_exact) <= 3, (fanout, rounds[fanout],
                                                    r_exact)
    assert rounds[2] >= rounds[6]

    # Bounded-fanout PURE PUSH must show the percolation plateau in
    # both engines.  The plateau LEVEL is deliberately not compared
    # across engines: the aligned family thins RECEIVER-side (each
    # peer keeps one circular window of f of its deg in-slots — a
    # single joint draw gating every sender that round), the edge
    # engine SENDER-side (each frontier peer picks f of its out-edges
    # independently), and the two one-shot bond-percolation processes
    # have different giant-component constants (measured ~0.43 vs
    # ~0.67 at n=4096, f=2, d=12 — a structural gap, not seed noise;
    # this assertion used to demand |Δ| < 0.1 and failed at seed).
    # What both engines MUST show, per seed-averaged run: spreading
    # far beyond the seed set, yet stalling well short of the full
    # coverage the pushpull comparison above reaches.
    for mk in (
        lambda s: AlignedSimulator(
            topo=build_aligned(seed=s, n=n, n_slots=d), n_msgs=8,
            mode="push", fanout=2, seed=0),
        lambda s: Simulator(
            topo=graph.erdos_renyi(s, n, avg_degree=d), n_msgs=8,
            mode="push", fanout=2, seed=0),
    ):
        plateau = np.mean([float(mk(s).run(48).coverage[-1])
                           for s in (23, 24)])
        assert 0.15 < plateau < 0.95, plateau


def test_fanout_deterministic():
    topo = build_aligned(seed=24, n=1024, n_slots=8)
    mk = lambda: AlignedSimulator(topo=topo, n_msgs=40, mode="pushpull",  # noqa: E731
                                  fanout=3, seed=9)
    ra, rb = mk().run(16), mk().run(16)
    np.testing.assert_array_equal(np.asarray(ra.state.seen_w),
                                  np.asarray(rb.state.seen_w))
    assert ra.coverage[-1] > 0.99


# ----------------------------------------------------------------------
# Strided liveness (the reference's probe cadence: 13 s ping sweeps vs
# 5 s messages, peer.cpp:330/377 — one sweep per ~2.6 message rounds)

def test_liveness_every_strides_the_pass():
    """With liveness_every=3 the strike/evict/rewire pass only runs on
    rounds where round % 3 == 0 — off-rounds must report zero evictions
    — and the churned network still recovers and converges."""
    from p2p_gossipprotocol_tpu.liveness import ChurnConfig

    topo = build_aligned(seed=7, n=2048, n_slots=8)
    sim = AlignedSimulator(topo=topo, n_msgs=8, mode="pushpull",
                           churn=ChurnConfig(rate=0.05, kill_round=1),
                           max_strikes=3, liveness_every=3, seed=1)
    res = sim.run(24)
    n = topo.n_peers
    ev = np.asarray(res.evictions)
    # metrics[i] is the round with pre-increment counter i, so the pass
    # runs at i % 3 == 0; every other round must be silent
    off = [i for i in range(24) if i % 3 != 0]
    assert ev[off].sum() == 0
    assert ev.sum() > 0                           # sweeps still evict
    assert n * 0.93 < res.live_peers[-1] < n
    assert res.coverage[-1] > 0.99                # still converges
    assert (np.asarray(res.topo.colidx) !=
            np.asarray(topo.colidx)).any()        # rewire still happens


def test_liveness_every_sharded_bitwise(devices8):
    """The stride composes with the mesh: sharded-vs-unsharded equality
    stays bitwise with liveness_every > 1."""
    from p2p_gossipprotocol_tpu.liveness import ChurnConfig
    from p2p_gossipprotocol_tpu.parallel import (AlignedShardedSimulator,
                                                 make_mesh)

    topo = build_aligned(seed=9, n=2048, n_slots=6, rowblk=1, n_shards=8)
    kw = dict(topo=topo, n_msgs=8, mode="pushpull",
              churn=ChurnConfig(rate=0.05, kill_round=1), max_strikes=2,
              liveness_every=2, seed=3)
    ru = AlignedSimulator(**kw).run(10)
    rs = AlignedShardedSimulator(mesh=make_mesh(8), **kw).run(10)
    np.testing.assert_array_equal(np.asarray(ru.state.seen_w),
                                  np.asarray(rs.state.seen_w))
    np.testing.assert_array_equal(np.asarray(ru.topo.colidx),
                                  np.asarray(rs.topo.colidx))
    np.testing.assert_array_equal(ru.evictions, rs.evictions)


def test_from_config_derives_liveness_cadence(tmp_path):
    """from_config turns the config's own probe/message intervals into
    the liveness stride — reference defaults (ping 13 s, messages 5 s)
    give one sweep per 3 rounds; explicit intervals are honored."""
    from p2p_gossipprotocol_tpu.config import NetworkConfig

    cfg = tmp_path / "net.txt"
    cfg.write_text("10.0.0.1:8000\nbackend=jax\nengine=aligned\n"
                   "graph=er\nn_peers=1024\nn_messages=8\n")
    sim = AlignedSimulator.from_config(NetworkConfig(str(cfg)))
    assert sim.liveness_every == 3          # round(13 / 5)

    cfg.write_text("10.0.0.1:8000\nbackend=jax\nengine=aligned\n"
                   "graph=er\nn_peers=1024\nn_messages=8\n"
                   "ping_interval=5\nmessage_interval=5\n")
    sim = AlignedSimulator.from_config(NetworkConfig(str(cfg)))
    assert sim.liveness_every == 1


def test_roll_groups_convergence_parity():
    """Grouped block rolls (the DMA-reuse layout) must not slow
    dissemination: rounds-to-99% within +2 of the fully-random layout on
    the same scenario."""
    def rounds_to_99(groups):
        topo = build_aligned(seed=11, n=65536, n_slots=16,
                             degree_law="powerlaw", roll_groups=groups)
        sim = AlignedSimulator(topo=topo, n_msgs=8, mode="pushpull",
                               seed=2)
        res = sim.run(16)
        hit = np.nonzero(res.coverage >= 0.99)[0]
        assert hit.size, f"groups={groups} never converged"
        return int(hit[0])

    base = rounds_to_99(None)
    grouped = rounds_to_99(4)
    assert grouped <= base + 2, (base, grouped)
    # Even ONE shared block roll for all 16 slots converges at parity —
    # the permutation + per-slot subrolls + lane draws supply the
    # mixing (round-5 CPU study: identical rounds-to-99 for 16/4/2/1
    # distinct rolls at 262k across seeds).  This is what makes the
    # 16x y-stream cut a pure bandwidth win if the pipeline's
    # resident-buffer reuse measures real (benchmarks/measure_round5).
    single = rounds_to_99(1)
    assert single <= base + 2, (base, single)


def test_roll_groups_layout():
    """roll_groups draws that many distinct block rolls over contiguous
    slot groups; subrolls/colidx stay per-slot."""
    topo = build_aligned(seed=3, n=65536, n_slots=16, roll_groups=4,
                         rowblk=64)        # t_blocks=8: rolls can differ
    rolls = np.asarray(topo.rolls)
    assert len(np.unique(rolls[0:4])) == 1
    assert len(np.unique(rolls[4:8])) == 1
    groups = {tuple(rolls[i:i + 4]) for i in range(0, 16, 4)}
    assert len(groups) >= 2          # t_blocks large enough to differ


def test_hbm_traffic_model_counts_streams():
    """The traffic model behind the bench's achieved_gb_s: scales with
    message planes, counts only distinct consecutive block rolls, and
    amortizes the liveness pass by its stride."""
    from p2p_gossipprotocol_tpu.liveness import ChurnConfig

    topo = build_aligned(seed=1, n=65536, n_slots=16, rowblk=64)
    topo_g = build_aligned(seed=1, n=65536, n_slots=16, rowblk=64,
                           roll_groups=4)

    def bytes_for(t, **kw):
        return AlignedSimulator(topo=t, mode="pushpull", seed=0,
                                **kw).hbm_bytes_per_round()

    assert bytes_for(topo_g, n_msgs=32) < bytes_for(topo, n_msgs=32)
    assert bytes_for(topo, n_msgs=64) > bytes_for(topo, n_msgs=32)
    churned = dict(churn=ChurnConfig(rate=0.05), n_msgs=32)
    every1 = bytes_for(topo, **churned)
    every3 = bytes_for(topo, liveness_every=3, **churned)
    assert bytes_for(topo, n_msgs=32) < every3 < every1
