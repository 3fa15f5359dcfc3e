"""Round-10 compute-hidden exchange (``overlap_mode``).

The sharded engines' push pass splits into a self-shard contribution
(local send planes, traced with NO dependency on the collective — the
exchange overlaps it on hardware) and a remote contribution OR-seeded
via ``acc_init``.  The two activity gates partition the grid, so the
merged accumulator is bitwise the single-pass one on every mode, fault
plan, and frontier regime — asserted as exact equality against both
the unsplit sharded run and the solo engine.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_gossipprotocol_tpu.aligned import (AlignedSimulator,
                                            _overlap_plans, build_aligned)
from p2p_gossipprotocol_tpu.liveness import ChurnConfig


def _assert_bitwise(ra, rb, ctx):
    for f in ("coverage", "deliveries", "live_peers", "evictions"):
        np.testing.assert_array_equal(np.asarray(getattr(ra, f)),
                                      np.asarray(getattr(rb, f)),
                                      err_msg=f"{ctx}:{f}")
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(ra.state.seen_w)),
        np.asarray(jax.device_get(rb.state.seen_w)),
        err_msg=f"{ctx}:seen_w")


def _topo(n=8192, shards=8):
    return build_aligned(seed=3, n=n, n_slots=8, degree_law="powerlaw",
                         roll_groups=2, n_shards=shards, block_perm=True,
                         n_msgs=64)


_KW = dict(n_msgs=64, mode="pushpull", max_strikes=3, liveness_every=2,
           byzantine_fraction=0.1, n_honest_msgs=48, message_stagger=1,
           seed=5)


@pytest.mark.parametrize("mode", ["push", "pushpull"])
@pytest.mark.parametrize("frontier", [0, 1])
def test_overlap_bitwise_parity_sharded(devices8, mode, frontier):
    """Split == unsplit == solo, bit for bit, dense AND frontier
    exchange, under churn + liveness + byzantine + stagger."""
    from p2p_gossipprotocol_tpu.parallel import (AlignedShardedSimulator,
                                                 make_mesh)

    topo = _topo()
    kw = dict(_KW, topo=topo, mode=mode,
              churn=ChurnConfig(rate=0.05, kill_round=1),
              frontier_mode=frontier)
    solo = AlignedSimulator(**kw).run(5)
    off = AlignedShardedSimulator(mesh=make_mesh(8), **kw).run(5)
    on = AlignedShardedSimulator(mesh=make_mesh(8), overlap_mode=1,
                                 **kw).run(5)
    _assert_bitwise(solo, off, f"{mode}/fr{frontier}:solo-vs-off")
    _assert_bitwise(off, on, f"{mode}/fr{frontier}:off-vs-on")


def test_overlap_2d_and_faults(devices8):
    """The 2-D mesh splits its peer-axis gather the same way, and the
    in-kernel fault gates (hashed per receiver/slot/round) land
    identically on whichever half serves a step."""
    from p2p_gossipprotocol_tpu.faults import FaultPlan
    from p2p_gossipprotocol_tpu.parallel import (Aligned2DShardedSimulator,
                                                 AlignedShardedSimulator,
                                                 make_mesh, make_mesh_2d)

    topo = _topo()
    plan = FaultPlan.parse("drop=0.2,delay=0.1,partition=2:4")
    kw = dict(_KW, topo=topo, churn=ChurnConfig(rate=0.05, kill_round=1),
              faults=plan, fanout=3)
    off = AlignedShardedSimulator(mesh=make_mesh(8), **kw).run(5)
    on = AlignedShardedSimulator(mesh=make_mesh(8), overlap_mode=1,
                                 prefetch_depth=2, **kw).run(5)
    _assert_bitwise(off, on, "faults-1d")
    on2 = Aligned2DShardedSimulator(mesh=make_mesh_2d(2, 4),
                                    overlap_mode=1, **kw).run(5)
    _assert_bitwise(off, on2, "faults-2d")


def test_overlap_plans_partition_the_grid():
    """Every (t, d) grid step is active in exactly one of the two
    passes when its block is frontier-live, and neither when dead —
    the partition that makes the OR-merge exact; pass A's indices land
    in the local frame."""
    rng = np.random.default_rng(0)
    ty_g, ty_l, D, blk, C = 8, 2, 4, 8, 128
    t_off = 4
    ytab_local = jnp.asarray(
        rng.integers(0, ty_g, size=(D, ty_l), dtype=np.int32))
    fr_l = jnp.asarray(rng.integers(0, 2, size=(1, ty_l * blk, C),
                                    dtype=np.int32))
    y_g = jnp.zeros((1, ty_g * blk, C), jnp.int32)
    y_g = y_g.at[:, t_off * blk:(t_off + ty_l) * blk].set(fr_l)
    y_g = y_g.at[:, 0:blk].set(1)          # one live remote block
    (yia, yaa), (yib, yab) = _overlap_plans(
        fr_l, y_g, blk, jnp.int32(t_off), ytab_local, skip=True)
    act_g = np.asarray(jnp.any(
        (y_g != 0).reshape(1, ty_g, blk * C), axis=(0, 2)))
    yaa, yab = np.asarray(yaa), np.asarray(yab)
    yia = np.asarray(yia)
    raw = np.asarray(ytab_local)           # [D, T]
    for t in range(ty_l):
        for d in range(D):
            g = raw[d, t]
            local = t_off <= g < t_off + ty_l
            want_a = int(local and act_g[g])
            want_b = int((not local) and act_g[g])
            assert yaa[d, t] == want_a and yab[d, t] == want_b, (t, d)
            if want_a:
                assert yia[d, t] == g - t_off
            assert 0 <= yia[d, t] < ty_l


def test_overlap_resolution_and_clamps():
    """The split needs a push pass and the block-perm overlay; from
    _config records the degrade for an explicit on, and the engine
    resolves it off silently-but-deterministically otherwise."""
    from p2p_gossipprotocol_tpu.config import NetworkConfig

    row = build_aligned(seed=0, n=1024, n_slots=8, roll_groups=2,
                        rowblk=8, block_perm=False)
    sim = AlignedSimulator(topo=row, n_msgs=16, mode="pushpull",
                           overlap_mode=1, seed=0)
    assert not sim._overlap            # row-perm: no block locality
    bp = build_aligned(seed=0, n=1024, n_slots=8, roll_groups=2,
                       rowblk=8, block_perm=True)
    assert AlignedSimulator(topo=bp, n_msgs=16, mode="pushpull",
                            overlap_mode=1, seed=0)._overlap
    assert not AlignedSimulator(topo=bp, n_msgs=16, mode="pull",
                                overlap_mode=1, seed=0)._overlap
    with pytest.raises(ValueError, match="overlap_mode"):
        AlignedSimulator(topo=bp, n_msgs=16, mode="push",
                         overlap_mode=3, seed=0)

    import tempfile
    with tempfile.TemporaryDirectory() as td:
        p = f"{td}/net.txt"
        with open(p, "w") as f:
            f.write("10.0.0.1:9000\nbackend=jax\nengine=aligned\n"
                    "n_peers=4096\nn_messages=16\nmode=pull\n"
                    "overlap_mode=1\n")
        clamps = []
        AlignedSimulator.from_config(NetworkConfig(p), clamps=clamps)
        assert any("overlap_mode" in c for c in clamps)
