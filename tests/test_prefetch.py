"""Round-10 double-buffered DMA prefetch (``prefetch_depth=2``).

gossip_pass's manual copy stream replaces the BlockSpec pipeline for
the y (and, fused, src_ok) operands: the block for grid step k+1 is
DMA'd into the free half of a VMEM ring while step k computes, with
copies issued by exactly stream_plan's dedup rule.  The contract is
BITWISE identity with the pipelined path on every mode, overlay
family, fault plan, frontier regime, and sharding — the same
discipline as fuse_update/block_perm/frontier before it.
"""
import numpy as np
import pytest

import jax

from p2p_gossipprotocol_tpu.aligned import (AlignedSimulator,
                                            build_aligned)
from p2p_gossipprotocol_tpu.liveness import ChurnConfig


def _mk(bp, mode, prefetch, **over):
    topo = build_aligned(seed=3, n=1024, n_slots=8,
                         degree_law="powerlaw", roll_groups=2, rowblk=8,
                         block_perm=bp)
    kw = dict(topo=topo, n_msgs=40, mode=mode,
              churn=ChurnConfig(rate=0.05, kill_round=1), max_strikes=3,
              liveness_every=2, byzantine_fraction=0.1, n_honest_msgs=32,
              message_stagger=1, prefetch_depth=prefetch, seed=5)
    kw.update(over)
    return AlignedSimulator(**kw)


def _assert_bitwise(ra, rb, ctx):
    for f in ("coverage", "deliveries", "live_peers", "evictions"):
        np.testing.assert_array_equal(np.asarray(getattr(ra, f)),
                                      np.asarray(getattr(rb, f)),
                                      err_msg=f"{ctx}:{f}")
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(ra.state.seen_w)),
        np.asarray(jax.device_get(rb.state.seen_w)),
        err_msg=f"{ctx}:seen_w")


@pytest.mark.parametrize("bp", [False, True])
@pytest.mark.parametrize("mode", ["push", "pull", "pushpull"])
def test_prefetch_bitwise_parity(bp, mode):
    """Prefetched == pipelined, bit for bit, under churn + liveness +
    byzantine + staggered generation, on both overlay families."""
    ra = _mk(bp, mode, 0).run(6)
    rb = _mk(bp, mode, 2).run(6)
    _assert_bitwise(ra, rb, f"bp={bp} mode={mode}")


@pytest.mark.parametrize("bp", [False, True])
def test_prefetch_composes_with_every_kernel_variant(bp):
    """fanout window + fuse_update finalize/census + link faults +
    frontier block skipping all ride the same prefetched stream."""
    from p2p_gossipprotocol_tpu.faults import FaultPlan

    plan = FaultPlan.parse("drop=0.2,partition=2:4")
    ra = _mk(bp, "pushpull", 0, fanout=3, fuse_update=True,
             faults=plan, frontier_mode=1).run(6)
    rb = _mk(bp, "pushpull", 2, fanout=3, fuse_update=True,
             faults=plan, frontier_mode=1).run(6)
    _assert_bitwise(ra, rb, f"variants bp={bp}")


def test_prefetch_sharded_parity(devices8):
    """The sharded engines inherit the prefetched stream through the
    shared aligned_round; 1-D and 2-D meshes stay bitwise-identical to
    the unsharded prefetched run."""
    from p2p_gossipprotocol_tpu.parallel import (Aligned2DShardedSimulator,
                                                 AlignedShardedSimulator,
                                                 make_mesh, make_mesh_2d)

    topo = build_aligned(seed=3, n=8192, n_slots=8,
                         degree_law="powerlaw", roll_groups=2, n_shards=8,
                         block_perm=True, n_msgs=64)
    kw = dict(topo=topo, n_msgs=64, mode="pushpull",
              churn=ChurnConfig(rate=0.05, kill_round=1), max_strikes=3,
              liveness_every=2, prefetch_depth=2, seed=5)
    base = AlignedSimulator(**kw).run(4)
    sh = AlignedShardedSimulator(mesh=make_mesh(8), **kw).run(4)
    _assert_bitwise(base, sh, "1d-sharded")
    sh2 = Aligned2DShardedSimulator(mesh=make_mesh_2d(2, 4), **kw).run(4)
    _assert_bitwise(base, sh2, "2d-mesh")


def test_prefetch_fleet_parity():
    """vmap composes: a fleet bucket of prefetched scenarios stays
    bitwise-equal to the solo prefetched runs (and to unprefetched)."""
    from p2p_gossipprotocol_tpu.fleet import FleetBucket

    def sims(prefetch):
        out = []
        for s in range(3):
            topo = build_aligned(seed=s, n=2048, n_slots=8,
                                 degree_law="powerlaw", roll_groups=2,
                                 block_perm=True, n_msgs=64)
            out.append(AlignedSimulator(
                topo=topo, n_msgs=64, mode="pushpull",
                churn=ChurnConfig(rate=0.05, kill_round=1),
                prefetch_depth=prefetch, seed=s))
        return out

    bres = FleetBucket(sims(2)).run(6)
    for i, (sim0, sim2) in enumerate(zip(sims(0), sims(2))):
        solo0, solo2 = sim0.run(6), sim2.run(6)
        _assert_bitwise(solo0, solo2, f"fleet-solo[{i}]")
        _assert_bitwise(solo2, bres.results[i], f"fleet-bucket[{i}]")


def test_prefetch_auto_and_validation():
    """-1 resolves off under interpret (the frontier_mode rule), bad
    values are rejected at construction, and the model's leak drops to
    the by-construction zero only on the engaged stream."""
    from p2p_gossipprotocol_tpu.aligned import Y_REUSE_LEAK_PREFETCH

    auto = _mk(True, "pushpull", -1)
    assert auto.interpret and auto._prefetch == 0
    forced = _mk(True, "pushpull", 2)
    assert forced._prefetch == 2
    assert Y_REUSE_LEAK_PREFETCH == 0.0
    with pytest.raises(ValueError, match="prefetch_depth"):
        _mk(True, "pushpull", 1)
    # the forced stream prices resident re-serves at zero leak: fewer
    # modeled bytes than the pipelined path, never more (conservative)
    assert (forced.traffic_model()["push_pass"]
            < _mk(True, "pushpull", 0).traffic_model()["push_pass"])


def test_prefetch_config_key(tmp_path):
    """prefetch_depth reaches the engine from a config file alone and
    the packer treats it as a compiled-program static."""
    from p2p_gossipprotocol_tpu.config import NetworkConfig
    from p2p_gossipprotocol_tpu.fleet.packer import bucket_signature

    base = ("10.0.0.1:9000\nbackend=jax\nengine=aligned\n"
            "n_peers=4096\nn_messages=64\nmode=pushpull\n")
    p = tmp_path / "net.txt"
    p.write_text(base + "prefetch_depth=2\n")
    sim = AlignedSimulator.from_config(NetworkConfig(str(p)))
    assert sim.prefetch_depth == 2 and sim._prefetch == 2
    p.write_text(base)
    auto = AlignedSimulator.from_config(NetworkConfig(str(p)))
    # __post_init__ resolves the -1 auto once, by its rule, so the
    # built sim carries the CONCRETE schedule (0 under interpret)
    assert auto.prefetch_depth == auto._prefetch == 0
    assert bucket_signature(sim) != bucket_signature(
        AlignedSimulator(topo=sim.topo, n_msgs=sim.n_msgs, mode=sim.mode,
                         churn=sim.churn, pull_window=sim.pull_window,
                         fuse_update=sim.fuse_update,
                         prefetch_depth=0, seed=sim.seed))
