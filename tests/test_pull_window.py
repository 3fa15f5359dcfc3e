"""Windowed pull (round-5 ``pull_window``): the pull contact is drawn
from the first roll group's slots only, and the pull pass runs a
window-sized grid whose slots share ONE block roll — a single
seen-plane stream instead of one per distinct roll.

Correctness anchor: a Dw-slot pass over ``colidx[:Dw]`` with gate in
[0, Dw) is BITWISE the same computation as the full-grid pass with the
same gate (slots >= Dw are masked off there); the engine-level draw
only changes the modulus.  Convergence is measured, not assumed.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_gossipprotocol_tpu.aligned import (AlignedSimulator,
                                            build_aligned)
from p2p_gossipprotocol_tpu.liveness import ChurnConfig
from p2p_gossipprotocol_tpu.ops.aligned_kernel import gossip_pass


def test_windowed_pass_equals_masked_full_pass():
    """gossip_pass on the sliced window == gossip_pass on the full grid
    when the sampled slots lie inside the window."""
    topo = build_aligned(seed=2, n=2048, n_slots=8, roll_groups=2,
                         rowblk=8)
    Dw = 4                      # 8 slots / 2 groups
    assert len(np.unique(np.asarray(topo.rolls)[:Dw])) == 1
    key = jax.random.PRNGKey(0)
    y = jax.random.randint(key, (2, topo.rows, 128),
                           jnp.iinfo(jnp.int32).min,
                           jnp.iinfo(jnp.int32).max, jnp.int32)
    delta = jax.random.randint(jax.random.PRNGKey(1),
                               (topo.rows, 128), 0, Dw, jnp.int8)
    full = gossip_pass(y, topo.colidx, delta, topo.rolls, topo.subrolls,
                       pull=True, rowblk=topo.rowblk, interpret=True)
    win = gossip_pass(y, topo.colidx[:Dw], delta, topo.rolls[:Dw],
                      topo.subrolls[:Dw], pull=True, rowblk=topo.rowblk,
                      interpret=True)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(win))
    # the same Dw-slot grid over the whole tables, as the round runs it:
    # the index maps read only their first Dw slots
    grid = gossip_pass(y, topo.colidx, delta, topo.rolls, topo.subrolls,
                       pull=True, slots=Dw, rowblk=topo.rowblk,
                       interpret=True)
    np.testing.assert_array_equal(np.asarray(grid), np.asarray(win))


def _sim(pw, mode="pushpull", **over):
    # rowblk=64 -> 8 row blocks, so the 4 roll groups draw DISTINCT
    # block rolls and the window is a real restriction (the 65k default
    # layout is a single 512-row block where every roll is 0 and the
    # window degenerates to all slots)
    topo = build_aligned(seed=3, n=65536, n_slots=16,
                         degree_law="powerlaw", roll_groups=4, rowblk=64)
    kw = dict(topo=topo, n_msgs=16, mode=mode,
              churn=ChurnConfig(rate=0.05, kill_round=1), max_strikes=3,
              liveness_every=3, pull_window=pw, seed=4)
    kw.update(over)
    return AlignedSimulator(**kw)


def test_pull_window_converges_at_parity():
    """Rounds-to-99 with the windowed draw stays within +2 of the
    unrestricted draw (slot identities are i.i.d., so a window is as
    random a neighbor set as any)."""
    def rounds_to_99(pw):
        res = _sim(pw).run(16)
        hit = np.nonzero(np.asarray(res.coverage) >= 0.99)[0]
        assert hit.size, f"pull_window={pw} never converged"
        return int(hit[0])
    base, windowed = rounds_to_99(False), rounds_to_99(True)
    assert windowed <= base + 2, (base, windowed)


def test_pull_window_model_bytes_drop():
    assert (_sim(True).hbm_bytes_per_round()
            < _sim(False).hbm_bytes_per_round())
    # pure pull drops even more in relative terms
    assert (_sim(True, mode="pull").hbm_bytes_per_round()
            < _sim(False, mode="pull").hbm_bytes_per_round())


def test_pull_window_rejects_degenerate_layouts():
    # per-slot rolls: rejected from the BUILT grouping (deterministic —
    # a seed whose first two rolls coincide must not be accepted)
    topo = build_aligned(seed=1, n=4096, n_slots=8, rowblk=8)
    assert topo.roll_groups is None
    with pytest.raises(ValueError, match="roll-grouped"):
        AlignedSimulator(topo=topo, n_msgs=8, mode="pull",
                         pull_window=True, seed=0)
    # groups of ONE slot: window 1 = the same neighbor every round
    topo1 = build_aligned(seed=1, n=4096, n_slots=8, roll_groups=8,
                          rowblk=8)
    with pytest.raises(ValueError, match=">= 2 slots"):
        AlignedSimulator(topo=topo1, n_msgs=8, mode="pull",
                         pull_window=True, seed=0)
    # push mode has no pull pass to window
    topo_g = build_aligned(seed=1, n=4096, n_slots=8, roll_groups=2,
                           rowblk=8)
    with pytest.raises(ValueError, match="pull"):
        AlignedSimulator(topo=topo_g, n_msgs=8, mode="push",
                         pull_window=True, seed=0)
    # pure pull on a block-perm overlay: the windowed pull-level block
    # graph is a single permutation cycle — dissemination would stall
    topo_bp = build_aligned(seed=1, n=4096, n_slots=8, roll_groups=2,
                            rowblk=8, block_perm=True)
    with pytest.raises(ValueError, match="cycle"):
        AlignedSimulator(topo=topo_bp, n_msgs=8, mode="pull",
                         pull_window=True, seed=0)
    # pushpull on the same overlay is fine (push mixes across rolls)
    AlignedSimulator(topo=topo_bp, n_msgs=8, mode="pushpull",
                     pull_window=True, seed=0)


def test_pull_window_sharded_parity(devices8):
    from p2p_gossipprotocol_tpu.parallel import (AlignedShardedSimulator,
                                                 make_mesh)

    topo = build_aligned(seed=3, n=8192, n_slots=8, roll_groups=2,
                         n_shards=8)
    kw = dict(topo=topo, n_msgs=32, mode="pushpull",
              churn=ChurnConfig(rate=0.05, kill_round=1), max_strikes=3,
              liveness_every=2, pull_window=True, fuse_update=True,
              seed=5)
    base = AlignedSimulator(**kw).run(4)
    sh = AlignedShardedSimulator(mesh=make_mesh(8), **kw).run(4)
    np.testing.assert_array_equal(np.asarray(base.state.seen_w),
                                  np.asarray(sh.state.seen_w))
    np.testing.assert_array_equal(np.asarray(base.coverage),
                                  np.asarray(sh.coverage))


# slow: broadest mesh variant (the PR 5 budget rule) — the unsharded
# parity case above and the shared-aligned_round inheritance tests in
# test_auto_select keep the window covered in tier-1
@pytest.mark.slow
def test_pull_window_2d_mesh_parity(devices8):
    """The 2-D (msgs x peers) mesh inherits the windowed pull through
    the shared aligned_round — bitwise vs the unsharded windowed run."""
    from p2p_gossipprotocol_tpu.parallel import (Aligned2DShardedSimulator,
                                                 make_mesh_2d)

    topo = build_aligned(seed=3, n=8192, n_slots=8, roll_groups=2,
                         n_shards=8)
    kw = dict(topo=topo, n_msgs=64, mode="pushpull",
              churn=ChurnConfig(rate=0.05, kill_round=1), max_strikes=3,
              liveness_every=2, pull_window=True, seed=5)
    base = AlignedSimulator(**kw).run(4)
    sh2 = Aligned2DShardedSimulator(mesh=make_mesh_2d(2, 4), **kw).run(4)
    np.testing.assert_array_equal(np.asarray(base.state.seen_w),
                                  np.asarray(sh2.state.seen_w))
    np.testing.assert_array_equal(np.asarray(base.coverage),
                                  np.asarray(sh2.coverage))


def test_pull_window_config_key(tmp_path):
    p = tmp_path / "net.txt"
    p.write_text("10.0.0.1:9000\nbackend=jax\nengine=aligned\n"
                 "n_peers=4096\nn_messages=16\nmode=pushpull\n"
                 "roll_groups=4\npull_window=1\n")
    from p2p_gossipprotocol_tpu.config import NetworkConfig
    cfg = NetworkConfig(str(p))
    assert cfg.pull_window == 1
    sim = AlignedSimulator.from_config(cfg)
    assert sim.pull_window is True and sim._pull_slots >= 2
