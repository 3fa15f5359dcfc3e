"""Round-10 fused SIR pressure (``sir_fuse``) — fused vs two-pass
bitwise parity, mirroring the test_fuse_update.py pattern.

The fused path replaces the permute-prep + solo count_pass pair with
ONE gossip_pass whose ``press`` output is the infectious-neighbor
count, streamed off the same colidx/rolls tables.  The contract: the
fused pressure plane equals the solo count_pass result EXACTLY, so
every compartment trajectory (S/I/R counts, new infections) is bitwise
identical across overlay families x churn x sharding x prefetch.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_gossipprotocol_tpu.aligned import build_aligned
from p2p_gossipprotocol_tpu.aligned_sir import AlignedSIRSimulator
from p2p_gossipprotocol_tpu.liveness import ChurnConfig

_FIELDS = ("susceptible", "infected", "recovered", "new_infections",
           "live_peers")


def _mk(bp, fuse, churn=0.0, prefetch=0, n=2048, **over):
    topo = build_aligned(seed=3, n=n, n_slots=8, degree_law="powerlaw",
                         roll_groups=2, rowblk=8, block_perm=bp)
    kw = dict(topo=topo, beta=0.4, gamma=0.1, n_seeds=4,
              churn=ChurnConfig(rate=churn), sir_fuse=fuse,
              prefetch_depth=prefetch, seed=7)
    kw.update(over)
    return AlignedSIRSimulator(**kw)


def _assert_bitwise(ra, rb, ctx):
    for f in _FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(ra, f)),
                                      np.asarray(getattr(rb, f)),
                                      err_msg=f"{ctx}:{f}")


@pytest.mark.parametrize("bp", [False, True])
@pytest.mark.parametrize("churn", [0.0, 0.05])
def test_sir_fuse_bitwise_parity(bp, churn):
    """Fused == solo count_pass, bit for bit, on both overlay families
    with and without churn, prefetch on and off."""
    ra = _mk(bp, 0, churn).run(8)
    rb = _mk(bp, 1, churn).run(8)
    rc = _mk(bp, 1, churn, prefetch=2).run(8)
    _assert_bitwise(ra, rb, f"bp={bp} churn={churn}")
    _assert_bitwise(rb, rc, f"bp={bp} churn={churn} prefetch")


def test_sir_fuse_pressure_plane_exact():
    """The kernel-level contract underneath the trajectories: one
    fused pass's pressure output equals the solo count_pass integers
    on the same inputs — not statistically, exactly."""
    from p2p_gossipprotocol_tpu.ops.aligned_kernel import (count_pass,
                                                           gossip_pass)

    rng = np.random.default_rng(11)
    R, C, D, blk = 64, 128, 6, 8
    flag = jnp.asarray(
        np.where(rng.random((R, C)) < 0.3, -1, 0).astype(np.int32))
    colidx = jnp.asarray(rng.integers(0, C, size=(D, R, C), dtype=np.int8))
    gate = jnp.asarray(rng.integers(1, D + 1, size=(R, C), dtype=np.int8))
    rolls = jnp.asarray(rng.integers(0, R // blk, size=D, dtype=np.int32))
    subrolls = jnp.asarray(rng.integers(0, blk, size=D, dtype=np.int32))
    solo = count_pass(flag, colidx, gate, rolls, subrolls, rowblk=blk,
                      interpret=True)
    _, fused = gossip_pass(flag[None], colidx, gate, rolls, subrolls,
                           press=True, rowblk=blk, interpret=True)
    np.testing.assert_array_equal(np.asarray(solo), np.asarray(fused))


def test_sir_fuse_sharded_parity(devices8):
    """The sharded SIR engine inherits the fused path through the
    shared aligned_sir_round — bitwise-equal to the solo fused run."""
    from p2p_gossipprotocol_tpu.parallel import (AlignedShardedSIRSimulator,
                                                 make_mesh)

    topo = build_aligned(seed=3, n=8192, n_slots=8,
                         degree_law="powerlaw", roll_groups=2,
                         n_shards=8, block_perm=True)
    kw = dict(topo=topo, beta=0.4, gamma=0.1, n_seeds=4,
              churn=ChurnConfig(rate=0.05), seed=7)
    base = AlignedSIRSimulator(sir_fuse=0, **kw).run(6)
    sh = AlignedShardedSIRSimulator(mesh=make_mesh(8), sir_fuse=1,
                                    prefetch_depth=2, **kw).run(6)
    _assert_bitwise(base, sh, "sharded-fused")


def test_sir_fuse_auto_and_config(tmp_path):
    """-1 resolves off under interpret (the frontier_mode rule) and the
    key reaches the engine from a config file alone."""
    from p2p_gossipprotocol_tpu.config import NetworkConfig

    auto = _mk(True, -1)
    assert auto.interpret and not auto._fuse
    assert _mk(True, 1)._fuse and _mk(False, 1)._fuse
    with pytest.raises(ValueError, match="sir_fuse"):
        _mk(True, 2)
    p = tmp_path / "net.txt"
    p.write_text("10.0.0.1:9000\nbackend=jax\nengine=aligned\n"
                 "n_peers=4096\nmode=sir\nsir_fuse=1\nblock_perm=1\n")
    sim = AlignedSIRSimulator.from_config(NetworkConfig(str(p)))
    assert sim.sir_fuse == 1 and sim._fuse
    assert sim.topo.ytab is not None


def test_sir_fuse_model_deletes_the_prep_stream():
    """The traffic model's round-10 claim, pinned: on a block-perm
    overlay the fused round's prep term is ZERO (the deleted second
    stream) and the whole fused round costs at most 1.3x one kernel
    stream — vs the solo round's prep + kernel pair."""
    solo = _mk(True, 0).traffic_model()
    fused = _mk(True, 1).traffic_model()
    assert solo["prep"] > 0 and fused["prep"] == 0
    # fused adds only the riding OR plane to the kernel stream
    plane = _mk(True, 1).topo.rows * 128 * 4
    assert fused["count_pass"] == solo["count_pass"] + plane
    assert fused["total"] <= 1.3 * solo["count_pass"]
    assert fused["total"] < solo["total"]
    # row-perm keeps the host-side permute: prep stays, honestly
    assert _mk(False, 1).traffic_model()["prep"] > 0
