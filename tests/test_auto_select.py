"""from_config fused-overlay AUTO-selection (round-6 tentpole).

`block_perm=-1` — the config default — makes `AlignedSimulator
.from_config` pick the block-granular fused overlay exactly where the
round-5 on-chip A/Bs measured it best: wide message sets (W >=
aligned.AUTO_BLOCK_PERM_MIN_WORDS, the -43% ms/round regime at 1M x
256), push/pushpull modes, and a roll grouping that can express a
block-level overlay.  Narrow sets keep the row-perm family (a wash at
W=1).  Illegal explicit combinations DEGRADE with a recorded clamp —
never a silent weakening, never an errored run — and the selection
flows through engines.build_simulator onto both sharded engines
unchanged (they lift the resolved fields).
"""
import numpy as np
import pytest

from p2p_gossipprotocol_tpu import autostatics as auto
from p2p_gossipprotocol_tpu.aligned import (AUTO_BLOCK_PERM_MIN_WORDS,
                                            AlignedSimulator,
                                            n_msg_words)
from p2p_gossipprotocol_tpu.config import NetworkConfig

BASE = "10.0.0.1:9000\nbackend=jax\nengine=aligned\nn_peers=8192\n"


def _cfg(tmp_path, extra=""):
    p = tmp_path / "net.txt"
    p.write_text(BASE + extra)
    return NetworkConfig(str(p))


def test_default_is_auto(tmp_path):
    assert _cfg(tmp_path).block_perm == -1


def test_wide_w_auto_selects_block_perm(tmp_path):
    """256 messages (W=8) + pushpull + grouped rolls: the product path
    is the fused overlay, zero knobs."""
    cfg = _cfg(tmp_path, "n_messages=256\nmode=pushpull\n")
    clamps = []
    sim = AlignedSimulator.from_config(cfg, clamps=clamps)
    assert n_msg_words(sim.n_msgs) >= AUTO_BLOCK_PERM_MIN_WORDS
    assert sim.topo.ytab is not None
    assert clamps == []           # a selection is not a clamp


def test_narrow_w_keeps_row_perm(tmp_path):
    """16 messages (W=1): measured a wash — row-perm stays."""
    cfg = _cfg(tmp_path, "n_messages=16\nmode=pushpull\n")
    sim = AlignedSimulator.from_config(cfg)
    assert sim.topo.ytab is None


def test_explicit_off_is_honored(tmp_path):
    cfg = _cfg(tmp_path, "n_messages=256\nmode=pushpull\nblock_perm=0\n")
    sim = AlignedSimulator.from_config(cfg)
    assert sim.topo.ytab is None


def test_pure_pull_auto_keeps_classic_path(tmp_path):
    """Auto never puts pure pull on a fused overlay (the windowed pull
    default would be confined to one block cycle)."""
    cfg = _cfg(tmp_path, "n_messages=256\nmode=pull\n")
    sim = AlignedSimulator.from_config(cfg)
    assert sim.topo.ytab is None and sim.pull_window is True


def test_block_perm_single_roll_degrades_with_clamp(tmp_path):
    """Explicit block_perm=1 + roll_groups=1: build_aligned would stall
    on a single permutation cycle; the config surface degrades to the
    row-perm overlay and RECORDS it."""
    cfg = _cfg(tmp_path, "n_messages=256\nmode=pushpull\n"
                         "block_perm=1\nroll_groups=1\n")
    clamps = []
    sim = AlignedSimulator.from_config(cfg, clamps=clamps)
    assert sim.topo.ytab is None
    assert any("block_perm" in c and "roll_groups" in c for c in clamps)


def test_pull_on_block_perm_degrades_pull_window_with_clamp(tmp_path):
    """Explicit block_perm=1 + mode=pull (pull_window defaulted on):
    the window falls back to classic pull, recorded."""
    cfg = _cfg(tmp_path, "n_messages=256\nmode=pull\nblock_perm=1\n")
    clamps = []
    sim = AlignedSimulator.from_config(cfg, clamps=clamps)
    assert sim.topo.ytab is not None and sim.pull_window is False
    assert any("pull_window" in c for c in clamps)


def test_small_w_widens_row_block(tmp_path):
    """The VMEM budget sizing: narrow message sets get wide row blocks
    (fewer grid steps, longer DMA streams), wide sets shrink them."""
    narrow = AlignedSimulator.from_config(
        _cfg(tmp_path, "n_peers=1048576\nn_messages=16\nmode=pushpull\n"))
    wide = AlignedSimulator.from_config(
        _cfg(tmp_path, "n_peers=1048576\nn_messages=256\nmode=pushpull\n"))
    assert narrow.topo.rowblk == 2048
    assert wide.topo.rowblk == 512
    # both respect the kernel budget
    assert narrow.n_words * narrow.topo.rowblk <= 4096
    assert wide.n_words * wide.topo.rowblk <= 4096


@pytest.mark.parametrize("mesh", ["1d", "2d"])
def test_sharded_engines_follow_the_selection(tmp_path, devices8, mesh):
    """engines.build_simulator lifts the resolved fields, so both
    sharded variants run the SAME auto-selected fused overlay — and
    stay bitwise-equal to the unsharded engine on it."""
    from p2p_gossipprotocol_tpu.engines import build_simulator

    extra = ("n_messages=256\nmode=pushpull\nmesh_devices=4\n"
             + ("msg_shards=2\n" if mesh == "2d" else ""))
    cfg = _cfg(tmp_path, extra)
    sim, name = build_simulator(cfg)
    assert sim.topo.ytab is not None, name
    assert name.startswith("aligned-2d" if mesh == "2d"
                           else "aligned-sharded")
    base = AlignedSimulator.from_config(cfg, n_shards=4)
    assert base.topo.ytab is not None
    ra, rb = base.run(3), sim.run(3)
    np.testing.assert_array_equal(np.asarray(ra.state.seen_w),
                                  np.asarray(rb.state.seen_w))
    np.testing.assert_array_equal(np.asarray(ra.coverage),
                                  np.asarray(rb.coverage))


def test_sharded_engines_follow_the_degrade(tmp_path, devices8):
    """The degrade-with-clamp seam reaches the sharded engines through
    the same lift: an illegal explicit combo lands every engine on the
    row-perm overlay with the clamp recorded once."""
    from p2p_gossipprotocol_tpu.engines import build_simulator

    cfg = _cfg(tmp_path, "n_messages=256\nmode=pushpull\nblock_perm=1\n"
                         "roll_groups=1\nmesh_devices=4\n")
    clamps = []
    sim, _ = build_simulator(cfg, clamps=clamps)
    assert sim.topo.ytab is None
    assert any("block_perm" in c for c in clamps)


# ------------------------------------------------------------------
# Every -1-auto static, through the config surface: an explicit value
# is honoured, and -1 gives the value of its rule in autostatics.py.

def _aligned(cfg):
    return AlignedSimulator.from_config(cfg)


def _sir(cfg):
    from p2p_gossipprotocol_tpu.aligned_sir import AlignedSIRSimulator

    return AlignedSIRSimulator.from_config(cfg)


def _serve(cfg):
    from p2p_gossipprotocol_tpu.serve.service import GossipService

    return GossipService(cfg)


def _realgraph(cfg):
    from p2p_gossipprotocol_tpu.realgraph import RealGraphSimulator

    return RealGraphSimulator.from_config(cfg)


#: static -> (extra config, builder, reader of the resolved value,
#: an explicit value and what it resolves to, the rule's -1 value as a
#: function of the built object)
AUTO_CASES = {
    "frontier_mode": (
        "n_messages=16\nmode=pushpull\n", _aligned,
        lambda s: (s.frontier_mode, s._frontier_delta), 1, (1, True),
        lambda s: (int(auto.heuristic_on(-1, s.interpret)),
                   auto.heuristic_on(-1, s.interpret))),
    "frontier_algo": (
        "n_messages=16\n", _aligned,
        lambda s: (s.frontier_algo, s._frontier_algo), 1, (1, True),
        lambda s: (int(auto.heuristic_on(-1, s.interpret)),
                   auto.heuristic_on(-1, s.interpret))),
    "frontier_threshold": (
        "n_messages=16\n", _aligned,
        lambda s: s.frontier_threshold, 0.5, 0.5,
        lambda s: auto.FRONTIER_THRESHOLD_DEFAULT),
    "prefetch_depth": (
        "n_messages=16\n", _aligned,
        lambda s: (s.prefetch_depth, s._prefetch), 2, (2, 2),
        lambda s: (auto.heuristic_prefetch(-1, s.interpret),) * 2),
    "overlap_mode": (
        "n_messages=256\nmode=pushpull\n", _aligned,
        lambda s: (s.overlap_mode, s._overlap), 1, (1, True),
        lambda s: (int(auto.heuristic_on(-1, s.interpret)),
                   auto.heuristic_on(-1, s.interpret))),
    "hier_mode": (
        "n_messages=16\n", _aligned,
        lambda s: s.hier_mode, 1, 1,
        lambda s: int(auto.heuristic_on(-1, s.interpret))),
    "sir_fuse": (
        "mode=sir\nblock_perm=1\n", _sir,
        lambda s: (s.sir_fuse, s._fuse), 1, (1, True),
        lambda s: (int(auto.heuristic_sir_fuse(-1, s.interpret, True)),
                   auto.heuristic_sir_fuse(-1, s.interpret, True))),
    "serve_chunk": (
        "n_messages=8\nrounds=24\nserve_slots=2\n", _serve,
        lambda s: s.chunk, 5, 5,
        lambda s: auto.SERVE_CHUNK_DEFAULT),
    "realgraph_pack_width": (
        "", _realgraph,
        lambda s: s._pack_width, 64, 64,
        lambda s: auto.REALGRAPH_PACK_WIDTH_DEFAULT),
    "realgraph_scatter": (
        "", _realgraph,
        lambda s: s._scatter, 1, 1,
        lambda s: auto.heuristic_realgraph_scatter(-1, s._dst_static)),
}


@pytest.mark.parametrize("static", sorted(AUTO_CASES))
def test_auto_static_explicit_wins_and_auto_takes_the_rule(tmp_path,
                                                          static):
    extra, build, read, explicit, want, rule = AUTO_CASES[static]
    if build is _realgraph:
        from p2p_gossipprotocol_tpu.realgraph import (rmat_edges,
                                                      write_edge_file)

        gf = str(tmp_path / "graph.txt")
        write_edge_file(gf, *rmat_edges(7, 800, seed=5))
        extra = f"n_messages=4\ngraph_file={gf}\n"
    assert getattr(_cfg(tmp_path, extra), static) == -1   # the default
    forced = build(_cfg(tmp_path, extra + f"{static}={explicit}\n"))
    assert read(forced) == want
    built = build(_cfg(tmp_path, extra + f"{static}=-1\n"))
    assert read(built) == rule(built)
