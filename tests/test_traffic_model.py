"""Traffic-model calibration guard (round-6 satellite).

`AlignedSimulator.traffic_model()` is the analytic HBM model behind
every `achieved_gb_s` the repo publishes.  Its kernel terms replay the
grid's DMA-descriptor sequence (`ops.aligned_kernel.stream_plan`) and
charge resident-buffer re-serves the topology's calibrated
``reuse_leak`` fraction.  These tests pin the model to an INDEPENDENT
closed-form recount of the documented terms (docs/PERFORMANCE.md
"Calibrating the y term") within the documented ~20% per-term
tolerance, on the CPU bench path — so a kernel edit that adds or
removes a stream cannot silently re-break the model: stream_plan sits
next to the BlockSpecs it describes, and this suite fails if its
totals drift from the documented accounting.
"""
import numpy as np
import pytest

from p2p_gossipprotocol_tpu.aligned import (AlignedSimulator, Y_REUSE_LEAK,
                                            build_aligned)
from p2p_gossipprotocol_tpu.liveness import ChurnConfig
from p2p_gossipprotocol_tpu.ops.aligned_kernel import stream_plan

TOLERANCE = 0.20          # the documented per-term model tolerance


def _sim(n=1 << 16, n_msgs=64, mode="pushpull", **kw):
    build_kw = {k: kw.pop(k) for k in ("roll_groups", "block_perm",
                                       "rowblk", "reuse_leak")
                if k in kw}
    topo = build_aligned(seed=0, n=n, n_slots=16, degree_law="powerlaw",
                         n_msgs=n_msgs, **build_kw)
    return AlignedSimulator(topo=topo, n_msgs=n_msgs, mode=mode, **kw)


def _closed_form_pass(sim, n_slots_d, final=False, seeded=False):
    """Independent recount of one gossip pass from the documented
    per-term table: y planes per effective stream, colidx once, gate
    once, accumulator out; fused adds src_ok per y fetch; the final
    fused-update pass adds seen in/out, rmask + ok planes and the
    census partial tiles."""
    topo = sim.topo
    R, C, W = topo.rows, 128, sim.n_words
    blk = topo.rowblk
    T = R // blk
    plane = R * C * 4
    plan = stream_plan(np.asarray(topo.rolls), T,
                       ytab=(None if topo.ytab is None
                             else np.asarray(topo.ytab)),
                       n_slots=n_slots_d)
    eff = plan["y"] + topo.reuse_leak * (plan["y_naive"] - plan["y"])
    wb = blk * C * 4
    b = eff * W * wb + n_slots_d * R * C + R * C + W * plane
    if topo.ytab is not None:
        b += eff * wb
    if final:
        b += 2 * W * plane + 2 * plane + 2 * T * 8 * C * 4
    if seeded:
        b += W * plane
    return b


@pytest.mark.parametrize("roll_groups,block_perm", [
    (None, False), (4, False), (1, False), (4, True), (2, True)])
def test_pass_terms_match_closed_form(roll_groups, block_perm):
    sim = _sim(roll_groups=roll_groups, block_perm=block_perm)
    terms = sim.traffic_model()
    D = sim.topo.n_slots
    for key, slots in (("push_pass", D), ("pull_pass", sim._pull_slots)):
        expect = _closed_form_pass(sim, slots)
        assert abs(terms[key] - expect) <= TOLERANCE * expect, (
            key, terms[key], expect)


def test_fused_update_pass_terms():
    sim = _sim(roll_groups=2, block_perm=True, fuse_update=True,
               rowblk=256)
    terms = sim.traffic_model()
    expect = _closed_form_pass(sim, sim._pull_slots, final=True,
                               seeded=True)
    assert abs(terms["pull_pass"] - expect) <= TOLERANCE * expect
    # the in-kernel census deletes the 2W-plane metrics re-read: the
    # remaining XLA metrics term is the small per-peer planes only
    assert terms["update"] == 0
    assert terms["metrics"] <= 2 * sim.topo.rows * 128 * 4


def test_calibrated_reuse_is_bounded_by_the_extremes():
    """The calibrated y term sits strictly between the perfect-reuse
    floor (leak=0) and the no-reuse ceiling (leak=1), and the default
    calibration equals the documented constant."""
    floor = _sim(roll_groups=4, reuse_leak=0.0).hbm_bytes_per_round()
    cal = _sim(roll_groups=4).hbm_bytes_per_round()
    ceil = _sim(roll_groups=4, reuse_leak=1.0).hbm_bytes_per_round()
    assert floor < cal < ceil
    assert _sim().topo.reuse_leak == Y_REUSE_LEAK == 0.43


def test_pull_window_cuts_the_pull_pass_only():
    # rowblk 64 -> 8 row blocks, so the 4 roll groups are really
    # distinct (one block is one roll and the window is 4 of 16 slots)
    a = _sim(roll_groups=4, rowblk=64).traffic_model()
    b = _sim(roll_groups=4, rowblk=64, pull_window=True).traffic_model()
    assert b["pull_pass"] < a["pull_pass"]
    assert b["push_pass"] == a["push_pass"]


def test_liveness_amortizes_with_stride():
    """The sweep's bytes, pinned: the alive plane per effective stream,
    the lane table and strikes read and written in place, the per-block
    eviction tiles written and reduced, the row-perm prep; every third
    round at stride 3."""
    k1 = _sim(churn=ChurnConfig(rate=0.05), liveness_every=1)
    k3 = _sim(churn=ChurnConfig(rate=0.05), liveness_every=3)
    t1, t3 = k1.traffic_model(), k3.traffic_model()
    assert t3["liveness"] == t1["liveness"] // 3
    topo = k1.topo
    R, D, C, blk = topo.rows, topo.n_slots, 128, topo.rowblk
    T = R // blk
    plan = stream_plan(np.asarray(topo.rolls), T)
    eff = plan["y"] + topo.reuse_leak * (plan["y_naive"] - plan["y"])
    assert topo.ytab is None
    assert t1["liveness"] == (eff * blk * C * 4 + 4 * D * R * C
                              + 2 * T * 8 * C * 4 + 3 * R * C * 4) // 1


def test_resident_src_ok_is_read_once():
    """A fused push on the manual stream holds src_ok whole in VMEM
    where the plane fits: the model charges it once, not once per y
    fetch.  The pull keeps its per-fetch charge."""
    sim = _sim(roll_groups=4, block_perm=True, prefetch_depth=2, rowblk=64)
    terms = sim.traffic_model()
    topo = sim.topo
    R, C, W, blk = topo.rows, 128, sim.n_words, topo.rowblk
    ytab = np.asarray(topo.ytab)
    push = stream_plan(np.asarray(topo.rolls), R // blk, ytab=ytab)
    assert push["y"] > R // blk           # the push re-reads blocks
    assert terms["push_pass"] == (push["y"] * W * blk * C * 4
                                  + push["tab"] * blk * C
                                  + push["row"] * blk * C
                                  + W * R * C * 4 + R * C * 4)
    pull = stream_plan(np.asarray(topo.rolls), R // blk, ytab=ytab,
                       n_slots=sim._pull_slots)
    assert terms["pull_pass"] == (pull["y"] * (W + 1) * blk * C * 4
                                  + pull["tab"] * blk * C
                                  + pull["row"] * blk * C + W * R * C * 4)


def test_total_is_the_sum_and_feeds_the_bench():
    sim = _sim()
    terms = sim.traffic_model()
    assert terms["total"] == sum(v for k, v in terms.items()
                                 if k != "total")
    assert sim.hbm_bytes_per_round() == terms["total"]


def test_frontier_terms_match_closed_form():
    """Round-8 frontier terms, pinned on BOTH paths: with the feature
    off the model is bit-for-bit the legacy accounting; with it on, the
    push pass replays the skip-gated descriptor stream (dead steps are
    resident re-serves, charged the calibrated leak like any other),
    ``frontier_scan`` charges exactly one read of the send planes, and
    ``delta_gather`` prices the exchange — the compacted (index, word)
    tables below capacity, the dense frontier planes above it."""
    from p2p_gossipprotocol_tpu.aligned import frontier_capacity

    off = _sim(roll_groups=4, rowblk=64)
    on = _sim(roll_groups=4, rowblk=64, frontier_mode=1)
    t_off, t_on = off.traffic_model(), on.traffic_model()
    # off-path parity: identical terms, no frontier keys
    assert "frontier_scan" not in t_off and "delta_gather" not in t_off
    for k in t_off:
        if k != "total":
            assert t_on[k] == t_off[k], k
    W, R, C = on.n_words, on.topo.rows, 128
    wp = W * R * C * 4
    assert t_on["frontier_scan"] == wp
    # skipped-block credit: a post-peak frontier (1% of blocks live)
    # must shrink the push pass within tolerance of the leak-only floor
    t_post = on.traffic_model(frontier_fill=0.01)
    assert t_post["push_pass"] < t_on["push_pass"]
    T, D = R // on.topo.rowblk, on.topo.n_slots
    blk = on.topo.rowblk
    plan0 = stream_plan(np.asarray(on.topo.rolls), T,
                        active=np.zeros(T, bool))
    # leading steps pin to step 0's raw index, which the pipeline (and
    # the round-10 prefetch stream) fetch ONCE even when gated — the
    # replay charges that copy honestly (round-10 drift-guard rule)
    assert plan0["y"] == 1 and plan0["y_skip"] == T * D
    floor = ((1 + on.topo.reuse_leak * (T * D - 1)) * W * blk * C * 4
             + D * R * C + R * C + wp)
    t_zero = on.traffic_model(frontier_fill=0.0)
    assert abs(t_zero["push_pass"] - floor) <= TOLERANCE * floor
    # delta-gather: sparse table below capacity, dense planes above
    S = 8
    L = W * (R // S) * C
    K = frontier_capacity(on.frontier_threshold, L)
    sparse = on.traffic_model(frontier_fill=K / (2 * L), n_shards=S)
    dense = on.traffic_model(frontier_fill=1.0, n_shards=S)
    plane = R * C * 4
    assert sparse["delta_gather"] == S * (2 * K + 1) * 4 + plane
    assert dense["delta_gather"] == wp + plane
    # the acceptance ratio (>= 2x post-peak) needs a realistic message
    # width: the two aux mask planes are W-independent, so at W=2 they
    # dominate both columns; at W=16 the planes do
    wide = _sim(n_msgs=512, roll_groups=4, rowblk=64, frontier_mode=1)
    Lw = wide.n_words * (wide.topo.rows // S) * C
    Kw = frontier_capacity(wide.frontier_threshold, Lw)
    w_sparse = wide.traffic_model(frontier_fill=Kw / (2 * Lw),
                                  n_shards=S)
    w_dense = wide.traffic_model(frontier_fill=1.0, n_shards=S)
    assert w_sparse["delta_gather"] * 2 <= w_dense["delta_gather"]


def test_halving_exchange_matches_closed_form():
    """Round-16 sparse-allreduce terms, pinned on both paths: with
    frontier_algo off the model is bit-for-bit the round-8 accounting
    (no halving keys at all); with it on, ``delta_gather`` charges the
    execution the runtime takes — ``(1 + log2(M))`` capacity tables
    when the merged total fits (the +1 self-table base anchors the
    M=1 degenerate to the gather pricing), the gather fallback when
    only per-shard tables fit, the dense planes above capacity — and
    ``halving_exchange``/``gather_exchange`` report both quotes
    side by side, excluded from ``total`` like the tier split."""
    from p2p_gossipprotocol_tpu.aligned import (frontier_capacity,
                                                halving_steps,
                                                project_exchange)

    S = 8
    gat = _sim(roll_groups=4, rowblk=64, frontier_mode=1)
    hal = _sim(roll_groups=4, rowblk=64, frontier_mode=1,
               frontier_algo=1)
    W, R, C = hal.n_words, hal.topo.rows, 128
    wp, plane = W * R * C * 4, R * C * 4
    L = W * (R // S) * C
    K = frontier_capacity(hal.frontier_threshold, L)
    fit = K / (S * L)                 # merged total == K: fits exactly
    t_g = gat.traffic_model(frontier_fill=fit, n_shards=S)
    t_h = hal.traffic_model(frontier_fill=fit, n_shards=S)
    # off-path parity: no halving keys, same terms
    assert "halving_exchange" not in t_g and "gather_exchange" not in t_g
    # fitted halving round: (1 + log2(S)) tables vs the gather's S
    steps = halving_steps(S)
    assert t_h["delta_gather"] == (1 + steps) * (2 * K + 1) * 4 + plane
    assert t_g["delta_gather"] == S * (2 * K + 1) * 4 + plane
    assert t_h["halving_exchange"] == t_h["delta_gather"]
    assert t_h["gather_exchange"] == t_g["delta_gather"]
    # the acceptance ratio on the table bytes themselves: exactly
    # S / (1 + log2(S)) = 2.0 at 8 shards
    assert (t_h["gather_exchange"] - plane) \
        == 2 * (t_h["halving_exchange"] - plane)
    # per-shard-fits-but-merged-overflows: priced at the gather
    # fallback the runtime executes
    over = hal.traffic_model(frontier_fill=K / (2 * L), n_shards=S)
    assert over["delta_gather"] == S * (2 * K + 1) * 4 + plane
    assert over["halving_exchange"] == over["gather_exchange"]
    # above capacity: both executions are the dense planes
    dense = hal.traffic_model(frontier_fill=1.0, n_shards=S)
    assert dense["delta_gather"] == wp + plane
    # the reporting keys never enter total (the tier-split discipline)
    assert t_h["total"] == sum(
        v for k, v in t_h.items()
        if k not in ("total", "ici_gather", "dcn_gather",
                     "halving_exchange", "gather_exchange"))
    # flat-degenerate: one shard's halving quote == the gather quote
    e1h = project_exchange(n_peers=R * C, n_msgs=hal.n_msgs, n_shards=1,
                           frontier_fill=fit, rows=R, algo=1)
    e1g = project_exchange(n_peers=R * C, n_msgs=hal.n_msgs, n_shards=1,
                           frontier_fill=fit, rows=R, algo=0)
    assert e1h["delta_gather"] == e1g["delta_gather"]
    assert e1h["halving_exchange"] == e1h["gather_exchange"]
    # non-power-of-two member count: structural gather pricing
    e6 = project_exchange(n_peers=R * C, n_msgs=hal.n_msgs, n_shards=6,
                          frontier_fill=0.0001, rows=R, algo=1)
    assert e6["halving_exchange"] == e6["gather_exchange"]


def test_halving_exchange_hier_tiers():
    """Per-tier halving quotes under the 2x4 factorization: the DCN
    tier at H=2 degenerates (one pairwise exchange == one gathered
    table), the ICI tier at D=4 drops from 3 to 2 column tables; both
    fall back per tier when their merged totals overflow."""
    from p2p_gossipprotocol_tpu.aligned import (frontier_capacity,
                                                project_exchange)

    S, H = 8, 2
    D = S // H
    hal = _sim(roll_groups=4, rowblk=64, frontier_mode=1,
               frontier_algo=1)
    W, R, C = hal.n_words, hal.topo.rows, 128
    L = W * (R // S) * C
    K = frontier_capacity(hal.frontier_threshold, L)
    Kc = frontier_capacity(hal.frontier_threshold, L * H)
    sl = (R // S) * C * 4
    fit = K / (S * L)
    eh = project_exchange(n_peers=R * C, n_msgs=hal.n_msgs, n_shards=S,
                          n_hosts=H, frontier_fill=fit, rows=R, algo=1)
    eg = project_exchange(n_peers=R * C, n_msgs=hal.n_msgs, n_shards=S,
                          n_hosts=H, frontier_fill=fit, rows=R, algo=0)
    # DCN: log2(2) = 1 table each way (the H=2 degenerate)
    assert eh["dcn_gather"] == eg["dcn_gather"] \
        == (H - 1) * ((2 * K + 1) * 4 + sl)
    # ICI: log2(4) = 2 column tables vs the gather's D-1 = 3
    assert eh["ici_gather"] == 2 * (2 * Kc + 1) * 4 + (D - 1) * H * sl
    assert eg["ici_gather"] == 3 * (2 * Kc + 1) * 4 + (D - 1) * H * sl
    assert eh["delta_gather"] == eh["dcn_gather"] + eh["ici_gather"]
    assert eh["halving_exchange"] == eh["delta_gather"]
    assert eh["gather_exchange"] == eg["delta_gather"]
    # a sim whose RESOLVED statics are hier+halving prices this via
    # traffic_model directly
    h_sim = _sim(roll_groups=4, rowblk=64, frontier_mode=1,
                 frontier_algo=1, hier_hosts=H, hier_devs=D,
                 hier_mode=1)
    th = h_sim.traffic_model(frontier_fill=fit, n_shards=S)
    assert th["dcn_gather"] == eh["dcn_gather"]
    assert th["ici_gather"] == eh["ici_gather"]
    # the 1B x 256 budget (ROADMAP item 4) under O(merged): the
    # halving DCN quote sits well under the gather one at 64 hosts
    b_h = project_exchange(n_peers=1 << 30, n_msgs=256, n_shards=256,
                           n_hosts=64, frontier_fill=0.0001, fused=True,
                           algo=1)
    b_g = project_exchange(n_peers=1 << 30, n_msgs=256, n_shards=256,
                           n_hosts=64, frontier_fill=0.0001, fused=True,
                           algo=0)
    assert b_g["dcn_gather"] >= 2 * b_h["dcn_gather"]


def test_hier_tier_terms_match_closed_form():
    """Round-11 per-tier terms, pinned closed-form on both paths.

    Flat-mesh degenerate case: the tier split exists but everything
    rides the fast tier — ``dcn_gather == 0``, ``ici_gather`` equals
    the whole exchange, and the TOTALS are bit-for-bit today's model
    (the tier keys are a decomposition, excluded from ``total`` like
    ``overlap_hidden``).  Hierarchical case: the DCN tier moves H-1
    per-device tables per chip (vs the flat exchange's S-D — the
    D-fold redundant inter-host delivery the hierarchy deletes), the
    ICI tier D-1 column tables under its own capacity, and the
    non-fused mask plane is staged the same way."""
    from p2p_gossipprotocol_tpu.aligned import (frontier_capacity,
                                                project_exchange)

    S, H = 8, 2
    D = S // H
    on = _sim(roll_groups=4, rowblk=64, frontier_mode=1)
    W, R, C = on.n_words, on.topo.rows, 128
    L = W * (R // S) * C
    K = frontier_capacity(on.frontier_threshold, L)
    Kc = frontier_capacity(on.frontier_threshold, L * H)
    plane = R * C * 4
    sl = (R // S) * C * 4
    fill = K / (2 * L)
    # flat degenerate: dcn == 0, ici == delta, total matches today's
    flat = on.traffic_model(frontier_fill=fill, n_shards=S)
    assert flat["dcn_gather"] == 0
    assert flat["ici_gather"] == flat["delta_gather"] \
        == S * (2 * K + 1) * 4 + plane
    assert flat["total"] == sum(
        v for k, v in flat.items()
        if k not in ("total", "ici_gather", "dcn_gather"))
    assert flat["total"] == on.traffic_model(
        frontier_fill=fill, n_shards=S, n_hosts=1)["total"]
    # hierarchical: per-tier closed forms (sparse regime)
    hier = on.traffic_model(frontier_fill=fill, n_shards=S, n_hosts=H)
    assert hier["dcn_gather"] == (H - 1) * ((2 * K + 1) * 4 + sl)
    assert hier["ici_gather"] == (D - 1) * ((2 * Kc + 1) * 4 + H * sl)
    assert hier["delta_gather"] == hier["ici_gather"] \
        + hier["dcn_gather"]
    # dense regime: H-1 device slices over DCN, D-1 column planes ICI
    dense = on.traffic_model(frontier_fill=1.0, n_shards=S, n_hosts=H)
    assert dense["dcn_gather"] == (H - 1) * (L * 4 + sl)
    assert dense["ici_gather"] == (D - 1) * H * (L * 4 + sl)
    # the projector is THE shared closed form, and its flat-DCN column
    # carries the acceptance ratio: >= 2x post-peak (expected ~D)
    ex = project_exchange(n_peers=R * C, n_msgs=on.n_msgs, n_shards=S,
                          n_hosts=H, frontier_fill=fill,
                          threshold=on.frontier_threshold, rows=R)
    assert ex["dcn_gather"] == hier["dcn_gather"]
    assert ex["ici_gather"] == hier["ici_gather"]
    assert ex["flat_dcn"] == (S - D) * ((2 * K + 1) * 4 + sl)
    assert ex["flat_dcn"] >= 2 * ex["dcn_gather"]
    # a sim whose RESOLVED hier statics are on prices hier by default
    h_sim = _sim(roll_groups=4, rowblk=64, frontier_mode=1,
                 hier_hosts=H, hier_devs=D, hier_mode=1)
    assert h_sim.traffic_model(frontier_fill=fill, n_shards=S) == hier
    # ... and hier_mode=0 (flat exchange really runs) prices flat
    h_off = _sim(roll_groups=4, rowblk=64, frontier_mode=1,
                 hier_hosts=H, hier_devs=D, hier_mode=0)
    assert h_off.traffic_model(frontier_fill=fill, n_shards=S) == flat


def test_project_exchange_1b_budget():
    """The 1B-peer projection (ROADMAP item 1): finite closed-form
    per-tier GB/round with no topology build, hier DCN well under the
    flat exchange's."""
    from p2p_gossipprotocol_tpu.aligned import project_exchange

    ex = project_exchange(n_peers=1 << 30, n_msgs=256, n_shards=256,
                          n_hosts=64, frontier_fill=0.001, fused=True)
    assert 0 < ex["dcn_gather"] < ex["flat_dcn"]
    assert ex["flat_dcn"] >= 2 * ex["dcn_gather"]
    assert ex["delta_gather"] == ex["ici_gather"] + ex["dcn_gather"]


def test_overlap_terms_match_closed_form():
    """Round-10 overlap terms, pinned on both paths: off keeps the
    legacy accounting bit-for-bit; on charges the split's honest extra
    (a second table/gate grid walk + the acc_init round-trip) inside
    ``total`` and moves the exchange bytes to ``overlap_hidden`` —
    reported but EXCLUDED from total (the split takes them off the
    critical path; excluding them only lowers achieved_gb_s and
    roofline_frac, the conservative direction)."""
    from p2p_gossipprotocol_tpu.aligned import frontier_capacity

    off = _sim(roll_groups=4, rowblk=64, block_perm=True)
    on = _sim(roll_groups=4, rowblk=64, block_perm=True, overlap_mode=1)
    S = 8
    t_off, t_on = off.traffic_model(n_shards=S), \
        on.traffic_model(n_shards=S)
    assert "overlap_extra" not in t_off and "overlap_hidden" not in t_off
    for k in t_off:
        if k != "total":
            assert t_on[k] == t_off[k], k
    R, C, W = on.topo.rows, 128, on.n_words
    blk = on.topo.rowblk
    T = R // blk
    D = on.topo.n_slots
    wp = W * R * C * 4
    assert t_on["overlap_extra"] == T * D * blk * C + T * blk * C + 2 * wp
    # dense sharded exchange: the hidden bytes are the frontier-plane
    # gather the model never charged to HBM — reported, not totaled
    assert t_on["overlap_hidden"] == wp
    assert t_on["total"] == sum(v for k, v in t_on.items()
                                if k not in ("total", "overlap_hidden"))
    # frontier path: the delta_gather bytes MOVE to overlap_hidden
    fr = _sim(roll_groups=4, rowblk=64, block_perm=True, frontier_mode=1,
              overlap_mode=1)
    fr_off = _sim(roll_groups=4, rowblk=64, block_perm=True,
                  frontier_mode=1)
    L = W * (R // S) * C
    K = frontier_capacity(fr.frontier_threshold, L)
    t_fr = fr.traffic_model(frontier_fill=K / (2 * L), n_shards=S)
    t_fr_off = fr_off.traffic_model(frontier_fill=K / (2 * L), n_shards=S)
    assert "delta_gather" not in t_fr
    assert t_fr["overlap_hidden"] == t_fr_off["delta_gather"] \
        == S * (2 * K + 1) * 4
    # solo (n_shards=1) and row-perm overlays never grow the terms
    assert "overlap_extra" not in on.traffic_model()
    assert "overlap_extra" not in _sim(
        roll_groups=4, rowblk=64, overlap_mode=1).traffic_model(
        n_shards=S)


def test_prefetch_leak_is_zero_by_construction():
    """The manual stream issues no descriptor for a resident re-serve,
    so its modeled pass bytes equal the leak=0 floor exactly — while
    the liveness pass (still BlockSpec-pipelined) keeps the calibrated
    κ charge."""
    base = _sim(roll_groups=4, churn=ChurnConfig(rate=0.05))
    pref = _sim(roll_groups=4, churn=ChurnConfig(rate=0.05),
                prefetch_depth=2)
    floor = _sim(roll_groups=4, reuse_leak=0.0,
                 churn=ChurnConfig(rate=0.05))
    tb, tp, tf = (s.traffic_model() for s in (base, pref, floor))
    for k in ("push_pass", "pull_pass"):
        assert tp[k] == tf[k] < tb[k], k
    assert tp["liveness"] == tb["liveness"]      # pipelined, keeps κ


def test_sir_model_round10_terms():
    """The SIR model's fused-vs-solo accounting (the measure_round10
    ``sir_fuse_ab`` row reads these numbers): fused deletes the prep
    stream on a block-perm overlay, adds exactly the riding OR plane,
    and lands under 1.3 kernel streams."""
    from p2p_gossipprotocol_tpu.aligned import build_aligned
    from p2p_gossipprotocol_tpu.aligned_sir import AlignedSIRSimulator

    topo = build_aligned(seed=0, n=1 << 16, n_slots=16,
                         degree_law="powerlaw", roll_groups=4,
                         block_perm=True)
    solo = AlignedSIRSimulator(topo=topo, sir_fuse=0, seed=0)
    fused = AlignedSIRSimulator(topo=topo, sir_fuse=1, seed=0)
    ts, tf = solo.traffic_model(), fused.traffic_model()
    plane = topo.rows * 128 * 4
    assert ts["prep"] == 3 * plane and tf["prep"] == 0
    assert tf["count_pass"] == ts["count_pass"] + plane
    assert tf["total"] <= 1.3 * ts["count_pass"]
    assert tf["total"] < ts["total"]
    assert solo.hbm_bytes_per_round() == ts["total"]


def test_stream_plan_replays_the_grid():
    """The replay's dedup rule against a hand-walked grid: contiguous
    equal rolls are served from the resident buffer, and the dedup
    crosses row-block boundaries (the old closed form overcounted
    there)."""
    rolls = np.array([0, 0, 3, 3], np.int32)
    plan = stream_plan(rolls, t_blocks=4)
    # t=0: y blocks 0,0,3,3 -> fetch 0, fetch 3; t=1: 1,1,0,0 -> 1, 0;
    # t=2: 2,2,1,1 -> 2, 1; t=3: 3,3,2,2 -> [3 resumes from t=0? no:
    # last was 1 -> fetch 3, fetch 2] = 8 fetches of 16 grid steps
    assert plan["y"] == 8 and plan["y_naive"] == 16
    # boundary dedup: one shared roll = ONE fetch per wrap cycle
    plan1 = stream_plan(np.array([2, 2, 2, 2], np.int32), t_blocks=4)
    assert plan1["y"] == 4          # one fetch per t, none within t
    # ytab table drives the fused replay
    ytab = np.tile(np.arange(4, dtype=np.int32), (4, 1))
    planf = stream_plan(np.zeros(4, np.int32), t_blocks=4, ytab=ytab)
    assert planf["y"] == 4          # constant down each t's slot loop
