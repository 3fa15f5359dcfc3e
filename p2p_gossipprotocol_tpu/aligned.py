"""Hardware-aligned gossip engine — the scale path (1M-10M peers).

The exact-graph engines (sim.Simulator over an explicit edge list) are the
reference semantics; they hit TPU's per-element gather wall at ~100k peers
(see ops/aligned_kernel.py).  This engine keeps the same *capability* —
random overlay with a configurable degree law, flood-push + anti-entropy
pull, bounded message set, per-round metrics — but samples the overlay
from a hardware-factored family:

    slot d of peer (r, c):  neighbor = ( perm[roll_d(r)], colidx_d[r, c] )

with ``perm`` a uniform random row permutation, ``roll_d`` a random block
roll, and ``colidx_d`` per-peer uniform lane choices.  Marginally each
slot's neighbor is uniform over all peers (perm uniform x lane uniform),
and a peer's D slots give D independent-row draws — the same
power-law-degree / uniform-target family as the reference's overlay
(selectAndConnectPeers, peer.cpp:214-253), with the one structural caveat
that peers sharing a row share their slot-d neighbor *row* (documented;
statistically irrelevant for dissemination — validated against the exact
engine in tests/test_aligned.py).

Messages are bit-packed 32-per-int32-word across W planes, so the whole
network state is one [W, R, 128] word array and dedup-by-OR (the
reference's messageList check, peer.cpp:280-286) is a single bitwise op.
W scales with the configured message count (the reference's per-peer
rumor universe, peer.cpp:357-366) — the engine is no longer capped at 32
messages; the practical ceiling is VMEM (see the rowblk check in
AlignedSimulator.__post_init__) and HBM for the state planes.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from flax import struct

from p2p_gossipprotocol_tpu import autostatics as auto
from p2p_gossipprotocol_tpu import faults as faults_lib
from p2p_gossipprotocol_tpu import telemetry
from p2p_gossipprotocol_tpu.liveness import ChurnConfig
from p2p_gossipprotocol_tpu.ops.aligned_kernel import (LANES, gossip_pass,
                                                       interpret_for,
                                                       liveness_pass,
                                                       neighbor_ids,
                                                       skip_tables)

WORD_BITS = 32
# VMEM ceiling for the gossip kernel: the y and acc blocks are
# int32[W, rowblk, 128] each, double-buffered — keep W * rowblk under
# this budget (4096 * 128 * 4 B * 2 arrays * 2 buffers ≈ 8 MiB of the
# ~16 MiB core VMEM).  build_aligned picks rowblk accordingly.
MAX_WORDS_X_ROWBLK = 4096

# Message ceiling for the config-driven entry points (from_config / CLI):
# 64 int32 planes, far past every BASELINE config.
MAX_CONFIG_MSGS = 2048

# Calibrated partial-reuse leak for the kernels' resident-buffer y reuse
# (round-5 kernel-only microbench, kernel_only_rolls_16/4: grouping 16
# slots into 4 distinct rolls cut kernel time 1.47x where perfect reuse
# predicts 2.3x).  A grid step whose y index repeats the previous step's
# still costs this FRACTION of a full block stream — Mosaic's pipeline
# re-issues part of the copy even for a resident block.  0.43 solves the
# 16-vs-4-roll pair exactly (see docs/PERFORMANCE.md "Calibrating the y
# term"); the 2-roll point measures BETTER than this (leak ~0), so the
# calibrated model errs conservative (more modeled bytes, never fewer).
Y_REUSE_LEAK = 0.43

# Partial-reuse leak on the MANUAL double-buffered stream
# (gossip_pass(prefetch_depth=2)): zero, by construction rather than
# calibration — the κ=0.43 above prices Mosaic's pipeline re-issuing
# part of a copy for a resident block, and the manual stream issues NO
# descriptor for a resident re-serve at all (the copy-start is gated on
# an index CHANGE, the same dedup rule stream_plan replays).  Charging
# 0 here is the CONSERVATIVE direction for everything the model feeds:
# fewer modeled bytes -> lower achieved_gb_s and roofline_frac, so the
# prefetch path can only under-report its own win.  A measured nonzero
# leak (kernel-only rolls 16-vs-4 under prefetch on/off, on the chip)
# would land here with its derivation (docs/PERFORMANCE.md "Round 10").
Y_REUSE_LEAK_PREFETCH = 0.0

# from_config auto-selects the block-perm fused overlay at this message
# width and above: the on-chip A/B (round5_tpu.jsonl) measured -43%
# ms/round at W=8 (256 msgs) and a wash at W=1 (16 msgs) — the deleted
# prep term scales with W, so the crossover sits between.
AUTO_BLOCK_PERM_MIN_WORDS = 4

# Frontier-sparse delta-exchange capacity, as a fraction of each
# shard's packed words.  Epidemic dissemination is frontier-bound: past
# the infection peak the per-round delta collapses to a sliver of the
# planes, yet the dense exchange still moves all of them.  The sparse
# regime ships (global word index, delta word) PAIRS — 2 int32 per
# changed word, vs 1 per word dense — so it only pays below ~L/2
# changed words; 1/64 keeps the sparse gather under ~3% of the dense
# transfer, far enough below breakeven that the compaction/scatter
# overhead can't erase the win, while post-peak rounds (typically
# <0.1% of words changed) fit with orders of magnitude to spare.
FRONTIER_THRESHOLD_DEFAULT = auto.FRONTIER_THRESHOLD_DEFAULT

# from_config's VMEM-budget row-block cap: at small W the budget admits
# blocks far wider than the legacy 512 (W=1 -> 2048 rows/block), which
# quarters the grid steps and the per-step DMA descriptor count — the
# block-sizing lever against the partial-reuse gap the r5 microbench
# exposed.  Capped at 2048 so y+acc (double-buffered) stay within half
# the core VMEM even at W=1.
MAX_CONFIG_ROWBLK = 2048


def n_msg_words(n_msgs: int) -> int:
    """Message planes needed for ``n_msgs`` bit-packed rumors."""
    return -(-n_msgs // WORD_BITS)


def mask_words(n_bits: int, n_planes: int) -> jax.Array:
    """int32[n_planes] with the low ``n_bits`` set across the planes
    (plane w holds messages [32w, 32w+32))."""
    k = np.clip(n_bits - WORD_BITS * np.arange(n_planes), 0, WORD_BITS)
    vals = ((np.uint64(1) << k.astype(np.uint64)) - 1).astype(np.uint32)
    return jnp.asarray(vals.view(np.int32))


def resolve_overlay(cfg, n_peers: int | None = None,
                    clamps: list[str] | None = None
                    ) -> tuple[int, str, int]:
    """(n_peers, degree_law, n_slots) for the aligned overlay family from
    a parsed NetworkConfig — shared by the gossip and SIR config entry
    points (CLI and facade).  Engine ceilings (int8 slot index →
    n_slots ≤ 127) and model substitutions are appended to ``clamps`` —
    never silently weaken the configured scenario (the
    parsed-then-quietly-altered defect class, SURVEY §2-C2).  Raises
    ValueError for an overlay the family cannot express."""
    clamps = clamps if clamps is not None else []
    n = n_peers or cfg.n_peers or len(cfg.seed_nodes)
    if cfg.graph in ("reference", "powerlaw"):
        law = "powerlaw"
    elif cfg.graph == "er":
        law = "regular"        # ER == uniform slot count, the direct analogue
    elif cfg.graph == "ba":
        # Preferential attachment has no aligned analogue; the heavy
        # tail is what matters for dissemination/epidemic dynamics, so
        # substitute the power-law degree family — surfaced, not silent.
        law = "powerlaw"
        clamps.append("graph ba -> aligned power-law degree family "
                      "(preferential attachment has no aligned analogue)")
    else:
        raise ValueError(
            f"the aligned engine supports reference/powerlaw/er/ba "
            f"overlays, not {cfg.graph!r} (use the edges engine)")
    n_slots = cfg.avg_degree or 16
    if n_slots > 127:
        clamps.append(f"avg_degree {n_slots} -> 127 "
                      "(aligned engine slot index is int8)")
        n_slots = 127
    return n, law, n_slots


@struct.dataclass
class AlignedTopology:
    """Static overlay tables (see module docstring for the neighbor map)."""

    perm: jax.Array      # int32[R]        random row permutation
    rolls: jax.Array     # int32[D]        per-slot block-roll offsets
    subrolls: jax.Array  # int32[D]        per-slot sublane roll in-block
    colidx: jax.Array    # int8 [D, R, 128] per-peer lane choices
    deg: jax.Array       # int8 [R, 128]   per-peer in-degree (slot count)
    valid_w: jax.Array   # int32[R, 128]   -1 for real peers, 0 for padding
    n_peers: int = struct.field(pytree_node=False)
    n_slots: int = struct.field(pytree_node=False)
    rowblk: int = struct.field(pytree_node=False)
    #: block-perm overlays only (build_aligned(block_perm=True)):
    #: int32[D, T] composed y-block table ytab[d, t] =
    #: pblock[(t + roll_d) % T].  Its presence switches the engines onto
    #: the FUSED round path — kernels read the raw state planes through
    #: this table (perm∘roll in the BlockSpec index map) with the send
    #: mask ANDed in-kernel, so the per-pass host-side permute+mask prep
    #: (the traffic model's 3W term) does not exist at all.
    ytab: jax.Array | None = None
    #: distinct block rolls the overlay was BUILT with (None = one per
    #: slot).  Static record, not an inference: pull_window's validity
    #: guard reads this — per-slot-roll overlays whose first two rolls
    #: happen to coincide must still be rejected deterministically.
    roll_groups: int | None = struct.field(pytree_node=False,
                                           default=None)
    #: calibrated partial-reuse leak the traffic model charges for grid
    #: steps whose y index repeats the previous step's (Y_REUSE_LEAK has
    #: the measurement); recorded on the topology so a future hardware
    #: recalibration travels with the overlay it was measured on.
    reuse_leak: float = struct.field(pytree_node=False,
                                     default=Y_REUSE_LEAK)

    @property
    def rows(self) -> int:
        return self.perm.shape[0]

    def neighbor_ids(self) -> jax.Array:
        """int32[D, R, 128] composite neighbor map (test/interop bridge)."""
        return neighbor_ids(self.perm, self.rolls, self.subrolls,
                            self.colidx, rowblk=self.rowblk)


def build_aligned(seed: int, n: int, n_slots: int = 16,
                  degree_law: str = "regular",
                  powerlaw_alpha: float = 2.5,
                  rowblk: int = 512, n_shards: int = 1,
                  n_msgs: int = 1,
                  roll_groups: int | None = None,
                  block_perm: bool = False,
                  reuse_leak: float = Y_REUSE_LEAK) -> AlignedTopology:
    """Sample an aligned overlay for ``n`` peers with ``n_slots`` in-edge
    slots per peer.

    ``n_msgs`` only influences the row-block size: the gossip kernel
    keeps int32[W, rowblk, 128] blocks resident in VMEM, so wide message
    sets shrink the block (W * rowblk <= MAX_WORDS_X_ROWBLK).

    degree_law:
      * ``regular``  — every peer listens on all slots (ER-like, average
        degree == n_slots);
      * ``powerlaw`` — the reference's law ``deg = min(cap, n * u^(1/a))``
        (peer.cpp:219-222) with cap = n_slots.

    ``n_shards`` rounds the row count so it splits into equal per-shard
    row-block groups for AlignedShardedSimulator (1 = single-chip layout;
    the tables are identical for any n_shards that divides the rounded
    row count, so a sharded topo also runs unsharded).

    ``roll_groups`` (None = one roll per slot, the fully-random default)
    draws only that many DISTINCT block rolls, assigned to contiguous
    slot groups.  The kernels stream one y block per (row-block, slot);
    consecutive slots sharing a block roll hit the SAME y block, which
    the pallas pipeline detects and serves from the resident VMEM buffer
    instead of re-DMAing — cutting the pass's dominant HBM term from
    n_slots to roll_groups y streams.  Per-slot sublane rolls and lane
    choices still differ, and the row permutation already scrambles rows
    globally, so neighbor draws stay effectively random (convergence
    parity asserted in tests/test_aligned.py).
    """
    if n_slots > 127:
        raise ValueError("n_slots must fit int8 gating (<= 127)")
    rowblk = min(rowblk,
                 max(8, (MAX_WORDS_X_ROWBLK // n_msg_words(n_msgs))
                     // 8 * 8))
    rng = np.random.default_rng(seed)
    rows0 = max(1, -(-n // LANES))
    # Padding peers are black holes (they listen to no one, so slots
    # pointing at them are wasted in-degree) — keep them under ~6% while
    # preferring 8-row (sublane-tile) alignment per shard.  The row-block
    # size is then the largest DIVISOR of the per-shard rows <= rowblk,
    # preferring multiples of 8; choosing a divisor instead of rounding
    # rows up to blk*n_shards is what bounds the padding (rounding up
    # would add ~26% phantom peers at the 10M/64-shard config).
    #
    # No minimum-row floor: a forced 8-row layout at small n makes MOST
    # rows black holes — at n=256 that starved every peer below one live
    # in-neighbor on average and dissemination died entirely (round-3
    # regression test test_aligned.py::test_small_n_converges).
    for align in (8, 4, 2, 1):
        rows = -(-rows0 // (align * n_shards)) * align * n_shards
        if rows - rows0 <= max(rows0 // 16, 0) or align == 1:
            break
    if rows - rows0 > rows0 // 4:
        # >25% black-hole rows silently starves the overlay of live
        # in-neighbors (dissemination stalls well short of coverage) —
        # refuse instead, like every other never-silently-weaken check.
        raise ValueError(
            f"{n} peers fill only {rows0} of the {rows} rows an "
            f"{n_shards}-shard layout needs — the padding rows would eat "
            "most in-edges; use fewer shards or the edge engine")
    local = rows // n_shards
    cap = min(rowblk, local)
    blk = next((d for d in range(cap - cap % 8, 0, -8) if local % d == 0),
               0) or next(d for d in range(cap, 0, -1) if local % d == 0)
    t_blocks = rows // blk

    if block_perm and roll_groups is not None and roll_groups <= 1 \
            and n_slots > 1:
        # With ONE shared block roll the block-level overlay under a
        # block permutation is a single permutation cycle (out-degree
        # 1): dissemination stalls at the cycle-reachable fraction
        # (measured: 25-37% coverage plateau at 262k).  The row-perm
        # family tolerates roll_groups=1 (rows scramble globally);
        # block_perm needs block-level mixing.
        raise ValueError(
            "block_perm needs >= 2 distinct block rolls "
            "(roll_groups >= 2, or None for one per slot)")
    if block_perm:
        # BLOCK-granular permutation: perm permutes whole row blocks, so
        # perm∘roll_d is itself a block map and can ride the kernels'
        # BlockSpec index table (ytab) — the engines then read the raw
        # state planes with NO host-side permute/mask pass per round.
        # Marginals are unchanged (pblock uniform over blocks x subroll
        # uniform over in-block rows x lane uniform over 128 = neighbor
        # row uniform over all rows); the structural caveat coarsens one
        # level: peers sharing a BLOCK share their slot-d neighbor
        # block, so block-level mixing needs >= 2 distinct rolls
        # (convergence parity asserted in tests/test_block_perm.py).
        pblock = rng.permutation(t_blocks).astype(np.int32)
        perm = (pblock[np.arange(rows) // blk] * blk
                + np.arange(rows) % blk).astype(np.int32)
    else:
        pblock = None
        perm = rng.permutation(rows).astype(np.int32)
    n_groups = (n_slots if roll_groups is None
                else max(1, min(roll_groups, n_slots)))
    if block_perm and t_blocks > 1:
        # Distinctness is load-bearing here: with-replacement draws can
        # collide (P=1/t_blocks per pair), and if ALL block rolls
        # coincide the block-level overlay degenerates to the
        # single-cycle stall the roll_groups<=1 guard above rejects.
        # Draw from a permutation so the first min(n_groups, t_blocks)
        # rolls are guaranteed distinct.  (t_blocks == 1 has no block
        # graph at all — subrolls + lanes do all the mixing.)
        distinct = rng.permutation(t_blocks).astype(np.int32)
        group_rolls = distinct[np.arange(n_groups) % t_blocks]
    else:
        group_rolls = rng.integers(0, t_blocks, size=n_groups,
                                   dtype=np.int32)
    rolls = group_rolls[(np.arange(n_slots) * n_groups)
                        // n_slots].astype(np.int32)
    subrolls = rng.integers(0, blk, size=n_slots, dtype=np.int32)
    colidx = rng.integers(0, LANES, size=(n_slots, rows, LANES),
                          dtype=np.int8)
    ytab = None
    if block_perm:
        ytab = pblock[(np.arange(t_blocks)[None, :] + rolls[:, None])
                      % t_blocks].astype(np.int32)

    if degree_law == "regular":
        deg = np.full((rows, LANES), n_slots, np.int8)
    elif degree_law == "powerlaw":
        u = rng.uniform(size=(rows, LANES))
        deg = np.minimum(n_slots,
                         (n * u ** (1.0 / powerlaw_alpha))).astype(np.int8)
        deg = np.maximum(deg, 1)
    else:
        raise ValueError(f"Unknown degree_law: {degree_law}")

    flat = np.arange(rows * LANES).reshape(rows, LANES)
    valid = flat < n
    deg = np.where(valid, deg, 0)             # padding peers listen to no one

    return AlignedTopology(
        perm=jnp.asarray(perm),
        rolls=jnp.asarray(rolls),
        subrolls=jnp.asarray(subrolls),
        colidx=jnp.asarray(colidx),
        deg=jnp.asarray(deg),
        valid_w=jnp.asarray(np.where(valid, -1, 0).astype(np.int32)),
        n_peers=n, n_slots=n_slots, rowblk=blk,
        ytab=None if ytab is None else jnp.asarray(ytab),
        roll_groups=None if roll_groups is None else n_groups,
        reuse_leak=reuse_leak,
    )


#: array leaves of AlignedTopology, in canonical-checkpoint order
#: (``ytab`` is optional and rides separately — see canonical_topo).
ALIGNED_TOPO_LEAVES = ("perm", "rolls", "subrolls", "colidx", "deg",
                       "valid_w")


def canonical_topo(topo: AlignedTopology) -> tuple[dict, dict]:
    """(arrays, meta) — the layout-free host form of an aligned overlay.
    ``arrays`` maps leaf name -> numpy (device_get gathers sharded
    leaves to their global view); ``meta`` records the static fields a
    reader needs to rebuild the identical AlignedTopology.  The
    canonicalize half of the elastic-checkpoint contract
    (utils/checkpoint.py): any aligned engine whose layout divides the
    recorded ``rowblk`` grid can restore and continue bitwise."""
    arrays = {k: np.asarray(jax.device_get(getattr(topo, k)))
              for k in ALIGNED_TOPO_LEAVES}
    if topo.ytab is not None:
        arrays["ytab"] = np.asarray(jax.device_get(topo.ytab))
    meta = {"n_peers": topo.n_peers, "n_slots": topo.n_slots,
            "rowblk": topo.rowblk, "roll_groups": topo.roll_groups,
            "reuse_leak": topo.reuse_leak}
    return arrays, meta


def topo_from_canonical(arrays: dict, meta: dict) -> AlignedTopology:
    """Rebuild an AlignedTopology from :func:`canonical_topo` output.
    The checkpoint's statics WIN over whatever the reader's config
    would have built — ``rowblk`` shapes the block-roll neighbor map,
    so continuing bitwise requires the writer's grid, not the
    reader's."""
    ytab = arrays.get("ytab")
    return AlignedTopology(
        **{k: jnp.asarray(arrays[k]) for k in ALIGNED_TOPO_LEAVES},
        ytab=None if ytab is None else jnp.asarray(ytab),
        n_peers=int(meta["n_peers"]), n_slots=int(meta["n_slots"]),
        rowblk=int(meta["rowblk"]),
        roll_groups=(None if meta.get("roll_groups") is None
                     else int(meta["roll_groups"])),
        reuse_leak=float(meta.get("reuse_leak", Y_REUSE_LEAK)))


@struct.dataclass
class AlignedState:
    """Bit-packed network state.  Maps to the edge engine's GossipState
    (state.py:34-51): ``seen_w``/``frontier_w`` pack the bool[peers, msgs]
    planes 32-per-word over W int32 planes (message m lives at bit m%32 of
    plane m//32), ``alive_b``/``byz_w`` are the liveness and adversary
    masks, ``strikes`` the per-slot consecutive-dead counters (the
    vectorized 3-strike rule, reference peer.cpp:335-339) — present
    only when liveness is enabled (None otherwise, an empty pytree leaf)."""

    seen_w: jax.Array      # int32[W, R, 128]  bit j of plane w = rumor 32w+j
    frontier_w: jax.Array  # int32[W, R, 128]  first heard last round
    alive_b: jax.Array     # bool [R, 128]  liveness mask
    byz_w: jax.Array       # int32[R, 128]  -1 = byzantine peer, 0 honest
    strikes: jax.Array | None   # int8[D, R, 128] or None
    key: jax.Array
    round: jax.Array


@struct.dataclass
class FrontierCarry:
    """Scan carry of the frontier-sparse exchange (sharded engines).

    ``replica_w`` is each chip's persistent copy of the UNPERMUTED
    global seen planes (int32[W_local, R_global, 128]); ``regime`` the
    on-device two-regime flag (0 dense / 1 sparse) with hysteresis.
    Both are DERIVED state, deliberately excluded from checkpoints: the
    replica equals the global seen planes at every round boundary (the
    engines initialize it from ``state.seen_w`` — correct for fresh
    AND resumed states alike), and the regime flag never influences the
    trajectory (both regimes are bitwise-identical), so a resume that
    restarts dense re-converges to the same regime on its own — the
    "checkpoints resume bitwise across the regime switch" contract
    costs nothing by construction.  ``replica_w`` is None in pure push
    mode (no pass reads global seen).

    ``byz_g`` (row-perm overlays only): the GATHERED byzantine words —
    the byzantine draw is static for a run, so the frontier path hoists
    its per-round plane gather to ONE gather at carry init; the fused
    path masks through ``src_ok`` and carries None.

    ``regime_ici`` (hierarchical meshes only, round 11): the ICI
    (intra-host) tier's own dense/sparse flag — each tier of the
    two-tier exchange reads its own census and switches independently
    (``regime`` is then the DCN tier's flag, driven by the SAME
    per-device census and capacity as the flat exchange, so the DCN
    regime trajectory is bitwise the flat one's).  Derived state like
    the rest of the carry; None on flat meshes."""

    replica_w: jax.Array | None
    byz_g: jax.Array | None
    regime: jax.Array              # int32 scalar
    regime_ici: jax.Array | None = None


def frontier_capacity(threshold: float, local_words: int) -> int:
    """Compacted delta capacity per shard, in int32 words — the static
    shape of the sparse gather (128-aligned, floored so toy shards
    still have a usable window, capped at the shard's own size)."""
    k = int(threshold * local_words)
    return max(min(128, local_words), min(local_words,
                                          -(-k // 128) * 128))


def resolve_hier(hier_hosts: int, hier_devs: int, peer_shards: int,
                 clamps: list[str] | None = None) -> tuple[int, int]:
    """Resolve a configured ``hier_hosts x hier_devs`` factorization
    against the actual peer-shard count — the one rule every surface
    shares (from_config for the solo/fleet statics, build_simulator
    for each sharded mesh).  Illegal combinations DEGRADE to the flat
    mesh with a recorded clamp (the PR 2 illegal-combo precedent),
    never a crash: the hierarchy changes routing only, so flat is
    always a correct fallback.  Returns ``(hosts, devs)`` — ``(0, 0)``
    for flat."""
    hh, hd = hier_hosts, hier_devs
    if hh <= 1:
        if hd and clamps is not None and hh == 0:
            clamps.append(
                f"hier_devs {hd} without hier_hosts -> flat mesh "
                "(the factorization needs both tiers)")
        return 0, 0
    if peer_shards <= 1:
        if clamps is not None:
            clamps.append(
                f"hier_hosts {hh} on a single-device run -> flat "
                "(the hierarchy factorizes a sharded peer axis)")
        return 0, 0
    if hd == 0:
        hd = peer_shards // hh if peer_shards % hh == 0 else 0
    if hh * hd != peer_shards:
        if clamps is not None:
            clamps.append(
                f"hier_hosts x hier_devs {hier_hosts}x{hier_devs} "
                f"does not factorize the {peer_shards}-shard peer "
                "axis -> flat mesh")
        return 0, 0
    return hh, hd


def project_exchange(n_peers: int, n_msgs: int, n_shards: int,
                     n_hosts: int = 0, frontier_fill: float = 1.0,
                     threshold: float = FRONTIER_THRESHOLD_DEFAULT,
                     fused: bool = False,
                     rows: int | None = None, algo: int = 0) -> dict:
    """Closed-form per-chip interconnect bytes of one round's frontier
    exchange — NO topology needed, so it projects scales no host can
    build (the 1B-peer per-tier byte budget ROADMAP item 1 asks for).
    ``traffic_model`` prices its exchange terms through this function,
    so the model and the projector cannot drift.

    Flat (``n_hosts`` <= 1): everything rides the fast tier —
    ``delta_gather`` is the pre-hierarchy model bit-for-bit (the
    compacted ``(index, word)`` tables below capacity, the dense W
    frontier planes above, plus the alive mask plane on the non-fused
    path) and ``dcn_gather == 0``.

    Hierarchical: the DCN tier moves each device's table/slice once
    per REMOTE HOST (``H-1`` tables of the flat per-device capacity —
    same census, same K), and the ICI tier assembles the ``D`` column
    slices within the host (``D-1`` column tables under the ICI
    capacity, or the dense column planes).  ``flat_dcn`` is what the
    FLAT exchange pushes across the host boundary per chip on the
    same physical layout — ``S-D`` remote tables, the D-fold
    redundant delivery the hierarchy deletes — so
    ``flat_dcn / dcn_gather`` is the round-11 A/B's headline ratio
    (~D post-peak).

    Sparse allreduce (round 16, ``algo=1``): each tier that can run
    the recursive-halving butterfly (power-of-two member count M >= 2)
    is priced per its real execution — when the merged table fits the
    tier's capacity (changed-word total over its members <= K), the
    chip receives ``log2(M)`` tables of ``2K+1`` int32 instead of the
    gather's M (the flat closed form keeps the self-table base term,
    so M=1 degenerates bit-for-bit to the gather pricing); an
    over-total fill is priced at the gather fallback the runtime
    executes.  ``halving_exchange``/``gather_exchange`` report both
    quotes side by side (the measure_round16 A/B's ratio);
    ``delta_gather`` charges whichever ``algo`` selects."""
    C = LANES
    R = rows if rows is not None else -(-n_peers // C)
    W = n_msg_words(n_msgs)
    L = W * (R // n_shards) * C          # packed words per device
    K = frontier_capacity(threshold, L)
    fill = min(max(frontier_fill, 0.0), 1.0)
    changed = int(fill * L)
    sparse = changed <= K
    sl = (R // n_shards) * C * 4         # one device's mask-plane slice
    wp, plane = W * R * C * 4, R * C * 4
    hier = (n_hosts and n_hosts > 1 and n_shards % n_hosts == 0
            and n_hosts < n_shards)
    def tier_halving(m: int, cap: int, tier_total: int, gather_b: int,
                     base: int) -> int:
        # one tier's halving-execution price at this fill (callers
        # invoke it only inside the tier's sparse regime): log2(m)
        # merged tables when the tier's merged total fits its capacity
        # (+ ``base`` self-table terms, the flat form's M=1 degeneracy
        # anchor), else exactly the gather fallback the runtime takes
        steps = halving_steps(m)
        if m < 2 or steps is None:
            return gather_b                      # structural fallback
        if tier_total <= cap:
            return (base + steps) * (2 * cap + 1) * 4
        return gather_b

    if not hier:
        gx = n_shards * (2 * K + 1) * 4 if sparse else wp
        if sparse:
            hx = tier_halving(n_shards, K, changed * n_shards, gx,
                              base=1)
        else:
            hx = wp                               # forced dense
        if not fused:
            gx += plane
            hx += plane
        delta = hx if algo else gx
        out = {"delta_gather": delta, "ici_gather": delta,
               "dcn_gather": 0, "flat_dcn": 0, "capacity_words": K}
        if algo:
            out["halving_exchange"] = hx
            out["gather_exchange"] = gx
        return out
    D = n_shards // n_hosts
    Kc = frontier_capacity(threshold, L * n_hosts)   # ICI column table
    sparse_i = changed * n_hosts <= Kc
    dcn = ((n_hosts - 1) * (2 * K + 1) * 4 if sparse
           else (n_hosts - 1) * L * 4)
    ici = ((D - 1) * (2 * Kc + 1) * 4 if sparse_i
           else (D - 1) * n_hosts * L * 4)
    # per-tier halving quotes: the DCN merge assembles one column table
    # (total = H x per-device changed), the ICI merge the global one
    # (total = S x changed); each tier falls back independently
    dcn_h = (tier_halving(n_hosts, K, changed * n_hosts, dcn, base=0)
             if sparse else (n_hosts - 1) * L * 4)
    ici_h = (tier_halving(D, Kc, changed * n_shards, ici, base=0)
             if sparse_i else (D - 1) * n_hosts * L * 4)
    flat_dcn = ((n_shards - D) * (2 * K + 1) * 4 if sparse
                else (n_shards - D) * L * 4)
    if not fused:
        # the alive mask plane, staged like every hier gather: one
        # slice per remote host over DCN, the column re-broadcast
        # over ICI (flat: one slice per remote chip crosses DCN)
        dcn += (n_hosts - 1) * sl
        dcn_h += (n_hosts - 1) * sl
        ici += (D - 1) * n_hosts * sl
        ici_h += (D - 1) * n_hosts * sl
        flat_dcn += (n_shards - D) * sl
    out = {"delta_gather": (dcn_h + ici_h) if algo else (dcn + ici),
           "ici_gather": ici_h if algo else ici,
           "dcn_gather": dcn_h if algo else dcn, "flat_dcn": flat_dcn,
           "capacity_words": K, "capacity_words_ici": Kc}
    if algo:
        out["halving_exchange"] = dcn_h + ici_h
        out["gather_exchange"] = dcn + ici
    return out


def _compact_table(planes: jax.Array, changed: jax.Array, K: int,
                   gidx: jax.Array):
    """Compact one member's changed words into a static ``K``-word
    ``(index, value)`` table pair — THE compaction both sparse
    executions share (the gather moves whole tables, the halving
    butterfly merges them pairwise).  Changed word j lands at slot
    pos[j] (< K on the caller's cond branch — its predicate guarantees
    the fit); unchanged words ADD zero at slot 0, which no real word
    can lose to."""
    flat = planes.reshape(-1)
    pos = jnp.cumsum(changed, dtype=jnp.int32) - 1
    tgt = jnp.where(changed, jnp.minimum(pos, K - 1), 0)
    vals = jnp.zeros(K, jnp.int32).at[tgt].add(
        jnp.where(changed, flat, 0))
    idxs = jnp.zeros(K, jnp.int32).at[tgt].add(
        jnp.where(changed, gidx, 0))
    return idxs, vals


def _sparse_gather(planes: jax.Array, changed: jax.Array,
                   n_changed: jax.Array, axis, K: int, gidx: jax.Array,
                   out_words: int):
    """One tier's scatter-compacted exchange: compact this member's
    changed words into a static ``K``-word ``(index, word)`` table,
    all-gather the tables over ``axis``, scatter-ADD into zeros of
    ``out_words`` int32.  Exact: deltas are bit-disjoint from zeros and
    every output word has exactly one owner member (``gidx`` is a
    member-disjoint map into the output space); invalid gathered slots
    add 0."""
    idxs, vals = _compact_table(planes, changed, K, gidx)
    idx_g = jax.lax.all_gather(idxs, axis)          # [M, K]
    val_g = jax.lax.all_gather(vals, axis)          # [M, K]
    cnt_g = jax.lax.all_gather(n_changed, axis)     # [M]
    valid = jnp.arange(K, dtype=jnp.int32)[None, :] < cnt_g[:, None]
    return jnp.zeros(out_words, jnp.int32).at[
        jnp.where(valid, idx_g, 0).reshape(-1)].add(
        jnp.where(valid, val_g, 0).reshape(-1))


#: sort sentinel for invalid table slots — larger than any global word
#: id, so the merge's sort pushes padding past every real entry
_MERGE_SENTINEL = (1 << 31) - 1


def halving_steps(m: int) -> int | None:
    """``log2(m)`` when ``m`` is a power of two >= 1, else None — the
    recursive-halving butterfly pairs member ``i`` with ``i ^ 2^s`` at
    step ``s``, which only tiles a power-of-two member count.  Callers
    treat None as "this tier executes its sparse regime by gather"
    (recorded at resolution time: aligned.from_config clamps an
    explicit ``frontier_algo=1`` on a non-power-of-two axis)."""
    if m >= 1 and (m & (m - 1)) == 0:
        return m.bit_length() - 1
    return None


def _merge_tables(idx_a, val_a, cnt_a, idx_b, val_b, cnt_b, K: int):
    """Sorted-index merge of two compacted ``(index, word)`` tables
    under the shared static capacity ``K`` — one butterfly step's
    reduction.  Invalid slots (>= each table's count) sort to the end
    behind the ``_MERGE_SENTINEL`` key; duplicate indices OR-combine
    (adjacent after the sort; each index appears at most once per input
    table, so runs are length <= 2 and one neighbor combine is exact —
    in this engine duplicates never occur at all, every global word
    having exactly one owner shard, but the OR keeps the reduction
    idempotent-exact on its own terms).  Returns ``(idx, val, count)``
    with the merged entries compacted to the front; the caller's fit
    predicate (merged total <= K) guarantees ``count <= K``, and the
    traced non-fit branch clamps instead of corrupting."""
    slot = jnp.arange(K, dtype=jnp.int32)
    keys = jnp.concatenate([
        jnp.where(slot < cnt_a, idx_a, _MERGE_SENTINEL),
        jnp.where(slot < cnt_b, idx_b, _MERGE_SENTINEL)])
    vals = jnp.concatenate([jnp.where(slot < cnt_a, val_a, 0),
                            jnp.where(slot < cnt_b, val_b, 0)])
    keys, vals = jax.lax.sort_key_val(keys, vals)
    dup = keys[1:] == keys[:-1]                     # [2K-1]
    nxt = jnp.concatenate([dup, jnp.zeros(1, bool)])
    combined = jnp.where(
        nxt, vals | jnp.concatenate([vals[1:], jnp.zeros(1, jnp.int32)]),
        vals)
    keep = (keys != _MERGE_SENTINEL) \
        & jnp.concatenate([jnp.ones(1, bool), ~dup])
    pos = jnp.cumsum(keep, dtype=jnp.int32) - 1
    tgt = jnp.where(keep, jnp.minimum(pos, K - 1), 0)
    out_i = jnp.zeros(K, jnp.int32).at[tgt].add(jnp.where(keep, keys, 0))
    out_v = jnp.zeros(K, jnp.int32).at[tgt].add(
        jnp.where(keep, combined, 0))
    return out_i, out_v, jnp.sum(keep, dtype=jnp.int32)


def _halving_allreduce(planes: jax.Array, changed: jax.Array,
                       n_changed: jax.Array, axis, M: int, K: int,
                       gidx: jax.Array, out_words: int):
    """One tier's sparse allreduce by recursive halving (arXiv:1312.3020
    adapted to the frontier's single-owner tables): compact this
    member's changed words into a static ``K``-word table, then run
    ``log2(M)`` pairwise ``lax.ppermute`` exchanges — step ``s`` pairs
    member ``i`` with ``i ^ 2^s`` and sorted-index-merges the received
    table into the local one, halving the number of unmerged table
    groups each step — so after the last step EVERY member holds the
    fully merged frontier table, scatter-ADDed into zeros exactly like
    the gather path (same compaction, same scatter, bitwise the same
    planes).

    The per-step capacity rule: every partial merge is bounded by the
    MERGED total, so one static capacity ``K`` (the same
    ``frontier_capacity`` the gather path sizes per member) serves all
    steps, and the caller pre-checks the exact fit (global changed-word
    total <= K, a scalar psum) before taking this branch.  Received
    bytes per chip: ``log2(M)`` tables of ``2K+1`` int32 — O(merged
    capacity x log M) against the gather's O(M x K) sum-of-tables.

    Two-phase reduce-scatter + allgather would add nothing here: each
    global word has exactly one owner shard, so an index-space-halving
    reduction has only empty messages (every member's table already IS
    the merged table restricted to its own region) — the butterfly
    above is the redistribution phase with the merge folded in, half
    the steps of the textbook pair."""
    idx, val = _compact_table(planes, changed, K, gidx)
    cnt = n_changed
    for s in range(halving_steps(M)):
        pairs = [(i, i ^ (1 << s)) for i in range(M)]
        msg = jnp.concatenate([idx, val, cnt[None]])
        got = jax.lax.ppermute(msg, axis, pairs)
        idx, val, cnt = _merge_tables(idx, val, cnt,
                                      got[:K], got[K:2 * K], got[2 * K],
                                      K)
    valid = jnp.arange(K, dtype=jnp.int32) < cnt
    return jnp.zeros(out_words, jnp.int32).at[
        jnp.where(valid, idx, 0)].add(jnp.where(valid, val, 0))


def _hier_gather(x: jax.Array, dcn_axis: str, ici_axis: str,
                 n_hosts: int, n_devs: int) -> jax.Array:
    """``all_gather`` of the rows axis (ndim-2), staged over the
    hierarchy: gather this device's row slice across HOSTS first (the
    DCN tier moves each slice once per host pair instead of once per
    remote CHIP — the flat all-gather's D-fold redundant inter-host
    delivery is the round-11 win), then assemble across the intra-host
    ICI tier and reshuffle the ``(d, h)``-ordered blocks into global
    ``(h, d)`` row order.  Pure data movement — bitwise the flat
    gather."""
    r_ax = x.ndim - 2
    rl, c = x.shape[r_ax], x.shape[-1]
    g1 = jax.lax.all_gather(x, dcn_axis, axis=r_ax, tiled=True)
    g2 = jax.lax.all_gather(g1, ici_axis)       # [D, ..., H*rl, c]
    pre = tuple(g2.shape[1:r_ax + 1])
    g2 = g2.reshape((n_devs,) + pre + (n_hosts, rl, c))
    g2 = jnp.moveaxis(g2, 0, -3)                # [..., H, D, rl, c]
    return g2.reshape(pre + (n_hosts * n_devs * rl, c))


def _frontier_exchange(sim, frontier_l: jax.Array, fr: FrontierCarry,
                       axis, pmax_axes, n_shards: int,
                       ici_axis: str | None = None, n_hosts: int = 1):
    """One round's cross-chip exchange on the frontier-sparse path —
    the drop-in replacement for the dense ``all_gather`` of the send
    planes, exact by seen-set monotonicity.

    Every bit the network state gains in a round enters through the
    frontier (byzantine injection and staggered generation write
    frontier and seen together; deferred-relay bits re-entering the
    frontier are already in seen), so:

      * the globalized FRONTIER is a scatter of each shard's nonzero
        frontier words into zeros (words are row-owned — no two shards
        ever contribute the same global word), and
      * the global SEEN replica advances by ``replica | frontier`` —
        OR-idempotent on the deferred re-entries, so the replica equals
        ``all_gather(seen)`` bitwise at every round, on either regime.

    Dense regime: one ``all_gather`` of the W frontier planes (already
    half the legacy pushpull exchange, which gathered send AND seen).
    Sparse regime: each shard compacts its changed words into a static
    ``K = frontier_capacity(...)``-word (global index, word) table;
    the gather moves ``2K+1`` int32 per shard instead of the planes,
    and a scatter-ADD rebuilds the global frontier (exact: deltas are
    bit-disjoint from zeros, and per-word single-writer).  The regime
    flag flips on-device with hysteresis — enter sparse below K/2
    changed words on the WORST shard, leave only past K (where the
    compaction no longer fits and dense is forced anyway) — so the
    choice lives inside the compiled scan with no host sync.
    ``axis`` may be a tuple of mesh axes (a hierarchical mesh running
    the FLAT exchange — hier_mode resolved off): the gathers and the
    member index generalize unchanged.

    SPARSE ALLREDUCE (round 16, ``sim._frontier_algo``): HOW the sparse
    regime executes is itself a two-way static — the all-gather of the
    K-word tables above (every chip receives all M tables, O(sum of
    capacities)), or the recursive-halving butterfly
    (:func:`_halving_allreduce`): log2(M) ``ppermute`` pairwise
    exchanges that sorted-index-merge the compacted tables, so each
    chip receives log2(M) tables instead of M.  The halving table must
    hold the MERGED frontier under the same static capacity K, so the
    branch engages only when the exact global census fits (total
    changed words <= K, pre-checked by a scalar psum made mesh-uniform
    like ``worst``); a sparse round whose merged total overflows falls
    back to the gather execution INSIDE the sparse regime — the
    regime predicate, the hysteresis, and the fr_sparse/fr_words
    series stay bit-for-bit the gather path's, which is what keeps
    "every metric" in the bitwise contract.  Over-capacity frontiers
    still force dense exactly like today (worst > K — the shared
    capacity rule).  Non-power-of-two member counts and multi-axis
    flat exchanges (a hier mesh running flat) keep the gather
    structurally (``halving_steps``; recorded at resolution time).

    HIERARCHICAL path (``ici_axis`` set, round 11): the exchange runs
    per TIER.  Tier 1 (DCN, ``axis`` = the host axis): each device
    exchanges its OWN row slice with its column group across hosts —
    dense tiled gather or the compacted table above, with the SAME
    per-device census and capacity as the flat exchange (so
    ``fr.regime`` and the fr_sparse diagnostic stay bitwise the flat
    trajectory) — yielding this column's host-major slice of the
    global frontier.  Crucially each slice crosses the inter-host tier
    ONCE per host pair; the flat all-gather delivers every remote
    table to each of the D co-located chips independently, a D-fold
    redundancy on exactly the links where gathered bytes hurt.  Tier 2
    (ICI, ``ici_axis``): the D column slices assemble into the global
    frontier within the host — dense stacked gather + static reshuffle
    into global row order, or the same compacted exchange on the
    column table under the ICI tier's OWN census/capacity/hysteresis
    (``fr.regime_ici``) scattering straight into global order.  Every
    regime combination is bitwise the flat gather (tests/test_hier.py).

    Returns ``(F_global, fr', went_sparse, worst_words, went_ici,
    went_halving, went_halving_ici)`` (``went_ici``/``went_halving_ici``
    None on the flat path; the went_halving flags are DIAGNOSTICS of
    which execution moved the bytes — like fr_sparse they ride the
    metric history for the A/B's received-byte reconstruction, but
    unlike fr_sparse they differ between algo runs by design and are
    never part of the parity surface)."""
    W_l, Rl, C = frontier_l.shape
    Rg = Rl * n_shards
    L = W_l * Rl * C
    K = frontier_capacity(sim.frontier_threshold, L)
    changed = (frontier_l != 0).reshape(-1)
    n_changed = jnp.sum(changed, dtype=jnp.int32)
    worst = n_changed
    for ax in pmax_axes:
        worst = jax.lax.pmax(worst, ax)
    i = jnp.arange(L, dtype=jnp.int32)
    algo = bool(getattr(sim, "_frontier_algo", False))

    if ici_axis is None:
        # the halving butterfly needs ONE named axis to ppermute over
        # (a hier mesh running the flat exchange gathers over the axis
        # PAIR) and a power-of-two member count >= 2
        use_h = (algo and not isinstance(axis, (tuple, list))
                 and n_shards >= 2
                 and halving_steps(n_shards) is not None)
        grow0 = jax.lax.axis_index(axis) * Rl
        # global word id of local word i: plane-major, global rows
        g_i = (i // (Rl * C)) * (Rg * C) + grow0 * C + i % (Rl * C)

        def dense(_):
            return jax.lax.all_gather(frontier_l, axis, axis=1,
                                      tiled=True)

        def by_gather(_):
            return _sparse_gather(frontier_l, changed, n_changed, axis,
                                  K, g_i, W_l * Rg * C
                                  ).reshape(W_l, Rg, C)

        went_sparse = (fr.regime == 1) & (worst <= K)
        if use_h:
            # exact fit of the MERGED table: the global changed-word
            # total (scalar psum, pmax-uniform so every device takes
            # the same branch of the nested conditional)
            total = jax.lax.psum(n_changed, axis)
            for ax in pmax_axes:
                total = jax.lax.pmax(total, ax)
            fits_h = total <= K

            def by_halving(_):
                return _halving_allreduce(
                    frontier_l, changed, n_changed, axis, n_shards, K,
                    g_i, W_l * Rg * C).reshape(W_l, Rg, C)

            def sparse(_):
                return jax.lax.cond(fits_h, by_halving, by_gather, None)

            went_halving = (went_sparse & fits_h).astype(jnp.int32)
        else:
            sparse = by_gather
            went_halving = jnp.int32(0)
        F = jax.lax.cond(went_sparse, sparse, dense, None)
        regime2 = jnp.where(fr.regime == 1, worst <= K,
                            worst <= K // 2).astype(jnp.int32)
        replica2 = None if fr.replica_w is None else fr.replica_w | F
        return (F, FrontierCarry(replica_w=replica2, byz_g=fr.byz_g,
                                 regime=regime2),
                went_sparse.astype(jnp.int32), worst, None,
                went_halving, None)

    # ---- hierarchical two-tier exchange -----------------------------
    D = n_shards // n_hosts
    Rc = n_hosts * Rl               # this column's rows (host-major)
    Lc = W_l * Rc * C
    K_i = frontier_capacity(sim.frontier_threshold, Lc)
    h = jax.lax.axis_index(axis)
    d = jax.lax.axis_index(ici_axis)
    # each tier takes the halving variant independently — its own
    # member count, its own power-of-two legality
    use_h_dcn = (algo and n_hosts >= 2
                 and halving_steps(n_hosts) is not None)
    use_h_ici = (algo and D >= 2 and halving_steps(D) is not None)
    # ICI-tier census: this COLUMN's total changed words (its table is
    # the union of one slice per host), made uniform across the mesh
    # like ``worst`` so every device takes the same cond branch
    col = jax.lax.psum(n_changed, axis)
    worst_col = col
    for ax in pmax_axes:
        worst_col = jax.lax.pmax(worst_col, ax)
    # word id inside the COLUMN table [W_l, H*Rl, C], host-major
    g_i = (i // (Rl * C)) * (Rc * C) + h * Rl * C + i % (Rl * C)

    def dcn_dense(_):
        return jax.lax.all_gather(frontier_l, axis, axis=1, tiled=True)

    def dcn_gather(_):
        return _sparse_gather(frontier_l, changed, n_changed, axis,
                              K, g_i, W_l * Rc * C).reshape(W_l, Rc, C)

    went_dcn = (fr.regime == 1) & (worst <= K)
    if use_h_dcn:
        # the DCN merge assembles one COLUMN table: its exact total is
        # the ICI census above, already pmax-uniform
        fits_dcn = worst_col <= K

        def dcn_halving(_):
            return _halving_allreduce(
                frontier_l, changed, n_changed, axis, n_hosts, K, g_i,
                W_l * Rc * C).reshape(W_l, Rc, C)

        def dcn_sparse(_):
            return jax.lax.cond(fits_dcn, dcn_halving, dcn_gather, None)

        went_halving = (went_dcn & fits_dcn).astype(jnp.int32)
    else:
        dcn_sparse = dcn_gather
        went_halving = jnp.int32(0)
    F_col = jax.lax.cond(went_dcn, dcn_sparse, dcn_dense, None)
    regime2 = jnp.where(fr.regime == 1, worst <= K,
                        worst <= K // 2).astype(jnp.int32)

    changed_c = (F_col != 0).reshape(-1)
    n_changed_c = jnp.sum(changed_c, dtype=jnp.int32)

    def ici_dense(_):
        g2 = jax.lax.all_gather(F_col, ici_axis)   # [D, W_l, H*Rl, C]
        g2 = g2.reshape(D, W_l, n_hosts, Rl, C)
        # (d, h)-ordered blocks -> global (h, d) row order
        return jnp.transpose(g2, (1, 2, 0, 3, 4)).reshape(W_l, Rg, C)

    # word id in the GLOBAL planes: column word (w, h*Rl + r, c)
    # lives at global row (h*D + d)*Rl + r
    j = jnp.arange(Lc, dtype=jnp.int32)
    w = j // (Rc * C)
    rem = j % (Rc * C)
    r_col, c = rem // C, rem % C
    hh, r = r_col // Rl, r_col % Rl
    g_j = w * (Rg * C) + ((hh * D + d) * Rl + r) * C + c

    def ici_gather(_):
        return _sparse_gather(F_col, changed_c, n_changed_c, ici_axis,
                              K_i, g_j, W_l * Rg * C
                              ).reshape(W_l, Rg, C)

    went_ici = (fr.regime_ici == 1) & (worst_col <= K_i)
    if use_h_ici:
        # the ICI merge assembles the GLOBAL frontier table: its exact
        # total is the global census (psum over both tiers)
        total_g = jax.lax.psum(col, ici_axis)
        for ax in pmax_axes:
            total_g = jax.lax.pmax(total_g, ax)
        fits_ici = total_g <= K_i

        def ici_halving(_):
            return _halving_allreduce(
                F_col, changed_c, n_changed_c, ici_axis, D, K_i, g_j,
                W_l * Rg * C).reshape(W_l, Rg, C)

        def ici_sparse(_):
            return jax.lax.cond(fits_ici, ici_halving, ici_gather, None)

        went_halving_ici = (went_ici & fits_ici).astype(jnp.int32)
    else:
        ici_sparse = ici_gather
        went_halving_ici = jnp.int32(0)
    F = jax.lax.cond(went_ici, ici_sparse, ici_dense, None)
    regime_i2 = jnp.where(fr.regime_ici == 1, worst_col <= K_i,
                          worst_col <= K_i // 2).astype(jnp.int32)
    replica2 = None if fr.replica_w is None else fr.replica_w | F
    return (F, FrontierCarry(replica_w=replica2, byz_g=fr.byz_g,
                             regime=regime2, regime_ici=regime_i2),
            went_dcn.astype(jnp.int32), worst,
            went_ici.astype(jnp.int32), went_halving, went_halving_ici)


def _skip_plan(y: jax.Array, rowblk: int, t_local: int,
               rolls_off: jax.Array | None = None,
               ytab_local: jax.Array | None = None):
    """(yidx, yact) for the push pass's in-kernel block skipping: mark
    every y block whose send words are all zero (it contributes nothing
    to the OR — gating it is exact by construction, however the mask
    was derived) and remap dead grid steps onto the resident buffer
    (ops/aligned_kernel.skip_tables).  Costs one read of the send
    planes (the traffic model's ``frontier_scan`` term) against up to
    D-1 saved block streams per dead block."""
    W_l, Ry, C = y.shape
    Ty = Ry // rowblk
    act = jnp.any((y != 0).reshape(W_l, Ty, rowblk * C), axis=(0, 2))
    if ytab_local is not None:
        idx_raw = ytab_local.T                          # [T, D]
    else:
        t = jnp.arange(t_local, dtype=jnp.int32)
        idx_raw = (t[:, None] + rolls_off[None, :]) % Ty
    return skip_tables(idx_raw, act)


def _overlap_plans(frontier_l: jax.Array, y_g: jax.Array, rowblk: int,
                   t_off: jax.Array, ytab_local: jax.Array, skip: bool):
    """((yidx_A, yact_A), (yidx_B, yact_B)) — the self/remote split of
    one push pass's grid for the compute-hidden exchange.

    Pass A computes the SELF-shard contribution from the LOCAL send
    planes (``frontier_l``) — it has no data dependency on the
    collective, so hardware schedulers overlap the exchange with it —
    and pass B the REMOTE contribution from the gathered planes,
    OR-seeded with pass A's accumulator.  The two activity gates are
    exact complements over the (frontier-)active blocks, so every grid
    step contributes in exactly one pass and the OR-merged result is
    bitwise the single-pass one.  With ``skip`` the frontier activity
    mask composes in (a dead block is gated off in BOTH passes, exactly
    like the single-pass skip).  Pass A's remap indices convert to the
    local block frame (its y array holds only this shard's blocks);
    leading pins that land outside it clamp — their steps are gated."""
    W_l, Rl, C = frontier_l.shape
    ty_l = Rl // rowblk
    ty_g = y_g.shape[1] // rowblk
    idx_raw = ytab_local.T                         # [T_local, D], global
    bid = jnp.arange(ty_g, dtype=jnp.int32)
    is_local = (bid >= t_off) & (bid < t_off + ty_l)
    if skip:
        act_l = jnp.any((frontier_l != 0).reshape(W_l, ty_l, rowblk * C),
                        axis=(0, 2))
        act_g = jnp.any(
            (y_g != 0).reshape(y_g.shape[0], ty_g, rowblk * C),
            axis=(0, 2))
    else:
        act_l = jnp.ones(ty_l, bool)
        act_g = jnp.ones(ty_g, bool)
    act_a = jax.lax.dynamic_update_slice(jnp.zeros(ty_g, bool), act_l,
                                         (t_off,))
    yidx_a, yact_a = skip_tables(idx_raw, act_a)
    yidx_a = jnp.clip(yidx_a - t_off, 0, ty_l - 1)
    yidx_b, yact_b = skip_tables(idx_raw, act_g & ~is_local)
    return (yidx_a, yact_a), (yidx_b, yact_b)


def _popcount_sum(words: jax.Array) -> jax.Array:
    return jnp.sum(jax.lax.population_count(words), dtype=jnp.int32)


def _popcount_pair(words: jax.Array) -> jax.Array:
    """Overflow-safe popcount total as an EXACT int32 pair [hi, lo]
    (total = hi * 1024 + lo).  A flat int32 popcount sum wraps at 2^31 —
    the 10M-peer x 256-message headline's 2.56e9 set bits came back as a
    NEGATIVE coverage on hardware (round-5 measure_round4 crash).  Split
    accounting: per-row totals (<= W*128*32 = 262k at W=64, exact) split
    at 1024, so each partial sum stays far below 2^31 for any
    configuration the engine admits (rows * 1023 <= 8e7 at 10M peers;
    rows * 256 for hi).  The pair stays integer through any psum —
    cross-shard sums are exact and order-invariant, preserving the
    bitwise 1-vs-N parity contract — and only :func:`_pair_total` turns
    it into one float32 at the very end."""
    per_row = jnp.sum(jax.lax.population_count(words),
                      axis=tuple(i for i in range(words.ndim)
                                 if i != words.ndim - 2),
                      dtype=jnp.int32)                       # [rows]
    return jnp.stack([jnp.sum(per_row >> 10, dtype=jnp.int32),
                      jnp.sum(per_row & 1023, dtype=jnp.int32)])


def _pair_total(pair: jax.Array) -> jax.Array:
    """float32 total from an (already reduced) [hi, lo] popcount pair.
    The pair is first NORMALIZED to the canonical (total >> 10,
    total & 1023) decomposition, so any exact [hi, lo] split of the
    same total — the jnp path's per-row split, the kernel census's
    per-block split — yields the bit-identical float: one deterministic
    rounding on exact ints, identical on every sharding of the same
    global state (float32 carries 2.56e9 with ~1e-7 relative error,
    far below any coverage threshold's needs)."""
    hi = pair[0] + (pair[1] >> 10)
    lo = pair[1] & 1023
    return hi.astype(jnp.float32) * 1024.0 + lo.astype(jnp.float32)


def _div_rn(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a / b`` in float32, rounded to nearest-even on every backend.
    XLA's TPU divide is not correctly rounded (the chip's coverage
    differed from the CPU's in the last bit), so the quotient is built
    by integer long division of the mantissas instead.  Needs finite
    ``a >= 0`` and ``b >= 1`` with a normal quotient — coverage's
    domain."""
    ia = jax.lax.bitcast_convert_type(jnp.float32(a), jnp.int32)
    ib = jax.lax.bitcast_convert_type(jnp.float32(b), jnp.int32)
    ma = (ia & 0x7FFFFF) | 0x800000
    mb = (ib & 0x7FFFFF) | 0x800000
    low = ma < mb
    r = jnp.where(low, ma << 1, ma)           # r / mb in [1, 2)
    e = (ia >> 23) - (ib >> 23) + 127 - low.astype(jnp.int32)
    q = jnp.int32(0)
    for _ in range(25):                       # 24 mantissa bits + guard
        bit = r >= mb
        q = (q << 1) | bit.astype(jnp.int32)
        r = jnp.where(bit, r - mb, r) << 1
    mant = q >> 1
    up = ((q & 1) == 1) & ((r != 0) | ((mant & 1) == 1))
    mant = mant + up.astype(jnp.int32)
    carry = mant >> 24                        # rounded up to 2.0
    bits = ((e + carry) << 23) | ((mant >> carry) & 0x7FFFFF)
    out = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return jnp.where(ia == 0, jnp.float32(0.0), out)


def _census_pair(partials: jax.Array) -> jax.Array:
    """Exact [hi, lo] popcount pair from a kernel census output — the
    int32[T, 8, 128] per-block partial tiles gossip_pass emits on the
    census path (ops/aligned_kernel.py).  Per-block totals stay far
    below 2^31 (<= W * blk * 128 * 32 <= 1.7e7 at the VMEM budget), and
    the 1024 split keeps both halves psum-exact at any admissible scale
    — the same discipline as :func:`_popcount_pair`."""
    q = jnp.sum(partials, axis=(1, 2), dtype=jnp.int32)        # [T]
    return jnp.stack([jnp.sum(q >> 10, dtype=jnp.int32),
                      jnp.sum(q & 1023, dtype=jnp.int32)])


def _pair_int(pair) -> int:
    """EXACT Python-int total from a device_get [hi, lo] pair — the
    host-side twin of :func:`_pair_total` (the 1024 split factor lives
    only here and in _popcount_pair)."""
    return int(pair[0]) * 1024 + int(pair[1])


# ----------------------------------------------------------------------
# Shard-invariant per-row randomness.  Every random decision is keyed on
# the GLOBAL row id via fold_in, so a shard drawing only its own rows gets
# bit-identical values to the unsharded engine drawing all rows — the
# discipline that makes "1 device vs N devices vs unsharded" an exact,
# testable property (same contract as parallel/sharded_sim.py, but O(local
# rows) instead of O(global peers) per device).

def row_uniform(key: jax.Array, grows: jax.Array,
                shape: tuple) -> jax.Array:
    """float32[len(grows), *shape] — U(0,1) per global row id."""
    return jax.vmap(
        lambda r: jax.random.uniform(jax.random.fold_in(key, r), shape)
    )(grows)


def row_randint(key: jax.Array, grows: jax.Array, shape: tuple,
                lo: int, hi: int, dtype=jnp.int32) -> jax.Array:
    """ints[len(grows), *shape] in [lo, hi) per global row id."""
    return jax.vmap(
        lambda r: jax.random.randint(jax.random.fold_in(key, r), shape,
                                     lo, hi, dtype)
    )(grows)


def churn_rows(key: jax.Array, grows: jax.Array, alive_b: jax.Array,
               valid_b: jax.Array, round_idx: jax.Array,
               cfg: ChurnConfig) -> jax.Array:
    """liveness.churn_step semantics on the [rows, 128] peer grid with
    per-row shard-invariant draws; padding peers can never revive."""
    u = row_uniform(key, grows, (2, LANES))
    u_die, u_rev = u[:, 0], u[:, 1]
    if cfg.kill_round >= 0:
        dies = (round_idx == cfg.kill_round) & (u_die < cfg.rate)
    else:
        dies = u_die < cfg.rate
    revives = u_rev < cfg.revive
    return ((alive_b & ~dies) | (~alive_b & revives)) & valid_b


def _init_program(valid_w, byz_b, src, key, *, n_words: int, n_slots: int,
                  n_msgs: int, n_honest: int, seeded: bool, liveness: bool):
    """The round-0 state's arrays as one program: (seen, byz_w, alive_b,
    strikes, key, round).  Every array comes in as an argument, so sims
    of one shape share the executable and no plane is baked into it as a
    constant; ``key`` passes through so the state owns a buffer of its
    own."""
    rows = valid_w.shape[0]
    byz_w = jnp.where(byz_b, jnp.int32(-1), jnp.int32(0))
    # Columns >= n_honest start empty (the adversary's injection budget);
    # with staggered generation NO columns are seeded here — column m is
    # injected at round m*k by aligned_round.
    m = jnp.arange(n_msgs)
    place = (m < n_honest) & seeded
    # Seed words with scatter-ADD: distinct message bits add like OR (so
    # colliding sources keep every rumor — every message is a distinct
    # (plane, bit) pair, so no addition carries), and bit 31, the sign
    # bit, adds without overflow where a max-combiner would drop it.  The
    # scatter writes the [W, rows, 128] planes in place: a flat plane
    # reshaped afterwards costs the chip two more passes (a relayout).
    bits = jnp.where(place, jnp.left_shift(jnp.int32(1), m % WORD_BITS), 0)
    pos = jnp.where(place, src, 0)
    seen = jnp.zeros((n_words, rows, LANES), jnp.int32).at[
        m // WORD_BITS, pos // LANES, pos % LANES].add(bits)
    strikes = (jnp.zeros((n_slots, rows, LANES), jnp.int8) if liveness
               else None)
    return seen, byz_w, valid_w != 0, strikes, key, jnp.int32(0)


_init_jit = jax.jit(_init_program, static_argnames=(
    "n_words", "n_slots", "n_msgs", "n_honest", "seeded", "liveness"))
#: compiled round-0 programs, keyed by statics and argument layouts
_INIT_PROGRAMS: dict = {}


@dataclass
class AlignedSimulator:
    """Same surface as sim.Simulator (step / run / run_to_coverage, same
    metric dict, churn + liveness/rewire + byzantine), flood-push,
    bounded-fanout rumor mongering (``fanout > 0``), or
    push+anti-entropy-pull, at HBM-bandwidth speed.

    Liveness semantics mirror liveness.strike_and_rewire: a slot whose
    neighbor looks dead gains a strike per round, eviction at
    ``max_strikes`` rewires the slot to a random replacement lane in the
    same permuted row (accepted only if itself alive — the re-bootstrap
    analogue, reference peer.cpp:400-404).  Byzantine peers receive but
    never relay and refuse to serve pulls (models/gossip.py semantics);
    junk columns >= ``n_honest_msgs`` are their injection budget."""

    topo: AlignedTopology
    n_msgs: int = 16
    mode: str = "push"           # push | pull | pushpull
    fanout: int = 0              # 0 = flood; else slots listened per round
    churn: ChurnConfig = None    # type: ignore[assignment]
    byzantine_fraction: float = 0.0
    n_honest_msgs: int | None = None   # None → all columns honest
    max_strikes: int = 3
    #: run the liveness/rewire pass every k-th round (1 = every round).
    #: The reference probes on a SLOWER cadence than it gossips (13 s
    #: ping sweeps vs 5 s messages, peer.cpp:330/377 — one sweep per
    #: ~2.6 message intervals), so a stride of 2-3 is the faithful
    #: setting, and it removes the pass's HBM traffic (colidx + strikes
    #: + alive gather, ~half the round's bytes) from off-rounds.
    liveness_every: int = 1
    #: rounds between successive message activations: column m enters at
    #: its source in round m*k (messageGenerationLoop cadence,
    #: peer.cpp:357-377).  0 = every rumor exists from round 0.
    message_stagger: int = 0
    #: fold the seen-update into the final gossip pass: the kernel turns
    #: its VMEM-resident accumulator into (new, seen') directly, and in
    #: pushpull the push pass's receive words seed the pull pass's
    #: accumulator — the XLA elementwise read-recv/read-seen/write-new/
    #: write-seen pass disappears (docs/PERFORMANCE.md "next factor").
    #: Opt-in until the on-chip A/B lands, like block_perm before it.
    fuse_update: bool = False
    #: restrict the pull contact draw to the FIRST roll group's slots
    #: (uniform over [0, min(deg, window)) — still one uniformly-random
    #: in-neighbor, since slot identities are i.i.d.).  The pull pass
    #: then runs a window-sized grid whose slots all share ONE block
    #: roll, cutting its seen-plane stream from `streams` to 1 and its
    #: lane-table stream by D/window (docs/PERFORMANCE.md "pull-window
    #: restriction").  Needs a roll-grouped overlay (window >= 2).
    #: Direct-construction default stays off (it changes every pull
    #: trajectory — an A/B knob); the CONFIG default is now on
    #: (config.py pull_window=1 + roll_groups=4, the measured-best
    #: layout per the round-5 on-chip A/Bs), with from_config falling
    #: back to the classic path when a scenario can't support it.
    pull_window: bool = False
    #: declarative fault plan (faults.FaultPlan): per-link drop +
    #: partition gates ride the kernels' in-register hash path,
    #: relay delay and crash/recovery schedules the host-side masks —
    #: all keyed on global ids, so faulted runs keep the bitwise
    #: sharded-vs-unsharded parity contract.  None = no faults, and
    #: the compiled round is exactly the pre-fault-plane program.
    faults: object | None = None
    #: frontier-sparse rounds: -1 auto (on for the compiled TPU path,
    #: off under interpret — the extra XLA-side work inverts there,
    #: the round-6 fused-path precedent), 0 off, 1 on.  On: the push
    #: pass skips dead sender blocks in-kernel (``_skip_plan``), and
    #: the sharded engines run the delta-compressed exchange
    #: (``_frontier_exchange``).  Bitwise-identical to the dense path
    #: by construction — state AND every metric — so it is excluded
    #: from checkpoint fingerprints like fuse_update.
    frontier_mode: int = 0
    #: sparse-exchange capacity per shard as a fraction of its packed
    #: words (FRONTIER_THRESHOLD_DEFAULT has the derivation).
    frontier_threshold: float = FRONTIER_THRESHOLD_DEFAULT
    #: HOW the sparse regime executes its exchange (round 16): 0 = the
    #: round-8 table all-gather, 1 = the recursive-halving sparse
    #: allreduce (log2(M) ppermute pairwise merges — each chip receives
    #: O(merged capacity x log M) bytes instead of O(M x K)), -1 auto
    #: (halving on the compiled path, gather under interpret — the
    #: butterfly's sort/merge work inverts on CPU, the round-6/8/10/11
    #: precedent).  A third way to EXECUTE the same regime: the regime
    #: predicate, hysteresis, and every metric are bitwise the gather
    #: path's (rounds whose merged total overflows the capacity fall
    #: back to the gather inside the sparse branch; non-power-of-two
    #: axes keep the gather structurally, recorded at resolution).
    #: Excluded from checkpoint fingerprints like every frontier_* key.
    frontier_algo: int = 0
    #: double-buffered DMA pipelining of the gossip kernels' y stream
    #: (round 10): -1 auto (2 on the compiled TPU path, 0 under
    #: interpret — the manual copy stream only adds interpreter work on
    #: CPU, the frontier_mode precedent), 0 = the legacy BlockSpec
    #: pipeline, 2 = the manual double-buffered stream
    #: (ops/aligned_kernel.gossip_pass prefetch_depth).  Bitwise-
    #: identical by construction, so it is excluded from checkpoint
    #: fingerprints like fuse_update/frontier_mode.
    prefetch_depth: int = 0
    #: compute-hidden cross-chip exchange (round 10, sharded engines
    #: only): split the push pass into a self-shard contribution (local
    #: send words, no collective dependency) and a remote-shard
    #: contribution (OR-seeded from the first via acc_init), so the
    #: frontier/all-gather exchange overlaps the self-shard kernel on
    #: hardware with async collectives.  -1 auto (on for the compiled
    #: path), 0/1 force.  Needs the block-perm overlay (row-granular
    #: permutations scatter every y block across shards) and a push
    #: pass; resolves off otherwise.  Bitwise-identical: each grid step
    #: contributes in exactly one of the two passes (complementary
    #: yact gates) and OR is associative.
    overlap_mode: int = 0
    #: two-tier hierarchical exchange (round 11): the resolved
    #: ``hosts x devs_per_host`` factorization of the peer mesh this
    #: scenario targets (0 = flat).  The solo engine never exchanges —
    #: these are RESOLVED STATICS carried for the sharded engines
    #: (which derive them from their mesh and thread them here) and
    #: for the fleet packer's bucket signature; ``hier_mode`` follows
    #: the frontier_mode auto rule: -1 = on for the compiled path /
    #: off under interpret, 0/1 force.  Routing only — bitwise-
    #: identical to the flat exchange (tests/test_hier.py) — so all
    #: three are excluded from checkpoint fingerprints like
    #: frontier_mode before them.
    hier_hosts: int = 0
    hier_devs: int = 0
    hier_mode: int = -1
    seed: int = 0
    interpret: bool | None = None   # None -> interpret_for(backend)

    def __post_init__(self):
        if self.n_msgs <= 0:
            raise ValueError("n_msgs must be positive")
        if self.faults is not None:
            self.faults.validate()
        if self.liveness_every < 1:
            raise ValueError("liveness_every must be >= 1")
        self.n_words = n_msg_words(self.n_msgs)
        if self.mode not in ("push", "pull", "pushpull"):
            raise ValueError(f"Unknown gossip mode: {self.mode}")
        if self.fanout < 0:
            raise ValueError("fanout must be >= 0 (0 = flood)")
        if not 0 < self.max_strikes <= 126:
            # strikes are int8 clamped at max_strikes + 1; 127 would wrap
            # and silently disable eviction (the edge engine's int32
            # counters accept any value — keep the configs that work there
            # from degrading here without a word)
            raise ValueError("aligned engine needs 0 < max_strikes <= 126")
        if self.churn is None:
            self.churn = ChurnConfig()
        if self.interpret is None:
            self.interpret = interpret_for(jax.default_backend())
        if not self.interpret and (self.topo.rows < 8
                                   or self.topo.rowblk % 8):
            # Mosaic requires the kernel's block shape — (rowblk, 128) —
            # to tile (8, 128) sublanes; fewer rows or a non-multiple-of-8
            # row block compile-errors deep inside the kernel.  Interpret
            # mode (CPU) handles any layout.
            raise ValueError(
                f"aligned engine on TPU needs >= 8 rows of {LANES} peers "
                f"and an 8-aligned row block (this overlay: "
                f"{self.topo.rows} rows, rowblk {self.topo.rowblk}) — "
                "use the edge engine, a larger overlay, or fewer shards")
        # The fused update keeps ~2x the word-blocks resident (seen +
        # seen' + pushpull's accumulator seed alongside y and acc), so
        # its VMEM budget is half the plain pass's.
        budget = (MAX_WORDS_X_ROWBLK // 2 if self.fuse_update
                  else MAX_WORDS_X_ROWBLK)
        if not self.interpret and \
                self.n_words * self.topo.rowblk > budget:
            # The kernel keeps int32[W, rowblk, 128] y/acc blocks resident
            # in VMEM; an over-budget combination compile-errors deep in
            # Mosaic.  Fail at construction with the fix spelled out —
            # and when no row block can help (build_aligned floors the
            # block at 8 sublanes), state the hard ceiling instead of
            # advising a rebuild that would fail the same way.
            hard_cap = (budget // 8) * WORD_BITS
            if self.n_words * 8 > budget:
                raise ValueError(
                    f"{self.n_msgs} messages exceed the aligned engine's "
                    f"hard ceiling of {hard_cap} (the VMEM row block "
                    "bottoms out at 8 sublanes"
                    + (", halved budget under fuse_update) — drop "
                       "fuse_update or use the edge engine"
                       if self.fuse_update else ") — use the edge engine"))
            fit_blk = max(8, budget // self.n_words // 8 * 8)
            raise ValueError(
                f"{self.n_msgs} messages ({self.n_words} planes) with row "
                f"block {self.topo.rowblk} exceed the kernel's VMEM "
                f"budget{' (halved under fuse_update)' if self.fuse_update else ''}"
                f" — rebuild the overlay with build_aligned(..., "
                f"n_msgs={self.n_msgs}, rowblk={fit_blk})")
        self._n_honest = (self.n_honest_msgs
                          if self.n_honest_msgs is not None else self.n_msgs)
        if not 0 < self._n_honest <= self.n_msgs:
            raise ValueError("n_honest_msgs must be in (0, n_msgs]")
        # Pull-window slot count: the first contiguous run of equal
        # block rolls (static per topology).  Without pull_window the
        # window is all slots — the unified pull path below then draws
        # and streams exactly what it always did.
        if self.pull_window:
            # The guard reads the overlay's BUILT grouping, never an
            # inference from the drawn rolls (a per-slot overlay whose
            # first two rolls coincide by chance must still be
            # rejected, deterministically).
            if self.topo.roll_groups is None:
                raise ValueError(
                    "pull_window needs a roll-grouped overlay "
                    "(build_aligned(roll_groups=g) with g <= n_slots/2)")
            rolls_np = np.asarray(self.topo.rolls)
            changes = np.nonzero(np.diff(rolls_np))[0]
            self._pull_slots = (int(changes[0]) + 1 if changes.size
                                else self.topo.n_slots)
            if self._pull_slots < 2:
                # window 1 = every peer pulls the SAME neighbor every
                # round (colidx is static) — anti-entropy degenerates.
                raise ValueError(
                    "pull_window needs a roll-grouped overlay whose "
                    "first group spans >= 2 slots (build_aligned("
                    "roll_groups=g) with g <= n_slots/2)")
            if self.mode == "push":
                raise ValueError("pull_window only affects pull/"
                                 "pushpull modes")
            if self.mode == "pull" and self.topo.ytab is not None:
                # Pure pull restricted to ONE shared block roll on a
                # block-perm overlay: the pull-level block graph is a
                # permutation cycle (out-degree 1) — the same stall
                # build_aligned rejects for block_perm + roll_groups=1
                # — and anti-entropy plateaus at the cycle-reachable
                # fraction.  pushpull is fine (the push pass still
                # mixes across all rolls).
                raise ValueError(
                    "pull_window with mode=pull on a block_perm "
                    "overlay confines anti-entropy to a single block "
                    "cycle — use pushpull, or a row-perm overlay")
        else:
            self._pull_slots = self.topo.n_slots
        # The schedule statics, each resolved HERE and only here (after
        # ``interpret`` is known — auto keys off it): the public field
        # takes its rule's concrete value (autostatics.py, the one home
        # of every -1 rule) and the private flags derive from it.  All
        # are bitwise-identical either way.  Frontier-sparse rounds:
        # block skipping needs a push pass to skip in; the delta
        # exchange engages only when a sharded engine passes its
        # FrontierCarry into the round.
        if self.frontier_mode not in (-1, 0, 1):
            raise ValueError("frontier_mode must be -1 (auto), 0, or 1")
        self.frontier_threshold = auto.heuristic_frontier_threshold(
            self.frontier_threshold)
        if not 0.0 < self.frontier_threshold <= 1.0:
            raise ValueError("frontier_threshold must be in (0, 1]")
        self.frontier_mode = int(auto.heuristic_on(self.frontier_mode,
                                                   self.interpret))
        fr_on = bool(self.frontier_mode)
        self._frontier_skip = fr_on and self.mode in ("push", "pushpull")
        self._frontier_delta = fr_on
        # Sparse-allreduce execution of the delta exchange (round 16):
        # the per-tier power-of-two legality is structural
        # (_frontier_exchange / halving_steps), so the resolved flag
        # means "halving wherever the mesh can".
        if self.frontier_algo not in (-1, 0, 1):
            raise ValueError("frontier_algo must be -1 (auto), 0 "
                             "(gather), or 1 (halving)")
        self.frontier_algo = int(auto.heuristic_on(self.frontier_algo,
                                                   self.interpret))
        self._frontier_algo = bool(self.frontier_algo)
        # Round-10 schedule knobs: the manual double-buffered DMA
        # stream, and the self/remote split that hides the sharded
        # exchange behind the self-shard kernel.
        if self.prefetch_depth not in (-1, 0, 2):
            raise ValueError("prefetch_depth must be -1 (auto), 0, or 2")
        self.prefetch_depth = auto.heuristic_prefetch(self.prefetch_depth,
                                                      self.interpret)
        self._prefetch = self.prefetch_depth
        if self.overlap_mode not in (-1, 0, 1):
            raise ValueError("overlap_mode must be -1 (auto), 0, or 1")
        self.overlap_mode = int(auto.heuristic_on(self.overlap_mode,
                                                  self.interpret))
        # the split needs a push pass to split and the block-perm
        # overlay's block-granular locality (a row-granular permutation
        # scatters every y block's rows across all shards); it engages
        # only when aligned_round actually runs sharded (n_shards > 1)
        self._overlap = (bool(self.overlap_mode)
                         and self.topo.ytab is not None
                         and self.mode in ("push", "pushpull"))
        # Hierarchical two-tier exchange (round 11): resolved here so
        # the fleet packer and the traffic model read one static; the
        # sharded engines additionally require their mesh to carry the
        # factorization.
        if self.hier_mode not in (-1, 0, 1):
            raise ValueError("hier_mode must be -1 (auto), 0, or 1")
        if self.hier_hosts < 0 or self.hier_devs < 0:
            raise ValueError("hier_hosts/hier_devs must be >= 0")
        self.hier_mode = int(auto.heuristic_on(self.hier_mode,
                                               self.interpret))
        self._hier = self.hier_hosts > 1 and bool(self.hier_mode)
        # Liveness (strikes/rewire) runs whenever peers can die — without
        # churn no neighbor is ever observed dead, so the pass is skipped
        # statically and the strike plane is never allocated.
        self._liveness = self.churn.rate > 0.0 or self.churn.revive > 0.0
        # Per-plane masks, int32[W]; broadcast as mask[:, None, None].
        self._honest_mask = mask_words(self._n_honest, self.n_words)
        self._junk_mask = (mask_words(self.n_msgs, self.n_words)
                           & ~self._honest_mask)
        self._run_cache: dict = {}
        self._loop_cache: dict = {}
        # eager, once: a traced cache would leak, and init_state then
        # does no eager work of its own
        self._plan_cache, self._init_key = self._seed_plan()

    # ------------------------------------------------------------------
    @classmethod
    def from_config(cls, cfg, n_peers: int | None = None,
                    n_shards: int = 1,
                    clamps: list[str] | None = None) -> "AlignedSimulator":
        """Build the scale engine from a parsed NetworkConfig — the
        facade/CLI entry, mirroring sim.Simulator.from_config.  Engine
        ceilings and model substitutions land in ``clamps`` (the CLI
        prints them and records them in the result line) — never a
        silent weakening of the configured scenario.  Raises ValueError
        for a scenario the engine cannot express (``mode=sir`` lives in
        aligned_sir.AlignedSIRSimulator).  ``n_shards > 1`` lays the
        overlay out for the sharded engine; lift the fields onto
        parallel.AlignedShardedSimulator the way the CLI does."""
        clamps = clamps if clamps is not None else []
        if cfg.mode not in ("push", "pull", "pushpull"):
            raise ValueError(
                f"the aligned engine supports push/pull/pushpull, not "
                f"{cfg.mode!r} (sir: aligned_sir.AlignedSIRSimulator)")
        n, law, n_slots = resolve_overlay(cfg, n_peers=n_peers,
                                          clamps=clamps)
        n_msgs = cfg.n_messages or cfg.max_message_count
        if n_msgs > MAX_CONFIG_MSGS:
            clamps.append(
                f"n_messages {n_msgs} -> {MAX_CONFIG_MSGS} "
                f"(aligned engine packs <= {MAX_CONFIG_MSGS} messages "
                "= 64 int32 planes)")
            n_msgs = MAX_CONFIG_MSGS
        from p2p_gossipprotocol_tpu import faults as faults_lib

        plan = faults_lib.plan_from_config(cfg)
        if plan is not None:
            telemetry.event("fault_plan", spec=plan.to_spec(),
                            kernel_gate=plan.kernel_active())
        # The plan's byzantine knob routes into the existing adversary
        # machinery (sim.Simulator.from_config has the same merge rule).
        byz = max(cfg.byzantine_fraction, plan.byzantine if plan else 0.0)
        n_honest = None
        if byz > 0.0:
            n_junk = max(1, n_msgs // 4)
            if n_msgs + n_junk > MAX_CONFIG_MSGS:
                clamps.append(
                    f"n_messages {n_msgs} -> {MAX_CONFIG_MSGS - n_junk} "
                    f"({MAX_CONFIG_MSGS}-message cap shared with "
                    f"{n_junk} byzantine junk columns)")
                n_msgs = MAX_CONFIG_MSGS - n_junk
            n_honest = n_msgs
            n_msgs = n_msgs + n_junk
        # Fused-overlay AUTO-selection (the product path follows the
        # measurements, zero knobs): block_perm=-1 (the config default)
        # picks the block-granular overlay whenever it is measured-best
        # AND legal — wide message sets (W >= AUTO_BLOCK_PERM_MIN_WORDS,
        # the on-chip -43% regime; a wash at W=1 keeps row-perm there),
        # push/pushpull modes (pure pull keeps the windowed classic
        # path — no measurement says fusion beats it there), and a roll
        # grouping that can express a block-level overlay (>= 2 distinct
        # rolls).  An EXPLICIT block_perm=0/1 is honored, except that
        # illegal combinations degrade with a recorded clamp instead of
        # erroring the run — same seam as every other engine ceiling.
        # The rule itself lives in autostatics.py.
        W = n_msg_words(n_msgs)
        groups = cfg.roll_groups or None
        block_perm = auto.heuristic_block_perm(
            cfg.block_perm, W, cfg.mode, n_slots, groups,
            min_words=AUTO_BLOCK_PERM_MIN_WORDS)
        if block_perm and groups is not None and groups <= 1 \
                and n_slots > 1:
            clamps.append(
                "block_perm with roll_groups=1 -> row-perm overlay "
                "(a block-granular overlay needs >= 2 distinct block "
                "rolls; one shared roll is a single permutation cycle "
                "and dissemination stalls)")
            block_perm = False
        # pull_window is DEFAULT-ON from the config surface (the
        # measured-best layout, round 5) but remains an
        # optimization, not the scenario: when this configuration can't
        # support it — push-only mode, or an overlay that isn't
        # roll-grouped with a >= 2-slot first group — fall back to the
        # classic pull path instead of erroring the run.  Pure pull on
        # a block-perm overlay would stall on a single block cycle
        # (__post_init__ rejects it); that degrade is RECORDED, since
        # it weakens an explicitly configured combination.
        pull_window = bool(cfg.pull_window)
        if pull_window:
            g = groups or 0
            if cfg.mode == "push" or not 1 <= g <= n_slots // 2:
                pull_window = False
            elif cfg.mode == "pull" and block_perm:
                clamps.append(
                    "pull_window with mode=pull on a block_perm "
                    "overlay -> classic pull (windowed anti-entropy "
                    "would be confined to one block cycle)")
                pull_window = False
        # Frontier-sparse rounds: AUTO (-1, the default) resolves
        # against the backend in __post_init__ (on for the compiled
        # path, off under interpret — same honesty as the round-6
        # fused-path negative).  An EXPLICIT on is honored — it is
        # always bitwise-safe — but a combination where half the
        # feature cannot exist is recorded, never silent.
        if cfg.frontier_mode == 1 and cfg.mode == "pull":
            clamps.append(
                "frontier_mode 1 with mode=pull -> delta exchange only "
                "(pure pull has no push pass to block-skip)")
        # Sparse-allreduce execution (round 16): the halving butterfly
        # tiles power-of-two member counts only — an explicit request
        # on an axis it cannot tile is recorded (the exchange then
        # keeps the gather structurally, same values either way).
        if cfg.frontier_algo == 1:
            hh_req, hd_req = resolve_hier(cfg.hier_hosts, cfg.hier_devs,
                                          n_shards, None)
            tiers = ((hh_req, hd_req) if hh_req else (n_shards,))
            bad = [m for m in tiers
                   if m > 1 and halving_steps(m) is None]
            if bad:
                clamps.append(
                    f"frontier_algo 1 on a non-power-of-two axis "
                    f"({'x'.join(str(m) for m in tiers)} members) -> "
                    "gather execution on that tier (the recursive-"
                    "halving butterfly pairs i with i^2^s)")
        # Round-10 schedule knobs: both bitwise-identical, so explicit
        # values are always SAFE; a combination where the feature
        # cannot exist is recorded, never silent (frontier precedent).
        if cfg.overlap_mode == 1:
            if cfg.mode == "pull":
                clamps.append(
                    "overlap_mode 1 with mode=pull -> 0 "
                    "(no push pass to split into self/remote halves)")
            elif not block_perm:
                clamps.append(
                    "overlap_mode 1 on a row-perm overlay -> 0 "
                    "(the self/remote split needs the block-perm "
                    "overlay's block-granular locality)")
        # Hierarchical two-tier exchange (round 11): resolve the
        # configured hosts x devs factorization against THIS build's
        # peer-shard count — illegal combinations degrade to flat with
        # a recorded clamp (resolve_hier; the 2-D engine re-resolves
        # against its peer sub-axis in engines.build_simulator).
        hier_hosts, hier_devs = resolve_hier(
            cfg.hier_hosts, cfg.hier_devs, n_shards, clamps)
        # n_msgs sizes the kernel's VMEM row block: wide message sets
        # shrink it (W * rowblk <= budget), and NARROW ones now widen it
        # up to MAX_CONFIG_ROWBLK — fewer grid steps and longer DMA
        # streams, the block-sizing lever against the partial-reuse gap
        # (W=1 -> 2048-row blocks vs the legacy 512).  The fused update
        # keeps twice the word-blocks resident, so its row block is
        # bounded by the HALVED budget directly (doubling n_msgs
        # instead under-shrinks whenever n_msg_words(2m) lands at 2w-1
        # — e.g. 129 messages: 258 msgs -> 9 words -> rowblk 448, but 5
        # words x 448 busts the 2048 budget).
        budget = MAX_WORDS_X_ROWBLK // (2 if cfg.fuse_update else 1)
        rowblk = auto.heuristic_rowblk(
            n_msg_words(n_msgs), budget, MAX_CONFIG_ROWBLK)
        topo = build_aligned(seed=cfg.prng_seed, n=n, n_slots=n_slots,
                             degree_law=law,
                             powerlaw_alpha=cfg.powerlaw_alpha,
                             n_shards=n_shards, n_msgs=n_msgs,
                             rowblk=rowblk,
                             roll_groups=cfg.roll_groups or None,
                             block_perm=block_perm)
        # Every remaining -1 auto passes through as configured:
        # __post_init__ resolves each schedule static once, by its rule
        # in autostatics.py.
        return cls(topo=topo, n_msgs=n_msgs, mode=cfg.mode,
                   fanout=cfg.fanout,
                   churn=ChurnConfig(rate=cfg.churn_rate),
                   byzantine_fraction=byz,
                   n_honest_msgs=n_honest,
                   max_strikes=cfg.max_missed_pings,
                   # probe cadence from the config's own intervals: one
                   # liveness sweep per ping_interval of message rounds
                   # (reference defaults 13 s / 5 s → every 3rd round).
                   # Sub-second message intervals keep their real ratio
                   # (ping=13, message=0.5 → every 26th round); only a
                   # zero/negative denominator falls back to 1:1.
                   liveness_every=max(1, round(
                       cfg.get_ping_interval()
                       / (cfg.get_message_interval()
                          if cfg.get_message_interval() > 0
                          else cfg.get_ping_interval()))),
                   message_stagger=cfg.message_stagger,
                   fuse_update=bool(cfg.fuse_update),
                   pull_window=pull_window,
                   faults=(plan if plan and plan.engine_active()
                           else None),
                   frontier_mode=cfg.frontier_mode,
                   frontier_threshold=cfg.frontier_threshold,
                   frontier_algo=cfg.frontier_algo,
                   prefetch_depth=cfg.prefetch_depth,
                   overlap_mode=cfg.overlap_mode,
                   hier_hosts=hier_hosts, hier_devs=hier_devs,
                   hier_mode=cfg.hier_mode,
                   seed=cfg.prng_seed)

    # ------------------------------------------------------------------
    def traffic_model(self, frontier_fill: float | None = None,
                      n_shards: int = 1,
                      n_hosts: int | None = None) -> dict:
        """Per-term analytic HBM model for one average round — the
        denominator behind the bench line's ``achieved_gb_s`` (measured
        wall-clock per round vs bytes this model says the round moves,
        comparable against the chip's ~800 GB/s HBM roof).

        Frontier-aware terms (round 8): with block skipping active
        (``_frontier_skip``) the push pass's y replay honors an
        activity mask of ``ceil(frontier_fill * T)`` evenly-spaced live
        blocks (``frontier_fill`` in [0, 1]; None = 1.0, the dense
        upper bound — the model never flatters a run whose frontier
        width it cannot know), and a ``frontier_scan`` term charges the
        one extra read of the send planes the activity reduce costs.
        With ``n_shards > 1`` and the delta exchange active, a
        ``delta_gather`` term gives the per-chip interconnect bytes of
        the exchange at that fill: the compacted ``(index, word)``
        tables when the changed words fit the capacity, the dense W
        frontier planes otherwise, plus the two per-peer mask planes
        the non-fused path gathers post-exchange.

        Per-TIER terms (round 11): whenever the exchange exists, the
        model also reports its ``ici_gather``/``dcn_gather`` split —
        per-chip fast-tier vs slow-tier interconnect bytes under the
        ``n_hosts`` factorization (None = this sim's resolved
        ``hier_hosts``; closed forms in :func:`project_exchange`,
        shared so model and projector cannot drift).  On a flat mesh
        the split is the degenerate one — everything on the fast tier,
        ``dcn_gather == 0`` — and the totals are bit-for-bit the
        pre-hierarchy model's.  Both tier keys are a DECOMPOSITION of
        the exchange, excluded from ``total`` like ``overlap_hidden``
        (the exchange itself is charged once, via ``delta_gather``).

        Kernel terms replay the grid's actual DMA-descriptor sequence
        (ops/aligned_kernel.stream_plan): a block whose index map
        repeats the previous grid step's is served from the resident
        VMEM buffer, but is still charged the topology's calibrated
        ``reuse_leak`` fraction of a stream — the round-5 kernel-only
        microbench measured the resident-buffer reuse PARTIAL (16->4
        distinct rolls cut kernel time 1.47x where perfect reuse
        predicts 2.3x; Y_REUSE_LEAK has the derivation).  XLA-side
        passes (permute/mask prep, the elementwise update, the popcount
        metrics) are charged one read+write per touched plane; on the
        fused-update path the update AND the census live inside the
        final kernel pass (per-block partial-popcount outputs) and
        those terms drop to the small per-peer planes.

        Returns ``{term: bytes, ..., "total": bytes}``;
        :meth:`hbm_bytes_per_round` is the total."""
        from p2p_gossipprotocol_tpu.ops.aligned_kernel import (ok_resident,
                                                               stream_plan)

        topo = self.topo
        R, D, W, C = topo.rows, topo.n_slots, self.n_words, LANES
        blk = topo.rowblk
        T = R // blk
        plane = R * C * 4                # one int32[R, 128] plane
        wp = W * plane                   # int32[W, R, 128]
        slot8 = D * R * C                # one int8[D, R, 128] table
        fused = topo.ytab is not None
        fin = self.fuse_update
        # The gossip passes' partial-reuse leak depends on the stream
        # implementation: the manual double-buffered stream issues no
        # descriptor for a resident re-serve, so its leak is 0 by
        # construction (Y_REUSE_LEAK_PREFETCH — the conservative
        # direction for every number this model feeds); the liveness
        # pass stays on the BlockSpec pipeline and keeps the
        # calibrated κ.
        leak = (Y_REUSE_LEAK_PREFETCH if self._prefetch
                else topo.reuse_leak)
        leak_live = topo.reuse_leak
        rolls = np.asarray(topo.rolls)
        ytab = None if topo.ytab is None else np.asarray(topo.ytab)

        fill = 1.0 if frontier_fill is None else min(max(
            frontier_fill, 0.0), 1.0)
        push_active = None
        if self._frontier_skip:
            # evenly spaced live blocks — the replay's stand-in for a
            # frontier this wide (any placement; the replay's dedup
            # makes spacing second-order)
            k_act = int(np.ceil(fill * T))
            push_active = np.zeros(T, bool)
            if k_act > 0:
                push_active[np.floor(
                    np.arange(k_act) * T / k_act).astype(int)] = True

        def y_eff(plan, lk=None):
            # calibrated partial reuse: full streams for index changes,
            # leak-fraction streams for resident-buffer re-serves
            # (skip-gated steps are re-serves of the pinned resident
            # block — same charge, so the model stays conservative)
            lk = leak if lk is None else lk
            return plan["y"] + lk * (plan["y_naive"] - plan["y"])

        def pass_bytes(n_slots_d, final, seeded, active=None, pull=False):
            plan = stream_plan(rolls, T, ytab=ytab, n_slots=n_slots_d,
                               active=active)
            eff = y_eff(plan)
            b = eff * W * blk * C * 4    # packed sender planes
            b += plan["tab"] * blk * C   # colidx (int8)
            b += plan["row"] * blk * C   # gate (int8)
            b += wp                      # OR-accumulator out
            if fused and self._prefetch == 2 and ok_resident(R, pull):
                b += plane               # src_ok, read once into VMEM
            elif fused:
                b += eff * blk * C * 4   # src_ok rides each y fetch
            if final:
                # in-kernel seen-update + census: seen in, seen' out,
                # rmask + census-ok planes, the partial-popcount tiles
                b += 2 * wp + 2 * plane + 2 * T * 8 * C * 4
            if seeded:
                b += wp                  # pushpull acc_init re-read
            return b

        terms = {}
        if self.mode in ("push", "pushpull"):
            terms["push_pass"] = pass_bytes(
                D, final=fin and self.mode == "push", seeded=False,
                active=push_active)
        if self.mode in ("pull", "pushpull"):
            # Pull-window: a window-sized grid whose slots share one
            # block roll — the replay sees the single stream directly.
            terms["pull_pass"] = pass_bytes(
                self._pull_slots, final=fin,
                seeded=fin and self.mode == "pushpull", pull=True)
        n_passes = len(terms)
        # XLA-side mask + permute gather per non-fused pass
        terms["prep"] = 0 if fused else 3 * wp * n_passes
        if self.fanout > 0 and self.mode != "pull":
            terms["fanout_shift"] = R * C          # int8 shift plane
        if self._liveness:
            plan = stream_plan(rolls, T, ytab=ytab)
            lv = (y_eff(plan, leak_live) * blk * C * 4   # alive plane
                  + 4 * slot8                 # colidx/strikes r+w
                  + 2 * T * 8 * C * 4         # eviction tiles w + reduce
                  + (plane if fused else 3 * plane))   # gather/prep
            terms["liveness"] = lv // self.liveness_every
        if fin:
            # update + census are inside the final pass; what remains
            # XLA-side are the small per-peer planes (ok/live popcounts)
            terms["update"] = 0
            terms["metrics"] = 2 * plane
        else:
            # XLA elementwise update: read each pass's receive words,
            # read seen, write new + seen'; metrics re-read the fresh
            # new (deliveries) and seen (coverage) planes
            terms["update"] = (n_passes + 3) * wp
            terms["metrics"] = 2 * wp + 2 * plane
        if self._frontier_skip and "push_pass" in terms:
            # the per-block activity reduce reads the send planes once
            terms["frontier_scan"] = wp
        overlap = (self._overlap and n_shards > 1 and fused
                   and "push_pass" in terms)
        if overlap:
            # the self/remote split's honest cost: the second grid walk
            # re-streams the per-step tables (colidx + gate) and
            # round-trips the pass-A accumulator through acc_init
            plan = stream_plan(rolls, T, ytab=ytab)
            terms["overlap_extra"] = (plan["tab"] * blk * C
                                      + plan["row"] * blk * C + 2 * wp)
        hidden = None
        tier = None
        halving = None
        if n_shards > 1 and self._frontier_delta:
            # interconnect bytes of the exchange, per chip per round
            # (the measure_round8/11 A/Bs' gathered-bytes columns):
            # the sparse table when the worst shard's changed words
            # fit K, the dense frontier planes otherwise; the
            # non-fused path additionally gathers the alive/byz mask
            # planes it now applies post-exchange.  Closed forms live
            # in project_exchange, which also prices the per-tier
            # split under the hier factorization.
            # an explicit n_hosts is the caller's what-if question;
            # None reads this sim's RESOLVED state (hier_mode off ->
            # the flat exchange really runs -> flat pricing)
            nh = (n_hosts if n_hosts is not None
                  else (self.hier_hosts if self._hier else 0))
            ex = project_exchange(
                n_peers=R * C, n_msgs=self.n_msgs, n_shards=n_shards,
                n_hosts=nh, frontier_fill=fill,
                threshold=self.frontier_threshold, fused=fused,
                rows=R, algo=int(self._frontier_algo))
            delta = ex["delta_gather"]
            tier = (ex["ici_gather"], ex["dcn_gather"])
            halving = (ex.get("halving_exchange"),
                       ex.get("gather_exchange"))
            if overlap:
                # the split moves the exchange off the critical path:
                # its bytes land in ``overlap_hidden`` (reported,
                # excluded from ``total`` — which only LOWERS every
                # achieved_gb_s/roofline_frac built on it)
                hidden = delta
            else:
                terms["delta_gather"] = delta
        elif overlap:
            # dense sharded exchange (never in ``total`` — it is
            # interconnect, not HBM): report the hidden frontier-plane
            # gather so the A/B can account what the split buys
            hidden = wp
        terms = {k: int(v) for k, v in terms.items()}
        terms["total"] = sum(terms.values())
        if hidden is not None:
            terms["overlap_hidden"] = int(hidden)
        if tier is not None:
            # per-tier decomposition of the exchange — reported next
            # to it, never double-charged into ``total``
            terms["ici_gather"] = int(tier[0])
            terms["dcn_gather"] = int(tier[1])
        if halving is not None and halving[0] is not None:
            # round 16: both execution quotes side by side (the A/B
            # ratio's provenance) — the exchange itself is charged once
            # above, through ``delta_gather`` at the RESOLVED algo
            terms["halving_exchange"] = int(halving[0])
            terms["gather_exchange"] = int(halving[1])
        return terms

    def hbm_bytes_per_round(self) -> int:
        """Total of :meth:`traffic_model` — the bytes the live
        roofline divides wall-clock into for ``achieved_gb_s``."""
        return self.traffic_model()["total"]

    # ------------------------------------------------------------------
    def _seed_plan(self):
        """((byz_b, src), key) — the byzantine draw, the per-column
        source positions (flat ``row*128 + lane`` ids) and the state's
        round-0 key, deterministic in the seed so init_state and the
        staggered in-round generation (aligned_round) place every rumor
        identically.

        Honest rumors must originate at honest peers (a byzantine source
        never relays — state.py:message_sources has the same rule);
        sources spread evenly over the honest population."""
        rows = self.topo.rows
        k_byz, key = jax.random.split(jax.random.PRNGKey(self.seed))
        valid_b = self.topo.valid_w != 0
        if self.byzantine_fraction > 0.0:
            byz_b = (jax.random.uniform(k_byz, (rows, LANES))
                     < self.byzantine_fraction) & valid_b
        else:
            byz_b = jnp.zeros((rows, LANES), bool)
        from p2p_gossipprotocol_tpu.state import sources_from_mask

        ok_flat = (valid_b & ~byz_b).reshape(-1)
        return (byz_b, sources_from_mask(ok_flat, self.n_msgs,
                                         self._n_honest)), key

    def _message_plan(self):
        """(byz_b, src), computed once at construction."""
        return self._plan_cache

    def init_state(self) -> AlignedState:
        """The round-0 state from one compiled program over the seed
        plan; ``compile`` (first call for a shape) and ``dispatch`` are
        telemetry spans inside ``init_state`` (docs/OBSERVABILITY.md)."""
        with telemetry.span("init_state"):
            byz_b, src = self._plan_cache
            args = (self.topo.valid_w, byz_b, src, self._init_key)
            statics = dict(n_words=self.n_words, n_slots=self.topo.n_slots,
                           n_msgs=self.n_msgs, n_honest=self._n_honest,
                           seeded=self.message_stagger <= 0,
                           liveness=self._liveness)
            cache_key = (tuple(statics.values()),
                         tuple((a.shape, a.dtype, a.sharding) for a in args))
            fn = _INIT_PROGRAMS.get(cache_key)
            if fn is None:
                with telemetry.span("compile"):
                    fn = _init_jit.lower(*args, **statics).compile()
                _INIT_PROGRAMS[cache_key] = fn
                telemetry.counter_add("init_state_compiles_total")
            with telemetry.span("dispatch"):
                seen, byz_w, alive_b, strikes, key, round0 = fn(*args)
            return AlignedState(seen_w=seen, frontier_w=seen,
                                alive_b=alive_b, byz_w=byz_w,
                                strikes=strikes, key=key, round=round0)

    # ------------------------------------------------------------------
    def step(self, state: AlignedState, topo: AlignedTopology | None = None
             ) -> tuple[AlignedState, AlignedTopology, dict]:
        """One full round: churn → liveness/rewire → (byz inject) → gossip
        — the same pipeline as sim.Simulator.step.  ``topo`` is carried
        because rewiring mutates the lane-choice table (the aligned
        analogue of the edge engine's dst mutation)."""
        topo = self.topo if topo is None else topo
        grows = jnp.arange(topo.rows, dtype=jnp.int32)
        return aligned_round(self, state, topo, grows=grows,
                             t_off=jnp.int32(0),
                             gather=lambda x: x, reduce=lambda x: x)


    # ------------------------------------------------------------------
    def run(self, rounds: int, state: AlignedState | None = None,
            topo: AlignedTopology | None = None, warmup: bool = False):
        """Fixed-round scan with full metric history; returns the same
        :class:`sim.SimResult` as the edge engine.

        ``warmup=True`` executes the compiled program once before the
        timed run, so ``wall`` excludes compilation and the first
        execution's one-time costs."""
        import time as _time

        from p2p_gossipprotocol_tpu.sim import SimResult

        state = self.init_state() if state is None else state
        topo = self.topo if topo is None else topo
        if rounds not in self._run_cache:
            def scan_fn(st, tp):
                def body(carry, _):
                    s, t = carry
                    s, t, metrics = self.step(s, t)
                    return (s, t), metrics
                return jax.lax.scan(body, (st, tp), None, length=rounds)
            self._run_cache[rounds] = jax.jit(scan_fn)
        fn = self._run_cache[rounds]
        if warmup:
            out = fn(state, topo)
            jax.device_get(out[0][0].round)
        t0 = _time.perf_counter()
        (state, topo), ys = fn(state, topo)
        int(jax.device_get(state.round))  # forces completion
        wall = _time.perf_counter() - t0
        return SimResult.from_metrics(state, topo, ys, wall)

    def run_to_coverage(self, target: float = 0.99, max_rounds: int = 256,
                        state: AlignedState | None = None,
                        topo: AlignedTopology | None = None,
                        warmup: bool = True, check_every: int = 1):
        """(state, topo, rounds_run, wall_s) — same 4-tuple shape as
        sim.Simulator.run_to_coverage.  ``topo`` must be passed when
        resuming a churned run (rewire mutates the lane table).  Compile
        and (with ``warmup``) first-execution program-upload excluded;
        completion forced via a scalar device_get, so the wall-clock is
        honest.

        ``check_every=K`` evaluates the coverage condition only after
        each chunk of K rounds (a ``lax.scan`` inside the while body).
        K=1 reproduces the classic loop exactly.  K>1 exists because the
        while cond depends on the round's census reduction — a full
        synchronization barrier per round that serializes the pipeline
        (measured 13.6 ms/round in-loop vs 3.1 ms/round in the free-
        running scan at 1M x 16) — and checking every K rounds amortizes
        that barrier.  The run may overshoot convergence by up to K-1
        rounds; those extra rounds are INCLUDED in rounds_run and the
        wall-clock, so the reported time-to-target is conservative,
        never flattering.  ``max_rounds`` stays a HARD cap (same
        contract as sim.Simulator.run_to_coverage): the chunked loop
        only takes chunks that fit under the cap, and a per-round tail
        loop inside the same program finishes the remainder."""
        import time as _time

        if check_every < 1:
            raise ValueError("check_every must be >= 1")
        state = self.init_state() if state is None else state
        topo = self.topo if topo is None else topo
        cache_key = (target, max_rounds, check_every)
        with telemetry.span("run_to_coverage"):
            if cache_key not in self._loop_cache:
                from p2p_gossipprotocol_tpu.state import (
                    build_coverage_loop, stagger_sched_end)

                with telemetry.span("compile"):
                    sched_end = stagger_sched_end(self._n_honest,
                                                  self.message_stagger)
                    looped = build_coverage_loop(
                        self.step, target=target, max_rounds=max_rounds,
                        check_every=check_every, sched_end=sched_end)
                    fn = jax.jit(looped)
                    self._loop_cache[cache_key] = fn.lower(
                        state, topo).compile()
                telemetry.counter_add("coverage_loop_compiles_total")
            fn_c = self._loop_cache[cache_key]
            if warmup:
                out = fn_c(state, topo)
                jax.device_get(out[0].round)
            t0 = _time.perf_counter()
            # dispatch: the compiled loop's call until it returns to the
            # host; fetch: the wait for the round count, i.e. the device
            with telemetry.span("dispatch"):
                st, tp, cov = fn_c(state, topo)
            with telemetry.span("fetch"):
                rounds_run = int(jax.device_get(st.round))
            wall = _time.perf_counter() - t0
        return st, tp, rounds_run, wall


def aligned_coverage(sim: AlignedSimulator, state: AlignedState,
                     topo: AlignedTopology | None = None) -> float:
    """Host-callable honest coverage of a state — the while-loop benchmark
    path (run_to_coverage) discards its in-loop coverage scalar, so a
    boundary-round result (rounds == max_rounds with the target already
    reached) needs this recheck.  Mirrors aligned_round's census
    (ok = live, honest, valid rows; honest message columns only)."""
    topo = sim.topo if topo is None else topo
    alive_w = jnp.where(state.alive_b, jnp.int32(-1), jnp.int32(0))
    ok_w = alive_w & ~state.byz_w & topo.valid_w
    # pair, not a flat sum: popcount(ok_w) = 32 x n_ok hits 2^31 at
    # exactly 64M peers (the 64M ceiling probe came back coverage=8.0)
    n_ok = max(_pair_int(jax.device_get(_popcount_pair(ok_w))) >> 5, 1)
    hits = _pair_int(jax.device_get(_popcount_pair(   # exact >2^31 bits
        state.seen_w & ok_w[None] & sim._honest_mask[:, None, None])))
    n_cols = sim._n_honest
    if sim.message_stagger > 0:
        # columns GENERATED so far (aligned_round's denominator rule):
        # a plane-wise OR leaves the nonempty-column bits, popcounted
        # under the honest mask — same jnp ops as the in-loop census
        or_w = jax.lax.reduce(state.seen_w, jnp.int32(0),
                              jax.lax.bitwise_or, (1, 2))
        n_cols = int(jax.device_get(
            _popcount_sum(or_w & sim._honest_mask)))
    return hits / (n_ok * max(n_cols, 1))


def aligned_round(sim: AlignedSimulator, state: AlignedState,
                  topo: AlignedTopology, *, grows: jax.Array,
                  t_off: jax.Array, gather, reduce,
                  msg_reduce=None, honest_mask: jax.Array | None = None,
                  junk_mask: jax.Array | None = None,
                  w_off: jax.Array | int = 0,
                  msg_only_reduce=None,
                  hash_seed: jax.Array | None = None,
                  msg_srcs: jax.Array | None = None,
                  fr: FrontierCarry | None = None,
                  fr_axis=None,
                  fr_pmax_axes: tuple = (),
                  fr_shards: int = 1,
                  fr_ici_axis: str | None = None,
                  fr_hosts: int = 1,
                  n_shards: int = 1):
    """THE round implementation, shared by the single-chip engine,
    AlignedShardedSimulator (parallel/aligned_sharded.py) and the 2-D
    peers x message-planes engine (parallel/aligned_2d.py).

    The callers differ only in how rows/planes map to the global grid:
      * ``grows``  — this caller's rows' GLOBAL row ids (per-row RNG keys);
      * ``t_off``  — this caller's first row-block index (offsets the
        kernel's per-slot block rolls);
      * ``gather`` — identity, or ``all_gather`` over the peer mesh axis
        (makes the row-permuted sender/alive words global before the
        kernels; must gather the ROWS axis, which is ndim-2: axis 0 of
        the 2D alive words, axis 1 of the 3D message planes);
      * ``reduce`` — identity, or ``psum`` over the peer axis (per-PEER
        metrics: live count, evictions, the coverage denominator);
      * ``msg_reduce`` — reduction for metrics that also sum over
        MESSAGE planes (deliveries, the coverage numerator); defaults to
        ``reduce``; the 2-D engine psums these over both mesh axes;
      * ``honest_mask``/``junk_mask`` — this caller's slice of the
        per-plane masks (int32[W_local]); default: the sim's full-width
        masks (the message axis is unsharded).
      * ``hash_seed``/``msg_srcs`` — per-SCENARIO overrides for the
        fleet engine (fleet/engine.py vmaps this round over a scenario
        axis): the liveness rewire-hash seed (defaults to the static
        ``sim.seed``) and the staggered-generation source table
        (defaults to ``sim._message_plan()``).  Both default to the
        solo engine's values, so every existing caller compiles the
        exact program it always did.
      * ``fr``/``fr_axis``/``fr_pmax_axes``/``fr_shards`` — the
        frontier-sparse exchange (sharded engines only): a
        :class:`FrontierCarry`, the mesh axis (or axis tuple) the send
        planes gather over, the axes the regime signal reduces over,
        and the peer shard count.  With ``fr`` the round REPLACES the
        dense send gathers with :func:`_frontier_exchange`'s output
        (the global frontier scatter and the per-chip seen replica),
        applies the row permutation and the alive/byzantine send masks
        locally POST-gather (so gathered content stays monotone), and
        returns a 4-tuple ``(state, topo, metrics, fr')`` — every
        other caller keeps the 3-tuple.  The fault plane's drop gates
        hash (receiver, slot, round) — never the transported words —
        so both paths see identical gate decisions by construction.
      * ``fr_ici_axis``/``fr_hosts`` — the hierarchical two-tier
        exchange (round 11): when set, ``fr_axis`` is the slow DCN
        (host) axis and ``fr_ici_axis`` the fast intra-host axis; the
        exchange runs per tier with per-tier censuses and regimes
        (``_frontier_exchange``'s hierarchical path), and the metrics
        gain an ``fr_sparse_ici`` diagnostic next to ``fr_sparse``.
      * ``n_shards`` — the peer-axis shard count (1 for the solo and
        fleet engines).  With ``sim._overlap`` and a block-perm
        overlay, ``n_shards > 1`` engages the compute-hidden exchange:
        the push pass splits into a self-shard pass over the LOCAL
        send planes (no collective dependency — the exchange overlaps
        it on hardware) and a remote pass over the gathered planes,
        OR-seeded via ``acc_init`` (:func:`_overlap_plans`).
    Everything else — churn, strikes/rewire, byzantine, gossip passes,
    metrics — is this one code path, so the engines cannot drift.

    Each phase runs under a ``jax.named_scope`` (``churn``,
    ``liveness``, ``injection``, ``exchange``, ``prep``, ``push``,
    ``pull``, ``update``, ``census``) and the kernels carry their pass's
    name (``push``, ``push_self``, ``pull``, ``liveness``), so a
    profiler trace tells the phases apart; names change op metadata
    only, never a result."""
    if msg_reduce is None:
        msg_reduce = reduce
    if hash_seed is None:
        hash_seed = sim.seed
    if msg_only_reduce is None:        # sums over MESSAGE shards only —
        msg_only_reduce = (lambda x: x)  # identity unless planes shard
    hmask = sim._honest_mask if honest_mask is None else honest_mask
    jmask = sim._junk_mask if junk_mask is None else junk_mask
    def prow(x):   # apply the row permutation on the rows (ndim-2) axis
        return jnp.take(x, topo.perm, axis=x.ndim - 2)

    # Block-perm overlays (topo.ytab) run the FUSED path: kernels read
    # the raw state planes through the perm∘roll index table with the
    # send mask ANDed in-kernel — prow and the host-side masking above
    # the kernels disappear entirely (the traffic model's 3W prep term).
    fused = topo.ytab is not None
    if fused:
        T_local = state.seen_w.shape[1] // topo.rowblk
        ytab_local = jax.lax.dynamic_slice(
            topo.ytab, (jnp.int32(0), jnp.int32(t_off)),
            (topo.ytab.shape[0], T_local))

    valid_b = topo.valid_w != 0
    # k_rew is retired (rewire candidates are hashed in-kernel) but the
    # 5-way split is kept so the round's key schedule — and with it every
    # churn/pull/fanout trajectory — is unchanged.
    key, k_churn, k_rew, k_pull, k_fan = jax.random.split(state.key, 5)
    del k_rew

    with jax.named_scope("churn"):
        alive_b = state.alive_b
        if sim.churn.rate > 0.0 or sim.churn.revive > 0.0:
            alive_b = churn_rows(k_churn, grows, alive_b, valid_b,
                                 state.round, sim.churn)

        # -- fault plane (faults.FaultPlan; None compiles the plain round) --
        # Every fault draw is keyed on (plan seed, round, global row) — never
        # the simulation's own key chain — so an unfaulted run's trajectory
        # is untouched and faulted runs keep the bitwise sharded-vs-unsharded
        # contract (per-global-row fold_ins + the kernels' global-id hash).
        plan = sim.faults
        fkey = None
        if plan is not None and plan.engine_active():
            fkey = faults_lib.round_key(plan, state.round)
        if plan is not None and (plan.crash or plan.recover):
            # Scheduled crash/recovery: real deaths/revivals — the liveness
            # strikes below observe them, unlike partitions, which sever
            # transfers only.  Padding rows can never revive (& valid_b).
            with jax.named_scope("faults"):
                alive_b = faults_lib.schedule_step(
                    plan, fkey, alive_b, valid_b, state.round,
                    lambda k: row_uniform(k, grows, (LANES,)))
        defer_w = None
        if (plan is not None and plan.delay > 0.0
                and sim.mode in ("push", "pushpull")):
            # Relay delay: a peer's push of its frontier slips one round
            # (sender-side, per-peer — the synchronous-round model's delayed
            # delivery); pull serves are unaffected (the peer's state is
            # intact, only its relay is late).
            with jax.named_scope("faults"):
                u = row_uniform(
                    jax.random.fold_in(fkey, faults_lib.TAG_DEFER), grows,
                    (LANES,))
                defer_w = jnp.where((u < plan.delay) & alive_b,
                                    jnp.int32(-1), jnp.int32(0))
        kf = plan is not None and plan.kernel_active()
        if kf:
            # Per-link drop + partition gates, evaluated in-register inside
            # the kernels (ops/aligned_kernel.py fault gate) — no HBM mask
            # tensor exists.  Push and pull passes get decorrelated hash
            # seeds (two passes = two independent uses of the same links).
            gbase_f = grows[::topo.rowblk]
            fmeta_push = faults_lib.kernel_meta(plan, state.round, 0)
            fmeta_pull = faults_lib.kernel_meta(plan, state.round, 1)
        alive_w = jnp.where(alive_b, jnp.int32(-1), jnp.int32(0))

    rolls_off = topo.rolls + t_off
    with jax.named_scope("liveness"):
        strikes = state.strikes
        n_evict = jnp.int32(0)
        if sim._liveness:
            # Candidate lanes are hashed in-kernel from (global peer id,
            # slot, round) — no int8[D, R, 128] tensor materialized per
            # round — and with ``liveness_every > 1`` the whole pass
            # (including its all_gather on the sharded path) only runs on
            # sweep rounds, mirroring the reference's probe cadence of one
            # ping sweep per ~2.6 message intervals (peer.cpp:330 vs 377).
            blk = min(topo.rowblk, topo.colidx.shape[1])

            def lv_run(ops):
                col, stk = ops
                y_alive = (gather(alive_w) if fused
                           else prow(gather(alive_w)))
                # the pass updates the lane table and strikes in place
                # and counts first crossings in per-block tiles
                col2, stk2, evict_tiles = liveness_pass(
                    y_alive, col, stk, topo.deg, rolls_off, topo.subrolls,
                    gbase=grows[::blk], round_idx=state.round,
                    hash_seed=hash_seed,
                    ytab=ytab_local if fused else None,
                    max_strikes=sim.max_strikes,
                    rowblk=topo.rowblk, interpret=sim.interpret)
                return col2, stk2, jnp.sum(evict_tiles, dtype=jnp.int32)

            def lv_skip(ops):
                col, stk = ops
                return col, stk, jnp.int32(0)

            if sim.liveness_every > 1:
                colidx, strikes, ev_local = jax.lax.cond(
                    state.round % sim.liveness_every == 0, lv_run, lv_skip,
                    (topo.colidx, strikes))
            else:
                colidx, strikes, ev_local = lv_run((topo.colidx, strikes))
            topo = topo.replace(colidx=colidx)
            n_evict = reduce(ev_local)

    with jax.named_scope("injection"):
        seen_w, frontier_w = state.seen_w, state.frontier_w
        if sim._n_honest < sim.n_msgs:
            # Byzantine injection (models/byzantine.py:24-38): junk bits
            # enter every byzantine peer's seen+frontier each round.
            inject = state.byz_w[None] & jmask[:, None, None] & ~seen_w
            seen_w = seen_w | inject
            frontier_w = frontier_w | inject

        if sim.message_stagger > 0:
            # Staggered generation: round m*k injects column m's bit at its
            # source (the messageGenerationLoop tick, peer.cpp:357-377) —
            # one dynamic single-element update, no plane-sized traffic.
            # Runs after churn, so a source that died before its activation
            # round never generates (the reference's generation thread stops
            # with its process); the frontier bit is relayed THIS round,
            # like the round-0 seeding.  All coordinates derive from the
            # replicated round scalar + the deterministic plan, so every
            # shard computes the same global decision and applies it only if
            # the (plane, row) cell is local.
            k = sim.message_stagger
            r = state.round
            m = r // k
            srcs = sim._message_plan()[1] if msg_srcs is None else msg_srcs
            src = srcs[jnp.clip(m, 0, sim.n_msgs - 1)]
            grow, lane = src // LANES, src % LANES
            W_l, rows_l = seen_w.shape[0], seen_w.shape[1]
            lrow = grow - grows[0]
            lw = (m // WORD_BITS) - w_off
            safe_r = jnp.clip(lrow, 0, rows_l - 1)
            safe_w = jnp.clip(lw, 0, W_l - 1)
            src_alive = jax.lax.dynamic_slice(
                alive_b, (safe_r, lane), (1, 1))[0, 0]
            do = ((r % k == 0) & (m < sim._n_honest) & src_alive
                  & (lrow >= 0) & (lrow < rows_l)
                  & (lw >= 0) & (lw < W_l))
            bit = jnp.where(do,
                            jnp.left_shift(jnp.int32(1), m % WORD_BITS),
                            jnp.int32(0))
            cell = (safe_w, safe_r, lane)
            seen_w = jax.lax.dynamic_update_slice(
                seen_w,
                jax.lax.dynamic_slice(seen_w, cell, (1, 1, 1)) | bit, cell)
            frontier_w = jax.lax.dynamic_update_slice(
                frontier_w,
                jax.lax.dynamic_slice(frontier_w, cell, (1, 1, 1)) | bit,
                cell)

    # -- frontier-sparse exchange (sharded engines, fr is not None) ----
    # Runs AFTER the injections above: byzantine junk and staggered
    # sources enter seen AND frontier together, which is exactly what
    # keeps the exchange's monotonicity argument airtight (every bit
    # the round gains rides the frontier).  The dense gathers below are
    # then replaced wholesale; permutation and send masks apply
    # post-gather, bitwise-identically (AND and the row gather commute
    # elementwise with the all_gather layout).
    F_g = seen_g = g_alive = g_byz = g_defer = None
    fr_sparse = fr_words = fr_sparse_ici = None
    fr_halving = fr_halving_ici = None
    if fr is not None:
        with jax.named_scope("exchange"):
            (F_g, fr, fr_sparse, fr_words, fr_sparse_ici, fr_halving,
             fr_halving_ici) = _frontier_exchange(
                    sim, frontier_w, fr, fr_axis, fr_pmax_axes, fr_shards,
                    ici_axis=fr_ici_axis, n_hosts=fr_hosts)
            seen_g = fr.replica_w
            if not fused:
                g_alive = gather(alive_w)
                g_byz = fr.byz_g    # static draw, gathered at carry init
                if defer_w is not None:
                    g_defer = gather(defer_w)

    with jax.named_scope("prep"):
        if fused:
            # the in-kernel send mask: -1 where the source is alive and
            # honest (dead peers don't send; byzantine peers never relay);
            # the push pass additionally drops deferred relayers, while the
            # pull pass keeps serving them (delay is a relay fault, not a
            # state fault)
            src_ok = gather(alive_w & ~state.byz_w)
            src_ok_push = src_ok
            if defer_w is not None:
                with jax.named_scope("faults"):
                    src_ok_push = gather(alive_w & ~state.byz_w & ~defer_w)
        # In-kernel seen-update (sim.fuse_update): the FINAL pass of the
        # round takes the receiver's seen planes + receive mask and emits
        # (new, seen') straight from its VMEM-resident accumulator — plus
        # the round CENSUS as per-block partial-popcount tiles (deliveries
        # bits of ``new``, coverage bits of ``seen' & ok & hmask``), so the
        # XLA-side 2W-plane metrics re-read does not exist on this path; in
        # pushpull the push receive seeds the pull accumulator.  Dead peers
        # don't receive either way (the link is gone — gossip.py:_advance).
        fin = sim.fuse_update
        rmask_w = (topo.valid_w & alive_w) if fin else None
        # ok = live, honest, valid — the coverage row filter (edge engine's
        # coverage_of); feeds the in-kernel census and the n_ok denominator.
        ok_w = alive_w & ~state.byz_w & topo.valid_w
        new = seen = None
        dpb = cpb = None
        deferred_w = None
        if defer_w is not None:
            # The would-have-been relays a deferred peer holds back: they
            # re-enter the frontier below, so the transfer lands one round
            # late instead of never (flood-once would otherwise drop it).
            with jax.named_scope("faults"):
                deferred_w = (frontier_w & alive_w[None]
                              & ~state.byz_w[None] & defer_w[None])
    if sim.mode in ("push", "pushpull"):
        # Dead peers don't send; byzantine peers never relay (suppression,
        # models/gossip.py:50-58) — both masked at the source words.
        with jax.named_scope("prep"):
            if fr is not None:
                if fused:
                    y = F_g
                else:
                    send_g = F_g & g_alive[None] & ~g_byz[None]
                    if g_defer is not None:
                        send_g = send_g & ~g_defer[None]
                    y = prow(send_g)
            elif fused:
                y = gather(frontier_w)
            else:
                send = frontier_w & alive_w[None] & ~state.byz_w[None]
                if defer_w is not None:
                    send = send & ~defer_w[None]
                y = prow(gather(send))
            # Compute-hidden exchange (round 10): the self/remote split
            # engages only sharded, fused, and with the knob resolved on —
            # pass A's plan depends on nothing gathered, so the collective
            # that produced ``y`` overlaps it on hardware.
            split = sim._overlap and n_shards > 1 and fused
            yidx = yact = None
            yidx_a = yact_a = None
            if split:
                (yidx_a, yact_a), (yidx, yact) = _overlap_plans(
                    frontier_w, y, topo.rowblk, t_off, ytab_local,
                    skip=sim._frontier_skip)
            elif sim._frontier_skip:
                # in-kernel block skipping: y blocks with no send bits this
                # round are gated off and never streamed — exact however
                # sparse or dense the frontier is (dead blocks OR in zero)
                yidx, yact = _skip_plan(
                    y, topo.rowblk, state.seen_w.shape[1] // topo.rowblk,
                    rolls_off=rolls_off,
                    ytab_local=ytab_local if fused else None)
            if sim.fanout > 0:
                # Rumor mongering: each peer listens on a random fanout-slot
                # window this round (shard-invariant per-row draw, same
                # discipline as the pull contact below).
                u = row_randint(k_fan, grows, (LANES,), 0, 1 << 30, jnp.int32)
                deg32 = topo.deg.astype(jnp.int32)
                shift = (u % jnp.maximum(deg32, 1)).astype(jnp.int8)
            else:
                shift = None
            push_final = fin and sim.mode == "push"
            acc0 = None
        with jax.named_scope("push"):
            if split:
                # Pass A: the self-shard contribution, from purely LOCAL
                # operands (raw local send planes + the ungathered send
                # mask) — traced with no dependency on the exchange, which
                # is the whole overlap.  Remote steps are gated off; pass B
                # gates the local ones off and OR-seeds from here.
                ok_self = alive_w & ~state.byz_w
                if defer_w is not None:
                    ok_self = ok_self & ~defer_w
                acc0 = gossip_pass(frontier_w, topo.colidx, topo.deg,
                                   rolls_off, topo.subrolls, pull=False,
                                   fanout=sim.fanout, shift=shift,
                                   ytab=ytab_local, src_ok=ok_self,
                                   fault_meta=fmeta_push if kf else None,
                                   gbase=gbase_f if kf else None,
                                   yidx=yidx_a, yact=yact_a,
                                   prefetch_depth=sim._prefetch,
                                   rowblk=topo.rowblk, name="push_self",
                                   interpret=sim.interpret)
            recv = gossip_pass(y, topo.colidx, topo.deg, rolls_off,
                               topo.subrolls, pull=False, fanout=sim.fanout,
                               shift=shift,
                               ytab=ytab_local if fused else None,
                               src_ok=src_ok_push if fused else None,
                               acc_init=acc0,
                               seen=seen_w if push_final else None,
                               rmask=rmask_w if push_final else None,
                               census_ok=ok_w if push_final else None,
                               census_hmask=hmask if push_final else None,
                               fault_meta=fmeta_push if kf else None,
                               gbase=gbase_f if kf else None,
                               yidx=yidx, yact=yact,
                               prefetch_depth=sim._prefetch,
                               rowblk=topo.rowblk, name="push",
                               interpret=sim.interpret)
        if push_final:
            new, seen, dpb, cpb = recv
    elif not fin:               # pure anti-entropy pull
        recv = jnp.zeros_like(seen_w)
    if sim.mode in ("pull", "pushpull"):
        # Anti-entropy: each peer pulls one random slot's neighbor's
        # full seen-set; dead/byzantine neighbors serve nothing
        # (gossip.py pull_round's alive[nbr] & ~byzantine[nbr]).
        # With sim.pull_window the contact is drawn from the FIRST roll
        # group only and the pass runs a Dw-slot grid (one shared block
        # roll -> ONE seen-plane stream); Dw == n_slots when off, which
        # reproduces the unrestricted draw and grid exactly.
        with jax.named_scope("prep"):
            if fr is not None:
                # the per-chip replica IS gather(seen) bitwise — the dense
                # seen gather does not exist on this path at all
                ys = (seen_g if fused
                      else prow(seen_g & g_alive[None] & ~g_byz[None]))
            elif fused:
                ys = gather(state.seen_w)
            else:
                ys = prow(gather(
                    state.seen_w & alive_w[None] & ~state.byz_w[None]))
            Dw = sim._pull_slots
            u = row_randint(k_pull, grows, (LANES,), 0, 1 << 30, jnp.int32)
            deg32 = jnp.minimum(topo.deg.astype(jnp.int32), Dw)
            delta = (u % jnp.maximum(deg32, 1)).astype(jnp.int8)
            delta = jnp.where(deg32 > 0, delta,
                              jnp.int8(Dw))                # no contact
        with jax.named_scope("pull"):
            # the Dw-slot grid reads the first Dw slots of the whole
            # tables through its index maps: nothing is sliced
            pulled = gossip_pass(ys, topo.colidx, delta, rolls_off,
                                 topo.subrolls, pull=True, slots=Dw,
                                 ytab=ytab_local if fused else None,
                                 src_ok=src_ok if fused else None,
                                 acc_init=(recv if fin and
                                           sim.mode == "pushpull" else None),
                                 seen=seen_w if fin else None,
                                 rmask=rmask_w,
                                 census_ok=ok_w if fin else None,
                                 census_hmask=hmask if fin else None,
                                 fault_meta=fmeta_pull if kf else None,
                                 gbase=gbase_f if kf else None,
                                 prefetch_depth=sim._prefetch,
                                 rowblk=topo.rowblk, name="pull",
                                 interpret=sim.interpret)
        with jax.named_scope("update"):
            if fin:
                new, seen, dpb, cpb = pulled
            else:
                recv = recv | pulled

    with jax.named_scope("update"):
        if not fin:
            recv = recv & topo.valid_w[None] & alive_w[None]
            new = recv & ~seen_w
            seen = seen_w | new
            # Receipts of already-seen messages — the degradation metric
            # link faults inflate (every redundant transfer still landed).
            # The fused path never materializes recv (its kernel emits
            # (new, seen') straight from VMEM), so it reports 0 there.
            redeliveries = _pair_total(msg_reduce(_popcount_pair(
                recv & seen_w)))
        else:
            redeliveries = jnp.float32(0)
        frontier = new
        if deferred_w is not None:
            with jax.named_scope("faults"):
                frontier = new | deferred_w
    with jax.named_scope("census"):
        # In this engine deliveries == frontier bits by construction (every
        # first receipt enters the next frontier); both keys are kept for
        # surface parity with sim.Simulator's metric dict.  Totals ride the
        # exact [hi, lo] int pair through the cross-shard reduction (a flat
        # int32 popcount wraps at the 10M x 256 scale) and become one
        # float32 only after it — bitwise-identical on every sharding.  On
        # the fused path both censuses come from the kernel's per-block
        # partial tiles instead of a 2W-plane re-read; _pair_total's
        # canonical normalization makes the two decompositions produce the
        # bit-identical float at any scale.
        deliveries = _pair_total(msg_reduce(
            _census_pair(dpb) if fin else _popcount_pair(new)))
        # Coverage over honest columns of LIVE HONEST peers (ok_w above) —
        # the edge engine's coverage_of (sim.py:33-43).  Each ok peer
        # contributes 32 bits to popcount(ok_w), hence the >> 5 peer count.
        # 32 bits per ok peer, so a flat int32 popcount wraps at exactly
        # 2^26 peers (the 64M probe: n_ok collapsed to 1, coverage 8.0).
        # The [hi, lo] pair rides the cross-shard reduce exactly; the final
        # float32 /32 is within +/-4 peers at 67M — invisible to any
        # coverage threshold.
        n_ok = jnp.maximum(
            _pair_total(reduce(_popcount_pair(ok_w))) / 32.0, 1.0)
        if sim.message_stagger > 0:
            # mean over the columns GENERATED so far (sim.py:coverage_of has
            # the rationale: a rumor that doesn't exist — not yet scheduled,
            # or lost to a pre-activation source death — can't count against
            # coverage).  Generated derives from the seen planes themselves:
            # OR over rows+lanes leaves one word per plane whose set bits
            # are the nonempty columns; cross-shard, the OR rides a psum of
            # the unpacked bits.
            or_w = jax.lax.reduce(seen, jnp.int32(0), jax.lax.bitwise_or,
                                  (1, 2))
            shifts = jnp.arange(WORD_BITS, dtype=jnp.int32)
            bits = (or_w[:, None] >> shifts) & 1
            hbits = (hmask[:, None] >> shifts) & 1
            gen = (reduce(bits) > 0) & (hbits > 0)
            n_cols = jnp.maximum(
                msg_only_reduce(jnp.sum(gen, dtype=jnp.int32)),
                1).astype(jnp.float32)
        else:
            n_cols = jnp.float32(sim._n_honest)
        coverage = _div_rn(_pair_total(msg_reduce(
            _census_pair(cpb) if fin else _popcount_pair(
                seen & ok_w[None] & hmask[:, None, None]))), n_ok * n_cols)
        live = _pair_total(reduce(_popcount_pair(
            alive_w & topo.valid_w))) / 32.0
    state = AlignedState(seen_w=seen, frontier_w=frontier, alive_b=alive_b,
                         byz_w=state.byz_w, strikes=strikes, key=key,
                         round=state.round + 1)
    metrics = {"coverage": coverage, "deliveries": deliveries,
               "frontier_size": deliveries,
               "live_peers": live, "evictions": n_evict,
               "redeliveries": redeliveries}
    if fr is None:
        return state, topo, metrics
    # Exchange DIAGNOSTICS, not simulation metrics: fr_words (the worst
    # shard's changed-word count — identical on either regime) and
    # fr_sparse (which regime this round actually ran).  They ride the
    # history so the A/B can reconstruct gathered bytes per round; the
    # six canonical metrics above stay bitwise-identical to every other
    # engine's.
    metrics["fr_sparse"] = fr_sparse
    metrics["fr_words"] = fr_words
    # fr_halving: which EXECUTION the sparse regime used this round
    # (1 = the recursive-halving butterfly, 0 = the table gather or a
    # dense round) — differs between frontier_algo runs by design, so
    # it sits OUTSIDE the parity surface, like fr_sparse sits outside
    # the six canonical metrics
    metrics["fr_halving"] = fr_halving
    if fr_sparse_ici is not None:
        # hierarchical meshes only: the ICI tier's regime this round
        # (fr_sparse is then the DCN tier's — same census and capacity
        # as the flat exchange, so that series stays bitwise flat)
        metrics["fr_sparse_ici"] = fr_sparse_ici
        metrics["fr_halving_ici"] = fr_halving_ici
    return state, topo, metrics, fr
