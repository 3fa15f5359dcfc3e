"""SIR epidemic model on the hardware-aligned overlay — the scale path
for BASELINE config 3 (the edges engine's SIRSimulator hits the same
~100k-peer gather wall as its gossip sibling; this runs the identical
compartment semantics at the aligned engine's 1M-10M-peer scale).

Semantics mirror models/sir.py:sir_round exactly:
  * infection pressure = number of transmitting (infected AND alive)
    in-neighbors, here one SUM-accumulated pallas pass over the aligned
    overlay's slots (ops/aligned_kernel.py:count_pass);
  * susceptible -> infected with p = 1 - (1-beta)^pressure;
  * infected -> recovered with probability gamma per round (dead peers
    included — recovery is biological, not network state, matching
    models/sir.py:29);
  * churn masks contacts the same way the gossip engines' does.

The reference has no epidemic model — its gossip IS the SI special case
(seen = infected, gamma = 0; peer.cpp:280-286) — so like the edges SIR
engine this consumes the ``sir_beta``/``sir_gamma`` config keys the
reference-parity config system exposes.

Every random draw (churn, infection, recovery) is keyed on the GLOBAL
row id via fold_in (aligned.row_uniform), so the sharded counterpart
(parallel/aligned_sharded.py:AlignedShardedSIRSimulator) is bitwise
equal to this engine — the same determinism contract as the gossip
pair.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from flax import struct

from p2p_gossipprotocol_tpu import autostatics as auto
from p2p_gossipprotocol_tpu.aligned import (AlignedTopology,
                                            Y_REUSE_LEAK_PREFETCH,
                                            churn_rows, row_uniform)
from p2p_gossipprotocol_tpu.liveness import ChurnConfig
from p2p_gossipprotocol_tpu.ops.aligned_kernel import (LANES, count_pass,
                                                       gossip_pass,
                                                       interpret_for,
                                                       stream_plan)


@struct.dataclass
class AlignedSIRState:
    """Compartments as two bool planes on the [rows, 128] peer grid
    (S = ~infected & ~recovered; the int8 0/1/2 compartment of
    state.py:SIRState unpacked into masks the VPU consumes directly)."""

    inf_b: jax.Array     # bool[R, 128]
    rec_b: jax.Array     # bool[R, 128]
    alive_b: jax.Array   # bool[R, 128]
    key: jax.Array
    round: jax.Array
    n_peers: int = struct.field(pytree_node=False)


def _count(mask_b: jax.Array, valid_b: jax.Array) -> jax.Array:
    return jnp.sum((mask_b & valid_b).astype(jnp.int32), dtype=jnp.int32)


@dataclass
class AlignedSIRSimulator:
    """Same surface as sim.SIRSimulator (step / run / SIRResult census,
    beta/gamma/n_seeds/churn knobs) on the aligned overlay."""

    topo: AlignedTopology
    beta: float = 0.3
    gamma: float = 0.1
    n_seeds: int = 1
    churn: ChurnConfig = None    # type: ignore[assignment]
    #: fuse the pressure count into the gossip kernel (round 10): the
    #: infectious-neighbor count rides gossip_pass's stream as its
    #: ``press`` output instead of a second full D-slot count_pass
    #: launch — on a block-perm overlay the host-side permute prep
    #: (``jnp.take(..., perm)``, the model's 3-plane term) disappears
    #: with it, one stream instead of two.  -1 auto (on for the
    #: compiled path when the overlay carries ``ytab``, off under
    #: interpret — the frontier_mode precedent), 0 = the solo
    #: count_pass (kept as the entry point for callers with no gossip
    #: stream to ride), 1 = force.  Bitwise-identical either way
    #: (tests/test_sir_fuse.py), so it is excluded from checkpoint
    #: fingerprints like fuse_update.
    sir_fuse: int = 0
    #: double-buffered DMA prefetch for the fused pass
    #: (aligned.AlignedSimulator.prefetch_depth semantics).
    prefetch_depth: int = 0
    seed: int = 0
    interpret: bool | None = None

    def __post_init__(self):
        if not 0.0 <= self.beta <= 1.0:
            raise ValueError("sir_beta must be in [0, 1]")
        if not 0.0 <= self.gamma <= 1.0:
            raise ValueError("sir_gamma must be in [0, 1]")
        if self.churn is None:
            self.churn = ChurnConfig()
        if self.interpret is None:
            self.interpret = interpret_for(jax.default_backend())
        if not self.interpret and (self.topo.rows < 8
                                   or self.topo.rowblk % 8):
            raise ValueError(
                f"aligned SIR on TPU needs >= 8 rows of {LANES} peers and "
                f"an 8-aligned row block (this overlay: {self.topo.rows} "
                f"rows, rowblk {self.topo.rowblk})")
        # the schedule statics resolve here, once, by their rules in
        # autostatics.py (the public field takes the concrete value)
        if self.sir_fuse not in (-1, 0, 1):
            raise ValueError("sir_fuse must be -1 (auto), 0, or 1")
        self.sir_fuse = int(auto.heuristic_sir_fuse(
            self.sir_fuse, self.interpret, self.topo.ytab is not None))
        self._fuse = bool(self.sir_fuse)
        if self.prefetch_depth not in (-1, 0, 2):
            raise ValueError("prefetch_depth must be -1 (auto), 0, or 2")
        self.prefetch_depth = auto.heuristic_prefetch(self.prefetch_depth,
                                                      self.interpret)
        self._prefetch = self.prefetch_depth
        self._scan_cache: dict = {}

    # ------------------------------------------------------------------
    @classmethod
    def from_config(cls, cfg, n_peers: int | None = None,
                    n_shards: int = 1,
                    clamps: list[str] | None = None
                    ) -> "AlignedSIRSimulator":
        """Build the scale-path SIR engine from a parsed NetworkConfig —
        shared by the CLI's ``--mode sir --engine aligned`` and the
        wrapper facade (mirrors AlignedSimulator.from_config; same
        resolve_overlay clamping contract)."""
        from p2p_gossipprotocol_tpu import faults as faults_lib
        from p2p_gossipprotocol_tpu.aligned import (build_aligned,
                                                    resolve_overlay)

        plan = faults_lib.plan_from_config(cfg)
        if plan is not None and plan.engine_active():
            raise ValueError(
                "fault plans apply to the gossip modes — the SIR model "
                "has no message-transfer path to fault (use churn_rate "
                "for its peer-level failures)")
        clamps = clamps if clamps is not None else []
        n, law, n_slots = resolve_overlay(cfg, n_peers=n_peers,
                                          clamps=clamps)
        topo = build_aligned(seed=cfg.prng_seed, n=n, n_slots=n_slots,
                             degree_law=law,
                             powerlaw_alpha=cfg.powerlaw_alpha,
                             n_shards=n_shards,
                             roll_groups=cfg.roll_groups or None,
                             # honored for overlay-family parity; with
                             # sir_fuse the block-perm table also lets
                             # the fused pass delete the permute prep
                             # (the flag plane rides ytab's index maps)
                             block_perm=cfg.block_perm > 0)
        if cfg.sir_fuse == 1 and topo.ytab is None:
            clamps.append(
                "sir_fuse 1 on a row-perm overlay -> fused count only "
                "(the permute prep stays host-side without block_perm; "
                "the pass itself still fuses, bitwise-identically)")
        return cls(topo=topo, beta=cfg.sir_beta, gamma=cfg.sir_gamma,
                   churn=ChurnConfig(rate=cfg.churn_rate),
                   sir_fuse=cfg.sir_fuse,
                   prefetch_depth=cfg.prefetch_depth,
                   seed=cfg.prng_seed)

    # ------------------------------------------------------------------
    def init_state(self) -> AlignedSIRState:
        """Seed infections spread evenly over the peer population (the
        deterministic analogue of init_sir_state's uniform choice)."""
        topo = self.topo
        n = topo.n_peers
        n_seeds = max(1, min(self.n_seeds, n))
        pos = (np.arange(n_seeds, dtype=np.int64)
               * max(n // n_seeds, 1)) % n
        inf = np.zeros(topo.rows * LANES, bool)
        inf[pos] = True
        return AlignedSIRState(
            inf_b=jnp.asarray(inf.reshape(topo.rows, LANES)),
            rec_b=jnp.zeros((topo.rows, LANES), bool),
            alive_b=topo.valid_w != 0,
            key=jax.random.PRNGKey(self.seed),
            round=jnp.int32(0),
            n_peers=n,
        )

    # ------------------------------------------------------------------
    def traffic_model(self) -> dict:
        """Per-term analytic HBM model of one SIR round (round 10) —
        the same accounting discipline as the gossip engine's
        (aligned.AlignedSimulator.traffic_model): kernel terms replay
        the grid's DMA-descriptor sequence (stream_plan) with the
        topology's calibrated partial-reuse leak (zero on the manual
        prefetch stream, by construction); XLA-side passes are charged
        one read+write per touched plane.

        Terms: ``prep`` — the host-side permute-gather of the flag
        plane (3 planes, the gossip model's per-pass prep rule); ZERO
        on the fused path over a block-perm overlay, where the
        permutation rides the ytab index maps — the deleted second
        stream.  ``count_pass`` — the D-slot kernel walk: the flag
        plane per effective y stream, colidx + gate once, the pressure
        plane out; the fused pass adds one plane (the OR accumulator
        riding along).  Pinned closed-form in
        tests/test_traffic_model.py."""
        topo = self.topo
        R, D, C = topo.rows, topo.n_slots, LANES
        blk = topo.rowblk
        T = R // blk
        plane = R * C * 4
        fused_o = self._fuse and topo.ytab is not None
        leak = (Y_REUSE_LEAK_PREFETCH
                if self._fuse and self._prefetch else topo.reuse_leak)
        plan = stream_plan(
            np.asarray(topo.rolls), T,
            ytab=np.asarray(topo.ytab) if fused_o else None)
        eff = plan["y"] + leak * (plan["y_naive"] - plan["y"])
        kern = (eff * blk * C * 4        # flag-plane streams
                + plan["tab"] * blk * C  # colidx (int8)
                + plan["row"] * blk * C  # gate (int8)
                + plane)                 # pressure out
        if self._fuse:
            kern += plane                # the OR accumulator rides along
        terms = {"prep": 0 if fused_o else 3 * plane,
                 "count_pass": int(kern)}
        terms["total"] = sum(terms.values())
        return terms

    def hbm_bytes_per_round(self) -> int:
        """Total of :meth:`traffic_model` (bench/roofline parity with
        the gossip engine)."""
        return self.traffic_model()["total"]

    # ------------------------------------------------------------------
    def step(self, state: AlignedSIRState,
             topo: AlignedTopology | None = None
             ) -> tuple[AlignedSIRState, dict]:
        topo = self.topo if topo is None else topo
        grows = jnp.arange(topo.rows, dtype=jnp.int32)
        return aligned_sir_round(self, state, topo, grows=grows,
                                 t_off=jnp.int32(0),
                                 gather=lambda x: x, reduce=lambda x: x)

    # ------------------------------------------------------------------
    def run(self, rounds: int, state: AlignedSIRState | None = None,
            warmup: bool = False):
        """Fixed-round scan; returns the shared :class:`sim.SIRResult`.

        ``warmup=True`` executes the compiled program once untimed first
        so ``wall_s`` excludes compile + one-time program upload — the
        same benchmark-parity flag as every other run() on the scale
        path (round-2 advisor finding)."""
        import time as _time

        from p2p_gossipprotocol_tpu.sim import SIRResult

        state = self.init_state() if state is None else state
        if rounds not in self._scan_cache:
            # topo is a traced ARGUMENT, never a closure capture: a
            # captured topology is baked into the HLO as a constant,
            # and at 32M+ peers the serialized lane table alone blew
            # the remote-compile transport's body limit (HTTP 413) —
            # the gossip engine's run() passes it for the same reason
            def scanned(st, tp):
                def body(carry, _):
                    s, metrics = self.step(carry, tp)
                    return s, metrics
                return jax.lax.scan(body, st, None, length=rounds)
            self._scan_cache[rounds] = jax.jit(scanned)
        if warmup:
            w_state, _ = self._scan_cache[rounds](state, self.topo)
            int(jax.device_get(w_state.round))
        t0 = _time.perf_counter()
        state, ys = self._scan_cache[rounds](state, self.topo)
        int(jax.device_get(state.round))   # forces completion
        wall = _time.perf_counter() - t0
        return SIRResult.from_metrics(state, self.topo, ys, wall)


def aligned_sir_round(sim: AlignedSIRSimulator, state: AlignedSIRState,
                      topo: AlignedTopology, *, grows: jax.Array,
                      t_off: jax.Array, gather, reduce
                      ) -> tuple[AlignedSIRState, dict]:
    """THE SIR round, shared by the single-chip engine and
    AlignedShardedSIRSimulator — same grows/t_off/gather/reduce seams as
    aligned.aligned_round (see its docstring)."""
    valid_b = topo.valid_w != 0
    key, k_churn, k_u = jax.random.split(state.key, 3)

    alive_b = state.alive_b
    if sim.churn.rate > 0.0 or sim.churn.revive > 0.0:
        alive_b = churn_rows(k_churn, grows, alive_b, valid_b,
                             state.round, sim.churn)

    transmitting = jnp.where(state.inf_b & alive_b, jnp.int32(-1),
                             jnp.int32(0))
    if sim._fuse:
        # Fused pressure (round 10): ONE gossip_pass streams the flag
        # plane and emits the infectious-neighbor count as its press
        # output — on a block-perm overlay the permutation rides the
        # ytab index maps, so the host-side permute prep below does not
        # exist at all (one stream instead of two); bitwise-equal to
        # the solo count_pass (tests/test_sir_fuse.py).
        fused_o = topo.ytab is not None
        if fused_o:
            t_local = state.inf_b.shape[0] // topo.rowblk
            ytab_local = jax.lax.dynamic_slice(
                topo.ytab, (jnp.int32(0), jnp.int32(t_off)),
                (topo.ytab.shape[0], t_local))
            y = gather(transmitting)
        else:
            y = jnp.take(gather(transmitting), topo.perm, axis=0)
        _, pressure = gossip_pass(
            y[None], topo.colidx, topo.deg, topo.rolls + t_off,
            topo.subrolls, press=True,
            ytab=ytab_local if fused_o else None,
            prefetch_depth=sim._prefetch,
            rowblk=topo.rowblk, interpret=sim.interpret)
    else:
        y = jnp.take(gather(transmitting), topo.perm, axis=0)
        pressure = count_pass(y, topo.colidx, topo.deg,
                              topo.rolls + t_off,
                              topo.subrolls, rowblk=topo.rowblk,
                              interpret=sim.interpret)
    p_infect = 1.0 - jnp.power(jnp.float32(1.0 - sim.beta),
                               pressure.astype(jnp.float32))
    u = row_uniform(k_u, grows, (2, LANES))
    u_inf, u_rec = u[:, 0], u[:, 1]
    sus_b = ~state.inf_b & ~state.rec_b & valid_b
    new_inf = sus_b & alive_b & (u_inf < p_infect)
    recovers = state.inf_b & (u_rec < sim.gamma)
    inf_b = (state.inf_b | new_inf) & ~recovers
    rec_b = state.rec_b | recovers

    metrics = {
        "susceptible": reduce(_count(~inf_b & ~rec_b, valid_b)),
        "infected": reduce(_count(inf_b, valid_b)),
        "recovered": reduce(_count(rec_b, valid_b)),
        "new_infections": reduce(_count(new_inf, valid_b)),
        "live_peers": reduce(_count(alive_b, valid_b)),
    }
    state = AlignedSIRState(inf_b=inf_b, rec_b=rec_b, alive_b=alive_b,
                            key=key, round=state.round + 1,
                            n_peers=state.n_peers)
    return state, metrics
