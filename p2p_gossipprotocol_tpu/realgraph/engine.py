"""RealGraphSimulator: the edges-engine round with a packed-SpMV wire.

THE PARITY CONTRACT (tests/test_realgraph.py pins it bitwise): a
realgraph round IS an edges-engine round.  :class:`RealGraphSimulator`
subclasses :class:`sim.Simulator` and changes exactly one thing — the
transport's ``deliver`` — so every key split, fault gate, churn draw,
strike/rewire decision, byzantine injection, stagger tick, and metric
reduction is inherited VERBATIM, in the same order, from the same
code.  The swapped delivery is a boolean OR-reduction, and boolean OR
is order-independent, so the degree-bucketed gather computes the SAME
``recv`` bits ``ops.propagate.edge_or_scatter`` computes from the same
inputs — parity holds by construction, per (seed, round, edge), not by
tolerance.  Everything the contract surface promises rides free:
faults (per-link drop hashed on edge id), crash/churn as vertex masks,
elastic canonical checkpoints (the ``edges`` checkpoint family — a
realgraph checkpoint resumes under the edges engine bit-for-bit, and
vice versa), telemetry spans, and the serving wire.

The gather path's one obligation is STATIC ``dst``: the packed tables
pre-resolve each vertex's in-edge ids, so they stay valid only while
``strike_and_rewire`` cannot rewrite ``dst`` (it mutates ``dst`` only
when ``rewire=True`` AND peers can die — churn or scheduled
crash/recovery).  ``realgraph_scatter`` resolves that choice by its
rule in autostatics.py: auto picks the gather whenever ``dst`` is
static and falls back to the inherited edge scatter otherwise (loudly,
through the clamp ledger, when a gather was forced on a dst-mutating
build).  Both paths are bitwise-identical.

Frontier-compaction regime + traffic model: the PR 5/14/16 frontier
machinery (``aligned.frontier_capacity`` / ``halving_steps`` /
``project_exchange``) prices the changed-vertex delta exchange the
sharded seam will move — :meth:`frontier_regime_series` reconstructs
the sparse/dense regime (with the aligned plane's hysteresis) from the
``frontier_size`` metric trajectory, which is engine-identical by the
parity contract, and :meth:`traffic_model` pins the per-round byte
terms closed-form.  Single-device note: the pack tables ride the jit
as closure constants; the sharded engine must pass them as arguments
(the aligned-SIR 32M remote-compile body-limit precedent) — that seam
is :func:`pack.shard_partition`'s documentation, not this round's
code.
"""

from __future__ import annotations

import zlib
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from p2p_gossipprotocol_tpu import autostatics as auto
from p2p_gossipprotocol_tpu import faults as faults_lib
from p2p_gossipprotocol_tpu import graph as graph_lib
from p2p_gossipprotocol_tpu.fleet.engine import FleetBucket
from p2p_gossipprotocol_tpu.liveness import ChurnConfig
from p2p_gossipprotocol_tpu.realgraph import ingest as ingest_lib
from p2p_gossipprotocol_tpu.realgraph.pack import (PACK_WIDTH_DEFAULT,
                                                   pack_signature,
                                                   pack_topology,
                                                   shard_partition)
from p2p_gossipprotocol_tpu.sim import Simulator
from p2p_gossipprotocol_tpu.transport.jax_transport import JaxTransport

#: the edges-family metric dtypes, exactly as the solo scan emits them
#: (sim.Simulator.step: coverage is the one float; every count is an
#: int32 sum) — the realgraph bucket's unpacked histories keep these so
#: a fleet/serve result is indistinguishable from a solo one
RG_METRIC_DTYPES = {"coverage": np.float32, "deliveries": np.int32,
                    "frontier_size": np.int32, "live_peers": np.int32,
                    "evictions": np.int32, "redeliveries": np.int32}

#: GossipState array leaves, in persist order (serve salvage payloads)
RG_STATE_LEAVES = ("seen", "frontier", "alive", "byzantine",
                   "edge_strikes", "key", "round")

#: Topology array leaves (graph.Topology — the edges family's tables)
RG_TOPO_LEAVES = ("src", "dst", "edge_mask", "row_ptr")


def host_graph_fingerprint(topo) -> str:
    """A synthetic overlay's identity (file-loaded graphs use the
    artifact manifest's CRC fingerprint instead): CRC32 over the
    canonical structural arrays, cheap enough to run at build time."""
    crc = 0
    for name in RG_TOPO_LEAVES:
        a = np.ascontiguousarray(np.asarray(getattr(topo, name)))
        crc = zlib.crc32(a.tobytes(), crc)
    return f"host-{topo.n_peers}-{crc:08x}"


def dst_is_static(rewire: bool, churn: ChurnConfig,
                  faults) -> bool:
    """True iff no round can rewrite ``topo.dst``:
    ``strike_and_rewire`` only rewires when ``rewire`` is on AND a dead
    peer can exist — continuous churn (rate/revive) or the fault
    plane's scheduled crash/recovery.  ``edge_mask`` mutations
    (per-link strikes with ``rewire=False``) are fine either way: the
    gather reads the mask live through ``gate[eid]``."""
    if not rewire:
        return True
    churn_active = (churn.rate > 0.0 or churn.revive > 0.0)
    fault_deaths = faults is not None and (faults.crash
                                           or faults.recover)
    return not (churn_active or fault_deaths)


class PackedTransport(JaxTransport):
    """The delivery SpMV, degree-bucketed: per block, gather each
    row's in-edge gates and source frontiers, OR across the row, and
    scatter one bit-row per destination vertex — O(rows x width) work
    against the edge scatter's O(edge_capacity) scatter traffic.

    Bitwise contract: ``edge_or_scatter`` ORs ``active[src] & gate``
    into ``out[dst]`` over every capacity lane (padding gated False);
    each packed row ORs exactly its vertex's valid in-edge subset of
    those terms and hub rows accumulate under the same OR — identical
    ``recv``, element for element.  ``fetch``/``push_to`` (the pull
    family's wires) are inherited untouched: they are already gathers.

    The message axis travels bit-packed through the block gathers
    (``packbits`` once per round, O(n x W)), so each in-edge moves
    ceil(W/8) bytes instead of W bool bytes and the row OR is a
    log2(width) halving over uint8 words — byte-level OR of exact bit
    patterns, so the unpacked result is the bool computation bit for
    bit (the round-19 A/B's 1M-edge CPU row measured the packed gather
    ~2x the bool one).

    With ``use_gather=False`` the transport IS its base class — the
    scatter fallback for dst-mutating builds costs zero new code."""

    def __init__(self, packed, use_gather: bool = True):
        self.packed = packed
        self.use_gather = use_gather

    def deliver(self, sending, topo, edge_gate=None):
        if not self.use_gather:
            return super().deliver(sending, topo, edge_gate)
        gate = (topo.edge_mask if edge_gate is None
                else topo.edge_mask & edge_gate)
        words = jnp.packbits(sending, axis=1)      # (n, ceil(W/8))
        out = jnp.zeros_like(sending)
        for b in self.packed.blocks:
            g = gate[b.eid] & b.valid
            rows = jnp.where(g[..., None], words[b.src], jnp.uint8(0))
            w = b.width                   # pow2: OR-halve to one row
            while w > 1:
                w //= 2
                rows = rows[:, :w] | rows[:, w:2 * w]
            hit = jnp.unpackbits(rows[:, 0],
                                 axis=-1)[:, :sending.shape[1]]
            out = out.at[b.vtx].max(hit.astype(bool), mode="drop")
        return out


# ---------------------------------------------------------------------
# The batched bucket (fleet sweeps + the serving plane).

class RealGraphBucket(FleetBucket):
    """A realgraph scenario batch: the FleetBucket protocol verbatim —
    signature check, convergence masking, resident slots, admission
    scatter, trace-count ledger — with the per-kind hooks (the round,
    the topology leaves, the metric dtypes, the salvage payload)
    swapped for the edges family's.  The bucket batches the EXACT solo
    simulators, so the PR 4 bitwise contract carries over unchanged:
    slot ``i``'s unpacked result is ``sims[i].run(...)`` bit for bit.

    The per-slot ``seed`` lane is carried but unread (the edges-family
    PRNG chain rides ``state.key``; aligned needs the lane for its
    liveness hash) — keeping it keeps the serving plane's
    admit/extract payload shape identical across bucket kinds."""

    metric_dtypes = RG_METRIC_DTYPES
    metric_keys = tuple(RG_METRIC_DTYPES)
    persist_kind = "realgraph"

    # -- per-kind hooks -------------------------------------------------
    def _srcs_row_of(self, s):
        return s._message_plan()

    def _one_round(self):
        tmpl = self.template

        def one(state, topo, seed, srcs):
            del seed               # protocol lane; see class docstring
            return tmpl.step(state, topo,
                             msg_srcs=(srcs if tmpl.message_stagger > 0
                                       else None))
        return one

    def unstack_topo(self, btopo, i: int, solo_topo):
        del solo_topo              # statics ride the pytree already
        return jax.tree.map(lambda x: x[i], btopo)

    # -- stacking -------------------------------------------------------
    def stack_topos(self):
        return jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[s.topo for s in self.sims])

    def init(self):
        bstate = jax.tree.map(lambda *xs: jnp.stack(xs),
                              *[s.init_state() for s in self.sims])
        return bstate, self.stack_topos()

    def init_idle(self):
        st = self.template.init_state()
        bstate = jax.tree.map(lambda x: jnp.stack([x] * self.size), st)
        btopo = jax.tree.map(lambda x: jnp.stack([x] * self.size),
                             self.template.topo)
        return bstate, btopo, jnp.ones(self.size, bool)

    # -- resident-slot admission ---------------------------------------
    def admit_args(self, sim):
        state = sim.init_state()
        leaves = {k: getattr(sim.topo, k) for k in RG_TOPO_LEAVES}
        seed = jnp.int32(sim.seed)
        if self.template.message_stagger > 0:
            srcs_row = sim._message_plan()
        else:
            srcs_row = jnp.zeros((1,), jnp.int32)
        return state, leaves, None, seed, srcs_row

    def _admit_fn(self):
        if "admit" in self._chunk_cache:
            return self._chunk_cache["admit"]

        def admit(bstate, btopo, done, seeds, srcs, slot,
                  nstate, nleaves, nytab, seed, srcs_row):
            del nytab              # payload-shape compatibility only
            bstate = jax.tree.map(lambda b, n: b.at[slot].set(n),
                                  bstate, nstate)
            btopo = btopo.replace(
                **{k: getattr(btopo, k).at[slot].set(nleaves[k])
                   for k in RG_TOPO_LEAVES})
            done = done.at[slot].set(False)
            seeds = seeds.at[slot].set(seed)
            srcs = srcs.at[slot].set(srcs_row)
            return bstate, btopo, done, seeds, srcs

        donate = (jax.default_backend() not in ("cpu",))
        fn = jax.jit(admit, donate_argnums=(0, 1, 2, 3, 4) if donate
                     else ())
        self._chunk_cache["admit"] = fn
        return fn

    def extract_slot_payload(self, bstate, btopo, seeds, srcs,
                             slot: int):
        state = jax.tree.map(lambda x: x[slot], bstate)
        leaves = {k: getattr(btopo, k)[slot] for k in RG_TOPO_LEAVES}
        return state, leaves, None, seeds[slot], srcs[slot]

    # -- serve salvage payloads ----------------------------------------
    def persist_arrays(self, bstate, btopo) -> dict:
        out = {f"state/{k}": getattr(bstate, k)
               for k in RG_STATE_LEAVES}
        # the two topology leaves a round can mutate (rewire writes
        # dst; strikes/faults write edge_mask) — src/row_ptr are
        # structural and re-derive from the template at resume
        out["topo/dst"] = btopo.dst
        out["topo/edge_mask"] = btopo.edge_mask
        return out

    def restore_arrays(self, btopo, payload):
        from p2p_gossipprotocol_tpu.state import GossipState

        state = GossipState(**{k: jnp.asarray(payload[f"state/{k}"])
                               for k in RG_STATE_LEAVES})
        btopo = btopo.replace(dst=jnp.asarray(payload["topo/dst"]),
                              edge_mask=jnp.asarray(
                                  payload["topo/edge_mask"]))
        return state, btopo


# ---------------------------------------------------------------------
# The simulator.

@dataclass
class RealGraphSimulator(Simulator):
    """The edges engine over an ingested real graph, delivered by the
    packed SpMV.  See the module docstring for the parity contract;
    every inherited knob (mode/fanout/churn/byzantine/stagger/faults)
    means exactly what it means on :class:`sim.Simulator`.

    ``pack_width`` / ``scatter`` are the ``realgraph_pack_width`` /
    ``realgraph_scatter`` config statics (-1 = auto by their rules in
    autostatics.py; both bitwise-safe).  ``graph_fp`` is
    the graph's array identity — the artifact manifest fingerprint for
    file-loaded graphs, a host CRC otherwise — and enters the bucket
    signature: slots sharing a bucket share the gather tables, so they
    MUST share the graph, not just its shapes."""

    pack_width: int = -1
    scatter: int = -1
    graph_file: str = ""
    graph_fp: str = ""

    def __post_init__(self):
        self._clamps: list[str] = []
        if not self.graph_fp:
            self.graph_fp = host_graph_fingerprint(self.topo)
        dst_static = dst_is_static(self.rewire, self.churn, self.faults)
        self._dst_static = dst_static
        width = auto.heuristic_realgraph_pack_width(self.pack_width)
        if not (isinstance(width, int) and 1 <= width <= 4096
                and not (width & (width - 1))):
            raise ValueError(
                f"realgraph_pack_width must be a power of two in "
                f"[1, 4096], got {width}")
        if self.scatter not in (-1, 0, 1):
            raise ValueError(
                f"realgraph_scatter must be -1 (auto), 0 (gather) or "
                f"1 (scatter), got {self.scatter}")
        scat = auto.heuristic_realgraph_scatter(self.scatter, dst_static)
        if scat == 0 and not dst_static:
            # an explicit gather on a dst-mutating build: the tables
            # would go stale on the first rewire — degrade loudly
            scat = 1
            self._clamps.append(
                "realgraph_scatter 0->1 (rewire with churn/crash "
                "mutates dst, which staleness the packed gather tables "
                "cannot follow — edge-scatter path forced)")
        self._scatter = int(scat)
        self._pack_width = int(width)
        self._pack = pack_topology(self.topo, width_cap=width)
        if self.transport is None:
            self.transport = PackedTransport(self._pack,
                                             use_gather=(scat == 0))
        self._bucket_class = RealGraphBucket
        super().__post_init__()

    # -- signatures -----------------------------------------------------
    def _bucket_signature(self) -> tuple:
        """The fleet/serve bucket signature (packer.bucket_signature
        dispatches here): everything static in the compiled round —
        graph identity included, because the gather tables are shared
        closure constants across a bucket's slots."""
        return ("realgraph", self.graph_fp, self.topo.n_peers,
                self.topo.edge_capacity, pack_signature(self._pack),
                self._scatter, self.n_msgs, self._n_honest, self.mode,
                self.fanout, self.max_strikes, self.rewire,
                self.message_stagger,
                (self.churn.rate, self.churn.revive,
                 self.churn.kill_round),
                self.faults)

    # -- frontier regime + traffic -------------------------------------
    def frontier_regime_series(self, frontier_size, n_shards: int = 1,
                               threshold: float = -1.0,
                               algo: int = -1) -> dict:
        """The sparse-exchange regime the frontier compaction would run
        per round, reconstructed from the ``frontier_size`` metric
        trajectory (engine-identical by the parity contract, so the
        regime series is too — the regime-parity test is exact, not
        approximate).  Per round the changed-vertex delta table holds
        at most ``frontier_size`` vertex ids; the per-shard worst case
        is modeled conservatively as ``min(shard_width, F)`` (the exact
        per-shard census is the sharded seam's job).  The sparse/dense
        hysteresis is the aligned plane's: enter sparse below HALF the
        capacity, stay sparse up to it.  ``halving`` reports
        ``aligned.halving_steps`` for the shard count — the PR 14/16
        recursive-halving merge depth, or None off the power-of-two
        grid."""
        from p2p_gossipprotocol_tpu.aligned import (frontier_capacity,
                                                    halving_steps)

        thr = auto.heuristic_frontier_threshold(threshold)
        f = np.asarray(frontier_size, np.int64)
        shard_width = -(-self.topo.n_peers // max(1, n_shards))
        cap = frontier_capacity(thr, shard_width)
        worst = np.minimum(shard_width, f)
        sparse = np.zeros(f.shape[0], bool)
        prev = False
        for i, w in enumerate(worst.tolist()):
            prev = (w <= cap) if prev else (w <= cap // 2)
            sparse[i] = prev
        use_halving = auto.heuristic_on(algo, False)
        return {
            "capacity": int(cap),
            "threshold": float(thr),
            "shard_width": int(shard_width),
            "worst_delta": worst,
            "sparse": sparse,
            "sparse_rounds": int(sparse.sum()),
            "halving": (halving_steps(n_shards) if use_halving
                        else None),
        }

    def traffic_model(self, n_shards: int = 1,
                      frontier_fill: float = 1.0) -> dict:
        """Closed-form per-round byte terms (the telemetry roofline's
        model side; every term is arithmetic over statics, zero device
        work).  Local terms price the delivery SpMV on the resolved
        path; with ``n_shards > 1`` the frontier delta exchange is
        priced through ``aligned.project_exchange`` — the PR 5/14
        machinery's own closed form, reused verbatim so the two
        engines' exchange economics stay one model."""
        from p2p_gossipprotocol_tpu.aligned import project_exchange

        n = int(self.topo.n_peers)
        e_cap = int(self.topo.edge_capacity)
        n_msgs = int(self.n_msgs)
        out: dict = {"path": "gather" if self._scatter == 0
                     else "scatter"}
        if self._scatter == 0:
            slots = sum(b.eid.shape[0] * b.width
                        for b in self._pack.blocks)
            out["table_bytes"] = slots * 8          # eid + src int32
            out["valid_bytes"] = slots              # bool mask
            out["gate_bytes"] = e_cap               # bool gate read
            out["payload_bytes"] = slots * n_msgs   # gathered frontier
            out["scatter_bytes"] = 2 * n * n_msgs   # out read+write
        else:
            out["table_bytes"] = e_cap * 8          # src + dst int32
            out["valid_bytes"] = 0
            out["gate_bytes"] = e_cap
            out["payload_bytes"] = e_cap * n_msgs
            out["scatter_bytes"] = 2 * n * n_msgs
        out["local_total_bytes"] = (out["table_bytes"]
                                    + out["valid_bytes"]
                                    + out["gate_bytes"]
                                    + out["payload_bytes"]
                                    + out["scatter_bytes"])
        if n_shards > 1:
            out["exchange"] = project_exchange(
                n, n_msgs, n_shards, frontier_fill=frontier_fill)
        return out

    def shard_bounds(self, n_shards: int) -> np.ndarray:
        """The 1-D in-degree-balanced vertex partition for ``n_shards``
        chips (pack.shard_partition over this graph's structural
        in-degrees) — the sharded seam's placement."""
        e = self._pack.n_edges
        dst = np.asarray(self.topo.dst)[:e]
        deg_in = np.bincount(dst, minlength=self.topo.n_peers)
        return shard_partition(deg_in, n_shards)

    # -- construction ---------------------------------------------------
    @classmethod
    def from_config(cls, cfg, n_peers: int | None = None,
                    clamps: list | None = None) -> "RealGraphSimulator":
        """Build from a :class:`NetworkConfig`: ``graph_file`` (an
        artifact directory or a raw edge list, ingested+cached) fixes
        the topology AND the peer count; without it the synthetic
        ``graph=`` overlay family builds exactly as the edges engine
        would.  Mirrors ``Simulator.from_config`` knob for knob."""
        graph_fp = ""
        if getattr(cfg, "graph_file", ""):
            topo, graph_fp, _manifest = ingest_lib.load_graph_file(
                cfg.graph_file, fmt=cfg.realgraph_format)
            if n_peers is not None and int(n_peers) != topo.n_peers:
                raise ValueError(
                    f"graph_file {cfg.graph_file!r} fixes "
                    f"n_peers={topo.n_peers}; a conflicting n_peers="
                    f"{n_peers} was requested (drop --n-peers or "
                    "re-ingest the graph)")
        else:
            topo = graph_lib.from_config(cfg, n_peers=n_peers)
        n_msgs = cfg.n_messages or cfg.max_message_count
        plan = faults_lib.plan_from_config(cfg)
        byz = max(cfg.byzantine_fraction,
                  plan.byzantine if plan else 0.0)
        n_junk = 0
        if byz > 0.0:
            n_junk = max(1, n_msgs // 4)
        churn = (ChurnConfig(rate=cfg.churn_rate) if cfg.churn_rate
                 else ChurnConfig())
        sim = cls(
            topo=topo,
            n_msgs=n_msgs + n_junk,
            mode=cfg.mode,
            fanout=cfg.fanout,
            churn=churn,
            byzantine_fraction=byz,
            n_honest_msgs=n_msgs if n_junk else None,
            max_strikes=cfg.max_missed_pings,
            message_stagger=cfg.message_stagger,
            seed=cfg.prng_seed,
            faults=plan if plan and plan.engine_active() else None,
            pack_width=cfg.realgraph_pack_width,
            scatter=cfg.realgraph_scatter,
            graph_file=getattr(cfg, "graph_file", ""),
            graph_fp=graph_fp,
        )
        if clamps is not None:
            clamps.extend(sim._clamps)
        return sim


def sir_from_config(cfg, n_peers: int | None = None):
    """``mode=sir`` + ``engine=realgraph``: the SIR epidemic model over
    the INGESTED topology — the same :class:`sim.SIRSimulator` the
    edges engine runs, handed the real graph instead of a synthetic
    overlay (models/sir.py's hooks consume any Topology)."""
    from p2p_gossipprotocol_tpu.sim import SIRSimulator

    if not getattr(cfg, "graph_file", ""):
        return SIRSimulator.from_config(cfg, n_peers=n_peers)
    plan = faults_lib.plan_from_config(cfg)
    if plan is not None and plan.engine_active():
        raise ValueError(
            "fault plans apply to the gossip modes — the SIR model "
            "has no message-transfer path to fault (use churn_rate "
            "for its peer-level failures)")
    topo, _fp, _manifest = ingest_lib.load_graph_file(
        cfg.graph_file, fmt=cfg.realgraph_format)
    if n_peers is not None and int(n_peers) != topo.n_peers:
        raise ValueError(
            f"graph_file {cfg.graph_file!r} fixes "
            f"n_peers={topo.n_peers}; a conflicting n_peers={n_peers} "
            "was requested")
    return SIRSimulator(
        topo=topo,
        beta=cfg.sir_beta,
        gamma=cfg.sir_gamma,
        churn=(ChurnConfig(rate=cfg.churn_rate) if cfg.churn_rate
               else ChurnConfig()),
        seed=cfg.prng_seed,
    )
