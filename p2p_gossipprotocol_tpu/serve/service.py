"""The resident serving loop: hot buckets, slot-swap admission, drain.

:class:`GossipService` is the ``wrapper.Peer``-style facade —
``submit()/result()/drain()`` — over a background serving thread that
keeps :class:`ServeBucket`\\ s resident on-device and admits/retires
scenarios at chunk (round) boundaries:

* **admission** routes on ``fleet/packer.py``'s compiled-program
  signature: a matching resident bucket with a free slot takes the
  scenario as a pure array scatter (``FleetBucket.admit_into`` — the
  one chunk program is never retraced, asserted by
  ``FleetBucket.trace_count``); a signature miss opens a new bucket (up
  to ``serve_max_buckets``, evicting an all-idle one first);
* **execution** runs each live bucket one ``serve_chunk``-round
  compiled chunk at a time.  Admission payloads for still-queued
  requests are staged (host→HBM transfers dispatched) while the chunk
  executes, so the next admission scatter overlaps the current chunk's
  result readback — the double-buffered staging the batch-offline
  driver never needed;
* **retirement** reuses convergence masking: the chunk's on-device
  ``done`` mask freezes a converged scenario at its exact round, the
  loop truncates its history there and frees the slot.  A scenario
  that exhausts ``serve_rounds`` retires unconverged (and is marked
  done so its slot frees) — never silently served forever.

The hard contract (tests/test_serve.py): every served scenario —
including one admitted mid-flight into a slot another scenario retired
from — is **bitwise-identical to its solo AlignedSimulator run**.  It
holds because admission only ever writes the scenario's own slot of the
batch (its exact solo init state, overlay, seed, and source table), the
vmapped round is per-slot independent (the PR 4 fleet contract), and
retirement freezes before reuse.

Drain/salvage (the preemption contract, extended to a server): SIGTERM
mid-serve persists every resident bucket through the elastic-checkpoint
discipline (CRC'd npz + atomic manifest, ``utils/checkpoint.py``'s
torn-write rules) plus the queue itself (request overrides + ids), the
CLI exits 75, and a restarted ``--serve --resume`` re-hydrates the
queue and completes every previously admitted scenario bitwise.
"""

from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp

from p2p_gossipprotocol_tpu import telemetry
from p2p_gossipprotocol_tpu.autostatics import heuristic_serve_chunk
from p2p_gossipprotocol_tpu.fleet.engine import (METRIC_KEYS, FleetBucket,
                                                 bucket_class_for)
from p2p_gossipprotocol_tpu.serve.scheduler import (DONE, FAILED, QUEUED,
                                                    RUNNING, Request,
                                                    Scheduler, ServeReject,
                                                    ServeShed,
                                                    resolve_request)

#: serve manifest schema (the sweep manifest's sibling; fingerprint /
#: atomic-write / CRC machinery shared from utils.checkpoint)
SERVE_SCHEMA = 1


@dataclass
class Occupant:
    """One live slot: the request it serves and its per-slot ledger.
    ``rounds`` counts rounds since ADMISSION (the scenario's own round
    counter — slot time, not bucket time), ``converged`` is its
    1-indexed convergence round or -1, ``hist`` accumulates the slot's
    column of each chunk's metric block."""

    req: Request
    rounds: int = 0
    converged: int = -1
    hist: dict = field(default_factory=lambda: {
        k: [] for k in METRIC_KEYS})

    @property
    def spec(self):
        return self.req.spec


class ServeBucket:
    """A resident, slot-swappable bucket: one compiled chunk program
    PER WIDTH serving a rotating population of signature-identical
    scenarios.  Round 17 made the width dynamic: :meth:`resize` swaps
    the batch onto a different power-of-two slot count, migrating live
    occupants bit-for-bit through the admit scatter; per-width
    :class:`FleetBucket`\\ s are cached, so returning to a width the
    bucket has served before compiles nothing."""

    _next_uid = 0

    def __init__(self, template_spec, slots: int, chunk: int,
                 target: float):
        from p2p_gossipprotocol_tpu.fleet.packer import bucket_signature

        #: stable identity for the autoscaler's streak/cooldown state
        #: and the ``autoscale`` ledger events
        self.uid = ServeBucket._next_uid
        ServeBucket._next_uid += 1
        self.template_spec = template_spec
        self.slots = slots
        self.chunk = chunk
        self.target = target
        self.signature = bucket_signature(template_spec.sim)
        #: one FleetBucket (and thus one chunk-program compile cache)
        #: per width this bucket has ever run at — a shrink-then-grow
        #: cycle re-uses the old program instead of retracing
        self._fleets: dict[int, FleetBucket] = {}
        self.fleet = self._fleet_for(slots)
        self.state, self.topo, self.done = self.fleet.init_idle()
        self.seeds = self.fleet._seeds
        self.srcs = self.fleet._srcs
        self.occupants: list[Occupant | None] = [None] * slots
        #: (width, chunk-length) pairs dispatched — the EXPECTED trace
        #: count: each pair compiles exactly once, nothing else may
        #: (the zero-admission-recompile ledger, now resize-aware)
        self._programs: set = set()
        #: chunk retraces observed during admit/resize scatters — the
        #: direct spelling of the PR 9 acceptance gate, asserted == 0
        self.admission_recompiles = 0
        self.resizes = 0

    def _fleet_for(self, slots: int) -> FleetBucket:
        if slots not in self._fleets:
            # engine-aware: realgraph sims carry their own bucket class
            # (fleet.engine.bucket_class_for) — the serving machinery
            # reads everything kind-specific off the bucket's hooks
            self._fleets[slots] = bucket_class_for(
                self.template_spec.sim).for_serving(
                    self.template_spec.sim, slots)
        return self._fleets[slots]

    # ------------------------------------------------------------------
    def park(self) -> None:
        """Move an idle bucket to the service's parking lot state
        (round 17): compiled per-width programs AND the inert batch
        arrays are kept — the PR 13 plane recompiled a bucket's chunk
        program on every signature re-miss, which under
        signature-diverse traffic is a compile per eviction cycle, the
        hidden half of the ~4 QPS knee.  Keeping the arrays is bitwise
        safe BY the retirement contract: every slot of an idle bucket
        is done-frozen (its stale world computes-and-discards under
        the convergence mask, and only occupied slots' metrics are
        ever read), so the next admission scatters a fresh world over
        it exactly as it would over the init_idle template.  Memory is
        bounded by the lot's LRU cap — a dropped bucket frees
        everything.  Only an idle bucket may park."""
        if self.live():
            raise ValueError("cannot park a bucket with live occupants")
        self.occupants = [None] * self.slots

    def unpark(self) -> None:
        """Re-arm a parked bucket: the resident batch is already
        all-done-inert and the programs are warm — reopening a
        signature family costs NOTHING but the admit scatter, never a
        retrace (asserted by the (width, chunk) program ledger)."""
        assert not self.live()

    # -- trace accounting ----------------------------------------------
    def trace_total(self) -> int:
        """Chunk retraces across every width this bucket has run at."""
        return sum(f.trace_count for f in self._fleets.values())

    def expected_traces(self) -> int:
        """What :meth:`trace_total` must equal on a healthy bucket:
        one compile per distinct (width, chunk-length) program ever
        dispatched.  Anything above is a real recompile — an admission
        or migration that changed the traced program."""
        return len(self._programs)

    # ------------------------------------------------------------------
    def free_slots(self) -> list[int]:
        return [i for i, o in enumerate(self.occupants) if o is None]

    def live(self) -> bool:
        return any(o is not None for o in self.occupants)

    def live_count(self) -> int:
        return sum(o is not None for o in self.occupants)

    # ------------------------------------------------------------------
    def resize(self, new_slots: int) -> None:
        """Move the resident batch to ``new_slots`` slots (round 17's
        autoscale primitive, round-boundary only).  Live occupants
        migrate through the existing scatter machinery: each one's
        current world is read out of the old batch
        (``FleetBucket.extract_slot_payload``) and admitted into the
        new one — state, PRNG chain, rewired lanes, liveness seed and
        stagger row carried bit-for-bit, so every migrated scenario's
        remaining trajectory is unchanged (its slot INDEX may change;
        nothing the round computes reads it).  Occupant ledgers
        (rounds/converged/hist) ride the Occupant objects untouched."""
        import os as _os
        import signal as _signal

        live = [(s, o) for s, o in enumerate(self.occupants)
                if o is not None]
        if new_slots < 1:
            raise ValueError("resize needs >= 1 slot")
        if len(live) > new_slots:
            raise ValueError(
                f"cannot resize to {new_slots} slots with "
                f"{len(live)} live occupants")
        if new_slots == self.slots:
            return
        old_fleet, old = self.fleet, (self.state, self.topo,
                                      self.seeds, self.srcs)
        payloads = [old_fleet.extract_slot_payload(
            old[0], old[1], old[2], old[3], s) for s, _ in live]
        traces_before = self.trace_total()
        self.fleet = self._fleet_for(new_slots)
        if _os.environ.get("GOSSIP_SERVE_KILL") == "resize":
            # deterministic chaos seam (the GOSSIP_CKPT_KILL
            # precedent): die MID-resize, after the new batch exists
            # but before the occupants migrate — the worst torn
            # window.  Recovery must come from the last persisted
            # manifest, never from this half-moved in-memory state.
            _os.kill(_os.getpid(), _signal.SIGKILL)
        self.state, self.topo, self.done = self.fleet.init_idle()
        self.seeds = self.fleet._seeds
        self.srcs = self.fleet._srcs
        new_occ: list[Occupant | None] = [None] * new_slots
        for j, ((_s, occ), payload) in enumerate(zip(live, payloads)):
            (self.state, self.topo, self.done, self.seeds,
             self.srcs) = self.fleet.admit_into(
                self.state, self.topo, self.done, self.seeds,
                self.srcs, j, payload=payload)
            new_occ[j] = occ
        self.occupants = new_occ
        self.slots = new_slots
        self.resizes += 1
        self.admission_recompiles += self.trace_total() - traces_before

    # ------------------------------------------------------------------
    def admit(self, req: Request, slot: int | None = None) -> int:
        """Scatter ``req``'s scenario into a free slot (round-boundary
        only — the loop calls this between chunks).  Uses the payload
        staged during the previous chunk when one exists."""
        if req.signature != self.signature:
            raise ValueError("scheduler routed a request to a bucket "
                             "with a different program signature")
        if slot is None:
            free = self.free_slots()
            if not free:
                raise ValueError("admit() on a full bucket")
            slot = free[0]
        payload = getattr(req, "_staged_payload", None)
        if payload is None:
            payload = self.fleet.admit_args(req.spec.sim)
        else:
            req._staged_payload = None
        traces_before = self.trace_total()
        (self.state, self.topo, self.done, self.seeds,
         self.srcs) = self.fleet.admit_into(
            self.state, self.topo, self.done, self.seeds, self.srcs,
            slot, payload=payload)
        self.admission_recompiles += self.trace_total() - traces_before
        self.occupants[slot] = Occupant(req=req)
        return slot

    def stage(self, req: Request) -> None:
        """Pre-build ``req``'s admission payload (init state, overlay
        leaves, seed, srcs — host work + async device transfers) while
        a chunk is still executing, so the scatter at the next boundary
        is purely on-device."""
        if getattr(req, "_staged_payload", None) is None:
            req._staged_payload = self.fleet.admit_args(req.spec.sim)

    # ------------------------------------------------------------------
    def next_step(self, max_rounds: int) -> int:
        """The next chunk length: ``chunk``, clamped so no occupant
        runs past its ``max_rounds`` cap (when the cap is not a chunk
        multiple the final chunk is shorter — the batch-offline
        ``FleetBucket.run`` final-chunk idiom; ``_chunk_fn`` caches per
        length, so each distinct short length compiles once per
        bucket)."""
        rem = [max_rounds - o.rounds for o in self.occupants
               if o is not None]
        return max(1, min([self.chunk] + rem))

    def dispatch(self, step: int | None = None):
        """Run one chunk of ``step`` rounds (default the bucket chunk;
        async — the returned metric arrays are futures until
        device_get)."""
        step = self.chunk if step is None else step
        # the expected-trace ledger: this (width, length) program
        # compiles at most once — see expected_traces()
        self._programs.add((self.slots, step))
        fn = self.fleet._chunk_fn(step, self.target)
        (self.state, self.topo, self.done, ys, dhist) = fn(
            self.state, self.topo, self.done, self.seeds, self.srcs)
        return ys, dhist

    def collect(self, ys, dhist, max_rounds: int,
                step: int | None = None):
        """Read back one chunk's metrics and retire finished occupants.
        Returns ``[(slot, occupant, sim_result), ...]`` for every
        scenario that converged (its history truncated at its exact
        convergence round) or hit the ``max_rounds`` cap (unconverged,
        slot force-frozen)."""
        from p2p_gossipprotocol_tpu.sim import SimResult

        step = self.chunk if step is None else step
        ys = {k: np.asarray(jax.device_get(ys[k]))
              for k in self.fleet.metric_keys}
        dh = np.asarray(jax.device_get(dhist))
        retired = []
        for s, occ in enumerate(self.occupants):
            if occ is None:
                continue
            for k in self.fleet.metric_keys:
                occ.hist[k].append(ys[k][:, s])
            if occ.converged < 0:
                hits = np.nonzero(dh[:, s])[0]
                if hits.size:
                    occ.converged = occ.rounds + int(hits[0]) + 1
            occ.rounds += step
            if occ.converged > 0 or occ.rounds >= max_rounds:
                if occ.converged < 0:
                    # cap-retired: freeze the slot so reuse is safe
                    self.done = self.fleet.mark_done(self.done, s)
                retired.append((s, occ, self._extract(s, occ)))
                self.occupants[s] = None
        return retired

    def _extract(self, slot: int, occ: Occupant):
        """The occupant's SimResult — its slot's state/topology slice
        and its history truncated at its own rounds-run count, the
        exact shape a solo ``sim.run(rounds_run)`` returns."""
        from p2p_gossipprotocol_tpu.sim import SimResult

        r_i = occ.converged if occ.converged > 0 else occ.rounds
        st_i = jax.tree.map(lambda x: x[slot], self.state)
        tp_i = self.fleet.unstack_topo(self.topo, slot,
                                       occ.spec.sim.topo)
        hist = {k: np.concatenate(occ.hist[k])[:r_i].astype(
            self.fleet.metric_dtypes[k], copy=False)
            for k in self.fleet.metric_keys}
        wall = time.perf_counter() - (occ.req.t_admit
                                      or occ.req.t_enqueue)
        return SimResult(state=st_i, topo=tp_i, wall_s=wall, **hist)

    def rounds_run_of(self, occ: Occupant) -> int:
        return occ.converged if occ.converged > 0 else occ.rounds


class GossipService:
    """submit()/result()/drain() facade over the resident serving loop
    (the ``wrapper.Peer`` lifecycle shape, serving many scenarios
    instead of embodying one peer)."""

    #: minimum seconds between autoscale control ticks (see _last_tick)
    AUTOSCALE_TICK_S = 0.2

    def __init__(self, cfg, n_peers: int | None = None, *,
                 slots: int | None = None, queue_max: int | None = None,
                 max_buckets: int | None = None, chunk: int | None = None,
                 target: float | None = None, rounds: int | None = None,
                 checkpoint_dir: str | None = None,
                 results_path: str | None = None, resume: bool = False,
                 persist_every_s: float = 0.0,
                 autoscale: bool | None = None, log=None):
        self.cfg = cfg
        self.n_peers = n_peers
        self.slots = slots or cfg.serve_slots
        self.max_buckets = max_buckets or cfg.serve_max_buckets
        self.target = cfg.serve_target if target is None else target
        self.rounds = rounds or cfg.serve_rounds or cfg.rounds or 64
        # admission cadence: -1 (the config default) = auto, the rule
        # in autostatics.py (the classic 8); explicit values honored.
        # Chunking only paces admission boundaries — every served
        # scenario is bitwise its solo run at any chunk.
        self.chunk = heuristic_serve_chunk(
            cfg.serve_chunk if chunk is None else int(chunk))
        self.checkpoint_dir = checkpoint_dir or cfg.checkpoint_dir or None
        self.results_path = results_path or cfg.serve_results or None
        # telemetry-driven autoscaling (round 17): the control loop
        # consumes the exact occupancy/queue-depth values the PR 10
        # gauges publish and resizes the fleet's shape under load —
        # power-of-two slot-width grow/shrink per bucket plus
        # open/close under serve_max_buckets, with hysteresis (the
        # policy lives jax-free in serve/autoscale.py).
        from p2p_gossipprotocol_tpu.serve.autoscale import Autoscaler

        self.autoscale = bool(getattr(cfg, "serve_autoscale", 0)
                              if autoscale is None else autoscale)
        self.autoscaler = Autoscaler(
            min_slots=int(getattr(cfg, "serve_autoscale_min", 1)),
            max_slots=int(getattr(cfg, "serve_autoscale_max", 64)),
            hold=int(getattr(cfg, "serve_autoscale_hold", 3)))
        self.autoscale_events = 0
        #: widest slot width any bucket reached (high-water mark — the
        #: bench/measurement rows record it; the instantaneous min/max
        #: can already have shrunk back by the time a row lands)
        self.slot_width_peak = 0
        #: the parking lot (autoscale mode): closed/evicted buckets
        #: keep their compiled per-width programs here, keyed by
        #: signature, so a returning signature family reopens with an
        #: init_idle instead of a retrace.  Bounded (LRU): programs
        #: for long-gone families are dropped, oldest first.
        self._parked: dict = {}
        self._parked_cap = max(16, 2 * self.max_buckets)
        #: warm-import inbox (round 18, the federation's warm-program
        #: gossip): manifests arrive on handler threads, but buckets
        #: belong to the serving loop — entries queue here and the loop
        #: pre-traces them at its next boundary (compilation moved OFF
        #: the admission path, counted in ``prewarmed``)
        self._warm_lock = threading.Lock()
        self._warm_inbox: list = []
        self.prewarmed = 0
        #: loop-published export manifest twin of the occupancy
        #: snapshot (same atomic-swap discipline): what ``park_export``
        #: serves without touching buckets the loop may be mutating
        self._park_manifest: dict = {"schema": 1, "entries": []}
        #: trace ledger of buckets that left entirely (discarded on
        #: eviction with autoscale off, or LRU-dropped from the lot):
        #: the recompile metrics are CUMULATIVE — compile work must
        #: not disappear from the row when the bucket that paid it
        #: does (an eviction-churn plane would otherwise report the
        #: same retrace count as a warm one)
        self._retired = {"traces": 0, "expected": 0, "admissions": 0}
        #: control-loop tick floor: observations are sampled at most
        #: every AUTOSCALE_TICK_S, so the hold hysteresis is a WALL
        #: time (hold * tick floor), not an iteration count that
        #: shrinks with chunk latency — an idle loop spinning at 50
        #: iterations/s must not close a bucket 60 ms after its last
        #: occupant retired
        self._last_tick = 0.0
        # periodic persistence (serve-fleet replicas): the salvage
        # snapshot a SIGTERM writes once is refreshed every N seconds
        # at a chunk boundary, so even a SIGKILL — which runs no
        # handler — leaves a recent manifest whose completed rows the
        # router replays instead of re-executing (zero lost work,
        # rid-deduped).  0 = off (the single-server default).
        self.persist_every_s = float(persist_every_s or 0.0)
        self._last_persist = time.perf_counter()
        # replica heartbeat (runtime/supervisor.py file contract): a
        # dedicated thread refreshes it sub-second, independent of
        # chunk length — SIGSTOP freezes the thread and the router's
        # staleness deadline fires; process death is caught by the
        # router's proc.poll().  Configured by the CLI before start().
        self.heartbeat_path: str | None = None
        self.heartbeat_port: int = 0
        self.heartbeat_rank: int = 0
        self._hb_stop = threading.Event()
        self._hb_thread: threading.Thread | None = None
        self.log = log
        self.scheduler = Scheduler(
            cfg, queue_max or cfg.serve_queue_max, n_peers=n_peers,
            pad_peers=bool(cfg.sweep_pad_peers))
        self.buckets: list[ServeBucket] = []
        self.salvaged = False
        self._error: Exception | None = None
        self._thread: threading.Thread | None = None
        self._draining = threading.Event()
        self._salvage = threading.Event()
        self._wake = threading.Event()
        # occupancy snapshot for /stats: published (atomic dict swap)
        # by whichever thread owns the buckets at the time — __init__/
        # _resume before the loop starts, the serving loop after — so
        # handler threads never iterate buckets the loop is mutating
        self._occupancy: dict = {}
        # on-demand bounded jax.profiler capture (the serve ``profile``
        # document): one at a time, never concurrent with itself
        self._profile_lock = threading.Lock()
        if resume:
            self._resume()
        self._publish_occupancy()

    # -- fingerprint ---------------------------------------------------
    def _fingerprint(self) -> str:
        """The BASE config's trajectory identity: every request is base
        + overrides, so a drifted base invalidates the whole serve
        checkpoint (the per-request overrides ride the manifest
        verbatim and re-resolve against the verified base)."""
        from p2p_gossipprotocol_tpu.engines import config_keys
        from p2p_gossipprotocol_tpu.utils.checkpoint import \
            config_fingerprint

        return config_fingerprint(
            {"serve_base": config_keys(self.cfg, n_peers=self.n_peers)})

    # -- lifecycle ------------------------------------------------------
    def configure_heartbeat(self, path: str, port: int,
                            rank: int = 0) -> None:
        """Arm the serve-replica heartbeat (call before start()): the
        file at ``path`` is refreshed every 0.2 s with the replica's
        bound ``port`` — how the fleet router discovers where a replica
        actually listens (an EADDRINUSE rebind lands here) and judges
        its liveness (runtime/supervisor.py file contract)."""
        self.heartbeat_path = path
        self.heartbeat_port = int(port)
        self.heartbeat_rank = int(rank)

    def _hb_loop(self) -> None:
        from p2p_gossipprotocol_tpu.runtime.supervisor import \
            write_heartbeat

        while not self._hb_stop.is_set():
            try:
                write_heartbeat(
                    self.heartbeat_path, rank=self.heartbeat_rank,
                    phase="run",
                    extra={"kind": "serve-replica",
                           "port": self.heartbeat_port})
            except OSError:
                pass                      # a torn disk never kills serving
            self._hb_stop.wait(0.2)

    def start(self) -> "GossipService":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        if self.heartbeat_path and self._hb_thread is None:
            self._hb_thread = threading.Thread(target=self._hb_loop,
                                               daemon=True)
            self._hb_thread.start()
        return self

    def is_running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    # -- client surface -------------------------------------------------
    def submit(self, overrides: dict) -> int:
        """Enqueue one scenario (a JSONL-line config dict); returns its
        request id.  Raises :class:`ServeReject` — full queue, draining
        server, dead serving loop, unresolvable scenario — the
        explicit-backpressure contract: a request the loop can never
        serve is refused at the door, not accepted to hang."""
        if self._error is not None:
            raise ServeReject("serving loop failed: "
                              f"{type(self._error).__name__}: "
                              f"{self._error}")
        if self._thread is not None and not self._thread.is_alive():
            raise ServeReject("serving loop has stopped "
                              "(drained or salvaged)")
        req = self.scheduler.submit(overrides)
        self._wake.set()
        return req.rid

    def result(self, rid: int, timeout: float | None = None) -> dict:
        """Block until request ``rid`` completes; returns its results
        row.  Raises KeyError for an unknown id, TimeoutError on
        timeout, and re-raises a serving-loop failure — a FAILED
        request never masquerades as a results row."""
        req = self.scheduler.requests[rid]
        if not req.done_event.wait(timeout):
            raise TimeoutError(f"request {rid} not done within "
                               f"{timeout}s")
        if req.status == FAILED:
            if (req.row or {}).get("shed"):
                # shed, not failed: typed — the client can distinguish
                # "your deadline expired" from "the server broke"
                raise ServeShed(req.row["error"])
            if self._error is not None:
                raise self._error
            raise RuntimeError(
                (req.row or {}).get("error",
                                    f"request {rid} failed"))
        return req.row

    def sim_result(self, rid: int):
        """The served scenario's full SimResult (state + metric
        history) — the bitwise-parity surface the tests compare against
        solo runs."""
        return self.scheduler.requests[rid].result

    def _publish_occupancy(self) -> None:
        """Build a fresh occupancy snapshot and swap it in (atomic
        reference assignment — readers see the old dict or the new one,
        never a half-mutated bucket list).  Called only by the thread
        that currently owns the buckets."""
        widths = [b.slots for b in self.buckets]
        self.slot_width_peak = max([self.slot_width_peak] + widths)
        # parked buckets keep their trace history — the recompile
        # ledger must not forget a bucket just because it is idle
        every = list(self.buckets) + list(self._parked.values())
        self._occupancy = {
            "buckets": len(self.buckets),
            "slots": sum(widths),
            "slots_free": sum(len(b.free_slots())
                              for b in self.buckets),
            "chunk_retraces": (sum(b.trace_total() for b in every)
                               + self._retired["traces"]),
            # round 17: the resize-aware zero-recompile ledger — the
            # Poisson harness asserts admission_recompiles == 0 and
            # chunk_retraces == expected_retraces on every row
            "expected_retraces": (sum(b.expected_traces()
                                      for b in every)
                                  + self._retired["expected"]),
            "admission_recompiles": (sum(b.admission_recompiles
                                         for b in every)
                                     + self._retired["admissions"]),
            "autoscale_events": self.autoscale_events,
            "slot_width_min": min(widths) if widths else 0,
            "slot_width_max": max(widths) if widths else 0,
            "slot_width_peak": self.slot_width_peak,
            # round 18: the warm-park inventory — every signature
            # family with a compiled chunk program (resident or
            # parked) and the widths it is warm at.  The federation's
            # locality router reads this through /stats.
            "park": {repr(b.signature): sorted(b._fleets)
                     for b in every},
            "prewarmed": self.prewarmed,
        }
        self._park_manifest = {"schema": 1, "entries": [
            {"overrides": dict(b.template_spec.overrides),
             "widths": sorted(b._fleets), "chunk": b.chunk,
             "signature": repr(b.signature)} for b in every]}
        # /metrics gauges mirror the snapshot (no-ops when telemetry
        # is off)
        telemetry.gauge_set("serve_buckets", self._occupancy["buckets"])
        telemetry.gauge_set("serve_slots_free",
                            self._occupancy["slots_free"])
        telemetry.gauge_set("serve_queue_depth",
                            len(self.scheduler.queue))
        telemetry.gauge_set("serve_slot_width_min",
                            self._occupancy["slot_width_min"])
        telemetry.gauge_set("serve_slot_width_max",
                            self._occupancy["slot_width_max"])

    def stats(self) -> dict:
        """The ``/stats`` payload: scheduler ledger + resident-bucket
        occupancy + the zero-recompile counter.  Occupancy comes from
        the loop-published snapshot (at most one chunk stale), not a
        live iteration over buckets the loop may be mutating."""
        out = self.scheduler.stats()
        out.update(self._occupancy)
        return out

    # -- warm-program export/import (round 18: federation gossip) -------
    def park_export(self) -> dict:
        """The warm-program manifest: one entry per signature family
        this service holds a compiled chunk program for — its template
        overrides (the family, re-resolvable anywhere the base config
        matches), the widths it is warm at, and its signature repr
        (the import-side identity check).  Served from the
        loop-published snapshot — safe from any thread, at most one
        chunk stale, same discipline as the occupancy snapshot."""
        return self._park_manifest

    def park_import(self, manifest: dict, timeout: float = 300.0
                    ) -> dict:
        """Warm this service from a neighbor's export manifest: every
        entry whose signature is not already warm here gets a parked
        bucket with its chunk programs PRE-TRACED at the advertised
        widths — compilation paid now, off the admission path, so the
        first request of an imported family admits with zero retraces
        (the cold-fleet acceptance).  Buckets belong to the serving
        loop, so entries queue through the warm inbox and the loop
        imports at its next boundary; this call blocks until then.
        Returns ``{"imported": n, "skipped": m}`` (already-warm and
        signature-mismatched entries skip)."""
        entries = manifest.get("entries")
        if not isinstance(entries, list):
            raise ServeReject("warm manifest needs an 'entries' list")
        box = {"imported": 0, "skipped": 0, "prewarm_traces": 0,
               "error": None}
        done = threading.Event()
        if not self.is_running():
            # no loop owns the buckets yet (pre-start warm) — import
            # inline on the caller's thread
            self._do_import(entries, box)
        else:
            with self._warm_lock:
                self._warm_inbox.append((entries, box, done))
            self._wake.set()
            deadline = time.monotonic() + timeout
            while not done.wait(0.1):
                if not self.is_running():
                    raise ServeReject(
                        "warm import dropped: the serving loop "
                        "stopped before the inbox drained")
                if time.monotonic() > deadline:
                    raise ServeReject(
                        f"warm import did not complete within "
                        f"{timeout:g}s")
        if box["error"] is not None:
            raise ServeReject(f"warm import failed: {box['error']}")
        return {"imported": box["imported"], "skipped": box["skipped"],
                "prewarm_traces": box["prewarm_traces"]}

    def _prewarm_bucket(self, b: ServeBucket, widths: list[int]) -> int:
        """Trace ``b``'s chunk program at each width, on the all-idle
        batch (computes-and-discards under the convergence mask — the
        park contract's safety argument, so the next admission scatters
        over it exactly as over init_idle).  The device_get is the sync
        point that makes the compile actually land here, not at first
        admission."""
        n = 0
        for w in sorted(widths):
            if (w, self.chunk) in b._programs:
                continue
            if b.slots != w:
                b.resize(w)            # idle: pure init_idle, no payload
            _ys, dhist = b.dispatch()
            jax.device_get(dhist)
            n += 1
        return n

    def _do_import(self, entries: list, box: dict) -> None:
        """Run on whichever thread owns the buckets (the serving loop,
        or the caller before start): resolve, verify, pre-trace, park.
        Never raises — the outcome rides ``box`` back to the waiter."""
        from p2p_gossipprotocol_tpu.fleet.packer import bucket_signature

        try:
            warm = {b.signature for b in self.buckets} \
                | set(self._parked)
            for e in entries:
                if not isinstance(e, dict):
                    box["skipped"] += 1
                    continue
                ov = dict(e.get("overrides") or {})
                widths = sorted({int(w) for w in
                                 (e.get("widths") or [])}) \
                    or [self.slots]
                spec = resolve_request(
                    self.cfg, ov, rid=-1, n_peers=self.n_peers,
                    pad_peers=bool(self.cfg.sweep_pad_peers))
                sig = bucket_signature(spec.sim)
                want = e.get("signature")
                if sig in warm or (want is not None
                                   and want != repr(sig)):
                    # already warm here, or the donor's base config
                    # resolves this family to a different program —
                    # importing would warm the WRONG signature
                    box["skipped"] += 1
                    continue
                b = ServeBucket(spec, widths[0], self.chunk,
                                self.target)
                traces = self._prewarm_bucket(b, widths)
                b.park()
                self._lot_insert(b)
                warm.add(sig)
                self.prewarmed += traces
                box["imported"] += 1
                box["prewarm_traces"] += traces
                telemetry.counter_add("serve_prewarm_total", traces)
            telemetry.event("park_import", imported=box["imported"],
                            skipped=box["skipped"],
                            prewarm_traces=box["prewarm_traces"])
            if self.log and box["imported"]:
                self.log(f"[serve] warm-imported {box['imported']} "
                         f"famil(ies) ({box['prewarm_traces']} "
                         f"prewarm trace(s)), {box['skipped']} "
                         "skipped")
        except ServeReject as e:
            box["error"] = e.reason
        except Exception as e:  # noqa: BLE001 — surface to the waiter
            box["error"] = f"{type(e).__name__}: {e}"

    def _drain_warm_inbox(self) -> None:
        """Loop-side: import every queued manifest at this boundary and
        release the waiters."""
        while True:
            with self._warm_lock:
                if not self._warm_inbox:
                    return
                entries, box, done = self._warm_inbox.pop(0)
            try:
                self._do_import(entries, box)
            finally:
                done.set()

    def profile_capture(self, duration_s: float = 2.0,
                        top_n: int = 20,
                        log_dir: str | None = None) -> dict:
        """On-demand BOUNDED ``jax.profiler`` capture of the running
        service (the serve ``profile`` document): trace for
        ``duration_s`` seconds (clamped to [0.1, 30] — a profiler left
        running is an outage, not an observation) while the serving
        loop keeps dispatching, then summarize the capture through the
        same top-ops accounting the offline post-mortems use
        (telemetry.traceview.summarize == benchmarks/trace_top.py).

        Returns ``{"trace": path, "duration_s": s, "ops": rows}``.
        One capture at a time — the profiler is process-global; a
        concurrent request raises :class:`ServeReject` instead of
        corrupting the in-flight capture."""
        import tempfile

        from p2p_gossipprotocol_tpu.telemetry.traceview import (
            find_trace, summarize)

        duration_s = min(max(float(duration_s), 0.1), 30.0)
        if not self._profile_lock.acquire(blocking=False):
            raise ServeReject("a profile capture is already running "
                              "(the profiler is process-global; retry "
                              "when it finishes)")
        try:
            d = log_dir or tempfile.mkdtemp(prefix="gossip_profile_")
            jax.profiler.start_trace(d)
            try:
                time.sleep(duration_s)
            finally:
                jax.profiler.stop_trace()
            trace = find_trace(d)
            ops = summarize(trace, top_n=max(1, int(top_n)))
        finally:
            self._profile_lock.release()
        telemetry.event("profile_capture", duration_s=duration_s,
                        trace=trace, n_ops=len(ops))
        telemetry.counter_add("profile_captures_total")
        return {"trace": trace, "duration_s": duration_s, "ops": ops}

    def drain(self, timeout: float | None = None) -> dict:
        """Stop accepting, serve everything already admitted or queued,
        stop the loop; returns the final stats."""
        self.scheduler.stop_accepting()
        self._draining.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout)
        self._hb_stop.set()
        if self._error is not None:
            raise self._error
        return self.stats()

    def salvage(self, timeout: float | None = None) -> dict:
        """Preemption path: persist every resident bucket + the queue
        at the next chunk boundary (needs ``checkpoint_dir``), then
        stop.  The restarted server (``resume=True``) completes every
        previously admitted scenario bitwise."""
        if not self.checkpoint_dir:
            raise ValueError("salvage needs a checkpoint_dir")
        self.scheduler.stop_accepting()
        self._salvage.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout)
        self._hb_stop.set()
        if self._error is not None:
            raise self._error
        return self.stats()

    # -- the serving loop ----------------------------------------------
    def _retire_ledger(self, b: ServeBucket) -> None:
        self._retired["traces"] += b.trace_total()
        self._retired["expected"] += b.expected_traces()
        self._retired["admissions"] += b.admission_recompiles

    def _lot_insert(self, b: ServeBucket) -> None:
        """Put an idle bucket into the parking lot at the fresh end of
        the LRU order, trimming past the cap (a dropped bucket's
        compile ledger survives via ``_retire_ledger``)."""
        self._parked.pop(b.signature, None)   # refresh LRU position
        self._parked[b.signature] = b
        while len(self._parked) > self._parked_cap:
            oldest = next(iter(self._parked))
            self._retire_ledger(self._parked[oldest])
            del self._parked[oldest]

    def _park(self, b: ServeBucket) -> None:
        """Autoscale mode: retire an idle bucket into the parking lot
        (compiled programs kept, batch arrays released); without the
        control loop, discard — the PR 13 behavior, preserved so the
        A/B axes stay independent.  Either way the bucket's compile
        ledger survives (``_retire_ledger``)."""
        if not self.autoscale:
            self._retire_ledger(b)
            return
        b.park()
        self._lot_insert(b)

    def _bucket_for(self, req: Request) -> ServeBucket | None:
        """Routing: same-signature bucket with a free slot, else a new
        bucket (evicting — parking, in autoscale mode — an all-idle
        one when at the cap), else None (the request keeps waiting).
        A parked bucket for the signature reopens warm: one
        init_idle, zero retraces (round 17)."""
        for b in self.buckets:
            if b.signature == req.signature and b.free_slots():
                return b
        if len(self.buckets) >= self.max_buckets:
            idle = [b for b in self.buckets if not b.live()]
            if not idle:
                return None
            self.buckets.remove(idle[0])
            self._park(idle[0])
        parked = self._parked.pop(req.signature, None)
        if parked is not None:
            parked.unpark()
            self.buckets.append(parked)
            if self.log:
                self.log(f"[serve] reopened parked bucket "
                         f"{parked.uid} ({parked.slots} slots, warm "
                         f"programs) for request {req.rid}")
            return parked
        b = ServeBucket(req.spec, self.slots, self.chunk, self.target)
        self.buckets.append(b)
        if self.log:
            self.log(f"[serve] opened bucket {len(self.buckets) - 1} "
                     f"({self.slots} slots) for request {req.rid}")
        return b

    def _admit_pending(self) -> int:
        # the admit-boundary SLO sweep: a request already past its
        # deadline is shed with a typed reason, never handed a slot —
        # and queued() orders the survivors earliest-deadline-first
        # within priority, so under overload the slots go to requests
        # that can still land
        self.scheduler.shed_doomed(draining=self._draining.is_set())
        n = 0
        for req in self.scheduler.queued():
            b = self._bucket_for(req)
            if b is None:
                continue
            slot = b.admit(req)
            self.scheduler.mark_admitted(req)
            telemetry.counter_add("serve_admitted_total")
            n += 1
            if self.log:
                self.log(f"[serve] request {req.rid} -> bucket "
                         f"{self.buckets.index(b)} slot {slot}")
        return n

    def _autoscale_tick(self) -> int:
        """One control-loop tick (round-boundary only — the loop owns
        the buckets here): feed the policy the same occupancy/queue-
        depth signals the gauges publish, apply its decisions through
        the slot-swap machinery, ledger each one as a typed
        ``autoscale`` event.  Returns the number of applied actions
        (the loop re-runs admission after a grow so fresh slots take
        waiters in the same tick)."""
        from p2p_gossipprotocol_tpu.serve.autoscale import \
            BucketObservation

        qd: dict = {}
        for req in self.scheduler.queued():
            qd[req.signature] = qd.get(req.signature, 0) + 1
        obs = [BucketObservation(
            uid=b.uid, slots=b.slots, live=b.live_count(),
            queue_depth=qd.get(b.signature, 0)) for b in self.buckets]
        applied = 0
        for d in self.autoscaler.observe(obs):
            b = next((x for x in self.buckets if x.uid == d.bucket),
                     None)
            if b is None:
                continue
            if d.action == "close":
                self.buckets.remove(b)
                self._park(b)
                self.autoscaler.forget(b.uid)
            else:
                b.resize(d.to_slots)
            applied += 1
            self.autoscale_events += 1
            telemetry.event("autoscale", action=d.action,
                            bucket=d.bucket, from_slots=d.from_slots,
                            to_slots=d.to_slots, occupancy=d.occupancy,
                            queue_depth=d.queue_depth)
            telemetry.counter_add("serve_autoscale_total")
            if self.log:
                self.log(f"[serve] autoscale {d.action}: bucket "
                         f"{d.bucket} {d.from_slots} -> "
                         f"{d.to_slots} slots (live {d.occupancy}, "
                         f"queued {d.queue_depth})")
        return applied

    def _stage_pending(self) -> None:
        """While chunks execute: pre-stage admission payloads for
        queued requests that already have a destination bucket — the
        host→HBM half of the next admissions overlaps this chunk's
        compute and readback."""
        sigs = {b.signature for b in self.buckets}
        for req in self.scheduler.queued():
            if req.signature in sigs:
                for b in self.buckets:
                    if b.signature == req.signature:
                        b.stage(req)
                        break

    def _finish(self, bucket_id: int, occ: Occupant, res) -> None:
        req = occ.req
        req.t_converge = time.perf_counter()
        spec = occ.spec
        r_i = len(res.coverage)
        row = {**spec.row_identity(), "engine": "serve",
               "request": req.rid, "bucket": bucket_id,
               "rounds_run": int(r_i),
               "converged": bool(occ.converged > 0)}
        if req.deadline_ms is not None:
            row["deadline_ms"] = req.deadline_ms
            row["deadline_met"] = not req.past_deadline()
        if req.priority:
            row["priority"] = req.priority
        if req.tenant:
            row["tenant"] = req.tenant
        if r_i:
            row["final_coverage"] = float(res.coverage[-1])
            row["total_deliveries"] = int(round(
                float(res.deliveries.sum())))
        if self.target:
            row[f"rounds_to_{self.target:g}"] = int(
                res.rounds_to(self.target))
        self.scheduler.finish(req, row, result=res)
        # request span with a STABLE id (request:<rid> — rids survive a
        # salvage/resume) carrying the enqueue→admit→converge→result
        # ledger the scheduler stamped
        lat = req.latency_ms()
        telemetry.recorder().span_record(
            "request", (req.t_result - req.t_enqueue),
            span_id=f"request:{req.rid}", bucket=bucket_id,
            rounds_run=int(r_i), converged=bool(occ.converged > 0),
            **lat)
        telemetry.counter_add("serve_requests_total")
        if occ.converged > 0:
            telemetry.counter_add("serve_converged_total")
        if self.results_path:
            from p2p_gossipprotocol_tpu.fleet.driver import append_rows

            append_rows(self.results_path, [req.row])

    def _loop(self) -> None:
        try:
            while True:
                if self._salvage.is_set():
                    self._persist_all()
                    self.salvaged = True
                    return
                # warm-program imports land at the boundary, BEFORE
                # admission: a request racing its own family's import
                # sees the parked warm bucket, not a cold miss
                self._drain_warm_inbox()
                self._admit_pending()
                now = time.perf_counter()
                if self.autoscale \
                        and now - self._last_tick \
                        >= self.AUTOSCALE_TICK_S:
                    self._last_tick = now
                    if self._autoscale_tick():
                        # a grow frees capacity NOW — admit into it
                        # before dispatching, so the waiters it was
                        # grown for ride this very chunk
                        self._admit_pending()
                self._publish_occupancy()
                active = [b for b in self.buckets if b.live()]
                if not active:
                    if self._draining.is_set() \
                            and not self.scheduler.queued():
                        return
                    self._wake.wait(0.02)
                    self._wake.clear()
                    continue
                if self.persist_every_s > 0 and self.checkpoint_dir \
                        and (time.perf_counter() - self._last_persist
                             >= self.persist_every_s):
                    # fleet-replica discipline: refresh the salvage
                    # snapshot so a SIGKILL (no handler runs) still
                    # leaves a recent manifest for the router to replay
                    self._persist_all(dump=False)
                    self._last_persist = time.perf_counter()
                for b in active:
                    # clamp the final chunk so rounds_run never exceeds
                    # the serve_rounds cap (chunk boundaries need not
                    # divide it)
                    step = b.next_step(self.rounds)
                    with telemetry.span(
                            "chunk", kind="serve", rounds=step,
                            bucket=self.buckets.index(b),
                            occupants=sum(
                                o is not None for o in b.occupants)):
                        ys, dhist = b.dispatch(step)
                        # overlap seam: stage the next admissions while
                        # the chunk executes; collect() below is the
                        # sync point
                        self._stage_pending()
                        retired = b.collect(ys, dhist, self.rounds,
                                            step=step)
                    telemetry.counter_add("serve_rounds_total", step)
                    for slot, occ, res in retired:
                        self._finish(self.buckets.index(b), occ, res)
                self._publish_occupancy()
        except Exception as e:  # noqa: BLE001 — surface via result()
            self._error = e
            # refuse new submissions BEFORE failing the pending ones:
            # scheduler registration and stop_accepting share a lock,
            # so every request registered is in the snapshot below and
            # every later submit is rejected — none can slip between
            # and hang
            self.scheduler.stop_accepting()
            for req in list(self.scheduler.requests.values()):
                if req.status in (RUNNING, QUEUED):
                    self.scheduler.finish(
                        req, {"request": req.rid,
                              "error": f"{type(e).__name__}: {e}"},
                        failed=True)
        finally:
            self._publish_occupancy()

    # -- salvage / resume ----------------------------------------------
    def _manifest_path(self) -> str:
        return os.path.join(self.checkpoint_dir, "serve_manifest.json")

    def _bucket_path(self, b: int) -> str:
        return os.path.join(self.checkpoint_dir, f"serve_bucket_{b}.npz")

    def _persist_all(self, dump: bool = True) -> None:
        """Persist the whole serving state at a chunk boundary: the
        queue (request ids + overrides + SLO fields, FIFO order),
        completed rows, and every live bucket's CRC'd snapshot — the
        sweep driver's torn-write discipline (payload lands, then the
        manifest commits atomically).  ``dump=False`` is the periodic
        fleet-replica refresh (no flight-recorder dump per tick)."""
        from p2p_gossipprotocol_tpu.utils.checkpoint import (_crc_entry,
                                                             _write_atomic)

        def _q_item(r):
            item = {"rid": r.rid, "overrides": r.overrides}
            if r.deadline_ms is not None:
                item["deadline_ms"] = r.deadline_ms
            if r.priority:
                item["priority"] = r.priority
            if r.tenant:
                item["tenant"] = r.tenant
            return item

        os.makedirs(self.checkpoint_dir, exist_ok=True)
        manifest = {
            "schema": SERVE_SCHEMA, "kind": "serve",
            "fingerprint": self._fingerprint(),
            "next_rid": self.scheduler._next_rid,
            "queued": [_q_item(r) for r in self.scheduler.queued()],
            "done": {str(r.rid): r.row
                     for r in self.scheduler.requests.values()
                     if r.status == DONE and r.row is not None},
            "buckets": [],
        }
        for bi, b in enumerate(self.buckets):
            if not b.live():
                continue
            payload = {k: np.asarray(jax.device_get(v)) for k, v in
                       b.fleet.persist_arrays(b.state, b.topo).items()}
            payload["mask/done"] = np.asarray(jax.device_get(b.done))
            occs = {}
            for s, occ in enumerate(b.occupants):
                if occ is None:
                    continue
                occs[str(s)] = {"rid": occ.req.rid,
                                "overrides": occ.req.overrides,
                                "rounds": occ.rounds,
                                "converged": occ.converged}
                for k in b.fleet.metric_keys:
                    payload[f"hist/{s}/{k}"] = (
                        np.concatenate(occ.hist[k])
                        if occ.hist[k]
                        else np.zeros((0,), b.fleet.metric_dtypes[k]))
            path = self._bucket_path(len(manifest["buckets"]))
            tmp = path + ".tmp.npz"
            np.savez(tmp, **payload)
            os.replace(tmp, path)
            manifest["buckets"].append({
                "slots": b.slots,
                "kind": b.fleet.persist_kind,
                "template": b.template_spec.overrides,
                "occupants": occs,
                "leaves": {k: _crc_entry(v)
                           for k, v in payload.items()},
            })
        _write_atomic(self._manifest_path(),
                      json.dumps(manifest, sort_keys=True))
        if not dump:
            return
        # flight-recorder dump ALONGSIDE the salvage (the exit-75
        # contract grew a black box): the post-mortem of a preempted
        # server ships its own spans/events/counters
        telemetry.event("salvage", kind_detail="serve",
                        buckets=len(manifest["buckets"]),
                        queued=len(manifest["queued"]))
        telemetry.dump("serve_salvage", directory=self.checkpoint_dir)
        if self.log:
            n_live = sum(len(e["occupants"])
                         for e in manifest["buckets"])
            self.log(f"[serve] salvaged {len(manifest['buckets'])} "
                     f"bucket(s), {n_live} in-flight scenario(s), "
                     f"{len(manifest['queued'])} queued")

    def _resume(self) -> None:
        """Re-hydrate a salvaged server: completed rows return as done
        requests, in-flight buckets restore CRC-verified (occupant
        worlds re-admitted from their re-resolved solo sims, then the
        snapshot's state/colidx/done overwrite them — the sweep
        driver's restore split: statics rebuild deterministically, only
        mutated arrays carry history), and the queue re-submits in its
        original FIFO order under the original request ids."""
        from p2p_gossipprotocol_tpu.utils.checkpoint import (
            CorruptCheckpoint, FingerprintMismatch, _crc_entry,
            read_manifest)

        if not self.checkpoint_dir:
            raise ValueError("resume needs a checkpoint_dir")
        manifest = read_manifest(self._manifest_path(),
                                 schema_max=SERVE_SCHEMA,
                                 what="serve checkpoint")
        fp = self._fingerprint()
        if manifest.get("fingerprint") != fp:
            raise FingerprintMismatch(
                "serve checkpoint was written under fingerprint "
                f"{manifest.get('fingerprint')}, this server "
                f"fingerprints as {fp} — resume with the original base "
                "config, or point --checkpoint-dir at a fresh "
                "directory")
        self.scheduler._next_rid = int(manifest.get("next_rid", 0))
        # completed rows come back as done requests (result() replays)
        for rid_s, row in manifest.get("done", {}).items():
            req = Request(rid=int(rid_s), overrides={}, spec=None,
                          signature=None, status=DONE,
                          t_enqueue=time.perf_counter())
            req.row = row
            req.done_event.set()
            self.scheduler.requests[int(rid_s)] = req
        for bi, entry in enumerate(manifest.get("buckets", [])):
            path = self._bucket_path(bi)
            try:
                with np.load(path) as m:
                    payload = {k: m[k] for k in m.files}
            except Exception as e:  # noqa: BLE001 — any unreadable snapshot
                raise CorruptCheckpoint(
                    f"serve bucket {bi} snapshot is unreadable "
                    f"({type(e).__name__}: {e})") from e
            for name, info in entry["leaves"].items():
                if name not in payload:
                    raise CorruptCheckpoint(
                        f"serve bucket {bi} snapshot is missing leaf "
                        f"{name!r}")
                if _crc_entry(payload[name])["crc32"] != info["crc32"]:
                    raise CorruptCheckpoint(
                        f"CRC mismatch in serve bucket {bi} leaf "
                        f"{name!r}")
            tmpl = resolve_request(self.cfg, entry["template"], rid=-1,
                                   n_peers=self.n_peers,
                                   pad_peers=bool(
                                       self.cfg.sweep_pad_peers))
            b = ServeBucket(tmpl, int(entry["slots"]), self.chunk,
                            self.target)
            for slot_s, occ_e in entry["occupants"].items():
                slot, rid = int(slot_s), int(occ_e["rid"])
                spec = resolve_request(
                    self.cfg, occ_e["overrides"], rid,
                    n_peers=self.n_peers,
                    pad_peers=bool(self.cfg.sweep_pad_peers))
                from p2p_gossipprotocol_tpu.fleet.packer import \
                    bucket_signature

                req = Request(rid=rid, overrides=dict(occ_e["overrides"]),
                              spec=spec,
                              signature=bucket_signature(spec.sim),
                              status=RUNNING,
                              t_enqueue=time.perf_counter())
                req.t_admit = req.t_enqueue
                self.scheduler.requests[rid] = req
                b.admit(req, slot=slot)
                occ = b.occupants[slot]
                occ.rounds = int(occ_e["rounds"])
                occ.converged = int(occ_e["converged"])
                for k in b.fleet.metric_keys:
                    h = payload[f"hist/{slot}/{k}"]
                    occ.hist[k] = [h] if len(h) else []
            kind = entry.get("kind", "aligned")
            if kind != b.fleet.persist_kind:
                raise CorruptCheckpoint(
                    f"serve bucket {bi} snapshot was written by a "
                    f"{kind!r} bucket but the template re-resolved as "
                    f"{b.fleet.persist_kind!r} — the base config "
                    "changed under the checkpoint")
            # the snapshot's mutated arrays win over the re-admitted
            # init worlds: state leaves wholesale, mutated topology
            # lanes (aligned: rewired colidx; realgraph: dst +
            # edge_mask), done
            b.state, b.topo = b.fleet.restore_arrays(b.topo, payload)
            b.done = jnp.asarray(payload["mask/done"])
            self.buckets.append(b)
        for item in manifest.get("queued", []):
            ov = dict(item["overrides"])
            # SLO fields ride the manifest beside the overrides; the
            # deadline clock restarts at re-enqueue (the original
            # enqueue instant died with the preempted process)
            if item.get("deadline_ms") is not None:
                ov["deadline_ms"] = item["deadline_ms"]
            if item.get("priority"):
                ov["priority"] = item["priority"]
            if item.get("tenant"):
                ov["tenant"] = item["tenant"]
            self.scheduler.submit(ov, rid=int(item["rid"]))
        if self.log:
            self.log(f"[serve] resumed {len(self.buckets)} bucket(s), "
                     f"{len(manifest.get('queued', []))} queued "
                     "request(s) re-hydrated")
