"""The global serving federation: cross-fleet routing with
warm-program locality, whole-fleet-loss recovery, and multi-tenant
SLO fairness.

PR 13/15 made one FLEET robust: a router over supervised replicas,
zero-lost/zero-dup under replica SIGKILL.  This tier answers the next
outage class — the whole fleet is the failure domain (a pod preempted,
a rack power event, a bad rollout taking every replica at once) — by
applying the SAME discipline one level up:

* **One wire, F fleets.**  :class:`FederationService` exposes the
  ``submit()/result()/stats()/drain()`` facade the single server and
  the router do, so the unmodified :class:`~p2p_gossipprotocol_tpu
  .serve.server.ServeServer` fronts it and a client cannot tell a
  federation from a single process.  Each member fleet is an ordinary
  ``--serve-fleet`` CLI child (the PR 13/15 router + its replicas,
  UNMODIFIED) on its own wire port, own run dir, own fleet-kind
  heartbeat — the replica contract lifted one level.

* **Locality routing over the warm-program directory.**  Requests
  resolve to their compiled-program identity (``fleet/packer
  .bucket_signature``, THE routing key, resolved once per scenario
  family exactly like the router) and stick to one fleet; a COLD
  signature prefers the live fleet whose warm parking lot already
  holds its program — the :class:`~p2p_gossipprotocol_tpu.serve
  .directory.FleetDirectory` carries every fleet's park inventory
  (signature → parked widths), refreshed each directory tick.  A
  seed-deterministic anti-entropy round (:func:`~p2p_gossipprotocol_tpu
  .serve.directory.gossip_pairs`) then exchanges warm-program
  manifests pairwise, so a cold fleet warms from its neighbors'
  exports (``park``/``warm`` wire ops — prewarm-traced parked buckets,
  ZERO admission recompiles) instead of paying XLA again.

* **Whole-fleet loss, exactly-once.**  The federation's
  :class:`~p2p_gossipprotocol_tpu.serve.directory.OwnershipLedger`
  owns every request: rid → (state, fleet, epoch), terminal rows win
  exactly once.  Fleets stamp sub-second fleet-kind heartbeats and
  refresh a fleet-level salvage manifest (done rows keyed by the
  FEDERATION's dispatch ids); on fleet death the federation (1) adopts
  the manifest's completed rows through the ledger's lattice join —
  the epoch fence refuses a stale generation's manifest wholesale —
  then (2) re-admits every remaining in-flight rid onto survivors by
  the locality rule, and (3) relaunches the slot as epoch+1 with a
  FRESH run dir (the corpse's artifacts can never be re-read).
  Detection + MTTR are recorded; recovered scenarios are bitwise equal
  to their solo runs (the PR 9 contract, preserved through two hops).

* **Multi-tenant SLO fairness.**  Requests carry ``tenant`` (an SLO
  field, stripped before resolution like ``deadline_ms``); the
  :class:`TenantGovernor` holds per-tenant admission budgets — a
  weighted share of ``federate_admit_rps``, refreshed every
  ``federate_budget_s`` — and sheds over-budget tenants with the typed
  reason ``SHED_OVER_BUDGET``, so one tenant's burst degrades THAT
  tenant's traffic, not the victim's p50.

docs/ROBUSTNESS.md "The federation" has the failure taxonomy.
"""

from __future__ import annotations

import json
import os
import signal
import threading
import time
from dataclasses import dataclass, field

from p2p_gossipprotocol_tpu import telemetry
from p2p_gossipprotocol_tpu.fleet.packer import bucket_signature
from p2p_gossipprotocol_tpu.fleet.spec import next_pow2
from p2p_gossipprotocol_tpu.runtime.supervisor import (classify_exit,
                                                       read_heartbeat,
                                                       serve_fleet_argv,
                                                       spawn_serve_fleet)
from p2p_gossipprotocol_tpu.serve.directory import (L_DONE, L_FAILED,
                                                    L_INFLIGHT,
                                                    FleetDirectory,
                                                    OwnershipLedger,
                                                    gossip_pairs)
from p2p_gossipprotocol_tpu.serve.scheduler import (SHED_OVER_BUDGET,
                                                    Scheduler, ServeReject,
                                                    ServeShed,
                                                    resolve_request)
from p2p_gossipprotocol_tpu.serve.server import ServeClient

#: warm-program entries exchanged per direction per anti-entropy pair
#: (bounded — a tick must stay cheap; the next tick continues)
ANTIENTROPY_MAX_ENTRIES = 4


def parse_tenant_weights(spec: str) -> dict[str, float]:
    """``federate_tenants`` ("alpha=3,beta=1") → weight map.  Raises
    ValueError on malformed entries (config validation surfaces it);
    an empty spec is an empty map — every tenant then weighs 1."""
    out: dict[str, float] = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, eq, w = part.partition("=")
        name = name.strip()
        if not name or not eq:
            raise ValueError(
                f"federate_tenants entry {part!r} is not name=weight")
        weight = float(w)
        if weight <= 0:
            raise ValueError(
                f"federate_tenants weight for {name!r} must be > 0, "
                f"got {weight:g}")
        out[name] = weight
    return out


class TenantGovernor:
    """Per-tenant admission budgets: each tenant owns a weighted share
    of ``admit_rps`` capacity, refreshed every ``budget_s`` window —
    tenant ``t`` may admit ``admit_rps * budget_s * w_t / W`` requests
    per window (W = the sum of all known weights; a tenant absent from
    the weight map joins at weight 1 on first sight).  Over budget →
    :class:`ServeShed` with the typed ``SHED_OVER_BUDGET`` reason.
    ``admit_rps=0`` disables the governor entirely (the single-tenant
    deployments of PR 13/15 are unchanged).

    The clock is injectable (``now``) so the fairness tests are pure —
    no sleeps, no processes."""

    def __init__(self, *, weights: dict[str, float] | None = None,
                 admit_rps: float = 0.0, budget_s: float = 1.0):
        self.admit_rps = float(admit_rps)
        self.budget_s = float(budget_s)
        if self.budget_s <= 0:
            raise ValueError("budget_s must be > 0")
        self._lock = threading.Lock()
        self._weights = dict(weights or {})
        self._spent: dict[str, int] = {}
        self._window_start: float | None = None
        self.n_admitted = 0
        self.n_shed = 0
        self._shed_by: dict[str, int] = {}

    def quota(self, tenant: str) -> float:
        """This window's budget for ``tenant`` (current weight map)."""
        with self._lock:
            return self._quota_locked(tenant)

    def _quota_locked(self, tenant: str) -> float:
        w = self._weights.setdefault(tenant, 1.0)
        total = sum(self._weights.values())
        return self.admit_rps * self.budget_s * w / total

    def admit(self, tenant: str, now: float | None = None) -> None:
        """Charge one request to ``tenant``'s budget; raises
        :class:`ServeShed` (``SHED_OVER_BUDGET``) when the window's
        share is spent.  The empty tenant is a tenant like any other
        (weight 1 unless configured) — unlabeled traffic cannot starve
        labeled traffic."""
        if self.admit_rps <= 0:
            return
        t = time.monotonic() if now is None else now
        with self._lock:
            if (self._window_start is None
                    or t - self._window_start >= self.budget_s):
                self._window_start = t
                self._spent = {}
            quota = self._quota_locked(tenant)
            spent = self._spent.get(tenant, 0)
            if spent >= quota:
                self.n_shed += 1
                self._shed_by[tenant] = self._shed_by.get(tenant, 0) + 1
                raise ServeShed(
                    f"{SHED_OVER_BUDGET}: tenant {tenant or '<default>'!r}"
                    f" spent {spent} of {quota:g} this "
                    f"{self.budget_s:g}s window")
            self._spent[tenant] = spent + 1
            self.n_admitted += 1

    def counts(self) -> dict:
        with self._lock:
            return {"admitted": self.n_admitted, "shed": self.n_shed,
                    "shed_by_tenant": dict(self._shed_by),
                    "weights": dict(self._weights)}


@dataclass
class FleetHandle:
    """One federation member: a ``--serve-fleet`` child (router +
    replicas), its fleet-kind heartbeat, its epoch-numbered run dir,
    and one pipelined control connection.  ``epoch`` bumps on every
    relaunch — a fresh epoch gets a fresh run dir, and the ownership
    ledger's fence makes the dead epoch's salvage unreadoptable."""

    index: int
    name: str
    epoch: int
    hb_path: str
    run_dir: str
    port: int = 0
    proc: object = None                  # subprocess.Popen
    client: ServeClient | None = None
    alive: bool = False
    joining: bool = True
    recovering: bool = False             # one recovery per corpse
    t_spawn: float = 0.0
    #: same discipline as the router's ReplicaHandle: a pipelined
    #: client multiplexes by seq (no lock needed); a legacy single-RPC
    #: client serializes here
    rpc_lock: threading.Lock = field(default_factory=threading.Lock,
                                     repr=False)

    @property
    def pipelined(self) -> bool:
        return self.client is not None and self.client.window > 0

    def submit(self, overrides: dict) -> int:
        if self.pipelined:
            return self.client.submit(overrides)
        with self.rpc_lock:
            return self.client.submit(overrides)

    def result(self, frid: int, timeout: float) -> dict:
        return self.client.result(frid, timeout=timeout)

    def stats(self) -> dict:
        if self.pipelined:
            return self.client.stats()
        with self.rpc_lock:
            return self.client.stats()

    def park(self) -> dict:
        if self.pipelined:
            return self.client.park()
        with self.rpc_lock:
            return self.client.park()

    def warm(self, manifest: dict) -> dict:
        if self.pipelined:
            return self.client.warm(manifest)
        with self.rpc_lock:
            return self.client.warm(manifest)

    def drain(self) -> dict:
        if self.pipelined:
            return self.client.drain()
        with self.rpc_lock:
            return self.client.drain()

    def manifest_path(self) -> str:
        return os.path.join(self.run_dir, "fleet_manifest.json")


@dataclass
class FedRequest:
    """One federation ledger entry's working record — the federation
    rid is the GLOBAL dedup key; ``fleet_rid`` is the id the owning
    fleet's router knows it by."""

    rid: int
    overrides: dict
    signature: str                       # repr(bucket_signature(...))
    tenant: str = ""
    fleet: str | None = None
    fleet_rid: int | None = None
    status: str = L_INFLIGHT
    redirects: int = 0
    row: dict | None = None


class FederationService:
    """submit()/result()/stats()/drain() over F supervised serving
    fleets (see module docstring) — drop-in behind ``ServeServer``."""

    def __init__(self, cfg, n_peers: int | None = None, *,
                 fleets: int | None = None, run_dir: str | None = None,
                 health_s: float | None = None, grace_s: float = 300.0,
                 poll_s: float = 0.05, restart: bool = True,
                 max_restarts: int = 4, directory_s: float | None = None,
                 fleet_extra_args: tuple[str, ...] = (), log=None):
        import tempfile

        self.cfg = cfg
        self.n_peers = n_peers
        self.n_fleets = int(fleets or
                            getattr(cfg, "federate_fleets", 2) or 2)
        if self.n_fleets < 1:
            raise ValueError("a federation needs >= 1 fleet")
        self.replicas_per_fleet = int(getattr(cfg, "serve_replicas", 3)
                                      or 3)
        self.run_dir = run_dir or tempfile.mkdtemp(prefix="gossip_fed_")
        self.health_s = float(health_s if health_s is not None
                              else getattr(cfg, "federate_health_s", 2.0))
        self.grace_s = float(grace_s)
        self.poll_s = float(poll_s)
        self.restart = bool(restart)
        self.max_restarts = int(max_restarts)
        self.directory_s = float(directory_s if directory_s is not None
                                 else max(0.5, self.health_s / 2))
        self.fleet_extra_args = tuple(fleet_extra_args)
        self.pad_peers = bool(getattr(cfg, "sweep_pad_peers", 1))
        self.inner_window = (int(getattr(cfg, "serve_inflight", 32))
                             if int(getattr(cfg, "serve_pipeline", 1))
                             else 0)
        self.seed = int(getattr(cfg, "prng_seed", 0) or 0)
        self.log = log
        self.directory = FleetDirectory(os.path.join(self.run_dir,
                                                     "directory"))
        self.ledger = OwnershipLedger()
        self.governor = TenantGovernor(
            weights=parse_tenant_weights(
                str(getattr(cfg, "federate_tenants", "") or "")),
            admit_rps=float(getattr(cfg, "federate_admit_rps", 0) or 0),
            budget_s=float(getattr(cfg, "federate_budget_s", 1.0)
                           or 1.0))
        self._lock = threading.Lock()
        self._sig_lock = threading.Lock()
        self._sig_cache: dict[tuple, str] = {}
        self._fleets: list[FleetHandle] = []
        self._requests: dict[int, FedRequest] = {}
        self._affinity: dict[str, int] = {}      # signature -> slot
        self._park_view: dict[str, set[str]] = {}  # fleet -> signatures
        self._next_rid = 0
        self._accepting = True
        self._n_deaths = 0
        self._n_restarts = 0
        self._n_redirects = 0
        self._n_adopted = 0
        self._n_warm_exchanges = 0
        self._mttr_s: float | None = None
        self._detect_s: float | None = None
        self._last_death_ts: float | None = None
        self._last_dir = 0.0
        self._dir_tick = 0
        self._stop = threading.Event()
        self._health_thread: threading.Thread | None = None

    # -- lifecycle ------------------------------------------------------
    def _spawn(self, index: int, epoch: int = 0) -> FleetHandle:
        from p2p_gossipprotocol_tpu.runtime.supervisor import _free_port

        name = str(index)
        tag = f"fleet_{index}_e{epoch}"
        h = FleetHandle(
            index=index, name=name, epoch=epoch,
            hb_path=os.path.join(self.run_dir, f"hb_{tag}.json"),
            run_dir=os.path.join(self.run_dir, tag),
            port=_free_port(), t_spawn=time.monotonic())
        argv = serve_fleet_argv(
            self.cfg.config_file_path, port=h.port,
            heartbeat_path=h.hb_path, run_dir=h.run_dir,
            fleet=name, epoch=epoch, n_peers=self.n_peers,
            extra_args=self.fleet_extra_args)
        h.proc = spawn_serve_fleet(argv, run_dir=self.run_dir,
                                   fleet=tag)
        self.ledger.advance_epoch(name, epoch)
        if self.log:
            self.log(f"[fed] spawned fleet {name} epoch {epoch} pid "
                     f"{h.proc.pid} port {h.port}")
        return h

    def start(self) -> "FederationService":
        if self._health_thread is not None:
            return self
        handles = [self._spawn(i) for i in range(self.n_fleets)]
        with self._lock:
            self._fleets = handles
        self._health_thread = threading.Thread(target=self._health_loop,
                                               daemon=True)
        self._health_thread.start()
        return self

    def wait_ready(self, min_live: int | None = None,
                   timeout: float = 600.0) -> int:
        """Block until ``min_live`` fleets (default: all) have joined —
        fleet-kind heartbeat up (which a fleet only stamps once ITS
        replicas joined), control connection established."""
        want = self.n_fleets if min_live is None else int(min_live)
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                live = sum(1 for h in self._fleets if h.alive)
            if live >= want:
                return live
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"only {live}/{want} fleets joined within "
                    f"{timeout:g}s (see {self.run_dir}/fleet_*.err)")
            time.sleep(0.05)

    # -- signature routing ---------------------------------------------
    def _signature_of(self, overrides: dict) -> str:
        """The request's compiled-program identity as the park
        inventory spells it — ``repr(bucket_signature(spec.sim))`` —
        resolved once per scenario FAMILY (the router's sketch-cache
        idiom: SLO fields and per-scenario arrays dropped, ``n_peers``
        padded the way the spec layer pads it)."""
        ov, _deadline, _priority, _tenant = Scheduler.split_slo(overrides)
        sketch = dict(ov)
        sketch.pop("prng_seed", None)
        if self.pad_peers and "n_peers" in sketch:
            sketch["n_peers"] = next_pow2(int(sketch["n_peers"]))
        key = tuple(sorted((k, repr(v)) for k, v in sketch.items()))
        with self._sig_lock:
            sig = self._sig_cache.get(key)
        if sig is not None:
            return sig
        spec = resolve_request(self.cfg, ov, rid=-1,
                               n_peers=self.n_peers,
                               pad_peers=self.pad_peers)
        sig = repr(bucket_signature(spec.sim))
        with self._sig_lock:
            self._sig_cache[key] = sig
        return sig

    @staticmethod
    def pick_fleet(sig: str, *, live: list[str],
                   affinity: dict[str, str],
                   park_view: dict[str, set[str]],
                   load: dict[str, int]) -> str:
        """The locality rule, as a pure function (pinned by the
        no-process tests): sticky owner if alive; else the live fleet
        already advertising ``sig`` warm in the directory (lowest name
        breaks ties); else the least-loaded live fleet (fewest owned
        signatures, lowest name).  Determinism here is what makes a
        recovery layout reproducible from the failure history."""
        if not live:
            raise ServeReject(
                "no live fleets (the federation is forming or lost "
                "all capacity — retry, or check the supervisor log)")
        owner = affinity.get(sig)
        if owner is not None and owner in live:
            return owner
        warm = sorted(n for n in live
                      if sig in park_view.get(n, ()))
        if warm:
            return warm[0]
        return min(live, key=lambda n: (load.get(n, 0), n))

    def _route(self, sig: str) -> FleetHandle:
        with self._lock:
            live = [h for h in self._fleets if h.alive]
            by_name = {h.name: h for h in live}
            load: dict[str, int] = {h.name: 0 for h in live}
            aff = {s: self._fleets[i].name
                   for s, i in self._affinity.items()}
            for s, n in aff.items():
                if n in load:
                    load[n] += 1
            name = self.pick_fleet(sig, live=sorted(by_name),
                                   affinity=aff,
                                   park_view=self._park_view,
                                   load=load)
            h = by_name[name]
            self._affinity[sig] = h.index
            return h

    # -- client surface -------------------------------------------------
    def submit(self, overrides: dict) -> int:
        """Enqueue one scenario onto the federation; returns the
        FEDERATION request id (the global dedup key).  The tenant
        budget is charged at this door — an over-budget tenant sheds
        HERE (``SHED_OVER_BUDGET``), before any fleet sees the work."""
        with self._lock:
            if not self._accepting:
                raise ServeReject("federation is draining (no new work)")
        _ov, _deadline, _priority, tenant = \
            Scheduler.split_slo(overrides)
        self.governor.admit(tenant)
        sig = self._signature_of(overrides)
        with self._lock:
            if not self._accepting:
                raise ServeReject("federation is draining (no new work)")
            rid = self._next_rid
            self._next_rid += 1
            req = FedRequest(rid=rid, overrides=dict(overrides),
                             signature=sig, tenant=tenant)
            self._requests[rid] = req
        try:
            self._dispatch(req)
        except ServeReject:
            with self._lock:
                req.status = L_FAILED
                del self._requests[rid]
            raise
        return rid

    def _dispatch(self, req: FedRequest) -> None:
        """Forward ``req`` to its locality fleet; a transport failure
        marks that fleet dead (the health loop confirms and recovers
        the rest of its load) and retries on the survivors."""
        last: Exception | None = None
        for _attempt in range(self.n_fleets + 1):
            h = self._route(req.signature)
            try:
                frid = h.submit(req.overrides)
            except ServeReject:
                raise                    # fleet-side policy: forward
            except (ConnectionError, OSError) as e:
                last = e
                self._mark_dead(h, f"submit transport error: "
                                   f"{type(e).__name__}: {e}")
                continue
            with self._lock:
                req.fleet = h.name
                req.fleet_rid = frid
            self.ledger.claim(req.rid, h.name, h.epoch)
            telemetry.counter_add("fed_dispatch_total")
            return
        raise ServeReject(f"no fleet accepted the request "
                          f"({type(last).__name__ if last else 'n/a'})")

    def result(self, rid: int, timeout: float | None = None) -> dict:
        """Block until federation request ``rid`` completes; returns
        its row (rewritten to the federation rid, tagged with the
        serving fleet).  A request whose fleet dies mid-wait is
        adopted or re-admitted by recovery and this wait follows it."""
        deadline = (time.monotonic() + timeout) if timeout else None
        while True:
            with self._lock:
                if rid not in self._requests:
                    raise KeyError(f"unknown request id {rid}")
                req = self._requests[rid]
                status, row = req.status, req.row
                fleet, frid = req.fleet, req.fleet_rid
                h = next((x for x in self._fleets
                          if x.name == fleet and x.alive), None)
            if status == L_DONE:
                return row
            if status == L_FAILED:
                if row and row.get("shed"):
                    raise ServeShed(row.get("error", row["shed"]))
                raise RuntimeError((row or {}).get(
                    "error", f"request {rid} failed"))
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(f"request {rid} not done within "
                                   f"{timeout}s")
            if h is None or frid is None:
                time.sleep(0.05)         # recovery is re-routing it
                continue
            try:
                raw = h.result(frid, timeout=2.0)
            except TimeoutError:
                continue                 # still pending — poll again
            except (ConnectionError, OSError):
                time.sleep(0.05)         # fleet died mid-wait
                continue
            except RuntimeError as e:
                msg = str(e)
                if "shed:" in msg:
                    self._finish(req, {"request": rid, "shed": msg,
                                       "error": msg}, failed=True)
                    raise ServeShed(msg) from e
                if "unknown request id" in msg:
                    # a relaunched epoch numbers rids afresh; recovery
                    # re-dispatches — follow it
                    time.sleep(0.05)
                    continue
                self._finish(req, {"request": rid, "error": msg},
                             failed=True)
                raise
            self._finish(req, raw)
            with self._lock:
                return req.row

    def _finish(self, req: FedRequest, raw: dict,
                failed: bool = False) -> None:
        """Record a terminal row exactly once — through the OWNERSHIP
        LEDGER's join, so a row adopted from a salvage manifest and one
        replayed by a survivor can never both land (zero duplicated,
        federation-wide)."""
        row = dict(raw)
        row["request"] = req.rid
        if req.fleet is not None:
            row["fleet"] = req.fleet
        if req.redirects:
            row["fed_redirects"] = req.redirects
        if not self.ledger.complete(req.rid, row, failed=failed):
            return                       # the other path already won
        with self._lock:
            if req.status != L_INFLIGHT:
                return
            req.row = row
            req.status = L_FAILED if failed else L_DONE

    def profile_capture(self, duration_s: float = 2.0, top_n: int = 20,
                        log_dir: str | None = None) -> dict:
        raise ServeReject(
            "the federation fronts fleets and owns no device — send "
            "`profile` to a replica port directly (stats() lists "
            "fleet wire ports)")

    # -- warm-program export/import (the gossip plane's facade) ---------
    def park_export(self) -> dict:
        """The FEDERATION's warm-program manifest: every live fleet's
        export, deduplicated by signature."""
        entries, seen = [], set()
        with self._lock:
            handles = [h for h in self._fleets if h.alive]
        for h in handles:
            try:
                m = h.park()
            except (ConnectionError, OSError, RuntimeError):
                continue
            for e in m.get("entries", []):
                s = e.get("signature")
                if s in seen:
                    continue
                seen.add(s)
                entries.append(e)
        return {"schema": 1, "entries": entries}

    def park_import(self, manifest: dict) -> dict:
        """Warm the federation from an external manifest: each entry
        routes to its signature's locality fleet and imports there."""
        entries = manifest.get("entries")
        if not isinstance(entries, list):
            raise ServeReject("warm manifest needs an 'entries' list")
        out = {"imported": 0, "skipped": 0, "prewarm_traces": 0}
        for e in entries:
            if not isinstance(e, dict):
                out["skipped"] += 1
                continue
            sig = self._signature_of(dict(e.get("overrides") or {}))
            h = self._route(sig)
            try:
                r = h.warm({"schema": 1, "entries": [e]})
            except (ConnectionError, OSError) as err:
                self._mark_dead(h, f"warm transport error: "
                                   f"{type(err).__name__}: {err}")
                out["skipped"] += 1
                continue
            for k in ("imported", "skipped", "prewarm_traces"):
                out[k] += int(r.get(k, 0))
        return out

    # -- stats ----------------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            reqs = list(self._requests.values())
            handles = list(self._fleets)
            out = {
                "federation": True,
                "fleets": self.n_fleets,
                "fleets_live": sum(1 for h in handles if h.alive),
                "deaths": self._n_deaths,
                "restarts": self._n_restarts,
                "redirects": self._n_redirects,
                "adopted": self._n_adopted,
                "warm_exchanges": self._n_warm_exchanges,
                "signatures": len(self._affinity),
                "park_view": {n: sorted(s) for n, s in
                              self._park_view.items()},
            }
            if self._mttr_s is not None:
                out["mttr_s"] = round(self._mttr_s, 3)
            if self._detect_s is not None:
                out["detect_s"] = round(self._detect_s, 3)
            if self._last_death_ts is not None:
                out["last_death_ts"] = self._last_death_ts
        out["submitted"] = len(reqs)
        out["done"] = sum(1 for r in reqs if r.status == L_DONE)
        out["failed"] = sum(1 for r in reqs if r.status == L_FAILED)
        out["inflight"] = sum(1 for r in reqs
                              if r.status == L_INFLIGHT)
        out["ledger"] = self.ledger.counts()
        out["tenants"] = self.governor.counts()
        per = {}
        for h in handles:
            if not h.alive:
                continue
            try:
                st = h.stats()
                st.pop("type", None)
                per[h.name] = {"port": h.port, "epoch": h.epoch, **st}
            except (ConnectionError, OSError, RuntimeError):
                continue
        out["fleet_stats"] = per
        lat = [(s.get("p50_ms"), s.get("p99_ms"))
               for s in per.values() if "p50_ms" in s]
        if lat:
            out["p50_ms"] = max(p for p, _ in lat)
            out["p99_ms"] = max(q for _, q in lat)
        return out

    # -- directory + anti-entropy ---------------------------------------
    def _tick_directory(self) -> None:
        """One directory round: stamp every live fleet's entry (epoch,
        wire port, park inventory — one ``park`` RPC each), refresh
        the locality router's park view, then run the tick's
        seed-deterministic anti-entropy exchanges."""
        with self._lock:
            handles = [h for h in self._fleets if h.alive]
            self._dir_tick += 1
            tick = self._dir_tick
        manifests: dict[str, dict] = {}
        for h in handles:
            try:
                manifests[h.name] = h.park()
            except (ConnectionError, OSError, RuntimeError):
                continue
            park = {e["signature"]: e.get("widths", [])
                    for e in manifests[h.name].get("entries", [])
                    if "signature" in e}
            self.directory.stamp(h.name, {"epoch": h.epoch,
                                          "port": h.port,
                                          "park": park})
        view = {n: {e["signature"]
                    for e in m.get("entries", []) if "signature" in e}
                for n, m in manifests.items()}
        with self._lock:
            self._park_view = view
        self._antientropy(tick, manifests,
                          {h.name: h for h in handles})

    def _antientropy(self, tick: int, manifests: dict[str, dict],
                     by_name: dict[str, FleetHandle]) -> None:
        """The warm-program gossip round: pair the live fleets by the
        seeded sampler and push each side the entries its partner has
        that it lacks (bounded per direction — the next tick
        continues).  Warming an already-warm signature is a no-op at
        the replica, so replay is free."""
        names = sorted(manifests)
        for a, b in gossip_pairs(names, seed=self.seed, tick=tick):
            for src, dst in ((a, b), (b, a)):
                have = {e["signature"]
                        for e in manifests[dst].get("entries", [])}
                missing = [e for e in manifests[src].get("entries", [])
                           if e.get("signature") not in have]
                missing = missing[:ANTIENTROPY_MAX_ENTRIES]
                if not missing:
                    continue
                try:
                    r = by_name[dst].warm({"schema": 1,
                                           "entries": missing})
                except (ConnectionError, OSError, RuntimeError):
                    continue
                with self._lock:
                    self._n_warm_exchanges += 1
                telemetry.counter_add("fed_warm_exchanges_total")
                telemetry.event("fleet_warm_exchange", src=src,
                                dst=dst, tick=tick,
                                entries=len(missing),
                                imported=int(r.get("imported", 0)),
                                traces=int(r.get("prewarm_traces", 0)))
                if self.log and r.get("imported"):
                    self.log(f"[fed] anti-entropy {src}→{dst}: "
                             f"{r['imported']} warm program(s), "
                             f"{r.get('prewarm_traces', 0)} trace(s)")

    # -- health + recovery ----------------------------------------------
    def _health_loop(self) -> None:
        while not self._stop.is_set():
            with self._lock:
                handles = list(self._fleets)
            for h in handles:
                with self._lock:
                    current = (self._fleets[h.index] is h
                               and (h.alive or h.joining))
                if not current:
                    continue
                detail = self._judge(h)
                if detail is not None:
                    self._on_death(h, detail)
            now = time.monotonic()
            if now - self._last_dir >= self.directory_s:
                self._last_dir = now
                self._tick_directory()
            self._stop.wait(self.poll_s)

    def _judge(self, h: FleetHandle) -> str | None:
        """None = healthy; else the death detail.  A joining fleet is
        promoted to live here (fleet-kind heartbeat up — which the
        router only stamps after ITS replicas joined — → connect)."""
        rc = h.proc.poll() if h.proc is not None else None
        if rc is not None:
            return f"process exited rc={rc} ({classify_exit(rc)})"
        hb = read_heartbeat(h.hb_path)
        now = time.time()
        if h.joining:
            if hb and hb.get("phase") == "run" and hb.get("port"):
                self._join(h, int(hb["port"]))
                return None
            if time.monotonic() - h.t_spawn > self.grace_s:
                return (f"no run heartbeat within grace "
                        f"{self.grace_s:g}s")
            return None
        age = (now - hb["mtime"]) if hb else float("inf")
        if age > self.health_s:
            return (f"heartbeat stale {age:.2f}s > federate_health_s="
                    f"{self.health_s:g} (hung — whole-fleet wedge)")
        return None

    def _join(self, h: FleetHandle, port: int) -> None:
        try:
            client = ServeClient("127.0.0.1", port,
                                 wire_format=self.cfg.wire_format,
                                 timeout=2.0, read_timeout=10.0,
                                 window=self.inner_window)
        except OSError:
            return                       # next poll retries
        with self._lock:
            h.port = port
            h.client = client
            h.alive = True
            h.joining = False
            live = sum(1 for x in self._fleets if x.alive)
        telemetry.gauge_set("fed_fleets_live", live)
        if self.log:
            self.log(f"[fed] fleet {h.name} epoch {h.epoch} joined on "
                     f"port {port}")

    def _fleet_pids(self, h: FleetHandle) -> list[int]:
        """Every pid in the fleet's blast radius: the fleet child
        itself plus its replica children, read from the heartbeat
        files under the fleet's run dir (replicas are their OWN
        sessions — reaping the fleet's group alone would leak them)."""
        pids = []
        if h.proc is not None:
            pids.append(h.proc.pid)
        try:
            names = sorted(os.listdir(h.run_dir))
        except OSError:
            names = []
        for fn in names:
            if not (fn.startswith("hb_") and fn.endswith(".json")):
                continue
            hb = read_heartbeat(os.path.join(h.run_dir, fn))
            pid = (hb or {}).get("pid")
            if pid:
                pids.append(int(pid))
        return pids

    def _kill_fleet_pids(self, h: FleetHandle,
                         *, cont_first: bool = True) -> list[int]:
        """SIGKILL the whole fleet's process groups (SIGCONT first
        unless this IS the chaos injection — a stopped process must
        not sleep through its own termination)."""
        pids = self._fleet_pids(h)
        sigs = ((signal.SIGCONT, signal.SIGKILL) if cont_first
                else (signal.SIGKILL,))
        for pid in pids:
            for sig in sigs:
                try:
                    os.killpg(pid, sig)
                except (ProcessLookupError, PermissionError, OSError):
                    try:
                        os.kill(pid, sig)
                    except (ProcessLookupError, OSError):
                        pass
        if h.proc is not None:
            try:
                h.proc.wait(timeout=10)
            except Exception:  # noqa: BLE001 — reaped later by the OS
                pass
        return pids

    def kill_fleet(self, name: str) -> list[int]:
        """CHAOS: SIGKILL every process of fleet ``name`` at once (the
        whole-fleet-loss injection).  Detection
        and recovery are the health loop's job — this only murders."""
        with self._lock:
            h = next(x for x in self._fleets if x.name == name)
        return self._kill_fleet_pids(h, cont_first=False)

    def _mark_dead(self, h: FleetHandle, detail: str) -> None:
        self._on_death(h, detail)

    def _salvaged_rows(self, h: FleetHandle) -> tuple[dict, int]:
        """The dead fleet's completed rows ``{fleet_rid: row}`` from
        its fleet-level salvage manifest, plus the manifest's stamped
        epoch (the ledger's fence input)."""
        try:
            with open(h.manifest_path()) as fp:
                manifest = json.load(fp)
        except (OSError, ValueError):
            return {}, h.epoch
        return ({int(k): v for k, v in
                 manifest.get("done", {}).items()},
                int(manifest.get("epoch", h.epoch)))

    def _on_death(self, h: FleetHandle, detail: str) -> None:
        t_detect = time.monotonic()
        hb = read_heartbeat(h.hb_path)
        with self._lock:
            if self._fleets[h.index] is not h:
                return                   # a later epoch took the slot
            if h.recovering:
                return                   # the other detector won
            h.recovering = True
            h.alive = False
            h.joining = False
            affected = [r for r in self._requests.values()
                        if r.fleet == h.name
                        and r.status == L_INFLIGHT]
            for sig in [s for s, i in self._affinity.items()
                        if i == h.index]:
                del self._affinity[sig]
            self._park_view.pop(h.name, None)
            self._n_deaths += 1
            self._last_death_ts = time.time()
            # detection latency: kill → the judge firing, measured by
            # the corpse's own last heartbeat stamp (same machine)
            self._detect_s = (time.time() - hb["mtime"]) if hb else None
            live = sum(1 for x in self._fleets if x.alive)
        if h.client is not None:
            h.client.close()
        self.directory.forget(h.name)
        self._kill_fleet_pids(h)
        telemetry.counter_add("fed_deaths_total")
        telemetry.gauge_set("fed_fleets_live", live)
        telemetry.event("fleet_death", fleet=h.name, epoch=h.epoch,
                        detail=detail[-300:], inflight=len(affected))
        if self.log:
            self.log(f"[fed] fleet {h.name} epoch {h.epoch} dead: "
                     f"{detail} — {len(affected)} in-flight "
                     f"request(s) to recover")
        # (1) adopt completed rows through the ledger's lattice join:
        # the manifest keys the FEDERATION's dispatch ids, the epoch
        # fence refuses a stale generation's manifest wholesale
        salvaged, m_epoch = self._salvaged_rows(h)
        translated = {}
        for req in affected:
            row = salvaged.get(req.fleet_rid)
            if row is not None:
                row = dict(row)
                row["request"] = req.rid
                row["fleet"] = h.name
                translated[req.rid] = row
        adopted, _dup, stale = self.ledger.merge(
            translated, fleet=h.name, epoch=m_epoch)
        if stale and self.log:
            self.log(f"[fed] refused stale salvage manifest from "
                     f"fleet {h.name} (epoch {m_epoch} < fence)")
        if adopted:
            with self._lock:
                for req in affected:
                    e = self.ledger.get(req.rid)
                    if (e and e["state"] == L_DONE
                            and req.status == L_INFLIGHT):
                        req.row = e["row"]
                        req.status = L_DONE
                self._n_adopted += adopted
            telemetry.counter_add("fed_adopted_total", adopted)
        # (2) re-admit the rest onto survivors (locality rule)
        redirected = 0
        for req in affected:
            with self._lock:
                if req.status != L_INFLIGHT:
                    continue
                req.fleet = None
                req.fleet_rid = None
                req.redirects += 1
            try:
                self._dispatch(req)
                redirected += 1
            except ServeReject as e:
                self._finish(req, {"request": req.rid,
                                   "error": f"recovery failed: "
                                            f"{e.reason}"},
                             failed=True)
        if redirected:
            with self._lock:
                self._n_redirects += redirected
            telemetry.counter_add("fed_redirects_total", redirected)
        mttr = time.monotonic() - t_detect
        with self._lock:
            self._mttr_s = mttr
        telemetry.gauge_set("fed_mttr_s", round(mttr, 3))
        if self.log:
            self.log(f"[fed] recovered: {adopted} adopted from "
                     f"salvage, {redirected} re-admitted, MTTR "
                     f"{mttr * 1e3:.0f} ms")
        # (3) relaunch the slot as epoch+1 with a FRESH run dir — the
        # ledger fence advances in _spawn, so the corpse's manifest is
        # unreadoptable from here on
        with self._lock:
            may_restart = (self.restart and not self._stop.is_set()
                           and self._n_restarts < self.max_restarts)
            if may_restart:
                self._n_restarts += 1
        if may_restart:
            nh = self._spawn(h.index, epoch=h.epoch + 1)
            with self._lock:
                if self._fleets[h.index] is h:
                    self._fleets[h.index] = nh
            telemetry.counter_add("fed_restarts_total")

    # -- drain / stop ----------------------------------------------------
    def drain(self, timeout: float | None = None) -> dict:
        """Stop accepting, wait for every ledger entry to complete
        (recovery included), drain the fleets, reap them; returns the
        final stats."""
        with self._lock:
            self._accepting = False
        deadline = (time.monotonic() + timeout) if timeout else None
        while True:
            with self._lock:
                pending = [r for r in self._requests.values()
                           if r.status == L_INFLIGHT]
            if not pending:
                break
            if deadline is not None and time.monotonic() > deadline:
                break
            for req in pending[:4]:
                try:
                    self.result(req.rid, timeout=5.0)
                except (TimeoutError, ServeReject, RuntimeError,
                        KeyError):
                    pass
        st = self.stats()
        self._stop.set()
        with self._lock:
            handles = list(self._fleets)
        for h in handles:
            if h.alive and h.client is not None:
                try:
                    h.drain()
                except (ConnectionError, OSError, RuntimeError):
                    pass
        for h in handles:
            self._kill_fleet_pids(h)
        if self._health_thread is not None:
            self._health_thread.join(timeout=5)
        return st

    def stop(self) -> None:
        """Immediate teardown (no drain): health loop off, every fleet
        (and every fleet's replicas) reaped — nothing outlives the
        federation."""
        self._stop.set()
        with self._lock:
            self._accepting = False
            handles = list(self._fleets)
        for h in handles:
            self._kill_fleet_pids(h)
        if self._health_thread is not None:
            self._health_thread.join(timeout=5)
