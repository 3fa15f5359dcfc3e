"""The serving fleet's front door: signature-affinity routing over
supervised replicas, with zero-lost-request recovery.

PR 9's server is one process: a SIGKILL loses every in-flight request.
This module is the protocol's own robustness story — liveness-checked
membership, evict the dead, re-route through the survivors — applied to
the traffic-bearing tier:

* **The router speaks the existing wire protocol.**
  :class:`RouterService` exposes the same ``submit()/result()/stats()/
  drain()`` facade :class:`~p2p_gossipprotocol_tpu.serve.service
  .GossipService` does, so the unmodified :class:`~p2p_gossipprotocol_tpu
  .serve.server.ServeServer` fronts it and clients cannot tell a fleet
  from a single server (submit/result/stats/drain documents unchanged).

* **Signature-affinity routing.**  Every request resolves to its
  compiled-program identity — ``fleet/packer.bucket_signature``, THE
  routing key — and all requests sharing a signature stick to one
  replica, so the zero-recompile admission contract survives the hop:
  a replica only ever compiles one chunk program per signature family
  it owns (``trace_count`` per replica unchanged by routing, asserted
  in tests).  Resolution is cached by a canonical sketch of the
  non-per-scenario overrides, so the router pays one simulator build
  per scenario *family*, not per request.

* **One pipelined connection per replica (round 17).**  The inner hop
  used to open a fresh connection per forwarded result wait — pure
  overhead at high offered load.  Each replica handle now holds ONE
  ``serve_inflight``-windowed pipelined :class:`~p2p_gossipprotocol_tpu
  .serve.server.ServeClient`: submits and result polls from every
  waiter multiplex over it, matched by seq, completing out-of-order,
  so fleet deployments no longer serialize on the inner connection
  (``serve_pipeline=0`` restores the PR 13 shape for old replicas).

* **Replica supervision.**  Replicas are ordinary ``--serve`` CLI
  children (``runtime/supervisor.py``'s serve-replica kind: own
  process group, own checkpoint dir, own port) that stamp the
  supervisor's heartbeat files sub-second from a dedicated thread.
  The health loop detects death three ways: process exit
  (``classify_exit``), a refused/reset connection, and a stale
  heartbeat past ``serve_health_s`` (the SIGSTOP/wedge case — a
  stopped process cannot refresh a file).

* **Zero-lost, zero-duplicated recovery.**  The router's ledger is the
  authoritative request registry (router request ids are the dedup
  key).  On replica death it (1) reads the dead replica's serve
  checkpoint manifest — the PR 9 salvage artifact, refreshed
  periodically by the replica precisely so a SIGKILL leaves a recent
  one — and ADOPTS any completed rows without re-execution; (2)
  re-admits every remaining in-flight request onto a survivor chosen
  by the affinity rule (a redirect, counted); (3) records MTTR
  (detect → last re-admission accepted).  A re-admitted scenario
  restarts from round 0 on the survivor, and because served scenarios
  are deterministic and bitwise-identical to their solo runs (the PR 9
  contract), the recovered result equals the one the dead replica
  would have produced — zero lost, zero duplicated, bit-for-bit.

docs/ROBUSTNESS.md "The serving fleet" has the failure taxonomy and
the re-admission semantics.
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
                                                       serve_replica_argv,
                                                       spawn_serve_replica)
from p2p_gossipprotocol_tpu.serve.scheduler import (Scheduler, ServeReject,
                                                    ServeShed,
                                                    resolve_request)
from p2p_gossipprotocol_tpu.serve.server import ServeClient

#: router-side request lifecycle
INFLIGHT, R_DONE, R_FAILED = "inflight", "done", "failed"


@dataclass
class ReplicaHandle:
    """One fleet member: its process, heartbeat file, checkpoint dir,
    and control connection.  ``generation`` bumps on every relaunch —
    a fresh generation gets a fresh checkpoint dir, so a stale salvage
    manifest can never be adopted twice."""

    rank: int
    port: int
    hb_path: str
    ckpt_dir: str
    proc: object = None                  # subprocess.Popen
    client: ServeClient | None = None
    alive: bool = False
    joining: bool = True
    recovering: bool = False             # one recovery per corpse
    generation: int = 0
    t_spawn: float = 0.0
    #: serializes RPCs on the one shared socket when the client is the
    #: legacy single-RPC kind (serve_pipeline=0); a pipelined client
    #: multiplexes — its seq matching makes concurrent callers safe,
    #: so the lock is bypassed and result waits share this connection
    #: too (round 17: one pipelined connection per replica, no
    #: per-forwarded-RPC reconnects)
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

    def result(self, rrid: int, timeout: float) -> dict:
        """Poll one forwarded request's result over the SHARED
        pipelined connection (many waiters multiplex; replies match by
        seq and complete out-of-order)."""
        return self.client.result(rrid, timeout=timeout)

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


@dataclass
class RouterRequest:
    """One ledger entry — the router rid is the fleet-wide dedup key."""

    rid: int
    overrides: dict
    signature: tuple
    replica: int | None = None
    replica_rid: int | None = None
    status: str = INFLIGHT
    redirects: int = 0
    row: dict | None = None


class RouterService:
    """submit()/result()/stats()/drain() over a supervised replica
    fleet (see module docstring) — drop-in behind ``ServeServer``."""

    def __init__(self, cfg, n_peers: int | None = None, *,
                 replicas: int | None = None, run_dir: str | None = None,
                 health_s: float | None = None, grace_s: float = 180.0,
                 poll_s: float = 0.05, restart: bool = True,
                 max_restarts: int = 8, persist_every_s: float = 1.0,
                 replica_extra_args: tuple[str, ...] = (), log=None):
        import tempfile

        self.cfg = cfg
        self.n_peers = n_peers
        self.n_replicas = int(replicas or
                              getattr(cfg, "serve_replicas", 3) or 3)
        if self.n_replicas < 1:
            raise ValueError("a serving fleet needs >= 1 replica")
        self.run_dir = run_dir or tempfile.mkdtemp(prefix="gossip_fleet_")
        self.health_s = float(health_s if health_s is not None
                              else getattr(cfg, "serve_health_s", 1.0))
        self.grace_s = float(grace_s)
        self.poll_s = float(poll_s)
        self.restart = bool(restart)
        self.max_restarts = int(max_restarts)
        self.persist_every_s = float(persist_every_s)
        self.replica_extra_args = tuple(replica_extra_args)
        self.pad_peers = bool(getattr(cfg, "sweep_pad_peers", 1))
        # round 17: the router→replica hop rides ONE pipelined
        # connection per replica (serve_inflight in-flight RPCs,
        # seq-matched) instead of a per-forwarded-RPC connection —
        # serve_pipeline=0 restores the PR 13 per-request-connection
        # shape for old replicas
        self.inner_window = (int(getattr(cfg, "serve_inflight", 32))
                             if int(getattr(cfg, "serve_pipeline", 1))
                             else 0)
        self.log = log
        self._lock = threading.Lock()
        self._sig_lock = threading.Lock()
        self._sig_cache: dict[tuple, tuple] = {}
        self._replicas: list[ReplicaHandle] = []
        self._requests: dict[int, RouterRequest] = {}
        self._affinity: dict[tuple, int] = {}
        self._next_rid = 0
        self._accepting = True
        self._n_deaths = 0
        self._n_restarts = 0
        self._n_redirects = 0
        self._n_adopted = 0
        self._mttr_s: float | None = None
        self._last_death_ts: float | None = None
        self._stop = threading.Event()
        self._health_thread: threading.Thread | None = None
        # round 18 (federation member mode): the router itself becomes
        # a supervised child — it stamps a fleet-kind heartbeat and
        # refreshes a fleet-level salvage manifest (done rows keyed by
        # ROUTER rid — the id space the federation dispatched into),
        # the replica discipline lifted one level.  Armed by
        # configure_heartbeat; the epoch is the federation's fence
        # against adopting a dead generation's stale manifest.
        self.fleet_name = ""
        self.fleet_epoch = 0
        self.heartbeat_path: str | None = None
        self.heartbeat_port = 0
        self._last_hb = 0.0
        self._last_fleet_persist = 0.0

    # -- lifecycle ------------------------------------------------------
    def _spawn(self, rank: int, generation: int = 0) -> ReplicaHandle:
        from p2p_gossipprotocol_tpu.runtime.supervisor import _free_port

        tag = f"replica_{rank}" + (f"_g{generation}" if generation
                                   else "")
        h = ReplicaHandle(
            rank=rank, port=_free_port(),
            hb_path=os.path.join(self.run_dir, f"hb_{tag}.json"),
            ckpt_dir=os.path.join(self.run_dir, f"{tag}_ck"),
            generation=generation, t_spawn=time.monotonic())
        argv = serve_replica_argv(
            self.cfg.config_file_path, rank=rank, port=h.port,
            heartbeat_path=h.hb_path, checkpoint_dir=h.ckpt_dir,
            n_peers=self.n_peers, extra_args=self.replica_extra_args)
        h.proc = spawn_serve_replica(argv, run_dir=self.run_dir,
                                     rank=rank)
        if self.log:
            self.log(f"[router] spawned replica {rank} (gen "
                     f"{generation}) pid {h.proc.pid} port {h.port}")
        return h

    def start(self) -> "RouterService":
        if self._health_thread is not None:
            return self
        handles = [self._spawn(r) for r in range(self.n_replicas)]
        with self._lock:
            self._replicas = handles
        self._health_thread = threading.Thread(target=self._health_loop,
                                               daemon=True)
        self._health_thread.start()
        return self

    def wait_ready(self, min_live: int | None = None,
                   timeout: float = 180.0) -> int:
        """Block until ``min_live`` replicas (default: all) have joined
        — heartbeat up, control connection established.  Returns the
        live count; raises TimeoutError if the fleet never forms."""
        want = self.n_replicas if min_live is None else int(min_live)
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                live = sum(1 for h in self._replicas if h.alive)
            if live >= want:
                return live
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"only {live}/{want} replicas joined within "
                    f"{timeout:g}s (see {self.run_dir}/replica_*.err)")
            time.sleep(0.05)

    def configure_heartbeat(self, path: str, port: int, *,
                            fleet: str = "", epoch: int = 0) -> None:
        """Arm the fleet-kind heartbeat + manifest (round 18, call
        before start()): the health loop stamps ``path`` sub-second
        with the router's bound wire ``port`` and its federation
        identity (fleet name + epoch), and refreshes the fleet salvage
        manifest every ``persist_every_s`` — what the federation
        adopts completed rows from after a whole-fleet SIGKILL."""
        self.heartbeat_path = path
        self.heartbeat_port = int(port)
        self.fleet_name = str(fleet)
        self.fleet_epoch = int(epoch)

    def _stamp_heartbeat(self) -> None:
        from p2p_gossipprotocol_tpu.runtime.supervisor import (
            SERVE_FLEET_KIND, write_heartbeat)

        try:
            write_heartbeat(
                self.heartbeat_path, rank=0, phase="run",
                extra={"kind": SERVE_FLEET_KIND,
                       "port": self.heartbeat_port,
                       "fleet": self.fleet_name,
                       "epoch": self.fleet_epoch})
        except OSError:
            pass                   # a torn disk never kills routing

    def fleet_manifest_path(self) -> str:
        return os.path.join(self.run_dir, "fleet_manifest.json")

    def _persist_fleet_manifest(self) -> None:
        """The fleet-level salvage artifact: completed rows keyed by
        ROUTER rid (the federation's dispatch id space — replica
        manifests key by replica-local rids the federation cannot
        map), plus the in-flight rid list, stamped with this fleet's
        epoch so a relaunched generation's federation refuses the
        corpse's manifest (atomic write — the reader must never see a
        torn one)."""
        from p2p_gossipprotocol_tpu.utils.checkpoint import _write_atomic

        with self._lock:
            done = {str(r.rid): r.row
                    for r in self._requests.values()
                    if r.status == R_DONE and r.row is not None}
            inflight = [r.rid for r in self._requests.values()
                        if r.status == INFLIGHT]
        manifest = {"schema": 1, "kind": "serve-fleet",
                    "fleet": self.fleet_name,
                    "epoch": self.fleet_epoch,
                    "done": done, "inflight": inflight}
        try:
            _write_atomic(self.fleet_manifest_path(),
                          json.dumps(manifest, sort_keys=True))
        except OSError:
            pass

    # -- signature routing ---------------------------------------------
    def _signature_of(self, overrides: dict) -> tuple:
        """The request's compiled-program identity (``fleet/packer
        .bucket_signature``), with one resolution per scenario FAMILY:
        per-scenario array values (``prng_seed``) and the SLO fields
        never change the compiled program, so they are dropped from the
        cache sketch; ``n_peers`` is padded exactly the way the spec
        layer pads it, so off-grid peer counts share their family's
        entry.  Raises :class:`ServeReject` on an unresolvable
        scenario — the named rejection stays at the door."""
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
        sig = bucket_signature(spec.sim)
        with self._sig_lock:
            self._sig_cache[key] = sig
        return sig

    def _route(self, sig: tuple) -> ReplicaHandle:
        """Sticky signature affinity: the owner if it lives, else the
        live replica owning the fewest signatures (lowest rank breaks
        ties — deterministic, so a recovery layout is reproducible
        from the failure history alone, the ``shrink()`` rule)."""
        with self._lock:
            live = [h for h in self._replicas if h.alive]
            if not live:
                raise ServeReject(
                    "no live replicas (the fleet is forming or lost "
                    "all capacity — retry, or check the supervisor "
                    "log)")
            owner = self._affinity.get(sig)
            if owner is not None and self._replicas[owner].alive:
                return self._replicas[owner]
            counts = {h.rank: 0 for h in live}
            for s, r in self._affinity.items():
                if r in counts:
                    counts[r] += 1
            best = min(live, key=lambda h: (counts[h.rank], h.rank))
            self._affinity[sig] = best.rank
            return best

    # -- client surface -------------------------------------------------
    def submit(self, overrides: dict) -> int:
        """Enqueue one scenario onto the fleet; returns the ROUTER
        request id (the dedup key recovery preserves).  Raises
        :class:`ServeReject`/:class:`ServeShed` exactly as the single
        server would — including the replica's own rejection reasons,
        forwarded verbatim."""
        with self._lock:
            if not self._accepting:
                raise ServeReject("router is draining (no new work)")
        sig = self._signature_of(overrides)
        with self._lock:
            if not self._accepting:
                raise ServeReject("router is draining (no new work)")
            rid = self._next_rid
            self._next_rid += 1
            req = RouterRequest(rid=rid, overrides=dict(overrides),
                                signature=sig)
            self._requests[rid] = req
        try:
            self._dispatch(req)
        except ServeReject:
            with self._lock:
                req.status = R_FAILED
                del self._requests[rid]
            raise
        return rid

    def _dispatch(self, req: RouterRequest) -> None:
        """Forward ``req`` to its affinity replica; on a transport
        failure mark that replica dead (the health loop confirms and
        recovers the rest of its load) and retry on the survivors —
        bounded by the fleet size."""
        last: Exception | None = None
        for _attempt in range(self.n_replicas + 1):
            h = self._route(req.signature)
            try:
                rrid = h.submit(req.overrides)
            except ServeReject:
                raise                   # replica-side policy: forward
            except (ConnectionError, OSError) as e:
                last = e
                self._mark_dead(h, f"submit transport error: "
                                   f"{type(e).__name__}: {e}")
                continue
            with self._lock:
                req.replica = h.rank
                req.replica_rid = rrid
            telemetry.counter_add("router_dispatch_total")
            return
        raise ServeReject(f"no replica accepted the request "
                          f"({type(last).__name__ if last else 'n/a'})")

    def result(self, rid: int, timeout: float | None = None) -> dict:
        """Block until router request ``rid`` completes; returns its
        row (rewritten to the router rid, with its replica and
        redirect count).  A request whose replica dies mid-wait is
        re-admitted by recovery and this wait follows it to the
        survivor.  Raises KeyError / TimeoutError / ServeShed /
        RuntimeError like the single server.

        Round 17: the wait polls over the replica's ONE pipelined
        control connection — many concurrent waiters multiplex there,
        matched by seq, completing out-of-order — instead of opening a
        connection per waiting request (the pre-pipelining shape,
        still taken when ``serve_pipeline=0``)."""
        deadline = (time.monotonic() + timeout) if timeout else None
        conn: ServeClient | None = None
        conn_key: tuple | None = None
        try:
            while True:
                with self._lock:
                    if rid not in self._requests:
                        raise KeyError(f"unknown request id {rid}")
                    req = self._requests[rid]
                    status, row = req.status, req.row
                    rep, rrid = req.replica, req.replica_rid
                    h = (self._replicas[rep] if rep is not None
                         else None)
                    live = h is not None and h.alive
                    port = h.port if h is not None else None
                    gen = h.generation if h is not None else None
                if status == R_DONE:
                    return row
                if status == R_FAILED:
                    if row and row.get("shed"):
                        raise ServeShed(row.get("error",
                                                row["shed"]))
                    raise RuntimeError((row or {}).get(
                        "error", f"request {rid} failed"))
                if deadline is not None \
                        and time.monotonic() > deadline:
                    raise TimeoutError(f"request {rid} not done "
                                       f"within {timeout}s")
                if not live or rrid is None:
                    time.sleep(0.05)     # recovery is re-routing it
                    continue
                if h.pipelined:
                    poll = lambda: h.result(rrid, timeout=2.0)  # noqa: E731
                else:
                    # legacy replicas: one wire connection per waiting
                    # request, re-opened when recovery moves it
                    if conn is None or conn_key != (rep, gen):
                        if conn is not None:
                            conn.close()
                        try:
                            conn = ServeClient(
                                "127.0.0.1", port,
                                wire_format=self.cfg.wire_format,
                                timeout=2.0, read_timeout=10.0,
                                retries=0)
                            conn_key = (rep, gen)
                        except OSError:
                            conn = None
                            time.sleep(0.1)
                            continue
                    poll = lambda: conn.result(rrid, timeout=2.0)  # noqa: E731
                try:
                    raw = poll()
                except TimeoutError:
                    continue            # still pending — poll again
                except (ConnectionError, OSError):
                    conn = None         # replica died mid-wait
                    time.sleep(0.05)
                    continue
                except RuntimeError as e:
                    msg = str(e)
                    if "shed:" in msg:
                        self._finish(req, {"request": rid,
                                           "shed": msg,
                                           "error": msg},
                                     failed=True)
                        raise ServeShed(msg) from e
                    if "unknown request id" in msg:
                        # a relaunched generation numbers rids afresh;
                        # recovery re-dispatches — follow it
                        time.sleep(0.05)
                        continue
                    self._finish(req, {"request": rid, "error": msg},
                                 failed=True)
                    raise
                self._finish(req, raw)
                with self._lock:
                    return req.row
        finally:
            if conn is not None:
                conn.close()

    def _finish(self, req: RouterRequest, raw: dict,
                failed: bool = False) -> None:
        """Record a terminal row exactly once — the dedup point: a row
        adopted from a salvage manifest and one replayed by a survivor
        land here, and only the first wins (zero duplicated)."""
        with self._lock:
            if req.status != INFLIGHT:
                return
            row = dict(raw)
            row["request"] = req.rid
            if req.replica is not None:
                row["replica"] = req.replica
            if req.redirects:
                row["redirects"] = req.redirects
            req.row = row
            req.status = R_FAILED if failed else R_DONE

    def profile_capture(self, duration_s: float = 2.0, top_n: int = 20,
                        log_dir: str | None = None) -> dict:
        raise ServeReject(
            "the router fronts replicas and owns no device — send "
            "`profile` to a replica port directly (stats() lists them)")

    # -- stats ----------------------------------------------------------
    def stats(self) -> dict:
        """Router ledger + fleet health + per-replica /stats (fetched
        live, best-effort — a replica mid-death reports absent)."""
        with self._lock:
            reqs = list(self._requests.values())
            handles = list(self._replicas)
            out = {
                "fleet": True,
                "replicas": self.n_replicas,
                "replicas_live": sum(1 for h in handles if h.alive),
                "deaths": self._n_deaths,
                "restarts": self._n_restarts,
                "redirects": self._n_redirects,
                "adopted": self._n_adopted,
                "signatures": len(self._affinity),
            }
            if self._mttr_s is not None:
                out["mttr_s"] = round(self._mttr_s, 3)
            if self._last_death_ts is not None:
                out["last_death_ts"] = self._last_death_ts
        out["submitted"] = len(reqs)
        out["done"] = sum(1 for r in reqs if r.status == R_DONE)
        out["failed"] = sum(1 for r in reqs if r.status == R_FAILED)
        out["inflight"] = sum(1 for r in reqs if r.status == INFLIGHT)
        shed = sum(1 for r in reqs
                   if r.status == R_FAILED and (r.row or {}).get("shed"))
        if shed:
            out["shed"] = shed
        lat = []
        per = {}
        park: dict[str, list[int]] = {}
        for h in handles:
            if not h.alive:
                continue
            try:
                st = h.stats()
                st.pop("type", None)
                per[str(h.rank)] = {"port": h.port,
                                    "generation": h.generation, **st}
                if "p50_ms" in st:
                    lat.append((st.get("p50_ms"), st.get("p99_ms")))
                # round 18: the fleet's warm-park inventory — the
                # union of every live replica's signature → widths map
                # (what the federation's locality router reads)
                for s, ws in (st.get("park") or {}).items():
                    got = set(park.get(s, ()))
                    got.update(int(w) for w in ws)
                    park[s] = sorted(got)
            except (ConnectionError, OSError, RuntimeError):
                continue
        out["replica_stats"] = per
        out["park"] = park
        if lat:
            out["p50_ms"] = max(p for p, _ in lat)
            out["p99_ms"] = max(q for _, q in lat)
        return out

    # -- warm-program export/import (round 18) --------------------------
    def park_export(self) -> dict:
        """The FLEET's warm-program manifest: every live replica's
        export, deduplicated by signature (first replica wins — entries
        for the same family are interchangeable: same overrides, and
        the widths ride per-entry)."""
        entries, seen = [], set()
        with self._lock:
            handles = [h for h in self._replicas if h.alive]
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
        """Warm this fleet from a neighbor's manifest: each entry is
        routed to its signature's AFFINITY replica (the one its
        requests will stick to — warming any other replica would be
        compilation nobody admits against) and imported there."""
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

    # -- health + recovery ----------------------------------------------
    def _health_loop(self) -> None:
        while not self._stop.is_set():
            now = time.monotonic()
            if self.heartbeat_path and now - self._last_hb >= 0.2:
                self._last_hb = now
                self._stamp_heartbeat()
            if now - self._last_fleet_persist >= self.persist_every_s:
                self._last_fleet_persist = now
                self._persist_fleet_manifest()
            with self._lock:
                handles = list(self._replicas)
            for h in handles:
                with self._lock:
                    current = (self._replicas[h.rank] is h
                               and (h.alive or h.joining))
                if not current:
                    continue
                detail = self._judge(h)
                if detail is not None:
                    self._on_death(h, detail)
            self._stop.wait(self.poll_s)

    def _judge(self, h: ReplicaHandle) -> str | None:
        """None = healthy; else the death detail.  Joining replicas are
        promoted to live here (heartbeat up → connect)."""
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
            return (f"heartbeat stale {age:.2f}s > serve_health_s="
                    f"{self.health_s:g} (hung — SIGSTOP or wedge)")
        return None

    def _join(self, h: ReplicaHandle, port: int) -> None:
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
            live = sum(1 for x in self._replicas if x.alive)
        telemetry.gauge_set("router_replicas_live", live)
        if self.log:
            self.log(f"[router] replica {h.rank} (gen {h.generation}) "
                     f"joined on port {port}")

    def _kill_group(self, h: ReplicaHandle) -> None:
        """SIGCONT first (a SIGSTOPped replica must not sleep through
        its own termination), then SIGKILL the whole group — the
        supervisor's reap rule."""
        if h.proc is None:
            return
        for sig in (signal.SIGCONT, signal.SIGKILL):
            try:
                os.killpg(h.proc.pid, sig)
            except (ProcessLookupError, PermissionError, OSError):
                try:
                    h.proc.send_signal(sig)
                except (ProcessLookupError, OSError):
                    pass
        try:
            h.proc.wait(timeout=10)
        except Exception:  # noqa: BLE001 — reaped later by the OS
            pass

    def _mark_dead(self, h: ReplicaHandle, detail: str) -> None:
        """Fast-path death from a transport error — same recovery as
        the health loop's; the per-corpse ``recovering`` flag makes
        the two detections race-free (exactly one recovery runs)."""
        self._on_death(h, detail)

    def _salvaged_rows(self, h: ReplicaHandle) -> dict:
        """The dead replica's completed rows, from its serve checkpoint
        manifest (PR 9's salvage artifact — refreshed periodically, so
        even a SIGKILL leaves a recent one).  ``{replica_rid: row}``;
        empty when no intact manifest exists."""
        path = os.path.join(h.ckpt_dir, "serve_manifest.json")
        try:
            with open(path) as fp:
                manifest = json.load(fp)
        except (OSError, ValueError):
            return {}
        return {int(k): v for k, v in manifest.get("done", {}).items()}

    def _on_death(self, h: ReplicaHandle, detail: str) -> None:
        t_detect = time.monotonic()
        with self._lock:
            if self._replicas[h.rank] is not h:
                return                   # a later generation took over
            if h.recovering:
                return                   # the other detector won
            h.recovering = True
            h.alive = False
            h.joining = False
            affected = [r for r in self._requests.values()
                        if r.replica == h.rank and r.status == INFLIGHT]
            for sig in [s for s, r in self._affinity.items()
                        if r == h.rank]:
                del self._affinity[sig]
            self._n_deaths += 1
            self._last_death_ts = time.time()
            live = sum(1 for x in self._replicas if x.alive)
        if h.client is not None:
            h.client.close()
        self._kill_group(h)
        telemetry.counter_add("router_deaths_total")
        telemetry.gauge_set("router_replicas_live", live)
        telemetry.event("replica_death", rank=h.rank,
                        generation=h.generation, detail=detail[-300:],
                        inflight=len(affected))
        if self.log:
            self.log(f"[router] replica {h.rank} dead: {detail} — "
                     f"{len(affected)} in-flight request(s) to recover")
        # (1) adopt completed rows from the salvage manifest: work the
        # replica finished must not be re-executed (and CANNOT be
        # double-reported — _finish dedups on the router rid)
        salvaged = self._salvaged_rows(h)
        adopted = 0
        for req in affected:
            row = salvaged.get(req.replica_rid)
            if row is not None:
                self._finish(req, row)
                adopted += 1
        if adopted:
            with self._lock:
                self._n_adopted += adopted
            telemetry.counter_add("router_adopted_total", adopted)
        # (2) re-admit the rest onto survivors (redirects)
        redirected = 0
        for req in affected:
            with self._lock:
                if req.status != INFLIGHT:
                    continue
                req.replica = None
                req.replica_rid = None
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
            telemetry.counter_add("router_redirects_total", redirected)
        mttr = time.monotonic() - t_detect
        with self._lock:
            self._mttr_s = mttr
        telemetry.gauge_set("router_mttr_s", round(mttr, 3))
        if self.log:
            self.log(f"[router] recovered: {adopted} adopted from "
                     f"salvage, {redirected} re-admitted, MTTR "
                     f"{mttr * 1e3:.0f} ms")
        # (3) optionally relaunch a fresh generation into the slot —
        # capacity heals; its old in-flight work already moved, so the
        # newcomer starts EMPTY (resume would double-serve)
        with self._lock:
            may_restart = (self.restart and not self._stop.is_set()
                           and self._n_restarts < self.max_restarts)
            if may_restart:
                self._n_restarts += 1
        if may_restart:
            nh = self._spawn(h.rank, generation=h.generation + 1)
            with self._lock:
                if self._replicas[h.rank] is h:
                    self._replicas[h.rank] = nh
            telemetry.counter_add("router_restarts_total")

    # -- drain / stop ----------------------------------------------------
    def drain(self, timeout: float | None = None) -> dict:
        """Stop accepting, wait for every ledger entry to complete
        (recovery included), drain the replicas, reap them; returns
        the final stats."""
        with self._lock:
            self._accepting = False
        deadline = (time.monotonic() + timeout) if timeout else None
        while True:
            with self._lock:
                pending = [r for r in self._requests.values()
                           if r.status == INFLIGHT]
            if not pending:
                break
            if deadline is not None and time.monotonic() > deadline:
                break
            # results are pulled by result() callers; a drain with
            # unfetched work pulls them itself so replicas can retire
            for req in pending[:4]:
                try:
                    self.result(req.rid, timeout=5.0)
                except (TimeoutError, ServeReject, RuntimeError,
                        KeyError):
                    pass
        st = self.stats()
        self._stop.set()
        with self._lock:
            handles = list(self._replicas)
        for h in handles:
            if h.alive and h.client is not None:
                try:
                    h.drain()
                except (ConnectionError, OSError, RuntimeError):
                    pass
        for h in handles:
            self._kill_group(h)
        if self._health_thread is not None:
            self._health_thread.join(timeout=5)
        return st

    def stop(self) -> None:
        """Immediate teardown (no drain): health loop off, every
        replica group reaped — nothing outlives the router."""
        self._stop.set()
        with self._lock:
            self._accepting = False
            handles = list(self._replicas)
        for h in handles:
            self._kill_group(h)
        if self._health_thread is not None:
            self._health_thread.join(timeout=5)
