"""Config / flag system.

Parses the reference's ``network.txt`` format with identical rules
(reference: config.cpp:53-143):

* blank lines and ``#`` comments are skipped (config.cpp:64)
* ``key=value`` lines set tuning params (config.cpp:93-96)
* any other line must be ``ip:port`` — IPv4-validated via inet_pton
  (config.cpp:103-115, 145-148), port in 1..65535 (config.cpp:150-152)
* errors carry line numbers (config.cpp:66-70)
* at least one seed required; quorum ``n // 2 + 1`` (config.cpp:73-76)
* validation: positive params, no duplicate seeds (config.cpp:122-143)

Fixes over the reference, per SURVEY.md §2-C3:

* ``local_ip`` / ``local_port`` keys exist (the reference hard-codes
  192.168.99.96:5000 for every process, config.cpp:38-39 — a port-collision
  bug); defaults preserved for compat.
* All parsed params are actually plumbed to the runtime (the reference
  parses then ignores them, wrapper.cpp:10-14 vs peer.cpp:330,337,358,377).
* Simulation keys for the JAX backend (backend, graph model, scale, mode,
  churn, ...) — unknown keys are still silently ignored, matching the
  reference's lenient key handling (config.cpp:93-96 has no else-clause).
"""

from __future__ import annotations

import random
import socket
from dataclasses import dataclass


class ConfigError(Exception):
    """Mirrors NetworkConfig::ConfigException (config.hpp:20-23)."""

    def __init__(self, message: str):
        super().__init__("Configuration Error: " + message)
        self.message = message


@dataclass(frozen=True)
class NodeInfo:
    """A seed/peer address. Equality ignores nothing — (ip, port) identity
    (reference config.hpp:9-18)."""

    ip: str = ""
    port: int = 0

    def to_string(self) -> str:
        return f"{self.ip}:{self.port}"

    def __str__(self) -> str:
        return self.to_string()


def is_valid_ip(ip: str) -> bool:
    """IPv4 dotted-quad check, same acceptance set as inet_pton
    (config.cpp:145-148): no leading-zero octets, exactly 4 octets."""
    try:
        socket.inet_pton(socket.AF_INET, ip)
        return True
    except (OSError, ValueError):
        return False


def is_valid_port(port: int) -> bool:
    return 0 < port < 65536


def _stoi(value: str) -> int:
    """C++ std::stoi semantics: parse a leading integer, ignore trailing
    junk, raise on no leading digits. The reference relies on stoi for both
    params (config.cpp:93-96) and ports (config.cpp:108)."""
    s = value.strip()
    i = 0
    if i < len(s) and s[i] in "+-":
        i += 1
    j = i
    while j < len(s) and s[j] in "0123456789":
        j += 1
    if j == i:
        raise ValueError(f"stoi: no conversion: {value!r}")
    return int(s[:j])


# Tuning params the reference parses (config.cpp:93-96), with its defaults
# (config.cpp:31-39).
_REFERENCE_INT_KEYS = {
    "ping_interval": "ping_interval_secs",
    "message_interval": "message_interval_secs",
    "max_messages": "max_message_count",
    "max_missed_pings": "max_missed_pings",
}

# New keys for the TPU-native backend. All optional.
_SIM_INT_KEYS = {
    "local_port": "local_port",
    "n_peers": "n_peers",
    "n_messages": "n_messages",
    "avg_degree": "avg_degree",
    "ba_m": "ba_m",
    "fanout": "fanout",
    # aligned engine: distinct block rolls in the overlay (0 = one per
    # slot); small values let the kernels reuse resident y blocks
    # across slots (build_aligned docstring).
    "roll_groups": "roll_groups",
    # aligned engine: 1 = block-granular permutation overlay — perm∘roll
    # rides the kernels' index table, eliminating the per-pass
    # permute/mask prep entirely (build_aligned(block_perm=True));
    # -1 (default) = auto-select it when measured-best and legal.
    "block_perm": "block_perm",
    # aligned engine: 1 = fold the seen-update into the final gossip
    # pass (the kernel emits (new, seen') from its resident accumulator
    # — aligned.AlignedSimulator.fuse_update).
    "fuse_update": "fuse_update",
    # aligned engine: 1 = draw the pull contact from the first roll
    # group only; the pull pass then streams ONE seen-plane copy
    # (aligned.AlignedSimulator.pull_window; needs roll_groups).
    "pull_window": "pull_window",
    # aligned engine: frontier-sparse rounds — 1 = on (in-kernel dead
    # sender-block skipping + delta-compressed cross-chip exchange on
    # the sharded engines), 0 = off, -1 (default) = auto-select on the
    # compiled (non-interpret) path only.  Bitwise-identical to the
    # dense path by construction (docs/ARCHITECTURE.md "The frontier
    # seam").
    "frontier_mode": "frontier_mode",
    # aligned engine, sharded meshes: HOW the sparse delta exchange
    # executes — 1 = recursive-halving sparse allreduce (log2(M)
    # pairwise ppermute merges of the compacted tables; each chip
    # receives O(merged capacity x log M) bytes instead of the
    # gather's O(M x K)), 0 = the round-8 table all-gather, -1
    # (default) = auto (halving on the compiled path, gather under
    # interpret).  Bitwise-identical either way, regime trajectory
    # included (docs/PERFORMANCE.md "Round 16").
    "frontier_algo": "frontier_algo",
    # aligned engine: double-buffered DMA pipelining of the gossip
    # kernels' sender stream — 2 = the manual copy stream (block k+1
    # prefetches while k computes), 0 = the legacy BlockSpec pipeline,
    # -1 (default) = auto (on for the compiled path only, the
    # frontier_mode rule).  Bitwise-identical either way.
    "prefetch_depth": "prefetch_depth",
    # aligned engine, sharded meshes: hide the cross-chip exchange
    # behind the self-shard half of the push kernel — -1 auto / 0 / 1
    # (needs a block-perm overlay and a push pass; degrades recorded).
    "overlap_mode": "overlap_mode",
    # aligned engine, sharded meshes: two-tier hierarchical exchange
    # (round 11) — factorize the mesh_devices peer axis into
    # hier_hosts x hier_devs (hosts = slow DCN tier, devs = fast ICI
    # tier; hier_devs=0 derives devices/host when it divides).  The
    # engines then stage every gather DCN-then-ICI and run the
    # frontier delta exchange per tier, bitwise-identical to the flat
    # exchange.  A factorization that doesn't divide the mesh DEGRADES
    # to flat with a recorded clamp (aligned.resolve_hier — checked at
    # engine-selection time like the msg_shards cross-field rules,
    # since CLI flags can override the mesh after this file parses).
    # hier_mode: -1 auto (two-tier on the compiled path, off under
    # interpret — the frontier_mode rule), 0/1 force.
    "hier_hosts": "hier_hosts",
    "hier_devs": "hier_devs",
    "hier_mode": "hier_mode",
    # aligned SIR engine: fuse the infectious-neighbor pressure count
    # into the gossip kernel's stream (one stream instead of the
    # permute prep + solo count_pass pair) — -1 auto / 0 / 1.
    "sir_fuse": "sir_fuse",
    # realgraph engine: pack-width cap (power of two) for the degree-
    # bucketed SpMV blocks, and the gather/scatter delivery choice —
    # both -1 = AUTO (autostatics.py); both bitwise-safe (they pick
    # HOW the same boolean OR executes).
    "realgraph_pack_width": "realgraph_pack_width",
    "realgraph_scatter": "realgraph_scatter",
    "rounds": "rounds",
    "prng_seed": "prng_seed",
    # jax backend: rounds between successive message activations —
    # column m enters the network at round m*k, the cadence of the
    # reference's messageGenerationLoop (one message per
    # message_interval, peer.cpp:357-377; one round ≈ one interval, so
    # 1 is the faithful timeline).  0 = every rumor exists from round 0.
    "message_stagger": "message_stagger",
    # jax backend: shard the peer axis over an N-device mesh (0/1 =
    # single device) — the config-file twin of --mesh-devices, so a
    # deployment can reach the sharded engines without CLI flags.
    "mesh_devices": "mesh_devices",
    # with engine=aligned and mesh_devices=N: also shard the bit-packed
    # message planes, as an M x (N/M) (msgs x peers) 2-D mesh — the
    # config-file twin of --msg-shards.
    "msg_shards": "msg_shards",
    # Socket mode: seconds between anti-entropy pulls (0 = off, the
    # reference's behavior — its flood-once push loses every message
    # generated before a connection existed, peer.cpp:297-318).
    "anti_entropy_interval": "anti_entropy_interval",
    # Fault plane (faults.FaultPlan): peers per partition group (power
    # of two <= 128) and the plan's own PRNG seed.
    "fault_partition_groups": "fault_partition_groups",
    "fault_seed": "fault_seed",
    # jax backend: checkpoint the full simulation state every N rounds
    # (0 = off) into checkpoint_dir — the config-file twins of the
    # CLI's --checkpoint-every/--resume, so a deployment (and the
    # wrapper.Peer facade) gets elastic kill-and-resume without CLI
    # flags.  checkpoint_resume=1 continues from the directory's
    # checkpoint; the resumed run may use a DIFFERENT engine layout
    # (mesh_devices/msg_shards) than the writer — the checkpoint is
    # canonical (utils/checkpoint.py).
    "checkpoint_every": "checkpoint_every",
    "checkpoint_resume": "checkpoint_resume",
    # Fleet engine (engine=fleet; fleet/): widest scenario batch one
    # bucket may hold (larger signature groups split), and whether
    # scenario peer counts pad UP to the next power of two so
    # heterogeneous sweeps share static-shape buckets (recorded per
    # row as n_peers_requested vs n_peers — never silent).
    "sweep_max_batch": "sweep_max_batch",
    "sweep_pad_peers": "sweep_pad_peers",
    # Serving plane (serve/; jax backend): serve=1 runs a RESIDENT
    # continuous-batching server over the fleet engine — scenarios
    # arrive as sweep-line config dicts over the socket surface
    # (local_ip/local_port, wire_format) or the GossipService facade,
    # are admitted into hot buckets at round boundaries (slots freed by
    # convergence masking), and every result stays bitwise-identical
    # to the scenario's solo run.  CLI twin: --serve.
    "serve": "serve",
    "serve_slots": "serve_slots",
    "serve_queue_max": "serve_queue_max",
    "serve_max_buckets": "serve_max_buckets",
    "serve_chunk": "serve_chunk",
    "serve_rounds": "serve_rounds",
    # Serving fleet (serve/router.py; CLI --serve-fleet): replica
    # count behind the signature-affinity router, and whether
    # deadline-expired requests are SHED (typed reasons, never
    # executed) or only ordered (serve_deadline_shed=0 keeps the
    # earliest-deadline-first queue but executes everything).
    "serve_replicas": "serve_replicas",
    "serve_deadline_shed": "serve_deadline_shed",
    # Wire pipelining (round 17; serve/server.py): serve_pipeline=1
    # lets clients (the fleet router's inner hop, bench, load drivers)
    # multiplex many in-flight RPCs over one connection, matched by
    # seq correlation ids; serve_inflight bounds the per-connection
    # window.  The server always demultiplexes; these keys shape the
    # CLIENT half, so old single-RPC callers keep working either way.
    "serve_pipeline": "serve_pipeline",
    "serve_inflight": "serve_inflight",
    # Telemetry-driven autoscaling (round 17; serve/autoscale.py):
    # serve_autoscale=1 lets the serving loop consume the occupancy /
    # queue-depth gauges and resize bucket slot widths (power-of-two
    # grow/shrink between serve_autoscale_min and serve_autoscale_max,
    # live occupants migrated bitwise) and close idle buckets, with
    # serve_autoscale_hold ticks of hysteresis so it never flaps.
    "serve_autoscale": "serve_autoscale",
    "serve_autoscale_min": "serve_autoscale_min",
    "serve_autoscale_max": "serve_autoscale_max",
    "serve_autoscale_hold": "serve_autoscale_hold",
    # Serving federation (round 18; serve/federation.py; CLI
    # --federate): federate=1 runs the cross-fleet tier — F
    # independent --serve-fleet children (each the unmodified router +
    # replicas) behind ONE client-facing wire, with warm-program
    # locality routing over the fleet directory, whole-fleet-loss
    # recovery through the epoch-fenced ownership ledger, and
    # per-tenant weighted admission budgets (federate_admit_rps
    # capacity split by federate_tenants weights per federate_budget_s
    # window; 0 = fairness governor off).
    "federate": "federate",
    "federate_fleets": "federate_fleets",
    # Self-healing multi-process runs (runtime/supervisor.py; jax
    # backend, engine=aligned): supervise=1 launches the run as
    # supervise_workers worker processes under the health plane —
    # heartbeat files, per-round deadlines priced from the traffic
    # model, hung/dead worker detection, and deterministic
    # shrink-to-survivors recovery from the last elastic checkpoint.
    # CLI twin: --supervise.
    "supervise": "supervise",
    "supervise_workers": "supervise_workers",
    "supervise_devs_per_proc": "supervise_devs_per_proc",
    "supervise_max_failures": "supervise_max_failures",
    "supervise_min_workers": "supervise_min_workers",
    # Telemetry plane (telemetry/; docs/OBSERVABILITY.md): telemetry=1
    # turns on spans + counters + the live roofline (the typed event
    # ledger is always on — clamps and fallbacks must survive into any
    # post-mortem).  Observational by contract: zero device
    # computation, bitwise-identical results on or off, and the
    # telemetry_* keys are EXCLUDED from checkpoint fingerprints
    # (engines.config_keys) — telemetry watches a run, never steers
    # it.  telemetry_ring bounds the flight recorder's span/event
    # rings.  CLI twin: --telemetry; env twin: GOSSIP_TELEMETRY=1.
    "telemetry": "telemetry",
    "telemetry_ring": "telemetry_ring",
}
_SIM_FLOAT_KEYS = {
    "er_p": "er_p",
    "churn_rate": "churn_rate",
    "byzantine_fraction": "byzantine_fraction",
    "powerlaw_alpha": "powerlaw_alpha",
    "sir_beta": "sir_beta",
    "sir_gamma": "sir_gamma",
    # Fault plane probabilities, all in [0, 1): per-round per-link drop,
    # per-round per-peer relay delay, wire-level duplication (socket
    # backend), and the unified entry to the byzantine machinery.
    "fault_link_drop": "fault_link_drop",
    "fault_delay": "fault_delay",
    "fault_duplicate": "fault_duplicate",
    "fault_byzantine": "fault_byzantine",
    # Fleet engine: coverage target for convergence masking + bucket
    # early-exit (0 = run every scenario the full fixed round count).
    "sweep_target": "sweep_target",
    # Serving plane: the convergence target that RETIRES a served
    # scenario (frees its slot); must be in (0, 1) — a server without
    # a retirement rule would hold slots forever.
    "serve_target": "serve_target",
    # SLO admission (serve/scheduler.py): the default admission-to-
    # result budget (ms) stamped on requests that carry no
    # deadline_ms of their own (0 = no default — only requests that
    # ask for a deadline get one).
    "serve_deadline_ms": "serve_deadline_ms",
    # Serving fleet (serve/router.py): seconds of heartbeat staleness
    # after which the router declares a replica hung (the
    # SIGSTOP/wedge case; process death is caught in ~one poll).
    "serve_health_s": "serve_health_s",
    # aligned engine: frontier-sparse delta-exchange capacity as a
    # fraction of each shard's packed words — the sparse regime engages
    # when every shard's changed-word count fits (with hysteresis;
    # aligned.FRONTIER_THRESHOLD_DEFAULT has the derivation).
    "frontier_threshold": "frontier_threshold",
    # Supervision deadlines (seconds): grace covers launch→first run
    # heartbeat (backend init + first compile); deadline_s=0 derives
    # the per-chunk deadline from the worker's traffic model
    # (runtime.supervisor.chunk_deadline_s).
    "supervise_grace_s": "supervise_grace_s",
    "supervise_deadline_s": "supervise_deadline_s",
    # Serving federation (round 18; serve/federation.py): fleet-
    # heartbeat staleness for whole-fleet-wedge detection, plus the
    # tenant-fairness capacity (requests/s, 0 = governor off) and the
    # window on which per-tenant budgets refresh.
    "federate_health_s": "federate_health_s",
    "federate_admit_rps": "federate_admit_rps",
    "federate_budget_s": "federate_budget_s",
}
_SIM_STR_KEYS = {
    "local_ip": "local_ip",
    "backend": "backend",
    "graph": "graph",
    "graph_backend": "graph_backend",
    "mode": "mode",
    "wire_format": "wire_format",
    # jax backend: exact edge-list engine, or the hardware-aligned
    # pallas scale engine (1M+ peers) — reachable from the facade and
    # the CLI alike, so a reference-parity deployment can opt into the
    # scale path without leaving the config file.
    "engine": "engine",
    # Real-graph engine (engine=realgraph): path to an on-disk edge
    # list (whitespace/CSV/SNAP) or a prebuilt .csr artifact directory,
    # plus the parser to use (auto sniffs on the first chunk).
    "graph_file": "graph_file",
    "realgraph_format": "realgraph_format",
    # Fault plane schedules: partition windows "start:heal[+start:heal]"
    # and crash/recover schedules "round:fraction[+round:fraction]".
    "fault_partition": "fault_partition",
    "fault_crash": "fault_crash",
    "fault_recover": "fault_recover",
    # jax backend: where checkpoints live (required when
    # checkpoint_every/checkpoint_resume are set).
    "checkpoint_dir": "checkpoint_dir",
    # Fleet engine: the sweep spec (JSONL, one scenario of config-key
    # overrides per line — the config-file twin of --sweep) and where
    # the per-scenario results table lands.
    "sweep_file": "sweep_file",
    "sweep_results": "sweep_results",
    # Serving plane: where served-scenario rows append (concurrency-
    # safe O_APPEND writes — fleet.driver.append_rows).
    "serve_results": "serve_results",
    # Serving federation: per-tenant fairness weights as
    # "name=weight,name=weight" (empty = every tenant weighs 1; the
    # share of federate_admit_rps each tenant may spend per window).
    "federate_tenants": "federate_tenants",
    # Supervision spmd mode: distributed (one jax.distributed job over
    # the workers; auto is the same), or chief (the single-process-spmd
    # rehearsal: the lowest rank runs the whole sharded program).
    "supervise_spmd": "supervise_spmd",
    # Telemetry plane: where flight-recorder dumps land (crash, SIGTERM
    # salvage, on demand); empty = checkpoint_dir when one exists, else
    # no automatic dump destination.
    "telemetry_dump_dir": "telemetry_dump_dir",
}


class NetworkConfig:
    """Parsed network configuration (reference config.hpp:25-39)."""

    def __init__(self, config_path: str):
        self.config_file_path = config_path
        self.seed_nodes = []
        self.min_connection_count = 0
        self.ping_interval_secs = 13
        self.message_interval_secs = 5
        self.max_message_count = 10
        self.max_missed_pings = 3
        self.local_ip = "192.168.99.96"
        self.local_port = 5000
        self.backend = "jax"
        self.graph = "reference"
        self.graph_backend = "numpy"   # numpy | native (C++ builders)
        self.wire_format = "json"      # json (reference-compat) | framed
        self.mode = "push"
        self.engine = "edges"          # edges | aligned (jax backend)
        # Real-graph engine (engine=realgraph; realgraph/): ingest an
        # on-disk edge list (or a prebuilt .csr artifact directory)
        # instead of a synthetic graph model.  graph_file set +
        # engine=realgraph routes one gossip round through the
        # degree-bucketed masked-SpMV delivery, bitwise-identical to
        # engine=edges on the same topology (docs/PARITY.md).
        self.graph_file = ""             # edge list / artifact dir
        self.realgraph_format = "auto"   # auto | ws | csv | snap
        # SpMV pack width cap (power of two) and gather/scatter
        # delivery choice — both -1 = AUTO, each by its rule in
        # autostatics.py (both pick HOW the same boolean OR is
        # computed, so they are bitwise-safe).
        self.realgraph_pack_width = -1
        self.realgraph_scatter = -1
        self.n_peers = 0
        self.n_messages = 0
        self.avg_degree = 8
        self.ba_m = 4
        self.er_p = 0.0
        self.fanout = 0
        # Measured-best aligned-engine defaults (round-5 on-chip A/Bs,
        # docs/PERFORMANCE.md "Default path == measured-best path"):
        # grouped block rolls + windowed pull are ON by default —
        # -29.5% steady-state ms/round at 1M — and from_config falls
        # back to the classic pull path when a scenario can't support
        # the window (push-only mode, un-groupable overlays).
        # block_perm AUTO-selects (round 6): the fused overlay was
        # measured -43% ms/round at 1M x 256 and a wash at W=1, so
        # from_config picks it at wide message widths and keeps the
        # row-perm family narrow.  fuse_update stays opt-in (measured
        # negative pre-census; the A/B with the in-kernel census is
        # not measured on the chip — ROADMAP Queue 1 item 2).
        self.roll_groups = 4           # aligned engine; 0 = per-slot rolls
        # aligned engine: -1 = AUTO (the default — from_config selects
        # the fused block-perm overlay whenever it is measured-best and
        # legal: wide message sets, push/pushpull, >= 2 distinct rolls);
        # 0/1 force it off/on, with illegal combinations degraded and
        # recorded rather than errored (aligned.AlignedSimulator
        # .from_config).
        self.block_perm = -1
        self.fuse_update = 0           # aligned engine; 1 = in-kernel seen|new
        self.pull_window = 1           # aligned engine; 0 = classic pull
        # aligned engine: frontier-sparse rounds — -1 = AUTO (on for the
        # compiled TPU path, off under interpret, where the extra XLA
        # work inverts — the round-6 fused-path precedent), 0/1 force.
        # Exact by seen-set monotonicity, so forcing it on is always
        # SAFE, never a different trajectory.
        self.frontier_mode = -1
        # delta-exchange capacity per shard as a fraction of its packed
        # words (aligned.FRONTIER_THRESHOLD_DEFAULT = 1/64: the sparse
        # gather must be well under the dense plane transfer to pay for
        # its bitmap+scatter overhead; 2*K words of idx+val vs L words
        # dense -> a 1/64 cap bounds the sparse gather at ~3% of dense).
        # -1 (the default) = AUTO: the 1/64 rule (autostatics.py; any
        # explicit value in (0, 1] is honored).  The capacity is
        # bitwise-safe at any value (sparse == dense by seen-set
        # monotonicity).
        self.frontier_threshold = -1.0
        # HOW the sparse regime moves its delta tables cross-chip —
        # -1 = AUTO (the recursive-halving sparse allreduce on the
        # compiled path, the table gather under interpret — the
        # butterfly's sort/merge work inverts on CPU, the
        # frontier_mode rule), 0 = gather, 1 = halving.  A third way
        # to EXECUTE the same sparse regime: bitwise-identical state
        # AND metrics, so forcing either is always SAFE.
        self.frontier_algo = -1
        # Round-10 schedule knobs, all -1 = AUTO (engaged on the
        # compiled TPU path, off under interpret — the frontier_mode
        # rule; all three are bitwise-identical to the legacy schedule,
        # so forcing any of them on is always SAFE):
        # double-buffered DMA prefetch of the kernels' sender stream,
        # the self/remote split that hides the sharded exchange behind
        # compute, and the fused SIR pressure count.
        self.prefetch_depth = -1
        self.overlap_mode = -1
        self.sir_fuse = -1
        # Two-tier hierarchical exchange (round 11): hosts x devs
        # factorization of the sharded peer axis (0 = flat mesh).
        self.hier_hosts = 0
        self.hier_devs = 0
        self.hier_mode = -1
        self.rounds = 0
        self.message_stagger = 0       # 0 = all rumors at round 0
        self.mesh_devices = 0          # 0/1 = single device
        self.msg_shards = 0            # 0/1 = peer-axis sharding only
        self.churn_rate = 0.0
        self.byzantine_fraction = 0.0
        self.powerlaw_alpha = 2.5
        self.sir_beta = 0.3
        self.sir_gamma = 0.1
        self.prng_seed = 0
        self.anti_entropy_interval = 0   # socket mode; 0 = off
        # Fault plane (faults.FaultPlan; all off by default)
        self.fault_link_drop = 0.0
        self.fault_delay = 0.0
        self.fault_duplicate = 0.0
        self.fault_byzantine = 0.0
        self.fault_partition = ""        # "start:heal[+start:heal...]"
        self.fault_partition_groups = 2
        self.fault_crash = ""            # "round:frac[+round:frac...]"
        self.fault_recover = ""
        self.fault_seed = 0
        # Elastic checkpointing (utils/checkpoint.py; jax backend)
        self.checkpoint_every = 0        # rounds per checkpoint; 0 = off
        self.checkpoint_dir = ""
        self.checkpoint_resume = 0       # 1 = continue from checkpoint_dir
        # Fleet engine (engine=fleet): batched multi-scenario sweeps
        self.sweep_file = ""             # JSONL scenario spec (--sweep)
        self.sweep_results = ""          # per-scenario results table
        self.sweep_max_batch = 256       # widest bucket (overflow splits)
        self.sweep_pad_peers = 1         # pad n_peers to powers of two
        self.sweep_target = 0.0          # >0 = early-exit coverage target
        # Serving plane (serve/): resident continuous-batching server
        self.serve = 0                   # 1 = run as a resident server
        self.serve_slots = 8             # slots per resident bucket
        self.serve_queue_max = 64        # bounded admission queue
        self.serve_max_buckets = 4       # resident signature buckets
        # rounds per admission boundary; -1 (default) = AUTO, the
        # classic 8 (autostatics.py; explicit values >= 1 honored).
        # Chunking only paces admission: every served scenario is
        # bitwise its solo run at any chunk (tests/test_serve.py).
        self.serve_chunk = -1
        self.serve_rounds = 0            # per-scenario cap; 0 = rounds/64
        self.serve_target = 0.99         # retirement coverage target
        self.serve_results = ""          # served-rows JSONL (append)
        # Serving fleet (serve/router.py; --serve-fleet) + SLO admission
        self.serve_replicas = 3          # replicas behind the router
        self.serve_deadline_ms = 0.0     # default request deadline; 0=off
        self.serve_deadline_shed = 1     # shed expired requests (typed)
        self.serve_health_s = 1.0        # heartbeat-staleness deadline
        # Wire pipelining (round 17): client-side multiplexing over one
        # connection (the server always demultiplexes seq-carrying
        # documents; old single-RPC clients are unaffected)
        self.serve_pipeline = 1          # 1 = clients pipeline the wire
        self.serve_inflight = 32         # bounded in-flight RPC window
        # Telemetry-driven autoscaling (round 17): the serving loop
        # consumes the occupancy/queue-depth gauges and resizes bucket
        # slot widths / closes idle buckets, with hysteresis
        self.serve_autoscale = 0         # 1 = autoscale the fleet shape
        self.serve_autoscale_min = 1     # narrowest slot width
        self.serve_autoscale_max = 64    # widest slot width
        self.serve_autoscale_hold = 3    # shrink/close hysteresis ticks
        # Serving federation (round 18; serve/federation.py;
        # --federate): fleet-of-fleets routing + recovery + fairness
        self.federate = 0                # 1 = run the federation tier
        self.federate_fleets = 2         # member --serve-fleet count
        self.federate_health_s = 2.0     # fleet-heartbeat staleness
        self.federate_admit_rps = 0.0    # tenant capacity; 0 = off
        self.federate_budget_s = 1.0     # budget refresh window (s)
        self.federate_tenants = ""       # "name=weight,..." shares
        # Telemetry plane (telemetry/; docs/OBSERVABILITY.md)
        self.telemetry = 0               # 1 = spans+counters+roofline on
        self.telemetry_ring = 4096       # flight-recorder ring bound
        self.telemetry_dump_dir = ""     # dump destination ("" = ckpt dir)
        # Self-healing supervision (runtime/supervisor.py)
        self.supervise = 0               # 1 = run under the supervisor
        self.supervise_workers = 2       # worker processes in the job
        self.supervise_devs_per_proc = 4
        self.supervise_spmd = "auto"     # auto | distributed | chief
        self.supervise_grace_s = 180.0   # launch -> first run heartbeat
        self.supervise_deadline_s = 0.0  # 0 = derive from traffic model
        self.supervise_max_failures = 0  # 0 = workers - 1
        self.supervise_min_workers = 1
        self._load_config()
        self._validate_config()

    # -- getters kept for API parity with config.hpp:25-39 ----------------
    def get_seed_nodes(self) -> list[NodeInfo]:
        return self.seed_nodes

    def get_local_ip(self) -> str:
        return self.local_ip

    def get_local_port(self) -> int:
        return self.local_port

    def get_min_required_seeds(self) -> int:
        return self.min_connection_count

    def get_ping_interval(self) -> int:
        return self.ping_interval_secs

    def get_message_interval(self) -> int:
        return self.message_interval_secs

    def get_max_messages(self) -> int:
        return self.max_message_count

    def get_max_missed_pings(self) -> int:
        return self.max_missed_pings

    # -- parsing ----------------------------------------------------------
    def _load_config(self) -> None:
        try:
            with open(self.config_file_path, "r") as f:
                lines = f.readlines()
        except OSError:
            raise ConfigError(
                f"Unable to open config file: {self.config_file_path}"
            )

        for line_number, raw in enumerate(lines, start=1):
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            try:
                self._parse_line(line)
            except ConfigError as e:
                raise ConfigError(f"Error at line {line_number}: {e.message}")

        if not self.seed_nodes:
            raise ConfigError("No valid seed nodes found in configuration")
        self.min_connection_count = len(self.seed_nodes) // 2 + 1

    def _parse_line(self, line: str) -> None:
        if "=" in line:
            key, _, value = line.partition("=")
            key = key.strip()
            value = value.strip()
            if not key or not value:
                raise ConfigError("Invalid configuration format")
            if key in _REFERENCE_INT_KEYS or key in _SIM_INT_KEYS:
                attr = _REFERENCE_INT_KEYS.get(key) or _SIM_INT_KEYS[key]
                try:
                    setattr(self, attr, _stoi(value))
                except ValueError:
                    raise ConfigError(f"Invalid value for {key}: {value}")
            elif key in _SIM_FLOAT_KEYS:
                try:
                    setattr(self, _SIM_FLOAT_KEYS[key], float(value))
                except ValueError:
                    raise ConfigError(f"Invalid value for {key}: {value}")
            elif key in _SIM_STR_KEYS:
                setattr(self, _SIM_STR_KEYS[key], value)
            # unknown keys silently ignored (reference config.cpp:93-96)
        else:
            ip, sep, port_str = line.partition(":")
            if not sep:
                raise ConfigError("Invalid seed node format")
            ip = ip.strip()
            port_str = port_str.strip()
            if not is_valid_ip(ip):
                raise ConfigError(f"Invalid IP address: {ip}")
            try:
                port = _stoi(port_str)
            except ValueError:
                raise ConfigError(f"Invalid port format: {port_str}")
            if not is_valid_port(port):
                raise ConfigError(f"Invalid port number: {port_str}")
            self.seed_nodes.append(NodeInfo(ip, port))

    def _validate_config(self) -> None:
        # Mirrors config.cpp:122-143.
        if self.ping_interval_secs <= 0:
            raise ConfigError("Ping interval must be positive")
        if self.message_interval_secs <= 0:
            raise ConfigError("Message interval must be positive")
        if self.max_message_count <= 0:
            raise ConfigError("Maximum message count must be positive")
        if self.max_missed_pings <= 0:
            raise ConfigError("Maximum missed pings must be positive")

        for node in self.seed_nodes:
            if not is_valid_ip(node.ip) or not is_valid_port(node.port):
                raise ConfigError(
                    f"Invalid seed node configuration: {node.to_string()}"
                )

        if len(set(self.seed_nodes)) != len(self.seed_nodes):
            raise ConfigError("Duplicate seed nodes found in configuration")

        # New-key sanity (not in the reference; fail fast instead of at
        # graph-build or socket-bind time).
        if not is_valid_ip(self.local_ip):
            raise ConfigError(f"Invalid local_ip: {self.local_ip}")
        if not is_valid_port(self.local_port):
            raise ConfigError(f"Invalid local_port: {self.local_port}")
        for k in ("n_peers", "n_messages", "avg_degree", "ba_m", "fanout",
                  "roll_groups", "fuse_update", "pull_window",
                  "rounds", "prng_seed", "anti_entropy_interval",
                  "message_stagger", "mesh_devices", "msg_shards",
                  "checkpoint_every", "checkpoint_resume",
                  "sweep_max_batch", "sweep_pad_peers",
                  "supervise", "supervise_max_failures",
                  "supervise_grace_s", "supervise_deadline_s",
                  "serve", "serve_rounds", "telemetry"):
            if getattr(self, k) < 0:
                raise ConfigError(f"{k} must be non-negative")
        for k in ("serve_slots", "serve_queue_max", "serve_max_buckets",
                  "serve_inflight", "serve_autoscale_min",
                  "serve_autoscale_max", "serve_autoscale_hold",
                  "telemetry_ring"):
            if getattr(self, k) < 1:
                raise ConfigError(f"{k} must be >= 1")
        if self.serve_pipeline not in (0, 1):
            raise ConfigError(
                "serve_pipeline must be 0 (single-RPC clients) or 1 "
                "(clients multiplex a bounded serve_inflight window)")
        if self.serve_autoscale not in (0, 1):
            raise ConfigError(
                "serve_autoscale must be 0 (fixed serving shape) or 1 "
                "(telemetry-driven slot-width/bucket autoscaling)")
        if self.serve_autoscale_max < self.serve_autoscale_min:
            raise ConfigError(
                "serve_autoscale_max must be >= serve_autoscale_min "
                "(the slot-width band the autoscaler moves within)")
        if self.serve_chunk != -1 and self.serve_chunk < 1:
            raise ConfigError(
                "serve_chunk must be >= 1, or -1 (auto)")
        if not (0.0 < self.serve_target < 1.0):
            raise ConfigError(
                "serve_target must be in (0, 1) — a served scenario "
                "retires (frees its slot) at this coverage")
        if self.serve_replicas < 1:
            raise ConfigError(
                "serve_replicas must be >= 1 (the fleet router needs "
                "at least one replica to route to)")
        if self.serve_deadline_ms < 0:
            raise ConfigError(
                "serve_deadline_ms must be >= 0 (0 = no default "
                "deadline; per-request deadline_ms fields still apply)")
        if self.serve_deadline_shed not in (0, 1):
            raise ConfigError(
                "serve_deadline_shed must be 0 (order only) or 1 "
                "(shed expired requests with a typed reason)")
        if self.serve_health_s <= 0:
            raise ConfigError(
                "serve_health_s must be > 0 — the router needs a "
                "finite heartbeat-staleness deadline to detect a hung "
                "replica")
        if self.federate not in (0, 1):
            raise ConfigError(
                "federate must be 0 (single fleet / single server) or "
                "1 (the cross-fleet federation tier)")
        if self.federate_fleets < 1:
            raise ConfigError(
                "federate_fleets must be >= 1 (the federation needs "
                "at least one member fleet to route to)")
        if self.federate_health_s <= 0:
            raise ConfigError(
                "federate_health_s must be > 0 — the federation needs "
                "a finite heartbeat-staleness deadline to detect a "
                "hung fleet")
        if self.federate_admit_rps < 0:
            raise ConfigError(
                "federate_admit_rps must be >= 0 (0 = fairness "
                "governor off; > 0 = admission capacity split among "
                "tenants by weight)")
        if self.federate_budget_s <= 0:
            raise ConfigError(
                "federate_budget_s must be > 0 (the window on which "
                "per-tenant admission budgets refresh)")
        for part in str(self.federate_tenants or "").split(","):
            part = part.strip()
            if not part:
                continue
            name, eq, w = part.partition("=")
            try:
                ok = bool(name.strip()) and bool(eq) and float(w) > 0
            except ValueError:
                ok = False
            if not ok:
                raise ConfigError(
                    f"federate_tenants entry {part!r} must be "
                    "name=weight with weight > 0 (e.g. "
                    "\"alpha=3,beta=1\")")
        if self.supervise:
            if self.supervise_workers < 1 \
                    or self.supervise_devs_per_proc < 1:
                raise ConfigError(
                    "supervise_workers/supervise_devs_per_proc must "
                    "be >= 1")
            if self.supervise_min_workers < 1 \
                    or self.supervise_min_workers > self.supervise_workers:
                raise ConfigError(
                    "supervise_min_workers must be in "
                    "[1, supervise_workers]")
        if self.supervise_spmd not in ("auto", "distributed", "chief"):
            raise ConfigError(
                f"Unknown supervise_spmd: {self.supervise_spmd} "
                "(auto|distributed|chief)")
        if (self.checkpoint_every > 0 or self.checkpoint_resume) \
                and not self.checkpoint_dir:
            raise ConfigError(
                "checkpoint_every/checkpoint_resume need checkpoint_dir")
        if self.block_perm < -1:
            # -1 = auto-select (the default); 0/1 force off/on
            raise ConfigError("block_perm must be -1 (auto), 0, or 1")
        if self.frontier_mode not in (-1, 0, 1):
            raise ConfigError("frontier_mode must be -1 (auto), 0, or 1")
        if self.frontier_threshold != -1.0 and \
                not (0.0 < self.frontier_threshold <= 1.0):
            raise ConfigError(
                "frontier_threshold must be in (0, 1], or -1 (auto)")
        if self.frontier_algo not in (-1, 0, 1):
            raise ConfigError(
                "frontier_algo must be -1 (auto), 0 (gather), or 1 "
                "(recursive-halving sparse allreduce)")
        if self.prefetch_depth not in (-1, 0, 2):
            raise ConfigError(
                "prefetch_depth must be -1 (auto), 0 (pipelined), or 2 "
                "(double-buffered manual stream)")
        if self.overlap_mode not in (-1, 0, 1):
            raise ConfigError("overlap_mode must be -1 (auto), 0, or 1")
        if self.sir_fuse not in (-1, 0, 1):
            raise ConfigError("sir_fuse must be -1 (auto), 0, or 1")
        if self.hier_mode not in (-1, 0, 1):
            raise ConfigError("hier_mode must be -1 (auto), 0, or 1")
        if self.hier_hosts < 0 or self.hier_devs < 0:
            raise ConfigError("hier_hosts/hier_devs must be >= 0")
        # whether hier_hosts x hier_devs factorizes the mesh is checked
        # at engine-selection time (aligned.resolve_hier, a recorded
        # clamp-to-flat — never a crash): CLI flags may override
        # mesh_devices/msg_shards after this file parses, so the
        # factorization is only knowable there, the same reasoning as
        # the msg_shards cross-field rules below.
        # msg_shards/mesh_devices CROSS-field rules are deliberately not
        # checked here: CLI flags may override engine/mode/mesh after
        # load, so the combination is validated at engine-selection time
        # (engines.build_simulator), the one place both surfaces share.
        if self.backend not in ("jax", "socket"):
            raise ConfigError(f"Unknown backend: {self.backend}")
        if self.graph not in ("reference", "er", "ba", "powerlaw"):
            raise ConfigError(f"Unknown graph model: {self.graph}")
        if self.graph_backend not in ("numpy", "native"):
            raise ConfigError(
                f"Unknown graph_backend: {self.graph_backend}")
        if self.wire_format not in ("json", "framed"):
            raise ConfigError(f"Unknown wire_format: {self.wire_format}")
        if self.mode not in ("push", "pull", "pushpull", "sir"):
            raise ConfigError(f"Unknown gossip mode: {self.mode}")
        if self.engine not in ("edges", "aligned", "fleet", "realgraph"):
            raise ConfigError(f"Unknown engine: {self.engine}")
        if self.realgraph_format not in ("auto", "ws", "csv", "snap"):
            raise ConfigError(
                f"Unknown realgraph_format: {self.realgraph_format}")
        w = self.realgraph_pack_width
        if w != -1 and (w < 1 or w > 4096 or (w & (w - 1))):
            raise ConfigError(
                "realgraph_pack_width must be -1 (auto) or a power of "
                f"two in [1, 4096], got {w}")
        if self.realgraph_scatter not in (-1, 0, 1):
            raise ConfigError(
                "realgraph_scatter must be -1 (auto), 0, or 1")
        if not (0.0 <= self.sweep_target < 1.0):
            raise ConfigError("sweep_target must be in [0, 1)")
        for k in ("sir_beta", "sir_gamma"):
            if not (0.0 <= getattr(self, k) <= 1.0):
                raise ConfigError(f"{k} must be in [0, 1]")
        if not (0.0 <= self.churn_rate < 1.0):
            raise ConfigError("churn_rate must be in [0, 1)")
        if not (0.0 <= self.byzantine_fraction < 1.0):
            raise ConfigError("byzantine_fraction must be in [0, 1)")
        # Fault-plane keys: one validation path with the CLI's
        # --fault-plan spec (faults.FaultPlan.validate), surfaced as
        # ConfigError like every other key.
        from p2p_gossipprotocol_tpu import faults as faults_lib

        try:
            faults_lib.plan_from_config(self)
        except ValueError as e:
            raise ConfigError(str(e))

    # -- helpers ----------------------------------------------------------
    def get_random_seeds(self, count: int, rng: random.Random | None = None
                         ) -> list[NodeInfo]:
        """Shuffled seed subset (reference config.cpp:154-165)."""
        if count > len(self.seed_nodes):
            raise ConfigError("Requested more seeds than available")
        result = list(self.seed_nodes)
        (rng or random).shuffle(result)
        return result[:count]

    def to_string(self) -> str:
        """Mirrors config.cpp:167-182 (printed by main.cpp:48)."""
        out = ["Network Configuration:", "----------------------",
               f"Seed Nodes ({len(self.seed_nodes)}):"]
        out += [f" {n.to_string()}" for n in self.seed_nodes]
        out += [
            f"Minimum Required Seeds: {self.min_connection_count}",
            "Network Parameters:",
            f" Ping Interval: {self.ping_interval_secs} seconds",
            f" Message Interval: {self.message_interval_secs} seconds",
            f" Max Messages: {self.max_message_count}",
            f" Max Missed Pings: {self.max_missed_pings}",
        ]
        return "\n".join(out) + "\n"

    def __str__(self) -> str:
        return self.to_string()
