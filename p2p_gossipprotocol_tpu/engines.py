"""The ONE engine-selection table.

A :class:`NetworkConfig` names an engine family (``engine=``), a model
(``mode=``), and a device layout (``mesh_devices=`` / ``msg_shards=``);
this module resolves that tuple to a simulator instance.  Both API
surfaces — the CLI (``--engine/--mesh-devices/--msg-shards`` override
the config keys) and the reference-parity facade ``wrapper.Peer``
(config keys only, wrapper.hpp:7-19 parity) — build through here, so a
config FILE alone can select every engine in the repo and the two
surfaces cannot drift.

Engines (all return the shared SimResult / SIRResult; the fleet engine
returns a fleet.SweepResult of per-scenario SimResults):

=========  =====  ============  ==========  ================================
engine     mode   mesh_devices  msg_shards  simulator
=========  =====  ============  ==========  ================================
edges      gossip 0/1           —           sim.Simulator
edges      gossip N             —           parallel.ShardedSimulator
edges      sir    0/1           —           sim.SIRSimulator
aligned    gossip 0/1           —           aligned.AlignedSimulator
aligned    gossip N             0/1         parallel.AlignedShardedSimulator
aligned    gossip N             M | N       parallel.Aligned2DShardedSimulator
aligned    sir    0/1           —           aligned_sir.AlignedSIRSimulator
aligned    sir    N             —           parallel.AlignedShardedSIRSimulator
realgraph  gossip 0/1           —           realgraph.RealGraphSimulator
                                            (ingested edge-list graphs via
                                            graph_file=; bitwise == edges
                                            on the same topology)
realgraph  sir    0/1           —           sim.SIRSimulator over the
                                            ingested topology
fleet      gossip 0/1           —           fleet.FleetSweep (batched
                                            multi-scenario serving; needs a
                                            sweep spec — sweep_file= or the
                                            CLI's --sweep)
=========  =====  ============  ==========  ================================

Raises ``ValueError`` for unsupported combinations; callers surface it
their way (the CLI prints to stderr and exits 1, the facade propagates).
"""

from __future__ import annotations


def config_keys(cfg, n_peers: int | None = None) -> dict:
    """The trajectory-determining config identity — the dict behind a
    checkpoint's config fingerprint (utils.checkpoint.config_fingerprint),
    built HERE because engines.build_simulator is the one table both the
    CLI and wrapper.Peer share, so the two surfaces fingerprint runs
    identically.

    Deliberately EXCLUDED: the device-layout keys (``mesh_devices``,
    ``msg_shards``) — migrating a checkpoint across layouts is the
    elastic-resume contract, and the bitwise sharded-vs-unsharded parity
    tests (docs/PARITY.md) guarantee the trajectory doesn't depend on
    them — ``fuse_update``, whose in-kernel update/census path is
    bitwise-parity-tested against the XLA path (test_fuse_update.py) —
    and the ``frontier_*`` keys, whose sparse execution path is
    bitwise-identical to the dense one by seen-set monotonicity
    (tests/test_frontier.py), so a checkpoint migrates freely between
    frontier-sparse and dense readers.  The round-10 schedule keys
    (``prefetch_depth``, ``overlap_mode``, ``sir_fuse``) are excluded
    on the same bitwise-identity grounds (tests/test_prefetch.py,
    test_overlap.py, test_sir_fuse.py): they pick HOW the same blocks
    move, never what the round computes.  The round-11 ``hier_*`` keys
    are excluded for the same reason plus the elastic-resume one: the
    two-tier exchange is pure routing (tests/test_hier.py pins hier ==
    flat bitwise), and a run must migrate between mesh factorizations
    — including hier -> flat — mid-flight.  The ``supervise_*`` keys are
    likewise excluded: supervision decides WHERE a run executes (how
    many worker processes, what deadlines), never its trajectory — a
    checkpoint written under supervision must resume unsupervised and
    vice versa, and a shrink-to-survivors recovery must not read as
    fingerprint drift (runtime/supervisor.py).  The ``telemetry_*``
    keys are excluded for the same reason: telemetry is observational
    by contract (zero device computation, bitwise-identical results on
    or off — tests/test_telemetry.py), so a checkpoint written with
    telemetry on must resume with it off and vice versa.  Everything
    that picks the overlay, the model, the randomness chain, or the
    fault schedule is included."""
    return {
        "n_peers": n_peers or cfg.n_peers or len(cfg.seed_nodes),
        "n_messages": cfg.n_messages or cfg.max_message_count,
        "engine": cfg.engine,
        "mode": cfg.mode,
        "graph": cfg.graph,
        "graph_backend": cfg.graph_backend,
        # realgraph: WHICH graph was ingested is trajectory-determining
        # (the artifact's own CRC fingerprint additionally guards the
        # content — realgraph.ingest.artifact_fingerprint); the pack
        # width / scatter knobs are deliberately absent, bitwise-safe
        # execution choices like the frontier_* family.
        "graph_file": cfg.graph_file,
        "realgraph_format": cfg.realgraph_format,
        "avg_degree": cfg.avg_degree,
        "ba_m": cfg.ba_m,
        "er_p": cfg.er_p,
        "powerlaw_alpha": cfg.powerlaw_alpha,
        "fanout": cfg.fanout,
        "churn_rate": cfg.churn_rate,
        "byzantine_fraction": cfg.byzantine_fraction,
        "max_missed_pings": cfg.max_missed_pings,
        "message_stagger": cfg.message_stagger,
        "prng_seed": cfg.prng_seed,
        "ping_interval": cfg.ping_interval_secs,
        "message_interval": cfg.message_interval_secs,
        "sir_beta": cfg.sir_beta,
        "sir_gamma": cfg.sir_gamma,
        "roll_groups": cfg.roll_groups,
        "block_perm": cfg.block_perm,
        "pull_window": cfg.pull_window,
        "fault_link_drop": cfg.fault_link_drop,
        "fault_delay": cfg.fault_delay,
        "fault_byzantine": cfg.fault_byzantine,
        "fault_partition": cfg.fault_partition,
        "fault_partition_groups": cfg.fault_partition_groups,
        "fault_crash": cfg.fault_crash,
        "fault_recover": cfg.fault_recover,
        "fault_seed": cfg.fault_seed,
    }


def build_simulator(cfg, *, n_peers: int | None = None,
                    mesh_devices: int | None = None,
                    msg_shards: int | None = None,
                    clamps: list[str] | None = None):
    """Resolve ``cfg`` to ``(simulator, engine_name)``.

    ``mesh_devices`` / ``msg_shards`` default to the config keys; the
    CLI passes its flag-resolved values.  ``clamps`` (aligned engines
    only) collects any configured value the engine had to reduce —
    surfaced by every caller, never silent.

    This wrapper is also THE clamp-ledger chokepoint: every clamp any
    engine records while resolving (auto-select degrades, frontier/
    hier/overlap illegal combos, engine ceilings) emits exactly one
    typed ``clamp`` event through the telemetry ledger (telemetry.record_clamps), whether or not the
    caller passed its own ``clamps`` list — one queryable stream
    instead of N scattered strings.  It also installs the telemetry
    trace mirror (:func:`device.install_trace_mirror`), so the build
    and every later span appear in a ``jax.profiler`` trace.
    """
    from p2p_gossipprotocol_tpu import telemetry
    from p2p_gossipprotocol_tpu.device import install_trace_mirror

    install_trace_mirror()
    clamps = [] if clamps is None else clamps
    n0 = len(clamps)
    try:
        with telemetry.span("build_simulator"):
            return _build_simulator(cfg, n_peers=n_peers,
                                    mesh_devices=mesh_devices,
                                    msg_shards=msg_shards, clamps=clamps)
    finally:
        telemetry.record_clamps(clamps[n0:], scope="build_simulator")


def _build_simulator(cfg, *, n_peers, mesh_devices, msg_shards, clamps):
    mesh_devices = (cfg.mesh_devices if mesh_devices is None
                    else mesh_devices)
    msg_shards = cfg.msg_shards if msg_shards is None else msg_shards
    n_shards = max(1, mesh_devices)

    if n_shards > 1:
        # Fail fast BEFORE topology construction — building a 10M-peer
        # overlay only to learn the mesh doesn't exist wastes tens of
        # seconds and GBs of host RAM (applies to the facade and the
        # CLI alike).
        import jax

        have = len(jax.devices())
        if n_shards > have:
            raise ValueError(
                f"requested {n_shards} devices, have {have}")

    if cfg.engine == "fleet":
        # Batched multi-scenario serving on ONE chip — a sweep of
        # NetworkConfig-expressible scenarios bucketed by program
        # signature and vmapped over the scenario axis
        # (fleet/engine.py).  Single-device by design: the scenario
        # axis IS the batching dimension; sharding one scenario's peers
        # across a mesh is the aligned-sharded engines' job.
        if n_shards > 1 or msg_shards > 1:
            raise ValueError(
                "engine=fleet serves many scenarios on one device — "
                "mesh_devices/msg_shards don't apply (use "
                "engine=aligned for one sharded scenario)")
        from p2p_gossipprotocol_tpu.fleet import FleetSweep

        sim = FleetSweep.from_config(cfg, n_peers=n_peers, clamps=clamps)
        return sim, "fleet"

    if msg_shards > 1:
        # same rule NetworkConfig._validate_config applies to the config
        # keys — re-checked here because the CLI flags bypass it
        if cfg.engine != "aligned" or n_shards <= 1 or cfg.mode == "sir":
            raise ValueError(
                "msg_shards needs engine=aligned, mesh_devices > 1, and "
                "a gossip mode (the 2-D mesh shards the bit-packed "
                "message planes)")
        if n_shards % msg_shards:
            raise ValueError(
                f"msg_shards ({msg_shards}) must divide mesh_devices "
                f"({n_shards})")

    if cfg.engine == "realgraph":
        # Ingested-graph engine (realgraph/): single-device by design
        # today — the pack tables ride the jit as closure constants;
        # the sharded seam (realgraph.pack.shard_partition + the PR
        # 5/14 frontier exchange) is documented, not built.
        if n_shards > 1 or msg_shards > 1:
            raise ValueError(
                "engine=realgraph is single-device (the sharded seam — "
                "realgraph.pack.shard_partition over the frontier "
                "delta exchange — is documented, not built); drop "
                "mesh_devices/msg_shards or use engine=aligned")
        if cfg.mode == "sir":
            from p2p_gossipprotocol_tpu.realgraph.engine import \
                sir_from_config

            return sir_from_config(cfg, n_peers=n_peers), "realgraph"
        from p2p_gossipprotocol_tpu.realgraph import RealGraphSimulator

        sim = RealGraphSimulator.from_config(cfg, n_peers=n_peers,
                                             clamps=clamps)
        return sim, "realgraph"

    if cfg.mode == "sir":
        if cfg.engine == "aligned":
            from p2p_gossipprotocol_tpu.aligned_sir import \
                AlignedSIRSimulator

            sim = AlignedSIRSimulator.from_config(
                cfg, n_peers=n_peers, n_shards=n_shards, clamps=clamps)
            if n_shards > 1:
                from p2p_gossipprotocol_tpu.parallel import (
                    AlignedShardedSIRSimulator, make_mesh)

                sim = AlignedShardedSIRSimulator(
                    mesh=make_mesh(n_shards), topo=sim.topo,
                    beta=sim.beta, gamma=sim.gamma, n_seeds=sim.n_seeds,
                    churn=sim.churn, sir_fuse=sim.sir_fuse,
                    prefetch_depth=sim.prefetch_depth, seed=sim.seed)
                return sim, f"aligned-sharded-{n_shards}"
            return sim, "aligned"
        if n_shards > 1:
            raise ValueError(
                "mesh_devices with the SIR model needs engine=aligned "
                "(the edges SIR engine is single-device)")
        from p2p_gossipprotocol_tpu.sim import SIRSimulator

        return SIRSimulator.from_config(cfg, n_peers=n_peers), "edges"

    if cfg.engine == "aligned":
        from p2p_gossipprotocol_tpu.aligned import AlignedSimulator

        # from_config owns every engine ceiling (overlay family, message
        # cap, byzantine junk budget, int8 strike range, VMEM row-block
        # budget)
        sim = AlignedSimulator.from_config(cfg, n_peers=n_peers,
                                           n_shards=n_shards,
                                           clamps=clamps)
        if n_shards <= 1:
            return sim, "aligned"
        # Same scenario over the mesh: from_config resolved every knob
        # (the schedule statics already concrete); lift them onto the
        # drop-in multi-chip simulator.
        lifted = dict(
            topo=sim.topo, n_msgs=sim.n_msgs, mode=sim.mode,
            fanout=sim.fanout, churn=sim.churn,
            byzantine_fraction=sim.byzantine_fraction,
            n_honest_msgs=sim.n_honest_msgs,
            max_strikes=sim.max_strikes,
            liveness_every=sim.liveness_every,
            message_stagger=sim.message_stagger,
            fuse_update=sim.fuse_update, pull_window=sim.pull_window,
            faults=sim.faults,
            frontier_mode=sim.frontier_mode,
            frontier_threshold=sim.frontier_threshold,
            frontier_algo=sim.frontier_algo,
            prefetch_depth=sim.prefetch_depth,
            overlap_mode=sim.overlap_mode,
            hier_mode=sim.hier_mode,
            seed=sim.seed)
        if msg_shards > 1:
            # 2-D mesh: message planes x peer rows (the SP analogue,
            # parallel/aligned_2d.py).  The hier factorization applies
            # to the PEER sub-axis, so it re-resolves against that
            # count (from_config resolved against the total — the
            # clamp rule is shared, illegal combos degrade to flat).
            from p2p_gossipprotocol_tpu.aligned import resolve_hier
            from p2p_gossipprotocol_tpu.parallel import (
                Aligned2DShardedSimulator, make_mesh_2d)

            peer_shards = n_shards // msg_shards
            hh, _hd = resolve_hier(cfg.hier_hosts, cfg.hier_devs,
                                   peer_shards, clamps)
            sim = Aligned2DShardedSimulator(
                mesh=make_mesh_2d(msg_shards, peer_shards, n_hosts=hh),
                **lifted)
            name = f"aligned-2d-{msg_shards}x{peer_shards}"
            return sim, (name + f"-hier{hh}" if hh else name)
        from p2p_gossipprotocol_tpu.parallel import (
            AlignedShardedSimulator, make_hier_mesh, make_mesh)

        # from_config resolved the hier_* factorization against this
        # shard count (illegal combos already clamped to flat); a
        # resolved hosts x devs builds the two-axis mesh whose routing
        # the engine reads off (parallel/mesh.py make_hier_mesh)
        if sim.hier_hosts > 1:
            mesh = make_hier_mesh(sim.hier_hosts, sim.hier_devs)
            sim = AlignedShardedSimulator(mesh=mesh, **lifted)
            return (sim, f"aligned-hier-{sim.n_hosts}x"
                    f"{sim.devs_per_host}")
        sim = AlignedShardedSimulator(mesh=make_mesh(n_shards), **lifted)
        return sim, f"aligned-sharded-{n_shards}"

    from p2p_gossipprotocol_tpu.sim import Simulator

    sim = Simulator.from_config(cfg, n_peers=n_peers)
    if n_shards > 1:
        from p2p_gossipprotocol_tpu.parallel import (ShardedSimulator,
                                                     make_mesh)

        sim = ShardedSimulator(
            topo=sim.topo, mesh=make_mesh(n_shards), n_msgs=sim.n_msgs,
            mode=sim.mode, fanout=sim.fanout, churn=sim.churn,
            byzantine_fraction=sim.byzantine_fraction,
            n_honest_msgs=sim.n_honest_msgs,
            max_strikes=sim.max_strikes,
            message_stagger=sim.message_stagger, faults=sim.faults,
            seed=sim.seed)
        return sim, f"edges-sharded-{n_shards}"
    return sim, "edges"
