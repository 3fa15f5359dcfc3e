"""The contract tables the rules check the tree against.

Everything here is a RESTATEMENT of a discipline some PR established in
code + docs — each table names its origin so a failing check points at
the contract, not just the pattern.  When a rule fires because a table
is out of date (a new knob, a new plane), updating the table IS the
review moment the rule exists to force: the author must classify the
new key/static one way or the other, in this file, in the same PR.
"""

from __future__ import annotations

# ---------------------------------------------------------------------
# Rule: fingerprint-exclusion (PR 3 established config fingerprints;
# PRs 5/7/8 excluded the how-not-what knobs; PR 6 supervision; PR 10
# telemetry — engines.config_keys docstring is the prose form).
#
# Every config-file key (config.py's key maps) must be either part of
# the trajectory identity (referenced by engines.config_keys) or
# listed here with the category that justifies its exclusion.  A key in
# neither place is an unclassified-config-key finding.

#: exact attr names / ``*``-suffix patterns -> exclusion category
FINGERPRINT_EXEMPT = {
    # device layout: elastic resume migrates checkpoints across layouts
    # (PR 3's cross-mesh contract; docs/PARITY.md)
    "mesh_devices": "layout",
    "msg_shards": "layout",
    # how-not-what knobs: bitwise-identical on or off by parity test
    # (fuse_update PR 2, frontier_* PR 5 — the pattern also covers
    # PR 16's frontier_algo, a third execution of the same sparse
    # regime — prefetch/overlap/sir_fuse PR 7, hier_* PR 8)
    "fuse_update": "bitwise-knob",
    "frontier_*": "bitwise-knob",
    "prefetch_depth": "bitwise-knob",
    "overlap_mode": "bitwise-knob",
    "sir_fuse": "bitwise-knob",
    "hier_*": "bitwise-knob",
    # realgraph (PR 19): pack width and gather/scatter pick HOW the
    # same masked boolean OR executes — bitwise-identical either way
    # (tests/test_realgraph.py pins realgraph == edges across both);
    # graph_file/realgraph_format ARE fingerprinted (which graph was
    # ingested is the trajectory)
    "realgraph_pack_width": "bitwise-knob",
    "realgraph_scatter": "bitwise-knob",
    # planes that watch or place a run, never steer it (supervise_*
    # PR 6, telemetry_* PR 10, serve_*/sweep_* PR 4/9 — the serving
    # and sweep surfaces wrap scenarios whose own keys ARE
    # fingerprinted per scenario; the round-17 wire/autoscale keys —
    # serve_pipeline/serve_inflight/serve_autoscale* — ride the
    # serve_* pattern DELIBERATELY: they shape how the plane moves
    # requests and resizes buckets, never a scenario's trajectory,
    # and they carry no -1-auto spelling, so they belong here and
    # not in AUTO_STATICS)
    "supervise": "plane",
    "supervise_*": "plane",
    "telemetry": "plane",
    "telemetry_*": "plane",
    "serve": "plane",
    "serve_*": "plane",
    "sweep_*": "plane",
    # the round-18 federation keys ride the same reasoning as serve_*:
    # they shape how the fleet-of-fleets tier routes, recovers, and
    # budgets tenants — never a scenario's trajectory (recovered and
    # re-routed results stay bitwise their solo runs by the PR 9
    # contract), and none carries a -1-auto spelling, so they belong
    # here and not in AUTO_STATICS
    "federate": "plane",
    "federate_*": "plane",
    # run-length / checkpoint mechanics: rounds is the runtime argument
    # (a checkpoint resumes into ANY remaining-rounds budget),
    # checkpoint_* is where/how-often state persists (PR 3)
    "rounds": "runtime",
    "checkpoint_every": "runtime",
    "checkpoint_dir": "runtime",
    "checkpoint_resume": "runtime",
    # socket/deployment surface: never reaches the jax trajectory
    # (local_* is this process's bind address; wire/backend choose the
    # transport; the reference timers only pace the socket loops;
    # fault_duplicate is wire-level duplication, socket backend only —
    # faults.py documents it has no jax-engine analogue)
    "local_ip": "socket",
    "local_port": "socket",
    "backend": "socket",
    "wire_format": "socket",
    "anti_entropy_interval": "socket",
    "fault_duplicate": "socket",
}

#: keys engines.config_keys reads via DIFFERENT attr spellings than the
#: config-file key (the reference's key->attr renames in config.py)
FINGERPRINT_ATTR_ALIASES = {
    "ping_interval_secs": "ping_interval",
    "message_interval_secs": "message_interval",
    "max_message_count": "max_messages",
}

# ---------------------------------------------------------------------
# Rule: packer-signature (PR 4 established the bucket signature; PRs
# 5/7/8 grew it with every resolved static that changes the compiled
# program — fleet/packer.bucket_signature's docstring is the contract).
#
# Underscore attributes AlignedSimulator resolves are statics by
# convention; each must appear in bucket_signature or be listed here
# with why it cannot change the single-device compiled program.
#
# Consumers of the signature beyond the packer: the serve scheduler's
# bucket routing (PR 9) and the fleet router's replica affinity
# (PR 13, serve/router.py — tests/test_serve_fleet.py pins that the
# router's cached signature IS bucket_signature, so a static this rule
# forces into the signature automatically re-routes across replicas
# too; a ghost static would break BOTH tiers, which is why the rule's
# scope stays the simulator, not each consumer).

PACKER_EXEMPT = {
    "_frontier_delta": (
        "the delta exchange is sharded-engines-only; on the fleet's "
        "single device only _frontier_skip (in the signature) enters "
        "the trace"),
    "_honest_mask": "derived from n_msgs/_n_honest, both in the signature",
    "_junk_mask": "derived from n_msgs/_n_honest, both in the signature",
    "_plan_cache": "host-side byzantine-plan cache, rebuilt per sim",
    "_init_key": "round-0 PRNG key drawn from sim.seed, an argument of "
                 "the init program, rebuilt per sim",
    "_run_cache": "jit cache, not a static",
    "_coverage_cache": "jit cache, not a static",
    "_scan_cache": "jit cache, not a static",
}

# ---------------------------------------------------------------------
# Rule: clamp-chokepoint (PR 10 unified every recorded-clamp site into
# the typed ledger through exactly two chokepoints).

#: functions allowed to call telemetry.record_clamps / emit "clamp"
#: events: (defining-symbol, function-name).  build_simulator wraps
#: every engine build; resolve_request is the serve admission path that
#: bypasses it; the recorder defines the primitive.
CLAMP_CHOKEPOINTS = {
    ("build_simulator", "build_simulator"),
    ("resolve_request", "resolve_request"),
    ("Recorder", "record_clamps"),
}

#: knob names whose silent conditional degradation the rule flags —
#: the resolved statics a from_config-style resolver may weaken
DEGRADE_KNOBS = {
    "block_perm", "pull_window", "fuse_update", "frontier_mode",
    "frontier_algo", "prefetch_depth", "overlap_mode", "sir_fuse",
    "hier_mode", "hier_hosts", "hier_devs", "mesh_devices",
    "msg_shards", "n_msgs", "n_messages", "roll_groups",
}

# ---------------------------------------------------------------------
# Rule: tracing-safety (the bitwise contract behind every engine: a
# host escape inside a traced function either crashes at trace time or
# — worse — bakes one host value into the compiled program).

#: wrappers whose function-valued arguments are traced entry points
TRACE_WRAPPERS = {
    "jax.jit", "jit", "pl.pallas_call", "pallas_call",
    "jax.shard_map", "shard_map",
    "jax.vmap", "vmap",
    "jax.lax.scan", "lax.scan", "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.cond", "lax.cond", "jax.lax.fori_loop", "lax.fori_loop",
    "jax.checkpoint", "jax.remat",
}

#: dotted-call prefixes that are host escapes inside a traced function
HOST_ESCAPE_CALLS = {
    "time.time": "wall-clock read",
    "time.perf_counter": "wall-clock read",
    "time.monotonic": "wall-clock read",
    "time.sleep": "host sleep",
    "random.": "host PRNG (stdlib random)",
    "np.random": "host PRNG (numpy)",
    "numpy.random": "host PRNG (numpy)",
    "os.urandom": "host entropy",
    "uuid.": "host entropy",
    "jax.device_get": "device sync",
    "open": "host I/O",
}

#: method names that force a tracer onto the host
HOST_ESCAPE_METHODS = {"item": "tracer -> host scalar"}

# ---------------------------------------------------------------------
# Rule: write-discipline (PR 3 tmp+rename, PR 9/10 O_APPEND rows —
# docs/ROBUSTNESS.md torn-write rules).

#: files whose open() calls ARE the blessed helpers
WRITE_HELPER_FILES = ("utils/checkpoint.py", "utils/logging.py")

# ---------------------------------------------------------------------
# Rule: telemetry-imports (PR 10: zero device computation — the
# telemetry package never imports jax, so it can never add device work
# or perturb compilation; tests/test_telemetry.py holds the bitwise
# side of the same contract).

TELEMETRY_PKG = "p2p_gossipprotocol_tpu/telemetry/"
TELEMETRY_BANNED_IMPORTS = ("jax",)

# ---------------------------------------------------------------------
# Rule: auto-statics (every -1-auto performance static is decided by
# one heuristic_* rule in autostatics.py, called from one place per
# engine.  An auto-sentinel test (``X == -1`` / ``X < 0``) on a known
# auto static anywhere else is a second, drifting rule for it.
# Validation guards — membership tests like ``not in (-1, 0, 1)`` and
# raise-only branches — are not resolution and stay exempt).

#: the one module allowed to test the auto sentinels (package-relative,
#: so the fixture trees can mimic it)
AUTO_RULES_MODULE = "p2p_gossipprotocol_tpu/autostatics.py"

#: statics whose -1 spelling means "auto" — each decided by its
#: heuristic_* rule in AUTO_RULES_MODULE
AUTO_STATICS = {
    "block_perm", "frontier_mode", "frontier_threshold",
    "frontier_algo", "prefetch_depth", "overlap_mode", "hier_mode",
    "sir_fuse", "serve_chunk", "realgraph_pack_width",
    "realgraph_scatter",
}

# ---------------------------------------------------------------------
# Rule: config-drift (PR 1 onward: every key config.py validates is
# documented in network.txt and consumed by some engine/plane —
# "parsed then ignored" is the reference's bug this repo exists to not
# have, config.py module docstring).  The rule's tables are local to
# analysis/rules/configsurface.py (the doc-token ignore set).
