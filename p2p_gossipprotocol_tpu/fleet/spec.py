"""Scenario specs: one sweep line = one NetworkConfig-expressible run.

A sweep file is JSONL — one JSON object per scenario, each key a
``network.txt`` config key (the SAME tables ``config.py`` parses, so
anything a config file can say a sweep line can say: peers, fanout,
mode, churn, byzantine fraction, fault plan, seed, ...), applied as
overrides on top of the base config the CLI was launched with.  Unknown
keys are an ERROR here — the lenient file parser's silently-ignored
unknown keys are a reference-parity behavior; a sweep typo silently
running the wrong scenario is exactly the defect class SURVEY §2-C2
exists to prevent.

Each spec resolves to the exact solo
:class:`~p2p_gossipprotocol_tpu.aligned.AlignedSimulator` the CLI would
build for that scenario (``from_config`` — same ceilings, same clamps
machinery, never silent), which is what makes the fleet's
bitwise-parity contract meaningful: the batched run serves *these*
simulators, not approximations of them.

Peer-count padding: ``pad_peers`` (the ``sweep_pad_peers`` config key,
default on) rounds each scenario's peer count UP to the next power of
two, so heterogeneous sweeps land on shared padded row grids and
collapse into few buckets (the static-shape-bucket trick the fleet
exists for).  The padding is recorded on the spec and in every results
row (``n_peers_requested`` vs ``n_peers``) — a changed scenario is
surfaced, never silent — and parity is asserted against the padded
scenario, which is the one that actually ran.
"""

from __future__ import annotations

import copy
import json
from dataclasses import dataclass, field

from p2p_gossipprotocol_tpu.config import (_REFERENCE_INT_KEYS,
                                           _SIM_FLOAT_KEYS, _SIM_INT_KEYS,
                                           _SIM_STR_KEYS, ConfigError,
                                           NetworkConfig)

#: every config-file key a sweep line may override, mapped to its
#: NetworkConfig attribute (the one source of truth is config.py's
#: parse tables — re-used here so the two surfaces cannot drift).
_KEY_TABLES = (_REFERENCE_INT_KEYS, _SIM_INT_KEYS, _SIM_FLOAT_KEYS,
               _SIM_STR_KEYS)

#: keys that name things a *scenario* cannot choose (the sweep itself,
#: the device layout, checkpointing — driver-level concerns).
_RESERVED = {"engine", "mesh_devices", "msg_shards", "sweep_file",
             "sweep_results", "sweep_max_batch", "sweep_pad_peers",
             "sweep_target", "checkpoint_every", "checkpoint_dir",
             "checkpoint_resume", "backend", "local_ip", "local_port",
             # serving plane: how the SERVER runs, never what one
             # scenario simulates (serve/scheduler.py resolves request
             # dicts through this same table; the per-REQUEST SLO
             # fields deadline_ms/priority are stripped before
             # resolution — scheduler.SLO_KEYS — so they never land
             # here)
             "serve", "serve_slots", "serve_queue_max",
             "serve_max_buckets", "serve_chunk", "serve_rounds",
             "serve_target", "serve_results", "serve_replicas",
             "serve_deadline_ms", "serve_deadline_shed",
             "serve_health_s", "serve_pipeline", "serve_inflight",
             "serve_autoscale", "serve_autoscale_min",
             "serve_autoscale_max", "serve_autoscale_hold",
             # telemetry watches the PROCESS, never one scenario
             "telemetry", "telemetry_ring", "telemetry_dump_dir"}


def _attr_for(key: str) -> str | None:
    for table in _KEY_TABLES:
        if key in table:
            return table[key]
    return None


def next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def parse_sweep_file(path: str) -> list[dict]:
    """Read a JSONL sweep file: one JSON object per line; blank lines
    and ``#`` comments skipped.  Errors carry line numbers, like the
    config parser's."""
    specs = []
    try:
        with open(path) as fp:
            lines = fp.readlines()
    except OSError as e:
        raise ConfigError(f"Unable to open sweep file: {path} ({e})")
    for ln, raw in enumerate(lines, start=1):
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        try:
            obj = json.loads(line)
        except ValueError as e:
            raise ConfigError(
                f"sweep file {path} line {ln}: not valid JSON ({e})")
        if not isinstance(obj, dict):
            raise ConfigError(
                f"sweep file {path} line {ln}: each line must be a "
                "JSON object of config-key overrides")
        specs.append(obj)
    if not specs:
        raise ConfigError(f"sweep file {path} holds no scenarios")
    return specs


@dataclass
class ScenarioSpec:
    """One resolved scenario: its overrides, effective config, the solo
    simulator the fleet batches, and everything the results row needs."""

    index: int
    overrides: dict
    cfg: NetworkConfig
    sim: object                       # aligned.AlignedSimulator
    n_peers: int                      # effective (possibly padded)
    n_peers_requested: int
    clamps: list[str] = field(default_factory=list)

    def row_identity(self) -> dict:
        """The spec-level fields of this scenario's results-table row."""
        out = {
            "scenario": self.index,
            "spec": self.overrides,
            "n_peers": self.n_peers,
            "n_msgs": self.sim.n_msgs,
            "mode": self.sim.mode,
            "seed": self.sim.seed,
        }
        if self.n_peers_requested != self.n_peers:
            out["n_peers_requested"] = self.n_peers_requested
        if self.clamps:
            out["clamped"] = list(self.clamps)
        return out


def apply_overrides(cfg: NetworkConfig, overrides: dict,
                    index: int) -> NetworkConfig:
    """Clone ``cfg`` and apply one sweep line's overrides, then re-run
    the config's own validation — a bad value fails with the scenario
    index, before anything is built."""
    out = copy.deepcopy(cfg)
    for key, value in overrides.items():
        attr = _attr_for(key)
        if attr is None or key in _RESERVED:
            raise ConfigError(
                f"sweep scenario {index}: unknown or reserved key "
                f"{key!r} (sweep lines override per-scenario config "
                "keys only)")
        current = getattr(out, attr)
        if isinstance(current, bool) or current is None:
            setattr(out, attr, value)
        elif isinstance(current, int) and not isinstance(value, bool):
            setattr(out, attr, int(value))
        elif isinstance(current, float):
            setattr(out, attr, float(value))
        else:
            setattr(out, attr, str(value))
    try:
        out._validate_config()
    except ConfigError as e:
        raise ConfigError(f"sweep scenario {index}: {e.message}")
    return out


def build_scenarios(base_cfg: NetworkConfig, specs: list[dict],
                    n_peers: int | None = None,
                    pad_peers: bool = True) -> list[ScenarioSpec]:
    """Resolve sweep lines to solo simulators, ready for the packer.

    ``n_peers`` (the CLI's ``--n-peers``) is the base peer count a
    scenario inherits when its line doesn't set one.  Scenarios must be
    gossip-mode (push/pull/pushpull) — the fleet batches the aligned
    engine; ``mode=sir`` and ``engine=edges`` scenarios are named
    errors, not silent substitutions.

    A scenario whose effective config carries ``graph_file`` builds a
    :class:`realgraph.RealGraphSimulator` instead (``engine`` itself is
    a reserved key — graph_file IS the realgraph request): the ingested
    graph fixes the peer count, so no power-of-two padding applies, and
    the scenario routes into its own signature bucket (the realgraph
    ``_bucket_signature`` leads with the engine name + graph
    fingerprint, so it can never collide with an aligned program)."""
    from p2p_gossipprotocol_tpu.aligned import AlignedSimulator

    out = []
    for i, overrides in enumerate(specs):
        cfg_i = apply_overrides(base_cfg, overrides, i)
        if cfg_i.mode not in ("push", "pull", "pushpull"):
            raise ConfigError(
                f"sweep scenario {i}: the fleet engine batches the "
                f"aligned gossip engine (push/pull/pushpull), not "
                f"mode={cfg_i.mode!r}")
        clamps: list[str] = []
        if cfg_i.graph_file:
            from p2p_gossipprotocol_tpu.realgraph import \
                RealGraphSimulator

            try:
                sim = RealGraphSimulator.from_config(cfg_i, clamps=clamps)
            except (ValueError, OSError) as e:
                raise ConfigError(f"sweep scenario {i}: {e}")
            n_eff = int(sim.topo.n_peers)
            out.append(ScenarioSpec(
                index=i, overrides=dict(overrides), cfg=cfg_i, sim=sim,
                n_peers=n_eff, n_peers_requested=n_eff, clamps=clamps))
            continue
        n_req = (int(overrides["n_peers"]) if "n_peers" in overrides
                 else (n_peers or cfg_i.n_peers
                       or len(cfg_i.seed_nodes)))
        n_eff = next_pow2(n_req) if pad_peers else n_req
        try:
            sim = AlignedSimulator.from_config(cfg_i, n_peers=n_eff,
                                               clamps=clamps)
        except ValueError as e:
            raise ConfigError(f"sweep scenario {i}: {e}")
        out.append(ScenarioSpec(index=i, overrides=dict(overrides),
                                cfg=cfg_i, sim=sim, n_peers=n_eff,
                                n_peers_requested=n_req, clamps=clamps))
    return out
