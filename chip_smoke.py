#!/usr/bin/env python
"""Bring-up check: the main path runs on the chip and agrees with the host.

One chip (no arguments): the ``examples/scale.txt`` deployment — 1,048,576
peers, power-law degree 16, pushpull, 5% churn with 3-strike eviction,
stagger 1, 48 rounds — is built through ``engines.build_simulator`` and
run twice on the chip: at its own 32 messages (W=1, the row-perm overlay)
and at 256 messages (W=8, where the auto rule picks the fused block-perm
overlay).  Each run is compared bitwise — final state, final overlay and
every per-round metric — against the same simulator run in this process
on the host CPU device with ``interpret=True``.

Four chips (``--chips 4``): the same deployment at 4,194,304 peers with
``mesh_devices=4`` (the sharded aligned engine, halving exchange) against
the single-chip engine of the same config on one of the four chips,
compared bitwise per round; the sharded state must span all four
devices.  No other phase runs.

Every phase prints one JSON line; the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failure — no TPU, a missing checkout, a mismatch — exits non-zero
before that line.  The script reads only committed files (the overlays
are built from the config's seed) and starts no process: a chip
belongs to one process.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SCALE_CFG = os.path.join(HERE, "examples", "scale.txt")
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeError(Exception):
    """A phase failed: the run exits non-zero and prints no result."""


class CompileClock:
    """Seconds JAX spent in backend compilation (or fetching from the
    persistent cache) since the last :meth:`take`."""

    def __init__(self):
        import jax.monitoring

        self._s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self._s += duration

    def take(self) -> float:
        s, self._s = self._s, 0.0
        return s


def _leaves(tree) -> list:
    import jax
    import numpy as np

    return [np.asarray(x) for x in jax.tree.leaves(jax.device_get(tree))]


def bitwise_diff(got, want) -> list[str]:
    """Names of the SimResult parts where ``got`` and ``want`` differ by
    a single bit: the final state, the final overlay and every per-round
    metric."""
    bad = []
    for part in ("state", "topo"):
        a, b = _leaves(getattr(got, part)), _leaves(getattr(want, part))
        if len(a) != len(b) or any(
                x.dtype != y.dtype or x.shape != y.shape
                or x.tobytes() != y.tobytes() for x, y in zip(a, b)):
            bad.append(part)
    for f in ("coverage", "deliveries", "frontier_size", "live_peers",
              "evictions", "redeliveries"):
        a, b = getattr(got, f), getattr(want, f)
        if (a is None) != (b is None):
            bad.append(f)
        elif a is not None:
            (x,), (y,) = _leaves([a]), _leaves([b])
            if x.dtype != y.dtype or x.shape != y.shape \
                    or x.tobytes() != y.tobytes():
                bad.append(f)
    return bad


def resolved_statics(sim) -> dict:
    inner = getattr(sim, "_inner", sim)
    return {"interpret": bool(inner.interpret),
            "block_perm": inner.topo.ytab is not None,
            "prefetch_depth": int(inner._prefetch),
            "frontier_skip": bool(inner._frontier_skip),
            "frontier_delta": bool(inner._frontier_delta),
            "frontier_halving": bool(inner._frontier_algo),
            "overlap": bool(inner._overlap),
            "rowblk": int(inner.topo.rowblk)}


def _expect(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def load_config(n_messages: int | None = None, n_peers: int | None = None,
                mesh_devices: int = 0):
    from p2p_gossipprotocol_tpu.config import NetworkConfig

    cfg = NetworkConfig(SCALE_CFG)
    if n_messages is not None:
        cfg.n_messages = n_messages
    if n_peers is not None:
        cfg.n_peers = n_peers
    cfg.mesh_devices = mesh_devices
    return cfg


def _report(name, sim, engine, res, *, device, clock_s, build_s,
            extra=None) -> dict:
    stats = device.memory_stats() or {}
    return {"phase": name, "engine": engine,
            "n_peers": int(getattr(sim, "_inner", sim).topo.n_peers),
            "n_msgs": int(sim.n_msgs), "rounds": int(len(res.coverage)),
            "final_coverage": float(res.coverage[-1]),
            "wall_s": res.wall_s, "compile_s": clock_s,
            "build_s": build_s, "platform": device.platform,
            "device_kind": device.device_kind,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "resolved": resolved_statics(sim), **(extra or {})}


def solo_phase(name: str, cfg, clock: CompileClock, *, ref_device,
               expect_block_perm: bool) -> dict:
    """The deployment through build_simulator on the default device,
    then the same simulator on ``ref_device`` in interpret mode."""
    import jax

    from p2p_gossipprotocol_tpu.engines import build_simulator

    rounds = cfg.rounds
    t0 = time.perf_counter()
    sim, engine = build_simulator(cfg)
    build_s = time.perf_counter() - t0
    _expect(engine == "aligned", f"{name}: engine {engine!r}, not aligned")
    st = resolved_statics(sim)
    _expect(not st["interpret"] and st["prefetch_depth"] == 2
            and st["frontier_skip"] and st["frontier_halving"]
            and st["block_perm"] == st["overlap"] == expect_block_perm,
            f"{name}: an auto static missed its compiled branch: {st}")
    clock.take()
    res = sim.run(rounds, warmup=True)
    compile_s = clock.take()
    row = _report(name, sim, engine, res, device=jax.devices()[0],
                  clock_s=compile_s, build_s=build_s)

    with jax.default_device(ref_device):
        ref_sim = dataclasses.replace(
            sim, topo=jax.device_put(sim.topo, ref_device), interpret=True)
        t0 = time.perf_counter()
        ref = ref_sim.run(rounds)
        row["ref_s"] = time.perf_counter() - t0
    clock.take()
    row["ref_device"] = f"{ref_device.platform}:{ref_device.device_kind}"
    bad = bitwise_diff(res, ref)
    row["bitwise_equal"] = not bad
    print(json.dumps(row), flush=True)
    _expect(not bad, f"{name}: differs from its interpret reference in "
                     f"{bad}")
    return row


def sharded_phase(clock: CompileClock, *, n_peers: int,
                  n_devices: int = 4) -> dict:
    """``mesh_devices=n_devices`` through build_simulator against the
    single-chip engine of the same config on the mesh's first chip."""
    import jax

    from p2p_gossipprotocol_tpu.engines import build_simulator

    cfg = load_config(n_peers=n_peers, mesh_devices=n_devices)
    rounds = cfg.rounds
    t0 = time.perf_counter()
    sim, engine = build_simulator(cfg)
    build_s = time.perf_counter() - t0
    _expect(engine == f"aligned-sharded-{n_devices}",
            f"sharded: engine {engine!r}")
    st = resolved_statics(sim)
    _expect(not st["interpret"] and st["frontier_delta"]
            and st["frontier_halving"] and st["prefetch_depth"] == 2,
            f"sharded: an auto static missed its compiled branch: {st}")
    clock.take()
    res = sim.run(rounds, warmup=True)
    compile_s = clock.take()
    mesh_devs = set(sim.mesh.devices.flat)
    seen_devs = res.state.seen_w.sharding.device_set
    row = _report("sharded", sim, engine, res, device=jax.devices()[0],
                  clock_s=compile_s, build_s=build_s,
                  extra={"seen_w_devices": sorted(d.id for d in seen_devs)})
    _expect(len(mesh_devs) == n_devices and all(
        x.sharding.device_set == mesh_devs
        for x in jax.tree.leaves(res.state)),
            f"sharded: the state does not span the {n_devices}-device "
            f"mesh (seen_w on {row['seen_w_devices']})")

    solo = sim._inner
    clock.take()
    ref = solo.run(rounds, warmup=True)
    row["solo_compile_s"] = clock.take()
    row["solo_wall_s"] = ref.wall_s
    row["solo_device"] = str(next(iter(
        ref.state.seen_w.sharding.device_set)))
    bad = bitwise_diff(res, ref)
    row["bitwise_equal"] = not bad
    print(json.dumps(row), flush=True)
    _expect(not bad, f"sharded: differs from the single-chip engine in "
                     f"{bad}")
    return row


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the sharded phase on a 2x2 host only")
    args = ap.parse_args(argv)
    if not os.path.exists(SCALE_CFG) or not os.path.isdir(
            os.path.join(HERE, "p2p_gossipprotocol_tpu")):
        print("[chip_smoke] FAIL: run from a checkout of the repo "
              f"(missing {SCALE_CFG} or the package)", file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)
    # the host CPU device is the reference: keep it next to the chip
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    import jax

    from p2p_gossipprotocol_tpu.device import enable_compile_cache

    cache = enable_compile_cache()
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        print(f"[chip_smoke] FAIL: no TPU — JAX's first device is "
              f"platform {d0.platform!r} ({d0.device_kind})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"[chip_smoke] FAIL: --chips {args.chips} but JAX sees "
              f"{len(devs)} {d0.device_kind}", file=sys.stderr)
        return 1
    print(json.dumps({"phase": "start", "platform": d0.platform,
                      "device_kind": d0.device_kind, "count": len(devs),
                      "compile_cache": cache, "jax": jax.__version__}),
          flush=True)
    clock = CompileClock()
    try:
        if args.chips == 4:
            sharded_phase(clock, n_peers=1 << 22)
        else:
            cpu = jax.devices("cpu")[0]
            solo_phase("scale_w1", load_config(), clock, ref_device=cpu,
                       expect_block_perm=False)
            solo_phase("scale_w8", load_config(n_messages=256), clock,
                       ref_device=cpu, expect_block_perm=True)
    except SmokeError as e:
        print(f"[chip_smoke] FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
