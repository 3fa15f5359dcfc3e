"""Set-up and the measured window: the program's normal path.

Set-up builds the simulator once through ``engines.build_simulator``,
then runs one warm-up scenario, which compiles (or fetches from the
persistent cache) every program the window uses.  The window is a
closed loop of scenarios, one in flight: ``init_state`` with the
scenario's own PRNG key, then ``run_to_coverage`` to the traffic's
target.  It ends at the first scenario that finishes past ``seconds``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import tempfile
import time

import numpy as np

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds JAX spent in backend compilation (or fetching from the
    persistent cache), and how many such events, since the last
    :meth:`take` (copied from chip_smoke.py)."""

    def __init__(self):
        import jax.monitoring

        self._s, self._n = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self._s += duration
            self._n += 1

    def take(self) -> tuple[float, int]:
        out = (self._s, self._n)
        self._s, self._n = 0.0, 0
        return out


def _entropy(seed: int) -> int:
    return seed % (1 << 64)


def scenario_key(seed: int, index: int) -> np.ndarray:
    """uint32[2] PRNG key of scenario ``index`` (-1 = the warm-up)."""
    ss = np.random.SeedSequence([_entropy(seed), 1, index + 1])
    return ss.generate_state(2, np.uint32)


@dataclasses.dataclass
class Scenario:
    index: int
    init_s: float       # init_state until its arrays are ready
    total_s: float      # from init_state to the host's receipt of the rounds
    rounds: int
    failed: bool


@dataclasses.dataclass
class Setup:
    sim: object
    build_s: float
    target: float
    cap: int
    sched_end: int

    def coverage(self, state, topo) -> float:
        """The program's census of a final state, ``aligned_coverage``.
        The sharded engine keeps its rules on its single-chip twin.  On
        the CPU the census's OR over rows would be an all-reduce that
        XLA's CPU backend lacks, so there the planes it counts are read
        on one device; a TPU reduces them where they lie."""
        import jax

        from p2p_gossipprotocol_tpu.aligned import aligned_coverage

        one = getattr(self.sim, "_inner", None)
        if one is None:
            return aligned_coverage(self.sim, state, topo)
        first = self.sim.mesh.devices.flat[0]
        if first.platform != "cpu":
            return aligned_coverage(one, state, topo)
        seen, alive, byz, valid = jax.device_put(
            (state.seen_w, state.alive_b, state.byz_w, topo.valid_w), first)
        return aligned_coverage(
            one, state.replace(seen_w=seen, alive_b=alive, byz_w=byz),
            topo.replace(valid_w=valid))


def network_config(cell):
    from p2p_gossipprotocol_tpu.config import NetworkConfig

    fd, path = tempfile.mkstemp(prefix="gossipbench-", suffix=".txt")
    try:
        with os.fdopen(fd, "w") as f:
            f.write(cell.network_text())
        cfg = NetworkConfig(path)
    finally:
        os.unlink(path)
    return cfg


def _span(name: str, index: int):
    import jax

    return jax.profiler.TraceAnnotation(f"gossipbench.{name}:{index}")


def run_scenario(setup: Setup, key: np.ndarray, index: int):
    """One scenario through the timed path: (Scenario, state, topo)."""
    import jax
    import jax.numpy as jnp

    sim = setup.sim
    t0 = time.perf_counter()
    with _span("init", index):
        st = sim.init_state().replace(key=jnp.asarray(key))
        jax.block_until_ready(st)
    t1 = time.perf_counter()
    with _span("run", index):
        st, tp, rounds, _ = sim.run_to_coverage(
            target=setup.target, max_rounds=setup.cap, state=st,
            warmup=False)
    t2 = time.perf_counter()
    failed = rounds < setup.sched_end or (
        rounds >= setup.cap and setup.coverage(st, tp) < setup.target)
    return Scenario(index, t1 - t0, t2 - t0, rounds, failed), st, tp


def engine_for(cell) -> str:
    """The engine a cell's chips imply: the aligned engine on one chip,
    its flat row-sharded twin on more.  The configuration's
    ``mesh_devices`` has to say as many chips; the 2-D and two-tier
    factorizations are not benchmarked."""
    from gossipbench import spec

    kv = cell.kv()
    mesh = max(int(kv.get("mesh_devices", "0")), 1)
    if mesh != cell.chips:
        raise spec.SpecError(f"{cell.name}: the cell asks for {cell.chips} "
                             f"chips, its config for mesh_devices={mesh}")
    for k in ("msg_shards", "hier_hosts", "hier_devs"):
        if int(kv.get(k, "0")) > 1:
            raise spec.SpecError(f"{cell.name}: {k}={kv[k]}: the benchmark "
                                 "runs the flat row-sharded engine only")
    return "aligned" if cell.chips == 1 else f"aligned-sharded-{cell.chips}"


def build(cell) -> Setup:
    # the engine's module is imported before the clock starts: build_s
    # times the overlay and the simulator, not Python's imports
    import p2p_gossipprotocol_tpu.aligned  # noqa: F401
    from p2p_gossipprotocol_tpu.engines import build_simulator

    kv = cell.kv()
    want = engine_for(cell)
    cfg = network_config(cell)
    t0 = time.perf_counter()
    sim, engine = build_simulator(cfg)
    build_s = time.perf_counter() - t0
    if engine != want:
        raise RuntimeError(f"{cell.name}: engine {engine!r}, not {want}")
    n_msgs = int(kv["n_messages"])
    stagger = int(kv.get("message_stagger", "0"))
    return Setup(sim=sim, build_s=build_s,
                 target=float(kv["coverage_target"]),
                 cap=int(kv["max_rounds"]),
                 sched_end=(n_msgs - 1) * stagger + 1 if stagger > 0 else 0)


def warm_up(setup: Setup, seed: int) -> None:
    """The warm-up scenario, plus the census the window calls on a
    scenario that reaches the round cap."""
    _, st, tp = run_scenario(setup, scenario_key(seed, -1), -1)
    setup.coverage(st, tp)


def window(setup: Setup, seed: int, seconds: float):
    """Scenarios until one finishes past ``seconds``.  Returns
    (scenarios, window_s, kept): ``kept`` is one finished scenario
    drawn from the seed by reservoir sampling, ``(index, key, state,
    topo, rounds)``, for the comparison with the reference."""
    pick = np.random.default_rng([_entropy(seed), 2])
    scenarios, kept = [], None
    # no collector pauses inside the window: what set-up left is frozen
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        t0 = time.perf_counter()
        i = 0
        while True:
            key = scenario_key(seed, i)
            sc, st, tp = run_scenario(setup, key, i)
            scenarios.append(sc)
            if pick.integers(0, i + 1) == 0:
                kept = (i, key, st, tp, sc.rounds)
            del st, tp
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    finally:
        gc.enable()
        gc.unfreeze()
    return scenarios, window_s, kept


@contextlib.contextmanager
def traced(trace_dir: str | None):
    """The profiler around the window when ``trace_dir`` is given."""
    if trace_dir is None:
        yield
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        yield
