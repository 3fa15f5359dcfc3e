"""One run of one cell: set-up, the measured window, the check, one line.

``python -m gossipbench --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``.  With ``--trace 0`` the result line carries the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the
profiler (at most ``TRACE_SECONDS``) and the line carries the per-layer
metrics, the device's busy and window seconds and a breakdown.  Either
way one scenario of the window, drawn from the seed, is compared with
the configuration's plain reference once the window has closed.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

#: the longest traced window: a trace of the 1M cell holds ~130 device
#: events a round, and the trace has to be read inside the run's limit
TRACE_SECONDS = 2.0


class NoChip(Exception):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def process_start_wall() -> float:
    """Wall-clock time at which this process started (Linux /proc),
    else now."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


@dataclasses.dataclass
class Run:
    """What the per-layer readers (metrics/<name>.py) read."""

    dep: object               # the reference's resolved deployment
    device_kind: str
    build_s: float
    compile_s: float
    scenarios: list           # gossipbench.window.Scenario of the window
    trace: object | None      # gossipbench.trace.Summary


def _devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs


def _peak_bytes(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def execute(args, *, require_tpu: bool = True, bench_path: str | None = None,
            t_start: float | None = None) -> tuple[dict, list[str]]:
    """The run: (result line, lines for standard error).
    ``require_tpu=False`` is for the harness's own tests on the CPU."""
    t_start = t_start if t_start is not None else process_start_wall()
    from gossipbench import check, spec, window as win

    cell = spec.load_cell(args.workload, bench_path)
    kv = cell.kv()
    ref_mod = spec.reference(kv["bench_reference"])
    dep = ref_mod.deployment(kv)

    import jax

    from p2p_gossipprotocol_tpu.device import enable_compile_cache

    if enable_compile_cache() is not None:
        # cache every program, the small eager ones of init_state too
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = _devices(cell.chips, require_tpu)[:cell.chips]
    clock = win.CompileClock()

    setup = win.build(cell)
    win.warm_up(setup, args.seed)
    compile_s, _ = clock.take()
    setup_s = time.time() - t_start

    summary = None
    trace_dir = tempfile.mkdtemp(prefix="gossipbench-") if args.trace else None
    try:
        seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
        with win.traced(trace_dir):
            scenarios, window_s, kept = win.window(setup, args.seed, seconds)
        _, compiles_in_window = clock.take()
        if trace_dir:
            from gossipbench import trace as tr

            summary = tr.summarize(*tr.read_events(tr.load(trace_dir)),
                                   n_chips=cell.chips)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    peak = _peak_bytes(devs)

    # the sampled scenario's answer; then the program's state goes
    index, key, st, tp, rounds = kept
    prog = check.flatten(st, tp, rounds, setup.coverage(st, tp))
    build_s = setup.build_s
    del st, tp, kept, setup
    gc.collect()
    t_ref = time.perf_counter()
    scen = ref_mod.Scenario(dep, devices=devs)
    numbers, parts = check.compare(prog, scen.run(key), scen.ov)
    ref_s = time.perf_counter() - t_ref

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    metrics = {}
    if args.trace:
        run = Run(dep=dep, device_kind=devs[0].device_kind, build_s=build_s,
                  compile_s=compile_s, scenarios=scenarios, trace=summary)
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    else:
        times = sorted(s.total_s for s in scenarios)
        values = {"setup_s": setup_s,
                  "time_to_cov_ms": 1000.0 * window_s / len(scenarios),
                  "time_to_cov_ms_p95": 1000.0 * (
                      statistics.quantiles(times, n=20)[18]
                      if len(times) > 1 else times[0])}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    line = {"correct": check.verdict(numbers), "attempted": len(scenarios),
            "failed": sum(s.failed for s in scenarios), "metrics": metrics,
            "device": device}
    if summary is not None:
        line["breakdown"] = {"device_ops": summary.top_ops,
                             "idle_gaps": summary.idle_gaps}
    line["checks"] = {k: {"value": numbers[k], "limit": check.LIMITS[k]}
                      for k in check.LIMITS}
    err = [f"{args.workload} seed {args.seed}: {len(scenarios)} scenarios in "
           f"{window_s:.3f} s, {sum(s.rounds for s in scenarios)} rounds, "
           f"set-up {setup_s:.3f} s (build {build_s:.3f} s, compile "
           f"{compile_s:.3f} s), compiles in the window {compiles_in_window}",
           f"reference of scenario {index} ({prog['rounds']} rounds) took "
           f"{ref_s:.3f} s; mismatch parts {json.dumps(parts)}"]
    return line, err + check.report_lines(numbers)


def main(argv: list[str] | None = None, t_start: float | None = None) -> int:
    ap = argparse.ArgumentParser(prog="python -m gossipbench",
                                 description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from gossipbench import spec

    try:
        line, err = execute(args, t_start=t_start)
    except (NoChip, spec.SpecError, ImportError) as e:
        print(f"[gossipbench] FAIL: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    for ln in err:
        print(ln, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
