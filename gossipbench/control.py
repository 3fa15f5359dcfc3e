"""The control: the reference with one stated guarantee broken, put in the
program's place, must come out not correct.

The configuration states one liveness sweep every ``round(ping /
message)`` rounds (13 s pings, 5 s messages: every 3rd round).  The
control sweeps half as often, the shortcut that would halve the
liveness pass's traffic.  ``python -m gossipbench.control --workload
<cell> --seeds <n> ...`` runs, for each seed, the window's first
scenario through the reference and through the control, and prints the
numbers ``check.compare`` reads for the control, one JSON line a seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from gossipbench import check, spec, window


def as_program(out, overlay) -> dict:
    """An Outcome laid out as ``check.flatten`` lays out the program's."""
    return {"seen": out.seen, "frontier": out.frontier, "alive": out.alive,
            "byz": 0 * out.alive, "strikes": out.strikes, "lanes": out.lanes,
            "perm": overlay.perm, "rolls": overlay.rolls,
            "subrolls": overlay.subrolls, "deg": overlay.deg,
            "valid": overlay.valid, "rounds": out.rounds,
            "coverage": out.coverage}


def readings(workload: str, seed: int, bench_path: str | None = None) -> dict:
    """Both runs on the cell's chips, as the check runs the reference."""
    import jax

    cell = spec.load_cell(workload, bench_path)
    kv = cell.kv()
    ref_mod = spec.reference(kv["bench_reference"])
    dep = ref_mod.deployment(kv)
    devices = jax.devices()[:cell.chips]
    key = window.scenario_key(seed, 0)
    t0 = time.perf_counter()
    sound = ref_mod.Scenario(dep, devices=devices)
    want = sound.run(key)
    t1 = time.perf_counter()
    broken = ref_mod.Scenario(dep, liveness_every=2 * dep.liveness_every,
                              devices=devices)
    got = broken.run(key)
    t2 = time.perf_counter()
    numbers, parts = check.compare(as_program(got, broken.ov), want, sound.ov)
    return {"workload": workload, "seed": seed, "numbers": numbers,
            "parts": parts, "correct": check.verdict(numbers),
            "rounds": [want.rounds, got.rounds],
            "reference_s": t1 - t0, "control_s": t2 - t1}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m gossipbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        print(json.dumps(readings(args.workload, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
