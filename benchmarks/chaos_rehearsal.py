"""Process-level chaos harness for the supervision plane.

Kills (SIGKILL) or wedges (SIGSTOP) a seed-chosen worker of a
supervised multi-process run at a seed-chosen round, then asserts the
promise of runtime/supervisor.py end to end:

* the supervisor DETECTS the failure within its deadline (dead worker
  via exit status, wedged worker via heartbeat staleness);
* it executes deterministic shrink-to-survivors recovery — torn job
  reaped, mesh rebuilt over the surviving process set, run resumed
  from the last intact elastic checkpoint;
* the completed run's final canonical state AND full metric history
  are **bitwise-equal** to an uninterrupted run on the survivor
  layout (and, by the PR-3 cross-layout contract, to the original
  layout's run) — a recovery that "works" but silently changes the
  trajectory is the defect class this repo never ships;
* the recovery's MTTR (failure detected → first post-resume progress)
  is measured and recorded.

    python benchmarks/chaos_rehearsal.py                 # seed 0
    python benchmarks/chaos_rehearsal.py --seed 3 --kill sigstop
    python benchmarks/chaos_rehearsal.py --out chaos.jsonl

Exit 0 iff the run self-healed AND parity held.  The driver process
never runs device code itself — the workers are real subprocesses, the
chaos signals are real signals.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import signal
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from p2p_gossipprotocol_tpu.runtime.supervisor import (  # noqa: E402
    Supervisor, heartbeat_path, plan_from_config, read_heartbeat)

N_WORKERS = 2
DEVS_PER_PROC = 2
#: long enough that the job can NEVER finish between injection and the
#: reap's graceful SIGTERM (which salvages at the next chunk boundary)
#: — the recovery must genuinely resume mid-run on the survivor mesh,
#: not discover an already-complete checkpoint
ROUNDS = 24
CKPT_EVERY = 2

#: the one rehearsed scenario — small enough for CPU, rich enough that
#: the resumed trajectory exercises churn + staggered generation
CONFIG_TEXT = """127.0.0.1:9001
backend=jax
engine=aligned
n_peers=4096
n_messages=8
mode=pushpull
churn_rate=0.05
message_stagger=1
prng_seed=5
rounds={rounds}
supervise=1
supervise_workers={workers}
supervise_devs_per_proc={devs}
supervise_spmd=chief
supervise_grace_s=150
supervise_deadline_s={deadline}
"""


def chaos_plan(seed: int, kill: str, victim: str) -> dict:
    """The seed-deterministic chaos decision: who dies, how, and when.
    ``kill``/``victim`` = "auto" draw from the seed; explicit values
    override (so one harness covers the whole failure grid)."""
    rng = random.Random(0x90551 + seed)
    k = kill if kill != "auto" else rng.choice(["sigkill", "sigstop"])
    v = victim if victim != "auto" else rng.choice(["chief", "holder"])
    return {"kill": k, "victim": v,
            "kill_round": rng.choice(range(3, 7)),
            "victim_rank": 0 if v == "chief" else
            rng.choice(range(1, N_WORKERS))}


def _worker_env(n_devices: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                        + str(n_devices))
    return env


def reference_run(cfg_path: str, survivors: tuple[int, ...],
                  ref_dir: str) -> dict:
    """The uninterrupted run ON THE SURVIVOR LAYOUT, through the exact
    worker entry the supervised job uses (same pinned topology: the
    overlay statics come from the ORIGINAL total_ranks x devs grid,
    which is what makes this trajectory the right reference for a
    shrunk resume)."""
    import subprocess

    chief = min(survivors)
    ck = os.path.join(ref_dir, "ck")
    argv = [sys.executable, "-m", "p2p_gossipprotocol_tpu.runtime"
            ".worker", cfg_path,
            "--rank", str(chief),
            "--survivors", ",".join(map(str, survivors)),
            "--total-ranks", str(N_WORKERS),
            "--devs-per-proc", str(DEVS_PER_PROC),
            "--rounds", str(ROUNDS),
            "--run-dir", ref_dir,
            "--spmd", "chief",
            "--checkpoint-dir", ck,
            "--checkpoint-every", str(CKPT_EVERY)]
    proc = subprocess.run(
        argv, env=_worker_env(len(survivors) * DEVS_PER_PROC),
        capture_output=True, text=True, timeout=300)
    if proc.returncode != 0:
        raise RuntimeError("reference run failed: "
                           + proc.stderr[-2000:])
    with open(os.path.join(ref_dir, "result.json")) as fp:
        return json.load(fp)


def final_generation(ck_dir: str):
    """(canonical leaves, metric history, round) of the latest intact
    generation — CRC-verified through the same latest_intact path the
    supervisor and the CLI resume use."""
    from p2p_gossipprotocol_tpu.utils.checkpoint import latest_intact

    gen = latest_intact(ck_dir)
    return gen.canonical, gen.hist, gen.round


def bitwise_equal(a_ck: str, b_ck: str) -> tuple[bool, str]:
    import numpy as np

    ca, ha, ra = final_generation(a_ck)
    cb, hb, rb = final_generation(b_ck)
    if ra != rb:
        return False, f"round mismatch {ra} != {rb}"
    for group in ("state", "topo"):
        if set(ca[group]) != set(cb[group]):
            return False, f"{group} leaf sets differ"
        for leaf in ca[group]:
            if not np.array_equal(ca[group][leaf], cb[group][leaf]):
                return False, f"{group}/{leaf} diverged"
    if set(ha) != set(hb):
        return False, "history key sets differ"
    for k in ha:
        if not np.array_equal(ha[k], hb[k]):
            return False, f"history {k!r} diverged"
    return True, ""


def run_chaos(seed: int, kill: str, victim: str, deadline_s: float,
              keep_dir: str | None = None, quiet: bool = False) -> dict:
    from p2p_gossipprotocol_tpu.config import NetworkConfig

    plan_d = chaos_plan(seed, kill, victim)
    base = keep_dir or tempfile.mkdtemp(prefix="gossip_chaos_")
    os.makedirs(base, exist_ok=True)
    from p2p_gossipprotocol_tpu.utils.logging import write_atomic

    cfg_path = os.path.join(base, "net.txt")
    write_atomic(cfg_path,
                 CONFIG_TEXT.format(rounds=ROUNDS, workers=N_WORKERS,
                                    devs=DEVS_PER_PROC,
                                    deadline=deadline_s))
    cfg = NetworkConfig(cfg_path)
    run_dir = os.path.join(base, "supervise")
    ck_dir = os.path.join(base, "ck")
    plan = plan_from_config(cfg, config_path=cfg_path, rounds=ROUNDS,
                            run_dir=run_dir, checkpoint_dir=ck_dir,
                            checkpoint_every=CKPT_EVERY)
    plan.job_timeout_s = 600
    log = (lambda m: None) if quiet else \
        (lambda m: print(m, file=sys.stderr))
    sup = Supervisor(plan, log=log)

    box: dict = {}

    def _run():
        box["result"] = sup.run()

    t = threading.Thread(target=_run, daemon=True)
    t.start()

    # -- the injector: wait for the seed-chosen round, then strike ----
    sig = (signal.SIGKILL if plan_d["kill"] == "sigkill"
           else signal.SIGSTOP)
    victim_rank = plan_d["victim_rank"]
    inject_t = None
    deadline = time.monotonic() + 420
    while time.monotonic() < deadline and t.is_alive():
        chief_hb = read_heartbeat(heartbeat_path(run_dir, 0))
        if chief_hb and chief_hb.get("phase") == "run" \
                and chief_hb["round"] >= plan_d["kill_round"]:
            vic_hb = read_heartbeat(
                heartbeat_path(run_dir, victim_rank))
            if vic_hb and vic_hb.get("pid"):
                try:
                    os.kill(int(vic_hb["pid"]), sig)
                    inject_t = time.monotonic()
                except ProcessLookupError:
                    pass   # raced a chunk boundary; victim respawns
            break
        time.sleep(0.05)
    if inject_t is None:
        sup._reap_job()
        raise RuntimeError(
            f"chaos injection never fired (chief heartbeat did not "
            f"reach round {plan_d['kill_round']})")
    t.join(timeout=600)
    res = box.get("result")
    if res is None:
        sup._reap_job()
        raise RuntimeError("supervisor did not return")

    row = {
        "config": f"chaos_{plan_d['kill']}_{plan_d['victim']}",
        "seed": seed, "n_peers": 4096, "rounds": ROUNDS,
        "workers": N_WORKERS, "devs_per_proc": DEVS_PER_PROC,
        "kill_round": plan_d["kill_round"],
        "victim_rank": victim_rank,
        "ok": bool(res.ok),
        "attempts": res.attempts,
        "recoveries": len(res.recoveries),
        "survivors": list(res.survivors),
        "wall_s": round(res.wall_s, 2),
    }
    if res.recoveries:
        r0 = res.recoveries[0]
        row["failure_kind"] = r0.failure.kind
        row["detect_s"] = round(r0.failure.detected_at - inject_t, 3)
        row["mttr_s"] = (round(r0.mttr_s, 3)
                         if r0.mttr_s is not None else None)
        row["resumed_round"] = r0.resumed_round
        # the claim under test is recovery MID-RUN: rounds really ran
        # on the shrunk survivor mesh after the failure
        row["resumed_midrun"] = r0.resumed_round < ROUNDS
    if not res.ok:
        row["parity_ok"] = False
        row["reason"] = res.reason
        return row

    # -- parity: uninterrupted run on the survivor layout -------------
    ref_dir = os.path.join(base, "ref")
    ref = reference_run(cfg_path, res.survivors, ref_dir)
    ok, why = bitwise_equal(ck_dir, os.path.join(ref_dir, "ck"))
    row["parity_ok"] = ok
    if not ok:
        row["parity_detail"] = why
    row["final_coverage"] = (res.result or {}).get("final_coverage")
    row["ref_coverage"] = ref.get("final_coverage")
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kill", choices=["auto", "sigkill", "sigstop"],
                    default="auto")
    ap.add_argument("--victim", choices=["auto", "chief", "holder"],
                    default="auto")
    ap.add_argument("--deadline-s", type=float, default=15.0,
                    help="supervise_deadline_s for the rehearsal (the "
                         "production default derives from the traffic "
                         "model; the rehearsal pins a small one so "
                         "SIGSTOP detection is test-speed)")
    ap.add_argument("--out", default=None, metavar="JSONL",
                    help="append the result row here")
    ap.add_argument("--keep-dir", default=None,
                    help="run under this directory (kept); default: "
                         "a fresh temp dir")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args()

    row = run_chaos(args.seed, args.kill, args.victim, args.deadline_s,
                    keep_dir=args.keep_dir, quiet=args.quiet)
    row["ts"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "a") as fp:
            fp.write(json.dumps(row) + "\n")
    return 0 if row.get("ok") and row.get("parity_ok") else 1


if __name__ == "__main__":
    sys.exit(main())
