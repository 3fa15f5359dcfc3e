"""The flight-recorder telemetry plane's contract (docs/OBSERVABILITY
.md): spans/events/counters are host-side only — every engine and the
serving plane produce BITWISE-identical results with telemetry on or
off, zero extra retraces — the clamp ledger absorbs every named clamp
site as exactly one typed event, dumps are atomic and readable, and
the serve server scrapes/captures live.

The STRUCTURAL halves of two of these contracts are enforced
statically by gossip-lint (tests/test_analysis.py,
docs/STATIC_ANALYSIS.md) rather than at runtime: the telemetry
package's jax-import ban (``telemetry-imports`` — the runtime suite
could only ever observe import-time effects; the static rule also
catches lazy in-function imports) and the telemetry_* fingerprint
exclusion (``fingerprint-exclusion``, which checks EVERY key's
classification, not one knob).  This module keeps the behavioral
sides: bitwise parity, retrace counts, ledger semantics."""

import json
import os
import threading
import time

import numpy as np
import pytest

import jax

from p2p_gossipprotocol_tpu import telemetry
from p2p_gossipprotocol_tpu.config import NetworkConfig
from p2p_gossipprotocol_tpu.telemetry.recorder import classify_clamp

STATE_LEAVES = ("seen_w", "frontier_w", "alive_b", "byz_w", "key",
                "round")
METRICS = ("coverage", "deliveries", "frontier_size", "live_peers",
           "evictions")


@pytest.fixture(autouse=True)
def _fresh_recorder():
    """Every test starts from a clean, DISABLED recorder and leaves
    one behind — telemetry state must never leak across tests."""
    rec = telemetry.recorder()
    mirror = rec._mirror
    rec.configure(enabled=False)
    rec.reset()
    yield rec
    rec.configure(enabled=False)
    rec.reset()
    rec.set_mirror(mirror)


def _write_cfg(tmp_path, extra: str = "", name: str = "net.txt") -> str:
    path = tmp_path / name
    path.write_text("127.0.0.1:8000\nbackend=jax\nn_peers=1024\n"
                    "n_messages=8\navg_degree=4\nrounds=8\n"
                    "local_ip=127.0.0.1\n" + extra)
    return str(path)


def _results_equal(a, b) -> bool:
    for k in STATE_LEAVES:
        if not np.array_equal(
                np.asarray(jax.device_get(getattr(a.state, k))),
                np.asarray(jax.device_get(getattr(b.state, k)))):
            return False
    return all(np.array_equal(np.asarray(getattr(a, k)),
                              np.asarray(getattr(b, k)))
               for k in METRICS)


# ----------------------------------------------------------------------
# Recorder unit contract.


def test_spans_nest_with_stable_ids(_fresh_recorder):
    rec = _fresh_recorder
    rec.configure(enabled=True)
    with rec.span("run", rounds=8) as outer:
        with rec.span("chunk", rounds=4) as inner:
            assert inner.parent == outer.sid
    spans = rec.spans()
    assert [s["name"] for s in spans] == ["chunk", "run"]
    chunk, run = spans
    assert chunk["parent"] == run["span"]
    assert chunk["dur_s"] >= 0 and run["dur_s"] >= chunk["dur_s"]
    # explicit span ids are honored verbatim (the serve request rule)
    rec.span_record("request", 0.25, span_id="request:7", queue_ms=1.0)
    assert rec.spans("request")[0]["span"] == "request:7"


def test_disabled_recorder_is_inert_but_ledger_stays_on(
        _fresh_recorder):
    rec = _fresh_recorder
    rec.set_mirror(None)
    assert not rec.enabled
    with rec.span("run") as sp:
        assert sp is None            # the shared no-op
    rec.counter_add("x", 5)
    rec.gauge_set("g", 1.0)
    assert rec.spans() == [] and rec.counters() == {}
    # events are the post-mortem ledger: ALWAYS recorded
    rec.event("clamp", site="auto_select", detail="d")
    assert len(rec.events("clamp")) == 1


class _Marks:
    """A stand-in trace mirror: logs each marker's enter and exit."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class Mark:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))
                return False

        return Mark()


def test_disabled_span_calls_only_the_mirror(_fresh_recorder):
    rec = _fresh_recorder
    marks = _Marks()
    rec.set_mirror(marks)
    with rec.span("dispatch", rounds=3):
        pass
    assert marks.log == [("enter", "gossip.dispatch"),
                         ("exit", "gossip.dispatch")]
    assert rec.spans() == []


def test_enabled_spans_record_parent_and_mirror(_fresh_recorder):
    rec = _fresh_recorder
    marks = _Marks()
    rec.set_mirror(marks)
    rec.configure(enabled=True)
    with rec.span("run_to_coverage") as outer:
        with rec.span("fetch") as inner:
            assert inner.parent == outer.sid
    fetch, run = rec.spans()
    assert (fetch["name"], fetch["parent"]) == ("fetch", run["span"])
    assert run["parent"] is None
    assert marks.log == [("enter", "gossip.run_to_coverage"),
                         ("enter", "gossip.fetch"),
                         ("exit", "gossip.fetch"),
                         ("exit", "gossip.run_to_coverage")]


def test_ring_is_bounded(_fresh_recorder):
    rec = _fresh_recorder
    rec.configure(enabled=True, ring=8)
    for i in range(50):
        rec.event("e", i=i)
        with rec.span("s", i=i):
            pass
    assert len(rec.events()) == 8 and len(rec.spans()) == 8
    assert rec.events()[-1]["i"] == 49       # newest survive
    rec.configure(ring=4096)


def test_dump_is_atomic_and_readable(_fresh_recorder, tmp_path):
    rec = _fresh_recorder
    rec.configure(enabled=True)
    rec.event("clamp", site="hier", detail="x")
    rec.counter_add("rounds_total", 12)
    with rec.span("chunk"):
        pass
    path = rec.dump("unit_test", directory=str(tmp_path))
    with open(path) as fp:
        snap = json.load(fp)
    assert snap["reason"] == "unit_test"
    assert snap["counters"]["rounds_total"] == 12
    assert snap["event_kinds"] == {"clamp": 1}
    assert [s["name"] for s in snap["spans"]] == ["chunk"]
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]


def test_render_metrics_catalog(_fresh_recorder):
    rec = _fresh_recorder
    rec.configure(enabled=True)
    rec.counter_add("rounds_total", 3)
    rec.gauge_set("roofline_frac", 0.42)
    rec.event("clamp", site="frontier", detail="d")
    with rec.span("chunk"):
        pass
    text = rec.render_metrics()
    assert "gossip_up 1" in text
    assert "gossip_rounds_total 3" in text
    assert "gossip_roofline_frac 0.42" in text
    assert 'gossip_events_total{kind="clamp"} 1' in text
    assert 'gossip_spans_total{name="chunk"} 1' in text


# ----------------------------------------------------------------------
# The unified clamp ledger: each named site -> exactly one typed event.


def _clamp_events(tmp_path, extra, **build_kw):
    from p2p_gossipprotocol_tpu.engines import build_simulator

    telemetry.recorder().reset()
    cfg = NetworkConfig(_write_cfg(tmp_path, extra))
    build_simulator(cfg, **build_kw)
    return telemetry.recorder().events("clamp")


@pytest.mark.parametrize("extra,site", [
    ("engine=aligned\nblock_perm=1\nroll_groups=1\n", "auto_select"),
    ("engine=aligned\nmode=pull\nfrontier_mode=1\npull_window=0\n",
     "frontier"),
    ("engine=aligned\nmode=pull\noverlap_mode=1\npull_window=0\n",
     "overlap"),
    ("engine=aligned\nhier_devs=2\n", "hier"),
    ("engine=aligned\navg_degree=200\n", "degree_cap"),
    ("engine=aligned\ngraph=ba\n", "graph_subst"),
])
def test_each_clamp_site_emits_one_typed_event(tmp_path, extra, site):
    evs = _clamp_events(tmp_path, extra)
    hits = [e for e in evs if e["site"] == site]
    assert len(hits) == 1, (site, evs)
    assert hits[0]["kind"] == "clamp" and hits[0]["detail"]


@pytest.mark.parametrize("extra,spec,gate", [
    ("", None, None),
    ("fault_link_drop=0.2\nfault_seed=3\n", "drop=0.2,seed=3", True),
    ("fault_delay=0.1\nfault_crash=2:0.3\n", "delay=0.1,crash=2:0.3",
     False),
])
def test_fault_plan_event_once_per_resolved_plan(tmp_path, extra, spec,
                                                 gate):
    from p2p_gossipprotocol_tpu.engines import build_simulator

    cfg = NetworkConfig(_write_cfg(tmp_path, "engine=aligned\n" + extra))
    telemetry.recorder().reset()
    sim, _ = build_simulator(cfg)
    evs = telemetry.recorder().events("fault_plan")
    if spec is None:
        assert sim.faults is None and evs == []
    else:
        assert [(e["spec"], e["kernel_gate"]) for e in evs] == [(spec, gate)]


def test_classify_covers_every_known_clamp_string():
    for text, site in [
        ("block_perm with roll_groups=1 -> row-perm overlay", "auto_select"),
        ("pull_window with mode=pull on a block_perm overlay -> classic "
         "pull", "auto_select"),
        ("frontier_mode 1 with mode=pull -> delta exchange only",
         "frontier"),
        ("overlap_mode 1 with mode=pull -> 0", "overlap"),
        ("hier_hosts x hier_devs 3x2 does not factorize", "hier"),
        ("mesh_devices 8 -> 1 (accelerator unavailable, CPU fallback)",
         "mesh_fallback"),
        ("n_messages 4096 -> 2048", "msg_cap"),
        ("avg_degree 200 -> 127", "degree_cap"),
        ("graph ba -> aligned power-law degree family", "graph_subst"),
        # names another knob in its explanation — must still classify
        # to its OWN site (table order, telemetry/recorder.py)
        ("sir_fuse 1 on a row-perm overlay -> fused count only (the "
         "permute prep stays host-side without block_perm)",
         "sir_fuse"),
    ]:
        assert classify_clamp(text) == site, text


def test_serve_admission_records_request_clamps(tmp_path):
    from p2p_gossipprotocol_tpu.serve.scheduler import resolve_request

    cfg = NetworkConfig(_write_cfg(tmp_path))
    telemetry.recorder().reset()
    resolve_request(cfg, {"avg_degree": 200}, rid=5)
    evs = telemetry.recorder().events("clamp")
    assert len(evs) == 1
    assert evs[0]["site"] == "degree_cap"
    assert evs[0]["scope"] == "request:5"


def test_supervisor_records_spmd_mode_and_gauges(tmp_path):
    """The supervisor records the spmd mode that ran (``auto`` runs
    the distributed job) and publishes its operator gauges."""
    from p2p_gossipprotocol_tpu.runtime.supervisor import (JobPlan,
                                                           Supervisor)

    telemetry.recorder().configure(enabled=True)
    telemetry.recorder().reset()
    script = tmp_path / "stub.py"
    script.write_text(
        "import sys\n"
        "sys.exit(0 if sys.argv[1] == 'distributed' else 1)\n")

    def argv(ctx):
        import sys as _sys
        return [_sys.executable, str(script), ctx.spmd]

    plan = JobPlan(ranks=(0,), run_dir=str(tmp_path / "run"),
                   argv=argv, spmd="auto", grace_s=30, poll_s=0.02)
    res = Supervisor(plan, log=lambda m: None).run()
    assert res.ok and res.spmd == "distributed"
    assert telemetry.recorder().counters().get(
        "supervise_survivors") == 1


def test_supervisor_worker_death_dumps_flight(tmp_path):
    """A crashing worker leaves a worker_death event AND a readable
    flight dump in the run dir (the supervisor-detected-death dump)."""
    from p2p_gossipprotocol_tpu.runtime.supervisor import (JobPlan,
                                                           Supervisor)

    telemetry.recorder().reset()
    script = tmp_path / "stub.py"
    script.write_text("import sys; sys.exit(9)\n")
    run_dir = tmp_path / "run"

    def argv(ctx):
        import sys as _sys
        return [_sys.executable, str(script)]

    plan = JobPlan(ranks=(0,), run_dir=str(run_dir), argv=argv,
                   spmd="chief", chief_only=True, grace_s=30,
                   poll_s=0.02, min_workers=1, max_recoveries=1)
    res = Supervisor(plan, log=lambda m: None).run()
    assert not res.ok
    assert telemetry.recorder().events("worker_death")
    dumps = [f for f in os.listdir(run_dir)
             if f.startswith("flight_")]
    assert dumps
    with open(run_dir / dumps[0]) as fp:
        snap = json.load(fp)
    assert snap["event_kinds"].get("worker_death", 0) >= 1


# ----------------------------------------------------------------------
# The observational contract: bitwise parity + zero retraces.


def _chunked(sim, rounds=6, every=3):
    from p2p_gossipprotocol_tpu.utils.checkpoint import run_chunked

    res, *_ = run_chunked(sim, rounds, every=every)
    return res


@pytest.mark.parametrize("extra", [
    "engine=aligned\n",
    "engine=aligned\nmesh_devices=2\n",
    # the 2-D mesh splits the packed planes: n_msgs multiple of 64
    "engine=aligned\nmesh_devices=4\nmsg_shards=2\nn_messages=64\n",
])
def test_bitwise_parity_solo_sharded_2d(tmp_path, extra):
    from p2p_gossipprotocol_tpu.engines import build_simulator

    cfg = NetworkConfig(_write_cfg(tmp_path, extra))
    rec = telemetry.recorder()
    sim, _ = build_simulator(cfg)
    off = _chunked(sim, 6, 3)
    rec.configure(enabled=True)
    sim2, _ = build_simulator(cfg)
    on = _chunked(sim2, 6, 3)
    rec.configure(enabled=False)
    assert _results_equal(off, on)
    # telemetry-on actually recorded the run
    names = {s["name"] for s in rec.spans()}
    assert {"run", "chunk"} <= names
    assert rec.counters().get("rounds_total") == 6


def test_bitwise_parity_and_zero_retraces_fleet(tmp_path):
    from p2p_gossipprotocol_tpu.fleet import FleetBucket, build_scenarios

    cfg = NetworkConfig(_write_cfg(tmp_path))
    specs = [{"prng_seed": s} for s in range(3)]
    rec = telemetry.recorder()

    sims_off = [s.sim for s in build_scenarios(cfg, specs)]
    b_off = FleetBucket(sims_off)
    res_off = b_off.run(8, target=0.99, check_every=4)

    rec.configure(enabled=True)
    sims_on = [s.sim for s in build_scenarios(cfg, specs)]
    b_on = FleetBucket(sims_on)
    res_on = b_on.run(8, target=0.99, check_every=4)
    rec.configure(enabled=False)

    for a, b in zip(res_off.results, res_on.results):
        assert _results_equal(a, b)
    # telemetry adds ZERO retraces: both buckets compiled the same
    # number of chunk programs
    assert b_on.trace_count == b_off.trace_count
    assert rec.counters().get("fleet_rounds_total", 0) > 0


def test_bitwise_parity_serve_and_trace_count(tmp_path):
    from p2p_gossipprotocol_tpu.fleet import build_scenarios
    from p2p_gossipprotocol_tpu.serve import GossipService

    cfg = NetworkConfig(_write_cfg(tmp_path))
    rec = telemetry.recorder()
    rec.configure(enabled=True)
    svc = GossipService(cfg, slots=4, queue_max=8, target=0.99,
                        rounds=16).start()
    specs = [{"prng_seed": 3}, {"prng_seed": 4}]
    rids = [svc.submit(s) for s in specs]
    rows = [svc.result(r, timeout=300) for r in rids]
    stats = svc.drain()
    rec.configure(enabled=False)
    # zero-recompile invariant holds WITH telemetry on
    assert stats["chunk_retraces"] == stats["buckets"]
    for spec, rid, row in zip(specs, rids, rows):
        served = svc.sim_result(rid)
        solo = build_scenarios(cfg, [spec])[0].sim.run(
            row["rounds_run"])
        assert _results_equal(served, solo)
    # the request spans landed with stable ids + the latency ledger
    spans = rec.spans("request")
    assert {s["span"] for s in spans} == {f"request:{r}" for r in rids}
    assert all("latency_ms" in s for s in spans)


def test_fingerprint_excludes_telemetry_keys(tmp_path):
    """RETIRED to gossip-lint: the full exclusion contract (every
    config key either fingerprinted or classified exempt — telemetry_*
    among them) is now the static ``fingerprint-exclusion`` rule,
    enforced tree-wide by tests/test_analysis.py over
    analysis/contracts.FINGERPRINT_EXEMPT.  One smoke assertion stays
    so a broken engines.config_keys import path can't hide behind a
    green lint."""
    from p2p_gossipprotocol_tpu.engines import config_keys

    cfg_off = NetworkConfig(_write_cfg(tmp_path))
    cfg_on = NetworkConfig(_write_cfg(
        tmp_path, "telemetry=1\ntelemetry_ring=128\n", name="on.txt"))
    assert config_keys(cfg_off) == config_keys(cfg_on)   # smoke


def test_roofline_counters_live(tmp_path):
    """The chunked runner publishes the live roofline: model bytes,
    achieved gb/s and the modeled-vs-achieved drift — and, on the CPU,
    no roofline_frac (the CPU has no roof)."""
    from p2p_gossipprotocol_tpu.engines import build_simulator

    cfg = NetworkConfig(_write_cfg(tmp_path, "engine=aligned\n"))
    rec = telemetry.recorder()
    rec.configure(enabled=True)
    sim, _ = build_simulator(cfg)
    _chunked(sim, 6, 3)
    rec.configure(enabled=False)
    c = rec.counters()
    assert c["rounds_total"] == 6
    assert c["model_bytes_total"] > 0
    assert c["achieved_gb_s"] > 0
    assert "roofline_frac" not in c
    assert 0.0 <= c["model_drift_frac"] <= 1.0
    expected = sim.traffic_model()["total"] * 6
    assert c["model_bytes_total"] == pytest.approx(expected)


# ----------------------------------------------------------------------
# The coverage path on the profiler's clock: spans, kernel names, scopes.

_LOOP_CFG = ("engine=aligned\nmode=pushpull\nchurn_rate=0.05\n"
             "liveness_every=3\n")


def _aligned(tmp_path, extra=""):
    from p2p_gossipprotocol_tpu.engines import build_simulator

    sim, engine = build_simulator(NetworkConfig(
        _write_cfg(tmp_path, _LOOP_CFG + extra)))
    assert engine == "aligned" and sim._liveness
    return sim


def _to_coverage(sim):
    st, tp, rounds, _ = sim.run_to_coverage(
        target=0.99, max_rounds=32, state=sim.init_state(), warmup=False)
    return jax.device_get((st, tp)), rounds


def test_coverage_path_spans(_fresh_recorder, tmp_path, monkeypatch):
    """build_simulator, init_state and run_to_coverage, each with
    compile (first call only, counted) and dispatch; fetch in the run."""
    from p2p_gossipprotocol_tpu import aligned

    monkeypatch.setattr(aligned, "_INIT_PROGRAMS", {})
    rec = _fresh_recorder
    rec.configure(enabled=True)
    sim = _aligned(tmp_path)
    _to_coverage(sim)
    _to_coverage(sim)
    by = {}
    for sp in rec.spans():
        by.setdefault(sp["name"], []).append(sp)
    assert len(by["build_simulator"]) == 1
    assert len(by["init_state"]) == 2 and len(by["run_to_coverage"]) == 2
    inits = {sp["span"] for sp in by["init_state"]}
    runs = {sp["span"] for sp in by["run_to_coverage"]}
    assert {sp["parent"] for sp in by["dispatch"]} == inits | runs
    assert {sp["parent"] for sp in by["fetch"]} == runs
    assert len(by["dispatch"]) == 4 and len(by["fetch"]) == 2
    assert len(by["compile"]) == 2
    assert {sp["parent"] in inits for sp in by["compile"]} == {True, False}
    assert {sp["parent"] for sp in by["compile"]} <= inits | runs
    assert rec.counters()["init_state_compiles_total"] == 1
    assert rec.counters()["coverage_loop_compiles_total"] == 1


def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out)
    return out


def test_coverage_loop_names_passes_and_scopes(tmp_path):
    """The lowered coverage loop names its kernels after their passes
    and carries the round's phase scopes (the census among them)."""
    from p2p_gossipprotocol_tpu.state import build_coverage_loop

    sim = _aligned(tmp_path)
    looped = build_coverage_loop(sim.step, target=0.99, max_rounds=32,
                                 check_every=1, sched_end=0)
    args = (sim.init_state(), sim.topo)
    names = _pallas_names(jax.make_jaxpr(looped)(*args).jaxpr, [])
    assert sorted(names) == ["liveness", "pull", "push"]
    text = jax.jit(looped).lower(*args).as_text(debug_info=True)
    for scope in ("churn", "liveness", "prep", "push", "pull", "update",
                  "census"):
        assert f"/{scope}/" in text, scope


@pytest.mark.parametrize("faulted", [False, True])
def test_fault_plane_runs_under_its_scope(tmp_path, faulted):
    """A faulted round's crash/recover schedule, defer draw and deferred
    re-entry carry the ``faults`` scope inside their phases; an
    unfaulted round has none, and its kernels keep their names."""
    from p2p_gossipprotocol_tpu.state import build_coverage_loop

    plan = ("fault_link_drop=0.2\nfault_delay=0.1\nfault_crash=2:0.3\n"
            "fault_recover=4:0.5\n") if faulted else ""
    sim = _aligned(tmp_path, plan)
    looped = build_coverage_loop(sim.step, target=0.99, max_rounds=32,
                                 check_every=1, sched_end=0)
    args = (sim.init_state(), sim.topo)
    names = _pallas_names(jax.make_jaxpr(looped)(*args).jaxpr, [])
    assert sorted(names) == ["liveness", "pull", "push"]
    text = jax.jit(looped).lower(*args).as_text(debug_info=True)
    for phase in ("churn", "prep", "update"):
        assert (f"/{phase}/faults/" in text) == faulted, phase


def test_names_and_spans_change_no_result(_fresh_recorder, tmp_path,
                                          monkeypatch):
    """Scopes, kernel names and spans are metadata: a traced run with
    the recorder on, and a run with every scope taken out, end in the
    same state, bit for bit."""
    import contextlib

    sim = _aligned(tmp_path)
    ref = _to_coverage(sim)
    _fresh_recorder.configure(enabled=True)
    # compiled before the capture: a CPU compile's own trace events
    # fill the profiler's buffer
    with jax.profiler.trace(str(tmp_path / "trace")):
        traced = _to_coverage(sim)
    _fresh_recorder.configure(enabled=False)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _to_coverage(_aligned(tmp_path))
    for other in (traced, bare):
        assert other[1] == ref[1]
        for a, b in zip(jax.tree.leaves(other[0]), jax.tree.leaves(ref[0])):
            assert np.array_equal(a, b)

    from p2p_gossipprotocol_tpu.telemetry import traceview

    spans = {r["span"] for r in traceview.program_spans(
        traceview.find_trace(str(tmp_path / "trace")))}
    assert {"init_state", "run_to_coverage", "dispatch", "fetch"} <= spans


# ----------------------------------------------------------------------
# The shared O_APPEND line discipline (NodeLogger + fleet results).


def test_nodelogger_single_open_and_no_torn_lines(tmp_path,
                                                  monkeypatch):
    from p2p_gossipprotocol_tpu.utils.logging import NodeLogger

    opens = {"n": 0}
    real_open = os.open

    def counting_open(*a, **kw):
        opens["n"] += 1
        return real_open(*a, **kw)

    monkeypatch.setattr(os, "open", counting_open)
    log = NodeLogger("peer", 9999, directory=str(tmp_path), jsonl=True)
    threads = [threading.Thread(
        target=lambda i=i: [log.log(f"m{i}-{j}", i=i, j=j)
                            for j in range(50)])
        for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    log.close()
    # ONE open per destination for 200 log() calls — the re-open-per-
    # line pattern is gone
    assert opens["n"] == 2
    lines = (tmp_path / "peer_9999_output.txt").read_text() \
        .strip().split("\n")
    assert len(lines) == 200
    assert all(": m" in ln for ln in lines)     # no interleaved halves
    events = log.read_events()
    assert len(events) == 200
    assert {(e["i"], e["j"]) for e in events} \
        == {(i, j) for i in range(4) for j in range(50)}


def test_shared_reader_skips_torn_lines(tmp_path):
    from p2p_gossipprotocol_tpu.utils.logging import (append_jsonl,
                                                      read_jsonl)

    path = tmp_path / "rows.jsonl"
    append_jsonl(path, [{"a": 1}, {"a": 2}])
    with open(path, "ab") as fp:
        fp.write(b'{"a": 3, "torn')       # crash mid-write
    assert [r["a"] for r in read_jsonl(path)] == [1, 2]
    # fleet.driver delegates to the same pair
    from p2p_gossipprotocol_tpu.fleet.driver import read_rows
    assert [r["a"] for r in read_rows(str(path))] == [1, 2]


# ----------------------------------------------------------------------
# Serve scrape + capture surfaces.


def test_serve_metrics_scrape_and_flight(tmp_path):
    from p2p_gossipprotocol_tpu.serve.server import (ServeClient,
                                                     ServeServer)
    from p2p_gossipprotocol_tpu.serve.service import GossipService

    rec = telemetry.recorder()
    rec.configure(enabled=True)
    cfg = NetworkConfig(_write_cfg(tmp_path))
    svc = GossipService(cfg, slots=4, queue_max=8, target=0.99,
                        rounds=16)
    srv = ServeServer(svc, "127.0.0.1", 0).start()
    try:
        client = ServeClient("127.0.0.1", srv.port, timeout=120)
        rid = client.submit({"prng_seed": 1})
        client.result(rid, timeout=300)
        text = client.metrics()
        for name in ("gossip_up 1", "gossip_serve_rounds_total",
                     "gossip_serve_requests_total",
                     "gossip_serve_admitted_total",
                     'gossip_spans_total{name="request"}'):
            assert name in text, text
        snap = client.flight()
        assert snap["counters"]["serve_requests_total"] >= 1
        assert any(s["name"] == "request" for s in snap["spans"])
        client.close()
    finally:
        srv.stop()
        svc.drain()
        rec.configure(enabled=False)


def test_serve_profile_capture_roundtrip(tmp_path):
    """The on-demand profile document: bounded capture of a LIVE
    service, summarized through the same accounting trace_top uses."""
    from p2p_gossipprotocol_tpu.serve.server import (ServeClient,
                                                     ServeServer)
    from p2p_gossipprotocol_tpu.serve.service import GossipService

    cfg = NetworkConfig(_write_cfg(tmp_path))
    svc = GossipService(cfg, slots=4, queue_max=16, target=0.99,
                        rounds=32)
    srv = ServeServer(svc, "127.0.0.1", 0).start()
    try:
        client = ServeClient("127.0.0.1", srv.port, timeout=120)
        rids = [client.submit({"prng_seed": s}) for s in range(3)]
        resp = client.profile(duration_s=0.5, top_n=10)
        assert resp["type"] == "profile"
        assert os.path.exists(resp["trace"])
        assert isinstance(resp["ops"], list)
        for op in resp["ops"]:
            assert {"op", "calls", "total_ms", "share"} <= set(op)
        for rid in rids:
            client.result(rid, timeout=300)
        client.close()
    finally:
        srv.stop()
        svc.drain()


def test_serve_salvage_leaves_flight_dump(tmp_path):
    from p2p_gossipprotocol_tpu.serve import GossipService

    rec = telemetry.recorder()
    rec.configure(enabled=True)
    ckpt = tmp_path / "ck"
    cfg = NetworkConfig(_write_cfg(tmp_path))
    svc = GossipService(cfg, slots=4, queue_max=8, target=0.99,
                        rounds=64, checkpoint_dir=str(ckpt)).start()
    svc.submit({"prng_seed": 0})
    svc.submit({"prng_seed": 1})
    time.sleep(0.2)
    svc.salvage(timeout=120)
    rec.configure(enabled=False)
    assert (ckpt / "serve_manifest.json").exists()
    dumps = [f for f in os.listdir(ckpt) if f.startswith("flight_")]
    assert dumps, os.listdir(ckpt)
    with open(ckpt / dumps[0]) as fp:
        snap = json.load(fp)
    assert snap["reason"] == "serve_salvage"
    assert snap["event_kinds"].get("salvage", 0) >= 1


@pytest.mark.slow
def test_cli_serve_sigterm_flight_dump_e2e(tmp_path):
    """Acceptance: a SIGTERM'd --serve run exits 75 AND leaves a
    readable flight-recorder dump alongside its salvage."""
    import signal
    import socket as socket_lib
    import subprocess
    import sys

    with socket_lib.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    ckpt = tmp_path / "ck"
    cfg_path = _write_cfg(
        tmp_path, f"telemetry=1\nlocal_port={port}\n", name="serve.txt")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "p2p_gossipprotocol_tpu.cli", cfg_path,
         "--serve", "--checkpoint-dir", str(ckpt), "--quiet"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    try:
        from p2p_gossipprotocol_tpu.serve.server import ServeClient
        deadline = time.time() + 60
        client = None
        while time.time() < deadline:
            try:
                client = ServeClient("127.0.0.1", port, timeout=30)
                break
            except OSError:
                time.sleep(0.25)
        assert client is not None, proc.stderr
        client.submit({"prng_seed": 0})
        client.close()
        time.sleep(0.5)
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    assert rc == 75, (rc, proc.stderr.read()[-2000:])
    dumps = [f for f in os.listdir(ckpt) if f.startswith("flight_")]
    assert dumps, os.listdir(ckpt)
    with open(ckpt / dumps[0]) as fp:
        snap = json.load(fp)
    assert snap["reason"] == "serve_salvage"
