"""Ahead-of-time compiles of the main path for a described TPU v5e.

Interpret mode runs every kernel on the CPU, so it cannot see what
Mosaic refuses: a slice not aligned to the tiling, more VMEM than a
kernel may use, a program that does not fit the device.  These tests
compile the kernels and the round programs at the widths
``examples/scale.txt`` runs (1M peers, degree 16) against a described
``v5e:2x2`` topology, with ``interpret=False``.  Nothing runs: a pass
here says the chip's compiler accepts the program, not that its results
or times are right (``chip_smoke.py`` checks those on the chip).

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every xdist worker
imports this file.
"""

import dataclasses
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from p2p_gossipprotocol_tpu.ops.aligned_kernel import (count_pass,
                                                       gossip_pass,
                                                       liveness_pass)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_PEERS = 1 << 20
ROWS = N_PEERS // 128
SLOTS = 16
#: every -1-auto static left to resolve against interpret=False — the
#: branch a chip build takes (a CPU build resolves them for interpret)
COMPILED = dict(interpret=False, frontier_mode=-1, frontier_algo=-1,
                prefetch_depth=-1, overlap_mode=-1, hier_mode=-1)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _overlay(sh, rowblk, words=1):
    T = ROWS // rowblk
    return dict(
        y=_sds((words, ROWS, 128), jnp.int32, sh),
        colidx=_sds((SLOTS, ROWS, 128), jnp.int8, sh),
        gate=_sds((ROWS, 128), jnp.int8, sh),
        rolls=_sds((SLOTS,), jnp.int32, sh),
        subrolls=_sds((SLOTS,), jnp.int32, sh),
        plane=_sds((ROWS, 128), jnp.int32, sh),
        table=_sds((SLOTS, T), jnp.int32, sh), T=T)


def _fault_gate(sh, T):
    """The fault plane's scalar prefetch (faults.kernel_meta, and the
    global row id of each row block)."""
    return _sds((5,), jnp.int32, sh), _sds((T,), jnp.int32, sh)


@pytest.mark.parametrize("prefetch,faulty", [(0, False), (2, False),
                                             (2, True)],
                         ids=["0", "2", "2-faulty"])
def test_gossip_pass_w1_compiles(one_chip, prefetch, faulty):
    """The W=1 push pass of the chip's default round (row-perm overlay,
    frontier block-skip tables), with and without the manual DMA
    prefetch ring, and with the fault plane's link gate."""
    o = _overlay(one_chip, 2048)
    gate_args = _fault_gate(one_chip, o["T"]) if faulty else ()

    def push(y, colidx, gate, rolls, subrolls, yidx, yact, *fault):
        meta, gbase = fault or (None, None)
        return gossip_pass(y, colidx, gate, rolls, subrolls, yidx=yidx,
                           yact=yact, fault_meta=meta, gbase=gbase,
                           prefetch_depth=prefetch, rowblk=2048,
                           interpret=False)

    _compile(push, o["y"], o["colidx"], o["gate"], o["rolls"],
             o["subrolls"], o["table"], o["table"], *gate_args)


def test_gossip_pass_w8_fused_compiles(one_chip):
    """The W=8 push pass on the fused block-perm overlay (ytab index
    maps, src_ok mask, skip tables) with the prefetch ring — what 256
    messages select."""
    o = _overlay(one_chip, 512, words=8)

    def push(y, colidx, gate, rolls, subrolls, ytab, src_ok, yidx, yact):
        return gossip_pass(y, colidx, gate, rolls, subrolls, ytab=ytab,
                           src_ok=src_ok, yidx=yidx, yact=yact,
                           prefetch_depth=2, rowblk=512, interpret=False)

    _compile(push, o["y"], o["colidx"], o["gate"], o["rolls"],
             o["subrolls"], o["table"], o["plane"], o["table"],
             o["table"])


@pytest.mark.parametrize("pass_", ["push", "pull"])
def test_gossip_pass_w8_fused_faulted_compiles(one_chip, pass_):
    """The W=8 fused passes with the fault plane's in-register link gate
    (fault_meta + gbase after the skip tables), as a faulted 256-message
    deployment runs them: the push pass with the skip tables, the pull
    pass over the first roll group's 4 slots."""
    o = _overlay(one_chip, 512, words=8)
    meta, gbase = _fault_gate(one_chip, o["T"])
    if pass_ == "push":
        def run(y, colidx, gate, rolls, subrolls, ytab, src_ok, meta,
                gbase, yidx, yact):
            return gossip_pass(y, colidx, gate, rolls, subrolls, ytab=ytab,
                               src_ok=src_ok, fault_meta=meta, gbase=gbase,
                               yidx=yidx, yact=yact, prefetch_depth=2,
                               rowblk=512, interpret=False, name="push")

        _compile(run, o["y"], o["colidx"], o["gate"], o["rolls"],
                 o["subrolls"], o["table"], o["plane"], meta, gbase,
                 o["table"], o["table"])
        return
    Dw = 4

    def run(y, colidx, delta, rolls, subrolls, ytab, src_ok, meta, gbase):
        return gossip_pass(y, colidx[:Dw], delta, rolls[:Dw], subrolls[:Dw],
                           pull=True, ytab=ytab[:Dw], src_ok=src_ok,
                           fault_meta=meta, gbase=gbase, prefetch_depth=2,
                           rowblk=512, interpret=False, name="pull")

    _compile(run, o["y"], o["colidx"], o["gate"], o["rolls"], o["subrolls"],
             o["table"], o["plane"], meta, gbase)


def test_count_pass_compiles(one_chip):
    o = _overlay(one_chip, 2048)

    def count(y, colidx, gate, rolls, subrolls):
        return count_pass(y, colidx, gate, rolls, subrolls, rowblk=2048,
                          interpret=False)

    _compile(count, o["plane"], o["colidx"], o["gate"], o["rolls"],
             o["subrolls"])


def test_liveness_pass_compiles(one_chip):
    o = _overlay(one_chip, 2048)

    def sweep(y_alive, colidx, strikes, gate, rolls, subrolls, gbase,
              round_idx, seed):
        return liveness_pass(y_alive, colidx, strikes, gate, rolls,
                             subrolls, gbase=gbase, round_idx=round_idx,
                             hash_seed=seed, rowblk=2048, interpret=False)

    scalar = _sds((), jnp.int32, one_chip)
    _compile(sweep, o["plane"], o["colidx"], o["colidx"], o["gate"],
             o["rolls"], o["subrolls"], _sds((o["T"],), jnp.int32,
                                             one_chip), scalar, scalar)


def test_liveness_pass_blk512_fused_compiles(one_chip):
    """The liveness sweep at the 512-row blocks a 256-message deployment
    runs it at, on the fused block-perm overlay (ytab index map)."""
    o = _overlay(one_chip, 512, words=8)

    def sweep(y_alive, colidx, strikes, gate, rolls, subrolls, ytab, gbase,
              round_idx, seed):
        return liveness_pass(y_alive, colidx, strikes, gate, rolls,
                             subrolls, gbase=gbase, round_idx=round_idx,
                             hash_seed=seed, ytab=ytab, rowblk=512,
                             interpret=False)

    scalar = _sds((), jnp.int32, one_chip)
    _compile(sweep, o["plane"], o["colidx"], o["colidx"], o["gate"],
             o["rolls"], o["subrolls"], o["table"],
             _sds((o["T"],), jnp.int32, one_chip), scalar, scalar)


def _scale_sim(n_shards=1, **over):
    from p2p_gossipprotocol_tpu.aligned import AlignedSimulator
    from p2p_gossipprotocol_tpu.config import NetworkConfig

    cfg = NetworkConfig(os.path.join(REPO, "examples", "scale.txt"))
    for k, v in over.items():
        setattr(cfg, k, v)
    sim = AlignedSimulator.from_config(cfg, n_shards=n_shards)
    return dataclasses.replace(sim, **COMPILED)


def _shapes(tree, sharding_of):
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                          sharding=s),
        tree, sharding_of)


def test_solo_step_compiles(one_chip):
    """One whole round of the scale deployment (1M x 32) on one chip,
    every auto static on its compiled branch."""
    sim = _scale_sim()
    assert sim._prefetch == 2 and sim._frontier_skip
    state = jax.eval_shape(sim.init_state)
    args = [jax.tree.map(lambda x: _sds(np.shape(x), x.dtype, one_chip),
                         t) for t in (state, sim.topo)]
    compiled = _compile(sim.step, *args)
    # the 16 GB chip holds the round's operands many times over
    assert compiled.memory_analysis().argument_size_in_bytes < 1 << 30


_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_CALLS = re.compile(r"(?:calls|body|condition|to_apply|true_computation"
                    r"|false_computation)=%?([\w.\-]+)"
                    r"|branch_computations=\{([^}]*)\}")


def _loop_instructions(text):
    """The instructions of an optimized HLO module's while loops: every
    computation a loop body reaches (fusions, conditional branches,
    nested loops), one stripped line each."""
    comps, lines = {}, None
    for line in text.splitlines():
        m = _HEADER.match(line)
        if m:
            lines = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            lines = None
        elif lines is not None:
            lines.append(line.strip())
    todo = [m.group(1) for body in comps.values() for line in body
            for m in [re.search(r" while\(.*body=%?([\w.\-]+)", line)] if m]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            for m in _CALLS.finditer(line):
                todo += ([m.group(1)] if m.group(1) else
                         [c.strip().lstrip("%") for c in m.group(2).split(",")])
    return [line for name in sorted(seen) for line in comps[name]]


@pytest.mark.parametrize("traffic", [
    dict(n_messages=32, message_stagger=1),
    dict(n_messages=256, message_stagger=0)], ids=["stagger32", "burst256"])
def test_coverage_loop_keeps_lane_table_in_place(one_chip, traffic):
    """The scale deployment's coverage loop, as ``run_to_coverage``
    compiles it (liveness sweeps, ``pull_window=1``; W=1 row-perm and W=8
    block-perm overlays), copies neither the lane table nor the strike
    counters (s8[D, R, 128]) inside its while loop, and materialises no
    slice of the windowed pull's first Dw slots (s8[Dw, R, 128]): the
    liveness pass updates both tables in place and the pull reads its
    slots through its index maps."""
    from p2p_gossipprotocol_tpu.state import (build_coverage_loop,
                                              stagger_sched_end)

    sim = _scale_sim(**traffic)
    assert sim._liveness and sim.pull_window
    D, R, Dw = sim.topo.n_slots, sim.topo.rows, sim._pull_slots
    assert Dw < D
    sh = lambda t: jax.tree.map(                            # noqa: E731
        lambda x: _sds(np.shape(x), x.dtype, one_chip), t)
    loop = build_coverage_loop(
        sim.step, target=0.99, max_rounds=128, check_every=1,
        sched_end=stagger_sched_end(sim._n_honest, sim.message_stagger))
    body = _loop_instructions(_compile(
        loop, sh(jax.eval_shape(sim.init_state)), sh(sim.topo)).as_text())
    assert any(re.match(r"%liveness[.\d]* = ", line) for line in body)
    waste = [line[:120] for line in body
             if re.search(rf"= s8\[{D},{R},128\]\{{[^}}]*\}} copy\(", line)
             or re.search(rf"= s8\[{Dw},{R},128\]\{{[^}}]*\}} slice\(", line)]
    assert not waste, waste


def test_sharded_step_compiles(topo):
    """One round of the sharded engine over the four described chips:
    the frontier exchange with the halving merge, the collectives the
    compiler inserts, and the kernels on each shard."""
    from p2p_gossipprotocol_tpu.aligned import FrontierCarry
    from p2p_gossipprotocol_tpu.parallel import AlignedShardedSimulator
    from p2p_gossipprotocol_tpu.parallel.mesh import PEER_AXIS

    inner = _scale_sim(n_shards=4)
    mesh = Mesh(np.asarray(topo.devices), (PEER_AXIS,))
    keep = {f.name for f in dataclasses.fields(AlignedShardedSimulator)}
    sim = AlignedShardedSimulator(
        mesh=mesh, **{k: getattr(inner, k) for k in keep
                      if k not in ("mesh", "frontier_threshold")
                      and hasattr(inner, k)})
    assert sim._frontier and sim._inner._frontier_algo
    st_spec, tp_spec, metric_spec = sim._specs()
    fr_spec = sim._fr_spec()
    ns = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    state = jax.eval_shape(sim._inner.init_state)
    st = _shapes(state, jax.tree.map(ns, st_spec))
    tp = _shapes(sim.topo, jax.tree.map(ns, tp_spec))
    fr = FrontierCarry(
        replica_w=_sds(state.seen_w.shape, jnp.int32, ns(P())),
        byz_g=(_sds(state.byz_w.shape, jnp.int32, ns(P()))
               if fr_spec.byz_g is not None else None),
        regime=_sds((), jnp.int32, ns(P())), regime_ici=None)

    def one_round(s, t, f):
        s, t, metrics, f = sim._step_local(s, t, f)
        return (s, t, f), metrics

    fn = jax.shard_map(one_round, mesh=mesh,
                       in_specs=(st_spec, tp_spec, fr_spec),
                       out_specs=((st_spec, tp_spec, fr_spec),
                                  metric_spec),
                       check_vma=False)
    text = _compile(fn, st, tp, fr).as_text()
    assert "collective-permute" in text and "all-gather" in text
