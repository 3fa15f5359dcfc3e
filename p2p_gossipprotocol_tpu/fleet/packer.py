"""Scenario packer: bucket solo simulators by compiled-program identity.

A bucket is a set of scenarios the batched engine can serve with ONE
static-shape compilation — i.e. scenarios whose round programs are the
same trace and whose array leaves stack.  The signature below is the
exhaustive list of everything :func:`aligned.aligned_round` reads as a
Python-level static: topology shape (rows/row block/slots/overlay
family), message width, mode/fanout, liveness cadence and strike cap,
churn schedule, stagger, the kernel-path knobs (fuse_update /
pull_window / its windowed slot count), the whole fault plan (a frozen,
hashable dataclass — its values bake into the trace, and its draws are
keyed on ``(plan-seed, round, global id)``, so every scenario sharing a
plan replays the solo fault schedule bitwise), and the interpret flag.

Everything NOT in the signature is a per-scenario ARRAY the engine
batches: the topology tables (each scenario keeps the exact overlay its
solo run would build — including ``valid_w``/``deg``, so peer counts
may differ within a bucket as long as they land on the same padded row
grid), the whole simulation state (seed/PRNG chain, byzantine draw,
alive mask), and the liveness hash seed.

Power-of-two peer counts land on shared row grids (n/128 rows), which
is why the spec layer pads peer counts up to powers of two by default —
heterogeneous sweeps then collapse into few buckets instead of
singletons.

The schedule statics below (``_prefetch``/``_overlap``/
``_frontier_skip``) are read POST-resolution, so two scenarios share a
compiled program only when their resolved schedules really are
identical.

The serving plane's SLOT COUNT is deliberately absent: a bucket's
width is the leading batch axis the engine vmaps over, not a static of
the per-scenario round program, which is what lets the round-17
autoscaler grow/shrink a resident bucket (migrating occupants through
the admit scatter) without ever changing where a request routes — the
signature, and therefore the affinity key the fleet router sticks to,
is width-invariant.
"""

from __future__ import annotations


def bucket_signature(sim) -> tuple:
    """Hashable identity of the compiled round program for ``sim``
    (an :class:`aligned.AlignedSimulator`).  Two sims with equal
    signatures batch into one bucket; the parity suite asserts the
    batched trajectories stay bitwise-identical to solo runs.

    Non-aligned engines that can batch (realgraph) publish their own
    identity through a ``_bucket_signature`` hook — their first element
    is the engine name, so cross-engine collisions are impossible and
    the tuple below stays the aligned family's exhaustive list."""
    fn = getattr(sim, "_bucket_signature", None)
    if fn is not None:
        return fn()
    t = sim.topo
    return (
        # --- array shapes (stacking) ---
        t.rows, t.rowblk, t.n_slots, sim.n_words,
        None if t.ytab is None else tuple(t.ytab.shape),
        # --- round-program statics ---
        sim.n_msgs, sim._n_honest, sim.mode, sim.fanout,
        sim.max_strikes, sim.liveness_every, sim.message_stagger,
        sim.fuse_update, sim.pull_window, sim._pull_slots,
        # the RESOLVED frontier block-skip flag, not the raw mode: it
        # alone decides whether the skip tables enter the trace (the
        # delta exchange never runs on the fleet's single device); the
        # resolved exchange algorithm rides next to it for the same
        # one-program-per-bucket discipline (round 16 — like _overlap,
        # it never engages on one device but keys the program family)
        sim._frontier_skip, sim._frontier_algo,
        # resolved round-10 schedule statics: the prefetch stream
        # changes the compiled kernel (scratch ring + manual DMA); the
        # overlap split never engages on the fleet's single device but
        # stays in the signature for the same one-program-per-bucket
        # discipline
        sim._prefetch, sim._overlap,
        # resolved round-11 hierarchy statics: like the overlap split,
        # the two-tier exchange never engages on the fleet's single
        # device, but the resolved factorization rides the signature
        # so a sweep mixing hier and flat scenario lines keeps the
        # one-program-per-bucket discipline
        sim.hier_hosts, sim.hier_devs, sim._hier,
        sim._liveness,
        (sim.churn.rate, sim.churn.revive, sim.churn.kill_round),
        sim.faults,            # frozen dataclass or None — hashable
        sim.interpret,
    )


def pack(sims: list, max_batch: int = 256) -> list[list[int]]:
    """Group scenario indices into buckets of signature-identical sims.

    Deterministic: buckets are ordered by first appearance and scenarios
    keep their input order inside a bucket, so a resumed sweep re-packs
    identically.  Groups larger than ``max_batch`` split into successive
    full buckets plus a remainder (the bucket-overflow path)."""
    if max_batch < 1:
        raise ValueError("max_batch must be >= 1")
    groups: dict[tuple, list[int]] = {}
    order: list[tuple] = []
    for i, sim in enumerate(sims):
        key = bucket_signature(sim)
        if key not in groups:
            groups[key] = []
            order.append(key)
        groups[key].append(i)
    buckets: list[list[int]] = []
    for key in order:
        idx = groups[key]
        for start in range(0, len(idx), max_batch):
            buckets.append(idx[start:start + max_batch])
    return buckets
