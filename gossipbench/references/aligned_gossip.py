"""Plain reference of one gossip scenario on the aligned overlay family.

The same semantics as the program's aligned engine, written from its
documented rules with numpy and ``jax.numpy`` only, and importing
nothing of the program:

* the overlay: a row permutation, per-slot block and sublane rolls and
  per-peer lane choices, drawn with numpy's PCG64 from the deployment's
  seed in the order the program documents (``build_aligned``), and the
  reference's power-law in-degree ``min(slots, n * u^(1/alpha))``
  (peer.cpp:219-222);
* a round: churn (each live peer dies with probability ``churn_rate``),
  a liveness sweep every ``round(ping / message)`` rounds (a slot whose
  neighbour is dead gains a strike; at ``max_missed_pings`` it is
  rewired to a hashed candidate lane of the same source row if that
  candidate is alive), staggered rumor generation (rumor ``m`` enters
  at its source in round ``m * stagger``), a flood push of every live
  peer's frontier over its in-slots, and one anti-entropy pull from a
  random slot of the first roll group;
* the census: coverage over live peers and generated rumors, the
  float32 stopping rule of ``run_to_coverage`` and the exact coverage
  of the final state.

Peers are flat ids ``row * 128 + lane``; slot ``d`` of peer ``(r, c)``
listens to ``perm[((r // blk + roll_d) % T) * blk + (r % blk + sub_d)
% blk] * 128 + lane_d[r, c]``: a gather of the source row, then of the
lane (:func:`lane_gather`).  Every random draw goes through
``jax.random`` with the program's key schedule, so the reference and
the program read the same coins.
"""

from __future__ import annotations

import dataclasses

import numpy as np

LANES = 128
WORD_BITS = 32
# the kernel's VMEM budget in int32 words x rows and the largest row
# block the config path asks for: they size the row blocks, and the row
# block is part of the neighbour map
WORDS_X_ROWBLK = 4096
ROWBLK_CAP = 2048
# from W = 4 message words on, the auto rule draws the block-granular
# overlay (a different overlay family with the same marginals)
BLOCK_PERM_MIN_WORDS = 4

#: keys whose non-default values this reference does not model
_UNMODELLED = {"fanout": "0", "byzantine_fraction": "0", "fault_link_drop": "0",
               "fault_delay": "0", "fault_byzantine": "0",
               "fault_partition": "", "fault_crash": "", "fault_recover": "",
               "msg_shards": "0", "hier_hosts": "0", "hier_devs": "0"}


def _mix32(h):
    import jax
    import jax.numpy as jnp

    h = h * jnp.int32(-2048144789)
    h = h ^ jax.lax.shift_right_logical(h, 13)
    h = h * jnp.int32(-1028477387)
    return h ^ jax.lax.shift_right_logical(h, 16)


def rewire_lane(flat_id, d, round_idx, seed):
    """Candidate lane in [0, 128) for slot ``d`` of peer ``flat_id``."""
    import jax.numpy as jnp

    h = flat_id ^ (round_idx * jnp.int32(-1640531527))
    h = h ^ (d * jnp.int32(0x3243F6A9))
    h = h ^ (seed * jnp.int32(0x27220A95))
    return _mix32(h) & jnp.int32(LANES - 1)


@dataclasses.dataclass(frozen=True)
class Deployment:
    """What the configuration states, resolved as the config path
    resolves it."""

    n_peers: int
    n_slots: int
    alpha: float
    churn_rate: float
    max_strikes: int
    liveness_every: int
    n_msgs: int
    stagger: int
    roll_groups: int | None
    pull_window: bool
    block_perm: bool
    rowblk: int
    seed: int
    target: float
    max_rounds: int
    shards: int = 1           # mesh_devices: the row layout's shard count

    @property
    def n_words(self) -> int:
        return -(-self.n_msgs // WORD_BITS)

    @property
    def sched_end(self) -> int:
        return (self.n_msgs - 1) * self.stagger + 1 if self.stagger > 0 else 0


def layout(n_peers: int, rowblk: int, shards: int = 1) -> tuple[int, int]:
    """(rows, row block) of the overlay for ``shards`` equal row shards:
    rows rounded to a multiple of 8 (or 4, 2, 1) rows a shard while the
    padding stays under 1/16, and the row block the largest divisor of
    a shard's rows up to ``rowblk``, a multiple of 8 where one divides.
    The row block shapes the neighbour map, so a layout whose shards
    hold fewer rows than one chip's row block draws another overlay."""
    rows0 = max(1, -(-n_peers // LANES))
    for align in (8, 4, 2, 1):
        rows = -(-rows0 // (align * shards)) * align * shards
        if rows - rows0 <= max(rows0 // 16, 0) or align == 1:
            break
    local = rows // shards
    cap = min(rowblk, local)
    blk = next((b for b in range(cap - cap % 8, 0, -8) if local % b == 0),
               0) or next(b for b in range(cap, 0, -1) if local % b == 0)
    return rows, blk


def deployment(kv: dict) -> Deployment:
    """Resolve the key=value pairs of a configuration and its traffic.
    ``mesh_devices`` is a layout key: the sharded engine runs the
    one-chip round on the same overlay, so it is modelled where the row
    count it rounds to is one chip's."""
    for k, default in _UNMODELLED.items():
        if kv.get(k, default) not in (default, "0", "0.0"):
            raise ValueError(f"the reference does not model {k}={kv[k]}")
    if kv.get("engine") != "aligned" or kv.get("graph") != "powerlaw":
        raise ValueError("the reference models engine=aligned, graph=powerlaw")
    if kv["mode"] != "pushpull":
        raise ValueError(f"the reference models mode=pushpull, not {kv['mode']}")
    n_slots = int(kv["avg_degree"])
    n_msgs = int(kv["n_messages"])
    words = -(-n_msgs // WORD_BITS)
    groups = int(kv.get("roll_groups", "4")) or None
    bp = int(kv.get("block_perm", "-1"))
    block_perm = ((words >= BLOCK_PERM_MIN_WORDS and n_slots >= 2
                   and (groups is None or groups >= 2)) if bp < 0
                  else bool(bp))
    if block_perm and groups is not None and groups <= 1 and n_slots > 1:
        block_perm = False
    pull_window = (bool(int(kv.get("pull_window", "1")))
                   and 1 <= (groups or 0) <= n_slots // 2)
    budget = WORDS_X_ROWBLK // (2 if int(kv.get("fuse_update", "0")) else 1)
    rowblk = min(ROWBLK_CAP, max(8, budget // words // 8 * 8))
    ping, msg = float(kv["ping_interval"]), float(kv["message_interval"])
    n_peers = int(kv["n_peers"])
    shards = max(int(kv.get("mesh_devices", "0")), 1)
    if layout(n_peers, rowblk, shards)[0] != layout(n_peers, rowblk)[0]:
        raise ValueError(
            f"mesh_devices={shards} pads {n_peers} peers to another row "
            "count than one chip does: the reference models the one-chip "
            "row count only")
    return Deployment(
        n_peers=n_peers, n_slots=n_slots,
        alpha=float(kv["powerlaw_alpha"]),
        churn_rate=float(kv["churn_rate"]),
        max_strikes=int(kv["max_missed_pings"]),
        liveness_every=max(1, round(ping / (msg if msg > 0 else ping))),
        n_msgs=n_msgs, stagger=int(kv.get("message_stagger", "0")),
        roll_groups=groups, pull_window=pull_window, block_perm=block_perm,
        rowblk=rowblk, seed=int(kv["prng_seed"]),
        target=float(kv["coverage_target"]),
        max_rounds=int(kv["max_rounds"]), shards=shards)


@dataclasses.dataclass
class Overlay:
    perm: np.ndarray       # int32[R]
    rolls: np.ndarray      # int32[D]
    subrolls: np.ndarray   # int32[D]
    lanes: np.ndarray      # int8[D, R, 128]
    deg: np.ndarray        # int8[R, 128]
    valid: np.ndarray      # bool[R, 128]
    blk: int

    @property
    def rows(self) -> int:
        return self.perm.shape[0]

    def sender_rows(self) -> np.ndarray:
        """int32[D, R]: the source row slot d of each row listens to."""
        R, blk = self.rows, self.blk
        T = R // blk
        r = np.arange(R)
        yr = (((r[None] // blk + self.rolls[:, None]) % T) * blk
              + (r[None] % blk + self.subrolls[:, None]) % blk)
        return self.perm[yr].astype(np.int32)

    def pull_slots(self) -> int:
        changes = np.nonzero(np.diff(self.rolls))[0]
        return int(changes[0]) + 1 if changes.size else len(self.rolls)


def sample_overlay(dep: Deployment) -> Overlay:
    n, D = dep.n_peers, dep.n_slots
    rowblk = min(dep.rowblk, max(8, (WORDS_X_ROWBLK // dep.n_words) // 8 * 8))
    rng = np.random.default_rng(dep.seed)
    rows, blk = layout(n, rowblk, dep.shards)
    T = rows // blk
    if dep.block_perm:
        pblock = rng.permutation(T).astype(np.int32)
        perm = (pblock[np.arange(rows) // blk] * blk
                + np.arange(rows) % blk).astype(np.int32)
    else:
        perm = rng.permutation(rows).astype(np.int32)
    groups = D if dep.roll_groups is None else max(1, min(dep.roll_groups, D))
    if dep.block_perm and T > 1:
        group_rolls = rng.permutation(T).astype(np.int32)[np.arange(groups) % T]
    else:
        group_rolls = rng.integers(0, T, size=groups, dtype=np.int32)
    rolls = group_rolls[(np.arange(D) * groups) // D].astype(np.int32)
    subrolls = rng.integers(0, blk, size=D, dtype=np.int32)
    lanes = rng.integers(0, LANES, size=(D, rows, LANES), dtype=np.int8)
    u = rng.uniform(size=(rows, LANES))
    deg = np.maximum(np.minimum(D, n * u ** (1.0 / dep.alpha)).astype(np.int8), 1)
    valid = np.arange(rows * LANES).reshape(rows, LANES) < n
    deg = np.where(valid, deg, 0).astype(np.int8)
    return Overlay(perm=perm, rolls=rolls, subrolls=subrolls, lanes=lanes,
                   deg=deg, valid=valid, blk=blk)


def sources(valid: np.ndarray, n_msgs: int) -> np.ndarray:
    """Rumor m starts at the (m * stride)-th valid peer, stride = n // msgs."""
    ok = np.flatnonzero(valid.reshape(-1))
    n_ok = max(ok.size, 1)
    stride = max(n_ok // max(n_msgs, 1), 1)
    pos = (np.arange(n_msgs) * stride) % n_ok
    return ok[pos].astype(np.int32) if ok.size else np.zeros(n_msgs, np.int32)


def honest_mask(n_msgs: int, n_words: int) -> np.ndarray:
    """int32[W]: the low ``n_msgs`` bits set across the message words."""
    k = [min(max(n_msgs - WORD_BITS * w, 0), WORD_BITS) for w in range(n_words)]
    return np.array([(1 << b) - 1 for b in k], np.uint32).view(np.int32)


@dataclasses.dataclass
class Outcome:
    """A finished scenario in the reference's flat layout."""

    rounds: int
    seen: np.ndarray       # int32[N, W]
    frontier: np.ndarray   # int32[N, W]
    alive: np.ndarray      # bool[N]
    strikes: np.ndarray    # int8[D, N]
    lanes: np.ndarray      # int8[D, N]
    coverage: float        # exact coverage of the final state


def lane_gather(y, lanes, chunk: int, parts: int = 1):
    """``out[r, k, c] = y[r, k, lanes[r, c]]``: each peer reads word ``k``
    of lane ``lanes[r, c]`` of the row beside it, exactly.  Written as a
    product with a one-hot matrix per row, byte by byte: a bfloat16
    holds 0-255 exactly and each output sums one nonzero term, so the
    product is exact on any backend, and on a TPU it runs on the matrix
    unit instead of as one gather descriptor per element.  The rows go
    ``chunk`` at a time; with ``parts`` > 1, rows split into that many
    equal parts (one a device) and each step takes ``chunk`` rows of
    every part, so no step waits on rows another device holds."""
    import jax
    import jax.numpy as jnp

    R, K, L = y.shape
    u = jax.lax.bitcast_convert_type(y, jnp.uint32)
    b = jnp.stack([(u >> (8 * i)) & 255 for i in range(4)], axis=2)
    iota = jnp.arange(L, dtype=jnp.int32)
    if parts == 1:
        b = b.reshape(R // chunk, chunk, 4 * K, L).astype(jnp.bfloat16)

        def one(args):
            bb, ll = args
            onehot = (ll[:, None, :] == iota[None, :, None]).astype(jnp.bfloat16)
            return jnp.einsum("rkj,rjc->rkc", bb, onehot,
                              preferred_element_type=jnp.float32)

        out = jax.lax.map(one, (b, lanes.reshape(R // chunk, chunk, L)))
    else:
        b = b.reshape(parts, R // parts // chunk, chunk, 4 * K, L)
        b = b.swapaxes(0, 1).astype(jnp.bfloat16)
        ls = lanes.reshape(parts, R // parts // chunk, chunk, L).swapaxes(0, 1)

        def one(args):
            bb, ll = args
            onehot = (ll[..., None, :] == iota[:, None]).astype(jnp.bfloat16)
            return jnp.einsum("prkj,prjc->prkc", bb, onehot,
                              preferred_element_type=jnp.float32)

        out = jax.lax.map(one, (b, ls)).swapaxes(0, 1)
    out = out.reshape(R, K, 4, L).astype(jnp.uint32)
    w = out[:, :, 0] | (out[:, :, 1] << 8) | (out[:, :, 2] << 16) | (out[:, :, 3] << 24)
    return jax.lax.bitcast_convert_type(w, jnp.int32)


def or_words(x, gather=None):
    """int32[W]: the OR of ``x[R, W, 128]`` over its rows and lanes.
    Where rows are split over devices, ``gather`` (a sharding that puts
    the whole on every device) brings the per-row ORs together first:
    XLA's CPU backend has no OR all-reduce."""
    import jax
    import jax.numpy as jnp

    if gather is None:
        return jax.lax.reduce(x, jnp.int32(0), jax.lax.bitwise_or, (0, 2))
    rows = jax.lax.reduce(x, jnp.int32(0), jax.lax.bitwise_or, (2,))
    rows = jax.lax.with_sharding_constraint(rows, gather)
    return jax.lax.reduce(rows, jnp.int32(0), jax.lax.bitwise_or, (0,))


class Scenario:
    """Runs scenarios of one deployment on the given devices (the chips
    once the program's state is freed; the CPU in tests), by default
    JAX's default device.  State is kept as rows of 128 peers: planes
    int32[R, W, 128], masks [R, 128], per-slot tables [D, R, 128].  On
    more than one device every row-indexed array is split by rows over
    a 1-D mesh of them, and the round stays a plain ``jit``: XLA's
    partitioner moves the rows a gather reads from another device."""

    def __init__(self, dep: Deployment, liveness_every: int | None = None,
                 devices=None):
        self.dep = dep
        self.every = liveness_every or dep.liveness_every
        self.ov = sample_overlay(dep)
        self.src = sources(self.ov.valid, dep.n_msgs)
        R = self.ov.rows
        self.parts = len(devices) if devices else 1
        self.mesh = None
        if self.parts > 1:
            from jax.sharding import Mesh

            if R % self.parts:
                raise ValueError(f"{R} rows do not split over {self.parts} devices")
            self.mesh = Mesh(np.asarray(devices), ("rows",))
        self.chunk = next(c for c in (4096, 2048, 1024, 512, 256, 128, 64,
                                      32, 16, 8, 4, 2, 1)
                          if (R // self.parts) % c == 0)
        self._fns = {}

    def _sharding(self, axis: int | None):
        """Where an array goes: split along its row ``axis`` over the
        mesh (None: whole on every device); no mesh, JAX's default."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P() if axis is None else P(*[None] * axis, "rows")
        return NamedSharding(self.mesh, spec)

    def _put(self, x, axis: int | None):
        import jax.numpy as jnp

        return jnp.asarray(x, device=self._sharding(axis))

    def _consts(self):
        ov = self.ov
        return {"srow": self._put(ov.sender_rows(), 1),
                "deg": self._put(ov.deg.astype(np.int32), 0),
                "valid": self._put(ov.valid, 0),
                "src": self._put(self.src, None)}

    def _round(self, live: bool):
        """The round, unjitted; ``run`` calls this under ``jit``."""
        import jax
        import jax.numpy as jnp

        dep, ov = self.dep, self.ov
        R, D, W = ov.rows, dep.n_slots, dep.n_words
        Dw = ov.pull_slots() if dep.pull_window else D
        hmask = jnp.asarray(honest_mask(dep.n_msgs, W))[None, :, None]
        rows = jnp.arange(R, dtype=jnp.int32)
        pid = rows[:, None] * LANES + jnp.arange(LANES, dtype=jnp.int32)[None]
        chunk, parts, whole = self.chunk, self.parts, self._sharding(None)

        def nbr_words(x, srow_d, lanes_d):
            """x[R, K, 128] as seen by every peer through one slot."""
            return lane_gather(jnp.take(x, srow_d, axis=0),
                               lanes_d.astype(jnp.int32), chunk, parts)

        def rnd(st, c, r, m, inject):
            key, k_churn, _, k_pull, _ = jax.random.split(st["key"], 5)
            u = jax.vmap(lambda g: jax.random.uniform(
                jax.random.fold_in(k_churn, g), (2, LANES)))(rows)
            alive = st["alive"] & ~(u[:, 0] < dep.churn_rate) & c["valid"]
            alive_w = jnp.where(alive, jnp.int32(-1), jnp.int32(0))
            lanes, strikes = st["lanes"], st["strikes"]
            if live:
                def slot(d, carry):
                    lanes, strikes = carry
                    y = jnp.take(alive_w[:, None], c["srow"][d], axis=0)
                    lane = lanes[d].astype(jnp.int32)
                    nbr_alive = lane_gather(y, lane, chunk, parts)[:, 0] != 0
                    s = strikes[d].astype(jnp.int32)
                    dead = (d < c["deg"]) & ~nbr_alive
                    s_new = jnp.where(dead, jnp.minimum(s + 1, dep.max_strikes + 1), 0)
                    cand = rewire_lane(pid, d, r, jnp.int32(dep.seed))
                    take = ((s_new >= dep.max_strikes)
                            & (lane_gather(y, cand, chunk, parts)[:, 0] != 0))
                    lanes = lanes.at[d].set(jnp.where(take, cand, lane).astype(jnp.int8))
                    strikes = strikes.at[d].set(jnp.where(take, 0, s_new).astype(jnp.int8))
                    return lanes, strikes

                lanes, strikes = jax.lax.fori_loop(0, D, slot, (lanes, strikes))
            seen0 = st["seen"]
            seen, frontier = seen0, st["frontier"]
            if dep.stagger > 0:
                src = c["src"][jnp.clip(m, 0, dep.n_msgs - 1)]
                row, lane, w = src // LANES, src % LANES, m // WORD_BITS
                bit = jnp.where(inject & alive[row, lane],
                                jnp.left_shift(jnp.int32(1), m % WORD_BITS), 0)
                seen = seen.at[row, w, lane].set(seen[row, w, lane] | bit)
                frontier = frontier.at[row, w, lane].set(frontier[row, w, lane] | bit)
            send = frontier & alive_w[:, None]

            def push(d, acc):
                z = nbr_words(send, c["srow"][d], lanes[d])
                return acc | jnp.where((d < c["deg"])[:, None], z, 0)

            recv = jax.lax.fori_loop(0, D, push, jnp.zeros_like(seen))
            ys = seen0 & alive_w[:, None]
            pu = jax.vmap(lambda g: jax.random.randint(
                jax.random.fold_in(k_pull, g), (LANES,), 0, 1 << 30,
                jnp.int32))(rows)
            degw = jnp.minimum(c["deg"], Dw)
            delta = jnp.where(degw > 0, pu % jnp.maximum(degw, 1), Dw)

            def pull(d, acc):
                z = nbr_words(ys, c["srow"][d], lanes[d])
                return acc | jnp.where((delta == d)[:, None], z, 0)

            recv = jax.lax.fori_loop(0, Dw, pull, recv)
            ok_w = alive_w & jnp.where(c["valid"], -1, 0)
            new = recv & ok_w[:, None] & ~seen
            seen = seen | new
            pc = jax.lax.population_count
            per_row = lambda x: jnp.sum(x.reshape(R, -1), axis=1, dtype=jnp.int32)
            orw = or_words(seen, whole)
            stats = {"hits": per_row(jnp.where((ok_w != 0)[:, None],
                                               pc(seen & hmask), 0)),
                     "n_ok": jnp.sum(ok_w != 0, dtype=jnp.int32),
                     "n_cols": jnp.sum(pc(orw & hmask[0, :, 0]), dtype=jnp.int32)}
            st = {"seen": seen, "frontier": new, "alive": alive,
                  "strikes": strikes, "lanes": lanes, "key": key}
            return st, stats

        return rnd

    def initial(self, key: np.ndarray) -> dict:
        import jax.numpy as jnp

        dep, ov = self.dep, self.ov
        R = ov.rows
        seen = np.zeros((R, dep.n_words, LANES), np.uint32)
        if dep.stagger <= 0:
            for m, s in enumerate(self.src):
                seen[s // LANES, m // WORD_BITS, s % LANES] |= np.uint32(1 << (m % WORD_BITS))
        seen = self._put(seen.view(np.int32), 0)
        return {"seen": seen, "frontier": seen, "alive": self._put(ov.valid, 0),
                "strikes": jnp.zeros((dep.n_slots, R, LANES), jnp.int8,
                                     device=self._sharding(1)),
                "lanes": self._put(ov.lanes, 1),
                "key": self._put(np.asarray(key, np.uint32), None)}

    def run(self, key: np.ndarray) -> Outcome:
        """The scenario from ``key`` to the stopping rule of a coverage
        run: stop once the float32 census reaches the target and every
        scheduled rumor exists, or at the round cap."""
        import jax
        import jax.numpy as jnp

        dep = self.dep
        c = self._consts()
        st = self.initial(key)
        r = 0
        target = np.float32(dep.target)
        while r < dep.max_rounds:
            live = r % self.every == 0
            if live not in self._fns:
                # the round's tables are built under the trace: a peer-id
                # plane made outside it would be baked into the program
                # as a constant (512 MB at 2^27 peers), an executable too
                # large for the persistent compile cache
                self._fns[live] = jax.jit(
                    lambda *a, live=live: self._round(live)(*a))
            m = r // dep.stagger if dep.stagger > 0 else 0
            inject = dep.stagger > 0 and r % dep.stagger == 0 and m < dep.n_msgs
            st, stats = self._fns[live](st, c, jnp.int32(r), jnp.int32(m),
                                        jnp.bool_(inject))
            s = {k: int(np.sum(np.asarray(v, np.int64)))
                 for k, v in jax.device_get(stats).items()}
            n_cols = s["n_cols"] if dep.stagger > 0 else dep.n_msgs
            cov32 = np.float32(s["hits"]) / (np.float32(max(s["n_ok"], 1))
                                             * np.float32(max(n_cols, 1)))
            r += 1
            if not (cov32 < target or r < dep.sched_end):
                break
        host = jax.device_get(st)
        R, D, W = self.ov.rows, dep.n_slots, dep.n_words
        flat = lambda x: np.asarray(x).transpose(0, 2, 1).reshape(R * LANES, W)
        return Outcome(rounds=r, seen=flat(host["seen"]),
                       frontier=flat(host["frontier"]),
                       alive=np.asarray(host["alive"]).reshape(-1),
                       strikes=np.asarray(host["strikes"]).reshape(D, -1),
                       lanes=np.asarray(host["lanes"]).reshape(D, -1),
                       coverage=s["hits"] / (max(s["n_ok"], 1) * max(n_cols, 1)))
